"""TPC-H Q18 (spec §2.4.18), the plain reference: exact integer sums over
the generator's arrays. Parameter (§2.4.18.3): ``quantity``, the whole
number the lines of a large order sum over (312 to 315; validation value
300).

One row for each order whose lines' quantities sum over QUANTITY: the
customer's name and key, the order's key, date and total price, the sum.
``o_totalprice`` descending, then ``o_orderdate`` (then the order key, so
that the reference is a function: a tie on both at the hundredth row
would leave the statement's own answer open, and has not been seen), the
first 100."""

import numpy as np

TABLES = ("customer", "orders", "lineitem")
COLUMNS = {"customer": ("c_custkey", "c_name"),
           "orders": ("o_orderkey", "o_custkey", "o_orderdate",
                      "o_totalprice"),
           "lineitem": ("l_orderkey", "l_quantity")}
_EPOCH = np.datetime64("1970-01-01", "D")


def bind(params: dict) -> dict:
    """What the statement's text takes from one draw of parameters."""
    return {"quantity": int(params["quantity"])}


def answer(tables: dict, params: dict, acc=np.int64) -> dict:
    """Rows as the wire carries them: name, customer key, order key, order
    date as an ISO string, total price and summed quantity (both exact
    hundredths, over 100). ``acc`` is the type the quantities are summed
    in: int64 is the reference; the control (``benchmarks/control.py``)
    passes a narrower one."""
    cu, od, li = tables["customer"], tables["orders"], tables["lineitem"]
    # order key -> the hundredths its lines sum to (keys need not be dense)
    summed = np.zeros(int(max(od["o_orderkey"].max(),
                              li["l_orderkey"].max())) + 1, dtype=acc)
    np.add.at(summed, li["l_orderkey"], li["l_quantity"].astype(acc))
    total = summed[od["o_orderkey"]]
    large = np.flatnonzero(total > acc(100 * int(params["quantity"])))
    row_of = np.full(int(cu["c_custkey"].max()) + 1, -1, dtype=np.int64)
    row_of[cu["c_custkey"]] = np.arange(len(cu["c_custkey"]))
    large = large[row_of[od["o_custkey"][large]] >= 0]
    top = large[np.lexsort((od["o_orderkey"][large], od["o_orderdate"][large],
                            -od["o_totalprice"][large].astype(np.int64)))][:100]
    rows = [[str(cu["c_name"][row_of[od["o_custkey"][g]]]),
             int(od["o_custkey"][g]), int(od["o_orderkey"][g]),
             str(_EPOCH + int(od["o_orderdate"][g])),
             int(od["o_totalprice"][g]) / 100, total[g].item() / 100]
            for g in top]
    return {"columns": ["c_name", "c_custkey", "o_orderkey", "o_orderdate",
                        "o_totalprice", "total_qty"], "rows": rows}
