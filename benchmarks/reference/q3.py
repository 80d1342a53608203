"""TPC-H Q3 (spec §2.4.3), the plain reference: exact integer sums over
the generator's arrays. Parameters (§2.4.3.3): ``segment``, the index of
SEGMENT in the spec's list of market segments (§4.2.2.13; validation
value BUILDING, 1), and ``day``, the day of March 1995 that DATE is
(1 to 31; validation value 15). A mix's grid holds whole numbers only,
so both are numbered here and written out by :func:`bind`.

Ten rows, ``revenue`` descending, then ``o_orderdate`` (then the order
key, so that the reference is a function: a tie on both at the tenth
row would leave the statement's own answer open, and has not been seen)."""

import numpy as np

TABLES = ("customer", "orders", "lineitem")
COLUMNS = {"customer": ("c_custkey", "c_mktsegment"),
           "orders": ("o_orderkey", "o_custkey", "o_orderdate",
                      "o_shippriority"),
           "lineitem": ("l_orderkey", "l_extendedprice", "l_discount",
                        "l_shipdate")}
SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")
_EPOCH = np.datetime64("1970-01-01", "D")


def _date(params: dict) -> np.datetime64:
    return np.datetime64("1995-03-01", "D") + (int(params["day"]) - 1)


def bind(params: dict) -> dict:
    """What the statement's text takes from one draw of parameters."""
    return {"segment": SEGMENTS[int(params["segment"])],
            "date": str(_date(params))}


def answer(tables: dict, params: dict, acc=np.int64) -> dict:
    """Rows as the wire carries them: order key, revenue (the exact sum
    of price x (100 - discount) at scale 4, over 10**4), order date as
    an ISO string, ship priority. ``acc`` is the type the products are
    taken and summed in: int64 is the reference; the control
    (``benchmarks/control.py``) passes a narrower one."""
    cu, od, li = tables["customer"], tables["orders"], tables["lineitem"]
    date = int((_date(params) - _EPOCH).astype(np.int64))
    segment = SEGMENTS[int(params["segment"])]
    in_segment = np.zeros(int(cu["c_custkey"].max()) + 1, dtype=bool)
    in_segment[cu["c_custkey"][cu["c_mktsegment"] == segment]] = True
    open_order = (od["o_orderdate"] < date) & in_segment[od["o_custkey"]]
    # order key -> its row in orders, or -1 (keys need not be dense)
    row_of = np.full(int(od["o_orderkey"].max()) + 1, -1, dtype=np.int64)
    row_of[od["o_orderkey"][open_order]] = np.flatnonzero(open_order)
    line_row = row_of[li["l_orderkey"]]
    keep = (li["l_shipdate"] > date) & (line_row >= 0)
    rows_of = line_row[keep]
    cents4 = (li["l_extendedprice"][keep].astype(acc)
              * (100 - li["l_discount"][keep].astype(acc)))
    order = np.argsort(rows_of, kind="stable")
    rows_of, cents4 = rows_of[order], cents4[order]
    first = np.flatnonzero(np.r_[True, rows_of[1:] != rows_of[:-1]]) \
        if len(rows_of) else np.zeros(0, dtype=np.int64)
    groups = rows_of[first]
    revenue = np.add.reduceat(cents4, first, dtype=acc) if len(first) \
        else np.zeros(0, dtype=acc)
    # revenue desc, o_orderdate, then the key
    top = np.lexsort((od["o_orderkey"][groups], od["o_orderdate"][groups],
                      -revenue.astype(np.float64) if revenue.dtype.kind == "f"
                      else -revenue.astype(np.int64)))[:10]
    rows = [[int(od["o_orderkey"][g]), revenue[i].item() / 10**4,
             str(_EPOCH + int(od["o_orderdate"][g])),
             int(od["o_shippriority"][g])]
            for i, g in ((i, groups[i]) for i in top)]
    return {"columns": ["l_orderkey", "revenue", "o_orderdate",
                        "o_shippriority"], "rows": rows}
