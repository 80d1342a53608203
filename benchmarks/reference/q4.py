"""TPC-H Q4 (spec §2.4.4), the plain reference: counts over the
generator's arrays. Parameters (§2.4.4.3): ``year`` and ``month``, the
first day of which DATE is (a month of January 1993 to October 1997;
validation value 1993-07-01). A mix's grid holds whole numbers only, so
the date is numbered here and written out by :func:`bind`.

One row for each order priority that has an order placed in the three
months from DATE with at least one line committed before it was
received, by priority: the number of such orders."""

import numpy as np

TABLES = ("orders", "lineitem")
COLUMNS = {"orders": ("o_orderkey", "o_orderdate", "o_orderpriority"),
           "lineitem": ("l_orderkey", "l_commitdate", "l_receiptdate")}
_EPOCH = np.datetime64("1970-01-01", "D")


def _months(params: dict) -> tuple:
    """DATE and DATE + 3 months, as days since the epoch."""
    first = np.datetime64(f"{int(params['year'])}-{int(params['month']):02d}",
                          "M")
    return tuple(int((np.datetime64(m, "D") - _EPOCH).astype(np.int64))
                 for m in (first, first + 3))


def bind(params: dict) -> dict:
    """What the statement's text takes from one draw of parameters."""
    return {"date": f"{int(params['year'])}-{int(params['month']):02d}-01"}


def answer(tables: dict, params: dict, acc=np.int64) -> dict:
    """Rows as the wire carries them: priority, order count. ``acc`` is
    the type the counts are summed in: int64 is the reference; the
    control (``benchmarks/control.py``) passes a narrower one, which
    counts of a few thousand orders survive: Q3's money sums carry the
    control in this cell."""
    od, li = tables["orders"], tables["lineitem"]
    lo, hi = _months(params)
    late = li["l_orderkey"][li["l_commitdate"] < li["l_receiptdate"]]
    # order key -> whether one of its lines was received after its commit
    has_late = np.zeros(int(max(od["o_orderkey"].max(),
                                late.max(initial=0))) + 1, dtype=bool)
    has_late[late] = True
    keep = ((od["o_orderdate"] >= lo) & (od["o_orderdate"] < hi)
            & has_late[od["o_orderkey"]])
    prio = od["o_orderpriority"][keep]
    rows = []
    for p in sorted(set(prio.tolist())):
        rows.append([p, int((prio == p).astype(acc).sum(dtype=acc))])
    return {"columns": ["o_orderpriority", "order_count"], "rows": rows}
