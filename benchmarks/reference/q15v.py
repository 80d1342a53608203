"""The body of TPC-H Q15's revenue view (spec §2.4.15.2), the plain
reference: per-supplier exact integer sums over the generator's arrays.
Parameter (§2.4.15.3): ``month``, counted from January 1993 (0) to
October 1997 (57), whose first day DATE is; the view covers the three
months from it (validation value 1996-01-01: 36).

The statement adds ``order by supplier_no`` to the view's body, so that
its answer has an order to compare (``assumed`` in the configuration)."""

import numpy as np

TABLES = ("lineitem",)
COLUMNS = {"lineitem": ("l_suppkey", "l_extendedprice", "l_discount",
                        "l_shipdate")}
_EPOCH = np.datetime64("1970-01-01", "D")


def _months(params: dict) -> tuple:
    lo = np.datetime64("1993-01", "M") + int(params["month"])
    return lo.astype("datetime64[D]"), (lo + 3).astype("datetime64[D]")


def bind(params: dict) -> dict:
    """What the statement's text takes from one draw of parameters: DATE
    and DATE + 3 months, both written as date literals (as ``q1.sql``
    writes its cutoff: an ``interval`` literal is not planned
    generically)."""
    lo, hi = _months(params)
    return {"date_lo": str(lo), "date_hi": str(hi)}


def answer(tables: dict, params: dict, acc=np.int64) -> dict:
    """One row for each supplier with a line shipped in the quarter, by
    supplier number: the exact sum of price x (100 - discount) at scale
    4, over 10**4. ``acc`` is the type the products are taken and summed
    in: int64 is the reference; the control passes a narrower one."""
    li = tables["lineitem"]
    lo, hi = (int((d - _EPOCH).astype(np.int64)) for d in _months(params))
    keep = (li["l_shipdate"] >= lo) & (li["l_shipdate"] < hi)
    supp = li["l_suppkey"][keep].astype(np.int64)
    cents4 = (li["l_extendedprice"][keep].astype(acc)
              * (100 - li["l_discount"][keep].astype(acc)))
    order = np.argsort(supp, kind="stable")
    supp, cents4 = supp[order], cents4[order]
    if not len(supp):
        return {"columns": ["supplier_no", "total_revenue"], "rows": []}
    first = np.flatnonzero(np.r_[True, supp[1:] != supp[:-1]])
    sums = np.add.reduceat(cents4, first, dtype=acc)
    return {"columns": ["supplier_no", "total_revenue"],
            "rows": [[int(s), v.item() / 10**4]
                     for s, v in zip(supp[first], sums)]}
