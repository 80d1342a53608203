"""TPC-H Q1 (spec §2.4.1), the plain reference: exact integer sums over
the generator's arrays. Parameter: ``delta`` days before 1998-12-01
(§2.4.1.3: 60 to 120; validation value 90).

The statement's text writes the cutoff as the date literal that
``date '1998-12-01' - interval '[DELTA]' day`` comes to (a variant of date
syntax, spec §2.2.3.3): the engine plans a date literal generically, and
compiles a program anew for every distinct ``interval`` literal (PR 25,
PERF.md §6), which would put a compile into every send of the window."""

import numpy as np

TABLES = ("lineitem",)
COLUMNS = {"lineitem": ("l_returnflag", "l_linestatus", "l_quantity",
                        "l_extendedprice", "l_discount", "l_tax",
                        "l_shipdate")}
# quotients, not sums: the engine divides in float64 on the device
RATIOS = ("avg_qty", "avg_price", "avg_disc")
_EPOCH = np.datetime64("1970-01-01", "D")


def _letters(col: np.ndarray) -> np.ndarray:
    if col.dtype != np.dtype("<U1"):
        raise TypeError(f"one-letter strings expected, got {col.dtype}")
    return np.ascontiguousarray(col).view(np.uint32)


def bind(params: dict) -> dict:
    """What the statement's text takes from one draw of parameters."""
    cutoff = np.datetime64("1998-12-01", "D") - int(params["delta"])
    return {"cutoff": str(cutoff)}


def answer(tables: dict, params: dict, acc=np.int64) -> dict:
    """Rows as the wire carries them. A money sum is the exact integer
    over 10**scale (quantity, price: cents; price x (1 - discount):
    scale 4; x (1 + tax): scale 6); an average is the exact sum over the
    count, then over 10**scale. ``acc`` is the type the columns are
    widened to and summed in: int64 is the reference; the control
    (``benchmarks/control.py``) passes a narrower one."""
    li = tables["lineitem"]
    cutoff = int((np.datetime64("1998-12-01", "D") - _EPOCH).astype(np.int64)
                 ) - int(params["delta"])
    keep = li["l_shipdate"] <= cutoff
    # one-letter strings compared as their code points (integers)
    flag = _letters(li["l_returnflag"])[keep]
    status = _letters(li["l_linestatus"])[keep]
    qty = li["l_quantity"][keep].astype(acc)
    price = li["l_extendedprice"][keep].astype(acc)
    disc = li["l_discount"][keep].astype(acc)
    tax = li["l_tax"][keep].astype(acc)
    disc_price = price * (100 - disc)
    charge = disc_price * (100 + tax)
    rows = []
    statuses = np.flatnonzero(np.bincount(status))
    for f in np.flatnonzero(np.bincount(flag)):
        for s in statuses:
            g = (flag == f) & (status == s)
            n = int(g.sum())
            if not n:
                continue
            s_qty, s_price, s_disc, s_dprice, s_charge = (
                x.sum(where=g, dtype=acc).item()
                for x in (qty, price, disc, disc_price, charge))
            rows.append([chr(f), chr(s), s_qty / 100, s_price / 100,
                         s_dprice / 10**4, s_charge / 10**6,
                         s_qty / n / 100, s_price / n / 100,
                         s_disc / n / 100, n])
    return {"columns": ["l_returnflag", "l_linestatus", "sum_qty",
                        "sum_base_price", "sum_disc_price", "sum_charge",
                        "avg_qty", "avg_price", "avg_disc", "count_order"],
            "rows": rows}
