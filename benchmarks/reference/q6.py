"""TPC-H Q6 (spec §2.4.6), the plain reference: an exact integer sum over
the generator's arrays. Parameters (§2.4.6.3): ``year`` 1993 to 1997,
``discount`` 0.02 to 0.09 in hundredths, ``quantity`` 24 or 25
(validation values 1994, 0.06, 24)."""

import numpy as np

TABLES = ("lineitem",)
COLUMNS = {"lineitem": ("l_extendedprice", "l_discount", "l_quantity",
                        "l_shipdate")}
_EPOCH = np.datetime64("1970-01-01", "D")


def _days(iso: str) -> int:
    return int((np.datetime64(iso, "D") - _EPOCH).astype(np.int64))


def bind(params: dict) -> dict:
    """What the statement's text takes from one draw of parameters:
    DISCOUNT - 0.01 and DISCOUNT + 0.01 written as two-digit decimals."""
    d = int(params["discount_pct"])
    return {"year": int(params["year"]),
            "discount_lo": f"{(d - 1) / 100:.2f}",
            "discount_hi": f"{(d + 1) / 100:.2f}",
            "quantity": int(params["quantity"])}


def answer(tables: dict, params: dict, acc=np.int64) -> dict:
    """One row: the sum of price x discount, exact at scale 4. ``acc`` is
    the type the product is taken and summed in: int64 is the reference;
    the control (``benchmarks/control.py``) passes a narrower one."""
    li = tables["lineitem"]
    year, d = int(params["year"]), int(params["discount_pct"])
    ship, disc = li["l_shipdate"], li["l_discount"]
    keep = ((ship >= _days(f"{year}-01-01"))
            & (ship < _days(f"{year + 1}-01-01"))
            & (disc >= d - 1) & (disc <= d + 1)
            & (li["l_quantity"] < int(params["quantity"]) * 100))
    if not keep.any():
        return {"columns": ["revenue"], "rows": [[None]]}
    cents4 = (li["l_extendedprice"][keep].astype(acc)
              * disc[keep].astype(acc)).sum(dtype=acc).item()
    return {"columns": ["revenue"], "rows": [[cents4 / 10**4]]}
