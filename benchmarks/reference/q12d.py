"""TPC-H Q12 (spec §2.4.12) with its modes drawn as the spec draws them,
the plain reference: counts over the generator's arrays. Parameters
(§2.4.12.3): ``shipmode1``, the index of SHIPMODE1 in the spec's list of
modes (§4.2.2.13, 0 to 6), ``apart``, how far along that list, round its
end, SHIPMODE2 lies (1 to 6, so that it differs from SHIPMODE1, as the
spec asks), and ``year``, whose first of January DATE is (1993 to 1997).
A mix's grid holds whole numbers only and cannot say "another mode" by
itself, so the modes are numbered here and written out by :func:`bind`;
its statement, ``statements/q12d.sql``, is q12.sql's text.

One row for each of the two modes that has a line, by mode: the lines
received in the year, committed before they were received and shipped
before they were committed, counted by whether their order's priority is
one of the two high ones."""

import numpy as np

TABLES = ("orders", "lineitem")
COLUMNS = {"orders": ("o_orderkey", "o_orderpriority"),
           "lineitem": ("l_orderkey", "l_shipmode", "l_commitdate",
                        "l_receiptdate", "l_shipdate")}
MODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")
HIGH = ("1-URGENT", "2-HIGH")
_EPOCH = np.datetime64("1970-01-01", "D")


def _modes(params: dict) -> tuple:
    first = int(params["shipmode1"])
    return (MODES[first], MODES[(first + int(params["apart"])) % len(MODES)])


def bind(params: dict) -> dict:
    """What the statement's text takes from one draw of parameters."""
    m1, m2 = _modes(params)
    return {"shipmode1": m1, "shipmode2": m2,
            "date": f"{int(params['year'])}-01-01"}


def answer(tables: dict, params: dict, acc=np.int64) -> dict:
    """Rows as the wire carries them: mode, high count, low count.
    ``acc`` is the type the counts are summed in: int64 is the
    reference; the control (``benchmarks/control.py``) passes a narrower
    one, which counts of a few thousand lines survive: Q3's money sums
    carry the control in this cell."""
    od, li = tables["orders"], tables["lineitem"]
    year = int(params["year"])
    lo, hi = (int((np.datetime64(f"{y}-01-01", "D") - _EPOCH)
                  .astype(np.int64)) for y in (year, year + 1))
    modes = _modes(params)
    keep = (np.isin(li["l_shipmode"], modes)
            & (li["l_commitdate"] < li["l_receiptdate"])
            & (li["l_shipdate"] < li["l_commitdate"])
            & (li["l_receiptdate"] >= lo) & (li["l_receiptdate"] < hi))
    # order key -> its row in orders, or -1 (keys need not be dense)
    row_of = np.full(int(od["o_orderkey"].max()) + 1, -1, dtype=np.int64)
    row_of[od["o_orderkey"]] = np.arange(len(od["o_orderkey"]))
    line_row = row_of[li["l_orderkey"][keep]]
    joined = line_row >= 0
    mode = li["l_shipmode"][keep][joined]
    high = np.isin(od["o_orderpriority"][line_row[joined]], HIGH)
    rows = []
    for m in sorted(set(modes)):
        mine = mode == m
        if mine.any():
            rows.append([m, int(high[mine].astype(acc).sum(dtype=acc)),
                         int((~high[mine]).astype(acc).sum(dtype=acc))])
    return {"columns": ["l_shipmode", "high_line_count", "low_line_count"],
            "rows": rows}
