"""H2D / launch: the share of the rows the window's programs scanned
that is padding. A one-segment scan's capacity is the rung of its row
count (``exec/kernels.py row_rung_up``), so that another seed's few
thousand rows more or fewer are the same shapes; the program's scans
hold ``scan_rows`` rows in ``scan_capacity_rows`` of capacity (both
counted where a launch is accounted): 100 x (capacity - rows) /
capacity, at most 3.1 by the ladder. 0.0 on a program without the
counters (its scans are not padded), as on a window that launched
nothing: the line may not leave a listed metric out."""


def read(r):
    cap = r.counter("scan_capacity_rows")
    return 100.0 * (cap - r.counter("scan_rows")) / cap if cap else 0.0
