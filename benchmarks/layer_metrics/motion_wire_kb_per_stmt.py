"""Motion: kilobytes a statement's motions put on the wire, per segment
(``motion_wire_bytes``: each Motion's receive capacity times its packed
wire row, the capacity plane's ``stmt_wire_bytes`` arithmetic) per
statement answered. Beside ``collective_ms_per_stmt`` it says whether the
collectives are bound by their size or by their latency. 0.0 on a program
without the counter."""


def read(r):
    n = r.answered()
    return r.counter("motion_wire_bytes") / n / 1e3 if n else 0.0
