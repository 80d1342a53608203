"""The XLA program: device busy time of the traced sub-window over the
statements answered in it (a statement that straddles an edge counts by
the share of its time inside)."""


def read(r):
    stmts = sum(share for _, share in r.sub_statements())
    busy = r.trace.get("busy_s")
    return busy / stmts * 1e3 if busy and stmts else None
