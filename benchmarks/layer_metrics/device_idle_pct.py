"""Device: the share of the traced sub-window in which no operation ran
on the chip."""


def read(r):
    busy, window = r.trace.get("busy_s"), r.trace.get("window_s")
    return 100.0 * (1.0 - busy / window) if busy and window else None
