"""Session: ``stage_seconds.parse`` + ``.plan`` summed over the window,
per statement answered (a statement served from the statement cache adds
none, and counts)."""


def read(r):
    n = r.answered()
    if not n:
        return None
    return (r.hist("stage_seconds.parse")[1]
            + r.hist("stage_seconds.plan")[1]) / n * 1e3
