"""Session: ``stage_seconds.admit`` (plan verify, admission's plan walk,
the capacity plane's, the dispatch seams) per statement answered."""


def read(r):
    n = r.answered()
    return (r.hist("stage_seconds.admit")[1]) / n * 1e3 if n else 0.0
