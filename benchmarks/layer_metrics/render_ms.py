"""Wire + front end: mean ``stage_seconds.render`` per statement."""


def read(r):
    n, seconds = r.hist("stage_seconds.render")
    return seconds / n * 1e3 if n else None
