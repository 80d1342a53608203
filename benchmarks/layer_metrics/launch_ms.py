"""H2D / pool / launch: mean ``stage_seconds.launch`` per statement (host
clock around the launch: input preparation, H2D, device, D2H)."""


def read(r):
    n, seconds = r.hist("stage_seconds.launch")
    return seconds / n * 1e3 if n else None
