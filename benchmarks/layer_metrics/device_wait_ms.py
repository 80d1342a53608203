"""Launch, one-shot path: ``launch_seconds.device_wait`` (from the
dispatch's return until the outputs are ready) per statement answered."""


def read(r):
    n = r.answered()
    return (r.hist("launch_seconds.device_wait")[1]) / n * 1e3 if n else 0.0
