"""Launch, one-shot path: ``launch_seconds.fetch`` (the device-to-host
reads of the check scalars and the result columns) per statement
answered."""


def read(r):
    n = r.answered()
    return (r.hist("launch_seconds.fetch")[1]) / n * 1e3 if n else 0.0
