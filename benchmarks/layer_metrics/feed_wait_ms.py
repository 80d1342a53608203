"""Launch, tiled path: ``launch_seconds.feed_wait`` (the statement thread
blocked on an empty tile queue) per statement answered."""


def read(r):
    n = r.answered()
    return (r.hist("launch_seconds.feed_wait")[1]) / n * 1e3 if n else 0.0
