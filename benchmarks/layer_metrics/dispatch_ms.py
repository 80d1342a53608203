"""Launch, one-shot path: ``launch_seconds.dispatch`` (the call of the
compiled program until it returns) per statement answered."""


def read(r):
    n = r.answered()
    return (r.hist("launch_seconds.dispatch")[1]) / n * 1e3 if n else 0.0
