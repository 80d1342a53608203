"""Launch, tiled path: ``launch_seconds.h2d`` (``jax.device_put`` of a
tile's columns, on the statement thread) per statement answered."""


def read(r):
    n = r.answered()
    return (r.hist("launch_seconds.h2d")[1]) / n * 1e3 if n else 0.0
