"""Launch, tiled path: ``launch_seconds.tile_step`` + ``.drain_stall``
(dispatching a tile's step and forcing the window's oldest control
scalars) per statement answered."""


def read(r):
    n = r.answered()
    return (r.hist("launch_seconds.tile_step")[1]
            + r.hist("launch_seconds.drain_stall")[1]) / n * 1e3 if n else 0.0
