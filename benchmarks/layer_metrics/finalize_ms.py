"""Launch, tiled path: ``launch_seconds.prelude`` + ``.finalize`` (programs
built or found, resident inputs, the feed started; the feed closed, the
finalize program, the result's device-to-host reads) per statement
answered."""


def read(r):
    n = r.answered()
    return (r.hist("launch_seconds.prelude")[1]
            + r.hist("launch_seconds.finalize")[1]) / n * 1e3 if n else 0.0
