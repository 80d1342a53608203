"""Launch, one-shot path: ``launch_seconds.inputs`` (assembling the
program's inputs: table columns, keyed scans, literal bindings) per
statement answered."""


def read(r):
    n = r.answered()
    return (r.hist("launch_seconds.inputs")[1]) / n * 1e3 if n else 0.0
