"""Distributed launch: host megabytes handed to the program and kept by
it (``dist_input_bytes``: the shards of the columns the statement scans,
which live on the host and cross to the chips on EVERY launch) per
statement answered. 0.0 on a program without the counter."""


def read(r):
    n = r.answered()
    return r.counter("dist_input_bytes") / n / 1e6 if n else 0.0
