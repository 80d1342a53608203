"""Storage read + decode: ``feed_seconds.part_read`` per statement
answered: the WALL time of the scan reader thread's partition reads
(file read, checksum, column-parallel decode; pool hits included, at
microseconds), where ``decode_ms`` sums the decode threads' busy seconds."""


def read(r):
    n = r.answered()
    return (r.hist("feed_seconds.part_read")[1]) / n * 1e3 if n else 0.0
