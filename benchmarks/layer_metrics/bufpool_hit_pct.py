"""Buffer pool: hits over lookups inside the window. A cell whose
statements never ask the pool has nothing to read."""


def read(r):
    hits, misses = r.counter("bufpool_hits"), r.counter("bufpool_misses")
    return 100.0 * hits / (hits + misses) if hits + misses else None
