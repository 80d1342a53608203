"""Storage read + decode: ``decode_seconds`` summed over the window, per
statement answered. Only a cell whose statements read the store inside
the window has samples; without any there is nothing to read."""


def read(r):
    n_dec, seconds = r.hist("decode_seconds")
    n = r.answered()
    return seconds / n * 1e3 if n and n_dec else None
