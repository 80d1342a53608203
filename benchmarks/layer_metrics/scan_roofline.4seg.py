"""The XLA program on four chips against their memory bandwidth: the
least time the sub-window's statements could take (the bytes they have to
read, by the configuration's ``column_bytes``, spread over the cell's
chips, each at the HBM peak of ``peaks.json``) over the device time they
took (``busy_s``: the MEAN over the device planes). The shared reader
divides by one chip's bandwidth, which on four planes would read four
times too high. Bound: HBM bandwidth. None, never 0, without a trace."""


def read(r):
    busy = r.trace.get("busy_s")
    need = sum(share * r.cell.scanned_bytes(stmt, r.rows)
               for stmt, share in r.sub_statements())
    if not busy or not need:
        return None
    return 100.0 * (need / (r.cell.chips * r.peak("hbm_bytes_per_s"))) / busy
