"""The XLA program against the chip's memory bandwidth: the least time
the sub-window's statements could take (the bytes they have to read, by
the configuration's ``column_bytes``, over the HBM peak of ``peaks.json``)
over the device time they took. Bound: bandwidth. Never 0: without a
device trace there is nothing to read."""


def read(r):
    busy = r.trace.get("busy_s")
    need = sum(share * r.cell.scanned_bytes(stmt, r.rows)
               for stmt, share in r.sub_statements())
    if not busy or not need:
        return None
    return 100.0 * (need / r.peak("hbm_bytes_per_s")) / busy
