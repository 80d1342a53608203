"""Set-up, first half: the generator's tables written through the
engine's micro-partition store, and the generator's own arrays kept for
the plain reference.

The engine-facing half follows ``tools/tpchgen.stream_load_tpch``: worker
threads generate key-range chunks ahead while the calling thread owns
encode + append (dictionary growth and manifest commits stay on one
thread). What the reference later reads is what the GENERATOR made, never
what the store gives back, so the comparison that decides ``correct``
covers store write, read and decode.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.datagen import tpch


def schemas() -> dict:
    """The DDL of the deployment's tables in the engine's types: TPC-H
    §1.4, every column, DECIMAL(2) money, keys as 64-bit integers."""
    from cloudberry_tpu import types as T
    from cloudberry_tpu.types import Schema

    return {
        "customer": (Schema.of(
            c_custkey=T.INT64, c_name=T.STRING, c_address=T.STRING,
            c_nationkey=T.INT64, c_phone=T.STRING, c_acctbal=T.DECIMAL(2),
            c_mktsegment=T.STRING, c_comment=T.STRING), ("c_custkey",)),
        "orders": (Schema.of(
            o_orderkey=T.INT64, o_custkey=T.INT64, o_orderstatus=T.STRING,
            o_totalprice=T.DECIMAL(2), o_orderdate=T.DATE,
            o_orderpriority=T.STRING, o_clerk=T.STRING,
            o_shippriority=T.INT32, o_comment=T.STRING), ("o_orderkey",)),
        "lineitem": (Schema.of(
            l_orderkey=T.INT64, l_partkey=T.INT64, l_suppkey=T.INT64,
            l_linenumber=T.INT32, l_quantity=T.DECIMAL(2),
            l_extendedprice=T.DECIMAL(2), l_discount=T.DECIMAL(2),
            l_tax=T.DECIMAL(2), l_returnflag=T.STRING,
            l_linestatus=T.STRING, l_shipdate=T.DATE, l_commitdate=T.DATE,
            l_receiptdate=T.DATE, l_shipinstruct=T.STRING,
            l_shipmode=T.STRING, l_comment=T.STRING), ("l_orderkey",)),
    }


def compact(col: np.ndarray) -> np.ndarray:
    """A reference column in the narrowest array that holds it."""
    if col.dtype == object:
        return col.astype("U")
    if col.dtype.kind == "i" and len(col) and \
            -2**31 <= col.min() and col.max() < 2**31:
        return col.astype(np.int32)
    return col


def load(session, tables: list, keep: dict, scale: float, seed: int,
         chunk_rows: int, workers: int = 4) -> tuple:
    """Write ``tables`` at ``scale`` from ``seed`` through the session's
    store. Returns (rows per table, the generator's arrays of the
    ``keep`` columns per table, joined over the chunks)."""
    from cloudberry_tpu.catalog.catalog import DistributionPolicy
    from cloudberry_tpu.columnar.batch import encode_column

    store = session.catalog.store
    if store is None:
        raise ValueError("the loader needs a store (storage.root)")
    ddl = schemas()
    rpp = session.config.storage.rows_per_partition
    # one chunk function may fill two tables (orders carry their lines)
    jobs = []
    for driver in dict.fromkeys(tpch.DRIVER[t] for t in tables):
        jobs += [(driver, *r)
                 for r in tpch.chunk_ranges(driver, scale, chunk_rows)]
    dicts: dict = {t: {} for t in tables}
    first = set(tables)
    rows = {t: 0 for t in tables}
    kept: dict = {t: {c: [] for c in sorted(keep.get(t, ()))} for t in tables}
    held: dict = {t: [] for t in tables}    # encoded, not yet written

    def gen(job):
        driver, i, lo, hi = job
        return tpch.CHUNK_FN[driver](seed, i, lo, hi, scale)

    def write(table: str, everything: bool) -> None:
        """Whole partitions of what is held go to the store, as a bulk
        load writes them: ``rows_per_partition`` rows each, and one
        shorter partition at the table's end."""
        n = sum(len(next(iter(enc.values()))) for enc in held[table])
        take = n if everything else n - n % rpp
        if not take:
            return
        schema, key = ddl[table]
        joined = {c: np.concatenate([enc[c] for enc in held[table]])
                  for c in held[table][0]}
        store.append(table, {c: v[:take] for c, v in joined.items()},
                     schema, dicts=dicts[table], rows_per_partition=rpp,
                     policy=DistributionPolicy.hashed(*key),
                     replace=table in first)
        first.discard(table)
        held[table] = [{c: v[take:] for c, v in joined.items()}] \
            if take < n else []

    def append(chunk: dict) -> None:
        for table, cols in chunk.items():
            if table not in rows:
                continue
            raw = tpch.for_engine(table, cols)
            held[table].append({
                f.name: encode_column(np.asarray(raw[f.name]), f,
                                      dicts[table])
                for f in ddl[table][0].fields})
            write(table, everything=False)
            rows[table] += len(next(iter(cols.values())))
            for c, parts in kept[table].items():
                parts.append(compact(cols[c]))

    with ThreadPoolExecutor(max_workers=workers) as pool:
        pending = []
        for job in jobs:
            pending.append(pool.submit(gen, job))
            if len(pending) > workers:
                append(pending.pop(0).result())
        for fut in pending:
            append(fut.result())
    for table in tables:
        write(table, everything=True)
    session._sync_store()
    truth = {t: {c: np.concatenate(parts) for c, parts in cols.items()}
             for t, cols in kept.items()}
    return rows, truth
