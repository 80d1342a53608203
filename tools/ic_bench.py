"""Standalone interconnect benchmark — the ic_bench / udp2 analog.

The reference ships a kernel-independent interconnect benchmark
(contrib/interconnect/test/ic_bench.c, contrib/udp2's standalone-testable
transport): measure the motion layer WITHOUT the executor on top. Here the
motion layer is XLA collectives over the segment mesh, so this tool times
exactly the three collectives the engine's motions lower to
(exec/dist_executor.py):

- GATHER / BROADCAST  -> all_gather
- HASH redistribute   -> all_to_all
- check reduction     -> psum

Two modes:

- primitive (default): raw collective bandwidth per payload size.
- motion (``--format packed|percol|both``): a full TPC-H-shaped hash
  SHUFFLE through the engine's real motion lowering
  (exec/dist_executor.py DistLowerer._redistribute) — ``packed`` ships
  every column plus the validity mask in ONE fused all_to_all on the
  wire format of exec/kernels.py, sized to the adaptive capacity rung
  the ladder converges to; ``percol`` replays the legacy one-collective-
  per-column launches over planner-worst-case buckets. Reports launches
  (counted at trace time), bytes-on-wire, padding efficiency, and wall
  time; ``both`` additionally cross-checks per-column checksums between
  the formats.

Runs on whatever mesh is visible: 8 virtual CPU devices (tests), a real
TPU slice, or a multi-host cluster joined via mesh.init_distributed
(CBTPU_* env). Prints one JSON line per measurement; ``--csv`` appends
the same rows to a CSV file.

A third mode (``--two-level``) A/Bs the flat vs HIERARCHICAL shuffle at
a simulated multi-host split (CBTPU_FORCE_HOSTS env-forced process
grouping on CPU): per format the analytic DCN/ICI byte split, launches,
wall time, and exact checksum parity — the two-level transport's
received buffers are bit-identical to flat by construction.

Usage: python -m tools.ic_bench [--segs N] [--sizes bytes,...]
       python -m tools.ic_bench --format packed [--rows N] [--cols 10]
                                [--skew 0.5] [--csv out.csv]
       python -m tools.ic_bench --two-level --hosts 4 [--csv out.csv]
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np


class CountingTransport:
    """Transport proxy counting data-plane collective launches at trace
    time (all_gather / all_to_all; the stats pmax and check psum are
    control-plane and excluded from the launch comparison)."""

    def __init__(self, inner):
        self.inner = inner
        self.launches = 0

    def all_gather(self, x, axis):
        self.launches += 1
        return self.inner.all_gather(x, axis)

    def all_to_all(self, x, axis):
        self.launches += 1
        return self.inner.all_to_all(x, axis)

    def psum(self, x, axis):
        return self.inner.psum(x, axis)

    def pmax(self, x, axis):
        return self.inner.pmax(x, axis)


def shuffle_columns(n_cols: int, rows: int, nseg: int, skew: float,
                    seed: int = 11, src_skew: bool = False) -> dict:
    """A TPC-H-shaped wide row set: int64 keys/amounts (DECIMAL cents ride
    int64), f64 prices, int32 dates, an f32 and a bool flag — ``n_cols``
    columns per segment, (nseg, rows) each. Column "c0" is the hash key;
    ``skew`` is the fraction of rows sharing ONE hot key. ``src_skew``
    concentrates the hot rows on SOURCE segment 0 (the one-shard-holds-
    the-hot-slice shape of time-ordered ingest) — the case where flat
    motion pads EVERY source segment's buckets to the hot shard's
    demand while the two-level exchange pads per host pair."""
    rng = np.random.default_rng(seed)
    cols: dict[str, np.ndarray] = {}
    kinds = ["i64", "i64", "f64", "i32", "i64", "f64", "i32", "f32",
             "bool", "i64"]
    for i in range(n_cols):
        kind = kinds[i % len(kinds)]
        if i == 0:
            k = rng.integers(0, 100_000, (nseg, rows))
            hot = rng.random((nseg, rows)) < skew
            if src_skew:
                hot &= (np.arange(nseg) == 0)[:, None]
            cols["c0"] = np.where(hot, 7, k).astype(np.int64)
        elif kind == "i64":
            cols[f"c{i}"] = rng.integers(-1 << 40, 1 << 40, (nseg, rows))
        elif kind == "f64":
            cols[f"c{i}"] = rng.standard_normal((nseg, rows))
        elif kind == "i32":
            cols[f"c{i}"] = rng.integers(0, 20_000, (nseg, rows)
                                         ).astype(np.int32)
        elif kind == "f32":
            cols[f"c{i}"] = rng.standard_normal(
                (nseg, rows)).astype(np.float32)
        else:
            cols[f"c{i}"] = rng.integers(0, 2, (nseg, rows)
                                         ).astype(np.bool_)
    return cols


def bench_shuffle(fmt: str, nseg: int, rows: int, n_cols: int,
                  skew: float, backend: str, reps: int,
                  capacity_factor: float = 2.0) -> dict:
    """One shuffle measurement through the engine's real motion lowering;
    returns the JSON record (and the received checksums under "_sums"
    for the both-formats parity check)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from cloudberry_tpu.exec import kernels as K
    from cloudberry_tpu.exec.dist_executor import DistLowerer, _shard_map
    from cloudberry_tpu.parallel.mesh import SEG_AXIS, segment_mesh
    from cloudberry_tpu.parallel.transport import make_transport
    from cloudberry_tpu.plan import expr as ex
    from cloudberry_tpu.plan import nodes as N
    from cloudberry_tpu.types import INT64
    from cloudberry_tpu.utils import hashing

    mesh = segment_mesh(nseg)
    data = shuffle_columns(n_cols, rows, nseg, skew)
    packed = fmt == "packed"

    # bucket capacity: percol replays the static planner discipline
    # (fair share × capacity_factor); packed sizes to the adaptive rung
    # the ladder converges to — the actual global max bucket, rounded up
    dest_all = hashing.jump_consistent_hash_np(
        hashing.hash_columns_np([data["c0"].reshape(-1)]), nseg)
    actual_max = int(np.bincount(
        np.repeat(np.arange(nseg), rows) * nseg + dest_all,
        minlength=nseg * nseg).max())
    if packed:
        bucket_cap = K.rung_up(actual_max)
    else:
        bucket_cap = max(int(np.ceil(rows / nseg * capacity_factor)), 8)
        bucket_cap = max(bucket_cap, actual_max)  # complete, not error

    node = N.PMotion(N.PScan("$dual", {}, 1), "redistribute",
                     hash_keys=[ex.ColumnRef("c0", INT64)])
    node.bucket_cap = bucket_cap

    tx = CountingTransport(make_transport(backend, nseg))

    def _cksum(v, osel):
        # order-independent exact checksum: sum of the value's u32 words
        # over selected rows, in uint64 (no float reduction-order noise —
        # the packed/percol parity comparison must be exact)
        if v.dtype == jnp.bool_:
            w = v.astype(jnp.uint32)[..., None]
        else:
            w = jax.lax.bitcast_convert_type(v, jnp.uint32)
            if w.ndim == v.ndim:
                w = w[..., None]
        return jnp.sum(jnp.where(osel[..., None], w,
                                 jnp.uint32(0)).astype(jnp.uint64))

    def seg_fn(x):
        cols = {k: v[0] for k, v in x.items()}
        sel = jnp.ones((rows,), dtype=jnp.bool_)
        low = DistLowerer({}, nseg, tx=tx, packed=packed)
        out, osel = low._redistribute(node, cols, sel)
        # checksums keep every received column alive (and cross-check
        # packed vs percol when both formats run)
        return {k: _cksum(v, osel)[None] for k, v in out.items()}

    in_specs = ({k: P(SEG_AXIS, None) for k in data},)
    fn = jax.jit(_shard_map(seg_fn, mesh, in_specs, P(SEG_AXIS)))
    out = jax.block_until_ready(fn(data))  # trace + compile (counts tx)
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        out = jax.block_until_ready(fn(data))
        best = min(best, time.time() - t0)

    layout = K.wire_layout({k: jnp.asarray(v[0]).dtype
                            for k, v in data.items()})
    n_bufrows = nseg * bucket_cap
    if packed:
        wire = n_bufrows * layout.row_bytes()
    else:
        wire = sum(n_bufrows * np.dtype(v.dtype).itemsize
                   for v in data.values()) + n_bufrows  # + bool sel buffer
    payload = rows * layout.payload_bytes()  # rows actually routed
    rec = {
        "mode": "shuffle",
        "format": fmt,
        "backend": backend,
        "n_segments": nseg,
        "rows_per_seg": rows,
        "n_cols": n_cols,
        "skew": skew,
        "bucket_cap": bucket_cap,
        "collective_launches": tx.launches,
        "wire_bytes_per_seg": int(wire),
        "payload_bytes_per_seg": int(payload),
        "padding_frac": round(1.0 - payload / wire, 4),
        "wall_ms": round(best * 1e3, 3),
        "gbps_per_seg": round(wire * 8 / best / 1e9, 3),
    }
    # keep exact uint64 checksums (a float() here would collapse low-bit
    # differences past 2^53 and mask real corruption in the parity check)
    rec["_sums"] = {k: int(np.asarray(v).sum(dtype=np.uint64))
                    for k, v in out.items()}
    return rec


def bench_two_level(nseg: int, hosts: int, rows: int, n_cols: int,
                    skew: float, reps: int,
                    csv_path: str | None) -> None:
    """Flat vs hierarchical shuffle A/B at a SIMULATED multi-host split
    (CBTPU_FORCE_HOSTS partitions the single-process mesh into
    contiguous uniform hosts — the env-forced process grouping). Both
    formats run the engine's real motion lowering; the hierarchical run
    carries the planner-style host stamps and the two-level transport.
    Reports per format the analytic DCN/ICI byte split (flat: every
    cross-host segment-pair block crosses DCN padded to the pair rung;
    two-level: one aggregated block per host pair at the host rung,
    with the lane staging hops riding ICI), collective launches counted
    at trace time, wall clock, and exact per-column checksum parity —
    the received buffers are bit-identical by construction, and the
    parity record proves it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from cloudberry_tpu.config import Config
    from cloudberry_tpu.exec import kernels as K
    from cloudberry_tpu.exec.dist_executor import DistLowerer, _shard_map
    from cloudberry_tpu.parallel.mesh import SEG_AXIS, segment_mesh
    from cloudberry_tpu.parallel.transport import (flat_wire_model,
                                                   hier_topology,
                                                   make_transport,
                                                   two_level_wire_model)
    from cloudberry_tpu.plan import expr as ex
    from cloudberry_tpu.plan import nodes as N
    from cloudberry_tpu.types import INT64
    from cloudberry_tpu.utils import hashing

    if nseg % hosts:
        raise SystemExit(f"--hosts {hosts} must divide --segs {nseg}")
    os.environ["CBTPU_FORCE_HOSTS"] = str(hosts)
    S = nseg // hosts
    mesh = segment_mesh(nseg)
    data = shuffle_columns(n_cols, rows, nseg, skew, src_skew=True)

    # adaptive rungs from the ACTUAL demand (the state the capacity
    # ladder converges to), at both granularities
    dest_all = hashing.jump_consistent_hash_np(
        hashing.hash_columns_np([data["c0"].reshape(-1)]), nseg)
    src_all = np.repeat(np.arange(nseg), rows)
    B = K.rung_up(int(np.bincount(
        src_all * nseg + dest_all, minlength=nseg * nseg).max()))
    HB = K.rung_up(int(np.bincount(
        (src_all // S) * hosts + dest_all // S,
        minlength=hosts * hosts).max()))

    layout = K.wire_layout({k: jnp.asarray(v[0]).dtype
                            for k, v in data.items()})
    rb = layout.row_bytes()
    cfg = Config(n_segments=nseg).with_overrides(
        **{"interconnect.hierarchical": "on"})

    def _cksum(v, osel):
        if v.dtype == jnp.bool_:
            w = v.astype(jnp.uint32)[..., None]
        else:
            w = jax.lax.bitcast_convert_type(v, jnp.uint32)
            if w.ndim == v.ndim:
                w = w[..., None]
        return jnp.sum(jnp.where(osel[..., None], w,
                                 jnp.uint32(0)).astype(jnp.uint64))

    recs = {}
    for fmt in ("flat", "hier"):
        node = N.PMotion(N.PScan("$dual", {}, 1), "redistribute",
                         hash_keys=[ex.ColumnRef("c0", INT64)])
        node.bucket_cap = B
        if fmt == "hier":
            node.host_bucket_cap = HB
            node.hier_hosts = hosts
            tx = make_transport("xla", nseg,
                                topo=hier_topology(cfg, nseg))
        else:
            tx = CountingTransport(make_transport("xla", nseg))

        def seg_fn(x):
            cols = {k: v[0] for k, v in x.items()}
            sel = jnp.ones((rows,), dtype=jnp.bool_)
            low = DistLowerer({}, nseg, tx=tx, packed=True)
            out, osel = low._redistribute(node, cols, sel)
            return {k: _cksum(v, osel)[None] for k, v in out.items()}

        in_specs = ({k: P(SEG_AXIS, None) for k in data},)
        fn = jax.jit(_shard_map(seg_fn, mesh, in_specs, P(SEG_AXIS)))
        out = jax.block_until_ready(fn(data))   # trace counts launches
        best = float("inf")
        for _ in range(reps):
            t0 = time.time()
            out = jax.block_until_ready(fn(data))
            best = min(best, time.time() - t0)

        launches = tx.launches
        if fmt == "hier":
            model = two_level_wire_model(nseg, hosts, B, HB, rb)
        else:
            model = flat_wire_model(nseg, hosts, B, rb)
        dcn, ici = model["dcn_bytes"], model["ici_bytes"]
        rec = {
            "mode": "two-level",
            "format": fmt,
            "hosts": hosts,
            "n_segments": nseg,
            "rows_per_seg": rows,
            "n_cols": n_cols,
            "skew": skew,
            "bucket_cap": B,
            "host_bucket_cap": HB if fmt == "hier" else 0,
            "launches": launches,
            "dcn_bytes": int(dcn),
            "ici_bytes": int(ici),
            "wall_ms": round(best * 1e3, 3),
        }
        rec["_sums"] = {k: int(np.asarray(v).sum(dtype=np.uint64))
                        for k, v in out.items()}
        recs[fmt] = rec
        _emit(rec, csv_path)
    a, b = recs["flat"]["_sums"], recs["hier"]["_sums"]
    ok = set(a) == set(b) and all(a[k] == b[k] for k in a)
    _emit({
        "mode": "two-level-summary",
        "hosts": hosts,
        "checksums_match": bool(ok),
        "dcn_ratio": round(recs["flat"]["dcn_bytes"]
                           / max(recs["hier"]["dcn_bytes"], 1), 3),
        "ici_ratio": round(recs["flat"]["ici_bytes"]
                           / max(recs["hier"]["ici_bytes"], 1), 3),
        "launch_delta": recs["hier"]["launches"]
        - recs["flat"]["launches"],
    }, csv_path)
    if not ok:
        raise SystemExit("two-level checksum parity FAILED")


def bench_join_filter(nseg: int, rows: int, dim_rows: int, skew: float,
                      reps: int, csv_path: str | None) -> None:
    """Engine-level PK–FK shuffle with the DIGEST runtime filter on vs
    off (the semijoin-reduction measurement): a skewed fact table joins a
    dimension covering only a fraction of the key domain, so most probe
    rows provably have no partner. Reports — per mode — the probe rows
    actually shipped (the filter's psum'd pre/post stats), the capacity
    rung the redistribute seeded, wire bytes at that rung, and wall time;
    then a repeated-statement microbench showing the join-index cache
    (cache-hit counter, compile delta — the no-argsort/no-recompile
    acceptance)."""
    import time as _t

    import cloudberry_tpu as cb
    from cloudberry_tpu.config import Config
    from cloudberry_tpu.exec import kernels as K
    from cloudberry_tpu.plan import nodes as PN
    from cloudberry_tpu.plan.binder import Binder
    from cloudberry_tpu.plan.planner import _optimize
    from cloudberry_tpu.sql.parser import parse_sql

    rng = np.random.default_rng(17)
    # fact keys: skew fraction lands on ONE hot key OUTSIDE the dim
    # domain (dim covers dim_rows keys, fact spans 10x them), the rest
    # uniform — so the filter both drops ~90% of the uniform probes AND
    # deletes the hot bucket that sized the unfiltered capacity rung:
    # semijoin reduction doubles as skew relief, the MPP classic
    # (every key a multiple of ``stride``: a span no array of the join comes
    # near, so the repeated statement keeps the sorted search that the
    # join-index cache serves, where dense keys would find their rows in
    # a direct-address table: PJoin.direct_lookup)
    stride = 1000
    ks = rng.integers(0, dim_rows * 10, rows) * stride
    hot = rng.random(rows) < skew
    grp = np.where(hot, dim_rows * 5 * stride, ks)

    def mk(enabled: bool):
        cfg = Config(n_segments=nseg).with_overrides(**{
            "planner.broadcast_threshold": 0,       # force redistribute
            "planner.runtime_filter_threshold": 0,  # digest, never exact
            "join_filter.enabled": enabled,
            "join_filter.bloom_bits": 1 << 14,
        })
        s = cb.Session(cfg)
        s.sql("create table fact (k bigint, grp bigint, v bigint) "
              "distributed by (k)")
        s.sql("create table dim (d bigint, p bigint) distributed by (d)")
        vals = ",".join(f"({i}, {int(g)}, {i % 97})"
                        for i, g in enumerate(grp))
        s.sql(f"insert into fact values {vals}")
        vals = ",".join(f"({i * stride}, {i * 2})"
                        for i in range(dim_rows))
        s.sql(f"insert into dim values {vals}")
        return s

    q = ("select grp, count(*) as n from fact, dim where grp = d "
         "group by grp order by grp")
    recs = {}
    for enabled in (False, True):
        s = mk(enabled)
        plan = _optimize(Binder(s.catalog, s.config)
                         .bind_query(parse_sql(q)), s)
        probe_motion = next(
            m for m in _walk(plan, PN.PMotion)
            if m.kind == "redistribute"
            and any(sc.table_name == "fact" for sc in _walk(m, PN.PScan)))
        layout = K.wire_layout({f.name: f.type.np_dtype
                                for f in probe_motion.fields})
        s.sql(q)  # warm (compile + first stats)
        best = float("inf")
        for _ in range(reps):
            t0 = _t.time()
            s.sql(q)
            best = min(best, _t.time() - t0)
        runs = 1 + reps
        # jf_rows_in == 0 means the cost model declined to insert any
        # filter: report the unfiltered row count, not a perfect 0
        fired = enabled and s.stmt_log.counter("jf_rows_in") > 0
        shipped = (s.stmt_log.counter("jf_rows_out") // runs
                   if fired else rows)
        rec = {
            "mode": "join_filter",
            "filter": "on" if enabled else "off",
            "n_segments": nseg,
            "fact_rows": rows,
            "dim_rows": dim_rows,
            "skew": skew,
            "probe_rows_shipped": int(shipped),
            "bucket_rung": int(probe_motion.bucket_cap),
            "wire_bytes_per_seg": int(probe_motion.bucket_cap * nseg
                                      * layout.row_bytes()),
            "wall_ms": round(best * 1e3, 3),
        }
        recs[enabled] = (rec, s)
        _emit(rec, csv_path)
    off, on = recs[False][0], recs[True][0]
    s_on = recs[True][1]
    c0 = s_on.stmt_log.counter("compiles")
    h0 = s_on.stmt_log.counter("join_index_hits")
    s_on.sql(q)
    s_on.sql(q)
    _emit({
        "mode": "join_filter-summary",
        "row_reduction": round(1.0 - on["probe_rows_shipped"]
                               / max(off["probe_rows_shipped"], 1), 4),
        "wire_bytes_reduction": round(1.0 - on["wire_bytes_per_seg"]
                                      / max(off["wire_bytes_per_seg"], 1),
                                      4),
        "rung_ratio": round(off["bucket_rung"]
                            / max(on["bucket_rung"], 1), 2),
        # repeated-statement microbench: the sorted-build cache serves
        # the dim argsort from the session LRU with ZERO recompiles
        "join_index_hits": s_on.stmt_log.counter("join_index_hits") - h0,
        "repeat_compiles": s_on.stmt_log.counter("compiles") - c0,
    }, csv_path)


def _walk(plan, kind):
    from cloudberry_tpu.exec.executor import all_nodes

    seen = set()
    out = []
    for n in all_nodes(plan):
        if isinstance(n, kind) and id(n) not in seen:
            seen.add(id(n))
            out.append(n)
    return out


def _emit(rec: dict, csv_path: str | None) -> None:
    sums = rec.pop("_sums", None)
    print(json.dumps(rec), flush=True)
    if csv_path:
        import csv
        import sys

        fields = list(rec)
        path = csv_path
        if os.path.exists(path):
            with open(path, newline="") as f:
                header = f.readline().strip().split(",")
            if header != fields:
                # primitive-mode and shuffle-mode rows have different
                # schemas: never append misaligned rows under a foreign
                # header — divert to a per-schema sibling file instead
                base, ext = os.path.splitext(path)
                path = f"{base}.{rec.get('mode', 'primitive')}" \
                       f"{ext or '.csv'}"
                print(f"csv schema differs from {csv_path}; "
                      f"writing to {path}", file=sys.stderr)
        new = not os.path.exists(path)
        with open(path, "a", newline="") as f:
            w = csv.DictWriter(f, fieldnames=fields)
            if new:
                w.writeheader()
            w.writerow(rec)
    if sums is not None:
        rec["_sums"] = sums


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--segs", type=int, default=0,
                    help="segments (default: all visible devices)")
    ap.add_argument("--sizes", type=str, default="65536,1048576,16777216",
                    help="per-segment payload bytes, comma-separated "
                         "(primitive mode)")
    ap.add_argument("--backend", default="xla",
                    help="motion transport: xla | ring "
                         "(parallel/transport.py)")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--format", choices=["packed", "percol", "both"],
                    default=None,
                    help="motion-level shuffle mode: packed (one fused "
                         "all_to_all) vs percol (one collective per "
                         "column); 'both' runs the pair and cross-checks")
    ap.add_argument("--rows", type=int, default=50000,
                    help="rows per segment (shuffle mode)")
    ap.add_argument("--cols", type=int, default=10,
                    help="columns in the shuffled row set")
    ap.add_argument("--skew", type=float, default=0.0,
                    help="fraction of rows sharing one hot key")
    ap.add_argument("--two-level", action="store_true",
                    help="flat vs hierarchical shuffle A/B at a "
                         "simulated multi-host split (CBTPU_FORCE_HOSTS "
                         "process grouping): dcn/ici byte split, "
                         "launches, wall, exact checksum parity")
    ap.add_argument("--hosts", type=int, default=4,
                    help="simulated host count for --two-level "
                         "(must divide the segment count)")
    ap.add_argument("--join-filter", action="store_true",
                    help="PK-FK shuffle with the digest runtime filter "
                         "on vs off: probe rows shipped, wire bytes, "
                         "capacity rung, plus the join-index cache "
                         "repeat microbench")
    ap.add_argument("--dim-rows", type=int, default=2000,
                    help="dimension rows (join-filter mode); fact keys "
                         "span 10x this domain")
    ap.add_argument("--csv", default=None,
                    help="append measurements to this CSV file")
    args = ap.parse_args()

    import sys

    import jax

    from cloudberry_tpu.parallel.mesh import init_distributed
    from cloudberry_tpu.utils.compilecache import entry_banner

    init_distributed()
    print(f"# {entry_banner()}", file=sys.stderr)
    nseg = args.segs or len(jax.devices())

    if args.two_level:
        # default: a source-concentrated hot key (src_skew puts it on
        # segment 0) — the measured 4-host/8-seg split shows ~3.6x
        # lower DCN bytes (flat pads EVERY source segment's buckets to
        # the hot shard's rung; two-level pads per host pair)
        skew = args.skew if args.skew > 0.0 else 0.7
        bench_two_level(nseg, args.hosts, args.rows, args.cols, skew,
                        args.reps, args.csv)
        return

    if args.join_filter:
        skew = args.skew if args.skew > 0.0 else 0.3
        bench_join_filter(nseg, args.rows, args.dim_rows, skew,
                          args.reps, args.csv)
        return

    if args.format is not None:
        fmts = ["percol", "packed"] if args.format == "both" \
            else [args.format]
        recs = {}
        for fmt in fmts:
            recs[fmt] = bench_shuffle(fmt, nseg, args.rows, args.cols,
                                      args.skew, args.backend, args.reps)
            _emit(recs[fmt], args.csv)
        if len(recs) == 2:
            a, b = recs["percol"]["_sums"], recs["packed"]["_sums"]
            ok = set(a) == set(b) and all(a[k] == b[k] for k in a)
            print(json.dumps({
                "mode": "shuffle-parity",
                "checksums_match": bool(ok),
                "launch_ratio": round(
                    recs["percol"]["collective_launches"]
                    / max(recs["packed"]["collective_launches"], 1), 2),
                "wire_bytes_ratio": round(
                    recs["percol"]["wire_bytes_per_seg"]
                    / max(recs["packed"]["wire_bytes_per_seg"], 1), 3),
            }), flush=True)
        return

    from jax.sharding import PartitionSpec as P

    from cloudberry_tpu.parallel.mesh import SEG_AXIS, segment_mesh
    from cloudberry_tpu.exec.dist_executor import _shard_map

    mesh = segment_mesh(nseg)

    def bench(fn, x, label, nbytes):
        out = jax.block_until_ready(fn(x))
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.time()
            out = jax.block_until_ready(fn(x))
            best = min(best, time.time() - t0)
        rec = {
            "collective": label,
            "payload_bytes_per_seg": nbytes,
            "n_segments": nseg,
            "wall_ms": round(best * 1e3, 3),
            "gbps_per_seg": round(nbytes * 8 / best / 1e9, 3),
        }
        _emit(rec, args.csv)
        return out

    from cloudberry_tpu.parallel.transport import make_transport

    tx = make_transport(args.backend, nseg)

    for size in (int(s) for s in args.sizes.split(",") if s.strip()):
        n = max(size // 4, nseg)           # f32 lanes per segment
        n += (-n) % nseg                   # all_to_all splits evenly
        x = np.arange(nseg * n, dtype=np.float32).reshape(nseg, n)

        def ag(v):
            return tx.all_gather(v[0], SEG_AXIS)

        def a2a(v):
            return tx.all_to_all(v[0].reshape(nseg, n // nseg), SEG_AXIS)

        def ps(v):
            # reduce the FULL payload so the reported bytes really cross
            # the interconnect (a scalar psum would move 4 bytes)
            return tx.psum(v[0], SEG_AXIS)

        for label, fn, spec in (("all_gather", ag, P(SEG_AXIS)),
                                ("all_to_all", a2a, P(SEG_AXIS)),
                                ("psum", ps, P())):
            f = jax.jit(_shard_map(
                lambda v, _fn=fn: _fn(v), mesh,
                (P(SEG_AXIS, None),), spec))
            bench(f, x, label, n * 4)


if __name__ == "__main__":
    main()
