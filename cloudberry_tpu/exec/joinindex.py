"""Join-index cache — sorted-build reuse across statements.

Every sorted-build join pays an O(n log n) argsort of its build side per
execution (exec/kernels.py build_sort) even though build sides are usually
dimension tables identical across statements, generic-plan re-executions,
and dispatcher batches. This module precomputes the build side's sort
scaffolding HOST-side — (stable sort order, sorted packed keys, packing
ranges), the exact numpy mirror of build_sort — caches it in a
session-level LRU keyed by (table, version, key columns, pack bits,
layout mode, segment count/slice), and feeds it to compiled programs as
an EXTRA INPUT next to the tables (like ``$params``): program shapes are
unchanged, so generic-plan zero-recompile reuse is preserved, and any
write bumps the table version, which changes the cache key — the existing
table-version/epoch machinery IS the invalidation contract.

Eligible joins (annotate_join_index, stamped post-distribution):

- the build subtree is a bare full-table scan (optionally via PShare), or
  that scan under a plain broadcast motion — the gathered buffer's row
  order is deterministic (shard-major), so the host can mirror it;
- every build key is a plain ColumnRef onto a scanned column;
- no build-side key-validity expression (NULL-key masking would change
  the masked sort order at run time).

Everything else falls back to the in-program argsort automatically: the
join lowering looks the input up with ``.get`` and computes the sort when
the key is absent (tiled/spill step programs assemble their own inputs
and strip the annotations at intake — exec/tiled.py ``plan_tiled``).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from cloudberry_tpu.plan import expr as ex
from cloudberry_tpu.plan import nodes as N

_U64_MAX = np.uint64(0xFFFFFFFFFFFFFFFF)
_U32_MAX = np.uint32(0xFFFFFFFF)
_SIGN64 = np.uint64(1) << np.uint64(63)


@dataclass(frozen=True)
class JoinIndexSpec:
    """One eligible join's cached-index contract: the program input key
    (shared by every join wanting the same index) plus how the host
    reconstructs the build fragment's row layout."""

    key: str          # program input key ("$jix:…")
    table: str
    phys: tuple       # physical key column names, join-key order
    bits: int         # PJoin.pack_bits
    mode: str         # 'table' | 'shard' | 'gathered'
    capacity: int     # build fragment rows as traced


# ------------------------------------------------------------ numpy mirror
# of kernels.sort_key_u64 / key_ranges / pack_with_ranges / downcast32 —
# bit-exact, including uint64 wraparound and STABLE argsort tie order, so
# a cached index is indistinguishable from the in-program computation.


def _np_sort_key_u64(col: np.ndarray) -> np.ndarray:
    a = np.asarray(col)
    if a.dtype == np.bool_:
        return a.astype(np.uint64)
    if a.dtype == np.float32:
        bits = a.view(np.uint32)
        mask = np.where(bits >> np.uint32(31) != 0,
                        np.uint32(0xFFFFFFFF), np.uint32(1) << np.uint32(31))
        return (bits ^ mask).astype(np.uint64)
    if a.dtype == np.float64:
        bits = a.view(np.uint64)
        mask = np.where(bits >> np.uint64(63) != 0, _U64_MAX, _SIGN64)
        return bits ^ mask
    return a.astype(np.int64).view(np.uint64) ^ _SIGN64


def _np_index(cols: list[np.ndarray], n_rows: int, capacity: int,
              bits: int) -> dict[str, np.ndarray]:
    """(order, sorted keys, per-key lo/span) over the first ``n_rows`` of
    ``cols`` padded to ``capacity`` — the host-side build_sort."""
    return _np_index_masked(cols, np.arange(capacity) < n_rows,
                            capacity, bits)


# ------------------------------------------------------------- annotation


def annotate_join_index(plan: N.PlanNode, session) -> None:
    """Stamp every eligible PJoin with its JoinIndexSpec (``_jix``); the
    input-assembly chokepoints then feed the cached index and the join
    lowering skips the build-side argsort. A lookup through a
    direct-address table (``PJoin.direct_lookup``) sorts nothing and
    reads no index: none is shipped for it."""
    if session.config.join_filter.index_cache <= 0:
        return
    from cloudberry_tpu.exec import executor as X

    nseg = session.config.n_segments
    direct = getattr(plan, "_direct_segment", None) is not None
    for node in X.all_nodes(plan):
        if isinstance(node, N.PJoin) and not hasattr(node, "_jix") \
                and not node.direct_lookup:
            spec = _build_spec(node, session, nseg, direct)
            if spec is not None:
                node._jix = spec


def _build_spec(node: N.PJoin, session, nseg: int, direct: bool):
    from cloudberry_tpu.exec.executor import keyed_scan

    if node.build_key_valid is not None:
        return None
    build = node.build
    mode = "table"
    while isinstance(build, N.PShare):
        build = build.child
    if isinstance(build, N.PMotion):
        if build.kind != "broadcast" or build.pre_compact:
            return None
        mode = "gathered"
        build = build.child
    while isinstance(build, N.PShare):
        build = build.child
    if not isinstance(build, N.PScan) or build.table_name == "$dual":
        return None
    if keyed_scan(build) or hasattr(build, "_point_col"):
        # pruned store reads / point slices change their row set per
        # statement — the table version cannot key their layout
        return None
    try:
        t = session.catalog.table(build.table_name)
    except KeyError:
        return None
    rev = {out: p for p, out in build.column_map.items()}
    phys = []
    for k in node.build_keys:
        if not isinstance(k, ex.ColumnRef):
            return None
        p = rev.get(k.name)
        if p is None:
            return None
        phys.append(p)
    if mode == "table" and nseg > 1 and not direct \
            and t.policy.kind != "replicated":
        # distributed colocated build: the fragment is this segment's
        # shard — one index row set per segment, sharded input
        mode = "shard"
    key = (f"$jix:{build.table_name}:{','.join(phys)}:"
           f"{node.pack_bits}:{mode}")
    return JoinIndexSpec(key, build.table_name, tuple(phys),
                         node.pack_bits, mode, build.capacity)


def strip_join_index(plan: N.PlanNode) -> None:
    """Remove every join-index annotation (tiled/spill intake): step
    programs assemble their own inputs and must never trace a program
    that expects an input nobody provides."""
    from cloudberry_tpu.exec import executor as X

    for node in X.all_nodes(plan):
        if isinstance(node, N.PJoin) and hasattr(node, "_jix"):
            del node._jix


def stash_join_index(plan: N.PlanNode) -> list:
    """(node, spec) pairs for every annotated join. Tiled planning strips
    speculatively before it knows it can execute the plan — a decline
    restores these (restore_join_index) so the one-shot fallback keeps
    the cached-index optimization."""
    from cloudberry_tpu.exec import executor as X

    return [(n, n._jix) for n in X.all_nodes(plan)
            if isinstance(n, N.PJoin) and hasattr(n, "_jix")]


def restore_join_index(stash) -> None:
    for node, spec in stash:
        node._jix = spec


def jix_specs_of(plan: N.PlanNode) -> list[JoinIndexSpec]:
    """Deduped (by input key) specs of every annotated join in the plan —
    the deterministic walk input assembly and trace both rely on."""
    from cloudberry_tpu.exec import executor as X

    seen: set[str] = set()
    out = []
    for node in X.all_nodes(plan):
        spec = getattr(node, "_jix", None) \
            if isinstance(node, N.PJoin) else None
        if spec is not None and spec.key not in seen:
            seen.add(spec.key)
            out.append(spec)
    return out


# ------------------------------------------------- shared-scope LRU
# (sched/sharedcache.py): sessions over the same durable store share one
# join-index scope — a dimension table's sorted-build scaffolding is
# computed once per store version engine-wide, not once per backend.


def _cache(session):
    from cloudberry_tpu.sched import sharedcache

    scope = sharedcache.scope_for(session)
    return scope.joinindex, scope.joinindex_lock


def _cached_index(session, spec: JoinIndexSpec, segment) -> dict:
    """The spec's index arrays from the scope LRU, built on miss.
    Keyed on the table's content-stable version token
    (sharedcache.table_key — the store version for store-backed tables
    outside transactions, object uid + local version otherwise): any
    write bumps it, so stale indexes are unreachable by construction
    (the invalidation contract)."""
    from cloudberry_tpu.sched import sharedcache

    t = session.catalog.table(spec.table)
    t.ensure_loaded()
    nseg = session.config.n_segments
    # the topology-epoch token rides every shared-tier key: an index
    # laid out under a pre-cutover epoch (shard-mode arrays follow the
    # epoch's placement) can never serve after the flip
    key = (sharedcache.table_key(session, spec.table), spec.phys,
           spec.bits, spec.mode, nseg, segment,
           sharedcache.topology_token(session))
    cache, lock = _cache(session)
    with lock:
        hit = cache.pop(key, None)
        if hit is not None:
            cache[key] = hit  # LRU touch
    log = getattr(session, "stmt_log", None)
    if hit is not None:
        if log is not None:
            log.bump("join_index_hits")
        return hit
    hit = _build_index(session, spec, segment, t, nseg)
    if log is not None:
        log.bump("join_index_builds")
    limit = max(session.config.join_filter.index_cache, 1)
    with lock:
        while len(cache) >= limit:
            cache.pop(next(iter(cache)))
        cache[key] = hit
    return hit


def _build_index(session, spec: JoinIndexSpec, segment, t, nseg: int):
    if spec.mode == "shard":
        st = session.sharded_table(spec.table)
        per = [_np_index([np.asarray(st.columns[p][s]) for p in spec.phys],
                         int(st.counts[s]), st.capacity, spec.bits)
               for s in range(nseg)]
        out = {k: np.stack([d[k] for d in per]) for k in per[0]}
        return out
    if spec.mode == "gathered":
        st = session.sharded_table(spec.table)
        cols = [np.asarray(st.columns[p]).reshape(-1) for p in spec.phys]
        cap = st.capacity * nseg
        # the broadcast buffer is shard-major with each shard's rows a
        # selected prefix — mirror via a per-row validity mask folded
        # into the sort sentinel (rows past a shard's count never sort
        # into the live region)
        sel_rows = np.concatenate([np.arange(st.capacity) < st.counts[s]
                                   for s in range(nseg)])
        return _np_index_masked(cols, sel_rows, cap, spec.bits)
    # mode == 'table': the whole table (single segment / replicated), or
    # ONE shard under direct dispatch
    if segment is not None and t.policy.kind not in ("replicated",):
        st = session.sharded_table(spec.table)
        cols = [np.asarray(st.columns[p][segment]) for p in spec.phys]
        return _np_index(cols, int(st.counts[segment]), st.capacity,
                         spec.bits)
    cols = [np.asarray(t.data[p]) for p in spec.phys]
    cap = max(spec.capacity, len(cols[0]) if cols else 1, 1)
    return _np_index(cols, t.num_rows, cap, spec.bits)


def _np_index_masked(cols, sel, capacity, bits):
    """_np_index over an explicit row-validity mask (gathered buffers:
    each shard contributes a selected prefix, not one global prefix)."""
    with np.errstate(over="ignore"):
        packed = np.zeros(capacity, dtype=np.uint64)
        oob = np.zeros(capacity, dtype=np.bool_)
        ranges = []
        for c in cols:
            u = np.zeros(capacity, dtype=np.uint64)
            u[:len(c)] = _np_sort_key_u64(c)[:capacity]
            lo = np.min(np.where(sel, u, _U64_MAX))
            hi = np.max(np.where(sel, u, np.uint64(0)))
            span = np.maximum(hi - lo, np.uint64(0)) + np.uint64(1)
            ranges.append((np.uint64(lo), np.uint64(span)))
            oob = oob | (u < lo) | (u - lo >= span)
            packed = packed * span + np.clip(u - lo, np.uint64(0),
                                             span - np.uint64(1))
        packed = np.where(oob, _U64_MAX, packed)
        if bits == 32:
            masked = np.where(sel, np.where(packed == _U64_MAX, _U32_MAX,
                                            packed.astype(np.uint32)),
                              _U32_MAX)
        else:
            masked = np.where(sel, packed, _U64_MAX)
    order = np.argsort(masked, kind="stable").astype(np.int32)
    out = {"order": order, "skeys": masked[order]}
    for i, (lo, span) in enumerate(ranges):
        out[f"lo{i}"] = lo
        out[f"span{i}"] = span
    return out


# -------------------------------------------------------- input assembly


def join_index_inputs(plan: N.PlanNode, session,
                      segment=None) -> dict:
    """{input key: index arrays} for every annotated join — the single /
    direct-dispatch assembly chokepoint (exec/executor.py
    _assemble_inputs, sched/paramplan.py bind_inputs)."""
    out = {}
    for spec in jix_specs_of(plan):
        out[spec.key] = _cached_index(session, spec, segment)
    return out


def dist_join_index_inputs(plan: N.PlanNode, session):
    """(inputs, in_specs) for the distributed program: 'shard'-mode
    indexes split on the segment axis, 'table'/'gathered' replicated."""
    from jax.sharding import PartitionSpec as P

    from cloudberry_tpu.parallel.mesh import SEG_AXIS

    inputs = {}
    specs = {}
    for spec in jix_specs_of(plan):
        arrs = _cached_index(session, spec, None)
        inputs[spec.key] = arrs
        if spec.mode == "shard":
            specs[spec.key] = {
                k: P(SEG_AXIS, None) if v.ndim == 2 else P(SEG_AXIS)
                for k, v in arrs.items()}
        else:
            specs[spec.key] = {k: P() for k in arrs}
    return inputs, specs
