"""Distributed executor: the plan as ONE SPMD program over the segment mesh.

The reference executes a distributed plan as N OS processes per slice wired
by a socket interconnect (gangs + cdbmotion + ic_udpifc); here the whole
multi-segment plan is a single ``shard_map`` program over a
``jax.sharding.Mesh`` — each mesh slot is a segment, and Motion lowers to
XLA collectives on the ``seg`` axis:

- GATHER / BROADCAST → ``lax.all_gather``  (BROADCAST motion)
- HASH (redistribute) → on-device bucketing + ``lax.all_to_all``, with
  per-destination bucket capacity as flow control (ic_udpifc.c:3018 analog):
  bucket overflow is a detected error, not a drop.

Routing uses jump_consistent_hash over the same column hash as load-time
placement (session.sharded_table), so scan-colocated joins need no motion at
all — the planner relies on that (plan/distribute.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from cloudberry_tpu.columnar.batch import ColumnBatch
from cloudberry_tpu.exec import executor as X
from cloudberry_tpu.exec import kernels as K
from cloudberry_tpu.exec.expr_compile import compile_expr
from cloudberry_tpu.obs import programs as PG
from cloudberry_tpu.parallel.mesh import SEG_AXIS, segment_mesh
from cloudberry_tpu.plan import nodes as N
from cloudberry_tpu.utils import hashing
from cloudberry_tpu.utils.faultinject import fault_point


def prepare_dist_inputs(plan: N.PlanNode, session, names=None):
    """(inputs, in_specs) for every scanned table: partitioned columns as
    (nseg, cap) arrays split on the seg axis, replicated tables whole.
    ``names`` overrides the table set (tiled execution keeps the streamed
    table out of the resident inputs)."""
    inputs = {}
    in_specs = {}
    if plan is not None:
        # cached sorted-build join indexes ride as extra program inputs
        # (exec/joinindex.py): 'shard'-mode arrays split on the segment
        # axis, whole-table/gathered ones replicated. Tiled callers pass
        # plan=None and the join lowering falls back to its in-program
        # argsort.
        from cloudberry_tpu.exec.joinindex import dist_join_index_inputs

        jix_in, jix_specs = dist_join_index_inputs(plan, session)
        inputs.update(jix_in)
        in_specs.update(jix_specs)
    if names is None:
        names = sorted({s.table_name for s in X.scans_of(plan)})
    for name in names:
        st = session.sharded_table(name)
        if st.replicated:
            inputs[name] = {"$cols": dict(st.columns),
                            "$nrows": np.full(1, st.counts[0])}
            in_specs[name] = {"$cols": {c: P() for c in st.columns},
                              "$nrows": P()}
        else:
            inputs[name] = {"$cols": dict(st.columns),
                            "$nrows": st.counts}
            in_specs[name] = {"$cols": {c: P(SEG_AXIS, None)
                                        for c in st.columns},
                              "$nrows": P(SEG_AXIS)}
    return inputs, in_specs


def wire_packed(session) -> bool:
    """Whether a Motion ships ONE packed buffer (kernels.wire_layout) or
    one collective per column: what the lowerer is built with, and what
    EXPLAIN ANALYZE's launch counts describe."""
    return session.config.interconnect.packed_wire


def dist_lowering(session):
    """``(mesh, lowerer)``: what every distributed program of ``session``
    is lowered with — the segment mesh over the live devices, and the
    DistLowerer constructor with the segment count, the Motion transport
    and the wire format bound. The host topology re-derives from the
    LIVE device list here, so an epoch flip (expand/shrink/failover)
    re-splits collectives the moment the new epoch's first program
    compiles (the shared cache tier keys programs by topology epoch, so
    a stale split never serves post-cutover), and the one-shot, tiled
    and EXPLAIN ANALYZE programs lower a Motion to the same collectives:
    a plan whose motions carry two-level stamps would otherwise pay
    their padding while shipping flat."""
    from cloudberry_tpu.parallel.transport import (hier_topology,
                                                   make_transport)

    nseg = session.config.n_segments
    live_ids = getattr(session, "_live_device_ids", None)
    ic = session.config.interconnect
    tx = make_transport(ic.backend, nseg, chunks=ic.ring_chunks,
                        topo=hier_topology(session.config, nseg, live_ids))
    return segment_mesh(nseg, live_ids), functools.partial(
        DistLowerer, nseg=nseg, tx=tx, packed=wire_packed(session))


def compile_distributed(plan: N.PlanNode, session, param_keys=None,
                        instrument=False):
    """Build the jitted SPMD program once; reusable across calls (the
    prepared-statement analog — inputs are re-prepared per call from the
    session's sharded-table cache). ``param_keys`` (generic plans,
    sched/paramplan.py) adds a replicated "$params" input: "$prm<slot>"
    scalars every segment reads identically, so literal rebinding never
    retraces the SPMD program. ``instrument=True`` (EXPLAIN ANALYZE's
    pipeline path) records per-node row counts into the existing
    replicated stats channel — partitioned-node counts psum across
    segments, replicated nodes report segment 0's — so the instrumented
    program is this same entry point's program, not a side path's."""
    from cloudberry_tpu.obs.capacity import motion_wire_bytes

    mesh, lowerer = dist_lowering(session)
    inputs, in_specs = prepare_dist_inputs(plan, session)
    if param_keys:
        in_specs["$params"] = {k: P() for k in param_keys}
    X.count_compile(session)

    def seg_fn(tables):
        low = lowerer(tables, params=tables.get("$params"),
                      count_rows=instrument)
        cols, sel = low.lower(plan)
        out = {f.name: cols[f.name][None] for f in plan.fields}
        # reduce checks to replicated scalars (any segment tripped) so
        # every HOST can read them — per-seg shards are not addressable
        # across processes on a multi-host mesh
        with jax.named_scope("checks"):
            checks = {
                k: low.tx.psum(jnp.asarray(v).astype(jnp.int32),
                               SEG_AXIS) > 0
                for k, v in low.checks.items()}
        # motion stats (already pmax-reduced, replicated): the observed
        # per-destination bucket demand each redistribute actually saw —
        # the capacity-ladder promotion reads these host-side
        return out, sel[None], checks, dict(low.stats)

    fn = PG.jit(_shard_map(seg_fn, mesh, (in_specs,),
                           _out_specs_like(plan)),
                X.node_titles(plan), "distributed")
    # what one launch hands the program and puts on its motions' wires
    # follows from the shapes the program is compiled for: reckoned here,
    # once, for execute_distributed's counters
    fn.input_bytes = scanned_input_bytes(plan, inputs)
    fn.wire_bytes = motion_wire_bytes(plan)
    fn.join_shapes = X.join_shapes(plan)
    return fn


def record_motion_stats(plan: N.PlanNode, stats: dict,
                        session=None) -> None:
    """Pin each redistribute's observed global bucket demand onto its
    motion node (``_observed_bucket``): on overflow the retry promotes
    straight to the rung that fits instead of probing rung by rung.
    Runtime-filter row counts pin the same way (``_jf_pre``/``_jf_post``),
    and the per-destination demand vector pins as ``_seg_rows`` with its
    derived max/mean ``_skew_ratio`` — the skew telemetry EXPLAIN
    ANALYZE's motion annotations render. With a ``session``, skew also
    feeds the engine registry (obs histograms + the ``skew_events``
    counter past ``config.obs.skew_ratio``). Engine-counter accumulation
    for join filters lives in record_jf_counters — called separately,
    only once raise_checks passed."""
    import re

    # keys name their node by its ordinal in the plan (X.numbered_nodes):
    # a program served from the rung cache was traced off a
    # signature-equal plan, whose ordinals are this plan's. The kind
    # filters keep an ordinal from another plan shape off the wrong node.
    nodes = X.numbered_nodes(plan)
    motions: dict = {}      # id -> redistribute this launch reported on

    def motion_of(key):
        n = X.keyed_node(nodes, key)
        if isinstance(n, N.PMotion) and n.kind == "redistribute":
            return motions.setdefault(id(n), n)
        return None

    for key, v in stats.items():
        if key.startswith("required bucket "):
            node = motion_of(key)
            if node is not None:
                node._observed_bucket = int(np.asarray(v))
        elif key.startswith("required host bucket "):
            node = motion_of(key)
            if node is not None:
                node._observed_host_bucket = int(np.asarray(v))
        elif key.startswith("seg rows "):
            node = motion_of(key)
            if node is not None:
                node._seg_rows = np.asarray(v).astype(np.int64)
        else:
            m = re.match(r"join_filter (pre|post) ", key)
            node = X.keyed_node(nodes, key) if m is not None else None
            if isinstance(node, N.PRuntimeFilter):
                which = "_jf_pre" if m.group(1) == "pre" else "_jf_post"
                setattr(node, which, int(np.asarray(v)))
    _record_skew(motions.values(), session)


def _record_skew(motions, session) -> None:
    """Per-motion skew observability (the capacity plane, ISSUE 12):
    from each redistribute's per-destination demand vector derive the
    max/mean skew ratio, record rows-per-segment and wire-bytes-per-
    segment histograms, and bump ``skew_events`` when a shuffle crosses
    ``config.obs.skew_ratio`` — hot destinations are the binding
    constraint the rung ladder pays for, and they must be visible in
    ``meta "metrics"`` before they become overflow retries."""
    from cloudberry_tpu.obs.capacity import _wire_row_bytes

    log = getattr(session, "stmt_log", None) if session is not None \
        else None
    threshold = float(session.config.obs.skew_ratio) \
        if session is not None else 0.0
    for node in motions:
        rows = getattr(node, "_seg_rows", None)
        if rows is None:
            continue
        total = int(rows.sum())
        if total <= 0 or rows.shape[0] == 0:
            node._skew_ratio = None
            continue
        mean = total / rows.shape[0]
        ratio = float(rows.max() / mean)
        node._skew_ratio = ratio
        # per-HOST skew next to per-segment: a host-skewed shuffle is
        # exactly the case the two-level exchange makes WORSE (one host
        # pair's block rung pads every host pair), so it must alarm in
        # the same place segment skew does
        hrows = _host_rows(rows, session)
        if hrows is not None:
            node._host_rows = hrows
            node._host_skew_ratio = float(
                hrows.max() / (total / hrows.shape[0]))
        else:
            node._host_skew_ratio = None
        if log is None or not log.obs_enabled:
            continue
        reg = log.registry
        reg.observe("motion_skew_ratio", ratio)
        reg.observe("motion_seg_rows_max", int(rows.max()))
        reg.observe("motion_seg_wire_bytes_max",
                    int(rows.max()) * _wire_row_bytes(node))
        if threshold > 0 and ratio >= threshold:
            log.bump("skew_events")
        if node._host_skew_ratio is not None:
            reg.observe("motion_host_skew_ratio", node._host_skew_ratio)
            reg.observe("motion_host_rows_max", int(hrows.max()))
            if threshold > 0 and node._host_skew_ratio >= threshold:
                log.bump("host_skew_events")


def _host_rows(seg_rows: np.ndarray, session) -> np.ndarray | None:
    """Per-destination-HOST row demand from the per-segment vector —
    None on single-host (or host-ambiguous) meshes. Uses the same
    HostTopology derivation the motion layer splits over (including the
    CBTPU_FORCE_HOSTS simulation), so the telemetry describes the links
    the bytes would actually cross."""
    from cloudberry_tpu.parallel.mesh import host_topology

    try:
        topo = host_topology(
            seg_rows.shape[0],
            getattr(session, "_live_device_ids", None)
            if session is not None else None)
    except Exception:
        return None
    if topo.n_hosts < 2:
        return None
    out = np.zeros(topo.n_hosts, dtype=np.int64)
    for h, segs in enumerate(topo.segs_by_host):
        out[h] = sum(int(seg_rows[s]) for s in segs
                     if s < seg_rows.shape[0])
    return out


def record_jf_counters(stats: dict, log) -> None:
    """Accumulate runtime-filter row counts on the engine counters
    (jf_rows_in / jf_rows_out) — the observed-reduction observability
    bench.py and ic_bench --join-filter read. Call AFTER raise_checks:
    an overflowed attempt that grow_expansion retries must not count its
    probe rows twice."""
    import re

    if log is None:
        return
    for key, v in stats.items():
        m = re.search(r"join_filter (pre|post)", key)
        if m is not None:
            log.bump("jf_rows_in" if m.group(1) == "pre"
                     else "jf_rows_out", int(np.asarray(v)))


def execute_distributed(plan: N.PlanNode, session, fn=None, *,
                        inputs_plan: N.PlanNode | None = None,
                        params: dict | None = None,
                        mode: str = "dist") -> ColumnBatch:
    """One launch of a distributed program, split where the time goes
    (obs/trace.py, children of ``launch``, under the one-shot path's
    names): ``inputs`` (the scanned tables' shards — HOST arrays, handed
    to every launch — and the ``$params`` of a generic plan), ``dispatch``
    until the call returns (the first call of a shape traces and
    compiles inside it), ``device-wait`` for the first blocking read
    (the selection mask: it holds the device's own time),
    ``motion-stats`` for the host's reading of the program's motion
    statistics and checks (a blocking read a leaf) and the feedback
    fold, ``fetch`` for the result columns, read leaf by leaf, and the
    batch. ``plan`` is the plan ``fn`` was traced from; a generic plan's
    rebind passes its freshly bound ``inputs_plan`` (same shapes, this
    send's scans) and ``params``."""
    from cloudberry_tpu.obs import trace as OT
    from cloudberry_tpu.plan.feedback import fold_plan

    if fn is None:
        fn = compile_distributed(plan, session)
    log = getattr(session, "stmt_log", None)
    with OT.stage("inputs", "launch_seconds", host=True, mode=mode):
        inputs, _ = prepare_dist_inputs(inputs_plan or plan, session)
        if params:
            inputs["$params"] = dict(params)
    fault_point("dist_execute_start")
    with OT.stage("dispatch", "launch_seconds", mode=mode):
        cols, sel, checks, stats = fn(inputs)
    with OT.stage("device-wait", "launch_seconds"):
        # every segment computed the (gathered) final result; read the
        # first shard THIS HOST can address (on a multi-host mesh,
        # segment 0 may live on another process — any local copy is
        # identical post-gather)
        host_sel = _local_row(sel)
    with OT.stage("motion-stats", "launch_seconds"):
        # one blocking read a leaf, made here and nowhere after
        stats = {k: np.asarray(v) for k, v in stats.items()}
        checks = {k: np.asarray(v) for k, v in checks.items()}
        record_motion_stats(plan, stats, session=session)
        X.raise_checks(checks)
        record_jf_counters(stats, log)
        fold_plan(session, plan)
    with OT.stage("fetch", "launch_seconds") as st:
        host_cols = {k: _local_row(v) for k, v in cols.items()}
        batch = X.make_batch(plan, host_cols, host_sel)
        reads = 1 + len(stats) + len(checks) + len(cols)
        st.args["columns"] = len(batch.columns)
        st.args["bytes"] = sum(int(a.nbytes)
                               for a in batch.columns.values())
        st.args["reads"] = reads
    if log is not None:
        log.bump("launch_dist")
        log.bump("launch_d2h_reads", reads)
        log.bump("dist_input_bytes", fn.input_bytes)
        log.bump("motion_wire_bytes", fn.wire_bytes)
        X.count_join_shapes(log, fn.join_shapes)
    return batch


def scanned_input_bytes(plan: N.PlanNode, inputs: dict) -> int:
    """Host bytes of ``inputs`` the program keeps: the shards of the
    columns (and validity masks) its scans name, and each table's row
    counts. ``jax.jit`` drops an argument the program never reads
    before it is transferred, so a table's other columns cost nothing."""
    used: dict = {}
    for s in X.scans_of(plan):
        used.setdefault(s.table_name, set()).update(
            list(s.column_map) + [f"$nn:{p}" for p in s.mask_map])
    total = 0
    for name, cols in used.items():
        t = inputs.get(name)
        if t is None:
            continue
        total += int(np.asarray(t["$nrows"]).nbytes)
        total += sum(int(t["$cols"][c].nbytes) for c in cols
                     if c in t["$cols"])
    return total


def _local_row(v) -> np.ndarray:
    if hasattr(v, "is_fully_addressable") and not v.is_fully_addressable:
        shards = v.addressable_shards
        if not shards:  # guarded up front by segment_mesh's host check
            raise X.ExecError(
                "this host owns no segment in the mesh and cannot read "
                "the result")
        return np.asarray(shards[0].data)[0]
    return np.asarray(v)[0]


def _out_specs_like(plan: N.PlanNode):
    cols_spec = {f.name: P(SEG_AXIS) for f in plan.fields}
    # checks and motion stats reduce to replicated scalars (P()) —
    # readable on every host
    return (cols_spec, P(SEG_AXIS), P(), P())


def _shard_map(f, mesh, in_specs, out_specs):
    """The engine's one shard_map call: replication is tracked by the
    plan's own locus algebra, so jax's varying-manual-axes check is off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


class DistLowerer(X.Lowerer):
    def __init__(self, tables, nseg: int, tx=None, packed: bool = True,
                 **kw):
        super().__init__(tables, **kw)
        self.nseg = nseg
        # motion transport (ic_modules.c vtable analog): XLA-native
        # collectives or ppermute ring compositions
        if tx is None:
            from cloudberry_tpu.parallel.transport import XlaCollectives

            tx = XlaCollectives()
        self.tx = tx
        # packed wire format (kernels.wire_layout): one collective per
        # motion; False = legacy one-collective-per-column (parity path)
        self.packed = packed

    def record_rows(self, node: N.PlanNode, n) -> None:
        """Counts ride the replicated stats channel: the global sum for
        partitioned nodes and segment 0's count for replicated ones
        (post-gather nodes must count once, not nseg times) —
        instrument_counts picks between them host-side."""
        is_seg0 = jnp.equal(jax.lax.axis_index(SEG_AXIS), 0)
        self.stats[f"node_rows_sum (node {self.ref(node)})"] = \
            self.tx.psum(n, SEG_AXIS)
        self.stats[f"node_rows_one (node {self.ref(node)})"] = \
            self.tx.psum(jnp.where(is_seg0, n, 0), SEG_AXIS)

    def scan(self, node: N.PScan):
        if node.table_name == "$dual":
            return {}, jnp.ones((1,), dtype=jnp.bool_)
        t = self.tables[node.table_name]
        cols = {}
        for phys, out in list(node.column_map.items()) + [
                (f"$nn:{p}", o) for p, o in node.mask_map.items()]:
            arr = t["$cols"][phys]
            if arr.ndim == 2:      # partitioned: (1, cap) block inside smap
                arr = arr[0]
            if arr.shape[0] < node.capacity:
                arr = jnp.zeros((node.capacity,), dtype=arr.dtype)
            cols[out] = arr
        n = t["$nrows"].reshape(())
        sel = jnp.arange(node.capacity) < n
        return cols, sel

    def global_any(self, x):
        local = jnp.any(x).astype(jnp.int32)
        return self.tx.psum(local, SEG_AXIS) > 0

    def runtime_filter(self, node):
        """Semi-join pushdown (nodeRuntimeFilter.c analog) before the
        probe's redistribute. mode='exact': all-gather the PACKED u64
        build keys (keys only — the cheapest complete collective) and
        sorted-membership-test the probe rows. mode='digest': all-gather
        only a per-key u64 min/max + bloom-bitmap digest (one tiny
        collective regardless of build size; bloom false positives let
        extra rows through, the join stays exact). Packing ranges reduce
        globally so every segment packs identically."""
        if getattr(node, "mode", "exact") == "digest":
            return self._digest_filter(node)
        pcols, psel = self.lower(node.child)
        bcols, bsel = self.lower_shared(node.build)
        bkeys = [self.expr(k, bcols) for k in node.build_keys]
        pkeys = [self.expr(k, pcols) for k in node.probe_keys]
        ranges = []
        for k in bkeys:
            u = K.sort_key_u64(k)
            lo = jnp.min(jnp.where(bsel, u, K._U64_MAX))
            hi = jnp.max(jnp.where(bsel, u, jnp.uint64(0)))
            lo = jnp.min(self.tx.all_gather(lo[None], SEG_AXIS))
            hi = jnp.max(self.tx.all_gather(hi[None], SEG_AXIS))
            span = jnp.maximum(hi - lo, jnp.uint64(0)) + jnp.uint64(1)
            ranges.append((lo, span))
        kb = jnp.where(bsel, K.pack_with_ranges(bkeys, ranges), K._U64_MAX)
        kp = K.pack_with_ranges(pkeys, ranges)
        big = K._U64_MAX
        if node.pack_bits == 32:
            # stats-proven narrow keys halve the all-gathered bytes too
            kb, kp, big = K.downcast32(kb), K.downcast32(kp), K._U32_MAX
        kb_all = self.tx.all_gather(kb, SEG_AXIS)
        kb_sorted = jax.lax.sort(kb_all, is_stable=False)
        pos = jnp.clip(jnp.searchsorted(kb_sorted, kp), 0,
                       kb_sorted.shape[0] - 1)
        hit = (kb_sorted[pos] == kp) & (kp != big)
        self._filter_stats(node, psel, psel & hit)
        return pcols, psel & hit

    def _digest_filter(self, node):
        """Digest-mode runtime filter: each segment builds a local digest
        — per key column the u64 [lo, hi] (as u32 word pairs) plus the
        bloom bitmap words — ships it in ONE all_gather, then reduces
        (min/max/OR) so every segment holds the GLOBAL digest. Probe rows
        outside any key's range or absent from the bloom drop before the
        shuffle; min/max is exact, bloom errs only toward keeping rows."""
        pcols, psel = self.lower(node.child)
        bcols, bsel = self.lower_shared(node.build)
        bus = [K.sort_key_u64(self.expr(k, bcols))
               for k in node.build_keys]
        pus = [K.sort_key_u64(self.expr(k, pcols))
               for k in node.probe_keys]
        bits = K.bloom_bits_pow2(node.bloom_bits)
        kk = max(node.bloom_k, 1)

        def u64_words(x):
            return jnp.stack([(x & jnp.uint64(0xFFFFFFFF)),
                              (x >> jnp.uint64(32))]).astype(jnp.uint32)

        parts = []
        for u in bus:
            lo = jnp.min(jnp.where(bsel, u, K._U64_MAX))
            hi = jnp.max(jnp.where(bsel, u, jnp.uint64(0)))
            parts += [u64_words(lo), u64_words(hi)]
        parts.append(K.bloom_build(bus, bsel, bits, kk))
        digest = jnp.concatenate(parts)            # (4·nkeys + bits/32,)
        D = digest.shape[0]
        topo = getattr(self.tx, "hier_topo", None)
        if topo is not None and self.nseg // topo.n_hosts > 1:
            # two-level digest: fold the HOST's digests locally (min/
            # max/OR are order-insensitive-exact, so the fold is
            # bit-identical to the flat reduction) and exchange ONE
            # combined digest per host over DCN — the "one partial per
            # host instead of one per segment" motion for digests
            S = self.nseg // topo.n_hosts
            local = self.tx.intra_all_gather(digest, SEG_AXIS) \
                .reshape(S, D)
            host_digest = _digest_fold(local, len(bus))
            gathered = self.tx.host_ring_exchange(host_digest, SEG_AXIS)
        else:
            # ONE tiny collective for the whole digest (tiled all_gather
            # concatenates: reshape back to per-segment rows)
            gathered = self.tx.all_gather(digest, SEG_AXIS) \
                .reshape(self.nseg, D)

        def seg_u64(col0):
            w = gathered[:, col0:col0 + 2].astype(jnp.uint64)
            return w[:, 0] | (w[:, 1] << jnp.uint64(32))

        hit = psel
        for i, u in enumerate(pus):
            glo = jnp.min(seg_u64(4 * i))
            ghi = jnp.max(seg_u64(4 * i + 2))
            hit = hit & (u >= glo) & (u <= ghi)
        off = 4 * len(bus)
        bloom = gathered[0, off:]
        for s in range(1, int(gathered.shape[0])):
            bloom = bloom | gathered[s, off:]
        hit = hit & K.bloom_test(bloom, pus, bits, kk)
        self._filter_stats(node, psel, psel & hit)
        return pcols, psel & hit

    def _filter_stats(self, node, pre, post):
        """Replicated observability: global probe rows before/after the
        filter (psum over segments) — the host pins them on the plan node
        (record_motion_stats) for EXPLAIN ANALYZE consumers, bench.py's
        join_filter record, and ic_bench --join-filter."""
        self.stats[f"join_filter pre (node {self.ref(node)})"] = self.tx.psum(
            jnp.sum(pre.astype(jnp.int32)), SEG_AXIS)
        self.stats[f"join_filter post (node {self.ref(node)})"] = self.tx.psum(
            jnp.sum(post.astype(jnp.int32)), SEG_AXIS)

    def motion(self, node: N.PMotion):
        cols, sel = self.lower_shared(node.child)
        return self._motion(node, cols, sel)

    def _motion(self, node: N.PMotion, cols, sel):
        if node.pre_compact:
            cols, sel, n = K.compact(cols, sel, node.pre_compact)
            self.checks[
                f"pre-gather compaction truncated rows {self.label(node)}: "
                "local top-N emitted more than its limit"] = \
                n > node.pre_compact
        if node.kind in ("gather", "broadcast"):
            if self.packed and cols:
                # one collective for the whole row set: every column plus
                # the validity mask rides ONE (cap, W) uint32 buffer
                layout = K.wire_layout({n: c.dtype
                                        for n, c in cols.items()})
                buf = K.pack_wire(cols, sel, layout)
                recv = self.tx.all_gather(buf, SEG_AXIS)
                return K.unpack_wire(recv, layout)
            out = {n: self.tx.all_gather(c, SEG_AXIS)
                   for n, c in cols.items()}
            osel = self.tx.all_gather(sel, SEG_AXIS)
            return out, osel
        if node.kind == "redistribute":
            return self._redistribute(node, cols, sel)
        raise X.ExecError(f"motion kind {node.kind}")

    def _use_hier(self, node: N.PMotion) -> bool:
        """Two-level exchange for this redistribute? Needs the
        hierarchical transport (topology gate passed at compile), the
        planner's host stamps, agreement between the stamped and live
        host grouping (an epoch flip between plan and compile replans —
        this is the belt-and-braces), the packed wire, and u32-address-
        able slots (the route-word contract)."""
        topo = getattr(self.tx, "hier_topo", None)
        return (self.packed and topo is not None
                and node.host_bucket_cap > 0
                and node.hier_hosts == topo.n_hosts
                and self.nseg % topo.n_hosts == 0
                and self.nseg * node.bucket_cap < 1 << 31)

    def _host_combine(self, node: N.PMotion, cols, sel):
        """Host-local combine of pre-aggregable motion inputs (agg
        partials): gather the HOST's rows over ICI (packed wire), merge
        partials by group key with the stamped order-insensitive-exact
        merge funcs, and keep the combined rows on ONE segment per host
        — the following exchange then ships one partial per (host,
        group) over DCN instead of one per (segment, group). Every
        segment of the host computes the identical combine; the lane-0
        selection mask is what de-duplicates, so no extra collective."""
        key_names, merges = node.combine_spec
        layout = K.wire_layout({n: c.dtype for n, c in cols.items()})
        buf = K.pack_wire(cols, sel, layout)
        hb = self.tx.intra_all_gather(buf, SEG_AXIS)     # (S*cap, W)
        hcols, hsel = K.unpack_wire(hb, layout)
        specs = [K.AggSpec(func, name) for name, func in merges]
        vals = {name: hcols[name] for name, _ in merges}
        out_keys, out_aggs, out_sel, _ = K.group_aggregate(
            {k: hcols[k] for k in key_names}, vals, specs, hsel,
            out_capacity=hb.shape[0])
        out = dict(out_keys)
        out.update(out_aggs)
        # group_aggregate widens some outputs (counts to int64); the
        # motion's schema is the contract — restore each column's dtype
        out = {n: v.astype(cols[n].dtype) for n, v in out.items()}
        S = self.nseg // node.hier_hosts
        t = jax.lax.axis_index(SEG_AXIS) % S
        return out, out_sel & (t == 0)

    def _redistribute(self, node: N.PMotion, cols, sel):
        if self._use_hier(node) and node.host_combine \
                and node.combine_spec and cols:
            cols, sel = self._host_combine(node, cols, sel)
        nseg, B = self.nseg, node.bucket_cap
        keys = [compile_expr(k)(cols) for k in node.hash_keys]
        h = hashing.hash_columns_jnp(keys)
        dest = hashing.jump_consistent_hash_jnp(h, nseg)
        dest = jnp.where(sel, dest, nseg)  # invalid rows → dropped bucket

        counts = jax.ops.segment_sum(sel.astype(jnp.int32), dest,
                                     num_segments=nseg + 1)[:nseg]
        self.checks[
            f"redistribute overflow: a destination bucket exceeded capacity "
            f"{B} {self.label(node)}; raise "
            f"config.interconnect.capacity_factor"] = (counts > B).any()
        # observed global bucket demand (replicated): the host reads it
        # after the run so an overflow promotes DIRECTLY to the capacity
        # rung that fits — one retry, not a probe up the ladder
        self.stats[f"required bucket (node {self.ref(node)})"] = \
            self.tx.pmax(jnp.max(counts), SEG_AXIS)
        # per-destination GLOBAL demand (replicated vector): the same
        # psum the rung adaptation rides, promoted to skew telemetry —
        # the host derives rows-per-segment / wire-bytes-per-segment
        # skew ratios (max/mean) from it (record_motion_stats)
        self.stats[f"seg rows (node {self.ref(node)})"] = \
            self.tx.psum(counts, SEG_AXIS)

        # rows sorted by destination, ties in position order; slot (d, r)
        # of the send buffer takes destination d's r-th row
        src, filled = K.bucket_slots(K.bucket_argsort(dest, nseg), counts, B)

        if self.packed and cols:
            # pack once, gather rows into their destination buckets,
            # ship ONE (nseg, B, W) buffer; unfilled slots are all-zero,
            # which unpacks as invalid — the validity mask needs no
            # separate collective
            layout = K.wire_layout({n: c.dtype for n, c in cols.items()})
            pbuf = K.pack_wire(cols, sel, layout)
            buf = jnp.where(filled[:, None], pbuf[src], jnp.uint32(0))
            if self._use_hier(node):
                # two-level exchange: intra-host re-bucket by dest host,
                # ONE aggregated DCN hop at the host rung, intra-host
                # scatter — bit-identical recv buffer by construction
                HB = node.host_bucket_cap
                recv, hostdem = self.tx.hier_all_to_all(
                    buf.reshape(nseg, B, layout.width), SEG_AXIS, HB)
                self.checks[
                    f"host bucket overflow: a host-pair block exceeded "
                    f"capacity {HB} {self.label(node)}; the two-level "
                    "retry promotes the host rung"] = (hostdem > HB).any()
                # observed host-pair demand (replicated): the host rung
                # ladder's one-retry promotion feed, like bucket_cap's
                self.stats[f"required host bucket (node {self.ref(node)})"] = \
                    self.tx.pmax(jnp.max(hostdem), SEG_AXIS)
            else:
                recv = self.tx.all_to_all(
                    buf.reshape(nseg, B, layout.width), SEG_AXIS)
            return K.unpack_wire(recv.reshape(nseg * B, layout.width),
                                 layout)

        out = {}
        for name, c in cols.items():
            buf = jnp.where(filled, c[src], jnp.zeros((), dtype=c.dtype))
            shaped = buf.reshape(nseg, B)
            recv = self.tx.all_to_all(shaped, SEG_AXIS)
            out[name] = recv.reshape(nseg * B)
        recv_sel = self.tx.all_to_all(filled.reshape(nseg, B),
                                      SEG_AXIS)
        return out, recv_sel.reshape(nseg * B)


def _digest_fold(rows: "jnp.ndarray", nkeys: int) -> "jnp.ndarray":
    """Combine (P, D) stacked runtime-filter digests into one (D,)
    digest: per key the u64 [lo, hi] fold (min/max over the u32 word
    pairs) and the bitwise OR of the bloom words. Order-insensitive and
    exact — the host-local fold produces the same global digest the
    flat per-segment reduction would."""
    def col_u64(c0):
        w = rows[:, c0:c0 + 2].astype(jnp.uint64)
        return w[:, 0] | (w[:, 1] << jnp.uint64(32))

    def u64_words(x):
        return jnp.stack([(x & jnp.uint64(0xFFFFFFFF)),
                          (x >> jnp.uint64(32))]).astype(jnp.uint32)

    parts = []
    for i in range(nkeys):
        parts.append(u64_words(jnp.min(col_u64(4 * i))))
        parts.append(u64_words(jnp.max(col_u64(4 * i + 2))))
    off = 4 * nkeys
    bloom = rows[0, off:]
    for p in range(1, int(rows.shape[0])):
        bloom = bloom | rows[p, off:]
    parts.append(bloom)
    return jnp.concatenate(parts)


def instrument_counts(plan: N.PlanNode, stats: dict) -> dict:
    """Host-side per-node counts (by ``id(node)``, for this process's
    renderers) from an instrumented program's stats, whose keys name
    nodes by ordinal: pick the cross-segment sum for partitioned nodes,
    segment 0's count
    for replicated ones."""
    import re

    sums, ones = {}, {}
    for key, v in stats.items():
        m = re.search(r"node_rows_(sum|one) \(node (\d+)\)", key)
        if m is None:
            continue
        (sums if m.group(1) == "sum" else ones)[int(m.group(2))] = \
            int(np.asarray(v))
    out = {}
    for o, n in enumerate(X.numbered_nodes(plan)):
        if o not in sums:
            continue
        if n.sharding is not None and n.sharding.is_partitioned:
            out[id(n)] = sums[o]
        else:
            out[id(n)] = ones.get(o, sums[o])
    return out
