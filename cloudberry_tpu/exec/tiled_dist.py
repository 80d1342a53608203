"""Distributed tiled (out-of-core) execution — spill on the segment mesh.

The reference spills operator state per segment process (workfile_mgr.c,
nodeHash.c's increase-nbatch discipline) while Motion keeps flowing between
slices. The XLA translation (exec/tiled.py rationale) moves the spill
boundary to plan time; HERE it moves onto the mesh: when an
admission-rejected plan is distributed (n_segments > 1), the probe-side
stream is tiled PER SEGMENT and each step is one shard_map program over the
segment mesh — the plan's Motions (redistribute / runtime filters) execute
INSIDE every step as per-tile collectives:

- prelude (once): every spine join's build subtree — including its own
  motions (broadcast of small tables, build-side redistributes) — computed
  by one SPMD program; the per-segment results stay resident on device;
- step (per tile): each segment feeds tile t of ITS shard; the spine's
  redistribute motions run per tile with bucket capacity min(planned, tile)
  — a tile of T rows can never send more than T rows to one destination,
  so per-tile flow control is overflow-free whenever the planned cap was
  exact; the tile's partial aggregation merges into a per-segment
  fixed-capacity accumulator (associative partials — any tile order and
  count gives the same answer, plan/distribute.py:_split_aggs);
- finalize (once): the accumulators take the partial aggregation's place in
  the ORIGINAL distributed plan — the merge motion (gather / redistribute
  by group keys), final aggregation, and post chain run unchanged as one
  last SPMD program.

Peak device memory per segment is the admitted estimate: resident builds +
one tile's working set (including its post-motion receive buffers) + the
accumulator — independent of the streamed table's size. That is the SF100
contract: shard size is bounded by host RAM, device HBM only by the budget.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from cloudberry_tpu.columnar.batch import ColumnBatch
from cloudberry_tpu.exec import bufferpool as BUF
from cloudberry_tpu.exec import executor as X
from cloudberry_tpu.exec import kernels as K
from cloudberry_tpu.exec import scanpipe as SP
from cloudberry_tpu.exec import tilepipe as TP
from cloudberry_tpu.exec.dist_executor import (_local_row, _shard_map,
                                               dist_lowering,
                                               prepare_dist_inputs)
from cloudberry_tpu.exec.resource import estimate_plan_memory
from cloudberry_tpu.exec.tiled import (_MAX_TILE, _MIN_TILE, _acc_width,
                                       _expr_dict, _merge_bytes, _out_cap,
                                       _raise_tile_checks, AdaptiveTiledMixin)
from cloudberry_tpu.obs import programs as PG
from cloudberry_tpu.parallel.mesh import SEG_AXIS
from cloudberry_tpu.parallel.topology import \
    topology_token as _topology_token
from cloudberry_tpu.plan import expr as ex
from cloudberry_tpu.plan import nodes as N
from cloudberry_tpu.utils.faultinject import fault_point
from cloudberry_tpu.plan.distribute import (_all_exprs, _finalize_project,
                                            _split_aggs)


@dataclass
class _DistTileShape:
    """Everything the rewrite discovered about the distributed plan."""

    root: N.PlanNode                 # finalize program root (whole plan)
    replace_node: N.PlanNode         # node the accumulator stands in for
    partial_plan: N.PAgg             # per-tile partial aggregation
    merge_motion: Optional[N.PMotion]  # motion above the partial (case A)
    final_agg: Optional[N.PAgg]      # merge aggregation (case A)
    spine: list[N.PlanNode]          # partial.child .. just above the stream
    stream: N.PScan                  # the tiled per-segment scan
    builds: list[N.PlanNode]         # prelude-computed subtrees
    stream_rows: int = 0             # max per-segment shard rows
    merge_specs: list = field(default_factory=list)
    group_names: list = field(default_factory=list)
    g_cap: int = 0                   # per-segment accumulator capacity
    max_groups: int = 0              # hard ceiling for g_cap growth
    mode: str = "agg"
    sortnode: Optional[N.PSort] = None  # topn/sort: the (synthetic) sort
    post: list = field(default_factory=list)  # topn: chain above spine
    post_above: list = field(default_factory=list)  # sort: above the sort
    winnode: Optional[N.PWindow] = None  # window: BOTTOM of the stack
    n_ckeys: int = 0                     # window: chunk-key count


def plan_tiled_dist(plan: N.PlanNode, session) -> Optional["DistTiledExecutable"]:
    """Re-plan an admission-rejected DISTRIBUTED statement for tiled
    execution over the segment mesh. None when the plan shape or the
    budget cannot support it."""
    if not session.config.resource.enable_spill:
        return None
    if getattr(plan, "_direct_segment", None) is not None:
        return None
    shape = _analyze_dist(plan, session)
    if shape is None:
        return None

    # whole-run growth marks belong to the untiled attempt; the tiled
    # adaptive loop re-learns spine buffer sizes per tile (builds keep
    # theirs — the prelude still computes whole builds)
    for node in shape.spine:
        if isinstance(node, N.PJoin) and hasattr(node, "_min_out_cap"):
            del node._min_out_cap
    # join-index inputs are a one-shot-executor feature (exec/joinindex):
    # tiled step programs assemble their own inputs, so drop the
    # annotations and let joins argsort in-program — speculatively: a
    # decline below restores them for the one-shot fallback
    from cloudberry_tpu.exec.joinindex import (restore_join_index,
                                               stash_join_index,
                                               strip_join_index)

    jix_stash = stash_join_index(plan)
    strip_join_index(plan)

    if shape.mode == "agg":
        from cloudberry_tpu.plan.cost import estimate_rows

        try:
            est_groups = estimate_rows(shape.partial_plan, session.catalog)
        except Exception:
            est_groups = 1024
        shape.g_cap = int(min(shape.max_groups,
                              max(1024, 4 * int(est_groups) + 1)))
        if not shape.group_names:
            shape.g_cap = 1

    budget = session.config.resource.query_mem_bytes
    tile_rows = _choose_tile_dist(shape, budget, session.config.n_segments)
    if tile_rows is None and shape.mode == "topn":
        # LIMIT+OFFSET exceeds any resident accumulator: fall back to
        # the full external sort (host RAM is the workfile) when the
        # chain above the sort can apply host-side
        s2 = _to_dist_sort(shape)
        if s2 is None:
            restore_join_index(jix_stash)
            return None
        shape = s2
        tile_rows = _choose_tile_dist(shape, budget,
                                      session.config.n_segments)
    if tile_rows is None:
        restore_join_index(jix_stash)
        return None
    cls = {"topn": DistTopNTiledExecutable,
           "sort": DistSortTiledExecutable,
           "window": DistWindowTiledExecutable,
           "agg": DistTiledExecutable}[shape.mode]
    return cls(shape, session, tile_rows, budget)


def _to_dist_sort(shape: _DistTileShape) -> Optional[_DistTileShape]:
    """Re-aim a topn shape at the external-sort executable."""
    from cloudberry_tpu.exec.tiled import host_post_ok

    post_above = shape.post[:shape.post.index(shape.sortnode)]
    if not host_post_ok(post_above, shape.sortnode.keys):
        return None
    shape.mode = "sort"
    shape.g_cap = 0
    shape.post_above = post_above
    return shape


def _analyze_dist(plan: N.PlanNode, session) -> Optional[_DistTileShape]:
    """Recognize the streamable distributed shape: post chain (projections /
    sorts / limits / gather motions) over a two-stage aggregation
    (final ← motion ← partial) — or a colocated one-stage aggregation —
    over a join/filter/redistribute spine whose probe path ends at a
    partitioned scan."""
    for e in _all_exprs(plan):
        for sub in ex.walk(e):
            if isinstance(sub, ex.SubqueryScalar):
                return None  # subquery plans scan outside the spine budget

    post: list[N.PlanNode] = []
    cur = plan
    while True:
        if isinstance(cur, (N.PProject, N.PSort, N.PLimit, N.PFilter)):
            post.append(cur)
            cur = cur.child
        elif isinstance(cur, N.PMotion) and cur.kind == "gather":
            post.append(cur)
            cur = cur.child
        else:
            break
    if isinstance(cur, N.PWindow):
        return _analyze_dist_window(plan, post, cur, session)
    if not isinstance(cur, N.PAgg):
        return _analyze_dist_topn(plan, post, session)

    if cur.mode == "final":
        final_agg = cur
        motion = final_agg.child
        if not isinstance(motion, N.PMotion) \
                or motion.kind not in ("gather", "redistribute"):
            return None
        partial = motion.child
        if not isinstance(partial, N.PAgg) or partial.mode != "partial":
            return None
        merge_specs = [K.AggSpec(call.func, name)
                       for name, call in final_agg.aggs]
        group_names = [n for n, _ in partial.group_keys]
        spine_res = _walk_spine(partial.child, session)
        if spine_res is None:
            return None
        spine, stream, builds, stream_rows = spine_res
        return _DistTileShape(
            root=plan, replace_node=partial, partial_plan=partial,
            merge_motion=motion, final_agg=final_agg, spine=spine,
            stream=stream, builds=builds, stream_rows=stream_rows,
            merge_specs=merge_specs, group_names=group_names,
            max_groups=partial.capacity)

    if cur.mode != "single":
        return None
    # one-stage colocated aggregation: build the partial/merge split the
    # single-node tiled planner uses; the accumulator IS the final state
    # per segment (groups are colocated), so finalize is just the
    # finalize-projection + post chain
    agg = cur
    try:
        partial_aggs, final_aggs, finalize = _split_aggs(agg.aggs)
    except ValueError:
        return None
    spine_res = _walk_spine(agg.child, session)
    if spine_res is None:
        return None
    spine, stream, builds, stream_rows = spine_res

    from cloudberry_tpu.exec.tiled import _AccLeaf

    partial = N.PAgg(agg.child, agg.group_keys, partial_aggs,
                     capacity=agg.capacity, mode="partial")
    partial.fields = [
        N.PlanField(n, e.dtype, _expr_dict(agg.child, e))
        for n, e in agg.group_keys
    ] + [N.PlanField(n, c.dtype, None) for n, c in partial_aggs]

    leaf = _AccLeaf()
    leaf.fields = list(partial.fields)
    leaf.sharding = agg.sharding
    fproj = _finalize_project(leaf, agg, finalize)
    fproj.sharding = agg.sharding
    if post:
        post[-1].child = fproj
        root = post[0]
    else:
        root = fproj
    merge_specs = [K.AggSpec(call.func, name) for name, call in final_aggs]
    return _DistTileShape(
        root=root, replace_node=leaf, partial_plan=partial,
        merge_motion=None, final_agg=None, spine=spine, stream=stream,
        builds=builds, stream_rows=stream_rows, merge_specs=merge_specs,
        group_names=[n for n, _ in agg.group_keys],
        max_groups=agg.capacity)


def _analyze_dist_topn(plan, post, session) -> Optional[_DistTileShape]:
    """ORDER BY + LIMIT with no aggregation: per-segment bounded top-N
    accumulators (the distributed twin of tiled.py's topn mode). Every
    segment keeps the best LIMIT+OFFSET rows of ITS stream — the global
    top-N is a subset of that union — and finalize re-runs the ORIGINAL
    plan (pre-gather compaction, gather, sorts, limits) over the
    accumulators as one SPMD program."""
    from cloudberry_tpu.exec.tiled import _topn_bound

    # motions in the chain are gathers (the walk guarantees): row-set-
    # preserving, so the limit search may cross them
    hit = _topn_bound(post, skip=(N.PMotion,))
    if hit is None:
        return _analyze_dist_sort(plan, post, session)
    sortnode, m = hit
    spine_res = _walk_spine(sortnode.child, session)
    if spine_res is None:
        return None
    spine, stream, builds, stream_rows = spine_res
    shape = _DistTileShape(
        root=plan, replace_node=sortnode.child,
        partial_plan=sortnode.child, merge_motion=None, final_agg=None,
        spine=spine, stream=stream, builds=builds,
        stream_rows=stream_rows, mode="topn", sortnode=sortnode,
        post=post)
    shape.g_cap = m
    shape.max_groups = m
    return shape


def _analyze_dist_sort(plan, post, session) -> Optional[_DistTileShape]:
    """Unbounded ORDER BY, distributed: the external-sort stream runs
    per segment (the spine's own motions execute per tile); the host
    pools every segment's rows — the gather is subsumed by collection —
    and the merge pass plus the chain above the sort apply host-side
    (tiled.py SortTiledExecutable's discipline on the mesh)."""
    sort_i = next((i for i in range(len(post) - 1, -1, -1)
                   if isinstance(post[i], N.PSort)), None)
    if sort_i is None:
        return None
    from cloudberry_tpu.exec.tiled import host_post_ok

    sortnode = post[sort_i]
    post_above = post[:sort_i]
    if not host_post_ok(post_above, sortnode.keys):
        return None
    below = sortnode.child
    while isinstance(below, N.PMotion) and below.kind == "gather":
        below = below.child
    spine_res = _walk_spine(below, session)
    if spine_res is None:
        return None
    spine, stream, builds, stream_rows = spine_res
    shape = _DistTileShape(
        root=plan, replace_node=below, partial_plan=below,
        merge_motion=None, final_agg=None, spine=spine, stream=stream,
        builds=builds, stream_rows=stream_rows, mode="sort",
        sortnode=sortnode, post=post)
    shape.post_above = post_above
    return shape


def _analyze_dist_window(plan, post, top_window,
                         session) -> Optional[_DistTileShape]:
    """Window stack, distributed: phase one is the per-segment
    external-sort stream grouped by the stack's common partition keys;
    phase two runs whole-partition chunks through the ORIGINAL plan
    (gathers lower as identity on pooled host rows) on one device —
    chunks are independent, so no mesh is needed above the stream."""
    for nd in post:
        if isinstance(nd, N.PMotion) and nd.kind == "gather":
            continue
        if isinstance(nd, N.PProject) and all(
                isinstance(e, ex.ColumnRef) for _, e in nd.exprs):
            continue
        return None
    node = top_window
    bottom = node
    common = None
    while isinstance(node, N.PWindow):
        bottom = node
        here = {repr(pk): pk for pk in node.partition_keys}
        common = here if common is None else \
            {k: v for k, v in common.items() if k in here}
        node = node.child
    if not common:
        return None
    below = bottom.child
    while isinstance(below, N.PMotion) and below.kind == "gather":
        below = below.child
    spine_res = _walk_spine(below, session)
    if spine_res is None:
        return None
    spine, stream, builds, stream_rows = spine_res
    ckeys = list(common.values())
    srt = N.PSort(below, [(ck, True) for ck in ckeys])
    srt.fields = list(below.fields)
    shape = _DistTileShape(
        root=plan, replace_node=bottom.child, partial_plan=below,
        merge_motion=None, final_agg=None, spine=spine, stream=stream,
        builds=builds, stream_rows=stream_rows, mode="window",
        sortnode=srt, post=post)
    shape.winnode = bottom
    shape.n_ckeys = len(ckeys)
    return shape


def _walk_spine(top: N.PlanNode, session):
    """Descend the probe path: filters/projections/runtime filters/joins/
    redistribute motions down to a partitioned scan (the stream)."""
    spine: list[N.PlanNode] = []
    builds: list[N.PlanNode] = []
    seen: set[int] = set()
    cur = top
    # graftlint: ignore[seam-loop] bounded plan-tree descent (one step per node; catalog lookups only) — terminates with the tree, never a tile/retry loop
    while True:
        if isinstance(cur, (N.PFilter, N.PProject)):
            spine.append(cur)
            cur = cur.child
        elif isinstance(cur, N.PRuntimeFilter):
            spine.append(cur)
            if id(cur.build) not in seen:
                seen.add(id(cur.build))
                builds.append(cur.build)
            cur = cur.child
        elif isinstance(cur, N.PMotion) and cur.kind == "redistribute":
            cur._orig_bucket_cap = cur.bucket_cap
            spine.append(cur)
            cur = cur.child
        elif isinstance(cur, N.PJoin):
            if cur.kind == "full":
                return None  # unmatched-BUILD emission is once-per-stmt
            spine.append(cur)
            if id(cur.build) not in seen:
                seen.add(id(cur.build))
                builds.append(cur.build)
            cur = cur.probe
        elif isinstance(cur, N.PScan) and cur.table_name != "$dual":
            try:
                t = session.catalog.table(cur.table_name)
            except KeyError:
                return None
            if t.policy.kind == "replicated":
                return None  # stream the partitioned side only
            st = session.sharded_table(cur.table_name)
            rows = int(st.counts.max()) if len(st.counts) else 0
            return spine, cur, builds, max(rows, 1)
        else:
            return None


def _retile_dist(shape: _DistTileShape, tile_rows: int, nseg: int) -> None:
    """Re-derive spine capacities for one tile per segment. Redistribute
    buckets are clamped to the per-tile send bound (a source segment's tile
    holds at most ``cap`` rows, so no destination bucket can exceed it);
    expansion joins keep the NDV pair-estimate floor scaled to the tile
    fraction, and runtime-grown buffers (_min_out_cap) never shrink."""
    frac = tile_rows / max(shape.stream_rows, 1)
    shape.stream.capacity = tile_rows
    shape.stream.num_rows = -2
    cap = tile_rows
    for node in reversed(shape.spine):
        if isinstance(node, N.PMotion):  # redistribute (walk guarantees)
            node.bucket_cap = max(min(node._orig_bucket_cap, cap), 8,
                                  getattr(node, "_min_bucket_cap", 0))
            node.out_capacity = node.bucket_cap * nseg
            cap = node.out_capacity
        elif isinstance(node, N.PJoin):
            bcap = _out_cap(node.build)
            est = getattr(node, "_est_pairs", None)
            floor = int(2 * est / nseg * min(frac, 1.0)) + 8 if est else 0
            floor = max(floor, getattr(node, "_min_out_cap", 0))
            if node.residual is not None:
                node.out_capacity = max(bcap + cap, floor)
            elif not node.unique_build:
                node.out_capacity = max(bcap + cap, floor)
                cap = node.out_capacity
    if shape.mode == "agg":
        shape.partial_plan.capacity = min(shape.g_cap, max(cap, 1)) \
            if shape.group_names else 1


def _finalize_bytes(shape: _DistTileShape, nseg: int) -> int:
    """Working set of the one-shot finalize program per segment: the merge
    motion's receive buffer and final aggregation both hold up to
    nseg·g_cap accumulator rows (one g_cap block from every segment); the
    colocated one-stage case never leaves the segment. topn finalize
    gathers every segment's accumulator for the global sort."""
    if shape.mode == "topn":
        rows = shape.g_cap * nseg
    else:
        rows = shape.g_cap * (nseg if shape.merge_motion is not None
                              else 1)
    return 3 * rows * _acc_width(shape)


def _choose_tile_dist(shape: _DistTileShape, budget: int,
                      nseg: int) -> Optional[int]:
    if _finalize_bytes(shape, nseg) > budget:
        return None  # no tile size can shrink the finalize program
    t = _MAX_TILE
    while t >= _MIN_TILE:
        _retile_dist(shape, t, nseg)
        est = estimate_plan_memory(shape.partial_plan).peak_bytes
        if est + _merge_bytes(shape) <= budget:
            return t
        t >>= 1
    return None


# --------------------------------------------------------------- execution


def _strip_seg(tree):
    """Per-segment block view inside shard_map: drop the leading (1,) axis
    every sharded leaf carries."""
    return jax.tree_util.tree_map(lambda a: a[0], tree)


def _add_seg(tree):
    return jax.tree_util.tree_map(lambda a: a[None], tree)


def _reduce_checks(checks: dict) -> dict:
    """Replicated any-segment-tripped scalars — readable on every host."""
    with jax.named_scope("checks"):
        return {k: jax.lax.psum(jnp.asarray(v).astype(jnp.int32),
                                SEG_AXIS) > 0
                for k, v in checks.items()}


def _motion_stats(low, motions, nseg: int):
    """Per-motion (required-bucket scalar, per-destination row vector)
    pairs off the lowerer's replicated stats channel
    (dist_executor.DistLowerer.motion psums/pmaxes them) — zeros when a
    motion lowered without the bucketed path. The skew sentinel
    (exec/tiled.py SkewSentinel) accumulates these host-side across
    tiles; the end-of-run fold publishes them to the feedback store."""
    return tuple(
        (low.stats.get(f"required bucket (node {low.ref(m)})",
                       jnp.zeros((), jnp.int32)),
         low.stats.get(f"seg rows (node {low.ref(m)})",
                       jnp.zeros((nseg,), jnp.int32)))
        for m in motions)


class DistTiledExecutable(AdaptiveTiledMixin):
    """Compiled distributed tiled statement: prelude (once) → step (per
    tile, lock-step across segments) → finalize. ``report`` records the
    spill decision for tests/EXPLAIN."""

    _what = "distributed tiled execution"

    def __init__(self, shape: _DistTileShape, session, tile_rows: int,
                 budget: int):
        self.shape = shape
        self.session = session
        self.nseg = session.config.n_segments
        self.tile_rows = tile_rows
        self.budget = budget
        self._compiled = None
        self._run_lock = threading.Lock()
        self._refresh_report()

    def _refresh_report(self) -> None:
        shape = self.shape
        _retile_dist(shape, self.tile_rows, self.nseg)
        est = estimate_plan_memory(shape.partial_plan).peak_bytes
        self.report = {
            "tiled": True,
            "distributed": True,
            "n_segments": self.nseg,
            # the topology epoch this executable was (re)built under
            # (parallel/topology.py): a report whose epoch differs from
            # the statement's pinned one is the cross-epoch-resume case
            "topology_epoch": _topology_token(self.session),
            "stream_table": shape.stream.table_name,
            "tile_rows": self.tile_rows,
            "acc_capacity": shape.g_cap,
            "est_step_bytes": est + _merge_bytes(shape),
            "est_finalize_bytes": _finalize_bytes(shape, self.nseg),
            # scan-pipeline staging charge (exec/scanpipe.py) plus the
            # dispatch window's extra in-flight (nseg, tile_rows) tiles
            # (exec/tilepipe.py) — obs/capacity.record_tiled adds both
            # to the statement's observed peak
            "est_pipeline_bytes": SP.queue_charge_bytes(
                shape.stream, self.tile_rows, self.session.config,
                nseg=self.nseg)
            + TP.window_charge_bytes(
                shape.stream, self.tile_rows, self.session.config,
                jax.default_backend(), nseg=self.nseg),
            # buffer-pool residency for the streamed table's packed
            # feed tiles (exec/bufferpool.py; host-side here —
            # shard_map owns device placement on the distributed path)
            "est_bufpool_bytes": _bufpool_charge_dist(
                self.session, shape.stream.table_name),
            "budget_bytes": self.budget,
        }

    def _over_budget(self) -> bool:
        return (self.report["est_step_bytes"] > self.budget
                or self.report["est_finalize_bytes"] > self.budget)

    def _groups_ceiling(self) -> int:
        return self.shape.max_groups

    # ------------------------------------------------------------ programs

    def _whole_plan(self) -> N.PlanNode:
        return self.shape.partial_plan

    def _resident_names(self) -> list[str]:
        return sorted({s.table_name
                       for s in X.scans_of(self.shape.partial_plan)
                       if s is not self.shape.stream})

    def _compile(self):
        if self._compiled is not None:
            return self._compiled
        shape = self.shape
        # the step programs' spine motions AND the finalize merge motion
        # lower as the in-memory dist path's do (dist_lowering)
        mesh, lowerer = dist_lowering(self.session)
        names = self._resident_names()
        _, res_specs = prepare_dist_inputs(None, self.session, names=names)

        def prelude_seg(tables):
            low = lowerer(tables, root=shape.partial_plan)
            outs = [_add_seg(low.lower_shared(b)) for b in shape.builds]
            return outs, _reduce_checks(low.checks)

        prelude_fn = PG.jit(_shard_map(
            prelude_seg, mesh, (res_specs,), (P(SEG_AXIS), P())),
            X.node_titles(shape.partial_plan), "tiled dist prelude")

        step_fn = self._make_step(mesh, lowerer, res_specs)

        def finalize_seg(acc):
            acc_cols, acc_sel = _strip_seg(tuple(acc))
            low = lowerer(
                {}, root=shape.partial_plan,
                replace={id(shape.replace_node): (acc_cols, acc_sel)})
            cols, sel = low.lower(shape.root)
            out = {f.name: cols[f.name][None] for f in shape.root.fields}
            return out, sel[None], _reduce_checks(low.checks)

        finalize_fn = PG.jit(_shard_map(
            finalize_seg, mesh, (P(SEG_AXIS),),
            (P(SEG_AXIS), P(SEG_AXIS), P())),
            X.node_titles(shape.partial_plan, shape.root),
            "tiled dist finalize")

        # a statement-level program set built: the engine's compile
        # counter moves here as it does in compile_plan
        X.count_compile(self.session)
        self._compiled = (prelude_fn, step_fn, finalize_fn)
        return self._compiled

    def _stat_motions(self):
        """The step program's redistribute motions, in deterministic
        traversal order — the skew sentinel (exec/tiled.py) watches
        their psum'd per-destination row counts, and the end-of-run
        fold publishes the cumulative observations to the feedback
        store (plan/feedback.py)."""
        return tuple(n for n in X.all_nodes(self.shape.partial_plan)
                     if isinstance(n, N.PMotion)
                     and n.kind == "redistribute")

    def _make_step(self, mesh, lowerer, res_specs):
        shape = self.shape
        nseg = self.nseg
        group_names = list(shape.group_names)
        specs = shape.merge_specs
        stat_motions = self._stat_motions()

        def step_seg(resident, prelude, tile, tile_n, acc):
            tables = dict(resident)
            tables["$tile"] = _strip_seg(tile)
            plocal = _strip_seg(prelude)
            replace = {id(b): tuple(plocal[i])
                       for i, b in enumerate(shape.builds)}
            low = lowerer(tables, replace=replace, stream=shape.stream,
                          tile_n=tile_n.reshape(()))
            pcols, psel = low.lower(shape.partial_plan)
            checks = dict(low.checks)
            srows = _motion_stats(low, stat_motions, nseg)
            acc_cols, acc_sel = _strip_seg(tuple(acc))
            g_cap = shape.g_cap
            # the tile's partial folded into the carry: no plan node's
            # (obs/programs.py UNNUMBERED)
            with jax.named_scope("tile:merge"):
                if group_names:
                    key_cols = {n: jnp.concatenate([acc_cols[n], pcols[n]])
                                for n in group_names}
                    agg_vals = {s.out_name: jnp.concatenate(
                        [acc_cols[s.out_name], pcols[s.out_name]])
                        for s in specs}
                    sel = jnp.concatenate([acc_sel, psel])
                    # the one-shot executor's grouped aggregation
                    ok, oa, osel, n_groups = K.group_aggregate(
                        key_cols, agg_vals, specs, sel, g_cap)
                    checks["tile merge overflow: more groups than capacity "
                           f"{g_cap}; raise the aggregation capacity"] = \
                        n_groups > g_cap
                    return _add_seg(({**ok, **oa}, osel)), \
                        _reduce_checks(checks), srows
                agg_vals = {s.out_name: jnp.concatenate(
                    [acc_cols[s.out_name], pcols[s.out_name]])
                    for s in specs}
                sel = jnp.concatenate([acc_sel, psel])
                out = K.global_aggregate(agg_vals, specs, sel)
                return _add_seg((out, jnp.ones((1,), dtype=jnp.bool_))), \
                    _reduce_checks(checks), srows

        return self._jit_step(step_seg, mesh, res_specs,
                              X.node_titles(shape.partial_plan))

    def _jit_step(self, step_seg, mesh, res_specs, titles):
        step_in = (res_specs, P(SEG_AXIS), P(SEG_AXIS), P(SEG_AXIS),
                   P(SEG_AXIS))
        donate = TP.step_donation(jax.default_backend())
        # third output: per-motion (required-bucket, per-destination
        # rows) telemetry pairs — psum/pmax replicated, so P() like the
        # checks; the skew sentinel consumes them host-side
        return PG.jit(_shard_map(step_seg, mesh, step_in,
                                 (P(SEG_AXIS), P(), P())),
                      titles, "tiled dist step", donate_argnums=donate)

    def _refinalize(self) -> None:
        """Size the merge boundary for the accumulator: a segment's acc has
        at most g_cap rows, so a redistribute bucket (all of one source's
        acc to one destination) is bounded by g_cap, and the final
        aggregation sees at most nseg·g_cap rows."""
        shape = self.shape
        if shape.merge_motion is not None:
            if shape.merge_motion.kind == "redistribute":
                shape.merge_motion.bucket_cap = shape.g_cap
            shape.merge_motion.out_capacity = shape.g_cap * self.nseg
        if shape.final_agg is not None:
            shape.final_agg.capacity = max(shape.g_cap * self.nseg, 1)

    def _init_acc(self):
        shape = self.shape
        g_cap = shape.g_cap
        cols = {}
        if shape.group_names:
            for f in shape.partial_plan.fields:
                cols[f.name] = np.zeros((self.nseg, g_cap),
                                        dtype=f.type.np_dtype)
            return cols, np.zeros((self.nseg, g_cap), dtype=np.bool_)
        for f, spec in zip(shape.partial_plan.fields, shape.merge_specs):
            dt = f.type.np_dtype
            if spec.func == "min":
                ident = np.array(
                    np.finfo(dt).max if np.issubdtype(dt, np.floating)
                    else np.iinfo(dt).max, dtype=dt)
            elif spec.func == "max":
                ident = np.array(
                    np.finfo(dt).min if np.issubdtype(dt, np.floating)
                    else np.iinfo(dt).min, dtype=dt)
            else:
                ident = np.zeros((), dtype=dt)
            cols[f.name] = np.full((self.nseg, 1), ident)
        # identity row stays unselected: min/max identities must not leak
        return cols, np.zeros((self.nseg, 1), dtype=np.bool_)

    # ----------------------------------------------------------------- run

    def run(self) -> ColumnBatch:
        with self._run_lock:
            return self._run_adaptive()

    def _run_once(self) -> ColumnBatch:
        from cloudberry_tpu.exec import recovery as R

        # mid-statement recovery (exec/recovery.py): the prepare step may
        # grow g_cap for re-sharded partials, so it runs BEFORE the
        # retile/refinalize/compile chain fixes the program shapes
        ctx = R.begin(self, dist=True)
        if ctx is not None:
            ctx.prepare_dist()
        _retile_dist(self.shape, self.tile_rows, self.nseg)
        self._refinalize()
        prelude_fn, step_fn, finalize_fn = self._compile()
        resident, _ = prepare_dist_inputs(
            None, self.session, names=self._resident_names())
        if self.shape.builds:
            prelude, pchecks = prelude_fn(resident)
            X.raise_checks(pchecks)
        else:
            prelude, pchecks = [], {}

        acc = self._init_acc()
        if ctx is not None:
            acc = ctx.restore_acc(acc)
        feed = (ctx.feed() if ctx is not None else None) \
            or _dist_tile_feed(self.shape.stream, self.session,
                               self.tile_rows)
        n_base = ctx.tiles_base if ctx is not None else 0
        n_local = 0
        from cloudberry_tpu.exec.tiled import SkewSentinel, _TileTimer

        timer = _TileTimer(self.session)
        tracker = _dist_progress_tracker(self, feed, n_base)
        sentinel = SkewSentinel(self, self._stat_motions(), ctx)
        pipe = TP.TilePipe(self.session, TP.effective_window(
            self.session.config, jax.default_backend()))
        # prefetch pipeline over the per-segment feed (exec/scanpipe.py:
        # host staging only — shard_map owns device placement); the
        # tracker/checkpoint math reads the UNWRAPPED feed above, and
        # progress counts consumed tiles, never staged ones
        stream = SP.maybe_pipeline(iter(feed), self.session.config,
                                   min_depth=pipe.window)
        n_sub = 0

        def _verified(d):
            # host effects for one drained-clean tile, in stream order
            nonlocal n_local
            tile_k, staged, srows = d.payload
            n_local = tile_k
            if srows is not None:
                sentinel.observe(srows)
            tracker.step(tile_k)
            if ctx is not None:
                ctx.tick(tile_k, staged if staged is not None
                         else (lambda: R.acc_payload(acc)))

        def _settle():
            # drain every dispatched tile so the replan snapshot's acc
            # (the newest) matches the settled tile count
            for d in pipe.drain_all():
                _verified(d)
            return n_sub

        try:
            for tile, tile_ns in stream:
                fault_point("tile_step_dist")
                fault_point("tile_device_lost")
                n_sub += 1
                stage = (ctx is not None and pipe.window > 1
                         and ctx.snapshot_due(n_sub))
                with timer.step(n_base + n_sub - 1):
                    acc, checks, srows = step_fn(resident, prelude, tile,
                                                 tile_ns, acc)
                    staged = TP.stage_checkpoint(acc) if stage else None
                    drained = pipe.submit(
                        n_base + n_sub - 1, checks,
                        (n_sub, staged,
                         srows if sentinel.collect else None))
                for d in drained:
                    _verified(d)
                # AFTER the cadence tick: an alarm at a tick tile reuses
                # that snapshot instead of saving twice
                sentinel.maybe_replan(n_local,
                                      lambda: R.acc_payload(acc),
                                      settle=_settle)
            for d in pipe.drain_all():
                _verified(d)
            if pipe.window > 1:
                # the tail's observes may alarm after the feed ended
                sentinel.maybe_replan(n_local,
                                      lambda: R.acc_payload(acc))
        finally:
            if pipe.deferred_fail:
                self._deferred_fail = True
            SP.close_feed(stream)
        SP.stamp_report(self.report, stream)
        timer.stamp(self.report)
        pipe.stamp(self.report)
        sentinel.fold_final()
        n_tiles = n_base + n_local
        if n_tiles == 0:  # empty stream: one all-masked tile seeds the acc
            tile, _ = _empty_dist_tile(self.shape.stream, self.tile_rows,
                                       self.nseg)
            zeros = np.zeros((self.nseg,), dtype=np.int64)
            acc, checks, _ = step_fn(resident, prelude, tile, zeros, acc)
            _raise_tile_checks(checks, 0)
            n_tiles = 1

        # cancel seam before the finalize motions (the merge collective):
        # the per-tile checks bound the stream, this bounds the tail
        from cloudberry_tpu.lifecycle import check_cancel

        check_cancel()
        cols, sel, fchecks = finalize_fn(acc)
        X.raise_checks(fchecks)
        self.report["n_tiles"] = n_tiles
        if ctx is not None:
            ctx.stamp_report(self.report)
        self._publish_report()
        host_cols = {k: _local_row(v) for k, v in cols.items()}
        return X.make_batch(self.shape.root, host_cols, _local_row(sel))


class DistTopNTiledExecutable(DistTiledExecutable):
    """Distributed tiled statement with per-segment bounded top-N row
    accumulators (tiled.py TopNTiledExecutable on the mesh): each
    segment's step merges its tile through one LOCAL bounding sort — no
    collectives beyond the spine's own motions — and finalize re-runs
    the original plan (pre-gather compaction, gather, global sort,
    LIMIT) over the accumulators."""

    _what = "distributed top-N tiled execution"

    def _groups_ceiling(self) -> int:
        return self.shape.g_cap  # fixed: LIMIT itself bounds the acc

    def _refresh_report(self) -> None:
        super()._refresh_report()
        self.report["mode"] = "topn"

    def _refinalize(self) -> None:
        # finalize re-runs the original post chain over m-row
        # accumulators: gather receive buffers were sized for the full
        # stream, shrink them to nseg·m
        shape = self.shape
        for node in shape.post:
            if isinstance(node, N.PMotion):
                node.out_capacity = shape.g_cap * self.nseg

    def _init_acc(self):
        shape = self.shape
        cols = {f.name: np.zeros((self.nseg, shape.g_cap),
                                 dtype=f.type.np_dtype)
                for f in shape.partial_plan.fields}
        return cols, np.zeros((self.nseg, shape.g_cap), dtype=np.bool_)

    def _make_step(self, mesh, lowerer, res_specs):
        from cloudberry_tpu.exec.tiled import _AccLeaf

        shape = self.shape
        nseg = self.nseg
        m = shape.g_cap
        mleaf = _AccLeaf()
        mleaf.fields = list(shape.partial_plan.fields)
        msort = N.PSort(mleaf, list(shape.sortnode.keys))
        msort.fields = list(mleaf.fields)
        names = [f.name for f in shape.partial_plan.fields]
        stat_motions = self._stat_motions()

        def step_seg(resident, prelude, tile, tile_n, acc):
            tables = dict(resident)
            tables["$tile"] = _strip_seg(tile)
            plocal = _strip_seg(prelude)
            replace = {id(b): tuple(plocal[i])
                       for i, b in enumerate(shape.builds)}
            low = lowerer(tables, replace=replace, stream=shape.stream,
                          tile_n=tile_n.reshape(()))
            pcols, psel = low.lower(shape.partial_plan)
            checks = dict(low.checks)
            srows = _motion_stats(low, stat_motions, nseg)
            acc_cols, acc_sel = _strip_seg(tuple(acc))
            with jax.named_scope("tile:merge"):
                ccols = {n: jnp.concatenate([acc_cols[n], pcols[n]])
                         for n in names}
                csel = jnp.concatenate([acc_sel, psel])
            low2 = lowerer({}, root=shape.partial_plan,
                           replace={id(mleaf): (ccols, csel)})
            scols, ssel = low2.lower(msort)
            checks.update(low2.checks)
            return _add_seg(({n: scols[n][:m] for n in names},
                             ssel[:m])), _reduce_checks(checks), srows

        return self._jit_step(step_seg, mesh, res_specs,
                              X.node_titles(shape.partial_plan, msort))


class DistSortTiledExecutable(DistTiledExecutable):
    """Distributed external sort (tiled.py SortTiledExecutable on the
    mesh): each step is one shard_map program — every segment streams a
    tile of ITS shard through the spine (per-tile collectives included)
    and emits surviving rows plus order-normalized u64 keys. The host
    pools all segments' rows (subsuming the plan's gather), one stable
    key sort is the merge pass, and the chain above the sort applies
    host-side."""

    _what = "distributed external-sort tiled execution"

    def _groups_ceiling(self) -> int:
        return 0  # no accumulator exists to grow

    def _refresh_report(self) -> None:
        super()._refresh_report()
        self.report["mode"] = "sort"

    def _compile(self):
        if self._compiled is not None:
            return self._compiled
        shape = self.shape
        mesh, lowerer = dist_lowering(self.session)
        rnames = self._resident_names()
        _, res_specs = prepare_dist_inputs(None, self.session,
                                           names=rnames)

        def prelude_seg(tables):
            low = lowerer(tables, root=shape.partial_plan)
            outs = [_add_seg(low.lower_shared(b)) for b in shape.builds]
            return outs, _reduce_checks(low.checks)

        prelude_fn = PG.jit(_shard_map(
            prelude_seg, mesh, (res_specs,), (P(SEG_AXIS), P())),
            X.node_titles(shape.partial_plan), "tiled dist prelude")

        sort = shape.sortnode
        kchild = sort.child
        names = [f.name for f in shape.partial_plan.fields]

        def step_seg(resident, prelude, tile, tile_n):
            tables = dict(resident)
            tables["$tile"] = _strip_seg(tile)
            plocal = _strip_seg(prelude)
            replace = {id(b): tuple(plocal[i])
                       for i, b in enumerate(shape.builds)}
            low = lowerer(tables, replace=replace, stream=shape.stream,
                          tile_n=tile_n.reshape(()))
            pcols, psel = low.lower(shape.partial_plan)
            n = psel.shape[0]
            keys = []
            for e, asc in sort.keys:
                arr = X._as_column(X._sortable(e, kchild, pcols), n)
                u = K.sort_key_u64(arr)
                keys.append(u if asc else ~u)
            out = {nm: X._as_column(pcols[nm], n) for nm in names}
            return _add_seg((out, psel, keys)), _reduce_checks(low.checks)

        step_fn = PG.jit(_shard_map(
            step_seg, mesh,
            (res_specs, P(SEG_AXIS), P(SEG_AXIS), P(SEG_AXIS)),
            (P(SEG_AXIS), P())),
            X.node_titles(shape.partial_plan), "tiled dist sort step")
        # a statement-level program set built: the engine's compile
        # counter moves here as it does in compile_plan
        X.count_compile(self.session)
        self._compiled = (prelude_fn, step_fn)
        return self._compiled

    def _stream_sorted(self):
        """Per-segment tile stream + host merge; returns (sorted child
        columns, sorted normalized keys, n_tiles, recovery ctx) as host
        arrays."""
        from cloudberry_tpu.exec import recovery as R

        ctx = R.begin(self, dist=True)
        if ctx is not None:
            ctx.prepare_dist()
        prelude_fn, step_fn = self._compile()
        shape = self.shape
        resident, _ = prepare_dist_inputs(
            None, self.session, names=self._resident_names())
        if shape.builds:
            prelude, pchecks = prelude_fn(resident)
            X.raise_checks(pchecks)
        else:
            prelude = []
        names = [f.name for f in shape.partial_plan.fields]
        runs: dict[str, list] = {nm: [] for nm in names}
        key_runs: list[list] = [[] for _ in shape.sortnode.keys]
        if ctx is not None:
            runs, key_runs = ctx.restore_runs(runs, key_runs)
        feed = (ctx.feed() if ctx is not None else None) \
            or _dist_tile_feed(shape.stream, self.session, self.tile_rows)
        n_base = ctx.tiles_base if ctx is not None else 0
        n_local = 0
        from cloudberry_tpu.exec.tiled import _TileTimer

        timer = _TileTimer(self.session)
        tracker = _dist_progress_tracker(self, feed, n_base)
        pipe = TP.TilePipe(self.session, TP.effective_window(
            self.session.config, jax.default_backend()))
        # same pipeline wrap as the agg-mode loop: staging off the
        # critical path, consumed-tile accounting unchanged
        stream = SP.maybe_pipeline(iter(feed), self.session.config,
                                   min_depth=pipe.window)
        n_sub = 0

        def _verified(d):
            # materialize one drained-clean tile's run slices, in
            # stream order (the async D2H started at submit); host runs
            # are exactly as-of the drained tile, so no staging needed
            nonlocal n_local
            tile_k, pcols, psel, keys = d.payload
            n_local = tile_k
            tracker.step(tile_k)
            selnp = np.asarray(psel)
            for s in range(self.nseg):
                m = selnp[s]
                for nm in names:
                    runs[nm].append(np.asarray(pcols[nm][s])[m])
                for i, k in enumerate(keys):
                    key_runs[i].append(np.asarray(k[s])[m])
            if ctx is not None:
                ctx.tick(tile_k,
                         lambda: R.runs_payload(runs, key_runs))

        try:
            for tile, tile_ns in stream:
                fault_point("tile_step_dist")
                fault_point("tile_device_lost")
                n_sub += 1
                with timer.step(n_base + n_sub - 1):
                    (pcols, psel, keys), checks = step_fn(
                        resident, prelude, tile, tile_ns)
                    drained = pipe.submit(n_base + n_sub - 1, checks,
                                          (n_sub, pcols, psel, keys))
                for d in drained:
                    _verified(d)
            for d in pipe.drain_all():
                _verified(d)
        finally:
            if pipe.deferred_fail:
                self._deferred_fail = True
            SP.close_feed(stream)
        SP.stamp_report(self.report, stream)
        timer.stamp(self.report)
        pipe.stamp(self.report)
        from cloudberry_tpu.exec.tiled import merge_sorted_runs

        cols, karr = merge_sorted_runs(runs, key_runs,
                                       shape.partial_plan.fields,
                                       len(shape.sortnode.keys))
        return cols, karr, max(n_base + n_local, 1), ctx

    def _run_once(self) -> ColumnBatch:
        _retile_dist(self.shape, self.tile_rows, self.nseg)
        shape = self.shape
        cols, _karr, n_tiles, ctx = self._stream_sorted()
        from cloudberry_tpu.exec.tiled import host_apply_post

        cols = host_apply_post(shape.post_above, cols)
        n_out = len(next(iter(cols.values()))) if cols else 0
        self.report["n_tiles"] = n_tiles
        if ctx is not None:
            ctx.stamp_report(self.report)
        self._publish_report()
        out_node = shape.post_above[0] if shape.post_above \
            else shape.sortnode
        return X.make_batch(out_node, cols,
                            np.ones((n_out,), dtype=bool))


class DistWindowTiledExecutable(DistSortTiledExecutable):
    """Distributed window spill: phase one is the per-segment
    external-sort stream grouped by the stack's common partition keys;
    phase two packs whole partitions into fixed chunks and runs the
    ORIGINAL plan above the stream on ONE device per chunk (gather
    motions lower as identity over the pooled host rows; chunks are
    independent so no mesh is needed)."""

    _what = "distributed windowed tiled execution"

    def _refresh_report(self) -> None:
        super()._refresh_report()
        self.report["mode"] = "window"

    def _chunk_fn(self):
        if getattr(self, "_chunk_compiled", None) is not None:
            return self._chunk_compiled
        shape = self.shape
        cap = self.tile_rows
        plat = jax.default_backend()

        def run_chunk(chunk_cols, n_valid):
            sel = jnp.arange(cap) < n_valid
            low = X.Lowerer(
                {}, platform=plat, root=shape.partial_plan,
                replace={id(shape.replace_node): (chunk_cols, sel)})
            cols, osel = low.lower(shape.root)
            out = {f.name: cols[f.name] for f in shape.root.fields}
            return out, osel, low.checks

        self._chunk_compiled = PG.jit(
            run_chunk, X.node_titles(shape.partial_plan, shape.root),
            "tiled dist window chunk")
        return self._chunk_compiled

    def _run_once(self) -> ColumnBatch:
        from cloudberry_tpu.exec.tiled import window_chunk_pass

        _retile_dist(self.shape, self.tile_rows, self.nseg)
        shape = self.shape
        self._chunk_compiled = None  # capacity may have changed
        cols, karr, n_tiles, ctx = self._stream_sorted()
        names = [f.name for f in shape.partial_plan.fields]
        final, n_chunks = window_chunk_pass(
            self._chunk_fn(), shape.root, names, cols, karr,
            shape.n_ckeys, self.tile_rows)
        n_out = len(next(iter(final.values()))) if final else 0
        self.report["n_tiles"] = n_tiles
        self.report["n_chunks"] = n_chunks
        if ctx is not None:
            ctx.stamp_report(self.report)
        self._publish_report()
        return X.make_batch(shape.root, final,
                            np.ones((n_out,), dtype=bool))


# -------------------------------------------------------------- tile feed


def _empty_dist_tile(scan: N.PScan, tile_rows: int, nseg: int):
    t = {}
    for phys in scan.column_map:
        t[phys] = np.zeros((nseg, tile_rows), dtype=np.int64)
    for phys in scan.mask_map:
        t[f"$nn:{phys}"] = np.zeros((nseg, tile_rows), dtype=np.bool_)
    return t, np.zeros((nseg,), dtype=np.int64)


def _dist_progress_tracker(exe, feed, n_base: int):
    """Live-progress feeder for a distributed tile loop
    (obs/progress.py): one lane per segment — the loop runs lock-step,
    so the longest shard sets the tile count. A resumed feed
    (_ResumedDistFeed) contributes its remaining per-shard counts and
    the consumed-mask population as the base; the fresh feed derives
    lanes from the counts-only shard layout."""
    from cloudberry_tpu.obs.progress import TileTracker, stream_rows

    session = exe.session
    total = stream_rows(exe.shape.stream, session)
    base_rows = 0
    if hasattr(feed, "counts") and hasattr(feed, "base_mask"):
        lanes = np.asarray(feed.counts)
        base_rows = int(np.asarray(feed.base_mask).sum())
    else:
        try:
            lanes = np.asarray(session.shard_counts(
                exe.shape.stream.table_name))
        except KeyError:
            lanes = np.asarray([total])
    return TileTracker(lanes, exe.tile_rows, n_base=n_base,
                       base_rows=base_rows, rows_total=total)


def _bufpool_charge_dist(session, table: str) -> int:
    bpool = BUF.pool_for(session)
    return bpool.table_bytes(table) if bpool is not None else 0


def _dist_tile_feed(scan: N.PScan, session, tile_rows: int):
    """Yield (tile dict of (nseg, tile_rows) arrays, per-segment valid
    counts). All segments step in lock-step; a segment whose shard ran dry
    contributes masked rows — the SPMD analog of a QE sending EOS while
    its peers still stream. Packed feed tiles resident in the buffer
    pool (exec/bufferpool.py, keyed by tile offset + the shared-tier
    content/epoch tokens) skip the slice-pad-copy work; the pool holds
    HOST arrays on this path — shard_map owns device placement, exactly
    like the pipeline's host-only staging."""
    st = session.sharded_table(scan.table_name)
    nseg, shard_cap = len(st.counts), st.capacity
    bpool = BUF.pool_for(session)
    cols_key = (tuple(sorted(scan.column_map)),
                tuple(sorted(scan.mask_map)))
    log = getattr(session, "stmt_log", None)
    counts = np.asarray(st.counts)
    cols: Optional[dict] = None  # built lazily: an all-hit feed never
    max_rows = int(st.counts.max()) if len(st.counts) else 0
    for off in range(0, max(max_rows, 0), tile_rows):
        n = min(tile_rows, max_rows - off)
        tile_ns = np.clip(counts - off, 0, tile_rows)
        key = None
        if bpool is not None:
            try:
                key = BUF.dist_tile_key(session, scan.table_name,
                                        cols_key, nseg, tile_rows, off)
            except KeyError:  # table dropped mid-plan: fall through
                key = None
        if key is not None:
            ent = bpool.lookup(key, log)
            if ent is not None:
                yield ent["tile"], tile_ns
                continue
        if cols is None:
            cols = {}
            for phys in scan.column_map:
                cols[phys] = np.asarray(st.columns[phys])
            for phys in scan.mask_map:
                vm = st.columns.get(f"$nn:{phys}")
                cols[f"$nn:{phys}"] = (
                    np.asarray(vm) if vm is not None
                    else np.ones((nseg, shard_cap), dtype=np.bool_))
        tile = {}
        for name, arr in cols.items():
            sl = arr[:, off:off + n]
            if n < tile_rows:
                sl = np.concatenate(
                    [sl, np.zeros((nseg, tile_rows - n), dtype=arr.dtype)],
                    axis=1)
            tile[name] = np.ascontiguousarray(sl)
        if key is not None:
            bpool.offer(key, {"tile": tile}, table=scan.table_name,
                        log=log, device=False)
        yield tile, tile_ns
