"""Tiled (out-of-core) execution — the workfile-manager / spill analog.

The reference survives bigger-than-memory queries by spilling operator state
to workfiles (src/backend/utils/workfile_manager/workfile_mgr.c, the batch
discipline of nodeHash.c) under a vmem red zone
(src/backend/utils/mmgr/redzone_handler.c). The XLA translation cannot page
a running program, so the spill boundary moves to PLAN TIME: when the
admission estimator (exec/resource.py) rejects a plan, this module re-plans
it as a STREAM OF FIXED-SHAPE TILES —

- the plan's big probe-side scan becomes the tile stream: host RAM (or
  micro-partition files, for cold tables) holds the table; the device only
  ever sees one tile of ``tile_rows`` rows;
- every spine join's build subtree is computed ONCE by a prelude program
  and its (bounded, estimated-and-admitted) result arrays stay resident;
- a spine join whose build does not fit the budget, but whose build keys
  have a proven span (plan/joincap.py ``direct_box``), is a BUILD STREAM
  (``_BuildStream``): before the probe stream starts, the build's own
  scan is streamed in tiles through the same feed, and each tile's rows
  are scattered into a direct-address table over that span
  (``kernels.direct_table_insert``) — the batch-file discipline of
  nodeHash.c with the table, not the build, resident; the probe steps
  read it with one gather (``Lowerer._join_table``);
- one jitted STEP program runs per tile: spine joins/filters/projections,
  a partial aggregation, and a merge into a fixed-capacity accumulator
  (the combine-function discipline of the distributed two-stage agg,
  plan/distribute.py:_split_aggs — partials merge associatively, so any
  tile order and count gives the same answer);
- a finalize program applies the post-aggregation chain (HAVING / ORDER BY /
  LIMIT / avg = sum/count) to the accumulator.

Per-tile capacities keep the engine's checked-overflow discipline: a tile
that overflows its expansion-join or group buffers raises, never truncates.
Peak device memory is the admitted estimate: resident builds + streamed
builds' tables + one tile's working set + the accumulator — independent
of the streamed tables' sizes.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from cloudberry_tpu.columnar.batch import ColumnBatch
from cloudberry_tpu.exec import bufferpool as BUF
from cloudberry_tpu.exec import executor as X
from cloudberry_tpu.exec import kernels as K
from cloudberry_tpu.exec import scanpipe as SP
from cloudberry_tpu.exec import tilepipe as TP
from cloudberry_tpu.exec.resource import estimate_plan_memory
from cloudberry_tpu.obs import programs as PG
from cloudberry_tpu.plan import expr as ex
from cloudberry_tpu.plan import nodes as N
from cloudberry_tpu.utils.faultinject import fault_point
from cloudberry_tpu.plan.distribute import (_all_exprs, _finalize_project,
                                            _split_aggs)

_MAX_TILE = 1 << 22
_MIN_TILE = 1 << 12

# The declared set of tiled executor modes that snapshot carried state
# into the recovery store (_TileShape.mode values whose tick() paths
# checkpoint). exec/recovery.py REPLACEABLE must cover every entry —
# the plan verifier (plan/verify.py recovery-mode-unreplaceable) and
# graftlint's planprops pass pin the two tables together BOTH ways, so
# a new checkpointing mode cannot ship without a degraded-mesh
# re-placement rule.
CHECKPOINT_MODES = ("agg", "topn", "sort", "window")


class _AccLeaf(N.PlanNode):
    """Plan leaf standing for the accumulator in the finalize program."""

    def title(self):
        return "TileAccumulator"


@dataclass
class _TileShape:
    """Everything the rewrite discovered about the plan. Two modes:
    "agg" streams into a partial-aggregation accumulator; "topn" streams
    into a fixed top-N row accumulator (ORDER BY + LIMIT over the spine,
    no aggregation — the tuplesort bounded-heap analog, nodeSort.c
    bounded mode)."""

    agg: Optional[N.PAgg]             # the streamed aggregation (agg mode)
    post: list[N.PlanNode]            # chain above agg/sort, root first
    spine: list[N.PlanNode]           # agg.child .. just above the stream
    stream: N.PScan                   # the tiled scan
    builds: list[N.PlanNode]          # spine joins' build subtrees
    stream_rows: int = 0              # whole-stream rows (floor scaling)
    partial_plan: N.PlanNode = None   # type: ignore[assignment]
    merge_specs: list = field(default_factory=list)
    finalize: dict = field(default_factory=dict)
    root: N.PlanNode = None           # type: ignore[assignment]
    g_cap: int = 0                    # accumulator capacity (groups / rows)
    mode: str = "agg"
    sortnode: Optional[N.PSort] = None  # topn/sort: the (synthetic) sort
    winnode: Optional[N.PWindow] = None  # window mode: BOTTOM of the stack
    wintop: Optional[N.PWindow] = None   # window mode: TOP of the stack
    n_ckeys: int = 0                  # window mode: chunk-key count
    # spine joins whose builds stream into direct tables (``_fit``)
    streams: list = field(default_factory=list)
    stmt: N.PlanNode = None           # type: ignore[assignment]
    decline: str = ""                 # why the budget cannot hold it


@dataclass
class _BuildStream:
    """A spine join whose build streams into a direct-address table over
    its keys' proven box before the probe stream starts: ``shape`` is the
    build's own spine down to the scan it streams (its own builds
    resident), ``box`` one ``(least value, span)`` a key, ``payload`` the
    build columns an inner or left join carries, with their dtypes."""

    join: N.PJoin
    shape: _TileShape
    box: list
    payload: list
    tile_rows: int = 0
    whole_rows: int = 0     # the build scan's capacity before its tiles

    def untile(self) -> None:
        """The build scan back at its whole capacity (a declined plan's
        build is computed whole by whichever program runs it)."""
        self.shape.stream.capacity = self.whole_rows

    @property
    def slots(self) -> int:
        return math.prod(span for _, span in self.box)

    @property
    def unique(self) -> bool:
        """An inner or left join's build: one row a key, checked."""
        return self.join.kind in ("inner", "left")

    @property
    def table_bytes(self) -> int:
        return self.slots * (1 + sum(np.dtype(dt).itemsize
                                     for _, dt in self.payload))

    def step_bytes(self) -> int:
        """One build tile's working set beside the tables: the build's
        spine at its tile and its resident builds, and the scatter's
        int32 owner table where duplicates are checked."""
        own = estimate_plan_memory(self.join.build).peak_bytes
        return own + (4 * self.slots if self.unique else 0)

    def empty_table(self):
        return (jnp.zeros((self.slots,), jnp.bool_),
                {n: jnp.zeros((self.slots,), dt) for n, dt in self.payload})


def plan_tiled(plan: N.PlanNode, session) -> Optional["TiledExecutable"]:
    """Try to re-plan an admission-rejected statement for tiled execution.
    Returns None when the plan shape or the budget cannot support it, and
    notes why on the plan (``declined``)."""
    plan._tile_declined = None
    if not session.config.resource.enable_spill:
        return _decline(plan, "spill is off (resource.enable_spill)")
    if session.config.n_segments > 1:
        from cloudberry_tpu.exec.tiled_dist import plan_tiled_dist

        return plan_tiled_dist(plan, session)
    if getattr(plan, "_direct_segment", None) is not None:
        return _decline(plan, "a statement dispatched to one segment")
    from cloudberry_tpu.plan.pointlookup import unbind_point_lookups

    # the tile stream and resident loads key inputs by TABLE NAME: a
    # point-sliced scan would miss its $pt input — restore full scans
    unbind_point_lookups(plan)
    # join-index inputs are a one-shot-executor feature: tiled prelude/
    # step programs assemble their own input dicts, so drop the
    # annotations — joins then compute their argsort in-program
    # (exec/joinindex.py documents the fallback contract). The strip is
    # speculative: a decline below restores the stash so the one-shot
    # fallback keeps its cached indexes.
    from cloudberry_tpu.exec.joinindex import (restore_join_index,
                                               stash_join_index,
                                               strip_join_index)
    shape = _analyze(plan)
    if shape is None:
        return _decline(plan, "no streamable shape")
    shape.stmt = plan
    # whole-run growth marks (session._run_with_growth) are meaningless at
    # tile scale and would poison the per-tile floor — the tiled adaptive
    # loop re-learns spine buffer sizes itself. Build-side joins keep
    # theirs: the prelude still computes whole builds.
    for node in shape.spine:
        if isinstance(node, N.PJoin) and hasattr(node, "_min_out_cap"):
            del node._min_out_cap
    stash = stash_join_index(plan)
    strip_join_index(plan)
    t = _plan_by_mode(shape, session)
    if t is None:
        restore_join_index(stash)
        _decline(plan, shape.decline or "no streamable shape")
    return t


def _decline(plan: N.PlanNode, why: str) -> None:
    plan._tile_declined = why


def declined(err: Exception, plan: N.PlanNode) -> Exception:
    """The admission error ``err`` of a plan ``plan_tiled`` declined,
    saying why it declined: the join whose build the budget cannot hold
    (its ordinal in the plan, its estimate against the budget, and what
    keeps it from streaming), or the shape it cannot stream."""
    why = getattr(plan, "_tile_declined", None)
    if not why:
        return err
    from cloudberry_tpu.exec.resource import ResourceError

    return ResourceError(f"{err}; tiled execution declined: {why}")


def _plan_by_mode(shape: "_TileShape", session):
    if shape.mode == "topn":
        t = _plan_topn(shape, session)
        if t is not None:
            return t
        # the LIMIT+OFFSET exceeds any resident accumulator: fall back
        # to the full external sort and apply the limit host-side
        shape.mode = "sort"
        shape.g_cap = 0
    if shape.mode == "sort":
        return _plan_sort(shape, session)
    if shape.mode == "window":
        return _plan_window(shape, session)
    try:
        partial_aggs, final_aggs, finalize = _split_aggs(shape.agg.aggs)
    except ValueError:
        shape.decline = "an aggregate with no partial and merge form"
        return None
    shape.finalize = finalize
    shape.merge_specs = [K.AggSpec(call.func, name)
                         for name, call in final_aggs]

    # Accumulator capacity: the binder's agg capacity is the worst case
    # (child rows) — useless as a resident buffer. Size from the NDV-based
    # group estimate with 4× headroom; a merge overflow at runtime grows it
    # and retries (the nodeHash.c increase-nbatch discipline) rather than
    # ever returning truncated groups.
    from cloudberry_tpu.plan.cost import estimate_rows

    est_groups = estimate_rows(shape.agg, session.catalog)
    shape.g_cap = int(min(shape.agg.capacity,
                          max(1024, 4 * int(est_groups) + 1)))

    # per-tile partial aggregation over the spine (mode/fields mirror the
    # distributed two-stage construction, plan/distribute.py:532)
    partial = N.PAgg(shape.agg.child, shape.agg.group_keys, partial_aggs,
                     capacity=shape.agg.capacity, mode="partial",
                     carried=shape.agg.carried)
    partial.fields = [
        N.PlanField(n, e.dtype, _expr_dict(shape.agg.child, e))
        for n, e in shape.agg.group_keys
    ] + [N.PlanField(n, c.dtype, None) for n, c in partial_aggs]
    shape.partial_plan = partial

    budget = session.config.resource.query_mem_bytes
    tile_rows = _fit(shape, session, budget)
    if tile_rows is None:
        return None

    # finalize plan: (acc leaf) -> finalize project -> original post chain
    leaf = _AccLeaf()
    leaf.fields = list(partial.fields)
    fproj = _finalize_project(leaf, shape.agg, finalize)
    if shape.post:
        shape.post[-1].child = fproj
        shape.root = shape.post[0]
    else:
        shape.root = fproj

    return TiledExecutable(shape, session, tile_rows, budget)


def _plan_topn(shape: _TileShape, session) -> Optional["TopNTiledExecutable"]:
    """Top-N streaming: the accumulator holds the best LIMIT+OFFSET rows
    of the sort's child so far; each tile merges through one bounding
    sort (tuplesort bounded-heap role, nodeSort.c). The post chain above
    the sort (LIMIT, projections) finalizes over the sorted accumulator."""
    sort = shape.sortnode
    shape.partial_plan = sort.child
    budget = session.config.resource.query_mem_bytes
    tile_rows = _fit(shape, session, budget)
    if tile_rows is None:
        return None  # LIMIT too large for a resident accumulator

    # merge program plan: bounding sort over (acc ∪ tile output)
    mleaf = _AccLeaf()
    mleaf.fields = list(sort.child.fields)
    msort = N.PSort(mleaf, list(sort.keys))
    msort.fields = list(mleaf.fields)
    shape.finalize = {"mleaf": mleaf, "msort": msort}

    # finalize plan: (sorted acc leaf) -> original post chain above sort
    fleaf = _AccLeaf()
    fleaf.fields = list(sort.child.fields)
    shape.post[-1].child = fleaf  # post is non-empty: the LIMIT lives there
    shape.root = shape.post[0]
    return TopNTiledExecutable(shape, session, tile_rows, budget)


def host_post_ok(nodes, sort_keys=None) -> bool:
    """True when a chain above a spilled sort can apply HOST-SIDE after
    the merge pass: column-pruning projections, LIMIT/OFFSET, gather
    motions (no-ops — the host already pools every segment's rows) and
    sorts on the same keys (already satisfied by the merge order). One
    predicate shared by the single-node and distributed recognizers so
    they cannot drift from host_apply_post."""
    for nd in nodes:
        if isinstance(nd, N.PLimit):
            continue
        if isinstance(nd, N.PProject) and all(
                isinstance(e, ex.ColumnRef) for _, e in nd.exprs):
            continue
        if isinstance(nd, N.PMotion) and nd.kind == "gather":
            continue
        if sort_keys is not None and isinstance(nd, N.PSort) \
                and repr(nd.keys) == repr(sort_keys):
            continue
        return False
    return True


def host_apply_post(nodes, cols: dict) -> dict:
    """Apply a host_post_ok-validated chain bottom-up over host arrays
    (gathers and merge-order sorts are no-ops here)."""
    for node in reversed(nodes):
        if isinstance(node, N.PLimit):
            total = len(next(iter(cols.values()))) if cols else 0
            lo = min(node.offset, total)
            cols = {nm: a[lo:lo + node.limit] for nm, a in cols.items()}
        elif isinstance(node, N.PProject):
            cols = {out: cols[e.name] for out, e in node.exprs}
    return cols


def merge_sorted_runs(runs: dict, key_runs: list, fields, nkeys: int):
    """The external sort's merge pass, shared by the single-node and
    distributed executables: one stable host key sort over the pooled
    runs (np.lexsort: LAST key is primary — mirror sort_indices).
    Returns (sorted columns, sorted normalized keys)."""
    names = list(runs)
    if not names or not any(len(r) for r in runs[names[0]]):
        cols = {f.name: np.zeros((0,), dtype=f.type.np_dtype)
                for f in fields}
        return cols, [np.zeros((0,), dtype=np.uint64)
                      for _ in range(nkeys)]
    karr = [np.concatenate(kr) for kr in key_runs]
    order = np.lexsort(tuple(reversed(karr)))
    cols = {nm: np.concatenate(runs[nm])[order] for nm in names}
    return cols, [k[order] for k in karr]


def _full_sort_shape(chain: list):
    """Unbounded ORDER BY shape: the lowest sort, with only a
    host-applicable chain above it — the external-sort path
    (tuplesort.c's spill-to-tape mode; here host RAM is the tape: the
    device streams spine tiles and emits rows plus their
    order-normalized u64 keys, the host keeps the runs and one C-speed
    stable key sort is the merge pass). Returns the sort node, or None
    when the chain has a different shape."""
    sort_i = next((i for i in range(len(chain) - 1, -1, -1)
                   if isinstance(chain[i], N.PSort)), None)
    if sort_i is None:
        return None
    if any(not isinstance(n, (N.PProject, N.PFilter))
           for n in chain[sort_i + 1:]):
        return None
    if not host_post_ok(chain[:sort_i], chain[sort_i].keys):
        return None
    return chain[sort_i]


def _plan_sort(shape: _TileShape,
               session) -> Optional["SortTiledExecutable"]:
    """Full external sort: stream the spine, keep every surviving row
    (plus order-normalized keys) in host RAM, one stable key sort as the
    merge pass, then apply the post chain (column pruning + LIMIT)
    host-side. The device budget covers resident builds + one tile's
    working set; the result itself lives host-side — the workfile."""
    # the topn fallback arrives here WITHOUT _full_sort_shape's chain
    # validation: re-check that everything above the sort is
    # host-applicable
    if not host_post_ok(shape.post, shape.sortnode.keys):
        return None
    shape.partial_plan = shape.sortnode.child
    budget = session.config.resource.query_mem_bytes
    tile_rows = _fit(shape, session, budget)
    if tile_rows is None:
        return None
    shape.root = shape.post[0] if shape.post else shape.sortnode
    return SortTiledExecutable(shape, session, tile_rows, budget)


def _plan_window(shape: _TileShape,
                 session) -> Optional["WindowTiledExecutable"]:
    """Window spill: phase one is the external-sort stream grouped by
    the partition keys COMMON to every spec in the stack; phase two
    windows whole-partition chunks on device (WindowTiledExecutable) —
    each chunk re-sorts per spec, so only the grouping must align. A
    stack with no common partition key is one giant partition — nothing
    bounds its working set, so it cannot stream (the reference buffers
    that case too)."""
    bottom = shape.winnode
    # common partition keys across the stack, matched structurally;
    # expr objects come from the BOTTOM spec (they bind over its child)
    common = {repr(pk): pk for pk in bottom.partition_keys}
    node = shape.wintop
    while isinstance(node, N.PWindow):
        here = {repr(pk) for pk in node.partition_keys}
        common = {k: v for k, v in common.items() if k in here}
        node = node.child
    if not common:
        shape.decline = "window specs with no common partition key"
        return None
    ckeys = list(common.values())
    srt = N.PSort(bottom.child, [(ck, True) for ck in ckeys])
    srt.fields = list(bottom.child.fields)
    shape.sortnode = srt
    shape.n_ckeys = len(ckeys)
    shape.partial_plan = bottom.child
    budget = session.config.resource.query_mem_bytes
    tile_rows = _fit(shape, session, budget)
    if tile_rows is None:
        return None
    shape.root = shape.post[0] if shape.post else shape.wintop
    return WindowTiledExecutable(shape, session, tile_rows, budget)


def _topn_bound(chain: list, skip: tuple = ()):
    """Locate a topn-streamable post chain's bounding sort and LIMIT: the
    LOWEST sort, fed only by projections/filters (part of the stream),
    with a LIMIT above it separated only by projections and ``skip``
    nodes (gather motions, distributed). An interposed SORT breaks the
    walk — a limit above a different sort bounds THAT order, not this
    one's — and a filter above the sort could starve the limit of rows
    the accumulator already dropped. Returns (sortnode, limit+offset) or
    None. Shared by the single-node and distributed analyzers so the
    recognizers cannot drift."""
    sort_i = next((i for i in range(len(chain) - 1, -1, -1)
                   if isinstance(chain[i], N.PSort)), None)
    if sort_i is None:
        return None
    if any(not isinstance(n, (N.PProject, N.PFilter))
           for n in chain[sort_i + 1:]):
        return None
    m = None
    for n in reversed(chain[:sort_i]):
        if isinstance(n, (N.PProject,) + skip):
            continue
        if isinstance(n, N.PLimit):
            m = n.limit + n.offset
        break
    if m is None or m <= 0:
        return None
    return chain[sort_i], m


def _analyze(plan: N.PlanNode) -> Optional[_TileShape]:
    """Recognize a streamable shape: a post chain over either one
    aggregation ("agg") or one bounding ORDER BY + LIMIT ("topn"), over a
    join/filter spine whose probe path ends at a scan."""
    for e in _all_exprs(plan):
        for sub in ex.walk(e):
            if isinstance(sub, ex.SubqueryScalar):
                return None  # subquery plans scan outside the spine budget

    chain: list[N.PlanNode] = []
    cur = plan
    while isinstance(cur, (N.PProject, N.PSort, N.PLimit, N.PFilter)):
        chain.append(cur)
        cur = cur.child

    agg: Optional[N.PAgg] = None
    sortnode: Optional[N.PSort] = None
    winnode: Optional[N.PWindow] = None
    post: list[N.PlanNode] = []
    m = 0
    if isinstance(cur, N.PAgg) and cur.mode == "single":
        agg = cur
        post = chain
        spine_top = agg.child
    elif isinstance(cur, N.PWindow):
        # window mode: a stack of window specs over the spine (one
        # PWindow per distinct OVER clause); above it only
        # column-pruning projections (the nodeWindowAgg spill shape).
        # Chunking needs partition keys COMMON to every spec — each
        # device chunk re-sorts per spec, so only the grouping must
        # align (checked in _plan_window).
        if any(not (isinstance(nd, N.PProject) and all(
                isinstance(e, ex.ColumnRef) for _, e in nd.exprs))
               for nd in chain):
            return None
        post = chain
        wintop = cur
        while isinstance(cur, N.PWindow):
            winnode = cur
            cur = cur.child
        spine_top = cur
    else:
        hit = _topn_bound(chain)
        if hit is not None:
            sortnode, m = hit
        else:
            # no bounding LIMIT: full external sort (host-RAM workfile)
            sortnode = _full_sort_shape(chain)
            if sortnode is None:
                return None
            m = 0
        post = chain[:chain.index(sortnode)]
        spine_top = sortnode.child

    walk = _spine_walk(spine_top)
    if walk is None:
        return None
    shape = _TileShape(agg, post, *walk)
    if winnode is not None:
        shape.mode = "window"
        shape.winnode = winnode
        shape.wintop = wintop
    elif agg is None:
        shape.mode = "topn" if m else "sort"
        shape.sortnode = sortnode
        shape.g_cap = m
    return shape


def _spine_walk(top: N.PlanNode):
    """(spine, builds, scan, scan rows) of a filter/projection/join chain
    whose probe path ends at a scan (the scan a tile stream reads), or
    None. The probe side of a statement's stream and a build stream's
    own (``_build_stream``) are found by this one walk."""
    spine: list[N.PlanNode] = []
    builds: list[N.PlanNode] = []
    cur = top
    while True:
        if isinstance(cur, (N.PFilter, N.PProject)):
            spine.append(cur)
            cur = cur.child
        elif isinstance(cur, N.PRuntimeFilter):
            spine.append(cur)
            cur = cur.child
        elif isinstance(cur, N.PJoin):
            if cur.kind == "full":
                # FULL joins emit unmatched BUILD rows — once per statement,
                # not once per tile; not streamable on the probe side
                return None
            spine.append(cur)
            builds.append(cur.build)
            cur = cur.probe
        elif isinstance(cur, N.PScan) and cur.table_name != "$dual":
            rows = cur.num_rows if cur.num_rows >= 0 else cur.capacity
            return spine, cur, builds, max(rows, 1)
        else:
            return None


def _retile(shape: _TileShape, tile_rows: int) -> None:
    """Set the stream scan to one tile and re-derive spine capacities (the
    same formulas the planner uses, per tile instead of whole): expansion
    joins keep the NDV pair-estimate floor scaled to the tile fraction, and
    any runtime-grown buffer (_min_out_cap, set by grow_expansion retries)
    is never shrunk back."""
    frac = tile_rows / max(shape.stream_rows, 1)
    shape.stream.capacity = tile_rows
    cap = tile_rows
    for node in reversed(shape.spine):
        if isinstance(node, N.PJoin):
            bcap = _out_cap(node.build)
            est = getattr(node, "_est_pairs", None)
            floor = int(2 * est * min(frac, 1.0)) + 8 if est else 0
            floor = max(floor, getattr(node, "_min_out_cap", 0))
            if node.residual is not None:
                # pairs expand internally; output rides the probe capacity
                node.out_capacity = max(bcap + cap, floor)
            elif not node.unique_build:
                node.out_capacity = max(bcap + cap, floor)
                cap = node.out_capacity
    if shape.agg is not None:
        shape.partial_plan.capacity = min(shape.g_cap, max(cap, 1))


# (the one capacity walk: plan/nodes.py)
_out_cap = N.capacity_of


def _acc_width(shape: _TileShape) -> int:
    return 1 + sum(f.type.np_dtype.itemsize
                   for f in shape.partial_plan.fields)


def _step_out_cap(shape) -> int:
    """Rows one tile's step can emit into the merge (shape is the single
    or distributed tile shape — both carry mode/partial_plan)."""
    return shape.partial_plan.capacity if shape.mode == "agg" \
        else _out_cap(shape.partial_plan)


def _merge_bytes(shape: _TileShape) -> int:
    """Accumulator + merge working set: the concat of acc and per-tile
    rows flowing through one sort-based group_aggregate (agg mode) or
    one bounding sort (topn mode)."""
    return 3 * (shape.g_cap + _step_out_cap(shape)) * _acc_width(shape)


def _choose_tile(shape: _TileShape, budget: int) -> Optional[int]:
    """Largest power-of-two tile whose estimated step memory fits: the
    spill-file-count decision of workfile_mgr, made at plan time."""
    t = _MAX_TILE
    while t >= _MIN_TILE:
        _retile(shape, t)
        if _step_bytes(shape) <= budget:
            return t
        t >>= 1
    return None


def _step_bytes(shape: _TileShape) -> int:
    """One probe step's estimate: the partial plan at its tile, the
    streamed builds' tables in their subtrees' place, the merge."""
    tables = {id(bs.join): bs.table_bytes for bs in shape.streams}
    return estimate_plan_memory(shape.partial_plan, tables).peak_bytes \
        + _merge_bytes(shape)


def _build_phase_bytes(shape: _TileShape) -> int:
    """The build streams' peak: every table, and the largest build
    tile's working set beside them."""
    if not shape.streams:
        return 0
    return sum(bs.table_bytes for bs in shape.streams) \
        + max(bs.step_bytes() for bs in shape.streams)


def _fit(shape: _TileShape, session, budget: int) -> Optional[int]:
    """``_choose_tile``, and where no tile fits, the spine joins' builds
    streamed into direct tables (``_build_stream``), the largest
    resident build first, until one does. None, with ``shape.decline``
    saying why, where none does."""
    t = _choose_tile(shape, budget)
    if t is not None:
        return t
    mib = 1 << 20
    joins = sorted((n for n in shape.spine if isinstance(n, N.PJoin)),
                   key=lambda j: -estimate_plan_memory(j.build).peak_bytes)
    why = []
    for j in joins:
        need = estimate_plan_memory(j.build).peak_bytes
        bs, cause = _build_stream(j, shape, session, budget)
        if bs is None:
            ordinal = next(i for i, n in enumerate(
                X.numbered_nodes(shape.stmt)) if n is j)
            why.append(f"join (node {ordinal}: {j.title()}) build "
                       f"estimate {need / mib:.1f} MiB against the budget "
                       f"{budget / mib:.1f} MiB: {cause}")
            continue
        shape.streams.append(bs)
        t = _choose_tile(shape, budget)
        if t is not None:
            return t
    t = _MIN_TILE
    _retile(shape, t)
    why.append(f"one {t}-row tile's step is estimated at "
               f"{_step_bytes(shape) / mib:.1f} MiB against the budget "
               f"{budget / mib:.1f} MiB")
    for bs in shape.streams:
        bs.untile()
    shape.streams.clear()
    shape.decline = "; ".join(why)
    return None


def _build_stream(join: N.PJoin, shape: _TileShape, session,
                  budget: int):
    """(a ``_BuildStream`` for ``join``, "") where its build can stream
    into a direct table, else (None, why): a lookup that holds no pair
    buffer (an inner or left join with a unique build, a semi or anti
    join with no residual), no NOT IN whose build keys may be NULL (its
    answer reads every build key), every build key a plain integer
    column with a proven span (plan/joincap.py ``direct_box``), a table
    of int32 slots, a build that is itself a spine of lookups ending at
    a scan (``_spine_walk``), and its table, the tables streamed before
    it and one build tile within the budget (the largest such tile)."""
    from cloudberry_tpu.plan.joincap import direct_box

    if join.expands:
        return None, "the join expands pairs"
    if join.kind == "anti" and join.null_aware \
            and join.build_key_valid is not None:
        return None, "a NOT IN whose build keys may be NULL"
    box = direct_box(join, session.catalog)
    if box is None:
        return None, "no proven key span"
    slots = math.prod(span for _, span in box)
    if slots >= 1 << 31:
        return None, f"its key span {slots} passes a table's int32 slots"
    walk = _spine_walk(join.build)
    if walk is None or any(isinstance(n, N.PJoin) and n.expands
                           for n in walk[0]):
        return None, "its build has no streamable shape"
    carried = join.build_payload if join.kind in ("inner", "left") else ()
    if not set(carried) <= set(join.build.names):
        return None, "its payload is not columns of its build"
    bshape = _TileShape(None, [], *walk, mode="build")
    bshape.partial_plan = join.build
    payload = [(n, np.dtype(join.build.field(n).type.np_dtype))
               for n in carried]
    bs = _BuildStream(join, bshape, box, payload,
                      whole_rows=bshape.stream.capacity)
    held = sum(b.table_bytes for b in shape.streams) + bs.table_bytes
    t = _MAX_TILE
    while t >= _MIN_TILE:
        _retile(bshape, t)
        if held + bs.step_bytes() <= budget:
            bs.tile_rows = t
            return bs, ""
        t >>= 1
    bs.untile()
    return None, (f"its table of {bs.table_bytes / (1 << 20):.1f} MiB and "
                  "one build tile do not fit")


# --------------------------------------------------------------- execution


class _TileTimer:
    """Per-tile step timing (the ISSUE-9 tiled telemetry): each step's
    wall feeds the engine ``tile_seconds`` histogram — so tile-time
    regressions show in ``meta "metrics"`` without an instrumented
    rerun — and, when the statement is traced, a per-tile span;
    ``stamp()`` summarizes the distribution onto the run report for
    EXPLAIN ANALYZE's tiled trailer. Bounded by construction: one
    fixed-size histogram, and spans ride the trace's own cap."""

    def __init__(self, session, **span_args):
        from cloudberry_tpu.obs.metrics import _Hist

        self._log = getattr(session, "stmt_log", None)
        self._h = _Hist()
        self._args = span_args   # what each span carries besides its tile

    def step(self, idx: int):
        import contextlib

        from cloudberry_tpu.obs import trace as OT

        @contextlib.contextmanager
        def _cm():
            st = OT.stage("tile-step", "launch_seconds", log=self._log,
                          tile=idx, **self._args)
            try:
                with st:
                    yield
            finally:
                # the report's distribution and ``tile_seconds`` take
                # the stage's own reading: one clock for all three
                self._h.add(st.dur)
                if self._log is not None and self._log.obs_enabled:
                    self._log.registry.observe("tile_seconds", st.dur)

        return _cm()

    def stamp(self, report: dict) -> None:
        if self._h.n:
            report["tile_time"] = {
                "count": self._h.n,
                "mean": round(self._h.total / self._h.n, 6),
                "p95": self._h.quantile(0.95),
            }


def _progress_tracker(exe, n_base: int, skip: int):
    """Live-progress feeder for a single-node tile loop
    (obs/progress.py): one lane — the remaining row prefix of the
    deterministic stream. A no-op object when the statement carries no
    Progress (obs off, or no lifecycle scope)."""
    from cloudberry_tpu.obs.progress import TileTracker, stream_rows

    total = stream_rows(exe.shape.stream, exe.session)
    return TileTracker(max(total - skip, 0), exe.tile_rows,
                       n_base=n_base, base_rows=min(skip, total),
                       rows_total=total)


class SkewSentinel:
    """Mid-statement adaptive-replan watcher for tiled-dist runs.

    The distributed step program already psums every redistribute's
    per-destination row counts for the capacity-forensics channel; the
    sentinel accumulates those vectors host-side across tiles and, when
    the CUMULATIVE distribution crosses the skew alarm
    (``config.feedback.replan_skew_ratio``, 0 = inherit
    ``config.obs.skew_ratio``), asks the session to re-plan the rest of
    the statement: it folds the observed counts into the feedback store
    as a partial sketch, force-checkpoints the carried state
    (exec/recovery.py), and raises TileReplan. Correctness never
    depends on it — an adaptation that cannot checkpoint simply
    disarms and the run finishes on the static plan.

    Guard rails, in check order: feature off / no recovery scope / too
    few tiles seen (``min_tiles``) / statement replan budget spent
    (``max_replans``) / no motion alarmed / ``tile_replan`` fault seam
    armed / checkpoint save failed."""

    def __init__(self, exe, motions, ctx):
        cfg = getattr(exe.session.config, "feedback", None)
        self.exe = exe
        self.session = exe.session
        self.motions = motions
        self.ctx = ctx
        self.min_tiles = cfg.min_tiles if cfg is not None else 2
        self.max_replans = cfg.max_replans if cfg is not None else 0
        self.threshold = float(
            (cfg.replan_skew_ratio or exe.session.config.obs.skew_ratio)
            if cfg is not None else 0.0)
        # collect: accumulate telemetry for the end-of-run fold (the
        # learning half works even with adaptation off); armed: the
        # mid-statement replan trigger itself
        self.collect = bool(cfg is not None and cfg.enabled and motions)
        self.armed = bool(
            self.collect and cfg.adaptive and ctx is not None
            and self.threshold > 0.0)
        self.cum = [np.zeros(exe.nseg, dtype=np.int64) for _ in motions]
        self.demand = [0] * len(motions)

    def observe(self, stats) -> None:
        """Fold one tile's per-motion (required-bucket scalar, psum'd
        per-destination row vector) pairs, traversal order matching
        ``self.motions``."""
        if not self.collect:
            return
        # counter-pinned host fetch: when feedback is off (or the plan
        # has no stat motions) the loop never even passes stats in, so
        # this stays 0 — the no-host-sync contract tests rely on
        log = getattr(self.session, "stmt_log", None)
        if log is not None:
            log.bump("tile_stat_syncs")
        for i, (bucket, rows) in enumerate(stats):
            self.demand[i] = max(self.demand[i], int(np.asarray(bucket)))
            self.cum[i] += np.asarray(rows, dtype=np.int64)

    def _pin(self) -> bool:
        """Stamp the cumulative observations onto the partial plan's
        motions the way record_motion_stats does for the one-shot path;
        True when anything flowed."""
        any_rows = False
        for m, c, d in zip(self.motions, self.cum, self.demand):
            if int(c.sum()) > 0:
                m._seg_rows = c.copy()
                any_rows = True
            if d > 0:
                # per-TILE demand, not cumulative: the rung a re-seeded
                # tiled run needs is the largest single-tile bucket
                m._observed_bucket = max(
                    d, getattr(m, "_observed_bucket", 0) or 0)
        return any_rows

    def fold_final(self) -> None:
        """End-of-run fold: the one-shot dist path folds in
        execute_distributed, the tiled stream folds here."""
        if not self.collect:
            return
        from cloudberry_tpu.plan import feedback as FB

        if self._pin():
            FB.fold_plan(self.session, self.exe.shape.partial_plan)

    def _worst(self):
        worst = None
        for m, c in zip(self.motions, self.cum):
            total = int(c.sum())
            if total <= 0:
                continue
            ratio = float(c.max()) * len(c) / total
            if ratio >= self.threshold and (worst is None
                                            or ratio > worst[1]):
                worst = (m, ratio)
        return worst

    def maybe_replan(self, tiles_local: int, payload_fn,
                     settle=None) -> None:
        """Raise TileReplan when the cumulative distribution alarms and
        the adaptation can resume safely; no-op otherwise.

        ``settle`` is the windowed-dispatch hook (exec/tilepipe.py): a
        zero-arg callable that drains every in-flight tile (folding
        their observations) and returns the new drained-tile count. The
        alarm fires on DRAINED telemetry, but the snapshot must capture
        the carried accumulator, which on accelerators is only valid
        for the newest dispatched step — settling first makes every
        dispatched tile verified-clean, so ``payload_fn`` (the live
        accumulator) and ``tiles_local`` agree again. At window=1 the
        queue is already empty and settle is a no-op, preserving the
        legacy sequence exactly."""
        from cloudberry_tpu.exec import recovery as R
        from cloudberry_tpu.lifecycle import current_handle
        from cloudberry_tpu.obs import trace as OT

        if not self.armed or tiles_local < self.min_tiles:
            return
        session = self.session
        # the replan budget rides the STATEMENT handle (session.sql
        # re-dispatches under the same one), and only handles the
        # session marked adaptation-safe (reads) may restart
        handle = current_handle()
        if handle is None or not getattr(handle, "adaptive_ok", False):
            return
        if getattr(handle, "tile_replans", 0) >= self.max_replans:
            return
        worst = self._worst()
        if worst is None:
            return
        if fault_point("tile_replan"):
            self.armed = False      # seam: suppress the adaptation
            return
        if settle is not None:
            # drain the in-flight window: a check that fires here aborts
            # the replan and rides the normal adaptive-retry path; the
            # drained tiles' observations fold into the cumulative view
            tiles_local = settle()
            worst = self._worst()
            if worst is None:       # the tail un-alarmed the ratio
                return
        # Publish what we actually saw BEFORE deciding to restart: pin
        # the cumulative counts on the partial plan's motions and fold a
        # partial sketch — the re-planned statement prices against it.
        from cloudberry_tpu.plan import feedback as FB

        self._pin()
        FB.fold_plan(session, self.exe.shape.partial_plan, partial=True)
        # The replanned run must resume from HERE, not re-stream: a
        # failed save disarms the sentinel and the static plan finishes.
        if not self.ctx.force_snapshot(tiles_local, payload_fn):
            self.armed = False
            return
        handle.tile_replans = getattr(handle, "tile_replans", 0) + 1
        log = getattr(session, "stmt_log", None)
        if log is not None:
            log.bump("tile_replans")
        import time as _t
        OT.stage_since("tile-replan", _t.perf_counter(), None, None,
                       tile=tiles_local, ratio=round(worst[1], 3))
        raise R.TileReplan(
            f"[tile {tiles_local}] cumulative redistribute skew "
            f"{worst[1]:.2f}x crossed the adaptive replan alarm "
            f"{self.threshold:.2f}x; carried state checkpointed",
            tiles_done=tiles_local, ratio=worst[1])


class AdaptiveTiledMixin:
    """Shared adaptive-retry discipline for tiled executables (single-node
    and distributed): classify a detected overflow, grow the guilty buffer
    (accumulator / join pair buffer) or shrink the tile, and re-run — the
    increase-nbatch-and-rescan loop of nodeHash.c, never truncation.

    Requires from the concrete class: ``shape`` (with ``partial_plan`` and
    ``g_cap``), ``tile_rows``, ``budget``, ``report``, ``_compiled``,
    ``_refresh_report()``, ``_run_once()``, ``_groups_ceiling()``, and
    ``_what`` (human name for the budget error)."""

    _what = "tiled execution"

    def refresh_bufpool_charge(self) -> None:
        """Re-stamp the report's ``est_bufpool_bytes``. The report is
        built once per compile but the pool's residency for the
        streamed table moves between statements (admits during a prior
        run, evictions, topology sweeps) — dispatch-time capacity
        recording and report publication both re-read it."""
        bpool = BUF.pool_for(self.session)
        self.report["est_bufpool_bytes"] = (
            bpool.table_bytes(self.shape.stream.table_name)
            if bpool is not None else 0)

    def _publish_report(self) -> None:
        self.refresh_bufpool_charge()
        self.session.last_tiled_report = dict(self.report)

    def _run_adaptive(self) -> ColumnBatch:
        from cloudberry_tpu.lifecycle import check_cancel

        while True:
            # cancel seam: each adaptive round restarts the whole tile
            # stream — a cancelled/over-deadline statement stops between
            # rounds instead of re-streaming the table
            check_cancel()
            try:
                batch = self._run_once()
                log = getattr(self.session, "stmt_log", None)
                X.count_join_shapes(log, X.join_shapes(self._whole_plan()))
                streams = getattr(self.shape, "streams", ())
                if streams and log is not None:
                    log.bump("tile_build_streams", len(streams))
                    log.bump("tile_build_rows", self._build_rows)
                    log.bump("tile_build_table_bytes",
                             sum(bs.table_bytes for bs in streams))
                return batch
            except X.ExecError as e:
                msg = str(e)
                shape = self.shape
                if not msg.startswith("[tile"):
                    # prelude/finalize failure: expansion overflows grow
                    # that join's pair buffer and retry
                    if not X.grow_expansion(shape.partial_plan, msg):
                        raise
                elif ("merge overflow" in msg
                      or "aggregation overflow" in msg):
                    # more groups than estimated: grow the accumulator and
                    # restart the stream — never truncate. Doubling (the
                    # nbatch discipline of nodeHash.c) overshoots the true
                    # group count by at most 2×, which matters downstream:
                    # the distributed finalize merges nseg·g_cap rows.
                    ceiling = self._groups_ceiling()
                    if shape.g_cap >= ceiling:
                        raise
                    shape.g_cap = min(shape.g_cap * 2, ceiling)
                elif "expansion overflow" in msg:
                    # a tile's join fanout blew its pair buffer: grow that
                    # join when the budget allows, else halve the tile
                    if not (self._try_grow(msg)
                            or self._try_halve_tile()):
                        raise
                elif "redistribute overflow" in msg:
                    # an estimate-sized bucket overflowed inside a tile:
                    # smaller tiles shrink every per-tile send bound
                    if not self._try_halve_tile():
                        raise
                else:
                    raise
                if getattr(self, "_deferred_fail", False):
                    # the failed check had already been outrun by newer
                    # in-flight launches (exec/tilepipe.py): this retry
                    # IS the deferred-failure window replay — it resumes
                    # from the last drained-clean checkpoint
                    self._deferred_fail = False
                    log = getattr(self.session, "stmt_log", None)
                    if log is not None:
                        log.bump("tile_window_replays")
                self._compiled = None
                self._refresh_report()
                # a grown accumulator may blow the step budget: smaller
                # tiles buy the room back before giving up
                while self._over_budget() and self._try_halve_tile():
                    self._refresh_report()
                if self._over_budget():
                    raise X.ExecError(
                        f"{self._what} working set (accumulator "
                        f"{shape.g_cap} groups, tile {self.tile_rows} "
                        "rows) exceeds the query memory budget "
                        f"{self.budget >> 20} MiB; raise "
                        "config.resource.query_mem_bytes") from e

    def _over_budget(self) -> bool:
        return self.report["est_step_bytes"] > self.budget

    def _try_grow(self, msg: str) -> bool:
        """Grow the overflowing spine join's pair buffer if the grown step
        still fits the budget; revert (and report False) otherwise."""
        node = X.find_expansion_node(self.shape.partial_plan, msg)
        if node is None:
            return False
        old = getattr(node, "_min_out_cap", 0)
        node._min_out_cap = max(node.out_capacity * 4, 64)
        self._refresh_report()
        if self.report["est_step_bytes"] <= self.budget:
            return True
        node._min_out_cap = old
        self._refresh_report()
        return False

    def _try_halve_tile(self) -> bool:
        if self.tile_rows <= _MIN_TILE:
            return False
        self.tile_rows >>= 1
        return True



class TiledExecutable(AdaptiveTiledMixin):
    """Compiled tiled statement: prelude (once) → step (per tile) →
    finalize. ``report`` records the spill decision for tests/EXPLAIN."""

    def __init__(self, shape: _TileShape, session, tile_rows: int,
                 budget: int):
        self.shape = shape
        self.session = session
        self.tile_rows = tile_rows
        self.budget = budget
        self._platform = jax.default_backend()
        self._compiled = None
        # server handler threads may hit the cached runner concurrently;
        # retries mutate shared plan capacities, so runs serialize (the
        # admission gate bounds statement concurrency anyway)
        import threading

        self._run_lock = threading.Lock()
        self._bprogs: dict = {}     # id(build stream) -> its programs
        self._build_rows = 0        # rows the last run's build streams read
        self._refresh_report()

    def _refresh_report(self) -> None:
        shape = self.shape
        _retile(shape, self.tile_rows)
        self.report = {
            "tiled": True,
            "stream_table": shape.stream.table_name,
            "tile_rows": self.tile_rows,
            "acc_capacity": shape.g_cap,
            "est_step_bytes": max(_step_bytes(shape),
                                  _build_phase_bytes(shape)),
            # scan-pipeline staging charge (exec/scanpipe.py) plus the
            # dispatch window's extra in-flight tiles (exec/tilepipe.py)
            # — obs/capacity.record_tiled adds both to the statement's
            # observed peak
            "est_pipeline_bytes": SP.queue_charge_bytes(
                shape.stream, self.tile_rows, self.session.config)
            + TP.window_charge_bytes(
                shape.stream, self.tile_rows, self.session.config,
                self._platform),
            # HBM buffer-pool residency attributable to the streamed
            # table (exec/bufferpool.py) — charged into the capacity
            # plane next to the pipeline's staging bytes
            "est_bufpool_bytes": _bufpool_charge(
                self.session, shape.stream.table_name),
            "budget_bytes": self.budget,
        }
        _stamp_build_streams(self.report, shape)

    # ------------------------------------------------------------ programs

    def _resident_inputs(self, bs: Optional[_BuildStream] = None) -> dict:
        """All step inputs except the tile: whole (non-stream) tables and
        pruned store reads — exactly prepare_inputs minus the stream. The
        probe phase reads no scan of a streamed build; a build stream
        ``bs`` reads its build's, but the one it streams."""
        if bs is None:
            away = {id(s) for b in self.shape.streams
                    for s in X.scans_of(b.join.build)}
            top, stream = self._whole_plan(), self.shape.stream
        else:
            away, top, stream = set(), bs.join.build, bs.shape.stream
        scans = [s for s in X.scans_of(top)
                 if s is not stream and id(s) not in away]
        store_scans = [s for s in scans if hasattr(s, "_store_parts")]
        names = sorted({s.table_name for s in scans
                        if not hasattr(s, "_store_parts")})
        return X._assemble_inputs(names, store_scans, self.session, None)

    def _whole_plan(self) -> N.PlanNode:
        # scans live under the partial plan (spine + builds); the post
        # chain/finalize reference only aggregate outputs
        return self.shape.partial_plan

    # -------------------------------------------------------- build streams

    def _build_tables(self) -> list:
        """Phase one of a statement with build streams: each streamed
        build's table, in ``shape.streams`` order, under one
        ``build-stream`` span. It takes no checkpoint: a fault here
        restarts the statement, and a resumed probe stream streams its
        builds again first (the tables are a function of the data)."""
        from cloudberry_tpu.obs import trace as OT

        if self._compiled is None:      # first run, or a retry re-planned
            self._bprogs = {}
        self._build_rows = 0
        if not self.shape.streams:
            return []
        with OT.stage("build-stream", "launch_seconds"):
            return [self._stream_build(bs) for bs in self.shape.streams]

    def _build_programs(self, bs: _BuildStream):
        """(prelude, step) of one build stream: the prelude computes the
        build's own resident builds once; the step runs the build's spine
        over one tile of its scan and scatters the selected rows into the
        table it carries (donated), checked as the one-shot lookup is."""
        shape = self.shape
        plat = self._platform
        join = bs.join
        prelude_fn = _prelude_fn(bs.shape, plat, root=shape.partial_plan)

        def step_fn(resident, prelude, tile, tile_n, table):
            low = _step_lowerer(bs.shape, plat, resident, prelude, tile,
                                tile_n, root=shape.partial_plan)
            cols, sel = low.lower(join.build)
            present, payload = table
            with jax.named_scope(f"n{low.ref(join)}:{X.node_kind(join)}"):
                keys = [low.expr(k, cols) for k in join.build_keys]
                if join.build_key_valid is not None:
                    sel = sel & low.expr(join.build_key_valid, cols)
                rows = {n: X._as_column(cols[n], sel.shape[0])
                        for n in payload}
                for n, t in payload.items():
                    if rows[n].dtype != t.dtype:
                        raise X.ExecError(
                            f"build stream of {low.label(join)}: column {n} "
                            f"is {rows[n].dtype}, its table {t.dtype}")
                present, payload, has_dup, past = K.direct_table_insert(
                    present, payload, keys, sel, bs.box, rows, bs.unique)
            checks = dict(low.checks)
            checks[f"join build key past its proven span {bs.slots} "
                   f"{low.label(join)}"] = past
            if bs.unique:
                checks[f"join build side has duplicate keys "
                       f"{low.label(join)} but the planner assumed a unique "
                       "(PK) build side"] = has_dup
            return (present, payload), checks

        # one program set of the statement's, as _compile counts its own
        X.count_compile(self.session)
        titles = X.node_titles(shape.partial_plan)
        return (PG.jit(prelude_fn, titles, "tiled build prelude"),
                PG.jit(step_fn, titles, "tiled build step",
                       donate_argnums=TP.step_donation(self._platform)))

    def _stream_build(self, bs: _BuildStream):
        """Stream one build's scan through the tile feed (reader thread,
        buffer pool, dispatch window) into its table; the table."""
        progs = self._bprogs.get(id(bs))
        if progs is None:
            progs = self._bprogs[id(bs)] = self._build_programs(bs)
        prelude_fn, step_fn = progs
        resident = self._resident_inputs(bs)
        prelude, pchecks = prelude_fn(resident)
        X.raise_checks(pchecks)
        table = bs.empty_table()
        timer = _TileTimer(self.session, phase="build")
        pipe = TP.TilePipe(self.session, TP.effective_window(
            self.session.config, self._platform))
        feed = _tile_feed(bs.shape.stream, self.session, bs.tile_rows,
                          min_depth=pipe.window,
                          span_args={"phase": "build"})
        n = 0
        try:
            for tile, tile_n in feed:
                fault_point("tile_step")
                with timer.step(n):
                    table, checks = step_fn(
                        resident, prelude, tile,
                        jnp.asarray(tile_n, dtype=jnp.int32), table)
                    pipe.submit(n, checks)
                n += 1
                self._build_rows += int(tile_n)
            pipe.drain_all()
        finally:
            SP.close_feed(feed)
        return table

    def _compile(self):
        if self._compiled is not None:
            return self._compiled
        shape = self.shape
        plat = self._platform
        prelude_fn = _prelude_fn(shape, plat)
        group_names = [n for n, _ in shape.agg.group_keys]
        specs = shape.merge_specs
        g_cap = shape.g_cap

        def step_fn(resident, prelude, tile, tile_n, acc):
            low = _step_lowerer(shape, plat, resident, prelude, tile,
                                tile_n)
            pcols, psel = low.lower(shape.partial_plan)
            checks = dict(low.checks)
            acc_cols, acc_sel = acc
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
                    # the one-shot executor's grouped aggregation: tiled
                    # and one-shot results cannot diverge
                    ok, oa, osel, n_groups = K.group_aggregate(
                        key_cols, agg_vals, specs, sel, g_cap,
                        carried=shape.agg.carried)
                    checks["tile merge overflow: more groups than capacity "
                           f"{g_cap}; raise the aggregation capacity"] = \
                        n_groups > g_cap
                    return ({**ok, **oa}, osel), checks
                agg_vals = {s.out_name: jnp.concatenate(
                    [acc_cols[s.out_name], pcols[s.out_name]])
                    for s in specs}
                sel = jnp.concatenate([acc_sel, psel])
                out = K.global_aggregate(agg_vals, specs, sel)
                return (out, jnp.ones((1,), dtype=jnp.bool_)), checks

        def finalize_fn(acc):
            acc_cols, acc_sel = acc
            low = X.Lowerer(
                {}, platform=plat, root=shape.partial_plan,
                replace={id(_leaf_of(shape.root)): (acc_cols, acc_sel)})
            cols, sel = low.lower(shape.root)
            out = {f.name: cols[f.name] for f in shape.root.fields}
            return out, sel, low.checks

        # a statement-level program set built: the engine's compile
        # counter moves here as it does in compile_plan
        X.count_compile(self.session)
        titles = X.node_titles(shape.partial_plan, shape.root)
        self._compiled = (
            PG.jit(prelude_fn, titles, "tiled prelude"),
            PG.jit(step_fn, titles, "tiled step",
                   donate_argnums=TP.step_donation(self._platform)),
            PG.jit(finalize_fn, titles, "tiled finalize"))
        return self._compiled

    def _init_acc(self):
        shape = self.shape
        g_cap = shape.g_cap
        group_names = {n for n, _ in shape.agg.group_keys}
        cols = {}
        if group_names:
            for f in shape.partial_plan.fields:
                cols[f.name] = jnp.zeros((g_cap,), dtype=f.type.np_dtype)
            return cols, jnp.zeros((g_cap,), dtype=jnp.bool_)
        for f, spec in zip(
                [f for f in shape.partial_plan.fields
                 if f.name not in group_names], shape.merge_specs):
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
            cols[f.name] = jnp.full((1,), ident)
        # identity row stays unselected: min/max identities must not leak
        # into the merge as real values when a tile contributes rows
        return cols, jnp.zeros((1,), dtype=jnp.bool_)

    # ----------------------------------------------------------------- run

    def run(self) -> ColumnBatch:
        with self._run_lock:
            return self._run_adaptive()

    def _groups_ceiling(self) -> int:
        return self.shape.agg.capacity

    def _run_once(self) -> ColumnBatch:
        from cloudberry_tpu.exec import recovery as R
        from cloudberry_tpu.obs import trace as OT

        # children of ``launch`` on the statement thread (obs/trace.py):
        # [build-stream] | prelude | (feed-wait | h2d | tile-step ⊃
        # drain-stall)* | finalize
        tables = self._build_tables()
        with OT.stage("prelude", "launch_seconds"):
            prelude_fn, step_fn, finalize_fn = self._compile()
            resident = self._resident_inputs()
            prelude, pchecks = prelude_fn(resident)
            X.raise_checks(pchecks)
            prelude = _with_tables(self.shape, prelude, tables)

            # mid-statement recovery (exec/recovery.py): resume from the
            # last K-tile checkpoint instead of replaying the whole
            # stream
            ctx = R.begin(self, dist=False)
            acc = self._init_acc()
            if ctx is not None:
                acc = ctx.restore_acc(acc)
            skip = ctx.skip_rows if ctx is not None else 0
            n_base = ctx.tiles_base if ctx is not None else 0
            n_local = 0
            n_sub = 0
            timer = _TileTimer(self.session)
            tracker = _progress_tracker(self, n_base, skip)
            pipe = TP.TilePipe(self.session, TP.effective_window(
                self.session.config, self._platform))
            feed = _tile_feed(self.shape.stream, self.session,
                              self.tile_rows, skip_rows=skip,
                              min_depth=pipe.window)

        def _verified(d):
            # host effects for ONE drained-clean tile, in stream order
            # and in the legacy sequence: progress, then the K-tile
            # checkpoint tick (a staged payload when the submit saw the
            # boundary coming; the live accumulator at window=1, where
            # drain is synchronous and acc IS this tile's state)
            nonlocal n_local
            tile_k, staged = d.payload
            n_local = tile_k
            tracker.step(tile_k)
            if ctx is not None:
                ctx.tick(tile_k, staged if staged is not None
                         else (lambda: R.acc_payload(acc)))

        try:
            for tile, tile_n in feed:
                fault_point("tile_step")
                fault_point("tile_device_lost")
                n_sub += 1
                stage = (ctx is not None and pipe.window > 1
                         and ctx.snapshot_due(n_sub))
                with timer.step(n_base + n_sub - 1):
                    acc, checks = step_fn(resident, prelude, tile,
                                          jnp.asarray(tile_n,
                                                      dtype=jnp.int32),
                                          acc)
                    staged = TP.stage_checkpoint(acc) if stage else None
                    drained = pipe.submit(n_base + n_sub - 1, checks,
                                          (n_sub, staged))
                for d in drained:
                    _verified(d)
            for d in pipe.drain_all():
                _verified(d)
        finally:
            # deterministic teardown on EVERY exit (cancel, overflow
            # retry, device loss): the reader joins and staged tiles
            # release — no orphan thread, no pinned prefetch buffers;
            # abandoned in-flight launches just complete into garbage-
            # collected buffers (nothing to join on the device side)
            if pipe.deferred_fail:
                self._deferred_fail = True
            SP.close_feed(feed)
        with OT.stage("finalize", "launch_seconds"):
            SP.stamp_report(self.report, feed)
            n_tiles = n_base + n_local
            timer.stamp(self.report)
            pipe.stamp(self.report)
            if n_tiles == 0:  # empty stream: an all-masked tile seeds acc
                empty = _empty_tile(self.shape.stream, self.tile_rows)
                acc, checks = step_fn(resident, prelude, empty,
                                      jnp.asarray(0, dtype=jnp.int32), acc)
                _raise_tile_checks(checks, 0)
                n_tiles = 1

            fault_point("tiled_finalize")
            from cloudberry_tpu.lifecycle import check_cancel

            check_cancel()
            cols, sel, fchecks = finalize_fn(acc)
            X.raise_checks(fchecks)
            self.report["n_tiles"] = n_tiles
            if ctx is not None:
                ctx.stamp_report(self.report)
            self._publish_report()
            return X.make_batch(self.shape.root, cols, sel)


class TopNTiledExecutable(TiledExecutable):
    """Tiled statement whose accumulator is the best LIMIT+OFFSET rows
    seen so far (nodeSort.c bounded-heap role): step = spine over one
    tile, then one bounding sort over (accumulator ∪ tile rows), keeping
    the first g_cap positions — selected rows sort first, so the slice
    is exactly the running top-N. Finalize runs the original post chain
    (LIMIT/projections) over the sorted accumulator."""

    _what = "top-N tiled execution"

    def _groups_ceiling(self) -> int:
        return self.shape.g_cap  # fixed: LIMIT itself bounds the acc

    def _init_acc(self):
        shape = self.shape
        cols = {f.name: jnp.zeros((shape.g_cap,), dtype=f.type.np_dtype)
                for f in shape.partial_plan.fields}
        return cols, jnp.zeros((shape.g_cap,), dtype=jnp.bool_)

    def _refresh_report(self) -> None:
        super()._refresh_report()
        self.report["mode"] = "topn"

    def _compile(self):
        if self._compiled is not None:
            return self._compiled
        shape = self.shape
        plat = self._platform
        m = shape.g_cap
        mleaf, msort = shape.finalize["mleaf"], shape.finalize["msort"]
        names = [f.name for f in shape.partial_plan.fields]
        prelude_fn = _prelude_fn(shape, plat)

        def step_fn(resident, prelude, tile, tile_n, acc):
            low = _step_lowerer(shape, plat, resident, prelude, tile,
                                tile_n)
            pcols, psel = low.lower(shape.partial_plan)
            checks = dict(low.checks)
            acc_cols, acc_sel = acc
            with jax.named_scope("tile:merge"):
                ccols = {n: jnp.concatenate([acc_cols[n], pcols[n]])
                         for n in names}
                csel = jnp.concatenate([acc_sel, psel])
            low2 = X.Lowerer({}, platform=plat, root=shape.partial_plan,
                             replace={id(mleaf): (ccols, csel)})
            scols, ssel = low2.lower(msort)
            checks.update(low2.checks)
            return ({n: scols[n][:m] for n in names}, ssel[:m]), checks

        def finalize_fn(acc):
            acc_cols, acc_sel = acc
            low = X.Lowerer(
                {}, platform=plat, root=shape.partial_plan,
                replace={id(_leaf_of(shape.root)): (acc_cols, acc_sel)})
            cols, sel = low.lower(shape.root)
            out = {f.name: cols[f.name] for f in shape.root.fields}
            return out, sel, low.checks

        # a statement-level program set built: the engine's compile
        # counter moves here as it does in compile_plan
        X.count_compile(self.session)
        self._compiled = (
            PG.jit(prelude_fn, X.node_titles(shape.partial_plan),
                   "tiled prelude"),
            PG.jit(step_fn, X.node_titles(shape.partial_plan, msort),
                   "tiled top-N step",
                   donate_argnums=TP.step_donation(self._platform)),
            PG.jit(finalize_fn,
                   X.node_titles(shape.partial_plan, shape.root),
                   "tiled finalize"))
        return self._compiled


class SortTiledExecutable(TiledExecutable):
    """Tiled statement whose result is a FULL ORDER BY with no bounding
    limit — the external-merge-sort analog (tuplesort.c spill mode,
    workfile_mgr.c's tape role played by host RAM). Per tile, the step
    program runs the spine and emits the surviving rows together with
    one order-normalized u64 column per sort key (same normalization
    kernels.sort_indices uses, so device and host orders cannot
    disagree — descending keys bit-complement, NULL ordering rides the
    binder's is-null companion keys). The host appends each tile's rows
    to the run store; the merge pass is one stable host key sort over
    the collected runs, then the post chain (column pruning, LIMIT)
    applies host-side."""

    _what = "external-sort tiled execution"

    def _groups_ceiling(self) -> int:
        return 0  # no accumulator exists to grow

    def _refresh_report(self) -> None:
        shape = self.shape
        _retile(shape, self.tile_rows)
        self.report = {
            "tiled": True,
            "mode": "sort",
            "stream_table": shape.stream.table_name,
            "tile_rows": self.tile_rows,
            "acc_capacity": 0,
            "est_step_bytes": max(_step_bytes(shape),
                                  _build_phase_bytes(shape)),
            "est_pipeline_bytes": SP.queue_charge_bytes(
                shape.stream, self.tile_rows, self.session.config)
            + TP.window_charge_bytes(
                shape.stream, self.tile_rows, self.session.config,
                self._platform),
            "est_bufpool_bytes": _bufpool_charge(
                self.session, shape.stream.table_name),
            "budget_bytes": self.budget,
        }
        _stamp_build_streams(self.report, shape)

    def _compile(self):
        if self._compiled is not None:
            return self._compiled
        shape = self.shape
        plat = self._platform
        sort = shape.sortnode
        names = [f.name for f in sort.child.fields]
        prelude_fn = _prelude_fn(shape, plat)

        def step_fn(resident, prelude, tile, tile_n):
            low = _step_lowerer(shape, plat, resident, prelude, tile,
                                tile_n)
            pcols, psel = low.lower(shape.partial_plan)
            n = psel.shape[0]
            keys = []
            for e, asc in sort.keys:
                arr = X._as_column(X._sortable(e, sort.child, pcols), n)
                u = K.sort_key_u64(arr)
                keys.append(u if asc else ~u)
            out = {nm: X._as_column(pcols[nm], n) for nm in names}
            return (out, psel, keys), low.checks

        # a statement-level program set built: the engine's compile
        # counter moves here as it does in compile_plan
        X.count_compile(self.session)
        titles = X.node_titles(shape.partial_plan)
        self._compiled = (PG.jit(prelude_fn, titles, "tiled prelude"),
                          PG.jit(step_fn, titles, "tiled sort step"))
        return self._compiled

    def _stream_sorted(self):
        """Run the tile stream and the merge pass; returns
        (sorted child columns, sorted normalized key columns, n_tiles,
        recovery ctx) as host arrays."""
        from cloudberry_tpu.exec import recovery as R

        tables = self._build_tables()
        prelude_fn, step_fn = self._compile()
        shape = self.shape
        resident = self._resident_inputs()
        prelude, pchecks = prelude_fn(resident)
        X.raise_checks(pchecks)
        prelude = _with_tables(shape, prelude, tables)

        ctx = R.begin(self, dist=False)
        names = [f.name for f in shape.sortnode.child.fields]
        runs: dict[str, list] = {nm: [] for nm in names}
        key_runs: list[list] = [[] for _ in shape.sortnode.keys]
        if ctx is not None:
            runs, key_runs = ctx.restore_runs(runs, key_runs)
        skip = ctx.skip_rows if ctx is not None else 0
        n_base = ctx.tiles_base if ctx is not None else 0
        n_local = 0
        n_sub = 0
        timer = _TileTimer(self.session)
        tracker = _progress_tracker(self, n_base, skip)
        pipe = TP.TilePipe(self.session, TP.effective_window(
            self.session.config, self._platform))
        feed = _tile_feed(shape.stream, self.session,
                          self.tile_rows, skip_rows=skip,
                          min_depth=pipe.window)

        def _verified(d):
            # one drained-clean tile: the run-store appends happen HERE
            # (materializing the async copies started at submit), so the
            # host collects tile k's rows while tiles k+1..k+W-1 compute;
            # the checkpoint payload is the runs themselves — host state,
            # exactly as of this tile, no staging needed
            nonlocal n_local
            tile_k, pcols, psel, keys = d.payload
            n_local = tile_k
            tracker.step(tile_k)
            mask = np.asarray(psel)
            for nm in names:
                runs[nm].append(np.asarray(pcols[nm])[mask])
            for i, k in enumerate(keys):
                key_runs[i].append(np.asarray(k)[mask])
            if ctx is not None:
                ctx.tick(tile_k,
                         lambda: R.runs_payload(runs, key_runs))

        try:
            for tile, tile_n in feed:
                fault_point("tile_step")
                fault_point("tile_device_lost")
                n_sub += 1
                with timer.step(n_base + n_sub - 1):
                    (pcols, psel, keys), checks = step_fn(
                        resident, prelude, tile,
                        jnp.asarray(tile_n, dtype=jnp.int32))
                    drained = pipe.submit(n_base + n_sub - 1, checks,
                                          (n_sub, pcols, psel, keys))
                for d in drained:
                    _verified(d)
            for d in pipe.drain_all():
                _verified(d)
        finally:
            if pipe.deferred_fail:
                self._deferred_fail = True
            SP.close_feed(feed)
        SP.stamp_report(self.report, feed)
        timer.stamp(self.report)
        pipe.stamp(self.report)

        fault_point("tiled_finalize")
        from cloudberry_tpu.lifecycle import check_cancel

        check_cancel()
        cols, karr = merge_sorted_runs(runs, key_runs,
                                       shape.sortnode.child.fields,
                                       len(shape.sortnode.keys))
        return cols, karr, max(n_base + n_local, 1), ctx

    def _run_once(self) -> ColumnBatch:
        shape = self.shape
        cols, _karr, n_tiles, ctx = self._stream_sorted()
        cols = host_apply_post(shape.post, cols)
        n_out = len(next(iter(cols.values()))) if cols else 0
        self.report["n_tiles"] = n_tiles
        if ctx is not None:
            ctx.stamp_report(self.report)
        self._publish_report()
        out_node = shape.post[0] if shape.post else shape.sortnode
        return X.make_batch(out_node, cols,
                            np.ones((n_out,), dtype=bool))


class WindowTiledExecutable(SortTiledExecutable):
    """Tiled window functions — the nodeWindowAgg.c spill analog. Phase
    one reuses the external-sort stream, ordered by (partition keys,
    order keys), so the host holds every surviving spine row grouped by
    partition. Phase two packs WHOLE partitions into fixed-capacity
    chunks and runs the original window (+ projection chain) on device
    once per chunk: window functions never cross partitions, so chunks
    are independent and every frame kind stays exact — no carry state.
    Only a single partition larger than the chunk capacity cannot
    stream; that raises with a clear message (the reference's
    one-partition tuplestore has the same working-set floor)."""

    _what = "windowed tiled execution"

    def _refresh_report(self) -> None:
        super()._refresh_report()
        self.report["mode"] = "window"

    def _chunk_fn(self):
        if getattr(self, "_chunk_compiled", None) is not None:
            return self._chunk_compiled
        shape = self.shape
        win = shape.winnode
        plat = self._platform
        cap = self.tile_rows

        def run_chunk(chunk_cols, n_valid):
            sel = jnp.arange(cap) < n_valid
            low = X.Lowerer(
                {}, platform=plat, root=shape.partial_plan,
                replace={id(win.child): (chunk_cols, sel)})
            cols, osel = low.lower(shape.root)
            out = {f.name: cols[f.name] for f in shape.root.fields}
            return out, osel, low.checks

        self._chunk_compiled = PG.jit(
            run_chunk, X.node_titles(shape.partial_plan, shape.root),
            "tiled window chunk")
        return self._chunk_compiled

    def _run_once(self) -> ColumnBatch:
        shape = self.shape
        self._chunk_compiled = None  # capacity may have changed
        cols, karr, n_tiles, ctx = self._stream_sorted()
        names = [f.name for f in shape.winnode.child.fields]
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


def window_chunk_pass(run, root, names, cols, karr, npk, cap):
    """Phase two of window spill, shared by the single-node and
    distributed executables: pack WHOLE partitions (runs of equal
    normalized chunk keys) into fixed-capacity chunks and feed each
    through the jitted window program ``run``. Returns (output columns,
    chunk count)."""
    out_fields = root.fields
    n = len(cols[names[0]]) if names else 0
    if n == 0:
        return ({f.name: np.zeros((0,), dtype=f.type.np_dtype)
                 for f in out_fields}, 0)
    new_part = np.zeros(n, dtype=bool)
    new_part[0] = True
    for k in karr[:npk]:
        new_part[1:] |= k[1:] != k[:-1]
    starts = np.flatnonzero(new_part)
    sizes = np.diff(np.append(starts, n))
    if sizes.max(initial=0) > cap:
        raise X.ExecError(
            f"windowed tiled execution: one partition holds "
            f"{int(sizes.max())} rows, more than the {cap}-row chunk "
            "the memory budget allows; raise "
            "config.resource.query_mem_bytes")
    outs: dict[str, list] = {f.name: [] for f in out_fields}
    n_chunks = 0
    chunk_lo = chunk_hi = 0

    def flush(lo, hi):
        nonlocal n_chunks
        if hi <= lo:
            return
        m = hi - lo
        chunk = {}
        for nm in names:
            a = cols[nm][lo:hi]
            if m < cap:
                a = np.concatenate(
                    [a, np.zeros((cap - m,), dtype=a.dtype)])
            chunk[nm] = a
        ocols, osel, checks = run(chunk, jnp.asarray(m, dtype=jnp.int32))
        _raise_tile_checks(checks, n_chunks)
        n_chunks += 1
        mask = np.asarray(osel)
        for nm in outs:
            outs[nm].append(np.asarray(ocols[nm])[mask])

    for s, size in zip(starts, sizes):
        if chunk_hi - chunk_lo + size > cap and chunk_hi > chunk_lo:
            flush(chunk_lo, chunk_hi)
            chunk_lo = s
        chunk_hi = s + size
    flush(chunk_lo, chunk_hi)
    final = {nm: np.concatenate(arrs) if arrs else
             np.zeros((0,), dtype=root.field(nm).type.np_dtype)
             for nm, arrs in outs.items()}
    return final, n_chunks


def _prelude_fn(shape: _TileShape, plat: str,
                root: Optional[N.PlanNode] = None):
    """The prelude program of a tile stream: every spine join's build
    computed once, but a streamed build's (its table is built before).
    ``root``: the plan its nodes are numbered in (``shape``'s partial
    plan, or the statement's where ``shape`` is a build's own)."""
    streamed = {id(bs.join.build) for bs in shape.streams}
    root = root or shape.partial_plan

    def prelude_fn(tables):
        low = X.Lowerer(tables, platform=plat, root=root)
        outs = [None if id(b) in streamed else low.lower_shared(b)
                for b in shape.builds]
        return outs, low.checks
    return prelude_fn


def _step_lowerer(shape: _TileShape, plat: str, resident, prelude, tile,
                  tile_n, root: Optional[N.PlanNode] = None) -> X.Lowerer:
    """The lowerer of one step over ``tile`` of ``shape``'s stream:
    ``prelude`` (one entry a build) stands for each build computed once,
    or for a streamed build's table, which its join reads."""
    tables = dict(resident)
    tables["$tile"] = tile
    streamed = {id(bs.join.build): bs for bs in shape.streams}
    replace, direct = {}, {}
    for b, out in zip(shape.builds, prelude):
        bs = streamed.get(id(b))
        if bs is None:
            replace[id(b)] = out
        else:
            direct[id(bs.join)] = (bs.box, out)
    return X.Lowerer(tables, platform=plat, root=root, replace=replace,
                     stream=shape.stream, tile_n=tile_n,
                     direct_tables=direct)


def _with_tables(shape: _TileShape, prelude: list, tables: list) -> list:
    """The prelude's outputs with each streamed build's table in its
    place (the prelude computes none of those)."""
    if not shape.streams:
        return prelude
    by_build = {id(bs.join.build): t for bs, t in zip(shape.streams, tables)}
    return [by_build.get(id(b), out) for b, out in zip(shape.builds, prelude)]


def _stamp_build_streams(report: dict, shape: _TileShape) -> None:
    """The run report's account of the build streams: for each, the
    table it streams, its tile, and its table's bytes."""
    if shape.streams:
        report["build_streams"] = [
            {"table": bs.shape.stream.table_name, "tile_rows": bs.tile_rows,
             "slots": bs.slots, "table_bytes": bs.table_bytes}
            for bs in shape.streams]


def _leaf_of(root: N.PlanNode) -> N.PlanNode:
    cur = root
    while not isinstance(cur, _AccLeaf):
        cur = cur.child  # post chain + finalize project are all unary
    return cur


def _raise_tile_checks(checks: dict, tile_idx: int) -> None:
    # the per-tile cancel seam (the CHECK_FOR_INTERRUPTS row-boundary
    # analog): every step/chunk of the single-node AND distributed tiled
    # executables passes through here, so cancellation latency is bounded
    # by one tile's device launch
    from cloudberry_tpu.lifecycle import check_cancel

    check_cancel()
    for msg, bad in checks.items():
        if bool(np.asarray(bad).any()):
            # (the class the one-shot lowering's check raises, as
            # X.raise_checks gives it: a semantic error, never retried)
            cls = X.DuplicateBuildKeyError if "duplicate keys" in msg \
                else X.ExecError
            raise cls(f"[tile {tile_idx}] {msg}")


def _expr_dict(plan: N.PlanNode, e: ex.Expr):
    if isinstance(e, ex.ColumnRef):
        try:
            return plan.field(e.name).sdict
        except KeyError:
            return None
    return None


# -------------------------------------------------------------- tile feed


def _phys_cols(scan: N.PScan) -> list[str]:
    return sorted(set(scan.column_map) | set(scan.mask_map))


def _empty_tile(scan: N.PScan, tile_rows: int) -> dict:
    t = {}
    for phys in scan.column_map:
        t[phys] = np.zeros((tile_rows,), dtype=np.int64)
    for phys in scan.mask_map:
        t[f"$nn:{phys}"] = np.zeros((tile_rows,), dtype=np.bool_)
    return t


def _tile_feed(scan: N.PScan, session, tile_rows: int,
               skip_rows: int = 0, min_depth: int = 1,
               span_args: Optional[dict] = None):
    """The single-node tile feed: (tile dict of padded arrays, n_valid)
    items, wrapped in the asynchronous scan pipeline when
    ``config.scan_pipeline`` enables it (exec/scanpipe.py — prefetch +
    column-parallel decode + double-buffered device staging; tile order
    and content are the synchronous feed's, bit-identical on/off).
    Cold tables stream micro-partition files (host staging: the device
    never holds more than one tile); warm tables slice their RAM
    arrays. ``skip_rows`` drops the already-consumed prefix — the
    mid-statement resume entry point (exec/recovery.py): single-node
    consumption is always a prefix of the deterministic stream order.
    Callers must close the feed (scanpipe.close_feed) on every exit."""
    from cloudberry_tpu.obs import trace as OT

    stats = SP.ScanStats()
    if hasattr(scan, "_store_parts"):
        # the reader thread's part-read spans name the stage that
        # started this feed as their parent
        gen = _store_tiles(scan, session, tile_rows, skip_rows, stats,
                           parent=OT.current_stage())
    else:
        gen = _ram_tiles(scan, session, tile_rows, skip_rows)
    # min_depth: the dispatch window (exec/tilepipe.py) keeps up to W
    # tiles in flight — a prefetch queue shallower than W would starve
    # the window it exists to feed
    return SP.maybe_pipeline(gen, session.config, device_stage=True,
                             stats=stats, min_depth=min_depth,
                             span_args=span_args)


def _ram_tiles(scan: N.PScan, session, tile_rows: int,
               skip_rows: int = 0):
    """Warm-table tile producer: slices of the resident RAM arrays."""
    t = session.catalog.table(scan.table_name)
    t.ensure_loaded()
    cols = {phys: np.asarray(t.data[phys]) for phys in scan.column_map}
    for phys in scan.mask_map:
        vm = t.validity.get(phys)
        cols[f"$nn:{phys}"] = (np.asarray(vm, dtype=np.bool_)
                               if vm is not None
                               else np.ones(t.num_rows, dtype=np.bool_))
    rows = t.num_rows
    for off in range(min(skip_rows, max(rows, 0)), max(rows, 0),
                     tile_rows):
        n = min(tile_rows, rows - off)
        yield _pad_tile(cols, off, n, tile_rows), n


class _PendBuf:
    """Offset-cursor ring over decoded partition chunks. ``take(n)``
    copies ONLY the emitted rows — each row at most once, never the
    whole pending tail the old code re-concatenated per emitted tile
    (O(n²) over a partition). A tile covering a chunk EXACTLY hands
    the chunk array over zero-copy; partial-chunk tiles copy rather
    than emit a view, because a view's base is the whole decoded
    partition column and the prefetch queue would pin partitions, not
    tiles (the out-of-core bound is one partition + bounded staging).
    ``skip(n)`` advances the cursor without touching a byte (the
    resume prefix). All columns share one chunk-length spine, so the
    cursor is maintained once."""

    def __init__(self, stats=None):
        self._names: Optional[list[str]] = None
        self._chunks: dict[str, list] = {}
        self._lens: list[int] = []
        self._off = 0           # consumed rows of the FIRST chunk
        self.rows = 0           # rows pending past the cursor
        self._stats = stats

    def append(self, cols: dict) -> None:
        n = len(next(iter(cols.values()))) if cols else 0
        if self._names is None:
            self._names = list(cols)
            self._chunks = {nm: [] for nm in self._names}
        if n == 0:
            return
        for nm in self._names:
            self._chunks[nm].append(cols[nm])
        self._lens.append(n)
        self.rows += n

    def _plan(self, n: int):
        """Slice plan [(chunk_idx, lo, hi)] covering the next n rows,
        plus the advanced cursor (chunks_to_drop, new_offset)."""
        plan = []
        i, off, need = 0, self._off, n
        while need > 0:
            length = self._lens[i]
            t = min(length - off, need)
            plan.append((i, off, off + t))
            need -= t
            off += t
            if off == length:
                i += 1
                off = 0
        return plan, i, off

    def _advance(self, drop: int, off: int, n: int) -> None:
        for _ in range(drop):
            self._lens.pop(0)
            for nm in self._names:
                self._chunks[nm].pop(0)
        self._off = off
        self.rows -= n

    def skip(self, n: int) -> None:
        _, drop, off = self._plan(n)
        self._advance(drop, off, n)

    def take(self, n: int) -> dict:
        plan, drop, off = self._plan(n)
        whole = (len(plan) == 1 and plan[0][1] == 0
                 and plan[0][2] == self._lens[plan[0][0]])
        out = {}
        for nm in self._names:
            chunks = self._chunks[nm]
            if whole:
                out[nm] = chunks[plan[0][0]]
            else:
                parts = [chunks[i][lo:hi] for i, lo, hi in plan]
                out[nm] = parts[0].copy() if len(parts) == 1 \
                    else np.concatenate(parts)
        if self._stats is not None:
            if whole:
                self._stats.view_rows += n
            else:
                self._stats.copy_rows += n
        self._advance(drop, off, n)
        return out


def _bufpool_charge(session, table: str) -> int:
    """The buffer pool's resident bytes for one table — the tiled
    report's ``est_bufpool_bytes`` capacity-plane charge."""
    bpool = BUF.pool_for(session)
    return bpool.table_bytes(table) if bpool is not None else 0


def _pool_chunk(scan: N.PScan, ent: dict) -> dict:
    """Assemble one feed chunk from a buffer-pool entry (the canonical
    ``{"cols", "validity"}`` read_partitions split) — the exact dict
    the cold path builds, so pooled and decoded chunks are
    interchangeable bit-for-bit."""
    cols, validity = ent["cols"], ent["validity"]
    n = len(next(iter(cols.values()))) if cols else 0
    chunk = {}
    for phys in scan.column_map:
        chunk[phys] = cols[phys]
    for phys in scan.mask_map:
        vm = validity.get(phys)
        chunk[f"$nn:{phys}"] = (vm if vm is not None
                                else np.ones(n, dtype=np.bool_))
    return chunk


def _store_tiles(scan: N.PScan, session, tile_rows: int,
                 skip_rows: int = 0, stats=None, parent=None):
    """Stream a pruned cold scan part-by-part, re-chunked to tile_rows:
    the out-of-core path — peak host memory is one partition + the
    pipeline's bounded staging. A resume's ``skip_rows`` drops whole
    already-consumed partitions WITHOUT reading or decoding them (the
    replay cost of a checkpointed restart is bounded by one partition
    plus ≤ K tiles, never the consumed prefix). Partitions resident in
    the HBM buffer pool (exec/bufferpool.py) are served from the device
    copy — no read, no decode, no host→device transfer; only misses go
    to the store (and hot misses are admitted for next time)."""
    store = session.catalog.store
    needed = _phys_cols(scan)
    stats = stats if stats is not None else SP.ScanStats()
    pool = SP.decode_pool(session.config)
    bpool = BUF.pool_for(session)
    cols_key = tuple(needed)
    log = getattr(session, "stmt_log", None)
    obs = log is not None and getattr(log, "obs_enabled", False)
    buf = _PendBuf(stats)
    skip_left = max(int(skip_rows), 0)

    parts = list(scan._store_parts)
    start = 0
    for part in parts:
        eff = int(part["num_rows"]) - len(part.get("deleted") or ())
        if skip_left < eff:
            break
        skip_left -= eff
        start += 1
        stats.parts_skipped += 1

    def drain(final: bool):
        nonlocal skip_left
        if skip_left > 0 and buf.rows > 0:
            t = min(skip_left, buf.rows)
            buf.skip(t)  # sub-partition resume remainder: cursor only
            skip_left -= t
        while buf.rows >= tile_rows or (final and buf.rows > 0):
            take = min(tile_rows, buf.rows)
            yield _pad_tile(buf.take(take), 0, take, tile_rows), take

    from cloudberry_tpu.obs import trace as OT

    for part in parts[start:]:
        key = ent = None
        # one partition, on whichever thread runs this generator (the
        # scan reader when pipelined): the pool's answer, else the wall
        # of read + checksum + column-parallel decode
        with OT.stage("part-read", "feed_seconds", log=log, parent=parent,
                      table=scan.table_name, partition=part["file"],
                      pool="hit") as st:
            if bpool is not None:
                key = BUF.partition_key(session, scan.table_name, part,
                                        cols_key)
                ent = bpool.lookup(key, log)
            if ent is None:
                st.args["pool"] = "miss"
                fault_point("scan_decode")
                dts: list = []  # per-column decode seconds (atomic append)
                cols, validity = store.read_partitions(
                    scan.table_name, [part], needed, pool=pool,
                    on_decode=dts.append)
                st.args["bytes"] = sum(
                    int(np.asarray(v).nbytes)
                    for d in (cols, validity) for v in d.values())
        if ent is not None:
            # HBM hit: the decoded chunk is already on-device — the
            # host path (read/decode/transfer) is skipped entirely,
            # like the resume parts_skipped fast path
            stats.parts_resident += 1
            buf.append(_pool_chunk(scan, ent))
            yield from drain(final=False)
            continue
        stats.read_s += st.dur  # report and histogram: one reading
        stats.parts_read += 1
        stats.decode_s += sum(dts)
        if log is not None:
            log.bump("host_decodes")
        if obs:
            for dt in dts:
                log.registry.observe("decode_seconds", dt)
        ent = {"cols": {c: np.asarray(v) for c, v in cols.items()},
               "validity": {c: np.asarray(v, dtype=np.bool_)
                            for c, v in validity.items()}}
        chunk = _pool_chunk(scan, ent)
        stats.bytes_decoded += sum(int(a.nbytes)
                                   for a in chunk.values())
        if bpool is not None:
            bpool.offer(key, ent, table=scan.table_name, log=log)
        buf.append(chunk)
        yield from drain(final=False)
    yield from drain(final=True)


def _pad_tile(cols: dict, off: int, n: int, tile_rows: int) -> dict:
    out = {}
    for name, arr in cols.items():
        if not isinstance(arr, np.ndarray) and off == 0 \
                and n == tile_rows and len(arr) == tile_rows:
            # device-resident (buffer-pool) column covering the tile
            # exactly: hand it through — routing it via numpy would
            # round-trip HBM→host→HBM
            out[name] = arr
            continue
        sl = arr[off:off + n]
        if n < tile_rows:
            sl = np.concatenate(
                [sl, np.zeros((tile_rows - n,), dtype=arr.dtype)])
        out[name] = np.ascontiguousarray(sl)
    return out
