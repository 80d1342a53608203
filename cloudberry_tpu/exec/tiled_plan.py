"""The tiled rewrite: which statements stream, as what shape, at what tile.

The reference survives bigger-than-memory queries by spilling operator state
to workfiles (src/backend/utils/workfile_manager/workfile_mgr.c, the batch
discipline of nodeHash.c) under a vmem red zone
(src/backend/utils/mmgr/redzone_handler.c). The XLA translation cannot page
a running program, so the spill boundary moves to PLAN TIME: when the
admission estimator (exec/resource.py) rejects a plan, this module re-plans
it as a STREAM OF FIXED-SHAPE TILES of its probe-side scan. It holds the
analysis only; exec/tiled.py runs what it finds, on one segment or on the
segment mesh.

A ``TileShape`` is the plan cut at the stream: the post chain above, the
spine of joins/filters/projections down to the streamed scan, the spine
joins' build subtrees (computed once by a prelude program), and one mode:

- "agg": a partial aggregation per tile merges into a fixed-capacity
  accumulator (the combine-function discipline of the distributed
  two-stage agg, plan/distribute.py:_split_aggs — partials merge
  associatively, so any tile order and count gives the same answer); a
  finalize program applies the post-aggregation chain;
- "topn": ORDER BY + LIMIT keeps the best LIMIT+OFFSET rows in a bounded
  row accumulator (the tuplesort bounded-heap role, nodeSort.c);
- "sort": an unbounded ORDER BY keeps every surviving row in host RAM
  (tuplesort.c's spill-to-tape mode; one stable key sort is the merge);
- "window": a window stack streams as the external sort grouped by its
  specs' common partition keys, then windows whole-partition chunks.

On one segment (``analyze`` / ``fit``) a spine join whose build does not
fit the budget, but whose build keys have a proven span (plan/joincap.py
``direct_box``), is a BUILD STREAM (``BuildStream``): before the probe
stream starts, the build's own scan is streamed in tiles and each tile's
rows are scattered into a direct-address table over that span
(``kernels.direct_table_insert``) — the batch-file discipline of
nodeHash.c with the table, not the build, resident.

On the mesh (``analyze_mesh`` / ``fit_mesh``) the plan is already
distributed: the stream is tiled PER SEGMENT, the spine's redistribute
motions run inside every step with bucket capacity min(planned, tile) — a
tile of T rows can never send more than T rows to one destination — and
the accumulators take the partial aggregation's place in the ORIGINAL
distributed plan, whose merge motion (gather / redistribute by group keys),
final aggregation and post chain the finalize program runs unchanged.

Per-tile capacities keep the engine's checked-overflow discipline: a tile
that overflows its expansion-join or group buffers raises, never truncates.
Peak device memory per segment is the admitted estimate: resident builds +
streamed builds' tables + one tile's working set + the accumulator —
independent of the streamed tables' sizes.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import jax.numpy as jnp
import numpy as np

from cloudberry_tpu.exec import executor as X
from cloudberry_tpu.exec import kernels as K
from cloudberry_tpu.exec.resource import estimate_plan_memory
from cloudberry_tpu.plan import expr as ex
from cloudberry_tpu.plan import nodes as N
from cloudberry_tpu.plan.cost import estimate_rows
from cloudberry_tpu.plan.distribute import (_all_exprs, _finalize_project,
                                            _split_aggs)

_MAX_TILE = 1 << 22
MIN_TILE = 1 << 12


class _AccLeaf(N.PlanNode):
    """Plan leaf standing for the accumulator in the finalize program."""

    def title(self):
        return "TileAccumulator"


@dataclass
class TileShape:
    """Everything the rewrite discovered about the plan (see the module
    docstring for the modes). The mesh's own fields are last."""

    agg: Optional[N.PAgg]             # one segment: the streamed aggregation
    post: list[N.PlanNode]            # chain above agg/sort, root first
    spine: list[N.PlanNode]           # partial .. just above the stream
    stream: N.PScan                   # the tiled scan
    builds: list[N.PlanNode]          # spine joins' build subtrees
    stream_rows: int = 0              # whole-stream rows (floor scaling)
    partial_plan: N.PlanNode = None   # type: ignore[assignment]
    merge_specs: list = field(default_factory=list)
    group_names: list = field(default_factory=list)
    root: N.PlanNode = None           # type: ignore[assignment]
    # the node the accumulator (a window chunk) stands in for under root
    replace_node: N.PlanNode = None   # type: ignore[assignment]
    g_cap: int = 0                    # accumulator capacity (groups / rows)
    max_groups: int = 0               # hard ceiling for g_cap growth
    mode: str = "agg"
    sortnode: Optional[N.PSort] = None  # topn/sort: the (synthetic) sort
    msort: Optional[N.PSort] = None     # topn: acc ∪ tile bounding sort
    winnode: Optional[N.PWindow] = None  # window mode: BOTTOM of the stack
    wintop: Optional[N.PWindow] = None   # window mode: TOP of the stack
    n_ckeys: int = 0                  # window mode: chunk-key count
    # spine joins whose builds stream into direct tables (``_fit``)
    streams: list = field(default_factory=list)
    stmt: N.PlanNode = None           # type: ignore[assignment]
    decline: str = ""                 # why the budget cannot hold it
    merge_motion: Optional[N.PMotion] = None  # mesh: motion above partial
    final_agg: Optional[N.PAgg] = None        # mesh: merge aggregation


@dataclass
class BuildStream:
    """A spine join whose build streams into a direct-address table over
    its keys' proven box before the probe stream starts: ``shape`` is the
    build's own spine down to the scan it streams (its own builds
    resident), ``box`` one ``(least value, span)`` a key, ``payload`` the
    build columns an inner or left join carries, with their dtypes."""

    join: N.PJoin
    shape: TileShape
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


# ------------------------------------------------------- shared recognizers


def host_post_ok(nodes, sort_keys=None) -> bool:
    """True when a chain above a spilled sort can apply HOST-SIDE after
    the merge pass: column-pruning projections, LIMIT/OFFSET, gather
    motions (no-ops — the host already pools every segment's rows) and
    sorts on the same keys (already satisfied by the merge order). One
    predicate for both layouts so the recognizers cannot drift from
    exec/tiled.py host_apply_post."""
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


def _lowest_sort(chain: list) -> Optional[int]:
    """The index of the lowest sort of a chain fed only by projections
    and filters (part of the stream), or None."""
    sort_i = next((i for i in range(len(chain) - 1, -1, -1)
                   if isinstance(chain[i], N.PSort)), None)
    if sort_i is None or any(not isinstance(n, (N.PProject, N.PFilter))
                             for n in chain[sort_i + 1:]):
        return None
    return sort_i


def _full_sort_shape(chain: list):
    """Unbounded ORDER BY shape: the lowest sort, with only a
    host-applicable chain above it — the external-sort path (host RAM is
    the tape: the device streams spine tiles and emits rows plus their
    order-normalized u64 keys, the host keeps the runs and one C-speed
    stable key sort is the merge pass). Returns the sort node, or None
    when the chain has a different shape."""
    sort_i = _lowest_sort(chain)
    if sort_i is None or not host_post_ok(chain[:sort_i],
                                          chain[sort_i].keys):
        return None
    return chain[sort_i]


def _topn_bound(chain: list, skip: tuple = ()):
    """Locate a topn-streamable post chain's bounding sort and LIMIT: the
    LOWEST sort, fed only by projections/filters (part of the stream),
    with a LIMIT above it separated only by projections and ``skip``
    nodes (gather motions, on the mesh). An interposed SORT breaks the
    walk — a limit above a different sort bounds THAT order, not this
    one's — and a filter above the sort could starve the limit of rows
    the accumulator already dropped. Returns (sortnode, limit+offset) or
    None. Shared by both analyzers so the recognizers cannot drift."""
    sort_i = _lowest_sort(chain)
    if sort_i is None:
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


def _has_subquery(plan: N.PlanNode) -> bool:
    # subquery plans scan outside the spine budget
    return any(isinstance(sub, ex.SubqueryScalar)
               for e in _all_exprs(plan) for sub in ex.walk(e))


def _partial_agg(agg: N.PAgg, partial_aggs) -> N.PAgg:
    """The per-tile partial aggregation of ``agg`` (mode/fields mirror
    the distributed two-stage construction, plan/distribute.py:532)."""
    partial = N.PAgg(agg.child, agg.group_keys, partial_aggs,
                     capacity=agg.capacity, mode="partial",
                     carried=agg.carried)
    partial.fields = [
        N.PlanField(n, e.dtype, _expr_dict(agg.child, e))
        for n, e in agg.group_keys
    ] + [N.PlanField(n, c.dtype, None) for n, c in partial_aggs]
    return partial


def _topn_merge(shape: TileShape) -> None:
    """The top-N step's merge plan: a bounding sort over the
    accumulator ∪ the tile's rows, an ``_AccLeaf`` under it."""
    mleaf = _AccLeaf()
    mleaf.fields = list(shape.partial_plan.fields)
    shape.msort = N.PSort(mleaf, list(shape.sortnode.keys))
    shape.msort.fields = list(mleaf.fields)


def _expr_dict(plan: N.PlanNode, e: ex.Expr):
    if isinstance(e, ex.ColumnRef):
        try:
            return plan.field(e.name).sdict
        except KeyError:
            return None
    return None


# ------------------------------------------------------------- one segment


def analyze(plan: N.PlanNode) -> Optional[TileShape]:
    """Recognize a streamable shape on one segment: a post chain over one
    aggregation ("agg"), one bounding ORDER BY + LIMIT ("topn"), one
    unbounded ORDER BY ("sort") or a window stack ("window"), over a
    join/filter spine whose probe path ends at a scan."""
    if _has_subquery(plan):
        return None

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
        # align (checked in _fit_window).
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
    shape = TileShape(agg, post, *walk)
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
    """(spine, scan, builds, scan rows) of a filter/projection/join chain
    whose probe path ends at a scan (the scan a tile stream reads), or
    None. The probe side of a statement's stream and a build stream's
    own (``_build_stream``) are found by this one walk."""
    spine: list[N.PlanNode] = []
    builds: list[N.PlanNode] = []
    cur = top
    while True:
        if isinstance(cur, (N.PFilter, N.PProject, N.PRuntimeFilter)):
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


def fit(shape: TileShape, session) -> Optional[int]:
    """The tile rows of a one-segment ``shape``, with its mode's merge
    and finalize plans built; None, with ``shape.decline`` saying why
    where the plan shape or the budget cannot support it. A top-N whose
    LIMIT+OFFSET exceeds any resident accumulator falls back to the full
    external sort, which applies the limit host-side."""
    budget = session.config.resource.query_mem_bytes
    if shape.mode == "topn":
        t = _fit_topn(shape, session, budget)
        if t is not None:
            return t
        shape.mode = "sort"
        shape.g_cap = 0
    if shape.mode == "sort":
        return _fit_sort(shape, session, budget)
    if shape.mode == "window":
        return _fit_window(shape, session, budget)
    return _fit_agg(shape, session, budget)


def _fit_agg(shape: TileShape, session, budget: int) -> Optional[int]:
    agg = shape.agg
    try:
        partial_aggs, final_aggs, finalize = _split_aggs(agg.aggs)
    except ValueError:
        shape.decline = "an aggregate with no partial and merge form"
        return None
    shape.merge_specs = [K.AggSpec(call.func, name)
                         for name, call in final_aggs]
    shape.group_names = [n for n, _ in agg.group_keys]
    shape.max_groups = agg.capacity

    # Accumulator capacity: the binder's agg capacity is the worst case
    # (child rows) — useless as a resident buffer. Size from the NDV-based
    # group estimate with 4× headroom; a merge overflow at runtime grows it
    # and retries (the nodeHash.c increase-nbatch discipline) rather than
    # ever returning truncated groups.
    est_groups = estimate_rows(agg, session.catalog)
    shape.g_cap = int(min(agg.capacity, max(1024, 4 * int(est_groups) + 1)))
    shape.partial_plan = _partial_agg(agg, partial_aggs)

    tile_rows = _fit(shape, session, budget)
    if tile_rows is None:
        return None

    # finalize plan: (acc leaf) -> finalize project -> original post chain
    leaf = shape.replace_node = _AccLeaf()
    leaf.fields = list(shape.partial_plan.fields)
    fproj = _finalize_project(leaf, agg, finalize)
    if shape.post:
        shape.post[-1].child = fproj
        shape.root = shape.post[0]
    else:
        shape.root = fproj
    return tile_rows


def _fit_topn(shape: TileShape, session, budget: int) -> Optional[int]:
    """Top-N streaming: the accumulator holds the best LIMIT+OFFSET rows
    of the sort's child so far; each tile merges through one bounding
    sort. The post chain above the sort (LIMIT, projections) finalizes
    over the sorted accumulator."""
    shape.partial_plan = shape.sortnode.child
    tile_rows = _fit(shape, session, budget)
    if tile_rows is None:
        return None  # LIMIT too large for a resident accumulator
    _topn_merge(shape)
    # finalize plan: (sorted acc leaf) -> original post chain above sort
    fleaf = shape.replace_node = _AccLeaf()
    fleaf.fields = list(shape.partial_plan.fields)
    shape.post[-1].child = fleaf  # post is non-empty: the LIMIT lives there
    shape.root = shape.post[0]
    return tile_rows


def _fit_sort(shape: TileShape, session, budget: int) -> Optional[int]:
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
    tile_rows = _fit(shape, session, budget)
    if tile_rows is None:
        return None
    shape.root = shape.post[0] if shape.post else shape.sortnode
    return tile_rows


def _fit_window(shape: TileShape, session, budget: int) -> Optional[int]:
    """Window spill: phase one is the external-sort stream grouped by
    the partition keys COMMON to every spec in the stack; phase two
    windows whole-partition chunks on device — each chunk re-sorts per
    spec, so only the grouping must align. A stack with no common
    partition key is one giant partition — nothing bounds its working
    set, so it cannot stream (the reference buffers that case too)."""
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
    shape.partial_plan = shape.replace_node = bottom.child
    tile_rows = _fit(shape, session, budget)
    if tile_rows is None:
        return None
    shape.root = shape.post[0] if shape.post else shape.wintop
    return tile_rows


def retile(shape: TileShape, tile_rows: int) -> None:
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


def _acc_width(shape: TileShape) -> int:
    return 1 + sum(f.type.np_dtype.itemsize
                   for f in shape.partial_plan.fields)


def _merge_bytes(shape: TileShape) -> int:
    """Accumulator + merge working set: the concat of acc and per-tile
    rows flowing through one sort-based group_aggregate (agg mode) or
    one bounding sort (topn mode)."""
    out = shape.partial_plan.capacity if shape.mode == "agg" \
        else _out_cap(shape.partial_plan)
    return 3 * (shape.g_cap + out) * _acc_width(shape)


def _choose_tile(shape: TileShape, budget: int) -> Optional[int]:
    """Largest power-of-two tile whose estimated step memory fits: the
    spill-file-count decision of workfile_mgr, made at plan time."""
    t = _MAX_TILE
    while t >= MIN_TILE:
        retile(shape, t)
        if step_bytes(shape) <= budget:
            return t
        t >>= 1
    return None


def step_bytes(shape: TileShape) -> int:
    """One probe step's estimate: the partial plan at its tile, the
    streamed builds' tables in their subtrees' place, the merge."""
    tables = {id(bs.join): bs.table_bytes for bs in shape.streams}
    return estimate_plan_memory(shape.partial_plan, tables).peak_bytes \
        + _merge_bytes(shape)


def build_phase_bytes(shape: TileShape) -> int:
    """The build streams' peak: every table, and the largest build
    tile's working set beside them."""
    if not shape.streams:
        return 0
    return sum(bs.table_bytes for bs in shape.streams) \
        + max(bs.step_bytes() for bs in shape.streams)


def _fit(shape: TileShape, session, budget: int) -> Optional[int]:
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
    t = MIN_TILE
    retile(shape, t)
    why.append(f"one {t}-row tile's step is estimated at "
               f"{step_bytes(shape) / mib:.1f} MiB against the budget "
               f"{budget / mib:.1f} MiB")
    for bs in shape.streams:
        bs.untile()
    shape.streams.clear()
    shape.decline = "; ".join(why)
    return None


def _build_stream(join: N.PJoin, shape: TileShape, session,
                  budget: int):
    """(a ``BuildStream`` for ``join``, "") where its build can stream
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
    bshape = TileShape(None, [], *walk, mode="build")
    bshape.partial_plan = join.build
    payload = [(n, np.dtype(join.build.field(n).type.np_dtype))
               for n in carried]
    bs = BuildStream(join, bshape, box, payload,
                     whole_rows=bshape.stream.capacity)
    held = sum(b.table_bytes for b in shape.streams) + bs.table_bytes
    t = _MAX_TILE
    while t >= MIN_TILE:
        retile(bshape, t)
        if held + bs.step_bytes() <= budget:
            bs.tile_rows = t
            return bs, ""
        t >>= 1
    bs.untile()
    return None, (f"its table of {bs.table_bytes / (1 << 20):.1f} MiB and "
                  "one build tile do not fit")


# -------------------------------------------------------------------- mesh


def analyze_mesh(plan: N.PlanNode, session) -> Optional[TileShape]:
    """Recognize the streamable distributed shape: post chain (projections /
    sorts / limits / gather motions) over a two-stage aggregation
    (final ← motion ← partial) — or a colocated one-stage aggregation,
    a top-N, an external sort or a window stack — over a
    join/filter/redistribute spine whose probe path ends at a
    partitioned scan."""
    if _has_subquery(plan):
        return None
    post: list[N.PlanNode] = []
    cur = plan
    while isinstance(cur, (N.PProject, N.PSort, N.PLimit, N.PFilter)) or (
            isinstance(cur, N.PMotion) and cur.kind == "gather"):
        post.append(cur)
        cur = cur.child
    if isinstance(cur, N.PWindow):
        return _analyze_mesh_window(plan, post, cur, session)
    if not isinstance(cur, N.PAgg):
        return _analyze_mesh_topn(plan, post, session)

    if cur.mode == "final":
        final_agg = cur
        motion = final_agg.child
        if not isinstance(motion, N.PMotion) \
                or motion.kind not in ("gather", "redistribute"):
            return None
        partial = motion.child
        if not isinstance(partial, N.PAgg) or partial.mode != "partial":
            return None
        walk = _spine_walk_mesh(partial.child, session)
        if walk is None:
            return None
        return TileShape(
            None, post, *walk, partial_plan=partial,
            merge_specs=[K.AggSpec(call.func, name)
                         for name, call in final_agg.aggs],
            group_names=[n for n, _ in partial.group_keys],
            root=plan, replace_node=partial, max_groups=partial.capacity,
            merge_motion=motion, final_agg=final_agg)

    if cur.mode != "single":
        return None
    # one-stage colocated aggregation: build the partial/merge split the
    # one-segment rewrite uses; the accumulator IS the final state per
    # segment (groups are colocated), so finalize is just the
    # finalize-projection + post chain
    agg = cur
    try:
        partial_aggs, final_aggs, finalize = _split_aggs(agg.aggs)
    except ValueError:
        return None
    walk = _spine_walk_mesh(agg.child, session)
    if walk is None:
        return None
    partial = _partial_agg(agg, partial_aggs)
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
    return TileShape(
        None, post, *walk, partial_plan=partial,
        merge_specs=[K.AggSpec(call.func, name) for name, call in final_aggs],
        group_names=[n for n, _ in agg.group_keys], root=root,
        replace_node=leaf, max_groups=agg.capacity)


def _analyze_mesh_topn(plan, post, session) -> Optional[TileShape]:
    """ORDER BY + LIMIT with no aggregation: per-segment bounded top-N
    accumulators. Every segment keeps the best LIMIT+OFFSET rows of ITS
    stream — the global top-N is a subset of that union — and finalize
    re-runs the ORIGINAL plan (pre-gather compaction, gather, sorts,
    limits) over the accumulators as one SPMD program."""
    # motions in the chain are gathers (the walk guarantees): row-set-
    # preserving, so the limit search may cross them
    hit = _topn_bound(post, skip=(N.PMotion,))
    if hit is None:
        return _analyze_mesh_sort(plan, post, session)
    sortnode, m = hit
    walk = _spine_walk_mesh(sortnode.child, session)
    if walk is None:
        return None
    shape = TileShape(
        None, post[:post.index(sortnode)], *walk,
        partial_plan=sortnode.child, root=plan,
        replace_node=sortnode.child, g_cap=m, max_groups=m, mode="topn",
        sortnode=sortnode)
    _topn_merge(shape)
    return shape


def _analyze_mesh_sort(plan, post, session) -> Optional[TileShape]:
    """Unbounded ORDER BY: the external-sort stream runs per segment (the
    spine's own motions execute per tile); the host pools every
    segment's rows — the gather is subsumed by collection — and the
    merge pass plus the chain above the sort apply host-side."""
    sort_i = next((i for i in range(len(post) - 1, -1, -1)
                   if isinstance(post[i], N.PSort)), None)
    if sort_i is None:
        return None
    sortnode = post[sort_i]
    if not host_post_ok(post[:sort_i], sortnode.keys):
        return None
    below = sortnode.child
    while isinstance(below, N.PMotion) and below.kind == "gather":
        below = below.child
    walk = _spine_walk_mesh(below, session)
    if walk is None:
        return None
    return TileShape(None, post[:sort_i], *walk, partial_plan=below,
                     root=plan, replace_node=below, mode="sort",
                     sortnode=sortnode)


def _analyze_mesh_window(plan, post, top_window,
                         session) -> Optional[TileShape]:
    """Window stack: phase one is the per-segment external-sort stream
    grouped by the stack's common partition keys; phase two runs
    whole-partition chunks through the ORIGINAL plan (gathers lower as
    identity on pooled host rows) on one device — chunks are
    independent, so no mesh is needed above the stream."""
    if not all(isinstance(nd, N.PMotion) and nd.kind == "gather"
               or isinstance(nd, N.PProject) and all(
                   isinstance(e, ex.ColumnRef) for _, e in nd.exprs)
               for nd in post):
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
    walk = _spine_walk_mesh(below, session)
    if walk is None:
        return None
    ckeys = list(common.values())
    srt = N.PSort(below, [(ck, True) for ck in ckeys])
    srt.fields = list(below.fields)
    return TileShape(None, post, *walk, partial_plan=below, root=plan,
                     replace_node=bottom.child, mode="window",
                     sortnode=srt, winnode=bottom, n_ckeys=len(ckeys))


def _spine_walk_mesh(top: N.PlanNode, session):
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


def fit_mesh(shape: TileShape, session) -> Optional[int]:
    """The per-segment tile rows of a mesh ``shape``, or None. A top-N
    whose LIMIT+OFFSET exceeds any resident accumulator falls back to the
    full external sort when the chain above its sort applies host-side."""
    nseg = session.config.n_segments
    if shape.mode == "agg":
        try:
            est_groups = estimate_rows(shape.partial_plan, session.catalog)
        except Exception:
            est_groups = 1024
        shape.g_cap = int(min(shape.max_groups,
                              max(1024, 4 * int(est_groups) + 1)))
        if not shape.group_names:
            shape.g_cap = 1
    budget = session.config.resource.query_mem_bytes
    tile_rows = _choose_tile_mesh(shape, budget, nseg)
    if tile_rows is None and shape.mode == "topn" \
            and host_post_ok(shape.post, shape.sortnode.keys):
        shape.mode = "sort"
        shape.g_cap = 0
        tile_rows = _choose_tile_mesh(shape, budget, nseg)
    if tile_rows is None:
        shape.decline = (f"no tile of {MIN_TILE} rows a segment or more "
                         f"fits the budget {budget / (1 << 20):.1f} MiB")
    return tile_rows


def retile_mesh(shape: TileShape, tile_rows: int, nseg: int) -> None:
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


def refinalize(shape: TileShape, nseg: int) -> None:
    """Size the finalize program's boundary for the accumulators. A
    segment's aggregate acc has at most g_cap rows, so a redistribute
    bucket (all of one source's acc to one destination) is bounded by
    g_cap, and the final aggregation sees at most nseg·g_cap rows; a
    top-N's gathers were sized for the whole stream, and receive nseg·m
    rows."""
    if shape.mode == "topn":
        for node in shape.post:
            if isinstance(node, N.PMotion):
                node.out_capacity = shape.g_cap * nseg
    if shape.merge_motion is not None:
        if shape.merge_motion.kind == "redistribute":
            shape.merge_motion.bucket_cap = shape.g_cap
        shape.merge_motion.out_capacity = shape.g_cap * nseg
    if shape.final_agg is not None:
        shape.final_agg.capacity = max(shape.g_cap * nseg, 1)


def finalize_bytes(shape: TileShape, nseg: int) -> int:
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


def _choose_tile_mesh(shape: TileShape, budget: int,
                      nseg: int) -> Optional[int]:
    if finalize_bytes(shape, nseg) > budget:
        return None  # no tile size can shrink the finalize program
    t = _MAX_TILE
    while t >= MIN_TILE:
        retile_mesh(shape, t, nseg)
        est = estimate_plan_memory(shape.partial_plan).peak_bytes
        if est + _merge_bytes(shape) <= budget:
            return t
        t >>= 1
    return None
