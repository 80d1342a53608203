"""Join capacities: a lookup join runs at rungs of the rows that reach it
and the rows it matches, where the planner expects either to be few.

A sorted-build lookup join (``PJoin.compacts``: inner or semi, unique
build, no residual) is lowered at its probe's capacity: one binary
search a probe ROW, selected or not, and one full-capacity gather a
payload word, and every node above it inherits that capacity. Where the
estimates say the probe's selected rows, or the join's matches, are a
small share of the capacity they arrive at, this pass stamps a capacity
of the join's own (``Lowerer._join`` compacts to it with
``kernels.compact_sparse``):

- ``probe_capacity``: the probe's selected rows, before the search;
- ``out_capacity``: the matched rows, after the match test and before
  the build rows and the payload words are gathered.

The rule, from what the planner can observe: an estimate under a
``SHARE``-th of the capacity the rows arrive at is stamped, at the power
of two (``kernels.rung_up``, the ladder a Motion's buckets climb) above
``SLACK`` times the estimate, floor ``FLOOR``; never at or above the
input's capacity. An estimate moves with a statement's literals and a
capacity is a program of its own to compile: on powers of two Q3's
thirty-one dates meet one. The capacities are checked at run time: an
overflow is a check that reports the rows that came, the session
answers it by ``grow`` (the power of two that holds them; at the
input's capacity the compaction is dropped) and a retry, never by a cut
row. A wrong estimate costs one retry and one compile, never an answer.

The estimates read only what the catalog knows of a table wherever its
rows are (``_Persisted``): a capacity is a shape of the program, and one
statement over one store must lower one program whether a backend has
the table in RAM or not.

The capacities are stamped where the statement's retry loop is entered
(``Session._run_with_growth``), not by the planner: a plan run by any
other caller (a DML rewrite, a cursor, EXPLAIN ANALYZE, a tiled step)
carries none and needs no retry. EXPLAIN stamps them for display.

Aggregates above a compacted join follow it: ``settle`` holds every
grouped aggregate's capacity to its child's (more groups than rows
cannot be), remembering the capacity the binder gave it, so that a
grown join's aggregate grows back with it.

A grouped aggregate is also held to a ceiling that is a PROOF
(``_group_ceiling``), never an estimate: an aggregation overflow is a
"cannot happen" check that no retry answers. Where every group key is a
plain column reference that traces back to a scan (``cost.col_origin``)
and can hold no NULL (a key an outer join can null-extend carries a
null mask, and has no ceiling), the groups number at most the product,
over the REFERENCES the keys come from, of the lesser of

- the scan's capacity: a column's values all come from its rows, and a
  scan emits no more rows than its capacity, by construction;
- the product, over the scan's integer keys, of their span (max - min
  + 1) by the zone maps of the micro-partitions the scan reads, or, for
  a table in RAM, by the range ``Table.set_data`` computes on every
  change of its data (a plan is keyed on the table's version).

The capacity is the rung (``kernels.row_rung_up``) above the least of
these and never above the child's. Q13's ``GROUP BY c_custkey`` over an
expansion of 1,671,168 pairs emits at customer's 151,552 rows, and its
second aggregate and sort run there; Q18's ``GROUP BY l_orderkey`` at
the key's span. An NDV, a histogram or a row estimate is never a
ceiling; a key that is an expression, a union's column or a table
whose rows change outside the engine's versions keeps the child's
capacity. A reference is a route from the aggregate down to a scan (the
side taken at each join), not a scan object: a CTE named twice is one
scan under two ``PShare`` nodes, and its two references multiply as two
scans of one table do.

One segment only: a distributed plan's capacities are per segment and
its aggregates and Motions are sized by ``plan/distribute.py`` (ROADMAP
S11).
"""

from __future__ import annotations

import math

from cloudberry_tpu.plan import expr as ex
from cloudberry_tpu.plan import nodes as N
from cloudberry_tpu.types import DType

SHARE = 16      # stamp where estimate × SHARE < the input's capacity
SLACK = 4       # ... at the power of two above SLACK × the estimate
FLOOR = 1024    # ... and not under FLOOR rows


class _Persisted:
    """The catalog's statistics that do not depend on where a table's
    rows are: its row count, its columns' ranges and histograms, its
    manifest's uniqueness flags, and the distinct counts of a table that
    lives in RAM only. A STORED table
    that a backend has loaded counts distinct values on demand
    (``Table.ndv``) and one it has not cannot; sized from those, a cold
    backend and a warm one would lower two programs for one statement
    over one store (two compiles of minutes each at SF1), so a stored
    table's capacities see none. ``est_slot``, ``unique_slot``: such
    estimates and uniqueness are kept on the nodes beside the planner's
    own (``cost.estimate_rows``, ``binder._unique_sets``), not in their
    place."""

    est_slot = "_est_rows_persisted"
    unique_slot = "_unique_sets_persisted"

    def __init__(self, catalog):
        self._catalog = catalog

    def table(self, name: str) -> "_PersistedTable":
        return _PersistedTable(self._catalog.table(name))


class _PersistedTable:
    def __init__(self, table):
        self._table = table
        self.stats = table.stats
        self.num_rows = table.num_rows

    def ndv(self, col: str):
        t = self._table
        return t.ndv(col) if t.backing is None else None

    def is_unique(self, col: str) -> bool:
        """A stored table's column is unique where its manifest can say
        so (``TableStore._unique_flags``: integer kinds, the flags kept
        current on every append), whether its rows are loaded or not."""
        t = self._table
        arr = t.data.get(col)
        if t.backing is not None and (arr is None
                                      or arr.dtype.kind not in "iu"):
            return False
        return t.is_unique(col)

    def is_unique_cols(self, cols: tuple) -> bool:
        """As ``is_unique``; a stored table's manifest flags single
        columns only (a cold table's rule, ``Table.is_unique_cols``)."""
        if self._table.backing is None:
            return self._table.is_unique_cols(cols)
        return any(self.is_unique(c) for c in cols)


def _post_order(plan: N.PlanNode) -> list:
    """Every node of the plan, subquery plans included, children before
    parents, each once (a shared subtree has several parents)."""
    from cloudberry_tpu.plan.distribute import _node_exprs

    seen: set[int] = set()
    out: list = []

    def visit(node: N.PlanNode) -> None:
        if id(node) in seen:
            return
        seen.add(id(node))
        for e in _node_exprs(node):
            for sub in ex.walk(e):
                if isinstance(sub, ex.SubqueryScalar):
                    visit(sub.plan)
        for c in node.children():
            visit(c)
        out.append(node)

    visit(plan)
    return out


def _capacity_for(est: float, rows_in: int) -> int:
    """The capacity stamped for ``est`` rows arriving at ``rows_in``, or
    0 where the rule does not engage."""
    from cloudberry_tpu.exec.kernels import rung_up

    if est * SHARE >= rows_in:
        return 0
    cap = rung_up(max(int(est * SLACK), FLOOR))
    return cap if cap < rows_in else 0


def _matches_estimate(join: N.PJoin, catalog) -> float:
    """Rows the join emits, for sizing: the larger of the planner's
    estimate and the key-containment one (every probe row finds its one
    row in the table the unique build's key comes from, and the build's
    filters and joins keep their share of that table's rows). Kept on
    the join: a join that builds on this one's output sizes by it. An
    estimate that is too small costs a retry and a compile, one too
    large a little padding."""
    from cloudberry_tpu.plan.cost import _col_source, estimate_rows

    est = estimate_rows(join, catalog)
    key = join.build_keys[0]
    src = _col_source(join.build, key.name) \
        if isinstance(key, ex.ColumnRef) else None
    if src is not None and join.kind == "inner":
        try:
            whole = float(catalog.table(src[0]).num_rows)
        except KeyError:
            whole = 0.0
        kept = getattr(join.build, "_est_matches", None)
        if kept is None:
            kept = estimate_rows(join.build, catalog)
        if whole > 0:
            est = max(est, estimate_rows(join.probe, catalog)
                      * min(kept / whole, 1.0))
    join._est_matches = est
    return est


_INTEGERS = (DType.INT32, DType.INT64, DType.DATE)


def _key_span(scan: N.PScan, phys: str, catalog, cold: bool = False):
    """How many values the scan's integer column can hold (max - min + 1),
    else None (``_key_range``)."""
    rng = _key_range(scan, phys, catalog, cold)
    return rng and rng[1] - rng[0] + 1


def _key_range(scan: N.PScan, phys: str, catalog, cold: bool = False):
    """The least and the greatest value the scan's integer column can
    hold, from a range that cannot be narrower than the rows the scan
    reads, else None. A store scan: the zone maps of the micro-partitions
    it is bound to, each written with its file. A table in RAM: the range
    ``Table.set_data`` recomputes with every change of the data; with
    ``cold``, a table not yet read in: the range its manifest gives over
    every partition of the version the plan is keyed on."""
    from cloudberry_tpu.catalog.catalog import unversioned

    parts = getattr(scan, "_store_parts", None)
    if parts is not None:
        ranges = [p.get("stats", {}).get(phys) for p in parts
                  if p["num_rows"]]
        if not ranges or any(r is None for r in ranges):
            return None
        lo, hi = min(r[0] for r in ranges), max(r[1] for r in ranges)
    else:
        try:
            t = catalog.table(scan.table_name)
        except KeyError:
            return None
        if (t.cold and not cold) or unversioned(t):
            return None
        mm = t.stats.min_max.get(phys)
        if mm is None:
            return None
        lo, hi = mm
    # (a RAM table's range is kept in float64: exact under 2**53)
    if not (-2 ** 53 < lo <= hi < 2 ** 53):
        return None
    return int(lo), int(hi)


def direct_box(join: N.PJoin, catalog):
    """The proven range of each of a lookup join's build keys, as
    ``(least value, span)`` pairs (``_key_range``; a cold table's by its
    manifest, so that a backend that has not read it in yet proves what
    one that has does), every key a plain integer column that traces
    back to a scan; None where the join expands or a key has no proof.
    A table laid over the box at ``key - least`` holds every build row
    the scan can read (exec/tiled.py's build stream)."""
    from cloudberry_tpu.plan.cost import col_origin

    if join.expands:
        return None
    box = []
    for k in join.build_keys:
        if not isinstance(k, ex.ColumnRef) or k.dtype.base not in _INTEGERS:
            return None
        src = col_origin(join.build, k.name, unions=False)
        rng = src and _key_range(src[0], src[1], catalog, cold=True)
        if not rng:
            return None
        box.append((rng[0], rng[1] - rng[0] + 1))
    return box


def direct_span(join: N.PJoin, catalog) -> int:
    """How many values a lookup join's packed build keys can take, by
    proof: the product of the keys' spans (``direct_box``). The kernel
    packs the keys with the build's own ranges at run time, each no
    wider than its proven span, so every packed key lies below the
    product. 0 where the join expands or a key has no proof."""
    box = direct_box(join, catalog)
    return 0 if box is None else math.prod(span for _, span in box)


def _group_ceiling(agg: N.PAgg, catalog):
    """The most groups ``agg`` can emit, by proof (module docstring), or
    None where a key is no plain non-null column of a scan."""
    from cloudberry_tpu.plan.cost import col_origin

    keys_of: dict[tuple, list] = {}     # a reference's route -> its keys
    for _, e in agg.group_keys:
        if not isinstance(e, ex.ColumnRef):
            return None
        try:
            if agg.child.field(e.name).null_mask is not None:
                return None
        except KeyError:
            return None
        src = col_origin(agg.child, e.name, unions=False)
        if src is None:
            return None
        scan, phys, route = src
        keys_of.setdefault(route, []).append((scan, phys, e.dtype.base))
    ceiling = 1
    for keys in keys_of.values():
        scan = keys[0][0]
        most = max(scan.capacity, 1)
        spans = [_key_span(scan, phys, catalog) if base in _INTEGERS
                 else None for _, phys, base in keys]
        # (a key without a proven span: its scan's rows alone bound it)
        if None not in spans:
            most = min(most, math.prod(spans))
        ceiling *= most
    return ceiling


def _settle(node: N.PAgg) -> None:
    from cloudberry_tpu.exec.kernels import row_rung_up

    bound = getattr(node, "_cap_bound", None)
    if bound is None:
        bound = node._cap_bound = node.capacity
    ceiling = getattr(node, "_cap_ceiling", None)
    if ceiling is not None:
        bound = min(bound, row_rung_up(ceiling))
    node.capacity = min(bound, max(N.capacity_of(node.child), 1))


def settle(plan: N.PlanNode) -> None:
    """Hold every grouped aggregate to its child's capacity and to its
    proven ceiling (children first, so a chain of them settles in one
    pass). ``_cap_bound`` keeps what the binder gave it: a join grown
    after an overflow takes its aggregate back up with it."""
    for node in _post_order(plan):
        if isinstance(node, N.PAgg) and node.group_keys:
            _settle(node)


def stamp_join_capacities(plan: N.PlanNode, catalog) -> None:
    """Stamp every lookup join of ``plan`` whose probe or matches the
    estimates call sparse, and every grouped aggregate's proven ceiling
    (module docstring), then ``settle``."""
    from cloudberry_tpu.plan.cost import estimate_rows

    stats = _Persisted(catalog)
    for node in _post_order(plan):
        if isinstance(node, N.PAgg) and node.group_keys:
            # (settled at once: a join above sizes by this capacity)
            node._cap_ceiling = _group_ceiling(node, catalog)
            _settle(node)
        if not (isinstance(node, N.PJoin) and node.compacts):
            continue
        rows_in = N.capacity_of(node.probe)
        # a probe that is a join this pass has sized arrives with that
        # size (key containment), not the planner's min(build, probe)
        reach = getattr(node.probe, "_est_matches", None)
        if reach is None:
            reach = estimate_rows(node.probe, stats)
        node.probe_capacity = _capacity_for(reach, rows_in)
        node.out_capacity = _capacity_for(
            _matches_estimate(node, stats),
            node.probe_capacity or rows_in)
    settle(plan)


def drop(plan: N.PlanNode) -> None:
    """Take every stamped capacity off ``plan`` (it goes to the tiled
    executor: a tile is its own, smaller, capacity) and ``settle`` it."""
    for node in _post_order(plan):
        if isinstance(node, N.PJoin) and node.compacts:
            node.probe_capacity = node.out_capacity = 0
    settle(plan)


def grow(plan: N.PlanNode, join: N.PJoin, what: str, rows: int) -> None:
    """``join``'s overflowed capacity (``what``: "probe" or "match") at
    the power of two that holds the ``rows`` the check counted (at least
    twice the last), or none at all once that reaches the capacity its
    rows arrive at: the ladder ends there, so growing does. Then
    ``settle``."""
    from cloudberry_tpu.exec.kernels import rung_up

    rows_in = N.capacity_of(join.probe)
    if what == "probe":
        cap = rung_up(max(rows, join.probe_capacity * 2))
        join.probe_capacity = cap if cap < rows_in else 0
    else:
        cap = rung_up(max(rows, join.out_capacity * 2))
        join.out_capacity = cap if cap < join.search_rows(rows_in) else 0
    settle(plan)
