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

One segment only: a distributed plan's capacities are per segment and
its aggregates and Motions are sized by ``plan/distribute.py`` (ROADMAP
S11).
"""

from __future__ import annotations

from cloudberry_tpu.plan import expr as ex
from cloudberry_tpu.plan import nodes as N

SHARE = 16      # stamp where estimate × SHARE < the input's capacity
SLACK = 4       # ... at the power of two above SLACK × the estimate
FLOOR = 1024    # ... and not under FLOOR rows


class _Persisted:
    """The catalog's statistics that do not depend on where a table's
    rows are: its row count, its columns' ranges and histograms, and the
    distinct counts of a table that lives in RAM only. A STORED table
    that a backend has loaded counts distinct values on demand
    (``Table.ndv``) and one it has not cannot; sized from those, a cold
    backend and a warm one would lower two programs for one statement
    over one store (two compiles of minutes each at SF1), so a stored
    table's capacities see none. ``est_slot``: such estimates are kept
    on the nodes beside the planner's own (``cost.estimate_rows``), not
    in their place."""

    est_slot = "_est_rows_persisted"

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


def settle(plan: N.PlanNode) -> None:
    """Hold every grouped aggregate to its child's capacity (children
    first, so a chain of them settles in one pass). ``_cap_bound`` keeps
    what the binder gave it: a join grown after an overflow takes its
    aggregate back up with it."""
    for node in _post_order(plan):
        if isinstance(node, N.PAgg) and node.group_keys:
            bound = getattr(node, "_cap_bound", None)
            if bound is None:
                bound = node._cap_bound = node.capacity
            node.capacity = min(bound, max(N.capacity_of(node.child), 1))


def stamp_join_capacities(plan: N.PlanNode, catalog) -> None:
    """Stamp every lookup join of ``plan`` whose probe or matches the
    estimates call sparse (module docstring), then ``settle``."""
    from cloudberry_tpu.plan.cost import estimate_rows

    stats = _Persisted(catalog)
    for node in _post_order(plan):
        if not (isinstance(node, N.PJoin) and node.compacts):
            continue
        rows_in = N.capacity_of(node.probe)
        node.probe_capacity = _capacity_for(
            estimate_rows(node.probe, stats), rows_in)
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
