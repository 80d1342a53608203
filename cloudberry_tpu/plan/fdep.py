"""Grouping keys that other grouping keys determine, by proof.

A grouped aggregate sorts its keys to find its groups, and the TPU
compiler's time for a sort grows with the words its comparator reads
(exec/kernels.py, "sorts"). Where some of the keys determine the others
on every row that reaches the aggregate, the groups of the whole tuple
are the groups of those keys alone: the aggregate sorts them and CARRIES
the rest, each taken at its group's first row (``kernels.group_layout``,
as the sorted keys' own values are). Q18's ``GROUP BY c_name, c_custkey,
o_orderkey, o_orderdate, o_totalprice`` sorts ``o_orderkey`` alone; Q3's
``GROUP BY l_orderkey, o_orderdate, o_shippriority`` sorts
``l_orderkey``.

``closure(plan, names)`` is every output column of ``plan`` that the
columns ``names`` determine, from these proofs only:

- a scan: a column the catalog calls unique (``Table.is_unique``: the
  data's own values in RAM, the manifest's flags for a cold table, which
  every append keeps current) and that holds no NULL determines every
  column of its table's row;
- a lookup join (inner, unique build, no residual; the build's
  uniqueness is checked again at run time): the probe keys determine
  every build column, and a build key and its probe key are equal on
  every row it emits, so each determines what the other does; semi and
  anti joins emit probe rows, so the probe's proofs hold;
- a left lookup join keeps the probe's proofs; its build side is
  null-extended and proves nothing;
- filters, sorts, limits, motions, shared subplans and runtime filters
  emit rows of their child; a projection renames plain column
  references and proves nothing of an expression;
- a grouped aggregate's keys together determine its row, and what its
  child proves among its plain key columns holds of its output.

Everything else proves nothing: expressions, an expansion (a pair per
match), a full join, a union, an external or foreign table (its rows
change outside the engine's versions), a float column (its uniqueness is
not recorded in a manifest, so a cold and a warm backend would disagree),
and a table whose unique flag an append took away. A plan is keyed on
the versions of its tables, so a proof holds for every run of it.
"""

from __future__ import annotations

from cloudberry_tpu.plan import expr as ex
from cloudberry_tpu.plan import nodes as N
from cloudberry_tpu.types import DType

# nodes whose rows are rows of their (first) child
_PASS = (N.PFilter, N.PSort, N.PLimit, N.PMotion, N.PShare,
         N.PRuntimeFilter, N.PWindow)


def _unique_at_scan(scan: N.PScan, names: set, catalog) -> bool:
    """Whether one of ``names`` is a scan column that identifies its row."""
    from cloudberry_tpu.catalog.catalog import unversioned

    if scan.table_name == "$dual":
        return False
    try:
        t = catalog.table(scan.table_name)
    except KeyError:
        return False
    if unversioned(t):
        return False
    for phys, out in scan.column_map.items():
        if out not in names or phys in scan.mask_map:
            continue
        try:
            base = t.schema.field(phys).dtype
        except KeyError:
            continue
        if base not in (DType.BOOL, DType.FLOAT64) and t.is_unique(phys):
            return True
    return False


def _refs(exprs) -> list | None:
    """The column names of ``exprs``, or None where one is no plain
    column reference."""
    names = [e.name for e in exprs if isinstance(e, ex.ColumnRef)]
    return names if len(names) == len(exprs) else None


def closure(plan: N.PlanNode, names, catalog) -> set:
    """The output columns of ``plan`` that ``names`` determine (module
    docstring); ``names`` themselves among them."""
    have = set(names) & set(plan.names)
    if not have:
        return have
    if isinstance(plan, N.PScan):
        return set(plan.names) if _unique_at_scan(plan, have, catalog) \
            else have
    if isinstance(plan, _PASS):
        return have | closure(plan.children()[0], have, catalog)
    if isinstance(plan, N.PProject):
        refs = {out: e.name for out, e in plan.exprs
                if isinstance(e, ex.ColumnRef)}
        got = closure(plan.child, {refs[n] for n in have if n in refs},
                      catalog)
        return have | {out for out, src in refs.items() if src in got}
    if isinstance(plan, N.PJoin):
        return have | _join_closure(plan, have, catalog)
    if isinstance(plan, N.PAgg) and plan.group_keys:
        keys = [n for n, _ in plan.group_keys]
        if set(keys) <= have:
            return set(plan.names)
        refs = {n: e.name for n, e in plan.group_keys
                if isinstance(e, ex.ColumnRef)}
        got = closure(plan.child, {refs[n] for n in have if n in refs},
                      catalog)
        have |= {n for n, src in refs.items() if src in got}
        return set(plan.names) if set(keys) <= have else have
    return have


def _join_closure(join: N.PJoin, have: set, catalog) -> set:
    probe = closure(join.probe, have, catalog)
    if join.kind in ("semi", "anti"):
        return probe
    if join.kind != "inner" or join.expands:
        # a left join's build side is null-extended; an expansion pairs
        # a probe row with many build rows; a full join both
        return probe if join.kind == "left" and not join.expands \
            else set()
    pk, bk = _refs(join.probe_keys), _refs(join.build_keys)
    build = closure(join.build, have, catalog)
    if pk is None or bk is None:
        return probe | build
    while True:
        # a build key and its probe key are equal on every row emitted
        known = [(p, b) for p, b in zip(pk, bk) if p in probe or b in build]
        if len(known) == len(pk):
            # the keys find at most one build row: all of it
            return closure(join.probe, probe | set(pk), catalog) \
                | set(join.build.names)
        grown = (closure(join.probe, probe | {p for p, _ in known}, catalog),
                 closure(join.build, build | {b for _, b in known}, catalog))
        if grown == (probe, build):
            return probe | build
        probe, build = grown


def carried_keys(agg: N.PAgg, catalog) -> tuple:
    """The output names of ``agg``'s grouping keys that the others
    determine by proof, in key order: keys the aggregate need not sort
    (PAgg.carried). A key is a candidate where it is a plain column
    reference that holds no NULL (a nullable key groups by a masked
    expression and its validity, both sorted); each candidate is dropped
    from the sorted set, in key order, where the keys left determine it.
    The keys left sorted then determine every key, and no one of them is
    determined by the others."""
    cand = {}
    for out, e in agg.group_keys:
        if not isinstance(e, ex.ColumnRef):
            continue
        try:
            if agg.child.field(e.name).null_mask is not None:
                continue
        except KeyError:
            continue
        cand[out] = e.name
    if len(cand) < 2:
        return ()
    kept = dict(cand)
    for out, src in cand.items():
        rest = {s for o, s in kept.items() if o != out}
        if rest and src in closure(agg.child, rest, catalog):
            del kept[out]
    return tuple(out for out in cand if out not in kept)
