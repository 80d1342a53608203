"""Cardinality estimation — the libgpdbcost / clauselist_selectivity analog.

Estimates row counts for bound plan subtrees from table statistics (row
counts, NDV, min/max — catalog.TableStats, filled lazily or by ANALYZE).
Drives the DP join-order search (plan/binder.py) and the distribution
pass's broadcast-vs-redistribute choice (plan/distribute.py) — the two
decisions ORCA spends its cost model on for TPC-H-class plans.

Estimates memoize on the node (attr ``_est_rows``); plans are per-statement
so the memo's lifetime is right by construction.
"""

from __future__ import annotations

from typing import Optional

from cloudberry_tpu.plan import expr as ex
from cloudberry_tpu.plan import nodes as N

DEFAULT_EQ_SEL = 0.1
DEFAULT_RANGE_SEL = 1.0 / 3.0
DEFAULT_SEL = 0.25


def estimate_rows(node: N.PlanNode, catalog) -> float:
    # kept on the node, under the slot of the statistics it came from (a
    # view of the catalog names its own: plan/joincap.py)
    slot = getattr(catalog, "est_slot", "_est_rows")
    cached = getattr(node, slot, None)
    if cached is not None:
        return cached
    est = max(_estimate(node, catalog), 0.0)
    setattr(node, slot, est)
    return est


def _estimate(node: N.PlanNode, catalog) -> float:
    if isinstance(node, N.PScan):
        if node.table_name == "$dual":
            return 1.0
        return float(node.num_rows if node.num_rows >= 0 else node.capacity)
    if isinstance(node, N.PFilter):
        return estimate_rows(node.child, catalog) * \
            selectivity(node.predicate, node.child, catalog)
    if isinstance(node, (N.PProject, N.PSort, N.PWindow, N.PShare,
                         N.PMotion)):
        return estimate_rows(node.children()[0], catalog)
    if isinstance(node, N.PLimit):
        return min(estimate_rows(node.child, catalog), float(node.limit))
    if isinstance(node, N.PConcat):
        return sum(estimate_rows(c, catalog) for c in node.inputs)
    if isinstance(node, N.PAgg):
        child = estimate_rows(node.child, catalog)
        if not node.group_keys:
            return 1.0
        prod = 1.0
        for _, e in node.group_keys:
            nd = _expr_ndv(node.child, e, catalog)
            prod *= nd if nd is not None else max(child ** 0.5, 1.0)
            if prod >= child:
                prod = child
                break
        est = min(prod, child)
        # feedback (plan/feedback.py): a prior merge motion over these
        # group keys COUNTED the shipped partials, bracketing the true
        # distinct-group count (every group ships >= 1 and <= nseg
        # partial rows) — clamp the static product into the observed
        # bracket. Refines both failure modes: an over-estimate shrinks
        # the merge rung (fewer padded wire bytes), an under-estimate
        # grows g_cap before the overflow-retry would have.
        fb = getattr(catalog, "_feedback", None)
        if fb is not None:
            bounds = fb.group_ndv(node)
            if bounds is not None:
                lo, hi = bounds
                clamped = min(max(est, float(lo)), float(hi), child)
                if clamped != est:
                    node._feedback_ndv = (lo, hi)
                    est = clamped
        return est
    if isinstance(node, N.PJoin):
        return _estimate_join(node, catalog)
    return 1.0


def _estimate_join(node: N.PJoin, catalog) -> float:
    b = estimate_rows(node.build, catalog)
    p = estimate_rows(node.probe, catalog)
    nd_b = _keys_ndv(node.build, node.build_keys, catalog)
    nd_p = _keys_ndv(node.probe, node.probe_keys, catalog)
    # |B ⋈ P| = |B||P| / max(ndv_B, ndv_P)  (System R equi-join formula)
    denom = max(nd_b or 1.0, nd_p or 1.0,
                1.0 if (nd_b or nd_p) else max(b, p, 1.0))
    inner = b * p / max(denom, 1.0)
    if node.kind == "inner":
        return inner
    if node.kind == "left":
        return max(inner, p)
    if node.kind == "full":
        return max(inner, p) + max(b - inner, 0.0)
    if node.kind == "semi":
        from cloudberry_tpu.plan.binder import _build_is_unique

        if _build_is_unique(node.probe, node.probe_keys, catalog):
            # each build key meets at most one probe row
            return min(p, b)
        # fraction of probe rows with a partner
        if nd_p:
            return p * min(1.0, (nd_b or b) / nd_p)
        return p * 0.5
    if node.kind == "anti":
        if nd_p:
            return p * (1.0 - min(1.0, (nd_b or b) / nd_p))
        return p * 0.5
    return inner


def semi_estimate(build: N.PlanNode, probe: N.PlanNode, build_keys,
                  probe_keys, catalog) -> float:
    """Rows of ``probe`` surviving a semi filter on the join keys (runtime-
    filter sizing)."""
    j = N.PJoin("semi", build, probe, list(build_keys), list(probe_keys), [])
    return _estimate_join(j, catalog)


def _keys_ndv(plan: N.PlanNode, keys, catalog) -> Optional[float]:
    """Combined NDV of a key tuple (product, capped by subtree rows)."""
    prod = 1.0
    any_known = False
    for k in keys:
        nd = _expr_ndv(plan, k, catalog)
        if nd is not None:
            any_known = True
            prod *= nd
    if not any_known:
        return None
    return min(prod, max(estimate_rows(plan, catalog), 1.0))


def _expr_ndv(plan: N.PlanNode, e: ex.Expr, catalog) -> Optional[int]:
    if not isinstance(e, ex.ColumnRef):
        return None
    src = _col_source(plan, e.name)
    if src is None:
        return None
    table, phys = src
    try:
        return catalog.table(table).ndv(phys)
    except KeyError:
        return None


def _col_source(plan: N.PlanNode, name: str):
    """Trace an output column back to (table, physical column) through
    renames; None when it crosses a computation."""
    src = col_origin(plan, name)
    return src and (src[0].table_name, src[1])


def col_origin(plan: N.PlanNode, name: str, unions: bool = True,
               route: tuple = ()):
    """(scan, physical column, route) of an output column that is its
    scan's column carried up through renames, else None. ``route``: the
    side taken at each join on the way down, so two columns with one
    route are columns of the same rows, and two references to one
    shared subplan (a CTE's ``PShare``: every reference holds the same
    scan object) have two. ``unions``: a union's column is traced
    through its first input, which serves an estimate; a proof passes
    False (the column holds every input's values)."""
    if isinstance(plan, N.PScan):
        for phys, out in plan.column_map.items():
            if out == name:
                return plan, phys, route
        return None
    if isinstance(plan, (N.PFilter, N.PRuntimeFilter, N.PSort, N.PLimit,
                         N.PMotion, N.PWindow, N.PShare)):
        return col_origin(plan.children()[0], name, unions, route)
    if isinstance(plan, N.PProject):
        for out, e in plan.exprs:
            if out == name:
                if isinstance(e, ex.ColumnRef):
                    return col_origin(plan.child, e.name, unions, route)
                return None
        return None
    if isinstance(plan, N.PJoin):
        if name in set(plan.probe.names):
            return col_origin(plan.probe, name, unions, route + ("probe",))
        if name in set(plan.build.names):
            return col_origin(plan.build, name, unions, route + ("build",))
        return None
    if isinstance(plan, N.PAgg):
        for out, e in plan.group_keys:
            if out == name and isinstance(e, ex.ColumnRef):
                return col_origin(plan.child, e.name, unions, route)
        return None
    if isinstance(plan, N.PConcat) and plan.inputs and unions:
        return col_origin(plan.inputs[0], name, unions, route)
    return None


def annotate_pack_bits(plan: N.PlanNode, catalog) -> None:
    """Prove 32-bit packed join keys from build-side column statistics,
    and one packed word (32 or 64 bits) for a grouped aggregate's keys
    and for a sort's; stamp each lookup join's proven key span
    (``PJoin.direct_span``, plan/joincap.py), each grouped aggregate's
    keys that the others determine (``PAgg.carried``, plan/fdep.py),
    which its word leaves out, and the proven box of the others and the
    widths of its sums (``PAgg.direct_box``, ``PAgg.sum_bits``,
    plan/joincap.py).

    The kernels pack key tuples into one order-preserving integer using the
    BUILD side's runtime ranges (kernels.pack_with_ranges); probe values
    outside those ranges hit the sentinel. The runtime build range is a
    subset of the build column's table min/max, so if the product of
    stats-proven spans fits 32 bits (minus the sentinel), every in-range
    pack does too — and the sort/search/collective lanes halve. TPC-H keys
    stay 32-bit provable through SF100 (orderkey max 6e9·0.1 < 2^31)."""
    from cloudberry_tpu.plan.fdep import carried_keys
    from cloudberry_tpu.plan.joincap import (direct_agg_box, direct_span,
                                             sum_bits)
    from cloudberry_tpu.types import DType

    # value-space spans only translate to pack-space for types whose
    # sort_key_u64 mapping is affine: integers, dates, scaled decimals,
    # and dictionary codes. FLOATS pack by IEEE bit pattern — a tiny value
    # span can cover ~2^52 bit patterns, so they are never narrowable.
    _AFFINE = (DType.INT32, DType.INT64, DType.DATE, DType.DECIMAL,
               DType.STRING)

    def span_product(src_node: N.PlanNode, keys, most: int):
        """Product of the keys' stats-proven spans, or None where a key is
        no plain column, has no statistics, or the product passes
        ``most``."""
        prod = 1
        for k in keys:
            if not isinstance(k, ex.ColumnRef) \
                    or k.dtype.base not in _AFFINE:
                return None
            src = _col_source(src_node, k.name)
            if src is None:
                return None
            try:
                mm = catalog.table(src[0]).stats.min_max.get(src[1])
            except KeyError:
                return None
            if mm is None:
                return None
            # stats store float64 min/max: beyond 2^53 the rounding could
            # understate a span that straddles a threshold
            if abs(mm[0]) >= 2 ** 53 or abs(mm[1]) >= 2 ** 53:
                return None
            span = int(mm[1]) - int(mm[0]) + 1
            if span <= 0:
                return None
            prod *= span
            if prod > most:
                return None
        return prod

    def bits_of(build: N.PlanNode, keys) -> int:
        return 64 if span_product(build, keys, (1 << 32) - 2) is None \
            else 32

    def word_bits(child: N.PlanNode, keys) -> int:
        """One packed word for a grouping or ordering sort (PAgg.pack_bits,
        PSort.pack_bits): the all-ones word stays free for the rows that
        are not selected."""
        prod = span_product(child, keys, (1 << 64) - 2)
        if prod is None:
            return 0
        return 32 if prod <= (1 << 32) - 2 else 64

    def walk(n: N.PlanNode):
        if isinstance(n, (N.PJoin, N.PRuntimeFilter)):
            n.pack_bits = bits_of(n.build, n.build_keys)
        if isinstance(n, N.PJoin):
            n.direct_span = direct_span(n, catalog)
        if isinstance(n, N.PAgg) and n.group_keys:
            n.carried = carried_keys(n, catalog)
            n.pack_bits = word_bits(n.child, [e for k, e in n.group_keys
                                              if k not in n.carried])
            n.direct_box = direct_agg_box(n, catalog)
            n.sum_bits = sum_bits(n, catalog) if n.direct_box else ()
        if isinstance(n, N.PSort):
            # (a string sorts by its collation rank, not by the code the
            # statistics are of)
            keys = [e for e, _ in n.keys]
            n.pack_bits = 0 if any(
                e.dtype.base == DType.STRING for e in keys) \
                else word_bits(n.child, keys)
        from cloudberry_tpu.plan.distribute import _node_exprs

        for e in _node_exprs(n):
            for sub in ex.walk(e):
                if isinstance(sub, ex.SubqueryScalar):
                    walk(sub.plan)
        for c in n.children():
            walk(c)

    walk(plan)


def selectivity(pred: ex.Expr, child: N.PlanNode, catalog) -> float:
    s = _sel(pred, child, catalog)
    return min(max(s, 1e-6), 1.0)


def _sel(e: ex.Expr, child: N.PlanNode, catalog) -> float:
    if isinstance(e, ex.BinOp):
        if e.op == "and":
            return _sel(e.left, child, catalog) * \
                _sel(e.right, child, catalog)
        if e.op == "or":
            a = _sel(e.left, child, catalog)
            b = _sel(e.right, child, catalog)
            return a + b - a * b
        if e.op in ("=", "<>", "<", "<=", ">", ">="):
            return _cmp_sel(e, child, catalog)
    if isinstance(e, ex.UnaryOp) and e.op == "not":
        return 1.0 - _sel(e.operand, child, catalog)
    if isinstance(e, ex.DictLookup) and e.table.dtype == bool:
        # LIKE/IN over a dictionary: fraction of codes selected (frequency-
        # blind, but exact over the value domain)
        n = len(e.table)
        return float(e.table.sum()) / n if n else DEFAULT_SEL
    if isinstance(e, ex.IsValid):
        return 0.9
    if isinstance(e, ex.Literal):
        return 1.0 if bool(e.value) else 0.0
    return DEFAULT_SEL


def _cmp_sel(e: ex.BinOp, child: N.PlanNode, catalog) -> float:
    l, r = e.left, e.right
    op = e.op
    if isinstance(r, ex.ColumnRef) and isinstance(l, ex.Literal):
        l, r = r, l
        op = {"=": "=", "<>": "<>", "<": ">", "<=": ">=",
              ">": "<", ">=": "<="}[op]
    if not (isinstance(l, ex.ColumnRef) and isinstance(r, ex.Literal)):
        return DEFAULT_RANGE_SEL if op not in ("=", "<>") else DEFAULT_EQ_SEL
    src = _col_source(child, l.name)
    if src is None:
        return DEFAULT_RANGE_SEL if op not in ("=", "<>") else DEFAULT_EQ_SEL
    try:
        t = catalog.table(src[0])
    except KeyError:
        return DEFAULT_SEL
    if op in ("=", "<>"):
        nd = t.ndv(src[1])
        s = 1.0 / nd if nd else DEFAULT_EQ_SEL
        return s if op == "=" else 1.0 - s
    if not isinstance(r.value, (int, float)) or isinstance(r.value, bool):
        return DEFAULT_RANGE_SEL
    hist = t.stats.hist.get(src[1])
    if hist and len(hist) >= 3:
        # equi-depth histogram (ANALYZE output, pg_statistic
        # histogram_bounds role): each bucket holds 1/N of the rows, so
        # P(col <= v) = full buckets below v + linear interpolation
        # inside the containing bucket — skew-proof where uniform
        # [min,max] interpolation is wildly wrong
        frac = _hist_le_frac(hist, float(r.value))
        return frac if op in ("<", "<=") else 1.0 - frac
    mm = t.stats.min_max.get(src[1])
    if mm is None or mm[1] <= mm[0]:
        return DEFAULT_RANGE_SEL
    lo, hi = mm
    frac = (float(r.value) - lo) / (hi - lo)
    frac = min(max(frac, 0.0), 1.0)
    return frac if op in ("<", "<=") else 1.0 - frac


def _hist_le_frac(bounds: list, v: float) -> float:
    """P(col <= v) from equi-depth bounds (N+1 ascending values)."""
    import bisect

    n = len(bounds) - 1
    if v < bounds[0]:
        return 0.0
    if v >= bounds[-1]:
        return 1.0
    i = bisect.bisect_right(bounds, v) - 1  # bucket containing v
    lo, hi = bounds[i], bounds[i + 1]
    inner = (v - lo) / (hi - lo) if hi > lo else 1.0
    return (i + inner) / n
