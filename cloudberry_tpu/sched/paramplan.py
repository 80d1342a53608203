"""Parameterized generic plans — the plan_cache.c analog.

``Session._stmt_cache`` keys on exact SQL text, so ``WHERE k = 42`` and
``WHERE k = 99`` each pay a full parse→plan→XLA-compile even though they
need the same program. This module makes same-shape statements share one
compiled executable:

1. ``normalize`` lexes the statement and hoists constant literals into a
   parameter vector, producing a SKELETON string (the cache key) — the
   query-fingerprint normalization of plan_cache.c's generic plans.
2. On first execution of a skeleton, the freshly bound plan's
   filter/project literals are rewritten to ``expr.Param`` slots and the
   program is compiled with a ``$params`` input; the literal VALUES travel
   as device inputs.
3. On a later execution with different literals, the statement is re-bound
   (host-only, sub-millisecond) and its plan's STRUCTURAL SIGNATURE is
   compared with the cached generic plan's; on a match the new literal
   values (and point-lookup row slices / direct-dispatch segment) bind
   into the existing program — ZERO XLA compiles.

Plans that fold literals into plan STRUCTURE — nextval (plan-time sequence
allocation, ``_no_stmt_cache``), literal-dependent partition pruning
(``_store_parts``), a point lookup whose match count changed, a
direct-dispatch row-count change — are non-generic by construction: the
signature (or the ``_no_stmt_cache`` gate) refuses the rebind and the
statement keeps today's compile-per-text path.

The signature deliberately captures everything the TRACE bakes in: node
shapes and capacities, baked literal values outside param sites, the
contents of DictLookup tables built from a dictionary alone (translation
tables of a derived dictionary), dictionary identity for collation rank
tables (guarded by table versions), and shared-subtree (PShare) topology.

A lookup table the statement's literals chose (a string IN list, a LIKE
pattern, an ordering comparison with a string: ``expr.LookupLiterals``)
is a parameter like a literal: the signature holds its column, dtype,
dictionary and padded length, and its contents ride per send as a
``$dlt<i>`` entry of ``$params``, at every length. Two draws of one
report with other modes or other words then share one program.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import numpy as np

from cloudberry_tpu.plan import expr as ex
from cloudberry_tpu.plan import nodes as N
from cloudberry_tpu.sql.lexer import LexError, tokenize
from cloudberry_tpu.types import DType, SqlType


class UnsupportedPlan(Exception):
    """The plan contains a shape the generic-plan walker does not model —
    the statement silently keeps the non-generic path."""


# ------------------------------------------------------------- skeletons


_PARAM_HEADS = ("select", "with", "(")
# literals after these keywords are STRUCTURAL (plan shape / bind-time
# folds), never parameters: LIMIT/OFFSET become static node fields and
# INTERVAL quantities fold into date arithmetic at bind time
_KEEP_AFTER = ("limit", "offset", "interval")


def param_head(sql: str) -> bool:
    """Does the statement open as one that can have a skeleton?"""
    head = sql.lstrip()[:1]
    if not head:
        return False
    first = sql.split(None, 1)[0].lower() if head != "(" else "("
    return first in _PARAM_HEADS


def _lex(sql: str):
    """(skeleton, literal texts, their offsets in ``sql``) for a
    parameterizable statement, else None. The skeleton is the token
    stream with number/string literals replaced by kind-tagged
    placeholders — same-shape statements collide on it regardless of
    their literal values."""
    if not param_head(sql):
        return None
    try:
        toks = tokenize(sql)
    except LexError:
        return None
    parts: list[str] = []
    params: list[str] = []
    offsets: list[int] = []
    prev = ""
    for t in toks:
        if t.kind == "number" and prev not in _KEEP_AFTER:
            params.append(t.text)
            offsets.append(t.pos)
            parts.append("?n")
        elif t.kind == "string" and prev not in _KEEP_AFTER:
            params.append(t.text)
            offsets.append(t.pos)
            parts.append("?s")
        elif t.kind == "string":
            parts.append(f"'{t.text}'")
        elif t.kind != "eof":
            parts.append(t.text)
        prev = t.text if t.kind == "ident" else ""
    return " ".join(parts), tuple(params), tuple(offsets)


def normalize(sql: str):
    """(skeleton, literal texts) of ``_lex``, else None."""
    lexed = _lex(sql)
    return lexed and lexed[:2]


# ------------------------------------------------------- plan signatures


def _tsig(t: Optional[SqlType]):
    if t is None:
        return None
    return (t.base.value, t.scale)


def _pyval(v) -> Any:
    """Baked literal value as a hashable python scalar."""
    if isinstance(v, str):
        return v
    try:
        return np.asarray(v).item()
    except (TypeError, ValueError):
        return repr(v)


def _param_scalar(e: ex.Literal) -> bool:
    """Literal eligible to travel as a device input: a numeric/bool/date
    scalar (strings stay baked — their plan effect is a dictionary code,
    a $prm slot of its own, or a DictLookup table, a $dlt binding)."""
    if isinstance(e.value, str):
        return False
    try:
        np.asarray(e.value, dtype=e.dtype.np_dtype)
    except (TypeError, ValueError, OverflowError):
        return False
    return np.ndim(e.value) == 0


class _Walker:
    """One canonical walk shared by signature building, parameter-slot
    numbering, binding extraction, and the literal→Param rewrite: every
    consumer MUST see nodes, expression sites, and literals in the same
    order, or rebinding would feed values into the wrong slots."""

    def __init__(self, session, rewrite: bool = False):
        self.rewrite = rewrite
        self.slots: list[SqlType] = []
        self.bindings: dict[str, np.ndarray] = {}
        self.keyed: list[N.PScan] = []
        # for the literal template: the literal behind each $prm slot
        # (None where a rewritten plan is walked again), and the literals
        # of a known origin that stay baked into the program
        self.slot_lits: list[Optional[ex.Literal]] = []
        self.baked: list[ex.Literal] = []
        # (key, mark) of each literal-chosen lookup table ($dlt<i>)
        self.tables: list[tuple[str, ex.LookupLiterals]] = []
        self.subplans = 0
        self._nrw = 0  # scan row-count parameter slots ($nrw<i>)
        self._memo: dict[int, int] = {}
        # table-owned dictionaries are version-pinned (any content change
        # bumps the table version) — only literal-derived dictionaries
        # need content hashing in the signature
        self._table_dicts = {
            id(d)
            for t in session.catalog.tables.values()
            for d in getattr(t, "dicts", {}).values()}

    # ------------------------------------------------------- expressions

    def esig(self, e: Optional[ex.Expr], paramable: bool):
        """(signature, possibly-rewritten expr) for one expression."""
        if e is None:
            return None, None
        if isinstance(e, ex.Literal):
            if paramable and _param_scalar(e):
                slot = len(self.slots)
                self.slots.append(e.dtype)
                self.slot_lits.append(e)
                key = f"$prm{slot}"
                self.bindings[key] = np.asarray(e.value,
                                                dtype=e.dtype.np_dtype)
                # the Param KEEPS the literal: the baked fallback for a
                # non-generic recompile (growth retry) and the binding
                # source when a rewritten plan is re-analyzed
                new = ex.Param(slot, e.dtype, e.value) if self.rewrite \
                    else e
                return ("P", _tsig(e.dtype)), new
            if e.origin is not None:
                self.baked.append(e)
            return ("L", _tsig(e.dtype), _pyval(e.value)), e
        if isinstance(e, ex.Param):
            # re-analysis of an already-rewritten plan (the expansion-growth
            # retry re-enters the generic gate with the same plan object):
            # the Param's kept build-time value IS the binding
            if not paramable or e.value is None:
                raise UnsupportedPlan("Param at a non-parameter site")
            slot = len(self.slots)
            self.slots.append(e.dtype)
            self.slot_lits.append(None)
            key = f"$prm{slot}"
            self.bindings[key] = np.asarray(e.value,
                                            dtype=e.dtype.np_dtype)
            new = ex.Param(slot, e.dtype, e.value) if self.rewrite else e
            return ("P", _tsig(e.dtype)), new
        if isinstance(e, ex.ColumnRef):
            return ("C", e.name, _tsig(e.dtype)), e
        if isinstance(e, ex.BinOp):
            ls, ln = self.esig(e.left, paramable)
            rs, rn = self.esig(e.right, paramable)
            new = ex.BinOp(e.op, ln, rn, e.dtype) if self.rewrite else e
            return ("B", e.op, _tsig(e.dtype), ls, rs), new
        if isinstance(e, ex.UnaryOp):
            s, n = self.esig(e.operand, paramable)
            new = ex.UnaryOp(e.op, n, e.dtype) if self.rewrite else e
            return ("U", e.op, _tsig(e.dtype), s), new
        if isinstance(e, ex.Cast):
            s, n = self.esig(e.operand, paramable)
            new = ex.Cast(n, e.dtype) if self.rewrite else e
            return ("T", _tsig(e.operand.dtype), _tsig(e.dtype), s), new
        if isinstance(e, ex.Func):
            # scale_down's k literal is consumed at COMPILE time
            # (expr_compile reads e.args[1].value) — args stay baked
            sub_param = paramable and e.name != "scale_down"
            sigs, news = [], []
            for a in e.args:
                s, n = self.esig(a, sub_param)
                sigs.append(s)
                news.append(n)
            new = ex.Func(e.name, tuple(news), e.dtype) if self.rewrite \
                else e
            return ("F", e.name, _tsig(e.dtype), tuple(sigs)), new
        if isinstance(e, ex.CaseWhen):
            sigs, news = [], []
            for c, v in e.whens:
                cs, cn = self.esig(c, paramable)
                vs, vn = self.esig(v, paramable)
                sigs.append((cs, vs))
                news.append((cn, vn))
            os_, on = self.esig(e.otherwise, paramable)
            new = ex.CaseWhen(tuple(news), on, e.dtype) if self.rewrite \
                else e
            return ("W", _tsig(e.dtype), tuple(sigs), os_), new
        if isinstance(e, ex.DictLookup) and e.literals is not None:
            # the statement's literals chose the table: a parameter, bound
            # per send at its rung; the signature keeps what the trace
            # sees of it
            from cloudberry_tpu.exec import kernels as K

            s, n = self.esig(e.column, False)
            key = f"{ex.TABLE_PARAM}{len(self.tables)}"
            tab = K.pad_to_rung(np.asarray(e.table))
            self.bindings[key] = tab
            self.tables.append((key, e.literals))
            tsig = ("DT", s, str(tab.dtype), len(tab),
                    self._dictsig(e.literals.sdict))
            if not self.rewrite:
                return tsig, e
            out = ex.DictLookup(n, e.table, e.dtype, e.literals)
            object.__setattr__(out, "_table_input", key)
            return tsig, out
        if isinstance(e, ex.DictLookup):
            s, n = self.esig(e.column, False)
            tab = np.asarray(e.table)
            tsig = ("DL", s, str(tab.dtype), tab.shape,
                    hash(tab.tobytes()), self._dictsig(
                        getattr(e, "_out_dict", None)))
            if self.rewrite and n is not e.column:
                out = ex.DictLookup(n, e.table, e.dtype)
                d = getattr(e, "_out_dict", None)
                if d is not None:
                    object.__setattr__(out, "_out_dict", d)
                return tsig, out
            return tsig, e
        if isinstance(e, ex.IsValid):
            return ("V", tuple(e.mask_names), e.negate), e
        if isinstance(e, ex.SubqueryScalar):
            # the subplan lowers inside the same program — recurse; its
            # filter/project literals are param sites like any other
            self.subplans += 1
            psig = self.nsig(e.plan)
            return ("SQ", e.mode, _tsig(e.dtype), psig), e
        raise UnsupportedPlan(f"expression {type(e).__name__}")

    def _dictsig(self, d):
        if d is None:
            return None
        if id(d) in self._table_dicts:
            return ("tdict", len(d))
        return ("dict", len(d), hash(tuple(d.values)))

    def _fieldsig(self, node: N.PlanNode):
        return tuple(
            (f.name, _tsig(f.type), f.masks, self._dictsig(f.sdict),
             f._is_null_col)
            for f in node.fields)

    # ------------------------------------------------------------- nodes

    def _site(self, node, attr: str, paramable: bool):
        """Signature one expression attribute; rewrite in place when
        building the generic plan."""
        s, n = self.esig(getattr(node, attr), paramable)
        if self.rewrite and n is not None:
            setattr(node, attr, n)
        return s

    def nsig(self, node: N.PlanNode):
        key = id(node)
        if key in self._memo:
            # shared subtree (PShare / runtime-filter build): reference by
            # first-visit index — topology is part of the program
            return ("ref", self._memo[key])
        self._memo[key] = len(self._memo)
        t = type(node).__name__
        if isinstance(node, N.PScan):
            if hasattr(node, "_point_rows"):
                extra = ("pt", len(node._point_rows))
                self.keyed.append(node)
                nrows = node.num_rows  # the slice length IS the shape
            elif hasattr(node, "_store_parts"):
                extra = ("store",
                         tuple(p["file"] for p in node._store_parts))
                self.keyed.append(node)
                nrows = node.num_rows
            else:
                # whole-table/shard scan: the row count is DATA, not
                # shape — bind it as a parameter so one program serves
                # every direct-dispatch segment (per-segment counts
                # differ; the padded capacity does not)
                extra = None
                nrows = "$param"
                key = f"$nrw{self._nrw}"
                self._nrw += 1
                self.bindings[key] = np.asarray(node.num_rows
                                                if node.num_rows >= 0
                                                else node.capacity,
                                                dtype=np.int64)
                if self.rewrite:
                    node._nrows_key = key
            return (t, node.table_name,
                    tuple(sorted(node.column_map.items())),
                    tuple(sorted(node.mask_map.items())),
                    node.capacity, nrows, extra,
                    self._fieldsig(node))
        if isinstance(node, N.PFilter):
            return (t, self._site(node, "predicate", True),
                    self.nsig(node.child))
        if isinstance(node, N.PProject):
            sigs = []
            for i, (name, e) in enumerate(list(node.exprs)):
                s, n = self.esig(e, True)
                if self.rewrite:
                    node.exprs[i] = (name, n)
                sigs.append((name, s))
            return (t, tuple(sigs), self._fieldsig(node),
                    self.nsig(node.child))
        if isinstance(node, N.PJoin):
            bk = tuple(self.esig(k, False)[0] for k in node.build_keys)
            pk = tuple(self.esig(k, False)[0] for k in node.probe_keys)
            # the join-index slot is structural: a program compiled WITH
            # the cached-sorted-build input cannot serve a plan without
            # it (and vice versa) — the spec key carries table/columns/
            # bits/layout so signature-equal plans want the same input
            jix = getattr(node, "_jix", None)
            return (t, node.kind, tuple(node.build_payload),
                    node.match_name, node.probe_match_name,
                    node.unique_build, node.out_capacity,
                    node.probe_capacity, node.null_aware,
                    node.pack_bits, node.direct_span,
                    jix.key if jix is not None else None,
                    bk, pk,
                    self._site(node, "residual", False),
                    self._site(node, "build_key_valid", False),
                    self._site(node, "probe_key_valid", False),
                    self.nsig(node.build), self.nsig(node.probe))
        if isinstance(node, N.PAgg):
            keys = tuple((name, self.esig(e, False)[0])
                         for name, e in node.group_keys)
            aggs = tuple(
                (name, c.func, c.distinct,
                 self.esig(c.arg, False)[0],
                 self.esig(c.filter, False)[0])
                for name, c in node.aggs)
            return (t, node.mode, node.capacity, node.pack_bits,
                    node.carried, node.direct_box, node.sum_bits,
                    keys, aggs,
                    self._fieldsig(node),
                    self.nsig(node.child))
        if isinstance(node, N.PSort):
            keys = tuple((self.esig(e, False)[0], asc)
                         for e, asc in node.keys)
            return (t, keys, node.pack_bits, self._fieldsig(node),
                    self.nsig(node.child))
        if isinstance(node, N.PLimit):
            return (t, node.limit, node.offset, self.nsig(node.child))
        if isinstance(node, N.PWindow):
            pk = tuple(self.esig(e, False)[0] for e in node.partition_keys)
            ok = tuple((self.esig(e, False)[0], asc)
                       for e, asc in node.order_keys)
            calls = tuple((name, func, self.esig(arg, False)[0])
                          for name, func, arg in node.calls)
            valids = tuple(self.esig(v, False)[0]
                           for v in (node.valids or ()))
            params = tuple(
                None if p is None else tuple(
                    (k, self.esig(v, False)[0]
                     if isinstance(v, ex.Expr) else v)
                    for k, v in sorted(p.items()))
                for p in (node.params or ()))
            return (t, pk, ok, calls, valids, params, node.frame,
                    self._fieldsig(node), self.nsig(node.child))
        if isinstance(node, N.PShare):
            return (t, self.nsig(node.child))
        if isinstance(node, N.PConcat):
            return (t, tuple(self.nsig(c) for c in node.inputs),
                    self._fieldsig(node))
        if isinstance(node, N.PRuntimeFilter):
            bk = tuple(self.esig(k, False)[0] for k in node.build_keys)
            pk = tuple(self.esig(k, False)[0] for k in node.probe_keys)
            # digest slots (mode + bloom geometry) are structural: the
            # traced collective and bitmap shapes differ per mode
            return (t, node.pack_bits, node.mode, node.bloom_bits,
                    node.bloom_k, bk, pk, self.nsig(node.build),
                    self.nsig(node.child))
        if isinstance(node, N.PMotion):
            hk = tuple(self.esig(k, False)[0] for k in node.hash_keys)
            return (t, node.kind, node.out_capacity, node.bucket_cap,
                    node.pre_compact, hk, self._fieldsig(node),
                    node.host_bucket_cap, node.hier_hosts,
                    node.host_combine, self.nsig(node.child))
        raise UnsupportedPlan(f"node {t}")


def _walk(session, plan: N.PlanNode, rewrite: bool = False):
    """(signature, the walker that made it) for a bound plan."""
    w = _Walker(session, rewrite=rewrite)
    root = ("root", w.nsig(plan),
            getattr(plan, "_direct_segment", None) is not None,
            w._fieldsig(plan))
    return root, w


def analyze(session, plan: N.PlanNode, rewrite: bool = False):
    """(signature, bindings, keyed scans, slot types) for a bound plan.
    ``rewrite=True`` (generic-plan build only) additionally replaces every
    parameter-site literal with its ``expr.Param`` slot IN PLACE."""
    root, w = _walk(session, plan, rewrite)
    return root, w.bindings, w.keyed, w.slots


# ------------------------------------------------------ the generic plan


def _next_pow2(n: int) -> int:
    return 1 << max(0, (int(n) - 1).bit_length())


def _text_converter(t: SqlType):
    """Literal token text → physical value, matching the binder's typed
    conversions (the fast-rebind contract is validated at build time:
    converter(build text) must equal the plan's bound literal)."""
    from cloudberry_tpu.plan.planner import _exact_decimal
    from cloudberry_tpu.types import date_to_days

    if t.base in (DType.INT32, DType.INT64):
        return lambda s: int(s)
    if t.base == DType.DECIMAL:
        return lambda s, k=t.scale: _exact_decimal(s, k)
    if t.base == DType.FLOAT64:
        return lambda s: float(s)
    if t.base == DType.DATE:
        return lambda s: date_to_days(s)
    return None


@dataclass
class FastRebind:
    """Tokenize-only rebinding for the canonical point-lookup shape
    (``WHERE k = ?`` on an indexed column): skip parse/bind/plan entirely
    — convert the literal text, sidecar-search the rows, slice the scan
    input, feed the value as the one parameter. The dispatcher's batch
    path leans on this to make per-request host work ~microseconds."""

    table: str
    phys: str
    sqltype: SqlType
    expect_rows: int
    input_key: str
    param_key: Optional[str]
    hashed_direct: bool          # multi-seg: route via the dist-key hash
    dist_dtype: Optional[np.dtype]

    def bind(self, session, text: str):
        """(inputs, bindings) for one literal text, or None → caller
        falls back to the full re-plan rebind."""
        from cloudberry_tpu.plan import pointlookup as PL

        conv = _text_converter(self.sqltype)
        try:
            v = conv(text)
        except (ValueError, TypeError, OverflowError):
            return None
        seg = None
        if self.hashed_direct:
            from cloudberry_tpu.utils import hashing

            nseg = session.config.n_segments
            h = hashing.hash_columns_np(
                [np.asarray([v], dtype=self.dist_dtype)])
            seg = int(hashing.jump_consistent_hash_np(h, nseg)[0])
        rows = PL._lookup(session, self.table, self.phys, seg, v)
        if rows is None or len(rows) != self.expect_rows:
            return None
        from cloudberry_tpu.exec import executor as X

        inputs = {self.input_key: X.point_scan_slice(
            self.table, rows, session, seg)}
        bindings = {}
        if self.param_key is not None:
            bindings[self.param_key] = np.asarray(
                v, dtype=self.sqltype.np_dtype)
        return inputs, bindings


def _redistributes(plan):
    """Redistribute motions in walk order, deduped by identity (shared
    subtrees re-walk) — the correspondence channel for copying observed
    bucket stats from the traced plan onto a signature-equal rebind."""
    from cloudberry_tpu.exec import executor as X

    seen: set[int] = set()
    out = []
    for n in X.all_nodes(plan):
        if isinstance(n, N.PMotion) and n.kind == "redistribute" \
                and id(n) not in seen:
            seen.add(id(n))
            out.append(n)
    return out


class GenericPlan:
    """One compiled program shared by every statement matching a
    (skeleton, signature) pair — rebinding feeds new literals/slices."""

    def __init__(self, session, skeleton: str, plan: N.PlanNode,
                 names, sig, bindings, keyed, slots):
        from cloudberry_tpu.exec import executor as X
        from cloudberry_tpu.exec.resource import estimate_plan_memory
        from cloudberry_tpu.sched import sharedcache

        self.skeleton = skeleton
        self.sig = sig
        self.config = session.config
        if session.config.debug.verify_plans:
            # planck gate on the GENERIC-PLAN FORM: the rewritten plan
            # (literals now $params slots, scan row counts now $nrw
            # inputs) must verify clean AND both slot families must
            # agree with the signature — a desynced slot would bind a
            # literal into the wrong predicate (or a row count into
            # the wrong scan) on every future rebind
            from cloudberry_tpu.plan.verify import check_plan

            check_plan(plan, session, "paramplan",
                       declared_slots=list(slots),
                       declared_nrw=sum(1 for k in bindings
                                        if k.startswith("$nrw")))
        # shared-tier guards (sched/sharedcache.py): content-stable table
        # version tokens + the plan epoch — store-scope entries match
        # across sessions, everything else stays private by construction
        self.names = names
        self.versions = sharedcache.table_versions(session, names)
        self.ddlv = sharedcache.plan_epoch(session)
        self.plan = plan
        self.keyed = keyed
        self.param_keys = sorted(bindings, key=lambda k: (k[:4],
                                                          int(k[4:])))
        self.keyed_keys = [s._input_key for s in keyed]
        self.table_names = sorted({s.table_name
                                   for s in X.scans_of(plan)
                                   if not X.keyed_scan(s)})
        # cached sorted-build join indexes this program reads next to its
        # tables (exec/joinindex.py) — rebinds re-feed them per table
        # version, the vmapped batch path rides them in_axes=None
        from cloudberry_tpu.exec.joinindex import jix_specs_of

        self.jix_keys = [s.key for s in jix_specs_of(plan)]
        self.est_bytes = estimate_plan_memory(plan).peak_bytes
        seg = getattr(plan, "_direct_segment", None)
        if session.config.n_segments > 1 and seg is None:
            self.kind = "dist"
        else:
            self.kind = "direct" if seg is not None else "single"
        if self.kind == "dist":
            from cloudberry_tpu.exec import dist_executor as DX

            self.fn = DX.compile_distributed(
                plan, session, param_keys=self.param_keys or None)
            self.exe = None
        else:
            self.exe = X.compile_plan(plan, session)
            self.fn = None
        # stacked-launch eligibility for the dispatcher (sched/dispatcher):
        # "sliced"  — every scan is a keyed point slice: stack ALL inputs;
        # "shared"  — no keyed scans, single-program: tables ride once
        #             (in_axes=None), only $params stacks.
        if self.kind in ("single", "direct") and self.keyed_keys \
                and not self.table_names:
            self.stack_mode = "sliced"
        elif self.kind == "single" and not self.keyed_keys \
                and self.param_keys:
            self.stack_mode = "shared"
        else:
            self.stack_mode = None
        self.fast: Optional[FastRebind] = None
        # the literal template (below), or why this variant has none
        self.template: Optional[LiteralTemplate] = None
        self.template_refused: Optional[str] = None
        self._rungs: dict[int, Any] = {}
        self._rung_lock = __import__("threading").Lock()

    def count_tables(self, session, sends: int = 1) -> None:
        """Count the literal-chosen lookup tables ``sends`` launches bind
        (``dict_params_bound``)."""
        n = sum(1 for k in self.param_keys if k.startswith(ex.TABLE_PARAM))
        if n:
            session.stmt_log.bump("dict_params_bound", n * sends)

    def matches(self, session, sig, versions, ddlv) -> bool:
        return (self.sig == sig and self.config is session.config
                and self.versions == versions and self.ddlv == ddlv)

    # --------------------------------------------------------- execution

    def bind_inputs(self, session, planB, keyedB, bindings) -> dict:
        """Assemble the program's inputs from a freshly bound plan:
        table columns (under the rebind's direct-dispatch segment), keyed
        scan slices REMAPPED to the compiled program's input keys, and the
        literal bindings as the ``$params`` entry."""
        from cloudberry_tpu.exec import executor as X

        seg = getattr(planB, "_direct_segment", None)
        tables = X.prepare_tables(self.table_names, session, segment=seg)
        if self.jix_keys:
            from cloudberry_tpu.exec.joinindex import join_index_inputs

            tables.update(join_index_inputs(self.plan, session, seg))
        for key, s in zip(self.keyed_keys, keyedB):
            if hasattr(s, "_point_rows"):
                tables[key] = X.point_scan_slice(
                    s.table_name, s._point_rows, session, seg)
            else:
                tables[key] = X._load_store_scan(s, session)
        if bindings:
            tables["$params"] = dict(bindings)
        return tables

    def run(self, session, planB, keyedB, bindings):
        """Execute the cached program with one rebind's values — never
        compiles."""
        from cloudberry_tpu.exec import executor as X
        from cloudberry_tpu.obs import trace as OT

        session.stmt_log.bump("param_binds")
        self.count_tables(session)
        if self.kind == "dist":
            from cloudberry_tpu.exec import dist_executor as DX

            # the program was traced from self.plan and names its nodes
            # by ordinal; the rebind's plan walks identically, so the
            # observed bucket demand pinned there is copied onto the
            # rebind's motions and a skew overflow still promotes
            # straight to the fitting rung
            try:
                return DX.execute_distributed(
                    self.plan, session, self.fn, inputs_plan=planB,
                    params=bindings, mode="dist-generic")
            finally:
                for a, b in zip(_redistributes(self.plan),
                                _redistributes(planB)):
                    ob = getattr(a, "_observed_bucket", None)
                    if ob is not None:
                        b._observed_bucket = ob
        with OT.stage("inputs", "launch_seconds", host=True):
            inputs = self.bind_inputs(session, planB, keyedB, bindings)
        return X.run_executable(self.exe, inputs, log=session.stmt_log)

    # ----------------------------------------------------- stacked launch

    def rung_fn(self, session, rung: int):
        """The vmapped executable for a batch of ``rung`` rebinds —
        compiled once per power-of-two rung (the dispatcher pads batches
        up to the rung, so recompiles are bounded by log2(max_batch))."""
        import jax

        with self._rung_lock:
            fn = self._rungs.get(rung)
        if fn is not None:
            return fn
        from cloudberry_tpu.exec import executor as X

        X.count_compile(session)
        session.stmt_log.bump("batch_rung_compiles")
        if self.stack_mode == "sliced":
            axes: Any = 0
        else:
            axes = {n: None for n in self.table_names}
            # join indexes ride once per batch, like the tables
            axes.update({k: None for k in self.jix_keys})
            axes["$params"] = 0
        from cloudberry_tpu.obs import programs as PG

        fn = PG.jit(jax.vmap(self.exe.raw_fn, in_axes=(axes,)),
                    X.node_titles(self.exe.plan), f"stacked x{rung}")
        with self._rung_lock:
            self._rungs[rung] = fn
        return fn


# --------------------------------------------------- the literal template
#
# A statement whose skeleton is known need not be parsed and planned to
# find the variant its plan would match: where every literal token either
# reaches the program as $prm values alone, by folds that can be run again
# on another text, or is repeated verbatim, the variant and its bindings
# follow from the tokens. The template is that derivation. It is SOUND
# when a hit runs the program, inputs and bindings the full path would
# for the same text; what it cannot show it refuses, each under a reason
# ``template_refused.<reason>`` counts:
#
#   dist, direct_segment   the plan is placed by a literal or spans segments
#   point_lookup           the literal chooses the scan's ROWS
#   plan_shape             a node outside _TEMPLATE_NODES (a join, a motion,
#                          a window, a subplan: capacities the planner
#                          estimates from a predicate's selectivity)
#   untracked_literal      a $prm slot, or a comparison that pruned a scan,
#                          whose literal names no token of the text
#   lookup_table           a literal-chosen lookup table ($dlt) one of whose
#                          literals names no token of the text
#   replay_mismatch        run again on the build's own text, the derivation
#                          does not give the build's bindings and partitions
#   arm_mismatch           a later send through the full path matched the
#                          variant with bindings the derivation does not give
#   no_stmt_cache, external, user_params
#                          (counted where those statements leave the gate)

_TEMPLATE_NODES = (N.PScan, N.PFilter, N.PProject, N.PAgg, N.PSort,
                   N.PLimit)


def _scan_files(scan) -> tuple:
    return tuple(p["file"] for p in scan._store_parts)


def _same_bindings(a: dict, b: dict) -> bool:
    """Bit for bit, dtype included."""
    return a.keys() == b.keys() and all(
        a[k].dtype == b[k].dtype and a[k].shape == b[k].shape
        and a[k].tobytes() == b[k].tobytes() for k in a)


class LiteralTemplate:
    """One variant's bindings as a function of the statement's literal
    tokens. ``pinned`` tokens must read as the build's did (they are baked
    into the program, or nothing accounts for them); each ``$prm`` slot
    replays its literal's origin on one of the others; each ``$dlt``
    lookup table is chosen again over its dictionary from its literals'
    tokens (``binder.lookup_table``); a store scan's partition list is
    decided again from the comparisons that pruned it and must come out
    as the variant's. ``unseen`` holds the free tokens no full-path send
    has yet shown under another text: the template binds nothing until
    it is empty."""

    def __init__(self, session, names, tokens, free, slots, fixed, scans,
                 files, tables):
        import weakref

        from cloudberry_tpu.plan.feedback import feedback_gen

        self.tokens = tokens        # the build's literal texts
        self.pinned = tuple((i, t) for i, t in enumerate(tokens)
                            if i not in free)
        self.slots = slots          # ((key, token index, origin, type), ...)
        self.fixed = fixed          # $nrw bindings: the table versions' own
        # ((key, token indices, mark, bound length), ...) a $dlt table
        self.tables = tables
        # per keyed scan: (table, ((col, op, origin, token index), ...),
        # the manifest's partitions to choose from), and the files the
        # variant reads of each
        self.scans = scans
        self.files = files
        self.unseen = frozenset(free)
        # which of the variant's tables were cold (scanned from the store,
        # not from RAM): a catalog's own state, and no version moves when a
        # table is materialized, yet the planner scans it otherwise
        self.cold = self._cold(session, names)
        self.fbgen = feedback_gen(session)
        # a shared scope's plan epoch leaves DDL to the signature, which
        # a hit never computes. What DDL can change under a text whose
        # tables are at the guarded versions is a view: catalogs without
        # views read the text alike (sharedcache.rung_scope_token's rule),
        # any other is held to the DDL version of its own last full-path
        # match
        self.viewless = not session.catalog.views
        self.ddl = weakref.WeakKeyDictionary()
        self.ddl[session.catalog] = session.catalog.ddl_version

    @staticmethod
    def _cold(session, names) -> tuple:
        tables = session.catalog.tables
        return tuple(getattr(tables.get(n), "cold", None) for n in names)

    def derive(self, session, tokens):
        """(bindings, each keyed scan's partition files) as this text's
        literal tokens give them, or None when the text is not one the
        template speaks for."""
        from cloudberry_tpu.exec import kernels as K
        from cloudberry_tpu.plan.binder import lookup_table, replay_literal
        from cloudberry_tpu.plan.scanprune import scan_bounds

        if len(tokens) != len(self.tokens):
            return None
        for i, text in self.pinned:
            if tokens[i] != text:
                return None
        out = dict(self.fixed)
        for key, i, origin, t in self.slots:
            lit = replay_literal(origin, tokens[i])
            if lit is None or lit.dtype != t:
                return None
            try:
                out[key] = np.asarray(lit.value, dtype=t.np_dtype)
            except (TypeError, ValueError, OverflowError):
                return None
        for key, idx, lits, n in self.tables:
            # (the table-version guard holds: the dictionary is the build's)
            tab = K.pad_to_rung(lookup_table(lits.kind, lits.sdict,
                                             [tokens[i] for i in idx]))
            if len(tab) != n:
                return None
            out[key] = tab
        files = []
        store = session.catalog.store
        for (table, cmps, candidates), own in zip(self.scans, self.files):
            if not cmps:
                files.append(own)  # no literal chose them
                continue
            vals = []
            for col, op, origin, i in cmps:
                lit = replay_literal(origin, tokens[i])
                if lit is None or not isinstance(lit.value, (int, float)):
                    return None
                vals.append((col, op, lit.value))
            # (the table-version guard holds: the manifest is the build's)
            parts, _ = store.select_partitions(
                table, *scan_bounds(vals), candidates=candidates)
            files.append(tuple(p["file"] for p in parts))
        return out, tuple(files)

    def bind(self, session, tokens) -> Optional[dict]:
        """The variant's bindings for this text, or None: not this
        template's text, or its literals prune other partitions than the
        variant reads."""
        got = self.derive(session, tokens)
        if got is None or got[1] != self.files:
            return None
        return got[0]

    def guards_hold(self, session, gp: "GenericPlan") -> bool:
        """What Session._cached_statement and GenericPlan.matches hold an
        entry to, short of the signature: config identity, plan epoch
        (UDF registry, topology), the feedback generation, this catalog's
        DDL, table versions, and which tables are cold."""
        from cloudberry_tpu.plan.feedback import feedback_gen
        from cloudberry_tpu.sched import sharedcache

        cat = session.catalog
        if gp.config is not session.config \
                or gp.ddlv != sharedcache.plan_epoch(session) \
                or self.fbgen != feedback_gen(session) \
                or not (self.viewless and not cat.views
                        or self.ddl.get(cat) == cat.ddl_version):
            return False
        try:
            return gp.versions == sharedcache.table_versions(
                session, gp.names) \
                and self.cold == self._cold(session, gp.names)
        except KeyError:
            return False

    def observe(self, session, tokens, bindings, keyed) -> bool:
        """A send that went the full path matched this variant: hold the
        derivation to what the planner bound. False: it differs."""
        from cloudberry_tpu.plan.feedback import feedback_gen

        got = self.derive(session, tokens)
        if got is None:
            # another pinned text or token type: the signature matched
            # all the same, so the template says nothing of this text
            return True
        if not _same_bindings(got[0], bindings) \
                or got[1] != tuple(_scan_files(s) for s in keyed):
            return False
        self.unseen = self.unseen - {
            i for i in self.unseen if tokens[i] != self.tokens[i]}
        self.fbgen = feedback_gen(session)
        self.viewless = self.viewless and not session.catalog.views
        self.ddl[session.catalog] = session.catalog.ddl_version
        return True


def _derive_template(session, gp: GenericPlan, plan, w: _Walker,
                     tokens, offsets):
    """The variant's LiteralTemplate, or the reason it has none."""
    from cloudberry_tpu.exec import executor as X

    if gp.kind != "single":
        return "dist" if gp.kind == "dist" else "direct_segment"
    if any(hasattr(s, "_point_rows") for s in w.keyed):
        return "point_lookup"
    if w.subplans or not all(isinstance(n, _TEMPLATE_NODES)
                             for n in X.all_nodes(plan)):
        return "plan_shape"
    index = {pos: i for i, pos in enumerate(offsets)}
    store = session.catalog.store

    def token_of(lit) -> Optional[int]:
        o = lit.origin if lit is not None else None
        return index.get(o.pos) if o is not None else None

    slots = []
    for k, lit in enumerate(w.slot_lits):
        i = token_of(lit)
        if i is None:
            return "untracked_literal"
        slots.append((f"$prm{k}", i, lit.origin, w.slots[k]))
    tables = []
    for key, lits in w.tables:
        idx = tuple(index.get(p) for p in lits.pos)
        if None in idx:
            return "lookup_table"
        tables.append((key, idx, lits, len(w.bindings[key])))
    scans = []
    for s in w.keyed:
        cmps = []
        for col, op, lit in getattr(s, "_prune_cmps", None) or ():
            i = token_of(lit)
            if i is None:
                return "untracked_literal"
            cmps.append((col, op, lit.origin, i))
        scans.append((s.table_name, tuple(cmps),
                      store.read_manifest(s.table_name)["partitions"]))
    # a token is free to change only where $prm slots and $dlt tables are
    # all it feeds
    free = ({i for _, i, _, _ in slots}
            | {i for _, idx, _, _ in tables for i in idx}) \
        - {token_of(b) for b in w.baked}
    fixed = {k: v for k, v in w.bindings.items() if k.startswith("$nrw")}
    t = LiteralTemplate(session, gp.names, tokens, free, tuple(slots), fixed,
                        tuple(scans), tuple(_scan_files(s) for s in w.keyed),
                        tuple(tables))
    got = t.bind(session, tokens)
    if got is None or not _same_bindings(got, w.bindings):
        return "replay_mismatch"
    return t


def _refuse_template(session, reason: str, gp: Optional[GenericPlan] = None
                     ) -> None:
    if gp is not None:
        gp.template = None
        gp.template_refused = reason
    session.stmt_log.bump(f"template_refused.{reason}")


def _note_template(session, gp: GenericPlan, plan, w: _Walker,
                   tokens, offsets) -> None:
    """Every full-path send that built or matched ``gp`` passes here:
    derive the variant's template once, then hold it to each such send
    (``observe``), which is also what arms it."""
    t = gp.template
    if t is None:
        if gp.template_refused is None:
            got = _derive_template(session, gp, plan, w, tokens, offsets)
            if isinstance(got, str):
                _refuse_template(session, got, gp)
            else:
                gp.template = got
                session.stmt_log.bump("template_builds")
    elif not t.observe(session, tokens, w.bindings, w.keyed):
        _refuse_template(session, "arm_mismatch", gp)


def template_bind(session, query: str):
    """(GenericPlan, bindings) where an armed template of the statement's
    skeleton speaks for this text and its guards hold, else None: one
    tokenisation, no parse, no plan. The caller launches the variant over
    its own plan and scans (``gp.run(session, gp.plan, gp.keyed,
    bindings)``)."""
    lexed = _lex(query)
    if lexed is None or not lexed[1]:
        return None
    skeleton, tokens, _ = lexed
    cache = session._generic_cache
    with session._generic_lock:
        bucket = cache.pop(skeleton, None)
        if bucket is None:
            return None
        cache[skeleton] = bucket  # LRU touch
        bucket = tuple(bucket)
    armed = False
    for gp in reversed(bucket):
        t = gp.template
        if t is None or t.unseen:
            continue
        armed = True
        if t.guards_hold(session, gp):
            bindings = t.bind(session, tokens)
            if bindings is not None:
                session.stmt_log.bump("template_binds")
                # a template hit IS a generic-plan reuse (as a fast
                # rebind is): the hit counter agrees with the full path
                session.stmt_log.bump("generic_hits")
                return gp, bindings
    if armed:
        session.stmt_log.bump("template_fallbacks")
    return None


# ----------------------------------------------------- session-side cache


_GENERIC_CACHE_MAX = 32


def _try_fast(session, gp: GenericPlan, plan, tok_params, bindings,
              keyed, slots) -> Optional[FastRebind]:
    """Attach the tokenize-only rebind template when the statement is the
    canonical single-parameter point lookup."""
    if len(tok_params) != 1 or len(slots) > 1 or len(keyed) != 1:
        return None
    if gp.kind == "dist" or gp.table_names:
        return None
    s = keyed[0]
    if not hasattr(s, "_point_rows"):
        return None
    out_to_phys = {out: phys for phys, out in s.column_map.items()}
    phys = out_to_phys.get(getattr(s, "_point_col", None))
    if phys is None:
        return None
    t = session.catalog.table(s.table_name)
    sqltype = t.schema.field(phys).type
    conv = _text_converter(sqltype)
    if conv is None:
        return None
    prm_keys = [k for k in gp.param_keys if k.startswith("$prm")]
    if len(prm_keys) != len(gp.param_keys):
        return None  # row-count params imply non-keyed scans — not fast
    param_key = prm_keys[0] if prm_keys else None
    try:
        v = conv(tok_params[0])
    except (ValueError, TypeError, OverflowError):
        return None
    # the converter must reproduce BOTH the bound literal (the $params
    # value) and the sidecar probe value, or fast rebinding would diverge
    # from the binder's typed folds — validate against the build's values
    if param_key is not None:
        bound = bindings[param_key]
        if slots[0] != sqltype or not np.asarray(v, bound.dtype) == bound:
            return None
    hashed_direct = False
    dist_dtype = None
    if session.config.n_segments > 1:
        if getattr(plan, "_direct_segment", None) is None:
            return None
        if t.policy.kind == "hashed":
            if list(t.policy.keys) != [phys]:
                return None
            hashed_direct = True
            dist_dtype = t.schema.field(phys).type.np_dtype
        elif t.policy.kind != "replicated":
            return None
    return FastRebind(s.table_name, phys, sqltype, s.num_rows,
                      gp.keyed_keys[0], param_key, hashed_direct,
                      dist_dtype)


@dataclass
class Prep:
    """One statement's rebinding package: the shared program plus this
    execution's freshly bound plan and its literal values."""
    gp: GenericPlan
    plan: N.PlanNode
    keyed: list
    bindings: dict
    built: bool = False

    def run(self, session):
        return self.gp.run(session, self.plan, self.keyed, self.bindings)


def lookup_or_build(session, query: str, plan) -> Optional[Prep]:
    """The generic-plan gate for one freshly bound plan: normalize, match
    the (skeleton, signature) cache, build on miss. None → the statement
    keeps the non-generic path."""
    from cloudberry_tpu.exec import executor as X

    if not session.config.sched.generic_plans:
        return None
    if getattr(plan, "_no_stmt_cache", False):
        _refuse_template(session, "no_stmt_cache")
        return None
    lexed = _lex(query)
    if lexed is None or not lexed[1]:
        return None
    skeleton, tokens, offsets = lexed
    names = sorted({s.table_name for s in X.scans_of(plan)})
    if session._any_external(names):
        _refuse_template(session, "external")
        return None
    from cloudberry_tpu.sched import sharedcache

    try:
        versions = sharedcache.table_versions(session, names)
    except KeyError:
        return None
    ddlv = sharedcache.plan_epoch(session)
    try:
        sig, w = _walk(session, plan)
    except UnsupportedPlan:
        return None
    lock = session._generic_lock
    cache = session._generic_cache
    with lock:
        bucket = cache.pop(skeleton, None)
        if bucket is not None:
            cache[skeleton] = bucket  # LRU touch
            bucket = tuple(bucket)
    for gp in bucket or ():
        if gp.matches(session, sig, versions, ddlv):
            session.stmt_log.bump("generic_hits")
            _note_template(session, gp, plan, w, tokens, offsets)
            return Prep(gp, plan, w.keyed, w.bindings)
    # build: re-walk with rewrite=True so the compiled program reads its
    # literals from $params (slot order identical by the walker contract)
    sig2, w2 = _walk(session, plan, rewrite=True)
    assert sig2 == sig and list(w2.bindings) == list(w.bindings)
    from cloudberry_tpu.obs import trace as OT

    with OT.stage("compile", skeleton=skeleton[:80]):
        gp = GenericPlan(session, skeleton, plan, names, sig, w2.bindings,
                         w2.keyed, w2.slots)
    gp.fast = _try_fast(session, gp, plan, tokens, w2.bindings, w2.keyed,
                        w2.slots)
    # the first walk saw the literals the rewrite has since replaced
    _note_template(session, gp, plan, w, tokens, offsets)
    session.stmt_log.bump("generic_builds")
    with lock:
        bucket = cache.setdefault(skeleton, [])
        bucket.append(gp)
        del bucket[:-session.config.sched.max_variants]
        while len(cache) > _GENERIC_CACHE_MAX:
            cache.pop(next(iter(cache)))
    return Prep(gp, plan, w2.keyed, w2.bindings, built=True)


def generic_runner(session, query: str, plan):
    """Session hook (session._execute_and_cache): a zero-argument runner
    over the shared compiled program, or None for non-generic
    statements."""
    prep = lookup_or_build(session, query, plan)
    if prep is None:
        return None
    return lambda: prep.run(session)


# -------------------------------------------------------- batch execution


def prepare_one(session, query: str) -> Optional[Prep]:
    """Full host-side preparation of one statement for the dispatcher:
    parse → bind/plan → generic lookup/build. None → not batchable."""
    from cloudberry_tpu.plan.planner import plan_statement
    from cloudberry_tpu.sql.parser import parse_sql

    session._sync_store()
    try:
        stmt = parse_sql(query)
        result = plan_statement(stmt, session, {})
    except Exception:
        return None
    if result.is_ddl:
        return None
    return lookup_or_build(session, query, result.plan)


def run_batch(session, sqls: list[str]):
    """Execute same-skeleton statements as ONE stacked launch: per-request
    host rebinding (tokenize-only when the fast template applies, else a
    host re-plan), inputs stacked to the next power-of-two rung, one
    vmapped program launch, results split per request.

    Returns a list of ColumnBatch (one per statement) or None when the
    group is not stackable — the dispatcher then falls back to sequential
    dispatch. Never compiles except once per (skeleton, signature, rung).
    """
    import jax

    from cloudberry_tpu.exec import executor as X
    from cloudberry_tpu.exec.resource import ResourceError
    from cloudberry_tpu.utils.faultinject import fault_point

    if len(sqls) < 2 or not session.config.sched.generic_plans:
        return None
    prep0 = prepare_one(session, sqls[0])
    if prep0 is None or prep0.gp.stack_mode is None:
        return None
    gp = prep0.gp
    shared = gp.stack_mode == "shared"
    if shared:
        # tables ride ONCE (vmap in_axes=None) — per request only the
        # literal bindings vary
        from cloudberry_tpu.exec import executor as X

        base = X.prepare_tables(gp.table_names, session, segment=None)
        if gp.jix_keys:
            from cloudberry_tpu.exec.joinindex import join_index_inputs

            base.update(join_index_inputs(gp.plan, session, None))
        per: list[dict] = [dict(prep0.bindings)]
    else:
        per = [gp.bind_inputs(session, prep0.plan, prep0.keyed,
                              prep0.bindings)]
    for q in sqls[1:]:
        bound = None
        if gp.fast is not None:
            norm = normalize(q)
            if norm is None or norm[0] != gp.skeleton:
                return None
            fb = gp.fast.bind(session, norm[1][0])
            if fb is not None:
                tabs, binds = fb
                if binds:
                    tabs["$params"] = binds
                bound = tabs
                session.stmt_log.bump("fast_rebinds")
                # a fast rebind IS a generic-plan reuse (the tokenize-
                # only subset): the hit counter must agree with the
                # prepare_one path so per-statement attribution
                # (dispatcher batch finishes) sums to the engine total
                session.stmt_log.bump("generic_hits")
        if bound is None:
            p = prepare_one(session, q)
            if p is None or p.gp is not gp:
                return None  # shape drifted mid-batch — sequential path
            bound = dict(p.bindings) if shared \
                else gp.bind_inputs(session, p.plan, p.keyed, p.bindings)
        per.append(bound)
    k = len(per)
    rung = _next_pow2(k)
    per += [per[-1]] * (rung - k)
    if shared:
        stacked = dict(base)
        stacked["$params"] = {
            key: np.stack([b[key] for b in per])
            for key in gp.param_keys}
    else:
        # host-side stacking: leaves are numpy (point_scan_slice), so the
        # whole batch crosses to the device as ONE transfer per leaf at
        # dispatch instead of one put per request per column
        stacked = jax.tree_util.tree_map(
            lambda *xs: np.stack([np.asarray(x) for x in xs]), *per)
    fn = gp.rung_fn(session, rung)
    cost = gp.est_bytes * (rung if gp.stack_mode == "shared" else 1)
    try:
        with session._gate, session._admitted(cost):
            fault_point("sched_flush")
            # cancel seam at the batched launch: a cancelled/expired
            # member aborts the flush (StatementError is NOT part of the
            # fallback catch below — the dispatcher re-routes survivors)
            from cloudberry_tpu.lifecycle import check_cancel

            check_cancel()
            session.stmt_log.bump("dispatches")
            from cloudberry_tpu.obs import trace as OT

            with OT.stage("launch", mode="stacked", rung=rung):
                cols, sel, checks = fn(stacked)
                X.raise_checks(checks)
    except (ResourceError, X.ExecError):
        # vmapped checks OR across lanes: ONE request's runtime check
        # (subquery cardinality, expansion overflow, ...) must not error
        # its batchmates — fall back to sequential dispatch, where each
        # statement gets its own verdict and the grow-and-retry loop
        return None
    session.stmt_log.bump("batched_statements", k)
    gp.count_tables(session, k)
    out = []
    host_cols = {name: np.asarray(v) for name, v in cols.items()}
    host_sel = np.asarray(sel)
    for i in range(k):
        out.append(X.make_batch(
            gp.plan, {name: v[i] for name, v in host_cols.items()},
            host_sel[i]))
    return out
