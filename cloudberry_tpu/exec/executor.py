"""Single-program executor: plan tree → one jitted XLA computation.

The reference pulls tuples through a process-per-slice Volcano tree
(ExecProcNode, src/backend/executor/execProcnode.c); here the WHOLE plan
compiles into one XLA program over fixed-capacity column arrays — scans are
function inputs, operators are the kernels in exec/kernels.py, and (in
distributed mode, exec/dist_executor.py) motions are collectives. Runtime
"can't happen" conditions (agg capacity overflow, duplicate build keys in a
PK join) are returned as scalar check outputs and raised host-side after the
run — the shape-world analog of ereport().
"""

from __future__ import annotations

import functools
import math
import re
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from cloudberry_tpu.columnar.batch import ColumnBatch
from cloudberry_tpu.exec import kernels as K
from cloudberry_tpu.exec.expr_compile import compile_expr
from cloudberry_tpu.obs import programs as PG
from cloudberry_tpu.plan import expr as ex
from cloudberry_tpu.plan import nodes as N
from cloudberry_tpu.types import DType, Field, Schema


class ExecError(RuntimeError):
    pass


class DuplicateBuildKeyError(ExecError):
    """The planner assumed a unique (PK) build side but the data holds
    duplicate build keys — a semantic error (results would be wrong, so
    the statement aborts; never retryable). Raised from the runtime
    duplicate check every inner/left lookup join carries, instead of
    silently trusting the planner's uniqueness inference."""


@dataclass
class Executable:
    plan: N.PlanNode
    fn: Callable  # (tables pytree) -> (cols dict, sel, checks dict)
    table_names: list[str]
    # scans bound to pruned micro-partition reads (plan/scanprune.py);
    # their inputs key by scan identity, not table name
    store_scans: list = None  # type: ignore[assignment]
    # the unjitted trace function — the micro-batch dispatcher vmaps it
    # into stacked-parameter executables (sched/paramplan.py rung_fn)
    raw_fn: Callable = None  # type: ignore[assignment]
    # instrumented programs return a 4th output (per-node row counts);
    # EXPLAIN ANALYZE's pipeline path runs them directly (instrument.py)
    instrumented: bool = False
    # the one-shot launch's program (run_executable): ``fn`` ending in
    # pack_answer, so the host gets the whole answer in one wait
    packed_fn: Callable = None  # type: ignore[assignment]
    # (lookups, expansions, compacted lookups, direct lookups, semi-joins
    # on a scan) among the plan's joins (join_shapes), fixed when the
    # plan is lowered and counted on every launch
    join_shapes: tuple = (0, 0, 0, 0, 0)
    # (rows in, capacity, keys carried, sort words, direct tables) summed
    # over the plan's grouped aggregates (agg_shapes), fixed and counted
    # likewise
    agg_shapes: tuple = (0, 0, 0, 0, 0)


def execute(plan: N.PlanNode, session) -> ColumnBatch:
    seg = getattr(plan, "_direct_segment", None)
    if session.config.n_segments > 1 and seg is None:
        from cloudberry_tpu.exec.dist_executor import execute_distributed

        return execute_distributed(plan, session)
    exe = compile_plan(plan, session)
    return run_executable(exe, prepare_inputs(exe, session, segment=seg),
                          log=getattr(session, "stmt_log", None))


# a scan's row count where it rides beside the scan's columns as data
NROWS = "$nrows"


def keyed_scan(s: N.PScan) -> bool:
    """Scans whose input rides under a per-scan key instead of the
    table name: pruned store reads and point-lookup slices."""
    return hasattr(s, "_store_parts") or hasattr(s, "_point_rows")


def count_compile(session) -> None:
    """Record one XLA program construction on the engine's shared counters
    (exec/instrument.py StatementLog) — the compile-hit observability every
    plan-cache consumer reads (zero after warmup is the generic-plan
    contract, sched/paramplan.py)."""
    log = getattr(session, "stmt_log", None)
    if log is not None:
        log.bump("compiles")


def compile_plan(plan: N.PlanNode, session,
                 platform: str | None = None,
                 instrument: bool = False) -> Executable:
    """``instrument=True`` (EXPLAIN ANALYZE's pipeline path,
    exec/instrument.py run_pipeline) compiles THE SAME program through
    this same entry point with per-node row counts as a 4th output —
    no private lowerer."""
    scans = list(scans_of(plan))
    store_scans = [s for s in scans if keyed_scan(s)]
    table_names = sorted({s.table_name for s in scans
                          if not keyed_scan(s)})
    platform = platform or jax.default_backend()
    count_compile(session)

    dicts = stamp_dict_tables(plan)
    titles = node_titles(plan)

    def run(tables):
        low = Lowerer(tables, platform=platform,
                      params=tables.get("$params"), count_rows=instrument)
        cols, sel = low.lower(plan)
        out = {f.name: cols[f.name] for f in plan.fields}
        if instrument:
            return out, sel, low.checks, low.node_counts
        return out, sel, low.checks

    def jit(fn, what):
        # registered where it is built (obs/programs.py): the statement,
        # the plan's node titles, each trace's abstract inputs
        jitted = PG.jit(fn, titles, what)
        return _WithDictTables(jitted, dicts) if dicts else jitted

    raw = run if not dicts else \
        (lambda tables: run({**tables, DICT_TABLES: dicts}))
    if instrument:
        return Executable(plan, jit(run, "instrumented"), table_names,
                          store_scans, raw, instrumented=True)
    return Executable(plan, jit(run, "one-shot"), table_names, store_scans,
                      raw,
                      packed_fn=jit(
                          lambda tables: pack_answer(*run(tables)),
                          "one-shot packed"),
                      join_shapes=join_shapes(plan),
                      agg_shapes=agg_shapes(plan, platform))


# A string predicate is decided once over its column's dictionary, on the
# host, and the program gathers the verdicts by code (ex.DictLookup). A
# table the statement's literals chose (ex.LookupLiterals: an IN list, a
# LIKE pattern) rides into the program as an INPUT at every length: its
# contents change from draw to draw, and a constant would make each draw
# a program of its own (in a generic plan it is a $params binding,
# sched/paramplan.py). Any other table longer than this rides as an input
# too, at the rung above its length: a large dictionary's size and
# contents follow from the data. Shorter ones (a rank or translation
# table over a flag, a mode, a segment) stay constants.
DICT_TABLES = "$dicts"
_DICT_TABLE_MIN = 64


def stamp_dict_tables(plan: N.PlanNode) -> dict:
    """{input key: device array} of the plan's input lookup tables (see
    above), each padded to its rung; every such ``DictLookup`` is
    stamped with its key (``_table_input``), which ``compile_expr``
    reads the table by when the lowerer offers it. Keys follow the
    plan's document order, so two plans of one shape name their tables
    alike. A table a generic plan binds (``ex.TABLE_PARAM``) is left to
    its ``$params``."""
    from cloudberry_tpu.plan.distribute import _node_exprs

    out = {}
    for node in numbered_nodes(plan):
        for e in _node_exprs(node):
            for sub in ex.walk(e):
                if not isinstance(sub, ex.DictLookup) or getattr(
                        sub, "_table_input", "$").startswith(
                            ex.TABLE_PARAM):
                    continue
                if sub.literals is not None \
                        or len(sub.table) > _DICT_TABLE_MIN:
                    key = f"$dict{len(out)}"
                    object.__setattr__(sub, "_table_input", key)
                    out[key] = jnp.asarray(K.pad_to_rung(
                        np.asarray(sub.table)))
    return out


class _WithDictTables:
    """A jitted program called with its dictionary tables beside the
    inputs it is handed."""

    def __init__(self, jitted, dicts: dict):
        self.jitted, self.dicts = jitted, dicts
        self.__name__ = getattr(jitted, "__name__", "run")

    def __call__(self, tables):
        return self.jitted({**tables, DICT_TABLES: self.dicts})

    def lower(self, tables):
        return self.jitted.lower({**tables, DICT_TABLES: self.dicts})


def prepare_tables(table_names: list[str], session,
                   segment: int | None = None) -> dict:
    """segment=None: whole tables (single-segment mode); otherwise ONE
    segment's shard (direct dispatch — cdbtargeteddispatch analog)."""
    tables = {}
    for name in table_names:
        t = session.catalog.table(name)
        t.ensure_loaded()  # safety: a cold table on the RAM path loads whole
        if segment is None or t.policy.kind == "replicated":
            # one segment: the columns at the scan's capacity rung, the
            # row count beside them as data (Lowerer.scan); a replicated
            # table under direct dispatch is read whole, at its length
            cap = K.row_rung_up(t.num_rows) if segment is None else 0
            tables[name] = {c: jnp.asarray(K.pad_rows(np.asarray(v), cap))
                            for c, v in t.data.items()}
            for c, vm in t.validity.items():
                tables[name][f"$nn:{c}"] = jnp.asarray(K.pad_rows(
                    np.asarray(vm, dtype=np.bool_), cap))
            if segment is None:
                tables[name][NROWS] = np.int64(t.num_rows)
        else:
            st = session.sharded_table(name)
            tables[name] = {c: jnp.asarray(v[segment])
                            for c, v in st.columns.items()}
    return tables


def prepare_inputs(exe: Executable, session,
                   segment: int | None = None) -> dict:
    """All inputs for one executable: RAM tables by name plus pruned
    store reads keyed by scan identity plus cached join indexes."""
    return _assemble_inputs(exe.table_names, exe.store_scans or (),
                            session, segment, plan=exe.plan)


def prepare_plan_inputs(plan: N.PlanNode, session,
                        segment: int | None = None) -> dict:
    """Same input assembly from a bare plan (instrumented execution)."""
    scans = list(scans_of(plan))
    return _assemble_inputs(
        sorted({s.table_name for s in scans if not keyed_scan(s)}),
        [s for s in scans if keyed_scan(s)],
        session, segment, plan=plan)


def _assemble_inputs(table_names, store_scans, session, segment,
                     plan=None) -> dict:
    tables = prepare_tables(table_names, session, segment=segment)
    for s in store_scans:
        if hasattr(s, "_point_rows"):
            tables[s._input_key] = _load_point_scan(s, session, segment)
        else:
            tables[s._input_key] = _load_store_scan(s, session)
    if plan is not None:
        # cached sorted-build join indexes ride next to the tables (the
        # $params discipline): same shapes every execution, so feeding a
        # fresh index never retraces — exec/joinindex.py
        from cloudberry_tpu.exec.joinindex import join_index_inputs

        tables.update(join_index_inputs(plan, session, segment))
    return tables


def _load_point_scan(scan: N.PScan, session, segment) -> dict:
    """Slice exactly the sidecar-matched rows (plan/pointlookup.py) out
    of the table — or its direct-dispatched shard — as the scan input."""
    return point_scan_slice(scan.table_name, scan._point_rows, session,
                            segment)


def point_scan_slice(table_name: str, rows, session, segment) -> dict:
    """One point-bound scan's input columns: the matched rows sliced from
    the table (or its direct-dispatched shard). Shared by normal input
    assembly and the generic-plan fast rebind (sched/paramplan.py), which
    re-slices per literal without re-planning. Slices stay HOST arrays —
    jit converts at dispatch, and the micro-batch path stacks many
    requests host-side before the single device transfer."""
    t = session.catalog.table(table_name)
    t.ensure_loaded()
    out = {}
    if segment is None or t.policy.kind == "replicated":
        for c, v in t.data.items():
            out[c] = np.asarray(v)[rows]
        for c, vm in t.validity.items():
            out[f"$nn:{c}"] = np.asarray(vm, dtype=np.bool_)[rows]
    else:
        st = session.sharded_table(table_name)
        for c, v in st.columns.items():
            out[c] = np.asarray(v[segment])[rows]
    return out


_STORE_SCAN_CACHE_MAX = 16


def _load_store_scan(scan: N.PScan, session) -> dict:
    """Read a pruned scan's columns from micro-partitions (column
    projection: ONLY column_map + mask_map physical columns are read),
    cached per (table, version, partitions, columns). Cache traffic is
    visible on the metrics plane (``store_scan_cache_*`` counters —
    meta "metrics"), and a cache miss consults the HBM buffer pool
    per partition before touching the store (exec/bufferpool.py)."""
    store = session.catalog.store
    key = (scan.table_name, store.effective_version(scan.table_name),
           tuple(p["file"] for p in scan._store_parts),
           tuple(sorted(scan.column_map)), tuple(sorted(scan.mask_map)))
    cache = session._store_scan_cache
    log = getattr(session, "stmt_log", None)
    # LRU, not FIFO: pop-and-reinsert moves a hit to the dict's end so a
    # hot table's scan survives a burst of one-off queries; eviction
    # takes the true least-recently-used head. Hits now MUTATE the dict,
    # and shared-session server mode runs concurrent readers — the lock
    # keeps reorder/evict/insert atomic (the store read itself runs
    # unlocked; two simultaneous misses read twice, harmlessly).
    lock = session._store_scan_lock
    with lock:
        hit = cache.pop(key, None)
        if hit is not None:
            cache[key] = hit
    if hit is not None:
        if log is not None:
            log.bump("store_scan_cache_hits")
        return hit
    if log is not None:
        log.bump("store_scan_cache_misses")
    hit = _read_scan_columns(scan, session, log)
    evicted = 0
    with lock:
        while len(cache) >= _STORE_SCAN_CACHE_MAX:
            cache.pop(next(iter(cache)))
            evicted += 1
        cache[key] = hit
    if evicted and log is not None:
        log.bump("store_scan_cache_evictions", evicted)
    return hit


def _read_scan_columns(scan: N.PScan, session, log) -> dict:
    """Assemble one pruned scan's input dict. With the buffer pool on,
    partitions are looked up (and admitted) individually and the chunks
    concatenated in part order — read_partitions does exactly that
    internally, so the assembly is bit-identical to one batched read;
    resident partitions skip the host read/decode entirely."""
    from cloudberry_tpu.exec import bufferpool as BUF

    store = session.catalog.store
    needed = sorted(set(scan.column_map) | set(scan.mask_map))
    parts = list(scan._store_parts)
    bpool = BUF.pool_for(session)
    # the columns cross to the device ONCE, at the scan's capacity rung
    # (zeros past the partitions' rows), the row count beside them
    if bpool is None or not parts:
        cols, validity = store.read_partitions(scan.table_name, parts,
                                               needed)
        if log is not None and parts:
            log.bump("host_decodes", len(parts))
        hit = {c: jnp.asarray(K.pad_rows(np.asarray(v), scan.capacity))
               for c, v in cols.items()}
        for c, v in validity.items():
            hit[f"$nn:{c}"] = jnp.asarray(K.pad_rows(
                np.asarray(v, dtype=np.bool_), scan.capacity))
        hit[NROWS] = np.int64(scan.num_rows)
        return hit
    cols_key = tuple(needed)
    col_chunks: dict[str, list] = {}
    val_chunks: dict[str, list] = {}
    for part in parts:
        pk = BUF.partition_key(session, scan.table_name, part, cols_key)
        ent = bpool.lookup(pk, log)
        if ent is None:
            cols, validity = store.read_partitions(
                scan.table_name, [part], needed)
            if log is not None:
                log.bump("host_decodes")
            ent = {"cols": {c: np.asarray(v) for c, v in cols.items()},
                   "validity": {c: np.asarray(v, dtype=np.bool_)
                                for c, v in validity.items()}}
            bpool.offer(pk, ent, table=scan.table_name, log=log)
        for c, v in ent["cols"].items():
            col_chunks.setdefault(c, []).append(v)
        for c, v in ent["validity"].items():
            val_chunks.setdefault(c, []).append(v)
    short = scan.capacity - scan.num_rows
    if short > 0:
        for vs in (*col_chunks.values(), *val_chunks.values()):
            vs.append(np.zeros((short,), dtype=vs[0].dtype))
    hit = {c: (jnp.asarray(vs[0]) if len(vs) == 1
               else jnp.concatenate([jnp.asarray(v) for v in vs]))
           for c, vs in col_chunks.items()}
    for c, vs in val_chunks.items():
        # chunks are bool by construction (pool entries and fresh
        # decodes both store np.bool_), so no re-cast is needed
        hit[f"$nn:{c}"] = (jnp.asarray(vs[0]) if len(vs) == 1
                           else jnp.concatenate(
                               [jnp.asarray(v) for v in vs]))
    hit[NROWS] = np.int64(scan.num_rows)
    return hit


# A leaf larger than this rides beside the packed buffers as an array of
# its own: packing is for answers whose cost is the number of reads, and
# concatenating a large result holds it twice on the device (the TPU
# compiler's byte relayout of an 8 MiB leaf takes 4x its size in
# temporaries). 1 MiB still covers a capacity-sized aggregate: 60,000
# rows of three int64 columns read in 1.40 ms packed against 3.15 ms as
# four overlapped reads (PERF.md §6, PR 27).
_PACK_LEAF_MAX = 1 << 20


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["bufs"], meta_fields=["layout"])
@dataclass
class PackedAnswer:
    """What a packed one-shot program returns: ``bufs``, the device
    arrays the host reads (the byte buffer, the float64 buffer, then
    every leaf too large to pack), and ``layout``, which says where each
    of the program's outputs lies in them. The layout is static pytree
    data: jit keeps it with the output structure of each shape it
    traced, so it is computed once, when the program is built, from the
    program's own output avals."""

    bufs: tuple
    layout: tuple


def _check_flag(bad):
    """A check as one scalar of the packed answer: whether any lane of it
    is set; a check that COUNTS (an integer: the rows that overflowed a
    join's own capacity, 0 where they fit) keeps its count, which the
    host grows the capacity by (``raise_checks``)."""
    return jnp.max(bad) if jnp.issubdtype(bad.dtype, jnp.integer) \
        else jnp.any(bad)


def pack_answer(cols, sel, checks) -> PackedAnswer:
    """The end of a one-shot program: one flag per check (``any()`` of
    it, in the dict's order), ``sel``, and every output column and mask,
    bit for bit in as few device buffers as the chip's compiler allows.
    Every dtype but float64 is bitcast into ONE uint8 buffer; float64
    leaves share one float64 buffer, because a TPU holds a float64 as
    two float32 and its compiler refuses the bitcast (the same rule on
    every platform, so the CPU tests run what the chip runs). Within a
    buffer the widest items come first: each host view is then aligned
    with no padding."""
    import jax.lax as lax

    # what a program does outside any plan node carries a scope too
    # (obs/programs.py UNNUMBERED): the check flags' reduction, the
    # packing
    with jax.named_scope("checks"):
        flags = [_check_flag(bad) for bad in checks.values()]
    leaves = [jnp.asarray(x) for x in (*flags, sel, *cols.values())]
    # 0: the byte buffer, 1: the float64 buffer, 2: beside them
    kinds = [2 if x.nbytes > _PACK_LEAF_MAX
             else int(x.dtype == jnp.float64) for x in leaves]
    bufs, place = [], {}  # place[leaf] = (buffer, offset in its items)
    with jax.named_scope("answer"):
        for k in (0, 1):
            mine = sorted((i for i, kind in enumerate(kinds) if kind == k),
                          key=lambda i: -leaves[i].dtype.itemsize)
            parts, at = [], 0
            for i in mine:
                x = leaves[i]
                if k == 0:
                    x = x.astype(jnp.uint8) if x.dtype == jnp.bool_ \
                        else lax.bitcast_convert_type(x, jnp.uint8)
                place[i] = (len(bufs), at)
                parts.append(x.reshape(-1))
                at += parts[-1].size
            if parts:
                bufs.append(jnp.concatenate(parts))
    for i, kind in enumerate(kinds):
        if kind == 2:
            place[i] = (len(bufs), -1)
            bufs.append(leaves[i])
    layout = (tuple(checks), tuple(cols),
              tuple((*place[i], x.shape, x.dtype.name)
                    for i, x in enumerate(leaves)))
    return PackedAnswer(tuple(bufs), layout)


def unpack_answer(layout, host_bufs):
    """``(cols, sel, checks)`` of host arrays from a PackedAnswer's
    buffers once they are NumPy arrays: views cut by the layout, with
    the dtypes and shapes the unpacked program returns."""
    check_names, col_names, slots = layout
    leaves = []
    for b, at, shape, dtype in slots:
        buf = host_bufs[b]
        if at >= 0:
            dtype = np.dtype(dtype)
            n = math.prod(shape) * dtype.itemsize // buf.itemsize
            buf = buf[at:at + n].view(dtype).reshape(shape)
        leaves.append(buf)
    k = len(check_names)
    return (dict(zip(col_names, leaves[k + 1:])), leaves[k],
            dict(zip(check_names, leaves[:k])))


def run_executable(exe: Executable, tables: dict, log=None) -> ColumnBatch:
    """One launch of a compiled program, split where the time goes
    (obs/trace.py, children of ``launch``): ``dispatch`` until the call
    returns (the first call of a shape traces and compiles inside it),
    ``device-wait`` for the blocking device-to-host read of the packed
    answer (every buffer's copy is started before the first is waited
    for: one read, two where the answer holds a float64), ``fetch`` for
    the host's part: cutting the buffers into views, the check flags —
    examined before a batch is built — and the batch. ``log`` (the
    engine's StatementLog) counts the launch and its reads."""
    from cloudberry_tpu.obs import trace as OT

    with OT.stage("dispatch", "launch_seconds",
                  plan=type(exe.plan).__name__):
        packed = exe.packed_fn(tables)
    with OT.stage("device-wait", "launch_seconds"):
        host = jax.device_get(packed.bufs)
    if log is not None:
        log.bump("launch_packed")
        log.bump("launch_d2h_reads", len(host))
        count_join_shapes(log, exe.join_shapes)
        count_agg_shapes(log, exe.agg_shapes)
        count_scan_rows(log, tables)
    with OT.stage("fetch", "launch_seconds", host=True) as st:
        cols, sel, checks = unpack_answer(packed.layout, host)
        raise_checks(checks)
        batch = make_batch(exe.plan, cols, sel)
        st.args["columns"] = len(batch.columns)
        st.args["bytes"] = sum(int(a.nbytes)
                               for a in batch.columns.values())
        st.args["reads"] = len(host)
    return batch


def run_prepared(exe: Executable, session, segment=None) -> ColumnBatch:
    """prepare_inputs (the ``inputs`` stage) + run_executable: the
    non-generic statement runner."""
    from cloudberry_tpu.obs import trace as OT

    with OT.stage("inputs", "launch_seconds", host=True):
        tables = prepare_inputs(exe, session, segment=segment)
    return run_executable(exe, tables, log=session.stmt_log)


def raise_checks(checks: dict) -> None:
    for msg, bad in checks.items():
        bad = np.asarray(bad)
        if bool(bad.any()):
            if "duplicate keys" in msg:
                raise DuplicateBuildKeyError(msg)
            if bad.dtype.kind in "iu":
                # a counting check (Lowerer._compact_rows): the rows
                # that came, for grow_expansion to size the retry by
                msg = f"{msg}: {int(bad.max())} rows"
            raise ExecError(msg)


def make_batch(plan: N.PlanNode, cols, sel) -> ColumnBatch:
    shown = [f for f in plan.fields if not f.name.startswith("$vm")]
    fields = tuple(Field(f.name, f.type) for f in shown)
    dicts = {f.name: f.sdict for f in shown if f.sdict is not None}
    validity = {}
    for f in shown:
        ms = f.masks
        if ms and all(m in cols for m in ms):
            v = np.asarray(cols[ms[0]]).astype(bool)
            for m in ms[1:]:
                v = v & np.asarray(cols[m]).astype(bool)
            validity[f.name] = v
    return ColumnBatch(Schema(fields),
                       {f.name: np.asarray(cols[f.name]) for f in shown},
                       np.asarray(sel), dicts, validity=validity)


def _rank_better(mx: bool, v1, r1, c1, v2, r2, c2):
    """True where lane 2 beats lane 1 by (valid desc, sort rank, code) —
    THE extreme comparator: an invalid (NULL) lane never beats a valid
    one, strings compare by collation rank with code as the
    associativity tie-break. Shared by the running-extreme segmented
    scan and the ROWS-frame sparse-table query so the two min/max paths
    cannot diverge."""
    if mx:
        by_rank = (r2 > r1) | ((r2 == r1) & (c2 > c1))
    else:
        by_rank = (r2 < r1) | ((r2 == r1) & (c2 < c1))
    return (v2 & ~v1) | ((v2 == v1) & by_rank)


def _as_column(v, cap: int):
    """Broadcast a 0-d (constant) value to column shape — constant
    projections, sort keys, and window keys (e.g. grouping() folded to a
    literal per grouping-sets branch) all need full columns."""
    return jnp.broadcast_to(v, (cap,)) if v.ndim == 0 else v


def _vsearch(s, target, lo, hi, cap: int, lower: bool):
    """Vectorized per-row binary search over the (partition-wise sorted)
    array s restricted to per-row inclusive bounds [lo, hi]: returns the
    insertion point — first index j with s[j] >= target (lower) or
    s[j] > target (upper); hi+1 when every bounded element is smaller.
    O(log cap) unrolled lock-step halvings (no data-dependent trip
    counts, so the whole thing stays inside the one XLA program)."""
    l = jnp.asarray(lo)
    h = jnp.asarray(hi) + 1
    for _ in range(max(1, int(cap).bit_length()) + 1):
        active = l < h
        m = (l + h) // 2
        mv = s[jnp.clip(m, 0, cap - 1)]
        go_right = (mv < target) if lower else (mv <= target)
        l = jnp.where(active & go_right, m + 1, l)
        h = jnp.where(active & ~go_right, m, h)
    return l


def _rmq_extreme(ks, cs, va, lo, hi, cap: int, mx: bool):
    """Per-row range extreme over [lo, hi] via a sparse table: O(n log n)
    build (static level count — XLA unrolls it), two gathers per query.
    Lanes compare by (valid desc, sort rank, code): an invalid (NULL)
    lane never beats a valid one, and string ranks follow collation, not
    code order. Empty/all-NULL frames return an arbitrary code — the
    caller's masks nullify them."""
    import jax.lax as lax

    def better(a, b):
        v1, r1, c1 = a
        v2, r2, c2 = b
        take2 = _rank_better(mx, v1, r1, c1, v2, r2, c2)
        return (v1 | v2, jnp.where(take2, r2, r1),
                jnp.where(take2, c2, c1))

    levels = [(va, ks, cs)]
    step = 1
    pos = jnp.arange(cap)
    n_levels = max(1, int(cap).bit_length())
    for _ in range(1, n_levels):
        pv, pr, pc = levels[-1]
        j2 = jnp.minimum(pos + step, cap - 1)
        levels.append(better((pv, pr, pc), (pv[j2], pr[j2], pc[j2])))
        step *= 2
    V = jnp.stack([v for v, _, _ in levels])
    R = jnp.stack([r for _, r, _ in levels])
    C = jnp.stack([c for _, _, c in levels])
    w = jnp.maximum(hi - lo + 1, 1).astype(jnp.int32)
    k = (jnp.int32(31) - lax.clz(w)).astype(jnp.int32)
    p1 = jnp.clip(lo, 0, cap - 1)
    p2 = jnp.clip(hi - (jnp.int32(1) << k) + 1, 0, cap - 1)
    _, _, out = better((V[k, p1], R[k, p1], C[k, p1]),
                       (V[k, p2], R[k, p2], C[k, p2]))
    return out


def all_nodes(plan: N.PlanNode):
    """Every node in the plan, including scalar-subquery plans and runtime
    filters' shared build subtrees (via their joins)."""
    yield plan
    from cloudberry_tpu.plan.distribute import _node_exprs

    for e in _node_exprs(plan):
        for sub in ex.walk(e):
            if isinstance(sub, ex.SubqueryScalar):
                yield from all_nodes(sub.plan)
    for c in plan.children():
        yield from all_nodes(c)


def numbered_nodes(plan: N.PlanNode) -> list:
    """The plan's nodes in document order (``all_nodes``), each once: a
    node's index here is its ORDINAL, the name check and stats keys give
    it. The plan itself fixes it, so the same statement traces to the
    same program text in every process (the persistent compile cache
    keys on that text, which carries the keys in ``jax.result_info``),
    and two signature-equal plans name their nodes alike."""
    return _dedupe_nodes(all_nodes(plan))


_NODE_REF = re.compile(r"\(node (\d+)[):]")


def keyed_node(plan_nodes: list, key: str):
    """The node a check message or stats key names (``(node <ordinal>)``
    or ``(node <ordinal>: <title>)``) among ``numbered_nodes(plan)``;
    None when it names none."""
    m = _NODE_REF.search(key)
    if m is None or int(m.group(1)) >= len(plan_nodes):
        return None
    return plan_nodes[int(m.group(1))]


def find_expansion_node(plan: N.PlanNode, message: str):
    """The join a detected overflow check message points at (an
    expansion's pair buffer, a lookup join's compaction: messages name
    the node by its ordinal), or None."""
    if "expansion overflow" not in message \
            and "compaction overflow" not in message:
        return None
    node = keyed_node(numbered_nodes(plan), message)
    return node if isinstance(node, N.PJoin) else None


def join_shapes(plan: N.PlanNode) -> tuple:
    """(lookups, pair expansions, lookups at capacities of their own,
    lookups through a direct-address table, semi-joins whose probe holds
    no join) among ``plan``'s joins, by the shape ``Lowerer.join`` takes
    for each (``PJoin.expands``, ``PJoin.direct_lookup``,
    plan/joincap.py) and by where the binder placed a semi-join
    (``Binder._place_in_subquery``)."""
    joins = _dedupe_nodes(nd for nd in all_nodes(plan)
                          if isinstance(nd, N.PJoin))
    expand = sum(nd.expands for nd in joins)
    probe_rows = [(nd, N.capacity_of(nd.probe)) for nd in joins
                  if nd.compacts]
    compacted = sum(nd.out_rows(rows) < rows for nd, rows in probe_rows)
    direct = sum(nd.direct_lookup for nd in joins)
    on_scan = sum(nd.kind == "semi" and not any(
        isinstance(c, N.PJoin) for c in all_nodes(nd.probe)) for nd in joins)
    return len(joins) - expand, expand, compacted, direct, on_scan


def count_join_shapes(log, shapes: tuple) -> None:
    """One launch's joins on the engine's counters: ``launch_joins_lookup``
    and ``launch_joins_expand`` say which join the planner chose in the
    programs that ran, ``launch_joins_compacted`` how many of the lookups
    ran at a capacity of their own, ``launch_joins_direct`` how many
    found their build rows in a direct-address table,
    ``launch_joins_semi_on_scan`` how many semi-joins filter a table
    before any join (a program that joins nothing bumps none)."""
    if log is None:
        return
    for name, n in zip(("launch_joins_lookup", "launch_joins_expand",
                        "launch_joins_compacted", "launch_joins_direct",
                        "launch_joins_semi_on_scan"),
                       shapes):
        if n:
            log.bump(name, n)


def agg_shapes(plan: N.PlanNode, platform: str) -> tuple:
    """(the capacity their rows arrive at, their own capacity, the keys
    they carry rather than sort, the words their grouping sorts compare,
    how many sum into a direct-address table) summed over ``plan``'s
    grouped aggregates: how far below its input the planner could hold
    an aggregate's output, and with it whatever runs above
    (plan/joincap.py, the proven ceilings), what the compiler's sorts
    read (plan/fdep.py, ``sort_words``), and how many sort nothing at
    all (``PAgg.direct``)."""
    aggs = _dedupe_nodes(nd for nd in all_nodes(plan)
                         if isinstance(nd, N.PAgg) and nd.group_keys)
    return (sum(N.capacity_of(nd.child) for nd in aggs),
            sum(nd.capacity for nd in aggs),
            sum(len(nd.carried) for nd in aggs),
            sum(sort_words(nd, dense_strategy(platform)) for nd in aggs),
            sum(nd.direct for nd in aggs))


def sort_words(agg: N.PAgg, strategy: str) -> int:
    """The u32 words a grouped aggregate's sort compares (its rows'
    positions, which every sort carries last, not counted): none on the
    perfect-hash path or the direct-address one, one for a 32-bit
    packed word, two for a 64-bit one, else each sorted key's own
    (``kernels.sort_key_word``)."""
    if agg.direct or dense_domain(agg, strategy) is not None:
        return 0
    if agg.pack_bits:
        return agg.pack_bits // 32
    return sum(1 if e.dtype.np_dtype.itemsize <= 4 else 2
               for k, e in agg.group_keys if k not in agg.carried)


def count_agg_shapes(log, shapes: tuple) -> None:
    """One launch's grouped aggregates on the engine's counters:
    ``launch_agg_rows_in`` and ``launch_agg_capacity``,
    ``launch_agg_keys_carried``, ``launch_agg_sort_words`` and
    ``launch_agg_direct`` (a program that groups nothing bumps none)."""
    rows_in, capacity, carried, words, direct = shapes
    if log is not None and rows_in:
        log.bump("launch_agg_rows_in", rows_in)
        log.bump("launch_agg_capacity", capacity)
        for name, n in (("launch_agg_keys_carried", carried),
                        ("launch_agg_sort_words", words),
                        ("launch_agg_direct", direct)):
            if n:
                log.bump(name, n)


def dense_strategy(platform: str) -> str:
    """How a perfect-hash aggregate lowers: scatter (segment ops) on CPU;
    unrolled masked reductions on TPU, where a domain of at most 64 cells
    is a few fused sweeps. (A scatter is no serialized loop there: on a
    TPU v5e the slot map's scatter-add took Q13's device time from
    1,193.6 to 211.3 ms, 6M rows scattered into a 1.5M-slot table in
    7.0 ms, and
    ``kernels.group_aggregate_direct`` sums by scatter-adds alone.)"""
    return "segment" if platform == "cpu" else "reduce"


def dense_domain(node: N.PAgg, strategy: str):
    """The sizes of a grouped aggregate's key domains where it takes the
    perfect-hash path (``Lowerer._dense_agg``): every key a dictionary-
    coded string, the product of their domains no larger than the
    capacity nor the unroll's cap ('reduce' unrolls one masked reduction
    per cell — cap it hard or XLA program size / compile time explodes;
    'segment', a CPU scatter, scales to larger domains). Else None."""
    sizes = []
    for name, _ in node.group_keys:
        f = node.field(name)
        if f.type.base != DType.STRING or f.sdict is None \
                or len(f.sdict) == 0:
            return None
        sizes.append(len(f.sdict))
    max_cells = 4096 if strategy == "segment" else 64
    if math.prod(sizes) > min(node.capacity, max_cells):
        return None
    return sizes


def count_scan_rows(log, tables: dict) -> None:
    """Bump ``scan_rows`` and ``scan_capacity_rows`` by the rows the
    launch's scanned inputs hold and the rows they are padded to (their
    capacity rungs); a point slice, which is all rows, counts in
    neither."""
    rows = capacity = 0
    for data in tables.values():
        n = data.get(NROWS)
        if n is not None:
            rows += int(n)
            # (every column of a scan's input has the capacity's length)
            capacity += next((v.shape[0] for k, v in data.items()
                              if k != NROWS), 0)
    if capacity:
        log.bump("scan_rows", rows)
        log.bump("scan_capacity_rows", capacity)


def _dedupe_nodes(nodes) -> list:
    """Unique by identity, preserving order — all_nodes re-walks shared
    (PShare) subtrees once per reference, and a buffer must be grown
    exactly once per retry."""
    seen: set[int] = set()
    out = []
    for nd in nodes:
        if id(nd) not in seen:
            seen.add(id(nd))
            out.append(nd)
    return out


def grow_expansion(plan: N.PlanNode, message: str, factor: int = 4,
                   allow_fallback: bool = False) -> bool:
    """Adaptive recovery from a detected join-expansion overflow (the
    increase-nbatch-and-retry discipline of nodeHash.c): grow the named
    join's pair buffer by ``factor`` and report success. The caller
    recompiles and re-runs — results are never truncated. A skew-blown
    redistribute bucket recovers the same way, except it promotes to
    the next CAPACITY RUNG that fits (``factor`` does not apply there —
    rung shapes are what the session's executable cache is keyed on).

    ``allow_fallback``: when the message's node ordinal resolves to no
    candidate of ``plan``, grow every candidate buffer instead of giving
    up. Only the statement retry loop sets this — blanket growth is
    padding at worst with guaranteed progress. (A program served from
    the rung cache was traced off a signature-equal plan, whose
    ordinals are this plan's: the fallback is a net, not the route.)
    Tiled callers keep it off: their miss means the overflowing node is
    genuinely outside the plan at hand, and the original error must
    surface, not a mutated retry."""
    from cloudberry_tpu.lifecycle import check_cancel

    # cancel seam: each grow-and-retry round recompiles and re-runs the
    # whole program — a cancelled statement must stop climbing the
    # capacity ladder, not ride it to the ceiling first
    check_cancel()
    node = find_expansion_node(plan, message)
    if "compaction overflow" in message:
        # a lookup join's own capacity (plan/joincap.py): the next one,
        # and the aggregates above follow
        if node is None or not node.compacts:
            return False
        from cloudberry_tpu.plan import joincap

        rows = re.search(r": (\d+) rows$", message)
        joincap.grow(plan, node, "probe" if "join probe " in message
                     else "match", int(rows.group(1)) if rows else 0)
        return True
    join_hits = [node] if node is not None else []
    if not join_hits and allow_fallback \
            and "expansion overflow" in message:
        join_hits = _dedupe_nodes(
            nd for nd in all_nodes(plan)
            if isinstance(nd, N.PJoin) and nd.expands)
    if join_hits:
        for nd in join_hits:
            nd.out_capacity = max(nd.out_capacity * factor, 64)
            # capacity re-derivations (e.g. tiled _retile) must never
            # shrink a runtime-grown buffer back below what overflowed
            nd._min_out_cap = nd.out_capacity
        return True
    named = keyed_node(numbered_nodes(plan), message)
    if "host bucket overflow" in message:
        hits = [named] if isinstance(named, N.PMotion) \
            and named.kind == "redistribute" \
            and named.host_bucket_cap > 0 else []
        if not hits and allow_fallback:
            hits = _dedupe_nodes(
                nd for nd in all_nodes(plan)
                if isinstance(nd, N.PMotion)
                and nd.kind == "redistribute" and nd.host_bucket_cap > 0)
        for nd in hits:
            # the two-level DCN block climbs the SAME pow2 ladder as the
            # per-segment rung — straight to the observed demand's rung
            observed = getattr(nd, "_observed_host_bucket", 0)
            nd.host_bucket_cap = K.rung_up(
                max(nd.host_bucket_cap * 2, observed, 64))
            # no _min_* floor needed: nothing re-derives host_bucket_cap
            # on a live plan (tiled_plan.retile_mesh re-derives bucket_cap
            # only), so the promoted rung cannot be shrunk back
        return bool(hits)
    if "redistribute overflow" in message:
        # kind filter matters: an ordinal from a program traced off
        # another plan shape must never promote a gather/broadcast
        hits = [named] if isinstance(named, N.PMotion) \
            and named.kind == "redistribute" else []
        if not hits and allow_fallback:
            # the ordinal names no redistribute of THIS plan: promote
            # every one — extra padding at worst, and the retry is
            # guaranteed progress
            hits = _dedupe_nodes(
                nd for nd in all_nodes(plan)
                if isinstance(nd, N.PMotion)
                and nd.kind == "redistribute")
        for nd in hits:
            # out_capacity tracks bucket_cap × nseg; recover the
            # factor so memory estimates see the grown buffer
            nseg = max(1, (nd.out_capacity or nd.bucket_cap)
                       // max(nd.bucket_cap, 1))
            # promote to the next capacity rung — or straight to the
            # rung fitting the observed global bucket demand when the
            # run reported one (dist_executor.record_motion_stats)
            observed = getattr(nd, "_observed_bucket", 0)
            nd.bucket_cap = K.rung_up(
                max(nd.bucket_cap * 2, observed, 64))
            nd.out_capacity = nd.bucket_cap * nseg
            # tiled re-derivations must never shrink it back
            nd._min_bucket_cap = nd.bucket_cap
            if nd.host_bucket_cap > 0:
                # keep the two-level invariant host_bucket_cap >=
                # bucket_cap (a pair bucket must fit its host block) and
                # fold in the host demand this run already observed —
                # otherwise the retry is a guaranteed host-rung overflow
                # costing one more full recompile+execute cycle
                nd.host_bucket_cap = K.rung_up(max(
                    nd.host_bucket_cap, nd.bucket_cap,
                    getattr(nd, "_observed_host_bucket", 0)))
        return bool(hits)
    return False


def scans_of(plan: N.PlanNode):
    if isinstance(plan, N.PScan) and plan.table_name != "$dual":
        yield plan
    # scalar subqueries ride inside expressions, not children — their scans
    # need table inputs too (a FROM-less outer SELECT may still scan)
    from cloudberry_tpu.plan.distribute import _node_exprs

    for e in _node_exprs(plan):
        for sub in ex.walk(e):
            if isinstance(sub, ex.SubqueryScalar):
                yield from scans_of(sub.plan)
    for c in plan.children():
        yield from scans_of(c)


# ------------------------------------------------------------- plan lowering


def number_nodes(ordinals: dict, plan: N.PlanNode) -> None:
    """Give every node under ``plan`` that has none the next ordinal, in
    document order: the one numbering (``Lowerer.ref``)."""
    for n in all_nodes(plan):
        ordinals.setdefault(id(n), len(ordinals))


def node_titles(*roots: N.PlanNode) -> dict:
    """{ordinal: ``node.title()``} as a ``Lowerer`` numbers the nodes of
    ``roots`` (its ``root`` first, then what it lowers beside it): what a
    registered program (obs/programs.py) says of its plan, capacities
    included, as EXPLAIN prints them."""
    ordinals: dict = {}
    for root in roots:
        number_nodes(ordinals, root)
    return {ordinals[id(n)]: n.title()
            for root in roots for n in all_nodes(root)}


def node_kind(node: N.PlanNode) -> str:
    """What a plan node is called in the scope its operations carry
    (``Lowerer.lower``): THE place that names a node for a profile. A
    closed vocabulary, which obs/programs.py ``NODE_CLASSES`` sorts into
    operator classes: scan, filter, project, join:lookup, join:expand
    (by the shape ``Lowerer._join`` takes), agg, sort, limit,
    motion:<kind>, window, share, rfilter, concat."""
    if isinstance(node, N.PJoin):
        return "join:expand" if node.expands else "join:lookup"
    if isinstance(node, N.PMotion):
        return f"motion:{node.kind}"
    kind = _NODE_KINDS.get(type(node))
    if kind is None:
        raise ExecError(f"cannot execute node {type(node).__name__}")
    return kind


_NODE_KINDS = {N.PScan: "scan", N.PFilter: "filter", N.PProject: "project",
               N.PAgg: "agg", N.PSort: "sort", N.PLimit: "limit",
               N.PWindow: "window", N.PShare: "share",
               N.PRuntimeFilter: "rfilter", N.PConcat: "concat"}


class Lowerer:
    """Traces a plan into jax ops. Subclassed by the distributed executor,
    which overrides scan (per-segment inputs) and motion (collectives).
    Four pieces of state vary what a lowering does, for both classes:
    ``replace`` (nodes already computed), ``stream``/``tile_n`` (the scan
    a tiled step feeds a tile at a time), ``direct_tables`` (joins whose
    build was streamed into a direct-address table before the step) and
    ``count_rows`` (EXPLAIN ANALYZE's per-node row counts)."""

    def __init__(self, tables, platform: str | None = None, params=None,
                 root=None, replace: dict | None = None,
                 stream: N.PScan | None = None, tile_n=None,
                 count_rows: bool = False,
                 direct_tables: dict | None = None):
        self.tables = tables
        # id(node) -> ordinal under ``root`` (numbered_nodes): how check
        # and stats keys name a node. Without a ``root`` the first plan
        # lowered is it; callers that lower a SUBTREE first (tiled
        # preludes) and look the node up in the whole plan pass it.
        self._ordinals: dict[int, int] = {}
        if root is not None:
            self._number(root)
        # runtime literal bindings for a generic plan (sched/paramplan.py):
        # "$prm<slot>" -> scalar array, "$dlt<i>" -> a literal-chosen
        # lookup table, injected next to the columns when an expression
        # carries Param leaves or gathers through such a table
        self.params = params
        # long dictionary tables riding as inputs (stamp_dict_tables):
        # injected next to the columns of an expression that gathers one
        self.dict_tables = tables.get(DICT_TABLES) or {}
        # id(node) -> (cols, sel): such a node lowers to what it is given
        # and its subtree is never traced (a tiled step's prelude-computed
        # builds, a finalize's accumulator leaf)
        self.replace = replace or {}
        # the streamed scan of a tiled step reads ``tables["$tile"]``,
        # ``tile_n`` rows of it; every other scan, of the same table too,
        # reads its table
        self.stream = stream
        self.tile_n = tile_n
        # id(join) -> (box, (present, payload)): the join reads a table
        # built before this program (exec/tiled.py build stream) and its
        # build subtree is never traced
        self.direct_tables = direct_tables or {}
        # EXPLAIN ANALYZE: every lowered node's selected-row count goes
        # through record_rows
        self.count_rows = count_rows
        # by the node's ORDINAL (ref): the counts are a program output,
        # and an output's key is part of the program's text
        self.node_counts: dict[int, jnp.ndarray] = {}
        self.checks: dict[str, jnp.ndarray] = {}
        # replicated observability scalars (e.g. each redistribute's
        # observed bucket demand) — the distributed executor returns
        # them next to checks for host-side capacity-rung promotion
        self.stats: dict[str, jnp.ndarray] = {}
        self._subcache: dict[int, jnp.ndarray] = {}
        # shared-subplan (PShare) results, keyed by child object identity
        self._sharecache: dict[int, tuple] = {}
        platform = platform or jax.default_backend()
        self.platform = platform
        self.dense_strategy = dense_strategy(platform)

    def _number(self, plan: N.PlanNode) -> None:
        number_nodes(self._ordinals, plan)

    def ref(self, node: N.PlanNode) -> int:
        """The node's ordinal: its name in check and stats keys. A node
        outside the numbered root (a finalize chain beside a partial
        plan) is numbered after it, so it names no node of the root."""
        if id(node) not in self._ordinals:
            self._number(node)
        return self._ordinals[id(node)]

    def label(self, node: N.PlanNode) -> str:
        """``(node <ordinal>: <title>)`` — a check message's reference,
        for the retry (the ordinal) and for a person (the title)."""
        return f"(node {self.ref(node)}: {node.title()})"

    def lower(self, node: N.PlanNode) -> tuple[dict, jnp.ndarray]:
        hit = self.replace.get(id(node))
        if hit is not None:
            return hit
        if not self._ordinals:
            self._number(node)
        # every operation the node emits carries the node's scope,
        # ``n<ordinal>:<kind>``, in its name (trace-time metadata, which
        # obs/programs.py reads back from the compiled module); children
        # lower inside it, so the innermost ``n<ordinal>:`` of a name is
        # the node the operation belongs to
        with jax.named_scope(f"n{self.ref(node)}:{node_kind(node)}"):
            cols, sel = self.scan_tile(node) if node is self.stream \
                else self.lower_node(node)
            if self.count_rows:
                self.record_rows(node, jnp.sum(sel.astype(jnp.int64)))
        return cols, sel

    def record_rows(self, node: N.PlanNode, n) -> None:
        """Where ``count_rows`` puts a lowered node's selected-row count:
        here a program output keyed by ordinal, in the distributed
        lowerer the replicated stats channel."""
        self.node_counts[self.ref(node)] = n

    def scan_tile(self, node: N.PScan):
        tile = self.tables["$tile"]
        cols = {}
        for phys, out in node.column_map.items():
            cols[out] = tile[phys]
        for phys, out in node.mask_map.items():
            cols[out] = tile[f"$nn:{phys}"]
        return cols, jnp.arange(node.capacity) < self.tile_n

    def lower_node(self, node: N.PlanNode) -> tuple[dict, jnp.ndarray]:
        if isinstance(node, N.PScan):
            return self.scan(node)
        if isinstance(node, N.PFilter):
            cols, sel = self.lower(node.child)
            mask = self.expr(node.predicate, cols)
            return cols, sel & mask
        if isinstance(node, N.PProject):
            cols, sel = self.lower(node.child)
            out = {}
            for name, e in node.exprs:
                out[name] = _as_column(self.expr(e, cols), sel.shape[0])
            return out, sel
        if isinstance(node, N.PJoin):
            return self.join(node)
        if isinstance(node, N.PAgg):
            return self.agg(node)
        if isinstance(node, N.PSort):
            cols, sel = self.lower(node.child)
            keys, desc = [], []
            for e, asc in node.keys:
                keys.append(_as_column(_sortable(e, node.child, cols),
                                       sel.shape[0]))
                desc.append(not asc)
            if node.pack_bits:
                packed = K.pack_keys(keys, sel, descending=desc)
                perm = K.sort_indices(
                    [K.downcast32(packed) if node.pack_bits == 32
                     else packed], sel)
            else:
                perm = K.sort_indices(keys, sel, descending=desc)
            return {n: c[perm] for n, c in cols.items()}, sel[perm]
        if isinstance(node, N.PLimit):
            cols, sel = self.lower(node.child)
            return cols, K.limit_mask(sel, node.limit, node.offset)
        if isinstance(node, N.PMotion):
            return self.motion(node)
        if isinstance(node, N.PWindow):
            return self.window(node)
        if isinstance(node, N.PShare):
            return self.lower_shared(node.child)
        if isinstance(node, N.PRuntimeFilter):
            return self.runtime_filter(node)
        if isinstance(node, N.PConcat):
            outs = [self.lower(c) for c in node.inputs]
            cols = {f.name: jnp.concatenate([o[0][f.name] for o in outs])
                    for f in node.fields}
            sel = jnp.concatenate([o[1] for o in outs])
            return cols, sel
        raise ExecError(f"cannot execute node {type(node).__name__}")

    # ------------------------------------------------------------ hookable

    def scan(self, node: N.PScan):
        if node.table_name == "$dual":
            return {}, jnp.ones((1,), dtype=jnp.bool_)
        data = self.tables[getattr(node, "_input_key", node.table_name)]
        cols = {}
        for name, out in [*node.column_map.items(),
                          *((f"$nn:{p}", o)
                            for p, o in node.mask_map.items())]:
            arr = data[name]
            if arr.shape[0] == 0:   # an empty table: no rows, capacity 1
                arr = jnp.zeros((node.capacity,), dtype=arr.dtype)
            elif arr.shape[0] != node.capacity:
                # a short column would be read past its rows: the inputs
                # are padded to the capacity where they cross to the
                # device (prepare_tables, _read_scan_columns)
                raise ExecError(
                    f"scan of {node.table_name}: column {name} has "
                    f"{arr.shape[0]} rows, the plan's capacity is "
                    f"{node.capacity}")
            cols[out] = arr
        # the row count is data, the CAPACITY is the shape: a whole-table
        # or store scan finds its count beside its columns, so that every
        # table on one capacity rung is one program; a point slice is all
        # rows
        n = data.get(NROWS)
        if n is None:
            n = node.num_rows if node.num_rows >= 0 else node.capacity
        key = getattr(node, "_nrows_key", None)
        if key is not None and self.params is not None \
                and key in self.params:
            # generic plan: the count rides the $params input, so one
            # compiled program serves every direct-dispatch segment
            n = self.params[key]
        sel = jnp.arange(node.capacity) < n
        return cols, sel

    def motion(self, node: N.PMotion):
        # single-program mode: loopback motion is the identity (the
        # MotionIPCLayer seam's test backend). lower_shared: a runtime
        # filter may reference the motion's child (build side) too.
        return self.lower_shared(node.child)

    def global_any(self, x) -> jnp.ndarray:
        """Any() across ALL data — the distributed lowerer reduces over the
        segment axis too (null-aware NOT IN needs a cluster-wide answer)."""
        return jnp.any(x)

    def lower_shared(self, node: N.PlanNode):
        """Lower a subtree at most once (PShare / runtime-filter build
        sharing) — the materialize-once contract at trace level."""
        key = id(node)
        if key not in self._sharecache:
            self._sharecache[key] = self.lower(node)
        return self._sharecache[key]

    def runtime_filter(self, node: N.PRuntimeFilter):
        """Single-program mode: motions are loopback, so the filter would
        only duplicate the join's own matching — pass through."""
        return self.lower(node.child)

    # ----------------------------------------------------------- expressions

    def expr(self, e: ex.Expr, cols) -> jnp.ndarray:
        """Evaluate an expression; uncorrelated scalar subqueries (InitPlan
        analog) are lowered once inside the same program and broadcast;
        Param leaves (generic plans) read their runtime binding from the
        program's "$params" input."""
        subs = [n for n in ex.walk(e) if isinstance(n, ex.SubqueryScalar)]
        if self.params is not None and any(
                isinstance(n, ex.Param)
                or getattr(n, "_table_input", None) in self.params
                for n in ex.walk(e)):
            cols = {**cols, **self.params}
        if self.dict_tables and any(
                getattr(n, "_table_input", None) in self.dict_tables
                for n in ex.walk(e)):
            cols = {**cols, **self.dict_tables}
        if not subs:
            return compile_expr(e)(cols)
        aug = dict(cols)
        mapping = {}
        for sq in subs:
            key = id(sq)
            if key not in self._subcache:
                scols, ssel = self.lower(sq.plan)
                n = jnp.sum(ssel.astype(jnp.int64))
                if sq.mode == "exists":
                    # presence term: did the subplan select any row at
                    # all (the 0-rows→NULL half of scalar semantics)
                    self._subcache[key] = n > 0
                else:
                    arr = scols[sq.plan.fields[0].name]
                    self.checks[
                        f"scalar subquery returned more than one row "
                        f"{self.label(sq.plan)}"] = n > 1
                    # 0 selected rows: argmax lands on row 0, whose value
                    # is arbitrary — the binder's presence term masks the
                    # result NULL, so it is never observed
                    idx = jnp.argmax(ssel)  # the single selected row
                    self._subcache[key] = arr[idx]
            name = f"$sqv{key}"
            mapping[key] = name
            aug[name] = self._subcache[key]
        return compile_expr(_substitute_subqueries(e, mapping))(aug)

    # ------------------------------------------------------------ operators

    def _join_index(self, node: N.PJoin):
        """Cached sorted-build index for this join (exec/joinindex.py):
        (order, sorted packed keys, packing ranges) fed as a program
        input, or None → compute the argsort in-program. Tiled/spill
        assemblies never provide the input, so the fallback is automatic
        there; distributed 'shard'-mode arrays arrive with a leading
        (1, …) segment axis inside shard_map and normalize here."""
        spec = getattr(node, "_jix", None)
        if spec is None:
            return None
        jix = self.tables.get(spec.key)
        if jix is None:
            return None
        order, skeys = jnp.asarray(jix["order"]), jnp.asarray(jix["skeys"])
        if order.ndim == 2:
            order, skeys = order[0], skeys[0]
        ranges = []
        for i in range(len(node.build_keys)):
            lo = jnp.asarray(jix[f"lo{i}"]).reshape(())
            span = jnp.asarray(jix[f"span{i}"]).reshape(())
            ranges.append((lo, span))
        return order, skeys, ranges

    def join(self, node: N.PJoin):
        table = self.direct_tables.get(id(node))
        if table is not None:
            pcols, psel = self.lower(node.probe)
            return self._join_table(node, *table, pcols, psel)
        # lower_shared: a runtime filter may reference the same build
        # subtree — it must trace once
        bcols, bsel = self.lower_shared(node.build)
        pcols, psel = self.lower(node.probe)
        return self._join(node, bcols, bsel, pcols, psel)

    def _compact_rows(self, node: N.PJoin, what: str, cols, sel,
                      capacity: int):
        """``cols`` at the ``capacity`` rows the planner stamped for the
        rows ``sel`` keeps, in order (``K.compact_sparse``); more of them
        than ``capacity`` is a check that carries their count, which the
        session answers by growing that capacity to hold them
        (``grow_expansion``), never a cut row."""
        out, osel, n = K.compact_sparse(cols, sel, capacity)
        self.checks[
            f"join {what} compaction overflow: rows exceed capacity "
            f"{capacity} {self.label(node)}"] = jnp.where(
                n > capacity, n, 0)
        return out, osel

    def _join(self, node: N.PJoin, bcols, bsel, pcols, psel):
        bkeys = [self.expr(k, bcols) for k in node.build_keys]
        pkeys = [self.expr(k, pcols) for k in node.probe_keys]

        # SQL NULL-key semantics: a NULL key matches nothing. NULL-key build
        # rows leave the build set; NULL-key probe rows become unmatched
        # (they still flow through left/full/anti via the ORIGINAL psel).
        bkv = self.expr(node.build_key_valid, bcols) \
            if node.build_key_valid is not None else None
        pkv = self.expr(node.probe_key_valid, pcols) \
            if node.probe_key_valid is not None else None
        bselm = bsel & bkv if bkv is not None else bsel
        pselm = psel & pkv if pkv is not None else psel

        if node.search_rows(psel.shape[0]) < psel.shape[0]:
            # a sparse probe (plan/joincap.py): the rows that can match,
            # and no others, reach the search, the match test and the
            # payload gathers (an inner or semi join emits matched rows
            # only, so the rest are nobody's)
            pcols, pselm = self._compact_rows(node, "probe", pcols, pselm,
                                              node.probe_capacity)
            psel = pselm
            pkeys = [self.expr(k, pcols) for k in node.probe_keys]

        if node.kind in ("semi", "anti") and node.residual is not None:
            return self._join_semi_residual(node, bcols, bselm, bkeys,
                                            pcols, psel, pselm, pkeys)
        if not node.unique_build:
            return self._join_expand(node, bcols, bsel, bselm, bkeys,
                                     pcols, psel, pselm, pkeys)

        direct = node.direct_lookup
        if direct:
            # a proven small key span: the build row is one gather from
            # a direct-address table, no sort and no search
            pos, matched, has_dup, past = K.join_lookup_direct(
                bkeys, bselm, pkeys, pselm, node.direct_span)
            self.checks[
                f"join build key past its proven span {node.direct_span} "
                f"{self.label(node)}"] = past
        else:
            order, kb_sorted, ranges = self._join_index(node) \
                or K.build_sort(bkeys, bselm, node.pack_bits)
            pos, matched = K.join_probe_sorted(kb_sorted, ranges, pkeys,
                                               pselm, bits=node.pack_bits)
            has_dup = K.dup_check(kb_sorted, node.pack_bits)
        if node.out_rows(psel.shape[0]) < psel.shape[0]:
            # sparse matches: the probe's columns and the positions found
            # are compacted to the matched rows, so the build rows and
            # the payload words are gathered for those alone and every
            # node above runs at this capacity
            out, matched = self._compact_rows(
                node, "match", {**pcols, "$joinpos": pos}, matched,
                node.out_capacity)
            pos = out.pop("$joinpos")
            pcols, psel = out, matched
        idx = pos if direct else order[pos].astype(jnp.int32)
        payload = K.gather_payload(
            {n: bcols[n] for n in node.build_payload}, idx, matched)
        if node.kind in ("inner", "left"):
            # semi/anti only test membership; inner/left rely on the
            # planner's uniqueness proof — verify it at runtime: the
            # lookup checks the build side itself (adjacent-equal on its
            # sorted keys, or a direct table read back at its keys)
            self.checks[
                f"join build side has duplicate keys {self.label(node)} but "
                "the planner assumed a unique (PK) build side"] = has_dup
        return self._lookup_out(
            node, pcols, psel, pkv, matched, payload,
            None if bkv is None
            else lambda: self.global_any(bsel & ~bkv))

    def _lookup_out(self, node: N.PJoin, pcols, psel, pkv, matched,
                    payload, build_null=None):
        """A lookup join's output: the probe's columns, the matched build
        rows' ``payload``, and the rows its kind keeps. ``build_null``:
        whether any build key is NULL (a NOT IN's test), None where no
        build key can be."""
        cols = {**pcols, **payload}
        if node.match_name:
            cols[node.match_name] = matched
        if node.kind in ("inner", "semi"):
            sel = matched
        elif node.kind == "left":
            sel = psel
        elif node.kind == "anti":
            sel = psel & ~matched
            if node.null_aware:
                # x NOT IN (...): never TRUE if x is NULL or ANY subquery
                # key is NULL — the build-side test must be GLOBAL across
                # segments (the NULL row may live on another shard)
                if pkv is not None:
                    sel = sel & pkv
                if build_null is not None:
                    sel = sel & ~build_null()
        else:
            raise ExecError(f"join kind {node.kind}")
        return cols, sel

    def _join_table(self, node: N.PJoin, box, table, pcols, psel):
        """A lookup against a direct-address table built before this
        program (``kernels.direct_table_insert``, a tile at a time): one
        gather a probe row, no build to lower, sort or search. The
        planner streams only builds no NULL key of which a NOT IN reads
        (exec/tiled.py ``_build_stream``)."""
        present, payload = table
        pkeys = [self.expr(k, pcols) for k in node.probe_keys]
        pkv = self.expr(node.probe_key_valid, pcols) \
            if node.probe_key_valid is not None else None
        pselm = psel & pkv if pkv is not None else psel
        slot, matched = K.direct_table_lookup(present, pkeys, pselm, box)
        return self._lookup_out(node, pcols, psel, pkv, matched,
                                K.gather_payload(payload, slot, matched))

    def window(self, node: N.PWindow):
        """Windows over sorted partitions — scatter-free: boundary flags,
        compacted starts, cumulative-sum differences (nodeWindowAgg analog;
        with ORDER BY the frame is RANGE UNBOUNDED PRECEDING..CURRENT ROW,
        peers included, per the SQL default)."""
        cols, sel = self.lower(node.child)
        cap = sel.shape[0]
        pk = [_as_column(self.expr(e, cols), cap)
              for e in node.partition_keys]
        # ORDER BY on strings sorts by collation rank, not dictionary code
        # (same rule PSort applies via _sortable)
        ok = [_as_column(_sortable(e, node.child, cols), cap)
              for e, _ in node.order_keys]
        desc = [not asc for _, asc in node.order_keys]
        perm = K.sort_indices(pk + ok, sel,
                              descending=[False] * len(pk) + desc)
        inv = K.inverse_permutation(perm)
        s_sel = sel[perm]
        n_sel = jnp.sum(s_sel.astype(jnp.int32))
        idx = jnp.arange(cap)

        def flags(keys):
            f = jnp.zeros(cap, dtype=jnp.bool_)
            for k in keys:
                ks = k[perm]
                f = f | (ks != jnp.roll(ks, 1))
            return (f.at[0].set(True)) & s_sel

        seg_flag = flags(pk) if pk else \
            (jnp.zeros(cap, dtype=jnp.bool_).at[0].set(True) & s_sel)
        run_flag = (seg_flag | flags(ok)) if ok else seg_flag

        seg_starts_c = K.flagged_first(seg_flag)
        seg_cum = K.prefix_sum(seg_flag.astype(jnp.int32))
        seg_id0 = jnp.clip(seg_cum - 1, 0, cap - 1)
        n_segs = jnp.sum(seg_flag.astype(jnp.int32))
        seg_start = seg_starts_c[seg_id0]
        nxt = seg_starts_c[jnp.clip(seg_id0 + 1, 0, cap - 1)]
        seg_end = jnp.where(seg_id0 + 1 < n_segs, nxt - 1, n_sel - 1)

        run_starts_c = K.flagged_first(run_flag)
        run_cum = K.prefix_sum(run_flag.astype(jnp.int32))
        run_id0 = jnp.clip(run_cum - 1, 0, cap - 1)
        n_runs = jnp.sum(run_flag.astype(jnp.int32))
        rnxt = run_starts_c[jnp.clip(run_id0 + 1, 0, cap - 1)]
        run_start = run_starts_c[run_id0]
        run_end = jnp.where(run_id0 + 1 < n_runs, rnxt - 1, n_sel - 1)

        def pref(vals):
            csum = K.prefix_sum(vals)
            return jnp.concatenate(
                [jnp.zeros((1,), dtype=csum.dtype), csum])

        # explicit frame (node.frame): per-row [flo, fhi] bounds in sorted
        # coordinates. The SQL default keeps the peer-inclusive RANGE
        # semantics (run_end); ROWS frames are purely positional and can
        # be EMPTY at partition edges (fempty)
        if node.frame is None:
            flo = seg_start
            fhi = run_end if node.order_keys else seg_end
            fempty = None
        elif node.frame[0] == "whole":
            flo, fhi = seg_start, seg_end
            fempty = None
        elif node.frame[0] == "rangepos":
            # positional RANGE (CURRENT ROW / UNBOUNDED bounds only):
            # peer-group or partition edges, never empty; the start is
            # always the peer-group head (UNBOUNDED-lo shapes reduced
            # to the default/whole frames at bind time). Without ORDER
            # BY every row is a peer (run_* == seg_*), the SQL rule.
            flo = run_start
            fhi = run_end if node.frame[2] == "peer" else seg_end
            fempty = None
        elif node.frame[0] == "rangeoff":
            # value-distance frame: per-row binary search for the key
            # interval [k+lo, k+hi] inside the partition's non-NULL span.
            # NULL-key rows frame exactly their peer group (the SQL rule:
            # NULL ± offset stays NULL, NULLs are peers of NULLs), while
            # UNBOUNDED sides keep the positional partition edge — which
            # includes NULL rows, matching nodeWindowAgg.c.
            _, lo_off, hi_off, knull = node.frame
            asc = node.order_keys[-1][1]
            kv_s = ok[-1][perm]
            if knull:
                keyvalid = (ok[0][perm] == 0) & s_sel
                # NULLs sort last ASC / first DESC (PSort's rule), so
                # valid keys are a prefix (asc) or suffix (desc) of the
                # partition
                C = pref(keyvalid.astype(jnp.int32))
                nv = C[jnp.clip(seg_end + 1, 0, cap)] - \
                    C[jnp.clip(seg_start, 0, cap)]
                vlo = seg_start if asc else seg_end - nv + 1
                vhi = seg_start + nv - 1 if asc else seg_end
            else:
                keyvalid = s_sel
                vlo, vhi = seg_start, seg_end
            # search in frame direction: DESC negates so "PRECEDING"
            # stays the -offset side of a nondecreasing array
            s = kv_s if asc else -kv_s
            knullrow = s_sel & ~keyvalid

            def _target(off):
                # numeric offsets are same-domain distances; a
                # ("months", n) offset is a CALENDAR shift of each
                # row's civil date (timestamp.c interval_pl: month
                # arithmetic with the day-of-month clamped), computed
                # in-program via the Hinnant civil<->days round trip.
                # DESC negates the search domain, so the month count
                # must flip too (s + off ≡ -(v - off) there): PRECEDING
                # under DESC reaches LATER dates.
                if isinstance(off, tuple):
                    sh = _shift_months_days(kv_s.astype(jnp.int64),
                                            off[1] if asc else -off[1])
                    return sh if asc else -sh
                return s + off
            if lo_off is None:
                flo = seg_start
            else:
                f = _vsearch(s, _target(lo_off), vlo, vhi, cap,
                             lower=True)
                flo = jnp.where(knullrow, run_start, f)
            if hi_off is None:
                fhi = seg_end
            else:
                f = _vsearch(s, _target(hi_off), vlo, vhi, cap,
                             lower=False) - 1
                fhi = jnp.where(knullrow, run_end, f)
            fempty = flo > fhi
        else:
            _, lo_off, hi_off = node.frame
            flo = seg_start if lo_off is None \
                else jnp.maximum(idx + lo_off, seg_start)
            fhi = seg_end if hi_off is None \
                else jnp.minimum(idx + hi_off, seg_end)
            fempty = flo > fhi

        out_cols = dict(cols)
        valids = node.valids or [None] * len(node.calls)
        params_list = node.params or [None] * len(node.calls)
        for (name, func, arg), valid, params in zip(node.calls, valids,
                                                    params_list):
            # per-call argument validity in sorted row order: count counts
            # only valid rows, avg divides by the valid count, 'anyvalid'
            # is the null mask for nullable agg outputs
            va = (s_sel & self.expr(valid, cols)[perm]) \
                if valid is not None else s_sel
            base = func.split("@", 1)[0]
            if func == "row_number":
                o = (idx - seg_start + 1).astype(jnp.int64)
            elif func == "ntile":
                # SQL ntile: larger buckets first — with s rows and n
                # buckets, the first s%n buckets get s//n+1 rows
                n = params["n"]
                rip = idx - seg_start
                psize = seg_end - seg_start + 1
                base_sz = psize // n
                rem = psize % n
                thresh = rem * (base_sz + 1)
                o = (jnp.where(rip < thresh,
                               rip // jnp.maximum(base_sz + 1, 1),
                               rem + (rip - thresh)
                               // jnp.maximum(base_sz, 1))
                     + 1).astype(jnp.int64)
            elif base in ("lead", "lag", "first_value", "last_value"):
                # positional reads within the sorted partition. The source
                # row index is computed per row; '<func>@mask' re-runs the
                # same gather over the argument's validity (plus the
                # in-partition range test) to produce the output null mask
                if base in ("lead", "lag"):
                    k = params["offset"]
                    src = idx + k if base == "lead" else idx - k
                    inrange = (src >= seg_start) & (src <= seg_end)
                elif base == "first_value":
                    # frame start (the partition head under the default)
                    src = flo
                    inrange = None if fempty is None else ~fempty
                else:
                    # last_value: frame end — under the default frame the
                    # current row's peer group, not the partition tail
                    src = fhi
                    inrange = None if fempty is None else ~fempty
                srcc = jnp.clip(src, 0, cap - 1)
                if func.endswith("@mask"):
                    o = va[srcc]
                    if inrange is not None:
                        if (params or {}).get("default") is not None:
                            # out-of-range rows take the (non-NULL) default
                            o = jnp.where(inrange, o, True)
                        else:
                            o = inrange & o
                else:
                    v = self.expr(arg, cols)[perm]
                    o = v[srcc]
                    if inrange is not None:
                        dflt = (params or {}).get("default")
                        fill = self.expr(dflt, cols).astype(v.dtype) \
                            if dflt is not None \
                            else jnp.zeros((), v.dtype)
                        o = jnp.where(inrange, o, fill)
            elif func == "rank":
                o = (run_start - seg_start + 1).astype(jnp.int64)
            elif func == "dense_rank":
                o = (run_cum - run_cum[seg_start] + 1).astype(jnp.int64)
            elif func in ("sum", "count", "avg", "anyvalid"):
                if func in ("count", "anyvalid") or arg is None:
                    v = va.astype(jnp.int64)
                else:
                    v = jnp.where(va, self.expr(arg, cols)[perm], 0)
                S = pref(v)
                hip = jnp.clip(fhi + 1, 0, cap)
                lop = jnp.clip(flo, 0, cap)
                o = S[hip] - S[lop]
                if fempty is not None:
                    o = jnp.where(fempty, jnp.zeros((), o.dtype), o)
                if func == "avg":
                    C = pref(va.astype(jnp.int64))
                    cnt = C[hip] - C[lop]
                    if fempty is not None:
                        cnt = jnp.where(fempty, 0, cnt)
                    o = o.astype(jnp.float64) / jnp.maximum(cnt, 1)
                    if arg is not None and arg.dtype.base == DType.DECIMAL:
                        o = o / (10.0 ** arg.dtype.scale)
                elif func == "anyvalid":
                    o = o > 0
            elif func in ("min", "max") and node.frame is not None \
                    and node.frame[0] in ("rows", "rangeoff", "rangepos"):
                # ROWS/RANGE-offset-frame extreme: sparse-table range
                # query over [flo, fhi] — the prefix-sum trick does not
                # invert for min/max, and the running scan only covers
                # suffix-anchored frames
                ks = _sortable(arg, node.child, cols)[perm]
                cs = self.expr(arg, cols)[perm]
                o = _rmq_extreme(ks, cs, va, flo, fhi, cap,
                                 mx=(func == "max"))
                if fempty is not None:
                    o = jnp.where(fempty, jnp.zeros((), o.dtype), o)
            elif func in ("min", "max") and node.frame is None \
                    and node.order_keys:
                # running extreme (RANGE UNBOUNDED PRECEDING..CURRENT ROW,
                # peers included via run_end): segmented scan over sorted
                # rows. The combine is the standard segmented-scan operator
                # (reset flag ? right : extreme(left, right)) with the
                # extreme taken lexicographically over (validity desc,
                # sort rank, code) so it stays associative on ties and an
                # invalid (NULL) lane can NEVER beat a valid one — not
                # even when a valid value equals the dtype extreme (an
                # all-NULL prefix is nullified by the 'anyvalid' mask).
                v = self.expr(arg, cols)
                ks = _sortable(arg, node.child, cols)[perm]
                cs = v[perm]
                mx = func == "max"

                def comb(a, b, mx=mx):
                    f1, w1, r1, c1 = a
                    f2, w2, r2, c2 = b
                    # segment reset flag ? right : extreme (the shared
                    # comparator keeps this path and the ROWS-frame RMQ
                    # ordering identical)
                    take2 = f2 | _rank_better(mx, w1, r1, c1, w2, r2, c2)
                    return (f1 | f2, jnp.where(take2, w2, w1),
                            jnp.where(take2, r2, r1),
                            jnp.where(take2, c2, c1))

                _, _, _, runext = jax.lax.associative_scan(
                    comb, (seg_flag, va, ks, cs))
                o = runext[run_end]
            elif func in ("min", "max"):
                # whole-partition extreme: re-sort with the value last; the
                # extreme lands on each partition's boundary row (strings
                # order by collation rank, output keeps the code). Invalid
                # (NULL) lanes sort behind every valid row in their
                # partition, so they reach the boundary only for all-NULL
                # partitions — which the 'anyvalid' mask nullifies.
                v = self.expr(arg, cols)
                vkey = _sortable(arg, node.child, cols)
                extra = [] if valid is None else \
                    [(~self.expr(valid, cols)).astype(jnp.int32)]
                p2 = K.sort_indices(pk + extra + [vkey], sel,
                                    descending=[False] * (len(pk)
                                                          + len(extra))
                                    + [func == "max"])
                o = v[p2][seg_start]
            else:
                raise ExecError(f"window function {func}")
            o = jnp.where(s_sel, o, jnp.zeros((), dtype=o.dtype))
            out_cols[name] = o[inv]  # back to the child's row order
        return out_cols, sel

    def _join_semi_residual(self, node: N.PJoin, bcols, bselm, bkeys,
                            pcols, psel, pselm, pkeys):
        """Correlated EXISTS with extra non-equi conditions (Q21 shape):
        expand equi-match pairs, evaluate the residual per pair, then
        OR-reduce back onto probe rows."""
        cap = node.out_capacity
        pi, bi, osel, _matched, total = self._expand_pairs(
            node, bkeys, bselm, pkeys, pselm, cap)
        self.checks[
            f"semi-join expansion overflow: match pairs exceed capacity "
            f"{cap} {self.label(node)}"] = total > cap
        paircols = {name: jnp.take(c, pi, axis=0) for name, c in pcols.items()}
        for name in node.build_payload:
            paircols[name] = jnp.take(bcols[name], bi, axis=0)
        rmask = self.expr(node.residual, paircols) & osel
        hit = jnp.zeros(psel.shape, dtype=jnp.bool_)
        hit = hit.at[pi].max(rmask, mode="drop")
        sel = psel & hit if node.kind == "semi" else psel & ~hit
        return dict(pcols), sel

    def _expand_pairs(self, node: N.PJoin, bkeys, bselm, pkeys, pselm,
                      cap: int):
        """join_expand through the cached sorted-build index when one is
        fed (skips the build argsort), else the full kernel."""
        jix = self._join_index(node)
        if jix is not None:
            return K.join_expand_sorted(jix[0], jix[1], jix[2], pkeys,
                                        pselm, cap, bits=node.pack_bits)
        return K.join_expand(bkeys, bselm, pkeys, pselm, cap,
                             bits=node.pack_bits)

    def _join_expand(self, node: N.PJoin, bcols, bsel, bselm, bkeys,
                     pcols, psel, pselm, pkeys):
        """Many-to-many expansion: one output row per match pair; LEFT joins
        append unmatched (preserved) probe rows after the pairs; FULL joins
        append unmatched rows from BOTH sides (NULL-key rows of either side
        are unmatched by construction — bselm/pselm exclude them from
        matching, bsel/psel keep them in the preserved regions)."""
        cap = node.out_capacity
        pi, bi, osel, matched, total = self._expand_pairs(
            node, bkeys, bselm, pkeys, pselm, cap)
        need = total
        is_pair = osel
        j = jnp.arange(cap, dtype=total.dtype)
        probe_valid = osel  # rows whose probe columns are real
        if node.kind in ("left", "full"):
            um = psel & ~matched
            um_rank = K.prefix_sum(um.astype(total.dtype)) - 1
            n_um = jnp.sum(um.astype(total.dtype))
            slot = jnp.where(um, total + um_rank, cap)
            pi = pi.at[slot].set(jnp.arange(um.shape[0], dtype=pi.dtype),
                                 mode="drop")
            osel = j < (total + n_um)
            is_pair = j < total
            probe_valid = osel
            need = total + n_um
            if node.kind == "full":
                bmatched = jnp.zeros(bsel.shape, dtype=jnp.bool_)
                bmatched = bmatched.at[bi].max(is_pair, mode="drop")
                um_b = bsel & ~bmatched
                umb_rank = K.prefix_sum(um_b.astype(total.dtype)) - 1
                n_umb = jnp.sum(um_b.astype(total.dtype))
                slot_b = jnp.where(um_b, total + n_um + umb_rank, cap)
                bi = bi.at[slot_b].set(
                    jnp.arange(um_b.shape[0], dtype=bi.dtype), mode="drop")
                osel = j < (total + n_um + n_umb)
                # build columns are real for pairs AND the build-only region
                is_pair = (j < total) | (j >= total + n_um)
                probe_valid = j < (total + n_um)
                need = total + n_um + n_umb
        elif node.kind != "inner":
            raise ExecError(f"expansion join does not support {node.kind}")
        self.checks[
            f"join expansion overflow: match pairs exceed capacity {cap} "
            f"{self.label(node)}"] = need > cap

        cols = {}
        for name, c in pcols.items():
            g = jnp.take(c, pi, axis=0)
            if node.kind == "full":
                # zero the build-only region; other kinds exclude those
                # rows via the selection mask already
                g = jnp.where(probe_valid, g, jnp.zeros((), dtype=g.dtype))
            cols[name] = g
        for name in node.build_payload:
            g = jnp.take(bcols[name], bi, axis=0)
            cols[name] = jnp.where(is_pair, g,
                                   jnp.zeros((), dtype=g.dtype))
        if node.match_name:
            cols[node.match_name] = is_pair
        if node.probe_match_name:
            cols[node.probe_match_name] = probe_valid
        return cols, osel

    def agg(self, node: N.PAgg):
        cols, sel = self.lower(node.child)
        agg_specs = []
        agg_values: dict[str, Any] = {}
        post_scale: dict[str, float] = {}
        for name, call in node.aggs:
            # NULL semantics are compiled away by the binder: nullable args
            # arrive identity-filled with companion valid-count aggregates
            # (Binder._mask_nullable_aggs), so only standard funcs remain.
            func = call.func
            if func in ("sum", "min", "max", "avg", "count"):
                agg_values[name] = self.expr(call.arg, cols) \
                    if call.arg is not None else None
            else:
                raise ExecError(f"aggregate {func} not implemented yet")
            if func == "avg" and call.arg is not None \
                    and call.arg.dtype.base == DType.DECIMAL:
                post_scale[name] = 10.0 ** call.arg.dtype.scale
            agg_specs.append(K.AggSpec(func, name))

        if not node.group_keys:
            out = K.global_aggregate(agg_values, agg_specs, sel)
            for name, div in post_scale.items():
                out[name] = out[name] / div
            return out, jnp.ones((1,), dtype=jnp.bool_)

        dense = self._dense_agg(node, cols, sel, agg_specs, agg_values,
                                post_scale)
        if dense is not None:
            return dense

        key_cols = {name: self.expr(e, cols)
                    for name, e in node.group_keys}
        if node.direct:
            # a proven key box no wider than the rows: each row's group
            # is its slot in a table over the box, no sort; groups never
            # outnumber the slots, so no overflow check
            out_keys, out_aggs, out_sel, past = K.group_aggregate_direct(
                key_cols, agg_values, agg_specs, sel, node.direct_box,
                node.capacity, carried=node.carried,
                value_bits={n: (b, sg) for n, b, sg in node.sum_bits})
            self.checks[
                f"aggregation key past its proven span "
                f"{math.prod(s for _, s in node.direct_box)} (or a summed "
                f"value past its proven width) {self.label(node)}"] = past
            for name, div in post_scale.items():
                out_aggs[name] = out_aggs[name] / div
            return {**out_keys, **out_aggs}, out_sel
        out_keys, out_aggs, out_sel, n_groups = K.group_aggregate(
            key_cols, agg_values, agg_specs, sel, node.capacity,
            pack_bits=node.pack_bits, carried=node.carried)
        self.checks[
            f"aggregation overflow: more groups than capacity "
            f"{node.capacity} {self.label(node)}"] = n_groups > node.capacity
        for name, div in post_scale.items():
            out_aggs[name] = out_aggs[name] / div
        return {**out_keys, **out_aggs}, out_sel

    def _dense_agg(self, node: N.PAgg, cols, sel, agg_specs, agg_values,
                   post_scale):
        """Perfect-hash aggregation when ALL group keys are dictionary-coded
        strings with a small static domain (nodeAgg's hashed strategy with a
        compile-time-perfect hash) — skips the sort entirely."""
        sizes = dense_domain(node, self.dense_strategy)
        if sizes is None:
            return None
        prod = math.prod(sizes)

        strides = []
        acc = 1
        for s in reversed(sizes):
            strides.append(acc)
            acc *= s
        strides.reverse()

        gid = jnp.zeros(sel.shape, dtype=jnp.int32)
        for (name, e), stride in zip(node.group_keys, strides):
            gid = gid + self.expr(e, cols).astype(jnp.int32) \
                * np.int32(stride)
        out_aggs, occupied = K.group_aggregate_dense(
            gid, prod, agg_values, agg_specs, sel,
            strategy=self.dense_strategy)
        for name, div in post_scale.items():
            out_aggs[name] = out_aggs[name] / div

        cell = jnp.arange(prod, dtype=jnp.int32)
        out_keys = {}
        for (name, _), stride, size in zip(node.group_keys, strides, sizes):
            out_keys[name] = (cell // np.int32(stride)) % np.int32(size)

        cap = node.capacity
        if cap > prod:
            pad = cap - prod
            out_keys = {n: jnp.pad(c, (0, pad)) for n, c in out_keys.items()}
            out_aggs = {n: jnp.pad(c, (0, pad)) for n, c in out_aggs.items()}
            occupied = jnp.pad(occupied, (0, pad))
        return {**out_keys, **out_aggs}, occupied


def _sortable(e: ex.Expr, child: N.PlanNode, cols) -> jnp.ndarray:
    """ORDER BY key array; string columns sort by host rank, not code."""
    arr = compile_expr(e)(cols)
    if e.dtype.base == DType.STRING:
        sdict = None
        if isinstance(e, ex.ColumnRef):
            try:
                sdict = child.field(e.name).sdict
            except KeyError:
                sdict = getattr(e, "_sdict", None)
        else:
            sdict = getattr(e, "_sdict", None) or getattr(e, "_out_dict", None)
        if sdict is not None and len(sdict):
            rank = jnp.asarray(sdict.rank_table())
            safe = jnp.clip(arr, 0, rank.shape[0] - 1)
            return jnp.where(arr >= 0, jnp.take(rank, safe), -1)
    return arr


def _days_from_civil(y, m, d):
    """(year, month, day) → days since 1970-01-01; Howard Hinnant's
    branchless days-from-civil (the inverse of
    expr_compile._civil_from_days)."""
    y = y.astype(jnp.int64) - (m <= 2)
    era = jnp.floor_divide(y, 400)
    yoe = y - era * 400
    doy = (153 * (m + jnp.where(m > 2, -3, 9)) + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


def _shift_months_days(days, n_months: int):
    """Shift day-numbers by n calendar months, clamping the day of month
    (Mar 31 - 1 month = Feb 28) — PG's date + interval 'n months'
    semantics (src/backend/utils/adt/timestamp.c interval_pl role),
    vectorized for the RANGE frame search."""
    from cloudberry_tpu.exec.expr_compile import _civil_from_days

    y, m, d = _civil_from_days(days)
    mm = m.astype(jnp.int64) - 1 + n_months
    y2 = y.astype(jnp.int64) + jnp.floor_divide(mm, 12)
    m2 = jnp.mod(mm, 12) + 1
    leap = ((y2 % 4 == 0) & ((y2 % 100 != 0) | (y2 % 400 == 0)))
    dim = jnp.asarray([31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31],
                      dtype=jnp.int64)[m2 - 1]
    dim = jnp.where((m2 == 2) & leap, 29, dim)
    d2 = jnp.minimum(d.astype(jnp.int64), dim)
    return _days_from_civil(y2, m2, d2)


def _substitute_subqueries(e: ex.Expr, mapping: dict[int, str]) -> ex.Expr:
    """Replace SubqueryScalar nodes with ColumnRefs into the augmented
    column dict (generic rewriter: new node types flow through)."""
    return ex.rewrite(
        e, lambda n: ex.ColumnRef(mapping[id(n)], n.dtype)
        if isinstance(n, ex.SubqueryScalar) else None)
