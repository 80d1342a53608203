"""Relational kernels over fixed-capacity column batches — all jittable.

Design discipline (SURVEY.md §7.3): XLA requires static shapes, so
- filters AND into the selection mask (no compaction);
- group-by takes one of two forms, and the planner picks (``PAgg.direct``):
  where the keys' box is proven and holds no more slots than the rows
  that arrive and the groups the node may emit, a scatter-add of each
  row into its slot of a DIRECT-ADDRESS table over the box
  (``group_aggregate_direct``); else sort-based: lexsort → boundary
  flags → segment reductions. Both exact (no hash collisions);
- a unique-build (PK–FK) join finds each probe row's build row in one of
  two forms, and the planner picks (``PJoin.direct_lookup``): where the
  build keys' span is proven and no larger than the largest array the
  join already holds (its build, or the rows that reach its search), one
  gather from a DIRECT-ADDRESS table of one int32 a key value
  (``join_lookup_direct``); else the "sorted-build lookup": sort the
  build, binary-search probes with ``searchsorted``. Payloads are then
  gathered. A many-to-many join expands pairs (``join_expand``).

These replace the reference's per-tuple executor nodes: nodeAgg.c,
nodeHash.c/nodeHashjoin.c, nodeSort.c, nodeLimit.c — pointer-chasing hash
tables have no TPU analog, sort+segment ops are the native formulation.
"""

from __future__ import annotations

import math
import operator
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Columns = dict[str, jnp.ndarray]

# --------------------------------------------------------------------------
# key normalization: every key column becomes a sortable uint64 whose order
# matches SQL order (ints/dates: offset; floats: IEEE total-order trick;
# strings: rank table gathered by caller).
# --------------------------------------------------------------------------

_SIGN64 = jnp.uint64(1) << jnp.uint64(63)


def sort_key_u64(col: jnp.ndarray) -> jnp.ndarray:
    """Map a column to uint64 preserving SQL ascending order."""
    if col.dtype == jnp.bool_:
        return col.astype(jnp.uint64)
    if col.dtype == jnp.float32:
        # IEEE total-order trick in 32 bits, then widen — avoids the f64
        # bitcast that the TPU backend cannot compile.
        bits = col.view(jnp.uint32)
        mask = jnp.where(bits >> jnp.uint32(31) != 0,
                         jnp.uint32(0xFFFFFFFF), jnp.uint32(1) << jnp.uint32(31))
        return (bits ^ mask).astype(jnp.uint64)
    if col.dtype == jnp.float64:
        # The TPU backend cannot compile a direct f64→u64 bitcast, but it
        # CAN bitcast f64 to two u32 words (bitcast_convert_type to a
        # narrower type appends a minor dimension, index 0 = least
        # significant word — XLA semantics). Reassemble the IEEE bits
        # with u64 shifts (u64 ARITHMETIC is supported/emulated), then
        # apply the same total-order mask as f32.
        words = jax.lax.bitcast_convert_type(col, jnp.uint32)
        bits = (words[..., 1].astype(jnp.uint64) << jnp.uint64(32)) \
            | words[..., 0].astype(jnp.uint64)
        mask = jnp.where(bits >> jnp.uint64(63) != 0,
                         jnp.uint64(0xFFFFFFFFFFFFFFFF), _SIGN64)
        return bits ^ mask
    return col.astype(jnp.int64).view(jnp.uint64) ^ _SIGN64


_U64_MAX = jnp.uint64(0xFFFFFFFFFFFFFFFF)


def key_ranges(
    keys: Sequence[jnp.ndarray], sel: jnp.ndarray
) -> list[tuple[jnp.ndarray, jnp.ndarray]]:
    """Per-column (lo, span) over the SELECTED rows, in u64 key space."""
    out = []
    for k in keys:
        u = sort_key_u64(k)
        lo = jnp.min(jnp.where(sel, u, _U64_MAX))
        hi = jnp.max(jnp.where(sel, u, jnp.uint64(0)))
        span = jnp.maximum(hi - lo, jnp.uint64(0)) + jnp.uint64(1)
        out.append((lo, span))
    return out


def pack_with_ranges(
    keys: Sequence[jnp.ndarray],
    ranges: Sequence[tuple[jnp.ndarray, jnp.ndarray]],
    descending: Sequence[bool] | None = None,
) -> jnp.ndarray:
    """Pack key columns into ONE order-preserving uint64 using given ranges.

    Exact when the product of spans fits 64 bits (always true for TPC-H key
    columns). Values outside a range pack to the all-ones sentinel, which
    never equals an in-range pack — so cross-side packing (join probe against
    build-side ranges) stays exact rather than aliasing.
    """
    packed = jnp.zeros(keys[0].shape, dtype=jnp.uint64)
    oob = jnp.zeros(keys[0].shape, dtype=jnp.bool_)
    desc = descending if descending is not None else [False] * len(keys)
    for k, (lo, span), d in zip(keys, ranges, desc):
        u = sort_key_u64(k)
        oob = oob | (u < lo) | (u - lo >= span)
        digit = jnp.clip(u - lo, jnp.uint64(0), span - jnp.uint64(1))
        # (a descending key counts down from its range's top)
        packed = packed * span + (span - jnp.uint64(1) - digit if d
                                  else digit)
    return jnp.where(oob, _U64_MAX, packed)


def pack_keys(keys: Sequence[jnp.ndarray], sel: jnp.ndarray,
              descending: Sequence[bool] | None = None) -> jnp.ndarray:
    """Pack multiple key columns of one batch into order-preserving uint64
    (selected rows are in-range by construction; others → sentinel)."""
    return pack_with_ranges(keys, key_ranges(keys, sel), descending)


_U32_MAX = jnp.uint32(0xFFFFFFFF)


def downcast32(packed: jnp.ndarray) -> jnp.ndarray:
    """Narrow packed u64 keys to u32 when the PLANNER proved (from table
    min/max statistics) that every in-range pack fits 32 bits — TPU sorts
    and searches run ~2× faster on 32-bit lanes. The u64 sentinel maps to
    the u32 sentinel; real packs are < 2^32-1 by the planner's proof, so
    no aliasing is possible."""
    return jnp.where(packed == _U64_MAX, _U32_MAX,
                     packed.astype(jnp.uint32))


# --------------------------------------------------------------------------
# sorts and prefix sums. The TPU compiler's time for ONE sort grows with
# the words its comparator reads, hardly with the rows: at 1.5M rows an
# unstable sort of one u32 takes it 4 s, of two key words 19 s, three 37 s,
# five 100 s, seven 172 s; a STABLE sort pays for a hidden position key
# besides, and payload operands ~15 s a word (sandbox, PR 28, v5e:2x2 ahead
# of time; jnp.lexsort of (flag, u64, u64, u64) with its int64 index: 477
# s). So every argsort here is one UNSTABLE ``lax.sort`` whose operands are
# all keys, as few 32-bit words as the dtypes allow, the row's position the
# last of them:
# the order is the stable one, and the sorted positions are the answer.
# --------------------------------------------------------------------------


def prefix_sum(x: jnp.ndarray) -> jnp.ndarray:
    """``jnp.cumsum`` of a 1-D integer array, as shifted adds: inside
    blocks of 128 (seven steps of add-what-lies-s-to-the-left), then the
    blocks' totals the same way. Integer sums wrap, so the order of the
    additions does not show. The TPU compiler takes 12 s over one int64
    cumsum of 1.5M rows and 31 s over an int32 one, one after the other
    (a join with a grouped aggregate and a limit has four); these adds
    take it 1.4 s (sandbox, PR 28). Floats keep ``jnp.cumsum``: there
    the order of the additions is the answer's last bits."""
    if x.ndim != 1 or not (jnp.issubdtype(x.dtype, jnp.integer)
                           or x.dtype == jnp.bool_):
        return jnp.cumsum(x)
    if x.dtype == jnp.bool_:
        x = x.astype(int)           # as cumsum counts flags
    n, block = x.shape[0], 128
    if n <= block:
        return _shifted(x, 0, operator.add)
    rows = jnp.pad(x, (0, -n % block)).reshape(-1, block)
    inner = _shifted(rows, 1, operator.add)
    totals = inner[:, -1]
    before = prefix_sum(totals) - totals
    return (inner + before[:, None]).reshape(-1)[:n]


def running_max(x: jnp.ndarray) -> jnp.ndarray:
    """The running maximum of a 1-D array of NON-NEGATIVE integers, in
    ``prefix_sum``'s shifted form with ``max`` for ``+`` (the zeros
    shifted in are its identity there): ``lax.cummax`` lowers on the TPU
    to a reduce-window, as ``cumsum`` does, whose compile ``prefix_sum``
    avoids. A block's prefix is the running maximum of the blocks'
    totals before it."""
    n, block = x.shape[0], 128
    if n <= block:
        return _shifted(x, 0, jnp.maximum)
    rows = jnp.pad(x, (0, -n % block)).reshape(-1, block)
    inner = _shifted(rows, 1, jnp.maximum)
    before = jnp.pad(running_max(inner[:, -1])[:-1], (1, 0))
    return jnp.maximum(inner, before[:, None]).reshape(-1)[:n]


def _shifted(m: jnp.ndarray, axis: int, combine) -> jnp.ndarray:
    """An inclusive scan of ``m`` along ``axis`` under ``combine``: in
    log2 steps, each combining every element with what lies ``s`` to its
    left (zeros shifted in from the edge)."""
    n, s = m.shape[axis], 1
    while s < n:
        pad = [(0, 0)] * m.ndim
        pad[axis] = (s, 0)
        keep = [slice(None)] * m.ndim
        keep[axis] = slice(0, n - s)
        m = combine(m, jnp.pad(m[tuple(keep)], pad))
        s *= 2
    return m


def sort_key_word(col: jnp.ndarray) -> jnp.ndarray:
    """``sort_key_u64`` in the narrowest unsigned word that holds the
    column: u32 for anything up to 32 bits wide."""
    if col.dtype == jnp.bool_:
        return col.astype(jnp.uint32)
    if col.dtype == jnp.float32:
        bits = col.view(jnp.uint32)
        mask = jnp.where(bits >> jnp.uint32(31) != 0,
                         jnp.uint32(0xFFFFFFFF), jnp.uint32(1) << jnp.uint32(31))
        return bits ^ mask
    if jnp.issubdtype(col.dtype, jnp.unsignedinteger) \
            and col.dtype.itemsize <= 4:
        return col.astype(jnp.uint32)
    if jnp.issubdtype(col.dtype, jnp.signedinteger) \
            and col.dtype.itemsize <= 4:
        return col.astype(jnp.int32).view(jnp.uint32) \
            ^ (jnp.uint32(1) << jnp.uint32(31))
    return sort_key_u64(col)


def _word_max(dt) -> jnp.ndarray:
    return _U32_MAX if dt == jnp.uint32 else _U64_MAX


def _positions(n: int, spare: int = 1) -> jnp.ndarray:
    """0..n-1 as one u32 word (``spare`` × n must fit it too)."""
    if spare * n > 1 << 32:
        raise ValueError(f"{n} rows: positions no longer fit one word")
    return jax.lax.iota(jnp.uint32, n)


def stable_argsort(*words: jnp.ndarray) -> jnp.ndarray:
    """Stable ascending argsort by unsigned ``words`` (most significant
    first)."""
    pos = _positions(words[0].shape[0])
    out = jax.lax.sort(tuple(words) + (pos,), num_keys=len(words) + 1,
                       is_stable=False)
    return out[-1].astype(jnp.int64)


def flagged_first(flag: jnp.ndarray) -> jnp.ndarray:
    """Positions of the flagged rows in order, then the others' in order
    (``argsort(~flag, stable=True)``): a sort of one word, the position
    moved past every flagged row's where the flag is down."""
    n = flag.shape[0]
    pos = _positions(n, spare=2)
    t = jax.lax.sort(pos + jnp.where(flag, jnp.uint32(0), jnp.uint32(n)),
                     is_stable=False)
    return (t - jnp.where(t >= n, jnp.uint32(n), jnp.uint32(0))) \
        .astype(jnp.int64)


def bucket_argsort(bucket: jnp.ndarray, n_buckets: int) -> jnp.ndarray:
    """Stable argsort of bucket numbers in 0..``n_buckets`` (the last one
    holds what is dropped): one word, bucket * rows + position, where
    that fits 32 bits."""
    n = bucket.shape[0]
    b = bucket.astype(jnp.uint32)
    if (n_buckets + 1) * n > 1 << 32:
        return stable_argsort(b)
    t = jax.lax.sort(b * jnp.uint32(n) + _positions(n), is_stable=False)
    return (t % jnp.uint32(n)).astype(jnp.int64)


def bucket_slots(order: jnp.ndarray, counts: jnp.ndarray,
                 cap: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """For every slot of an (n_buckets, cap) buffer, the row that fills
    it and whether one does. ``order`` sorts the rows by bucket, ties in
    position order (``bucket_argsort``), and ``counts[b]`` rows go to
    bucket b, so bucket b's rows are sorted rows start[b].. and slot
    (b, r) takes sorted row start[b] + r while r < counts[b]; rows past
    ``cap`` find no slot. A gather of n_buckets * cap rows where the
    scatter it replaces moved every input row: the TPU serializes a
    scatter, and its compiler takes half a minute over one of 1.5M rows
    (sandbox, PR 28)."""
    n_buckets = counts.shape[0]
    start = jnp.cumsum(counts) - counts
    j = jnp.arange(n_buckets * cap)
    b, r = j // cap, j % cap
    filled = r < counts[b]
    src = order[jnp.clip(start[b] + r, 0, order.shape[0] - 1)]
    return src, filled


def inverse_permutation(perm: jnp.ndarray) -> jnp.ndarray:
    """``argsort(perm)`` of a permutation: its values are distinct, so
    the position rides as a payload and no tie needs an order."""
    _, pos = jax.lax.sort((perm.astype(jnp.uint32),
                           _positions(perm.shape[0])),
                          num_keys=1, is_stable=False)
    return pos.astype(jnp.int64)


def sort_indices(
    keys: Sequence[jnp.ndarray],
    sel: jnp.ndarray,
    descending: Sequence[bool] | None = None,
) -> jnp.ndarray:
    """Permutation putting selected rows first, ordered by keys, ties in
    position order; the unselected rows follow in position order.

    keys[0] is the PRIMARY key (SQL ORDER BY first column). An unselected
    row carries the largest word in every key and its position moved past
    every selected row's, so it sorts behind a selected row even where
    that row's keys are all the largest too: no flag operand."""
    n = sel.shape[0]
    desc = list(descending) if descending is not None else [False] * len(keys)
    words = []
    for k, d in zip(keys, desc):
        u = sort_key_word(k)
        words.append(jnp.where(sel, ~u if d else u, _word_max(u.dtype)))
    tie = _positions(n, spare=2) \
        + jnp.where(sel, jnp.uint32(0), jnp.uint32(n))
    t = jax.lax.sort(tuple(words) + (tie,), num_keys=len(words) + 1,
                     is_stable=False)[-1]
    return (t - jnp.where(t >= n, jnp.uint32(n), jnp.uint32(0))) \
        .astype(jnp.int64)


# --------------------------------------------------------------------------
# group-by
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class AggSpec:
    """One aggregate: func ∈ {sum,count,min,max,avg}; count with arg=None is
    COUNT(*). ``values`` are pre-evaluated argument arrays (None for *)."""
    func: str
    out_name: str


@dataclass
class GroupLayout:
    """Sorted-group scaffolding of the sort-based aggregation: the sort,
    boundary detection and start compaction."""

    names: list              # the keys sorted (not the carried ones)
    perm: jnp.ndarray        # sort permutation (selected rows first)
    s_sel: jnp.ndarray       # selection in sorted order
    s_keys: Columns          # sorted key columns in sorted order
    new_grp: jnp.ndarray     # group-start flags over sorted selected rows
    n_groups: jnp.ndarray
    n_sel: jnp.ndarray
    starts: jnp.ndarray      # per output slot: group start row (0 pad)
    ends: jnp.ndarray        # per output slot: group end row (0 pad)
    valid: jnp.ndarray       # slot < n_groups
    out_keys: Columns        # compacted key columns (zeros on pad)


def group_layout(key_cols: Columns, sel: jnp.ndarray,
                 out_capacity: int, pack_bits: int = 0,
                 carried: Sequence[str] = ()) -> GroupLayout:
    """``pack_bits`` 32 or 64: the planner's proof (PAgg.pack_bits) that
    the sorted keys of the selected rows pack into one word that wide;
    the sort then compares that word. ``carried``: keys the sorted ones
    determine (PAgg.carried, plan/fdep.py), never sorted, each gathered
    at its group's first row."""
    names = [n for n in key_cols if n not in carried]
    key_list = [key_cols[n] for n in names]
    if pack_bits:
        packed = pack_keys(key_list, sel)
        perm = sort_indices(
            [downcast32(packed) if pack_bits == 32 else packed], sel)
    else:
        perm = sort_indices(key_list, sel)
    s_sel = sel[perm]
    s_keys = {n: key_cols[n][perm] for n in names}

    new_grp = jnp.zeros_like(s_sel)
    for n in names:
        k = s_keys[n]
        new_grp = new_grp | (k != jnp.roll(k, 1))
    new_grp = new_grp.at[0].set(True)
    new_grp = new_grp & s_sel

    n_groups = jnp.sum(new_grp.astype(jnp.int32))
    n_sel = jnp.sum(s_sel.astype(jnp.int32))

    # boundary positions compact to the front via a stable bool argsort
    starts_all = flagged_first(new_grp)
    g = jnp.arange(out_capacity)
    starts = starts_all[jnp.clip(g, 0, starts_all.shape[0] - 1)]
    next_start = starts_all[jnp.clip(g + 1, 0, starts_all.shape[0] - 1)]
    valid = g < n_groups
    ends = jnp.where(g + 1 < n_groups, next_start - 1, n_sel - 1)
    starts = jnp.where(valid, starts, 0)
    ends = jnp.where(valid, ends, 0)

    out_keys: Columns = {}
    first = perm[starts] if carried else None
    for n in key_cols:
        col = key_cols[n][first] if n in carried else s_keys[n][starts]
        out_keys[n] = jnp.where(valid, col, jnp.zeros((), dtype=col.dtype))
    return GroupLayout(names, perm, s_sel, s_keys, new_grp, n_groups,
                       n_sel, starts, ends, valid, out_keys)


def group_aggregate(
    key_cols: Columns,
    agg_values: dict[str, Optional[jnp.ndarray]],
    aggs: Sequence[AggSpec],
    sel: jnp.ndarray,
    out_capacity: int,
    pack_bits: int = 0,
    carried: Sequence[str] = (),
) -> tuple[Columns, Columns, jnp.ndarray, jnp.ndarray]:
    """Sort-based grouped aggregation (nodeAgg.c analog).

    Returns (out_key_cols, out_agg_cols, out_sel, n_groups); groups are
    emitted in ascending order of the sorted keys (``carried`` keys are
    determined by them, ``group_layout``). ``n_groups`` is the TRUE group
    count — the executor must check it against out_capacity after the
    run: groups beyond capacity are clipped into the last slot, so
    n_groups > out_capacity means wrong results and is an error, never
    silent (the capacity-flow-control discipline of
    ic_udpifc.c:3018 applied to shapes).

    Scatter-free segmented reduction: every per-group aggregate is a
    cumulative-sum DIFFERENCE between consecutive group boundaries — pure
    sort/scan/gather. Where the keys' box is proven and small enough the
    planner takes ``group_aggregate_direct`` instead: no sort at all, and
    a scatter is no serialized loop on TPU (on a v5e a slot map's
    scatter-add took Q13's device time from 1,193.6 to 211.3 ms; 6M rows
    scatter into a 1.5M-slot table in 7.0 ms).
    """
    lay = group_layout(key_cols, sel, out_capacity, pack_bits, carried)
    names, key_list = lay.names, [key_cols[n] for n in lay.names]
    perm, s_sel = lay.perm, lay.s_sel
    n_groups, n_sel = lay.n_groups, lay.n_sel
    starts, ends, valid = lay.starts, lay.ends, lay.valid
    out_keys = lay.out_keys

    def seg_sum(vals):
        csum = prefix_sum(vals)
        c0 = jnp.concatenate([jnp.zeros((1,), dtype=csum.dtype), csum])
        return jnp.where(valid, c0[ends + 1] - c0[starts], 0)

    counts = jnp.where(valid, (ends - starts + 1), 0).astype(jnp.int64)

    extreme_perm_cache: dict[bool, jnp.ndarray] = {}

    def seg_extreme(v_unpermuted, want_max: bool):
        # re-sort with the value as the last key: each group's extreme lands
        # on its boundary row (one extra sort only when min/max is used)
        if want_max not in extreme_perm_cache:
            extreme_perm_cache[want_max] = sort_indices(
                key_list + [v_unpermuted], sel,
                descending=[False] * len(key_list) + [want_max])
        p2 = extreme_perm_cache[want_max]
        return v_unpermuted[p2][starts]

    out_aggs: Columns = {}
    for spec in aggs:
        v = agg_values.get(spec.out_name)
        if spec.func == "count":
            out = counts
        elif spec.func == "count_nn":
            out = seg_sum((s_sel & v[perm]).astype(jnp.int64))
        elif spec.func == "sum":
            out = seg_sum(jnp.where(s_sel, v[perm], 0))
        elif spec.func == "min":
            ident = _dtype_max(v.dtype)
            out = jnp.where(valid & (counts > 0),
                            seg_extreme(v, want_max=False), ident)
        elif spec.func == "max":
            ident = _dtype_min(v.dtype)
            out = jnp.where(valid & (counts > 0),
                            seg_extreme(v, want_max=True), ident)
        elif spec.func == "avg":
            # integer-carried values (BIGINT, DECIMAL cents) sum EXACTLY
            # in int64 before the f64 division — an f64 cumsum rounds
            # once prefixes pass 2^53. The widen matters for INT32/DATE
            # too: cumsum keeps the input dtype, so an un-widened int32
            # numerator would wrap at 2^31.
            masked = jnp.where(s_sel, v[perm], 0).astype(
                jnp.int64 if jnp.issubdtype(v.dtype, jnp.integer)
                else jnp.float64)
            out = seg_sum(masked).astype(jnp.float64) \
                / jnp.maximum(counts, 1)
        else:
            raise NotImplementedError(spec.func)
        out_aggs[spec.out_name] = out

    out_sel = jnp.arange(out_capacity) < n_groups
    return out_keys, out_aggs, out_sel, n_groups


def group_aggregate_dense(
    gid: jnp.ndarray,
    n_cells: int,
    agg_values: dict[str, Optional[jnp.ndarray]],
    aggs: Sequence[AggSpec],
    sel: jnp.ndarray,
    strategy: str = "reduce",
) -> tuple[Columns, jnp.ndarray]:
    """Perfect-hash grouped aggregation for small, statically-known key
    domains (e.g. dictionary-coded strings: Q1's returnflag × linestatus).

    strategy='reduce' (TPU): unrolled per-cell masked tree-reductions.
    strategy='segment' (CPU): scatter-based segment ops.

    No sort: on TPU an unrolled per-cell masked tree-reduction, a fused
    sweep a cell, which for a domain of at most 64 cells needs no table.
    (An earlier reading of ~150 ms per 1.8M-row segment_sum on TPU does
    not hold for today's scatters: on a v5e 6M rows scatter into a
    1.5M-slot table in 7.0 ms.) Exact for int64 (tree
    reduction of exact adds). Returns (agg columns
    indexed by cell id, occupancy mask); key reconstruction from cell id is
    the caller's job.
    """
    gid = jnp.where(sel, jnp.clip(gid, 0, n_cells - 1), n_cells)
    out: Columns = {}
    if strategy == "segment":
        # scatter-based: best on CPU, where XLA emits a tight update loop
        counts = jax.ops.segment_sum(sel.astype(jnp.int64), gid,
                                     num_segments=n_cells + 1)[:n_cells]
        seg = lambda vv: jax.ops.segment_sum(
            vv, gid, num_segments=n_cells + 1)[:n_cells]
        smin = lambda vv: jax.ops.segment_min(
            vv, gid, num_segments=n_cells + 1)[:n_cells]
        smax = lambda vv: jax.ops.segment_max(
            vv, gid, num_segments=n_cells + 1)[:n_cells]
        for spec in aggs:
            v = agg_values.get(spec.out_name)
            if spec.func == "count":
                out[spec.out_name] = counts
            elif spec.func == "count_nn":
                out[spec.out_name] = seg((sel & v).astype(jnp.int64))
            elif spec.func == "sum":
                out[spec.out_name] = seg(jnp.where(sel, v, 0))
            elif spec.func == "min":
                out[spec.out_name] = smin(jnp.where(sel, v, _dtype_max(v.dtype)))
            elif spec.func == "max":
                out[spec.out_name] = smax(jnp.where(sel, v, _dtype_min(v.dtype)))
            elif spec.func == "avg":
                # int64 widen: segment_sum keeps the input dtype, so an
                # int32 numerator would wrap (see group_aggregate)
                masked = jnp.where(sel, v, 0).astype(
                    jnp.int64 if jnp.issubdtype(v.dtype, jnp.integer)
                    else jnp.float64)
                out[spec.out_name] = seg(masked).astype(jnp.float64) \
                    / jnp.maximum(counts, 1)
            else:
                raise NotImplementedError(spec.func)
        return out, counts > 0
    cell_masks = [gid == c for c in range(n_cells)]
    counts = jnp.stack([m.sum(dtype=jnp.int64) for m in cell_masks])
    for spec in aggs:
        v = agg_values.get(spec.out_name)
        if spec.func == "count":
            out[spec.out_name] = counts
        elif spec.func == "count_nn":
            out[spec.out_name] = jnp.stack(
                [(m & v).sum(dtype=jnp.int64) for m in cell_masks])
        elif spec.func == "sum":
            out[spec.out_name] = jnp.stack(
                [jnp.where(m, v, 0).sum() for m in cell_masks])
        elif spec.func == "min":
            big = _dtype_max(v.dtype)
            out[spec.out_name] = jnp.stack(
                [jnp.where(m, v, big).min() for m in cell_masks])
        elif spec.func == "max":
            small = _dtype_min(v.dtype)
            out[spec.out_name] = jnp.stack(
                [jnp.where(m, v, small).max() for m in cell_masks])
        elif spec.func == "avg":
            # exact int64 numerator for integer values (see group_aggregate)
            acc_dt = jnp.int64 if jnp.issubdtype(v.dtype, jnp.integer) \
                else jnp.float64
            s = jnp.stack([jnp.where(m, v, 0).sum(dtype=acc_dt)
                           for m in cell_masks])
            out[spec.out_name] = s.astype(jnp.float64) \
                / jnp.maximum(counts, 1)
        else:
            raise NotImplementedError(spec.func)
    return out, counts > 0


def exact_table_sum(slot: jnp.ndarray, size: int, v: jnp.ndarray,
                    counts: jnp.ndarray,
                    value_bits: Optional[tuple[int, bool]] = None
                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The exact int64 sum, a slot of a table of ``size``, of the integer
    values ``v`` of the rows whose ``slot`` lies below ``size`` (the
    others are dropped writes); ``counts``: the rows a slot holds. The
    values are cut into words of b bits, b = 32 less the bits of the
    row count, so no word's sum over every row can pass 2^32, and each
    word is a u32 scatter-add; the words are recombined in int64 at the
    table's size. Two's complement sums wrap alike, so the recombined
    sum is the true one wherever that fits int64, negative values
    included. ``value_bits``: (bits, signed), the width ``v`` fits by
    proof, unsigned or two's complement; the words then cover ``v -
    least`` only, least 0 or -2^(bits-1) (Q18's ``l_quantity``, 0..5,000
    cents, 13 bits: two words of 9 at 6M rows, not eight), and ``counts
    × least`` is added back. A width, not a range: a table whose values
    move inside it keeps its program. Returns (the sums, a row's value
    outside ``value_bits``: the proof is broken)."""
    b = 32 - int(v.shape[0]).bit_length()
    v = v.astype(jnp.int64)
    width, least = 64, 0
    if value_bits is not None:
        width, signed = value_bits
        least = -(1 << (width - 1)) if signed else 0
    u = (v - least).view(jnp.uint64)
    outside = (u >> jnp.uint64(width)) != 0 if width < 64 \
        else jnp.zeros(v.shape, jnp.bool_)
    total = jnp.zeros(size, jnp.uint64)
    mask = jnp.uint64((1 << b) - 1)
    for at in range(0, max(width, 1), b):
        word = ((u >> jnp.uint64(at)) & mask).astype(jnp.uint32)
        acc = jnp.zeros(size, jnp.uint32).at[slot].add(word, mode="drop")
        total = total + (acc.astype(jnp.uint64) << jnp.uint64(at))
    return total.view(jnp.int64) + counts * least, outside


def group_aggregate_direct(
    key_cols: Columns,
    agg_values: dict[str, Optional[jnp.ndarray]],
    aggs: Sequence[AggSpec],
    sel: jnp.ndarray,
    box: Sequence[tuple[int, int]],
    out_capacity: int,
    carried: Sequence[str] = (),
    value_bits: Optional[dict] = None,
) -> tuple[Columns, Columns, jnp.ndarray, jnp.ndarray]:
    """Grouped aggregation into a direct-address table, no sort: the
    keys that are not ``carried`` lie in a box proven at plan time
    (``PAgg.direct_box``: one ``(least value, span)`` a key), so a
    row's group is its slot in a table of the box's size
    (``direct_slots``), and every aggregate is a scatter-add over the
    slots: counts in int32, integer sums exactly (``exact_table_sum``;
    ``value_bits``: an aggregate's proven argument width, by its output
    name). A carried key is read at whichever row of its group
    the table kept (the sorted keys determine it). The planner engages
    it where the box holds no more slots than ``out_capacity`` (and the
    rows), so every group has its slot and none can overflow.

    Returns (out_key_cols, out_agg_cols, out_sel, past): groups in
    ascending key order at their slots, NOT compacted to the front (the
    occupied slots are ``out_sel``; pad beyond the box); ``past`` where
    a selected row's keys lie outside the box or a summed value outside
    its ``value_bits`` (the proof is broken: the answer is not to be
    used)."""
    names = [n for n in key_cols if n not in carried]
    slot, inside = direct_slots([key_cols[n] for n in names], box)
    size = math.prod(span for _, span in box)
    past = (sel & ~inside).any()
    slot = jnp.where(sel, slot, size)       # (outside: already ``size``)
    counts = jnp.zeros(size, jnp.int32).at[slot].add(
        1, mode="drop").astype(jnp.int64)
    occupied = counts > 0
    widths = value_bits or {}

    out_aggs: Columns = {}
    for spec in aggs:
        v = agg_values.get(spec.out_name)
        if spec.func == "count":
            out = counts
        elif spec.func == "count_nn":
            out = jnp.zeros(size, jnp.int32).at[slot].add(
                v.astype(jnp.int32), mode="drop").astype(jnp.int64)
        elif spec.func in ("sum", "avg"):
            total, outside = exact_table_sum(slot, size, v, counts,
                                             widths.get(spec.out_name))
            past = past | (sel & outside).any()
            if spec.func == "avg":
                out = total.astype(jnp.float64) / jnp.maximum(counts, 1)
            else:
                # (the sort path's sum keeps the argument's type)
                out = total.astype(jnp.where(sel, v, 0).dtype)
        else:
            raise NotImplementedError(spec.func)
        out_aggs[spec.out_name] = out

    out_keys: Columns = {}
    cell = jax.lax.iota(jnp.int64, size)
    stride = size
    for n, (lo, span) in zip(names, box):
        stride //= span
        k = lo + (cell // stride) % span
        out_keys[n] = jnp.where(occupied, k, 0).astype(key_cols[n].dtype)
    if carried:
        rows = jax.lax.iota(jnp.int32, sel.shape[0])
        first = jnp.zeros(size, jnp.int32).at[slot].set(rows, mode="drop")
        for n in carried:
            col = key_cols[n][first]
            out_keys[n] = jnp.where(occupied, col,
                                    jnp.zeros((), dtype=col.dtype))

    def pad(c):
        return jnp.pad(c, (0, out_capacity - size))

    out_keys = {n: pad(out_keys[n]) for n in key_cols}
    return (out_keys, {n: pad(c) for n, c in out_aggs.items()},
            pad(occupied), past)


def global_aggregate(
    agg_values: dict[str, Optional[jnp.ndarray]],
    aggs: Sequence[AggSpec],
    sel: jnp.ndarray,
) -> Columns:
    """Ungrouped aggregation → one-row columns (shape (1,))."""
    out: Columns = {}
    for spec in aggs:
        v = agg_values.get(spec.out_name)
        if spec.func == "count":
            out[spec.out_name] = jnp.sum(sel.astype(jnp.int64))[None]
        elif spec.func == "count_nn":
            out[spec.out_name] = jnp.sum((sel & v).astype(jnp.int64))[None]
        elif spec.func == "sum":
            out[spec.out_name] = jnp.sum(jnp.where(sel, v, 0))[None]
        elif spec.func == "min":
            out[spec.out_name] = jnp.min(
                jnp.where(sel, v, _dtype_max(v.dtype)))[None]
        elif spec.func == "max":
            out[spec.out_name] = jnp.max(
                jnp.where(sel, v, _dtype_min(v.dtype)))[None]
        elif spec.func == "avg":
            # exact int64 numerator for integer values (see group_aggregate)
            masked = jnp.where(sel, v, 0).astype(
                jnp.int64 if jnp.issubdtype(v.dtype, jnp.integer)
                else jnp.float64)
            s = jnp.sum(masked).astype(jnp.float64)
            c = jnp.sum(sel.astype(jnp.int64))
            out[spec.out_name] = (s / jnp.maximum(c, 1))[None]
        else:
            raise NotImplementedError(spec.func)
    return out


def _dtype_max(dt):
    return jnp.asarray(jnp.finfo(dt).max if jnp.issubdtype(dt, jnp.floating)
                       else jnp.iinfo(dt).max, dtype=dt)


def _dtype_min(dt):
    return jnp.asarray(jnp.finfo(dt).min if jnp.issubdtype(dt, jnp.floating)
                       else jnp.iinfo(dt).min, dtype=dt)


# --------------------------------------------------------------------------
# join: sorted-build lookup (PK–FK)
# --------------------------------------------------------------------------


def build_sort(
    build_key: Sequence[jnp.ndarray],
    build_sel: jnp.ndarray,
    bits: int = 64,
) -> tuple[jnp.ndarray, jnp.ndarray, list]:
    """The build side's sort scaffolding: (order, sorted packed keys,
    packing ranges). ONE implementation shared by the in-program joins
    and the host-side join-index cache (exec/joinindex.py mirrors it in
    numpy) — the two must agree bit-for-bit, including stable tie order,
    for cached indexes to be drop-in replacements."""
    ranges = key_ranges(list(build_key), build_sel)
    kb = pack_with_ranges(list(build_key), ranges)
    big = _U32_MAX if bits == 32 else _U64_MAX
    if bits == 32:
        kb = downcast32(kb)
    kb_masked = jnp.where(build_sel, kb, big)
    order = stable_argsort(kb_masked)
    return order, kb_masked[order], ranges


def dup_check(kb_sorted: jnp.ndarray, bits: int = 64) -> jnp.ndarray:
    """Duplicate build keys, for free off the already-sorted keys (the
    sentinel — unselected/out-of-range rows — never counts)."""
    big = _U32_MAX if bits == 32 else _U64_MAX
    if kb_sorted.shape[0] <= 1:
        return jnp.asarray(False)
    return ((kb_sorted[1:] == kb_sorted[:-1])
            & (kb_sorted[1:] != big)).any()


def join_lookup_sorted(
    order: jnp.ndarray,
    kb_sorted: jnp.ndarray,
    ranges: Sequence[tuple[jnp.ndarray, jnp.ndarray]],
    probe_key: Sequence[jnp.ndarray],
    probe_sel: jnp.ndarray,
    bits: int = 64,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """join_lookup against a PRE-SORTED build (computed in-program or fed
    from the session join-index cache): probe packing + binary search
    only, no argsort."""
    pos_c, matched = join_probe_sorted(kb_sorted, ranges, probe_key,
                                       probe_sel, bits)
    build_row = order[pos_c].astype(jnp.int32)
    return build_row, matched, dup_check(kb_sorted, bits)


def join_probe_sorted(
    kb_sorted: jnp.ndarray,
    ranges: Sequence[tuple[jnp.ndarray, jnp.ndarray]],
    probe_key: Sequence[jnp.ndarray],
    probe_sel: jnp.ndarray,
    bits: int = 64,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The search and the match test of ``join_lookup_sorted``: for each
    probe row its position among the sorted build keys and whether the
    key there is its own. ``order[pos]`` is then the build row; a caller
    that compacts the matched rows first (``compact_sparse``) takes it
    for those alone."""
    kp = pack_with_ranges(list(probe_key), ranges)
    big = _U64_MAX
    if bits == 32:
        kp, big = downcast32(kp), _U32_MAX
    pos = jnp.searchsorted(kb_sorted, kp)
    pos_c = jnp.clip(pos, 0, kb_sorted.shape[0] - 1)
    # kp == sentinel marks out-of-range probes; excluding it also makes the
    # empty-build case (kb_sorted all sentinel) correctly match nothing.
    matched = (kb_sorted[pos_c] == kp) & probe_sel & (kp != big)
    return pos_c, matched


def join_lookup_direct(
    build_key: Sequence[jnp.ndarray],
    build_sel: jnp.ndarray,
    probe_key: Sequence[jnp.ndarray],
    probe_sel: jnp.ndarray,
    span: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The unique-build lookup through a direct-address table, no sort
    and no search: the build keys packed with the build's own ranges lie
    below ``span`` by the planner's proof (``PJoin.direct_span``), so a
    table of ``span`` int32 holds each key's build row, -1 where none,
    and a probe row's build row is one gather from it. Returns
    (build_row int32[cap_p], matched bool[cap_p], has_dup, past_span):
    ``has_dup`` where two selected build rows share a key (the table
    holds one of them, whichever write the device kept: read back, the
    other differs), ``past_span`` where a selected build key packs to
    ``span`` or above (the proof is broken: the answer is not to be
    used)."""
    ranges = key_ranges(list(build_key), build_sel)
    kb = pack_with_ranges(list(build_key), ranges)
    kp = pack_with_ranges(list(probe_key), ranges)
    top = jnp.uint64(span)
    inside = build_sel & (kb < top)
    past_span = (build_sel & ~inside).any()
    # (a row outside the table is written at ``span``: dropped)
    slot = jnp.where(inside, kb, top).astype(jnp.int32)
    rows = jax.lax.iota(jnp.int32, build_sel.shape[0])
    table = jnp.full(span, -1, jnp.int32).at[slot].set(rows, mode="drop")
    has_dup = (inside & (table[jnp.minimum(slot, span - 1)] != rows)).any()
    # (an out-of-range probe packs to the all-ones sentinel: no entry)
    hit = kp < top
    row = jnp.where(hit, table[jnp.where(hit, kp, 0).astype(jnp.int32)], -1)
    return jnp.maximum(row, 0), (row >= 0) & probe_sel, has_dup, past_span


def direct_slots(key: Sequence[jnp.ndarray], box: Sequence[tuple[int, int]]
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Each row's slot in a direct-address table laid over a PROVEN key
    box (``box``: one ``(least value, span)`` a key, plan/joincap.py
    ``direct_box``), and whether its keys lie inside the box. Unlike
    ``join_lookup_direct``, which packs with the build's own ranges in
    one program, the box is fixed at plan time: a table built across
    many programs, a tile at a time, packs every tile alike, and so do
    the probes that read it. Returns (slot int32, inside bool); a slot
    outside the box is the table's size (a dropped write)."""
    slot = jnp.zeros(key[0].shape, jnp.int64)
    inside = jnp.ones(key[0].shape, jnp.bool_)
    for k, (lo, span) in zip(key, box):
        off = k.astype(jnp.int64) - lo
        inside = inside & (off >= 0) & (off < span)
        slot = slot * span + off
    size = math.prod(span for _, span in box)
    return jnp.where(inside, slot, size).astype(jnp.int32), inside


def direct_table_insert(
    present: jnp.ndarray,
    payload: Columns,
    build_key: Sequence[jnp.ndarray],
    build_sel: jnp.ndarray,
    box: Sequence[tuple[int, int]],
    rows: Columns,
    unique: bool,
) -> tuple[jnp.ndarray, Columns, jnp.ndarray, jnp.ndarray]:
    """The build half of ``join_lookup_direct``, one tile at a time: the
    selected rows' keys mark their slots in ``present`` (bool[size]) and
    their ``rows`` (payload columns) are written at those slots of
    ``payload``. Returns (present, payload, has_dup, past_span):
    ``past_span`` where a selected key lies outside the proven box (the
    proof is broken: the table is not to be used); with ``unique`` (an
    inner or left join's build), ``has_dup`` where a selected key's slot
    was taken by an earlier tile or by another row of this one (the
    table then holds one of them, whichever write the device kept, and
    the answer is not to be used). A semi or anti join's build records
    presence only; its repeated keys are expected."""
    slot, inside = direct_slots(build_key, box)
    inside = inside & build_sel
    past_span = (build_sel & ~inside).any()
    size = present.shape[0]
    slot = jnp.where(inside, slot, size)
    has_dup = jnp.asarray(False)
    if unique:
        at = jnp.minimum(slot, size - 1)
        ids = jax.lax.iota(jnp.int32, slot.shape[0])
        owner = jnp.full(size, -1, jnp.int32).at[slot].set(ids, mode="drop")
        has_dup = (inside & (present[at] | (owner[at] != ids))).any()
    present = present.at[slot].set(True, mode="drop")
    payload = {n: t.at[slot].set(rows[n], mode="drop")
               for n, t in payload.items()}
    return present, payload, has_dup, past_span


def direct_table_lookup(
    present: jnp.ndarray,
    probe_key: Sequence[jnp.ndarray],
    probe_sel: jnp.ndarray,
    box: Sequence[tuple[int, int]],
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """A probe against a table ``direct_table_insert`` built: one gather.
    Returns (slot int32, matched bool): ``payload[slot]`` is the matched
    build row's; a probe key outside the box matches nothing."""
    slot, inside = direct_slots(probe_key, box)
    slot = jnp.where(inside, slot, 0)
    return slot, inside & probe_sel & present[slot]


def join_lookup(
    build_key: Sequence[jnp.ndarray],
    build_sel: jnp.ndarray,
    probe_key: Sequence[jnp.ndarray],
    probe_sel: jnp.ndarray,
    bits: int = 64,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """For each probe row: index of the matching build row, and a match mask.

    Requires the build side unique on the key (the planner puts the PK side
    here — same choice nodeHash.c makes for the hash side). Exact: compares
    packed keys, and packing is order-preserving/injective for in-range ints.
    ``bits=32`` (planner-proven via table stats) narrows the packed keys so
    the sort/search run on 32-bit lanes. Returns (build_row_idx
    int32[cap_p], matched bool[cap_p], has_dup scalar bool — duplicate
    build keys detected, for free off the already-sorted keys).
    """
    order, kb_sorted, ranges = build_sort(build_key, build_sel, bits)
    return join_lookup_sorted(order, kb_sorted, ranges, probe_key,
                              probe_sel, bits)


def gather_payload(cols: Columns, idx: jnp.ndarray, matched: jnp.ndarray) -> Columns:
    """Gather build-side payload columns to probe rows (0 where unmatched)."""
    out = {}
    for name, c in cols.items():
        g = jnp.take(c, idx, axis=0)
        out[name] = jnp.where(matched, g, jnp.zeros((), dtype=c.dtype))
    return out


def join_expand(
    build_key: Sequence[jnp.ndarray],
    build_sel: jnp.ndarray,
    probe_key: Sequence[jnp.ndarray],
    probe_sel: jnp.ndarray,
    out_capacity: int,
    bits: int = 64,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Many-to-many join: emit ONE OUTPUT ROW PER MATCH PAIR.

    Sorted-build range lookup: probe row i matches the build range
    [start_i, end_i); match pairs are laid out consecutively by probe row
    (offsets = cumsum of per-probe match counts), and output slot j maps back
    to (probe row, k-th match) by a running count of the rows that end at
    or before it — fully vectorized, no data-dependent shapes. Total
    matches beyond ``out_capacity`` are reported, never silently dropped.

    Returns (probe_row[out_cap], build_row[out_cap], out_sel[out_cap],
             matched[probe_cap] (per-probe any-match, for outer joins),
             total_matches scalar).
    """
    order, kb_sorted, ranges = build_sort(build_key, build_sel, bits)
    return join_expand_sorted(order, kb_sorted, ranges, probe_key,
                              probe_sel, out_capacity, bits)


def join_expand_sorted(
    order: jnp.ndarray,
    kb_sorted: jnp.ndarray,
    ranges: Sequence[tuple[jnp.ndarray, jnp.ndarray]],
    probe_key: Sequence[jnp.ndarray],
    probe_sel: jnp.ndarray,
    out_capacity: int,
    bits: int = 64,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """join_expand against a PRE-SORTED build (see join_lookup_sorted)."""
    n_build = kb_sorted.shape[0]
    if out_capacity >= 1 << 31 or n_build >= 1 << 31:
        raise ValueError(
            f"expansion join: a pair buffer of {out_capacity} slots over "
            f"{n_build} build rows no longer fits int32 positions")
    kp = pack_with_ranges(list(probe_key), ranges)
    big = _U64_MAX
    if bits == 32:
        kp, big = downcast32(kp), _U32_MAX

    start = jnp.searchsorted(kb_sorted, kp, side="left")
    end = jnp.searchsorted(kb_sorted, kp, side="right")
    ok = probe_sel & (kp != big)
    # overflow hardening: searchsorted returns a NARROW index dtype, and a
    # cumsum KEEPS its input dtype — per-probe counts must widen to int64
    # BEFORE the prefix sum so the total-vs-capacity overflow check can
    # never itself wrap on a large fanout (capacities past 2^16 rows with
    # hot keys multiply fast; the check is the last line of defense and
    # must be exact at any count)
    cnt = jnp.where(ok, (end - start).astype(jnp.int64), jnp.int64(0))
    matched = cnt > 0

    offsets = prefix_sum(cnt)
    total = offsets[-1] if cnt.shape[0] else jnp.asarray(0, jnp.int64)
    # the slot map: slot j belongs to the first probe row i with
    # offsets[i] > j, and the slots are 0..capacity-1 in order, so that
    # row is the NUMBER of rows whose pairs end at or before j: a mark
    # where each row ends (clipped to the capacity, one int32 word: a
    # row that ends at or past it drops out, a row without pairs marks
    # its neighbour's slot) and one running count over the slots. No
    # search: 18 dependent gathers a slot through the int64 count were
    # 0.94 s of Q13's 1.29 s launch, through one word 0.21 s (PR 35).
    offsets_c = jnp.minimum(offsets, out_capacity).astype(jnp.int32)
    ends = jnp.zeros(out_capacity, jnp.int32).at[offsets_c].add(
        1, mode="drop", indices_are_sorted=True)
    pi = prefix_sum(ends)
    j = jax.lax.iota(jnp.int32, out_capacity)
    pi_c = jnp.clip(pi, 0, cnt.shape[0] - 1)
    # first slot of probe row pi: where the row before it ends (at or
    # under j for a selected slot, so the clipped word is the exact one)
    base = jnp.where(pi_c > 0, offsets_c[jnp.maximum(pi_c - 1, 0)], 0)
    k = j - base
    out_sel = j < jnp.minimum(total, out_capacity).astype(jnp.int32)
    build_pos = jnp.clip(start[pi_c] + k, 0, n_build - 1)
    build_row = order[build_pos].astype(jnp.int32)
    return pi_c, build_row, out_sel, matched, total


# --------------------------------------------------------------------------
# bloom digest — runtime join filters (plan/nodes.py PRuntimeFilter
# mode="digest"): a fixed-size bitmap over RANGE-FREE key hashes, so every
# segment's insertions agree on bit positions without a collective range
# reduction first. The digest (per-key u64 min/max + the bitmap words)
# rides ONE tiny all_gather; probe rows failing the min/max or bloom test
# drop BEFORE their redistribute. False positives only let extra rows
# through — the join itself stays exact.
# --------------------------------------------------------------------------


_MIX_M1 = jnp.uint64(0xBF58476D1CE4E5B9)
_MIX_M2 = jnp.uint64(0x94D049BB133111EB)
_MIX_SEED = jnp.uint64(0x9E3779B97F4A7C15)


def _mix64(x: jnp.ndarray) -> jnp.ndarray:
    """splitmix64 finalizer — u64 arithmetic only (TPU-legal)."""
    x = (x ^ (x >> jnp.uint64(30))) * _MIX_M1
    x = (x ^ (x >> jnp.uint64(27))) * _MIX_M2
    return x ^ (x >> jnp.uint64(31))


def bloom_hash(key_u64s: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """One u64 hash per row over the sort_key_u64 forms of the key tuple.
    Deliberately independent of packing ranges: equal key tuples hash
    identically on every segment, unlike packed keys whose ranges are
    fragment-local."""
    h = jnp.broadcast_to(_MIX_SEED, key_u64s[0].shape)
    for u in key_u64s:
        h = _mix64(h ^ u)
    return h


def bloom_bits_pow2(bits: int) -> int:
    """Clamp a configured bitmap size to a power of two ≥ 64 (word math
    and the position mask rely on it)."""
    return 1 << max(6, int(bits - 1).bit_length())


def _bloom_positions(h: jnp.ndarray, bits: int, k: int) -> list:
    """k bit positions per row sliced from ONE 64-bit hash — disjoint
    slices while they fit, overlapping (still a valid bloom) beyond."""
    lb = max(bits.bit_length() - 1, 1)
    step = max((64 - lb) // max(k, 1), 1)
    mask = jnp.uint64(bits - 1)
    return [((h >> jnp.uint64(i * step)) & mask).astype(jnp.int32)
            for i in range(max(k, 1))]


def bloom_build(key_u64s: Sequence[jnp.ndarray], sel: jnp.ndarray,
                bits: int, k: int) -> jnp.ndarray:
    """(bits // 32,) uint32 bitmap over the SELECTED rows' key hashes.
    Built as a bool bitmap (scatter of ones — the bitmap is tiny) then
    packed to words for the wire; cross-segment combination is a bitwise
    OR of the words."""
    h = bloom_hash(key_u64s)
    bm = jnp.zeros((bits,), dtype=jnp.bool_)
    for pos in _bloom_positions(h, bits, k):
        idx = jnp.where(sel, pos, bits)
        bm = bm.at[idx].set(True, mode="drop")
    w = bm.reshape(bits // 32, 32).astype(jnp.uint32)
    return jnp.sum(w << jnp.arange(32, dtype=jnp.uint32), axis=1,
                   dtype=jnp.uint32)


def bloom_test(words: jnp.ndarray, key_u64s: Sequence[jnp.ndarray],
               bits: int, k: int) -> jnp.ndarray:
    """Per-row membership test against a packed bitmap: True = possibly
    present (false positives possible), False = definitely absent."""
    h = bloom_hash(key_u64s)
    ok = jnp.ones(h.shape, dtype=jnp.bool_)
    for pos in _bloom_positions(h, bits, k):
        w = words[pos >> 5]
        ok = ok & (((w >> (pos & 31).astype(jnp.uint32))
                    & jnp.uint32(1)) != 0)
    return ok


# --------------------------------------------------------------------------
# motion wire format: pack every column of a row set (plus the row-validity
# mask) into ONE (rows, W) uint32 buffer, so each motion costs exactly one
# collective instead of one per column. Restoration is bit-identical: 4-byte
# dtypes bitcast to a u32 word, 8-byte dtypes to two words (the TPU-legal
# formulation — a direct f64↔u64 bitcast does not compile there, u32 word
# pairs do; see sort_key_u64), and bool columns ride as BITS of the leading
# flag word(s) next to the validity bit, so a shuffle ships no dedicated
# bool buffers at all.
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class WireLayout:
    """Static description of one packed wire buffer. Word 0 bit 0 is the
    row-validity bit; bool columns occupy the following bits (spilling
    into additional flag words past 32 bools); wider columns get 1 or 2
    whole words each, in sorted-name order so any two sessions that agree
    on the column dict agree on the layout."""

    names: tuple          # all column names, layout order (bools first)
    dtypes: tuple         # jnp/np dtype per name
    flag_bits: dict       # bool column name -> (word, bit)
    offsets: dict         # non-bool column name -> first word index
    n_flag_words: int     # leading words carrying validity + bool bits
    width: int            # W: total uint32 words per row

    def row_bytes(self) -> int:
        return 4 * self.width

    def payload_bytes(self) -> int:
        """Bytes of actual column data per row (excludes flag-word
        padding) — the numerator of wire efficiency."""
        bits = 1  # validity
        total = 0
        for dt in self.dtypes:
            if np.dtype(dt) == np.bool_:
                bits += 1
            else:
                total += np.dtype(dt).itemsize
        return total + (bits + 7) // 8


# the packed wire's declared dtype contract (the int64/DECIMAL limb
# convention bitcasts whole u32 words): a motion may ship bool columns
# (flag bits) and columns of exactly these byte widths. The plan
# verifier (plan/verify.py motion-wire-dtype) checks every motion's
# schema against this BEFORE execution; wire_layout enforces it at
# lowering time.
WIRE_ITEMSIZES = (4, 8)


def wire_layout(col_dtypes: dict) -> WireLayout:
    """Layout for a column dict (name -> dtype). Deterministic: bools in
    sorted order take flag bits, then the remaining columns in sorted
    order take whole words."""
    bools = sorted(n for n, dt in col_dtypes.items()
                   if np.dtype(dt) == np.bool_)
    wides = sorted(n for n, dt in col_dtypes.items()
                   if np.dtype(dt) != np.bool_)
    n_flag_words = max(1, -(-(1 + len(bools)) // 32))
    flag_bits = {}
    for i, n in enumerate(bools):
        flag_bits[n] = ((1 + i) // 32, (1 + i) % 32)
    offsets = {}
    w = n_flag_words
    for n in wides:
        size = np.dtype(col_dtypes[n]).itemsize
        if size not in WIRE_ITEMSIZES:
            raise NotImplementedError(
                f"wire pack: column {n!r} has {size}-byte dtype "
                f"{col_dtypes[n]}; only 4/8-byte dtypes and bool ship")
        offsets[n] = w
        w += size // 4
    names = tuple(bools + wides)
    dtypes = tuple(col_dtypes[n] for n in names)
    return WireLayout(names, dtypes, flag_bits, offsets, n_flag_words, w)


def pack_wire(cols: Columns, sel: jnp.ndarray,
              layout: WireLayout) -> jnp.ndarray:
    """(rows, W) uint32 buffer carrying every column and the validity
    mask. An all-zero row unpacks as invalid — scattered send buffers
    need no separate initialization for unused slots."""
    rows = sel.shape[0]
    words: list = [None] * layout.width
    flags = [jnp.zeros((rows,), jnp.uint32)
             for _ in range(layout.n_flag_words)]
    flags[0] = sel.astype(jnp.uint32)
    for name, (w, bit) in layout.flag_bits.items():
        flags[w] = flags[w] | (cols[name].astype(jnp.uint32)
                               << jnp.uint32(bit))
    for i, f in enumerate(flags):
        words[i] = f
    for name, off in layout.offsets.items():
        c = cols[name]
        u = jax.lax.bitcast_convert_type(c, jnp.uint32)
        if u.ndim == c.ndim:        # 4-byte dtype: one word
            words[off] = u
        else:                       # 8-byte dtype: two words (lo, hi)
            words[off] = u[..., 0]
            words[off + 1] = u[..., 1]
    return jnp.stack(words, axis=-1)


def unpack_wire(buf: jnp.ndarray,
                layout: WireLayout) -> tuple[Columns, jnp.ndarray]:
    """Inverse of pack_wire: bit-identical columns + the validity mask."""
    sel = (buf[..., 0] & jnp.uint32(1)).astype(jnp.bool_)
    cols: Columns = {}
    for name, dt in zip(layout.names, layout.dtypes):
        if np.dtype(dt) == np.bool_:
            w, bit = layout.flag_bits[name]
            cols[name] = ((buf[..., w] >> jnp.uint32(bit))
                          & jnp.uint32(1)).astype(jnp.bool_)
            continue
        off = layout.offsets[name]
        if np.dtype(dt).itemsize == 4:
            cols[name] = jax.lax.bitcast_convert_type(buf[..., off], dt)
        else:
            pair = jnp.stack([buf[..., off], buf[..., off + 1]], axis=-1)
            cols[name] = jax.lax.bitcast_convert_type(pair, dt)
    return cols, sel


def wire_rebucket(rows: jnp.ndarray, key: jnp.ndarray,
                  valid: jnp.ndarray, n_buckets: int,
                  cap: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Permutation re-bucket of PACKED wire rows — the two-level
    motion's host-combine primitive (no unpack: rows move as opaque
    (W,) u32 word vectors).

    ``rows`` (n, W) are wire rows, ``key`` (n,) the integer bucket for
    each row, ``valid`` which rows carry data. Valid rows compact
    stably (by position) into their bucket's slots; all-zero fill
    (which unpacks as invalid by the wire convention) pads the rest.
    Returns ((n_buckets, cap, W) buffer, (n_buckets,) int32 demand) —
    rows past ``cap`` are DROPPED FROM THE BUFFER but counted, so the
    caller's overflow check (demand > cap) fires before any result
    could ship; the capacity-ladder retry then promotes the rung.
    Same slot discipline as the redistribute lowering (``bucket_slots``)."""
    k = jnp.where(valid, key, n_buckets)
    counts = jax.ops.segment_sum(valid.astype(jnp.int32), k,
                                 num_segments=n_buckets + 1)[:n_buckets]
    src, filled = bucket_slots(bucket_argsort(k, n_buckets), counts, cap)
    out = jnp.where(filled[:, None], rows[src],
                    jnp.zeros((), dtype=rows.dtype))
    return out.reshape(n_buckets, cap, rows.shape[1]), counts


def rung_up(n: int) -> int:
    """Round a bucket capacity up to its ladder rung (the next power of
    two, floor 8): rungs quantize motion buffer shapes so the set of
    compiled executables per motion is small and bounded — ≤ log2 of the
    worst-case/seed ratio — and skew promotion always lands on a cached
    shape instead of an arbitrary new one."""
    n = max(int(n), 8)
    return 1 << (n - 1).bit_length()


def row_rung_up(n: int) -> int:
    """Round a scan's row capacity up to its rung: the next multiple of
    a 64th of the power of two above it (at most 3.1 % of padding, none
    up to 64 rows). Every shape of a program follows from its scans'
    capacities, and the persistent compile cache keys on the shapes: a
    table reloaded with a few thousand rows more or fewer (a new
    ``--seed`` of a benchmark, a day's appends) then meets the program
    the last process compiled instead of a multi-minute compile. One
    ladder for a one-segment scan (its table's rows, or those of the
    micro-partitions it reads: ``plan/binder.py Binder._scan``,
    ``plan/scanprune.py``) and for a shard (``Session.shard_capacity``);
    the row count itself reaches the program as data."""
    n = max(int(n), 1)
    step = 1 << max((n - 1).bit_length() - 6, 0)
    return -(-n // step) * step


def pad_rows(arr: np.ndarray, capacity: int) -> np.ndarray:
    """A scanned host column (or ``$nn:`` mask) at its scan's capacity,
    padded before it crosses to the device: zeros past the table's rows,
    which ``sel = arange(capacity) < rows`` never selects."""
    short = capacity - arr.shape[0]
    if short <= 0:
        return arr
    return np.concatenate([arr, np.zeros((short,), dtype=arr.dtype)])


def pad_to_rung(arr: np.ndarray) -> np.ndarray:
    """A host array padded to the rung above its length: a dictionary
    lookup table, whose length follows from the data, rides into a
    program at a length on the ladder."""
    return pad_rows(arr, row_rung_up(len(arr)))


# --------------------------------------------------------------------------
# misc
# --------------------------------------------------------------------------


def limit_mask(sel: jnp.ndarray, k: int, offset: int = 0) -> jnp.ndarray:
    """Keep rows offset..offset+k of the SELECTED sequence (post-sort)."""
    rank = prefix_sum(sel.astype(jnp.int64)) - 1
    return sel & (rank >= offset) & (rank < offset + k)


def compact(
    cols: Columns, sel: jnp.ndarray, capacity: int
) -> tuple[Columns, jnp.ndarray, jnp.ndarray]:
    """Stable-compact selected rows to the front at a (possibly smaller)
    capacity — used before motions to shrink shuffle width (the TupleSplit /
    multi-stage-agg motivation, SURVEY.md §2.2).

    Also returns the TRUE selected-row count; the executor must check it
    against ``capacity`` post-run — rows beyond capacity are truncated, which
    is an error to surface, never silence."""
    n_selected = jnp.sum(sel.astype(jnp.int64))
    idx = flagged_first(sel)[:capacity]
    out = {n: c[idx] for n, c in cols.items()}
    return out, sel[idx], n_selected


def compact_sparse(
    cols: Columns, sel: jnp.ndarray, capacity: int
) -> tuple[Columns, jnp.ndarray, jnp.ndarray]:
    """``compact`` for a selection that is sparse in its input: the
    selected rows, in order, at ``capacity`` rows, the mask of the slots
    they fill and their TRUE count (the caller checks it against
    ``capacity``: rows past it are cut). ``compact`` sorts a word over
    the whole input, the cost a sparse selection is compacted to save.
    Here the mask is packed 32 rows a word, and a word's selected rows
    fill the slots from its first, the running count of set bits before
    it. Each non-empty word's rank and bits are scattered to its first
    slot, and running maxima over the slots give every slot the latest
    word to start at or before it: the word that holds it. That costs
    n / 32 updates plus a scan of ``capacity`` slots (seven shifted
    steps in blocks of 128, then the blocks'), however few rows come.
    It replaced a binary search a slot through the running count,
    ``capacity`` × log2(n / 32) dependent probes whatever the rows: 6.6
    ns a probe and step with that table in fast memory, 22 ns through a
    running count of every row in HBM (TPU v5e, TPC-H SF1), the reason
    the count is kept a word and not a row. From 6,029,312 rows to
    1,048,576 slots that search took 220 ms on a v5e, this form 29 ms,
    27 of them the column gathers. The slot's row is the word's k-th set
    bit, found by five halvings on popcounts."""
    n = sel.shape[0]
    bits = jnp.pad(sel, (0, -n % 32)).reshape(-1, 32).astype(jnp.uint32)
    words = (bits << jax.lax.iota(jnp.uint32, 32)).sum(
        axis=1, dtype=jnp.uint32)
    per_word = jax.lax.population_count(words).astype(jnp.int32)
    upto = prefix_sum(per_word)
    n_selected = upto[-1]
    # a non-empty word's first slot is the count before it, unique among
    # such words; an empty word's lies past every slot and every start,
    # and drops, as does a start at or past the capacity
    wi = jax.lax.iota(jnp.int32, words.shape[0])
    first = jnp.where(per_word > 0, upto - per_word,
                      max(capacity, n + 1) + wi)
    held = jnp.zeros(capacity, jnp.int32).at[first].set(
        wi + 1, mode="drop", unique_indices=True)
    held_bits = jnp.zeros(capacity, jnp.uint32).at[first].set(
        words, mode="drop", unique_indices=True)
    # the word of each slot, its first slot and its bits are those of the
    # latest word started at or before it: running maxima of the word's
    # rank and of (first slot, bits) packed in one key, no gather
    j = jax.lax.iota(jnp.int32, capacity)
    w = running_max(held) - 1
    latest = running_max(jnp.where(
        held > 0, (j.astype(jnp.uint64) << jnp.uint64(32))
        | held_bits.astype(jnp.uint64), jnp.uint64(0)))
    k = j + 1 - (latest >> jnp.uint64(32)).astype(jnp.int32)
    word = (latest & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    pos = jnp.zeros_like(w).astype(jnp.uint32)
    for width in (16, 8, 4, 2, 1):
        low = jax.lax.population_count(
            (word >> pos) & jnp.uint32((1 << width) - 1)).astype(jnp.int32)
        high = k > low
        k = jnp.where(high, k - low, k)
        pos = jnp.where(high, pos + jnp.uint32(width), pos)
    idx = jnp.clip(w * 32 + pos.astype(jnp.int32), 0, n - 1)
    out = {name: c[idx] for name, c in cols.items()}
    return out, j < n_selected, n_selected
