"""Pallas TPU kernels for the three hottest executor ops.

1. Grouped aggregation over a scan (Q1's shape: 6M rows → 6 cells ×
~8 aggregates). The XLA formulation (exec/kernels.group_aggregate_dense)
is a chain of masked reductions; ``dense_agg_tiles_pallas`` fuses the
whole thing into ONE pass over HBM:

  per row-tile:
      onehot = (gid == cell_ids) & sel          # (cells, TILE) in VMEM
      counts = sum(onehot, axis=1)
      sums   = values @ onehot.T                # (K, cells) on the MXU

Each grid step writes ITS OWN partial block (n_tiles, K+1, cells); the
caller combines per-tile partials outside the kernel. That split is what
makes int64-cent money sums EXACT through the f32 MXU: the caller splits
each int64 column into five 13-bit limbs (``int64_to_agg_limbs``). Every
per-tile dot-product partial sum is then an integer below
TILE × 2^13 = 2^24, which f32 represents exactly regardless of the MXU's
accumulation order — so each tile's limb sums are exact integers, the
cross-tile combine runs in int64, and carry propagation between limbs
happens once at the end (``agg_limbs_to_int64``). SUM/AVG over DECIMAL
(int64 cents) and BIGINT therefore reproduce the XLA path bit for bit;
float sums ride a single f32 row (approximate, as before).

1b. Mid-cardinality grouped aggregation (``sorted_segment_aggregate``):
between the tiny static cell domain above and the generic XLA sort path
there was no fused kernel. This one reuses ``kernels.sort_indices`` to
order rows by key, then streams tiles through ``_sorted_seg_kernel``: a
carried (last-gid, partial-accumulator) pair lives in SMEM, each tile
runs one segmented Hillis–Steele scan on the VPU, and a completed
group's total is flushed at the row where the NEXT group begins. Sums
accumulate in int32 over 8-bit limbs (group totals stay below 2^31 for
up to 2^23 rows), so int64/DECIMAL sums are exact here too. Group count
is bounded only by the agg capacity — far beyond any one-hot domain.

2. Probe-side join against a SMALL unique build (the nodeHash.c probe
loop's role; every dim join in TPC-H's star shapes). The XLA
formulation sorts the build and binary-searches every probe key;
``probe_join_pallas`` instead streams probe tiles once and, per tile,
compare-alls the (whole, VMEM-resident) build keys on the VPU and
gathers the payload with ONE one-hot matmul on the MXU:

      eq = (bkeys[:, None] == pkeys[None, :]) & bsel & psel  # (B, TILE)
      matched = sum(eq, axis=0)            # 0/1 (unique build); >1 = dup
      gathered = payload @ eq              # (P, TILE) on the MXU

Payload transport is EXACT for integers: the caller splits each int64
column into three 21/21/22-bit limbs, each an integer ≤ 2^22 that f32
represents exactly; a matched row gathers exactly one source, so limb
recombination reproduces the original bits (two's complement via the
uint64 round trip). That is the TPU-native answer to "hash-join gather"
— no scatter, no pointer chase, the MXU does the routing.

All kernels are gated by ``config.exec.use_pallas`` (wired through
Lowerer), default off: as of PR 22 the TPU compiler refuses all three
(docs/PALLAS_AB.md has what was asked and what it said; the refusals are
pinned in tests/test_tpu_compile.py), so they have only ever run with
``interpret=True``. ROADMAP D7 decides whether each is repaired or deleted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _dense_agg_kernel(gid_ref, vals_ref, sel_ref, out_ref, *, n_cells: int):
    g = gid_ref[:]                       # (TILE,)
    s = sel_ref[:]                       # (TILE,)
    v = vals_ref[:]                      # (K, TILE)
    cells = jax.lax.broadcasted_iota(jnp.int32, (n_cells, g.shape[0]), 0)
    onehot = (g[None, :] == cells) & s[None, :]          # (cells, TILE)
    oh_f = onehot.astype(jnp.float32)
    counts = jnp.sum(oh_f, axis=1)                       # (cells,)
    sums = jnp.dot(v, oh_f.T,
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)  # (K, cells) on MXU
    out_ref[0, 0, :] = counts
    out_ref[0, 1:, :] = sums


@functools.partial(jax.jit, static_argnames=("n_cells", "tile", "interpret"))
def dense_agg_tiles_pallas(gid: jnp.ndarray, vals: jnp.ndarray,
                           sel: jnp.ndarray, n_cells: int, tile: int = 2048,
                           interpret: bool = False):
    """Fused one-pass grouped sum+count, PER-TILE partials.

    gid: int32[N] cell per row; vals: float32[K, N]; sel: bool[N].
    Returns f32[n_tiles, K+1, cells] — row 0 of each tile block is the
    tile's counts, rows 1.. its sums. N must be a multiple of ``tile``
    (caller pads; sel masks padding). Each per-tile partial is a sum of
    at most ``tile`` values; with limb-encoded inputs (< 2^13) every
    partial stays below 2^24 and the f32 transport is exact — the caller
    combines tiles in int64 (``agg_limbs_to_int64``)."""
    k, n = vals.shape
    assert n % tile == 0, "pad rows to a tile multiple"
    grid = (n // tile,)
    return pl.pallas_call(
        functools.partial(_dense_agg_kernel, n_cells=n_cells),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile,), lambda i: (i,)),
            pl.BlockSpec((k, tile), lambda i: (0, i)),
            pl.BlockSpec((tile,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((1, k + 1, n_cells), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n // tile, k + 1, n_cells),
                                       jnp.float32),
        interpret=interpret,
    )(gid, vals, sel)


def dense_agg_pallas(gid: jnp.ndarray, vals: jnp.ndarray, sel: jnp.ndarray,
                     n_cells: int, tile: int = 2048,
                     interpret: bool = False):
    """Fused grouped sum+count for a small static cell domain.

    Returns (counts f32[cells], sums f32[K, cells]); the per-tile
    partials of ``dense_agg_tiles_pallas`` combined in f32 — the
    float-valued convenience wrapper (exact integer sums go through the
    limb path in the executor instead)."""
    out = jnp.sum(dense_agg_tiles_pallas(gid, vals, sel, n_cells,
                                         tile=tile, interpret=interpret),
                  axis=0)
    return out[0], out[1:]

def _probe_join_kernel(bkeys_ref, bsel_ref, pkeys_ref, psel_ref, pay_ref,
                       out_ref):
    bk = bkeys_ref[:]                       # (B,)
    bs = bsel_ref[:]                        # (B,)
    pk = pkeys_ref[:]                       # (TILE,)
    ps = psel_ref[:]                        # (TILE,)
    pay = pay_ref[:]                        # (P, B)
    eq = (bk[:, None] == pk[None, :]) & bs[:, None] & ps[None, :]
    eqf = eq.astype(jnp.float32)            # (B, TILE)
    matched = jnp.sum(eqf, axis=0)          # 0/1; >1 flags a dup build
    # HIGHEST precision is REQUIRED for exactness: default MXU matmul
    # decomposes f32 into bf16 passes, which would truncate 21/22-bit
    # limbs before the gather
    gathered = jnp.dot(pay, eqf,
                       preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)  # MXU
    out_ref[0, :] = matched
    out_ref[1:, :] = gathered


@functools.partial(jax.jit,
                   static_argnames=("tile", "interpret"))
def probe_join_pallas(bkeys: jnp.ndarray, bsel: jnp.ndarray,
                      pkeys: jnp.ndarray, psel: jnp.ndarray,
                      payload: jnp.ndarray, tile: int = 1024,
                      interpret: bool = False):
    """Fused probe join against a small unique build.

    bkeys: u32[B] packed build keys (B caller-padded; bsel masks pads);
    pkeys: u32[N] packed probe keys (N a multiple of ``tile``);
    payload: f32[P, B] limb-encoded build payload.
    Returns (match f32[N] — 0/1, >1 ⇒ duplicate build keys;
    gathered f32[P, N])."""
    p, b = payload.shape
    n = pkeys.shape[0]
    assert n % tile == 0, "pad probe rows to a tile multiple"
    grid = (n // tile,)
    out = pl.pallas_call(
        _probe_join_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((b,), lambda i: (0,)),
            pl.BlockSpec((b,), lambda i: (0,)),
            pl.BlockSpec((tile,), lambda i: (i,)),
            pl.BlockSpec((tile,), lambda i: (i,)),
            pl.BlockSpec((p, b), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((p + 1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((p + 1, n), jnp.float32),
        interpret=interpret,
    )(bkeys, bsel, pkeys, psel, payload)
    return out[0], out[1:]


# 21/21/22-bit limb split: every limb is an integer < 2^22, exactly
# representable in float32 — the one-hot matmul then transports int64
# payloads losslessly (exactly one source row per matched column).
_LIMB_BITS = (21, 21, 22)
_LIMB_SHIFTS = (0, 21, 42)


def int64_to_limbs(col: jnp.ndarray) -> list:
    """int64 → three f32 limb rows (two's complement via uint64)."""
    u = col.astype(jnp.int64).view(jnp.uint64)
    out = []
    for bits, shift in zip(_LIMB_BITS, _LIMB_SHIFTS):
        mask = jnp.uint64((1 << bits) - 1)
        out.append(((u >> jnp.uint64(shift)) & mask).astype(jnp.float32))
    return out


def limbs_to_int64(l0: jnp.ndarray, l1: jnp.ndarray,
                   l2: jnp.ndarray) -> jnp.ndarray:
    """Inverse of int64_to_limbs (rounding to nearest undoes the f32
    transport exactly because every limb is an integer < 2^24)."""
    u = (jnp.round(l2).astype(jnp.uint64) << jnp.uint64(42)) \
        | (jnp.round(l1).astype(jnp.uint64) << jnp.uint64(21)) \
        | jnp.round(l0).astype(jnp.uint64)
    return u.view(jnp.int64)


# --------------------------------------------------------------------------
# Aggregation limb schemes. The probe join's 21/21/22 split transports ONE
# value per matched row; aggregation SUMS limbs, so the width must leave
# headroom for the accumulation:
#
# - dense (MXU, f32): 5×13-bit limbs. A per-tile dot-product partial sum is
#   ≤ TILE(2048) × (2^13−1) < 2^24, so every f32 add in the MXU reduction
#   is exact; tiles combine in int64 outside the kernel.
# - sorted-segment (VPU, int32): 8×8-bit limbs. A group total is
#   ≤ 2^23 rows × (2^8−1) < 2^31, so int32 never overflows for streams up
#   to MAX_SEG_ROWS; limbs recombine in uint64 with two's-complement wrap,
#   exactly like the probe join's scheme.
# --------------------------------------------------------------------------

AGG_LIMB_BITS = (13, 13, 13, 13, 12)
SEG_LIMB_BITS = (8,) * 8
MAX_SEG_ROWS = 1 << 23  # 2^23 × (2^8−1) < 2^31: int32 accumulator proof


def _limb_shifts(bits):
    shifts, acc = [], 0
    for b in bits:
        shifts.append(acc)
        acc += b
    return shifts


def _split_limbs(col: jnp.ndarray, bits, dtype) -> list:
    """int64 → limb rows of ``bits`` widths in ``dtype`` (two's
    complement via uint64 — the recombine side is limb_sums_to_int64)."""
    u = col.astype(jnp.int64).view(jnp.uint64)
    out = []
    for b, sh in zip(bits, _limb_shifts(bits)):
        mask = jnp.uint64((1 << b) - 1)
        out.append(((u >> jnp.uint64(sh)) & mask).astype(dtype))
    return out


def int64_to_agg_limbs(col: jnp.ndarray) -> list:
    """int64 → five f32 13-bit limb rows (the dense MXU scheme)."""
    return _split_limbs(col, AGG_LIMB_BITS, jnp.float32)


def int64_to_seg_limbs(col: jnp.ndarray) -> list:
    """int64 → eight int32 8-bit limb rows (the sorted-segment scheme)."""
    return _split_limbs(col, SEG_LIMB_BITS, jnp.int32)


def limb_sums_to_int64(totals, bits) -> jnp.ndarray:
    """Recombine per-limb int64 SUM totals into the exact int64 sum.

    Each total is Σ rows of one limb — nonnegative, far below 2^63. The
    recombination Σ_l total_l << shift_l runs mod 2^64 (uint64), which
    equals the true int64 sum mod 2^64 — i.e. exactly the same value
    (and the same wraparound behavior) int64 addition produces."""
    u = jnp.zeros_like(totals[0], dtype=jnp.uint64)
    for t, sh in zip(totals, _limb_shifts(bits)):
        u = u + (t.astype(jnp.uint64) << jnp.uint64(sh))
    return u.view(jnp.int64)


def agg_limbs_to_int64(totals) -> jnp.ndarray:
    return limb_sums_to_int64(totals, AGG_LIMB_BITS)


# --------------------------------------------------------------------------
# Sorted-segment grouped aggregation (mid-cardinality): rows arrive sorted
# by group id; each tile runs one segmented scan with a carried
# (last-gid, partial-accumulator) pair in SMEM, flushing a group's total
# at the row where the NEXT group starts.
# --------------------------------------------------------------------------

_SEG_SENTINEL = 2147483647  # int32 max: gid of unselected / padded rows


def _shift1(x, d: int):
    """Shift right by ``d`` along the last axis, zero-filling — pad+slice
    (no wraparound gather), which lowers to cheap lane shifts on TPU."""
    widths = [(0, 0)] * (x.ndim - 1) + [(d, 0)]
    return jnp.pad(x, widths)[..., :x.shape[-1]]


def _sorted_seg_kernel(gid_ref, vals_ref, out_ref, carry_ref, lastg_ref):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        carry_ref[:] = jnp.zeros_like(carry_ref)
        lastg_ref[0] = jnp.int32(-1)

    g = gid_ref[:]                           # (T,) int32, nondecreasing
    v = vals_ref[:]                          # (R, T) int32, masked rows = 0
    t = g.shape[0]
    pos = jax.lax.broadcasted_iota(jnp.int32, (t, 1), 0)[:, 0]
    first = pos == 0
    gprev = jnp.where(first, lastg_ref[0], _shift1(g, 1))
    nb = g != gprev                          # segment-start flags (T,)
    carry = carry_ref[:]                     # (R, 1) running group partial

    # segmented inclusive scan (Hillis–Steele, log2 T static steps):
    # acc[r, j] = sum of v[r] over the current group's rows within this
    # tile, seeded with the carried partial when the first group continues
    # from the previous tile.
    acc = v + jnp.where((first & ~nb[0])[None, :], carry, 0)
    flg = nb
    d = 1
    while d < t:
        flg_s = jnp.pad(flg, (d, 0), constant_values=True)[:t]
        acc = acc + jnp.where(flg[None, :], 0, _shift1(acc, d))
        flg = flg | flg_s
        d *= 2

    # flush: at a segment start, emit the PREVIOUS group's completed
    # total (its running sum at the row before — the carry itself when
    # the boundary is the tile's first row).
    prev_acc = jnp.where(first[None, :], carry, _shift1(acc, 1))
    out_ref[:] = jnp.where(nb[None, :], prev_acc, 0)

    carry_ref[:] = acc[:, t - 1:t]
    lastg_ref[0] = g[t - 1]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def sorted_seg_pallas(gid: jnp.ndarray, vals: jnp.ndarray,
                      tile: int = 2048, interpret: bool = False):
    """Tile-streamed segmented sum over SORTED group ids.

    gid: int32[N] nondecreasing (unselected/pad rows = sentinel);
    vals: int32[R, N] with masked rows zeroed. Returns flush int32[R, N]:
    column j holds the completed total of the group ENDING at row j-1
    wherever gid[j] != gid[j-1], else 0. The caller guarantees at least
    one trailing sentinel row so the last real group flushes."""
    r, n = vals.shape
    assert n % tile == 0, "pad rows to a tile multiple"
    grid = (n // tile,)
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        _sorted_seg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile,), lambda i: (i,)),
            pl.BlockSpec((r, tile), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((r, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((r, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((r, 1), jnp.int32),
                        pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
    )(gid, vals)


def sorted_segment_eligible(aggs, agg_values, n_rows: int) -> bool:
    """Shape/dtype gate for the fused sorted-segment path: SUM/AVG over
    integer-carried values (BIGINT, DECIMAL cents, INT) plus COUNT, at
    most MAX_SEG_ROWS input rows (the int32-accumulator proof). MIN/MAX,
    BOOL and float sums keep the XLA path."""
    if n_rows > MAX_SEG_ROWS:
        return False
    for spec in aggs:
        if spec.func == "count":
            continue
        if spec.func not in ("sum", "avg"):
            return False
        v = agg_values.get(spec.out_name)
        if v is None or not jnp.issubdtype(v.dtype, jnp.integer):
            return False
    return True


def sorted_segment_aggregate(key_cols, agg_values, aggs, sel,
                             out_capacity: int, tile: int = 2048,
                             interpret: bool = False):
    """Drop-in for kernels.group_aggregate on an eligible agg: same sort
    and boundary discipline, but ALL accumulations run in one fused
    Pallas pass (count row + 8-bit limb rows per sum, int32 exact).

    Returns (out_key_cols, out_agg_cols, out_sel, n_groups) with the
    XLA path's exact contract: groups in ascending key order, int sums
    bit-identical, avg the same f64 division of the same exact ints."""
    from cloudberry_tpu.exec import kernels as K

    # the sort/boundary/compaction scaffolding is SHARED with the XLA
    # path (kernels.group_layout) — the two aggregations must stay
    # bit-identical by contract, so the grouping rules live once
    lay = K.group_layout(key_cols, sel, out_capacity)
    gid = jnp.where(lay.s_sel,
                    jnp.cumsum(lay.new_grp.astype(jnp.int32)) - 1,
                    _SEG_SENTINEL)

    # value rows: count first, then 8 limb rows per sum/avg argument
    rows = [lay.s_sel.astype(jnp.int32)]
    layout = []  # (spec, first limb row, arg dtype)
    for spec in aggs:
        if spec.func == "count":
            continue
        v = agg_values[spec.out_name][lay.perm]
        v = jnp.where(lay.s_sel, v, jnp.zeros((), dtype=v.dtype))
        layout.append((spec, len(rows), v.dtype))
        rows.extend(int64_to_seg_limbs(v))
    vals = jnp.stack(rows)

    # pad to a tile multiple PLUS one whole sentinel tile: the boundary
    # at the first sentinel row flushes the last real group.
    pad = (-gid.shape[0]) % tile + tile
    gid_p = jnp.concatenate(
        [gid, jnp.full((pad,), _SEG_SENTINEL, jnp.int32)])
    vals_p = jnp.pad(vals, ((0, 0), (0, pad)))
    flush = sorted_seg_pallas(gid_p, vals_p, tile=tile,
                              interpret=interpret)

    # a group's total flushes at the row where the NEXT group begins —
    # lay.ends + 1, which for the last group is n_sel: always a real
    # position thanks to the sentinel tile
    n_groups, valid = lay.n_groups, lay.valid
    flushpos = jnp.where(valid, lay.ends + 1, 0)
    out_keys = lay.out_keys

    fg = flush[:, flushpos]  # (R, out_capacity) int32
    counts = jnp.where(valid, fg[0].astype(jnp.int64), 0)
    out_aggs = {}
    for spec, row0, dt in layout:
        totals = [fg[row0 + i].astype(jnp.int64)
                  for i in range(len(SEG_LIMB_BITS))]
        ssum = jnp.where(valid, limb_sums_to_int64(totals, SEG_LIMB_BITS),
                         0)
        if spec.func == "avg":
            out_aggs[spec.out_name] = ssum.astype(jnp.float64) \
                / jnp.maximum(counts, 1)
        else:
            out_aggs[spec.out_name] = ssum.astype(dt)
    for spec in aggs:
        if spec.func == "count":
            out_aggs[spec.out_name] = counts

    out_sel = jnp.arange(out_capacity) < n_groups
    return out_keys, out_aggs, out_sel, n_groups
