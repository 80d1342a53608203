"""Tiled (out-of-core) execution — the workfile-manager / spill analog.

When the admission estimator (exec/resource.py) rejects a plan,
``plan_tiled`` re-plans it as a stream of fixed-shape tiles of its
probe-side scan (the rewrite and its modes: exec/tiled_plan.py) and hands
the shape to one executable per mode — aggregate, top-N, external sort,
window — each run as

- a prelude program (once): every spine join's build computed, its
  results resident; a join whose build streams into a direct-address
  table (``TileShape.streams``) has its table built first, by a stream of
  its own (``build-stream``);
- a step program per tile: the spine over one tile, merged into the
  carried accumulator (aggregate, top-N) or emitted with its sort keys to
  host-RAM runs (sort, window);
- a finalize program, or the host merge pass and window chunks.

The executables are written once over a SEGMENT LAYOUT, a value passed in
(``_OneSegment`` or ``_Mesh``), which owns what differs between one
segment and the segment mesh: how a traced function becomes a program,
the lowerer, the per-segment view in and out, the checks, the motion
statistics and the skew sentinel, the accumulator's leading axis, the
tile feed, the host rows of the answer and the layout's report fields.

Per-tile capacities keep the engine's checked-overflow discipline: a tile
that overflows its expansion-join or group buffers raises, never truncates
— the adaptive loop grows the guilty buffer or halves the tile and runs
again (nodeHash.c's increase-nbatch-and-rescan).
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from cloudberry_tpu.columnar.batch import ColumnBatch
from cloudberry_tpu.exec import bufferpool as BUF
from cloudberry_tpu.exec import executor as X
from cloudberry_tpu.exec import kernels as K
from cloudberry_tpu.exec import recovery as R
from cloudberry_tpu.exec import scanpipe as SP
from cloudberry_tpu.exec import tiled_plan as TS
from cloudberry_tpu.exec import tilepipe as TP
from cloudberry_tpu.exec.dist_executor import (_local_row, _shard_map,
                                               dist_lowering,
                                               prepare_dist_inputs)
from cloudberry_tpu.exec.joinindex import (restore_join_index,
                                           stash_join_index,
                                           strip_join_index)
from cloudberry_tpu.lifecycle import check_cancel, current_handle
from cloudberry_tpu.obs import programs as PG
from cloudberry_tpu.obs import trace as OT
from cloudberry_tpu.obs.progress import TileTracker, stream_rows
from cloudberry_tpu.parallel.mesh import SEG_AXIS
from cloudberry_tpu.parallel.topology import topology_token
from cloudberry_tpu.plan import nodes as N
from cloudberry_tpu.plan.pointlookup import unbind_point_lookups
from cloudberry_tpu.utils.faultinject import fault_point

# The declared set of tiled executor modes that snapshot carried state
# into the recovery store (TileShape.mode values whose tick() paths
# checkpoint). exec/recovery.py REPLACEABLE must cover every entry —
# the plan verifier (plan/verify.py recovery-mode-unreplaceable) and
# graftlint's planprops pass pin the two tables together BOTH ways, so
# a new checkpointing mode cannot ship without a degraded-mesh
# re-placement rule.
CHECKPOINT_MODES = ("agg", "topn", "sort", "window")


def plan_tiled(plan: N.PlanNode, session) -> Optional["TiledExecutable"]:
    """Try to re-plan an admission-rejected statement for tiled execution:
    on one segment, or on the segment mesh where the plan is distributed
    (n_segments > 1). Returns None when the plan shape or the budget
    cannot support it, and notes why on the plan (``declined``)."""
    plan._tile_declined = None
    if not session.config.resource.enable_spill:
        return _decline(plan, "spill is off (resource.enable_spill)")
    if getattr(plan, "_direct_segment", None) is not None:
        return _decline(plan, "a statement dispatched to one segment")
    mesh = session.config.n_segments > 1
    if mesh:
        shape = TS.analyze_mesh(plan, session)
    else:
        # the tile stream and resident loads key inputs by TABLE NAME: a
        # point-sliced scan would miss its $pt input — restore full scans
        unbind_point_lookups(plan)
        shape = TS.analyze(plan)
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
    # join-index inputs are a one-shot-executor feature: tiled prelude/
    # step programs assemble their own input dicts, so drop the
    # annotations — joins then compute their argsort in-program
    # (exec/joinindex.py documents the fallback contract). The strip is
    # speculative: a decline below restores the stash so the one-shot
    # fallback keeps its cached indexes.
    stash = stash_join_index(plan)
    strip_join_index(plan)
    tile_rows = TS.fit_mesh(shape, session) if mesh \
        else TS.fit(shape, session)
    if tile_rows is None:
        restore_join_index(stash)
        return _decline(plan, shape.decline or "no streamable shape")
    layout = _Mesh(session.config.n_segments) if mesh else _OneSegment()
    return _EXECUTABLES[shape.mode](
        shape, session, tile_rows, session.config.resource.query_mem_bytes,
        layout)


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
    """The external sort's merge pass: one stable host key sort over the
    pooled runs (np.lexsort: LAST key is primary — mirror sort_indices).
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


# --------------------------------------------------------------- execution


class _TileTimer:
    """Per-tile step timing: each step's
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


class SkewSentinel:
    """Mid-statement adaptive-replan watcher for tiled runs on the mesh.

    The mesh's step program already psums every redistribute's
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


# --------------------------------------------------------- segment layouts


def _identity(tree):
    return tree


class _OneSegment:
    """The segment layout of one segment: each program is one jit over
    the whole tile; no motion runs inside a step, so a step has no
    motion statistics to return and the run no skew to watch; the tile
    feed stages its tiles on the device (exec/scanpipe.py)."""

    nseg = 1
    dist = False
    prefix = "tiled"          # of every program's label
    adjective = ""            # of the budget error's executor name
    # the traced functions' per-segment view in and out, and their checks
    local = seg = checks = staticmethod(_identity)

    def __init__(self):
        self.platform = jax.default_backend()

    def compiler(self, exe):
        """(lowerer constructor, jit) of one compile. The jit takes a
        traced function, its nodes' titles, what program it is, and —
        read by the mesh only — its inputs' and outputs' placements (R:
        the resident tables, s: per segment, r: replicated) and whether
        it donates its carried state."""
        def jit(fn, titles, what, ins="", outs="", donate=False):
            return PG.jit(fn, titles, f"{self.prefix} {what}",
                          donate_argnums=TP.step_donation(self.platform)
                          if donate else ())
        return functools.partial(X.Lowerer, platform=self.platform), jit

    def local_n(self, tile_n):
        return tile_n

    def stats(self, low, shape) -> tuple:
        return ()

    # ------------------------------------------------------------ host

    def tile_n(self, n):
        return jnp.asarray(n, dtype=jnp.int32)

    host = staticmethod(_identity)

    @staticmethod
    def by_segment(a):
        return a[None]

    def fault_step(self) -> None:
        fault_point("tile_step")

    def acc(self, n: int, dtype, fill=0):
        return jnp.full((n,), fill, dtype=dtype)

    def retile(self, shape, tile_rows: int) -> None:
        TS.retile(shape, tile_rows)

    def prepare(self, exe):
        """The run's recovery context (exec/recovery.py): resume from the
        last K-tile checkpoint instead of replaying the whole stream."""
        return R.begin(exe, dist=False)

    def resident(self, exe, bs=None) -> dict:
        """All step inputs except the tile: whole (non-stream) tables and
        pruned store reads — exactly prepare_inputs minus the stream. The
        probe phase reads no scan of a streamed build; a build stream
        ``bs`` reads its build's, but the one it streams."""
        if bs is None:
            away = {id(s) for b in exe.shape.streams
                    for s in X.scans_of(b.join.build)}
            top, stream = exe.shape.partial_plan, exe.shape.stream
        else:
            away, top, stream = set(), bs.join.build, bs.shape.stream
        scans = [s for s in X.scans_of(top)
                 if s is not stream and id(s) not in away]
        store_scans = [s for s in scans if hasattr(s, "_store_parts")]
        names = sorted({s.table_name for s in scans
                        if not hasattr(s, "_store_parts")})
        return X._assemble_inputs(names, store_scans, exe.session, None)

    def feed(self, exe, ctx, window: int):
        """(tile feed, progress tracker) of a run: one lane, the
        remaining row prefix of the deterministic stream (single-segment
        consumption is always a prefix, so a resume skips it)."""
        skip = ctx.skip_rows if ctx is not None else 0
        n_base = ctx.tiles_base if ctx is not None else 0
        total = stream_rows(exe.shape.stream, exe.session)
        tracker = TileTracker(max(total - skip, 0), exe.tile_rows,
                              n_base=n_base, base_rows=min(skip, total),
                              rows_total=total)
        return _tile_feed(exe.shape.stream, exe.session, exe.tile_rows,
                          skip_rows=skip, min_depth=window), tracker

    def empty_tile(self, scan: N.PScan, tile_rows: int):
        return _empty_tile(scan, (tile_rows,)), 0

    def sentinel(self, exe, ctx):
        return None

    def report(self, exe) -> dict:
        return {}


class _Mesh:
    """The segment layout of the segment mesh (n_segments > 1). The
    reference spills operator state per segment process while Motion
    keeps flowing between slices; here the probe-side stream is tiled
    PER SEGMENT and each program is one shard_map over the segment mesh:

    - the prelude computes every spine build — its own motions
      (broadcasts, build-side redistributes) included — by one SPMD
      program, whose per-segment results stay resident;
    - each step feeds tile t of every segment's shard in lock-step (a
      segment whose shard ran dry sends masked rows, the SPMD analog of
      a QE's EOS) and runs the spine's redistributes per tile as
      collectives; its checks psum, so every host reads them, and it
      returns each redistribute's row counts to the skew sentinel;
    - the accumulators carry a leading segment axis, and the finalize
      program's merge motion is the original plan's.

    Peak device memory per segment is the admitted estimate: resident
    builds + one tile's working set (including its post-motion receive
    buffers) + the accumulator — independent of the streamed table's
    size, which is bounded by host RAM alone."""

    dist = True
    prefix = "tiled dist"
    adjective = "distributed "

    def __init__(self, nseg: int):
        self.nseg = nseg
        self.platform = jax.default_backend()

    def compiler(self, exe):
        # every program's motions lower as the in-memory mesh path's do
        mesh, lowerer = dist_lowering(exe.session)
        _, res_specs = prepare_dist_inputs(None, exe.session,
                                           names=self._names(exe))
        specs = {"R": res_specs, "s": P(SEG_AXIS), "r": P()}

        def jit(fn, titles, what, ins="", outs="", donate=False):
            return PG.jit(_shard_map(fn, mesh, tuple(specs[c] for c in ins),
                                     tuple(specs[c] for c in outs)),
                          titles, f"{self.prefix} {what}",
                          donate_argnums=TP.step_donation(self.platform)
                          if donate else ())
        return lowerer, jit

    @staticmethod
    def local(tree):
        """Per-segment block view inside shard_map: drop the leading (1,)
        axis every sharded leaf carries."""
        return jax.tree_util.tree_map(lambda a: a[0], tree)

    @staticmethod
    def seg(tree):
        return jax.tree_util.tree_map(lambda a: a[None], tree)

    @staticmethod
    def checks(checks: dict) -> dict:
        """Replicated any-segment-tripped scalars — readable on every
        host."""
        with jax.named_scope("checks"):
            return {k: jax.lax.psum(jnp.asarray(v).astype(jnp.int32),
                                    SEG_AXIS) > 0
                    for k, v in checks.items()}

    def local_n(self, tile_n):
        return tile_n.reshape(())

    def stats(self, low, shape) -> tuple:
        """Per-motion (required-bucket scalar, per-destination row vector)
        pairs off the lowerer's replicated stats channel
        (dist_executor.DistLowerer.motion psums/pmaxes them) — zeros when
        a motion lowered without the bucketed path — as the step's third
        output, for the skew sentinel."""
        return (tuple(
            (low.stats.get(f"required bucket (node {low.ref(m)})",
                           jnp.zeros((), jnp.int32)),
             low.stats.get(f"seg rows (node {low.ref(m)})",
                           jnp.zeros((self.nseg,), jnp.int32)))
            for m in _stat_motions(shape)),)

    # ------------------------------------------------------------ host

    tile_n = by_segment = staticmethod(_identity)
    host = staticmethod(_local_row)

    def fault_step(self) -> None:
        fault_point("tile_step_dist")

    def acc(self, n: int, dtype, fill=0):
        return np.full((self.nseg, n), fill, dtype=dtype)

    def retile(self, shape, tile_rows: int) -> None:
        TS.retile_mesh(shape, tile_rows, self.nseg)

    def prepare(self, exe):
        """The run's recovery context, its fallible resume work done
        first (it may grow g_cap for re-sharded partials), then the
        capacities the programs are built at."""
        ctx = R.begin(exe, dist=True)
        if ctx is not None:
            ctx.prepare_dist()
        self.retile(exe.shape, exe.tile_rows)
        TS.refinalize(exe.shape, self.nseg)
        return ctx

    def _names(self, exe) -> list[str]:
        return sorted({s.table_name for s in X.scans_of(exe.shape.partial_plan)
                       if s is not exe.shape.stream})

    def resident(self, exe, bs=None) -> dict:
        return prepare_dist_inputs(None, exe.session,
                                   names=self._names(exe))[0]

    def feed(self, exe, ctx, window: int):
        """(tile feed, progress tracker) of a run. The prefetch pipeline
        stages on the host only — shard_map owns device placement. The
        tracker has one lane a segment (the loop runs lock-step, so the
        longest shard sets the tile count): a resumed feed's remaining
        per-shard counts, with the consumed mask as its base, or the
        counts-only shard layout."""
        shape, session = exe.shape, exe.session
        feed = (ctx.feed() if ctx is not None else None) \
            or _dist_tile_feed(shape.stream, session, exe.tile_rows)
        total = stream_rows(shape.stream, session)
        base_rows = 0
        if hasattr(feed, "counts") and hasattr(feed, "base_mask"):
            lanes = np.asarray(feed.counts)
            base_rows = int(np.asarray(feed.base_mask).sum())
        else:
            try:
                lanes = np.asarray(session.shard_counts(
                    shape.stream.table_name))
            except KeyError:
                lanes = np.asarray([total])
        tracker = TileTracker(
            lanes, exe.tile_rows,
            n_base=ctx.tiles_base if ctx is not None else 0,
            base_rows=base_rows, rows_total=total)
        return SP.maybe_pipeline(iter(feed), session.config,
                                 min_depth=window), tracker

    def empty_tile(self, scan: N.PScan, tile_rows: int):
        return (_empty_tile(scan, (self.nseg, tile_rows)),
                np.zeros((self.nseg,), dtype=np.int64))

    def sentinel(self, exe, ctx):
        s = SkewSentinel(exe, _stat_motions(exe.shape), ctx)
        return s if s.collect else None

    def report(self, exe) -> dict:
        return {"distributed": True, "n_segments": self.nseg,
                # the topology epoch this executable was (re)built under
                # (parallel/topology.py): a report whose epoch differs
                # from the statement's pinned one is the cross-epoch-
                # resume case
                "topology_epoch": topology_token(exe.session),
                "est_finalize_bytes": TS.finalize_bytes(exe.shape,
                                                        self.nseg)}


def _stat_motions(shape) -> tuple:
    """The step program's redistribute motions, in deterministic
    traversal order — the skew sentinel watches their psum'd
    per-destination row counts, and the end-of-run fold publishes the
    cumulative observations to the feedback store (plan/feedback.py)."""
    return tuple(n for n in X.all_nodes(shape.partial_plan)
                 if isinstance(n, N.PMotion) and n.kind == "redistribute")


# ------------------------------------------------------------ executables


class TiledExecutable:
    """A tiled aggregate: prelude (once) → step (per tile) → finalize,
    over a segment layout. The base of every mode: the report, the build
    streams, and the adaptive discipline — classify a detected overflow,
    grow the guilty buffer (accumulator / join pair buffer) or shrink the
    tile, and re-run, never truncate."""

    _what = "tiled execution"

    def __init__(self, shape: TS.TileShape, session, tile_rows: int,
                 budget: int, layout):
        self.shape = shape
        self.session = session
        self.tile_rows = tile_rows
        self.budget = budget
        self.lay = layout
        self._compiled = None
        # server handler threads may hit the cached runner concurrently;
        # retries mutate shared plan capacities, so runs serialize (the
        # admission gate bounds statement concurrency anyway)
        self._run_lock = threading.Lock()
        self._bprogs: dict = {}     # id(build stream) -> its programs
        self._build_rows = 0        # rows the last run's build streams read
        self._refresh_report()

    @property
    def nseg(self) -> int:
        return self.lay.nseg

    def _refresh_report(self) -> None:
        shape, lay, cfg = self.shape, self.lay, self.session.config
        lay.retile(shape, self.tile_rows)
        self.report = {
            "tiled": True,
            "mode": shape.mode,
            "stream_table": shape.stream.table_name,
            "tile_rows": self.tile_rows,
            "acc_capacity": shape.g_cap,
            "est_step_bytes": max(TS.step_bytes(shape),
                                  TS.build_phase_bytes(shape)),
            # scan-pipeline staging charge (exec/scanpipe.py) plus the
            # dispatch window's extra in-flight tiles (exec/tilepipe.py)
            # — obs/capacity.record_tiled adds both to the statement's
            # observed peak
            "est_pipeline_bytes": SP.queue_charge_bytes(
                shape.stream, self.tile_rows, cfg, nseg=lay.nseg)
            + TP.window_charge_bytes(shape.stream, self.tile_rows, cfg,
                                     lay.platform, nseg=lay.nseg),
            # buffer-pool residency attributable to the streamed table
            # (exec/bufferpool.py) — charged into the capacity plane next
            # to the pipeline's staging bytes
            "est_bufpool_bytes": _bufpool_charge(
                self.session, shape.stream.table_name),
            "budget_bytes": self.budget,
            **lay.report(self),
        }
        if shape.streams:
            self.report["build_streams"] = [
                {"table": bs.shape.stream.table_name,
                 "tile_rows": bs.tile_rows, "slots": bs.slots,
                 "table_bytes": bs.table_bytes} for bs in shape.streams]

    def refresh_bufpool_charge(self) -> None:
        """Re-stamp the report's ``est_bufpool_bytes``. The report is
        built once per compile but the pool's residency for the
        streamed table moves between statements (admits during a prior
        run, evictions, topology sweeps) — dispatch-time capacity
        recording and report publication both re-read it."""
        # graftlint: ignore[lock-unguarded] one item store of the pool's current residency: dispatch-time capacity recording re-stamps it outside the run, last writer wins with an equally fresh value
        self.report["est_bufpool_bytes"] = _bufpool_charge(
            self.session, self.shape.stream.table_name)

    def _publish_report(self) -> None:
        self.refresh_bufpool_charge()
        self.session.last_tiled_report = dict(self.report)

    # ------------------------------------------------------------ programs

    def _compile(self):
        if self._compiled is None:
            self._compiled = self._programs(*self.lay.compiler(self))
            # a statement-level program set built: the engine's compile
            # counter moves here as it does in compile_plan
            X.count_compile(self.session)
        return self._compiled

    def _programs(self, lower, jit) -> tuple:
        shape, lay = self.shape, self.lay
        group_names = shape.group_names
        specs = shape.merge_specs
        g_cap = shape.g_cap

        def step_fn(resident, prelude, tile, tile_n, acc):
            low = self._step_lowerer(lower, shape, resident, prelude, tile,
                                     tile_n)
            pcols, psel = low.lower(shape.partial_plan)
            checks = dict(low.checks)
            stats = lay.stats(low, shape)
            acc_cols, acc_sel = lay.local(tuple(acc))
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
                        carried=shape.partial_plan.carried)
                    checks["tile merge overflow: more groups than capacity "
                           f"{g_cap}; raise the aggregation capacity"] = \
                        n_groups > g_cap
                    out = ({**ok, **oa}, osel)
                else:
                    agg_vals = {s.out_name: jnp.concatenate(
                        [acc_cols[s.out_name], pcols[s.out_name]])
                        for s in specs}
                    sel = jnp.concatenate([acc_sel, psel])
                    out = (K.global_aggregate(agg_vals, specs, sel),
                           jnp.ones((1,), dtype=jnp.bool_))
                return (lay.seg(out), lay.checks(checks)) + stats

        titles = X.node_titles(shape.partial_plan, shape.root)
        return (jit(self._prelude_fn(lower, shape), titles, "prelude",
                    "R", "sr"),
                jit(step_fn, titles, "step", "Rssss", "srr", donate=True),
                jit(self._finalize_fn(lower), titles, "finalize",
                    "s", "ssr"))

    def _prelude_fn(self, lower, shape: TS.TileShape,
                    root: Optional[N.PlanNode] = None):
        """The prelude program of a tile stream: every spine join's build
        computed once, but a streamed build's (its table is built before).
        ``root``: the plan its nodes are numbered in (``shape``'s partial
        plan, or the statement's where ``shape`` is a build's own)."""
        lay = self.lay
        streamed = {id(bs.join.build) for bs in shape.streams}
        root = root or shape.partial_plan

        def prelude_fn(tables):
            low = lower(tables, root=root)
            outs = [None if id(b) in streamed
                    else lay.seg(low.lower_shared(b)) for b in shape.builds]
            return outs, lay.checks(low.checks)
        return prelude_fn

    def _step_lowerer(self, lower, shape: TS.TileShape, resident, prelude,
                      tile, tile_n, root: Optional[N.PlanNode] = None):
        """The lowerer of one step over ``tile`` of ``shape``'s stream:
        ``prelude`` (one entry a build) stands for each build computed
        once, or for a streamed build's table, which its join reads."""
        lay = self.lay
        tables = dict(resident)
        tables["$tile"] = lay.local(tile)
        streamed = {id(bs.join.build): bs for bs in shape.streams}
        replace, direct = {}, {}
        for b, out in zip(shape.builds, lay.local(prelude)):
            bs = streamed.get(id(b))
            if bs is None:
                replace[id(b)] = out
            else:
                direct[id(bs.join)] = (bs.box, out)
        return lower(tables, root=root, replace=replace,
                     stream=shape.stream, tile_n=lay.local_n(tile_n),
                     direct_tables=direct)

    def _finalize_fn(self, lower):
        """The finalize program: the accumulator in its leaf's place (the
        partial aggregation's, on the mesh), the chain above it."""
        shape, lay = self.shape, self.lay

        def finalize_fn(acc):
            acc_cols, acc_sel = lay.local(tuple(acc))
            low = lower(
                {}, root=shape.partial_plan,
                replace={id(shape.replace_node): (acc_cols, acc_sel)})
            cols, sel = low.lower(shape.root)
            out = {f.name: lay.seg(cols[f.name]) for f in shape.root.fields}
            return out, lay.seg(sel), lay.checks(low.checks)
        return finalize_fn

    def _prelude(self, prelude_fn, resident, tables: list) -> list:
        """The prelude's outputs with each streamed build's table in its
        place (the prelude computes none of those). The mesh launches no
        prelude for a spine without builds."""
        shape = self.shape
        prelude = []
        if shape.builds or not self.lay.dist:
            prelude, pchecks = prelude_fn(resident)
            X.raise_checks(pchecks)
        if not shape.streams:
            return prelude
        by_build = {id(bs.join.build): t
                    for bs, t in zip(shape.streams, tables)}
        return [by_build.get(id(b), out)
                for b, out in zip(shape.builds, prelude)]

    def _init_acc(self):
        shape, lay = self.shape, self.lay
        g_cap = shape.g_cap
        if shape.group_names:
            return ({f.name: lay.acc(g_cap, f.type.np_dtype)
                     for f in shape.partial_plan.fields},
                    lay.acc(g_cap, np.bool_))
        cols = {}
        for f, spec in zip(shape.partial_plan.fields, shape.merge_specs):
            dt = f.type.np_dtype
            ident = np.zeros((), dtype=dt)
            if spec.func in ("min", "max"):
                info = np.finfo(dt) if np.issubdtype(dt, np.floating) \
                    else np.iinfo(dt)
                ident = np.array(info.max if spec.func == "min"
                                 else info.min, dtype=dt)
            cols[f.name] = lay.acc(1, dt, ident)
        # identity row stays unselected: min/max identities must not leak
        # into the merge as real values when a tile contributes rows
        return cols, lay.acc(1, np.bool_)

    # -------------------------------------------------------- build streams

    def _build_tables(self) -> list:
        """Phase one of a statement with build streams: each streamed
        build's table, in ``shape.streams`` order, under one
        ``build-stream`` span. It takes no checkpoint: a fault here
        restarts the statement, and a resumed probe stream streams its
        builds again first (the tables are a function of the data)."""
        if self._compiled is None:      # first run, or a retry re-planned
            self._bprogs = {}
        self._build_rows = 0
        if not self.shape.streams:
            return []
        with OT.stage("build-stream", "launch_seconds"):
            return [self._stream_build(bs) for bs in self.shape.streams]

    def _build_programs(self, bs: TS.BuildStream):
        """(prelude, step) of one build stream: the prelude computes the
        build's own resident builds once; the step runs the build's spine
        over one tile of its scan and scatters the selected rows into the
        table it carries (donated), checked as the one-shot lookup is."""
        shape = self.shape
        join = bs.join
        lower, jit = self.lay.compiler(self)
        prelude_fn = self._prelude_fn(lower, bs.shape,
                                      root=shape.partial_plan)

        def step_fn(resident, prelude, tile, tile_n, table):
            low = self._step_lowerer(lower, bs.shape, resident, prelude,
                                     tile, tile_n, root=shape.partial_plan)
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
        return (jit(prelude_fn, titles, "build prelude"),
                jit(step_fn, titles, "build step", donate=True))

    def _stream_build(self, bs: TS.BuildStream):
        """Stream one build's scan through the tile feed (reader thread,
        buffer pool, dispatch window) into its table; the table."""
        progs = self._bprogs.get(id(bs))
        if progs is None:
            progs = self._bprogs[id(bs)] = self._build_programs(bs)
        prelude_fn, step_fn = progs
        resident = self.lay.resident(self, bs)
        prelude, pchecks = prelude_fn(resident)
        X.raise_checks(pchecks)
        table = bs.empty_table()
        timer = _TileTimer(self.session, phase="build")
        pipe = TP.TilePipe(self.session, TP.effective_window(
            self.session.config, self.lay.platform))
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

    # ----------------------------------------------------------------- run

    def run(self) -> ColumnBatch:
        with self._run_lock:
            return self._run_adaptive()

    def _groups_ceiling(self) -> int:
        return self.shape.max_groups

    def _run_once(self) -> ColumnBatch:
        lay, shape = self.lay, self.shape
        # children of ``launch`` on the statement thread (obs/trace.py):
        # [build-stream] | prelude | (feed-wait | h2d | tile-step ⊃
        # drain-stall)* | finalize
        tables = self._build_tables()
        with OT.stage("prelude", "launch_seconds"):
            ctx = lay.prepare(self)
            prelude_fn, step_fn, finalize_fn = self._compile()
            resident = lay.resident(self)
            prelude = self._prelude(prelude_fn, resident, tables)
            acc = self._init_acc()
            if ctx is not None:
                acc = ctx.restore_acc(acc)
            n_base = ctx.tiles_base if ctx is not None else 0
            n_local = 0
            n_sub = 0
            timer = _TileTimer(self.session)
            pipe = TP.TilePipe(self.session, TP.effective_window(
                self.session.config, lay.platform))
            feed, tracker = lay.feed(self, ctx, pipe.window)
            sentinel = lay.sentinel(self, ctx)

        def _verified(d):
            # host effects for ONE drained-clean tile, in stream order
            # and in the legacy sequence: the skew observation, progress,
            # then the K-tile checkpoint tick (a staged payload when the
            # submit saw the boundary coming; the live accumulator at
            # window=1, where drain is synchronous and acc IS this
            # tile's state)
            nonlocal n_local
            tile_k, staged, srows = d.payload
            n_local = tile_k
            if srows is not None:
                sentinel.observe(srows)
            tracker.step(tile_k)
            if ctx is not None:
                ctx.tick(tile_k, staged if staged is not None
                         else (lambda: R.acc_payload(acc)))

        def _settle():
            # drain every dispatched tile so the replan snapshot's acc
            # (the newest) matches the settled tile count
            for d in pipe.drain_all():
                _verified(d)
            return n_sub

        try:
            for tile, tile_n in feed:
                lay.fault_step()
                fault_point("tile_device_lost")
                n_sub += 1
                stage = (ctx is not None and pipe.window > 1
                         and ctx.snapshot_due(n_sub))
                with timer.step(n_base + n_sub - 1):
                    acc, checks, *stats = step_fn(
                        resident, prelude, tile, lay.tile_n(tile_n), acc)
                    staged = TP.stage_checkpoint(acc) if stage else None
                    drained = pipe.submit(
                        n_base + n_sub - 1, checks,
                        (n_sub, staged,
                         stats[0] if sentinel is not None else None))
                for d in drained:
                    _verified(d)
                if sentinel is not None:
                    # AFTER the cadence tick: an alarm at a tick tile
                    # reuses that snapshot instead of saving twice
                    sentinel.maybe_replan(n_local,
                                          lambda: R.acc_payload(acc),
                                          settle=_settle)
            for d in pipe.drain_all():
                _verified(d)
            if sentinel is not None and pipe.window > 1:
                # the tail's observes may alarm after the feed ended
                sentinel.maybe_replan(n_local, lambda: R.acc_payload(acc))
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
            if sentinel is not None:
                sentinel.fold_final()
            if n_tiles == 0:  # empty stream: an all-masked tile seeds acc
                tile, tile_n = lay.empty_tile(shape.stream, self.tile_rows)
                acc, checks, *_ = step_fn(resident, prelude, tile,
                                          lay.tile_n(tile_n), acc)
                _raise_tile_checks(checks, 0)
                n_tiles = 1

            fault_point("tiled_finalize")
            # cancel seam before the finalize (the mesh's merge
            # collective): the per-tile checks bound the stream, this
            # bounds the tail
            check_cancel()
            cols, sel, fchecks = finalize_fn(acc)
            X.raise_checks(fchecks)
            self.report["n_tiles"] = n_tiles
            if ctx is not None:
                ctx.stamp_report(self.report)
            self._publish_report()
            return X.make_batch(shape.root,
                                {k: lay.host(v) for k, v in cols.items()},
                                lay.host(sel))

    def _run_adaptive(self) -> ColumnBatch:
        while True:
            # cancel seam: each adaptive round restarts the whole tile
            # stream — a cancelled/over-deadline statement stops between
            # rounds instead of re-streaming the table
            check_cancel()
            try:
                batch = self._run_once()
                log = getattr(self.session, "stmt_log", None)
                X.count_join_shapes(
                    log, X.join_shapes(self.shape.partial_plan))
                streams = self.shape.streams
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
                    # the mesh's finalize merges nseg·g_cap rows.
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
                        f"{self.lay.adjective}{self._what} working set "
                        f"(accumulator {shape.g_cap} groups, tile "
                        f"{self.tile_rows} rows) exceeds the query memory "
                        f"budget {self.budget >> 20} MiB; raise "
                        "config.resource.query_mem_bytes") from e

    def _over_budget(self) -> bool:
        return max(self.report["est_step_bytes"],
                   self.report.get("est_finalize_bytes", 0)) > self.budget

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
        if self.tile_rows <= TS.MIN_TILE:
            return False
        self.tile_rows >>= 1
        return True


class TopNTiledExecutable(TiledExecutable):
    """Tiled statement whose accumulator is the best LIMIT+OFFSET rows
    seen so far (nodeSort.c bounded-heap role): step = spine over one
    tile, then one bounding sort over (accumulator ∪ tile rows), keeping
    the first g_cap positions — selected rows sort first, so the slice
    is exactly the running top-N. On the mesh every segment keeps its
    own, merged through a LOCAL sort with no collective beyond the
    spine's. Finalize runs the original post chain (LIMIT/projections;
    on the mesh the pre-gather compaction, the gather and the global
    sort too) over the sorted accumulator."""

    _what = "top-N tiled execution"

    def _groups_ceiling(self) -> int:
        return self.shape.g_cap  # fixed: LIMIT itself bounds the acc

    def _init_acc(self):
        shape, lay = self.shape, self.lay
        return ({f.name: lay.acc(shape.g_cap, f.type.np_dtype)
                 for f in shape.partial_plan.fields},
                lay.acc(shape.g_cap, np.bool_))

    def _programs(self, lower, jit) -> tuple:
        shape, lay = self.shape, self.lay
        m = shape.g_cap
        msort = shape.msort
        names = [f.name for f in shape.partial_plan.fields]

        def step_fn(resident, prelude, tile, tile_n, acc):
            low = self._step_lowerer(lower, shape, resident, prelude, tile,
                                     tile_n)
            pcols, psel = low.lower(shape.partial_plan)
            checks = dict(low.checks)
            stats = lay.stats(low, shape)
            acc_cols, acc_sel = lay.local(tuple(acc))
            with jax.named_scope("tile:merge"):
                ccols = {n: jnp.concatenate([acc_cols[n], pcols[n]])
                         for n in names}
                csel = jnp.concatenate([acc_sel, psel])
            low2 = lower({}, root=shape.partial_plan,
                         replace={id(msort.child): (ccols, csel)})
            scols, ssel = low2.lower(msort)
            checks.update(low2.checks)
            return (lay.seg(({n: scols[n][:m] for n in names}, ssel[:m])),
                    lay.checks(checks)) + stats

        pp = shape.partial_plan
        return (jit(self._prelude_fn(lower, shape), X.node_titles(pp),
                    "prelude", "R", "sr"),
                jit(step_fn, X.node_titles(pp, msort), "top-N step",
                    "Rssss", "srr", donate=True),
                jit(self._finalize_fn(lower), X.node_titles(pp, shape.root),
                    "finalize", "s", "ssr"))


class SortTiledExecutable(TiledExecutable):
    """Tiled statement whose result is a FULL ORDER BY with no bounding
    limit — the external-merge-sort analog (tuplesort.c spill mode,
    workfile_mgr.c's tape role played by host RAM). Per tile, the step
    program runs the spine and emits the surviving rows together with
    one order-normalized u64 column per sort key (same normalization
    kernels.sort_indices uses, so device and host orders cannot
    disagree — descending keys bit-complement, NULL ordering rides the
    binder's is-null companion keys). The host appends each tile's rows
    — every segment's, on the mesh, subsuming the plan's gather — to the
    run store; the merge pass is one stable host key sort over the
    collected runs, then the post chain (column pruning, LIMIT) applies
    host-side."""

    _what = "external-sort tiled execution"

    def _groups_ceiling(self) -> int:
        return 0  # no accumulator exists to grow

    def _programs(self, lower, jit) -> tuple:
        shape, lay = self.shape, self.lay
        sort = shape.sortnode
        names = [f.name for f in shape.partial_plan.fields]

        def step_fn(resident, prelude, tile, tile_n):
            low = self._step_lowerer(lower, shape, resident, prelude, tile,
                                     tile_n)
            pcols, psel = low.lower(shape.partial_plan)
            n = psel.shape[0]
            keys = []
            for e, asc in sort.keys:
                arr = X._as_column(X._sortable(e, sort.child, pcols), n)
                u = K.sort_key_u64(arr)
                keys.append(u if asc else ~u)
            out = {nm: X._as_column(pcols[nm], n) for nm in names}
            return lay.seg((out, psel, keys)), lay.checks(low.checks)

        titles = X.node_titles(shape.partial_plan)
        return (jit(self._prelude_fn(lower, shape), titles, "prelude",
                    "R", "sr"),
                jit(step_fn, titles, "sort step", "Rsss", "sr"))

    def _stream_sorted(self):
        """Run the tile stream and the merge pass; returns
        (sorted child columns, sorted normalized key columns, n_tiles,
        recovery ctx) as host arrays."""
        lay, shape = self.lay, self.shape
        tables = self._build_tables()
        ctx = lay.prepare(self)
        prelude_fn, step_fn = self._compile()
        resident = lay.resident(self)
        prelude = self._prelude(prelude_fn, resident, tables)
        names = [f.name for f in shape.partial_plan.fields]
        runs: dict[str, list] = {nm: [] for nm in names}
        key_runs: list[list] = [[] for _ in shape.sortnode.keys]
        if ctx is not None:
            runs, key_runs = ctx.restore_runs(runs, key_runs)
        n_base = ctx.tiles_base if ctx is not None else 0
        n_local = 0
        n_sub = 0
        timer = _TileTimer(self.session)
        pipe = TP.TilePipe(self.session, TP.effective_window(
            self.session.config, lay.platform))
        feed, tracker = lay.feed(self, ctx, pipe.window)

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
            cols = [lay.by_segment(np.asarray(pcols[nm])) for nm in names]
            keyh = [lay.by_segment(np.asarray(k)) for k in keys]
            for s, mask in enumerate(lay.by_segment(np.asarray(psel))):
                for nm, c in zip(names, cols):
                    runs[nm].append(c[s][mask])
                for i, k in enumerate(keyh):
                    key_runs[i].append(k[s][mask])
            if ctx is not None:
                ctx.tick(tile_k,
                         lambda: R.runs_payload(runs, key_runs))

        try:
            for tile, tile_n in feed:
                lay.fault_step()
                fault_point("tile_device_lost")
                n_sub += 1
                with timer.step(n_base + n_sub - 1):
                    (pcols, psel, keys), checks = step_fn(
                        resident, prelude, tile, lay.tile_n(tile_n))
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
        check_cancel()
        cols, karr = merge_sorted_runs(runs, key_runs,
                                       shape.partial_plan.fields,
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
    chunks and runs the original window (+ projection chain; on the mesh
    the original plan, its gathers identity over the pooled rows) on ONE
    device once per chunk: window functions never cross partitions, so
    chunks are independent and every frame kind stays exact — no carry
    state. Only a single partition larger than the chunk capacity cannot
    stream; that raises with a clear message (the reference's
    one-partition tuplestore has the same working-set floor)."""

    _what = "windowed tiled execution"
    _chunk_compiled = None

    def _chunk_fn(self):
        if self._chunk_compiled is not None:
            return self._chunk_compiled
        shape = self.shape
        plat = self.lay.platform
        cap = self.tile_rows

        def run_chunk(chunk_cols, n_valid):
            sel = jnp.arange(cap) < n_valid
            low = X.Lowerer(
                {}, platform=plat, root=shape.partial_plan,
                replace={id(shape.replace_node): (chunk_cols, sel)})
            cols, osel = low.lower(shape.root)
            out = {f.name: cols[f.name] for f in shape.root.fields}
            return out, osel, low.checks

        self._chunk_compiled = PG.jit(
            run_chunk, X.node_titles(shape.partial_plan, shape.root),
            f"{self.lay.prefix} window chunk")
        return self._chunk_compiled

    def _run_once(self) -> ColumnBatch:
        shape = self.shape
        self._chunk_compiled = None  # capacity may have changed
        cols, karr, n_tiles, ctx = self._stream_sorted()
        names = [f.name for f in shape.partial_plan.fields]
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


_EXECUTABLES = {"agg": TiledExecutable, "topn": TopNTiledExecutable,
                "sort": SortTiledExecutable,
                "window": WindowTiledExecutable}


def window_chunk_pass(run, root, names, cols, karr, npk, cap):
    """Phase two of window spill: pack WHOLE partitions (runs of equal
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


def _raise_tile_checks(checks: dict, tile_idx: int) -> None:
    # the per-tile cancel seam (the CHECK_FOR_INTERRUPTS row-boundary
    # analog): every step/chunk of the tiled executables, in either
    # layout, passes through here, so cancellation latency is bounded
    # by one tile's device launch
    check_cancel()
    for msg, bad in checks.items():
        if bool(np.asarray(bad).any()):
            # (the class the one-shot lowering's check raises, as
            # X.raise_checks gives it: a semantic error, never retried)
            cls = X.DuplicateBuildKeyError if "duplicate keys" in msg \
                else X.ExecError
            raise cls(f"[tile {tile_idx}] {msg}")


# -------------------------------------------------------------- tile feed


def _phys_cols(scan: N.PScan) -> list[str]:
    return sorted(set(scan.column_map) | set(scan.mask_map))


def _empty_tile(scan: N.PScan, shape: tuple) -> dict:
    """An all-masked tile of ``shape`` (rows, or segments × rows)."""
    t = {}
    for phys in scan.column_map:
        t[phys] = np.zeros(shape, dtype=np.int64)
    for phys in scan.mask_map:
        t[f"$nn:{phys}"] = np.zeros(shape, dtype=np.bool_)
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


def _dist_tile_feed(scan: N.PScan, session, tile_rows: int):
    """Yield (tile dict of (nseg, tile_rows) arrays, per-segment valid
    counts). All segments step in lock-step; a segment whose shard ran dry
    contributes masked rows — the SPMD analog of a QE sending EOS while
    its peers still stream. Packed feed tiles resident in the buffer
    pool (exec/bufferpool.py, keyed by tile offset + the shared-tier
    content/epoch tokens) skip the slice-pad-copy work; the pool holds
    HOST arrays on this path — shard_map owns device placement, exactly
    like the pipeline's host-only staging."""
    st = session.sharded_table(scan.table_name)
    nseg, shard_cap = len(st.counts), st.capacity
    bpool = BUF.pool_for(session)
    cols_key = (tuple(sorted(scan.column_map)),
                tuple(sorted(scan.mask_map)))
    log = getattr(session, "stmt_log", None)
    counts = np.asarray(st.counts)
    cols: Optional[dict] = None  # built lazily: an all-hit feed never
    max_rows = int(st.counts.max()) if len(st.counts) else 0
    for off in range(0, max(max_rows, 0), tile_rows):
        n = min(tile_rows, max_rows - off)
        tile_ns = np.clip(counts - off, 0, tile_rows)
        key = None
        if bpool is not None:
            try:
                key = BUF.dist_tile_key(session, scan.table_name,
                                        cols_key, nseg, tile_rows, off)
            except KeyError:  # table dropped mid-plan: fall through
                key = None
        if key is not None:
            ent = bpool.lookup(key, log)
            if ent is not None:
                yield ent["tile"], tile_ns
                continue
        if cols is None:
            cols = {}
            for phys in scan.column_map:
                cols[phys] = np.asarray(st.columns[phys])
            for phys in scan.mask_map:
                vm = st.columns.get(f"$nn:{phys}")
                cols[f"$nn:{phys}"] = (
                    np.asarray(vm) if vm is not None
                    else np.ones((nseg, shard_cap), dtype=np.bool_))
        tile = {}
        for name, arr in cols.items():
            sl = arr[:, off:off + n]
            if n < tile_rows:
                sl = np.concatenate(
                    [sl, np.zeros((nseg, tile_rows - n), dtype=arr.dtype)],
                    axis=1)
            tile[name] = np.ascontiguousarray(sl)
        if key is not None:
            bpool.offer(key, {"tile": tile}, table=scan.table_name,
                        log=log, device=False)
        yield tile, tile_ns
