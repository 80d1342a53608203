"""Windowed in-flight tile dispatch — keep the accelerator queue full.

The tiled streaming loops (exec/tiled.py, either segment layout) are
the engine's out-of-core hot path, and before this module every tile
round-tripped the device: ``step_fn`` launches, then
``_raise_tile_checks``/``sentinel.observe`` immediately force the tiny
per-tile check/stat scalars to host, so the device queue drains to
empty between tiles and the scan pipeline's staged tiles wait on a
stalled consumer. The discipline here is the one Theseus (PAPERS.md)
states for GPU MPP engines and every training input pipeline applies:
never synchronize the accelerator on per-batch control scalars — keep
a bounded window of W steps in flight, start the device→host copy of
each step's control scalars the moment it is dispatched, and only
block when the OLDEST in-flight tile's scalars are genuinely not ready.

``TilePipe`` is that window. The loop calls ``submit(idx, checks,
payload)`` right after dispatching tile ``idx``'s step; submit starts
async host copies for the checks and payload, then drains the oldest
entries until at most ``window-1`` remain in flight, returning the
drained entries so the caller runs their host-side effects (progress,
run appends, checkpoint ticks, sentinel folds) in stream order.
``drain_all()`` flushes the tail after the feed ends.

Correctness rules:

- **Deferred failure, bounded by W.** A capacity-overflow check or skew
  alarm for tile k is observed at most W tiles late, while tiles
  k+1..k+W-1 may already be dispatched. The checkpoint tick for a tile
  only happens when that tile has DRAINED CLEAN, so the last durable
  checkpoint never includes a failed tile's state: the adaptive retry
  (or device-loss resume) rewinds through the recovery store and
  replays ≤ W+K tiles at the grown rung — bit-identical to the
  synchronous path by construction, since tile order, kernel programs,
  and merge semantics are unchanged; only when the host *learns* of a
  failure moves.
- **Checkpoint payloads stage at submit.** On accelerators the carried
  accumulator is donated to the next step, so a drain-time snapshot
  could not read it; ``stage_checkpoint`` makes a device-side copy and
  starts its async D2H copy at submit time (decided by
  ``RecoveryCtx.snapshot_due``), and the drain-time tick materializes
  the staged copy without blocking the window.
- **Cancellation still polls per drained tile.** Every drain routes
  through ``_raise_tile_checks`` (the ``check_cancel`` seam), so
  cancellation latency is bounded by W in-flight launches instead of
  one — the graftlint seam-loop pass accepts ``drain_one``/
  ``drain_all`` as cancel polls for exactly this reason.
- **``inflight_tiles=1`` is the legacy loop, exactly.** submit drains
  the just-submitted tile immediately: checks force right after the
  step inside the same timer window, host effects run in the same
  order, no staging copies are made. That is the CPU-backend default
  (``effective_window``): a single-threaded host gains nothing from
  in-flight depth, accelerators default to a window of 4.

Telemetry: ``drain_stall_s`` (host seconds blocked forcing drained
scalars), ``inflight_depth`` (window high-water mark) stamp the tiled
run report for EXPLAIN ANALYZE's trailer and the bench ladder; the
``tile_inflight`` gauge and the ``tile_deferred_overflows``/
``tile_window_replays`` counters ride the engine registry. The window's
extra in-flight device tiles are charged into the statement's capacity
estimate (``window_charge_bytes`` → est_pipeline_bytes).
"""

from __future__ import annotations

from collections import deque
from typing import NamedTuple

import jax
import jax.numpy as jnp

from cloudberry_tpu.utils.faultinject import fault_point

# Auto window depth on accelerator backends (TPU/GPU): deep enough to
# overlap D2H of tile k's scalars + H2D of tile k+2's data with tile
# k+1's compute, shallow enough that a deferred overflow replays only a
# few tiles past the last checkpoint.
_AUTO_ACCEL_WINDOW = 4
_MAX_WINDOW = 64


def step_donation(platform: str, argnum: int = 4) -> tuple:
    """The accumulator-donation rule every tiled step program shares
    (agg + topn, single-node and distributed — including the top-N
    heap carry, whose donation is legal because the bounding sort's
    first g_cap positions match the (g_cap,) input acc shape exactly):
    donate the carried accumulator argument so the step updates it in
    place on device and the sequential dependency never leaves HBM.
    CPU XLA can't always honor donation and warns — skip it there."""
    return () if platform == "cpu" else (argnum,)


def effective_window(config, platform: str) -> int:
    """The in-flight tile window for this run. ``inflight_tiles <= 0``
    means auto: 1 on the CPU backend (the legacy loop, exactly — a
    single-threaded host has nothing to overlap), ``_AUTO_ACCEL_WINDOW``
    on accelerators."""
    tp = getattr(config, "tile_pipeline", None)
    if tp is None or not tp.enabled:
        return 1
    w = int(tp.inflight_tiles)
    if w <= 0:
        w = 1 if platform == "cpu" else _AUTO_ACCEL_WINDOW
    return max(1, min(w, _MAX_WINDOW))


def window_charge_bytes(scan, tile_rows: int, config, platform: str,
                        nseg: int = 1) -> int:
    """Capacity-plane charge for the dispatch window: beyond the first
    tile (already counted in est_step_bytes), each additional in-flight
    tile pins one tile's working set on device until its scalars
    drain."""
    w = effective_window(config, platform)
    if w <= 1:
        return 0
    from cloudberry_tpu.exec import scanpipe as SP

    return (w - 1) * SP.tile_host_bytes(scan, tile_rows, nseg)


def _host_async(tree) -> None:
    """Start async device→host copies for every jax leaf of ``tree`` —
    advisory: a leaf that cannot stage just blocks at materialization,
    which is the pre-pipeline behavior, never an error."""
    for leaf in jax.tree_util.tree_leaves(tree):
        fn = getattr(leaf, "copy_to_host_async", None)
        if fn is not None:
            try:
                fn()
            except Exception:  # noqa: BLE001 — staging is best-effort
                pass


def _own_copy(x):
    # device-side defensive copy: the ORIGINAL buffer is donated to the
    # next step on accelerators, the copy is ours to read at drain time
    return jnp.copy(x) if isinstance(x, jax.Array) else x


def stage_checkpoint(acc):
    """Checkpoint staging for a windowed submit (window > 1 only): copy
    the carried accumulator ON DEVICE before the next step donates the
    original, start the copy's async D2H, and return the zero-arg
    payload builder ``RecoveryCtx.tick`` runs at drain time — by then
    the transfer has usually landed, so the tick never stalls the
    window."""
    from cloudberry_tpu.exec import recovery as R

    cp = jax.tree_util.tree_map(_own_copy, acc)
    _host_async(cp)
    return lambda: R.acc_payload(cp)


class Drained(NamedTuple):
    """One verified tile, handed back to the loop in stream order."""

    idx: int        # global tile index (n_base + local ordinal)
    payload: object  # whatever the loop attached at submit


class _InFlight(NamedTuple):
    idx: int
    checks: dict
    payload: object


class TilePipe:
    """Bounded window of in-flight tile steps whose control scalars
    drain late. Single-threaded by design: the statement thread owns
    both ends (JAX's async dispatch IS the concurrency), so there is no
    lock and no reader to leak — an abandoned pipe (error unwind) just
    drops its entries and the device launches complete into garbage-
    collected buffers; the feed's ``finally`` close is unchanged."""

    def __init__(self, session, window: int):
        self.window = max(int(window), 1)
        self._log = getattr(session, "stmt_log", None)
        self._q: deque = deque()
        self.max_depth = 0       # in-flight high-water mark
        self.drained = 0         # tiles verified
        self.drain_stall_s = 0.0  # host blocked forcing drained scalars
        self.deferred_fail = False  # a check fired with newer tiles live

    # ------------------------------------------------------------- submit

    def submit(self, idx: int, checks: dict, payload=None) -> list:
        """Enqueue tile ``idx``'s just-dispatched control scalars; start
        their async host copies; drain until at most ``window-1``
        entries remain in flight. Returns the drained entries (possibly
        empty) in stream order — at window=1 that is always exactly the
        submitted tile, forced synchronously like the legacy loop."""
        fault_point("tile_enqueue")
        _host_async((checks, payload))
        self._q.append(_InFlight(idx, checks, payload))
        # high-water mark only — the ``tile_inflight`` gauge is written
        # from obs/capacity.record_tiled off the stamped report, where
        # every other point-in-time gauge lives
        self.max_depth = max(self.max_depth, len(self._q))
        out = []
        while len(self._q) >= self.window:
            out.append(self.drain_one())
        return out

    # -------------------------------------------------------------- drain

    def drain_one(self) -> Drained:
        """Force the OLDEST in-flight tile's checks (the per-tile cancel
        poll rides ``_raise_tile_checks``) and hand it back. A check
        that fires here may be up to ``window`` tiles late — when newer
        tiles were already dispatched the failure is *deferred* and the
        adaptive retry replays from the last drained checkpoint."""
        from cloudberry_tpu.exec.tiled import _raise_tile_checks

        from cloudberry_tpu.obs import trace as OT

        entry = self._q.popleft()
        # (the seam inside the stage: a delay injected there is a stall
        # the report shows, however short the wait for the tile itself)
        with OT.stage("drain-stall", "launch_seconds", log=self._log,
                      tile=entry.idx) as st:
            fault_point("tile_drain")
            try:
                _raise_tile_checks(entry.checks, entry.idx)
            except Exception:
                if self._q:
                    self.deferred_fail = True
                    if self._log is not None:
                        self._log.bump("tile_deferred_overflows")
                raise
        self.drain_stall_s += st.dur  # report and histogram: one reading
        self.drained += 1
        return Drained(entry.idx, entry.payload)

    def drain_all(self) -> list:
        """Flush the window after the feed ends (or before an action
        that needs every dispatched tile verified, e.g. the skew
        sentinel's settle before a mid-statement replan snapshot)."""
        out = []
        while self._q:
            out.append(self.drain_one())
        return out

    # ---------------------------------------------------------- telemetry

    def stamp(self, report: dict) -> None:
        report["tile_window"] = self.window
        report["inflight_depth"] = self.max_depth
        report["drain_stall_s"] = round(self.drain_stall_s, 6)
