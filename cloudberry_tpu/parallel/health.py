"""Failure detection — the FTS analog.

The reference's fault-tolerance service probes every segment postmaster on an
interval, runs a per-segment state machine, and promotes mirrors on failure
(src/backend/fts/fts.c:118, ftsprobe.c:60-95). Mesh slots have no mirrors —
recovery is re-execution (immutable storage makes segments stateless, SURVEY
§7.1) — so the analog is:

- ``probe()``: run a tiny collective across every device and report per-slot
  health (the FTS_MSG_PROBE analog);
- ``HealthMonitor``: background interval prober with status history and a
  failure callback (the bgworker loop);
- ``run_with_retry``: re-dispatch a failed query (device loss surfaces as an
  XLA error; the job-restart recovery model).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Optional


@dataclass
class ProbeResult:
    ok: bool
    n_devices: int            # LIVE device count (the degraded-mesh input)
    latency_s: float
    error: Optional[str] = None
    # indices (into jax.devices()) of the devices that answered — a real
    # loss leaves a hole in the MIDDLE of the list, so recovery must mesh
    # over these exact survivors, not devices[:n]
    live: Optional[list] = None


def probe(n_devices: Optional[int] = None) -> ProbeResult:
    """One health probe: a tiny reduction PER DEVICE, each failure
    isolated — one dead device must report the n−1 survivors, not a
    whole-probe failure (the per-segment state machine of ftsprobe.c)."""
    import jax
    import jax.numpy as jnp

    from cloudberry_tpu.utils.faultinject import fault_point

    t0 = time.time()
    try:
        devices = list(enumerate(jax.devices()))
    except Exception as e:  # noqa: BLE001 — runtime itself is gone
        return ProbeResult(False, 0, time.time() - t0, str(e), live=[])
    if n_devices is not None:
        devices = devices[:n_devices]
    if fault_point("probe_degraded"):
        # chaos seam: report one device lost ('skip' action) — on the
        # virtual CPU mesh no device can really die, so degraded-mesh
        # recovery is provoked deterministically (faultinjector.c role)
        devices = devices[:-1]
    live: list[int] = []
    errors: list[str] = []
    for i, d in devices:
        try:
            x = jax.device_put(jnp.ones((8,), dtype=jnp.float32), d)
            if float(jnp.sum(x)) == 8.0:
                live.append(i)
            else:
                errors.append(f"device {i}: bad probe sum")
        except Exception as e:  # noqa: BLE001 — this device is a finding
            errors.append(f"device {i}: {e}")
    ok = not errors
    return ProbeResult(ok, len(live), time.time() - t0,
                       "; ".join(errors) or None, live=live)


@dataclass
class HealthMonitor:
    """Interval prober (FtsProbeMain loop analog). ``history`` is a
    BOUNDED ring: a long-lived server probing on an interval must never
    grow its status log without bound. ``history_maxlen`` 0 (the
    default) reads config.health.monitor_history."""

    interval_s: float = 30.0
    on_failure: Optional[Callable[[ProbeResult], None]] = None
    history_maxlen: int = 0
    history: "object" = None
    # optional TopologyManager (parallel/topology.py): every probe
    # result — healthy or not — feeds its persistence detector, so a
    # monitored server promotes PERSISTENT device loss to an automatic
    # failover-shrink epoch and device recovery to the symmetric expand
    # back (the FTS probe → configuration-update loop, versioned)
    topology: Optional[object] = None
    _stop: threading.Event = field(default_factory=threading.Event)
    _thread: Optional[threading.Thread] = None

    def __post_init__(self):
        import collections

        if not self.history_maxlen:
            from cloudberry_tpu.config import get_config

            self.history_maxlen = get_config().health.monitor_history
        self.history = collections.deque(self.history or (),
                                         maxlen=self.history_maxlen)

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()  # allow stop() → start() restarts

        def loop():
            while not self._stop.wait(self.interval_s):
                r = probe()
                self.history.append(r)
                if self.topology is not None:
                    self.topology.note_probe(r)
                if not r.ok and self.on_failure is not None:
                    self.on_failure(r)

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="cb-fts-probe")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def probe_now(self) -> ProbeResult:
        r = probe()
        self.history.append(r)
        if self.topology is not None:
            self.topology.note_probe(r)
        if not r.ok and self.on_failure is not None:
            self.on_failure(r)
        return r


def recoverable(e: Exception) -> bool:
    """Failures worth a re-dispatch: device/runtime loss (XLA surfaces
    dead devices as runtime errors), never semantic errors (bind, OCC
    serialization, resource refusals). InjectedFault device-loss seams
    (names containing 'device_lost') count — that is how the virtual CPU
    mesh provokes a loss deterministically."""
    name = type(e).__name__
    if "XlaRuntimeError" in name or "JaxRuntimeError" in name:
        return True
    return "device_lost" in str(e)


def run_with_retry(fn: Callable, retries: int = 1,
                   backoff_s: float = 0.5,
                   on_retry: Optional[Callable] = None,
                   max_backoff_s: float = 5.0,
                   budget_s: float = 0.0,
                   jitter: float = 0.5,
                   recoverable_fn: Optional[Callable] = None) -> object:
    """Re-dispatch on device/runtime failure (the recovery model: stateless
    segments over immutable storage → failed statements simply re-run;
    mid-statement checkpoints make the re-run incremental,
    exec/recovery.py).

    - backoff between attempts is EXPONENTIAL with up to ``jitter``
      proportional randomization (a lost device fails every statement on
      it at once — synchronized retries would stampede the survivors),
      capped at ``max_backoff_s``;
    - ``budget_s`` is the per-statement retry budget: once that much
      wall clock has gone to failed attempts + backoff, the next
      recoverable failure raises instead of retrying (0 = no budget);
    - the backoff honors the statement lifecycle: it waits on the
      current statement's cancel token (interruptible — a cancel or
      watchdog timeout cuts it short), never sleeps past the deadline,
      and re-checks the deadline before dispatching the next attempt, so
      an in-progress recovery counts as LIVENESS while the DEADLINE
      stays enforced (lifecycle.py Watchdog contract);
    - ``on_retry(exc, backoff_s)`` runs between attempts — the Session
      passes its probe-and-degrade hook there (fts.c probe →
      configuration update) and surfaces both args in the activity row;
    - ``recoverable_fn`` overrides the re-dispatch classifier — the
      Session widens it for statements whose pinned topology epoch was
      cut over mid-flight (parallel/topology.py): a flip between plan
      and launch can surface as a shape error rather than device loss,
      and re-planning at the new epoch is exactly the recovery.
    """
    import random

    rec = recoverable if recoverable_fn is None else recoverable_fn
    t0 = time.monotonic()
    last: Exception | None = None
    for attempt in range(retries + 1):
        try:
            return fn()
        except Exception as e:  # noqa: BLE001
            if not rec(e) or attempt == retries:
                raise
            if budget_s and time.monotonic() - t0 >= budget_s:
                raise
            last = e
            delay = min(backoff_s * (2 ** attempt)
                        * (1.0 + jitter * random.random()),
                        max_backoff_s)
            if on_retry is not None:
                on_retry(e, delay)
            from cloudberry_tpu.lifecycle import current_handle
            from cloudberry_tpu.obs import trace as OT

            h = current_handle()
            token = getattr(h, "token", None)
            # the recovery attempt + its backoff are spans on the
            # statement's trace: a recovery storm reads as exactly that
            # in the exported timeline, not as unexplained dead time
            with OT.stage("recovery-backoff", None, attempt=attempt + 1,
                          error=type(e).__name__):
                if token is not None:
                    rem = h.remaining()
                    if rem is not None:
                        delay = min(delay, max(rem, 0.0))
                    if delay > 0:
                        token.wait(delay)
                    # raises StatementTimeout/StatementCancelled when
                    # the deadline passed (or a cancel landed) during
                    # the wait: the statement dies of its deadline, not
                    # as a "hang"
                    h.check()
                elif delay > 0:
                    time.sleep(delay)
    raise last  # unreachable
