"""Statement lifecycle — cancellation, timeouts, watchdog, breaker.

The reference treats every statement as an interruptible unit:
``statement_timeout`` arms a SIGALRM, ``pg_cancel_backend()`` sets
QueryCancelPending, and executor nodes poll CHECK_FOR_INTERRUPTS() at row
boundaries (src/backend/tcop/postgres.c, miscadmin.h). An XLA program
cannot be interrupted mid-launch, so the poll points move to the HOST-SIDE
seams this engine already owns — the per-tile step loop, the adaptive
grow-and-retry loop, the OCC commit window, the dispatcher flush — which
bound how long a statement can run past its deadline by one device launch.

Pieces:

- a retryable-vs-semantic error taxonomy (``StatementError`` subclasses
  plus a name registry for the sched errors) shared by the server, which
  stamps every wire error with ``retryable``, and the client, which may
  auto-retry idempotent reads;
- ``CancelToken`` / ``StatementHandle``: one per statement, registered in
  the engine's StatementLog (the pg_stat_activity row), cancellable from
  any thread (the pg_cancel_backend analog);
- ``statement_scope`` / ``check_cancel``: a thread-local current-statement
  registry so deep execution seams poll without threading a handle through
  every signature (CHECK_FOR_INTERRUPTS reads a global for the same
  reason);
- ``Watchdog``: a background thread cancelling over-deadline statements —
  the asynchronous SIGALRM role; a statement wedged at a seam that only
  polls its token (the interruptible ``hang`` fault) still dies on time;
- ``CircuitBreaker``: admission breaker that trips to read-only-degraded
  after K consecutive device-loss recoveries and half-opens via health
  probes (the FTS "mark down and stop dispatching" decision, scoped to
  writes — reads stay safe to serve from a flapping mesh because
  re-execution cannot change state).
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional


# ------------------------------------------------------------- taxonomy


class StatementError(RuntimeError):
    """Base of the lifecycle taxonomy. ``retryable`` is the contract the
    serving layer exports on the wire: True means the failure is about
    WHEN the statement ran (load, shutdown, a flapping mesh), so an
    idempotent retry may succeed; False means it is about the statement
    itself (explicitly cancelled, semantically wrong)."""

    retryable = False


class StatementCancelled(StatementError):
    """Explicitly cancelled (the pg_cancel_backend analog) — semantic:
    retrying would defeat the cancel."""

    retryable = False


class StatementTimeout(StatementError):
    """Deadline/statement_timeout exceeded — transient (deadline
    pressure, a wedged seam): a retry under lighter load may fit."""

    retryable = True


class ServerDraining(StatementError):
    """The server refused or abandoned the statement because it is
    draining for shutdown — retry against the promoted standby."""

    retryable = True


class BreakerOpen(StatementError):
    """The admission circuit breaker is open (read-only-degraded):
    writes are refused until health probes close it."""

    retryable = True


class ServerBusy(StatementError):
    """The accept-path connection cap refused the connection (one
    SERVER_BUSY line, then close) — pure load shedding, retry after
    backoff. The server writes this refusal as a dict literal at accept
    time (no exception crosses the wire), but the class must EXIST so
    the by-name contract round-trips: the client retries the etype
    ``ServerBusy`` because this name is in the taxonomy, and graftlint's
    tax-name-unknown rule holds the registry to names that resolve."""

    retryable = True


class IngestQueueFull(StatementError):
    """The streaming ingest buffer for a (table, tenant) is at its
    ``config.ingest.max_buffered_rows`` cap — pure write backpressure,
    the SchedQueueFull analog for the append plane: back off and retry
    once a flush drains the buffer."""

    retryable = True


class StorageIOError(StatementError):
    """A storage write/read failed at the OS layer (ENOSPC, EIO, a torn
    or short write the shim surfaced) — about the ENVIRONMENT the
    statement ran in, not the statement: the commit protocol left the
    previous snapshot intact, so an idempotent retry may succeed once
    the device/space condition clears. Counted in ``storage_io_errors``
    (storage/iofault.py) and breaker-visible like every retryable
    refusal."""

    retryable = True


class StorageCorruptionError(StatementError):
    """Stored bytes failed their content checksum (or a container parsed
    as garbage) — semantic and sticky: retrying re-reads the same bad
    bytes. The read path raises this INSTEAD of returning a wrong
    answer; ``mgmt fsck`` finds the same file offline. The pg_checksums
    verdict class."""

    retryable = False


# errors raised OUTSIDE this module that belong to the retryable side:
# the dispatcher's backpressure/deadline pair (sched/dispatcher.py) and
# the per-tenant admission refusal (exec/resource.py TenantQueueFull)
# are about load and WHEN the statement ran, not the statement itself
_RETRYABLE_NAMES = frozenset({
    "StatementTimeout", "ServerDraining", "BreakerOpen",
    "SchedQueueFull", "SchedDeadline",
    "TenantQueueFull", "ServerBusy", "IngestQueueFull",
    "CompactionError", "StorageIOError",
})


def is_retryable(err) -> bool:
    """One classifier for server and client: accepts an exception or an
    etype name string."""
    if isinstance(err, BaseException):
        if isinstance(err, StatementError):
            return err.retryable
        err = type(err).__name__
    return str(err) in _RETRYABLE_NAMES


# ---------------------------------------------------------- cancel token


_REASON_EXC = {
    "cancelled": StatementCancelled,
    "timeout": StatementTimeout,
    "drain": ServerDraining,
}


class CancelToken:
    """One statement's cancellation flag, settable from any thread.
    First cancel wins; the recorded reason picks which taxonomy error
    the statement's own thread raises at its next poll point."""

    def __init__(self):
        self._event = threading.Event()
        self._lock = threading.Lock()
        self.reason: Optional[str] = None
        self.message: Optional[str] = None

    @property
    def cancelled(self) -> bool:
        return self._event.is_set()

    def cancel(self, reason: str = "cancelled",
               message: Optional[str] = None) -> bool:
        """Request cancellation; returns True if this call was the first
        (later calls never overwrite the reason — the statement dies of
        whatever killed it first)."""
        with self._lock:
            if self._event.is_set():
                return False
            self.reason = reason
            self.message = message
            self._event.set()
            return True

    def raise_if_cancelled(self) -> None:
        if not self._event.is_set():
            return
        exc = _REASON_EXC.get(self.reason or "cancelled",
                              StatementCancelled)
        raise exc(self.message or f"statement {self.reason}")

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._event.wait(timeout)


class StatementHandle:
    """Identity + deadline + token for one executing statement — the
    per-backend PGPROC slot analog. ``deadline`` is a MONOTONIC absolute
    (time.monotonic()), or None for no limit."""

    def __init__(self, statement_id: int,
                 deadline: Optional[float] = None,
                 token: Optional[CancelToken] = None):
        self.statement_id = statement_id
        self.deadline = deadline
        self.token = token if token is not None else CancelToken()
        self.started = time.monotonic()
        # the statement's trace span collection (obs/trace.py), set by
        # whoever begins the statement; spans follow the handle across
        # threads exactly like cancellation does (obs.trace reads it via
        # current_handle())
        self.trace = None
        # the engine's StatementLog, set by its attach(): where
        # obs.trace.stage finds the metrics registry
        self.log = None
        # the statement's live progress gauge (obs/progress.py), set by
        # whoever begins the statement when the telemetry plane is on;
        # the tiled executors' tile loops feed it through the same
        # thread-local scope channel
        self.progress = None

    def remaining(self) -> Optional[float]:
        if self.deadline is None:
            return None
        return self.deadline - time.monotonic()

    def check(self) -> None:
        """The CHECK_FOR_INTERRUPTS analog: raise the taxonomy error when
        cancelled or past deadline. Crossing the deadline here records it
        on the token too, so every other seam (and the wire response)
        agrees on why the statement died."""
        self.token.raise_if_cancelled()
        if self.deadline is not None and time.monotonic() > self.deadline:
            self.token.cancel(
                "timeout",
                f"statement timed out after "
                f"{time.monotonic() - self.started:.2f}s "
                "(deadline/statement_timeout exceeded)")
            self.token.raise_if_cancelled()


# ------------------------------------------------- current-statement scope


class CompositeHandle:
    """Scope handle polling several member handles: the dispatcher's
    stacked batch executes as ONE launch under one scope, but every
    member keeps its own token/deadline — cancelling any member aborts
    the launch, and the dispatcher then re-routes the innocent
    batchmates through the sequential path."""

    def __init__(self, handles):
        self.handles = list(handles)
        # the batch head's trace records the stacked launch's spans (one
        # launch, many statements — attributing it to the head matches
        # how the compile counter attributes batch compiles)
        self.trace = next((h.trace for h in self.handles
                           if getattr(h, "trace", None) is not None),
                          None)
        self.statement_id = next((h.statement_id for h in self.handles),
                                 None)
        self.log = next((h.log for h in self.handles
                         if getattr(h, "log", None) is not None), None)
        # batched statements are stacked point reads — no tile loop, so
        # the composite scope carries no progress feed of its own (each
        # member's Progress still completes at its finish)
        self.progress = None

    def check(self) -> None:
        for h in self.handles:
            h.check()


_tls = threading.local()


class statement_scope:
    """Context manager installing ``handle`` as the thread's current
    statement. Nests (the dispatcher's batch scope around a sequential
    session.sql): inner statements shadow, exit restores."""

    def __init__(self, handle: StatementHandle):
        self._handle = handle

    def __enter__(self) -> StatementHandle:
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self._handle)
        return self._handle

    def __exit__(self, *exc) -> bool:
        _tls.stack.pop()
        return False


def current_handle() -> Optional[StatementHandle]:
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


def check_cancel() -> None:
    """Poll point for execution seams: no-op outside a statement scope
    (library callers without lifecycle management lose nothing), raises
    StatementCancelled/StatementTimeout/ServerDraining inside one."""
    h = current_handle()
    if h is not None:
        h.check()


# --------------------------------------------------------------- watchdog


class Watchdog:
    """Background canceller for over-deadline statements (the SIGALRM /
    statement_timeout enforcement role). Cooperative checks already raise
    at seams that compare the deadline; the watchdog covers statements
    wedged where only the TOKEN is polled (the interruptible ``hang``
    fault point, a blocking wait) and makes the timeout visible in the
    activity view (state flips to 'cancelling') while the serving thread
    survives to run the next statement."""

    def __init__(self, stmt_log, interval_s: float = 0.05):
        self.stmt_log = stmt_log
        self.interval_s = interval_s
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "Watchdog":
        if self._thread is None:
            self._stop.clear()
            self._thread = threading.Thread(target=self._loop, daemon=True,
                                            name="cbtpu-watchdog")
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            self.scan()

    def scan(self) -> int:
        """One pass; returns how many statements it cancelled (exposed
        for deterministic tests)."""
        now = time.monotonic()
        n = 0
        for sid, handle in self.stmt_log.active_handles():
            if handle.deadline is None or now <= handle.deadline \
                    or handle.token.cancelled:
                continue
            if handle.token.cancel(
                    "timeout",
                    f"statement {sid} cancelled by watchdog "
                    f"{now - handle.started:.2f}s after start "
                    "(deadline exceeded)"):
                self.stmt_log.mark_cancelling(sid)
                self.stmt_log.bump("watchdog_timeouts")
                n += 1
        return n


# --------------------------------------------------------- circuit breaker


class CircuitBreaker:
    """Admission breaker over device-loss recoveries (the FTS
    mark-down decision as flow control): K CONSECUTIVE statements that
    needed a device-loss recovery trip it open — the mesh is flapping,
    and a write retried into a flap can neither be replayed (DML is
    never re-dispatched) nor trusted to commit. Open refuses WRITES with
    the retryable BreakerOpen (read-only-degraded: reads stay safe —
    re-execution cannot change state). After ``cooldown_s`` the next
    write HALF-OPENS: one health probe decides — a clean probe lets that
    write through, and its success closes the breaker; a dirty probe
    re-arms the cooldown."""

    def __init__(self, threshold: int = 3, cooldown_s: float = 30.0,
                 probe_fn: Optional[Callable] = None):
        self.threshold = threshold
        self.cooldown_s = cooldown_s
        self._probe_fn = probe_fn
        self._lock = threading.Lock()
        self.state = "closed"            # closed | open | half-open
        self.consecutive = 0
        self.trips = 0
        self._opened_at = 0.0

    def _probe(self):
        if self._probe_fn is not None:
            return self._probe_fn()
        from cloudberry_tpu.parallel.health import probe

        return probe()

    def record_recovery(self) -> None:
        """One statement needed a device-loss recovery — counted whether
        the statement ultimately succeeded or exhausted its retries (a
        hard outage must trip the breaker too, not just a flap mild
        enough for retries to win)."""
        with self._lock:
            self.consecutive += 1
            if self.state == "closed" and self.threshold \
                    and self.consecutive >= self.threshold:
                self.state = "open"
                self._opened_at = time.monotonic()
                self.trips += 1

    def record_success(self) -> None:
        """One statement completed without needing recovery. Resets the
        streak when closed; a half-open breaker is NOT closed here —
        only the trial write's own success closes it (a concurrent read
        succeeding proves nothing about writes on a flapping mesh)."""
        with self._lock:
            if self.state == "closed":
                self.consecutive = 0

    def check_write(self) -> bool:
        """Admission gate for a write statement. Returns True when this
        write is the half-open TRIAL: the caller owns the verdict and
        MUST report it back via trial_succeeded()/trial_failed()."""
        with self._lock:
            if self.state == "closed":
                return False
            if self.state == "half-open":
                # another write is mid-trial; stay degraded until it lands
                raise BreakerOpen(
                    "circuit breaker half-open: a trial write is in "
                    "flight; retry shortly")
            if time.monotonic() - self._opened_at < self.cooldown_s:
                raise BreakerOpen(
                    "circuit breaker open after "
                    f"{self.consecutive} consecutive device-loss "
                    "recoveries: engine is read-only-degraded; retry "
                    f"after the {self.cooldown_s:.0f}s cooldown")
            self.state = "half-open"
        # a RAISING probe counts as a failed one: the half-open slot
        # must always resolve (back open with a fresh cooldown), never
        # wedge waiting for a trial that no longer exists
        try:
            r = self._probe()
            detail = getattr(r, "error", None)
        except Exception as e:  # noqa: BLE001 — the probe IS the verdict
            r, detail = None, f"probe raised {type(e).__name__}: {e}"
        if getattr(r, "ok", False):
            return True  # this write is the trial
        with self._lock:
            self.state = "open"
            self._opened_at = time.monotonic()
        raise BreakerOpen(
            "circuit breaker: health probe failed during half-open "
            f"({detail}); staying read-only-degraded")

    def trial_succeeded(self) -> None:
        with self._lock:
            if self.state == "half-open":
                self.state = "closed"
                self.consecutive = 0

    def trial_failed(self) -> None:
        """The trial write failed for ANY reason (device loss, semantic
        error, cancellation): back to open with a fresh cooldown — the
        half-open slot must never wedge waiting for a verdict that
        already arrived."""
        with self._lock:
            if self.state == "half-open":
                self.state = "open"
                self._opened_at = time.monotonic()

    def snapshot(self) -> dict:
        with self._lock:
            return {"state": self.state,
                    "consecutive_recoveries": self.consecutive,
                    "trips": self.trips,
                    "threshold": self.threshold,
                    "cooldown_s": self.cooldown_s}
