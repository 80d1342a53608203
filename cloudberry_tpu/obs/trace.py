"""Statement trace spans — where one statement's time went.

The reference answers "where did the time go" with per-node
Instrumentation shipped QE→QD (cdbexplain_sendExecStats) plus gpperfmon;
here a statement's host-side journey is a SPAN TREE riding the existing
thread-local statement scope (lifecycle.py): the handle a scope installs
carries the statement's ``Trace``, so any seam on any thread — the
session's parse/plan, a dispatcher worker's flush, the tiled step loop,
a recovery backoff — records spans against the statement it is serving
without threading a context object through every signature. Crossing
threads is exactly the lifecycle-handle mechanism: whoever enters a
``statement_scope`` with the handle inherits its trace.

ONE primitive records an interval: ``stage`` (below). It appends the
span to the statement's trace, feeds one registry histogram, holds a
``jax.profiler.TraceAnnotation("cbtpu:<name>", statement_id=...)`` open
on the thread that does the work (so any profiler session carries the
host stages on the profile's own clock, traced statement or not), and —
for stages marked host-only — adds wall minus thread-CPU time to the
wire request's off-CPU accumulator (``Request``).

Span taxonomy (docs/DESIGN.md "Observability"). Top-level stages of one
request, disjoint, histogram family ``stage_seconds.<name>``: wire-in,
parse, plan, admit, queue-wait (dispatch-queue-wait on the batch path),
bind, compile, launch, render, wire-out, wire-flush; roots ``request``
(``request_seconds``) and ``statement``. Children of launch, family
``launch_seconds``: inputs, dispatch, device-wait, fetch (one-shot);
prelude, feed-wait, h2d, tile-step, drain-stall, finalize (tiled). On
the scan reader thread, family ``feed_seconds``: part-read. Also
recovery-backoff, tile-replan, and ``compile`` (xla=True) for every
program JAX hands to the compiler. Spans are Chrome-trace "X"
(complete) events — ts/dur in µs, tid = recording thread — so the
export loads directly into Perfetto / chrome://tracing, where per-tid
time-nesting reproduces the call tree; ``args`` carry ``statement_id``
and ``parent`` (the enclosing open stage on that thread, or the stage
that started the feed for a helper thread).

Bounds: each trace keeps at most ``max_spans`` spans (drops counted on
the trace), and completed traces land in a bounded ring on the shared
StatementLog (``meta "trace"`` reads it newest-first).
"""

from __future__ import annotations

import itertools
import threading
import time

from cloudberry_tpu.lifecycle import _tls as _scope_tls


def current_trace():
    """The executing statement's Trace, from the thread's lifecycle
    scope — None outside a statement or when tracing is off/sampled
    out."""
    h = _handle()
    return getattr(h, "trace", None) if h is not None else None


class Trace:
    """One statement's bounded span collection. Append-only under a leaf
    lock (multiple threads may serve one statement: dispatcher worker,
    handler thread, watchdog)."""

    def __init__(self, statement_id: int, sql: str,
                 max_spans: int = 512, tenant: str | None = None):
        self.statement_id = statement_id
        self.sql = sql[:200]
        self.tenant = tenant
        self.max_spans = max_spans
        self._lock = threading.Lock()
        self._spans: list[tuple] = []
        self.dropped = 0
        self.attempt = 0
        self.t0 = time.perf_counter()
        self.wall_s = 0.0
        self.status = "running"

    def add(self, name: str, t_start: float, dur_s: float,
            args: dict | None = None, sid=None, parent=None) -> None:
        """Record one completed interval (perf_counter seconds); ``sid``
        and ``parent`` join ``args`` as ``statement_id`` / ``parent``.
        Kept raw — this sits on the per-statement hot path; ``export``
        makes the Chrome-trace events."""
        span = (name, t_start, dur_s, threading.get_ident(), args, sid,
                parent)
        with self._lock:
            if len(self._spans) >= self.max_spans:
                self.dropped += 1
                return
            self._spans.append(span)

    def finish(self, status: str) -> None:
        """Close the root span; the statement's whole wall clock."""
        self.status = status
        self.wall_s = time.perf_counter() - self.t0
        self.add("statement", self.t0, self.wall_s,
                 {"sql": self.sql, "status": status,
                  "statement_id": self.statement_id,
                  "tenant": self.tenant, "attempt": self.attempt})

    def export(self) -> dict:
        """JSON-safe export: the ring entry / wire payload."""
        with self._lock:
            raw = list(self._spans)
        spans = []
        # (tid: the thread's ident folded into 31 bits by a prime — a
        # mask would give threads whose stacks lie 16 MiB apart one tid)
        for name, t_start, dur_s, tid, args, sid, parent in raw:
            ev = {"name": name, "ph": "X", "ts": round(t_start * 1e6, 1),
                  "dur": round(dur_s * 1e6, 1), "pid": 1,
                  "tid": tid % 0x7FFFFFFF, "cat": "statement"}
            if sid is not None or parent is not None:
                args = dict(args or (), parent=parent)
                args.setdefault("statement_id", sid)
            if args:
                ev["args"] = args
            spans.append(ev)
        return {
            "statement_id": self.statement_id,
            "sql": self.sql,
            "tenant": self.tenant,
            "status": self.status,
            "wall_s": round(self.wall_s, 6),
            "attempt": self.attempt,
            "spans_dropped": self.dropped,
            "events": spans,
        }


def _handle():
    """The thread's current statement handle (lifecycle.current_handle,
    inlined: this runs twice a stage)."""
    stack = getattr(_scope_tls, "stack", None)
    return stack[-1] if stack else None


try:
    from jax.profiler import TraceAnnotation
except Exception:  # pragma: no cover - profiler API drift
    TraceAnnotation = None

_tls = threading.local()  # .open: this thread's open stages; .request
_now = time.perf_counter
_cpu_now = time.thread_time
# thread CPU time is a system call (6 µs on the chip's host, where the
# wall clock is 0.1 µs): one wire request in CPU_SAMPLE measures it
CPU_SAMPLE = 8
_req_seq = itertools.count()
_HISTS: dict = {}  # (family, stage name) -> histogram name


def current_request():
    """The wire request this thread is serving, or None."""
    return getattr(_tls, "request", None)


def current_stage() -> str | None:
    """Name of the innermost open stage on this thread — what a feed
    hands its helper thread as the ``parent`` of the spans it records."""
    stack = getattr(_tls, "open", None)
    return stack[-1].name if stack else None


class stage:
    """THE way to record a named interval on the calling thread: trace
    span + one histogram sample (``<family>.<name>``, dashes as
    underscores, or ``hist``) + a profiler annotation held open for the
    interval, from ONE pair of clock reads. The engine's log comes from
    the thread's statement handle, else the thread's wire request, else
    ``log``; with none, or ``obs.enabled`` off, the body just runs.

    ``host=True`` marks pure host code (no device or queue wait by
    design): wall minus this thread's CPU time goes to the request's
    off-CPU accumulator, for the requests that sample it. ``since``
    starts the interval's wall clock earlier than the enter (a hand-over
    from another thread). A stage
    nested in one of its own family is taken out of the outer's
    histogram sample (not its span), so a family's sums stay a partition
    of wall time. A plain class, not a generator context manager — this
    sits on the per-statement hot path."""

    __slots__ = ("name", "family", "hist", "host", "args", "t0", "cpu0",
                 "log", "sink", "req", "sid", "parent", "child_s", "ann",
                 "dur")

    def __init__(self, name: str, family: str | None = "stage_seconds",
                 *, hist: str | None = None, host: bool = False,
                 since: float | None = None, log=None, trace=None,
                 request=None, parent: str | None = None, **args):
        self.name = name
        self.family = family
        if hist is None and family is not None:
            hist = _HISTS.get((family, name))
            if hist is None:
                hist = _HISTS[family, name] = \
                    f"{family}.{name.replace('-', '_')}"
        self.hist = hist
        self.host = host
        self.args = args
        self.t0 = since
        self.log = log
        self.sink = trace
        self.req = request
        self.parent = parent
        self.child_s = 0.0
        self.ann = None
        self.dur = 0.0  # the interval's seconds, once it is over

    def _begin(self, annotate: bool) -> bool:
        stack = getattr(_scope_tls, "stack", None)
        h = stack[-1] if stack else None  # lifecycle.current_handle()
        req = self.req
        if req is None:
            req = self.req = getattr(_tls, "request", None)
        log = self.log
        if log is None:
            log = h.log if h is not None else None
            if log is None and req is not None:
                log = req.log
        if log is None or not log.obs_enabled:
            self.log = None
            if self.t0 is None:
                self.t0 = _now()
            return False
        self.log = log
        if h is not None:
            # inside a statement: its trace (None when sampled out)
            self.sid = h.statement_id
            if self.sink is None:
                self.sink = h.trace
        else:
            # around one (wire-in, render, wire-out): the request, which
            # hands the span to the statement's trace once it has one
            self.sid = req.statement_id if req is not None else None
            if self.sink is None:
                self.sink = req
        open_ = getattr(_tls, "open", None)
        if open_ is None:
            open_ = _tls.open = []
        if self.parent is None and open_:
            self.parent = open_[-1].name
        self.cpu0 = None
        if annotate:
            open_.append(self)
            if TraceAnnotation is not None and TraceAnnotation.is_enabled():
                # (a flag test while no profiler session is on)
                self.ann = TraceAnnotation("cbtpu:" + self.name,
                                           statement_id=self.sid or 0)
                self.ann.__enter__()
            if self.host and req is not None and req.cpu:
                self.cpu0 = _cpu_now()
        if self.t0 is None:
            self.t0 = _now()  # last: the body's own time
        return True

    def _end(self, t1: float) -> None:
        dur = t1 - self.t0
        if dur < 0.0:
            dur = 0.0
        self.dur = dur
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
            self.ann = None
        open_ = _tls.open
        if open_ and open_[-1] is self:
            open_.pop()
        family = self.family
        if open_ and family is not None and open_[-1].family == family:
            open_[-1].child_s += dur
        if self.host:
            req = self.req
            if req is not None and req.cpu:
                # signed: where the kernel accounts CPU time by ticks a
                # stage reads 0 or a whole tick, and only the sum is fair
                req.offcpu_s += dur - (_cpu_now() - self.cpu0) \
                    if self.cpu0 is not None else dur
        if self.hist is not None:
            own = dur - self.child_s
            self.log.registry.observe(self.hist, own if own > 0.0 else 0.0)
        if self.sink is not None:
            self.sink.add(self.name, self.t0, dur, self.args or None,
                          self.sid, self.parent)

    def __enter__(self):
        self._begin(True)
        return self

    def __exit__(self, *exc) -> bool:
        t1 = _now()
        if self.log is not None:
            self._end(t1)
        else:
            self.dur = t1 - self.t0
        return False


def stage_since(name: str, t_start: float, t_end: float | None = None,
                family: str | None = "stage_seconds", **kw) -> None:
    """A stage whose interval is already over — a wait measured from
    another thread's timestamp, a duration an API reports after the fact
    (``t_end`` defaults to now). Same span and histogram as ``stage``;
    no profiler annotation can be held open for the past."""
    st = stage(name, family, since=t_start, **kw)
    if st._begin(False):
        st._end(_now() if t_end is None else t_end)


class Request:
    """One wire request, from the line's arrival to its answer's last
    byte: the root span ``request`` (histogram ``request_seconds``), the
    off-CPU seconds its host-only stages add (``host_offcpu_seconds``,
    one sample for each request in ``CPU_SAMPLE``: the others never read
    the thread's CPU clock), and the spans recorded before the statement
    it carries has a trace. Entering installs it as the thread's current
    request; ``Session.sql`` adopts the statement into it."""

    __slots__ = ("log", "t0", "t_queued", "offcpu_s", "cpu", "trace",
                 "statement_id", "_early", "_prev")

    def __init__(self, log, t0: float | None = None):
        self.log = log
        self.t0 = _now() if t0 is None else t0
        self.t_queued = 0.0
        self.offcpu_s = 0.0
        self.cpu = next(_req_seq) % CPU_SAMPLE == 0  # reads thread CPU
        self.trace = None
        self.statement_id = None
        self._early: list | None = []

    def __enter__(self) -> "Request":
        self._prev = getattr(_tls, "request", None)
        _tls.request = self
        return self

    def __exit__(self, *exc) -> bool:
        _tls.request = self._prev
        return False

    def add(self, name: str, t_start: float, dur_s: float,
            args: dict | None = None, sid=None, parent=None) -> None:
        """Span sink for stages outside the statement's scope."""
        if self.trace is not None:
            self.trace.add(name, t_start, dur_s, args, self.statement_id,
                           parent)
        elif self._early is not None and len(self._early) < 8:
            self._early.append((name, t_start, dur_s, args, parent))

    def adopt(self, handle) -> None:
        """The statement this request carries has begun."""
        self.statement_id = handle.statement_id
        self.trace = handle.trace
        early, self._early = self._early, None
        for name, t_start, dur_s, args, parent in early or ():
            self.add(name, t_start, dur_s, args, None, parent)

    def queued(self) -> None:
        """The answer's bytes are with the event loop (which calls
        ``flushed`` when the last one is sent)."""
        self.t_queued = _now()

    def flushed(self) -> None:
        stage_since("wire-flush", self.t_queued, request=self, host=True)
        self.finish()

    def finish(self) -> None:
        log = self.log
        if log is None or not log.obs_enabled:
            return
        dur = _now() - self.t0
        log.registry.observe("request_seconds", dur)
        args = None
        if self.cpu:
            log.registry.observe("host_offcpu_seconds", self.offcpu_s)
            args = {"offcpu_s": round(self.offcpu_s, 6)}
        self.add("request", self.t0, dur, args)


def adopt_statement(handle) -> None:
    """Tie the statement ``handle`` begins to the wire request this
    thread serves (no-op for library callers)."""
    req = getattr(_tls, "request", None)
    if req is not None:
        req.adopt(handle)


# ------------------------------------------------- compiles, by who pays

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_listening = False


def _on_jax_duration(event: str, seconds: float, **_kw) -> None:
    """jax.monitoring listener, run on the compiling thread: every
    program handed to the compiler on any path (one-shot, tiled
    prelude/step/finalize, the pool-hit eager slice, eager jnp helpers)
    bumps ``xla_compiles`` on the engine whose statement or request is
    open on that thread and leaves a ``compile`` span naming it."""
    if event != _COMPILE_EVENT:
        return
    h = _handle()
    req = getattr(_tls, "request", None)
    log = getattr(h, "log", None) or (req.log if req is not None else None)
    if log is None:
        return
    log.bump("xla_compiles")
    now = _now()
    stage_since("compile", now - seconds, now, None,
                hist="xla_compile_seconds", xla=True)
    if TraceAnnotation is not None and log.obs_enabled \
            and TraceAnnotation.is_enabled():
        # the compile is over: a zero-length mark at its end carries its
        # length, so a profile reader can rebuild the interval
        with TraceAnnotation("cbtpu:compile", seconds=seconds,
                             statement_id=getattr(h, "statement_id", 0)
                             or 0):
            pass


def listen_for_compiles() -> None:
    """Register the compile listener once per process (the engine calls
    this when a StatementLog is built)."""
    global _listening
    if _listening:
        return
    _listening = True
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def chrome_trace(exports: list[dict]) -> dict:
    """Assemble ring exports into ONE Chrome-trace JSON document
    (Perfetto-loadable): {"traceEvents": [...]} with every statement's
    events concatenated (ts values share the perf_counter timebase, so
    concurrent statements interleave truthfully)."""
    events = []
    for ex in exports:
        events.extend(ex.get("events", ()))
    return {"traceEvents": events, "displayTimeUnit": "ms"}
