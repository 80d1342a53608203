"""Resource governance — the vmem-tracker / resource-group analog.

The reference tracks per-segment virtual memory in chunks with a red zone and
a runaway killer (vmem_tracker.c:94, redzone_handler.c, runaway_cleaner.c),
and gates statement admission through a shared slot pool (resgroup.c:135).
Here memory is PREDICTABLE — every node's capacity and column widths are
static at plan time — so governance is:

- a plan-time memory estimator (sum of live intermediate arrays, an upper
  bound analogous to per-operator memory quotas), refusing queries whose
  estimate exceeds ``resource.query_mem_bytes`` BEFORE compiling (the
  admission decision the reference can only make with runtime tracking);
- a concurrency gate (slot pool) limiting simultaneous statements.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass

from cloudberry_tpu.plan import nodes as N


class ResourceError(RuntimeError):
    pass


class RunawayError(ResourceError):
    """A RUNNING statement's adaptive growth crossed the vmem red line —
    it is terminated (runaway_cleaner.c), never spilled."""


class TenantQueueFull(ResourceError):
    """Per-tenant admission refusal: the tenant's bounded request queue
    (or concurrency slot wait) stayed full past the grace period.
    RETRYABLE by taxonomy name (lifecycle._RETRYABLE_NAMES) — the
    refusal is about load, not the statement."""


@dataclass
class TenantGroup:
    """One named workload tenant's resource-group record — the
    resgroup.c analog extended from admission-only to THROUGHPUT
    scheduling (sched/tenancy.py owns the deficit-weighted-round-robin
    pick order and aging; this record is the declared shape plus the
    runtime accounting it schedules with). All mutable fields are
    guarded by the owning TenantScheduler's lock."""

    name: str
    weight: int = 1
    max_concurrency: int = 0        # concurrent statements; 0 = unlimited
    max_queue: int = 64             # bounded queue depth (backpressure)
    # -- runtime state (TenantScheduler's lock) --
    deficit: float = 0.0            # DWRR deficit counter, in requests
    queued: int = 0                 # waiting in this tenant's QUEUE
    waiting: int = 0                # direct-path slot() waiters — kept
    # separate from queued: the two paths would otherwise fight over one
    # counter (slot increments, enqueue overwrites with len(queue))
    running: int = 0                # picked/admitted, not yet finished
    last_pick_t: float = 0.0        # monotonic time of the last pick —
    # the aging channel only serves tenants the scheduler has NOT
    # touched lately (over-age heads alone would turn deep saturation
    # into global FIFO and erase the weights)
    # -- observability counters --
    picks: int = 0                  # requests admitted by the scheduler
    served: int = 0                 # requests finished (ok or error)
    rejected: int = 0               # TenantQueueFull refusals
    aged: int = 0                   # picks forced by the starvation bound
    wait_sum_ms: float = 0.0        # queue-wait accumulation (picked)
    wait_max_ms: float = 0.0
    max_depth: int = 0              # peak queue depth observed


@dataclass
class MemoryEstimate:
    peak_bytes: int
    per_node: list[tuple[str, int]]


def estimate_plan_memory(plan: N.PlanNode) -> MemoryEstimate:
    """Upper-bound device bytes per segment for one query.

    Node capacities are already per-segment after the distribution pass
    (scan capacities are shard capacities, motion capacities are receive
    buffers), so summing capacity × Σ column widths (+ masks) directly gives
    the per-segment bound. An over-estimate (XLA frees fused intermediates)
    but shape-exact — the point is a hard admission bound, not a profile."""
    per_node: list[tuple[str, int]] = []
    total = 0

    def width(node: N.PlanNode) -> int:
        w = 1  # selection mask
        for f in node.fields:
            w += f.type.np_dtype.itemsize
        return w

    def rec(node: N.PlanNode):
        nonlocal total
        b = N.capacity_of(node) * width(node)
        per_node.append((node.title(), b))
        total += b
        for c in node.children():
            rec(c)

    rec(plan)
    return MemoryEstimate(total, per_node)


def check_admission(plan: N.PlanNode, session) -> MemoryEstimate:
    from cloudberry_tpu.utils.faultinject import fault_point

    fault_point("admission_check")
    est = estimate_plan_memory(plan)
    budget = session.config.resource.query_mem_bytes
    if est.peak_bytes > budget:
        top = sorted(est.per_node, key=lambda x: -x[1])[:3]
        raise ResourceError(
            f"query memory estimate {est.peak_bytes >> 20} MiB exceeds the "
            f"per-query budget {budget >> 20} MiB "
            f"(largest nodes: {top}); raise "
            "config.resource.query_mem_bytes or reduce capacities")
    return est


_PRIORITY = {"min": 0, "low": 100, "medium": 200, "high": 300, "max": 400}


@dataclass
class ResourceQueue:
    """A named admission queue (resqueue.c analog): bounded concurrent
    statements, a plan-cost ceiling (here: the memory estimate in bytes —
    the engine's native cost unit), and a backoff.c-style priority weight
    that orders WAITERS (higher priority wakes first)."""

    name: str
    active_statements: int = 0      # 0 = unlimited
    max_cost: int = 0               # bytes; 0 = unlimited
    priority: str = "medium"
    active: int = 0                 # running statements (observability)
    waiting: int = 0


class QueueManager:
    """Slot accounting for every resource queue in one engine process.
    Waiters admit in (priority desc, arrival) order via a per-queue heap —
    the prioritization backoff.c implements with CPU weights, expressed
    here at the admission boundary where this engine schedules work."""

    def __init__(self):
        self._cond = threading.Condition()
        self._seq = 0
        self._waiters: dict[str, list] = {}

    def slot(self, queue: ResourceQueue, cost: int, priority: str,
             timeout_s: float = 60.0):
        import contextlib
        import heapq
        import time as _t

        if queue.max_cost and cost > queue.max_cost:
            raise ResourceError(
                f"resource queue {queue.name!r}: statement cost "
                f"{cost >> 20} MiB exceeds MAX_COST "
                f"{queue.max_cost >> 20} MiB")

        @contextlib.contextmanager
        def _slot():
            if not queue.active_statements:
                with self._cond:
                    queue.active += 1
                try:
                    yield
                finally:
                    with self._cond:
                        queue.active -= 1
                return
            key = None
            with self._cond:
                self._seq += 1
                key = (-_PRIORITY.get(priority, 200), self._seq)
                heap = self._waiters.setdefault(queue.name, [])
                heapq.heappush(heap, key)
                queue.waiting = len(heap)
                end = _t.monotonic() + timeout_s
                try:
                    # admit only when a slot is free AND no better-ranked
                    # waiter exists (priority beats arrival)
                    while queue.active >= queue.active_statements \
                            or heap[0] != key:
                        left = end - _t.monotonic()
                        if left <= 0:
                            raise ResourceError(
                                f"resource queue {queue.name!r}: no slot "
                                f"within {timeout_s:.0f}s "
                                f"({queue.active} active, "
                                f"{len(heap)} waiting)")
                        self._cond.wait(timeout=min(left, 1.0))
                    heapq.heappop(heap)
                finally:
                    if heap and key in heap:
                        heap.remove(key)
                        heapq.heapify(heap)
                    queue.waiting = len(heap)
                    # whoever is next-ranked must learn the head changed
                    # NOW, not on its poll timeout
                    self._cond.notify_all()
                queue.active += 1
            try:
                yield
            finally:
                with self._cond:
                    queue.active -= 1
                    self._cond.notify_all()

        return _slot()


class VmemTracker:
    """Engine-wide memory reservation (vmem_tracker.c + redzone_handler.c
    analog): every admitted statement reserves its plan-time estimate;
    reservations past the red line WAIT (bounded), and a RUNNING statement
    whose adaptive growth (join-expansion retry) would cross the red line
    is TERMINATED — the runaway_cleaner.c decision, made exactly at the
    one point where this engine's memory is not statically predictable."""

    def __init__(self, budget_bytes: int):
        self.budget = budget_bytes
        self.used = 0
        self.by_stmt: dict[int, int] = {}
        self._cond = threading.Condition()

    def reserve(self, stmt_id: int, nbytes: int,
                timeout_s: float = 60.0) -> None:
        import time as _t

        if nbytes > self.budget:
            # can NEVER fit — fail fast instead of holding queue/gate
            # slots for the whole timeout
            raise ResourceError(
                f"vmem red zone: {nbytes >> 20} MiB exceeds the entire "
                f"engine budget {self.budget >> 20} MiB")
        end = _t.monotonic() + timeout_s
        with self._cond:
            while self.used + nbytes > self.budget:
                self._cond.wait(timeout=max(
                    min(end - _t.monotonic(), 1.0), 0.01))
                if _t.monotonic() >= end:
                    raise ResourceError(
                        f"vmem red zone: {nbytes >> 20} MiB reservation "
                        f"cannot fit ({self.used >> 20} MiB of "
                        f"{self.budget >> 20} MiB in use) after "
                        f"{timeout_s:.0f}s")
            self.used += nbytes
            self.by_stmt[stmt_id] = self.by_stmt.get(stmt_id, 0) + nbytes

    def grow(self, stmt_id: int, new_total: int) -> None:
        """Re-reserve a RUNNING statement at a larger estimate; crossing
        the red line terminates THIS statement (it is the runaway — its
        growth, not its admission, broke the budget)."""
        with self._cond:
            cur = self.by_stmt.get(stmt_id, 0)
            if self.used - cur + new_total > self.budget:
                raise RunawayError(
                    "runaway query terminated: adaptive growth to "
                    f"{new_total >> 20} MiB would cross the vmem red "
                    f"zone ({(self.used - cur) >> 20} MiB held by other "
                    f"statements, budget {self.budget >> 20} MiB)")
            self.used += new_total - cur
            self.by_stmt[stmt_id] = new_total

    def release(self, stmt_id: int) -> None:
        with self._cond:
            self.used -= self.by_stmt.pop(stmt_id, 0)
            self._cond.notify_all()


class AdmissionGate:
    """Slot-pool concurrency limit (ResGroupSlotData free list analog).
    Tracks active and peak occupancy so servers/tests can OBSERVE that
    admission control actually bounded concurrency."""

    def __init__(self, max_concurrency: int):
        self._sem = threading.BoundedSemaphore(max_concurrency)
        self.max_concurrency = max_concurrency
        self._lock = threading.Lock()
        self.active = 0
        self.peak = 0
        self.total_admitted = 0

    def __enter__(self):
        acquired = self._sem.acquire(timeout=60.0)
        if not acquired:
            raise ResourceError(
                "admission timeout: all "
                f"{self.max_concurrency} statement slots busy for 60s")
        with self._lock:
            self.active += 1
            self.peak = max(self.peak, self.active)
            self.total_admitted += 1
        return self

    def __exit__(self, *exc):
        with self._lock:
            self.active -= 1
        self._sem.release()
        return False
