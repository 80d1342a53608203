"""Session — the QD (query dispatcher) analog.

A Session owns a catalog, a config, and a device mesh; ``sql()`` runs the full
pipeline: parse → bind/plan (motion insertion) → compile → execute. The
reference's equivalent surface is a libpq connection to the coordinator
backend (exec_simple_query, src/backend/tcop/postgres.c:1655); here it is an
in-process Python API (the serving layer comes later).

The session also owns segment data placement: the analog of the reference's
load-time row routing (cdbhash + jump_consistent_hash, cdbhash.c:55-78),
cached per (table, n_segments) the way segment data lives on segment disks.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import numpy as np

from cloudberry_tpu.config import Config, get_config


from cloudberry_tpu.sql.classify import read_only as _read_only  # noqa: E402
# (the shared classifier: statements safe to re-execute after a device
# failure — re-running a query cannot change state; replayed DML/DDL/COPY
# or nextval() double-applies)


class SerializationError(RuntimeError):
    """COMMIT lost the single-writer OCC race: another session committed a
    conflicting table version after this transaction's BEGIN snapshot."""


@dataclass
class ShardedTable:
    """Host-side sharded layout: per-column (n_segments, capacity) arrays
    padded to the largest shard's rung, plus true per-segment row counts."""
    columns: dict[str, np.ndarray]
    counts: np.ndarray          # (n_segments,) int64
    capacity: int
    replicated: bool
    version: int


class Session:
    def __init__(self, config: Config | None = None):
        from cloudberry_tpu.catalog.catalog import Catalog

        self.config = config or get_config()
        self.catalog = Catalog()
        # durable storage: register stored tables cold (schema/stats only),
        # then bind the catalog so new tables persist (order matters: the
        # registration itself must not write empty snapshots)
        self.store = None
        if self.config.storage.root:
            from cloudberry_tpu.storage.table_store import TableStore

            self.store = TableStore(self.config.storage.root)
            self.store.rows_per_partition = \
                self.config.storage.rows_per_partition
            self.store.quota_bytes = self.config.storage.quota_bytes
            self.store.verify_checksums = \
                self.config.storage.verify_checksums
            if self.config.storage.encryption_key:
                from cloudberry_tpu.utils.tde import make_cipher

                self.store.cipher = make_cipher(
                    self.config.storage.encryption_key)
            for name in self.store.table_names():
                self.store.register_cold(self.catalog, name)
            self.catalog.store = self.store
            from cloudberry_tpu.plan.matview import load_defs

            load_defs(self)
        # per-query pruned store reads, keyed (table, version, parts, cols)
        self._store_scan_cache: dict = {}
        # guards the scan cache's LRU mutations (hits reorder the dict,
        # and shared-session server mode runs concurrent readers)
        self._store_scan_lock = __import__("threading").Lock()
        self._sync_lock = __import__("threading").Lock()
        self._shard_cache: dict[str, ShardedTable] = {}
        # query_info_collect_hook analog: callables receiving QueryMetrics
        self.metrics_hooks: list = []
        from cloudberry_tpu.exec.resource import (AdmissionGate,
                                                  QueueManager, VmemTracker)

        self._gate = AdmissionGate(self.config.resource.max_concurrency)
        # resource queues + engine-wide vmem red line (resqueue.c /
        # vmem_tracker.c analogs, exec/resource.py)
        self._queues = QueueManager()
        self._vmem = VmemTracker(self.config.resource.total_mem_bytes)
        self._stmt_ids = __import__("itertools").count(1)
        # prepared-statement cache: sql text -> (tables, versions, nseg, run)
        # LRU + lock-guarded (the store-scan cache discipline): hits
        # reorder the dict, and shared-session server mode runs
        # concurrent readers
        self._stmt_cache: dict = {}
        self._stmt_lock = __import__("threading").Lock()
        # shared cache tier (sched/sharedcache.py): the generic-plan,
        # capacity-rung, and join-index caches live in an engine-wide
        # SCOPE — sessions over the same durable store share one (tenant
        # B re-binds tenant A's compiled skeleton with zero recompiles),
        # storeless sessions get a private scope (pre-tier behavior).
        # The _generic_cache/_rung_cache properties below are views into
        # it so existing callers and tests keep working.
        from cloudberry_tpu.sched import sharedcache

        self._cache_scope = sharedcache.scope_for(self)
        # counts-only shard layout (planning fast path; sharded_table
        # materializes the actual arrays for execution)
        self._shard_count_cache: dict = {}
        # spill diagnostics for the LAST statement (None = not tiled)
        self.last_tiled_report = None
        # adaptive-capacity growths this session (expansion-overflow
        # recoveries, exec/executor.py:grow_expansion) — observability for
        # skew tests and EXPLAIN ANALYZE consumers
        self.growth_events = 0
        # statement history + active registry (pg_stat_activity / log
        # collector analog); a server shares ONE across its connection
        # sessions (serve/server.py:_connection_session)
        from cloudberry_tpu.exec.instrument import StatementLog

        self.stmt_log = StatementLog()
        # observability plane (cloudberry_tpu/obs/): the log carries the
        # engine's metrics registry, trace ring, and statement-stats
        # table; the session's ObsConfig sizes/gates them
        self.stmt_log.configure_obs(self.config.obs)
        # admission circuit breaker (lifecycle.py): K consecutive
        # device-loss recoveries trip writes to read-only-degraded; a
        # server shares ONE across its connection sessions, like the gate
        from cloudberry_tpu.lifecycle import CircuitBreaker

        self._breaker = CircuitBreaker(
            self.config.health.breaker_threshold,
            self.config.health.breaker_cooldown_s)
        # mid-statement recovery checkpoints (exec/recovery.py): the
        # tiled executors snapshot carried state every K tiles here, and
        # a device-loss retry resumes from the last snapshot instead of
        # replaying the whole stream; statement-scoped — discarded when
        # the statement finishes
        from cloudberry_tpu.exec.recovery import RecoveryStore

        self._recovery = RecoveryStore(
            self.config.recovery.max_statements,
            max_bytes=self.config.recovery.max_bytes,
            log=self.stmt_log)
        self._session_id = id(self) & 0xFFFF
        # versioned topology (parallel/topology.py): every statement
        # pins the current TopologyEpoch at dispatch; expand/shrink
        # creates a successor epoch (online rebalance + cutover) instead
        # of mutating the mesh in place. A server shares ONE manager
        # across its connection backends, like the breaker and the
        # recovery store.
        from cloudberry_tpu.parallel.topology import TopologyManager

        self._topology = TopologyManager(self)
        # feedback-driven re-optimization (plan/feedback.py): learned
        # per-(table, key-set) sketches folded from live motion stats.
        # The store is scope-anchored (shared across sessions of a store
        # root); the VIEW is stamped on the catalog so cost/memo code
        # that only sees the catalog can consult sketches.
        from cloudberry_tpu.plan import feedback as FB

        fb_store = FB.store_for(self)
        if fb_store is not None:
            self.catalog._feedback = FB.FeedbackView(fb_store, self)
        # planck verifications still owed after a topology adoption
        # (config.topology.verify_replans): the first fresh plans after
        # a cutover run through the gate even when debug.verify_plans
        # is off
        self._verify_next_plans = 0
        # COPY ... LOG ERRORS row rejects, per table (the error-log /
        # gp_read_error_log analog, cdbsreh.c)
        self.copy_errors: dict[str, list] = {}
        # open parallel retrieve cursors (the endpoint registry analog,
        # cdbendpoint.c EndpointTokenHash) — name -> ParallelCursor
        self.parallel_cursors: dict[str, object] = {}

    # shared-tier views (sched/sharedcache.py): one lock/dict pair per
    # cache per SCOPE — shared across every session of a store scope,
    # private otherwise. Kept as properties so the pre-tier call sites
    # (paramplan, tests, degrade_mesh) stay unchanged.
    @property
    def _generic_cache(self) -> dict:
        return self._cache_scope.generic

    @property
    def _generic_lock(self):
        return self._cache_scope.generic_lock

    @property
    def _rung_cache(self) -> dict:
        return self._cache_scope.rung

    @property
    def _rung_lock(self):
        return self._cache_scope.rung_lock

    def retrieve(self, cursor: str, segment: int,
                 limit: int | None = None, token: str | None = None):
        """Drain rows from one endpoint of a PARALLEL RETRIEVE CURSOR
        (the retrieve-mode connection analog, cdbendpointretrieve.c)."""
        from cloudberry_tpu.exec.endpoint import retrieve as _r

        return _r(self, cursor, segment, limit, token)

    def dir_upload(self, table: str, rel: str, data: bytes) -> str:
        """Put a file into a DIRECTORY TABLE (the gpdirtableload role)."""
        from cloudberry_tpu.storage import dirtable as DT

        return DT.upload(self, table, rel, data)

    def dir_read(self, table: str, rel: str) -> bytes:
        """Read one file's content from a DIRECTORY TABLE."""
        from cloudberry_tpu.storage import dirtable as DT

        return DT.read(self, table, rel)

    def dir_remove(self, table: str, rel: str) -> None:
        from cloudberry_tpu.storage import dirtable as DT

        DT.remove(self, table, rel)

    def read_error_log(self, table: str):
        """Rejected rows recorded by COPY ... LOG ERRORS for ``table``
        (the gp_read_error_log() analog): DataFrame of line/errmsg/rawdata."""
        import pandas as pd

        return pd.DataFrame(self.copy_errors.get(table.lower(), []),
                            columns=["line", "errmsg", "rawdata"])

    def sql(self, query: str, _deadline: float | None = None,
            **params: Any):
        """Run one statement with failure recovery (the FTS consumption
        point, fts.c:118): a device/runtime failure probes the devices,
        optionally shrinks the segment mesh to the live count (stateless
        segments — placement re-derives for any n), and re-dispatches.

        ``_deadline`` (monotonic absolute seconds, lifecycle.py): the
        statement's cancellation deadline, checked cooperatively at
        execution seams. ``config.statement_timeout_s`` tightens it;
        the dispatcher/server pass their per-request deadline here so it
        governs EXECUTION, not just queueing. (Underscored so it can
        never shadow a user bind parameter in ``**params``.)"""
        import time as _t

        from cloudberry_tpu import lifecycle
        from cloudberry_tpu.parallel.health import (recoverable,
                                                    run_with_retry)

        h = self.config.health
        log_id = self.stmt_log.begin(query, self._session_id)
        deadline = _deadline
        timeout = self.config.statement_timeout_s
        if timeout:
            t_dl = _t.monotonic() + timeout
            deadline = t_dl if deadline is None else min(deadline, t_dl)
        handle = lifecycle.StatementHandle(log_id, deadline=deadline)
        # statement trace (obs/trace.py): the span tree rides the handle
        # so every thread serving this statement records against it; the
        # sampler (config.obs.trace_sample) bounds tracing under load
        handle.trace = self.stmt_log.start_trace(log_id, query)
        # live progress (obs/progress.py): the tiled executors' tile
        # loops feed it through the same handle channel; meta
        # "progress" and the activity rows read it
        if self.stmt_log.obs_enabled:
            from cloudberry_tpu.obs.progress import Progress

            handle.progress = Progress()
        self.stmt_log.attach(log_id, handle)
        # a served statement joins its wire request (obs/trace.py): the
        # request's spans land on this trace, under this statement id
        from cloudberry_tpu.obs import trace as OT

        OT.adopt_statement(handle)
        t_begin = _t.monotonic()
        is_read = _read_only(query)
        # device-loss recoveries THIS statement needed — the circuit
        # breaker's consecutive-recovery signal; trial = this write is
        # the half-open probe write and owns the breaker verdict
        recoveries = [0]
        t_first_fail = [0.0]
        trial = False
        # the classifier's last verdict was epoch-motivated: counted in
        # on_retry (a verdict on the FINAL attempt raises instead of
        # retrying and must not inflate the counter)
        epoch_retry = [False]

        def on_retry(e, backoff_s=0.0):
            if epoch_retry[0]:
                epoch_retry[0] = False
                self.stmt_log.bump("topo_epoch_retries")
            recoveries[0] += 1
            if not t_first_fail[0]:
                t_first_fail[0] = _t.monotonic()
            if handle.trace is not None:
                handle.trace.attempt = recoveries[0]
            # recovery observability: the activity row shows the attempt
            # count + planned backoff, and the state flips to
            # 'recovering' so a stalled row reads as a retry in
            # progress, not a hang (the watchdog still enforces the
            # DEADLINE — recovery is liveness, not license)
            self.stmt_log.bump("recoveries")
            self.stmt_log.set_state(log_id, "recovering")
            self.stmt_log.annotate(
                log_id, attempts=recoveries[0],
                backoff_s=round(backoff_s, 4),
                last_error=type(e).__name__)
            if h.probe_on_error:
                self._recover_mesh(e)
            # the retry replans at the CURRENT epoch — re-stamp the
            # handle so a later unrelated failure is not misclassified
            # as another topology race (one flip buys one re-dispatch)
            handle.topology_epoch = self._topology.current.epoch_id

        def epoch_recoverable(e):
            """Device loss as always — PLUS any non-semantic failure of
            a read whose pinned topology epoch was cut over mid-flight
            (parallel/topology.py): the flip between plan and launch can
            surface as a shape/compile error rather than device loss,
            and re-dispatching at the new epoch IS the recovery."""
            from cloudberry_tpu.exec.recovery import TileReplan
            from cloudberry_tpu.parallel.topology import \
                TopologyRaceError

            if isinstance(e, TileReplan):
                return False  # the adaptive-replan loop in sql() owns it
            if recoverable(e) or isinstance(e, TopologyRaceError):
                return True
            if isinstance(e, (lifecycle.StatementError,
                              SerializationError)):
                return False
            ep = getattr(handle, "topology_epoch", None)
            if ep is None or ep == self._topology.current.epoch_id:
                return False
            epoch_retry[0] = True
            return True

        # per-statement compile observability: the delta of the engine-wide
        # compile counter over this statement (exact single-threaded; an
        # upper bound under concurrency) — "zero after warmup" is the
        # generic-plan acceptance contract
        compiles_before = self.stmt_log.counter("compiles")
        # per-statement generic-plan observability, same delta discipline
        # as the compile counter: the statements table aggregates the
        # generic-hit rate per skeleton from these (obs/statements.py)
        generic_before = self.stmt_log.counter("generic_hits")
        head = query.lstrip()[:10].split(None, 1)
        is_txn_control = bool(head) and head[0].lower() in (
            "begin", "commit", "rollback", "abort", "start", "end")
        topo_epoch = None
        try:
            # topology pin (parallel/topology.py): the statement runs to
            # completion against this epoch; a concurrent cutover waits
            # for pinned statements (bounded) before flipping, and the
            # pin is what the drain barrier counts. Pinning also ADOPTS
            # a newer epoch into this session first (a backend that
            # missed a flip, or a cross-process `mgmt expand --online`).
            topo_epoch = self._topology.pin(self)
            handle.topology_epoch = topo_epoch.epoch_id
            with lifecycle.statement_scope(handle):
                if not is_read and not is_txn_control:
                    # read-only-degraded admission: an open breaker
                    # refuses writes (retryable) while reads keep
                    # flowing. Transaction control is EXEMPT: it is
                    # host-side only (never dispatches to devices), and
                    # a session must always be able to ROLLBACK out of
                    # an open transaction on a degraded engine
                    trial = self._breaker.check_write()
                # mid-statement adaptive replan (exec/tiled.py
                # SkewSentinel): reads only — a write's tiled subplan
                # must never restart after host-side mutation. The
                # sentinel checks this flag (and its own per-handle
                # replan budget) before raising TileReplan.
                handle.adaptive_ok = is_read
                from cloudberry_tpu.exec.recovery import TileReplan
                adaptations = 0
                while True:
                    try:
                        if h.retries <= 0 or not is_read:
                            # DML/DDL/COPY are NOT retried: a device
                            # failure striking after the host-side
                            # mutation would re-apply the statement on
                            # retry (re-execution is only safe when
                            # re-running cannot change state — the
                            # reference's FTS likewise lets in-flight
                            # write transactions abort rather than
                            # replay them)
                            out = self._sql_once(query, **params)
                        else:
                            def attempt():
                                # a retried attempt is live again: the
                                # activity row leaves 'recovering' when
                                # execution resumes
                                if recoveries[0]:
                                    self.stmt_log.set_state(
                                        log_id, "running")
                                return self._sql_once(query, **params)

                            out = run_with_retry(
                                attempt,
                                retries=h.retries,
                                backoff_s=h.backoff_s,
                                on_retry=on_retry,
                                max_backoff_s=h.backoff_max_s,
                                budget_s=h.retry_budget_s,
                                recoverable_fn=epoch_recoverable)
                        break
                    except TileReplan as e:
                        # NOT a failure (no probe, no backoff, no
                        # breaker signal): the sentinel already folded
                        # the observed sketch and force-checkpointed the
                        # carried state. Evict the cached statement so
                        # the immediate re-dispatch re-plans against the
                        # fresh sketch, owe the plan verifier a pass on
                        # whatever the re-plan produces, and re-run
                        # under the SAME statement handle — the
                        # replanned executable resumes from the
                        # checkpoint (plan_signature excludes motion
                        # choices by design).
                        adaptations += 1
                        if adaptations > self.config.feedback\
                                .max_replans + 1:
                            raise  # belt over the sentinel's budget
                        with self._stmt_lock:
                            self._stmt_cache.pop(
                                self._stmt_cache_key(query, params),
                                None)
                        self._verify_next_plans = max(
                            getattr(self, "_verify_next_plans", 0), 1)
                        self.stmt_log.bump("adaptive_replans")
                        self.stmt_log.set_state(log_id, "replanning")
                        self.stmt_log.annotate(
                            log_id, adaptive_skew=round(e.ratio, 2),
                            replan_at_tile=e.tiles_done)
        except BaseException as e:
            # BaseException too: a Ctrl-C mid-statement must not leave a
            # phantom "running" entry in the shared active registry
            if trial:
                # the half-open trial write failed (loss, semantic error,
                # cancel — any reason): re-arm the cooldown, never wedge
                self._breaker.trial_failed()
            elif recoveries[0]:
                # recovery was attempted but the statement still failed
                # (retries exhausted): a hard outage counts toward the
                # trip threshold exactly like a recovered flap
                self._breaker.record_recovery()
            if isinstance(e, lifecycle.StatementTimeout):
                self.stmt_log.bump("statement_timeouts")
            elif isinstance(e, lifecycle.StatementCancelled):
                self.stmt_log.bump("statement_cancels")
            else:
                from cloudberry_tpu.exec.executor import \
                    DuplicateBuildKeyError

                if isinstance(e, DuplicateBuildKeyError):
                    # the PK-inference violation surfaced by the join's
                    # runtime duplicate check — a counted, semantic
                    # (never-retried) error class of its own
                    self.stmt_log.bump("duplicate_build_key_errors")
            self.stmt_log.finish(log_id, "error",
                                 error=f"{type(e).__name__}: {e}")
            # flight recorder (obs/flightrec.py): an erroring statement
            # auto-captures its debug bundle — after finish, so the
            # trace is closed and the bundle ships complete spans
            from cloudberry_tpu.obs import flightrec as OF

            OF.maybe_capture(
                self, query, "error", _t.monotonic() - t_begin, handle,
                params=params, error=e, counters={
                    "compiles": self.stmt_log.counter("compiles")
                    - compiles_before,
                    "generic_hits": self.stmt_log.counter("generic_hits")
                    - generic_before,
                    "recoveries": recoveries[0]})
            raise
        finally:
            # statement-scoped checkpoints die with their statement:
            # success consumed them, and a semantic failure must not
            # leak state to whatever reuses the log id space later
            self._recovery.discard(log_id)
            if topo_epoch is not None:
                self._topology.unpin(topo_epoch)
        if trial:
            self._breaker.trial_succeeded()
        if recoveries[0]:
            self._breaker.record_recovery()
            # recovery latency observability: wall clock from the first
            # device-loss failure to the statement completing
            self.stmt_log.bump(
                "recovery_wall_ms",
                int((_t.monotonic() - t_first_fail[0]) * 1000))
        else:
            self._breaker.record_success()
        is_batch = hasattr(out, "num_rows")
        compiles_d = self.stmt_log.counter("compiles") - compiles_before
        generic_d = self.stmt_log.counter("generic_hits") - generic_before
        self.stmt_log.finish(
            log_id, "ok" if is_batch else str(out)[:80],
            rows=out.num_rows() if is_batch else -1,
            compiles=compiles_d, generic_hits=generic_d)
        # flight recorder (obs/flightrec.py): a statement crossing
        # config.obs.slow_ms auto-captures its debug bundle — including
        # the result digest tools/flight_replay.py re-checks offline
        from cloudberry_tpu.obs import flightrec as OF

        OF.maybe_capture(
            self, query, "ok", _t.monotonic() - t_begin, handle,
            params=params, result=out if is_batch else None,
            counters={"compiles": compiles_d, "generic_hits": generic_d,
                      "recoveries": recoveries[0]})
        return out

    def _recover_mesh(self, e: Exception) -> None:
        """Between-retry hook: probe every device; when any are gone,
        re-derive the mesh over the SURVIVORS (probeWalRepUpdateConfig
        analog — except nothing promotes: placement is recomputed). A
        real loss leaves a hole mid-list, so the survivor indices matter,
        not just the count (segment_mesh skips the dead device)."""
        from cloudberry_tpu.parallel.health import probe

        r = probe()
        if self.config.health.degrade and r.live:
            self.degrade_mesh(len(r.live), r.live)
        # failover-as-shrink (parallel/topology.py): the probe result
        # also feeds the persistence detector — the SAME survivor set
        # observed config.topology.promote_after times promotes this
        # per-statement degrade to a formal shrink epoch, and recovery
        # triggers the symmetric expand back. Called OUTSIDE
        # degrade_mesh's sync lock (lock-order discipline).
        self._topology.note_probe(r)

    def degrade_mesh(self, n_devices: int, live_ids=None) -> bool:
        """Shrink the segment mesh to ``n_devices`` (over ``live_ids``
        when given) and invalidate every placement/plan cache. Derived
        placement (jump hash over shared storage) makes this a pure
        recompute — no data movement protocol, the reference's
        gprecoverseg/rebalance role collapses into cache invalidation.

        Versioned (parallel/topology.py): the degrade MINTS a 'degrade'
        TopologyEpoch FIRST, then adopts it (config swap + cache
        clears). Mint-before-swap matters: a statement pinning in the
        window sees the new epoch and adopts the shrunken config — the
        old ordering let a racing pin re-impose the previous epoch's
        config on top of the degrade, yielding mixed-shape plans; and
        the moved epoch token is what lets a statement that raced the
        swap re-dispatch (epoch_recoverable) instead of surfacing a
        shape error."""
        cur = self._topology.current
        n = max(1, min(cur.nseg, n_devices))
        ids = None
        if live_ids is not None:
            l = list(live_ids)
            if len(l) > n:
                # more survivors than segments: the first n suffice,
                # and an unchanged prefix keeps caches valid
                l = l[:n]
            if l != list(range(n)):
                ids = l  # a hole mid-list: the mesh must skip dead ones
        ep = self._topology.note_degrade(n, ids)
        if ep is not None:
            self._topology._adopt(self, ep)
            return True
        # the epoch already reflects this loss (another backend minted
        # it): THIS session may still be on the old config — adopt the
        # current epoch so the retry replans on the survivor mesh
        # instead of re-failing at the dead size every attempt
        cur = self._topology.current
        if cur.nseg == n and (cur.device_ids or None) == \
                (tuple(ids) if ids else None):
            return self._topology._adopt(self, cur)
        return False

    @staticmethod
    def _dispatch_seams(fault_point) -> None:
        """The two seams every dispatch path hits: dispatch_start (not
        retriable) and exec_device_lost (retriable via health.recoverable
        — the virtual mesh cannot lose a real device; this seam can).
        The cancel check AFTER them gates dispatch: an already-expired or
        cancelled statement never launches (the dispatcher's
        deadline-before-dispatch discipline, now for every path)."""
        from cloudberry_tpu.lifecycle import check_cancel

        fault_point("dispatch_start")
        fault_point("exec_device_lost")
        check_cancel()

    @staticmethod
    def _stmt_cache_key(query: str, params: dict) -> str:
        """Statement-cache key: the SQL text PLUS the user-supplied
        ``sql(query, **params)`` arguments — two calls with the same text
        but different params must never share a cached runner (the
        reference's plan cache likewise keys prepared statements on their
        parameter signature)."""
        if not params:
            return query
        return query + "\x00" + repr(sorted(params.items()))

    def _sql_once(self, query: str, **params: Any):
        from cloudberry_tpu.exec.resource import check_admission
        from cloudberry_tpu.obs import trace as OT
        from cloudberry_tpu.plan.planner import plan_statement
        from cloudberry_tpu.sql.parser import parse_sql
        from cloudberry_tpu.utils.faultinject import fault_point

        hit = None
        with OT.stage("bind", host=True, lookup=True) as lookup:
            self._sync_store()
            self.last_tiled_report = None  # set again by a tiled runner
            ckey = self._stmt_cache_key(query, params)
            cached = self._cached_statement(ckey)
            if cached is None and self.config.sched.generic_plans:
                # literal template (sched/paramplan.py): a text whose
                # skeleton has an armed template binds its literals
                # here, without parse or plan
                from cloudberry_tpu.sched import paramplan

                if not params:
                    hit = paramplan.template_bind(self, query)
                    if hit is not None:
                        lookup.args["template"] = True
                elif paramplan.param_head(query):
                    self.stmt_log.bump("template_refused.user_params")
        if hit is not None:
            from cloudberry_tpu.exec.executor import ExecError

            gp, bindings = hit
            try:
                return self._dispatch_cached(
                    lambda: gp.run(self, gp.plan, gp.keyed, bindings),
                    gp.est_bytes, gp.est_bytes)
            except ExecError:
                # what _run_with_growth answers by growing the plan: the
                # full path below owns that, once
                self.stmt_log.bump("template_fallbacks")
        if cached is not None:
            self.stmt_log.bump("stmt_cache_hits")
            return self._dispatch_cached(*cached)

        with OT.stage("parse", host=True):
            stmt = parse_sql(query)
        # the config this statement PLANS under: a topology cutover
        # swapping it before execute/cache makes the plan's baked
        # capacities stale — the executors below refuse with the
        # retryable TopologyRaceError instead of tracing (or caching) a
        # mixed-shape program (parallel/topology.py)
        cfg_plan = self.config
        with OT.stage("plan", host=True):
            result = plan_statement(stmt, self, params)
        if result.is_ddl:
            return result.ddl_result
        from cloudberry_tpu.exec.resource import ResourceError
        from cloudberry_tpu.obs import capacity as OC

        texe = None
        with OT.stage("admit", host=True):
            # the planck gate (config.debug.verify_plans): every plan
            # the planner or memo emitted is verified against the
            # derived-vs-required property rules RIGHT BEFORE compile —
            # a finding is a refusal, not a silently wrong answer at 8
            # segments
            self._verify_plan(result.plan, "session")
            # admission control: memory budget check + queue slot + vmem
            # reservation (vmem-tracker / resqueue analogs,
            # exec/resource.py); an over-budget plan falls back to tiled
            # out-of-core execution (the workfile manager / spill
            # analog, exec/tiled.py) first
            try:
                est = check_admission(result.plan, self)
            except ResourceError as e:
                texe = self._plan_tiled_fallback(stmt, params, result.plan,
                                                 e)
                # a cached executable's report predates the pool's
                # current residency — re-stamp before charging the
                # capacity plane
                texe.refresh_bufpool_charge()
                OC.record_tiled(self.stmt_log, texe.report)
            else:
                # capacity plane: itemized device-byte estimate
                # (intermediates + wire buffers + rung capacities) for
                # every fresh plan
                OC.record_statement(self.stmt_log, result.plan, self,
                                    est=est)
            self.stmt_log.bump("dispatches")
            self._dispatch_seams(fault_point)
        if texe is not None:
            with self._slot(self.config.resource.query_mem_bytes):
                return self._run_cached_tiled(ckey, texe, cfg_plan)
        with self._slot(est.peak_bytes) as sid:
            return self._run_with_growth(ckey, query, result.plan, sid,
                                         cfg_plan)

    def _dispatch_cached(self, runner, cost: int, obs_bytes: int):
        """Admit and launch a runner whose plan is already known (a
        statement-cache hit, a literal-template hit): the dispatch count,
        the capacity sample, the fault seams and the cancel check, the
        queue slot, the launch."""
        from cloudberry_tpu.obs import capacity as OC
        from cloudberry_tpu.obs import trace as OT
        from cloudberry_tpu.utils.faultinject import fault_point

        with OT.stage("admit", host=True):
            self.stmt_log.bump("dispatches")
            # capacity plane (obs/capacity.py): the cached DEVICE-BYTE
            # estimate — one histogram sample, no plan walk on the hot
            # path. Kept separate from the admission cost: a tiled
            # runner admits against the whole per-query budget but its
            # measured working set is the step estimate, and feeding the
            # budget constant here would pin the peak gauge at config
            # forever
            OC.observe_stmt_bytes(self.stmt_log, obs_bytes)
            self._dispatch_seams(fault_point)
        with self._slot(cost):
            return self._obs_launch(runner)

    def _plan_tiled_fallback(self, stmt, params, plan, err):
        """The tiled executable for an over-budget plan; where there is
        none, the admission error ``err`` saying why the tiled executor
        declined (``tiled.declined``)."""
        from cloudberry_tpu.exec.tiled import declined, plan_tiled
        from cloudberry_tpu.plan.planner import plan_statement

        texe = plan_tiled(plan, self)
        if texe is None and self.config.planner.enable_memo:
            # the memo's joint order may have put a big relation on a
            # BUILD side (cheap in memory, spill-hostile: tiling streams
            # the probe path only). Re-plan greedy — the fact side stays
            # the stream — and tile that instead; the reference likewise
            # re-plans when a hash join flips to batches (nodeHash.c
            # increase-nbatch). A shallow session clone carries the
            # greedy config so concurrent planners (and the mesh-resize
            # path, which also assigns self.config) never observe the
            # override
            import copy

            clone = copy.copy(self)
            clone.config = self.config.with_overrides(
                **{"planner.enable_memo": False})
            result2 = plan_statement(stmt, clone, params)
            self._verify_plan(result2.plan, "greedy-replan")
            texe = plan_tiled(result2.plan, clone)
            if texe is None:
                raise declined(err, result2.plan) from None
            # the clone only existed to plan greedy: runs must
            # report (last_tiled_report) to the REAL session
            texe.session = self
        if texe is None:
            raise declined(err, plan) from None
        return texe

    def _slot(self, cost: int):
        """The admission gate + queue slot for one statement, entered
        under the ``queue-wait`` stage: from requesting the slot to
        holding it. Yields the statement id ``_admitted`` yields."""
        import contextlib

        from cloudberry_tpu.obs import trace as OT

        @contextlib.contextmanager
        def _cm():
            with contextlib.ExitStack() as held:
                with OT.stage("queue-wait"):
                    held.enter_context(self._gate)
                    sid = held.enter_context(self._admitted(cost))
                yield sid

        return _cm()

    def _obs_launch(self, runner):
        """Run a compiled statement runner under the ``launch`` stage
        (its children record inside run_executable / the tiled loop)."""
        from cloudberry_tpu.obs import trace as OT

        with OT.stage("launch"):
            return runner()

    def _admitted(self, cost: int):
        """Queue slot (bounded active statements, MAX_COST, priority wake
        order) + engine-wide vmem reservation for one statement; yields
        the statement id growth re-reservations key on."""
        import contextlib

        q = self.catalog.resource_queues.get(
            self.config.resource.queue.lower()) \
            or self.catalog.resource_queues["default"]

        @contextlib.contextmanager
        def _cm():
            with self._queues.slot(q, cost, q.priority):
                sid = next(self._stmt_ids)
                self._vmem.reserve(sid, cost)
                try:
                    yield sid
                finally:
                    self._vmem.release(sid)

        return _cm()

    def _run_with_growth(self, ckey: str, query: str, plan,
                         stmt_id: int = 0, cfg_plan=None):
        """Execute; on a detected join-expansion overflow (or a lookup
        join's compaction overflow), grow that capacity (re-checking
        admission) and retry — adaptive capacity, never truncation
        (exec/executor.py:grow_expansion). Growth that blows the
        per-query budget falls back to tiled execution; growth that would
        cross the ENGINE-WIDE vmem red line terminates this statement (the
        runaway_cleaner.c decision).

        This loop is what makes a capacity taken from an estimate safe,
        so the lookup joins' own capacities are stamped here, on the way
        in (plan/joincap.py), and on no plan that runs elsewhere. Their
        overflows do not draw on the six growths of the other buffers:
        each reports the rows that came and is grown to hold them, so a
        join overflows each of its two capacities at most once and the
        plan's joins bound the retries."""
        from cloudberry_tpu.exec.executor import ExecError, grow_expansion
        from cloudberry_tpu.exec.resource import ResourceError, check_admission
        from cloudberry_tpu.plan import joincap

        if self.config.n_segments <= 1:
            joincap.stamp_join_capacities(plan, self.catalog)
            if self.config.debug.verify_plans:
                from cloudberry_tpu.plan.verify import check_plan

                check_plan(plan, self, "join-capacities")
        growths = 0
        while True:
            try:
                return self._execute_and_cache(ckey, query, plan,
                                               cfg_plan)
            except ExecError as e:
                compaction = "compaction overflow" in str(e)
                if not compaction and growths >= 6:
                    raise
                with self._stmt_lock:  # drop the failed runner
                    self._stmt_cache.pop(ckey, None)
                # allow_fallback: should the message's ordinal name no
                # candidate of this plan, blanket growth still
                # guarantees progress here
                if not grow_expansion(plan, str(e), allow_fallback=True):
                    raise
                self.growth_events += 1
                if compaction:
                    self.stmt_log.bump("join_compact_retries")
                else:
                    growths += 1
                from cloudberry_tpu.exec.resource import RunawayError

                try:
                    est = check_admission(plan, self)  # budget-ok growth…
                    self._vmem.grow(stmt_id, est.peak_bytes)  # …red-zone ok
                except RunawayError:
                    raise  # red-zone termination, never a spill case
                except ResourceError as err:
                    from cloudberry_tpu.exec.tiled import declined, plan_tiled

                    joincap.drop(plan)  # a tile is its own capacity
                    texe = plan_tiled(plan, self)  # …or the plan spills
                    if texe is None:
                        raise declined(err, plan) from None
                    return self._run_cached_tiled(ckey, texe, cfg_plan)

    def _check_topology_race(self, cfg_plan) -> None:
        """Refuse to execute (or cache) a plan whose epoch moved under
        it: the baked capacities no longer match placement, and the
        compiled program — or worse, a CACHED one serving later
        statements — would mix shard shapes from two epochs. The
        epoch-race retry replans at the new epoch."""
        if cfg_plan is not None and cfg_plan is not self.config:
            from cloudberry_tpu.parallel.topology import TopologyRaceError

            self.stmt_log.bump("topo_plan_races")
            raise TopologyRaceError(
                "topology epoch changed between plan and execute; "
                "the statement re-plans at the new epoch")

    def _run_cached_tiled(self, ckey: str, texe, cfg_plan=None):
        from cloudberry_tpu.exec import executor as X

        from cloudberry_tpu.obs import capacity as OC
        from cloudberry_tpu.obs import trace as OT

        with OT.stage("bind", host=True):
            self._check_topology_race(cfg_plan)
            names = sorted({s.table_name
                            for s in X.scans_of(texe._whole_plan())})
            if not self._any_external(names):
                report = texe.report
                self._cache_statement(
                    ckey, names, texe.run,
                    self.config.resource.query_mem_bytes,
                    obs_bytes=max(int(report.get("est_step_bytes", 0)),
                                  int(report.get("est_finalize_bytes",
                                                 0))),
                    cfg=cfg_plan)
        out = self._obs_launch(texe.run)

        OC.record_tile_dispatch(self.stmt_log, texe.report)
        return out

    def _any_external(self, names) -> bool:
        # foreign (FDW) and directory tables count: their rows change
        # outside this engine's versioning, so cached programs would
        # replay stale reads
        from cloudberry_tpu.catalog.catalog import unversioned

        return any(unversioned(self.catalog.tables.get(n)) for n in names)

    def _sync_store(self) -> None:
        """Pick up OTHER sessions' committed changes at statement start
        (outside transactions): any table whose store version moved
        re-registers cold; new tables appear, dropped ones vanish. The
        coordinator-catalog analog of the reference's shared catalog —
        manifests ARE the catalog of record."""
        if self.store is None \
                or getattr(self, "_txn_snapshot", None) is not None:
            return
        with self._sync_lock:  # server handler threads share this session
            from cloudberry_tpu.utils.faultinject import fault_point

            fault_point("sync_store")
            # fast path: one epoch read; the per-table walk only runs when
            # SOMETHING changed since this session last looked
            epoch = self.store.epoch()
            if epoch == getattr(self, "_seen_epoch", None):
                return
            self._seen_epoch = epoch
            names = set(self.store.table_names())
            for name in list(self.catalog.tables):
                t = self.catalog.tables[name]
                if t.backing is None:
                    continue
                if name not in names:
                    del self.catalog.tables[name]
                    self.catalog.bump_ddl()
                    continue
                v = self.store.current_version(name)
                if v != getattr(t, "_store_version", None):
                    del self.catalog.tables[name]
                    self.store.register_cold(self.catalog, name)
            for name in sorted(names - set(self.catalog.tables)):
                self.store.register_cold(self.catalog, name)
            # matview definitions are store state too (another session may
            # have created/refreshed one)
            from cloudberry_tpu.plan.matview import load_defs

            self.catalog.matviews = {}
            load_defs(self)

    # ----------------------------------------------------- transactions
    # Single-session transactions over the in-memory catalog: BEGIN
    # snapshots every table's (immutable-once-set) data dict plus deep
    # copies of the mutable string dictionaries and the view registry;
    # ROLLBACK restores and bumps versions so statement caches invalidate.
    # The durable-store analog is TableStore's snapshot manifests (atomic
    # CURRENT commit); this is the session-surface counterpart.

    def txn(self, kind: str) -> str:
        from cloudberry_tpu.columnar.dictionary import StringDictionary
        from cloudberry_tpu.plan.binder import BindError

        snap = getattr(self, "_txn_snapshot", None)
        if kind == "begin":
            if snap is not None:
                raise BindError("already in a transaction")
            import copy

            self._txn_snapshot = {
                "tables": {
                    name: (t, t.data,
                           {c: StringDictionary(d.values)
                            for c, d in t.dicts.items()},
                           t.policy, dict(t.validity), t.cold,
                           copy.deepcopy(t.stats))
                    for name, t in self.catalog.tables.items()},
                "views": dict(self.catalog.views),
                "matviews": dict(self.catalog.matviews),
            }
            if self.store is not None:
                # durable writes defer to COMMIT; ROLLBACK never touches
                # disk. The BEGIN snapshot's versions are the OCC base.
                self.store.begin_txn()
                self._txn_base = dict(self.store.pinned)
            return "BEGIN"
        if snap is None:
            raise BindError(f"{kind.upper()}: no transaction in progress")
        if kind == "commit":
            if self.store is not None:
                # OCC commit (the 2PC-role analog, cdbtm.c:883): first
                # committer wins for REWRITES; append-only writes merge
                # onto the concurrent snapshot instead of aborting (the
                # concurrent-DML capability of the reference's GDD). The
                # store lock makes check-then-publish atomic ACROSS
                # PROCESSES — and because it is the ONLY commit-time lock
                # and conflicts abort rather than wait, no waits-for cycle
                # can form: the no-deadlock argument that replaces the
                # reference's global deadlock detector (gdd/README.md).
                with self.store.lock():
                    # chaos seam inside the commit critical section:
                    # 'sleep' widens the conflict window for race tests,
                    # 'error' exercises in-lock failure cleanup
                    from cloudberry_tpu.utils.faultinject import \
                        fault_point

                    fault_point("occ_commit_window")
                    # cancellation seam: a statement cancelled while
                    # waiting on (or wedged inside) the commit window
                    # aborts cleanly — nothing published, lock released,
                    # RAM state restored (the before-commit-point abort)
                    from cloudberry_tpu import lifecycle

                    try:
                        lifecycle.check_cancel()
                    except lifecycle.StatementError:
                        self.store.abort_txn()
                        self._restore_snapshot(snap)
                        raise
                    base = getattr(self, "_txn_base", {})
                    conflicts = self.store.conflicting_tables(base)
                    if conflicts:
                        self.store.abort_txn()
                        self._restore_snapshot(snap)
                        raise SerializationError(
                            "could not serialize access: table(s) "
                            f"{', '.join(conflicts)} were modified by "
                            "another session after this transaction began")
                    merged = [n for n in list(self.store._txn_dirty)
                              if self.store.txn_append_only(n)
                              and self.store.current_version(n)
                              != base.get(n, 0)]
                    self.store.commit_txn(base)
                # a merged table's RAM copy is missing the other
                # session's rows — drop it so the next statement reloads
                # the merged snapshot from the store
                for name in merged:
                    self.catalog.tables.pop(name, None)
                    self.store.register_cold(self.catalog, name)
                    self.catalog.bump_ddl()
                if getattr(self, "_matviews_dirty", False):
                    # definitions deferred during the transaction flush
                    # only after the data commit succeeded
                    from cloudberry_tpu.plan.matview import _persist_defs

                    self._matviews_dirty = False
                    _persist_defs(self)
            self._txn_snapshot = None
            return "COMMIT"
        # rollback: restore RAM state WITHOUT persisting (the store never
        # saw the transaction's writes); cold tables restore to cold —
        # their placeholder arrays must never overwrite stored data
        if self.store is not None:
            self.store.abort_txn()
        self._restore_snapshot(snap)
        return "ROLLBACK"

    def _restore_snapshot(self, snap) -> None:
        self.catalog.tables = {}
        for name, (t, data, dicts, policy, validity, cold, stats) in \
                snap["tables"].items():
            t.policy = policy
            t._loading = True
            try:
                t.set_data(data, dicts, validity=validity)  # bumps version
            finally:
                t._loading = False
            t.cold = cold
            t.stats = stats  # manifest-derived stats survive (cold tables)
            self.catalog.tables[name] = t
        self.catalog.views = snap["views"]
        self.catalog.matviews = snap.get("matviews", {})
        # rolled-back DML may have advanced view contents/tokens — every
        # view is conservatively stale until refreshed or re-maintained
        from cloudberry_tpu.plan.matview import invalidate_all

        invalidate_all(self)
        self._matviews_dirty = False  # deferred defs die with the rollback
        self.catalog.bump_ddl()
        self._txn_snapshot = None

    # ------------------------------------------------- statement cache
    # The prepared-statement / plan-cache analog: a repeated query string
    # reuses its compiled XLA program as long as every referenced table's
    # data version (and the segment count) is unchanged — shapes are static
    # per version, so reuse is exact, never heuristic.

    def _table_versions(self, names) -> tuple:
        out = []
        for n in names:
            t = self.catalog.table(n)
            out.append((n, getattr(t, "_version", 0),
                        getattr(t, "_stats_version", 0)))
        return tuple(out)

    _STMT_CACHE_MAX = 64

    def _cached_statement(self, ckey: str):
        """(runner, admission cost, obs device-byte estimate) from a
        live cache entry, else None — returned together so the caller
        never re-indexes an entry a concurrent thread may have evicted.
        LRU: a hit moves the entry to the dict's end (under the lock —
        hits MUTATE the dict) so hot prepared statements survive bursts
        of one-off queries."""
        with self._stmt_lock:
            entry = self._stmt_cache.pop(ckey, None)
            if entry is not None:
                self._stmt_cache[ckey] = entry  # LRU touch
        if entry is None:
            return None
        from cloudberry_tpu.exec.udf import registry_version
        from cloudberry_tpu.plan.feedback import feedback_gen

        names, versions, cfg, ddlv, runner, cost, obs_bytes, fbgen = \
            entry
        # ddlv pairs the catalog DDL version with the UDF registry
        # version: re-registering a function must drop plans that baked
        # its OLD results in at bind time. The config IDENTITY check is
        # the config-epoch guard: any with_overrides/degrade_mesh swap
        # (n_segments, packed wire, ...) replaces the frozen tree
        # wholesale, so `is` catches every knob a program may have baked.
        # fbgen is the feedback-store generation the plan was built
        # against: a MATERIAL sketch fold (plan/feedback.py — new
        # observation or >10% drift, never a steady-state re-fold) bumps
        # it, so learned stats reach even statements the cache would
        # otherwise pin to their first plan forever.
        stale = (cfg is not self.config
                 or ddlv != (self.catalog.ddl_version,
                             registry_version())
                 or fbgen != feedback_gen(self))
        if not stale:
            try:
                stale = self._table_versions(names) != versions
            except KeyError:
                stale = True
        if stale:
            with self._stmt_lock:  # free the compiled program
                self._stmt_cache.pop(ckey, None)
            return None
        return runner, cost, obs_bytes

    def _execute_and_cache(self, ckey: str, query: str, plan,
                           cfg_plan=None):
        from cloudberry_tpu.exec import executor as X
        from cloudberry_tpu.obs import trace as OT

        with OT.stage("bind", host=True):
            self._check_topology_race(cfg_plan)
            names = sorted({s.table_name for s in X.scans_of(plan)})
            seg = getattr(plan, "_direct_segment", None)
            runner = None
            if self.config.sched.generic_plans:
                # generic-plan gate (sched/paramplan.py): same-shape
                # statements share one compiled program with literals
                # bound as device inputs — zero recompiles on a skeleton
                # hit
                from cloudberry_tpu.sched import paramplan

                runner = paramplan.generic_runner(self, query, plan)
            if runner is not None:
                pass
            elif seg is not None:
                exe = X.compile_plan(plan, self)
                runner = lambda: X.run_prepared(exe, self, segment=seg)
            elif self.config.n_segments > 1:
                from cloudberry_tpu.exec.dist_executor import \
                    execute_distributed

                fn = self._rung_executable(query, plan, names)
                runner = lambda: execute_distributed(plan, self, fn)
            else:
                exe = X.compile_plan(plan, self)
                runner = lambda: X.run_prepared(exe, self)
            # external tables re-read their source per statement — a
            # cached program would replay the previous read
            if not getattr(plan, "_no_stmt_cache", False) \
                    and not self._any_external(names):
                from cloudberry_tpu.exec.resource import \
                    estimate_plan_memory

                self._cache_statement(
                    ckey, names, runner,
                    estimate_plan_memory(plan).peak_bytes, cfg=cfg_plan)
        return self._obs_launch(runner)

    def _cache_statement(self, ckey: str, names, runner,
                         cost: int = 0, obs_bytes: int | None = None,
                         cfg=None) -> None:
        """``cost`` is the ADMISSION reservation for cache hits;
        ``obs_bytes`` (defaults to cost) is the device-byte estimate the
        capacity plane observes — tiled runners reserve the whole
        budget but measure their step working set. ``cfg`` pins the
        entry to the config the runner's plan was BUILT under (not
        whatever config the session holds at cache time): a topology
        flip between plan and cache must leave an entry the identity
        guard rejects, never one that serves a stale-epoch program."""
        from cloudberry_tpu.exec.udf import registry_version
        from cloudberry_tpu.plan.feedback import feedback_gen

        entry = (
            names, self._table_versions(names),
            cfg if cfg is not None else self.config,
            (self.catalog.ddl_version, registry_version()),
            runner, cost,
            cost if obs_bytes is None else int(obs_bytes),
            feedback_gen(self))
        with self._stmt_lock:
            self._stmt_cache.pop(ckey, None)  # re-insert at the tail
            while len(self._stmt_cache) >= self._STMT_CACHE_MAX:
                # LRU eviction (hits reorder, so the head really is the
                # least recently used) keeps the cache and its pinned
                # XLA programs bounded under literal-inlining workloads
                self._stmt_cache.pop(next(iter(self._stmt_cache)))
            self._stmt_cache[ckey] = entry

    # ----------------------------------------------- capacity-rung cache
    # Redistribute bucket capacities live on a power-of-two rung ladder
    # (plan/distribute.py seeds a rung, skew overflow promotes one —
    # exec/executor.py:grow_expansion). Each rung changes motion buffer
    # SHAPES, hence needs its own compiled SPMD program; this cache keeps
    # every rung's executable for the session so recompiles are bounded
    # by the ladder height per motion shape, and re-promoted statements
    # land on a cached program.

    _RUNG_CACHE_MAX = 32

    def _motion_rung_sig(self, plan) -> tuple:
        from cloudberry_tpu.exec import executor as X
        from cloudberry_tpu.plan import nodes as N

        # joins ride in the signature too: adaptive growth also resizes
        # PJoin.out_capacity (expansion overflow), and a retry must not
        # be served the pre-growth executable
        sig = []
        for n in X.all_nodes(plan):
            if isinstance(n, N.PMotion):
                sig.append((n.kind, n.bucket_cap, n.out_capacity,
                            n.pre_compact, n.host_bucket_cap,
                            n.hier_hosts, n.host_combine))
            elif isinstance(n, N.PJoin):
                sig.append(("join", n.out_capacity, n.probe_capacity))
        return tuple(sig)

    def _rung_executable(self, query: str, plan, names):
        """Compiled distributed program for this plan's motion rungs,
        from the session cache when an equivalent (same statement, same
        table versions, same rung signature) program already exists."""
        from cloudberry_tpu.exec.dist_executor import compile_distributed
        from cloudberry_tpu.exec.udf import registry_version

        # plans that bake per-execution state into the program (folded
        # sequence nextval literals) or read outside the version system
        # (external tables) must compile fresh every time — reusing the
        # executable would replay the baked values
        if getattr(plan, "_no_stmt_cache", False) \
                or self._any_external(names):
            return compile_distributed(plan, self)
        from cloudberry_tpu.sched import sharedcache

        try:
            versions = sharedcache.table_versions(self, names)
        except KeyError:
            return compile_distributed(plan, self)
        # rung programs close over their traced plan, so cross-session
        # reuse demands the plan be a pure function of store content:
        # the scope token pins entries to one catalog generation unless
        # the scope is shared and view-free (sharedcache.rung_scope_token)
        key = (query, self.config.n_segments,
               sharedcache.rung_scope_token(self),
               registry_version(), versions, self._motion_rung_sig(plan))
        with self._rung_lock:
            fn = self._rung_cache.pop(key, None)
            if fn is not None:
                self._rung_cache[key] = fn  # LRU touch
        if fn is not None:
            # the program names its plan's nodes by ordinal, and this
            # signature-equal plan numbers its own alike: motion stats
            # (and the feedback fold behind them) survive the cache hit
            return fn
        fn = compile_distributed(plan, self)
        with self._rung_lock:
            while len(self._rung_cache) >= self._RUNG_CACHE_MAX:
                self._rung_cache.pop(next(iter(self._rung_cache)))
            self._rung_cache[key] = fn
        return fn

    def _verify_plan(self, plan, context: str) -> None:
        """config.debug.verify_plans gate (plan/verify.py): verify a
        freshly planned statement and raise PlanVerifyError with
        node-path findings instead of compiling a broken plan."""
        if plan is None:
            return
        owed = getattr(self, "_verify_next_plans", 0)
        if not self.config.debug.verify_plans and owed <= 0:
            return
        if owed > 0:
            # post-cutover replan window (config.topology.verify_replans):
            # approximate decrement — an extra verification under a
            # concurrent race costs wall clock, never correctness
            self._verify_next_plans = owed - 1
        from cloudberry_tpu.plan.verify import check_plan

        check_plan(plan, self, context)

    def explain(self, query: str) -> str:
        from cloudberry_tpu.sql.parser import parse_sql
        from cloudberry_tpu.plan.planner import plan_statement

        self._sync_store()
        stmt = parse_sql(query)
        result = plan_statement(stmt, self, {}, explain_only=True)
        if result.is_ddl:
            return str(result.ddl_result)
        if self.config.n_segments > 1 \
                and getattr(result.plan, "_direct_segment", None) is None:
            # stamp the verifier's DERIVED distribution on every node
            # so the plan text shows sharding explicitly (``dist:``):
            # the bracketed locus is what the distributor STAMPED, the
            # dist: suffix is what the rule table DERIVES — golden
            # diffs pin both, independently. The annotation walk IS a
            # verification, so the debug gate rides it for free.
            from cloudberry_tpu.plan.verify import (PlanVerifyError,
                                                    annotate_derived)

            findings = annotate_derived(result.plan, self)
            if findings and self.config.debug.verify_plans:
                raise PlanVerifyError(findings, "explain")
        else:
            if self.config.n_segments <= 1:
                # the capacities a send of it would run at: stamped
                # where a statement's retry loop starts, here for display
                from cloudberry_tpu.plan import joincap

                joincap.stamp_join_capacities(result.plan, self.catalog)
            self._verify_plan(result.plan, "explain")
        return result.plan.explain()

    def explain_analyze(self, query: str) -> str:
        """Execute with instrumentation; returns the annotated plan (the
        distributed EXPLAIN ANALYZE analog, explain_gp.c).

        Runs THROUGH the statement pipeline (instrument.run_pipeline):
        lifecycle handle + activity entry, dispatch seams, admission
        gate, and the generic-plan form of the program — the same
        program the serving path runs, with per-node row counts as an
        extra output. Motion nodes annotate with collective launches /
        wire bytes / capacity rung, runtime filters with observed
        jf_rows_in/out, and tiled execution appends its per-tile time
        histogram + checkpoint/resume counters."""
        from cloudberry_tpu.exec.instrument import (
            explain_analyze_text, plan_nodes_in_order, run_pipeline)
        from cloudberry_tpu.plan.planner import plan_statement
        from cloudberry_tpu.sql.parser import parse_sql

        self._sync_store()
        stmt = parse_sql(query)
        result = plan_statement(stmt, self, {})
        if result.is_ddl:
            return str(result.ddl_result)
        if self.config.n_segments <= 1:
            # the program a send of it runs, at the lookup joins' own
            # capacities (plan/joincap.py). This run has no retry loop:
            # like an expansion's, a capacity's overflow is its error,
            # which names the join and the rows that came
            from cloudberry_tpu.plan import joincap

            joincap.stamp_join_capacities(result.plan, self.catalog)
        self._verify_plan(result.plan, "explain-analyze")
        _, metrics, annotations = run_pipeline(result.plan, self, query)
        counts = {id(n): r for n, (_, _, r) in
                  zip(plan_nodes_in_order(result.plan), metrics.node_rows)
                  if r >= 0}
        return explain_analyze_text(result.plan, counts,
                                    metrics.wall_s, metrics.compile_s,
                                    annotations=annotations,
                                    tiled_report=self.last_tiled_report)

    # ------------------------------------------------------- data placement

    def sharded_table(self, name: str) -> ShardedTable:
        t = self.catalog.table(name)
        t.ensure_loaded()  # distributed placement needs whole arrays
        nseg = self.config.n_segments
        key = f"{name}@{nseg}"
        cached = self._shard_cache.get(key)
        version = getattr(t, "_version", t.stats.row_count)
        if cached is not None and cached.version == version:
            return cached

        # validity masks ride as ordinary "$nn:<col>" bool columns so the
        # distributed input plumbing shards them like any other column
        phys_cols = dict(t.data)
        for cname, vm in t.validity.items():
            phys_cols[f"$nn:{cname}"] = np.asarray(vm, dtype=np.bool_)
        if t.policy.kind == "replicated":
            st = ShardedTable(phys_cols, self.shard_counts(name),
                              max(t.num_rows, 1), True, version)
        else:
            assign = t.shard_assignment(nseg)
            # ONE derivation of per-segment counts (shard_counts) feeds
            # both the planner's capacities and this materialization —
            # reusing this call's assignment so rows hash exactly once
            counts = self.shard_counts(name, _assign=assign)
            cap = self.shard_capacity(name)
            cols = {}
            order = np.argsort(assign, kind="stable") if len(assign) else assign
            starts = np.concatenate([[0], np.cumsum(counts)])
            for cname, arr in phys_cols.items():
                buf = np.zeros((nseg, cap), dtype=arr.dtype)
                sorted_arr = arr[order]
                for s in range(nseg):
                    n = counts[s]
                    buf[s, :n] = sorted_arr[starts[s]:starts[s] + n]
                cols[cname] = buf
            st = ShardedTable(cols, counts, cap, False, version)
        # deliberate lock-free publish: key embeds nseg, entry is
        # version-checked on read, and concurrent writers produce
        # identical values (last-writer-wins is idempotent)
        self._shard_cache[key] = st
        return st

    def shard_counts(self, name: str, _assign=None) -> np.ndarray:
        """Per-segment row counts WITHOUT materializing the (nseg, cap)
        shard arrays — the planner (shard capacities, motion sizing)
        only needs the counts; execution materializes via
        sharded_table, which passes its already-computed row assignment
        through ``_assign`` so the per-row hash runs once. ONE
        derivation either way, so the two always agree."""
        t = self.catalog.table(name)
        t.ensure_loaded()
        nseg = self.config.n_segments
        version = getattr(t, "_version", t.stats.row_count)
        key = (name, nseg)
        hit = self._shard_count_cache.get(key)
        if hit is not None and hit[0] == version:
            return hit[1]
        st = self._shard_cache.get(f"{name}@{nseg}")
        if st is not None and st.version == version:
            counts = st.counts  # a materialized layout already knows
        elif t.policy.kind == "replicated":
            counts = np.full(nseg, t.num_rows, dtype=np.int64)
        else:
            assign = t.shard_assignment(nseg) if _assign is None \
                else _assign
            counts = np.bincount(assign, minlength=nseg).astype(np.int64)\
                if len(assign) else np.zeros(nseg, dtype=np.int64)
        # deliberate lock-free publish: version rides the value and all
        # writers derive identical counts — a race only repeats work
        self._shard_count_cache[key] = (version, counts)
        return counts

    def shard_capacity(self, name: str) -> int:
        """The largest shard's rows, rounded up to its rung: the planner's
        scan capacity and the materialized (nseg, cap) arrays' width."""
        from cloudberry_tpu.exec.kernels import row_rung_up

        return row_rung_up(self.shard_counts(name).max(initial=1))
