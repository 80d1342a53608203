"""Closed-loop serving benchmark — QPS/latency for the micro-batch
dispatcher vs one-at-a-time dispatch (the ISSUE-3 acceptance harness).

N simulated clients hammer a Server over the wire protocol with a
statement mix; each mode runs the SAME closed loop and the CSV rows make
the comparison direct:

    mode,mix,clients,duration_s,requests,qps,p50_ms,p99_ms,compiles,\
dispatches,batches,batched_requests,avg_occupancy,deadline_misses,\
cancels,recovery_count,tiles_replayed,recovery_ms,tenant,tenant_qps,\
tenant_p50_ms,tenant_p99_ms,tenant_queue_depth,fairness_index

Small runs drive one OS thread per client; large runs (or any --tenants
run) multiplex the clients over a few selector driver threads, each
connection an INDEPENDENT closed loop — that is how the bench sustains
1000+ simulated clients against the event-loop serving core
(serve/asyncore.py). With --tenants, requests carry tenant names, the
server schedules them deficit-weighted-round-robin (sched/tenancy.py),
and each tenant gets its own CSV row (per-tenant QPS / p50 / p99 /
peak queue depth) under the aggregate's fairness_index (Jain's index
over weight-normalized picks; 1.0 = throughput exactly proportional to
weight).

- ``direct``  — dispatcher off: every request is its own parse→(generic
  rebind)→launch through the shared session.
- ``batched`` — dispatcher on (config.sched.enabled): same-skeleton
  requests coalesce per tick into one stacked vmapped launch.

Mixes:
- ``point`` — repeated point lookups with rotating literals
  (``SELECT k, v, w FROM pts WHERE k = <r>``): the prepared-statement
  serving shape; generic plans make it compile-free, the dispatcher makes
  it launch-amortized.
- ``q6``    — a parameterized TPC-H-Q6-shaped aggregate over a synthetic
  lineitem slice with rotating predicate literals.
- ``mixed`` — 80% point / 20% q6.
- ``coldscan`` — 1-in-8 requests run a long COLD tiled aggregate (the
  catalog is store-backed and the budget shrunk, so ``li`` streams
  micro-partition files through the scan pipeline, exec/scanpipe.py)
  while the rest stay point lookups: the multi-tenant starvation case —
  long out-of-core statements competing with latency-sensitive points.
  Pair with --tenants to read the fairness columns under it.
- ``hotcold`` — the HBM buffer-pool serving workload (ISSUE 16): a
  store-backed HOT table scanned by the SAME tiled aggregate on most
  requests (from the third scan the pool serves its tiles from device
  memory at zero host reads/decodes) against a same-shape COLD table
  scanned with rotating literals under a pool budget sized to hold only
  the hot set (the cold set is refused over evicting hotter, then
  churns). The bufpool_hit_rate / host_decodes CSV columns report the
  run's counter deltas, and an after-window probe times one pool-warm
  hot scan vs one cold scan on the same container size — printed as a
  rows/s comparison with the hot probe's host-decode count (zero when
  the claim holds).
- ``readwrite`` — the write-plane workload (ISSUE 18): 1-in-4 requests
  are wire-level APPENDs into a store-backed table through the
  streaming ingest plane (group-committed INSERT flushes) while the
  rest stay point lookups, with the background compaction service
  enabled and folding the append debt DURING the measured window. The
  ingest_qps / flush_ms_p95 / compact_chunks / delta_parts_max CSV
  columns report the write plane's side of the run; the read QPS
  column is the bench's pin that foreground serving holds up while
  compaction runs.

Runs on CPU (JAX_PLATFORMS=cpu) for CI smoke; on real hardware the launch
amortization grows with dispatch overhead. Usage:

    python tools/serve_bench.py --mode both --mix point --clients 8 \
        --duration 5 --csv out.csv
"""

from __future__ import annotations

import argparse
import json
import os
import selectors
import socket
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CSV_HEADER = ("mode,mix,clients,duration_s,requests,qps,p50_ms,p99_ms,"
              "compiles,dispatches,batches,batched_requests,avg_occupancy,"
              "deadline_misses,cancels,recovery_count,tiles_replayed,"
              "recovery_ms,tenant,tenant_qps,tenant_p50_ms,tenant_p99_ms,"
              "tenant_queue_depth,fairness_index,"
              # ISSUE 9: server-side latency percentiles from the obs
              # registry's statement_seconds histogram (engine clocks,
              # not client clocks) + per-stage time shares + sampled
              # trace span counts
              "srv_p50_ms,srv_p95_ms,srv_p99_ms,queue_wait_share,"
              "compile_share,launch_share,render_share,trace_spans,"
              # ISSUE 12 (capacity & forensics plane): flight-recorder
              # captures over the run (--slow-ms arms the threshold),
              # skew alarms from the motion telemetry, and the peak
              # per-statement device-byte estimate
              "flight_captures,skew_events,peak_stmt_mb,"
              # ISSUE 13 (online topology changes): --expand-at /
              # --shrink-at land an epoch-versioned resize mid-load —
              # cutover wall clock, rows the background rebalancer
              # moved (jump-hash minimal delta), and epoch flips over
              # the run (failover promotions included)
              "cutover_ms,moved_rows,epoch_flips,"
              # ISSUE 16 (HBM buffer pool): pool hit rate over the
              # run's store scans (bufpool_hits / lookups) and host
              # decode count — under --mix hotcold the hot set's
              # repeats are served from device memory, so decodes
              # track the COLD set only
              "bufpool_hit_rate,host_decodes,"
              # ISSUE 17 (feedback-driven re-optimization):
              # mid-statement adaptive replans taken over the window
              # and capacity rungs the learned sketches priced down
              # from the static estimate on repeat statements
              "adaptive_replans,rung_downgrades,"
              # ISSUE 18 (write plane): appends/s accepted by the
              # streaming ingest buffers over the window, the p95 group
              # flush commit latency, compaction chunks folded DURING
              # the run, and the post-run bounded-invariant census
              # (worst per-table delta-partition count)
              "ingest_qps,flush_ms_p95,compact_chunks,delta_parts_max,"
              # ISSUE 19 (crash-only storage): --kill-at SEAM runs one
              # process-kill torture pass (tools/crash_torture.py) —
              # recovery_ms carries restart-to-first-answer wall clock
              # and acked_lost MUST be 0 (acked writes survive the
              # kill). Normal bench rows report acked_lost=0.
              "acked_lost,"
              # ISSUE 20 (windowed tile dispatch, exec/tilepipe.py):
              # checks that fired after newer tiles were already in
              # flight, and the window replays those deferrals cost
              "tile_deferred_overflows,tile_window_replays")


def parse_tenantspec(spec: str, clients: int):
    """'gold:3,silver:1' → [TenantSpec, ...]; per-field form is
    name:weight[:max_concurrency[:max_queue]]. The default queue depth
    scales with the client count so a closed-loop bench saturates the
    SCHEDULER (the fairness story), not the admission refusal."""
    from cloudberry_tpu.config import TenantSpec

    out = []
    for part in spec.split(","):
        if not part.strip():
            continue
        bits = part.strip().split(":")
        # the scheduler lowercases group names — match it here so the
        # per-tenant snapshot lookups (queue depth) resolve
        name = bits[0].lower()
        weight = int(bits[1]) if len(bits) > 1 else 1
        conc = int(bits[2]) if len(bits) > 2 else 0
        queue = int(bits[3]) if len(bits) > 3 else max(256, clients * 2)
        out.append(TenantSpec(name=name, weight=weight,
                              max_concurrency=conc, max_queue=queue))
    return out


def build_session(mode: str, rows: int, tick_s: float, max_batch: int,
                  mix: str = "point", chaos: float = 0.0,
                  tenants=None, server_core: str = "async",
                  clients: int = 16, aging_s: float = None,
                  trace_sample: int = 0, slow_ms: float = None,
                  segments: int = 1, compact_off: bool = False):
    import numpy as np

    import cloudberry_tpu as cb
    from cloudberry_tpu.config import Config

    over = {
        "sched.enabled": mode == "batched",
        "sched.tick_s": tick_s,
        "sched.max_batch": max_batch,
        "serve.threaded": server_core == "threaded",
        "n_segments": max(1, segments),
    }
    if clients > 64:
        # warehouse-concurrency closed loop: the global dispatcher queue
        # must hold every in-flight client
        over["sched.max_queue"] = max(256, clients * 2)
    if tenants:
        over["tenancy.enabled"] = True
        over["tenancy.tenants"] = tuple(tenants)
        if aging_s is not None:
            # the weights-vs-tail dial: queues deeper than aging_s's
            # wait turn DWRR into oldest-first (bounded p99, flattened
            # ratio) — raise it when the ratio is what you measure
            over["tenancy.aging_s"] = aging_s
    if mix == "spill":
        # the chaos workload streams tiles: shrink the budget so the li
        # aggregate runs through the tiled (checkpointable) path
        over["resource.query_mem_bytes"] = 1 << 20
    if mix == "coldscan":
        # long COLD tiled scans competing with point lookups: back the
        # catalog with a store and shrink the budget so li streams
        # micro-partition files through the scan pipeline; a FRESH
        # session binds below (set_data leaves tables warm in the
        # loading session). pts stays small enough to dispatch direct.
        over["storage.root"] = tempfile.mkdtemp(
            prefix="cbtpu_servebench_cold_")
        over["resource.query_mem_bytes"] = 2 << 20
    if mix == "hotcold":
        # the buffer-pool serving workload: li (hot) and lc (cold) are
        # the same store-backed shape; the pool budget holds the hot
        # statement's two scanned columns (~2MB at 120k rows) with a
        # little slack but NOT both tables, so the hot set goes
        # device-resident while the cold set is refused over evicting
        # hotter entries and churns in the remainder
        over["storage.root"] = tempfile.mkdtemp(
            prefix="cbtpu_servebench_hot_")
        over["resource.query_mem_bytes"] = 2 << 20
        over["bufferpool.max_bytes"] = 3 << 20
    if mix == "readwrite":
        # the write-plane workload: every table store-backed, the ingest
        # buffers tuned so a closed loop's appends group-commit visibly,
        # and the compaction service folding the debt DURING the window
        # (tight interval, low invariant threshold, small partitions so
        # small flushed tails actually accumulate census)
        over["storage.root"] = tempfile.mkdtemp(
            prefix="cbtpu_servebench_rw_")
        over["storage.rows_per_partition"] = 4096
        over["ingest.flush_rows"] = 128
        over["ingest.flush_ms"] = 5.0
        # --no-compact is the A/B baseline for the acceptance claim
        # ("read QPS holds while compaction runs"): same closed loop,
        # same append share, debt just accumulates unfolded
        over["compact.enabled"] = not compact_off
        over["compact.interval_s"] = 0.25
        over["compact.max_delta_parts"] = 8
    if chaos > 0:
        # probabilistic device loss compounds per tile: give recovery
        # more re-dispatches than the default flap allowance
        over["health.retries"] = 4
    if trace_sample:
        # --trace-sample N: keep every Nth statement's span tree; the
        # run dumps the ring as ONE perfetto-loadable file at the end
        over["obs.trace_sample"] = max(1, trace_sample)
        over["obs.trace_ring"] = 512
    if slow_ms is not None:
        # --slow-ms N: arm the flight recorder at this threshold so the
        # run's slow-statement captures show up in the CSV
        over["obs.slow_ms"] = float(slow_ms)
    cfg = Config().with_overrides(**over)
    s = cb.Session(cfg)
    # coldscan sizing: pts small enough to stay under the shrunken
    # budget (point lookups must dispatch direct), li big enough that
    # the cold aggregate streams several tiles per statement
    n_pts = min(rows, _COLD_PTS_ROWS) \
        if mix in ("coldscan", "hotcold") else rows
    s.sql("create table pts (k bigint, v bigint, w double) "
          "distributed by (k)")
    t = s.catalog.table("pts")
    t.set_data({
        "k": np.arange(n_pts, dtype=np.int64),
        "v": (np.arange(n_pts, dtype=np.int64) * 7) % 1000,
        "w": np.arange(n_pts, dtype=np.float64) * 0.5,
    }, {})
    s.sql("create table li (qty decimal(2), price decimal(2), "
          "disc decimal(2), sd date)")
    rng = np.random.default_rng(11)
    m = max(rows * 2, 120_000) if mix in ("coldscan", "hotcold") \
        else max(rows // 2, 1024)
    s.catalog.table("li").set_data({
        "qty": rng.integers(1, 5000, m).astype(np.int64),
        "price": rng.integers(100, 10000, m).astype(np.int64),
        "disc": rng.integers(0, 11, m).astype(np.int64),
        "sd": rng.integers(8000, 12000, m).astype(np.int32),
    }, {})
    if mix == "hotcold":
        # the COLD container: identical schema and row count as li so
        # the after-window rows/s probe compares pool-served vs
        # host-decoded scans of the SAME shape
        s.sql("create table lc (qty decimal(2), price decimal(2), "
              "disc decimal(2), sd date)")
        s.catalog.table("lc").set_data({
            "qty": rng.integers(1, 5000, m).astype(np.int64),
            "price": rng.integers(100, 10000, m).astype(np.int64),
            "disc": rng.integers(0, 11, m).astype(np.int64),
            "sd": rng.integers(8000, 12000, m).astype(np.int32),
        }, {})
    if mix == "readwrite":
        # the append target: store-backed with a committed base, so
        # compaction has a manifest to fold the flushed tails into
        s.sql("create table ing (k bigint, v bigint) distributed by (k)")
        s.catalog.table("ing").set_data({
            "k": np.arange(4096, dtype=np.int64),
            "v": np.zeros(4096, dtype=np.int64)}, {})
        s._servebench_root = cfg.storage.root
    if mix in ("coldscan", "hotcold"):
        s = cb.Session(cfg)  # fresh bind: tables come up cold
        s._servebench_root = cfg.storage.root
        s._servebench_rows = m
    return s


def _point_sql(i: int, rows: int) -> str:
    return f"select k, v, w from pts where k = {(i * 2654435761) % rows}"


def _q6_sql(i: int) -> str:
    lo = 1 + (i % 5)
    return ("select sum(price * disc) as rev from li "
            f"where disc between 0.0{lo} and 0.0{lo + 4} "
            f"and qty < {20 + (i % 7)}.0")


def _spill_sql(i: int) -> str:
    # a tiled (out-of-core) aggregate with rotating literals: under the
    # shrunken spill-mix budget this statement streams tiles through the
    # checkpoint seams — the --chaos recovery workload
    return ("select sum(price) as sp, count(*) as c from li "
            f"where qty < {4000 + (i % 50)}.0")


def _hot_sql() -> str:
    # IDENTICAL every time: the same statement re-scans the same tiles,
    # so from the third scan the buffer pool serves it from device
    # memory (admit_min_scans=2; the warmup scan counts as the first)
    return "select sum(price) as sp, count(*) as c from li " \
           "where qty < 4000.0"


def _cold_sql(i: int) -> str:
    # same shape/size container as the hot statement but a rotating
    # literal over lc — whose tiles never fit the hotcold pool budget
    # next to li's, so every scan pays host read+decode
    return ("select sum(price) as sp, count(*) as c from lc "
            f"where qty < {4000 + (i % 50)}.0")


# coldscan keeps pts small so point lookups dispatch direct under the
# shrunken tiled budget; _mix_sql caps the key range to match
_COLD_PTS_ROWS = 10_000


def _is_append(mix: str, i: int) -> bool:
    # readwrite: every 4th request is a wire-level APPEND — the drivers
    # branch on this BEFORE asking _mix_sql for a statement
    return mix == "readwrite" and i % 4 == 3


def _append_req(i: int) -> dict:
    return {"append": {"table": "ing",
                       "rows": [[1_000_000 + i, i % 97]]}}


def _mix_sql(mix: str, i: int, rows: int) -> str:
    if mix == "readwrite":
        return _point_sql(i, rows)
    if mix == "point":
        return _point_sql(i, rows)
    if mix == "q6":
        return _q6_sql(i)
    if mix == "spill":
        return _spill_sql(i)
    if mix == "coldscan":
        # 1-in-8 long cold tiled scans (same statement shape as spill,
        # but li is store-backed: every run re-streams and re-decodes
        # its micro-partitions through the scan pipeline) against a
        # majority of latency-sensitive point lookups
        return (_spill_sql(i) if i % 8 == 7
                else _point_sql(i, min(rows, _COLD_PTS_ROWS)))
    if mix == "hotcold":
        # 6-in-8 hot (identical, pool-served once admitted) against
        # 2-in-8 cold rotating scans: the 3:1 scan-frequency gap is
        # what keeps the hot set winning the refusal-over-evicting-
        # hotter comparison
        return _cold_sql(i) if i % 8 in (3, 7) else _hot_sql()
    return _q6_sql(i) if i % 5 == 4 else _point_sql(i, rows)


_BACKPRESSURE_ETYPES = ("TenantQueueFull", "SchedQueueFull", "ServerBusy",
                        "IngestQueueFull")


def _mux_driver(wid: int, n_conns: int, first_idx: int, host, port,
                mix: str, rows: int, tenant_names, stop_at, lat_map,
                lat_lock, rejects, errors, reads):
    """One driver thread simulating ``n_conns`` independent closed-loop
    clients: a selector loop sends each connection's next request the
    moment its previous response lands, so per-tenant throughput under
    saturation reflects the SERVER's scheduling (a lock-step
    send-all/recv-all cycle would equalize tenants by construction)."""
    sel = selectors.DefaultSelector()
    conns = []
    local: dict = {}
    rej_local = 0
    reads_local = 0
    try:
        for j in range(n_conns):
            idx = first_idx + j
            s = socket.create_connection((host, port), timeout=120)
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            r = s.makefile("rb")
            w = s.makefile("wb")
            tenant = tenant_names[idx % len(tenant_names)] \
                if tenant_names else None
            rec = {"s": s, "r": r, "w": w, "tenant": tenant,
                   "i": idx * 100_003, "t0": 0.0}
            conns.append(rec)
            sel.register(s, selectors.EVENT_READ, rec)
            local.setdefault(tenant, [])

        def send_next(rec):
            rec["ap"] = _is_append(mix, rec["i"])
            req = _append_req(rec["i"]) if rec["ap"] \
                else {"sql": _mix_sql(mix, rec["i"], rows)}
            if rec["tenant"]:
                req["tenant"] = rec["tenant"]
            rec["i"] += 1
            rec["t0"] = time.monotonic()
            rec["w"].write(json.dumps(req).encode() + b"\n")
            rec["w"].flush()

        for rec in conns:
            send_next(rec)
        while time.monotonic() < stop_at[0]:
            for key, _ in sel.select(timeout=0.1):
                rec = key.data
                line = rec["r"].readline()
                if not line:
                    raise RuntimeError("server closed a bench connection")
                resp = json.loads(line)
                dt = time.monotonic() - rec["t0"]
                if resp.get("ok"):
                    local[rec["tenant"]].append(dt)
                    if not rec.get("ap"):
                        reads_local += 1
                elif resp.get("etype") in _BACKPRESSURE_ETYPES:
                    # retryable refusal: counted as BACKPRESSURE (its
                    # own metric — NOT a deadline miss), loop retries
                    rej_local += 1
                else:
                    raise RuntimeError(resp.get("error", "bench error"))
                if time.monotonic() < stop_at[0]:
                    send_next(rec)
    except Exception as e:  # pragma: no cover - surfaced in result
        errors.append(f"{type(e).__name__}: {e}")
    finally:
        for rec in conns:
            try:
                rec["s"].close()
            except OSError:
                pass
        sel.close()
    with lat_lock:
        rejects[0] += rej_local
        reads[0] += reads_local
        for tenant, lats in local.items():
            lat_map.setdefault(tenant, []).extend(lats)


def _pct(lats, p: float) -> float:
    if not lats:
        return 0.0
    return lats[min(len(lats) - 1, int(p * len(lats)))] * 1000


def _stage_shares(registry) -> tuple[dict, int]:
    """(per-stage time shares, sampled span count) from the obs
    registry: each stage_seconds.<stage> histogram's SUM over the total
    across stages — where a served statement's time actually went,
    measured server-side."""
    snap = registry.snapshot()
    hists = snap.get("histograms", {})
    sums = {name.split(".", 1)[1]: h["sum"]
            for name, h in hists.items()
            if name.startswith("stage_seconds.")}
    total = sum(sums.values()) or 1.0
    shares = {f"{k}_share": round(v / total, 4) for k, v in sums.items()}
    spans = snap.get("counters", {}).get("trace_statements", 0)
    return shares, spans


def _hotcold_probe(session) -> dict:
    """After the measured window closes: time ONE pool-warm hot scan
    against ONE cold scan of the same-size container, each with its
    host_decodes counter delta — the bench's direct pin that the hot
    set is served with ZERO host reads/decodes (a counter fact, not a
    clock fact) and at measurably higher rows/s than the cold path.
    Rides as non-CSV extras (underscore keys) + a stderr summary."""
    log = session.stmt_log
    m = getattr(session, "_servebench_rows", 0)
    # settle scan: guarantees the hot set is past admission (scan 3+)
    # even if a very short window only reached it once
    session.sql(_hot_sql())
    out = {}
    for name, sql in (("hot", _hot_sql()), ("cold", _cold_sql(2))):
        d0 = log.counter("host_decodes")
        t0 = time.monotonic()
        session.sql(sql)
        wall = time.monotonic() - t0
        out[f"_{name}_rows_per_s"] = int(m / wall) if wall > 0 else 0
        out[f"_{name}_host_decodes"] = log.counter("host_decodes") - d0
    return out


def run_mode(mode: str, mix: str, clients: int, duration_s: float,
             rows: int, tick_s: float, max_batch: int,
             cancel_mix: float = 0.0, deadline_s: float = 0.005,
             chaos: float = 0.0, tenants=None,
             server_core: str = "async",
             driver_threads: int = 16, aging_s: float = None,
             trace_sample: int = 0, trace_out: str = None,
             slow_ms: float = None, segments: int = 1,
             expand_at=None, shrink_at=None,
             compact_off: bool = False) -> dict:
    """One closed-loop run; returns the CSV row fields.

    ``cancel_mix``: fraction of requests carrying a TIGHT per-request
    deadline (``deadline_s``) — the statement-lifecycle workload. Those
    that miss fail with the retryable timeout taxonomy (StatementTimeout
    / SchedDeadline) and count as ``deadline_misses``, not errors; the
    ``cancels`` column reports the engine's cancellation counters
    (cancel verb + watchdog) over the run.

    ``chaos``: per-hit device-loss probability armed on the dispatch and
    tile seams (utils/faultinject probabilistic arms) — the recovery
    workload. The recovery_count / tiles_replayed / recovery_ms columns
    report what the engine's checkpointed re-execution actually did;
    pair with ``--mix spill`` so statements stream tiles worth
    resuming."""
    from cloudberry_tpu.serve import Client, Server, ServerError
    from cloudberry_tpu.utils import faultinject as FI

    session = build_session(mode, rows, tick_s, max_batch,
                            mix=mix, chaos=chaos, tenants=tenants,
                            server_core=server_core, clients=clients,
                            aging_s=aging_s, trace_sample=trace_sample,
                            slow_ms=slow_ms, segments=segments,
                            compact_off=compact_off)
    # warm the compile caches OUTSIDE the measured window: the bench
    # compares steady-state dispatch, not first-compile latency
    session.sql(_point_sql(0, rows))
    session.sql(_q6_sql(0))
    if mix in ("spill", "coldscan"):
        session.sql(_spill_sql(0))
    if mix == "hotcold":
        # compiles both scan shapes outside the window; the hot warmup
        # is also the pool's FIRST observed scan (frequency 1), so the
        # measured window opens exactly one scan short of admission
        session.sql(_hot_sql())
        session.sql(_cold_sql(0))
    c_before = session.stmt_log.counter("compiles")
    d_before = session.stmt_log.counter("dispatches")
    x_before = (session.stmt_log.counter("cancel_requests")
                + session.stmt_log.counter("watchdog_timeouts"))
    r_before = session.stmt_log.counter("recoveries")
    tr_before = session.stmt_log.counter("tiles_replayed")
    rw_before = session.stmt_log.counter("recovery_wall_ms")
    fl_before = session.stmt_log.counter("flight_captures")
    sk_before = session.stmt_log.counter("skew_events")
    ef_before = session.stmt_log.counter("epoch_flips")
    mr_before = session.stmt_log.counter("topo_moved_rows")
    bh_before = session.stmt_log.counter("bufpool_hits")
    bm_before = session.stmt_log.counter("bufpool_misses")
    hd_before = session.stmt_log.counter("host_decodes")
    ar_before = session.stmt_log.counter("adaptive_replans")
    rd_before = session.stmt_log.counter("rung_downgrades")
    ia_before = session.stmt_log.counter("ingest_appends")
    cc_before = session.stmt_log.counter("compact_chunks")
    do_before = session.stmt_log.counter("tile_deferred_overflows")
    wr_before = session.stmt_log.counter("tile_window_replays")

    _MISS_ETYPES = ("StatementTimeout", "StatementCancelled",
                    "SchedDeadline")
    # a chaos run's residual losses (retries exhausted under the armed
    # device-loss rate) are the workload working, not bench failures
    _CHAOS_ETYPES = ("InjectedFault", "XlaRuntimeError")
    lats: list[float] = []
    misses = [0]
    lat_lock = threading.Lock()
    errors: list[str] = []
    stop_at = [0.0]
    stride = max(1, int(round(1.0 / cancel_mix))) if cancel_mix else 0

    def worker(wid: int):
        lat_local = []
        miss_local = 0
        reads_local = 0
        try:
            with Client(srv.host, srv.port) as c:
                i = wid * 100_003
                while time.monotonic() < stop_at[0]:
                    ap = _is_append(mix, i)
                    sql = None if ap else _mix_sql(mix, i, rows)
                    dl = deadline_s if stride and i % stride == 0 else None
                    t0 = time.monotonic()
                    try:
                        if ap:
                            c.append("ing", _append_req(i)["append"]["rows"])
                        else:
                            c.sql(sql, deadline_s=dl)
                            reads_local += 1
                    except ServerError as e:
                        # a deadlined request missing its deadline is the
                        # workload working, not a bench failure
                        if dl is not None and e.etype in _MISS_ETYPES:
                            miss_local += 1
                        elif e.etype in _BACKPRESSURE_ETYPES:
                            pass  # retryable refusal; the loop retries
                        elif chaos and e.etype in _CHAOS_ETYPES:
                            pass
                        else:
                            raise
                    i += 1
                    lat_local.append(time.monotonic() - t0)
        except Exception as e:  # pragma: no cover - surfaced in result
            errors.append(f"{type(e).__name__}: {e}")
        with lat_lock:
            lats.extend(lat_local)
            misses[0] += miss_local
            reads[0] += reads_local

    if chaos > 0:
        FI.inject_fault("tile_device_lost", "error", p=chaos, seed=1234)
        FI.inject_fault("exec_device_lost", "error", p=chaos, seed=4321)
    # mid-load topology chaos (--expand-at/--shrink-at "T:N"): a control
    # thread lands an epoch-versioned online resize T seconds into the
    # measured window while the clients keep hammering — the cutover_ms
    # / moved_rows / epoch_flips columns report what it cost
    topo_events = []
    for spec in ((("expand", expand_at),) if expand_at else ()) + \
            ((("shrink", shrink_at),) if shrink_at else ()):
        topo_events.append(spec)
    cutover_ms = [0.0]
    topo_errors: list[str] = []

    def _topo_driver():
        t_base = time.monotonic()
        for _, (at_s, target) in sorted(topo_events,
                                        key=lambda e: e[1][0]):
            delay = t_base + at_s - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            if time.monotonic() >= stop_at[0]:
                return
            try:
                out = session._topology.online_resize(target)
                cutover_ms[0] += out["cutover_ms"]
            except Exception as e:  # noqa: BLE001 — surfaced after run
                topo_errors.append(f"{type(e).__name__}: {e}")
                return
    lat_map: dict = {}
    rejects = [0]  # backpressure refusals (mux driver) — own metric
    reads = [0]    # successful READ requests (the readwrite split)
    tenant_names = [t.name for t in tenants] if tenants else None
    # driver choice: one OS thread per client stays exact for small runs
    # (and the cancel-mix workload needs per-request deadlines); past
    # that — or whenever tenants are declared — a few selector driver
    # threads each multiplex many independent closed-loop connections,
    # which is how the bench sustains 1k+ simulated clients
    mux = tenants is not None or clients > 32
    with Server(session=session) as srv:
        stop_at[0] = time.monotonic() + duration_s
        if mux:
            nthreads = min(driver_threads, clients)
            per = (clients + nthreads - 1) // nthreads
            threads = []
            first = 0
            for i in range(nthreads):
                n = min(per, clients - first)
                if n <= 0:
                    break
                threads.append(threading.Thread(
                    target=_mux_driver,
                    args=(i, n, first, srv.host, srv.port, mix, rows,
                          tenant_names, stop_at, lat_map, lat_lock,
                          rejects, errors, reads)))
                first += n
        else:
            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(clients)]
        t_start = time.monotonic()
        topo_thread = None
        if topo_events:
            topo_thread = threading.Thread(target=_topo_driver,
                                           daemon=True)
            topo_thread.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=duration_s + 120)
        if topo_thread is not None:
            topo_thread.join(timeout=60)
        wall = time.monotonic() - t_start
        disp = session.stmt_log
        dsnap = getattr(session, "_dispatcher", None)
        dstats = dsnap.snapshot() if dsnap is not None else {}
        tsnap = srv.tenancy.snapshot() if srv.tenancy is not None else {}
        fidx = srv.tenancy.fairness_index() \
            if srv.tenancy is not None else 1.0
    if chaos > 0:
        FI.reset_fault("tile_device_lost")
        FI.reset_fault("exec_device_lost")
    # the store root must outlive the counter reads AND the hotcold
    # probe below (which re-scans the store after the window closes)
    root = getattr(session, "_servebench_root", None)

    def _cleanup():
        if root:
            import shutil

            shutil.rmtree(root, ignore_errors=True)
    if errors:
        _cleanup()
        raise RuntimeError(f"bench clients failed: {errors[:3]}")
    if topo_errors:
        _cleanup()
        raise RuntimeError(f"topology chaos failed: {topo_errors}")
    if not mux:
        lat_map[None] = lats
    all_lats = sorted(x for ls in lat_map.values() for x in ls)

    out = {
        "mode": mode, "mix": mix, "clients": clients,
        "duration_s": round(wall, 2), "requests": len(all_lats),
        "qps": round(len(all_lats) / max(wall, 1e-9), 1),
        "p50_ms": round(_pct(all_lats, 0.50), 3),
        "p99_ms": round(_pct(all_lats, 0.99), 3),
        "compiles": disp.counter("compiles") - c_before,
        "dispatches": disp.counter("dispatches") - d_before,
        "batches": dstats.get("batches", 0),
        "batched_requests": dstats.get("batched_requests", 0),
        "avg_occupancy": dstats.get("avg_occupancy", 0.0),
        "deadline_misses": misses[0],
        "cancels": (disp.counter("cancel_requests")
                    + disp.counter("watchdog_timeouts")) - x_before,
        "recovery_count": disp.counter("recoveries") - r_before,
        "tiles_replayed": disp.counter("tiles_replayed") - tr_before,
        "recovery_ms": disp.counter("recovery_wall_ms") - rw_before,
        "tenant": "all",
        "tenant_qps": round(len(all_lats) / max(wall, 1e-9), 1),
        "tenant_p50_ms": round(_pct(all_lats, 0.50), 3),
        "tenant_p99_ms": round(_pct(all_lats, 0.99), 3),
        "tenant_queue_depth": dstats.get("max_depth", 0),
        "fairness_index": round(fidx, 4),
        "acked_lost": 0,  # the --kill-at column; a live run loses nothing
        # non-CSV extras for programmatic callers
        "_backpressure": rejects[0],
    }
    # server-side percentiles + stage time shares (obs registry): the
    # engine's own statement_seconds histogram, immune to client-side
    # queuing in the bench drivers
    reg = session.stmt_log.registry
    sh = reg.hist("statement_seconds") or {}
    shares, spans = _stage_shares(reg)
    out["srv_p50_ms"] = round(sh.get("p50", 0.0) * 1000, 3)
    out["srv_p95_ms"] = round(sh.get("p95", 0.0) * 1000, 3)
    out["srv_p99_ms"] = round(sh.get("p99", 0.0) * 1000, 3)
    for col in ("queue_wait_share", "compile_share", "launch_share",
                "render_share"):
        out[col] = shares.get(col, 0.0)
    out["trace_spans"] = spans
    # capacity & forensics columns (ISSUE 12): flight captures over the
    # run, skew alarms from the motion telemetry, and the peak
    # per-statement device-byte estimate (high-water gauge)
    out["flight_captures"] = disp.counter("flight_captures") - fl_before
    out["skew_events"] = disp.counter("skew_events") - sk_before
    peak = reg.snapshot()["gauges"].get("stmt_device_bytes_peak", 0.0)
    out["peak_stmt_mb"] = round(peak / (1 << 20), 3)
    # online-topology chaos columns (ISSUE 13)
    out["cutover_ms"] = round(cutover_ms[0], 2)
    out["moved_rows"] = disp.counter("topo_moved_rows") - mr_before
    out["epoch_flips"] = disp.counter("epoch_flips") - ef_before
    # HBM buffer-pool columns (ISSUE 16): hit rate over the run's pool
    # lookups and the host decode count — under --mix hotcold the hot
    # set's repeats stop decoding once admitted, so host_decodes
    # tracks the cold set (plus the hot set's single admission pass)
    bh = disp.counter("bufpool_hits") - bh_before
    bm = disp.counter("bufpool_misses") - bm_before
    out["bufpool_hit_rate"] = round(bh / (bh + bm), 4) if bh + bm else 0.0
    out["host_decodes"] = disp.counter("host_decodes") - hd_before
    # feedback-driven re-optimization columns (ISSUE 17): mid-statement
    # adaptive replans taken over the window, and capacity rungs the
    # learned sketches priced DOWN from the static estimate (the wire /
    # padding saving the feedback loop bought on repeat statements)
    out["adaptive_replans"] = (disp.counter("adaptive_replans")
                               - ar_before)
    out["rung_downgrades"] = (disp.counter("rung_downgrades")
                              - rd_before)
    # write-plane columns (ISSUE 18): appends/s the ingest buffers
    # accepted, p95 group-flush commit latency, compaction chunks
    # folded during the window, and a LIVE end-of-run census of the
    # bounded invariant (worst per-table delta-partition count, read
    # from the manifests rather than the compactor's cached gauge)
    out["ingest_qps"] = round(
        (disp.counter("ingest_appends") - ia_before) / max(wall, 1e-9), 1)
    fh = reg.hist("ingest_flush_seconds") or {}
    out["flush_ms_p95"] = round(fh.get("p95", 0.0) * 1000, 3)
    out["compact_chunks"] = disp.counter("compact_chunks") - cc_before
    # windowed tile dispatch columns (ISSUE 20)
    out["tile_deferred_overflows"] = (
        disp.counter("tile_deferred_overflows") - do_before)
    out["tile_window_replays"] = (
        disp.counter("tile_window_replays") - wr_before)
    dmax = 0
    if session.store is not None and mix == "readwrite":
        from cloudberry_tpu.storage.compact import delta_parts

        rpp = getattr(session.store, "rows_per_partition", 1 << 20)
        tf = session.config.compact.target_fill
        for name in session.store.table_names():
            man = session.store.read_manifest(name)
            if man["schema"] is not None:
                dmax = max(dmax, delta_parts(man, rpp, tf))
    out["delta_parts_max"] = dmax
    out["_read_qps"] = round(reads[0] / max(wall, 1e-9), 1)
    if mix == "hotcold":
        out.update(_hotcold_probe(session))
    _cleanup()
    if trace_sample and trace_out:
        from cloudberry_tpu.obs.trace import chrome_trace

        with open(trace_out, "w") as fh:
            json.dump(chrome_trace(session.stmt_log.traces(512)), fh)
        print(f"# trace written to {trace_out} "
              f"({spans} sampled statements)", file=sys.stderr)
    if tenant_names:
        # one CSV row per tenant, riding the aggregate's shared columns
        trs = []
        for name in tenant_names:
            tl = sorted(lat_map.get(name, []))
            tr = dict(out)
            tr.update({
                "tenant": name,
                "tenant_qps": round(len(tl) / max(wall, 1e-9), 1),
                "tenant_p50_ms": round(_pct(tl, 0.50), 3),
                "tenant_p99_ms": round(_pct(tl, 0.99), 3),
                "tenant_queue_depth": tsnap.get(name, {}).get(
                    "max_depth", 0),
            })
            trs.append(tr)
        out["_tenants"] = trs
    return out


def run_killat(seam: str, hit: int | None = None) -> dict:
    """--kill-at: one process-kill torture pass as a bench row. The
    heavy lifting (server subprocess, CBTPU_INJECT arming, restart,
    wire verify, fsck) is tools/crash_torture.py's run_seam; this
    wrapper shapes the verdict into the serving CSV so crash recovery
    rides the same dashboards as QPS. acked_lost != 0 or any problem
    is a FAILURE, surfaced both in the row and on stderr."""
    from tools.crash_torture import MATRIX_SEAMS, run_seam

    known = dict(MATRIX_SEAMS)
    if hit is None:
        hit = known.get(seam, 6)
    rec = run_seam(seam, hit=hit)
    row = {k: 0 for k in CSV_HEADER.split(",")}
    row.update({
        "mode": "killat", "mix": seam, "clients": 1,
        "duration_s": 0.0, "requests": rec["acked_inserts"],
        "qps": 0.0, "p50_ms": 0.0, "p99_ms": 0.0,
        "avg_occupancy": 0.0, "fairness_index": 1.0, "tenant": "all",
        "recovery_count": 1 if rec["fired"] else 0,
        "recovery_ms": rec["recovery_ms"] or 0.0,
        "acked_lost": rec["acked_lost"],
        # non-CSV extras for programmatic callers / tests
        "_torture": rec,
    })
    for p in rec["problems"]:
        print(f"# kill-at {seam}@{hit}: {p}", file=sys.stderr)
    if not rec["problems"]:
        print(f"# kill-at {seam}@{hit}: clean — exit=137, "
              f"acked={rec['acked_inserts']}, acked_lost=0, "
              f"recovery={rec['recovery_ms']}ms, fsck clean",
              file=sys.stderr)
    return row


def _parse_at(spec):
    """'T:N' → (T seconds into the run, N target segments), or None."""
    if not spec:
        return None
    t, _, n = str(spec).partition(":")
    return (float(t), int(n))


def csv_row(r: dict) -> str:
    return ",".join(str(r[k]) for k in CSV_HEADER.split(","))


def main(argv=None) -> list[dict]:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mode", default="both",
                    choices=["both", "direct", "batched"])
    ap.add_argument("--mix", default="point",
                    choices=["point", "q6", "mixed", "spill",
                             "coldscan", "hotcold", "readwrite"])
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--duration", type=float, default=5.0)
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--tick-s", type=float, default=0.002)
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--cancel-mix", type=float, default=0.0,
                    help="fraction of requests carrying a tight "
                         "per-request deadline (lifecycle workload)")
    ap.add_argument("--deadline-s", type=float, default=0.005,
                    help="the tight deadline used by --cancel-mix")
    ap.add_argument("--chaos", type=float, default=0.0,
                    help="per-hit device-loss probability armed on the "
                         "dispatch/tile seams (recovery workload; pair "
                         "with --mix spill)")
    ap.add_argument("--tenants", default=None,
                    help="tenant spec 'name:weight[:conc[:queue]],...' "
                         "— enables per-tenant fair scheduling and the "
                         "per-tenant CSV rows (e.g. gold:3,silver:1)")
    ap.add_argument("--server-core", default="async",
                    choices=["async", "threaded"],
                    help="serving transport: the event-loop front end "
                         "(default) or legacy thread-per-connection")
    ap.add_argument("--driver-threads", type=int, default=16,
                    help="selector driver threads multiplexing the "
                         "simulated clients (large --clients runs)")
    ap.add_argument("--aging-s", type=float, default=None,
                    help="tenancy starvation bound override (waits past "
                         "it are served oldest-first, trading weight "
                         "proportionality for bounded p99)")
    ap.add_argument("--trace-sample", type=int, default=0,
                    help="sample every Nth statement's span tree into "
                         "--trace-out (perfetto-loadable) and report "
                         "per-stage time-share columns")
    ap.add_argument("--trace-out", default="serve_trace.json",
                    help="chrome-trace output path for --trace-sample")
    ap.add_argument("--slow-ms", type=float, default=None,
                    help="flight-recorder threshold for the run "
                         "(config.obs.slow_ms): statements slower than "
                         "this capture debug bundles, counted in the "
                         "flight_captures CSV column")
    ap.add_argument("--segments", type=int, default=1,
                    help="segment count the serving session starts at "
                         "(online resizes move FROM here)")
    ap.add_argument("--expand-at", default=None, metavar="T:N",
                    help="land an epoch-versioned online expand to N "
                         "segments T seconds into the measured window "
                         "(needs N visible devices; cutover_ms / "
                         "moved_rows / epoch_flips CSV columns)")
    ap.add_argument("--shrink-at", default=None, metavar="T:N",
                    help="same, shrinking to N segments")
    ap.add_argument("--kill-at", default=None, metavar="SEAM",
                    help="crash-recovery bench: launch a real server "
                         "subprocess, kill it (os._exit) at this armed "
                         "durability seam mid-workload, restart, and "
                         "verify — emits one CSV row whose recovery_ms "
                         "is restart-to-first-answer and whose "
                         "acked_lost MUST be 0 (see "
                         "tools/crash_torture.py MATRIX_SEAMS)")
    ap.add_argument("--kill-hit", type=int, default=None,
                    help="fire --kill-at on the Nth seam hit "
                         "(default: the torture matrix's)")
    ap.add_argument("--no-compact", action="store_true",
                    help="readwrite baseline: same append share with "
                         "the compaction service off (the A/B for the "
                         "read-QPS-holds-under-compaction claim)")
    ap.add_argument("--csv", default=None,
                    help="append CSV rows to this file")
    args = ap.parse_args(argv)

    if args.clients > 256:
        # 1k+ simulated clients need 2x that many fds in ONE process
        # (both socket ends live here); lift the soft limit to the hard
        try:
            import resource as _res

            soft, hard = _res.getrlimit(_res.RLIMIT_NOFILE)
            want = min(hard, max(soft, args.clients * 4 + 256))
            if want > soft:
                _res.setrlimit(_res.RLIMIT_NOFILE, (want, hard))
        except (ImportError, ValueError, OSError):
            pass
    if args.kill_at:
        r = run_killat(args.kill_at, args.kill_hit)
        print(CSV_HEADER)
        print(csv_row(r), flush=True)
        if args.csv:
            new = not os.path.exists(args.csv)
            with open(args.csv, "a") as fh:
                if new:
                    fh.write(CSV_HEADER + "\n")
                fh.write(csv_row(r) + "\n")
        return [r]
    tenants = parse_tenantspec(args.tenants, args.clients) \
        if args.tenants else None
    modes = ["direct", "batched"] if args.mode == "both" else [args.mode]
    out = []
    rows_out = []
    print(CSV_HEADER)
    for mode in modes:
        r = run_mode(mode, args.mix, args.clients, args.duration,
                     args.rows, args.tick_s, args.max_batch,
                     cancel_mix=args.cancel_mix,
                     deadline_s=args.deadline_s, chaos=args.chaos,
                     tenants=tenants, server_core=args.server_core,
                     driver_threads=args.driver_threads,
                     aging_s=args.aging_s,
                     trace_sample=args.trace_sample,
                     trace_out=args.trace_out,
                     slow_ms=args.slow_ms, segments=args.segments,
                     expand_at=_parse_at(args.expand_at),
                     shrink_at=_parse_at(args.shrink_at),
                     compact_off=args.no_compact)
        out.append(r)
        rows_out.append(r)
        rows_out.extend(r.get("_tenants", ()))
        for rr in [r] + list(r.get("_tenants", ())):
            print(csv_row(rr), flush=True)
        if args.mix == "hotcold":
            print(f"# hotcold[{mode}]: hot {r['_hot_rows_per_s']} rows/s"
                  f" ({r['_hot_host_decodes']} host decodes) vs cold "
                  f"{r['_cold_rows_per_s']} rows/s "
                  f"({r['_cold_host_decodes']} host decodes); "
                  f"run hit rate {r['bufpool_hit_rate']}",
                  file=sys.stderr)
    if args.csv:
        new = not os.path.exists(args.csv)
        with open(args.csv, "a") as fh:
            if new:
                fh.write(CSV_HEADER + "\n")
            for r in rows_out:
                fh.write(csv_row(r) + "\n")
    if len(out) == 2:
        base, batched = out[0]["qps"], out[1]["qps"]
        if base > 0:
            print(f"# batched/direct QPS: {batched / base:.2f}x",
                  file=sys.stderr)
    return out


if __name__ == "__main__":
    from cloudberry_tpu.utils.compilecache import entry_banner

    print(f"# {entry_banner()}", file=sys.stderr)
    main()
