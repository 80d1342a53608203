"""ISSUE 9 observability plane: the metrics registry, statement trace
spans, the pg_stat_statements analog, EXPLAIN ANALYZE through the
statement pipeline, and the meta wire surface — all pinned."""

import json
import threading
import time

import numpy as np
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import Config
from cloudberry_tpu.obs.metrics import MetricsRegistry
from cloudberry_tpu.obs.statements import StatementStats


# ------------------------------------------------------------- registry


def test_registry_counters_gauges_hists():
    r = MetricsRegistry()
    r.bump("a")
    r.bump("a", 4)
    r.bump("b", 2, tenant="gold")
    r.gauge("depth", 7)
    for v in (0.001, 0.002, 0.004, 0.1):
        r.observe("lat", v)
    assert r.counter("a") == 5
    assert r.counter("b") == 2  # labeled bumps ride the total too
    snap = r.snapshot()
    assert snap["labeled_counters"] == {"b{tenant=gold}": 2}
    assert snap["gauges"]["depth"] == 7.0
    h = snap["histograms"]["lat"]
    assert h["count"] == 4 and h["sum"] == pytest.approx(0.107)
    # log2-bucket quantiles are conservative upper bounds
    assert h["p50"] >= 0.002 and h["p99"] >= 0.1
    text = r.exposition()
    assert "# TYPE cbtpu_a counter" in text and "cbtpu_a 5" in text
    # labeled series live under a DISTINCT metric name: sum() over the
    # unlabeled total must never double-count the tenant partitions
    assert 'cbtpu_b_by_tenant{tenant="gold"} 2' in text
    assert "# TYPE cbtpu_b_by_tenant counter" in text
    assert "cbtpu_lat_bucket" in text and "cbtpu_lat_count 4" in text


def test_registry_series_bound():
    r = MetricsRegistry(max_series=4)
    for i in range(10):
        r.bump(f"c{i}")
    snap = r.snapshot()
    assert len(snap["counters"]) == 4
    assert snap["series_dropped"] == 6


def test_counter_view_is_registry_backed():
    log = cb.Session().stmt_log
    log.bump("xyz", 3)
    assert log.counters["xyz"] == 3
    assert log.counters.get("xyz") == 3
    assert log.counter_snapshot()["xyz"] == 3
    assert "xyz" in log.counters
    assert dict(log.counters.items())["xyz"] == 3


# ------------------------------------------------------ honest split


class _FakeJit:
    """No .lower(): exercises the two-call fallback. First call sleeps
    compile+execute, later calls execute only."""

    def __init__(self, compile_s, exec_s):
        self.compile_s = compile_s
        self.exec_s = exec_s
        self.calls = 0

    def __call__(self, inputs):
        self.calls += 1
        time.sleep(self.exec_s + (self.compile_s if self.calls == 1
                                  else 0.0))
        return np.zeros(1)


class _FakeAot:
    """AOT API stub: lower().compile() pays the compile cost, the
    compiled callable pays only execution."""

    def __init__(self, compile_s, exec_s):
        self.compile_s = compile_s
        self.exec_s = exec_s

    def lower(self, inputs):
        outer = self

        class _L:
            def compile(self):
                time.sleep(outer.compile_s)
                return lambda inputs: (time.sleep(outer.exec_s),
                                       np.zeros(1))[1]

        return _L()


def test_timed_compile_run_fallback_split():
    """The satellite bugfix pinned: the old code labeled the whole first
    call compile_s even though it also executed; the fallback split
    subtracts a warm execution."""
    from cloudberry_tpu.exec.instrument import _timed_compile_run

    fn = _FakeJit(compile_s=0.10, exec_s=0.03)
    _, compile_s, exec_s = _timed_compile_run(fn, {})
    assert fn.calls == 2
    assert compile_s == pytest.approx(0.10, abs=0.04)
    assert exec_s == pytest.approx(0.03, abs=0.02)
    # the honest invariant: compile_s excludes the warm execution
    assert compile_s < 0.10 + 0.03 - 0.01


def test_timed_compile_run_aot_split():
    from cloudberry_tpu.exec.instrument import _timed_compile_run

    _, compile_s, exec_s = _timed_compile_run(
        _FakeAot(compile_s=0.08, exec_s=0.03), {})
    assert compile_s == pytest.approx(0.08, abs=0.04)
    assert exec_s == pytest.approx(0.03, abs=0.02)


def test_metrics_hook_exception_safe():
    """A raising metrics hook must never abort the statement (satellite
    bugfix) — it is counted instead."""
    s = cb.Session()
    s.sql("create table hk (k bigint)")
    s.sql("insert into hk values (1), (2)")

    def bad_hook(m):
        raise RuntimeError("observer bug")

    got = []
    s.metrics_hooks.append(bad_hook)
    s.metrics_hooks.append(got.append)
    text = s.explain_analyze("select count(*) as n from hk")
    assert "rows=" in text
    assert len(got) == 1  # later hooks still fire
    assert s.stmt_log.counter("metrics_hook_errors") == 1


# ----------------------------------------- EXPLAIN ANALYZE via pipeline


@pytest.fixture(scope="module")
def dist_session():
    s = cb.Session(Config(n_segments=8))
    s.sql("create table d8 (k bigint, v bigint) distributed by (k)")
    s.sql("insert into d8 values "
          + ",".join(f"({i},{i % 7})" for i in range(64)))
    return s


@pytest.mark.parametrize("nseg", [1, 8])
def test_pipeline_counts_match_the_data(nseg, dist_session):
    """Per-node row counts from the pipeline path (generic-plan form,
    shared compile entry points) against counts reckoned from the
    inserted rows, at 1 and 8 segments: 64 rows, ``k < 32`` keeps 32,
    7 groups; a partial aggregate emits one row per (segment, group)
    present on that segment, and the gather above it counts them once."""
    from cloudberry_tpu.exec.instrument import run_pipeline
    from cloudberry_tpu.plan.planner import plan_statement
    from cloudberry_tpu.sql.parser import parse_sql

    if nseg == 1:
        s = cb.Session()
        s.sql("create table d1 (k bigint, v bigint) distributed by (k)")
        s.sql("insert into d1 values "
              + ",".join(f"({i},{i % 7})" for i in range(64)))
        q = "select v, count(*) as n from d1 where k < 32 group by v"
        partials = None
    else:
        s = dist_session
        q = "select v, count(*) as n from d8 where k < 32 group by v"
        st = s.sharded_table("d8")
        partials = 0
        for seg in range(8):
            k = np.asarray(st.columns["k"][seg])[:st.counts[seg]]
            v = np.asarray(st.columns["v"][seg])[:st.counts[seg]]
            partials += len(np.unique(v[k < 32]))
    kk = np.arange(64)
    n_filtered = int((kk < 32).sum())
    n_groups = len(np.unique((kk % 7)[kk < 32]))

    def expected(title):
        if title.startswith("Scan"):
            return 64
        if title.startswith("Filter"):
            return n_filtered
        if "partial" in title or title.startswith("Motion"):
            return partials
        return n_groups     # the (final) aggregate and what sits on it

    plan = plan_statement(parse_sql(q), s, {}).plan
    batch, pipe, _ann = run_pipeline(plan, s, q)
    assert len(pipe.node_rows) == (4 if nseg == 1 else 7)
    assert [(t, r) for t, _, r in pipe.node_rows] == \
        [(t, expected(t)) for t, _, _ in pipe.node_rows]
    assert batch.num_rows() == pipe.rows_out == n_groups
    # pipeline semantics: the run is a real statement — logged, counted
    recent = s.stmt_log.recent(5)
    assert recent[0]["sql"] == q and recent[0]["status"] == "ok"
    assert recent[0]["compiles"] >= 1


def test_explain_analyze_motion_annotations(dist_session):
    s = dist_session
    text = s.explain_analyze(
        "select v, count(*) as n from d8 group by v")
    assert "launches=" in text and "wire_bytes=" in text, text


def test_explain_analyze_tiled_trailer():
    """Over-budget statements take the tiled path; EXPLAIN ANALYZE then
    reports the per-tile time distribution + tile counts."""
    cfg = Config().with_overrides(**{"resource.query_mem_bytes": 1 << 20})
    s = cb.Session(cfg)
    s.sql("create table big (k bigint, v double)")
    n = 200_000
    s.catalog.table("big").set_data({
        "k": np.arange(n, dtype=np.int64) % 97,
        "v": np.arange(n, dtype=np.float64)}, {})
    text = s.explain_analyze(
        "select k, sum(v) as sv from big group by k")
    assert "Tiled execution" in text, text
    assert "tile step: mean" in text, text
    # the tile-time histogram also lands on the engine registry
    # (``tile_seconds`` — visible in meta "metrics" without an
    # instrumented rerun)
    h = s.stmt_log.registry.hist("tile_seconds")
    assert h is not None and h["count"] >= 1


# -------------------------------------------------- statements analog


def test_statement_stats_aggregates():
    s = cb.Session()
    s.sql("create table st (k bigint, v bigint) distributed by (k)")
    s.catalog.table("st").set_data({
        "k": np.arange(500, dtype=np.int64),
        "v": np.arange(500, dtype=np.int64) * 2}, {})
    for i in range(6):
        s.sql(f"select v from st where k = {i}")
    rows = s.stmt_log.statements.snapshot()
    row = next(r for r in rows if "st" in r["query"] and "?n" in r["query"])
    assert row["calls"] == 6
    assert row["compiles"] == 1           # one generic build
    assert row["generic_hits"] == 5       # five zero-compile rebinds
    assert row["generic_hit_rate"] == pytest.approx(5 / 6, abs=0.01)
    assert row["rows"] == 6               # one row per lookup
    assert row["total_wall_s"] > 0 and row["p95_wall_s"] > 0
    assert row["errors"] == 0


def test_statement_stats_bounded_lru():
    st = StatementStats(max_rows=4)
    for i in range(10):
        st.observe({"sql": f"select {i} api_unique_{i}", "wall_s": 0.001,
                    "status": "ok", "rows": 1})
    assert len(st) == 4
    assert st.evicted == 6


def test_counters_consistency_with_history():
    """Registry totals == the sum of per-statement history records for a
    pinned single-threaded workload (the engine-wide counter and the
    per-statement attribution must never drift)."""
    s = cb.Session()
    s.sql("create table cc (k bigint, v bigint) distributed by (k)")
    s.catalog.table("cc").set_data({
        "k": np.arange(100, dtype=np.int64),
        "v": np.arange(100, dtype=np.int64)}, {})
    for i in range(5):
        s.sql(f"select v from cc where k = {i}")
    s.sql("select count(*) as n from cc")
    recent = s.stmt_log.recent(100)
    assert sum(e.get("compiles", 0) for e in recent) \
        == s.stmt_log.counter("compiles")
    assert sum(e.get("generic_hits", 0) for e in recent) \
        == s.stmt_log.counter("generic_hits")


# ------------------------------------------------------------- tracing


def _span_intervals_nest(events, eps=2.0):
    """Within each tid, spans must properly nest (contain or be
    disjoint) — the invariant Perfetto's track rendering assumes."""
    by_tid = {}
    for e in events:
        by_tid.setdefault(e["tid"], []).append(
            (e["ts"], e["ts"] + e["dur"]))
    for ivals in by_tid.values():
        ivals.sort(key=lambda p: (p[0], -p[1]))
        stack = []
        for lo, hi in ivals:
            while stack and lo >= stack[-1] - eps:
                stack.pop()
            if stack and hi > stack[-1] + eps:
                return False
            stack.append(hi)
    return True


def test_trace_q5_coverage_and_nesting():
    """The acceptance pin: a traced TPC-H Q5 statement exports
    Chrome-trace JSON whose root span covers >=95% of the externally
    measured wall time, with child spans for every pipeline stage, all
    properly nested."""
    from tools.tpch_queries import QUERIES
    from tools.tpchgen import load_tpch

    s = cb.Session()
    load_tpch(s, sf=0.01, seed=7)
    t0 = time.perf_counter()
    s.sql(QUERIES["q5"])
    wall = time.perf_counter() - t0
    tr = s.stmt_log.traces(1)[0]
    assert tr["status"] == "ok"
    root = next(e for e in tr["events"] if e["name"] == "statement")
    assert root["dur"] / 1e6 >= 0.95 * wall, (root["dur"], wall)
    names = {e["name"] for e in tr["events"]}
    assert {"parse", "plan", "queue-wait", "launch"} <= names, names
    assert _span_intervals_nest(tr["events"]), tr["events"]
    # the export is chrome-trace/perfetto shaped
    from cloudberry_tpu.obs.trace import chrome_trace

    doc = chrome_trace([tr])
    json.dumps(doc)  # JSON-serializable end to end
    assert all(e["ph"] == "X" for e in doc["traceEvents"])


def test_trace_ring_and_span_bounds():
    cfg = Config().with_overrides(**{"obs.trace_ring": 3,
                                     "obs.max_spans": 16})
    s = cb.Session(cfg)
    s.sql("create table tb (k bigint)")
    for i in range(6):
        s.sql(f"insert into tb values ({i})")
    assert len(s.stmt_log.traces(100)) == 3  # ring bound holds
    for tr in s.stmt_log.traces(100):
        assert len(tr["events"]) <= 16


def test_trace_sampling_and_disable():
    cfg = Config().with_overrides(**{"obs.trace_sample": 3})
    s = cb.Session(cfg)
    s.sql("create table ts1 (k bigint)")
    for i in range(8):
        s.sql(f"insert into ts1 values ({i})")
    n_sampled = len(s.stmt_log.traces(100))
    assert 2 <= n_sampled <= 4  # every 3rd of 9 statements

    off = cb.Session(Config().with_overrides(**{"obs.enabled": False}))
    off.sql("create table ts2 (k bigint)")
    off.sql("insert into ts2 values (1)")
    assert off.sql("select count(*) as n from ts2").num_rows() == 1
    assert off.stmt_log.traces(100) == []
    assert len(off.stmt_log.statements) == 0


def test_dispatcher_batch_trace_spans():
    """Batched statements (dispatcher worker thread) get their own
    traces: the dispatch-queue-wait span precedes the root statement
    span, and the stacked launch's spans nest on the worker."""
    from cloudberry_tpu.sched import Dispatcher

    cfg = Config().with_overrides(**{"sched.enabled": True,
                                     "sched.tick_s": 0.02})
    s = cb.Session(cfg)
    s.sql("create table db (k bigint, v bigint) distributed by (k)")
    s.catalog.table("db").set_data({
        "k": np.arange(1000, dtype=np.int64),
        "v": np.arange(1000, dtype=np.int64)}, {})
    s.sql("select v from db where k = 0")  # warm the generic plan
    d = Dispatcher(s).start()
    try:
        outs, threads = [], []
        for i in range(6):
            t = threading.Thread(
                target=lambda i=i: outs.append(
                    d.submit(f"select v from db where k = {i + 1}")))
            threads.append(t)
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert len(outs) == 6
    finally:
        d.stop()
    assert d.stats["batched_requests"] >= 2  # a batch actually formed
    batched = [tr for tr in s.stmt_log.traces(50)
               if any(e["name"] == "dispatch-queue-wait"
                      for e in tr["events"])]
    assert batched, s.stmt_log.traces(50)
    for tr in batched:
        assert _span_intervals_nest(tr["events"]), tr["events"]
        root = next(e for e in tr["events"] if e["name"] == "statement")
        qw = next(e for e in tr["events"]
                  if e["name"] == "dispatch-queue-wait")
        assert qw["ts"] + qw["dur"] <= root["ts"] + 2.0
    # worker-thread spans and caller-thread spans coexist in the export
    tids = {e["tid"] for tr in s.stmt_log.traces(50)
            for e in tr["events"]}
    assert len(tids) >= 2
    # statements-table integrity through the dispatcher: the 7 real
    # executions (1 warm + 6 submits) count once each — 'requeued'
    # bookkeeping stubs never pollute the aggregates — and batched
    # members count as generic reuses (per-entry sums == engine total)
    row = next(r for r in s.stmt_log.statements.snapshot()
               if "db" in r["query"])
    assert row["calls"] == 7, row
    assert row["generic_hits"] >= d.stats["batched_requests"] - 1, row
    recent = s.stmt_log.recent(100)
    executed = [e for e in recent if e.get("status") != "requeued"]
    assert sum(e.get("generic_hits", 0) for e in executed) \
        == s.stmt_log.counter("generic_hits")


# ------------------------------------- the request, split where it works
# ISSUE 26: one primitive (obs.trace.stage) records every stage of a
# served statement as span + histogram + profiler annotation.

_TOP = ("wire-in", "bind", "parse", "plan", "admit", "queue-wait",
        "launch", "render", "wire-out")
_KIDS = {"oneshot": ("inputs", "dispatch", "device-wait", "fetch"),
         "tiled": ("prelude", "feed-wait", "h2d", "tile-step",
                   "drain-stall", "finalize")}
_STAGED_Q = ("select g, sum(v) as sv, count(*) as n from staged "
             "where v < {} group by g order by g")


@pytest.fixture(scope="module")
def staged_store(tmp_path_factory):
    """A store-backed table of 15 partitions, and the two deployments
    that serve it: one-shot (default memory) and tiled (1 MiB)."""
    root = str(tmp_path_factory.mktemp("staged") / "store")
    base = {"storage.root": root, "storage.rows_per_partition": 4096}
    s = cb.Session(Config().with_overrides(**base))
    s.sql("create table staged (k bigint, v bigint, g bigint) "
          "distributed by (k)")
    s.sql("insert into staged values " + ",".join(
        f"({i},{i % 7},{i % 3})" for i in range(60_000)))
    return {"oneshot": base,
            "tiled": dict(base, **{"resource.query_mem_bytes": 1 << 20,
                                   "bufferpool.max_bytes": 1 << 20})}


def _hist_counts(log):
    return {k: (h["count"], h["sum"]) for k, h in
            log.registry.snapshot()["histograms"].items()}


def _served_trace(log, c, sql, want="request"):
    """Send ``sql``; its trace once the request's last span landed (the
    event loop closes the request after the answer's last byte)."""
    c.sql(sql)
    deadline = time.monotonic() + 5.0
    while True:
        tr = log.traces(1)[0]
        if tr["sql"] == sql[:200] and any(
                e["name"] == want for e in tr["events"]):
            return tr
        assert time.monotonic() < deadline, tr
        time.sleep(0.01)


@pytest.mark.parametrize("threaded", [False, True],
                         ids=["async", "threaded"])
@pytest.mark.parametrize("path", ["oneshot", "tiled"])
def test_served_statement_spans_where_the_work_happens(
        staged_store, path, threaded, monkeypatch):
    from cloudberry_tpu.obs import trace as OT
    from cloudberry_tpu.serve import Client, Server

    # every request reads its thread's CPU clock here (one in
    # OT.CPU_SAMPLE does in service: the read is a system call)
    monkeypatch.setattr(OT, "CPU_SAMPLE", 1)

    cfg = Config().with_overrides(**staged_store[path],
                                  **{"serve.threaded": threaded})
    with Server(config=cfg) as srv, Client(srv.host, srv.port) as c:
        log = srv.session.stmt_log
        _served_trace(log, c, _STAGED_Q.format(5))      # cold: compiles
        before = _hist_counts(log)
        tr = _served_trace(log, c, _STAGED_Q.format(4))
        after = _hist_counts(log)
    assert tr["status"] == "ok"
    events = tr["events"]
    sid = tr["statement_id"]
    names = [e["name"] for e in events]
    top = _TOP + (() if threaded else ("wire-flush",))
    want = set(top) | set(_KIDS[path]) | {"request", "statement"} \
        | ({"part-read"} if path == "tiled" else set())
    assert want <= set(names), sorted(want - set(names))
    assert not set(_KIDS["tiled" if path == "oneshot" else "oneshot"]) \
        & set(names)
    # one statement id on every span of every thread; parents named
    assert all(e["args"]["statement_id"] == sid for e in events), events
    launch = next(e for e in events if e["name"] == "launch")
    eps = 2.0
    for e in events:
        if e["name"] in _KIDS[path]:
            assert e["args"]["parent"] in ("launch", "tile-step"), e
            assert e["tid"] == launch["tid"]
            assert e["ts"] >= launch["ts"] - eps and e["ts"] + e["dur"] \
                <= launch["ts"] + launch["dur"] + eps, (e, launch)
        elif e["name"] == "part-read":
            # the reader thread names the stage that started its feed
            assert e["args"]["parent"] == "prelude", e
            assert e["tid"] != launch["tid"]
            assert e["args"]["table"] == "staged" and e["args"]["pool"]
    assert _span_intervals_nest(events), events
    request = next(e for e in events if e["name"] == "request")
    for e in events:
        assert e["ts"] >= request["ts"] - eps and e["ts"] + e["dur"] \
            <= request["ts"] + request["dur"] + eps, (e, request)

    # each histogram is fed once per span, from the same measurement
    def fed(hist):
        return after.get(hist, (0, 0.0))[0] - before.get(hist, (0, 0.0))[0]

    def secs(hist):
        return after.get(hist, (0, 0.0))[1] - before.get(hist, (0, 0.0))[1]

    for name in top:
        assert fed("stage_seconds." + name.replace("-", "_")) \
            == names.count(name), name
    for name in _KIDS[path]:
        assert fed("launch_seconds." + name.replace("-", "_")) \
            == names.count(name), name
    assert fed("feed_seconds.part_read") == names.count("part-read")
    assert fed("request_seconds") == fed("host_offcpu_seconds") == 1
    # (signed: wall minus thread CPU summed over the host-only stages)
    assert abs(request["args"]["offcpu_s"]) <= request["dur"] / 1e6
    # the top-level stages partition the request: their sum fits in it,
    # and the launch's children fit in the launch
    stage_sum = sum(secs(h) for h in after if h.startswith("stage_seconds."))
    assert 0 < stage_sum <= secs("request_seconds") + 1e-4
    kids_sum = sum(secs(h) for h in after if h.startswith("launch_seconds."))
    assert 0 < kids_sum <= secs("stage_seconds.launch") + 1e-4
    if path == "tiled":
        # the run report and the histograms read one measurement
        assert fed("tile_seconds") == names.count("tile-step")
    else:
        # the packed answer (ISSUE 27): the one blocking read is
        # ``device-wait``; ``fetch`` is the host's part and says how many
        # reads the answer took (three int64 columns at the scan's
        # capacity, 480 kB each, and ``sel``: still one buffer)
        fetch = next(e for e in events if e["name"] == "fetch")
        assert fetch["args"]["reads"] == 1, fetch
        assert fetch["args"]["columns"] == 3 and fetch["args"]["bytes"] > 0
        assert names.count("device-wait") == names.count("fetch") == 1


def test_compiles_name_the_statement_that_paid(staged_store):
    """The tiled path has no generic plans: a new literal builds its
    programs again. ``compiles`` moves (the tiled ``_compile`` miss),
    ``xla_compiles`` moves by every program JAX hands to the compiler,
    and each leaves a ``compile`` span on the statement that paid; a
    repeat of the same text pays nothing."""
    s = cb.Session(Config().with_overrides(**staged_store["tiled"]))
    log = s.stmt_log
    s.sql(_STAGED_Q.format(6))
    c0, x0 = log.counter("compiles"), log.counter("xla_compiles")
    assert c0 >= 1 and x0 >= 3      # prelude, step, finalize at the least
    s.sql(_STAGED_Q.format(3))
    assert log.counter("compiles") == c0 + 1
    paid = log.counter("xla_compiles") - x0
    assert paid >= 1
    tr = s.stmt_log.traces(1)[0]
    spans = [e for e in tr["events"] if e["name"] == "compile"
             and e["args"].get("xla")]
    assert len(spans) == paid
    assert all(e["args"]["statement_id"] == tr["statement_id"]
               and e["dur"] > 0 for e in spans)
    assert log.registry.hist("xla_compile_seconds")["count"] \
        == log.counter("xla_compiles")
    s.sql(_STAGED_Q.format(3))
    assert log.counter("compiles") == c0 + 1
    assert log.counter("xla_compiles") == x0 + paid
    assert not [e for e in s.stmt_log.traces(1)[0]["events"]
                if e["name"] == "compile"]


def test_xla_compiles_sees_the_pool_hit_slice(tmp_path):
    """``compiles`` is silent where no statement-level program is built;
    the feed's first pool hit still compiles an eager slice (partitions
    of 5000 rows do not tile 16384 evenly), on the scan reader thread:
    ``xla_compiles`` counts it, named by statement."""
    base = {"storage.root": str(tmp_path / "store"),
            "storage.rows_per_partition": 5000}
    w = cb.Session(Config().with_overrides(**base))
    w.sql("create table staged (k bigint, v bigint, g bigint) "
          "distributed by (k)")
    w.sql("insert into staged values " + ",".join(
        f"({i},{i % 7},{i % 3})" for i in range(60_000)))
    cfg = Config().with_overrides(**dict(base, **{
        "resource.query_mem_bytes": 1 << 20,
        "bufferpool.max_bytes": 64 << 20,
        "bufferpool.admit_min_scans": 1}))
    s = cb.Session(cfg)
    log = s.stmt_log
    q = _STAGED_Q.format(2)
    for _ in range(6):
        c0, x0 = log.counter("compiles"), log.counter("xla_compiles")
        h0 = log.counter("bufpool_hits")
        s.sql(q)
        if log.counter("bufpool_hits") > h0:
            break
    else:
        pytest.fail("the pool never served the repeated scan")
    assert log.counter("compiles") == c0          # statement-cache hit
    assert log.counter("xla_compiles") > x0
    tr = s.stmt_log.traces(1)[0]
    assert any(e["name"] == "compile" and e["args"].get("xla")
               and e["args"]["statement_id"] == tr["statement_id"]
               for e in tr["events"]), tr["events"]
    assert any(e["name"] == "part-read" and e["args"]["pool"] == "hit"
               for e in tr["events"])


def test_host_stages_reach_a_profiler_session(staged_store, tmp_path):
    """With a profiler session on, the host plane holds the stages as
    ``cbtpu:`` events carrying the statement id, on the thread that did
    the work — traced statement or not (``obs.trace_sample`` 1000 here
    keeps this one's span tree out of the ring)."""
    import glob

    import jax

    cfg = Config().with_overrides(**staged_store["tiled"],
                                  **{"obs.trace_sample": 1000})
    s = cb.Session(cfg)
    s.sql(_STAGED_Q.format(1))          # sampled in: warms the programs
    with jax.profiler.trace(str(tmp_path)):
        s.sql(_STAGED_Q.format(1))
    assert len(s.stmt_log.traces(10)) == 1
    sid = s.stmt_log.recent(1)[0]["id"]
    found = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    prof = jax.profiler.ProfileData.from_file(found[-1])
    by_thread = {}
    for plane in prof.planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith("cbtpu:") and \
                            dict(e.stats).get("statement_id") == sid:
                        by_thread.setdefault(i, set()).add(e.name[6:])
    seen = set().union(*by_thread.values())
    assert {"bind", "admit", "queue-wait", "launch", "prelude", "h2d",
            "tile-step", "finalize", "part-read"} <= seen, seen
    # the reader thread's reads are on a line of their own
    assert any("part-read" in names and "launch" not in names
               for names in by_thread.values()), by_thread


def test_annotations_are_built_only_under_a_profiler_session(monkeypatch):
    """No profiler session: a stage tests a flag and builds no
    annotation object at all; with one on, a served one-shot statement
    builds one per stage (counts, not timings)."""
    from cloudberry_tpu.obs import trace as OT
    from cloudberry_tpu.serve import Client, Server

    built, session_on = [], [False]

    class Counting(OT.TraceAnnotation):
        def __init__(self, name, **kw):
            built.append(name)
            super().__init__(name, **kw)

        @staticmethod
        def is_enabled():
            return session_on[0]

    s = cb.Session()
    s.sql("create table an (k bigint, v bigint) distributed by (k)")
    s.catalog.table("an").set_data({
        "k": np.arange(300, dtype=np.int64),
        "v": np.arange(300, dtype=np.int64)}, {})
    with Server(session=s) as srv, Client(srv.host, srv.port) as c:
        c.sql("select v from an where k = 1")
        monkeypatch.setattr(OT, "TraceAnnotation", Counting)
        _served_trace(s.stmt_log, c, "select v from an where k = 2")
        assert built == []
        session_on[0] = True
        _served_trace(s.stmt_log, c, "select v from an where k = 3")
    assert 10 <= len(built) <= 16, built
    assert len(set(built)) >= 10 and all(
        n.startswith("cbtpu:") for n in built)


def test_obs_disabled_records_no_stage():
    off = cb.Session(Config().with_overrides(**{"obs.enabled": False}))
    off.sql("create table od (k bigint)")
    off.sql("insert into od values (1), (2)")
    assert off.sql("select count(*) as n from od").num_rows() == 1
    hists = off.stmt_log.registry.snapshot()["histograms"]
    assert not [h for h in hists if h.split(".")[0] in (
        "stage_seconds", "launch_seconds", "feed_seconds",
        "request_seconds", "host_offcpu_seconds")], hists


# ------------------------------------------------------- wire surface


@pytest.mark.parametrize("threaded", [False, True],
                         ids=["async", "threaded"])
def test_meta_obs_roundtrip_both_transports(threaded):
    from cloudberry_tpu.serve import Client, Server

    cfg = Config().with_overrides(**{"serve.threaded": threaded})
    s = cb.Session(cfg)
    s.sql("create table mt (k bigint, v bigint) distributed by (k)")
    s.catalog.table("mt").set_data({
        "k": np.arange(200, dtype=np.int64),
        "v": np.arange(200, dtype=np.int64)}, {})
    with Server(session=s) as srv:
        with Client(srv.host, srv.port) as c:
            for i in range(4):
                c.sql(f"select v from mt where k = {i}")
            m = c.meta("metrics")
            assert m["counters"]["dispatches"] >= 4
            assert "statement_seconds" in m["histograms"]
            assert m["series"] > 0 and "series_dropped" in m
            prom = c.meta("metrics", "prom")
            assert "# TYPE cbtpu_dispatches counter" in prom
            st = c.meta("statements")
            row = next(r for r in st if "mt" in r["query"])
            assert row["calls"] == 4 and row["wire_bytes"] > 0
            assert row["generic_hits"] == 3
            tr = c.meta("trace", 4)
            assert len(tr["traces"]) >= 1
            assert tr["chrome"]["traceEvents"]
            acts = c.meta("activity")
            assert isinstance(acts["recent"], list)


def test_server_render_stage_recorded():
    from cloudberry_tpu.serve import Client, Server

    s = cb.Session()
    s.sql("create table rr (k bigint)")
    s.sql("insert into rr values (1), (2), (3)")
    with Server(session=s) as srv:
        with Client(srv.host, srv.port) as c:
            c.sql("select k from rr")
    h = s.stmt_log.registry.hist("stage_seconds.render")
    assert h is not None and h["count"] >= 1


# ----------------------------------------------------------- lint pass


def test_lint_obs_counter_home(tmp_path):
    import textwrap

    from cloudberry_tpu.lint import run_lint
    from cloudberry_tpu.lint.config import LintConfig

    root = tmp_path / "pkg"
    (root / "sched").mkdir(parents=True)
    (root / "sched" / "thing.py").write_text(textwrap.dedent("""
        import collections


        class T:
            def __init__(self):
                self.counters = collections.Counter()
    """))
    result = run_lint([str(root)], LintConfig(exclude_files=frozenset()))
    hits = [f for f in result.unsuppressed
            if f.rule == "obs-counter-home"]
    assert hits and hits[0].file.endswith("sched/thing.py")


def test_lint_obs_meta_verbs_both_ways(tmp_path):
    import textwrap

    from cloudberry_tpu.lint import run_lint
    from cloudberry_tpu.lint.config import LintConfig

    root = tmp_path / "pkg"
    (root / "serve").mkdir(parents=True)
    (root / "serve" / "meta.py").write_text(textwrap.dedent('''
        def describe(session, kind, arg=None):
            """Answers. Kinds: tables | ghost."""
            if kind == "tables":
                return []
            if kind == "hidden":
                return {}
            raise ValueError(kind)
    '''))
    result = run_lint([str(root)], LintConfig(exclude_files=frozenset()))
    msgs = [f.message for f in result.unsuppressed
            if f.rule == "obs-meta-verbs"]
    assert any("'hidden' is implemented but missing" in m for m in msgs)
    assert any("'ghost' is documented but not implemented" in m
               for m in msgs)


def test_repo_meta_verbs_in_sync():
    """The live serve/meta.py passes its own contract (direct pin, so a
    pass regression cannot mask a drift)."""
    import os

    import cloudberry_tpu
    from cloudberry_tpu.lint import run_lint

    pkg = os.path.dirname(os.path.abspath(cloudberry_tpu.__file__))
    result = run_lint([os.path.join(pkg, "serve", "meta.py")])
    assert not [f for f in result.unsuppressed
                if f.rule == "obs-meta-verbs"]
