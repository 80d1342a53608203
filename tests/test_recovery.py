"""Mid-statement fault recovery (exec/recovery.py) — the chaos ladder.

The contract under test: killing a device (faultinject
``tile_device_lost``) at an ARBITRARY tile of a tiled statement, on one
segment or on the mesh, yields bit-identical results vs the
uninterrupted run, with ``tiles_replayed`` strictly less than the total tile count (resume from
the last K-tile checkpoint, not restart) — including the degraded case
where the survivor mesh has fewer segments than the original plan.
Plus the recovery/lifecycle interplay: an in-progress recovery counts
as liveness under the watchdog while the statement DEADLINE stays
enforced, retries back off with a visible budget, and the
fault-injection registry reports which seams fired."""

import time

import numpy as np
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu import lifecycle
from cloudberry_tpu.config import get_config
from cloudberry_tpu.utils import faultinject as FI


@pytest.fixture(autouse=True)
def _clean_faults():
    FI.reset_fault()
    yield
    FI.reset_fault()


# one merge-motion aggregate (dim distributed on a DIFFERENT key than
# the join key, so the probe redistributes and the GROUP BY needs a
# merge motion — the placement-free degraded-resume case) ...
DIST_Q = ("SELECT g, sum(v) AS sv, count(*) AS c "
          "FROM fact JOIN dim ON fact.d = dim.d "
          "GROUP BY g ORDER BY g")
# ... and one COLOCATED one-stage aggregate (grouping on the
# distribution key: no merge motion, so changed-nseg resume declines)
COLOC_Q = "SELECT k, sum(v) AS sv FROM fact GROUP BY k ORDER BY k LIMIT 20"

SINGLE_Q = ("SELECT g, sum(v) AS sv, count(*) AS c "
            "FROM fact JOIN dim ON fact.k = dim.k "
            "GROUP BY g ORDER BY g")


def _mk(nseg=1, budget=2 << 20, **extra):
    ov = {"n_segments": nseg,
          "resource.query_mem_bytes": budget,
          # small K so short test streams cross several checkpoints
          "recovery.checkpoint_every": 2}
    if nseg > 1:
        ov["planner.broadcast_threshold"] = 0
    ov.update(extra)
    return cb.Session(get_config().with_overrides(**ov))


def _load_single(s, n=200_000, nd=500):
    rng = np.random.default_rng(3)
    s.sql("CREATE TABLE dim (k BIGINT, g BIGINT) DISTRIBUTED BY (k)")
    s.sql("CREATE TABLE fact (k BIGINT, v BIGINT) DISTRIBUTED BY (k)")
    s.catalog.table("dim").set_data(
        {"k": np.arange(nd), "g": np.arange(nd) % 9})
    s.catalog.table("fact").set_data(
        {"k": rng.integers(0, nd, n), "v": rng.integers(0, 100, n)})


def _load_dist(s, n=400_000, nd=500):
    rng = np.random.default_rng(3)
    s.sql("CREATE TABLE dim (d BIGINT, g BIGINT) DISTRIBUTED BY (g)")
    s.sql("CREATE TABLE fact (k BIGINT, d BIGINT, v BIGINT) "
          "DISTRIBUTED BY (k)")
    s.catalog.table("dim").set_data(
        {"d": np.arange(nd), "g": np.arange(nd) % 9})
    # k: 997 distinct values — a colocatable GROUP BY key
    s.catalog.table("fact").set_data(
        {"k": np.arange(n) % 997,
         "d": rng.integers(0, nd, n),
         "v": rng.integers(0, 100, n)})


def _arm_kill(k: int) -> None:
    """Deterministic device loss at 0-based tile ``k`` of the NEXT
    attempt (the seam is hit once per tile; the retry's later hits fall
    outside the window)."""
    FI.inject_fault("tile_device_lost", "error",
                    start_hit=k + 1, end_hit=k + 1)


def _kill_and_run(s, q, k: int):
    """Arm a kill at tile k, run, and return (df, replayed, resumed,
    report)."""
    FI.reset_fault("tile_device_lost")
    _arm_kill(k)
    b_rep = s.stmt_log.counter("tiles_replayed")
    b_res = s.stmt_log.counter("tile_resumes")
    df = s.sql(q).to_pandas()
    return (df, s.stmt_log.counter("tiles_replayed") - b_rep,
            s.stmt_log.counter("tile_resumes") - b_res,
            s.last_tiled_report)


# --------------------------------------------------- kill-at-tile matrix


def test_tiled_kill_matrix():
    """Single-node tiled agg: kill at tile 0 / mid / last — bit-identical
    results, replay bounded by K (checkpoint granularity), never a full
    restart once a checkpoint exists."""
    s = _mk()
    _load_single(s)
    clean = s.sql(SINGLE_Q).to_pandas()
    total = s.last_tiled_report["n_tiles"]
    assert total >= 4  # the matrix needs a real stream
    for k in (0, total // 2, total - 1):
        df, replayed, resumed, rep = _kill_and_run(s, SINGLE_Q, k)
        assert clean.equals(df), f"kill@{k} diverged"
        assert replayed < total, f"kill@{k} replayed everything"
        if k >= 2:  # a checkpoint existed: resumed, ≤ K tiles replayed
            assert resumed == 1 and rep["resumed_from_tile"] > 0
            assert replayed <= 2
        assert rep["n_tiles"] == total


def test_tiled_dist_kill_matrix():
    """Distributed tiled agg (merge-motion two-stage): same matrix on
    the 8-segment mesh — per-tile SPMD steps resume from the
    per-segment accumulator snapshot."""
    s = _mk(nseg=8)
    _load_dist(s)
    clean = s.sql(DIST_Q).to_pandas()
    total = s.last_tiled_report["n_tiles"]
    assert total >= 4
    for k in (0, total // 2, total - 1):
        df, replayed, resumed, rep = _kill_and_run(s, DIST_Q, k)
        assert clean.equals(df), f"kill@{k} diverged"
        assert replayed < total, f"kill@{k} replayed everything"
        if k >= 2:
            assert resumed == 1 and rep["resumed_from_tile"] > 0
            assert replayed <= 2
        assert s.config.n_segments == 8  # no degrade without a probe arm


# --------------------------------------------------- degraded-mesh resume


def test_dist_degraded_resume():
    """The acceptance centerpiece: device loss mid-stream + a probe
    reporting one device gone — the statement resumes on the SEVEN
    survivors from the checkpoint (remaining rows re-sharded by the
    placement hash, partials re-placed round-robin ahead of the merge
    motion) and the result is bit-identical to the clean 8-segment
    run."""
    s = _mk(nseg=8)
    _load_dist(s)
    clean = s.sql(DIST_Q).to_pandas()
    total = s.last_tiled_report["n_tiles"]
    k = max(total // 2, 2)
    FI.inject_fault("probe_degraded", "skip")  # probe sees 7 devices
    df, replayed, resumed, rep = _kill_and_run(s, DIST_Q, k)
    assert s.config.n_segments == 7
    assert clean.equals(df)
    assert resumed == 1 and rep["resumed_from_tile"] > 0
    assert replayed < total and replayed <= 2
    assert rep["n_segments"] == 7
    # the degraded session keeps serving (and resuming) afterwards
    FI.reset_fault()
    assert clean.equals(s.sql(DIST_Q).to_pandas())


def test_dist_degraded_colocated_declines_but_completes():
    """Colocated one-stage agg partials would need the group-key hash to
    re-place on a smaller mesh: the changed-nseg resume DECLINES (a
    counted decision, not an error) and the statement re-executes fresh
    on the survivors — correct, just not incremental."""
    s = _mk(nseg=8, budget=1 << 20)
    _load_dist(s, n=800_000)
    clean = s.sql(COLOC_Q).to_pandas()
    total = s.last_tiled_report["n_tiles"]
    assert total >= 3
    k = min(max(total // 2, 2), total - 1)
    FI.inject_fault("probe_degraded", "skip")
    b_dec = s.stmt_log.counter("tile_resume_declined")
    df, replayed, resumed, rep = _kill_and_run(s, COLOC_Q, k)
    assert s.config.n_segments == 7
    assert clean.equals(df)
    assert resumed == 0
    assert s.stmt_log.counter("tile_resume_declined") - b_dec >= 1
    assert replayed == k  # honest accounting: the fresh run replays all


def test_dist_colocated_same_mesh_resumes():
    """An UNCHANGED mesh never needs re-placement: the colocated
    one-stage agg resumes verbatim from its per-segment snapshot."""
    s = _mk(nseg=8, budget=1 << 20)
    _load_dist(s, n=800_000)
    clean = s.sql(COLOC_Q).to_pandas()
    total = s.last_tiled_report["n_tiles"]
    k = min(max(total // 2, 2), total - 1)
    df, replayed, resumed, rep = _kill_and_run(s, COLOC_Q, k)
    assert clean.equals(df)
    assert resumed == 1 and rep["resumed_from_tile"] > 0
    assert replayed <= 2 < total


# --------------------------------------------------------- other modes


def test_tiled_topn_resume():
    """Top-N mode: the bounded accumulator snapshot resumes mid-stream
    (sort-key-only projection keeps boundary ties value-identical)."""
    q = "SELECT v, k FROM fact ORDER BY v DESC, k LIMIT 25"
    s = _mk(budget=1 << 20)
    _load_single(s)
    clean = s.sql(q).to_pandas()
    total = s.last_tiled_report["n_tiles"]
    assert s.last_tiled_report["mode"] == "topn" and total >= 4
    df, replayed, resumed, rep = _kill_and_run(s, q, max(total // 2, 2))
    assert clean.equals(df)
    assert resumed == 1 and replayed <= 2 < total


def test_tiled_sort_resume():
    """External-sort mode: the host-resident run store IS the
    checkpoint payload (shallow list pins); resume streams only the
    remaining tiles into it."""
    q = "SELECT v, k FROM fact WHERE v > 90 ORDER BY v, k"
    s = _mk(budget=1 << 20)
    _load_single(s)
    clean = s.sql(q).to_pandas()
    total = s.last_tiled_report["n_tiles"]
    assert s.last_tiled_report["mode"] == "sort" and total >= 4
    df, replayed, resumed, rep = _kill_and_run(s, q, max(total // 2, 2))
    assert clean.equals(df)
    assert resumed == 1 and replayed <= 2 < total


@pytest.mark.slow
def test_dist_topn_degraded_resume():
    """Distributed top-N on a shrunken mesh: the pooled per-segment
    heaps pre-select the global best m host-side (the device's own key
    normalization) and round-robin onto the survivors."""
    q = "SELECT v, k, d FROM fact ORDER BY v DESC, k, d LIMIT 25"
    s = _mk(nseg=8, budget=1 << 20)
    _load_dist(s)
    clean = s.sql(q).to_pandas()
    total = s.last_tiled_report["n_tiles"]
    assert s.last_tiled_report["mode"] == "topn" and total >= 4
    FI.inject_fault("probe_degraded", "skip")
    df, replayed, resumed, rep = _kill_and_run(s, q, max(total // 2, 2))
    assert s.config.n_segments == 7
    assert clean.equals(df)
    assert resumed == 1 and replayed <= 2 < total


@pytest.mark.slow
def test_dist_sort_degraded_resume():
    q = "SELECT v, k FROM fact WHERE v > 90 ORDER BY v, k"
    s = _mk(nseg=8, budget=1 << 20)
    _load_dist(s)
    clean = s.sql(q).to_pandas()
    total = s.last_tiled_report["n_tiles"]
    assert s.last_tiled_report["mode"] == "sort" and total >= 4
    FI.inject_fault("probe_degraded", "skip")
    df, replayed, resumed, rep = _kill_and_run(s, q, max(total // 2, 2))
    assert s.config.n_segments == 7
    assert clean.equals(df)
    assert resumed == 1 and replayed <= 2 < total


# ------------------------------------------------- checkpoint hygiene


def test_checkpoints_die_with_their_statement():
    s = _mk()
    _load_single(s)
    s.sql(SINGLE_Q)
    assert s._recovery._ckpts == {}  # discarded at statement end
    # a kill mid-statement leaves nothing behind either once recovered
    total = s.last_tiled_report["n_tiles"]
    _arm_kill(max(total // 2, 2))
    s.sql(SINGLE_Q)
    assert s._recovery._ckpts == {}


def test_ckpt_save_skip_forces_full_restart():
    """The ckpt_save chaos arm suppresses snapshots: recovery still
    works (stateless re-execution) but replays the whole consumed
    prefix — the pre-checkpoint world, pinned as the contrast case."""
    s = _mk()
    _load_single(s)
    clean = s.sql(SINGLE_Q).to_pandas()
    total = s.last_tiled_report["n_tiles"]
    k = max(total // 2, 2)
    FI.inject_fault("ckpt_save", "skip")
    df, replayed, resumed, rep = _kill_and_run(s, SINGLE_Q, k)
    assert clean.equals(df)
    assert resumed == 0 and replayed == k


def test_ckpt_resume_skip_forces_fresh_run():
    s = _mk()
    _load_single(s)
    clean = s.sql(SINGLE_Q).to_pandas()
    total = s.last_tiled_report["n_tiles"]
    k = max(total // 2, 2)
    FI.inject_fault("ckpt_resume", "skip")
    df, replayed, resumed, rep = _kill_and_run(s, SINGLE_Q, k)
    assert clean.equals(df)
    assert resumed == 0 and replayed == k


# ------------------------------------- watchdog / deadline interplay


def test_recovery_counts_as_liveness_under_watchdog():
    """A statement recovering within its deadline must NOT be cancelled
    by the watchdog: recovery is liveness (state 'recovering' in the
    activity row), and only the DEADLINE can kill it."""
    s = _mk(**{"statement_timeout_s": 120.0, "health.backoff_s": 0.05})
    wd = lifecycle.Watchdog(s.stmt_log, interval_s=0.01).start()
    try:
        _load_single(s)
        clean = s.sql(SINGLE_Q).to_pandas()
        total = s.last_tiled_report["n_tiles"]
        df, _, resumed, _ = _kill_and_run(s, SINGLE_Q,
                                          max(total // 2, 2))
        assert clean.equals(df) and resumed == 1
        assert s.stmt_log.counter("watchdog_timeouts") == 0
    finally:
        wd.stop()


def test_deadline_enforced_during_recovery_backoff():
    """The deadline governs the RESUME too: a huge backoff must neither
    sleep past the statement deadline nor dispatch another attempt
    after it — the statement dies of StatementTimeout (the deadline
    verdict), not of a hang classification or the injected fault."""
    s = _mk(**{"statement_timeout_s": 0.5, "health.backoff_s": 30.0,
               "health.retries": 3})
    s.sql("create table t1 (x bigint)")
    s.catalog.table("t1").set_data({"x": np.arange(64, dtype=np.int64)})
    FI.inject_fault("exec_device_lost", "error")  # every dispatch
    t0 = time.monotonic()
    with pytest.raises(lifecycle.StatementTimeout):
        s.sql("select sum(x) from t1")
    assert time.monotonic() - t0 < 5.0  # not 30s of backoff


def test_retry_budget_stops_redispatch():
    """health.retry_budget_s bounds a statement's recovery spend: once
    failed attempts have consumed it, the next recoverable failure
    raises instead of retrying."""
    s = _mk(**{"health.retries": 5, "health.backoff_s": 0.01,
               "health.retry_budget_s": 1e-6})
    s.sql("create table t1 (x bigint)")
    s.catalog.table("t1").set_data({"x": np.arange(8, dtype=np.int64)})
    FI.inject_fault("exec_device_lost", "error")
    with pytest.raises(FI.InjectedFault):
        s.sql("select sum(x) from t1")
    # the budget refused every re-dispatch: exactly one attempt ran
    assert FI.list_faults()["armed"]["exec_device_lost"]["fired"] == 1


def test_retry_visible_in_activity_history():
    s = _mk(**{"health.backoff_s": 0.01})
    s.sql("create table t1 (x bigint)")
    s.catalog.table("t1").set_data({"x": np.arange(8, dtype=np.int64)})
    FI.inject_fault("exec_device_lost", "error", start_hit=1, end_hit=1)
    s.sql("select sum(x) from t1")
    entry = s.stmt_log.recent(1)[0]
    assert entry["attempts"] == 1
    assert entry["backoff_s"] > 0
    assert entry["last_error"] == "InjectedFault"
    assert s.stmt_log.counter("recoveries") == 1
    assert s.stmt_log.counter("recovery_wall_ms") >= 0


# -------------------------------------------- faultinject chaos arms


def test_probabilistic_arm_fires_reproducibly():
    def fire_count(n=200):
        fired = 0
        for _ in range(n):
            try:
                FI.fault_point("p_seam")
            except FI.InjectedFault:
                fired += 1
        return fired

    FI.inject_fault("p_seam", "error", p=0.4, seed=7)
    f1 = fire_count()
    info = FI.list_faults()["armed"]["p_seam"]
    assert info["hits"] == 200 and info["fired"] == f1
    assert 40 < f1 < 160  # probabilistic, not all-or-nothing
    # same seed → same firing sequence (reproducible soaks)
    FI.inject_fault("p_seam", "error", p=0.4, seed=7)
    assert fire_count() == f1
    assert "p_seam" in FI.list_faults()["seen"]


def test_list_faults_reports_armed_window():
    FI.inject_fault("w_seam", "skip", start_hit=3, end_hit=4)
    for _ in range(5):
        FI.fault_point("w_seam")
    info = FI.list_faults()["armed"]["w_seam"]
    assert info["hits"] == 5 and info["fired"] == 2
    assert info["start_hit"] == 3 and info["end_hit"] == 4


# ------------------------------------------------- serving / tooling


def test_serve_bench_chaos_smoke():
    """CPU smoke of the --chaos workload: the spill mix streams tiles
    under probabilistic device loss and the CSV row carries the
    recovery counters."""
    import tools.serve_bench as SB

    r = SB.run_mode("direct", "spill", clients=2, duration_s=1.0,
                    rows=200_000, tick_s=0.002, max_batch=8, chaos=0.2)
    assert r["requests"] > 0
    for k in ("recovery_count", "tiles_replayed", "recovery_ms"):
        assert k in r and r[k] >= 0
    row = SB.csv_row(r)
    assert len(row.split(",")) == len(SB.CSV_HEADER.split(","))


def test_meta_info_recovery_counters():
    from cloudberry_tpu.serve.meta import describe

    s = _mk()
    _load_single(s)
    total_before = s.sql(SINGLE_Q).to_pandas()
    _arm_kill(2)
    s.sql(SINGLE_Q)
    info = describe(s, "info")
    rec = info["recovery"]
    assert rec["recoveries"] >= 1 and rec["tile_checkpoints"] >= 1
    assert rec["tile_resumes"] >= 1
    del total_before


# ------------------------------------------------------- chaos soak


@pytest.mark.slow
def test_chaos_soak_randomized_tpch():
    """Randomized fault-point × TPC-H soak: probabilistic device losses
    across the tile stream (plus dispatch-seam losses) must never change
    results vs a clean run, and the fault registry reports exactly which
    seams fired."""
    from tools.tpch_oracle import ORACLES
    from tools.tpch_queries import QUERIES
    from tools.tpchgen import load_tpch

    big = cb.Session(get_config().with_overrides(
        **{"n_segments": 1}))
    load_tpch(big, sf=0.02, seed=7)
    tables = {n: t.to_pandas() for n, t in big.catalog.tables.items()}

    for qn in ("q5", "q9"):
        exp = ORACLES[qn](tables)
        for seed in (1, 2, 3):
            s = _mk(budget=10 << 20,
                    **{"health.retries": 6, "health.backoff_s": 0.01})
            load_tpch(s, sf=0.02, seed=7)
            FI.reset_fault()
            # bounded window: random kills early in the stream, then the
            # arm goes inert so the soak always terminates (each failing
            # attempt consumes exactly one fired hit)
            FI.inject_fault("tile_device_lost", "error", p=0.25,
                            seed=seed, end_hit=10)
            got = s.sql(QUERIES[qn]).to_pandas()
            FI.reset_fault()
            assert s.last_tiled_report["n_tiles"] > 1
            assert len(got) == len(exp), f"{qn} seed={seed}"
            for gc, ec in zip(got.columns, exp.columns):
                g, e = got[gc].to_numpy(), exp[ec].to_numpy()
                if g.dtype.kind == "f" or e.dtype.kind == "f":
                    np.testing.assert_allclose(
                        g.astype(np.float64), e.astype(np.float64),
                        rtol=1e-9, atol=1e-2,
                        err_msg=f"{qn}.{gc} seed={seed}")
                else:
                    np.testing.assert_array_equal(
                        g, e, err_msg=f"{qn}.{gc} seed={seed}")
    # the soak's report of record: the tile seam fired at least once
    # across the run (list_faults survives reset only via 'seen')
    assert "tile_device_lost" in FI.known_fault_points()
