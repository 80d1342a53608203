"""Windowed in-flight tile dispatch (exec/tilepipe.py) — the async
tile-step pipeline over the tiled executors.

The contract under test: window on/off is BIT-IDENTICAL across every
tiled mode (agg/topn/sort/window, single-node and dist8) because the
window only moves WHEN the host learns of a tile's control scalars,
never what executes; a capacity overflow observed up to W tiles late
replays from the last drained-clean checkpoint and still converges to
the synchronous answer; device loss mid-window resumes with ≤ W+K
tiles replayed (in-flight tiles never count as progress); cancellation
mid-window dies promptly with no orphan threads and a clean rerun; the
``tile_enqueue``/``tile_drain`` fault seams fire and recover; and the
sentinel's per-tile stat fetch is skipped outright when feedback is
off (``tile_stat_syncs`` pins the no-host-sync claim both ways).
"""

import threading
import time

import numpy as np
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu import lifecycle
from cloudberry_tpu.config import get_config
from cloudberry_tpu.exec import tilepipe as TP
from cloudberry_tpu.utils import faultinject as FI

AGG_Q = ("SELECT g, sum(v) AS sv, count(*) AS c "
         "FROM fact JOIN dim ON fact.k = dim.k GROUP BY g ORDER BY g")
TOPN_Q = ("SELECT fact.k AS k, v, g FROM fact JOIN dim ON fact.k = dim.k "
          "WHERE v < 90 ORDER BY v, fact.k, g LIMIT 25")
SORT_Q = ("SELECT g, v FROM fact JOIN dim ON fact.k = dim.k "
          "WHERE v < 50 ORDER BY g, v DESC, fact.k")
WIN_Q = ("SELECT g, v, rank() over (partition by g order by v desc) AS r,"
         " sum(v) over (partition by g) AS sv "
         "FROM fact JOIN dim ON fact.k = dim.k")


def _load(s, n_fact=120_000, n_dim=500, n_groups=9):
    rng = np.random.default_rng(3)
    s.sql("CREATE TABLE dim (k BIGINT, g BIGINT) DISTRIBUTED BY (k)")
    s.sql("CREATE TABLE fact (k BIGINT, v BIGINT) DISTRIBUTED BY (k)")
    s.catalog.table("dim").set_data(
        {"k": np.arange(n_dim), "g": np.arange(n_dim) % n_groups})
    s.catalog.table("fact").set_data(
        {"k": rng.integers(0, n_dim, n_fact),
         "v": rng.integers(0, 100, n_fact)})


def _mk(budget=None, window=None, nseg=1, **extra):
    ov = {"n_segments": nseg}
    if budget is not None:
        ov["resource.query_mem_bytes"] = budget
    if window is not None:
        ov["tile_pipeline.inflight_tiles"] = window
    ov.update(extra)
    return cb.Session(get_config().with_overrides(**ov))


@pytest.fixture(autouse=True)
def _clean_faults():
    FI.reset_fault()
    yield
    FI.reset_fault()


# ------------------------------------------------------ window semantics


def test_effective_window_defaults():
    """auto (inflight_tiles=0) is 1 on CPU — the legacy loop exactly —
    and the accelerator default elsewhere; explicit values clamp."""
    cfg = get_config()
    assert TP.effective_window(cfg, "cpu") == 1
    assert TP.effective_window(cfg, "tpu") == TP._AUTO_ACCEL_WINDOW
    cfg3 = cfg.with_overrides(**{"tile_pipeline.inflight_tiles": 3})
    assert TP.effective_window(cfg3, "cpu") == 3
    off = cfg.with_overrides(**{"tile_pipeline.enabled": False,
                                "tile_pipeline.inflight_tiles": 8})
    assert TP.effective_window(off, "tpu") == 1
    huge = cfg.with_overrides(**{"tile_pipeline.inflight_tiles": 10_000})
    assert TP.effective_window(huge, "cpu") == TP._MAX_WINDOW


def test_step_donation_shared_rule():
    assert TP.step_donation("cpu") == ()
    assert TP.step_donation("tpu") == (4,)
    assert TP.step_donation("gpu", argnum=2) == (2,)


def test_window_charge_zero_at_one():
    """window=1 charges nothing extra (existing capacity reports and
    their pinned tests are untouched on the CPU default); wider windows
    charge (W-1) in-flight tiles."""
    s = _mk(budget=3 << 20, window=1)
    _load(s)
    s.sql(AGG_Q)
    base = s.last_tiled_report["est_pipeline_bytes"]
    s4 = _mk(budget=3 << 20, window=4)
    _load(s4)
    s4.sql(AGG_Q)
    rep = s4.last_tiled_report
    assert rep["est_pipeline_bytes"] > base
    per_tile = (rep["est_pipeline_bytes"] - base) // 3
    assert per_tile > 0  # 3 extra in-flight tiles at W=4


# ------------------------------------------------- on/off bit-identity


@pytest.fixture(scope="module")
def expected():
    s = _mk()
    _load(s)
    return {q: s.sql(q).to_pandas() for q in (AGG_Q, TOPN_Q, SORT_Q,
                                              WIN_Q)}


@pytest.mark.parametrize("q,mode", [(AGG_Q, None), (TOPN_Q, "topn"),
                                    (SORT_Q, "sort"), (WIN_Q, "window")],
                         ids=["agg", "topn", "sort", "window"])
def test_window_bit_identical_single(expected, q, mode):
    got = {}
    for w in (1, 2, 4):
        s = _mk(budget=3 << 20, window=w)
        _load(s)
        got[w] = s.sql(q).to_pandas()
        rep = s.last_tiled_report
        assert rep["tiled"] and rep["n_tiles"] > 1
        if mode is not None:
            assert rep["mode"] == mode
        assert rep["tile_window"] == w
        assert 1 <= rep["inflight_depth"] <= w
        assert rep["drain_stall_s"] >= 0.0
        if w > 1:
            assert rep["inflight_depth"] > 1
    assert got[1].equals(got[2]) and got[1].equals(got[4])
    if mode != "window":  # window row order is sort-compared elsewhere
        assert expected[q].equals(got[1])


# per-mode dist8 shapes mirror test_scan_pipeline's matrix: the window
# path needs finer groups over more rows at the budget whose spill
# chunk capacity holds a partition
_DIST8 = [(AGG_Q, None, 1 << 20, 120_000, 9),
          (TOPN_Q, "topn", 1 << 20, 120_000, 9),
          (SORT_Q, "sort", 1 << 20, 120_000, 9),
          (WIN_Q, "window", 4 << 20, 240_000, 300)]


@pytest.mark.parametrize("q,mode,budget,n_fact,n_groups", _DIST8,
                         ids=["agg", "topn", "sort", "window"])
def test_window_bit_identical_dist8(q, mode, budget, n_fact, n_groups):
    got = {}
    for w in (1, 4):
        s = _mk(budget=budget, window=w, nseg=8)
        _load(s, n_fact=n_fact, n_groups=n_groups)
        got[w] = s.sql(q).to_pandas()
        rep = s.last_tiled_report
        assert rep["tiled"] and rep["n_tiles"] > 1
        assert rep["tile_window"] == w
    assert got[1].equals(got[4])


# ------------------------------------------- deferred overflow + replay


def test_deferred_overflow_replays_bit_identical():
    """A merge overflow whose check drains AFTER newer tiles were
    dispatched: the deferral is counted, the adaptive retry replays the
    window from the last drained-clean checkpoint, and the answer (and
    grown accumulator) match the synchronous run exactly."""
    def load(s):
        rng = np.random.default_rng(3)
        s.sql("CREATE TABLE fact (k BIGINT, v BIGINT) "
              "DISTRIBUTED BY (k)")
        s.catalog.table("fact").set_data(
            {"k": rng.integers(0, 10_000, 200_000),
             "v": rng.integers(0, 100, 200_000)})

    # expression group key: NDV unknown -> sqrt estimate, true count 7k
    q = ("SELECT k % 7000 AS kk, count(*) AS c, sum(v) AS sv "
         "FROM fact GROUP BY k % 7000 ORDER BY kk LIMIT 50")
    res = {}
    for w in (1, 4):
        s = _mk(budget=4 << 20, window=w)
        load(s)
        res[w] = s.sql(q).to_pandas()
        log = s.stmt_log
        if w == 1:
            assert log.counter("tile_deferred_overflows") == 0
            assert log.counter("tile_window_replays") == 0
        else:
            assert log.counter("tile_deferred_overflows") >= 1
            assert log.counter("tile_window_replays") >= 1
        assert s.last_tiled_report["acc_capacity"] >= 7000
    assert res[1].equals(res[4])


# --------------------------------------------------- mid-window resume


def test_device_loss_mid_window_replays_at_most_w_plus_k():
    """Device loss with a full window in flight: resume from the last
    drained-clean checkpoint replays ≤ W+K tiles (in-flight launches
    never counted as progress), bit-identical."""
    W, K = 4, 2
    s0 = _mk(budget=1 << 20)
    _load(s0)
    exp = s0.sql(AGG_Q).to_pandas()
    total = s0.last_tiled_report["n_tiles"]
    assert total >= 6

    s = _mk(budget=1 << 20, window=W,
            **{"recovery.checkpoint_every": K,
               "health.retries": 2, "health.backoff_s": 0.01})
    _load(s)
    FI.inject_fault("tile_device_lost", "error", start_hit=6, end_hit=6)
    b = s.stmt_log.counter("tiles_replayed")
    got = s.sql(AGG_Q).to_pandas()
    FI.reset_fault()
    assert exp.equals(got)
    rep = s.last_tiled_report
    assert rep["resumed_from_tile"] >= 1
    assert s.stmt_log.counter("tiles_replayed") - b <= W + K


def test_degraded_8_to_7_resume_with_open_window():
    """The PR-6 acceptance centerpiece with a non-empty dispatch
    window: device loss mid-stream + a probe reporting one device gone
    resumes on the SEVEN survivors from the drained checkpoint,
    bit-identical to the clean 8-segment run."""
    s = _mk(nseg=8, budget=2 << 20, window=4,
            **{"planner.broadcast_threshold": 0,
               "recovery.checkpoint_every": 2,
               "health.retries": 2, "health.backoff_s": 0.01})
    rng = np.random.default_rng(3)
    s.sql("CREATE TABLE dim (d BIGINT, g BIGINT) DISTRIBUTED BY (g)")
    s.sql("CREATE TABLE fact (k BIGINT, d BIGINT, v BIGINT) "
          "DISTRIBUTED BY (k)")
    s.catalog.table("dim").set_data(
        {"d": np.arange(500), "g": np.arange(500) % 9})
    n = 400_000
    s.catalog.table("fact").set_data(
        {"k": np.arange(n) % 997,
         "d": rng.integers(0, 500, n),
         "v": rng.integers(0, 100, n)})
    q = ("SELECT g, sum(v) AS sv, count(*) AS c "
         "FROM fact JOIN dim ON fact.d = dim.d GROUP BY g ORDER BY g")
    clean = s.sql(q).to_pandas()
    total = s.last_tiled_report["n_tiles"]
    k = max(total // 2, 2)
    FI.inject_fault("probe_degraded", "skip")  # probe sees 7 devices
    FI.inject_fault("tile_device_lost", "error",
                    start_hit=k + 1, end_hit=k + 1)
    got = s.sql(q).to_pandas()
    FI.reset_fault()
    assert s.config.n_segments == 7
    assert clean.equals(got)
    rep = s.last_tiled_report
    assert rep["n_segments"] == 7 and rep["resumed_from_tile"] > 0


# -------------------------------------------------------- cancellation


def test_cancel_mid_window_no_orphan_inflight():
    """Cancel lands while a full window is in flight (the consumer is
    slowed by a tile_step sleep): the statement dies with
    StatementCancelled within the W-tile drain bound, no stray threads
    survive, and a rerun on the same session is bit-identical."""
    expect_s = _mk(budget=1 << 20)
    _load(expect_s)
    expect = expect_s.sql(AGG_Q).to_pandas()

    s = _mk(budget=1 << 20, window=4)
    _load(s)
    FI.inject_fault("tile_step", "sleep", sleep_s=0.05)
    errs = []
    before = set(threading.enumerate())

    def bg():
        try:
            s.sql(AGG_Q)
        except BaseException as e:  # noqa: BLE001 — assertion target
            errs.append(e)

    th = threading.Thread(target=bg)
    th.start()
    act = None
    for _ in range(500):
        act = s.stmt_log.activity()
        if act:
            break
        time.sleep(0.01)
    assert act, "statement never appeared in the activity view"
    time.sleep(0.25)  # let the window fill behind the slow steps
    assert s.stmt_log.cancel(act[0]["id"])
    th.join(timeout=60)
    assert errs and isinstance(errs[0], lifecycle.StatementCancelled)
    # abandoned in-flight launches leave no threads behind (JAX's async
    # dispatch completes into garbage-collected buffers). Only threads
    # THIS statement started count: on a multi-core host an earlier
    # test's store scan leaves the process-lifetime cbtpu-scan-decode
    # pool alive by design (exec/scanpipe.py decode pool), and this
    # statement may be the one that creates it.
    stray = [t.name for t in threading.enumerate()
             if t not in before and t.is_alive()
             and t.name.startswith("cbtpu-")
             and not t.name.startswith("cbtpu-scan-decode")]
    assert not stray, stray

    FI.reset_fault()
    got = s.sql(AGG_Q).to_pandas()
    assert expect.equals(got)


# --------------------------------------------------------- fault seams


def test_enqueue_drain_seams_fire_and_recover():
    """The new dispatch seams are live: an error on either raises out
    of the statement (counted by the registry), a sleep on tile_drain
    lands in drain_stall_s, and a reset rerun is bit-identical."""
    s = _mk(budget=3 << 20, window=4)
    _load(s)
    exp = s.sql(AGG_Q).to_pandas()

    for seam in ("tile_enqueue", "tile_drain"):
        FI.inject_fault(seam, "error", start_hit=2, end_hit=2)
        with pytest.raises(Exception) as ei:
            s.sql(AGG_Q)
        assert seam in str(ei.value)
        FI.reset_fault()
        assert exp.equals(s.sql(AGG_Q).to_pandas())
    assert {"tile_enqueue", "tile_drain"} <= set(FI.known_fault_points())

    FI.inject_fault("tile_drain", "sleep", sleep_s=0.02)
    assert exp.equals(s.sql(AGG_Q).to_pandas())
    FI.reset_fault()
    assert s.last_tiled_report["drain_stall_s"] >= 0.02


# ------------------------------------------- no-host-sync stat fetches


def test_stat_sync_skipped_when_feedback_off():
    """Satellite pin for the removed per-tile host sync: with feedback
    disabled the sentinel's srows never leave the device (zero
    tile_stat_syncs); enabled, the drains fold them as before."""
    def load(s):
        rng = np.random.default_rng(3)
        s.sql("CREATE TABLE dim (d BIGINT, g BIGINT) DISTRIBUTED BY (g)")
        s.sql("CREATE TABLE fact (k BIGINT, d BIGINT, v BIGINT) "
              "DISTRIBUTED BY (k)")
        s.catalog.table("dim").set_data(
            {"d": np.arange(500), "g": np.arange(500) % 9})
        n = 200_000
        s.catalog.table("fact").set_data(
            {"k": np.arange(n) % 997,
             "d": rng.integers(0, 500, n),
             "v": rng.integers(0, 100, n)})

    q = ("SELECT g, sum(v) AS sv FROM fact JOIN dim ON fact.d = dim.d "
         "GROUP BY g ORDER BY g")
    res = {}
    for fb in (False, True):
        s = _mk(budget=2 << 20, window=2, nseg=8,
                **{"planner.broadcast_threshold": 0,
                   "feedback.enabled": fb})
        load(s)
        res[fb] = s.sql(q).to_pandas()
        assert s.last_tiled_report["n_tiles"] > 1
        syncs = s.stmt_log.counter("tile_stat_syncs")
        if fb:
            assert syncs > 0
        else:
            assert syncs == 0
    assert res[False].equals(res[True])


# ----------------------------------------------------- trailer / gauge


def test_explain_analyze_dispatch_trailer():
    """EXPLAIN ANALYZE's tiled trailer grows a dispatch line only when
    a window was open — window=1 keeps the legacy trailer exactly."""
    for w, present in ((1, False), (4, True)):
        s = _mk(budget=1 << 20, window=w)
        s.sql("create table big (k bigint, v double)")
        n = 200_000
        s.catalog.table("big").set_data({
            "k": np.arange(n, dtype=np.int64) % 97,
            "v": np.arange(n, dtype=np.float64)}, {})
        text = s.explain_analyze(
            "select k, sum(v) as sv from big group by k")
        assert "Tiled execution" in text, text
        assert ("tile dispatch: window" in text) is present, text
        if present:
            assert f"window {w}" in text
            g = s.stmt_log.registry.snapshot()["gauges"]
            assert g.get("tile_inflight", 0) > 1
