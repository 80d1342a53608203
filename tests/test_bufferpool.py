"""HBM-resident buffer pool (exec/bufferpool.py) — ISSUE 16.

The contract under test: hot scans are served from device-resident
decoded chunks with ZERO host reads/decodes once admitted (the
``bufpool_*``/``host_decodes`` counters pin it); pool-on vs pool-off is
BIT-IDENTICAL across the tiled matrix at 1 and 8 segments including
mid-statement device loss; every invalidation axis — store VERSION
bump, config-epoch swap, topology-epoch flip (forced regression via a
config-uid collision, the PR-13 stale-nseg pattern) — means a stale
entry's key can never be asked for again; admission is by observed scan
frequency with LRU-by-bytes eviction that REFUSES rather than evicting
a hotter victim; and a 4-thread admission/eviction stress stays clean
under the runtime lock-order witness.
"""

import threading
import time

import numpy as np
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import get_config
from cloudberry_tpu.exec import bufferpool as BUF
from cloudberry_tpu.utils import faultinject as FI

AGG_Q = "select g, sum(v) as sv, count(*) as c from fact group by g order by g"
TOPN_Q = "select k, v from fact where v < 90 order by v, k limit 25"
SORT_Q = "select k, v from fact where v < 5 order by v desc, k"
WIN_Q = ("select g, v, rank() over (partition by g order by v desc) as r,"
         " sum(v) over (partition by g) as sv from fact")


@pytest.fixture(autouse=True)
def _clean_faults():
    FI.reset_fault()
    yield
    FI.reset_fault()


def _mk_store(root, n=120_000, n_groups=9, parts=20_000, nseg=1):
    """Write a cold fact table (k, g, v) under ``root`` and return the
    writer session (readers open fresh sessions over the same root)."""
    s = cb.Session(get_config().with_overrides(**{
        "n_segments": nseg, "storage.root": root,
        "storage.rows_per_partition": parts}))
    rng = np.random.default_rng(5)
    s.sql("create table fact (k bigint, g bigint, v bigint) "
          "distributed by (k)")
    s.catalog.table("fact").set_data({
        "k": (np.arange(n, dtype=np.int64) * 7) % 997,
        "g": rng.integers(0, n_groups, n).astype(np.int64),
        "v": rng.integers(0, 100, n).astype(np.int64)})
    return s


def _open(root, nseg=1, budget=None, pool=True, **extra):
    ov = {"n_segments": nseg, "storage.root": root}
    if budget is not None:
        ov["resource.query_mem_bytes"] = budget
    if not pool:
        ov["bufferpool.enabled"] = False
    ov.update(extra)
    return cb.Session(get_config().with_overrides(**ov))


def _ent(n=512, seed=0):
    return {"cols": {"v": np.arange(seed, seed + n, dtype=np.int64)},
            "validity": {}}


_NB = 512 * 8  # _ent() bytes


# ------------------------------------------------------------ unit: policy


def test_admission_needs_min_scans():
    p = BUF.BufferPool(max_bytes=1 << 20, admit_min_scans=2)
    k = ("part", "t", 1, "p0", ("v",), 1, 0)
    assert p.lookup(k) is None           # freq 1
    assert not p.offer(k, _ent(), device=False)
    assert p.lookup(k) is None           # freq 2
    assert p.offer(k, _ent(), device=False)
    assert p.lookup(k) is not None       # resident now
    snap = p.snapshot()
    assert snap["entries"] == 1 and snap["bytes"] == _NB
    # re-offering a resident key is a no-op, not a double charge
    assert not p.offer(k, _ent(), device=False)
    assert p.snapshot()["bytes"] == _NB


def test_lru_eviction_under_byte_budget():
    p = BUF.BufferPool(max_bytes=3 * _NB, admit_min_scans=2)
    keys = [("part", "t", 1, f"p{i}", ("v",), 1, 0) for i in range(4)]
    for k in keys[:3]:
        p.lookup(k), p.lookup(k)
        assert p.offer(k, _ent(), device=False)
    p.lookup(keys[0])  # touch: k0 is now most-recent, k1 is the head
    p.lookup(keys[3]), p.lookup(keys[3])
    assert p.offer(keys[3], _ent(), device=False)
    snap = p.snapshot()
    assert snap["entries"] == 3 and snap["evictions"] == 1
    with p._lock:
        resident = set(p._entries)
    assert keys[1] not in resident  # the true LRU head went
    assert keys[0] in resident and keys[3] in resident


def test_refusal_over_evicting_hotter():
    p = BUF.BufferPool(max_bytes=_NB, admit_min_scans=2)
    hot = ("part", "t", 1, "hot", ("v",), 1, 0)
    for _ in range(5):
        p.lookup(hot)
    assert p.offer(hot, _ent(), device=False)
    cold = ("part", "t", 1, "cold", ("v",), 1, 0)
    p.lookup(cold), p.lookup(cold)
    assert not p.offer(cold, _ent(), device=False)
    snap = p.snapshot()
    assert snap["refusals"] == 1 and snap["evictions"] == 0
    assert p.lookup(hot) is not None  # the hotter victim survived


def test_oversize_chunk_refused_not_flushed():
    p = BUF.BufferPool(max_bytes=_NB, admit_min_scans=1)
    small = ("part", "t", 1, "s", ("v",), 1, 0)
    p.lookup(small)
    assert p.offer(small, _ent(), device=False)
    big = ("part", "t", 1, "b", ("v",), 1, 0)
    p.lookup(big)
    assert not p.offer(big, _ent(n=4096), device=False)
    snap = p.snapshot()
    assert snap["refusals"] == 1 and snap["entries"] == 1


def test_sweep_clear_and_grow_only():
    p = BUF.BufferPool(max_bytes=1 << 20, admit_min_scans=1)
    for i in range(3):
        k = ("part", "t", 1, f"p{i}", ("v",), 1, 0)
        p.lookup(k)
        assert p.offer(k, _ent(), device=False)
    assert p.sweep(lambda k: k[3] == "p1") == 1
    assert p.snapshot()["entries"] == 2
    assert p.snapshot()["bytes"] == 2 * _NB
    assert p.clear() == 2
    snap = p.snapshot()
    assert snap["entries"] == 0 and snap["bytes"] == 0
    assert snap["tracked_keys"] == 0  # heat resets with the placement
    p.grow(2 << 20)
    assert p.snapshot()["max_bytes"] == 2 << 20
    p.grow(1 << 10)  # never shrinks under a peer session
    assert p.snapshot()["max_bytes"] == 2 << 20


def test_fault_seams_suppress_admit_and_force_refusal():
    p = BUF.BufferPool(max_bytes=_NB, admit_min_scans=1)
    k = ("part", "t", 1, "p0", ("v",), 1, 0)
    p.lookup(k)
    FI.inject_fault("bufpool_admit", "skip")
    assert not p.offer(k, _ent(), device=False)
    FI.reset_fault("bufpool_admit")
    assert p.offer(k, _ent(), device=False)
    # pool is full: an eviction-requiring offer with the evict seam
    # armed refuses instead of displacing
    k2 = ("part", "t", 1, "p1", ("v",), 1, 0)
    p.lookup(k2), p.lookup(k2)
    FI.inject_fault("bufpool_evict", "skip")
    assert not p.offer(k2, _ent(), device=False)
    assert p.lookup(k) is not None
    FI.reset_fault("bufpool_evict")
    with pytest.raises(FI.InjectedFault):
        FI.inject_fault("bufpool_admit", "error")
        p.lookup(k2)
        p.offer(k2, _ent(), device=False)


# ------------------------------------------- hot scans serve from the pool


def test_hot_tiled_scan_zero_host_decodes(tmp_path):
    """The headline behavior: scans 1-2 observe and admit, scan 3+ of
    the same tiled statement touch NO partition files — bufpool hits
    with a zero host_decodes delta — and stay bit-identical. The
    capacity plane sees the residency (est_bufpool_bytes, mem_bufpool_*
    gauges)."""
    from cloudberry_tpu.obs import capacity

    root = str(tmp_path / "store")
    _mk_store(root)
    s = _open(root, budget=1 << 20)
    assert s.catalog.table("fact").cold

    def ctr(n):
        return s.stmt_log.counter(n)

    res, deltas = [], []
    for _ in range(4):
        before = {n: ctr(n) for n in ("bufpool_hits", "bufpool_misses",
                                      "bufpool_admits", "host_decodes")}
        res.append(s.sql(AGG_Q).to_pandas())
        deltas.append({n: ctr(n) - v for n, v in before.items()})
    assert all(res[0].equals(r) for r in res[1:])
    rep = s.last_tiled_report
    assert rep["tiled"] and rep["n_tiles"] > 1
    # scan 1: all misses, nothing admitted yet (admit_min_scans=2)
    assert deltas[0]["bufpool_misses"] > 0
    assert deltas[0]["bufpool_admits"] == 0
    # scan 2: misses again, but every partition admits
    assert deltas[1]["bufpool_admits"] == deltas[1]["bufpool_misses"] > 0
    # scans 3-4: served from HBM — zero host reads/decodes
    for d in deltas[2:]:
        assert d["bufpool_hits"] > 0 and d["bufpool_misses"] == 0
        assert d["host_decodes"] == 0
    assert rep["pipeline"]["parts_resident"] > 0
    assert rep["est_bufpool_bytes"] > 0
    vals = capacity.refresh_gauges(s)
    assert vals["mem_bufpool_bytes"] > 0
    assert vals["mem_bufpool_entries"] > 0
    assert s._cache_scope.snapshot()["bufferpool"]["hits"] > 0


def test_one_shot_scan_shares_pool_across_sessions(tmp_path):
    """One-shot (non-tiled) scans: a session's private store-scan LRU
    absorbs its own repeats, but the pool is scope-wide — a THIRD
    session's first scan of the hot table is served from HBM with zero
    host decodes (store_scan_cache_* counters track the LRU side)."""
    root = str(tmp_path / "store")
    _mk_store(root, n=40_000)
    q = "select sum(v) as sv from fact"
    # one Config OBJECT for all three sessions (the server-backend
    # shape: per-connection backends share the serving session's
    # config) — distinct configs are a different epoch by design
    cfg = get_config().with_overrides(**{"storage.root": root})
    a, b, c = cb.Session(cfg), cb.Session(cfg), cb.Session(cfg)
    a.sql(q)
    assert a.last_tiled_report is None  # one-shot path
    assert a.stmt_log.counter("host_decodes") > 0
    assert a.stmt_log.counter("store_scan_cache_misses") > 0
    a.sql(q)  # private LRU hit — no pool traffic needed
    assert a.stmt_log.counter("store_scan_cache_hits") > 0
    b.sql(q)  # freq reaches admit_min_scans: admits
    assert b.stmt_log.counter("bufpool_admits") > 0
    got = c.sql(q).to_pandas()
    assert c.stmt_log.counter("bufpool_hits") > 0
    assert c.stmt_log.counter("host_decodes") == 0
    assert got.equals(a.sql(q).to_pandas())


# ------------------------------------------------------------ invalidation


def test_version_bump_invalidates_by_key(tmp_path):
    """A DML commit publishes a new store version: the old entries'
    keys can never be asked for again (no stale hit), and the post-DML
    answer includes the new row."""
    root = str(tmp_path / "store")
    _mk_store(root, n=40_000)
    s = _open(root, budget=1 << 20)
    for _ in range(3):
        s.sql(AGG_Q)
    pool = BUF.pool_for(s)
    assert pool.snapshot()["hits"] > 0
    v0 = s.catalog.store.effective_version("fact")
    s.sql("insert into fact values (1, 0, 1000)")
    assert s.catalog.store.effective_version("fact") != v0
    h0 = pool.snapshot()["hits"]
    got = s.sql(AGG_Q).to_pandas()
    # every lookup missed: stale-version entries never matched
    assert pool.snapshot()["hits"] == h0
    fresh = _open(root, budget=1 << 20)
    assert got.equals(fresh.sql(AGG_Q).to_pandas())
    assert int(got["sv"].sum()) == int(
        _open(root).sql("select sum(v) as sv from fact")
        .to_pandas()["sv"][0])


def test_config_epoch_swap_never_serves_foreign_entries(tmp_path):
    """Two sessions over the same store root share one pool (one cache
    scope), but their keys differ in exactly the config-uid component —
    programs bake config knobs, so entries built under another Config
    object must never serve."""
    from cloudberry_tpu.sched import sharedcache

    root = str(tmp_path / "store")
    _mk_store(root, n=40_000)
    a = _open(root, budget=1 << 20)
    for _ in range(3):
        exp = a.sql(AGG_Q).to_pandas()
    b = _open(root, budget=1 << 20)
    pool = BUF.pool_for(a)
    assert BUF.pool_for(b) is pool  # shared scope, shared pool
    ka = BUF.dist_tile_key(a, "fact", (("g", "v"), ()), 1, 1024, 0)
    kb = BUF.dist_tile_key(b, "fact", (("g", "v"), ()), 1, 1024, 0)
    assert ka[:-1] == kb[:-1] and ka[-1] != kb[-1], \
        "config uid must be the (only) differing key component"
    assert sharedcache.config_uid(a.config) != \
        sharedcache.config_uid(b.config)
    h0 = pool.snapshot()["hits"]
    got = b.sql(AGG_Q).to_pandas()
    assert pool.snapshot()["hits"] == h0, \
        "a foreign config's entry served (stale config-epoch hit)"
    assert exp.equals(got)


@pytest.mark.slow  # two online rebalances on one core: ~5s of wall
def test_topology_flip_forced_regression_never_serves_stale(
        tmp_path, monkeypatch):
    """The PR-13 stale-nseg pattern aimed at the pool: collapse
    config_uid so after a 4->6->4 round trip every key component
    ALIASES except the topology token — remove the token and the
    epoch-1 entries would serve at epoch 3. With it, the keys differ in
    exactly that slot; and the cutover additionally drops the resident
    bytes eagerly (the heat sketch too: the old placement's frequency
    is not evidence about the new one)."""
    from cloudberry_tpu.sched import sharedcache

    root = str(tmp_path / "store")
    _mk_store(root, n=160_000, nseg=4)
    s = _open(root, nseg=4, budget=1 << 20)
    monkeypatch.setattr(sharedcache, "config_uid", lambda cfg: 0)
    first = None
    for _ in range(3):
        first = s.sql(AGG_Q).to_pandas()
    rep = s.last_tiled_report
    assert rep["tiled"] and rep["n_tiles"] > 1
    pool = BUF.pool_for(s)
    assert pool.snapshot()["entries"] > 0
    cols = (("g", "v"), ())

    def snap_keys():
        # collapse every ALIASABLE component (config uid, store/table
        # version — both genuinely can alias: a pure failover shrink
        # moves nothing) so the topology token is the only live
        # distinguisher, exactly the stale-nseg construction
        with monkeypatch.context() as m:
            m.setattr(sharedcache, "config_uid", lambda cfg: 0)
            m.setattr(sharedcache, "table_key",
                      lambda sess, name: (name, "sv", 7))
            m.setattr(s.catalog.store, "effective_version",
                      lambda name: 7)
            return (BUF.dist_tile_key(s, "fact", cols, 4, 1024, 0),
                    BUF.partition_key(s, "fact", {"file": "f0"},
                                      ("g", "v")))

    k1, p1 = snap_keys()
    s._topology.online_resize(6)
    s._topology.online_resize(4)  # same nseg as epoch 1 again
    # eager drop at cutover: stale keys could never serve, but the HBM
    # bytes are placement-era garbage — freed immediately
    snap = pool.snapshot()
    assert snap["entries"] == 0 and snap["bytes"] == 0
    k3, p3 = snap_keys()
    for old, new in ((k1, k3), (p1, p3)):
        assert old != new
        assert old[:-2] == new[:-2] and old[-1] == new[-1], \
            "keys must alias everywhere except the topology token"
        assert old[-2] != new[-2]
    # end-to-end: the re-warmed pool only ever holds current-token
    # entries and the answer stays bit-identical
    h0 = pool.snapshot()["hits"]
    for _ in range(3):
        assert first.equals(s.sql(AGG_Q).to_pandas())
    assert pool.snapshot()["hits"] > h0  # re-admitted AND re-served
    tok = sharedcache.topology_token(s)
    with pool._lock:
        keys = list(pool._entries)
    assert keys and all(k[-2] == tok for k in keys)


@pytest.mark.slow
def test_degraded_shrink_resume_stale_epoch_never_serves():
    """8->7 mid-statement: a tiled distributed statement killed by
    device loss resumes AFTER a shrink cutover landed during its
    backoff. The warm epoch-8 pool entries are dropped at the flip and
    the resumed attempt re-keys at the new token — bit-identical, with
    no stale-epoch entry resident afterwards."""
    from cloudberry_tpu.sched import sharedcache

    s = cb.Session(get_config().with_overrides(**{
        "n_segments": 8, "resource.query_mem_bytes": 512 << 10,
        "recovery.checkpoint_every": 2, "health.retries": 2,
        "health.backoff_s": 1.0, "health.backoff_max_s": 1.0}))
    s.sql("create table big (k bigint, g bigint, v bigint) "
          "distributed by (k)")
    n = 400_000
    rng = np.random.default_rng(7)
    s.catalog.table("big").set_data(
        {"k": np.arange(n, dtype=np.int64) % 997,
         "g": rng.integers(0, 9, n).astype(np.int64),
         "v": rng.integers(0, 1000, n).astype(np.int64)}, {})
    q = "select g, sum(v) as sv from big group by g order by g"
    expected = s.sql(q).to_pandas()
    assert s.last_tiled_report is not None
    assert s.last_tiled_report["n_tiles"] >= 3
    s.sql(q)  # second scan: partitions admit — the pool is warm
    pool = BUF.pool_for(s)
    warm = pool.snapshot()["entries"] if pool is not None else 0
    tok_before = sharedcache.topology_token(s)
    FI.inject_fault("tile_device_lost", "error", start_hit=3, end_hit=3)
    done = {}

    def run():
        done["df"] = s.sql(q).to_pandas()

    th = threading.Thread(target=run)
    th.start()
    deadline = time.monotonic() + 10
    rows = []
    while time.monotonic() < deadline:
        rows = [r for r in s.stmt_log.activity()
                if r.get("state") == "recovering"]
        if rows:
            break
        time.sleep(0.01)
    assert rows, "statement never entered recovery"
    s._topology.begin(7)
    s._topology.rebalance()
    s._topology.cutover(wait_s=0.0)  # shrink under the in-flight stmt
    th.join(timeout=60)
    assert "df" in done and expected.equals(done["df"])
    assert s.config.n_segments == 7
    assert s.stmt_log.counter("tile_resumes") >= 1
    tok = sharedcache.topology_token(s)
    assert tok != tok_before
    if pool is not None and warm:
        with pool._lock:
            keys = list(pool._entries)
        assert all(k[-2] == tok for k in keys), \
            "an epoch-8 entry survived the shrink cutover"


# --------------------------------------------- pool on/off bit-identity


# per-mode shapes mirroring test_scan_pipeline's single/dist8 matrix:
# the dist8 (nseg, tile_rows) tile covers 8x the single-node rows, so it
# streams multiple tiles at a tighter budget; the dist8 window needs
# every partition to fit one spill chunk, so it runs finer groups over
# more rows at the budget whose chunk capacity holds them
# the dist8 rows are slow-tier: they need 240k rows to stream >1 tile
# per segment, and on a single-core host the four of them cost ~20s of
# the tier-1 wall budget for coverage the single-node rows already pin
_slow = pytest.mark.slow
_MATRIX = [(AGG_Q, None, 1, 1 << 20, 120_000, 9),
           (TOPN_Q, "topn", 1, 1 << 20, 120_000, 9),
           (SORT_Q, "sort", 1, 1 << 20, 120_000, 9),
           (WIN_Q, "window", 1, 2 << 20, 60_000, 9),
           pytest.param(AGG_Q, None, 8, 1 << 20, 240_000, 9,
                        marks=_slow),
           pytest.param(TOPN_Q, "topn", 8, 1 << 20, 240_000, 9,
                        marks=_slow),
           pytest.param(SORT_Q, "sort", 8, 1 << 20, 240_000, 9,
                        marks=_slow),
           pytest.param(WIN_Q, "window", 8, 4 << 20, 240_000, 300,
                        marks=_slow)]


@pytest.mark.parametrize("q,mode,nseg,budget,n,n_groups", _MATRIX)
def test_pool_on_off_bit_identical(tmp_path, q, mode, nseg, budget, n,
                                   n_groups):
    """Every tiled mode, single-node and dist8: pool-on runs covering
    miss+admit then serve-from-HBM all equal the pool-off answer
    (admit_min_scans=1 so the second run already serves)."""
    root = str(tmp_path / "store")
    _mk_store(root, nseg=nseg, n=n, n_groups=n_groups)
    off = _open(root, nseg=nseg, budget=budget, pool=False)
    expected = off.sql(q).to_pandas()
    rep = off.last_tiled_report
    assert rep["tiled"] and rep["n_tiles"] > 1
    if mode is not None:
        assert rep["mode"] == mode
    s = _open(root, nseg=nseg, budget=budget,
              **{"bufferpool.admit_min_scans": 1})
    for i in range(2):
        h0 = s.stmt_log.counter("bufpool_hits")
        assert expected.equals(s.sql(q).to_pandas())
        if i == 1:
            assert s.stmt_log.counter("bufpool_hits") > h0, \
                "second scan must serve from the pool"


def test_pool_on_device_loss_resume_bit_identical(tmp_path):
    """Mid-statement device loss on a WARM pool: the resumed attempt
    (which mixes resident chunks, skipped partitions, and fresh reads)
    is bit-identical to the pool-off answer."""
    root = str(tmp_path / "store")
    _mk_store(root)
    off = _open(root, budget=1 << 20, pool=False)
    expected = off.sql(AGG_Q).to_pandas()
    s = _open(root, budget=1 << 20, **{
        "recovery.checkpoint_every": 2, "health.retries": 2,
        "health.backoff_s": 0.01})
    s.sql(AGG_Q), s.sql(AGG_Q)  # warm: partitions resident
    assert s.last_tiled_report["n_tiles"] >= 3
    FI.inject_fault("tile_device_lost", "error", start_hit=3, end_hit=3)
    assert expected.equals(s.sql(AGG_Q).to_pandas())
    rep = s.last_tiled_report
    assert rep["resumed_from_tile"] >= 1
    assert rep["pipeline"]["parts_resident"] > 0


# ------------------------------------------------------ concurrency/locks


@pytest.mark.slow  # the witness instruments every lock: ~4s fixed cost
def test_four_thread_stress_clean_under_witness():
    """4 threads hammer lookup/offer/sweep over overlapping keys with a
    live StatementLog: the runtime lock-order witness records zero
    violations (pool lock is a leaf; counter bumps and fault seams run
    outside it), the byte budget holds, and the accounting stays
    internally consistent."""
    from cloudberry_tpu.exec.instrument import StatementLog
    from cloudberry_tpu.lint import witness

    pool = BUF.BufferPool(max_bytes=16 * _NB, admit_min_scans=2)
    log = StatementLog()
    errs = []

    def worker(tid):
        try:
            for i in range(100):
                k = ("part", "t", 1, f"p{(tid * 7 + i) % 24}",
                     ("v",), 1, 0)
                if pool.lookup(k, log) is None:
                    pool.offer(k, _ent(seed=tid), table="t", log=log,
                               device=False)
                if i % 40 == 0:
                    pool.sweep(lambda kk: kk[3] == f"p{tid}")
        except Exception as e:  # noqa: BLE001 — assertion target
            errs.append(e)

    witness.install()
    try:
        witness.reset_violations()
        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert witness.violations() == []
    finally:
        witness.uninstall()
        witness.reset_violations()
    assert not errs
    snap = pool.snapshot()
    assert snap["bytes"] <= snap["max_bytes"]
    with pool._lock:
        assert pool.bytes == sum(nb for _, nb, _
                                 in pool._entries.values())
        assert len(pool._entries) == snap["entries"]


def test_serve_bench_hotcold_smoke():
    """serve_bench --mix hotcold CPU smoke (ISSUE 16): a hot store
    table scanned by the SAME tiled aggregate against a cold table
    under a pool budget that holds only the hot set. The run's CSV row
    carries the pool columns, and the after-window probe pins the
    acceptance claim by counters: the pool-warm hot scan pays ZERO
    host decodes and runs at higher rows/s than the cold scan of the
    same-size container."""
    import tools.serve_bench as SB

    r = SB.run_mode("direct", "hotcold", clients=2, duration_s=1.2,
                    rows=30_000, tick_s=0.002, max_batch=8)
    assert r["requests"] > 0
    assert r["mix"] == "hotcold"
    # the hot set went device-resident during the window: pool hits
    # flowed, while the cold set kept the host decoders busy
    assert r["bufpool_hit_rate"] > 0
    assert r["host_decodes"] > 0
    # the probe's counter-pinned claim: zero host reads/decodes for
    # the hot scan, at least one decode for the cold one — and the
    # pool-served scan is measurably faster on the same row count
    assert r["_hot_host_decodes"] == 0
    assert r["_cold_host_decodes"] > 0
    assert r["_hot_rows_per_s"] > r["_cold_rows_per_s"]
    row = SB.csv_row(r)
    assert len(row.split(",")) == len(SB.CSV_HEADER.split(","))


def test_scan_bench_hot_point_smoke(tmp_path):
    """tools/scan_bench.py hot_point CPU smoke: the second-pass
    buffer-pool ladder record at toy SF — the pool pass serves every
    chunk (hit rate 1.0, zero host decodes), beats no-pool wall, and
    is bit-identical to the admission pass."""
    import tools.scan_bench as sb

    p = sb.hot_point(0.01, root=str(tmp_path / "st"), budget=1 << 20)
    assert p["bufpool_hit_rate"] == 1.0
    assert p["host_decodes_pool_pass"] == 0
    assert p["bufpool_admits"] > 0
    assert p["bit_identical"]
    assert p["rows_per_s_pool"] > 0 and p["rows_per_s_cold"] > 0


# ------------------------------------------------ slow tier: SF10 TPC-H


@pytest.mark.slow
def test_tpch_tiled_dist_sf10_second_pass_hit_rates(tmp_path):
    """Carried evidence debt (ROADMAP round 15): FULL TPC-H — not just
    the scan shape — tiled on the mesh at SF10 in the slow tier, each
    query run twice in ONE session with first-scan admission
    (admit_min_scans=1) so the SECOND pass is served by the buffer
    pool, recording per-query second-pass hit rates as one JSON line
    (TPCH_POOL_HIT_RATES ...). Env knobs for smaller rehearsals and
    real hardware: CBTPU_TPCH_SF (default 10), CBTPU_TPCH_BUDGET
    (tiled admission budget, default 64MB), CBTPU_POOL_BYTES (pool
    budget, default 4GB — size to the HBM actually present). Every
    completed query must be bit-identical across passes; a query the
    tiled path cannot express at this budget is recorded as refused,
    never silently skipped."""
    import json
    import os

    from tools.tpch_queries import QUERIES
    from tools.tpchgen import stream_load_tpch

    sf = float(os.environ.get("CBTPU_TPCH_SF", "10"))
    budget = int(os.environ.get("CBTPU_TPCH_BUDGET", str(64 << 20)))
    pool_bytes = int(os.environ.get("CBTPU_POOL_BYTES", str(4 << 30)))
    root = str(tmp_path / "tpch")
    loader = _open(root, nseg=8)
    stream_load_tpch(loader, sf=sf, seed=1)
    s = _open(root, nseg=8, budget=budget,
              **{"bufferpool.max_bytes": pool_bytes,
                 "bufferpool.admit_min_scans": 1})
    log = s.stmt_log
    record: dict = {}
    for qn in sorted(QUERIES):
        try:
            first = s.sql(QUERIES[qn]).to_pandas()
        except Exception as e:  # noqa: BLE001 — recorded, not hidden
            record[qn] = {"outcome":
                          f"refused: {type(e).__name__}: {e}"[:200]}
            continue
        before = {c: log.counter(c) for c in
                  ("bufpool_hits", "bufpool_misses", "host_decodes")}
        try:
            second = s.sql(QUERIES[qn]).to_pandas()
        except Exception as e:  # noqa: BLE001 — rung growth can push a
            # replay past a tight budget; record it, never hide it
            record[qn] = {"outcome":
                          f"refused_2nd: {type(e).__name__}: {e}"[:200]}
            continue
        hits = log.counter("bufpool_hits") - before["bufpool_hits"]
        miss = log.counter("bufpool_misses") - before["bufpool_misses"]
        rep = s.last_tiled_report
        record[qn] = {
            "outcome": "ok",
            "tiled": bool(rep and rep.get("tiled")),
            "bufpool_hit_rate": round(hits / (hits + miss), 4)
            if hits + miss else None,
            "host_decodes_2nd": log.counter("host_decodes")
            - before["host_decodes"],
        }
        assert list(first.columns) == list(second.columns), qn
        for col in first.columns:
            a = first[col].to_numpy()
            b = second[col].to_numpy()
            assert a.shape == b.shape, f"{qn}.{col}"
            if a.dtype.kind == "f":
                same = (a == b) | (np.isnan(a) & np.isnan(b))
            else:
                same = a == b
            assert np.all(same), f"{qn}.{col} second pass diverged"
    print("\nTPCH_POOL_HIT_RATES " + json.dumps(record, sort_keys=True))
    ok = [q for q, r in record.items() if r["outcome"] == "ok"]
    assert ok, f"no TPC-H query completed: {record}"
    served = [q for q, r in record.items()
              if (r.get("bufpool_hit_rate") or 0) > 0]
    assert served, f"no second pass saw pool traffic: {record}"
    if sf >= 1:
        # at real scale the scan-heavy core MUST run tiled with
        # second-pass pool traffic (a rehearsal SF may fit in memory)
        for qn in ("q1", "q6"):
            r = record.get(qn, {})
            assert r.get("outcome") == "ok", f"{qn}: {r}"
            assert r.get("tiled"), f"{qn} did not tile: {r}"
            assert r.get("bufpool_hit_rate") is not None, f"{qn}: {r}"
