"""Shared cache tier (sched/sharedcache.py): cross-session zero-recompile
reuse over a durable store, version/config-epoch invalidation, and
thread-stress on the shared LRUs (ISSUE-7 satellite)."""

import threading

import numpy as np
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import Config


@pytest.fixture(autouse=True, scope="module")
def _lock_witness():
    # runtime lock-order witness (lint/witness.py): the shared-LRU
    # stress below runs its lock traffic under declared-order checking
    from cloudberry_tpu.lint import witness

    with witness.watching():
        yield


def _store_cfg(tmp_path):
    return Config().with_overrides(
        **{"storage.root": str(tmp_path / "store")})


def _seed(cfg, rows=64):
    s = cb.Session(cfg)
    s.sql("create table d (x bigint, y bigint) distributed by (x)")
    s.sql("insert into d values " +
          ",".join(f"({i}, {i * 3})" for i in range(rows)))
    s.sql("create table dim (k bigint, name bigint) distributed by (k)")
    s.sql("insert into dim values " +
          ",".join(f"({i}, {i + 100})" for i in range(16)))
    return s


def test_cross_session_zero_recompile(tmp_path):
    """ISSUE-7 acceptance pin: tenant B's identical-skeleton statement
    over the same store compiles NOTHING — it re-binds tenant A's
    compiled generic plan (StatementLog compile counter)."""
    cfg = _store_cfg(tmp_path)
    _seed(cfg)

    a = cb.Session(cfg)  # tenant A backend (cold register, like a server)
    a.sql("select x, y from d where x = 1")
    assert a.stmt_log.counter("compiles") >= 1

    b = cb.Session(cfg)  # tenant B backend
    c0 = b.stmt_log.counter("compiles")
    out = b.sql("select x, y from d where x = 7").to_pandas()
    assert out.values.tolist() == [[7, 21]]
    assert b.stmt_log.counter("compiles") - c0 == 0
    assert b.stmt_log.counter("generic_hits") >= 1
    # the scope really is shared, and it is the store kind
    assert a._cache_scope is b._cache_scope
    assert a._cache_scope.kind == "store"


def test_version_bump_invalidates_shared_entries(tmp_path):
    """A write through one backend bumps the store version; the other
    backend's next same-skeleton statement must NOT reuse the stale
    entry (fresh results prove it; the generic cache key carries the
    store version)."""
    cfg = _store_cfg(tmp_path)
    _seed(cfg)
    a = cb.Session(cfg)
    b = cb.Session(cfg)
    assert b.sql("select y from d where x = 3").to_pandas()\
        .values.tolist() == [[9]]
    a.sql("update d set y = 999 where x = 3")
    out = b.sql("select y from d where x = 3").to_pandas()
    assert out.values.tolist() == [[999]]


def test_config_epoch_invalidates(tmp_path):
    """The config OBJECT identity is the config epoch: a session under a
    different (even equal-valued) Config object never reuses entries
    built under another epoch."""
    cfg = _store_cfg(tmp_path)
    _seed(cfg)
    a = cb.Session(cfg)
    a.sql("select x, y from d where x = 1")
    # a new frozen tree with an execution-irrelevant knob changed: same
    # plans, DIFFERENT epoch — entries must not bleed across
    b = cb.Session(cfg.with_overrides(**{"health.retries": 2}))
    c0 = b.stmt_log.counter("compiles")
    b.sql("select x, y from d where x = 2")
    assert b.stmt_log.counter("compiles") - c0 >= 1  # no epoch bleed


def test_private_scope_for_storeless_sessions():
    """Storeless sessions keep private scopes (their tables have no
    cross-session identity): no sharing, the pre-tier behavior."""
    a = cb.Session(Config())
    b = cb.Session(Config())
    assert a._cache_scope is not b._cache_scope
    assert a._cache_scope.kind == "session"


def test_join_index_shared_across_backends(tmp_path):
    """The join-index cache rides the same tier: backend B's first join
    reuses backend A's sorted-build scaffolding (hits with zero
    builds)."""
    cfg = _store_cfg(tmp_path)
    # one key a million off: dim.k spans more values than any array of
    # the join holds, so it keeps the sorted search whose index the tier
    # shares (dense keys read a direct-address table: no index)
    _seed(cfg).sql("insert into dim values (1000000, 0)")
    q = ("select dim.name, count(*) as n, sum(d.y) as sy from d, dim "
         "where d.x = dim.k group by dim.name order by dim.name")

    def warm(s):
        # cold store tables scan via pruned store reads (per-statement
        # row sets — never index-eligible); a loaded table scans whole
        for name in ("d", "dim"):
            s.catalog.table(name).ensure_loaded()

    a = cb.Session(cfg)
    warm(a)
    ra = a.sql(q).to_pandas()
    assert a.stmt_log.counter("join_index_builds") >= 1
    b = cb.Session(cfg)
    warm(b)
    rb = b.sql(q).to_pandas()
    assert rb.values.tolist() == ra.values.tolist()
    assert b.stmt_log.counter("join_index_hits") >= 1
    assert b.stmt_log.counter("join_index_builds") == 0


def test_in_transaction_entries_stay_private(tmp_path):
    """Mid-transaction table state has no store identity: entries built
    inside a transaction key on the table OBJECT (uid), so another
    backend can never hit them — and results stay correct."""
    cfg = _store_cfg(tmp_path)
    _seed(cfg)
    a = cb.Session(cfg)
    b = cb.Session(cfg)
    a.txn("begin")
    a.sql("update d set y = 5555 where x = 5")
    assert a.sql("select y from d where x = 5").to_pandas()\
        .values.tolist() == [[5555]]
    # b sees the committed (old) value despite a's in-txn entries
    assert b.sql("select y from d where x = 5").to_pandas()\
        .values.tolist() == [[15]]
    a.txn("rollback")
    assert a.sql("select y from d where x = 5").to_pandas()\
        .values.tolist() == [[15]]


def test_shared_lru_thread_stress(tmp_path):
    """Thread-stress the shared scope: several backends hammer the same
    skeletons (generic cache) and join indexes concurrently while a
    writer bumps versions — no exceptions, correct results throughout."""
    cfg = _store_cfg(tmp_path)
    _seed(cfg, rows=128)
    sessions = [cb.Session(cfg) for _ in range(3)]
    errors = []
    stop = threading.Event()

    def reader(s, seed):
        rng = np.random.default_rng(seed)
        try:
            while not stop.is_set():
                k = int(rng.integers(0, 100))
                out = s.sql(f"select x, y from d where x = {k}")
                rows = out.to_pandas().values.tolist()
                if rows and rows[0][0] != k:
                    errors.append(f"wrong row for {k}: {rows}")
        except Exception as e:  # pragma: no cover
            errors.append(f"{type(e).__name__}: {e}")

    def writer(s):
        try:
            i = 0
            while not stop.is_set():
                s.sql(f"insert into dim values ({1000 + i}, {i})")
                i += 1
        except Exception as e:  # pragma: no cover
            errors.append(f"writer {type(e).__name__}: {e}")

    threads = [threading.Thread(target=reader, args=(s, i))
               for i, s in enumerate(sessions)]
    threads.append(threading.Thread(target=writer,
                                    args=(cb.Session(cfg),)))
    for t in threads:
        t.start()
    import time

    time.sleep(1.5)
    stop.set()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors[:3]


def test_meta_sched_reports_shared_cache(tmp_path):
    from cloudberry_tpu.serve.meta import describe

    cfg = _store_cfg(tmp_path)
    s = _seed(cfg)
    s.sql("select x from d where x = 1")
    info = describe(s, "sched")["shared_cache"]
    assert info["kind"] in ("store", "session")
    assert "generic_skeletons" in info
