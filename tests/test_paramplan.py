"""Parameterized generic plans (sched/paramplan.py, the plan_cache.c
analog): skeleton normalization, zero-recompile rebinding with
bit-identical results, non-generic opt-outs, and the statement-cache
keying audit (user params + config epoch)."""

import threading

import numpy as np
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import Config
from cloudberry_tpu.sched import paramplan


def _pts_session(nseg=1, rows=100_000, generic=True):
    s = cb.Session(Config(n_segments=nseg).with_overrides(
        **{"sched.generic_plans": generic}))
    s.sql("create table pts (k bigint, v bigint, w double) "
          "distributed by (k)")
    s.catalog.table("pts").set_data({
        "k": np.arange(rows, dtype=np.int64),
        "v": (np.arange(rows, dtype=np.int64) * 7) % 1000,
        "w": np.arange(rows, dtype=np.float64) * 0.5}, {})
    return s


# ------------------------------------------------------------- skeletons


def test_normalize_same_shape_collides():
    a = paramplan.normalize("select k from t where k = 42")
    b = paramplan.normalize("select k from t where k = 99")
    assert a is not None and a[0] == b[0]
    assert a[1] == ("42",) and b[1] == ("99",)


def test_normalize_structural_literals_stay():
    # LIMIT/OFFSET and INTERVAL quantities shape the plan — never params
    a = paramplan.normalize("select k from t where k > 1 limit 5")
    b = paramplan.normalize("select k from t where k > 1 limit 7")
    assert a[0] != b[0]
    assert a[1] == ("1",)
    c = paramplan.normalize(
        "select k from t where d < date '1994-01-01' + interval '1' year")
    assert c[1] == ("1994-01-01",)  # the date is a param, the '1' is not


def test_normalize_rejects_non_queries():
    assert paramplan.normalize("insert into t values (1)") is None
    assert paramplan.normalize("create table t (a int)") is None


# --------------------------------------------- zero-recompile acceptance


@pytest.mark.parametrize("nseg", [1, 8])
def test_point_lookup_rebinds_without_recompiling(nseg):
    """ISSUE-3 acceptance: a repeated point lookup with DIFFERENT literals
    triggers zero recompiles after the first execution (compile counter in
    StatementLog) and returns bit-identical results vs the
    unparameterized path."""
    s = _pts_session(nseg=nseg)
    off = _pts_session(nseg=nseg, generic=False)
    q = "select k, v, w from pts where k = {}"
    s.sql(q.format(4242))  # warmup: builds the generic plan
    c0 = s.stmt_log.counter("compiles")
    for key in (7, 999, 31337, 77777):
        got = s.sql(q.format(key))
        want = off.sql(q.format(key))
        gsel, wsel = np.asarray(got.sel), np.asarray(want.sel)
        for name in got.columns:
            np.testing.assert_array_equal(
                np.asarray(got.columns[name])[gsel],
                np.asarray(want.columns[name])[wsel], err_msg=name)
    assert s.stmt_log.counter("compiles") - c0 == 0
    # per-statement observability: the history rows carry compiles=0
    rec = s.stmt_log.recent(3)
    assert all(e["compiles"] == 0 for e in rec)


@pytest.mark.parametrize("nseg", [1, 8])
def test_parameterized_q6_shape_zero_recompiles(nseg):
    s = cb.Session(Config(n_segments=nseg))
    off = cb.Session(Config(n_segments=nseg).with_overrides(
        **{"sched.generic_plans": False}))
    rng = np.random.default_rng(5)
    m = 40_000
    data = {"qty": rng.integers(1, 5000, m).astype(np.int64),
            "price": rng.integers(100, 10000, m).astype(np.int64),
            "disc": rng.integers(0, 11, m).astype(np.int64),
            "sd": rng.integers(8000, 12000, m).astype(np.int32)}
    for sess in (s, off):
        sess.sql("create table li (qty decimal(2), price decimal(2), "
                 "disc decimal(2), sd date)")
        sess.catalog.table("li").set_data(dict(data), {})
    q = ("select sum(price * disc) as rev from li where sd >= "
         "date '1994-01-01' and disc between 0.0{lo} and 0.0{hi} "
         "and qty < {q}.0")
    s.sql(q.format(lo=5, hi=7, q=24))
    c0 = s.stmt_log.counter("compiles")
    for lo, hi, qty in ((3, 5, 20), (1, 9, 48), (6, 8, 10)):
        got = s.sql(q.format(lo=lo, hi=hi, q=qty)).to_pandas()
        want = off.sql(q.format(lo=lo, hi=hi, q=qty)).to_pandas()
        # DECIMAL sums are exact int64 fixed-point — bit-identical
        assert got.rev[0] == want.rev[0]
    assert s.stmt_log.counter("compiles") - c0 == 0
    assert s.stmt_log.counter("generic_hits") >= 3


def test_date_literal_rebinds():
    s = _pts_session(rows=1000)
    s.sql("create table ev (d date, x bigint)")
    s.catalog.table("ev").set_data({
        "d": np.arange(8000, 9000, dtype=np.int32),
        "x": np.arange(1000, dtype=np.int64)}, {})
    q = "select count(*) as n from ev where d >= date '{}'"
    assert s.sql(q.format("1991-01-01")).to_pandas().n[0] == 1000
    c0 = s.stmt_log.counter("compiles")
    # 8500 days ≈ 1993-04; exact oracle via numpy
    got = s.sql(q.format("1993-04-14")).to_pandas().n[0]
    from cloudberry_tpu.types import date_to_days

    assert got == int((np.arange(8000, 9000)
                       >= date_to_days("1993-04-14")).sum())
    assert s.stmt_log.counter("compiles") == c0


# ------------------------------------------------- non-generic opt-outs


def test_nextval_stays_non_generic():
    s = cb.Session(Config())
    s.sql("create sequence sq")
    a = s.sql("select nextval('sq') as n").to_pandas().n[0]
    b = s.sql("select nextval('sq') as n").to_pandas().n[0]
    assert (a, b) == (1, 2)  # a cached/generic replay would repeat 1
    assert not s._generic_cache  # declared itself non-generic


def test_point_match_count_change_is_a_new_variant():
    """A point lookup whose MATCH COUNT changes folds a different row
    slice shape at plan time — the signature refuses the rebind and a
    separate variant compiles; results stay exact."""
    s = _pts_session(rows=100_000)
    # duplicate key 55 once: k=55 now matches 2 rows
    t = s.catalog.table("pts")
    data = {c: np.concatenate([np.asarray(v), np.asarray(v[55:56])])
            for c, v in t.data.items()}
    t.set_data(data, {})
    q = "select k, v from pts where k = {}"
    assert s.sql(q.format(7)).num_rows() == 1
    got = s.sql(q.format(55))
    assert got.num_rows() == 2  # the 2-row variant, not a stale 1-row one
    assert s.sql(q.format(8)).num_rows() == 1


def test_growth_retry_over_generic_plan_recovers():
    """Expansion overflow on a generic-built (rewritten) plan: the retry
    loop recompiles the plan on whichever path it takes — the kept Param
    values must bake as constants there (no $params input), and the
    post-growth rebind must still work."""
    s = cb.Session(Config())
    rng = np.random.default_rng(13)
    n = 40_000
    s.sql("create table probe (k bigint, x bigint) distributed by (k)")
    s.sql("create table build (k bigint, y bigint) distributed by (k)")
    pk = np.where(rng.random(n) < 0.3, 0,
                  rng.integers(1, 30_000, n)).astype(np.int64)
    s.catalog.table("probe").set_data(
        {"k": pk, "x": np.ones(n, dtype=np.int64)}, {})
    bk = np.concatenate([np.zeros(12, dtype=np.int64),
                         np.arange(1, 2000, dtype=np.int64)])
    s.catalog.table("build").set_data(
        {"k": bk, "y": np.arange(len(bk), dtype=np.int64)}, {})
    q = ("select count(*) as n from probe, build "
         "where probe.k = build.k and probe.x > {}")
    import pandas as pd

    want = pd.DataFrame({"k": pk}).merge(
        pd.DataFrame({"k": bk}), on="k").shape[0]
    assert s.sql(q.format(0)).to_pandas().n[0] == want
    assert s.growth_events > 0  # the overflow actually tripped
    # rebind with a different literal AFTER the growth
    assert s.sql(q.format(-1)).to_pandas().n[0] == want


def test_version_bump_invalidates_generic():
    s = _pts_session(rows=40_000)
    q = "select sum(v) as sv from pts where k < {}"
    r1 = s.sql(q.format(1000)).to_pandas().sv[0]
    s.sql("insert into pts values (1000000, 123, 0.5)")
    r2 = s.sql(q.format(1000)).to_pandas().sv[0]
    assert r1 == r2 == int(((np.arange(1000) * 7) % 1000).sum())
    s.sql("insert into pts values (500, 500, 0.5)")  # inside the range
    r3 = s.sql(q.format(1000)).to_pandas().sv[0]
    assert r3 == r1 + 500


# --------------------------------- statement-cache keying audit (S1)


def test_stmt_cache_keys_on_user_params():
    """sql(query, **params) with the same text but different params must
    not share a cache entry (the prepared-statement parameter-signature
    rule)."""
    s = _pts_session(rows=1024)
    q = "select count(*) as n from pts"
    s.sql(q, tenant=1)
    s.sql(q, tenant=2)
    keys = list(s._stmt_cache)
    assert len([k for k in keys if k.startswith(q)]) == 2
    assert s._stmt_cache_key(q, {"a": 1}) != s._stmt_cache_key(q, {"a": 2})
    assert s._stmt_cache_key(q, {}) == q


def test_stmt_cache_config_epoch_invalidates():
    """A config swap (with_overrides / degraded mesh) must drop cached
    runners — the entry pins the config object identity."""
    s = _pts_session(rows=1024)
    q = "select count(*) as n from pts"
    s.sql(q)
    assert s._cached_statement(q) is not None
    s.config = s.config.with_overrides(
        **{"interconnect.packed_wire": False})
    assert s._cached_statement(q) is None  # stale under the new epoch


def test_generic_cache_cleared_on_mesh_degrade():
    s = _pts_session(nseg=8, rows=50_000)
    s.sql("select k, v from pts where k = 77")
    assert s._generic_cache
    assert s.degrade_mesh(4)
    assert not s._generic_cache


# ------------------------------------------- thread-stress the LRU (S2)


def test_stmt_cache_lru_thread_stress():
    """Concurrent sql() across threads while the 64-entry LRU evicts:
    pins the PR-2 lock-guarded LRU claim (hits mutate the dict)."""
    s = _pts_session(rows=2048)
    errors = []

    def worker(wid):
        try:
            for i in range(40):
                # > _STMT_CACHE_MAX distinct texts across threads, plus
                # a shared hot statement that must keep hitting
                key = (wid * 40 + i) % 90
                n = s.sql("select count(*) as n from pts "
                          f"where k >= {key}").to_pandas().n[0]
                assert n == 2048 - key, (key, n)
                hot = s.sql("select count(*) as n from pts").to_pandas()
                assert hot.n[0] == 2048
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert not errors
    assert len(s._stmt_cache) <= s._STMT_CACHE_MAX


def test_generic_rebind_thread_stress():
    """Concurrent rebinding of one skeleton: the generic cache is shared
    state; results must stay exact and compiles bounded."""
    s = _pts_session(rows=100_000)
    s.sql("select k, v, w from pts where k = 1")  # build once
    c0 = s.stmt_log.counter("compiles")
    errors = []

    def worker(wid):
        try:
            for i in range(25):
                key = wid * 1000 + i
                got = s.sql(f"select k, v, w from pts where k = {key}")
                df = got.to_pandas()
                assert df.k[0] == key and df.v[0] == (key * 7) % 1000
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert not errors
    assert s.stmt_log.counter("compiles") == c0  # zero recompiles
