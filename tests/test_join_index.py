"""Join-index cache (exec/joinindex.py): sorted-build reuse across
statements — cache hit on repeat, invalidation on any write (table
version keying), zero recompiles on the repeated-statement path, the
argsort genuinely gone from the traced program, and bit-identical
results vs the cache-disabled engine. Plus the duplicate-build-key
error surfaced as its own typed, counted error."""

import jax
import numpy as np
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import Config
from cloudberry_tpu.exec import executor as X
from cloudberry_tpu.exec.executor import DuplicateBuildKeyError
from cloudberry_tpu.plan import nodes as N

Q = ("select grp, count(*) as n, sum(p) as sp from fact, dim "
     "where grp = d group by grp order by grp")
# dim's keys lie this far apart: a span no array of the join comes near,
# so it keeps the sorted search the index serves (a dense key finds its
# row in a direct-address table instead: PJoin.direct_lookup)
STRIDE = 1000


def _mk(nseg=1, **ov):
    s = cb.Session(Config(n_segments=nseg).with_overrides(**ov))
    s.sql("create table fact (k bigint, grp bigint, v bigint) "
          "distributed by (k)")
    s.sql("create table dim (d bigint, p bigint) distributed by (d)")
    rows = ",".join(f"({i}, {i % 500 * STRIDE}, {i % 7})"
                    for i in range(2000))
    s.sql(f"insert into fact values {rows}")
    rows = ",".join(f"({i * STRIDE}, {i * 3})" for i in range(500))
    s.sql(f"insert into dim values {rows}")
    return s


def test_cache_hit_and_no_recompile_single():
    s = _mk(1)
    a = s.sql(Q).to_pandas()
    assert s.stmt_log.counter("join_index_builds") >= 1
    c0 = s.stmt_log.counter("compiles")
    h0 = s.stmt_log.counter("join_index_hits")
    b = s.sql(Q).to_pandas()
    assert a.values.tolist() == b.values.tolist()
    assert s.stmt_log.counter("join_index_hits") > h0
    assert s.stmt_log.counter("compiles") == c0, "repeat recompiled"


def test_results_match_cache_disabled():
    on = _mk(1)
    off = _mk(1, **{"join_filter.index_cache": 0})
    assert not any(hasattr(n, "_jix") for n in _plan_nodes(off, Q))
    a = on.sql(Q).to_pandas()
    b = off.sql(Q).to_pandas()
    assert a.values.tolist() == b.values.tolist()


def test_invalidate_on_write():
    s = _mk(1)
    s.sql(Q)
    b0 = s.stmt_log.counter("join_index_builds")
    s.sql(f"insert into dim values ({500 * STRIDE}, 9999)")
    s.sql(f"insert into fact values (99999, {500 * STRIDE}, 1)")
    out = s.sql(Q).to_pandas()
    # the write bumped the table version → fresh index, fresh results
    assert s.stmt_log.counter("join_index_builds") > b0
    assert len(out) == 501
    assert out[out.grp == 500 * STRIDE].sp.tolist() == [9999]


def test_dist_shard_mode_parity():
    """Colocated (redistributed-probe) build: per-segment shard indexes
    ride the program split on the segment axis."""
    ov = {"planner.broadcast_threshold": 0}  # force redist, keep shards
    on = _mk(8, **ov)
    off = _mk(8, **{**ov, "join_filter.index_cache": 0})
    plan = _plan(on, Q)
    assert any(getattr(j, "_jix", None) is not None
               and j._jix.mode == "shard" for j in _walk(plan, N.PJoin))
    a = on.sql(Q).to_pandas()
    b = off.sql(Q).to_pandas()
    assert a.values.tolist() == b.values.tolist()
    assert on.stmt_log.counter("join_index_builds") >= 1
    h0 = on.stmt_log.counter("join_index_hits")
    on.sql(Q)
    assert on.stmt_log.counter("join_index_hits") > h0


def test_dist_gathered_mode_parity():
    """Broadcast build (the common small-dim shape): the cached index
    mirrors the gathered buffer's shard-major row order."""
    # greedy rules broadcast the small build; the memo might prefer a
    # probe redistribute, which would be the 'shard' shape instead
    ov = {"planner.enable_memo": False}
    on = _mk(8, **ov)
    off = _mk(8, **{**ov, "join_filter.index_cache": 0})
    plan = _plan(on, Q)
    joins = [n for n in _walk(plan, N.PJoin)]
    assert any(getattr(j, "_jix", None) is not None
               and j._jix.mode == "gathered" for j in joins), \
        [getattr(getattr(j, "_jix", None), "mode", None) for j in joins]
    a = on.sql(Q).to_pandas()
    b = off.sql(Q).to_pandas()
    assert a.values.tolist() == b.values.tolist()


def test_expansion_join_uses_index():
    """Non-unique (many-to-many) builds ride the cached index too."""
    on = _mk(1)
    off = _mk(1, **{"join_filter.index_cache": 0})
    q = ("select f1.grp, count(*) as n from fact f1, fact f2 "
         "where f1.grp = f2.grp group by f1.grp order by f1.grp")
    a = on.sql(q).to_pandas()
    b = off.sql(q).to_pandas()
    assert a.values.tolist() == b.values.tolist()


def test_argsort_eliminated_from_program():
    """The traced program with the cached index holds strictly fewer
    sort ops than without — the argsort is gone, not just cached."""
    on = _mk(1)
    off = _mk(1, **{"join_filter.index_cache": 0})

    def sort_count(s):
        from cloudberry_tpu.plan.planner import plan_statement
        from cloudberry_tpu.sql.parser import parse_sql

        plan = plan_statement(parse_sql(Q), s, {}).plan
        exe = X.compile_plan(plan, s)
        inputs = X.prepare_inputs(exe, s)
        jaxpr = jax.make_jaxpr(exe.raw_fn)(inputs)
        return str(jaxpr).count("sort[")

    assert sort_count(on) < sort_count(off)


def _plan(s, sql):
    from cloudberry_tpu.plan.binder import Binder
    from cloudberry_tpu.plan.planner import _optimize
    from cloudberry_tpu.sql.parser import parse_sql

    return _optimize(Binder(s.catalog, s.config).bind_query(
        parse_sql(sql)), s)


def _walk(plan, kind):
    out = []

    def rec(n):
        if isinstance(n, kind):
            out.append(n)
        for c in n.children():
            rec(c)

    rec(plan)
    return out


def _plan_nodes(s, sql):
    return _walk(_plan(s, sql), N.PJoin)


# ------------------------------------------------- duplicate-build-keys


def _dup_dim_key(s):
    """Duplicate one dim key IN PLACE (same shape): d becomes
    [0, 0, 2, 3, …] — two build rows for key 0."""
    t = s.catalog.table("dim")
    data = {c: np.asarray(v).copy() for c, v in t.data.items()}
    data["d"][1] = data["d"][0]
    t.set_data(data, t.dicts)


def test_duplicate_build_key_error_end_to_end():
    """A unique_build join over data that actually holds duplicate keys
    must abort with the typed error, never return wrong rows — the plan
    was built while dim.d WAS unique (the stale-inference scenario), the
    data changed underneath, and the runtime check is the last line."""
    s = _mk(1)
    from cloudberry_tpu.plan.planner import plan_statement
    from cloudberry_tpu.sql.parser import parse_sql

    q = ("select grp, p from fact, dim where grp = d order by grp, p "
         "limit 5")
    plan = plan_statement(parse_sql(q), s, {}).plan
    joins = [n for n in X.all_nodes(plan) if isinstance(n, N.PJoin)]
    assert joins and all(j.unique_build for j in joins)
    _dup_dim_key(s)
    from cloudberry_tpu.exec.joinindex import strip_join_index

    strip_join_index(plan)  # exercise the in-program dup check
    with pytest.raises(DuplicateBuildKeyError):
        X.execute(plan, s)


def test_duplicate_build_key_error_through_cached_index(monkeypatch):
    """Same end-to-end shape through session.sql with the JOIN-INDEX fed
    (dup_check runs on the cached sorted keys too) and the uniqueness
    inference pinned stale — the typed error surfaces and is counted."""
    s = _mk(1)
    s.sql(Q)
    _dup_dim_key(s)
    t = s.catalog.table("dim")
    monkeypatch.setattr(type(t), "is_unique_cols",
                        lambda self, cols: True)  # stale PK inference
    with pytest.raises(DuplicateBuildKeyError):
        s.sql("select grp, p from fact, dim where grp = d "
              "order by grp, p limit 5")
    assert s.stmt_log.counter("duplicate_build_key_errors") == 1
