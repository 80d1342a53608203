"""Plan nodes are named by ordinal in check and stats keys (ISSUE 28):
the retry finds the node an overflow names, a program served from the rung
cache still records its motion statistics on the plan at hand, and the
instrumented lowerers report per-node counts under the same names."""

from __future__ import annotations

import numpy as np
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import Config
from cloudberry_tpu.exec import dist_executor as DX
from cloudberry_tpu.exec import executor as X
from cloudberry_tpu.plan import expr as ex
from cloudberry_tpu.plan import nodes as N
from cloudberry_tpu.types import INT64


def _plan(s, sql):
    from cloudberry_tpu.plan.planner import plan_statement
    from cloudberry_tpu.sql.parser import parse_sql

    return plan_statement(parse_sql(sql), s, {}).plan


def _synthetic():
    scan = N.PScan("t", {"k": "k"}, 64)
    red = N.PMotion(scan, "redistribute",
                    hash_keys=[ex.ColumnRef("k", INT64)])
    red.bucket_cap, red.out_capacity = 64, 256
    gather = N.PMotion(red, "gather")
    return gather, red, scan


def test_ordinals_are_document_order_and_each_node_once():
    gather, red, scan = _synthetic()
    assert X.numbered_nodes(gather) == [gather, red, scan]
    nodes = X.numbered_nodes(gather)
    assert X.keyed_node(nodes, "required bucket (node 1)") is red
    assert X.keyed_node(nodes, "x (node 1: Motion redistribute); y") is red
    assert X.keyed_node(nodes, "x (node 3)") is None
    assert X.keyed_node(nodes, "no reference here") is None


@pytest.mark.parametrize("message,grows", [
    ("redistribute overflow: ... (node 1: Motion redistribute); raise", True),
    # an ordinal that names the gather must never promote it
    ("redistribute overflow: ... (node 0: Motion gather); raise", False),
    ("redistribute overflow: ... (node 7: Motion redistribute)", False),
])
def test_redistribute_overflow_finds_its_motion_by_ordinal(message, grows):
    gather, red, _ = _synthetic()
    red._observed_bucket = 3000
    assert X.grow_expansion(gather, message) is grows
    assert red.bucket_cap == (4096 if grows else 64)
    assert gather.bucket_cap == 0 or not grows or gather.kind == "gather"


def test_lowerer_names_nodes_under_its_root_and_strangers_after():
    gather, red, scan = _synthetic()
    low = X.Lowerer({}, platform="cpu", root=gather)
    assert [low.ref(n) for n in (gather, red, scan)] == [0, 1, 2]
    assert low.label(red) == "(node 1: Motion redistribute)"
    other = N.PScan("u", {"k": "k"}, 8)       # outside the root
    assert low.ref(other) == 3
    assert X.keyed_node(X.numbered_nodes(gather), "(node 3)") is None


JOIN = ("select sum(j2.w) as sw from (select key as kk from j1) x "
        "join j2 on kk = j2.key")


def _skewed_session(hot: int = 1500):
    cfg = Config(n_segments=4).with_overrides(**{
        "planner.broadcast_threshold": 0,
        "planner.runtime_filter_threshold": 0})
    s = cb.Session(cfg)
    s.sql("create table j1 (a bigint, key bigint) distributed by (a)")
    s.sql("create table j2 (b bigint, key bigint, w bigint) "
          "distributed by (b)")
    s.sql("insert into j1 values " +
          ",".join(f"({i}, {0 if i < hot else i})" for i in range(2000)))
    s.sql("insert into j2 values " +
          ",".join(f"({i}, {i}, {i})" for i in range(2000)))
    return s


def test_overflowed_redistribute_names_its_node_and_grows():
    """The program's own message, raised by the run, leads the retry to
    the one motion that overflowed (and to no other)."""
    s = _skewed_session()
    q = JOIN
    plan = _plan(s, q)
    reds = [n for n in X.numbered_nodes(plan)
            if isinstance(n, N.PMotion) and n.kind == "redistribute"]
    caps = [n.bucket_cap for n in reds]
    with pytest.raises(X.ExecError, match=r"redistribute overflow") as ei:
        DX.execute_distributed(plan, s)
    named = X.keyed_node(X.numbered_nodes(plan), str(ei.value))
    assert named in reds and "Motion redistribute" in str(ei.value)
    # the failed run pinned what it saw on the node it names
    assert named._observed_bucket > named.bucket_cap
    assert X.grow_expansion(plan, str(ei.value))
    grown = [n for n, c in zip(reds, caps) if n.bucket_cap != c]
    assert grown == [named] and named.bucket_cap >= named._observed_bucket
    out = DX.execute_distributed(plan, s).to_pandas()
    assert out.sw[0] == sum(range(1500, 2000))
    # and the whole statement path recovers by itself
    s2 = _skewed_session()
    assert s2.sql(q).to_pandas().sw[0] == sum(range(1500, 2000))
    assert s2.growth_events >= 1


def test_overflowed_join_expansion_names_its_join_and_grows():
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
    plan = _plan(s, "select count(*) as n from probe, build "
                    "where probe.k = build.k")
    with pytest.raises(X.ExecError, match=r"expansion overflow") as ei:
        X.execute(plan, s)
    join = X.find_expansion_node(plan, str(ei.value))
    assert isinstance(join, N.PJoin)
    assert f"(node {X.numbered_nodes(plan).index(join)}: Join" \
        in str(ei.value)
    cap = join.out_capacity
    assert X.grow_expansion(plan, str(ei.value))
    assert join.out_capacity == cap * 4


def test_rung_cache_hit_records_motion_stats_on_the_plan_at_hand():
    """Two plans of one statement: the second is served the first's
    program from the rung cache, and its OWN redistribute is pinned with
    what the run saw (stats keys are ordinals, so no aliasing of one
    plan's addresses onto another's is needed)."""
    s = _skewed_session(hot=0)
    q = JOIN
    p1, p2 = _plan(s, q), _plan(s, q)
    assert p1 is not p2
    compiles = s.stmt_log.counter("compiles")
    fn1 = s._rung_executable(q, p1, ["j1", "j2"])
    fn2 = s._rung_executable(q, p2, ["j1", "j2"])
    assert fn2 is fn1
    assert s.stmt_log.counter("compiles") == compiles + 1
    try:
        out = DX.execute_distributed(p2, s, fn2).to_pandas()
    except X.ExecError as e:
        # the estimate-seeded rung may be a few rows short: the message
        # then names p2's own motion, and the promoted rung answers
        assert X.grow_expansion(p2, str(e))
        out = DX.execute_distributed(
            p2, s, s._rung_executable(q, p2, ["j1", "j2"])).to_pandas()
    assert out.sw[0] == sum(range(2000))
    red2 = [n for n in X.numbered_nodes(p2)
            if isinstance(n, N.PMotion) and n.kind == "redistribute"]
    red1 = [n for n in X.numbered_nodes(p1)
            if isinstance(n, N.PMotion) and n.kind == "redistribute"]
    assert red2 and all(getattr(n, "_observed_bucket", 0) > 0 for n in red2)
    assert all(int(n._seg_rows.sum()) > 0 for n in red2)
    assert not any(hasattr(n, "_observed_bucket") for n in red1)
    assert not hasattr(p2, "_stat_id_alias")


@pytest.mark.parametrize("nseg", [1, 4])
def test_explain_analyze_counts_find_their_nodes(nseg):
    """The instrumented programs report per-node rows by ordinal, and
    the renderer lays them back on the plan."""
    s = cb.Session(Config(n_segments=nseg))
    s.sql("create table t (a bigint, b bigint) distributed by (a)")
    s.sql("insert into t values " + ",".join(
        f"({i},{i % 5})" for i in range(100)))
    text = s.explain_analyze("select b, count(*) as c from t "
                             "where a < 50 group by b")
    lines = [ln for ln in text.splitlines() if "->" in ln]
    assert all("rows=" in ln for ln in lines), text
    scan = next(ln for ln in lines if "Scan t" in ln)
    top = lines[0]
    assert "rows=5" in top and ("rows=100" in scan or "rows=50" in scan)
