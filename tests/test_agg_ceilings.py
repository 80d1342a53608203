"""A grouped aggregate's capacity is held to a ceiling that is a proof
(ISSUE 34, ``plan/joincap.py _group_ceiling``): where every group key is
a plain, non-null column of a scan, the groups number at most the
product over those scans of the lesser of the scan's capacity and the
product of its integer keys' spans. Q13's ``GROUP BY c_custkey`` over
its expansion's pair buffer then emits at customer's rows, Q18's
``GROUP BY l_orderkey`` at the key's span. Only proofs: an aggregation
overflow is an error no retry answers, so a property test over seeded
random tables holds every answer to a numpy group-by."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import Config
from cloudberry_tpu.exec.executor import all_nodes
from cloudberry_tpu.exec.kernels import row_rung_up
from cloudberry_tpu.plan import joincap
from cloudberry_tpu.plan import nodes as N
from cloudberry_tpu.plan.planner import plan_statement
from cloudberry_tpu.sql.parser import parse_sql

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import cell as C, load            # noqa: E402

SEED, SCALE = 2147486231, 0.01
DRAWS = {"q13": {"word1": 0, "word2": 1}, "q18": {"quantity": 250}}


def _text(stmt: str) -> str:
    with open(os.path.join(C.BENCH, "statements", stmt + ".sql"),
              encoding="utf-8") as f:
        return f.read().format(
            **C.load_module("reference", stmt).bind(DRAWS[stmt]))


def _aggs(session, query: str) -> list:
    """The grouped aggregates of the statement's plan, stamped as a send
    stamps it, in document order (the outermost first)."""
    plan = plan_statement(parse_sql(query), session, {},
                          explain_only=True).plan
    if session.config.n_segments == 1:
        joincap.stamp_join_capacities(plan, session.catalog)
    return [nd for nd in all_nodes(plan)
            if isinstance(nd, N.PAgg) and nd.group_keys]


@pytest.fixture(scope="module")
def stored(tmp_path_factory):
    """(a session over a COLD SF0.01 store, rows per table)."""
    root = str(tmp_path_factory.mktemp("store"))
    cfg = Config(n_segments=1).with_overrides(**{
        "storage.root": root, "storage.rows_per_partition": 1 << 20})
    keep: dict = {}
    for stmt in DRAWS:
        for t, cols in C.load_module("reference", stmt).COLUMNS.items():
            keep.setdefault(t, set()).update(cols)
    rows, _ = load.load(cb.Session(cfg), ["lineitem", "orders", "customer"],
                        keep, SCALE, SEED, 2500)
    return cb.Session(cfg), rows


def test_q13s_aggregates_emit_at_customers_rows(stored):
    """Every value of ``c_custkey`` comes from a row of customer: both
    aggregates (the second groups the first's output) and the sort above
    them run at customer's rung, not at the expansion's pair buffer."""
    s, rows = stored
    outer, inner = _aggs(s, _text("q13"))
    join = inner.child
    assert isinstance(join, N.PJoin) and join.expands
    assert join.out_capacity > 4 * rows["customer"]
    assert inner.capacity == outer.capacity == row_rung_up(rows["customer"])
    assert s.explain(_text("q13")).count(
        f"GroupAgg single [{row_rung_up(rows['customer'])}]") == 2


def test_q18s_order_aggregate_emits_at_its_keys_span(stored):
    """``GROUP BY l_orderkey`` over lineitem: the key's values lie in
    1..orders, by the zone maps of the partitions the scan reads. The
    semi-join's probe is a join sized by key containment (every line
    finds its order), so no probe capacity is stamped to overflow."""
    s, rows = stored
    final, per_order = _aggs(s, _text("q18"))
    assert per_order.capacity == row_rung_up(rows["orders"])
    assert N.capacity_of(per_order.child) == row_rung_up(rows["lineitem"])
    assert final.capacity <= N.capacity_of(final.child)
    text = s.explain(_text("q18"))
    assert f"GroupAgg single [{row_rung_up(rows['orders'])}]" in text
    assert "[probe " not in text


def _ram_session(n_segments: int = 1):
    s = cb.Session(Config(n_segments=n_segments))
    s.sql("create table big (k int, g int, v int) distributed by (k)")
    s.sql("create table dim (k int, h int) distributed by (k)")
    k = np.arange(4000)
    s.catalog.table("big").set_data(
        {"k": k.astype(np.int32), "g": (k % 37 + 100).astype(np.int32),
         "v": (k % 11).astype(np.int32)})
    s.catalog.table("dim").set_data(
        {"k": np.arange(50, dtype=np.int32),
         "h": (np.arange(50) * 1000).astype(np.int32)})
    return s


def test_the_lesser_of_span_and_rows_is_the_ceiling():
    s = _ram_session()
    # a narrow span in a large table: 37 values of g
    (agg,) = _aggs(s, "select g, count(*) as n from big group by g")
    assert agg.capacity == row_rung_up(37)
    # a wide span in a small table: 50 rows of h, span 49,001
    (agg,) = _aggs(s, "select h, count(*) as n from dim group by h")
    assert agg.capacity == row_rung_up(50)
    # keys of two scans: the product, held to the child's capacity
    (agg,) = _aggs(s, "select g, h, count(*) as n from big, dim "
                      "where big.v = dim.k group by g, h")
    assert agg.capacity == min(row_rung_up(37 * 50),
                               N.capacity_of(agg.child))
    # two scans of ONE table are two sources, not one
    (agg,) = _aggs(s, "select x.h as a, y.h as b, count(*) as n "
                      "from dim x, dim y where x.k % 5 = y.k % 5 "
                      "group by x.h, y.h")
    assert agg.capacity > 50


CTE_TWICE = {
    # every reference to a CTE holds the SAME scan object under its own
    # PShare: keys of two references are keys of two sources
    "scan": "with c as (select r, g from pairs) "
            "select a.g as x, b.g as y, count(*) as n from c a, c b "
            "where a.r = b.r group by a.g, b.g",
    "aggregate": "with c as (select r, g from pairs group by r, g) "
                 "select a.g as x, b.g as y, count(*) as n from c a, c b "
                 "where a.r = b.r group by a.g, b.g",
}


@pytest.mark.parametrize("shared", sorted(CTE_TWICE))
def test_two_references_to_one_cte_are_two_sources(shared):
    """300 rows, 30 to a value of ``r``, ``g`` distinct: 9,000 pairs of
    distinct ``g``. One bucket for both references would give a ceiling
    of 300 rows and an aggregation overflow no retry answers."""
    s = _ram_session()
    s.sql("create table pairs (r int, g int) distributed by (r)")
    i = np.arange(300)
    s.catalog.table("pairs").set_data(
        {"r": (i % 10).astype(np.int32), "g": i.astype(np.int32)})
    (agg, *_) = _aggs(s, CTE_TWICE[shared])
    assert agg._cap_ceiling == 300 * 300
    assert agg.capacity == min(row_rung_up(300 * 300),
                               N.capacity_of(agg.child))
    assert len(s.sql(CTE_TWICE[shared]).to_pandas()) == 9000
    # two keys of ONE reference are still one source
    (agg, *_) = _aggs(s, "with c as (select r, g from pairs) "
                         "select a.r, a.g, count(*) as n from c a, c b "
                         "where a.r = b.r group by a.r, a.g")
    assert agg._cap_ceiling == row_rung_up(300)  # the scan's capacity


def test_a_column_that_holds_nulls_has_no_ceiling():
    """NULL is a group of its own and lies in no span: a key that can be
    NULL (a nullable column, or a side an outer join null-extends)
    keeps the child's capacity."""
    s = _ram_session()
    s.sql("create table nn (k int, g int) distributed by (k)")
    s.sql("insert into nn values (1, 7), (2, 7), (3, null), (4, 8)")
    q = "select g, count(*) as n from nn group by g"
    (agg,) = _aggs(s, q)
    assert agg.capacity >= 3
    assert len(s.sql(q).to_pandas()) == 3
    (agg,) = _aggs(s, "select big.g as g, count(*) as n from dim "
                      "left outer join big on dim.k = big.k group by big.g")
    assert agg._cap_ceiling is None
    assert agg.capacity == N.capacity_of(agg.child)


def test_a_key_that_is_an_expression_keeps_the_childs_capacity():
    s = _ram_session()
    (agg,) = _aggs(s, "select g + 0 as gg, count(*) as n from big "
                      "group by g + 0")
    assert agg.capacity == N.capacity_of(agg.child) == row_rung_up(4000)
    assert agg._cap_ceiling is None


def test_a_unions_column_has_no_ceiling():
    """A union's output column holds every input's values: the first
    input's rows bound nothing."""
    s = _ram_session()
    (agg,) = _aggs(s, "select x, count(*) as n from (select h as x from dim "
                      "union all select k as x from big) u group by x")
    assert agg._cap_ceiling is None
    assert agg.capacity == N.capacity_of(agg.child)
    got = s.sql("select x, count(*) as n from (select h as x from dim "
                "union all select k as x from big) u group by x").to_pandas()
    assert len(got) == len(set(range(4000)) | set(range(0, 50000, 1000)))


def test_a_distributed_plan_is_not_stamped(monkeypatch):
    """Across segments an aggregate's capacity is per segment and sized
    by ``plan/distribute.py``: no ceiling is computed and none applied."""
    def never(*_a):
        raise AssertionError("a ceiling was computed for a dist plan")
    monkeypatch.setattr(joincap, "_group_ceiling", never)
    s = _ram_session(n_segments=4)
    q = "select g, count(*) as n from big group by g order by g"
    assert all(getattr(a, "_cap_ceiling", None) is None
               for a in _aggs(s, q))
    s.explain(q)
    got = s.sql(q).to_pandas()
    assert got["n"].sum() == 4000 and len(got) == 37


# ------------------------------------------------------------ the property

SHAPES = {
    # name: (rows of fact, key of fact -> values, rows of dim)
    "skewed": (3000, lambda r, n: (r.zipf(1.3, n) % 500).astype(np.int32),
               200),
    "wide_span_small_table": (
        40, lambda r, n: (r.integers(0, 2 ** 30, n)).astype(np.int32), 25),
    "narrow_span_large_table": (
        5000, lambda r, n: r.integers(7, 12, n).astype(np.int32), 300),
    "empty": (0, lambda r, n: np.zeros(0, dtype=np.int32), 10),
}
QUERIES = {
    "plain": "select g, count(*) as n, sum(v) as s from f group by g",
    "two_scans": "select f.g as g, d.h as h, count(*) as n from f, d "
                 "where f.k = d.k group by f.g, d.h",
    "outer_probe_key": (      # Q13's shape: an aggregate of an aggregate
        "select n, count(*) as c from (select d.k as k, count(f.g) as n "
        "from d left outer join f on d.k = f.k group by d.k) t group by n"),
    "outer_build_key": (      # the NULL-extended side's key
        "select f.g as g, count(*) as n from d left outer join f "
        "on d.k = f.k group by f.g"),
    "self_join": "select x.g as a, y.g as b, count(*) as n from f x, f y "
                 "where x.k = y.k group by x.g, y.g",
    "cte_self_join": (        # one scan object under two PShare nodes
        "with c as (select k, g from f) "
        "select x.g as a, y.g as b, count(*) as n from c x, c y "
        "where x.k = y.k group by x.g, y.g"),
    "cte_shared_aggregate": (
        "with c as (select k, g from f group by k, g) "
        "select x.g as a, y.g as b, count(*) as n from c x, c y "
        "where x.k = y.k group by x.g, y.g"),
    # Q3's grouping: the probe's key and a column of the one build row it
    # finds (carried, plan/fdep.py)
    "q3_grouping": "select f.k as k, d.h as h, sum(f.v) as s from f, d "
                   "where f.k = d.k group by f.k, d.h",
    # Q18's: a unique key and its row's column, the carried one first,
    # over the rows a semi-join on an aggregate keeps
    "q18_grouping": (
        "select d.k as k, d.h as h, count(*) as n from d, f "
        "where d.k = f.k and d.k in (select k from f group by k "
        "having sum(v) > 40) group by d.h, d.k"),
}


def _tables(shape: str, seed: int):
    n, keys, m = SHAPES[shape]
    r = np.random.default_rng([seed, len(shape)])
    f = {"k": r.integers(0, max(m, 1) * 2, n).astype(np.int32),
         "g": keys(r, n), "v": r.integers(-5, 50, n).astype(np.int32)}
    d = {"k": np.arange(m, dtype=np.int32),
         "h": r.integers(0, 9, m).astype(np.int32)}
    return f, d


def _expected(name: str, f: dict, d: dict) -> list:
    import pandas as pd

    F, D = pd.DataFrame(f), pd.DataFrame(d)
    if name == "plain":
        out = F.groupby("g").agg(n=("v", "size"), s=("v", "sum"))
        return sorted((int(g), int(r.n), int(r.s))
                      for g, r in out.iterrows())
    if name == "two_scans":
        out = F.merge(D, on="k").groupby(["g", "h"]).size()
        return sorted((int(g), int(h), int(n)) for (g, h), n in out.items())
    if name == "outer_probe_key":
        per = D.merge(F, on="k", how="left").groupby("k")["g"].count()
        out = per.groupby(per).size()
        return sorted((int(n), int(c)) for n, c in out.items())
    if name == "outer_build_key":
        out = D.merge(F, on="k", how="left").groupby(
            "g", dropna=False).size()
        return [((None if g != g else int(g)), int(n))
                for g, n in out.items()]
    if name == "q3_grouping":
        out = F.merge(D, on="k").groupby(["k", "h"])["v"].sum()
        return sorted((int(k), int(h), int(s)) for (k, h), s in out.items())
    if name == "q18_grouping":
        heavy = F.groupby("k")["v"].sum().loc[lambda x: x > 40].index
        out = D[D["k"].isin(heavy)].merge(F, on="k").groupby(
            ["k", "h"]).size()
        return sorted((int(k), int(h), int(n)) for (k, h), n in out.items())
    if name == "cte_shared_aggregate":
        F = F[["k", "g"]].drop_duplicates()
    out = F.merge(F, on="k").groupby(["g_x", "g_y"]).size()
    return sorted((int(a), int(b), int(n)) for (a, b), n in out.items())


def _nulls_last(row: tuple) -> tuple:
    return tuple((v is None, v or 0) for v in row)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_no_ceiling_is_under_the_groups_that_come(shape):
    """Seeded random tables, every query shape: no "aggregation overflow",
    every answer a pandas group-by's, every stamped capacity at least
    the groups that came and at most its child's. Q3's and Q18's
    groupings carry the key their other key determines."""
    for seed in (1, 2):
        f, d = _tables(shape, seed)
        s = cb.Session(Config(n_segments=1))
        s.sql("create table f (k int, g int, v int) distributed by (k)")
        s.sql("create table d (k int, h int) distributed by (k)")
        s.catalog.table("f").set_data(f)
        s.catalog.table("d").set_data(d)
        for name, q in QUERIES.items():
            got = s.sql(q).to_pandas()
            rows = [
                tuple(None if v is None or v != v else int(v) for v in row)
                for row in got.itertuples(index=False)]
            assert sorted(rows, key=_nulls_last) == sorted(
                _expected(name, f, d), key=_nulls_last), (shape, seed, name)
            for agg in _aggs(s, q):
                assert 1 <= agg.capacity <= max(
                    N.capacity_of(agg.child), 1), (shape, name)
            outer = _aggs(s, q)[0]
            assert outer.capacity >= len(rows), (shape, seed, name)
            if name in ("q3_grouping", "q18_grouping"):
                assert outer.carried == ("d.h",), (shape, seed, name)


def test_an_append_widens_the_range_before_the_next_plan():
    """A table in RAM: ``set_data`` recomputes a column's range with
    every change of its data and bumps the version plans are keyed on,
    so the statement after an insert outside the old range is planned
    from the new one."""
    s = cb.Session(Config(n_segments=1))
    s.sql("create table t (k int, g int) distributed by (k)")
    s.sql("insert into t values " + ", ".join(
        f"({i}, {i % 3})" for i in range(200)))
    q = "select g, count(*) as n from t group by g"
    assert _aggs(s, q)[0].capacity == 3
    assert len(s.sql(q).to_pandas()) == 3
    s.sql("insert into t values " + ", ".join(
        f"({i}, {i})" for i in range(1000, 1100)))
    assert _aggs(s, q)[0].capacity >= 103
    assert len(s.sql(q).to_pandas()) == 103
    s.sql("update t set g = g + 5000 where k < 50")
    assert len(s.sql(q).to_pandas()) == len(
        {i % 3 for i in range(50, 200)} | {i % 3 + 5000 for i in range(50)}
        | set(range(1000, 1100)))


def test_an_aggregation_overflow_is_an_error_no_retry_answers(monkeypatch):
    """Why only proofs may hold an aggregate: were a ceiling ever under
    the groups that come, the check is an error, the statement is not
    run again, and no capacity grows."""
    from cloudberry_tpu.exec.executor import ExecError

    s = _ram_session()
    monkeypatch.setattr(joincap, "_group_ceiling", lambda agg, catalog: 2)
    with pytest.raises(ExecError, match="aggregation overflow"):
        s.sql("select g, count(*) as n from big group by g")
    assert s.growth_events == 0
    assert s.stmt_log.counter("join_compact_retries") == 0
