"""Where an IN-subquery's semi-join is placed, and how it is sized.

``x IN (subquery)`` whose outer columns all belong to one FROM item of a
join filters that item before the join tree (``Binder._place_in_subquery``:
σ_{x∈S}(R ⋈ T) = σ_{x∈S}(R) ⋈ T), and a semi-join whose probe keys are
unique in its probe emits at most ``min(probe, build)`` rows
(``cost._estimate_join``). Q18's ``o_orderkey IN (…)`` then filters
orders, and the lookups above it run at the few rows it keeps, not at
lineitem's. NOT IN, EXISTS, a predicate over two items and one on an
outer join's side keep their place above the join tree. Answers equal
the unmoved plan's.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import Config
from cloudberry_tpu.exec.executor import all_nodes
from cloudberry_tpu.plan import binder as B
from cloudberry_tpu.plan import cost, joincap
from cloudberry_tpu.plan import nodes as N
from cloudberry_tpu.plan.planner import plan_statement
from cloudberry_tpu.sql.parser import parse_sql
from tools.tpch_queries import QUERIES
from tools.tpchgen import load_tpch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import cell as C, load       # noqa: E402


def _plan(session, sql: str) -> N.PlanNode:
    return plan_statement(parse_sql(sql), session, {},
                          explain_only=True).plan


def _filtering_joins(plan: N.PlanNode) -> list:
    return [nd for nd in all_nodes(plan)
            if isinstance(nd, N.PJoin) and nd.kind in ("semi", "anti")]


def _on_scan(join: N.PJoin) -> bool:
    return not any(isinstance(nd, N.PJoin) for nd in all_nodes(join.probe))


@pytest.fixture
def unmoved(monkeypatch):
    """Every IN-subquery applied above the join tree, as before."""
    def hold():
        monkeypatch.setattr(B.Binder, "_place_in_subquery",
                            lambda self, pred, plans, scope: None)
    return hold


def _rows(session, sql: str) -> list:
    df = session.sql(sql).to_pandas()
    return sorted(map(tuple, df.astype(object).where(
        df.notna(), None).values.tolist()), key=repr)


# ------------------------------------------------------- hand cases

def _hand_session():
    """``a`` 400 rows keyed by ``k`` (``x`` repeats, ``g`` a group),
    ``b`` keyed by ``k`` over every other key of ``a`` (``y`` some NULL),
    ``s`` 60 (v, w) pairs, a NULL ``v`` among them."""
    s = cb.Session(Config(n_segments=1))
    s.sql("create table a (k bigint, x bigint, g bigint) distributed by (k)")
    s.sql("create table b (k bigint, y bigint) distributed by (k)")
    s.sql("create table s (v bigint, w bigint) distributed by (w)")
    k = np.arange(400, dtype=np.int64)
    s.catalog.table("a").set_data({"k": k, "x": k % 97, "g": k % 7})
    kb = k[::2].copy()
    s.catalog.table("b").set_data({"k": kb, "y": kb % 53},
                                  validity={"y": kb % 11 != 3})
    rng = np.random.default_rng(40)
    v = rng.integers(0, 100, 60)
    s.catalog.table("s").set_data({"v": v, "w": rng.integers(0, 7, 60)},
                                  validity={"v": np.arange(60) != 5})
    return s


JOIN = "select a.k, a.x, b.y from a, b where a.k = b.k and "
OUTER = ("select a.k, a.x, b.y, t.v from a left join b on a.k = b.k, s t "
         "where t.w = a.g and ")
HAND = {
    # (statement, the tables the semi- or anti-join's probe scans: one
    # where it filters a FROM item, all of them above the join tree)
    "in": (JOIN + "a.x in (select v from s)", {"a"}),
    "in_correlated": (JOIN + "a.x in (select v from s where s.w = a.g)",
                      {"a"}),
    "in_on_the_other_item": (JOIN + "b.y in (select v from s)", {"b"}),
    "not_in": (JOIN + "a.x not in (select v from s where v is not null)",
               {"a", "b"}),
    "exists": (JOIN + "exists (select 1 from s where s.v = a.x)",
               {"a", "b"}),
    "not_exists": (JOIN + "not exists (select 1 from s where s.v = a.x)",
                   {"a", "b"}),
    "in_over_two_items": (JOIN + "a.x in (select v from s where s.w = b.y)",
                          {"a", "b"}),
    "in_of_an_expression_over_two_items": (
        JOIN + "a.x + b.y in (select v from s)", {"a", "b"}),
    "in_on_an_outer_joins_side": (
        "select a.k, a.x, b.y from a left join b on a.k = b.k "
        "where b.y in (select v from s)", {"a", "b"}),
    "in_on_an_outer_joins_side_among_joins": (
        OUTER + "b.y in (select v from s)", {"a", "b", "s"}),
    "in_on_an_outer_joins_preserved_side_among_joins": (
        OUTER + "a.x in (select v from s)", {"a", "b", "s"}),
    "in_on_a_right_joins_null_extended_side_among_joins": (
        "select a.k, a.x, b.y, t.v from b right join a on a.k = b.k, s t "
        "where t.w = a.g and b.y in (select v from s)", {"a", "b", "s"}),
}


@pytest.mark.parametrize("case", sorted(HAND))
def test_a_semi_join_filters_its_one_item_before_the_joins(case):
    sql, tables = HAND[case]
    plan = _plan(_hand_session(), sql)
    (j,) = _filtering_joins(plan)
    assert {nd.table_name for nd in all_nodes(j.probe)
            if isinstance(nd, N.PScan)} == tables, plan.explain()
    assert _on_scan(j) == (len(tables) == 1)


@pytest.mark.parametrize("case", sorted(HAND))
def test_the_answer_is_the_unmoved_plans(case, unmoved):
    sql, _ = HAND[case]
    got = _rows(_hand_session(), sql)
    unmoved()
    want = _rows(_hand_session(), sql)
    assert got == want
    assert len(got) > 0 or case == "not_in"


# --------------------------------------------------- TPC-H, SF 0.01

@pytest.fixture(scope="module")
def tpch():
    s = cb.Session(Config(n_segments=1))
    load_tpch(s, sf=0.01, seed=7)
    return s


def test_q18s_semi_join_probes_orders(tpch):
    (j,) = _filtering_joins(_plan(tpch, QUERIES["q18"]))
    assert isinstance(j.probe, N.PScan) and j.probe.table_name == "orders"


# (Q18 at its validation value keeps no order at this scale)
TPCH = {**{q: QUERIES[q] for q in ("q20", "q21", "q22")},
        "q18": QUERIES["q18"].replace("> 300", "> 200")}


@pytest.mark.parametrize("qname", sorted(TPCH))
def test_tpch_answers_equal_the_unmoved_plans(tpch, qname, unmoved):
    got = _rows(tpch, TPCH[qname])
    unmoved()
    s = cb.Session(Config(n_segments=1))
    load_tpch(s, sf=0.01, seed=7)
    assert got == _rows(s, TPCH[qname])
    assert len(got) > 0


# ------------------------------------------------------- the estimate

@pytest.mark.parametrize("key,unique", [("k", True), ("x", False)])
def test_a_unique_probe_key_bounds_the_semi_join(key, unique):
    """Probe ``a`` on its unique ``k``: each build row meets at most one
    probe row, so at most ``min(probe, build)`` survive; on ``x`` (97
    values over 400 rows) the old fraction of the probe stays."""
    cat = _hand_session().catalog
    a = B._scan_node(cat.table("a"), "a", 400)
    sc = B._scan_node(cat.table("s"), "s", 60)
    j = N.PJoin("semi", sc, a, [B._colref(sc.field("s.v"))],
                [B._colref(a.field(f"a.{key}"))], [])
    p, b = cost.estimate_rows(a, cat), cost.estimate_rows(sc, cat)
    old = p * min(1.0, cat.table("s").ndv("v") / cat.table("a").ndv(key))
    want = min(p, b) if unique else old
    assert cost.estimate_rows(j, cat) == want
    assert old != want or not unique


# ----------------------------------------- Q18 through a store, cold

SEED, SCALE = 2147486231, 0.05
STMTS = {"q18": {"quantity": 300}, "q3": {"segment": 1, "day": 15},
         "q13": {"word1": 0, "word2": 1}}


def _text(stmt: str, params: dict) -> str:
    with open(os.path.join(C.BENCH, "statements", stmt + ".sql"),
              encoding="utf-8") as f:
        return f.read().format(**C.load_module("reference", stmt)
                               .bind(params))


@pytest.fixture(scope="module")
def stored(tmp_path_factory):
    """(the store's config, the generator's arrays)."""
    root = str(tmp_path_factory.mktemp("store"))
    cfg = Config(n_segments=1).with_overrides(**{
        "storage.root": root, "storage.rows_per_partition": 1 << 20})
    keep: dict = {}
    for stmt in STMTS:
        for t, cols in C.load_module("reference", stmt).COLUMNS.items():
            keep.setdefault(t, set()).update(cols)
    _, truth = load.load(cb.Session(cfg), ["lineitem", "orders", "customer"],
                         keep, SCALE, SEED, 2500)
    return cfg, truth


def test_q18s_capacities_above_the_semi_join_are_its_few_rows(stored):
    """Cold, as a send stamps it: the semi-join on orders emits at a
    capacity of its own, and every join, aggregate and sort above it runs
    at 16,384 rows or fewer, none at lineitem's."""
    cfg, _ = stored
    s = cb.Session(cfg)
    plan = _plan(s, _text("q18", STMTS["q18"]))
    joincap.stamp_join_capacities(plan, s.catalog)
    (semi,) = _filtering_joins(plan)
    assert _on_scan(semi)
    assert 0 < semi.out_capacity < N.capacity_of(semi.probe)

    def above(nd):
        return nd is not semi and any(c is semi for c in all_nodes(nd))
    over = [nd for nd in all_nodes(plan)
            if isinstance(nd, (N.PJoin, N.PAgg, N.PSort)) and above(nd)]
    assert len(over) >= 4
    assert all(N.capacity_of(nd) <= 16384 for nd in over), plan.explain()


@pytest.mark.parametrize("quantity", [300, 312, 313, 314, 315])
def test_q18_retries_nothing_and_answers_the_reference(stored, quantity):
    cfg, truth = stored
    s = cb.Session(cfg)
    got = s.sql(_text("q18", {"quantity": quantity}))
    ref = C.load_module("reference", "q18").answer(
        truth, {"quantity": quantity})
    assert s.stmt_log.counter("join_compact_retries") == 0
    df = got.to_pandas()
    assert list(map(int, df["o_orderkey"])) == [r[2] for r in ref["rows"]]
    assert list(df["total_qty"].astype(float)) == [r[5] for r in ref["rows"]]


@pytest.mark.parametrize("stmt,want", [("q18", 1), ("q3", 0), ("q13", 0)])
def test_a_launch_counts_its_semi_joins_on_a_scan(stored, stmt, want):
    cfg, truth = stored
    s = cb.Session(cfg)
    got = s.sql(_text(stmt, STMTS[stmt]))
    assert s.stmt_log.counter("launch_joins_semi_on_scan") == want
    assert got.num_rows() >= (0 if stmt == "q18" else 1)


@pytest.mark.parametrize("table,cols,unique", [
    ("orders", ("o_orderkey",), True), ("orders", ("o_custkey",), False),
    ("orders", ("o_orderkey", "o_custkey"), True),
    # (unique as a pair, which a loaded copy could count; the manifest
    # flags single columns)
    ("pairs", ("i", "j"), False), ("pairs", ("i",), False)])
def test_a_stored_tables_uniqueness_is_its_manifests_cold_or_warm(
        stored, table, cols, unique):
    """The capacities' view of the catalog answers from the manifest's
    rule whether the table is loaded or not: a cold backend and a warm
    one size a semi-join alike."""
    cfg, _ = stored
    s = cb.Session(cfg)
    if "pairs" not in s.catalog.tables:
        s.sql("create table pairs (i bigint, j bigint) distributed by (i)")
        s.catalog.table("pairs").set_data(
            {"i": np.arange(600) // 3, "j": np.arange(600) % 3})
    answers = []
    for warm in (False, True):
        s = cb.Session(cfg)
        if warm:
            s.catalog.table(table).ensure_loaded()
            assert s.catalog.table(table).is_unique_cols(cols) \
                == (unique or table == "pairs" and len(cols) == 2)
        t = joincap._Persisted(s.catalog).table(table)
        answers.append(t.is_unique_cols(cols) if len(cols) > 1
                       else t.is_unique(cols[0]))
    assert answers == [unique, unique]
