"""A grouping key that the other keys determine by proof is carried, not
sorted (``plan/fdep.py``, ``PAgg.carried``): the aggregate sorts the
keys that determine the rest, packs only them, and takes each carried
key at its group's first row. Q18's five-key ``GROUP BY`` sorts
``o_orderkey`` and carries four; Q3's sorts ``l_orderkey`` and carries
two. Only proofs: unique keys of the scanned tables (the manifest's
flags for a cold store) and lookups through them. An expansion, an
expression, a null-extended side, a union, a table whose flag an append
took away and a grouping without the determinant carry nothing. Every
answer equals the plain reference or a pandas group-by, at one segment
and at four, one-shot and tiled."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import Config
from cloudberry_tpu.exec import kernels as K
from cloudberry_tpu.exec.executor import all_nodes
from cloudberry_tpu.plan import joincap
from cloudberry_tpu.plan import nodes as N
from cloudberry_tpu.plan.planner import plan_statement
from cloudberry_tpu.serve.client import Client
from cloudberry_tpu.serve.server import Server
from cloudberry_tpu.sql.parser import parse_sql

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import cell as C, compare, load       # noqa: E402

SEED, SCALE = 2147486231, 0.01
# (300, the validation value, keeps no order at this scale)
DRAWS = {"q18": {"quantity": 250}, "q3": {"segment": 1, "day": 15}}
# statement: (the keys it sorts, the keys it carries, the words its
# grouping sorts compare: Q18's order aggregate one, its five-key one one)
CARRY = {"q18": (("o_orderkey",),
                 ("c_name", "c_custkey", "o_orderdate", "o_totalprice"), 2),
         "q3": (("l_orderkey",), ("o_orderdate", "o_shippriority"), 1)}
COUNTERS = ("launch_agg_keys_carried", "launch_agg_sort_words",
            "launch_agg_rows_in", "compiles")


def _config(root: str, **over):
    return Config(n_segments=1).with_overrides(**{
        "storage.root": root, "storage.rows_per_partition": 1 << 20,
        **over})


def _text(stmt: str) -> str:
    with open(os.path.join(C.BENCH, "statements", stmt + ".sql"),
              encoding="utf-8") as f:
        return f.read().format(
            **C.load_module("reference", stmt).bind(DRAWS[stmt]))


def _plan(session, query: str) -> N.PlanNode:
    plan = plan_statement(parse_sql(query), session, {},
                          explain_only=True).plan
    if session.config.n_segments == 1:
        joincap.stamp_join_capacities(plan, session.catalog)
    return plan


def _bare(names) -> tuple:
    """Column names without the table the binder qualifies them by."""
    return tuple(n.rsplit(".", 1)[-1] for n in names)


def _aggs(session, query: str) -> list:
    """The grouped aggregates of the statement's plan, the outermost
    first."""
    return [nd for nd in all_nodes(_plan(session, query))
            if isinstance(nd, N.PAgg) and nd.group_keys]


@pytest.fixture(scope="module")
def loaded(tmp_path_factory):
    """(the store's root, the generator's arrays) of a COLD SF0.01 store
    written by the benchmark's loader."""
    root = str(tmp_path_factory.mktemp("store"))
    keep: dict = {}
    for stmt in DRAWS:
        for t, cols in C.load_module("reference", stmt).COLUMNS.items():
            keep.setdefault(t, set()).update(cols)
    _, truth = load.load(cb.Session(_config(root)),
                         ["lineitem", "orders", "customer"], keep, SCALE,
                         SEED, 2500)
    return root, truth


@pytest.fixture(scope="module")
def served(loaded):
    """{statement: (the second send's wire answer, counters it added)}
    from a server of its own over the cold store."""
    root, _ = loaded
    out = {}
    with Server(config=_config(root)) as srv:
        log = srv.session.stmt_log
        c = Client(srv.host, srv.port, timeout=300.0)
        try:
            for stmt in sorted(DRAWS):
                c.sql(_text(stmt))
                before = {n: log.counter(n) for n in COUNTERS}
                got = c.sql(_text(stmt))
                out[stmt] = got, {n: log.counter(n) - before[n]
                                  for n in COUNTERS}
        finally:
            c.close()
    return out


# ------------------------------------------------ Q18 and Q3, cold store

@pytest.mark.parametrize("stmt", sorted(CARRY))
def test_the_statement_sorts_one_key_and_carries_the_rest(loaded, stmt):
    root, _ = loaded
    s = cb.Session(_config(root))          # every table cold
    agg = _aggs(s, _text(stmt))[0]
    sorted_keys, carried, _ = CARRY[stmt]
    assert _bare(agg.carried) == carried
    assert _bare(k for k, _ in agg.group_keys
                 if k not in agg.carried) == sorted_keys
    assert agg.pack_bits == 32             # one u32 word, not a 64-bit pack
    text = s.explain(_text(stmt))
    assert f"GroupAgg single [{agg.capacity}] carry {len(carried)}" in text
    assert text.count(" carry ") == 1


def test_q18s_top_sort_packs_its_carried_columns_in_one_word(loaded):
    """The carried ``o_totalprice`` and ``o_orderdate`` keep the scan they
    come from, so the top-100 sort above them packs one 64-bit word by
    their statistics; the order aggregate under the semi-join has one
    key and carries nothing."""
    root, _ = loaded
    s = cb.Session(_config(root))
    plan = _plan(s, _text("q18"))
    (top,) = [nd for nd in all_nodes(plan) if isinstance(nd, N.PSort)]
    assert top.pack_bits == 64
    per_order = _aggs(s, _text("q18"))[1]
    assert _bare(k for k, _ in per_order.group_keys) == ("l_orderkey",)
    assert per_order.carried == () and per_order.pack_bits == 32


@pytest.mark.parametrize("stmt", sorted(CARRY))
def test_the_served_answer_equals_the_plain_reference(loaded, served, stmt):
    _, truth = loaded
    ref = C.load_module("reference", stmt).answer(truth, DRAWS[stmt])
    assert len(ref["rows"]) >= 10
    got, _ = served[stmt]
    wrong, ulps = compare.gap(got, ref)
    assert wrong == 0, (got["rows"][:3], ref["rows"][:3])
    assert max(ulps.values(), default=0.0) == 0.0


@pytest.mark.parametrize("stmt", sorted(CARRY))
def test_a_served_launch_counts_carried_keys_and_sort_words(served, stmt):
    _, added = served[stmt]
    _, carried, words = CARRY[stmt]
    assert added["launch_agg_keys_carried"] == len(carried)
    assert added["launch_agg_sort_words"] == words
    assert added["launch_agg_rows_in"] > 0 and added["compiles"] == 0


# ---------------------------------------------- what proves nothing

def _ram_session(n_segments: int = 1, n_big: int = 4000, **over):
    s = cb.Session(Config(n_segments=n_segments).with_overrides(**over))
    s.sql("create table big (k int, g int, v int) distributed by (k)")
    s.sql("create table dim (k int, h int) distributed by (k)")
    s.sql("create table multi (k int, h int) distributed by (k)")
    k = np.arange(n_big)
    s.catalog.table("big").set_data(
        {"k": k.astype(np.int32), "g": (k % 37 + 100).astype(np.int32),
         "v": (k % 11).astype(np.int32)})
    s.catalog.table("dim").set_data(
        {"k": np.arange(50, dtype=np.int32),
         "h": (np.arange(50) % 9 * 1000).astype(np.int32)})
    m = np.arange(120)
    s.catalog.table("multi").set_data(
        {"k": (m % 40).astype(np.int32), "h": (m % 7).astype(np.int32)})
    return s


def _frames(s) -> dict:
    import pandas as pd

    return {t: pd.DataFrame(s.catalog.table(t).data)
            for t in ("big", "dim", "multi")}


def _rows(s, sql) -> list:
    got = s.sql(sql).to_pandas().astype(object)
    return sorted((tuple(None if v is None or v != v else int(v)
                         for v in row)
                   for row in got.itertuples(index=False)), key=repr)


def _want(frame, keys: list) -> list:
    out = frame.groupby(keys, dropna=False).size()
    return sorted((tuple(None if v != v else int(v) for v in
                         (ix if isinstance(ix, tuple) else (ix,)))
                   + (int(n),) for ix, n in out.items()), key=repr)


def _big_twice(f):
    """big's (k, g), then each row again with g + 1: k repeats."""
    import pandas as pd

    b = f["big"][["k", "g"]]
    return pd.concat([b, b.assign(g=b["g"] + 1)])


NOTHING = {
    # a build key that is no key of its table: the lookup rule would
    # have dim.k determine multi.h
    "an_expansion": (
        "select d.k as k, m.h as h, count(*) as n from dim d, multi m "
        "where d.k = m.k group by d.k, m.h",
        lambda f: _want(f["dim"].merge(f["multi"], on="k"), ["k", "h_y"])),
    "an_expression_key": (
        "select k + 0 as kk, g, count(*) as n from big group by k + 0, g",
        lambda f: _want(f["big"], ["k", "g"])),
    "a_null_extended_build": (
        "select d.k as k, b.g as g, count(*) as n from dim d "
        "left outer join big b on d.h = b.k group by d.k, b.g",
        lambda f: _want(f["dim"].merge(f["big"], left_on="h", right_on="k",
                                       how="left"), ["k_x", "g"])),
    "a_grouping_without_the_determinant": (
        "select g, v, count(*) as n from big group by g, v",
        lambda f: _want(f["big"], ["g", "v"])),
    "a_union": (
        "select k, g, count(*) as n from (select k, g from big union all "
        "select k, g + 1 as g from big) u group by k, g",
        lambda f: _want(_big_twice(f), ["k", "g"])),
}


@pytest.mark.parametrize("case", sorted(NOTHING))
def test_what_proves_nothing_keeps_every_key_sorted(case):
    s = _ram_session()
    sql, want = NOTHING[case]
    assert all(a.carried == () for a in _aggs(s, sql))
    assert " carry " not in s.explain(sql)
    assert _rows(s, sql) == want(_frames(s))


@pytest.mark.parametrize("cold", [False, True], ids=["warm", "cold"])
def test_an_appended_duplicate_takes_the_proof_away(tmp_path, cold):
    """``k`` is unique until an insert repeats it with another ``g``: the
    next plan sorts both keys, a warm backend by the data and a cold one
    by the manifest's flag, and the two groups of that ``k`` are two
    rows."""
    root = str(tmp_path)
    s = cb.Session(_config(root))
    s.sql("create table t (k int not null, g int not null) "
          "distributed by (k)")
    s.sql("insert into t values " + ", ".join(
        f"({i}, {i % 5})" for i in range(300)))
    q = "select k, g, count(*) as n from t group by k, g"
    assert _bare(_aggs(s, q)[0].carried) == ("g",)
    assert len(s.sql(q).to_pandas()) == 300
    s.sql("insert into t values (7, 99)")
    if cold:
        s = cb.Session(_config(root))
        assert s.catalog.table("t").cold
    assert _aggs(s, q)[0].carried == ()
    got = s.sql(q).to_pandas()
    assert len(got) == 301
    assert sorted(got[got["k"] == 7]["g"].tolist()) == [2, 99]


# ------------------------------------ one segment and four, one-shot and tiled

def _by(frame, keys: list, col: str) -> list:
    """(key..., sum of ``col``) a group, as the statements select them."""
    out = frame.groupby(keys)[col].sum()
    return sorted(tuple(int(v) for v in ix) + (int(n),)
                  for ix, n in out.items())


def _kept(f) -> "object":
    """dim's rows whose key is a ``v`` whose lines' ``g`` sum over 40,000."""
    heavy = f["big"].groupby("v")["g"].sum().loc[lambda x: x > 40000].index
    return f["dim"][f["dim"]["k"].isin(heavy)]


CARRIED = {
    # Q3's: the probe's key finds one build row, which holds h
    "q3_shape": (
        "select b.v as k, d.h as h, sum(b.g) as s from big b, dim d "
        "where b.v = d.k group by b.v, d.h",
        lambda f: _by(f["big"].merge(f["dim"], left_on="v", right_on="k"),
                      ["v", "h"], "g")),
    # the build's own unique key and a column of its row, the carried
    # one named first
    "unique_key_shape": (
        "select d.k as k, d.h as h, sum(b.g) as s from big b, dim d "
        "where b.v = d.k group by d.h, d.k",
        lambda f: _by(f["big"].merge(f["dim"], left_on="v", right_on="k"),
                      ["k_y", "h"], "g")),
    # Q18's: the same over the lines a semi-join on an aggregate keeps
    "q18_shape": (
        "select d.k as k, d.h as h, sum(b.g) as s from dim d, big b "
        "where d.k = b.v and d.k in (select v from big group by v "
        "having sum(g) > 40000) group by d.h, d.k",
        lambda f: _by(_kept(f).merge(f["big"], left_on="k", right_on="v"),
                      ["k_x", "h"], "g")),
}
HOW = [(shape, how) for shape in sorted(CARRIED)
       for how in ("one_segment", "four_segments")] + [
    # (a tile step streams big and builds on dim; the semi-join's
    # aggregate of big does not tile)
    ("q3_shape", "tiled"), ("unique_key_shape", "tiled")]


def _sums(s, sql) -> list:
    return sorted(tuple(int(v) for v in row) for row in
                  s.sql(sql).to_pandas().itertuples(index=False))


@pytest.mark.parametrize("shape, how", HOW)
def test_a_carried_key_answers_as_a_sorted_one(shape, how):
    sql, want = CARRIED[shape]
    s = _ram_session(4) if how == "four_segments" else \
        _ram_session(1, 200_000, **{"resource.query_mem_bytes": 4 << 20}) \
        if how == "tiled" else _ram_session()
    assert any(_bare(a.carried) == ("h",) for a in _aggs(s, sql))
    assert " carry 1" in s.explain(sql)
    got = _sums(s, sql)
    assert got == want(_frames(s)) and len(got) >= 3
    if how == "tiled":
        assert s.last_tiled_report["n_tiles"] > 1


# -------------------------------------------------------- the kernel alone

@pytest.mark.parametrize("pack_bits", [0, 32])
def test_group_aggregate_gathers_a_carried_key_at_its_groups_first_row(
        pack_bits):
    """With ``b`` a function of ``a``, sorting ``a`` alone and carrying
    ``b`` gives the groups, sums and key columns of sorting both."""
    rng = np.random.default_rng(5)
    a = rng.integers(0, 300, 5000).astype(np.int32)
    b = ((a * 7919) % 1000).astype(np.int32)
    v = rng.integers(0, 100, 5000).astype(np.int64)
    sel = rng.random(5000) < 0.8
    specs = [K.AggSpec("sum", "s"), K.AggSpec("count", "n")]
    both = K.group_aggregate({"a": a, "b": b}, {"s": v, "n": None}, specs,
                             sel, 512)
    one = K.group_aggregate({"a": a, "b": b}, {"s": v, "n": None}, specs,
                            sel, 512, pack_bits=pack_bits, carried=("b",))
    assert int(both[3]) == int(one[3]) == len(np.unique(a[sel]))
    for got, want in zip(one[:3], both[:3]):
        if isinstance(got, dict):
            for c in want:
                np.testing.assert_array_equal(np.asarray(got[c]),
                                              np.asarray(want[c]))
        else:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
