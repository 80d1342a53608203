"""The literal template (sched/paramplan.py, ISSUE 29): a statement whose
skeleton is known binds its literal tokens and launches, without parse or
plan, and what it launches is what the full path would have.

(a) differential against the full path on the benchmark's Q1 and Q6 texts;
(b) every refusal reason, each still answered by the full path; (c) what
sends the next statement down the full path again; (d) a literal that
prunes other partitions; (e) the dispatch count, the fault seams and the
cancel check of a hit; (f) a hit calls neither the parser nor the planner.
"""

from __future__ import annotations

import os
import random
import time

import numpy as np
import pandas as pd
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu import lifecycle
from cloudberry_tpu.config import Config
from cloudberry_tpu.sched import paramplan
from cloudberry_tpu.utils import faultinject as FI

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STATEMENTS = os.path.join(ROOT, "benchmarks", "statements")


def _text(name: str) -> str:
    with open(os.path.join(STATEMENTS, name + ".sql"), encoding="utf-8") as f:
        return f.read()


Q1 = _text("q1")
Q6 = _text("q6")
# Q6 with the whole date open, so the `+ interval '1' year` fold meets
# leap days and month ends; and the same with a month's shift
Q6_DATE = Q6.replace("'{year}-01-01'", "'{date}'")
Q6_MONTH = Q6_DATE.replace("interval '1' year", "interval '1' month")
EDGE_DATES = ["1996-02-29", "1992-02-29", "1995-02-28", "1994-12-31",
              "1996-01-31", "1995-01-31", "1993-03-31", "1997-08-31",
              "1994-10-31", "1996-12-01", "1995-05-30", "1992-01-30"]


def _counter(s, name: str) -> int:
    return s.stmt_log.counter(name)


def _templates(s):
    return [gp.template for bucket in s._generic_cache.values()
            for gp in bucket if gp.template is not None]


def _refused(s, reason: str) -> int:
    return _counter(s, "template_refused." + reason)


# ------------------------------------------------------------ (a) Q1, Q6


def _q1_draw(rng) -> str:
    delta = rng.randint(60, 120)      # traffic/scan-streams.json
    return Q1.format(cutoff=str(np.datetime64("1998-12-01", "D") - delta))


def _q6_params(rng) -> dict:
    d = rng.randint(2, 9)
    return {"discount_lo": f"{(d - 1) / 100:.2f}",
            "discount_hi": f"{(d + 1) / 100:.2f}",
            "quantity": rng.randint(24, 25)}


def _q6_draw(rng) -> str:
    return Q6.format(year=rng.randint(1993, 1997), **_q6_params(rng))


def _edge_draw(text: str):
    def draw(rng) -> str:
        if rng.random() < 0.5:
            date = rng.choice(EDGE_DATES)
        else:
            date = str(np.datetime64("1992-01-01", "D")
                       + rng.randint(0, 2200))
        return text.format(date=date, **_q6_params(rng))
    return draw


@pytest.fixture(scope="module")
def lineitem(tmp_path_factory):
    """A store-backed one-segment session over TPC-H lineitem, several
    partitions."""
    from tools.tpchgen import stream_load_tpch

    cfg = Config(n_segments=1).with_overrides(**{
        "storage.root": str(tmp_path_factory.mktemp("store")),
        "storage.rows_per_partition": 8192})
    stream_load_tpch(cb.Session(cfg), sf=0.01, seed=3, tables=["lineitem"])
    return cb.Session(cfg)


@pytest.mark.parametrize("name,draw", [
    ("q1", _q1_draw), ("q6", _q6_draw),
    ("q6-any-date", _edge_draw(Q6_DATE)),
    ("q6-month", _edge_draw(Q6_MONTH))])
def test_a_hit_is_the_full_path(lineitem, name, draw):
    s = lineitem
    rng = random.Random(name)
    for _ in range(12):               # build, then arm
        s.sql(draw(rng))
    s._stmt_cache.clear()             # the arming sends' texts: Q1 has 61
    hits = 0
    for _ in range(210):
        text = draw(rng)
        hit = paramplan.template_bind(s, text)
        # the full path's choice for the same text: parse, plan, the
        # generic gate (prepare_one goes no other way)
        prep = paramplan.prepare_one(s, text)
        assert prep is not None and not prep.built
        if hit is None:
            continue                  # e.g. a date the binder refuses
        hits += 1
        gp, bindings = hit
        assert gp is prep.gp
        assert paramplan._same_bindings(bindings, prep.bindings)
        assert [paramplan._scan_files(k) for k in gp.keyed] == \
            [paramplan._scan_files(k) for k in prep.keyed]
        before = _counter(s, "template_binds")
        got = s.sql(text).to_pandas()
        assert _counter(s, "template_binds") == before + 1
        pd.testing.assert_frame_equal(got, prep.run(s).to_pandas(),
                                      check_exact=True)
    assert hits >= 200


def test_a_template_arms_only_once_every_free_token_has_moved(lineitem):
    s = lineitem
    text = ("select count(*) as n from lineitem where l_quantity < {q} "
            "and l_discount < {d}")
    s.sql(text.format(q=10, d="0.05"))
    t = next(t for t in _templates(s) if len(t.tokens) == 2)
    assert t.unseen == {0, 1}
    s.sql(text.format(q=11, d="0.05"))
    assert t.unseen == {1}
    assert paramplan.template_bind(s, text.format(q=12, d="0.05")) is None
    s.sql(text.format(q=11, d="0.06"))
    assert not t.unseen
    assert paramplan.template_bind(s, text.format(q=12, d="0.07"))


# ------------------------------------------------- (b) refusal reasons


def _mem_session(nseg: int = 1, rows: int = 200) -> cb.Session:
    s = cb.Session(Config(n_segments=nseg))
    s.sql("create table t (a bigint, b bigint, c text, d double) "
          "distributed by (a)")
    vals = ",".join(f"({i}, {i * 10}, '{'xyz'[i % 3]}', {i}.5)"
                    for i in range(rows))
    s.sql(f"insert into t values {vals}")
    s.sql("create table u (a bigint, g bigint) distributed by (a)")
    s.sql("insert into u values (1, 2), (3, 4), (5, 6)")
    return s


def _pts_session(nseg: int, rows: int = 65_536) -> cb.Session:
    s = cb.Session(Config(n_segments=nseg))
    s.sql("create table pts (k bigint, v bigint) distributed by (k)")
    s.catalog.table("pts").set_data({
        "k": np.arange(rows, dtype=np.int64),
        "v": np.arange(rows, dtype=np.int64) * 7 % 1000}, {})
    return s


REFUSALS = [
    ("dist", lambda: _mem_session(4),
     "select sum(b) as x from t where a < {}", (50, 60, 70),
     lambda v: sum(i * 10 for i in range(v))),
    ("direct_segment", lambda: _pts_session(4),
     "select v as x from pts where k = {}", (42, 43, 44),
     lambda v: v * 7 % 1000),
    ("point_lookup", lambda: _pts_session(1),
     "select v as x from pts where k = {}", (42, 43, 44),
     lambda v: v * 7 % 1000),
    ("plan_shape", lambda: _mem_session(1),
     "select sum(t.b) as x from t, u where t.a = u.a and t.b < {}",
     (20, 40, 60), lambda v: sum(b for b in (10, 30, 50) if b < v)),
    ("untracked_literal", lambda: _mem_session(1),
     "select sum(b) as x from t where c = 'x' and a < {}", (10, 20, 30),
     lambda v: sum(i * 10 for i in range(v) if i % 3 == 0)),
]


@pytest.mark.parametrize("reason,mk,text,values,want",
                         REFUSALS, ids=[r[0] for r in REFUSALS])
def test_a_refused_shape_answers_through_the_full_path(reason, mk, text,
                                                       values, want):
    s = mk()
    for v in values:
        assert s.sql(text.format(v)).to_pandas().x[0] == want(v)
    # once a variant, not once a send (the join's capacity follows its
    # predicate's selectivity: every literal is a variant there)
    variants = _counter(s, "generic_builds")
    assert _refused(s, reason) == variants >= 1
    assert variants == 1 or reason == "plan_shape"
    assert _counter(s, "template_binds") == 0
    assert not _templates(s)


def test_refused_no_stmt_cache():
    s = _mem_session()
    s.sql("create sequence sq")
    for want in (1, 2, 3):
        assert s.sql("select nextval('sq') as x").to_pandas().x[0] == want
    assert _refused(s, "no_stmt_cache") == 3
    assert _counter(s, "template_binds") == 0 and not _templates(s)


def test_refused_external(tmp_path):
    s = _mem_session()
    path = tmp_path / "ext.csv"
    path.write_text("1|10\n2|20\n3|30\n")
    s.sql(f"create external table ext (a bigint, b bigint) "
          f"location('file://{path}')")
    for v, want in ((2, 10), (3, 30), (4, 60)):
        out = s.sql(f"select sum(b) as x from ext where a < {v}")
        assert out.to_pandas().x[0] == want
    assert _refused(s, "external") == 3
    assert _counter(s, "template_binds") == 0 and not _templates(s)


def test_refused_user_params():
    s = _mem_session()
    text = "select sum(b) as x from t where a < {}"
    for v in (5, 6, 7):               # armed: a bare send would now hit
        s.sql(text.format(v))
    assert paramplan.template_bind(s, text.format(8)) is not None
    before = _counter(s, "template_binds")
    out = s.sql(text.format(9), tenant=1)
    assert out.to_pandas().x[0] == sum(i * 10 for i in range(9))
    assert _refused(s, "user_params") == 1
    assert _counter(s, "template_binds") == before


def test_refused_replay_mismatch(monkeypatch):
    """A derivation that does not give the build's own bindings back is
    no template."""
    from cloudberry_tpu.plan import binder

    s = _mem_session()
    monkeypatch.setitem(binder._REPLAY, "neg", lambda e: e)
    text = "select sum(b) as x from t where a > {}"
    for v in (-5, -6, -7):
        assert s.sql(text.format(v)).to_pandas().x[0] == 199 * 100 * 10
    assert _refused(s, "replay_mismatch") == 1
    assert _counter(s, "template_binds") == 0 and not _templates(s)


def test_refused_arm_mismatch(monkeypatch):
    """A derivation the build's text cannot tell from the right one is
    caught by the first full-path send with another text."""
    from cloudberry_tpu.plan import binder

    s = _mem_session()
    real = binder._literal_cast

    def wrong(e, t):                  # right on 0.5 alone
        out = real(e, t)
        return out if e.value == 5 else real(binder.ex.Literal(
            e.value + 1, e.dtype), t)

    text = "select count(*) as x from t where d < {}"
    s.sql(text.format("0.5"))
    assert len(_templates(s)) == 1
    monkeypatch.setitem(binder._REPLAY, "cast", wrong)
    assert s.sql(text.format("1.5")).to_pandas().x[0] == 1
    assert _refused(s, "arm_mismatch") == 1 and not _templates(s)
    assert s.sql(text.format("2.5")).to_pandas().x[0] == 2
    assert _counter(s, "template_binds") == 0


def test_another_token_type_takes_the_full_path():
    s = _mem_session()
    text = "select count(*) as x from t where d < {}"
    for v in ("0.5", "1.5", "2.5"):
        s.sql(text.format(v))
    assert paramplan.template_bind(s, text.format("4.5")) is not None
    # 3.25 is another decimal scale, 3 an integer: the plan around the
    # literal may differ, so the template says nothing of them
    for v, want in (("3.25", 3), ("3", 3), ("3.75", 4)):
        assert paramplan.template_bind(s, text.format(v)) is None
        assert s.sql(text.format(v)).to_pandas().x[0] == want
    # a text that is no literal at all fails as it always did
    with pytest.raises(ValueError):
        s.sql("select count(*) as x from t where a < 1 "
              "and d < date '1995-02-30'")


# --------------------------------------------------------- (c) staleness


def _armed(s, text: str, values=(5, 6, 7)) -> None:
    for v in values:
        s.sql(text.format(v))
    assert paramplan.template_bind(s, text.format(values[0])) is not None


def _bump_feedback(s) -> None:
    from cloudberry_tpu.plan import feedback as FB

    FB.store_for(s).gen += 1


def _reregister_udf(s) -> None:
    from cloudberry_tpu import types as T
    from cloudberry_tpu.exec import udf

    udf.register_function("lt_twice", lambda x: 2 * x, [T.INT64], T.INT64)


STALE = [
    ("append", lambda s: s.sql("insert into t values (1, 1000, 'x', 0.5)"),
     1000),
    ("ddl", lambda s: s.sql("create table fresh (a bigint)"), 0),
    ("udf", _reregister_udf, 0),
    ("feedback", _bump_feedback, 0),
]


@pytest.mark.parametrize("what,change,more", STALE,
                         ids=[c[0] for c in STALE])
def test_a_stale_template_takes_the_full_path(what, change, more,
                                              monkeypatch):
    s = _mem_session()
    text = "select sum(b) as x from t where a < {}"
    _armed(s, text)
    change(s)
    binds, falls = _counter(s, "template_binds"), \
        _counter(s, "template_fallbacks")
    parsed = []
    from cloudberry_tpu.sql import parser

    real = parser.parse_sql
    monkeypatch.setattr(parser, "parse_sql",
                        lambda q: parsed.append(q) or real(q))
    assert s.sql(text.format(9)).to_pandas().x[0] == \
        sum(i * 10 for i in range(9)) + more
    assert parsed == [text.format(9)]
    assert _counter(s, "template_binds") == binds
    assert _counter(s, "template_fallbacks") == falls + 1
    # and the full path leaves a template behind that serves again
    for v in (10, 11, 12):
        s.sql(text.format(v))
    assert _counter(s, "template_binds") > binds


def test_a_config_swap_takes_the_full_path():
    s = _mem_session()
    text = "select sum(b) as x from t where a < {}"
    _armed(s, text)
    s.config = s.config.with_overrides(
        **{"interconnect.packed_wire": False})
    binds = _counter(s, "template_binds")
    assert paramplan.template_bind(s, text.format(9)) is None
    assert s.sql(text.format(9)).to_pandas().x[0] == \
        sum(i * 10 for i in range(9))
    assert _counter(s, "template_binds") == binds


def test_a_view_redefined_under_a_shared_scope(tmp_path):
    """Sessions over one store share generic plans, and the plan epoch of
    a shared scope holds no DDL counter: the template holds each catalog
    to the DDL version a full-path send last matched under."""
    cfg = Config(n_segments=1).with_overrides(**{
        "storage.root": str(tmp_path / "store")})
    s = cb.Session(cfg)
    s.sql("create table t (a bigint, b bigint) distributed by (a)")
    s.sql("insert into t values " + ",".join(
        f"({i}, {i * 10})" for i in range(100)))
    s.sql("create view v as select a, b from t")
    text = "select sum(b) as x from v where a < {}"
    _armed(s, text, (60, 70, 80))
    s.sql("drop view v")
    s.sql("create view v as select a, b + b as b from t")
    assert s.sql(text.format(90)).to_pandas().x[0] == \
        2 * sum(i * 10 for i in range(90))


def test_a_backend_without_views_joins_without_a_full_path_send(tmp_path):
    """Catalogs without views read a text alike once its tables stand at
    the guarded versions: a new connection's first send hits. A catalog
    that holds a view is held to its own last full-path match."""
    cfg = Config(n_segments=1).with_overrides(**{
        "storage.root": str(tmp_path / "store")})
    s = cb.Session(cfg)
    s.sql("create table t (a bigint, b bigint) distributed by (a)")
    s.sql("insert into t values " + ",".join(
        f"({i}, {i * 10})" for i in range(100)))
    text = "select sum(b) as x from t where a < {}"
    warm = cb.Session(cfg)            # the same Config object, as a server's
    _armed(warm, text, (60, 70, 80))
    # the loader holds the table in RAM, where the planner scans it
    # otherwise than a backend that finds it cold: not its template
    assert paramplan.template_bind(s, text.format(55)) is None
    other = cb.Session(cfg)
    assert other.sql(text.format(50)).to_pandas().x[0] == \
        sum(i * 10 for i in range(50))
    assert _counter(other, "template_binds") == 1
    viewed = cb.Session(cfg)
    viewed.sql("create view w as select a from t")
    assert paramplan.template_bind(viewed, text.format(40)) is None
    assert viewed.sql(text.format(40)).to_pandas().x[0] == \
        sum(i * 10 for i in range(40))    # the full path: it joins
    assert paramplan.template_bind(viewed, text.format(30)) is not None
    # and the template has now seen a catalog with views: a newcomer joins
    # by a full-path send of its own
    late = cb.Session(cfg)
    assert paramplan.template_bind(late, text.format(20)) is None


# ------------------------------------------- (d) a literal that prunes


def test_other_partitions_fall_back(tmp_path):
    cfg = Config(n_segments=1).with_overrides(**{
        "storage.root": str(tmp_path / "store"),
        "storage.rows_per_partition": 50})
    s = cb.Session(cfg)
    s.sql("create table t (a bigint, b bigint) distributed by (a)")
    s.sql("insert into t values " + ",".join(
        f"({i}, {i * 10})" for i in range(200)))    # four partitions by a
    s = cb.Session(cfg)
    text = "select sum(b) as x from t where a >= {}"
    want = lambda v: sum(i * 10 for i in range(v, 200))
    for v in (150, 160, 170):         # the last partition alone
        assert s.sql(text.format(v)).to_pandas().x[0] == want(v)
    binds = _counter(s, "template_binds")
    assert s.sql(text.format(180)).to_pandas().x[0] == want(180)
    assert _counter(s, "template_binds") == binds + 1
    falls = _counter(s, "template_fallbacks")
    builds = _counter(s, "generic_builds")
    assert s.sql(text.format(120)).to_pandas().x[0] == want(120)
    assert _counter(s, "template_binds") == binds + 1
    assert _counter(s, "template_fallbacks") == falls + 1
    assert _counter(s, "generic_builds") == builds + 1  # two partitions
    # both variants keep their templates: each speaks for its partitions
    for v in (130, 140):
        assert s.sql(text.format(v)).to_pandas().x[0] == want(v)
    binds = _counter(s, "template_binds")
    for v in (110, 190):
        assert s.sql(text.format(v)).to_pandas().x[0] == want(v)
    assert _counter(s, "template_binds") == binds + 2


# ------------------------------------ (e) dispatch, seams, cancel check


@pytest.fixture
def armed():
    s = _mem_session()
    text = "select sum(b) as x from t where a < {}"
    _armed(s, text)
    FI.reset_fault()
    yield s, text
    FI.reset_fault()


def test_a_hit_is_a_dispatch(armed):
    s, text = armed
    before = {c: _counter(s, c) for c in
              ("dispatches", "param_binds", "generic_hits",
               "template_binds", "stmt_cache_hits", "compiles")}
    cached = len(s._stmt_cache)
    s.sql(text.format(11))
    after = {c: _counter(s, c) for c in before}
    assert after == {**before,
                     "dispatches": before["dispatches"] + 1,
                     "param_binds": before["param_binds"] + 1,
                     "generic_hits": before["generic_hits"] + 1,
                     "template_binds": before["template_binds"] + 1}
    assert len(s._stmt_cache) == cached   # 141 texts would only churn it


def test_a_hit_passes_the_fault_seams(armed):
    s, text = armed
    FI.inject_fault("dispatch_start", "error")
    with pytest.raises(FI.InjectedFault):
        s.sql(text.format(12))
    FI.reset_fault()
    assert s.sql(text.format(12)).to_pandas().x[0] == \
        sum(i * 10 for i in range(12))


def test_a_hit_honours_the_cancel_check(armed):
    s, text = armed
    binds = _counter(s, "template_binds")
    launched = _counter(s, "param_binds")
    with pytest.raises(lifecycle.StatementTimeout):
        s.sql(text.format(13), _deadline=time.monotonic() - 1.0)
    assert _counter(s, "template_binds") == binds + 1  # bound, not launched
    assert _counter(s, "param_binds") == launched


def test_a_hit_takes_the_admission_slot_of_the_full_path(armed):
    s, text = armed
    costs = []
    real = s._slot
    s._slot = lambda cost: costs.append(cost) or real(cost)
    s.sql(text.format(14))            # a hit
    for gp in [g for b in s._generic_cache.values() for g in b]:
        gp.template = None            # the same text's kind, the full path
    s.sql(text.format(15))
    assert len(costs) == 2 and costs[0] == costs[1] > 0


def test_a_hit_carries_the_span(armed):
    s, text = armed
    s.sql(text.format(16))
    spans = s.stmt_log.traces(1)[0]["events"]
    names = [sp["name"] for sp in spans]
    assert "parse" not in names and "plan" not in names
    bind = next(sp for sp in spans if sp["name"] == "bind")
    assert bind["args"]["lookup"] and bind["args"]["template"]
    assert {"admit", "queue-wait", "launch"} <= set(names)


# ----------------------------------------------- (f) no parse, no plan


def test_a_hit_calls_neither_parser_nor_planner(armed, monkeypatch):
    from cloudberry_tpu.plan import planner
    from cloudberry_tpu.sql import parser

    s, text = armed

    def never(*a, **kw):
        raise AssertionError("a template hit went the full path")

    monkeypatch.setattr(parser, "parse_sql", never)
    monkeypatch.setattr(planner, "plan_statement", never)
    for v in (20, 21, 22):
        assert s.sql(text.format(v)).to_pandas().x[0] == \
            sum(i * 10 for i in range(v))
    with pytest.raises(AssertionError):
        s.sql("select sum(b) as x from t where b < 3")    # a new skeleton


def test_generic_plans_off_is_templates_off():
    s = cb.Session(Config(n_segments=1).with_overrides(
        **{"sched.generic_plans": False}))
    s.sql("create table t (a bigint, b bigint) distributed by (a)")
    s.sql("insert into t values (1, 10), (2, 20), (3, 30)")
    for v, want in ((2, 10), (3, 30), (4, 60)):
        assert s.sql(f"select sum(b) as x from t where a < {v}"
                     ).to_pandas().x[0] == want
    assert not s._generic_cache and _counter(s, "template_binds") == 0
