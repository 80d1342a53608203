"""planck — the plan-IR verifier (plan/verify.py), pinned three ways.

1. Seeded plan-mutation fuzzing: every corruption class in
   plan/mutate.py (drop a motion, wrong hash cols, lie about a rung,
   desync a param slot, ...) must be CAUGHT with a node-path finding
   carrying the expected rule — and the uncorrupted plan must verify
   clean first, so a finding is attributable to the mutation alone.
2. The ``config.debug.verify_plans`` session gate: clean statements
   run bit-identically with the gate on; a corrupted plan raises
   PlanVerifyError instead of compiling.
3. Contract surfaces: $params slot consistency against the paramplan
   signature, EXPLAIN's ``dist:`` derived-distribution annotation, the
   recovery-mode re-placement registry, and the rule-table coverage
   counters the bench's ``planverify`` record rides.
"""

import numpy as np
import pandas as pd
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import Config
from cloudberry_tpu.plan import nodes as N
from cloudberry_tpu.plan.mutate import MUTATIONS
from cloudberry_tpu.plan.planner import plan_statement
from cloudberry_tpu.plan.verify import (PlanVerifyError, Verifier,
                                        check_plan, verify_plan,
                                        verify_stats)
from cloudberry_tpu.sql.parser import parse_sql
from tools.tpch_queries import QUERIES
from tools.tpchgen import load_tpch


@pytest.fixture(scope="module")
def dist_session():
    s = cb.Session(Config(n_segments=8))
    load_tpch(s, sf=0.01, seed=7)
    return s


@pytest.fixture(scope="module")
def single_session():
    s = cb.Session()
    load_tpch(s, sf=0.01, seed=7)
    return s


def _plan(session, sql):
    return plan_statement(parse_sql(sql), session, {}).plan


# ------------------------------------------------- clean-plan baseline


@pytest.mark.parametrize("qname", ["q1", "q3", "q5", "q9", "q18"])
def test_tpch_plans_verify_clean(dist_session, single_session, qname):
    for s in (dist_session, single_session):
        findings = verify_plan(_plan(s, QUERIES[qname]), s)
        assert findings == [], [f.render() for f in findings]


def test_rule_table_covers_walked_nodes(dist_session):
    """Every node class the TPC-H corpus exercises hits a rule row —
    the coverage counters the bench planverify record reports."""
    stats = verify_stats(_plan(dist_session, QUERIES["q3"]),
                         dist_session)
    assert stats["findings"] == []
    assert stats["nodes"] > 10
    for want in ("PScan", "PJoin", "PMotion", "PAgg", "PSort",
                 "PLimit", "PFilter", "PProject"):
        assert want in stats["rules_hit"], stats["rules_hit"]


# ------------------------------------------- seeded mutation fuzzing


@pytest.mark.parametrize("mutation", sorted(MUTATIONS))
def test_mutation_caught(dist_session, mutation):
    sql, fn, expected = MUTATIONS[mutation]
    plan = _plan(dist_session, sql)
    pre = verify_plan(plan, dist_session)
    assert pre == [], (
        f"fixture query dirty before mutation: "
        f"{[f.render() for f in pre]}")
    out = fn(plan, dist_session)
    assert out is not None, (
        f"mutation {mutation!r} found no target in its fixture plan — "
        "the corpus went stale; update its SQL in plan/mutate.py")
    mutated, desc = out
    findings = verify_plan(mutated, dist_session)
    hit = [f for f in findings if f.rule in expected]
    assert hit, (
        f"{mutation!r} ({desc}) not caught: expected one of "
        f"{sorted(expected)}, got "
        f"{[f.render() for f in findings] or 'CLEAN'}")
    # every finding is a node-path diagnostic, not a bare message: the
    # path anchors at a node label (class-cased) and renders as
    # "path: rule: message"
    for f in hit:
        assert f.path and f.path[0].isupper(), f.render()
        assert f.render().startswith(f"{f.path}: {f.rule}: ")


def test_mutation_corpus_size():
    """The acceptance floor: >= 15 distinct corruption classes."""
    assert len(MUTATIONS) >= 15


# --------------------------------------------------- the session gate


def test_gate_clean_statement_bit_identical():
    base = cb.Session(Config(n_segments=8))
    load_tpch(base, sf=0.01, seed=7)
    gated = cb.Session(Config(n_segments=8).with_overrides(
        **{"debug.verify_plans": True}))
    load_tpch(gated, sf=0.01, seed=7)
    for qname in ("q3", "q6"):
        a = base.sql(QUERIES[qname]).to_pandas()
        b = gated.sql(QUERIES[qname]).to_pandas()
        pd.testing.assert_frame_equal(a, b)


def test_gate_raises_on_corrupt_plan(dist_session):
    sql, fn, expected = MUTATIONS["drop-motion-under-join"]
    plan = _plan(dist_session, sql)
    mutated, _ = fn(plan, dist_session)
    with pytest.raises(PlanVerifyError) as ei:
        check_plan(mutated, dist_session, "test")
    assert any(f.rule in expected for f in ei.value.findings)
    # the error text carries the node path (file:node-path diagnostic)
    assert "Join" in str(ei.value)


def test_gate_on_in_golden_sessions():
    from tools.golden_plans import _config

    assert _config(8).debug.verify_plans
    assert _config(1).debug.verify_plans


# ------------------------------------------------ paramplan slot gate


def test_param_slots_verify_against_signature(dist_session):
    from cloudberry_tpu.sched import paramplan

    plan = _plan(dist_session,
                 "select l_orderkey from lineitem where l_quantity > 17")
    sig, bindings, keyed, slots = paramplan.analyze(
        dist_session, plan, rewrite=True)
    assert slots, "expected a parameterized literal"
    assert verify_plan(plan, dist_session,
                       declared_slots=list(slots)) == []
    # declared signature shorter than the plan's slots: desync
    bad = verify_plan(plan, dist_session, declared_slots=[])
    assert any(f.rule == "param-slot-desync" for f in bad)
    # declared dtype disagrees with the plan's Param dtype: desync
    from cloudberry_tpu.types import BOOL

    bad = verify_plan(plan, dist_session,
                      declared_slots=[BOOL] * len(slots))
    assert any(f.rule == "param-slot-desync" for f in bad)


def test_nrw_slots_verify_against_signature(dist_session):
    from cloudberry_tpu.plan import nodes as N
    from cloudberry_tpu.sched import paramplan

    plan = _plan(dist_session,
                 "select count(*) as n from lineitem, orders "
                 "where l_orderkey = o_orderkey")
    sig, bindings, keyed, slots = paramplan.analyze(
        dist_session, plan, rewrite=True)
    nrw = sum(1 for k in bindings if k.startswith("$nrw"))
    assert nrw >= 2, bindings.keys()
    assert verify_plan(plan, dist_session, declared_slots=list(slots),
                       declared_nrw=nrw) == []
    # signature count desync
    bad = verify_plan(plan, dist_session, declared_nrw=nrw + 1)
    assert any(f.rule == "param-slot-desync" and "$nrw" in f.message
               for f in bad)
    # duplicate stamp: two scans feeding off one row-count input
    scans = [n for n, _ in
             __import__("cloudberry_tpu.plan.verify",
                        fromlist=["_walk_paths"])._walk_paths(plan)
             if isinstance(n, N.PScan)
             and getattr(n, "_nrows_key", None)]
    scans[1]._nrows_key = scans[0]._nrows_key
    bad = verify_plan(plan, dist_session, declared_nrw=nrw)
    assert any(f.rule == "param-slot-desync" and "stamped on" in
               f.message for f in bad)


def test_generic_plan_build_runs_gate():
    """The GenericPlan constructor verifies the rewritten ($params)
    form when the gate is on — and the statement still executes."""
    s = cb.Session(Config(n_segments=1).with_overrides(
        **{"debug.verify_plans": True}))
    load_tpch(s, sf=0.01, seed=7)
    q = "select count(*) as n from lineitem where l_quantity > 17"
    a = s.sql(q).to_pandas()
    b = s.sql(q.replace("17", "18")).to_pandas()  # rebind, same skeleton
    assert int(a["n"][0]) > int(b["n"][0]) > 0


# ------------------------------------------------- explain annotation


def test_explain_dist_annotation(dist_session, single_session):
    txt = dist_session.explain(QUERIES["q3"])
    assert "dist:hashed(" in txt
    assert "dist:singleton" in txt
    assert "dist:replicated" in txt
    # every node line carries the derived annotation at nseg > 1
    for line in txt.splitlines():
        if "-> " in line:
            assert "dist:" in line, line
    # single-segment plans have no distribution to derive
    assert "dist:" not in single_session.explain(QUERIES["q3"])


def test_explain_dist_matches_stamp(dist_session):
    """In a clean plan the derived annotation agrees with the stamped
    locus — the bracketed and dist: values are independent
    computations of the same property."""
    txt = dist_session.explain(QUERIES["q10"])
    for line in txt.splitlines():
        if "[" in line and "dist:" in line:
            head = line.split("dist:", 1)[0]
            stamped = head.rsplit("[", 1)[1].split("]", 1)[0]
            derived = line.split("dist:", 1)[1].strip()
            assert stamped == derived, line


# ------------------------------------------------- contract registries


def test_recovery_mode_drift_is_a_finding(dist_session, monkeypatch):
    import cloudberry_tpu.exec.recovery as R

    monkeypatch.setattr(
        R, "REPLACEABLE",
        {k: v for k, v in R.REPLACEABLE.items() if k != "topn"})
    findings = verify_plan(_plan(dist_session, QUERIES["q6"]),
                           dist_session)
    assert any(f.rule == "recovery-mode-unreplaceable"
               for f in findings)


def test_unruled_node_class_is_a_finding(dist_session):
    from cloudberry_tpu.plan import nodes as N

    class PRogue(N.PlanNode):
        pass

    rogue = PRogue()
    rogue.fields = []
    plan = _plan(dist_session, QUERIES["q6"])
    # graft the rogue node over the root: walking it must report the
    # missing rule row instead of crashing or silently passing
    rogue.children = lambda: [plan]
    findings = verify_plan(rogue, dist_session)
    assert any(f.rule == "planprops-unruled" for f in findings)


# ----------------------------------------------------- corpus helper


def test_verify_corpus_smoke(monkeypatch):
    """The lint_gate --plans / bench planverify entry point, on a
    TPC-H-only corpus (the full TPC-DS sweep rides the golden tests)."""
    import tools.golden_plans as G

    monkeypatch.setattr(
        G, "corpus",
        lambda: [("tpch", G.make_session,
                  {"q3": QUERIES["q3"], "q6": QUERIES["q6"]})])
    rec = G.verify_corpus(nsegs=(8,))
    assert rec["plans"] == 2
    assert rec["findings"] == []
    assert rec["nodes"] > 10 and rec["wall_s"] > 0
    assert "PMotion" in rec["rules_hit"]


def test_verifier_local_mode_skips_distribution(single_session):
    """Single-segment plans have no sharding stamps; the verifier
    still runs every lowering-contract check."""
    plan = _plan(single_session, QUERIES["q1"])
    v = Verifier(single_session, plan)
    assert v.local
    assert v.verify(plan) == []
    # a local-mode contract still fires: scan row overflow
    from cloudberry_tpu.plan import nodes as N

    def scans(p):
        if isinstance(p, N.PScan):
            yield p
        for c in p.children():
            yield from scans(c)
    sc = next(scans(plan))
    sc.num_rows = sc.capacity + 1
    findings = verify_plan(plan, single_session)
    assert any(f.rule == "scan-rows" for f in findings)


# ---------------------------------------- join capacities (plan/joincap.py)


def _nodes(plan, cls):
    from cloudberry_tpu.exec.executor import all_nodes

    return [n for n in all_nodes(plan) if isinstance(n, cls)]


def _stamped(session, sql):
    """The plan with the capacities a send of it runs at: the session
    stamps them where the statement's retry loop starts."""
    from cloudberry_tpu.plan import joincap

    plan = _plan(session, sql)
    joincap.stamp_join_capacities(plan, session.catalog)
    return plan


def _over_the_probe(plan):
    j = next(n for n in _nodes(plan, N.PJoin) if n.probe_capacity)
    j.probe_capacity = N.capacity_of(j.probe) + 64


def _over_the_search(plan):
    j = next(n for n in _nodes(plan, N.PJoin) if n.probe_capacity)
    j.out_capacity = j.probe_capacity + 64


def _on_an_outer_join(plan):
    j = next(n for n in _nodes(plan, N.PJoin) if n.probe_capacity)
    j.kind = "left"


def _agg_above_its_child(plan):
    a = next(n for n in _nodes(plan, N.PAgg)
             if n.group_keys and isinstance(n.child, N.PJoin)
             and n.child.out_capacity)
    a.capacity = N.capacity_of(a.child) + 64


@pytest.mark.parametrize("qname, corrupt, rule", [
    ("q12", _over_the_probe, "join-capacity"),
    ("q12", _over_the_search, "join-capacity"),
    ("q12", _on_an_outer_join, "join-capacity"),
    ("q11", _agg_above_its_child, "agg-capacity"),
], ids=["probe_above_its_input", "out_above_the_search",
        "outer_join_compacted", "agg_above_compacted_child"])
def test_a_join_capacity_out_of_place_is_a_finding(single_session, qname,
                                                   corrupt, rule):
    """A lookup join's own capacities (ISSUE 33) hold only below the
    capacity their rows arrive at and on joins that drop unmatched rows;
    the aggregate above follows its compacted child. The planner's own
    stamps verify clean first."""
    plan = _stamped(single_session, QUERIES[qname])
    assert any(n.probe_capacity or (n.compacts and n.out_capacity)
               for n in _nodes(plan, N.PJoin)), plan.explain()
    assert verify_plan(plan, single_session) == []
    corrupt(plan)
    findings = verify_plan(plan, single_session)
    assert rule in {f.rule for f in findings}, \
        [f.render() for f in findings]


@pytest.mark.parametrize("qname", ["q13", "q18"])
def test_an_aggregate_at_its_proven_ceiling_verifies(single_session, qname):
    """ISSUE 34: an aggregate held under its child's capacity by a proof
    (Q13's ``GROUP BY c_custkey`` at customer's rows, Q18's ``GROUP BY
    l_orderkey`` at the key's span) verifies clean; one above its
    compacted child's capacity stays PR 33's finding (the ``q11`` case
    above, whose plan carries its ceilings too)."""
    plan = _stamped(single_session, QUERIES[qname])
    held = [a for a in _nodes(plan, N.PAgg)
            if a.group_keys and a.capacity < N.capacity_of(a.child)]
    assert held and all(a.capacity >= a._cap_ceiling for a in held), \
        plan.explain()
    assert verify_plan(plan, single_session) == []


def test_every_capacity_walk_follows_the_joins_own(single_session):
    """One derivation: the binder's, the memory estimate's, the tiled
    planner's and the verifier's row rule give a compacted join the
    capacity its lowering emits."""
    from cloudberry_tpu.exec import tiled_plan
    from cloudberry_tpu.plan import binder

    plan = _stamped(single_session, QUERIES["q12"])
    j = next(n for n in _nodes(plan, N.PJoin) if n.probe_capacity)
    want = j.probe_capacity
    assert 0 < want < N.capacity_of(j.probe)
    assert N.capacity_of(j) == binder._plan_capacity(j) \
        == tiled_plan._out_cap(j) == want
    from cloudberry_tpu.plan.verify import _join_rows

    assert _join_rows(j, N.capacity_of(j.build),
                      N.capacity_of(j.probe)) == want
