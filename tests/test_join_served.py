"""The one-segment join path served (ISSUE 32): TPC-H SF0.01 written
through the store by the benchmark's loader, an in-process
``serve.Server`` at one segment whose backend plans each statement from
a COLD store, one TCP client. The benchmark's plain references
(``benchmarks/reference/q3.py``, ``q12.py``, ``q13.py``) hold the
answers: rows and order exactly. Q3 and Q12 are sorted-build lookups,
Q13's outer join on ``o_custkey`` an expansion; the launch counts the
rows its scans hold and the rows they are padded to. ISSUE 34: Q18
(``reference/q18.py``; three lookups, one of them a semi-join whose probe
is sized by key containment) compiles ONE program cold, and Q13's
aggregates emit under the capacity their rows arrive at. ISSUE 37: a
lookup whose build keys have a proven small span finds its build row in
a direct-address table (``PJoin.direct_lookup``): every join kind equals
the sorted search row for row, a broken proof is an error, and a span
past the rule keeps the search."""

from __future__ import annotations

import os
import sys

import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import Config
from cloudberry_tpu.exec.kernels import row_rung_up
from cloudberry_tpu.serve.client import Client
from cloudberry_tpu.serve.server import Server

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import cell as C, compare, load       # noqa: E402

CELL = "tpch-sf1-joins.join-streams"


EXTRA = ("q13", "q18")      # served here, in no mix of this cell's


def _statement(cell, stmt: str) -> tuple:
    """(text, reference) of a statement: the cell's own, or, for Q13 and
    Q18 (another configuration's), the benchmark's files."""
    if stmt in cell.statements:
        return cell.statements[stmt]
    with open(os.path.join(C.BENCH, "statements", stmt + ".sql"),
              encoding="utf-8") as f:
        return f.read(), C.load_module("reference", stmt)


def _columns(cell) -> dict:
    """What the loader keeps for the references: the cell's, Q13's and
    Q18's."""
    keep = cell.reference_columns()
    for stmt in EXTRA:
        for table, cols in C.load_module("reference", stmt).COLUMNS.items():
            keep.setdefault(table, set()).update(cols)
    return keep
SEED, SCALE = 2147486231, 0.01
DRAWS = {"q3": {"segment": 1, "day": 15},
         "q12": {"shipmode1": 5, "shipmode2": 3, "year": 1994},
         "q13": {"word1": 0, "word2": 1},
         # (300, the validation value, keeps one order in a hundred
         # thousand: none at this scale)
         "q18": {"quantity": 250}}
# lookups, expansions, lookups at a capacity of their own (ISSUE 33: Q3's
# two joins match few of their probe rows, Q12's filter keeps few; Q18's
# semi-join keeps the lines of a few large orders), lookups through a
# direct-address table (ISSUE 37: the generator's keys are dense)
JOINS = {"q3": (2, 0, 2, 2), "q12": (1, 0, 1, 1), "q13": (0, 1, 0, 0),
         "q18": (3, 0, 1, 3)}
# the tables a statement scans, one entry a scan
SCANS = {"q18": ("customer", "orders", "lineitem", "lineitem")}
COUNTERS = ("launch_joins_lookup", "launch_joins_expand",
            "launch_joins_compacted", "launch_joins_direct",
            "join_compact_retries", "scan_rows",
            "scan_capacity_rows", "launch_packed", "compiles",
            "launch_agg_rows_in", "launch_agg_capacity")


def _config(root: str):
    return Config(n_segments=1).with_overrides(**{
        "storage.root": root, "storage.rows_per_partition": 1 << 20})


def _text(cell, stmt: str) -> str:
    text, ref = _statement(cell, stmt)
    return text.format(**ref.bind(DRAWS[stmt]))


@pytest.fixture(scope="module")
def loaded(tmp_path_factory):
    """(cell, the store's root, rows, the generator's arrays)."""
    cell = C.Cell(CELL)
    root = str(tmp_path_factory.mktemp("store"))
    rows, truth = load.load(cb.Session(_config(root)), cell.tables(),
                            _columns(cell), SCALE, SEED, 2500)
    return cell, root, rows, truth


def _send_twice(root: str, cell, stmts) -> dict:
    """{statement: (the second send's wire answer, counters it added,
    counters both sends added, the first send's answer)} from a server
    of its own over the store, each statement sent twice over TCP."""
    out = {}
    with Server(config=_config(root)) as srv:
        log = srv.session.stmt_log
        c = Client(srv.host, srv.port, timeout=300.0)
        try:
            for stmt in stmts:
                start = {n: log.counter(n) for n in COUNTERS}
                first = c.sql(_text(cell, stmt))
                before = {n: log.counter(n) for n in COUNTERS}
                got = c.sql(_text(cell, stmt))
                out[stmt] = (got,
                             {n: log.counter(n) - before[n]
                              for n in COUNTERS},
                             {n: log.counter(n) - start[n]
                              for n in COUNTERS}, first)
        finally:
            c.close()
    return out


@pytest.fixture(scope="module")
def served(loaded):
    """(cell, rows, the generator's arrays, what ``_send_twice`` gives
    for every statement)."""
    cell, root, rows, truth = loaded
    return cell, rows, truth, _send_twice(root, cell, sorted(DRAWS))


@pytest.mark.parametrize("stmt", sorted(DRAWS))
def test_served_answer_equals_the_plain_reference(served, stmt):
    cell, _, truth, out = served
    ref = _statement(cell, stmt)[1].answer(truth, DRAWS[stmt])
    assert len(ref["rows"]) >= (10 if stmt in ("q3", "q18") else 2)
    wrong, ulps = compare.gap(out[stmt][0], ref)
    assert wrong == 0, (out[stmt][0]["rows"][:3], ref["rows"][:3])
    assert max(ulps.values(), default=0.0) <= \
        cell.config["limits"]["sum_gap_ulps"]


def test_q13_counts_the_customers_without_orders(served):
    """The outer join's null extension: a third of the customers place
    no order (spec 4.2.3), and ``count(o_orderkey)`` counts none for
    them; a padded customer row would be one more."""
    _, rows, _, out = served
    zero = [r for r in out["q13"][0]["rows"] if r[0] == 0]
    assert len(zero) == 1 and zero[0][1] >= rows["customer"] // 3
    assert sum(r[1] for r in out["q13"][0]["rows"]) == rows["customer"]


@pytest.mark.parametrize("stmt", sorted(DRAWS))
def test_a_repeat_send_launches_the_joins_and_compiles_nothing(served,
                                                               stmt):
    _, _, _, out = served
    added = out[stmt][1]
    assert (added["launch_joins_lookup"], added["launch_joins_expand"],
            added["launch_joins_compacted"],
            added["launch_joins_direct"]) == JOINS[stmt]
    assert added["launch_packed"] == 1 and added["compiles"] == 0


def test_the_pair_runs_three_joins_at_capacities_of_their_own(served):
    """Q3 and Q12 as the cell sends them: three lookup joins a pair, each
    at a capacity the planner stamped, and the estimates' slack holds
    the rows that came: no send was retried."""
    _, _, _, out = served
    assert sum(out[s][1]["launch_joins_compacted"]
               for s in ("q3", "q12")) == 3
    assert all(out[s][2]["join_compact_retries"] == 0 for s in out)


def test_q18_compiles_one_program_cold(served):
    """Q18's semi-join probes with lineitem JOIN orders JOIN customer,
    which every line survives: sized by the planner's min(build, probe)
    it overflowed a stamped ``[probe n]`` on every cold backend, which
    then compiled a second program (ISSUE 34). Sized as a build is, by
    key containment, the first send compiles one and retries nothing."""
    _, _, _, out = served
    both = out["q18"][2]
    assert both["join_compact_retries"] == 0 and both["compiles"] == 1
    assert both["launch_packed"] == 2


def test_q13s_aggregates_emit_under_the_expansions_capacity(served):
    """One expansion a launch, and both grouped aggregates at customer's
    rows (``plan/joincap.py``, the proven ceilings): the first one's
    rows arrive at the pair buffer's capacity, many times that."""
    _, rows, _, out = served
    added = out["q13"][1]
    assert added["launch_joins_expand"] == 1
    assert added["launch_agg_capacity"] == 2 * row_rung_up(rows["customer"])
    assert added["launch_agg_rows_in"] > 4 * added["launch_agg_capacity"]


def test_an_estimate_too_small_is_retried_to_the_exact_answer(
        loaded, monkeypatch):
    """No slack on the estimates: Q3's customer join is stamped for the
    729 orders the planner expects and 1,340 match. The overflow is a
    check, the join runs again at the next capacity (once), the repeat
    send meets the grown plan, and both answers are the reference's."""
    from cloudberry_tpu.plan import joincap

    monkeypatch.setattr(joincap, "SLACK", 1)
    monkeypatch.setattr(joincap, "FLOOR", 64)
    cell, root, _, truth = loaded
    got, second, both, first = _send_twice(root, cell, ["q3"])["q3"]
    ref = _statement(cell, "q3")[1].answer(truth, DRAWS["q3"])
    assert compare.gap(first, ref)[0] == 0
    assert compare.gap(got, ref)[0] == 0
    assert both["join_compact_retries"] == 1
    assert second["join_compact_retries"] == 0 and second["compiles"] == 0
    assert second["launch_joins_compacted"] == 2


@pytest.mark.parametrize("counted", [True, False])
def test_a_capacity_climbs_its_ladder_to_the_exact_answer(
        loaded, monkeypatch, counted):
    """A capacity of eight rows where some three thousand orders match. The
    overflow's check reports the rows that came and the join is grown to
    the power of two that holds them: one retry. With the count withheld
    it doubles, nine retries where the loop allows the other
    buffers six together: the ladder ends the retries (at the probe's
    capacity, by then without compaction), no budget cuts them short,
    and the count is the generator's every time."""
    import numpy as np

    from cloudberry_tpu.plan import joincap

    monkeypatch.setattr(joincap, "SHARE", 2)
    monkeypatch.setattr(joincap, "SLACK", 0)
    monkeypatch.setattr(joincap, "FLOOR", 1)
    if not counted:
        grow = joincap.grow
        monkeypatch.setattr(
            joincap, "grow",
            lambda plan, join, what, rows: grow(plan, join, what, 0))
    _, root, _, truth = loaded
    cu, od = truth["customer"], truth["orders"]
    building = cu["c_custkey"][cu["c_mktsegment"] == "BUILDING"]
    want = int(np.isin(od["o_custkey"], building).sum())
    assert want > 2048
    s = cb.Session(_config(root))
    q = ("select count(*) as n from orders, customer "
         "where o_custkey = c_custkey and c_mktsegment = 'BUILDING'")
    assert "Join inner [out 8]" in s.explain(q)   # the ladder's first
    got = s.sql(q).to_pandas()
    assert int(got["n"][0]) == want
    retries = s.stmt_log.counter("join_compact_retries")
    assert retries == (1 if counted else 9), retries
    assert int(s.sql(q).to_pandas()["n"][0]) == want
    assert s.stmt_log.counter("join_compact_retries") == retries


def test_explain_analyze_runs_at_the_capacities_and_has_no_retry(
        loaded, monkeypatch):
    """EXPLAIN ANALYZE times the program a send runs: the rows stand
    beside the capacities. It has no retry loop, so, as with an
    expansion's buffer, an overflow is its error, and that names the
    join and the rows that came."""
    import re

    from cloudberry_tpu.exec.executor import ExecError
    from cloudberry_tpu.plan import joincap

    cell, root, _, _ = loaded
    s = cb.Session(_config(root))
    text = s.explain_analyze(_text(cell, "q12"))
    cap, rows = map(int, re.search(
        r"Join inner \[probe (\d+)\]  rows=(\d+)", text).groups())
    assert 0 < rows <= cap < 15104, text
    monkeypatch.setattr(joincap, "SLACK", 0)
    monkeypatch.setattr(joincap, "FLOOR", 1)
    with pytest.raises(ExecError, match=rf"join probe compaction "
                       rf"overflow.*\[probe 8\]\): {rows} rows"):
        s.explain_analyze(_text(cell, "q12"))


@pytest.mark.parametrize("stmt", sorted(DRAWS))
def test_the_launch_counts_its_scans_rows_and_their_rungs(served, stmt):
    cell, rows, _, out = served
    added = out[stmt][1]
    tables = SCANS.get(stmt) or _statement(cell, stmt)[1].TABLES
    assert added["scan_rows"] == sum(rows[t] for t in tables)
    assert added["scan_capacity_rows"] == sum(row_rung_up(rows[t])
                                              for t in tables)
    assert added["scan_capacity_rows"] > added["scan_rows"]


def test_q3s_dates_plan_to_one_set_of_capacities(loaded):
    """The capacities follow from estimates, the estimates from the
    literals, and a capacity is a program to compile: on the coarse
    ladder (``joincap.LADDER``) every day of March 1995 that Q3's DATE
    draws (spec 2.4.3.3) plans both joins to the capacities of the
    validation value's plan."""
    import re

    cell, root, _, _ = loaded
    s = cb.Session(_config(root))
    text, ref = _statement(cell, "q3")
    seen = set()
    for day in range(1, 32):
        plan = s.explain(text.format(**ref.bind({"segment": 1,
                                                 "day": day})))
        seen.add(tuple(re.findall(r"Join inner \[out (\d+)\]", plan)))
    assert len(seen) == 1 and len(next(iter(seen))) == 2, seen


# ---------------------------------------------- direct-address lookups


def _lookup_session(stride: int = 1):
    """A session at one segment over tables in RAM: ``b`` keyed by 300
    dense keys 100..399 (times ``stride``), ``b2`` by the pairs of 100
    keys and 3 sub-keys, ``bn`` by every other key of ``b`` and one
    NULL, ``p`` 1,000 probe rows whose keys run past both ends of
    ``b``'s and hit both, some NULL."""
    import numpy as np

    s = cb.Session(Config(n_segments=1))
    for t, cols in (("b", "k bigint, v bigint"),
                    ("b2", "k bigint, k2 bigint, v bigint"),
                    ("bn", "k bigint, v bigint"),
                    ("p", "k bigint, k2 bigint, w bigint")):
        s.sql(f"create table {t} ({cols}) distributed by (v)"
              if t != "p" else f"create table {t} ({cols}) "
              "distributed by (w)")
    k = np.arange(100, 400, dtype=np.int64)
    s.catalog.table("b").set_data({"k": k * stride, "v": k * 2})
    s.catalog.table("b2").set_data({"k": 100 + np.arange(300) // 3,
                                    "k2": np.arange(300) % 3, "v": k})
    kn = k[::2].copy()
    s.catalog.table("bn").set_data({"k": kn * stride, "v": kn},
                                   validity={"k": kn != 110})
    rng = np.random.default_rng(37)
    pk = rng.integers(50, 450, 1000)
    pk[:2] = (100, 399)
    s.catalog.table("p").set_data(
        {"k": pk * stride, "k2": rng.integers(0, 3, 1000),
         "w": np.arange(1000)}, validity={"k": np.arange(1000) % 17 != 5})
    return s


LOOKUPS = {
    "inner": "select p.w, b.v from p join b on p.k = b.k",
    "left": "select p.w, b.v from p left join b on p.k = b.k",
    "semi": "select w from p where k in (select k from bn)",
    "anti": "select w from p where not exists "
            "(select 1 from bn where bn.k = p.k)",
    "null_aware_anti": "select w from p where k not in (select k from bn)",
    "not_in": "select w from p where k not in (select k from b)",
    "two_keys": "select p.w, b2.v from p join b2 "
                "on p.k = b2.k and p.k2 = b2.k2",
    "empty_build": "select p.w, b.v from p join b on p.k = b.k "
                   "where b.v < 0",
}


def _rows(s, sql) -> list:
    return sorted(map(tuple, s.sql(sql).to_pandas().astype(
        object).where(lambda f: f.notna(), None).values.tolist()),
        key=repr)


@pytest.mark.parametrize("kind", sorted(LOOKUPS))
def test_a_direct_lookup_equals_the_sorted_search(kind, monkeypatch):
    """Each join kind through a direct-address table and through the
    sorted search (``direct_lookup`` held off, as a span past the rule
    would): the same rows. NULL keys on either side match nothing, a
    NULL in a NOT IN's subquery empties it, probes outside the build's
    keys and at both ends of its span find what the search finds."""
    from cloudberry_tpu.plan import nodes as N

    direct = _lookup_session()
    got = _rows(direct, LOOKUPS[kind])
    assert direct.stmt_log.counter("launch_joins_direct") >= 1
    monkeypatch.setattr(N.PJoin, "direct_lookup",
                        property(lambda self: False))
    search = _lookup_session()
    assert got == _rows(search, LOOKUPS[kind])
    assert search.stmt_log.counter("launch_joins_direct") == 0
    if kind in ("null_aware_anti", "empty_build"):
        assert got == []
    else:
        assert len(got) > 50


def _direct_plan(s, sql):
    from cloudberry_tpu.exec.executor import all_nodes
    from cloudberry_tpu.plan import nodes as N
    from cloudberry_tpu.plan.planner import plan_statement
    from cloudberry_tpu.sql.parser import parse_sql

    plan = plan_statement(parse_sql(sql), s, {}).plan
    joins = [n for n in all_nodes(plan) if isinstance(n, N.PJoin)]
    return plan, joins


@pytest.mark.parametrize("fault", ["duplicate_keys", "key_past_the_span"])
def test_a_direct_table_that_cannot_hold_the_build_is_an_error(fault):
    """Planned while ``b.k`` was unique over 100..399, run after the data
    changed in place: a repeated key trips the lookup's duplicate check
    (the table read back at the keys), a key past the proven span the
    guard. Neither returns an answer."""
    import numpy as np

    from cloudberry_tpu.exec import executor as X

    s = _lookup_session()
    plan, joins = _direct_plan(s, LOOKUPS["inner"])
    assert [j.direct_lookup for j in joins] == [True]
    assert joins[0].direct_span == 300
    t = s.catalog.table("b")
    data = {c: np.asarray(v).copy() for c, v in t.data.items()}
    data["k"][1] = data["k"][0] if fault == "duplicate_keys" else 400
    t.set_data(data, t.dicts)
    want = X.DuplicateBuildKeyError if fault == "duplicate_keys" \
        else X.ExecError
    match = "duplicate keys" if fault == "duplicate_keys" \
        else "past its proven span 300"
    with pytest.raises(want, match=match):
        X.execute(plan, s)


@pytest.mark.parametrize("stride", [1, 1000])
def test_a_span_past_the_rule_keeps_the_search(stride):
    """Keys 1,000 apart span 299,001 values for 300 build rows and 1,000
    probe rows: the table would outgrow every array of the join, so the
    sorted search stays (its ``while``), ``launch_joins_direct`` stays 0
    and the answer is the same. Dense keys lower with no loop at all, and
    the plan's memory estimate holds their table."""
    import jax

    from cloudberry_tpu.exec import executor as X
    from cloudberry_tpu.exec.resource import estimate_plan_memory

    s = _lookup_session(stride)
    sql = "select count(*) as n, sum(b.v) as sv from p join b on p.k = b.k"
    plan, joins = _direct_plan(s, sql)
    assert [j.direct_lookup for j in joins] == [stride == 1]
    # admission counts the table's int32 words where it is built
    held = estimate_plan_memory(plan).peak_bytes
    span, joins[0].direct_span = joins[0].direct_span, 0
    assert held - estimate_plan_memory(plan).peak_bytes == \
        (4 * span if stride == 1 else 0)
    joins[0].direct_span = span
    exe = X.compile_plan(plan, s)
    text = jax.jit(exe.raw_fn).lower(X.prepare_inputs(exe, s)).as_text()
    assert ("while" in text) == (stride != 1)
    got = s.sql(sql).to_pandas()
    assert s.stmt_log.counter("launch_joins_direct") == (stride == 1)
    ref = _lookup_session(1).sql(sql).to_pandas()
    assert got.values.tolist() == ref.values.tolist()
    assert int(got["n"][0]) > 500
