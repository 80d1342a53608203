"""The served distributed path (ISSUE 28): TPC-H SF0.01 through the store,
an in-process ``serve.Server`` at one and at four segments, two TCP
clients. The benchmark's plain references (``benchmarks/reference/q3.py``,
``q15v.py``) hold both deployments: rows and order exactly, and the
four-segment answer is the one-segment answer. The distributed launch
records its five stages and four counters, and two backend sessions
launching four-device programs from two threads do not hang.

ISSUE 31: the loader's appends leave the keys' uniqueness in the
manifests, so Q3's two joins are lookups on a COLD table as on a loaded
one, the served Q3 counts two lookup joins and no expansion, and a
manifest that lies ends in ``DuplicateBuildKeyError``.
"""

from __future__ import annotations

import os
import sys
import threading

import numpy as np
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import Config
from cloudberry_tpu.serve.client import Client
from cloudberry_tpu.serve.server import Server

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import cell as C, compare, load       # noqa: E402

CELL = "tpch-sf1-4seg.motion"
SEED, SCALE = 2147486111, 0.01
DRAWS = {"q3": {"segment": 1, "day": 15}, "q15v": {"month": 36}}


def _config(root: str, nseg: int):
    return Config(n_segments=nseg).with_overrides(**{
        "storage.root": root, "storage.rows_per_partition": 1 << 20})


@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    """(cell, store root, the generator's arrays): the cell's three
    tables at SF0.01, written once through the store."""
    cell = C.Cell(CELL)
    root = str(tmp_path_factory.mktemp("store"))
    _, truth = load.load(cb.Session(_config(root, 1)), cell.tables(),
                         cell.reference_columns(), SCALE, SEED, 2500)
    return cell, root, truth


def _text(cell, stmt: str) -> str:
    text, ref = cell.statements[stmt]
    return text.format(**ref.bind(DRAWS[stmt]))


@pytest.fixture(scope="module")
def answers(deployment):
    """{(segments, statement): the wire answer}, each served over TCP."""
    cell, root, _ = deployment
    out = {}
    for nseg in (1, 4):
        with Server(config=_config(root, nseg)) as srv:
            c = Client(srv.host, srv.port, timeout=300.0)
            try:
                for stmt in sorted(DRAWS):
                    out[nseg, stmt] = c.sql(_text(cell, stmt))
                if nseg == 4:
                    out["explain"] = {
                        stmt: srv.session.explain(_text(cell, stmt))
                        for stmt in DRAWS}
            finally:
                c.close()
    return out


@pytest.mark.parametrize("nseg", [1, 4])
@pytest.mark.parametrize("stmt", sorted(DRAWS))
def test_served_answer_equals_the_plain_reference(deployment, answers,
                                                  stmt, nseg):
    cell, _, truth = deployment
    ref = cell.statements[stmt][1].answer(truth, DRAWS[stmt])
    assert len(ref["rows"]) == (10 if stmt == "q3" else 100)
    wrong, ulps = compare.gap(answers[nseg, stmt], ref)
    assert wrong == 0, (answers[nseg, stmt]["rows"][:3], ref["rows"][:3])
    assert max(ulps.values(), default=0.0) <= \
        cell.config["limits"]["sum_gap_ulps"]


@pytest.mark.parametrize("stmt", sorted(DRAWS))
def test_four_segments_answer_as_one(answers, stmt):
    assert answers[4, stmt]["columns"] == answers[1, stmt]["columns"]
    assert answers[4, stmt]["rows"] == answers[1, stmt]["rows"]


def test_the_plans_move_rows_as_the_cell_says(answers):
    assert "Motion redistribute" in answers["explain"]["q15v"]
    assert "Motion broadcast" in answers["explain"]["q3"]
    assert "Motion gather" in answers["explain"]["q3"]


LAUNCH_STAGES = ("inputs", "dispatch", "device_wait", "fetch",
                 "motion_stats")
LAUNCH_COUNTERS = ("launch_dist", "launch_d2h_reads", "dist_input_bytes",
                   "motion_wire_bytes")


def _launch_snapshot(log) -> dict:
    snap = log.registry.snapshot()
    out = {f"launch_seconds.{s}": snap["histograms"].get(
        f"launch_seconds.{s}", {"count": 0})["count"]
        for s in LAUNCH_STAGES}
    out.update({c: log.counter(c) for c in LAUNCH_COUNTERS})
    out["launch_packed"] = log.counter("launch_packed")
    return out


def test_distributed_launch_records_its_stages_and_counters(deployment):
    cell, root, _ = deployment
    s = cb.Session(_config(root, 4))
    sql = _text(cell, "q15v")
    for _ in range(3):              # the third send compiles nothing
        s.sql(sql)
    compiles = s.stmt_log.counter("compiles")
    before = _launch_snapshot(s.stmt_log)
    assert s.sql(sql).num_rows() == 100
    after = _launch_snapshot(s.stmt_log)
    added = {k: after[k] - before[k] for k in after}
    assert s.stmt_log.counter("compiles") == compiles
    for stage in LAUNCH_STAGES:
        assert added[f"launch_seconds.{stage}"] == 1, (stage, added)
    assert added["launch_dist"] == 1 and added["launch_packed"] == 0
    # sel + two result columns + three checks + two motion statistics
    assert added["launch_d2h_reads"] == 8, added
    # the four scanned columns' shards (one int64, one int32 date, two
    # decimals) and the per-segment row counts; not the table's other 12
    st = s.sharded_table("lineitem")
    want = sum(st.columns[c].nbytes for c in (
        "l_suppkey", "l_extendedprice", "l_discount", "l_shipdate")) \
        + st.counts.nbytes
    assert added["dist_input_bytes"] == want
    assert want < sum(a.nbytes for a in st.columns.values()) / 2
    # gather of <= capacity partial groups and the redistribute's
    # buckets: kilobytes
    assert 0 < added["motion_wire_bytes"] < 4 << 20


def test_the_load_leaves_the_keys_uniqueness_in_the_manifests(deployment):
    _, root, _ = deployment
    store = cb.Session(_config(root, 1)).catalog.store
    flags = {t: store.read_manifest(t)["unique"]
             for t in ("lineitem", "orders", "customer")}
    assert flags["orders"]["o_orderkey"] and flags["customer"]["c_custkey"]
    assert not flags["lineitem"]["l_orderkey"]
    assert not flags["orders"]["o_custkey"]


def _q3_plan(session, cell):
    """Q3's plan as a send of it runs: at one segment with the lookup
    joins' own capacities, which the session stamps where the
    statement's retry loop starts (plan/joincap.py)."""
    from cloudberry_tpu.plan import joincap
    from cloudberry_tpu.plan.planner import plan_statement
    from cloudberry_tpu.sql.parser import parse_sql

    plan = plan_statement(parse_sql(_text(cell, "q3")), session, {},
                          explain_only=True).plan
    if session.config.n_segments == 1:
        joincap.stamp_join_capacities(plan, session.catalog)
    return plan


def _shape(plan) -> list:
    """Every node of the plan: its kind, its capacities and what decides
    a join's lowering; then the plan's text, less the one thing that
    follows from where the rows are (a cold table's one-segment scan is
    bound to its store partitions: ``parts n/m``)."""
    import re

    return [(type(nd).__name__, getattr(nd, "unique_build", None),
             getattr(nd, "out_capacity", None),
             getattr(nd, "probe_capacity", None),
             getattr(nd, "capacity", None)) for nd in _all(plan)] \
        + [re.sub(r" parts \d+/\d+", "", plan.explain())]


@pytest.mark.parametrize("nseg", [1, 4])
def test_a_cold_session_plans_q3_as_a_loaded_one_does(deployment, nseg):
    """A plan must not depend on whether a table happens to be in RAM:
    cold, ``is_unique`` answers from the manifest; loaded, from the data."""
    from cloudberry_tpu.plan import nodes as N

    cell, root, _ = deployment
    cold = cb.Session(_config(root, nseg))
    cold._sync_store()
    tables = [cold.catalog.table(t) for t in cell.tables()]
    assert all(t.cold for t in tables)
    plan = _q3_plan(cold, cell)
    if nseg == 1:       # (at four, sizing the shards loads the tables)
        assert all(t.cold for t in tables)      # planning loaded nothing
    joins = [nd for nd in _all(plan) if isinstance(nd, N.PJoin)]
    assert len(joins) == 2
    assert all(j.unique_build and not j.expands for j in joins)
    if nseg == 1:       # both joins at capacities of their own (ISSUE 33)
        assert all(0 < j.out_capacity < N.capacity_of(j.probe)
                   for j in joins)
    warm = cb.Session(_config(root, nseg))
    warm._sync_store()
    for t in cell.tables():
        warm.catalog.table(t).ensure_loaded()
    assert not any(warm.catalog.table(t).cold for t in cell.tables())
    assert _shape(_q3_plan(warm, cell)) == _shape(plan)


def _all(plan):
    from cloudberry_tpu.exec.executor import all_nodes

    return list(all_nodes(plan))


@pytest.mark.parametrize("nseg", [1, 4])
def test_served_q3_launches_two_lookup_joins(deployment, nseg):
    """``launch_joins_lookup`` / ``launch_joins_expand``: the joins of each
    shape in every program launched. A session over the cold store, as
    the server's backend is: Q3 counts two lookups a send and the view,
    which joins nothing, counts none."""
    cell, root, truth = deployment
    with Server(config=_config(root, nseg)) as srv:
        c = Client(srv.host, srv.port, timeout=300.0)
        try:
            def counters():     # the engine's log, shared by backends
                log = srv.session.stmt_log
                return (log.counter("launch_joins_lookup"),
                        log.counter("launch_joins_expand"))
            assert counters() == (0, 0)
            got = c.sql(_text(cell, "q3"))
            assert counters() == (2, 0)
            c.sql(_text(cell, "q15v"))
            assert counters() == (2, 0)
            c.sql(_text(cell, "q3"))
            assert counters() == (4, 0)
        finally:
            c.close()
    ref = cell.statements["q3"][1].answer(truth, DRAWS["q3"])
    assert compare.gap(got, ref)[0] == 0


@pytest.mark.parametrize("nseg", [1, 4])
def test_a_manifest_that_lies_ends_in_an_error_not_an_answer(tmp_path,
                                                             nseg):
    """The flag is a claim the program re-verifies: a build side flagged
    unique whose data repeats a key trips the lookup's duplicate check."""
    from cloudberry_tpu import types as T
    from cloudberry_tpu.catalog.catalog import DistributionPolicy
    from cloudberry_tpu.exec.executor import DuplicateBuildKeyError
    from cloudberry_tpu.types import Schema

    root = str(tmp_path)
    store = cb.Session(_config(root, 1)).catalog.store
    dim = Schema.of(d_key=T.INT64, d_val=T.INT64)
    fact = Schema.of(f_key=T.INT64, f_val=T.INT64)
    store.append("dim", {"d_key": np.asarray([1, 2, 2, 3], dtype=np.int64),
                         "d_val": np.arange(4, dtype=np.int64)}, dim,
                 policy=DistributionPolicy.hashed("d_key"),
                 unique={"d_key": True, "d_val": True})
    store.append("fact", {"f_key": np.asarray([1, 2, 3, 3], dtype=np.int64),
                          "f_val": np.arange(4, dtype=np.int64)}, fact,
                 policy=DistributionPolicy.hashed("f_key"))
    s = cb.Session(_config(root, nseg))
    sql = ("select f_val, d_val from fact join dim on f_key = d_key "
           "order by f_val, d_val")
    with pytest.raises(DuplicateBuildKeyError):
        s.sql(sql)
    assert s.stmt_log.counter("duplicate_build_key_errors") == 1
    # the honest flags: an expansion, and the answer
    store.append("dim", {"d_key": np.asarray([1, 2, 2, 3], dtype=np.int64),
                         "d_val": np.arange(4, dtype=np.int64)}, dim,
                 policy=DistributionPolicy.hashed("d_key"), replace=True)
    s = cb.Session(_config(root, nseg))
    got = s.sql(sql).to_pandas()
    assert got.values.tolist() == [[0, 0], [1, 1], [1, 2], [2, 3], [3, 3]]
    assert s.stmt_log.counter("launch_joins_expand") == 1
    assert s.stmt_log.counter("launch_joins_lookup") == 0


def test_two_backends_launch_four_device_programs_at_once(deployment):
    """Two TCP clients on two threads, a backend session each, 50 sends
    of the redistribute statement each, on a four-device mesh: every
    send answered, all alike, inside the time limit."""
    cell, root, truth = deployment
    ref = cell.statements["q15v"][1].answer(truth, DRAWS["q15v"])
    sql = _text(cell, "q15v")
    got = {0: [], 1: []}
    errors = []
    with Server(config=_config(root, 4)) as srv:
        assert srv.per_connection
        clients = [Client(srv.host, srv.port, timeout=300.0)
                   for _ in range(2)]

        def body(i):
            try:
                for _ in range(50):
                    got[i].append(clients[i].sql(sql)["rows"])
            except Exception as e:      # noqa: BLE001 — reported below
                errors.append(f"stream {i}: {type(e).__name__}: {e}")

        threads = [threading.Thread(target=body, args=(i,), daemon=True)
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(240.0)
        hung = [i for i, t in enumerate(threads) if t.is_alive()]
        for c in clients:
            c.close()
        assert not hung, f"streams {hung} did not end in 240 s"
    assert not errors, errors
    assert [len(got[0]), len(got[1])] == [50, 50]
    want = [[r[0], r[1]] for r in ref["rows"]]
    assert all(rows == want for i in got for rows in got[i])
