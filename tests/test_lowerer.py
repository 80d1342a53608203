"""How a plan becomes a program (ISSUE 30): two lowerer classes, ``Lowerer``
and ``DistLowerer``, and three pieces of state they hold — ``replace`` (nodes
already computed), ``stream``/``tile_n`` (the scan a tiled step feeds a tile
at a time), ``count_rows`` (EXPLAIN ANALYZE's counts) — plus one function,
``dist_executor.dist_lowering``, that decides which collectives a Motion
lowers to for every distributed program of a session.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import cloudberry_tpu as cb
from cloudberry_tpu.config import Config
from cloudberry_tpu.exec import dist_executor as DX
from cloudberry_tpu.exec import executor as X
from cloudberry_tpu.exec import tiled as T
from cloudberry_tpu.parallel.mesh import SEG_AXIS
from cloudberry_tpu.plan import nodes as N
from program_texts import record_tiled_programs, recording

NSEG = 8
ROWS = 64


def _session(nseg: int):
    s = cb.Session(Config(n_segments=nseg))
    s.sql("create table t (k bigint, v bigint) distributed by (k)")
    s.catalog.table("t").set_data(
        {"k": np.arange(ROWS), "v": np.arange(ROWS) % 7})
    return s


def _plan(s, sql):
    from cloudberry_tpu.plan.planner import plan_statement
    from cloudberry_tpu.sql.parser import parse_sql

    return plan_statement(parse_sql(sql), s, {}).plan


def _scans(plan):
    return [n for n in X.numbered_nodes(plan) if isinstance(n, N.PScan)]


def _selected(cols, sel):
    sel = np.asarray(sel)
    return {c: np.asarray(v)[sel] for c, v in cols.items()}


def _run_dist(s, plan, make_lowerer, extra=None, extra_specs=None):
    """Lower ``plan`` inside the session's shard_map with the lowerer
    ``make_lowerer(constructor, tables)`` builds; ``extra`` are inputs
    beside the scanned tables, split on the segment axis. Returns the
    gathered (replicated) answer's selected rows."""
    mesh, lowerer = DX.dist_lowering(s)
    inputs, in_specs = DX.prepare_dist_inputs(plan, s)
    inputs.update(extra or {})
    in_specs.update(extra_specs or {})

    def seg_fn(tables):
        low = make_lowerer(lowerer, tables)
        cols, sel = low.lower(plan)
        return {f.name: cols[f.name][None] for f in plan.fields}, sel[None]

    out_specs = ({f.name: P(SEG_AXIS) for f in plan.fields}, P(SEG_AXIS))
    cols, sel = jax.jit(DX._shard_map(seg_fn, mesh, (in_specs,),
                                      out_specs))(inputs)
    return _selected({c: np.asarray(v)[0] for c, v in cols.items()},
                     np.asarray(sel)[0])


# ------------------------------------------------------------------ replace


GROUPED = "select v, count(*) as n, sum(k) as sk from t group by v order by v"


def _grouped_oracle(k, v):
    uv = np.unique(v)
    return {"v": uv, "n": np.array([(v == x).sum() for x in uv]),
            "sk": np.array([k[v == x].sum() for x in uv])}


def test_a_replaced_nodes_subtree_is_never_traced():
    """The scan beneath a replaced node reads a table that is not among
    the inputs: with the node in ``replace`` nothing looks for it, and the
    answer is computed from what the node was given."""
    s = _session(1)
    plan = _plan(s, GROUPED)
    (scan,) = _scans(plan)
    k = np.arange(10, 40)
    v = k % 3
    given = ({scan.column_map["k"]: jnp.asarray(k),
              scan.column_map["v"]: jnp.asarray(v)},
             jnp.ones(k.shape[0], dtype=bool))
    with pytest.raises(KeyError):
        X.Lowerer({}).lower(plan)
    cols, sel = X.Lowerer({}, replace={id(scan): given}).lower(plan)
    got = _selected({f.name: cols[f.name] for f in plan.fields}, sel)
    want = _grouped_oracle(k, v)
    assert {c: got[c].tolist() for c in want} == \
        {c: want[c].tolist() for c in want}


def test_a_replaced_nodes_subtree_is_never_traced_distributed():
    """The same through ``DistLowerer``, which inherits the state: every
    segment is handed its own rows for the scan node, the table itself is
    taken out of the program's inputs, and partial aggregate, Motion and
    final aggregate run over what was given."""
    s = _session(NSEG)
    plan = _plan(s, GROUPED)
    (scan,) = _scans(plan)
    assert any(isinstance(n, N.PMotion) for n in X.numbered_nodes(plan))
    k = np.arange(NSEG * 4).reshape(NSEG, 4) + 100
    v = k % 5
    extra = {"$given": {"k": k, "v": v}}
    specs = {"$given": {"k": P(SEG_AXIS, None), "v": P(SEG_AXIS, None)}}

    def make(lowerer, tables):
        mine = tables["$given"]
        given = ({scan.column_map["k"]: mine["k"][0],
                  scan.column_map["v"]: mine["v"][0]},
                 jnp.ones(4, dtype=bool))
        return lowerer({}, replace={id(scan): given})

    got = _run_dist(s, plan, make, extra, specs)
    want = _grouped_oracle(k.ravel(), v.ravel())
    assert {c: got[c].tolist() for c in want} == \
        {c: want[c].tolist() for c in want}


# ------------------------------------------------------------- streamed scan


TWICE = "select k from t where v = 1 union all select k from t where v = 2"


def test_the_streamed_scan_reads_the_tile_and_its_twin_the_table():
    """Two scans of ONE table in one plan: the one named ``stream`` reads
    ``tables["$tile"]``, ``tile_n`` rows of it; the other reads the table."""
    s = _session(1)
    plan = _plan(s, TWICE)
    first, second = _scans(plan)
    assert first.table_name == second.table_name == "t"
    tile_k = np.arange(1000, 1000 + first.capacity)
    tile = {"k": jnp.asarray(tile_k),
            "v": jnp.ones(first.capacity, dtype=np.int64)}
    tables = X.prepare_plan_inputs(plan, s)
    tables["$tile"] = tile
    cols, sel = X.Lowerer(tables, stream=first, tile_n=5).lower(plan)
    got = np.sort(_selected(cols, sel)[plan.fields[0].name])
    k = np.arange(ROWS)
    assert got.tolist() == sorted(tile_k[:5].tolist()
                                  + k[k % 7 == 2].tolist())
    # streaming the OTHER scan swaps the roles: nothing but identity
    # tells the two scans apart
    tile["v"] = jnp.full(first.capacity, 2, dtype=np.int64)
    cols, sel = X.Lowerer(tables, stream=second, tile_n=5).lower(plan)
    got = np.sort(_selected(cols, sel)[plan.fields[0].name])
    assert got.tolist() == sorted(k[k % 7 == 1].tolist()
                                  + tile_k[:5].tolist())


def test_the_streamed_scan_reads_the_tile_distributed():
    """``DistLowerer.scan`` holds no copy of the rule: the streamed scan
    reads this segment's tile, its twin this segment's shard."""
    s = _session(NSEG)
    plan = _plan(s, TWICE)
    first, second = _scans(plan)
    cap = first.capacity
    tile_k = np.arange(NSEG * cap).reshape(NSEG, cap) + 1000
    extra = {"$tile": {"k": tile_k, "v": np.ones((NSEG, cap), np.int64)},
             "$tile_n": np.full(NSEG, 2, np.int64)}
    specs = {"$tile": {"k": P(SEG_AXIS, None), "v": P(SEG_AXIS, None)},
             "$tile_n": P(SEG_AXIS)}

    def make(lowerer, tables):
        tables = dict(tables)
        tables["$tile"] = {c: a[0] for c, a in tables["$tile"].items()}
        return lowerer(tables, stream=first,
                       tile_n=tables["$tile_n"].reshape(()))

    got = np.sort(_run_dist(s, plan, make, extra, specs)
                  [plan.fields[0].name])
    k = np.arange(ROWS)
    assert got.tolist() == sorted(tile_k[:, :2].ravel().tolist()
                                  + k[k % 7 == 2].tolist())


# --------------------------------------------------------------- count_rows


@pytest.fixture(scope="module")
def lineitem():
    from tools.tpchgen import load_tpch

    s = cb.Session()
    load_tpch(s, sf=0.01, seed=7, tables=["lineitem"])
    return s


def test_count_rows_leaves_the_answer_and_the_checks_unchanged(lineitem):
    from tools.tpch_queries import QUERIES

    s = lineitem
    for q in ("q1", "q6"):
        plan = _plan(s, QUERIES[q])
        plain = X.compile_plan(plan, s)
        counting = X.compile_plan(plan, s, instrument=True)
        inputs = X.prepare_inputs(plain, s)
        cols, sel, checks = plain.fn(inputs)
        icols, isel, ichecks, counts = counting.fn(inputs)
        assert list(checks) == list(ichecks)
        for a, b in zip(jax.tree_util.tree_leaves((cols, sel, checks)),
                        jax.tree_util.tree_leaves((icols, isel, ichecks))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # one count a node, by ordinal; the root's is the answer's rows
        assert sorted(counts) == list(range(len(X.numbered_nodes(plan))))
        assert int(counts[0]) == int(np.asarray(sel).sum())


def test_count_rows_off_is_not_in_the_programs_text(lineitem):
    """Q6's program as ``compile_plan`` builds it, flag off, against the
    same plan lowered by a lowerer that was never given the argument:
    the module text is the same, byte for byte (and the flag on is not)."""
    from tools.tpch_queries import QUERIES

    s = lineitem
    plan = _plan(s, QUERIES["q6"])
    exe = X.compile_plan(plan, s)
    inputs = X.prepare_inputs(exe, s)

    def run(tables):
        low = X.Lowerer(tables, platform=jax.default_backend(),
                        params=tables.get("$params"))
        cols, sel = low.lower(plan)
        return {f.name: cols[f.name] for f in plan.fields}, sel, low.checks

    text = exe.fn.lower(inputs).as_text()
    assert text == jax.jit(run).lower(inputs).as_text()
    counting = X.compile_plan(plan, s, instrument=True)
    assert counting.fn.lower(inputs).as_text() != text


# ------------------------------------------------ one transport, four programs


AGG = ("SELECT v % 13 AS b, sum(v) AS sv, count(*) AS c, min(k) AS mn, "
       "max(k) AS mx FROM fact GROUP BY b ORDER BY b")
SORT = "SELECT k, g, v FROM fact JOIN dim ON fact.grp = dim.d ORDER BY v, k, g"

# program kind -> (statement, how it is sent, overrides, the program that
# holds the redistribute)
PROGRAMS = {
    "one_shot": (AGG, "sql", {}, "seg_fn"),
    "explain_analyze": (AGG, "explain_analyze", {}, "seg_fn"),
    "tiled_aggregate": (AGG, "sql", {"resource.query_mem_bytes": 1 << 20},
                        "finalize_fn"),
    "tiled_sort": (SORT, "sql", {"resource.query_mem_bytes": 1 << 20,
                                 "planner.broadcast_threshold": 0},
                   "step_fn"),
}


@pytest.fixture
def launched(monkeypatch):
    """``(function name, module text)`` of every distributed program a
    statement launches, kept at the program's first launch."""
    texts = []
    compile_distributed = DX.compile_distributed
    monkeypatch.setattr(
        DX, "compile_distributed",
        lambda *a, **kw: recording(compile_distributed(*a, **kw), texts,
                                   once=True))
    record_tiled_programs((T,), texts, monkeypatch.setattr)
    return texts


def _collectives(text):
    return {op for op in ("all_to_all", "collective_permute", "all_gather")
            if re.search(rf"stablehlo\.{op}\b", text)}


@pytest.mark.parametrize("kind", sorted(PROGRAMS))
def test_every_distributed_program_lowers_a_redistribute_alike(
        kind, launched, monkeypatch):
    """At the forced split of 8 virtual segments over 4 hosts
    (tests/test_hier_motion.py), a redistribute is ONE all-to-all with
    ``interconnect.hierarchical`` off and the two-level exchange
    (collective permutes only: no all-to-all, no all-gather) with it on
    — in the one-shot program, EXPLAIN ANALYZE's, the tiled aggregate's
    finalize and the tiled sort's step alike: ``dist_lowering`` is the
    one place that decides it."""
    from test_hier_motion import _mk_session

    monkeypatch.setenv("CBTPU_FORCE_HOSTS", "4")
    sql, send, over, holder = PROGRAMS[kind]
    for hier, want in (("off", {"all_to_all", "all_gather"}),
                       ("on", {"collective_permute"})):
        del launched[:]
        s = _mk_session(hier, **over)
        getattr(s, send)(sql)
        assert bool(over) == bool(s.last_tiled_report
                                  and s.last_tiled_report["distributed"])
        (text,) = [t for name, t in launched if name == holder]
        assert _collectives(text) == want, (kind, hier)


# -------------------------------------------------------------------- option


def test_an_override_of_the_removed_option_is_refused():
    """``exec.use_pallas`` went with the kernels it selected: naming it
    is refused as any unknown section or field is."""
    for path in ("exec.use_pallas", "nosuch.field"):
        with pytest.raises(AttributeError):
            Config().with_overrides(**{path: True})
    with pytest.raises(TypeError):
        Config().with_overrides(**{"interconnect.nosuch": True})
    assert not hasattr(Config(), "exec")
