"""Device time by plan node (ISSUE 36): one named scope a node in every
lowered program, and the program's own map from compiled instruction to
node (``cloudberry_tpu/obs/programs.py``).

- every node of a lowered plan owns exactly one ``n<ordinal>:<kind>`` and
  the ordinal is ``Lowerer.ref``'s: at one segment, at four (a Motion's kind
  in the scope) and through a tiled step;
- ``instruction_map`` of a served join + aggregate + sort + limit statement
  covers every XLA event the CPU profile of three launches holds for that
  program, and puts the search ``while`` and a gather under a join's node;
- the abstract inputs are taken once a signature, and nothing of the scopes,
  the table or the map runs at a launch;
- the scopes change no program's text and no persistent-cache key.
"""

from __future__ import annotations

import contextlib
import gc
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import Config
from cloudberry_tpu.exec import dist_executor as DX
from cloudberry_tpu.exec import executor as X
from cloudberry_tpu.obs import programs as PG
from cloudberry_tpu.plan import nodes as N

JOINED = ("select d.g, sum(f.v) as sv, count(*) as c from fact f "
          "join dim d on f.k = d.k where f.v > 1 group by d.g "
          "order by sv desc, d.g limit 5")
KINDS = {"scan", "filter", "project", "join:lookup", "join:expand", "agg",
         "sort", "limit", "window", "share", "rfilter", "concat",
         "motion:gather", "motion:broadcast", "motion:redistribute"}


def _session(nseg: int):
    s = cb.Session(Config(n_segments=nseg))
    s.sql("create table fact (k bigint, v bigint) distributed by (k)")
    s.sql("create table dim (k bigint, g bigint) distributed by (g)")
    k = np.arange(256)
    s.catalog.table("fact").set_data({"k": k % 32, "v": k % 7})
    s.catalog.table("dim").set_data({"k": np.arange(32),
                                     "g": np.arange(32) % 5})
    return s


def _plan(s, sql):
    from cloudberry_tpu.plan.planner import plan_statement
    from cloudberry_tpu.sql.parser import parse_sql

    return plan_statement(parse_sql(sql), s, {}).plan


@pytest.fixture
def scopes(monkeypatch):
    """Every name ``jax.named_scope`` was entered with, in order."""
    seen: list = []
    real = jax.named_scope

    def recording(name):
        seen.append(name)
        return real(name)

    monkeypatch.setattr(jax, "named_scope", recording)
    return seen


def _node_scopes(seen):
    return [n for n in seen if re.match(r"n\d+:", n)]


def _check_one_scope_a_node(plan, seen, lowered=None):
    """Each lowered node of ``plan`` was given exactly one scope,
    ``n<its ordinal>:<its kind>``; no scope names another ordinal."""
    nodes = X.numbered_nodes(plan)
    by_ordinal: dict = {}
    for name in _node_scopes(seen):
        ordinal, kind = name[1:].split(":", 1)
        by_ordinal.setdefault(int(ordinal), set()).add(kind)
        assert kind in KINDS, name
    lowered = nodes if lowered is None else lowered
    assert sorted(by_ordinal) == sorted(
        i for i, nd in enumerate(nodes) if nd in lowered)
    for i, kinds in by_ordinal.items():
        assert kinds == {X.node_kind(nodes[i])}, (i, kinds)
    # the two ad-hoc families live on in the vocabulary alone
    assert not [n for n in seen if n.startswith(("join:", "motion:"))]


def test_every_node_owns_one_scope_at_one_segment(scopes):
    s = _session(1)
    plan = _plan(s, JOINED)
    exe = X.compile_plan(plan, s)
    X.run_executable(exe, X.prepare_inputs(exe, s))
    _check_one_scope_a_node(plan, scopes)
    kinds = {n.split(":", 1)[1] for n in _node_scopes(scopes)}
    assert {"scan", "join:lookup", "agg", "sort", "limit"} <= kinds
    assert {"answer", "checks"} <= set(scopes)
    # the ordinal is Lowerer.ref's: what a check's label carries
    low = X.Lowerer({}, root=plan)
    for i, nd in enumerate(X.numbered_nodes(plan)):
        assert low.ref(nd) == i and f"(node {i}:" in low.label(nd)


def test_every_node_owns_one_scope_at_four_segments(scopes):
    s = _session(4)
    plan = _plan(s, JOINED)
    motions = [nd for nd in X.numbered_nodes(plan)
               if isinstance(nd, N.PMotion)]
    assert motions
    fn = DX.compile_distributed(plan, s)
    batch = DX.execute_distributed(plan, s, fn)
    assert batch.num_rows() > 0
    _check_one_scope_a_node(plan, scopes)
    for m in motions:
        assert f"n{X.numbered_nodes(plan).index(m)}:motion:{m.kind}" \
            in scopes
    assert "checks" in scopes


def test_every_node_owns_one_scope_through_a_tiled_step(scopes):
    """The streamed scan's tile is read inside the scan's own scope; a
    replaced node opens none (its subtree is never traced)."""
    s = _session(1)
    plan = _plan(s, JOINED)
    nodes = X.numbered_nodes(plan)
    stream = next(nd for nd in nodes if isinstance(nd, N.PScan)
                  and nd.table_name == "fact")
    build = next(nd for nd in nodes if isinstance(nd, N.PJoin)).build
    tables = X.prepare_plan_inputs(plan, s)
    tables["$tile"] = {"k": jnp.arange(stream.capacity) % 32,
                       "v": jnp.full(stream.capacity, 3)}
    low = X.Lowerer(tables, root=plan)
    given = low.lower_shared(build)
    del scopes[:]
    cols, sel = X.Lowerer(tables, root=plan, replace={id(build): given},
                          stream=stream, tile_n=5).lower(plan)
    assert int(np.asarray(sel).sum()) > 0
    under = set(map(id, X.all_nodes(build)))
    _check_one_scope_a_node(plan, scopes,
                            [nd for nd in nodes if id(nd) not in under])
    assert f"n{nodes.index(stream)}:scan" in scopes


def test_the_tiled_programs_are_registered_with_their_merge_scope(scopes):
    s = cb.Session(Config().with_overrides(
        **{"resource.query_mem_bytes": 1 << 20}))
    s.sql("create table fact (k bigint, v bigint) distributed by (k)")
    k = np.arange(200_000)
    s.catalog.table("fact").set_data({"k": k, "v": k % 11})
    before = {e.seq for e in PG.entries()}
    got = s.sql("select v, sum(k) as sk from fact group by v order by v")
    assert s.last_tiled_report and got.num_rows() == 11
    assert "tile:merge" in scopes
    whats = {e.what for e in PG.entries() if e.seq not in before}
    assert {"tiled prelude", "tiled step", "tiled finalize"} <= whats


@pytest.mark.parametrize("path,want", [
    ("jit(run)/n0:limit/n1:sort/n3:agg/n5:join:lookup/while/body/gather",
     (5, "join:lookup")),
    ("jit(run)/vmap(n0:sort)/n1:scan/mul", (1, "scan")),
    ("jit(seg_fn)/shard_map/n2:motion:redistribute/all_to_all",
     (2, "motion:redistribute")),
    ("jit(<lambda>)/answer/concatenate", (None, "answer")),
    ("jit(<lambda>)/tile:merge/checks/psum", (None, "checks")),
    ("jit(<lambda>)/n0:limit/answer/concatenate", (0, "limit")),
    ("jit(run)/convert_element_type", (None, "unscoped")),
    ("tables['lineitem']['l_orderkey']", (None, "input")),
    ("", (None, "unscoped")),
])
def test_where_of_takes_the_innermost_node(path, want):
    w = PG.where_of(path)
    assert (w.ordinal, w.kind) == want and w.path == path


MODULE = '''HloModule jit_run, is_scheduled=true

%fused_computation (param_0: s64[8]) -> s64[8] {
  %param_0 = s64[8]{0} parameter(0)
  %c = s64[] constant(2)
  %b = s64[8]{0} broadcast(%c), dimensions={}, metadata={op_name="jit(run)/n0:sort/n1:scan/mul"}
  ROOT %mul.0 = s64[8]{0} multiply(%param_0, %b), metadata={op_name="jit(run)/n0:sort/n1:scan/mul"}
}

%fused_computation.1 (p: s64[8]) -> (s64[8], s64[8]) {
  %p = s64[8]{0} parameter(0)
  %neg.1 = s64[8]{0} negate(%p), metadata={op_name="jit(run)/n0:sort/n2:filter/neg"}
  %neg.2 = s64[8]{0} negate(%neg.1), metadata={op_name="jit(run)/n0:sort/n2:filter/neg"}
  %abs.1 = s64[8]{0} abs(%p), metadata={op_name="jit(run)/n0:sort/abs"}
  ROOT %tuple.9 = (s64[8]{0}, s64[8]{0}) tuple(%neg.2, %abs.1)
}

%body (arg: (s32[], u32[8])) -> (s32[], u32[8]) {
  %arg = (s32[], u32[8]{0}) parameter(0)
  %gather.3 = u32[8]{0} gather(%arg), metadata={op_name="jit(run)/n0:sort/n4:join:lookup/while/body/gather"}
  %get-tuple-element.4 = u32[8]{0} get-tuple-element(%arg), index=1
  %copy-start.2 = (u32[8]{0:S(1)}, u32[8]{0}, u32[]) copy-start(%get-tuple-element.4)
  ROOT %tuple.1 = (s32[], u32[8]{0}) tuple(%arg, %gather.3)
}

ENTRY %main.3 (t: s64[8]) -> s64[8] {
  %t = s64[8]{0} parameter(0), metadata={op_name="t"}
  %fusion.7 = s64[8]{0} fusion(%t), kind=kLoop, calls=%fused_computation
  %fusion.8 = (s64[8]{0}, s64[8]{0}) fusion(%t), kind=kLoop, calls=%fused_computation.1
  %while.2 = (s32[], u32[8]{0:T(128)}) while(%t), condition=%cond, body=%body, metadata={op_name="jit(run)/n0:sort/n4:join:lookup/while"}
  %copy.5 = s64[8]{0} copy(%t)
  %concatenate.1 = u8[64]{0} concatenate(%t), dimensions={0}, metadata={op_name="jit(run)/answer/concatenate"}
  %sort.1 = s64[8]{0} sort(%fusion.7), dimensions={0}, metadata={op_name="jit(run)/n0:sort/jit(sort)/sort"}
  %copy.6 = s64[8]{0} copy(%t, %sort.1)
  ROOT %sort.0 = s64[8]{0} sort(%copy.6), dimensions={0}, metadata={op_name="jit(run)/n0:sort/jit(sort)/sort"}
}
'''


def test_parse_module_applies_the_rules_of_attribution():
    module, where, shapes = PG.parse_module(MODULE)
    assert module == "jit_run"
    got = {k: (w.ordinal, w.kind) for k, w in where.items()}
    # a fusion with no name of its own: its root's
    assert got["fusion.7"] == (1, "scan")
    # a root that names no node (a tuple): what most of the fusion carries
    assert got["fusion.8"] == (2, "filter")
    # an instruction inside a loop body: its own name; the loop: its own
    assert got["gather.3"] == got["while.2"] == (4, "join:lookup")
    assert got["sort.0"] == (0, "sort")
    assert got["concatenate.1"] == (None, "answer")
    # a program input, and the compiler's copy of it
    assert got["copy.5"] == got["t"] == (None, "input")
    # no name at all, no operand with a node, in a loop's body: the loop's
    assert got["copy-start.2"] == (4, "join:lookup")
    assert where["copy-start.2"].path.endswith("/while <- %while.2")
    # no name at all: with the first operand that has a node
    assert got["copy.6"] == (0, "sort")
    assert where["copy.6"].path.endswith("<- %sort.1")
    assert shapes["while.2"] == ("s32[]", "u32[8]")
    assert shapes["fusion.7"] == ("s64[8]",)


@pytest.mark.parametrize("name,want", [
    ("%fusion.258 = u32[6029312]{0:T(1024)S(1)} fusion(u32[524288]{0:T(1024)"
     "S(1)} %p), kind=kLoop", ("fusion.258", ("u32[6029312]",))),
    ("%while.15 = (u32[]{:T(128)}, s32[6029312]{0:T(1024)}) while((u32[], "
     "s32[6029312]) %t), body=%b", ("while.15", ("u32[]", "s32[6029312]"))),
    ("bitcast_gather_fusion.2", ("bitcast_gather_fusion.2", None)),
    ("ThunkExecutor::Execute (wait for completion)",
     ("ThunkExecutor::Execute (wait for completion)", None)),
])
def test_event_instruction_reads_a_tpu_line_and_a_cpu_name(name, want):
    assert PG.event_instruction(name) == want


def _map(module, instrs):
    return PG.ProgramMap(
        module,
        {k: PG.where_of(path) for k, (path, _) in instrs.items()},
        {k: shape for k, (_, shape) in instrs.items()})


def test_find_tells_two_programs_with_a_fusion_7_apart():
    a = _map("jit__lambda", {
        "fusion.7": ("jit(f)/n1:scan/mul", ("u32[64]",)),
        "while.2": ("jit(f)/n2:join:lookup/while", ("s32[64]",))})
    b = _map("jit__lambda", {
        "fusion.7": ("jit(f)/n3:agg/add", ("u32[128]",)),
        "sort.1": ("jit(f)/n0:sort/sort", ("u32[128]",))})
    maps = [("A", a), ("B", b)]
    # by every instruction (and shape) seen under the module event
    seen = {"fusion.7": ("u32[64]",), "while.2": None}
    assert PG.find("jit__lambda(9)", seen, maps)[0] == "A"
    assert PG.find("jit__lambda(9)", {"fusion.7": ("u32[128]",)},
                   maps)[0] == "B"
    # never by an instruction's name alone; nor what no program holds
    assert PG.find("jit__lambda(9)", {"fusion.7": None}, maps) is None
    assert PG.find("jit__lambda(9)", {"copy.3": None}, maps) is None
    assert PG.find("jit_other(9)", {}, maps) is None
    # a program registered twice is one program
    assert PG.find("jit__lambda(9)", seen, maps + [("A2", a)])[0] == "A"
    assert PG.attribute(a, [("fusion.7", 2.0), ("while.2", 1.0),
                            ("copy.9", 0.5)]) == {
        (1, "scan"): 2.0, (2, "join:lookup"): 1.0, (None, "unscoped"): 0.5}
    assert PG.attribute(None, [("fusion.7", 2.0)]) == {
        (None, "unscoped"): 2.0}


# ------------------------------------------------- the served statement


@pytest.fixture(scope="module")
def served():
    from tools.tpch_queries import QUERIES
    from tools.tpchgen import load_tpch

    s = cb.Session()
    load_tpch(s, sf=0.01, seed=7,
              tables=["lineitem", "orders", "customer"])
    before = {e.seq for e in PG.entries()}
    s.sql(QUERIES["q3"])
    mine = [e for e in PG.entries()
            if e.seq not in before and e.signatures]
    return s, QUERIES["q3"], mine


def test_the_served_statement_is_registered_with_its_plan(served):
    s, q, mine = served
    (entry,) = [e for e in mine if e.what == "one-shot packed"]
    assert entry.sql == q[:200]
    titles = list(entry.nodes.values())
    assert titles[0] == "Limit 10" and any(
        t.startswith("Scan lineitem [") for t in titles)
    assert len(entry.signatures) == 1
    leaves = jax.tree_util.tree_leaves(entry.signatures[0])
    assert leaves and all(isinstance(x, jax.ShapeDtypeStruct)
                          for x in leaves)
    from cloudberry_tpu.serve.meta import describe

    listed = describe(s, "programs")["programs"]
    row = next(p for p in listed if p["program"] == entry.seq)
    assert row["sql"] == entry.sql and row["nodes"]["0"] == "Limit 10"
    assert row["live"] and row["traces"] == 1


def test_the_map_covers_every_event_of_the_cpu_profile(served, tmp_path):
    s, q, mine = served
    (entry,) = [e for e in mine if e.what == "one-shot packed"]
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, d, **kw: compiles.append(ev)
        if "backend_compile" in ev else None)
    pmap = PG.instruction_map(entry)
    # the executable of the launches, out of JAX's caches: no compile
    assert pmap is not None and not compiles
    assert pmap.module.startswith("jit__lambda")
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        s.sql(q)
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    profile = jax.profiler.ProfileData.from_file(path)
    ops = set()
    for plane in profile.planes:
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if stats.get("hlo_module") == pmap.module:
                    ops.add(stats["hlo_op"])
    assert len(ops) > 20
    assert ops <= set(pmap.where), sorted(ops - set(pmap.where))[:5]
    kinds = {op: pmap.where[op].kind for op in ops}
    # Q3's lookups read direct-address tables (PR 37): the table's
    # scatter and gather are the join's, as a search's loop is where one
    # is left
    whiles = [op for op in ops if op.startswith("while")]
    assert all(kinds[op] == "join:lookup" for op in whiles)
    assert any("scatter" in op and kinds[op] == "join:lookup" for op in ops)
    assert any("gather" in op and kinds[op] == "join:lookup" for op in ops)
    # the numbered nodes own the program's device work
    share = sum(1 for op in ops if pmap.where[op].ordinal is not None)
    assert share / len(ops) > 0.9, sorted(
        op for op in ops if pmap.where[op].ordinal is None)
    found = PG.find(f"{pmap.module}(7)", {op: None for op in ops},
                    [(entry, pmap)])
    assert found is not None and found[0] is entry


def test_nothing_runs_at_a_launch_once_a_function_is_traced(
        served, monkeypatch):
    """100 launches of a traced signature: the abstract inputs were taken
    once, and no scope, no registration and no map code ran again."""
    s, q, mine = served
    (entry,) = [e for e in mine if e.what == "one-shot packed"]
    calls = {"scope": 0, "signature": 0, "jit": 0, "map": 0}

    def counting(key, real):
        def fn(*a, **kw):
            calls[key] += 1
            return real(*a, **kw)
        return fn

    monkeypatch.setattr(jax, "named_scope",
                        counting("scope", jax.named_scope))
    monkeypatch.setattr(PG, "_note_signature",
                        counting("signature", PG._note_signature))
    monkeypatch.setattr(PG, "jit", counting("jit", PG.jit))
    monkeypatch.setattr(PG, "instruction_map",
                        counting("map", PG.instruction_map))
    for _ in range(100):
        s.sql(q)
    assert calls == {"scope": 0, "signature": 0, "jit": 0, "map": 0}
    assert len(entry.signatures) == 1


def test_a_program_is_registered_weakly():
    s = _session(1)
    plan = _plan(s, JOINED)
    exe = X.compile_plan(plan, s)
    X.run_executable(exe, X.prepare_inputs(exe, s))
    mine = [e for e in PG.entries() if e.fn() is exe.packed_fn]
    assert len(mine) == 1 and mine[0].signatures
    seq = mine[0].seq
    del exe, mine
    gc.collect()
    assert seq not in {e.seq for e in PG.entries()}


# ----------------------------------------- no text and no cache key moves


def _lowered(s, plan, scoped: bool):
    """The one-shot packed program of ``plan``, lowered with the scopes
    in, or with ``jax.named_scope`` a no-op."""
    real = jax.named_scope
    if not scoped:
        jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        exe = X.compile_plan(plan, s)
        return exe.packed_fn.lower(X.prepare_inputs(exe, s))
    finally:
        jax.named_scope = real


def test_the_scopes_change_no_text_and_no_cache_key():
    from jax._src import cache_key, compiler
    from jax.extend import backend as jb

    s = _session(1)
    plan = _plan(s, JOINED)
    with_, without = _lowered(s, plan, True), _lowered(s, plan, False)
    assert with_.as_text() == without.as_text()
    # (the texts above print no location; these do: the scopes are there)
    assert "/n1:limit/" in with_.as_text(debug_info=True)
    assert "/n1:limit/" not in without.as_text(debug_info=True)
    backend = jb.get_backend()
    devices = np.array(jax.devices()[:1])
    options = compiler.get_compile_options(num_replicas=1, num_partitions=1)
    keys = {cache_key.get(low.compiler_ir(), devices, options, backend)
            for low in (with_, without)}
    assert len(keys) == 1


def test_an_unknown_node_has_no_kind():
    class PNew(N.PlanNode):
        pass

    with pytest.raises(X.ExecError, match="cannot execute node PNew"):
        X.node_kind(PNew.__new__(PNew))
