"""Tests of what PR 36 adds to the benchmark: device time by plan node
(``harness/nodetime.py``) on synthetic profiles with a ``while`` that holds
two body operations on two planes and synthetic maps, and the readers'
promise of a finite number whenever there is a trace.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_nodetime.py -q -p no:cacheprovider
"""

from __future__ import annotations

import math
import os
import shutil
import sys
from types import SimpleNamespace as NS

import pytest

from benchmarks.harness import cell as C, nodetime, trace
from cloudberry_tpu.obs import programs as PG

RULE = C.read_json(C.BENCH, "planes.json")["tpu"]
MARK = (1_000, 26_000)

# Q: a join program; two launches on plane 0, one on plane 1. Its search
# is a ``while`` whose body holds a gather and a compare.
WHILE = "%while.2 = (s32[], u32[64]{0:T(128)}) while((s32[], u32[64]) %t)"
GATHER = "%fusion.7 = u32[64]{0:T(128)} fusion(u32[64]{0} %p), kind=kLoop"
COMPARE = "%compare.3 = pred[64]{0} compare(u32[64]{0} %a, u32[64]{0} %b)"
SORT = "%sort.1 = u32[64]{0} sort(u32[64]{0} %x), dimensions={0}"
PACK = "%concatenate.9 = u8[512]{0} concatenate(u8[256]{0} %a, u8[256] %b)"
# V: another program with a fusion.7 of its own, over other shapes
V_FUSION = "%fusion.7 = u32[128]{0:T(128)} fusion(u32[128]{0} %p), kind=kLoop"
V_AGG = "%reduce.4 = u32[8]{0} reduce(u32[128]{0} %x), dimensions={0}"


def _launch_q(t):
    """One launch of Q from ``t``: 10,000 ns; while 6,000 (gather 2 x
    1,500, compare 1,000, its own 2,000), sort 3,000, pack 500, idle 500."""
    return ([("jit__lambda(111)", t, 10_000)],
            [(WHILE, t, 6_000), (GATHER, t + 500, 1_500),
             (COMPARE, t + 2_000, 1_000), (GATHER, t + 3_500, 1_500),
             (SORT, t + 6_000, 3_000), (PACK, t + 9_000, 500)])


def _launch_v(t):
    return ([("jit__lambda(222)", t, 4_000)],
            [(V_FUSION, t, 1_000), (V_AGG, t + 1_000, 3_000)])


def _plane(name, launches):
    ev = lambda n, s, d: NS(name=n, start_ns=s, duration_ns=d)     # noqa
    mods = [ev(*m) for ms, _ in launches for m in ms]
    ops = [ev(*o) for _, os_ in launches for o in os_]
    return NS(name=name, lines=[NS(name="XLA Ops", events=ops),
                                NS(name="XLA Modules", events=mods),
                                NS(name="Steps", events=[ev("s", 0, 9)])])


def _profile(eager=False):
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        NS(name=trace.MARK, start_ns=MARK[0],
           duration_ns=MARK[1] - MARK[0])])])
    p0 = [_launch_q(1_000), _launch_v(11_000), _launch_q(15_000)]
    p1 = [_launch_q(2_000), _launch_v(12_000)]
    if eager:       # a helper program nobody registered
        p1.append(([("jit_copy(7)", 17_000, 1_000)],
                   [("%copy.1 = u8[8]{0} copy(u8[8]{0} %p)", 17_000, 1_000)]))
    return NS(planes=[host, _plane("/device:TPU:0", p0),
                      _plane("/device:TPU:1", p1)])


def _map(module, instrs):
    return PG.ProgramMap(
        module,
        {k: PG.where_of(path) for k, (path, _) in instrs.items()},
        {k: shape for k, (_, shape) in instrs.items()})


Q_MAP = _map("jit__lambda", {
    "while.2": ("jit(f)/n0:sort/n2:join:lookup/while", ("s32[]", "u32[64]")),
    "fusion.7": ("jit(f)/n0:sort/n2:join:lookup/while/body/gather",
                 ("u32[64]",)),
    "compare.3": ("jit(f)/n0:sort/n2:join:lookup/while/body/lt",
                  ("pred[64]",)),
    "sort.1": ("jit(f)/n0:sort/sort", ("u32[64]",)),
    "concatenate.9": ("jit(f)/answer/concatenate", ("u8[512]",))})
V_MAP = _map("jit__lambda", {
    "fusion.7": ("jit(g)/n1:agg/n3:scan/mul", ("u32[128]",)),
    "reduce.4": ("jit(g)/n1:agg/reduce_sum", ("u32[8]",))})


def _entry(seq, sql, nodes):
    return NS(seq=seq, sql=sql, what="one-shot packed",
              title=lambda k: nodes.get(k, ""), signatures=[None],
              maps={0: True})


ENTRIES = [(_entry(1, "select q", {0: "Sort", 2: "Join inner [out 64]"}),
            Q_MAP),
           (_entry(2, "select v", {1: "GroupAgg [8]", 3: "Scan t [128]"}),
            V_MAP)]


def _programs(maps=ENTRIES):
    return NS(all_maps=lambda: list(maps), find=PG.find, holders=PG.holders,
              event_instruction=PG.event_instruction,
              attribute=PG.attribute,
              entries=lambda: [e for e, _ in maps])


def _table(profile, programs=None):
    by_plane, launches = nodetime.op_events(profile, RULE, MARK)
    return by_plane, nodetime.attribute(by_plane, launches,
                                        programs or _programs())


def test_self_time_of_a_while_is_its_length_less_its_body():
    evs = _launch_q(0)[1]
    got = dict(zip([e[0] for e in evs[:3]] + ["gather2", SORT, PACK],
                   nodetime.self_ns([(n, a, a + d) for n, a, d in evs])))
    assert got[WHILE] == 2_000 and got[GATHER] == 1_500
    assert got["gather2"] == 1_500 and got[COMPARE] == 1_000
    assert got[SORT] == 3_000 and got[PACK] == 500
    # events that overlap without nesting: every instant is charged once
    odd = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("d", 30, 35)]
    assert nodetime.self_ns(odd) == [5, 15, 5, 5]
    assert sum(nodetime.self_ns(odd)) == pytest.approx(trace.union_seconds(
        [(a, b) for _, a, b in odd]) * 1e9)


def test_rows_classes_and_the_mean_over_two_planes():
    by_plane, table = _table(_profile())
    # the rows sum to busy_s: the mean over planes of the union
    union = sum(trace.union_seconds([(a, b) for _, a, b, _ in evs])
                for evs in by_plane.values()) / 2
    total = sum(r["seconds"] for r in table["rows"]) \
        + sum(table["rest"].values())
    assert total == pytest.approx(union) == pytest.approx(table["busy_s"])
    assert union == pytest.approx((2 * 9_500 + 4_000 + 9_500 + 4_000)
                                  / 2 / 1e9)
    rows = {(r["program"], r["ordinal"]): r for r in table["rows"]}
    # three launches of Q on two planes: 1.5 a plane
    join = rows[1, 2]
    assert join["kind"] == "join:lookup"
    assert join["title"] == "Join inner [out 64]"
    assert join["seconds"] == pytest.approx(1.5 * 6_000 / 1e9)
    assert join["launches"] == pytest.approx(1.5)
    assert rows[1, 0]["seconds"] == pytest.approx(1.5 * 3_000 / 1e9)
    assert table["rest"] == {"answer": pytest.approx(1.5 * 500 / 1e9)}
    # the two programs' fusion.7 are told apart (by their shapes)
    assert rows[2, 3]["kind"] == "scan"
    assert rows[2, 3]["seconds"] == pytest.approx(1_000 / 1e9)
    assert rows[2, 1]["seconds"] == pytest.approx(3_000 / 1e9)
    by_class = {}
    for r in table["rows"]:
        c = nodetime.node_class(r["kind"])
        by_class[c] = by_class.get(c, 0.0) + r["seconds"]
    assert by_class == {"join": pytest.approx(9_000 / 1e9),
                        "sort": pytest.approx(4_500 / 1e9),
                        "scan": pytest.approx(1_000 / 1e9),
                        "agg": pytest.approx(3_000 / 1e9)}


def test_a_launch_the_mark_cuts_counts_by_its_share_inside():
    # plane 0's second launch of Q runs 15,000-25,000: 0.6 of it lies in
    _, launches = nodetime.op_events(_profile(), RULE, (1_000, 21_000))
    assert launches["jit__lambda(111)"] == {
        "/device:TPU:0": pytest.approx(1.6), "/device:TPU:1": 1.0}
    assert launches["jit__lambda(222)"] == {
        "/device:TPU:0": 1.0, "/device:TPU:1": 1.0}


@pytest.mark.parametrize("kind,cls", [
    ("scan", "scan"), ("filter", "scan"), ("project", "scan"),
    ("rfilter", "scan"), ("concat", "scan"), ("share", "scan"),
    ("join:lookup", "join"), ("join:expand", "join"), ("agg", "agg"),
    ("window", "agg"), ("sort", "sort"), ("limit", "sort"),
    ("motion:gather", "motion"), ("motion:redistribute", "motion"),
    ("answer", None), ("unscoped", None)])
def test_every_kind_of_the_vocabulary_has_its_class(kind, cls):
    assert nodetime.node_class(kind) == cls


def test_a_module_no_program_owns_is_unattributed():
    _, table = _table(_profile(eager=True))
    assert table["rest"]["no program"] == pytest.approx(1_000 / 2 / 1e9)
    mod = {m["module"]: m for m in table["modules"]}
    assert mod["jit_copy(7)"]["program"] is None
    assert mod["jit__lambda(111)"]["program"] == 1
    assert mod["jit__lambda(222)"]["program"] == 2
    # a program registered with no shapes to tell it by: two programs
    # hold a fusion.7 and a reduce.4; never a guess
    blind = _map("jit__lambda", {
        "fusion.7": ("jit(h)/n5:filter/mul", ("u32[128]",)),
        "reduce.4": ("jit(h)/n4:agg/reduce_sum", ("u32[8]",)),
        "extra.1": ("jit(h)/n4:agg/add", ("u32[8]",))})
    _, table = _table(_profile(), _programs(
        ENTRIES + [(_entry(3, "select h", {}), blind)]))
    mod = {m["module"]: m for m in table["modules"]}
    assert mod["jit__lambda(222)"]["program"] is None
    assert mod["jit__lambda(222)"]["holders"] == 2
    assert mod["jit__lambda(111)"]["program"] == 1


# ------------------------------------------------------------ the readers


class _Reading:
    def __init__(self, bench, trace_):
        self.cell = NS(bench=bench, name="cell")
        self.trace = trace_
        self.device = {"platform": "tpu"}

    def sub_statements(self):
        return [("q", 1.0), ("v", 0.5), ("q", 0.5)]


@pytest.fixture
def reading(tmp_path, monkeypatch):
    """A reading whose profile on disk is the synthetic one."""
    import jax

    bench = tmp_path / "benchmarks"
    at = bench / "work" / "cell" / "trace" / "plugins" / "profile" / "x"
    os.makedirs(at)
    (at / "vm.xplane.pb").write_bytes(b"")
    shutil.copy(os.path.join(C.BENCH, "planes.json"), bench)
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: _profile(eager=True)))
    monkeypatch.setattr(PG, "all_maps", lambda: list(ENTRIES))
    monkeypatch.setattr(PG, "entries", lambda: [e for e, _ in ENTRIES])
    busy = (2 * 9_500 + 4_000 + 9_500 + 4_000 + 1_000) / 2 / 1e9
    return _Reading(str(bench), {"busy_s": busy})


def _read(metric, r):
    return C.reader(metric)(r)


def test_the_readers_read_the_table_and_its_rows_sum_to_busy_s(
        reading, capsys):
    got = {m: _read(m, reading) for m in (
        "node_attributed_pct.joins", "join_device_ms_per_stmt.joins",
        "agg_device_ms_per_stmt", "sort_device_ms_per_stmt.4seg",
        "scan_device_ms_per_stmt.custdist", "motion_device_ms_per_stmt.4seg")}
    busy = reading.trace["busy_s"]
    named = (9_000 + 4_500 + 1_000 + 3_000) / 1e9
    assert got["node_attributed_pct.joins"] == pytest.approx(
        100 * named / busy)
    # two statements' worth of sends lie in the sub-window
    assert got["join_device_ms_per_stmt.joins"] == pytest.approx(
        9_000 / 1e9 / 2 * 1e3)
    assert got["agg_device_ms_per_stmt"] == pytest.approx(3_000 / 1e6 / 2)
    assert got["sort_device_ms_per_stmt.4seg"] == pytest.approx(
        4_500 / 1e6 / 2)
    assert got["scan_device_ms_per_stmt.custdist"] == pytest.approx(
        1_000 / 1e6 / 2)
    assert got["motion_device_ms_per_stmt.4seg"] == 0.0
    # the profile was parsed once; the table is printed, and sums up
    err = capsys.readouterr().err
    lines = [ln for ln in err.splitlines() if ln.startswith("[nodes] ")]
    assert any("n2 join:lookup [Join inner [out 64]]" in ln for ln in lines)
    assert any(ln.startswith("[nodes] rest no program") for ln in lines)
    assert any("module jit_copy(7)" in ln and "no program" in ln
               for ln in lines)
    table = nodetime.of_reading(reading)
    total = sum(r["seconds"] for r in table["rows"]) \
        + sum(table["rest"].values())
    assert total == pytest.approx(busy)
    classes = sum(v for k, v in got.items() if "_device_ms" in k)
    rest = sum(table["rest"].values()) / 2 * 1e3
    assert classes + rest == pytest.approx(busy / 2 * 1e3)


def test_without_a_trace_a_reader_reads_nothing(reading):
    reading.trace = {}
    assert _read("node_attributed_pct", reading) is None
    assert _read("join_device_ms_per_stmt.joins", reading) is None


def test_a_program_that_offers_no_table_reads_zero_and_says_so(
        reading, monkeypatch, capsys):
    import cloudberry_tpu.obs as obs

    monkeypatch.delattr(obs, "programs")
    monkeypatch.setitem(sys.modules, "cloudberry_tpu.obs.programs", None)
    assert _read("node_attributed_pct.custdist", reading) == 0.0
    assert _read("join_device_ms_per_stmt.custdist", reading) == 0.0
    assert "keeps no map" in capsys.readouterr().err


def test_an_attribution_that_raises_reads_zero_and_says_so(
        reading, monkeypatch, capsys):
    def boom(*a):
        raise KeyError("fusion.7")

    monkeypatch.setattr(nodetime, "attribute", boom)
    got = _read("node_attributed_pct", reading)
    assert got == 0.0 and math.isfinite(got)
    assert _read("scan_device_ms_per_stmt", reading) == 0.0
    err = capsys.readouterr().err
    assert "the attribution raised KeyError" in err


def test_the_twenty_entries_are_each_cells_own():
    bm = C.read_json(C.REPO, "BENCHMARK.json")
    mine = [m for m in bm["per_layer"] if m["name"].split(".")[0] in (
        "node_attributed_pct", "join_device_ms_per_stmt",
        "agg_device_ms_per_stmt", "sort_device_ms_per_stmt",
        "scan_device_ms_per_stmt", "motion_device_ms_per_stmt")]
    assert len(mine) == 20 and bm["per_layer"][-20:] == mine
    device = {m["name"]: m for m in bm["per_layer"]
              if m["name"].startswith("device_ms_per_stmt")}
    for m in mine:
        assert m["source"] == "device_trace" and len(m["workloads"]) == 1
        suffix = m["name"].partition(".")[1] + m["name"].partition(".")[2]
        twin = device["device_ms_per_stmt" + suffix]
        assert (m["moves"], m["workloads"], m["layer"]) == (
            twin["moves"], twin["workloads"], twin["layer"])
        assert m["better"] == ("higher" if m["unit"] == "%" else "lower")
        assert callable(C.reader(m["name"]))
