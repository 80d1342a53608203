"""The benchmark's own tests: CPU, tiny scale, no chip.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q -p no:cacheprovider

They cover the harness (cells resolve to files, the last line and its
validator, the trace reduction, the client arithmetic, the byte count of
the roofline, a cell added as data alone) and the comparison that decides
``correct``: the control must fail it, and so must a run whose timed path
is broken underneath (an answer altered where it is produced; half of the
rows left out of the store).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
from types import SimpleNamespace as NS

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import control, run as R                      # noqa: E402
from benchmarks.harness import (cell as C, client, compare,   # noqa: E402
                                lastline, trace, traffic)

BM = C.read_json(REPO, "BENCHMARK.json")
CELLS = [w["name"] for w in BM["workloads"]]
SCALE = 0.05


def _args(workload, trace_on, seed=7, seconds=2.0):
    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace_on, rehearse_scale=SCALE)


# ------------------------------------------------------------ data files

def test_benchmark_json_keeps_to_the_contract():
    import re

    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    assert sorted(BM) == sorted(["command", "paths", "run_seconds",
                                 "configs", "workloads", "end_to_end",
                                 "per_layer"])
    assert 1 <= BM["run_seconds"] <= 51

    def line(t):
        return 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t

    for c in BM["configs"]:
        assert sorted(c) == ["file", "name", "reduced", "source", "why"]
        assert name.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith(BM["paths"][0] + "/")
        assert all(name.match(k) for k in c["reduced"])
        assert set(c["reduced"]) == set(
            C.read_json(REPO, c["file"])["reduced"])
    pairs = set()
    for w in BM["workloads"]:
        assert sorted(w) == ["chips", "config", "name", "traffic", "why"]
        assert name.match(w["name"]) and name.match(w["traffic"])
        assert line(w["why"]) and w["chips"] in (1, 4)
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(BM["workloads"])
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BM["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")

    def reports(m, cell):
        return "workloads" not in m or cell in m["workloads"]

    for m in BM["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert line(m["layer"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for cell in m.get("workloads", CELLS):
            assert reports(e2e[m["moves"]], cell), (m["name"], cell)
    for cell in CELLS:
        mine = [m for m in BM["end_to_end"] if reports(m, cell)]
        assert len(mine) >= 2 and any(m["name"] == "setup_s" for m in mine)
        assert any(reports(m, cell) for m in BM["per_layer"])
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_files(name):
    cell = C.Cell(name)
    assert cell.tables() and cell.statements
    for m in cell.per_layer:
        assert callable(C.reader(m["name"]))
    for stmt, (text, ref) in cell.statements.items():
        draw = traffic.grid(cell.traffic["statements"][stmt]["params"])[0]
        assert "{" not in text.format(**ref.bind(draw))
    kinds = C.read_json(C.BENCH, "peaks.json")
    assert all("source" in v for v in kinds.values())


def test_roofline_bytes_at_sf1_rows():
    cell = C.Cell(CELLS[0])
    rows = {"lineitem": 6_001_215}
    # Q1: flag 1 + status 1 + quantity 1 + price 4 + discount 1 + tax 1
    # + shipdate 2 = 11 B a row; Q6: price 4 + discount 1 + quantity 1
    # + shipdate 2 = 8 B a row
    assert cell.scanned_bytes("q1", rows) == 11 * 6_001_215
    assert cell.scanned_bytes("q6", rows) == 8 * 6_001_215


def test_same_draws_for_every_seed_in_another_order():
    mix = C.Cell(CELLS[0]).traffic
    a, b = traffic.Schedule(mix, 1), traffic.Schedule(mix, 2**31 + 5)
    n = len(traffic.grid(mix["statements"]["q6"]["params"]))

    def take(schedule):
        out = []
        for stmt, params in schedule.sends(0):
            if stmt == "q6":
                out.append(json.dumps(params, sort_keys=True))
            if len(out) == n:
                return out

    assert sorted(take(a)) == sorted(take(b)) and take(a) != take(b)
    assert take(a) == take(traffic.Schedule(mix, 1))


# ------------------------------------------------------- client arithmetic

def test_percentile_and_rate_on_a_fixed_sample():
    sends = [client.Send(0, "q", {}, t_send=i * 0.1,
                         t_done=i * 0.1 + (i + 1) / 1000.0)
             for i in range(100)]
    sends.append(client.Send(0, "q", {}, t_send=9.95, t_done=10.5))
    sends.append(client.Send(1, "q", {}, 1.0, 1.5, error="boom"))
    m = client.end_to_end(sends, 0.0, 10.0)
    assert m["stmt_per_s"] == pytest.approx(100 / 10.0)   # one came late
    assert m["lat_p50_ms"] == pytest.approx(51.0)
    assert m["lat_p95_ms"] == pytest.approx(96.0)
    assert client.percentile([1.0], 0.95) == 1.0


# ---------------------------------------------------------- trace reduction

def _profile(device_events, mark=(1_000, 9_000)):
    ev = lambda n, s, d: NS(name=n, start_ns=s, duration_ns=d)     # noqa
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        ev(trace.MARK, mark[0], mark[1] - mark[0])])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev(*e) for e in device_events]),
        NS(name="Steps", events=[ev("step", 0, 10_000)])])
    return NS(planes=[host, dev])


def test_trace_reduction_gives_the_known_busy_time():
    rule = C.read_json(C.BENCH, "planes.json")["tpu"]
    prof = _profile([("fusion.1", 0, 2_000),        # half before the mark
                     ("fusion.2", 3_000, 1_000),
                     ("copy", 3_500, 1_500),        # overlaps fusion.2
                     ("fusion.1", 8_500, 2_000)])   # runs past the mark
    by_plane = trace.clip(trace.device_events(prof, rule),
                          *trace.marked_window(prof))
    modules = trace.clip(trace.device_events(prof, rule, "module_lines"),
                         *trace.marked_window(prof))
    assert modules == {}                 # this profile has no such line
    out = trace.reduce_events(by_plane, 8_000 / 1e9, modules={
        "/device:TPU:0": [("jit_run(1)", 1_000, 5_000)]})
    assert out["device_ops"][0] == ["program jit_run(1)", 4_000 / 1e9]
    out = trace.reduce_events(by_plane, 8_000 / 1e9)
    # inside [1000, 9000): 1000 + (3000..5000) 2000 + 500 = 3500 ns
    assert out["busy_s"] == pytest.approx(3_500 / 1e9)
    assert out["window_s"] == pytest.approx(8_000 / 1e9)
    assert out["device_ops"][0][0] == "fusion.1"
    assert out["idle_gaps"][0][1] == pytest.approx(3_500 / 1e9)
    assert trace.reduce_events({}, 1.0) == {}
    assert trace.union_seconds([(0, 10), (5, 20), (30, 40)]) == 30 / 1e9


# ------------------------------------------------------------ the last line

def _line(cell, traced):
    metrics = cell.metrics(traced)
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
           "memory_peak_bytes": 1 << 30}
    if traced:
        dev.update(busy_s=0.5, window_s=2.0)
    return lastline.build(True, 10, 0, {m["name"]: 1.5 for m in metrics},
                          metrics, dev, {"wrong_values": [0, 0]}), metrics


@pytest.mark.parametrize("traced", [False, True])
def test_validator_accepts_a_whole_line_and_names_what_is_missing(traced):
    cell = C.Cell(CELLS[0])
    line, metrics = _line(cell, traced)
    assert lastline.problems(line, metrics, traced, chips=1) == []
    assert list(line)[-1] == "compared"
    gone = metrics[0]["name"]
    del line["metrics"][gone]
    assert any(gone in p for p in lastline.problems(line, metrics, traced))
    line, _ = _line(cell, traced)
    line["device"]["platform"] = "cpu"
    assert lastline.problems(line, metrics, traced)
    assert lastline.problems(line, metrics, traced, platform=None) == []


@pytest.mark.parametrize("busy,window", [(0.0, 2.0), (2.5, 2.0), (None, 2.0)])
def test_validator_rejects_busy_outside_the_window(busy, window):
    cell = C.Cell(CELLS[0])
    line, metrics = _line(cell, True)
    line["device"].update(busy_s=busy, window_s=window)
    assert any("busy_s" in p for p in lastline.problems(line, metrics, True))


# ------------------------------------------------------------- whole runs

@pytest.fixture(scope="module")
def rehearsals():
    """Each cell x trace mode once, past the look for a chip."""
    out = {}
    for name in CELLS:
        for traced in (0, 1):
            out[name, traced] = R.run(_args(name, traced))
    return out


@pytest.mark.parametrize("traced", [0, 1])
@pytest.mark.parametrize("name", CELLS)
def test_each_cell_prints_a_line_the_validator_accepts(rehearsals, name,
                                                       traced):
    code, line = rehearsals[name, traced]
    assert code == 3 and line["rehearsal"] is True
    cell = C.Cell(name)
    assert lastline.problems(line, cell.metrics(bool(traced)), bool(traced),
                             platform=None) == []
    assert line["correct"] is True and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert not os.path.exists(os.path.join(C.BENCH, "work", name))
    if traced:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        compiles = [v["value"] for k, v in line["metrics"].items()
                    if k.split(".")[0] == "compiles_in_window"]
        assert compiles == [0]


def test_without_a_chip_there_is_no_result():
    args = _args(CELLS[0], 0)
    args.rehearse_scale = None
    assert R.run(args) == (2, None)


def test_a_cell_added_as_data_runs_without_editing_a_file(tmp_path):
    """A later PR's cell: a configuration, a mix, a statement with its
    reference and a per-layer metric, all as NEW files, and entries in
    BENCHMARK.json. No file that was there is touched."""
    repo = tmp_path / "repo"
    shutil.copytree(C.BENCH, repo / "benchmarks",
                    ignore=shutil.ignore_patterns("work", "__pycache__"))
    bench = repo / "benchmarks"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    cfg = C.read_json(C.BENCH, "configs", "tpch-sf1-resident.json")
    cfg.update(name="tpch-new", statements=["q6", "qcount"])
    (bench / "configs" / "tpch-new.json").write_text(json.dumps(cfg))
    mix = C.read_json(C.BENCH, "traffic", "scan-streams.json")
    mix.update(streams=1, order=[["qcount", "q6"]])
    mix["statements"] = {"q6": mix["statements"]["q6"], "qcount": {
        "params": {"quantity": {"range": [10, 12]}}}}
    (bench / "traffic" / "count-stream.json").write_text(json.dumps(mix))
    (bench / "statements" / "qcount.sql").write_text(
        "select count(*) as n from lineitem where l_quantity < {quantity}")
    (bench / "reference" / "qcount.py").write_text(
        "TABLES = ('lineitem',)\n"
        "COLUMNS = {'lineitem': ('l_quantity',)}\n"
        "def bind(p):\n    return {'quantity': int(p['quantity'])}\n"
        "def answer(tables, p):\n"
        "    q = tables['lineitem']['l_quantity']\n"
        "    return {'columns': ['n'], 'rows': "
        "[[int((q < int(p['quantity']) * 100).sum())]]}\n")
    (bench / "layer_metrics" / "dispatches.py").write_text(
        "def read(r):\n    return r.counter('dispatches') or None\n")
    bm = json.loads(json.dumps(BM))
    bm["configs"].append({"name": "tpch-new", "source": "x", "reduced": [],
                          "file": "benchmarks/configs/tpch-new.json",
                          "why": "x"})
    bm["workloads"].append({"name": "tpch-new.count-stream", "chips": 1,
                            "config": "tpch-new", "traffic": "count-stream",
                            "why": "x"})
    bm["per_layer"].append({
        "name": "dispatches", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "session", "moves":
        "stmt_per_s", "workloads": ["tpch-new.count-stream"]})
    (repo / "BENCHMARK.json").write_text(json.dumps(bm))
    code, line = R.run(_args("tpch-new.count-stream", 1), repo=str(repo))
    assert code == 3 and line["correct"] is True
    assert line["metrics"]["dispatches"]["value"] > 0
    assert "decode_ms" not in line["metrics"]       # another cell's metric
    assert all(p.read_bytes() == b for p, b in before.items())


# ------------------------------------------ the comparison: control, faults

@pytest.mark.parametrize("seed", [3, 2**31 + 11, 99])
@pytest.mark.parametrize("how", sorted(control.CONTROLS))
def test_the_control_comes_out_not_correct(seed, how):
    cell = C.Cell(CELLS[0])
    _, tables = control.truth_tables(cell, 0.02, seed)
    sound = control.control_run(cell, tables, seed, np.int64)
    assert sound["correct"] is True
    for key in ("sum_gap_ulps", "avg_gap_ulps"):
        assert sound["compared"][key][0] == 0.0
    broken = control.control_run(cell, tables, seed, control.CONTROLS[how])
    assert broken["correct"] is False
    for key in ("sum_gap_ulps", "avg_gap_ulps"):
        number, limit = broken["compared"][key]
        assert number >= 3 * limit


def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch):
    """The server renders every answer in ``Server._render``: one cent on
    one money sum of every Q1 answer there, and the run is not correct."""
    from cloudberry_tpu.serve.server import Server

    sound = Server._render

    def altered(self, result):
        resp = sound(self, result)
        if resp.get("columns", [None])[0] == "l_returnflag":
            resp["rows"][0][3] += 0.01          # sum_base_price
        return resp

    monkeypatch.setattr(Server, "_render", altered)
    code, line = R.run(_args(CELLS[0], 0))
    assert code == 3 and line["correct"] is False
    number, limit = line["compared"]["sum_gap_ulps"]
    assert number > limit
    assert line["compared"]["wrong_values"][0] == 0
    assert line["compared"]["avg_gap_ulps"][0] <= \
        line["compared"]["avg_gap_ulps"][1]


def test_an_altered_count_is_a_wrong_value(monkeypatch):
    from cloudberry_tpu.serve.server import Server

    sound = Server._render

    def altered(self, result):
        resp = sound(self, result)
        if resp.get("columns", [None])[0] == "l_returnflag":
            resp["rows"][-1][-1] += 1           # count_order
        return resp

    monkeypatch.setattr(Server, "_render", altered)
    code, line = R.run(_args(CELLS[-1], 0))
    assert line["correct"] is False
    assert line["compared"]["wrong_values"][0] > 0


def test_half_of_the_rows_left_out_is_not_correct(monkeypatch):
    """Half of every write never reaches the store (the generator's
    arrays keep all of it): the scans read half of the table, and every
    sum and count is short."""
    from cloudberry_tpu.storage.table_store import TableStore

    sound, calls = TableStore.append, [0]

    def half(self, table, data, schema, **kw):
        calls[0] += 1
        return sound(self, table, {c: v[:len(v) // 2]
                                   for c, v in data.items()}, schema, **kw)

    monkeypatch.setattr(TableStore, "append", half)
    code, line = R.run(_args(CELLS[-1], 0))
    assert calls[0] >= 1 and line["correct"] is False
    assert line["compared"]["wrong_values"][0] > 0      # the counts
    assert line["compared"]["sum_gap_ulps"][0] > \
        line["compared"]["sum_gap_ulps"][1]


def test_compare_counts_errors_silence_and_disagreement():
    cols = ["x", "avg", "s", "n"]
    ref = NS(RATIOS=("avg",), answer=lambda t, p: {
        "columns": cols, "rows": [[1.5, 2.5, "a", 2]]})
    lim = {"unanswered": 0, "wrong_values": 0, "sum_gap_ulps": 2.0,
           "avg_gap_ulps": 4096.0, "draws_differ": 0, "not_compared": 0}
    ok = {"columns": cols, "rows": [[1.5, 2.5, "a", 2]]}
    mk = lambda a, err=None, p=1: client.Send(                     # noqa
        0, "q", {"p": p}, 0.0, 1.0, answer=a, error=err)
    v = compare.compare([mk(ok), mk(ok)], {"q": ref}, {}, lim, 4, 1)
    assert v["correct"] and v["answers_compared"] == 2
    v = compare.compare([mk(None, "boom")], {"q": ref}, {}, lim, 4, 1)
    assert not v["correct"] and v["compared"]["not_compared"][0] == 1
    assert v["compared"]["unanswered"][0] == 1
    off = {"columns": cols, "rows": [[1.5 + 1e-12, 2.5 + 1e-13, "b", 2.0]]}
    v = compare.compare([mk(ok), mk(off)], {"q": ref}, {}, lim, 4, 1)
    assert v["compared"]["draws_differ"][0] == 1
    assert v["compared"]["wrong_values"][0] == 2      # "b", and 2.0 for 2
    assert v["compared"]["sum_gap_ulps"][0] > 2.0          # 1e-12 on 1.5
    assert 0 < v["compared"]["avg_gap_ulps"][0] < 4096.0   # 1e-13 on 2.5
    assert not v["correct"]
