"""Tests of the drawn join cell ``tpch-sf1-drawn-joins.drawn-join-streams``:
the join cell's Q3 and Q12 with their substitution parameters drawn from
the spec's whole grids on every send, Q12 as ``q12d`` (SHIPMODE2 drawn
apart from SHIPMODE1). Its entries, its mix and its reference, a
rehearsal at SF 0.01 to a line the validator accepts, and the check that
decides ``correct`` failing the cell where the reference of another draw
is put in its draw's place. Every entry is looked up by NAME: an entry a
later PR appends after these moves nothing here.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_drawn_join_cell.py -q -p no:cacheprovider
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import cell as C, lastline, traffic   # noqa: E402

CELL = "tpch-sf1-drawn-joins.drawn-join-streams"
CONFIG = "tpch-sf1-drawn-joins"
JOINS = "tpch-sf1-joins.join-streams"
BM = C.read_json(REPO, "BENCHMARK.json")
RATE = "stmt_per_s.outofcore"
# the join cell's per-layer entries this cell reports too
METRICS = ("plan_ms", "bind_ms", "admit_ms", "compiles_in_window",
           "request_ms", "launch_ms", "device_ms_per_stmt",
           "device_idle_pct", "idle_attributed_pct", "node_attributed_pct",
           "join_device_ms_per_stmt", "agg_device_ms_per_stmt")
SUFFIX = ".joins"


def _entry(kind: str, name: str) -> dict:
    found = [e for e in BM[kind] if e["name"] == name]
    assert len(found) == 1, (kind, name)
    return found[0]


# ------------------------------------------------------------ the entries

def test_the_cell_and_its_configuration_are_entries_by_name():
    w = _entry("workloads", CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        CONFIG, "drawn-join-streams", 1)
    c = _entry("configs", CONFIG)
    cfg = C.read_json(REPO, c["file"])
    assert c["source"] == cfg["source"] and len(c["source"]) <= 200
    assert set(c["reduced"]) == set(cfg["reduced"]) == {"scale",
                                                        "statements"}
    assert cfg["statements"] == ["q3", "q12d"]
    joins = C.read_json(REPO, _entry("configs", "tpch-sf1-joins")["file"])
    for key in ("generator", "scale", "tables", "chunk_orders", "engine",
                "limits", "column_bytes", "trace_seconds"):
        assert cfg[key] == joins[key], key
    assert cfg["compare"] == {"draws_per_statement": 6}
    assert cfg["guarantees"]["approximate"] == "nothing"
    assert "every_draw_is_its_own" in cfg["guarantees"]
    assert {k: v for k, v in cfg["guarantees"].items()
            if k != "every_draw_is_its_own"} == joins["guarantees"]
    cell = C.Cell(CELL)
    named = {c for _, ref in cell.statements.values()
             for cols in ref.COLUMNS.values() for c in cols}
    assert named == set(cfg["column_bytes"])


def test_the_cell_joins_the_named_lists_and_adds_no_metric():
    cell = C.Cell(CELL)
    assert sorted(m["name"] for m in cell.end_to_end) == ["setup_s", RATE]
    assert CELL in _entry("end_to_end", RATE)["workloads"]
    assert sorted(m["name"] for m in cell.per_layer) == sorted(
        m + SUFFIX for m in METRICS)
    for m in cell.per_layer:
        assert m["moves"] == RATE
        assert m["workloads"] == [JOINS, CELL]
        assert callable(C.reader(m["name"]))
    assert len(BM["per_layer"]) == 128
    for kind in ("end_to_end", "per_layer"):
        for m in BM[kind]:
            if CELL in m.get("workloads", ()):
                assert m["workloads"][-1] == CELL, m["name"]


def test_the_mix_draws_the_spec_s_whole_grids():
    cell = C.Cell(CELL)
    mix = cell.traffic
    assert (mix["loop"], mix["streams"], mix["think_s"]) == ("closed", 2, 0)
    assert mix["order"] == [["q3", "q12d"], ["q12d", "q3"]]
    joins = C.Cell(JOINS).traffic
    assert mix["warm"] == joins["warm"]
    grids = {s: traffic.grid(mix["statements"][s]["params"])
             for s in mix["statements"]}
    assert len(grids["q3"]) == 155 and len(grids["q12d"]) == 210
    ref3, ref12 = (cell.statements[s][1] for s in ("q3", "q12d"))
    assert {ref3.bind(p)["segment"] for p in grids["q3"]} == set(
        ref3.SEGMENTS)
    assert {ref3.bind(p)["date"] for p in grids["q3"]} == {
        f"1995-03-{d:02d}" for d in range(1, 32)}
    pairs: dict = {}
    for p in grids["q12d"]:
        b = ref12.bind(p)
        assert b["shipmode1"] != b["shipmode2"]
        key = frozenset((b["shipmode1"], b["shipmode2"]))
        pairs[key] = pairs.get(key, 0) + 1
    assert len(pairs) == 21 and set(pairs.values()) == {10}  # 2 x 5 years
    assert {ref12.bind(p)["date"] for p in grids["q12d"]} == {
        f"{y}-01-01" for y in range(1993, 1998)}


def test_q12d_is_q12s_text_and_answer():
    """The statement is Q12's text; the reference is Q12's answer with the
    second mode numbered from the first."""
    cell = C.Cell(CELL)
    q12 = C.Cell(JOINS).statements["q12"]
    assert cell.statements["q12d"][0] == q12[0]
    ref = cell.statements["q12d"][1]
    assert ref.bind({"shipmode1": 5, "apart": 5, "year": 1994}) == \
        q12[1].bind({"shipmode1": 5, "shipmode2": 3, "year": 1994})
    for name in ("TABLES", "COLUMNS", "bind", "answer"):
        assert hasattr(ref, name)
    with open(os.path.join(C.BENCH, "reference", "q12d.py"),
              encoding="utf-8") as f:
        text = f.read()
    assert "cloudberry" not in text and "import jax" not in text


# -------------------------------------------------------- the rehearsal

@pytest.fixture(scope="module")
def rehearsals():
    """{traced: (exit code, the printed line)}: each rehearsal as the
    driver runs the cell, a process of its own, a large seed."""
    out = {}
    for traced in (0, 1):
        p = subprocess.run(
            [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
             "--workload", CELL, "--seed", str(2**31 + 4507), "--seconds",
             "4", "--trace", str(traced), "--rehearse-scale", "0.01"],
            capture_output=True, text=True, timeout=900, cwd=REPO)
        assert p.stdout.strip(), p.stderr[-4000:]
        out[traced] = p.returncode, json.loads(p.stdout.splitlines()[-1])
    return out


@pytest.mark.parametrize("traced", [0, 1])
def test_the_cell_rehearses_to_a_line_the_validator_accepts(rehearsals,
                                                            traced):
    code, line = rehearsals[traced]
    cell = C.Cell(CELL)
    assert code == 3 and line["rehearsal"] is True
    assert lastline.problems(line, cell.metrics(bool(traced)), bool(traced),
                             platform=None) == []
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 12
    assert line["compared"]["not_compared"] == [0, 0]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    if not traced:
        assert sorted(got) == ["setup_s", RATE]
        return
    assert sorted(got) == sorted(m + SUFFIX for m in METRICS)
    assert got["compiles_in_window.joins"] == 0
    # every send of a new draw is parsed and planned again
    assert got["plan_ms.joins"] > 0
    for name in ("request_ms", "launch_ms", "device_ms_per_stmt"):
        assert got[name + SUFFIX] > 0, name


def test_another_draws_reference_is_not_correct(monkeypatch):
    """Q12d's reference answers the draw one mode along from the one the
    text was sent with (the table an earlier send bound, had it been
    kept): the window's answers differ from it and the line is not
    correct, by ``wrong_values``."""
    from benchmarks import run as R

    load = C.load_module

    def swapped(kind, name, bench=C.BENCH):
        mod = load(kind, name, bench)
        if (kind, name) == ("reference", "q12d"):
            answer = mod.answer
            mod.answer = lambda tables, params, acc=np.int64: answer(
                tables, {**params,
                         "shipmode1": (int(params["shipmode1"]) + 1) % 7},
                acc)
        return mod

    monkeypatch.setattr(C, "load_module", swapped)
    code, line = R.run(argparse.Namespace(
        workload=CELL, seed=2**31 + 4508, seconds=3.0, trace=0,
        rehearse_scale=0.01))
    assert code == 3 and line["failed"] == 0
    assert line["correct"] is False
    number, limit = line["compared"]["wrong_values"]
    assert number > limit == 0
