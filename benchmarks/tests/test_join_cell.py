"""Tests of what PR 32 adds to the benchmark: the one-segment join cell
``tpch-sf1-joins.join-streams`` rehearsed end to end on the CPU, the two
new references (Q12, the cell's; Q13, which left the mix and stands beside
it as data for a follow-up) against ``tools/tpch_oracle.py``'s pandas oracles, the
control, and its new readers. Every entry is looked up by NAME: an entry
a later PR appends after these moves nothing here.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_join_cell.py -q -p no:cacheprovider
"""

from __future__ import annotations

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

from benchmarks import control                                # noqa: E402
from benchmarks.harness import cell as C, compare, lastline   # noqa: E402
from benchmarks.harness.client import Send                    # noqa: E402
from benchmarks.harness.reading import Reading                # noqa: E402

CELL = "tpch-sf1-joins.join-streams"
CONFIG = "tpch-sf1-joins"
DRAWS = {"q3": {"segment": 1, "day": 15},
         "q12": {"shipmode1": 5, "shipmode2": 3, "year": 1994},
         "q13": {"word1": 0, "word2": 1}}
BM = C.read_json(REPO, "BENCHMARK.json")
RATE = "stmt_per_s.outofcore"
METRICS = ("request_ms", "render_ms", "plan_ms", "bind_ms",
           "compiles_in_window", "launch_ms", "inputs_ms", "dispatch_ms",
           "device_wait_ms", "fetch_ms", "d2h_reads_per_stmt",
           "device_ms_per_stmt", "device_idle_pct", "idle_attributed_pct",
           "scan_roofline", "scan_pad_pct", "lookup_joins_per_stmt",
           "admit_ms",
           "host_offcpu_ms")


def _entry(kind: str, name: str) -> dict:
    found = [e for e in BM[kind] if e["name"] == name]
    assert len(found) == 1, (kind, name)
    return found[0]


# ------------------------------------------------------------ the entries

def test_the_cell_and_its_configuration_are_entries_by_name():
    w = _entry("workloads", CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        CONFIG, "join-streams", 1)
    c = _entry("configs", CONFIG)
    cfg = C.read_json(REPO, c["file"])
    assert c["source"] == cfg["source"] and len(c["source"]) <= 200
    assert set(c["reduced"]) == set(cfg["reduced"]) == {
        "scale", "statements", "substitution_parameters"}
    assert cfg["statements"] == ["q3", "q12"]
    assert cfg["engine"] == {"n_segments": 1, "overrides": {
        "storage.rows_per_partition": 1048576}}
    resident = C.read_json(REPO, "benchmarks/configs/tpch-sf1-resident.json")
    assert cfg["limits"] == resident["limits"]
    assert cfg["guarantees"]["approximate"] == "nothing"
    for key in ("published", "assumed", "guarantees", "column_bytes"):
        assert cfg[key]
    cell = C.Cell(CELL)
    named = {c for _, ref in cell.statements.values()
             for cols in ref.COLUMNS.values() for c in cols}
    assert named == set(cfg["column_bytes"])


def test_the_cell_reports_the_outofcore_rate_and_set_up():
    cell = C.Cell(CELL)
    assert sorted(m["name"] for m in cell.end_to_end) == [
        "setup_s", RATE]
    assert CELL in _entry("end_to_end", RATE)["workloads"]
    assert _entry("end_to_end", RATE)["bound"] == 0.18
    assert sorted(m["name"] for m in cell.per_layer) == sorted(
        m + ".joins" for m in METRICS)
    for m in cell.per_layer:
        assert m["moves"] == RATE and m["workloads"] == [CELL]
        base = m["name"].split(".", 1)[0]
        older = [e for e in BM["per_layer"]
                 if e["name"].split(".", 1)[0] == base
                 and not e["name"].endswith(".joins")]
        if older:       # a layer BENCHMARK.json already names: its name
            assert m["layer"] in {e["layer"] for e in BM["per_layer"]
                                  if not e["name"].endswith(".joins")}
            assert m["unit"] == older[0]["unit"]
        assert callable(C.reader(m["name"]))


def test_the_mix_holds_the_validation_values():
    cell = C.Cell(CELL)
    mix = cell.traffic
    assert (mix["loop"], mix["streams"], mix["think_s"]) == ("closed", 2, 0)
    assert mix["order"] == [["q3", "q12"], ["q12", "q3"]]
    assert sorted(mix["statements"]) == ["q12", "q3"]
    for stmt in mix["statements"]:
        params, grid = DRAWS[stmt], mix["statements"][stmt]["params"]
        assert {k: v["range"] for k, v in grid.items()} == {
            k: [v, v] for k, v in params.items()}
    bound = {s: ref.bind(DRAWS[s]) for s, (_, ref) in
             cell.statements.items()}
    bound["q13"] = C.load_module("reference", "q13").bind(DRAWS["q13"])
    assert bound["q3"] == {"segment": "BUILDING", "date": "1995-03-15"}
    assert bound["q12"] == {"shipmode1": "MAIL", "shipmode2": "SHIP",
                            "date": "1994-01-01"}
    assert bound["q13"] == {"word1": "special", "word2": "requests"}
    motion = C.read_json(C.BENCH, "traffic", "motion.json")
    assert mix["warm"] == motion["warm"]
    rows = {"lineitem": 6_001_215, "orders": 1_500_000, "customer": 150_000}
    assert cell.scanned_bytes("q3", rows) == (
        10 * 6_001_215 + 9 * 1_500_000 + 4 * 150_000)
    assert cell.scanned_bytes("q12", rows) == 10 * 6_001_215 + 4 * 1_500_000


# --------------------------------------- the references and the oracles

def _truth_tables(cell, scale: float, seed: int) -> dict:
    """The generator's arrays the cell's references read (as
    ``test_motion_cell._truth_tables``: ``control.truth_tables`` cannot
    make a mix whose tables have two drivers)."""
    from benchmarks.datagen import tpch
    from benchmarks.harness.load import compact

    keep = cell.reference_columns()
    # (and Q13's, whose statement and reference stand beside the cell's:
    # it left the mix by the room a cold first run has)
    for t, cols in C.load_module("reference", "q13").COLUMNS.items():
        keep.setdefault(t, set()).update(cols)
    parts: dict = {t: {c: [] for c in cols} for t, cols in keep.items()}
    for driver in dict.fromkeys(tpch.DRIVER[t] for t in keep):
        for i, lo, hi in tpch.chunk_ranges(driver, scale, 5000):
            chunk = tpch.CHUNK_FN[driver](seed, i, lo, hi, scale)
            for t in set(chunk) & set(parts):
                for c, acc in parts[t].items():
                    acc.append(compact(chunk[t][c]))
    return {t: {c: np.concatenate(v) for c, v in cols.items()}
            for t, cols in parts.items()}


@pytest.fixture(scope="module")
def truth():
    cell = C.Cell(CELL)
    return cell, _truth_tables(cell, 0.02, 2**31 + 9)


def _frames(tables: dict) -> dict:
    import pandas as pd

    epoch = np.datetime64("1970-01-01", "D")
    out = {}
    for t, cols in tables.items():
        df = pd.DataFrame({c: (v.astype(object) if v.dtype.kind == "U"
                               else v) for c, v in cols.items()})
        for c in df.columns:
            if c.endswith("date"):
                df[c] = (epoch + cols[c].astype(np.int64)).astype(
                    "datetime64[ns]")
        out[t] = df
    return out


@pytest.mark.parametrize("stmt", ["q12", "q13"])
def test_a_new_reference_equals_the_pandas_oracle(truth, stmt):
    from tools.tpch_oracle import q12, q13

    cell, tables = truth
    ref = C.load_module("reference", stmt).answer(tables, DRAWS[stmt])
    want = {"q12": q12, "q13": q13}[stmt](_frames(tables))
    assert list(want.columns) == ref["columns"]
    assert [[v.item() if hasattr(v, "item") else v for v in row]
            for row in want.values.tolist()] == ref["rows"]
    assert len(ref["rows"]) >= (2 if stmt == "q12" else 10)
    if stmt == "q13":       # the outer join's zero-order customers
        assert ref["rows"][0][0] == 0 or any(r[0] == 0 for r in ref["rows"])


def test_q13s_pattern_is_a_plain_string_search():
    like = C.load_module("reference", "q13").like
    got = like(np.asarray([
        "special requests final ideas", "requests special final ideas",
        "even special bold requests", "specialrequests", "special",
        "requests packages special deposits"]), "special", "requests")
    assert got.tolist() == [True, False, True, True, False, False]


def test_the_references_import_nothing_of_the_engine():
    for stmt in ("q12", "q13"):
        with open(os.path.join(C.BENCH, "reference", stmt + ".py"),
                  encoding="utf-8") as f:
            text = f.read()
        assert "cloudberry" not in text and "import jax" not in text
        ref = C.load_module("reference", stmt)
        for name in ("TABLES", "COLUMNS", "bind", "answer"):
            assert hasattr(ref, name)


# ---------------------------------------------------------- the control

@pytest.mark.parametrize("how", sorted(control.CONTROLS))
def test_the_control_fails_the_cell(truth, how):
    """Q3's money sums carry the control: Q12's are counts of a few
    thousand, which a narrower type holds exactly."""
    cell, tables = truth
    assert control.control_run(cell, tables, 5, np.int64)["correct"] is True
    broken = control.control_run(cell, tables, 5, control.CONTROLS[how])
    assert broken["correct"] is False
    ref = cell.statements["q3"][1]
    sound = ref.answer(tables, DRAWS["q3"])
    wrong, ulps = compare.gap(
        ref.answer(tables, DRAWS["q3"], acc=control.CONTROLS[how]), sound)
    assert wrong > 0 or max(ulps.values(), default=0.0) > \
        cell.config["limits"]["sum_gap_ulps"]


def test_a_count_off_by_one_and_a_missing_row_are_not_correct(truth):
    import copy

    cell, tables = truth
    refs = {s: ref for s, (_, ref) in cell.statements.items()}
    sound = {s: refs[s].answer(tables, DRAWS[s]) for s in refs}

    def verdict(answers):
        sends = [Send(0, s, DRAWS[s], 0.0, 1.0, answer=a)
                 for s, a in answers.items()]
        return compare.compare(sends, refs, tables, cell.config["limits"],
                               2, seed=11)

    assert verdict(sound)["correct"] is True
    off = copy.deepcopy(sound)
    off["q12"]["rows"][0][1] += 1            # one line in the wrong bucket
    assert verdict(off)["correct"] is False
    lost = copy.deepcopy(sound)              # a mode's row left out
    del lost["q12"]["rows"][-1]
    v = verdict(lost)
    assert v["correct"] is False and v["compared"]["wrong_values"][0] > 0


# ------------------------------------------------------------ the readers

def _reading(counters=None, hists=None):
    snap = lambda c, h: {"counters": c, "hists": h, "jax_compiles": {}}
    return Reading(before=snap({}, {}),
                   after=snap(counters or {}, hists or {}), sends=[],
                   t_open=0.0, t_close=51.0, cell=C.Cell(CELL),
                   rows={"lineitem": 6_001_215, "orders": 1_500_000,
                         "customer": 150_000},
                   device={"platform": "tpu", "kind": "TPU v5 lite",
                           "count": 1},
                   peaks=C.read_json(C.BENCH, "peaks.json"))


def test_the_counter_readers_and_what_a_parent_reads():
    r = _reading({"launch_joins_lookup": 45,
                  "scan_rows": 9_950_000, "scan_capacity_rows": 10_000_000},
                 {"statement_seconds": (30, 60.0)})
    assert C.reader("lookup_joins_per_stmt.joins")(r) == 1.5
    assert C.reader("scan_pad_pct.joins")(r) == pytest.approx(0.5)
    # (a file of its own, as the four-segment cell's is)
    assert C.reader("lookup_joins_per_stmt.joins") is not \
        C.reader("lookup_joins_per_stmt.4seg")
    # a program without the counters (the parent): 0.0, since the line
    # may not leave a listed metric out; without a trace the device
    # readers give nothing
    parent = _reading({}, {"statement_seconds": (30, 60.0)})
    for name in ("lookup_joins_per_stmt", "scan_pad_pct"):
        assert C.reader(name + ".joins")(parent) == 0.0
    assert C.reader("scan_roofline.joins")(parent) is None


# -------------------------------------------------------- the rehearsal

@pytest.fixture(scope="module")
def rehearsals():
    """{traced: (exit code, the printed line)}: each rehearsal as the
    driver runs the cell, a process of its own."""
    out = {}
    for traced in (0, 1):
        p = subprocess.run(
            [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
             "--workload", CELL, "--seed", str(2**31 + 91), "--seconds", "4",
             "--trace", str(traced), "--rehearse-scale", "0.05"],
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
    assert line["attempted"] >= 6
    got = {k: v["value"] for k, v in line["metrics"].items()}
    if not traced:
        assert sorted(got) == ["setup_s", RATE]
        return
    assert sorted(got) == sorted(m + ".joins" for m in METRICS)
    assert got["compiles_in_window.joins"] == 0
    assert got["plan_ms.joins"] == 0.0      # the statement cache serves
    for name in ("request_ms", "launch_ms", "inputs_ms", "dispatch_ms",
                 "device_wait_ms", "fetch_ms", "device_ms_per_stmt"):
        assert got[name + ".joins"] > 0, name
    # Q3 two lookups, Q12 one, sent equally often
    assert got["lookup_joins_per_stmt.joins"] == pytest.approx(1.5, abs=0.1)
    assert 0 < got["scan_pad_pct.joins"] < 3.2
    assert 0 < got["scan_roofline.joins"] < 100
