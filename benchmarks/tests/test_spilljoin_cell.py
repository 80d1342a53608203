"""Tests of the configuration ``tpch-sf1-spilljoin`` and its one cell
``tpch-sf1-spilljoin.spill-join-streams`` (Q3 and Q4 under Cloudberry's
default 125 MB statement memory: each join's build streams into a
direct-address table before the probe side streams): the entries found
by name, the mix, the tables and columns a run loads, Q4's plain
reference, and the two guarantees the build stream holds, each broken in
turn on the plain reference put in the program's place (one lineitem
tile left out of Q4's build; a Q3 payload taken from another order).
Then the cell rehearsed end to end on the CPU as ``benchmarks/run.py``
runs it. Every entry is looked up by NAME.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_spilljoin_cell.py -q -p no:cacheprovider
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

from benchmarks.harness import cell as C, compare, lastline   # noqa: E402
from benchmarks.harness.client import Send                    # noqa: E402

CELL = "tpch-sf1-spilljoin.spill-join-streams"
CONFIG = "tpch-sf1-spilljoin"
RATE = "stmt_per_s.outofcore"
PER_LAYER = ("compiles_in_window.outofcore", "device_idle_pct.outofcore")
BM = C.read_json(REPO, "BENCHMARK.json")
DRAWS = {"q3": {"segment": 1, "day": 15}, "q4": {"year": 1993, "month": 7}}


def _entry(kind: str, name: str) -> dict:
    found = [e for e in BM[kind] if e["name"] == name]
    assert len(found) == 1, (kind, name)
    return found[0]


# ------------------------------------------------------------ the entries

def test_the_cell_and_its_configuration_are_entries_by_name():
    w = _entry("workloads", CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        CONFIG, "spill-join-streams", 1)
    assert len(w["why"]) <= 200
    c = _entry("configs", CONFIG)
    cfg = C.read_json(REPO, c["file"])
    assert c["file"] == "benchmarks/configs/tpch-sf1-spilljoin.json"
    assert c["source"] == cfg["source"] and len(c["source"]) <= 200
    assert set(c["reduced"]) == set(cfg["reduced"]) == {
        "scale", "statements", "substitution_parameters"}
    assert cfg["statements"] == ["q3", "q4"] and cfg["architecture"] is None
    outofcore = C.read_json(REPO, "benchmarks/configs/tpch-sf1-outofcore.json")
    joins = C.read_json(REPO, "benchmarks/configs/tpch-sf1-joins.json")
    # Cloudberry's defaults: 125 MB of statement memory and of pool
    assert cfg["engine"] == outofcore["engine"]
    assert cfg["engine"]["overrides"]["resource.query_mem_bytes"] \
        == 131_072_000
    for key in ("limits", "scale", "tables", "chunk_orders", "trace_seconds",
                "compare"):
        assert cfg[key] == joins[key], key
    assert {"exact", "padding_is_never_data", "build_is_whole",
            "no_slot_is_shared", "checksums", "approximate"} == set(
        cfg["guarantees"])
    assert cfg["guarantees"]["approximate"] == "nothing"
    named = {c for stmt in DRAWS
             for cols in C.load_module("reference", stmt).COLUMNS.values()
             for c in cols}
    assert named == set(cfg["column_bytes"])
    assert all(v["why"] for v in cfg["column_bytes"].values())


def test_the_cell_reports_the_outofcore_rate_set_up_and_two_layers():
    cell = C.Cell(CELL)
    assert sorted(m["name"] for m in cell.end_to_end) == ["setup_s", RATE]
    assert _entry("end_to_end", RATE)["workloads"][-1] == CELL
    # no per-layer entry of its own (128 of 128): two that list their
    # cells have this one appended, each moving the rate it reports
    assert sorted(m["name"] for m in cell.per_layer) == sorted(PER_LAYER)
    for name in PER_LAYER:
        m = _entry("per_layer", name)
        assert m["workloads"][-1] == CELL and m["moves"] == RATE
    assert len(BM["per_layer"]) <= 128


def test_the_mix_holds_the_validation_values():
    cell = C.Cell(CELL)
    mix = cell.traffic
    assert (mix["loop"], mix["streams"], mix["think_s"]) == ("closed", 2, 0)
    assert mix["order"] == [["q3", "q4"], ["q4", "q3"]]
    assert sorted(cell.statements) == ["q3", "q4"]
    for stmt, draw in DRAWS.items():
        assert mix["statements"][stmt]["params"] == {
            p: {"range": [v, v]} for p, v in draw.items()}
    text, ref = cell.statements["q4"]
    sql = text.format(**ref.bind(DRAWS["q4"]))
    assert "date '1993-07-01'" in sql and "exists" in sql


def test_the_cell_loads_the_three_tables_and_the_references_columns():
    cell = C.Cell(CELL)
    assert cell.tables() == ["lineitem", "orders", "customer"]
    assert cell.reference_columns() == {
        "customer": {"c_custkey", "c_mktsegment"},
        "orders": {"o_orderkey", "o_custkey", "o_orderdate",
                   "o_shippriority", "o_orderpriority"},
        "lineitem": {"l_orderkey", "l_extendedprice", "l_discount",
                     "l_shipdate", "l_commitdate", "l_receiptdate"}}


# ------------------------------------------- the reference and its breaks

def _truth_tables(scale: float, seed: int) -> dict:
    """The generator's arrays the two references read."""
    from benchmarks.datagen import tpch
    from benchmarks.harness.load import compact

    keep = C.Cell(CELL).reference_columns()
    parts = {t: {c: [] for c in cols} for t, cols in keep.items()}
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
    return C.Cell(CELL), _truth_tables(0.02, 2**31 + 4111)


def test_q4_reference_against_a_row_by_row_count(truth):
    _, tables = truth
    od, li = tables["orders"], tables["lineitem"]
    late = set(li["l_orderkey"][li["l_commitdate"] < li["l_receiptdate"]]
               .tolist())
    lo, hi = (int((np.datetime64(d) - np.datetime64("1970-01-01"))
                  .astype(int)) for d in ("1993-07-01", "1993-10-01"))
    counts: dict = {}
    for k, d, p in zip(od["o_orderkey"].tolist(), od["o_orderdate"].tolist(),
                       od["o_orderpriority"].tolist()):
        if lo <= d < hi and k in late:
            counts[p] = counts.get(p, 0) + 1
    ans = C.load_module("reference", "q4").answer(tables, DRAWS["q4"])
    assert ans["columns"] == ["o_orderpriority", "order_count"]
    assert ans["rows"] == [[p, counts[p]] for p in sorted(counts)]
    assert len(ans["rows"]) == 5


def _verdict(cell, tables: dict, stmt: str, answer: dict) -> dict:
    refs = {s: ref for s, (_, ref) in cell.statements.items()}
    sends = [Send(i, stmt, DRAWS[stmt], 0.0, 1.0, answer=answer)
             for i in range(2)]
    return compare.compare(sends, refs, tables, cell.config["limits"],
                           2, seed=11)


def _a_lineitem_tile_left_out(ref, tables):
    """``build_is_whole`` broken: Q4's build without one tile of
    lineitem (4,096 rows, the least tile), as a table the build stream
    finished before its last tile came would hold."""
    li = tables["lineitem"]
    keep = np.ones(len(li["l_orderkey"]), dtype=bool)
    keep[len(keep) // 2:len(keep) // 2 + 4096] = False
    short = {**tables, "lineitem": {c: v[keep] for c, v in li.items()}}
    return ref.answer(short, DRAWS["q4"])


def _a_payload_from_another_order(ref, tables):
    """``no_slot_is_shared`` broken: the first row's ``o_orderdate`` is
    another order's, as a slot that a second order's row overwrote would
    give it."""
    sound = ref.answer(tables, DRAWS["q3"])
    rows = [list(r) for r in sound["rows"]]
    other = next(r[2] for r in rows[1:] if r[2] != rows[0][2])
    rows[0][2] = other
    return {**sound, "rows": rows}


BROKEN = {"q4": _a_lineitem_tile_left_out,
          "q3": _a_payload_from_another_order}


@pytest.mark.parametrize("stmt", sorted(BROKEN))
def test_a_broken_guarantee_is_not_correct(truth, stmt):
    cell, tables = truth
    ref = cell.statements[stmt][1]
    sound = ref.answer(tables, DRAWS[stmt])
    assert len(sound["rows"]) >= 2
    assert _verdict(cell, tables, stmt, sound)["correct"] is True
    broken = BROKEN[stmt](ref, tables)
    assert broken["rows"] != sound["rows"]
    v = _verdict(cell, tables, stmt, broken)
    over = {k for k, (n, limit) in v["compared"].items() if n > limit}
    assert v["correct"] is False and over == {"wrong_values"}


# -------------------------------------------------------- the rehearsal

@pytest.fixture(scope="module")
def rehearsals():
    """{traced: (exit code, the printed line)}: each rehearsal as the
    benchmark runs the cell, a process of its own, a large seed."""
    out = {}
    for traced in (0, 1):
        p = subprocess.run(
            [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
             "--workload", CELL, "--seed", str(2**31 + 4127), "--seconds",
             "4", "--trace", str(traced), "--rehearse-scale", "0.05"],
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
    assert line["attempted"] >= 4
    assert line["compared"]["not_compared"] == [0, 0]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    if not traced:
        assert sorted(got) == ["setup_s", RATE]
        return
    assert sorted(got) == sorted(PER_LAYER)
    assert got["compiles_in_window.outofcore"] == 0
