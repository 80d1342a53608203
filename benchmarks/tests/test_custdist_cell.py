"""Tests of what PR 34 adds to the benchmark: the one-segment cell
``tpch-sf1-custdist.custdist-streams`` (Q13 alone: an outer expansion
join, a LIKE folded into a dictionary table, an aggregate of an
aggregate) rehearsed end to end on the CPU, Q18's reference (listed by
the configuration, sent by no mix yet) against pandas, the two new
readers, and the guarantees Q13 tests, each broken in turn: the
control's narrower arithmetic (``control.py``) cannot fail a statement
of small counts, so ``reference/q13.py`` is put in the program's place
with the customers without orders dropped, one pair of the expansion
cut, and the pattern's words in the other order; the store's fault
(half of every write lost) is planted on this cell and on the join cell,
whose money sums the standing test of "the last cell" used to read.
Every entry is looked up by NAME: an entry a later PR appends after these moves nothing here.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_custdist_cell.py -q -p no:cacheprovider
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

CELL = "tpch-sf1-custdist.custdist-streams"
CONFIG = "tpch-sf1-custdist"
SUFFIX = ".custdist"
DRAWS = {"q13": {"word1": 0, "word2": 1}, "q18": {"quantity": 300}}
BM = C.read_json(REPO, "BENCHMARK.json")
RATE = "stmt_per_s.outofcore"
METRICS = ("request_ms", "render_ms", "plan_ms", "bind_ms", "admit_ms",
           "compiles_in_window", "launch_ms", "inputs_ms", "dispatch_ms",
           "device_wait_ms", "fetch_ms", "d2h_reads_per_stmt",
           "device_ms_per_stmt", "device_idle_pct", "idle_attributed_pct",
           "host_offcpu_ms", "scan_roofline", "scan_pad_pct",
           "expand_joins_per_stmt", "agg_capacity_pct")


def _entry(kind: str, name: str) -> dict:
    found = [e for e in BM[kind] if e["name"] == name]
    assert len(found) == 1, (kind, name)
    return found[0]


# ------------------------------------------------------------ the entries

def test_the_cell_and_its_configuration_are_entries_by_name():
    w = _entry("workloads", CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        CONFIG, "custdist-streams", 1)
    assert len(w["why"]) <= 200
    c = _entry("configs", CONFIG)
    cfg = C.read_json(REPO, c["file"])
    assert c["source"] == cfg["source"] and len(c["source"]) <= 200
    assert set(c["reduced"]) == set(cfg["reduced"]) == {
        "scale", "statements", "substitution_parameters"}
    # q18 is listed so that a later mix may send it with no edit here
    assert cfg["statements"] == ["q13", "q18"]
    joins = C.read_json(REPO, "benchmarks/configs/tpch-sf1-joins.json")
    for key in ("engine", "limits", "scale", "tables", "chunk_orders",
                "trace_seconds", "compare", "published"):
        assert cfg[key] == joins[key], key
    for key, text in joins["guarantees"].items():
        assert cfg["guarantees"][key] == text
    assert {"outer_rows_are_kept", "no_pair_is_cut"} <= set(
        cfg["guarantees"])
    named = {c for stmt in cfg["statements"] for cols in
             C.load_module("reference", stmt).COLUMNS.values() for c in cols}
    assert named == set(cfg["column_bytes"])
    assert all(v["why"] and v["bytes"] >= 1
               for v in cfg["column_bytes"].values())


def test_the_cell_reports_the_outofcore_rate_and_set_up():
    cell = C.Cell(CELL)
    assert sorted(m["name"] for m in cell.end_to_end) == ["setup_s", RATE]
    assert CELL in _entry("end_to_end", RATE)["workloads"]
    assert sorted(m["name"] for m in cell.per_layer) == sorted(
        m + SUFFIX for m in METRICS)
    layers = {e["layer"] for e in BM["per_layer"]
              if not e["name"].endswith(SUFFIX)}
    for m in cell.per_layer:
        assert m["moves"] == RATE and m["workloads"] == [CELL]
        assert m["layer"] in layers     # a layer BENCHMARK.json names
        older = [e for e in BM["per_layer"]
                 if e["name"] == m["name"].split(".", 1)[0] + ".joins"]
        if older:
            assert {k: older[0][k] for k in ("unit", "better", "source",
                                             "layer")} == \
                {k: m[k] for k in ("unit", "better", "source", "layer")}
        assert callable(C.reader(m["name"]))
    for name in ("expand_joins_per_stmt", "agg_capacity_pct"):
        assert _entry("per_layer", name + SUFFIX)["source"] == \
            "program_counter"
        assert os.path.isfile(os.path.join(C.BENCH, "layer_metrics",
                                           name + ".py"))


def test_the_mix_holds_the_validation_values():
    cell = C.Cell(CELL)
    mix = cell.traffic
    assert (mix["loop"], mix["streams"], mix["think_s"]) == ("closed", 2, 0)
    assert mix["order"] == [["q13"], ["q13"]]
    assert sorted(mix["statements"]) == ["q13"] == sorted(cell.statements)
    grid = mix["statements"]["q13"]["params"]
    assert {k: v["range"] for k, v in grid.items()} == {
        k: [v, v] for k, v in DRAWS["q13"].items()}
    assert cell.statements["q13"][1].bind(DRAWS["q13"]) == {
        "word1": "special", "word2": "requests"}
    assert C.load_module("reference", "q18").bind(DRAWS["q18"]) == {
        "quantity": 300}
    assert mix["warm"] == C.read_json(C.BENCH, "traffic",
                                      "join-streams.json")["warm"]
    # orders and customer only: the run loads no lineitem
    assert cell.tables() == ["orders", "customer"]
    rows = {"orders": 1_500_000, "customer": 150_000}
    assert cell.scanned_bytes("q13", rows) == 9 * 1_500_000 + 3 * 150_000


# ------------------------------------------- the references and pandas

def _truth_tables(scale: float, seed: int) -> dict:
    """The generator's arrays Q13's and Q18's references read."""
    from benchmarks.datagen import tpch
    from benchmarks.harness.load import compact

    keep: dict = {}
    for stmt in DRAWS:
        for t, cols in C.load_module("reference", stmt).COLUMNS.items():
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
    return C.Cell(CELL), _truth_tables(0.02, 2**31 + 13)


@pytest.mark.parametrize("quantity", [300, 250])
def test_q18s_reference_equals_a_pandas_group_by(truth, quantity):
    import pandas as pd

    _, tables = truth
    ref = C.load_module("reference", "q18").answer(
        tables, {"quantity": quantity})
    cu, od, li = (pd.DataFrame({c: (v.astype(object) if v.dtype.kind == "U"
                                    else v) for c, v in tables[t].items()})
                  for t in ("customer", "orders", "lineitem"))
    total = li.groupby("l_orderkey")["l_quantity"].sum()
    large = total[total > 100 * quantity].rename("total").reset_index()
    j = od.merge(large, left_on="o_orderkey", right_on="l_orderkey") \
        .merge(cu, left_on="o_custkey", right_on="c_custkey")
    j = j.sort_values(["o_totalprice", "o_orderdate", "o_orderkey"],
                      ascending=[False, True, True]).head(100)
    epoch = np.datetime64("1970-01-01", "D")
    want = [[str(r.c_name), int(r.c_custkey), int(r.o_orderkey),
             str(epoch + int(r.o_orderdate)), int(r.o_totalprice) / 100,
             int(r.total) / 100] for r in j.itertuples()]
    assert ref["columns"] == ["c_name", "c_custkey", "o_orderkey",
                              "o_orderdate", "o_totalprice", "total_qty"]
    assert ref["rows"] == want
    assert len(want) >= (1 if quantity == 300 else 50)
    assert all(r[5] > quantity for r in want)


def test_q18s_control_sums_in_the_narrower_type(truth):
    """``acc`` narrows the quantity sum, as the control asks of every
    reference: in float32 the hundredths of a 300-unit sum still come
    out exact, so Q18 too will need its guarantees broken by hand."""
    _, tables = truth
    ref = C.load_module("reference", "q18")
    sound = ref.answer(tables, {"quantity": 250})
    for acc in control.CONTROLS.values():
        assert ref.answer(tables, {"quantity": 250}, acc=acc)["rows"] \
            == sound["rows"]


def test_the_references_import_nothing_of_the_engine():
    for stmt in DRAWS:
        with open(os.path.join(C.BENCH, "reference", stmt + ".py"),
                  encoding="utf-8") as f:
            text = f.read()
        assert "cloudberry" not in text and "import jax" not in text
        ref = C.load_module("reference", stmt)
        for name in ("TABLES", "COLUMNS", "bind", "answer"):
            assert hasattr(ref, name)


# ------------------------------------------- the guarantees, each broken

def _inner_join(tables: dict) -> dict:
    """``outer_rows_are_kept`` broken: customer INNER JOIN orders, so a
    customer without a counted order is no row."""
    od = tables["orders"]
    counted = ~C.load_module("reference", "q13").like(
        od["o_comment"], "special", "requests")
    has = np.isin(tables["customer"]["c_custkey"],
                  od["o_custkey"][counted])
    return {**tables, "customer": {
        c: v[has] for c, v in tables["customer"].items()}}


def _one_pair_cut(tables: dict) -> dict:
    """``no_pair_is_cut`` broken: the expansion's last pair is lost, as
    a buffer one row short would lose it."""
    od = tables["orders"]
    counted = np.flatnonzero(~C.load_module("reference", "q13").like(
        od["o_comment"], "special", "requests"))
    keep = np.ones(len(od["o_custkey"]), dtype=bool)
    keep[counted[-1]] = False
    return {**tables, "orders": {c: v[keep] for c, v in od.items()}}


def _words_swapped(tables: dict, monkeypatch) -> dict:
    """The pattern as '%requests%special%': the reference's own search,
    its two words in the other order."""
    ref = C.Cell(CELL).statements["q13"][1]
    words = ref._words
    monkeypatch.setattr(ref, "_words", lambda params: words(params)[::-1])
    return ref.answer(tables, DRAWS["q13"])


BROKEN = {
    "customers_without_orders_dropped": lambda ref, t, _: ref.answer(
        _inner_join(t), DRAWS["q13"]),
    "one_pair_of_the_expansion_cut": lambda ref, t, _: ref.answer(
        _one_pair_cut(t), DRAWS["q13"]),
    "the_patterns_words_in_the_other_order": lambda _, t, patch:
        _words_swapped(t, patch),
}


def _verdict(cell, tables: dict, answer: dict) -> dict:
    refs = {s: ref for s, (_, ref) in cell.statements.items()}
    sends = [Send(i, "q13", DRAWS["q13"], 0.0, 1.0, answer=answer)
             for i in range(2)]
    return compare.compare(sends, refs, tables, cell.config["limits"],
                           2, seed=11)


def test_the_control_cannot_fail_a_statement_of_small_counts(truth):
    cell, tables = truth
    for acc in (np.int64, *control.CONTROLS.values()):
        assert control.control_run(cell, tables, 5, acc)["correct"] is True


@pytest.mark.parametrize("how", sorted(BROKEN))
def test_a_broken_guarantee_is_not_correct(truth, how, monkeypatch):
    cell, tables = truth
    ref = cell.statements["q13"][1]
    sound = ref.answer(tables, DRAWS["q13"])
    assert _verdict(cell, tables, sound)["correct"] is True
    assert sum(r[1] for r in sound["rows"]) == len(
        tables["customer"]["c_custkey"])
    broken = BROKEN[how](ref, tables, monkeypatch)
    assert broken["rows"] != sound["rows"]
    v = _verdict(cell, tables, broken)
    assert v["correct"] is False and v["compared"]["wrong_values"][0] > 0


# --------------------------------- the store's fault: half the rows

# benchmarks/tests/test_benchmark.py plants this fault on "the last
# cell" and looks for a money sum that is short; the last cell is now
# this one, whose Q13 has no sum. So the fault is planted here on both,
# each by NAME: the join cell keeps its sums' reading, this cell shows
# what the fault does to counts alone.
HALVED = {
    "tpch-sf1-joins.join-streams": "sum_gap_ulps",
    CELL: None,
}


@pytest.mark.parametrize("name", sorted(HALVED))
def test_half_of_the_rows_left_out_is_not_correct(name, monkeypatch):
    """Half of every write never reaches the store (the generator's
    arrays keep all of it): the scans read half of each table, every
    count is short, and a cell with money sums reads them short too."""
    import argparse

    from benchmarks import run as R
    from cloudberry_tpu.storage.table_store import TableStore

    sound, calls = TableStore.append, [0]

    def half(self, table, data, schema, **kw):
        calls[0] += 1
        return sound(self, table, {c: v[:len(v) // 2]
                                   for c, v in data.items()}, schema, **kw)

    monkeypatch.setattr(TableStore, "append", half)
    code, line = R.run(argparse.Namespace(
        workload=name, seed=2**31 + 7, seconds=2.0, trace=0,
        rehearse_scale=0.05))
    assert calls[0] >= 1 and line["correct"] is False
    assert line["compared"]["wrong_values"][0] > 0      # the counts
    if HALVED[name]:
        number, limit = line["compared"][HALVED[name]]
        assert number > limit


# ------------------------------------------------------------ the readers

def _reading(counters=None, hists=None):
    snap = lambda c, h: {"counters": c, "hists": h, "jax_compiles": {}}  # noqa: E731
    return Reading(before=snap({}, {}),
                   after=snap(counters or {}, hists or {}), sends=[],
                   t_open=0.0, t_close=51.0, cell=C.Cell(CELL),
                   rows={"orders": 1_500_000, "customer": 150_000},
                   device={"platform": "tpu", "kind": "TPU v5 lite",
                           "count": 1},
                   peaks=C.read_json(C.BENCH, "peaks.json"))


def test_the_counter_readers_and_what_a_parent_reads():
    r = _reading({"launch_joins_expand": 31,
                  "launch_agg_rows_in": 31 * (1_671_168 + 151_552),
                  "launch_agg_capacity": 31 * 2 * 151_552},
                 {"statement_seconds": (31, 50.0)})
    assert C.reader("expand_joins_per_stmt.custdist")(r) == 1.0
    assert C.reader("agg_capacity_pct.custdist")(r) == pytest.approx(
        16.63, abs=0.01)
    # a program without the counters (the parent), and a window that
    # answered nothing: 0.0, since the line may not leave a listed
    # metric out
    for r in (_reading({}, {"statement_seconds": (31, 50.0)}), _reading()):
        assert C.reader("expand_joins_per_stmt.custdist")(r) == 0.0
        assert C.reader("agg_capacity_pct.custdist")(r) == 0.0


# -------------------------------------------------------- the rehearsal

@pytest.fixture(scope="module")
def rehearsals():
    """{traced: (exit code, the printed line)}: each rehearsal as the
    driver runs the cell, a process of its own, a large seed."""
    out = {}
    for traced in (0, 1):
        p = subprocess.run(
            [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
             "--workload", CELL, "--seed", str(2**31 + 134), "--seconds",
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
    assert line["attempted"] >= 6
    assert line["compared"]["not_compared"] == [0, 0]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    if not traced:
        assert sorted(got) == ["setup_s", RATE]
        return
    assert sorted(got) == sorted(m + SUFFIX for m in METRICS)
    assert got["compiles_in_window.custdist"] == 0
    assert got["plan_ms.custdist"] == 0.0   # the statement cache serves
    for name in ("request_ms", "launch_ms", "inputs_ms", "dispatch_ms",
                 "device_wait_ms", "fetch_ms", "device_ms_per_stmt"):
        assert got[name + SUFFIX] > 0, name
    assert got["expand_joins_per_stmt.custdist"] == 1.0
    assert 0 < got["agg_capacity_pct.custdist"] < 100
    assert 0 < got["scan_pad_pct.custdist"] < 3.2
    assert 0 < got["scan_roofline.custdist"] < 100
