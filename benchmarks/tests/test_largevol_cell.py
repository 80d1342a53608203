"""Tests of the configuration ``tpch-sf1-largevol`` and its one cell
``tpch-sf1-largevol.large-volume-streams`` (Q18 alone: a 1.5M-group
HAVING aggregate as a semi-join's build, three lookups, a five-key
GROUP BY sorted on the one key that determines the others, a top 100):
the entries found by name, the tables and columns a run loads, the three
readers of its own, and the guarantees Q18 tests, each broken in turn on
the plain reference put in the program's place (the control's narrower
arithmetic cannot fail a sum of a few hundred units): the HAVING at
QUANTITY - 1, a carried ``o_totalprice`` taken from another order, and
a tie of the ORDER BY resolved the other way. Then the cell rehearsed
end to end on the CPU as ``benchmarks/run.py`` runs it. Every entry is looked up by
NAME: an entry a later PR appends after these moves nothing here.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_largevol_cell.py -q -p no:cacheprovider
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

CELL = "tpch-sf1-largevol.large-volume-streams"
CONFIG = "tpch-sf1-largevol"
SUFFIX = ".largevol"
RATE = "stmt_per_s.outofcore"
BM = C.read_json(REPO, "BENCHMARK.json")
METRICS = ("request_ms", "compiles_in_window", "device_ms_per_stmt",
           "scan_roofline", "join_device_ms_per_stmt",
           "agg_device_ms_per_stmt", "sort_device_ms_per_stmt",
           "direct_joins_per_stmt", "carried_keys_per_stmt",
           "agg_sort_words_per_stmt")
OWN = ("direct_joins_per_stmt.largevol", "carried_keys_per_stmt",
       "agg_sort_words_per_stmt")     # reader files this cell brought


def _entry(kind: str, name: str) -> dict:
    found = [e for e in BM[kind] if e["name"] == name]
    assert len(found) == 1, (kind, name)
    return found[0]


# ------------------------------------------------------------ the entries

def test_the_cell_and_its_configuration_are_entries_by_name():
    w = _entry("workloads", CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        CONFIG, "large-volume-streams", 1)
    assert len(w["why"]) <= 200
    c = _entry("configs", CONFIG)
    cfg = C.read_json(REPO, c["file"])
    assert c["file"] == "benchmarks/configs/tpch-sf1-largevol.json"
    assert c["source"] == cfg["source"] and len(c["source"]) <= 200
    assert set(c["reduced"]) == set(cfg["reduced"]) == {
        "scale", "statements", "substitution_parameters"}
    assert cfg["statements"] == ["q18"] and cfg["architecture"] is None
    joins = C.read_json(REPO, "benchmarks/configs/tpch-sf1-joins.json")
    for key in ("engine", "limits", "scale", "tables", "chunk_orders",
                "trace_seconds", "compare"):
        assert cfg[key] == joins[key], key
    assert {"exact", "padding_is_never_data", "checksums", "approximate",
            "having_is_exact", "carried_keys_are_proven"} == set(
        cfg["guarantees"])
    assert cfg["guarantees"]["approximate"] == "nothing"
    named = {c for cols in C.load_module("reference", "q18").COLUMNS.values()
             for c in cols}
    assert named == set(cfg["column_bytes"])
    assert {k: v["bytes"] for k, v in cfg["column_bytes"].items()} == {
        "c_name": 3, "c_custkey": 3, "o_orderkey": 3, "o_custkey": 3,
        "o_orderdate": 2, "o_totalprice": 4, "l_orderkey": 3,
        "l_quantity": 1}
    assert all(v["why"] for v in cfg["column_bytes"].values())


def test_the_cell_reports_the_outofcore_rate_set_up_and_its_metrics():
    cell = C.Cell(CELL)
    assert sorted(m["name"] for m in cell.end_to_end) == ["setup_s", RATE]
    assert _entry("end_to_end", RATE)["workloads"][-1] == CELL
    assert sorted(m["name"] for m in cell.per_layer) == sorted(
        m + SUFFIX for m in METRICS)
    layers = {e["layer"] for e in BM["per_layer"]
              if not e["name"].endswith(SUFFIX)}
    for m in cell.per_layer:
        assert m["moves"] == RATE and m["workloads"] == [CELL]
        assert m["layer"] in layers     # a layer BENCHMARK.json names
        assert callable(C.reader(m["name"]))
    for name in OWN:
        assert os.path.isfile(os.path.join(C.BENCH, "layer_metrics",
                                           name + ".py"))
    assert len(BM["per_layer"]) <= 128


def test_the_mix_holds_the_validation_value():
    cell = C.Cell(CELL)
    mix = cell.traffic
    assert (mix["loop"], mix["streams"], mix["think_s"]) == ("closed", 2, 0)
    assert mix["order"] == [["q18"], ["q18"]]
    assert sorted(cell.statements) == ["q18"]
    assert mix["statements"]["q18"]["params"] == {
        "quantity": {"range": [300, 300]}}
    # the least warm-up that leaves nothing to compile in the window: a
    # quiet round of each stream, one staggered round, a second together
    # (one-shot, no buffer pool to fill)
    assert mix["warm"] == {"rounds": 1, "stagger_rounds": 1,
                           "max_rounds": 6, "stagger_s": [0.1],
                           "together_s": 1.0}


def test_the_cell_loads_the_three_tables_and_the_references_columns():
    cell = C.Cell(CELL)
    assert cell.tables() == ["lineitem", "orders", "customer"]
    assert cell.reference_columns() == {
        "customer": {"c_custkey", "c_name"},
        "orders": {"o_orderkey", "o_custkey", "o_orderdate",
                   "o_totalprice"},
        "lineitem": {"l_orderkey", "l_quantity"}}
    rows = {"lineitem": 6_001_215, "orders": 1_500_000,
            "customer": 150_000}
    assert cell.scanned_bytes("q18", rows) == (
        6 * 150_000 + 12 * 1_500_000 + 4 * 6_001_215)


# ------------------------------------------------------------ the readers

def _reading(counters=None, hists=None):
    snap = lambda c, h: {"counters": c, "hists": h, "jax_compiles": {}}  # noqa: E731
    return Reading(before=snap({}, {}),
                   after=snap(counters or {}, hists or {}), sends=[],
                   t_open=0.0, t_close=51.0, cell=C.Cell(CELL),
                   rows={"lineitem": 6_001_215, "orders": 1_500_000,
                         "customer": 150_000},
                   device={"platform": "tpu", "kind": "TPU v5 lite",
                           "count": 1},
                   peaks=C.read_json(C.BENCH, "peaks.json"))


NEW = ("carried_keys_per_stmt.largevol", "agg_sort_words_per_stmt.largevol",
       "direct_joins_per_stmt.largevol")


def test_the_counter_readers_and_what_a_parent_reads():
    r = _reading({"launch_agg_keys_carried": 4 * 23,
                  "launch_agg_sort_words": 2 * 23,
                  "launch_joins_direct": 3 * 23},
                 {"statement_seconds": (23, 50.0)})
    assert [C.reader(m)(r) for m in NEW] == [4.0, 2.0, 3.0]
    # a program without the counters (an older one), and a window that
    # answered nothing: 0.0, since the line may not leave a listed
    # metric out
    for r in (_reading({}, {"statement_seconds": (23, 50.0)}), _reading()):
        assert [C.reader(m)(r) for m in NEW] == [0.0, 0.0, 0.0]


# ------------------------------------------- the reference and pandas

def _truth_tables(scale: float, seed: int) -> dict:
    """The generator's arrays Q18's reference reads."""
    from benchmarks.datagen import tpch
    from benchmarks.harness.load import compact

    keep = C.load_module("reference", "q18").COLUMNS
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
    return C.Cell(CELL), _truth_tables(0.02, 2**31 + 29)


def _totals(tables: dict) -> np.ndarray:
    """Each order's lines' quantities summed, in hundredths, by row."""
    od, li = tables["orders"], tables["lineitem"]
    summed = np.zeros(int(max(od["o_orderkey"].max(),
                              li["l_orderkey"].max())) + 1, dtype=np.int64)
    np.add.at(summed, li["l_orderkey"], li["l_quantity"].astype(np.int64))
    return summed[od["o_orderkey"]]


def _a_quantity_some_order_sums_to(tables: dict) -> int:
    """The largest whole QUANTITY that an order's lines sum to exactly,
    with two orders or more over it and at most 100 at or over it: the
    HAVING at QUANTITY - 1 then adds that order to the top 100."""
    totals = _totals(tables)
    for q in range(int(totals.max()) // 100, 0, -1):
        if (totals == 100 * q).any() and (totals > 100 * q).sum() >= 2 \
                and (totals > 100 * (q - 1)).sum() <= 100:
            return q
    raise AssertionError("no order sums to a whole quantity")


def _verdict(cell, tables: dict, params: dict, answer: dict) -> dict:
    refs = {s: ref for s, (_, ref) in cell.statements.items()}
    sends = [Send(i, "q18", params, 0.0, 1.0, answer=answer)
             for i in range(2)]
    return compare.compare(sends, refs, tables, cell.config["limits"],
                           2, seed=11)


def test_the_control_cannot_fail_a_sum_of_a_few_hundred_units(truth):
    cell, tables = truth
    for acc in (np.int64, *control.CONTROLS.values()):
        assert control.control_run(cell, tables, 5, acc)["correct"] is True


def _having_one_under(ref, tables, q):
    return tables, ref.answer(tables, {"quantity": q - 1})


def _price_of_another_order(ref, tables, q):
    """``carried_keys_are_proven`` broken: the first row's carried
    ``o_totalprice`` is another order's, as a carry that no proof held
    could take it from a row of another order."""
    sound = ref.answer(tables, {"quantity": q})
    rows = [list(r) for r in sound["rows"]]
    rows[0][4] = rows[1][4]
    return tables, {**sound, "rows": rows}


def _a_tie_the_other_way(ref, tables, q):
    """The ORDER BY's tie resolved the other way: the first two orders
    given one price and one date (a tie the statement leaves open, which
    the reference breaks by the order key), then their rows swapped."""
    sound = ref.answer(tables, {"quantity": q})
    a, b = sound["rows"][0][2], sound["rows"][1][2]
    od = {c: v.copy() for c, v in tables["orders"].items()}
    ia, ib = (int(np.flatnonzero(od["o_orderkey"] == k)[0]) for k in (a, b))
    for c in ("o_totalprice", "o_orderdate"):
        od[c][ib] = od[c][ia]
    tied = {**tables, "orders": od}
    rows = list(ref.answer(tied, {"quantity": q})["rows"])
    assert [r[2] for r in rows[:2]] == sorted((a, b))
    rows[0], rows[1] = rows[1], rows[0]
    return tied, {"columns": sound["columns"], "rows": rows}


BROKEN = {
    "the_having_at_quantity_minus_one": _having_one_under,
    "a_carried_price_from_another_order": _price_of_another_order,
    "a_tie_resolved_the_other_way": _a_tie_the_other_way,
}


BROKEN_READS = {
    "the_having_at_quantity_minus_one": {"wrong_values"},
    "a_carried_price_from_another_order": {"sum_gap_ulps"},
    # (the keys differ, and the two orders' quantity sums)
    "a_tie_resolved_the_other_way": {"wrong_values", "sum_gap_ulps"},
}


@pytest.mark.parametrize("how", sorted(BROKEN))
def test_a_broken_guarantee_is_not_correct(truth, how):
    cell, tables = truth
    ref = cell.statements["q18"][1]
    q = _a_quantity_some_order_sums_to(tables)
    params = {"quantity": q}
    sound = ref.answer(tables, params)
    assert len(sound["rows"]) >= 2
    assert _verdict(cell, tables, params, sound)["correct"] is True
    at, broken = BROKEN[how](ref, tables, q)
    assert broken["rows"] != ref.answer(at, params)["rows"]
    v = _verdict(cell, at, params, broken)
    # (a price crosses the wire as a float: the gap is read in ulps)
    over = {k for k, (n, limit) in v["compared"].items() if n > limit}
    assert v["correct"] is False and over == BROKEN_READS[how]


# -------------------------------------------------------- the rehearsal

@pytest.fixture(scope="module")
def rehearsals():
    """{traced: (exit code, the printed line)}: each rehearsal as the
    benchmark runs the cell, a process of its own, a large seed."""
    out = {}
    for traced in (0, 1):
        p = subprocess.run(
            [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
             "--workload", CELL, "--seed", str(2**31 + 1801), "--seconds",
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
    assert got["compiles_in_window.largevol"] == 0
    assert got["carried_keys_per_stmt.largevol"] == 4.0
    assert got["agg_sort_words_per_stmt.largevol"] == 2.0
    assert got["direct_joins_per_stmt.largevol"] == 3.0
    assert got["request_ms.largevol"] > 0
    assert got["device_ms_per_stmt.largevol"] > 0
    assert 0 < got["scan_roofline.largevol"] < 100
