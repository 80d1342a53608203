"""The reader of ``compacted_joins_per_stmt.joins`` (PR 33) and its entry.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import cell as C                      # noqa: E402
from benchmarks.harness.reading import Reading                # noqa: E402

JOINS = "tpch-sf1-joins.join-streams"
METRIC = "compacted_joins_per_stmt.joins"


def _reading(before, after):
    snap = lambda c, h: {"counters": c, "hists": h, "jax_compiles": {}}
    return Reading(before=snap(*before), after=snap(*after), sends=[],
                   t_open=0.0, t_close=51.0, cell=None, rows={},
                   device={}, peaks={})


@pytest.mark.parametrize("before, after, want", [
    # a program without the counter (the parent): 0.0, not left out
    (({"launch_joins_lookup": 6}, {"statement_seconds": (4, 0.1)}),
     ({"launch_joins_lookup": 42}, {"statement_seconds": (28, 99.3)}), 0.0),
    # no statement answered inside the window
    (({"launch_joins_compacted": 6}, {"statement_seconds": (4, 0.1)}),
     ({"launch_joins_compacted": 6}, {"statement_seconds": (4, 0.1)}), 0.0),
    (({"launch_joins_compacted": 6}, {}),
     ({"launch_joins_compacted": 9}, {}), 0.0),
    # the quotient of what the window added: Q3 two, Q12 one
    (({"launch_joins_compacted": 6}, {"statement_seconds": (4, 9.0)}),
     ({"launch_joins_compacted": 78}, {"statement_seconds": (52, 60.0)}),
     1.5),
    (({}, {}),
     ({"launch_joins_compacted": 2}, {"statement_seconds": (2, 0.1)}), 1.0),
], ids=["no_counter", "nothing_answered", "no_histogram", "q3_and_q12",
        "one_each"])
def test_reader_gives_compacted_joins_per_statement_answered(before, after,
                                                             want):
    got = C.reader(METRIC)(_reading(before, after))
    assert isinstance(got, float) and got == want


def test_the_entry_is_the_programs_layer_and_the_join_cells_only():
    bm = C.read_json(REPO, "BENCHMARK.json")
    entries = [m for m in bm["per_layer"] if m["name"] == METRIC]
    assert len(entries) == 1         # found by its name, wherever it stands
    entry = entries[0]
    assert entry["source"] == "program_counter"
    assert entry["moves"] == "stmt_per_s.outofcore"
    assert entry["workloads"] == [JOINS]
    assert entry["layer"] in {m["layer"] for m in bm["per_layer"]
                              if m["name"] != METRIC}
    assert METRIC in [m["name"] for m in C.Cell(JOINS).per_layer]
    assert all(METRIC not in [m["name"] for m in C.Cell(w["name"]).per_layer]
               for w in bm["workloads"] if w["name"] != JOINS)
