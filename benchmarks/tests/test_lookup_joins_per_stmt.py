"""The reader of ``lookup_joins_per_stmt.4seg`` (PR 31) and its entry.

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

MOTION = "tpch-sf1-4seg.motion"
METRIC = "lookup_joins_per_stmt.4seg"


def _reading(before, after):
    snap = lambda c, h: {"counters": c, "hists": h, "jax_compiles": {}}
    return Reading(before=snap(*before), after=snap(*after), sends=[],
                   t_open=0.0, t_close=51.0, cell=None, rows={},
                   device={}, peaks={})


@pytest.mark.parametrize("before, after, want", [
    # a program without the counter (the parent): 0.0, not left out
    (({}, {"statement_seconds": (10, 0.1)}),
     ({}, {"statement_seconds": (39, 99.3)}), 0.0),
    # every join of the window an expansion: the counter never moved
    (({"launch_joins_expand": 4}, {"statement_seconds": (4, 9.1)}),
     ({"launch_joins_expand": 34}, {"statement_seconds": (33, 99.3)}), 0.0),
    # no statement answered inside the window
    (({"launch_joins_lookup": 4}, {"statement_seconds": (10, 0.1)}),
     ({"launch_joins_lookup": 4}, {"statement_seconds": (10, 0.1)}), 0.0),
    (({"launch_joins_lookup": 4}, {}), ({"launch_joins_lookup": 9}, {}),
     0.0),
    # the quotient of what the window added: Q3 two lookups, the view none
    (({"launch_joins_lookup": 8}, {"statement_seconds": (8, 9.0)}),
     ({"launch_joins_lookup": 72}, {"statement_seconds": (72, 60.0)}), 1.0),
    (({}, {}),
     ({"launch_joins_lookup": 6}, {"statement_seconds": (4, 0.1)}), 1.5),
], ids=["no_counter", "expansions_only", "nothing_answered", "no_histogram",
        "q3_and_the_view", "three_in_two"])
def test_reader_gives_lookup_joins_per_statement_answered(before, after,
                                                          want):
    got = C.reader(METRIC)(_reading(before, after))
    assert isinstance(got, float) and got == want


def test_the_entry_is_the_session_layers_and_the_motion_cells_only():
    bm = C.read_json(REPO, "BENCHMARK.json")
    entries = [m for m in bm["per_layer"] if m["name"] == METRIC]
    assert len(entries) == 1         # found by its name, wherever it stands
    plan = next(m for m in bm["per_layer"] if m["name"] == "plan_ms.4seg")
    assert entries[0] == {"name": METRIC, "unit": "joins/stmt",
                          "better": "higher", "source": "program_counter",
                          "layer": plan["layer"],
                          "moves": "stmt_per_s.outofcore",
                          "workloads": [MOTION]}
    for w in bm["workloads"]:
        names = [m["name"] for m in C.Cell(w["name"]).per_layer]
        assert (METRIC in names) == (w["name"] == MOTION)
