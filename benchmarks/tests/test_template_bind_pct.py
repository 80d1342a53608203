"""The reader of ``template_bind_pct`` (PR 29) and its entry.

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

RESIDENT = "tpch-sf1-resident.scan-streams"
METRIC = "template_bind_pct"


def _reading(before, after):
    snap = lambda c, h: {"counters": c, "hists": h, "jax_compiles": {}}
    return Reading(before=snap(*before), after=snap(*after), sends=[],
                   t_open=0.0, t_close=51.0, cell=None, rows={},
                   device={}, peaks={})


@pytest.mark.parametrize("before, after, want", [
    # a program without the counter (the parent): 0.0, not left out
    (({}, {"statement_seconds": (10, 0.1)}),
     ({}, {"statement_seconds": (110, 1.3)}), 0.0),
    # no statement answered inside the window
    (({"template_binds": 4}, {"statement_seconds": (10, 0.1)}),
     ({"template_binds": 4}, {"statement_seconds": (10, 0.1)}), 0.0),
    (({"template_binds": 4}, {}), ({"template_binds": 9}, {}), 0.0),
    # the share of what the window added: three sends in four hit
    (({"template_binds": 30}, {"statement_seconds": (40, 0.2)}),
     ({"template_binds": 180}, {"statement_seconds": (240, 1.4)}), 75.0),
    (({}, {}),
     ({"template_binds": 7}, {"statement_seconds": (7, 0.1)}), 100.0),
], ids=["no_counter", "nothing_answered", "no_histogram", "three_in_four",
        "every_send"])
def test_reader_gives_the_share_of_statements_answered(before, after, want):
    got = C.reader(METRIC)(_reading(before, after))
    assert isinstance(got, float) and got == want


def test_the_entry_is_the_session_layers_and_the_resident_cells_only():
    bm = C.read_json(REPO, "BENCHMARK.json")
    entries = [m for m in bm["per_layer"] if m["name"] == METRIC]
    assert len(entries) == 1         # found by its name, wherever it stands
    plan = next(m for m in bm["per_layer"] if m["name"] == "plan_ms")
    assert entries[0] == {"name": METRIC, "unit": "%", "better": "higher",
                          "source": "program_counter",
                          "layer": plan["layer"], "moves": "stmt_per_s",
                          "workloads": [RESIDENT]}
    for w in bm["workloads"]:
        names = [m["name"] for m in C.Cell(w["name"]).per_layer]
        assert (METRIC in names) == (w["name"] == RESIDENT)
