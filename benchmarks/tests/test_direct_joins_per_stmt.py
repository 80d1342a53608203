"""The readers of ``direct_joins_per_stmt.joins`` and ``.4seg`` (PR 37)
and their entries.

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

CELLS = {"direct_joins_per_stmt.joins": "tpch-sf1-joins.join-streams",
         "direct_joins_per_stmt.4seg": "tpch-sf1-4seg.motion"}


def _reading(before, after):
    snap = lambda c, h: {"counters": c, "hists": h, "jax_compiles": {}}
    return Reading(before=snap(*before), after=snap(*after), sends=[],
                   t_open=0.0, t_close=51.0, cell=None, rows={},
                   device={}, peaks={})


@pytest.mark.parametrize("metric", sorted(CELLS))
@pytest.mark.parametrize("before, after, want", [
    # a program without the counter (the parent): 0.0, not left out
    (({"launch_joins_lookup": 6}, {"statement_seconds": (4, 0.1)}),
     ({"launch_joins_lookup": 42}, {"statement_seconds": (28, 99.3)}), 0.0),
    # no statement answered inside the window
    (({"launch_joins_direct": 6}, {"statement_seconds": (4, 0.1)}),
     ({"launch_joins_direct": 6}, {"statement_seconds": (4, 0.1)}), 0.0),
    (({"launch_joins_direct": 6}, {}),
     ({"launch_joins_direct": 9}, {}), 0.0),
    # the quotient of what the window added: Q3 two, Q12 one
    (({"launch_joins_direct": 6}, {"statement_seconds": (4, 9.0)}),
     ({"launch_joins_direct": 78}, {"statement_seconds": (52, 60.0)}),
     1.5),
], ids=["no_counter", "nothing_answered", "no_histogram", "q3_and_q12"])
def test_reader_gives_direct_joins_per_statement_answered(metric, before,
                                                          after, want):
    got = C.reader(metric)(_reading(before, after))
    assert isinstance(got, float) and got == want


@pytest.mark.parametrize("metric", sorted(CELLS))
def test_the_entry_is_the_programs_layer_and_its_cell_only(metric):
    bm = C.read_json(REPO, "BENCHMARK.json")
    entries = [m for m in bm["per_layer"] if m["name"] == metric]
    assert len(entries) == 1         # found by its name, wherever it stands
    entry = entries[0]
    assert entry["source"] == "program_counter"
    assert entry["moves"] == "stmt_per_s.outofcore"
    assert entry["workloads"] == [CELLS[metric]]
    assert entry["layer"] in {m["layer"] for m in bm["per_layer"]
                              if m["name"] != metric}
    for w in bm["workloads"]:
        names = [m["name"] for m in C.Cell(w["name"]).per_layer]
        assert (metric in names) == (w["name"] == CELLS[metric])
