"""The reader of ``d2h_reads_per_stmt`` (PR 27) and its entry.

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
    (({"launch_d2h_reads": 4}, {"statement_seconds": (10, 0.1)}),
     ({"launch_d2h_reads": 4}, {"statement_seconds": (10, 0.1)}), 0.0),
    (({"launch_d2h_reads": 4}, {}), ({"launch_d2h_reads": 9}, {}), 0.0),
    # the quotient of what the window added: Q1 2 reads, Q6 1
    (({"launch_d2h_reads": 30}, {"statement_seconds": (20, 0.2)}),
     ({"launch_d2h_reads": 180}, {"statement_seconds": (120, 1.4)}), 1.5),
    (({}, {}),
     ({"launch_d2h_reads": 7}, {"statement_seconds": (7, 0.1)}), 1.0),
], ids=["no_counter", "nothing_answered", "no_histogram", "q1_and_q6",
        "one_read_each"])
def test_reader_gives_reads_per_statement_answered(before, after, want):
    read = C.reader("d2h_reads_per_stmt")
    got = read(_reading(before, after))
    assert isinstance(got, float) and got == want


def test_the_entry_is_the_launch_layers_and_the_resident_cells_only():
    bm = C.read_json(REPO, "BENCHMARK.json")
    entry = bm["per_layer"][-1]          # appended, nothing moved
    launch = next(m for m in bm["per_layer"] if m["name"] == "launch_ms")
    assert entry == {"name": "d2h_reads_per_stmt", "unit": "count",
                     "better": "lower", "source": "program_counter",
                     "layer": launch["layer"], "moves": "lat_p50_ms",
                     "workloads": [RESIDENT]}
    assert [m["name"] for m in bm["per_layer"]].count(
        "d2h_reads_per_stmt") == 1
    names = [m["name"] for m in C.Cell(RESIDENT).per_layer]
    assert "d2h_reads_per_stmt" in names
    other = [w["name"] for w in bm["workloads"] if w["name"] != RESIDENT]
    for name in other:
        assert "d2h_reads_per_stmt" not in [
            m["name"] for m in C.Cell(name).per_layer]
