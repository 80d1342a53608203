"""The benchmark's Q13 cell (ISSUE 34) in tier-1: the cell
``tpch-sf1-custdist.custdist-streams`` resolves by name and a traced CPU
rehearsal, a process of its own as the driver runs it, prints every
metric the cell lists; the window's own answers equal
``benchmarks/reference/q13.py``, every launch is one expansion join, and
the aggregates emit under the capacity their rows arrive at.
``benchmarks/tests/test_custdist_cell.py`` holds the cell's other tests
(its entries, Q18's reference, the guarantees broken in turn)."""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import cell as C, lastline        # noqa: E402

CELL = "tpch-sf1-custdist.custdist-streams"


def test_the_cell_rehearses_traced_to_every_listed_metric():
    cell = C.Cell(CELL)
    assert cell.chips == 1 and sorted(cell.statements) == ["q13"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # one CPU device, as one chip
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 34), "--seconds", "3",
         "--trace", "1", "--rehearse-scale", "0.02"],
        capture_output=True, text=True, timeout=600, cwd=REPO, env=env)
    assert p.returncode == 3 and p.stdout.strip(), p.stderr[-4000:]
    line = json.loads(p.stdout.splitlines()[-1])
    assert lastline.problems(line, cell.metrics(True), True,
                             platform=None) == []
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["not_compared"] == [0, 0]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert sorted(got) == sorted(m["name"] for m in cell.per_layer)
    assert got["expand_joins_per_stmt.custdist"] == 1.0
    assert 0 < got["agg_capacity_pct.custdist"] < 100
    assert got["compiles_in_window.custdist"] == 0
