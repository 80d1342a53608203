"""The control of the comparison that decides ``correct``: the plain
reference put in the program's place, with one guarantee of the
configuration broken, has to come out as NOT correct.

The configurations state exact answers: DECIMAL sums in int64 fixed point,
nothing approximate. The step that would tempt a later PR is narrower
arithmetic in the kernels (ROADMAP S6), so the control widens the columns
to, multiplies and sums in, a narrower type: ``float32`` (the nearest
step down that still gives a plausible answer) or ``int32`` (which wraps).
Everything else is the reference's own code, and its answers go through
``compare.compare`` as if the window had returned them.

    python3 benchmarks/control.py --workload <cell> --seeds 1 2 3 [--scale 1]

needs no chip and no engine: generator, reference and comparison only. It
prints, per seed and control, every number compared beside its limit.
``benchmarks/tests/test_benchmark.py`` keeps it as a test at a small scale.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CONTROLS = {"float32": np.float32, "int32": np.int32}


class _As:
    """A reference module whose answers are computed in ``acc``."""

    def __init__(self, ref, acc):
        self.ref, self.acc = ref, acc

    def answer(self, tables, params):
        return self.ref.answer(tables, params, acc=self.acc)


def truth_tables(cell, scale: float, seed: int) -> tuple:
    """The generator's arrays the references read, with no engine in
    sight: (rows per table, table -> column -> array)."""
    from benchmarks.datagen import tpch
    from benchmarks.harness.load import compact

    keep = cell.reference_columns()
    parts: dict = {t: {c: [] for c in cols} for t, cols in keep.items()}
    step = max(int(cell.config["chunk_orders"] * min(scale, 1.0)), 64)
    for driver in dict.fromkeys(tpch.DRIVER[t] for t in keep):
        for i, lo, hi in tpch.chunk_ranges(driver, scale, step):
            chunk = tpch.CHUNK_FN[driver](seed, i, lo, hi, scale)
            for t, cols in parts.items():
                for c, acc in cols.items():
                    acc.append(compact(chunk[t][c]))
    tables = {t: {c: np.concatenate(v) for c, v in cols.items()}
              for t, cols in parts.items()}
    return {t: len(next(iter(c.values()))) for t, c in tables.items()}, tables


def control_run(cell, tables: dict, seed: int, acc, sends_per_draw: int = 1):
    """The comparison's verdict when the control answers in the program's
    place: one send for each draw of the mix's grids."""
    from benchmarks.harness import compare, traffic
    from benchmarks.harness.client import Send

    refs = {s: ref for s, (_, ref) in cell.statements.items()}
    sends = []
    for stmt in sorted(cell.traffic["statements"]):
        for params in traffic.grid(cell.traffic["statements"][stmt]["params"]):
            ans = _As(refs[stmt], acc).answer(tables, params)
            for _ in range(sends_per_draw):
                sends.append(Send(0, stmt, params, 0.0, 1.0, answer=ans))
    return compare.compare(
        sends, refs, tables, cell.config["limits"],
        int(cell.config["compare"]["draws_per_statement"]), seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--scale", type=float, default=None)
    args = ap.parse_args(argv)
    from benchmarks.harness.cell import Cell

    cell = Cell(args.workload)
    scale = float(args.scale if args.scale is not None
                  else cell.config["scale"])
    passed = []
    for seed in args.seeds:
        rows, tables = truth_tables(cell, scale, seed)
        for name, acc in {"reference": np.int64, **CONTROLS}.items():
            v = control_run(cell, tables, seed, acc)
            print(json.dumps({"seed": seed, "answers_in": name,
                              "rows": rows, "correct": v["correct"],
                              "compared": v["compared"]}), flush=True)
            if name != "reference" and v["correct"]:
                passed.append((seed, name))
    if passed:
        print(f"CONTROL PASSED AS CORRECT: {passed}", flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
