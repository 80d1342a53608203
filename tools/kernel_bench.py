"""Standalone relational-kernel benchmark — per-primitive timing.

The reference benchmarks its executor primitives outside the engine
(contrib/pax_storage's pax_gbench.cc, ic_bench.c for the transport); this
is the same stance for the TPU kernels in exec/kernels.py: time each hot
primitive — sorted-build lookup join (u64 and stats-proven u32 packing),
many-to-many expansion, sort-based grouped aggregation, sort — on whatever
backend JAX selects (it prints which), one JSON line per measurement.

Usage:
  python -m tools.kernel_bench [--build N] [--probe N] [--reps R]
  python -m tools.kernel_bench grouped-agg [--rows N] [--ladder LO,HI]
      [--reps R] [--csv PATH]

``grouped-agg`` sweeps a group-cardinality ladder (2^LO … 2^HI, default
2^4 … 2^20) through the grouped aggregation (kernels.group_aggregate),
one record per ladder point under the strategy name ``xla_sort``: a
second strategy adds its own records beside it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _setup_jax():
    import jax

    # importing the package turns jax_enable_x64 on
    from cloudberry_tpu.utils.compilecache import entry_banner

    print(f"# {entry_banner()}", file=sys.stderr)
    return jax


def _bench_loop(jax, fn, *xs, reps: int):
    out = jax.block_until_ready(fn(*xs))  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        out = jax.block_until_ready(fn(*xs))
        best = min(best, time.time() - t0)
    return best, out


def grouped_agg_sweep(args) -> None:
    """Cardinality ladder for grouped aggregation, one JSON line (and
    optional CSV row) per (groups, strategy) point."""
    jax = _setup_jax()
    import functools

    import jax.numpy as jnp
    import numpy as np

    from cloudberry_tpu.exec import kernels as K

    try:
        lo, hi = (int(x) for x in args.ladder.split(","))
        assert lo <= hi
    except (ValueError, AssertionError):
        raise SystemExit(
            f"--ladder must be LO,HI with LO <= HI (got {args.ladder!r})")
    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    n = args.rows
    specs = [K.AggSpec("sum", "s"), K.AggSpec("count", "c")]
    v = jnp.asarray(rng.integers(-10**12, 10**12, n))
    sel = jnp.ones(n, bool)
    rows_out = []
    for lg in range(lo, hi + 1, args.step):
        groups = 1 << lg
        keys = jnp.asarray(rng.integers(0, groups, n).astype(np.int64))
        cap = min(max(2 * groups, 1024), max(n, 1024))

        def make_fn(agg_fn):
            # specs/cap close over the trace: AggSpec is static config,
            # not a traced argument
            @jax.jit
            def f(k, vv, s):
                return agg_fn({"k": k}, {"s": vv, "c": None}, specs, s)
            return f

        strategies = {
            "xla_sort": make_fn(functools.partial(
                K.group_aggregate, out_capacity=cap)),
        }
        for name, fn in strategies.items():
            best, _ = _bench_loop(jax, fn, keys, v, sel, reps=args.reps)
            rec = {
                "kernel": "grouped_agg", "strategy": name,
                "groups": groups, "rows": n, "device": str(dev),
                "wall_ms": round(best * 1e3, 2),
                "mrows_per_s": round(n / best / 1e6, 1),
            }
            rows_out.append(rec)
            print(json.dumps(rec), flush=True)
    if args.csv:
        import csv

        with open(args.csv, "w", newline="") as fh:
            w = csv.DictWriter(fh, fieldnames=list(rows_out[0]))
            w.writeheader()
            w.writerows(rows_out)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", nargs="?", default="primitives",
                    choices=["primitives", "grouped-agg"])
    ap.add_argument("--build", type=int, default=1_500_000)
    ap.add_argument("--probe", type=int, default=6_000_000)
    ap.add_argument("--groups", type=int, default=4_000_000)
    ap.add_argument("--reps", type=int, default=3)
    # grouped-agg sweep knobs
    ap.add_argument("--rows", type=int, default=2_000_000,
                    help="grouped-agg: rows per measurement")
    ap.add_argument("--ladder", default="4,20",
                    help="grouped-agg: log2 group-count range LO,HI")
    ap.add_argument("--step", type=int, default=2,
                    help="grouped-agg: log2 ladder stride")
    ap.add_argument("--csv", default=None,
                    help="grouped-agg: also write a CSV table here")
    args = ap.parse_args()

    if args.mode == "grouped-agg":
        grouped_agg_sweep(args)
        return

    jax = _setup_jax()

    import jax.numpy as jnp
    import numpy as np

    from cloudberry_tpu.exec import kernels as K

    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    NB, NP = args.build, args.probe

    def bench(label, fn, *xs, rows):
        best, out = _bench_loop(jax, fn, *xs, reps=args.reps)
        print(json.dumps({
            "kernel": label, "rows": rows, "device": str(dev),
            "wall_ms": round(best * 1e3, 2),
            "mrows_per_s": round(rows / best / 1e6, 1),
        }), flush=True)
        return out

    bk = jnp.asarray(rng.permutation(NB).astype(np.int64))
    bs = jnp.ones(NB, bool)
    pk = jnp.asarray(rng.integers(0, NB, NP).astype(np.int64))
    ps = jnp.ones(NP, bool)

    for bits in (64, 32):
        bench(f"join_lookup_u{bits}",
              jax.jit(lambda b, s, p, q, _bits=bits:
                      K.join_lookup([b], s, [p], q, bits=_bits)),
              bk, bs, pk, ps, rows=NP)

    dup = jnp.asarray(rng.integers(0, NB // 8, NB).astype(np.int64))
    cap = NP + NB
    for bits in (64, 32):
        bench(f"join_expand_u{bits}",
              jax.jit(lambda b, s, p, q, _bits=bits:
                      K.join_expand([b], s, [p], q, cap, bits=_bits)),
              dup, bs, pk, ps, rows=NP)

    gk = jnp.asarray(rng.integers(0, args.groups, NP).astype(np.int64))
    gv = jnp.asarray(rng.integers(0, 1000, NP).astype(np.int64))
    bench("group_aggregate",
          jax.jit(lambda k, v, s: K.group_aggregate(
              {"k": k}, {"s": v, "c": None},
              [K.AggSpec("sum", "s"), K.AggSpec("count", "c")],
              s, args.groups)),
          gk, gv, ps, rows=NP)

    bench("sort_indices",
          jax.jit(lambda k, s: K.sort_indices([k], s)),
          pk, ps, rows=NP)


if __name__ == "__main__":
    main()
