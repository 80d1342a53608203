"""Device: the share of the traced sub-window's idle seconds that falls
under a named host stage of the thread that ended each gap
(``harness/hostspans.py``, which prints the whole table as ``[idle]``
lines); the rest is ``between-requests``: a stream with no request
inside the server. Beside it on stderr: the window's ``xla_compiles``
against the compile events the benchmark counts itself, and every stage
histogram's milliseconds per statement answered (``[stages]``: what the
request, the launch and the feed are made of)."""

import sys


def read(r):
    if not r.trace:
        return None
    from benchmarks.harness import hostspans

    print(f"[compiles] window: xla_compiles {r.counter('xla_compiles')}, "
          f"compiles {r.counter('compiles')}, jax events "
          + str(r.after["jax_compiles"]["programs"]
                - r.before["jax_compiles"]["programs"]),
          file=sys.stderr, flush=True)
    n = r.answered() or 1
    for family in ("request_seconds", "host_offcpu_seconds",
                   "stage_seconds.", "launch_seconds.", "feed_seconds.",
                   "statement_seconds", "decode_seconds", "tile_seconds",
                   "xla_compile_seconds"):
        print("[stages] " + ", ".join(
            f"{k} {r.hist(k)[1] / n * 1e3:.4f} ms x{r.hist(k)[0] / n:.2f}"
            for k in sorted(r.after["hists"]) if k.startswith(family)),
            file=sys.stderr, flush=True)
    table = hostspans.of_reading(r)
    return hostspans.attributed_pct(table) if table else 0.0
