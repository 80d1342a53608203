"""Motion: device time of the collective operations (all-to-all,
all-gather, all-reduce, collective-permute; an asynchronous pair's
``-start`` and ``-done`` halves are both the collective's, and the union
of the intervals counts an instant once) in the traced sub-window, mean
over the device planes, per statement answered in it. Read from the
profile ``run.py`` left under ``work/<cell>/trace``, cut to the
``bench_window`` mark as ``harness/trace.py`` cuts it. None without a
trace; 0.0 where the trace holds no collective (one segment; the CPU
rehearsal names its operations otherwise)."""

import glob
import os
import re

COLLECTIVE = re.compile(
    r"all-to-all|all-gather|all-reduce|collective-permute|"
    r"all_to_all|all_gather|all_reduce|collective_permute|ppermute|psum",
    re.IGNORECASE)


def collective_seconds(by_plane: dict) -> float:
    """Mean over planes of the union of the collectives' intervals."""
    from benchmarks.harness.trace import union_seconds

    if not by_plane:
        return 0.0
    return sum(union_seconds([(lo, hi) for name, lo, hi in evs
                              if COLLECTIVE.search(name)])
               for evs in by_plane.values()) / len(by_plane)


def read(r):
    stmts = sum(share for _, share in r.sub_statements())
    if not r.trace or not stmts:
        return None
    found = sorted(glob.glob(os.path.join(
        r.cell.bench, "work", r.cell.name, "trace", "plugins", "profile",
        "*", "*.xplane.pb")))
    if not found:
        return None
    import jax

    from benchmarks.harness.cell import read_json
    from benchmarks.harness.trace import clip, device_events, marked_window

    profile = jax.profiler.ProfileData.from_file(found[-1])
    mark = marked_window(profile)
    if mark is None:
        return None
    rule = read_json(r.cell.bench, "planes.json")[r.device["platform"]]
    return collective_seconds(
        clip(device_events(profile, rule), *mark)) / stmts * 1e3
