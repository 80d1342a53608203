"""From a profiler trace to device busy time: the reduction the per-layer
device metrics read.

``busy_s`` is the union of the intervals in which an operation ran on a
device, averaged over the devices found; which planes and lines of the
``.xplane.pb`` are a device's is a table (``planes.json``) keyed by
platform. The profiler runs for a short sub-window only (the
configuration's ``trace_seconds``): a whole window of a cell that launches
thousands of small programs is too large to read inside a run's limit.
"""

from __future__ import annotations

import glob
import os
import time


def union_seconds(intervals: list) -> float:
    """Total length covered by [(start_ns, end_ns)] intervals."""
    total, cur_lo, cur_hi = 0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        elif hi > cur_hi:
            cur_hi = hi
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total / 1e9


def device_events(profile, rule: dict, lines_key: str = "lines") -> dict:
    """plane name -> [(name, start_ns, end_ns)] of the operations that ran
    on each device plane the rule selects (``lines_key="module_lines"``:
    of the whole programs instead)."""
    out: dict = {}
    for plane in profile.planes:
        if not plane.name.startswith(rule["plane_prefix"]):
            continue
        evs = []
        for line in plane.lines:
            if lines_key in rule and line.name not in rule[lines_key]:
                continue
            if "line_prefix" in rule and \
                    not line.name.startswith(rule["line_prefix"]):
                continue
            for e in line.events:
                if e.duration_ns <= 0 or \
                        e.name.startswith(rule.get("skip_prefix", "\0")):
                    continue
                evs.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
        if evs:
            out[plane.name] = evs
    return out


MARK = "bench_window"


def marked_window(profile) -> tuple | None:
    """(start_ns, end_ns) of the ``MARK`` annotation the recording thread
    held open for the whole sub-window: the sub-window on the trace's own
    clock, so device events can be cut to exactly what the host counted
    its statements over."""
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == MARK:
                    return (e.start_ns, e.start_ns + e.duration_ns)
    return None


def clip(by_plane: dict, lo: float, hi: float) -> dict:
    out = {}
    for plane, evs in by_plane.items():
        cut = [(n, max(a, lo), min(b, hi)) for n, a, b in evs
               if b > lo and a < hi]
        if cut:
            out[plane] = cut
    return out


NAME_CHARS = 100    # a TPU op's name is its whole HLO line


def top_seconds(by_plane: dict, top: int, prefix: str = "") -> list:
    """[[name, seconds]] of the names that took most time, summed over
    their events and averaged over the planes."""
    per: dict = {}
    for evs in by_plane.values():
        for name, lo, hi in evs:
            per[name] = per.get(name, 0.0) + (hi - lo) / 1e9
    n = max(len(by_plane), 1)
    return [[prefix + k[:NAME_CHARS], v / n] for k, v in
            sorted(per.items(), key=lambda kv: -kv[1])[:top]]


def reduce_events(by_plane: dict, window_s: float, top: int = 10,
                  modules: dict | None = None) -> dict:
    """busy_s (mean over planes of the union of op intervals), the
    programs and ops that took most device time, and the longest gaps
    between ops (named by the op that ended each)."""
    if not by_plane:
        return {}
    busy = [union_seconds([(lo, hi) for _, lo, hi in evs])
            for evs in by_plane.values()]
    n = len(by_plane)
    progs = top_seconds(modules or {}, 3, "program ")
    ops = progs + top_seconds(by_plane, top - len(progs))
    first = sorted(next(iter(by_plane.values())), key=lambda e: e[1])
    gaps, reach = [], None
    for name, lo, hi in first:
        if reach is not None and lo > reach:
            gaps.append([f"before {name[:NAME_CHARS]}", (lo - reach) / 1e9])
        reach = hi if reach is None else max(reach, hi)
    gaps = sorted(gaps, key=lambda kv: -kv[1])[:top]
    return {"busy_s": sum(busy) / n, "window_s": window_s,
            "device_ops": ops, "idle_gaps": gaps, "planes": n,
            "events": sum(len(v) for v in by_plane.values())}


def reduce_file(path: str, rule: dict) -> dict:
    """The reduction of one ``.xplane.pb``: device events cut to the
    marked sub-window. A trace without the mark or without a device
    plane gives {} and the device metrics are left out of the line."""
    import jax

    profile = jax.profiler.ProfileData.from_file(path)
    mark = marked_window(profile)
    if mark is None:
        return {}
    events = clip(device_events(profile, rule), *mark)
    modules = clip(device_events(profile, rule, "module_lines"), *mark) \
        if "module_lines" in rule else None
    return reduce_events(events, (mark[1] - mark[0]) / 1e9, modules=modules)


class SubWindow:
    """The profiler on for ``seconds``, from the calling thread, while the
    streams run: ``t_start``/``t_stop`` on the host clock bound it."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.t_start = self.t_stop = 0.0

    def record(self, seconds: float) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # no Python call stacks: smaller
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(MARK):
                self.t_start = time.perf_counter()
                time.sleep(seconds)
                self.t_stop = time.perf_counter()
        finally:
            jax.profiler.stop_trace()

    def xplane(self) -> str:
        found = sorted(glob.glob(os.path.join(
            self.log_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.log_dir}")
        return found[-1]
