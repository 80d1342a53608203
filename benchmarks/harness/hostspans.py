"""Device idle time by host stage: what the host was doing while the chip
waited.

The engine holds a ``jax.profiler.TraceAnnotation("cbtpu:<stage>")`` open
around every stage of a request, on the thread that does the work, so a
profile carries them on its own clock, one ``/host:`` line per thread.
This module lays them against the gaps between device operations inside
the ``bench_window`` mark (``harness/trace.py``'s cut, imported).

The rule. The cell's streams are closed loops, and the chip is idle
because none of them has a program on it: every idle instant is shared
equally among the streams. A stream with a request inside the server is
charged to the innermost open ``cbtpu:`` span of the thread serving it; a
stream with none is ``between-requests`` (the answer on its way out, the
client, the next line on its way in). ``feed-wait`` is told apart by what
the statement's scan reader thread is doing: ``feed-wait:part-read``
(read, checksum, decode) or ``feed-wait:assembly`` (cutting and padding
tiles, or nothing). Should more threads than streams have a span open
(the dispatcher's worker beside a handler), they share the instant.

(ISSUE 26 proposed charging a whole gap to the thread whose dispatch
ended it. On the real profile that reads wrong twice: a pool of workers
serves each connection, so "the thread" is idle between requests that
another worker is serving; and whatever follows a dispatch on its own
thread (fetch, render, wire-out) is never before the dispatch that ends a
gap, though it delays the stream's next program as much as planning
does.)

The rows of the table sum to the window's idle seconds (window minus the
union of the device's operations), averaged over the device planes.
"""

from __future__ import annotations

import glob
import os
import sys
import time

from benchmarks.harness.trace import clip, device_events, marked_window

PREFIX = "cbtpu:"
HELPERS = ("part-read",)        # spans of a statement's helper threads
BETWEEN = "between-requests"


def host_spans(profile) -> list:
    """One list per host thread: [(stage, start_ns, end_ns, statement)]
    of its ``cbtpu:`` events. A zero-length ``cbtpu:compile`` mark carries
    the compile's ``seconds`` and is laid out backwards from its end."""
    threads = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = []
            for e in line.events:
                if not e.name.startswith(PREFIX):
                    continue
                name = e.name[len(PREFIX):].split("#", 1)[0]
                stats = dict(getattr(e, "stats", ()))
                lo, hi = e.start_ns, e.start_ns + e.duration_ns
                if name == "compile" and stats.get("seconds"):
                    lo = hi - float(stats["seconds"]) * 1e9
                spans.append((name, lo, hi, stats.get("statement_id")))
            if spans:
                threads.append(sorted(spans, key=lambda s: (s[1], -s[2])))
    return threads


def innermost(spans: list) -> list:
    """[(start_ns, end_ns, stage, statement)], disjoint and ascending: at
    each instant the span that started last among those open on the
    thread."""
    cuts = sorted({t for _, lo, hi, _ in spans for t in (lo, hi)})
    out = []
    open_, i = [], 0        # open_: (end, name, statement), spans nest
    for a, b in zip(cuts, cuts[1:]):
        while i < len(spans) and spans[i][1] <= a:
            open_.append((spans[i][2], spans[i][0], spans[i][3]))
            i += 1
        open_ = [s for s in open_ if s[0] > a]
        if open_:
            _, name, sid = open_[-1]
            if out and out[-1][2:] == (name, sid) and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name, sid)
            else:
                out.append((a, b, name, sid))
    return out


def device_gaps(events: list, lo: float, hi: float) -> list:
    """[(start_ns, end_ns)] inside [lo, hi] in which no operation ran."""
    gaps, reach = [], lo
    for _, a, b in sorted(events, key=lambda e: e[1]):
        if a > reach:
            gaps.append((reach, a))
        reach = max(reach, b)
    if hi > reach:
        gaps.append((reach, hi))
    return gaps


def attribute(profile, rule: dict, streams: int) -> dict | None:
    """{"idle_s", "by_stage": {stage: idle seconds}, "gaps": the ten
    longest as (seconds, {stage: seconds})}; None without the mark or a
    device plane."""
    mark = marked_window(profile)
    if mark is None:
        return None
    by_plane = clip(device_events(profile, rule), *mark)
    if not by_plane:
        return None
    threads = host_spans(profile)
    # change points of every thread's innermost span, in time order
    points = []
    for k, spans in enumerate(threads):
        helper = all(name in HELPERS for name, *_ in spans)
        reach = None
        for a, b, name, sid in innermost(spans):
            if reach is not None and a > reach:
                points.append((reach, k, helper, None, None))
            points.append((a, k, helper, name, sid))
            reach = b
        if reach is not None:
            points.append((reach, k, helper, None, None))
    points.sort(key=lambda p: p[0])
    by_stage: dict = {}
    longest = []
    for events in by_plane.values():
        now: dict = {}      # statement thread -> (stage, statement)
        reading = {}        # helper thread -> statement it reads for
        i = 0
        for g0, g1 in device_gaps(events, *mark):
            share: dict = {}
            t = g0
            while t < g1:
                while i < len(points) and points[i][0] <= t:
                    _, k, helper, name, sid = points[i]
                    table = reading if helper else now
                    if name is None:
                        table.pop(k, None)
                    else:
                        table[k] = sid if helper else (name, sid)
                    i += 1
                nxt = min(points[i][0], g1) if i < len(points) else g1
                each = (nxt - t) / max(streams, len(now))
                for name, sid in now.values():
                    if name == "feed-wait":
                        name += ":part-read" if sid in reading.values() \
                            else ":assembly"
                    share[name] = share.get(name, 0.0) + each
                if len(now) < streams:
                    share[BETWEEN] = share.get(BETWEEN, 0.0) \
                        + each * (streams - len(now))
                t = nxt
            for name, ns in share.items():
                by_stage[name] = by_stage.get(name, 0.0) + ns
            longest.append(((g1 - g0) / 1e9,
                            {k: v / 1e9 for k, v in share.items()}))
    n = len(by_plane)
    by_stage = {k: v / 1e9 / n for k, v in by_stage.items()}
    return {"idle_s": sum(by_stage.values()), "by_stage": by_stage,
            "gaps": sorted(longest, key=lambda g: -g[0])[:10],
            "threads": len(threads),
            "compiles": sum(1 for spans in threads for name, _, hi, _ in spans
                            if name == "compile" and mark[0] <= hi <= mark[1])}


def attributed_pct(table: dict) -> float:
    """Idle seconds under a named span over all idle seconds, in %."""
    named = sum(v for k, v in table["by_stage"].items() if k != BETWEEN)
    return 100.0 * named / table["idle_s"] if table["idle_s"] else 0.0


def of_reading(r) -> dict | None:
    """The table for a run's ``Reading``: the profile where ``run.py``
    put it (``<bench>/work/<cell>/trace``, still on disk when the readers
    run), parsed once a run and kept on the reading; printed to stderr as
    ``[idle]`` lines."""
    if "_hostspans" in r.__dict__:
        return r.__dict__["_hostspans"]
    table = None
    found = sorted(glob.glob(os.path.join(
        r.cell.bench, "work", r.cell.name, "trace", "plugins", "profile",
        "*", "*.xplane.pb")))
    if found and r.trace:
        import jax

        from benchmarks.harness.cell import read_json

        t0 = time.perf_counter()
        rule = read_json(r.cell.bench, "planes.json")[r.device["platform"]]
        table = attribute(jax.profiler.ProfileData.from_file(found[-1]),
                          rule, int(r.cell.traffic["streams"]))
        if table is not None:
            say(table, time.perf_counter() - t0)
    r.__dict__["_hostspans"] = table
    return table


def say(table: dict, parse_s: float) -> None:
    def out(line):
        print(line, file=sys.stderr, flush=True)

    out(f"[idle] {table['idle_s']:.6f} s idle on the device in the "
        f"sub-window; {table['threads']} host threads with cbtpu spans; "
        f"{table['compiles']} compiles marked; second parse of the "
        f"profile {parse_s:.2f} s")
    for name, s in sorted(table["by_stage"].items(), key=lambda kv: -kv[1]):
        out(f"[idle] {name} {s:.6f} s "
            f"{100.0 * s / table['idle_s'] if table['idle_s'] else 0:.2f} %")
    for s, share in table["gaps"]:
        parts = ", ".join(f"{k} {v * 1e3:.3f}" for k, v in
                          sorted(share.items(), key=lambda kv: -kv[1])[:4])
        out(f"[idle-gap] {s * 1e3:.3f} ms: {parts}")
