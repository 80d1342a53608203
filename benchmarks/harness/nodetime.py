"""Device time by plan node: whose operations the chip was running.

Every operation of a lowered plan carries its node's scope in its name
(``n<ordinal>:<kind>``), and the engine keeps, for every program it built,
the way from a compiled instruction to its node
(``cloudberry_tpu/obs/programs.py``). A profile names the instruction that
ran. This module lays the two together, over the profile ``run.py`` left
under ``work/<cell>/trace``, cut to the ``bench_window`` mark as
``harness/trace.py`` cuts it (``device_events``, ``clip``,
``marked_window``: imported).

The rule. On each device plane every instant in which an operation ran is
charged to ONE operation: the one that started last among those open (a
``while`` is charged its length less its body's operations: SELF time), so
a plane's rows sum to the union of its operations' intervals, which is
``busy_s``. An operation belongs to the program whose module event (the
rule's ``module_lines``) holds it; a module event belongs to the ONE
registered program of its name whose text holds every instruction (and
result shape) seen under events of that name (the number in
``jit__lambda(8371619553279078626)`` is the profiler's: no executable
offers it): none or several is
``no program``, never a guess by an instruction's name alone (``fusion.7``
is in every program). Within its program an operation goes to a plan node
by the program's map, or to an unnumbered scope (``answer``, ``checks``,
``tile:merge``), to ``input`` (what runs on a program's inputs before any
node reads them), or to ``unscoped``. Seconds are the mean over the planes.

Classes of the per-class metrics (by the node's kind): **scan** = scan,
filter, project, rfilter, concat, share; **join** = join:lookup,
join:expand; **agg** = agg, window; **sort** = sort, limit; **motion** =
motion:*.

A reading is ``None`` without a trace and a table always with one: empty
(every metric 0.0, a line on stderr saying why) where the program under
test offers no map (a tree without ``obs/programs.py``), and where the
attribution raises.
"""

from __future__ import annotations

import bisect
import glob
import heapq
import os
import sys
import time
import traceback

from benchmarks.harness.trace import clip, device_events, marked_window

CLASSES = {
    "scan": "scan", "filter": "scan", "project": "scan", "rfilter": "scan",
    "concat": "scan", "share": "scan",
    "join:lookup": "join", "join:expand": "join",
    "agg": "agg", "window": "agg",
    "sort": "sort", "limit": "sort",
}
# the bucket of module events no registered program owns: eager helpers
# (``jit_copy``), and the programs whose map is unavailable
NO_PROGRAM = "no program"


def node_class(kind: str) -> str | None:
    return "motion" if kind.startswith("motion:") else CLASSES.get(kind)


def self_ns(events: list) -> list:
    """Nanoseconds charged to each of ``events`` [(name, lo, hi, …)], in
    their order: every instant goes to the event that started last among
    those open at it (ties: the one that ends first), so the sum is the
    length of the union of the intervals."""
    out = [0] * len(events)
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    open_: list = []        # (-start, end, index): innermost on top
    t = None

    def run_to(limit):
        nonlocal t
        while open_ and t < limit:
            _, end, i = open_[0]
            if end <= t:
                heapq.heappop(open_)
                continue
            upto = min(end, limit)
            out[i] += upto - t
            t = upto
        t = max(t, limit)

    for i in order:
        lo, hi = events[i][1], events[i][2]
        if t is None:
            t = lo
        run_to(lo)
        heapq.heappush(open_, (-lo, hi, i))
    if open_:
        run_to(max(e[2] for e in events))
    return out


def _stat_modules(profile, rule: dict) -> dict:
    """{(plane, name, start_ns): "module(program id)"} of the operation
    events that carry their program in their own stats (XLA:CPU's:
    REHEARSAL ONLY; a TPU's operations lie under module events)."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith(rule["plane_prefix"]):
            continue
        for line in plane.lines:
            if "line_prefix" in rule and \
                    not line.name.startswith(rule["line_prefix"]):
                continue
            for e in line.events:
                stats = dict(getattr(e, "stats", ()) or ())
                if "hlo_module" in stats:
                    out[plane.name, e.name, e.start_ns] = \
                        f"{stats['hlo_module']}({stats.get('program_id')})"
    return out


def op_events(profile, rule: dict, mark: tuple) -> tuple:
    """(plane -> [(name, lo, hi, module event's name or None)] of the
    operations inside the mark, module event's name -> {plane: launches
    inside the mark, one the mark cuts by its share})."""
    ops = clip(device_events(profile, rule), *mark)
    launches: dict = {}
    out = {}
    if "module_lines" in rule:
        mods = device_events(profile, rule, "module_lines")
        for plane, evs in ops.items():
            mine = sorted(mods.get(plane, ()), key=lambda m: m[1])
            starts = [m[1] for m in mine]
            tagged = []
            for name, lo, hi in evs:
                k = bisect.bisect_right(starts, lo) - 1
                held = k >= 0 and lo < mine[k][2]
                tagged.append((name, lo, hi, mine[k][0] if held else None))
            out[plane] = tagged
            for name, lo, hi in mine:
                # (a launch the mark cuts counts by its share inside)
                inside = min(hi, mark[1]) - max(lo, mark[0])
                if inside > 0:
                    per = launches.setdefault(name, {})
                    per[plane] = per.get(plane, 0.0) + inside / (hi - lo)
    else:
        # (an operation cut by the mark's edge has another start: no
        # program's)
        tags = _stat_modules(profile, rule)
        for plane, evs in ops.items():
            out[plane] = [(name, lo, hi, tags.get((plane, name, lo)))
                          for name, lo, hi in evs]
    return out, launches


def attribute(by_plane: dict, launches: dict, programs) -> dict:
    """The table of one profile: ``by_plane`` and ``launches`` from
    ``op_events``; ``programs`` the engine's ``obs.programs`` module (or
    anything with its ``all_maps``, ``holders``, ``event_instruction``,
    ``attribute``, ``entries``).

    {"busy_s", "rows": [{program, sql, what, ordinal, kind, title,
    seconds, launches}], "rest": {bucket: seconds}, "modules": [{module,
    seconds, launches, program or the reason it has none}]}"""
    n = max(len(by_plane), 1)
    per_module: dict = {}       # module -> {instruction: [ns, shapes]}
    for evs in by_plane.values():
        for (name, _, _, module), ns in zip(evs, self_ns(evs)):
            instr, shapes = programs.event_instruction(name)
            slot = per_module.setdefault(module, {}).setdefault(
                instr, [0, shapes])
            slot[0] += ns
    maps = programs.all_maps()
    rows: dict = {}
    rest: dict = {}
    modules = []
    for module, instrs in sorted(per_module.items(), key=lambda kv: -sum(
            v[0] for v in kv[1].values())):
        seconds = sum(v[0] for v in instrs.values()) / 1e9 / n
        count = sum((launches.get(module) or {}).values()) / n
        held = programs.holders(
            module, {k: v[1] for k, v in instrs.items()}, maps) \
            if module is not None else []
        modules.append({"module": module, "seconds": seconds,
                        "launches": count, "instructions": len(instrs),
                        "program": held[0][0].seq if len(held) == 1
                        else None, "holders": len(held)})
        if len(held) != 1:      # none, or several: never a guess
            rest[NO_PROGRAM] = rest.get(NO_PROGRAM, 0.0) + seconds
            continue
        entry, pmap = held[0]
        for (ordinal, kind), s in programs.attribute(pmap, (
                (k, v[0] / 1e9 / n) for k, v in instrs.items())).items():
            if ordinal is None:
                rest[kind] = rest.get(kind, 0.0) + s
                continue
            row = rows.setdefault((entry.seq, ordinal), {
                "program": entry.seq, "sql": entry.sql, "what": entry.what,
                "ordinal": ordinal, "kind": kind,
                "title": entry.title(ordinal), "seconds": 0.0,
                "launches": count, "program_seconds": seconds})
            row["seconds"] += s
    unmapped = sum(1 for e in programs.entries()
                   for i in range(len(e.signatures))
                   if e.maps.get(i, 0) is None)
    return {"busy_s": sum(m["seconds"] for m in modules),
            "rows": sorted(rows.values(), key=lambda r: -r["seconds"]),
            "rest": rest, "modules": modules, "maps": len(maps),
            "unmapped": unmapped}


EMPTY = {"busy_s": 0.0, "rows": [], "rest": {}, "modules": [], "maps": 0,
         "unmapped": 0}


def of_reading(r) -> dict | None:
    """The table for a run's ``Reading``, made once a run and kept on the
    reading; None without a trace."""
    if "_nodetime" in r.__dict__:
        return r.__dict__["_nodetime"]
    table = None
    found = sorted(glob.glob(os.path.join(
        r.cell.bench, "work", r.cell.name, "trace", "plugins", "profile",
        "*", "*.xplane.pb")))
    if found and r.trace:
        t0 = time.perf_counter()
        table = dict(EMPTY)
        try:
            import jax

            from benchmarks.harness.cell import read_json

            try:
                from cloudberry_tpu.obs import programs
            except ImportError:
                programs = None
            if programs is None:
                table["why"] = "the program under test keeps no map " \
                    "(no cloudberry_tpu/obs/programs.py)"
            else:
                profile = jax.profiler.ProfileData.from_file(found[-1])
                mark = marked_window(profile)
                rule = read_json(r.cell.bench,
                                 "planes.json")[r.device["platform"]]
                if mark is not None:
                    table = attribute(*op_events(profile, rule, mark),
                                      programs)
        except Exception as e:      # a listed metric must still print
            table = dict(EMPTY, why=f"the attribution raised "
                         f"{type(e).__name__}: {e}")
            traceback.print_exc(file=sys.stderr)
        table["parse_s"] = time.perf_counter() - t0
    r.__dict__["_nodetime"] = table
    return table


def class_ms_per_stmt(r, cls: str) -> float | None:
    """Milliseconds of device time of the nodes of class ``cls`` per
    statement answered in the sub-window; None without a trace."""
    table = of_reading(r)
    if table is None:
        return None
    stmts = sum(share for _, share in r.sub_statements())
    return sum(row["seconds"] for row in table["rows"]
               if node_class(row["kind"]) == cls) / stmts * 1e3 \
        if stmts else 0.0


def attributed_pct(r) -> float | None:
    """100 × seconds charged to a numbered plan node ÷ ``busy_s``."""
    table = of_reading(r)
    if table is None:
        return None
    busy = r.trace.get("busy_s") or table["busy_s"]
    return 100.0 * sum(row["seconds"] for row in table["rows"]) / busy \
        if busy else 0.0


def say(r, table: dict) -> None:
    """The whole table on stderr as ``[nodes]`` lines: one a (program,
    node), one a bucket of the rest, one a module event's name."""
    def out(line):
        print("[nodes] " + line, file=sys.stderr, flush=True)

    stmts = sum(share for _, share in r.sub_statements()) or 1.0
    busy = table["busy_s"]
    out(f"{busy:.6f} s of operations' self time, mean over the planes "
        f"(busy_s {r.trace.get('busy_s', 0.0):.6f}); {table['maps']} maps "
        f"of registered programs, {table['unmapped']} unavailable; "
        f"{stmts:.2f} statements in the sub-window; read in "
        f"{table.get('parse_s', 0.0):.2f} s"
        + (f"; {table['why']}" if "why" in table else ""))
    for m in table["modules"]:
        out(f"module {m['module']}: {m['seconds']:.6f} s, "
            f"{m['launches']:.1f} launches, {m['instructions']} "
            f"instructions seen, " + (
                f"program {m['program']}" if m["program"] else
                f"{NO_PROGRAM}: {m['holders']} registered programs hold "
                "them all"))
    for row in table["rows"]:
        per = row["seconds"] / row["launches"] * 1e3 \
            if row["launches"] else 0.0
        out(f"program {row['program']} ({row['what']}) "
            f"{' '.join(row['sql'].split())[:60]!r} n{row['ordinal']} "
            f"{row['kind']} [{row['title']}]: {row['seconds']:.6f} s, "
            f"{per:.4f} ms a launch, "
            f"{100.0 * row['seconds'] / row['program_seconds']:.2f} % of "
            f"its program, {row['seconds'] / stmts * 1e3:.4f} ms a "
            f"statement")
    for bucket, s in sorted(table["rest"].items(), key=lambda kv: -kv[1]):
        out(f"rest {bucket}: {s:.6f} s "
            f"{100.0 * s / busy if busy else 0.0:.2f} %, "
            f"{s / stmts * 1e3:.4f} ms a statement")
    by_class: dict = {}
    for row in table["rows"]:
        c = node_class(row["kind"]) or "other"
        by_class[c] = by_class.get(c, 0.0) + row["seconds"]
    out("classes, ms a statement: " + ", ".join(
        f"{c} {s / stmts * 1e3:.4f}" for c, s in sorted(by_class.items()))
        + f"; rest {sum(table['rest'].values()) / stmts * 1e3:.4f}")
