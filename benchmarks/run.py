"""Run one cell of BENCHMARK.json on the machine this is started on.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process; it starts no child. Phases:

  device   anything but a TPU with the cell's number of chips ends the
           run at once, non-zero, with no result.
  load     the configuration's tables that the mix's statements read,
           made from ``--seed`` and written through the engine's store
           under ``benchmarks/work/`` (removed at exit).
  serve    an in-process ``serve.Server`` on a thread; every stream
           connects over TCP and warms its statements on the connection
           the window will use, until nothing compiles any more.
  window   ``--seconds`` of the mix's closed loops. ``setup_s`` runs from
           the start of this process to the window's first send. With
           ``--trace 1`` the profiler is on for the configuration's
           ``trace_seconds`` in the middle of it.
  compare  the window's own answers against the plain reference over the
           generator's arrays (``harness/compare.py``).
  line     the result, checked against the contract before it is printed
           (``harness/lastline.py``) as the last line of standard output.

``--rehearse-scale <sf>`` is for rehearsals and tests only: it overrides
the configuration's scale, lets the run go on without a TPU, marks the
line ``"rehearsal": true`` and exits 3. No number of such a run is a
device's.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402
import threading    # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import (cell as C, client, compare,     # noqa: E402
                                lastline, load, system, trace, traffic)
from benchmarks.harness.reading import Reading                  # noqa: E402

CLIENT_TIMEOUT_S = 300.0


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] +{time.perf_counter() - T_PROCESS:.1f}s {msg}",
          file=sys.stderr, flush=True)


def render(cell, stmt: str, params: dict) -> str:
    text, ref = cell.statements[stmt]
    return text.format(**ref.bind(params))


def _until_quiet(what: str, w: dict, one_round,
                 least: str = "rounds") -> None:
    """``one_round(k)`` again and again until a round was quiet: it
    compiled (or loaded) no program and ``one_round`` returned true. At
    least ``w[least]`` times, at most ``max_rounds``: a last round that
    still compiled fails the run; one that compiled nothing but is not
    settled (a pool that cannot hold a chunk never serves one) goes on."""
    for k in range(int(w["max_rounds"])):
        before = system.compiled_programs()
        settled = one_round(k)
        moved = system.compiled_programs() - before
        say("serve", f"{what} warm round {k + 1}: {moved} programs "
            f"compiled or loaded{'' if settled else '; not settled'}")
        if k + 1 >= int(w[least]) and moved == 0 and settled:
            return
    if moved:
        raise RuntimeError(f"{what} still compiles after "
                           f"{w['max_rounds']} warm rounds")
    say("serve", f"{what} never settled: the pool served no send of some "
        "statement; should it in the window, the first hit compiles")


def warm(cell, streams, schedule, log) -> None:
    """Every shape the window will use, on the connections it will use:
    (1) each stream's statements, one stream after the other, in rounds
    until a round compiles nothing (at least ``rounds``, which passes
    the pool's ``admit_min_scans``); (2) every statement on all streams
    at once, each stream a lag after the last (round k takes the k-th of
    ``stagger_s``), so that the later stream finds the partitions the
    earlier one has just put into the buffer pool. The pool's hit path
    slices a resident chunk with a program of its own per partition
    length, and in the window two streams that drift meet it at random,
    so a statement that asks the pool at all is warm only once the pool
    has served it; the order of the statements turns from round to
    round, because the pool refuses a chunk scanned less often than
    those it holds; (3) all streams together for a moment, as in the
    window."""
    w = cell.traffic["warm"]
    failures: list = []

    def send(st, stmt, params, delay=0.0):
        time.sleep(delay)
        s = st.one(stmt, params)
        if s.error is not None:
            failures.append(f"warm send {stmt} {params} failed: {s.error}")

    def check():
        if failures:
            raise RuntimeError(failures[0])

    for st in streams:
        sends = schedule.warm_sends(st.index, int(w["max_rounds"]))
        n = len(schedule.order(st.index))

        def alone(k, st=st, sends=sends, n=n):
            for stmt, params in sends[k * n:(k + 1) * n]:
                send(st, stmt, params)
            check()
            return True
        _until_quiet(f"stream {st.index}", w, alone)

    def pool():
        hits = log.counter("bufpool_hits")
        return hits, hits + log.counter("bufpool_misses")

    lags = [float(x) for x in w["stagger_s"]]
    stmts = sorted(cell.traffic["statements"])
    served = {stmt: False for stmt in stmts}

    def staggered(k):
        for stmt in stmts[k % len(stmts):] + stmts[:k % len(stmts)]:
            hits, lookups = pool()
            threads = [threading.Thread(target=send, args=(
                st, stmt, schedule.warm_draw(st.index, stmt, k),
                st.index * lags[k % len(lags)])) for st in streams]
            for t in threads:
                t.start()
            for t in threads:
                t.join(CLIENT_TIMEOUT_S)
            check()
            now_hits, now_lookups = pool()
            served[stmt] |= now_hits > hits or now_lookups == lookups
        return all(served.values())
    _until_quiet("all streams, staggered,", w, staggered, "stagger_rounds")

    sources = [iter(schedule.warm_sends(st.index, 10_000)) for st in streams]
    _, _, threads = client.run_streams(streams, sources,
                                       float(w["together_s"]))
    for t in threads:
        t.join(CLIENT_TIMEOUT_S)


def run(args, require_chip: bool = True, repo: str | None = None) -> tuple:
    """(exit code, result line or None). ``require_chip=False`` is the
    tests' way past the look for a chip; everything else is the run.
    ``repo``: another checkout's BENCHMARK.json and data files."""
    cell = C.Cell(args.workload, repo or C.REPO)
    rehearsal = args.rehearse_scale is not None
    device = system.device_info()
    say("device", json.dumps(device))
    if require_chip and not rehearsal and (
            device["platform"] != "tpu" or device["count"] < cell.chips):
        say("device", f"this cell needs {cell.chips} TPU chip(s): no result")
        return 2, None
    scale = float(args.rehearse_scale if rehearsal else cell.config["scale"])
    system.listen_for_compiles()
    if device["platform"] != "cpu":
        # (XLA:CPU's cache loader logs kilobytes per hit; a rehearsal
        # compiles its few small programs anew)
        say("device", f"compile cache at {system.enable_compile_cache()}")

    # a fixed path: one process per chip, so no two runs share a checkout
    work = os.path.join(cell.bench, "work", cell.name)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(os.path.join(work, "store"))
    try:
        return _run_in(args, cell, device, scale, work, rehearsal)
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _run_in(args, cell, device, scale, work, rehearsal) -> tuple:
    """The run inside its work directory: load, serve, window, compare,
    line. The engine is imported here, after the look for a chip."""
    import cloudberry_tpu as cb
    from cloudberry_tpu.native import load_native
    from cloudberry_tpu.serve.client import Client
    from cloudberry_tpu.serve.server import Server

    # ---------------------------------------------------------------- load
    t0 = time.perf_counter()
    if load_native() is None:
        raise RuntimeError("the C++ codec did not build: the store would "
                           "run on its per-value Python fallback")
    cfg = system.engine_config(cell.config, os.path.join(work, "store"),
                               shrink=scale / float(cell.config["scale"]))
    rows, truth = load.load(
        cb.Session(cfg), cell.tables(), cell.reference_columns(), scale,
        args.seed, max(int(cell.config["chunk_orders"] * min(scale, 1.0)), 64))
    say("load", f"scale {scale:g} seed {args.seed}: {json.dumps(rows)} rows "
        f"through the store in {time.perf_counter() - t0:.1f}s")

    # --------------------------------------------------------------- serve
    schedule = traffic.Schedule(cell.traffic, args.seed)
    with Server(config=cfg) as srv:
        log = srv.session.stmt_log
        clients = [Client(srv.host, srv.port, timeout=CLIENT_TIMEOUT_S)
                   for _ in range(schedule.streams)]
        try:
            streams = [client.Stream(i, c, lambda s, p: render(cell, s, p))
                       for i, c in enumerate(clients)]
            t0 = time.perf_counter()
            warm(cell, streams, schedule, log)
            say("serve", f"{len(streams)} streams warm in "
                f"{time.perf_counter() - t0:.1f}s")

            # ---------------------------------------------------- window
            before = system.snapshot(log)
            sources = [schedule.sends(st.index) for st in streams]
            t_open, t_close, threads = client.run_streams(
                streams, sources, args.seconds)
            setup_s = t_open - T_PROCESS
            sub = None
            if args.trace:
                sub = trace.SubWindow(os.path.join(work, "trace"))
                span = min(float(cell.config["trace_seconds"]),
                           args.seconds / 2)
                time.sleep(max((args.seconds - span) / 2, 0.0))
                sub.record(span)
            for t in threads:
                t.join(args.seconds + CLIENT_TIMEOUT_S)
            if any(t.is_alive() for t in threads):
                raise RuntimeError("a stream did not end")
            after = system.snapshot(log)
            peak = system.memory_peak_bytes()
        finally:
            for c in clients:
                c.close()
    sends = [s for st in streams for s in st.sends]
    say("window", f"{len(sends)} sends in {t_close - t_open:.2f}s, "
        f"set-up {setup_s:.1f}s; programs compiled inside the window: "
        + str(after["jax_compiles"]["programs"]
              - before["jax_compiles"]["programs"]))

    for stmt in sorted(cell.statements):
        mine = sorted(s.seconds for s in sends
                      if s.stmt == stmt and s.error is None)
        third = (t_close - t_open) / 3
        per_third = [sum(1 for s in sends if s.stmt == stmt and
                         t_open + i * third <= s.t_send < t_open
                         + (i + 1) * third) for i in range(3)]
        if mine:
            say("window", f"{stmt}: {len(mine)} answered, median "
                f"{client.percentile(mine, 0.5) * 1e3:.2f} ms, p95 "
                f"{client.percentile(mine, 0.95) * 1e3:.2f} ms; sent in "
                f"each third of the window: {per_third}")

    # ------------------------------------------------------------- metrics
    reading = Reading(before, after, sends, t_open, t_close, cell, rows,
                      device, C.read_json(cell.bench, "peaks.json"),
                      rehearsal=rehearsal)
    dev = dict(device, memory_peak_bytes=peak)
    breakdown = None
    if args.trace:
        t0 = time.perf_counter()
        planes = C.read_json(cell.bench, "planes.json")
        reading.trace = trace.reduce_file(sub.xplane(),
                                          planes[device["platform"]])
        reading.sub = (sub.t_start, sub.t_stop)
        say("trace", f"{reading.trace.get('events', 0)} device events on "
            f"{reading.trace.get('planes', 0)} plane(s) read in "
            f"{time.perf_counter() - t0:.1f}s")
        if reading.trace:
            dev["busy_s"] = reading.trace["busy_s"]
            dev["window_s"] = reading.trace["window_s"]
            breakdown = {"device_ops": reading.trace["device_ops"],
                         "idle_gaps": reading.trace["idle_gaps"]}
        values = {}
        for m in cell.per_layer:
            v = C.reader(m["name"], cell.bench)(reading)
            if v is not None:
                values[m["name"]] = v
    else:
        # (an end-to-end metric split by cells, ``<base>.<suffix>``, is
        # the same arithmetic under another name and bound)
        measured = dict(client.end_to_end(sends, t_open, t_close),
                        setup_s=setup_s)
        values = {m["name"]: measured[m["name"].split(".", 1)[0]]
                  for m in cell.end_to_end}

    # ------------------------------------------------------------- compare
    t0 = time.perf_counter()
    verdict = compare.compare(
        sends, {s: ref for s, (_, ref) in cell.statements.items()}, truth,
        cell.config["limits"], int(cell.config["compare"]
                                   ["draws_per_statement"]), args.seed)
    say("compare", f"{verdict['answers_compared']} answers of "
        f"{verdict['draws_compared']} draws against the reference in "
        f"{time.perf_counter() - t0:.1f}s; widest float gap by column, in "
        f"ulps: {json.dumps(verdict['gap_by_column'])}")

    # ---------------------------------------------------------------- line
    metrics = cell.metrics(bool(args.trace))
    line = lastline.build(verdict["correct"], len(sends),
                          verdict["compared"]["unanswered"][0], values,
                          metrics, dev, verdict["compared"], breakdown)
    bad = lastline.problems(line, metrics, bool(args.trace),
                            platform=None if rehearsal else "tpu",
                            chips=None if rehearsal else cell.chips)
    if bad:
        say("line", "would have been: " + lastline.dumps(line))
        for b in bad:
            say("line", f"NOT PRINTED: {b}")
    # the numbers compared, each beside its limit: stderr's last lines
    for name, (number, limit) in verdict["compared"].items():
        print(f"[compared] {name} {number} limit {limit}", file=sys.stderr,
              flush=True)
    if bad:
        return 4, None
    if rehearsal:
        line = {"rehearsal": True, **line}
    return (3 if rehearsal else 0), line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse-scale", type=float, default=None,
                    help="rehearsals and tests only: run at this scale "
                         "factor, on whatever platform JAX finds")
    code, line = run(ap.parse_args(argv))
    if line is not None:
        print(lastline.dumps(line), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
