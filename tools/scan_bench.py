"""scan_bench — pipeline on/off A/B over a cold tiled scan + the per-SF
roofline ladder.

The out-of-core scan path (exec/tiled.py `_store_tiles`) is the only
path that matters once tables exceed per-device memory; this bench
makes its throughput claims measurable:

- **A/B** (``--sf N``): stream-load TPC-H lineitem at the given SF into
  a store root (tools/tpchgen.py stream_load_tpch — chunked, never a
  whole-SF table in RAM), then run the Q1-shaped cold tiled aggregate
  with the scan pipeline OFF, ON with serial decode, and ON with the
  configured decode pool — reporting wall, stall %, decode-parallel
  speedup, and an exact result checksum (bit-identity pinned per run).
- **ladder** (``ladder_point(sf)`` / ``--ladder-json``): one
  pipeline-on cold run per SF emitting the roofline ladder record —
  rows/sec/chip, wire bytes (live at 1 segment the merge is motion-
  free, so an 8-segment plan MODEL rides along, clearly labeled),
  decode-vs-compute overlap fraction, and pipeline stall time. bench.py
  attaches these records per round (SF0.1/SF1 live; SF10 replayed from
  a committed artifact with its provenance spelled out — the honest
  REPLAY labeling rules unchanged).
- **hot ladder** (``hot_point(sf)`` / ``--hot-json``): the HBM
  buffer-pool second-pass record — the same ladder query three times
  in ONE session so scan 3 is served from the pool (exec/bufferpool),
  reporting cold vs pool rows/s, the pool pass's hit rate, its
  host-decode count (zero when the hot set is resident), and bit
  identity between passes. bench.py attaches these as its
  "bufferpool" record.

Caveats stated rather than hidden: "cold" means the TABLE is cold (the
scan streams micro-partition files); the OS page cache may still be
warm, so the A/B isolates decode+staging overlap, not disk latency.
On a single-core host the decode-parallel column honestly reports ~1×.

Usage:
    python tools/scan_bench.py --sf 1 --reps 2 --csv out.csv
    python tools/scan_bench.py --sf 10 --ladder-json SCAN_SF10.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # direct script invocation
    sys.path.insert(0, REPO)

Q = ("select l_returnflag, l_linestatus, sum(l_quantity) as sq, "
     "sum(l_extendedprice) as se, count(*) as c from lineitem "
     "group by l_returnflag, l_linestatus "
     "order by l_returnflag, l_linestatus")

CSV_HEADER = ("sf,mode,wall_s,n_tiles,tile_rows,rows,rows_per_s,"
              "feed_s,stall_s,stall_pct,decode_s,read_s,overlap_frac,"
              "parts_read,tile_window,inflight_depth,drain_stall_s,"
              "step_wall_s,checksum")


def _session(root: str, budget: int | None = None, pipeline: bool = True,
             decode_workers: int | None = None, extra: dict | None = None):
    import cloudberry_tpu as cb
    from cloudberry_tpu.config import get_config

    ov: dict = {"storage.root": root,
                "scan_pipeline.enabled": pipeline}
    if budget is not None:
        ov["resource.query_mem_bytes"] = budget
    if decode_workers is not None:
        ov["scan_pipeline.decode_workers"] = decode_workers
    if extra:
        ov.update(extra)
    return cb.Session(get_config().with_overrides(**ov))


def ensure_data(root: str, sf: float, seed: int = 1,
                chunk_rows: int = 1_000_000) -> int:
    """Stream-load lineitem (+orders for realism of the manifest) into
    ``root`` unless already there; returns lineitem rows. A reused root
    must actually hold the requested SF — ~4 lineitems per order in the
    generator's model — or the record would carry a wrong sf label."""
    from tools.tpchgen import _sizes, stream_load_tpch

    s = _session(root)
    try:
        t = s.catalog.table("lineitem")
        expect = 4.0 * _sizes(sf)["n_ord"]
        if not 0.8 * expect <= t.num_rows <= 1.2 * expect:
            raise ValueError(
                f"store root {root!r} holds {t.num_rows} lineitem rows "
                f"but sf={sf} expects ~{int(expect)}: refusing to label "
                "a mismatched dataset — pass a fresh --root")
        return t.num_rows
    except KeyError:
        pass
    counts = stream_load_tpch(s, sf=sf, seed=seed, tables=["lineitem"],
                              chunk_rows=chunk_rows)
    return counts.get("lineitem", 0)


def _checksum(df) -> int:
    """Process-stable exact result digest: the committed SF10 artifact's
    checksum must verify against any later replay, so string columns go
    through sha256 (Python's builtin hash() is salted per process)."""
    import hashlib

    import numpy as np

    acc = 0
    for col in df.columns:
        v = df[col].to_numpy()
        if v.dtype.kind in "iuf":
            acc ^= int(np.asarray(v, dtype=np.float64).view(np.uint64)
                       .sum() & 0xFFFFFFFFFFFFFFFF)
        else:
            digest = hashlib.sha256(
                "\x1f".join(map(str, v.tolist())).encode()).digest()
            acc ^= int.from_bytes(digest[:8], "little")
    return acc


def _one_run(root: str, sf: float, budget: int, pipeline: bool,
             decode_workers: int | None = None,
             window: int | None = None) -> dict:
    """One COLD-SCAN run: a fresh session (the table binds cold), one
    compile statement, then the TIMED statement through the cached
    tiled runner — the stream re-reads and re-decodes every
    micro-partition per statement (tiled streams never warm the
    table), so the measured wall is read+decode+stage+compute with
    compilation excluded from the A/B. ``window`` pins
    ``tile_pipeline.inflight_tiles`` (the windowed dispatch A/B)."""
    extra = ({"tile_pipeline.inflight_tiles": window}
             if window is not None else None)
    s = _session(root, budget=budget, pipeline=pipeline,
                 decode_workers=decode_workers, extra=extra)
    rows = s.catalog.table("lineitem").num_rows
    s.sql(Q)  # compile + first stream (not timed)
    assert s.catalog.table("lineitem").cold  # still the cold path
    t0 = time.perf_counter()
    df = s.sql(Q).to_pandas()
    wall = time.perf_counter() - t0
    rep = s.last_tiled_report
    if rep is None:
        raise RuntimeError(
            "statement did not take the tiled path — shrink --budget")
    pl = rep.get("pipeline", {})
    feed = float(pl.get("feed_s", 0.0) or pl.get("read_s", 0.0) or 0.0)
    stall = float(pl.get("stall_s", 0.0))
    return {
        "sf": sf, "wall_s": round(wall, 4),
        "n_tiles": rep["n_tiles"], "tile_rows": rep["tile_rows"],
        "rows": rows, "rows_per_s": int(rows / wall) if wall else 0,
        "feed_s": round(feed, 4), "stall_s": round(stall, 4),
        "stall_pct": round(100.0 * stall / wall, 2) if wall else 0.0,
        "decode_s": round(float(pl.get("decode_s", 0.0)), 4),
        "read_s": round(float(pl.get("read_s", 0.0)), 4),
        "overlap_frac": float(pl.get("overlap_frac", 0.0)),
        "parts_read": int(pl.get("parts_read", 0)),
        # windowed tile dispatch (exec/tilepipe.py): the window that
        # actually ran, its in-flight high-water mark, the host seconds
        # blocked forcing drained scalars, and the summed device step
        # wall it overlaps against
        "tile_window": int(rep.get("tile_window", 1)),
        "inflight_depth": int(rep.get("inflight_depth", 0)),
        "drain_stall_s": round(float(rep.get("drain_stall_s", 0.0)), 4),
        "step_wall_s": round(
            float(rep["tile_time"]["mean"] * rep["tile_time"]["count"])
            if rep.get("tile_time") else 0.0, 4),
        "checksum": _checksum(df),
    }


def run_ab(sf: float, root: str | None = None, reps: int = 2,
           budget: int = 8 << 20, seed: int = 1,
           chunk_rows: int = 1_000_000) -> list[dict]:
    """The A/B matrix: off / on-serial-decode / on. Best-of-``reps``
    per mode (fresh cold session each rep); exact checksums pin
    bit-identity across modes."""
    own = root is None
    root = root or tempfile.mkdtemp(prefix="cbtpu_scanbench_")
    try:
        ensure_data(root, sf, seed=seed, chunk_rows=chunk_rows)
        # one discarded warmup: backend init + first-compile noise must
        # not land on whichever mode happens to run first
        _one_run(root, sf, budget, True)
        out = []
        for mode, pipe, workers in (("off", False, None),
                                    ("on1", True, 1),
                                    ("on", True, None)):
            best = None
            for _ in range(max(int(reps), 1)):
                r = _one_run(root, sf, budget, pipe, workers)
                if best is None or r["wall_s"] < best["wall_s"]:
                    best = r
            best["mode"] = mode
            out.append(best)
        return out
    finally:
        if own:
            import shutil

            shutil.rmtree(root, ignore_errors=True)


def window_ab(sf: float, root: str | None = None, reps: int = 2,
              budget: int = 8 << 20, seed: int = 1,
              chunk_rows: int = 1_000_000, window: int = 4) -> dict:
    """Windowed-dispatch A/B (exec/tilepipe.py): the same cold tiled
    run at ``inflight_tiles=1`` (the legacy synchronous loop) vs
    ``window``, scan pipeline on in both arms so only the dispatch
    window moves. Best-of-``reps`` per arm; the record carries the
    overlap evidence the ISSUE asks for — counter-pinned in-flight
    depth and the drain stall vs device step wall — plus bit identity
    across the arms. On a single-core CPU host the wall-clock verdict
    is honestly ~1×: there is no second execution stream to overlap
    with, so the win shows up as drain_stall_s ≪ step_wall_s, not as
    wall time."""
    own = root is None
    root = root or tempfile.mkdtemp(prefix="cbtpu_scanwin_")
    try:
        ensure_data(root, sf, seed=seed, chunk_rows=chunk_rows)
        _one_run(root, sf, budget, True, window=1)  # discarded warmup
        arms = {}
        for label, w in (("w1", 1), ("on", window)):
            best = None
            for _ in range(max(int(reps), 1)):
                r = _one_run(root, sf, budget, True, window=w)
                if best is None or r["wall_s"] < best["wall_s"]:
                    best = r
            arms[label] = best
        w1, on = arms["w1"], arms["on"]
        return {
            "sf": sf, "window": on["tile_window"],
            "inflight_depth": on["inflight_depth"],
            "wall_s_w1": w1["wall_s"], "wall_s_on": on["wall_s"],
            "speedup_window": round(w1["wall_s"] / on["wall_s"], 3)
            if on["wall_s"] else None,
            "drain_stall_s_w1": w1["drain_stall_s"],
            "drain_stall_s_on": on["drain_stall_s"],
            "step_wall_s": on["step_wall_s"],
            "stall_frac_of_step": round(
                on["drain_stall_s"] / on["step_wall_s"], 4)
            if on["step_wall_s"] else None,
            "bit_identical": w1["checksum"] == on["checksum"],
            "checksum": on["checksum"],
        }
    finally:
        if own:
            import shutil

            shutil.rmtree(root, ignore_errors=True)


def summarize(rows: list[dict]) -> dict:
    by = {r["mode"]: r for r in rows}
    rec = {"speedup_pipeline": None, "speedup_decode_parallel": None,
           "bit_identical": None}
    if "on" in by and "off" in by:
        rec["speedup_pipeline"] = round(
            by["off"]["wall_s"] / by["on"]["wall_s"], 3) \
            if by["on"]["wall_s"] else None
        rec["bit_identical"] = by["on"]["checksum"] == by["off"]["checksum"]
    if "on" in by and "on1" in by and by["on"]["wall_s"]:
        rec["speedup_decode_parallel"] = round(
            by["on1"]["wall_s"] / by["on"]["wall_s"], 3)
    return rec


def to_csv(rows: list[dict]) -> str:
    lines = [CSV_HEADER]
    for r in rows:
        lines.append(",".join(str(r.get(k, ""))
                              for k in CSV_HEADER.split(",")))
    return "\n".join(lines) + "\n"


def _wire_model_8seg(root: str) -> int:
    """Static 8-segment wire-byte MODEL for the ladder query (the
    single-chip live run has no motions): plan at nseg=8 and total
    every Motion's packed-wire footprint — the same arithmetic
    bench.py's interconnect record uses."""
    import copy

    from cloudberry_tpu.exec import kernels as K
    from cloudberry_tpu.exec.executor import all_nodes
    from cloudberry_tpu.plan import nodes as PN
    from cloudberry_tpu.plan.planner import plan_statement
    from cloudberry_tpu.sql.parser import parse_sql

    s = _session(root)
    clone = copy.copy(s)
    clone.config = s.config.with_overrides(n_segments=8)
    plan = plan_statement(parse_sql(Q), clone, {}).plan
    total = 0
    seen: set = set()
    for node in all_nodes(plan):
        if not isinstance(node, PN.PMotion) or id(node) in seen:
            continue
        seen.add(id(node))
        layout = K.wire_layout(
            {f.name: f.type.np_dtype for f in node.fields})
        total += max(int(node.out_capacity), 1) * layout.row_bytes()
    return total


def ladder_point(sf: float, root: str | None = None,
                 budget: int = 8 << 20, seed: int = 1,
                 chunk_rows: int = 1_000_000) -> dict:
    """One roofline-ladder record at ``sf``: a single pipeline-on cold
    tiled run plus the 8-segment wire model."""
    own = root is None
    root = root or tempfile.mkdtemp(prefix="cbtpu_scanladder_")
    try:
        t0 = time.perf_counter()
        rows = ensure_data(root, sf, seed=seed, chunk_rows=chunk_rows)
        load_s = time.perf_counter() - t0
        _one_run(root, sf, budget, True)  # discarded process warmup
        r = _one_run(root, sf, budget, True)  # cold table, warm process
        try:
            wire_model = _wire_model_8seg(root)
        except Exception:  # noqa: BLE001 — the model must never kill a run
            wire_model = None
        return {
            "sf": sf, "rows": rows,
            "rows_per_s_chip": r["rows_per_s"],
            "wall_s": r["wall_s"], "load_s": round(load_s, 2),
            "n_tiles": r["n_tiles"], "tile_rows": r["tile_rows"],
            "stall_s": r["stall_s"], "stall_pct": r["stall_pct"],
            "decode_s": r["decode_s"],
            "overlap_frac": r["overlap_frac"],
            "wire_bytes_live_1seg": 0,
            "wire_bytes_8seg_model": wire_model,
            "checksum": r["checksum"],
        }
    finally:
        if own:
            import shutil

            shutil.rmtree(root, ignore_errors=True)


def hot_point(sf: float, root: str | None = None,
              budget: int = 8 << 20, seed: int = 1,
              chunk_rows: int = 1_000_000,
              pool_bytes: int = 1 << 30) -> dict:
    """One SECOND-PASS buffer-pool record at ``sf`` (ISSUE 16): ONE
    session runs the ladder query three times against the HBM buffer
    pool — scan 1 is cold (misses, admission frequency 1), scan 2
    still decodes but admits every chunk, scan 3 is served from the
    pool. The record compares the admission pass (full host
    read+decode) with the pool pass on the SAME container: rows/s
    each, the pool pass's hit rate and host-decode count (the ZERO
    claim, pinned by counters rather than clocks), and bit identity
    between the passes. ``pool_bytes`` must exceed the SF's decoded
    working set (the 1 GiB default covers SF1, NOT SF10 — pass
    ``--pool-bytes`` there) — this record measures hit-rate behavior,
    not budget pressure (tests/test_bufferpool.py owns the eviction
    story)."""
    own = root is None
    root = root or tempfile.mkdtemp(prefix="cbtpu_scanhot_")
    try:
        rows = ensure_data(root, sf, seed=seed, chunk_rows=chunk_rows)
        s = _session(root, budget=budget,
                     extra={"bufferpool.max_bytes": pool_bytes})
        log = s.stmt_log
        s.sql(Q)  # compile + scan 1: cold, counts each chunk once
        if s.last_tiled_report is None:
            # a one-shot scan warms the TABLE in this session and the
            # later passes would measure RAM, not the pool — the record
            # only means something on the tiled streaming path
            raise RuntimeError(
                "statement did not take the tiled path — shrink --budget")
        passes = []
        for _ in range(2):  # scan 2 admits, scan 3 serves from HBM
            before = {c: log.counter(c) for c in
                      ("bufpool_hits", "bufpool_misses", "bufpool_admits",
                       "host_decodes")}
            t0 = time.perf_counter()
            df = s.sql(Q).to_pandas()
            wall = time.perf_counter() - t0
            passes.append({
                "wall_s": wall, "checksum": _checksum(df),
                **{c: log.counter(c) - v for c, v in before.items()}})
        admit, pool = passes
        seen = pool["bufpool_hits"] + pool["bufpool_misses"]
        return {
            "sf": sf, "rows": rows,
            "rows_per_s_cold": int(rows / admit["wall_s"])
            if admit["wall_s"] else 0,
            "rows_per_s_pool": int(rows / pool["wall_s"])
            if pool["wall_s"] else 0,
            "speedup_pool": round(admit["wall_s"] / pool["wall_s"], 3)
            if pool["wall_s"] else None,
            "bufpool_hit_rate": round(pool["bufpool_hits"] / seen, 4)
            if seen else 0.0,
            "host_decodes_pool_pass": pool["host_decodes"],
            "bufpool_admits": admit["bufpool_admits"],
            "bit_identical": admit["checksum"] == pool["checksum"],
            "checksum": pool["checksum"],
        }
    finally:
        if own:
            import shutil

            shutil.rmtree(root, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--sf", type=float, default=1.0)
    ap.add_argument("--root", default=None,
                    help="store root to (re)use; default: temp dir")
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--budget", type=int, default=8 << 20)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--chunk-rows", type=int, default=1_000_000)
    ap.add_argument("--csv", default=None, help="write CSV here")
    ap.add_argument("--ladder-json", default=None,
                    help="emit ONE ladder_point record to this file "
                         "(skips the A/B matrix)")
    ap.add_argument("--hot-json", default=None,
                    help="emit ONE hot_point record (second-pass HBM "
                         "buffer-pool hit rate) to this file — how an "
                         "SF10 pool point gets committed on hardware")
    ap.add_argument("--pool-bytes", type=int, default=1 << 30,
                    help="bufferpool.max_bytes for --hot-json; must "
                         "exceed the SF's decoded working set or the "
                         "record measures eviction, not hit rate "
                         "(SF10 needs ~8 GiB)")
    ap.add_argument("--window-ab", action="store_true",
                    help="run the windowed tile-dispatch A/B "
                         "(inflight_tiles 1 vs --window) instead of "
                         "the pipeline matrix")
    ap.add_argument("--window", type=int, default=4,
                    help="in-flight window for --window-ab's on arm")
    args = ap.parse_args(argv)

    if args.window_ab:
        rec = window_ab(args.sf, root=args.root, reps=args.reps,
                        budget=args.budget, seed=args.seed,
                        chunk_rows=args.chunk_rows, window=args.window)
        print(json.dumps(rec))
        if args.csv:
            with open(args.csv, "w") as f:
                json.dump(rec, f, indent=1)
                f.write("\n")
        return 0

    if args.hot_json:
        rec = hot_point(args.sf, root=args.root, budget=args.budget,
                        seed=args.seed, chunk_rows=args.chunk_rows,
                        pool_bytes=args.pool_bytes)
        rec["measured_utc"] = time.strftime("%Y-%m-%d", time.gmtime())
        with open(args.hot_json, "w") as f:
            json.dump(rec, f, indent=1)
            f.write("\n")
        print(json.dumps(rec))
        return 0

    if args.ladder_json:
        rec = ladder_point(args.sf, root=args.root, budget=args.budget,
                           seed=args.seed, chunk_rows=args.chunk_rows)
        rec["measured_utc"] = time.strftime("%Y-%m-%d",
                                            time.gmtime())
        with open(args.ladder_json, "w") as f:
            json.dump(rec, f, indent=1)
            f.write("\n")
        print(json.dumps(rec))
        return 0

    rows = run_ab(args.sf, root=args.root, reps=args.reps,
                  budget=args.budget, seed=args.seed,
                  chunk_rows=args.chunk_rows)
    csv = to_csv(rows)
    print(csv, end="")
    print(json.dumps(summarize(rows)))
    if args.csv:
        with open(args.csv, "w") as f:
            f.write(csv)
    return 0


if __name__ == "__main__":
    from cloudberry_tpu.utils.compilecache import entry_banner

    print(f"# {entry_banner()}", file=sys.stderr)
    sys.exit(main())
