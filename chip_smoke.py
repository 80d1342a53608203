"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the served path once — ``serve.Client`` → TCP → ``serve.Server`` →
``Session.sql`` → store read + decode → H2D → the XLA program → D2H →
render — over TPC-H held in a micro-partition store, and checks every
answer against the pandas oracle. One process; no child touches JAX.

    python chip_smoke.py                 # one chip: load, serve, tiled, check
    python chip_smoke.py --with-join     # the same, with Q3 among the statements
    python chip_smoke.py --chips 4       # four chips: the mesh path only

Phases of the default run:

  device  platform / device_kind / count. Anything but ``tpu`` fails the
          run whatever the later phases find; they still run, so the CPU
          rehearsal (``JAX_PLATFORMS=cpu python chip_smoke.py --sf 0.01``)
          exercises the whole script. Nothing switches platform.
  load    TPC-H at ``--sf`` generated from ``--seed`` and written through
          the store under ``--workdir`` (statements read micro-partitions).
  serve   Q6 and Q1 over TCP, three sends each: seconds of each send,
          rows, and the StatementLog ``compiles`` counter (repeat sends
          must add 0). Q3 — the join path — joins them under
          ``--with-join`` only: its program holds five sorts over up to
          7.6M rows, and compiling it took 1009 s ahead of time in the
          sandbox and 375 s on the chip host (PR 22) — the first leaves a
          cold default run no margin inside its 1200 s.
  tiled   Q6 once more under a budget that forces ``exec/tiled.py``; the
          answer must equal the one-shot answer bit for bit.
  check   every answer against ``tools/tpch_oracle.py`` over the same data.

These are a smoke's figures, not a benchmark's: one reading each, compile
included where it says so. The last line of stdout is the verdict,
``{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}``;
the exit code is 0 only when it says ``"ok": true``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
STATEMENTS = ("q6", "q1")   # and "q3" under --with-join
TABLES = ("lineitem", "orders", "customer")
# a cold Q3 is 6 to 17 minutes of compile: no client gives up first
CLIENT_TIMEOUT_S = 3000.0


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


class Smoke:
    def __init__(self, args):
        self.args = args
        self.root = os.path.join(args.workdir, "store")
        self.statements = STATEMENTS + (("q3",) if args.with_join else ())
        self.dev = {"platform": None, "kind": None, "count": 0}
        self.failed: list[str] = []
        self.answers: dict[str, dict] = {}  # statement -> wire response

    def phase(self, name: str, fn) -> bool:
        """Run one phase; a raised exception fails the RUN (recorded,
        printed) but not the script — later phases still get their turn."""
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:
            self.failed.append(name)
            traceback.print_exc()
            say(name, f"FAILED after {time.perf_counter() - t0:.1f}s")
            return False
        say(name, f"ok in {time.perf_counter() - t0:.1f}s")
        return True

    # ------------------------------------------------------------ device

    def device(self) -> None:
        import jax

        devs = jax.devices()
        self.dev = {"platform": devs[0].platform,
                    "kind": devs[0].device_kind, "count": len(devs)}
        say("device", json.dumps(self.dev))
        if self.dev["platform"] != "tpu":
            raise RuntimeError(
                f"no accelerator: platform is {self.dev['platform']!r}, "
                "this smoke passes on a TPU only")
        if self.dev["count"] != self.args.chips:
            raise RuntimeError(f"--chips {self.args.chips} but JAX sees "
                               f"{self.dev['count']} devices")

    # -------------------------------------------------------------- load

    def config(self, n_segments: int = 1, **over):
        from cloudberry_tpu.config import Config

        return Config(n_segments=n_segments).with_overrides(
            **{"storage.root": self.root, **over})

    def load(self) -> None:
        import cloudberry_tpu as cb
        from cloudberry_tpu.native import load_native
        from tools.tpchgen import stream_load_tpch

        native = load_native() is not None
        say("load", f"native codec: {'C++' if native else 'MISSING'}")
        shutil.rmtree(self.root, ignore_errors=True)
        os.makedirs(self.root)
        t0 = time.perf_counter()
        counts = stream_load_tpch(cb.Session(self.config()),
                                  sf=self.args.sf, seed=self.args.seed,
                                  tables=list(TABLES))
        say("load", f"sf={self.args.sf:g} seed={self.args.seed} through the "
            f"store at {self.root}: {json.dumps(counts)} rows in "
            f"{time.perf_counter() - t0:.1f}s")
        if not native:
            raise RuntimeError("load_native() returned None: the store ran "
                               "on the per-value Python varint fallback")

    # ------------------------------------------------------------- serve

    def _send3(self, client, log, qname: str) -> dict:
        from tools.tpch_queries import QUERIES

        secs, compiles, resp = [], [log.counter("compiles")], None
        for _ in range(3):
            t0 = time.perf_counter()
            resp = client.sql(QUERIES[qname])
            secs.append(time.perf_counter() - t0)
            compiles.append(log.counter("compiles"))
        say("serve", f"{qname}: first {secs[0]:.3f}s, then {secs[1]:.3f}s "
            f"and {secs[2]:.3f}s; {resp['rowcount']} rows; compiles "
            + " -> ".join(map(str, compiles)))
        if compiles[3] != compiles[1]:
            raise RuntimeError(f"{qname}: repeat sends compiled "
                               f"{compiles[3] - compiles[1]} programs")
        return resp

    def serve(self) -> None:
        from cloudberry_tpu.serve.client import Client
        from cloudberry_tpu.serve.server import Server

        bad = []
        with Server(config=self.config()) as srv, \
                Client(srv.host, srv.port, timeout=CLIENT_TIMEOUT_S) as c:
            say("serve", f"server on {srv.host}:{srv.port}, "
                f"statements {', '.join(self.statements)}")
            for qname in self.statements:
                try:
                    self.answers[qname] = self._send3(
                        c, srv.session.stmt_log, qname)
                except Exception:
                    traceback.print_exc()
                    bad.append(qname)
        if bad:
            raise RuntimeError(f"statements failed: {', '.join(bad)}")

    # ------------------------------------------------------------- tiled

    def tiled(self) -> None:
        import cloudberry_tpu as cb
        from cloudberry_tpu.serve.client import Client
        from cloudberry_tpu.serve.server import Server
        from tools.tpch_queries import QUERIES

        # admission estimates Q6 at ~385 B per lineitem row (~2.2 GiB at
        # SF1): a budget of 256 MiB per SF refuses the one-shot plan and
        # streams the scan through exec/tiled.py in a dozen or so
        # power-of-two tiles
        budget = max(int(self.args.sf * (256 << 20)), 1 << 20)
        sess = cb.Session(self.config(
            **{"resource.query_mem_bytes": budget}))
        # session= pins one shared backend, so its tiled report is ours
        with Server(session=sess) as srv, \
                Client(srv.host, srv.port, timeout=CLIENT_TIMEOUT_S) as c:
            t0 = time.perf_counter()
            resp = c.sql(QUERIES["q6"])
            dt = time.perf_counter() - t0
        rep = sess.last_tiled_report
        if not rep or not rep.get("tiled"):
            raise RuntimeError(f"budget {budget} did not tile Q6: {rep}")
        say("tiled", f"q6 under query_mem_bytes={budget}: {dt:.3f}s, "
            f"n_tiles={rep['n_tiles']} tile_rows={rep['tile_rows']} "
            f"window={rep['tile_window']} "
            f"inflight_depth={rep['inflight_depth']}")
        one_shot = self.answers.get("q6")
        if one_shot is None:
            raise RuntimeError("no one-shot Q6 answer to compare with")
        if resp["rows"] != one_shot["rows"]:
            raise RuntimeError(f"tiled Q6 {resp['rows']} != one-shot "
                               f"{one_shot['rows']}")
        say("tiled", f"answer {resp['rows']} equals the one-shot answer "
            "bit for bit")

    # ------------------------------------------------------------- check

    def oracle_tables(self) -> dict:
        """The generated tables as pandas frames, read back from the store
        by a session of their own (the serving backends stay cold)."""
        import cloudberry_tpu as cb

        s = cb.Session(self.config())
        out = {}
        for name in TABLES:
            t = s.catalog.table(name)
            t.ensure_loaded()
            out[name] = t.to_pandas()
        return out

    def check(self) -> None:
        from tools.tpch_oracle import ORACLES

        t0 = time.perf_counter()
        tables = self.oracle_tables()
        say("check", f"oracle tables decoded in "
            f"{time.perf_counter() - t0:.1f}s")
        bad = []
        for qname, resp in self.answers.items():
            try:
                frames_match(resp, ORACLES[qname](tables), qname)
                say("check", f"{qname}: equals the oracle "
                    f"({resp['rowcount']} rows)")
            except Exception:
                traceback.print_exc()
                bad.append(qname)
        missing = [q for q in self.statements if q not in self.answers]
        if bad or missing:
            raise RuntimeError(f"wrong: {bad}; unanswered: {missing}")

    # -------------------------------------------------------- four chips

    def mesh(self) -> None:
        """Motion over the real interconnect: each MESH statement at four
        segments, against its one-segment answer and a plain reference."""
        import jax

        import cloudberry_tpu as cb
        from tools.tpch_oracle import assert_frames_match

        n = self.args.chips
        sessions = {f"{n}seg": cb.Session(self.config(n_segments=n)),
                    "1seg": cb.Session(self.config())}
        s4 = sessions[f"{n}seg"]
        got: dict = {}
        stmts = mesh_statements(self.args.with_join)
        for name, (sql, _ref, want, sort_by) in stmts.items():
            motions = [ln.strip().split("  ")[0].lstrip("-> ")
                       for ln in s4.explain(sql).splitlines()
                       if "Motion" in ln]
            say("mesh", f"{name} at {n} segments, motions from explain: "
                f"{motions}")
            if not any(want in m for m in motions):
                raise RuntimeError(f"{name}: no {want!r} in {motions}")
            for label, sess in sessions.items():
                secs, compiles = [], []
                for _ in range(3):
                    c0 = sess.stmt_log.counter("compiles")
                    t0 = time.perf_counter()
                    batch = sess.sql(sql)
                    secs.append(time.perf_counter() - t0)
                    compiles.append(sess.stmt_log.counter("compiles") - c0)
                df = batch.to_pandas()
                if sort_by:
                    df = df.sort_values(sort_by).reset_index(drop=True)
                got[name, label] = df
                # the second send may compile again (feedback re-seeds a
                # redistribute's bucket rung from what the first one saw),
                # so it takes a third to see a send that compiles nothing.
                # Both programs are the same text in every process (check
                # and stats keys name plan nodes by ordinal, PR 28), so in
                # a later process with the cache kept both are cache hits
                # and cost a load each, not a compile
                say("mesh", f"{name} {label}: first {secs[0]:.3f}s, then "
                    f"{secs[1]:.3f}s and {secs[2]:.3f}s; {len(df)} rows; "
                    f"compiles per send {compiles}")
                if compiles[2]:
                    raise RuntimeError(f"{name} {label}: the third send "
                                       f"compiled {compiles[2]} programs")
        shares = [int(x) for x in s4.shard_counts("lineitem")]
        stats = [d.memory_stats() or {} for d in jax.devices()]
        mem = [int(m.get("bytes_in_use", -1)) for m in stats]
        peak = [int(m.get("peak_bytes_in_use", -1)) for m in stats]
        say("mesh", f"lineitem rows per device {shares}; bytes_in_use per "
            f"device {mem}, peak_bytes_in_use {peak}; recoveries "
            f"{s4.stmt_log.counter('recoveries')}")
        # shards are host arrays handed to each launch, so bytes_in_use
        # falls back once a statement ends: the peak is what shows a
        # device that never held its share
        if min(shares) == 0 or min(peak) == 0:
            raise RuntimeError("a device held no rows or no bytes: the "
                               "mesh did not spread the data")
        tables = self.oracle_tables()
        for name, (_sql, ref, _want, sort_by) in stmts.items():
            assert_frames_match(got[name, f"{n}seg"], got[name, "1seg"],
                                f"{name} {n}seg vs 1seg")
            say("mesh", f"{name}: {n}-segment answer equals the 1-segment "
                "answer")
            exp = ref(tables)
            if sort_by:
                exp = exp.sort_values(sort_by).reset_index(drop=True)
            assert_frames_match(got[name, f"{n}seg"], exp,
                                f"{name} {n}seg vs reference")
            say("mesh", f"{name}: {n}-segment answer equals the reference")

    # --------------------------------------------------------------- run

    def run(self) -> int:
        from cloudberry_tpu.utils import compilecache as CC

        cache = CC.enable_compile_cache()
        n0 = CC.cache_entries(cache)
        say("cache", f"dir {cache}, {n0} entries before")
        self.phase("device", self.device)
        if self.phase("load", self.load):
            if self.args.chips > 1:
                self.phase("mesh", self.mesh)
            else:
                self.phase("serve", self.serve)
                self.phase("tiled", self.tiled)
                self.phase("check", self.check)
        else:
            self.failed.append("(phases after load not run)")
        say("cache", f"dir {cache}, {CC.cache_entries(cache)} entries after "
            f"({n0} before); this process: {json.dumps(CC.cache_counts())}")
        shutil.rmtree(self.root, ignore_errors=True)
        ok = not self.failed
        if not ok:
            say("verdict", f"failed phases: {', '.join(self.failed)}")
        print(json.dumps({"ok": ok, "device": self.dev}), flush=True)
        return 0 if ok else 1


def mesh_statements(with_join: bool) -> dict:
    """name -> (sql, plain reference over pandas tables, the Motion its
    plan must show, host-side sort key for unordered results).
    ``by_supp`` — lineitem, distributed by l_orderkey, grouped by
    l_suppkey — is the cheapest statement whose plan has a hash
    redistribute (the program tests/test_tpu_compile.py compiles for
    2x2); the benchmark's cell ``tpch-sf1-4seg.motion`` sends the spec's
    form of it, the body of Q15's revenue view. Q3 joins co-located
    orders and lineitem and broadcasts customer, so it has none, and it
    compiles for minutes at each segment count the FIRST time: the
    persistent cache serves it to every later process (its module text no
    longer carries ``id()`` addresses, PR 28). ``--with-join`` only."""
    from tools.tpch_oracle import ORACLES
    from tools.tpch_queries import QUERIES

    out = {"by_supp": (
        "select l_suppkey, count(*) as c from lineitem group by l_suppkey",
        lambda t: t["lineitem"].groupby("l_suppkey", as_index=False)
        .agg(c=("l_suppkey", "size")),
        "Motion redistribute", "l_suppkey")}
    if with_join:
        out["q3"] = (QUERIES["q3"], ORACLES["q3"], "Motion broadcast", None)
    return out


def frames_match(resp: dict, exp, name: str) -> None:
    """A wire response against an oracle frame; dates cross the wire as
    ISO strings, so the oracle's side is rendered the same way."""
    import pandas as pd

    from tools.tpch_oracle import assert_frames_match

    exp = exp.copy()
    for col in exp.columns:
        if exp[col].dtype.kind == "M":
            exp[col] = exp[col].dt.strftime("%Y-%m-%d")
    assert_frames_match(
        pd.DataFrame(resp["rows"], columns=resp["columns"]), exp, name)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=1.0,
                    help="TPC-H scale factor (default 1)")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--workdir",
                    default=os.path.join(REPO, "chip_smoke_work"),
                    help="where the store is written (emptied first)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the four-segment mesh path (a hash-"
                         "redistribute statement; Q3 too under --with-join)")
    ap.add_argument("--with-join", action="store_true",
                    help="also run Q3, whose cold compile takes the TPU "
                         "compiler 6 to 17 minutes at SF1")
    return Smoke(ap.parse_args()).run()


if __name__ == "__main__":
    sys.exit(main())
