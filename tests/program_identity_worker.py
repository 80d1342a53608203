"""Worker for tests/test_program_identity.py: one process that serves one
statement through ``Session.sql`` (the generic-plan path the server takes)
and prints, for every program the statement launched, a hash of its
lowered module text and the ``jax.result_info`` keys it carries.

    python tests/program_identity_worker.py <n_segments> \
        <q15v|q3|q12|q13|q18|q1|q6> \
        [--no-origin] [--tiled] [--store [--warm]]

``--no-origin`` binds every literal without its origin (``expr.Literal``'s
``origin``, ISSUE 29), as the tree before it did: the witness that the
origin reaches no program.

``--tiled`` holds the statement to a memory budget that sends it through
``exec/tiled.py`` (on the segment mesh on several segments) as the
out-of-core cell's 125 MiB does at SF1
(``resource.query_mem_bytes``: 4 MiB at this worker's SF 0.01 — 1.25 MiB,
the cell's budget cut with the data, is under the least tile Q6 runs at),
and keeps the text of each tiled program (prelude, step, finalize) at its
first launch.

``--store`` writes the tables through a micro-partition store in several
appends (``tools/tpchgen.stream_load_tpch``, as the benchmark's loader
and ``chip_smoke.py`` do) and serves the statement from a fresh session,
whose tables are COLD: the planner knows of them what the manifests say
(ISSUE 31). ``--warm`` loads them into RAM before the statement is
planned. The line also carries the joins the launches counted, by shape,
and the semi-joins among them that filter a scan before any join.

Run in two checkouts, it is the check that a change leaves a statement's
programs as they were: equal ``hashes`` and ``result_info`` for the same
arguments.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import sys

N_SEG = int(sys.argv[1])
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import cloudberry_tpu as cb                              # noqa: E402
from cloudberry_tpu.config import Config                 # noqa: E402
from cloudberry_tpu.exec import dist_executor as DX      # noqa: E402
from cloudberry_tpu.exec import executor as X            # noqa: E402
from cloudberry_tpu.exec import tiled as T               # noqa: E402
from program_texts import (record_tiled_programs,        # noqa: E402
                           recording)
from tools.tpch_queries import QUERIES                   # noqa: E402
from tools.tpchgen import load_tpch                      # noqa: E402

Q15V = ("select l_suppkey as supplier_no, "
        "sum(l_extendedprice * (1 - l_discount)) as total_revenue "
        "from lineitem where l_shipdate >= date '1996-01-01' "
        "and l_shipdate < date '1996-04-01' "
        "group by l_suppkey order by supplier_no")
STATEMENTS = {"q15v": Q15V, **{q: QUERIES[q] for q in
                               ("q3", "q12", "q13", "q18", "q1", "q6")}}

if "--no-origin" in sys.argv[3:]:
    from cloudberry_tpu.plan import binder

    _token_literal = binder._token_literal
    binder._token_literal = lambda kind, text, pos=-1: _token_literal(
        kind, text)

programs: list = []     # (function name, module text) of every launch

_compile_distributed = DX.compile_distributed
_compile_plan = X.compile_plan


def compile_distributed(*a, **kw):
    return recording(_compile_distributed(*a, **kw), programs)


def compile_plan(*a, **kw):
    exe = _compile_plan(*a, **kw)
    exe.packed_fn = recording(exe.packed_fn, programs)
    return exe


DX.compile_distributed = compile_distributed
X.compile_plan = compile_plan

overrides = {}
if "--tiled" in sys.argv[3:]:
    overrides["resource.query_mem_bytes"] = 4 << 20
    record_tiled_programs((T,), programs)

TABLES = ["lineitem", "orders", "customer"]
if "--store" in sys.argv[3:]:
    import tempfile

    from tools.tpchgen import stream_load_tpch

    overrides["storage.root"] = tempfile.mkdtemp(prefix="cbtpu_identity_")
    config = Config(n_segments=N_SEG).with_overrides(**overrides)
    stream_load_tpch(cb.Session(config), sf=0.01, seed=7, tables=TABLES,
                     chunk_rows=4000)
    s = cb.Session(config)
    if "--warm" in sys.argv[3:]:
        for name in TABLES:
            s.catalog.table(name).ensure_loaded()
else:
    s = cb.Session(Config(n_segments=N_SEG).with_overrides(**overrides))
    load_tpch(s, sf=0.01, seed=7, tables=TABLES)
rows = s.sql(STATEMENTS[sys.argv[2]]).num_rows()
info = [m for _, t in programs
        for m in re.findall(r'jax\.result_info = "([^"]*)"', t)]
print(json.dumps({"rows": rows, "programs": len(programs),
                  "names": [name for name, _ in programs],
                  "joins": [s.stmt_log.counter("launch_joins_lookup"),
                            s.stmt_log.counter("launch_joins_expand")],
                  "semi_on_scan": s.stmt_log.counter(
                      "launch_joins_semi_on_scan"),
                  "hashes": [hashlib.sha256(t.encode()).hexdigest()
                             for _, t in programs],
                  "result_info": info}))
