"""Worker for tests/test_scan_rungs.py: one process with JAX's persistent
compile cache in a directory of its own (tests do not turn it on), which
serves one grouped statement over a table, appends rows that stay inside
the table's capacity rung, serves it again, appends over the rung, and
serves it a third time. Prints, for each of the three sends, the programs
JAX compiled that MISSED the cache, the cache hits, the module text's
hash, the engine's ``compiles`` counter and the answer.

    python tests/scan_rungs_worker.py <cache dir>
"""

from __future__ import annotations

import hashlib
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_COMPILATION_CACHE_DIR"] = sys.argv[1]
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import numpy as np                                        # noqa: E402

import cloudberry_tpu as cb                               # noqa: E402
from cloudberry_tpu.config import Config                  # noqa: E402
from cloudberry_tpu.exec import executor as X             # noqa: E402
from cloudberry_tpu.utils import compilecache             # noqa: E402
from program_texts import recording                       # noqa: E402

compilecache.enable_compile_cache()
programs: list = []
_compile_plan = X.compile_plan


def compile_plan(*a, **kw):
    exe = _compile_plan(*a, **kw)
    exe.packed_fn = recording(exe.packed_fn, programs)
    return exe


X.compile_plan = compile_plan

RUNG = 1056
s = cb.Session(Config(n_segments=1))
s.sql("create table g (a bigint, k bigint) distributed by (a)")
a = np.arange(RUNG - 20, dtype=np.int64)
s.catalog.table("g").set_data({"a": a, "k": a % 5}, {})
Q = "select k, count(*) as n, sum(a) as sa from g group by k order by k"


def send() -> dict:
    before = compilecache.cache_counts()
    del programs[:]
    got = s.sql(Q).to_pandas()
    after = compilecache.cache_counts()
    return {"misses": after["misses"] - before["misses"],
            "hits": after["hits"] - before["hits"],
            "hashes": [hashlib.sha256(t.encode()).hexdigest()
                       for _, t in programs],
            "compiles": s.stmt_log.counter("compiles"),
            "capacity": int(s.explain(Q).split("Scan g [")[1].split("]")[0]),
            "n": int(got.n.sum()), "sa": int(got.sa.sum())}


out = [send()]
s.sql("insert into g values " + ", ".join(
    f"({RUNG + i}, {i % 5})" for i in range(10)))
out.append(send())
s.sql("insert into g values " + ", ".join(
    f"({2 * RUNG + i}, {i % 5})" for i in range(20)))
out.append(send())
print(json.dumps(out))
