"""A join build too large for the statement budget streams into a direct-
address table (exec/tiled.py ``_BuildStream``): TPC-H Q3 and Q4 tiled
under Cloudberry's default 125 MiB statement memory, cut with the data
(SF 0.05 through the store, 125 MiB x 0.05), served over the wire and
compared with the plain references and with the one-shot answer at the
engine's default 4 GiB; a NOT EXISTS over lineitem's repeated keys; the
duplicate-key and span checks of the table, each broken by hand on the
build's tiles; the builds that still refuse, and why.

    JAX_PLATFORMS=cpu python -m pytest tests/test_tiled_build_stream.py -q
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import cloudberry_tpu as cb                                    # noqa: E402
from benchmarks.harness import cell as C, compare, load, system  # noqa: E402
from cloudberry_tpu.exec import executor as X                  # noqa: E402
from cloudberry_tpu.exec import kernels as K                   # noqa: E402
from cloudberry_tpu.exec import tiled as T                     # noqa: E402
from cloudberry_tpu.exec.resource import ResourceError         # noqa: E402
from cloudberry_tpu.serve.client import Client                 # noqa: E402
from cloudberry_tpu.serve.server import Server                 # noqa: E402

SF = 0.05
SEED = 4_000_000_017
CONFIG = C.read_json(REPO, "benchmarks/configs/tpch-sf1-spilljoin.json")
DRAWS = {"q3": {"segment": 1, "day": 15}, "q4": {"year": 1993, "month": 7},
         "q13": {"word1": 0, "word2": 0}}
NOT_EXISTS = ("select o_orderpriority, count(*) as n from orders "
              "where not exists (select * from lineitem "
              "where l_orderkey = o_orderkey "
              "and l_commitdate < l_receiptdate) "
              "group by o_orderpriority order by o_orderpriority")


def _text(stmt: str) -> str:
    with open(os.path.join(C.BENCH, "statements", stmt + ".sql")) as f:
        return f.read().format(**_ref(stmt).bind(DRAWS[stmt]))


def _ref(stmt: str):
    return C.load_module("reference", stmt)


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    """SF 0.05 through the store; the generator's arrays for the
    references; the budget the configuration's cut to the data gives."""
    root = str(tmp_path_factory.mktemp("spilljoin"))
    small = system.engine_config(CONFIG, root, shrink=SF)
    keep: dict = {}
    for stmt in ("q3", "q4"):
        for table, cols in _ref(stmt).COLUMNS.items():
            keep.setdefault(table, set()).update(cols)
    _, truth = load.load(cb.Session(small), ["lineitem", "orders",
                                             "customer"],
                         keep, SF, SEED, int(250_000 * SF))
    big = small.with_overrides(**{"resource.query_mem_bytes": 4 << 30,
                                  "bufferpool.max_bytes": 4 << 30})
    return small, big, truth


@pytest.fixture(scope="module")
def served(store):
    """``ask(sql)``: (the answer at 125 MiB x 0.05, the counters it
    moved, its tiled report, the answer at 4 GiB), each through a
    ``serve.Server`` as the benchmark's streams send."""
    small, big, _ = store
    with Server(config=small) as s_srv, Server(config=big) as b_srv:
        backends = []           # the connection's own session: its report
        connect = s_srv._connection_session
        s_srv._connection_session = \
            lambda: backends.append(connect()) or backends[-1]
        cs = Client(s_srv.host, s_srv.port, timeout=300.0)
        cb_ = Client(b_srv.host, b_srv.port, timeout=300.0)

        def ask(sql: str):
            log = s_srv.session.stmt_log
            before = {k: log.counter(k) for k in (
                "tile_build_streams", "tile_build_table_bytes")}
            got = cs.sql(sql)
            moved = {k: log.counter(k) - v for k, v in before.items()}
            report = backends[-1].last_tiled_report
            return got, moved, report, cb_.sql(sql)
        try:
            yield ask
        finally:
            cs.close()
            cb_.close()


@pytest.mark.parametrize("stmt", ["q3", "q4"])
def test_q3_q4_tile_with_one_build_stream_and_answer_exactly(
        store, served, stmt):
    small, _, truth = store
    got, moved, report, one_shot = served(_text(stmt))
    assert moved["tile_build_streams"] == 1
    assert len(report["build_streams"]) == 1
    assert moved["tile_build_table_bytes"] == \
        report["build_streams"][0]["table_bytes"]
    # the admitted estimate: the table, one step and the accumulator, or
    # the table and one build tile, whichever is larger
    assert report["est_step_bytes"] <= report["budget_bytes"] \
        == small.resource.query_mem_bytes
    ref = _ref(stmt).answer(truth, DRAWS[stmt])
    wrong, ulps = compare.gap(got, ref)
    assert wrong == 0 and len(ref["rows"]) > 0
    assert all(u <= CONFIG["limits"]["sum_gap_ulps"] for u in ulps.values())
    assert got["rows"] == one_shot["rows"]


def test_not_exists_over_repeated_build_keys_equals_one_shot(served):
    got, moved, report, one_shot = served(NOT_EXISTS)
    assert moved["tile_build_streams"] == 1
    assert report["build_streams"][0]["table"] == "lineitem"
    assert got["rows"] == one_shot["rows"] and len(got["rows"]) == 5


@pytest.mark.parametrize("broken,check", [
    ("duplicate", "duplicate keys"),
    ("past_span", "past its proven span"),
])
def test_a_broken_table_raises_its_check_and_gives_no_answer(
        store, monkeypatch, broken, check):
    """Q3's orders build, a unique one, with its first tile broken by
    hand as the build stream reads it: its rows on one key's slot, or
    their keys past the span the planner proved. The store is untouched (the
    tile is a copy)."""
    small, _, _ = store
    stream_tiles = T._store_tiles

    def tiles(scan, *a, **kw):
        first = scan.table_name == "orders"
        for tile, n in stream_tiles(scan, *a, **kw):
            if first:
                first = False
                tile = {k: np.array(v) for k, v in tile.items()}
                # (every row of the tile: the rows the build's filters
                # and its customer lookup select are among them)
                key = tile["o_orderkey"]
                key[:] = key[0] if broken == "duplicate" else key + 10 ** 7
            yield tile, n
    monkeypatch.setattr(T, "_store_tiles", tiles)
    s = cb.Session(small)
    with pytest.raises(X.ExecError, match=check) as err:
        s.sql(_text("q3"))
    if broken == "duplicate":
        assert isinstance(err.value, X.DuplicateBuildKeyError)
    assert s.stmt_log.counter("tile_build_streams") == 0


@pytest.mark.parametrize("sql,why", [
    # a build keyed by an expression: no span is proven for it
    ("select count(*) from orders where exists (select * from lineitem "
     "where l_orderkey + 0 = o_orderkey "
     "and l_commitdate < l_receiptdate)", "no proven key span"),
    # ... by a string
    ("select count(*) from orders where exists (select * from lineitem "
     "where l_shipmode = o_orderpriority "
     "and l_commitdate < l_receiptdate)", "no proven key span"),
    # an aggregate of an aggregate over an outer join: no tile stream
    ("q13", "tiled execution declined: no streamable shape"),
])
def test_what_still_refuses_says_why(store, sql, why):
    small, _, _ = store
    s = cb.Session(small)
    with pytest.raises(ResourceError, match="memory estimate") as err:
        s.sql(_text(sql) if sql in DRAWS else sql)
    assert why in str(err.value), str(err.value)
    assert "tiled execution declined: " in str(err.value)
    assert s.stmt_log.counter("tile_build_streams") == 0


BOX = [(10, 8)]                        # keys 10..17


@pytest.mark.parametrize("tiles,unique,dup,past", [
    ([[10, 11], [12, 13]], True, False, False),
    ([[10, 10], [12, 13]], True, True, False),     # in one tile
    ([[10, 11], [11, 13]], True, True, False),     # across tiles
    ([[10, 10], [11, 11]], False, False, False),   # a semi-join's repeats
    ([[10, 18], [12, 13]], True, False, True),     # past the box
    ([[10, 9], [12, 13]], False, False, True),     # below it
])
def test_direct_table_insert_across_tiles(tiles, unique, dup, past):
    import jax.numpy as jnp

    present = jnp.zeros((8,), jnp.bool_)
    payload = {"v": jnp.zeros((8,), jnp.int32)}
    got_dup = got_past = False
    for t in tiles:
        keys = jnp.asarray(t, jnp.int64)
        present, payload, d, p = K.direct_table_insert(
            present, payload, [keys], jnp.ones((2,), jnp.bool_), BOX,
            {"v": keys.astype(jnp.int32) * 2}, unique)
        got_dup |= bool(d)
        got_past |= bool(p)
    assert (got_dup, got_past) == (dup, past)
    if not (dup or past):
        slot, matched = K.direct_table_lookup(
            present, [jnp.asarray([11, 14, 99], jnp.int64)],
            jnp.ones((3,), jnp.bool_), BOX)
        assert np.asarray(matched).tolist() == [True, False, False]
        if unique:
            assert int(payload["v"][int(slot[0])]) == 22
