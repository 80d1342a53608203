"""Tiled out-of-core execution (exec/tiled.py) — the workfile-manager /
spill analog (workfile_mgr.c, nodeHash.c batch discipline).

The contract under test: a statement whose plan-time memory estimate
exceeds ``resource.query_mem_bytes`` still completes — streamed in tiles
whose admitted per-step estimate stays inside the budget — and produces
exactly the same result as the all-in-memory path."""

import numpy as np
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import get_config

JOIN_GROUP_Q = ("SELECT g, sum(v) AS sv, count(*) AS c "
                "FROM fact JOIN dim ON fact.k = dim.k "
                "GROUP BY g ORDER BY g")


def _load(session, n_fact=200_000, n_dim=500, seed=3):
    rng = np.random.default_rng(seed)
    session.sql("CREATE TABLE dim (k BIGINT, g BIGINT) DISTRIBUTED BY (k)")
    session.sql("CREATE TABLE fact (k BIGINT, v BIGINT) DISTRIBUTED BY (k)")
    session.catalog.table("dim").set_data(
        {"k": np.arange(n_dim), "g": np.arange(n_dim) % 9})
    session.catalog.table("fact").set_data(
        {"k": rng.integers(0, n_dim, n_fact),
         "v": rng.integers(0, 100, n_fact)})


def _mk(budget=None, **extra):
    ov = {"n_segments": 1}
    if budget is not None:
        ov["resource.query_mem_bytes"] = budget
    ov.update(extra)
    s = cb.Session(get_config().with_overrides(**ov))
    return s


@pytest.fixture(scope="module")
def expected():
    s = _mk()
    _load(s)
    return s.sql(JOIN_GROUP_Q).to_pandas()


def test_tiled_join_group_matches_in_memory(expected):
    s = _mk(budget=4 << 20)
    _load(s)
    got = s.sql(JOIN_GROUP_Q).to_pandas()
    assert expected.equals(got)
    rep = s.last_tiled_report
    assert rep["tiled"] and rep["n_tiles"] > 1
    assert rep["stream_table"] == "fact"
    # the admitted per-step estimate IS the peak bound: it must respect
    # the budget the admission gate enforced
    assert rep["est_step_bytes"] <= rep["budget_bytes"] == 4 << 20


def test_tiled_statement_cache_reuses_runner(expected):
    s = _mk(budget=4 << 20)
    _load(s)
    got1 = s.sql(JOIN_GROUP_Q).to_pandas()
    got2 = s.sql(JOIN_GROUP_Q).to_pandas()
    assert expected.equals(got1) and expected.equals(got2)


def test_spill_disabled_refuses():
    from cloudberry_tpu.exec.resource import ResourceError

    s = _mk(budget=4 << 20, **{"resource.enable_spill": False})
    _load(s)
    with pytest.raises(ResourceError, match="memory estimate"):
        s.sql(JOIN_GROUP_Q)


def test_tiled_global_agg(expected):
    q = ("SELECT sum(v) AS sv, min(v) AS mn, max(v) AS mx, "
         "count(*) AS c, avg(v) AS av FROM fact")
    big = _mk()
    _load(big)
    exp = big.sql(q).to_pandas()
    s = _mk(budget=1 << 20)
    _load(s)
    got = s.sql(q).to_pandas()
    assert s.last_tiled_report["n_tiles"] > 1
    for c in exp.columns:
        np.testing.assert_allclose(got[c].to_numpy().astype(float),
                                   exp[c].to_numpy().astype(float))


def test_merge_overflow_grows_accumulator():
    """An under-estimated group count grows the accumulator and retries
    (the increase-nbatch discipline) instead of truncating groups."""
    s = _mk(budget=4 << 20)
    _load(s, n_fact=200_000, n_dim=10_000)
    # expression group key: NDV unknown -> sqrt estimate (~450), but the
    # true group count is 7k — forces at least one growth round
    q = ("SELECT k % 7000 AS kk, count(*) AS c, sum(v) AS sv "
         "FROM fact GROUP BY k % 7000 ORDER BY kk LIMIT 50")
    big = _mk()
    _load(big, n_fact=200_000, n_dim=10_000)
    exp = big.sql(q).to_pandas()
    got = s.sql(q).to_pandas()
    assert exp.equals(got)
    assert s.last_tiled_report["acc_capacity"] >= 7000


def test_tiled_spine_expansion_join():
    """A many-to-many (expansion) join ON the tiled spine: per-tile pair
    buffers are floored by the tile-scaled NDV estimate, and the adaptive
    loop (grow buffer / halve tile) absorbs whatever the floor missed."""
    def load2(s):
        rng = np.random.default_rng(5)
        s.sql("CREATE TABLE dup (k BIGINT, g BIGINT) DISTRIBUTED BY (k)")
        s.sql("CREATE TABLE fact (k BIGINT, v BIGINT) DISTRIBUTED BY (k)")
        # 20 duplicate rows per key: every probe row matches 20 partners
        keys = np.repeat(np.arange(100), 20)
        s.catalog.table("dup").set_data({"k": keys, "g": keys % 7})
        s.catalog.table("fact").set_data(
            {"k": rng.integers(0, 100, 150_000),
             "v": rng.integers(0, 50, 150_000)})

    q = ("SELECT g, count(*) AS c, sum(v) AS sv "
         "FROM fact JOIN dup ON fact.k = dup.k GROUP BY g ORDER BY g")
    big = _mk()
    load2(big)
    exp = big.sql(q).to_pandas()
    s = _mk(budget=8 << 20)
    load2(s)
    got = s.sql(q).to_pandas()
    assert exp.equals(got)
    rep = s.last_tiled_report
    assert rep["n_tiles"] > 1
    assert rep["est_step_bytes"] <= rep["budget_bytes"]


def test_tiled_streams_cold_storage(tmp_path):
    """Cold tables stream tile-by-tile from micro-partition files: the
    device (and the tile feed) never materializes the whole table."""
    root = str(tmp_path / "store")
    cfg = get_config().with_overrides(
        n_segments=1, **{"storage.root": root,
                         "storage.rows_per_partition": 25_000})
    s = cb.Session(cfg)
    _load(s, n_fact=150_000)
    exp = s.sql(JOIN_GROUP_Q).to_pandas()

    cfg2 = get_config().with_overrides(
        n_segments=1, **{"storage.root": root,
                         "resource.query_mem_bytes": 3 << 20})
    s2 = cb.Session(cfg2)
    fact = s2.catalog.table("fact")
    assert fact.cold
    got = s2.sql(JOIN_GROUP_Q).to_pandas()
    assert exp.equals(got)
    rep = s2.last_tiled_report
    assert rep["n_tiles"] > 1
    # the stream table must still be cold: the tile feed read partition
    # files, never session RAM
    assert s2.catalog.table("fact").cold


TOPN_Q = ("SELECT fact.k AS k, v, g FROM fact JOIN dim ON fact.k = dim.k "
          "WHERE v < 90 ORDER BY v, fact.k, g LIMIT 25")


def test_tiled_topn_matches_in_memory():
    """ORDER BY + LIMIT over a join spine with no aggregation: streams
    through a bounded top-N accumulator (nodeSort.c bounded-heap role)."""
    big = _mk()
    _load(big)
    exp = big.sql(TOPN_Q).to_pandas()
    assert big.last_tiled_report is None  # in-memory baseline

    s = _mk(budget=4 << 20)
    _load(s)
    got = s.sql(TOPN_Q).to_pandas()
    assert exp.equals(got)
    rep = s.last_tiled_report
    assert rep["tiled"] and rep["n_tiles"] > 1
    assert rep["mode"] == "topn"
    assert rep["acc_capacity"] == 25
    assert rep["est_step_bytes"] <= rep["budget_bytes"] == 4 << 20


def test_tiled_topn_offset_and_desc():
    big = _mk()
    _load(big)
    q = ("SELECT v, fact.k AS k FROM fact JOIN dim ON fact.k = dim.k "
         "ORDER BY v DESC, fact.k DESC LIMIT 10 OFFSET 7")
    exp = big.sql(q).to_pandas()
    s = _mk(budget=4 << 20)
    _load(s)
    got = s.sql(q).to_pandas()
    assert exp.equals(got)
    rep = s.last_tiled_report
    assert rep["mode"] == "topn" and rep["acc_capacity"] == 17


def test_tiled_topn_empty_result():
    s = _mk(budget=4 << 20)
    _load(s)
    got = s.sql("SELECT v FROM fact JOIN dim ON fact.k = dim.k "
                "WHERE v < 0 ORDER BY v LIMIT 5").to_pandas()
    assert len(got) == 0
    assert s.last_tiled_report["mode"] == "topn"


def test_tpch_q5_q9_tiled():
    """VERDICT round-1 done-criterion: TPC-H join-heavy queries complete
    under an artificially small budget with in-budget tiles."""
    from tools.tpch_oracle import ORACLES
    from tools.tpch_queries import QUERIES
    from tools.tpchgen import load_tpch

    big = _mk()
    load_tpch(big, sf=0.02, seed=7)
    tables = {n: t.to_pandas() for n, t in big.catalog.tables.items()}

    s = _mk(budget=10 << 20)
    load_tpch(s, sf=0.02, seed=7)
    for qn in ("q5", "q9"):
        got = s.sql(QUERIES[qn]).to_pandas()
        rep = s.last_tiled_report
        assert rep and rep["n_tiles"] > 1, f"{qn} did not tile"
        assert rep["est_step_bytes"] <= 10 << 20
        exp = ORACLES[qn](tables)
        assert len(got) == len(exp)
        for gc, ec in zip(got.columns, exp.columns):
            g, e = got[gc].to_numpy(), exp[ec].to_numpy()
            if g.dtype.kind == "f" or e.dtype.kind == "f":
                np.testing.assert_allclose(
                    g.astype(np.float64), e.astype(np.float64),
                    rtol=1e-9, atol=1e-2, err_msg=f"{qn}.{gc}")
            else:
                np.testing.assert_array_equal(g, e, err_msg=f"{qn}.{gc}")


def test_tiled_grouped_money_sums_bit_identical_to_oneshot():
    """A mid-cardinality GROUP BY with no join (1,500 groups over 60,000
    rows, DECIMAL sums) answers IDENTICALLY one-shot and tiled — the
    step's merge is the one-shot aggregation, and int64 partial sums
    merge exactly — and both equal numpy's int64 sums."""
    nf = 60_000
    rng = np.random.default_rng(12)
    data = {"g": rng.integers(0, 1500, nf),
            "amt": rng.integers(-10**9, 10**9, nf)}
    q = ("select g, sum(amt) as sa, count(*) as n from f "
         "group by g order by g")

    def run(budget):
        s = _mk(budget=budget)
        s.sql("create table f (g bigint, amt decimal(12,2))")
        s.catalog.table("f").set_data(dict(data))
        batch = s.sql(q)
        return s, batch, batch.to_pandas()

    big, _, one = run(4 << 30)
    assert big.last_tiled_report is None
    s2, batch, tiled = run(1 << 20)
    rep = s2.last_tiled_report
    assert rep and rep.get("n_tiles", 0) > 1, rep
    assert one.equals(tiled)
    want = np.zeros(1500, np.int64)
    np.add.at(want, data["g"], data["amt"])
    sel = np.asarray(batch.sel)
    assert np.asarray(batch.columns["sa"])[sel].tolist() == \
        want[np.unique(data["g"])].tolist()
