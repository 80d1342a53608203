"""Spill shapes beyond agg/top-N: full external ORDER BY and windows
(exec/tiled.py SortTiledExecutable / WindowTiledExecutable, on one
segment and on the mesh layout) — the tuplesort.c spill-to-tape and nodeWindowAgg.c
disciplines with host RAM as the workfile.

Contract: an admission-rejected unbounded-sort or windowed statement
completes tiled (n_tiles > 1) with results exactly equal to the
all-in-memory path, single-node and on the 8-segment mesh.
"""

import numpy as np
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import get_config

SORT_Q = ("SELECT g, v, w FROM fact JOIN dim ON fact.k = dim.k "
          "WHERE v < 50 ORDER BY g, v DESC, w")
# the ROWS frame orders by (v, w): w is ~unique, making the frame
# deterministic — with ties the frame content would legitimately differ
# between execution orders
WIN_Q = ("SELECT g, v, rank() over (partition by g order by v desc) AS r,"
         " sum(v) over (partition by g) AS sv, "
         "avg(w) over (partition by g order by v, w "
         "rows between 2 preceding and current row) AS aw "
         "FROM fact JOIN dim ON fact.k = dim.k")


def _load(s, n_fact=200_000, n_dim=500):
    rng = np.random.default_rng(3)
    s.sql("CREATE TABLE dim (k BIGINT, g BIGINT) DISTRIBUTED BY (k)")
    s.sql("CREATE TABLE fact (k BIGINT, v BIGINT, w DOUBLE) "
          "DISTRIBUTED BY (k)")
    s.catalog.table("dim").set_data(
        {"k": np.arange(n_dim), "g": np.arange(n_dim) % 300})
    s.catalog.table("fact").set_data(
        {"k": rng.integers(0, n_dim, n_fact),
         "v": rng.integers(0, 100, n_fact),
         "w": rng.standard_normal(n_fact)})


def _mk(nseg, budget=None):
    ov = {"n_segments": nseg}
    if budget is not None:
        ov["resource.query_mem_bytes"] = budget
    s = cb.Session(get_config().with_overrides(**ov))
    _load(s)
    return s


@pytest.fixture(scope="module", params=[1, 8], ids=["single", "dist8"])
def pair(request):
    return (_mk(request.param), _mk(request.param, budget=4 << 20),
            request.param)


def test_external_sort_matches_in_memory(pair):
    ref, tiled, nseg = pair
    want = ref.sql(SORT_Q).to_pandas()
    got = tiled.sql(SORT_Q).to_pandas()
    assert want.equals(got)
    rep = tiled.last_tiled_report
    assert rep["tiled"] and rep["mode"] == "sort" and rep["n_tiles"] > 1
    assert rep["est_step_bytes"] <= rep["budget_bytes"]


def test_window_spill_matches_in_memory(pair):
    ref, tiled, nseg = pair
    order = ["g", "v", "r", "sv", "aw"]
    want = ref.sql(WIN_Q).to_pandas().sort_values(order) \
        .reset_index(drop=True)
    got = tiled.sql(WIN_Q).to_pandas().sort_values(order) \
        .reset_index(drop=True)
    assert want[["g", "v", "r", "sv"]].equals(got[["g", "v", "r", "sv"]])
    assert np.allclose(want["aw"], got["aw"])
    rep = tiled.last_tiled_report
    assert rep["tiled"] and rep["mode"] == "window"
    assert rep["n_tiles"] > 1 and rep["n_chunks"] > 1


def test_huge_offset_limit_falls_back_to_sort(pair):
    """A LIMIT whose OFFSET exceeds any resident accumulator cannot run
    top-N; the external sort applies it host-side."""
    ref, tiled, nseg = pair
    q = SORT_Q + " LIMIT 1000 OFFSET 60000"
    want = ref.sql(q).to_pandas()
    got = tiled.sql(q).to_pandas()
    assert want.equals(got) and len(got) == 1000
    assert tiled.last_tiled_report["mode"] == "sort"


def test_single_partition_too_big_is_a_clear_error():
    s = _mk(1, budget=3 << 20)
    s.sql("CREATE TABLE one (k BIGINT, v BIGINT) DISTRIBUTED BY (k)")
    s.catalog.table("one").set_data(
        {"k": np.zeros(300_000, dtype=np.int64),
         "v": np.arange(300_000)})
    with pytest.raises(Exception, match="partition"):
        s.sql("SELECT k, sum(v) over (partition by k) AS sv FROM one")


def test_skewed_redistribute_grows_bucket():
    """An untiled skew-blown redistribute bucket grows and retries (the
    Motion receive-buffer resize) instead of failing the statement."""
    s = _mk(8)
    df = s.sql(WIN_Q).to_pandas()
    assert len(df) == 200_000
