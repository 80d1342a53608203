"""Topology-aware two-level Motion (ISSUE 14): hierarchical
all_to_all / gather / broadcast over simulated ICI/DCN with host-local
combine, pinned BIT-IDENTICAL to the flat transport.

The CPU stand-in for a multi-host cluster is the env-forced process
grouping (``CBTPU_FORCE_HOSTS`` partitions the 8-virtual-device mesh
into contiguous uniform hosts — parallel/mesh.py HostTopology); the
real 2-process cluster variant lives in tests/test_multihost.py. The
transport contract is exact: ``hier_all_to_all`` returns the SAME
buffer ``lax.all_to_all`` would (route words reproduce the flat slot
layout), so every parity pin below is equality, not tolerance."""

import subprocess
import sys

import numpy as np
import pandas as pd
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import Config

NSEG = 8


@pytest.fixture()
def hosts4(monkeypatch):
    monkeypatch.setenv("CBTPU_FORCE_HOSTS", "4")
    return 4


@pytest.fixture()
def hosts2(monkeypatch):
    monkeypatch.setenv("CBTPU_FORCE_HOSTS", "2")
    return 2


def _mk_session(hier: str, nseg: int = NSEG, **over):
    cfg = Config(n_segments=nseg).with_overrides(**{
        "interconnect.hierarchical": hier, **over})
    s = cb.Session(cfg)
    rng = np.random.default_rng(11)
    s.sql("CREATE TABLE dim (d BIGINT, g BIGINT) DISTRIBUTED BY (d)")
    s.sql("CREATE TABLE fact (k BIGINT, grp BIGINT, v BIGINT) "
          "DISTRIBUTED BY (k)")
    s.catalog.table("dim").set_data(
        {"d": np.arange(100), "g": np.arange(100) % 6})
    s.catalog.table("fact").set_data(
        {"k": rng.integers(0, 4000, 20_000),
         "grp": rng.integers(0, 100, 20_000),
         "v": rng.integers(0, 1000, 20_000)})
    return s


QUERIES = [
    # redistribute join (both sides move) + two-stage agg + gathered sort
    "SELECT g, sum(v) AS sv, count(*) AS c FROM fact "
    "JOIN dim ON fact.grp = dim.d GROUP BY g ORDER BY g",
    # broadcast join (small build)
    "SELECT count(*) AS n FROM fact JOIN dim ON fact.grp = dim.d "
    "WHERE g < 3",
    # top-N pushdown through the gather motion
    "SELECT k, v FROM fact ORDER BY v DESC, k LIMIT 7",
    # two-stage agg on a non-distribution key: the host-combined merge
    # motion (sum/count/min/max partials — all exact merges)
    "SELECT v % 13 AS b, sum(v) AS sv, count(*) AS c, min(k) AS mn, "
    "max(k) AS mx FROM fact GROUP BY b ORDER BY b",
]


# ------------------------------------------------- transport bit-identity


@pytest.mark.parametrize("n_hosts", [2, 4])
def test_transport_bit_identical(session, monkeypatch, n_hosts):
    """hier_all_to_all and the tree all_gather return byte-for-byte the
    flat collectives' buffers on random wire blocks (validity-bit
    convention, invalid slots all-zero)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from cloudberry_tpu.exec.dist_executor import _shard_map
    from cloudberry_tpu.parallel.mesh import (SEG_AXIS, host_topology,
                                              segment_mesh)
    from cloudberry_tpu.parallel.transport import (HierarchicalCollectives,
                                                   XlaCollectives)

    monkeypatch.setenv("CBTPU_FORCE_HOSTS", str(n_hosts))
    mesh = segment_mesh(NSEG)
    topo = host_topology(NSEG)
    assert topo.n_hosts == n_hosts and topo.uniform_contiguous()
    tx, flat = HierarchicalCollectives(topo), XlaCollectives()
    S, B, W = NSEG // n_hosts, 16, 5
    rng = np.random.default_rng(3)
    x = rng.integers(0, 2 ** 31, (NSEG, NSEG, B, W)).astype(np.uint32)
    valid = rng.random((NSEG, NSEG, B)) < 0.6
    x[..., 0] = (x[..., 0] & ~np.uint32(1)) | valid.astype(np.uint32)
    x = np.where(valid[..., None], x, 0).astype(np.uint32)

    def fn(v):
        a = v[0][0]
        r_flat = flat.all_to_all(a, SEG_AXIS)
        r_hier, demand = tx.hier_all_to_all(a, SEG_AXIS,
                                            host_cap=S * S * B)
        g_flat = flat.all_gather(a.reshape(NSEG * B, W), SEG_AXIS)
        g_hier = tx.all_gather(a.reshape(NSEG * B, W), SEG_AXIS)
        return (jnp.all(r_flat == r_hier)[None].astype(jnp.int32),
                jnp.all(g_flat == g_hier)[None].astype(jnp.int32),
                demand[None])

    f = jax.jit(_shard_map(fn, mesh, ({0: P(SEG_AXIS)},),
                           (P(SEG_AXIS), P(SEG_AXIS), P(SEG_AXIS))))
    eq_a2a, eq_ag, dem = f({0: x})
    assert np.asarray(eq_a2a).all(), "hier_all_to_all != flat"
    assert np.asarray(eq_ag).all(), "tree all_gather != flat"
    # every valid row is accounted to exactly one host pair
    assert int(np.asarray(dem).sum()) == int(valid.sum())
    assert tx.launches > 0       # the ICI/DCN ppermutes really ran


# -------------------------------------------------- engine-level parity


def test_hier_queries_bit_identical(hosts4):
    """hierarchical=on vs off at a forced 4-host/8-seg split: every
    query shape (redistribute join, broadcast join, top-N gather,
    host-combined agg merge) decodes bit-identically."""
    s_off = _mk_session("off")
    s_on = _mk_session("on", **{"debug.verify_plans": True})
    for q in QUERIES:
        a = s_off.sql(q).to_pandas()
        b = s_on.sql(q).to_pandas()
        pd.testing.assert_frame_equal(a, b)


def test_host_combine_stamped_and_single_seg_parity(hosts4):
    """The two-stage agg's merge motion carries the host-combine stamp
    at 8 segments (and the planck gate accepts it); at 1 segment the
    topology gate never fires — plans stay unstamped and results match
    (the zero-regression single-host half of the satellite)."""
    from cloudberry_tpu.exec.executor import all_nodes
    from cloudberry_tpu.plan import nodes as PN
    from cloudberry_tpu.plan.planner import plan_statement
    from cloudberry_tpu.sql.parser import parse_sql

    s_on = _mk_session("on")
    plan = plan_statement(parse_sql(QUERIES[3]), s_on, {}).plan
    stamped = [m for m in all_nodes(plan) if isinstance(m, PN.PMotion)
               and m.kind == "redistribute" and m.host_combine]
    assert stamped, "merge motion did not get the host-combine stamp"
    assert all(m.host_bucket_cap >= m.bucket_cap and m.hier_hosts == 4
               for m in stamped)

    s1_on = _mk_session("on", nseg=1)
    s1_off = _mk_session("off", nseg=1)
    for q in QUERIES:
        pd.testing.assert_frame_equal(s1_off.sql(q).to_pandas(),
                                      s1_on.sql(q).to_pandas())
    p1 = plan_statement(parse_sql(QUERIES[3]), s1_on, {}).plan
    assert all(m.host_bucket_cap == 0 and not m.host_combine
               for m in all_nodes(p1) if isinstance(m, PN.PMotion))


def test_single_host_plans_unstamped(session):
    """No CBTPU_FORCE_HOSTS, one real host: the gate never fires even
    with hierarchical=on — flat remains default-equivalent."""
    from cloudberry_tpu.exec.executor import all_nodes
    from cloudberry_tpu.plan import nodes as PN
    from cloudberry_tpu.plan.planner import plan_statement
    from cloudberry_tpu.sql.parser import parse_sql

    s = _mk_session("on")
    for q in QUERIES:
        plan = plan_statement(parse_sql(q), s, {}).plan
        assert all(m.host_bucket_cap == 0 and m.hier_hosts == 0
                   and not m.host_combine
                   for m in all_nodes(plan) if isinstance(m, PN.PMotion))


def test_tpch_q3_hier_parity(hosts4):
    """Acceptance pin: TPC-H Q3 at 8 segments decodes bit-identically
    with the two-level transport on (Q10 rides the slow tier)."""
    _tpch_parity("q3")


@pytest.mark.slow
def test_tpch_q10_hier_parity(hosts4):
    _tpch_parity("q10")


def _tpch_parity(qname):
    from tools.tpch_queries import QUERIES as TPCH
    from tools.tpchgen import load_tpch

    flat = cb.Session(Config(n_segments=NSEG))
    load_tpch(flat, sf=0.01, seed=7)
    hier = cb.Session(Config(n_segments=NSEG).with_overrides(
        **{"interconnect.hierarchical": "on"}))
    load_tpch(hier, sf=0.01, seed=7)
    pd.testing.assert_frame_equal(flat.sql(TPCH[qname]).to_pandas(),
                                  hier.sql(TPCH[qname]).to_pandas())


# --------------------------------------------- host rung overflow ladder


def test_host_rung_overflow_promotes_and_retries(hosts4):
    """An undersized host rung is a DETECTED overflow (never silent):
    the check names the node, grow_expansion promotes straight to the
    rung fitting the observed host demand, and the retry is
    bit-identical to flat."""
    from cloudberry_tpu.exec import dist_executor as DX
    from cloudberry_tpu.exec import executor as X
    from cloudberry_tpu.exec.executor import all_nodes
    from cloudberry_tpu.plan import nodes as PN
    from cloudberry_tpu.plan.planner import plan_statement
    from cloudberry_tpu.sql.parser import parse_sql

    q = QUERIES[0]
    # broadcast_threshold 0 forces the join onto redistributes, so the
    # probe motion carries host stamps (dim would broadcast otherwise)
    s_off = _mk_session("off", **{"planner.broadcast_threshold": 0})
    want = s_off.sql(q).to_pandas()

    s_on = _mk_session("on", **{"planner.broadcast_threshold": 0})
    plan = plan_statement(parse_sql(q), s_on, {}).plan
    motions = [m for m in all_nodes(plan) if isinstance(m, PN.PMotion)
               and m.host_bucket_cap > 0]
    # the fact-side JOIN shuffle (not the host-combined merge — its
    # post-combine demand is a handful of groups): thousands of rows
    # per host pair, so an 8-row host block must overflow
    plain = [m for m in motions if not m.host_combine]
    assert plain
    m = max(plain, key=lambda n: n.bucket_cap)
    m.host_bucket_cap = 8            # valid rung, guaranteed overflow
    fn = DX.compile_distributed(plan, s_on)
    with pytest.raises(X.ExecError) as ei:
        DX.execute_distributed(plan, s_on, fn)
    assert "host bucket overflow" in str(ei.value)
    assert getattr(m, "_observed_host_bucket", 0) > 8
    assert X.grow_expansion(plan, str(ei.value))
    assert m.host_bucket_cap >= m._observed_host_bucket
    got = DX.execute_distributed(plan, s_on).to_pandas()
    pd.testing.assert_frame_equal(got.reset_index(drop=True),
                                  want.reset_index(drop=True))


def test_segment_rung_promotion_lifts_host_rung(hosts4):
    """Promoting bucket_cap on a hier-stamped motion must keep the
    host_bucket_cap >= bucket_cap invariant AND fold in the host demand
    the failing run already observed — otherwise the retry is a
    guaranteed host-rung overflow costing one more recompile cycle."""
    from cloudberry_tpu.exec.executor import grow_expansion
    from cloudberry_tpu.plan import expr as ex
    from cloudberry_tpu.plan import nodes as PN
    from cloudberry_tpu.types import INT64

    scan = PN.PScan("t", {"k": "k"}, 64)
    m = PN.PMotion(scan, "redistribute",
                   hash_keys=[ex.ColumnRef("k", INT64)])
    m.bucket_cap, m.out_capacity = 64, 64 * NSEG
    m.host_bucket_cap, m.hier_hosts = 256, 4
    m._observed_bucket = 5000
    m._observed_host_bucket = 9000
    assert grow_expansion(m, "redistribute overflow (node 0: Motion)")
    assert m.bucket_cap == 8192
    assert m.host_bucket_cap >= max(m.bucket_cap, 9000)


# ------------------------------------------------- satellite regressions


def test_segment_mesh_stale_device_ids_raise(session):
    from cloudberry_tpu.parallel.mesh import (DeviceRestrictionError,
                                              segment_mesh)

    # formerly: `if i < len(devices)` silently skipped the hole
    with pytest.raises(DeviceRestrictionError) as ei:
        segment_mesh(4, device_ids=[0, 1, 2, 99])
    assert ei.value.kind == "stale"
    assert "99" in str(ei.value)
    with pytest.raises(DeviceRestrictionError) as ei:
        segment_mesh(2, device_ids=[0, -1])
    assert ei.value.kind == "invalid"
    with pytest.raises(DeviceRestrictionError) as ei:
        segment_mesh(2, device_ids=[0, 0, 1])
    assert ei.value.kind == "invalid"
    # a well-formed survivor restriction still builds the mesh
    mesh = segment_mesh(4, device_ids=[0, 1, 2, 3])
    assert mesh.devices.size == 4


def test_host_skew_telemetry(hosts4):
    """A host-skewed shuffle (every row to one destination host — the
    case two-level makes WORSE) alarms: per-HOST skew histograms +
    host_skew_events next to the per-segment ones."""
    cfg = Config(n_segments=NSEG).with_overrides(**{
        "interconnect.hierarchical": "on",
        "planner.broadcast_threshold": 0,    # force the redistribute
    })
    s = cb.Session(cfg)
    s.sql("CREATE TABLE dim (d BIGINT, g BIGINT) DISTRIBUTED BY (d)")
    s.sql("CREATE TABLE fact (k BIGINT, grp BIGINT) DISTRIBUTED BY (k)")
    s.catalog.table("dim").set_data(
        {"d": np.arange(100), "g": np.arange(100) % 6})
    n = 4000
    # every fact row carries the same join key -> one destination
    # segment, hence one destination host
    s.catalog.table("fact").set_data(
        {"k": np.arange(n), "grp": np.full(n, 7)})
    before = s.stmt_log.counter("host_skew_events")
    s.sql("SELECT count(*) AS n FROM fact JOIN dim "
          "ON fact.grp = dim.d")
    assert s.stmt_log.counter("host_skew_events") > before
    assert s.stmt_log.registry.hist("motion_host_skew_ratio")


def test_capacity_accounts_two_level_staging(hosts4):
    from cloudberry_tpu.exec.executor import all_nodes
    from cloudberry_tpu.obs.capacity import (plan_device_bytes,
                                             two_level_staging_bytes)
    from cloudberry_tpu.plan import nodes as PN
    from cloudberry_tpu.plan.planner import plan_statement
    from cloudberry_tpu.sql.parser import parse_sql

    s_on = _mk_session("on", **{"planner.broadcast_threshold": 0})
    plan = plan_statement(parse_sql(QUERIES[0]), s_on, {}).plan
    stamped = [m for m in all_nodes(plan) if isinstance(m, PN.PMotion)
               and m.host_bucket_cap > 0]
    assert stamped
    assert all(two_level_staging_bytes(m) > 0 for m in stamped)
    with_staging = plan_device_bytes(plan)["wire_bytes"]
    for m in stamped:
        m.host_bucket_cap = 0
        m.hier_hosts = 0
    assert plan_device_bytes(plan)["wire_bytes"] < with_staging


def test_tiled_dist_hier_parity(hosts4):
    """The TILED distributed path runs the SAME two-level motion
    semantics as the in-memory path: an admission-rejected statement on
    a forced-4-host session streams tiles through the hierarchical
    transport (host-combined merge included) and matches the unbudgeted
    flat run exactly — a stamped plan must never pay the combine's
    grown rungs while shipping flat."""

    def mk(hier, budget=None):
        over = {"n_segments": NSEG, "planner.broadcast_threshold": 0,
                "interconnect.hierarchical": hier}
        if budget is not None:
            over["resource.query_mem_bytes"] = budget
        s = cb.Session(Config(n_segments=NSEG).with_overrides(**over))
        rng = np.random.default_rng(5)
        n = 200_000
        s.sql("CREATE TABLE dim (d BIGINT, g BIGINT) "
              "DISTRIBUTED BY (g)")
        s.sql("CREATE TABLE fact (k BIGINT, d BIGINT, v BIGINT) "
              "DISTRIBUTED BY (k)")
        s.catalog.table("dim").set_data(
            {"d": np.arange(500), "g": np.arange(500) % 9})
        s.catalog.table("fact").set_data(
            {"k": np.arange(n) % 997,
             "d": rng.integers(0, 500, n),
             "v": rng.integers(0, 100, n)})
        return s

    q = ("SELECT g, sum(v) AS sv, count(*) AS c FROM fact "
         "JOIN dim ON fact.d = dim.d GROUP BY g ORDER BY g")
    want = mk("off").sql(q).to_pandas()
    s = mk("on", budget=2 << 20)
    got = s.sql(q).to_pandas()
    pd.testing.assert_frame_equal(want, got)
    rep = s.last_tiled_report
    assert rep["tiled"] and rep["distributed"] and rep["n_tiles"] > 1


def test_ic_bench_two_level_smoke():
    """tools/ic_bench --two-level: dcn/ici split + exact checksum
    parity on the simulated 4-host split (CPU smoke; the acceptance
    measurement at 50k rows shows ~3.6x lower DCN bytes)."""
    import json
    import os

    out = subprocess.run(
        [sys.executable, "-m", "tools.ic_bench", "--two-level",
         "--hosts", "4", "--rows", "2000", "--reps", "1"],
        capture_output=True, text=True, timeout=540,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    assert out.returncode == 0, out.stderr[-2000:]
    recs = [json.loads(ln) for ln in out.stdout.splitlines()
            if ln.startswith("{")]
    by_mode = {}
    for r in recs:
        by_mode.setdefault(r["mode"], []).append(r)
    assert {"two-level", "two-level-summary"} <= set(by_mode)
    summary = by_mode["two-level-summary"][0]
    assert summary["checksums_match"] is True
    assert summary["dcn_ratio"] > 1.0
    fmts = {r["format"]: r for r in by_mode["two-level"]}
    assert fmts["hier"]["dcn_bytes"] < fmts["flat"]["dcn_bytes"]
    assert {"dcn_bytes", "ici_bytes", "launches",
            "wall_ms"} <= set(fmts["hier"])
