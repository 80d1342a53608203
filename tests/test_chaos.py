"""Chaos engineering over the fault-injection seams (faultinjector.c role).

The reference compiles ~230 named fault points and provokes races/failures
deterministically from isolation2 tests (gp_inject_fault). This suite
exercises the analog seams across the engine — dispatch, device loss,
degraded-mesh recovery (the FTS consumption point), tiled execution, the
OCC commit window, endpoints, serving, storage reads, admission — plus an
inventory test pinning the seam count so coverage cannot silently shrink.
"""

import numpy as np
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import get_config
from cloudberry_tpu.utils import faultinject as FI


@pytest.fixture(autouse=True)
def _clean_faults():
    FI.reset_fault()
    yield
    FI.reset_fault()


def _mk(nseg=1, **ov):
    over = {"n_segments": nseg}
    over.update(ov)
    return cb.Session(get_config().with_overrides(**over))


def _load(s, n=64):
    s.sql("create table t (k bigint, v bigint) distributed by (k)")
    s.catalog.table("t").set_data(
        {"k": np.arange(n, dtype=np.int64),
         "v": (np.arange(n, dtype=np.int64) * 7) % 13})


# ---------------------------------------------------- device-loss recovery


def test_device_loss_retries_and_succeeds():
    """One injected device loss -> health.recoverable -> re-dispatch wins
    (the stateless-segment recovery model: failed statements re-run)."""
    s = _mk()
    _load(s)
    FI.inject_fault("exec_device_lost", "error", start_hit=1, end_hit=1)
    df = s.sql("select sum(v) as sv from t").to_pandas()
    assert df["sv"][0] == int(((np.arange(64) * 7) % 13).sum())


def test_device_loss_exhausts_retries():
    s = _mk()
    _load(s)
    FI.inject_fault("exec_device_lost", "error")  # every hit
    with pytest.raises(FI.InjectedFault):
        s.sql("select sum(v) from t")


def test_one_device_loss_surfaces_to_the_client():
    """One chip, one segment: there is no survivor to degrade to. A
    device error that persists is re-dispatched ``health.retries`` times
    ON THE SAME one-device mesh and then reaches the client as an error —
    never a success from some other backend (ISSUE 22 step 4)."""
    import jax

    from cloudberry_tpu.serve.client import Client, ServerError
    from cloudberry_tpu.serve.server import Server

    s = _mk(**{"health.backoff_s": 0.01})
    _load(s)
    backend = jax.default_backend()
    with Server(session=s) as srv, Client(srv.host, srv.port) as c:
        FI.inject_fault("exec_device_lost", "error")  # every dispatch
        with pytest.raises(ServerError, match="device_lost") as ei:
            c.sql("select sum(v) from t")
        assert ei.value.etype == "InjectedFault"
        # first attempt + health.retries re-dispatches, all on the one
        # device: the mesh never shrank below it or moved elsewhere
        fired = FI.list_faults()["armed"]["exec_device_lost"]["fired"]
        assert fired == 1 + s.config.health.retries
        assert s.config.n_segments == 1
        assert jax.default_backend() == backend
        assert s.stmt_log.counter("recoveries") == s.config.health.retries
        FI.reset_fault()
        assert c.sql("select sum(v) as sv from t")["rows"] == \
            [[int(((np.arange(64) * 7) % 13).sum())]]


def test_non_recoverable_fault_not_retried():
    """dispatch_start is not a device-loss seam: no retry, one hit."""
    s = _mk()
    _load(s)
    FI.inject_fault("dispatch_start", "error")
    with pytest.raises(FI.InjectedFault):
        s.sql("select sum(v) from t")
    arm = FI._registry["dispatch_start"]
    assert arm.hits == 1


def test_degraded_mesh_replanning():
    """Device loss + a probe reporting one device gone -> the session
    shrinks the segment mesh and the statement completes on n-1 segments
    (fts.c probe -> configuration update; placement re-derives)."""
    s = _mk(nseg=8)
    _load(s, n=128)
    expect = s.sql("select k, v from t where v > 6 order by k").to_pandas()

    FI.inject_fault("exec_device_lost", "error", start_hit=1, end_hit=1)
    FI.inject_fault("probe_degraded", "skip")  # probe sees 7 devices
    got = s.sql("select k, v from t where v > 6 order by k").to_pandas()
    assert s.config.n_segments == 7
    assert expect.equals(got)
    # subsequent statements keep running on the degraded mesh
    FI.reset_fault()
    df = s.sql("select count(*) as c from t").to_pandas()
    assert df["c"][0] == 128


def test_degraded_mesh_skips_mid_list_hole():
    """A REAL device loss leaves a hole in the middle of jax.devices();
    recovery must mesh over the survivors, not devices[:n-1]."""
    s = _mk(nseg=8)
    _load(s, n=256)
    expect = s.sql("select v, count(*) as c from t group by v "
                   "order by v").to_pandas()
    # probe found device 3 dead: survivors are a non-prefix subset
    assert s.degrade_mesh(7, live_ids=[0, 1, 2, 4, 5, 6, 7])
    assert s.config.n_segments == 7
    assert s._live_device_ids == [0, 1, 2, 4, 5, 6, 7]
    got = s.sql("select v, count(*) as c from t group by v "
                "order by v").to_pandas()
    assert expect.equals(got)


def test_probe_reports_live_indices():
    from cloudberry_tpu.parallel import health

    r = health.probe()
    assert r.ok and r.live == list(range(r.n_devices))
    FI.inject_fault("probe_degraded", "skip")
    r2 = health.probe()
    assert r2.n_devices == r.n_devices - 1
    assert r2.live == list(range(r.n_devices - 1))


def test_read_only_classifier():
    from cloudberry_tpu.session import _read_only

    assert _read_only("select 1")
    assert _read_only("  (select 1) union (select 2)")
    assert _read_only("WITH q AS (select 1) select * from q")
    assert not _read_only("insert into t values (1)")
    assert not _read_only("create table t (x int)")
    # sequence allocation happens at plan time: a replay would burn values
    assert not _read_only("select nextval('s')")


def test_degrade_disabled_still_retries():
    s = _mk(nseg=4, **{"health.degrade": False})
    _load(s)
    FI.inject_fault("exec_device_lost", "error", start_hit=1, end_hit=1)
    FI.inject_fault("probe_degraded", "skip")
    df = s.sql("select count(*) as c from t").to_pandas()
    assert df["c"][0] == 64
    assert s.config.n_segments == 4  # mesh untouched


def test_dml_never_retried(monkeypatch):
    """A recoverable failure during DML must NOT re-dispatch: the mutation
    may already be applied, and re-execution would double-apply it. A
    recoverable failure during a SELECT retries."""

    class FakeXla(RuntimeError):
        pass

    FakeXla.__name__ = "XlaRuntimeError"
    s = _mk()
    _load(s)
    calls = []
    orig = type(s)._sql_once

    def flaky(self, query, **kw):
        calls.append(query)
        if len(calls) == 1:
            raise FakeXla("device lost mid-statement")
        return orig(self, query, **kw)

    monkeypatch.setattr(type(s), "_sql_once", flaky)
    with pytest.raises(FakeXla):
        s.sql("insert into t values (999, 1)")
    assert len(calls) == 1  # one attempt, no replay of the mutation

    calls.clear()
    df = s.sql("select count(*) as c from t").to_pandas()
    assert len(calls) == 2 and df["c"][0] == 64  # retried and answered


def test_retries_zero_disables_recovery():
    s = _mk(**{"health.retries": 0})
    _load(s)
    FI.inject_fault("exec_device_lost", "error", start_hit=1, end_hit=1)
    with pytest.raises(FI.InjectedFault):
        s.sql("select count(*) from t")


# ---------------------------------------------------------- tiled seams


def test_tile_step_fault_fails_clean_then_recovers():
    """A fault mid-tile-stream surfaces cleanly, releases the admission
    slot, and the same statement succeeds after disarm."""
    rng = np.random.default_rng(5)
    s = _mk(**{"resource.query_mem_bytes": 4 << 20, "health.retries": 0})
    s.sql("create table dim (k bigint, g bigint) distributed by (k)")
    s.sql("create table fact (k bigint, v bigint) distributed by (k)")
    s.catalog.table("dim").set_data(
        {"k": np.arange(500), "g": np.arange(500) % 9})
    s.catalog.table("fact").set_data(
        {"k": rng.integers(0, 500, 200_000),
         "v": rng.integers(0, 100, 200_000)})
    q = ("select g, sum(v) as sv from fact join dim on fact.k = dim.k "
         "group by g order by g")
    FI.inject_fault("tile_step", "error", start_hit=2)
    with pytest.raises(FI.InjectedFault):
        s.sql(q)
    FI.reset_fault()
    df = s.sql(q).to_pandas()
    assert s.last_tiled_report["n_tiles"] > 1
    assert len(df) == 9


# ------------------------------------------------------ OCC commit window


def test_occ_commit_window_fault_releases_lock(tmp_path):
    """An error inside the commit critical section must release the store
    lock: another session can still commit afterwards."""
    a = cb.Session(get_config().with_overrides(
        **{"storage.root": str(tmp_path)}))
    a.sql("create table ct (x bigint)")
    a.sql("insert into ct values (1)")
    a.sql("begin")
    a.sql("insert into ct values (2)")
    FI.inject_fault("occ_commit_window", "error")
    with pytest.raises(FI.InjectedFault):
        a.sql("commit")
    FI.reset_fault()
    b = cb.Session(get_config().with_overrides(
        **{"storage.root": str(tmp_path)}))
    b.sql("insert into ct values (3)")  # lock free -> this commits
    assert len(b.sql("select x from ct").to_pandas()) >= 2


# ----------------------------------------------------------- other seams


def test_admission_check_seam():
    s = _mk(**{"health.retries": 0})
    _load(s)
    FI.inject_fault("admission_check", "error")
    with pytest.raises(FI.InjectedFault):
        s.sql("select v from t")
    FI.reset_fault()
    assert len(s.sql("select v from t").to_pandas()) == 64


def test_store_read_partition_seam(tmp_path):
    s = cb.Session(get_config().with_overrides(
        **{"storage.root": str(tmp_path), "health.retries": 0}))
    s.sql("create table st (x bigint)")
    s.sql("insert into st values (1),(2),(3)")
    s2 = cb.Session(get_config().with_overrides(
        **{"storage.root": str(tmp_path), "health.retries": 0}))
    FI.inject_fault("store_read_partition", "error")
    with pytest.raises(FI.InjectedFault):
        s2.sql("select sum(x) from st").to_pandas()
    FI.reset_fault()
    assert s2.sql("select sum(x) as s from st").to_pandas()["s"][0] == 6


def test_matview_maintain_seam():
    s = _mk(**{"health.retries": 0})
    _load(s)
    s.sql("create incremental materialized view mv as "
          "select count(*) as c from t")
    FI.inject_fault("matview_maintain", "error")
    with pytest.raises(FI.InjectedFault):
        s.sql("insert into t values (1000, 1)")
    FI.reset_fault()
    s.sql("insert into t values (1001, 2)")


def test_seam_inventory():
    """Pin the declared seam count: the faultinjector.c analog loses its
    value if refactors silently drop seams. grep the package source for
    fault_point(\"name\") declarations."""
    import pathlib
    import re

    root = pathlib.Path(cb.__file__).parent
    names = set()
    for p in root.rglob("*.py"):
        names |= set(re.findall(r'fault_point\("([a-z_]+)"\)',
                                p.read_text()))
    assert len(names) >= 20, sorted(names)
    # the load-bearing seams must exist by exact name — including the
    # mid-statement recovery trio (exec/recovery.py): the deterministic/
    # probabilistic tile kill and the checkpoint/resume chaos arms
    for required in ("dispatch_start", "exec_device_lost", "probe_degraded",
                     "tile_step", "tile_step_dist", "occ_commit_window",
                     "storage_commit_before_current", "endpoint_drain",
                     "serve_handler", "store_read_partition",
                     "admission_check", "dml_update", "dml_delete",
                     "tile_device_lost", "ckpt_save", "ckpt_resume"):
        assert required in names, required
