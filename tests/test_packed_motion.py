"""Packed-wire motion (exec/kernels.py wire format + the fused one-
collective-per-motion paths in exec/dist_executor.py): bit-identical to
the legacy per-column launches for every dtype, every motion kind, and
1- and 8-segment meshes — plus the adaptive capacity-rung ladder end to
end (skew overflow promotes a rung and retries without intervention)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import Config
from cloudberry_tpu.exec import kernels as K
from cloudberry_tpu.plan import nodes as N


# ----------------------------------------------------------- kernel level


def _roundtrip(cols, sel):
    lay = K.wire_layout({k: v.dtype for k, v in cols.items()})
    buf = jax.jit(lambda c, s: K.pack_wire(c, s, lay))(cols, sel)
    assert buf.dtype == jnp.uint32 and buf.shape == (sel.shape[0],
                                                     lay.width)
    out, osel = jax.jit(lambda b: K.unpack_wire(b, lay))(buf)
    assert np.array_equal(np.asarray(osel), np.asarray(sel))
    for k, v in cols.items():
        a, b = np.asarray(v), np.asarray(out[k])
        assert a.dtype == b.dtype, k
        if a.dtype == np.bool_:
            assert np.array_equal(a, b), k
        else:
            w = f"u{a.dtype.itemsize}"
            assert np.array_equal(a.view(w), b.view(w)), k
    return lay


def test_wire_roundtrip_all_dtypes_bit_identical():
    rng = np.random.default_rng(5)
    n = 33
    cols = {
        "b": jnp.asarray(rng.integers(0, 2, n).astype(bool)),
        "i32": jnp.asarray(np.concatenate(
            [[0, -1, 2**31 - 1, -2**31],
             rng.integers(-10**9, 10**9, n - 4)]).astype(np.int32)),
        "i64": jnp.asarray(np.concatenate(
            [[0, -1, 2**63 - 1, -2**63],
             rng.integers(-2**62, 2**62, n - 4)])),
        "f32": jnp.asarray(np.array(
            [0.0, -0.0, np.nan, np.inf, -np.inf, 1e-39]
            + list(rng.standard_normal(n - 6)), dtype=np.float32)),
        "f64": jnp.asarray(np.array(
            [0.0, -0.0, np.nan, np.inf, 1e308, 5e-324]
            + list(rng.standard_normal(n - 6)))),
    }
    sel = jnp.asarray(rng.integers(0, 2, n).astype(bool))
    lay = _roundtrip(cols, sel)
    # int64-limb transport convention: the two u32 words reassemble the
    # exact bit pattern (PR 1's DECIMAL/int64 discipline on the wire)
    assert lay.width == 1 + 1 + 2 + 1 + 2
    # an all-zero slot (an unfilled redistribute bucket) is INVALID
    zero = jnp.zeros((4, lay.width), jnp.uint32)
    _, zsel = K.unpack_wire(zero, lay)
    assert not bool(np.asarray(zsel).any())


def test_wire_roundtrip_many_bools_spill_flag_words():
    # >31 bool columns must spill into a second flag word
    rng = np.random.default_rng(6)
    n = 16
    cols = {f"b{i:02d}": jnp.asarray(rng.integers(0, 2, n).astype(bool))
            for i in range(40)}
    sel = jnp.asarray(rng.integers(0, 2, n).astype(bool))
    lay = _roundtrip(cols, sel)
    assert lay.width == 2  # 41 bits of flags -> two words, zero payload


def test_rung_ladder_is_pow2_and_monotone():
    assert [K.rung_up(x) for x in (0, 1, 8, 9, 500, 512, 513)] == \
        [8, 8, 8, 16, 512, 512, 1024]


# ------------------------------------------------------------ query level


def _dist_plan(s, sql):
    """Bound + distributed plan regardless of n_segments (the 1-segment
    mesh still exercises real collectives through execute_distributed,
    unlike the loopback single-program path)."""
    from cloudberry_tpu.plan.binder import Binder
    from cloudberry_tpu.plan.cost import annotate_pack_bits
    from cloudberry_tpu.plan.distribute import distribute_plan
    from cloudberry_tpu.plan.prune import prune_plan
    from cloudberry_tpu.sql.parser import parse_sql

    plan = prune_plan(Binder(s.catalog).bind_query(parse_sql(sql)))
    annotate_pack_bits(plan, s.catalog)
    return distribute_plan(plan, s)


def _session(nseg, packed, **over):
    cfg = Config(n_segments=nseg).with_overrides(
        **{"interconnect.packed_wire": packed, **over})
    return cb.Session(cfg)


def _fill(s):
    s.sql("create table t (k bigint, i int, d decimal(10,2), "
          "f float8, dt date, txt text, v bigint) "
          "distributed by (k)")
    rows = []
    for i in range(160):
        v = "null" if i % 11 == 0 else str(i * 3 - 200)
        rows.append(f"({i}, {i % 37 - 18}, {i}.{i % 100:02d}, "
                    f"{(i - 80) * 1.25e-3}, date '1995-0{i % 9 + 1}-17', "
                    f"'s{i % 5}', {v})")
    s.sql("insert into t values " + ",".join(rows))
    s.sql("create table dim (j bigint, j2 bigint, w float8) "
          "distributed by (j2)")
    s.sql("insert into dim values " + ",".join(
        f"({i - 15}, {i}, {i * 0.5 - 3})" for i in range(30)))


# gather (sort), broadcast (small build, probe keys ≠ distribution), and
# redistribute (two-stage group-by forced past GATHER_SINGLE)
_QUERIES = [
    "select k, i, d, f, dt, txt, v from t order by k",
    "select t.k, t.f, dim.w, t.v from t join dim on t.i = dim.j "
    "order by t.k",
    "select i, sum(v) as sv, count(*) as c, max(f) as mf from t "
    "group by i order by i",
]


def _assert_batches_bit_identical(a, b, ctx=""):
    assert np.array_equal(np.asarray(a.sel), np.asarray(b.sel)), ctx
    m = np.asarray(a.sel)
    assert set(a.columns) == set(b.columns), ctx
    for name in a.columns:
        x = np.asarray(a.columns[name])[m]
        y = np.asarray(b.columns[name])[m]
        assert x.dtype == y.dtype, (ctx, name)
        if x.dtype.kind == "f":
            w = f"u{x.dtype.itemsize}"
            assert np.array_equal(x.view(w), y.view(w)), (ctx, name)
        else:
            assert np.array_equal(x, y), (ctx, name)
    for name in set(a.validity) | set(b.validity):
        assert np.array_equal(np.asarray(a.validity[name])[m],
                              np.asarray(b.validity[name])[m]), (ctx, name)


_FILL_SESSIONS: dict = {}


def _fill_session(nseg, packed):
    # gather_single_threshold=0 only affects the group-by query (forces
    # its merge onto a redistribute), so one session per (nseg, packed)
    # serves all three motion kinds
    key = (nseg, packed)
    if key not in _FILL_SESSIONS:
        s = _session(nseg, packed,
                     **{"planner.gather_single_threshold": 0})
        _fill(s)
        _FILL_SESSIONS[key] = s
    return _FILL_SESSIONS[key]


@pytest.mark.parametrize("nseg", [1, 8], ids=["seg1", "seg8"])
@pytest.mark.parametrize("qi", range(len(_QUERIES)),
                         ids=["gather", "broadcast", "redistribute"])
def test_packed_matches_percol_all_motion_kinds(nseg, qi):
    from cloudberry_tpu.exec.dist_executor import execute_distributed

    batches = {}
    for packed in (False, True):
        s = _fill_session(nseg, packed)
        plan = _dist_plan(s, _QUERIES[qi])
        kinds = {n.kind for n in _walk_motions(plan)}
        if qi == 1:
            assert "broadcast" in kinds
        if qi == 2:
            assert "redistribute" in kinds
        batches[packed] = execute_distributed(plan, s)
    _assert_batches_bit_identical(batches[True], batches[False],
                                  f"nseg={nseg} q={qi}")


def _walk_motions(plan):
    out = []

    def walk(n):
        if isinstance(n, N.PMotion):
            out.append(n)
        for c in n.children():
            walk(c)

    walk(plan)
    return out


_TPCH_SESSIONS: dict = {}


def _tpch_session(nseg, packed):
    """One loaded session per (nseg, packed) for the whole module — the
    Q3/Q10 pins share them."""
    from tools.tpchgen import load_tpch

    key = (nseg, packed)
    if key not in _TPCH_SESSIONS:
        s = _session(nseg, packed)
        load_tpch(s, sf=0.01, seed=7)
        _TPCH_SESSIONS[key] = s
    return _TPCH_SESSIONS[key]


@pytest.mark.parametrize("nseg", [1, 8], ids=["seg1", "seg8"])
@pytest.mark.parametrize("qname", ["q3", "q10"])
def test_tpch_packed_parity_pinned(nseg, qname):
    """Acceptance pin: packed motion is bit-identical to the per-column
    path across TPC-H Q3/Q10 at 1 and 8 segments."""
    from cloudberry_tpu.exec.dist_executor import execute_distributed
    from tools.tpch_queries import QUERIES

    batches = {}
    for packed in (False, True):
        s = _tpch_session(nseg, packed)
        plan = _dist_plan(s, QUERIES[qname])
        batches[packed] = execute_distributed(plan, s)
    _assert_batches_bit_identical(batches[True], batches[False],
                                  f"{qname} nseg={nseg}")


# --------------------------------------------- adaptive rung ladder, e2e


def test_skewed_rung_promotion_end_to_end():
    """A hot join key behind a projection (so the exact plan-time bucket
    sizer cannot see the base scan) overflows the estimate-seeded rung;
    the retry must promote to the rung fitting the OBSERVED bucket
    demand and finish with no user action — and every compiled rung
    lands in the session's executable cache."""
    cfg = Config(n_segments=8).with_overrides(**{
        "planner.broadcast_threshold": 0,
        "planner.runtime_filter_threshold": 0,
    })
    s = cb.Session(cfg)
    s.sql("create table j1 (a bigint, key bigint) distributed by (a)")
    s.sql("create table j2 (b bigint, key bigint, w bigint) "
          "distributed by (b)")
    s.sql("insert into j1 values " +
          ",".join(f"({i}, {0 if i < 1500 else i})" for i in range(2000)))
    s.sql("insert into j2 values " +
          ",".join(f"({i}, {i}, {i})" for i in range(2000)))
    # the projection hides the base scan from _exact_bucket_cap: the
    # probe redistribute is sized from the fair-share estimate, which
    # the 75%-hot key blows through
    q = ("select sum(j2.w) as sw from (select key as kk from j1) x "
         "join j2 on kk = j2.key")
    out = s.sql(q).to_pandas()
    assert out.sw[0] == 0 * 1500 + sum(range(1500, 2000))

    # the seed rung overflowed at least once and promotion recovered
    assert s.growth_events >= 1
    # every promoted rung signature has its own session-cached executable
    assert len(s._rung_cache) >= 2
    for (_, _, _, _, _, rung_sig) in s._rung_cache:
        for entry in rung_sig:
            if entry[0] == "redistribute":
                bucket_cap = entry[1]
                assert bucket_cap & (bucket_cap - 1) == 0, \
                    f"bucket cap {bucket_cap} is off the pow2 ladder"

    # re-execution reuses the promoted runner: no further growth
    before = s.growth_events
    out2 = s.sql(q).to_pandas()
    assert out2.equals(out)
    assert s.growth_events == before


def test_stmt_cache_is_lru_and_bounded():
    """Satellite: the prepared-statement cache evicts least-recently-USED
    (hits reorder), not first-inserted, and stays bounded."""
    s = cb.Session()
    s.sql("create table lt (a bigint)")
    s.sql("insert into lt values (1),(2),(3)")
    s._STMT_CACHE_MAX = 4
    # one skeleton each: texts that differ in a literal alone bind through
    # the skeleton's literal template and never enter this cache
    qs = [f"select a + {i} as x{i} from lt" for i in range(4)]
    for q in qs:
        s.sql(q)
    assert all(q in s._stmt_cache for q in qs)
    s.sql(qs[0])                       # touch the oldest -> MRU
    s.sql("select a + 99 as x from lt")  # evicts qs[1], not qs[0]
    assert qs[0] in s._stmt_cache
    assert qs[1] not in s._stmt_cache
    assert len(s._stmt_cache) <= 4
