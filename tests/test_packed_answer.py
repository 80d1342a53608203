"""The packed answer of a one-shot launch (exec/executor.py pack_answer /
unpack_answer / run_executable, ISSUE 27): the program ends by packing its
check flags, ``sel`` and every output column and mask into as few device
buffers as the chip's compiler allows (bytes; float64), the host reads
them in one wait and cuts NumPy views by a layout jit keeps with the
program. Pinned here: the batch is array for array and dtype for dtype
what ``make_batch`` builds from the unpacked program's outputs; a fired
check raises what ``raise_checks`` raises and no batch leaves; the
counters count one-shot launches only.
"""

import numpy as np
import pytest

import jax

import cloudberry_tpu as cb
from cloudberry_tpu.config import Config
from cloudberry_tpu.exec import executor as X
from cloudberry_tpu.plan import nodes as N


def _plan(session, sql):
    from cloudberry_tpu.plan.planner import plan_statement
    from cloudberry_tpu.sql.parser import parse_sql

    return plan_statement(parse_sql(sql), session, {}).plan


@pytest.fixture(scope="module")
def session():
    s = cb.Session(Config())
    s.sql("create table t (a int, b decimal(10,2), c text, d double, "
          "e bigint) distributed by (a)")
    rows = []
    for i in range(40):
        d = "null" if i % 5 == 0 else f"{i * 0.37 - 3.1}"
        rows.append(f"({i}, {i * 1.25}, 'k{i % 3}', {d}, {i * 10**12})")
    s.sql("insert into t values " + ",".join(rows))
    s.sql("create table wide (k bigint, v double) distributed by (k)")
    s.sql("insert into wide values " + ",".join(
        f"({i},{i / 7})" for i in range(10_000)))
    return s


def _same_batch(a, b):
    assert [f.name for f in a.schema.fields] \
        == [f.name for f in b.schema.fields]
    assert set(a.columns) == set(b.columns)
    for name in a.columns:
        x, y = np.asarray(a.columns[name]), b.columns[name]
        assert isinstance(y, np.ndarray), name
        assert x.dtype == y.dtype and x.shape == y.shape, name
        assert x.tobytes() == y.tobytes(), name
        assert y.flags.aligned, name
    sa, sb = np.asarray(a.sel), b.sel
    assert sa.dtype == sb.dtype == np.bool_ and sa.shape == sb.shape
    assert sa.tobytes() == sb.tobytes()
    assert set(a.validity) == set(b.validity)
    for name in a.validity:
        assert a.validity[name].dtype == b.validity[name].dtype
        assert a.validity[name].tobytes() == b.validity[name].tobytes()
    assert a.dicts == b.dicts


# every output kind: int32 dictionary codes, int64 sums, float64 averages,
# ``$vm`` masks, ``sel`` with masked rows, zero rows, scalars, bool columns,
# leaves too large to pack
_KINDS = {
    "grouped": ("select c, sum(b) sb, avg(b) ab, avg(d) ad, count(*) n, "
                "max(d) md, min(e) me from t where a < 30 group by c "
                "order by c", 2),
    "masked_rows": ("select a, b, c, d, e from t where a % 3 = 1", 2),
    "zero_rows": ("select c, sum(b) sb, avg(d) ad from t where a < 0 "
                  "group by c", 2),
    "scalar_agg": ("select sum(b) sb, avg(d) ad, count(d) nd from t", 2),
    "ints_only": ("select c, sum(e) se, count(*) n from t group by c", 1),
    "bool_column": ("select a, a > 7 as big, d is null as dn from t "
                    "where a < 20", 1),
    "large_leaves": ("select k, v from wide where k % 2 = 0", None),
}


@pytest.mark.parametrize("kind", list(_KINDS))
def test_packed_batch_equals_make_batch_of_the_unpacked_program(
        session, kind, monkeypatch):
    sql, reads = _KINDS[kind]
    if kind == "large_leaves":
        # (read when the program is traced) 10,000 rows of 8 bytes pass it
        monkeypatch.setattr(X, "_PACK_LEAF_MAX", 1 << 16)
    plan = _plan(session, sql)
    exe = X.compile_plan(plan, session)
    tables = X.prepare_inputs(exe, session)
    cols, sel, checks = exe.fn(tables)
    X.raise_checks(checks)
    want = X.make_batch(plan, cols, sel)
    log = session.stmt_log
    before = (log.counter("launch_packed"), log.counter("launch_d2h_reads"))
    got = X.run_executable(exe, tables, log=log)
    _same_batch(want, got)
    packed = exe.packed_fn(tables)
    made = log.counter("launch_d2h_reads") - before[1]
    assert log.counter("launch_packed") - before[0] == 1
    assert made == len(packed.bufs)
    if reads is not None:
        assert made == reads, packed.layout
    if kind == "large_leaves":
        # leaves over _PACK_LEAF_MAX ride beside the byte buffer as
        # arrays of their own, not held twice on the device
        big = [b for b in packed.bufs if b.nbytes > X._PACK_LEAF_MAX]
        assert len(big) == 2 and made == 3
        # (sel, a byte a row of the scan's capacity: the rung above
        # the table's 10,000 rows)
        assert sum(b.nbytes for b in packed.bufs[:1]) <= 10_240
    if kind == "zero_rows":
        assert not got.sel.any()
    if kind == "masked_rows":
        assert got.sel.any() and not got.sel.all()
    if kind == "grouped":
        assert got.validity and got.dicts


def test_pack_and_unpack_every_dtype_bit_for_bit():
    rng = np.random.default_rng(11)
    cols = {
        "i64": rng.integers(-2**63, 2**63 - 1, 9, dtype=np.int64),
        "u64": rng.integers(0, 2**64 - 1, 3, dtype=np.uint64),
        "i32": rng.integers(-2**31, 2**31 - 1, 7, dtype=np.int32),
        "i16": rng.integers(-2**15, 2**15 - 1, 3, dtype=np.int16),
        "i8": rng.integers(-128, 127, 5, dtype=np.int8),
        "f32": rng.standard_normal(5).astype(np.float32),
        "f64": np.array([0.0, -0.0, np.inf, -np.inf, np.nan, 5e-324,
                         1 / 3, 1.7976931348623157e308]),
        "b": rng.integers(0, 2, 9).astype(np.bool_),
        "empty": np.zeros(0, np.int64),
        "scalar": np.int64(-12345678901234),
        "matrix": rng.integers(0, 99, (3, 4), dtype=np.int32),
    }
    sel = rng.integers(0, 2, 9).astype(np.bool_)
    checks = {"quiet (node 1)": np.zeros(4, np.bool_),
              "fires (node 2)": np.array([False, True]),
              "counts (node 3)": np.int32(3)}
    packed = jax.jit(X.pack_answer)(cols, sel, checks)
    assert [str(b.dtype) for b in packed.bufs] == ["uint8", "float64"]
    c, s, k = X.unpack_answer(packed.layout,
                              [np.asarray(b) for b in packed.bufs])
    # (jit hands pack_answer its dicts in sorted key order)
    assert list(c) == sorted(cols) and list(k) == sorted(checks)
    for name, v in cols.items():
        v = np.asarray(v)
        assert c[name].dtype == v.dtype and c[name].shape == v.shape, name
        assert c[name].tobytes() == v.tobytes(), name
        assert c[name].flags.aligned, name
    assert s.dtype == np.bool_ and s.tobytes() == sel.tobytes()
    assert {m: bool(v) for m, v in k.items()} == {
        "quiet (node 1)": False, "fires (node 2)": True,
        "counts (node 3)": True}
    with pytest.raises(X.ExecError, match=r"counts \(node 3\)"):
        X.raise_checks(k)  # the first that fired, in the dict's order


def test_layout_rides_with_each_traced_shape():
    """jit keeps the layout in the output structure of the shape it
    traced: the same program at another capacity cuts its own views."""
    f = jax.jit(lambda cols, sel: X.pack_answer(cols, sel, {}))
    for n in (4, 9, 4):
        cols = {"x": np.arange(n, dtype=np.int64),
                "y": np.arange(n, dtype=np.float64) / 3}
        p = f(cols, np.ones(n, np.bool_))
        c, s, _ = X.unpack_answer(p.layout,
                                  [np.asarray(b) for b in p.bufs])
        assert c["x"].tolist() == list(range(n)) and s.shape == (n,)
        assert c["y"].tobytes() == cols["y"].tobytes()
    assert f._cache_size() == 2
    # static data: equal layouts hash and compare equal (a treedef's)
    leaves, tree = jax.tree_util.tree_flatten(p)
    assert len(leaves) == 2
    assert tree == jax.tree_util.tree_structure(f(cols, np.ones(4, bool)))


# ------------------------------------------------ a fired check still raises


def _dup_session():
    s = cb.Session(Config())
    s.sql("create table dim (d int, p int) distributed by (d)")
    s.sql("create table fact (grp int, x int) distributed by (grp)")
    s.sql("insert into dim values " + ",".join(
        f"({i},{i * 10})" for i in range(8)))
    s.sql("insert into fact values " + ",".join(
        f"({i % 8},{i})" for i in range(64)))
    return s


def _fired_duplicate_keys():
    from cloudberry_tpu.exec.joinindex import strip_join_index

    s = _dup_session()
    plan = _plan(s, "select grp, p from fact, dim where grp = d "
                    "order by grp, p limit 5")
    joins = [n for n in X.all_nodes(plan) if isinstance(n, N.PJoin)]
    assert joins and all(j.unique_build for j in joins)
    # the proof goes stale: one key duplicated IN PLACE (same shape)
    t = s.catalog.table("dim")
    data = {c: np.asarray(v).copy() for c, v in t.data.items()}
    data["d"][1] = data["d"][0]
    t.set_data(data, t.dicts)
    strip_join_index(plan)  # the in-program check, not the cached index
    return s, plan, X.DuplicateBuildKeyError, "duplicate keys"


def _fired_aggregation_overflow():
    s = cb.Session(Config())
    s.sql("create table t (a int, g int) distributed by (a)")
    s.sql("insert into t values " + ",".join(
        f"({i},{i % 17})" for i in range(200)))
    plan = _plan(s, "select g, count(*) n from t group by g")
    aggs = [n for n in X.all_nodes(plan)
            if hasattr(n, "group_keys") and hasattr(n, "capacity")]
    assert aggs
    for n in aggs:
        n.capacity = 4  # 17 groups
    return s, plan, X.ExecError, "aggregation overflow"


def _fired_scalar_subquery():
    s = cb.Session(Config())
    s.sql("create table t (a int, g int) distributed by (a)")
    s.sql("insert into t values (1, 1), (2, 1), (3, 2)")
    plan = _plan(s, "select a from t where g = (select g from t "
                    "where a < 3)")
    return s, plan, X.ExecError, "more than one row"


@pytest.mark.parametrize("fired", [_fired_duplicate_keys,
                                   _fired_aggregation_overflow,
                                   _fired_scalar_subquery],
                         ids=["duplicate_build_keys", "aggregation_overflow",
                              "scalar_subquery_rows"])
def test_a_fired_check_raises_the_same_error_and_no_batch(fired):
    s, plan, cls, word = fired()
    exe = X.compile_plan(plan, s)
    tables = X.prepare_inputs(exe, s)
    _cols, _sel, checks = exe.fn(tables)
    with pytest.raises(X.ExecError) as want:
        X.raise_checks(checks)
    assert type(want.value) is cls and word in str(want.value)
    out = None
    with pytest.raises(X.ExecError) as got:
        out = X.run_executable(exe, tables, log=s.stmt_log)
    assert out is None
    assert type(got.value) is cls
    assert str(got.value) == str(want.value)
    # the read was made and counted; the check is what stopped the answer
    assert s.stmt_log.counter("launch_packed") == 1


# ------------------------------------------------------------- the counters


def _counts(log):
    return (log.counter("launch_packed"), log.counter("launch_d2h_reads"))


def test_one_shot_statement_counts_one_packed_launch(session):
    log = session.stmt_log
    before = _counts(log)
    session.sql("select c, sum(e) se from t where a < 11 group by c")
    packed, reads = (a - b for a, b in zip(_counts(log), before))
    assert (packed, reads) == (1, 1)
    # generic plans take the same launch (GenericPlan.run)
    before = _counts(log)
    session.sql("select c, sum(e) se from t where a < 12 group by c")
    assert tuple(a - b for a, b in zip(_counts(log), before)) == (1, 1)
    # a float64 in the answer is one more buffer, read in the same wait
    before = _counts(log)
    session.sql("select c, avg(d) ad from t group by c")
    assert tuple(a - b for a, b in zip(_counts(log), before)) == (1, 2)


def test_tiled_statement_counts_no_packed_launch(tmp_path):
    cfg = Config().with_overrides(**{
        "storage.root": str(tmp_path / "store"),
        "storage.rows_per_partition": 4096,
        "resource.query_mem_bytes": 1 << 20,
        "bufferpool.max_bytes": 1 << 20})
    s = cb.Session(cfg)
    s.sql("create table staged (k bigint, v bigint, g bigint) "
          "distributed by (k)")
    s.sql("insert into staged values " + ",".join(
        f"({i},{i % 7},{i % 3})" for i in range(60_000)))
    before = _counts(s.stmt_log)
    tiles = s.stmt_log.registry.snapshot()["histograms"].get(
        "tile_seconds", {"count": 0})["count"]
    out = s.sql("select g, sum(v) as sv from staged where v < 5 "
                "group by g order by g").to_pandas()
    assert len(out) == 3
    assert s.stmt_log.registry.snapshot()["histograms"][
        "tile_seconds"]["count"] > tiles       # it did tile
    assert _counts(s.stmt_log) == before


def test_distributed_statement_counts_no_packed_launch():
    """The distributed launch reads leaf by leaf: it counts every blocking
    read on the shared counter and itself on ``launch_dist``, never on
    ``launch_packed``."""
    s = cb.Session(Config(n_segments=4))
    s.sql("create table t (a int, b bigint) distributed by (a)")
    s.sql("insert into t values " + ",".join(
        f"({i},{i * 3})" for i in range(100)))
    before = _counts(s.stmt_log)
    dist = s.stmt_log.counter("launch_dist")
    out = s.sql("select b % 5 as m, sum(b) sb from t group by b % 5 "
                "order by m").to_pandas()
    assert len(out) == 5
    packed, reads = (a - b for a, b in zip(_counts(s.stmt_log), before))
    assert packed == 0
    assert s.stmt_log.counter("launch_dist") - dist == 1
    assert reads >= 4       # sel, two columns and at least one check
