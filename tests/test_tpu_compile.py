"""Programs of the main path, compiled for a v5e that is described, not
attached (on-chip-measurement guide §2, third rehearsal).

The TPU's compiler is installed wherever the tests run; it compiles for a
``v5e:2x2`` topology description without a chip. Nothing here runs, so
nothing here is a chip result — these tests only say that the compiler
ACCEPTS what the engine would hand it at TPC-H SF1 shapes (lineitem
6,001,215 rows), and pin what it refuses today (a float64 bitcast).

Rules this file obeys (the driver runs 6 xdist workers, and only one
process may hold libtpu): the topology is described inside a module-scoped
fixture that skips when it cannot be — never at import, never in
conftest, never autouse — and everything compiles in the test's own
process, all in this one file. Code under test asks
``jax.default_backend()`` for its platform branch (executor.py Lowerer,
tilepipe.step_donation); the ``as_tpu`` fixture steers that here, in the
test, not through a program option. The persistent compilation cache is
not on under tests (entry points enable it, utils/compilecache.py), so
these compiles neither read nor write one.

Q3's one-segment program (5 sorts) takes the TPU compiler 342 s at
SF0.01 shapes and 1009 s at SF1 shapes (PR 22 sandbox, one thread), so it
is NOT compiled in tier-1: ``test_q3_compiles_for_tpu`` is marked slow.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

SF1_LINEITEM_ROWS = 6_001_215


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """Make the engine's ``jax.default_backend()`` platform branches take
    their accelerator side while a program is built and traced."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _shapes(tree, sharding):
    """Arrays → ShapeDtypeStructs placed by ``sharding`` (a described
    device holds no array, so programs lower from shapes)."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=sharding), tree)


def _tpch_session(tables, rows=None, **over):
    """A session holding ``tables`` of TPC-H SF0.01, each encoded column
    cyclically resized to ``rows[name]`` rows where given: SF1 SHAPES
    without SF1 generation (the compiler reads shapes, not values)."""
    import cloudberry_tpu as cb
    from cloudberry_tpu.catalog.catalog import DistributionPolicy
    from cloudberry_tpu.columnar.batch import encode_column
    from cloudberry_tpu.config import Config
    from tools.tpchgen import DIST_KEYS, SCHEMAS, generate

    raw = generate(0.01, seed=7)
    s = cb.Session(Config().with_overrides(**over))
    for name in tables:
        schema, keys = SCHEMAS[name], DIST_KEYS[name]
        t = s.catalog.create_table(
            name, schema, DistributionPolicy.replicated() if keys is None
            else DistributionPolicy.hashed(*keys))
        enc = {f.name: encode_column(raw[name][f.name], f, t.dicts)
               for f in schema.fields}
        if rows and name in rows:
            enc = {c: np.resize(v, rows[name]) for c, v in enc.items()}
        t.set_data(enc, t.dicts)
    return s


@pytest.fixture(scope="module")
def sf1_lineitem():
    return _tpch_session(["lineitem"],
                         rows={"lineitem": SF1_LINEITEM_ROWS})


def _plan(session, sql):
    from cloudberry_tpu.plan.planner import plan_statement
    from cloudberry_tpu.sql.parser import parse_sql

    return plan_statement(parse_sql(sql), session, {}).plan


def _compile_one_shot(session, qname, one_chip, packed=False):
    from cloudberry_tpu.exec.executor import compile_plan, prepare_inputs
    from tools.tpch_queries import QUERIES

    exe = compile_plan(_plan(session, QUERIES[qname]), session,
                       platform="tpu")
    shapes = _shapes(prepare_inputs(exe, session), one_chip)
    return (exe.packed_fn if packed else exe.fn).lower(shapes).compile()


@pytest.mark.parametrize("qname", ["q1", "q6"])
def test_scan_agg_programs_compile_for_tpu(sf1_lineitem, one_chip, qname):
    """Q1 and Q6 — the scan + (grouped) aggregate programs of the served
    path — in the TPU formulation (dense_strategy="reduce") at SF1 shapes."""
    compiled = _compile_one_shot(sf1_lineitem, qname, one_chip)
    mem = compiled.memory_analysis()
    # fits one 16 GB chip with room: arguments + temporaries
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 8 << 30


@pytest.mark.parametrize("qname, bufs", [("q1", ["uint8", "float64"]),
                                         ("q6", ["uint8"])])
def test_packed_answers_compile_for_tpu(sf1_lineitem, one_chip, qname,
                                        bufs):
    """The programs the served one-shot path launches (run_executable):
    the same plans ending in pack_answer. The TPU compiler takes the
    bitcast of every integer column to bytes; float64 (two float32 on
    the chip, no bitcast) rides in a buffer of its own, so Q1's answer
    is two device buffers and Q6's one."""
    compiled = _compile_one_shot(sf1_lineitem, qname, one_chip, packed=True)
    out = jax.tree_util.tree_leaves(compiled.out_info)
    assert [o.dtype.name for o in out] == bufs
    assert all(o.ndim == 1 for o in out)
    # an answer of a few hundred bytes
    assert compiled.memory_analysis().output_size_in_bytes < 1 << 16


def test_float64_bitcast_is_what_the_tpu_compiler_refuses(one_chip):
    """Why pack_answer keeps float64 out of the byte buffer: pinned, so
    a compiler that learns the bitcast is noticed (the answer could then
    be one buffer)."""
    import jax.lax as lax

    x = jax.ShapeDtypeStruct((8,), jnp.float64, sharding=one_chip)
    with pytest.raises(Exception, match="X64"):
        jax.jit(lambda v: lax.bitcast_convert_type(v, jnp.uint8)) \
            .lower(x).compile()
    y = jax.ShapeDtypeStruct((8,), jnp.int64, sharding=one_chip)
    jax.jit(lambda v: lax.bitcast_convert_type(v, jnp.uint8)) \
        .lower(y).compile()


def test_tiled_step_compiles_for_tpu_with_donation(sf1_lineitem, one_chip,
                                                   as_tpu):
    """One exec/tiled.py tile step of Q6 under the smoke's 256 MiB budget:
    on an accelerator the step DONATES its accumulator
    (tilepipe.step_donation) — a path no CPU test takes."""
    import copy

    from cloudberry_tpu.exec import scanpipe as SP
    from cloudberry_tpu.exec import tiled
    from cloudberry_tpu.exec import tilepipe as TP
    from tools.tpch_queries import QUERIES

    s = copy.copy(sf1_lineitem)
    s.config = sf1_lineitem.config.with_overrides(
        **{"resource.query_mem_bytes": 256 << 20})
    texe = tiled.plan_tiled(_plan(s, QUERIES["q6"]), s)
    assert texe is not None and texe.lay.platform == "tpu"
    assert TP.step_donation(texe.lay.platform) == (4,)
    assert TP.effective_window(s.config, texe.lay.platform) == 4
    prelude_fn, step_fn, _ = texe._compile()
    resident = texe.lay.resident(texe)
    feed = tiled._tile_feed(texe.shape.stream, s, texe.tile_rows)
    try:
        tile, _ = next(iter(feed))
    finally:
        SP.close_feed(feed)
    assert len(next(iter(tile.values()))) == texe.tile_rows
    prelude = jax.eval_shape(lambda r: prelude_fn(r)[0], resident)
    args = _shapes((resident, prelude, tile, np.int32(0),
                    texe._init_acc()), one_chip)
    compiled = step_fn.lower(*args).compile()
    # the donated accumulator is aliased in place, not copied
    assert "input_output_alias" in compiled.as_text()


def test_redistribute_compiles_to_all_to_all_on_four_chips(topo, as_tpu,
                                                           monkeypatch):
    """One 4-device shard_map program with a hash redistribute. Q3 joins
    co-located tables and broadcasts customer, so its plan has none (and
    its compile is minutes): this is ``chip_smoke.py --chips 4``'s
    statement, the cheapest whose plan redistributes — lineitem
    (distributed by l_orderkey) grouped by l_suppkey. The compiler must
    place an all-to-all on the 2x2 mesh."""
    from chip_smoke import mesh_statements
    from cloudberry_tpu.exec import dist_executor as DX
    from cloudberry_tpu.parallel.mesh import SEG_AXIS

    s = _tpch_session(["lineitem"], n_segments=4)
    sql = mesh_statements(with_join=False)["by_supp"][0]
    assert "Motion redistribute" in s.explain(sql)
    plan = _plan(s, sql)
    mesh = Mesh(np.asarray(topo.devices[:4]), (SEG_AXIS,))
    monkeypatch.setattr(DX, "segment_mesh", lambda n, ids=None: mesh)
    fn = DX.compile_distributed(plan, s)
    inputs, in_specs = DX.prepare_dist_inputs(plan, s)
    shapes = jax.tree_util.tree_map(
        lambda x, spec: jax.ShapeDtypeStruct(
            np.shape(x), x.dtype, sharding=NamedSharding(mesh, spec)),
        inputs, in_specs)
    compiled = fn.lower(shapes).compile()
    assert "all-to-all" in compiled.as_text()


@pytest.mark.slow
def test_q3_compiles_for_tpu(one_chip):
    """The join program (build_sort + searchsorted + group sort + top-N:
    5 sorts), at SF0.01 shapes. Slow tier: 342 s in the PR 22 sandbox
    (1009 s at SF1 shapes)."""
    s = _tpch_session(["lineitem", "orders", "customer"])
    _compile_one_shot(s, "q3", one_chip)


# ------------------------------------------- the three kernel shapes, as XLA
# The shapes three Pallas kernels were written for and the TPU compiler
# refused (docs/DESIGN.md "Known limitations" has the three refusals): what
# is true of them on the path that runs is that the XLA formulations
# compile. One-word sorts and 2^16 rows: a sort's compile time grows with
# the words its comparator reads (PERF.md section 6, PR 28).

_N = 1 << 16


def _mid_cardinality_agg(sh):
    """K.group_aggregate at 2^16 groups: far beyond a dense cell domain."""
    from cloudberry_tpu.exec import kernels as K

    specs = [K.AggSpec("sum", "s"), K.AggSpec("count", "c"),
             K.AggSpec("avg", "a")]

    def f(k, v, sel):
        return K.group_aggregate({"k": k}, {"s": v, "c": None, "a": v},
                                 specs, sel, _N, pack_bits=32)
    return jax.jit(f).lower(sh((_N,), jnp.int64), sh((_N,), jnp.int64),
                            sh((_N,), jnp.bool_))


def _small_build_probe_join(sh):
    """K.join_lookup + gather_payload against a 1,024-row unique build,
    an int64 payload gathered to every probe row."""
    from cloudberry_tpu.exec import kernels as K

    def f(bk, bsel, pk, psel, pay):
        idx, matched, has_dup = K.join_lookup([bk], bsel, [pk], psel,
                                              bits=32)
        return K.gather_payload({"p": pay}, idx, matched), matched, has_dup
    return jax.jit(f).lower(sh((1024,), jnp.int64), sh((1024,), jnp.bool_),
                            sh((_N,), jnp.int64), sh((_N,), jnp.bool_),
                            sh((1024,), jnp.int64))


def _dense_agg_q1(sh):
    """Q1's aggregate: 6 cells (returnflag x linestatus), four exact
    int64 money sums, three averages and a count, chip strategy."""
    from cloudberry_tpu.exec import kernels as K

    sums, avgs = ["s0", "s1", "s2", "s3"], ["a0", "a1", "a2"]
    specs = [K.AggSpec("sum", n) for n in sums] \
        + [K.AggSpec("avg", n) for n in avgs] + [K.AggSpec("count", "c")]

    def f(gid, v, sel):
        vals = {n: v for n in sums + avgs}
        return K.group_aggregate_dense(gid, 6, {**vals, "c": None}, specs,
                                       sel, strategy="reduce")
    return jax.jit(f).lower(sh((_N,), jnp.int32), sh((_N,), jnp.int64),
                            sh((_N,), jnp.bool_))


def _sparse_compaction(sh, n, cap):
    from cloudberry_tpu.exec import kernels as K

    def f(key, mode, sel):
        return K.compact_sparse({"k": key, "m": mode}, sel, cap)
    return jax.jit(f).lower(sh((n,), jnp.int64), sh((n,), jnp.int32),
                            sh((n,), jnp.bool_))


def _sparse_compaction_q12(sh):
    """K.compact_sparse at the width SF1's Q12 runs it: the
    lines its filter keeps, of lineitem's 6,029,312 rows of capacity, to
    the 262,144 the planner stamps: the mask packed 32 rows a word, each
    of the 188,416 words' index scattered to its first slot, a running
    maximum over the 262,144 slots, five popcount halvings inside the
    word found, two columns gathered."""
    return _sparse_compaction(sh, 6_029_312, 262_144)


def _sparse_compaction_q3(sh):
    """K.compact_sparse at the width SF1's Q3 runs its lineitem ⋈ orders
    match compaction: 6,029,312 rows to the 1,048,576 the planner
    stamps, two columns gathered."""
    return _sparse_compaction(sh, 6_029_312, 1_048_576)


def _direct_lookup_q3(sh):
    """K.join_lookup_direct at the width SF1's Q3 runs it (ISSUE 37):
    the 524,288 orders its customer join keeps scattered into a table of
    o_orderkey's 1,500,000 values, lineitem's 6,029,312 probe rows read
    from it, one int64 payload gathered: no sort, no search."""
    from cloudberry_tpu.exec import kernels as K

    nb, n = 524_288, 6_029_312

    def f(bk, bsel, pk, psel, pay):
        idx, matched, has_dup, past = K.join_lookup_direct(
            [bk], bsel, [pk], psel, 1_500_000)
        return K.gather_payload({"p": pay}, idx, matched), has_dup, past
    return jax.jit(f).lower(sh((nb,), jnp.int64), sh((nb,), jnp.bool_),
                            sh((n,), jnp.int64), sh((n,), jnp.bool_),
                            sh((nb,), jnp.int64))


def _direct_agg_q18(sh):
    """K.group_aggregate_direct at the width SF1's Q18 runs it:
    lineitem's 6,029,312 rows summed into a table of l_orderkey's
    1,500,000 values, ``sum(l_quantity)`` in two 9-bit words by its
    proven 13 bits, a count, an average over 64 bits: no sort."""
    from cloudberry_tpu.exec import kernels as K

    n = 6_029_312
    specs = [K.AggSpec("sum", "s"), K.AggSpec("count", "c"),
             K.AggSpec("avg", "a")]

    def f(k, v, sel):
        return K.group_aggregate_direct(
            {"k": k}, {"s": v, "c": None, "a": v}, specs, sel,
            ((1, 1_500_000),), 1_507_328, value_bits={"s": (13, False)})
    return jax.jit(f).lower(sh((n,), jnp.int64), sh((n,), jnp.int64),
                            sh((n,), jnp.bool_))


@pytest.mark.parametrize("lower", [_mid_cardinality_agg,
                                   _small_build_probe_join, _dense_agg_q1,
                                   _sparse_compaction_q12,
                                   _direct_lookup_q3, _direct_agg_q18,
                                   _sparse_compaction_q3],
                         ids=["group_aggregate_2e16", "join_lookup_1024",
                              "dense_agg_q1", "compact_sparse_q12",
                              "join_lookup_direct_q3", "direct_agg_q18",
                              "compact_sparse_q3"])
def test_xla_formulations_of_the_kernel_shapes_compile_for_tpu(one_chip,
                                                               lower):
    def sh(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = lower(sh).compile()
    assert "tpu_custom_call" not in compiled.as_text()
