"""Sorts, prefix sums and bucket moves in the formulation the TPU compiler
takes quickly (kernels.py "sorts", PR 28): each against the plain
formulation it replaced, and a census of what a distributed statement's
module carries.

The TPU compiler's time for one sort grows steeply with the words its
comparator reads (seven key words: 172 s; two: 19 s; one: 4 s, at 1.5M
rows), a stable sort pays for a hidden position key, a payload operand
~15 s a word, an int64 cumsum 12 s and a 1.5M-row scatter 33 s (sandbox,
PR 28, v5e:2x2 ahead of time). Four segments' Q3 compiled for ~300 s on
the chip's host and the benchmark's run limit is 360 s.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloudberry_tpu.exec import kernels as K

RNG = np.random.default_rng(28)

Q15V = ("select l_suppkey as supplier_no, "
        "sum(l_extendedprice * (1 - l_discount)) as total_revenue "
        "from lineitem where l_shipdate >= date '1996-01-01' "
        "and l_shipdate < date '1996-04-01' "
        "group by l_suppkey order by supplier_no")


def _statement(name: str) -> str:
    from tools.tpch_queries import QUERIES

    return Q15V if name == "q15v" else QUERIES[name]


def _lexsort(keys, sel, desc):
    """What ``sort_indices`` was: selected rows first, by the keys, ties
    in position order."""
    cols = [~K.sort_key_u64(k) if d else K.sort_key_u64(k)
            for k, d in zip(keys, desc)]
    return jnp.lexsort(tuple(reversed(cols)) + (~sel,))


def _key(kind: int, n: int):
    i64 = np.iinfo(np.int64)
    return [
        lambda: jnp.asarray(RNG.integers(-3, 3, n), dtype=jnp.int64),
        lambda: jnp.asarray(RNG.integers(-3, 3, n), dtype=jnp.int32),
        lambda: jnp.asarray(RNG.choice([i64.max, i64.min, 0], n),
                            dtype=jnp.int64),
        lambda: jnp.asarray(RNG.choice([-1.5, 0.0, 2.5, np.inf], n),
                            dtype=jnp.float64),
        lambda: jnp.asarray(RNG.choice([-1.5, 0.0, 2.5], n),
                            dtype=jnp.float32),
        lambda: jnp.asarray(RNG.integers(0, 2, n).astype(bool)),
    ][kind]()


@pytest.mark.parametrize("trial", range(6))
def test_sort_indices_is_the_lexsort_on_the_selected_rows(trial):
    """Keys of every width, the extremes of int64 among them (an
    unselected row carries the largest word in every key: a selected row
    that does too must still come first), ascending and descending."""
    for _ in range(25):
        n = int(RNG.integers(1, 200))
        nk = int(RNG.integers(1, 4))
        keys = [_key((trial + j) % 6, n) for j in range(nk)]
        sel = jnp.asarray(RNG.random(n) < 0.7)
        desc = [bool(RNG.integers(0, 2)) for _ in range(nk)]
        want = np.asarray(_lexsort(keys, sel, desc))
        got = np.asarray(K.sort_indices(keys, sel, desc))
        n_sel = int(np.asarray(sel).sum())
        assert (got[:n_sel] == want[:n_sel]).all()
        # the others follow in position order
        assert (got[n_sel:] == np.sort(want[n_sel:])).all()


@pytest.mark.parametrize("n", [1, 2, 127, 128, 129, 5000, 70001])
def test_argsorts_of_one_word(n):
    flag = jnp.asarray(RNG.random(n) < 0.5)
    assert (np.asarray(K.flagged_first(flag))
            == np.asarray(jnp.argsort(~flag, stable=True))).all()
    bucket = jnp.asarray(RNG.integers(0, 5, n), dtype=jnp.int32)
    assert (np.asarray(K.bucket_argsort(bucket, 4))
            == np.asarray(jnp.argsort(bucket, stable=True))).all()
    perm = jnp.asarray(RNG.permutation(n))
    assert (np.asarray(K.inverse_permutation(perm))
            == np.asarray(jnp.argsort(perm))).all()
    key = jnp.asarray(RNG.integers(0, 7, n), dtype=jnp.uint64)
    assert (np.asarray(K.stable_argsort(key))
            == np.asarray(jnp.argsort(key, stable=True))).all()


@pytest.mark.parametrize("n", [1, 127, 128, 129, 16384, 70001])
@pytest.mark.parametrize("dtype", ["int64", "int32"])
def test_prefix_sum_is_cumsum(n, dtype):
    lo, hi = (-2**62, 2**62) if dtype == "int64" else (-2**30, 2**30)
    x = RNG.integers(lo, hi, n).astype(dtype)    # sums wrap, as cumsum's
    with np.errstate(over="ignore"):
        want = np.cumsum(x, dtype=dtype)
    got = np.asarray(jax.jit(K.prefix_sum)(jnp.asarray(x)))
    assert got.dtype == want.dtype and (got == want).all()


def test_prefix_sum_leaves_floats_to_cumsum():
    x = jnp.asarray(RNG.random(1000))
    assert (np.asarray(K.prefix_sum(x)) == np.asarray(jnp.cumsum(x))).all()


@pytest.mark.parametrize("cap", [4, 64])
def test_bucket_slots_fill_what_the_scatter_filled(cap):
    """Slot (b, r) holds bucket b's r-th row in position order; rows past
    ``cap`` and the dropped bucket's rows find no slot."""
    n, n_buckets = 300, 4
    bucket = RNG.integers(0, n_buckets + 1, n).astype(np.int32)
    rows = np.arange(1, n + 1, dtype=np.int64)
    counts = np.bincount(bucket, minlength=n_buckets + 1)[:n_buckets]
    src, filled = K.bucket_slots(
        K.bucket_argsort(jnp.asarray(bucket), n_buckets),
        jnp.asarray(counts.astype(np.int32)), cap)
    got = np.where(np.asarray(filled), rows[np.asarray(src)], 0)
    want = np.zeros(n_buckets * cap, dtype=np.int64)
    for b in range(n_buckets):
        mine = rows[bucket == b][:cap]
        want[b * cap:b * cap + len(mine)] = mine
    assert (got == want).all()


@pytest.mark.parametrize("pack_bits", [32, 64])
def test_a_packed_grouping_sort_groups_as_the_tuple_sort(pack_bits):
    n = 4000
    keys = {"a": jnp.asarray(RNG.integers(-50, 50, n), dtype=jnp.int64),
            "b": jnp.asarray(RNG.integers(0, 40, n), dtype=jnp.int32)}
    vals = {"s": jnp.asarray(RNG.integers(-10**12, 10**12, n))}
    sel = jnp.asarray(RNG.random(n) < 0.6)
    specs = [K.AggSpec("sum", "s")]
    want = K.group_aggregate(keys, vals, specs, sel, n)
    got = K.group_aggregate(keys, vals, specs, sel, n, pack_bits=pack_bits)
    for w, g in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        assert (np.asarray(w) == np.asarray(g)).all()


def test_a_packed_ordering_sort_orders_as_the_tuple_sort():
    """Descending keys count down from their range's top inside the
    packed word."""
    n = 3000
    keys = [jnp.asarray(RNG.integers(-20, 20, n), dtype=jnp.int64),
            jnp.asarray(RNG.integers(0, 9, n), dtype=jnp.int32),
            jnp.asarray(RNG.integers(-5, 5, n), dtype=jnp.int64)]
    sel = jnp.asarray(RNG.random(n) < 0.6)
    n_sel = int(np.asarray(sel).sum())
    for desc in ([False] * 3, [True, False, True], [True] * 3):
        packed = K.pack_keys(keys, sel, descending=desc)
        want = np.asarray(_lexsort(keys, sel, desc))[:n_sel]
        for word in (packed, K.downcast32(packed)):
            got = np.asarray(K.sort_indices([word], sel))[:n_sel]
            assert (got == want).all(), desc


def _tpch(n_segments: int):
    import cloudberry_tpu as cb
    from cloudberry_tpu.config import Config
    from tools.tpchgen import load_tpch

    s = cb.Session(Config(n_segments=n_segments))
    load_tpch(s, sf=0.01, seed=7, tables=["lineitem", "orders", "customer"])
    return s


def test_sort_keys_are_proven_packable_from_statistics():
    """Q3 groups by (l_orderkey, o_orderdate, o_shippriority) and the view
    by l_suppkey: plain columns with min/max statistics, so the plan
    carries the proof, on both stages of a two-stage aggregate; a
    computed key carries none."""
    from cloudberry_tpu.exec.executor import all_nodes
    from cloudberry_tpu.plan import nodes as N
    from cloudberry_tpu.plan.planner import plan_statement
    from cloudberry_tpu.sql.parser import parse_sql
    s = _tpch(4)

    def aggs(sql):
        plan = plan_statement(parse_sql(sql), s, {}).plan
        return [n for n in all_nodes(plan)
                if isinstance(n, N.PAgg) and n.group_keys]

    for name in ("q3", "q15v"):
        found = aggs(_statement(name))
        assert found and all(a.pack_bits == 32 for a in found), \
            [(a.title(), a.pack_bits) for a in found]
    assert {a.mode for a in aggs(_statement("q15v"))} == {"partial",
                                                          "final"}
    computed = aggs("select l_suppkey + 1 as k, count(*) as n "
                    "from lineitem group by l_suppkey + 1")
    assert computed and all(a.pack_bits == 0 for a in computed)

    def sorts(sql):
        plan = plan_statement(parse_sql(sql), s, {}).plan
        return [n.pack_bits for n in all_nodes(plan)
                if isinstance(n, N.PSort)]

    # the view orders by its group key; Q3 by a sum, which has no
    # statistics, on both sides of its top-N gather
    assert sorts(_statement("q15v")) == [32]
    assert sorts(_statement("q3")) == [0, 0]
    # a string sorts by collation rank, not by the code the statistics
    # are of
    assert sorts("select c_mktsegment from customer "
                 "order by c_mktsegment") == [0]


@pytest.mark.parametrize("stmt", ["q15v", "q3"])
def test_a_distributed_module_carries_no_slow_formulation(stmt):
    """What the four-segment programs hand the compiler: every sort
    unstable, all of its operands keys, at most four of them (Q3's ORDER
    BY revenue desc, o_orderdate: two words, one, and the position); no
    cumsum over rows as a reduce_window; no scatter of a shard's rows."""
    from cloudberry_tpu.exec import dist_executor as DX
    texts = []
    compile_distributed = DX.compile_distributed

    def recording(*a, **kw):
        fn = compile_distributed(*a, **kw)

        @functools.wraps(fn)    # what a launch counts rides on the program
        def call(inputs):
            texts.append(fn.lower(inputs).as_text())
            return fn(inputs)
        return call

    DX.compile_distributed = recording
    try:
        assert _tpch(4).sql(_statement(stmt)).num_rows() > 0
    finally:
        DX.compile_distributed = compile_distributed
    assert texts
    for text in texts:
        sorts = re.findall(
            r'"stablehlo\.sort"\(([^)]*)\).*?is_stable = (\w+).*?'
            r'\) : \(([^)]*)\) ->', text, re.S)
        assert sorts
        for operands, stable, types in sorts:
            assert stable == "false", types
            assert len(operands.split(",")) <= 4, types
            assert "i1>" not in types and "xi64>" not in types, types
        # (the prefix sum over a motion's few buckets stays a cumsum)
        windows = re.findall(r"window_dimensions = array<i64: (\d+)>", text)
        assert all(int(w) <= 64 for w in windows), windows
        # a scatter sets one flag, counts rows into a motion's few
        # buckets, or writes row NUMBERS into a lookup's direct-address
        # table (one int32 word a row, 1-D: 2.3 s of compile at Q3's SF1
        # widths, sandbox AOT, PR 37); none moves rows
        for m in re.finditer(r'"stablehlo\.scatter"', text):
            types = re.search(r"\}\) : \(([^)]*)\) ->",
                              text[m.start():m.start() + 4000]).group(1)
            operand, _, updates = [t.strip() for t in types.split(", ")]
            cells = int(np.prod([int(d) for d in re.findall(
                r"(\d+)x", operand)] or [1]))
            row_numbers = re.fullmatch(r"tensor<\d+xi32>", operand) \
                and re.fullmatch(r"tensor<\d+xi32>", updates)
            assert updates.startswith("tensor<i") or cells <= 64 \
                or row_numbers, types
