import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from cloudberry_tpu.exec import kernels as K


def _sel(n, cap):
    s = np.zeros(cap, dtype=bool)
    s[:n] = True
    return jnp.asarray(s)


def test_sort_indices_orders_and_pushes_invalid_last():
    cap = 8
    k = jnp.asarray(np.array([5, 1, 3, 2, 9, 0, 0, 0], dtype=np.int64))
    sel = _sel(5, cap)
    perm = K.sort_indices([k], sel)
    got = np.asarray(k[perm][:5])
    np.testing.assert_array_equal(got, [1, 2, 3, 5, 9])
    assert np.asarray(sel[perm])[5:].sum() == 0


def test_sort_descending_and_secondary():
    a = jnp.asarray(np.array([1, 2, 1, 2, 1], dtype=np.int64))
    b = jnp.asarray(np.array([10.0, 20.0, 30.0, 5.0, 20.0]))
    sel = jnp.ones(5, dtype=bool)
    perm = K.sort_indices([a, b], sel, descending=[False, True])
    rows = list(zip(np.asarray(a[perm]).tolist(), np.asarray(b[perm]).tolist()))
    assert rows == [(1, 30.0), (1, 20.0), (1, 10.0), (2, 20.0), (2, 5.0)]


def test_sort_negative_floats():
    v = jnp.asarray(np.array([0.5, -1.5, -0.25, 2.0, -1.5]))
    perm = K.sort_indices([v], jnp.ones(5, dtype=bool))
    got = np.asarray(v[perm])
    np.testing.assert_array_equal(got, np.sort(np.asarray(v)))


@pytest.mark.parametrize("jit", [False, True])
def test_group_aggregate_vs_pandas(jit):
    rng = np.random.default_rng(0)
    n, cap = 900, 1024
    k1 = rng.integers(0, 7, n).astype(np.int64)
    k2 = rng.integers(0, 3, n).astype(np.int32)
    v = rng.normal(size=n)
    df = pd.DataFrame({"k1": k1, "k2": k2, "v": v})
    expect = (
        df.groupby(["k1", "k2"])
        .agg(s=("v", "sum"), c=("v", "size"), mn=("v", "min"), a=("v", "mean"))
        .reset_index()
        .sort_values(["k1", "k2"])
    )

    key_cols = {
        "k1": jnp.asarray(np.pad(k1, (0, cap - n))),
        "k2": jnp.asarray(np.pad(k2, (0, cap - n))),
    }
    vals = jnp.asarray(np.pad(v, (0, cap - n)))
    sel = _sel(n, cap)
    aggs = [K.AggSpec("sum", "s"), K.AggSpec("count", "c"),
            K.AggSpec("min", "mn"), K.AggSpec("avg", "a")]
    agg_values = {"s": vals, "c": None, "mn": vals, "a": vals}

    fn = lambda kc, av, s: K.group_aggregate(kc, av, aggs, s, 64)
    if jit:
        fn = jax.jit(fn)
    out_keys, out_aggs, out_sel, n_groups = fn(key_cols, agg_values, sel)
    assert int(n_groups) == len(expect)

    m = np.asarray(out_sel)
    got = pd.DataFrame({
        "k1": np.asarray(out_keys["k1"])[m],
        "k2": np.asarray(out_keys["k2"])[m],
        "s": np.asarray(out_aggs["s"])[m],
        "c": np.asarray(out_aggs["c"])[m],
        "mn": np.asarray(out_aggs["mn"])[m],
        "a": np.asarray(out_aggs["a"])[m],
    })
    assert len(got) == len(expect)
    np.testing.assert_array_equal(got["k1"], expect["k1"].to_numpy())
    np.testing.assert_array_equal(got["k2"], expect["k2"].to_numpy())
    np.testing.assert_allclose(got["s"], expect["s"].to_numpy(), rtol=1e-12)
    np.testing.assert_array_equal(got["c"], expect["c"].to_numpy())
    np.testing.assert_allclose(got["mn"], expect["mn"].to_numpy(), rtol=1e-12)
    np.testing.assert_allclose(got["a"], expect["a"].to_numpy(), rtol=1e-12)


def _assert_group_aggregate_exact(keys, v, sel, cap, pack_bits=0):
    """K.group_aggregate against numpy over the selected rows: keys in
    ascending order, int64 sums and counts exact, avg within an ulp,
    and the true group count."""
    specs = [K.AggSpec("sum", "s"), K.AggSpec("count", "c"),
             K.AggSpec("avg", "a")]
    av = {"s": jnp.asarray(v), "c": None, "a": jnp.asarray(v)}
    ok, oa, osel, ng = K.group_aggregate(
        {"k": jnp.asarray(keys)}, av, specs, jnp.asarray(sel), cap,
        pack_bits=pack_bits)
    uk, inv = np.unique(keys[sel], return_inverse=True)
    sums = np.zeros(len(uk), np.int64)
    np.add.at(sums, inv, v[sel])
    counts = np.bincount(inv, minlength=len(uk))
    m = np.asarray(osel)
    assert int(ng) == len(uk) == int(m.sum())
    assert m[:len(uk)].all()      # groups sit at the front, in key order
    np.testing.assert_array_equal(np.asarray(ok["k"])[m], uk)
    got_s = np.asarray(oa["s"])[m]
    assert got_s.dtype == np.int64
    np.testing.assert_array_equal(got_s, sums)
    np.testing.assert_array_equal(np.asarray(oa["c"])[m], counts)
    np.testing.assert_allclose(np.asarray(oa["a"])[m], sums / counts,
                               rtol=1e-15)
    return int(ng)


def _boundary_case(shape):
    """Shapes that stress group boundaries, all (1536 rows, capacity
    512): the sorted-segment shapes of the kernel this path outlived."""
    rng = np.random.default_rng(6)
    n, cap = 1536, 512
    if shape == "groups_equal_capacity":
        keys = np.concatenate([np.repeat(np.arange(cap, dtype=np.int64), 2),
                               np.zeros(n - 2 * cap, np.int64)])
        sel = np.arange(n) < 2 * cap
        expect = cap
    elif shape == "one_group":
        keys, sel, expect = np.zeros(n, np.int64), np.ones(n, bool), 1
    elif shape == "all_filtered":
        keys = rng.integers(0, 50, n).astype(np.int64)
        sel, expect = np.zeros(n, bool), 0
    else:  # one hot group between smaller ones
        keys = np.concatenate([rng.integers(0, 40, 300), np.full(900, 40),
                               rng.integers(41, 80, 336)]).astype(np.int64)
        rng.shuffle(keys)
        sel, expect = rng.random(n) > 0.2, None
    return keys, rng.integers(-10**12, 10**12, n), sel, cap, expect


@pytest.mark.parametrize("shape", ["groups_equal_capacity", "one_group",
                                   "all_filtered", "hot_group"])
def test_group_aggregate_boundary_shapes(shape):
    keys, v, sel, cap, expect = _boundary_case(shape)
    ng = _assert_group_aggregate_exact(keys, v, sel, cap)
    assert expect is None or ng == expect


def test_group_aggregate_beyond_dense_domain():
    """2^16 groups — far beyond any dense cell domain — over 2^17 rows,
    values past 2^53 in sum: every group id appears, so the group count
    is exactly 2^16, and the packed one-word sort gives the same."""
    rng = np.random.default_rng(10)
    groups = 1 << 16
    keys0 = np.concatenate([np.arange(groups, dtype=np.int64),
                            rng.integers(0, groups, groups)])
    sel0 = np.ones(keys0.shape[0], bool)
    # filter only duplicate-half rows: every group keeps one survivor
    sel0[groups:] = rng.random(groups) > 0.25
    perm = rng.permutation(keys0.shape[0])
    keys, sel = keys0[perm], sel0[perm]
    v = rng.integers(-10**17, 10**17, keys.shape[0])
    for bits in (0, 32):
        assert _assert_group_aggregate_exact(keys, v, sel, 1 << 17,
                                             pack_bits=bits) == groups


def test_global_aggregate():
    v = jnp.asarray(np.array([1.0, 2.0, 3.0, 100.0]))
    sel = jnp.asarray(np.array([True, True, True, False]))
    out = K.global_aggregate(
        {"s": v, "c": None, "mx": v},
        [K.AggSpec("sum", "s"), K.AggSpec("count", "c"), K.AggSpec("max", "mx")],
        sel,
    )
    assert float(out["s"][0]) == 6.0
    assert int(out["c"][0]) == 3
    assert float(out["mx"][0]) == 3.0


def _lookup_case(name: str):
    """(build key columns, build selection, probe key columns, probe
    selection, a proven span of the build's packed keys) of one unique
    build: the shapes a lookup join meets. A NULL key is an unselected
    row (``Lowerer._join`` masks it out of either side)."""
    rng = np.random.default_rng(sum(map(ord, name)))
    if name == "pk_fk":
        bk = [np.array([10, 20, 30, 40, 0, 0, 0, 0], dtype=np.int64)]
        pk = [np.array([20, 20, 99, 40, 10, 30, 30, 7] + [0] * 8,
                       dtype=np.int64)]
        return bk, _sel(4, 8), pk, _sel(8, 16), 31
    if name == "two_keys":
        bk = [np.array([1, 1, 2, 2], dtype=np.int64),
              np.array([1, 2, 1, 2], dtype=np.int64)]
        pk = [np.array([1, 2, 2, 3], dtype=np.int64),
              np.array([2, 1, 9, 1], dtype=np.int64)]
        return bk, jnp.ones(4, dtype=bool), pk, jnp.ones(4, dtype=bool), 4
    if name == "empty_build":
        k = [np.array([1, 2, 3, 4], dtype=np.int64)]
        return k, jnp.zeros(4, dtype=bool), k, jnp.ones(4, dtype=bool), 4
    bk = [rng.permutation(1000)[:300].astype(np.int64) + 5000]
    bsel = rng.random(300) < 0.9
    pk = [rng.integers(4900, 6100, 2000).astype(np.int64)]
    psel = rng.random(2000) < 0.8
    span = int(bk[0].max() - bk[0].min() + 1)
    if name == "null_keys":
        # NULL keys hold 0 and leave the selection on both sides
        bk[0][:40], bsel[:40] = 0, False
        pk[0][:100], psel[:100] = 0, False
        span = int(bk[0][40:].max() - bk[0][40:].min() + 1)
    elif name == "span_ends":
        # the proven span's two ends are keys, and probes hit both
        lo, hi = bk[0].min(), bk[0].max()
        bsel[bk[0] == lo] = bsel[bk[0] == hi] = True
        pk[0][:4], psel[:4] = [lo, hi, lo - 1, hi + 1], True
    elif name == "selection_narrower_than_proof":
        # the build's rows at run time span less than the proof
        bsel &= (bk[0] > 5300) & (bk[0] < 5700)
        two = rng.integers(0, 40, 300).astype(np.int64)
        return [bk[0], two], jnp.asarray(bsel), \
            [pk[0], rng.integers(0, 40, 2000).astype(np.int64)], \
            jnp.asarray(psel), span * 40
    return bk, jnp.asarray(bsel), pk, jnp.asarray(psel), span


LOOKUP_CASES = ["pk_fk", "two_keys", "empty_build", "out_of_range",
                "null_keys", "span_ends", "selection_narrower_than_proof"]


@pytest.mark.parametrize("jit", [False, True])
@pytest.mark.parametrize("case", LOOKUP_CASES)
def test_join_lookup_forms_find_the_build_row_of_every_key(case, jit):
    """The sorted search (``join_lookup``) and the direct-address table
    (``join_lookup_direct``) against numpy, and against each other row
    for row: the same matches, the same build row for each."""
    bk, bsel, pk, psel, span = _lookup_case(case)
    bk = [jnp.asarray(k) for k in bk]
    pk = [jnp.asarray(k) for k in pk]
    search, direct = K.join_lookup, \
        lambda *a: K.join_lookup_direct(*a, span)
    if jit:
        search, direct = jax.jit(search), jax.jit(direct)
    row0, m0, dup0 = search(bk, bsel, pk, psel)
    row1, m1, dup1, past = direct(bk, bsel, pk, psel)
    m0, m1 = np.asarray(m0), np.asarray(m1)
    b = np.stack([np.asarray(k) for k in bk], 1)[np.asarray(bsel)]
    p = np.stack([np.asarray(k) for k in pk], 1)
    want = np.array([tuple(r) in set(map(tuple, b)) for r in p]) \
        & np.asarray(psel)
    np.testing.assert_array_equal(m0, want)
    np.testing.assert_array_equal(m1, want)
    np.testing.assert_array_equal(np.asarray(row1)[m1],
                                  np.asarray(row0)[m0])
    got = np.stack([np.asarray(k) for k in bk], 1)[np.asarray(row1)[m1]]
    np.testing.assert_array_equal(got, p[m1])
    assert not bool(dup0) and not bool(dup1) and not bool(past)
    if case == "empty_build":
        assert not m1.any()


@pytest.mark.parametrize("fault", ["duplicate_keys", "key_past_the_span"])
def test_join_lookup_direct_reports_what_its_table_cannot_hold(fault):
    """Two selected build rows with one key: the table keeps one of
    them and the other reads back another row (the sorted search's
    adjacent-equal test says the same). A key that packs past the span
    the planner proved: the guard fires."""
    bk = jnp.asarray(np.array([7, 3, 9, 3, 5], dtype=np.int64))
    bsel = jnp.asarray([True, True, True, fault == "duplicate_keys", True])
    pk = [jnp.asarray(np.arange(12, dtype=np.int64))]
    span = 7 if fault == "duplicate_keys" else 6    # keys 3..9: 7 values
    _, _, dup, past = K.join_lookup_direct([bk], bsel, pk,
                                           jnp.ones(12, bool), span)
    _, _, dup0 = K.join_lookup([bk], bsel, pk, jnp.ones(12, bool))
    assert bool(dup) == bool(dup0) == (fault == "duplicate_keys")
    assert bool(past) == (fault == "key_past_the_span")


def test_limit_mask():
    sel = jnp.asarray(np.array([True, False, True, True, True, False, True]))
    out = np.asarray(K.limit_mask(sel, 2, offset=1))
    np.testing.assert_array_equal(
        out, [False, False, True, True, False, False, False])


def test_compact():
    cols = {"x": jnp.asarray(np.array([9, 8, 7, 6], dtype=np.int64))}
    sel = jnp.asarray(np.array([False, True, False, True]))
    out, osel, n = K.compact(cols, sel, 2)
    assert np.asarray(osel).all()
    assert int(n) == 2
    np.testing.assert_array_equal(np.asarray(out["x"]), [8, 6])


def test_compact_overflow_reported():
    cols = {"x": jnp.asarray(np.arange(4, dtype=np.int64))}
    sel = jnp.ones(4, dtype=bool)
    _, _, n = K.compact(cols, sel, 2)
    assert int(n) == 4  # caller sees 4 > capacity 2 and errors


def _mask(n: int, k, seed: int) -> np.ndarray:
    """``k`` of ``n`` rows selected, at random places; or, where ``k`` is
    a tuple of (first, last) row ranges, those rows and no others."""
    sel = np.zeros(n, dtype=bool)
    if isinstance(k, tuple):
        for first, last in k:
            sel[first:last + 1] = True
        return sel
    sel[np.random.default_rng(seed).choice(n, k, replace=False)] = True
    return sel


@pytest.mark.parametrize("n,k,capacity", [
    (1000, 0, 8),          # nothing selected
    (1000, 1000, 1000),    # everything, at the input's own capacity
    (777, 64, 64),         # exactly at capacity
    (777, 65, 64),         # one over: reported, the first 64 kept
    (5000, 93, 256),       # sparse
    (130, 7, 16),          # one block of the prefix sum and a bit
    (128, 128, 16),        # far over
    pytest.param(160, ((32, 63), (70, 70)), 64, id="full-word"),
    pytest.param(300_000, ((5, 5), (9_000, 9_001), (299_990, 299_990)), 8,
                 id="long-empty-runs"),
    pytest.param(256, ((32, 63), (64, 80)), 40, id="capacity-inside-word"),
    pytest.param(3200, tuple((32 * w + w % 32,) * 2 for w in range(100)),
                 16, id="more-words-than-slots"),
    pytest.param(1001, ((0, 0), (995, 995), (1000, 1000)), 8,
                 id="n-not-a-multiple-of-32"),
    pytest.param(320, 40, 64, id="capacity-past-the-words"),
])
@pytest.mark.parametrize("jit", [False, True])
def test_compact_sparse_is_compact(jit, n, k, capacity):
    """The sparse form (a prefix sum, a scatter of the words' ranks and a
    running maximum) against ``K.compact`` (a sort of the whole input)
    and numpy: the selected rows in their order, the mask of the slots
    they fill, and the TRUE count, which the caller checks against the
    capacity."""
    sel = _mask(n, k, seed=n + k if isinstance(k, int) else n)
    picked = int(sel.sum())
    rng = np.random.default_rng(picked)
    cols = {"pos": jnp.arange(n, dtype=jnp.int64),
            "val": jnp.asarray(rng.integers(-99, 99, n).astype(np.int32)),
            "flag": jnp.asarray(rng.random(n) < 0.5)}
    fn = jax.jit(K.compact_sparse, static_argnums=2) if jit \
        else K.compact_sparse
    out, osel, count = fn(cols, jnp.asarray(sel), capacity)
    ref, rsel, rcount = K.compact(cols, jnp.asarray(sel), capacity)
    assert int(count) == int(rcount) == picked
    kept = min(picked, capacity)
    np.testing.assert_array_equal(np.asarray(osel),
                                  np.arange(capacity) < kept)
    np.testing.assert_array_equal(np.asarray(osel), np.asarray(rsel))
    want = np.flatnonzero(sel)[:kept]
    np.testing.assert_array_equal(np.asarray(out["pos"])[:kept], want)
    for name in cols:
        assert out[name].shape == (capacity,)
        assert out[name].dtype == cols[name].dtype
        np.testing.assert_array_equal(np.asarray(out[name])[:kept],
                                      np.asarray(ref[name])[:kept])
        np.testing.assert_array_equal(np.asarray(out[name])[:kept],
                                      np.asarray(cols[name])[want])


@pytest.mark.parametrize("n,capacity", [(6_029_312, 1_048_576),
                                        (1_507_328, 4_096)])
def test_compact_sparse_lowers_without_a_loop(n, capacity):
    """No search is left in the compaction: its lowering holds no
    ``while``, at the join cell's widest width and largevol's narrowest
    (traced from shapes, nothing runs)."""
    def f(key, mode, sel):
        return K.compact_sparse({"k": key, "m": mode}, sel, capacity)
    text = jax.jit(f).lower(
        jax.ShapeDtypeStruct((n,), jnp.int64),
        jax.ShapeDtypeStruct((n,), jnp.int32),
        jax.ShapeDtypeStruct((n,), jnp.bool_)).as_text()
    assert "stablehlo.scatter" in text
    assert "while" not in text


@pytest.mark.parametrize("ladder, pad", [("row_rung_up", 1 / 32),
                                         ("rung_up", 1.0)])
def test_row_rungs_at_both_ladders(ladder, pad):
    """A scan's ladder (64 rungs an octave) and the powers of two that
    capacities following from an estimate sit on (plan/joincap.py):
    never under the rows, padding under a rung's step, a rung is its own
    rung and so is twice a rung."""
    up = getattr(K, ladder)
    for n in (1, 7, 64, 65, 729, 1360, 59_877, 233_128, 5_998_031):
        r = up(n)
        assert n <= r <= max(n * (1 + pad) + 1, 8)
        assert up(r) == r
        assert up(2 * r) == 2 * r
    assert K.row_rung_up(5_998_031) == 6_029_312
    assert K.rung_up(233_128) == 262_144


def test_join_probe_sorted_is_the_lookups_search():
    """``join_lookup_sorted`` is ``join_probe_sorted`` and one gather of
    the build's order: a caller that compacts the matched rows in
    between takes the build rows for those alone."""
    rng = np.random.default_rng(3)
    bk = rng.permutation(500)[:200].astype(np.int64)
    pk = rng.integers(0, 500, 900).astype(np.int64)
    bsel = jnp.asarray(rng.random(200) < 0.9)
    psel = jnp.asarray(rng.random(900) < 0.7)
    order, kb_sorted, ranges = K.build_sort([jnp.asarray(bk)], bsel)
    row, matched, _ = K.join_lookup_sorted(order, kb_sorted, ranges,
                                           [jnp.asarray(pk)], psel)
    pos, matched2 = K.join_probe_sorted(kb_sorted, ranges,
                                        [jnp.asarray(pk)], psel)
    np.testing.assert_array_equal(np.asarray(matched), np.asarray(matched2))
    np.testing.assert_array_equal(np.asarray(row),
                                  np.asarray(order)[np.asarray(pos)])
    hit = np.asarray(matched)
    np.testing.assert_array_equal(bk[np.asarray(row)[hit]], pk[hit])
    assert hit.sum() == (np.isin(pk, bk[np.asarray(bsel)])
                         & np.asarray(psel)).sum()


def test_group_overflow_reported():
    cols = {"k": jnp.asarray(np.arange(8, dtype=np.int64))}
    sel = jnp.ones(8, dtype=bool)
    *_, n_groups = K.group_aggregate(cols, {"c": None}, [K.AggSpec("count", "c")], sel, 4)
    assert int(n_groups) == 8  # caller sees 8 > capacity 4 and errors


def test_decimal_int_ingest():
    import pandas as pd
    from cloudberry_tpu.columnar import ColumnBatch
    from cloudberry_tpu.types import Schema, DECIMAL
    b = ColumnBatch.from_arrays({"p": np.array([100, 200], dtype=np.int64)},
                                Schema.of(p=DECIMAL(2)))
    np.testing.assert_array_equal(np.asarray(b.columns["p"]), [10000, 20000])
    assert b.to_pandas()["p"].tolist() == [100.0, 200.0]


def test_join_lookup_32bit_matches_64bit():
    """Stats-proven narrow packing (kernels.downcast32) must be
    bit-identical to the u64 path, including sentinel (no-match) rows."""
    rng = np.random.default_rng(2)
    bk = jnp.asarray(rng.permutation(1000).astype(np.int64))
    bs = jnp.asarray(rng.random(1000) < 0.9)
    pk = jnp.asarray(rng.integers(-50, 1100, 5000).astype(np.int64))
    ps = jnp.asarray(rng.random(5000) < 0.95)
    i64, m64, d64 = K.join_lookup([bk], bs, [pk], ps, bits=64)
    i32, m32, d32 = K.join_lookup([bk], bs, [pk], ps, bits=32)
    np.testing.assert_array_equal(np.asarray(m64), np.asarray(m32))
    np.testing.assert_array_equal(np.asarray(i64)[np.asarray(m64)],
                                  np.asarray(i32)[np.asarray(m32)])
    assert bool(d64) == bool(d32)


def test_join_expand_32bit_matches_64bit():
    rng = np.random.default_rng(3)
    bk = jnp.asarray(rng.integers(0, 200, 1000).astype(np.int64))
    bs = jnp.ones(1000, dtype=bool)
    pk = jnp.asarray(rng.integers(0, 250, 2000).astype(np.int64))
    ps = jnp.asarray(rng.random(2000) < 0.9)
    cap = 16384
    r64 = K.join_expand([bk], bs, [pk], ps, cap, bits=64)
    r32 = K.join_expand([bk], bs, [pk], ps, cap, bits=32)
    for a, b in zip(r64, r32):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pack_bits_annotation_tpch():
    """TPC-H integer-key joins (orderkey/custkey class) must be proven
    32-bit packable from table stats; the plan carries the proof."""
    import cloudberry_tpu as cb
    from cloudberry_tpu.config import get_config
    from cloudberry_tpu.exec.executor import all_nodes
    from cloudberry_tpu.plan import nodes as N
    from cloudberry_tpu.plan.planner import plan_statement
    from cloudberry_tpu.sql.parser import parse_sql
    from tools.tpch_queries import QUERIES
    from tools.tpchgen import load_tpch

    s = cb.Session(get_config().with_overrides(n_segments=1))
    load_tpch(s, sf=0.01, seed=7)
    plan = plan_statement(parse_sql(QUERIES["q3"]), s, {}).plan
    joins = [n for n in all_nodes(plan) if isinstance(n, N.PJoin)]
    assert joins and all(j.pack_bits == 32 for j in joins), \
        [(j.title(), j.pack_bits) for j in joins]


def test_pack_bits_rejects_float_keys():
    """FLOAT keys pack by IEEE bit pattern (sort_key_u64), where a tiny
    value span covers ~2^52 patterns — the 32-bit proof must refuse them
    (narrowing would alias distinct keys)."""
    import cloudberry_tpu as cb
    from cloudberry_tpu.config import get_config
    from cloudberry_tpu.exec.executor import all_nodes
    from cloudberry_tpu.plan import nodes as N
    from cloudberry_tpu.plan.planner import plan_statement
    from cloudberry_tpu.sql.parser import parse_sql

    s = cb.Session(get_config().with_overrides(n_segments=1))
    s.sql("CREATE TABLE fb (x DOUBLE, p BIGINT) DISTRIBUTED BY (p)")
    s.sql("CREATE TABLE fp (y DOUBLE, v BIGINT) DISTRIBUTED BY (v)")
    s.catalog.table("fb").set_data(
        {"x": np.array([1.5, 2.5, 3.5]), "p": np.arange(3)})
    s.catalog.table("fp").set_data(
        {"y": np.array([2.5, 3.5, 9.0, 1.5]), "v": np.arange(4)})
    plan = plan_statement(parse_sql(
        "SELECT sum(v) AS sv FROM fp JOIN fb ON fp.y = fb.x"), s, {}).plan
    joins = [n for n in all_nodes(plan) if isinstance(n, N.PJoin)]
    assert joins and all(j.pack_bits == 64 for j in joins)
    # and the join itself must still be correct
    assert s.sql("SELECT count(*) AS c FROM fp JOIN fb ON fp.y = fb.x"
                 ).to_pandas()["c"].tolist() == [3]


def test_sort_key_f64_two_word_path():
    """DOUBLE sort keys build their IEEE total-order u64 from two u32
    bitcast words (the TPU backend compiles no direct f64->u64 bitcast);
    the result must be bit-identical to the direct-view formulation and
    order exactly like SQL ascending floats."""
    import numpy as np

    from cloudberry_tpu.exec.kernels import sort_key_u64

    vals = np.array([0.0, -0.0, 1.5, -1.5, np.inf, -np.inf, 3.14e300,
                     -3.14e300, 5e-324, -5e-324, 123456.789],
                    dtype=np.float64)
    rng = np.random.default_rng(0)
    vals = np.concatenate([vals, rng.standard_normal(500) *
                           (10.0 ** rng.integers(-300, 300, 500)
                            .astype(np.float64))])
    got = np.asarray(jax.jit(sort_key_u64)(jnp.asarray(vals)))
    bits = vals.view(np.uint64)
    mask = np.where(bits >> 63 != 0, np.uint64(0xFFFFFFFFFFFFFFFF),
                    np.uint64(1) << 63)
    assert (got == (bits ^ mask)).all()
    assert (vals[np.argsort(vals, kind="stable")]
            == vals[np.argsort(got, kind="stable")]).all()


def test_double_order_by_end_to_end():
    """ORDER BY over a genuine DOUBLE column (the round-4 verdict's
    platform caveat: this must not depend on a CPU-only bitcast)."""
    import cloudberry_tpu as cb
    from cloudberry_tpu.config import Config

    s = cb.Session(Config(n_segments=8))
    s.sql("create table fd (k bigint, x double) distributed by (k)")
    s.sql("insert into fd values (1, 2.5), (2, -1.5), (3, 1e300), "
          "(4, -1e300), (5, 0.0), (6, 3.25), (7, null)")
    df = s.sql("select k from fd order by x").to_pandas()
    assert list(df["k"]) == [4, 2, 5, 1, 6, 3, 7]  # NULLs last


def test_join_expand_total_exact_past_2_16():
    """Regression: the pair-count cumsum and capacity comparison must run
    in int64 regardless of searchsorted's narrow index dtype — a fanout
    past 2^16 pairs must report its EXACT total (a wrapped count would
    defeat the overflow check itself)."""
    nb, np_ = 300, 300  # 90000 pairs > 2^16
    bk = [jnp.zeros(nb, dtype=jnp.int64)]
    pk = [jnp.zeros(np_, dtype=jnp.int64)]
    cap = 1 << 17
    pi, bi, osel, matched, total = K.join_expand(
        bk, jnp.ones(nb, dtype=bool), pk, jnp.ones(np_, dtype=bool), cap)
    assert total.dtype == jnp.int64
    assert int(total) == nb * np_
    assert int(np.asarray(osel).sum()) == nb * np_
    assert bool(np.asarray(matched).all())
    # each probe row pairs with every build row exactly once
    counts = np.bincount(np.asarray(pi)[np.asarray(osel)], minlength=np_)
    np.testing.assert_array_equal(counts, np.full(np_, nb))


@pytest.mark.parametrize("cap", [64, 890, 4096])
def test_join_expand_slots_equal_numpy_under_and_over_the_capacity(cap):
    """An expansion's pair buffer against a numpy expansion (ISSUE 34:
    Q13's join is the first the benchmark runs): every slot under the
    capacity names the probe row and the build row numpy gives it,
    whether the pairs fit (4096), nearly fit (890 for 899) or overflow
    many times over (64); the total stays the exact count, which is
    what the overflow check and the retry's size read."""
    rng = np.random.default_rng(34)
    bk = rng.integers(0, 40, 300).astype(np.int64)
    pk = rng.integers(0, 60, 200).astype(np.int64)
    psel = rng.random(200) < 0.8
    pi, bi, osel, matched, total = K.join_expand(
        [jnp.asarray(bk)], jnp.ones(300, dtype=bool), [jnp.asarray(pk)],
        jnp.asarray(psel), cap)
    order = np.argsort(bk, kind="stable")
    want = [(i, int(b)) for i in range(200) if psel[i]
            for b in order[bk[order] == pk[i]]]
    assert int(total) == len(want) == 899
    assert int(np.asarray(osel).sum()) == min(cap, len(want))
    got = list(zip(np.asarray(pi)[np.asarray(osel)].tolist(),
                   np.asarray(bi)[np.asarray(osel)].tolist()))
    assert got == want[:cap]
    np.testing.assert_array_equal(
        np.asarray(matched), psel & np.isin(pk, bk))


def _slot_map_case(name):
    """(build keys, probe keys, probe selection, capacity) of one shape
    the slot map has to get right; every build row is selected, so the
    host-built index (a selected prefix) fits each."""
    rng = np.random.default_rng(35)
    bk = rng.integers(10, 30, 120).astype(np.int64)
    pk = rng.integers(10, 30, 90).astype(np.int64)
    psel = rng.random(90) < 0.85
    total = int(sum((bk == k).sum() for k in pk[psel]))

    def hit(n):
        return rng.integers(10, 30, n).astype(np.int64)

    def miss(n):
        return rng.integers(40, 50, n).astype(np.int64)

    if name == "capacity-is-the-total":
        return bk, pk, psel, total
    if name == "capacity-one-under-the-total":
        return bk, pk, psel, total - 1
    if name == "leading-rows-without-a-match":
        return bk, np.concatenate([miss(40), hit(30)]), np.ones(70, bool), 400
    if name == "trailing-rows-without-a-match":
        return bk, np.concatenate([hit(30), miss(40)]), np.ones(70, bool), 400
    if name == "long-inner-runs-without-a-match":
        pk = np.concatenate([hit(3), miss(50), hit(2), miss(70), hit(4)])
        psel = np.ones(pk.shape[0], bool)
        psel[60:90] = False         # unselected rows inside a run
        return bk, pk, psel, 128
    if name == "no-probe-row-selected":
        return bk, pk, np.zeros(90, bool), 64
    if name == "one-probe-row-owns-every-slot":
        pk = miss(33)
        pk[17] = 7
        return np.full(150, 7, np.int64), pk, np.ones(33, bool), 150
    if name == "fan-out-past-2-16-clipped-at-the-capacity":
        # 300 x 300 pairs: every offset past the fourth row's saturates
        return (np.zeros(300, np.int64), np.zeros(300, np.int64),
                np.ones(300, bool), 1000)
    raise KeyError(name)


@pytest.mark.parametrize("presorted", [False, True],
                         ids=["in-program-sort", "host-built-index"])
@pytest.mark.parametrize("bits", [64, 32])
@pytest.mark.parametrize("case", [
    "capacity-is-the-total", "capacity-one-under-the-total",
    "leading-rows-without-a-match", "trailing-rows-without-a-match",
    "long-inner-runs-without-a-match", "no-probe-row-selected",
    "one-probe-row-owns-every-slot",
    "fan-out-past-2-16-clipped-at-the-capacity"])
def test_join_expand_slot_map_equals_numpy(case, bits, presorted):
    """The slot map of an expansion (ISSUE 35: one int32 word, the
    offsets clipped to the capacity): every selected slot names the
    probe row and the build row a numpy expansion gives it, the total
    is the exact int64 count and ``matched`` the per-probe any-match,
    wherever rows without pairs sit and wherever the capacity cuts."""
    from cloudberry_tpu.exec.joinindex import _np_index

    bk, pk, psel, cap = _slot_map_case(case)
    nb = bk.shape[0]
    bsel = jnp.ones(nb, dtype=bool)
    if presorted:
        jix = _np_index([bk], nb, nb, bits)
        ranges = [(jnp.asarray(jix["lo0"]), jnp.asarray(jix["span0"]))]
        got = K.join_expand_sorted(
            jnp.asarray(jix["order"]), jnp.asarray(jix["skeys"]), ranges,
            [jnp.asarray(pk)], jnp.asarray(psel), cap, bits=bits)
    else:
        got = K.join_expand([jnp.asarray(bk)], bsel, [jnp.asarray(pk)],
                            jnp.asarray(psel), cap, bits=bits)
    pi, bi, osel, matched, total = (np.asarray(a) for a in got)
    order = np.argsort(bk, kind="stable")
    want = [(i, int(b)) for i in range(pk.shape[0]) if psel[i]
            for b in order[bk[order] == pk[i]]]
    assert total.dtype == np.int64 and int(total) == len(want)
    assert pi.dtype == bi.dtype == np.int32
    n = min(cap, len(want))
    np.testing.assert_array_equal(osel, np.arange(cap) < n)
    assert list(zip(pi[:n].tolist(), bi[:n].tolist())) == want[:n]
    # what a masked slot holds is never read, but it is gathered with:
    assert pi.min() >= 0 and pi.max() < pk.shape[0]
    assert bi.min() >= 0 and bi.max() < nb
    np.testing.assert_array_equal(matched, psel & np.isin(pk, bk))


@pytest.mark.parametrize("cap,n_build", [(1 << 31, 1024), (1024, 1 << 31)])
def test_join_expand_refuses_positions_past_int32(cap, n_build):
    """The slot map's positions are int32 words: a pair buffer or a
    build of 2^31 rows is refused while the program is traced (abstract
    shapes: nothing is allocated), never run on a wrapped position."""
    u64 = jax.ShapeDtypeStruct((), jnp.uint64)
    with pytest.raises(ValueError, match="expansion join"):
        jax.eval_shape(
            lambda order, kb, lo, span, pk, ps: K.join_expand_sorted(
                order, kb, [(lo, span)], [pk], ps, cap),
            jax.ShapeDtypeStruct((n_build,), jnp.int32),
            jax.ShapeDtypeStruct((n_build,), jnp.uint64), u64, u64,
            jax.ShapeDtypeStruct((512,), jnp.int64),
            jax.ShapeDtypeStruct((512,), jnp.bool_))


def test_join_lookup_presorted_parity():
    """join_lookup fed a HOST-precomputed index (the join-index cache's
    numpy mirror) must be bit-identical to the in-program argsort path —
    order, matches, dup flag, at 64 and 32 bits."""
    from cloudberry_tpu.exec.joinindex import _np_index

    rng = np.random.default_rng(5)
    nb, np_ = 512, 1024
    bvals = rng.permutation(1 << 12)[:nb].astype(np.int64)
    pvals = rng.integers(0, 1 << 13, np_).astype(np.int64)
    n_build = 400  # tail rows unselected
    bsel = _sel(n_build, nb)
    psel = _sel(900, np_)
    for bits in (64, 32):
        idx0, m0, dup0 = K.join_lookup([jnp.asarray(bvals)], bsel,
                                       [jnp.asarray(pvals)], psel,
                                       bits=bits)
        jix = _np_index([bvals], n_build, nb, bits)
        ranges = [(jnp.asarray(jix["lo0"]), jnp.asarray(jix["span0"]))]
        idx1, m1, dup1 = K.join_lookup_sorted(
            jnp.asarray(jix["order"]), jnp.asarray(jix["skeys"]), ranges,
            [jnp.asarray(pvals)], psel, bits=bits)
        np.testing.assert_array_equal(np.asarray(m0), np.asarray(m1))
        np.testing.assert_array_equal(np.asarray(idx0)[np.asarray(m0)],
                                      np.asarray(idx1)[np.asarray(m1)])
        assert bool(dup0) == bool(dup1) == False  # noqa: E712


def test_join_expand_presorted_parity():
    """join_expand through a host-precomputed index: identical pair sets
    AND identical output order (stable ties mirror np argsort)."""
    from cloudberry_tpu.exec.joinindex import _np_index

    rng = np.random.default_rng(6)
    nb, np_ = 256, 512
    bvals = rng.integers(0, 64, nb).astype(np.int64)  # heavy dups
    pvals = rng.integers(0, 96, np_).astype(np.int64)
    n_build = 200
    bsel = _sel(n_build, nb)
    psel = _sel(480, np_)
    cap = 1 << 13
    r0 = K.join_expand([jnp.asarray(bvals)], bsel,
                       [jnp.asarray(pvals)], psel, cap)
    jix = _np_index([bvals], n_build, nb, 64)
    ranges = [(jnp.asarray(jix["lo0"]), jnp.asarray(jix["span0"]))]
    r1 = K.join_expand_sorted(jnp.asarray(jix["order"]),
                              jnp.asarray(jix["skeys"]), ranges,
                              [jnp.asarray(pvals)], psel, cap)
    for a, b in zip(r0, r1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_kernel_bench_grouped_agg_smoke():
    """The grouped-agg cardinality sweep of tools/kernel_bench.py runs on
    the CPU and emits one record per ladder point."""
    import json
    import os
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-m", "tools.kernel_bench", "grouped-agg",
         "--rows", "4096", "--ladder", "4,6", "--reps", "1"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    recs = [json.loads(ln) for ln in out.stdout.splitlines()
            if ln.startswith("{")]
    assert [(r["strategy"], r["groups"]) for r in recs] == \
        [("xla_sort", 16), ("xla_sort", 64)]
    assert all(r["rows"] == 4096 and r["mrows_per_s"] > 0 for r in recs)
