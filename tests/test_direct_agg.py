"""A grouped aggregate whose keys have a proven box no wider than its rows
sums into a direct-address table over the box (``PAgg.direct``,
``kernels.group_aggregate_direct``): each row's group is its slot, every
aggregate a scatter-add, no sort. The kernel answers as the sort path
does on the same rows, at the box's edges, with a negative least value,
sums near ±2^62, no rows, one group; a selected key past the box is an
error, never an answer. In the plan: Q18's ``GROUP BY l_orderkey`` and
Q13's ``GROUP BY c_custkey`` take it, the aggregates above them, Q3's and
Q1's do not, and the served answers equal the plain references."""

from __future__ import annotations

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import Config
from cloudberry_tpu.exec import executor as X
from cloudberry_tpu.exec import kernels as K
from cloudberry_tpu.plan import joincap
from cloudberry_tpu.plan import nodes as N
from cloudberry_tpu.plan.planner import plan_statement
from cloudberry_tpu.serve.client import Client
from cloudberry_tpu.serve.server import Server
from cloudberry_tpu.sql.parser import parse_sql

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import cell as C, compare, load       # noqa: E402

# ------------------------------------------------------------- the kernel

FUNCS = ("count", "count_nn", "sum", "avg")


def _rows(seed, n, box, values, n_sel=None):
    """``n`` rows of one int32 key inside ``box`` (its both edges among
    them), an int64 value drawn from ``values``, a bool for count_nn and
    an int32 key the first determines (carried)."""
    rng = np.random.default_rng(seed)
    (lo, span), = box
    k = rng.integers(lo, lo + span, n)
    k[:2] = lo, lo + span - 1
    v = rng.choice(np.asarray(values, np.int64), n)
    sel = np.zeros(n, bool)
    sel[:n if n_sel is None else n_sel] = True
    return k.astype(np.int32), v, rng.random(n) < 0.7, sel


def _groups(keys, aggs, sel) -> dict:
    """{key tuple: agg tuple} of the selected output rows."""
    keys = {n: np.asarray(c) for n, c in keys.items()}
    aggs = {n: np.asarray(c) for n, c in aggs.items()}
    out = {}
    for i in np.flatnonzero(np.asarray(sel)):
        out[tuple(int(c[i]) for c in keys.values())] = tuple(
            c[i].item() for c in aggs.values())
    return out


def _both(k, v, nn, sel, box, carried: bool):
    key_cols = {"k": jnp.asarray(k)}
    if carried:
        key_cols["c"] = jnp.asarray(k * 3 + 1)
    vals = {"n": None, "nn": jnp.asarray(nn), "s": jnp.asarray(v),
            "a": jnp.asarray(v)}
    specs = [K.AggSpec(f, o) for f, o in zip(FUNCS, ("n", "nn", "s", "a"))]
    cap = max(box[0][1], 8)
    cr = ("c",) if carried else ()
    sel = jnp.asarray(sel)
    dk, da, dsel, past = K.group_aggregate_direct(
        key_cols, vals, specs, sel, box, cap, carried=cr)
    sk, sa, ssel, _ = K.group_aggregate(key_cols, vals, specs, sel,
                                        len(k), carried=cr)
    return _groups(dk, da, dsel), _groups(sk, sa, ssel), bool(past)


CASES = {
    # every aggregate against the sort path, keys at both edges
    "small_values": (1, 4000, ((0, 97),), range(-50, 51), None, False),
    "a_carried_key": (2, 4000, ((10, 300),), range(0, 5001), None, True),
    "negative_least": (3, 3000, ((-1000, 513),), range(-9, 10), None, False),
    "sums_near_plus_2_62": (4, 64, ((5, 1),), [2 ** 62 // 64 - 1], None,
                            False),
    "sums_near_minus_2_62": (5, 64, ((5, 1),), [-(2 ** 62 // 64) + 1],
                             None, False),
    "mixed_signs_of_2_60": (6, 2048, ((0, 16),), [2 ** 60, -(2 ** 60) + 3,
                                                  7], None, False),
    "no_rows_selected": (7, 512, ((0, 64),), range(5), 0, False),
    "one_group_holds_every_row": (8, 4096, ((42, 1),), range(-3, 1000),
                                  None, False),
    "half_the_rows_selected": (9, 4096, ((0, 1024),), range(10 ** 6),
                               2048, True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_direct_table_answers_as_the_sort_does(case):
    seed, n, box, values, n_sel, carried = CASES[case]
    k, v, nn, sel = _rows(seed, n, box, values, n_sel)
    direct, by_sort, past = _both(k, v, nn, sel, box, carried)
    assert not past
    assert direct == by_sort
    assert len(direct) == len(np.unique(k[sel]))
    if case.startswith("sums_near"):
        # the sums themselves reach ±2^62 (exact: no f64 rounding)
        assert max(abs(g[2]) for g in direct.values()) > 2 ** 61


WIDTHS = {
    # (values, the proven width): l_quantity's cents, unsigned 13 bits
    "unsigned_13_bits": ((100, 5001), (13, False)),
    # two's complement: -4,096..4,095 in 13 bits, least -4,096 added back
    "signed_13_bits": ((-4096, 4096), (13, True)),
    "a_flag": ((0, 2), (1, False)),
}


@pytest.mark.parametrize("case", sorted(WIDTHS))
def test_a_proven_width_sums_fewer_words_exactly(case):
    """``value_bits`` cuts the words to the argument's width (two of 9
    bits at 6,000 rows, not eight) and adds ``counts × least`` back: the
    same sums as the full 64 bits. A value past the width is flagged,
    not summed."""
    (lo, hi), bits = WIDTHS[case]
    rng = np.random.default_rng(11)
    n, size = 6000, 700
    slot = jnp.asarray(rng.integers(0, size + 3, n).astype(np.int32))
    v = rng.integers(lo, hi, n).astype(np.int64)
    v[:2] = lo, hi - 1
    counts = jnp.zeros(size, jnp.int32).at[slot].add(
        1, mode="drop").astype(jnp.int64)
    full, _ = K.exact_table_sum(slot, size, jnp.asarray(v), counts)
    cut, outside = K.exact_table_sum(slot, size, jnp.asarray(v), counts,
                                     bits)
    want = np.zeros(size + 3, np.int64)
    np.add.at(want, np.asarray(slot), v)
    assert (np.asarray(full) == want[:size]).all()
    assert (np.asarray(cut) == want[:size]).all()
    assert not np.asarray(outside).any()
    v[5] = hi + (1 << bits[0])
    _, outside = K.exact_table_sum(slot, size, jnp.asarray(v), counts,
                                   bits)
    assert np.flatnonzero(np.asarray(outside)).tolist() == [5]


def test_a_selected_key_past_the_box_is_flagged():
    k, v, nn, sel = _rows(12, 1000, ((0, 50),), range(9))
    k[5] = 50                               # one past the box
    _, _, past = _both(k, v, nn, sel, ((0, 50),), False)
    assert past
    sel[5] = False                          # not selected: no fault
    _, _, past = _both(k, v, nn, sel, ((0, 50),), False)
    assert not past


# ---------------------------------------------------- a table in RAM

def _ram_session():
    s = cb.Session(Config(n_segments=1))
    s.sql("create table t (k int, g int, v bigint, d decimal(12,2)) "
          "distributed by (k)")
    rng = np.random.default_rng(5)
    k = rng.integers(-40, 260, 6000)
    s.catalog.table("t").set_data(
        {"k": k.astype(np.int32), "g": (k % 7).astype(np.int32),
         "v": rng.integers(-2 ** 40, 2 ** 40, 6000).astype(np.int64),
         "d": rng.integers(-10 ** 6, 10 ** 6, 6000).astype(np.int64)})
    return s


def _aggs_of(plan) -> list:
    return [nd for nd in X.all_nodes(plan)
            if isinstance(nd, N.PAgg) and nd.group_keys]


SQL = "select k, count(*) as n, sum(v) as sv, sum(d) as sd from t group by k"


def test_a_statement_whose_key_leaves_its_box_raises():
    """Planned over keys -40..259, run after a key of 260 was written in
    place: the slot check fires and no answer comes back."""
    s = _ram_session()
    plan = plan_statement(parse_sql(SQL), s, {}).plan
    (agg,) = _aggs_of(plan)
    assert agg.direct and agg.direct_box == ((-40, 300),)
    t = s.catalog.table("t")
    data = {c: np.asarray(a).copy() for c, a in t.data.items()}
    data["k"][3] = 260
    t.set_data(data, t.dicts)
    with pytest.raises(X.ExecError, match="past its proven span 300"):
        X.execute(plan, s)


NO_BOX = {
    "a_float_sum": "select k, sum(cast(v as double)) as s "
                   "from t group by k",
    "a_min": "select k, min(v) as m from t group by k",
    "an_expression_key": "select k + 1 as k1, count(*) as n from t "
                         "group by k + 1",
}


@pytest.mark.parametrize("case", sorted(NO_BOX))
def test_what_the_direct_path_lacks_keeps_the_sort(case):
    s = _ram_session()
    plan = plan_statement(parse_sql(NO_BOX[case]), s, {}).plan
    assert [a.direct_box for a in _aggs_of(plan)] == [()]
    assert " direct" not in s.explain(NO_BOX[case])


def test_a_box_wider_than_the_rows_keeps_the_sort():
    """Keys 1,000 apart: 299,001 slots for 6,000 rows."""
    s = _ram_session()
    t = s.catalog.table("t")
    data = {c: np.asarray(a).copy() for c, a in t.data.items()}
    data["k"] = (data["k"].astype(np.int64) * 1000).astype(np.int32)
    t.set_data(data, t.dicts)
    (agg,) = _aggs_of(plan_statement(parse_sql(SQL), s, {}).plan)
    assert agg.direct_box and not agg.direct
    got = s.sql(SQL).to_pandas()
    assert s.stmt_log.counter("launch_agg_direct") == 0
    assert len(got) == len(np.unique(data["k"]))


# ------------------------------------------------- TPC-H, a cold store

SEED, SCALE = 2147486291, 0.01
DRAWS = {"q18": {"quantity": 250}, "q13": {"word1": 0, "word2": 1},
         "q3": {"segment": 1, "day": 15}, "q1": {"delta": 90}}
# statement: ([direct?] of its grouped aggregates, the outermost first)
DIRECT = {"q18": [False, True], "q13": [False, True], "q3": [False],
          "q1": [False]}
COUNTERS = ("launch_agg_direct", "launch_agg_sort_words")


def _config(root: str):
    return Config(n_segments=1).with_overrides(**{
        "storage.root": root, "storage.rows_per_partition": 1 << 20})


def _text(stmt: str) -> str:
    with open(os.path.join(C.BENCH, "statements", stmt + ".sql"),
              encoding="utf-8") as f:
        return f.read().format(
            **C.load_module("reference", stmt).bind(DRAWS[stmt]))


@pytest.fixture(scope="module")
def loaded(tmp_path_factory):
    """(the store's root, the generator's arrays) of a COLD SF0.01 store
    written by the benchmark's loader."""
    root = str(tmp_path_factory.mktemp("store"))
    keep: dict = {}
    for stmt in DRAWS:
        for t, cols in C.load_module("reference", stmt).COLUMNS.items():
            keep.setdefault(t, set()).update(cols)
    _, truth = load.load(cb.Session(_config(root)),
                         ["lineitem", "orders", "customer"], keep, SCALE,
                         SEED, 2500)
    return root, truth


@pytest.mark.parametrize("stmt", sorted(DIRECT))
def test_which_aggregates_sum_into_a_table(loaded, stmt):
    root, _ = loaded
    s = cb.Session(_config(root))           # every table cold
    plan = plan_statement(parse_sql(_text(stmt)), s, {},
                          explain_only=True).plan
    joincap.stamp_join_capacities(plan, s.catalog)
    aggs = _aggs_of(plan)
    assert [a.direct for a in aggs] == DIRECT[stmt]
    text = s.explain(_text(stmt))
    assert text.count(" direct") == sum(DIRECT[stmt])
    for a in aggs:
        if a.direct:
            assert f"GroupAgg single [{a.capacity}] direct" in text
            assert X.sort_words(a, "segment") == 0


@pytest.mark.parametrize("stmt,bits", [("q18", 13), ("q13", 1)])
def test_a_sum_is_cut_to_its_arguments_proven_bits(loaded, stmt, bits):
    """Q18's ``sum(l_quantity)`` by the column's zone maps (1..50 units,
    in cents: 13 bits), Q13's ``count(o_orderkey)``, a sum of CASE WHEN
    matched THEN 1 ELSE 0, by its branches (1 bit)."""
    root, _ = loaded
    s = cb.Session(_config(root))
    plan = plan_statement(parse_sql(_text(stmt)), s, {},
                          explain_only=True).plan
    joincap.stamp_join_capacities(plan, s.catalog)
    (agg,) = [a for a in _aggs_of(plan) if a.direct]
    assert [b[1:] for b in agg.sum_bits] == [(bits, False)]


@pytest.fixture(scope="module")
def served(loaded):
    """{(statement, cold or warm): (the wire answer, counters its launch
    added)}: a server of its own over the cold store, then every table
    read into RAM and each statement sent again."""
    root, _ = loaded
    out = {}
    with Server(config=_config(root)) as srv:
        log = srv.session.stmt_log
        c = Client(srv.host, srv.port, timeout=300.0)
        try:
            for phase in ("cold", "warm"):
                if phase == "warm":
                    for t in ("lineitem", "orders", "customer"):
                        srv.session.catalog.table(t).ensure_loaded()
                for stmt in ("q18", "q13"):
                    before = {n: log.counter(n) for n in COUNTERS}
                    got = c.sql(_text(stmt))
                    out[stmt, phase] = got, {
                        n: log.counter(n) - before[n] for n in COUNTERS}
        finally:
            c.close()
    return out


@pytest.mark.parametrize("phase", ["cold", "warm"])
@pytest.mark.parametrize("stmt", ["q18", "q13"])
def test_the_served_answer_equals_the_plain_reference(loaded, served, stmt,
                                                       phase):
    _, truth = loaded
    ref = C.load_module("reference", stmt).answer(truth, DRAWS[stmt])
    assert len(ref["rows"]) >= 5
    got, _ = served[stmt, phase]
    wrong, ulps = compare.gap(got, ref)
    assert wrong == 0, (got["rows"][:3], ref["rows"][:3])
    assert max(ulps.values(), default=0.0) == 0.0


@pytest.mark.parametrize("stmt", ["q18", "q13"])
def test_a_launch_counts_its_direct_aggregates(served, stmt):
    """One direct aggregate a launch of each; Q18's five-key aggregate
    still sorts its one word (``o_orderkey``), its order aggregate none."""
    for phase in ("cold", "warm"):
        _, added = served[stmt, phase]
        assert added["launch_agg_direct"] == 1
        if stmt == "q18":
            assert added["launch_agg_sort_words"] == 1
