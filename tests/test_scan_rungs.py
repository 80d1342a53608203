"""One-segment scans sit on capacity rungs (ISSUE 32): a scan's capacity
is the rung above its rows (``exec/kernels.py row_rung_up``, the ladder the
shards of a distributed table sit on), its columns cross to the device
padded to it, and the row count reaches the program as data. Every shape
of a program follows from its scans' capacities, so two loads of a table
that differ by a few rows are ONE program, which the persistent compile
cache answers; rows past the count are never selected, counted, matched
or null-extended."""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import Config
from cloudberry_tpu.exec import executor as X
from cloudberry_tpu.exec.kernels import row_rung_up
from cloudberry_tpu.plan import nodes as N

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (REPO, os.path.dirname(os.path.abspath(__file__))):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.harness import cell as C, compare, load       # noqa: E402
from program_texts import recording                           # noqa: E402

CELL = "tpch-sf1-joins.join-streams"


def _statement(cell, stmt: str) -> tuple:
    """(text, reference) of a statement: the cell's own, or, for Q13 (which
    left the cell's mix: the cold run's room), the benchmark's files."""
    if stmt in cell.statements:
        return cell.statements[stmt]
    with open(os.path.join(C.BENCH, "statements", stmt + ".sql"),
              encoding="utf-8") as f:
        return f.read(), C.load_module("reference", stmt)


def _columns(cell) -> dict:
    """What the loader keeps for the references: the cell's and Q13's."""
    keep = cell.reference_columns()
    for table, cols in C.load_module("reference", "q13").COLUMNS.items():
        keep.setdefault(table, set()).update(cols)
    return keep
DRAWS = {"q3": {"segment": 1, "day": 15},
         "q12": {"shipmode1": 5, "shipmode2": 3, "year": 1994},
         "q13": {"word1": 0, "word2": 1}}


# ------------------------------- (a) two seeds on one rung are one program

def _store_config(root: str):
    return Config(n_segments=1).with_overrides(**{
        "storage.root": root, "storage.rows_per_partition": 1 << 20})


@pytest.fixture(scope="module")
def loads(tmp_path_factory, request):
    """{label: (rows, truth, {statement: (module text hashes, answer)})}
    of the join cell's three statements served from a cold store that the
    benchmark's loader wrote: two seeds whose lineitem counts differ and
    share a rung, and a scale whose tables sit on the next rungs."""
    cell = C.Cell(CELL)
    programs: list = []
    compile_plan = X.compile_plan

    def recording_compile(*a, **kw):
        exe = compile_plan(*a, **kw)
        exe.packed_fn = recording(exe.packed_fn, programs)
        return exe

    X.compile_plan = recording_compile
    request.addfinalizer(lambda: setattr(X, "compile_plan", compile_plan))
    out = {}
    for label, seed, scale in (("a", 2147483712, 0.01),
                               ("b", 2147483715, 0.01),
                               ("next", 2147483713, 0.0102)):
        cfg = _store_config(str(tmp_path_factory.mktemp("store_" + label)))
        rows, truth = load.load(cb.Session(cfg), cell.tables(),
                                _columns(cell), scale, seed, 2500)
        s = cb.Session(cfg)            # a fresh session: its tables cold
        got = {}
        for stmt in sorted(DRAWS):
            text, ref = _statement(cell, stmt)
            del programs[:]
            batch = s.sql(text.format(**ref.bind(DRAWS[stmt])))
            got[stmt] = ([hashlib.sha256(t.encode()).hexdigest()
                          for _, t in programs], batch.to_pandas())
        rows["o_comment values"] = len(
            s.catalog.table("orders").dicts["o_comment"])
        out[label] = rows, truth, got
    return cell, out


@pytest.mark.parametrize("stmt", sorted(DRAWS))
def test_two_seeds_on_one_rung_lower_to_one_module_text(loads, stmt):
    _, out = loads
    (rows_a, _, a), (rows_b, _, b) = out["a"], out["b"]
    # the rows differ, and so do the distinct order comments (Q13's LIKE
    # is decided over their dictionary, which rides into the program at
    # the rung above its length): each pair on one rung
    for what in ("lineitem", "o_comment values"):
        assert rows_a[what] != rows_b[what]
        assert row_rung_up(rows_a[what]) == row_rung_up(rows_b[what])
    assert len(a[stmt][0]) >= 1 and a[stmt][0] == b[stmt][0]


@pytest.mark.parametrize("stmt", sorted(DRAWS))
def test_across_a_rung_the_module_text_differs(loads, stmt):
    _, out = loads
    rows_a, rows_n = out["a"][0], out["next"][0]
    assert all(row_rung_up(rows_a[t]) < row_rung_up(rows_n[t])
               for t in ("lineitem", "orders", "customer"))
    assert out["a"][2][stmt][0] != out["next"][2][stmt][0]


@pytest.mark.parametrize("label", ["a", "b", "next"])
@pytest.mark.parametrize("stmt", sorted(DRAWS))
def test_padded_scans_answer_as_the_plain_reference(loads, stmt, label):
    cell, out = loads
    _, truth, got = out[label]
    ref = _statement(cell, stmt)[1].answer(truth, DRAWS[stmt])
    df = got[stmt][1]
    assert list(df.columns) == ref["columns"]
    rows = [[(str(v) if hasattr(v, "isoformat") else
              v.item() if hasattr(v, "item") else v) for v in r]
            for r in df.values.tolist()]
    for r in rows:      # a date comes back as a date, money as a float
        for i, v in enumerate(r):
            if isinstance(v, str) and len(v) > 10 and v[4] == "-":
                r[i] = v[:10]
    wrong, ulps = compare.gap({"columns": ref["columns"], "rows": rows}, ref)
    assert wrong == 0, (rows[:3], ref["rows"][:3])
    assert max(ulps.values(), default=0.0) <= \
        cell.config["limits"]["sum_gap_ulps"]


# ------------------ (b) at, over, under a rung and empty: padding is no row

def _tables(n: int):
    """t: n rows, k cycling 0..6 with NULLs at every 11th row; u: the
    dimension (unique k 0..4, so k 5 and 6 match nothing); d: duplicates
    (k 0..2 twice), for the expansion join."""
    s = cb.Session(Config(n_segments=1))
    s.sql("create table t (a bigint, k bigint, v bigint) distributed by (a)")
    s.sql("create table u (k bigint, w bigint) distributed by (k)")
    s.sql("create table d (k bigint, x bigint) distributed by (k)")
    a = np.arange(n, dtype=np.int64)
    valid = (a % 11) != 10
    s.catalog.table("t").set_data(
        {"a": a, "k": a % 7, "v": a * 3 - 5}, {}, validity={"k": valid})
    uk = np.arange(5, dtype=np.int64)
    s.catalog.table("u").set_data({"k": uk, "w": uk * 10}, {})
    dk = np.asarray([0, 0, 1, 1, 2, 2], dtype=np.int64)
    s.catalog.table("d").set_data({"k": dk, "x": np.arange(6,
                                                           dtype=np.int64)},
                                  {})
    return s, a, valid


def _frame(a, valid):
    import pandas as pd

    t = pd.DataFrame({"a": a, "k": (a % 7).astype("float64"),
                      "v": a * 3 - 5})
    t.loc[~valid, "k"] = np.nan
    u = pd.DataFrame({"k": np.arange(5.0), "w": np.arange(5) * 10})
    d = pd.DataFrame({"k": [0.0, 0, 1, 1, 2, 2], "x": np.arange(6)})
    return t, u, d


RUNG = 1024 + 32            # a rung of the ladder's 1024..2048 stretch
SIZES = {"at": RUNG, "over": RUNG + 1, "under": RUNG - 1, "empty": 0}


@pytest.fixture(scope="module", params=sorted(SIZES))
def sized(request):
    n = SIZES[request.param]
    s, a, valid = _tables(n)
    assert row_rung_up(RUNG) == RUNG
    return (s, n) + _frame(a, valid)


def _one(s, sql):
    return s.sql(sql).to_pandas()


def test_the_scan_is_padded_only_past_its_rows(sized):
    s, n, *_ = sized
    plan = s.explain("select a from t")
    assert f"Scan t [{row_rung_up(n)}]" in plan
    assert len(_one(s, "select a from t")) == n


def test_count_min_max_see_no_padding(sized):
    s, n, t, _, _ = sized
    got = _one(s, "select count(*) as n, count(k) as nk, min(v) as mn, "
                  "max(v) as mx, sum(v) as sv from t")
    assert got.n[0] == n and got.nk[0] == int(t.k.notna().sum())
    if n:
        # the padding's zeros lie inside [min, max]: only sel keeps them out
        assert (got.mn[0], got.mx[0], got.sv[0]) == (-5, 3 * (n - 1) - 5,
                                                     int(t.v.sum()))
    else:
        assert got.mn.isna()[0] and got.mx.isna()[0]


def test_every_join_kind_matches_no_padded_row(sized):
    s, n, t, u, d = sized
    inner = t.merge(u, on="k")
    got = _one(s, "select count(*) as n, sum(w) as sw from t join u "
                  "on t.k = u.k")
    assert got.n[0] == len(inner)
    assert n == 0 or got.sw[0] == int(inner.w.sum())
    # left: every row of t once, NULL-extended where k is 5, 6 or NULL;
    # a padded probe row (k = 0, which MATCHES u) would add to both
    got = _one(s, "select count(*) as n, count(u.w) as m from t left join "
                  "u on t.k = u.k")
    assert (got.n[0], got.m[0]) == (n, len(inner))
    # the padded side as the BUILD of an outer join: u's five rows kept,
    # a padded t row (k = 0) would match u's k = 0
    got = _one(s, "select count(*) as n, count(t.a) as m from u left join "
                  "t on t.k = u.k")
    lj = u.merge(t, on="k", how="left")
    assert (got.n[0], got.m[0]) == (len(lj), int(lj.a.notna().sum()))
    semi = t[t.k.isin(u.k)]
    assert _one(s, "select count(*) as n from t where k in "
                   "(select k from u)").n[0] == len(semi)
    anti = t[t.k.notna() & ~t.k.isin(u.k)]
    assert _one(s, "select count(*) as n from t where k is not null and "
                   "not exists (select 1 from u where u.k = t.k)"
                ).n[0] == len(anti)
    # expansion (d repeats its keys), inner and outer
    pairs = t.merge(d, on="k")
    got = _one(s, "select count(*) as n, sum(x) as sx from t join d "
                  "on t.k = d.k")
    assert got.n[0] == len(pairs)
    assert n == 0 or got.sx[0] == int(pairs.x.sum())
    got = _one(s, "select count(*) as n, count(d.x) as m from t left join "
                  "d on t.k = d.k")
    assert (got.n[0], got.m[0]) == (len(pairs) + int((~t.k.isin(d.k)).sum()),
                                    len(pairs))
    full = t.merge(d, on="k", how="outer")
    got = _one(s, "select count(*) as n from t full join d on t.k = d.k")
    # pandas matches NaN keys to each other; t's NULL keys match nothing
    # in d (it has none), so the outer merge is the SQL answer here
    assert got.n[0] == len(full)


def test_distinct_window_sort_and_limit_see_no_padding(sized):
    s, n, t, _, _ = sized
    got = _one(s, "select distinct k from t order by k")
    want = sorted(t.k.dropna().unique().tolist())
    assert got.k.dropna().tolist() == want
    assert int(got.k.isna().sum()) == int(t.k.isna().any())
    # NULLs sort as larger than every value: last ascending, first
    # descending; the padding's k = 0 rows would come first ascending
    got = _one(s, "select a, k from t order by k, a")
    assert len(got) == n
    assert got.k.isna().tolist() == sorted(t.k.isna().tolist())
    got = _one(s, "select a, k from t order by k desc, a limit 3")
    assert len(got) == min(n, 3)
    if n:
        assert got.k.isna().all()
    got = _one(s, "select a from t limit 5")         # no ORDER BY
    assert len(got) == min(n, 5) and set(got.a) <= set(t.a)
    got = _one(s, "select a, row_number() over (order by a desc) as rn, "
                  "sum(v) over () as sv, count(*) over (partition by k) "
                  "as nk from t order by a")
    assert len(got) == n
    if n:
        assert got.rn.tolist() == list(range(n, 0, -1))
        assert (got.sv == int(t.v.sum())).all()
        per = t.groupby("k", dropna=False).size()
        nulls = int(t.k.isna().sum())
        want = [nulls if np.isnan(k) else int(per[k]) for k in t.k]
        assert got.nk.tolist() == want


def test_dml_over_a_padded_table(sized):
    s, n, t, _, _ = sized
    s.sql("create table c (a bigint, k bigint, v bigint) distributed by (a)")
    tt = s.catalog.table("t")
    s.catalog.table("c").set_data(
        {c: np.array(v) for c, v in tt.data.items()}, {},
        validity={c: np.array(v) for c, v in tt.validity.items()})
    s.sql("update c set v = v + 1 where k = 0")
    assert _one(s, "select sum(v) as sv, count(*) as n from c").n[0] == n
    hit = int((t.k == 0).sum())
    if n:
        assert _one(s, "select sum(v) as sv from c").sv[0] == \
            int(t.v.sum()) + hit
    s.sql("delete from c where k = 0")
    assert _one(s, "select count(*) as n from c").n[0] == n - hit
    s.sql("insert into c values (-1, 0, 7)")
    got = _one(s, "select count(*) as n, min(a) as mn from c")
    assert (got.n[0], got.mn[0]) == (n - hit + 1, -1)
    s.sql("drop table c")


def test_explain_analyze_counts_rows_not_capacity(sized):
    s, n, *_ = sized
    text = s.explain_analyze("select a from t where a >= 0")
    scan = next(ln for ln in text.splitlines() if "Scan t" in ln)
    assert f"[{row_rung_up(n)}]" in scan and f"rows={n}" in scan, text


# --------------------------------- (c) appends inside and across the rung

def test_an_append_inside_the_rung_compiles_nothing_that_misses_the_cache(
        tmp_path):
    """A table version is a new plan (the statement and generic-plan
    caches key on it: their programs bake the dictionaries' constants),
    so the engine's ``compiles`` counts a program construction a version.
    What the rung keeps is the program: inside it the new plan lowers to
    the module text the last one did, and the compile cache answers."""
    p = subprocess.run([sys.executable, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scan_rungs_worker.py"),
        str(tmp_path)], capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    first, inside, across = json.loads(p.stdout.strip().splitlines()[-1])
    assert first["misses"] >= 1 and len(first["hashes"]) == 1
    assert (first["n"], inside["n"], across["n"]) == (1036, 1046, 1066)
    assert first["capacity"] == inside["capacity"] == 1056
    assert inside["hashes"] == first["hashes"]
    assert inside["misses"] == 0 and inside["hits"] >= 1, inside
    assert across["capacity"] == row_rung_up(1066) == 1088
    assert across["hashes"] != first["hashes"] and across["misses"] >= 1
    a = np.arange(1036)
    assert first["sa"] == int(a.sum())
    assert inside["sa"] == first["sa"] + sum(1056 + i for i in range(10))
    assert across["sa"] == inside["sa"] + sum(2112 + i for i in range(20))


def test_the_launch_counts_rows_and_capacity():
    s = cb.Session(Config(n_segments=1))
    s.sql("create table g (a bigint) distributed by (a)")
    s.catalog.table("g").set_data(
        {"a": np.arange(1000, dtype=np.int64)}, {})
    s.sql("select count(*) from g")
    assert s.stmt_log.counter("scan_rows") == 1000
    assert s.stmt_log.counter("scan_capacity_rows") == row_rung_up(1000) \
        == 1008


# ----------------- (d) a short column of a table with rows is an error

def test_a_short_column_raises_and_an_empty_one_is_filled():
    scan = N.PScan("t", {"a": "t.a"}, capacity=8, num_rows=5)
    scan.fields = []
    short = {"t": {"a": jnp.arange(5)}}
    with pytest.raises(X.ExecError, match="5 rows, the plan's capacity "
                                          "is 8"):
        X.Lowerer(short).scan(scan)
    cols, sel = X.Lowerer({"t": {"a": jnp.arange(8),
                                 X.NROWS: np.int64(5)}}).scan(scan)
    assert cols["t.a"].shape == (8,) and int(sel.sum()) == 5
    empty = N.PScan("t", {"a": "t.a"}, capacity=1, num_rows=0)
    empty.fields = []
    cols, sel = X.Lowerer({"t": {"a": jnp.zeros((0,), jnp.int64)}}
                          ).scan(empty)
    assert cols["t.a"].shape == (1,) and not bool(sel.any())
