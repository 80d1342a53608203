"""End-to-end TPC-H correctness: SQL → parse → bind/plan → jitted kernels →
result, validated against the pandas oracle (tools/tpch_oracle.py) on the
same generated data — the regress-suite analog."""

import pytest

import cloudberry_tpu as cb
from tools.tpch_oracle import ORACLES, assert_frames_match  # noqa: F401
from tools.tpch_queries import QUERIES
from tools.tpchgen import load_tpch


@pytest.fixture(scope="module")
def tpch_session():
    s = cb.Session()
    load_tpch(s, sf=0.01, seed=7)
    tables = {n: t.to_pandas() for n, t in s.catalog.tables.items()}
    return s, tables


@pytest.mark.parametrize("qname", sorted(QUERIES))
def test_tpch_query(tpch_session, qname):
    session, tables = tpch_session
    if qname not in ORACLES:
        pytest.skip(f"no oracle for {qname}")
    got = session.sql(QUERIES[qname]).to_pandas()
    exp = ORACLES[qname](tables)
    assert_frames_match(got, exp, qname)


def test_explain_q3(tpch_session):
    session, _ = tpch_session
    text = session.explain(QUERIES["q3"])
    assert "Join" in text and "Scan lineitem" in text and "GroupAgg" in text


def test_q1_money_sums_exact_in_int64(tpch_session):
    """Q1's four money sums are DECIMAL sums carried as scaled int64
    (scales 2, 2, 4, 6): bit for bit numpy's int64 arithmetic over the
    stored cents, where a float sum would round; the counts alike."""
    import datetime

    import numpy as np

    session, _ = tpch_session
    batch = session.sql(QUERIES["q1"])
    li = {c: np.asarray(v) for c, v in
          session.catalog.table("lineitem").data.items()}
    cutoff = (datetime.date(1998, 12, 1) - datetime.timedelta(days=90)
              - datetime.date(1970, 1, 1)).days
    keep = li["l_shipdate"] <= cutoff
    disc = li["l_extendedprice"] * (100 - li["l_discount"])
    exact = {"sum_qty": li["l_quantity"],
             "sum_base_price": li["l_extendedprice"],
             "sum_disc_price": disc,
             "sum_charge": disc * (100 + li["l_tax"]),
             "count_order": np.ones_like(li["l_quantity"])}
    sel = np.asarray(batch.sel)
    flags = np.asarray(batch.columns["l_returnflag"])[sel]
    status = np.asarray(batch.columns["l_linestatus"])[sel]
    assert len(flags) == 4
    for name, v in exact.items():
        got = np.asarray(batch.columns[name])[sel]
        assert got.dtype == np.int64
        want = [int(v[keep & (li["l_returnflag"] == f)
                      & (li["l_linestatus"] == st)].sum())
                for f, st in zip(flags, status)]
        assert got.tolist() == want, name
