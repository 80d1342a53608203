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
