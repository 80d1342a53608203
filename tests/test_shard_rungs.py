"""Shard capacities sit on rungs (ISSUE 28's review): every shape of a
distributed program follows from its scans' shard capacities, so two
loads of a table that differ by a few rows have to meet ONE program, or
each is a multi-minute compile that no cache can answer."""

from __future__ import annotations

import numpy as np
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import Config
from cloudberry_tpu.exec import dist_executor as DX
from cloudberry_tpu.exec.kernels import row_rung_up


@pytest.mark.parametrize("n,rung", [
    (0, 1), (1, 1), (64, 64), (65, 66), (1000, 1008), (1024, 1024),
    (1025, 1056),
    # TPC-H SF1 over four segments, the largest shard of four seeds
    (1_505_420, 1_507_328), (1_503_424, 1_507_328), (1_501_806, 1_507_328),
    (376_100, 376_832), (37_717, 37_888)])
def test_the_ladder(n, rung):
    assert row_rung_up(n) == rung


def test_a_rung_is_never_under_and_at_most_a_32nd_over():
    for n in list(range(1, 5000)) + [10**k + 7 for k in range(4, 10)]:
        r = row_rung_up(n)
        assert n <= r <= n + max(n // 32, 0) + 1, n
        assert row_rung_up(r) == r


def _session(rows: int):
    s = cb.Session(Config(n_segments=4))
    s.sql("create table t (a bigint, g bigint, v bigint) distributed by (a)")
    a = np.arange(rows, dtype=np.int64)
    s.catalog.table("t").set_data({"a": a, "g": a % 37, "v": a * 3}, {})
    return s


Q = "select g, sum(v) as sv, count(*) as n from t group by g order by g"


def _program_text(s) -> str:
    from cloudberry_tpu.plan.planner import plan_statement
    from cloudberry_tpu.sql.parser import parse_sql

    plan = plan_statement(parse_sql(Q), s, {}).plan
    fn = DX.compile_distributed(plan, s)
    inputs, _ = DX.prepare_dist_inputs(plan, s)
    return fn.lower(inputs).as_text()


def test_two_row_counts_on_one_rung_are_one_program_and_both_right():
    small, large = _session(40_000), _session(40_300)
    caps = [s.shard_capacity("t") for s in (small, large)]
    maxes = [int(s.shard_counts("t").max()) for s in (small, large)]
    assert maxes[0] != maxes[1] and caps[0] == caps[1] > max(maxes)
    # the materialized shards are as wide as the planner's capacity
    assert small.sharded_table("t").columns["v"].shape == (4, caps[0])
    assert _program_text(small) == _program_text(large)
    for s, rows in ((small, 40_000), (large, 40_300)):
        a = np.arange(rows, dtype=np.int64)
        out = s.sql(Q).to_pandas()
        assert out.g.tolist() == list(range(37))
        assert out.sv.tolist() == [int((a[a % 37 == g] * 3).sum())
                                   for g in range(37)]
        assert out.n.tolist() == [int((a % 37 == g).sum())
                                  for g in range(37)]
