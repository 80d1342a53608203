"""The one-segment join path served (ISSUE 32): TPC-H SF0.01 written
through the store by the benchmark's loader, an in-process
``serve.Server`` at one segment whose backend plans each statement from
a COLD store, one TCP client. The benchmark's plain references
(``benchmarks/reference/q3.py``, ``q12.py``, ``q13.py``) hold the
answers: rows and order exactly. Q3 and Q12 are sorted-build lookups,
Q13's outer join on ``o_custkey`` an expansion; the launch counts the
rows its scans hold and the rows they are padded to."""

from __future__ import annotations

import os
import sys

import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import Config
from cloudberry_tpu.exec.kernels import row_rung_up
from cloudberry_tpu.serve.client import Client
from cloudberry_tpu.serve.server import Server

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import cell as C, compare, load       # noqa: E402

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
SEED, SCALE = 2147486231, 0.01
DRAWS = {"q3": {"segment": 1, "day": 15},
         "q12": {"shipmode1": 5, "shipmode2": 3, "year": 1994},
         "q13": {"word1": 0, "word2": 1}}
JOINS = {"q3": (2, 0), "q12": (1, 0), "q13": (0, 1)}    # lookups, expansions


def _config(root: str):
    return Config(n_segments=1).with_overrides(**{
        "storage.root": root, "storage.rows_per_partition": 1 << 20})


def _text(cell, stmt: str) -> str:
    text, ref = _statement(cell, stmt)
    return text.format(**ref.bind(DRAWS[stmt]))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """(cell, rows, the generator's arrays, {statement: (wire answer,
    counters the send added)}), each statement sent twice over TCP."""
    cell = C.Cell(CELL)
    root = str(tmp_path_factory.mktemp("store"))
    rows, truth = load.load(cb.Session(_config(root)), cell.tables(),
                            _columns(cell), SCALE, SEED, 2500)
    names = ("launch_joins_lookup", "launch_joins_expand", "scan_rows",
             "scan_capacity_rows", "launch_packed", "compiles")
    out = {}
    with Server(config=_config(root)) as srv:
        log = srv.session.stmt_log
        c = Client(srv.host, srv.port, timeout=300.0)
        try:
            for stmt in sorted(DRAWS):
                c.sql(_text(cell, stmt))
                before = {n: log.counter(n) for n in names}
                got = c.sql(_text(cell, stmt))
                out[stmt] = got, {n: log.counter(n) - before[n]
                                  for n in names}
        finally:
            c.close()
    return cell, rows, truth, out


@pytest.mark.parametrize("stmt", sorted(DRAWS))
def test_served_answer_equals_the_plain_reference(served, stmt):
    cell, _, truth, out = served
    ref = _statement(cell, stmt)[1].answer(truth, DRAWS[stmt])
    assert len(ref["rows"]) >= (10 if stmt == "q3" else 2)
    wrong, ulps = compare.gap(out[stmt][0], ref)
    assert wrong == 0, (out[stmt][0]["rows"][:3], ref["rows"][:3])
    assert max(ulps.values(), default=0.0) <= \
        cell.config["limits"]["sum_gap_ulps"]


def test_q13_counts_the_customers_without_orders(served):
    """The outer join's null extension: a third of the customers place
    no order (spec 4.2.3), and ``count(o_orderkey)`` counts none for
    them; a padded customer row would be one more."""
    _, rows, _, out = served
    zero = [r for r in out["q13"][0]["rows"] if r[0] == 0]
    assert len(zero) == 1 and zero[0][1] >= rows["customer"] // 3
    assert sum(r[1] for r in out["q13"][0]["rows"]) == rows["customer"]


@pytest.mark.parametrize("stmt", sorted(DRAWS))
def test_a_repeat_send_launches_the_joins_and_compiles_nothing(served,
                                                               stmt):
    _, _, _, out = served
    added = out[stmt][1]
    assert (added["launch_joins_lookup"],
            added["launch_joins_expand"]) == JOINS[stmt]
    assert added["launch_packed"] == 1 and added["compiles"] == 0


@pytest.mark.parametrize("stmt", sorted(DRAWS))
def test_the_launch_counts_its_scans_rows_and_their_rungs(served, stmt):
    cell, rows, _, out = served
    added = out[stmt][1]
    tables = _statement(cell, stmt)[1].TABLES
    assert added["scan_rows"] == sum(rows[t] for t in tables)
    assert added["scan_capacity_rows"] == sum(row_rung_up(rows[t])
                                              for t in tables)
    assert added["scan_capacity_rows"] > added["scan_rows"]
