"""A lookup table that a statement's literals chose (a string IN list, a
LIKE pattern, an ordering comparison with a string: ``expr.LookupLiterals``)
is a parameter of the program, not a part of it: a generic plan binds its
contents per send as a ``$dlt<i>`` entry of ``$params``
(sched/paramplan.py), so two draws of TPC-H's Q12 with other shipping
modes, or of Q13 with other words, run one compiled program, each with its
own table, never an earlier send's.

Served from a cold SF 0.01 store written by the benchmark's loader, every
answer against the plain references under ``benchmarks/reference``:
(a) drawn Q3 and Q12d texts, one after the other on one connection, and
the first again at the end; (b) what compiles and what is counted; (c) two
Q13 word pairs; (d) literals the dictionary lacks and NOT IN; (e) the
literal template of a scan-only statement derives the table from the
tokens, or refuses and says why; (f) a stacked launch binds one table a
statement; (g) two draws lower to one module text, in two processes.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import cloudberry_tpu as cb
from cloudberry_tpu.config import Config
from cloudberry_tpu.plan import binder
from cloudberry_tpu.sched import paramplan
from cloudberry_tpu.serve import meta
from cloudberry_tpu.serve.client import Client
from cloudberry_tpu.serve.server import Server

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import cell as C, compare, load       # noqa: E402

SCALE, SEED = 0.01, 2**31 + 45
TABLES = ["lineitem", "orders", "customer"]
MODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")
# six draws of each statement from the cell's grids, sent alternately
DRAWS = {
    "q12d": [{"shipmode1": 5, "apart": 5, "year": 1994},
             {"shipmode1": 0, "apart": 1, "year": 1997},
             {"shipmode1": 2, "apart": 4, "year": 1993},
             {"shipmode1": 3, "apart": 1, "year": 1995},
             {"shipmode1": 6, "apart": 3, "year": 1996},
             {"shipmode1": 4, "apart": 6, "year": 1994}],
    "q3": [{"segment": 1, "day": 15}, {"segment": 0, "day": 1},
           {"segment": 4, "day": 31}, {"segment": 2, "day": 9},
           {"segment": 3, "day": 22}, {"segment": 1, "day": 3}],
}
# Q12d's modes drawn at one year: the year moves the estimate of the rows
# that reach the join, and at this scale the power of two it is stamped
# on (plan/joincap.py), a program of its own by design; the modes move
# nothing but the table
SAME_YEAR = [{"shipmode1": m, "apart": a, "year": 1995}
             for m, a in ((5, 5), (0, 1), (2, 4), (3, 2), (6, 3), (4, 6))]
WORDS = [{"word1": 0, "word2": 1}, {"word1": 1, "word2": 3},
         {"word1": 3, "word2": 0}]


def _config(root: str):
    return Config(n_segments=1).with_overrides(**{
        "storage.root": root, "storage.rows_per_partition": 1 << 20})


def _ref(stmt: str):
    return C.load_module("reference", stmt)


def _text(stmt: str, params: dict) -> str:
    with open(os.path.join(C.BENCH, "statements", stmt + ".sql"),
              encoding="utf-8") as f:
        return f.read().format(**_ref(stmt).bind(params))


@pytest.fixture(scope="module")
def loaded(tmp_path_factory):
    """(the store's root, the generator's arrays) of a COLD SF 0.01 store
    written by the benchmark's loader."""
    root = str(tmp_path_factory.mktemp("store"))
    keep: dict = {"lineitem": {"l_shipmode", "l_quantity"}}
    for stmt in ("q3", "q12d", "q13"):
        for t, cols in _ref(stmt).COLUMNS.items():
            keep.setdefault(t, set()).update(cols)
    _, truth = load.load(cb.Session(_config(root)), TABLES, keep, SCALE,
                         SEED, 2500)
    return root, truth


def _send(srv, c, text: str) -> tuple:
    """(the wire answer, {counter: what the send added})."""
    log = srv.session.stmt_log
    names = ("compiles", "dict_params_bound", "generic_hits")
    before = {n: log.counter(n) for n in names}
    got = c.sql(text)
    return got, {n: log.counter(n) - before[n] for n in names}


@pytest.fixture(scope="module")
def served(loaded):
    """Everything this module sends on one connection of one server over
    the cold store: {part: [(statement, draw, answer, counters), ...]}."""
    root, _ = loaded
    out: dict = {}
    with Server(config=_config(root)) as srv:
        c = Client(srv.host, srv.port, timeout=300.0)
        try:
            sends = [(stmt, DRAWS[stmt][k]) for k in range(6)
                     for stmt in ("q3", "q12d")]
            sends.append(sends[1])          # the first Q12d text again
            out["drawn"] = [(s, p, *_send(srv, c, _text(s, p)))
                            for s, p in sends]
            out["same_year"] = [("q12d", p, *_send(srv, c, _text("q12d", p)))
                                for p in SAME_YEAR]
            out["words"] = [("q13", p, *_send(srv, c, _text("q13", p)))
                            for p in WORDS]
        finally:
            c.close()
    return out


def _equal(got: dict, ref: dict) -> None:
    wrong, ulps = compare.gap(got, ref)
    assert wrong == 0, (got["rows"][:3], ref["rows"][:3])
    assert max(ulps.values(), default=0.0) == 0.0


# ------------------------------------------------------ (a) the answers


@pytest.mark.parametrize("k", range(13))
def test_a_drawn_text_is_answered_with_its_own_draw(loaded, served, k):
    """Six Q3 and six Q12d draws alternately, then the first Q12d draw
    again (a statement-cache hit after five other tables were bound):
    each equals the reference of its own draw."""
    _, truth = loaded
    stmt, params, got, _ = served["drawn"][k]
    ref = _ref(stmt).answer(truth, params)
    assert ref["rows"]
    _equal(got, ref)


def test_the_draws_answer_differently():
    """(the check above would pass a stale table only where two draws
    answered alike: they do not)"""
    assert len({json.dumps(_ref("q12d").bind(p), sort_keys=True)
                for p in DRAWS["q12d"]}) == 6


def test_q12ds_grid_draws_two_distinct_modes():
    ref = _ref("q12d")
    pairs = {}
    for m in range(7):
        for a in range(1, 7):
            b = ref.bind({"shipmode1": m, "apart": a, "year": 1994})
            assert b["shipmode1"] != b["shipmode2"]
            key = frozenset((b["shipmode1"], b["shipmode2"]))
            pairs[key] = pairs.get(key, 0) + 1
    assert len(pairs) == 21 and set(pairs.values()) == {2}


# --------------------------------------------- (b) compiles and counters


def test_other_modes_compile_nothing_and_bind_one_table(served):
    """After the first Q12d send of one year, five other mode pairs
    compile no program and each binds its table (``dict_params_bound``);
    a Q3 send binds none (its segment is a dictionary code, a $prm)."""
    sends = served["same_year"]
    assert [s[3]["compiles"] for s in sends[1:]] == [0] * 5
    assert [s[3]["generic_hits"] for s in sends[1:]] == [1] * 5
    assert [s[3]["dict_params_bound"] for s in sends] == [1] * 6
    for stmt, _, _, added in served["drawn"]:
        assert added["dict_params_bound"] == (stmt == "q12d")


def test_the_same_year_draws_answer_as_their_references(loaded, served):
    _, truth = loaded
    for _, params, got, _ in served["same_year"]:
        _equal(got, _ref("q12d").answer(truth, params))


# ---------------------------------------------------------- (c) Q13


def test_two_word_pairs_share_one_program(loaded, served):
    """Q13's LIKE over some hundreds of distinct comments: each word pair
    equals ``reference/q13.py`` and the later pairs compile nothing."""
    _, truth = loaded
    sends = served["words"]
    assert [s[3]["compiles"] for s in sends[1:]] == [0, 0]
    assert [s[3]["dict_params_bound"] for s in sends] == [1, 1, 1]
    answers = []
    for _, params, got, _ in sends:
        ref = _ref("q13").answer(truth, params)
        _equal(got, ref)
        answers.append(ref["rows"])
    assert answers[0] != answers[1]


# ------------------------------------ (d) literals the dictionary lacks


def _modes(truth) -> np.ndarray:
    return truth["lineitem"]["l_shipmode"]


@pytest.mark.parametrize("pred,keep", [
    ("in ('MAIL', 'NOSUCHMODE')", lambda m: m == "MAIL"),
    ("in ('NOSUCHMODE', 'NOSUCHMODE2')", lambda m: np.zeros(len(m), bool)),
    ("not in ('MAIL', 'SHIP')", lambda m: ~np.isin(m, ["MAIL", "SHIP"])),
    ("not in ('AIR', 'NOSUCHMODE')", lambda m: m != "AIR"),
    ("like '%AIR'", lambda m: np.char.endswith(m, "AIR")),
    ("not like 'R%'", lambda m: ~np.char.startswith(m, "R")),
    ("< 'MAIL'", lambda m: m < "MAIL"),
    (">= 'RAIL'", lambda m: m >= "RAIL"),
])
def test_a_lookup_answers_as_numpy(loaded, pred, keep):
    """Sent after a draw that binds another table to the same program
    where the skeleton matches: a literal the dictionary lacks matches
    nothing, NOT IN and NOT LIKE keep every other mode."""
    root, truth = loaded
    s = cb.Session(_config(root))
    modes = _modes(truth)
    text = ("select l_shipmode, count(*) as n from lineitem "
            f"where l_shipmode {pred} group by l_shipmode "
            "order by l_shipmode")
    warm = text.replace("'MAIL'", "'FOB'").replace("'AIR'", "'RAIL'")
    s.sql(warm)
    got = s.sql(text).to_pandas()
    want = keep(modes)
    names, counts = np.unique(modes[want], return_counts=True)
    assert got["l_shipmode"].tolist() == names.tolist()
    assert got["n"].tolist() == counts.tolist()


# ------------------------------------------------ (e) the literal template


SCAN = ("select count(*) from lineitem where l_shipmode in ('{a}', '{b}') "
        "and l_quantity < {q}")
SCAN_DRAWS = [(5, 3, 10), (0, 1, 20), (2, 6, 30), (3, 4, 40), (1, 5, 12),
              (4, 0, 13), (6, 2, 14), (5, 3, 44)]


def _scan_answers(s, truth) -> list:
    modes = _modes(truth)
    qty = truth["lineitem"]["l_quantity"]
    out = []
    for a, b, q in SCAN_DRAWS:
        got = s.sql(SCAN.format(a=MODES[a], b=MODES[b], q=q)).to_pandas()
        want = int(np.sum(np.isin(modes, [MODES[a], MODES[b]])
                          & (qty < q * 100)))
        out.append((int(got.iloc[0, 0]), want))
    return out


def test_a_scan_templates_its_table_from_the_tokens(loaded):
    """A scan-only statement: once every free token has moved, a draw
    with other modes binds without parse or plan, its table chosen again
    over the dictionary from the tokens, and answers as numpy does."""
    root, truth = loaded
    s = cb.Session(_config(root))
    log = s.stmt_log
    answers = _scan_answers(s, truth)
    assert [g for g, _ in answers] == [w for _, w in answers]
    assert log.counter("template_builds") == 1
    assert log.counter("template_binds") >= 4
    assert log.counter("template_refused.lookup_table") == 0
    assert log.counter("compiles") == 1
    assert log.counter("dict_params_bound") == len(SCAN_DRAWS)
    assert meta.describe(s, "sched")["counters"]["dict_params_bound"] \
        == len(SCAN_DRAWS)


def test_a_table_of_untracked_literals_refuses_the_template(loaded,
                                                            monkeypatch):
    """Where a literal of the table names no token of the text (bound
    here without positions), the template refuses under ``lookup_table``
    and every draw goes the full path, still one program."""
    root, truth = loaded
    make = binder._literal_lookup
    monkeypatch.setattr(binder, "_literal_lookup",
                        lambda e, kind, texts, pos: make(e, kind, texts,
                                                         [-1] * len(pos)))
    s = cb.Session(_config(root))
    log = s.stmt_log
    answers = _scan_answers(s, truth)
    assert [g for g, _ in answers] == [w for _, w in answers]
    assert log.counter("template_refused.lookup_table") == 1
    assert log.counter("template_binds") == 0
    assert log.counter("compiles") == 1


# ------------------------------------------------ (f) a stacked launch


def test_a_stacked_launch_binds_each_statements_table():
    """Same-skeleton statements over a table in RAM in ONE vmapped launch
    (``paramplan.run_batch``): each lane gathers through its own table."""
    s = cb.Session(Config(n_segments=1))
    s.sql("create table t (k int, m text) distributed by (k)")
    s.sql("insert into t values " + ", ".join(
        f"({i}, '{MODES[i % 7]}')" for i in range(70)))
    sqls = [f"select count(*) from t where m in ('{MODES[a]}', '{MODES[b]}')"
            f" and k < {q}" for a, b, q in ((5, 3, 70), (0, 1, 70),
                                            (2, 6, 35))]
    s.sql(sqls[0])
    before = s.stmt_log.counter("dict_params_bound")
    got = paramplan.run_batch(s, sqls)
    assert got is not None
    assert [int(b.to_pandas().iloc[0, 0]) for b in got] == [20, 20, 10]
    assert s.stmt_log.counter("dict_params_bound") - before == 3


# ----------------------------------------------- (g) the programs' text


WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "program_identity_worker.py")


@pytest.mark.parametrize("stmt", ["q12", "q13"])
def test_two_draws_lower_to_one_module_text(stmt):
    """In two processes, Q12 with other modes and Q13 with other words
    lower to the same module text, so the compile cache of one draw
    serves the others."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    procs = [subprocess.Popen([sys.executable, WORKER, "1", stmt, "--draw",
                               str(k)], stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, env=env, text=True)
             for k in (0, 1)]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, err[-2000:]
        outs.append(json.loads(out.strip().splitlines()[-1]))
    a, b = outs
    assert a["programs"] == b["programs"] >= 1
    assert a["rows"] > 0 and b["rows"] > 0
    assert a["hashes"] == b["hashes"]
    assert a["result_info"] == b["result_info"]
