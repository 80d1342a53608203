"""A statement is the same program in every process (ISSUE 28).

Check and stats keys are outputs of the program, and an output's key is
part of the module's text (``jax.result_info``), which the persistent
compile cache keys on. They name a plan node by its ordinal in the plan,
never by ``id()``: two processes lower the same statement to the same
text, and a program that carries a check hits the cache in the next
process.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
WORKER = os.path.join(HERE, "program_identity_worker.py")


def _start(nseg: int, stmt: str, *flags: str):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    return subprocess.Popen([sys.executable, WORKER, str(nseg), stmt, *flags],
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            env=env, text=True)


@pytest.mark.parametrize("nseg,stmt", [(1, "q15v"), (4, "q15v"), (4, "q3")])
def test_two_processes_lower_the_same_module(nseg, stmt):
    procs = [_start(nseg, stmt) for _ in range(2)]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, err[-2000:]
        outs.append(json.loads(out.strip().splitlines()[-1]))
    a, b = outs
    assert a["programs"] >= 1 and a["rows"] == b["rows"] > 0
    assert a["hashes"] == b["hashes"]
    assert a["result_info"] == b["result_info"]
    keyed = [k for k in a["result_info"] if "(node " in k]
    # a distributed program returns its checks and motion statistics by
    # name (the one-shot program packs its answer: its keys decide the
    # ORDER of the packed leaves, which the hashes above cover)
    assert keyed or nseg == 1, a["result_info"]
    for key in keyed:
        for ordinal in re.findall(r"\(node (\d+)", key):
            assert int(ordinal) < 1000, f"an address, not an ordinal: {key}"
    if nseg > 1 and stmt == "q15v":
        assert any(k.startswith("result[3]['required bucket (node ")
                   for k in a["result_info"])


@pytest.mark.parametrize("nseg,stmt", [(1, "q1"), (1, "q6"), (4, "q15v"),
                                       (4, "q3")])
def test_a_literals_origin_reaches_no_program(nseg, stmt):
    """The origin a literal carries for the literal template (ISSUE 29)
    is beside its value, never in the program: bound with it and without
    it (as the tree before it bound), a statement lowers to the same
    module text, so the compile cache of the benchmark's cells is met."""
    procs = [_start(nseg, stmt), _start(nseg, stmt, "--no-origin")]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, err[-2000:]
        outs.append(json.loads(out.strip().splitlines()[-1]))
    a, b = outs
    assert a["programs"] == b["programs"] >= 1
    assert a["rows"] == b["rows"] > 0
    assert a["hashes"] == b["hashes"]
    assert a["result_info"] == b["result_info"]


def test_a_cold_table_lowers_to_the_program_of_a_loaded_one():
    """Q3 at four segments over a store written in several appends
    (ISSUE 31): served from a session whose tables are cold, where the
    planner has the manifests' word for the keys' uniqueness, and from
    one that loaded them first, where it has the data, the statement is
    the same module text, and both its joins are lookups."""
    procs = [_start(4, "q3", "--store"), _start(4, "q3", "--store", "--warm")]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, err[-2000:]
        outs.append(json.loads(out.strip().splitlines()[-1]))
    cold, warm = outs
    assert cold["programs"] == warm["programs"] == 1
    assert cold["rows"] == warm["rows"] == 10
    assert cold["hashes"] == warm["hashes"]
    assert cold["result_info"] == warm["result_info"]
    assert cold["joins"] == warm["joins"] == [2, 0]
    assert not any("expansion overflow" in k for k in cold["result_info"])


def test_no_program_key_embeds_an_address():
    """No check or stats key anywhere in the engine is built from
    ``id()``: the only ``(node ...)`` references are ordinals
    (``Lowerer.ref`` / ``Lowerer.label``)."""
    root = os.path.join(os.path.dirname(HERE), "cloudberry_tpu")
    bad = []
    for dirpath, _, files in os.walk(root):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            with open(path, encoding="utf-8") as f:
                for n, line in enumerate(f, 1):
                    if re.search(r"\(node \{id\(", line) or \
                            re.search(r"node_counts\[id\(", line):
                        bad.append(f"{path}:{n}: {line.strip()}")
    assert not bad, "\n".join(bad)
