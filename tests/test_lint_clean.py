"""The graftlint tier-1 gate: the repo must be finding-free.

``python -m cloudberry_tpu.lint cloudberry_tpu/`` exits 0 — zero
unsuppressed findings — and every suppression carries a justification.
A new finding here means a concurrency/kernel/taxonomy/seam invariant
regressed (or a pass needs a justified ``# graftlint: ignore[rule]``
at the site — with the reasoning, not just the tag).
"""

import functools
import os

import cloudberry_tpu
from cloudberry_tpu.lint import run_lint

PKG = os.path.dirname(os.path.abspath(cloudberry_tpu.__file__))


@functools.lru_cache(maxsize=1)
def _result():
    return run_lint([PKG])


def test_repo_is_finding_free():
    result = _result()
    msgs = [f.render() for f in result.unsuppressed]
    assert not msgs, "graftlint findings:\n" + "\n".join(msgs)


def test_every_suppression_has_a_justification():
    result = _result()
    bare = [f.render() for f in result.suppressed
            if not f.justification.strip()]
    assert not bare, ("suppressions without a justification:\n"
                      + "\n".join(bare))


def test_gate_runner_agrees():
    """tools/lint_gate.py emits the same verdict the in-process API
    gives (the CI entry point must never drift from the tests)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "lint_gate", os.path.join(os.path.dirname(PKG), "tools",
                                  "lint_gate.py"))
    gate = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gate)
    rec = gate.gate_record()
    assert rec["ok"] is True
    assert rec["findings"] == []
    assert rec["suppressions"] >= 1  # the documented deliberate sites
    assert all(s["justification"] for s in rec["suppression_sites"])


def test_fault_point_inventory_in_sync():
    """Pinned both-ways sync between the faultinject INVENTORY and the
    engine's fault_point call sites (the seam pass's model, asserted
    directly so a pass regression cannot mask an inventory drift)."""
    import ast

    from cloudberry_tpu.utils.faultinject import INVENTORY

    sites = set()
    for dirpath, dirnames, filenames in os.walk(PKG):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(dirpath, fn)) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if isinstance(node, ast.Call) and node.args \
                        and isinstance(node.args[0], ast.Constant) \
                        and isinstance(node.args[0].value, str):
                    fname = getattr(node.func, "id",
                                    getattr(node.func, "attr", ""))
                    if fname == "fault_point":
                        sites.add(node.args[0].value)
    assert sites == set(INVENTORY), (
        f"missing from INVENTORY: {sorted(sites - set(INVENTORY))}; "
        f"stale in INVENTORY: {sorted(set(INVENTORY) - sites)}")


def test_witness_order_covers_discovered_locks():
    """Every lock the static pass discovers in the concurrent-core
    modules either has a declared witness rank or is a known
    per-object/private lock — the declared order cannot silently rot
    as modules grow."""
    from cloudberry_tpu.lint.config import witness_ranks

    result = _result()
    ranks = witness_ranks()
    resolved = 0
    for name, (_f, _l, _kind, alias) in result.lock_sites.items():
        if name in ranks or (alias and alias in ranks):
            resolved += 1
    # the declared order must cover a healthy majority of real sites
    assert resolved >= 15, (resolved, sorted(result.lock_sites))


def test_planprops_rule_table_exhaustive_live():
    """The live mirror of the planprops pass: plan/verify.py RULES
    covers every PlanNode subclass actually importable from the
    package, both ways — so the static rule and the runtime table can
    never drift apart."""
    from cloudberry_tpu.exec.tiled_plan import _AccLeaf  # noqa: F401
    from cloudberry_tpu.plan import nodes as N
    from cloudberry_tpu.plan.verify import RULES

    def subclasses(cls):
        for sub in cls.__subclasses__():
            yield sub
            yield from subclasses(sub)

    live = {c.__name__ for c in subclasses(N.PlanNode)}
    assert live <= set(RULES), sorted(live - set(RULES))
    assert set(RULES) <= live, sorted(set(RULES) - live)


def test_planprops_mode_tables_agree_live():
    from cloudberry_tpu.exec.recovery import REPLACEABLE
    from cloudberry_tpu.exec.tiled import CHECKPOINT_MODES

    assert set(CHECKPOINT_MODES) == set(REPLACEABLE)
