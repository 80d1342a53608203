"""chip_smoke.py cannot rot between chip runs: the whole script is driven
here on the CPU (on-chip-measurement guide §2, rehearsals 1 and 2), where
every phase must run and check its answers and the verdict must still be
``"ok": false`` with a non-zero exit — BECAUSE the platform is not ``tpu``;
the script has no option that waives that."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(tmp_path, *args, devices=1, cwd=REPO, script=SMOKE):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    p = subprocess.run(
        [sys.executable, script, "--workdir", str(tmp_path / "work"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=900)
    return p, p.stdout.strip().splitlines()


def _verdict_is_a_cpu_failure(p, lines, count):
    assert p.returncode != 0, p.stdout[-2000:]
    verdict = json.loads(lines[-1])
    assert verdict == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": count}}
    assert "[device] FAILED" in p.stdout
    # the platform is the ONLY reason: every other phase passed
    assert "[verdict] failed phases: device" in lines


def test_one_chip_run_on_cpu_runs_every_phase_and_fails(tmp_path):
    p, lines = _run(tmp_path, "--sf", "0.01", "--with-join")
    _verdict_is_a_cpu_failure(p, lines, 1)
    out = p.stdout
    for phase in ("load", "serve", "tiled", "check"):
        assert f"[{phase}] ok in" in out, out[-3000:]
    assert "[load] native codec: C++" in out
    for q in ("q6", "q1", "q3"):
        assert f"[check] {q}: equals the oracle" in out
    assert "equals the one-shot answer bit for bit" in out
    # repeat sends compiled nothing: "compiles a -> b -> b -> b"
    for ln in lines:
        if ln.startswith("[serve] q"):
            counts = ln.split("compiles ")[1].split(" -> ")
            assert counts[1] == counts[2] == counts[3], ln
    # the cache went where JAX_COMPILATION_CACHE_DIR says, and only there
    cache = str(tmp_path / "cache")
    assert f"[cache] dir {cache}, 0 entries before" in out
    assert any(n.endswith("-cache") for n in os.listdir(cache))
    # the store was temporary
    assert not os.path.exists(tmp_path / "work" / "store")


def test_four_chip_option_runs_only_the_mesh_path(tmp_path):
    p, lines = _run(tmp_path, "--sf", "0.01", "--chips", "4", "--with-join",
                    devices=4)
    _verdict_is_a_cpu_failure(p, lines, 4)
    out = p.stdout
    assert "[mesh] ok in" in out, out[-3000:]
    assert "Motion redistribute" in out and "Motion broadcast" in out
    assert "lineitem rows per device" in out
    assert out.count("equals the 1-segment answer") == 2
    assert out.count("equals the reference") == 2
    for phase in ("serve", "tiled", "check"):
        assert f"[{phase}]" not in out


def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path):
    """The contract's last case: a directory that holds chip_smoke.py and
    nothing else of the repo. It must not pass, or print a verdict."""
    lone = tmp_path / "lone"
    lone.mkdir()
    shutil.copy(SMOKE, lone / "chip_smoke.py")
    p, lines = _run(tmp_path, "--sf", "0.01", cwd=str(lone),
                    script=str(lone / "chip_smoke.py"))
    assert p.returncode != 0
    assert not any(ln.startswith("{") for ln in lines)
    assert "ModuleNotFoundError" in p.stderr
