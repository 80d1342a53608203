"""The result line, and the check it has to pass before it is printed.

The last line of standard output is one JSON object with the keys
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``compared``: every number the
comparison read beside its limit. Nothing else goes on it. A line that
would break the contract is not printed: the fault is named and the run
exits non-zero.
"""

from __future__ import annotations

import json
import math

DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")


def _number(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) \
        and math.isfinite(x)


def problems(line: dict, metrics: list, trace: bool,
             platform: str | None = "tpu", chips: int | None = None) -> list:
    """Why ``line`` is not a result of a cell that has to report
    ``metrics`` (entries of BENCHMARK.json); [] when it is one.
    ``platform=None`` leaves the platform unchecked (rehearsals)."""
    bad = []
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        if key not in line:
            bad.append(f"key {key!r} is missing")
    if bad:
        return bad
    if not isinstance(line["correct"], bool):
        bad.append("correct is not true or false")
    for key in ("attempted", "failed"):
        if not isinstance(line[key], int) or line[key] < 0:
            bad.append(f"{key} is not a count")
    got = line["metrics"]
    for m in metrics:
        entry = got.get(m["name"])
        if entry is None:
            bad.append(f"metric {m['name']!r} is missing")
        elif not _number(entry.get("value")):
            bad.append(f"metric {m['name']!r} has no finite value")
        elif entry.get("unit") != m["unit"]:
            bad.append(f"metric {m['name']!r} has unit {entry.get('unit')!r}"
                       f", not {m['unit']!r}")
    known = {m["name"] for m in metrics}
    for name in got:
        if name not in known:
            bad.append(f"metric {name!r} is not one of this cell's")
    dev = line["device"]
    for key in DEVICE_KEYS:
        if key not in dev:
            bad.append(f"device.{key} is missing")
    if platform is not None and dev.get("platform") != platform:
        bad.append(f"device.platform is {dev.get('platform')!r}, "
                   f"not {platform!r}")
    if chips is not None and dev.get("count") != chips:
        bad.append(f"device.count is {dev.get('count')!r}, not {chips}")
    if "memory_peak_bytes" in dev and not _number(dev["memory_peak_bytes"]):
        bad.append("device.memory_peak_bytes is not a number")
    if trace:
        busy, window = dev.get("busy_s"), dev.get("window_s")
        if not _number(busy) or not _number(window):
            bad.append("device.busy_s / device.window_s are missing")
        elif not 0 < busy <= window:
            bad.append(f"device.busy_s {busy} is not above 0 and at most "
                       f"window_s {window}")
        bd = line.get("breakdown")
        if bd is not None:
            for key in ("device_ops", "idle_gaps"):
                rows = bd.get(key, [])
                if len(rows) > 10 or not all(
                        len(r) == 2 and isinstance(r[0], str)
                        and _number(r[1]) for r in rows):
                    bad.append(f"breakdown.{key} is not at most 10 "
                               "[name, seconds] pairs")
    return bad


def build(correct: bool, attempted: int, failed: int, values: dict,
          metrics: list, device: dict, compared: dict,
          breakdown: dict | None = None) -> dict:
    """The line's object; ``values`` holds only the metrics that had
    something to read, each as measured with all its digits."""
    units = {m["name"]: m["unit"] for m in metrics}
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed),
            "metrics": {k: {"value": v, "unit": units[k]}
                        for k, v in values.items() if k in units},
            "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    line["compared"] = compared
    return line


def dumps(line: dict) -> str:
    return json.dumps(line, separators=(", ", ": "))
