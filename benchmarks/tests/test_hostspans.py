"""Tests of what PR 26 adds to the benchmark: the idle-time table by host
stage (``harness/hostspans.py``) on a synthetic profile with two threads
and known gaps, and both cells' CPU rehearsals with ``--trace 1`` printing
every new per-layer metric.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import math
from types import SimpleNamespace as NS

import pytest

from benchmarks.harness import cell as C, hostspans, lastline
from benchmarks.tests.test_benchmark import (BM, CELLS, R, _args,  # noqa: F401
                                             _profile)

NEW = ("request_ms", "outside_server_ms", "host_offcpu_ms", "admit_ms",
       "bind_ms", "inputs_ms", "dispatch_ms", "device_wait_ms", "fetch_ms",
       "part_read_ms", "feed_wait_ms", "h2d_ms", "tile_step_ms",
       "finalize_ms", "idle_attributed_pct")


def _thread(name, spans):
    return NS(name=name, events=[
        NS(name="cbtpu:" + n, start_ns=a, duration_ns=b - a, stats=stats)
        for n, a, b, *rest in spans
        for stats in [list(rest[0].items()) if rest else []]])


def _two_threads():
    prof = _profile([("fusion.a", 2_000, 1_000), ("fusion.b", 5_000, 500),
                     ("fusion.c", 8_000, 500)])
    prof.planes[0].lines += [
        _thread("worker-1", [
            ("launch", 1_500, 3_300), ("inputs", 1_500, 1_900),
            ("dispatch", 1_900, 2_050), ("device-wait", 2_050, 3_000),
            ("fetch", 3_000, 3_300), ("render", 3_300, 3_400),
            ("wire-in", 7_000, 7_100), ("plan", 7_100, 7_600),
            ("launch", 7_600, 8_700), ("dispatch", 7_900, 8_050),
            ("device-wait", 8_050, 8_500), ("fetch", 8_500, 8_700)]),
        _thread("worker-2", [
            ("launch", 4_000, 5_800), ("inputs", 4_000, 4_800),
            ("dispatch", 4_800, 5_050), ("device-wait", 5_050, 5_500),
            ("fetch", 5_500, 5_800)])]
    return prof


def _ns(table):
    return {k: round(v * 1e9) for k, v in table["by_stage"].items()}


def test_idle_table_of_two_threads_with_known_gaps():
    rule = C.read_json(C.BENCH, "planes.json")["tpu"]
    table = hostspans.attribute(_two_threads(), rule, streams=2)
    # gaps inside the mark [1000, 9000): 1000-2000, 3000-5000, 5500-8000,
    # 8500-9000: 6000 ns idle, 2000 ns busy. Each instant is halved
    # between the two streams: the innermost open span of a worker that
    # has one, between-requests for a stream that has none.
    assert _ns(table) == {"between-requests": 4_300, "inputs": 600,
                          "dispatch": 200, "fetch": 400, "render": 50,
                          "wire-in": 50, "plan": 250, "launch": 150}
    assert table["idle_s"] == pytest.approx(6_000 / 1e9)
    assert hostspans.attributed_pct(table) == pytest.approx(100 * 17 / 60)
    assert table["threads"] == 2 and table["compiles"] == 0
    longest = table["gaps"][0]
    assert longest[0] == pytest.approx(2_500 / 1e9)
    assert round(longest[1]["between-requests"] * 1e9) == 1_850
    # one stream: the second worker's spans share each instant instead
    one = hostspans.attribute(_two_threads(), rule, streams=1)
    assert one["idle_s"] == pytest.approx(6_000 / 1e9)
    assert _ns(one)["between-requests"] == 500 + 600 + 1_200 + 300


def test_a_compile_mark_is_laid_out_backwards_from_its_end():
    prof = _two_threads()
    # worker-2 compiled for 700 ns inside its inputs span, up to 4700
    prof.planes[0].lines[-1].events.append(NS(
        name="cbtpu:compile", start_ns=4_700, duration_ns=0,
        stats=[("seconds", 700 / 1e9), ("statement_id", 9)]))
    rule = C.read_json(C.BENCH, "planes.json")["tpu"]
    table = hostspans.attribute(prof, rule, streams=2)
    assert _ns(table)["compile"] == 350 and _ns(table)["inputs"] == 250
    assert table["compiles"] == 1
    assert table["idle_s"] == pytest.approx(6_000 / 1e9)


def test_feed_wait_is_told_apart_by_the_reader_thread():
    prof = _profile([("step", 2_000, 1_000), ("step", 8_000, 500)])
    sid = [("statement_id", 7)]
    prof.planes[0].lines += [
        _thread("statement", [("launch", 1_000, 9_000, dict(sid)),
                              ("feed-wait", 3_000, 7_000, dict(sid)),
                              ("tile-step", 7_000, 8_200, dict(sid))]),
        _thread("reader", [("part-read", 2_500, 6_000, dict(sid))])]
    rule = C.read_json(C.BENCH, "planes.json")["tpu"]
    table = hostspans.attribute(prof, rule, streams=1)
    # idle: 1000-2000 (launch), 3000-8000, 8500-9000 (launch)
    assert _ns(table) == {"launch": 1_500, "feed-wait:part-read": 3_000,
                          "feed-wait:assembly": 1_000, "tile-step": 1_000}
    assert hostspans.attributed_pct(table) == pytest.approx(100.0)


def test_a_profile_without_host_spans_attributes_nothing():
    rule = C.read_json(C.BENCH, "planes.json")["tpu"]
    table = hostspans.attribute(_profile([("fusion.a", 2_000, 1_000)]),
                                rule, streams=2)
    assert _ns(table) == {"between-requests": 7_000}
    assert hostspans.attributed_pct(table) == 0.0
    assert hostspans.attribute(NS(planes=[]), rule, 2) is None


def test_every_new_metric_has_an_entry_and_a_reader():
    by_name = {m["name"]: m for m in BM["per_layer"]}
    for base in NEW:
        mine = [n for n in by_name if n.split(".", 1)[0] == base]
        assert mine, base
        for name in mine:
            assert callable(C.reader(name))
            assert by_name[name]["source"] == (
                "device_trace" if base == "idle_attributed_pct"
                else "program_span")


@pytest.fixture(scope="module")
def traced():
    return {name: R.run(_args(name, 1, seed=2**31 + 26)) for name in CELLS}


@pytest.mark.parametrize("name", CELLS)
def test_traced_rehearsal_prints_every_new_metric(traced, name):
    code, line = traced[name]
    assert code == 3 and line["correct"] is True
    cell = C.Cell(name)
    assert lastline.problems(line, cell.metrics(True), True,
                             platform=None) == []
    got = {k.split(".", 1)[0]: v["value"] for k, v in line["metrics"].items()}
    mine = {m["name"].split(".", 1)[0] for m in cell.per_layer}
    assert mine & set(NEW) and mine <= set(got)
    for base in mine & set(NEW):
        assert math.isfinite(got[base]) and got[base] >= 0.0, base
    # the program was inside every stage its cell's metrics name
    assert got["request_ms"] > 0 and got["host_offcpu_ms"] > 0
    assert 0.0 < got["idle_attributed_pct"] <= 100.0
    tiled = "part_read_ms" in mine
    for base in (("part_read_ms", "h2d_ms", "tile_step_ms", "finalize_ms")
                 if tiled else ("admit_ms", "bind_ms", "inputs_ms",
                                "dispatch_ms", "fetch_ms")):
        assert got[base] > 0, base
    # children inside their parent, stages inside the request
    kids = ("feed_wait_ms", "h2d_ms", "tile_step_ms", "finalize_ms") \
        if tiled else ("inputs_ms", "dispatch_ms", "device_wait_ms",
                       "fetch_ms")
    assert sum(got[k] for k in kids) <= got["launch_ms"] * 1.001
    assert got["launch_ms"] + got["plan_ms"] + got["render_ms"] \
        <= got["request_ms"] * 1.001
