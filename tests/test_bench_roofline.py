"""bench.py roofline context (VERDICT r5 item 8): every emitted speedup
carries a bytes-scanned ÷ HBM-bandwidth denominator — the peak is the
caller's, looked up by ``device_kind`` on a chip — and, since the
packed-wire motion PR, an interconnect record (collective launches +
bytes-on-wire per query at the 8-segment plan shape)."""

import pytest

import bench


def test_static_scan_bytes_scales_with_sf():
    b1 = bench.static_scan_bytes("q1", 1.0)
    b01 = bench.static_scan_bytes("q1", 0.1)
    # q1 scans 44 bytes per lineitem row
    assert b1 == int(6_001_215 * 44)
    assert abs(b01 * 10 - b1) / b1 < 1e-6
    assert bench.static_scan_bytes("q99", 1.0) is None


def test_hbm_peak_is_keyed_by_device_kind():
    # v5e: 819 GB/s (Google Cloud documentation, "TPU v5e"); a kind with
    # no published peak in the table is an error, never a default
    assert bench.hbm_gbps("TPU v5 lite") == 819.0
    with pytest.raises(KeyError, match="no published HBM bandwidth"):
        bench.hbm_gbps("cpu")


def test_roofline_context_static_and_measured():
    # static shape: denominator only (no wall times)
    rep = bench.roofline_context(["q1", "q3"], 1.0, 819.0)
    assert rep["hbm_gbps_nominal"] == 819.0
    assert set(rep["per_query"]) == {"q1", "q3"}
    for rec in rep["per_query"].values():
        assert rec["bytes_scanned"] > 0
        assert "hbm_frac" not in rec
    # measured shape: bytes + wall time → achieved GB/s + HBM fraction
    live = bench.roofline_context(
        ["q1"], 1.0, 819.0, bytes_by_q={"q1": 2_000_000_000},
        wall_by_q={"q1": 0.01})
    rec = live["per_query"]["q1"]
    assert rec["scan_gbps"] == 200.0
    assert rec["hbm_frac"] == round(200.0 / 819.0, 4)


def test_interconnect_context_records_shuffle_volume():
    """The bench JSON's interconnect record: metadata-only planning at 8
    segments totals every motion's launches and bytes-on-wire, packed vs
    per-column — packed must need fewer launches AND fewer bytes."""
    import cloudberry_tpu as cb
    from tools.tpchgen import load_tpch

    s = cb.Session()
    load_tpch(s, sf=0.01, seed=3, tables=["lineitem", "orders",
                                          "customer", "nation"])
    ic = bench.interconnect_context(s, ["q3", "q10"], nseg=8)
    assert ic["n_segments"] == 8
    for qn in ("q3", "q10"):
        rec = ic["per_query"][qn]
        assert rec["motions"] >= 1
        assert rec["launches_packed"] == rec["motions"]
        assert rec["launches_percol"] > rec["launches_packed"]
        # same bucket shapes in this static accounting, so packed pays
        # only the word-alignment overhead — pinned small; the real
        # padded-bytes win (adaptive rung vs worst-case static buckets)
        # is measured live by tools/ic_bench.py --format packed|percol
        assert 0 < rec["wire_bytes_packed"] \
            < 1.25 * rec["wire_bytes_percol"]
    # the metadata pass must not have materialized 8-segment shard
    # arrays on the 1-segment session (counts-only planning fast path)
    assert not any(k.endswith("@8") for k in s._shard_cache)
