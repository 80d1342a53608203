"""Test harness: 8 virtual CPU devices — the demo-cluster analog.

The reference tests multi-node behavior with N postmasters on localhost
(gpMgmt/demo, SURVEY.md §4.2); we test multi-chip behavior with N virtual XLA
CPU devices. Must run before jax initializes.
"""

import os

# Tests always run on the CPU, whatever the machine holds: the env var
# covers child processes, and jax.config below covers this one even when
# something imported jax before conftest (no backend is initialized yet).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    # 'slow' marks the opt-out tier: tier-1 runs `-m 'not slow'` under a
    # hard wall-clock cap (ROADMAP.md); slow tests run in the full suite
    config.addinivalue_line(
        "markers", "slow: excluded from the capped tier-1 run")


# Duration-based re-tiering (tier-1 overran its 870s cap): the slowest
# tests whose coverage a cheaper tier-1 sibling retains move to the slow
# tier — single-segment variants stay for every marked dist8 case, q3
# stays for the marked q10 packed-parity pins, the memo module keeps its
# behavior tests while its perf-property searches move, and the spill
# modules keep one representative of each recognized spine. Node-id
# suffixes so fixture-parametrized products can be tiered individually.
# One executable a mode serves both segment layouts (exec/tiled.py), so
# each mode keeps a tier-1 case on the mesh: the dist8 join-group, global
# and colocated aggregates and the top-N OFFSET case stay tier-1, beside
# test_spill_dist.py's small sort and window cases.
_SLOW_TIER = (
    "test_spill_dist.py::test_dist_merge_overflow_grows_accumulator",
    "test_spill_dist.py::test_dist_tiled_topn_matches_in_memory",
    "test_spill_sort_window.py::test_window_spill_matches_in_memory"
    "[dist8]",
    "test_spill_sort_window.py::test_skewed_redistribute_grows_bucket",
    "test_spill.py::test_tiled_spine_expansion_join",
    "test_packed_motion.py::test_tpch_packed_parity_pinned[q10-seg1]",
    "test_packed_motion.py::test_tpch_packed_parity_pinned[q10-seg8]",
    "test_memo.py::test_memo_region_survives_out_of_grammar_sibling",
    "test_memo.py::test_memo_equivalence_random_queries",
    "test_memo.py::test_memo_lookahead_beats_greedy_threshold",
    "test_memo.py::test_joint_order_beats_row_dp",
    "test_tpcds_round5.py::test_tpcds_round5[dist8-q59]",
    "test_tpcds_round5.py::test_tpcds_round5[dist8-q38]",
    "test_tpcds_round5.py::test_tpcds_round5[dist8-q74]",
    "test_tpcds_round5.py::test_tpcds_round5[dist8-q33]",
    "test_tpcds.py::test_tpcds_distributed[q17]",
    "test_tpcds.py::test_tpcds_distributed[q25]",
    "test_tpcds.py::test_tpcds_distributed[q29]",
    # round 5 (PR 5 margin): more dist8 variants whose single-segment
    # sibling stays tier-1; the tiled-dist q5/q9 sweep keeps its
    # single-segment twin (test_spill.py::test_tpch_q5_q9_tiled), and
    # digest-parity q5-dist8 stays covered by the slow full sweep
    # (test_join_filter.py::test_tpch_digest_parity_full_sweep) while
    # q3/q10 dist8 + the whole single-segment subset remain tier-1.
    "test_spill_dist.py::test_tpch_q5_q9_tiled_distributed",
    "test_cte.py::test_q15_as_cte[dist8]",
    "test_cte.py::test_shared_cte_self_join[dist8]",
    "test_join_filter.py::test_tpch_digest_parity_dist8[q5]",
    "test_window_longtail.py::test_range_offset_min_max[dist8]",
    "test_window_longtail.py::test_rows_frame_min_max[dist8]",
    "test_window_longtail.py::test_range_offset_can_be_empty[dist8]",
    "test_window_longtail.py::test_range_offset_month_year_interval"
    "[dist8]",
    "test_spill_sort_window.py::test_external_sort_matches_in_memory"
    "[dist8]",
    "test_cte.py::test_basic_cte[dist8]",
    "test_grouping_sets.py::test_cube[dist8]",
    "test_setop_all.py::test_running_extreme_null_never_beats_dtype_extreme"
    "[seg8]",
    "test_dqa.py::test_mixed_distinct_and_plain[dist8]",
    "test_spill_sort_window.py::test_huge_offset_limit_falls_back_to_sort"
    "[dist8]",
    "test_window_longtail.py::test_range_offset_first_last_value[dist8]",
    "test_tpcds.py::test_tpcds_distributed[q65]",
    "test_tpcds.py::test_tpcds_distributed[q98]",
    "test_distributed.py::test_tpch_distributed[q2]",
    "test_distributed.py::test_tpch_distributed[q8]",
    # round 7 (PR 7 margin): the single-node kill matrix + degraded-dist
    # recovery tests stay tier-1 while the dist8 kill matrix moves; the
    # plain dist topn stays covered slow-tier; digest-parity q5 single
    # rides the slow full sweep like q5 dist8 already does (q3/q10 both
    # stay).
    "test_recovery.py::test_tiled_dist_kill_matrix",
    "test_join_filter.py::test_tpch_digest_parity_single[q5]",
    # round 8 (PR 8 margin — lint gate + witness fixtures + taxonomy
    # suite joined tier-1): more dist8/heavy variants whose cheaper
    # sibling stays — dist degraded-resume keeps the colocated-declines
    # dist8 case + the single-node resume matrix; the dist statement-
    # cache test keeps its single-node twin in test_spill.py;
    # digest-parity q10-dist8 keeps q3-dist8 + the q10
    # single-seg subset; lead-offset/packed-redistribute/generic-q3
    # keep their single/seg1 twins; four more TPC-H dist8 queries keep
    # their test_tpch_query single-seg siblings (q2/q8 precedent); DS
    # q86/q60 keep their single-seg runs.
    "test_recovery.py::test_dist_degraded_resume",
    "test_spill_dist.py::test_dist_tiled_statement_cache_reuses_runner",
    "test_join_filter.py::test_tpch_digest_parity_dist8[q10]",
    "test_window_longtail.py::test_lead_offset_and_default[dist8]",
    "test_packed_motion.py::test_packed_matches_percol_all_motion_kinds"
    "[redistribute-seg8]",
    "test_generic_parity.py::test_subset_parity_dist8[q3]",
    "test_distributed.py::test_tpch_distributed[q7]",
    "test_distributed.py::test_tpch_distributed[q13]",
    "test_distributed.py::test_tpch_distributed[q20]",
    "test_distributed.py::test_tpch_distributed[q21]",
    "test_tpcds.py::test_tpcds_distributed[q86]",
    "test_tpcds_round5.py::test_tpcds_round5[dist8-q60]",
    # round 17 (feedback/adaptive tests join tier-1): the five worst
    # remaining offenders (~72s) move — the two-host cluster parity and
    # host-rung/hier bit-identity sweeps keep their cheaper siblings
    # (test_hier_queries q3 parity, host-combine stamp parity, and the
    # ic_bench two-level smoke all stay tier-1; multihost keeps its
    # worker-level transport tests; degraded-progress monotonicity
    # keeps the single-kill recovery matrix already in tier 1).
    "test_multihost.py::test_two_host_cluster_matches_single_host",
    "test_hier_motion.py::test_hier_queries_bit_identical",
    "test_hier_motion.py::test_tiled_dist_hier_parity",
    "test_hier_motion.py::test_host_rung_overflow_promotes_and_retries",
    "test_capacity_forensics.py::test_progress_monotone_degraded_8_to_7",
    # round 18 (write-path suite joins tier-1): the two consumers of the
    # module-scoped adaptive_expected fixture move together (the fixture
    # build alone is ~39s; moving only one test would just shift it to
    # the other) — the feedback plane keeps its tier-1 coverage via the
    # fold/persistence/invalidation tests plus the rung-downgrade and
    # bench-counter paths; the expand-cutover checkpoint-resume test
    # keeps its cheaper cutover siblings (stale-nseg, epoch-pin,
    # under-load cutover) in tier 1.
    "test_feedback.py::test_midstatement_adaptive_replan",
    "test_feedback.py::test_fault_skip_suppresses_adaptation",
    "test_topology.py::test_checkpointed_statement_resumes_across_expand_cutover",
    # round 19 (crash-torture + iofault suites join tier-1): more
    # dist8/heavy variants whose cheaper sibling stays — seven more
    # TPC-H dist8 queries keep their test_tpch_query single-seg
    # siblings (q2/q8 precedent), DS distributed/round5 dist8 cases
    # keep their single-seg runs, digest-parity q3-dist8 now rides the
    # slow full sweep like q5/q10 already do (the whole single-seg
    # digest subset minus q5 stays tier-1), packed-parity q3-seg8
    # keeps q3-seg1.
    "test_join_filter.py::test_tpch_digest_parity_dist8[q3]",
    "test_tpcds_round5.py::test_tpcds_round5[dist8-q43]",
    "test_tpcds_round5.py::test_tpcds_round5[dist8-q94]",
    "test_tpcds_round5.py::test_tpcds_round5[dist8-q97]",
    "test_tpcds_round5.py::test_tpcds_round5[dist8-q16]",
    "test_tpcds_round5.py::test_tpcds_round5[dist8-q56]",
    "test_distributed.py::test_tpch_distributed[q9]",
    "test_distributed.py::test_tpch_distributed[q15]",
    "test_distributed.py::test_tpch_distributed[q10]",
    "test_distributed.py::test_tpch_distributed[q18]",
    "test_distributed.py::test_tpch_distributed[q17]",
    "test_distributed.py::test_tpch_distributed[q22]",
    "test_distributed.py::test_tpch_distributed[q11]",
    "test_tpcds.py::test_tpcds_distributed[q36]",
    "test_tpcds.py::test_tpcds_distributed[q20]",
    "test_tpcds.py::test_tpcds_distributed[q42]",
    "test_tpcds.py::test_tpcds_distributed[q27]",
    "test_tpcds.py::test_tpcds_distributed[q55]",
    "test_tpcds.py::test_tpcds_distributed[q12]",
    "test_packed_motion.py::test_tpch_packed_parity_pinned[q3-seg8]",
    # round 20 (windowed tile-dispatch suite joins tier-1, ~75s): more
    # dist8 TPC-H/DS queries whose single-seg twins stay tier-1 (the
    # q2/q8 precedent continues), and the windowed suite's own heaviest
    # dist8 case — the window-mode spill query — rides slow while its
    # three dist8 siblings (agg/topn/sort) and the full single-node
    # W∈{1,2,4} matrix stay tier-1.
    "test_distributed.py::test_tpch_distributed[q16]",
    "test_distributed.py::test_tpch_distributed[q19]",
    "test_distributed.py::test_tpch_distributed[q12]",
    "test_tpcds.py::test_tpcds_distributed[q21]",
    "test_tpcds.py::test_tpcds_distributed[q52]",
    "test_tilepipe.py::test_window_bit_identical_dist8[window]",
)


# Environment skips, PINNED (ISSUE 19 triage): tests whose only failure
# mode is a dependency this image does not ship skip with the reason
# spelled out instead of failing — tier-1 signal must be clean so a real
# regression (e.g. in the crash matrix) is never lost in known noise.
# The pin is the explicit node-id list: only THESE tests may skip for
# the named module, and they run normally wherever the module exists.
_ENV_SKIPS = (
    ("cryptography", (
        "test_tde.py::test_roundtrip_under_encryption",
        "test_tde.py::test_no_plaintext_on_disk",
        "test_tde.py::test_wrong_or_missing_key_refused",
        "test_dirtable.py::test_directory_table_tde",
    )),
)


def _module_missing(name: str) -> bool:
    import importlib.util

    try:
        return importlib.util.find_spec(name) is None
    except (ImportError, ValueError):
        return True


def pytest_collection_modifyitems(config, items):
    env_skips = {}
    for mod, nodeids in _ENV_SKIPS:
        if _module_missing(mod):
            mark = pytest.mark.skip(
                reason=f"needs the {mod!r} package (not in this image)")
            for nid in nodeids:
                env_skips[nid] = mark
    for item in items:
        if item.nodeid.endswith(_SLOW_TIER):
            item.add_marker(pytest.mark.slow)
        for nid, mark in env_skips.items():
            if item.nodeid.endswith(nid):
                item.add_marker(mark)


@pytest.fixture
def session():
    import cloudberry_tpu as cb

    return cb.Session()
