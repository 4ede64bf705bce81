"""Test configuration: force the CPU backend with 8 virtual devices so
multi-chip sharding tests run on any host (SURVEY.md §4 lesson — multi-chip
parity is a first-class CI test here, unlike the reference)."""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The package places a persistent compilation cache at <checkout>/.jax_cache
# (lightgbmv1_tpu/__init__.py).  The CPU suite turns the cache off — through
# the environment, so the worker processes the tests spawn inherit it:
# tier-1 must not write into the checkout, and XLA:CPU entries must not be
# there for the chip tool to copy onto a machine with other CPU features.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest

# Tier-1 wall-budget accounting (tools/tier1_budget.py): when
# LGBMV1_T1_DURATIONS names a file, every test phase's duration is
# appended as one JSON line, so the budget tool can project the tier-1
# wall against the driver's 870 s budget and rank the worst offenders
# without re-running the suite.
_DUR_PATH = os.environ.get("LGBMV1_T1_DURATIONS")


def pytest_runtest_logreport(report):
    if _DUR_PATH:
        import json

        with open(_DUR_PATH, "a") as fh:
            fh.write(json.dumps({
                "nodeid": report.nodeid, "when": report.when,
                "duration": round(report.duration, 4),
                "outcome": report.outcome,
            }) + "\n")


# Tier-1 wall-budget re-mark table (ISSUE 16 session): the tier-1 verify
# command runs under a HARD 870 s timeout, and this session's container
# measured the full not-slow suite at ~1190 s of test time (~1.5x the
# per-test durations earlier sessions recorded — same code, slower box:
# an A/B with the session's diff stashed reproduced the slowdown on
# untouched tests).  Per tools/tier1_budget.py's remedy the worst
# offenders move to `slow` — they still run in driver captures and any
# `-m ''`/full invocation — keeping at least one arm of every parity
# family in tier-1 (kept deliberately: two_process_data_parallel,
# bit_exact_resume_binary, fused_bookkeeping[params0], the hierarchical
# 4-shard parity pin).  Centralized here instead of 45 scattered
# decorators so a future session on a faster box can re-promote them by
# deleting entries.
_T1_REMARK_SLOW = frozenset((
    "test_api.py::test_cv",
    "test_aux.py::test_auc_mu_metric",
    "test_categorical.py::test_categorical_beats_ordinal",
    "test_categorical.py::test_levelwise_categorical",
    "test_cegb.py::test_split_penalty_prunes",
    "test_checkpoint.py::test_checkpoint_file_sniff_and_validate",
    "test_cli.py::test_cli_snapshot_auto_resume",
    "test_drift.py::test_serve_drift_follows_version_swap",
    "test_efb.py::test_efb_data_parallel_parity",
    "test_efb.py::test_efb_training_parity[leafwise_serial]",
    "test_efb.py::test_efb_training_parity[levelwise]",
    "test_forced_and_earlystop.py::test_forced_splits",
    "test_forced_and_earlystop.py::test_forced_splits_levelwise",
    "test_forced_and_earlystop.py::test_pred_early_stop_multiclass",
    "test_golden_compat.py::test_our_model_text_parses_reference_fields",
    "test_int8sr.py::test_int8sr_bit_reproducible",
    "test_missing.py::test_zero_as_missing",
    "test_monotone.py::test_intermediate_mode_enforced_and_tighter",
    "test_monotone.py::test_monotone_constraints_enforced[levelwise]",
    "test_multihost.py::test_two_process_sharded_storage",
    "test_native_parser.py::test_native_predictor_parity",
    "test_parallel.py::"
    "test_reduce_scatter_vs_allreduce_vs_serial_bit_identical[2]",
    "test_parallel.py::test_voting_selection_non_degenerate",
    "test_params.py::test_dart_uniform_and_weighted_drop",
    "test_params.py::test_extra_seed_changes_extra_trees",
    "test_params.py::test_histogram_pool_size_pool_free_mode",
    "test_partition_grower.py::test_partition_matches_masked[params0]",
    "test_partition_grower.py::test_partition_matches_masked[params3]",
    "test_phase_attrib.py::test_fused_bookkeeping_bit_identical[params1]",
    "test_phase_attrib.py::test_fused_bookkeeping_bit_identical[params2]",
    "test_ranking.py::test_bucketed_matches_oracle[True]",
    "test_serve.py::test_degraded_truncation_rounds_to_iteration_boundary",
    "test_sklearn_api.py::test_classifier_multiclass",
    "test_train.py::test_dart_fused_matches_host_path",
    "test_train.py::test_dart_predict_matches_scores",
    "test_wave_bucket.py::test_bucketed_rounds_match_single_bucket[params1]",
    "test_wave_fused.py::test_fused_parity_monotone_l1",
    "test_wave_grower.py::test_valid_row_routing_matches_tree_walk",
    "test_wave_grower.py::test_wave1_matches_sequential[params0]",
    "test_wave_grower.py::test_wave1_matches_sequential[params1]",
    "test_wave_grower.py::test_wave1_matches_sequential[params3]",
    "test_wave_grower.py::test_wave_quality_parity",
    "test_wave_grower.py::test_wave_size_variants_same_quality",
    "test_wave_pipeline.py::test_pipeline_bit_parity_binary_bagging_ff",
    "test_wave_pipeline.py::test_pipeline_bit_parity_dart",
    # second tranche: the first re-mark's full run still measured 840.9 s
    # wall (in-suite inflation over summed call durations ~15%) — thin
    # against the 870 s timeout, so the next offenders move too
    "test_wave_fused.py::test_fused_parity_nan_missing",
    "test_split_features.py::test_interaction_constraints_respected"
    "[levelwise]",
    "test_cegb.py::test_coupled_penalty_avoids_expensive_features",
    "test_continue.py::test_continue_training_matches_straight_run",
    "test_phase_attrib.py::test_fused_bookkeeping_valid_routing_identical",
    "test_aux.py::test_binary_dataset_cache_round_trip",
    "test_chaos.py::test_poisoned_gradients_detected_and_clamped",
    "test_xla_obs.py::test_predictor_lru_eviction_recompile_counted_once",
    "test_model_quality.py::test_registry_meta_importance_and_shift",
    "test_model_quality.py::test_quality_snapshot_multiclass_iterations",
    "test_wave_fused.py::test_fused_pool_free_parity",
    "test_train.py::test_weights_change_model",
    "test_parallel.py::test_parallel_matches_serial_binary[feature]",
    # third tranche (PR 18): the packed-bin additions (~44 s) put the
    # measured wall at 865 s / projected 853.5 s — over the 95% bar —
    # so the next tier1_budget offenders move, again one arm per family
    # kept (three_way_parity binary/lambdarank/dart, the golden
    # zero_as_missing + regression training parities, the other
    # publish-rejection and wave-loop-fallback reasons)
    "test_params.py::test_objective_seed_changes_rank_xendcg",
    "test_predict_engine.py::test_three_way_parity_multiclass",
    "test_wave_fused.py::test_wave_loop_ffbynode_falls_back_with_reason",
    "test_golden_compat.py::test_max_delta_step_training_parity",
    "test_serve_faults.py::test_publish_rejects_nan_leaves",
))


def pytest_collection_modifyitems(config, items):
    for item in items:
        nid = item.nodeid
        if nid.startswith("tests/"):
            nid = nid[len("tests/"):]
        if nid in _T1_REMARK_SLOW:
            item.add_marker(pytest.mark.slow)


@pytest.fixture
def rng():
    return np.random.RandomState(42)


def make_binary_problem(n=1500, f=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    logit = 1.5 * X[:, 0] - X[:, 1] + 0.8 * X[:, 2] * X[:, 3] + 0.5 * np.sin(X[:, 4])
    y = (logit + rng.randn(n) * 0.4 > 0).astype(np.float64)
    return X, y


def make_regression_problem(n=1500, f=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = 2.0 * X[:, 0] - X[:, 1] + 0.5 * X[:, 2] * X[:, 3] + rng.randn(n) * 0.1
    return X, y
