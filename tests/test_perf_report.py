"""tools/perf_report.py renders a captured BENCH record as a markdown
report: every number greps to a record field and the output is
byte-stable.  The records these tests render are built in the tests."""

import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


# A captured-record fixture built here: the report tests must not depend
# on a record file in the repo root (the benchmark PRs replace those).
FIXTURE_NAME = "BENCH_rTEST.json"
FIXTURE = {
    "metric": "higgs-synthetic leaf-wise training throughput",
    "value": 6.571, "vs_baseline": 0.1628, "vs_ref_same_host": 1.68,
    "auc": 0.89186, "auc_iters": 20,
    "tpu_500iter_wall_s": 65.59, "tpu_500iter_auc": 0.913452,
    "ref_cpp_500iter_wall_s": 93.23, "ref_cpp_500iter_auc": 0.912632,
    "vs_ref_500iter": 1.4215,
}


@pytest.fixture(scope="module")
def report_text():
    import perf_report

    return perf_report.generate(FIXTURE, FIXTURE_NAME)


def test_report_file_matches_generator_output(tmp_path, report_text):
    """The written report IS the generator's output for the record it
    names in its header — byte-stable, nothing hand-edited in between."""
    import json

    import perf_report

    rec_path = os.path.join(tmp_path, FIXTURE_NAME)
    with open(rec_path, "w") as fh:
        json.dump({"n": 1, "parsed": FIXTURE}, fh)
    out_path = os.path.join(tmp_path, "REPORT.md")
    perf_report.main(["perf_report", rec_path, out_path])
    with open(out_path) as fh:
        on_disk = fh.read()
    m = re.search(r"from `(BENCH_r\w+\.json)`", on_disk.splitlines()[0])
    assert m, "the report must name its source BENCH record in the header"
    assert m.group(1) == FIXTURE_NAME
    regenerated = perf_report.generate(perf_report.load(rec_path),
                                       m.group(1))
    assert on_disk.strip() == regenerated.strip() == report_text.strip()


def test_headline_numbers_grep_to_record(report_text):
    import perf_report

    for key in ("value", "vs_baseline", "tpu_500iter_wall_s"):
        assert perf_report.fmt(FIXTURE[key], 4).rstrip("x") in report_text \
            or f"{FIXTURE[key]}" in report_text, key


def test_comm_guard_and_table():
    """The comm-bytes regression guard (PR 3): reduce-scatter histogram
    bytes must beat allreduce by ~D; a silent fallback to a full-width
    reduction (or an allgather of the scattered slices) must trip it."""
    sys.path.insert(0, REPO)
    from lightgbmv1_tpu.parallel.cluster import (comm_guard_ok,
                                                 comm_table_per_round)

    D, F, B, K = 8, 16, 64, 16
    rs = comm_table_per_round("data", "reduce_scatter", k=K, F=F, B=B,
                              ndev=D)
    ar = comm_table_per_round("data", "allreduce", k=K, F=F, B=B, ndev=D)
    assert rs["hist_bytes"] * D == ar["hist_bytes"]   # exact D-fold (F%D==0)
    assert ar["split_sync_bytes"] == 0                # replicated selection
    assert rs["split_sync_bytes"] > 0                 # SplitInfo sync
    assert comm_guard_ok(rs["hist_bytes"], ar["hist_bytes"], D)
    assert not comm_guard_ok(ar["hist_bytes"], ar["hist_bytes"], D)
    assert not comm_guard_ok(ar["hist_bytes"] // 2, ar["hist_bytes"], D)
    # non-divisible F pads the shard grid: bytes quantize UP, never down
    rs11 = comm_table_per_round("data", "reduce_scatter", k=K, F=11, B=B,
                                ndev=D)
    assert rs11["hist_bytes"] == rs["hist_bytes"]     # 11 -> padded to 16
    # feature-parallel never reduces histograms; voting reduces 2k
    # children of the selected set
    assert comm_table_per_round("feature", "allreduce", k=K, F=F, B=B,
                                ndev=D)["hist_bytes"] == 0
    vt = comm_table_per_round("voting", "reduce_scatter", k=K, F=F, B=B,
                              ndev=D, sel_k=F)
    assert vt["vote_bytes"] > 0


def test_prediction_section_renders_split_fields():
    """The Prediction section (PR 4) is generated from the BENCH predict_*
    fields: the engine table (native / depth-stepped walk / scan pin),
    the parse/prebin/H2D/walk/write component split, and the predict_ok
    guard all grep to record fields."""
    import perf_report

    rec = {
        "predict_rows": 1000000, "predict_n_trees": 100,
        "predict_M_rows_per_s": 1.5,
        "predict_native_compute_M_rows_per_s": 4.2,
        "predict_device_M_rows_per_s": 2.5,
        "predict_device_compute_M_rows_per_s": 61.25,
        "predict_device_scan_M_rows_per_s": 7.125,
        "predict_parse_ms": 900.5, "predict_prebin_ms": 120.25,
        "predict_h2d_ms": 8.5, "predict_walk_ms": 16.75,
        "predict_write_ms": 300.0, "predict_h2d_bytes_per_row": 28,
        "predict_cache_retraces": 0,
        "predict_parity_ok": True, "predict_ok": True,
    }
    lines = []
    perf_report.prediction_section(lines.append, rec)
    txt = "\n".join(lines)
    assert "## Prediction" in txt
    for needle in ("61.25", "7.125", "120.25", "16.75",
                   "predict_ok=True", "depth-stepped", "parity pin",
                   "0 retraces"):
        assert needle in txt, needle
    # a record with no predict capture renders the placeholder, never dies
    lines = []
    perf_report.prediction_section(lines.append, {})
    txt = "\n".join(lines)
    assert "No predict fields" in txt


def test_prediction_section_renders_fused_fields():
    """ISSUE 19: the fused-megakernel rows — engine-table row, the packed
    transport line (bytes/row, reduction, cost_analysis bytes) and the
    predict_fused_ok guard — all grep to BENCH record fields, and a
    record predating the fields (the r05 lineage) renders without them."""
    import perf_report

    rec = {
        "predict_rows": 1000000, "predict_n_trees": 100,
        "predict_M_rows_per_s": 1.5,
        "predict_native_compute_M_rows_per_s": 4.2,
        "predict_device_M_rows_per_s": 2.5,
        "predict_device_compute_M_rows_per_s": 61.25,
        "predict_fused_M_rows_per_s": 133.5,
        "predict_h2d_bytes_per_row_packed": 14,
        "predict_packed_h2d_reduction": 2.0,
        "predict_fused_bytes_accessed": 4100096,
        "predict_fused_bytes_analytic": 3670016,
        "predict_fused_cache_retraces": 0,
        "predict_fused_parity_ok": True, "predict_fused_ok": True,
        "predict_parity_ok": True, "predict_ok": True,
    }
    lines = []
    perf_report.prediction_section(lines.append, rec)
    txt = "\n".join(lines)
    for needle in ("fused megakernel (walk+accumulate)", "133.5",
                   "14 H2D", "2x reduction", "4100096", "3670016",
                   "predict_fused_ok=True", "single-read contract",
                   "0 retraces across varied batch sizes through the "
                   "fused dispatch"):
        assert needle in txt, needle
    # an r05-era record without the fused fields: no fused rows, no crash
    for k in list(rec):
        if "fused" in k or "packed" in k:
            rec.pop(k)
    lines = []
    perf_report.prediction_section(lines.append, rec)
    txt = "\n".join(lines)
    assert "fused megakernel" not in txt
    assert "predict_fused_ok" not in txt


def test_serving_section_renders_serve_fields():
    """The Serving section (PR 5) is generated from the BENCH serve_*
    fields (bench.py measure_serve via tools/loadgen.py): the loadgen
    table, the hot-swap version accounting, the overload shed/bounded-
    queue line and the serve_ok guard all grep to record fields."""
    import perf_report

    rec = {
        "serve_requests": 1700, "serve_offered_qps": 400.0,
        "serve_qps": 386.2, "serve_p50_ms": 3.225, "serve_p99_ms": 16.646,
        "serve_p999_ms": 23.675, "serve_batch_occupancy": 0.0666,
        "serve_shed_frac": 0.0, "serve_swap_count": 2,
        "serve_versions": {"v1": 1081, "v2": 619},
        "serve_overload_shed_frac": 0.2527,
        "serve_overload_queue_max": 256, "serve_overload_queue_ok": True,
        "serve_ok": True,
    }
    lines = []
    perf_report.serving_section(lines.append, rec)
    txt = "\n".join(lines)
    assert "## Serving" in txt
    for needle in ("386.2", "16.646", "0.0666", "v1: 1081", "v2: 619",
                   "0.2527", "serve_ok=True", "bit-identical",
                   "never unbounded growth"):
        assert needle in txt, needle
    # a record with no serve capture renders the placeholder, never dies
    lines = []
    perf_report.serving_section(lines.append, {})
    txt = "\n".join(lines)
    assert "No serve fields" in txt


def test_robustness_section_renders_chaos_fields():
    """The Robustness section (PR 6) is generated from the BENCH chaos_*
    fields (bench.py measure_chaos via tools/chaos.py): the per-scenario
    recovery table and the chaos_ok guard grep to record fields."""
    import perf_report

    rec = {
        "chaos_ok": True, "chaos_n_scenarios": 7, "chaos_seconds": 31.2,
        "chaos_scenarios": {
            "train_kill_resume": True, "torn_snapshot": True,
            "poisoned_gradients": True, "publish_of_garbage": True,
            "dispatcher_stall": True, "overload": True,
            "h2d_transient": True,
        },
    }
    lines = []
    perf_report.robustness_section(lines.append, rec)
    txt = "\n".join(lines)
    assert "## Robustness" in txt
    for needle in ("chaos_ok=True", "bit-identical model text",
                   "never serves an answer", "watchdog 503",
                   "finite_guard", "31.2", "7 scripted fault scenarios"):
        assert needle in txt, needle
    # a record with no chaos capture renders the placeholder, never dies
    lines = []
    perf_report.robustness_section(lines.append, {})
    txt = "\n".join(lines)
    assert "No chaos fields" in txt
    # a failed scenario renders False (the guard line carries it)
    rec["chaos_scenarios"]["overload"] = False
    rec["chaos_ok"] = False
    lines = []
    perf_report.robustness_section(lines.append, rec)
    txt = "\n".join(lines)
    assert "chaos_ok=False" in txt and "| False |" in txt


def test_streaming_section_renders_stream_fields():
    """The Streaming section (PR 8) is generated from the BENCH stream_*
    fields (bench.py measure_stream, data/ block cache + row-block
    trainer): clocks, the ledger peak vs the analytic bound, and the
    stream_ok guard grep to record fields."""
    import perf_report

    rec = {
        "stream_ok": True, "stream_parity_ok": True, "stream_mem_ok": True,
        "stream_rows": 20000, "stream_block_rows": 4096,
        "stream_ms_per_iter": 812.5, "stream_resident_ms_per_iter": 401.3,
        "stream_vs_resident_ratio": 2.025,
        "stream_peak_device_bytes": 1234567,
        "stream_peak_device_bound_bytes": 2345678,
        "stream_resident_matrix_bytes": 560000,
    }
    lines = []
    perf_report.streaming_section(lines.append, rec)
    txt = "\n".join(lines)
    assert "## Streaming" in txt
    for needle in ("stream_ok=True", "stream_parity_ok=True",
                   "stream_mem_ok=True", "byte-identical", "812.5",
                   "1234567", "2345678", "4096-row blocks",
                   "not dataset rows"):
        assert needle in txt, needle
    # no capture yet -> placeholder, never dies
    lines = []
    perf_report.streaming_section(lines.append, {})
    assert "No stream fields" in "\n".join(lines)
    # a parity/memory failure surfaces on the guard line
    rec["stream_ok"] = False
    rec["stream_parity_ok"] = False
    lines = []
    perf_report.streaming_section(lines.append, rec)
    txt = "\n".join(lines)
    assert "stream_ok=False" in txt and "stream_parity_ok=False" in txt


def test_split_breakdown_and_pipeline_render():
    """The PR-7 fields render from the record: the split sub-phase line
    inside the phase table, the pipeline-overlap A/B section, and the
    int8sr AUC-parity experiment line — every figure greps to a BENCH
    field; absent fields render nothing (older records stay stable)."""
    import perf_report

    rec = {
        "phase_hist_ms": 66.78, "phase_partition_ms": 9.7,
        "phase_valid_route_ms": 2.1, "phase_split_ms": 22.8,
        "phase_other_ms": 50.48, "phase_total_measured_ms": 151.9,
        "wave_rounds_per_tree": 10.4,
        "phase_split_breakdown": {"split_cumsum_ms": 6.25,
                                  "split_gain_ms": 9.12,
                                  "split_pick_ms": 3.5},
        "phase_split_unattributed_ms": 3.91,
        "pipeline_ms_per_iter": 140.25, "pipeline_serialized_ms_per_iter":
        151.88, "pipeline_overlap_ms": 11.63, "pipeline_ok": True,
        "precision_expt": {"deep_int8sr": {
            "auc": 0.91342, "auc_iters": 100,
            "auc_delta_vs_default": -0.00012, "auc_parity": True,
            "M_row_trees_per_s": 9.875,
            "quant_buckets_active": [16, 63]}},
        "auc": 0.91354,
        "hist_achieved_tf_s": 1.0, "device_matmul_peak_tf_s": 2.0,
        "hist_roofline_frac": 0.5, "hist_ms_per_iter": 60.0,
    }
    txt = perf_report.generate(rec, "BENCH_rTEST.json")
    for needle in ("6.25", "9.12", "3.91",
                   "## Wave pipelining", "140.25", "151.88", "11.63",
                   "pipeline_ok=True", "tests/test_wave_pipeline.py",
                   "auc_parity=True", "[16, 63]", "0.91342",
                   "hist_dtype_deep=auto"):
        assert needle in txt, needle
    # absent fields: no pipeline section, no split line, no expt line —
    # the on-disk PERF.md (generated from an r05-era record) stays stable
    txt0 = perf_report.generate({"auc": 0.9}, "BENCH_rTEST.json")
    assert "## Wave pipelining" not in txt0
    assert "split_cumsum_ms" not in txt0
    assert "AUC-parity experiment" not in txt0


def test_observability_section_renders_obs_fields():
    """The Observability section (ISSUE 9) is generated from the BENCH
    obs_* fields (bench.py measure_obs): overhead vs the 2% contract,
    off-path parity, trace validity and the obs_ok guard all grep to
    record fields."""
    import perf_report

    rec = {
        "obs_ok": True, "obs_overhead_frac": 0.0125,
        "obs_span_cover_frac": 0.9321, "obs_trace_events": 412,
        "obs_parity_ok": True, "obs_trace_ok": True,
        "obs_serve_trace_ok": True, "obs_prom_ok": True,
    }
    lines = []
    perf_report.observability_section(lines.append, rec)
    txt = "\n".join(lines)
    assert "## Observability" in txt
    for needle in ("0.0125", "0.9321", "412", "obs_ok=True",
                   "obs_parity_ok=True", "obs_trace_ok=True",
                   "obs_serve_trace_ok=True", "byte-identical",
                   "`obs_trace`", "`trace_out`", "`obs_ring_events`",
                   "Prometheus"):
        assert needle in txt, needle
    # a record with no obs capture renders the placeholder, never dies
    lines = []
    perf_report.observability_section(lines.append, {})
    assert "No obs fields" in "\n".join(lines)


def test_forensics_slo_section_renders_fields():
    """The Forensics & SLO section (ISSUE 10) is generated from the
    BENCH slo_*/forensics/agg fields (bench.py measure_obs +
    measure_chaos): SLIs, burn rate, exemplar count and all four guards
    grep to record fields."""
    import perf_report

    rec = {
        "slo_ok": True, "slo_availability": 0.9987,
        "slo_latency_sli": 0.9912, "slo_availability_burn": 1.3,
        "slo_exemplars": 5, "forensics_ok": True, "obs_agg_ok": True,
        "obs_agg_sources": 2, "chaos_forensics_ok": True,
    }
    lines = []
    perf_report.forensics_slo_section(lines.append, rec)
    txt = "\n".join(lines)
    assert "## Forensics & SLO" in txt
    for needle in ("0.9987", "0.9912", "1.3", "5", "slo_ok=True",
                   "forensics_ok=True", "obs_agg_ok=True",
                   "chaos_forensics_ok=True", "`crash_dir`",
                   "`obs_dir`", "`serve_slo_*`", "burn-rate",
                   "Perfetto-loadable"):
        assert needle in txt, needle
    # a record with no forensics/SLO capture renders the placeholder
    lines = []
    perf_report.forensics_slo_section(lines.append, {})
    assert "No forensics/SLO fields" in "\n".join(lines)


def test_model_quality_section_renders_fields():
    """The Model quality & drift section (ISSUE 14) is generated from
    the BENCH drift_*/train_* fields (bench.py measure_drift): the
    skew-injection probe's PSI figures, the quality telemetry summary
    and the drift_ok guard all grep to record fields."""
    import perf_report

    rec = {
        "drift_ok": True, "drift_injected_psi": 1.2709,
        "drift_clean_psi_max": 0.0118, "drift_clean_false_alarms": 0,
        "drift_overhead_frac": 0.0096,
        "drift_ref_stream_parity_ok": True,
        "train_split_gain_p50": 50.62, "train_split_gain_p90": 388.41,
        "train_tree_leaves_mean": 31.0, "train_tree_depth_mean": 6.4,
        "train_top_gain_features": ["Column_0", "Column_1"],
    }
    lines = []
    perf_report.model_quality_section(lines.append, rec)
    txt = "\n".join(lines)
    assert "## Model quality & drift" in txt
    for needle in ("1.2709", "0.0118", "0.0096", "drift_ok=True",
                   "50.62", "388.41", "31", "6.4",
                   "Column_0, Column_1", "skew-injection",
                   "byte-identical", "`drift_sample_rows`",
                   "`drift_psi_threshold`", "`GET /drift`"):
        assert needle in txt, needle
    # a record with no drift capture renders the placeholder
    lines = []
    perf_report.model_quality_section(lines.append, {})
    assert "No model-quality fields" in "\n".join(lines)


def test_report_carries_model_quality_section(report_text):
    """The generated report always carries the Model quality section —
    placeholder or rendered."""
    assert "## Model quality & drift" in report_text


def test_fleet_section_renders_fields():
    """The Fleet section (ISSUE 11) is generated from the BENCH fleet_*
    / router_* fields (bench.py measure_fleet): the loadgen-under-kill
    row, the hedge rate, the recovery clock and every sub-guard grep to
    record fields."""
    import perf_report

    rec = {
        "fleet_ok": True, "fleet_requests": 625, "fleet_qps": 247.1,
        "fleet_p99_ms": 18.44, "router_hedge_frac": 0.0163,
        "fleet_router_retries": 3, "fleet_recovery_s": 5.21,
        "fleet_elastic_world": 2, "fleet_zero_error_ok": True,
        "fleet_replica_ejected_ok": True, "fleet_publish_ok": True,
        "fleet_kill_resume_ok": True, "chaos_fleet_ok": True,
    }
    lines = []
    perf_report.fleet_section(lines.append, rec)
    txt = "\n".join(lines)
    assert "## Fleet" in txt
    for needle in ("625", "247.1", "18.44", "0.0163", "5.21",
                   "fleet_ok=True", "fleet_zero_error_ok=True",
                   "fleet_replica_ejected_ok=True",
                   "fleet_publish_ok=True", "fleet_kill_resume_ok=True",
                   "chaos_fleet_ok=True", "`serve_replicas`",
                   "BYTE-IDENTICAL"):
        assert needle in txt, needle
    # a record with no fleet capture renders the placeholder
    lines = []
    perf_report.fleet_section(lines.append, {})
    assert "No fleet fields" in "\n".join(lines)


def test_tenants_section_renders_fields():
    """The Multi-tenant serving section (ISSUE 20) is generated from
    the BENCH tenant_* fields (bench.py measure_tenants): the
    compile-share counters, the isolation probe row and every
    sub-guard grep to record fields."""
    import perf_report

    rec = {
        "tenant_ok": True, "tenant_compile_share_frac": 0.5,
        "tenant_shared_cache_hits": 4,
        "tenant_second_warm_compiles": 0, "tenant_mixed_retraces": 0,
        "tenant_hot_shed": 6, "tenant_cold_shed": 0,
        "tenant_cold_p99_ms": 8.8,
        "tenant_isolation_p99_delta_ms": 4.82,
        "tenant_placement_moves": 1,
        "tenant_compile_share_ok": True, "tenant_fair_share_ok": True,
        "tenant_publish_parity_ok": True,
        "tenant_placement_move_ok": True,
    }
    lines = []
    perf_report.tenants_section(lines.append, rec)
    txt = "\n".join(lines)
    assert "## Multi-tenant serving" in txt
    for needle in ("0.5", "4.82", "8.8", "tenant_ok=True",
                   "tenant_compile_share_ok=True",
                   "tenant_fair_share_ok=True",
                   "tenant_publish_parity_ok=True",
                   "tenant_placement_move_ok=True",
                   "`tenant_manifest`", "`registry_keep_versions`",
                   "placement.move"):
        assert needle in txt, needle
    # a record with no tenant capture renders the placeholder
    lines = []
    perf_report.tenants_section(lines.append, {})
    assert "No tenant fields" in "\n".join(lines)


def test_report_carries_tenants_section(report_text):
    assert "## Multi-tenant serving" in report_text


def test_device_truth_section_renders_fields():
    """The Device truth section (ISSUE 12) is generated from the BENCH
    device-truth fields (bench.py measure_obs's device block via
    obs/xla.py): compile clock, per-label counters, the zero-retrace
    probe, HBM/ledger reconciliation and the roofline rows all grep to
    record fields."""
    import perf_report

    rec = {
        "obs_device_ok": True, "compile_ms_total": 1234.5,
        "serve_bucket_retraces": 0, "hbm_peak_bytes": 987654321,
        "ledger_agreement": 0.9312,
        "compile_counts": {"train.scan": 3, "predict.leaf": 2},
        "retrace_counts": {"train.scan": 1, "predict.leaf": 0},
        "train_step_flops": 5.0e9, "train_step_bytes_accessed": 2.5e9,
        "train_step_temp_bytes": 123456,
        "phase_roofline": {
            "hist": {"ms": 40.0, "achieved_tf_s": 21.5,
                     "frac_of_peak": 0.1612, "bound": "compute"},
        },
    }
    lines = []
    perf_report.device_truth_section(lines.append, rec)
    txt = "\n".join(lines)
    assert "## Device truth" in txt
    for needle in ("1234.5", "987654321", "0.9312",
                   "train.scan 3 (1)", "predict.leaf 2 (0)",
                   "obs_device_ok=True", "| hist | 40 | 21.5 | 0.1612 "
                   "| compute |", "compile_ms_total", "hbm_peak_bytes"):
        assert needle in txt, needle
    # a record with no device-truth capture renders the placeholder
    lines = []
    perf_report.device_truth_section(lines.append, {})
    txt = "\n".join(lines)
    assert "No device-truth fields" in txt
    assert "tools/capture.py" in txt


def test_trend_section_renders_sentinel_rows(tmp_path):
    """The Trend section is rendered BY the sentinel (bench_trend.run),
    so PERF.md's table and the gate's verdict cannot disagree."""
    import json as _json

    import perf_report

    for name, parsed in (("BENCH_r01.json", {"value": 5.0}),
                         ("BENCH_r02.json", {"value": 4.0,
                                             "serve_ok": False})):
        with open(os.path.join(tmp_path, name), "w") as fh:
            _json.dump({"parsed": parsed}, fh)
    lines = []
    perf_report.trend_section(lines.append, root=str(tmp_path))
    txt = "\n".join(lines)
    assert "## Trend" in txt
    assert "**REGRESSED**" in txt           # 5.0 -> 4.0 is >10% down
    assert "**GUARD_FALSE**" in txt         # serve_ok False flagged
    assert "Sentinel verdict: FLAGGED" in txt
    # a healthy series renders OK (the same check the gate runs)
    healthy = tmp_path / "healthy"
    healthy.mkdir()
    for name, parsed in (("BENCH_r01.json", {"value": 5.0,
                                             "serve_ok": True}),
                         ("BENCH_r02.json", {"value": 5.2,
                                             "serve_ok": True})):
        with open(os.path.join(healthy, name), "w") as fh:
            _json.dump({"parsed": parsed}, fh)
    lines = []
    perf_report.trend_section(lines.append, root=str(healthy))
    txt = "\n".join(lines)
    assert "Sentinel verdict: OK" in txt and "| value |" in txt


def test_comm_section_renders_in_report(report_text):
    """The generated report must carry the Cross-chip comms section and
    its figures must grep to the analytic formula."""
    sys.path.insert(0, REPO)
    from lightgbmv1_tpu.parallel.cluster import comm_table_per_round

    txt = report_text
    assert "## Cross-chip comms" in txt
    rs = comm_table_per_round("data", "reduce_scatter", k=16, F=16, B=64,
                              ndev=8)
    assert str(rs["hist_bytes"]) in txt


# ---------------------------------------------------------------------------
# Pod-scale comms (ISSUE 16): the hierarchical table, its guard, and the
# PERF.md section — bytes pinned at the dryrun smoke shape
# ---------------------------------------------------------------------------


def test_hier_comm_table_bytes_pinned():
    """Byte-pin the two-level analytic table at the smoke shape
    (K=16, F=16, B=64, D=8 as 2x4): ICI reduce-scatter sends
    M*(C-1)/C, only the 1/C slice crosses DCN, and the guard trips if
    the DCN bytes stop beating the flat wire by the host fan-in."""
    sys.path.insert(0, REPO)
    from lightgbmv1_tpu.parallel.cluster import (hier_comm_ok,
                                                 hier_comm_table_per_round,
                                                 wire_bytes)

    K, F, B, D, H = 16, 16, 64, 8, 2
    t = hier_comm_table_per_round("data", k=K, F=F, B=B, ndev=D,
                                  num_hosts=H)
    M = K * F * B * 3                           # (k, F, B, 3) f32 stack
    assert t["num_hosts"] == 2 and t["chips_per_host"] == 4
    assert t["ici"]["hist_bytes"] == M * 3 // 4 * 4 == 147456
    assert t["dcn"]["hist_bytes"] == (M // 4) // 2 * 4 == 24576
    assert t["flat_hist_wire_bytes"] == M * 7 // 8 * 4 == 172032
    # the round-count-free invariant the measured-vs-analytic probe
    # pins: ICI/DCN wire ratio = C(C-1)H / (H-1) = 6 at 2x4
    assert t["ici"]["hist_bytes"] / t["dcn"]["hist_bytes"] == 6.0
    assert t["hier_ms"] < t["flat_ms"]          # the hierarchy pays
    # wire_bytes conventions the table is built from
    assert wire_bytes(100, 4, "reduce_scatter") == 75 * 4
    assert wire_bytes(100, 4, "allreduce") == 150 * 4
    assert wire_bytes(100, 4, "all_gather") == 300 * 4
    assert wire_bytes(100, 1, "reduce_scatter") == 0
    # guard: DCN bytes must beat flat wire / H; degenerate H=1 passes
    assert hier_comm_ok(t["dcn"]["hist_bytes"],
                        t["flat_hist_wire_bytes"], H)
    assert not hier_comm_ok(t["flat_hist_wire_bytes"],
                            t["flat_hist_wire_bytes"], H)
    assert hier_comm_ok(10**9, 1, 1)
    # the config-lifted bandwidth knobs (hier_ici_gbps / hier_dcn_gbps,
    # ISSUE 17): modeled ms scales inversely, byte columns — and hence
    # the guard — are knob-invariant
    t2 = hier_comm_table_per_round("data", k=K, F=F, B=B, ndev=D,
                                   num_hosts=H, ici_gbps=200.0,
                                   dcn_gbps=20.0)
    assert t2["ici"] == t["ici"] and t2["dcn"] == t["dcn"]
    assert t2["flat_hist_wire_bytes"] == t["flat_hist_wire_bytes"]
    assert t2["hier_ms"] == pytest.approx(t["hier_ms"] / 2)
    assert t2["flat_ms"] == pytest.approx(t["flat_ms"] / 2)
    from lightgbmv1_tpu.config import Config
    with pytest.raises(Exception, match="hier_ici_gbps"):
        Config.from_dict({"objective": "binary", "verbosity": -1,
                          "hier_dcn_gbps": 0.0})
    # voting: the top-2k election payload is priced at BOTH levels and
    # the vote bound catches a selective reduce that silently widened
    v = hier_comm_table_per_round("voting", k=K, F=F, B=B, ndev=D,
                                  num_hosts=H, sel_k=F)
    assert v["ici"]["vote_bytes"] > 0 and v["dcn"]["vote_bytes"] > 0
    assert not hier_comm_ok(v["dcn"]["hist_bytes"],
                            v["flat_hist_wire_bytes"], H,
                            vote_bound_bytes=v["dcn"]["hist_bytes"] - 1)


def test_pod_comm_section_renders(tmp_path):
    """The Pod-scale comms section: analytic table always renders (and
    greps to hier_comm_table_per_round at the smoke shape), the
    measured guards render when the MULTICHIP record carries them, and
    an empty record yields the placeholder — the section never dies."""
    import perf_report

    mc = {
        "n_devices": 8,
        "hier_comm_bytes_per_round": {
            "data": {"ici": {"hist_bytes": 82944},
                     "dcn": {"hist_bytes": 13824, "total_bytes": 17568},
                     "flat_hist_wire_bytes": 96768}},
        "hier_comm_ok": True,
        "hier_wire_measured": {"ici_bytes": 156672, "dcn_bytes": 26112,
                               "ici_dcn_ratio": 6.0},
        "hier_wire_analytic_ici_dcn_ratio": 6.0,
        "hier_measured_vs_analytic_ok": True,
    }
    lines = []
    perf_report.pod_comm_section(lines.append, "MULTICHIP_rXX.json", mc)
    txt = "\n".join(lines)
    assert "## Pod-scale comms" in txt
    for needle in ("147456", "24576", "172032",      # analytic pins
                   "13824", "96768",                 # measured fields
                   "hier_comm_ok=True",
                   "hier_measured_vs_analytic_ok=True"):
        assert needle in txt, needle
    lines = []
    perf_report.pod_comm_section(lines.append, None, None)
    txt = "\n".join(lines)
    assert "## Pod-scale comms" in txt
    assert "No MULTICHIP capture with hierarchical fields" in txt


def test_pod_comm_section_renders_in_report(report_text):
    """The generated report carries the Pod-scale comms section with the
    smoke-shape analytic figures."""
    txt = report_text
    assert "## Pod-scale comms" in txt
    assert "147456" in txt and "24576" in txt
