"""Render a captured BENCH record as a markdown report (VERDICT r5 #2).

Every number in the report greps to a field of the ``BENCH_r*.json``
record it names in its header, so a quoted figure cannot outlive the
capture it came from:

    python tools/perf_report.py BENCH_rNN.json           # -> stdout
    python tools/perf_report.py BENCH_rNN.json out.md    # -> out.md
    python tools/perf_report.py                          # newest record

``PERF.md`` at the repo root is NOT this tool's output: it is the
builders' hand-kept account (what ran on which device, what compiles,
what was learned), and this tool never writes there by default.

Mechanism narrative (what a lever IS) lives in the module docstrings and
git history it links; THIS file holds only the record-to-table mapping
plus cross-record notes computed from the records themselves (e.g. the
r04->r05 roofline-denominator drift).
"""
import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The dryrun smoke shape (tools/dryrun_multichip, __graft_entry__.py):
# the analytic comm table below is computed at exactly these constants so
# every figure greps to a formula input, not a hand-typed number.
SMOKE = dict(ndev=8, F=16, B=64, K=16, top_k=20)


def load(path):
    with open(path) as fh:
        rec = json.load(fh)
    return rec.get("parsed", rec)


def load_multichip(root=ROOT):
    """Newest MULTICHIP_r*.json whose captured tail carries the dryrun
    PARITY record (older captures were liveness-only).  Returns
    ``(name, parsed record or None)``."""
    recs = sorted(glob.glob(os.path.join(root, "MULTICHIP_r*.json")))
    for path in reversed(recs):
        try:
            with open(path) as fh:
                rec = json.load(fh)
        except ValueError:
            continue
        m = re.search(r"dryrun_multichip PARITY (\{.*\})",
                      rec.get("tail", ""))
        if m:
            try:
                return os.path.basename(path), json.loads(m.group(1))
            except ValueError:
                continue
    return (os.path.basename(recs[-1]) if recs else None), None


def comm_section(w, mc_name, mc):
    """Cross-chip comms: the analytic per-round byte table of every
    learner at the dryrun smoke shape (single source of truth:
    lightgbmv1_tpu.parallel.cluster.comm_table_per_round — the same
    function the trainer logs at build time and dryrun_multichip records),
    plus the measured-record guard when a MULTICHIP capture carries it."""
    try:
        if ROOT not in sys.path:
            sys.path.insert(0, ROOT)
        from lightgbmv1_tpu.parallel.cluster import comm_table_per_round
    except Exception as e:  # noqa: BLE001 — report generation must not die
        w(f"(comm table unavailable: {type(e).__name__})")
        w("")
        return
    w("## Cross-chip comms (per sustained wave round, analytic)")
    w("")
    w(f"Output-payload bytes per device per K={SMOKE['K']}-split round at "
      f"the dryrun smoke shape (D={SMOKE['ndev']}, F={SMOKE['F']}, "
      f"B={SMOKE['B']}; parallel/cluster.py comm_table_per_round — the "
      "trainer logs the same table at build time):")
    w("")
    w("| learner / collective | histogram | split sync | votes | total |")
    w("|---|---|---|---|---|")
    rows = (
        ("data / reduce_scatter", "data", "reduce_scatter", None),
        ("data / allreduce (parity pin)", "data", "allreduce", None),
        ("voting / reduce_scatter", "voting", "reduce_scatter",
         min(2 * SMOKE["top_k"], SMOKE["F"])),
        ("feature", "feature", "allreduce", None),
    )
    for label, learner, coll, sel_k in rows:
        t = comm_table_per_round(learner, coll, k=SMOKE["K"],
                                 F=SMOKE["F"], B=SMOKE["B"],
                                 ndev=SMOKE["ndev"], sel_k=sel_k)
        w(f"| {label} | {t['hist_bytes']} | {t['split_sync_bytes']} | "
          f"{t.get('vote_bytes', '—')} | {t['total_bytes']} |")
    w("")
    w("The reduce-scatter path keeps F/D features per chip and syncs only "
      "packed SplitInfo (the reference's ReduceScatter + "
      "SyncUpGlobalBestSplit mapping); int8sr rounds move raw int32 "
      "through the histogram collective (ops/quantize.py global scales).")
    w("")
    if mc and mc.get("comm_bytes_per_round"):
        w(f"Measured-record table (`{mc_name}`, replayed wave schedule, "
          f"mean-k rounds, D={mc.get('n_devices')}):")
        w("")
        w("| learner | histogram | split sync | total | dtype |")
        w("|---|---|---|---|---|")
        for name, t in mc["comm_bytes_per_round"].items():
            w(f"| {name} | {t.get('hist_bytes')} | "
              f"{t.get('split_sync_bytes')} | {t.get('total_bytes')} | "
              f"{t.get('hist_dtype')} |")
        w("")
        w(f"Comm guard `comm_ok={mc.get('comm_ok')}` (reduce-scatter "
          "histogram bytes must be <= allreduce / (D*0.9); "
          "cluster.comm_guard_ok — the dryrun asserts it, this report "
          "surfaces it).")
    else:
        w("No MULTICHIP capture with a PARITY record yet — the next "
          "driver run of tools/dryrun_multichip records the measured "
          "table and the `comm_ok` guard into the MULTICHIP record.")
    w("")


def pod_comm_section(w, mc_name, mc):
    """Pod-scale comms (ISSUE 16): the hierarchical ICI/DCN collective's
    per-level analytic wire table at the dryrun smoke shape (single
    source of truth: parallel/cluster.py hier_comm_table_per_round — the
    same function the trainer logs at build time and dryrun_multichip
    records), plus the measured-record guards when a MULTICHIP capture
    carries them.  Placeholder until then — the section never dies."""
    try:
        if ROOT not in sys.path:
            sys.path.insert(0, ROOT)
        from lightgbmv1_tpu.parallel.cluster import hier_comm_table_per_round
    except Exception as e:  # noqa: BLE001 — report generation must not die
        w(f"(hier comm table unavailable: {type(e).__name__})")
        w("")
        return
    H = 2
    w("## Pod-scale comms (hierarchical ICI/DCN collective, per wave "
      "round)")
    w("")
    w(f"Per-level ring SEND bytes per device per K={SMOKE['K']}-split "
      f"round at the dryrun smoke shape (D={SMOKE['ndev']} as H={H} "
      f"hosts x C={SMOKE['ndev'] // H} chips, F={SMOKE['F']}, "
      f"B={SMOKE['B']}; parallel/cluster.py hier_comm_table_per_round). "
      "`data_parallel_collective=hierarchical` reduce-scatters over the "
      "fast intra-host ICI axis FIRST, so only the F/D-sliced partials "
      "ever cross the slow inter-host DCN link; the voting learner's "
      "top-2k election additionally compresses WHAT crosses:")
    w("")
    w("| learner / level | histogram | split sync | votes | total |")
    w("|---|---|---|---|---|")
    tables = {}
    for learner in ("data", "voting"):
        t = hier_comm_table_per_round(
            learner, k=SMOKE["K"], F=SMOKE["F"], B=SMOKE["B"],
            ndev=SMOKE["ndev"], num_hosts=H,
            sel_k=(min(2 * SMOKE["top_k"], SMOKE["F"])
                   if learner == "voting" else None))
        tables[learner] = t
        for level in ("ici", "dcn"):
            lv = t[level]
            w(f"| {learner} / {level} | {lv['hist_bytes']} | "
              f"{lv['split_sync_bytes']} | {lv['vote_bytes']} | "
              f"{lv['total_bytes']} |")
        w(f"| {learner} / flat ring (all-DCN baseline) | "
          f"{t['flat_hist_wire_bytes']} | — | — | — |")
    w("")
    dt = tables["data"]
    w(f"Modeled round latency at the ICI/DCN bandwidth gap "
      f"(cluster.ICI_GBPS/DCN_GBPS): hierarchical "
      f"{fmt(dt['hier_ms'], 5)} ms vs flat {fmt(dt['flat_ms'], 5)} ms "
      f"for the data learner — the flat ring's slowest hop is a DCN "
      "hop, which is exactly why the hierarchy pays.")
    w("")
    if mc and mc.get("hier_comm_bytes_per_round"):
        w(f"Measured-record table (`{mc_name}`, "
          f"D={mc.get('n_devices')}, mean-k rounds):")
        w("")
        w("| learner | ICI hist | DCN hist | DCN total | flat wire |")
        w("|---|---|---|---|---|")
        for name, t in mc["hier_comm_bytes_per_round"].items():
            w(f"| {name} | {(t.get('ici') or {}).get('hist_bytes')} | "
              f"{(t.get('dcn') or {}).get('hist_bytes')} | "
              f"{(t.get('dcn') or {}).get('total_bytes')} | "
              f"{t.get('flat_hist_wire_bytes')} |")
        w("")
        wire = mc.get("hier_wire_measured") or {}
        w(f"Guards: `hier_comm_ok={mc.get('hier_comm_ok')}` (DCN "
          "histogram bytes <= flat reduce-scatter wire / num_hosts, the "
          "voting learner additionally within its top-2k analytic bound "
          "— cluster.hier_comm_ok, required by tools/ci_gate.py "
          "--require-guards) and `hier_measured_vs_analytic_ok="
          f"{mc.get('hier_measured_vs_analytic_ok')}` (the lowered "
          "StableHLO's reduce-scatter ops, split by replica-group size: "
          f"measured ICI/DCN wire ratio {get(wire, 'ici_dcn_ratio', 2)} "
          "vs analytic "
          f"{get(mc, 'hier_wire_analytic_ici_dcn_ratio', 2)}, within "
          "5%).")
    else:
        w("No MULTICHIP capture with hierarchical fields yet — the next "
          "driver run of tools/dryrun_multichip trains the "
          "data_hierarchical/voting_hierarchical parity set on the 2x4 "
          "virtual mesh and records the per-level table, the "
          "`hier_comm_ok` guard and the measured-vs-analytic wire "
          "ratio into the MULTICHIP record.")
    w("")


def prediction_section(w, rec):
    """Prediction: the serving-engine table (native C++ / depth-stepped
    device walk / legacy scan pin) plus the component split of the device
    file->file window (parse / prebin / H2D / walk / write) and the
    ``predict_ok`` guard — every figure greps to a BENCH predict_* field
    (bench.py measure_predict).  Renders a placeholder until the first
    capture that carries the fields."""
    w("## Prediction (file->file on the bench set)")
    w("")
    if rec.get("predict_M_rows_per_s") is None:
        w("No predict fields in this record yet — the next driver capture "
          "runs bench.py's measure_predict (native C++ predictor, the "
          "depth-stepped all-trees device walk on prebinned serving "
          "codes, and the legacy scan-walk parity pin) and this section "
          "renders its parse/H2D/walk split and the `predict_ok` guard.")
        w("")
        return
    w(f"{get(rec, 'predict_n_trees', 0)} trees, "
      f"{get(rec, 'predict_rows', 0)} rows:")
    w("")
    w("| engine | M rows/s (file->file) | M rows/s (compute only) |")
    w("|---|---|---|")
    w(f"| native C++ predictor | {get(rec, 'predict_M_rows_per_s', 3)}"
      f" | {get(rec, 'predict_native_compute_M_rows_per_s', 3)} |")
    w(f"| device depth-stepped walk | "
      f"{get(rec, 'predict_device_M_rows_per_s', 3)} | "
      f"{get(rec, 'predict_device_compute_M_rows_per_s', 3)} |")
    if rec.get("predict_device_scan_M_rows_per_s") is not None:
        w(f"| device scan walk (parity pin) | — | "
          f"{get(rec, 'predict_device_scan_M_rows_per_s', 3)} |")
    if rec.get("predict_fused_M_rows_per_s") is not None:
        w(f"| fused megakernel (walk+accumulate) | — | "
          f"{get(rec, 'predict_fused_M_rows_per_s', 3)} |")
    if rec.get("predict_ref_cpp_M_rows_per_s"):
        w(f"| reference CLI task=predict | "
          f"{get(rec, 'predict_ref_cpp_M_rows_per_s', 3)} | — |")
    w("")
    if rec.get("predict_walk_ms") is not None:
        w("Device window components (ms, chunk-sized batch): parse "
          f"{get(rec, 'predict_parse_ms')} / prebin "
          f"{get(rec, 'predict_prebin_ms')} / H2D "
          f"{get(rec, 'predict_h2d_ms')} / walk "
          f"{get(rec, 'predict_walk_ms')} / write "
          f"{get(rec, 'predict_write_ms')}; "
          f"{get(rec, 'predict_h2d_bytes_per_row', 0)} H2D bytes/row "
          "(prebinned serving codes), "
          f"{get(rec, 'predict_cache_retraces', 0)} retraces across "
          "varied batch sizes (predictor cache).")
        w("")
    if rec.get("predict_h2d_bytes_per_row_packed") is not None:
        w("Serving megakernel transport: "
          f"{get(rec, 'predict_h2d_bytes_per_row_packed', 0)} H2D "
          "bytes/row with 4-bit packed serving codes "
          f"({get(rec, 'predict_packed_h2d_reduction')}x reduction vs "
          "the byte-wide twin, analytic ceil(F/2)); measured "
          "cost_analysis bytes "
          f"{get(rec, 'predict_fused_bytes_accessed', 0)} vs analytic "
          f"single-read floor {get(rec, 'predict_fused_bytes_analytic', 0)}"
          f"; {get(rec, 'predict_fused_cache_retraces', 0)} retraces "
          "across varied batch sizes through the fused dispatch.")
        w("")
    if rec.get("predict_ok") is not None:
        w(f"Guard `predict_ok={rec.get('predict_ok')}`: node-exact leaf "
          f"parity vs the host walk "
          f"(`predict_parity_ok={rec.get('predict_parity_ok')}`) AND the "
          "depth-stepped walk at >= 0.95x the scan-walk compute rate "
          "(bench.py asserts the split; this report surfaces it).")
        w("")
    if rec.get("predict_fused_ok") is not None:
        w(f"Guard `predict_fused_ok={rec.get('predict_fused_ok')}`: the "
          "fused walk+accumulate megakernel node/bit-exact vs the host "
          "oracle "
          f"(`predict_fused_parity_ok={rec.get('predict_fused_parity_ok')}"
          "`), zero retraces within a bucket, and on device >= 1.5x the "
          "scan walk's compute rate with cost_analysis bytes confirming "
          "the single-read contract.")
        w("")


def serving_section(w, rec):
    """Serving: the online-subsystem loadgen figures (serve/ — deadline-
    aware micro-batching, hot-swap registry, bounded-queue admission
    control) — every figure greps to a BENCH serve_* field written by
    bench.py's measure_serve via tools/loadgen.py.  Renders a placeholder
    until the first capture that carries the fields."""
    w("## Serving (open-loop loadgen against the in-process server)")
    w("")
    if rec.get("serve_qps") is None:
        w("No serve fields in this record yet — the next driver capture "
          "runs bench.py's measure_serve (tools/loadgen.py open-loop "
          "Poisson traffic with a mid-run hot-swap, then a bounded-queue "
          "overload probe) and this section renders the QPS / latency "
          "quantiles / batch occupancy / shed figures and the `serve_ok` "
          "guard.")
        w("")
        return
    w(f"{get(rec, 'serve_requests', 0)} requests at "
      f"{get(rec, 'serve_offered_qps', 1)} offered QPS "
      "(live phase, hot-swap mid-run):")
    w("")
    w("| achieved QPS | p50 ms | p99 ms | p999 ms | batch occupancy | "
      "shed frac |")
    w("|---|---|---|---|---|---|")
    w(f"| {get(rec, 'serve_qps', 1)} | {get(rec, 'serve_p50_ms', 3)} | "
      f"{get(rec, 'serve_p99_ms', 3)} | {get(rec, 'serve_p999_ms', 3)} | "
      f"{get(rec, 'serve_batch_occupancy', 4)} | "
      f"{get(rec, 'serve_shed_frac', 4)} |")
    w("")
    versions = rec.get("serve_versions") or {}
    if versions:
        served = ", ".join(f"{k}: {v}" for k, v in versions.items())
        w(f"Hot swap under live traffic: versions served {{{served}}} "
          f"across {get(rec, 'serve_swap_count', 0)} publishes — every "
          "response bit-identical to `Booster.predict` of the version "
          "tag it carries (checked per request by the loadgen).")
        w("")
    if rec.get("serve_overload_shed_frac") is not None:
        w(f"Overload probe (2x+ capacity into a "
          f"{get(rec, 'serve_overload_queue_max', 0)}-row-max queue): "
          f"shed frac {get(rec, 'serve_overload_shed_frac', 4)} with the "
          "backlog bounded at the configured admission depth "
          f"(`serve_overload_queue_ok="
          f"{rec.get('serve_overload_queue_ok')}`) — explicit rejection, "
          "never unbounded growth.")
        w("")
    if rec.get("serve_ok") is not None:
        w(f"Guard `serve_ok={rec.get('serve_ok')}`: zero "
          "failed/incorrect responses in the live phase AND both "
          "versions served across the swap AND the overload queue "
          "stayed bounded (bench.py asserts the split; this report "
          "surfaces it).")
        w("")


def streaming_section(w, rec):
    """Streaming: the out-of-core block-cache trainer record (PR 8 —
    bench.py measure_stream, data/ subsystem).  Every figure greps to a
    BENCH stream_* field; placeholder until the first capture carrying
    them."""
    w("## Streaming (out-of-core row-block training, data/ block cache)")
    w("")
    if rec.get("stream_ok") is None:
        w("No stream fields in this record yet — the next driver capture "
          "runs bench.py's measure_stream (sharded block cache written "
          "once, row-block streaming trainer vs the resident trainer at "
          "the same sequential schedule) and this section renders the "
          "per-iteration clocks, the ledger-accounted peak device bytes "
          "against the O(stream_block_rows · F) bound, and the "
          "`stream_ok` guard (byte-identical model text AND bounded "
          "memory).")
        w("")
        return
    w(f"{get(rec, 'stream_rows', 0)} rows streamed in "
      f"{get(rec, 'stream_block_rows', 0)}-row blocks:")
    w("")
    w("| stream ms/iter | resident ms/iter | ratio | peak device bytes | "
      "bound | resident matrix bytes |")
    w("|---|---|---|---|---|---|")
    w(f"| {get(rec, 'stream_ms_per_iter', 2)} | "
      f"{get(rec, 'stream_resident_ms_per_iter', 2)} | "
      f"{get(rec, 'stream_vs_resident_ratio', 3)} | "
      f"{get(rec, 'stream_peak_device_bytes', 0)} | "
      f"{get(rec, 'stream_peak_device_bound_bytes', 0)} | "
      f"{get(rec, 'stream_resident_matrix_bytes', 0)} |")
    w("")
    w(f"Guard `stream_ok={rec.get('stream_ok')}`: model text "
      f"byte-identical to the resident trainer "
      f"(`stream_parity_ok={rec.get('stream_parity_ok')}` — the fixed-"
      "block-order parity contract, BASELINE.md) AND ledger-accounted "
      "peak device bytes within the analytic block-scaled bound "
      f"(`stream_mem_ok={rec.get('stream_mem_ok')}`): the device "
      "working set scales with `stream_block_rows`, not dataset rows.")
    w("")


def robustness_section(w, rec):
    """Robustness: the scripted chaos-suite record (PR 6 — bench.py
    measure_chaos via tools/chaos.py).  Each row is one injected-fault
    scenario and whether its recovery path held; ``chaos_ok`` is the
    all-scenarios guard.  Renders a placeholder until the first capture
    that carries the fields."""
    w("## Robustness (scripted fault injection, tools/chaos.py)")
    w("")
    if rec.get("chaos_ok") is None:
        w("No chaos fields in this record yet — the next driver capture "
          "runs bench.py's measure_chaos (the fast deterministic subset "
          "of tools/chaos.py: kill-and-resume with bit-identical model "
          "text, torn-checkpoint fallback, NaN-poisoned gradients, "
          "publish-of-garbage, dispatcher stall/death, bounded-queue "
          "overload, transient-H2D retry) and this section renders the "
          "per-scenario table and the `chaos_ok` guard.")
        w("")
        return
    scenarios = rec.get("chaos_scenarios") or {}
    w(f"{get(rec, 'chaos_n_scenarios', 0)} scripted fault scenarios"
      + (f" in {get(rec, 'chaos_seconds', 1)} s"
         if rec.get("chaos_seconds") is not None else "") + ":")
    w("")
    w("| scenario | recovered |")
    w("|---|---|")
    labels = {
        "train_kill_resume": "kill mid-training -> checkpoint auto-resume "
                             "(bit-identical model text)",
        "torn_snapshot": "torn newest checkpoint -> validated fallback to "
                         "previous intact bundle",
        "poisoned_gradients": "NaN-poisoned gradient pass -> finite_guard "
                              "detect (raise) + survive (clamp)",
        "publish_of_garbage": "corrupt model publish -> rejected pre-swap, "
                              "never serves an answer",
        "dispatcher_stall": "stalled/dead dispatcher -> watchdog 503 + "
                            "thread restart",
        "overload": "burst over capacity -> explicit shed, bounded queue",
        "h2d_transient": "transient H2D failure -> bounded "
                         "retry-with-backoff, zero client errors",
    }
    for name, ok in scenarios.items():
        w(f"| {labels.get(name, name)} | {ok} |")
    w("")
    w(f"Guard `chaos_ok={rec.get('chaos_ok')}`: EVERY injected fault "
      "recovered (bench.py runs the suite on every backend; "
      "__graft_entry__.chaos_smoke hard-asserts it each driver "
      "capture).  Knobs: `finite_guard=off|warn|raise|clamp` on the "
      "gradient pass; `serve_retry_max`/`serve_breaker_failures`/"
      "`serve_watchdog_ms`/`serve_probe_rows` on the serving failure "
      "domains (BASELINE.md).")
    w("")


def observability_section(w, rec):
    """Observability: the obs/ subsystem's own cost and validity record
    (ISSUE 9 — bench.py measure_obs): armed-tracer overhead vs the
    2% contract, off-path bit-parity, trace validity for the train and
    serve paths, and Prometheus exposition health.  Placeholder until
    the first capture that carries the fields."""
    w("## Observability (span tracer + metrics registry, obs/)")
    w("")
    if rec.get("obs_ok") is None:
        w("No obs fields in this record yet — the next driver capture "
          "runs bench.py's measure_obs (A/B train with the span tracer "
          "armed vs off, a traced serve loadgen window, and a Prometheus "
          "exposition probe) and this section renders the overhead "
          "fraction against the 2% contract and the `obs_ok` guard.")
        w("")
        return
    w("| armed overhead frac | span cover of train wall | trace events | "
      "off-path parity | prom exposition |")
    w("|---|---|---|---|---|")
    w(f"| {get(rec, 'obs_overhead_frac', 4)} | "
      f"{get(rec, 'obs_span_cover_frac', 4)} | "
      f"{get(rec, 'obs_trace_events', 0)} | "
      f"{rec.get('obs_parity_ok')} | {rec.get('obs_prom_ok')} |")
    w("")
    w(f"Guard `obs_ok={rec.get('obs_ok')}`: armed tracing costs <= 2% of "
      "train wall AND the disarmed run's model text is byte-identical "
      f"(`obs_parity_ok={rec.get('obs_parity_ok')}`) AND both exported "
      "Chrome traces are valid with train iteration spans covering the "
      "measured wall within 10% "
      f"(`obs_trace_ok={rec.get('obs_trace_ok')}`) and serve request "
      "spans decomposing queue/walk "
      f"(`obs_serve_trace_ok={rec.get('obs_serve_trace_ok')}`).  Knobs: "
      "`obs_trace`, `trace_out`, `obs_ring_events` (BASELINE.md); "
      "`GET /metrics` serves Prometheus text under content negotiation.")
    w("")


def device_truth_section(w, rec):
    """Device truth (ISSUE 12 — bench.py measure_obs's device block +
    obs/xla.py): compile telemetry (labeled compile walls, retrace
    counters, the serving zero-retrace probe), HBM footprint vs the
    streaming ledger, and the per-phase roofline join.  Placeholder
    until the first capture that carries the fields."""
    w("## Device truth (compile/memory/cost telemetry, obs/xla.py)")
    w("")
    if rec.get("obs_device_ok") is None:
        w("No device-truth fields in this record yet — the next driver "
          "capture runs the extended measure_obs (labeled lower/compile "
          "telemetry on the trainer dispatches, predictor cache and "
          "parallel learners; a serving-bucket zero-retrace probe; "
          "device.memory_stats() reconciled against the streaming "
          "DeviceLedger; the per-phase roofline join) and this section "
          "renders `compile_ms_total`, the retrace counters, "
          "`hbm_peak_bytes`/`ledger_agreement` and the `obs_device_ok` "
          "guard.  `tools/capture.py` is the one-command driver that "
          "produces it.")
        w("")
        return
    w("| compile ms (total) | serve bucket retraces | HBM peak bytes | "
      "ledger agreement |")
    w("|---|---|---|---|")
    w(f"| {get(rec, 'compile_ms_total', 1)} | "
      f"{get(rec, 'serve_bucket_retraces', 0)} | "
      f"{get(rec, 'hbm_peak_bytes', 0)} | "
      f"{get(rec, 'ledger_agreement', 4)} |")
    w("")
    counts = rec.get("compile_counts") or {}
    retraces = rec.get("retrace_counts") or {}
    if counts:
        w("Per-label compiles (retraces): "
          + ", ".join(f"{k} {counts[k]} ({retraces.get(k, 0)})"
                      for k in sorted(counts)) + ".")
        w("")
    if rec.get("train_step_flops") is not None:
        w(f"Compiled train step cost analysis: "
          f"{get(rec, 'train_step_flops', 0)} flops, "
          f"{get(rec, 'train_step_bytes_accessed', 0)} bytes accessed, "
          f"{get(rec, 'train_step_temp_bytes', 0)} temp bytes "
          "(the compiled executable's own cost/memory analysis — "
          "obs/xla.py records it at every labeled compile).")
        w("")
    rl = rec.get("phase_roofline") or {}
    if rl:
        w("Per-phase roofline (measured phase ms x cost-analysis split "
          "vs the same-session matmul peak; "
          "tools/phase_attrib.roofline_attribution):")
        w("")
        w("| phase | ms | achieved TF/s | frac of peak | bound |")
        w("|---|---|---|---|---|")
        for phase in sorted(rl):
            row = rl[phase]
            w(f"| {phase} | {fmt(row.get('ms'))} | "
              f"{fmt(row.get('achieved_tf_s'), 4)} | "
              f"{fmt(row.get('frac_of_peak'), 4)} | "
              f"{row.get('bound', '—')} |")
        w("")
    w(f"Guard `obs_device_ok={rec.get('obs_device_ok')}`: compile "
      "telemetry present for the training dispatches AND zero serving "
      "bucket retraces AND (when the backend reports allocator stats) a "
      "positive HBM peak with the ledger agreement in (0, 1.5].  "
      "`tools/bench_trend.py` watches `compile_ms_total` (generous 50% "
      "bar — compile time is noisy) and `hbm_peak_bytes` (10%).")
    w("")


def forensics_slo_section(w, rec):
    """Forensics & SLO (ISSUE 10 — bench.py measure_obs + measure_chaos):
    the serving SLO burn-rate block (availability / latency SLIs,
    exemplar trace ids), the flight-recorder drill, the loadgen+server
    aggregation probe, and the chaos suite's bundle contract.
    Placeholder until the first capture that carries the fields."""
    w("## Forensics & SLO (flight recorder + burn-rate, obs/dump.py + "
      "serve/slo.py)")
    w("")
    if rec.get("slo_ok") is None and rec.get("forensics_ok") is None:
        w("No forensics/SLO fields in this record yet — the next driver "
          "capture runs the extended measure_obs (SLO burn-rate "
          "evaluation over the loadgen window with exemplar trace ids, "
          "a flight-recorder drill writing one validated bundle, and "
          "the loadgen+server artifact aggregation probe) plus "
          "measure_chaos's per-scenario bundle contract, and this "
          "section renders the `slo_ok` / `forensics_ok` / "
          "`obs_agg_ok` / `chaos_forensics_ok` guards.")
        w("")
        return
    w("| availability SLI (fast) | latency SLI (fast) | avail burn | "
      "exemplars | agg sources |")
    w("|---|---|---|---|---|")
    w(f"| {get(rec, 'slo_availability', 4)} | "
      f"{get(rec, 'slo_latency_sli', 4)} | "
      f"{get(rec, 'slo_availability_burn', 4)} | "
      f"{get(rec, 'slo_exemplars', 0)} | "
      f"{get(rec, 'obs_agg_sources', 0)} |")
    w("")
    w(f"Guards: `slo_ok={rec.get('slo_ok')}` (sane multi-window "
      "burn-rate evaluation, page-on-burning/quiet-on-clean alert "
      "logic, 16-hex exemplar trace ids on the latency buckets, "
      "`GET /slo` payload serializes); "
      f"`forensics_ok={rec.get('forensics_ok')}` (an armed flight "
      "recorder writes exactly ONE schema-valid, digest-intact, "
      "Perfetto-loadable bundle per arming); "
      f"`obs_agg_ok={rec.get('obs_agg_ok')}` (tools/obs_aggregate.py "
      "merges the loadgen + server artifacts into one trace with "
      "distinct pid lanes and one additive snapshot); "
      f"`chaos_forensics_ok={rec.get('chaos_forensics_ok')}` (every "
      "chaos kill/wedge left exactly one validated bundle, every "
      "recovered fault left none).  Knobs: `crash_dir` / "
      "`LGBMV1_CRASH_DIR`, `obs_dir` / `LGBMV1_OBS_DIR`, "
      "`serve_slo_*` (BASELINE.md).")
    w("")


def model_quality_section(w, rec):
    """Model quality & drift (ISSUE 14 — bench.py measure_drift +
    obs/model.py + obs/drift.py): the trainer quality telemetry summary
    and the serving-side skew-injection probe (clean traffic quiet,
    injected shift detected, streamed-vs-resident reference byte
    parity, armed-sampling overhead vs the <= 2% contract).
    Placeholder until the first capture that carries the fields."""
    w("## Model quality & drift (reference capture + skew detection, "
      "obs/model.py + obs/drift.py)")
    w("")
    if rec.get("drift_ok") is None:
        w("No model-quality fields in this record yet — the next driver "
          "capture runs bench.py's measure_drift (deterministic "
          "skew-injection probe against a drift-armed server, the "
          "streamed-vs-resident reference byte-parity check, the armed "
          "sampling overhead A/B, and the trainer quality telemetry "
          "summary) and this section renders the injected/clean PSI, "
          "the split-gain and tree-shape aggregates, and the `drift_ok` "
          "guard.")
        w("")
        return
    w("| injected PSI | clean PSI max | clean false alarms | "
      "overhead frac | stream ref parity |")
    w("|---|---|---|---|---|")
    w(f"| {get(rec, 'drift_injected_psi', 4)} | "
      f"{get(rec, 'drift_clean_psi_max', 4)} | "
      f"{get(rec, 'drift_clean_false_alarms', 0)} | "
      f"{get(rec, 'drift_overhead_frac', 4)} | "
      f"{rec.get('drift_ref_stream_parity_ok')} |")
    w("")
    top = rec.get("train_top_gain_features") or []
    w(f"Trainer quality telemetry: split gain p50 "
      f"{get(rec, 'train_split_gain_p50')} / p90 "
      f"{get(rec, 'train_split_gain_p90')}, mean "
      f"{get(rec, 'train_tree_leaves_mean')} leaves / depth "
      f"{get(rec, 'train_tree_depth_mean')} per tree"
      + (f"; top gain features: {', '.join(top)}" if top else "")
      + ".")
    w("")
    w(f"Guard `drift_ok={rec.get('drift_ok')}`: the +3-sigma "
      "skew-injection probe is DETECTED (injected feature alerts, "
      "ranks top-1, publishes a `drift.alert` event) AND clean traffic "
      "raises zero false alarms AND the serialized training reference "
      "is byte-identical between the resident and streaming trainers "
      "AND armed sampling stays within the <= 2% serving contract "
      f"(`drift_overhead_frac={get(rec, 'drift_overhead_frac', 4)}`).  "
      "Knobs: `drift_sample_rows` (hard-off default 0), "
      "`drift_psi_threshold`, `drift_top_k`, `drift_sample_stride` "
      "(BASELINE.md); `GET /drift` serves the evaluation.")
    w("")


def fleet_section(w, rec):
    """Fault-tolerant fleet (ISSUE 11 — bench.py measure_fleet): the
    replica-kill-under-loadgen drill (zero client-visible errors,
    router hedge rate, health-check ejection), the coordinated
    two-phase publish, and the elastic training kill-resume byte-parity
    drill with its recovery clock.  Placeholder until the first capture
    that carries the fields."""
    w("## Fleet (elastic recovery + self-healing serving, "
      "parallel/elastic.py + serve/router.py)")
    w("")
    if rec.get("fleet_ok") is None:
        w("No fleet fields in this record yet — the next driver capture "
          "runs bench.py's measure_fleet (a 3-replica fleet behind the "
          "self-healing router with one replica killed under open-loop "
          "loadgen, a coordinated two-phase publish onto the degraded "
          "fleet, and an elastic-coordinator training run killed at "
          "iteration 3 and re-bootstrapped from its checkpoint bundle) "
          "and this section renders the zero-error/ejection/parity "
          "guards, `router_hedge_frac` and `fleet_recovery_s`.")
        w("")
        return
    w("| requests | qps | p99 ms | hedge frac | router retries | "
      "recovery s | elastic world |")
    w("|---|---|---|---|---|---|---|")
    w(f"| {get(rec, 'fleet_requests', 0)} | {get(rec, 'fleet_qps', 1)} | "
      f"{get(rec, 'fleet_p99_ms', 2)} | "
      f"{get(rec, 'router_hedge_frac', 4)} | "
      f"{get(rec, 'fleet_router_retries', 0)} | "
      f"{get(rec, 'fleet_recovery_s', 2)} | "
      f"{get(rec, 'fleet_elastic_world', 0)} |")
    w("")
    w(f"Guard `fleet_ok={rec.get('fleet_ok')}`: replica killed "
      "mid-loadgen with ZERO client-visible errors "
      f"(`fleet_zero_error_ok={rec.get('fleet_zero_error_ok')}`), the "
      "dead replica health-check ejected "
      f"(`fleet_replica_ejected_ok={rec.get('fleet_replica_ejected_ok')}"
      "`), a two-phase publish landing one aligned tag fleet-wide "
      f"(`fleet_publish_ok={rec.get('fleet_publish_ok')}`), and the "
      "elastic kill-at-k run resuming to BYTE-IDENTICAL model text "
      f"(`fleet_kill_resume_ok={rec.get('fleet_kill_resume_ok')}`).  "
      "The chaos suite's fleet subset rides `chaos_fleet_ok="
      f"{rec.get('chaos_fleet_ok')}`.  Knobs: `serve_replicas`, "
      "`router_*` (hedge/retry/health), `elastic_*` (lease timeout, "
      "max restarts) — BASELINE.md \"Fault-tolerant fleet\".")
    w("")


def tenants_section(w, rec):
    """Multi-tenant serving (ISSUE 20 — bench.py measure_tenants): the
    compile-bucket-sharing counters, the fair-share isolation probe,
    per-tenant publish/rollback parity and the placement-move drill.
    Placeholder until the first capture that carries the fields."""
    w("## Multi-tenant serving (serve/tenants.py + serve/placement.py)")
    w("")
    if rec.get("tenant_ok") is None:
        w("No tenant fields in this record yet — the next driver "
          "capture runs bench.py's measure_tenants (two same-shape "
          "tenants sharing ONE compiled executable proven by per-label "
          "compile counters, a 2x hot-tenant overload with the cold "
          "tenant's p99 held inside its SLO, per-tenant "
          "publish/rollback bit-parity, and a burn-rate-triggered "
          "placement move) and this section renders "
          "`tenant_compile_share_frac`, the isolation p99 tax and the "
          "four probe guards.")
        w("")
        return
    w("| share frac | cache hits | 2nd-warm compiles | mixed retraces "
      "| hot sheds | cold sheds | cold p99 ms | isolation Δp99 ms | "
      "placement moves |")
    w("|---|---|---|---|---|---|---|---|---|")
    w(f"| {get(rec, 'tenant_compile_share_frac', 4)} | "
      f"{get(rec, 'tenant_shared_cache_hits', 0)} | "
      f"{get(rec, 'tenant_second_warm_compiles', 0)} | "
      f"{get(rec, 'tenant_mixed_retraces', 0)} | "
      f"{get(rec, 'tenant_hot_shed', 0)} | "
      f"{get(rec, 'tenant_cold_shed', 0)} | "
      f"{get(rec, 'tenant_cold_p99_ms', 2)} | "
      f"{get(rec, 'tenant_isolation_p99_delta_ms', 2)} | "
      f"{get(rec, 'tenant_placement_moves', 0)} |")
    w("")
    w(f"Guard `tenant_ok={rec.get('tenant_ok')}`: the second tenant's "
      "warm adopted the first tenant's executables — zero new "
      "per-label compiles, zero retraces under mixed-tenant traffic "
      f"(`tenant_compile_share_ok={rec.get('tenant_compile_share_ok')}"
      "`); the hot tenant shed its OWN traffic while the cold tenant "
      "kept zero sheds and a p99 inside its SLO bound "
      f"(`tenant_fair_share_ok={rec.get('tenant_fair_share_ok')}`); "
      "publishing v2 into tenant A left tenant B bit-identical and "
      "A's rollback restored v1 bit-exactly "
      f"(`tenant_publish_parity_ok={rec.get('tenant_publish_parity_ok')}"
      "`); the burn-rate signal moved the hot tenant with a fully "
      "attributed `placement.move` event "
      f"(`tenant_placement_move_ok={rec.get('tenant_placement_move_ok')}"
      "`).  Knobs: `tenant_manifest`, `registry_keep_versions`, "
      "`placement_*` — BASELINE.md \"Multi-tenant serving\".")
    w("")


def trend_section(w, root=ROOT):
    """Trend: the regression sentinel's view of the whole BENCH record
    trajectory (tools/bench_trend.py — the same comparator that gates
    captures renders this table, so PERF.md and the gate cannot
    disagree)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import bench_trend
    except Exception as e:  # noqa: BLE001 — report generation must not die
        w(f"(trend unavailable: {type(e).__name__})")
        w("")
        return
    result = bench_trend.run(root)
    w("## Trend (tools/bench_trend.py over every captured record)")
    w("")
    names = result["bench_records"]
    w(f"{len(names)} BENCH records "
      f"({names[0] if names else '—'} → {names[-1] if names else '—'}), "
      f"{len(result['multichip_records'])} MULTICHIP PARITY records.  "
      "Newest-record bars: watched fields within tolerance of the best "
      "prior capture, every `*_ok` guard True — the same check "
      "`tools/ci_gate.py` gates on.")
    w("")
    w("| field | newest | best prior | record | verdict |")
    w("|---|---|---|---|---|")
    for row in result["trend_rows"]:
        verdict = "**REGRESSED**" if row["regressed"] else "ok"
        prior = (f"{fmt(row['best_prior'], 4)} "
                 f"({row['best_prior_record']})"
                 if row["best_prior"] is not None else "first capture")
        w(f"| {row['field']} | {fmt(row['current'], 4)} | {prior} | "
          f"{row['record']} | {verdict} |")
    for f in result["flags"]:
        if f["kind"] != "regression":
            w(f"| {f['field']} | False | — | {f['record']} | "
              f"**{f['kind'].upper()}** |")
    w("")
    w(f"Sentinel verdict: {'OK' if result['ok'] else 'FLAGGED'} "
      "(`python tools/bench_trend.py` exits non-zero on any flag).")
    w("")


def fmt(v, nd=2):
    if v is None:
        return "—"
    if isinstance(v, float):
        return f"{v:.{nd}f}".rstrip("0").rstrip(".") if nd else f"{v:g}"
    return str(v)


def get(d, k, nd=2):
    return fmt(d.get(k), nd)


def generate(rec, name, prev=None, prev_name=None):
    L = []
    w = L.append
    w(f"# Performance record — generated by tools/perf_report.py "
      f"from `{name}`")
    w("")
    w("Every number below is a field of the captured record (grep the "
      "JSON); regenerate after each driver capture.  Mechanisms behind "
      "the numbers are documented where they live: ops/hist_pallas.py "
      "(kernel + precision modes), ops/quantize.py (int8sr stochastic "
      "rounding), models/grower_wave.py (wave schedule, slot buckets, "
      "quantized-round gate), tools/phase_attrib.py (residual "
      "attribution), and the git history.")
    w("")

    w(f"## Headline — {rec.get('metric', 'training throughput')}")
    w("")
    w("| | M row-trees/s | vs same-host ref C++ | vs published baseline |"
      " held-out AUC |")
    w("|---|---|---|---|---|")
    w(f"| reference C++ (this host, 1 core) | "
      f"{get(rec, 'ref_cpp_same_host_M_row_trees_per_s', 3)} | 1.00x | — | "
      f"{get(rec, 'auc_ref_lightgbm_cpp', 6)} |")
    w(f"| **leaf-wise (headline)** | **{get(rec, 'value', 3)}** | "
      f"**{get(rec, 'vs_ref_same_host', 4)}x** | "
      f"**{get(rec, 'vs_baseline', 4)}** | {get(rec, 'auc', 5)} "
      f"@{get(rec, 'auc_iters', 0)} iters |")
    w(f"| level-wise | {get(rec, 'levelwise_M_row_trees_per_s', 3)} | "
      f"{get(rec, 'levelwise_vs_ref_same_host', 4)}x | — | "
      f"{get(rec, 'levelwise_auc', 5)} |")
    if rec.get("dart_M_row_trees_per_s") is not None:
        w(f"| DART (per-iter dispatch) | "
          f"{get(rec, 'dart_M_row_trees_per_s', 3)} | — | — | — "
          f"({get(rec, 'dart_frac_of_scanned_gbdt', 3)} of scanned "
          f"leaf-wise) |")
    for k, label in (("goss", "GOSS (fused scan)"),
                     ("rf", "RF (fused scan)")):
        if rec.get(f"{k}_M_row_trees_per_s") is not None:
            w(f"| {label} | {get(rec, f'{k}_M_row_trees_per_s', 3)} | — | "
              f"— | — |")
    w("")
    w("`vs_baseline` divides by the published dual-Xeon HIGGS bar "
      "(40.36 M row-trees/s, docs/Experiments.rst:110-124); "
      "`vs_ref_same_host` by the reference C++ binary on THIS host — the "
      "like-for-like comparison.  The bench set is HIGGS-shaped "
      "synthetic (real HIGGS is not downloadable in this zero-egress "
      "environment).")
    w("")

    if rec.get("tpu_500iter_wall_s") is not None:
        w("## 500-tree north star (docs/Experiments.rst methodology)")
        w("")
        w("| | wall (500 trees) | valid AUC @500 |")
        w("|---|---|---|")
        w(f"| reference C++ (1 core) | "
          f"{get(rec, 'ref_cpp_500iter_wall_s')} s | "
          f"{get(rec, 'ref_cpp_500iter_auc', 6)} |")
        w(f"| **this repo** | **{get(rec, 'tpu_500iter_wall_s')} s** | "
          f"**{get(rec, 'tpu_500iter_auc', 6)}** |")
        w("")
        w(f"**{get(rec, 'vs_ref_500iter', 4)}x the reference** — the "
          "single-dispatch-amortized wall is the stable instrument "
          "(short windows swing up to ~2x run to run; see "
          "`train_seconds_for_timed_block` vs the phase totals below).")
        w("")

    if rec.get("phase_hist_ms") is not None:
        w("## Per-phase breakdown (ms per leaf-wise iteration)")
        w("")
        w("| hist | partition | valid-route | split | other | "
          "measured total |")
        w("|---|---|---|---|---|---|")
        w(f"| {get(rec, 'phase_hist_ms')} | "
          f"{get(rec, 'phase_partition_ms')} | "
          f"{get(rec, 'phase_valid_route_ms')} | "
          f"{get(rec, 'phase_split_ms')} | "
          f"{get(rec, 'phase_other_ms')} | "
          f"{get(rec, 'phase_total_measured_ms')} |")
        w("")
        tot = rec.get("phase_total_measured_ms") or 0
        hist = rec.get("phase_hist_ms") or 0
        if tot:
            w(f"Histogram work is ~{100 * hist / tot:.0f}% of the "
              f"iteration at `wave_rounds_per_tree` = "
              f"{get(rec, 'wave_rounds_per_tree')} (replayed schedule; "
              "sustained rounds priced at the deep dtype they actually "
              "run).")
        bd = rec.get("phase_other_breakdown")
        if bd:
            w("")
            w("`phase_other_ms` attribution (tools/phase_attrib.py): "
              + ", ".join(f"{k} {fmt(v)}" for k, v in bd.items())
              + f"; unattributed {get(rec, 'phase_other_unattributed_ms')}"
              f" (ok={rec.get('phase_attrib_ok')}).")
        sbd = rec.get("phase_split_breakdown")
        if sbd:
            w("")
            w("`phase_split_ms` sub-phases (ops/split.py fused scan — "
              "cumsum+missing-adjust / stacked gain eval / tie-band pick; "
              "tools/phase_attrib.py): "
              + ", ".join(f"{k} {fmt(v)}" for k, v in sbd.items())
              + f"; remainder {get(rec, 'phase_split_unattributed_ms')} "
              "(vmap plumbing + result assembly).")
        w("")

    if rec.get("pipeline_ok") is not None:
        w("## Wave pipelining (async_wave_pipeline A/B)")
        w("")
        w(f"Pipelined {get(rec, 'pipeline_ms_per_iter')} ms/iter vs "
          f"serialized legacy body "
          f"{get(rec, 'pipeline_serialized_ms_per_iter')} ms/iter — "
          f"overlap {get(rec, 'pipeline_overlap_ms')} ms/iter recovered "
          f"(`pipeline_ok={rec.get('pipeline_ok')}`: the overlapped "
          "per-iter total must not exceed the serialized sum; trivially "
          "true on CPU captures, where the backend serializes "
          "everything).  The pipelined schedule defers each round's "
          "histogram-state scatter and valid-row routing into the next "
          "round's computation (models/grower_wave.py) — bit-parity "
          "against the serialized body is pinned in "
          "tests/test_wave_pipeline.py.")
        w("")

    w("## Histogram kernel (bench config, measured same-session)")
    w("")
    w("| pass | ms |")
    w("|---|---|")
    for k, label in (
            ("hist_ms_per_pass", "bf16x2 full pass (K slots)"),
            ("hist_ms_per_pass_deep", "deep pass as trained (policy dtype)"),
            ("hist_ms_per_pass_int8sr", "int8sr quantized pass (K slots)"),
            ("hist_ms_per_pass_s16_int8sr", "int8sr quantized pass (16)"),
            ("hist_ms_per_pass_s16", "16-slot ramp bucket"),
            ("hist_ms_per_pass_s4", "4-slot ramp bucket"),
            ("hist_ms_per_pass_root", "root (1-slot) pass"),
    ):
        if rec.get(k) is not None:
            w(f"| {label} | {get(rec, k)} |")
    w("")
    w(f"Roofline: {get(rec, 'hist_achieved_tf_s')} TF/s achieved vs "
      f"{get(rec, 'device_matmul_peak_tf_s')} TF/s same-session matmul "
      f"peak = **{get(rec, 'hist_roofline_frac', 4)}** fraction "
      f"(`hist_ms_per_iter` {get(rec, 'hist_ms_per_iter')} over the "
      "replayed round schedule).")
    pe = (rec.get("precision_expt") or {}).get("deep_int8sr")
    if pe:
        w("")
        w("int8sr AUC-parity experiment (the `hist_dtype_deep=auto` flip "
          f"gate): auc {fmt(pe.get('auc'), 5)} vs default "
          f"{get(rec, 'auc', 5)} at {fmt(pe.get('auc_iters'), 0)} iters "
          f"(delta {fmt(pe.get('auc_delta_vs_default'), 6)}, "
          f"auc_parity={pe.get('auc_parity')}), "
          f"{fmt(pe.get('M_row_trees_per_s'), 3)} M row-trees/s, "
          f"quantized buckets active: {pe.get('quant_buckets_active')} "
          "(empty = the shape never reached the quantized gate — the "
          "flip needs a device capture where it engages).")
    if prev is not None and prev.get("hist_roofline_frac") is not None:
        w("")
        w(f"Cross-record note ({prev_name} -> {name}): "
          f"`hist_roofline_frac` {get(prev, 'hist_roofline_frac', 4)} -> "
          f"{get(rec, 'hist_roofline_frac', 4)} is mostly DENOMINATOR "
          f"drift — `device_matmul_peak_tf_s` moved "
          f"{get(prev, 'device_matmul_peak_tf_s')} -> "
          f"{get(rec, 'device_matmul_peak_tf_s')} between captures (the "
          f"same run-to-run drift the throughput ranges carry), while the "
          f"achieved pass moved "
          f"{get(prev, 'hist_achieved_tf_s')} -> "
          f"{get(rec, 'hist_achieved_tf_s')} TF/s — not a kernel "
          "regression.")
    w("")

    if rec.get("multiclass_M_row_trees_per_s") is not None \
            or rec.get("rank_M_row_trees_per_s") is not None:
        w("## Parity set beyond binary (same-host reference CLI, "
          "identical synthetic data)")
        w("")
        w("| family | ours M r-t/s | ref C++ | speed | quality (ours / "
          "ref) |")
        w("|---|---|---|---|---|")
        if rec.get("multiclass_M_row_trees_per_s") is not None:
            w(f"| multiclass softmax | "
              f"{get(rec, 'multiclass_M_row_trees_per_s', 3)}"
              + (f" ({get(rec, 'multiclass_window_iters', 0)}-iter window)"
                 if rec.get("multiclass_window_iters") else "")
              + f" | {get(rec, 'multiclass_ref_cpp_M_row_trees_per_s', 3)}"
              f" | {get(rec, 'multiclass_vs_ref_same_host', 4)}x | "
              f"mlogloss {get(rec, 'multiclass_logloss', 5)} / "
              f"{get(rec, 'multiclass_ref_cpp_logloss', 6)} |")
        if rec.get("rank_M_row_trees_per_s") is not None:
            w(f"| lambdarank | {get(rec, 'rank_M_row_trees_per_s', 3)}"
              + (f" ({get(rec, 'rank_window_iters', 0)}-iter window)"
                 if rec.get("rank_window_iters") else "")
              + f" | {get(rec, 'rank_ref_cpp_M_row_trees_per_s', 3)} | "
              f"{get(rec, 'rank_vs_ref_same_host', 4)}x | ndcg@10 "
              f"{get(rec, 'rank_ndcg10', 5)} / "
              f"{get(rec, 'rank_ref_cpp_ndcg10', 6)} |")
        w("")
        w("(Throughput from ONE long scanned window per family — the "
          "binary block's 500-iter methodology — after the old best-of-3 "
          "short windows recorded 2x run-to-run swings.)")
        w("")
        w("Multiclass parity config (tools/mc_gap_ab.py A/B, CPU smoke "
          "on record): the mlogloss gap vs the reference is driven by "
          "the WAVE SCHEDULE, not precision — `gpu_use_dp` (f32 "
          "histograms) is bit-identical to base while "
          "`leafwise_wave_size=1` diverges from base at tree 0.  "
          "`leafwise_wave_size=1` is the documented parity setting (the "
          "reference's exact sequential best-first order; "
          "tests/test_wave_grower.py pins it reproducing the sequential "
          "grower's trees on the multiclass smoke shape — see "
          "BASELINE.md).")
        w("")

    prediction_section(w, rec)

    serving_section(w, rec)

    streaming_section(w, rec)

    robustness_section(w, rec)

    observability_section(w, rec)

    device_truth_section(w, rec)

    forensics_slo_section(w, rec)

    model_quality_section(w, rec)

    fleet_section(w, rec)

    tenants_section(w, rec)

    mc_name, mc = load_multichip()
    comm_section(w, mc_name, mc)

    pod_comm_section(w, mc_name, mc)

    trend_section(w)

    w("## Provenance")
    w("")
    w(f"Source record: `{name}`"
      + (f"; cross-record notes vs `{prev_name}`." if prev_name else "."))
    w("Reference-side constants (same-host C++ CLI timings, quality "
      "numbers) are recorded in bench.py next to their measurement "
      "dates; tools/measure_ref_parity.py re-measures them on an idle "
      "host.")
    w("")
    return "\n".join(L)


def main(argv):
    if len(argv) > 1:
        path = argv[1]
    else:
        recs = sorted(glob.glob(os.path.join(ROOT, "BENCH_r*.json")))
        if not recs:
            sys.exit("no BENCH_r*.json records found")
        path = recs[-1]
    out_path = argv[2] if len(argv) > 2 else None
    rec = load(path)
    name = os.path.basename(path)
    # previous record for cross-capture drift notes
    recs = sorted(glob.glob(os.path.join(ROOT, "BENCH_r*.json")))
    prev = prev_name = None
    try:
        i = [os.path.basename(r) for r in recs].index(name)
        if i > 0:
            prev = load(recs[i - 1])
            prev_name = os.path.basename(recs[i - 1])
    except ValueError:
        pass
    text = generate(rec, name, prev, prev_name)
    if out_path is None:
        print(text)
        return
    with open(out_path, "w") as fh:
        fh.write(text)
    print(f"wrote {out_path} from {name}"
          + (f" (drift notes vs {prev_name})" if prev_name else ""))


if __name__ == "__main__":
    main(sys.argv)
