"""Training configuration.

TPU-native re-design of the reference config system (reference:
``include/LightGBM/config.h`` declares ~240 parameters; ``src/io/config_auto.cpp``
holds the generated alias table and parser; ``Config::KV2Map`` at ``config.h:80``
parses CLI ``key=value`` pairs).

Here the config is a plain Python dataclass covering the parameters the TPU
framework implements, with the same names, defaults, and aliases as the
reference so that reference-style param dicts and ``train.conf`` files work
unchanged.  Unknown keys warn (reference behavior: ``Config::Set`` ignores
unknowns with a warning).
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple, Union

from .utils.log import log_warning

# ---------------------------------------------------------------------------
# Alias table (reference: src/io/config_auto.cpp GetAliasTable / docs/Parameters.rst)
# ---------------------------------------------------------------------------
_ALIASES: Dict[str, str] = {
    # core
    "config_file": "config",
    "task_type": "task",
    "objective_type": "objective",
    "app": "objective",
    "application": "objective",
    "boosting_type": "boosting",
    "boost": "boosting",
    "train": "data",
    "train_data": "data",
    "train_data_file": "data",
    "data_filename": "data",
    "test": "valid",
    "valid_data": "valid",
    "valid_data_file": "valid",
    "test_data": "valid",
    "test_data_file": "valid",
    "valid_filenames": "valid",
    "num_trees": "num_iterations",
    "num_iteration": "num_iterations",
    "num_tree": "num_iterations",
    "num_round": "num_iterations",
    "num_rounds": "num_iterations",
    "num_boost_round": "num_iterations",
    "n_iter": "num_iterations",
    "n_estimators": "num_iterations",
    "shrinkage_rate": "learning_rate",
    "eta": "learning_rate",
    "num_leaf": "num_leaves",
    "max_leaves": "num_leaves",
    "max_leaf": "num_leaves",
    "tree": "tree_learner",
    "tree_type": "tree_learner",
    "tree_learner_type": "tree_learner",
    "num_thread": "num_threads",
    "nthread": "num_threads",
    "nthreads": "num_threads",
    "n_jobs": "num_threads",
    "device": "device_type",
    "random_seed": "seed",
    "random_state": "seed",
    # learning control
    "min_data_per_leaf": "min_data_in_leaf",
    "min_data": "min_data_in_leaf",
    "min_child_samples": "min_data_in_leaf",
    "min_sum_hessian_per_leaf": "min_sum_hessian_in_leaf",
    "min_sum_hessian": "min_sum_hessian_in_leaf",
    "min_hessian": "min_sum_hessian_in_leaf",
    "min_child_weight": "min_sum_hessian_in_leaf",
    "sub_row": "bagging_fraction",
    "subsample": "bagging_fraction",
    "bagging": "bagging_fraction",
    "pos_sub_row": "pos_bagging_fraction",
    "pos_subsample": "pos_bagging_fraction",
    "pos_bagging": "pos_bagging_fraction",
    "neg_sub_row": "neg_bagging_fraction",
    "neg_subsample": "neg_bagging_fraction",
    "neg_bagging": "neg_bagging_fraction",
    "subsample_freq": "bagging_freq",
    "bagging_fraction_seed": "bagging_seed",
    "sub_feature": "feature_fraction",
    "colsample_bytree": "feature_fraction",
    "sub_feature_bynode": "feature_fraction_bynode",
    "colsample_bynode": "feature_fraction_bynode",
    "early_stopping_rounds": "early_stopping_round",
    "early_stopping": "early_stopping_round",
    "n_iter_no_change": "early_stopping_round",
    "max_tree_output": "max_delta_step",
    "max_leaf_output": "max_delta_step",
    "reg_alpha": "lambda_l1",
    "l1_regularization": "lambda_l1",
    "reg_lambda": "lambda_l2",
    "lambda": "lambda_l2",
    "l2_regularization": "lambda_l2",
    "min_split_gain": "min_gain_to_split",
    "rate_drop": "drop_rate",
    "topk": "top_k",
    "mc": "monotone_constraints",
    "monotone_constraint": "monotone_constraints",
    "cegb_penalty_feature_lazy": "cegb_penalty_feature_lazy",
    "fc": "forcedsplits_filename",
    "forced_splits_filename": "forcedsplits_filename",
    "forced_splits_file": "forcedsplits_filename",
    "forced_splits": "forcedsplits_filename",
    "verbose": "verbosity",
    # IO
    "max_bins": "max_bin",
    "subsample_for_bin": "bin_construct_sample_cnt",
    "data_seed": "data_random_seed",
    "model_output": "output_model",
    "model_out": "output_model",
    "model_input": "input_model",
    "model_in": "input_model",
    "predict_result": "output_result",
    "prediction_result": "output_result",
    "predict_name": "output_result",
    "prediction_name": "output_result",
    "pred_name": "output_result",
    "name_pred": "output_result",
    "is_sparse": "is_enable_sparse",
    "enable_sparse": "is_enable_sparse",
    "sparse": "is_enable_sparse",
    "is_enable_bundle": "enable_bundle",
    "bundle": "enable_bundle",
    "is_pre_partition": "pre_partition",
    "two_round_loading": "two_round",
    "use_two_round_loading": "two_round",
    "is_save_binary": "save_binary",
    "is_save_binary_file": "save_binary",
    "has_header": "header",
    "label": "label_column",
    "weight": "weight_column",
    "group": "group_column",
    "group_id": "group_column",
    "query_column": "group_column",
    "query": "group_column",
    "query_id": "group_column",
    "ignore_feature": "ignore_column",
    "blacklist": "ignore_column",
    "categorical_column": "categorical_feature",
    "cat_column": "categorical_feature",
    "categorical_columns": "categorical_feature",
    "cat_feature": "categorical_feature",
    "cat_features": "categorical_feature",
    "is_predict_raw_score": "predict_raw_score",
    "predict_rawscore": "predict_raw_score",
    "raw_score": "predict_raw_score",
    "is_predict_leaf_index": "predict_leaf_index",
    "leaf_index": "predict_leaf_index",
    "is_predict_contrib": "predict_contrib",
    "contrib": "predict_contrib",
    # objective
    "num_classes": "num_class",
    "unbalance": "is_unbalance",
    "unbalanced_sets": "is_unbalance",
    "sigmoid_": "sigmoid",
    # metric
    "metrics": "metric",
    "metric_types": "metric",
    "output_freq": "metric_freq",
    "training_metric": "is_provide_training_metric",
    "is_training_metric": "is_provide_training_metric",
    "train_metric": "is_provide_training_metric",
    "ndcg_eval_at": "eval_at",
    "ndcg_at": "eval_at",
    "map_eval_at": "eval_at",
    "map_at": "eval_at",
    # network
    "num_machine": "num_machines",
    "local_port": "local_listen_port",
    "port": "local_listen_port",
    "machine_list_file": "machine_list_filename",
    "machine_list": "machine_list_filename",
    "mlist": "machine_list_filename",
    "workers": "machines",
    "nodes": "machines",
}

_OBJECTIVE_ALIASES: Dict[str, str] = {
    "regression_l2": "regression",
    "l2": "regression",
    "mean_squared_error": "regression",
    "mse": "regression",
    "l2_root": "regression",
    "root_mean_squared_error": "regression",
    "rmse": "regression",
    "l1": "regression_l1",
    "mean_absolute_error": "regression_l1",
    "mae": "regression_l1",
    "mean_absolute_percentage_error": "mape",
    "multiclassova": "multiclassova",
    "multiclass_ova": "multiclassova",
    "ova": "multiclassova",
    "ovr": "multiclassova",
    "xentropy": "cross_entropy",
    "xentlambda": "cross_entropy_lambda",
    "mean_average_precision": "map",
    "rank_xendcg": "rank_xendcg",
    "xendcg": "rank_xendcg",
    "xe_ndcg": "rank_xendcg",
    "xe_ndcg_mart": "rank_xendcg",
    "xendcg_mart": "rank_xendcg",
}


def canonical_objective(name: str) -> str:
    return _OBJECTIVE_ALIASES.get(name, name)


_BOOL_TRUE = {"true", "1", "yes", "on", "+", "t", "y"}
_BOOL_FALSE = {"false", "0", "no", "off", "-", "f", "n"}


@dataclass
class Config:
    """Parameters. Names/defaults mirror reference ``include/LightGBM/config.h``."""

    # -- core ---------------------------------------------------------------
    config: str = ""   # config-file path; consumed by from_cli before
                       # parameter resolution (reference application.cpp:49-82)
    task: str = "train"
    objective: str = "regression"
    boosting: str = "gbdt"
    data: str = ""
    valid: List[str] = field(default_factory=list)
    num_iterations: int = 100
    learning_rate: float = 0.1
    num_leaves: int = 31
    tree_learner: str = "serial"
    num_threads: int = 0
    device_type: str = "tpu"
    seed: int = 0
    deterministic: bool = False

    # -- learning control ---------------------------------------------------
    max_depth: int = -1
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    bagging_fraction: float = 1.0
    pos_bagging_fraction: float = 1.0
    neg_bagging_fraction: float = 1.0
    bagging_freq: int = 0
    bagging_seed: int = 3
    feature_fraction: float = 1.0
    feature_fraction_bynode: float = 1.0
    feature_fraction_seed: int = 2
    extra_trees: bool = False
    extra_seed: int = 6
    early_stopping_round: int = 0
    first_metric_only: bool = False
    max_delta_step: float = 0.0
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_gain_to_split: float = 0.0
    drop_rate: float = 0.1
    max_drop: int = 50
    skip_drop: float = 0.5
    xgboost_dart_mode: bool = False
    uniform_drop: bool = False
    drop_seed: int = 4
    top_rate: float = 0.2
    other_rate: float = 0.1
    min_data_per_group: int = 100
    max_cat_threshold: int = 32
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_cat_to_onehot: int = 4
    top_k: int = 20
    monotone_constraints: List[int] = field(default_factory=list)
    monotone_constraints_method: str = "basic"
    monotone_penalty: float = 0.0
    feature_contri: List[float] = field(default_factory=list)
    forcedsplits_filename: str = ""
    forcedbins_filename: str = ""
    refit_decay_rate: float = 0.9
    cegb_tradeoff: float = 1.0
    cegb_penalty_split: float = 0.0
    cegb_penalty_feature_lazy: List[float] = field(default_factory=list)
    cegb_penalty_feature_coupled: List[float] = field(default_factory=list)
    path_smooth: float = 0.0
    interaction_constraints: str = ""   # e.g. "[0,1,2],[2,3]" (reference
                                        # config.h:517)
    verbosity: int = 1

    # reference config.h:134-160: force col-wise / row-wise histogram
    # building.  Mapped onto hist_method in __post_init__ (the TPU analogs:
    # col-wise CPU gather == "scatter", row-wise multi-val == the Pallas
    # row-tile kernel / "onehot" MXU path).
    force_col_wise: bool = False
    force_row_wise: bool = False
    # reference config.h:548 histogram_pool_size (MB): caps the sequential
    # grower's per-leaf histogram cache (models/grower.py).  <0 = auto:
    # pooled up to 512 MB of HBM, then pool-free growth (both children
    # rebuilt per split).  The reference's unlimited-cache behavior =
    # any explicit value large enough for num_leaves histograms.
    histogram_pool_size: float = -1.0

    # -- TPU-specific (new; no reference equivalent) ------------------------
    tree_growth: str = "leafwise"  # leafwise (best-first policy, wave-batched
                                   # schedule) | leafwise_serial (one split
                                   # per round — the reference's exact
                                   # sequential order) | leafwise_masked
                                   # (sequential, O(N)-per-split variant) |
                                   # levelwise (depth-wise batched)
    leafwise_wave_size: int = 0    # frontier leaves split per round in the
                                   # wave-batched leaf-wise schedule; 0 =
                                   # auto (num_leaves // 4, capped at 64 —
                                   # K=1 i.e. exact sequential best-first
                                   # order for trees up to 7 leaves); 1 ==
                                   # exact sequential best-first order
    # auto: the static pick by platform and bin width
    # (ops/histogram.default_hist_method: XLA:CPU -> scatter, TPU -> pallas
    # for 1-byte bins, onehot for 2-byte bins)
    hist_method: str = "auto"  # auto | scatter | onehot | pallas
    # device bin-matrix layout (the reference's DenseBin<VAL_T, IS_4BIT>
    # choice, bin.h): "packed4" stores two 4-bit bins per byte —
    # (ceil(F/2), N) instead of (F, N) — so the per-round HBM binned
    # read, the streaming block cache's disk/H2D bytes, and the kernels'
    # VMEM row-tile footprint all halve; the histogram kernel's operand
    # is unpacked once, at placement (ops/hist_pallas.pack4bit layout:
    # lo nibble = feature 2p, hi = 2p+1).  Needs num_total_bin <= 16
    # (max_bin <= 15 plus the missing bin), uint8 bins, no EFB bundling,
    # the pallas hist method, and not gpu_use_dp / feature-parallel.
    # "auto" packs exactly when eligible (silent); an explicit "packed4"
    # on an ineligible config falls back to "u8" with the staged warning.
    # Trees are bit-identical across layouts (`tests/test_packed_bins.py`).
    bin_layout: str = "auto"   # auto | u8 | packed4
    hist_dtype: str = "bf16x2"     # bf16 | bf16x2 | f32 | int8 (quantized) precision
    # histogram precision for the wave grower's SUSTAINED rounds (the
    # largest slot bucket of a big wave — deep-frontier rounds whose
    # leaves hold small gradient aggregates); "" = auto: bf16x2 drops to
    # single-pass bf16 there (measured faster at equal-or-better 500-iter
    # AUC), any other hist_dtype is used unchanged.  Ramp-up rounds and
    # the root pass — where per-bin sums are large and precision-critical
    # — always use hist_dtype.  The TPU analog of the reference's
    # fp32-hist-GPU-parity precedent (docs/GPU-Performance.rst:133-160).
    # "int8sr" (OPT-IN until a device AUC-parity capture lands,
    # tools/precision_expt.py): stochastic-rounded int8 histograms
    # (ops/quantize.py) on the int8 MXU path — unbiased per-bin sums at
    # 2x bf16 throughput, extended to BOTH the sustained bucket and the
    # 16-slot ramp bucket of a K>16 wave; rounding is a deterministic
    # counter-based PRNG keyed per (iteration, round), bit-reproducible
    # given `seed`.  Plain "int8" (round-to-nearest) was measured and
    # rejected at -0.007 AUC@500 (PERF.md round 5).
    # "auto" (ROADMAP item 3a): backend-resolved policy — int8sr on TPU
    # backends (the int8 MXU path the mode targets; the flip is gated on
    # bench.py's precision_expt AUC-parity record), full bf16x2
    # everywhere else.  Opt out by setting any explicit dtype.
    hist_dtype_deep: str = ""
    # fused per-round bookkeeping in the wave grower: the frontier /
    # tree-assembly state lives in two packed tables written with ONE
    # coalesced multi-node scatter each per round, instead of ~30
    # per-field scatters (the phase-attribution harness measured the
    # scatter storm as the dominant slice of the per-iteration residual,
    # tools/phase_attrib.py).  False = legacy per-field scatters; trees
    # are bit-identical either way on the exact-fp32 scatter path
    # (tests/test_phase_attrib.py pins this).
    fused_bookkeeping: bool = True
    # software-pipelined wave rounds (models/grower_wave.py): the per-leaf
    # histogram-state scatter and the valid-row routing of round r are
    # deferred into a pending carry and issued inside round r+1 — off its
    # critical path (top-k -> partition -> histogram -> split scan), so
    # the scheduler overlaps them with the next round's MXU pass instead
    # of serializing at the while-loop body barrier.  Parent-histogram
    # reads are value-forwarded, and a post-loop drain applies the final
    # round's routing, so trees / leaf ids / valid routings are
    # bit-identical to the sequential schedule (false = the legacy
    # fully-serialized round body, kept as the bit-parity pin;
    # tests/test_wave_pipeline.py).
    async_wave_pipeline: bool = True
    # donate the score caches (train + valid) into the fused per-iteration
    # step (jax donate_argnums): the iteration's score update runs in
    # place instead of allocating a second (N, K) buffer per cache —
    # halves steady-state score HBM footprint and removes the defensive
    # copy at the dispatch boundary.  Rollback/finite-guard snapshots keep
    # explicit copies when armed (models/gbdt.py _save_rollback_state).
    # No-op on the CPU backend (XLA:CPU ignores donation).
    donate_buffers: bool = True
    # -- out-of-core streaming training (data/ subsystem) --------------
    # stream_enable=true trains through the row-block streaming trainer
    # (models/gbdt_stream.py) even on resident in-memory data: the binned
    # matrix reaches the device one block at a time (double-buffered H2D)
    # and per-row score/gradient/routing state stays host-side, so peak
    # device bytes are O(stream_block_rows * num_features) instead of
    # O(num_data * num_features).  Training data that IS a block-cache
    # directory (task=save_binary output) streams automatically.  With a
    # fixed block order the streamed run's model text is byte-identical
    # to the resident trainer at the sequential best-first schedule
    # (the parity contract, tests/test_stream_train.py).
    stream_enable: bool = False
    # rows per cache block / per H2D transfer.  The device working-set
    # knob; also the shard size task=save_binary writes.  For the strict
    # onehot-method parity contract keep it a multiple of 16384 (the
    # resident one-hot pass's own accumulation chunk); scatter (the CPU
    # oracle) is exact at any block size.
    stream_block_rows: int = 65536
    # double-buffer host->device block transfers: the next block's
    # device_put is issued before the current block's histogram pass is
    # consumed (the PR-4 predict-path overlap, applied to training)
    stream_prefetch: bool = True
    # task=save_binary output directory ("" = <data>.blocks)
    stream_cache_dir: str = ""
    # Cross-chip collective of the row-sharded (data/voting) learners:
    # "reduce_scatter" (default) maps the reference's ReduceScatter of
    # histogram blocks faithfully — each device reduces and KEEPS only its
    # F/D feature slice, finds its local best split there, and only packed
    # SplitInfo crosses chips (Allreduce-max, the SyncUpGlobalBestSplit
    # analog), cutting histogram comm bytes ~D-fold per round;
    # "allreduce" keeps the PR-2-era full-histogram lax.psum (every chip
    # materializes every feature's bins) — retained as the parity pin and
    # for A/B measurement (tools/dryrun_multichip records both);
    # "hierarchical" (ISSUE 16) is the topology-aware two-level path:
    # reduce-scatter over the fast intra-host ICI axis first, then over
    # the slow inter-host DCN axis, so only the 1/C-sliced partials ever
    # cross the slow link (parallel/cluster.make_hier_mesh — requires a
    # device count divisible into num_hosts equal hosts).
    data_parallel_collective: str = "reduce_scatter"
    num_shards: int = 0            # devices for data-parallel (0 = all available)
    # host rows of the hierarchical mesh (0 = auto: the real process
    # count in a multi-process run, 1 otherwise).  A single-process run
    # can model a pod by setting it explicitly (the 2x4 dryrun rig).
    num_hosts: int = 0
    # modeled per-link bandwidths (GB/s) behind the hierarchical
    # collective's comm table (parallel/cluster.hier_comm_table_per_round
    # "modeled-ms" column): intra-host ICI and inter-host DCN.  Defaults
    # are the v4-pod planning guesses the table shipped with; a pod
    # capture calibrates them from measured per-round ms without a code
    # change.  Purely observational — they never change collective
    # selection or results.
    hier_ici_gbps: float = 100.0
    hier_dcn_gbps: float = 10.0
    # -- serving (models/predict.py batched inference engine) ----------
    # prediction engine: "auto" keeps the host routing (native C++ bulk
    # predictor above the work threshold, vectorized numpy below);
    # "native"/"host" force those; "depthwise" is the depth-stepped
    # all-trees device walk; "pallas" pins the node tables in VMEM
    # (ops/predict_pallas.py; raises if the backend cannot lower it);
    # "fused" is the serving megakernel — one
    # Pallas pass per row tile walks every tree AND accumulates the
    # per-class scores in VMEM (plan_predict_tiles tiles the node
    # tables when they exceed the VMEM budget; staged walk with a
    # logged reason when the planner refuses, an error when the backend
    # cannot lower the kernel);
    # "scan" is the legacy per-tree scan walk, kept as the bit-parity
    # pin.
    predict_method: str = "auto"
    # prebinned serving codes (uint8/uint16) for the device walks: "auto"
    # = on whenever the ensemble's thresholds admit an EXACT serving
    # binning (models/predict.build_serving_binner), else the raw-f32
    # walk; "on"/"off" force it (on falls back with a warning when
    # exactness is impossible)
    predict_prebin: str = "auto"
    # serving-code transport width: "auto" packs two 4-bit codes per
    # byte for predict_method=fused whenever every feature's serving
    # binner fits 16 codes (reserved NaN/zero included), halving the
    # H2D bytes per row; "packed4" forces packing for any prebinned
    # device walk (refused with a warning when a feature needs more
    # than 16 codes); "u8" keeps the byte-wide codes.
    predict_code_layout: str = "auto"
    predict_bucket_min: int = 256   # smallest power-of-two row bucket of
                                    # the predictor's compile cache
    predict_chunk_rows: int = 131072  # streaming chunk: bounds device
                                    # memory and double-buffers H2D
    predict_cache_entries: int = 64  # LRU bound on the predictor's
                                    # compiled-walk cache ((bucket, kind)
                                    # keys; a long-running server seeing
                                    # many batch shapes stays bounded)
    predict_num_shards: int = 0     # >1: rows sharded over the mesh
                                    # (parallel/cluster.make_mesh)
    # reconstruct raw scores host-side in float64 from device leaf
    # indices (bit-identical to the native C++ predictor); default off —
    # the on-device f32 sum is the fast serving path
    predict_f64_scores: bool = False
    # -- online serving (serve/ subsystem; CLI task=serve) -------------
    # micro-batcher policy: a batch dispatches when it FILLS
    # serve_max_batch_rows (device occupancy) or when its oldest request
    # has waited serve_max_batch_delay_ms (p99 latency) — the
    # occupancy/latency trade as an explicit knob (serve/server.py)
    serve_max_batch_rows: int = 1024
    serve_max_batch_delay_ms: float = 2.0
    # admission control: bounded request queue in ROWS; a submit that
    # would exceed it is shed immediately (HTTP 503), never queued into
    # unbounded memory growth
    serve_queue_depth: int = 4096
    serve_timeout_ms: float = 0.0   # per-request deadline in queue; 0=off
    # overload degradation: >0 serves backlogged periods from a
    # truncated-tree predictor of this many trees (rounded down to an
    # iteration boundary); answers are flagged `degraded`
    serve_degrade_trees: int = 0
    serve_http_port: int = 8080     # task=serve HTTP listener; 0 = pick
                                    # an ephemeral port (logged)
    serve_duration_s: float = 0.0   # task=serve runs this long (0 = until
                                    # interrupted); bounded runs for CI
    # -- serving failure domains (serve/server.py, serve/registry.py) --
    # transient device errors (a failed H2D, a flaky dispatch) are
    # retried on the dispatcher with exponential backoff before the
    # batch is failed; 0 disables retries
    serve_retry_max: int = 2
    serve_retry_backoff_ms: float = 5.0
    # circuit breaker: this many CONSECUTIVE failed device batches
    # auto-roll the registry back to the previous version (a bad publish
    # that slipped past validation un-ships itself); 0 disables
    serve_breaker_failures: int = 3
    # dispatcher watchdog: a device batch running longer than this is
    # declared stalled — its requests fail with 503 (DispatcherStalled)
    # instead of hanging the queue, and a dead dispatcher thread is
    # restarted; 0 disables the watchdog
    serve_watchdog_ms: float = 0.0
    # publish-time golden probe: the candidate predictor must reproduce
    # the host-tree walk bit-exactly on this many seeded probe rows
    # BEFORE the atomic swap (a corrupt model can never reach traffic);
    # 0 disables the semantic probe (structural+finite checks remain)
    serve_probe_rows: int = 64
    # -- multi-tenant serving (ISSUE 20; serve/tenants.py) -------------
    # bounded ModelRegistry history: the registry retains the current
    # version plus the most recent keep_versions-1 predecessors per
    # lineage (rollback stays safe down to the oldest kept); continuous
    # publish churn can no longer grow memory without bound
    registry_keep_versions: int = 4
    # task=serve tenant manifest: "name[:weight],name[:weight],..." —
    # stands up one named model lineage per entry with that fair-share
    # admission weight (default 1.0).  Empty = single-tenant serving,
    # bit-identical to the pre-tenancy behavior
    tenant_manifest: str = ""
    # placement controller (serve/placement.py): number of replicas each
    # tenant is pinned to; 0 disables placement (every tenant routes to
    # every replica)
    placement_replicas_per_tenant: int = 0
    # migration triggers: a tenant whose fast-window SLO burn rate
    # exceeds placement_burn_threshold OR whose queue occupancy exceeds
    # placement_occupancy_frac is a candidate to move to the
    # least-loaded replica subset; per-tenant cooldown bounds churn
    placement_burn_threshold: float = 2.0
    placement_occupancy_frac: float = 0.75
    placement_cooldown_s: float = 30.0
    # -- training robustness ------------------------------------------
    # guard on the grad/hess pass: "off" (no cost) | "warn" / "raise"
    # (detect NaN/Inf propagation at each iteration boundary — one
    # scalar device read) | "clamp" (zero non-finite grad/hess entries
    # inside the traced step; a poisoned row behaves like a bagged-out
    # row and training continues on the surviving rows)
    finite_guard: str = "off"
    # snapshots/checkpoints retained on disk by the CLI (last N of each;
    # >= 2 so a torn newest file always has an intact predecessor)
    snapshot_keep: int = 2
    profile_dir: str = ""          # write a jax.profiler device trace of
                                   # training here; hist/split/partition
                                   # phases carry lgbm.* named scopes (the
                                   # USE_TIMETAG analog, utils/common.h)
    # -- observability (obs/ subsystem) --------------------------------
    # arm the host-side span tracer (obs/trace.py) for the run: nested
    # spans (iteration / streaming block pipeline / checkpoint / serve
    # request legs) into a bounded ring, exported as Chrome trace-event
    # JSON.  HARD-OFF by default: the disarmed path is one flag check.
    obs_trace: bool = False
    # task=train: write the Chrome trace JSON here at the end of the run
    # (atomic tmp+fsync+rename, fileio.atomic_write_bytes).  Setting it
    # implies obs_trace=true.  Composes with profile_dir — profile_dir
    # captures the DEVICE trace via jax.profiler, trace_out the HOST
    # span timeline; set both to line the two up in Perfetto.  When both
    # tracers would contend (they don't share state), profile_dir wins
    # nothing: precedence is simply "each writes its own artifact".
    trace_out: str = ""
    # span ring capacity while armed; the OLDEST events are overwritten
    # under sustained load and the export reports the dropped count
    obs_ring_events: int = 65536
    # -- forensics & fleet telemetry (ISSUE 10) ------------------------
    # always-on structured event ring capacity (obs/events.py): the
    # black-box tail every forensic bundle carries
    obs_event_ring: int = 4096
    # crash-dump flight recorder (obs/dump.py): arm it at this directory
    # — the first crash-grade moment (unhandled exception, fatal,
    # SIGTERM, watchdog stall, injected kill) atomically writes ONE
    # forensic bundle there.  Empty = recorder disarmed (the
    # LGBMV1_CRASH_DIR env var is the subprocess-spanning equivalent)
    crash_dir: str = ""
    # per-process telemetry artifact export (obs/agg.py): at the end of
    # a task=train / task=serve run, write <role>-<host>-<pid>.trace.json
    # / .metrics.json / .events.jsonl here for tools/obs_aggregate.py to
    # merge into one Perfetto timeline.  Empty = no export
    # (LGBMV1_OBS_DIR is the env equivalent)
    obs_dir: str = ""
    # -- serving SLOs (serve/slo.py; GET /slo) -------------------------
    # availability: fraction of requests answered successfully (sheds,
    # timeouts, batch errors and watchdog failures all spend budget)
    serve_slo_availability_target: float = 0.999
    # latency: fraction of SUCCESSFUL requests under the objective
    serve_slo_latency_ms: float = 50.0
    serve_slo_latency_target: float = 0.99
    # multi-window burn-rate evaluation windows (page needs BOTH the
    # fast and slow window over threshold — blips don't page, and pages
    # clear when the fast window recovers)
    serve_slo_fast_window_s: float = 60.0
    serve_slo_slow_window_s: float = 600.0
    # -- fault-tolerant fleet (ISSUE 11) -------------------------------
    # task=serve with serve_replicas > 1 stands up a replicated fleet
    # (serve/fleet.py: N replica Servers, two-phase coordinated publish)
    # behind the self-healing router (serve/router.py); 1 = single
    # Server, the pre-fleet behavior
    serve_replicas: int = 1
    # router health poller: a replica failing router_eject_after
    # consecutive health checks (dead/wedged dispatcher, nothing
    # published) is ejected from the candidate set; readmitted after
    # router_readmit_after consecutive healthy checks
    router_health_period_ms: float = 25.0
    router_eject_after: int = 2
    router_readmit_after: int = 2
    # per-request self-healing: retryable replica failures are retried
    # on a DIFFERENT replica up to router_retry_max extra attempts;
    # router_hedge_ms > 0 launches a hedge attempt on another replica
    # when the primary hasn't answered within that delay (first answer
    # wins, the loser is discarded without double-counting)
    router_retry_max: int = 2
    router_hedge_ms: float = 0.0
    # whole-request deadline across retries/hedges; exhaustion returns
    # 504 (RequestTimeout), never 500; 0 = no deadline
    router_deadline_ms: float = 0.0
    # -- model & data drift observability (ISSUE 14; obs/drift.py) -----
    # serving-side train/serve skew detection: > 0 arms a bounded
    # sampling ring of this many rows on the serve path (HARD-OFF
    # default 0 — the disarmed serving path is one integer compare).
    # Sampled request rows re-bin through the published version's own
    # bin mappers (the training reference obs/model.py captures) and
    # GET /drift reports per-feature PSI, unseen-bin/out-of-range/NaN
    # counters and prediction-score drift; features crossing
    # drift_psi_threshold publish drift.alert events and the top
    # drift_top_k features get Prometheus gauges (capped cardinality)
    drift_sample_rows: int = 0
    drift_per_batch_rows: int = 64    # rows copied from one device batch
    drift_min_rows: int = 256         # sampled rows before PSI is judged
    drift_psi_threshold: float = 0.25  # conventional "major shift" bar
    drift_top_k: int = 8              # per-feature gauges / top list cap
    # equal-mass PSI buckets per feature: PSI over the raw max_bin-wide
    # training bins has a ~bins/window noise floor; the conventional
    # 10-20-bucket practice keeps clean traffic under the alert bar
    drift_psi_groups: int = 16
    # sample every Nth device batch: the row copy is tens of us, drift
    # is a minutes-scale phenomenon — striding amortizes the armed
    # serving cost 1/N (the <= 2% contract headroom on small batches)
    drift_sample_stride: int = 4
    # training-score reference histogram resolution (obs/model.py
    # capture_reference; also the serving-side score-drift comparison)
    drift_score_bins: int = 16

    # -- elastic training recovery (parallel/elastic.py) ---------------
    # worker lease staleness bound: a peer whose lease file goes stale
    # past this is declared dead and survivors abort for re-bootstrap
    # (the bounded detection window)
    elastic_lease_timeout_s: float = 3.0
    # re-bootstraps the elastic coordinator attempts before giving up;
    # each resumes bit-exactly from the newest intact checkpoint bundle
    elastic_max_restarts: int = 2

    # -- IO -----------------------------------------------------------------
    max_bin: int = 255
    max_bin_by_feature: List[int] = field(default_factory=list)
    min_data_in_bin: int = 3
    bin_construct_sample_cnt: int = 200000
    # reference config.h:592: pre-filter features that cannot satisfy
    # min_data_in_leaf on any split (BinMapper marks them trivial)
    feature_pre_filter: bool = True
    # reference config.h:620 is_enable_sparse: SparseBin storage toggle.
    # EXPLICIT no-op here: there is no sparse bin storage to toggle — wide
    # sparse inputs are handled by EFB bundles + from_csr (io/bundle.py)
    is_enable_sparse: bool = True
    data_random_seed: int = 1
    output_model: str = "LightGBM_model.txt"
    snapshot_freq: int = -1
    input_model: str = ""
    output_result: str = "LightGBM_predict_result.txt"
    initscore_filename: str = ""
    valid_data_initscores: List[str] = field(default_factory=list)
    pre_partition: bool = False
    enable_bundle: bool = True
    max_conflict_rate: float = 0.0  # EFB conflict budget (fraction of rows
                                    # where bundled features may collide —
                                    # reference config.h max_conflict_rate)
    use_missing: bool = True
    zero_as_missing: bool = False
    two_round: bool = False
    save_binary: bool = False
    header: bool = False
    label_column: str = ""
    weight_column: str = ""
    group_column: str = ""
    ignore_column: str = ""
    categorical_feature: str = ""
    predict_raw_score: bool = False
    predict_leaf_index: bool = False
    predict_contrib: bool = False
    start_iteration_predict: int = 0
    num_iteration_predict: int = -1
    predict_disable_shape_check: bool = False
    # reference config.h:886: importance type written into the model file
    # (0 = split counts, 1 = total gains)
    saved_feature_importance_type: int = 0
    pred_early_stop: bool = False
    pred_early_stop_freq: int = 10
    pred_early_stop_margin: float = 10.0
    convert_model_language: str = ""
    convert_model: str = "gbdt_prediction.cpp"

    # -- objective ----------------------------------------------------------
    num_class: int = 1
    is_unbalance: bool = False
    scale_pos_weight: float = 1.0
    sigmoid: float = 1.0
    boost_from_average: bool = True
    reg_sqrt: bool = False
    alpha: float = 0.9
    fair_c: float = 1.0
    poisson_max_delta_step: float = 0.7
    tweedie_variance_power: float = 1.5
    lambdarank_truncation_level: int = 20
    lambdarank_norm: bool = True
    label_gain: List[float] = field(default_factory=list)
    # reference config.h:797 (rank_xendcg sampling seed; config.cpp:198-201
    # re-draws it from `seed` unless set explicitly)
    objective_seed: int = 5

    # -- metric -------------------------------------------------------------
    metric: List[str] = field(default_factory=list)
    metric_freq: int = 1
    is_provide_training_metric: bool = False
    eval_at: List[int] = field(default_factory=lambda: [1, 2, 3, 4, 5])
    multi_error_top_k: int = 1
    auc_mu_weights: List[float] = field(default_factory=list)

    # -- network ------------------------------------------------------------
    num_machines: int = 1
    local_listen_port: int = 12400
    machines: str = ""            # host:port list (reference socket linker);
                                  # multi-host here goes via jax.distributed
    time_out: int = 120
    machine_list_filename: str = ""

    # -- GPU (reference config.h:976-1005) ----------------------------------
    # gpu_platform_id / gpu_device_id select an OpenCL device; EXPLICIT
    # no-ops here — device selection is JAX's (jax.devices()/JAX_PLATFORMS).
    gpu_platform_id: int = -1
    gpu_device_id: int = -1
    # gpu_use_dp = double-precision GPU histograms; mapped onto
    # hist_dtype="f32" in __post_init__ (f32 is this framework's highest
    # histogram precision; fp64 is not MXU-native)
    gpu_use_dp: bool = False

    # ------------------------------------------------------------------
    def __post_init__(self):
        from .utils.log import set_verbosity

        set_verbosity(self.verbosity)
        self.objective = canonical_objective(self.objective)
        if self.objective in ("multiclass", "multiclassova") and self.num_class <= 1:
            raise ValueError("num_class must be >1 for multiclass objectives")
        if self.force_col_wise and self.force_row_wise:
            # reference config.cpp CheckParamConflict fatals on both
            raise ValueError(
                "Cannot set both force_col_wise and force_row_wise")
        if self.hist_method == "auto":
            # reference force_*_wise picks the histogram build strategy
            # (dataset.cpp:590-684 auto-benchmark override); TPU analogs:
            # col-wise per-feature gather = "scatter", row-wise multi-feature
            # tiles = the "onehot" MXU path
            if self.force_col_wise:
                self.hist_method = "scatter"
            elif self.force_row_wise:
                self.hist_method = "onehot"
        if self.hist_method not in ("auto", "scatter", "onehot", "pallas"):
            raise ValueError(
                f"hist_method={self.hist_method!r}: expected auto | scatter "
                "| onehot | pallas")
        if self.bin_layout not in ("auto", "u8", "packed4"):
            raise ValueError(
                f"bin_layout={self.bin_layout!r}: expected auto | u8 "
                "| packed4")
        if self.data_parallel_collective not in (
                "reduce_scatter", "allreduce", "hierarchical"):
            raise ValueError(
                f"data_parallel_collective="
                f"{self.data_parallel_collective!r}: expected "
                "reduce_scatter | allreduce | hierarchical")
        if self.num_hosts < 0:
            raise ValueError("num_hosts must be >= 0 (0 = auto-detect)")
        if self.hier_ici_gbps <= 0 or self.hier_dcn_gbps <= 0:
            raise ValueError("hier_ici_gbps / hier_dcn_gbps must be > 0 "
                             "(modeled link bandwidths of the "
                             "hierarchical collective's comm table)")
        if self.predict_method not in (
                "auto", "native", "host", "depthwise", "pallas", "fused",
                "scan"):
            raise ValueError(
                f"predict_method={self.predict_method!r}: expected auto | "
                "native | host | depthwise | pallas | fused | scan")
        if self.predict_prebin not in ("auto", "on", "off"):
            raise ValueError(
                f"predict_prebin={self.predict_prebin!r}: expected "
                "auto | on | off")
        if self.predict_code_layout not in ("auto", "u8", "packed4"):
            raise ValueError(
                f"predict_code_layout={self.predict_code_layout!r}: "
                "expected auto | u8 | packed4")
        if self.serve_max_batch_rows < 1:
            raise ValueError("serve_max_batch_rows must be >= 1")
        if self.serve_max_batch_delay_ms < 0:
            raise ValueError("serve_max_batch_delay_ms must be >= 0")
        if self.serve_queue_depth < self.serve_max_batch_rows:
            raise ValueError("serve_queue_depth must be >= "
                             "serve_max_batch_rows (admission control "
                             "must admit at least one full batch)")
        if self.finite_guard not in ("off", "warn", "raise", "clamp"):
            raise ValueError(
                f"finite_guard={self.finite_guard!r}: expected "
                "off | warn | raise | clamp")
        if self.serve_retry_max < 0 or self.serve_retry_backoff_ms < 0:
            raise ValueError("serve_retry_max / serve_retry_backoff_ms "
                             "must be >= 0")
        if self.serve_breaker_failures < 0:
            raise ValueError("serve_breaker_failures must be >= 0 "
                             "(0 disables the circuit breaker)")
        if self.serve_watchdog_ms < 0:
            raise ValueError("serve_watchdog_ms must be >= 0 "
                             "(0 disables the watchdog)")
        if self.serve_probe_rows < 0:
            raise ValueError("serve_probe_rows must be >= 0")
        if self.registry_keep_versions < 1:
            raise ValueError("registry_keep_versions must be >= 1 "
                             "(the current version is always kept)")
        if self.placement_replicas_per_tenant < 0:
            raise ValueError("placement_replicas_per_tenant must be "
                             ">= 0 (0 disables placement)")
        if self.placement_burn_threshold <= 0:
            raise ValueError("placement_burn_threshold must be > 0")
        if not 0 < self.placement_occupancy_frac <= 1:
            raise ValueError("placement_occupancy_frac must be in "
                             "(0, 1]")
        if self.placement_cooldown_s < 0:
            raise ValueError("placement_cooldown_s must be >= 0")
        if self.stream_block_rows < 1:
            raise ValueError("stream_block_rows must be >= 1")
        if self.snapshot_keep < 2:
            raise ValueError("snapshot_keep must be >= 2 (a torn newest "
                             "snapshot needs an intact predecessor)")
        if self.obs_ring_events < 16:
            raise ValueError("obs_ring_events must be >= 16")
        if self.obs_event_ring < 16:
            raise ValueError("obs_event_ring must be >= 16")
        for name in ("serve_slo_availability_target",
                     "serve_slo_latency_target"):
            v = getattr(self, name)
            if not 0.0 < v < 1.0:
                raise ValueError(f"{name} must be in (0, 1), got {v}")
        if self.serve_slo_latency_ms <= 0:
            raise ValueError("serve_slo_latency_ms must be > 0")
        if not 0 < self.serve_slo_fast_window_s \
                <= self.serve_slo_slow_window_s:
            raise ValueError(
                "serve_slo windows need 0 < fast_window_s <= "
                "slow_window_s (the page rule evaluates both)")
        if self.serve_replicas < 1:
            raise ValueError("serve_replicas must be >= 1")
        if self.router_health_period_ms <= 0:
            raise ValueError("router_health_period_ms must be > 0")
        if self.router_eject_after < 1 or self.router_readmit_after < 1:
            raise ValueError("router_eject_after / router_readmit_after "
                             "must be >= 1")
        if self.router_retry_max < 0 or self.router_hedge_ms < 0 \
                or self.router_deadline_ms < 0:
            raise ValueError("router_retry_max / router_hedge_ms / "
                             "router_deadline_ms must be >= 0")
        if self.drift_sample_rows < 0:
            raise ValueError("drift_sample_rows must be >= 0 (0 = off)")
        if self.drift_per_batch_rows < 1:
            raise ValueError("drift_per_batch_rows must be >= 1")
        if self.drift_min_rows < 1:
            raise ValueError("drift_min_rows must be >= 1")
        if self.drift_psi_threshold <= 0:
            raise ValueError("drift_psi_threshold must be > 0")
        if self.drift_top_k < 1:
            raise ValueError("drift_top_k must be >= 1")
        if self.drift_score_bins < 2:
            raise ValueError("drift_score_bins must be >= 2")
        if self.drift_psi_groups < 2:
            raise ValueError("drift_psi_groups must be >= 2")
        if self.drift_sample_stride < 1:
            raise ValueError("drift_sample_stride must be >= 1")
        if self.elastic_lease_timeout_s <= 0:
            raise ValueError("elastic_lease_timeout_s must be > 0 "
                             "(the peer-loss detection window)")
        if self.elastic_max_restarts < 0:
            raise ValueError("elastic_max_restarts must be >= 0")
        if self.trace_out:
            # the artifact path is the arming intent (documented knob
            # precedence: trace_out implies obs_trace)
            self.obs_trace = True
        if self.predict_cache_entries < 2:
            raise ValueError("predict_cache_entries must be >= 2 (the "
                             "walk and its score executable share a "
                             "bucket)")
        if self.hist_dtype_deep not in (
                "", "auto", "f32", "bf16", "bf16x2", "int8", "int8sr"):
            raise ValueError(
                f"hist_dtype_deep={self.hist_dtype_deep!r}: expected one of "
                "auto | f32 | bf16 | bf16x2 | int8 | int8sr (or empty for "
                "the legacy bf16-drop policy)")
        if self.gpu_use_dp and not self.hist_dtype_deep:
            # the double-precision request covers deep wave rounds too —
            # but an EXPLICIT hist_dtype_deep wins (the trainer documents
            # "hist_dtype_deep overrides"; stomping it broke that contract)
            self.hist_dtype_deep = "f32"
        if self.gpu_use_dp and self.hist_dtype in ("bf16", "bf16x2", "int8"):
            # gpu_use_dp = highest-precision device histograms
            # (reference gpu_tree_learner.h:79 hist_t selection)
            self.hist_dtype = "f32"

    # ------------------------------------------------------------------
    @property
    def num_tree_per_iteration(self) -> int:
        if self.objective in ("multiclass", "multiclassova"):
            return self.num_class
        # custom objective (objective=none) with num_class>1 still trains one
        # tree per class — reference gbdt.cpp:71 sets num_tree_per_iteration_
        # from num_class when the objective function is null
        if self.objective in ("none", "custom", "") and self.num_class > 1:
            return self.num_class
        return 1

    @property
    def label_gain_or_default(self) -> List[float]:
        if self.label_gain:
            return list(self.label_gain)
        return [float((1 << i) - 1) for i in range(31)]

    # ------------------------------------------------------------------
    @classmethod
    def from_dict(cls, params: Dict[str, Any]) -> "Config":
        cfg = cls.__new__(cls)
        # set defaults first
        for f in dataclasses.fields(cls):
            if f.default is not dataclasses.MISSING:
                setattr(cfg, f.name, f.default)
            else:
                setattr(cfg, f.name, f.default_factory())  # type: ignore
        cfg.update(params)
        cfg.__post_init__()
        return cfg

    def update(self, params: Dict[str, Any]) -> None:
        resolved: Dict[str, Any] = {}
        for key, value in params.items():
            name = _ALIASES.get(key, key)
            if name in resolved and key != name:
                continue  # explicit name beats alias (reference: KeyAliasTransform)
            resolved[name] = value
        fields = {f.name: f for f in dataclasses.fields(self)}
        for name, value in resolved.items():
            if name not in fields:
                log_warning(f"Unknown parameter: {name}")
                continue
            setattr(self, name, _coerce(value, fields[name], name))

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    # reference: Config::KV2Map config.h:80 — parse "key=value" strings
    @staticmethod
    def kv2map(args: List[str]) -> Dict[str, str]:
        out: Dict[str, str] = {}
        for arg in args:
            arg = arg.split("#", 1)[0].strip()
            if not arg:
                continue
            if "=" not in arg:
                log_warning(f"Unknown option: {arg}")
                continue
            k, v = arg.split("=", 1)
            out[k.strip()] = v.strip()
        return out

    @classmethod
    def from_cli(cls, argv: List[str]) -> "Config":
        kv = cls.kv2map(argv)
        config_file = kv.get("config", kv.get("config_file", ""))
        file_kv: Dict[str, str] = {}
        if config_file:
            from .utils.fileio import open_file

            with open_file(config_file) as fh:
                file_kv = cls.kv2map(fh.read().splitlines())
        # CLI args override config-file values (reference: application.cpp:49-82)
        file_kv.update(kv)
        file_kv.pop("config", None)
        file_kv.pop("config_file", None)
        return cls.from_dict(file_kv)


def _coerce(value: Any, f: dataclasses.Field, name: str) -> Any:
    ftype = f.type if isinstance(f.type, str) else getattr(f.type, "__name__", str(f.type))
    is_list = "List" in str(ftype)
    if is_list:
        if isinstance(value, (list, tuple)):
            items = list(value)
        elif isinstance(value, str):
            items = [s for s in value.replace(",", " ").split() if s]
        else:
            items = [value]
        if "int" in str(ftype):
            return [int(float(x)) for x in items]
        if "float" in str(ftype):
            return [float(x) for x in items]
        return [str(x) for x in items]
    default = f.default
    if isinstance(default, bool):
        if isinstance(value, str):
            lv = value.strip().lower()
            if lv in _BOOL_TRUE:
                return True
            if lv in _BOOL_FALSE:
                return False
            raise ValueError(f"Cannot parse bool parameter {name}={value}")
        return bool(value)
    if isinstance(default, int):
        return int(float(value))
    if isinstance(default, float):
        return float(value)
    return str(value)
