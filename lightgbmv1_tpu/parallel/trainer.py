"""Distributed tree learners over a jax.sharding Mesh.

TPU-native re-design of the reference's parallel tree learners and network
stack:

* ``tree_learner=data``  — DataParallelTreeLearner
  (reference: src/treelearner/data_parallel_tree_learner.cpp): rows are
  sharded over the ``data`` mesh axis; each device builds local histograms
  and — faithfully to the reference now — a ``lax.psum_scatter`` over the
  feature axis replaces its ReduceScatter of histogram blocks
  (``FindBestSplits`` :155-173, ``HistogramSumReducer`` bin.h:44-57): each
  device reduces and KEEPS only its ``F/D`` feature slice, searches its
  local best split there, and an all_gather + deterministic-tie-break
  argmax over packed SplitInfo (``SyncUpGlobalBestSplit``,
  parallel_tree_learner.h:190-213) elects the winner — so only split
  metadata, never histograms, crosses chips after the reduce, cutting
  histogram comm payload ~D-fold per round.  Under
  ``hist_dtype_deep=int8sr`` the reduce runs on raw int32 histograms
  (global-scale quantization, ops/quantize.py) and dequantization folds
  into the now-local split scan.  ``config.data_parallel_collective=
  "allreduce"`` keeps the previous full-histogram ``lax.psum`` (split
  selection replicated, no split sync) as the parity pin; both paths grow
  identical trees thanks to the reduction-order-invariant tie-break
  (ops/split.py tie_tol).  The root grad/hess Allreduce (:126-151) stays a
  ``psum`` of the g3 totals either way.
* ``tree_learner=feature`` — FeatureParallelTreeLearner
  (reference: src/treelearner/feature_parallel_tree_learner.cpp): every
  device holds all rows (data replicated) but builds histograms and searches
  splits only for its feature shard; the winning split is chosen by an
  ``all_gather`` of packed SplitInfo + argmax — the analog of
  ``SyncUpGlobalBestSplit``'s Allreduce-max over serialized SplitInfo pairs
  (parallel_tree_learner.h:190-213).
* ``tree_learner=voting`` — VotingParallelTreeLearner (PV-Tree)
  (reference: src/treelearner/voting_parallel_tree_learner.cpp): row-sharded
  like ``data``, but each shard votes for its local top-k features, the
  global top-2k winners are selected by a vote psum (``GlobalVoting``
  :152-180), and only those features' histograms are reduced across shards
  (``CopyLocalHistogram``) — comm drops from O(F·B) to O(2k·B) per split.
  The selective reduce rides the same sharded primitive as the data
  learner: under ``data_parallel_collective=reduce_scatter`` the selected
  features' histograms are psum_scattered so each chip keeps 2k/D of them
  and syncs only SplitInfo, and under int8sr the reduce sums the RAW
  quantized integers with one dequantize after the collective (the
  selective reduce honors the integer domain — previously only the data
  branch did; its wire dtype stays f32 because the op is shared with
  full-precision rounds, but the summed values are exact integers).  With
  ``top_k >= num_features`` it is exactly the data-parallel learner.

The socket/MPI ``Network``/``Linkers`` machinery of the reference
(src/network/) has no equivalent here by design: XLA emits the collectives
over ICI/DCN. Multi-host scaling initializes ``jax.distributed`` through
``parallel/cluster.py`` and spans the same Mesh across processes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import Config
from ..models.grower import make_leafwise_grower
from ..models.grower_wave import make_wave_grower
from ..models.tree import TreeArrays
from ..obs import xla as obs_xla
from ..ops.histogram import (default_hist_method, hist_one_leaf, hist_wave,
                             hist_wave_quant)
from ..ops.split import (FeatureMeta, SplitParams, SplitResult,
                         find_best_split, leaf_gain, narrow_meta,
                         take_columns, tie_tol)
from ..utils.log import log_fatal, log_info, log_warning
from .cluster import (comm_table_per_round, hier_comm_table_per_round,
                      make_hier_mesh, make_mesh, publish_comm_metrics,
                      publish_hier_comm_metrics)

def _make_mesh(num_shards: int, axis: str) -> Mesh:
    return make_mesh(num_shards, axis)   # parallel/cluster.py (topology home)


def shard_rows(fn, mesh: Mesh, axis: str = "rows", n_replicated: int = 0):
    """Row-shard a batch function over ``mesh``: the first
    ``n_replicated`` arguments (model tables) are replicated on every
    chip, the remaining arguments split on their leading (row) axis, and
    outputs come back row-sharded.  No collective runs at all — this is
    the embarrassingly-parallel serving layout (the reference's OMP
    row-partitioned Predictor, predictor.hpp:105-135, mapped onto chips);
    used by models/predict.BatchPredictor for sharded inference."""

    def wrapped(*args):
        in_specs = tuple([P()] * n_replicated
                         + [P(axis)] * (len(args) - n_replicated))
        sharded = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                            out_specs=P(axis), check_vma=False)
        return sharded(*args)

    return wrapped


def _pack_split(res: SplitResult) -> jnp.ndarray:
    """SplitInfo wire format for the cross-shard argmax (reference:
    SplitInfo::CopyTo, split_info.hpp — fixed-size serialization). The
    categorical bitset words ride along bit-exactly via a f32 bitcast."""
    bits_f32 = lax.bitcast_convert_type(res.cat_bitset, jnp.float32)
    return jnp.concatenate([
        jnp.stack([res.gain, res.feature.astype(jnp.float32),
                   res.threshold_bin.astype(jnp.float32),
                   res.default_left.astype(jnp.float32),
                   res.is_cat.astype(jnp.float32)]),
        res.left_sum, res.right_sum, bits_f32,
    ])


def _unpack_split(v: jnp.ndarray) -> SplitResult:
    return SplitResult(
        gain=v[0],
        feature=v[1].astype(jnp.int32),
        threshold_bin=v[2].astype(jnp.int32),
        default_left=v[3] > 0.5,
        left_sum=v[5:8],
        right_sum=v[8:11],
        is_cat=v[4] > 0.5,
        cat_bitset=lax.bitcast_convert_type(v[11:], jnp.uint32),
    )


COLLECTIVE_SCOPE = "lgbm.collective"


def _reduce_bytes(what: str, x, times: int = 1) -> None:
    """Trace time: ``dp_reduce_bytes_per_round{what}`` holds the bytes one
    device hands the largest cross-chip op of that kind in a round (the
    histogram block before its reduction, a round's gathered split
    records, the root's and the renewal's sums), from the static shapes."""
    from ..obs.metrics import default_registry

    default_registry().gauge(
        "dp_reduce_bytes_per_round",
        "Bytes a device hands one round's cross-chip op, by what it carries",
        label_names=("what",)).labels(what=what).set_max(
            float(x.size * x.dtype.itemsize * times))


def _scan_columns(owned: int, scanned: int) -> None:
    """Trace time: ``dp_scan_columns{what}`` holds the histogram columns a
    device owns after the reduce-scatter and the columns of the array its
    split scan is handed, from the static shapes."""
    from ..obs.metrics import default_registry

    gauge = default_registry().gauge(
        "dp_scan_columns",
        "Histogram columns a device owns after the reduce-scatter, and "
        "columns its split scan reads", label_names=("what",))
    gauge.labels(what="owned").set(float(owned))
    gauge.labels(what="scanned").set(float(scanned))


def _psum(what: str, x, axes):
    """``lax.psum`` of a row-sharded learner, traced: under
    ``lgbm.collective``, its operand counted in the gauge."""
    _reduce_bytes(what, x)
    with jax.named_scope(COLLECTIVE_SCOPE):
        return lax.psum(x, axes)


def _psum_scatter(x, axis, dim):
    with jax.named_scope(COLLECTIVE_SCOPE):
        return lax.psum_scatter(x, axis, scatter_dimension=dim, tiled=True)


def _over_row_shards(grow, mesh: Mesh, row_axes, placed, N: int, N_pad: int):
    """A row-sharded learner's ``grow``: the grower over the mesh on what
    was ``placed`` (``_binned_specs``), g3 with zero rows up to the shards'
    ``N_pad``, the leaf ids cut back to ``N``."""
    sharded = jax.shard_map(
        grow,
        mesh=mesh,
        in_specs=(_binned_specs(placed, row_axes), P(row_axes, None),
                  P(), P(), P()),
        out_specs=(
            jax.tree_util.tree_map(lambda _: P(), TreeArrays(
                *([0] * len(TreeArrays._fields)))),
            P(row_axes),
            P(),
        ),
        check_vma=False,
    )

    def grow_fn(binned, g3, base_mask, key, cegb_used):
        with jax.named_scope("lgbm.sample"):    # weightless rows, as bagged
            g3p = jnp.pad(g3, ((0, N_pad - N), (0, 0)))
        tree, leaf_id, third = sharded(binned, g3p, base_mask, key,
                                       cegb_used)
        with jax.named_scope("lgbm.sample"):
            return tree, leaf_id[:N], third

    return grow_fn


def _sync_best_split(local: SplitResult, parent_sum, params: SplitParams,
                     axis, children: int = 1) -> SplitResult:
    """Elect the global best split from per-shard locals — the reference's
    ``SyncUpGlobalBestSplit`` Allreduce-max over serialized SplitInfo
    (parallel_tree_learner.h:190-213), shared by the feature-parallel,
    reduce-scatter data-parallel and sharded voting learners.  ``axis``
    may be a tuple of mesh axes (the hierarchical ``("host", "chip")``
    mesh): the all_gather then spans both levels, major axis first, so
    the election sees every shard in device-linear order.

    The winner must be DEVICE-COUNT-INVARIANT: gains carry f32
    reduction-order noise, so candidates within ``tie_tol`` of the best
    (ops/split.py — the same band the per-shard search used internally)
    are tied and the LOWEST FEATURE ID wins, matching the serial search's
    first-feature-in-band rule exactly (SplitInfo::operator> tie-break,
    split_info.hpp:147-152)."""
    # the records' packing and the election ride the exchange's scope: they
    # exist only because there is one
    with jax.named_scope(COLLECTIVE_SCOPE):
        allp = lax.all_gather(_pack_split(local), axis)    # (ndev, 11 + W)
        # ``children``: how many of these one round gathers (the wave
        # grower vmaps this over its 2K children)
        _reduce_bytes("split", allp, children)
        g = allp[:, 0]
        m = jnp.max(g)
        scale = leaf_gain(parent_sum[0], parent_sum[1], params)
        in_band = g >= m - tie_tol(m, scale)
        feat = jnp.where(in_band, allp[:, 1], jnp.inf)
        return _unpack_split(allp[jnp.argmin(feat)])


def parse_interaction_constraints(spec, num_features: int):
    """'[0,1,2],[2,3]' -> (G, F) bool group matrix, or None when unset
    (reference: config.h:517 interaction_constraints,
    Config::Set -> interaction_constraints_vector)."""
    import re

    if not spec:
        return None
    groups = []
    for m in re.findall(r"\[([\d,\s]*)\]", str(spec)):
        idx = [int(x) for x in m.replace(",", " ").split()]
        row = np.zeros(num_features, bool)
        row[[i for i in idx if i < num_features]] = True
        groups.append(row)
    if not groups:
        return None
    return np.stack(groups)


def _cegb_lazy(config: Config, num_features: int, learner: str,
               levelwise: bool):
    """cegb_penalty_feature_lazy validated -> (F,) np array or None.
    Implemented by the masked sequential leaf-wise grower (per-row marks);
    other learners/growth orders warn and drop it, like the reference's
    serial-learner-only CEGB."""
    pen = config.cegb_penalty_feature_lazy
    if not pen:
        return None
    if len(pen) != num_features:
        log_fatal("cegb_penalty_feature_lazy should be the same size as "
                  f"feature number ({len(pen)} vs {num_features})")
    if learner not in ("serial", "") or levelwise:
        log_warning("cegb_penalty_feature_lazy requires the serial "
                    "leaf-wise learner; lazy feature costs are ignored for "
                    f"tree_learner={learner or 'serial'}"
                    + (", tree_growth=levelwise" if levelwise else ""))
        return None
    return np.asarray(pen, np.float64)


def _cegb_coupled(config: Config, num_features: int):
    """cegb_penalty_feature_coupled padded/validated -> (F,) or None."""
    pen = config.cegb_penalty_feature_coupled
    if not pen:
        return None
    if len(pen) != num_features:
        log_fatal("cegb_penalty_feature_coupled should be the same size as "
                  f"feature number ({len(pen)} vs {num_features})")
    return np.asarray(pen, np.float64)


def parse_forced_splits(filename: str, bin_mappers, num_leaves: int):
    """forcedsplits_filename JSON -> (S, 5) [parent_step, side, feature, bin,
    dl] in BFS order (reference: SerialTreeLearner::ForceSplits,
    serial_tree_learner.cpp:427-539; JSON format {'feature': f,
    'threshold': t, 'left': {...}, 'right': {...}}).

    Leaf ids are NOT precomputed: a forced step can be skipped at runtime
    (empty child), which shifts every later leaf index, so each entry names
    its PARENT forced step (-1 = root) and which child leaf (0 = left,
    1 = right) it splits; the grower resolves the realized leaf id from the
    tracked per-step [left, right] leaves (the analog of the reference's
    ``left_``/``right_`` queues carrying actual leaf indices)."""
    import json

    if not filename:
        return None
    from ..utils.fileio import open_file

    with open_file(filename) as fh:
        spec = json.load(fh)
    if not spec:
        return None
    out = []
    queue = [(spec, -1, 0)]
    step = 0
    while queue and step < num_leaves - 1:
        node, pstep, side = queue.pop(0)
        f = int(node["feature"])
        thr = float(node["threshold"])
        b = int(bin_mappers[f].value_to_bin(np.asarray([thr]))[0])
        dl = bool(node.get("default_left", False))
        depth = 0 if pstep < 0 else int(out[pstep][5]) + 1
        out.append([pstep, side, f, b, int(dl), depth])
        if node.get("left"):
            queue.append((node["left"], step, 0))
        if node.get("right"):
            queue.append((node["right"], step, 1))
        step += 1
    return np.asarray(out, np.int64) if out else None


def resolve_deep_dtype(requested: str, precision: str, backend: str) -> str:
    """``hist_dtype_deep`` resolution policy, one pure function so the
    tests can pin it per backend (tests/test_wave_pipeline.py).

    ``"auto"`` (ROADMAP item 3a) resolves by backend: ``int8sr`` on TPU —
    the int8 MXU path the mode was built for, with the default flip gated
    on bench.py's ``precision_expt`` AUC-parity record — and full
    ``bf16x2`` on CPU (no int8 MXU economics there; full precision is the
    honest default).  Any other platform is an error naming it: the
    policy has never been decided there.  Opt out by setting any
    explicit dtype.  ``""`` keeps the legacy policy: bf16x2 drops to
    single-pass bf16 on sustained rounds, any other explicit
    ``hist_dtype`` is used unchanged."""
    if requested == "auto":
        if backend not in ("tpu", "cpu"):
            raise ValueError(
                f"hist_dtype_deep=auto is undecided for platform "
                f"{backend!r} (tpu -> int8sr, cpu -> bf16x2); set an "
                "explicit dtype")
        requested = "int8sr" if backend == "tpu" else "bf16x2"
    return requested or ("bf16" if precision == "bf16x2" else precision)


@dataclasses.dataclass(frozen=True)
class HistPlan:
    """How a booster builds its histograms and stores its bins: decided
    once per build (``resolve_hist_plan``) and read by every layer below,
    which decides neither again.

    ``method`` is the histogram implementation (``scatter`` | ``onehot`` |
    ``pallas``), ``packed`` whether the stored matrix holds two 4-bit bins
    a byte (``hist_pallas.pack4bit``), ``layout`` what a row of the stored
    matrix holds as ``ops/partition_pallas`` decodes it (``"u8"``, one
    feature's bins; ``"packed4"``, two features' nibbles; ``""``, what it
    does not decode: an EFB bundle column, bins wider than a byte), and
    ``interpret`` whether the Pallas kernels run through the interpreter
    (``pallas`` on XLA:CPU)."""
    method: str
    packed: bool
    layout: str
    interpret: bool


def resolve_hist_plan(config: Config, *, bin_dtype, num_total_bin: int = 0,
                      bundled: bool = False,
                      stored: Optional[str] = None) -> HistPlan:
    """The one choice of histogram method and stored bin layout a booster
    makes, with the once-per-build engagement/refusal logging.

    The method is ``default_hist_method``'s static rule.  ``stored`` is a
    layout already fixed on disk (a streamed block cache's manifest);
    otherwise ``config.bin_layout`` resolves to ``"u8"`` or ``"packed4"``
    from ``num_total_bin`` and ``bundled``.
    Eligibility for ``packed4`` (the reference ``DenseBin<.., IS_4BIT>``
    gate, dense_bin.hpp:52): every feature fits 4 bits
    (``num_total_bin <= 16``), uint8 bins (int16-binned data exceeds the
    nibble), no EFB bundling (bundle offsets address byte bins), the
    pallas hist method (scatter/onehot gathers address unpacked
    bins), ``tree_learner != "feature"`` (feature shards split the byte
    pairing), and not ``gpu_use_dp`` (an explicit request for the widest
    histogram datapath; packing narrows the read stream — dp wins, the
    int8sr precedent).  ``auto`` packs exactly when eligible, silently on
    refusal; an EXPLICIT ``packed4`` refusal logs the staged warning.
    The gauge ``bin_layout_engaged{layout}`` reads 1 for the layout taken
    and 0 for the other."""
    from ..obs.metrics import default_registry

    method = default_hist_method(config.hist_method, bin_dtype)
    if stored is None:
        stored = _stored_layout(config, method, num_total_bin, bin_dtype,
                                bundled)
    engaged = default_registry().gauge(
        "bin_layout_engaged",
        "1 for the layout the last booster built stores its bins in",
        label_names=("layout",))
    for name in ("u8", "packed4"):
        engaged.labels(layout=name).set(float(name == stored))
    wide = np.dtype(bin_dtype).itemsize > 1
    return HistPlan(method=method, packed=stored == "packed4",
                    layout="" if bundled or wide else stored,
                    interpret=(method == "pallas"
                               and jax.default_backend() == "cpu"))


def _stored_layout(config: Config, method: str, num_total_bin: int,
                   bin_dtype, bundled: bool) -> str:
    if config.bin_layout == "u8":
        return "u8"
    explicit = config.bin_layout == "packed4"
    reason = ""
    if np.dtype(bin_dtype).itemsize > 1:
        reason = "int16-binned data exceeds the 4-bit nibble"
    elif num_total_bin > 16:
        reason = (f"num_total_bin={num_total_bin} needs more than 4 bits "
                  "per bin")
    elif bundled:
        reason = "EFB bundle offsets address unpacked byte bins"
    elif method != "pallas":
        reason = (f"hist method {method!r} gathers unpacked bins "
                  "(the pallas kernel's operand is unpacked at placement)")
    elif config.tree_learner == "feature":
        reason = ("tree_learner=feature shards features, not byte "
                  "pairs")
    elif config.gpu_use_dp:
        reason = ("gpu_use_dp requests the widest histogram datapath; "
                  "packed bins narrow the read stream")
    if reason:
        if explicit:
            log_warning(f"bin_layout=packed4: {reason}; storing u8 bins")
        return "u8"
    log_info("bin_layout=packed4: 4-bit packed bins engaged — two bins "
             "per byte, the stored (F, N) matrix, the partition's read and "
             "the streaming cache shards halve (ops/hist_pallas.pack4bit)")
    return "packed4"


# The bytes rule's budget: the share of a device's memory (``bytes_limit``)
# the histogram kernel's prepared bin operand may hold resident.  The rule,
# its three outcomes (the block form, the lane-dense form, the raw matrix)
# and the bytes of the benchmark's five cells are stated once, in
# ``ops/hist_pallas``'s module docstring, and computed by
# ``hist_pallas.hist_bins_form``; a row-sharded learner is judged on one
# chip's shard of the rows.  On the v5e a quarter is 4,227,334,016 B.
_HIST_BINS_SHARE = 0.25


def _hist_bins_budget() -> Optional[int]:
    """Bytes the prepared histogram operands may occupy on this process's
    first device; ``None`` where the backend reports no limit (XLA:CPU)."""
    stats = obs_xla.device_memory_stats()
    if not stats or not stats.get("bytes_limit"):
        return None
    return int(stats["bytes_limit"] * _HIST_BINS_SHARE)


def _place_hist_bins(binned_dev: jax.Array, num_bins: int, packed: bool,
                     mesh: Optional[Mesh] = None, row_axes=None):
    """Lay the placed bins out for the histogram kernel ONCE
    (ops/hist_pallas.prepare_hist_bins, one jitted call on the device), in
    the form the bytes rule gives (``hist_pallas.hist_bins_form``), or
    hand the matrix back where neither form fits the budget: the kernel
    reads the form off the operand and lays a raw matrix out in every
    pass.  With ``mesh`` the matrix is sharded ``P(None, row_axes)`` and
    every chip lays out its own shard: it pads its own rows and holds the
    blocks of those rows only (global blocks ``P(row_axes, None)``), and
    the bytes are a chip's."""
    from ..io.dataset import construct_phase
    from ..obs.metrics import default_registry
    from ..ops.hist_pallas import hist_bins_form, prepare_hist_bins

    shards = 1 if mesh is None else mesh.devices.size
    budget = _hist_bins_budget()
    form, need = hist_bins_form(binned_dev.shape[0],
                                binned_dev.shape[1] // shards, num_bins,
                                packed, budget)
    registry = default_registry()
    registry.gauge(
        "hist_bins_prepared_bytes",
        "Bytes a device holds of the histogram kernel's prepared bin "
        "operands (0: the passes lay the bins out on the fly)"
    ).set(need.get(form, 0))
    would = registry.gauge(
        "hist_bins_need_bytes",
        "Bytes a device would hold of the histogram kernel's bin operand "
        "in each form its rung has", label_names=("form",))
    for name, cost in need.items():
        would.labels(form=name).set(cost)
    registry.gauge(
        "hist_bins_budget_bytes",
        "Bytes the bytes rule lets the prepared bin operand hold on a "
        "device (0: the backend reports no limit)").set(budget or 0)
    if form != next(iter(need)):        # the rung's first form is over
        sizes = ", ".join(f"{name} {cost >> 20} MiB"
                          for name, cost in need.items())
        took = ("stay raw" if form == "raw"
                else "take the lane-dense form")
        log_info(f"histogram bins {took}: the prepared operands ({sizes}) "
                 f"against {_HIST_BINS_SHARE:.0%} of device memory "
                 f"({budget >> 20} MiB)")
    if form == "raw":
        return binned_dev

    def blocks_of(b):
        # only the blocks leave the jit: the matrix stays the placed buffer
        return dataclasses.replace(
            prepare_hist_bins(b, num_bins, packed, dense=form == "dense"),
            matrix=None)

    if mesh is not None:
        blocks_of = jax.shard_map(
            blocks_of, mesh=mesh, in_specs=P(None, row_axes),
            out_specs=P(row_axes, None), check_vma=False)
    with construct_phase("layout"):
        made = jax.jit(blocks_of)(binned_dev)
    return dataclasses.replace(made, matrix=binned_dev)


def _binned_specs(placed, row_axes):
    """``shard_map`` specs of what a row-sharded learner placed: the matrix
    split on its rows, and where ``_place_hist_bins`` prepared the operand
    every block on its own."""
    from ..ops.hist_pallas import HistBins

    matrix = P(None, row_axes)
    if not isinstance(placed, HistBins):
        return matrix
    return dataclasses.replace(
        placed, matrix=matrix,
        blocks=(P(row_axes, None),) * len(placed.blocks))


def build_trainer(
    config: Config,
    plan: HistPlan,                  # resolve_hist_plan's, once per build
    binned_np: np.ndarray,           # (F, N) bins or (BF, N) EFB bundles
    meta: FeatureMeta,
    params: SplitParams,
    num_bins: int,
    bin_mappers=None,
    bundle=None,                     # io/bundle.py BundleArrays (EFB) or None
    bundle_num_bins: Optional[int] = None,   # padded bundle-space bin count
    row_sharded: bool = False,       # binned_np is THIS process's row shard
) -> Tuple[Callable, jax.Array, int]:
    """Return ``(grow_fn, binned_device, num_data)`` for the configured
    tree_learner.  ``grow_fn(binned_device, g3, base_mask, key)`` has the
    serial grower's signature; ``binned_device`` is already placed/padded
    for the chosen topology.  ``plan`` names the histogram method and how
    ``binned_np`` is stored (4-bit packed where ``plan.packed``).  With
    ``bundle`` set, histograms run in bundle space and the split search
    expands them back to original features (io/bundle.py
    expand_bundle_hist — the FixHistogram analog)."""
    learner = config.tree_learner
    precision = config.hist_dtype
    N = binned_np.shape[1]
    if row_sharded:
        if learner != "data":
            log_fatal("row-sharded datasets require tree_learner=data")
        # binned_np holds only THIS process's rows; the global row count is
        # world * R (parallel/dist_data.py make_process_sharded contract)
        N = binned_np.shape[1] * jax.process_count()
    F = int(meta.num_bins.shape[0])  # ORIGINAL feature count
    B = num_bins
    Bh = bundle_num_bins if bundle is not None else B   # histogram bin axis

    if config.device_type in ("gpu", "cuda"):
        # reference configs select the OpenCL/CUDA learners here; this
        # framework's accelerated path is the TPU/XLA backend
        log_warning(f"device_type={config.device_type}: this framework's "
                    f"device path is XLA ({jax.default_backend()} backend); "
                    "the GPU-learner role is filled by the Pallas histogram "
                    "kernel")

    from ..models.grower import make_levelwise_grower
    from ..ops.histogram import hist_frontier

    levelwise = config.tree_growth == "levelwise"

    def local_hist(binned, g3, leaf_id, target):
        return hist_one_leaf(binned, g3, leaf_id, target, Bh,
                             method=plan.method, precision=precision,
                             packed=plan.packed, num_features=F,
                             interpret=plan.interpret)

    def local_frontier(binned, g3, leaf_id, L_level):
        return hist_frontier(binned, g3, leaf_id, L_level, Bh,
                             method=plan.method, precision=precision,
                             packed=plan.packed, num_features=F,
                             interpret=plan.interpret)

    # depth-adaptive wave precision: the grower flags sustained
    # (largest-bucket) rounds of big waves with deep=True — those run a
    # cheaper dtype; ramp rounds + the root pass keep full precision.
    # Default policy: bf16x2 (the default dtype) drops to single-pass bf16
    # on deep rounds — measured 1.11x end-to-end at EQUAL-or-better
    # 500-iter AUC (0.91345 vs 0.91338, tools/precision_expt.py r5); deep
    # leaves hold small aggregates, where bf16's 8-bit mantissa is ample.
    # int8 deep was measured and REJECTED (-0.007 AUC).  Any other
    # explicit hist_dtype is respected everywhere; hist_dtype_deep
    # overrides (set hist_dtype_deep=bf16x2 to force full precision).
    deep_precision = resolve_deep_dtype(config.hist_dtype_deep, precision,
                                        jax.default_backend())
    # hist_dtype_deep="int8sr": stochastic-rounded int8 histograms
    # (ops/quantize.py) — eligible wave rounds route to a separate
    # quantized pass (hist_wave_quant_fn below) instead of the plain deep
    # dtype; any residual deep=True call keeps full precision.  The mode
    # is structurally incompatible with gpu_use_dp (an explicit request
    # for the HIGHEST histogram precision): dp wins, with a warning.
    use_int8sr = deep_precision == "int8sr"
    if use_int8sr and config.gpu_use_dp:
        log_warning("hist_dtype_deep=int8sr conflicts with gpu_use_dp "
                    "(double-precision histograms requested); int8sr "
                    "disabled, deep rounds run f32")
        use_int8sr = False
        deep_precision = "f32"
    elif use_int8sr:
        deep_precision = precision

    def local_wave(binned, g3, label, nslots, deep=False):
        return hist_wave(binned, g3, label, nslots, Bh,
                         method=plan.method,
                         precision=deep_precision if deep else precision,
                         packed=plan.packed, num_features=F,
                         interpret=plan.interpret)

    def local_wave_quant(binned, g3, label, nslots, key, axis_name=None):
        # axis_name: row-sharded learners pass their mesh axis so the
        # quantization scale is pmax'd globally and shard histograms are
        # summable in the raw integer domain (ops/quantize.py)
        return hist_wave_quant(binned, g3, label, nslots, Bh, key,
                               method=plan.method, packed=plan.packed,
                               num_features=F, axis_name=axis_name,
                               interpret=plan.interpret)

    # EFB: split search + decisions speak ORIGINAL features; only the
    # histogram pass runs over bundle columns
    if bundle is not None:
        from ..io.bundle import bundle_bins_of_feat, expand_bundle_hist

        def split_bundle(hist, parent, mask, key, uid, constraint, depth,
                         parent_output, cegb_pen=None):
            h = expand_bundle_hist(hist, parent, bundle, B)
            rk = jax.random.fold_in(key,
                                    uid + 1_000_003 + params.extra_seed) \
                if params.extra_trees else None
            return find_best_split(h, parent, meta, mask, params,
                                   constraint, depth,
                                   config.monotone_penalty, parent_output,
                                   rk, cegb_pen)

        split_local = split_bundle

        def bins_feat_fn(binned, f):
            return bundle_bins_of_feat(binned, f, bundle)
    elif plan.packed:
        # 4-bit packed bins: decisions decode the nibble of their feature
        # (reference DenseBin<.., IS_4BIT>::data access, dense_bin.hpp:425)
        from ..ops.hist_pallas import packed_bins_of_feat

        split_local = None
        bins_feat_fn = packed_bins_of_feat
    else:
        split_local = None
        bins_feat_fn = None

    # the wave-batched best-first schedule is the leaf-wise default; CEGB
    # needs the sequential grower's exact split ORDER (its penalties depend
    # on the features used by earlier splits of the same tree), and forced
    # splits occupy the first steps of the sequential order
    use_cegb = (config.cegb_tradeoff * config.cegb_penalty_split > 0
                or bool(config.cegb_penalty_feature_coupled)
                or bool(config.cegb_penalty_feature_lazy))
    cegb_lazy = _cegb_lazy(config, F, learner, levelwise)
    wave_size = config.leafwise_wave_size
    if wave_size == 0:   # auto: batched for big trees, sequential for small.
        # num_leaves // 4 (= 63 at 255 leaves): with the smaller-child
        # subtraction pass the per-round histogram cost halved, moving the
        # measured optimum from K=32 to ~64 (PERF.md round-4 sweep).
        # Small trees (num_leaves <= 7) stay at K=1 — the reference's exact
        # sequential best-first order, which the golden parity fixtures pin.
        from ..models.grower_wave import auto_wave_size

        wave_size = auto_wave_size(config.num_leaves)
    # cap bounds the unrolled per-round decision loop's compile-time graph
    if wave_size > 128:
        log_warning(f"leafwise_wave_size={wave_size} capped to 128 (the "
                    "per-round decision pass unrolls over the wave)")
        wave_size = 128
    mono_mode = config.monotone_constraints_method or "basic"
    has_mono = bool(config.monotone_constraints) and any(
        config.monotone_constraints)
    if has_mono and mono_mode == "advanced":
        log_warning("monotone_constraints_method=advanced (slow constraint "
                    "recomputation) is approximated by 'intermediate'")
        mono_mode = "intermediate"
    # auto wave_size == 1 routes to the sequential grower (same trees,
    # compacted-segment histograms); an EXPLICIT leafwise_wave_size >= 1
    # forces the wave grower (K=1 == sequential order, used by parity
    # tests), as does intermediate-mode monotonicity (implemented there)
    wants_inter = has_mono and mono_mode == "intermediate"
    use_wave = (config.tree_growth == "leafwise"
                and not use_cegb
                and (config.leafwise_wave_size >= 1 or wave_size > 1
                     or wants_inter))
    if has_mono and mono_mode == "intermediate" and (
            not use_wave or bool(config.forcedsplits_filename)):
        # forced splits route leaf-wise growth to the sequential grower,
        # which implements basic-mode constraints only
        log_warning("monotone_constraints_method=intermediate is "
                    "implemented by the wave-batched leaf-wise grower; "
                    f"falling back to 'basic' for this configuration "
                    f"(tree_growth={config.tree_growth}"
                    + (", forced splits" if config.forcedsplits_filename
                       else "") + ")")
        mono_mode = "basic"

    common = dict(
        num_leaves=config.num_leaves,
        num_bins=B,
        meta=meta,
        params=params,
        max_depth=config.max_depth,
        feature_fraction_bynode=config.feature_fraction_bynode,
        monotone_penalty=config.monotone_penalty,
        interaction_groups=parse_interaction_constraints(
            config.interaction_constraints, F),
        cegb_coupled=_cegb_coupled(config, F),
    )
    wave_common = {k: v for k, v in common.items() if k != "cegb_coupled"}
    wave_common["wave_size"] = wave_size
    wave_common["monotone_mode"] = mono_mode
    wave_common["fused_bookkeeping"] = config.fused_bookkeeping
    wave_common["async_wave_pipeline"] = config.async_wave_pipeline
    wave_common["hist_method"] = plan.method
    wave_common["pallas_interpret"] = plan.interpret
    wave_common["stored_layout"] = plan.layout
    # sequential-grower histogram pool cap (reference histogram_pool_size;
    # the wave/level growers use frontier-sized buffers and need no cap)
    lw_pool = dict(hist_pool_mb=config.histogram_pool_size, num_features=F)
    forced = None
    if config.forcedsplits_filename:
        if bin_mappers is None:
            log_warning("forcedsplits_filename requires bin mappers; ignored")
        else:
            forced = parse_forced_splits(config.forcedsplits_filename,
                                         bin_mappers, config.num_leaves)

    # ---- stored sums that hold at small leaves (models/renew.py) --------
    # every grower's tree passes through ``renew_tree`` before anything
    # reads it.  The policy is what this function can see: the method and
    # precisions of the passes, whether the wave has a deep bucket at the
    # rows one device sums, whether the grower subtracts.
    from ..models import renew
    from ..models.grower_wave import _SUB_STATE_CAP_BYTES, slot_buckets_for

    wave_grows = use_wave and forced is None and not levelwise
    # split records one round gathers (the wave vmaps its 2K children)
    round_children = 2 * wave_size if wave_grows else 1
    renew_policy = None        # what the returned grow_fn carries

    def local_leaf_sums(leaf_id, g3):
        return renew.leaf_sums(leaf_id, g3, config.num_leaves,
                               method=plan.method, precision=precision,
                               interpret=plan.interpret)

    def renewing(grow, rows, is_wave, leaf_sums_fn=local_leaf_sums,
                 cols=binned_np.shape[0] if bundle is not None else F):
        """``grow`` with its marked sums renewed; ``rows`` are what one
        device's passes sum, ``leaf_sums_fn`` the learner's direct sums (a
        row-sharded learner adds its shards up inside), ``cols`` the
        columns of the histograms the grower keeps."""
        nonlocal renew_policy
        if (has_mono or params.path_smooth > 0 or config.num_leaves < 2
                or config.num_leaves > renew.MAX_LEAVES):
            return grow
        later = [precision]
        if is_wave:
            K_eff = max(1, min(wave_size, max(config.num_leaves - 1, 1)))
            if len(slot_buckets_for(K_eff, rows)) > 1:
                if K_eff >= 32:
                    later.append(deep_precision)
                if use_int8sr:
                    later.append("int8sr")
        renew_policy = renew.RenewPolicy(
            eps_root=renew.pass_rounding(plan.method, precision, rows),
            eps_rest=max(renew.pass_rounding(plan.method, p, rows)
                         for p in later),
            subtracts=not (is_wave and config.num_leaves * cols * Bh * 12
                           > _SUB_STATE_CAP_BYTES),
            gains=meta.contri is None and not use_cegb)
        return renew.with_renewal(grow, params, renew_policy, leaf_sums_fn)

    def finished(grow_fn, label):
        grow_fn._renew_policy = renew_policy
        return obs_xla.instrument_jit(grow_fn, label)

    if learner in ("serial", ""):
        if levelwise:
            grow = make_levelwise_grower(
                hist_frontier_fn=local_frontier, split_fn=split_local,
                bins_of_fn=bins_feat_fn, forced_splits=forced,
                **common)
        elif use_wave and forced is None:
            # wave-batched best-first: the leaf-wise default schedule
            # (models/grower_wave.py)
            grow = make_wave_grower(hist_wave_fn=local_wave,
                                    hist_wave_quant_fn=(
                                        local_wave_quant if use_int8sr
                                        else None),
                                    split_fn=split_local,
                                    bins_of_fn=bins_feat_fn,
                                    **wave_common)
        else:
            # sequential best-first (the reference's exact split order):
            # DataPartition fast path by default; tree_growth=leafwise_masked
            # keeps the O(N)-per-split variant; per-row lazy feature costs
            # need the masked variant's leaf ids
            grow = make_leafwise_grower(
                hist_fn=local_hist, forced_splits=forced,
                split_fn=split_local, bins_of_fn=bins_feat_fn,
                cegb_lazy=cegb_lazy,
                partition=(config.tree_growth != "leafwise_masked"
                           and cegb_lazy is None),
                **lw_pool, **common)
        # the instrumented jit copies grow.__dict__ (the jax.jit /
        # functools.wraps contract), so the wave grower's
        # _supports_valids capability flag — valid rows routed through
        # each round's splits instead of per-tree walks — rides the
        # wrapped callable automatically; compile telemetry (obs/xla.py)
        # labels this dispatch per learner
        binned_dev = jnp.asarray(binned_np)
        if plan.method == "pallas":
            binned_dev = _place_hist_bins(binned_dev, Bh, plan.packed)
        grow = renewing(grow, N, wave_grows)
        return finished(grow, "grow.serial"), binned_dev, N

    if learner == "voting" and levelwise:
        log_warning("tree_learner=voting requires the leaf-wise grower; "
                    "using tree_learner=data for tree_growth=levelwise")
        learner = "data"

    if forced is not None and learner in ("voting", "feature"):
        log_warning(f"forcedsplits_filename is not supported with "
                    f"tree_learner={learner}; ignored")
        forced = None

    if learner == "voting":
        # PV-Tree voting (reference: VotingParallelTreeLearner,
        # src/treelearner/voting_parallel_tree_learner.cpp:152-310): rows are
        # sharded like the data-parallel learner, but instead of reducing the
        # full (F, B) histogram block, each shard votes for its local top-k
        # features, the global top-2k vote winners are selected
        # (GlobalVoting :152-180), and only the selected features' histograms
        # are summed across shards (CopyLocalHistogram) — comm volume drops
        # from O(F·B) to O(2k·B).
        from ..ops.split import per_feature_best_gain

        collective = config.data_parallel_collective
        hier = collective == "hierarchical"
        if hier:
            # two-level (host, chip) mesh (ISSUE 16): the vote psum and
            # the selective reduce run level-by-level so only the
            # 1/C-sliced partials cross the slow DCN axis
            mesh = make_hier_mesh(config.num_shards, config.num_hosts)
            NH, NC = (int(s) for s in mesh.devices.shape)
            row_axes = ("host", "chip")
        else:
            mesh = _make_mesh(config.num_shards, "data")
            NH = NC = 0
            row_axes = "data"
        ndev = mesh.devices.size
        N_pad = ((N + ndev - 1) // ndev) * ndev
        binned_p = np.zeros((binned_np.shape[0], N_pad),
                            dtype=binned_np.dtype)
        binned_p[:, :N] = binned_np
        binned_dev = jax.device_put(
            jnp.asarray(binned_p), NamedSharding(mesh, P(None, row_axes))
        )
        if plan.method == "pallas":
            binned_dev = _place_hist_bins(binned_dev, Bh, plan.packed, mesh,
                                          row_axes)
        top_k = max(1, min(config.top_k, F))
        sel_k = min(2 * top_k, F)
        use_hier = hier and ndev > 1
        use_rs = (collective == "reduce_scatter" and ndev > 1) or use_hier
        sel_pad = -(-sel_k // ndev) * ndev
        sel_loc = sel_pad // ndev
        log_info(f"Voting-parallel training over {ndev} devices "
                 f"(top_k={top_k}, {sel_k} features reduced per split, "
                 f"{collective} selective reduce)")
        _comm_tbl = comm_table_per_round(
            "voting", "reduce_scatter" if hier else collective,
            k=wave_size, F=F, B=B, ndev=ndev, sel_k=sel_k,
            int8sr=use_int8sr)
        log_info("comm/round (analytic, K=%d wave): %s"
                 % (wave_size, _comm_tbl))
        # the top-2k ELECTION payload itself — the (2K, F) vote psum that
        # buys the selective reduce — is priced next to the histograms it
        # compresses (vote_bytes), never riding uncounted
        log_info("voting election payload (GlobalVoting vote psum): "
                 "%d B/round analytic, recorded as vote_bytes"
                 % _comm_tbl.get("vote_bytes", 0))
        publish_comm_metrics("voting", _comm_tbl)
        if hier:
            _hier_tbl = hier_comm_table_per_round(
                "voting", k=wave_size, F=F, B=B, ndev=ndev, num_hosts=NH,
                sel_k=sel_k, int8sr=use_int8sr,
                ici_gbps=config.hier_ici_gbps,
                dcn_gbps=config.hier_dcn_gbps)
            log_info("hier comm/round (per-level ring wire, K=%d wave): %s"
                     % (wave_size, _hier_tbl))
            publish_hier_comm_metrics("voting", _hier_tbl)

        def hist_fn(binned, g3, leaf_id, target):
            # local histogram only — the reduce happens per-split in split_fn
            # (local_hist handles 4-bit packed and bundle-space bins)
            return local_hist(binned, g3, leaf_id, target)

        def sums_fn(g3):
            return _psum("root", g3.sum(axis=0), row_axes)

        def voting_wave_quant(binned, g3, label, nslots, key):
            # global (pmax'd) scales: the selective reduce in split_fn can
            # then sum the RAW integer histograms across shards (the
            # int8sr integer-domain contract the data learner follows);
            # under the hierarchical mesh the pmax spans both levels
            return local_wave_quant(binned, g3, label, nslots, key,
                                    axis_name=row_axes)

        def split_fn(local_hist, parent, mask, key, uid, constraint, depth,
                     parent_output, cegb_pen=None, hist_scale=None):
            # ``hist_scale`` non-None marks a quantized round whose
            # histogram is still raw integers (wave grower hands custom
            # split_fns the integer stack when accepts_hist_scale is set):
            # votes are computed on a locally-dequantized view (no comm),
            # while the cross-shard selective reduce below sums the raw
            # integer values and dequantizes only after the collective
            hist_f = (local_hist if hist_scale is None
                      else local_hist * hist_scale[None, None, :])
            # local parent stats: any feature's bin sums cover the shard rows
            local_parent = hist_f[0].sum(axis=0)
            gains = per_feature_best_gain(hist_f, local_parent, meta,
                                          mask, params, parent_output)
            if cegb_pen is not None:
                # CEGB must influence WHICH features win the vote, not just
                # the final reduced search (serial-semantics parity)
                gains = jnp.where(jnp.isfinite(gains), gains - cegb_pen,
                                  gains)
            _, local_top = lax.top_k(gains, top_k)
            votes = jnp.zeros(F, jnp.float32).at[local_top].add(
                jnp.where(jnp.isfinite(gains[local_top]), 1.0, 0.0))
            votes = _psum("vote", votes, row_axes)        # GlobalVoting
            # tie-break deterministically by feature index
            order_score = votes * (F + 1) - jnp.arange(F, dtype=jnp.float32)
            _, selected = lax.top_k(order_score, sel_k)   # (sel_k,)
            rk = jax.random.fold_in(key, uid + 1_000_003 + params.extra_seed) \
                if params.extra_trees else None
            # int8sr integer domain: quantized rounds reduce the RAW
            # integer values and the one dequantize multiply runs AFTER
            # the reduce (find_best_split's hist_scale fold) on the
            # reduced slice only.  Unlike the data learner's per-bucket
            # wrapper, this collective is shared by quantized and
            # full-precision rounds (hist_scale is identity on the
            # latter), so the wire dtype stays f32 — integer sums are
            # still exact (|values| << 2^24) and reduction-order-free.
            wire = local_hist[selected]                   # (sel_k, B, 3)
            if use_rs:
                # CopyLocalHistogram via the sharded primitive: each chip
                # reduces+keeps sel_k/D of the voted features, searches
                # them, and only SplitInfo crosses chips
                wire = jnp.pad(wire, ((0, sel_pad - sel_k), (0, 0), (0, 0)))
                _reduce_bytes("hist", wire)
                if use_hier:
                    # two-level selective reduce: full (sel_pad, B, 3)
                    # wire rides the fast ICI ring only; the slow DCN hop
                    # carries the 1/C chip slice of the ELECTED features
                    sl = _psum_scatter(wire, "chip", 0)    # (sel_pad/C,...)
                    sl = _psum_scatter(sl, "host", 0)      # (sel_loc, B, 3)
                    lo = (lax.axis_index("chip") * (sel_pad // NC)
                          + lax.axis_index("host") * sel_loc)
                else:
                    sl = _psum_scatter(wire, "data", 0)    # (sel_loc, B, 3)
                    lo = lax.axis_index("data") * sel_loc
                sl = sl.astype(jnp.float32)
                sel_p = jnp.pad(selected, (0, sel_pad - sel_k),
                                constant_values=F)        # F = drop slot
                mine = lax.dynamic_slice(sel_p, (lo,), (sel_loc,))
                full = jnp.zeros((F, B, 3), jnp.float32) \
                    .at[mine].set(sl, mode="drop")
                sel_mask = jnp.zeros(F, bool).at[mine].set(True, mode="drop")
                local = find_best_split(full, parent, meta, mask & sel_mask,
                                        params, constraint, depth,
                                        config.monotone_penalty,
                                        parent_output, rk, cegb_pen,
                                        hist_scale=hist_scale)
                return _sync_best_split(local, parent, params, row_axes,
                                        round_children)
            hist_sel = _psum("hist", wire, row_axes).astype(jnp.float32)
            full = jnp.zeros((F, B, 3), jnp.float32).at[selected].set(hist_sel)
            sel_mask = jnp.zeros(F, bool).at[selected].set(True)
            return find_best_split(full, parent, meta, mask & sel_mask,
                                   params, constraint, depth,
                                   config.monotone_penalty, parent_output,
                                   rk, cegb_pen, hist_scale=hist_scale)

        # the wave grower must hand quantized rounds' INTEGER histograms
        # through (bundle-space hists would mix units in expand, so EFB
        # keeps the pre-dequantized path)
        split_fn.accepts_hist_scale = bundle is None

        if use_wave:
            # the wave grower's vmapped split_fn batches the vote psum and
            # the selective histogram reduce across all 2K children of a
            # round — same PV-Tree semantics, one collective round-trip
            grow = make_wave_grower(hist_wave_fn=local_wave,
                                    hist_wave_quant_fn=(
                                        voting_wave_quant if use_int8sr
                                        else None),
                                    split_fn=split_fn, sums_fn=sums_fn,
                                    bins_of_fn=bins_feat_fn, **wave_common)
        else:
            grow = make_leafwise_grower(
                hist_fn=hist_fn, split_fn=split_fn, sums_fn=sums_fn,
                bins_of_fn=bins_feat_fn, **lw_pool, **common)
        grow = renewing(
            grow, N_pad // ndev, use_wave,
            lambda lid, g3: _psum("renew", local_leaf_sums(lid, g3),
                                  row_axes))
        return (finished(_over_row_shards(grow, mesh, row_axes, binned_dev,
                                          N, N_pad),
                         f"grow.{learner}"), binned_dev, N)

    if learner == "data":
        collective = config.data_parallel_collective
        if forced is not None and collective in ("reduce_scatter",
                                                 "hierarchical"):
            # forced splits read left/right sums straight off the leaf
            # histogram (models/grower.forced_split_stats) — a shard-
            # resident slice cannot serve a forced feature outside the
            # shard, so the full-histogram path carries them
            log_warning("forcedsplits_filename requires full histograms "
                        "on every shard; data_parallel_collective falls "
                        "back to allreduce")
            collective = "allreduce"
        hier = collective == "hierarchical"
        if hier:
            # two-level (host, chip) mesh (ISSUE 16): histograms
            # reduce-scatter over the fast ICI axis first, and only the
            # 1/C-sliced partials cross the slow DCN axis
            mesh = make_hier_mesh(config.num_shards, config.num_hosts)
            NH, NC = (int(s) for s in mesh.devices.shape)
            row_axes = ("host", "chip")
        else:
            mesh = _make_mesh(config.num_shards, "data")
            NH = NC = 0
            row_axes = "data"
        ndev = mesh.devices.size
        sharding = NamedSharding(mesh, P(None, row_axes))
        if row_sharded:
            # process-local shards -> one global sharded array; no process
            # ever materializes the full matrix (the reference's per-rank
            # memory win, dataset_loader.cpp:167 + Experiments.rst:228-240)
            N_pad = N                      # already world * R, R % d == 0
            binned_dev = jax.make_array_from_process_local_data(
                sharding, binned_np)
        else:
            N_pad = ((N + ndev - 1) // ndev) * ndev
            binned_p = np.zeros((binned_np.shape[0], N_pad),
                                dtype=binned_np.dtype)
            binned_p[:, :N] = binned_np
            if jax.process_count() > 1:
                # host-replicated multi-host input: every process carries
                # the full array and contributes its addressable shards
                binned_dev = jax.make_array_from_callback(
                    binned_p.shape, sharding,
                    lambda idx: jnp.asarray(binned_p[idx]))
            else:
                binned_dev = jax.device_put(jnp.asarray(binned_p), sharding)
        if plan.method == "pallas":
            binned_dev = _place_hist_bins(binned_dev, Bh, plan.packed, mesh,
                                          row_axes)
        use_hier = hier and ndev > 1
        use_rs = (collective == "reduce_scatter" and ndev > 1) or use_hier
        # the HISTOGRAM column axis being sharded: bundle columns under
        # EFB, original features otherwise (4-bit packed histograms come
        # out of the pallas kernel with F columns)
        FH = binned_np.shape[0] if bundle is not None else F
        FH_pad = -(-FH // ndev) * ndev
        FH_loc = FH_pad // ndev
        log_info(f"Data-parallel training over {ndev} devices "
                 f"({N_pad // ndev} rows/device, "
                 f"{jax.process_count()} processes, {collective} collective"
                 + (", process-sharded storage" if row_sharded else "")
                 + ")")
        _comm_tbl = comm_table_per_round(
            "data", "reduce_scatter" if hier else collective, k=wave_size,
            F=FH, B=Bh, ndev=ndev, int8sr=use_int8sr)
        log_info("comm/round (analytic, K=%d wave): %s"
                 % (wave_size, _comm_tbl))
        publish_comm_metrics("data", _comm_tbl)
        if hier:
            _hier_tbl = hier_comm_table_per_round(
                "data", k=wave_size, F=FH, B=Bh, ndev=ndev, num_hosts=NH,
                int8sr=use_int8sr,
                ici_gbps=config.hier_ici_gbps,
                dcn_gbps=config.hier_dcn_gbps)
            log_info("hier comm/round (per-level ring wire, K=%d wave): %s"
                     % (wave_size, _hier_tbl))
            publish_hier_comm_metrics("data", _hier_tbl)

        def _scatter_keep(h, int_domain=False):
            """The reference's ReduceScatter of histogram blocks
            (data_parallel_tree_learner.cpp:155-173): reduce over the
            row shards, each device KEEPING only its FH_loc-column
            feature slice, compact as it arrives: ``(slots, FH_loc, B,
            3)``.  The grower's histogram state, the sibling subtraction
            and the scan (_split_sharded) all run at that width; the
            allgather the old psum implied is replaced by the SplitInfo
            sync there.  ``int_domain``: quantized rounds cross the wire
            as raw int32 (exact, order-invariant sums; ops/quantize.py
            global scales make shard partials commensurable)."""
            nb = h.ndim - 3                   # leading slot axes (0 or 1)
            with jax.named_scope(COLLECTIVE_SCOPE):     # the wire's form
                hp = jnp.pad(h, [(0, 0)] * nb
                             + [(0, FH_pad - FH), (0, 0), (0, 0)])
                if int_domain:
                    hp = hp.astype(jnp.int32)
            _reduce_bytes("hist", hp)
            if use_hier:
                # level 1 (ICI): the full FH_pad block rides the fast
                # intra-host ring; level 2 (DCN): only the FH_pad/C chip
                # slice crosses hosts — 1/C of the flat wire volume
                sl = _psum_scatter(hp, "chip", nb)
                sl = _psum_scatter(sl, "host", nb)
            else:
                sl = _psum_scatter(hp, "data", nb)
            with jax.named_scope(COLLECTIVE_SCOPE):
                return sl.astype(jnp.float32)

        def _shard_lo():
            """First histogram column this device owns after the
            reduce-scatter.  Hierarchical keep-slices are chip-major
            (the second scatter subdivides the chip slice by host), so
            the offset composes both axis indices."""
            if use_hier:
                return (lax.axis_index("chip") * (FH_pad // NC)
                        + lax.axis_index("host") * FH_loc)
            return lax.axis_index("data") * FH_loc

        # the features each column slice owns, ascending, padded with F
        # (no feature) to the largest count: a plain range of FH_loc ids
        # but under EFB, where a slice owns the features of its bundles
        col_of = (np.asarray(bundle.bundle_of) if bundle is not None
                  else np.arange(F))
        owned = [np.flatnonzero(col_of // FH_loc == s) for s in range(ndev)]
        own_tbl = np.full((ndev, max(map(len, owned))), F, np.int32)
        for s, ids in enumerate(owned):
            own_tbl[s, :len(ids)] = ids
        own_tbl = jnp.asarray(own_tbl)

        def _split_sharded(hist, parent, mask, key, uid, constraint, depth,
                           parent_output, cegb_pen=None, hist_scale=None):
            """Best split over the ``(FH_loc, B, 3)`` slice this device
            kept, then the SplitInfo sync —
            FindBestSplitsFromHistograms restricted to OWN features, as
            the reference data-parallel learner does after its
            ReduceScatter (data_parallel_tree_learner.cpp:175-199).  The
            per-feature inputs are cut to the owned ids (ops/split.py
            narrow_meta) and the winner comes back under its global id."""
            # the scan's operands cut to the owned columns: the scan's scope
            with jax.named_scope("lgbm.split"):
                lo = _shard_lo()
                own = own_tbl[lo // FH_loc]
                if bundle is not None:
                    from ..io.bundle import expand_bundle_hist

                    hist = expand_bundle_hist(hist, parent, bundle, B,
                                              columns=own, first_column=lo)
                _scan_columns(owned=FH_loc, scanned=hist.shape[0])
                rk = jax.random.fold_in(
                    key, uid + 1_000_003 + params.extra_seed) \
                    if params.extra_trees else None
                own_meta = narrow_meta(meta, own)
                own_mask = take_columns(mask, own, False)
                own_pen = (None if cegb_pen is None
                           else take_columns(cegb_pen, own, 0.0))
            local = find_best_split(
                hist, parent, own_meta, own_mask, params, constraint, depth,
                config.monotone_penalty, parent_output, rk, own_pen,
                hist_scale=hist_scale)
            return _sync_best_split(local, parent, params, row_axes,
                                    round_children)

        # integer histograms cannot cross expand_bundle_hist (its zero-bin
        # fix mixes real-unit parent sums in), so EFB keeps the grower's
        # pre-dequantized path; the collective still moved int32
        _split_sharded.accepts_hist_scale = bundle is None

        def hist_fn(binned, g3, leaf_id, target):
            h = local_hist(binned, g3, leaf_id, target)
            return (_scatter_keep(h) if use_rs
                    else _psum("hist", h, row_axes))

        def sums_fn(g3):
            return _psum("root", g3.sum(axis=0), row_axes)

        split_dp = _split_sharded if use_rs else split_local

        if levelwise:
            def frontier_fn(binned, g3, leaf_id, L_level):
                h = local_frontier(binned, g3, leaf_id, L_level)
                return (_scatter_keep(h) if use_rs
                        else _psum("hist", h, row_axes))

            grow = make_levelwise_grower(
                hist_frontier_fn=frontier_fn, sums_fn=sums_fn,
                split_fn=split_dp, bins_of_fn=bins_feat_fn,
                forced_splits=forced, **common)
        elif use_wave and forced is None:
            # one histogram collective per ROUND (up to 2K child
            # histograms batched) instead of one per split — the wave
            # schedule's distributed dividend
            def wave_fn(binned, g3, label, nslots, deep=False):
                h = local_wave(binned, g3, label, nslots, deep)
                return (_scatter_keep(h) if use_rs
                        else _psum("hist", h, row_axes))

            if use_rs:
                def wave_quant_fn(binned, g3, label, nslots, key):
                    # GLOBAL (pmax'd) scales make the shard partials one
                    # integer system: the collective reduces raw int32
                    # and the single dequantize multiply happens at the
                    # consumer (subtraction pass / split scan hist_scale)
                    # — the quantized pipeline's cross-chip contract.
                    # Hierarchical runs pmax the scale across BOTH levels
                    # and cross int32 on both hops (exact, order-free).
                    h, sc = local_wave_quant(binned, g3, label, nslots,
                                             key, axis_name=row_axes)
                    return _scatter_keep(h, int_domain=True), sc
            else:
                def wave_quant_fn(binned, g3, label, nslots, key):
                    # legacy allreduce: each shard quantizes with its
                    # LOCAL per-pass scales (unbiasedness is per-row, so
                    # the psum of dequantized shard histograms stays an
                    # unbiased estimator); the psum therefore runs on
                    # dequantized f32 and the grower sees identity scales
                    h, sc = local_wave_quant(binned, g3, label, nslots,
                                             key)
                    h = _psum("hist", h * sc[:, None, None, :], row_axes)
                    return h, jnp.ones_like(sc)

            grow = make_wave_grower(hist_wave_fn=wave_fn, sums_fn=sums_fn,
                                    hist_wave_quant_fn=(
                                        wave_quant_fn if use_int8sr
                                        else None),
                                    split_fn=split_dp,
                                    bins_of_fn=bins_feat_fn, **wave_common)
        else:
            # the pool holds what hist_fn returns: the kept slice
            pool = dict(lw_pool, num_features=FH_loc) if use_rs else lw_pool
            grow = make_leafwise_grower(hist_fn=hist_fn, sums_fn=sums_fn,
                                        split_fn=split_dp,
                                        bins_of_fn=bins_feat_fn,
                                        forced_splits=forced,
                                        **pool, **common)
        grow = renewing(
            grow, N_pad // ndev, wave_grows,
            lambda lid, g3: _psum("renew", local_leaf_sums(lid, g3),
                                  row_axes),
            cols=FH_loc if use_rs else FH)
        return (finished(_over_row_shards(grow, mesh, row_axes, binned_dev,
                                          N, N_pad),
                         f"grow.{learner}"), binned_dev, N)

    if learner == "feature":
        mesh = _make_mesh(config.num_shards, "feature")
        ndev = mesh.devices.size
        F_pad = ((F + ndev - 1) // ndev) * ndev
        F_loc = F_pad // ndev
        binned_p = np.zeros((F_pad, N), dtype=binned_np.dtype)
        binned_p[:F] = binned_np
        # every device holds ALL rows and ALL features (reference feature-
        # parallel replicates the data); only histogram build + split search
        # are feature-sharded
        binned_dev = jax.device_put(
            jnp.asarray(binned_p), NamedSharding(mesh, P(None, None))
        )
        pad_f = F_pad - F
        meta_p = FeatureMeta(
            num_bins=jnp.pad(meta.num_bins, (0, pad_f), constant_values=1),
            missing_type=jnp.pad(meta.missing_type, (0, pad_f)),
            nan_bin=jnp.pad(meta.nan_bin, (0, pad_f), constant_values=-1),
            zero_bin=jnp.pad(meta.zero_bin, (0, pad_f)),
            is_categorical=jnp.pad(meta.is_categorical, (0, pad_f)),
            usable=jnp.pad(meta.usable, (0, pad_f)),
            monotone_type=jnp.pad(meta.monotone_type, (0, pad_f)),
            contri=(jnp.pad(meta.contri, (0, pad_f), constant_values=1.0)
                    if meta.contri is not None else None),
        )
        log_info(f"Feature-parallel training over {ndev} devices "
                 f"({F_loc} features/device)")
        _comm_tbl = comm_table_per_round("feature", "allreduce",
                                         k=wave_size, F=F, B=B, ndev=ndev)
        log_info("comm/round (analytic, K=%d wave): %s"
                 % (wave_size, _comm_tbl))
        publish_comm_metrics("feature", _comm_tbl)

        def hist_fn(binned, g3, leaf_id, target):
            # build histograms only for this device's feature block, placed
            # at the right offset of a full-width (zero elsewhere) array
            lo = lax.axis_index("feature") * F_loc
            block = lax.dynamic_slice(binned, (lo, 0), (F_loc, N))
            h = hist_one_leaf(block, g3, leaf_id, target, B,
                              method=plan.method, precision=precision,
                              interpret=plan.interpret)
            full = jnp.zeros((F_pad, B, 3), jnp.float32)
            return lax.dynamic_update_slice(full, h, (lo, 0, 0))

        def hist_wave_fp(binned, g3, label, nslots, deep=False):
            lo = lax.axis_index("feature") * F_loc
            block = lax.dynamic_slice(binned, (lo, 0), (F_loc, N))
            h = hist_wave(block, g3, label, nslots, B,
                          method=plan.method,
                          precision=deep_precision if deep else precision,
                          interpret=plan.interpret)
            full = jnp.zeros((nslots, F_pad, B, 3), jnp.float32)
            return lax.dynamic_update_slice(full, h, (0, lo, 0, 0))

        def hist_wave_quant_fp(binned, g3, label, nslots, key):
            # g3/label/key are replicated, so every shard derives the SAME
            # per-pass scales — the feature-block histograms compose into
            # one consistently-quantized full-width array (zeros outside
            # the shard dequantize to zero)
            lo = lax.axis_index("feature") * F_loc
            block = lax.dynamic_slice(binned, (lo, 0), (F_loc, N))
            h, sc = hist_wave_quant(block, g3, label, nslots, B, key,
                                    method=plan.method,
                                    interpret=plan.interpret)
            full = jnp.zeros((nslots, F_pad, B, 3), jnp.float32)
            return lax.dynamic_update_slice(full, h, (0, lo, 0, 0)), sc

        def split_fn(hist, parent, mask, key, uid, constraint, depth,
                     parent_output, cegb_pen=None):
            # search only this device's features, then Allreduce-max over
            # packed SplitInfo (reference SyncUpGlobalBestSplit) with the
            # reduction-order-invariant tie-break (_sync_best_split)
            lo = lax.axis_index("feature") * F_loc
            in_shard = (
                lax.broadcasted_iota(jnp.int32, (F_pad, 1), 0)[:, 0] >= lo
            ) & (
                lax.broadcasted_iota(jnp.int32, (F_pad, 1), 0)[:, 0] < lo + F_loc
            )
            rk = jax.random.fold_in(key, uid + 1_000_003 + params.extra_seed) \
                if params.extra_trees else None
            local = find_best_split(hist, parent, meta_p, mask & in_shard,
                                    params, constraint, depth,
                                    config.monotone_penalty, parent_output,
                                    rk, cegb_pen)
            return _sync_best_split(local, parent, params, "feature")

        coupled_fp = _cegb_coupled(config, F)
        if coupled_fp is not None:
            coupled_fp = np.pad(coupled_fp, (0, pad_f))
        fp_kwargs = dict(
            num_leaves=config.num_leaves, num_bins=B, meta=meta_p,
            params=params, max_depth=config.max_depth,
            feature_fraction_bynode=config.feature_fraction_bynode,
            monotone_penalty=config.monotone_penalty,
            interaction_groups=parse_interaction_constraints(
                config.interaction_constraints, F_pad),
        )
        if not levelwise and use_wave:
            # the wave grower implements intermediate-mode monotonicity;
            # the level-wise grower is basic-only (warned above)
            fp_kwargs["monotone_mode"] = mono_mode
            fp_kwargs["async_wave_pipeline"] = config.async_wave_pipeline
        if levelwise:
            # feature-sharded frontier histograms + vmapped all_gather
            # argmax per leaf — the level-wise grower composes with the
            # feature-parallel learner like the leaf-wise ones do
            def fp_frontier(binned, g3, leaf_id, L_level):
                lo = lax.axis_index("feature") * F_loc
                block = lax.dynamic_slice(binned, (lo, 0), (F_loc, N))
                h = hist_frontier(block, g3, leaf_id, L_level, Bh,
                                  method=plan.method, precision=precision,
                                  interpret=plan.interpret)
                full = jnp.zeros((L_level, F_pad, Bh, 3), jnp.float32)
                return lax.dynamic_update_slice(full, h, (0, lo, 0, 0))

            grow = make_levelwise_grower(
                hist_frontier_fn=fp_frontier, split_fn=split_fn,
                cegb_coupled=coupled_fp, **fp_kwargs)
        elif use_wave:
            grow = make_wave_grower(
                hist_wave_fn=hist_wave_fp,
                hist_wave_quant_fn=(hist_wave_quant_fp if use_int8sr
                                    else None),
                split_fn=split_fn,
                wave_size=wave_size, **fp_kwargs)
        else:
            grow = make_leafwise_grower(
                hist_fn=hist_fn, split_fn=split_fn, cegb_coupled=coupled_fp,
                hist_pool_mb=config.histogram_pool_size,
                num_features=F_pad, **fp_kwargs)
        # every device holds all rows: each sums its leaves alone
        grow = renewing(grow, N, use_wave and not levelwise)
        sharded = jax.shard_map(
            grow,
            mesh=mesh,
            in_specs=(P(None, None), P(None, None), P(), P(), P()),
            out_specs=(
                jax.tree_util.tree_map(lambda _: P(), TreeArrays(
                    *([0] * len(TreeArrays._fields)))),
                P(),
                P(),
            ),
            check_vma=False,
        )

        def grow_fn(binned, g3, base_mask, key, cegb_used):
            maskp = jnp.pad(base_mask, (0, pad_f))
            return sharded(binned, g3, maskp, key,
                           jnp.pad(cegb_used, (0, pad_f)))

        return finished(grow_fn, f"grow.{learner}"), binned_dev, N

    log_fatal(f"Unknown tree_learner: {learner}")
