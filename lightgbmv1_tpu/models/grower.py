"""Leaf-wise (best-first) tree growth, fully on device.

TPU-native re-design of the reference SerialTreeLearner
(``SerialTreeLearner::Train`` src/treelearner/serial_tree_learner.cpp:152-202,
``FindBestSplits`` :316, ``SplitInner`` :541-659) and DataPartition
(src/treelearner/data_partition.hpp:101-120).

Design mapping (SURVEY.md §7):

* The reference's permuted row-index partition becomes a per-row ``leaf_id``
  array; ``DataPartition::Split``'s parallel scatter becomes a vectorized
  ``where`` over all rows.
* The histogram pool with parent-reuse + the smaller/larger-leaf subtraction
  trick (``BeforeFindBestSplit`` serial_tree_learner.cpp:274-314,
  ``FeatureHistogram::Subtract`` feature_histogram.hpp:79) is kept exactly:
  one histogram pass over the smaller child per split, larger child =
  parent - smaller (a pure vector op).
* The whole per-tree loop is a ``lax.fori_loop`` of ``num_leaves - 1`` steps
  under one ``jit``; a latched ``done`` flag reproduces the reference's
  early stop when no split has positive gain
  (serial_tree_learner.cpp:192-195).
* Distribution is injected through ``hist_fn`` (see parallel/): the
  data-parallel learner wraps it in a psum over the row mesh axis — the
  analog of DataParallelTreeLearner's ReduceScatter
  (data_parallel_tree_learner.cpp:155-173) — while this module stays
  topology-agnostic.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..io.binning import MISSING_NAN, MISSING_ZERO
from ..ops.hist_pallas import bin_matrix
from ..ops.split import (
    NO_CONSTRAINT,
    FeatureMeta,
    SplitParams,
    find_best_split,
    leaf_output,
    smooth_output,
)
from .tree import TreeArrays

# Level-wise frontier chunk cap — the analog of the wave grower's 128-slot
# wave_size cap: the level-wise partition + smaller-child label passes are
# (Ld, N) broadcast-compares over the whole frontier, and a wide level
# (Ld up to num_leaves/2) would materialize multi-GB intermediates at
# bench N (128 x 1M int32 is already 512 MB).  Frontier slices are
# processed in groups of at most this many splits — disjoint row
# ownership makes the chunked int32 accumulation bit-identical to the
# single-pass sum (tests/test_partition_grower.py pins this).  Lowered by
# tests to exercise the chunked branches.
_LEVEL_CHUNK = 128


class GrowerState(NamedTuple):
    leaf_id: jax.Array        # (N,) int32
    hist_pool: jax.Array      # (L, F, B, 3)
    leaf_sums: jax.Array      # (L, 3)
    leaf_depth: jax.Array     # (L,) int32
    best_gain: jax.Array      # (L,)
    best_feat: jax.Array      # (L,) int32
    best_bin: jax.Array       # (L,) int32
    best_dl: jax.Array        # (L,) bool
    best_left: jax.Array      # (L, 3)
    best_right: jax.Array     # (L, 3)
    best_iscat: jax.Array     # (L,) bool
    best_bitset: jax.Array    # (L, W) uint32
    leaf_constr: jax.Array    # (L, 2) — per-leaf [min, max] output bound
                              # (reference BasicLeafConstraints entries_)
    leaf_out: jax.Array       # (L,) — current leaf output values (smoothing)
    leaf_used: jax.Array      # (L, F) bool — branch features per leaf
                              # (reference Tree::branch_features)
    cegb_used: jax.Array      # (F,) bool — model-level used features (CEGB)
    cegb_marks: jax.Array     # (N, F) bool — rows already charged for a
                              # feature (cegb_penalty_feature_lazy;
                              # (1, 1) dummy when lazy costs are off)
    order: jax.Array          # (N+CAPMAX,) int32 — rows grouped by leaf
                              # (reference DataPartition indices_; ghost
                              # entries hold N). dummy (1,) when masked mode
    leaf_begin: jax.Array     # (L,) int32 — segment begin per leaf
    leaf_phys: jax.Array      # (L,) int32 — physical rows per leaf
    forced_leaf: jax.Array    # (S, 2) int32 — realized [left, right] leaf ids
                              # per applied forced step (-1 = not applied);
                              # dummy (1, 2) when no forced splits
    tree: TreeArrays
    leaf_is_left: jax.Array   # (L,) bool
    num_leaves: jax.Array     # () int32
    done: jax.Array           # () bool


def forced_split_stats(hf, parent_sum, ffeat, fbin, fdl, meta, params):
    """Left/right sums + ACTUAL gain of a forced split, from the leaf's
    histogram of the forced feature (the reference computes the real
    SplitInfo for forced thresholds, serial_tree_learner.cpp:500-520).
    Shared by the sequential and level-wise growers so the NaN
    default-direction accounting and the relative-gain convention cannot
    drift apart."""
    from ..ops.split import leaf_gain

    cumf = jnp.cumsum(hf, axis=0)                    # (B, 3)
    has_nan = meta.missing_type[ffeat] == MISSING_NAN
    has_zero = meta.missing_type[ffeat] == MISSING_ZERO
    # the missing mass (NaN bin or zero-as-missing bin) rides with the
    # default direction, independent of its position vs the threshold
    miss_bin = jnp.where(has_nan, jnp.maximum(meta.nan_bin[ffeat], 0),
                         meta.zero_bin[ffeat])
    miss_c = hf[miss_bin] * jnp.where(has_nan | has_zero, 1.0, 0.0)
    in_cum = (has_nan | has_zero) & (miss_bin <= fbin)
    flsum = cumf[fbin] + miss_c * (
        jnp.asarray(fdl).astype(jnp.float32) - in_cum.astype(jnp.float32))
    frsum = parent_sum - flsum
    fgain = (leaf_gain(flsum[0], flsum[1], params)
             + leaf_gain(frsum[0], frsum[1], params)
             - leaf_gain(parent_sum[0], parent_sum[1], params)
             - params.min_gain_to_split)
    return flsum, frsum, fgain


def allowed_features_for(groups, used):
    """reference ColSampler::GetByNode: branch features + union of
    interaction-constraint groups containing ALL branch features
    (src/treelearner/col_sampler.hpp:92-112).  ``groups`` is the (G, F)
    bool constraint matrix or None; ``used`` the leaf's (F,) branch-feature
    mask.  Shared by the sequential, level-wise and wave growers."""
    if groups is None:
        return jnp.ones_like(used)
    fits = jnp.all(groups | ~used[None, :], axis=1)       # (G,)
    return used | jnp.any(groups & fits[:, None], axis=0)


def _node_feature_mask(key, uid, base_mask, fraction: float):
    """Per-node column sampling (reference: ColSampler bynode,
    src/treelearner/col_sampler.hpp:20)."""
    if fraction >= 1.0:
        return base_mask
    F = base_mask.shape[0]
    scores = jax.random.uniform(jax.random.fold_in(key, uid), (F,))
    scores = jnp.where(base_mask, scores, jnp.inf)
    n_allowed = jnp.sum(base_mask)
    k = jnp.maximum(1, jnp.ceil(fraction * n_allowed)).astype(jnp.int32)
    thresh = jnp.sort(scores)[jnp.maximum(k - 1, 0)]
    return base_mask & (scores <= thresh)


def make_leafwise_grower(
    *,
    num_leaves: int,
    num_bins: int,
    meta: FeatureMeta,
    params: SplitParams,
    max_depth: int = -1,
    feature_fraction_bynode: float = 1.0,
    monotone_penalty: float = 0.0,
    interaction_groups=None,
    forced_splits=None,
    cegb_coupled=None,
    cegb_lazy=None,
    partition: bool = False,
    hist_fn: Callable = None,
    split_fn: Callable = None,
    sums_fn: Callable = None,
    bins_of_fn: Callable = None,
    num_features: int = 0,
    hist_pool_mb: float = -1.0,
):
    """Build the jittable ``grow(binned, g3, base_mask, key)`` function.

    ``partition=True`` selects the DataPartition-based fast path (reference:
    src/treelearner/data_partition.hpp — rows kept grouped by leaf in an
    index array): each split only touches its parent's segment and the
    smaller child's histogram is built over COMPACTED rows, so per-split
    cost is O(segment) instead of O(num_data).  Dynamic segment sizes are
    bucketed into a few static capacities dispatched with ``lax.switch``.

    ``forced_splits``: optional (S, 5) int array [parent_step, side, feature,
    bin, default_left] applied as the first S steps in BFS order
    (parse_forced_splits format; parent_step = -1 is the root, side selects
    the parent step's realized left/right child leaf — reference:
    SerialTreeLearner::ForceSplits, serial_tree_learner.cpp:427-539).

    ``hist_fn(binned, g3, leaf_id, target_leaf) -> (F, B, 3)`` — histogram of
    one leaf's rows (globally summed in distributed mode).
    ``split_fn(hist, parent_sum, feature_mask, key, uid, constraint, depth,
    parent_output) -> SplitResult`` — defaults to the local vectorized
    search; the feature-parallel learner substitutes a sharded search +
    cross-shard argmax.  ``constraint`` is the leaf's monotone [min, max]
    output bound; ``parent_output`` the leaf's current value (path
    smoothing).
    ``sums_fn(g3) -> (3,)`` — root grad/hess/count totals (psum over the row
    mesh axis in data-parallel mode; the analog of the reference's root
    sum Allreduce, data_parallel_tree_learner.cpp:126-151).
    ``interaction_groups``: optional (G, F) bool matrix of interaction
    constraints (reference ColSampler::GetByNode, col_sampler.hpp:92-112).
    """
    L = num_leaves
    L1 = max(L - 1, 1)
    use_mc = bool(np.asarray(meta.monotone_type).any())
    groups = (jnp.asarray(interaction_groups)
              if interaction_groups is not None else None)
    S_forced = 0 if forced_splits is None else min(len(forced_splits), L - 1)
    if S_forced:
        # (S, 5) [parent_step, side, feature, bin, dl] — leaf ids resolved at
        # runtime from the realized forced_leaf table (see GrowerState)
        f_parent = jnp.asarray(forced_splits[:S_forced, 0], jnp.int32)
        f_side = jnp.asarray(forced_splits[:S_forced, 1], jnp.int32)
        f_feat = jnp.asarray(forced_splits[:S_forced, 2], jnp.int32)
        f_bin = jnp.asarray(forced_splits[:S_forced, 3], jnp.int32)
        f_dl = jnp.asarray(forced_splits[:S_forced, 4] != 0)

    use_cegb = ((params.cegb_penalty_split > 0) or (cegb_coupled is not None)
                or (cegb_lazy is not None))
    coupled = (jnp.asarray(cegb_coupled, jnp.float32)
               if cegb_coupled is not None else None)
    lazy = (jnp.asarray(cegb_lazy, jnp.float32)
            if cegb_lazy is not None else None)
    if lazy is not None and partition:
        raise ValueError("cegb_penalty_feature_lazy requires the masked "
                         "leaf-wise grower (per-row leaf ids)")

    def cegb_penalty_vec(parent_cnt, used_model, unmarked_cnt=None):
        """reference: CostEfficientGradientBoosting::DetlaGain —
        tradeoff*(penalty_split*n_leaf + coupled_penalty[unused features]
        + lazy_penalty[f]*#unmarked-rows-in-leaf
        (CalculateOndemandCosts, cost_effective_gradient_boosting.hpp:125))."""
        if not use_cegb:
            return None
        pen = jnp.full(meta.num_bins.shape[0],
                       params.cegb_tradeoff * params.cegb_penalty_split
                       * parent_cnt, jnp.float32)
        if coupled is not None:
            pen = pen + params.cegb_tradeoff * coupled * (
                ~used_model).astype(jnp.float32)
        if lazy is not None and unmarked_cnt is not None:
            pen = pen + params.cegb_tradeoff * lazy * unmarked_cnt
        return pen

    if split_fn is None:
        def split_fn(hist, parent, mask, key, uid, constraint, depth,
                     parent_output, cegb_pen=None):
            rk = jax.random.fold_in(key, uid + 1_000_003 + params.extra_seed) \
                if params.extra_trees else None
            return find_best_split(hist, parent, meta, mask, params,
                                   constraint, depth, monotone_penalty,
                                   parent_output, rk, cegb_pen)

    def allowed_features(used):
        return allowed_features_for(groups, used)

    if sums_fn is None:
        def sums_fn(g3):
            # ordered scatter fold into one slot, NOT jnp.sum: scatter-add
            # applies the row additions sequentially in row order, which
            # the out-of-core row-block trainer CONTINUES across blocks
            # bit-exactly (ops/histogram.sums_accum) — jnp.sum's internal
            # reduction tree is shape-dependent and not streamable.  Same
            # mechanism as the histogram pass itself; value differs from
            # jnp.sum only in the last ulp.
            return jnp.zeros((1, 3), jnp.float32).at[
                jnp.zeros(g3.shape[0], jnp.int32)].add(g3)[0]

    if bins_of_fn is None:
        def bins_of_fn(binned, feat):
            return binned[feat]

    # ---- histogram pool sizing (reference: HistogramPool LRU bounded by
    # histogram_pool_size MB, feature_histogram.hpp:1061-1290).  The pool
    # holds one (F, B, 3) f32 histogram per leaf to enable the subtraction
    # trick; when it would exceed the cap (histogram_pool_size > 0) or the
    # 512 MB auto bound (histogram_pool_size < 0), switch to pool-free mode:
    # both children's histograms are built directly (2 passes per split,
    # the reference's no-cache behavior) and HBM stays O(F·B) regardless of
    # num_leaves.  Forced splits read parent histograms after the fact and
    # therefore keep the pool.
    F_pool = num_features if num_features else len(np.asarray(meta.num_bins))
    pool_bytes = float(L) * F_pool * num_bins * 3 * 4
    cap_bytes = (hist_pool_mb * (1 << 20) if hist_pool_mb > 0
                 else 512.0 * (1 << 20))
    use_pool = S_forced > 0 or pool_bytes <= cap_bytes
    if not use_pool:
        from ..utils.log import log_info

        log_info(
            f"Histogram pool would need {pool_bytes / (1 << 20):.0f} MB "
            f"(> {cap_bytes / (1 << 20):.0f} MB cap); using pool-free "
            "growth (children histograms rebuilt per split)")

    def clamp_out(sums, constr, parent_out=0.0):
        out = leaf_output(sums[0], sums[1], params)
        if params.path_smooth > 0:
            out = smooth_output(out, sums[2], parent_out, params)
        if not use_mc:
            return out
        return jnp.clip(out, constr[0], constr[1])

    def apply_decision(binned, leaf_id, leaf, new_leaf, feat, thr, dl,
                       is_cat, bitset):
        with jax.named_scope("lgbm.partition"):
            bins_f = bins_of_fn(binned, feat)       # (N,) original bins
            is_na = ((meta.missing_type[feat] == MISSING_NAN)
                     & (bins_f == meta.nan_bin[feat])) | (
                (meta.missing_type[feat] == MISSING_ZERO)
                & (bins_f == meta.zero_bin[feat]))
            go_left = jnp.where(is_na, dl, bins_f <= thr)
            bi = bins_f.astype(jnp.int32)
            word = bitset[bi >> 5]
            in_set = ((word >> (bi.astype(jnp.uint32) & 31)) & 1) == 1
            go_left = jnp.where(is_cat, in_set, go_left)
            return jnp.where((leaf_id == leaf) & (~go_left), new_leaf,
                             leaf_id)

    def grow(binned, g3, base_mask, key, cegb_used=None):
        # ``binned`` may arrive prepared for the histogram kernel
        # (hist_pallas.HistBins): whole-matrix passes take it as it is,
        # decisions and the segment gather read its (F, N) matrix
        bins = bin_matrix(binned)
        N = bins.shape[1]
        F = base_mask.shape[0]    # ORIGINAL features (binned may be the
                                  # narrower EFB bundle matrix)
        B = num_bins
        marks_in = None
        if isinstance(cegb_used, (tuple, list)):
            cegb_used, marks_in = cegb_used
        if cegb_used is None:
            cegb_used = jnp.zeros(F, bool)
        if lazy is not None:
            marks0 = (marks_in if marks_in is not None
                      else jnp.zeros((N, F), bool))
        else:
            marks0 = jnp.zeros((1, 1), bool)

        # ---- bucketed static capacities for the partition fast path -----
        if partition:
            caps = []
            c = 2048
            while c < N:
                caps.append(c)
                c = (c * 3) // 2
            caps.append(N)
            capmax = caps[-1]

            def bucket_of(n):
                b = jnp.zeros((), jnp.int32)
                for cc in caps[:-1]:
                    b = b + (n > cc).astype(jnp.int32)
                return b

            def partition_segment(order, s_begin, n_p, feat, thr, dl,
                                  iscat, bitset):
                """Stable two-way partition of one leaf's segment
                (reference DataPartition::Split, data_partition.hpp:101)."""
                bins_row = bins_of_fn(bins, feat)          # (N,) orig bins

                def make_branch(CAP):
                    def br(op):
                        order, s_begin, n_p, thr, dl, iscat, bitset = op
                        seg = lax.dynamic_slice(order, (s_begin,), (CAP,))
                        bseg = jnp.take(bins_row, seg, mode="fill",
                                        fill_value=0)
                        valid = jnp.arange(CAP) < n_p
                        is_na = ((meta.missing_type[feat]
                                  == MISSING_NAN)
                                 & (bseg == meta.nan_bin[feat])) | (
                            (meta.missing_type[feat] == MISSING_ZERO)
                            & (bseg == meta.zero_bin[feat]))
                        gl = jnp.where(is_na, dl, bseg <= thr)
                        bi = bseg.astype(jnp.int32)
                        word = bitset[bi >> 5]
                        in_set = ((word >> (bi.astype(jnp.uint32) & 31))
                                  & 1) == 1
                        gl = jnp.where(iscat, in_set, gl) & valid
                        n_l = gl.sum().astype(jnp.int32)
                        posl = jnp.where(gl, size=CAP, fill_value=CAP)[0]
                        posr = jnp.where((~gl) & valid, size=CAP,
                                         fill_value=CAP)[0]
                        lrows = jnp.take(seg, posl, mode="fill", fill_value=N)
                        rrows = jnp.take(seg, posr, mode="fill", fill_value=N)
                        pos = jnp.arange(CAP)
                        rpick = jnp.take(rrows,
                                         jnp.clip(pos - n_l, 0, CAP - 1))
                        comb = jnp.where(pos < n_l, lrows, rpick)
                        comb = jnp.where(valid, comb, seg)  # ghosts untouched
                        order2 = lax.dynamic_update_slice(order, comb,
                                                          (s_begin,))
                        return order2, n_l
                    return br

                with jax.named_scope("lgbm.partition"):
                    return lax.switch(
                        bucket_of(n_p), [make_branch(cc) for cc in caps],
                        (order, s_begin, n_p, thr, dl, iscat, bitset))

            def hist_compact(order, s_begin, n_s):
                """Histogram of one COMPACTED segment (the smaller child)
                — the reference's ordered-gradient smaller-leaf pass.  The
                slice capacity can exceed the segment, so rows beyond n_s
                (they belong to OTHER leaves) are zero-masked."""
                def make_branch(CAP):
                    def br(op):
                        order, s_begin, n_s = op
                        rows = lax.dynamic_slice(order, (s_begin,), (CAP,))
                        in_seg = jnp.arange(CAP) < n_s
                        bins_sub = jnp.take(bins, rows, axis=1,
                                            mode="fill", fill_value=0)
                        g3_sub = jnp.take(g3, rows, axis=0, mode="fill",
                                          fill_value=0.0)
                        g3_sub = jnp.where(in_seg[:, None], g3_sub, 0.0)
                        return hist_fn(bins_sub, g3_sub,
                                       jnp.zeros(CAP, jnp.int32),
                                       jnp.asarray(0, jnp.int32))
                    return br

                return lax.switch(
                    bucket_of(n_s), [make_branch(cc) for cc in caps],
                    (order, s_begin, n_s))

            order0 = jnp.concatenate([
                jnp.arange(N, dtype=jnp.int32),
                jnp.full(capmax, N, jnp.int32)])
            leaf_begin0 = jnp.zeros(L, jnp.int32)
            leaf_phys0 = jnp.zeros(L, jnp.int32).at[0].set(N)
        else:
            order0 = jnp.zeros(1, jnp.int32)
            leaf_begin0 = jnp.zeros(L, jnp.int32)
            leaf_phys0 = jnp.zeros(L, jnp.int32)

        leaf_id = jnp.zeros(N, jnp.int32)
        hist0 = hist_fn(binned, g3, leaf_id, jnp.asarray(0, jnp.int32))
        root_sum = sums_fn(g3)
        mask0 = _node_feature_mask(key, 0, base_mask, feature_fraction_bynode)
        no_constr = jnp.asarray(NO_CONSTRAINT, jnp.float32)
        used0 = jnp.zeros(F, bool)
        mask0 = mask0 & allowed_features(used0)
        out0 = leaf_output(root_sum[0], root_sum[1], params)
        if params.path_smooth > 0:
            out0 = smooth_output(out0, root_sum[2], 0.0, params)
        unmk0 = ((~marks0).sum(axis=0).astype(jnp.float32)
                 if lazy is not None else None)
        res0 = split_fn(hist0, root_sum, mask0, key, 0, no_constr, 0, out0,
                        cegb_penalty_vec(root_sum[2], cegb_used, unmk0))

        from ..models.tree import empty_tree

        W = res0.cat_bitset.shape[0]
        st = GrowerState(
            leaf_id=leaf_id,
            hist_pool=(jnp.zeros((L,) + hist0.shape,
                                 jnp.float32).at[0].set(hist0)
                       if use_pool else jnp.zeros((1, 1, 1, 3), jnp.float32)),
            leaf_sums=jnp.zeros((L, 3), jnp.float32).at[0].set(root_sum),
            leaf_depth=jnp.zeros(L, jnp.int32),
            best_gain=jnp.full(L, -jnp.inf, jnp.float32).at[0].set(res0.gain),
            best_feat=jnp.zeros(L, jnp.int32).at[0].set(res0.feature),
            best_bin=jnp.zeros(L, jnp.int32).at[0].set(res0.threshold_bin),
            best_dl=jnp.zeros(L, bool).at[0].set(res0.default_left),
            best_left=jnp.zeros((L, 3), jnp.float32).at[0].set(res0.left_sum),
            best_right=jnp.zeros((L, 3), jnp.float32).at[0].set(res0.right_sum),
            best_iscat=jnp.zeros(L, bool).at[0].set(res0.is_cat),
            best_bitset=jnp.zeros((L, W), jnp.uint32).at[0].set(res0.cat_bitset),
            leaf_constr=jnp.tile(jnp.asarray(NO_CONSTRAINT, jnp.float32), (L, 1)),
            leaf_out=jnp.zeros(L, jnp.float32).at[0].set(out0),
            leaf_used=jnp.zeros((L, F), bool),
            cegb_used=cegb_used,
            cegb_marks=marks0,
            order=order0,
            leaf_begin=leaf_begin0,
            leaf_phys=leaf_phys0,
            forced_leaf=jnp.full((max(S_forced, 1), 2), -1, jnp.int32),
            tree=empty_tree(L, W),
            leaf_is_left=jnp.zeros(L, bool),
            num_leaves=jnp.asarray(1, jnp.int32),
            done=jnp.asarray(L <= 1),
        )

        def body(s, st: GrowerState) -> GrowerState:
            leaf = jnp.argmax(st.best_gain).astype(jnp.int32)
            gain = st.best_gain[leaf]
            is_forced = jnp.asarray(False)
            if S_forced:
                # forced splits occupy the first S steps (reference
                # ForceSplits BFS, serial_tree_learner.cpp:427-539); a forced
                # split that would create an empty child is skipped, and any
                # step whose parent step was skipped is skipped too (the
                # realized forced_leaf entry stays -1)
                sidx = jnp.minimum(s, S_forced - 1)
                maybe = s < S_forced
                pstep = f_parent[sidx]
                fleaf_raw = jnp.where(
                    pstep < 0, 0,
                    st.forced_leaf[jnp.maximum(pstep, 0), f_side[sidx]])
                parent_ok = (pstep < 0) | (fleaf_raw >= 0)
                fleaf = jnp.maximum(fleaf_raw, 0)
                ffeat = f_feat[sidx]
                fthr, fdl = f_bin[sidx], f_dl[sidx]
                flsum, frsum, forced_gain = forced_split_stats(
                    st.hist_pool[fleaf, ffeat], st.leaf_sums[fleaf],
                    ffeat, fthr, fdl, meta, params)
                ok_f = maybe & parent_ok & (flsum[2] > 0) & (frsum[2] > 0)
                is_forced = ok_f
                leaf = jnp.where(ok_f, fleaf, leaf)
                gain = jnp.where(ok_f, forced_gain, gain)
            active = (~st.done) & ((gain > 0) | is_forced)

            def do_split(st: GrowerState) -> GrowerState:
                nl = st.num_leaves                    # new (right-child) leaf index
                node = nl - 1                         # internal node index
                feat = st.best_feat[leaf]
                thr = st.best_bin[leaf]
                dl = st.best_dl[leaf]
                lsum = st.best_left[leaf]
                rsum = st.best_right[leaf]
                iscat = st.best_iscat[leaf]
                bitset = st.best_bitset[leaf]
                if S_forced:
                    sidx2 = jnp.minimum(s, S_forced - 1)
                    feat = jnp.where(is_forced, f_feat[sidx2], feat)
                    thr = jnp.where(is_forced, f_bin[sidx2], thr)
                    dl = jnp.where(is_forced, f_dl[sidx2], dl)
                    lsum = jnp.where(is_forced, flsum, lsum)
                    rsum = jnp.where(is_forced, frsum, rsum)
                    iscat = iscat & (~is_forced)
                    bitset = jnp.where(is_forced,
                                       jnp.zeros_like(bitset), bitset)
                    # record the REALIZED child leaf ids of this forced step
                    # (left child keeps the parent's leaf id, right child is
                    # the new leaf) so descendant forced steps resolve
                    # against actual leaf numbering
                    forced_next = st.forced_leaf.at[sidx2].set(
                        jnp.where(is_forced, jnp.stack([leaf, nl]),
                                  st.forced_leaf[sidx2]))
                else:
                    forced_next = st.forced_leaf
                parent_sum = st.leaf_sums[leaf]

                if partition:
                    s_begin = st.leaf_begin[leaf]
                    n_p = st.leaf_phys[leaf]
                    order2, n_l_phys = partition_segment(
                        st.order, s_begin, n_p, feat, thr, dl, iscat, bitset)
                    leaf_id = st.leaf_id      # reconstructed once at the end
                else:
                    order2, n_l_phys = st.order, jnp.asarray(0, jnp.int32)
                    leaf_id = apply_decision(bins, st.leaf_id, leaf, nl,
                                             feat, thr, dl, iscat, bitset)

                # monotone constraint propagation (reference:
                # BasicLeafConstraints::Update, monotone_constraints.hpp:99-117)
                pconstr = st.leaf_constr[leaf]
                pout = st.leaf_out[leaf]
                out_l = clamp_out(lsum, pconstr, pout)
                out_r = clamp_out(rsum, pconstr, pout)
                if use_mc:
                    mono = meta.monotone_type[feat]
                    mid = 0.5 * (out_l + out_r)
                    upd = (~iscat) & (mono != 0)
                    new_max_l = jnp.where(upd & (mono > 0),
                                          jnp.minimum(pconstr[1], mid), pconstr[1])
                    new_min_l = jnp.where(upd & (mono < 0),
                                          jnp.maximum(pconstr[0], mid), pconstr[0])
                    new_max_r = jnp.where(upd & (mono < 0),
                                          jnp.minimum(pconstr[1], mid), pconstr[1])
                    new_min_r = jnp.where(upd & (mono > 0),
                                          jnp.maximum(pconstr[0], mid), pconstr[0])
                    constr_l = jnp.stack([new_min_l, new_max_l])
                    constr_r = jnp.stack([new_min_r, new_max_r])
                else:
                    constr_l = constr_r = pconstr

                # histogram-subtraction trick: one pass over the smaller child
                if partition:
                    n_r_phys = n_p - n_l_phys
                    smaller_is_left = n_l_phys <= n_r_phys
                    sm_begin = jnp.where(smaller_is_left, s_begin,
                                         s_begin + n_l_phys)
                    sm_n = jnp.minimum(n_l_phys, n_r_phys)
                    h_small = hist_compact(order2, sm_begin, sm_n)
                else:
                    smaller_is_left = lsum[2] <= rsum[2]
                    smaller = jnp.where(smaller_is_left, leaf, nl)
                    h_small = hist_fn(binned, g3, leaf_id, smaller)
                if use_pool:
                    h_parent = st.hist_pool[leaf]
                    h_left = jnp.where(smaller_is_left, h_small,
                                       h_parent - h_small)
                    h_right = h_parent - h_left
                    pool = st.hist_pool.at[leaf].set(h_left).at[nl].set(h_right)
                else:
                    # pool-free: build the larger child directly too
                    if partition:
                        lg_begin = jnp.where(smaller_is_left,
                                             s_begin + sm_n, s_begin)
                        h_large = hist_compact(order2, lg_begin, n_p - sm_n)
                    else:
                        larger = jnp.where(smaller_is_left, nl, leaf)
                        h_large = hist_fn(binned, g3, leaf_id, larger)
                    h_left = jnp.where(smaller_is_left, h_small, h_large)
                    h_right = jnp.where(smaller_is_left, h_large, h_small)
                    pool = st.hist_pool

                d = st.leaf_depth[leaf] + 1
                depth_ok = (max_depth <= 0) | (d < max_depth)

                used_child = st.leaf_used[leaf].at[feat].set(True)
                allow_child = allowed_features(used_child)
                mask_l = _node_feature_mask(
                    key, 2 * s + 1, base_mask, feature_fraction_bynode
                ) & allow_child
                mask_r = _node_feature_mask(
                    key, 2 * s + 2, base_mask, feature_fraction_bynode
                ) & allow_child
                cegb_next = st.cegb_used.at[feat].set(True) \
                    if use_cegb else st.cegb_used
                if lazy is not None:
                    # mark the split leaf's rows for the split feature
                    # (UpdateLeafBestSplits, cegb hpp:110-121), THEN price
                    # the children's candidates by their unmarked rows
                    in_parent = st.leaf_id == leaf
                    marks_next = st.cegb_marks | (
                        in_parent[:, None]
                        & jax.nn.one_hot(feat, F, dtype=bool))
                    notm = (~marks_next).astype(jnp.float32)
                    unmk_l = (leaf_id == leaf).astype(jnp.float32) @ notm
                    unmk_r = (leaf_id == nl).astype(jnp.float32) @ notm
                else:
                    marks_next = st.cegb_marks
                    unmk_l = unmk_r = None
                res_l = split_fn(h_left, lsum, mask_l, key, 2 * s + 1,
                                 constr_l, d, out_l,
                                 cegb_penalty_vec(lsum[2], cegb_next, unmk_l))
                res_r = split_fn(h_right, rsum, mask_r, key, 2 * s + 2,
                                 constr_r, d, out_r,
                                 cegb_penalty_vec(rsum[2], cegb_next, unmk_r))
                gain_l = jnp.where(depth_ok, res_l.gain, -jnp.inf)
                gain_r = jnp.where(depth_ok, res_r.gain, -jnp.inf)

                t = st.tree
                # re-wire the parent pointer that pointed at ~leaf
                p = t.leaf_parent[leaf]
                p_safe = jnp.maximum(p, 0)
                was_left = st.leaf_is_left[leaf]
                lc = t.left_child.at[p_safe].set(
                    jnp.where((p >= 0) & was_left, node, t.left_child[p_safe])
                )
                rc = t.right_child.at[p_safe].set(
                    jnp.where((p >= 0) & (~was_left), node, t.right_child[p_safe])
                )
                lc = lc.at[node].set(-(leaf + 1))
                rc = rc.at[node].set(-(nl + 1))

                tree = t._replace(
                    num_leaves=nl + 1,
                    split_feature=t.split_feature.at[node].set(feat),
                    threshold_bin=t.threshold_bin.at[node].set(thr),
                    default_left=t.default_left.at[node].set(dl),
                    is_cat=t.is_cat.at[node].set(iscat),
                    cat_bitset=t.cat_bitset.at[node].set(bitset),
                    missing_type=t.missing_type.at[node].set(meta.missing_type[feat]),
                    left_child=lc,
                    right_child=rc,
                    split_gain=t.split_gain.at[node].set(gain),
                    internal_value=t.internal_value.at[node].set(pout),
                    internal_weight=t.internal_weight.at[node].set(parent_sum[1]),
                    internal_count=t.internal_count.at[node].set(
                        parent_sum[2].astype(jnp.int32)),
                    leaf_value=t.leaf_value.at[leaf].set(out_l).at[nl].set(out_r),
                    leaf_weight=t.leaf_weight.at[leaf].set(lsum[1]).at[nl].set(rsum[1]),
                    leaf_count=t.leaf_count.at[leaf].set(lsum[2].astype(jnp.int32))
                    .at[nl].set(rsum[2].astype(jnp.int32)),
                    leaf_parent=t.leaf_parent.at[leaf].set(node).at[nl].set(node),
                )

                return GrowerState(
                    leaf_id=leaf_id,
                    hist_pool=pool,
                    leaf_sums=st.leaf_sums.at[leaf].set(lsum).at[nl].set(rsum),
                    leaf_depth=st.leaf_depth.at[leaf].set(d).at[nl].set(d),
                    best_gain=st.best_gain.at[leaf].set(gain_l).at[nl].set(gain_r),
                    best_feat=st.best_feat.at[leaf].set(res_l.feature).at[nl].set(res_r.feature),
                    best_bin=st.best_bin.at[leaf]
                    .set(res_l.threshold_bin)
                    .at[nl]
                    .set(res_r.threshold_bin),
                    best_dl=st.best_dl.at[leaf].set(res_l.default_left).at[nl].set(res_r.default_left),
                    best_left=st.best_left.at[leaf].set(res_l.left_sum).at[nl].set(res_r.left_sum),
                    best_right=st.best_right.at[leaf].set(res_l.right_sum).at[nl].set(res_r.right_sum),
                    best_iscat=st.best_iscat.at[leaf].set(res_l.is_cat).at[nl].set(res_r.is_cat),
                    best_bitset=st.best_bitset.at[leaf].set(res_l.cat_bitset).at[nl].set(res_r.cat_bitset),
                    leaf_constr=st.leaf_constr.at[leaf].set(constr_l).at[nl].set(constr_r),
                    leaf_out=st.leaf_out.at[leaf].set(out_l).at[nl].set(out_r),
                    leaf_used=st.leaf_used.at[leaf].set(used_child)
                    .at[nl].set(used_child),
                    cegb_used=cegb_next,
                    cegb_marks=marks_next,
                    order=order2,
                    leaf_begin=st.leaf_begin.at[nl].set(
                        st.leaf_begin[leaf] + n_l_phys) if partition
                    else st.leaf_begin,
                    leaf_phys=st.leaf_phys.at[leaf].set(n_l_phys)
                    .at[nl].set(st.leaf_phys[leaf] - n_l_phys) if partition
                    else st.leaf_phys,
                    forced_leaf=forced_next,
                    tree=tree,
                    leaf_is_left=st.leaf_is_left.at[leaf].set(True).at[nl].set(False),
                    num_leaves=nl + 1,
                    done=st.done,
                )

            def no_split(st: GrowerState) -> GrowerState:
                return st._replace(done=jnp.asarray(True))

            return lax.cond(active, do_split, no_split, st)

        st = lax.fori_loop(0, L - 1, body, st) if L > 1 else st
        if partition and L > 1:
            # reconstruct the per-row leaf assignment from the partition
            # (one pass; the loop never touched the O(N) leaf_id array):
            # sort active segments by begin, find each position's segment by
            # searchsorted, then scatter through the row order.
            beg_eff = jnp.where(st.leaf_phys > 0, st.leaf_begin,
                                N + 1 + jnp.arange(L))
            leaf_order = jnp.argsort(beg_eff)
            sorted_begin = beg_eff[leaf_order]
            pos = jnp.arange(N)
            ordinal = jnp.clip(
                jnp.searchsorted(sorted_begin, pos, side="right") - 1, 0, L - 1)
            pos_leaf = leaf_order[ordinal].astype(jnp.int32)
            rows = st.order[:N]
            leaf_id_final = jnp.zeros(N, jnp.int32).at[rows].set(
                pos_leaf, mode="drop", unique_indices=True)
            return st.tree, leaf_id_final, root_sum
        return st.tree, st.leaf_id, root_sum

    return grow


# ---------------------------------------------------------------------------
# Level-wise (depth-wise) grower — the batched fast path
# ---------------------------------------------------------------------------


def make_levelwise_grower(
    *,
    num_leaves: int,
    num_bins: int,
    meta: FeatureMeta,
    params: SplitParams,
    max_depth: int = -1,
    feature_fraction_bynode: float = 1.0,
    monotone_penalty: float = 0.0,
    interaction_groups=None,
    cegb_coupled=None,
    forced_splits=None,
    hist_frontier_fn: Callable = None,
    split_fn: Callable = None,
    sums_fn: Callable = None,
    bins_of_fn: Callable = None,
):
    """Depth-wise tree growth with the whole frontier batched per level.

    ``forced_splits``: optional (S, 6) int array [parent_step, side,
    feature, bin, default_left, depth] in BFS order (parse_forced_splits).
    A forced step applies at its BFS depth's level: the targeted frontier
    leaf splits on the forced (feature, bin) instead of its best split,
    bypassing the gain test and the per-level budget ranking (reference:
    SerialTreeLearner::ForceSplits, serial_tree_learner.cpp:427-539 —
    forced splits occupy the top of the tree in both growth orders).

    Rationale: an exact leaf-wise step histograms ONE leaf, which on the MXU
    is a 3-row matmul (3/128 utilization).  Batching all `2^d` leaves of a
    level multiplies the matmul row count by the frontier size, which is what
    makes GBDT training MXU-bound instead of latency-bound.  Semantics match
    xgboost_hist's depthwise policy — the configuration the reference
    benchmarks itself against (docs/Experiments.rst:110-135) — with the
    ``num_leaves`` budget enforced by per-level gain ranking.

    ``hist_frontier_fn(binned, g3, leaf_id, L_level) -> (L_level, F, B, 3)``
    computes histograms for every leaf in one pass (psum-wrapped when
    data-parallel).
    """
    import math as _math

    from ..ops.split import find_best_split_batch

    L = num_leaves
    L1 = max(L - 1, 1)
    levels = _math.ceil(_math.log2(max(L, 2)))
    if max_depth > 0:
        levels = min(levels, max_depth)
    use_mc = bool(np.asarray(meta.monotone_type).any())
    groups_lw = (jnp.asarray(interaction_groups)
                 if interaction_groups is not None else None)

    S_forced = 0 if forced_splits is None else min(len(forced_splits), L - 1)
    steps_at_depth = {}
    if S_forced:
        fs_np = np.asarray(forced_splits)[:S_forced]
        if max_depth <= 0:
            # forced chains deeper than ceil(log2(L)) extend the level loop
            levels = max(levels, min(int(fs_np[:, 5].max()) + 1, L - 1))
        for s in range(S_forced):
            d = int(fs_np[s, 5])
            if d < levels:
                steps_at_depth.setdefault(d, []).append(s)

    use_cegb_lw = (params.cegb_penalty_split > 0) or (cegb_coupled is not None)
    coupled_lw = (jnp.asarray(cegb_coupled, jnp.float32)
                  if cegb_coupled is not None else None)

    def cegb_penalty_batch(parent_cnt, used_model):
        if not use_cegb_lw:
            return None
        F = meta.num_bins.shape[0]
        pen = (params.cegb_tradeoff * params.cegb_penalty_split
               * parent_cnt[:, None]) * jnp.ones((1, F), jnp.float32)
        if coupled_lw is not None:
            pen = pen + params.cegb_tradeoff * coupled_lw[None, :] * (
                ~used_model)[None, :].astype(jnp.float32)
        return pen

    if split_fn is None:
        def split_fn(hist, parent, mask, key, uid, constraint, depth,
                     parent_output, cegb_pen=None):
            rk = jax.random.fold_in(key, uid + 1_000_003 + params.extra_seed) \
                if params.extra_trees else None
            return find_best_split(hist, parent, meta, mask, params,
                                   constraint, depth, monotone_penalty,
                                   parent_output, rk, cegb_pen)

    if sums_fn is None:
        def sums_fn(g3):
            return g3.sum(axis=0)

    if bins_of_fn is None:
        def bins_of_fn(binned, feat):
            return binned[feat]

    use_cat_lw = bool(np.asarray(meta.is_categorical).any())

    def allowed_features_batch(used):
        if groups_lw is None:
            return jnp.ones_like(used)
        return jax.vmap(lambda u: allowed_features_for(groups_lw, u))(used)

    def clamp_out_batch(sums, constr, parent_out=None):
        out = jax.vmap(lambda s: leaf_output(s[0], s[1], params))(sums)
        if params.path_smooth > 0 and parent_out is not None:
            out = smooth_output(out, sums[:, 2], parent_out, params)
        if not use_mc:
            return out
        return jnp.clip(out, constr[:, 0], constr[:, 1])

    def grow(binned, g3, base_mask, key, cegb_used=None):
        bins = bin_matrix(binned)     # see the leaf-wise grower's note
        N = bins.shape[1]
        F = base_mask.shape[0]    # ORIGINAL features (EFB: binned narrower)
        if cegb_used is None:
            cegb_used = jnp.zeros(F, bool)
        from .tree import empty_tree

        leaf_id = jnp.zeros(N, jnp.int32)
        root_sum = sums_fn(g3)
        W = -(-num_bins // 32)
        tree = empty_tree(L, W)
        leaf_sums = jnp.zeros((L, 3), jnp.float32).at[0].set(root_sum)
        leaf_constr = jnp.tile(jnp.asarray(NO_CONSTRAINT, jnp.float32), (L, 1))
        out_root = leaf_output(root_sum[0], root_sum[1], params)
        if params.path_smooth > 0:
            out_root = smooth_output(out_root, root_sum[2], 0.0, params)
        leaf_out = jnp.zeros(L, jnp.float32).at[0].set(out_root)
        leaf_used = jnp.zeros((L, F), bool)
        leaf_active = jnp.zeros(L, bool).at[0].set(True)
        leaf_is_left = jnp.zeros(L, bool)
        num_leaves_cur = jnp.asarray(1, jnp.int32)
        num_nodes_cur = jnp.asarray(0, jnp.int32)
        forced_leaf = jnp.full((max(S_forced, 1), 2), -1, jnp.int32)

        # smaller-sibling + subtraction across levels (the reference's
        # smaller-leaf trick): level d rebuilds only the SMALLER child of
        # each level-(d-1) split; the sibling comes from the parent's stored
        # histogram by subtraction, and unsplit leaves keep theirs.  Halves
        # the per-level histogram pass.  Disabled when the carried state
        # would exceed 512 MB (wide-F configs).
        prev = None          # (hist, split_mask, new_leaf, sm_left)
        for d in range(levels):
            Ld = min(1 << d, L)
            if prev is None:
                hist = hist_frontier_fn(binned, g3, leaf_id, Ld)  # (Ld,F,B,3)
                use_sub_lw = (L * int(np.prod(hist.shape[1:])) * 4
                              ) <= 512 * (1 << 20)
            else:
                p_hist, p_mask, p_new, p_sml = prev
                Lp = p_hist.shape[0]
                # label rows of each split's smaller child with the PARENT
                # slot; everything else is dead (slot Lp, sliced away).
                # (Lp, N) broadcast-compare, NOT a per-row table gather —
                # 1M-row gathers measure 8-12 ms on this device vs ~3 ms
                # for a whole compare pass (tools/microbench_gather.py)
                sm_id = jnp.where(p_sml, jnp.arange(Lp, dtype=jnp.int32),
                                  p_new)
                sm_leaf = jnp.where(p_mask, sm_id, L + 1)       # (Lp,)
                # chunked (<=_LEVEL_CHUNK, N) broadcast-compare: each row
                # is owned by at most ONE frontier slot, so the chunked
                # int32 accumulation is bit-identical to one (Lp, N) pass
                acc = jnp.zeros(N, jnp.int32)
                for c0 in range(0, Lp, _LEVEL_CHUNK):
                    c1 = min(c0 + _LEVEL_CHUNK, Lp)
                    mine_c = sm_leaf[c0:c1, None] == leaf_id[None, :]
                    acc = acc + jnp.sum(jnp.where(
                        mine_c,
                        jnp.arange(c0, c1, dtype=jnp.int32)[:, None] - Lp,
                        0), axis=0)
                label = acc + Lp
                h_small = hist_frontier_fn(binned, g3, label, Lp + 1)[:Lp]
                smL = p_sml[:, None, None, None]
                h_left = jnp.where(smL, h_small, p_hist - h_small)
                h_right = p_hist - h_left
                hist = jnp.zeros((Ld,) + h_left.shape[1:], jnp.float32)
                hist = hist.at[:Lp].set(
                    jnp.where(p_mask[:, None, None, None], h_left,
                              p_hist))
                hist = hist.at[jnp.where(p_mask, p_new, Ld + 1)].set(
                    h_right, mode="drop")
            if feature_fraction_bynode < 1.0:
                masks = jnp.stack([
                    _node_feature_mask(key, d * (2 * L) + i, base_mask,
                                       feature_fraction_bynode)
                    for i in range(Ld)
                ])
            else:
                masks = jnp.broadcast_to(base_mask, (Ld, F))
            masks = masks & allowed_features_batch(leaf_used[:Ld])
            cegb_pen = cegb_penalty_batch(leaf_sums[:Ld, 2], cegb_used)
            # one uid per LEAF (not per level) so extra_trees draws distinct
            # random thresholds for each node, like the leaf-wise 2s+1/2s+2
            # numbering; shares the level-d feature-mask uid base
            uids = d * (2 * L) + jnp.arange(Ld, dtype=jnp.int32)
            if cegb_pen is None:
                res = jax.vmap(
                    lambda h, p, m, c, po, u: split_fn(h, p, m, key, u, c, d, po)
                )(hist, leaf_sums[:Ld], masks, leaf_constr[:Ld], leaf_out[:Ld],
                  uids)
            else:
                res = jax.vmap(
                    lambda h, p, m, c, po, u, cp: split_fn(
                        h, p, m, key, u, c, d, po, cp)
                )(hist, leaf_sums[:Ld], masks, leaf_constr[:Ld],
                  leaf_out[:Ld], uids, cegb_pen)

            # ---- forced splits for this level (BFS depth == d) ------------
            forced_now = jnp.zeros(Ld, bool)
            forced_steps_d = steps_at_depth.get(d, [])
            forced_resolved = {}          # s -> (tleaf, ok) for recording
            for s in forced_steps_d:
                pstep, side = int(fs_np[s, 0]), int(fs_np[s, 1])
                ffeat, fbin = int(fs_np[s, 2]), int(fs_np[s, 3])
                fdl = bool(fs_np[s, 4])
                traw = (jnp.asarray(0, jnp.int32) if pstep < 0
                        else forced_leaf[pstep, side])
                ok_p = (traw >= 0) & (traw < Ld)
                tleaf = jnp.clip(traw, 0, Ld - 1)
                flsum, frsum, fgain = forced_split_stats(
                    hist[tleaf, ffeat], leaf_sums[tleaf], ffeat, fbin, fdl,
                    meta, params)
                ok = ok_p & leaf_active[tleaf] & (flsum[2] > 0) & \
                    (frsum[2] > 0)
                forced_resolved[s] = (tleaf, ok)
                sel = jax.nn.one_hot(tleaf, Ld, dtype=bool) & ok
                res = res._replace(
                    gain=jnp.where(sel, fgain, res.gain),
                    feature=jnp.where(sel, ffeat, res.feature),
                    threshold_bin=jnp.where(sel, fbin, res.threshold_bin),
                    default_left=jnp.where(sel, fdl, res.default_left),
                    is_cat=jnp.where(sel, False, res.is_cat),
                    left_sum=jnp.where(sel[:, None], flsum[None, :],
                                       res.left_sum),
                    right_sum=jnp.where(sel[:, None], frsum[None, :],
                                        res.right_sum),
                )
                forced_now = forced_now | sel

            gains = jnp.where(leaf_active[:Ld], res.gain, -jnp.inf)
            rank_gains = jnp.where(forced_now, jnp.inf, gains)
            want = rank_gains > 0
            # budget: rank wanted splits by gain, keep the top (L - current);
            # forced splits rank first (reference applies them regardless of
            # the gain test)
            order = jnp.argsort(-jnp.where(want, rank_gains, -jnp.inf))
            rank = jnp.zeros(Ld, jnp.int32).at[order].set(
                jnp.arange(Ld, dtype=jnp.int32))
            budget = L - num_leaves_cur
            split_mask = want & (rank < budget)

            split_order = jnp.cumsum(split_mask.astype(jnp.int32)) - 1
            node_idx = num_nodes_cur + split_order          # (Ld,)
            new_leaf = num_leaves_cur + split_order
            for s in forced_steps_d:
                # record the REALIZED children of applied forced steps so
                # deeper forced steps resolve against actual leaf ids
                # (left child keeps the leaf slot, right child is new_leaf)
                tleaf, ok = forced_resolved[s]
                applied = ok & split_mask[tleaf]
                forced_leaf = forced_leaf.at[s].set(jnp.where(
                    applied, jnp.stack([tleaf, new_leaf[tleaf]]),
                    forced_leaf[s]))

            # partition update: (Ld, N) broadcast-compare over the level's
            # split leaves (the same formulation as the wave grower's
            # round_pass — per-row table gathers measure 8-12 ms per 1M
            # rows on this device vs ~3 ms for the whole compare pass,
            # tools/microbench_gather.py; this was ~2/3 of the level-wise
            # iteration before round 5), processed in frontier chunks of
            # at most _LEVEL_CHUNK splits so wide levels never
            # materialize the full (Ld, N) intermediates (the wave
            # grower's 128-slot cap, applied to the level frontier).
            # Disjoint row ownership keeps the chunked accumulation
            # bit-identical to the single pass.
            feat_k = res.feature                             # (Ld,)
            leafk = jnp.where(split_mask,
                              jnp.arange(Ld, dtype=jnp.int32), L)
            delta = jnp.zeros(N, jnp.int32)
            for c0 in range(0, Ld, _LEVEL_CHUNK):
                c1 = min(c0 + _LEVEL_CHUNK, Ld)
                fk = feat_k[c0:c1]
                bk = jax.vmap(lambda f: bins_of_fn(bins, f))(fk) \
                    .astype(jnp.int32)                       # (<=C, N)
                mt_k = meta.missing_type[fk][:, None]
                na_k = ((mt_k == MISSING_NAN)
                        & (bk == meta.nan_bin[fk][:, None])) | (
                    (mt_k == MISSING_ZERO)
                    & (bk == meta.zero_bin[fk][:, None]))
                glk = jnp.where(na_k, res.default_left[c0:c1, None],
                                bk <= res.threshold_bin[c0:c1, None])
                if use_cat_lw:  # categorical: bin-space bitset membership
                    word = jnp.zeros(bk.shape, jnp.uint32)
                    for wv in range(W):
                        word = jnp.where(
                            (bk >> 5) == wv,
                            res.cat_bitset[c0:c1, wv][:, None], word)
                    in_set = ((word >> (bk.astype(jnp.uint32) & 31))
                              & 1) == 1
                    glk = jnp.where(res.is_cat[c0:c1, None], in_set, glk)
                mine = leafk[c0:c1, None] == leaf_id[None, :]
                go_r = mine & (~glk)
                delta = delta + jnp.sum(
                    jnp.where(go_r, new_leaf[c0:c1, None]
                              - leaf_id[None, :], 0), axis=0)
            leaf_id = leaf_id + delta

            # tree array updates (scatter with out-of-bounds drop for masked)
            nd = jnp.where(split_mask, node_idx, L1 + 1)
            nl = jnp.where(split_mask, new_leaf, L + 1)
            ld_idx = jnp.where(split_mask, jnp.arange(Ld), L + 1)
            pconstr = leaf_constr[:Ld]
            parent_out = leaf_out[:Ld]
            left_out = clamp_out_batch(res.left_sum, pconstr, parent_out)
            right_out = clamp_out_batch(res.right_sum, pconstr, parent_out)
            if use_mc:
                # BasicLeafConstraints::Update, vectorized over the level
                mono = meta.monotone_type[res.feature]
                mid = 0.5 * (left_out + right_out)
                upd = (~res.is_cat) & (mono != 0)
                max_l = jnp.where(upd & (mono > 0),
                                  jnp.minimum(pconstr[:, 1], mid), pconstr[:, 1])
                min_l = jnp.where(upd & (mono < 0),
                                  jnp.maximum(pconstr[:, 0], mid), pconstr[:, 0])
                max_r = jnp.where(upd & (mono < 0),
                                  jnp.minimum(pconstr[:, 1], mid), pconstr[:, 1])
                min_r = jnp.where(upd & (mono > 0),
                                  jnp.maximum(pconstr[:, 0], mid), pconstr[:, 0])
                constr_l = jnp.stack([min_l, max_l], axis=1)
                constr_r = jnp.stack([min_r, max_r], axis=1)
            else:
                constr_l = constr_r = pconstr

            t = tree
            # re-wire parents of the split leaves
            p = t.leaf_parent[jnp.minimum(ld_idx, L - 1)]
            fix_l = jnp.where(split_mask & (p >= 0) & leaf_is_left[jnp.minimum(ld_idx, L - 1)],
                              jnp.maximum(p, 0), L1 + 1)
            fix_r = jnp.where(split_mask & (p >= 0) & (~leaf_is_left[jnp.minimum(ld_idx, L - 1)]),
                              jnp.maximum(p, 0), L1 + 1)
            lc = t.left_child.at[fix_l].set(nd, mode="drop")
            rc = t.right_child.at[fix_r].set(nd, mode="drop")
            lc = lc.at[nd].set(-(ld_idx + 1), mode="drop")
            rc = rc.at[nd].set(-(nl + 1), mode="drop")
            tree = t._replace(
                num_leaves=num_leaves_cur + split_mask.sum(),
                split_feature=t.split_feature.at[nd].set(res.feature, mode="drop"),
                threshold_bin=t.threshold_bin.at[nd].set(res.threshold_bin, mode="drop"),
                default_left=t.default_left.at[nd].set(res.default_left, mode="drop"),
                is_cat=t.is_cat.at[nd].set(res.is_cat, mode="drop"),
                cat_bitset=t.cat_bitset.at[nd].set(res.cat_bitset, mode="drop"),
                missing_type=t.missing_type.at[nd].set(
                    meta.missing_type[res.feature], mode="drop"),
                left_child=lc,
                right_child=rc,
                split_gain=t.split_gain.at[nd].set(res.gain, mode="drop"),
                internal_value=t.internal_value.at[nd].set(parent_out, mode="drop"),
                internal_weight=t.internal_weight.at[nd].set(
                    leaf_sums[:Ld, 1], mode="drop"),
                internal_count=t.internal_count.at[nd].set(
                    leaf_sums[:Ld, 2].astype(jnp.int32), mode="drop"),
                leaf_value=t.leaf_value.at[ld_idx].set(left_out, mode="drop")
                .at[nl].set(right_out, mode="drop"),
                leaf_weight=t.leaf_weight.at[ld_idx].set(res.left_sum[:, 1], mode="drop")
                .at[nl].set(res.right_sum[:, 1], mode="drop"),
                leaf_count=t.leaf_count.at[ld_idx].set(
                    res.left_sum[:, 2].astype(jnp.int32), mode="drop")
                .at[nl].set(res.right_sum[:, 2].astype(jnp.int32), mode="drop"),
                leaf_parent=t.leaf_parent.at[ld_idx].set(nd, mode="drop")
                .at[nl].set(nd, mode="drop"),
            )
            leaf_sums = leaf_sums.at[ld_idx].set(res.left_sum, mode="drop") \
                .at[nl].set(res.right_sum, mode="drop")
            leaf_constr = leaf_constr.at[ld_idx].set(constr_l, mode="drop") \
                .at[nl].set(constr_r, mode="drop")
            leaf_out = leaf_out.at[ld_idx].set(left_out, mode="drop") \
                .at[nl].set(right_out, mode="drop")
            if use_cegb_lw:
                cegb_used = cegb_used | jnp.any(
                    jax.nn.one_hot(res.feature, F, dtype=bool)
                    & split_mask[:, None], axis=0)
            used_child = leaf_used[:Ld] | jax.nn.one_hot(
                res.feature, F, dtype=bool)
            leaf_used = leaf_used.at[ld_idx].set(used_child, mode="drop") \
                .at[nl].set(used_child, mode="drop")
            leaf_is_left = leaf_is_left.at[ld_idx].set(True, mode="drop") \
                .at[nl].set(False, mode="drop")
            leaf_active = (leaf_active & jnp.pad(split_mask, (0, L - Ld))
                           if Ld < L else leaf_active & split_mask)
            leaf_active = leaf_active.at[nl].set(True, mode="drop")
            num_leaves_cur = num_leaves_cur + split_mask.sum()
            num_nodes_cur = num_nodes_cur + split_mask.sum()
            if d + 1 < levels and use_sub_lw:
                prev = (hist, split_mask,
                        jnp.where(split_mask, new_leaf, L + 1),
                        res.left_sum[:, 2] <= res.right_sum[:, 2])
            else:
                prev = None

        return tree, leaf_id, root_sum

    return grow
