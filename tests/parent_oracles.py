"""Test-only oracles: the code of the parent of PR 32, kept to hold its
rewrites to the same values.

* ``_greedy_find_bin`` / ``_distinct_with_zero``: the bin finder as a walk
  over the distinct values in the interpreter (the port of the reference's
  ``GreedyFindBin``), against which ``io/binning.py`` finds each boundary
  by bisection.
* ``find_best_split``: the split scan on channel-minor ``(.., 3)`` arrays
  with the winner's sums read by a gather, against which ``ops/split.py``
  scans channel planes and reads the winner by a masked maximum.

Copied as they stood (commit c506df1); everything they call that did not
change is the live module's.  Nothing outside ``tests/`` imports this.
"""

import math
from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from lightgbmv1_tpu.io.binning import (MISSING_NAN, MISSING_NONE,
                                       MISSING_ZERO, _eq_ordered,
                                       _upper_bound_1ulp)
from lightgbmv1_tpu.ops.split import (NEG_INF, NO_CONSTRAINT, SplitResult,
                                      _any_categorical, _any_monotone,
                                      _cat_split_gain, _feature_uniform,
                                      _pack_bitset, gain_shift, leaf_gain,
                                      leaf_gain_given_output, leaf_output,
                                      monotone_penalty_factor,
                                      scan_pick_feature, smooth_output,
                                      tie_tol)

def _greedy_find_bin(
    distinct_values: np.ndarray,
    counts: np.ndarray,
    max_bin: int,
    total_cnt: int,
    min_data_in_bin: int,
) -> List[float]:
    """Greedy equal-count boundary search — exact behavioral port of
    GreedyFindBin (reference src/io/bin.cpp:78-156), including the
    adaptive mean-bin-size recomputation, the big-count-value lookahead,
    and the one-ulp boundary dedupe, so bin boundaries agree with the
    reference bit-for-bit on the same sample."""
    bounds: List[float] = []
    nd = len(distinct_values)
    if nd == 0:
        return [math.inf]
    if nd <= max_bin:
        cur = 0
        for i in range(nd - 1):
            cur += int(counts[i])
            if cur >= min_data_in_bin:
                val = _upper_bound_1ulp(
                    (distinct_values[i] + distinct_values[i + 1]) / 2.0)
                if not bounds or not _eq_ordered(bounds[-1], val):
                    bounds.append(val)
                    cur = 0
        bounds.append(math.inf)
        return bounds

    if min_data_in_bin > 0:
        max_bin = max(1, min(max_bin, int(total_cnt) // min_data_in_bin))
    mean_bin_size = total_cnt / max_bin

    rest_bin_cnt = max_bin
    rest_sample_cnt = int(total_cnt)
    is_big = np.asarray(counts, np.int64) >= mean_bin_size
    rest_bin_cnt -= int(is_big.sum())
    rest_sample_cnt -= int(counts[is_big].sum())

    def _mean(cnt, bins):
        if bins != 0:
            return cnt / bins
        return math.inf if cnt > 0 else math.nan

    mean_bin_size = _mean(rest_sample_cnt, rest_bin_cnt)
    upper = [math.inf] * max_bin
    lower = [math.inf] * max_bin
    bin_cnt = 0
    lower[0] = float(distinct_values[0])
    cur = 0
    for i in range(nd - 1):
        if not is_big[i]:
            rest_sample_cnt -= int(counts[i])
        cur += int(counts[i])
        if (is_big[i] or cur >= mean_bin_size
                or (is_big[i + 1] and cur >= max(1.0, mean_bin_size * 0.5))):
            upper[bin_cnt] = float(distinct_values[i])
            bin_cnt += 1
            lower[bin_cnt] = float(distinct_values[i + 1])
            if bin_cnt >= max_bin - 1:
                break
            cur = 0
            if not is_big[i]:
                rest_bin_cnt -= 1
                mean_bin_size = _mean(rest_sample_cnt, rest_bin_cnt)
    bin_cnt += 1
    for i in range(bin_cnt - 1):
        val = _upper_bound_1ulp((upper[i] + lower[i + 1]) / 2.0)
        if not bounds or not _eq_ordered(bounds[-1], val):
            bounds.append(val)
    bounds.append(math.inf)
    return bounds


def _distinct_with_zero(values_sorted: np.ndarray, zero_cnt: int):
    """Distinct values + counts from a SORTED non-NaN sample — behavioral
    port of the reference's construction (src/io/bin.cpp:352-390):
    neighbouring values within one ulp merge (keeping the larger value),
    and the implicit-zero count is spliced in where zero sorts (front /
    between the sign change / back)."""
    n = len(values_sorted)
    if n == 0:
        return np.array([0.0]), np.array([zero_cnt], np.int64)
    v = values_sorted
    # group boundaries: value i starts a new group when NOT within one ulp
    # of value i-1 (CheckDoubleEqualOrdered on consecutive sample values)
    new_grp = np.empty(n, bool)
    new_grp[0] = True
    new_grp[1:] = v[1:] > np.nextafter(v[:-1], np.inf)
    gid = np.cumsum(new_grp) - 1
    counts = np.bincount(gid).astype(np.int64)
    ends = np.cumsum(counts) - 1
    distinct = v[ends]                 # reference keeps the LARGE value
    starts = ends - counts + 1

    out_v: List[float] = []
    out_c: List[int] = []
    if v[0] > 0.0 and zero_cnt > 0:
        out_v.append(0.0)
        out_c.append(zero_cnt)
    for g in range(len(distinct)):
        if g > 0 and v[starts[g] - 1] < 0.0 and v[starts[g]] > 0.0:
            # sign change between consecutive sample values: splice zero
            # (reference pushes it with zero_cnt even when that is 0)
            out_v.append(0.0)
            out_c.append(zero_cnt)
        out_v.append(float(distinct[g]))
        out_c.append(int(counts[g]))
    if v[-1] < 0.0 and zero_cnt > 0:
        out_v.append(0.0)
        out_c.append(zero_cnt)
    return np.asarray(out_v, np.float64), np.asarray(out_c, np.int64)


def _best_categorical(hist, parent_sum, meta, feature_mask, params,
                      shift=0.0, constraint=None, parent_output=0.0,
                      rand_key=None, cegb_penalty=None):
    """Best categorical split across all features of one leaf.

    reference: FindBestThresholdCategoricalInner,
    src/treelearner/feature_histogram.hpp:278-460 — one-vs-rest for features
    with few categories (max_cat_to_onehot), otherwise a two-direction scan
    over bins sorted by grad/(hess+cat_smooth) with cat_l2 regularization and
    min_data_per_group batching.  Returned gains are RELATIVE (minus
    ``shift`` = parent gain + min_gain_to_split) with the per-feature
    ``meta.contri`` penalty applied, matching ``output->gain`` after
    FindBestThreshold (feature_histogram.hpp:94).

    Deviation from the reference: the trailing "other/unseen/NaN" bin of a
    categorical feature is never placed in the left (in-set) side, so the
    bin-space decision used in training is always exactly expressible as a
    raw-category bitset in the v3 model format (unseen categories at
    prediction time go right, like the reference's FindInBitset miss).
    """
    F, B, _ = hist.shape
    eps = 1e-15
    use_mc = constraint is not None
    use_smooth = params.path_smooth > 0
    if constraint is None:
        constraint = jnp.asarray(NO_CONSTRAINT, jnp.float32)
    g, h, c = hist[..., 0], hist[..., 1], hist[..., 2]
    total_g, total_h, total_c = parent_sum[0], parent_sum[1], parent_sum[2]
    t_idx = lax.broadcasted_iota(jnp.int32, (F, B), 1)
    nb = meta.num_bins[:, None]
    fmask = (feature_mask & meta.usable & meta.is_categorical)[:, None]
    # exclude the trailing other/unseen bin from left-set membership
    bin_ok = (t_idx < nb - 1) & fmask
    use_onehot = (nb <= params.max_cat_to_onehot)
    use_rand = params.extra_trees and rand_key is not None
    if use_rand:
        ku = _feature_uniform(jax.random.fold_in(rand_key, 7), meta, (2,))

    # ---- one-vs-rest (reference :316-369) --------------------------------
    oth_g, oth_h, oth_c = total_g - g, total_h - h, total_c - c
    ok1 = (
        bin_ok & use_onehot
        & (c >= params.min_data_in_leaf)
        & (h >= params.min_sum_hessian_in_leaf)
        & (oth_c >= params.min_data_in_leaf)
        & (oth_h - eps >= params.min_sum_hessian_in_leaf)
    )
    if use_rand:
        # USE_RAND (reference :316-318,344-348): only one random bin per
        # feature is evaluated
        rb1 = (ku[0] * jnp.maximum(meta.num_bins - 1, 1)
               ).astype(jnp.int32)[:, None]
        ok1 = ok1 & (t_idx == rb1)
    gain1 = _cat_split_gain(g, h + eps, oth_g, oth_h - eps, c, oth_c,
                            params, constraint, parent_output,
                            use_mc, use_smooth) - shift
    if meta.contri is not None:
        gain1 = gain1 * meta.contri[:, None]
    if cegb_penalty is not None:
        gain1 = gain1 - cegb_penalty[:, None]
    gain1 = jnp.where(ok1, gain1, NEG_INF)

    # ---- sorted two-direction scan (reference :371-470) ------------------
    l2cat = params._replace(lambda_l2=params.lambda_l2 + params.cat_l2)
    valid = bin_ok & (~use_onehot) & (c >= params.cat_smooth)
    ratio = jnp.where(valid, g / (h + params.cat_smooth), jnp.inf)
    order = jnp.argsort(ratio, axis=1)                 # (F, B) valid first
    used_bin = valid.sum(axis=1)                       # (F,)
    sg = jnp.take_along_axis(g, order, axis=1)
    sh = jnp.take_along_axis(h, order, axis=1)
    sc = jnp.take_along_axis(c, order, axis=1)
    # backward direction: positions used_bin-1, used_bin-2, ...
    bwd_idx = jnp.clip(used_bin[:, None] - 1 - t_idx, 0, B - 1)
    sg2 = jnp.stack([sg, jnp.take_along_axis(sg, bwd_idx, axis=1)])  # (2,F,B)
    sh2 = jnp.stack([sh, jnp.take_along_axis(sh, bwd_idx, axis=1)])
    sc2 = jnp.stack([sc, jnp.take_along_axis(sc, bwd_idx, axis=1)])
    clg = jnp.cumsum(sg2, axis=2)
    clh = jnp.cumsum(sh2, axis=2) + eps
    clc = jnp.cumsum(sc2, axis=2)
    crg, crh, crc = total_g - clg, total_h - clh, total_c - clc

    max_num_cat = jnp.minimum(params.max_cat_threshold, (used_bin + 1) // 2)
    pos_ok = (
        (t_idx[None] < max_num_cat[None, :, None])
        & (t_idx[None] < used_bin[None, :, None])
        & (clc >= params.min_data_in_leaf)
        & (clh >= params.min_sum_hessian_in_leaf)
        & (crc >= params.min_data_in_leaf)
        & (crc >= params.min_data_per_group)
        & (crh >= params.min_sum_hessian_in_leaf)
    )
    if use_rand:
        # USE_RAND (reference :400-404,448-451): one random prefix position,
        # shared by both scan directions; NextInt(0, max_threshold) is
        # half-open, so positions are drawn from [0, max_threshold)
        max_thr = jnp.maximum(jnp.minimum(max_num_cat, used_bin) - 1, 0)
        rp = (ku[1] * jnp.maximum(max_thr, 1)).astype(jnp.int32)
        pos_ok = pos_ok & (t_idx[None] == rp[None, :, None])

    # min_data_per_group batching: evaluate a prefix only when >= mdpg rows
    # accumulated since the previous evaluated prefix (reference
    # cnt_cur_group) — the single sequential piece, scanned over positions.
    n_steps = min(B, int(params.max_cat_threshold))

    def grp_step(grp, i):
        grp = grp + sc2[:, :, i]
        can = pos_ok[:, :, i] & (grp >= params.min_data_per_group)
        return jnp.where(can, 0.0, grp), can

    _, can_eval = lax.scan(grp_step, jnp.zeros((2, F)), jnp.arange(n_steps))
    can_eval = jnp.moveaxis(can_eval, 0, 2)            # (2, F, n_steps)
    can_eval = jnp.pad(can_eval, ((0, 0), (0, 0), (0, B - n_steps)))

    gain2 = _cat_split_gain(clg, clh, crg, crh, clc, crc, l2cat,
                            constraint, parent_output,
                            use_mc, use_smooth) - shift
    if meta.contri is not None:
        gain2 = gain2 * meta.contri[None, :, None]
    if cegb_penalty is not None:
        gain2 = gain2 - cegb_penalty[None, :, None]
    gain2 = jnp.where(can_eval, gain2, NEG_INF)        # (2, F, B)

    # ---- pick the best categorical candidate -----------------------------
    flat = jnp.concatenate([gain1.reshape(-1), gain2.reshape(-1)])
    best = jnp.argmax(flat)
    best_gain = flat[best]
    from_onehot = best < F * B
    idx2 = jnp.maximum(best - F * B, 0)
    direction = (idx2 // (F * B)).astype(jnp.int32)    # 0 fwd, 1 bwd
    feat = jnp.where(from_onehot, (best // B) % F, (idx2 // B) % F).astype(jnp.int32)
    pos = jnp.where(from_onehot, best % B, idx2 % B).astype(jnp.int32)

    left1 = hist[feat, pos] + jnp.array([0.0, eps, 0.0])
    left2 = jnp.stack([clg[direction, feat, pos],
                       clh[direction, feat, pos],
                       clc[direction, feat, pos]])
    left = jnp.where(from_onehot, left1, left2)

    # membership: one-hot -> the single bin; sorted -> prefix of the order
    pos_iota = t_idx[0]                                # (B,)
    ub = used_bin[feat]
    member_pos = jnp.where(direction == 0,
                           pos_iota <= pos,
                           (pos_iota >= ub - 1 - pos) & (pos_iota < ub))
    member_sorted = jnp.zeros(B, bool).at[order[feat]].set(member_pos)
    member_bins = jnp.where(from_onehot, pos_iota == pos, member_sorted)
    bitset = _pack_bitset(member_bins, B)

    return best_gain, feat, left, bitset


def scan_left_sums(hist, meta, hist_scale=None):
    """Phase 1 of the fused split scan: ONE cumulative-sum pass over the
    bin axis plus the missing-mass adjustments, both scan directions
    stacked into a single ``(2, F, B, 3)`` tensor (direction 0 =
    missing/default right, direction 1 = missing joins the left side).

    Dequantize-aware (stochastic-rounded int8 histograms,
    ops/quantize.py): ``hist`` holds exact integer counts and
    ``hist_scale`` the per-channel dequant multipliers.  The cumsum runs
    in the INTEGER domain — exact, no f32 summation-order noise — and
    ONE broadcast multiply dequantizes the prefix sums; the same scale
    lands on the nan/zero missing-mass rows below.  The histogram is
    consumed straight from HBM in quantized form: no separate
    dequantization pass ever writes a real-valued copy back.

    Returns ``(left2, hist)`` where ``hist`` is the (dequantized) input
    for the point reads the categorical search and the missing-direction
    bookkeeping still need.  Module-level so tools/phase_attrib.py can
    time exactly this sub-phase of the scan the grower runs."""
    F, B, _ = hist.shape
    cum = jnp.cumsum(hist, axis=1)                    # (F, B, 3) inclusive
    if hist_scale is not None:
        cum = cum * hist_scale[None, None, :]
        hist = hist * hist_scale[None, None, :]       # point reads below
    t_idx = lax.broadcasted_iota(jnp.int32, (F, B), 1)

    nan_contrib = jnp.take_along_axis(
        hist,
        jnp.maximum(meta.nan_bin, 0)[:, None, None].repeat(3, axis=2),
        axis=1,
    )[:, 0, :]                                        # (F, 3)
    is_nan_f = (meta.missing_type == MISSING_NAN)[:, None]     # (F, 1)
    is_zero_f = (meta.missing_type == MISSING_ZERO)[:, None]   # (F, 1)

    # MISSING_ZERO: the reference's two scans SKIP the default (zero) bin
    # while accumulating (FindBestThresholdSequentially SKIP_DEFAULT_BIN,
    # feature_histogram.hpp:879-882,968-971), so the zero-bin mass rides
    # with the missing direction — left in the reverse scan, right in the
    # forward scan — INDEPENDENT of where the threshold falls relative to
    # the zero bin.
    zero_contrib = jnp.take_along_axis(
        hist, meta.zero_bin[:, None, None].repeat(3, axis=2),
        axis=1)[:, 0, :]                              # (F, 3)
    zb = meta.zero_bin[:, None]                       # (F, 1)

    # direction 0: missing/default right (forward scan)
    left_a = cum - jnp.where(
        (is_zero_f & (t_idx >= zb))[..., None], zero_contrib[:, None, :], 0.0)
    # direction 1: missing joins the left side (reverse scan equivalent)
    left_b = cum + jnp.where(
        is_nan_f[..., None], nan_contrib[:, None, :],
        jnp.where((is_zero_f & (t_idx < zb))[..., None],
                  zero_contrib[:, None, :], 0.0))
    return jnp.stack([left_a, left_b]), hist          # (2, F, B, 3)


def scan_direction_gains(left2, parent_sum, meta, feature_mask, params,
                         constraint=None, depth=0, monotone_penalty=0.0,
                         parent_output=0.0, rand_key=None,
                         cegb_penalty=None):
    """Phase 2 of the fused split scan: gains of every (direction,
    feature, bin) candidate in ONE stacked evaluation over the
    ``(2, F, B, 3)`` left sums from :func:`scan_left_sums` — the gain
    math (leaf_gain / smoothing / monotone clamps) is traced once on the
    doubled tensor instead of once per direction, so the whole
    cumsum → gain chain lowers as a single fused pass.

    Returns ``(gains (2, F, B), shift)`` with gains RELATIVE (shift =
    parent gain + min_gain_to_split already subtracted) and every
    penalty applied.  Module-level for tools/phase_attrib.py."""
    _, F, B, _ = left2.shape
    total_g, total_h, total_c = parent_sum[0], parent_sum[1], parent_sum[2]
    use_mc = _any_monotone(meta)
    use_smooth = params.path_smooth > 0
    if constraint is None:
        constraint = jnp.asarray(NO_CONSTRAINT, jnp.float32)
    t_idx = lax.broadcasted_iota(jnp.int32, (F, B), 1)
    nb = meta.num_bins[:, None]                       # (F, 1)
    is_nan_f = (meta.missing_type == MISSING_NAN)[:, None]     # (F, 1)
    is_zero_f = (meta.missing_type == MISSING_ZERO)[:, None]   # (F, 1)
    has_miss_dir = is_nan_f | is_zero_f

    def eval_direction(left):
        lg, lh, lc = left[..., 0], left[..., 1], left[..., 2]
        rg, rh, rc = total_g - lg, total_h - lh, total_c - lc
        ok = (
            (lc >= params.min_data_in_leaf)
            & (rc >= params.min_data_in_leaf)
            & (lh >= params.min_sum_hessian_in_leaf)
            & (rh >= params.min_sum_hessian_in_leaf)
        )
        if not use_mc and not use_smooth:
            gain = leaf_gain(lg, lh, params) + leaf_gain(rg, rh, params)
            return jnp.where(ok, gain, NEG_INF)
        # constrained/smoothed mode (reference: GetSplitGains with USE_MC /
        # USE_SMOOTHING, feature_histogram.hpp:782-830): leaf outputs are
        # smoothed toward the parent's output and clamped to the leaf's
        # [min, max] bound; the gain is evaluated at those outputs, and a
        # split violating the feature's monotone direction is rejected.
        out_l = leaf_output(lg, lh, params)
        out_r = leaf_output(rg, rh, params)
        if use_smooth:
            out_l = smooth_output(out_l, lc, parent_output, params)
            out_r = smooth_output(out_r, rc, parent_output, params)
        if use_mc:
            out_l = jnp.clip(out_l, constraint[0], constraint[1])
            out_r = jnp.clip(out_r, constraint[0], constraint[1])
        gain = (leaf_gain_given_output(lg, lh, out_l, params)
                + leaf_gain_given_output(rg, rh, out_r, params))
        if use_mc:
            mono = meta.monotone_type[:, None]         # (F, 1)
            violates = ((mono > 0) & (out_l > out_r)) | (
                (mono < 0) & (out_l < out_r))
            ok = ok & (~violates)
        return jnp.where(ok, gain, NEG_INF)

    numerical_ok = feature_mask[:, None] & meta.usable[:, None] & (
        ~meta.is_categorical[:, None])
    base_valid = (t_idx <= nb - 2) & numerical_ok
    if params.extra_trees and rand_key is not None:
        # extremely-randomized trees (reference USE_RAND: one random
        # threshold per feature per node, feature_histogram.hpp:919-930)
        u = _feature_uniform(rand_key, meta)
        rand_bin = (u * jnp.maximum(meta.num_bins - 1, 1)).astype(jnp.int32)
        base_valid = base_valid & (t_idx == rand_bin[:, None])
    # both directions masked and evaluated in one shot: direction 1 only
    # exists for features with a missing direction
    valid2 = jnp.stack([base_valid, base_valid & has_miss_dir])
    gains2 = jnp.where(valid2, eval_direction(left2), NEG_INF)

    shift = gain_shift(parent_sum, parent_output, params)

    # Work in RELATIVE gains from here on — the reference's output->gain is
    # best_gain - min_gain_shift, and every penalty below operates on that
    # relative value (ComputeBestSplitForFeature,
    # serial_tree_learner.cpp:701-736):
    #   1. feature_contri multiply (inside FindBestThreshold,
    #      feature_histogram.hpp:94)
    #   2. CEGB DetlaGain subtract (serial_tree_learner.cpp:723-727)
    #   3. monotone depth-penalty multiply (:728-732)
    gains = gains2 - shift                            # (2, F, B)
    finite = jnp.isfinite(gains)
    if meta.contri is not None:
        gains = jnp.where(finite, gains * meta.contri[None, :, None], gains)
    if cegb_penalty is not None:
        gains = jnp.where(finite, gains - cegb_penalty[None, :, None], gains)
    if use_mc and monotone_penalty > 0:
        factor = monotone_penalty_factor(jnp.asarray(depth), monotone_penalty)
        mono_f = (meta.monotone_type != 0)[None, :, None]
        gains = jnp.where(finite & mono_f, gains * factor, gains)
    return gains, shift


def scan_pick(gains, shift, meta):
    """Phase 3 of the fused split scan: the tie-band preference argmax.

    Tie-breaking (matters when gains plateau, e.g. under max_delta_step
    clamping).  The reference evaluates the REVERSE scan first and the
    forward scan replaces only on strictly greater gain
    (FuncForNumricalL3, feature_histogram.hpp:157-215), and each scan
    keeps the FIRST candidate seen (`current_gain > best_gain`,
    :928,1002): reverse = highest threshold, forward = lowest.  For
    missing-none (or 2-bin) features only the reverse scan runs, so our
    direction-0 candidates inherit its highest-threshold preference.
    Cross-feature ties pick the smaller feature (SplitInfo::operator>,
    split_info.hpp:147-152) — argmax first-occurrence order below.

    Returns ``(best_gain, feature, threshold, direction)``.  Module-level
    for tools/phase_attrib.py."""
    _, F, B = gains.shape
    fbest, sel_f = scan_pick_feature(gains, shift, meta)
    gains_f = jnp.concatenate([gains[0], gains[1]], axis=1)   # (F, 2B)
    gbest = jnp.max(fbest)
    feature = jnp.argmax(fbest >= gbest - tie_tol(gbest, shift)) \
        .astype(jnp.int32)                   # first in band = min feature
    sel = sel_f[feature]
    best_gain = gains_f[feature, sel]
    direction = (sel // B).astype(jnp.int32)
    threshold = (sel % B).astype(jnp.int32)
    return best_gain, feature, threshold, direction


def find_best_split(
    hist, parent_sum, meta, feature_mask, params, constraint=None, depth=0,
    monotone_penalty=0.0, parent_output=0.0, rand_key=None, cegb_penalty=None,
    hist_scale=None,
) -> SplitResult:
    # One fused scan pass (round-7 split-phase burn-down): cumsum +
    # missing-mass adjust (scan_left_sums, dequantize fold included) →
    # stacked both-direction gain evaluation (scan_direction_gains) →
    # tie-band preference argmax (scan_pick).  The three stages are
    # module-level so the phase-attribution harness times the exact code
    # objects this search runs; candidate values are bit-identical to the
    # historical per-direction evaluation (same formulas, elementwise).
    F, B, _ = hist.shape
    use_mc = _any_monotone(meta)
    if constraint is None:
        constraint = jnp.asarray(NO_CONSTRAINT, jnp.float32)

    left2, hist = scan_left_sums(hist, meta, hist_scale)
    gains, shift = scan_direction_gains(
        left2, parent_sum, meta, feature_mask, params, constraint, depth,
        monotone_penalty, parent_output, rand_key, cegb_penalty)
    best_gain, feature, threshold, direction = scan_pick(gains, shift, meta)

    left = left2[direction, feature, threshold]

    # categorical candidates (compiled in only when the dataset has any —
    # meta arrays are trace-time constants via the grower closure)
    has_cat = _any_categorical(meta)
    W = -(-B // 32)
    if has_cat:
        cgain, cfeat, cleft, cbitset = _best_categorical(
            hist, parent_sum, meta, feature_mask, params,
            shift=shift, constraint=constraint if use_mc else None,
            parent_output=parent_output, rand_key=rand_key,
            cegb_penalty=cegb_penalty)
        use_cat = cgain > best_gain
        best_gain = jnp.maximum(best_gain, cgain)
        feature = jnp.where(use_cat, cfeat, feature)
        threshold = jnp.where(use_cat, 0, threshold)
        left = jnp.where(use_cat, cleft, left)
        is_cat = use_cat
        cat_bitset = jnp.where(use_cat, cbitset, jnp.zeros(W, jnp.uint32))
    else:
        is_cat = jnp.asarray(False)
        cat_bitset = jnp.zeros(W, jnp.uint32)

    right = parent_sum - left

    # default direction for missing values at prediction time: the side the
    # missing mass (NaN bin / zero bin) was accumulated on
    mtype = meta.missing_type[feature]
    default_left = jnp.where(
        (mtype == MISSING_NAN) | (mtype == MISSING_ZERO),
        direction == 1, False)
    default_left = default_left & (~is_cat)

    # best_gain is already relative (shift subtracted before the argmax)
    rel_gain = jnp.where(jnp.isfinite(best_gain), best_gain, NEG_INF)
    if meta.window is not None:
        # the winner by its id in the whole; no candidate names feature 0,
        # as the whole scan's argmax does (a padding id never leaves)
        feature = jnp.where(rel_gain > NEG_INF,
                            meta.window.columns[feature], 0)

    return SplitResult(
        gain=rel_gain.astype(jnp.float32),
        feature=feature,
        threshold_bin=threshold,
        default_left=default_left,
        left_sum=left.astype(jnp.float32),
        right_sum=right.astype(jnp.float32),
        is_cat=is_cat,
        cat_bitset=cat_bitset,
    )
