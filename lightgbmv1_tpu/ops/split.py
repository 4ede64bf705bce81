"""Best-split search over histograms.

TPU-native re-design of the reference split finding
(``FeatureHistogram::FindBestThresholdSequentially``,
src/treelearner/feature_histogram.hpp:855-1056, and the gain math
``GetSplitGains``/``CalculateSplittedLeafOutput``/``ThresholdL1``
feature_histogram.hpp:734-782).

The reference scans each feature's bins twice sequentially (forward scan =
missing defaults right; reverse scan = missing defaults left).  Here both
directions are expressed as cumulative sums over the bin axis and evaluated
for **all features, all bins, both directions at once** — a handful of
vectorized ops + one argmax, no sequential loop.  This runs per-leaf and is
vmapped over the tree frontier.

Differences from the reference:
* No most-freq-bin offset arithmetic — histograms store every bin densely
  (see ops/histogram.py), so the reference's ``FixHistogram``
  (src/io/dataset.cpp:1410) has no equivalent here.
* Counts are exact fp32 sums instead of the reference's
  ``RoundInt(sum_hess * cnt_factor)`` estimate (feature_histogram.hpp:885);
  min_data_in_leaf gating is therefore exact.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..io.binning import MISSING_NAN, MISSING_NONE, MISSING_ZERO

NEG_INF = -jnp.inf

# Near-tie tolerance of the split argmax (relative to the gain scale).
# Distributed histograms are f32 reductions whose summation ORDER differs
# between the serial sum, lax.psum and lax.psum_scatter; candidate gains
# therefore carry reduction-order noise of a few f32 ulps of the LEAF GAIN
# terms they are differences of (the shift/parent-gain magnitude — the
# final gain itself can be arbitrarily small through cancellation).
# Candidates within ``TIE_RTOL * (|shift| + |best|)`` of the best are
# treated as TIED and resolved by the deterministic preference order
# (reference scan-order within a feature, lowest feature id across
# features), which makes the chosen split invariant to reduction order and
# device count — the fix for the psum-summation-order near-tie threshold
# flips tests/test_parallel.py[data] exposed.  The band is ~30 f32 ulps:
# far below any gain gap the reference itself could distinguish, so the
# golden-parity fixtures are unaffected.
TIE_RTOL = 4e-6


def tie_tol(best_gain, scale):
    """Absolute gain tolerance under which two split candidates count as
    tied.  ``scale`` is the leaf-gain magnitude the candidate gains were
    differenced against (the parent-gain shift); ``best_gain`` may be
    -inf (no candidate), which contributes nothing."""
    b = jnp.where(jnp.isfinite(best_gain), jnp.abs(best_gain), 0.0)
    return TIE_RTOL * (jnp.abs(scale) + b)


def go_left_rule(bins, thr, dl, mt, nan_bin, zero_bin):
    """The committed numerical split's go-left decision on raw bin ids —
    bin compare plus the NaN/zero missing-direction rules (reference
    ``NumericalBin::data + missing-type dispatch``, dense_bin.hpp:85-140).

    All inputs broadcast (``bins`` is int32 bin ids, the rest per-split
    scalars or column vectors; ``dl`` bool, ``mt``/``nan_bin``/
    ``zero_bin`` int32).  Pure integer/bool ops — exact everywhere, so
    the (S, N) partition pass (models/grower_wave.py ``go_left_s``) and
    the deferred valid-routing drain (``route_pending``) evaluate the
    SAME code object: the decision cannot drift between them.
    Categorical bitset membership stays with the callers.

    ``where(na, dl, bins <= thr)`` written as and / or / not: the row-tiled
    partition kernel (ops/partition_pallas.py) evaluates this same code
    object inside its body, and Mosaic has no select between two vectors
    of booleans."""
    na = ((mt == MISSING_NAN) & (bins == nan_bin)) | (
        (mt == MISSING_ZERO) & (bins == zero_bin))
    return (na & dl) | (~na & (bins <= thr))


class SplitParams(NamedTuple):
    """Static-ish regularization parameters (traced scalars are fine too)."""

    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: float = 20.0
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    max_delta_step: float = 0.0
    # categorical split parameters (reference config.h / feature_histogram.hpp)
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_cat_threshold: int = 32
    max_cat_to_onehot: int = 4
    min_data_per_group: float = 100.0
    # path smoothing (reference CalculateSplittedLeafOutput USE_SMOOTHING,
    # feature_histogram.hpp:756-760) and extremely-randomized trees
    path_smooth: float = 0.0
    extra_trees: bool = False
    extra_seed: int = 0       # offsets the extra_trees threshold stream
                              # (reference config.h extra_seed)
    # cost-effective gradient boosting (reference
    # cost_effective_gradient_boosting.hpp:22 DetlaGain)
    cegb_tradeoff: float = 1.0
    cegb_penalty_split: float = 0.0


class SplitResult(NamedTuple):
    gain: jax.Array          # relative gain (already minus parent gain and
                             # min_gain_to_split); <= 0 means "don't split"
    feature: jax.Array       # int32
    threshold_bin: jax.Array  # int32 — rows with bin <= threshold_bin go left
    default_left: jax.Array  # bool — missing-value direction
    left_sum: jax.Array      # (3,) [grad, hess, count]
    right_sum: jax.Array     # (3,)
    is_cat: jax.Array        # bool — categorical (bitset) split
    cat_bitset: jax.Array    # (W,) uint32 — bin-space membership bitset
                             # (W = ceil(num_bins/32)); bins in the set go left


def threshold_l1(s: jax.Array, l1: float) -> jax.Array:
    """reference: ThresholdL1, feature_histogram.hpp:734."""
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def leaf_gain(g: jax.Array, h: jax.Array, p: SplitParams) -> jax.Array:
    """reference: GetLeafGain, feature_histogram.hpp:823-839.

    With ``max_delta_step > 0`` (USE_MAX_OUTPUT) the reference evaluates the
    gain AT the clamped output via GetLeafGainGivenOutput instead of the
    closed form — the closed form would overstate the gain of leaves whose
    unconstrained optimum exceeds the clamp (feature_histogram.hpp:833-838).
    The smoothing counterpart lives in the callers (smooth_output needs the
    leaf count, which this signature doesn't carry)."""
    if isinstance(p.max_delta_step, (int, float)) and p.max_delta_step <= 0:
        t = threshold_l1(g, p.lambda_l1)
        return (t * t) / (h + p.lambda_l2)
    return leaf_gain_given_output(g, h, leaf_output(g, h, p), p)


def leaf_output(g: jax.Array, h: jax.Array, p: SplitParams) -> jax.Array:
    """reference: CalculateSplittedLeafOutput, feature_histogram.hpp:740-778."""
    out = -threshold_l1(g, p.lambda_l1) / (h + p.lambda_l2)
    if isinstance(p.max_delta_step, (int, float)) and p.max_delta_step <= 0:
        return out
    return jnp.where(
        jnp.asarray(p.max_delta_step) > 0,
        jnp.clip(out, -p.max_delta_step, p.max_delta_step),
        out,
    )


class FeatureMeta(NamedTuple):
    """Per-feature binning metadata consumed by the split finder; built once
    per dataset from the BinMappers (host) and shipped to device."""

    num_bins: jax.Array       # (F,) int32
    missing_type: jax.Array   # (F,) int32
    nan_bin: jax.Array        # (F,) int32 (-1 if none)
    zero_bin: jax.Array       # (F,) int32
    is_categorical: jax.Array  # (F,) bool
    usable: jax.Array         # (F,) bool — not trivial
    monotone_type: jax.Array  # (F,) int32 — -1 / 0 / +1 constraint direction
    contri: Optional[jax.Array] = None  # (F,) f32 feature_contri gain
                              # multipliers (reference FeatureMetainfo::penalty,
                              # feature_histogram.hpp:32,94,1139) or None
    window: Optional["FeatureWindow"] = None  # set by narrow_meta only


class FeatureWindow(NamedTuple):
    """Marks a :class:`FeatureMeta` as some columns of a wider one (the
    features a chip owns after the data-parallel reduce-scatter).  Its
    arrays are traced gathers, so what the scan decides at trace time and
    what it draws per GLOBAL feature come from the whole, kept here."""

    columns: jax.Array        # (F,) int32 ids in the whole; past its end:
                              # padding
    whole: FeatureMeta


def _whole(meta: FeatureMeta) -> FeatureMeta:
    return meta if meta.window is None else meta.window.whole


def _any_monotone(meta: FeatureMeta) -> bool:
    return bool(np.asarray(_whole(meta).monotone_type).any())


def _any_categorical(meta: FeatureMeta) -> bool:
    return bool(np.asarray(_whole(meta).is_categorical).any())


def take_columns(x: jax.Array, columns: jax.Array, fill) -> jax.Array:
    """``x[..., columns]``, ``fill`` where an id lies past the end."""
    return jnp.take(x, columns, axis=-1, mode="fill", fill_value=fill)


def narrow_meta(meta: FeatureMeta, columns: jax.Array) -> FeatureMeta:
    """``meta`` of the features ``columns`` (traced ids; past the end =
    padding: unusable, one bin).  ``find_best_split`` on a histogram of
    those columns ranks the candidates the whole scan ranks for them and
    names its winner by its id in the whole."""
    take = functools.partial(take_columns, columns=columns)
    return FeatureMeta(
        num_bins=take(meta.num_bins, fill=1),
        missing_type=take(meta.missing_type, fill=MISSING_NONE),
        nan_bin=take(meta.nan_bin, fill=-1),
        zero_bin=take(meta.zero_bin, fill=0),
        is_categorical=take(meta.is_categorical, fill=False),
        usable=take(meta.usable, fill=False),
        monotone_type=take(meta.monotone_type, fill=0),
        contri=(None if meta.contri is None
                else take(meta.contri, fill=1.0)),
        window=FeatureWindow(columns, meta))


def _feature_uniform(key, meta: FeatureMeta, lead=()) -> jax.Array:
    """``lead + (F,)`` uniforms, one per GLOBAL feature: a window cuts its
    columns out of the whole problem's draw, so a node's random
    thresholds do not depend on how the features are spread over chips."""
    u = jax.random.uniform(key, lead + (_whole(meta).num_bins.shape[0],))
    if meta.window is None:
        return u
    return take_columns(u, meta.window.columns, 0.0)


def make_feature_meta(dataset, monotone_constraints=None,
                      feature_contri=None) -> FeatureMeta:
    F = len(dataset.num_bins)
    mono = np.zeros(F, np.int32)
    if monotone_constraints:
        mc = np.asarray(list(monotone_constraints), np.int32)
        mono[: min(F, len(mc))] = mc[:F]
    contri = None
    if feature_contri:
        contri = np.ones(F, np.float32)
        fc = np.asarray(list(feature_contri), np.float32)
        contri[: min(F, len(fc))] = fc[:F]
        contri = jnp.asarray(contri)
    return FeatureMeta(
        num_bins=jnp.asarray(dataset.num_bins, jnp.int32),
        missing_type=jnp.asarray(dataset.missing_types, jnp.int32),
        nan_bin=jnp.asarray(dataset.nan_bins, jnp.int32),
        zero_bin=jnp.asarray(dataset.zero_bins, jnp.int32),
        is_categorical=jnp.asarray(dataset.is_categorical),
        usable=jnp.asarray(~dataset.is_trivial),
        monotone_type=jnp.asarray(mono),
        contri=contri,
    )


NO_CONSTRAINT = (-3.0e38, 3.0e38)   # f32-max-ish; reference uses double max


def leaf_gain_given_output(g, h, out, p: SplitParams):
    """reference: GetLeafGainGivenOutput, feature_histogram.hpp — the gain
    of a leaf forced to emit ``out`` (equals leaf_gain at the unconstrained
    optimum)."""
    t = threshold_l1(g, p.lambda_l1)
    return -(2.0 * t * out + (h + p.lambda_l2) * out * out)


def smooth_output(raw_out, count, parent_output, p: SplitParams):
    """Path smoothing (reference feature_histogram.hpp:756-760):
    ``out*(n/a)/(n/a+1) + parent/(n/a+1)`` with a = path_smooth."""
    w = count / p.path_smooth
    return raw_out * w / (w + 1.0) + parent_output / (w + 1.0)


def monotone_penalty_factor(depth, penalization):
    """reference: ComputeMonotoneSplitGainPenalty,
    monotone_constraints.hpp:66-76."""
    eps = 1e-10
    d = depth.astype(jnp.float32) if hasattr(depth, "astype") else float(depth)
    small = 1.0 - penalization / (2.0 ** d) + eps
    large = 1.0 - 2.0 ** (penalization - 1.0 - d) + eps
    out = jnp.where(penalization <= 1.0, small, large)
    return jnp.where(penalization >= d + 1.0, eps, out)


def _pack_bitset(member: jax.Array, num_bins: int) -> jax.Array:
    """(B,) bool membership -> (ceil(B/32),) uint32 bitset words."""
    W = -(-num_bins // 32)
    pad = W * 32 - num_bins
    m = jnp.pad(member.astype(jnp.uint32), (0, pad)).reshape(W, 32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return (m << shifts).sum(axis=1).astype(jnp.uint32)


def bitset_contains(bitset: jax.Array, bins: jax.Array) -> jax.Array:
    """Vectorized FindInBitset (reference include/LightGBM/utils/common.h):
    bitset (..., W) uint32, bins (...,) int — True where bit is set."""
    b = bins.astype(jnp.int32)
    word = jnp.take_along_axis(
        bitset, (b[..., None] >> 5).astype(jnp.int32), axis=-1)[..., 0]
    return ((word >> (b.astype(jnp.uint32) & 31)) & 1) == 1


def _cat_split_gain(lg, lh, rg, rh, lc, rc, p, constraint, parent_output,
                    use_mc, use_smooth):
    """GetSplitGains<USE_MC, USE_SMOOTHING> for categorical candidates
    (reference feature_histogram.hpp:350-355,450-456): leaf outputs smoothed
    toward the parent and clamped to the leaf's [min, max] bound; no monotone
    direction check — categorical features cannot carry monotone constraints
    (dataset_loader.cpp:569 fatals on that combination)."""
    if not use_mc and not use_smooth:
        return leaf_gain(lg, lh, p) + leaf_gain(rg, rh, p)
    out_l = leaf_output(lg, lh, p)
    out_r = leaf_output(rg, rh, p)
    if use_smooth:
        out_l = smooth_output(out_l, lc, parent_output, p)
        out_r = smooth_output(out_r, rc, parent_output, p)
    if use_mc:
        out_l = jnp.clip(out_l, constraint[0], constraint[1])
        out_r = jnp.clip(out_r, constraint[0], constraint[1])
    return (leaf_gain_given_output(lg, lh, out_l, p)
            + leaf_gain_given_output(rg, rh, out_r, p))


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
    _, F, B = hist.shape                              # channel planes
    eps = 1e-15
    use_mc = constraint is not None
    use_smooth = params.path_smooth > 0
    if constraint is None:
        constraint = jnp.asarray(NO_CONSTRAINT, jnp.float32)
    g, h, c = hist[0], hist[1], hist[2]
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

    left1 = hist[:, feat, pos] + jnp.array([0.0, eps, 0.0])
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


def _no_cat_result(num_bins: int):
    W = -(-num_bins // 32)
    return jnp.zeros(W, jnp.uint32)


def find_best_split(
    hist: jax.Array,          # (F, B, 3) — [sum_grad, sum_hess, count]
    parent_sum: jax.Array,    # (3,)
    meta: FeatureMeta,
    feature_mask: jax.Array,  # (F,) bool — col-sampled usable features
    params: SplitParams,
    constraint: Optional[jax.Array] = None,  # (2,) [min, max] leaf output bound
    depth=0,                  # leaf depth (monotone_penalty)
    monotone_penalty: float = 0.0,
    parent_output=0.0,        # this leaf's current output (path smoothing)
    rand_key: Optional[jax.Array] = None,    # extra_trees threshold sampling
    cegb_penalty: Optional[jax.Array] = None,  # (F,) CEGB gain penalty
    hist_scale: Optional[jax.Array] = None,  # (3,) dequant multipliers when
                              # ``hist`` carries QUANTIZED integer counts
) -> SplitResult:
    with jax.named_scope("lgbm.split"):
        return _find_best_split(hist, parent_sum, meta, feature_mask, params,
                                constraint, depth, monotone_penalty,
                                parent_output, rand_key, cegb_penalty,
                                hist_scale)


def _count_scan_columns(scanned: int, block: int) -> None:
    """Trace time: ``split_scan_columns{what}`` holds the histogram columns
    one scan covers and the columns one scanned block holds, from the
    static shapes."""
    from ..obs.metrics import default_registry

    gauge = default_registry().gauge(
        "split_scan_columns",
        "Histogram columns a split scan covers, and columns of one "
        "scanned block", label_names=("what",))
    gauge.labels(what="scanned").set(float(scanned))
    gauge.labels(what="block").set(float(block))


def scan_left_sums(hist, meta, hist_scale=None):
    """Phase 1 of the fused split scan: ONE cumulative-sum pass over the
    bin axis plus the missing-mass adjustments, both scan directions
    stacked into a single channel-major ``(3, 2, F, B)`` tensor (plane 0 /
    1 / 2 = grad / hess / count; direction 0 = missing/default right,
    direction 1 = missing joins the left side).

    Channel-major on purpose: no array of the scan has the 3 channels as
    its minor dimension, which a TPU tiles to 128 lanes (42.7x the bytes;
    at 2,000 columns x 126 children one such array alone passes the
    chip's memory).  The values are those of the channel-minor scan,
    element for element.

    Dequantize-aware (stochastic-rounded int8 histograms,
    ops/quantize.py): ``hist`` holds exact integer counts and
    ``hist_scale`` the per-channel dequant multipliers.  The cumsum runs
    in the INTEGER domain — exact, no f32 summation-order noise — and
    ONE broadcast multiply dequantizes the prefix sums; the same scale
    lands on the nan/zero missing-mass rows below.  The histogram is
    consumed straight from HBM in quantized form: no separate
    dequantization pass ever writes a real-valued copy back.

    Returns ``(left2, planes)`` where ``planes`` is the (dequantized)
    input as ``(3, F, B)`` for the point reads the categorical search
    still needs.  Module-level so tools/phase_attrib.py can time exactly
    this sub-phase of the scan the grower runs."""
    F, B, _ = hist.shape
    planes = jnp.moveaxis(hist, 2, 0)                 # (3, F, B)
    cum = jnp.cumsum(planes, axis=2)                  # inclusive
    if hist_scale is not None:
        cum = cum * hist_scale[:, None, None]
        planes = planes * hist_scale[:, None, None]   # point reads below
    t_idx = lax.broadcasted_iota(jnp.int32, (F, B), 1)

    def bin_of_each_feature(b):                       # (F,) -> (3, F, 1)
        return jnp.take_along_axis(
            planes, jnp.broadcast_to(b[None, :, None], (3, F, 1)), axis=2)

    nan_contrib = bin_of_each_feature(jnp.maximum(meta.nan_bin, 0))
    is_nan_f = (meta.missing_type == MISSING_NAN)[:, None]     # (F, 1)
    is_zero_f = (meta.missing_type == MISSING_ZERO)[:, None]   # (F, 1)

    # MISSING_ZERO: the reference's two scans SKIP the default (zero) bin
    # while accumulating (FindBestThresholdSequentially SKIP_DEFAULT_BIN,
    # feature_histogram.hpp:879-882,968-971), so the zero-bin mass rides
    # with the missing direction — left in the reverse scan, right in the
    # forward scan — INDEPENDENT of where the threshold falls relative to
    # the zero bin.
    zero_contrib = bin_of_each_feature(meta.zero_bin)
    zb = meta.zero_bin[:, None]                       # (F, 1)

    # direction 0: missing/default right (forward scan)
    left_a = cum - jnp.where(is_zero_f & (t_idx >= zb), zero_contrib, 0.0)
    # direction 1: missing joins the left side (reverse scan equivalent)
    left_b = cum + jnp.where(
        is_nan_f, nan_contrib,
        jnp.where(is_zero_f & (t_idx < zb), zero_contrib, 0.0))
    return jnp.stack([left_a, left_b], axis=1), planes   # (3, 2, F, B)


def gain_shift(parent_sum, parent_output, params):
    """The gain baseline every candidate is differenced against: parent
    gain (at the smoothed current output when path smoothing is on) plus
    ``min_gain_to_split``."""
    total_g, total_h = parent_sum[0], parent_sum[1]
    if params.path_smooth > 0:
        # reference: with smoothing the gain shift is the leaf's gain AT
        # its current (already-smoothed) output value
        parent_gain = leaf_gain_given_output(total_g, total_h,
                                             parent_output, params)
    else:
        parent_gain = leaf_gain(total_g, total_h, params)
    return parent_gain + params.min_gain_to_split


def scan_direction_gains(left2, parent_sum, meta, feature_mask, params,
                         constraint=None, depth=0, monotone_penalty=0.0,
                         parent_output=0.0, rand_key=None,
                         cegb_penalty=None):
    """Phase 2 of the fused split scan: gains of every (direction,
    feature, bin) candidate in ONE stacked evaluation over the
    ``(3, 2, F, B)`` left sums from :func:`scan_left_sums` — the gain
    math (leaf_gain / smoothing / monotone clamps) is traced once on the
    doubled tensor instead of once per direction, so the whole
    cumsum → gain chain lowers as a single fused pass.

    Returns ``(gains (2, F, B), shift)`` with gains RELATIVE (shift =
    parent gain + min_gain_to_split already subtracted) and every
    penalty applied.  Module-level for tools/phase_attrib.py."""
    _, _, F, B = left2.shape
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
        lg, lh, lc = left[0], left[1], left[2]
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


def scan_pick_feature(gains, shift, meta):
    """Per-feature stage of the tie-band preference argmax: each
    feature's best candidate gain over its ``2B`` (direction, bin) slots
    plus the preferred in-band candidate index.  Returns
    ``(fbest (F,), sel_f (F,))`` with ``sel_f`` encoding
    ``direction * B + threshold``."""
    _, F, B = gains.shape
    t_idx = lax.broadcasted_iota(jnp.int32, (F, B), 1)
    rev_like_a = ((meta.missing_type == MISSING_NONE)
                  | (meta.num_bins <= 2))[:, None]        # (F, 1)
    pref_a = jnp.where(rev_like_a, 2 * B + t_idx, B - 1 - t_idx)
    pref_b = jnp.broadcast_to(2 * B + t_idx, (F, B))
    gains_f = jnp.concatenate([gains[0], gains[1]], axis=1)   # (F, 2B)
    pref_f = jnp.concatenate([pref_a, pref_b], axis=1)        # (F, 2B)
    fbest = gains_f.max(axis=1)                               # (F,)
    # near-tie band (tie_tol above): every candidate within the band of
    # its feature's best competes on the deterministic preference order
    # alone, so reduction-order ulp noise cannot flip the pick
    tol_f = tie_tol(fbest, shift)                             # (F,)
    sel_f = jnp.argmax(
        jnp.where(gains_f >= (fbest - tol_f)[:, None], pref_f, -1),
        axis=1)                                               # (F,)
    return fbest, sel_f


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
    B = gains.shape[-1]
    # the gains are ranked and the winner's is read back: both from one
    # stored array, so that the compiler does not evaluate the gain math
    # once for each reader (and, where it contracts multiply-adds by
    # context, round the two differently)
    gains = lax.optimization_barrier(gains)
    fbest, sel_f = scan_pick_feature(gains, shift, meta)
    gbest = jnp.max(fbest)
    feature = jnp.argmax(fbest >= gbest - tie_tol(gbest, shift)) \
        .astype(jnp.int32)                   # first in band = min feature
    sel = sel_f[feature]
    direction = (sel // B).astype(jnp.int32)
    threshold = (sel % B).astype(jnp.int32)
    best_gain = read_candidate(gains, direction, feature, threshold)
    return best_gain, feature, threshold, direction


def read_candidate(x, direction, feature, threshold):
    """``x[..., direction, feature, threshold]`` of a ``(..., 2, F, B)``
    array as a masked maximum: it reads ``x`` in whatever layout the scan
    left it, where a gather of single numbers has the operand copied to a
    layout of its own.  One position passes the mask, so the value comes
    out as stored (a maximum, not a sum: -0.0 stays -0.0)."""
    D, F, B = x.shape[-3:]
    hit = ((lax.broadcasted_iota(jnp.int32, (D, F, B), 0) == direction)
           & (lax.broadcasted_iota(jnp.int32, (D, F, B), 1) == feature)
           & (lax.broadcasted_iota(jnp.int32, (D, F, B), 2) == threshold))
    return jnp.max(jnp.where(hit, x, NEG_INF), axis=(-3, -2, -1))


def _find_best_split(
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
    _count_scan_columns(F, F)
    use_mc = _any_monotone(meta)
    if constraint is None:
        constraint = jnp.asarray(NO_CONSTRAINT, jnp.float32)

    left2, hist = scan_left_sums(hist, meta, hist_scale)
    gains, shift = scan_direction_gains(
        left2, parent_sum, meta, feature_mask, params, constraint, depth,
        monotone_penalty, parent_output, rand_key, cegb_penalty)
    best_gain, feature, threshold, direction = scan_pick(gains, shift, meta)

    left = read_candidate(left2, direction, feature, threshold)   # (3,)

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


def per_feature_best_gain(
    hist: jax.Array,          # (F, B, 3)
    parent_sum: jax.Array,    # (3,)
    meta: FeatureMeta,
    feature_mask: jax.Array,  # (F,) bool
    params: SplitParams,
    parent_output=0.0,        # leaf's current output (path smoothing shift)
) -> jax.Array:               # (F,) best split gain per feature (-inf if none)
    """Per-feature best numerical gain — the PV-Tree voting score
    (reference: VotingParallelTreeLearner computes local best splits per
    feature before voting, voting_parallel_tree_learner.cpp:300-310)."""
    F, B, _ = hist.shape
    total_g, total_h, total_c = parent_sum[0], parent_sum[1], parent_sum[2]
    t_idx = lax.broadcasted_iota(jnp.int32, (F, B), 1)
    nb = meta.num_bins[:, None]
    is_nan_f = (meta.missing_type == MISSING_NAN)[:, None]
    is_zero_f = (meta.missing_type == MISSING_ZERO)[:, None]
    # missing-direction accounting is find_best_split's own (zero-as-missing
    # mass rides the scan direction, SKIP_DEFAULT_BIN semantics)
    left2, _ = scan_left_sums(hist, meta)             # (3, 2, F, B)
    lg, lh, lc = left2[0], left2[1], left2[2]
    rg, rh, rc = total_g - lg, total_h - lh, total_c - lc
    ok = ((lc >= params.min_data_in_leaf)
          & (rc >= params.min_data_in_leaf)
          & (lh >= params.min_sum_hessian_in_leaf)
          & (rh >= params.min_sum_hessian_in_leaf))
    gain = jnp.where(ok, leaf_gain(lg, lh, params)
                     + leaf_gain(rg, rh, params), NEG_INF)

    valid = (t_idx <= nb - 2) & feature_mask[:, None] & meta.usable[:, None] \
        & (~meta.is_categorical[:, None])
    ga = jnp.where(valid, gain[0], NEG_INF)
    gb = jnp.where(valid & (is_nan_f | is_zero_f), gain[1], NEG_INF)
    best = jnp.maximum(ga.max(axis=1), gb.max(axis=1))
    # votes rank RELATIVE gains with the feature_contri penalty applied,
    # like the full search (the constant shift is rank-neutral without
    # contri, but with per-feature multipliers it changes the ordering);
    # with path smoothing the shift is the smoothed parent gain, matching
    # find_best_split's baseline so votes rank consistently with the
    # search they gate
    if params.path_smooth > 0:
        parent_gain = leaf_gain_given_output(total_g, total_h,
                                             parent_output, params)
    else:
        parent_gain = leaf_gain(total_g, total_h, params)
    shift = parent_gain + params.min_gain_to_split
    best = jnp.where(jnp.isfinite(best), best - shift, best)
    if meta.contri is not None:
        best = jnp.where(jnp.isfinite(best), best * meta.contri, best)
    return best


# vmapped over a batch of leaves: hist (K, F, B, 3), parent (K, 3), mask (K, F),
# constraint (K, 2), parent_output (K,); depth/penalty/key shared
find_best_split_batch = jax.vmap(
    find_best_split, in_axes=(0, 0, None, 0, None, 0, None, None, 0, None))
