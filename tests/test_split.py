"""Split finder vs brute force — validates the vectorized two-direction scan
against an explicit enumeration of every (feature, threshold, direction)."""

import jax.numpy as jnp
import numpy as np
import pytest

from lightgbmv1_tpu.io.binning import MISSING_NAN, MISSING_NONE
from lightgbmv1_tpu.ops.split import (
    FeatureMeta,
    SplitParams,
    find_best_split,
    leaf_output,
    threshold_l1,
)


def make_meta(num_bins, missing=None):
    F = len(num_bins)
    missing = missing or [MISSING_NONE] * F
    nan_bin = [nb - 1 if mt == MISSING_NAN else -1 for nb, mt in zip(num_bins, missing)]
    return FeatureMeta(
        num_bins=jnp.asarray(num_bins, jnp.int32),
        missing_type=jnp.asarray(missing, jnp.int32),
        nan_bin=jnp.asarray(nan_bin, jnp.int32),
        zero_bin=jnp.asarray([0] * F, jnp.int32),
        is_categorical=jnp.zeros(F, bool),
        usable=jnp.ones(F, bool),
        monotone_type=jnp.zeros(F, jnp.int32),
    )


def brute_force(hist, parent, num_bins, missing, params):
    """Enumerate every split the reference's sequential scans would consider."""
    F, B, _ = hist.shape
    best = (-np.inf, -1, -1, False)
    l1, l2 = params.lambda_l1, params.lambda_l2

    def gain(g, h):
        t = np.sign(g) * max(abs(g) - l1, 0.0)
        return t * t / (h + l2)

    parent_gain = gain(parent[0], parent[1])
    for f in range(F):
        nb = num_bins[f]
        nanb = nb - 1 if missing[f] == MISSING_NAN else -1
        for direction in (0, 1):
            if direction == 1 and nanb < 0:
                continue
            for t in range(nb - 1):
                left = hist[f, : t + 1].sum(axis=0)
                if direction == 1 and nanb > t:
                    left = left + hist[f, nanb]
                right = parent - left
                if (
                    left[2] < params.min_data_in_leaf
                    or right[2] < params.min_data_in_leaf
                    or left[1] < params.min_sum_hessian_in_leaf
                    or right[1] < params.min_sum_hessian_in_leaf
                ):
                    continue
                g = gain(left[0], left[1]) + gain(right[0], right[1])
                if g > best[0]:
                    best = (g, f, t, direction == 1)
    rel = best[0] - parent_gain - params.min_gain_to_split
    return rel, best[1], best[2], best[3]


@pytest.mark.parametrize("l1,l2,min_data", [(0.0, 0.0, 1), (0.5, 1.0, 5), (0.0, 10.0, 20)])
def test_matches_brute_force(rng, l1, l2, min_data):
    F, B = 4, 16
    num_bins = [16, 12, 9, 16]
    hist = np.zeros((F, B, 3))
    for f in range(F):
        nb = num_bins[f]
        hist[f, :nb, 0] = rng.randn(nb) * 5
        hist[f, :nb, 1] = rng.rand(nb) * 10 + 0.1
        hist[f, :nb, 2] = rng.randint(1, 50, nb)
    # consistent totals across features
    parent = hist[0].sum(axis=0)
    for f in range(1, F):
        nb = num_bins[f]
        hist[f, :nb] *= (parent / np.maximum(hist[f].sum(axis=0), 1e-12))[None, :]

    params = SplitParams(lambda_l1=l1, lambda_l2=l2, min_data_in_leaf=min_data,
                         min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0)
    meta = make_meta(num_bins)
    res = find_best_split(jnp.asarray(hist, jnp.float32),
                          jnp.asarray(parent, jnp.float32), meta,
                          jnp.ones(F, bool), params)
    bg, bf, bt, bdl = brute_force(hist, parent, num_bins, [MISSING_NONE] * F, params)
    if bg <= 0 and not np.isfinite(bg):
        assert not np.isfinite(float(res.gain))
        return
    np.testing.assert_allclose(float(res.gain), bg, rtol=1e-4)
    assert int(res.feature) == bf
    assert int(res.threshold_bin) == bt


def test_nan_direction(rng):
    """With a NaN bin, both default directions are scanned and the best wins."""
    F, B = 1, 8
    nb = 8
    hist = np.zeros((F, B, 3))
    hist[0, :, 1] = 1.0
    hist[0, :, 2] = 10.0
    # negative grads in low bins, positive in high bins; NaN bin mildly
    # negative — pairing NaN with the left (negative) side must beat both
    # isolating it and sending it right
    hist[0, :4, 0] = -5.0
    hist[0, 4:7, 0] = +5.0
    hist[0, 7, 0] = -1.0  # NaN bin
    parent = hist[0].sum(axis=0)
    params = SplitParams(min_data_in_leaf=1)
    meta = make_meta([nb], [MISSING_NAN])
    res = find_best_split(jnp.asarray(hist, jnp.float32),
                          jnp.asarray(parent, jnp.float32), meta,
                          jnp.ones(F, bool), params)
    bg, bf, bt, bdl = brute_force(hist, parent, [nb], [MISSING_NAN], params)
    np.testing.assert_allclose(float(res.gain), bg, rtol=1e-5)
    assert bool(res.default_left) == bdl
    assert bool(res.default_left)  # NaN belongs with the negative (left) side


def test_min_data_blocks_split():
    F, B = 1, 4
    hist = np.zeros((F, B, 3))
    hist[0, :, 0] = [-5, 5, -5, 5]
    hist[0, :, 1] = 1.0
    hist[0, :, 2] = 3.0
    parent = hist[0].sum(axis=0)
    meta = make_meta([4])
    params = SplitParams(min_data_in_leaf=100)
    res = find_best_split(jnp.asarray(hist, jnp.float32),
                          jnp.asarray(parent, jnp.float32), meta,
                          jnp.ones(1, bool), params)
    assert not np.isfinite(float(res.gain)) or float(res.gain) <= 0


def test_feature_mask_respected(rng):
    F, B = 3, 8
    hist = rng.rand(F, B, 3) + 0.1
    hist[0, :, 0] = [-50, 50, -50, 50, -50, 50, -50, 50]  # feature 0 is best
    parent = hist[0].sum(axis=0)
    meta = make_meta([8, 8, 8])
    params = SplitParams(min_data_in_leaf=0)
    mask = jnp.asarray([False, True, True])
    res = find_best_split(jnp.asarray(hist, jnp.float32),
                          jnp.asarray(parent, jnp.float32), meta, mask, params)
    assert int(res.feature) != 0


def test_leaf_output_l1_l2():
    p = SplitParams(lambda_l1=1.0, lambda_l2=2.0)
    out = float(leaf_output(jnp.asarray(5.0), jnp.asarray(3.0), p))
    np.testing.assert_allclose(out, -(5.0 - 1.0) / (3.0 + 2.0))
    p2 = SplitParams(max_delta_step=0.1)
    out2 = float(leaf_output(jnp.asarray(5.0), jnp.asarray(1.0), p2))
    np.testing.assert_allclose(out2, -0.1)


# ---------------------------------------------------------------------------
# Deterministic near-tie resolution (reduction-order invariance, PR 3)
# ---------------------------------------------------------------------------


def test_exact_tie_prefers_lower_feature():
    """Two features with IDENTICAL histograms (an exact gain tie): the
    split must land on the lower feature id, invariant to how the
    histogram was reduced (SplitInfo::operator> tie-break)."""
    B = 8
    hist_f = np.zeros((B, 3), np.float32)
    hist_f[:, 0] = [-4, -3, -2, -1, 1, 2, 3, 4]
    hist_f[:, 1] = 1.0
    hist_f[:, 2] = 10.0
    hist = np.stack([hist_f, hist_f, hist_f])         # 3 identical features
    parent = hist[0].sum(axis=0)
    meta = make_meta([B, B, B])
    params = SplitParams(min_data_in_leaf=0)
    res = find_best_split(jnp.asarray(hist), jnp.asarray(parent), meta,
                          jnp.ones(3, bool), params)
    assert float(res.gain) > 0
    assert int(res.feature) == 0


def test_near_tie_within_tolerance_is_order_invariant():
    """Perturb the tied copy by less than the tie_tol band (the magnitude
    of psum-vs-serial f32 summation-order noise): the pick must STILL be
    the lower feature, in either perturbation direction — the fix for the
    psum near-tie threshold flips tests/test_parallel.py[data] pinned."""
    from lightgbmv1_tpu.ops.split import TIE_RTOL

    B = 8
    hist_f = np.zeros((B, 3), np.float32)
    hist_f[:, 0] = [-4, -3, -2, -1, 1, 2, 3, 4]
    hist_f[:, 1] = 1.0
    hist_f[:, 2] = 10.0
    for sign in (+1.0, -1.0):
        bumped = hist_f.copy()
        # ~2 ulp-scale relative bump on the gradient channel — well inside
        # the tie band, the size of a reduction-order flip
        bumped[:, 0] *= 1.0 + sign * 0.05 * TIE_RTOL
        hist = np.stack([hist_f, bumped])
        parent = hist[0].sum(axis=0)
        meta = make_meta([B, B])
        params = SplitParams(min_data_in_leaf=0)
        res = find_best_split(jnp.asarray(hist), jnp.asarray(parent), meta,
                              jnp.ones(2, bool), params)
        assert int(res.feature) == 0, sign


def test_genuinely_distinct_gains_not_tied():
    """A gain gap far above the band must still pick the strictly better
    feature even when it has the HIGHER id (the tolerance must not bleed
    into real decisions — the golden-parity guarantee)."""
    B = 8
    weak = np.zeros((B, 3), np.float32)
    weak[:, 0] = [-1, 1, -1, 1, -1, 1, -1, 1]
    weak[:, 1] = 1.0
    weak[:, 2] = 10.0
    strong = weak.copy()
    strong[:, 0] = [-4, -3, -2, -1, 1, 2, 3, 4]
    hist = np.stack([weak, strong])
    parent = hist[0].sum(axis=0)
    meta = make_meta([B, B])
    params = SplitParams(min_data_in_leaf=0)
    res = find_best_split(jnp.asarray(hist), jnp.asarray(parent), meta,
                          jnp.ones(2, bool), params)
    assert int(res.feature) == 1


def _window_problem(seed):
    """11 features x 16 bins: NaN- and zero-missing columns, a categorical
    one, an unusable one, a monotone pair, a contri penalty."""
    from lightgbmv1_tpu.io.binning import MISSING_ZERO

    rs = np.random.RandomState(seed)
    F, B = 11, 16
    nb = rs.randint(4, B + 1, F)
    missing = [MISSING_NONE] * F
    missing[3], missing[4], missing[9] = MISSING_NAN, MISSING_ZERO, MISSING_NAN
    cnt = rs.randint(0, 40, (F, B)).astype(np.float64)
    cnt *= np.arange(B)[None, :] < nb[:, None]
    total = 400.0
    cnt = np.round(cnt / cnt.sum(axis=1, keepdims=True) * total)
    cnt[:, 0] += total - cnt.sum(axis=1)
    hist = np.stack([rs.randn(F, B) * cnt, cnt * 0.25, cnt], axis=-1)
    parent = hist[0].sum(axis=0)
    hist[:, 0, 0] += parent[0] - hist[:, :, 0].sum(axis=1)
    meta = make_meta(nb.tolist(), missing)._replace(
        zero_bin=jnp.asarray(rs.randint(0, 3, F), jnp.int32),
        is_categorical=jnp.zeros(F, bool).at[2].set(True),
        usable=jnp.ones(F, bool).at[7].set(False),
        monotone_type=jnp.zeros(F, jnp.int32).at[0].set(1).at[5].set(-1),
        contri=jnp.asarray(rs.uniform(0.5, 1.0, F), jnp.float32))
    mask = jnp.asarray(rs.rand(F) < 0.8)
    return (jnp.asarray(hist, jnp.float32), jnp.asarray(parent, jnp.float32),
            meta, mask)


@pytest.mark.parametrize("extra_trees", [False, True])
@pytest.mark.parametrize("lo,width", [(0, 3), (3, 3), (6, 3), (9, 3),
                                      (12, 3), (0, 6), (6, 6), (0, 11)])
def test_window_scan_is_the_whole_scan_of_its_columns(lo, width, extra_trees):
    """``narrow_meta``: the scan of a window's columns elects what the
    whole scan elects with every other column masked out: same gain,
    same global feature id, threshold, direction, sums and bitset; ids
    past the end are padding that never wins; one extra_trees draw per
    global feature."""
    import jax

    from lightgbmv1_tpu.ops.split import narrow_meta, take_columns

    hist, parent, meta, mask = _window_problem(lo + width)
    F = hist.shape[0]
    params = SplitParams(min_data_in_leaf=5.0, extra_trees=extra_trees,
                         max_cat_to_onehot=2, min_data_per_group=5.0,
                         cat_smooth=1.0)
    key = jax.random.PRNGKey(3) if extra_trees else None
    bounds = jnp.asarray([-0.8, 0.9], jnp.float32)
    pen = jnp.linspace(0.0, 0.02, F)
    cols = jnp.arange(lo, lo + width, dtype=jnp.int32)
    inside = (jnp.arange(F) >= lo) & (jnp.arange(F) < lo + width)
    whole = jax.jit(lambda m: find_best_split(
        hist, parent, meta, m, params, bounds, 2, 0.5, 0.1, key, pen))(
            mask & inside)
    rows = jnp.take(hist, jnp.clip(cols, 0, F - 1), axis=0) \
        * (cols < F)[:, None, None]          # padding columns arrive zero
    part = jax.jit(lambda c: find_best_split(
        rows, parent, narrow_meta(meta, c), take_columns(mask, c, False),
        params, bounds, 2, 0.5, 0.1, key, take_columns(pen, c, 0.0)))(cols)
    if lo >= F:         # nothing but padding: no candidate, feature 0
        assert not np.isfinite(float(part.gain)) and int(part.feature) == 0
        whole, part = whole[:2], part[:2]
    for a, b in zip(whole, part):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
