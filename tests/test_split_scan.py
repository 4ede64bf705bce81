"""The split scan on channel planes (PR 32) against what it replaced and
against a plain float64 scan.

* bit for bit against the parent's scan (``parent_oracles.find_best_split``:
  channel-minor arrays, the winner read by a gather) over 17 / 67 / 137 /
  2,000 columns x 1 / 126 children x plain, missing values, monotone,
  categorical present, quantized (``hist_scale``);
* against a NumPy float64 scan (cumulative sums in both directions, the
  gain ``GL^2/(HL+l2) + GR^2/(HR+l2) - G^2/(H+l2)`` of
  ``benchmarks/reference.py``, the first best wins) at small sizes;
* the ``split_scan_columns`` gauge held to the shapes' arithmetic.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parent_oracles
from lightgbmv1_tpu.io.binning import MISSING_NAN, MISSING_NONE, MISSING_ZERO
from lightgbmv1_tpu.obs.metrics import default_registry
from lightgbmv1_tpu.ops import split as split_mod
from lightgbmv1_tpu.ops.split import (FeatureMeta, SplitParams,
                                      find_best_split, narrow_meta,
                                      per_feature_best_gain)

MODES = ("plain", "missing", "monotone", "categorical", "hist_scale")


def problem(F, K, mode, seed):
    """(hist (K, F, B, 3), parents (K, 3), masks (K, F), meta, kwargs)."""
    rng = np.random.RandomState(seed)
    B = 64 if F * K <= 20_000 else 16
    num_bins = rng.randint(3, B + 1, F)
    num_bins[:: 5] = B
    missing = np.full(F, MISSING_NONE)
    is_cat = np.zeros(F, bool)
    mono = np.zeros(F, np.int32)
    if mode in ("missing", "categorical"):
        missing = rng.choice([MISSING_NONE, MISSING_NAN, MISSING_ZERO], F)
    if mode == "categorical":
        is_cat[rng.rand(F) < 0.25] = True
        missing[is_cat] = MISSING_NONE
    if mode == "monotone":
        mono = rng.choice([-1, 0, 1], F).astype(np.int32)
    nan_bin = np.where(missing == MISSING_NAN, num_bins - 1, -1)
    zero_bin = rng.randint(0, 3, F) % num_bins
    in_range = np.arange(B)[None, :] < num_bins[:, None]          # (F, B)
    cnt = rng.randint(0, 40, (K, F, B)) * in_range
    # every feature of a child holds the child's rows: equal totals
    cnt[:, :, 0] += cnt.sum(axis=2).max(axis=1)[:, None] - cnt.sum(axis=2)
    if mode == "hist_scale":
        grad = rng.randint(-90, 90, (K, F, B)) * in_range
        hess = rng.randint(0, 60, (K, F, B)) * in_range
    else:
        grad = rng.randn(K, F, B) * np.sqrt(cnt)
        hess = rng.rand(K, F, B) * cnt * 0.25
    hist = np.stack([grad, hess, cnt], axis=-1).astype(np.float32)
    scale = (rng.rand(K, 3) * 0.01 + 0.001).astype(np.float32)
    scale[:, 2] = 1.0
    totals = hist[:, 0].sum(axis=1)                               # (K, 3)
    parents = (totals * scale if mode == "hist_scale" else totals)
    meta = FeatureMeta(
        num_bins=jnp.asarray(num_bins, jnp.int32),
        missing_type=jnp.asarray(missing, jnp.int32),
        nan_bin=jnp.asarray(nan_bin, jnp.int32),
        zero_bin=jnp.asarray(zero_bin, jnp.int32),
        is_categorical=jnp.asarray(is_cat),
        usable=jnp.asarray(rng.rand(F) < 0.95),
        monotone_type=jnp.asarray(mono))
    masks = rng.rand(K, F) < 0.9
    kw = {}
    if mode == "monotone":
        kw = dict(constraint=jnp.asarray([-0.4, 0.6], jnp.float32), depth=2,
                  monotone_penalty=0.5)
    params = SplitParams(lambda_l2=0.5, min_data_in_leaf=3.0,
                         min_sum_hessian_in_leaf=1.0, min_data_per_group=5.0,
                         cat_smooth=2.0)
    return (jnp.asarray(hist), jnp.asarray(parents, jnp.float32),
            jnp.asarray(masks), meta, params, kw,
            jnp.asarray(scale) if mode == "hist_scale" else None)


def scan_children(fn, hist, parents, masks, meta, params, kw, scale):
    if scale is None:
        one = lambda h, p, m: fn(h, p, meta, m, params, **kw)
        return jax.jit(jax.vmap(one))(hist, parents, masks)
    one = lambda h, p, m, s: fn(h, p, meta, m, params, hist_scale=s, **kw)
    return jax.jit(jax.vmap(one))(hist, parents, masks, scale)


def bits(x):
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


def same_result(new, old):
    """Every field of two ``SplitResult`` s bit for bit; the gain to its
    last bit.  XLA:CPU rounds ``a * b + c`` once or twice by what else
    reads the product, so one program's gain can differ from another's
    in the last place though every candidate's gain, stage by stage, is
    the same number (``test_stages_bit_identical_to_parent_scan``)."""
    for field in new._fields:
        a, b = np.asarray(getattr(new, field)), np.asarray(getattr(old, field))
        if field != "gain":
            np.testing.assert_array_equal(bits(a), bits(b), err_msg=field)
            continue
        finite = np.isfinite(a)
        np.testing.assert_array_equal(finite, np.isfinite(b))
        np.testing.assert_array_equal(a[~finite], b[~finite])
        if finite.any():
            np.testing.assert_array_max_ulp(a[finite], b[finite], maxulp=1)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("children", [1, 126])
@pytest.mark.parametrize("columns", [17, 67, 137, 2000])
def test_bit_identical_to_parent_scan(columns, children, mode):
    args = problem(columns, children, mode, seed=columns + children)
    new = scan_children(find_best_split, *args)
    old = scan_children(parent_oracles.find_best_split, *args)
    assert np.isfinite(np.asarray(new.gain)).any()
    same_result(new, old)


@pytest.mark.parametrize("mode", [m for m in MODES if m != "categorical"])
@pytest.mark.parametrize("children", [1, 126])
@pytest.mark.parametrize("columns", [17, 137])
def test_stages_bit_identical_to_parent_scan(columns, children, mode):
    """The left sums and the gains of EVERY candidate, each stage a
    program of its own: the planes hold the parent's numbers.  (The
    categorical search reads the same planes; its result is held whole,
    above.)"""
    hist, parents, masks, meta, params, kw, scale = problem(
        columns, children, mode, seed=columns * children)
    over = lambda fn, *xs: jax.jit(jax.vmap(fn))(*xs)
    scales = scale if scale is not None else jnp.ones((children, 3))
    left_new, _ = over(lambda h, s: split_mod.scan_left_sums(h, meta, s),
                       hist, scales)
    left_old, _ = over(lambda h, s: parent_oracles.scan_left_sums(h, meta, s),
                       hist, scales)
    np.testing.assert_array_equal(
        bits(left_new), bits(jnp.moveaxis(left_old, -1, 1)))
    gains = lambda mod, left: over(
        lambda l, p, m: mod.scan_direction_gains(
            l, p, meta, m, params, kw.get("constraint"), kw.get("depth", 0),
            kw.get("monotone_penalty", 0.0))[0], left, parents, masks)
    np.testing.assert_array_equal(bits(gains(split_mod, left_new)),
                                  bits(gains(parent_oracles, left_old)))


def test_window_bit_identical_to_parent_scan():
    """``narrow_meta``'s traced columns (the four-chip learner's scan)."""
    hist, parents, masks, meta, params, kw, _ = problem(67, 8, "missing", 7)
    cols = jnp.asarray(np.r_[34:51, 67, 68], jnp.int32)        # 2 of padding
    take = lambda x, fill: jnp.take(x, cols, axis=1, mode="fill",
                                    fill_value=fill)
    h_w, m_w = take(hist, 0.0), take(masks, False)
    run = lambda fn: jax.jit(jax.vmap(lambda h, p, m: fn(
        h, p, narrow_meta(meta, cols), m, params)))(h_w, parents, m_w)
    same_result(run(find_best_split), run(parent_oracles.find_best_split))


def float64_scan(hist, parent, meta, mask, params):
    """Every (feature, threshold, direction) in float64, first best kept:
    (gain, feature, threshold, direction, left sums)."""
    hist = np.asarray(hist, np.float64)
    nb, mt = np.asarray(meta.num_bins), np.asarray(meta.missing_type)
    F, B, _ = hist.shape
    l2 = params.lambda_l2
    leaf = lambda g, h: g * g / (h + l2)
    best = (-np.inf, -1, -1, -1, None)
    for f in range(F):
        if not (mask[f] and np.asarray(meta.usable)[f]):
            continue
        cum = np.cumsum(hist[f], axis=0)
        nan = hist[f, nb[f] - 1] if mt[f] == MISSING_NAN else None
        for d in ((0, 1) if nan is not None else (0,)):
            for t in range(nb[f] - 1):
                left = cum[t] + (nan if d == 1 else 0.0)
                right = parent - left
                if (min(left[2], right[2]) < params.min_data_in_leaf
                        or min(left[1], right[1])
                        < params.min_sum_hessian_in_leaf):
                    continue
                g = (leaf(left[0], left[1]) + leaf(right[0], right[1])
                     - leaf(parent[0], parent[1]))
                if g > best[0]:
                    best = (g, f, t, d, left)
    return best


@pytest.mark.parametrize("mode", ["plain", "missing"])
@pytest.mark.parametrize("columns", [5, 17, 67])
def test_against_float64_scan(columns, mode):
    rng = np.random.RandomState(columns)
    B, K = 16, 6
    hist, parents, masks, meta, params, kw, _ = problem(columns, K, "plain",
                                                         seed=columns)
    hist = np.array(hist[..., :B, :])      # fewer bins: a loop in Python
    nb = np.minimum(np.asarray(meta.num_bins), B)
    missing = np.full(columns, MISSING_NONE)
    if mode == "missing":
        missing[rng.rand(columns) < 0.5] = MISSING_NAN
    hist *= (np.arange(B)[None, :] < nb[:, None])[None, :, :, None]
    hist[:, :, 0, 2] += hist[..., 2].sum(axis=2).max(axis=1)[:, None] \
        - hist[..., 2].sum(axis=2)
    parents = hist[:, 0].sum(axis=1)
    meta = meta._replace(
        num_bins=jnp.asarray(nb, jnp.int32),
        missing_type=jnp.asarray(missing, jnp.int32),
        nan_bin=jnp.asarray(np.where(missing == MISSING_NAN, nb - 1, -1),
                            jnp.int32))
    res = scan_children(find_best_split, jnp.asarray(hist),
                        jnp.asarray(parents), masks, meta, params, {}, None)
    for k in range(K):
        gain, f, t, d, left = float64_scan(hist[k], parents[k], meta,
                                           np.asarray(masks[k]), params)
        assert gain > 0
        assert float(res.gain[k]) == pytest.approx(gain, rel=2e-5, abs=1e-4)
        assert (int(res.feature[k]), int(res.threshold_bin[k]),
                bool(res.default_left[k])) == (f, t, bool(d))
        np.testing.assert_allclose(np.asarray(res.left_sum[k]), left,
                                   rtol=2e-5, atol=1e-4)
        np.testing.assert_allclose(np.asarray(res.right_sum[k]),
                                   parents[k] - left, rtol=2e-5, atol=1e-3)


def test_per_feature_best_gain_is_the_scan_s_best():
    """The voting learner's per-feature gains come from the same left
    sums: the largest of them is the scan's winner's gain."""
    hist, parents, masks, meta, params, _, _ = problem(67, 4, "missing", 3)
    res = scan_children(find_best_split, hist, parents, masks, meta, params,
                        {}, None)
    gains = jax.vmap(lambda h, p, m: per_feature_best_gain(
        h, p, meta, m, params))(hist, parents, masks)
    np.testing.assert_array_equal(np.asarray(gains.max(axis=1)),
                                  np.asarray(res.gain))
    np.testing.assert_array_equal(np.asarray(gains.argmax(axis=1)),
                                  np.asarray(res.feature))


@pytest.mark.parametrize("columns", [17, 2000])
def test_split_scan_columns_gauge(columns):
    """Set when a scan is traced: the columns it covers, and the columns
    of one block of it (the scan is not cut into blocks: both the same)."""
    hist, parents, masks, meta, params, kw, _ = problem(columns, 1, "plain",
                                                         seed=1)
    jax.jit(jax.vmap(lambda h, p, m: find_best_split(
        h, p, meta, m, params))).lower(hist, parents, masks)
    gauge = default_registry().get("split_scan_columns")
    assert gauge.labels(what="scanned").get() == columns
    assert gauge.labels(what="block").get() == columns
