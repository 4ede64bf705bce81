"""The histogram kernel's 256-bin rung on its lane-dense bin operand
(``hist_pallas.HistBins`` with ``windows`` > 1): the library's default
``max_bin=255``, which the benchmark's ``higgs-255b-train`` runs at
10,500,000 x 28.

The operand stores the matrix's byte columns side by side, 128 an array,
and a feature block of a pass is a static window of 8 of them; the tests
hold it to the scatter oracle and to the raw path through the kernel
(interpreter), its bytes and gauges to the shapes' arithmetic, and a small
booster at 255 bins to a plain numpy histogram GBDT at the same bins.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbmv1_tpu as lgb
from lightgbmv1_tpu.obs.metrics import default_registry
from lightgbmv1_tpu.ops.hist_pallas import (MAX_ROW_TILE, HistBins,
                                            _block_windows, _count_operand,
                                            _feature_blocks,
                                            hist_leaves_pallas,
                                            prepare_hist_bins,
                                            prepared_bins_bytes)
from lightgbmv1_tpu.ops.histogram import hist_leaves_scatter

B = 256
N = 1500                         # no multiple of a row tile


def _inputs(F, slots, precision):
    rng = np.random.RandomState(7 * F + slots)
    bins = rng.randint(0, B, size=(F, N)).astype(np.uint8)
    bins[:, :B] = np.arange(B, dtype=np.uint8)      # every bin, 255 too
    if precision == "f32":
        # on a 2^-6 grid every partial sum is exact, whatever way the
        # interpreter's f32 matmul blocks them (PR 29)
        g3 = (rng.randint(-256, 257, size=(N, 3)) / 64.0).astype(np.float32)
    else:
        g3 = rng.randn(N, 3).astype(np.float32)
    g3[:, 2] = rng.rand(N) < 0.9                    # the count: a 0/1 mask
    label = rng.randint(0, slots + 1, N).astype(np.int32)   # slots: dead
    return bins, jnp.asarray(g3), label


@pytest.mark.parametrize("precision", ["f32", "bf16x2"])
@pytest.mark.parametrize("slots", [1, 4, 16, 63])
@pytest.mark.parametrize("F", [8, 28, 29, 137])
def test_dense_operand_against_oracle_and_raw_path(F, slots, precision):
    """One block, a part-filled last block (28 = 3 x 8 + 4), one column
    into the next window (29), and two stored arrays (137 = 128 + 9)."""
    bins, g3, label = _inputs(F, slots, precision)
    matrix = jnp.asarray(bins)
    prepared = prepare_hist_bins(matrix, B)
    assert isinstance(prepared, HistBins) and prepared.matrix is matrix
    assert (prepared.tile_cols, prepared.windows) == (8, 16)
    assert [b.shape for b in prepared.blocks] == \
        [(2 * MAX_ROW_TILE, 128)] * -(-F // 128)
    stored = np.concatenate([np.asarray(b) for b in prepared.blocks], axis=1)
    np.testing.assert_array_equal(stored[:N, :F], bins.T)
    assert (stored[:N, F:] == 255).all() and (stored[N:] == 255).all()

    kw = dict(precision=precision, interpret=True)
    got = np.asarray(hist_leaves_pallas(prepared, g3, jnp.asarray(label),
                                        slots, B, **kw))
    raw = np.asarray(hist_leaves_pallas(matrix, g3, jnp.asarray(label),
                                        slots, B, **kw))
    assert got.shape == (slots, F, B, 3)
    np.testing.assert_array_equal(got, raw)

    live = label < slots
    ref = np.asarray(hist_leaves_scatter(
        jnp.asarray(bins[:, live]), g3[live], jnp.asarray(label[live]),
        slots, B))
    np.testing.assert_array_equal(got[..., 2], ref[..., 2])
    if precision == "f32":
        np.testing.assert_array_equal(got, ref)
    else:
        # each addend is off by at most 2^-16 of itself (hi + lo bf16)
        np.testing.assert_allclose(got, ref, rtol=1e-2, atol=1e-2)
        ref_abs = np.asarray(hist_leaves_scatter(
            jnp.asarray(bins[:, live]), jnp.abs(g3[live]),
            jnp.asarray(label[live]), slots, B))
        assert (np.abs(got - ref) <= 2.0 ** -14 * ref_abs + 1e-6).all()


def test_dense_operand_refuses_another_rung():
    bins, g3, label = _inputs(28, 4, "bf16x2")
    with pytest.raises(ValueError, match="do not fit this pass"):
        hist_leaves_pallas(prepare_hist_bins(jnp.asarray(bins), 64), g3,
                           jnp.asarray(label), 4, B, interpret=True)
    with pytest.raises(ValueError, match="do not fit this pass"):
        hist_leaves_pallas(prepare_hist_bins(jnp.asarray(bins) // 4, B), g3,
                           jnp.asarray(label), 4, 64, interpret=True)


# (columns, rows, bins) of the benchmark's one-chip cells
_HIGGS = (28, 10_500_000, 256)
_MSLR = (137, 2_270_296, 64)
_EPSILON = (2000, 400_000, 64)


def _gauges():
    snap = default_registry().snapshot()
    return ({w: snap.get('hist_operand_lanes{what="%s"}' % w)
             for w in ("stored", "live")},
            {r: snap.get('hist_pass_blocks{rung="%s"}' % r)
             for r in ("16", "64", "256")})


@pytest.mark.parametrize("shape,lanes,rung,blocks", [
    (_HIGGS, (128, 28), "256", 4),
    (_MSLR, (128, 32), "64", 5),
    (_EPSILON, (128, 32), "64", 63),
    ((137, 10_500_000, 256), (128, 128), "256", 18),
])
@pytest.mark.parametrize("site", ["placement", "pass"])
def test_operand_gauges_follow_the_shapes(shape, lanes, rung, blocks, site):
    """``hist_operand_lanes{what}`` and ``hist_pass_blocks{rung}``, set
    when a placement or a pass is traced, at the cells' own shapes (traced
    abstractly: nothing is allocated)."""
    F, rows, bins = shape
    matrix = jax.ShapeDtypeStruct((F, rows), jnp.uint8)
    default_registry().gauge(
        "hist_operand_lanes", "", label_names=("what",)).labels(
            what="live").set(-1.0)
    if site == "placement":
        made = jax.eval_shape(lambda b: prepare_hist_bins(b, bins), matrix)
        assert sum(int(np.prod(b.shape)) for b in made.blocks) == \
            prepared_bins_bytes(F, rows, bins)
    else:
        jax.eval_shape(
            lambda b, g, l: hist_leaves_pallas(b, g, l, 4, bins,
                                               precision="bf16x2"),
            matrix, jax.ShapeDtypeStruct((rows, 3), jnp.float32),
            jax.ShapeDtypeStruct((rows,), jnp.int32))
    got_lanes, got_blocks = _gauges()
    assert (got_lanes["stored"], got_lanes["live"]) == lanes
    assert got_blocks[rung] == blocks


def test_operand_gauges_of_the_parent_s_256_rung():
    """One array a block, the form the 256 rung had before the lane-dense
    operand: 8 of a row's 128 stored byte columns carry a feature."""
    _, tile_cols, nfb = _feature_blocks(28, 256, False)
    assert _block_windows(tile_cols, 256) == 16
    _count_operand(28, tile_cols, 1, nfb, 256)      # windows=1: the parent
    lanes, blocks = _gauges()
    assert (lanes["stored"], lanes["live"]) == (128, 8)
    assert blocks["256"] == 4


# ---------------------------------------------------------------------------
# a small booster at max_bin=255 against a plain numpy histogram GBDT
# ---------------------------------------------------------------------------

def _numpy_best_split(bins, g, h, rows, min_hess):
    """Best ``(gain, feature, bin)`` of the rows of one leaf: float64
    histograms by ``bincount``, every feature, every threshold."""
    G, H, n = g[rows].sum(), h[rows].sum(), len(rows)
    best = (0.0, -1, -1)
    for f in range(bins.shape[0]):
        b = bins[f, rows]
        gl = np.cumsum(np.bincount(b, weights=g[rows], minlength=B))[:-1]
        hl = np.cumsum(np.bincount(b, weights=h[rows], minlength=B))[:-1]
        cl = np.cumsum(np.bincount(b, minlength=B))[:-1]
        ok = (cl >= 1) & (n - cl >= 1) & (hl >= min_hess) \
            & (H - hl >= min_hess)
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = gl ** 2 / hl + (G - gl) ** 2 / (H - hl) - G ** 2 / H
        gain = np.where(ok, gain, -np.inf)
        t = int(np.argmax(gain))
        if gain[t] > best[0]:
            best = (float(gain[t]), f, t)
    return best


def _numpy_gbdt(bins, y, trees, num_leaves, rate, min_hess):
    """Best-first histogram GBDT on the binary log-loss, float64: per tree
    ``[(rows, value)]`` of its leaves and the gains of its splits."""
    p0 = y.mean()
    score = np.full(len(y), np.log(p0 / (1 - p0)))
    out = []
    for _ in range(trees):
        p = 1.0 / (1.0 + np.exp(-score))
        g, h = p - y, p * (1 - p)
        leaves = [np.arange(len(y))]
        cand = [_numpy_best_split(bins, g, h, leaves[0], min_hess)]
        gains = []
        while len(leaves) < num_leaves:
            i = int(np.argmax([c[0] for c in cand]))
            gain, f, t = cand[i]
            if f < 0:
                break
            rows = leaves[i]
            left = bins[f, rows] <= t
            leaves[i], new = rows[left], rows[~left]
            leaves.append(new)
            cand[i] = _numpy_best_split(bins, g, h, leaves[i], min_hess)
            cand.append(_numpy_best_split(bins, g, h, new, min_hess))
            gains.append(gain)
        tree = []
        for rows in leaves:
            value = -g[rows].sum() / h[rows].sum() * rate
            score[rows] += value
            tree.append((rows, value))
        out.append((tree, gains))
    return out, np.log(p0 / (1 - p0))


def test_booster_at_255_bins_matches_a_numpy_histogram_gbdt():
    """Three trees of a binary model at ``max_bin=255``, 28 columns, 4,096
    rows through ``lgb.train`` on the normal path (the kernel's 256 rung
    on the prepared lane-dense operand, through the interpreter) against
    a plain numpy GBDT on the program's own bins: the same leaves row for
    row, so leaf counts exact; leaf values and gains to float32 rounding
    of the bf16x2 histograms."""
    rng = np.random.RandomState(255)
    rows, F, leaves = 4096, 28, 7
    X = rng.randn(rows, F).astype(np.float32)
    score = (1.2 * X[:, 0] - X[:, 1] + 0.6 * X[:, 2] * X[:, 3]
             + 0.4 * X[:, 4] + rng.randn(rows))
    y = (score > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": leaves, "max_bin": 255,
              "learning_rate": 0.1, "min_data_in_leaf": 1,
              "min_sum_hessian_in_leaf": 20, "hist_method": "pallas",
              "verbosity": -1}
    booster = lgb.train(dict(params), lgb.Dataset(X, label=y),
                        num_boost_round=3)
    gbdt = booster._gbdt
    grow_binned = gbdt._grow_binned
    assert isinstance(grow_binned, HistBins)
    assert (grow_binned.tile_cols, grow_binned.windows) == (8, 16)
    assert [b.shape for b in grow_binned.blocks] == [(4096, 128)]
    assert int(gbdt.num_bins) == B
    bins = np.asarray(gbdt.binned)
    assert bins.dtype == np.uint8 and bins.shape == (F, rows)
    assert bins.max() >= 250                       # the rung's top bins

    want, init = _numpy_gbdt(bins, y, 3, leaves, 0.1, 20.0)
    leaf_of = np.asarray(booster.predict(X, pred_leaf=True))
    dump = booster.dump_model()["tree_info"]
    for k, (tree, gains) in enumerate(want):
        info = dump[k]
        stored_leaves, stored_gains = {}, []
        stack = [info["tree_structure"]]
        while stack:
            node = stack.pop()
            if "split_index" in node:
                stored_gains.append(node["split_gain"])
                stack += [node["left_child"], node["right_child"]]
            else:
                stored_leaves[node["leaf_index"]] = node
        assert len(stored_leaves) == len(tree) == leaves
        for rows_of, value in tree:
            mine = np.unique(leaf_of[rows_of, k])
            assert len(mine) == 1              # the same rows, one leaf
            node = stored_leaves[int(mine[0])]
            assert node["leaf_count"] == len(rows_of)
            assert (leaf_of[:, k] == mine[0]).sum() == len(rows_of)
            np.testing.assert_allclose(
                node["leaf_value"], value + (init if k == 0 else 0.0),
                rtol=2e-4, atol=2e-6)
        np.testing.assert_allclose(sorted(stored_gains), sorted(gains),
                                   rtol=1e-3)
