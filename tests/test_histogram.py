"""Histogram implementation equality tests — the analog of the reference's
GPU/CPU comparator (gpu_tree_learner.cpp:71-98 CompareHistograms)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbmv1_tpu.ops.histogram import (
    hist_leaves_onehot,
    hist_leaves_scatter,
    hist_one_leaf,
)


def make_inputs(rng, N=1000, F=5, B=16, L=4):
    binned = jnp.asarray(rng.randint(0, B, size=(F, N)).astype(np.uint8))
    g3 = jnp.asarray(rng.randn(N, 3).astype(np.float32))
    leaf_id = jnp.asarray(rng.randint(0, L, size=N).astype(np.int32))
    return binned, g3, leaf_id


def numpy_hist(binned, g3, leaf_id, L, B):
    binned, g3, leaf_id = map(np.asarray, (binned, g3, leaf_id))
    F, N = binned.shape
    out = np.zeros((L, F, B, 3), np.float64)
    for n in range(N):
        for f in range(F):
            out[leaf_id[n], f, binned[f, n]] += g3[n]
    return out


def test_scatter_matches_numpy(rng):
    binned, g3, leaf_id = make_inputs(rng, N=300, F=3, B=8, L=3)
    expect = numpy_hist(binned, g3, leaf_id, 3, 8)
    got = hist_leaves_scatter(binned, g3, leaf_id, 3, 8)
    np.testing.assert_allclose(np.asarray(got), expect, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("precision", ["f32", "bf16x2"])
def test_onehot_matches_scatter(rng, precision):
    binned, g3, leaf_id = make_inputs(rng, N=2000, F=6, B=32, L=5)
    ref = hist_leaves_scatter(binned, g3, leaf_id, 5, 32)
    got = hist_leaves_onehot(binned, g3, leaf_id, 5, 32, precision=precision,
                             row_chunk=512)
    rtol = 1e-4 if precision == "f32" else 3e-3
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=rtol, atol=1e-2)


def test_onehot_bf16_precision_hierarchy(rng):
    """bf16x2 must be strictly more accurate than bf16."""
    binned, g3, leaf_id = make_inputs(rng, N=4000, F=4, B=16, L=2)
    ref = np.asarray(hist_leaves_scatter(binned, g3, leaf_id, 2, 16))
    err16 = np.abs(np.asarray(
        hist_leaves_onehot(binned, g3, leaf_id, 2, 16, precision="bf16")) - ref).max()
    err16x2 = np.abs(np.asarray(
        hist_leaves_onehot(binned, g3, leaf_id, 2, 16, precision="bf16x2")) - ref).max()
    assert err16x2 < err16


def test_count_channel_exact(rng):
    """Counts (channel 2 with unit weights) must be exactly integral."""
    binned, g3, leaf_id = make_inputs(rng, N=5000, F=3, B=16, L=4)
    g3 = g3.at[:, 2].set(1.0)
    got = np.asarray(hist_leaves_onehot(binned, g3, leaf_id, 4, 16, precision="bf16x2"))
    counts = got[..., 2]
    np.testing.assert_array_equal(counts, np.round(counts))
    assert counts.sum() == 5000 * 3  # every row counted once per feature


def test_hist_one_leaf_masks_rows(rng):
    binned, g3, leaf_id = make_inputs(rng, N=500, F=4, B=8, L=3)
    full = np.asarray(hist_leaves_scatter(binned, g3, leaf_id, 3, 8))
    one = np.asarray(hist_one_leaf(binned, g3, leaf_id, jnp.asarray(1), 8))
    np.testing.assert_allclose(one, full[1], rtol=1e-5, atol=1e-5)


def test_padded_rows_dropped(rng):
    """onehot path pads rows to the chunk size; padding must not leak."""
    binned, g3, leaf_id = make_inputs(rng, N=777, F=2, B=8, L=3)
    ref = hist_leaves_scatter(binned, g3, leaf_id, 3, 8)
    got = hist_leaves_onehot(binned, g3, leaf_id, 3, 8, precision="f32", row_chunk=256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Pallas kernel equality vs the scatter oracle (interpret mode on CPU; the
# same tests run against real hardware when a TPU backend is present) —
# the CompareHistograms analog for the Pallas path.
# ---------------------------------------------------------------------------

_PALLAS_INTERPRET = jax.default_backend() != "tpu"


@pytest.mark.parametrize("precision", ["f32", "bf16x2", "bf16", "int8"])
def test_pallas_matches_scatter(rng, precision):
    from lightgbmv1_tpu.ops.hist_pallas import hist_leaves_pallas

    N, F, B, L = 1777, 6, 32, 5   # non-divisible N exercises row padding
    binned, g3, leaf_id = make_inputs(rng, N=N, F=F, B=B, L=L)
    g3 = g3.at[:, 2].set(1.0)     # count channel carries the 0/1 row mask
    ref = np.asarray(hist_leaves_scatter(binned, g3, leaf_id, L, B))
    got = np.asarray(hist_leaves_pallas(
        binned, g3, leaf_id, L, B, precision=precision,
        interpret=_PALLAS_INTERPRET))
    # counts are exact in every mode (int8 uses a power-of-two count scale)
    np.testing.assert_array_equal(got[..., 2], ref[..., 2])
    if precision == "f32":
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)
    elif precision == "bf16x2":
        np.testing.assert_allclose(got, ref, rtol=1e-2, atol=1e-2)
    else:  # single-pass bf16 / quantized int8: coarse but bounded
        assert np.abs(got - ref).max() < 0.5
        np.testing.assert_allclose(got.sum((0, 2)), ref.sum((0, 2)),
                                   rtol=5e-2, atol=5e-1)


@pytest.mark.parametrize("F", [6, 7])   # odd F exercises the phantom nibble
def test_pallas_packed_4bit_matches_scatter(rng, F):
    """4-bit packed bins (reference DenseBin<..,IS_4BIT>, dense_bin.hpp:52):
    the packed kernel must reproduce the unpacked histograms exactly."""
    from lightgbmv1_tpu.ops.hist_pallas import hist_leaves_pallas, pack4bit

    N, B, L = 1234, 16, 5
    binned, g3, leaf_id = make_inputs(rng, N=N, F=F, B=B, L=L)
    g3 = g3.at[:, 2].set(1.0)
    ref = np.asarray(hist_leaves_scatter(binned, g3, leaf_id, L, B))
    packed = jnp.asarray(pack4bit(np.asarray(binned)))
    got = np.asarray(hist_leaves_pallas(
        packed, g3, leaf_id, L, B, precision="f32",
        interpret=_PALLAS_INTERPRET, packed=True, num_features=F))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(got[..., 2], ref[..., 2])


def test_pallas_feature_padding_and_big_bins(rng):
    """F not a multiple of the feature block and B=256 (max uint8 bins)."""
    from lightgbmv1_tpu.ops.hist_pallas import hist_leaves_pallas

    N, F, B, L = 513, 3, 256, 2
    binned, g3, leaf_id = make_inputs(rng, N=N, F=F, B=B, L=L)
    ref = np.asarray(hist_leaves_scatter(binned, g3, leaf_id, L, B))
    got = np.asarray(hist_leaves_pallas(
        binned, g3, leaf_id, L, B, precision="f32",
        interpret=_PALLAS_INTERPRET))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_pallas_rejects_int16_bins(rng):
    from lightgbmv1_tpu.ops.hist_pallas import hist_leaves_pallas

    binned = jnp.zeros((2, 64), jnp.int16)
    g3 = jnp.zeros((64, 3), jnp.float32)
    leaf = jnp.zeros(64, jnp.int32)
    with pytest.raises(ValueError, match="uint8"):
        hist_leaves_pallas(binned, g3, leaf, 2, 300,
                           interpret=_PALLAS_INTERPRET)


def test_pallas_single_leaf_masks_rows(rng):
    """hist_one_leaf through the pallas method (the leafwise smaller-child
    pass) must equal the scatter slice."""
    binned, g3, leaf_id = make_inputs(rng, N=700, F=4, B=16, L=3)
    # the count channel is a 0/1 row mask in every caller; hist_one_leaf's
    # own precision (bf16x2) gives it no lo term
    g3 = g3.at[:, 2].set((rng.rand(700) < 0.8).astype(np.float32))
    full = np.asarray(hist_leaves_scatter(binned, g3, leaf_id, 3, 16))
    import lightgbmv1_tpu.ops.hist_pallas as hp
    import functools
    orig = hp.hist_leaves_pallas
    patched = functools.partial(orig, interpret=_PALLAS_INTERPRET,
                                precision="f32")
    hp.hist_leaves_pallas = patched
    try:
        one = np.asarray(hist_one_leaf(binned, g3, leaf_id, jnp.asarray(2), 16,
                                       method="pallas"))
    finally:
        hp.hist_leaves_pallas = orig
    np.testing.assert_allclose(one, full[2], rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# prepared bins (hist_pallas.HistBins): the kernel's operand laid out once
# ---------------------------------------------------------------------------

# (num_bins, packed, F): F leaves the last feature block part-filled
# (blocks of 128 / 32 / 8 features at 16 / 64 / 256 bins)
_PREPARED_SHAPES = [(16, False, 130), (16, True, 131), (64, False, 37),
                    (256, False, 11)]


@pytest.mark.parametrize("precision", ["bf16x2", "bf16", "int8sr"])
@pytest.mark.parametrize("slots", [1, 5, 17, 64, 128])
@pytest.mark.parametrize("num_bins,packed,F", _PREPARED_SHAPES)
def test_prepared_bins_bit_identical(num_bins, packed, F, slots, precision):
    """A prepared operand and the raw matrix give the SAME bits: the layout
    is the same function run once instead of in the pass, and the rows it
    pads beyond the pass's own tile carry zero g3.  N is no multiple of
    1024, so the 128-slot pass (512-row tiles) sees one all-padding tile
    more through the prepared operand than through the raw matrix."""
    from lightgbmv1_tpu.ops.hist_pallas import (HistBins, MAX_ROW_TILE,
                                                hist_leaves_pallas, pack4bit,
                                                prepare_hist_bins)

    rng = np.random.RandomState(num_bins + slots)
    N = 1500
    bins = rng.randint(0, num_bins, size=(F, N)).astype(np.uint8)
    matrix = jnp.asarray(pack4bit(bins) if packed else bins)
    if precision == "int8sr":       # rows arrive pre-quantized
        g3 = rng.randint(-127, 128, size=(N, 3)).astype(np.float32)
    else:
        g3 = rng.randn(N, 3).astype(np.float32)
    leaf = jnp.asarray(rng.randint(0, slots, N).astype(np.int32))
    kw = dict(precision=precision, interpret=_PALLAS_INTERPRET,
              packed=packed, num_features=F)
    prepared = prepare_hist_bins(matrix, num_bins, packed)
    assert isinstance(prepared, HistBins) and prepared.matrix is matrix
    assert all(b.shape == (2 * MAX_ROW_TILE, 128) for b in prepared.blocks)
    raw = hist_leaves_pallas(matrix, jnp.asarray(g3), leaf, slots, num_bins,
                             **kw)
    got = hist_leaves_pallas(prepared, jnp.asarray(g3), leaf, slots,
                             num_bins, **kw)
    assert raw.shape == (slots, F, num_bins, 3)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(raw))


def test_prepared_bins_refuse_another_pass(rng):
    """Bins prepared for one kernel width do not fit a pass of another."""
    from lightgbmv1_tpu.ops.hist_pallas import (hist_leaves_pallas,
                                                prepare_hist_bins)

    binned, g3, leaf_id = make_inputs(rng, N=300, F=40, B=16, L=2)
    prepared = prepare_hist_bins(binned, 64)       # blocks of 32 features
    with pytest.raises(ValueError, match="do not fit this pass"):
        hist_leaves_pallas(prepared, g3, leaf_id, 2, 16,
                           interpret=_PALLAS_INTERPRET)


@pytest.mark.parametrize("grower,params", [
    ("wave", {"num_leaves": 15}),
    ("levelwise", {"num_leaves": 15, "tree_growth": "levelwise"}),
    ("leafwise", {"num_leaves": 7}),      # auto wave size 1: sequential
])
def test_prepared_bins_train_same_model(monkeypatch, grower, params):
    """Boosters on the prepared operand (the serial learner's placement)
    dump the same model text as on the parent's path, where every pass
    lays the raw matrix out: forced here through the bytes rule."""
    import lightgbmv1_tpu as lgb
    from lightgbmv1_tpu.ops.hist_pallas import HistBins
    from lightgbmv1_tpu.parallel import trainer

    rng = np.random.RandomState(3)
    X = rng.randn(1300, 9)
    y = (X[:, 0] - 0.7 * X[:, 1] + 0.3 * rng.randn(1300) > 0).astype(float)
    p = {"objective": "binary", "verbosity": -1, "seed": 11,
         "hist_method": "pallas", "min_data_in_leaf": 5, **params}

    def train():
        bst = lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=3)
        return bst, bst.model_to_string()

    a, text_prepared = train()
    assert isinstance(a._gbdt._grow_binned, HistBins)
    assert a._gbdt.binned.shape == (9, 1300)
    monkeypatch.setattr(trainer, "_hist_bins_budget", lambda: 0)
    b, text_raw = train()
    assert not isinstance(b._gbdt._grow_binned, HistBins)
    assert text_prepared == text_raw


# ---------------------------------------------------------------------------
# the wave pass's MXU left operand: only rows that carry information
# ---------------------------------------------------------------------------

_WAVE_PRECISIONS = ["bf16", "bf16x2", "f32", "int8", "int8sr"]


def _wave_inputs(layout, slots, precision, N=1500, seed=0):
    """``(binned, bins, g3, label, num_bins, kw)`` of a wave pass: labels
    ``0..slots-1`` are live, ``slots`` is the wave's dead label, and a few
    rows carry a label far above it.  ``N`` is no multiple of a row tile."""
    from lightgbmv1_tpu.ops.hist_pallas import pack4bit, prepare_hist_bins

    rng = np.random.RandomState(1000 * seed + slots)
    packed = layout == "packed4"
    B, F = (16, 11) if packed else (64, 37)      # 64 bins: two blocks of 32
    bins = rng.randint(0, B, size=(F, N)).astype(np.uint8)
    binned = jnp.asarray(pack4bit(bins) if packed else bins)
    if layout == "prepared":
        binned = prepare_hist_bins(binned, B)
    if precision == "int8sr":        # rows arrive pre-quantized
        g3 = rng.randint(-127, 128, size=(N, 3)).astype(np.float32)
    elif precision == "f32":
        # on a 2^-6 grid every partial sum is exact: XLA:CPU's f32 matmul
        # (the interpreter's) blocks by the operands' shapes, the MXU does not
        g3 = (rng.randint(-256, 257, size=(N, 3)) / 64.0).astype(np.float32)
    else:
        g3 = rng.randn(N, 3).astype(np.float32)
    g3[:, 2] = rng.rand(N) < 0.9                 # the count: a 0/1 row mask
    label = rng.randint(0, slots + 1, N).astype(np.int32)
    label[::13] = slots + 1000
    kw = dict(precision=precision, packed=packed, num_features=F,
              interpret=_PALLAS_INTERPRET)
    return binned, bins, jnp.asarray(g3), label, B, kw


@pytest.mark.parametrize("precision", _WAVE_PRECISIONS)
@pytest.mark.parametrize("slots", [1, 4, 16, 63])
@pytest.mark.parametrize("layout", ["prepared", "raw", "packed4"])
def test_wave_pass_bit_equal_to_dead_slot_form(layout, slots, precision):
    """``hist_wave`` on the Pallas method asks the kernel for the live
    slots alone.  The parent's form, written out here — one slot more for
    the dead rows, sliced away — gives the same bits on the same row tile:
    an output row is its own f32 dot products, whatever other rows share
    the product; bf16x2's count row is its hi product alone.  Against the
    scatter oracle: each precision's bound, counts exact."""
    from lightgbmv1_tpu.ops.histogram import hist_wave
    from lightgbmv1_tpu.ops.hist_pallas import (_feature_blocks,
                                                _row_tile_for, bin_matrix,
                                                hist_leaves_pallas,
                                                pass_rows)

    binned, bins, g3, label, B, kw = _wave_inputs(layout, slots, precision)
    got = hist_wave(binned, g3, jnp.asarray(label), slots, B,
                    method="pallas", **kw)
    assert got.shape == (slots, bins.shape[0], B, 3)
    fblk = _feature_blocks(bin_matrix(binned).shape[0], B, kw["packed"])[0]
    tile = _row_tile_for(pass_rows(slots, precision)[2], fblk * B)
    parent = hist_leaves_pallas(
        binned, g3, jnp.asarray(np.minimum(label, slots)), slots + 1, B,
        row_tile=tile, **kw)[:slots]
    got, parent = np.asarray(got), np.asarray(parent)
    np.testing.assert_array_equal(got, parent)
    np.testing.assert_array_equal(np.signbit(got), np.signbit(parent))

    live = label < slots
    ref = np.asarray(hist_leaves_scatter(
        jnp.asarray(bins[:, live]), g3[live], jnp.asarray(label[live]),
        slots, B))
    np.testing.assert_array_equal(got[..., 2], ref[..., 2])
    if precision in ("f32", "int8sr"):           # exact sums by construction
        np.testing.assert_array_equal(got, ref)
    elif precision == "bf16x2":
        np.testing.assert_allclose(got, ref, rtol=1e-2, atol=1e-2)
    else:       # single-pass bf16 / quantized int8: coarse but bounded
        assert np.abs(got - ref).max() < 0.5
        np.testing.assert_allclose(got.sum((0, 2)), ref.sum((0, 2)),
                                   rtol=5e-2, atol=5e-1)


@pytest.mark.parametrize("precision", ["bf16x2", "bf16", "int8sr"])
@pytest.mark.parametrize("slots", [8, 16, 64])
def test_wave_dead_rows_add_nothing(slots, precision):
    """Where the live slots fill the operand to its last row (a multiple
    of 8), rows labelled ``slots``, rows labelled far above it and the row
    padding still land nowhere: whatever values they carry, no bit of any
    live slot's histogram moves, and the sums are the live rows' alone."""
    from lightgbmv1_tpu.ops.histogram import hist_wave

    binned, bins, g3, label, B, kw = _wave_inputs("raw", slots, precision)
    dead = jnp.asarray(label >= slots)[:, None]
    lab = jnp.asarray(label)
    loud = hist_wave(binned, jnp.where(dead, 100.0, g3), lab, slots, B,
                     method="pallas", **kw)
    quiet = hist_wave(binned, jnp.where(dead, 0.0, g3), lab, slots, B,
                      method="pallas", **kw)
    np.testing.assert_array_equal(np.asarray(loud), np.asarray(quiet))
    live = label < slots
    ref = np.asarray(hist_leaves_scatter(
        jnp.asarray(bins[:, live]), g3[live], jnp.asarray(label[live]),
        slots, B))
    np.testing.assert_array_equal(np.asarray(loud)[..., 2], ref[..., 2])


@pytest.mark.parametrize("slots,precision,mxu_rows,live_rows", [
    (1, "bf16x2", 16, 5), (4, "bf16x2", 32, 20), (16, "bf16x2", 80, 80),
    (63, "bf16", 192, 189), (63, "int8sr", 192, 189), (1, "f32", 8, 3)])
def test_pass_rows_gauges(slots, precision, mxu_rows, live_rows):
    """``hist_pass_mxu_rows`` / ``hist_pass_live_rows``: the left operand's
    rows as padded and as they carry data, set when a pass is traced."""
    from lightgbmv1_tpu.obs.metrics import default_registry
    from lightgbmv1_tpu.ops.histogram import hist_wave

    default_registry().reset(["hist_pass_mxu_rows", "hist_pass_live_rows"])
    binned, _, g3, label, B, kw = _wave_inputs("raw", slots, precision, N=64)
    jax.jit(lambda b, g, l: hist_wave(b, g, l, slots, B, method="pallas",
                                      **kw)).lower(
        binned, g3, jnp.asarray(label))
    snap = default_registry().snapshot()
    labels = '{slots="%d",precision="%s"}' % (slots, precision)
    assert snap["hist_pass_mxu_rows" + labels] == mxu_rows
    assert snap["hist_pass_live_rows" + labels] == live_rows


# (num_bins, F, dense): the cells' operands and their lanes a call,
# fblk x bins: higgs-15b-train 32 x 16 = 512 (the 16 rung's repeated
# block); mslr-train, epsilon-train and criteo-dp4-train 32 x 64 = 2,048
# (the block form); criteo-tall-train 32 x 64 (lane-dense); higgs-255b-train
# 8 x 256 = 2,048
_CELL_OPERANDS = [(16, 28, False), (64, 37, False), (64, 67, True),
                  (256, 28, True)]


@pytest.mark.parametrize("slots,precision,tiles", [
    (1, "bf16x2", (1024, 1024)), (4, "bf16x2", (1024, 1024)),
    (16, "bf16x2", (1024, 1024)), (63, "bf16", (1024, 1024)),
    (63, "bf16x2", (1024, 1024)), (63, "int8sr", (1024, 1024)),
    (128, "bf16", (512, 512)), (255, "bf16", (512, 256))])
@pytest.mark.parametrize("num_bins,F,dense", _CELL_OPERANDS)
def test_one_row_tile_rule_for_every_rung(num_bins, F, dense, slots,
                                          precision, tiles):
    """``_row_tile_for`` is one VMEM budget on every rung and form: 1024
    rows a grid step for every slot bucket the cells run, 512 only where
    the estimate at 1024 passes the budget (128 slots and more), 256 where
    it passes it at 512 too (255 slots at 2,048 lanes; ``tiles`` is the
    tile at 512 lanes, then at 2,048).  A traced pass reads the tile it
    took in ``hist_pass_row_tile{rung,slots,precision}``."""
    from lightgbmv1_tpu.obs.metrics import default_registry
    from lightgbmv1_tpu.ops.hist_pallas import (_feature_blocks,
                                                _row_tile_for,
                                                hist_leaves_pallas,
                                                kernel_width, pass_rows,
                                                prepare_hist_bins)

    fblk = _feature_blocks(F, num_bins, False, dense)[0]
    lanes = fblk * num_bins
    tile = dict(zip((512, 2048), tiles))[lanes]
    assert _row_tile_for(pass_rows(slots, precision)[2], lanes) == tile

    N = 3000
    default_registry().reset(["hist_pass_row_tile"])
    prepared = jax.eval_shape(
        lambda b: prepare_hist_bins(b, num_bins, dense=dense),
        jax.ShapeDtypeStruct((F, N), jnp.uint8))
    jax.eval_shape(lambda b, g, l: hist_leaves_pallas(
        b, g, l, slots, num_bins, precision=precision,
        interpret=_PALLAS_INTERPRET),
        prepared, jax.ShapeDtypeStruct((N, 3), jnp.float32),
        jax.ShapeDtypeStruct((N,), jnp.int32))
    labels = '{rung="%d",slots="%d",precision="%s"}' % (
        kernel_width(num_bins), slots, precision)
    assert default_registry().snapshot()["hist_pass_row_tile" + labels] \
        == tile


# sha256 of the model text the PARENT of PR 29 (commit 8bc3106: the kernel
# asked for one dead slot more, slots padded to 8, six rows a slot under
# bf16x2) dumps for the problem below, from a run of that commit on this
# installation's CPU interpreter
_PARENT_MODEL_TEXT = {
    "wave": "9a63b072d933c77a99ba11bae85fc187be519f2a9305484c60e18a6f2db1c06a",
    "levelwise":
        "8f4db26cee56705cd29575f686d56998164b8211d07061fa78a60ea5d58eaa92",
    "leafwise":
        "fa2595c8529043c815fdae89b70c248c7dec1d03ce1a40d0fc3f13d6c1ac1005",
}


@pytest.mark.parametrize("grower,params", [
    ("wave", {"num_leaves": 63}),
    ("levelwise", {"num_leaves": 15, "tree_growth": "levelwise"}),
    ("leafwise", {"num_leaves": 7}),      # auto wave size 1: sequential
])
def test_growers_dump_the_parents_model_text(monkeypatch, grower, params):
    """No result bit changed: on 2,600 rows (three row tiles of 1024) and
    the 4 / 16 / K slot ladder the three growers dump, byte for byte, the
    model text of the parent commit."""
    import hashlib

    import lightgbmv1_tpu as lgb
    from lightgbmv1_tpu.models import grower_wave as gw

    monkeypatch.setattr(gw, "_BUCKET_MIN_N", 1)
    rng = np.random.RandomState(5)
    X = rng.randn(2600, 9)
    y = (X[:, 0] - 0.7 * X[:, 1] + 0.4 * X[:, 2] * X[:, 3]
         + 0.3 * rng.randn(2600) > 0).astype(float)
    p = {"objective": "binary", "verbosity": -1, "seed": 11, "max_bin": 63,
         "hist_method": "pallas", "min_data_in_leaf": 5, **params}
    text = lgb.train(p, lgb.Dataset(X, label=y),
                     num_boost_round=3).model_to_string()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        _PARENT_MODEL_TEXT[grower]
