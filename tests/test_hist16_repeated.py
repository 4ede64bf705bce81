"""The histogram kernel's 16-bin rung on its repeated operand
(``hist_pallas._feature_blocks``): a feature block, unpacked at placement,
padded to a power of two of at least 8 features and repeated across the 128
lanes of a ``u8[n_pad, 128]`` array, so that a chunk of the one-hot is whole
vregs of the tile.  The benchmark's ``higgs-15b-train`` runs it at
10,500,000 x 28, packed.

The pass is held bit for bit to the library's XLA one-hot method on inputs
every precision sums exactly (multiples of 1/64 that bfloat16 holds, and
that int8's per-tile scale reproduces), the layout lane by lane, the bytes
and the form the bytes rule gives to the parent's arithmetic, and the
``hist_block_copies`` gauge to the shapes.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbmv1_tpu.obs.metrics import default_registry
from lightgbmv1_tpu.ops import hist_pallas
from lightgbmv1_tpu.ops.hist_pallas import (MAX_ROW_TILE, _feature_blocks,
                                            hist_bins_form,
                                            hist_leaves_pallas, pack4bit,
                                            prepare_hist_bins,
                                            prepared_bins_bytes)
from lightgbmv1_tpu.ops.histogram import hist_leaves_onehot

B = 16
N = 1500                    # no multiple of a row tile: an edge tile
FEATURES = [1, 7, 8, 9, 28, 29, 33, 64, 128, 137]
PRECISIONS = ["f32", "bf16", "bf16x2", "int8", "int8sr"]


@functools.lru_cache(maxsize=None)
def _bins(F):
    rng = np.random.RandomState(F)
    bins = rng.randint(0, B, size=(F, N)).astype(np.uint8)
    bins[:, :B] = np.arange(B, dtype=np.uint8)[None, :]     # every bin
    return bins


@functools.lru_cache(maxsize=None)
def _rows(slots):
    """``(k, label)``: integer gradient / hessian rows in [-127, 127] with
    +-127 in every 512th row (so every row tile of the pass holds one: the
    int8 path's per-tile scale is then exactly 1/64), a 0/1 count, and
    labels ``0..slots`` (``slots`` the wave's dead label) with a few far
    above it."""
    rng = np.random.RandomState(1000 + slots)
    k = rng.randint(-127, 128, size=(N, 3)).astype(np.float32)
    k[::512, :2] = 127.0
    k[1::512, :2] = -127.0
    k[:, 2] = rng.rand(N) < 0.9
    label = rng.randint(0, slots + 1, N).astype(np.int32)
    label[::13] = slots + 1000
    return k, label


@functools.lru_cache(maxsize=None)
def _reference(F, slots):
    """The XLA one-hot method's histogram of the integer rows: every sum
    is exact."""
    k, label = _rows(slots)
    return np.asarray(hist_leaves_onehot(
        jnp.asarray(_bins(F)), jnp.asarray(k), jnp.asarray(label), slots,
        B, precision="f32"))


@functools.lru_cache(maxsize=None)
def _operand(F, layout):
    bins = _bins(F)
    packed = layout == "packed4"
    return prepare_hist_bins(jnp.asarray(pack4bit(bins) if packed else bins),
                             B, packed)


@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("slots", [1, 4, 16, 63])
@pytest.mark.parametrize("layout", ["packed4", "u8"])
@pytest.mark.parametrize("F", FEATURES)
def test_repeated_pass_is_the_onehot_reference_bit_for_bit(F, layout, slots,
                                                          precision):
    """One block of 8 / 16 / 32 / 64 / 128 features, blocks part-filled
    (7, 9, 29, 33, 137), an odd count packed (a phantom nibble), two
    arrays (137 = 128 + 9): the pass equals the reference in every bit.
    ``int8sr`` takes the integers as they are and returns integer sums; the
    other precisions take them over 64."""
    k, label = _rows(slots)
    ref = _reference(F, slots).copy()
    g3 = k
    if precision != "int8sr":
        g3 = k.copy()
        g3[:, :2] /= 64.0
        ref[..., :2] /= 64.0
    got = np.asarray(hist_leaves_pallas(
        _operand(F, layout), jnp.asarray(g3), jnp.asarray(label), slots, B,
        precision=precision, interpret=True, packed=layout == "packed4",
        num_features=F))
    assert got.shape == (slots, F, B, 3)
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("layout", ["packed4", "u8"])
@pytest.mark.parametrize("F", FEATURES)
def test_the_repeated_layout_lane_by_lane(monkeypatch, F, layout):
    """Array ``a``'s lane l holds feature ``a * fblk + l % fblk``; lanes
    past the stored features hold 255 (no bin below 16), a packed odd
    count's phantom nibble 0.  Made in steps of rows that do not divide
    the padded rows, so the last step is clamped back onto the rows' end
    and lays some rows out twice."""
    monkeypatch.setattr(hist_pallas, "_LAYOUT_ROWS", 1536)
    bins = _bins(F)
    packed = layout == "packed4"
    matrix = jnp.asarray(pack4bit(bins) if packed else bins)
    made = prepare_hist_bins(matrix, B, packed)
    stored = 2 * matrix.shape[0] if packed else F      # features, unpacked
    fblk, tile_cols, nfb = _feature_blocks(matrix.shape[0], B, packed)
    assert fblk == max(8, 1 << (min(stored, 128) - 1).bit_length())
    assert (tile_cols, made.tile_cols, made.windows) == (128, 128, 1)
    assert made.matrix is matrix
    assert [b.shape for b in made.blocks] == [(2 * MAX_ROW_TILE, 128)] * nfb
    for a, block in enumerate(made.blocks):
        block = np.asarray(block)[:N]
        feature = a * fblk + np.arange(128) % fblk
        for lane, f in enumerate(feature):
            want = bins[f] if f < F else (0 if f < stored else 255)
            np.testing.assert_array_equal(block[:, lane], want)


@pytest.mark.parametrize("packed", [False, True])
def test_bytes_and_form_are_the_parent_s(packed):
    """The repeated form stores what the parent's blocks stored at every
    16-rung shape: one ``u8[n_pad, 128]`` array a block of up to 128
    features (64 packed bytes), so ``prepared_bins_bytes`` and the bytes
    rule's answer are the parent's, written out here."""
    budget = 16_909_336_064 // 4
    for stored in range(1, 301):
        if packed:          # the parent's blocks: 64 bytes of 128 nibbles
            cols = max(2, min(2 * stored, 128) & ~1) // 2
        else:
            cols = min(stored, 128)
        blocks = -(-stored // cols)
        for rows in (1, 1500, 10_500_000, 26_562_500):
            n_pad = -(-rows // MAX_ROW_TILE) * MAX_ROW_TILE
            parent = blocks * n_pad * 128
            assert prepared_bins_bytes(stored, rows, B, packed) == parent
            assert hist_bins_form(stored, rows, B, packed, budget) == (
                "block" if parent <= budget else "raw", {"block": parent})
    assert prepared_bins_bytes(14, 10_500_000, B, True) == 1_344_012_288


def _gauges(rung):
    snap = default_registry().snapshot()
    return (snap.get('hist_block_copies{rung="%s"}' % rung),
            snap.get('hist_operand_lanes{what="live"}'))


@pytest.mark.parametrize("site", ["placement", "pass"])
@pytest.mark.parametrize("F,bins,packed,dense,copies,live", [
    (28, 16, True, False, 4, 28),        # higgs-15b-train
    (28, 16, False, False, 4, 28),
    (8, 16, False, False, 16, 8),
    (5, 16, True, False, 16, 6),         # a phantom nibble of 3 bytes
    (128, 16, False, False, 1, 128),
    (137, 16, False, False, 1, 128),
    (67, 64, False, False, 1, 32),       # the 64 rung's block form
    (67, 64, False, True, 1, 67),        # and its lane-dense form
    (28, 256, False, False, 1, 28),      # the 256 rung
])
def test_hist_block_copies(site, F, bins, packed, dense, copies, live):
    """``hist_block_copies{rung}``, set where the operand is counted, when
    a placement or a pass is traced (abstractly: nothing is allocated):
    the copies of one feature block a stored row holds, beside the
    distinct features ``hist_operand_lanes{what="live"}`` counts."""
    rows = 10_500_000
    stored = -(-F // 2) if packed else F
    matrix = jax.ShapeDtypeStruct((stored, rows), jnp.uint8)
    rung = str(hist_pallas.kernel_width(bins))
    default_registry().gauge("hist_block_copies", "",
                             label_names=("rung",)).labels(rung=rung).set(-1)
    made = jax.eval_shape(
        lambda b: prepare_hist_bins(b, bins, packed, dense=dense), matrix)
    if site == "pass":
        default_registry().gauge(
            "hist_block_copies", "", label_names=("rung",)).labels(
                rung=rung).set(-1)
        jax.eval_shape(
            lambda b, g, l: hist_leaves_pallas(b, g, l, 4, bins,
                                               precision="bf16x2",
                                               packed=packed,
                                               num_features=F),
            made, jax.ShapeDtypeStruct((rows, 3), jnp.float32),
            jax.ShapeDtypeStruct((rows,), jnp.int32))
    assert _gauges(rung) == (copies, live)
