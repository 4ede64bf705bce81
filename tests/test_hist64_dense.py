"""The histogram kernel's 64-bin rung on the lane-dense bin operand
(``hist_pallas.HistBins`` with ``windows`` 4): the form the bytes rule
gives a table whose one-array-a-block operand is over a quarter of the
device (``criteo-tall-train``: 26,562,500 x 67 at 63 bins, 10.2 GB as
blocks, 3.4 GB lane-dense).

The operand stores the matrix's byte columns side by side, 128 an array,
and a feature block of a pass is a static window of 32 of them.  The tests
hold it to the scatter oracle and, bit for bit, to the block form through
the kernel (interpreter); the rule's one function to the five cells'
shapes; and boosters driven into the form through the rule's budget to the
block form's trees, field for field, and to the plain numpy histogram GBDT
of ``test_hist256.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbmv1_tpu as lgb
from lightgbmv1_tpu.obs.metrics import default_registry
from lightgbmv1_tpu.ops.hist_pallas import (MAX_ROW_TILE, HistBins,
                                            hist_bins_form,
                                            hist_leaves_pallas,
                                            prepare_hist_bins,
                                            prepared_bins_bytes)
from lightgbmv1_tpu.ops.histogram import hist_leaves_scatter
from lightgbmv1_tpu.parallel import trainer

B = 64
N = 1500                         # no multiple of a row tile
V5E_QUARTER = 16_909_336_064 // 4


def _inputs(F, slots, precision):
    rng = np.random.RandomState(11 * F + slots)
    bins = rng.randint(0, B - 1, size=(F, N)).astype(np.uint8)   # max_bin=63
    bins[:, :B] = np.arange(B, dtype=np.uint8)                   # every bin
    if precision == "f32":
        # on a 2^-6 grid every partial sum is exact, whatever way the
        # interpreter's f32 matmul blocks them (PR 29)
        g3 = (rng.randint(-256, 257, size=(N, 3)) / 64.0).astype(np.float32)
    else:
        g3 = rng.randn(N, 3).astype(np.float32)
    g3[:, 2] = rng.rand(N) < 0.9                    # the count: a 0/1 mask
    label = rng.randint(0, slots + 1, N).astype(np.int32)   # slots: dead
    return bins, jnp.asarray(g3), label


@pytest.mark.parametrize("precision", ["f32", "bf16", "bf16x2", "int8"])
@pytest.mark.parametrize("slots", [1, 4, 16, 63])
@pytest.mark.parametrize("F", [67, 137])
def test_dense_64_operand_against_oracle_and_block_form(F, slots, precision):
    """67 columns (one stored array, a last window of 3 live columns) and
    137 (two arrays, the second's first window 9 live columns): the same
    bits as one array a block, and the oracle's sums."""
    bins, g3, label = _inputs(F, slots, precision)
    matrix = jnp.asarray(bins)
    dense = prepare_hist_bins(matrix, B, dense=True)
    block = prepare_hist_bins(matrix, B)
    assert isinstance(dense, HistBins) and dense.matrix is matrix
    assert (dense.tile_cols, dense.windows) == (32, 4)
    assert (block.tile_cols, block.windows) == (32, 1)
    assert [b.shape for b in dense.blocks] == \
        [(2 * MAX_ROW_TILE, 128)] * -(-F // 128)
    assert len(block.blocks) == -(-F // 32)
    stored = np.concatenate([np.asarray(b) for b in dense.blocks], axis=1)
    np.testing.assert_array_equal(stored[:N, :F], bins.T)
    assert (stored[:N, F:] == 255).all() and (stored[N:] == 255).all()

    kw = dict(precision=precision, interpret=True)
    got = np.asarray(hist_leaves_pallas(dense, g3, jnp.asarray(label),
                                        slots, B, **kw))
    parent = np.asarray(hist_leaves_pallas(block, g3, jnp.asarray(label),
                                           slots, B, **kw))
    assert got.shape == (slots, F, B, 3)
    np.testing.assert_array_equal(got, parent)
    np.testing.assert_array_equal(np.signbit(got), np.signbit(parent))

    live = label < slots
    ref = np.asarray(hist_leaves_scatter(
        jnp.asarray(bins[:, live]), g3[live], jnp.asarray(label[live]),
        slots, B))
    np.testing.assert_array_equal(got[..., 2], ref[..., 2])
    if precision == "f32":
        np.testing.assert_array_equal(got, ref)
    elif precision == "bf16x2":
        np.testing.assert_allclose(got, ref, rtol=1e-2, atol=1e-2)
    else:       # single-pass bf16 / quantized int8: coarse but bounded
        assert np.abs(got - ref).max() < 0.5
        np.testing.assert_allclose(got.sum((0, 2)), ref.sum((0, 2)),
                                   rtol=5e-2, atol=5e-1)


def test_a_pass_reads_the_form_off_the_operand():
    """The 64 rung has two forms: a pass takes either as it is handed, a
    raw matrix in the block form, and refuses an operand of another rung."""
    bins, g3, label = _inputs(67, 4, "bf16x2")
    label = jnp.asarray(label)
    kw = dict(precision="bf16x2", interpret=True)
    raw = hist_leaves_pallas(jnp.asarray(bins), g3, label, 4, B, **kw)
    dense = hist_leaves_pallas(
        prepare_hist_bins(jnp.asarray(bins), B, dense=True), g3, label, 4,
        B, **kw)
    np.testing.assert_array_equal(np.asarray(raw), np.asarray(dense))
    with pytest.raises(ValueError, match="do not fit this pass"):
        hist_leaves_pallas(
            prepare_hist_bins(jnp.asarray(bins), B, dense=True), g3, label,
            4, 256, **kw)
    # the 16 rung's blocks fill an array's lanes: it has no second form
    narrow = prepare_hist_bins(jnp.asarray(bins % 16), 16, dense=True)
    assert (narrow.tile_cols, narrow.windows) == (128, 1)


# (stored columns, rows a device, bins) of the benchmark's five cells
_CELLS = {
    "mslr-train": (137, 2_270_296, 64),
    "criteo-dp4-train": (67, 4_000_000, 64),
    "epsilon-train": (2000, 400_000, 64),
    "higgs-255b-train": (28, 10_500_000, 256),
    "criteo-tall-train": (67, 26_562_500, 64),
}


@pytest.mark.parametrize("cell,form,held", [
    ("mslr-train", "block", 1_453_588_480),
    ("criteo-dp4-train", "block", 1_536_294_912),
    ("epsilon-train", "block", 3_228_696_576),
    ("higgs-255b-train", "dense", 1_344_012_288),
    ("criteo-tall-train", "dense", 3_400_007_680),
])
def test_the_rule_at_the_cells_shapes(cell, form, held):
    """At the v5e's budget the four accepted cells keep the parent's form
    (the 256 rung has the lane-dense one only) and the tall table takes the
    lane-dense form: its blocks would be 2.4x the rule."""
    got, need = hist_bins_form(*_CELLS[cell], False, V5E_QUARTER)
    assert got == form and need[form] == held <= V5E_QUARTER
    assert list(need)[0] == ("dense" if _CELLS[cell][2] == 256 else "block")
    # no budget (XLA:CPU reports no limit): the rung's first form
    assert hist_bins_form(*_CELLS[cell], False, None)[0] == list(need)[0]


def test_the_rule_s_three_outcomes_at_67_columns():
    tall = hist_bins_form(67, 26_562_500, 64, False, V5E_QUARTER)
    assert tall == ("dense", {"block": 10_200_023_040,
                              "dense": 3_400_007_680})
    assert tall[1]["block"] == 3 * 26_562_560 * 128 > V5E_QUARTER
    # the block form's reach, the lane-dense form's, and the raw matrix
    assert hist_bins_form(67, 11_000_000, 64, False, V5E_QUARTER)[0] == \
        "block"
    assert hist_bins_form(67, 11_100_000, 64, False, V5E_QUARTER)[0] == \
        "dense"
    assert hist_bins_form(67, 33_000_000, 64, False, V5E_QUARTER)[0] == \
        "dense"
    form, need = hist_bins_form(67, 33_100_000, 64, False, V5E_QUARTER)
    assert form == "raw" and min(need.values()) > V5E_QUARTER
    # the 16 rung and packed bins have the block form or none
    assert hist_bins_form(28, 1_000, 16, False, 0) == (
        "raw", {"block": 1024 * 128})
    assert list(hist_bins_form(14, 1_000, 16, True, None)[1]) == ["block"]
    assert prepared_bins_bytes(67, 26_562_500, 64, dense=True) == \
        3_400_007_680


# ---------------------------------------------------------------------------
# a booster driven into the lane-dense form through the rule's budget
# ---------------------------------------------------------------------------

def _problem(rows=1300, F=67, seed=64):
    rng = np.random.RandomState(seed)
    X = rng.randn(rows, F).astype(np.float32)
    score = (1.2 * X[:, 0] - X[:, 1] + 0.6 * X[:, 2] * X[:, 3]
             + 0.4 * X[:, 40] - 0.5 * X[:, 66] + rng.randn(rows))
    return X, (score > 0).astype(np.float64)


def _snap(prefix):
    return {k: v for k, v in default_registry().snapshot().items()
            if k.startswith(prefix)}


def test_booster_over_the_rule_takes_the_dense_form_and_grows_the_same_trees(
        monkeypatch):
    """67 columns at 63 bins: with a budget between the two forms' bytes
    placement takes the lane-dense form and the model text, tree for tree
    and field for field, is the block form's; over both it keeps the raw
    matrix, the same text again.  The registry says what each form would
    cost, the budget, what was taken, and the operand's lanes."""
    X, y = _problem()
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
              "learning_rate": 0.1, "min_data_in_leaf": 5,
              "hist_method": "pallas", "verbosity": -1, "seed": 3}
    block_b, dense_b = 3 * 2048 * 128, 2048 * 128

    def train():
        before = _snap("hist_bins_layout_total")
        bst = lgb.train(dict(params), lgb.Dataset(X, label=y),
                        num_boost_round=3)
        after = _snap("hist_bins_layout_total")
        laid = {k.split('"')[1]: after[k] - before.get(k, 0) for k in after}
        return bst, bst.model_to_string(), laid

    a, text_block, _ = train()
    placed = a._gbdt._grow_binned
    assert isinstance(placed, HistBins) and placed.windows == 1
    assert len(placed.blocks) == 3
    assert _snap("hist_bins_prepared_bytes") == {
        "hist_bins_prepared_bytes": block_b}
    assert _snap("hist_bins_budget_bytes") == {"hist_bins_budget_bytes": 0}

    monkeypatch.setattr(trainer, "_hist_bins_budget", lambda: 300_000)
    hist_leaves_pallas.clear_cache()        # the counter counts traces
    b, text_dense, laid = train()
    placed = b._gbdt._grow_binned
    assert isinstance(placed, HistBins)
    assert (placed.tile_cols, placed.windows) == (32, 4)
    assert [blk.shape for blk in placed.blocks] == [(2048, 128)]
    assert placed.matrix.shape == b._gbdt.binned.shape == (67, 1300)
    assert laid["placement"] == 1 and laid.get("pass", 0) == 0
    assert _snap("hist_bins_prepared_bytes") == {
        "hist_bins_prepared_bytes": dense_b}
    assert _snap("hist_bins_need_bytes") == {
        'hist_bins_need_bytes{form="block"}': block_b,
        'hist_bins_need_bytes{form="dense"}': dense_b}
    assert _snap("hist_bins_budget_bytes") == {
        "hist_bins_budget_bytes": 300_000}
    assert _snap("hist_operand_lanes") == {
        'hist_operand_lanes{what="stored"}': 128,
        'hist_operand_lanes{what="live"}': 67}
    assert _snap("hist_pass_blocks")['hist_pass_blocks{rung="64"}'] == 3
    assert text_dense == text_block

    monkeypatch.setattr(trainer, "_hist_bins_budget", lambda: 200_000)
    hist_leaves_pallas.clear_cache()
    c, text_raw, laid = train()
    assert not isinstance(c._gbdt._grow_binned, HistBins)
    assert laid.get("placement", 0) == 0 and laid["pass"] >= 1
    assert _snap("hist_bins_prepared_bytes") == {
        "hist_bins_prepared_bytes": 0}
    assert text_raw == text_block


def test_dense_form_booster_matches_a_numpy_histogram_gbdt(monkeypatch):
    """Three trees of a binary model at ``max_bin=63``, 67 columns, on the
    lane-dense operand against ``test_hist256.py``'s plain numpy GBDT on
    the program's own bins: the same leaves row for row, leaf values and
    gains to float32 rounding of the bf16x2 histograms."""
    import test_hist256 as plain

    monkeypatch.setattr(trainer, "_hist_bins_budget", lambda: 1_000_000)
    monkeypatch.setattr(plain, "B", B)
    rows, F, leaves = 4096, 67, 7
    X, y = _problem(rows, F, seed=63)
    params = {"objective": "binary", "num_leaves": leaves, "max_bin": 63,
              "learning_rate": 0.1, "min_data_in_leaf": 1,
              "min_sum_hessian_in_leaf": 20, "hist_method": "pallas",
              "verbosity": -1}
    booster = lgb.train(dict(params), lgb.Dataset(X, label=y),
                        num_boost_round=3)
    gbdt = booster._gbdt
    placed = gbdt._grow_binned
    assert isinstance(placed, HistBins)
    assert (placed.tile_cols, placed.windows) == (32, 4)
    assert [b.shape for b in placed.blocks] == [(4096, 128)]
    assert int(gbdt.num_bins) == B
    bins = np.asarray(gbdt.binned)
    assert bins.dtype == np.uint8 and bins.shape == (F, rows)

    want, init = plain._numpy_gbdt(bins, y, 3, leaves, 0.1, 20.0)
    leaf_of = np.asarray(booster.predict(X, pred_leaf=True))
    dump = booster.dump_model()["tree_info"]
    for k, (tree, gains) in enumerate(want):
        stored_leaves, stored_gains = {}, []
        stack = [dump[k]["tree_structure"]]
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
            np.testing.assert_allclose(
                node["leaf_value"], value + (init if k == 0 else 0.0),
                rtol=2e-4, atol=2e-6)
        np.testing.assert_allclose(sorted(stored_gains), sorted(gains),
                                   rtol=1e-3)


def test_placement_says_which_form_it_took(monkeypatch):
    """One log line where the rung's first form is over the budget, in the
    raw matrix's line's words; none where it fits."""
    from lightgbmv1_tpu.config import Config
    from lightgbmv1_tpu.io.dataset import BinnedDataset
    from lightgbmv1_tpu.models.gbdt import create_boosting

    said = []
    monkeypatch.setattr(trainer, "log_info", said.append)
    X, y = _problem(rows=700)
    cfg = Config.from_dict({"objective": "binary", "max_bin": 63,
                            "num_leaves": 7, "hist_method": "pallas",
                            "verbosity": -1})

    def place(budget):
        said.clear()
        monkeypatch.setattr(trainer, "_hist_bins_budget", lambda: budget)
        create_boosting(cfg, BinnedDataset.from_numpy(X, label=y,
                                                      config=cfg))
        return [s for s in said if s.startswith("histogram bins")]

    assert place(None) == [] and place(1 << 30) == []
    dense, = place(200_000)
    assert dense.startswith("histogram bins take the lane-dense form")
    assert "block 0 MiB, dense 0 MiB" in dense and "25%" in dense
    raw, = place(100_000)
    assert raw.startswith("histogram bins stay raw")
