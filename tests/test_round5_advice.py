"""Regression tests closing three round-5 review findings.

1. ``gpu_use_dp`` must not stomp an explicitly-set ``hist_dtype_deep``
   (config.py — the trainer documents "hist_dtype_deep overrides").
2. ``leaf_lookup`` documents its in-range precondition and, in debug
   mode, poisons out-of-range rows with NaN instead of silently
   contributing 0.0 (models/tree.py).
3. The level-wise partition processes the frontier in chunks of at most
   ``_LEVEL_CHUNK`` splits (the wave grower's 128-slot cap applied to
   levels) — chunked and unchunked growth must be bit-identical
   (models/grower.py).
"""

import numpy as np
import pytest

import lightgbmv1_tpu as lgb
from lightgbmv1_tpu.config import Config


# ---------------------------------------------------------------------------
# 1. gpu_use_dp vs explicit hist_dtype_deep
# ---------------------------------------------------------------------------


def test_gpu_use_dp_defaults_deep_dtype_when_unset():
    cfg = Config.from_dict({"objective": "binary", "gpu_use_dp": True})
    assert cfg.hist_dtype_deep == "f32"
    assert cfg.hist_dtype == "f32"


def test_gpu_use_dp_respects_explicit_hist_dtype_deep():
    cfg = Config.from_dict({"objective": "binary", "gpu_use_dp": True,
                            "hist_dtype_deep": "bf16x2"})
    # the explicitly-set value must survive (round-5 finding 1: it was stomped)
    assert cfg.hist_dtype_deep == "bf16x2"
    assert cfg.hist_dtype == "f32"


# ---------------------------------------------------------------------------
# 2. leaf_lookup out-of-range contract
# ---------------------------------------------------------------------------


def test_leaf_lookup_debug_bounds(monkeypatch):
    import jax.numpy as jnp

    from lightgbmv1_tpu.models import tree as tree_mod

    table = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    ids = jnp.asarray([0, 3, 7, -1], jnp.int32)
    # default contract: out-of-range contributes 0.0 (documented; differs
    # from the clamping gather it replaced)
    monkeypatch.setattr(tree_mod, "DEBUG_BOUNDS", False)
    out = np.asarray(tree_mod.leaf_lookup(table, ids))
    np.testing.assert_allclose(out, [1.0, 4.0, 0.0, 0.0])
    # debug mode: violations surface as NaN, in-range rows untouched
    monkeypatch.setattr(tree_mod, "DEBUG_BOUNDS", True)
    out = np.asarray(tree_mod.leaf_lookup(table, ids))
    np.testing.assert_allclose(out[:2], [1.0, 4.0])
    assert np.isnan(out[2]) and np.isnan(out[3])


# ---------------------------------------------------------------------------
# 3. level-wise frontier chunking
# ---------------------------------------------------------------------------


# tier-1 wall budget (tools/tier1_budget.py): slow-marked — still run by the full
# suite and driver captures
@pytest.mark.slow
def test_levelwise_chunked_partition_bit_identical(monkeypatch):
    from lightgbmv1_tpu.models import grower as grower_mod

    rng = np.random.RandomState(11)
    n = 4000
    X = rng.randn(n, 6)
    X[::7, 1] = np.nan
    y = (X[:, 0] - 0.5 * X[:, 1] + rng.randn(n) * 0.4 > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
              "tree_growth": "levelwise"}

    def run():
        return lgb.train(params, lgb.Dataset(X, label=y),
                         num_boost_round=3)

    a = run()                                        # single-chunk (cap 128)
    monkeypatch.setattr(grower_mod, "_LEVEL_CHUNK", 3)   # force chunking
    b = run()
    for ta, tb in zip(a._all_trees(), b._all_trees()):
        assert ta.num_leaves == tb.num_leaves
        np.testing.assert_array_equal(ta.split_feature, tb.split_feature)
        np.testing.assert_array_equal(ta.threshold_bin, tb.threshold_bin)
        np.testing.assert_array_equal(ta.leaf_count, tb.leaf_count)
        np.testing.assert_array_equal(np.asarray(ta.leaf_value),
                                      np.asarray(tb.leaf_value))
    np.testing.assert_array_equal(a.predict(X), b.predict(X))
