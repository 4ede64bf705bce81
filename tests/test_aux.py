"""Aux subsystem tests: binary dataset cache, auc_mu, phase timer.

reference: Dataset::SaveBinaryFile / LoadFromBinFile
(dataset.h:473, dataset_loader.cpp:273), AucMuMetric
(multiclass_metric.hpp:183), USE_TIMETAG global_timer (common.h:1054-1138).
"""

import numpy as np
import pytest

import lightgbmv1_tpu as lgb
from lightgbmv1_tpu.utils.timer import global_timer


def test_binary_dataset_cache_round_trip(tmp_path):
    rng = np.random.RandomState(0)
    X = rng.randn(500, 6)
    X[::7, 2] = np.nan                      # missing values survive the cache
    y = (X[:, 0] > 0).astype(float)
    w = rng.rand(500)
    ds = lgb.Dataset(X, label=y, weight=w)
    path = str(tmp_path / "train.bin")
    ds.save_binary(path)

    from lightgbmv1_tpu.io.dataset import BinnedDataset
    assert BinnedDataset.is_binary_file(path)
    assert not BinnedDataset.is_binary_file(__file__)

    ds2 = lgb.Dataset(path)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1}
    b1 = lgb.train(params, lgb.Dataset(X, label=y, weight=w), num_boost_round=5)
    b2 = lgb.train(params, ds2, num_boost_round=5)
    np.testing.assert_allclose(b1.predict(X), b2.predict(X),
                               rtol=1e-6, atol=1e-7)


def test_binary_cache_preserves_categorical(tmp_path):
    rng = np.random.RandomState(1)
    cat = rng.randint(0, 6, 800).astype(float)
    y = np.isin(cat, [1, 4]).astype(float)
    X = np.column_stack([cat, rng.randn(800)])
    ds = lgb.Dataset(X, label=y, categorical_feature=[0])
    path = str(tmp_path / "cat.bin")
    ds.save_binary(path)
    ds2 = lgb.Dataset(path)
    ds2.construct()
    assert ds2._binned.is_categorical[0]
    assert not ds2._binned.is_categorical[1]
    b = lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1},
                  ds2, num_boost_round=5)
    acc = ((b.predict(X) > 0.5) == (y > 0.5)).mean()
    assert acc > 0.95


def test_auc_mu_metric():
    rng = np.random.RandomState(2)
    K, n = 3, 900
    y = rng.randint(0, K, n).astype(float)
    X = rng.randn(n, 4)
    X[:, 0] += y                               # separable-ish signal
    bst = lgb.train({"objective": "multiclass", "num_class": K,
                     "metric": "auc_mu", "num_leaves": 7, "verbosity": -1},
                    lgb.Dataset(X, label=y), num_boost_round=8)
    out = bst._gbdt.eval_train()
    vals = {m: v for (_, m, v, _) in out}
    assert "auc_mu" in vals
    assert 0.75 < vals["auc_mu"] <= 1.0

    # permutation-invariance sanity: random labels ~ 0.5
    y_rand = rng.randint(0, K, n).astype(float)
    bst2 = lgb.train({"objective": "multiclass", "num_class": K,
                      "metric": "auc_mu", "num_leaves": 4, "verbosity": -1},
                     lgb.Dataset(rng.randn(n, 2), label=y_rand),
                     num_boost_round=1)
    out2 = {m: v for (_, m, v, _) in bst2._gbdt.eval_train()}
    assert abs(out2["auc_mu"] - 0.5) < 0.15


def test_global_timer_sections():
    global_timer.reset()
    global_timer.enabled = True
    try:
        rng = np.random.RandomState(3)
        X = rng.randn(300, 4)
        y = (X[:, 0] > 0).astype(float)
        bst = lgb.train({"objective": "binary", "num_leaves": 4,
                         "verbosity": -1}, lgb.Dataset(X, label=y),
                        num_boost_round=3)
        bst.predict(X)   # forces host-tree materialization
        rep = global_timer.report()
        assert "GBDT::" in rep
        assert global_timer.totals   # phases actually recorded
    finally:
        global_timer.enabled = False
        global_timer.reset()


def test_named_scopes_reach_lowered_hlo():
    """The lgbm.hist / lgbm.split named scopes must survive into the
    compiled program's metadata so device traces attribute time per phase
    (the USE_TIMETAG analog; VERDICT r3 item 10).  profile_dir (cli.py)
    captures a trace around training."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lightgbmv1_tpu.ops.histogram import hist_frontier
    from lightgbmv1_tpu.ops.split import (FeatureMeta, SplitParams,
                                          find_best_split)

    binned = jnp.zeros((3, 64), jnp.uint8)
    g3 = jnp.zeros((64, 3), jnp.float32)
    lid = jnp.zeros(64, jnp.int32)
    txt = jax.jit(
        lambda b, g, l: hist_frontier(b, g, l, 2, 8)).lower(
        binned, g3, lid).as_text(debug_info=True)
    assert "lgbm.hist" in txt

    meta = FeatureMeta(
        num_bins=jnp.full(3, 8, jnp.int32),
        missing_type=jnp.zeros(3, jnp.int32),
        nan_bin=jnp.full(3, -1, jnp.int32),
        zero_bin=jnp.zeros(3, jnp.int32),
        is_categorical=jnp.zeros(3, bool),
        usable=jnp.ones(3, bool),
        monotone_type=jnp.zeros(3, jnp.int32),
    )
    hist = jnp.zeros((3, 8, 3), jnp.float32)
    txt2 = jax.jit(lambda h, p, m: find_best_split(
        h, p, meta, m, SplitParams())).lower(
        hist, jnp.zeros(3), jnp.ones(3, bool)).as_text(debug_info=True)
    assert "lgbm.split" in txt2
