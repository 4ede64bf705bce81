"""TPU-native batched inference engine (models/predict.py).

Three-way raw-score / leaf-index parity — native C++ predictor vs the
HostTree numpy walk vs the depth-stepped device walk — across the four
objective families (binary, multiclass softmax, lambdarank, DART), with
NaN/missing-type routing, categorical bitset splits, zero-as-missing and
the prediction-early-stop path; plus the predictor-cache contract
(zero retraces within a bucket, model-version invalidation), the Pallas
kernel's interpret-mode bit parity against the XLA walk, row-sharded
predict parity on the virtual 8-device mesh, and the bounded-walk /
model-load validation of malformed (cyclic) tree structures.

One binary NaN-routed model is trained once per module (`bin_model`) and
shared by every test that only needs *a* model — training dominates the
file's wall time, not the engine under test.
"""

import numpy as np
import pytest

import lightgbmv1_tpu as lgb
from lightgbmv1_tpu.models.predict import (BatchPredictor,
                                           build_serving_binner)
from lightgbmv1_tpu.utils.log import LightGBMError

from conftest import make_binary_problem


def _train(params, X, y, rounds=10, **dsk):
    ds = lgb.Dataset(X, label=y, **dsk)
    return lgb.train({"verbosity": -1, "min_data_in_leaf": 5, **params},
                     ds, num_boost_round=rounds)


def _host_raw(booster, X):
    return np.asarray(booster.predict(X, raw_score=True,
                                      predict_method="host"))


def _native_raw(booster, X, trees, K):
    """Native C++ predictor leg; None when no compiler is available."""
    return booster._predict_raw_native(X, trees, K)


@pytest.fixture(scope="module")
def bin_model():
    """Binary model with NaN-routed splits, shared across the module."""
    rng = np.random.RandomState(21)
    X, y = make_binary_problem(900, 8, seed=1)
    X[rng.rand(*X.shape) < 0.15] = np.nan
    return _train({"objective": "binary", "num_leaves": 31}, X, y,
                  rounds=10)


@pytest.fixture(scope="module")
def xt_nan():
    rng = np.random.RandomState(22)
    Xt = rng.randn(700, 8)
    Xt[rng.rand(*Xt.shape) < 0.2] = np.nan
    return Xt


def _assert_three_way(booster, X, K=1):
    """HostTree walk == device depth-stepped walk (leaf-exact + f64 raw
    bit-exact) == native C++ predictor (when buildable)."""
    trees = booster._all_trees()
    F = booster.num_feature()
    bp = BatchPredictor(trees, K, F)
    leaf_host = np.stack([t.predict_leaf_index(X) for t in trees], axis=1)
    leaf_dev = bp.predict_leaf(X)
    assert np.array_equal(leaf_dev, leaf_host)
    raw_host = _host_raw(booster, X)
    raw_dev = bp.predict_raw(X, f64_exact=True)
    if K == 1:
        raw_dev = raw_dev[:, 0]
    assert np.array_equal(raw_dev, raw_host), (
        "f64-reconstructed device scores must be bit-identical to the "
        "HostTree walk")
    native = _native_raw(booster, X, trees, K)
    if native is not None:
        nv = native[:, 0] if K == 1 else native
        assert np.array_equal(nv, raw_host), (
            "native C++ predictor diverged from the HostTree walk")
    # f32 on-device sum: value-equal to tolerance
    raw_f32 = bp.predict_raw(X)
    if K == 1:
        raw_f32 = raw_f32[:, 0]
    np.testing.assert_allclose(raw_f32, raw_host, rtol=1e-4, atol=1e-5)
    return bp


def test_three_way_parity_binary_with_missing(bin_model, xt_nan):
    bp = _assert_three_way(bin_model, xt_nan)
    assert bp.prebin and bp.binner.ok   # uint8 serving codes in play
    assert bp.binner.dtype == np.uint8
    assert bp.h2d_bytes(1) == 8         # 4x under f32, 8x under f64


def test_three_way_parity_multiclass(rng):
    X = rng.randn(700, 10)
    y = rng.randint(0, 4, 700).astype(float)
    b = _train({"objective": "multiclass", "num_class": 4,
                "num_leaves": 15}, X, y, rounds=4)
    Xt = rng.randn(400, 10)
    _assert_three_way(b, Xt, K=4)
    # transformed output routes through the same objective conversion
    p_host = b.predict(Xt, predict_method="host")
    p_dev = b.predict(Xt, predict_method="depthwise",
                      predict_f64_scores=True)
    np.testing.assert_array_equal(p_dev, p_host)


def test_three_way_parity_lambdarank(rng):
    X = rng.randn(600, 8)
    y = rng.randint(0, 4, 600).astype(float)
    b = _train({"objective": "lambdarank", "num_leaves": 15}, X, y,
               rounds=6, group=np.full(30, 20))
    _assert_three_way(b, rng.randn(300, 8))


def test_three_way_parity_dart(rng):
    X, y = make_binary_problem(700, 8, seed=3)
    b = _train({"objective": "binary", "boosting": "dart",
                "num_leaves": 15, "drop_rate": 0.3}, X, y, rounds=8)
    _assert_three_way(b, rng.randn(400, 8))


def test_three_way_parity_categorical(rng):
    X = rng.randn(700, 8)
    X[:, 2] = rng.randint(0, 12, 700)
    X[:, 5] = rng.randint(0, 30, 700)
    y = ((X[:, 2] % 3 == 0) ^ (X[:, 0] > 0)).astype(float)
    b = _train({"objective": "binary", "num_leaves": 31}, X, y, rounds=8,
               categorical_feature=[2, 5])
    Xt = rng.randn(500, 8)
    Xt[:, 2] = rng.randint(-3, 20, 500)   # negatives + unseen categories
    Xt[:, 5] = rng.randint(0, 40, 500)
    Xt[rng.rand(500) < 0.1, 2] = np.nan   # NaN on a categorical column
    bp = _assert_three_way(b, Xt)
    assert bp.has_cat
    # the raw (non-prebinned) walk carries the same raw-space bitsets
    bpr = BatchPredictor(b._all_trees(), 1, 8, prebin="off")
    assert np.array_equal(bpr.predict_leaf(Xt), bp.predict_leaf(Xt))


def test_three_way_parity_zero_as_missing(rng):
    X = rng.randn(700, 8)
    X[rng.rand(*X.shape) < 0.3] = 0.0
    y = (X[:, 1] > 0).astype(float)
    b = _train({"objective": "binary", "num_leaves": 31,
                "zero_as_missing": True}, X, y, rounds=8)
    Xt = rng.randn(500, 8)
    Xt[rng.rand(*Xt.shape) < 0.3] = 0.0
    Xt[rng.rand(*Xt.shape) < 0.05] = np.nan
    _assert_three_way(b, Xt)


def test_prediction_early_stop_stays_host_and_agrees(bin_model, xt_nan):
    full = bin_model.predict(xt_nan)
    es = bin_model.predict(xt_nan, pred_early_stop=True,
                           pred_early_stop_freq=3,
                           pred_early_stop_margin=1e9)
    # an unreachable margin means no row stops early -> identical output
    np.testing.assert_array_equal(es, full)
    # a device method request with early-stop active still routes host
    es2 = bin_model.predict(xt_nan, pred_early_stop=True,
                            pred_early_stop_freq=3,
                            pred_early_stop_margin=1e9,
                            predict_method="depthwise")
    np.testing.assert_array_equal(es2, full)


def test_scan_method_is_parity_pin(bin_model, xt_nan):
    raw_scan = bin_model.predict(xt_nan, raw_score=True,
                                 predict_method="scan")
    raw_host = _host_raw(bin_model, xt_nan)
    np.testing.assert_allclose(raw_scan, raw_host, rtol=1e-4, atol=1e-5)


def test_pallas_kernel_bit_parity_interpret(bin_model, xt_nan):
    trees = bin_model._all_trees()
    ref = BatchPredictor(trees, 1, 8).predict_leaf(xt_nan)
    bpp = BatchPredictor(trees, 1, 8, method="pallas", interpret=True)
    got = bpp.predict_leaf(xt_nan)
    assert np.array_equal(got, ref), (
        "Pallas serving kernel diverged from the XLA depth-stepped walk")


# ---------------------------------------------------------------------------
# predictor cache
# ---------------------------------------------------------------------------


def test_cache_zero_retraces_within_bucket(bin_model, rng):
    bp = BatchPredictor(bin_model._all_trees(), 1, 8, bucket_min=256)
    bp.predict_raw(rng.randn(700, 8))    # traces the 1024 bucket
    t0 = bp.trace_count
    for n in (700, 513, 1000, 1024, 600):
        bp.predict_raw(rng.randn(n, 8))  # all pad to the 1024 bucket
    assert bp.trace_count == t0, (
        "varying batch sizes within one bucket must never retrace")
    # a new bucket traces exactly once (leaf + scores), then is warm too
    bp.predict_raw(rng.randn(100, 8))    # 256 bucket
    t1 = bp.trace_count
    assert t1 > t0
    bp.predict_raw(rng.randn(200, 8))
    assert bp.trace_count == t1
    assert bp.cache_stats()["entries"] >= 2


def test_cache_lru_bound_and_info(bin_model, xt_nan, rng):
    """The jit cache is LRU-bounded over (bucket, kind) keys: a server
    seeing many batch shapes must never accumulate compiled executables
    without limit.  Eviction costs a retrace on re-touch but never
    correctness."""
    trees = bin_model._all_trees()
    bp = BatchPredictor(trees, 1, 8, bucket_min=8, cache_entries=4)
    ref = _host_raw(bin_model, xt_nan[:64])
    for n in (8, 16, 32, 64):        # 4 buckets x (leaf + scores) entries
        bp.predict_raw(rng.randn(n, 8))
    info = bp.cache_info()
    assert info["capacity"] == 4
    assert info["entries"] <= 4, info
    assert info["evictions"] >= 4, info
    assert info["misses"] >= 8 and info["traces"] >= 8
    # the LRU-evicted 8-bucket retraces on re-touch — and stays correct
    t0 = bp.trace_count
    out = bp.predict_raw(xt_nan[:8], f64_exact=True)
    assert bp.trace_count > t0
    assert np.array_equal(out[:, 0], ref[:8])
    # hits: an in-cache bucket served twice back to back never retraces
    bp.predict_raw(rng.randn(64, 8))
    h0, t1 = bp.cache_info()["hits"], bp.trace_count
    bp.predict_raw(rng.randn(64, 8))
    assert bp.cache_info()["hits"] > h0 and bp.trace_count == t1
    # capacity floor: the walk and its scores executable share a bucket
    assert BatchPredictor(trees, 1, 8, cache_entries=0).cache_capacity == 2


def test_booster_plumbs_cache_entries(bin_model, rng):
    bin_model._device_pred_cache = None   # predictor key ignores kwargs
    bin_model.predict(rng.randn(50, 8), predict_method="depthwise",
                      predict_cache_entries=6)
    assert bin_model._device_pred_cache[1].cache_capacity == 6


def test_cache_leaf_and_raw_share_walk(bin_model, rng):
    bp = BatchPredictor(bin_model._all_trees(), 1, 8)
    bp.predict_leaf(rng.randn(300, 8))
    t0 = bp.trace_count
    bp.predict_leaf(rng.randn(312, 8))
    assert bp.trace_count == t0


def test_booster_cache_invalidation_on_update(rng):
    X, y = make_binary_problem(700, 8, seed=10)
    ds = lgb.Dataset(X, label=y, params={"verbosity": -1})
    b = lgb.train({"objective": "binary", "num_leaves": 15,
                   "min_data_in_leaf": 5, "verbosity": -1}, ds,
                  num_boost_round=4, keep_training_booster=True)
    Xt = rng.randn(200, 8)
    b.predict(Xt, predict_method="depthwise")
    key1, bp1 = b._device_pred_cache
    b.predict(Xt[:100], predict_method="depthwise")
    assert b._device_pred_cache[1] is bp1   # same model -> same predictor
    b.update()                              # version bump
    b.predict(Xt, predict_method="depthwise")
    key2, bp2 = b._device_pred_cache
    assert key2 != key1 and bp2 is not bp1, (
        "model mutation must invalidate the device predictor cache")
    # the refreshed predictor serves the grown ensemble exactly
    np.testing.assert_array_equal(
        b.predict(Xt, raw_score=True, predict_method="depthwise",
                  predict_f64_scores=True),
        _host_raw(b, Xt))


def test_refit_booster_predicts_with_fresh_engine(bin_model, rng):
    X, y = make_binary_problem(900, 8, seed=1)
    Xt = rng.randn(300, 8)
    bin_model.predict(Xt, predict_method="depthwise")
    b2 = bin_model.refit(X, y, decay_rate=0.5)
    # the refitted booster is a new object with its own (empty) cache and
    # new leaf values; its device path must match ITS host walk
    assert not hasattr(b2, "_device_pred_cache")
    np.testing.assert_array_equal(
        b2.predict(Xt, raw_score=True, predict_method="depthwise",
                   predict_f64_scores=True),
        _host_raw(b2, Xt))
    assert not np.array_equal(_host_raw(b2, Xt), _host_raw(bin_model, Xt))


# ---------------------------------------------------------------------------
# sharded predict (8 virtual devices, conftest)
# ---------------------------------------------------------------------------


def test_sharded_predict_parity(bin_model, rng):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices")
    Xt = rng.randn(777, 8)
    trees = bin_model._all_trees()
    bp1 = BatchPredictor(trees, 1, 8)
    bp4 = BatchPredictor(trees, 1, 8, num_shards=4)
    np.testing.assert_array_equal(bp4.predict_leaf(Xt),
                                  bp1.predict_leaf(Xt))
    np.testing.assert_array_equal(bp4.predict_raw(Xt),
                                  bp1.predict_raw(Xt))
    # booster-level routing via params
    out = bin_model.predict(Xt, raw_score=True, predict_method="depthwise",
                            predict_num_shards=4, predict_f64_scores=True)
    np.testing.assert_array_equal(out, _host_raw(bin_model, Xt))


def test_predict_comm_table():
    from lightgbmv1_tpu.parallel.cluster import predict_comm_table

    t = predict_comm_table(8000, 16, 8, itemsize=1, K=1)
    assert t == {"h2d_bytes": 1000 * 16, "d2h_bytes": 1000 * 4,
                 "collective_bytes": 0}
    assert predict_comm_table(8000, 16, 1, itemsize=4)["h2d_bytes"] \
        == 8000 * 64
    # bytes_per_row override: the 4-bit packed transport ships ceil(F/2)
    # bytes/row, which no integer itemsize expresses
    assert predict_comm_table(8000, 15, 8, bytes_per_row=8)["h2d_bytes"] \
        == 1000 * 8


# ---------------------------------------------------------------------------
# malformed models: bounded walks + load-time validation
# ---------------------------------------------------------------------------


def test_cyclic_model_text_fails_loudly(bin_model):
    s = bin_model.model_to_string()
    # rewrite the children so an internal node is reached twice
    lines = s.splitlines()
    for i, ln in enumerate(lines):
        if ln.startswith("left_child="):
            parts = ln.split("=", 1)[1].split()
            if len(parts) >= 2:
                parts[1] = "0"
                lines[i] = "left_child=" + " ".join(parts)
                break
    with pytest.raises(LightGBMError, match="Invalid model file"):
        lgb.Booster(model_str="\n".join(lines))


def test_bounded_walks_terminate_on_cyclic_arrays():
    """The device walks must TERMINATE on a cyclic child graph built via
    the array API (defense in depth under the load-time validator)."""
    import jax.numpy as jnp

    from lightgbmv1_tpu.models.tree import (empty_tree,
                                            tree_leaf_index_binned,
                                            tree_predict_raw)

    t = empty_tree(4)
    t = t._replace(
        num_leaves=jnp.asarray(3, jnp.int32),
        split_feature=jnp.zeros(3, jnp.int32),
        threshold=jnp.asarray([0.0, 0.0, 0.0], jnp.float32),
        left_child=jnp.asarray([1, 0, -1], jnp.int32),   # 0 <-> 1 cycle
        right_child=jnp.asarray([1, 0, -2], jnp.int32),
    )
    X = jnp.zeros((8, 2), jnp.float32)
    out = tree_predict_raw(t, X)          # must return, not hang
    assert out.shape == (8,)
    binned = jnp.zeros((2, 8), jnp.uint8)
    leaf = tree_leaf_index_binned(
        t, binned, jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32))
    assert leaf.shape == (8,)


def test_validate_host_tree_rejects_malformed():
    from lightgbmv1_tpu.models.tree import validate_host_tree

    class T:
        pass

    t = T()
    t.num_leaves = 3
    t.left_child = np.array([1, -1], np.int32)
    t.right_child = np.array([-2, -3], np.int32)
    validate_host_tree(t)                 # proper 3-leaf tree
    t.left_child = np.array([1, 0], np.int32)   # cycle
    with pytest.raises(ValueError, match="cyclic|twice"):
        validate_host_tree(t)
    t.left_child = np.array([1, -9], np.int32)  # leaf out of range
    with pytest.raises(ValueError, match="out of range"):
        validate_host_tree(t)


# ---------------------------------------------------------------------------
# serving binner details + engine API
# ---------------------------------------------------------------------------


def test_serving_binner_code_semantics(bin_model, rng):
    binner = build_serving_binner(bin_model._all_trees(), 8)
    assert binner.ok
    Xt = rng.randn(100, 8)
    Xt[0, 0] = np.nan
    Xt[1, 0] = 0.0
    codes = binner.prebin(Xt)
    assert codes[0, 0] == binner.nan_code
    assert codes[1, 0] == binner.zero_code
    # monotone: code order preserves value order away from the reserves
    v = np.linspace(-3, 3, 50)
    c = binner.prebin(np.tile(v[:, None], (1, 8)))[:, 0].astype(int)
    c = c[(c != binner.nan_code) & (c != binner.zero_code)]
    assert (np.diff(c) >= 0).all()


def test_keep_training_booster_false_returns_serving_booster(rng):
    X, y = make_binary_problem(600, 8, seed=15)
    params = {"objective": "binary", "num_leaves": 15,
              "min_data_in_leaf": 5, "verbosity": -1}
    bt = lgb.train(params, lgb.Dataset(X, label=y,
                                       params={"verbosity": -1}),
                   num_boost_round=4, keep_training_booster=True)
    bs = lgb.train(params, lgb.Dataset(X, label=y,
                                       params={"verbosity": -1}),
                   num_boost_round=4, keep_training_booster=False)
    assert bs._gbdt is None and bs._loaded is not None
    Xt = rng.randn(200, 8)
    np.testing.assert_array_equal(_host_raw(bs, Xt), _host_raw(bt, Xt))
    np.testing.assert_array_equal(
        bs.predict(Xt, raw_score=True, predict_method="depthwise",
                   predict_f64_scores=True),
        _host_raw(bt, Xt))


def test_config_validates_predict_knobs():
    from lightgbmv1_tpu.config import Config

    cfg = Config.from_dict({"predict_method": "depthwise",
                            "predict_prebin": "on"})
    assert cfg.predict_method == "depthwise"
    with pytest.raises(ValueError, match="predict_method"):
        Config.from_dict({"predict_method": "warp"})
    with pytest.raises(ValueError, match="predict_prebin"):
        Config.from_dict({"predict_prebin": "yes"})
    # ISSUE 19: the megakernel method + the code-layout knob
    cfg = Config.from_dict({"predict_method": "fused",
                            "predict_code_layout": "packed4"})
    assert (cfg.predict_method, cfg.predict_code_layout) \
        == ("fused", "packed4")
    with pytest.raises(ValueError, match="predict_code_layout"):
        Config.from_dict({"predict_code_layout": "nibble"})


def test_cli_task_predict_device_route(bin_model, rng, tmp_path):
    """task=predict file->file through the device engine matches the host
    route byte-for-byte (f64 score reconstruction)."""
    from lightgbmv1_tpu.cli import main as cli_main

    model = tmp_path / "model.txt"
    bin_model.save_model(str(model))
    data = tmp_path / "pred.tsv"
    Xt = rng.randn(300, 8)
    np.savetxt(data, np.column_stack([np.zeros(300), Xt]), delimiter="\t")
    out_host = tmp_path / "out_host.txt"
    out_dev = tmp_path / "out_dev.txt"
    base = [f"task=predict", f"input_model={model}", f"data={data}",
            "verbosity=-1"]
    cli_main(base + [f"output_result={out_host}", "predict_method=host"])
    cli_main(base + [f"output_result={out_dev}",
                     "predict_method=depthwise", "predict_f64_scores=true"])
    assert out_host.read_text() == out_dev.read_text()


# ---------------------------------------------------------------------------
# serving megakernel (predict_method=fused, ISSUE 19)
# ---------------------------------------------------------------------------


def _fused_assert_parity(booster, X, K=1, **bpk):
    """Fused megakernel vs HostTree oracle: leaf node-exact, f64 scores
    bit-exact, f32 single-launch scores value-equal — and the kernel must
    have actually run (not the staged fallback)."""
    trees = booster._all_trees()
    bp = BatchPredictor(trees, K, booster.num_feature(), method="fused",
                        **bpk)
    assert bp.fused_plan is not None and bp.fused_plan["eligible"], \
        bp.fused_plan
    leaf_host = np.stack([t.predict_leaf_index(X) for t in trees], axis=1)
    assert np.array_equal(bp.predict_leaf(X), leaf_host)
    raw_host = _host_raw(booster, X)
    raw64 = bp.predict_raw(X, f64_exact=True)
    if K == 1:
        raw64 = raw64[:, 0]
    assert np.array_equal(raw64, raw_host), (
        "fused f64-reconstructed scores must be bit-identical to the "
        "HostTree walk")
    raw32 = bp.predict_raw(X)
    if K == 1:
        raw32 = raw32[:, 0]
    np.testing.assert_allclose(raw32, raw_host, rtol=1e-4, atol=1e-5)
    assert bp._fused_engaged()
    return bp


def test_fused_parity_binary_with_missing(bin_model, xt_nan):
    bp = _fused_assert_parity(bin_model, xt_nan)
    assert bp.interpret            # CPU lane pins via interpret mode
    assert bp.fused_plan["n_tree_tiles"] >= 1
    # the tree-tile pad parks on zero-leaf trees: T rounded up
    assert bp.fused_plan["t_pad"] % bp.fused_plan["tree_tile"] == 0


def test_fused_parity_dart(rng):
    X, y = make_binary_problem(700, 8, seed=3)
    b = _train({"objective": "binary", "boosting": "dart",
                "num_leaves": 15, "drop_rate": 0.3}, X, y, rounds=8)
    _fused_assert_parity(b, rng.randn(400, 8))


@pytest.mark.slow
def test_fused_parity_multiclass(rng):
    X = rng.randn(700, 10)
    y = rng.randint(0, 4, 700).astype(float)
    b = _train({"objective": "multiclass", "num_class": 4,
                "num_leaves": 15}, X, y, rounds=4)
    _fused_assert_parity(b, rng.randn(400, 10), K=4)


@pytest.mark.slow
def test_fused_parity_lambdarank(rng):
    X = rng.randn(600, 8)
    y = rng.randint(0, 4, 600).astype(float)
    b = _train({"objective": "lambdarank", "num_leaves": 15}, X, y,
               rounds=6, group=np.full(30, 20))
    _fused_assert_parity(b, rng.randn(300, 8))


@pytest.mark.slow
def test_fused_parity_zero_as_missing(rng):
    X = rng.randn(700, 8)
    X[rng.rand(*X.shape) < 0.3] = 0.0
    y = (X[:, 1] > 0).astype(float)
    b = _train({"objective": "binary", "num_leaves": 31,
                "zero_as_missing": True}, X, y, rounds=8)
    Xt = rng.randn(500, 8)
    Xt[rng.rand(*Xt.shape) < 0.3] = 0.0
    Xt[rng.rand(*Xt.shape) < 0.05] = np.nan
    _fused_assert_parity(b, Xt)


def test_fused_categorical_falls_back_staged(rng):
    """Categorical bitsets stay on the staged walk: the planner refuses
    with the honest reason line and predictions remain oracle-exact."""
    X = rng.randn(500, 8)
    X[:, 2] = rng.randint(0, 12, 500)
    y = ((X[:, 2] % 3 == 0) ^ (X[:, 0] > 0)).astype(float)
    b = _train({"objective": "binary", "num_leaves": 15}, X, y, rounds=4,
               categorical_feature=[2])
    bp = BatchPredictor(b._all_trees(), 1, 8, method="fused")
    assert not bp.fused_plan["eligible"]
    assert "categorical" in bp.fused_plan["reason"]
    assert not bp._fused_engaged()
    Xt = rng.randn(300, 8)
    Xt[:, 2] = rng.randint(-3, 20, 300)
    leaf_host = np.stack([t.predict_leaf_index(Xt)
                          for t in b._all_trees()], axis=1)
    assert np.array_equal(bp.predict_leaf(Xt), leaf_host)
    assert np.array_equal(bp.predict_raw(Xt, f64_exact=True)[:, 0],
                          _host_raw(b, Xt))


def test_fused_epilogue_predict_scores(bin_model, xt_nan):
    """The in-kernel sigmoid epilogue rides the same launch and matches
    the host-side transform of the raw scores; the staged engine's
    predict_scores applies the same math out of kernel."""
    raw_host = _host_raw(bin_model, xt_nan)
    want = 1.0 / (1.0 + np.exp(-raw_host))
    trees = bin_model._all_trees()
    bpf = BatchPredictor(trees, 1, 8, method="fused")
    got = bpf.predict_scores(xt_nan, transform="sigmoid")[:, 0]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    bpd = BatchPredictor(trees, 1, 8)
    got_staged = bpd.predict_scores(xt_nan, transform="sigmoid")[:, 0]
    np.testing.assert_allclose(got_staged, want, rtol=1e-4, atol=1e-6)
    # raw passthrough and validation
    np.testing.assert_allclose(
        bpf.predict_scores(xt_nan)[:, 0], raw_host, rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="transform"):
        bpf.predict_scores(xt_nan, transform="probit")


def test_fused_zero_retraces_within_bucket(bin_model, rng):
    bp = BatchPredictor(bin_model._all_trees(), 1, 8, method="fused",
                        bucket_min=256)
    bp.predict_raw(rng.randn(700, 8))    # traces the 1024 bucket
    t0 = bp.trace_count
    for n in (700, 513, 1000, 1024, 600):
        bp.predict_raw(rng.randn(n, 8))
    assert bp.trace_count == t0, (
        "varying batch sizes within one bucket must never retrace "
        "through the fused dispatch")
    assert bp._fused_engaged()


def test_kernel_failure_propagates(bin_model, monkeypatch):
    """A kernel the caller asked for that cannot lower RAISES with the
    compiler's message — no staged walk is swapped in behind the
    caller's back (the fused and the pallas lane alike), and the next
    call raises again rather than serving from a remembered fallback."""
    from lightgbmv1_tpu.ops import predict_pallas as pp_mod

    def boom(*a, **k):
        raise NotImplementedError("Only 2D gather is supported")

    rng = np.random.RandomState(31)
    Xt = rng.randn(600, 8)
    trees = bin_model._all_trees()
    monkeypatch.setattr(pp_mod, "serving_fused_pallas", boom)
    bp = BatchPredictor(trees, 1, 8, method="fused", bucket_min=64,
                        chunk_rows=128)
    for call in (bp.predict_leaf, bp.predict_raw, bp.predict_leaf):
        with pytest.raises(NotImplementedError, match="2D gather"):
            call(Xt)
    assert bp._fused_engaged()       # still the fused plan, not re-routed
    monkeypatch.setattr(pp_mod, "serving_leaf_pallas", boom)
    bpp = BatchPredictor(trees, 1, 8, method="pallas", bucket_min=64,
                         chunk_rows=128)
    for _ in range(2):
        with pytest.raises(NotImplementedError, match="2D gather"):
            bpp.predict_leaf(Xt)
    # through the user-facing entry point too
    with pytest.raises(NotImplementedError, match="2D gather"):
        bin_model.predict(Xt, pred_leaf=True, predict_method="pallas")


def test_booster_fused_route(bin_model, xt_nan):
    out = bin_model.predict(xt_nan, raw_score=True,
                            predict_method="fused",
                            predict_f64_scores=True)
    np.testing.assert_array_equal(out, _host_raw(bin_model, xt_nan))
    # the code-layout knob plumbs through Booster.predict kwargs
    bin_model._device_pred_cache = None
    out_u8 = bin_model.predict(xt_nan, raw_score=True,
                               predict_method="fused",
                               predict_code_layout="u8",
                               predict_f64_scores=True)
    np.testing.assert_array_equal(out_u8, out)
    bin_model._device_pred_cache = None


# ---------------------------------------------------------------------------
# 4-bit packed serving codes
# ---------------------------------------------------------------------------


def test_packed_codes_roundtrip():
    from lightgbmv1_tpu.models.predict import (pack_serving_codes,
                                               unpack_serving_codes)

    rng = np.random.RandomState(7)
    for F in (8, 7, 1):                     # even, odd, degenerate
        codes = rng.randint(0, 16, (50, F)).astype(np.uint8)
        packed = pack_serving_codes(codes)
        assert packed.shape == (50, (F + 1) // 2)
        assert packed.dtype == np.uint8
        # lo nibble = even feature (the PR 18 pack4bit convention)
        assert np.array_equal(packed[:, 0] & 15, codes[:, 0])
        out = unpack_serving_codes(packed, F)
        assert np.array_equal(out, codes)


def _packed_model(rounds=8):
    X, y = make_binary_problem(700, 8, seed=9)
    return _train({"objective": "binary", "num_leaves": 15,
                   "max_bin": 10}, X, y, rounds=rounds)


def test_packed_fused_parity_and_h2d(rng):
    """A packed-eligible model (every feature <= 15 serving codes incl.
    the reserves): auto-packing engages on the fused path, halves the
    transport, and stays node/bit-exact; the staged packed4 twin unpacks
    ON DEVICE with identical results."""
    b = _packed_model()
    Xt = rng.randn(400, 8)
    bp = _fused_assert_parity(b, Xt)
    assert bp.binner.packed_ok and bp.packed
    assert bp.h2d_bytes(1) == 4            # ceil(8/2), was 8
    bp_u8 = BatchPredictor(b._all_trees(), 1, 8, method="fused",
                           code_layout="u8")
    assert not bp_u8.packed and bp_u8.h2d_bytes(1) == 8
    assert bp_u8.h2d_bytes(1) == 2 * bp.h2d_bytes(1)   # 2.0x analytic
    # staged twin: explicit packed4 on the depth-stepped engine
    bp_st = BatchPredictor(b._all_trees(), 1, 8, code_layout="packed4")
    assert bp_st.packed and bp_st.h2d_bytes(1) == 4
    leaf_host = np.stack([t.predict_leaf_index(Xt)
                          for t in b._all_trees()], axis=1)
    assert np.array_equal(bp_st.predict_leaf(Xt), leaf_host)


def test_packed_refusal_reasons(bin_model, monkeypatch):
    """Explicit packed4 on an ineligible model refuses with one honest
    reason and serves unpacked."""
    from lightgbmv1_tpu.models import predict as predict_mod

    monkeypatch.setattr(predict_mod, "_logged_once", set())
    warnings = []
    monkeypatch.setattr(predict_mod, "log_warning",
                        lambda m: warnings.append(m))
    # bin_model's binner needs > 16 codes (31-leaf trees, 10 rounds)
    bp = BatchPredictor(bin_model._all_trees(), 1, 8,
                        code_layout="packed4")
    assert not bp.packed
    assert any("exceed the 16 nibble values" in m for m in warnings)
    # raw-walk predictor: packing needs prebinned codes at all
    warnings.clear()
    monkeypatch.setattr(predict_mod, "_logged_once", set())
    bp2 = BatchPredictor(bin_model._all_trees(), 1, 8, prebin="off",
                         code_layout="packed4")
    assert not bp2.packed
    assert any("not in play" in m for m in warnings)


def test_packed_eligibility_boundary():
    """The 15/16-code boundary: 13 thresholds -> nan_code 15 (the last
    nibble value) packs; 14 thresholds -> nan_code 16 refuses."""
    t_ok = _bst_tree([i + 0.5 for i in range(13)])
    binner = build_serving_binner([t_ok], 4)
    assert binner.ok and binner.nan_code == 15 and binner.packed_ok
    t_over = _bst_tree([i + 0.5 for i in range(14)])
    binner2 = build_serving_binner([t_over], 4)
    assert binner2.ok and binner2.nan_code == 16 and not binner2.packed_ok
    bp = BatchPredictor([t_ok], 1, 4, method="fused")
    assert bp.packed
    bp2 = BatchPredictor([t_over], 1, 4, method="fused")
    assert not bp2.packed


# ---------------------------------------------------------------------------
# serving-binner edge geometry through fused + staged (ISSUE 19)
# ---------------------------------------------------------------------------


def _bst_tree(thresholds, feature=0, nan_left=False):
    """A balanced BST HostTree over sorted numeric thresholds on one
    feature (value <= t goes left), MISSING_NAN routing — the geometry
    scaffold for binner-edge tests where training can't pin the exact
    threshold count."""
    import jax.numpy as jnp

    from lightgbmv1_tpu.io.binning import MISSING_NAN
    from lightgbmv1_tpu.models.tree import HostTree, empty_tree

    ths = sorted(float(v) for v in thresholds)
    n = len(ths)
    nodes = [None] * n
    order = []

    def build(lo, hi):                    # leaves lo..hi inclusive
        if lo == hi:
            return -(lo + 1)
        i = len(order)
        order.append(i)
        mid = (lo + hi) // 2
        nodes[i] = [ths[mid], build(lo, mid), build(mid + 1, hi)]
        return i

    build(0, n)
    arr = empty_tree(n + 1)._replace(
        num_leaves=jnp.asarray(n + 1, jnp.int32),
        split_feature=jnp.full(n, feature, jnp.int32),
        threshold=jnp.asarray([nd[0] for nd in nodes], jnp.float32),
        default_left=jnp.full(n, bool(nan_left), bool),
        missing_type=jnp.full(n, MISSING_NAN, jnp.int32),
        left_child=jnp.asarray([nd[1] for nd in nodes], jnp.int32),
        right_child=jnp.asarray([nd[2] for nd in nodes], jnp.int32),
        leaf_value=jnp.asarray(
            np.linspace(-1.0, 1.0, n + 1), jnp.float32),
    )
    return HostTree(arr)


def _geometry_assert(trees, F, X):
    """Fused (interpret) == staged depth-stepped == HostTree oracle."""
    leaf_host = np.stack([t.predict_leaf_index(X) for t in trees], axis=1)
    bpf = BatchPredictor(trees, 1, F, method="fused", bucket_min=64)
    assert bpf.fused_plan["eligible"], bpf.fused_plan
    assert np.array_equal(bpf.predict_leaf(X), leaf_host)
    bps = BatchPredictor(trees, 1, F, bucket_min=64)
    assert np.array_equal(bps.predict_leaf(X), leaf_host)
    return bpf


@pytest.mark.slow
def test_uint16_codes_with_reserved_geometry(rng):
    """> 255 serving bins force uint16 codes; the reserved NaN/zero codes
    then live above 255 and must still route exactly through the fused
    walk and its staged twin."""
    ths = [i + 0.5 for i in range(300)]
    trees = [_bst_tree(ths, feature=0, nan_left=False),
             _bst_tree([0.5, 1.5, 2.5], feature=1, nan_left=True)]
    binner = build_serving_binner(trees, 3)
    assert binner.ok and binner.dtype == np.uint16
    assert binner.nan_code > 255 and not binner.packed_ok
    X = np.column_stack([
        rng.uniform(-5, 305, 500),
        rng.uniform(-2, 5, 500),
        rng.randn(500)])
    X[rng.rand(500) < 0.15, 0] = np.nan       # reserved nan code
    X[rng.rand(500) < 0.15, 1] = np.nan
    X[rng.rand(500) < 0.15, 0] = 0.0          # reserved zero code
    X[:8, 0] = [0.5, 299.5, -1e9, 1e9, 0.0, np.nan, 150.5, 150.4999]
    bpf = _geometry_assert(trees, 3, X)
    assert not bpf.packed


def test_single_serving_bin_collapse(rng):
    """A feature whose threshold set collapses to ONE serving bin edge
    (single threshold -> two codes + reserves) beside a wide feature:
    the degenerate geometry must not skew either walk."""
    trees = [_bst_tree([2.5], feature=0),
             _bst_tree([i + 0.5 for i in range(9)], feature=1)]
    binner = build_serving_binner(trees, 2)
    assert binner.ok and len(binner.thresholds[0]) == 1
    X = np.column_stack([rng.uniform(0, 5, 300), rng.uniform(-1, 11, 300)])
    X[rng.rand(300) < 0.2, 0] = np.nan
    X[:4, 0] = [2.5, 2.5000002, 0.0, -1e9]    # the edge itself + zero
    bpf = _geometry_assert(trees, 2, X)
    assert bpf.packed                          # 10+2 codes fit nibbles


# ---------------------------------------------------------------------------
# plan_predict_tiles (pure planner)
# ---------------------------------------------------------------------------


def test_plan_predict_tiles_reasons_and_tiling():
    from lightgbmv1_tpu.ops.predict_pallas import plan_predict_tiles

    base = dict(T=100, L1=30, L=31, F=28, K=1, depth=6)
    plan = plan_predict_tiles(**base)
    assert plan["eligible"] and plan["reason"] == ""
    assert plan["t_pad"] % plan["tree_tile"] == 0
    assert plan["t_pad"] >= base["T"]
    assert plan["total_bytes"] <= plan["vmem_budget"]
    # refusals carry one honest reason line each
    assert "prebinned" in plan_predict_tiles(**base, prebin=False)["reason"]
    assert "categorical" in \
        plan_predict_tiles(**base, has_cat=True)["reason"]
    tight = plan_predict_tiles(**base, vmem_budget=1 << 10)
    assert not tight["eligible"] and "VMEM budget" in tight["reason"]
    # a model too big for one tile still fits via tree tiling
    big = plan_predict_tiles(T=4096, L1=255, L=256, F=28, K=1, depth=8)
    assert big["eligible"] and big["n_tree_tiles"] > 1
    assert big["tree_tile"] * big["n_tree_tiles"] == big["t_pad"]
    # the packed layout halves the codes-tile footprint
    pk = plan_predict_tiles(**base, packed=True)
    assert pk["codes_tile_bytes"] < plan["codes_tile_bytes"]
