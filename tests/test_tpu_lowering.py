"""Cross-lower every Pallas kernel the package ships for ``tpu`` — from
the CPU suite, no chip needed.

``jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",))`` runs the
Pallas -> Mosaic lowering only (the Mosaic compiler proper runs on the
chip; chip_smoke.py covers that).  Lowering is therefore necessary, not
sufficient — but it is where four of the five kernels stop on the
installed JAX, and nothing else in tier-1 would notice: on CPU they all
run in interpret mode.

A kernel that cannot lower is a STRICT xfail carrying the compiler's
message, so a JAX upgrade or a repair flips it visibly (XPASS fails the
suite until the marker is removed).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbmv1_tpu as lgb
from lightgbmv1_tpu.models.predict import BatchPredictor
from lightgbmv1_tpu.ops import wave_fused as wf
from lightgbmv1_tpu.ops.hist_pallas import hist_leaves_pallas, pack4bit
from lightgbmv1_tpu.ops.predict_pallas import (serving_fused_pallas,
                                               serving_leaf_pallas)
from lightgbmv1_tpu.ops.split import FeatureMeta, SplitParams

CUMSUM = ("Unimplemented primitive in Pallas TPU lowering for "
          "KernelType.TC: cumsum (ops/split.scan_left_sums in the kernel "
          "body; with the cumsum written as a triangular matmul the next "
          "stop is 'Only 2D gather is supported', the (F, B, 3) "
          "take_along_axis point reads of the same function)")
GATHER = ("Only 2D gather is supported (jnp.take of the flattened 1-D "
          "node table, ops/predict_pallas.py walk body)")


def lower_for_tpu(fn, *args):
    txt = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in txt, "no Mosaic kernel in the lowering"
    return txt


@pytest.fixture(scope="module")
def rows():
    rng = np.random.RandomState(0)
    N = 2048
    return (rng, N, jnp.asarray(rng.randn(N, 3).astype(np.float32)))


@pytest.mark.parametrize("precision",
                         ["bf16x2", "bf16", "f32", "int8", "int8sr"])
@pytest.mark.parametrize("num_bins", [16, 64, 256])
def test_hist_kernel_lowers(rows, num_bins, precision):
    """The default-on-TPU histogram kernel at every kernel-width rung,
    every precision, and the slot counts of the wave ladder (1 = root
    pass, 16 and 64 = ramp and sustained buckets incl. the dead slot)."""
    rng, N, g3 = rows
    binned = jnp.asarray(rng.randint(0, num_bins, (28, N)).astype(np.uint8))
    for slots in (1, 16, 64):
        leaf = jnp.asarray(rng.randint(0, slots, N).astype(np.int32))
        lower_for_tpu(
            lambda b, g, l: hist_leaves_pallas(b, g, l, slots, num_bins,
                                               precision=precision),
            binned, g3, leaf)


@pytest.mark.parametrize("precision", ["bf16x2", "bf16", "int8sr"])
def test_hist_kernel_packed4_lowers(rows, precision):
    rng, N, g3 = rows
    packed = jnp.asarray(pack4bit(
        rng.randint(0, 16, (27, N)).astype(np.uint8)))     # odd F tail
    leaf = jnp.asarray(rng.randint(0, 64, N).astype(np.int32))
    lower_for_tpu(
        lambda b, g, l: hist_leaves_pallas(b, g, l, 64, 16,
                                           precision=precision, packed=True,
                                           num_features=27),
        packed, g3, leaf)


def _probe_meta(F, B):
    return FeatureMeta(
        num_bins=jnp.full(F, B, jnp.int32),
        missing_type=jnp.zeros(F, jnp.int32),
        nan_bin=jnp.full(F, -1, jnp.int32),
        zero_bin=jnp.zeros(F, jnp.int32),
        is_categorical=jnp.zeros(F, bool),
        usable=jnp.ones(F, bool),
        monotone_type=jnp.zeros(F, jnp.int32),
    )


def _round_args(F=4, B=8, N=64, S=2):
    rng = np.random.RandomState(0)
    binned = jnp.asarray(rng.randint(0, B, (F, N)).astype(np.uint8))
    g3 = jnp.asarray(rng.randn(N, 3).astype(np.float32))
    lids = jnp.asarray(rng.randint(0, 2 * S, N).astype(np.int32))
    kw = dict(mask=jnp.ones((2 * S, F), bool),
              csums=jnp.abs(jnp.asarray(
                  rng.randn(2 * S, 3).astype(np.float32))),
              constr=jnp.tile(jnp.asarray([-3e38, 3e38], jnp.float32),
                              (2 * S, 1)),
              depth=jnp.ones(2 * S, jnp.int32),
              pout=jnp.zeros(2 * S, jnp.float32))
    route = dict(feats=jnp.arange(S, dtype=jnp.int32),
                 thrs=jnp.full(S, B // 2, jnp.int32),
                 dls=jnp.zeros(S, bool),
                 leafs=jnp.arange(S, dtype=jnp.int32),
                 nls=jnp.arange(S, dtype=jnp.int32) + S,
                 num_leaves=2 * S)
    return binned, g3, lids, kw, route


def test_fused_route_rows_lowers():
    """The valid-set router of the fused round — the one piece of
    ops/wave_fused.py the installed JAX can put on a TPU."""
    F, B, S = 4, 8, 2
    binned, _, lids, _, route = _round_args(F, B, S=S)
    fn = wf.make_fused_round(meta=_probe_meta(F, B), params=SplitParams(),
                             num_bins=B, precision="bf16x2",
                             deep_precision="bf16")
    lower_for_tpu(lambda b, l: fn.route_rows(b, l, **route), binned, lids)


@pytest.mark.xfail(strict=True, raises=NotImplementedError, reason=CUMSUM)
def test_fused_round_lowers():
    """hist_method=fused: the routed single-round megakernel."""
    F, B, S = 4, 8, 2
    binned, g3, lids, kw, route = _round_args(F, B, S=S)
    fn = wf.make_fused_round(meta=_probe_meta(F, B), params=SplitParams(),
                             num_bins=B, precision="bf16x2",
                             deep_precision="bf16")
    lower_for_tpu(
        lambda b, g, l: fn(b, g, None, S, **kw,
                           route=dict(leaf_id=l, **route)),
        binned, g3, lids)


@pytest.mark.xfail(strict=True, raises=NotImplementedError, reason=CUMSUM)
def test_fused_wave_loop_lowers():
    """wave_loop_rounds > 1: the persistent multi-round kernel."""
    F, B, N, K, L = 4, 8, 64, 2, 8
    binned, g3, _, _, _ = _round_args(F, B, N)
    fn = wf.make_fused_wave_loop(
        meta=_probe_meta(F, B), params=SplitParams(), num_bins=B,
        precision="f32", deep_precision="f32", rounds=2)
    ft = jnp.zeros((L, 12), jnp.float32).at[0, 0].set(1.0)
    pool = jnp.zeros((L, F, B, 3), jnp.float32)
    lower_for_tpu(
        lambda b, g, l, f, p, k: fn(
            b, g, l, f, 1, k, K=K, slot_buckets=(K,), quant_buckets=(),
            max_depth=0, base_mask=jnp.ones(F, bool), pool=p),
        binned, g3, jnp.zeros(N, jnp.int32), ft, pool,
        jnp.zeros(2, jnp.uint32))


@pytest.fixture(scope="module")
def predictor():
    rng = np.random.RandomState(4)
    X = rng.randn(400, 6)
    y = (X[:, 0] - X[:, 1] + 0.3 * X[:, 2] > 0).astype(float)
    booster = lgb.train({"objective": "binary", "num_leaves": 7,
                         "min_data_in_leaf": 5, "verbosity": -1},
                        lgb.Dataset(X, label=y), num_boost_round=3,
                        verbose_eval=False)
    bp = BatchPredictor(booster._all_trees(), 1, 6, method="fused",
                        code_layout="u8", bucket_min=256)
    assert bp.fused_plan["eligible"], bp.fused_plan
    return bp, jnp.asarray(bp.encode(X[:256]))


@pytest.mark.xfail(strict=True, raises=NotImplementedError, reason=GATHER)
def test_serving_leaf_pallas_lowers(predictor):
    """predict_method=pallas: VMEM-pinned node tables."""
    bp, codes = predictor
    lower_for_tpu(
        lambda a, c: serving_leaf_pallas(
            a, c, n_steps=bp.depth, zero_code=bp.binner.zero_code,
            nan_code=bp.binner.nan_code),
        bp.arrays, codes)


@pytest.mark.parametrize("mode", ["leaf", "scores"])
@pytest.mark.xfail(strict=True, raises=NotImplementedError, reason=GATHER)
def test_serving_fused_pallas_lowers(predictor, mode):
    """predict_method=fused: the walk + accumulate megakernel."""
    bp, codes = predictor
    lower_for_tpu(
        lambda t, c: serving_fused_pallas(
            t, c, n_steps=bp.depth, zero_code=bp.binner.zero_code,
            nan_code=bp.binner.nan_code, K=1,
            tree_tile=bp.fused_plan["tree_tile"], mode=mode),
        bp._fused_tables, codes)
