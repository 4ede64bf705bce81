"""Fused wave-round megakernel (ops/wave_fused.py) — bit-parity and
gating tests.

The parity contract (ISSUE 13): ``hist_method=fused`` grows trees
BIT-IDENTICAL to the staged ``hist_method=pallas`` path (interpret mode
on CPU — the same arithmetic, fused vs staged scheduling) across the
golden matrix: binary / multiclass / DART / categorical+NaN (where the
fused gate falls back, so parity is the fallback working) / monotone+L1.
Model text equality is the strongest pin — structure, thresholds, leaf
values and metadata all byte-compare.

The int8sr tests pin the quantized lane: the fused kernel consumes the
SAME ``sr_quantize_g3`` rounding stream as the staged pass, so quantized
fused trees are bit-identical to quantized staged trees AND
bit-reproducible run-to-run given the seed; the eligibility gate (root
and <=4-slot ramp buckets never quantize; ``gpu_use_dp`` disables int8sr
with the staged path's warning) is shared, not re-implemented.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbmv1_tpu.models.grower_wave as gw
from lightgbmv1_tpu.basic import _objective_string
from lightgbmv1_tpu.config import Config
from lightgbmv1_tpu.io.dataset import BinnedDataset
from lightgbmv1_tpu.io.model_text import model_to_string
from lightgbmv1_tpu.models.gbdt import create_boosting

_INTERP = jax.default_backend() != "tpu"


def _binary_problem(n=1400, f=8, seed=0, with_nan=False):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    logit = (1.5 * X[:, 0] - X[:, 1] + 0.8 * X[:, 2] * X[:, 3]
             + 0.5 * np.sin(X[:, 4]))
    y = (logit + rng.randn(n) * 0.4 > 0).astype(np.float64)
    if with_nan:
        X[rng.rand(n, f) < 0.08] = np.nan
    return X, y


def _train_text(over, X, y, iters=3, **ds_kw):
    cfg = Config.from_dict({
        "objective": "binary", "num_leaves": 31, "min_data_in_leaf": 5,
        "verbosity": -1, "tree_growth": "leafwise",
        "leafwise_wave_size": 8, **over})
    ds = BinnedDataset.from_numpy(X, label=y, config=cfg, **ds_kw)
    gb = create_boosting(cfg, ds)
    for _ in range(iters):
        gb.train_one_iter(check_stop=False)
    trees = gb.materialize_host_trees()
    return model_to_string(
        trees, objective_string=_objective_string(cfg), num_class=1,
        num_tree_per_iteration=cfg.num_tree_per_iteration,
        feature_names=list(ds.feature_names),
        feature_infos=ds.feature_infos())


def _parity(over=None, problem=None, iters=3, **ds_kw):
    X, y = problem if problem is not None else _binary_problem()
    over = over or {}
    staged = _train_text({**over, "hist_method": "pallas"}, X, y,
                         iters=iters, **ds_kw)
    fused = _train_text({**over, "hist_method": "fused"}, X, y,
                        iters=iters, **ds_kw)
    assert staged == fused, "fused trees diverged from the staged path"
    return fused


def _warnings(fn):
    """Run ``fn`` capturing log lines; returns the captured list."""
    from lightgbmv1_tpu.utils import log

    lines = []
    log.register_callback(lines.append)
    try:
        fn()
    finally:
        log.register_callback(None)
    return lines


# ---------------------------------------------------------------------------
# Golden-matrix bit parity (interpret mode on CPU)
# ---------------------------------------------------------------------------


def test_fused_parity_binary():
    _parity()


@pytest.mark.slow    # tier-1 budget (ISSUE 13 discipline): the full suite,
                     # bench measure_fused and every dryrun_multichip
                     # capture (fused_parity_ok) still run this
def test_fused_parity_multiclass():
    rng = np.random.RandomState(3)
    n, f, k = 1200, 6, 3
    X = rng.randn(n, f)
    y = (np.abs(X[:, 0]) + X[:, 1] > 1).astype(np.float64) \
        + (X[:, 2] > 0.3).astype(np.float64)
    X2, y2 = X, np.clip(y, 0, k - 1)
    cfg_over = {"objective": "multiclass", "num_class": k,
                "metric": "multi_logloss"}

    def text(hm):
        cfg = Config.from_dict({
            "objective": "multiclass", "num_class": k, "num_leaves": 15,
            "min_data_in_leaf": 5, "verbosity": -1,
            "tree_growth": "leafwise", "leafwise_wave_size": 4,
            "hist_method": hm, **cfg_over})
        ds = BinnedDataset.from_numpy(X2, label=y2, config=cfg)
        gb = create_boosting(cfg, ds)
        for _ in range(2):
            gb.train_one_iter(check_stop=False)
        return model_to_string(
            gb.materialize_host_trees(),
            objective_string=_objective_string(cfg), num_class=k,
            num_tree_per_iteration=k,
            feature_names=list(ds.feature_names),
            feature_infos=ds.feature_infos())

    assert text("pallas") == text("fused")


@pytest.mark.slow    # tier-1 budget (ISSUE 13 discipline): the full suite,
                     # bench measure_fused and every dryrun_multichip
                     # capture (fused_parity_ok) still run this
def test_fused_parity_dart():
    _parity({"boosting": "dart", "drop_rate": 0.3, "drop_seed": 5},
            iters=4)


def test_fused_parity_monotone_l1():
    # monotone constraints ride the kernel's constraint inputs; L1 rides
    # the gain chain (threshold_l1) — both inside the fused scan
    _parity({"monotone_constraints": [1, -1, 0, 0, 0, 0, 0, 0],
             "lambda_l1": 0.5, "lambda_l2": 0.1})


@pytest.mark.slow    # tier-1 budget (ISSUE 13 discipline): the full suite,
                     # bench measure_fused and every dryrun_multichip
                     # capture (fused_parity_ok) still run this
def test_fused_parity_monotone_intermediate():
    # intermediate mode recomputes constraints per round OUTSIDE the
    # kernel and feeds them in as inputs — same values, same trees
    _parity({"monotone_constraints": [1, -1, 0, 0, 0, 0, 0, 0],
             "monotone_constraints_method": "intermediate"})


def test_fused_parity_nan_missing():
    _parity(problem=_binary_problem(with_nan=True))


@pytest.mark.slow    # tier-1 budget (ISSUE 13 discipline): the full suite,
                     # bench measure_fused and every dryrun_multichip
                     # capture (fused_parity_ok) still run this
def test_fused_categorical_falls_back_with_reason():
    """Categorical datasets run the staged path (the sorted-scan argsort
    has no kernel lowering) — parity holds trivially AND the fallback
    logs its taxonomy reason."""
    rng = np.random.RandomState(4)
    n = 1200
    Xc = rng.randn(n, 4)
    Xc[:, 0] = rng.randint(0, 8, n)
    y = ((Xc[:, 0] % 3 == 1).astype(np.float64)
         + (Xc[:, 1] > 0)).clip(0, 1)
    lines = _warnings(lambda: _parity({"verbosity": 0}, problem=(Xc, y),
                                      iters=2, categorical_features=[0]))
    assert any("categorical" in ln and "fused" in ln for ln in lines), lines


@pytest.mark.slow    # tier-1 budget (ISSUE 13 discipline): the full suite,
                     # bench measure_fused and every dryrun_multichip
                     # capture (fused_parity_ok) still run this
def test_fused_extra_trees_falls_back():
    lines = _warnings(
        lambda: _parity({"extra_trees": True, "extra_seed": 9,
                         "verbosity": 0}, iters=2))
    assert any("extra_trees" in ln for ln in lines), lines


@pytest.mark.slow    # tier-1 budget (ISSUE 13 discipline): the full suite,
                     # bench measure_fused and every dryrun_multichip
                     # capture (fused_parity_ok) still run this
def test_fused_parity_serialized_body():
    # async_wave_pipeline=false: no pending carry, the parent gather is
    # the plain (non-forwarded) table read feeding the kernel
    _parity({"async_wave_pipeline": False}, iters=2)


@pytest.mark.slow    # tier-1 budget (ISSUE 13 discipline): the full suite,
                     # bench measure_fused and every dryrun_multichip
                     # capture (fused_parity_ok) still run this
def test_fused_parity_legacy_store():
    # the legacy per-field store commits h_left/h_right separately —
    # the fused table update must feed it the same stacks
    _parity({"fused_bookkeeping": False}, iters=2)


def test_fused_pool_free_parity(monkeypatch):
    """Wide-F configs skip the per-leaf histogram state: the fused
    kernel then accumulates all 2S children from scratch in VMEM and
    emits ONLY the packed SplitInfo (no histogram output at all)."""
    monkeypatch.setattr(gw, "_SUB_STATE_CAP_BYTES", 0)
    _parity()


@pytest.mark.slow    # tier-1 budget (ISSUE 13 discipline): the full suite,
                     # bench measure_fused and every dryrun_multichip
                     # capture (fused_parity_ok) still run this
def test_fused_slot_buckets_parity(monkeypatch):
    """The sliced ramp buckets (4/16/K) each trace their own fused
    kernel variant; parity must hold across the whole ladder."""
    monkeypatch.setattr(gw, "_BUCKET_MIN_N", 1)
    _parity({"num_leaves": 63, "leafwise_wave_size": 24})


# ---------------------------------------------------------------------------
# Single-pass round (ISSUE 15): partition + valid routing fused in-kernel
# ---------------------------------------------------------------------------


def _valid_problem(seed=7, n=500, f=8):
    rng = np.random.RandomState(seed)
    Xv = rng.randn(n, f)
    yv = (1.2 * Xv[:, 0] - Xv[:, 1] + rng.randn(n) * 0.3 > 0) \
        .astype(np.float64)
    return Xv, yv


def _train_with_valid(over, X, y, Xv, yv, iters=3):
    cfg = Config.from_dict({
        "objective": "binary", "num_leaves": 31, "min_data_in_leaf": 5,
        "verbosity": -1, "tree_growth": "leafwise",
        "leafwise_wave_size": 8, "metric": "binary_logloss", **over})
    ds = BinnedDataset.from_numpy(X, label=y, config=cfg)
    dv = BinnedDataset.from_numpy(Xv, label=yv, config=cfg, reference=ds)
    gb = create_boosting(cfg, ds)
    gb.add_valid(dv, "v")
    for _ in range(iters):
        gb.train_one_iter(check_stop=False)
    text = model_to_string(
        gb.materialize_host_trees(),
        objective_string=_objective_string(cfg), num_class=1,
        num_tree_per_iteration=cfg.num_tree_per_iteration,
        feature_names=list(ds.feature_names),
        feature_infos=ds.feature_infos())
    evals = [(name, float(v)) for (_, name, v, _) in gb.eval_valid()]
    return text, evals


def _valid_parity(over=None):
    """Fused vs staged with a valid set attached: the fused run routes
    valid rows through the kernel decision stage (route_rows) — valid
    METRICS must be bit-equal, not just trees (ISSUE 15 satellite)."""
    X, y = _binary_problem()
    Xv, yv = _valid_problem()
    over = over or {}
    t_s, ev_s = _train_with_valid({**over, "hist_method": "pallas"},
                                  X, y, Xv, yv)
    t_f, ev_f = _train_with_valid({**over, "hist_method": "fused"},
                                  X, y, Xv, yv)
    assert t_s == t_f, "fused trees diverged with a valid set attached"
    assert ev_s == ev_f, (
        f"fused valid metrics diverged from staged: {ev_f} vs {ev_s}")


@pytest.mark.slow    # tier-1 budget (ISSUE 15 discipline): the full
                     # suite, bench measure_fused and every
                     # dryrun_multichip capture (valid-score equality
                     # behind partition_fused_parity_ok) still run this;
                     # the fast routing-kernel test below keeps an
                     # in-tier-1 pin on the decision stage itself
def test_fused_valid_routing_parity_pipelined():
    # the pipelined drain (route_pending) rides the fused router
    _valid_parity()


@pytest.mark.slow    # tier-1 budget (ISSUE 13 discipline): the full suite,
                     # bench measure_fused and every dryrun_multichip
                     # capture (partition_fused_parity_ok) still run this
def test_fused_valid_routing_parity_serialized():
    # async_wave_pipeline=false: valids route IN-ROUND through the
    # kernel stage (the second route_rows call site)
    _valid_parity({"async_wave_pipeline": False})


@pytest.mark.slow    # tier-1 budget (ISSUE 13 discipline): the full suite,
                     # bench measure_fused and every dryrun_multichip
                     # capture (fused_parity_ok) still run this
def test_fused_parity_bagging_feature_fraction():
    # bagging zeroes out-of-bag gradients; per-node column sampling
    # feeds the kernel's per-child mask inputs — both must survive the
    # routed single-pass round bit-exactly
    _parity({"bagging_fraction": 0.6, "bagging_freq": 1,
             "bagging_seed": 5, "feature_fraction": 0.75,
             "feature_fraction_bynode": 0.8,
             "feature_fraction_seed": 7}, iters=3)


def test_fused_routing_kernel_matches_staged_partition(rng):
    """Kernel-level (no grower): the routed megakernel's emitted leaf
    ids, the routing-only valid-set kernel (fused_route_rows) and the
    staged (S, N) partition formula must agree EXACTLY — including the
    NaN/zero missing-direction rules (shared split.go_left_rule)."""
    from lightgbmv1_tpu.ops import wave_fused as wf
    from lightgbmv1_tpu.ops.split import (NO_CONSTRAINT, SplitParams,
                                          go_left_rule)

    F, B, N, S, L = 5, 16, 777, 3, 12
    meta = _unit_meta(F, B)._replace(
        missing_type=jnp.asarray([1, 2, 0, 0, 0], jnp.int32),
        nan_bin=jnp.asarray([B - 1, -1, -1, -1, -1], jnp.int32),
        zero_bin=jnp.asarray([0, 3, 0, 0, 0], jnp.int32))
    binned = jnp.asarray(rng.randint(0, B, (F, N)).astype(np.uint8))
    g3 = jnp.asarray(np.stack(
        [rng.randn(N), np.abs(rng.randn(N)) + 0.1, np.ones(N)],
        axis=1).astype(np.float32))
    lids = jnp.asarray(rng.randint(0, L, N).astype(np.int32))
    feats = jnp.asarray(rng.randint(0, F, S).astype(np.int32))
    thrs = jnp.asarray(rng.randint(0, B, S).astype(np.int32))
    dls = jnp.asarray(rng.rand(S) < 0.5)
    leafs = jnp.asarray(rng.choice(L, S, replace=False).astype(np.int32))
    nls = jnp.asarray((np.arange(S) + L).astype(np.int32))

    # staged partition (grower_wave go_left_s formula, shared rule)
    bk = jax.vmap(lambda f: binned[f])(feats).astype(jnp.int32)
    gl = go_left_rule(bk, thrs[:, None], dls[:, None],
                      meta.missing_type[feats][:, None],
                      meta.nan_bin[feats][:, None],
                      meta.zero_bin[feats][:, None])
    mine = lids[None, :] == leafs[:, None]
    want = np.asarray(lids + jnp.sum(
        jnp.where(mine & (~gl), nls[:, None] - lids[None, :], 0), axis=0))

    params = SplitParams(min_data_in_leaf=5.0)
    fn = wf.make_fused_round(meta=meta, params=params, num_bins=B,
                             precision="bf16x2", deep_precision="bf16",
                             interpret=_INTERP)
    assert fn.supports_route
    # the routing-only kernel (the valid-set lane)
    got_v = fn.route_rows(binned, lids, feats=feats, thrs=thrs, dls=dls,
                          leafs=leafs, nls=nls, num_leaves=L + S)
    np.testing.assert_array_equal(np.asarray(got_v), want)
    # the megakernel's routed train lane: emitted leaf ids + packed
    # SplitInfo equal to the label-input (PR 13) kernel fed the staged
    # partition's label
    C = 2 * S
    siota = jnp.arange(S, dtype=jnp.int32)
    label = jnp.sum(jnp.where(
        mine, 2 * siota[:, None] + (~gl).astype(jnp.int32) - 2 * S, 0),
        axis=0) + 2 * S
    csums = jnp.asarray(np.abs(rng.randn(C, 3)).astype(np.float32))
    kw = dict(mask=jnp.ones((C, F), bool), csums=csums,
              constr=jnp.tile(jnp.asarray(NO_CONSTRAINT, jnp.float32),
                              (C, 1)),
              depth=jnp.ones(C, jnp.int32),
              pout=jnp.zeros(C, jnp.float32))
    p_lab, _, _ = fn(binned, g3, label, S, **kw)
    p_rt, _, _, nl = fn(binned, g3, None, S, **kw,
                        route=dict(leaf_id=lids, feats=feats, thrs=thrs,
                                   dls=dls, leafs=leafs, nls=nls,
                                   num_leaves=L + S))
    np.testing.assert_array_equal(np.asarray(nl), want)
    np.testing.assert_array_equal(np.asarray(p_lab), np.asarray(p_rt))


# ---------------------------------------------------------------------------
# int8sr: shared quantization stream, shared eligibility gate
# ---------------------------------------------------------------------------


def _int8sr_over():
    return {"num_leaves": 64, "leafwise_wave_size": 32,
            "hist_dtype_deep": "int8sr"}


@pytest.mark.slow    # tier-1 budget (ISSUE 13 discipline): the full suite,
                     # bench measure_fused and every dryrun_multichip
                     # capture (fused_parity_ok) still run this
def test_fused_int8sr_parity_and_reproducible(monkeypatch):
    monkeypatch.setattr(gw, "_BUCKET_MIN_N", 1)
    X, y = _binary_problem(n=1600)
    t1 = _train_text({**_int8sr_over(), "hist_method": "fused"}, X, y,
                     iters=2)
    t2 = _train_text({**_int8sr_over(), "hist_method": "fused"}, X, y,
                     iters=2)
    assert t1 == t2, "int8sr fused trees not bit-reproducible"
    staged = _train_text({**_int8sr_over(), "hist_method": "pallas"}, X, y,
                         iters=2)
    assert t1 == staged, "int8sr fused diverged from staged int8sr"


@pytest.mark.slow    # tier-1 budget (ISSUE 13 discipline): the full suite,
                     # bench measure_fused and every dryrun_multichip
                     # capture (fused_parity_ok) still run this
def test_fused_int8sr_gate_root_and_small_ramps_never_quantize(
        monkeypatch):
    """The fused path must route through the SAME quant gate as the
    staged one: sr_quantize_g3 is only ever traced for the eligible
    buckets (the sustained K bucket and the 16-slot ramp of a K>16
    wave) — never for the root pass or the <=4-slot ramps."""
    import lightgbmv1_tpu.ops.quantize as qz

    monkeypatch.setattr(gw, "_BUCKET_MIN_N", 1)
    calls = []
    orig = qz.sr_quantize_g3

    def probe(g3, label, nslots, key, axis_name=None):
        calls.append(int(nslots))
        return orig(g3, label, nslots, key, axis_name=axis_name)

    monkeypatch.setattr(qz, "sr_quantize_g3", probe)
    X, y = _binary_problem(n=1600)
    _train_text({**_int8sr_over(), "hist_method": "fused"}, X, y, iters=1)
    assert calls, "int8sr buckets never engaged"
    K = 32
    # sub mode quantizes the smaller-child slots: eligible buckets are
    # S == K (sustained) and S == 16 (the big-wave ramp harvest)
    assert set(calls) <= {16, K, 2 * 16, 2 * K}, calls
    assert all(c > 4 for c in calls), f"root/small ramp quantized: {calls}"


@pytest.mark.slow    # tier-1 budget (ISSUE 13 discipline): the full suite,
                     # bench measure_fused and every dryrun_multichip
                     # capture (fused_parity_ok) still run this
def test_fused_int8sr_disabled_by_gpu_use_dp(monkeypatch):
    monkeypatch.setattr(gw, "_BUCKET_MIN_N", 1)
    X, y = _binary_problem(n=1200)
    lines = _warnings(lambda: _train_text(
        {**_int8sr_over(), "hist_method": "fused", "gpu_use_dp": True,
         "verbosity": 0}, X, y, iters=1))
    assert any("int8sr conflicts with gpu_use_dp" in ln
               for ln in lines), lines


# ---------------------------------------------------------------------------
# Persistent multi-round wave loop (ROADMAP item 1): R rounds per launch,
# frontier state resident in VMEM (ops/wave_fused.make_fused_wave_loop)
# ---------------------------------------------------------------------------


_LOOP_ENGAGED = "persistent multi-round wave loop engaged"


def _loop_problem():
    # smaller than _binary_problem: the loop tests train staged + fused
    # and tier-1 carries several of them
    return _binary_problem(n=700, f=6, seed=11)


def test_wave_loop_parity_r2():
    # the loop's whole contract: R in-VMEM rounds == R staged rounds,
    # bit-for-bit, trees byte-compared via model text
    _parity({"wave_loop_rounds": 2}, problem=_loop_problem(), iters=2)


def test_wave_loop_parity_r4_and_engagement_log():
    lines = _warnings(lambda: _parity(
        {"wave_loop_rounds": 4, "verbosity": 1},
        problem=_loop_problem(), iters=2))
    assert any(_LOOP_ENGAGED in ln for ln in lines), lines


def test_wave_loop_planner_gates():
    """plan_wave_loop is the loop's whole eligibility story — every
    fallback leg returns its taxonomy reason (recorded verbatim in the
    BENCH record), and rounds==1 NEVER builds a loop."""
    from lightgbmv1_tpu.ops import wave_fused as wf

    base = dict(N=4096, F=8, num_bins=32, K=32, L=64, use_sub=True,
                slot_buckets=(4, 16, 32), quant_buckets=())
    plan = wf.plan_wave_loop(rounds=6, **base)
    assert plan["eligible"] and plan["rounds"] == 6, plan
    assert plan["total_bytes"] <= plan["vmem_budget"]
    assert wf.plan_wave_loop(rounds=1, **base)["reason"] \
        == "wave_loop_rounds=1 (single-round dispatch)"
    assert wf.plan_wave_loop(
        rounds=10_000, **base)["rounds"] == wf._LOOP_MAX_ROUNDS
    assert "MAX_LANES" in wf.plan_wave_loop(
        rounds=6, **{**base, "F": 128})["reason"]
    assert "monotone" in wf.plan_wave_loop(
        rounds=6, use_mc=True, **base)["reason"]
    assert "int8sr-in-loop" in wf.plan_wave_loop(
        rounds=6, precision="bf16x2",
        **{**base, "quant_buckets": (16, 32)})["reason"]
    assert "deep-precision" in wf.plan_wave_loop(
        rounds=6, deep_precision="bf16", **base)["reason"]
    assert "VMEM budget" in wf.plan_wave_loop(
        rounds=6, vmem_budget=1 << 10, **base)["reason"]


def test_fused_lowering_failure_propagates(monkeypatch):
    # there is no compile probe and no staged fallback behind it: when
    # the kernel the user asked for cannot be lowered, train() raises
    # with the compiler's message (single-round and wave-loop alike)
    import lightgbmv1_tpu as lgb
    from lightgbmv1_tpu.ops import wave_fused as wf

    assert not hasattr(wf, "backend_lowers_fused")
    assert not hasattr(wf, "backend_lowers_fused_loop")

    def boom(*a, **k):
        raise NotImplementedError(
            "Unimplemented primitive in Pallas TPU lowering: cumsum")

    # the kernel body's own cumsum site (child_scan_residue), which is
    # where the TPU lowering fails; the staged path does not use wf's name
    monkeypatch.setattr(wf, "scan_left_sums", boom)
    rng = np.random.RandomState(3)
    X = rng.randn(600, 6)
    y = (X[:, 0] - X[:, 1] > 0).astype(float)
    base = {"objective": "binary", "num_leaves": 15, "max_bin": 15,
            "min_data_in_leaf": 5, "verbosity": -1,
            "leafwise_wave_size": 4, "hist_method": "fused"}
    for extra in ({}, {"wave_loop_rounds": 4}):
        with pytest.raises(NotImplementedError, match="cumsum"):
            lgb.train({**base, **extra}, lgb.Dataset(X, label=y),
                      num_boost_round=1, verbose_eval=False)


def test_wave_loop_ffbynode_falls_back_with_reason():
    # per-node column sampling draws a fresh mask every round — the loop
    # kernel freezes round-0 state, so the trainer must refuse the loop
    # (logged reason) and run the single-round fused dispatch: parity
    # with the staged path is the fallback working
    lines = _warnings(lambda: _parity(
        {"wave_loop_rounds": 2, "feature_fraction_bynode": 0.8,
         "feature_fraction_seed": 7, "verbosity": 0},
        problem=_loop_problem(), iters=2))
    assert any("feature_fraction_bynode" in ln and "single-round" in ln
               for ln in lines), lines


@pytest.mark.slow    # tier-1 budget (ISSUE 13 discipline): the full suite,
                     # bench measure_fused_waveloop (fused_loop_ok) and
                     # every dryrun_multichip capture still run this
def test_wave_loop_parity_multiclass():
    rng = np.random.RandomState(3)
    n, f, k = 1200, 6, 3
    X = rng.randn(n, f)
    y = np.clip((np.abs(X[:, 0]) + X[:, 1] > 1).astype(np.float64)
                + (X[:, 2] > 0.3).astype(np.float64), 0, k - 1)

    def text(over):
        cfg = Config.from_dict({
            "objective": "multiclass", "num_class": k, "num_leaves": 15,
            "min_data_in_leaf": 5, "verbosity": -1,
            "tree_growth": "leafwise", "leafwise_wave_size": 4,
            "metric": "multi_logloss", **over})
        ds = BinnedDataset.from_numpy(X, label=y, config=cfg)
        gb = create_boosting(cfg, ds)
        for _ in range(2):
            gb.train_one_iter(check_stop=False)
        return model_to_string(
            gb.materialize_host_trees(),
            objective_string=_objective_string(cfg), num_class=k,
            num_tree_per_iteration=k,
            feature_names=list(ds.feature_names),
            feature_infos=ds.feature_infos())

    assert text({"hist_method": "pallas"}) \
        == text({"hist_method": "fused", "wave_loop_rounds": 3})


@pytest.mark.slow    # tier-1 budget (ISSUE 13 discipline): the full suite,
                     # bench measure_fused_waveloop (fused_loop_ok) and
                     # every dryrun_multichip capture still run this
def test_wave_loop_parity_dart():
    # DART re-weights trees BETWEEN iterations — per-iteration g3 feeds
    # the loop unchanged, so R-round launches must not perturb it
    _parity({"boosting": "dart", "drop_rate": 0.3, "drop_seed": 5,
             "wave_loop_rounds": 2}, iters=4)


@pytest.mark.slow    # tier-1 budget (ISSUE 13 discipline): the full suite,
                     # bench measure_fused_waveloop (fused_loop_ok) and
                     # every dryrun_multichip capture still run this
def test_wave_loop_parity_serialized_body():
    # async_wave_pipeline=false is also the schedule loop mode itself
    # runs under (nothing defers across a launch) — the flag must stay
    # a no-op for trees either way
    _parity({"async_wave_pipeline": False, "wave_loop_rounds": 2},
            iters=2)


@pytest.mark.slow    # tier-1 budget (ISSUE 13 discipline): the full suite,
                     # bench measure_fused_waveloop (fused_loop_ok) and
                     # every dryrun_multichip capture still run this
def test_wave_loop_int8sr_parity_and_reproducible(monkeypatch):
    """The quantized lane THROUGH the loop: int8sr rounds draw the same
    fold_in(key, 8_000_011 + num_leaves) stream in-kernel, accumulate
    exact integers through the f32 path, and dequantize with the staged
    subtraction's exact op shape — trees bit-equal to staged int8sr and
    bit-reproducible run-to-run.  hist_dtype=f32 is the planner's
    int8sr-in-loop requirement; the engagement line proves the matrix
    point is not vacuously running single-round."""
    monkeypatch.setattr(gw, "_BUCKET_MIN_N", 1)
    X, y = _binary_problem(n=800, f=6, seed=0)
    over = {"num_leaves": 48, "leafwise_wave_size": 32, "max_bin": 31,
            "hist_dtype": "f32", "hist_dtype_deep": "int8sr",
            "wave_loop_rounds": 2, "verbosity": 1}
    lines = _warnings(lambda: _parity(over, problem=(X, y), iters=2))
    assert any(_LOOP_ENGAGED in ln for ln in lines), lines
    t1 = _train_text({**over, "hist_method": "fused"}, X, y, iters=2)
    t2 = _train_text({**over, "hist_method": "fused"}, X, y, iters=2)
    assert t1 == t2, "int8sr loop trees not bit-reproducible"


@pytest.mark.slow    # tier-1 budget (ISSUE 13 discipline): the full suite,
                     # bench measure_fused_waveloop (fused_loop_ok) and
                     # every dryrun_multichip capture still run this
def test_wave_loop_int8sr_default_dtype_falls_back(monkeypatch):
    # int8sr under the DEFAULT bf16x2 base dtype: exact-integer f32
    # accumulate unavailable -> the planner refuses the loop with its
    # taxonomy reason and the single-round dispatch keeps parity
    monkeypatch.setattr(gw, "_BUCKET_MIN_N", 1)
    X, y = _binary_problem(n=800, f=6, seed=0)
    lines = _warnings(lambda: _parity(
        {"num_leaves": 48, "leafwise_wave_size": 32, "max_bin": 31,
         "hist_dtype_deep": "int8sr", "wave_loop_rounds": 2,
         "verbosity": 0}, problem=(X, y), iters=2))
    assert any("int8sr-in-loop" in ln for ln in lines), lines


@pytest.mark.slow    # tier-1 budget (ISSUE 13 discipline): the full suite,
                     # bench measure_fused_waveloop (fused_loop_ok) and
                     # every dryrun_multichip capture still run this
def test_wave_loop_categorical_nan_never_engages():
    # categorical datasets never reach the loop (the fused gate falls
    # back BEFORE it) — parity holds through the staged path and no
    # engagement line may appear
    rng = np.random.RandomState(4)
    n = 900
    Xc = rng.randn(n, 4)
    Xc[:, 0] = rng.randint(0, 8, n)
    Xc[rng.rand(n, 4) < 0.05] = np.nan
    Xc[:, 0] = np.abs(np.nan_to_num(Xc[:, 0]))
    y = ((Xc[:, 0] % 3 == 1).astype(np.float64)
         + (Xc[:, 1] > 0)).clip(0, 1)
    lines = _warnings(lambda: _parity(
        {"wave_loop_rounds": 2, "verbosity": 1}, problem=(Xc, y),
        iters=2, categorical_features=[0]))
    assert any("categorical" in ln for ln in lines), lines
    assert not any(_LOOP_ENGAGED in ln for ln in lines), lines


@pytest.mark.slow    # tier-1 budget (ISSUE 13 discipline): the full suite,
                     # bench measure_fused_waveloop (fused_loop_ok) and
                     # every dryrun_multichip capture still run this
def test_wave_loop_monotone_falls_back_with_reason():
    lines = _warnings(lambda: _parity(
        {"wave_loop_rounds": 2, "verbosity": 0,
         "monotone_constraints": [1, -1, 0, 0, 0, 0]},
        problem=_loop_problem(), iters=2))
    assert any("monotone" in ln and "single-round" in ln
               for ln in lines), lines


# ---------------------------------------------------------------------------
# Kernel-level unit parity (no grower in the loop)
# ---------------------------------------------------------------------------


def _unit_meta(F, B):
    from lightgbmv1_tpu.ops.split import FeatureMeta

    return FeatureMeta(
        num_bins=jnp.full(F, B, jnp.int32),
        missing_type=jnp.zeros(F, jnp.int32),
        nan_bin=jnp.full(F, -1, jnp.int32),
        zero_bin=jnp.zeros(F, jnp.int32),
        is_categorical=jnp.zeros(F, bool),
        usable=jnp.ones(F, bool),
        monotone_type=jnp.zeros(F, jnp.int32),
    )


def test_fused_round_matches_staged_split(rng):
    from lightgbmv1_tpu.ops import wave_fused as wf
    from lightgbmv1_tpu.ops.hist_pallas import hist_leaves_pallas
    from lightgbmv1_tpu.ops.split import (NO_CONSTRAINT, SplitParams,
                                          find_best_split)

    F, B, N, S = 5, 16, 777, 3
    C = 2 * S
    meta = _unit_meta(F, B)
    params = SplitParams(min_data_in_leaf=5.0)
    binned = jnp.asarray(rng.randint(0, B, (F, N)).astype(np.uint8))
    g3 = jnp.asarray(np.stack(
        [rng.randn(N), np.abs(rng.randn(N)) + 0.1, np.ones(N)],
        axis=1).astype(np.float32))
    label = jnp.asarray(rng.randint(0, C + 1, N).astype(np.int32))
    h = hist_leaves_pallas(binned, g3, label, C + 1, B,
                           precision="bf16x2", interpret=_INTERP)[:C]
    csums = h.sum(axis=(1, 2))
    mask = jnp.ones((C, F), bool)
    nc = jnp.asarray(NO_CONSTRAINT, jnp.float32)
    ref = jax.vmap(lambda hh, ps: find_best_split(
        hh, ps, meta, mask[0], params, nc, 1, 0.0, 0.0, None, None)
    )(h, csums)
    fn = wf.make_fused_round(meta=meta, params=params, num_bins=B,
                             precision="bf16x2", deep_precision="bf16",
                             interpret=_INTERP)
    packed, hsm, _ = fn(binned, g3, label, S, mask=mask, csums=csums,
                        constr=jnp.tile(nc, (C, 1)),
                        depth=jnp.ones(C, jnp.int32),
                        pout=jnp.zeros(C, jnp.float32))
    assert hsm is None                      # pool-free: no hist output
    got = wf.unpack_children(packed, B)
    for name in ("gain", "feature", "threshold_bin", "default_left",
                 "left_sum", "right_sum"):
        np.testing.assert_array_equal(np.asarray(getattr(ref, name)),
                                      np.asarray(getattr(got, name)),
                                      err_msg=name)


def test_pack_unpack_roundtrip(rng):
    from lightgbmv1_tpu.ops import wave_fused as wf
    from lightgbmv1_tpu.ops.split import SplitResult

    C, B = 6, 64
    W = -(-B // 32)
    res = SplitResult(
        gain=jnp.asarray(rng.randn(C).astype(np.float32)),
        feature=jnp.asarray(rng.randint(0, 9, C).astype(np.int32)),
        threshold_bin=jnp.asarray(rng.randint(0, B, C).astype(np.int32)),
        default_left=jnp.asarray(rng.rand(C) < 0.5),
        left_sum=jnp.asarray(rng.randn(C, 3).astype(np.float32)),
        right_sum=jnp.asarray(rng.randn(C, 3).astype(np.float32)),
        is_cat=jnp.zeros(C, bool),
        cat_bitset=jnp.zeros((C, W), jnp.uint32),
    )
    back = wf.unpack_children(wf.pack_children(res), B)
    for name in res._fields:
        np.testing.assert_array_equal(np.asarray(getattr(res, name)),
                                      np.asarray(getattr(back, name)),
                                      err_msg=name)


# ---------------------------------------------------------------------------
# Feature-parallel: fused kernel per feature slice + SplitInfo election
# ---------------------------------------------------------------------------


@pytest.mark.slow    # tier-1 budget: dryrun_multichip asserts this per
                     # driver capture (fused_parity_ok)
def test_fused_feature_parallel_parity():
    X, y = _binary_problem(n=1200, f=6)
    serial = _train_text({"hist_method": "fused"}, X, y, iters=2)
    fp = _train_text({"hist_method": "fused", "tree_learner": "feature",
                      "num_shards": 2}, X, y, iters=2)
    assert serial == fp, "feature-parallel fused diverged from serial"


def test_config_rejects_unknown_hist_method():
    with pytest.raises(ValueError, match="hist_method"):
        Config.from_dict({"objective": "binary", "hist_method": "warp"})


# ---------------------------------------------------------------------------
# Sub-byte bin residency (ISSUE 18): 4-bit packed bins through the fused
# round, the persistent wave loop, and the width-specialized kernel ladder
# ---------------------------------------------------------------------------


_PACKED_ENGAGED = "4-bit packed bins engaged"


def _packed_parity(over=None, problem=None, iters=3, **ds_kw):
    """The packed contract: bin_layout=packed4 trees are byte-identical
    to the unpacked fused AND staged paths — four texts, one string."""
    X, y = problem if problem is not None else _binary_problem()
    over = {"max_bin": 15, **(over or {})}
    texts = {
        (hm, bl): _train_text(
            {**over, "hist_method": hm, "bin_layout": bl}, X, y,
            iters=iters, **ds_kw)
        for hm in ("pallas", "fused") for bl in ("u8", "packed4")}
    ref = texts[("pallas", "u8")]
    for key, t in texts.items():
        assert t == ref, f"{key} diverged from staged u8 trees"
    return ref


def test_pack4bit_roundtrip_and_odd_tail(rng):
    """pack/unpack inverse across even and odd F; an odd-F tail's
    phantom hi nibble is ZERO (the inert feature the kernels pad meta
    for) and unpack slices it away."""
    from lightgbmv1_tpu.ops.hist_pallas import pack4bit, unpack4bit

    for F in (1, 2, 7, 8):
        a = rng.randint(0, 16, (F, 33)).astype(np.uint8)
        p = pack4bit(a)
        assert p.shape == (-(-F // 2), 33)
        np.testing.assert_array_equal(unpack4bit(p, F), a)
        np.testing.assert_array_equal(
            np.asarray(unpack4bit(jnp.asarray(p), F)), a)
        if F % 2:
            np.testing.assert_array_equal(np.asarray(p[-1] >> 4),
                                          np.zeros(33, np.uint8))


def test_kernel_width_ladder():
    # the histogram16/64/256 rungs: callers specialize tiling on the
    # rung, and ONLY the <=16 rung admits nibble-packed bins
    from lightgbmv1_tpu.ops.hist_pallas import kernel_width

    assert kernel_width(2) == 16
    assert kernel_width(16) == 16
    assert kernel_width(17) == 64
    assert kernel_width(64) == 64
    assert kernel_width(65) == 256
    assert kernel_width(256) == 256
    with pytest.raises(ValueError, match="num_bins <= 256"):
        kernel_width(257)


def test_packed_parity_binary():
    # tier-1 arm of the packed parity family, sized for the wall budget;
    # the full-shape cells below (odd F, wave loop, multiclass, DART,
    # int8sr, valid routing) run in the full suite and every capture
    _packed_parity(problem=_binary_problem(n=700, f=6, seed=11), iters=2)


@pytest.mark.slow    # tier-1 budget (ISSUE 18 discipline): the full suite,
                     # bench measure_packed (packed_ok) and every
                     # dryrun_multichip capture still run this
def test_packed_parity_odd_f():
    # odd F exercises the phantom hi-nibble feature end to end: it must
    # be inert in the scan (never picked) and in routing
    _packed_parity(problem=_binary_problem(n=1000, f=7, seed=2))


@pytest.mark.slow    # tier-1 budget (ISSUE 18 discipline): the full suite,
                     # bench measure_packed (packed_ok) and every
                     # dryrun_multichip capture still run this
def test_packed_wave_loop_parity_r4():
    # the packed matrix stays resident across R in-VMEM rounds: the loop
    # kernel's decision lane decodes nibbles per round
    _packed_parity({"wave_loop_rounds": 4}, problem=_loop_problem(),
                   iters=2)


@pytest.mark.slow    # tier-1 budget (ISSUE 18 discipline): the full suite,
                     # bench measure_packed (packed_ok) and every
                     # dryrun_multichip capture still run this
def test_packed_wave_loop_parity_odd_f():
    _packed_parity({"wave_loop_rounds": 4},
                   problem=_binary_problem(n=1000, f=7, seed=2), iters=2)


@pytest.mark.slow    # tier-1 budget (ISSUE 18 discipline): the full suite,
                     # bench measure_packed (packed_ok) and every
                     # dryrun_multichip capture still run this
def test_packed_parity_multiclass():
    rng = np.random.RandomState(3)
    n, f, k = 1200, 6, 3
    X = rng.randn(n, f)
    y = np.clip((np.abs(X[:, 0]) + X[:, 1] > 1).astype(np.float64)
                + (X[:, 2] > 0.3).astype(np.float64), 0, k - 1)

    def text(over):
        cfg = Config.from_dict({
            "objective": "multiclass", "num_class": k, "num_leaves": 15,
            "min_data_in_leaf": 5, "verbosity": -1, "max_bin": 15,
            "tree_growth": "leafwise", "leafwise_wave_size": 4,
            "metric": "multi_logloss", **over})
        ds = BinnedDataset.from_numpy(X, label=y, config=cfg)
        gb = create_boosting(cfg, ds)
        for _ in range(2):
            gb.train_one_iter(check_stop=False)
        return model_to_string(
            gb.materialize_host_trees(),
            objective_string=_objective_string(cfg), num_class=k,
            num_tree_per_iteration=k,
            feature_names=list(ds.feature_names),
            feature_infos=ds.feature_infos())

    ref = text({"hist_method": "pallas"})
    assert ref == text({"hist_method": "fused", "bin_layout": "packed4"})
    assert ref == text({"hist_method": "pallas", "bin_layout": "packed4"})


@pytest.mark.slow    # tier-1 budget (ISSUE 18 discipline): the full suite,
                     # bench measure_packed (packed_ok) and every
                     # dryrun_multichip capture still run this
def test_packed_parity_dart():
    _packed_parity({"boosting": "dart", "drop_rate": 0.3,
                    "drop_seed": 5}, iters=4)


@pytest.mark.slow    # tier-1 budget (ISSUE 18 discipline): the full suite,
                     # bench measure_packed (packed_ok) and every
                     # dryrun_multichip capture still run this
def test_packed_parity_int8sr(monkeypatch):
    # the quantized lane consumes the UNPACKED VMEM view — the same
    # sr_quantize_g3 stream, so packed int8sr == unpacked int8sr
    monkeypatch.setattr(gw, "_BUCKET_MIN_N", 1)
    _packed_parity({"num_leaves": 48, "leafwise_wave_size": 32,
                    "hist_dtype_deep": "int8sr"},
                   problem=_binary_problem(n=1600), iters=2)


@pytest.mark.slow    # tier-1 budget (ISSUE 18 discipline): the full suite,
                     # bench measure_packed (packed_ok) and every
                     # dryrun_multichip capture still run this
def test_packed_valid_routing_parity():
    """Valid rows route through the packed decision lane (nibble decode
    in decision_bins / the loop kernel): valid METRICS and trees must
    be bit-equal across layouts AND vs the staged path."""
    X, y = _binary_problem()
    Xv, yv = _valid_problem()
    for extra in ({}, {"wave_loop_rounds": 4}):
        over = {"max_bin": 15, **extra}
        t_s, ev_s = _train_with_valid(
            {**over, "hist_method": "pallas"}, X, y, Xv, yv)
        t_u, ev_u = _train_with_valid(
            {**over, "hist_method": "fused"}, X, y, Xv, yv)
        t_p, ev_p = _train_with_valid(
            {**over, "hist_method": "fused", "bin_layout": "packed4"},
            X, y, Xv, yv)
        assert t_s == t_u == t_p, f"trees diverged ({extra})"
        assert ev_s == ev_u == ev_p, f"valid metrics diverged ({extra})"


@pytest.mark.slow    # tier-1 budget (ISSUE 18 discipline): the full suite,
                     # bench measure_packed (packed_ok) and every
                     # dryrun_multichip capture still run this
def test_packed_num_bins_boundary():
    """num_bins 15/16 fit a nibble (no refusal, trees bit-equal to
    unpacked); 17 exceeds 4 bits — an explicit packed4 falls back to u8
    with the staged warning and trains unpacked."""
    X, y = _binary_problem()
    for mb in (15, 16):
        texts = {}
        lines = _warnings(lambda: texts.update(
            (bl, _train_text({"hist_method": "fused", "max_bin": mb,
                              "bin_layout": bl, "verbosity": 0},
                             X, y, iters=2))
            for bl in ("u8", "packed4")))
        assert not any("storing u8 bins" in ln for ln in lines), (mb, lines)
        assert texts["u8"] == texts["packed4"], f"max_bin={mb} diverged"
    lines = _warnings(lambda: _train_text(
        {"hist_method": "fused", "max_bin": 17, "bin_layout": "packed4",
         "verbosity": 0}, X, y, iters=1))
    assert any("needs more than 4 bits" in ln
               and "storing u8 bins" in ln for ln in lines), lines


def test_packed_engagement_logged_once():
    X, y = _binary_problem()
    lines = _warnings(lambda: _train_text(
        {"hist_method": "fused", "bin_layout": "packed4", "max_bin": 15,
         "verbosity": 1}, X, y, iters=3))
    hits = [ln for ln in lines if _PACKED_ENGAGED in ln]
    assert len(hits) == 1, lines


def test_packed_refused_by_gpu_use_dp():
    # gpu_use_dp pins the double-precision staged lane — packed4 refuses
    # with the staged warning and the run proceeds on u8 bins
    X, y = _binary_problem()
    lines = _warnings(lambda: _train_text(
        {"hist_method": "fused", "bin_layout": "packed4", "max_bin": 15,
         "gpu_use_dp": True, "verbosity": 0}, X, y, iters=1))
    assert any("gpu_use_dp" in ln and "storing u8 bins" in ln
               for ln in lines), lines


@pytest.mark.slow    # tier-1 budget (ISSUE 18 discipline): the full suite,
                     # bench measure_packed (packed_ok) and every
                     # dryrun_multichip capture still run this
def test_packed_auto_engages_and_auto_refuses():
    # bin_layout=auto packs exactly when eligible: engagement info at
    # max_bin<=15, SILENT u8 fallback above (no staged warning — the
    # user never asked for packing)
    X, y = _binary_problem()
    lines = _warnings(lambda: _train_text(
        {"hist_method": "fused", "max_bin": 15, "verbosity": 1},
        X, y, iters=1))
    assert any(_PACKED_ENGAGED in ln for ln in lines), lines
    lines = _warnings(lambda: _train_text(
        {"hist_method": "fused", "max_bin": 63, "verbosity": 0},
        X, y, iters=1))
    assert not any("storing u8 bins" in ln for ln in lines), lines


def test_config_rejects_unknown_bin_layout():
    with pytest.raises(ValueError, match="bin_layout"):
        Config.from_dict({"objective": "binary", "bin_layout": "packed2"})
