"""Async wave pipelining (PR 7 tentpole) — bit-parity pins + donation.

The pipelined wave schedule (``async_wave_pipeline``, default on) defers
each round's leaf-histogram-state scatter and valid-row routing into the
next round's computation (value-forwarded parent reads, post-loop drain)
so they overlap the next round's partition + histogram pass instead of
serializing at the while-loop body barrier (models/grower_wave.py).  The
contract pinned here: trees, leaf routings and valid-set scores are
BIT-IDENTICAL to the fully-serialized legacy body
(``async_wave_pipeline=false`` — the pin), across binary incl.
bagging + feature_fraction + categorical + NaN, multiclass, and DART;
and the PR-6 checkpoint kill-at-k byte-identical-resume guarantee is
unchanged with the pipeline enabled (the drain applies all pending state
before any boundary a checkpoint can observe).

Also here: the fused-step buffer-donation audit (the score caches must
carry input-output aliasing in the lowered HLO — a silent donation
regression doubles score-cache HBM traffic with no test tripping
otherwise), and the ``hist_dtype_deep="auto"`` backend resolution
policy (parallel/trainer.resolve_deep_dtype).
"""

import numpy as np
import pytest

import lightgbmv1_tpu as lgb
from tests.conftest import make_binary_problem


def _mixed_problem(n=2500, seed=0):
    """Binary problem with a categorical column and NaN missing values —
    the routing paths the deferred valid-row pass must reproduce."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 6)
    X[:, 0] = rng.randint(0, 6, n)
    X[rng.rand(n, 6) < 0.05] = np.nan
    y = (np.nan_to_num(X[:, 1]) - np.nan_to_num(X[:, 2]) > 0).astype(float)
    return X, y


def _train_pair(params, make, rounds, valid=True):
    """Train the same config with the pipeline on vs off; return both
    boosters.  ``leafwise_wave_size`` is set explicitly so the wave
    grower (not the sequential one) runs at these small test shapes."""
    out = []
    for pipe in (True, False):
        X, y = make()
        p = {**params, "async_wave_pipeline": pipe, "verbosity": -1}
        ds = lgb.Dataset(X, label=y, params=p,
                         categorical_feature=p.pop("_cat", "auto"))
        kw = {}
        if valid:
            Xv, yv = make()
            kw = dict(valid_sets=[lgb.Dataset(Xv, label=yv, reference=ds)],
                      valid_names=["v"], verbose_eval=False)
        out.append(lgb.train(p, ds, num_boost_round=rounds, **kw))
    return out


def _assert_bit_identical(a, b, check_valid=True):
    assert a.model_to_string() == b.model_to_string()
    if check_valid and a._gbdt._valid_scores:
        np.testing.assert_array_equal(
            np.asarray(a._gbdt._valid_scores[0].score),
            np.asarray(b._gbdt._valid_scores[0].score))


def test_pipeline_bit_parity_binary_bagging_ff():
    """Binary with bagging + per-tree feature_fraction + categorical +
    NaN + a valid set — the full deferred-routing surface in one config."""
    params = {"objective": "binary", "num_leaves": 31,
              "leafwise_wave_size": 8, "min_data_in_leaf": 10,
              "bagging_fraction": 0.7, "bagging_freq": 1,
              "feature_fraction": 0.8, "metric": "auc", "_cat": [0]}
    a, b = _train_pair(params, _mixed_problem, rounds=6)
    _assert_bit_identical(a, b)


# tier-1 wall budget (tools/tier1_budget.py): the binary + DART parity
# pins stay in tier-1; the multiclass variant is slow-marked (full suite)
@pytest.mark.slow
def test_pipeline_bit_parity_multiclass():
    params = {"objective": "multiclass", "num_class": 3, "num_leaves": 15,
              "leafwise_wave_size": 4, "min_data_in_leaf": 10,
              "metric": "multi_logloss", "_cat": []}

    def make():
        rng = np.random.RandomState(3)
        X = rng.randn(1200, 6)
        y = ((X[:, 0] > 0).astype(int) + (X[:, 1] > 0.5)).astype(float)
        return X, y

    a, b = _train_pair(params, make, rounds=3)
    _assert_bit_identical(a, b)
    assert len(a._all_trees()) == 9       # 3 iters x 3 classes


def test_pipeline_bit_parity_dart():
    """DART exercises the pipeline inside the fused drop iteration (drop
    removal + K tree builds + restore in one dispatch)."""
    params = {"objective": "binary", "boosting": "dart", "num_leaves": 15,
              "leafwise_wave_size": 4, "min_data_in_leaf": 20,
              "drop_rate": 0.5, "skip_drop": 0.0, "_cat": []}
    a, b = _train_pair(params, lambda: make_binary_problem(n=1000),
                       rounds=6, valid=False)
    _assert_bit_identical(a, b, check_valid=False)


def test_pipeline_bit_parity_legacy_store():
    """The pipeline composes with the legacy per-field bookkeeping store
    (fused_bookkeeping=false) — the deferred interleaved scatter equals
    the legacy two-half-scatter commit bit-for-bit."""
    params = {"objective": "binary", "num_leaves": 15,
              "leafwise_wave_size": 4, "fused_bookkeeping": False,
              "_cat": []}
    a, b = _train_pair(params, lambda: make_binary_problem(n=1000),
                       rounds=4, valid=False)
    _assert_bit_identical(a, b, check_valid=False)


def test_pipeline_checkpoint_resume_bit_exact(tmp_path):
    """PR 6's kill-at-k + resume byte-identical guarantee is unchanged
    with the pipeline enabled: the drain applies every pending commit
    before grow() returns, so a checkpoint written between iterations
    never observes half-applied pipeline state."""
    params = {"objective": "binary", "num_leaves": 15,
              "leafwise_wave_size": 4, "min_data_in_leaf": 20,
              "feature_fraction": 0.7, "bagging_fraction": 0.8,
              "bagging_freq": 1, "async_wave_pipeline": True,
              "verbosity": -1}
    X, y = make_binary_problem(n=1000)
    straight = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=6,
                         verbose_eval=False)
    part = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=3,
                     verbose_eval=False)
    ckpt = str(tmp_path / "pipe.ckpt")
    part.save_checkpoint(ckpt)
    del part
    resumed = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=3,
                        init_model=ckpt, verbose_eval=False)
    assert straight.model_to_string() == resumed.model_to_string()


def test_fused_step_donates_score_caches():
    """Buffer-donation audit (HLO probe): the fused per-iteration step
    must carry input-output aliasing for the train score cache (and the
    valid caches when attached) in its lowered module — the
    ``tf.aliasing_output`` attribute XLA turns into an in-place update.
    A silent donation regression doubles score-cache HBM traffic with
    nothing else tripping; this probe is the tripwire.  Lowering-only:
    XLA:CPU ignores donation at run time, which is why the CPU trainer
    leaves ``_donate`` off and the test arms it explicitly."""
    import jax.numpy as jnp

    from lightgbmv1_tpu.config import Config
    from lightgbmv1_tpu.io.dataset import BinnedDataset
    from lightgbmv1_tpu.models.gbdt import create_boosting

    X, y = make_binary_problem(n=400)
    cfg = Config.from_dict({"objective": "binary", "num_leaves": 7,
                            "min_data_in_leaf": 5, "verbosity": -1})
    ds = BinnedDataset.from_numpy(X, label=y, config=cfg)
    gb = create_boosting(cfg, ds)
    assert cfg.donate_buffers            # default on
    gb._donate = True                    # arm (CPU backend gates it off)
    step = gb._build_step()
    feat_masks = jnp.asarray(np.stack([gb._tree_feature_mask()]))
    lowered = step.lower(gb._grow_binned, (), gb._train_scores.score, (),
                         jnp.asarray(0, jnp.int32), feat_masks,
                         gb._cegb_used)
    txt = lowered.as_text()
    assert "tf.aliasing_output" in txt or "jax.buffer_donor" in txt, (
        "fused step lost score-cache donation (no aliasing attribute in "
        "the lowered module)")
    # un-donated control: the same step without donation carries none
    gb2 = create_boosting(cfg, ds)
    gb2._donate = False
    step2 = gb2._build_step()
    lowered2 = step2.lower(gb2._grow_binned, (), gb2._train_scores.score,
                           (), jnp.asarray(0, jnp.int32), feat_masks,
                           gb2._cegb_used)
    assert "tf.aliasing_output" not in lowered2.as_text()


def test_rollback_survives_donation_snapshot():
    """_save_rollback_state keeps copies when donation is armed, so
    rollback_one_iter hands back live buffers (not donated ones)."""
    from lightgbmv1_tpu.config import Config
    from lightgbmv1_tpu.io.dataset import BinnedDataset
    from lightgbmv1_tpu.models.gbdt import create_boosting

    X, y = make_binary_problem(n=400)
    cfg = Config.from_dict({"objective": "binary", "num_leaves": 7,
                            "min_data_in_leaf": 5, "verbosity": -1})
    ds = BinnedDataset.from_numpy(X, label=y, config=cfg)
    gb = create_boosting(cfg, ds)
    gb._donate = True                    # snapshot path must copy
    gb.train_one_iter(check_stop=False)
    after_one = np.asarray(gb._train_scores.score).copy()
    gb.train_one_iter(check_stop=False)
    gb.rollback_one_iter()               # undo iteration 2
    assert gb.iter == 1
    np.testing.assert_array_equal(np.asarray(gb._train_scores.score),
                                  after_one)


def test_resolve_deep_dtype_policy():
    """hist_dtype_deep='auto' resolves per backend (ROADMAP item 3a):
    int8sr on TPU, full bf16x2 elsewhere; '' keeps the legacy bf16-drop
    policy; explicit dtypes pass through untouched."""
    from lightgbmv1_tpu.config import Config
    from lightgbmv1_tpu.parallel.trainer import resolve_deep_dtype

    assert resolve_deep_dtype("auto", "bf16x2", "tpu") == "int8sr"
    assert resolve_deep_dtype("auto", "bf16x2", "cpu") == "bf16x2"
    with pytest.raises(ValueError, match="gpu"):
        resolve_deep_dtype("auto", "bf16x2", "gpu")
    assert resolve_deep_dtype("", "bf16x2", "tpu") == "bf16"
    assert resolve_deep_dtype("", "f32", "tpu") == "f32"
    assert resolve_deep_dtype("int8sr", "bf16x2", "cpu") == "int8sr"
    assert resolve_deep_dtype("f32", "bf16x2", "tpu") == "f32"
    # config validation accepts the new value and still rejects garbage
    Config.from_dict({"objective": "binary", "hist_dtype_deep": "auto",
                      "verbosity": -1})
    with pytest.raises(ValueError):
        Config.from_dict({"objective": "binary",
                          "hist_dtype_deep": "float8", "verbosity": -1})


def test_deep_dtype_auto_trains_bit_identical_on_cpu():
    # training end-to-end with auto on the CPU backend resolves to full
    # precision and stays bit-identical to an explicit bf16x2 request
    X, y = make_binary_problem(n=800)
    a = lgb.train({"objective": "binary", "num_leaves": 15,
                   "hist_dtype_deep": "auto", "verbosity": -1},
                  lgb.Dataset(X, label=y), num_boost_round=3)
    b = lgb.train({"objective": "binary", "num_leaves": 15,
                   "hist_dtype_deep": "bf16x2", "verbosity": -1},
                  lgb.Dataset(X, label=y), num_boost_round=3)
    assert a.model_to_string() == b.model_to_string()


def _tb():
    import sys

    sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
    from tools import tier1_budget as tb

    return tb


def test_tier1_budget_tool_jsonl(tmp_path):
    """tools/tier1_budget.py on the conftest JSONL recorder format:
    projects the wall, ranks offenders, flips to failure over the bar."""
    import json

    tb = _tb()
    p = tmp_path / "dur.jsonl"
    rows = [{"nodeid": f"tests/test_a.py::t{i}", "when": "call",
             "duration": d, "outcome": "passed"}
            for i, d in enumerate([5.0, 1.0, 30.0])]
    p.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    per_test, wall = tb.load(str(p))
    assert wall == pytest.approx(36.0)
    assert max(per_test, key=per_test.get).endswith("t2")
    out = []
    assert tb.report(per_test, wall, budget=100.0, frac=0.95,
                     out=out.append)           # 36 <= 95
    assert not tb.report(per_test, wall, budget=30.0, frac=0.95,
                         out=out.append)       # 36 > 28.5
    assert any("t2" in line for line in out)   # worst offender listed
    assert tb.main([str(p), "--budget", "100"]) == 0
    assert tb.main([str(p), "--budget", "30"]) == 1


def test_tier1_budget_tool_pytest_log(tmp_path):
    """The same tool on a tee'd pytest console log: the trailing summary
    wall and any --durations lines drive the projection."""
    tb = _tb()
    log = tmp_path / "t1.log"
    log.write_text("12.50s call     tests/test_b.py::slowest\n"
                   "== 300 passed, 3 failed in 862.95s (0:14:22) ==\n")
    per_test, wall = tb.load(str(log))
    assert wall == pytest.approx(862.95)
    assert per_test["tests/test_b.py::slowest"] == pytest.approx(12.5)
    out = []
    assert not tb.report(per_test, wall, budget=870.0, frac=0.95,
                         out=out.append)       # 862.95 > 826.5 -> over
    assert tb.main([str(log)]) == 1
