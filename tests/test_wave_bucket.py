"""Slot-bucketed wave rounds (models/grower_wave.py round_pass).

Ramp-up rounds (frontier < K splits) run a SLICED (S, N) partition +
(S+1)-slot histogram variant selected by ``lax.switch`` over the round's
n_split.  On the exact fp32 scatter histogram path the sliced rounds must
produce IDENTICAL trees to the single full-wave path: the same rows land
in the same (leaf, feature, bin) cells in the same row order, only the
slot index differs (reference parity anchor: the slot layout of the
histogram build has no counterpart in SerialTreeLearner — only per-leaf
histogram CONTENT matters, serial_tree_learner.cpp:274-314)."""

import numpy as np
import pytest

import lightgbmv1_tpu as lgb
from lightgbmv1_tpu.models import grower_wave


def make_problem(n=3000, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 7)
    X[::9, 2] = np.nan
    X[:, 6] = rng.randint(0, 6, n).astype(float)
    y = (X[:, 0] * 1.3 - X[:, 1] + np.isin(X[:, 6], [1, 4]) * 1.2
         + rng.randn(n) * 0.5 > 0.2).astype(float)
    return X, y


# tier-1 wall budget: the bagged regression arm keeps the contract in
# tier-1; the heavier binary arm is slow-marked (full suite only)
@pytest.mark.parametrize("params", [
    pytest.param({"objective": "binary", "num_leaves": 63},
                 marks=pytest.mark.slow),
    {"objective": "regression", "num_leaves": 63,
     "bagging_fraction": 0.6, "bagging_freq": 1},
])
def test_bucketed_rounds_match_single_bucket(params, monkeypatch):
    X, y = make_problem()
    params = {**params, "verbosity": -1, "tree_growth": "leafwise",
              "leafwise_wave_size": 16}

    def run():
        m = lgb.train(params, lgb.Dataset(X, label=y,
                                          categorical_feature=[6]),
                      num_boost_round=4)
        return m

    monkeypatch.setattr(grower_wave, "_BUCKET_MIN_N", 1 << 60)  # off
    a = run()
    monkeypatch.setattr(grower_wave, "_BUCKET_MIN_N", 256)      # on: {4,16}
    b = run()

    for ta, tb in zip(a._all_trees(), b._all_trees()):
        assert ta.num_leaves == tb.num_leaves
        np.testing.assert_array_equal(ta.split_feature, tb.split_feature)
        np.testing.assert_array_equal(ta.threshold_bin, tb.threshold_bin)
        np.testing.assert_array_equal(ta.leaf_count, tb.leaf_count)
        np.testing.assert_allclose(ta.leaf_value, tb.leaf_value, rtol=1e-6)
    np.testing.assert_allclose(a.predict(X), b.predict(X), rtol=1e-6)


def test_round_counter_matches_tree_replay():
    """The grower counts its rounds by slot bucket on the device
    (``WaveState.rounds``, handed back third and kept in the per-tree
    record), and replay_wave_schedule must reproduce the SAME counts from
    the grown trees alone — the replay is what bench.py records as
    wave_rounds_per_tree, so the timed program carries no host callback."""
    from lightgbmv1_tpu.obs import trace

    X, y = make_problem(n=1200)
    trace.reset()
    m = lgb.train({"objective": "binary", "num_leaves": 31,
                   "leafwise_wave_size": 8, "tree_growth": "leafwise",
                   "verbosity": -1},
                  lgb.Dataset(X, label=y), num_boost_round=2)
    live = [r[8] for r in trace.iteration_records()]
    trees = m._all_trees()
    buckets = grower_wave.slot_buckets_for(8, 1200)
    assert buckets == [8]                    # below _BUCKET_MIN_N: no ladder
    replayed = [grower_wave.rounds_by_bucket(s, buckets)
                for s in grower_wave.replay_wave_schedule(trees, 8)]
    t = trees[0]
    # a 31-leaf tree at K=8 needs >= ceil(30/8) = 4 rounds; the ramp
    # (1, 2, 4, 8, ...) makes it >= 6 when the tree fills its budget
    assert sum(map(sum, live)) >= \
        2 * max(1, int(np.ceil((t.num_leaves - 1) / 8)))
    assert replayed == live
