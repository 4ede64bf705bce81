"""A wave round after which no child can be split measures no children
(models/grower_wave.py ``children_can_split``).

The round that spends the tree's last leaves, and a depth-limited tree's
last level, still partition their rows (the score update reads the leaf
ids) but skip the histogram pass: its result would feed the sibling
subtraction, the children's scan and their rows of the histogram state,
and nothing of the tree, the leaf ids or the valid routing.  So the grower
with the rule is, bit for bit, the grower with the rule patched to "always
measure" (the parent's program), for every learner and option that runs
the round; the record's tenth field says how often the rule engaged, and a
host-side counter round the pass says the pass is really not run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbmv1_tpu as lgb
from lightgbmv1_tpu.models import grower_wave as gw
from lightgbmv1_tpu.obs import trace
from lightgbmv1_tpu.ops.histogram import hist_wave
from lightgbmv1_tpu.ops.split import FeatureMeta, SplitParams


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------

_T, _F = True, False


@pytest.mark.parametrize("num_leaves,n_split,L,depth_ok,cvalid,want", [
    # leaves are left after the round and a child may go deeper: measure
    (1, 1, 255, [_T, _T, _T, _T], [_T, _T, _F, _F], True),
    (190, 63, 255, [_T] * 4, [_T] * 4, True),
    # the round spends the budget (253 + 2 = 255), or would pass it
    (253, 2, 255, [_T] * 4, [_T] * 4, False),
    (192, 63, 255, [_T] * 4, [_T] * 4, False),
    (254, 2, 255, [_T] * 4, [_T] * 4, False),
    # one leaf short of the budget: the next round splits one more
    (252, 2, 255, [_T] * 4, [_T] * 4, True),
    # every real child sits at max_depth (the slots that may go deeper are
    # no split's): a depth-limited tree's last level
    (8, 2, 255, [_F, _F, _T, _T], [_T, _T, _F, _F], False),
    (8, 2, 255, [_F] * 4, [_T] * 4, False),
    # one real child may still go deeper
    (8, 2, 255, [_F, _F, _T, _T], [_T, _T, _T, _T], True),
    # a round of no split has no child (the loop never takes one)
    (8, 0, 255, [_T] * 4, [_F] * 4, False),
])
def test_children_can_split(num_leaves, n_split, L, depth_ok, cvalid, want):
    got = gw.children_can_split(
        jnp.int32(num_leaves), jnp.int32(n_split), L,
        jnp.asarray(depth_ok), jnp.asarray(cvalid))
    assert got.shape == () and got.dtype == jnp.bool_
    assert bool(got) is want


# ---------------------------------------------------------------------------
# the grower with the rule is the grower without it
# ---------------------------------------------------------------------------


def _rows(n=2400, classes=2, seed=11):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 7)
    X[::11, 3] = np.nan
    s = X[:, 0] * 1.3 - X[:, 1] + 0.7 * X[:, 2] * np.nan_to_num(X[:, 3])
    s = s + rng.randn(n) * 0.5
    if classes == 2:
        return X, (s > 0.2).astype(float)
    return X, np.digitize(s, np.quantile(s, [1 / 3, 2 / 3])).astype(float)


# 31 leaves at 8 splits a round, on a ladder of two buckets (4, 8): the
# schedule 1, 2, 4, 8, 8, 7 spends the budget in its sixth round
_BASE = {"objective": "binary", "num_leaves": 31, "leafwise_wave_size": 8,
         "tree_growth": "leafwise", "min_data_in_leaf": 5, "max_bin": 31,
         "verbosity": -1}

# name -> (params over _BASE, what the case needs, skipped a tree: "1" a
# tree that spends its leaves, "0" one that does not, "some" at least 1)
_CASES = {
    "serial": ({}, {}, "1"),
    "serial_pallas": ({"hist_method": "pallas"}, {}, "1"),
    "data2": ({"tree_learner": "data", "num_shards": 2}, {}, "1"),
    "data4": ({"tree_learner": "data", "num_shards": 4}, {}, "1"),
    "data2_pallas": ({"tree_learner": "data", "num_shards": 2,
                      "hist_method": "pallas"}, {}, "1"),
    "voting2": ({"tree_learner": "voting", "num_shards": 2}, {}, "1"),
    "voting4": ({"tree_learner": "voting", "num_shards": 4}, {}, "1"),
    "pipeline_off": ({"async_wave_pipeline": False}, {}, "1"),
    "no_sub": ({}, {"no_sub": True}, "1"),
    "no_sub_pipeline_off": ({"async_wave_pipeline": False},
                            {"no_sub": True}, "1"),
    "legacy_store": ({"fused_bookkeeping": False}, {}, "1"),
    "no_ladder": ({}, {"ladder": False}, "1"),
    # 8 leaves at most under max_depth 3: the last level's round measures
    # nothing, and the loop goes on to find no gain left
    "max_depth": ({"max_depth": 3}, {}, "some"),
    # 16 of 20 slots is a quantized bucket: 80 leaves pass through it
    "int8sr": ({"num_leaves": 80, "leafwise_wave_size": 20,
                "min_data_in_leaf": 2, "hist_dtype_deep": "int8sr"},
               {"rows": 4000}, "1"),
    "multiclass3": ({"objective": "multiclass", "num_class": 3,
                     "num_leaves": 15, "leafwise_wave_size": 6},
                    {"classes": 3}, "1"),
    "monotone": ({"monotone_constraints": [1, -1, 0, 0, 0, 0, 0],
                  "monotone_constraints_method": "intermediate"}, {}, "1"),
    "goss": ({"boosting": "goss", "top_rate": 0.3, "other_rate": 0.2},
             {}, "1"),
    # a tree that runs out of gain before it runs out of leaves: the rule
    # never engages and the program is the parent's
    "gain_exhausted": ({"min_data_in_leaf": 150}, {}, "0"),
}


def _train(params, need, rule_on, monkeypatch):
    """Three iterations with a valid set; the model text, the training and
    valid scores, and the records' ``rounds`` and ``hist_skipped``."""
    if not rule_on:
        monkeypatch.setattr(gw, "children_can_split",
                            lambda *a: jnp.asarray(True))
    X, y = _rows(need.get("rows", 2400), need.get("classes", 2))
    Xv, yv = _rows(600, need.get("classes", 2), seed=12)
    ds = lgb.Dataset(X, label=y, params=dict(params))
    trace.reset()
    m = lgb.train(dict(params), ds, num_boost_round=3,
                  valid_sets=[lgb.Dataset(Xv, label=yv, reference=ds)],
                  valid_names=["v"], verbose_eval=False)
    recs = trace.iteration_records()
    return dict(
        model=m.model_to_string(),
        train=np.asarray(m._gbdt._train_scores.score),
        valid=np.asarray(m._gbdt._valid_scores[0].score),
        rounds=[r[8] for r in recs], skipped=[r[9] for r in recs],
        leaves=[t.num_leaves for t in m._all_trees()])


@pytest.mark.parametrize("case", list(_CASES))
def test_the_rule_changes_no_bit(case, monkeypatch):
    over, need, skipped = _CASES[case]
    params = {**_BASE, **over}
    monkeypatch.setattr(gw, "_BUCKET_MIN_N",
                        256 if need.get("ladder", True) else 1 << 60)
    if need.get("no_sub"):
        monkeypatch.setattr(gw, "_SUB_STATE_CAP_BYTES", 0)
    with_rule = _train(params, need, True, monkeypatch)
    without = _train(params, need, False, monkeypatch)

    assert with_rule["model"] == without["model"]
    # the leaf ids (the training scores are a lookup by them) and the valid
    # rows' routing
    np.testing.assert_array_equal(with_rule["train"], without["train"])
    np.testing.assert_array_equal(with_rule["valid"], without["valid"])
    assert with_rule["rounds"] == without["rounds"]
    assert without["skipped"] == [0, 0, 0]      # the patch took
    trees = params.get("num_class", 1)
    if skipped == "1":
        assert all(n == params["num_leaves"] for n in with_rule["leaves"])
        assert with_rule["skipped"] == [trees] * 3
    elif skipped == "0":
        assert all(1 < n < params["num_leaves"]
                   for n in with_rule["leaves"])
        assert with_rule["skipped"] == [0, 0, 0]
    else:
        assert all(n <= 8 for n in with_rule["leaves"])
        assert all(n >= 1 for n in with_rule["skipped"])


# ---------------------------------------------------------------------------
# the pass is really not run
# ---------------------------------------------------------------------------


def _meta(F, B):
    return FeatureMeta(
        num_bins=jnp.full(F, B, jnp.int32),
        missing_type=jnp.zeros(F, jnp.int32),
        nan_bin=jnp.full(F, -1, jnp.int32),
        zero_bin=jnp.zeros(F, jnp.int32),
        is_categorical=jnp.zeros(F, bool),
        usable=jnp.ones(F, bool),
        monotone_type=jnp.zeros(F, jnp.int32),
    )


@pytest.mark.parametrize("max_depth,min_data", [(-1, 2.0), (3, 2.0),
                                                (-1, 400.0)],
                         ids=["budget", "max_depth", "gain_exhausted"])
def test_a_skipped_round_calls_no_histogram_pass(monkeypatch, max_depth,
                                                 min_data):
    """A host-side counter inside ``hist_wave_fn``: the passes that ran are
    the root's and one a round less the skipped ones, which is also what
    the finished tree replays to."""
    rng = np.random.RandomState(5)
    N, F, B, L, K = 3000, 6, 16, 31, 8
    bins = jnp.asarray(rng.randint(0, B, (F, N)).astype(np.uint8))
    signal = np.asarray(bins[:3]).astype(np.float32).sum(axis=0)
    g3 = jnp.asarray(np.stack([signal - signal.mean() + rng.randn(N),
                               np.ones(N), np.ones(N)],
                              axis=1).astype(np.float32))
    monkeypatch.setattr(gw, "_BUCKET_MIN_N", 1)
    passes = []

    def hist(b, g, label, n, deep=False):
        jax.debug.callback(lambda: passes.append(n), ordered=True)
        return hist_wave(b, g, label, n, B, method="scatter")

    grow = gw.make_wave_grower(
        num_leaves=L, num_bins=B, meta=_meta(F, B), max_depth=max_depth,
        params=SplitParams(min_data_in_leaf=min_data), wave_size=K,
        hist_wave_fn=hist, stored_layout="u8")
    tree, _, third = jax.block_until_ready(jax.jit(grow)(
        bins, g3, jnp.ones(F, bool), jax.random.PRNGKey(0)))
    jax.effects_barrier()
    rounds = tuple(int(n) for n in third.rounds)
    skipped = int(third.hist_skipped)
    buckets = gw.slot_buckets_for(K, N)
    schedule, = gw.replay_wave_schedule([tree], K)
    assert rounds == gw.rounds_by_bucket(schedule, buckets)
    assert passes[0] == 1                            # the root's
    assert len(passes) == sum(rounds) - skipped + 1
    if max_depth > 0:
        # 1, 2, 4 splits: the third level's children sit at the limit
        assert int(tree.num_leaves) == 8 and schedule == [1, 2, 4]
        assert skipped == 1 and passes == [1, 4, 4]
        return      # a depth limit is not in the replay's model
    # what prices a replayed tree's passes (bench.py, __graft_entry__.py)
    assert len(schedule) - len(gw.measured_rounds(schedule, L)) == skipped
    if min_data > 2:
        assert 1 < int(tree.num_leaves) < L and skipped == 0
    else:
        assert int(tree.num_leaves) == L and skipped == 1
        # the last round's bucket is the one that is missing
        ran = gw.rounds_by_bucket(schedule[:-1], buckets)
        assert [passes[1:].count(S) for S in buckets] == list(ran)
