"""The plain reference's own arithmetic, on cases small enough to check by
hand or by brute force."""

import numpy as np
import pytest

import datagen
import reference
from test_roofline import three_leaf_tree


def test_route_follows_thresholds_in_float64():
    tree = three_leaf_tree()
    t32 = np.float32(1.0)
    XT = np.array([[-1.0, 0.0, 0.5, 0.5, 0.5],
                   [9.0, 9.0, 1.0, np.nextafter(t32, np.float32(2)), 0.0]],
                  np.float32)
    assert reference.route(tree, XT).tolist() == [0, 0, 1, 2, 1]
    # a threshold between two float32 values: x <= t decided in float64
    assert reference._f32_floor(1.0 + 1e-12) == np.float32(1.0)
    assert reference._f32_floor(1.0 - 1e-12) < np.float32(1.0)
    assert reference.predict_raw([tree, tree], XT)[3] == pytest.approx(0.6)


def test_auc_matches_pair_counting():
    rng = np.random.default_rng(0)
    y = (rng.random(300) < 0.4).astype(np.float64)
    s = np.round(rng.normal(size=300) + y, 1)          # many ties
    pos, neg = s[y > 0], s[y == 0]
    brute = ((pos[:, None] > neg[None, :]).sum()
             + 0.5 * (pos[:, None] == neg[None, :]).sum()) / (len(pos) * len(neg))
    assert reference.auc(y, s) == pytest.approx(brute, abs=1e-12)


def test_ndcg_by_hand():
    # one query of three docs, labels 2,0,1; scores rank them 0,2,1 (ideal)
    y = np.array([2.0, 0.0, 1.0, 0.0, 0.0])
    s = np.array([3.0, 1.0, 2.0, 0.5, 0.7])
    g = np.array([3, 2])
    assert reference.ndcg_at(y, s, g, 10) == pytest.approx(1.0)
    # reverse the first query's order: DCG = 0/1 + 1/log2(3) + 3/2
    s2 = np.array([1.0, 3.0, 2.0, 0.5, 0.7])
    ideal = 3.0 + 1.0 / np.log2(3.0)
    got = 1.0 / np.log2(3.0) + 3.0 / 2.0
    assert reference.ndcg_at(y, s2, g, 10) == pytest.approx(
        (got / ideal + 1.0) / 2.0)


def test_lambdarank_matches_a_pair_loop():
    spec = {"kind": "rank", "features": 3, "linear": [[0, 1.0]],
            "noise_sd": 1.0, "grade_cuts": [0.5, 0.75, 0.9, 0.97],
            "length_seed": 1, "length_mean": 12, "length_sigma": 0.7,
            "length_min": 1, "length_max": 40}
    train = datagen.make_split(spec, 5, "train", rows=300)
    obj = reference.Lambdarank(train, {})
    s = np.random.default_rng(1).normal(size=train.rows)
    s = np.round(s, 1)                                   # ties in score
    g, h = obj.grads(s)
    gg, hh = np.zeros(train.rows), np.zeros(train.rows)
    start = 0
    for n in train.group:
        idx = np.arange(start, start + n)
        start += n
        sc, lab = s[idx].astype(np.float32), train.y[idx]
        gain = 2.0 ** lab - 1
        order = np.lexsort((np.arange(n), -sc))
        rank = np.empty(n, int)
        rank[order] = np.arange(n)
        disc = np.where(rank < 20, 1 / np.log2(2.0 + rank), 0.0)
        top = np.sort(gain)[::-1][:20]
        mx = (top / np.log2(np.arange(len(top)) + 2.0)).sum()
        lam = np.zeros((n, n))
        hes = np.zeros((n, n))
        for i in range(n):
            for j in range(n):
                if gain[i] > gain[j] and (disc[i] > 0 or disc[j] > 0) and mx > 0:
                    p = 1 / (1 + np.exp(float(sc[i] - sc[j])))
                    d = abs(gain[i] - gain[j]) * abs(disc[i] - disc[j]) / mx
                    lam[i, j], hes[i, j] = p * d, p * (1 - p) * d
        tot = lam.sum() + 1e-10
        scale = np.log2(1 + tot) / tot
        gg[idx] = (-lam.sum(1) + lam.sum(0)) * scale
        hh[idx] = (hes.sum(1) + hes.sum(0)) * scale
    np.testing.assert_allclose(g, gg, rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(h, np.maximum(hh, 1e-20), rtol=2e-4, atol=2e-6)


def test_same_seed_same_data_and_same_sizes_for_every_seed():
    spec = {"kind": "rank", "features": 4, "linear": [[0, 1.0]],
            "noise_sd": 0.0, "grade_cuts": [0.5, 0.75, 0.9, 0.97],
            "length_seed": 1, "length_mean": 12, "length_sigma": 0.9,
            "length_min": 1, "length_max": 60}
    big = 2 ** 31 + 11
    a = datagen.make_split(spec, big, "train", rows=500)
    b = datagen.make_split(spec, big, "train", rows=500)
    c = datagen.make_split(spec, 7, "train", rows=500)
    assert np.array_equal(a.XT, b.XT) and np.array_equal(a.y, b.y)
    assert a.rows == c.rows == 500
    # the lengths and the place of every grade are the same for every seed
    assert np.array_equal(a.group, c.group) and np.array_equal(a.y, c.y)
    assert not np.array_equal(a.XT, c.XT)
    # and the grade is still the quantile of the row's score in its query
    score = a.XT[0]
    start = 0
    for n in a.group:
        q = slice(start, start + n)
        start += n
        order = np.argsort(score[q], kind="stable")
        assert (np.diff(a.y[q][order]) >= 0).all()
    assert set(np.unique(a.y)) <= {0.0, 1.0, 2.0, 3.0, 4.0}


def sound_tree_and_data():
    """100 rows placed as the three-leaf tree routes them (30 / 60 / 10),
    half of every leaf positive, and the tree filled in with what a sound
    binary step stores: every row has p = 1/2 at step 0, so h = 1/4."""
    tree = three_leaf_tree()
    XT = np.zeros((2, 100), np.float32)
    XT[0, :30], XT[0, 30:] = -1.0, 1.0
    XT[1, 30:90], XT[1, 90:] = 0.0, 2.0
    y = np.zeros(100)
    y[[*range(0, 12), *range(30, 66), *range(90, 92)]] = 1.0   # 12, 36, 2
    train = datagen.Split(XT, y, None)
    params = {"objective": "binary", "learning_rate": 0.1}
    (s,) = reference.follow([tree], train, params)
    tree.leaf_value[:] = s.value + reference.BinaryLogloss(train,
                                                           params).init_score
    tree.leaf_weight[:], tree.node_weight[:] = s.H, s.node_H
    tree.gain[:] = s.gain
    return tree, train, params


def test_judge_reads_nothing_on_a_sound_step_and_sees_each_fault():
    tree, train, params = sound_tree_and_data()
    numbers = reference.judge([tree], train, params)
    assert set(numbers) == set(reference.NUMBERS)
    assert all(v < 1e-12 for v in numbers.values()), numbers
    # G = sum(p - y) with p = 1/2: leaf 0 holds 30 rows, 12 positive
    (s,) = reference.follow([tree], train, params)
    assert s.value[0] == pytest.approx(-(15 - 12) / 7.5 * 0.1)
    tree.leaf_value[1] *= 2.0
    assert reference.judge([tree], train, params)["update_norm_gap"] > 0.1
    tree, train, params = sound_tree_and_data()
    tree.leaf_count[2] += 1
    assert reference.judge([tree], train, params)["count_mismatch"] == 1
    tree.node_weight[1] *= 1.5
    tree.gain[0] *= 1.01
    numbers = reference.judge([tree], train, params)
    assert numbers["hess_sum_gap"] == pytest.approx(0.5 * 17.5 / 17.5)
    assert numbers["root_gain_gap"] == pytest.approx(0.01)
    assert 0 < numbers["total_gain_gap"] < 0.01


def test_verdict_limits_every_number():
    numbers = dict.fromkeys(reference.NUMBERS, 0.0)
    limits = dict.fromkeys(reference.NUMBERS, 0.0)
    assert reference.verdict(numbers, limits)[0] is True
    numbers["hess_sum_gap"] = 1e-9
    correct, rows = reference.verdict(numbers, limits)
    assert correct is False and len(rows) == len(reference.NUMBERS)
    del limits["total_gain_gap"]
    with pytest.raises(KeyError):
        reference.verdict(numbers, limits)
