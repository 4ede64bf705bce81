"""Stored sums that hold at small leaves (models/renew.py, PR 28).

A binary model's first tree, where every row of a class carries one
gradient, grown on single-bf16 histograms (relative rounding 2^-9, so 10^5
rows show what 16M rows show at the default bf16 hi+lo pair): every leaf's
and split node's stored count / G / H against ``numpy.bincount`` in
float64, on the three growers.  Before PR 28 the sums a child inherited
through ``parent - sibling`` were off by tens of percent in small leaves.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import lightgbmv1_tpu as lgb
from lightgbmv1_tpu.models import renew
from lightgbmv1_tpu.obs import trace as obs_trace
from lightgbmv1_tpu.obs.metrics import default_registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LR = 0.1


def binary_rows(n, f=10, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    score = X[:, 0] - 0.8 * X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
    y = (score + rng.randn(n) > 1.0).astype(np.float32)
    return X, y


def first_tree(params, X, y):
    ds = lgb.Dataset(X, label=y, params=dict(params)).construct()
    booster = lgb.Booster(params=dict(params), train_set=ds)
    booster.update()
    return booster


def node_sums(structure, leaf_of_row, g, h):
    """Walk ``dump_model``'s tree: per leaf and per split node the stored
    (count, G, H) beside the float64 sums over the rows routed there."""
    cnt = np.bincount(leaf_of_row).astype(np.float64)
    G = np.bincount(leaf_of_row, weights=g)
    H = np.bincount(leaf_of_row, weights=h)
    out = []

    def walk(node):
        if "leaf_index" in node or "split_index" not in node:
            i = int(node.get("leaf_index", 0))
            true = np.array([cnt[i], G[i], H[i]])
            stored = (node["leaf_count"], node["leaf_weight"],
                      node["leaf_value"])
            out.append(("leaf", stored, true))
            return true
        true = walk(node["left_child"]) + walk(node["right_child"])
        stored = (node["internal_count"], node["internal_weight"],
                  node["internal_value"])
        out.append(("node", stored, true))
        return true

    walk(structure)
    return out


GROWERS = {
    "wave": {},
    "leafwise": {"leafwise_wave_size": 0, "num_leaves": 7},
    "levelwise": {"tree_growth": "levelwise"},
}


@pytest.mark.parametrize("grower", sorted(GROWERS))
def test_small_leaves_store_sums_the_rows_bear_out(grower):
    n = 100_000
    X, y = binary_rows(n)
    params = {"objective": "binary", "num_leaves": 63, "max_bin": 63,
              "learning_rate": LR, "verbosity": -1, "min_data_in_leaf": 20,
              "min_sum_hessian_in_leaf": 1e-3, "hist_method": "onehot",
              "hist_dtype": "bf16", **GROWERS[grower]}
    booster = first_tree(params, X, y)
    tree = booster.dump_model(num_iteration=1)["tree_info"][0]
    leaf = booster.predict(X, pred_leaf=True).reshape(n).astype(np.int64)
    p = float(y.mean())
    init = np.log(p / (1 - p))
    g = np.full(n, p, np.float64) - y
    h = np.full(n, p * (1 - p), np.float64)
    worst = {"count": 0.0, "H": 0.0, "G": 0.0}
    for kind, (s_cnt, s_H, s_val), (cnt, G, H) in node_sums(
            tree["tree_structure"], leaf, g, h):
        assert s_cnt == cnt
        worst["H"] = max(worst["H"], abs(s_H - H) / H)
        # the stored output is -G / H over the initial score (a leaf's x the
        # learning rate): G as the tree stores it
        s_G = -(s_val - init) / (LR if kind == "leaf" else 1.0) * s_H
        # against the rows' own |g| mass: G itself may be near zero
        mass = cnt * max(p, 1 - p)
        worst["G"] = max(worst["G"], abs(s_G - G) / mass)
    # a direct bf16 sum is off by 2^-9 of its own mass (0.0024 read here);
    # the parent of this PR read H 1.18 and G 0.16 on the wave and
    # level-wise growers, H 0.014 on the 7-leaf sequential one
    assert worst["H"] < 0.005, worst
    assert worst["G"] < 0.005, worst
    # the always-on record says how many entries were measured again
    assert obs_trace.iteration_records()[-1][7] > 0


def test_marks_follow_the_error_accounting():
    """A chain of larger children cut by subtraction from a measured root:
    the foreign error grows down the chain while the rows shrink."""
    # root 0 -> (leaf 0, node 1); node 1 -> (leaf 1, node 2); ...
    L = 6
    counts = [100_000, 60_000, 35_000, 20_000, 11_000]
    left = np.array([-1, -2, -3, -4, -5], np.int32)
    right = np.array([1, 2, 3, 4, -6], np.int32)
    leaf_count = np.array([40_000, 25_000, 15_000, 9_000, 10_970, 30],
                          np.float32)
    cnt = np.asarray(counts, np.float32)
    leaf = lambda l: L - 1 + l
    # a bf16 hi+lo pair in every pass: only what subtraction brought counts
    pair = renew.RenewPolicy(eps_root=2.0 ** -17, eps_rest=2.0 ** -17,
                             subtracts=True, gains=True)
    mark, anc = renew.inherited_error(np, left, right, L, cnt, leaf_count,
                                      pair)
    # the 30-row right leaf at the end of the chain of derived nodes
    assert mark[leaf(5)]
    # the root is measured and its sums are a plain reduction; its children
    # are cells of its own histogram, and so are the first left leaves
    assert not mark[0] and not mark[1]
    assert not mark[leaf(0)] and not mark[leaf(1)]
    # ancestors: leaf 5 sits under every split node
    assert anc[leaf(5), :L - 1].all() and not anc[leaf(0), 1:L - 1].any()
    # the default policy of the chip: a single bf16 in the deep rounds
    # rounds past tau by itself, so all below the root's children is marked
    deep = renew.RenewPolicy(eps_root=2.0 ** -17, eps_rest=2.0 ** -9,
                             subtracts=True, gains=True)
    mark_deep, _ = renew.inherited_error(np, left, right, L, cnt,
                                         leaf_count, deep)
    assert not mark_deep[[0, 1, leaf(0)]].any()
    assert mark_deep[[2, 3, 4]].all() and mark_deep[L:].all()
    # at float32 rounding nothing of this tree but the 30 rows is marked
    fine = renew.RenewPolicy(eps_root=2.0 ** -24, eps_rest=2.0 ** -24,
                             subtracts=True, gains=True)
    mark32, _ = renew.inherited_error(np, left, right, L, cnt, leaf_count,
                                      fine)
    assert not mark32[:leaf(5)].any()
    # and a grower that measures both children leaves the histograms no
    # foreign error: what is left is parent_sum - left down the right edge
    both = pair._replace(subtracts=False)
    mark_b, _ = renew.inherited_error(np, left, right, L, cnt, leaf_count,
                                      both)
    assert mark_b.sum() <= mark.sum() and not mark_b[L:leaf(5)].any()


def test_renewal_counts_rows_exactly_past_2_to_the_24():
    """A 26,562,500-row root whose larger child holds an odd 17,562,499
    rows: float32 has no such number (its grid there is 2 wide), so the
    grower's counts, sums and differences of float32, store the child one
    row off and every count cut from it by subtraction after it.  The
    renewal's pass counts each leaf's rows (exact below 2^24 rows a leaf)
    and the nodes' counts are integer sums of those: int32, to the row."""
    import jax.numpy as jnp
    from lightgbmv1_tpu.models.tree import empty_tree
    from lightgbmv1_tpu.ops.split import SplitParams

    # root 0 -> (leaf 0, node 1); node 1 -> (leaf 1, leaf 2)
    true_leaves = np.array([9_000_001, 8_562_499, 9_000_000], np.int64)
    assert int(np.float32(17_562_499)) != 17_562_499     # no such float32
    as_grown = np.float32(26_562_500) - np.float32(9_000_001)
    assert int(as_grown) != 17_562_499
    tree = empty_tree(3)._replace(
        num_leaves=jnp.asarray(3, jnp.int32),
        left_child=jnp.asarray([-1, -2], jnp.int32),
        right_child=jnp.asarray([1, -3], jnp.int32),
        # what the scan's float32 arithmetic leaves: one row off down the
        # right edge
        internal_count=jnp.asarray([26_562_500, int(as_grown)], jnp.int32),
        leaf_count=jnp.asarray(
            [9_000_001, 8_562_499, int(as_grown) - 8_562_499], jnp.int32),
        leaf_weight=jnp.asarray([2.0e6, 2.0e6, 2.0e6], jnp.float32),
        internal_weight=jnp.asarray([6.0e6, 4.0e6], jnp.float32))
    assert tree.leaf_count.dtype == tree.internal_count.dtype == jnp.int32
    measured = jnp.asarray(np.stack(
        [np.zeros(3), true_leaves * 0.25, true_leaves], axis=1), jnp.float32)
    deep = renew.RenewPolicy(eps_root=2.0 ** -17, eps_rest=2.0 ** -9,
                             subtracts=True, gains=True)
    got = renew.renew_tree(tree, None, None, SplitParams(), deep,
                           lambda leaf_id, g3: measured)
    assert got.leaf_count.dtype == got.internal_count.dtype == jnp.int32
    np.testing.assert_array_equal(got.leaf_count, true_leaves)
    np.testing.assert_array_equal(got.internal_count,
                                  [26_562_500, 17_562_499])


@pytest.mark.parametrize("method", ["scatter", "onehot", "pallas"])
def test_leaf_sums_match_bincount(method):
    rng = np.random.RandomState(0)
    n, L = 20_000, 255
    leaf = rng.randint(0, L, n).astype(np.int32)
    g3 = np.stack([rng.randn(n), rng.rand(n), np.ones(n)], 1).astype(
        np.float32)
    got = np.asarray(renew.leaf_sums(
        jax.numpy.asarray(leaf), jax.numpy.asarray(g3), L, method=method,
        precision="f32" if method == "pallas" else "bf16x2",
        interpret=method == "pallas"))
    want = np.stack([np.bincount(leaf, weights=g3[:, k].astype(np.float64),
                                 minlength=L) for k in range(3)], 1)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-4)
    assert (got[:, 2] == want[:, 2]).all()


@pytest.mark.parametrize("method", ["auto", "pallas"])
def test_data_learner_is_traced_and_counted(method):
    """``lgbm.collective`` in the data learner's lowered step and not in
    the serial one; the gauge's bytes from the shapes; the per-tree count
    in the iteration record.  Under ``pallas`` both learners hand the step
    the prepared operand (``HistBins``; the data learner's blocks a shard's
    rows each), under ``auto`` the matrix."""
    from lightgbmv1_tpu.ops.hist_pallas import HistBins
    from lightgbmv1_tpu.parallel.trainer import COLLECTIVE_SCOPE

    n, f, leaves, bins = 4000, 6, 15, 31
    X, y = binary_rows(n, f)
    base = {"objective": "binary", "num_leaves": leaves, "max_bin": bins,
            "verbosity": -1, "min_data_in_leaf": 20, "hist_method": method}
    default_registry().reset(["dp_reduce_bytes_per_round"])
    texts = {}
    for name, extra in (("serial", {}),
                        ("data", {"tree_learner": "data", "num_shards": 4})):
        booster = first_tree({**base, **extra}, X, y)
        gbdt = booster._gbdt
        grow = gbdt._grow
        assert isinstance(gbdt._grow_binned, HistBins) == (method == "pallas")
        g3 = jax.numpy.zeros((n, 3), jax.numpy.float32)
        lowered = jax.jit(grow.__wrapped__ if hasattr(grow, "__wrapped__")
                          else grow).lower(
            gbdt._grow_binned, g3, jax.numpy.ones(f, bool),
            jax.random.PRNGKey(0), gbdt._cegb_used)
        texts[name] = lowered.as_text(debug_info=True)
        rec = obs_trace.iteration_records()[-1]
        assert len(rec) == 10 and isinstance(rec[7], int)
        # one slot bucket at this size; the row-sharded learner hands the
        # count out of its shard_map like the serial one
        assert len(rec[8]) == 1 and rec[8][0] >= 3
        assert rec[7] == renew.count_marked(gbdt._device_trees[0],
                                            grow._renew_policy)
    assert COLLECTIVE_SCOPE in texts["data"]
    assert COLLECTIVE_SCOPE not in texts["serial"]
    snap = default_registry().snapshot()
    gauge = {k: v for k, v in snap.items()
             if k.startswith("dp_reduce_bytes_per_round")}
    ndev, K, B = 4, 3, bins + 1               # K = num_leaves // 4 splits
    f_pad = -(-f // ndev) * ndev
    # the widest histogram block handed to the reduce-scatter: a round's K
    # smaller children, features padded to the devices
    assert gauge['dp_reduce_bytes_per_round{what="hist"}'] == \
        K * f_pad * B * 3 * 4
    # a round's split records: 2K children x ndev x (11 + one bitset word)
    assert gauge['dp_reduce_bytes_per_round{what="split"}'] == \
        2 * K * ndev * (11 + 1) * 4
    assert gauge['dp_reduce_bytes_per_round{what="root"}'] == 3 * 4
    assert gauge['dp_reduce_bytes_per_round{what="renew"}'] == \
        leaves * 3 * 4


def test_new_cell_rehearses_and_its_metrics_are_declared():
    """``criteo-dp4-train`` through ``benchmarks/run.py --rehearse-cpu``:
    four virtual devices, ``correct`` true; the learner's two metric files
    say what ``BENCHMARK.json`` says."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_ENABLE_COMPILATION_CACHE": "false"}
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "criteo-dp4-train", "--seed", "2147483659",
         # 6 trees have to finish in the window while five other workers
         # load the box
         "--seconds", "12", "--trace", "0", "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    cell = [w for w in manifest["workloads"]
            if w["name"] == "criteo-dp4-train"]
    assert cell and cell[0]["chips"] == 4
    declared = {m["name"]: m for m in manifest["per_layer"]}
    for name in ("collective.exposed_ms_per_tree",
                 "collective.device_ms_per_tree"):
        with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                               name + ".json")) as fh:
            doc = json.load(fh)
        for key in ("unit", "better", "source", "layer", "moves",
                    "workloads"):
            assert doc[key] == declared[name][key], (name, key)
        assert doc["workloads"] == ["criteo-dp4-train"]
