"""Tree model arrays and vectorized prediction.

TPU-native re-design of the reference flat-array tree
(reference: ``class Tree``, include/LightGBM/tree.h:25-602, src/io/tree.cpp).

Node encoding follows the reference exactly so the v3 model-text format
round-trips: internal nodes are numbered in split order; ``left_child`` /
``right_child`` hold either an internal node index (>= 0) or ``~leaf_index``
(< 0).  Prediction is a fully vectorized root-to-leaf walk: every row carries
its current node index and a ``lax.while_loop`` advances all rows together
(the reference's per-row ``Tree::Predict`` walk, tree.h:132, becomes a
gather + select per level).

Deployment-scale batched inference lives in ``models/predict.py`` (the
depth-stepped all-trees walk, prebinned serving codes, predictor cache);
this module keeps the single-tree training-time walks, the stacked-scan
parity pin, and the shared host-side structure validators.
"""

from __future__ import annotations

import os
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..io.binning import K_ZERO_THRESHOLD, MISSING_NAN, MISSING_NONE, MISSING_ZERO


class TreeArrays(NamedTuple):
    """One tree (or a stack of trees when arrays carry a leading T axis)."""

    num_leaves: jax.Array       # () int32 — actual leaves (arrays are padded)
    split_feature: jax.Array    # (L-1,) int32
    threshold_bin: jax.Array    # (L-1,) int32
    threshold: jax.Array        # (L-1,) float32 — real-valued threshold
    default_left: jax.Array     # (L-1,) bool
    missing_type: jax.Array     # (L-1,) int32 — copied from split feature meta
    left_child: jax.Array       # (L-1,) int32 (>=0 node, <0 is ~leaf)
    right_child: jax.Array      # (L-1,) int32
    split_gain: jax.Array       # (L-1,) float32
    internal_value: jax.Array   # (L-1,) float32
    internal_weight: jax.Array  # (L-1,) float32
    internal_count: jax.Array   # (L-1,) int32: rows, exact past 2^24
    leaf_value: jax.Array       # (L,) float32
    leaf_weight: jax.Array      # (L,) float32
    leaf_count: jax.Array       # (L,) int32
    leaf_parent: jax.Array      # (L,) int32
    is_cat: jax.Array           # (L-1,) bool — categorical (bitset) split
    cat_bitset: jax.Array       # (L-1, W) uint32 — bin-space membership
                                # (reference: cat_threshold_inner_, tree.h:427)


# Debug-mode bounds contract for leaf_lookup (set LGBM_TPU_DEBUG_BOUNDS=1
# or flip this flag in tests): out-of-range leaf ids poison their rows
# with NaN instead of silently contributing 0.0, so a caller relying on
# the gather's clamp semantics fails loudly instead of training on wrong
# scores.  Off by default — the where() adds a pass over the rows.
DEBUG_BOUNDS = bool(int(os.environ.get("LGBM_TPU_DEBUG_BOUNDS", "0")))


def leaf_lookup(table: jax.Array, leaf_id: jax.Array) -> jax.Array:
    """``table[leaf_id]`` without a device gather.

    PRECONDITION: every ``leaf_id`` must be in ``[0, len(table))``.  The
    XLA gather this replaces CLAMPS out-of-bounds indices to the edge
    entry; the broadcast-compare below instead contributes **0.0** for
    any out-of-range id — a silent semantic change for a caller that
    relied on the clamp.  All in-tree call sites pass partition-produced
    leaf ids, which are in-range by construction; new callers must
    guarantee the same (enable ``DEBUG_BOUNDS`` to get NaN poisoning on
    violations instead of silent zeros).

    TPU gathers run at ~1 element per several cycles (7.8 ms for 1M rows
    from a 255-entry table, tools/microbench_gather.py) while a
    broadcast-compare select-reduce streams the same lookup in ~0.8 ms
    and is EXACT — each row reduces exactly one nonzero, so there is no
    summation error.  Falls back to the native gather for wide tables
    where the O(rows·L) compare loses.  This is the score-application
    analog of the reference ScoreUpdater's per-leaf AddScore
    (src/boosting/score_updater.hpp), reformulated for the VPU."""
    L = table.shape[0]
    lid = leaf_id.astype(jnp.int32)
    if L > 1024:
        out = table[leaf_id]
    else:
        iota = jnp.arange(L, dtype=jnp.int32)
        eq = lid[:, None] == iota[None, :]
        # Each element of the result is value-equal to table[leaf_id], but
        # consumers may see 1-ulp drift vs the gather formulation: XLA is
        # free to reassociate a producer's scale factor across the reduce
        # and fma-fuse into a consumer add (one rounding instead of two).
        # Paths with a PINNED bit-parity contract (the wave grower's
        # valid-score routing vs the tree walk) therefore keep the native
        # gather — valid sets are small; this formulation is for the big
        # train-row tables.
        out = jnp.sum(jnp.where(eq, table[None, :], 0), axis=1)
    if DEBUG_BOUNDS:
        out = jnp.where((lid >= 0) & (lid < L), out, jnp.nan)
    return out


def empty_tree(max_leaves: int, cat_words: int = 1) -> TreeArrays:
    L = max_leaves
    L1 = max(L - 1, 1)
    return TreeArrays(
        num_leaves=jnp.asarray(1, jnp.int32),
        split_feature=jnp.zeros(L1, jnp.int32),
        threshold_bin=jnp.zeros(L1, jnp.int32),
        threshold=jnp.zeros(L1, jnp.float32),
        default_left=jnp.zeros(L1, bool),
        missing_type=jnp.zeros(L1, jnp.int32),
        left_child=jnp.full(L1, -1, jnp.int32),
        right_child=jnp.full(L1, -2, jnp.int32),
        split_gain=jnp.zeros(L1, jnp.float32),
        internal_value=jnp.zeros(L1, jnp.float32),
        internal_weight=jnp.zeros(L1, jnp.float32),
        internal_count=jnp.zeros(L1, jnp.int32),
        leaf_value=jnp.zeros(L, jnp.float32),
        leaf_weight=jnp.zeros(L, jnp.float32),
        leaf_count=jnp.zeros(L, jnp.int32),
        leaf_parent=jnp.full(L, -1, jnp.int32),
        is_cat=jnp.zeros(L1, bool),
        cat_bitset=jnp.zeros((L1, cat_words), jnp.uint32),
    )


# ---------------------------------------------------------------------------
# Binned prediction (training-time: validation data shares the training bins)
# ---------------------------------------------------------------------------


def tree_leaf_index_binned(
    tree: TreeArrays,
    binned: jax.Array,        # (F, N) bins, (BF, N) EFB bundles, or
                              # (ceil(F/2), N) 4-bit packed bytes
    nan_bins: jax.Array,      # (F,) int32
    missing_types: jax.Array,  # (F,) int32
    bundle=None,              # io/bundle.py BundleArrays when EFB applied
    packed: bool = False,     # 4-bit packed bins (two features per byte)
    zero_bins=None,           # (F,) int32 — zero-as-missing routing
) -> jax.Array:               # (N,) int32 leaf index per row
    N = binned.shape[1]
    # Walks are BOUNDED by the node count: an acyclic root-to-leaf path
    # visits each internal node at most once, so `n_nodes` steps always
    # suffice; a malformed/cyclic model (caught at model-text load by
    # validate_host_tree, but constructible via the array API) terminates
    # instead of hanging the predictor.
    max_steps = int(tree.split_feature.shape[0]) + 1

    def cond(state):
        node, it = state
        return jnp.any(node >= 0) & (it < max_steps)

    def body(state):
        node, it = state
        active = node >= 0
        nd = jnp.maximum(node, 0)
        f = tree.split_feature[nd]
        if bundle is not None:
            from ..io.bundle import bundle_bins_of_rows

            b = bundle_bins_of_rows(binned, f, bundle)
        elif packed:
            from ..ops.hist_pallas import packed_bins_of_rows

            b = packed_bins_of_rows(binned, f)
        else:
            b = jnp.take_along_axis(binned, f[None, :], axis=0)[0]
        t = tree.threshold_bin[nd]
        dl = tree.default_left[nd]
        is_na = (missing_types[f] == MISSING_NAN) & (b == nan_bins[f])
        if zero_bins is not None:
            # zero-as-missing rows follow the node's default direction
            # (reference NumericalDecision MissingType::Zero, tree.h:~430;
            # training-side the zero mass rides the scan direction)
            is_na = is_na | ((missing_types[f] == MISSING_ZERO)
                             & (b == zero_bins[f]))
        go_left = jnp.where(is_na, dl, b <= t)
        # categorical: bitset membership (reference CategoricalDecisionInner,
        # tree.h:322-335); the other/unseen bin is never in the set => right
        W = tree.cat_bitset.shape[-1]
        bi = b.astype(jnp.int32)
        word = tree.cat_bitset.reshape(-1)[nd * W + (bi >> 5)]
        in_set = ((word >> (bi.astype(jnp.uint32) & 31)) & 1) == 1
        go_left = jnp.where(tree.is_cat[nd], in_set, go_left)
        nxt = jnp.where(go_left, tree.left_child[nd], tree.right_child[nd])
        node = jnp.where(active, nxt, node)
        return node, it + 1

    node0 = jnp.where(tree.num_leaves > 1,
                      jnp.zeros(N, jnp.int32),
                      jnp.full(N, -1, jnp.int32))
    node, _ = lax.while_loop(cond, body, (node0, jnp.asarray(0, jnp.int32)))
    return -node - 1   # ~node


def leaf_path_features(tree: TreeArrays, num_features: int) -> jax.Array:
    """(L, F) bool — the features split on along each leaf's root path
    (the reference's per-leaf branch features).  Used to mark rows for
    cegb_penalty_feature_lazy: a row 'uses' exactly the features on its
    leaf's path (cost_effective_gradient_boosting.hpp:110-121 marks the
    split leaf's rows at every applied split — the union over the tree is
    precisely the path features of each row's final leaf)."""
    L1 = tree.left_child.shape[0]
    L = tree.leaf_parent.shape[0]
    nidx = jnp.arange(L1, dtype=jnp.int32)
    par = jnp.full(L1, -1, jnp.int32)
    par = par.at[jnp.where(tree.left_child >= 0, tree.left_child,
                           L1 + 1)].set(nidx, mode="drop")
    par = par.at[jnp.where(tree.right_child >= 0, tree.right_child,
                           L1 + 1)].set(nidx, mode="drop")

    def body(_, carry):
        node, feats = carry
        active = node >= 0
        nd = jnp.maximum(node, 0)
        f = tree.split_feature[nd]
        feats = feats | (jax.nn.one_hot(f, num_features, dtype=bool)
                         & active[:, None])
        node = jnp.where(active, par[nd], -1)
        return node, feats

    node0 = tree.leaf_parent
    feats0 = jnp.zeros((L, num_features), bool)
    _, feats = lax.fori_loop(0, max(L1, 1), body, (node0, feats0))
    return feats


def tree_predict_binned(tree, binned, nan_bins, missing_types, bundle=None,
                        packed: bool = False, zero_bins=None):
    leaf = tree_leaf_index_binned(tree, binned, nan_bins, missing_types,
                                  bundle, packed, zero_bins)
    return tree.leaf_value[leaf]


# ---------------------------------------------------------------------------
# Raw-feature prediction (deployment path, reference Tree::Predict)
# ---------------------------------------------------------------------------


def tree_predict_raw(tree: TreeArrays, X: jax.Array) -> jax.Array:
    """X: (N, F) float; NaN = missing. Mirrors Tree::NumericalDecision
    (reference include/LightGBM/tree.h:~430) including missing-type handling.

    Categorical (bitset) nodes are not supported on this device path — the
    deployment predictor for categorical models is the host ``HostTree``
    walk (Booster.predict) or the binned path; raw categorical decisions
    need the raw->bin category dictionary, which lives host-side."""
    N = X.shape[0]
    # bounded like tree_leaf_index_binned: a cyclic child graph must
    # terminate (garbage scores beat a hung predictor; load-time
    # validation is the correctness gate)
    max_steps = int(tree.split_feature.shape[0]) + 1

    def cond(state):
        node, it = state
        return jnp.any(node >= 0) & (it < max_steps)

    def body(state):
        node, it = state
        active = node >= 0
        nd = jnp.maximum(node, 0)
        f = tree.split_feature[nd]
        v = jnp.take_along_axis(X, f[:, None], axis=1)[:, 0]
        t = tree.threshold[nd]
        dl = tree.default_left[nd]
        mtype = tree.missing_type[nd]
        is_nan = jnp.isnan(v)
        v0 = jnp.where(is_nan, 0.0, v)
        is_missing = jnp.where(
            mtype == MISSING_NAN,
            is_nan,
            jnp.where(mtype == MISSING_ZERO,
                      is_nan | (jnp.abs(v0) <= K_ZERO_THRESHOLD), False),
        )
        go_left = jnp.where(is_missing, dl, v0 <= t)
        nxt = jnp.where(go_left, tree.left_child[nd], tree.right_child[nd])
        return jnp.where(active, nxt, node), it + 1

    node0 = jnp.where(tree.num_leaves > 1,
                      jnp.zeros(N, jnp.int32),
                      jnp.full(N, -1, jnp.int32))
    node, _ = lax.while_loop(cond, body, (node0, jnp.asarray(0, jnp.int32)))
    return tree.leaf_value[-node - 1]


def tree_used_features(tree: TreeArrays, num_features: int) -> jax.Array:
    """(F,) bool — features used by this tree's valid internal nodes
    (CEGB model-level used-feature tracking, the analog of
    is_feature_used_in_split_ in cost_effective_gradient_boosting.hpp)."""
    n_nodes = tree.split_feature.shape[0]
    valid = jnp.arange(n_nodes) < (tree.num_leaves - 1)
    oh = jax.nn.one_hot(tree.split_feature, num_features, dtype=bool)
    return jnp.any(oh & valid[:, None], axis=0)


def stack_trees(trees: List[TreeArrays]) -> TreeArrays:
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def host_trees_to_stacked(trees, num_leaves: int = 0) -> TreeArrays:
    """Pad HostTrees (ragged per-tree arrays, REAL thresholds filled) back
    to a fixed-size stacked ``TreeArrays`` for the device batch walk
    (``ensemble_predict_raw``).

    The training-time ``_device_trees`` carry bin-space thresholds only
    (``threshold`` is zeros until ``_fill_real_thresholds`` runs on the
    host copy), so deployment prediction on RAW features must route
    through the host trees — this is the bridge back to the device."""
    L = num_leaves or max(max(t.num_leaves, 2) for t in trees)
    L1 = max(L - 1, 1)
    W = max((t.cat_bitset.shape[1] if t.cat_bitset.ndim == 2
             and t.cat_bitset.shape[0] else 1) for t in trees)

    def pad(a, n, fill, dtype):
        out = np.full(n, fill, dtype)
        out[: len(a)] = a
        return out

    def pad2(a, n, w):
        out = np.zeros((n, w), np.uint32)
        if a.ndim == 2 and a.shape[0]:
            out[: a.shape[0], : a.shape[1]] = a
        return out

    arrs = []
    for t in trees:
        arrs.append(TreeArrays(
            num_leaves=np.int32(t.num_leaves),
            split_feature=pad(t.split_feature, L1, 0, np.int32),
            threshold_bin=pad(t.threshold_bin, L1, 0, np.int32),
            threshold=pad(t.threshold, L1, 0.0, np.float32),
            default_left=pad(t.default_left, L1, False, bool),
            missing_type=pad(t.missing_type, L1, 0, np.int32),
            left_child=pad(t.left_child, L1, -1, np.int32),
            right_child=pad(t.right_child, L1, -2, np.int32),
            split_gain=pad(t.split_gain, L1, 0.0, np.float32),
            internal_value=pad(t.internal_value, L1, 0.0, np.float32),
            internal_weight=pad(t.internal_weight, L1, 0.0, np.float32),
            internal_count=pad(t.internal_count, L1, 0, np.int32),
            leaf_value=pad(t.leaf_value, L, 0.0, np.float32),
            leaf_weight=pad(t.leaf_weight, L, 0.0, np.float32),
            leaf_count=pad(t.leaf_count, L, 0, np.int32),
            leaf_parent=pad(t.leaf_parent, L, -1, np.int32),
            is_cat=pad(t.is_cat, L1, False, bool),
            cat_bitset=pad2(t.cat_bitset, L1, W),
        ))
    return stack_trees([jax.tree_util.tree_map(jnp.asarray, a)
                        for a in arrs])


def ensemble_predict_raw(stacked: TreeArrays, X: jax.Array) -> jax.Array:
    """Sum of all stacked trees' raw predictions for each row.

    PARITY PIN: the sequential per-tree scan walk (one data-dependent
    while-loop per tree).  Deployment prediction routes through the
    depth-stepped all-trees walk (models/predict.serving_leaf_raw /
    serving_leaf_binned); this path is kept as the bit-parity reference
    and is reachable via ``predict_method=scan``."""

    def step(acc, tree):
        return acc + tree_predict_raw(tree, X), None

    out, _ = lax.scan(step, jnp.zeros(X.shape[0], jnp.float32), stacked)
    return out


def leaves_to_scores(leaf_value: jax.Array, leaf: jax.Array,
                     K: int) -> jax.Array:
    """(N, T) leaf indices + (T, L) stacked leaf values -> (N, K) raw
    scores, class k summing trees ``k, k+K, k+2K, ...`` (iteration-major
    tree order, reference GBDT::PredictRaw)."""
    N, T = leaf.shape
    ti = jnp.arange(T, dtype=jnp.int32)[None, :]
    vals = leaf_value[ti, leaf]                            # (N, T)
    return vals.reshape(N, T // K, K).sum(axis=1)


def pad_tree_axis(tables, t_pad: int):
    """Zero-pad every stacked (T, ...) table along the TREE axis to
    ``t_pad`` trees — the fused serving kernel's tile slicing
    (ops/predict_pallas.serving_fused_pallas) needs the tree axis to be
    a multiple of the planner's tree tile.  A zero-padded tree has
    ``num_leaves == 0``, so the walks park it on leaf 0 whose value is
    0.0: scores are unchanged and leaf-mode callers slice the pad away.
    Works on any NamedTuple of stacked arrays whose leading axis is T
    (ServingArrays, TreeArrays)."""
    T = int(tables.num_leaves.shape[0])
    if t_pad < T:
        raise ValueError(f"t_pad={t_pad} < T={T}")
    if t_pad == T:
        return tables
    pad = t_pad - T
    return type(tables)(*(
        jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
        for a in tables))


def validate_host_tree(t, index: int = -1) -> None:
    """Child-pointer structural validation (cycle / out-of-range /
    reconvergence / unreachable-leaf detection).  A malformed model file
    previously HUNG the bounded-by-``any(active)`` while-loop walks; now
    load fails loudly here and the device walks are step-bounded as
    defense in depth.  Raises ``ValueError``."""
    n = int(t.num_leaves)
    where = f"tree {index}" if index >= 0 else "tree"
    if n <= 1:
        return
    n_nodes = n - 1
    lc = np.asarray(t.left_child)
    rc = np.asarray(t.right_child)
    if len(lc) < n_nodes or len(rc) < n_nodes:
        raise ValueError(f"{where}: child arrays shorter than num_leaves-1")
    seen = np.zeros(n_nodes, bool)
    seen_leaf = np.zeros(n, bool)
    seen[0] = True
    stack = [0]
    while stack:
        nd = stack.pop()
        for c in (int(lc[nd]), int(rc[nd])):
            if c >= 0:
                if c >= n_nodes:
                    raise ValueError(
                        f"{where}: child index {c} out of range "
                        f"(num_leaves={n})")
                if seen[c]:
                    raise ValueError(
                        f"{where}: node {c} reached twice — cyclic or "
                        "reconvergent child pointers")
                seen[c] = True
                stack.append(c)
            else:
                leaf = -c - 1
                if leaf >= n:
                    raise ValueError(
                        f"{where}: leaf index {leaf} out of range "
                        f"(num_leaves={n})")
                if seen_leaf[leaf]:
                    raise ValueError(
                        f"{where}: leaf {leaf} reached twice — malformed "
                        "child pointers")
                seen_leaf[leaf] = True
    if not seen.all():
        raise ValueError(f"{where}: unreachable internal nodes "
                         f"{np.flatnonzero(~seen).tolist()}")
    if not seen_leaf.all():
        raise ValueError(f"{where}: unreachable leaves "
                         f"{np.flatnonzero(~seen_leaf).tolist()}")


def host_tree_depth(t) -> int:
    """Max root-to-leaf decision count (edges).  Assumes a validated
    tree; guards the level walk by the node count regardless."""
    n = int(t.num_leaves)
    if n <= 1:
        return 0
    n_nodes = n - 1
    lc = np.asarray(t.left_child)
    rc = np.asarray(t.right_child)
    depth = 0
    frontier = [0]
    while frontier and depth <= n_nodes:
        depth += 1
        frontier = [c for nd in frontier for c in (int(lc[nd]), int(rc[nd]))
                    if c >= 0]
    if frontier:
        raise ValueError("host_tree_depth: path longer than the node "
                         "count — cyclic child pointers")
    return depth


# ---------------------------------------------------------------------------
# Host-side (numpy) tree — exact mirror used by the text model format/CLI
# ---------------------------------------------------------------------------


class HostTree:
    """Numpy copy of one tree; the object serialized to/from model text."""

    FIELDS = [
        "split_feature", "threshold_bin", "threshold", "default_left",
        "missing_type", "left_child", "right_child", "split_gain",
        "internal_value", "internal_weight", "internal_count",
        "leaf_value", "leaf_weight", "leaf_count", "leaf_parent",
    ]

    def __init__(self, arrays: TreeArrays, shrinkage: float = 1.0):
        self.num_leaves = int(arrays.num_leaves)
        n_nodes = max(self.num_leaves - 1, 0)
        as_np = lambda a: np.asarray(a)
        self.split_feature = as_np(arrays.split_feature)[:n_nodes].astype(np.int32)
        self.threshold_bin = as_np(arrays.threshold_bin)[:n_nodes].astype(np.int32)
        self.threshold = as_np(arrays.threshold)[:n_nodes].astype(np.float64)
        self.default_left = as_np(arrays.default_left)[:n_nodes].astype(bool)
        self.missing_type = as_np(arrays.missing_type)[:n_nodes].astype(np.int32)
        self.left_child = as_np(arrays.left_child)[:n_nodes].astype(np.int32)
        self.right_child = as_np(arrays.right_child)[:n_nodes].astype(np.int32)
        self.split_gain = as_np(arrays.split_gain)[:n_nodes].astype(np.float64)
        self.internal_value = as_np(arrays.internal_value)[:n_nodes].astype(np.float64)
        self.internal_weight = as_np(arrays.internal_weight)[:n_nodes].astype(np.float64)
        self.internal_count = as_np(arrays.internal_count)[:n_nodes].astype(np.int64)
        self.leaf_value = as_np(arrays.leaf_value)[: self.num_leaves].astype(np.float64)
        self.leaf_weight = as_np(arrays.leaf_weight)[: self.num_leaves].astype(np.float64)
        self.leaf_count = as_np(arrays.leaf_count)[: self.num_leaves].astype(np.int64)
        self.leaf_parent = as_np(arrays.leaf_parent)[: self.num_leaves].astype(np.int32)
        self.is_cat = as_np(arrays.is_cat)[:n_nodes].astype(bool)
        self.cat_bitset = as_np(arrays.cat_bitset)[:n_nodes].astype(np.uint32)
        # raw-category sets per node (None for numerical nodes); filled from
        # the bin mappers by GBDT._fill_real_thresholds — the bin->category
        # translation the reference does in Tree::SplitCategorical
        self.cat_sets = [None] * n_nodes
        self.shrinkage = shrinkage

    def cat_bins_of(self, node: int) -> np.ndarray:
        """Bins in node's left set, decoded from the bin-space bitset."""
        words = self.cat_bitset[node]
        bits = np.unpackbits(words.view(np.uint8), bitorder="little")
        return np.flatnonzero(bits)

    def apply_shrinkage(self, rate: float) -> None:
        """reference: Tree::Shrinkage, tree.h:187-196."""
        self.leaf_value *= rate
        self.internal_value *= rate
        self.shrinkage *= rate

    def add_bias(self, val: float) -> None:
        """Fold a constant score into the tree (reference: Tree::AddBias,
        tree.h:198-211 — used to embed the boost-from-average init score into
        the saved model; forces shrinkage to 1)."""
        if val == 0.0:
            return
        self.leaf_value += val
        self.internal_value += val
        self.shrinkage = 1.0

    def set_leaf_values(self, values: np.ndarray) -> None:
        self.leaf_value = np.asarray(values, dtype=np.float64)[: self.num_leaves]

    # -- numpy prediction (exact, host) ------------------------------------
    def _go_left(self, nd: np.ndarray, v: np.ndarray) -> np.ndarray:
        """Vectorized Tree::Decision (reference tree.h:331-339): numerical
        threshold compare or categorical raw-value bitset membership."""
        t = self.threshold[nd]
        dl = self.default_left[nd]
        mt = self.missing_type[nd]
        isnan = np.isnan(v)
        v0 = np.where(isnan, 0.0, v)
        miss = np.where(
            mt == MISSING_NAN, isnan,
            np.where(mt == MISSING_ZERO,
                     isnan | (np.abs(v0) <= K_ZERO_THRESHOLD), False),
        )
        go_left = np.where(miss, dl, v0 <= t)
        cat_rows = self.is_cat[nd]
        if cat_rows.any():
            # reference CategoricalDecision (tree.h:302-320): C truncation
            # cast (static_cast<int>), NOT rounding; negatives and NaN go
            # right (our binning routes both to the other/unseen bin, which
            # is never in the left set)
            vi = np.where(isnan, -1, np.trunc(v0)).astype(np.int64)
            for node in np.unique(nd[cat_rows]):
                m = cat_rows & (nd == node)
                s = self.cat_sets[node]
                if s is None:
                    s = self.cat_bins_of(node)
                go_left[m] = (vi[m] >= 0) & np.isin(vi[m], np.asarray(s))
        return go_left

    def _walk(self, X: np.ndarray):
        """Root-to-leaf walk; returns the leaf index per row."""
        N = X.shape[0]
        leaf = np.zeros(N, dtype=np.int32)
        if self.num_leaves <= 1:
            return leaf
        node = np.zeros(N, dtype=np.int64)
        active = np.ones(N, dtype=bool)
        while active.any():
            nd = node[active]
            f = self.split_feature[nd]
            v = X[active, f].astype(np.float64)
            go_left = self._go_left(nd, v)
            nxt = np.where(go_left, self.left_child[nd], self.right_child[nd])
            node[active] = nxt
            idx = np.flatnonzero(active)
            done = nxt < 0
            leaf[idx[done]] = -nxt[done] - 1
            active[idx[done]] = False
        return leaf

    def predict(self, X: np.ndarray) -> np.ndarray:
        if self.num_leaves < 1:
            return np.zeros(X.shape[0], dtype=np.float64)
        return self.leaf_value[self._walk(X)]

    def predict_leaf_index(self, X: np.ndarray) -> np.ndarray:
        return self._walk(X)

    def to_arrays(self, max_leaves: int) -> TreeArrays:
        L = max_leaves
        L1 = max(L - 1, 1)
        W = self.cat_bitset.shape[1] if self.cat_bitset.ndim == 2 and \
            self.cat_bitset.shape[1] > 0 else 1

        def pad(a, n, dtype, fill=0):
            out = np.full(n, fill, dtype=dtype)
            out[: len(a)] = a
            return jnp.asarray(out)

        bitset = np.zeros((L1, W), np.uint32)
        bitset[: len(self.cat_bitset)] = self.cat_bitset
        return TreeArrays(
            num_leaves=jnp.asarray(self.num_leaves, jnp.int32),
            split_feature=pad(self.split_feature, L1, np.int32),
            threshold_bin=pad(self.threshold_bin, L1, np.int32),
            threshold=pad(self.threshold, L1, np.float32),
            default_left=pad(self.default_left, L1, bool),
            missing_type=pad(self.missing_type, L1, np.int32),
            left_child=pad(self.left_child, L1, np.int32, -1),
            right_child=pad(self.right_child, L1, np.int32, -1),
            split_gain=pad(self.split_gain, L1, np.float32),
            internal_value=pad(self.internal_value, L1, np.float32),
            internal_weight=pad(self.internal_weight, L1, np.float32),
            internal_count=pad(self.internal_count, L1, np.int32),
            leaf_value=pad(self.leaf_value, L, np.float32),
            leaf_weight=pad(self.leaf_weight, L, np.float32),
            leaf_count=pad(self.leaf_count, L, np.int32),
            leaf_parent=pad(self.leaf_parent, L, np.int32, -1),
            is_cat=pad(self.is_cat, L1, bool),
            cat_bitset=jnp.asarray(bitset),
        )
