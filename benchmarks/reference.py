"""The plain reference, and the comparison that decides ``correct``.

Imports nothing of the program and takes nothing the program has made
except its *output*: the model dump (``Booster.dump_model()``, the
reference library's own public format) of the first trees the timed
booster produced.  From the seed's raw data the reference then follows
those trees one boosting step at a time (``follow``):

  1. it routes every training row through tree k by the dumped real-valued
     thresholds (``x <= threshold`` goes left) - raw float32 features, not
     the program's bins, so a row the program binned or partitioned wrongly
     lands in another node here;
  2. it computes the objective's gradients and hessians itself, in float64
     on the host (binary log-loss) or in plain ``jax.numpy`` float32
     (lambdarank's pairwise sums), at the reference's own scores;
  3. per node it sums counts, gradients and hessians in float64
     (``numpy.bincount``) and derives what a histogram GBDT has to store
     there: count, hessian sum, leaf output ``-G/(H+l2) * learning_rate``,
     and each split's gain ``GL^2/(HL+l2) + GR^2/(HR+l2) - G^2/(H+l2)``;
  4. it moves its scores by the tree's stored leaf outputs, so that step
     k+1 is judged at what the model of k trees predicts (lambdarank's
     lambdas jump where two scores change order, so a reference that
     followed its own outputs would drift from a sound program by more
     than rounding), and goes on.

Numbers compared (each the worst over the checked trees; see PERF.md §2):

  count_mismatch   nodes whose stored count differs from the routed count,
                   summed over the trees, +1 for a tree that did not split
  update_norm_gap  the gap between the norms, over the training rows, of
                   the stored and the reference's score update (every row
                   moves by its leaf's value), over the reference's norm
  hess_sum_gap     the worst node's stored hessian sum, over
                   max(|reference|, median |reference| of the tree)
  root_gain_gap    the first split's stored gain against the reference's
  total_gain_gap   the sum of a tree's stored gains against the reference's

What one leaf, one split or one threshold reads is a diagnostic, not a
number compared: ``tools/calibrate.py`` computes those from the same steps.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np


# ---------------------------------------------------------------------------
# trees as arrays
# ---------------------------------------------------------------------------

@dataclass
class Tree:
    feature: np.ndarray       # (S,) int   S = splits
    threshold: np.ndarray     # (S,) float64
    left: np.ndarray          # (S,) int   child: >= 0 split, < 0 leaf ~child
    right: np.ndarray
    gain: np.ndarray          # (S,) stored split gain
    node_weight: np.ndarray   # (S,) stored hessian sum
    node_count: np.ndarray    # (S,) stored row count
    leaf_value: np.ndarray    # (L,)
    leaf_weight: np.ndarray
    leaf_count: np.ndarray

    @property
    def num_leaves(self) -> int:
        return len(self.leaf_value)


def parse_tree(tree_info: dict) -> Tree:
    """One entry of ``dump_model()["tree_info"]`` to arrays."""
    splits: Dict[int, dict] = {}
    leaves: Dict[int, dict] = {}

    def child_id(node: dict) -> int:
        if "leaf_index" in node or "split_index" not in node:
            idx = int(node.get("leaf_index", 0))
            leaves[idx] = node
            return ~idx
        idx = int(node["split_index"])
        if node.get("decision_type", "<=") != "<=":
            raise ValueError("reference walks numerical '<=' splits only")
        splits[idx] = node
        return idx

    stack = [tree_info["tree_structure"]]
    child_id(stack[0])
    links = {}
    while stack:
        node = stack.pop()
        if "split_index" not in node:
            continue
        lc, rc = node["left_child"], node["right_child"]
        links[int(node["split_index"])] = (child_id(lc), child_id(rc))
        stack += [lc, rc]
    S, L = len(splits), len(leaves)
    sget = lambda key, dt: np.array([splits[i][key] for i in range(S)], dt)
    lget = lambda key, dt: np.array([leaves[i].get(key, 0) for i in range(L)],
                                    dt)
    return Tree(
        feature=sget("split_feature", np.int64),
        threshold=sget("threshold", np.float64),
        left=np.array([links[i][0] for i in range(S)], np.int64),
        right=np.array([links[i][1] for i in range(S)], np.int64),
        gain=sget("split_gain", np.float64),
        node_weight=sget("internal_weight", np.float64),
        node_count=sget("internal_count", np.int64),
        leaf_value=lget("leaf_value", np.float64),
        leaf_weight=lget("leaf_weight", np.float64),
        leaf_count=lget("leaf_count", np.int64))


def _f32_floor(t: float) -> np.float32:
    """Largest float32 <= t, so that for a float32 x: x <= t (in float64)
    is exactly x <= _f32_floor(t) (in float32)."""
    t32 = np.float32(np.clip(t, -3.4e38, 3.4e38))
    if float(t32) > t:
        t32 = np.nextafter(t32, np.float32(-np.inf))
    return t32


def route(tree: Tree, XT: np.ndarray):
    """Leaf index of every row (int32), walking top-down with row lists."""
    n = XT.shape[1]
    leaf_of_row = np.zeros(n, np.int32)
    if tree.num_leaves <= 1 or len(tree.feature) == 0:
        return leaf_of_row
    stack = [(0, None)]
    while stack:
        node, rows = stack.pop()
        col = XT[tree.feature[node]]
        x = col if rows is None else col[rows]
        go_left = x <= _f32_floor(tree.threshold[node])
        if rows is None:
            parts = (np.flatnonzero(go_left).astype(np.int32),
                     np.flatnonzero(~go_left).astype(np.int32))
        else:
            parts = (rows[go_left], rows[~go_left])
        for child, part in zip((tree.left[node], tree.right[node]), parts):
            if child < 0:
                leaf_of_row[part] = ~child
            else:
                stack.append((int(child), part))
    return leaf_of_row


def predict_raw(trees: List[Tree], XT: np.ndarray) -> np.ndarray:
    """Sum of leaf values of ``trees`` per row, float64."""
    out = np.zeros(XT.shape[1], np.float64)
    for t in trees:
        out += t.leaf_value[route(t, XT)]
    return out


# ---------------------------------------------------------------------------
# held-out quality (the yardstick's own arithmetic)
# ---------------------------------------------------------------------------

def auc(y: np.ndarray, score: np.ndarray) -> float:
    """Exact AUC, half credit for ties (rank-sum form)."""
    order = np.argsort(score, kind="stable")
    s = score[order]
    ranks = np.empty(len(s), np.float64)
    # average ranks over runs of equal scores
    edges = np.flatnonzero(np.concatenate([[True], s[1:] != s[:-1], [True]]))
    for_rank = (edges[:-1] + edges[1:] + 1) / 2.0
    ranks[order] = np.repeat(for_rank, np.diff(edges))
    pos = y > 0
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2.0)
                 / (n_pos * n_neg))


def ndcg_at(y: np.ndarray, score: np.ndarray, group: np.ndarray, k: int
            ) -> float:
    """Mean NDCG@k over queries, gains 2^label - 1, ties in score broken by
    document order; a query with no relevant document counts 1 (LightGBM's
    convention)."""
    qid = np.repeat(np.arange(len(group)), group)
    starts = np.concatenate([[0], np.cumsum(group)[:-1]])
    gains = np.exp2(y) - 1.0

    def dcg(key: np.ndarray) -> np.ndarray:
        order = np.lexsort((np.arange(len(key)), -key, qid))
        pos = np.arange(len(key)) - np.repeat(starts, group)
        w = np.where(pos < k, 1.0 / np.log2(pos + 2.0), 0.0)
        return np.bincount(qid, weights=gains[order] * w,
                           minlength=len(group))

    got, best = dcg(score), dcg(y)
    return float(np.mean(np.where(best > 0, got / np.maximum(best, 1e-300),
                                  1.0)))


def heldout_quality(kind: str, trees: List[Tree], held, eval_at: int = 10
                    ) -> float:
    raw = predict_raw(trees, held.XT)
    if kind == "rank":
        return ndcg_at(held.y, raw, held.group, eval_at)
    return auc(held.y, raw)


# ---------------------------------------------------------------------------
# the objectives, plainly
# ---------------------------------------------------------------------------

class BinaryLogloss:
    """g = p - y, h = p (1 - p), p = sigmoid(score); scores start at the
    log-odds of the mean label (boost_from_average)."""

    def __init__(self, train, params):
        self.y = train.y
        p = float(np.clip(self.y.mean(), 1e-15, 1 - 1e-15))
        self.init_score = float(np.log(p / (1.0 - p)))

    def grads(self, score: np.ndarray):
        p = 1.0 / (1.0 + np.exp(-score))
        return p - self.y, p * (1.0 - p)


class Lambdarank:
    """Pairwise lambdas weighted by |delta NDCG| (LightGBM's
    rank_objective.hpp as the configuration states it: sigmoid 1, gains
    2^label - 1, truncation level 20, lambdarank_norm): for documents i
    better than j of one query,

        p = sigmoid(-(s_i - s_j));  d = |gain_i - gain_j| *
        |disc_i - disc_j| / maxDCG;  lambda_i -= p d, lambda_j += p d,
        hess_{i,j} += p (1 - p) d

    with disc = 1/log2(2 + rank) for rank < truncation, else 0, pairs
    counted when either has a discount, ranks by descending score with ties
    in document order, and each query's lambdas scaled by
    log2(1 + sum|lambda|) / sum|lambda|.  Queries are padded to powers of
    two and run through one jitted ``jax.numpy`` function per width; float32
    as the program states."""

    def __init__(self, train, params):
        import jax
        import jax.numpy as jnp

        self.init_score = 0.0
        self.trunc = int(params.get("lambdarank_truncation_level", 20))
        group = train.group
        self.n = int(group.sum())
        starts = np.concatenate([[0], np.cumsum(group)[:-1]])
        gain = np.exp2(train.y) - 1.0
        widths = np.maximum(8, 2 ** np.ceil(np.log2(np.maximum(group, 1)))
                            ).astype(np.int64)
        self.blocks = []
        budget = 1 << 23
        for w in np.unique(widths):
            qs = np.flatnonzero(widths == w)
            per = max(1, budget // int(w * w))
            for a in range(0, len(qs), per):
                q = qs[a:a + per]
                idx = starts[q][:, None] + np.arange(int(w))[None, :]
                mask = np.arange(int(w))[None, :] < group[q][:, None]
                idx = np.where(mask, idx, 0)
                g = np.where(mask, gain[idx], 0.0)
                top = -np.sort(-g, axis=1)[:, :self.trunc]
                dcg = (top / np.log2(np.arange(top.shape[1]) + 2.0)).sum(1)
                inv = np.where(dcg > 0, 1.0 / np.maximum(dcg, 1e-300), 0.0)
                self.blocks.append((idx, mask, g.astype(np.float32),
                                    inv.astype(np.float32)))
        trunc = self.trunc

        def pairwise(s, gain, mask, inv):
            m = s.shape[1]
            pos = jnp.arange(m)
            s = jnp.where(mask, s, -jnp.inf)
            ahead = (s[:, None, :] > s[:, :, None]) | (
                (s[:, None, :] == s[:, :, None])
                & (pos[None, None, :] < pos[None, :, None]))
            rank = jnp.sum(ahead & mask[:, None, :], axis=2)
            disc = jnp.where(rank < trunc,
                             1.0 / jnp.log2(2.0 + rank.astype(jnp.float32)),
                             0.0)
            better = gain[:, :, None] > gain[:, None, :]
            live = (mask[:, :, None] & mask[:, None, :] & better
                    & ((disc[:, :, None] > 0) | (disc[:, None, :] > 0)))
            d = (jnp.abs(gain[:, :, None] - gain[:, None, :])
                 * jnp.abs(disc[:, :, None] - disc[:, None, :])
                 * inv[:, None, None])
            p = jax.nn.sigmoid(-(s[:, :, None] - s[:, None, :]))
            lam = jnp.where(live, p * d, 0.0)
            hes = jnp.where(live, p * (1.0 - p) * d, 0.0)
            grad = -lam.sum(2) + lam.sum(1)
            hess = hes.sum(2) + hes.sum(1)
            tot = lam.sum((1, 2)) + 1e-10
            scale = jnp.log2(1.0 + tot) / tot
            return grad * scale[:, None], hess * scale[:, None]

        self._pairwise = jax.jit(pairwise)

    def grads(self, score: np.ndarray):
        g = np.zeros(self.n, np.float64)
        h = np.zeros(self.n, np.float64)
        s32 = score.astype(np.float32)
        for idx, mask, gain, inv in self.blocks:
            gq, hq = self._pairwise(s32[idx], gain, mask, inv)
            g[idx[mask]] = np.asarray(gq, np.float64)[mask]
            h[idx[mask]] = np.asarray(hq, np.float64)[mask]
        return g, np.maximum(h, 1e-20)


OBJECTIVES = {"binary": BinaryLogloss, "lambdarank": Lambdarank}


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def _rel_each(stored: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """|stored - ref| over max(|ref|, median |ref|), per element."""
    scale = np.maximum(np.abs(ref), np.median(np.abs(ref)))
    return np.abs(stored - ref) / np.where(scale > 0, scale, 1.0)


@dataclass
class Step:
    """What the reference holds of one boosting step: the program's tree,
    the reference's gradients at its own scores, and the sums a histogram
    GBDT has to store, per leaf (L) and per split node (S)."""
    k: int
    tree: Tree
    leaf: Optional[np.ndarray] = None      # (rows,) leaf of every row
    g: Optional[np.ndarray] = None         # (rows,) gradients, float64
    h: Optional[np.ndarray] = None
    cnt: Optional[np.ndarray] = None       # (L,) routed rows
    H: Optional[np.ndarray] = None         # (L,) hessian sums
    value: Optional[np.ndarray] = None     # (L,) -G/(H+l2) * learning_rate
    stored: Optional[np.ndarray] = None    # (L,) the tree's, init score off
    node_cnt: Optional[np.ndarray] = None  # (S,)
    node_H: Optional[np.ndarray] = None
    gain: Optional[np.ndarray] = None      # (S,) reference split gains


def follow(trees: List[Tree], train, params: dict):
    """Walk ``trees`` (the program's first trees, in order) from the seed's
    training data, one ``Step`` a tree.  A tree that did not split yields a
    ``Step`` with nothing but the tree."""
    lr = float(params.get("learning_rate", 0.1))
    l2 = float(params.get("lambda_l2", 0.0))
    obj = OBJECTIVES[params["objective"]](train, params)
    score = np.full(train.rows, obj.init_score, np.float64)
    for k, tree in enumerate(trees):
        if tree.num_leaves <= 1:
            yield Step(k, tree)
            continue
        g, h = obj.grads(score)
        leaf = route(tree, train.XT)
        L, S = tree.num_leaves, len(tree.feature)
        cnt = np.bincount(leaf, minlength=L).astype(np.int64)
        G = np.bincount(leaf, weights=g, minlength=L)
        H = np.bincount(leaf, weights=h, minlength=L)
        # sums of split nodes from their children, bottom-up
        nc, nG, nH = (np.zeros(S, np.int64), np.zeros(S), np.zeros(S))
        order, stack = [], [0]
        while stack:
            s = stack.pop()
            order.append(s)
            stack += [int(c) for c in (tree.left[s], tree.right[s]) if c >= 0]
        child = lambda c: ((cnt[~c], G[~c], H[~c]) if c < 0
                           else (nc[c], nG[c], nH[c]))
        gain = np.zeros(S)
        for s in reversed(order):
            (cl, gl, hl), (cr, gr, hr) = child(tree.left[s]), child(tree.right[s])
            nc[s], nG[s], nH[s] = cl + cr, gl + gr, hl + hr
            gain[s] = (gl * gl / (hl + l2) + gr * gr / (hr + l2)
                       - nG[s] ** 2 / (nH[s] + l2))
        stored = tree.leaf_value.copy()
        if k == 0:
            stored -= obj.init_score     # the model folds the init score in
        yield Step(k, tree, leaf, g, h, cnt, H, -G / (H + l2) * lr, stored,
                   nc, nH, gain)
        score += stored[leaf]


NUMBERS = ("count_mismatch", "update_norm_gap", "hess_sum_gap",
           "root_gain_gap", "total_gain_gap")


def step_numbers(s: Step) -> Dict[str, float]:
    """The numbers of ``NUMBERS`` for one step."""
    if s.leaf is None:
        # a tree that did not split is a fault at these sizes
        return {"count_mismatch": 1.0}
    tree = s.tree
    # the score update over the training rows: every row moves by its
    # leaf's value, so leaves weigh by their rows
    ref_norm = np.sqrt(np.sum(s.cnt * s.value ** 2))
    return {
        "count_mismatch": float((s.cnt != tree.leaf_count).sum()
                                + (s.node_cnt != tree.node_count).sum()),
        "update_norm_gap": float(
            abs(np.sqrt(np.sum(s.cnt * s.stored ** 2)) - ref_norm) / ref_norm),
        "hess_sum_gap": float(_rel_each(
            np.concatenate([tree.leaf_weight, tree.node_weight]),
            np.concatenate([s.H, s.node_H])).max()),
        "root_gain_gap": float(abs(tree.gain[0] - s.gain[0]) / s.gain[0]),
        "total_gain_gap": float(
            abs(tree.gain.sum() - s.gain.sum()) / s.gain.sum()),
    }


def judge(trees: List[Tree], train, params: dict,
          say=lambda **kw: None, look=None) -> Dict[str, float]:
    """Follow ``trees`` and return every number of ``NUMBERS``: the worst
    over the trees (``count_mismatch``: their sum).  ``look`` is handed each
    ``Step`` (``tools/calibrate.py`` reads its diagnostics there)."""
    out = dict.fromkeys(NUMBERS, 0.0)
    for s in follow(trees, train, params):
        per_tree = step_numbers(s)
        for name, v in per_tree.items():
            out[name] = (out[name] + v if name == "count_mismatch"
                         else max(out[name], v))
        say(tree=s.k, leaves=s.tree.num_leaves, **per_tree)
        if look is not None:
            look(s)
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]):
    """Each number beside its limit; correct when none passes its limit.
    A cell limits every number of ``NUMBERS``: one it left out would be a
    number taken out of ``correct``, so that is an error, not a pass."""
    rows = [(name, float(numbers[name]), float(limits[name]))
            for name in NUMBERS]
    return all(v <= lim for _, v, lim in rows), rows
