"""Stored sums that hold at small leaves: renewal from the rows.

A histogram GBDT never sums a node's gradients directly.  What a tree
stores of a node comes from its parent's split scan: the left child's sums
are a cumulative sum over cells of the parent's histogram, the right
child's are ``parent_sum - left`` (ops/split.py), and the parent's
histogram is itself either measured (the root, the smaller child of a
split) or ``parent - sibling`` (the larger child: reference
``FeatureHistogram::Subtract``).  Every subtraction keeps the ABSOLUTE
rounding error of what it subtracts from.  The reference sums in float64
and never notices; this program's passes round each addend to a bf16
hi+lo pair (relative 2^-17), to a single bf16 in the sustained deep
rounds (2^-9), or sum float32 cell by cell on the CPU (which loses
``rows * 2^-26`` of a cell once its sum dwarfs the addend) - and where
every row of a class carries the same gradient, as in the first trees of
a binary model, that rounding has one sign for millions of rows.  A
20-row leaf cut by subtraction from a 16M-row ancestor then stores sums
that are all error (PERF.md, PR 28: leaf value 390.6 for 0.144).

This module does the accounting and the repair, after a tree is grown and
before anything reads it, for every grower and learner alike:

* ``inherited_error`` follows the finished tree (children, exact counts)
  and bounds, in rows-times-rounding units, the part of each node's stored
  sums that rows OUTSIDE the node contributed: the foreign error.  A
  node's own rows round proportionally to its own sums, which no pass can
  avoid; the foreign part is what subtraction brings.
* a node whose foreign error, with the rounding of the pass its sums were
  read from, exceeds ``tau`` of its own row count is **marked**: its sums
  do not hold to ``tau``.  (So every sum read from a single-bf16 deep
  round is marked: those rounds find splits, they do not fill the model.)
* when a tree has a marked node, ONE more pass sums every leaf's rows
  directly (``leaf_sums``: a histogram pass with the leaf id for its only
  feature, at the configured ``hist_dtype`` - a direct measurement at the
  precision the user chose, not a better one), node sums follow bottom-up,
  and marked leaves and nodes take their weight and output, and splits
  with a marked child their gain, from the measured sums.  Unmarked
  entries keep what the scan gave them.  The same pass counts every
  leaf's rows, and the stored counts (int32) are those and their integer
  sums up the tree: the scan's counts are float32 sums and differences,
  exact only below 2^24 rows a node.

The reference library has the same remedy for its own low-precision
histograms: ``quant_train_renew_leaf`` recomputes leaf outputs from the
true gradients after a tree grown on quantized ones
(gradient_discretizer.cpp RenewIntGradTreeOutput).

No option: the policy is read from what the trainer can see (histogram
method, precisions, whether a deep bucket exists, whether the grower
subtracts).  Left as grown: trees under monotone constraints or path
smoothing (their outputs need per-leaf constraints and parent outputs the
finished tree does not keep), and trees of more than ``MAX_LEAVES``
leaves.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.split import SplitParams, leaf_gain, leaf_output

TAU = 2.0 ** -13          # foreign error tolerated, as a share of own rows
MAX_LEAVES = 2048         # the accounting holds (2L - 1)^2 flags

# relative rounding of one addend of a histogram pass, worst case
_ROUNDING = {"f32": 2.0 ** -24, "bf16x2": 2.0 ** -17, "bf16": 2.0 ** -9,
             "int8": 2.0 ** -8, "int8sr": 2.0 ** -7}


def pass_rounding(method: str, precision: str, rows: int) -> float:
    """Relative rounding one histogram pass leaves on a cell, worst case
    (every addend off the same way).  The scatter path adds float32 cell
    by cell in row order whatever ``precision`` says: once a cell's sum is
    2^k addends the next addend loses its last k bits, the same way for
    every row that follows."""
    if method == "scatter":
        return 2.0 ** -24 + rows * 2.0 ** -28
    return _ROUNDING[precision]


class RenewPolicy(NamedTuple):
    """What the trainer saw (all static)."""
    eps_root: float          # rounding of the root pass
    eps_rest: float          # the worst rounding of any later pass
    subtracts: bool          # larger children come by parent - sibling
    gains: bool              # split gains are the plain three-term form
    tau: float = TAU


# ---------------------------------------------------------------------------
# the accounting: one function for jnp (the step) and numpy (the record)
# ---------------------------------------------------------------------------

def inherited_error(xp, left_child, right_child, num_leaves, internal_count,
                    leaf_count, policy: RenewPolicy):
    """``(mark, anc)`` over the combined index space of a tree of ``L``
    leaf slots: internal node ``i`` is ``i``, leaf ``l`` is ``L - 1 + l``
    (``M = 2L - 1`` entries).  ``mark[x]``: the stored sums of ``x`` carry
    more error than ``tau`` of its rows (the foreign error F below, and
    the rounding of the pass whose cells they were read from).
    ``anc[x, y]``: ``y`` is ``x`` or an ancestor of ``x``.

    Per node, in units of rows x rounding:

      E[x]  foreign error of x's HISTOGRAM: 0 where it was measured (root,
            smaller child), else E[parent] + (eps(parent) + eps_rest) *
            rows(sibling): everything the subtraction kept of rows that
            left
      F[x]  foreign error of x's stored SUMS: a left child's are cells of
            the parent's histogram, F = E[parent]; a right child's are
            parent_sum - left, F = F[parent] + E[parent] + eps(parent) *
            rows(left) + (the parent's own rounding, unless it is the
            root, whose sums are a plain float32 reduction)
    """
    L1 = left_child.shape[0]                  # internal slots = L - 1
    L = L1 + 1
    M = L + L1
    idx = xp.arange(M)
    node_ok = xp.arange(L1) < (num_leaves - 1)
    comb = lambda c: xp.where(c >= 0, c, L1 + (-c - 1))
    lc = xp.where(node_ok, comb(left_child), M)           # (L1,) or M=none
    rc = xp.where(node_ok, comb(right_child), M)
    is_l = idx[:, None] == lc[None, :]                    # (M, L1)
    is_r = idx[:, None] == rc[None, :]
    has_par = (is_l | is_r).any(axis=1)
    parent = xp.where(has_par, (is_l | is_r).argmax(axis=1), 0)   # root: 0
    is_right = is_r.any(axis=1)
    count = xp.concatenate([internal_count, leaf_count]).astype(xp.float32)
    n_left = xp.concatenate([count, xp.zeros(1, xp.float32)])[lc]  # (L1,)
    n_right = xp.concatenate([count, xp.zeros(1, xp.float32)])[rc]
    n_sib = xp.where(is_right, n_left[parent], n_right[parent])
    n_sib = xp.where(has_par, n_sib, 0.0)
    is_root = idx == 0
    # a one-leaf tree has no node 0: nothing is marked below
    live = has_par | (is_root & (num_leaves > 1))

    # ancestor-or-self by pointer doubling: rows of ancestors OR-ed in
    anc = idx[:, None] == idx[None, :]
    jump = xp.where(has_par, parent, idx)
    for _ in range(max(1, int(np.ceil(np.log2(max(L, 2)))))):
        anc = anc | anc[jump]
        jump = jump[jump]
    depth = anc.sum(axis=1)

    def since(reset, add):
        """Sum of ``add`` over the path from the deepest ancestor-or-self
        where ``reset`` holds (exclusive) down to each entry (inclusive);
        ``reset`` holds at the root."""
        total = (anc * add[None, :]).sum(axis=1)
        base = (anc & reset[None, :]) * depth[None, :]
        at = base.argmax(axis=1)
        return total - total[at], at

    eps_any = max(policy.eps_root, policy.eps_rest)
    eps_of = xp.where(is_root, policy.eps_root, eps_any).astype(xp.float32)
    if policy.subtracts:
        # the larger child is derived; equal counts measure the left
        derived = has_par & xp.where(is_right, n_sib <= count, n_sib < count)
    else:
        derived = xp.zeros(M, bool)
    w = xp.where(derived, (eps_of[parent] + policy.eps_rest) * n_sib, 0.0)
    E, _ = since(~derived, w.astype(xp.float32))
    own_par = xp.where(is_root[parent], 0.0, eps_any * count[parent])
    a = xp.where(is_right,
                 E[parent] + eps_of[parent] * n_sib + own_par, 0.0)
    F, q = since(~is_right, a.astype(xp.float32))
    F = F + xp.where(has_par[q], E[parent[q]], 0.0)
    # beside the foreign error, the rounding of the pass behind the cells
    # the sums were read from: where that alone passes tau (a single-bf16
    # deep round), every sum read from such cells is measured again
    mark = live & has_par & (F + eps_of[parent] * count > policy.tau * count)
    return mark, anc


def count_marked(tree, policy: Optional[RenewPolicy]) -> int:
    """Nodes and leaves of a finished tree (host or device arrays) whose
    stored sums were renewed: the per-tree count of the iteration record.
    The marks depend on the tree's shape and exact counts alone, so they
    can be read back from the tree at any later time."""
    if policy is None:
        return 0
    L = int(np.asarray(tree.leaf_count).shape[-1])
    if L > MAX_LEAVES or L < 2:
        return 0
    mark, _ = inherited_error(
        np, np.asarray(tree.left_child), np.asarray(tree.right_child),
        int(np.asarray(tree.num_leaves)), np.asarray(tree.internal_count),
        np.asarray(tree.leaf_count), policy)
    return int(mark.sum())


# ---------------------------------------------------------------------------
# the measurement: every leaf's rows summed directly
# ---------------------------------------------------------------------------

def leaf_sums(leaf_id, g3, L: int, method: str = "scatter",
              precision: str = "bf16x2", interpret: bool = False):
    """``(L, 3)`` sums of ``g3`` over the rows of each leaf, at the
    precision of the learner's own histogram passes (ops/leaf_sums.py):
    the kernel where the passes are the kernel's, float32 partial sums
    otherwise - of addends rounded as ``precision`` rounds them where the
    method's passes round theirs (``onehot``), as they are where it sums
    float32 (``scatter``)."""
    from ..ops.leaf_sums import (TERMS, bf16_terms, leaf_sums_chunked,
                                 leaf_sums_pallas, quantized)

    with jax.named_scope("lgbm.hist"), jax.named_scope("lgbm.renew"):
        if method == "pallas":
            return leaf_sums_pallas(leaf_id, g3, L, precision=precision,
                                    interpret=interpret)
        if method == "onehot":
            v, scale = quantized(g3.astype(jnp.float32).T, precision)
            v = sum(t.astype(jnp.float32)
                    for t in bf16_terms(v, TERMS[precision]))
            g3 = (v if scale is None else v * scale).T
        return leaf_sums_chunked(leaf_id, g3, L)


# ---------------------------------------------------------------------------
# the repair
# ---------------------------------------------------------------------------

def renew_tree(tree, leaf_id, g3, params: SplitParams, policy: RenewPolicy,
               leaf_sums_fn: Callable):
    """``tree`` with the marked entries' weights, outputs and gains taken
    from directly measured sums.  ``leaf_sums_fn(leaf_id, g3) -> (L, 3)``
    is the learner's (a row-sharded learner adds its shards up inside)."""
    L = tree.leaf_value.shape[0]
    L1 = L - 1
    if L < 2 or L > MAX_LEAVES:
        return tree
    with jax.named_scope("lgbm.renew"):
        mark, anc = inherited_error(
            jnp, tree.left_child, tree.right_child, tree.num_leaves,
            tree.internal_count, tree.leaf_count, policy)
        any_marked = mark.any()

    def renewed(_):
        S_leaf = leaf_sums_fn(leaf_id, g3)                    # (L, 3)
        with jax.named_scope("lgbm.renew"):
            under = anc[L1:, :L1]                             # leaf x node
            S_node = jnp.sum(jnp.where(under[:, :, None],
                                       S_leaf[:, None, :], 0.0), axis=0)
            S = jnp.concatenate([S_node, S_leaf])             # (M, 3)
            out = leaf_output(S[:, 0], S[:, 1], params)
            m_node, m_leaf = mark[:L1], mark[L1:]
            # the rows of every leaf and node, counted again whether marked
            # or not: the grower's counts are float32 sums and differences,
            # exact only below 2^24 rows a node (a 26.6 M-row root's larger
            # child reads one row off, and every count derived from it by
            # subtraction after it); a leaf's measured count is exact below
            # 2^24 rows a LEAF, and the nodes' are integer sums of those
            n_leaf = jnp.round(S_leaf[:, 2]).astype(jnp.int32)
            n_node = jnp.sum(jnp.where(under, n_leaf[:, None], 0), axis=0)
            new = tree._replace(
                leaf_count=n_leaf, internal_count=n_node,
                internal_weight=jnp.where(m_node, S_node[:, 1],
                                          tree.internal_weight),
                internal_value=jnp.where(m_node, out[:L1],
                                         tree.internal_value),
                leaf_weight=jnp.where(m_leaf, S_leaf[:, 1],
                                      tree.leaf_weight),
                leaf_value=jnp.where(m_leaf, out[L1:], tree.leaf_value))
            if policy.gains:
                comb = lambda c: jnp.where(c >= 0, c, L1 + (-c - 1))
                l, r = comb(tree.left_child), comb(tree.right_child)
                g = lambda s: leaf_gain(s[:, 0], s[:, 1], params)
                gain = (g(S[l]) + g(S[r]) - g(S_node)
                        - params.min_gain_to_split)
                redo = (mark[l] | mark[r]) & ~tree.is_cat
                new = new._replace(split_gain=jnp.where(
                    redo, gain.astype(jnp.float32), tree.split_gain))
            return new

    return lax.cond(any_marked, renewed, lambda _: tree, None)


def with_renewal(grow: Callable, params: SplitParams, policy: RenewPolicy,
                 leaf_sums_fn: Callable) -> Callable:
    """``grow`` followed by ``renew_tree``; same signature, same outputs."""
    def grown(binned, g3, base_mask, key, cegb_used=None, **kw):
        out = grow(binned, g3, base_mask, key, cegb_used, **kw)
        tree = renew_tree(out[0], out[1], g3, params, policy, leaf_sums_fn)
        return (tree,) + tuple(out[1:])

    grown.__dict__.update(grow.__dict__)
    return grown
