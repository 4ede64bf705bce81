"""Lambdarank objective tests: pairwise gradients on row windows.

reference: rank_objective.hpp:98-230 (per-query sigmoid-weighted lambdas,
|ΔNDCG| scaling, truncation, lambdarank_norm).  The window layout
(objectives._window_queries) must (a) match a direct per-query oracle
exactly and (b) survive MSLR-shaped query-length distributions (30k+
queries, docs/query up to ~1300) without materializing (Q, Mmax, Mmax).
The package builds heads x documents pair tensors (Qc, T, W) on windows of
whole 128-document rows; the documents x documents formulation it
replaced, on queries padded to powers of two and gathered document by
document, is kept here as the plain reference (``_square_chunk_grads``).
"""

import numpy as np
import pytest

import lightgbmv1_tpu as lgb
from lightgbmv1_tpu.config import Config
from lightgbmv1_tpu.io.dataset import Metadata
from lightgbmv1_tpu.objectives import (LambdarankNDCG, _count_pair_elements,
                                        _window_queries, _window_rows)


def _oracle_lambdarank(scores, labels, qb, gains, sigmoid, trunc, norm):
    """Direct per-query numpy port of the reference's GetGradientsForOneQuery
    (rank_objective.hpp:139-230) under this repo's formulation."""
    N = len(scores)
    grad = np.zeros(N)
    hess = np.zeros(N)
    for b, e in zip(qb[:-1], qb[1:]):
        sc = scores[b:e]
        g = gains[labels[b:e]]
        n = e - b
        order = np.argsort(-sc, kind="stable")
        ranks = np.empty(n, np.int64)
        ranks[order] = np.arange(n)
        disc = np.where(ranks < trunc, 1.0 / np.log2(2.0 + ranks), 0.0)
        ideal = np.sort(g)[::-1][: max(trunc, 1)]
        idcg = (ideal / np.log2(np.arange(2, len(ideal) + 2))).sum()
        inv = 1.0 / idcg if idcg > 0 else 0.0
        lam = np.zeros((n, n))
        hes = np.zeros((n, n))
        for i in range(n):
            for j in range(n):
                if g[i] <= g[j] or (disc[i] == 0 and disc[j] == 0):
                    continue
                delta = abs(g[i] - g[j]) * abs(disc[i] - disc[j]) * inv
                p = 1.0 / (1.0 + np.exp(sigmoid * (sc[i] - sc[j])))
                lam[i, j] = -sigmoid * p * delta
                hes[i, j] = sigmoid * sigmoid * p * (1 - p) * delta
        gq = lam.sum(axis=1) - lam.sum(axis=0)
        hq = hes.sum(axis=1) + hes.sum(axis=0)
        if norm:
            s = np.abs(lam).sum() + 1e-10
            scale = np.log2(1.0 + s) / s
            gq, hq = gq * scale, hq * scale
        grad[b:e] = gq
        hess[b:e] = hq
    return grad, np.maximum(hess, 1e-20)


def _square_chunk_grads(scores, gains, q_mask, inv_dcg, sig, trunc, norm):
    """The documents x documents formulation the package had before the
    pair tensor became heads x documents: every (Qc, Mb, Mb) pair built,
    those with no discount on either side zeroed.  float32 ``jax.numpy``,
    as the package computes."""
    import jax
    import jax.numpy as jnp

    scores = jnp.where(q_mask, scores, -jnp.inf)
    order = jnp.argsort(-scores, axis=1)                # stable: ties in
    ranks = jnp.zeros_like(order).at[                   # document order
        jnp.arange(order.shape[0])[:, None], order
    ].set(jnp.arange(order.shape[1])[None, :])
    discount = 1.0 / jnp.log2(2.0 + ranks.astype(jnp.float32))
    discount = jnp.where(ranks < trunc, discount, 0.0)
    sd = scores[:, :, None] - scores[:, None, :]
    gd = gains[:, :, None] - gains[:, None, :]
    dd = jnp.abs(discount[:, :, None] - discount[:, None, :])
    pair_mask = (
        q_mask[:, :, None] & q_mask[:, None, :] & (gd > 0)
        & ((discount[:, :, None] > 0) | (discount[:, None, :] > 0)))
    delta = jnp.abs(gd) * dd * inv_dcg[:, None, None]
    p = jax.nn.sigmoid(-sig * sd)
    lam = jnp.where(pair_mask, -sig * p * delta, 0.0)
    hes = jnp.where(pair_mask, sig * sig * p * (1.0 - p) * delta, 0.0)
    grad_q = lam.sum(axis=2) - lam.sum(axis=1)
    hess_q = hes.sum(axis=2) + hes.sum(axis=1)
    if norm:
        tot = jnp.sum(jnp.abs(lam), axis=(1, 2)) + 1e-10
        scale = jnp.log2(1.0 + tot) / tot
        grad_q, hess_q = grad_q * scale[:, None], hess_q * scale[:, None]
    return grad_q, hess_q


def _square_gradients(obj, scores, labels):
    """``get_gradients`` through ``_square_chunk_grads``, query by bucket
    of one power-of-two width (few queries a call: XLA:CPU holds the
    (Qc, Mb, Mb) tensors)."""
    import jax.numpy as jnp

    qb = obj.qb
    sizes = np.diff(qb)
    gain = np.asarray(obj.config.label_gain_or_default,
                      np.float32)[np.asarray(labels, np.int64)]
    grad = np.zeros(len(scores), np.float32)
    hess = np.zeros(len(scores), np.float32)
    widths = np.maximum(8, 1 << np.ceil(
        np.log2(np.maximum(sizes, 1))).astype(np.int64))
    for w in np.unique(widths):
        qids = np.flatnonzero(widths == w)
        per = max(1, (1 << 22) // int(w * w))
        for a in range(0, len(qids), per):
            q = qids[a:a + per]
            idx = qb[q][:, None] + np.arange(int(w))[None, :]
            mask = np.arange(int(w))[None, :] < sizes[q][:, None]
            idx = np.where(mask, idx, 0)
            inv = np.zeros(len(q))
            for r, qi in enumerate(q):
                g = np.sort(gain[qb[qi]:qb[qi + 1]].astype(np.float64)
                            )[::-1][:obj._trunc]
                dcg = (g / np.log2(np.arange(2, len(g) + 2))).sum()
                inv[r] = 1.0 / dcg if dcg > 0 else 0.0
            gq, hq = _square_chunk_grads(
                jnp.asarray(scores[idx]), jnp.asarray(gain[idx]),
                jnp.asarray(mask), jnp.asarray(inv, jnp.float32),
                obj._sig, obj._trunc, obj._norm)
            grad[idx[mask]] = np.asarray(gq)[mask]
            hess[idx[mask]] = np.asarray(hq)[mask]
    return grad, np.maximum(hess, 1e-20)


def _make_objective(labels, group, cfg_extra=None):
    cfg = Config.from_dict({"objective": "lambdarank", "verbosity": -1,
                            **(cfg_extra or {})})
    obj = LambdarankNDCG(cfg)
    meta = Metadata(label=np.asarray(labels, np.float32))
    meta.set_group(np.asarray(group))
    obj.init(meta, len(labels))
    return obj, cfg


@pytest.mark.parametrize("norm", [True, False])
def test_bucketed_matches_oracle(norm):
    rng = np.random.RandomState(0)
    group = rng.randint(3, 40, size=25)              # mixed query lengths
    N = int(group.sum())
    labels = rng.randint(0, 4, N)
    scores = rng.randn(N).astype(np.float32)
    obj, cfg = _make_objective(labels, group,
                               {"lambdarank_norm": norm})
    import jax.numpy as jnp

    g, h = obj.get_gradients(jnp.asarray(scores))
    qb = np.concatenate([[0], np.cumsum(group)])
    gains = np.asarray(cfg.label_gain_or_default)
    go, ho = _oracle_lambdarank(scores.astype(np.float64), labels, qb, gains,
                                cfg.sigmoid,
                                cfg.lambdarank_truncation_level, norm)
    np.testing.assert_allclose(np.asarray(g), go, rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h), ho, rtol=2e-4, atol=1e-6)


def _heads_dataset():
    """Query lengths 1 ... 700 in one dataset: shorter than, at and past
    every truncation level tried, one 700 long (width 1,024); every third
    query has one label only (no live pair, and where the label is 0 an
    ``inv_dcg`` of 0)."""
    rng = np.random.RandomState(7)
    group = np.array([1, 2, 3, 5, 6, 8, 9, 16, 19, 20, 21, 24, 31, 33, 40,
                      64, 65, 100, 130, 257, 700])
    labels = rng.randint(0, 5, int(group.sum()))
    qb = np.concatenate([[0], np.cumsum(group)])
    for qi in range(0, len(group), 3):
        labels[qb[qi]:qb[qi + 1]] = qi % 2 * 3          # all 0 or all 3
    return group, labels


def _heads_scores(kind, n):
    rng = np.random.RandomState(11)
    if kind == "equal":                 # tree 0: heads are the first T
        return np.zeros(n, np.float32)  # documents, in document order
    if kind == "tied":                  # five values: blocks of ties that
        return (rng.randint(0, 5, n) * 0.37 - 0.5).astype(np.float32)
    return rng.randn(n).astype(np.float32)      # straddle every place T


@pytest.mark.parametrize("norm", [True, False])
@pytest.mark.parametrize("trunc", [1, 5, 20, 4096])
@pytest.mark.parametrize("kind", ["random", "equal", "tied"])
def test_heads_match_square_reference(kind, trunc, norm):
    """The (Qc, T, W) gradients against the (Qc, Mb, Mb) formulation they
    replaced: every pair left out is one the square mask zeroes, so only
    the order of float32 additions differs."""
    import jax.numpy as jnp

    group, labels = _heads_dataset()
    scores = _heads_scores(kind, len(labels))
    obj, _ = _make_objective(labels, group, {
        "lambdarank_norm": norm, "lambdarank_truncation_level": trunc})
    g, h = obj.get_gradients(jnp.asarray(scores))
    g, h = np.asarray(g), np.asarray(h)
    gs, hs = _square_gradients(obj, scores, labels)
    assert np.isfinite(g).all() and np.isfinite(h).all()
    # a row's sum holds terms of both signs: the absolute floor stands
    # for the rounding of its largest term
    np.testing.assert_allclose(g, gs, rtol=2e-5, atol=2e-6 * np.abs(gs).max())
    np.testing.assert_allclose(h, hs, rtol=2e-5, atol=2e-6 * np.abs(hs).max())


def test_pair_element_gauges():
    """``lambdarank_pair_elements{built, square}`` and ``lambdarank_heads``
    from the shapes' arithmetic; ``built == square`` exactly when every
    window is at most as wide as the truncation level."""
    from lightgbmv1_tpu.obs.metrics import default_registry

    def read():
        pairs = default_registry().get("lambdarank_pair_elements")
        return (pairs.labels(what="built").get(),
                pairs.labels(what="square").get(),
                default_registry().get("lambdarank_heads").get())

    # starts 0 100 130 330 1030: windows of 1, 2, 2, 7 -> 8 and 1 rows
    group = np.array([100, 30, 200, 700, 3])
    labels = np.arange(int(group.sum())) % 3
    widths = [128, 256, 256, 1024, 128]
    square = sum(w * w for w in widths)
    _make_objective(labels, group)                      # T = 20
    assert read() == (20 * sum(widths), square, 20)
    _make_objective(labels, group, {"lambdarank_truncation_level": 5})
    assert read() == (5 * sum(widths), square, 5)
    # heads of the widest window only: the narrow ones are square already
    _make_objective(labels, group, {"lambdarank_truncation_level": 300})
    assert read() == (square - 1024 * 1024 + 300 * 1024, square, 300)
    # every window at most T wide: documents x documents again
    _make_objective(labels, group, {"lambdarank_truncation_level": 1024})
    assert read() == (square, square, 1024)
    _make_objective(labels[:100], group[:1])            # one window of one row
    assert read() == (20 * 128, 128 * 128, 20)
    # and straight from shapes, chunked or not
    _count_pair_elements([(3, 1536), (40, 1536), (7, 128)], 200)
    assert read() == (43 * 200 * 1536 + 7 * 128 * 128,
                      43 * 1536 ** 2 + 7 * 128 * 128, 200)


def test_window_layout_covers_all_queries():
    rng = np.random.RandomState(1)
    group = rng.randint(1, 700, size=400)
    qb = np.concatenate([[0], np.cumsum(group)])
    n = int(group.sum())
    seen = np.zeros(-(-n // 128) * 128, np.int32)
    qseen = np.zeros(len(group), np.int32)
    for rows, off, size, qids in _window_queries(qb, 20):
        w = rows.shape[1] * 128
        assert (np.diff(qids) > 0).all()         # a chunk's queries in row order
        assert (off + size <= w).all() and (off < 128).all()
        np.testing.assert_array_equal(size, group[qids])
        col = np.arange(w)
        mask = (col >= off[:, None]) & (col < (off + size)[:, None])
        flat = (rows[:, :, None] * 128 + np.arange(128)).reshape(len(qids), w)
        np.testing.assert_array_equal(flat[np.arange(len(qids)), off],
                                      qb[qids])  # a window starts its query
        np.add.at(seen, flat[mask], 1)
        qseen[qids] += 1
    assert (seen[:n] == 1).all() and (seen[n:] == 0).all()
    assert (qseen == 1).all()            # every row, every query exactly once


def test_window_rows_ladder():
    got = [_window_rows(t) for t in range(1, 18)]
    assert got == [1, 2, 3, 4, 6, 6, 8, 8, 12, 12, 12, 12, 16, 16, 16, 16, 24]


# tier-1 wall budget (tools/tier1_budget.py): slow-marked — still run by the full
# suite and driver captures
@pytest.mark.slow
def test_mslr_shaped_scale():
    """MSLR/Yahoo-regime query widths (up to ~1300 docs/query): the
    bucketed gradients must fit in memory — the old global-pad layout
    would need a (Q, 1300, 1300) pairwise tensor (~200 TB at the full 30k
    queries).  8k queries here keeps CI wall-clock sane; memory scales
    linearly in Q, the width axis is what the bucketing fixes."""
    rng = np.random.RandomState(2)
    Q = 1000     # memory scales linearly in Q (see docstring); 1k queries
                 # exercise the same width regime at an eighth the cost —
                 # the WIDTH mixture below is what the bucketing fixes
    u = rng.rand(Q)
    sizes = np.where(u < 0.85, rng.randint(8, 200, Q),
                     np.where(u < 0.97, rng.randint(200, 600, Q),
                              rng.randint(600, 1300, Q)))
    N = int(sizes.sum())
    labels = rng.randint(0, 5, N)
    scores = rng.randn(N).astype(np.float32)
    obj, _ = _make_objective(labels, sizes)
    import jax.numpy as jnp

    g, h = obj.get_gradients(jnp.asarray(scores))
    g, h = np.asarray(g), np.asarray(h)
    assert g.shape == (N,)
    assert np.isfinite(g).all() and np.isfinite(h).all()
    assert (h > 0).any()
    # winners (high label) should on average be pushed up (negative grad
    # means score increase in GBDT convention: new tree fits -grad)
    assert g[labels >= 3].mean() < g[labels == 0].mean()
