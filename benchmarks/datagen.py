"""Synthetic data at a configuration's published shape, from ``--seed``.

One general generator, driven by the ``data`` block of a configuration
file (``benchmarks/configs/<name>.json``).  The formulas are the sound ones
of ``bench.make_data`` / ``bench.make_rank_data`` (a logit of a few
informative columns among noise columns; relevance graded by within-query
score quantiles), copied here so that no later PR can change the yardstick,
with the width and a heavy-tailed query-length draw as parameters.

Layout: features are made column-major, ``XT`` of shape (features, rows),
float32, one random stream per column so that columns fill in parallel.
The program is handed ``XT.T`` (its binning reads one column at a time);
the plain reference routes rows through ``XT`` directly.

Every seed gives the same sizes in the same places: the query lengths, and
the position of each relevance grade within each query, are drawn from the
configuration's own ``length_seed``; ``--seed`` draws the features and the
noise, and the documents of a query (which are exchangeable) are then put
in the order that gives the query its fixed sequence of grades.  So every
run compiles and runs the same shapes, and a program that bakes a dataset's
query boundaries and labels into its compiled step (this one does) finds
that step in the persistent cache on a new seed.  For the same reason the
columns are not centred on zero: the program, like its reference, gives
the values below zero ``int(share * (max_bin - 1))`` bins, bakes that count
into the step, and a share of one half puts it on the edge between 30 and
31 at 63 bins, a coin flipped by every seed in every column.  A
``location`` of +-0.1 puts the share at 0.46 or 0.54.
"""

from __future__ import annotations

import itertools
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Optional

import numpy as np

_THREADS = 6


@dataclass
class Split:
    XT: np.ndarray                    # (features, rows) float32
    y: np.ndarray                     # (rows,) float64
    group: Optional[np.ndarray]       # (queries,) int64 lengths, or None

    @property
    def rows(self) -> int:
        return int(self.XT.shape[1])

    @property
    def X(self) -> np.ndarray:
        """(rows, features) view, column-major, no copy."""
        return self.XT.T


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def _normal_columns(seed: int, stream: int, features: int, rows: int
                    ) -> np.ndarray:
    XT = np.empty((features, rows), np.float32)

    def fill(f: int) -> None:
        _rng(seed, stream, f).standard_normal(rows, dtype=np.float32,
                                              out=XT[f])

    with ThreadPoolExecutor(max_workers=_THREADS,
                            thread_name_prefix="datagen") as pool:
        list(pool.map(fill, range(features)))
    return XT


def _permute_columns(XT: np.ndarray, take: np.ndarray) -> None:
    """``XT[:, i] = XT[:, take[i]]`` in place, a column of the matrix (a
    row of ``XT``) at a time."""
    def one(f: int) -> None:
        XT[f] = XT[f][take]

    with ThreadPoolExecutor(max_workers=_THREADS,
                            thread_name_prefix="datagen") as pool:
        list(pool.map(one, range(XT.shape[0])))


def _signal(XT: np.ndarray, spec: dict) -> np.ndarray:
    """The noiseless score of each row: linear, product and sine terms of
    the informative columns named in ``spec`` (all others are noise)."""
    s = np.zeros(XT.shape[1], np.float32)
    for f, w in spec.get("linear", []):
        s += np.float32(w) * XT[f]
    for f, g, w in spec.get("products", []):
        s += np.float32(w) * XT[f] * XT[g]
    for f, freq, w in spec.get("sines", []):
        s += np.float32(w) * np.sin(np.float32(freq) * XT[f])
    return s


def query_lengths(spec: dict, total_rows: Optional[int], queries:
                  Optional[int], stream: int) -> np.ndarray:
    """A heavy-tailed multiset of query lengths (log-normal, clipped), the
    same for every ``--seed``.  Either ``total_rows`` (lengths are drawn
    until they cover it; the last is trimmed) or ``queries`` is given."""
    rng = _rng(spec["length_seed"], stream)
    sigma = float(spec["length_sigma"])
    mu = np.log(float(spec["length_mean"])) - 0.5 * sigma * sigma
    lo, hi = int(spec["length_min"]), int(spec["length_max"])

    def draw(n: int) -> np.ndarray:
        return np.clip(np.rint(rng.lognormal(mu, sigma, n)), lo, hi
                       ).astype(np.int64)

    if queries is not None:
        return draw(int(queries))
    est = int(total_rows / float(spec["length_mean"]) * 1.5) + 16
    lens = draw(est)
    while lens.sum() < total_rows:
        lens = np.concatenate([lens, draw(est)])
    k = int(np.searchsorted(np.cumsum(lens), total_rows)) + 1
    lens = lens[:k].copy()
    lens[-1] -= lens.sum() - total_rows
    return lens[lens > 0]


def _query_start_of_row(group: np.ndarray) -> np.ndarray:
    return np.repeat(np.concatenate([[0], np.cumsum(group)[:-1]]), group)


def _order_within_query(key: np.ndarray, group: np.ndarray):
    """(order, rank): ``order`` lists the rows query by query, each query's
    by ascending ``key``; ``rank[i]`` is row i's place in its own query."""
    qid = np.repeat(np.arange(len(group)), group)
    order = np.lexsort((key, qid))
    rank = np.empty(len(key), np.int64)
    rank[order] = np.arange(len(key)) - _query_start_of_row(group)
    return order, rank


def _grades_in_place(spec: dict, group: np.ndarray, stream: int):
    """(y, rank): the relevance of every position, 0..len(cuts), and the
    within-query score rank the document put there has to have.  A
    position's rank is drawn from ``length_seed`` (the same for every
    ``--seed``) and its grade is the quantile of that rank in its query."""
    n = int(group.sum())
    _, rank = _order_within_query(
        _rng(spec["length_seed"], stream, 10_002).random(n), group)
    denom = np.maximum(np.repeat(group, group) - 1, 1)
    y = np.digitize(rank / denom, spec["grade_cuts"]).astype(np.float64)
    return y, rank


def make_split(spec: dict, seed: int, which: str, rows: Optional[int] = None,
               queries: Optional[int] = None) -> Split:
    """``which`` is ``train`` or ``heldout``; they use disjoint streams."""
    stream = {"train": 1, "heldout": 2}[which]
    features = int(spec["features"])
    group = None
    if spec["kind"] == "rank":
        lens = query_lengths(spec, rows if queries is None else None, queries,
                             stream)
        group = lens
        rows = int(group.sum())
    XT = _normal_columns(seed, stream, features, int(rows))
    noise = _rng(seed, stream, 10_000).standard_normal(
        int(rows), dtype=np.float32) * np.float32(spec["noise_sd"])
    score = _signal(XT, spec) + noise
    if spec["kind"] == "binary":
        y = (score > np.float32(spec.get("bias", 0.0))).astype(np.float64)
    elif spec["kind"] == "rank":
        # the document with the k-th lowest score of its query goes where
        # the fixed pattern wants rank k
        y, rank = _grades_in_place(spec, group, stream)
        by_score, _ = _order_within_query(score, group)
        take = by_score[_query_start_of_row(group) + rank]
        _permute_columns(XT, take)
    else:
        raise ValueError(f"unknown data kind {spec['kind']!r}")
    # labels are drawn from the centred columns; then each column moves to
    # its own location (``location``, cycled over the columns)
    for f, loc in zip(range(features), itertools.cycle(
            spec.get("location", [0.0]))):
        if loc:
            XT[f] += np.float32(loc)
    return Split(XT, y, group)


def make_data(spec: dict, seed: int, scale: float = 1.0):
    """Training and held-out splits of a configuration.  ``scale`` < 1 is
    the CPU rehearsal's cut (never used on the chip)."""
    def cut(n):
        return None if n is None else max(int(n * scale), 64)

    if spec["kind"] == "rank":
        train = make_split(spec, seed, "train", rows=cut(spec["rows"]))
        held = make_split(spec, seed, "heldout",
                          queries=cut(spec["heldout_queries"]))
    else:
        train = make_split(spec, seed, "train", rows=cut(spec["rows"]))
        held = make_split(spec, seed, "heldout",
                          rows=cut(spec["heldout_rows"]))
    return train, held
