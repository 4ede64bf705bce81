"""The bin finder without a walk over the distinct values (PR 32), held
boundary for boundary to the walk it replaced (``parent_oracles``: the
port of the reference's ``GreedyFindBin`` as a loop in the interpreter),
and the columns of ``Dataset.construct()`` found and applied in threads.
"""

import numpy as np
import pytest

import parent_oracles
from lightgbmv1_tpu.config import Config
from lightgbmv1_tpu.io import binning
from lightgbmv1_tpu.io.binning import BIN_CATEGORICAL, BinMapper
from lightgbmv1_tpu.io.dataset import BinnedDataset
from lightgbmv1_tpu.obs.metrics import default_registry


def column(kind, seed, n=20_000):
    rng = np.random.RandomState(seed)
    if kind == "continuous":
        return rng.randn(n) + 0.1
    if kind == "float32":
        return (rng.randn(n) - 0.1).astype(np.float32)
    if kind == "heavy_ties":           # a few values hold most of the rows
        v = np.round(rng.standard_exponential(n) * 3.0, 1)
        v[rng.rand(n) < 0.3] = 2.5
        v[rng.rand(n) < 0.1] = -1.0
        return v
    if kind == "zeros":                # sparse: mostly zero, both signs
        v = rng.randn(n)
        v[rng.rand(n) < 0.7] = 0.0
        return v
    if kind == "positive_sparse":
        v = np.abs(rng.randn(n))
        v[rng.rand(n) < 0.5] = 0.0
        return v
    if kind == "nan":
        v = rng.randn(n) * 10
        v[rng.rand(n) < 0.2] = np.nan
        return v
    if kind == "few_distinct":         # fewer distinct values than bins
        return rng.randint(-7, 9, n).astype(np.float64) * 0.5
    if kind == "big_and_small":        # big counts among many small ones
        v = rng.randn(n)
        v[: n // 4] = np.round(v[: n // 4], 0)
        return v
    if kind == "one_ulp":              # neighbours within one ulp merge
        v = rng.randint(1, 400, n).astype(np.float64)
        return np.where(rng.rand(n) < 0.5, v, np.nextafter(v, np.inf))
    raise ValueError(kind)


KINDS = ("continuous", "float32", "heavy_ties", "zeros", "positive_sparse",
         "nan", "few_distinct", "big_and_small", "one_ulp")
SETTINGS = (
    dict(max_bin=63, min_data_in_bin=3),
    dict(max_bin=255, min_data_in_bin=3),
    dict(max_bin=63, min_data_in_bin=40),
    dict(max_bin=16, min_data_in_bin=1, zero_as_missing=True),
    dict(max_bin=63, min_data_in_bin=3, use_missing=False),
    dict(max_bin=5, min_data_in_bin=3),
)


def find(sample, total, **kw):
    return BinMapper.find_bin(sample, total_sample_cnt=total, **kw)


@pytest.mark.parametrize("setting", range(len(SETTINGS)))
@pytest.mark.parametrize("kind", KINDS)
def test_boundaries_equal_the_walk_s(kind, setting, monkeypatch):
    kw = SETTINGS[setting]
    for seed in range(3):
        v = column(kind, 100 * setting + seed)
        # the sparse contract: rows left out of the sample are zeros
        sample = v[v != 0.0] if kind.endswith("sparse") or kind == "zeros" \
            else v
        new = find(sample, len(v), **kw)
        with monkeypatch.context() as m:
            m.setattr(binning, "_greedy_find_bin",
                      parent_oracles._greedy_find_bin)
            m.setattr(binning, "_distinct_with_zero",
                      parent_oracles._distinct_with_zero)
            old = find(sample, len(v), **kw)
        assert new.bin_upper_bound.tobytes() == old.bin_upper_bound.tobytes()
        assert (new.num_bin, new.missing_type, new.is_trivial) == (
            old.num_bin, old.missing_type, old.is_trivial)
        assert (new.min_value, new.max_value, new.sparse_rate) == (
            old.min_value, old.max_value, old.sparse_rate)
        np.testing.assert_array_equal(new.value_to_bin(v),
                                      old.value_to_bin(v))


@pytest.mark.parametrize("kind", KINDS)
def test_distinct_values_equal_the_walk_s(kind):
    for zero_cnt in (0, 17):
        v = column(kind, 5)
        v = np.sort(v[~np.isnan(v) & (v != 0.0)], kind="stable")
        for part in (v, v[v > 0], v[v < 0], v[:0]):
            d_new, c_new = binning._distinct_with_zero(part, zero_cnt)
            d_old, c_old = parent_oracles._distinct_with_zero(part, zero_cnt)
            assert d_new.tobytes() == d_old.tobytes()
            np.testing.assert_array_equal(c_new, c_old)


def test_greedy_walk_on_adversarial_counts():
    """Random counts with zeros, big values side by side and budgets that
    run out: the bisection closes the bins the walk closes."""
    rng = np.random.RandomState(0)
    for trial in range(300):
        nd = rng.randint(2, 400)
        distinct = np.cumsum(rng.rand(nd) + 1e-3) - rng.rand() * nd * 0.5
        counts = rng.randint(0, 6, nd) * rng.choice([1, 1, 1, 40], nd)
        counts[rng.rand(nd) < 0.05] += rng.randint(50, 400)
        total = int(counts.sum()) + rng.choice([0, 0, 25])
        max_bin = int(rng.choice([1, 2, 3, 8, 63, 255]))
        mdb = int(rng.choice([0, 1, 3, 30]))
        if max_bin == 1 and nd > 1:
            continue        # the walk indexes past its one bin there
        args = (distinct, counts.astype(np.int64), max_bin, total, mdb)
        assert binning._greedy_find_bin(*args) == \
            parent_oracles._greedy_find_bin(*args), trial


def test_columns_in_threads_give_the_serial_dataset(monkeypatch):
    """Columns long enough for the thread pool: mappers and bins are those of a
    one-column-at-a-time build, and ``find_bin_columns_total`` counts the
    columns by the path that found them."""
    rng = np.random.RandomState(3)
    X = rng.randn(6_000, 40).astype(np.float32)
    X[:, 3] = rng.randint(0, 9, 6_000)
    X[rng.rand(6_000) < 0.1, 5] = np.nan
    cfg = Config.from_dict(dict(max_bin=63, verbosity=-1))
    counter = lambda path: default_registry().counter(
        "find_bin_columns_total", label_names=("path",)).labels(
            path=path).get()
    before = counter("array"), counter("loop")
    monkeypatch.setattr("lightgbmv1_tpu.io.dataset._POOL_MIN_ROWS", 1)
    pooled = BinnedDataset.from_numpy(X, config=cfg, categorical_features=[3])
    assert (counter("array") - before[0], counter("loop") - before[1]) \
        == (39, 1)
    monkeypatch.setattr("lightgbmv1_tpu.io.dataset._POOL_MIN_ROWS", 1 << 60)
    serial = BinnedDataset.from_numpy(X, config=cfg, categorical_features=[3])
    np.testing.assert_array_equal(pooled.binned, serial.binned)
    for a, b in zip(pooled.bin_mappers, serial.bin_mappers):
        assert a.bin_upper_bound.tobytes() == b.bin_upper_bound.tobytes()
        assert (a.num_bin, a.missing_type, a.bin_type) == (
            b.num_bin, b.missing_type, b.bin_type)
    assert pooled.bin_mappers[3].bin_type == BIN_CATEGORICAL
