"""A whole small booster at 2,000 columns (the width of the benchmark's
``epsilon-2000f-63b``) through ``Dataset`` / ``Booster.update()`` on the
CPU, judged by the benchmark's own plain reference: it follows the dumped
trees from the raw float32 data (``benchmarks/reference.py``, which
imports nothing of the program) and the five numbers of ``correct`` stay
under the cell's limits."""

import json
import os
import sys

import numpy as np
import pytest

import lightgbmv1_tpu as lgb

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, BENCH)
    try:
        import datagen
        import reference
        yield datagen, reference
    finally:
        sys.path.remove(BENCH)


def test_wide_booster_passes_the_reference(bench):
    datagen, reference = bench
    with open(os.path.join(BENCH, "configs", "epsilon-2000f-63b.json")) as fh:
        config = json.load(fh)
    with open(os.path.join(BENCH, "workloads", "epsilon-train.json")) as fh:
        limits = json.load(fh)["limits"]
    assert config["data"]["features"] == 2_000
    train, _ = datagen.make_data(config["data"], 2147483999, 0.015)
    assert train.X.shape == (6_000, 2_000)
    # 15 leaves: a few hundred rows a leaf, and with a hessian of at most
    # a quarter a row the source's min_sum_hessian_in_leaf=100 still binds
    params = {**config["params"], "num_leaves": 15}
    booster = lgb.Booster(
        params=dict(params),
        train_set=lgb.Dataset(train.X, label=train.y,
                              params=dict(params)).construct())
    for _ in range(3):
        booster.update()
    trees = [reference.parse_tree(t)
             for t in booster.dump_model()["tree_info"]]
    assert all(8 <= t.num_leaves <= 15 for t in trees)
    # the winners come from all over the 2,000 columns' informative ones
    informative = {f for f, *_ in config["data"]["linear"]
                   + config["data"]["sines"]}
    used = {int(f) for t in trees for f in t.feature}
    assert len(used & informative) >= 3 and max(used) >= 32
    numbers = reference.judge(trees, train, params)
    correct, rows = reference.verdict(numbers, limits)
    assert correct, rows
    assert numbers["count_mismatch"] == 0
