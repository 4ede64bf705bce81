"""Multiclass parity-gap diagnostic A/B (VERDICT r5 #1, first step).

The recorded parity gap: at the multiclass bench config (250k rows x 28
features, 5 classes, 127 leaves, 50 iters) this framework holds mlogloss
0.851 vs the reference C++'s 0.830, while the small-scale (20-iter) gap is
0.005.  The round-5 record attributed it to "ulp-level split divergence
compounding over 250 trees" WITHOUT evidence — this tool puts a named
mechanism on record by A/B-ing the two levers that hypothesis implies,
each against the default run on identical data:

* ``wave1``  — ``leafwise_wave_size=1``: the exact sequential best-first
  split ORDER (the reference's schedule).  If the gap closes here, the
  wave schedule's round-commit batching is the mechanism, not ulp noise.
* ``dp_f32`` — ``gpu_use_dp=true``: f32 histograms everywhere (disables
  the depth-adaptive bf16 drop).  If the gap closes here, histogram
  precision is the mechanism.

For every variant the FIRST DIVERGENT TREE against the base run is
dumped: tree index, node index, and both sides' (feature, threshold bin,
gain) at the divergence — the concrete split where the trajectories part,
reproducible from the seeds alone (all data is generated, no files).

Run on the device session: ``python tools/mc_gap_ab.py``.  Environment
knobs: MC_AB_ROWS / MC_AB_ITERS (CPU smoke: MC_AB_ROWS=20000).
Prints one JSON line per variant.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import make_multiclass_data  # noqa: E402

import jax  # noqa: E402

from lightgbmv1_tpu.config import Config  # noqa: E402
from lightgbmv1_tpu.io.dataset import BinnedDataset  # noqa: E402
from lightgbmv1_tpu.models.gbdt import create_boosting  # noqa: E402

ON_CPU = jax.default_backend() == "cpu"
N = int(os.environ.get("MC_AB_ROWS", 20_000 if ON_CPU else 250_000))
NV = max(N // 5, 1000)
IT = int(os.environ.get("MC_AB_ITERS", 10 if ON_CPU else 50))
CLS = 5

BASE = {
    "objective": "multiclass", "num_class": CLS, "num_leaves": 127,
    "max_bin": 63, "learning_rate": 0.1, "min_data_in_leaf": 20,
    "metric": "multi_logloss", "verbosity": -1, "tree_growth": "leafwise",
}

# the levers of the recorded "ulp divergence" hypothesis, isolated
VARIANTS = [
    ("base", {}),
    ("wave1", {"leafwise_wave_size": 1}),
    ("dp_f32", {"gpu_use_dp": True}),
]


def train(over):
    cfg = Config.from_dict({**BASE, **over})
    ds = BinnedDataset.from_numpy(Xm, label=ym, config=cfg)
    dv = BinnedDataset.from_numpy(Xmv, label=ymv, config=cfg, reference=ds)
    gb = create_boosting(cfg, ds)
    gb.add_valid(dv, "test")
    t0 = time.time()
    gb.train_iters(IT)
    jax.block_until_ready(gb._train_scores.score)
    wall = time.time() - t0
    mll = None
    for (_, name, value, _) in gb.eval_valid():
        if name == "multi_logloss":
            mll = float(value)
    return gb.materialize_host_trees(), mll, wall


def first_divergence(trees_a, trees_b):
    """(tree_idx, node_idx, {a, b}) of the first structural difference, or
    None when every tree matches node-for-node."""
    for ti, (a, b) in enumererate_safe(trees_a, trees_b):
        na, nb = a.num_leaves - 1, b.num_leaves - 1
        for ni in range(max(na, nb)):
            da = _node(a, ni) if ni < na else None
            db = _node(b, ni) if ni < nb else None
            if da != db:
                return {"tree": ti, "node": ni, "a": da, "b": db}
    return None


def enumererate_safe(xs, ys):
    return enumerate(zip(xs, ys))


def _node(t, i):
    return {"feature": int(t.split_feature[i]),
            "threshold_bin": int(t.threshold_bin[i]),
            "gain": round(float(t.split_gain[i]), 6)}


Xm, ym = make_multiclass_data(N, 10, CLS)
Xmv, ymv = make_multiclass_data(NV, 11, CLS)

base_trees = None
base_mll = None
for name, over in VARIANTS:
    trees, mll, wall = train(over)
    rec = {"variant": name, "rows": N, "iters": IT,
           "mlogloss": round(mll, 6) if mll is not None else None,
           "wall_s": round(wall, 2)}
    if name == "base":
        base_trees, base_mll = trees, mll
    else:
        if mll is not None and base_mll is not None:
            rec["mlogloss_delta_vs_base"] = round(mll - base_mll, 6)
        div = first_divergence(base_trees, trees)
        rec["first_divergent_tree"] = div["tree"] if div else None
        rec["divergence"] = div
    print(json.dumps(rec), flush=True)
