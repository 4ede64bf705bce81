"""500-iter AUC + wall under histogram precision variants (VERDICT r4 #6).

Done-bar: a variant within 0.0005 AUC of bf16x2 at 500 iters and >= 1.2x
its throughput.  Variants ride the depth-adaptive knob (hist_dtype_deep):
sustained (slot-bucket >= 32) rounds run the cheap dtype, ramp rounds and
the root pass keep bf16x2.  ``deep_int8sr`` additionally quantizes the
16-slot ramp bucket (the gate extension, models/grower_wave.py).

This experiment is the GATE for defaulting int8sr on: the mode ships
opt-in until a device capture of this script shows ``auc_parity`` true
(|AUC - bf16x2 AUC| <= 0.0005 at 500 iters) — the bar the round-5
rejection of plain int8 (-0.007 AUC) established.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import make_data  # noqa: E402

import jax  # noqa: E402

from lightgbmv1_tpu.config import Config  # noqa: E402
from lightgbmv1_tpu.io.dataset import BinnedDataset  # noqa: E402
from lightgbmv1_tpu.models.gbdt import create_boosting  # noqa: E402

N = int(os.environ.get("BENCH_ROWS", 1_000_000))
X, y = make_data(N, 0)
Xt, yt = make_data(100_000, 1)

base = {"objective": "binary", "num_leaves": 255, "max_bin": 63,
        "learning_rate": 0.1, "min_data_in_leaf": 20, "metric": "auc",
        "verbosity": -1, "tree_growth": "leafwise"}
cfg0 = Config.from_dict(base)
ds = BinnedDataset.from_numpy(X, label=y, config=cfg0)
dt = BinnedDataset.from_numpy(Xt, label=yt, config=cfg0, reference=ds)

VARIANTS = [
    ("bf16x2", {}),
    ("deep_bf16", {"hist_dtype_deep": "bf16"}),
    ("deep_int8", {"hist_dtype_deep": "int8"}),
    ("deep_int8sr", {"hist_dtype_deep": "int8sr"}),
    ("all_int8", {"hist_dtype": "int8"}),
]

AUC_PARITY_BAR = 0.0005     # |AUC - bf16x2| at 500 iters (VERDICT r5 #4)

auc_ref = None
for name, over in VARIANTS:
    cfg = Config.from_dict({**base, **over})
    gb = create_boosting(cfg, ds)
    gb.add_valid(dt, "test")
    gb.train_iters(100)
    jax.block_until_ready(gb._train_scores.score)
    t0 = time.time()
    for _ in range(4):
        gb.train_iters(100)
    jax.block_until_ready(gb._train_scores.score)
    wall500 = (time.time() - t0) * 500.0 / 400.0
    auc = None
    for (_, mname, value, _) in gb.eval_valid():
        if mname == "auc":
            auc = float(value)
    rec = {"variant": name, "wall500_s": round(wall500, 2),
           "auc500": round(auc, 6) if auc is not None else None}
    if name == "bf16x2":
        auc_ref = auc
    elif auc is not None and auc_ref is not None:
        delta = auc - auc_ref
        rec["auc_delta_vs_bf16x2"] = round(delta, 6)
        rec["auc_parity"] = bool(abs(delta) <= AUC_PARITY_BAR)
    print(json.dumps(rec), flush=True)
