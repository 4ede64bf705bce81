"""Read the numbers ``correct`` compares, for the program, its control and
its planted faults, on several seeds in one process (one set-up of the
chip, one compile cache).  What the limits in ``workloads/<cell>.json`` are
set from; PERF.md section 2 holds the readings.

    python3 benchmarks/tools/calibrate.py --workload <cell> \
        --seeds 11,12,13 --variants program,control,half,quarter \
        [--serial] [--set min_sum_hessian_in_leaf=100] [--rehearse-cpu] \
        [--out chiprun_out/calibrate_<cell>.jsonl]

``--serial`` drops ``tree_learner`` / ``num_shards`` from every variant, so
that a four-chip cell's faults can be read on one chip (a planted fault is
a faulty program either way; ``program`` and ``control`` of such a cell are
read on four).

variants
  program   the cell as configured
  control   the program's own next-lower precision: ``control_params`` of
            the cell's traffic file over the configuration's (int8
            histograms)
  half      fault: half of the batch left out - the program trained on the
            first half of the rows, judged against all of them
  quarter   fault: the exchange between chips left out - what one of four
            shards grows alone: the program trained on the first quarter of
            the rows (serial learner), judged against all of them
  stale     fault: a step that returns its state unchanged - tree 2 is
            tree 1 again (needs no chip: the dumped tree is copied)
  altered   fault: one leaf value altered where it is produced (x3)

Every line printed is one JSON object: cell, seed, variant, the numbers
compared, ``correct`` and the numbers ``over`` their limit by
``reference.verdict`` under the cell's own limits (the program has to come
out correct, the control and every fault not), and, under ``diagnostics``,
what is looked at and not compared: the worst single leaf and split, the
norm of the difference of the two updates, and whether the stored threshold
is as good as any on its feature.
"""

from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _best_gain_on_feature(x, g, h, l2, min_rows, min_hess) -> float:
    """Best split gain any threshold on this feature gives for these rows
    (exact scan over the sorted values, float64)."""
    import numpy as np

    order = np.argsort(x, kind="stable")
    xs = x[order]
    G, H = np.cumsum(g[order]), np.cumsum(h[order])
    n = len(xs)
    cnt = np.arange(1, n + 1)
    ok = np.concatenate([xs[1:] != xs[:-1], [False]])
    ok &= (cnt >= min_rows) & (n - cnt >= min_rows)
    ok &= (H >= min_hess) & (H[-1] - H >= min_hess)
    if not ok.any():
        return 0.0
    gl, hl = G[ok], H[ok]
    gain = (gl * gl / (hl + l2) + (G[-1] - gl) ** 2 / (H[-1] - hl + l2)
            - G[-1] ** 2 / (H[-1] + l2))
    return float(gain.max())


def diagnose(s, train, params) -> dict:
    """One step's diagnostics (a ``reference.Step``): none is compared."""
    import numpy as np

    import reference

    if s.leaf is None:
        return {"tree": s.k, "leaves": s.tree.num_leaves}
    gaps = reference._rel_each(s.stored, s.value)
    w = int(np.argmax(gaps))
    out = {
        "tree": s.k, "leaves": s.tree.num_leaves,
        "update_rms_gap": float(
            np.sqrt(np.sum(s.cnt * (s.stored - s.value) ** 2))
            / np.sqrt(np.sum(s.cnt * s.value ** 2))),
        "leaf_median_gap": float(np.median(gaps)),
        "leaf_value_gap": float(gaps[w]),
        "split_gain_gap": float(
            reference._rel_each(s.tree.gain, s.gain).max()),
        "worst_leaf": {"rows": int(s.cnt[w]), "stored": float(s.stored[w]),
                       "reference": float(s.value[w]), "H": float(s.H[w]),
                       "stored_H": float(s.tree.leaf_weight[w])},
    }
    if s.k == 0:
        # is the root's stored threshold as good as any on its feature?
        best = _best_gain_on_feature(
            train.XT[s.tree.feature[0]], s.g, s.h,
            float(params.get("lambda_l2", 0.0)),
            int(params.get("min_data_in_leaf", 20)),
            float(params.get("min_sum_hessian_in_leaf", 1e-3)))
        if best > 0:
            out["root_threshold_loss"] = (best - float(s.gain[0])) / best
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="program,control")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--serial", action="store_true")
    ap.add_argument("--set", default="", metavar="KEY=JSON,...",
                    help="training parameters over the configuration's, "
                    "to try before a configuration file is written")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    import run as harness

    cell, config = harness.load_cell(args.workload)
    chips = 1 if args.serial else int(cell["chips"])
    if args.rehearse_cpu:
        harness.rehearse_on_cpu(chips)
    import jax
    import numpy as np

    import lightgbmv1_tpu as lgb

    import datagen
    import reference

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearse_cpu:
        print("calibrate: no TPU (use --rehearse-cpu)", file=sys.stderr)
        return 3
    scale = float(cell.get("rehearse_scale", 0.01)) if args.rehearse_cpu \
        else 1.0
    k = int(cell["checked_trees"])
    out = open(args.out, "a") if args.out else None

    def emit(**rec):
        line = json.dumps({"cell": args.workload, "platform": platform,
                           **rec}, default=float)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    def bin_it(params, split):
        return lgb.Dataset(split.X, label=split.y, group=split.group,
                           params=dict(params)).construct()

    def grow(params, dataset):
        b = lgb.Booster(params=dict(params), train_set=dataset)
        for _ in range(k):
            b.update()
        dump = b.dump_model(num_iteration=k)
        del b
        gc.collect()
        return [reference.parse_tree(t) for t in dump["tree_info"]]

    def subset(split, frac):
        if split.group is None:
            n = int(split.rows * frac)
            return datagen.Split(np.ascontiguousarray(split.XT[:, :n]),
                                 split.y[:n], None)
        q = max(int(len(split.group) * frac), 1)
        n = int(split.group[:q].sum())
        return datagen.Split(np.ascontiguousarray(split.XT[:, :n]),
                             split.y[:n], split.group[:q])

    configured = {**config["params"],
                  **{key: json.loads(v) for key, v in (
                      pair.split("=", 1) for pair in args.set.split(",")
                      if pair)}}
    serial = {key: v for key, v in configured.items()
              if key not in ("tree_learner", "num_shards")}
    base = serial if args.serial else configured
    for seed in [int(s) for s in args.seeds.split(",")]:
        train, _ = datagen.make_data(config["data"], seed, scale)
        program_trees = None
        full = None                    # one binning for program and control
        for variant in args.variants.split(","):
            t = time.perf_counter()
            if variant in ("program", "control", "stale", "altered") \
                    and full is None:
                full = bin_it(base, train)
            if variant == "program":
                program_trees = trees = grow(base, full)
            elif variant == "control":
                trees = grow({**base, **cell["control_params"]}, full)
            elif variant == "half":
                trees = grow(base, bin_it(base, subset(train, 0.5)))
            elif variant == "quarter":
                trees = grow(serial, bin_it(serial, subset(train, 0.25)))
            elif variant in ("stale", "altered"):
                if program_trees is None:
                    program_trees = grow(base, full)
                trees = copy.deepcopy(program_trees)
                if variant == "stale":
                    trees[1] = copy.deepcopy(trees[0])
                    # the copy carries tree 0's folded-in initial score
                    trees[1].leaf_value = (
                        trees[1].leaf_value
                        - reference.OBJECTIVES[base["objective"]](
                            train, base).init_score)
                else:
                    trees[0].leaf_value[3] = (
                        trees[0].leaf_value[3] * 3.0)
            else:
                raise SystemExit(f"unknown variant {variant}")
            grow_s = time.perf_counter() - t
            t = time.perf_counter()
            looks = []
            numbers = reference.judge(
                trees, train, base,
                look=lambda s: looks.append(diagnose(s, train, base)))
            correct, rows = reference.verdict(numbers, cell["limits"])
            emit(seed=seed, variant=variant, grow_s=grow_s,
                 judge_s=time.perf_counter() - t, **numbers,
                 correct=correct,
                 over=[name for name, v, lim in rows if v > lim],
                 diagnostics=looks)
        del full
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
