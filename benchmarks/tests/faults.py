"""Plant a fault under the harness and drive the rest of a run.

    python3 benchmarks/tests/faults.py <fault> --workload <cell> --seed N \
        --seconds S --trace 0 --rehearse-cpu

The harness's look for a chip is skipped (``--rehearse-cpu``); everything
after it is the real run.  Faults, each planted in the program underneath
``Booster.update()`` / ``Dataset`` so that the timed path itself is broken:

  none      nothing planted (the harness's own run)
  stale     a step that returns its state unchanged: the training scores are
            put back after every tree, so each tree is grown on the first
            tree's gradients
  half      half of the batch left out: the program's dataset holds only the
            first half of the rows (the mean is taken over the rest)
  shard     the exchange between chips left out: the model is what one of
            four shards grows alone - the first quarter of the rows, serial
  altered   an answer altered where it is produced: one leaf value of every
            tree scaled by 3 as the step stores it
  altered-in-window  the same, from the window's first tree on: the warm-up
            trees are sound
  control   the program's next-lower precision (``control_params`` of the
            traffic file, over a Pallas histogram so that it binds on a CPU)
  warmup-only  no fault of the program: the cell is read as if it warmed up
            past its checked trees, which the harness has to refuse
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH]


def plant(fault: str, cell: dict) -> None:
    import lightgbmv1_tpu.basic as basic
    from lightgbmv1_tpu.models import gbdt as gbdt_mod

    if fault in ("none", "warmup-only"):
        return
    if fault in ("half", "shard"):
        frac = 0.5 if fault == "half" else 0.25
        real_init = basic.Dataset.__init__

        def cut_init(self, data, label=None, group=None, params=None, **kw):
            import numpy as np
            if group is not None:
                q = max(int(len(group) * frac), 1)
                group = np.asarray(group)[:q]
                n = int(group.sum())
            else:
                n = int(data.shape[0] * frac)
            if fault == "shard" and params:
                params = {k: v for k, v in params.items()
                          if k not in ("tree_learner", "num_shards")}
            real_init(self, data[:n], label=label[:n], group=group,
                      params=params, **kw)

        basic.Dataset.__init__ = cut_init
        if fault == "shard":
            real_booster = basic.Booster.__init__

            def serial_booster(self, params=None, **kw):
                params = {k: v for k, v in (params or {}).items()
                          if k not in ("tree_learner", "num_shards")}
                real_booster(self, params=params, **kw)

            basic.Booster.__init__ = serial_booster
        return
    real_iter = gbdt_mod.GBDT._fused_train_one_iter
    if fault == "stale":
        def stale(self):
            import jax.numpy as jnp
            keep = jnp.array(self._train_scores.score, copy=True)
            real_iter(self)
            self._train_scores.score = keep
        gbdt_mod.GBDT._fused_train_one_iter = stale
    elif fault in ("altered", "altered-in-window"):
        sound = int(cell["warmup_trees"]) if fault != "altered" else 0

        def altered(self):
            real_iter(self)
            if len(self._device_trees) <= sound:
                return
            t = self._device_trees[-1]
            self._device_trees[-1] = t._replace(
                leaf_value=t.leaf_value.at[3].multiply(3.0))
        gbdt_mod.GBDT._fused_train_one_iter = altered
    elif fault == "control":
        real_booster = basic.Booster.__init__

        def low_precision(self, params=None, **kw):
            params = {**(params or {}), **cell["control_params"],
                      "hist_method": "pallas"}
            real_booster(self, params=params, **kw)

        basic.Booster.__init__ = low_precision
    else:
        raise SystemExit(f"unknown fault {fault!r}")


def main() -> int:
    fault, argv = sys.argv[1], sys.argv[2:]
    import run as harness

    name = argv[argv.index("--workload") + 1]
    cell, _ = harness.load_cell(name)
    if "--rehearse-cpu" not in argv:
        raise SystemExit("faults are planted in rehearsals only")
    harness.rehearse_on_cpu(int(cell["chips"]))
    plant(fault, cell)
    # a planted run is read for ``correct`` alone: quality at the checked
    # trees, so that a slow interpreted kernel need not reach quality_trees
    load_cell = harness.load_cell

    def short_cell(name):
        c, config = load_cell(name)
        if fault == "warmup-only":
            c = {**c, "warmup_trees": int(c["checked_trees"])}
        return {**c, "rehearse_quality_trees": int(c["checked_trees"])}, config

    harness.load_cell = short_cell
    return harness.main(argv)


if __name__ == "__main__":
    sys.exit(main())
