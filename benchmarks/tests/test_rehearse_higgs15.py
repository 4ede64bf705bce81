"""``higgs-15b-train`` rehearsed on the CPU (28 columns at 15 bins, packed
two to a byte, a thousandth of the rows), and the faults ``half``,
``stale`` and ``altered`` planted under it: which of the compared numbers
catches each.

``test_rehearse.py`` walks every cell's file, this one among them, for
``correct`` alone (the int8 control too); here the cell's own keys are
held: ``higgs-255b-train``'s data, objective, leaves and learner at the
library's 4-bit bin count with the layout left to the program, the scopes
in the order that names the slow lane's ops, and the traced line.
"""

import json
import os

import pytest

from conftest import BENCH
from test_rehearse import FAULTS, RUN, drive, result_of

CELL = "higgs-15b-train"
SEED = "2147494041"


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


def test_cell_file_and_configuration():
    cell = _json("workloads", CELL + ".json")
    traffic = _json("traffic", cell["traffic"] + ".json")
    config = _json("configs", cell["config"] + ".json")
    sibling = _json("configs", "higgs-28f-255b.json")
    # a window of ~1 s trees could hold the mix's 16; the sibling's 8
    # keep the two cells' held-out models alike
    assert cell["quality_trees"] == 8 < traffic["quality_trees"]
    assert cell["rehearse_quality_trees"] == 4
    assert cell["chips"] == 1 and cell["traffic"] == "train-from-scratch"
    # ``trace_reduce.scope_of`` names an op by the FIRST listed scope on
    # its path: the layout and the leaf-sum pass lie inside lgbm.hist
    assert cell["scopes"][:3] == ["lgbm.layout", "lgbm.renew", "lgbm.hist"]
    assert set(traffic["scopes"]) | {"lgbm.pool"} <= set(cell["scopes"])
    # the same rows at the same seed as higgs-255b-train: the pair isolates
    # the bin format and the rung
    assert config["data"] == sibling["data"]
    params = config["params"]
    assert params == {**sibling["params"], "max_bin": 15}
    # nothing forces a layout: bin_layout=auto packs at max_bin <= 15
    assert "bin_layout" not in params and "hist_method" not in params
    assert config["published"]["max_bin"] == 15
    assert config["reduced"] == ["num_iterations"]
    assert config["precision"] == sibling["precision"]
    assert len(config["source"]) <= 200


def test_traced_rehearsal_reads_the_cell_s_metrics():
    r = result_of(drive([RUN], cell=CELL, seed=SEED, trace="1"))
    assert r["correct"] is True and r["failed"] == 0
    assert r["device"]["platform"] == "cpu"
    assert {"bin.rows_per_s", "step.compile_s", "step.median_tree_ms",
            "train.compiles_in_window"} <= set(r["metrics"])
    assert r["metrics"]["train.compiles_in_window"]["value"] == 0
    # no device time, share of a peak or scope reading from a CPU run: the
    # new metric names its kernel and stays out of a CPU line
    assert not any("roofline" in k or "mfu" in k or "device" in k
                   for k in r["metrics"])


def test_partition_metric_names_the_kernel():
    metric = _json("layer_metrics", "partition_pallas.device_ms_per_tree.json")
    assert metric["reader"] == "device_ms_of_op"
    assert metric["args"] == {"op": "partition_pallas"}
    assert metric["layer"] == "partition"
    assert metric["workloads"] == [CELL, "higgs-255b-train"]
    from lightgbmv1_tpu.ops.partition_pallas import KERNEL_NAME
    assert KERNEL_NAME == metric["args"]["op"]


@pytest.mark.parametrize("fault,caught_by", [
    ("half", "count_mismatch"),
    ("stale", "update_norm_gap"),
    ("altered", "update_norm_gap"),
])
def test_fault_is_caught_by(fault, caught_by):
    r = result_of(drive([FAULTS, fault], cell=CELL, seed=SEED))
    assert r["correct"] is False, r["compared"]
    over = [k for k, c in r["compared"].items() if c["value"] > c["limit"]]
    assert caught_by in over, r["compared"]
