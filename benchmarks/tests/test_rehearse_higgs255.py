"""``higgs-255b-train`` rehearsed on the CPU (28 columns at 255 bins, a
thousandth of the rows), and the faults ``half``, ``stale`` and
``altered`` and the int8 control planted under it: which of the compared
numbers catches each.

``test_rehearse.py`` walks every cell's file, this one among them, for
``correct`` alone; here the cell's own keys are held: the library's
default bin count, the source's leaf minima, its ``quality_trees``, the
``lgbm.pool`` scope, and the traced line.
"""

import json
import os

import pytest

from conftest import BENCH
from test_rehearse import FAULTS, RUN, drive, result_of

CELL = "higgs-255b-train"
SEED = "2147494001"


def test_cell_file_overrides_the_traffic_mix():
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as fh:
        cell = json.load(fh)
    with open(os.path.join(BENCH, "traffic",
                           cell["traffic"] + ".json")) as fh:
        traffic = json.load(fh)
    with open(os.path.join(BENCH, "configs", cell["config"] + ".json")) as fh:
        config = json.load(fh)
    # a window of ~2 s trees holds fewer than the mix's 16
    assert cell["quality_trees"] == 8 < traffic["quality_trees"]
    assert "lgbm.pool" in cell["scopes"]
    assert set(traffic["scopes"]) <= set(cell["scopes"])
    assert cell["chips"] == 1
    data, params = config["data"], config["params"]
    assert (data["rows"], data["features"], data["heldout_rows"]) == (
        10_500_000, 28, 500_000)
    # the library's default bin count: the kernel's 256-bin rung
    assert params["max_bin"] == 255 and params["tree_learner"] == "serial"
    # the source's own leaf minima, not the library's
    assert (params["min_data_in_leaf"],
            params["min_sum_hessian_in_leaf"]) == (1, 100)
    assert config["reduced"] == ["num_iterations"]
    assert len(config["source"]) <= 200


def test_traced_rehearsal_reads_the_cell_s_metrics():
    r = result_of(drive([RUN], cell=CELL, seed=SEED, trace="1"))
    assert r["correct"] is True and r["failed"] == 0
    assert r["device"]["platform"] == "cpu"
    assert {"bin.rows_per_s", "step.compile_s", "step.median_tree_ms",
            "train.compiles_in_window"} <= set(r["metrics"])
    assert r["metrics"]["train.compiles_in_window"]["value"] == 0
    # no device time, share of a peak or scope reading from a CPU run
    assert not any("roofline" in k or "mfu" in k or "device" in k
                   for k in r["metrics"])


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
