"""``criteo-tall-train`` rehearsed on the CPU (67 columns at 63 bins, a
thousandth of the rows), and the faults ``half``, ``stale`` and
``altered`` planted under it: which of the compared numbers catches each.

``test_rehearse.py`` walks every cell's file, this one among them, for
``correct`` alone (the int8 control too); here the cell's own keys are
held: the four-chip cell's data, objective, leaves and bins on the serial
learner, one machine's rows of a job spread over 64, its ``quality_trees``,
the scopes in the order that names the slow lane's ops, and the traced
line.
"""

import json
import os

import pytest

from conftest import BENCH
from test_rehearse import FAULTS, RUN, drive, result_of

CELL = "criteo-tall-train"
SEED = "2147498001"


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


def test_cell_file_and_configuration():
    cell = _json("workloads", CELL + ".json")
    traffic = _json("traffic", cell["traffic"] + ".json")
    config = _json("configs", cell["config"] + ".json")
    sibling = _json("configs", "criteo-dp4-67f.json")
    # a window of ~3 s trees holds fewer than the mix's 16
    assert cell["quality_trees"] == 8 < traffic["quality_trees"]
    assert cell["rehearse_quality_trees"] == 4
    assert cell["chips"] == 1 and cell["traffic"] == "train-from-scratch"
    # ``trace_reduce.scope_of`` names an op by the FIRST listed scope on
    # its path: the layout and the leaf-sum pass lie inside lgbm.hist
    assert cell["scopes"][:3] == ["lgbm.layout", "lgbm.renew", "lgbm.hist"]
    assert set(traffic["scopes"]) | {"lgbm.pool"} <= set(cell["scopes"])
    data, params = config["data"], config["params"]
    # one machine's rows of the source's job spread over 64: 1.7 B / 64
    assert data["rows"] == 26_562_500 == \
        config["published"]["num_data"] // 64
    assert {**data, "rows": 0} == {**sibling["data"], "rows": 0}
    assert params == {**{k: v for k, v in sibling["params"].items()
                         if k != "num_shards"}, "tree_learner": "serial"}
    assert params["max_bin"] == 63 and params["num_leaves"] == 255
    assert config["published"] == sibling["published"]
    assert config["reduced"] == ["num_data", "num_iterations",
                                 "num_machines", "tree_learner"]
    assert config["architecture"] is None
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
