"""The harness end to end at a tiny size on the CPU: the rehearsal, the
refusal without it, and ``correct`` coming out false for every fault a cell
can have and for the lower-precision control."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

RUN = os.path.join(BENCH, "run.py")
FAULTS = os.path.join(BENCH, "tests", "faults.py")
ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
       "JAX_ENABLE_COMPILATION_CACHE": "false"}


CELLS = sorted(fn[:-5] for fn in os.listdir(os.path.join(BENCH, "workloads")))


def drive(cmd, seconds="8", trace="0", cell="mslr-train", seed="2147483777"):
    p = subprocess.run(
        [sys.executable, *cmd, "--workload", cell, "--seed", seed,
         "--seconds", seconds, "--trace", trace, "--rehearse-cpu"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=600)
    return p


def result_of(p):
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_refuses_without_a_tpu():
    p = subprocess.run(
        [sys.executable, RUN, "--workload", "mslr-train", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_end_to_end(cell):
    r = result_of(drive([RUN], cell=cell))
    assert list(r)[-1] == "compared"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["platform"] == "cpu"      # never a device number
    assert set(r["metrics"]) == {"train_row_trees_per_s", "heldout_quality",
                                 "setup_s"}
    assert 0.3 < r["metrics"]["heldout_quality"]["value"] <= 1.0
    for name, c in r["compared"].items():
        assert c["value"] <= c["limit"], name


def test_same_seed_same_quality_and_traced_line():
    a = result_of(drive([RUN], seconds="6"))
    b = result_of(drive([RUN], seconds="8", trace="1"))
    assert "heldout_quality" not in b["metrics"]
    assert {"bin.rows_per_s", "step.compile_s", "step.median_tree_ms",
            "train.compiles_in_window"} <= set(b["metrics"])
    # no share of a chip's peak and no device time from a CPU run
    assert not any("roofline" in k or "mfu" in k or "device" in k
                   for k in b["metrics"])
    assert b["metrics"]["train.compiles_in_window"]["value"] == 0
    c = result_of(drive([RUN], seconds="8"))
    assert (a["metrics"]["heldout_quality"]["value"]
            == c["metrics"]["heldout_quality"]["value"])


def faults_of(cell):
    """Every fault the cell can have: a four-chip cell also the exchange
    between chips left out."""
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as fh:
        chips = json.load(fh)["chips"]
    return ["stale", "half", "altered", "control"] + ["shard"] * (chips == 4)


@pytest.mark.parametrize("fault,cell", [(f, c) for c in CELLS
                                        for f in faults_of(c)])
def test_fault_comes_out_not_correct(fault, cell):
    r = result_of(drive([FAULTS, fault], cell=cell))
    assert r["correct"] is False, r["compared"]
    assert [k for k, c in r["compared"].items() if c["value"] > c["limit"]]


def test_comparison_has_to_reach_the_window():
    """A fault that starts with the window's first tree comes out not
    correct, and a cell whose checked trees all lie in the warm-up is
    refused."""
    r = result_of(drive([FAULTS, "altered-in-window"]))
    assert r["correct"] is False, r["compared"]
    p = drive([FAULTS, "warmup-only"])
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "checked_trees" in p.stderr
