"""The six metrics PR 36 added, on a made-up device lane: a round's scope
goes round the scopes the pass already had, so one op is read under both;
``unnamed.`` is what no scope of the program holds."""

import json
import os

import pytest

import readers
import trace_reduce

from conftest import BENCH

MS = 1000.0            # trace events are in microseconds
ROUND = "jit(step)/jit(grown)/while/body/cond/branch_1_fun/lgbm.round.b16/"
META = [{"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 1, "tid": 1, "name": "thread_name",
         "args": {"name": "XLA Ops"}}]


def ctx_of(trace, trees):
    return readers.Context(
        cell="t", chips=1, platform="tpu", device_kind="TPU v5 lite",
        rows=1000, features=28, bins=64, host={}, tree_ms=[], window_s=0.0,
        compile_stats={}, compiles_before={}, compiles_after={},
        tree_counts=[], peak_bytes=None, trace=trace, traced_trees=trees,
        traced_window_s=0.03)


def lane():
    ev = lambda name, ts, dur, path, cat="": {
        "ph": "X", "pid": 1, "tid": 1, "ts": ts * MS, "dur": dur * MS,
        "name": name, "args": {"hlo_category": cat, "tf_op": path}}
    return trace_reduce.reduce_events(META + [
        ev("hist_leaves_pallas.3", 0, 6,
           ROUND + "lgbm.hist/jit(hist_leaves_pallas)/pallas_call:"),
        ev("pad_bitcast_fusion.1", 6, 2, ROUND
           + "lgbm.hist/jit(hist_leaves_pallas)/lgbm.layout/transpose:"),
        ev("partition_pallas.2", 8, 1, ROUND + "lgbm.partition/pallas_call:"),
        ev("fusion.7", 9, 3, "jit(step)/jit(grown)/lgbm.round.root/"
           "lgbm.hist/jit(hist_leaves_pallas)/pallas_call:"),
        ev("fusion.9", 12, 4, "jit(step)/jit(grown)/while/body/"
           "vmap(lgbm.split)/reduce_window_sum:"),
        ev("reduce-window.27", 16, 5, "jit(step)/jit(grown)/while:"),
        ev("copy.582", 21, 1, "")])


def metric(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as fh:
        doc = json.load(fh)
    return readers.READERS[doc["reader"]], doc.get("args", {})


def read(ctx, name):
    fn, args = metric(name)
    return fn(ctx, **args)


def test_a_round_and_the_pass_inside_it_both_count_the_op():
    ctx = ctx_of(lane(), trees=2)
    assert read(ctx, "round_b16.device_ms_per_tree") == pytest.approx(4.5)
    assert read(ctx, "round_root.device_ms_per_tree") == pytest.approx(1.5)
    assert read(ctx, "hist.device_ms_per_tree") == pytest.approx(5.5)
    assert read(ctx, "partition.device_ms_per_tree") == pytest.approx(0.5)
    assert read(ctx, "hist_layout.device_ms_per_tree") == pytest.approx(1.0)
    # a bucket the lane never ran reads nothing: the line leaves it out, as
    # a parent without the scopes leaves all four out
    assert read(ctx, "round_b4.device_ms_per_tree") is None
    assert read(ctx, "round_bK.device_ms_per_tree") is None
    # busy 22 ms; hist, split, partition and the rounds hold 16 of them
    assert read(ctx, "unscoped.device_ms_per_tree") == pytest.approx(3.0)
    assert read(ctx, "unnamed.device_ms_per_tree") == pytest.approx(3.0)


def test_unnamed_lists_every_scope_unscoped_does_and_the_later_ones():
    _, old = metric("unscoped.device_ms_per_tree")
    _, new = metric("unnamed.device_ms_per_tree")
    assert set(old["scopes"]) < set(new["scopes"])
    assert {"lgbm.renew", "lgbm.collective", "lgbm.round.bK",
            "lgbm.layout"} <= set(new["scopes"])
    # an op under a later scope alone is unscoped, and is not unnamed
    ev = {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 2 * MS,
          "name": "all-reduce.1",
          "args": {"hlo_category": "all-reduce",
                   "tf_op": "jit(step)/shard_map/lgbm.collective/psum:"}}
    ctx = ctx_of(trace_reduce.reduce_events(META + [ev]), trees=1)
    assert read(ctx, "unscoped.device_ms_per_tree") == pytest.approx(2.0)
    assert read(ctx, "unnamed.device_ms_per_tree") == pytest.approx(0.0)
