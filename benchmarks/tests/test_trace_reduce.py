"""The trace reducer on a small recorded chip trace (one tree of a
2,000,000 x 28 run on one TPU v5 lite, recorded by PR 25)."""

import os

import pytest

import readers
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPES = ["lgbm.hist", "lgbm.split", "lgbm.partition"]


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.load(os.path.join(HERE, "data",
                                          "one_tree.trace.json.gz"))


def ctx_for(trace):
    return readers.Context(
        cell="t", chips=1, platform="tpu", device_kind="TPU v5 lite",
        rows=2_000_000, features=28, bins=64, host={}, tree_ms=[245.0],
        window_s=0.245, compile_stats={}, compiles_before={},
        compiles_after={}, tree_counts=[], peak_bytes=None, trace=trace,
        traced_trees=1, traced_window_s=0.2451299, scopes=SCOPES)


def test_busy_is_the_union_and_self_times_add_up(trace):
    (dev,) = trace.devices.values()
    assert len(dev.ops) == 4075
    assert trace.busy_s == pytest.approx(0.2361651, rel=1e-5)
    # nested while/conditional bodies are not counted twice
    assert sum(o.self_s for o in dev.ops) == pytest.approx(trace.busy_s,
                                                           rel=1e-6)
    assert sum(o.dur for o in dev.ops) > 2 * trace.busy_s


def test_readers_on_the_recorded_tree(trace):
    ctx = ctx_for(trace)
    hist = readers.device_ms_under_scope(ctx, "lgbm.hist")
    kern = readers.device_ms_of_op(ctx, "hist_leaves_pallas")
    part = readers.device_ms_under_scope(ctx, "lgbm.partition")
    other = readers.device_ms_outside_scopes(ctx, SCOPES)
    assert kern == pytest.approx(138.348, rel=1e-4)
    assert hist == pytest.approx(147.436, rel=1e-4) and hist > kern
    assert part == pytest.approx(20.712, rel=1e-4)
    split = readers.device_ms_under_scope(ctx, "lgbm.split")
    assert split == pytest.approx(57.700, rel=1e-4)      # vmap(lgbm.split)
    assert hist + split + part + other == pytest.approx(
        trace.busy_s * 1e3, rel=1e-3)
    assert readers.device_idle_share(ctx) == pytest.approx(3.657, abs=0.01)
    roof = readers.kernel_roofline(ctx, "hist_leaves_pallas")
    assert 5.0 < roof < 100.0
    assert ctx.notes["hist_leaves_pallas_bound_by"] == "operations"
    assert readers.collective_exposed_ms(ctx) is None      # one chip
    assert readers.device_ms_under_scope(ctx, "no.such.scope") is None


def test_breakdown_names_and_gaps(trace):
    ops = trace_reduce.top_device_ops(trace, SCOPES)
    assert ops[0][0] == "lgbm.hist/hist_leaves_pallas"
    assert len(ops) == 10 and all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    gaps = trace_reduce.top_idle_gaps(trace)
    assert gaps and sum(s for _, s in gaps) <= 0.2451299 - trace.busy_s + 1e-6


def test_union_and_exposed_collectives():
    assert trace_reduce.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    ev = lambda name, ts, dur, cat="": {
        "ph": "X", "pid": 1, "tid": 1, "ts": ts, "dur": dur, "name": name,
        "args": {"hlo_category": cat}}
    meta = [{"ph": "M", "pid": 1, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "pid": 1, "tid": 1, "name": "thread_name",
             "args": {"name": "XLA Ops"}}]
    t = trace_reduce.reduce_events(meta + [
        ev("fusion.1", 0, 10), ev("all-reduce.3", 10, 6, "all-reduce"),
        ev("fusion.2", 20, 5)])
    assert trace_reduce.exposed_collective_seconds(t) == pytest.approx(6e-6)
    assert t.busy_s == pytest.approx(21e-6)
