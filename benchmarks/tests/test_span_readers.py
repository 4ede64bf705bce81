"""The readers of ``span_readers.py``: on made-up records and lanes where
the answer can be worked out by hand, on a one-tree trace recorded on the
chip after PR 26 (``one_tree_spans.trace.json.gz``: 2,270,296 x 137, one
TPU v5 lite), and on PR 25's trace, which holds no program span and has to
read as nothing."""

import os

import pytest

import readers
import span_readers
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
PHASES = list(span_readers.PHASE_SPANS)
MS = 1_000_000


def ctx_for(trace=None, trees=1, window_s=0.0, **fields):
    ctx = readers.Context(
        cell="t", chips=1, platform="tpu", device_kind="TPU v5 lite",
        rows=2_270_296, features=137, bins=64, host={}, tree_ms=[],
        window_s=0.0, compile_stats={}, compiles_before={},
        compiles_after={}, tree_counts=[], peak_bytes=None, trace=trace,
        traced_trees=trees, traced_window_s=window_s)
    for k, v in fields.items():
        setattr(ctx, k, v)
    return ctx


def record(i, prepare, dispatch, bookkeep, wait, slack=0.01):
    total = prepare + dispatch + bookkeep + wait + slack
    return (i, i * 725 * MS, int(prepare * MS), int(dispatch * MS),
            int(bookkeep * MS), int(wait * MS), int(total * MS))


def test_the_readers_register_beside_the_old_ones():
    for name in ("iteration_phase_ms", "slowest_tree_excess_ms",
                 "idle_ms_under_host_span", "registry_value"):
        assert readers.READERS[name] is getattr(span_readers, name)
    assert "device_ms_under_scope" in readers.READERS


def test_phase_medians_and_the_stalled_trees_side():
    recs = [record(i, 1.0, 5.0, 3.0, 715.0) for i in range(9)]
    recs[4] = record(4, 1.0, 5.5, 3.0, 815.0)        # a stall in the wait
    ctx = ctx_for(iteration_records=recs)
    assert span_readers.iteration_phase_ms(ctx, ["dispatch"]) == 5.0
    assert span_readers.iteration_phase_ms(ctx, ["wait"]) == 715.0
    assert span_readers.iteration_phase_ms(
        ctx, ["prepare", "bookkeep"]) == 4.0
    assert span_readers.slowest_tree_excess_ms(ctx, ["wait"]) == \
        pytest.approx(100.0)
    assert span_readers.slowest_tree_excess_ms(
        ctx, ["total", "-wait"]) == pytest.approx(0.5)
    recs[4] = record(4, 1.0, 105.0, 3.0, 715.0)      # ... in the dispatch
    assert span_readers.slowest_tree_excess_ms(ctx, ["wait"]) == 0.0
    assert span_readers.slowest_tree_excess_ms(
        ctx, ["total", "-wait"]) == pytest.approx(100.0)


def test_nothing_to_read_is_none_not_an_error():
    bare = ctx_for()                  # a parent's run: no such fields
    assert span_readers.iteration_phase_ms(bare, ["wait"]) is None
    assert span_readers.slowest_tree_excess_ms(bare, ["wait"]) is None
    assert span_readers.registry_value(bare, "x") is None
    assert span_readers.idle_ms_under_host_span(bare, PHASES) is None
    assert span_readers.slowest_tree_excess_ms(
        ctx_for(iteration_records=[record(0, 1, 1, 1, 1)]), ["wait"]) is None
    reg = {'dataset_construct_seconds{phase="find_bins"}': 52.5}
    ctx = ctx_for(registry=reg)
    assert span_readers.registry_value(
        ctx, 'dataset_construct_seconds{phase="find_bins"}') == 52.5
    assert span_readers.registry_value(
        ctx, 'dataset_construct_seconds{phase="bundle"}') is None


def lanes(device, host):
    """A trace of one device line and one host line; times in us."""
    meta = [{"ph": "M", "pid": 1, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "pid": 1, "tid": 1, "name": "thread_name",
             "args": {"name": "XLA Ops"}},
            {"ph": "M", "pid": 2, "name": "process_name",
             "args": {"name": "/host:CPU"}},
            {"ph": "M", "pid": 2, "tid": 1, "name": "thread_name",
             "args": {"name": "python"}}]
    ev = lambda pid, name, ts, dur: {"ph": "X", "pid": pid, "tid": 1,
                                     "ts": ts, "dur": dur, "name": name}
    return trace_reduce.reduce_events(
        meta + [ev(1, n, t, d) for n, t, d in device]
        + [ev(2, n, t, d) for n, t, d in host])


def test_idle_goes_to_the_phase_the_host_was_in():
    # two trees of 1000 us; the device idles 0-30 and 1000-1040 (while the
    # host prepares 10 and dispatches 20 / 30) and 990-1000 (the harness's
    # sync and loop, inside no phase)
    t = lanes(
        device=[("fusion.1", 30, 960), ("fusion.2", 1040, 960)],
        host=[("bench.update", 0, 995), ("train.iteration", 0, 990),
              ("train.prepare", 0, 10), ("train.dispatch", 10, 20),
              ("train.bookkeep", 30, 5), ("train.wait", 35, 950),
              ("bench.update", 1000, 1000), ("train.iteration", 1000, 995),
              ("train.prepare", 1000, 10), ("train.dispatch", 1010, 30),
              ("train.bookkeep", 1040, 5), ("train.wait", 1045, 950)])
    ctx = ctx_for(t, trees=2, window_s=0.002)
    read = lambda spans, **kw: span_readers.idle_ms_under_host_span(
        ctx, spans, **kw)
    assert read(["train.dispatch"]) == pytest.approx((20 + 30) / 2e3)
    assert read(["train.prepare", "train.bookkeep", "train.wait"]) == \
        pytest.approx((10 + 10) / 2e3)
    assert read(PHASES, complement=True) == pytest.approx(10 / 2e3)
    total = sum(b - a for a, b in span_readers.idle_gaps_in_window(t))
    assert total == pytest.approx(80e-6)
    assert read(["train.dispatch"]) + read(
        ["train.prepare", "train.bookkeep", "train.wait"]) + read(
        PHASES, complement=True) == pytest.approx(total * 1e3 / 2)


def test_the_old_fixture_holds_no_span_and_reads_as_before():
    old = trace_reduce.load(os.path.join(HERE, "data",
                                         "one_tree.trace.json.gz"))
    ctx = ctx_for(old, trees=1, window_s=0.2451299)
    assert span_readers.idle_ms_under_host_span(ctx, PHASES) is None
    assert readers.device_ms_under_scope(ctx, "lgbm.objective") is None
    # the metrics PR 26 adds over the existing readers leave the old
    # readings where they were: every new scope is absent, so what lies
    # outside all seven scopes is what lay outside the three
    three = ["lgbm.hist", "lgbm.split", "lgbm.partition"]
    seven = three + ["lgbm.objective", "lgbm.sample", "lgbm.score",
                     "lgbm.select"]
    assert readers.device_ms_outside_scopes(ctx, seven) == \
        readers.device_ms_outside_scopes(ctx, three)


@pytest.fixture(scope="module")
def spans_trace():
    return trace_reduce.load(os.path.join(
        HERE, "data", "one_tree_spans.trace.json.gz"))


def test_the_recorded_tree_splits_other_and_idle(spans_trace):
    """One tree of ``mslr-train`` after PR 26 (the booster's 48th, 717 ms
    of device time): the four new scopes and what stays unscoped add up
    to what ``other`` read, and the three idle readers to the window's
    idle time."""
    (window,) = [(s, d) for n, s, d in spans_trace.host
                 if n == "bench.update"]
    ctx = ctx_for(spans_trace, trees=1, window_s=window[1])
    under = lambda scope: readers.device_ms_under_scope(ctx, scope)
    three = ["lgbm.hist", "lgbm.split", "lgbm.partition"]
    four = ["lgbm.objective", "lgbm.sample", "lgbm.score", "lgbm.select"]
    assert under("lgbm.objective") == pytest.approx(95.517, rel=1e-4)
    assert under("lgbm.score") == pytest.approx(1.9279, rel=1e-4)
    assert under("lgbm.select") == pytest.approx(1.5207, rel=1e-4)
    assert 0 < under("lgbm.sample") < 1e-3        # one 0.3 us pad
    other = readers.device_ms_outside_scopes(ctx, three)
    unscoped = readers.device_ms_outside_scopes(ctx, three + four)
    assert other == pytest.approx(136.372, rel=1e-4)
    assert unscoped == pytest.approx(37.406, rel=1e-4)
    assert sum(under(s) for s in four) + unscoped == pytest.approx(
        other, abs=1e-6)
    assert sum(under(s) for s in three) + other == pytest.approx(
        spans_trace.busy_s * 1e3, rel=1e-6)
    # the program's spans are in the host lane, on the device ops' clock
    spans = {n: d for n, s, d in spans_trace.host if n.startswith("train.")}
    assert set(spans) == set(PHASES) | {"train.iteration"}
    assert sum(spans[p] for p in PHASES) <= spans["train.iteration"]
    read = lambda names, **kw: span_readers.idle_ms_under_host_span(
        ctx, names, **kw)
    in_dispatch = read(["train.dispatch"])
    in_host = read(["train.prepare", "train.bookkeep", "train.wait"])
    outside = read(PHASES, complement=True)
    assert in_dispatch == pytest.approx(1.7750, rel=1e-3)
    assert in_host == pytest.approx(6.4392, rel=1e-3)
    assert outside == pytest.approx(0.3963, rel=1e-3)
    idle_ms = (window[1] - spans_trace.busy_s) * 1e3
    assert in_dispatch + in_host + outside == pytest.approx(idle_ms,
                                                            rel=1e-3)
    assert readers.device_idle_share(ctx) == pytest.approx(1.1870, rel=1e-3)
    # and the accepted readers still read a kernel and its roofline there
    assert readers.device_ms_of_op(ctx, "hist_leaves_pallas") == \
        pytest.approx(459.18, rel=1e-4)
    assert 5.0 < readers.kernel_roofline(ctx, "hist_leaves_pallas") < 100.0
