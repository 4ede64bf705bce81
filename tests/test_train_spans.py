"""The boosting step and the binning seen from inside (PR 26).

Device side: every op of an iteration falls under an ``lgbm.*`` named
scope (HLO metadata only: a model trained with the scopes patched away is
the same model).  Host side: the phases of ``train_one_iter`` are real
spans that reach a ``jax.profiler`` trace started by anyone, with the
tracer disarmed, and one always-on record a tree says where its time
went.  Binning reports its phases to the registry.
"""

import contextlib
import glob
import gzip
import json
import os
import time

import jax
import numpy as np
import pytest

import lightgbmv1_tpu as lgb
from lightgbmv1_tpu.obs import metrics as obs_metrics
from lightgbmv1_tpu.obs import trace

from conftest import make_binary_problem

PHASES = ("train.prepare", "train.dispatch", "train.bookkeep", "train.wait")
PARAMS = {"objective": "binary", "num_leaves": 15, "max_bin": 31,
          "min_data_in_leaf": 5, "verbosity": -1}


@pytest.fixture(autouse=True)
def _tracer_clean():
    trace.reset()
    yield
    trace.reset()


def _booster(objective="binary", rows=1200, **over):
    params = dict(PARAMS, objective=objective, **over)
    X, y = make_binary_problem(rows, 6, seed=5)
    extra = {}
    if objective == "lambdarank":
        y = np.random.RandomState(5).randint(0, 4, rows).astype(float)
        extra["group"] = [40] * (rows // 40)
    ds = lgb.Dataset(X, label=y, params=dict(params), **extra).construct()
    return lgb.Booster(params=dict(params), train_set=ds)


# ---------------------------------------------------------------------------
# host: spans on the profiler's clock, the always-on record, the ring
# ---------------------------------------------------------------------------


def test_spans_reach_a_profiler_trace_with_the_tracer_disarmed(tmp_path):
    """Two ``update()`` calls under a ``jax.profiler`` session nobody told
    the program about: the four phases are in the profiler's host lane,
    twice each, inside the two ``train.iteration`` intervals, carrying
    the tree's ``iteration``."""
    b = _booster()
    b.update()                               # compile outside the capture
    assert not trace.enabled()
    jax.profiler.start_trace(str(tmp_path))
    try:
        b.update()
        b.update()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.trace.json.gz"),
                      recursive=True)
    with gzip.open(path, "rt") as fh:
        events = [e for e in json.load(fh)["traceEvents"]
                  if e.get("ph") == "X"
                  and str(e.get("name", "")).startswith("train.")]
    iters = sorted((e for e in events if e["name"] == "train.iteration"),
                   key=lambda e: e["ts"])
    assert [int(e["args"]["iteration"]) for e in iters] == [1, 2]
    for name in PHASES:
        mine = sorted((e for e in events if e["name"] == name),
                      key=lambda e: e["ts"])
        assert len(mine) == 2, (name, len(mine))
        for e, it in zip(mine, iters):
            assert int(e["args"]["iteration"]) == \
                int(it["args"]["iteration"])
            assert it["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= it["ts"] + it["dur"] + 1.0  # us
    assert trace.drain()["events"] == []     # nothing armed the ring


def test_one_record_a_tree_with_the_tracer_disarmed():
    b = _booster(rows=20000)
    b.update()
    trace.reset()
    for _ in range(5):
        b.update()
    recs = trace.iteration_records()
    assert [r[0] for r in recs] == [1, 2, 3, 4, 5]
    covered = []
    for (it, t0, prepare, dispatch, bookkeep, wait, total, renewed,
         rounds, hist_skipped) in recs:
        assert isinstance(renewed, int) and renewed >= 0
        # 15 leaves at K = 3: no ladder, one bucket, at least 5 rounds
        assert len(rounds) == 1 and rounds[0] >= 5
        assert hist_skipped in (0, 1)
        assert min(prepare, dispatch, bookkeep, wait) > 0
        assert prepare + dispatch + bookkeep + wait <= total
        covered.append((prepare + dispatch + bookkeep + wait) / total)
    # the phases are the iteration but for a few span exits: within 5%
    # (the median, so that one descheduled tree of a loaded test box
    # does not fail it)
    assert sorted(covered)[len(covered) // 2] >= 0.95, covered
    assert [r[1] for r in recs] == sorted(r[1] for r in recs)


@pytest.mark.parametrize("ladder", [True, False])
def test_the_record_counts_rounds_by_slot_bucket(ladder, monkeypatch):
    """Field 8, ``rounds``: what the device counted where it chose a
    round's bucket is what the finished trees replay to, bucket by bucket,
    on a three-bucket ladder and on the one bucket small data gets; it is
    read from the device when the record is first read, not when it is
    written, and reads the same again."""
    from lightgbmv1_tpu.models import grower_wave

    if ladder:
        monkeypatch.setattr(grower_wave, "_BUCKET_MIN_N", 256)
    b = _booster(rows=4000, num_leaves=127, min_data_in_leaf=2)
    K = grower_wave.auto_wave_size(127)
    buckets = grower_wave.slot_buckets_for(K, 4000)
    assert buckets == ([4, 16, K] if ladder else [K])
    for _ in range(3):
        b.update()
    # written as the span closed, still the device's: nothing waited
    assert all(callable(r[8]._v) for r in trace._iterations)
    recs = trace.iteration_records()
    assert all(isinstance(r[8]._v, tuple) for r in trace._iterations)
    live = [r[8] for r in recs]
    assert live == [r[8] for r in trace.iteration_records()]
    assert all(isinstance(n, int) for t in live for n in t)
    # ... and is what the finished trees' structure and gains replay to
    assert live == [
        grower_wave.rounds_by_bucket(s, buckets)
        for s in grower_wave.replay_wave_schedule(b._all_trees(), K)]
    if ladder:          # the ramp 1, 2, 4, ... passes through every bucket
        assert all(min(t) >= 1 for t in live), live


def test_a_grower_without_rounds_leaves_the_field_empty():
    """The level-wise grower has no wave rounds: ``rounds`` is None, as
    ``renewed`` is where nobody said; and the record's phase fields are
    where ``benchmarks/span_readers.py`` looks for them."""
    import ast
    import re

    b = _booster(tree_growth="levelwise")
    b.update()
    rec = trace.iteration_records()[-1]
    assert len(rec) == 10 and rec[8] is None and rec[9] is None
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "span_readers.py")
    with open(path) as fh:
        fields = ast.literal_eval(re.search(
            r"^_FIELDS = (\{.*\})$", fh.read(), re.M).group(1))
    assert fields == {"prepare": 2, "dispatch": 3, "bookkeep": 4,
                      "wait": 5, "total": 6}
    assert tuple(fields)[:4] == trace.ITERATION_PHASES
    assert rec[6] >= sum(rec[2:6]) > 0


def test_iteration_ring_is_bounded():
    for i in range(trace.ITERATION_RING + 150):
        with trace.iteration_span(i):
            pass
    recs = trace.iteration_records()
    assert len(recs) == trace.ITERATION_RING
    assert recs[0][0] == 150 and recs[-1][0] == trace.ITERATION_RING + 149


def test_armed_spans_nest_under_the_iteration_and_export():
    b = _booster()
    b.update()
    trace.arm(ring_events=4096)
    b.update()
    b.update()
    evs = [e for e in trace.export_chrome()["traceEvents"]
           if e.get("ph") == "X"]
    iters = [e for e in evs if e["name"] == "train.iteration"]
    assert [e["args"]["iteration"] for e in iters] == [1, 2]
    for it in iters:
        kids = [e for e in evs if e["name"] in PHASES
                and e["args"]["iteration"] == it["args"]["iteration"]]
        assert sorted(e["name"] for e in kids) == sorted(PHASES)
        for e in kids:
            assert it["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= it["ts"] + it["dur"] + 1e-3
            assert e["tid"] == it["tid"]
        assert sum(e["dur"] for e in kids) <= it["dur"]


def test_scanned_block_is_one_span_with_its_phases():
    b = _booster()
    trace.arm(ring_events=1024)
    b._gbdt.train_iters(3)
    evs = [e for e in trace.export_chrome()["traceEvents"]
           if e.get("ph") == "X"]
    block, = [e for e in evs if e["name"] == "train.iterations"]
    assert block["args"] == {"n": 3, "start_iter": 0}
    assert sorted(e["name"] for e in evs if e["name"] in PHASES) == \
        ["train.bookkeep", "train.dispatch", "train.prepare"]
    assert trace.iteration_records() == []   # a block is not a tree


def test_bridged_span_is_cheap_with_no_profiler_session():
    """Per-tree sites pay for a ``TraceAnnotation`` that nobody reads:
    a few microseconds; the ceiling keeps a later edit from making it
    dear unnoticed (a 724 ms tree opens five)."""
    n = 2000
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter_ns()
        for _ in range(n):
            with trace.phase_span("dispatch"):
                pass
        best = min(best, (time.perf_counter_ns() - t0) / n)
    assert best < 20_000, f"{best:.0f} ns a span"


# ---------------------------------------------------------------------------
# device: named scopes, and nothing but metadata
# ---------------------------------------------------------------------------


def _name_stacks(jaxpr, out, prefix=""):
    """Every equation's scope path; a sub-jaxpr's stacks are relative to
    the equation that holds it (a nested jit, a loop body, a branch), as
    the lowering joins them."""
    for eqn in jaxpr.eqns:
        here = "/".join(p for p in (prefix, str(eqn.source_info.name_stack))
                        if p)
        out.add(here)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _name_stacks(inner, out, here)
    return out


def _parts(stack):
    return stack.replace("(", "/").replace(")", "/").split("/")


STEP_SCOPES = ("lgbm.objective", "lgbm.sample", "lgbm.score")
GROWER_SCOPES = ("lgbm.select", "lgbm.hist", "lgbm.split",
                 "lgbm.partition")


@pytest.mark.parametrize("objective", ["binary", "lambdarank"])
@pytest.mark.parametrize("path", ["fused", "host_loop", "dart"])
def test_every_phase_of_an_iteration_is_a_named_scope(path, objective):
    """One iteration traced as a whole: the step's own phases and the
    grower's are all in the jaxpr's name stacks, on the fused step, on
    the host loop and on DART's step with trees dropped."""
    over = {}
    if path == "dart":
        over = dict(boosting="dart", drop_rate=1.0, skip_drop=0.0)
    b = _booster(objective, **over)
    b.update()
    g = b._gbdt
    if path == "host_loop":
        g._supports_fused_step = lambda: False
    jaxpr = jax.make_jaxpr(
        lambda: g.train_one_iter(check_stop=False))()
    stacks = [_parts(s) for s in _name_stacks(jaxpr.jaxpr, set())]
    for scope in STEP_SCOPES + GROWER_SCOPES:
        assert any(scope in s for s in stacks), (scope, path, objective)
    # the wave grower's passes carry their bucket around the scopes they
    # had: the root's, and the one bucket 1,200 rows get
    for outer, inner in (("lgbm.round.root", "lgbm.hist"),
                         ("lgbm.round.bK", "lgbm.hist"),
                         ("lgbm.round.bK", "lgbm.partition"),
                         ("lgbm.round.bK", "lgbm.select")):
        assert any(outer in s and inner in s for s in stacks), \
            (outer, inner, path, objective)
    assert not any("lgbm.round.b4" in s for s in stacks)


def test_a_ladder_names_every_bucket_and_the_pass_its_layout(monkeypatch):
    """On a three-bucket ladder with the kernel's passes (the raw matrix
    laid out in the pass, as a learner's over the bytes rule's budget or a
    streamed block is): every bucket's scope holds a histogram pass and a
    partition, and ``lgbm.layout`` sits inside ``lgbm.hist`` inside a
    round: one op's path keeps all three components."""
    from lightgbmv1_tpu.models import grower_wave
    from lightgbmv1_tpu.parallel import trainer

    monkeypatch.setattr(grower_wave, "_BUCKET_MIN_N", 256)
    # over the budget placement keeps the raw matrix: every pass lays it
    # out itself
    monkeypatch.setattr(trainer, "_hist_bins_budget", lambda: 0)
    b = _booster(rows=1500, num_leaves=127, min_data_in_leaf=2,
                 hist_method="pallas")
    b.update()
    g = b._gbdt
    jaxpr = jax.make_jaxpr(lambda: g.train_one_iter(check_stop=False))()
    stacks = [_parts(s) for s in _name_stacks(jaxpr.jaxpr, set())]
    rounds = ["lgbm.round.root", "lgbm.round.b4", "lgbm.round.b16",
              "lgbm.round.bK"]
    for outer in rounds:
        assert any(outer in s and "lgbm.hist" in s and "lgbm.layout" in s
                   for s in stacks), outer
    for outer in rounds[1:]:
        # (the largest bucket's result needs no padding: no lgbm.pool)
        for inner in ("lgbm.partition", "lgbm.select",
                      "lgbm.pool")[:2 if outer == rounds[-1] else 3]:
            assert any(outer in s and inner in s for s in stacks), \
                (outer, inner)
    assert not any("lgbm.round.b31" in s for s in stacks)
    # a layout op is never outside the histogram pass's scope
    assert all("lgbm.hist" in s for s in stacks if "lgbm.layout" in s)


def test_scopes_are_metadata_only(monkeypatch):
    """The same data and parameters with ``jax.named_scope`` patched to a
    no-op give the same model, to the last digit of its text."""
    def train():
        b = _booster("lambdarank")
        for _ in range(4):
            b.update()
        return b.model_to_string()

    scoped = train()
    calls = []

    def no_scope(name):
        calls.append(name)
        return contextlib.nullcontext()

    monkeypatch.setattr(jax, "named_scope", no_scope)
    bare = train()
    assert {"lgbm.objective", "lgbm.sample", "lgbm.score",
            "lgbm.select", "lgbm.round.root", "lgbm.round.bK"} <= set(calls)
    assert bare == scoped


# ---------------------------------------------------------------------------
# binning from inside
# ---------------------------------------------------------------------------


def test_dataset_construct_seconds_has_every_phase():
    reg = obs_metrics.default_registry()
    key = 'dataset_construct_seconds{phase="%s"}'
    phases = ("convert", "sample", "find_bins", "apply_bins", "bundle")
    X, y = make_binary_problem(40000, 12, seed=2)
    X = X.astype(np.float32)
    lgb.Dataset(X[:200], label=y[:200]).construct()   # first-use imports
    before = reg.snapshot()
    t0 = time.perf_counter()
    ds = lgb.Dataset(X, label=y, params={"max_bin": 63})
    ds.construct()
    wall = time.perf_counter() - t0
    after = reg.snapshot()
    spent = {p: after[key % p] - before.get(key % p, 0) for p in phases}
    assert all(v > 0 for v in spent.values()), spent
    assert sum(spent.values()) == pytest.approx(wall, rel=0.10)
    # the booster's placement of the bins is a phase of its own
    lgb.Booster(params=dict(PARAMS), train_set=ds)
    assert reg.snapshot()[key % "place"] > 0


# ---------------------------------------------------------------------------
# the histogram kernel's bin operand: laid out once, at placement
# ---------------------------------------------------------------------------


def _layout_counts():
    snap = obs_metrics.default_registry().snapshot()
    return {site: snap.get('hist_bins_layout_total{site="%s"}' % site, 0)
            for site in ("placement", "pass")}


def test_serial_booster_lays_bins_out_once_at_placement():
    from lightgbmv1_tpu.ops.hist_pallas import HistBins, prepared_bins_bytes

    reg = obs_metrics.default_registry()
    key = 'dataset_construct_seconds{phase="layout"}'
    before, spent = _layout_counts(), reg.snapshot().get(key, 0)
    bst = _booster(rows=1234, hist_method="pallas")
    bst.update()
    after = _layout_counts()
    assert after["placement"] - before["placement"] == 1
    assert after["pass"] - before["pass"] == 0
    snap = reg.snapshot()
    assert snap[key] > spent
    grow_binned = bst._gbdt._grow_binned
    assert isinstance(grow_binned, HistBins)
    assert bst._gbdt.binned.shape == grow_binned.matrix.shape == (6, 1234)
    # 6 byte columns of 2048 padded rows, lane-padded to 128
    assert snap["hist_bins_prepared_bytes"] == prepared_bins_bytes(
        6, 1234, 32) == 2048 * 128


def test_streaming_booster_lays_bins_out_in_the_pass(monkeypatch):
    """Row blocks arrive raw, so each traced pass makes its own layout."""
    import functools

    import lightgbmv1_tpu.ops.hist_pallas as hp

    # the streaming grower has no interpreter switch: bind it here
    monkeypatch.setattr(hp, "hist_leaves_pallas", functools.partial(
        hp.hist_leaves_pallas, interpret=True))
    before = _layout_counts()
    bst = _booster(rows=1144, hist_method="pallas", num_leaves=7,
                   stream_enable=True, stream_block_rows=104)
    bst.update()
    after = _layout_counts()
    assert after["pass"] > before["pass"]
    assert after["placement"] == before["placement"]
    assert bst._gbdt._grow_binned is None
