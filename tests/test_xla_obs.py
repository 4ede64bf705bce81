"""Device-truth observability (ISSUE 12): obs/xla.py compile telemetry,
the profiler lane + phase reconciliation in obs/agg.py, the roofline
math, and the tools/capture.py harness."""

import gzip
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from lightgbmv1_tpu.obs import agg as obs_agg  # noqa: E402
from lightgbmv1_tpu.obs import trace as obs_trace  # noqa: E402
from lightgbmv1_tpu.obs import xla as obs_xla  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_stats():
    obs_xla.reset_compile_stats()
    yield
    obs_xla.reset_compile_stats()


# ---------------------------------------------------------------------------
# instrument_jit: counting, caching, parity, nesting
# ---------------------------------------------------------------------------


def test_instrument_jit_counts_compiles_and_caches():
    import jax.numpy as jnp

    def f(a, b):
        return (a * b).sum(axis=0) + 1.0

    wrapped = obs_xla.instrument_jit(f, "t.count")
    a = jnp.arange(12.0).reshape(3, 4)
    b = jnp.ones((3, 4))
    out1 = wrapped(a, b)
    out2 = wrapped(a * 2, b)          # same signature: cached executable
    st = obs_xla.compile_stats()["t.count"]
    assert st["compiles"] == 1 and st["retraces"] == 0
    assert st["compile_ms_total"] > 0
    assert st["fallbacks"] == 0
    # new signature compiles again (a new shape is NOT a retrace)
    wrapped(jnp.ones((5, 4)), jnp.ones((5, 4)))
    st = obs_xla.compile_stats()["t.count"]
    assert st["compiles"] == 2 and st["retraces"] == 0
    # bit-parity with the plain jit path
    import jax

    ref = jax.jit(f)(a, b)
    assert np.array_equal(np.asarray(out1), np.asarray(ref))
    assert np.array_equal(np.asarray(out2),
                          np.asarray(jax.jit(f)(a * 2, b)))
    # always-on metrics carry the labeled counters
    from lightgbmv1_tpu.obs.metrics import default_registry

    snap = default_registry().snapshot()
    assert snap.get('xla_compile_total{label="t.count"}', 0) >= 2


def test_instrument_jit_failures_propagate():
    """A compile or dispatch failure propagates — the wrapper never
    re-runs the call through another path (a retry would re-use
    arguments a failed donated dispatch may already have consumed), and
    nothing is counted as a fallback."""
    import jax.numpy as jnp

    def bad_trace(a):
        raise NotImplementedError("cannot lower this kernel")

    wrapped = obs_xla.instrument_jit(bad_trace, "t.fail")
    for _ in range(2):                      # not remembered as "broken"
        with pytest.raises(NotImplementedError, match="cannot lower"):
            wrapped(jnp.ones(3))
    assert wrapped.cache_info()["broken"] == 0

    ok = obs_xla.instrument_jit(lambda a: a + 1, "t.dispatch")
    ok(jnp.ones(3))
    (sig, compiled), = ok._compiled.items()

    class Boom:
        def __call__(self, *a, **k):
            raise RuntimeError("dispatch failed on device")

    ok._compiled[sig] = Boom()
    with pytest.raises(RuntimeError, match="dispatch failed"):
        ok(jnp.ones(3))
    stats = obs_xla.compile_stats()
    assert stats["t.dispatch"]["fallbacks"] == 0
    assert "t.fail" not in stats or stats["t.fail"]["fallbacks"] == 0


def test_instrument_jit_retrace_is_same_signature_recompile():
    import jax.numpy as jnp

    def f(a):
        return a + 1

    a = jnp.ones(7)
    obs_xla.instrument_jit(f, "t.retrace")(a)
    # a NEW wrapper under the same label recompiling the same signature
    # is the retrace event (the LRU-eviction / rebuild storm detector)
    obs_xla.instrument_jit(f, "t.retrace")(a)
    st = obs_xla.compile_stats()["t.retrace"]
    assert st["compiles"] == 2 and st["retraces"] == 1


def test_instrument_jit_cost_and_memory_present_or_none_on_cpu():
    """The contract is present-or-None: backends without cost/memory
    analysis yield None fields, never an exception.  XLA:CPU implements
    both, so this pins the populated path too."""
    import jax.numpy as jnp

    def f(a, b):
        return a @ b

    obs_xla.instrument_jit(f, "t.cost")(jnp.ones((16, 16)),
                                        jnp.ones((16, 16)))
    st = obs_xla.compile_stats()["t.cost"]
    for key in ("flops", "bytes_accessed", "temp_bytes",
                "argument_bytes", "output_bytes",
                "generated_code_bytes"):
        assert key in st
        assert st[key] is None or st[key] >= 0
    # a 16x16x16 matmul reports real flops on CPU
    assert st["flops"] and st["flops"] >= 2 * 16 ** 3


def test_instrument_jit_nested_inside_outer_jit_passes_through():
    import jax
    import jax.numpy as jnp

    inner = obs_xla.instrument_jit(lambda a: a * 2, "t.inner")

    @jax.jit
    def outer(a):
        return inner(a) + 1

    out = outer(jnp.ones(4))
    assert np.array_equal(np.asarray(out), np.full(4, 3.0))
    # tracer args bypass the AOT bookkeeping: the inner label never
    # records a compile of its own (it inlines into the outer program)
    assert "t.inner" not in obs_xla.compile_stats()


def test_instrument_jit_kwargs_and_capability_flags():
    import jax.numpy as jnp

    def f(a, scale=None):
        return a.sum() if scale is None else (a * scale).sum()

    f._supports_valids = True       # the jax.jit __dict__-copy contract
    wrapped = obs_xla.instrument_jit(f, "t.kwargs")
    assert wrapped._supports_valids is True
    a = jnp.ones(6)
    assert float(wrapped(a, scale=jnp.asarray(2.0))) == 12.0
    assert float(wrapped(a, scale=jnp.asarray(3.0))) == 18.0
    st = obs_xla.compile_stats()["t.kwargs"]
    assert st["compiles"] == 1      # same signature, kwarg value is data


def test_instrument_jit_disabled_falls_back_to_plain_jit():
    import jax.numpy as jnp

    obs_xla.set_enabled(False)
    try:
        wrapped = obs_xla.instrument_jit(lambda a: a - 1, "t.disabled")
        out = wrapped(jnp.ones(3))
        assert np.array_equal(np.asarray(out), np.zeros(3))
        assert "t.disabled" not in obs_xla.compile_stats()
    finally:
        obs_xla.set_enabled(True)


def test_instrument_jit_rejects_static_args():
    with pytest.raises(ValueError):
        obs_xla.instrument_jit(lambda a: a, "t.static",
                               static_argnums=(0,))


# ---------------------------------------------------------------------------
# BatchPredictor compile counters (the serving zero-retrace contract)
# ---------------------------------------------------------------------------


def _tiny_predictor(cache_entries=64):
    import lightgbmv1_tpu as lgb
    from lightgbmv1_tpu.models.predict import BatchPredictor

    rng = np.random.RandomState(3)
    X = rng.randn(600, 5)
    y = (X[:, 0] - X[:, 1] > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 7, "max_bin": 15,
              "verbosity": -1, "min_data_in_leaf": 5}
    ds = lgb.Dataset(X, label=y, params=dict(params))
    bst = lgb.train(dict(params), ds, num_boost_round=2,
                    verbose_eval=False)
    trees = bst._gbdt.materialize_host_trees()
    return BatchPredictor(trees, 1, 5, bucket_min=32,
                          cache_entries=cache_entries), X


def test_predictor_bucket_path_zero_retrace_via_counters():
    """Varying batch sizes inside one power-of-two bucket must not move
    the per-label compile counters — the compile-amortization contract,
    asserted through obs/xla.py instead of the ad-hoc trace counter."""
    bp, X = _tiny_predictor()
    bp.predict_raw(X[:60])                 # warm the 64-row bucket
    before = obs_xla.compile_counts()
    for n in (60, 50, 40, 33):
        bp.predict_raw(X[:n])
    after = obs_xla.compile_counts()
    for label in ("predict.leaf", "predict.scores"):
        assert after.get(label, 0) == before.get(label, 0), label
    assert sum(obs_xla.retrace_counts().values()) == 0


def test_predictor_lru_eviction_recompile_counted_once():
    """Evicting a (bucket, kind) executable and re-touching the bucket
    recompiles a signature the label has already seen: exactly one
    retrace per evicted kind, visible in the label counters."""
    bp, X = _tiny_predictor(cache_entries=2)
    bp.predict_raw(X[:40])                 # bucket 64 (leaf + scores)
    assert obs_xla.retrace_counts().get("predict.leaf", 0) == 0
    bp.predict_raw(X[:100])                # bucket 128 — evicts bucket 64
    bp.predict_raw(X[:300])                # bucket 512 — evicts more
    assert sum(obs_xla.retrace_counts().values()) == 0
    before = obs_xla.compile_stats()
    bp.predict_raw(X[:40])                 # re-touch the evicted bucket
    st = obs_xla.compile_stats()
    for label in ("predict.leaf", "predict.scores"):
        assert st[label]["retraces"] == \
            before[label]["retraces"] + 1, label


def test_publish_warm_records_compile_bill():
    """A registry publish's warm phase carries its compile bill in the
    version meta (warm_compile_ms / warm_compiles) — priced by the same
    obs/xla.py counters as everything else."""
    import lightgbmv1_tpu as lgb
    from lightgbmv1_tpu.serve import ServeConfig, Server

    rng = np.random.RandomState(1)
    X = rng.randn(500, 4)
    y = (X[:, 0] > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 7, "max_bin": 15,
              "verbosity": -1, "min_data_in_leaf": 5}
    ds = lgb.Dataset(X, label=y, params=dict(params))
    bst = lgb.train(dict(params), ds, num_boost_round=2,
                    verbose_eval=False)
    server = Server(config=ServeConfig(
        max_batch_rows=64, predictor_kwargs={"bucket_min": 32}))
    try:
        server.publish(bst)
        mv = server.registry.current()
        assert mv.meta["warm_compiles"] >= 1
        assert mv.meta["warm_compile_ms"] > 0
        assert mv.meta["n_warm"] >= 1
    finally:
        server.close()


# ---------------------------------------------------------------------------
# roofline math (tools/phase_attrib.py) — pinned on a constructed table
# ---------------------------------------------------------------------------


def test_roofline_attribution_pinned():
    from phase_attrib import roofline_attribution

    phase_ms = {"hist": 50.0, "split": 10.0, "other": 5.0}
    cost = {
        "hist": {"flops": 1.0e12, "bytes": 4.0e9},     # 20 TF/s, 80 GB/s
        "split": {"flops": 1.0e9, "bytes": 8.0e9},     # 0.1 TF/s, 800 GB/s
        # "other" has no cost row -> omitted
    }
    rows = roofline_attribution(phase_ms, cost,
                                peak_flops_per_s=40.0e12,
                                peak_bytes_per_s=800.0e9)
    assert set(rows) == {"hist", "split"}
    h = rows["hist"]
    assert h["achieved_tf_s"] == 20.0
    assert h["frac_of_peak_flops"] == 0.5
    assert h["achieved_gb_s"] == 80.0
    assert h["frac_of_peak_bw"] == 0.1
    assert h["frac_of_peak"] == 0.5 and h["bound"] == "compute"
    s = rows["split"]
    assert s["frac_of_peak_bw"] == 1.0
    assert s["frac_of_peak"] == 1.0 and s["bound"] == "memory"
    # flops-only peak: bandwidth columns absent, never zero-filled
    rows = roofline_attribution(phase_ms, cost, peak_flops_per_s=40.0e12)
    assert "frac_of_peak_bw" not in rows["hist"]
    assert rows["hist"]["frac_of_peak"] == 0.5


def test_split_cost_by_ms_proportional():
    from phase_attrib import split_cost_by_ms

    table = split_cost_by_ms(100.0, 50.0, {"a": 75.0, "b": 25.0})
    assert table["a"]["flops"] == 75.0 and table["b"]["flops"] == 25.0
    assert table["a"]["bytes"] == 37.5 and table["b"]["bytes"] == 12.5
    assert split_cost_by_ms(None, None, {"a": 1.0}) == {}
    assert split_cost_by_ms(100.0, None, {}) == {}


# ---------------------------------------------------------------------------
# device memory: graceful absence + ledger reconciliation
# ---------------------------------------------------------------------------


def test_device_memory_graceful_on_cpu():
    # XLA:CPU exposes no allocator stats: absence is a value, not a crash
    assert obs_xla.device_memory_stats() is None
    assert obs_xla.sample_device_memory() is None


def test_ledger_agreement_math():
    assert obs_xla.ledger_agreement(None, 100) is None
    assert obs_xla.ledger_agreement(100, None) is None
    assert obs_xla.ledger_agreement(0, 100) is None
    assert obs_xla.ledger_agreement(90, 100) == 0.9
    assert obs_xla.ledger_agreement(150, 100) == 1.5


# ---------------------------------------------------------------------------
# profiler lane: anchor sidecar, merge, estimated-span reconciliation
# ---------------------------------------------------------------------------


def _write_device_capture(prof_dir, t0_unix_ns, events):
    """A synthetic jax.profiler-shaped capture: gzipped Chrome trace
    under plugins/profile/<run>/ plus the obs/xla.py anchor sidecar."""
    run_dir = os.path.join(prof_dir, "plugins", "profile", "run1")
    os.makedirs(run_dir)
    doc = {"displayTimeUnit": "ns", "traceEvents": events}
    with gzip.open(os.path.join(run_dir, "host.trace.json.gz"),
                   "wt") as fh:
        json.dump(doc, fh)
    with open(os.path.join(prof_dir, obs_xla.ANCHOR_FILE), "w") as fh:
        json.dump({"t0_unix_ns": t0_unix_ns,
                   "identity": {"host": "devbox", "pid": 999,
                                "role": "device", "run_id": "r"}}, fh)


def test_profiler_lane_merges_with_host_spans(tmp_path):
    """A host artifact with the program's own iteration spans + a device
    capture carrying lgbm.* rows merge into one trace: the device lane
    is a source of its own beside the trainer's, and every span of both
    survives the merge."""
    art = tmp_path / "obs"
    prof = tmp_path / "device"
    art.mkdir()
    obs_trace.reset()
    obs_trace.arm(ring_events=1024)
    with obs_trace.iteration_span(0):
        with obs_trace.phase_span("dispatch"):
            t0 = obs_trace.now_ns()
            while obs_trace.now_ns() - t0 < 2_000_000:   # ~2 ms
                pass
    obs_agg.export_process_artifacts(str(art), label="trainer")
    obs_trace.reset()

    _write_device_capture(str(prof), t0_unix_ns=1, events=[
        {"ph": "X", "name": "fusion.3 lgbm.hist/one_hot", "ts": 10.0,
         "dur": 1000.0, "pid": 7, "tid": 1},
        {"ph": "X", "name": "lgbm.hist", "ts": 1100.0, "dur": 500.0,
         "pid": 7, "tid": 1},
        {"ph": "X", "name": "unrelated.op", "ts": 0.0, "dur": 50.0,
         "pid": 7, "tid": 2},
    ])
    summary = obs_agg.aggregate_dir(str(art), profile_dir=str(prof))
    assert summary["device_lanes"] == 1
    with open(summary["merged_trace"]) as fh:
        doc = json.load(fh)
    roles = {s["label"]: s.get("role")
             for s in doc["otherData"]["sources"]}
    assert any(lbl.startswith("device-") for lbl in roles)
    names = [e.get("name") for e in doc["traceEvents"]
             if e.get("ph") == "X"]
    assert {"train.iteration", "train.dispatch", "lgbm.hist",
            "unrelated.op"} <= set(names)
    lanes = {e["pid"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert len(lanes) == 2              # the trainer's and the device's


def test_profiler_trace_python_frames_dropped(tmp_path):
    """The profiler host lane's per-call python-frame events ($file:line)
    are dropped at ingestion — megabytes of interpreter noise that would
    drown the XLA rows the device lane exists for."""
    prof = tmp_path / "device"
    _write_device_capture(str(prof), t0_unix_ns=1, events=[
        {"ph": "X", "name": "$foo.py:1 bar", "ts": 0.0, "dur": 1.0,
         "pid": 7, "tid": 1},
        {"ph": "X", "name": "real.op", "ts": 0.0, "dur": 1.0,
         "pid": 7, "tid": 1},
    ])
    docs = obs_agg.load_profiler_traces(str(prof))
    assert len(docs) == 1
    _, doc = docs[0]
    names = [e["name"] for e in doc["traceEvents"]]
    assert names == ["real.op"]
    assert doc["otherData"]["python_frames_dropped"] == 1


# ---------------------------------------------------------------------------
# capture harness (tools/capture.py) — CPU dry-run with stubbed stages
# ---------------------------------------------------------------------------


def _stub_record_cmd():
    import ci_gate

    rec = {g: True for g in ci_gate.REQUIRED_GUARDS}
    rec.update({"metric": "stub", "value": 1.0, "unit": "M row-trees/s"})
    return [sys.executable, "-c",
            "import json; print(json.dumps(" + repr(rec) + "))"]


# tier-1 wall budget (tools/tier1_budget.py, the PR-6/7/10 discipline):
# at ~32 s (a real profiled window + subprocess stages) this is the
# single largest tier-1 offender; the harness it rehearses runs FOR
# REAL on every driver capture (tools/capture.py + ci_gate), its gate
# mechanics stay fast-pinned in tests/test_obs.py, and the full suite
# still runs it
@pytest.mark.slow
def test_capture_dry_run_produces_validated_trace_and_gated_record(
        tmp_path):
    """tools/capture.py --dry-run on CPU: the profiled window + merge +
    record emission + ci_gate --require-guards pipeline end-to-end (the
    bench/smoke stages stubbed with a guard-complete record so the test
    exercises the HARNESS, not a multi-minute bench run — bench.py's own
    record is asserted by the driver capture)."""
    from capture import run_capture, validate_merged_trace

    summary = run_capture(
        out_dir=str(tmp_path / "cap"), dry_run=True,
        bench_cmd=_stub_record_cmd(),
        smoke_cmd=[sys.executable, "-c", "print('smoke ok')"],
        window_rows=256, out=lambda *_: None)
    assert summary["ok"] is True
    assert summary["bench_rc"] == 0 and summary["smoke_rc"] == 0
    assert summary["gate"]["ok"] is True
    # records landed in the SCRATCH dir, in the captured format
    assert os.path.dirname(summary["bench_record"]) == \
        summary["records_dir"]
    assert summary["records_dir"] != REPO
    with open(summary["bench_record"]) as fh:
        rec = json.load(fh)
    assert rec["parsed"]["obs_device_ok"] is True
    assert rec["rc"] == 0 and "tail" in rec
    # the merged trace re-validates and has >= 2 lanes (host + device)
    info = validate_merged_trace(summary["merged_trace"]["path"])
    assert info["events"] > 0 and info["lanes"] >= 2
    assert summary["device_lanes"] >= 1


@pytest.mark.slow
def test_capture_gate_fails_on_missing_guard(tmp_path):
    """A bench record that silently drops a required guard (here: all of
    them) must fail the capture's gate — a guard that vanishes is a
    guard that failed.  Slow-marked (a second real profiler window) per
    the tier-1 budget discipline: the guards_ok mechanism itself is
    pinned fast by tests/test_obs.py's ci_gate pins."""
    from capture import run_capture

    bad = [sys.executable, "-c",
           "import json; print(json.dumps({'metric': 's', 'value': 1.0}))"]
    summary = run_capture(
        out_dir=str(tmp_path / "cap"), dry_run=True, bench_cmd=bad,
        smoke_cmd=[sys.executable, "-c", "print('ok')"],
        window_rows=256, out=lambda *_: None)
    assert summary["ok"] is False
    assert summary["gate"]["guards_ok"] is False


def test_capture_parent_stays_off_jax_until_stages_ran(tmp_path):
    """One process per chip: the capture parent spawns its three stages
    (profiled window, bench, smokes) and must not have imported JAX when
    any of them starts — a parent that has touched JAX holds the chip
    and the stage that needs it fails or hangs."""
    import subprocess

    art = tmp_path / "obs"
    art.mkdir()
    script = f"""
import json, sys
sys.path.insert(0, {os.path.join(REPO, "tools")!r})
import capture
seen = []
def stub_stage(cmd, env=None, timeout_s=0.0):
    seen.append("jax" in sys.modules)
    return {{"cmd": "stub", "rc": 0, "tail": "", "seconds": 0.0,
             "parsed": {{"artifact_dir": {str(art)!r},
                         "profile_dir": {str(tmp_path / "device")!r},
                         "artifacts": []}}}}
capture.run_stage = stub_stage
try:
    capture.run_capture(out_dir={str(tmp_path / "cap")!r}, dry_run=True,
                        out=lambda *_: None)
except Exception as e:      # the merge of three empty stubs may object;
    pass                    # only the import discipline is under test
print(json.dumps(seen))
"""
    out = subprocess.run([sys.executable, "-c", script], cwd=str(tmp_path),
                         capture_output=True, text=True, timeout=300,
                         check=True).stdout
    assert json.loads(out.strip().splitlines()[-1]) == [False] * 3


def test_validate_merged_trace_rejects_garbage(tmp_path):
    from capture import validate_merged_trace

    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"traceEvents": "nope"}))
    with pytest.raises(ValueError):
        validate_merged_trace(str(p))
    p.write_text(json.dumps({
        "traceEvents": [{"ph": "X", "name": "e", "pid": 1, "ts": -5,
                         "dur": 1}],
        "otherData": {"sources": [{"label": "x"}]}}))
    with pytest.raises(ValueError):
        validate_merged_trace(str(p))


def test_capture_next_round_numbering(tmp_path):
    from capture import next_round

    assert next_round(str(tmp_path)) == 1
    (tmp_path / "BENCH_r04.json").write_text("{}")
    (tmp_path / "MULTICHIP_r07.json").write_text("{}")
    assert next_round(str(tmp_path)) == 8


# ---------------------------------------------------------------------------
# export-once profiler helper (the cli.py profile_dir fix)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_profiler_session_export_once_and_anchor(tmp_path):
    """start/stop_profiler: the second stop is a no-op (export-once — the
    crash path and the clean path can both call it), and the anchor
    sidecar lands with the wall instant of the arm."""
    import jax.numpy as jnp

    d = str(tmp_path / "prof")
    session = obs_xla.start_profiler(d)
    jnp.ones(8).sum().block_until_ready()
    assert obs_xla.stop_profiler(session) is True
    assert obs_xla.stop_profiler(session) is False
    anchor = obs_xla.read_anchor(d)
    assert anchor and anchor["t0_unix_ns"] > 0
    assert anchor["identity"]["pid"] == os.getpid()
    assert obs_agg.load_profiler_traces(d), "capture produced no trace"


@pytest.mark.slow
def test_cli_profile_dir_covers_predict(tmp_path):
    """profile_dir is honored by task=predict (it was train-only), and
    the capture survives the window via the export-once helper."""
    from lightgbmv1_tpu.cli import main as cli_main

    rng = np.random.RandomState(0)
    X = rng.randn(300, 4)
    y = (X[:, 0] > 0).astype(float)
    data = str(tmp_path / "train.tsv")
    np.savetxt(data, np.column_stack([y, X]), fmt="%.6g", delimiter="\t")
    model = str(tmp_path / "m.txt")
    cli_main([f"data={data}", "num_trees=2", "num_leaves=7",
              f"output_model={model}", "verbosity=-1"])
    prof = str(tmp_path / "predict_prof")
    out = str(tmp_path / "preds.txt")
    cli_main([f"task=predict", f"data={data}", f"input_model={model}",
              f"output_result={out}", f"profile_dir={prof}",
              "verbosity=-1"])
    assert os.path.exists(out)
    files = [os.path.join(r, f) for r, _, fs in os.walk(prof) for f in fs]
    assert files, "predict profiler capture is empty"
    assert obs_xla.read_anchor(prof) is not None
