"""Unified observability layer (lightgbmv1_tpu/obs/ + the sentinel tools).

The contracts under test (ISSUE 9):

* **tracer** — span nesting (thread-local stack, children inside their
  parent's interval), ring-buffer overflow (oldest events overwritten,
  drop count reported), Chrome trace-event export validity, and the
  hard-off contract: the disarmed ``span()`` path allocates NOTHING
  (singleton no-op, pinned with ``sys.getallocatedblocks``).
* **trace-id propagation** — threaded HTTP clients: every response
  carries a unique ``X-Trace-Id`` echoed in header + body, a
  client-sent id is echoed verbatim, and an armed tracer decomposes
  each request into queue/walk spans carrying the id.
* **metrics registry** — Prometheus text exposition PINNED (label
  escaping, monotone cumulative histogram buckets, ``+Inf`` = count),
  thread-safe counters, JSON snapshot, serve-metrics adapter parity.
* **sentinel** — tools/bench_trend.py: a healthy five-record
  trajectory exits 0; a synthetic regressed record and a guard flip
  exit 1; tools/ci_gate.py combines trend + tier-1 budget into one
  exit code.
"""

import gc
import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import lightgbmv1_tpu as lgb
from lightgbmv1_tpu.obs import metrics as obs_metrics
from lightgbmv1_tpu.obs import trace
from lightgbmv1_tpu.serve import ServeConfig, ServeHTTP, Server

from conftest import make_binary_problem

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


@pytest.fixture(autouse=True)
def _tracer_clean():
    trace.reset()
    yield
    trace.reset()


@pytest.fixture(scope="module")
def booster():
    X, y = make_binary_problem(1000, 6, seed=3)
    b = lgb.train({"objective": "binary", "num_leaves": 15,
                   "min_data_in_leaf": 5, "verbosity": -1},
                  lgb.Dataset(X, label=y), num_boost_round=3)
    return b, X


def _serve_cfg(**over):
    kw = dict(max_batch_rows=64, max_batch_delay_ms=1.0,
              queue_depth_rows=1024, f64_scores=True,
              predictor_kwargs={"bucket_min": 64})
    kw.update(over)
    return ServeConfig(**kw)


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


def test_span_nesting_and_export():
    trace.arm(ring_events=256)
    with trace.span("outer", cat="t", args={"k": 1}):
        assert trace.depth() == 1
        time.sleep(0.002)
        with trace.span("inner"):
            assert trace.depth() == 2
            time.sleep(0.002)
    assert trace.depth() == 0
    doc = trace.export_chrome()
    evs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    assert set(evs) == {"outer", "inner"}
    outer, inner = evs["outer"], evs["inner"]
    # child interval nests inside the parent's
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert outer["args"] == {"k": 1}
    assert doc["otherData"]["dropped_events"] == 0
    json.dumps(doc)   # valid Chrome trace JSON end to end


def test_span_threads_are_independent():
    trace.arm(ring_events=256)
    seen = {}

    def worker():
        with trace.span("w"):
            seen["depth"] = trace.depth()

    with trace.span("main"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        assert trace.depth() == 1      # worker's stack never leaked here
    assert seen["depth"] == 1          # worker saw only its own span
    tids = {e["tid"] for e in trace.export_chrome()["traceEvents"]
            if e["ph"] == "X"}
    assert len(tids) == 2              # two OS threads, two lanes


def test_ring_buffer_overflow_keeps_newest():
    trace.arm(ring_events=16)
    for i in range(40):
        trace.instant(f"e{i}")
    snap = trace.drain()
    assert len(snap["events"]) == 16
    assert snap["dropped"] == 24
    names = [e[0] for e in snap["events"]]
    assert names == [f"e{i}" for i in range(24, 40)]   # oldest overwritten
    assert trace.export_chrome()["otherData"]["dropped_events"] == 24


def test_disarmed_span_allocates_nothing():
    """The hard-off contract: span() while disarmed returns the shared
    no-op singleton and the loop allocates no blocks."""
    assert not trace.enabled()
    assert trace.span("a") is trace.span("b")   # singleton
    with trace.span("noop"):                    # usable as a context mgr
        pass
    # min-of-3 windows: a stray daemon thread from an earlier test module
    # allocating during one window must not flake the pin
    delta = 1 << 30
    for _ in range(3):
        gc.collect()
        before = sys.getallocatedblocks()
        for _ in range(10_000):
            with trace.span("hot"):
                pass
        delta = min(delta, sys.getallocatedblocks() - before)
    assert delta < 50, f"disarmed span path allocated {delta} blocks"


def test_disarm_mid_span_drops_cleanly():
    trace.arm(ring_events=64)
    sp = trace.span("half")
    with sp:
        trace.disarm()
    assert trace.drain()["events"] == []   # dropped, never crashed


def test_rearm_mid_span_drops_pre_arm_events_at_export():
    """A span ENTERED before the most recent arm() carries a t0 from the
    previous epoch; exporting it would produce a negative ts.  The
    export drops it and reports the count (ISSUE 10 satellite)."""
    trace.arm(ring_events=64)
    sp = trace.span("stale")
    with sp:
        time.sleep(0.002)
        trace.arm(ring_events=64)          # re-arm MID-span
        with trace.span("fresh"):
            time.sleep(0.001)
    # the stale span closed after the re-arm: it recorded into the new
    # ring with a pre-arm t0
    doc = trace.export_chrome()
    names = [e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert "fresh" in names and "stale" not in names
    assert doc["otherData"]["pre_arm_dropped"] == 1
    assert all(e["ts"] >= 0 for e in doc["traceEvents"]
               if e.get("ph") == "X")
    json.dumps(doc)


def test_export_carries_wall_anchor_and_identity():
    from lightgbmv1_tpu.obs import events as obs_events

    trace.arm(ring_events=64)
    with trace.span("x"):
        pass
    other = trace.export_chrome()["otherData"]
    ident = obs_events.identity()
    assert other["t0_unix_ns"] > 1e18          # a real wall instant (ns)
    assert other["pid"] == os.getpid()
    assert other["host"] == ident["host"]
    assert other["role"] == ident["role"]



def test_train_iteration_spans_and_registry(booster):
    """An armed tracer records one span per boosting iteration, and the
    per-iteration wall histogram is published to the default registry
    whether or not the tracer is armed."""
    X, y = make_binary_problem(800, 6, seed=4)
    reg = obs_metrics.default_registry()
    before = reg.counter("train_iterations_total").get()
    trace.arm(ring_events=4096)
    lgb.train({"objective": "binary", "num_leaves": 7,
               "min_data_in_leaf": 5, "verbosity": -1},
              lgb.Dataset(X, label=y), num_boost_round=3)
    doc = trace.export_chrome()
    iters = [e for e in doc["traceEvents"]
             if e["name"] == "train.iteration"]
    assert len(iters) == 3
    assert [e["args"]["iteration"] for e in iters] == [0, 1, 2]
    assert reg.counter("train_iterations_total").get() == before + 3
    assert reg.histogram("train_iteration_ms").window_len() >= 0  # exists


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_prometheus_exposition_pinned():
    """The exposition format is PINNED byte-for-byte: HELP/TYPE headers,
    escaped label values, cumulative monotone buckets ending at +Inf."""
    reg = obs_metrics.Registry()
    c = reg.counter("req_total", "Requests", label_names=("route",))
    c.labels(route='/a"b\\c\nd').inc(3)
    g = reg.gauge("depth", "Queue depth")
    g.set(7)
    h = reg.histogram("lat_ms", "Latency", buckets=(1, 5, 10))
    for v in (0.5, 4.0, 9.0, 50.0):
        h.observe(v)
    assert reg.prometheus_text() == (
        '# HELP depth Queue depth\n'
        '# TYPE depth gauge\n'
        'depth 7\n'
        '# HELP lat_ms Latency\n'
        '# TYPE lat_ms histogram\n'
        'lat_ms_bucket{le="1"} 1\n'
        'lat_ms_bucket{le="5"} 2\n'
        'lat_ms_bucket{le="10"} 3\n'
        'lat_ms_bucket{le="+Inf"} 4\n'
        'lat_ms_sum 63.5\n'
        'lat_ms_count 4\n'
        '# HELP req_total Requests\n'
        '# TYPE req_total counter\n'
        'req_total{route="/a\\"b\\\\c\\nd"} 3\n'
    )


def test_histogram_buckets_monotone_and_quantiles():
    reg = obs_metrics.Registry()
    h = reg.histogram("h_ms", "", buckets=(10, 1, 5), sample_window=128)
    assert h.bucket_bounds == (1.0, 5.0, 10.0)   # sorted at registration
    vals = [0.5, 2, 3, 6, 8, 12, 100]
    for v in vals:
        h.observe(v)
    text = reg.prometheus_text()
    counts = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
              if line.startswith("h_ms_bucket")]
    assert counts == sorted(counts)              # cumulative => monotone
    assert counts[-1] == len(vals)               # +Inf == observation count
    assert h.quantile(0.5) == 6                  # exact over the window
    assert h.quantile(1.0) == 100


def test_registry_thread_safety():
    reg = obs_metrics.Registry()
    c = reg.counter("n_total", "", label_names=("who",))
    h = reg.histogram("d_ms", "", sample_window=64)
    N, T = 2500, 8

    def worker(i):
        child = c.labels(who=str(i % 2))
        for _ in range(N):
            child.inc()
            h.observe(1.0)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = sum(child.get() for _, child in c.children())
    assert total == N * T                        # no lost increments
    assert h._solo().count == N * T


def test_histogram_rejects_nonfinite_observations():
    """observe(NaN/±Inf) is REJECTED and counted — before this guard a
    single NaN landed silently in the +Inf bucket and poisoned `sum`
    (and through it every mean) forever (ISSUE 10 satellite)."""
    reg = obs_metrics.Registry()
    h = reg.histogram("lat_ms", "", buckets=(1, 10), sample_window=16)
    h.observe(2.0)
    for bad in (float("nan"), float("inf"), float("-inf")):
        h.observe(bad)
    snap = reg.snapshot()
    assert snap["lat_ms_count"] == 1            # only the finite one
    assert snap["lat_ms_sum"] == 2.0            # sum not poisoned
    assert snap['obs_bad_observations_total{metric="lat_ms"}'] == 3
    assert h.quantile(1.0) == 2.0               # window clean too
    # the +Inf bucket holds only real observations
    text = reg.prometheus_text()
    assert 'lat_ms_bucket{le="+Inf"} 1' in text
    # and each rejection published a warning event
    from lightgbmv1_tpu.obs import events

    evs = events.tail(kind_prefix="metrics.bad_observation", n=3)
    assert len(evs) == 3 and evs[-1]["fields"]["metric"] == "lat_ms"


def test_registry_reset_races_concurrent_writers():
    """reset() racing observe()/inc() from serving threads: no torn
    buckets, no exceptions, and the post-race state is consistent
    (bucket cumsum == count) (ISSUE 10 satellite)."""
    reg = obs_metrics.Registry()
    c = reg.counter("n_total", "")
    h = reg.histogram("d_ms", "", buckets=(1, 5, 10), sample_window=32)
    stop = threading.Event()
    errors = []

    def writer():
        try:
            while not stop.is_set():
                c.inc()
                h.observe(3.0)
        except Exception as e:  # noqa: BLE001 — any raise fails the test
            errors.append(e)

    def resetter():
        try:
            for _ in range(200):
                reg.reset()
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=writer) for _ in range(4)] \
        + [threading.Thread(target=resetter)]
    for t in threads:
        t.start()
    time.sleep(0.15)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert not errors
    child = h._solo()
    with h.lock:
        assert sum(child.buckets) <= child.count   # never torn past count
        assert len(child._window) <= 32
    # a final reset + write round works normally
    reg.reset()
    h.observe(2.0)
    assert h._solo().count == 1


def test_registry_snapshot_under_labeled_child_churn():
    """snapshot()/prometheus_text() while another thread creates new
    labeled children: no RuntimeError from dict mutation, every
    snapshot internally consistent (ISSUE 10 satellite)."""
    reg = obs_metrics.Registry()
    c = reg.counter("churn_total", "", label_names=("who",))
    stop = threading.Event()
    errors = []

    def churner():
        # cycle over a bounded label set: the race under test is
        # child-creation vs snapshot iteration, not unbounded growth
        # (100k children would make each snapshot O(n^2) and blow the
        # tier-1 wall for no extra coverage)
        i = 0
        try:
            while not stop.is_set():
                c.labels(who=f"w{i % 64}").inc()
                i += 1
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def reader():
        try:
            for _ in range(300):
                snap = reg.snapshot()
                assert all(v >= 0 for v in snap.values())
                reg.prometheus_text()
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=churner),
               threading.Thread(target=reader)]
    for t in threads:
        t.start()
    time.sleep(0.15)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert not errors
    assert 0 < len(c.children()) <= 64
    total = sum(child.get() for _, child in c.children())
    assert total >= len(c.children())   # every surviving child was inc'd


def test_log_callback_races_set_verbosity():
    """register_callback()/_emit() are thread-safe: serving threads log
    while another thread swaps the callback and the verbosity — no
    exceptions, no line delivered to a half-installed callback
    (ISSUE 10 satellite)."""
    from lightgbmv1_tpu.utils import log

    lines = []
    lock = threading.Lock()
    errors = []
    stop = threading.Event()

    def cb(msg):
        with lock:
            lines.append(msg)

    def logger():
        try:
            while not stop.is_set():
                log.log_warning("race line")
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def flipper():
        try:
            for i in range(300):
                log.register_callback(cb if i % 2 == 0 else None)
                log.set_verbosity(-1 if i % 3 == 0 else 1)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    prev_level = log._level
    try:
        log.set_verbosity(-1)   # keep stderr quiet for the None phases
        threads = [threading.Thread(target=logger) for _ in range(3)] \
            + [threading.Thread(target=flipper)]
        for t in threads:
            t.start()
        time.sleep(0.15)
        stop.set()
        for t in threads:
            t.join(timeout=10)
    finally:
        log.register_callback(None)
        log.set_verbosity(prev_level)
    assert not errors
    assert all("race line" in ln for ln in lines)


def test_registry_get_or_create_and_conflicts():
    reg = obs_metrics.Registry()
    a = reg.counter("x_total", "first")
    assert reg.counter("x_total", "again") is a   # idempotent
    with pytest.raises(ValueError):
        reg.gauge("x_total")                      # kind conflict
    with pytest.raises(ValueError):
        a.labels(nope="x")                        # undeclared label
    with pytest.raises(ValueError):
        a.inc(-1)                                 # counters only go up


def test_label_cardinality_cap_with_overflow_counter():
    """ISSUE 14 satellite (the ROADMAP item 4 label-explosion stress):
    a labeled metric holds at most ``label_cardinality`` children; new
    combinations beyond the cap collapse into ONE shared ``_overflow``
    child and every collapsed write is counted in
    ``obs_label_overflow_total{metric=...}`` — bounded exposition,
    explicit overflow."""
    reg = obs_metrics.Registry()
    c = reg.counter("tenant_requests_total", "per-tenant requests",
                    label_names=("tenant",), label_cardinality=4)
    for i in range(10):
        c.labels(tenant=f"t{i}").inc()
    with c.lock:
        n_children = len(c._children)
    assert n_children == 5          # 4 real + 1 overflow
    ov = c.labels(tenant="t9")      # routed to the shared overflow child
    assert ov is c.labels(tenant="t8")
    assert ov.get() == 6.0          # t4..t9 once each, minus... 6 writes
    ovf = reg.get("obs_label_overflow_total")
    assert ovf is not None
    # every collapsed labels() call counted (6 creations + 2 lookups)
    assert ovf.labels(metric="tenant_requests_total").get() == 8.0
    # an EXISTING key keeps resolving to its own child past the cap
    assert c.labels(tenant="t0").get() == 1.0
    # the exposition stays bounded and carries the overflow series
    text = reg.prometheus_text()
    assert text.count('tenant_requests_total{tenant="') == 5
    assert 'tenant="_overflow"' in text
    assert "obs_label_overflow_total" in text
    # snapshot() is equally bounded
    snap = reg.snapshot()
    assert sum(1 for k in snap
               if k.startswith("tenant_requests_total{")) == 5


def test_label_cardinality_default_is_generous():
    """The default cap (256) never bites normal label usage."""
    reg = obs_metrics.Registry()
    g = reg.gauge("g", label_names=("k",))
    assert g.label_cardinality == obs_metrics.DEFAULT_LABEL_CARDINALITY
    for i in range(64):
        g.labels(k=str(i)).set(i)
    assert reg.get("obs_label_overflow_total") is None   # never created
    with g.lock:
        assert len(g._children) == 64


def test_tenant_explosion_collapses_at_300_plus_scale():
    """ISSUE 20 satellite: the multi-tenant serving counter shape
    (``tenant`` x ``outcome``, exactly what server.py's
    ``serve_tenant_requests_total`` writes) driven past the default cap
    by 320 tenants.  The first ``label_cardinality`` combinations keep
    their own children; every later tenant collapses into ONE shared
    ``_overflow`` child; the exposition stays bounded; and the
    top-of-cap tenants' series are NOT poisoned by the tail — they keep
    counting exactly.  (test_tenants.py proves the same cap inside a
    live Server, where the SLO/drift/tenants snapshots ride per-tenant
    state objects and survive the collapse untouched.)"""
    cap = obs_metrics.DEFAULT_LABEL_CARDINALITY
    n = 320
    assert n > cap                       # the test must overflow the cap
    reg = obs_metrics.Registry()
    c = reg.counter("serve_tenant_requests_total",
                    "Per-tenant request outcomes",
                    label_names=("tenant", "outcome"))
    for i in range(n):
        c.labels(tenant=f"t{i:03d}", outcome="ok").inc()
    with c.lock:
        assert len(c._children) == cap + 1           # cap real + overflow
    # a top-of-cap tenant keeps ITS child past the explosion: counting
    # stays exact, unpoisoned by the 64-tenant overflow tail
    top = c.labels(tenant="t000", outcome="ok")
    assert top.get() == 1.0
    top.inc()
    assert c.labels(tenant="t000", outcome="ok").get() == 2.0
    # every post-cap tenant shares ONE overflow child, and each
    # collapsed write was counted on the overflow meter
    late = c.labels(tenant=f"t{cap:03d}", outcome="ok")
    assert late is c.labels(tenant=f"t{n - 1:03d}", outcome="ok")
    assert late.get() == float(n - cap)
    ovf = reg.get("obs_label_overflow_total")
    assert ovf.labels(
        metric="serve_tenant_requests_total").get() >= n - cap
    # bounded exposition no matter how many tenants wrote
    text = reg.prometheus_text()
    assert text.count("serve_tenant_requests_total{") == cap + 1
    assert 'tenant="_overflow"' in text
    snap = reg.snapshot()
    assert sum(1 for k in snap
               if k.startswith("serve_tenant_requests_total{")) == cap + 1


def test_serve_metrics_adapter_parity_and_exposition(booster):
    """serve/metrics.py is a thin adapter over the registry: the JSON
    snapshot keeps its exact pre-obs key set, and the SAME store renders
    Prometheus text."""
    b, X = booster
    srv = Server(b, config=_serve_cfg())
    try:
        for n in (1, 3):
            srv.submit(X[:n])
        snap = srv.metrics.snapshot()
        for key in ("submitted", "completed", "shed", "qps", "p50_ms",
                    "p99_ms", "p999_ms", "batch_occupancy",
                    "mean_batch_rows", "queue_depth", "queue_depth_max",
                    "shed_frac", "latency_window"):
            assert key in snap, key
        assert snap["completed"] == 2
        text = srv.metrics.prometheus_text()
        assert "# TYPE serve_completed_total counter" in text
        assert "serve_completed_total 2" in text
        assert "# TYPE serve_latency_ms histogram" in text
        assert 'serve_latency_ms_bucket{le="+Inf"} 2' in text
        srv.metrics.reset()
        assert srv.metrics.snapshot()["completed"] == 0
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# trace-id propagation (serve path)
# ---------------------------------------------------------------------------


def test_submit_decomposes_queue_and_walk(booster):
    b, X = booster
    srv = Server(b, config=_serve_cfg())
    try:
        srv.submit(X[:4])                        # warm
        trace.arm(ring_events=2048)
        res = srv.submit(X[:8])
        assert len(res.trace_id) == 16
        assert res.queue_ms >= 0 and res.walk_ms > 0
        # the decomposition accounts for the latency (completion fanout
        # after the walk is the only unattributed sliver)
        assert res.queue_ms + res.walk_ms <= res.latency_ms * 1.5 + 5.0
        evs = trace.export_chrome()["traceEvents"]
        q = [e for e in evs if e["name"] == "serve.queue"
             and e["args"]["trace_id"] == res.trace_id]
        w = [e for e in evs if e["name"] == "serve.walk"
             and e["args"]["trace_id"] == res.trace_id]
        batch = [e for e in evs if e["name"] == "serve.batch"]
        assert len(q) == 1 and len(w) == 1 and batch
        # explicit trace id is honored end to end
        res2 = srv.submit(X[:2], trace_id="deadbeefdeadbeef")
        assert res2.trace_id == "deadbeefdeadbeef"
    finally:
        srv.close()


def test_http_trace_id_unique_and_echoed_threaded(booster):
    """Threaded HTTP clients: every response's X-Trace-Id is unique,
    echoed in header AND body, and a client-provided id round-trips."""
    b, X = booster
    srv = Server(b, config=_serve_cfg())
    http = ServeHTTP(srv, port=0).start()
    got = []
    lock = threading.Lock()
    try:
        u = f"http://127.0.0.1:{http.port}/predict"

        def client():
            for _ in range(3):
                req = urllib.request.Request(
                    u, data=json.dumps({"rows": X[:2].tolist()}).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req) as resp:
                    body = json.loads(resp.read())
                    with lock:
                        got.append((resp.headers.get("X-Trace-Id"), body))

        threads = [threading.Thread(target=client) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(got) == 18
        header_ids = [h for h, _ in got]
        assert len(set(header_ids)) == 18        # unique per response
        for hdr, body in got:
            assert hdr and body["trace_id"] == hdr   # header == body
            assert body["queue_ms"] >= 0 and body["walk_ms"] >= 0
        # a client-sent id is echoed verbatim (propagation, not minting)
        req = urllib.request.Request(
            u, data=json.dumps({"rows": X[:1].tolist()}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Trace-Id": "cafe0123cafe0123"})
        with urllib.request.urlopen(req) as resp:
            assert resp.headers.get("X-Trace-Id") == "cafe0123cafe0123"
            assert json.loads(resp.read())["trace_id"] == "cafe0123cafe0123"
        # error paths carry the header too (a shed request is traceable)
        bad = urllib.request.Request(
            u, data=b"not json",
            headers={"Content-Type": "application/json",
                     "X-Trace-Id": "feed0123feed0123"})
        try:
            urllib.request.urlopen(bad)
            assert False, "expected HTTP 400"
        except urllib.error.HTTPError as e:
            assert e.code == 400
            assert e.headers.get("X-Trace-Id") == "feed0123feed0123"
    finally:
        http.shutdown()
        srv.close()


def test_http_metrics_content_negotiation(booster):
    b, X = booster
    srv = Server(b, config=_serve_cfg())
    http = ServeHTTP(srv, port=0).start()
    try:
        srv.submit(X[:2])
        u = f"http://127.0.0.1:{http.port}/metrics"
        # default: the JSON snapshot (pre-obs contract, unchanged)
        with urllib.request.urlopen(u) as resp:
            assert resp.headers["Content-Type"] == "application/json"
            snap = json.loads(resp.read())
            assert snap["completed"] >= 1 and "version" in snap
        # Accept: text/plain -> Prometheus exposition from the SAME store
        req = urllib.request.Request(u, headers={"Accept": "text/plain"})
        with urllib.request.urlopen(req) as resp:
            assert resp.headers["Content-Type"].startswith("text/plain")
            text = resp.read().decode()
        assert "# TYPE serve_completed_total counter" in text
        assert "serve_latency_ms_bucket" in text
        # query-param form works without an Accept header
        with urllib.request.urlopen(u + "?format=prometheus") as resp:
            assert resp.read().decode().startswith("# HELP")
    finally:
        http.shutdown()
        srv.close()


def test_loadgen_emits_through_registry(booster):
    from tools.loadgen import run_loadgen

    b, X = booster
    srv = Server(b, config=_serve_cfg())
    try:
        srv.submit(X[:4])
        lg = run_loadgen(srv, X, rate_qps=120.0, duration_s=0.5,
                         rows_per_req=1, n_threads=4, seed=2)
    finally:
        srv.close()
    cm = lg["client_metrics"]
    assert cm['loadgen_requests_total{outcome="ok"}'] == lg["ok"]
    assert cm['loadgen_requests_total{outcome="shed"}'] == lg["shed"]
    assert cm["loadgen_latency_ms_count"] == lg["ok"]
    assert lg["versions_served"] == {"v1": lg["ok"]}
    json.dumps(lg)   # still one JSON-able record end to end


# ---------------------------------------------------------------------------
# CLI trace_out
# ---------------------------------------------------------------------------


def test_cli_trace_out_writes_chrome_trace(tmp_path):
    from lightgbmv1_tpu.cli import run_train
    from lightgbmv1_tpu.config import Config

    X, y = make_binary_problem(400, 5, seed=6)
    data = tmp_path / "train.csv"
    with open(data, "w") as fh:
        for i in range(len(y)):
            fh.write(",".join([str(int(y[i]))]
                              + [f"{v:.6f}" for v in X[i]]) + "\n")
    out = tmp_path / "trace.json"
    cfg = Config.from_dict({
        "task": "train", "data": str(data), "objective": "binary",
        "num_iterations": 3, "num_leaves": 7, "min_data_in_leaf": 5,
        "verbosity": -1, "output_model": str(tmp_path / "m.txt"),
        "trace_out": str(out)})
    assert cfg.obs_trace          # trace_out implies arming (documented)
    run_train(cfg)
    assert not trace.enabled()    # disarmed on the way out
    doc = json.loads(out.read_text())
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    iters = [e for e in evs if e["name"] == "train.iteration"]
    assert len(iters) == 3
    assert any(e["name"] == "train.materialize_host_trees" for e in evs)
    assert doc["otherData"]["dropped_events"] == 0
    # no stray tmp file: the write was atomic (fileio tmp+rename)
    assert not [p for p in os.listdir(tmp_path)
                if p.startswith(".trace.json.tmp")]


def test_config_obs_knobs_validate():
    from lightgbmv1_tpu.config import Config

    with pytest.raises(ValueError):
        Config.from_dict({"obs_ring_events": 4})
    cfg = Config.from_dict({"obs_trace": True})
    assert cfg.obs_trace and not cfg.trace_out


# ---------------------------------------------------------------------------
# regression sentinel + CI gate
# ---------------------------------------------------------------------------


def _write_rec(d, name, parsed):
    with open(os.path.join(d, name), "w") as fh:
        json.dump({"n": 1, "parsed": parsed}, fh)


def test_bench_trend_healthy_series_passes(tmp_path):
    import bench_trend

    for i, value in enumerate((0.53, 1.87, 5.39, 6.30, 6.57), start=1):
        _write_rec(tmp_path, f"BENCH_r{i:02d}.json",
                   {"value": value, "auc": 0.8767, "serve_ok": True})
    result = bench_trend.run(str(tmp_path))
    assert result["ok"], result["flags"]
    assert len(result["bench_records"]) >= 5
    assert bench_trend.main(["--dir", str(tmp_path)]) == 0


def test_bench_trend_flags_regression_and_guard_flip(tmp_path):
    import bench_trend

    base = {"value": 5.0, "serve_p99_ms": 10.0, "stream_ok": True}
    _write_rec(tmp_path, "BENCH_r01.json", base)
    # healthy newest record -> exit 0
    _write_rec(tmp_path, "BENCH_r02.json",
               {"value": 5.2, "serve_p99_ms": 10.5, "stream_ok": True})
    assert bench_trend.main(["--dir", str(tmp_path)]) == 0
    # >10% throughput drop vs the BEST prior -> regression, exit 1
    _write_rec(tmp_path, "BENCH_r03.json",
               {"value": 4.0, "serve_p99_ms": 10.0, "stream_ok": True})
    result = bench_trend.run(str(tmp_path))
    assert not result["ok"]
    kinds = {(f["kind"], f["field"]) for f in result["flags"]}
    assert ("regression", "value") in kinds
    assert bench_trend.main(["--dir", str(tmp_path)]) == 1
    # a >10% ms rise is a regression on a lower-is-better field
    _write_rec(tmp_path, "BENCH_r03.json",
               {"value": 5.3, "serve_p99_ms": 12.0, "stream_ok": True})
    flags = bench_trend.run(str(tmp_path))["flags"]
    assert {f["field"] for f in flags} == {"serve_p99_ms"}
    # guard flip: True in a prior record, False in the newest -> exit 1
    _write_rec(tmp_path, "BENCH_r03.json",
               {"value": 5.3, "serve_p99_ms": 10.0, "stream_ok": False})
    flags = bench_trend.run(str(tmp_path))["flags"]
    assert flags == [{"kind": "guard_flip", "field": "stream_ok",
                      "record": "BENCH_r03.json",
                      "prior_record": "BENCH_r02.json"}]
    # a first-capture False guard is still flagged (guard_false)
    _write_rec(tmp_path, "BENCH_r03.json",
               {"value": 5.3, "serve_p99_ms": 10.0, "stream_ok": True,
                "obs_ok": False})
    flags = bench_trend.run(str(tmp_path))["flags"]
    assert [f["kind"] for f in flags] == ["guard_false"]
    # within-tolerance wobble never flags (the sentinel must not cry wolf)
    _write_rec(tmp_path, "BENCH_r03.json",
               {"value": 4.8, "serve_p99_ms": 10.9, "stream_ok": True})
    assert bench_trend.run(str(tmp_path))["ok"]


def test_bench_trend_reads_multichip_parity_tail(tmp_path):
    import bench_trend

    _write_rec(tmp_path, "BENCH_r01.json", {"value": 5.0})
    rec = {"n_devices": 8, "rc": 0,
           "tail": 'x\ndryrun_multichip PARITY {"comm_ok": false}\ny'}
    with open(os.path.join(tmp_path, "MULTICHIP_r01.json"), "w") as fh:
        json.dump(rec, fh)
    result = bench_trend.run(str(tmp_path))
    assert result["multichip_records"] == ["MULTICHIP_r01.json"]
    assert [f["field"] for f in result["flags"]] == ["comm_ok"]


def test_ci_gate_required_guards(tmp_path, capsys):
    """--require-guards (ISSUE 10): the newest record must CARRY each
    named guard as True — a capture that silently dropped the field
    fails, not just one that flipped it to False."""
    import ci_gate

    t1 = tmp_path / "durations.jsonl"
    with open(t1, "w") as fh:
        fh.write(json.dumps({"nodeid": "tests/test_a.py::t",
                             "when": "call", "duration": 1.0}) + "\n")
    _write_rec(tmp_path, "BENCH_r01.json",
               {"value": 5.0, "slo_ok": True, "forensics_ok": True})
    base = ["--records", str(tmp_path), "--t1-log", str(t1)]
    assert ci_gate.main(base + ["--require-guards",
                                "slo_ok,forensics_ok"]) == 0
    # missing guard field -> FAIL (trend alone would pass this record)
    assert ci_gate.main(base + ["--require-guards",
                                "slo_ok,forensics_ok,obs_ok"]) == 1
    # present-but-False -> FAIL (and the trend guard sweep flags it too)
    _write_rec(tmp_path, "BENCH_r02.json",
               {"value": 5.0, "slo_ok": False, "forensics_ok": True})
    assert ci_gate.main(base + ["--require-guards", "slo_ok"]) == 1
    # no --require-guards: old behavior intact apart from the flip flag
    _write_rec(tmp_path, "BENCH_r02.json",
               {"value": 5.0, "slo_ok": True, "forensics_ok": True})
    assert ci_gate.main(base) == 0
    capsys.readouterr()


def test_ci_gate_combines_trend_and_tier1(tmp_path, capsys):
    import ci_gate

    # healthy records + a within-budget durations file -> PASS
    _write_rec(tmp_path, "BENCH_r01.json", {"value": 5.0})
    _write_rec(tmp_path, "BENCH_r02.json", {"value": 5.5})
    t1 = tmp_path / "durations.jsonl"
    with open(t1, "w") as fh:
        fh.write(json.dumps({"nodeid": "tests/test_a.py::t", "when": "call",
                             "duration": 12.5}) + "\n")
    assert ci_gate.main(["--records", str(tmp_path),
                         "--t1-log", str(t1)]) == 0
    # a regressed record fails the ONE exit code
    _write_rec(tmp_path, "BENCH_r03.json", {"value": 1.0})
    assert ci_gate.main(["--records", str(tmp_path),
                         "--t1-log", str(t1)]) == 1
    # trend healthy again, but an over-budget suite fails it too
    _write_rec(tmp_path, "BENCH_r03.json", {"value": 5.6})
    with open(t1, "w") as fh:
        fh.write(json.dumps({"nodeid": "tests/test_a.py::t", "when": "call",
                             "duration": 9999.0}) + "\n")
    assert ci_gate.main(["--records", str(tmp_path),
                         "--t1-log", str(t1)]) == 1
    # a MISSING tier-1 log fails loudly (a guard that skips is no guard)
    assert ci_gate.main(["--records", str(tmp_path),
                         "--t1-log", str(tmp_path / "nope.log")]) == 1
    # ... unless the caller explicitly waives it (records-only box)
    assert ci_gate.main(["--records", str(tmp_path),
                         "--t1-log", str(tmp_path / "nope.log"),
                         "--skip-t1"]) == 0
    capsys.readouterr()


def test_obs_overhead_guard_drift_block_treatment():
    """ISSUE 15 satellite: the tracer A/B guard passes at <= 2% relative
    OR <= 20 ms absolute (the PR 14 session measured 0.0201 vs the bare
    0.02 bar in one of three otherwise-identical CPU runs — ~20 ms of
    scheduler noise on a ~1 s wall, not tracer cost).  The formula is a
    pure bench.py helper so this pin holds it still."""
    sys.path.insert(0, REPO)
    from bench import obs_overhead_guard_ok

    assert obs_overhead_guard_ok(0.0, 0.0)
    assert obs_overhead_guard_ok(0.02, 500.0)        # at the relative bar
    assert obs_overhead_guard_ok(0.0201, 15.0)       # the PR 14 flake
    assert obs_overhead_guard_ok(0.05, 19.9)         # fast wall, tiny abs
    assert not obs_overhead_guard_ok(0.0201, 21.0)   # over BOTH bars
    assert not obs_overhead_guard_ok(0.05, 500.0)    # a real regression
    assert not obs_overhead_guard_ok(None, 1.0)      # absent truth fails
    assert not obs_overhead_guard_ok(0.0201, None)
