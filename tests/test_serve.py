"""Online serving subsystem (lightgbmv1_tpu/serve/).

The contracts under test:

* **hot-swap under concurrent traffic** — threaded clients hammer
  ``Server.submit()`` across a mid-traffic ``publish()``; zero requests
  may drop, every response must be BIT-IDENTICAL to a direct
  ``Booster.predict`` of the version tag it carries, and the publish-time
  warm must leave zero retraces within a bucket (the PR 4 trace
  counters).
* **deadline-aware micro-batching** — concurrent submits coalesce into
  one device batch; a lone request dispatches on the delay budget, not
  the bucket fill.
* **admission control** — the bounded queue sheds EXPLICITLY
  (ServerOverloaded) instead of growing; per-request deadlines expire as
  RequestTimeout; overload degradation serves truncated-tree answers
  flagged ``degraded``.
* **registry** — atomic publish/rollback with version tags; metrics
  snapshot sanity; the stdlib HTTP front-end status-code mapping.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import lightgbmv1_tpu as lgb
from lightgbmv1_tpu.serve import (ModelRegistry, RequestTimeout,
                                  ServeConfig, ServeHTTP, Server,
                                  ServerOverloaded)

from conftest import make_binary_problem


def _train(rounds, num_leaves=15, seed=1):
    X, y = make_binary_problem(1200, 8, seed=seed)
    return lgb.train({"objective": "binary", "num_leaves": num_leaves,
                      "min_data_in_leaf": 5, "verbosity": -1},
                     lgb.Dataset(X, label=y), num_boost_round=rounds), X


def _host_raw(booster, X):
    return np.asarray(booster.predict(X, raw_score=True,
                                      predict_method="host"), np.float64)


@pytest.fixture(scope="module")
def boosters():
    b1, X = _train(4)
    b2, _ = _train(8, num_leaves=31)
    return b1, b2, X


def _serve_cfg(**over):
    kw = dict(max_batch_rows=128, max_batch_delay_ms=1.0,
              queue_depth_rows=4096, f64_scores=True,
              predictor_kwargs={"bucket_min": 64})
    kw.update(over)
    return ServeConfig(**kw)


# ---------------------------------------------------------------------------
# the satellite contract: hot-swap under threaded traffic
# ---------------------------------------------------------------------------


def test_hot_swap_under_threaded_traffic(boosters):
    """Threaded clients across a mid-traffic publish(): zero dropped
    responses, every response bit-identical to Booster.predict of the
    version tag it carries, zero retraces within a bucket."""
    b1, b2, X = boosters
    pool = X[:512]
    expected = {}
    versions = {}
    srv = Server(config=_serve_cfg())

    def publish(b):
        exp = _host_raw(b, pool)
        tag = srv.publish(b)
        expected[tag] = exp
        versions[tag] = srv.registry.current()
        return tag

    publish(b1)
    srv.submit(pool[:32])            # client-path warm
    warm_traces = {t: v.predictor.trace_count for t, v in versions.items()}

    N_CLIENTS, MIN_REQS = 8, 20
    failures = []
    served = []
    served_lock = threading.Lock()
    stop = threading.Event()
    barrier = threading.Barrier(N_CLIENTS + 1)
    rng = np.random.RandomState(3)

    def client(ci):
        crng = np.random.RandomState(100 + ci)
        barrier.wait()
        ri = 0
        # run until stopped so traffic brackets the publish no matter how
        # long its off-path warm takes (clients keep hammering while the
        # new version compiles, then keep going once it is swapped in)
        while not stop.is_set() or ri < MIN_REQS:
            s = int(crng.randint(0, 500))
            n = 1 + (ri % 4)
            ri += 1
            try:
                res = srv.submit(pool[s: s + n])
            except Exception as e:  # noqa: BLE001 — a drop IS the failure
                failures.append(f"client{ci}/{ri}: {type(e).__name__}: {e}")
                continue
            for _ in range(1000):    # wait out the tag-assignment window
                if res.version in expected:
                    break
                time.sleep(0.001)
            want = expected[res.version][s: s + n]
            if not np.array_equal(res.values[:, 0], want):
                failures.append(
                    f"client{ci}/{ri}: values diverged from "
                    f"Booster.predict of {res.version}")
            with served_lock:
                served.append(res.version)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(N_CLIENTS)]
    for t in threads:
        t.start()
    barrier.wait()                   # all clients in flight, then swap
    time.sleep(0.02)
    publish(b2)                      # concurrent with live traffic
    time.sleep(0.2)                  # let the new version serve
    stop.set()
    for t in threads:
        t.join()
    try:
        assert not failures, failures[:5]
        assert len(served) >= N_CLIENTS * MIN_REQS
        assert set(served) == {"v1", "v2"}, set(served)
        for tag, v in versions.items():
            grew = v.predictor.trace_count - warm_traces.get(
                tag, v.predictor.trace_count)
            assert grew == 0, (
                f"{tag}: {grew} retraces under live traffic — the "
                "publish-time warm must cover every live bucket")
        snap = srv.metrics_snapshot()
        assert snap["completed"] >= N_CLIENTS * MIN_REQS
        assert snap["swaps"] == 2 and snap["shed"] == 0
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# micro-batcher policy
# ---------------------------------------------------------------------------


def test_concurrent_submits_coalesce_into_one_batch(boosters):
    b1, _, X = boosters
    srv = Server(b1, config=_serve_cfg(max_batch_delay_ms=30.0))
    try:
        srv.submit(X[:1])            # warm
        srv.metrics.reset()
        barrier = threading.Barrier(6)
        results = []

        def client(i):
            barrier.wait()
            results.append(srv.submit(X[i: i + 1]))

        ts = [threading.Thread(target=client, args=(i,)) for i in range(6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        snap = srv.metrics_snapshot()
        # 6 concurrent 1-row submits under a 30 ms budget must ride few
        # device batches (not 6); each response records its batch size
        assert snap["batches"] < 6
        assert max(r.batch_rows for r in results) >= 2
        assert snap["completed"] == 6
    finally:
        srv.close()


def test_lone_request_dispatches_on_delay_budget(boosters):
    b1, _, X = boosters
    srv = Server(b1, config=_serve_cfg(max_batch_delay_ms=25.0))
    try:
        srv.submit(X[:1])            # warm (compile outside the window)
        t0 = time.monotonic()
        res = srv.submit(X[:1])
        wall_ms = (time.monotonic() - t0) * 1e3
        # the batch can never fill from one row: dispatch must come from
        # the deadline, i.e. >= the delay budget but not the 100 ms
        # idle-poll fallback
        assert res.batch_rows == 1
        assert wall_ms >= 20.0, wall_ms
        assert wall_ms < 500.0, wall_ms
    finally:
        srv.close()


def test_full_bucket_dispatches_before_delay(boosters):
    b1, _, X = boosters
    srv = Server(b1, config=_serve_cfg(max_batch_rows=64,
                                       max_batch_delay_ms=5000.0))
    try:
        srv.submit(X[:64])           # warm the bucket
        t0 = time.monotonic()
        res = srv.submit(X[:64])     # fills max_batch_rows exactly
        wall_ms = (time.monotonic() - t0) * 1e3
        assert res.batch_rows == 64
        assert wall_ms < 2500.0, (
            "a full bucket must dispatch immediately, not wait out the "
            f"5 s delay budget (took {wall_ms:.0f} ms)")
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# admission control / degradation
# ---------------------------------------------------------------------------


def test_bounded_queue_sheds_explicitly(boosters):
    b1, _, X = boosters
    srv = Server(b1, config=_serve_cfg(max_batch_rows=8,
                                       queue_depth_rows=8,
                                       max_batch_delay_ms=300.0))
    try:
        srv.submit(X[:2])            # warm
        held = []

        def holder():
            held.append(srv.submit(X[:6]))   # 6 rows < 8: waits for delay

        t = threading.Thread(target=holder)
        t.start()
        time.sleep(0.05)             # the 6-row request is now queued
        with pytest.raises(ServerOverloaded):
            srv.submit(X[:6])        # 6 + 6 > 8 -> shed NOW, not queued
        t.join()
        assert held and held[0].values.shape == (6, 1)
        snap = srv.metrics_snapshot()
        assert snap["shed"] == 1 and snap["completed"] >= 2
        assert snap["shed_frac"] > 0
    finally:
        srv.close()


def test_request_timeout_in_queue(boosters):
    b1, _, X = boosters
    srv = Server(b1, config=_serve_cfg(max_batch_rows=64,
                                       max_batch_delay_ms=120.0))
    try:
        srv.submit(X[:1], timeout_ms=0)      # warm; no deadline
        with pytest.raises(RequestTimeout):
            # deadline far below the batcher's delay budget: the request
            # expires in queue and is answered with the timeout, not a
            # late prediction
            srv.submit(X[:1], timeout_ms=5.0)
        assert srv.metrics_snapshot()["timeouts"] == 1
    finally:
        srv.close()


def test_overload_degrades_to_truncated_trees(boosters):
    _, b2, X = boosters
    srv = Server(config=_serve_cfg(degrade_trees=4, degrade_queue_frac=0.0))
    try:
        srv.publish(b2)
        res = srv.submit(X[:16])
        # degrade_queue_frac=0 -> every batch beyond warm runs the
        # truncated predictor: answers equal predict at num_iteration=4
        assert res.degraded
        want = np.asarray(b2.predict(X[:16], raw_score=True,
                                     num_iteration=4,
                                     predict_method="host"))
        np.testing.assert_array_equal(res.values[:, 0], want)
        assert srv.metrics_snapshot()["degraded"] >= 1
    finally:
        srv.close()


def test_degraded_truncation_rounds_to_iteration_boundary():
    rng = np.random.RandomState(5)
    X = rng.randn(900, 8)
    y = rng.randint(0, 3, 900).astype(float)
    b = lgb.train({"objective": "multiclass", "num_class": 3,
                   "num_leaves": 7, "min_data_in_leaf": 5,
                   "verbosity": -1}, lgb.Dataset(X, label=y),
                  num_boost_round=4)
    reg = ModelRegistry()
    reg.publish(b, degrade_trees=7, max_batch_rows=64)   # 7 -> 6 trees
    mv = reg.current()
    assert mv.degraded is not None
    assert mv.degraded.T == 6       # whole per-class groups only
    assert mv.degraded.K == 3


# ---------------------------------------------------------------------------
# registry / metrics / server lifecycle
# ---------------------------------------------------------------------------


def test_registry_publish_rollback_tags(boosters):
    b1, b2, X = boosters
    srv = Server(config=_serve_cfg())
    try:
        with pytest.raises(RuntimeError):
            srv.registry.current()
        t1 = srv.publish(b1)
        t2 = srv.publish(b2)
        assert (t1, t2) == ("v1", "v2")
        assert srv.version() == "v2"
        assert srv.registry.versions() == ["v1", "v2"]
        assert srv.rollback() == "v1"
        r = srv.submit(X[:4])
        assert r.version == "v1"
        np.testing.assert_array_equal(r.values[:, 0],
                                      _host_raw(b1, X[:4]))
        with pytest.raises(RuntimeError):
            srv.rollback()           # history exhausted
        snap = srv.metrics_snapshot()
        assert snap["swaps"] == 3 and snap["rollbacks"] == 1
    finally:
        srv.close()


def test_publish_rejects_empty_and_submit_validates_width(boosters):
    b1, _, X = boosters
    srv = Server(b1, config=_serve_cfg())
    try:
        with pytest.raises(ValueError, match="features"):
            srv.submit(np.zeros((2, 5)))
        with pytest.raises(ValueError, match="zero trees"):
            srv.publish(([], 1, 8))
    finally:
        srv.close()


def test_close_fails_pending_and_rejects_new(boosters):
    b1, _, X = boosters
    srv = Server(b1, config=_serve_cfg())
    srv.submit(X[:1])
    srv.close()
    from lightgbmv1_tpu.serve import ServerClosed

    with pytest.raises(ServerClosed):
        srv.submit(X[:1])


def test_metrics_snapshot_shape(boosters):
    b1, _, X = boosters
    srv = Server(b1, config=_serve_cfg())
    try:
        for n in (1, 3, 7):
            srv.submit(X[:n])
        snap = srv.metrics_snapshot()
        for key in ("qps", "p50_ms", "p99_ms", "p999_ms",
                    "batch_occupancy", "queue_depth_max", "shed_frac",
                    "completed", "swaps", "version", "versions"):
            assert key in snap, key
        assert snap["completed"] == 3
        assert 0 < snap["batch_occupancy"] <= 1
        assert snap["p50_ms"] > 0
        json.dumps(snap)             # JSON-able end to end
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# HTTP front-end + CLI task=serve
# ---------------------------------------------------------------------------


def test_http_endpoint_roundtrip(boosters):
    b1, _, X = boosters
    srv = Server(b1, config=_serve_cfg())
    http = ServeHTTP(srv, port=0).start()
    try:
        u = f"http://127.0.0.1:{http.port}"
        req = urllib.request.Request(
            u + "/predict",
            data=json.dumps({"rows": X[:3].tolist()}).encode(),
            headers={"Content-Type": "application/json"})
        out = json.loads(urllib.request.urlopen(req).read())
        assert out["version"] == "v1" and not out["degraded"]
        np.testing.assert_array_equal(
            np.asarray(out["values"])[:, 0], _host_raw(b1, X[:3]))
        health = json.loads(urllib.request.urlopen(u + "/healthz").read())
        # liveness, not process-up (PR 6): registry + dispatcher state;
        # ISSUE 9 adds the build version + replica uptime
        assert health["ok"] is True and health["version"] == "v1"
        assert health["dispatcher_alive"] is True
        assert health["published"] is True
        from lightgbmv1_tpu import __version__

        assert health["server_version"] == __version__
        assert health["uptime_s"] >= 0
        m = json.loads(urllib.request.urlopen(u + "/metrics").read())
        assert m["completed"] >= 1 and m["version"] == "v1"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                u + "/predict", data=b"not json",
                headers={"Content-Type": "application/json"}))
        assert ei.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(u + "/nope")
        assert ei.value.code == 404
    finally:
        http.shutdown()
        srv.close()


def test_http_sheds_map_to_503(boosters):
    b1, _, X = boosters
    srv = Server(b1, config=_serve_cfg(max_batch_rows=8, queue_depth_rows=8,
                                       max_batch_delay_ms=300.0))
    http = ServeHTTP(srv, port=0).start()
    try:
        srv.submit(X[:2])
        u = f"http://127.0.0.1:{http.port}/predict"

        def fire():
            try:
                urllib.request.urlopen(urllib.request.Request(
                    u, data=json.dumps({"rows": X[:6].tolist()}).encode(),
                    headers={"Content-Type": "application/json"}))
            except urllib.error.HTTPError:
                pass

        t = threading.Thread(target=fire)
        t.start()
        time.sleep(0.05)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                u, data=json.dumps({"rows": X[:6].tolist()}).encode(),
                headers={"Content-Type": "application/json"}))
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["shed"] is True
        t.join()
    finally:
        http.shutdown()
        srv.close()


def test_cli_task_serve_bounded_run(boosters, tmp_path):
    """task=serve end to end: load model, serve HTTP for a bounded
    window, answer a live request, shut down clean."""
    import socket

    from lightgbmv1_tpu.cli import run_serve
    from lightgbmv1_tpu.config import Config

    b1, _, X = boosters
    model = tmp_path / "model.txt"
    b1.save_model(str(model))
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    cfg = Config.from_dict({
        "task": "serve", "input_model": str(model), "verbosity": -1,
        "serve_http_port": port, "serve_duration_s": 2.0,
        "serve_max_batch_delay_ms": 1.0, "predict_f64_scores": True})
    got = {}

    def client():
        u = f"http://127.0.0.1:{port}"
        # the bounded window opens when the server is up, however long the
        # model load and the predictor's compile take on a busy machine
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            try:
                urllib.request.urlopen(u + "/healthz", timeout=0.2)
                break
            except OSError:
                time.sleep(0.05)
        req = urllib.request.Request(
            u + "/predict",
            data=json.dumps({"rows": X[:2].tolist()}).encode(),
            headers={"Content-Type": "application/json"})
        got.update(json.loads(urllib.request.urlopen(req).read()))

    t = threading.Thread(target=client)
    t.start()
    server, http = run_serve(cfg)
    t.join()
    assert got["version"] == "v1"
    np.testing.assert_array_equal(np.asarray(got["values"])[:, 0],
                                  _host_raw(b1, X[:2]))
    snap = server.metrics_snapshot()
    assert snap["completed"] >= 1


# ---------------------------------------------------------------------------
# loadgen (the open-loop harness itself)
# ---------------------------------------------------------------------------


def test_loadgen_smoke_and_record_fields(boosters):
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    from tools.loadgen import run_loadgen, serve_record_fields

    b1, _, X = boosters
    srv = Server(b1, config=_serve_cfg())
    try:
        srv.submit(X[:8])
        lg = run_loadgen(srv, X[:512], rate_qps=200.0, duration_s=0.8,
                         rows_per_req=2, n_threads=4, seed=2)
        assert lg["ok"] >= 100 and lg["error"] == 0
        fields = serve_record_fields(lg)
        for key in ("serve_qps", "serve_p99_ms", "serve_batch_occupancy",
                    "serve_shed_frac", "serve_swap_count"):
            assert key in fields, key
        assert fields["serve_shed_frac"] == 0.0
    finally:
        srv.close()
