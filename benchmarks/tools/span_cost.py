"""What the program's own tracing says of a cell, and what it costs.

    python3 benchmarks/tools/span_cost.py --workload <cell> --seed <n> \
        --seconds <s> [--arm 1] [--trace 1] [--fixture <out.trace.json.gz>] \
        [--rehearse-cpu]

One run of a cell's set-up and window exactly as ``run.py`` makes them
(same data, same booster, same loop: ``update()`` then a
``block_until_ready`` a tree), with two differences: ``--arm 1`` calls
``lightgbmv1_tpu.obs.trace.arm()`` before the window, so that the rate
with the tracer's ring on stands beside the rate with it off (PERF.md
section 6 holds three seeds of each); and after the window the readers of
``span_readers.py`` are applied to the program's per-tree records, its
registry and, with ``--trace 1``, the traced trees.  No comparison with
the reference, no quality: ``run.py`` decides ``correct``.

The one line printed is a JSON object: the rate, the per-tree arrays of
the window (``dispatch_ms`` / ``wait_ms`` / ``bookkeep_ms`` /
``prepare_ms`` beside the harness's ``tree_ms``), the binning phases, and
``metrics``: every span reader under the name PERF.md gives it.
``--fixture`` traces ONE more tree in a session of its own and writes it,
without the interpreter's frame events, for ``tests/data``.
"""

from __future__ import annotations

import argparse
import gc
import gzip
import json
import os
import shutil
import sys
import tempfile
import time

T_PROCESS = time.perf_counter()

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

PHASES = ("prepare", "dispatch", "bookkeep", "wait")
#: metric name -> (reader, args): the table of ISSUE 26
METRICS = {
    "step.dispatch_ms_per_tree": ("iteration_phase_ms",
                                  {"phases": ["dispatch"]}),
    "step.wait_ms_per_tree": ("iteration_phase_ms", {"phases": ["wait"]}),
    "step.host_ms_per_tree": ("iteration_phase_ms",
                              {"phases": ["prepare", "bookkeep"]}),
    "step.slowest_tree_wait_excess_ms": ("slowest_tree_excess_ms",
                                         {"phases": ["wait"]}),
    "step.slowest_tree_host_excess_ms": ("slowest_tree_excess_ms",
                                         {"phases": ["total", "-wait"]}),
    "idle.dispatch_ms_per_tree": ("idle_ms_under_host_span",
                                  {"spans": ["train.dispatch"]}),
    "idle.host_ms_per_tree": ("idle_ms_under_host_span",
                              {"spans": ["train.prepare", "train.bookkeep",
                                         "train.wait"]}),
    "idle.outside_program_ms_per_tree": (
        "idle_ms_under_host_span",
        {"spans": ["train.prepare", "train.dispatch", "train.bookkeep",
                   "train.wait"], "complement": True}),
    "bin.find_bins_s": ("registry_value", {
        "key": 'dataset_construct_seconds{phase="find_bins"}'}),
    "bin.apply_bins_s": ("registry_value", {
        "key": 'dataset_construct_seconds{phase="apply_bins"}'}),
}


def write_fixture(trace_dir: str, out: str) -> None:
    """The trace cut to what ``trace_reduce`` and the readers read: the
    devices' ``XLA Ops`` lines with each op's scope and category (and the
    custom fusions' ``long_name``, where the roofline reads a kernel's
    shapes), and the host lane without the interpreter's ``$file:line
    fn`` events."""
    import trace_reduce

    with gzip.open(trace_reduce.find_trace_json(trace_dir), "rt") as fh:
        doc = json.load(fh)
    events = doc["traceEvents"]
    procs = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    ops = {(e["pid"], e["tid"]) for e in events
           if e.get("ph") == "M" and e.get("name") == "thread_name"
           and e["args"]["name"] == "XLA Ops"}

    def keep(e):
        if e.get("ph") != "X":
            return e.get("ph") == "M"
        if procs.get(e.get("pid"), "").startswith("/device:"):
            return (e["pid"], e.get("tid")) in ops
        return not str(e.get("name", "")).startswith("$")

    doc["traceEvents"] = events = [e for e in events if keep(e)]
    for e in events:
        args = e.get("args") or {}
        if "hlo_category" in args:
            wanted = ("tf_op", "hlo_category") + (
                ("long_name",) if args["hlo_category"] == "custom fusion"
                else ())
            e["args"] = {k: args[k] for k in wanted if k in args}
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with gzip.open(out, "wt", compresslevel=9) as fh:
        json.dump(doc, fh, separators=(",", ":"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--arm", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fixture")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    import run as bench

    cell, config = bench.load_cell(args.workload)
    if args.rehearse_cpu:
        bench.rehearse_on_cpu(int(cell["chips"]))
    import jax
    import lightgbmv1_tpu as lgb
    from lightgbmv1_tpu.obs import trace as obs_trace
    from lightgbmv1_tpu.obs.metrics import default_registry

    import datagen
    import readers
    import span_readers  # noqa: F401  (registers its readers)
    import trace_reduce

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearse_cpu:
        print(f"span_cost: platform is {platform!r}, not 'tpu'",
              file=sys.stderr)
        return bench.EXIT_NO_DEVICE
    now = time.perf_counter
    params = dict(config["params"])
    scale = float(cell.get("rehearse_scale", 0.01)) if args.rehearse_cpu \
        else 1.0
    train, _ = datagen.make_data(config["data"], args.seed, scale)
    t = now()
    dtrain = lgb.Dataset(train.X, label=train.y, group=train.group,
                         params=dict(params)).construct()
    binning_s = now() - t
    booster = lgb.Booster(params=dict(params), train_set=dtrain)
    gbdt = booster._gbdt

    def sync():
        jax.block_until_ready(gbdt._train_scores.score)

    sync()
    for _ in range(int(cell["warmup_trees"])):
        booster.update()
        sync()
    gc.collect()
    gc.disable()
    if args.arm:
        obs_trace.arm()

    # -- the window, as run.py's ---------------------------------------------
    first = len(obs_trace.iteration_records())
    t0 = now()
    setup_s = t0 - T_PROCESS
    ticks = [t0]
    while ticks[-1] - t0 < args.seconds:
        booster.update()
        sync()
        ticks.append(now())
    gc.enable()
    armed_events = len(obs_trace.drain()["events"])
    obs_trace.disarm()
    records = obs_trace.iteration_records()[first:]
    window_s = ticks[-1] - t0
    tree_ms = [(b - a) * 1e3 for a, b in zip(ticks[:-1], ticks[1:])]

    # -- the traced trees ----------------------------------------------------
    trace, traced, traced_window_s = None, 0, 0.0
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="span-cost-")
        try:
            jax.profiler.start_trace(trace_dir)
            ta = now()
            for _ in range(int(cell["traced_trees"])):
                with jax.profiler.TraceAnnotation("bench.update"):
                    booster.update()
                    sync()
                traced += 1
            traced_window_s = now() - ta
            jax.profiler.stop_trace()
            trace = trace_reduce.load(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    if args.fixture:
        trace_dir = tempfile.mkdtemp(prefix="span-cost-")
        try:
            jax.profiler.start_trace(trace_dir)
            with jax.profiler.TraceAnnotation("bench.update"):
                booster.update()
                sync()
            jax.profiler.stop_trace()
            write_fixture(trace_dir, args.fixture)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    ctx = readers.Context(
        cell=args.workload, chips=int(cell["chips"]), platform=platform,
        device_kind=jax.devices()[0].device_kind, rows=train.rows,
        features=int(config["data"]["features"]),
        bins=int(params["max_bin"]) + 1, host={}, tree_ms=tree_ms,
        window_s=window_s, compile_stats={}, compiles_before={},
        compiles_after={}, tree_counts=[], peak_bytes=None, trace=trace,
        traced_trees=traced, traced_window_s=traced_window_s)
    ctx.iteration_records = records
    ctx.registry = default_registry().snapshot()
    metrics = {}
    for name, (fn, kw) in METRICS.items():
        value = readers.READERS[fn](ctx, **kw)
        if value is not None:
            metrics[name] = value
    out = {
        "cell": args.workload, "seed": args.seed, "platform": platform,
        "armed": bool(args.arm), "armed_ring_events": armed_events,
        "train_row_trees_per_s": train.rows * len(tree_ms) / window_s,
        "trees": len(tree_ms), "window_s": window_s, "setup_s": setup_s,
        "binning_s": binning_s,
        "binning_phases_s": {
            k.split('"')[1]: v for k, v in ctx.registry.items()
            if k.startswith("dataset_construct_seconds")},
        "median_tree_ms": readers.median_tree_ms(ctx),
        "tree_ms": tree_ms,
        **{p + "_ms": [r[2 + i] / 1e6 for r in records]
           for i, p in enumerate(PHASES)},
        "iteration_ms": [r[6] / 1e6 for r in records],
        "metrics": metrics,
    }
    if trace is not None and trace.devices:
        out["traced"] = {
            "trees": traced, "window_s": traced_window_s,
            "busy_s": trace.busy_s,
            "idle_share": readers.device_idle_share(ctx),
            "idle_ms_per_tree": (traced_window_s - trace.busy_s) * 1e3
            / traced,
            "idle_gaps": trace_reduce.top_idle_gaps(trace)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
