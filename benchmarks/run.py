"""The benchmark's one entry point.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--rehearse-cpu]

Finds ``workloads/<cell>.json``, from it ``configs/<config>.json`` and
``traffic/<traffic>.json``, and the per-layer metrics in
``layer_metrics/*.json``; nothing in this file names a cell, a
configuration or a metric.  One run:

  set-up    data from the seed, the program's own binning, one ``Booster``
            through the normal entry (``lightgbmv1_tpu.Booster`` /
            ``Booster.update()``, which is what ``lightgbmv1_tpu.train``
            loops over), warm-up trees (the first compiles or loads the
            persistent cache);
  window    ``Booster.update()`` and a ``block_until_ready`` per tree until
            ``--seconds`` is up; the last tree started inside it is
            finished; no valid set, no host metric, ``gc`` off;
  trace     (``--trace 1``) a few more trees under the JAX profiler;
  after     peak memory read, the model dumped, the program's state freed,
            then held-out quality and the comparison with the plain
            reference (``reference.py``) that decides ``correct``: it
            follows the warm-up trees and the window's first.

The last line of standard output is the result the driver reads; every
other number goes on earlier lines.  Without a TPU the run fails unless
``--rehearse-cpu`` is given, which cuts the sizes by the cell's
``rehearse_scale`` and stamps ``"platform": "cpu"`` on what it prints:
such a line is never a device number.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

EXIT_NO_DEVICE = 3
EXIT_NO_PROGRAM = 4


def _read(*parts):
    with open(os.path.join(HERE, *parts)) as fh:
        return json.load(fh)


def load_cell(name: str):
    """The cell's file, its configuration and its traffic mix; the mix's
    parameters first, the cell's own keys over them."""
    cell = _read("workloads", f"{name}.json")
    config = _read("configs", f"{cell['config']}.json")
    traffic = _read("traffic", f"{cell['traffic']}.json")
    return {**traffic, **cell}, config


def load_layer_metrics(cell_name: str):
    """Every ``layer_metrics/*.json`` that lists this cell (or no cell)."""
    out = []
    for path in sorted(glob.glob(os.path.join(HERE, "layer_metrics",
                                               "*.json"))):
        with open(path) as fh:
            m = json.load(fh)
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        out.append(m)
    return out


def rehearse_on_cpu(chips: int) -> None:
    """Hold JAX to the CPU, with ``chips`` virtual devices; before JAX is
    imported."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if chips > 1 and "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={chips}").strip()


def say(kind: str, **fields) -> None:
    """An earlier line: one JSON object, never the last."""
    print(json.dumps({"line": kind, **fields}, default=float), flush=True)


def tree_counts(tree) -> tuple:
    """(root rows, [(left rows, right rows) per split]) of a parsed tree."""
    if len(tree.feature) == 0:
        return int(tree.leaf_count.sum()), []
    cnt = lambda c: int(tree.leaf_count[~c] if c < 0 else tree.node_count[c])
    return (int(tree.node_count[0]),
            [(cnt(l), cnt(r)) for l, r in zip(tree.left, tree.right)])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    cell, config = load_cell(args.workload)
    chips = int(cell["chips"])
    if args.rehearse_cpu:
        rehearse_on_cpu(chips)

    try:
        import jax
        import lightgbmv1_tpu as lgb
        from lightgbmv1_tpu.obs import xla as obs_xla
    except ImportError as e:
        print(f"benchmark: the program is not here: {e}", file=sys.stderr)
        return EXIT_NO_PROGRAM
    import numpy as np

    import datagen
    import readers
    import reference
    import trace_reduce

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"benchmark: no device: {e}", file=sys.stderr)
        return EXIT_NO_DEVICE
    platform = devices[0].platform
    if not args.rehearse_cpu and platform != "tpu":
        print(f"benchmark: platform is {platform!r}, not 'tpu' "
              "(--rehearse-cpu rehearses at a tiny size)", file=sys.stderr)
        return EXIT_NO_DEVICE
    if len(devices) < chips:
        print(f"benchmark: cell asks for {chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return EXIT_NO_DEVICE
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if platform == "tpu":
        from roofline import peaks_for
        peaks_for(device["kind"])          # an unknown device is an error

    now = time.perf_counter
    params = dict(config["params"])
    spec = config["data"]
    if int(cell["checked_trees"]) <= int(cell["warmup_trees"]):
        print("benchmark: checked_trees has to pass warmup_trees, so that "
              "the comparison reaches a tree of the window", file=sys.stderr)
        return 1
    scale = float(cell.get("rehearse_scale", 0.01)) if args.rehearse_cpu \
        else 1.0
    parts = {}

    # -- set-up ------------------------------------------------------------
    t = now()
    train, held = datagen.make_data(spec, args.seed, scale)
    parts["data_s"] = now() - t

    t = now()
    dtrain = lgb.Dataset(train.X, label=train.y, group=train.group,
                         params=dict(params)).construct()
    parts["binning_s"] = now() - t
    bin_rows_per_s = train.rows / parts["binning_s"]

    t = now()
    booster = lgb.Booster(params=dict(params), train_set=dtrain)
    gbdt = booster._gbdt

    def sync():
        jax.block_until_ready(gbdt._train_scores.score)

    sync()
    parts["placement_s"] = now() - t

    warm_ms = []
    for _ in range(int(cell["warmup_trees"])):
        t = now()
        booster.update()
        sync()
        warm_ms.append((now() - t) * 1e3)
    parts["warmup_s"] = sum(warm_ms) / 1e3
    stats_setup = obs_xla.compile_stats()
    parts["compile_s_by_label"] = {
        k: v["compile_ms_total"] / 1e3 for k, v in stats_setup.items()}
    compiles_before = obs_xla.compile_counts()
    gc.collect()
    gc.disable()

    # -- the window --------------------------------------------------------
    t0 = now()
    setup_s = t0 - T_PROCESS
    ticks, returned = [t0], []
    while ticks[-1] - t0 < args.seconds:
        booster.update()
        returned.append(now())
        sync()
        ticks.append(now())
    gc.enable()
    compiles_after = obs_xla.compile_counts()
    window_s = ticks[-1] - t0
    tree_ms = [(b - a) * 1e3 for a, b in zip(ticks[:-1], ticks[1:])]
    n_window = len(tree_ms)
    first_window_tree = int(cell["warmup_trees"])
    rate = train.rows * n_window / window_s
    say("setup", platform=platform, setup_s=setup_s, **parts,
        warmup_tree_ms=warm_ms)
    # a tree's time, and how much of it ``update()`` took to return: a
    # stall in the first is the host's dispatch, in the rest the device's
    say("window", platform=platform, trees=n_window, window_s=window_s,
        tree_ms=tree_ms,
        update_ms=[(r - a) * 1e3 for a, r in zip(ticks, returned)])

    # -- the traced trees ----------------------------------------------------
    trace = None
    traced_trees, traced_window_s = 0, 0.0
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            jax.profiler.start_trace(trace_dir)
            ta = now()
            for _ in range(int(cell["traced_trees"])):
                with jax.profiler.TraceAnnotation("bench.update"):
                    booster.update()
                    sync()
                traced_trees += 1
            traced_window_s = now() - ta
            jax.profiler.stop_trace()
            trace = trace_reduce.load(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # -- after the window ----------------------------------------------------
    # the allocator counts live buffers (``peak_bytes_in_use``) and the
    # scratch that loaded programs reserve for their temporaries
    # (``peak_bytes_reserved``) apart, and both hold HBM: the peak the
    # result reports is their sum on the fullest device, and both parts
    # stand beside it
    mem = [{k: int(v) for k, v in (d.memory_stats() or {}).items()
            if "bytes" in k} for d in devices]
    fullest = max(mem, key=lambda st: st.get("peak_bytes_in_use", 0)
                  + st.get("peak_bytes_reserved", 0))
    in_use = fullest.get("peak_bytes_in_use")
    reserved = fullest.get("peak_bytes_reserved", 0)
    say("memory", platform=platform, per_device=mem)
    n_trees = first_window_tree + n_window
    dump = booster.dump_model(num_iteration=n_trees)
    stats_all = obs_xla.compile_stats()
    del booster, gbdt, dtrain
    gc.collect()

    trees = [reference.parse_tree(t) for t in dump["tree_info"]]
    del dump
    failed = sum(1 for t in trees[first_window_tree:] if t.num_leaves <= 1)
    q_trees = int(cell["rehearse_quality_trees" if args.rehearse_cpu
                       else "quality_trees"])
    if n_trees < q_trees:
        print(f"benchmark: the run finished {n_trees} trees, fewer than "
              f"quality_trees={q_trees}", file=sys.stderr)
        return 1
    t = now()
    kind = spec["kind"]
    quality = reference.heldout_quality(
        kind, trees[:q_trees], held, int(params.get("eval_at", 10)))
    quality_s = now() - t

    t = now()
    numbers = reference.judge(
        trees[:int(cell["checked_trees"])], train, params,
        say=lambda **kw: say("judge", **kw))
    correct, rows = reference.verdict(numbers, cell["limits"])
    say("after", platform=platform, quality_s=quality_s,
        comparison_s=now() - t)

    # -- the result ------------------------------------------------------------
    if in_use is not None:
        device.update(memory_peak_bytes=in_use + reserved,
                      peak_bytes_in_use=in_use, peak_bytes_reserved=reserved)
    end_to_end = {
        "train_row_trees_per_s": (rate, "row-trees/s"),
        "heldout_quality": (quality, "auc_or_ndcg10"),
        "setup_s": (setup_s, "s"),
    }
    if args.trace:
        ctx = readers.Context(
            cell=args.workload, chips=chips, platform=platform,
            device_kind=device["kind"],
            rows=train.rows, features=int(spec["features"]),
            bins=int(params["max_bin"]) + 1,
            host={"bin_rows_per_s": bin_rows_per_s},
            tree_ms=tree_ms, window_s=window_s, compile_stats=stats_all,
            compiles_before=compiles_before, compiles_after=compiles_after,
            tree_counts=[tree_counts(t) for t in
                         trees[first_window_tree:n_trees]],
            peak_bytes=in_use, trace=trace, traced_trees=traced_trees,
            traced_window_s=traced_window_s,
            scopes=list(cell.get("scopes", [])))
        metrics = {}
        for m in load_layer_metrics(args.workload):
            value = readers.READERS[m["reader"]](ctx, **m.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if ctx.notes:
            say("readers", platform=platform, **ctx.notes)
        if trace is not None and trace.devices:
            device["busy_s"] = trace.busy_s
            device["window_s"] = traced_window_s
    else:
        metrics = {k: {"value": v, "unit": u}
                   for k, (v, u) in end_to_end.items()}
    result = {"correct": bool(correct), "attempted": n_window,
              "failed": failed, "metrics": metrics, "device": device}
    if trace is not None and trace.devices:
        result["breakdown"] = {
            "device_ops": trace_reduce.top_device_ops(
                trace, list(cell.get("scopes", []))),
            "idle_gaps": trace_reduce.top_idle_gaps(trace)}
    result["compared"] = {name: {"value": v, "limit": lim}
                          for name, v, lim in rows}
    if compiles_after != compiles_before:
        print("benchmark: something compiled inside the window: "
              f"{compiles_before} -> {compiles_after}", file=sys.stderr)
    sys.stdout.flush()
    for name, v, lim in rows:
        print(f"compared {name} = {v:.6g} (limit {lim:g})"
              f"{'' if v <= lim else '  <-- over'}", file=sys.stderr)
    print(f"correct = {bool(correct)}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
