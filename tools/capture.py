"""One-command driver capture: arm, run, merge, record, gate.

Every device capture before ISSUE 12 was a hand-run session: bench here,
dryrun there, artifacts scattered, records assembled by copy-paste, the
gate run (or forgotten) afterwards.  This harness makes ROADMAP item 2's
capture campaign an executable procedure:

1. **Arm** — the XLA profiler (obs/xla.py ``profiler_session`` — device
   lane + wall-clock anchor sidecar) and the span tracer around a
   dedicated profiled training window whose host artifacts (trace /
   metrics / events) are exported next to the capture.  The window is a
   STAGE SUBPROCESS like the other two (``capture.py --window DIR``): a
   chip belongs to one process at a time, so this parent never imports
   JAX before its stages have run — a parent that trained in-process
   would hold the chip and starve ``bench.py``.
2. **Run** — ``bench.py`` (ALL blocks: train/predict/serve/chaos/stream/
   fleet/obs incl. the new device-truth block) and the
   ``__graft_entry__.py`` smoke battery (compile-check + serve_smoke +
   chaos_smoke + ``dryrun_multichip``), each as a subprocess with
   ``LGBMV1_OBS_DIR`` pointed at the capture's artifact directory.  The
   stages run one after another, each releasing the chip when it exits.
3. **Merge** — every artifact + the profiler capture into ONE Perfetto
   trace (obs/agg.py ``aggregate_dir(profile_dir=...)``): host span
   lanes, per-process metric/event artifacts and the device lane on one
   wall-clock axis, estimated phase spans reconciled against measured
   ``lgbm.*`` device rows (agreement ratio recorded).  The merged trace
   is schema-validated (:func:`validate_merged_trace`).
4. **Record** — ``BENCH_rNN.json`` / ``MULTICHIP_rNN.json`` in the
   repo's captured-record format ({n, cmd, rc, tail, parsed}).
5. **Gate** — ``tools/ci_gate.py`` with ``--require-guards default``
   (every ``*_ok`` the record must carry, incl. ``obs_device_ok``).

Usage::

    python tools/capture.py                  # real capture: records into
                                             # the repo, gate vs priors
    python tools/capture.py --dry-run        # CPU rehearsal: records into
                                             # a scratch dir, gated in
                                             # isolation (no priors), repo
                                             # records untouched
    python tools/capture.py --out DIR        # keep artifacts in DIR

Exit 0 only when every stage ran AND the gate passed.  Prints one JSON
summary line last.  ``run_capture`` is the library entry (tests drive it
with stubbed stage commands).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import subprocess
import sys
import tempfile
import time

TOOLS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(TOOLS)
for p in (ROOT, TOOLS):
    if p not in sys.path:
        sys.path.insert(0, p)

TAIL_BYTES = 40_000


def next_round(records_dir: str) -> int:
    """1 + the highest round among BENCH_r*/MULTICHIP_r* records."""
    best = 0
    for path in glob.glob(os.path.join(records_dir, "*_r*.json")):
        m = re.search(r"_r(\d+)\.json$", path)
        if m:
            best = max(best, int(m.group(1)))
    return best + 1


def run_stage(cmd, env=None, timeout_s: float = 7200.0) -> dict:
    """Run one capture stage as a subprocess; returns the record-shaped
    ``{cmd, rc, tail, parsed}`` dict (``parsed`` is the LAST JSON object
    line of stdout, the bench convention; None when none parses)."""
    t0 = time.time()
    try:
        proc = subprocess.run(
            cmd, cwd=ROOT, env=env, timeout=timeout_s,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        out = proc.stdout.decode("utf-8", "replace")
        rc = proc.returncode
    except subprocess.TimeoutExpired as e:
        out = (e.stdout or b"").decode("utf-8", "replace") + "\nTIMEOUT"
        rc = 124
    parsed = None
    for line in reversed(out.splitlines()):
        line = line.strip()
        if line.startswith("{") and line.endswith("}"):
            try:
                parsed = json.loads(line)
                break
            except ValueError:
                continue
    return {"cmd": " ".join(map(str, cmd)), "rc": rc,
            "tail": out[-TAIL_BYTES:], "parsed": parsed,
            "seconds": round(time.time() - t0, 1)}


def profiled_window(out_dir: str, rows: int = 4096, iters: int = 3) -> dict:
    """The dedicated profiled training window: a small train under the
    armed XLA profiler + span tracer (phase profile installed so the
    estimated spans exist for the reconciliation), exporting this
    process's host artifacts into ``out_dir`` and the device capture
    into ``out_dir``/device.  Small by design — the heavyweight numbers
    come from bench.py; this window exists to light up the device lane."""
    import numpy as np

    import lightgbmv1_tpu as lgb
    from lightgbmv1_tpu.obs import agg as obs_agg
    from lightgbmv1_tpu.obs import trace as obs_trace
    from lightgbmv1_tpu.obs import xla as obs_xla

    prof_dir = os.path.join(out_dir, "device")
    art_dir = os.path.join(out_dir, "obs")
    os.makedirs(art_dir, exist_ok=True)
    rng = np.random.RandomState(0)
    X = rng.randn(int(rows), 8)
    y = (X[:, 0] - X[:, 1] + 0.5 * X[:, 2] > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
              "verbosity": -1, "seed": 5}
    obs_trace.reset()
    obs_trace.arm(ring_events=1 << 15)
    try:
        with obs_xla.profiler_session(prof_dir):
            ds = lgb.Dataset(X, label=y, params=dict(params))
            lgb.train(dict(params), ds, num_boost_round=int(iters),
                      verbose_eval=False)
        paths = obs_agg.export_process_artifacts(art_dir, label="capture")
    finally:
        obs_trace.reset()
    return {"profile_dir": prof_dir, "artifact_dir": art_dir,
            "artifacts": sorted(paths)}


def validate_merged_trace(path: str) -> dict:
    """Schema validation of a merged Chrome trace: a JSON object with a
    ``traceEvents`` list whose complete events carry name/ph/ts/dur/pid
    with non-negative clocks, plus the merge provenance otherData.
    Raises ValueError on any violation; returns summary counts."""
    with open(path) as fh:
        doc = json.load(fh)
    if not isinstance(doc, dict) or not isinstance(
            doc.get("traceEvents"), list):
        raise ValueError("merged trace: not a Chrome trace document")
    other = doc.get("otherData") or {}
    if not isinstance(other.get("sources"), list) or not other["sources"]:
        raise ValueError("merged trace: missing merge provenance")
    lanes = set()
    n_complete = 0
    for ev in doc["traceEvents"]:
        ph = ev.get("ph")
        if ph == "X":
            n_complete += 1
            if not ev.get("name") or "pid" not in ev:
                raise ValueError(f"merged trace: malformed event {ev!r}")
            if float(ev.get("ts", -1)) < 0 or float(ev.get("dur", -1)) < 0:
                raise ValueError(
                    f"merged trace: negative clock in {ev.get('name')!r}")
            lanes.add(ev["pid"])
    if not n_complete:
        raise ValueError("merged trace: no complete events")
    return {"events": n_complete, "lanes": len(lanes),
            "sources": len(other["sources"])}


def run_capture(records_dir: str = ROOT, out_dir: str = None,
                round_no: int = None, dry_run: bool = False,
                bench_cmd=None, smoke_cmd=None, window_cmd=None,
                skip_t1: bool = True,
                t1_log: str = "/tmp/_t1.log", window_rows: int = 4096,
                stage_timeout_s: float = 7200.0, out=print) -> dict:
    """The full capture pipeline (module docstring).  ``dry_run`` writes
    the records into a SCRATCH records dir and gates them in isolation —
    the repo's captured history is never touched by a rehearsal.
    ``bench_cmd``/``smoke_cmd``/``window_cmd`` override the stage
    commands (tests stub them); ``skip_t1`` passes through to the gate
    (a capture box has no tier-1 log unless the suite just ran)."""
    import ci_gate  # noqa: E402 — sibling tool, path set above

    out_dir = out_dir or tempfile.mkdtemp(prefix="capture_")
    os.makedirs(out_dir, exist_ok=True)
    rec_out = (tempfile.mkdtemp(prefix="capture_records_")
               if dry_run else records_dir)
    n = round_no if round_no is not None else next_round(records_dir)
    summary = {"round": n, "out_dir": out_dir, "records_dir": rec_out,
               "dry_run": bool(dry_run), "ok": False}

    # 1. armed profiled window (device lane + host artifacts) — its own
    # process, which holds the chip only until it exits
    window_cmd = window_cmd or [
        sys.executable, os.path.abspath(__file__), "--window", out_dir,
        "--window-rows", str(int(window_rows))]
    out(f"capture: running window stage: {' '.join(map(str, window_cmd))}")
    win = run_stage(window_cmd, timeout_s=stage_timeout_s)
    window = win["parsed"]
    summary["window_rc"] = win["rc"]
    if win["rc"] != 0 or not isinstance(window, dict):
        summary["window_error"] = win["tail"][-2000:]
        return summary
    summary["window"] = window
    art_dir = window["artifact_dir"]

    env = dict(os.environ)
    env["LGBMV1_OBS_DIR"] = art_dir
    env.setdefault("LGBMV1_RUN_ID", f"capture_r{n:02d}")

    # 2. bench (all blocks) + the smoke battery (entry/serve/chaos/dryrun)
    bench_cmd = bench_cmd or [sys.executable, "bench.py"]
    smoke_cmd = smoke_cmd or [sys.executable, "__graft_entry__.py"]
    out(f"capture: running bench stage: {' '.join(map(str, bench_cmd))}")
    bench = run_stage(bench_cmd, env=env, timeout_s=stage_timeout_s)
    out(f"capture: bench rc={bench['rc']} in {bench['seconds']}s")
    out(f"capture: running smoke stage: {' '.join(map(str, smoke_cmd))}")
    smoke = run_stage(smoke_cmd, env=env, timeout_s=stage_timeout_s)
    out(f"capture: smokes rc={smoke['rc']} in {smoke['seconds']}s")
    summary["bench_rc"] = bench["rc"]
    summary["smoke_rc"] = smoke["rc"]

    # 3. merge every artifact + the device capture into one trace
    from lightgbmv1_tpu.obs import agg as obs_agg

    agg_summary = obs_agg.aggregate_dir(
        art_dir, profile_dir=window["profile_dir"])
    try:
        summary["merged_trace"] = validate_merged_trace(
            agg_summary["merged_trace"])
        summary["merged_trace"]["path"] = agg_summary["merged_trace"]
        trace_ok = True
    except ValueError as e:
        summary["merged_trace_error"] = str(e)
        trace_ok = False
    summary["device_lanes"] = agg_summary.get("device_lanes", 0)

    # 4. emit the records in the captured format
    def write_record(name: str, stage: dict) -> str:
        path = os.path.join(rec_out, name)
        doc = {"n": n, "cmd": stage["cmd"], "rc": stage["rc"],
               "tail": stage["tail"]}
        if stage.get("parsed") is not None:
            doc["parsed"] = stage["parsed"]
        with open(path, "w") as fh:
            json.dump(doc, fh, indent=1)
        return path

    summary["bench_record"] = write_record(f"BENCH_r{n:02d}.json", bench)
    summary["multichip_record"] = write_record(
        f"MULTICHIP_r{n:02d}.json", smoke)

    # 5. gate: trend + required guards (+ tier-1 budget when a log exists)
    gate = ci_gate.run_gate(
        rec_out, t1_log, skip_t1=skip_t1,
        require_guards=ci_gate.REQUIRED_GUARDS, out=out)
    summary["gate"] = gate
    summary["ok"] = bool(bench["rc"] == 0 and smoke["rc"] == 0
                         and trace_ok and gate["ok"])
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--records-dir", default=ROOT,
                    help="where existing records live (round numbering + "
                         "trend priors)")
    ap.add_argument("--out", default=None,
                    help="capture artifact directory (default: a temp dir)")
    ap.add_argument("--round", type=int, default=None,
                    help="force the record round number")
    ap.add_argument("--dry-run", action="store_true",
                    help="rehearsal: records into a scratch dir, gated in "
                         "isolation; the repo's records are untouched")
    ap.add_argument("--t1-log", default="/tmp/_t1.log")
    ap.add_argument("--with-t1", action="store_true",
                    help="also enforce the tier-1 wall budget guard "
                         "(requires --t1-log from a suite run)")
    ap.add_argument("--window-rows", type=int, default=4096)
    ap.add_argument("--window", metavar="OUT_DIR", default=None,
                    help="stage mode: run ONLY the profiled training "
                         "window into OUT_DIR and print its summary as "
                         "the last line (run_capture spawns this)")
    ap.add_argument("--stage-timeout-s", type=float, default=7200.0)
    args = ap.parse_args(argv)
    if args.window:
        print(json.dumps(profiled_window(args.window,
                                         rows=args.window_rows)))
        return 0
    summary = run_capture(
        records_dir=args.records_dir, out_dir=args.out,
        round_no=args.round, dry_run=args.dry_run,
        skip_t1=not args.with_t1, t1_log=args.t1_log,
        window_rows=args.window_rows,
        stage_timeout_s=args.stage_timeout_s)
    print(json.dumps(summary, default=str))
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
