"""Cross-process telemetry aggregation — N processes, one timeline.

A fleet run is never one process: the loadgen CLI drives a serve CLI,
the ``dist_data``/multihost tests spawn worker subprocesses, and
ROADMAP item 1's real multi-host training will be N trainer processes
per pod.  Each process exports its OWN artifacts (trace ring, metrics
snapshot, event tail) because a dying process cannot be asked to
coordinate; this module is the offline half that merges them back into
one picture:

* **One Perfetto trace, pid lanes.**  Each per-process Chrome export
  carries a wall-clock anchor (``otherData.t0_unix_ns`` — the wall
  instant its relative ``ts=0`` corresponds to, recorded at ``arm()``)
  plus its identity.  The merger rebases every process onto the
  earliest anchor and assigns each artifact a distinct lane pid with a
  ``process_name`` metadata record (``role host:pid``), so Perfetto
  renders the server's dispatch batches directly under the loadgen's
  request spans on a shared time axis.
* **One merged metrics snapshot.**  Per-process snapshots are kept
  verbatim under ``processes`` and additively merged under ``merged``:
  ``*_total`` / ``*_count`` / ``*_sum`` keys sum across processes (the
  Prometheus aggregation rule), ``*_max`` keys take the max; everything
  else is inherently per-process and stays only there.
* **One event log.**  Structured event tails interleave by wall clock —
  the cross-process "what happened in what order" a post-mortem starts
  from.

Inputs are the artifact files :func:`export_process_artifacts` writes
(``<label>.trace.json`` / ``<label>.metrics.json`` /
``<label>.events.jsonl``) and — because a crashed process leaves a
forensic bundle instead of a clean export — ``crash-*.zip`` bundles
(obs/dump.py), whose members are pulled in the same way.  CLI driver:
``tools/obs_aggregate.py``.

ISSUE 12 adds the **device lane**: a ``jax.profiler`` capture directory
(``profile_dir`` / the ``tools/capture.py`` harness) is ingested as one
more trace source per ``*.trace.json(.gz)`` it holds, rebased onto the
shared wall axis via the ``profile.anchor.json`` sidecar obs/xla.py
writes at ``start_trace``.  That anchor is the CROSS-PROCESS fallback:
inside the capturing process the program's own per-tree spans
(``train.*`` / ``data.*``, obs/trace.py ``bridged_span``) are already in
the profiler's host lane on the device ops' clock, and that is the
alignment to read a tree's phases against its device time by.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Tuple

from . import events as obs_events
from . import trace as obs_trace

TRACE_SUFFIX = ".trace.json"
METRICS_SUFFIX = ".metrics.json"
EVENTS_SUFFIX = ".events.jsonl"
MERGED_TRACE = "merged.trace.json"
MERGED_METRICS = "merged.metrics.json"


def _safe_label(s: str) -> str:
    return "".join(c if c.isalnum() or c in "-_." else "_" for c in s)


def process_label(identity: Optional[dict] = None) -> str:
    ident = identity or obs_events.identity()
    return _safe_label(
        f"{ident.get('role', 'proc')}-{ident.get('host', '?')}-"
        f"{ident.get('pid', 0)}")


def export_process_artifacts(out_dir: str,
                             label: Optional[str] = None,
                             registry=None) -> Dict[str, str]:
    """Write THIS process's trace/metrics/events artifacts into
    ``out_dir`` (atomic writes; safe under a concurrent aggregator).
    ``registry`` defaults to the process-wide default registry; a serve
    replica passes its own.  Returns ``{kind: path}``."""
    from ..utils import fileio
    from .metrics import default_registry

    os.makedirs(str(out_dir), exist_ok=True)
    label = _safe_label(label) if label else process_label()
    reg = registry if registry is not None else default_registry()
    paths = {}

    tp = os.path.join(str(out_dir), label + TRACE_SUFFIX)
    fileio.atomic_write_bytes(
        tp, json.dumps(obs_trace.export_chrome()).encode("utf-8"),
        site="obs_artifact")
    paths["trace"] = tp

    mp = os.path.join(str(out_dir), label + METRICS_SUFFIX)
    fileio.atomic_write_bytes(
        mp, json.dumps({"identity": obs_events.identity(),
                        "snapshot": reg.snapshot()},
                       sort_keys=True, default=str).encode("utf-8"),
        site="obs_artifact")
    paths["metrics"] = mp

    ep = os.path.join(str(out_dir), label + EVENTS_SUFFIX)
    fileio.atomic_write_bytes(
        ep, obs_events.to_jsonl(obs_events.tail()).encode("utf-8"),
        site="obs_artifact")
    paths["events"] = ep
    return paths


# ---------------------------------------------------------------------------
# device lane: jax.profiler capture ingestion
# ---------------------------------------------------------------------------

def load_profiler_traces(profile_dir: str) -> List[Tuple[str, dict]]:
    """``[(label, chrome_doc)]`` from a ``jax.profiler`` capture
    directory: every ``*.trace.json(.gz)`` under ``plugins/profile/``
    (or directly in the directory) becomes one device-lane source,
    anchored by the ``profile.anchor.json`` sidecar when present so the
    merger can rebase it onto the shared wall-clock axis."""
    import glob as _glob
    import gzip

    from . import xla as obs_xla

    profile_dir = str(profile_dir)
    anchor = obs_xla.read_anchor(profile_dir) or {}
    ident = anchor.get("identity") or {}
    paths = sorted(
        _glob.glob(os.path.join(profile_dir, "plugins", "profile", "*",
                                "*.trace.json.gz"))
        + _glob.glob(os.path.join(profile_dir, "plugins", "profile", "*",
                                  "*.trace.json"))
        + _glob.glob(os.path.join(profile_dir, "*.trace.json.gz")))
    docs: List[Tuple[str, dict]] = []
    for path in paths:
        try:
            if path.endswith(".gz"):
                with gzip.open(path, "rt") as fh:
                    doc = json.load(fh)
            else:
                with open(path) as fh:
                    doc = json.load(fh)
        except (OSError, ValueError) as e:
            from ..utils.log import log_warning

            log_warning(f"obs/agg: skipping unreadable profiler trace "
                        f"{path} ({type(e).__name__}: {e})")
            continue
        if not isinstance(doc, dict) or "traceEvents" not in doc:
            continue
        # the profiler's host lane interleaves a python-interpreter frame
        # event (``$file:line fn``) for nearly every call — megabytes of
        # noise per second of capture that drowns the XLA op rows the
        # device lane exists for.  Drop the interpreter frames, keep
        # everything else (XLA ops, TraceAnnotations, metadata).
        kept = [e for e in doc["traceEvents"]
                if not (e.get("ph") == "X"
                        and str(e.get("name", "")).startswith("$"))]
        dropped_frames = len(doc["traceEvents"]) - len(kept)
        doc["traceEvents"] = kept
        other = dict(doc.get("otherData") or {})
        if dropped_frames:
            other["python_frames_dropped"] = dropped_frames
        other.setdefault("t0_unix_ns", anchor.get("t0_unix_ns"))
        other.setdefault("role", "device")
        other.setdefault("host", ident.get("host", "?"))
        other.setdefault("pid", ident.get("pid", 0))
        other.setdefault("run_id", ident.get("run_id"))
        other.setdefault("exporter", "jax.profiler")
        doc["otherData"] = other
        stem = os.path.basename(path).split(".trace.json")[0]
        docs.append(("device-" + _safe_label(stem), doc))
    return docs


# ---------------------------------------------------------------------------
# merging
# ---------------------------------------------------------------------------


def merge_trace_docs(docs: List[Tuple[str, dict]]) -> dict:
    """Merge ``[(label, chrome_doc)]`` into one Chrome trace document.

    Each source gets lane pid ``i+1`` (distinct even when two artifacts
    came from the same OS pid — e.g. two roles of one process) plus a
    ``process_name`` metadata event; timestamps are rebased onto the
    earliest wall-clock anchor so the lanes share one time axis.
    Sources without an anchor (foreign traces) keep their own zero."""
    anchors = []
    for _, doc in docs:
        t0 = (doc.get("otherData") or {}).get("t0_unix_ns")
        if isinstance(t0, (int, float)) and t0 > 0:
            anchors.append(t0)
    base = min(anchors) if anchors else 0
    merged: List[dict] = []
    sources = []
    dropped = 0
    for i, (label, doc) in enumerate(docs):
        lane = i + 1
        other = doc.get("otherData") or {}
        t0 = other.get("t0_unix_ns")
        shift_us = ((t0 - base) / 1e3
                    if isinstance(t0, (int, float)) and t0 > 0 and base
                    else 0.0)
        dropped += int(other.get("dropped_events", 0) or 0)
        name = (f"{other.get('role', label)} "
                f"{other.get('host', '?')}:{other.get('pid', '?')}"
                if other.get("role") else label)
        merged.append({"name": "process_name", "ph": "M", "pid": lane,
                       "tid": 0, "args": {"name": name}})
        merged.append({"name": "process_sort_index", "ph": "M",
                       "pid": lane, "tid": 0, "args": {"sort_index": i}})
        for ev in doc.get("traceEvents", []):
            ev = dict(ev)
            ev["pid"] = lane
            if "ts" in ev:
                ev["ts"] = ev["ts"] + shift_us
            merged.append(ev)
        sources.append({"label": label, "lane": lane,
                        "host": other.get("host"),
                        "pid": other.get("pid"),
                        "role": other.get("role"),
                        "run_id": other.get("run_id"),
                        "t0_unix_ns": t0,
                        "events": len(doc.get("traceEvents", []))})
    return {
        "traceEvents": merged,
        "displayTimeUnit": "ms",
        "otherData": {
            "exporter": "lightgbmv1_tpu.obs.agg",
            "merged_from": len(docs),
            "dropped_events": dropped,
            "t0_unix_ns": base,
            "sources": sources,
        },
    }


_SUM_SUFFIXES = ("_total", "_count", "_sum")
_MAX_SUFFIXES = ("_max",)


def merge_metrics_snapshots(snaps: Dict[str, dict]) -> dict:
    """``{label: snapshot}`` -> ``{"processes": ..., "merged": ...}``.
    Only additively-meaningful keys merge (see module docstring); the
    base name (before any ``{label=...}`` suffix) decides the rule."""
    merged: Dict[str, float] = {}
    for snap in snaps.values():
        for key, val in (snap or {}).items():
            if not isinstance(val, (int, float)) or isinstance(val, bool):
                continue
            base = key.split("{", 1)[0]
            if base.endswith(_SUM_SUFFIXES):
                merged[key] = merged.get(key, 0) + val
            elif base.endswith(_MAX_SUFFIXES):
                merged[key] = max(merged.get(key, val), val)
    return {"processes": dict(snaps), "merged": merged}


def merge_event_lists(lists: List[List[dict]]) -> List[dict]:
    """Interleave per-process event tails by wall clock (seq breaks
    ties within a process)."""
    flat = [e for lst in lists for e in lst]
    flat.sort(key=lambda e: (e.get("t_wall", 0), e.get("pid", 0),
                             e.get("seq", 0)))
    return flat


# ---------------------------------------------------------------------------
# directory scan + one-call aggregation
# ---------------------------------------------------------------------------


def load_artifact_dir(art_dir: str) -> dict:
    """Scan a directory for per-process artifacts AND forensic bundles;
    returns ``{"traces": [(label, doc)], "metrics": {label: snap},
    "events": [[...], ...]}`` (merged outputs of a previous run are
    skipped)."""
    traces: List[Tuple[str, dict]] = []
    metrics: Dict[str, dict] = {}
    event_lists: List[List[dict]] = []
    art_dir = str(art_dir)
    for name in sorted(os.listdir(art_dir)):
        path = os.path.join(art_dir, name)
        if name in (MERGED_TRACE, MERGED_METRICS):
            continue
        try:
            if name.endswith(TRACE_SUFFIX):
                with open(path) as fh:
                    traces.append((name[: -len(TRACE_SUFFIX)],
                                   json.load(fh)))
            elif name.endswith(METRICS_SUFFIX):
                with open(path) as fh:
                    doc = json.load(fh)
                label = name[: -len(METRICS_SUFFIX)]
                metrics[label] = doc.get("snapshot", doc)
            elif name.endswith(EVENTS_SUFFIX):
                with open(path) as fh:
                    event_lists.append(obs_events.from_jsonl(fh.read()))
            elif name.startswith("crash-") and name.endswith(".zip"):
                from . import dump

                bundle = dump.read_bundle(path)
                ident = bundle["manifest"].get("identity", {})
                label = "crash-" + process_label(ident)
                traces.append((label, bundle["trace.json"]))
                snap = bundle["metrics.json"]
                metrics[label] = snap.get("default", snap)
                event_lists.append(bundle["events.jsonl"])
        except (OSError, ValueError, KeyError) as e:
            # a torn artifact from a crashed writer: skip loudly, merge
            # the rest — forensics must degrade, not fail closed
            from ..utils.log import log_warning

            log_warning(f"obs_aggregate: skipping unreadable artifact "
                        f"{path} ({type(e).__name__}: {e})")
    return {"traces": traces, "metrics": metrics, "events": event_lists}


def aggregate_dir(art_dir: str, out_trace: Optional[str] = None,
                  out_metrics: Optional[str] = None,
                  profile_dir: Optional[str] = None) -> dict:
    """One-call aggregation: scan ``art_dir``, merge, optionally write
    ``merged.trace.json`` / ``merged.metrics.json`` (defaults inside
    ``art_dir``), return a summary dict.  ``profile_dir`` additionally
    ingests a ``jax.profiler`` capture as device lane(s)."""
    from ..utils import fileio

    arts = load_artifact_dir(art_dir)
    traces = list(arts["traces"])
    if profile_dir:
        traces.extend(load_profiler_traces(profile_dir))
    trace_doc = merge_trace_docs(traces)
    metrics_doc = merge_metrics_snapshots(arts["metrics"])
    merged_events = merge_event_lists(arts["events"])
    out_trace = out_trace or os.path.join(str(art_dir), MERGED_TRACE)
    out_metrics = out_metrics or os.path.join(str(art_dir),
                                              MERGED_METRICS)
    fileio.atomic_write_bytes(
        out_trace, json.dumps(trace_doc).encode("utf-8"),
        site="obs_merged")
    fileio.atomic_write_bytes(
        out_metrics,
        json.dumps({**metrics_doc, "events": merged_events},
                   sort_keys=True, default=str).encode("utf-8"),
        site="obs_merged")
    lanes = {e["pid"] for e in trace_doc["traceEvents"]
             if e.get("ph") == "X"}
    device_lanes = {s["lane"] for s in trace_doc["otherData"]["sources"]
                    if s.get("role") == "device"}
    return {
        "sources": [s["label"] for s in
                    trace_doc["otherData"]["sources"]],
        "lanes": len(lanes),
        "device_lanes": len(device_lanes & lanes),
        "trace_events": sum(1 for e in trace_doc["traceEvents"]
                            if e.get("ph") == "X"),
        "merged_events": len(merged_events),
        "metrics_processes": sorted(metrics_doc["processes"]),
        "merged_trace": out_trace,
        "merged_metrics": out_metrics,
    }
