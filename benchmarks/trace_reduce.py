"""From a JAX profiler trace to per-device op lists, busy time and gaps.

Reads the ``*.trace.json.gz`` the profiler writes beside its ``.xplane.pb``
(the JSON carries each device op's ``tf_op``: the ``jax.named_scope`` path
the readers match on, which ``jax.profiler.ProfileData`` does not expose).
What a TPU trace holds (``benchmarks/tools/trace_dump.py`` shows it):

  process ``/device:TPU:<i>``   thread ``XLA Ops``: one event per executed
      HLO op; ``while`` / ``conditional`` / ``call`` events *contain* the
      ops of their bodies, so times are summed as **self time** (an event's
      duration less the events nested in it);
  process ``/host:CPU``         host threads: Python frames, PJRT calls and
      the harness's own ``TraceAnnotation`` spans, on the same clock.

All times here are seconds.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast)")
_CONTAINER = ("while", "conditional", "call")


@dataclass
class Op:
    name: str          # HLO op name, e.g. hist_leaves_pallas.7
    start: float
    dur: float
    self_s: float
    scope: str         # tf_op: jit(step)/jit(grow)/.../lgbm.hist/...
    category: str
    long_name: str

    @property
    def base(self) -> str:
        """Name without its numeric suffixes: ``fusion.190`` -> ``fusion``."""
        return re.sub(r"(\.\d+)+$", "", self.name.lstrip("%"))

    @property
    def is_collective(self) -> bool:
        return bool(_COLLECTIVE.match(self.base))

    @property
    def is_container(self) -> bool:
        return self.category in _CONTAINER or self.base in _CONTAINER


@dataclass
class DeviceTrace:
    ops: List[Op] = field(default_factory=list)
    busy_s: float = 0.0
    busy_intervals: List[Tuple[float, float]] = field(default_factory=list)


@dataclass
class Trace:
    devices: Dict[str, DeviceTrace]
    host: List[Tuple[str, float, float]]     # (name, start, dur)

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices traced."""
        if not self.devices:
            return 0.0
        return sum(d.busy_s for d in self.devices.values()) / len(self.devices)


def find_trace_json(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.trace.json.gz"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no *.trace.json.gz under {path}")
    return found[-1]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _self_times(events: List[dict]) -> List[float]:
    """Duration of each event less the events nested in it (one thread's
    events, which nest but do not otherwise overlap)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i]["ts"], -events[i]["dur"]))
    self_t = [e["dur"] for e in events]
    stack: List[int] = []
    for i in order:
        e = events[i]
        while stack and (events[stack[-1]]["ts"] + events[stack[-1]]["dur"]
                         <= e["ts"]):
            stack.pop()
        if stack:
            self_t[stack[-1]] -= e["dur"]
        stack.append(i)
    return [max(t, 0.0) for t in self_t]


def load(path: str) -> Trace:
    """Parse a trace; ``path`` is the JSON file or a directory holding it."""
    with gzip.open(find_trace_json(path), "rt") as fh:
        doc = json.load(fh)
    return reduce_events(doc["traceEvents"])


def reduce_events(events: List[dict]) -> Trace:
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("name") == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    per_dev: Dict[str, List[dict]] = {}
    host: List[Tuple[str, float, float]] = []
    for e in events:
        if e.get("ph") != "X":
            continue
        proc = procs.get(e.get("pid"), "")
        if proc.startswith("/device:TPU:"):
            if threads.get((e["pid"], e.get("tid"))) == "XLA Ops":
                per_dev.setdefault(proc, []).append(e)
        elif proc.startswith("/host:"):
            host.append((e["name"], e["ts"] * 1e-6, e.get("dur", 0.0) * 1e-6))
    devices = {}
    for proc, evs in sorted(per_dev.items()):
        self_t = _self_times(evs)
        dev = DeviceTrace()
        for e, st in zip(evs, self_t):
            args = e.get("args", {})
            dev.ops.append(Op(
                name=e["name"], start=e["ts"] * 1e-6, dur=e["dur"] * 1e-6,
                self_s=st * 1e-6, scope=args.get("tf_op", ""),
                category=args.get("hlo_category", ""),
                long_name=(args.get("long_name", "")
                           or args.get("shape_with_layout", ""))))
        dev.busy_intervals = union([(o.start, o.start + o.dur)
                                    for o in dev.ops if o.dur > 0])
        dev.busy_s = sum(b - a for a, b in dev.busy_intervals)
        devices[proc] = dev
    return Trace(devices, sorted(host, key=lambda h: h[1]))


# -- sums the readers use ---------------------------------------------------

def _unwrap(part: str) -> str:
    """``vmap(lgbm.split)`` -> ``lgbm.split``: a transform wraps the scope's
    name where the scoped code runs under it."""
    while part.endswith(")") and "(" in part and not part.startswith("jit("):
        part = part[part.index("(") + 1:-1]
    return part


def scope_of(op: Op, scopes: List[str]) -> Optional[str]:
    """The first of ``scopes`` that is a path component of the op's scope,
    bare or wrapped by a transform (``vmap(<scope>)``)."""
    parts = {_unwrap(p) for p in op.scope.split("/")}
    for s in scopes:
        if s in parts:
            return s
    return None


def self_seconds(trace: Trace, pick) -> float:
    """Self time of the ops ``pick`` accepts, averaged over devices."""
    if not trace.devices:
        return 0.0
    tot = sum(o.self_s for d in trace.devices.values() for o in d.ops
              if pick(o))
    return tot / len(trace.devices)


def exposed_collective_seconds(trace: Trace) -> Optional[float]:
    """Device time of collective ops during which no compute op runs on
    that device, averaged over devices; None when the trace has none."""
    per_dev, seen = [], False
    for d in trace.devices.values():
        coll = union([(o.start, o.start + o.dur) for o in d.ops
                      if o.is_collective and o.dur > 0])
        if coll:
            seen = True
        comp = union([(o.start, o.start + o.dur) for o in d.ops
                      if not o.is_collective and not o.is_container
                      and o.dur > 0])
        covered = 0.0
        j = 0
        for a, b in coll:
            while j < len(comp) and comp[j][1] <= a:
                j += 1
            k = j
            while k < len(comp) and comp[k][0] < b:
                covered += min(b, comp[k][1]) - max(a, comp[k][0])
                k += 1
        per_dev.append(sum(b - a for a, b in coll) - covered)
    if not seen:
        return None
    return sum(per_dev) / len(per_dev)


def top_device_ops(trace: Trace, scopes: List[str], n: int = 10):
    """[name, seconds] of the ops that took most self time, named
    ``<scope>/<op base name>`` as PR 22's breakdown was."""
    agg: Dict[str, float] = {}
    for d in trace.devices.values():
        for o in d.ops:
            if o.is_container:
                continue
            s = scope_of(o, scopes)
            if s is None:
                parts = [p for p in o.scope.split("/")
                         if p.startswith("jit(")]
                s = parts[0][4:-1] if parts else ""
            key = f"{s}/{o.base}" if s else o.base
            agg[key] = agg.get(key, 0.0) + o.self_s
    k = max(len(trace.devices), 1)
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec / k] for name, sec in top]


def top_idle_gaps(trace: Trace, n: int = 10, min_gap_s: float = 2e-6):
    """[host span, seconds]: the device's idle gaps, each charged to the
    host event that overlaps it most (the shortest such event on a tie, so
    the innermost frame), summed by name.  First device only: one host
    drives them all."""
    if not trace.devices:
        return []
    dev = next(iter(trace.devices.values()))
    iv = dev.busy_intervals
    gaps = [(iv[i][1], iv[i + 1][0]) for i in range(len(iv) - 1)
            if iv[i + 1][0] - iv[i][1] >= min_gap_s]
    host = [h for h in trace.host if h[2] > 0]
    agg: Dict[str, float] = {}
    lo = 0
    for a, b in gaps:
        while lo < len(host) and host[lo][1] + host[lo][2] < a - 5.0:
            lo += 1
        best, best_key = None, None
        for name, s, d in host[lo:]:
            if s >= b:
                break
            ov = min(b, s + d) - max(a, s)
            if ov <= 0:
                continue
            key = (ov, -d)
            if best_key is None or key > best_key:
                best, best_key = name, key
        name = best or "(no host event)"
        agg[name] = agg.get(name, 0.0) + (b - a)
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec] for name, sec in top]
