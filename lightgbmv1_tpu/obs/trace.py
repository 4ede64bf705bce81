"""Low-overhead nested-span tracer with Chrome trace-event export.

Two kinds of span, one ring:

* **``span()`` — per-request and per-block sites** (``serve.*``,
  ``stream.*``).  Hard-off by default: it checks ONE module-level flag
  and returns a shared no-op context manager when tracing is disarmed —
  no dict, no object, no clock read is allocated on the off path
  (tests/test_obs.py pins the zero-allocation property with
  tracemalloc).  Hot paths that want to skip even argument construction
  guard with ``trace.enabled()``.
* **``bridged_span()`` — per-tree and coarser sites** (``train.*``,
  ``data.*``).  It ALWAYS enters a ``jax.profiler.TraceAnnotation`` of
  the same name, which the runtime drops while no profiler session is
  open: a ``jax.profiler.start_trace`` started by anyone (a benchmark's
  ``--trace 1``, ``profile_dir=``, an operator) then holds the program's
  spans in its host lane, on the same clock as the device ops, with no
  arming by the caller.  Armed, it also records into the ring like
  ``span()``.  A few microseconds a span with no session open
  (tests/test_train_spans.py holds a ceiling on it).
* **One record a tree, always on.**  ``iteration_span()`` is the
  bridged ``train.iteration`` span ``Booster.update`` runs a tree under;
  the ``phase_span()`` children inside it (``train.prepare`` /
  ``train.dispatch`` / ``train.bookkeep`` / ``train.wait``) carry the
  tree's ``iteration`` and add their time to it, and when it closes it
  writes ONE tuple of ten fields ``(iteration, t0_ns, prepare_ns,
  dispatch_ns, bookkeep_ns, wait_ns, total_ns, renewed, rounds,
  hist_skipped)`` into a bounded ring (``iteration_records()``): which
  host phase a slow tree's extra milliseconds passed in, without a
  profiler and without arming.
  It is host time: where the runtime blocks the host in a phase until
  the device is done (a TPU does, in ``bookkeep``; ``GBDT._stopped``),
  that phase holds the device's time too, and the record cannot tell
  them apart.  The last three fields are the device's own counts, read
  back when the record is: ``renewed`` (models/renew.py), ``rounds``,
  the rounds the iteration's trees ran in each slot bucket of the wave
  grower (``(b4, b16, bK)``): a tree that took one more round reads so
  here, a tree the host stalled on reads the same rounds; and
  ``hist_skipped``, those of the rounds that ran no histogram pass
  because no child of theirs could be split (a tree's last round where
  it spends its leaves), so ``sum(rounds) - hist_skipped`` plus one a
  tree for the root is the iteration's histogram passes.

Common to both:

* **Monotonic clocks.**  All timestamps are ``time.perf_counter_ns()``
  — immune to wall-clock steps; the export rebases to the arm instant.
* **Thread-local span stack.**  Nesting needs no global coordination;
  concurrent serving threads trace independently and the export keys
  events by OS thread id, which is exactly how Perfetto lanes them.
* **Ring-buffered events.**  A fixed-capacity ring (``arm(ring_events=
  ...)``) overwrites the OLDEST events under sustained load — tracing
  can be left armed on a serving replica without unbounded growth; the
  export reports how many events were dropped.
* **Trace ids.**  ``new_trace_id()`` mints a 16-hex-char id; the serving
  path propagates it request -> admission queue -> micro-batch ->
  predictor walk -> ``X-Trace-Id`` response header, so one p999 outlier
  decomposes into its queue / batch / walk spans by grepping the id in
  the exported trace.

Export is the Chrome trace-event JSON format (``{"traceEvents": [...]}``
of ``"ph": "X"`` complete events) — open the file at https://ui.perfetto.dev
or chrome://tracing.

What runs inside one jitted dispatch (objective / sample / grower phases
/ score update) the host cannot see; it is on the device lane of a
profiler trace under the ``lgbm.*`` named scopes, never invented here.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from ..utils.timer import global_timer

DEFAULT_RING_EVENTS = 65536
ITERATION_RING = 4096           # always-on per-tree records kept
ITERATION_PHASES = ("prepare", "dispatch", "bookkeep", "wait")

_armed = False                  # THE hot-path flag: checked once per span
_lock = threading.Lock()        # guards the ring and arm/disarm
_ring: List[tuple] = []         # (name, cat, t0_ns, dur_ns, tid, args)
_ring_cap = DEFAULT_RING_EVENTS
_ring_pos = 0                   # next slot when the ring has wrapped
_dropped = 0
_t_arm_ns = 0                   # export rebases timestamps to this
_t_arm_unix_ns = 0              # wall-clock anchor of the SAME instant —
                                # the cross-process alignment key agg.py
                                # merges timelines on
# (iteration, t0_ns, prepare_ns, dispatch_ns, bookkeep_ns, wait_ns,
# total_ns, renewed, rounds), one a tree, armed or not
_iterations: collections.deque = collections.deque(maxlen=ITERATION_RING)

_tls = threading.local()


def enabled() -> bool:
    """True while the tracer is armed (the off path is one global read)."""
    return _armed


def arm(ring_events: int = DEFAULT_RING_EVENTS) -> None:
    """Arm the tracer with a fresh ring of ``ring_events`` capacity."""
    global _armed, _ring, _ring_cap, _ring_pos, _dropped, _t_arm_ns, \
        _t_arm_unix_ns
    with _lock:
        _ring = []
        _ring_cap = max(int(ring_events), 16)
        _ring_pos = 0
        _dropped = 0
        # the two clocks are read back to back: the pair (monotonic,
        # wall) anchors this process's relative timestamps onto the
        # shared wall-clock axis for cross-process merging
        _t_arm_ns = time.perf_counter_ns()
        _t_arm_unix_ns = time.time_ns()
        _armed = True


def disarm() -> None:
    global _armed
    _armed = False


def reset() -> None:
    """Disarm and drop all buffered events and iteration records."""
    global _armed, _ring, _ring_pos, _dropped
    with _lock:
        _armed = False
        _ring = []
        _ring_pos = 0
        _dropped = 0
        _iterations.clear()


def _record(name: str, cat: str, t0_ns: int, dur_ns: int,
            args: Optional[dict]) -> None:
    global _ring_pos, _dropped
    ev = (name, cat, t0_ns, dur_ns, threading.get_ident(), args)
    with _lock:
        if len(_ring) < _ring_cap:
            _ring.append(ev)
        else:
            _ring[_ring_pos] = ev
            _ring_pos = (_ring_pos + 1) % _ring_cap
            _dropped += 1


class _NoopSpan:
    """Shared do-nothing context manager: the disarmed ``span()`` return
    value.  A singleton, so the off path allocates nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


class _Span:
    __slots__ = ("name", "cat", "args", "t0")

    def __init__(self, name: str, cat: str, args: Optional[dict]):
        self.name = name
        self.cat = cat
        self.args = args
        self.t0 = 0

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self._finish(time.perf_counter_ns() - self.t0)
        return False

    def _finish(self, dur_ns: int) -> None:
        stack = getattr(_tls, "stack", None)
        if stack and stack[-1] is self:
            stack.pop()
        if _armed:   # disarmed mid-span: drop, never crash
            tid = current_trace_id()
            args = self.args
            if tid is not None:
                args = dict(args) if args else {}
                args["trace_id"] = tid
            _record(self.name, self.cat, self.t0, dur_ns, args)


def span(name: str, cat: str = "app", args: Optional[dict] = None):
    """Context manager timing a nested span.  ``args`` is an optional
    dict rendered into the Chrome event (pass a literal dict only when
    armed-path cost is acceptable; the disarmed call allocates nothing)."""
    if not _armed:
        return _NOOP
    return _Span(name, cat, args)


def depth() -> int:
    """Current thread's span-nesting depth (tests / debugging)."""
    stack = getattr(_tls, "stack", None)
    return len(stack) if stack else 0


def add_span(name: str, t0_ns: int, dur_ns: int, cat: str = "app",
             args: Optional[dict] = None) -> None:
    """Record a span measured elsewhere (retro-recording: the serving
    dispatcher records each request's queue wait AFTER the batch is
    collected, from timestamps it already holds)."""
    if not _armed:
        return
    _record(name, cat, int(t0_ns), max(int(dur_ns), 0), args)


def instant(name: str, cat: str = "app", args: Optional[dict] = None) -> None:
    """Zero-duration marker event."""
    if not _armed:
        return
    _record(name, cat, time.perf_counter_ns(), 0, args)


def now_ns() -> int:
    return time.perf_counter_ns()


# ---------------------------------------------------------------------------
# trace ids (request-scoped correlation, independent of arming)
# ---------------------------------------------------------------------------

def new_trace_id() -> str:
    """16 hex chars from the OS entropy pool — unique per request at any
    realistic request rate, cheap enough to mint unconditionally."""
    return os.urandom(8).hex()


def set_trace_id(trace_id: Optional[str]) -> None:
    """Bind ``trace_id`` to the current thread; spans recorded while
    bound carry it in their args.  ``None`` clears."""
    _tls.trace_id = trace_id


def current_trace_id() -> Optional[str]:
    return getattr(_tls, "trace_id", None)


# ---------------------------------------------------------------------------
# bridged spans (per-tree and coarser) and the always-on per-tree record
# ---------------------------------------------------------------------------

class _BridgedSpan(_Span):
    """A span that also lives in the JAX profiler's host lane (see the
    module docstring).  ``dur_ns`` holds its time after exit."""

    __slots__ = ("dur_ns", "_ann")

    def __enter__(self):
        self._ann = TraceAnnotation(self.name, **(self.args or {}))
        self._ann.__enter__()
        return super().__enter__()

    def __exit__(self, *exc):
        self.dur_ns = time.perf_counter_ns() - self.t0
        self._ann.__exit__(*exc)
        self._finish(self.dur_ns)
        if global_timer.enabled:
            global_timer.totals[self.name] += self.dur_ns / 1e9
            global_timer.counts[self.name] += 1
        return False


def bridged_span(name: str, cat: str = "app",
                 args: Optional[dict] = None) -> _BridgedSpan:
    """Context manager for a per-tree or coarser site: always a
    ``jax.profiler.TraceAnnotation`` (dropped by the runtime while no
    profiler session is open), a ring event when armed, a
    ``global_timer`` section when that is enabled.  ``args`` become the
    annotation's and the event's arguments."""
    return _BridgedSpan(name, cat, args)


class _IterationSpan(_BridgedSpan):
    """``train.iteration``: the bridged span one tree runs under, which
    its ``phase_span`` children add their time to."""

    __slots__ = ("iteration", "phase_ns", "renewed", "rounds",
                 "hist_skipped", "_outer")

    def __init__(self, iteration: int):
        super().__init__("train.iteration", "train",
                         {"iteration": iteration})
        self.iteration = iteration
        self.phase_ns = dict.fromkeys(ITERATION_PHASES, 0)
        # nodes and leaves of this iteration's trees whose stored sums were
        # measured again from the rows (models/renew.py): a number, or a
        # callable that gives it when the record is read, so that writing
        # the record never waits for the device
        self.renewed = None
        # rounds the iteration's trees ran in each slot bucket of the wave
        # grower (models/grower_wave.py), likewise a tuple or a callable;
        # and how many of those rounds ran no histogram pass
        self.rounds = None
        self.hist_skipped = None

    def __enter__(self):
        self._outer = getattr(_tls, "iteration", None)
        _tls.iteration = self
        return super().__enter__()

    def __exit__(self, *exc):
        super().__exit__(*exc)
        _tls.iteration = self._outer
        # the one place an iteration is written down, armed or not
        _iterations.append((self.iteration, self.t0,
                            *(self.phase_ns[p] for p in ITERATION_PHASES),
                            self.dur_ns, _Later(self.renewed),
                            _Later(self.rounds), _Later(self.hist_skipped)))
        return False


class _Later:
    """A value, or a callable resolved once, on first read."""
    __slots__ = ("_v",)

    def __init__(self, v):
        self._v = v

    def get(self):
        if callable(self._v):
            self._v = self._v()
        return self._v


class _PhaseSpan(_BridgedSpan):
    __slots__ = ("phase", "owner")

    def __init__(self, phase: str, owner: Optional[_IterationSpan]):
        super().__init__(
            "train." + phase, "train",
            None if owner is None else {"iteration": owner.iteration})
        self.phase, self.owner = phase, owner

    def __exit__(self, *exc):
        super().__exit__(*exc)
        if self.owner is not None:
            self.owner.phase_ns[self.phase] += self.dur_ns
        return False


def iteration_span(iteration: int) -> _IterationSpan:
    """``train.iteration``: the enclosing span of one boosting iteration
    (``Booster.update``); closing it writes the tree's record."""
    return _IterationSpan(int(iteration))


def phase_span(phase: str) -> _PhaseSpan:
    """``train.<phase>`` (one of ``ITERATION_PHASES``): a bridged child of
    the thread's open ``iteration_span``, carrying its ``iteration``; with
    none open (a caller driving ``train_one_iter`` itself) it is a plain
    bridged span."""
    return _PhaseSpan(phase, getattr(_tls, "iteration", None))


def iteration_records() -> List[tuple]:
    """The last ``ITERATION_RING`` iterations, oldest first, each the
    ten fields ``(iteration, t0_ns, prepare_ns, dispatch_ns, bookkeep_ns,
    wait_ns, total_ns, renewed, rounds, hist_skipped)``: times on the
    ``perf_counter_ns`` clock; ``renewed`` the nodes and leaves of the
    iteration's trees whose stored sums were measured again from the rows;
    ``rounds`` a tuple of the rounds those trees ran in each slot bucket
    of the wave grower, smallest bucket first (``(b4, b16, bK)``, or
    ``(bK,)`` without a ladder); ``hist_skipped`` how many of those rounds
    ran no histogram pass (no child of theirs could be split: 1 a tree
    that spends its leaves, 0 for one that runs out of gain).  The last
    three are None where nobody said (``rounds`` and ``hist_skipped``: a
    grower that has no rounds), and are read from the device on the first
    call that reaches them."""
    return [(*r[:-3], *(later.get() for later in r[-3:]))
            for r in _iterations]


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------

def drain() -> Dict:
    """Snapshot the ring (oldest -> newest) without disturbing it:
    ``{"events": [...], "dropped": n, "t0_ns": arm_instant,
    "t0_unix_ns": the same instant on the wall clock}``."""
    with _lock:
        if len(_ring) < _ring_cap or _ring_pos == 0:
            events = list(_ring)
        else:
            events = _ring[_ring_pos:] + _ring[:_ring_pos]
        return {"events": events, "dropped": _dropped, "t0_ns": _t_arm_ns,
                "t0_unix_ns": _t_arm_unix_ns}


def export_chrome(path: Optional[str] = None) -> Dict:
    """Chrome trace-event JSON of the buffered spans (Perfetto-viewable).
    When ``path`` is given the JSON is written via
    ``fileio.atomic_write_bytes`` — a crash mid-export leaves the old
    file, never a torn one — and the dict is returned either way."""
    import json

    snap = drain()
    t0 = snap["t0_ns"]
    events = []
    tids = {}
    pre_arm = 0
    for name, cat, t_ns, dur_ns, tid, args in snap["events"]:
        if t_ns < t0:
            # a span ENTERED before the most recent arm() (or re-arm)
            # carries a t0 from the previous epoch — exporting it would
            # produce a negative ts Perfetto renders at minus-infinity.
            # Drop it and report the count instead.
            pre_arm += 1
            continue
        tids.setdefault(tid, len(tids))
        ev = {
            "name": name,
            "cat": cat,
            "ph": "X",
            "ts": (t_ns - t0) / 1e3,       # microseconds
            "dur": dur_ns / 1e3,
            "pid": os.getpid(),
            "tid": tid,
        }
        if args:
            ev["args"] = args
        events.append(ev)
    for tid, i in tids.items():
        events.append({"name": "thread_name", "ph": "M", "pid": os.getpid(),
                       "tid": tid, "args": {"name": f"thread-{i}"}})
    from . import events as obs_events

    ident = obs_events.identity()
    doc = {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {"dropped_events": snap["dropped"],
                      "pre_arm_dropped": pre_arm,
                      "exporter": "lightgbmv1_tpu.obs.trace",
                      # cross-process merge keys (obs/agg.py): the wall
                      # instant ts=0 corresponds to, plus who we are
                      "t0_unix_ns": snap["t0_unix_ns"],
                      "host": ident["host"], "pid": ident["pid"],
                      "role": ident["role"], "run_id": ident["run_id"]},
    }
    if path:
        from ..utils import fileio

        fileio.atomic_write_bytes(
            str(path), json.dumps(doc).encode("utf-8"), site="trace_out")
    return doc
