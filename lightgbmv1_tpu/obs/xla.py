"""Compiler/device-truth telemetry: what XLA and the chip actually did.

The obs stack through PR 10 observes the HOST — spans, events, metrics
of what N python processes did.  Every device-side figure (compile
walls, HBM footprints, flops/bytes of the compiled step) was either
uncaptured or an estimated host-side guess.  This module is the
instrument layer underneath ROADMAP item 2's capture campaign; three
surfaces:

* **Labeled lower/compile wrapper** (:func:`instrument_jit`) — a drop-in
  for ``jax.jit`` adopted by the trainer's fused/scanned dispatches
  (models/gbdt.py), the BatchPredictor jit cache (models/predict.py) and
  the parallel learners (parallel/trainer.py).  Each wrapper runs the
  AOT pipeline explicitly (``jit(f).lower(args).compile()``) so every
  compilation is an OBSERVED event: per-label compile counts, retrace
  counts (a compile for a (label, signature) already seen — the retrace-
  storm detector), ``compile_ms``, and the compiled executable's
  ``cost_analysis()`` (flops, bytes accessed) and ``memory_analysis()``
  (temp / argument / output / generated-code bytes) land in the process
  stats table (:func:`compile_stats`) and the unified metrics registry
  (``xla_compile_total{label}`` and friends) — always on.  Execution
  goes through the SAME compiled executable, so the numbers describe the
  program that actually ran, and results are bit-identical to the plain
  ``jax.jit`` path (pinned by tests/test_xla_obs.py).

  Safety: a call whose arguments are tracers (the wrapper nested inside
  an outer jit) passes straight through to the inlined jit.  A compile
  or dispatch failure PROPAGATES — execution never silently changes
  path (a retry through plain ``jax.jit`` would also re-use arguments
  the failed dispatch may already have donated).  Only the signature
  bookkeeping (an unhashable argument structure) may degrade a wrapper
  to plain ``jax.jit``, counted as a fallback; telemetry extraction
  (``cost_analysis`` / ``memory_analysis``) may be absent, never fatal.

* **Live device-memory gauges** (:func:`sample_device_memory`) — the
  runtime allocator's view via ``device.memory_stats()`` (``None`` on
  backends that expose none, e.g. CPU — graceful absence, never a
  crash), published as ``device_bytes_in_use`` / ``device_peak_bytes_in_use``
  gauges and reconciled against the PR 8 ``DeviceLedger`` analytic
  bound (:func:`ledger_agreement`).

* **XLA profiler lane** (:func:`profiler_session` /
  :func:`start_profiler` / :func:`stop_profiler`) — arms
  ``jax.profiler`` around a capture window and writes a wall-clock
  anchor sidecar (``profile.anchor.json``) next to the capture, so
  obs/agg.py can rebase the device timeline onto the same axis as the
  host span lanes and reconcile the estimated phase spans against
  measured ``lgbm.*``-scoped device rows.

Knobs: ``LGBMV1_XLA_TELEMETRY=0`` (env) or :func:`set_enabled` disables
the AOT bookkeeping (wrappers degrade to plain ``jax.jit``); the
per-wrapper executable cache is bounded at ``cache_entries``.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

ANCHOR_FILE = "profile.anchor.json"

# per-wrapper compiled-executable cache bound: signatures beyond this
# evict LRU (re-touching retraces, counted) — the same discipline as the
# BatchPredictor's jit cache
DEFAULT_CACHE_ENTRIES = 32

_MEM_FIELDS = ("temp_bytes", "argument_bytes", "output_bytes",
               "alias_bytes", "generated_code_bytes")

_lock = threading.Lock()
_stats: Dict[str, Dict[str, Any]] = {}
_seen_sigs: set = set()
_enabled = os.environ.get("LGBMV1_XLA_TELEMETRY", "1") != "0"


def enabled() -> bool:
    return _enabled


def set_enabled(on: bool) -> None:
    """Process-wide switch for the AOT bookkeeping path (the wrappers
    themselves stay in place and dispatch through plain ``jax.jit``
    when disabled)."""
    global _enabled
    _enabled = bool(on)


# ---------------------------------------------------------------------------
# per-label stats + metrics publication
# ---------------------------------------------------------------------------


def _new_label_stats() -> Dict[str, Any]:
    return {"compiles": 0, "retraces": 0, "fallbacks": 0,
            "compile_ms_total": 0.0, "last_compile_ms": None,
            "flops": None, "bytes_accessed": None,
            "temp_bytes": None, "argument_bytes": None,
            "output_bytes": None, "alias_bytes": None,
            "generated_code_bytes": None}


def _metric(kind: str, name: str, help_text: str):
    from .metrics import default_registry

    reg = default_registry()
    factory = reg.counter if kind == "counter" else reg.gauge
    return factory(name, help_text, label_names=("label",))


def _extract_cost(compiled) -> Tuple[Optional[float], Optional[float]]:
    """(flops, bytes accessed) from ``cost_analysis()``; ``None`` where
    the backend reports none."""
    try:
        ca = compiled.cost_analysis()
    except Exception:   # noqa: BLE001 — absent on some backends
        return None, None
    if not isinstance(ca, dict):
        return None, None

    def field(key):
        v = ca.get(key)
        return float(v) if isinstance(v, (int, float)) and v >= 0 else None

    return field("flops"), field("bytes accessed")


def _extract_memory(compiled) -> Dict[str, Optional[int]]:
    """``memory_analysis()`` → the device-side byte fields, all ``None``
    when the backend does not implement compiled memory stats."""
    out: Dict[str, Optional[int]] = {k: None for k in _MEM_FIELDS}
    try:
        ma = compiled.memory_analysis()
    except Exception:   # noqa: BLE001
        return out
    if ma is None:
        return out
    for field, attr in (("temp_bytes", "temp_size_in_bytes"),
                        ("argument_bytes", "argument_size_in_bytes"),
                        ("output_bytes", "output_size_in_bytes"),
                        ("alias_bytes", "alias_size_in_bytes"),
                        ("generated_code_bytes",
                         "generated_code_size_in_bytes")):
        v = getattr(ma, attr, None)
        if isinstance(v, (int, float)):
            out[field] = int(v)
    return out


def _record_compile(label: str, sig_hash: int, compile_ms: float,
                    compiled) -> None:
    flops, bytes_accessed = _extract_cost(compiled)
    mem = _extract_memory(compiled)
    with _lock:
        st = _stats.setdefault(label, _new_label_stats())
        st["compiles"] += 1
        key = (label, sig_hash)
        retrace = key in _seen_sigs
        if retrace:
            st["retraces"] += 1
        else:
            _seen_sigs.add(key)
        st["compile_ms_total"] += compile_ms
        st["last_compile_ms"] = round(compile_ms, 3)
        if flops is not None:
            st["flops"] = flops
        if bytes_accessed is not None:
            st["bytes_accessed"] = bytes_accessed
        for k in _MEM_FIELDS:
            if mem[k] is not None:
                st[k] = mem[k]
    try:
        _metric("counter", "xla_compile_total",
                "Labeled lower/compile events").labels(label=label).inc()
        if retrace:
            _metric("counter", "xla_retrace_total",
                    "Compiles for an already-seen (label, signature)"
                    ).labels(label=label).inc()
        _metric("counter", "xla_compile_ms_total",
                "Milliseconds spent lowering+compiling, per label"
                ).labels(label=label).inc(compile_ms)
        if flops is not None:
            _metric("gauge", "xla_flops",
                    "cost_analysis flops of the last compiled executable"
                    ).labels(label=label).set(flops)
        if bytes_accessed is not None:
            _metric("gauge", "xla_bytes_accessed",
                    "cost_analysis bytes accessed of the last compile"
                    ).labels(label=label).set(bytes_accessed)
        for k in _MEM_FIELDS:
            if mem[k] is not None:
                _metric("gauge", f"xla_{k}",
                        f"memory_analysis {k.replace('_', ' ')} of the "
                        "last compile").labels(label=label).set(mem[k])
        from . import events

        events.publish(
            "xla.compile",
            f"{label}: compiled in {compile_ms:.1f} ms"
            + (" (retrace)" if retrace else ""),
            label=label, compile_ms=round(compile_ms, 3),
            retrace=retrace)
    except Exception:   # noqa: BLE001 — telemetry must never throw
        pass


def _record_fallback(label: str) -> None:
    with _lock:
        st = _stats.setdefault(label, _new_label_stats())
        st["fallbacks"] += 1
    try:
        _metric("counter", "xla_instrument_fallback_total",
                "Wrappers that fell back to plain jax.jit dispatch"
                ).labels(label=label).inc()
    except Exception:   # noqa: BLE001
        pass


def compile_stats() -> Dict[str, Dict[str, Any]]:
    """Per-label snapshot: compiles / retraces / fallbacks /
    compile_ms_total plus the last executable's cost and memory fields
    (present-or-None — backends without the analysis report None)."""
    with _lock:
        return {label: dict(st) for label, st in _stats.items()}


def reset_compile_stats() -> None:
    """Zero the process stats table (bench A/B windows; the metrics
    registry counters are cumulative and stay)."""
    with _lock:
        _stats.clear()
        _seen_sigs.clear()


def compile_ms_total() -> float:
    with _lock:
        return sum(st["compile_ms_total"] for st in _stats.values())


def retrace_counts() -> Dict[str, int]:
    with _lock:
        return {label: st["retraces"] for label, st in _stats.items()}


def compile_counts() -> Dict[str, int]:
    with _lock:
        return {label: st["compiles"] for label, st in _stats.items()}


# ---------------------------------------------------------------------------
# the labeled lower/compile wrapper
# ---------------------------------------------------------------------------


def _leaf_sig(x) -> tuple:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(shape), str(dtype))
    # python scalars trace as weak-typed 0-d values: the TYPE is the
    # signature, the value is an argument of the compiled executable
    return ("py", type(x).__name__)


def _has_tracer(leaves) -> bool:
    from jax.core import Tracer

    return any(isinstance(leaf, Tracer) for leaf in leaves)


class InstrumentedJit:
    """``jax.jit`` with the compile pipeline made observable (see the
    module docstring).  Bit-identical results; per-instance executable
    cache keyed on the argument signature (pytree structure + leaf
    shape/dtype)."""

    def __init__(self, fn, label: str,
                 cache_entries: int = DEFAULT_CACHE_ENTRIES,
                 **jit_kwargs):
        import jax

        if "static_argnums" in jit_kwargs or "static_argnames" in jit_kwargs:
            raise ValueError("instrument_jit does not support static "
                             "arguments; jit them directly")
        self._label = label
        self._jit = jax.jit(fn, **jit_kwargs)
        self._compiled: "OrderedDict[Any, Any]" = OrderedDict()
        self._cache_entries = max(int(cache_entries), 2)
        self._broken = False
        # jax.jit copies fn.__dict__ (functools.wraps) and callers rely
        # on capability flags riding the callable (e.g. the wave
        # grower's _supports_valids) — preserve that contract
        try:
            self.__dict__.update(getattr(fn, "__dict__", {}) or {})
        except Exception:   # noqa: BLE001
            pass

    @property
    def label(self) -> str:
        return self._label

    def cache_info(self) -> Dict[str, int]:
        return {"entries": len(self._compiled),
                "capacity": self._cache_entries,
                "broken": int(self._broken)}

    def lower(self, *args, **kwargs):
        """AOT passthrough — callers (the donation HLO-aliasing probes)
        inspect the lowered module exactly as with a plain jax.jit."""
        return self._jit.lower(*args, **kwargs)

    def _compile_now(self, sig, args, kwargs):
        t0 = time.perf_counter()
        compiled = self._jit.lower(*args, **kwargs).compile()
        compile_ms = (time.perf_counter() - t0) * 1e3
        _record_compile(self._label, hash(sig), compile_ms, compiled)
        self._compiled[sig] = compiled
        self._compiled.move_to_end(sig)
        while len(self._compiled) > self._cache_entries:
            self._compiled.popitem(last=False)
        return compiled

    def __call__(self, *args, **kwargs):
        if self._broken or not _enabled:
            return self._jit(*args, **kwargs)
        import jax

        try:
            leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
            if _has_tracer(leaves):
                # nested inside an outer trace: inline through plain jit
                return self._jit(*args, **kwargs)
            sig = (treedef, tuple(_leaf_sig(leaf) for leaf in leaves))
        except Exception:   # noqa: BLE001 — unhashable exotica: fall back
            self._broken = True
            _record_fallback(self._label)
            return self._jit(*args, **kwargs)
        compiled = self._compiled.get(sig)
        if compiled is None:
            compiled = self._compile_now(sig, args, kwargs)
        else:
            self._compiled.move_to_end(sig)
        return compiled(*args, **kwargs)


def instrument_jit(fn, label: str,
                   cache_entries: int = DEFAULT_CACHE_ENTRIES,
                   **jit_kwargs) -> InstrumentedJit:
    """Drop-in for ``jax.jit(fn, **jit_kwargs)`` with compile telemetry
    under ``label`` (see module docstring)."""
    return InstrumentedJit(fn, label, cache_entries=cache_entries,
                           **jit_kwargs)


# ---------------------------------------------------------------------------
# live device memory (graceful absence on CPU)
# ---------------------------------------------------------------------------


def device_memory_stats(device=None) -> Optional[Dict[str, int]]:
    """``device.memory_stats()`` of the first local device (or the one
    given) — the runtime allocator's live view.  ``None`` when the
    backend exposes no stats (XLA:CPU) or anything fails: absence is a
    value here, never an exception."""
    try:
        import jax

        dev = device if device is not None else jax.local_devices()[0]
        stats = dev.memory_stats()
    except Exception:   # noqa: BLE001
        return None
    if not stats:
        return None
    return {k: int(v) for k, v in stats.items()
            if isinstance(v, (int, float))}


def sample_device_memory(registry=None) -> Optional[Dict[str, int]]:
    """Sample :func:`device_memory_stats` into live gauges
    (``device_bytes_in_use`` / ``device_peak_bytes_in_use`` /
    ``device_bytes_limit``).  Returns the raw stats dict (None on
    backends without stats — the gauges are simply not written)."""
    stats = device_memory_stats()
    if stats is None:
        return None
    from .metrics import default_registry

    reg = registry if registry is not None else default_registry()
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                "largest_free_block_bytes"):
        if key in stats:
            reg.gauge(f"device_{key}",
                      "Runtime allocator view (device.memory_stats)"
                      ).set(stats[key])
    return stats


def ledger_agreement(ledger_peak_bytes: Optional[float],
                     device_peak_bytes: Optional[float]) -> Optional[float]:
    """Analytic-ledger peak over allocator peak — the reconciliation
    number between the PR 8 ``DeviceLedger`` (what the trainer DECLARED
    it allocated) and ``memory_stats`` (what the runtime SAW).  ~1.0
    means the ledger explains the footprint; well below 1.0 means
    unaccounted allocations; ``None`` when either side is unavailable
    (CPU has no allocator stats; a run without streaming has no
    ledger)."""
    if not ledger_peak_bytes or not device_peak_bytes:
        return None
    return round(float(ledger_peak_bytes) / float(device_peak_bytes), 4)


# ---------------------------------------------------------------------------
# XLA profiler lane (device capture + wall-clock anchor sidecar)
# ---------------------------------------------------------------------------


def start_profiler(out_dir: str) -> Dict[str, Any]:
    """Arm ``jax.profiler`` writing into ``out_dir`` and return the
    session dict (wall-clock anchor + identity).  The anchor is the wall
    instant ``start_trace`` RETURNS at (it sets the tracers up for
    hundreds of ms on a TPU before the session's ``ts=0``) — the epoch
    obs/agg.py rebases the lane with across processes; inside the
    capturing process the program's own ``train.*`` spans are in the
    profiler's host lane and need no anchor."""
    import jax

    from . import events as obs_events

    os.makedirs(str(out_dir), exist_ok=True)
    jax.profiler.start_trace(str(out_dir))
    return {"profile_dir": str(out_dir),
            "t0_unix_ns": time.time_ns(),
            "identity": obs_events.identity(),
            "_open": True}


def stop_profiler(session: Optional[Dict[str, Any]]) -> bool:
    """Stop the session exactly once (export-once: safe to call from
    both the crash path and the clean path) and write the anchor
    sidecar.  Returns True on the call that actually stopped it."""
    if not session or not session.get("_open"):
        return False
    session["_open"] = False
    import jax

    from ..utils import fileio

    try:
        jax.profiler.stop_trace()
    finally:
        doc = {k: v for k, v in session.items() if not k.startswith("_")}
        fileio.atomic_write_bytes(
            os.path.join(session["profile_dir"], ANCHOR_FILE),
            json.dumps(doc, sort_keys=True).encode("utf-8"),
            site="profile_anchor")
    return True


class profiler_session:
    """``with profiler_session(dir) as s:`` — arm the XLA profiler for
    the block and write the anchor sidecar on exit (any exit)."""

    def __init__(self, out_dir: str):
        self._dir = out_dir
        self.session: Optional[Dict[str, Any]] = None

    def __enter__(self):
        self.session = start_profiler(self._dir)
        return self.session

    def __exit__(self, *exc):
        stop_profiler(self.session)
        return False


def read_anchor(profile_dir: str) -> Optional[Dict[str, Any]]:
    """The anchor sidecar of a capture directory, or None."""
    path = os.path.join(str(profile_dir), ANCHOR_FILE)
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return None
