"""Unified observability layer (span tracing + one metrics registry).

Two pillars (ISSUE 9), one schema across the train/serve boundary:

* :mod:`~lightgbmv1_tpu.obs.trace` — a low-overhead nested-span tracer
  (thread-local span stack, monotonic clocks, ring-buffered events)
  exporting Chrome trace-event JSON viewable in Perfetto.  Per-request
  and per-block spans (``serve.*``, ``stream.*``) are hard-off by
  default; per-tree and coarser spans (``train.*``, ``data.*``) are
  always also ``jax.profiler.TraceAnnotation``s, so any profiler session
  holds them on the device ops' clock, and every tree leaves one
  always-on record (``trace.iteration_records()``).  Serving requests
  carry a propagated trace id end to end.
* :mod:`~lightgbmv1_tpu.obs.metrics` — counters / gauges / histograms
  with labels in one registry; JSON snapshots and Prometheus text
  exposition.

The forensics-and-fleet half (ISSUE 10) builds on those:

* :mod:`~lightgbmv1_tpu.obs.events` — an always-on bounded structured
  wide-event log with process identity; every warning, fatal and guard
  trip (finite guard, shed, watchdog, breaker, publish reject, block
  cache, fault injection) is a first-class event.
* :mod:`~lightgbmv1_tpu.obs.dump` — a crash-dump flight recorder: the
  first crash-grade moment of an armed process atomically writes ONE
  validated forensic bundle (event tail + trace + metrics + config +
  versions) into a crash dir.
* :mod:`~lightgbmv1_tpu.obs.agg` + ``tools/obs_aggregate.py`` — merge
  per-process trace/metrics/event artifacts (and crash bundles) into
  ONE Perfetto trace with pid lanes and one merged snapshot.
* :mod:`~lightgbmv1_tpu.serve.slo` — availability/latency SLOs with
  multi-window burn-rate evaluation and exemplar trace ids
  (``GET /slo``).

The device-truth half (ISSUE 12) closes the host/chip gap:

* :mod:`~lightgbmv1_tpu.obs.xla` — a labeled lower/compile wrapper
  (compile walls, retrace counts, cost/memory analysis of the compiled
  executables, always-on), live device-memory gauges reconciled against
  the streaming ``DeviceLedger``, and the XLA-profiler lane (wall-clock
  anchored device capture) obs/agg.py merges next to the host spans;
  ``tools/capture.py`` is the one-command driver-capture orchestrator.

The model-quality half (ISSUE 14) watches the MODEL, not the system:

* :mod:`~lightgbmv1_tpu.obs.model` — training-time reference capture
  (per-feature bin-occupancy over the ensemble's own BinMapper bins,
  NaN rates, score distribution; digest-verified bytes carried in
  checkpoint bundles and ModelVersion meta) + after-the-fact trainer
  quality telemetry (split-gain distribution, leaf/depth stats, metric
  curves, gain/split importance).
* :mod:`~lightgbmv1_tpu.obs.drift` — serving-side train/serve skew
  detection: a bounded sampling ring on the serve path (hard-off by
  default) re-bins request rows through the version's own mappers;
  per-feature PSI + unseen-bin/NaN counters and score drift at
  ``GET /drift``, capped-cardinality Prometheus gauges (top-K), and
  ``drift.alert`` events.

Contract: the ring is OFF by default and ``span()``'s off path must cost
nothing measurable (one module-level flag check, no allocation).  The
always-bridged per-tree spans cost a few microseconds each with no
profiler session open (five a tree; tests/test_train_spans.py holds a
ceiling), and the per-tree record one tuple append.  What an armed ring
costs a training cell on the chip is in PERF.md (PR 26).  Metrics are
always on — counter bumps are nanoseconds against millisecond
iterations and requests.
"""

from . import agg, drift, dump, events, metrics, model, trace, xla
from .metrics import Registry, default_registry
from .trace import span

__all__ = ["agg", "drift", "dump", "events", "metrics", "model", "trace",
           "xla", "Registry", "default_registry", "span"]
