"""Readers over what the program records of itself (PR 26): the always-on
per-tree record (``lightgbmv1_tpu.obs.trace.iteration_records()``), the
``train.*`` spans the program leaves in the profiler's host lane, and the
metrics registry.

Importing this module registers the readers in ``readers.READERS``.  They
read three fields a run may put on its ``readers.Context``:

  ``iteration_records``  the window's records, one tuple a tree:
                         ``(iteration, t0_ns, prepare_ns, dispatch_ns,
                         bookkeep_ns, wait_ns, total_ns)``
  ``registry``           ``default_registry().snapshot()`` after the window
  ``trace``              the traced trees (``trace.host`` holds the spans)

Where a field is missing, or the program records no such thing (a parent
commit), a reader returns ``None`` and raises nothing.
``tools/span_cost.py`` runs a cell and reads them; ``run.py`` does not yet
(PERF.md, Open questions: which lines of it would).
"""

from __future__ import annotations

import statistics
from typing import List, Optional, Tuple

import trace_reduce
from readers import Context, reader

_FIELDS = {"prepare": 2, "dispatch": 3, "bookkeep": 4, "wait": 5, "total": 6}
PHASE_SPANS = ("train.prepare", "train.dispatch", "train.bookkeep",
               "train.wait")


def _phase_ns(record: tuple, phases: List[str]) -> int:
    """Sum of a record's named fields; ``-name`` subtracts, so
    ``["total", "-wait"]`` is everything but the wait."""
    return sum(-record[_FIELDS[p[1:]]] if p.startswith("-")
               else record[_FIELDS[p]] for p in phases)


def _records(ctx: Context) -> list:
    return list(getattr(ctx, "iteration_records", None) or [])


@reader
def iteration_phase_ms(ctx: Context, phases: List[str]) -> Optional[float]:
    """Median over the window's trees of the named phases' host time."""
    recs = _records(ctx)
    if not recs:
        return None
    return statistics.median(_phase_ns(r, phases) for r in recs) / 1e6


@reader
def slowest_tree_excess_ms(ctx: Context, phases: List[str]
                           ) -> Optional[float]:
    """For the window's slowest tree (by its total), its named phases less
    the window's median of them: which side held a stalled tree's extra
    milliseconds."""
    recs = _records(ctx)
    if len(recs) < 3:
        return None
    slowest = max(recs, key=lambda r: r[_FIELDS["total"]])
    median = statistics.median(_phase_ns(r, phases) for r in recs)
    return (_phase_ns(slowest, phases) - median) / 1e6


@reader
def registry_value(ctx: Context, key: str) -> Optional[float]:
    """One entry of the registry's snapshot, by its flat name
    (``dataset_construct_seconds{phase="find_bins"}``)."""
    value = (getattr(ctx, "registry", None) or {}).get(key)
    return None if value is None else float(value)


def _overlap(gaps: List[Tuple[float, float]],
             spans: List[Tuple[float, float]]) -> float:
    """Seconds of the (sorted, disjoint) gaps inside the union of spans."""
    total, j = 0.0, 0
    spans = trace_reduce.union(spans)
    for a, b in gaps:
        while j < len(spans) and spans[j][1] <= a:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < b:
            total += min(b, spans[k][1]) - max(a, spans[k][0])
            k += 1
    return total


def idle_gaps_in_window(trace, window_span: str = "bench.update"
                        ) -> List[Tuple[float, float]]:
    """The first device's idle intervals between the start of the first
    and the end of the last ``window_span`` in the host lane (between the
    first and last device op where the lane has none)."""
    dev = next(iter(trace.devices.values()))
    busy = dev.busy_intervals
    if not busy:
        return []
    marks = [(s, s + d) for name, s, d in trace.host if name == window_span]
    lo = min(m[0] for m in marks) if marks else busy[0][0]
    hi = max(m[1] for m in marks) if marks else busy[-1][1]
    gaps, at = [], lo
    for a, b in busy:
        if a > at:
            gaps.append((at, min(a, hi)))
        at = max(at, b)
        if at >= hi:
            break
    if at < hi:
        gaps.append((at, hi))
    return [(a, b) for a, b in gaps if b > a]


@reader
def idle_ms_under_host_span(ctx: Context, spans: List[str],
                            complement: bool = False) -> Optional[float]:
    """Device idle time inside the host-lane intervals of the named
    program spans, per traced tree (first device: one host drives them
    all).  The four phases of an iteration do not nest in one another, so
    a gap's time goes to the phase the host was in, and a gap that runs
    across phases is split at their borders.  ``complement``: the idle
    time of the traced window inside NONE of the named spans instead (the
    harness's own ``sync()`` and what lies between two updates)."""
    trace = ctx.trace
    if trace is None or not trace.devices or not ctx.traced_trees:
        return None
    mine = [(s, s + d) for name, s, d in trace.host
            if name in spans and d > 0]
    if not any(name in PHASE_SPANS for name, _, _ in trace.host):
        return None              # a program that leaves no spans
    gaps = idle_gaps_in_window(trace)
    inside = _overlap(gaps, mine)
    if complement:
        inside = sum(b - a for a, b in gaps) - inside
    return inside * 1e3 / ctx.traced_trees

