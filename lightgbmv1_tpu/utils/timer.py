"""Aggregate wall-clock phase timer + device-phase measurement helpers.

TPU-native analog of the reference's compile-time-gated ``Common::Timer`` /
``FunctionTimer`` (include/LightGBM/utils/common.h:1054-1138) fed by a global
``global_timer``: here a context-manager/decorator that aggregates per-phase
wall time and can print a sorted report, plus optional hooks into
``jax.profiler`` traces via ``named_scope``.

Also home to the two shared pieces of the phase-attribution machinery
(bench.py + tools/phase_attrib.py both import them, so the methodology
cannot drift between the headline record and the residual breakdown):

* ``scan_differential_ms`` — the two-length-scan differential that
  cancels per-dispatch fixed costs (launch, argument handling, the
  result fetch — comparable to the few-ms phases being measured),
* ``PhaseBreakdown`` — the bookkeeping object that keeps a named
  sub-phase decomposition honest against a measured total: parts are
  clamped non-negative, the unattributed remainder is total − Σ(parts)
  by construction, and the record it emits carries the coverage flag the
  acceptance bar reads (unattributed ≤ 10% of measured wall), so the
  residual can never silently regrow without the record saying so.
"""

from __future__ import annotations

import contextlib
import time
from collections import defaultdict
from typing import Callable, Dict, Iterator


class Timer:
    def __init__(self) -> None:
        self.totals: Dict[str, float] = defaultdict(float)
        self.counts: Dict[str, int] = defaultdict(int)
        self.enabled = False

    @contextlib.contextmanager
    def section(self, name: str) -> Iterator[None]:
        if not self.enabled:
            yield
            return
        start = time.perf_counter()
        try:
            yield
        finally:
            self.totals[name] += time.perf_counter() - start
            self.counts[name] += 1

    def report(self) -> str:
        lines = ["LightGBM-TPU timer report:"]
        for name, total in sorted(self.totals.items(), key=lambda kv: -kv[1]):
            lines.append(f"  {name}: {total:.3f}s ({self.counts[name]} calls)")
        return "\n".join(lines)

    def reset(self) -> None:
        self.totals.clear()
        self.counts.clear()


global_timer = Timer()


def scan_differential_ms(make_reps: Callable[[int], Callable], r1: int = 4,
                         r2: int = 16, probes: int = 5) -> float:
    """Per-rep milliseconds from a TWO-length-scan differential.

    ``make_reps(r)`` returns a zero-argument jitted callable running the
    measured op ``r`` times inside one ``lax.scan`` (ONE device dispatch).
    ``(wall(r2) - wall(r1)) / (r2 - r1)`` cancels dispatch latency and
    every other per-call fixed cost, which would otherwise overstate a
    few-ms per-rep time.
    MEDIAN of ``probes`` interleaved pairs, not min: the minimum of a
    difference of two noisy walls can go spuriously small (slow short run
    + fast long run) and overstate throughput past physical peaks."""
    import jax

    f1, f2 = make_reps(r1), make_reps(r2)
    jax.block_until_ready(f1())
    jax.block_until_ready(f2())
    diffs = []
    for _ in range(probes):
        t0 = time.perf_counter()
        jax.block_until_ready(f1())
        t1 = time.perf_counter()
        jax.block_until_ready(f2())
        t2 = time.perf_counter()
        diffs.append(((t2 - t1) - (t1 - t0)) / (r2 - r1))
    diffs.sort()
    return max(diffs[len(diffs) // 2] * 1e3, 1e-6)


class PhaseBreakdown:
    """Named decomposition of a measured wall time.

    ``add`` records a sub-phase (clamped at 0 — a differential can come
    out marginally negative in noise); ``record(total_ms, wall_ms)``
    emits the fields bench.py merges into the BENCH record: the named
    parts, ``unattributed_ms = total − Σ(parts)`` (the arithmetic is BY
    CONSTRUCTION, so named parts + remainder always reproduce the
    measured total exactly), the remainder's fraction of the full
    per-iteration wall, and the ≤10%-of-wall coverage flag."""

    def __init__(self) -> None:
        self.parts: Dict[str, float] = {}

    def add(self, name: str, ms: float) -> None:
        self.parts[name] = round(max(float(ms), 0.0), 3)

    def total_attributed(self) -> float:
        return sum(self.parts.values())

    def record(self, total_ms: float, wall_ms: float,
               max_unattr_frac: float = 0.10) -> Dict:
        unattr = float(total_ms) - self.total_attributed()
        return {
            "phase_other_breakdown": dict(self.parts),
            "phase_other_unattributed_ms": round(unattr, 3),
            "phase_unattributed_frac_of_wall": round(
                unattr / wall_ms if wall_ms > 0 else 0.0, 4),
            "phase_attrib_ok": bool(
                unattr <= max_unattr_frac * wall_ms),
        }
