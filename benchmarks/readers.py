"""Readers: each per-layer metric is a JSON file under ``layer_metrics/``
naming one of these functions and its arguments.

A reader takes the run's context and its own arguments and returns a
number, or ``None`` when it finds nothing to read (the harness then leaves
the metric out of the line; it never prints 0 for a share).  A later PR
adds a metric over an existing reader as one new JSON file.
"""

from __future__ import annotations

import statistics
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import roofline
import trace_reduce


@dataclass
class Context:
    cell: str
    chips: int
    platform: str
    device_kind: str
    rows: int
    features: int
    bins: int
    host: Dict[str, float]                 # host-clock readings by name
    tree_ms: List[float]                   # per-tree host times, window
    window_s: float
    compile_stats: Dict[str, Dict[str, Any]]
    compiles_before: Dict[str, int]
    compiles_after: Dict[str, int]
    tree_counts: list                      # per window tree, see roofline
    peak_bytes: Optional[int]
    trace: Optional[trace_reduce.Trace] = None
    traced_trees: int = 0
    traced_window_s: float = 0.0
    scopes: List[str] = field(default_factory=list)
    notes: Dict[str, Any] = field(default_factory=dict)   # earlier lines


READERS: Dict[str, Callable[..., Optional[float]]] = {}


def reader(fn):
    READERS[fn.__name__] = fn
    return fn


@reader
def host_value(ctx: Context, key: str) -> Optional[float]:
    """A reading the harness took on the host clock, by name."""
    return ctx.host.get(key)


@reader
def median_tree_ms(ctx: Context) -> Optional[float]:
    return statistics.median(ctx.tree_ms) if ctx.tree_ms else None


@reader
def compile_seconds(ctx: Context, label: str) -> Optional[float]:
    """Seconds ``obs/xla.compile_stats()`` charged to one jit label
    (lowering, compiling or loading from the persistent cache)."""
    st = ctx.compile_stats.get(label)
    if not st:
        return None
    ms = st.get("compile_ms_total", st.get("compile_ms"))
    return None if ms is None else float(ms) / 1e3


@reader
def compiles_in_window(ctx: Context) -> float:
    """Compilations counted by ``compile_counts()`` after the window less
    before it, all labels."""
    return float(sum(ctx.compiles_after.values())
                 - sum(ctx.compiles_before.values()))


def _per_tree_ms(ctx: Context, seconds: float) -> Optional[float]:
    if ctx.trace is None or not ctx.traced_trees or not ctx.trace.devices:
        return None
    return seconds * 1e3 / ctx.traced_trees


@reader
def device_ms_under_scope(ctx: Context, scope: str) -> Optional[float]:
    """Self time of device ops under a ``jax.named_scope``, per traced
    tree, averaged over devices."""
    if ctx.trace is None:
        return None
    sec = trace_reduce.self_seconds(
        ctx.trace, lambda o: not o.is_container
        and trace_reduce.scope_of(o, [scope]) == scope)
    return _per_tree_ms(ctx, sec) if sec > 0 else None


@reader
def device_ms_of_op(ctx: Context, op: str) -> Optional[float]:
    """Self time of the device ops of one name (a kernel), per tree."""
    if ctx.trace is None:
        return None
    sec = trace_reduce.self_seconds(ctx.trace, lambda o: o.base == op)
    return _per_tree_ms(ctx, sec) if sec > 0 else None


@reader
def device_ms_outside_scopes(ctx: Context, scopes: List[str]
                             ) -> Optional[float]:
    """Device busy time less what ran under the given scopes, per tree."""
    if ctx.trace is None or not ctx.trace.devices:
        return None
    inside = trace_reduce.self_seconds(
        ctx.trace, lambda o: not o.is_container
        and trace_reduce.scope_of(o, scopes) is not None)
    return _per_tree_ms(ctx, ctx.trace.busy_s - inside)


@reader
def kernel_roofline(ctx: Context, op: str, model: str = "hist_pass",
                    peak: str = "flops_bf16") -> Optional[float]:
    """Share of its roofline a kernel reached over the traced window: the
    least time the chip could take for each call (the larger of operations
    over peak operations/s and bytes over peak bytes/s, counted by
    ``roofline.hist_pass_work`` from the cell's rows, features and bins and
    the call's slots), summed, over the kernel's traced time."""
    if ctx.trace is None or not ctx.trace.devices or model != "hist_pass":
        return None
    pk = roofline.peaks_for(ctx.device_kind)
    least = took = t_ops = t_bytes = 0.0
    calls: Dict[tuple, List[float]] = {}
    for dev in ctx.trace.devices.values():
        for o in dev.ops:
            if o.base != op:
                continue
            shp = roofline.hist_call_shapes(o.long_name, ctx.bins)
            if shp is None:
                ctx.notes[f"{op}_unread_shape"] = o.long_name[:300]
                return None
            # a wide matrix goes through the kernel in blocks of features:
            # the calls of one pass share the cell's features between them
            blocks = -(-ctx.features // max(shp["features"], 1))
            ops, byts = roofline.hist_pass_work(
                ctx.rows // ctx.chips, ctx.features / blocks, ctx.bins,
                shp["slots"])
            a, b = ops / pk[peak], byts / pk["hbm_bytes_per_s"]
            least += max(a, b)
            t_ops += a
            t_bytes += b
            took += o.dur
            seen = calls.setdefault((shp["slots"], shp["features"],
                                     shp.get("rows", 0)), [0, 0.0])
            seen[0] += 1
            seen[1] += o.dur
    if took <= 0:
        return None
    ctx.notes[f"{op}_bound_by"] = ("operations" if t_ops >= t_bytes
                                   else "bytes")
    ctx.notes[f"{op}_least_s_ops_bytes"] = [t_ops, t_bytes]
    ctx.notes[f"{op}_calls_slots_features_rows_count_s"] = [
        [*k, *v] for k, v in sorted(calls.items())]
    return 100.0 * least / took


@reader
def necessary_bytes_share(ctx: Context) -> Optional[float]:
    """Share of the chips' peak memory bandwidth the whole window reached
    on the bytes a histogram GBDT cannot avoid
    (``roofline.necessary_bytes`` of every tree finished in the window,
    from the model's own node counts)."""
    if not ctx.tree_counts or ctx.window_s <= 0 or ctx.platform != "tpu":
        return None      # a share of a chip's peak is a chip number
    pk = roofline.peaks_for(ctx.device_kind)
    total = sum(roofline.necessary_bytes(tc, ctx.features)
                for tc in ctx.tree_counts)
    return 100.0 * total / (ctx.window_s * ctx.chips
                            * pk["hbm_bytes_per_s"])


@reader
def collective_exposed_ms(ctx: Context) -> Optional[float]:
    """Collective time with no compute op running on that device, per tree
    (a four-chip cell's; no cell reads it yet, PERF.md Open questions)."""
    if ctx.trace is None:
        return None
    sec = trace_reduce.exposed_collective_seconds(ctx.trace)
    return None if sec is None else _per_tree_ms(ctx, sec)


@reader
def device_idle_share(ctx: Context) -> Optional[float]:
    if ctx.trace is None or not ctx.trace.devices or ctx.traced_window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.traced_window_s)


@reader
def device_peak_bytes(ctx: Context) -> Optional[float]:
    """``memory_stats()["peak_bytes_in_use"]`` of the fullest device: live
    buffers only; the result's ``memory_peak_bytes`` adds what loaded
    programs reserve."""
    return None if ctx.peak_bytes is None else float(ctx.peak_bytes)
