"""Stochastic-rounded gradient quantization for the histogram pass.

The round-5 precision experiment (tools/precision_expt.py, PERF.md) showed
plain int8 histograms recover the int8 MXU's 2x-bf16 throughput but lose
0.007 AUC at 500 iterations: round-to-nearest quantization of gradients is
BIASED per bin, and the bias compounds over the boosting recursion.  The
fix with real-world lineage is *stochastic rounding* — LightGBM's own
quantized-training work ("Quantized Training of Gradient Boosting Decision
Trees", Shi et al., NeurIPS 2022) rounds gradients up or down with
probability proportional to the fractional part, which makes every
quantized per-bin SUM an unbiased estimator of the fp32 sum:

    E[floor(x + U)] = x   for U ~ Uniform[0, 1)

so the split finder sees zero-mean noise instead of systematic drift.

Determinism contract: the rounding stream is a **counter-based PRNG**
(``jax.random`` threefry) keyed by fold-ins of (iteration, round) — the
grower folds its per-tree key (already unique per (iteration, class)) with
the round's leaf count, and this module draws the whole row block from
that key in one counter-indexed sweep.  Results are bit-reproducible given
the seed on every backend, and the NumPy reference in
tests/test_int8sr.py reproduces the quantization bit-for-bit from the
same uniforms.

Scale placement: the interface carries **per-slot scales** ``(nslots, 3)``
so a per-leaf refinement can drop in, but the implementation uses one
per-pass scale (the global |grad| / |hess| max over the pass's rows):
a per-slot segment-max is a scatter, and scatters measured ~8 ms at bench
shapes on this device (tools/microbench_gather.py) — more than the whole
deep histogram pass the quantization is trying to speed up.

Counts stay EXACT: the count/weight channel is quantized with a
power-of-two scale (deterministic round-to-nearest, exact for unit
weights), preserving the repo-wide "counts are exact" guarantee that
min_data_in_leaf gating relies on (ops/histogram.py module docstring).

ALL scales are powers of two — grad/hess too, snapped down from
amax/127 (sr_prequantize_g3).  Exact dequantization multiplies make the
parent-subtraction arithmetic rounding-order independent (see the
comment at the snap site).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

INT8_QMAX = 127.0


@functools.partial(jax.jit, static_argnames=("nslots", "axis_name"))
def sr_quantize_g3(g3: jax.Array, label: jax.Array, nslots: int,
                   key: jax.Array, axis_name=None):
    """Quantize ``g3`` (N, 3) [grad, hess, count] to int8-ranged integers
    with stochastic rounding on the grad/hess channels.

    Returns ``(q3, scales)``:

    * ``q3`` (N, 3) float32 holding exact integers in [-127, 127] — kept
      in f32 because the TPU VPU has no int8 vector select (the kernel's
      leaf-mask ``where`` runs in f32 and the int8 cast is the final op
      feeding the MXU, ops/hist_pallas.py).
    * ``scales`` (nslots, 3) float32 — dequantization multipliers per
      slot: real histogram = integer histogram * scales.  Currently every
      slot carries the same per-pass scale (see module docstring).

    ``label`` is accepted (and unused by the global-scale implementation)
    so a per-slot scale can be introduced without touching call sites.

    ``axis_name``: when the rows are a SHARD of a mesh axis (data/voting
    parallel learners), pass its name — the quantization range is then
    pmax'd across shards so every shard quantizes against the IDENTICAL
    scale.  That is what lets the cross-chip histogram reduction run in
    the raw INTEGER domain (int32 through lax.psum_scatter/psum,
    parallel/trainer.py) with one shared dequantization folded into the
    split scan; per-shard scales would make the integer partials
    incommensurable.  SR unbiasedness holds for any scale, so the global
    scale (>= each local amax) changes nothing statistically.
    """
    del label  # per-pass scales; see module docstring
    zg, qc, scales = sr_prequantize_g3(g3, nslots, axis_name=axis_name)
    u = jax.random.uniform(key, zg.shape, dtype=jnp.float32)  # [0, 1)
    q = jnp.clip(jnp.floor(zg + u), -INT8_QMAX, INT8_QMAX)
    q3 = jnp.concatenate([q, qc[:, None]], axis=1)
    return q3, scales


def sr_prequantize_g3(g3: jax.Array, nslots: int, axis_name=None):
    """The key-INDEPENDENT half of :func:`sr_quantize_g3`: scaled
    grad/hess rows ``zg = g * inv`` (N, 2), the exactly-rounded count
    channel ``qc`` (N,), and the (nslots, 3) dequantization scales."""
    from jax import lax as _lax

    g = g3[:, :2].astype(jnp.float32)
    amax = jnp.max(jnp.abs(g), axis=0)                       # (2,)
    if axis_name is not None:
        with jax.named_scope("lgbm.collective"):
            amax = _lax.pmax(amax, axis_name)
    # grad/hess scales snap DOWN to a power of two (inv = 2^floor(log2(
    # 127/amax)), scale = 1/inv): a power-of-two dequantization multiply
    # is EXACT in f32, so `parent - q*scale` rounds identically whether a
    # compiler contracts the multiply into the subtraction (fma, one
    # rounding) or not (two roundings): trees must not hang on a
    # contraction heuristic (optimization_barrier does not stop it).
    # Costs at most one bit of int8 range; SR unbiasedness holds for any
    # scale (module docstring).
    e2 = jnp.floor(jnp.log2(INT8_QMAX / amax))
    inv = jnp.where(amax > 0, jnp.exp2(e2), 0.0)
    scale = jnp.where(amax > 0, jnp.exp2(-e2), 0.0)
    zg = g * inv[None, :]

    # count channel: power-of-two scale, deterministic rounding => exact
    # integer counts for unit weights (inv_c = 64, the historical
    # _COUNT_SCALE) and safe for weighted rows
    c = g3[:, 2].astype(jnp.float32)
    cmax = jnp.max(jnp.abs(c))
    if axis_name is not None:
        with jax.named_scope("lgbm.collective"):
            cmax = _lax.pmax(cmax, axis_name)
    inv_c = jnp.where(
        cmax > 0,
        jnp.minimum(jnp.exp2(jnp.floor(jnp.log2(INT8_QMAX / cmax))), 64.0),
        1.0)
    qc = jnp.round(c * inv_c)

    scales = jnp.concatenate(
        [jnp.broadcast_to(scale[None, :], (nslots, 2)),
         jnp.full((nslots, 1), 1.0, jnp.float32) / inv_c], axis=1)
    return zg, qc, scales


def dequantize_hist(hist_q: jax.Array, scales: jax.Array) -> jax.Array:
    """(S, F, B, 3) integer histogram * (S, 3) per-slot scales -> real
    units.  One fused broadcast multiply — the explicit form of the
    dequantization the split scan / subtraction pass otherwise folds in."""
    return hist_q * scales[:, None, None, :]
