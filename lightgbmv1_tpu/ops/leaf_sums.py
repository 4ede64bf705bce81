"""Sums of (grad, hess, count) over the rows of each leaf of a finished tree.

What ``models/renew.py`` measures a tree's marked sums from.  Two
implementations, told apart by the trainer's histogram method:

* ``leaf_sums_pallas`` - a histogram pass with the leaf id as the only
  "feature": a grid step owns a tile of rows, builds the leaves' one-hot
  ``(leaves, rows)`` from the row-major leaf ids by one iota compare (rows
  stay on the lanes, where ``g3`` and the leaf ids already lie: no operand
  is laid out for it, unlike ``hist_leaves_pallas``, whose bin operand
  wants rows on the sublanes) and adds ``values @ one_hot^T`` to a
  ``(terms, leaves)`` accumulator: the addends cut to the configured
  precision's bf16 terms inside the kernel, as ``hist_leaves_pallas`` cuts
  its own; bf16 products, float32 sums.
* ``leaf_sums_chunked`` - plain XLA, float32: rows scatter into per-chunk
  partial sums of at most ``_CHUNK`` addends, which are then added (a
  float32 cell that takes a million equal addends in a row loses its low
  bits the same way every time).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_CHUNK = 4096             # addends one float32 partial sum of the XLA path takes
_ONE_HOT_BYTES = 4 << 20  # VMEM the (leaves, rows) bf16 one-hot may take


def leaf_sums_chunked(leaf_id, g3, L: int):
    N = leaf_id.shape[0]
    nc = -(-N // _CHUNK)
    cell = (jnp.arange(N, dtype=jnp.int32) // _CHUNK) * L + leaf_id
    part = jnp.zeros((nc * L, 3), jnp.float32).at[cell].add(
        g3.astype(jnp.float32))
    return part.reshape(nc, L, 3).sum(axis=0)


TERMS = {"bf16": 1, "bf16x2": 2, "f32": 3, "int8": 1, "int8sr": 1}


def quantized(g3t, precision: str):
    """``(3, N)`` float32 addends -> ``(addends, scale)`` as an ``int8`` /
    ``int8sr`` pass sees them: gradient and hessian rounded to 1/127 of
    the pass's largest (integers up to 127, exact in one bf16 term; the
    count is exact as it is).  Other precisions: unchanged, no scale."""
    if precision not in ("int8", "int8sr"):
        return g3t, None
    amax = jnp.max(jnp.abs(g3t[:2]), axis=1, keepdims=True)
    inv = jnp.where(amax > 0, 127.0 / amax, 0.0)
    q = jnp.concatenate([jnp.round(g3t[:2] * inv), g3t[2:]], axis=0)
    scale = jnp.concatenate([jnp.where(amax > 0, amax / 127.0, 0.0),
                             jnp.ones((1, 1), jnp.float32)], axis=0)
    return q, scale


def bf16_terms(v, n: int):
    """``v`` (float32) as ``n`` bf16 terms whose sum is ``v`` to 8n bits:
    how the histogram passes cut their addends (``bf16`` one term,
    ``bf16x2`` hi + lo, ``f32`` three).  Inside a kernel this is what it
    says; in plain XLA on a TPU the compiler may keep the excess
    precision and leave the later terms zero."""
    parts, rest = [], v
    for _ in range(n):
        t = rest.astype(jnp.bfloat16)
        parts.append(t)
        rest = rest - t.astype(jnp.float32)
    return parts


def _kernel(leaf_ref, v_ref, out_ref, *, n_terms):
    """Grid: (row tiles,); ``out`` revisited.  leaf (1, T) int32, v (8, T)
    float32 (three rows of addends, five of zeros), out (8 n_terms, Lp)
    float32: one block of 8 rows a term."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    Lp = out_ref.shape[1]
    T = leaf_ref.shape[1]
    hot = (lax.broadcasted_iota(jnp.int32, (Lp, T), 0)
           == leaf_ref[...]).astype(jnp.bfloat16)
    terms = bf16_terms(v_ref[...], n_terms)
    lhs = terms[0] if n_terms == 1 else jnp.concatenate(terms, axis=0)
    out_ref[...] += lax.dot_general(
        lhs, hot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("L", "precision", "interpret"))
def leaf_sums_pallas(leaf_id, g3, L: int, precision: str = "bf16x2",
                     interpret: bool = False):
    """``(L, 3)`` float32."""
    N = leaf_id.shape[0]
    Lp = -(-L // 128) * 128
    T = max(128, min(8192, (_ONE_HOT_BYTES // (2 * Lp)) // 128 * 128))
    n_pad = -(-N // T) * T
    v, scale = quantized(g3.astype(jnp.float32).T, precision)   # (3, N)
    n_terms = TERMS[precision]
    R = 8 * n_terms
    v = jnp.pad(v, ((0, 5), (0, n_pad - N)))
    # padded rows carry leaf -1, which is no leaf
    leaf = jnp.pad(leaf_id.astype(jnp.int32), (0, n_pad - N),
                   constant_values=-1)[None, :]
    out = pl.pallas_call(
        functools.partial(_kernel, n_terms=n_terms),
        grid=(n_pad // T,),
        in_specs=[pl.BlockSpec((1, T), lambda i: (0, i)),
                  pl.BlockSpec((8, T), lambda i: (0, i))],
        out_specs=pl.BlockSpec((R, Lp), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((R, Lp), jnp.float32),
        interpret=interpret,
        name="leaf_sums_pallas",
    )(leaf, v)
    sums = out.reshape(n_terms, 8, Lp)[:, :3].sum(axis=0)        # (3, Lp)
    if scale is not None:
        sums = sums * scale
    return sums.T[:L]
