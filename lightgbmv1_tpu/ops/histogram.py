"""Histogram construction ops.

TPU-native replacement for the reference's histogram machinery:

* reference CPU hot loop: ``DenseBin::ConstructHistogramInner``
  (src/io/dense_bin.hpp:98-141) — per-row gather-accumulate into
  (feature, bin) grad/hess pairs.
* reference GPU kernels: ``src/treelearner/ocl/histogram{16,64,256}.cl`` —
  per-workgroup local sub-histograms + atomic float adds + cross-workgroup
  reduction.

TPUs have no scatter-add worth using in the hot path, but they have an MXU.
The TPU formulation is a **one-hot matmul**: for a tile of rows, build

    leafG (3·L, tile)   — per-leaf-masked [grad, hess, count] rows
    onehot (tile, B)    — bin one-hot per feature

and accumulate ``leafG @ onehot -> (3·L, B)`` per feature on the MXU with
fp32 accumulation.  Batching the leaf dimension (all leaves of the current
frontier in one pass) is what keeps the matmul non-skinny; it replaces both
the reference's per-leaf histogram loop and its most-freq-bin elision.

Three interchangeable implementations (equality-tested against each other,
the analog of the reference's GPU/CPU comparator ``CompareHistograms``,
gpu_tree_learner.cpp:71-98):

* ``hist_leaves_scatter`` — jnp scatter-add; exact fp32; the oracle; fast on
  CPU for tests.
* ``hist_leaves_onehot``  — chunked one-hot matmuls in pure jnp (XLA maps
  them onto the MXU); bf16 / bf16x2 / f32 precision modes.
* ``hist_leaves_pallas``  — hand-tiled Pallas kernel (ops/hist_pallas.py).

Output layout: ``(L, F, B, 3)`` float32 — [sum_grad, sum_hess, count] per
(leaf, feature, bin). Counts are exact: the count channel multiplies one-hot
by 1.0 and MXU accumulation is fp32 (exact integers to 2^24).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

# ---------------------------------------------------------------------------
# Scatter-add oracle
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("num_leaves", "num_bins"))
def hist_leaves_scatter(
    binned: jax.Array,      # (F, N) uint8/int16
    g3: jax.Array,          # (N, 3) f32 — [grad, hess, count(=sample weight mask)]
    leaf_id: jax.Array,     # (N,) int32
    num_leaves: int,
    num_bins: int,
) -> jax.Array:             # (L, F, B, 3) f32
    L, B = num_leaves, num_bins
    leaf_off = leaf_id.astype(jnp.int32) * B

    def per_feature(bins_f):
        idx = leaf_off + bins_f.astype(jnp.int32)
        h = jnp.zeros((L * B, 3), jnp.float32).at[idx].add(g3)
        return h.reshape(L, B, 3)

    h = lax.map(per_feature, binned)          # (F, L, B, 3)
    return h.transpose(1, 0, 2, 3)


# ---------------------------------------------------------------------------
# One-hot matmul path
# ---------------------------------------------------------------------------


def _matmul_hist(lg, onehot, precision: str):
    """(C, T) @ (T, B) with fp32 accumulation under the chosen input precision."""
    if precision == "f32":
        return jnp.dot(lg, onehot.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    oh = onehot.astype(jnp.bfloat16)
    if precision == "bf16":
        return jnp.dot(lg.astype(jnp.bfloat16), oh,
                       preferred_element_type=jnp.float32)
    # bf16x2: split fp32 into two bf16 terms; one-hot is exact, so this
    # recovers ~fp32 accuracy at 2 MXU passes (cheaper than native f32).
    hi = lg.astype(jnp.bfloat16)
    lo = (lg - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return (
        jnp.dot(hi, oh, preferred_element_type=jnp.float32)
        + jnp.dot(lo, oh, preferred_element_type=jnp.float32)
    )


@functools.partial(
    jax.jit,
    static_argnames=("num_leaves", "num_bins", "precision", "row_chunk"),
)
def hist_leaves_onehot(
    binned: jax.Array,      # (F, N)
    g3: jax.Array,          # (N, 3)
    leaf_id: jax.Array,     # (N,)
    num_leaves: int,
    num_bins: int,
    precision: str = "bf16x2",
    row_chunk: int = 16384,
    init: Optional[jax.Array] = None,   # (Lp*3, F*B) carry — streamed
                                        # accumulation (hist_one_leaf_accum)
) -> jax.Array:             # (L, F, B, 3)
    F, N = binned.shape
    L, B = num_leaves, num_bins
    C = min(row_chunk, max(256, N))
    num_chunks = -(-N // C)
    pad = num_chunks * C - N
    # padded rows route to a sacrificial extra leaf slot
    Lp = L + 1
    binned_p = jnp.pad(binned, ((0, 0), (0, pad)))
    g3_p = jnp.pad(g3, ((0, pad), (0, 0)))
    leaf_p = jnp.pad(leaf_id, (0, pad), constant_values=L)

    binned_c = binned_p.reshape(F, num_chunks, C).transpose(1, 0, 2)  # (nc, F, C)
    g3_c = g3_p.reshape(num_chunks, C, 3)
    leaf_c = leaf_p.reshape(num_chunks, C)

    def chunk_body(acc, inputs):
        bins_ck, g3_ck, leaf_ck = inputs
        leaf_onehot = (
            leaf_ck[None, :] == lax.broadcasted_iota(jnp.int32, (Lp, 1), 0)
        ).astype(jnp.float32)                                   # (Lp, C)
        lg = (leaf_onehot[:, None, :] * g3_ck.T[None, :, :]).reshape(Lp * 3, C)
        # one-hot over ALL features at once, laid out (C, F*B) so the whole
        # chunk is a single large MXU matmul instead of F skinny ones
        onehot = (
            bins_ck.T[:, :, None].astype(jnp.int32)
            == lax.broadcasted_iota(jnp.int32, (1, 1, B), 2)
        ).reshape(C, F * B)                                     # (C, F*B)
        h = _matmul_hist(lg, onehot, precision)                 # (Lp*3, F*B)
        return acc + h, None

    if init is None:
        init = jnp.zeros((Lp * 3, F * B), jnp.float32)
    h, _ = lax.scan(chunk_body, init, (binned_c, g3_c, leaf_c))
    h = h.reshape(Lp, 3, F, B).transpose(0, 2, 3, 1)             # (Lp, F, B, 3)
    return h[:L]


# ---------------------------------------------------------------------------
# Single-leaf histogram (leaf-wise smaller-child pass)
# ---------------------------------------------------------------------------


def hist_one_leaf(
    binned: jax.Array,
    g3: jax.Array,
    leaf_id: jax.Array,
    target_leaf: jax.Array,
    num_bins: int,
    method: str = "scatter",
    precision: str = "bf16x2",
    packed: bool = False,
    num_features: int = 0,
    interpret: bool = False,
) -> jax.Array:             # (F, B, 3)
    """Histogram over the rows currently in ``target_leaf`` only — the
    smaller-child pass of the histogram-subtraction trick (reference:
    ``BeforeFindBestSplit`` serial_tree_learner.cpp:274-314 keeps the parent
    histogram with the larger leaf and computes only the smaller)."""
    with jax.named_scope("lgbm.hist"):
        mask = (leaf_id == target_leaf).astype(jnp.float32)
        g3m = g3 * mask[:, None]
        zeros = jnp.zeros_like(leaf_id)
        if method == "pallas":
            from .hist_pallas import hist_leaves_pallas

            # forward interpret only when SET: callers (and tests) may
            # bind it on hist_leaves_pallas itself via functools.partial
            kw = {"interpret": True} if interpret else {}
            return hist_leaves_pallas(binned, g3m, zeros, 1, num_bins,
                                      precision=precision, packed=packed,
                                      num_features=num_features, **kw)[0]
        if packed:
            raise ValueError(
                "4-bit packed bins require the pallas hist method")
        if method == "onehot":
            return hist_leaves_onehot(binned, g3m, zeros, 1, num_bins,
                                      precision)[0]
        return hist_leaves_scatter(binned, g3m, zeros, 1, num_bins)[0]


# ---------------------------------------------------------------------------
# Streamed (row-block) accumulation — out-of-core training (data/ subsystem)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=())
def _scatter_accum(acc, binned, g3m):
    """Scatter one row block's masked gradient rows INTO ``acc`` (F, B, 3).

    Bit-exactness contract: XLA's scatter-add applies updates sequentially
    in index order, so scattering block b's rows into the accumulator
    CONTINUES the same left-fold of row-order additions that one
    ``hist_leaves_scatter`` pass over the concatenated rows performs —
    the streamed histogram is bit-identical to the resident one (pinned
    by tests/test_stream_train.py).  Summing per-block PARTIAL histograms
    instead would re-associate the f32 adds and break the parity."""
    def per_feature(args):
        af, bins_f = args
        return af.at[bins_f.astype(jnp.int32)].add(g3m)

    return lax.map(per_feature, (acc, binned))


def _onehot_layout(acc, num_bins):
    """(F, B, 3) accumulator -> the (Lp*3, F*B) layout of the
    hist_leaves_onehot chunk scan, leaf slot 0 (Lp = 2: slot 1 is the
    sacrificial pad-row slot, zero here)."""
    F, B, _ = acc.shape
    h = jnp.zeros((2, 3, F, B), jnp.float32).at[0].set(acc.transpose(2, 0, 1))
    return h.reshape(2 * 3, F * B)


@functools.partial(jax.jit, static_argnames=("num_bins", "precision"))
def _onehot_accum(acc, binned, g3m, num_bins, precision):
    F, B = binned.shape[0], num_bins
    h = hist_leaves_onehot(
        binned, g3m, jnp.zeros(binned.shape[1], jnp.int32), 1, num_bins,
        precision, 16384, init=_onehot_layout(acc, num_bins))
    return h[0]


def hist_one_leaf_accum(
    acc: jax.Array,         # (F, B, 3) running accumulator
    binned: jax.Array,      # (F, n) one row block's bins
    g3: jax.Array,          # (n, 3)
    leaf_id: jax.Array,     # (n,) int32 — this block's current leaf routing
    target_leaf,            # scalar
    num_bins: int,
    method: str = "scatter",
    precision: str = "bf16x2",
) -> jax.Array:
    """Streamed continuation of :func:`hist_one_leaf`: fold one row block
    into ``acc``.  Folding every block in fixed block-sequential order
    reproduces the resident full-matrix pass bit-for-bit on the
    ``scatter`` method (update-order continuation, see ``_scatter_accum``)
    and on ``onehot`` when the block size is a multiple of the 16384-row
    chunk (the resident pass's own accumulation granularity).  ``pallas``
    blocks fall back to partial-sum accumulation: deterministic at fixed
    block order, but not bit-equal to the resident kernel."""
    with jax.named_scope("lgbm.hist_stream"):
        mask = (leaf_id == target_leaf).astype(jnp.float32)
        g3m = g3 * mask[:, None]
        if method == "onehot":
            return _onehot_accum(acc, binned, g3m, num_bins, precision)
        if method == "pallas":
            return acc + hist_one_leaf(binned, g3m,
                                       jnp.zeros_like(leaf_id),
                                       jnp.asarray(0, jnp.int32), num_bins,
                                       method=method, precision=precision)
        return _scatter_accum(acc, binned, g3m)


@jax.jit
def sums_accum(acc, g3):
    """Streamed continuation of the sequential grower's ordered-scatter
    root-sum fold (models/grower.py sums_fn): scatter block rows into the
    (1, 3) carry slot — update order continues the resident fold exactly,
    so the streamed root statistics are bit-identical."""
    return acc.at[jnp.zeros(g3.shape[0], jnp.int32)].add(g3)


def hist_frontier(
    binned: jax.Array,
    g3: jax.Array,
    leaf_id: jax.Array,
    num_leaves: int,
    num_bins: int,
    method: str = "scatter",
    precision: str = "bf16x2",
    packed: bool = False,
    num_features: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """All-leaves histogram in a single pass (level-wise grower).

    ``interpret`` reaches the Pallas kernel only: the CPU backend runs
    ``hist_method=pallas`` through the interpreter.

    Wrapped in ``jax.named_scope`` so device traces attribute histogram
    time the way the reference's USE_TIMETAG FunctionTimer tags host time
    (utils/common.h:1054-1138); capture a trace with ``profile_dir``."""
    with jax.named_scope("lgbm.hist"):
        if method == "pallas":
            from .hist_pallas import hist_leaves_pallas

            # forward interpret only when SET (see hist_one_leaf)
            kw = {"interpret": True} if interpret else {}
            return hist_leaves_pallas(binned, g3, leaf_id, num_leaves,
                                      num_bins, precision=precision,
                                      packed=packed,
                                      num_features=num_features, **kw)
        if packed:
            raise ValueError(
                "4-bit packed bins require the pallas hist method")
        if method == "onehot":
            return hist_leaves_onehot(binned, g3, leaf_id, num_leaves,
                                      num_bins, precision)
        return hist_leaves_scatter(binned, g3, leaf_id, num_leaves, num_bins)


def hist_wave(
    binned: jax.Array,
    g3: jax.Array,
    label: jax.Array,       # (N,) int32 — child slot per row; nslots = dead
    nslots: int,
    num_bins: int,
    method: str = "scatter",
    precision: str = "bf16x2",
    packed: bool = False,
    num_features: int = 0,
    interpret: bool = False,
) -> jax.Array:             # (nslots, F, B, 3)
    """Histograms of the rows labeled ``0..nslots-1`` in one pass; rows
    labeled ``nslots`` (not part of the current wave) contribute nothing.
    Used by the wave-batched leaf-wise grower (models/grower_wave.py).  The
    Pallas kernel drops a row whose label is no slot's; ``scatter`` and
    ``onehot`` index by the label, so there one sacrificial slot absorbs
    the dead rows, then is sliced away."""
    dead = 0 if method == "pallas" else 1
    return hist_frontier(binned, g3, label, nslots + dead, num_bins,
                         method=method, precision=precision,
                         packed=packed, num_features=num_features,
                         interpret=interpret)[:nslots]


def hist_wave_quant(
    binned: jax.Array,
    g3: jax.Array,
    label: jax.Array,
    nslots: int,
    num_bins: int,
    key: jax.Array,
    method: str = "scatter",
    packed: bool = False,
    num_features: int = 0,
    axis_name=None,
    interpret: bool = False,
):
    """Stochastic-rounded int8 wave histogram: quantize the gradient rows
    (ops/quantize.sr_quantize_g3 — deterministic counter-based rounding
    keyed by ``key``) and accumulate the INTEGER histogram.

    ``axis_name`` (row-sharded learners): pmax the quantization range
    across the named mesh axis so every shard's integer histogram shares
    one scale and the cross-chip reduction can run on raw int32 partials
    (see sr_quantize_g3).

    Returns ``(hist_q, scales)``: ``hist_q`` (nslots, F, B, 3) holds exact
    integer sums of the quantized rows, ``scales`` (nslots, 3) the per-slot
    dequantization multipliers.  The caller keeps the histogram in integer
    units as long as possible — the wave grower folds dequantization into
    the smaller-child subtraction, and ops/split.py's gain scan accepts
    ``hist_scale`` to dequantize after its (exact, integer) cumsum.

    On the ``pallas`` method this runs the int8 MXU path (one pass, 2x
    bf16 throughput, int8→int32 hierarchical widening); ``scatter`` and
    ``onehot`` accumulate the same integer rows exactly in f32, so every
    method produces the identical integer histogram (the property the
    oracle test pins, tests/test_int8sr.py)."""
    from .quantize import sr_quantize_g3

    with jax.named_scope("lgbm.hist_q"):
        q3, scales = sr_quantize_g3(g3, label, nslots, key,
                                    axis_name=axis_name)
        prec = "int8sr" if method == "pallas" else "f32"
        h = hist_wave(binned, q3, label, nslots, num_bins, method=method,
                      precision=prec, packed=packed,
                      num_features=num_features, interpret=interpret)
        return h, scales


def default_hist_method(config_method: str = "auto",
                        bin_dtype=None) -> str:
    """Pick the histogram implementation.

    TPU default is the Pallas kernel (validated vs the scatter oracle in
    tests/test_histogram.py, the analog of the reference's CompareHistograms
    debug comparator, gpu_tree_learner.cpp:71-98).  int16-binned data
    (num_bins > 256) routes to the XLA one-hot path — the Pallas kernel is
    uint8-only (see hist_pallas.hist_leaves_pallas).  A booster asks
    once, through ``parallel/trainer.resolve_hist_plan``.
    """
    if config_method != "auto":
        return config_method
    platform = jax.default_backend()
    if platform == "cpu":
        return "scatter"
    if platform != "tpu":
        # the kernel family is Pallas-TPU (Mosaic); handing it to another
        # accelerator would fail deep inside a lowering — name it here
        raise ValueError(
            f"hist_method=auto has no histogram kernel for platform "
            f"{platform!r} (tpu -> pallas, cpu -> scatter); name a "
            "hist_method explicitly")
    if bin_dtype is not None and jnp.dtype(bin_dtype).itemsize > 1:
        return "onehot"
    return "pallas"
