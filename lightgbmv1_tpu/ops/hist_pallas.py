"""Pallas TPU histogram kernel.

TPU-native replacement for the reference's OpenCL histogram kernels
(reference: ``src/treelearner/ocl/histogram{16,64,256}.cl`` — per-workgroup
local-memory sub-histograms with hand-rolled atomic float adds and a
cross-workgroup reduction, 2,299 LoC of OpenCL).

TPUs have no atomics; the design maps the OpenCL structure onto the MXU:

* a grid step owns a (rows × feature-block) tile and builds the bin one-hot
  for its whole feature block in VMEM, laid out ``(rows, bins*features)``
  via a tile-repeat of the bin ids (``pltpu.repeat``) compared against a
  ``lane // FBLK`` iota — nothing intermediate ever touches HBM, which is
  what made the pure-XLA one-hot path bandwidth-bound,
* the histogram update is ONE MXU matmul per tile:
  ``(M, rows) @ (rows, bins*features)``, whose left operand holds the
  per-leaf-masked gradient rows and nothing else (``pass_rows``: 3 rows a
  leaf, 5 under ``bf16x2``, padded as a whole to the MXU's row granule),
  built by an iota-vs-leaf compare (cheap VPU work),
* the per-workgroup local histogram of the OpenCL kernels becomes a VMEM
  f32 accumulator block revisited across the row-tile grid dimension (the
  analog of ``within_kernel_reduction256x4``, histogram256.cl:139-310,
  without the atomic counter dance),
* precision modes replace the OpenCL ``USE_DP_FLOAT`` switch:
    - ``int8``  — per-tile-quantized gradients on the int8 MXU path (2×
      bf16 throughput; counts are exact via a power-of-two scale). The
      TPU analog of LightGBM's quantized-histogram training.
    - ``int8sr``— PRE-quantized gradients (ops/quantize.sr_quantize_g3:
      stochastic rounding, deterministic counter-based PRNG) on the same
      int8 MXU path with hierarchical widening: int8 multiplicands →
      int32 MXU accumulators → exact integer f32 across row tiles.  The
      kernel does NO scale math at all — neither the per-tile amax
      reduction of ``int8`` nor the per-chunk dequant multiply — and
      emits the RAW integer histogram; the caller holds the scales and
      dequantization is folded into the consumer (the split scan /
      smaller-child subtraction), so the histogram write stream carries
      no extra pass.  Integer accumulation in f32 is exact to 2^24
      (±127 per row ⇒ exact beyond 130k rows per (leaf, bin) cell —
      far past any real bin occupancy at bench shapes).
    - ``bf16``  — single bf16 pass (the GPU learner's single-precision
      default, gpu_tree_learner.h:79).
    - ``bf16x2``— hi/lo-split bf16, ~fp32 accuracy at 2 MXU passes.
    - ``f32``   — exact; used by tests/CPU.

The bin operand is made by ONE function, ``prepare_hist_bins``.  A learner
calls it once at placement and hands ``hist_leaves_pallas`` the result
(``HistBins``): the serial learner for the whole matrix, the row-sharded
learners (``tree_learner=data`` and ``voting``) once a chip, each chip for
its own shard of the rows inside one ``shard_map`` (it pads its own rows,
so a global block is ``chips x n_pad_loc`` rows tall and a chip holds the
``n_pad_loc`` of its rows).  The raw ``(F, N)`` matrix is left to the
streamed blocks (``grower_stream.py``), ``tree_learner=feature`` and a
learner whose operand is over the budget below: they get the same layout
made inside the pass, every pass (pad + transposition, and in the block
form one slice per block: 7-9x the bins in temporaries).  With a prepared
operand the HBM traffic of a pass is the stored arrays + g3 + leaf_id and
nothing else.  A TPU tiles a ``u8`` array ``T(8,128)(4,1)``, so a row of a
stored array occupies 128 byte lanes whatever its shape says, and the
operand costs ``arrays x n_pad x 128`` bytes (``prepared_bins_bytes``).

The operand has two forms, and a pass reads which off the ``HistBins`` it
is handed (``windows``), not off the bin count:

* **block**: each feature block its own row-major ``u8[n_pad, 128]``
  array, ``tile_cols`` live columns and lane padding.  Stored AT lane
  width, the array's default device layout is the row-major one the
  kernel's call takes (a tall ``u8[n, 32]`` array would be stored
  column-major and copied in every pass) and the pass's column slice back
  to ``tile_cols`` is a bitcast there.  The 64 rung's 32-column blocks
  store 4x the bins' own bytes at 128 columns and 5.7x at 67.  **The 16
  rung's blocks are repeated** (``_feature_blocks``): a block of up to 128
  features, unpacked where the matrix is ``packed4``, padded to a power of
  two ``fblk`` of at least 8 and copied ``128 // fblk`` times across the
  lanes (lane l holds feature l % fblk: 4 copies of 32 at 28 features), so
  the call takes the whole array and the kernel builds its one-hot by
  repeating whole vregs of the tile, with no nibble unpack or lane
  repeat of narrow pieces left in the pass.  The bytes are the ones a
  block stored before it was repeated.
* **dense** (lane-dense): the matrix's columns side by side, 128 a
  ``u8[n_pad, 128]`` array, and feature block ``fb`` of a pass is the
  static window of ``tile_cols`` columns ``(fb % windows) * tile_cols``
  columns into array ``fb // windows`` (``windows`` = 4 of the 64 rung's
  32-column blocks, 16 of the 256 rung's 8-column ones).  Each call takes
  the whole array a (T, 128) tile at a time and the kernel picks its window
  with the MXU (``_kernel``: a 128 x 128 selection, which also repeats the
  window across the lanes).  The 256 rung has this form only: one array a
  block would store 16x its bins.

**The bytes rule** (``hist_bins_form``, called by
``trainer._place_hist_bins``; one budget, no option): the operand may hold a
quarter of a device's ``bytes_limit`` (4,227,334,016 B on the v5e), judged
on the rows ONE device holds.  Three outcomes: the block form where it fits
(the 16 and 64 rungs); else the lane-dense form where that fits (the 64 and
256 rungs); else the raw matrix.  The benchmark's five cells:
``mslr-train`` block, 5 x ``u8[2271232,128]`` = 1,453,588,480 B;
``epsilon-train`` block, 63 x ``u8[400384,128]`` = 3,228,696,576 B;
``criteo-dp4-train`` block, 3 x ``u8[4000768,128]`` = 1,536,294,912 B a
chip; ``higgs-255b-train`` dense, one ``u8[10500096,128]`` =
1,344,012,288 B (5,376,049,152 as one array a block); ``criteo-tall-train``
(26,562,500 x 67) dense, one ``u8[26562560,128]`` = 3,400,007,680 B, its
block form 10,200,023,040 B being 2.4x the rule.  At 67 columns the block
form reaches 11.0 M rows a device, the dense form 33.0 M, the raw matrix
whatever fits beside its passes' temporaries.

**The row tile** (``_row_tile_for``; one VMEM budget, no option): a call's
grid step takes the largest of 1024 / 512 / 256 / 128 rows whose VMEM
estimate fits 12 MB, the same rule on every rung and form.  Every slot
bucket a 255-leaf tree runs (1 / 4 / 16 / 63 slots) takes 1024 rows on all
three rungs, 512 / 2,048 lanes alike; 128 slots take 512, 255 slots at
2,048 lanes 256.  A stored operand is padded to ``MAX_ROW_TILE`` rows
once, at placement.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MAX_LANES = 2048          # lanes per one-hot block: FBLK * num_bins
MAX_ROW_TILE = 1024       # the largest row tile _row_tile_for returns: rows
                          # padded to it serve every slot bucket's tile
_VMEM_BUDGET = 12 * 2**20  # _row_tile_for's ceiling on its VMEM estimate
_LANES = 128              # byte columns a stored u8 row occupies at least
_COUNT_SCALE = 64.0       # power-of-two count quantizer => exact counts
# ``jax.named_scope`` of what a pass does to make the kernel's operands and
# is not the kernel: the bin layout where the pass was handed the raw
# matrix (every pass of a streamed block, of tree_learner=feature and of a
# learner over the bytes rule's budget), and g3's and the labels' padding
# and transposition.  Inside ``lgbm.hist`` wherever the pass is; placement's
# one-off layout is set-up and has none.
LAYOUT_SCOPE = "lgbm.layout"


def kernel_width(num_bins: int) -> int:
    """Static kernel-width rung for a bin count — the TPU analog of the
    reference's histogram16/64/256 OpenCL kernel ladder
    (src/treelearner/ocl/histogram{16,64,256}.cl): every caller
    specializes its tiling on the rung, not the raw bin count, so two
    configs on the same rung compile the same kernel.  The <=16 rung is
    the 4-bit packed leg's home: only there can a bin id live in a
    nibble (``pack4bit``)."""
    if num_bins <= 16:
        return 16
    if num_bins <= 64:
        return 64
    if num_bins <= 256:
        return 256
    raise ValueError("uint8 kernel family holds num_bins <= 256; route "
                     "int16-binned data to the onehot/scatter path")


# rows the MXU's left operand packs into one sublane tile, by its dtype
_ROW_GRANULE = {"f32": 8, "bf16": 16, "bf16x2": 16, "int8": 32, "int8sr": 32}


def pass_rows(num_leaves: int, precision: str) -> Tuple[int, int, int]:
    """``(m_pad, m_live, out_rows)`` of a pass over ``num_leaves`` slots.

    The left operand of the kernel's product is channel-major: rows
    ``[g | h | c]``, ``num_leaves`` each, and under ``bf16x2`` the lo terms
    ``[g | h]`` below them — the count is 0.0 or 1.0, exact in bfloat16, so
    its lo term is zero in every row and is not built.  ``m_live`` of its
    rows carry data; ``m_pad`` rounds THAT up to the dtype's row granule
    (slots are never rounded: a 1-slot root pass is 5 live rows in 16).
    The result block is the hi + lo sum, ``3 * num_leaves`` rows rounded
    up to the f32 tile's 8."""
    m_live = (5 if precision == "bf16x2" else 3) * num_leaves
    g = _ROW_GRANULE[precision]
    return -(-m_live // g) * g, m_live, -(-3 * num_leaves // 8) * 8


def _row_tile_for(m_pad: int, num_lanes: int) -> int:
    """Row-tile size keeping the VMEM working set (chunked one-hot + repeat
    buffer + lg rows + out accumulator) within Mosaic's ~16MB scoped-vmem
    budget.  ``m_pad`` is the result block's rows (``pass_rows``' third):
    the left operand's own rows, up to 5/3 of them, ride in the estimate's
    16 bytes a row.  The estimate is deliberately conservative: per-chunk f32
    temporaries (repeat buffer, compare, select, cast) can coexist.  No
    ``compiler_params`` is passed, so the chip's default scoped limit
    applies.

    One budget, ``_VMEM_BUDGET`` (12 MB), for every rung, operand form and
    precision: the tile is a pure function of the two shapes.  It admits
    1024 rows up to 64 slots at 2,048 lanes (M 192: 12.06 MB) and 512
    beyond (128 slots: 16.8 MB at 1024 rows, 9.97 at 512).  Every shape
    chip_smoke.py runs (16/64/256 bins, 1-64 slots, the default-policy
    precisions, packed4, the 64 rung's lane-dense operand) compiles under
    it and runs on TPU v5 lite; the compiler alone also accepted 1-255
    slots of all five precisions at 256 and 128 bins.  A grid step has a
    fixed cost (the pipeline step, the accumulator's read-add-write), so
    the larger tile pays where the product is large.  Ms a pass (one call,
    g3's layout inside) at 512 / 1024 rows, 16 slots (``bf16x2``) and 63
    (``bf16``), on the v5e (chip run of 2026-10-18):

    * 16 rung, 10,500,096 rows, 32 x 16 lanes: 9.58 / 7.33, 16.73 / 14.42;
    * 64 rung, block form, 32 x 64 lanes: 2,271,232 rows 6.35 / 5.80,
      12.06 / 11.24; 4,000,768 rows 11.10 / 10.13, 21.12 / 19.68; 400,384
      rows 1.17 / 1.08, 2.16 / 2.04;
    * 64 rung, lane-dense, 26,562,560 rows: 80.40 / 70.77, 143.45 / 131.08;
    * 256 rung, 10,500,096 rows, 8 x 256 lanes: 31.62 / 27.96, 57.03 /
      51.86.

    The 256 rung's blocks are always whole windows of a 128-lane tile,
    whatever the matrix's width."""
    out_bytes = m_pad * num_lanes * 4
    per_row = 14 * min(num_lanes, 512) + 16 * m_pad
    for t in (MAX_ROW_TILE, 512, 256, 128):
        if out_bytes + t * per_row <= _VMEM_BUDGET:
            return t
    return 128


def _kernel(iota_ref, bins_ref, g3_ref, leaf_ref, out_ref, *, num_leaves,
            num_bins, fblk, precision, interpret, window=None):
    """Grid: (feature_blocks, row_tiles); out revisited across row tiles.

    iota_ref: (1, FBLK*B) f32          — precomputed ``lane // FBLK`` pattern
                                         (bin ids are < 256 => exact;
                                         v5e has no int8 vector compare)
    bins_ref: (T, FBLK) uint8          — row-major bin tile of one block
                                         (the 64 rung's block form); a
                                         (T, 128) tile in the two 128-lane
                                         forms: with ``window`` a tile of
                                         a lane-dense array, of which the
                                         block is the FBLK columns from
                                         column ``window`` on; without, the
                                         16 rung's repeated block, lane l
                                         holding feature l % FBLK (unpacked
                                         at placement, packed or not)
    g3_ref:   (3, T) f32               — grad / hess / count (pre-transposed)
    leaf_ref: (1, T) int32             — leaf id per row, never negative;
                                         a row whose id is num_leaves or
                                         more adds to nothing
    out_ref:  (1, out_rows, FBLK*B) f32 — rows are (channel-major,
                                         leaf-minor): ``pass_rows``
    """
    rt = pl.program_id(1)
    B = num_bins
    L = num_leaves
    m_pad, m_live, out_rows = pass_rows(L, precision)

    @pl.when(rt == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    def rep(x, n, axis):
        if interpret:
            reps = [1, 1]
            reps[axis] = n
            return jnp.tile(x, reps)
        return pltpu.repeat(x, n, axis)

    # --- the left operand's rows, described once on an (M, 1) column -------
    # row r is piece r // L (0..2: g / h / c; 3..4: the lo terms of g / h)
    # of leaf r % L.  A padding row (r >= m_live) takes leaf id -1, which
    # no row carries: it stays zero whatever m_pad is.
    r = lax.broadcasted_iota(jnp.int32, (m_pad, 1), 0)
    piece = r // L
    row_leaf = jnp.where(r < m_live, r - piece * L, -1)
    chan = jnp.where(piece >= 3, piece - 3, piece)
    is_lo = piece >= 3

    def rows_of(v3):
        """(3, n) channel values -> (M, n): each row its channel's."""
        return jnp.where(chan == 0, v3[0:1],
                         jnp.where(chan == 1, v3[1:2], v3[2:3]))

    mine = row_leaf == leaf_ref[...]                         # (M, T) bool
    g3 = g3_ref[...]                                         # (3, T) f32

    # VPU constraints on this target: vector compare/select only in i32/f32;
    # narrow dtypes appear only via a final astype feeding the MXU.
    scale_rep = None
    if precision == "int8sr":
        # rows arrive PRE-quantized to exact integers in [-127, 127]
        # (ops/quantize.sr_quantize_g3); the leaf mask runs in f32 and the
        # int8 cast is the final op feeding the MXU — no scale math here
        lhs = jnp.where(mine, rows_of(g3), 0.0).astype(jnp.int8)
    elif precision == "int8":
        amax = jnp.max(jnp.abs(g3[:2]), axis=1, keepdims=True)       # (2, 1)
        inv = jnp.where(amax > 0, 127.0 / amax, 0.0)
        scale = jnp.where(amax > 0, amax / 127.0, 0.0)
        inv3 = jnp.concatenate(
            [inv, jnp.full((1, 1), _COUNT_SCALE, jnp.float32)], axis=0)
        scale3 = jnp.concatenate(
            [scale, jnp.full((1, 1), 1.0 / _COUNT_SCALE, jnp.float32)], axis=0)
        q3 = jnp.round(g3 * inv3)                                    # (3, T)
        lhs = jnp.where(mine, rows_of(q3), 0.0).astype(jnp.int8)
        scale_rep = rows_of(scale3)                                  # (M, 1)
    elif precision in ("bf16", "bf16x2"):
        lg = jnp.where(mine, rows_of(g3), 0.0)                # (M, T) f32
        if precision == "bf16x2":
            # hi rows hold bf16(x), lo rows bf16(x - bf16(x)): the split
            # sits inside the kernel (outside it the compiler keeps the
            # excess precision and the lo term reads zero)
            hi = lg.astype(jnp.bfloat16).astype(jnp.float32)
            lg = jnp.where(is_lo, lg - hi, hi)
        lhs = lg.astype(jnp.bfloat16)
    else:  # f32 — exact (HIGHEST forces true-f32 MXU passes)
        lhs = jnp.where(mine, rows_of(g3), 0.0)

    # --- bin one-hot, built in column chunks to bound VMEM -----------------
    # column b*FBLK + f is (feature f, bin b); the repeat pattern of the bin
    # ids over one chunk of bins is chunk-invariant, so it is hoisted.
    cb = max(1, min(B, 512 // fblk))         # bins per chunk
    n_chunks = -(-B // cb)
    bins_f = bins_ref[...].astype(jnp.int32).astype(jnp.float32)
    pattern = None
    if window is not None:
        # the block's FBLK columns, which start ``window`` columns into the
        # lane-dense tile, repeated across 128 lanes by the MXU: lane l of
        # the product is column window + l % FBLK (one 1.0 a column of the
        # selection, bin ids < 256 are exact in bfloat16, so the product is
        # the id).  A lane repeat of 8-lane pieces cost 2.5x the whole
        # 32-feature build of the 64 rung (46.0 against 18.7 ms a root call
        # at 10.5 M rows, chip run of 2026-10-03).
        k = lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
        lane = lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)
        # (the interpreter's XLA:CPU dot has no bf16 x bf16 -> f32 at every
        # shape: it selects in f32, as exactly)
        dt = jnp.float32 if interpret else jnp.bfloat16
        sel = (k == window + (lane & (fblk - 1))).astype(dt)
        pattern = lax.dot_general(
            bins_f.astype(dt), sel, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=lax.Precision.HIGHEST if interpret else None)
    elif bins_f.shape[1] == _LANES:
        # the 16 rung's tile is its own repeat pattern: a chunk of bins is
        # whole vregs of it.  An in-kernel nibble unpack (a lane concatenate
        # of two 14-lane pieces) and a repeat of 28-lane pieces took 39-46
        # ms a call at 10.5 M rows x 28 features on the v5e, whatever the
        # slots
        pattern = bins_f

    for c in range(n_chunks):
        cb_c = min(cb, B - c * cb)
        sl = slice(c * cb * fblk, (c * cb + cb_c) * fblk)
        if pattern is not None:
            bw = rep(pattern, -(-cb_c * fblk // _LANES), 1)  # whole vregs
            bw = bw[:, :cb_c * fblk]
        else:
            bw = rep(bins_f, cb_c, 1)                        # (T, cb_c*FBLK)
        oh_cmp = bw == iota_ref[0:1, sl]
        # bool -> numeric cast IS the one-hot (exactly 1.0/0.0): a direct
        # convert, not a select pass — the one-hot build is the
        # slot-count-independent floor of the whole pass, so every VPU op
        # here is measurable in the roofline fraction
        if precision in ("int8", "int8sr"):
            prod = lax.dot_general(lhs, oh_cmp.astype(jnp.int8),
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.int32
                                   ).astype(jnp.float32)
            if scale_rep is not None:       # int8sr stays in integer units
                prod = prod * scale_rep
        elif precision in ("bf16", "bf16x2"):
            # bf16x2: ONE (5·L, T) @ (T, lanes) product shares the built
            # one-hot block between the hi and the lo accumulation —
            # the one-hot build + stream is the slot-count-independent
            # floor of the pass
            prod = lax.dot_general(lhs, oh_cmp.astype(jnp.bfloat16),
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        else:
            prod = lax.dot_general(
                lhs, oh_cmp.astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=lax.Precision.HIGHEST)
        if precision == "bf16x2":
            # g and h: hi + lo, each output row's own two f32 dots added in
            # that order before it joins the accumulator; the count is its
            # hi dot alone (its lo dot was +0.0 on a non-negative sum)
            out_ref[0, :2 * L, sl] += prod[:2 * L] + prod[3 * L:5 * L]
            out_ref[0, 2 * L:3 * L, sl] += prod[2 * L:3 * L]
        else:
            out_ref[0, :, sl] += prod[:out_rows]


def pack4bit(binned: np.ndarray) -> np.ndarray:
    """(F, N) uint8 bins < 16 -> (ceil(F/2), N) packed bytes, two features
    per byte (lo nibble = feature 2p, hi = 2p+1) — the analog of the
    reference's 4-bit dense bins (DenseBin<VAL_T, IS_4BIT=true>,
    src/io/dense_bin.hpp:52): at max_bin <= 15 it halves the stored
    matrix in HBM and what a partition round reads of it
    (``partition_pallas`` decodes the nibble).  It does not halve the
    histogram pass's read: ``prepare_hist_bins`` unpacks the matrix once,
    at placement, and repeats each block across an array's 128 lanes, the
    same bytes packed or not."""
    binned = np.asarray(binned)
    F, N = binned.shape
    if F % 2:
        binned = np.concatenate(
            [binned, np.zeros((1, N), binned.dtype)], axis=0)
    return (binned[0::2] | (binned[1::2] << 4)).astype(np.uint8)


def unpack4bit(packed, num_features: int):
    """(ceil(F/2), N) packed bytes -> (F, N) uint8 bins — ``pack4bit``'s
    inverse in natural feature order (works on numpy and jnp arrays, so
    the streaming cache can ship packed bytes over PCIe and unpack ON
    DEVICE).  The phantom hi-nibble feature of an odd-F tail is sliced
    away."""
    xp = jnp if isinstance(packed, jax.Array) else np
    lo = packed & 15
    hi = packed >> 4
    un = xp.stack([lo, hi], axis=1).reshape(2 * packed.shape[0],
                                            packed.shape[1])
    return un[:num_features].astype(xp.uint8)


def packed_bins_of_feat(binned, feat):
    """(ceil(F/2), N) packed bytes -> (N,) bins of ORIGINAL feature ``feat``
    (traced scalar).  The single source of truth for the nibble layout
    (lo nibble = feature 2p, hi = 2p+1) outside the kernel."""
    byte = binned[feat >> 1].astype(jnp.int32)
    return (byte >> (4 * (feat & 1))) & 15


def packed_bins_of_rows(binned, f_row):
    """Per-row feature variant: ``f_row`` (N,) -> (N,) original bins."""
    byte = jnp.take_along_axis(
        binned, (f_row >> 1)[None, :], axis=0)[0].astype(jnp.int32)
    return (byte >> (4 * (f_row & 1))) & 15


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["matrix", "blocks"],
                   meta_fields=["tile_cols", "windows"])
@dataclasses.dataclass(frozen=True)
class HistBins:
    """The kernel's bin operand, made once for a dataset by
    ``prepare_hist_bins``: row-major ``u8[n_pad, 128k]`` arrays, each
    holding ``windows`` feature blocks of ``tile_cols`` byte columns side by
    side (module docstring).  With ``windows`` 1 (the block form: the 16
    rung, and the 64 rung where the bytes rule admits it)
    ``blocks[fb][:, :tile_cols]`` is feature block ``fb``, the operand of
    that block's ``pallas_call``, and the columns beyond are the lane
    padding the device would add anyway (the 16 rung's ``tile_cols`` is
    128: its block, unpacked, fills the lanes with copies of itself); with
    more (the lane-dense form: the 256 rung, and the 64 rung over the
    rule) block ``fb`` is columns
    ``(fb % windows) * tile_cols ...`` of ``blocks[fb // windows]``, which
    the call takes whole.  ``windows`` IS the form: a pass reads it here.
    ``matrix`` is the untouched ``(F, N)``
    matrix (packed: ``(ceil(F/2), N)``) the blocks were cut from, which
    everything but the histogram pass (partition decisions, tree walks)
    keeps reading.  Under a row-sharded learner every chip made the blocks
    of its own shard: globally ``matrix`` is split on its rows
    ``P(None, rows)``, a block ``P(rows, None)`` with ``chips x n_pad_loc``
    rows, and inside the learner's ``shard_map`` both are a shard's (the
    block's rows past the shard's are that chip's own padding).  A pytree
    of arrays; ``tile_cols`` and ``windows`` are static."""

    matrix: jax.Array
    blocks: Tuple[jax.Array, ...]
    tile_cols: int
    windows: int = 1


def bin_matrix(binned) -> jax.Array:
    """The ``(F, N)`` bin matrix of a ``binned`` argument, prepared or raw."""
    return binned.matrix if isinstance(binned, HistBins) else binned


def _dense(num_bins: int, dense: bool) -> bool:
    """Whether the operand of such a pass is lane-dense: the 256 rung's
    always, the 64 rung's where asked (``dense``: the form the bytes rule
    chose, ``hist_bins_form``), the 16 rung's never: its blocks already
    fill an array's 128 lanes, repeated (``_feature_blocks``)."""
    rung = kernel_width(num_bins)
    return rung == 256 or (dense and rung == 64)


def _feature_blocks(stored_rows: int, num_bins: int, packed: bool,
                    dense: bool = False):
    """``(fblk, tile_cols, nfb)`` of a matrix with ``stored_rows`` feature
    rows: features per one-hot block, byte columns of a block's call, and
    the number of blocks.

    The 16 rung's blocks are **repeated**: ``fblk`` (unpacked) features, a
    power of two from 8 to 128, copied ``128 // fblk`` times across the 128
    lanes, so that lane l of a row holds feature l % fblk and a chunk of
    ``512 // fblk`` bins of the one-hot is whole vregs of the tile
    (``_kernel``).  A call takes the whole array (``tile_cols`` 128); packed
    bins (two features a byte, ``stored_rows`` the bytes) are unpacked by
    ``prepare_hist_bins``."""
    if kernel_width(num_bins) == 16:
        features = 2 * stored_rows if packed else stored_rows
        fblk = max(8, 1 << (min(features, _LANES) - 1).bit_length())
        return fblk, _LANES, -(-features // fblk)
    if _dense(num_bins, dense):
        # a window of a lane-dense array: always whole, whatever the matrix
        # holds (columns beyond it are padding, sliced away by the pass),
        # and a power of two, so that windows tile the 128 lanes (8 columns
        # at 256 bins, 16 at 128, 32 at 64)
        fblk = tile_cols = 1 << ((MAX_LANES // num_bins).bit_length() - 1)
    else:
        fblk = max(1, min(stored_rows, MAX_LANES // num_bins))
        tile_cols = fblk
    return fblk, tile_cols, -(-stored_rows // tile_cols)


def _block_windows(tile_cols: int, num_bins: int, dense: bool = False) -> int:
    """Feature blocks one stored array holds side by side: in the
    lane-dense form as many as tile its 128 lanes (16 of the 256 rung's
    8-column blocks, 4 of the 64 rung's 32-column ones), else one."""
    return _LANES // tile_cols if _dense(num_bins, dense) else 1


def prepared_bins_bytes(stored_rows: int, num_rows: int, num_bins: int,
                        packed: bool = False, dense: bool = False) -> int:
    """Bytes of the blocks ``prepare_hist_bins`` makes of such a matrix."""
    _, tile_cols, nfb = _feature_blocks(stored_rows, num_bins, packed, dense)
    windows = _block_windows(tile_cols, num_bins, dense)
    n_pad = -(-num_rows // MAX_ROW_TILE) * MAX_ROW_TILE
    return -(-nfb // windows) * n_pad * (-(-tile_cols // _LANES) * _LANES)


def hist_bins_form(stored_rows: int, num_rows: int, num_bins: int,
                   packed: bool, budget: Optional[int]):
    """The bytes rule (module docstring), one pure function: ``(form,
    need)`` of a matrix of ``stored_rows`` stored columns and the
    ``num_rows`` rows ONE device holds.  ``need`` maps each form the rung
    has to the bytes of its operand: ``"block"`` (one array a feature
    block: the 16 and 64 rungs and packed bins) and ``"dense"`` (the
    lane-dense arrays: the 64 and 256 rungs).  ``form`` is the first of
    them, in that order, that fits ``budget`` bytes, or ``"raw"`` where
    none does (the passes lay the matrix out themselves); no budget
    (``None``: XLA:CPU reports no limit) takes the first."""
    need = {}
    if kernel_width(num_bins) != 256:
        need["block"] = prepared_bins_bytes(stored_rows, num_rows, num_bins,
                                            packed)
    if _dense(num_bins, True):
        need["dense"] = prepared_bins_bytes(stored_rows, num_rows, num_bins,
                                            packed, dense=True)
    for form, cost in need.items():
        if budget is None or cost <= budget:
            return form, need
    return "raw", need


def _count_operand(features: int, tile_cols: int, windows: int, nfb: int,
                   num_bins: int, copies: int = 1) -> None:
    """Trace time: ``hist_operand_lanes{what}``, the byte columns a row of
    the fullest stored array occupies and the distinct features it
    carries, ``hist_block_copies{rung}``, the copies of one feature block
    a row holds (the 16 rung's repeated blocks), and
    ``hist_pass_blocks{rung}``, the kernel calls of a pass."""
    from ..obs.metrics import default_registry

    rung = str(kernel_width(num_bins))
    lanes = default_registry().gauge(
        "hist_operand_lanes",
        "Byte columns of a row of the histogram kernel's stored bin "
        "operand: as stored, and carrying a distinct feature",
        label_names=("what",))
    lanes.labels(what="stored").set(float(-(-tile_cols // _LANES) * _LANES))
    lanes.labels(what="live").set(float(min(features,
                                            windows * tile_cols // copies)))
    default_registry().gauge(
        "hist_block_copies",
        "Copies of one feature block a stored row of the histogram "
        "kernel's bin operand holds", label_names=("rung",)).labels(
            rung=rung).set(float(copies))
    default_registry().gauge(
        "hist_pass_blocks", "Kernel calls of one histogram pass",
        label_names=("rung",)).labels(rung=rung).set(float(nfb))


# Rows one step of the 16 rung's layout makes.  The whole matrix's unpack
# and repeat in one program compiled for the v5e in 23-180 s at 10.5 M rows
# (its compile time grows with the rows); a loop over steps of this many
# compiles in about a second, and holds one step's temporaries.
_LAYOUT_ROWS = 131072


def _repeated_blocks(binned, packed: bool, fblk: int, nfb: int,
                     n_pad: int) -> Tuple[jax.Array, ...]:
    """The 16 rung's arrays (``_feature_blocks``): ``nfb`` row-major
    ``u8[n_pad, 128]``, lane l of array ``a`` holding feature
    ``a * fblk + l % fblk`` (bin 255 past the matrix's features, which
    matches no bin below 16), a packed matrix split into its nibbles on
    the way, ``_LAYOUT_ROWS`` rows a step."""
    stored, N = binned.shape
    features = 2 * stored if packed else stored
    rows = jnp.pad(binned, ((0, 0), (0, n_pad - N)))
    step = min(_LAYOUT_ROWS, n_pad)

    def lay(x):                   # (stored, step) -> nfb x (step, 128)
        x = x.astype(jnp.int32)
        if packed:                # lo nibble feature 2p, hi 2p+1 (pack4bit)
            x = jnp.stack([x & 15, x >> 4], axis=1).reshape(features, step)
        x = jnp.pad(x, ((0, nfb * fblk - features), (0, 0)),
                    constant_values=255).reshape(nfb, fblk, step)
        x = jnp.tile(x, (1, _LANES // fblk, 1))
        return [a.T.astype(jnp.uint8) for a in x]

    def body(k, out):
        # the last step is clamped back onto the rows' end: it lays some
        # rows out twice, alike
        start = jnp.minimum(k * step, n_pad - step)
        made = lay(lax.dynamic_slice_in_dim(rows, start, step, axis=1))
        return tuple(lax.dynamic_update_slice_in_dim(o, m, start, axis=0)
                     for o, m in zip(out, made))

    out = tuple(jnp.zeros((n_pad, _LANES), jnp.uint8) for _ in range(nfb))
    return lax.fori_loop(0, -(-n_pad // step), body, out)


def prepare_hist_bins(binned: jax.Array, num_bins: int, packed: bool = False,
                      row_tile: int = MAX_ROW_TILE,
                      resident: bool = True, dense: bool = False) -> HistBins:
    """The ONLY place the kernel's bin layout is made: ``(F, N)`` uint8
    bins (packed: ``(ceil(F/2), N)``) -> ``HistBins``.

    Rows are padded to a multiple of ``row_tile`` — by default the largest
    tile ``_row_tile_for`` returns, so one layout serves every slot
    bucket's tile; padded rows carry zero g3, so a pass whose tile is
    smaller adds zeros from the extra all-padding tiles and its sums stay
    bit-identical.  Padded features get bin 255 (matches no b < 256 when
    B < 256; for B == 256 they land in bin 255 of a feature the caller
    slices away).  The 16 rung's blocks are repeated across the lanes
    (``_feature_blocks``), a packed matrix unpacked here first: the phantom
    feature of an odd count (its nibble 0) is sliced away by the pass.

    ``resident`` blocks are what a learner keeps on the device: stored at
    lane width (module docstring; the padding columns are never read).
    ``hist_leaves_pallas`` passes False where it was handed the raw matrix
    and makes the layout inside the pass, consumed at once at its own
    width; lane-dense arrays and the 16 rung's are the same either way.
    ``dense`` asks the 64 rung for the lane-dense form (``hist_bins_form``
    says when); the 256 rung has no other.
    Traceable; each trace counts in ``hist_bins_layout_total`` under
    ``site="placement"`` (resident) or ``"pass"``."""
    if binned.dtype not in (jnp.uint8, np.uint8):
        raise ValueError(
            "hist_leaves_pallas requires uint8 bins (num_bins <= 256); "
            "route int16-binned data to the onehot/scatter path")
    if packed and num_bins > 16:
        raise ValueError("packed (4-bit) bins require num_bins <= 16")
    from ..obs.metrics import default_registry

    default_registry().counter(
        "hist_bins_layout_total",
        "Traces that lay the histogram kernel's bin operand out, by site",
        label_names=("site",)).labels(
            site="placement" if resident else "pass").inc()
    stored, N = binned.shape
    fblk, tile_cols, nfb = _feature_blocks(stored, num_bins, packed, dense)
    windows = _block_windows(tile_cols, num_bins, dense)
    features = 2 * stored if packed else stored
    _count_operand(features, tile_cols, windows, nfb, num_bins,
                   tile_cols // fblk)
    n_pad = -(-N // row_tile) * row_tile
    if kernel_width(num_bins) == 16:
        return HistBins(binned, _repeated_blocks(binned, packed, fblk, nfb,
                                                 n_pad), tile_cols, windows)
    # byte columns of one stored array, and how many arrays
    width = _LANES if windows > 1 else tile_cols
    n_arrays = -(-nfb // windows)
    binned_rm = jnp.pad(
        binned, ((0, n_arrays * width - stored), (0, n_pad - N)),
        constant_values=255).T                      # (n_pad, arrays*width)
    blocks = [binned_rm[:, a * width:(a + 1) * width]
              for a in range(n_arrays)]
    if resident and width % _LANES:
        blocks = [jnp.pad(b, ((0, 0), (0, -width % _LANES)),
                          constant_values=255) for b in blocks]
    return HistBins(binned, tuple(blocks), tile_cols, windows)


def _count_pass_rows(slots: int, precision: str, num_bins: int,
                     row_tile: int) -> None:
    """Trace time: the rows of a pass's MXU left operand, padded and live,
    in ``hist_pass_mxu_rows`` / ``hist_pass_live_rows{slots,precision}``,
    and the rows of its grid step in
    ``hist_pass_row_tile{rung,slots,precision}``."""
    from ..obs.metrics import default_registry

    m_pad, m_live, _ = pass_rows(slots, precision)
    for name, rows, what in (
            ("hist_pass_mxu_rows", m_pad, "as padded to the row granule"),
            ("hist_pass_live_rows", m_live, "that carry data")):
        default_registry().gauge(
            name, "Rows of the histogram kernel's MXU left operand " + what,
            label_names=("slots", "precision")).labels(
                slots=str(slots), precision=precision).set(float(rows))
    default_registry().gauge(
        "hist_pass_row_tile", "Rows of one grid step of a histogram pass",
        label_names=("rung", "slots", "precision")).labels(
            rung=str(kernel_width(num_bins)), slots=str(slots),
            precision=precision).set(float(row_tile))


@functools.partial(
    jax.jit,
    static_argnames=("num_leaves", "num_bins", "precision", "row_tile",
                     "interpret", "packed", "num_features"),
)
def hist_leaves_pallas(
    binned,                 # HistBins, or the raw (F, N) uint8 matrix
                            # (packed: (ceil(F/2), N)) laid out in the pass
    g3: jax.Array,          # (N, 3) f32: grad, hess, count — the count a
                            # 0/1 row mask (exact in bfloat16: bf16x2
                            # gives it no lo term)
    leaf_id: jax.Array,     # (N,) int32; outside [0, num_leaves): dropped
    num_leaves: int,
    num_bins: int,
    precision: str = "int8",
    row_tile: int = 0,
    interpret: bool = False,
    packed: bool = False,
    num_features: int = 0,  # REAL feature count when packed (else derived)
) -> jax.Array:             # (L, F, B, 3) f32
    L, B = num_leaves, num_bins
    stored, N = bin_matrix(binned).shape
    F = (num_features or 2 * stored) if packed else stored
    # the form is the operand's own: the 64 rung has two (module docstring)
    dense = isinstance(binned, HistBins) and binned.windows > 1
    fblk, tile_cols, nfb = _feature_blocks(stored, B, packed, dense)
    windows = _block_windows(tile_cols, B, dense)
    f_pad = nfb * fblk
    out_rows = pass_rows(L, precision)[2]
    T = row_tile if row_tile > 0 else _row_tile_for(out_rows, fblk * B)
    _count_pass_rows(L, precision, B, T)

    if isinstance(binned, HistBins):
        _count_operand(2 * stored if packed else stored, tile_cols, windows,
                       nfb, B, tile_cols // fblk)
    else:       # counted there
        with jax.named_scope(LAYOUT_SCOPE):
            binned = prepare_hist_bins(binned, B, packed, row_tile=T,
                                       resident=False)
    n_pad = binned.blocks[0].shape[0]
    if (n_pad < N or n_pad % T or len(binned.blocks) != -(-nfb // windows)
            or (binned.tile_cols, binned.windows) != (tile_cols, windows)):
        raise ValueError(
            f"prepared bins ({len(binned.blocks)} arrays, blocks of "
            f"{binned.tile_cols} columns, {binned.windows} an array, "
            f"{n_pad} rows) do not fit this pass ({nfb} blocks of "
            f"{tile_cols} columns, {windows} an array, {N} rows in tiles "
            f"of {T}): prepare them with the pass's num_bins / packed")
    nrt = n_pad // T

    # padded rows carry zero g3 => no effect; they and every row labelled
    # outside [0, L) (a wave's dead rows) take the one id, L, that no row of
    # the left operand has, whatever its padding
    with jax.named_scope(LAYOUT_SCOPE):
        g3t = jnp.pad(g3.astype(jnp.float32),
                      ((0, n_pad - N), (0, 0))).T           # (3, n_pad)
        leaf_id = leaf_id.astype(jnp.int32)
        leaf_p = jnp.pad(
            jnp.where((leaf_id >= 0) & (leaf_id < L), leaf_id, L),
            (0, n_pad - N), constant_values=L)[None, :]      # (1, n_pad)

        iota_bins = (jnp.arange(B * fblk, dtype=jnp.int32)
                     // fblk).astype(jnp.float32)[None, :]   # (1, B*fblk)

    def one_block(bins_block, window=None):
        # Mosaic requires the bins block's lane dim to equal the array dim
        # (or be 128-divisible), so each feature block is its own call; the
        # row-tile grid dimension does the accumulation.  A lane-dense
        # array is taken 128 columns at a time and the kernel cuts the
        # block's static ``window`` of them.
        kernel = functools.partial(
            _kernel, num_leaves=L, num_bins=B, fblk=fblk,
            precision=precision, interpret=interpret, window=window)
        return pl.pallas_call(
            kernel,
            grid=(1, nrt),
            in_specs=[
                pl.BlockSpec((1, fblk * B), lambda fb, rt: (0, 0)),
                pl.BlockSpec((T, bins_block.shape[1]),
                             lambda fb, rt: (rt, 0)),
                pl.BlockSpec((3, T), lambda fb, rt: (0, rt)),
                pl.BlockSpec((1, T), lambda fb, rt: (0, rt)),
            ],
            out_specs=pl.BlockSpec((1, out_rows, fblk * B),
                                   lambda fb, rt: (0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((1, out_rows, fblk * B),
                                           jnp.float32),
            interpret=interpret,
        )(iota_bins, bins_block, g3t, leaf_p)

    if windows > 1:
        blocks = [one_block(binned.blocks[fb // windows],
                            (fb % windows) * tile_cols)
                  for fb in range(nfb)]
    else:
        blocks = [one_block(b[:, :tile_cols]) for b in binned.blocks]
    out = jnp.concatenate(blocks, axis=0) if nfb > 1 else blocks[0]

    # (nfb, out_rows, B*fblk) -> (L, F, B, 3)
    h = out[:, :3 * L].reshape(nfb, 3, L, B, fblk)
    h = h.transpose(2, 0, 4, 3, 1).reshape(L, f_pad, B, 3)
    return h[:, :F]
