"""One wave round's partition of the training rows, in one pass over the
bin matrix.

``models/grower_wave.round_pass`` decides a round's ``S`` splits for every
row: the row's bin at each split's feature, ``ops/split.go_left_rule``, then
a masked sum over the slots that gives the row's new leaf id and its
histogram label (``assign_rows``).  Two memory forms of that one algorithm:

* **gather** (``round_pass`` itself, plain XLA): ``bins[feats_s]`` gathers
  ``S`` rows of the ``u8`` ``(F, N)`` matrix into an ``(S, N)`` array in
  HBM, which is widened to int32 and read back for the decisions: about 10
  bytes a slot-row, 39-45 ps a slot-row on a v5e whatever the cell
  (PERF.md section 6, PR 35).
* **kernel** (``partition_pallas``, here): a grid step owns a block of rows
  (rows on the lanes, as ``ops/leaf_sums.py`` has them), reads the
  ``(F, T)`` block of the matrix and the ``(T,)`` leaf ids, picks the
  ``S`` feature rows out of the block with one int8 MXU product of an
  ``(S, F)`` 0/1 selection (a row of the selection holds one 1, so the
  product **is** the selected byte: the argument of
  ``hist_pallas._kernel``'s window selection), makes the ``(S, T)``
  decisions in VMEM with ``go_left_rule`` itself and writes the ``(T,)``
  leaf ids and labels.  Nothing ``(S, N)``-shaped touches HBM; the matrix
  is read whole, ``F_pad32 x N`` bytes, where the gather reads ``S`` rows.

The kernel reads both layouts a booster stores its bins in: ``u8``, one
row a feature, and ``packed4`` (``hist_pallas.pack4bit``, ``max_bin <=
15``), ``ceil(F/2)`` rows with feature ``f`` in the nibble ``4 * (f & 1)``
bits up of row ``f >> 1``: the selection picks the byte row and a shift
and a mask on the ``(S, T)`` product give the bin id, as
``hist_pallas.packed_bins_of_feat`` states the layout.  EFB bundle
columns and categorical columns keep the gather form.

``partition_path`` says which form a round takes, from shapes alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .hist_pallas import packed_bins_of_feat
from .split import MISSING_NONE, go_left_rule

KERNEL_NAME = "partition_pallas"
_ROW_CHUNK = 1024       # rows whose (S, rows) decisions are live at once
_U8_ROWS = 32           # sublanes of a u8 tile: a stored matrix pads to it
# rows a grid step owns (lanes of its blocks): as many as keep its block of
# the matrix at _BLOCK_BYTES, double-buffered beside the (8, rows) int32
# blocks of the ids and the chunk's (S, 1024) temporaries, all under the
# 16 MB a kernel may take
_BLOCK_BYTES = 2 << 20
_MAX_ROW_BLOCK = 16384

# What a row of one round costs in each form, picoseconds on a v5e, fitted
# from the traced benchmark cells of 2026-10-03 (per call, in the step;
# PERF.md section 6, PR 35):
#
#   gather, ms a round at 4 / 16 / 63 slots: 10,500,000 x 28: 2.99 / 7.68 /
#     28.3; 2,270,296 x 137: 0.52 / 1.69 / 5.92; 400,000 x 2,000: 0.07 /
#     0.26 / 1.10: 40-41 ps a slot-row and 70-120 a row, whatever the width
#   kernel: 10,500,000 x 28: 0.680 / 0.718 / 1.181 (0.871 / 0.921 / 1.333
#     at 8,192-row blocks: a grid step costs 0.35 us); 2,270,296 x 137:
#     - / 0.446 / 0.460 (the stored matrix at 800 GB/s: the HBM's rate);
#     400,000 x 2,000, alone and not in a step: 1.22 at every slot count
#     (1.5 ps a byte) against the gather form's 0.49 at 63 slots
#
# The kernel's byte term is what a bandwidth-bound pass sustains, not the
# 0.9 ps the two narrow shapes fit (they are bound by the grid's steps, a
# wide matrix by its bytes); the sum overstates the narrow shapes by 15-50%,
# on the gather form's side of every choice.
_GATHER_PS_SLOT = 41.0
_GATHER_PS_ROW = 70.0
_KERNEL_PS_BYTE = 1.3
_KERNEL_PS_SLOT = 0.8
_KERNEL_PS_ROW = 15.0            # 12 bytes of leaf ids and label
_KERNEL_PS_GRID_STEP = 350_000.0
# ``assign_rows``' word: a label (at most 2 x 128 slots) under a leaf id
# under the bit that says a slot matched the row
_LABEL_BITS = 9
MAX_LEAF_IDS = 1 << 21
_HIT = 1 << 30
# rows of the ``(9, S, 1)`` per-slot operand
_COLS = ("feats", "thrs", "dls", "leafs", "nls", "sml", "mt", "nan", "zero")
# the stored layouts the kernel decodes (``partition_path``)
KERNEL_LAYOUTS = ("u8", "packed4")


def _pad(n: int, to: int) -> int:
    return -(-n // to) * to


def partition_path(columns: int, slots: int, rows: int, *, pallas: bool,
                   layout: str, use_cat: bool) -> str:
    """``"kernel"`` or ``"gather"`` for a round of ``slots`` slots over a
    stored matrix of ``columns`` rows by ``rows`` columns: the one place
    the choice is made, called when a bucket's pass is traced.

    The kernel serves what it can read: the resolved histogram method is
    the Pallas one (XLA:CPU keeps the gather form; the interpreter runs the
    kernel in tests), the matrix's ``layout`` is one the kernel decodes
    (``KERNEL_LAYOUTS``: ``u8``, one row a feature, and ``packed4``, two
    features' nibbles a row; an EFB bundle column and bins wider than a
    byte keep the gather form), no column is categorical (bitset
    membership stays with the gather form), and there is a whole chunk of
    rows (Mosaic takes a 1-D array of fewer in another tiling than the
    compiler gives it).  Then the widths decide, by each form's cost a row
    in picoseconds (the constants above, with the chip readings they are
    fitted from): the kernel's byte term prices the rows it reads, the
    stored ones (``ceil(F/2)`` packed), padded to a tile."""
    if not pallas or layout not in KERNEL_LAYOUTS or use_cat \
            or rows < _ROW_CHUNK:
        return "gather"
    stored = _pad(columns, _U8_ROWS)
    if stored * _ROW_CHUNK > _BLOCK_BYTES:
        return "gather"     # no block of it fits the kernel's VMEM budget
    kernel_ps = (_KERNEL_PS_BYTE * stored + _KERNEL_PS_SLOT * _pad(slots, 8)
                 + _KERNEL_PS_ROW + _KERNEL_PS_GRID_STEP / _row_block(stored))
    gather_ps = _GATHER_PS_SLOT * slots + _GATHER_PS_ROW
    return "kernel" if kernel_ps < gather_ps else "gather"


def _row_block(stored_columns: int) -> int:
    """Rows of a grid step for a stored matrix that many rows tall."""
    rows = _BLOCK_BYTES // stored_columns // _ROW_CHUNK * _ROW_CHUNK
    return max(_ROW_CHUNK, min(_MAX_ROW_BLOCK, rows))


def partition_bytes(path: str, columns: int, slots: int, rows: int) -> int:
    """HBM bytes a round of that form moves, from the shapes: the kernel
    the stored matrix once and 12 bytes a row (leaf ids in and out, the
    label out), the gather form 10 bytes a slot-row."""
    if path == "kernel":
        return _pad(columns, _U8_ROWS) * rows + 12 * rows
    return 10 * slots * rows


def count_partition_round(path: str, slots: int) -> None:
    """Trace time: one more bucket's pass in
    ``partition_rounds_traced_total{path,slots}``."""
    from ..obs.metrics import default_registry

    default_registry().counter(
        "partition_rounds_traced_total",
        "Wave round passes traced, by the form their partition takes",
        label_names=("path", "slots")).labels(
            path=path, slots=str(slots)).inc()


def count_partition_bytes(path: str, columns: int, slots: int,
                          rows: int) -> None:
    """Trace time: ``partition_bytes_per_round{path}``, what the widest
    bucket's partition moves."""
    from ..obs.metrics import default_registry

    default_registry().gauge(
        "partition_bytes_per_round",
        "HBM bytes the widest bucket's partition moves, from the shapes",
        label_names=("path",)).labels(path=path).set(
            float(partition_bytes(path, columns, slots, rows)))


def assign_rows(gl, leaf, leafs, nls, sml, slots: int, use_sub: bool):
    """``(new leaf ids, labels)``, each ``(1, rows)`` int32, from a round's
    ``(S, rows)`` left-decisions ``gl``: what both memory forms do after
    ``go_left_rule``.  ``leaf`` is ``(1, rows)``; ``leafs`` / ``nls`` /
    ``sml`` are ``(S, 1)``: the leaf a slot splits (an empty slot: an id no
    row has), its new right leaf, whether its left child is the smaller.
    A row stays in its leaf where its leaf's split sends it left and moves
    to ``nls`` where it sends it right; a row of a leaf no slot splits
    keeps its leaf and takes the dead label.  Labels: ``use_sub`` the slot
    whose smaller child the row falls in, else ``slots`` (dead); otherwise
    ``2 * slot + right``, else ``2 * slots`` (``gl`` may hold more than
    ``slots`` rows: the rest match no row).

    A slot's two outcomes are each one word, ``_HIT | leaf << _LABEL_BITS
    | label``, built on the ``(S, 1)`` columns; at most one slot matches a
    row, so one masked sum over the slots picks the row's word: four
    operations a slot-row where two sums over separate conditions took
    ten."""
    assert 2 * slots < (1 << _LABEL_BITS), slots
    siota = lax.broadcasted_iota(jnp.int32, (gl.shape[0], 1), 0)
    dead = slots if use_sub else 2 * slots
    if use_sub:
        label_l, label_r = jnp.where(sml, siota, dead), jnp.where(sml, dead,
                                                                  siota)
    else:
        label_l, label_r = 2 * siota, 2 * siota + 1
    left = _HIT | (leafs << _LABEL_BITS) | label_l
    right = _HIT | (nls << _LABEL_BITS) | label_r
    word = jnp.sum(jnp.where(leafs == leaf, jnp.where(gl, left, right), 0),
                   axis=0, keepdims=True)
    hit = word >= _HIT
    new = jnp.where(hit, (word >> _LABEL_BITS) & (MAX_LEAF_IDS - 1), leaf)
    return new, jnp.where(hit, word & ((1 << _LABEL_BITS) - 1), dead)


@functools.partial(jax.jit, static_argnames=("use_sub", "packed"))
def partition_gather(bins, leaf_id, cols, *, use_sub: bool,
                     packed: bool = False):
    """``partition_pallas``'s round in the gather form on the same stored
    matrix (``packed``: ``packed_bins_of_feat`` decodes a feature's
    nibbles): what the kernel is held to in tests and on the chip."""
    col = {name: cols[name][:, None] for name in _COLS}
    ids = jax.vmap(lambda f: packed_bins_of_feat(bins, f) if packed
                   else bins[f])(cols["feats"]).astype(jnp.int32)
    gl = go_left_rule(ids, col["thrs"], col["dls"], col["mt"], col["nan"],
                      col["zero"])
    new, label = assign_rows(gl, leaf_id[None, :], col["leafs"], col["nls"],
                             col["sml"], cols["feats"].shape[0], use_sub)
    return new[0], label[0]


def _kernel(cols_ref, bins_ref, leaf_ref, new_ref, label_ref, *, slots,
            use_sub, missing, packed):
    """Grid: (row blocks,).  cols (9, Sp, 1) int32, one (Sp, 1) column a
    name of ``_COLS`` (slots past the live ones carry a leaf id no row
    has); bins (Fp, T) uint8, the rows past the matrix's stored ones
    undefined bytes that the selection multiplies by 0 (``packed``: row
    ``f >> 1`` holds feature ``f``'s bins in its nibble ``f & 1``);
    leaf, new, label (T,) int32: the ids' own 1-D arrays (as a ``(1, N)``
    view they were a copy each way, in and out, wherever the compiler
    keeps an array of their size in its fast memory: 0.27-0.36 ms a round
    at 4 M and 2.27 M rows, chip run of 2026-10-03).  The lanes of an edge
    block past the last row compute on undefined bytes and are not written
    back."""
    c = {name: cols_ref[i] for i, name in enumerate(_COLS)}
    Sp = c["feats"].shape[0]
    Fp, T = bins_ref.shape
    # slot s's row of the selection holds one 1, at its feature: the int8
    # product with the tile read as signed bytes is that feature's bin id
    # less 256 where the id is 128 or more, so its low byte is the id.  The
    # tile goes to the MXU as it is stored: nothing widens it.
    # (packed: at its feature's byte row)
    sel = (lax.broadcasted_iota(jnp.int32, (Sp, Fp), 1)
           == (c["feats"] >> 1 if packed else c["feats"])
           ).astype(jnp.int32).astype(jnp.int8)
    # with no missing type on any column the rule's NaN / zero terms fold
    mt = c["mt"] if missing else MISSING_NONE

    for lo in range(0, T, _ROW_CHUNK):
        rows = pl.ds(lo, min(_ROW_CHUNK, T - lo))
        at = (slice(None), rows)
        ids = lax.dot_general(
            sel, lax.bitcast_convert_type(bins_ref[at], jnp.int8),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32) & 255          # (Sp, rows)
        if packed:      # the feature's nibble of the byte
            ids = (ids >> (4 * (c["feats"] & 1))) & 15
        gl = go_left_rule(ids, c["thrs"], c["dls"] != 0, mt, c["nan"],
                          c["zero"])
        new, label = assign_rows(
            gl, leaf_ref[rows][None, :], c["leafs"], c["nls"],
            c["sml"] != 0, slots, use_sub)
        new_ref[rows], label_ref[rows] = new[0], label[0]


@functools.partial(jax.jit, static_argnames=("use_sub", "missing", "packed",
                                             "row_block", "interpret"))
def partition_pallas(bins, leaf_id, cols, *, use_sub: bool,
                     missing: bool = True, packed: bool = False,
                     row_block: int = 0, interpret: bool = False):
    """``(new leaf ids, labels)``, each ``(N,)`` int32, of one round.

    ``bins`` is the ``(F, N)`` uint8 matrix as it is stored (a row-sharded
    learner's shard inside its ``shard_map``; ``packed``: the ``(ceil(F/2),
    N)`` bytes of ``hist_pallas.pack4bit``, whose odd-F phantom nibble no
    slot names), ``leaf_id`` ``(N,)`` int32, ``cols`` a dict of the
    round's ``(S,)`` per-slot columns under the names of ``_COLS``: the
    split's feature, threshold and default direction, the leaf it splits
    (an empty slot: a leaf id no row has), the new right leaf, whether the
    left child is the smaller, and the feature's ``missing_type`` /
    ``nan_bin`` / ``zero_bin``.  ``use_sub``
    picks the labeling: the smaller child's slot or S; else ``2 * slot +
    right`` or 2S.  ``missing=False`` says no column has a missing type
    (every ``mt`` is ``MISSING_NONE``): the kernel hands ``go_left_rule``
    the constant.  ``row_block`` (tests): rows of a grid step, a multiple
    of 1,024; 0 takes ``_row_block``'s."""
    F, N = bins.shape
    S = cols["feats"].shape[0]
    Sp = _pad(S, 8)
    # a slot padded on holds leaf id -1, which no row carries
    stacked = jnp.stack([
        jnp.pad(cols[name].astype(jnp.int32), (0, Sp - S),
                constant_values=-1 if name == "leafs" else 0)
        for name in _COLS])[:, :, None]
    Fp = _pad(F, _U8_ROWS)
    T = min(row_block or _row_block(Fp), _pad(N, _ROW_CHUNK))
    new, label = pl.pallas_call(
        functools.partial(_kernel, slots=S, use_sub=use_sub,
                          missing=missing, packed=packed),
        grid=(-(-N // T),),
        in_specs=[pl.BlockSpec((len(_COLS), Sp, 1), lambda i: (0, 0, 0)),
                  pl.BlockSpec((Fp, T), lambda i: (0, i)),
                  pl.BlockSpec((T,), lambda i: (i,))],
        out_specs=[pl.BlockSpec((T,), lambda i: (i,)),
                   pl.BlockSpec((T,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((N,), jnp.int32)] * 2,
        interpret=interpret,
        name=KERNEL_NAME,
    )(stacked, bins, leaf_id.astype(jnp.int32))
    return new, label
