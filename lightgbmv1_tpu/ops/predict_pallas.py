"""Pallas TPU inference kernels — VMEM-pinned node tables.

The XLA depth-stepped walk (models/predict.serving_leaf_binned) re-reads
the stacked node tables from HBM on every one of its ``max_depth`` steps:
each gather of (feature, threshold-bin, children) streams the (T, L1)
tables again, and for deep ensembles the walk is table-bandwidth-bound,
not row-bound.  Two kernels fix that:

* ``serving_leaf_pallas`` (PR 4, ``predict_method=pallas``) pins ALL
  node tables in VMEM once per row tile — for a 500-tree, 255-leaf
  model the full table set is ~3.5 MB, comfortably inside the ~16 MB
  VMEM budget — so the ``depth`` gather steps run entirely out of
  on-chip memory and HBM traffic drops to the prebinned code tile in +
  the leaf-index tile out.  The (N, T) leaf intermediate still lands in
  HBM and the leaf-value gather/sum is a second XLA pass.

* ``serving_fused_pallas`` (``predict_method=fused``) is the serving
  megakernel: one launch per row tile walks every tree to its leaf AND
  accumulates the per-class raw scores in a VMEM-resident (TILE, K)
  block, so neither the (N, T) pointer intermediate nor the leaf-value
  gather ever touches HBM.  The grid is (row_tiles, tree_tiles) with
  the TREE dim innermost: the scores block's index map is constant over
  the tree dim (a revisited accumulator, the histogram kernels'
  pattern) and so is the codes block — Pallas fetches the row codes
  from HBM once per tile-sweep instead of once per depth step.  When
  the stacked tables exceed the VMEM budget, ``plan_predict_tiles``
  (the ``plan_wave_loop`` idiom: static, honest reason strings) tiles
  trees into VMEM-sized groups streamed via the grid's inner dim.  With
  4-bit packed serving codes (every feature <= 15 codes incl. the
  reserved NaN/zero codes) the decision lane decodes nibbles in-kernel
  (ops/hist_pallas.pack4bit layout), halving both the H2D stream and
  the per-tile code footprint.  An optional sigmoid/softmax epilogue
  runs on the accumulator in the same launch.

Scope: the PREBINNED, non-categorical serving path (where the table-pin
pays; categorical ensembles ride the XLA walk).  The pure-XLA walk is the
bit-parity pin: `tests/test_predict_engine.py` pins kernel-vs-XLA leaf
equality (interpret mode on CPU).  `predict_method=pallas`/``fused`` are
opt-in, and a backend that cannot lower or compile them raises — there
is no silent switch to the XLA walk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..io.binning import MISSING_NAN, MISSING_ZERO


def _kernel(nl_ref, feat_ref, tbin_ref, zbin_ref, dl_ref, mt_ref, lc_ref,
            rc_ref, codes_ref, out_ref, *, n_steps, zero_code, nan_code):
    """Grid: (row_tiles,).  All table refs hold the FULL (T, L1) arrays in
    VMEM; ``codes_ref`` is this tile's (TILE, F) serving codes."""
    T, L1 = feat_ref.shape
    rows = codes_ref.shape[0]

    codes = codes_ref[...].astype(jnp.int32)              # (TILE, F)
    feat = feat_ref[...].reshape(-1)                      # (T*L1,)
    tbin = tbin_ref[...].reshape(-1)
    zbin = zbin_ref[...].reshape(-1)
    dl = dl_ref[...].reshape(-1)
    mt = mt_ref[...].reshape(-1)
    lc = lc_ref[...].reshape(-1)
    rc = rc_ref[...].reshape(-1)
    t_off = lax.broadcasted_iota(jnp.int32, (rows, T), 1) * L1

    def body(_, node):
        nd = jnp.maximum(node, 0)
        flat = nd + t_off                                  # (TILE, T)
        f = jnp.take(feat, flat, axis=0)
        b = jnp.take_along_axis(codes, f, axis=1)
        is_nan = b == nan_code
        is_zero = b == zero_code
        b0 = jnp.where(is_nan | is_zero, jnp.take(zbin, flat, axis=0), b)
        mtype = jnp.take(mt, flat, axis=0)
        is_missing = jnp.where(
            mtype == MISSING_NAN, is_nan,
            jnp.where(mtype == MISSING_ZERO, is_nan | is_zero, False))
        go_left = jnp.where(is_missing, jnp.take(dl, flat, axis=0) != 0,
                            b0 <= jnp.take(tbin, flat, axis=0))
        nxt = jnp.where(go_left, jnp.take(lc, flat, axis=0),
                        jnp.take(rc, flat, axis=0))
        return jnp.where(node >= 0, nxt, node)

    node0 = jnp.where(nl_ref[...] > 1,
                      jnp.zeros((rows, T), jnp.int32),
                      jnp.full((rows, T), -1, jnp.int32))
    node = lax.fori_loop(0, max(int(n_steps), 1), body, node0)
    out_ref[...] = -node - 1


def serving_leaf_pallas(arrays, codes, *, n_steps: int, zero_code: int,
                        nan_code: int, interpret: bool = False,
                        row_tile: int = 512):
    """(N, F) serving codes -> (N, T) leaf indices, node tables pinned in
    VMEM.  ``N`` must be a multiple of the row tile after the caller's
    bucket padding (buckets are powers of two >= 256, so any power-of-two
    tile <= N divides it)."""
    N, _ = codes.shape
    T, L1 = arrays.split_feature.shape
    tile = min(row_tile, N)
    while N % tile:
        tile //= 2
    grid = (N // tile,)

    def full(a, dtype=jnp.int32):
        return a.astype(dtype)

    tables = (
        full(arrays.num_leaves.reshape(1, T)),
        full(arrays.split_feature),
        full(arrays.threshold_bin),
        full(arrays.zero_bin),
        full(arrays.default_left),
        full(arrays.missing_type),
        full(arrays.left_child),
        full(arrays.right_child),
    )
    table_specs = [pl.BlockSpec(t.shape, lambda i: (0, 0)) for t in tables]
    kern = functools.partial(_kernel, n_steps=n_steps, zero_code=zero_code,
                             nan_code=nan_code)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=table_specs + [
            pl.BlockSpec((tile, codes.shape[1]), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile, T), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, T), jnp.int32),
        interpret=interpret,
    )(*tables, codes)


# ---------------------------------------------------------------------------
# Serving megakernel: fused walk + accumulate with tree tiling
# ---------------------------------------------------------------------------

_PREDICT_VMEM_BUDGET = 14 * 2 ** 20


def plan_predict_tiles(*, T, L1, L, F, K, depth, has_cat=False,
                       prebin=True, packed=False, row_tile=512,
                       vmem_budget=_PREDICT_VMEM_BUDGET):
    """Static VMEM-budget planner for the serving megakernel (the
    ``plan_wave_loop`` idiom: decided entirely from shapes and knobs,
    every refusal one honest reason line, the returned dict recorded
    verbatim in the BENCH record so a capture shows WHY a model ran
    fused or fell back to the staged walk).

    Prices one (row_tile, tree_tile) kernel step: the tree tile's node
    tables (seven int32 (Tt, L1) tables + the (Tt, L) f32 leaf values +
    num_leaves), the row tile's serving codes (packed: half the
    columns), the (TILE, K) scores accumulator, and the walk's live
    (TILE, Tt) int32 working set.  ``tree_tile`` is the largest tree
    count whose step fits ``vmem_budget``; a single tree that does not
    fit refuses (staged walk).  Categorical bitset decisions and the
    raw-feature walk stay staged — the megakernel serves prebinned
    numeric codes only."""
    Fc = -(-int(F) // 2) if packed else int(F)
    per_tree = (7 * int(L1) + int(L) + 1) * 4
    codes_bytes = int(row_tile) * Fc * 4       # int32-widened decode lane
    acc_bytes = int(row_tile) * max(int(K), 1) * 4
    # the walk's live per-step arrays (node pointers + gathered operands),
    # all (row_tile, tree_tile) int32 — priced at 6 concurrently-live
    def step_bytes(tt):
        return (tt * per_tree + codes_bytes + acc_bytes
                + 6 * int(row_tile) * tt * 4)

    tree_tile = max(int(T), 1)
    while tree_tile > 1 and step_bytes(tree_tile) > vmem_budget:
        tree_tile = -(-tree_tile // 2)
    n_tiles = -(-max(int(T), 1) // tree_tile)
    plan = dict(eligible=False, reason="", tree_tile=int(tree_tile),
                n_tree_tiles=int(n_tiles), t_pad=int(n_tiles * tree_tile),
                row_tile=int(row_tile),
                table_tile_bytes=int(tree_tile * per_tree),
                codes_tile_bytes=int(codes_bytes), acc_bytes=int(acc_bytes),
                total_bytes=int(step_bytes(tree_tile)),
                packed=bool(packed), vmem_budget=int(vmem_budget))
    if not prebin:
        plan["reason"] = ("raw-feature walk: the fused kernel serves "
                          "prebinned serving codes only")
        return plan
    if has_cat:
        plan["reason"] = ("categorical bitset decision stays on the "
                          "staged walk")
        return plan
    if step_bytes(tree_tile) > vmem_budget:
        plan["reason"] = (
            f"one tree's tables + working set ({step_bytes(1)} B) exceed "
            f"the VMEM budget ({int(vmem_budget)} B)")
        return plan
    plan["eligible"] = True
    return plan


def _fused_kernel(nl_ref, feat_ref, tbin_ref, zbin_ref, dl_ref, mt_ref,
                  lc_ref, rc_ref, lv_ref, codes_ref, out_ref, *, n_steps,
                  zero_code, nan_code, K, n_tree_tiles, mode, packed,
                  transform):
    """Grid: (row_tiles, tree_tiles), TREE dim innermost.  The scores
    block's index map is constant over the tree dim, so Mosaic keeps it
    resident in VMEM as a revisited accumulator (zeroed at tree tile 0),
    and the codes block — also constant over the tree dim — is copied
    from HBM once per row tile, not once per depth step.  ``mode``:

    * ``"scores"`` — (TILE, K) per-class raw-score accumulator; leaf
      values gathered and class-summed in VMEM right after the walk
      (class of global tree g is ``g % K``, iteration-major tree order).
      ``transform`` (None | 'sigmoid' | 'softmax') runs on the finished
      accumulator at the last tree tile — the objective epilogue rides
      the same launch.
    * ``"leaf"`` — the (TILE, Tt) leaf indices are written out per tree
      tile (the node-exactness pin + the f64-exact reconstruction lane).

    ``packed``: ``codes_ref`` holds 4-bit packed rows (two features per
    byte, ops/hist_pallas.pack4bit nibble layout); the decision lane
    decodes with a constant shift + select — never a data-dependent
    shift amount, which Mosaic cannot lower."""
    Tt, L1 = feat_ref.shape
    rows = codes_ref.shape[0]
    t = pl.program_id(1)

    codes = codes_ref[...].astype(jnp.int32)
    feat = feat_ref[...].reshape(-1)
    tbin = tbin_ref[...].reshape(-1)
    zbin = zbin_ref[...].reshape(-1)
    dl = dl_ref[...].reshape(-1)
    mt = mt_ref[...].reshape(-1)
    lc = lc_ref[...].reshape(-1)
    rc = rc_ref[...].reshape(-1)
    t_off = lax.broadcasted_iota(jnp.int32, (rows, Tt), 1) * L1

    def body(_, node):
        nd = jnp.maximum(node, 0)
        flat = nd + t_off                                  # (TILE, Tt)
        f = jnp.take(feat, flat, axis=0)
        if packed:
            byte = jnp.take_along_axis(codes, f >> 1, axis=1)
            b = jnp.where((f & 1) == 1, byte >> 4, byte) & 15
        else:
            b = jnp.take_along_axis(codes, f, axis=1)
        is_nan = b == nan_code
        is_zero = b == zero_code
        b0 = jnp.where(is_nan | is_zero, jnp.take(zbin, flat, axis=0), b)
        mtype = jnp.take(mt, flat, axis=0)
        is_missing = jnp.where(
            mtype == MISSING_NAN, is_nan,
            jnp.where(mtype == MISSING_ZERO, is_nan | is_zero, False))
        go_left = jnp.where(is_missing, jnp.take(dl, flat, axis=0) != 0,
                            b0 <= jnp.take(tbin, flat, axis=0))
        nxt = jnp.where(go_left, jnp.take(lc, flat, axis=0),
                        jnp.take(rc, flat, axis=0))
        return jnp.where(node >= 0, nxt, node)

    node0 = jnp.where(nl_ref[...] > 1,
                      jnp.zeros((rows, Tt), jnp.int32),
                      jnp.full((rows, Tt), -1, jnp.int32))
    node = lax.fori_loop(0, max(int(n_steps), 1), body, node0)
    leaf = -node - 1

    if mode == "leaf":
        out_ref[...] = leaf
        return

    L = lv_ref.shape[1]
    lv = lv_ref[...].reshape(-1)
    l_off = lax.broadcasted_iota(jnp.int32, (rows, Tt), 1) * L
    vals = jnp.take(lv, jnp.maximum(leaf, 0) + l_off, axis=0)
    if K == 1:
        contrib = jnp.sum(vals, axis=1, keepdims=True)
    else:
        g = t * Tt + lax.broadcasted_iota(jnp.int32, (Tt, K), 0)
        onehot = (g % K == lax.broadcasted_iota(
            jnp.int32, (Tt, K), 1)).astype(jnp.float32)
        contrib = jnp.dot(vals, onehot,
                          preferred_element_type=jnp.float32)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += contrib

    if transform is not None:
        @pl.when(t == n_tree_tiles - 1)
        def _epilogue():
            acc = out_ref[...]
            if transform == "sigmoid":
                out_ref[...] = 1.0 / (1.0 + jnp.exp(-acc))
            else:                                          # softmax
                mx = jnp.max(acc, axis=1, keepdims=True)
                e = jnp.exp(acc - mx)
                out_ref[...] = e / jnp.sum(e, axis=1, keepdims=True)


def serving_fused_pallas(tables, codes, *, n_steps: int, zero_code: int,
                         nan_code: int, K: int, tree_tile: int,
                         mode: str = "scores", packed: bool = False,
                         transform=None, interpret: bool = False,
                         row_tile: int = 512):
    """The serving megakernel.  ``tables`` is a ServingArrays whose tree
    axis is padded to a multiple of ``tree_tile`` (models/tree.
    pad_tree_axis — zero trees park on leaf 0 with value 0.0, so scores
    are unchanged and leaf-mode callers slice the pad away); ``codes``
    is this batch's (N, F) serving codes, or (N, ceil(F/2)) packed
    bytes.  Returns (N, K) f32 scores or (N, T_pad) int32 leaves."""
    N = codes.shape[0]
    T, L1 = tables.split_feature.shape
    L = tables.leaf_value.shape[1]
    if T % tree_tile:
        raise ValueError(f"tree axis {T} not a multiple of the tree tile "
                         f"{tree_tile} (pad with pad_tree_axis)")
    n_tt = T // tree_tile
    tile = min(row_tile, N)
    while N % tile:
        tile //= 2
    grid = (N // tile, n_tt)

    ins = (
        tables.num_leaves.reshape(1, T).astype(jnp.int32),
        tables.split_feature.astype(jnp.int32),
        tables.threshold_bin.astype(jnp.int32),
        tables.zero_bin.astype(jnp.int32),
        tables.default_left.astype(jnp.int32),
        tables.missing_type.astype(jnp.int32),
        tables.left_child.astype(jnp.int32),
        tables.right_child.astype(jnp.int32),
        tables.leaf_value.astype(jnp.float32),
    )
    in_specs = (
        [pl.BlockSpec((1, tree_tile), lambda r, t: (0, t))]
        + [pl.BlockSpec((tree_tile, L1), lambda r, t: (t, 0))
           for _ in range(7)]
        + [pl.BlockSpec((tree_tile, L), lambda r, t: (t, 0)),
           pl.BlockSpec((tile, codes.shape[1]), lambda r, t: (r, 0))]
    )
    if mode == "leaf":
        out_spec = pl.BlockSpec((tile, tree_tile), lambda r, t: (r, t))
        out_shape = jax.ShapeDtypeStruct((N, T), jnp.int32)
    else:
        out_spec = pl.BlockSpec((tile, K), lambda r, t: (r, 0))
        out_shape = jax.ShapeDtypeStruct((N, K), jnp.float32)
    kern = functools.partial(
        _fused_kernel, n_steps=n_steps, zero_code=zero_code,
        nan_code=nan_code, K=K, n_tree_tiles=n_tt, mode=mode,
        packed=packed, transform=transform)
    return pl.pallas_call(
        kern, grid=grid, in_specs=in_specs, out_specs=out_spec,
        out_shape=out_shape, interpret=interpret,
    )(*ins, codes)
