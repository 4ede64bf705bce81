"""Fused wave-round megakernel: histogram + split scan in ONE Pallas pass.

The staged wave round (the r05 phase table) is a pure-bandwidth
round-trip: ``hist_pallas`` writes the ``(slots, F, B, 3)`` histogram
stack to HBM, ``models/grower_wave.subtract_child_hists`` reads it back
to build the 2K-child stack, and ``ops/split.py``'s scan streams that
stack in again — three traversals of a tensor that is consumed exactly
once.  This kernel keeps the round's histograms in VMEM end to end:

* the row-tile grid REUSES ``hist_pallas._kernel`` verbatim (the one-hot
  MXU formulation with its bf16 / bf16x2 / int8 / int8sr precision
  modes) to accumulate each wave slot's histogram into a VMEM scratch
  accumulator,
* on the LAST row tile the same kernel invocation runs the split scan on
  the VMEM-resident stack: the smaller-child-subtraction path reads the
  parent histograms as a kernel input and subtracts in VMEM before
  scanning (the int8sr dequantize multiply folded in), then the staged
  scan's own stages — ``scan_left_sums`` (stacked two-direction cumsum +
  missing-mass adjust), ``scan_direction_gains`` (gain/penalty chain)
  and ``scan_pick_feature`` (tie-band preference argmax, per-feature
  half) — are composed AS THE SAME CODE OBJECTS on the VMEM values, so
  interpret-mode results are bit-identical to the staged path by
  construction, not by re-derivation,
* the round's PARTITION rides the same pass (ISSUE 15, the single-pass
  wave round): the feature-block-0 kernel invocation receives each
  row's DECISION BIN (the committed split feature's bin for the row's
  current leaf — one O(N) gather, the only extra touch of the binned
  matrix) plus the packed per-slot split metadata, evaluates the
  go-left decisions in VMEM with the staged partition's own
  ``ops/split.go_left_rule`` (bin compare + the NaN/zero
  missing-direction rules, op-for-op), writes the updated row→slot
  label into its own output block and accumulates the child histograms
  from it IN THE SAME SWEEP — the staged path's separate (S, N)
  decision pass over the binned rows (``phase_partition_ms``) and its
  HBM-resident mask intermediates disappear, and the kernel emits the
  new per-row leaf ids as a second O(N) output.  Valid-set routing
  rides the same decision stage (``fused_route_rows`` — a routing-only
  grid over the valid binned matrix, same ``route_tile`` code object),
  replacing the staged gather chain (``phase_valid_route_ms``),
* only an O(F) per-(child, feature) residue (best gain, in-band pick,
  left sums at the pick — ``RES_COLS`` floats per feature) leaves the
  kernel; the grid iterates feature blocks and the cross-feature half of
  ``scan_pick`` runs on the concatenated residue outside the kernel.
  The tie band needs the GLOBAL best gain, so a running in-VMEM
  reduction across feature blocks could mis-pick inside overlapping
  near-tie bands; reducing to the O(F) residue in VMEM and finishing the
  O(F) argmax outside keeps bit-exactness while still shrinking the
  kernel's HBM output from O(F·B) histograms to O(F) floats,
* the packed per-slot SplitInfo (``PACK_COLS`` floats per child) is all
  the round emits in pool-free mode; the subtraction-composed mode also
  emits the K smaller-child histograms (the per-leaf state the NEXT
  round's subtraction needs) — the ``(2K, F, B, 3)`` scan stack itself
  never materializes off-chip in either mode.

Fallback taxonomy (every gate logs once at build time,
parallel/trainer.py):

* categorical features — the sorted two-direction categorical scan
  (``_best_categorical``) argsorts per feature, which has no Mosaic
  lowering; such datasets run the staged path,
* ``extra_trees`` — per-node threshold sampling draws ``jax.random``
  inside the scan,
* EFB bundles / int16 bins — the scan runs in original-feature uint8
  bin space only.  4-bit PACKED bins are NOT a fallback leg any more
  (ISSUE 18): on the ``num_bins <= 16`` rung of the kernel-width
  ladder (``hist_pallas.kernel_width``) the fused round and the
  persistent wave loop consume the ``(ceil(F/2), N)`` packed matrix
  directly — nibbles unpack in VMEM (the reused ``_hist_tile`` packed
  path), the accumulator is restored to natural feature order before
  the scan, and the routing stage decodes decision bins from the
  packed bytes — so the round's dominant HBM read halves; packed bins
  at ``num_bins > 16`` cannot exist (a nibble holds 16 values) and are
  refused honestly,
* row-sharded learners (``tree_learner=data``/``voting``) — the
  cross-shard histogram reduce needs the explicit histogram on the wire;
  the feature-parallel learner DOES run the kernel per feature slice and
  elects through the existing ``_sync_best_split``,
* feature-parallel partition (partition-specific) — the in-kernel
  routing stage needs the committed split feature's GLOBAL column, but
  each shard's kernel sees only its own feature slice; the
  feature-parallel learner therefore keeps the staged (S, N) partition
  and per-slice election while still fusing histogram + scan,
* EFB decisions (partition-specific) — the go-left stage compares raw
  uint8 bins; bundle-column decode happens in ``bins_of_fn`` outside
  any kernel (EFB is already excluded by the histogram gate above, so
  the partition gate never fires alone).  Packed nibble decode, by
  contrast, IS in-kernel now: ``decision_bins`` gathers the packed
  byte by ``feature >> 1`` and selects the nibble by feature parity
  (the ``packed_bins_of_rows`` layout contract),

A lowering or compile failure on a device backend is NOT a fallback leg:
an eligible config that asks for this kernel runs it or raises with the
compiler's message (tests/test_tpu_lowering.py cross-lowers every kernel
for ``tpu`` from the CPU suite).  The CPU backend always runs the kernel
in interpret mode (the bit-parity lane the tests pin).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..io.binning import MISSING_NAN, MISSING_ZERO
from .hist_pallas import (MAX_LANES, _kernel as _hist_tile, _row_tile_for,
                          packed_bins_of_rows, pass_rows)
from .split import (
    NEG_INF,
    NO_CONSTRAINT,
    FeatureMeta,
    SplitResult,
    child_leaf_output,
    gain_shift,
    go_left_rule,
    scan_direction_gains,
    scan_left_sums,
    scan_pick_feature,
    tie_tol,
)

RES_COLS = 6    # fbest, gain_at_sel, sel (direction*B+thr), left g/h/c
PACK_COLS = 10  # gain, feature, threshold, default_left, left(3), right(3)
RMETA_COLS = 8  # leaf, new-leaf, thr, default_left, mtype, nan_bin,
                # zero_bin, smaller-is-left — the packed per-slot split
                # metadata the routing stage consumes (int32)



def route_tile(dbin, oleaf, rmeta, *, nslots, sub, want_label=True):
    """The fused decision stage on one row tile — pure jnp on VALUES, so
    the megakernel (train rows), the routing-only valid-set kernel and
    any host-side replay all run the SAME code object.

    ``dbin`` (1, T) int32 — each row's DECISION bin: the bin of its
    current leaf's committed split feature (rows of non-splitting
    leaves carry an arbitrary bin; their ``mine`` mask is False).
    ``oleaf`` (1, T) int32 — current leaf ids (pad rows carry -1).
    ``rmeta`` (S, RMETA_COLS) int32 — per-slot split metadata; dead
    slots carry leaf id ``num_leaves`` (matches no row).

    Returns ``(new_leaf (1, T), label (1, T) or None)``: the updated
    row→leaf routing and (``want_label``) the row→histogram-slot label
    (smaller-child slot in subtraction mode, ``2s + right`` pool-free;
    ``nslots`` = dead).  Mirrors the staged ``go_left_s`` partition
    op-for-op — every update term is int32, so deferring/fusing is
    bit-identical to the staged pass by construction."""
    S = rmeta.shape[0]
    leafs = rmeta[:, 0:1]
    nls = rmeta[:, 1:2]
    thr = rmeta[:, 2:3]
    dl = rmeta[:, 3:4] != 0
    mt = rmeta[:, 4:5]
    nanb = rmeta[:, 5:6]
    zb = rmeta[:, 6:7]
    sml = rmeta[:, 7:8] != 0
    mine = oleaf == leafs                                    # (S, T)
    g = go_left_rule(dbin, thr, dl, mt, nanb, zb)            # (S, T)
    new_leaf = oleaf + jnp.sum(
        jnp.where(mine & (~g), nls - oleaf, 0), axis=0, keepdims=True)
    if not want_label:
        return new_leaf, None
    siota = lax.broadcasted_iota(jnp.int32, (S, 1), 0)
    if sub:
        hit = mine & (g == sml)
        slot = jnp.broadcast_to(siota, mine.shape)
    else:
        hit = mine
        slot = 2 * siota + (~g).astype(jnp.int32)
    label = jnp.sum(jnp.where(hit, slot - nslots, 0),
                    axis=0, keepdims=True) + nslots
    return new_leaf, label


def pack_route_meta(feats, thrs, dls, leafs, nls, meta, sml=None):
    """(S, RMETA_COLS) int32 routing metadata from rank/slot-order split
    arrays + the feature meta — one place, so the megakernel's train
    stage and the valid-set router cannot pack differently."""
    feats = feats.astype(jnp.int32)
    z = jnp.zeros_like(feats)
    return jnp.stack([
        leafs.astype(jnp.int32),
        nls.astype(jnp.int32),
        thrs.astype(jnp.int32),
        dls.astype(jnp.int32),
        meta.missing_type[feats].astype(jnp.int32),
        meta.nan_bin[feats].astype(jnp.int32),
        meta.zero_bin[feats].astype(jnp.int32),
        (sml.astype(jnp.int32) if sml is not None else z),
    ], axis=1)


def decision_bins(binned, lids, feats, leafs, num_leaves, packed=False):
    """Each row's decision bin — ``binned[f(leaf(row)), row]`` via a
    leaf→feature table and ONE per-element gather (O(N) bytes), the
    only touch of the binned matrix the routing stage adds.  Rows of
    non-splitting leaves read feature 0; their slot mask is False.
    ``packed``: ``binned`` is the 4-bit matrix — the gather indexes the
    packed byte (``feature >> 1``, HALF the bytes touched) and selects
    the nibble by feature parity (``packed_bins_of_rows``, the layout's
    single source of truth)."""
    tab = jnp.zeros(num_leaves + 1, jnp.int32) \
        .at[leafs].set(feats.astype(jnp.int32), mode="drop")
    f_of = tab[lids]                                        # (N,)
    if packed:
        return packed_bins_of_rows(binned, f_of)
    return jnp.take_along_axis(binned, f_of[None, :], axis=0)[0] \
        .astype(jnp.int32)


def child_scan_residue(hc, mask_c, csum_c, constr_c, depth_c, pout_c,
                       hsc_c, *, meta_blk, params, use_mc,
                       monotone_penalty, child_scale, num_bins, fblk):
    """One child's in-VMEM split scan -> its (fblk, RES_COLS) residue:
    the staged scan's OWN stages (``scan_left_sums`` ->
    ``scan_direction_gains`` -> ``scan_pick_feature``) composed on VMEM
    values.  Module-level so the single-round megakernel and the
    persistent wave-loop kernel (``make_fused_wave_loop``) run the SAME
    code object — the loop's bit-parity contract rides on that, exactly
    as the grower's ``clamp_out`` rides on ``split.child_leaf_output``."""
    left2, _ = scan_left_sums(hc, meta_blk, hsc_c if child_scale else None)
    gains, shift = scan_direction_gains(
        left2, csum_c, meta_blk, mask_c, params, constr_c, depth_c,
        monotone_penalty, pout_c, None, None, use_mc=use_mc)
    fbest, sel = scan_pick_feature(gains, shift, meta_blk)
    gains_f = jnp.concatenate([gains[0], gains[1]], axis=1)
    gsel = jnp.take_along_axis(gains_f, sel[:, None], axis=1)[:, 0]
    lsel = left2[sel // num_bins, jnp.arange(fblk), sel % num_bins]
    return jnp.concatenate(
        [fbest[:, None], gsel[:, None],
         sel.astype(jnp.float32)[:, None], lsel], axis=1)


def _fused_kernel(*refs, nrt, num_bins, fblk, precision, interpret,
                  params, use_mc, monotone_penalty, has_contri, sub,
                  apply_scale, child_scale, nslots, nchildren,
                  route_blk=False, fpb=0):
    """Grid ``(1, row_tiles)``: every tile accumulates its rows via the
    REUSED ``hist_pallas._kernel``; the last tile runs the split scan on
    the VMEM accumulator and writes the per-feature residue (plus, in
    subtraction mode, the raw smaller-child histograms).

    ``route_blk`` (feature block 0 of a routed round): the tile FIRST
    evaluates the committed splits' go-left decisions (``route_tile`` on
    the decision-bin/old-leaf tiles + the packed slot metadata), writes
    the row→slot label into its own output block — which the remaining
    feature blocks consume as their ``leaf`` input — and the new per-row
    leaf ids, then accumulates this block's histogram FROM the label it
    just produced: partition and histogram share one sweep of the rows.

    ``fpb > 0`` (4-bit packed bins, ISSUE 18): the bins tile holds
    ``fpb`` packed byte columns whose nibbles ``_hist_tile`` unpacks in
    VMEM to the ``fblk == 2*fpb`` unpacked feature block — its lane
    order is [lo nibbles | hi nibbles], so before the scan the
    accumulator's feature axis is re-interleaved back to NATURAL order
    (lo/hi alternating).  Everything downstream — subtraction, residue
    scan, the order-sensitive tie-band pick — then sees exactly the
    unpacked kernel's values in the unpacked kernel's order.
    """
    names = ["iota", "bins", "g3"]
    names += (["dbin", "oleaf", "rmeta"] if route_blk else ["leaf"])
    names += ["nb", "mt", "nanb", "zb", "usbl", "mono"]
    if has_contri:
        names.append("contri")
    names += ["mask", "csums", "constr", "depth", "pout"]
    if child_scale:
        names.append("cscale")
    if sub and apply_scale:
        names.append("sscale")
    if sub:
        names += ["sml", "parent"]
    names.append("res")
    if sub:
        names.append("hsmall")
    if route_blk:
        names += ["lab", "nleaf"]
    names.append("acc")
    r = dict(zip(names, refs))

    if route_blk:
        new_leaf, label = route_tile(
            r["dbin"][...], r["oleaf"][...], r["rmeta"][...],
            nslots=nslots, sub=sub)
        r["lab"][...] = label
        r["nleaf"][...] = new_leaf
        leaf_ref = r["lab"]
    else:
        leaf_ref = r["leaf"]

    _hist_tile(r["iota"], r["bins"], r["g3"], leaf_ref, r["acc"],
               num_leaves=nslots, num_bins=num_bins, fblk=fblk,
               precision=precision, interpret=interpret, packed=fpb > 0)

    rt = pl.program_id(1)
    B = num_bins

    @pl.when(rt == nrt - 1)
    def _scan():
        # accumulator rows are (channel-major, slot-minor), lanes are
        # (bin-major, feature-minor) — the same unscramble
        # hist_leaves_pallas applies outside, here on VMEM values
        acc = r["acc"][0, :3 * nslots]                  # (3*S, B*fblk)
        h = acc.reshape(3, nslots, B, fblk).transpose(1, 3, 2, 0)
        if fpb:
            # packed accumulator order is [lo nibbles | hi nibbles]; the
            # tie-band pick is feature-ORDER-sensitive (first in band =
            # min feature), so restore natural order BEFORE any scan
            h = jnp.stack([h[:, :fpb], h[:, fpb:]], axis=2) \
                .reshape(nslots, fblk, B, 3)
        meta_blk = FeatureMeta(
            num_bins=r["nb"][...][0],
            missing_type=r["mt"][...][0],
            nan_bin=r["nanb"][...][0],
            zero_bin=r["zb"][...][0],
            is_categorical=jnp.zeros(fblk, bool),
            usable=r["usbl"][...][0] != 0,
            monotone_type=r["mono"][...][0],
            contri=(r["contri"][...][0] if has_contri else None),
        )
        if sub:
            # smaller-child + parent subtraction IN VMEM — the exact op
            # order of subtract_child_hists (dequant multiply first, then
            # the smaller/larger select), so values are bit-identical
            hsm = h                                     # (S, fblk, B, 3)
            r["hsmall"][...] = hsm                      # raw (int on quant)
            if apply_scale:
                # power-of-two scales (ops/quantize.py) make this exact,
                # so the parent subtraction rounds the same with or
                # without fma contraction — matches the host grower's
                # subtract_child_hists bit-for-bit in any fusion context
                hsm = hsm * r["sscale"][...][:, None, None, :]
            sml = (r["sml"][...][:, 0] != 0)[:, None, None, None]
            parent = r["parent"][...]
            h_left = jnp.where(sml, hsm, parent - hsm)
            h_right = parent - h_left
            ch = jnp.stack([h_left, h_right], axis=1).reshape(
                (2 * nslots,) + h_left.shape[1:])       # (2S, fblk, B, 3)
        else:
            ch = h[:nchildren]


        mask = r["mask"][...] != 0                      # (C, fblk)
        csums = r["csums"][...]
        constr = r["constr"][...]
        depth = r["depth"][...][:, 0]
        pout = r["pout"][...][:, 0]
        cscale = (r["cscale"][...] if child_scale
                  else jnp.zeros((nchildren, 3), jnp.float32))

        child_scan = functools.partial(
            child_scan_residue, meta_blk=meta_blk, params=params,
            use_mc=use_mc, monotone_penalty=monotone_penalty,
            child_scale=child_scale, num_bins=B, fblk=fblk)
        r["res"][...] = jax.vmap(child_scan)(
            ch, mask, csums, constr, depth, pout, cscale)


def fused_wave_scan(binned, g3, label, *, nslots, nchildren, num_bins,
                    precision, interpret, meta, params, use_mc,
                    monotone_penalty, mask, csums, constr, depth, pout,
                    cscale=None, sscale=None, sml=None, parent=None,
                    apply_scale=False, row_tile=0, route=None,
                    packed=False):
    """One fused wave round over all feature blocks.

    ``nslots`` counts the ACCUMULATED slots (smaller children in
    subtraction mode, all 2S children pool-free); a row labelled
    ``nslots`` is dead and adds to nothing, as in ``hist_wave``'s Pallas
    pass, whose operand shapes and row tile this round shares
    (``pass_rows``).  ``parent`` non-None
    selects the subtraction-composed mode.  ``route`` non-None (dict
    ``dbin (N,) / oleaf (N,) / rmeta (S, RMETA_COLS)``) folds the
    partition in: ``label`` is ignored (pass None) — feature block 0
    evaluates the go-left decisions in VMEM, emits the label the other
    blocks consume and the updated per-row leaf ids.  ``packed``:
    ``binned`` is the ``(ceil(F/2), N)`` 4-bit matrix (num_bins <= 16)
    — each block streams its PACKED byte columns (half the HBM binned
    read) and unpacks nibbles in VMEM; a block's ``fblk`` unpacked
    features are the CONTIGUOUS natural range ``[fb*fblk, (fb+1)*fblk)``
    (lo nibble = feature 2p, hi = 2p+1), so the per-feature meta/mask/
    parent slices below are identical to the unpacked layout.  Returns
    ``(residue (C, F, RES_COLS), hsmall (nslots, F, B, 3) or None,
    new_leaf (N,) or None)``.
    """
    sub = parent is not None
    C = nchildren
    F = mask.shape[1]
    B = num_bins
    N = binned.shape[1]
    if packed:
        # fblk counts UNPACKED features and must be even (each byte
        # column contributes its lo and hi nibble feature); the phantom
        # hi-nibble feature of an odd-F tail pads to unusable below
        Fp = binned.shape[0]
        fblk = max(2, min(2 * Fp, MAX_LANES // B) & ~1)
        fpb = fblk // 2                  # packed byte columns per block
        nfb = -(-Fp // fpb)
    else:
        fpb = 0
        fblk = max(1, min(F, MAX_LANES // B))
        nfb = -(-F // fblk)
    f_pad = nfb * fblk
    out_rows = pass_rows(nslots, precision)[2]
    # the row tile is priced on the UNPACKED lane count either way: the
    # same T means the same row partition, so every (leaf, bin, feature)
    # accumulator cell sums the same per-tile dots in the same order —
    # the packed round's f32 histograms are bit-identical to unpacked
    T = row_tile if row_tile > 0 else _row_tile_for(
        out_rows, max(1, min(F, MAX_LANES // B)) * B, B)
    nrt = -(-N // T)
    n_pad = nrt * T

    # padding identical to hist_leaves_pallas: padded features collect
    # bin 255 (no bin when B < 256; masked unusable below when B == 256;
    # packed pad bytes are 0 -> phantom features collect bin 0 and are
    # masked unusable below), padded rows carry zero g3 and an
    # out-of-range slot id
    tile_cols = fpb if packed else fblk   # stored byte columns per block
    binned_rm = jnp.pad(
        binned,
        ((0, nfb * tile_cols - binned.shape[0]), (0, n_pad - N)),
        constant_values=0 if packed else 255).T   # (n_pad, nfb*tile_cols)
    g3t = jnp.pad(g3.astype(jnp.float32), ((0, n_pad - N), (0, 0))).T
    if route is not None:
        # pad rows: leaf -1 matches no slot -> the routing stage labels
        # them with the dead slot (zero g3 anyway) and passes the -1
        # leaf through (sliced off below)
        dbin_p = jnp.pad(route["dbin"].astype(jnp.int32),
                         (0, n_pad - N))[None, :]
        oleaf_p = jnp.pad(route["oleaf"].astype(jnp.int32),
                          (0, n_pad - N), constant_values=-1)[None, :]
        rmeta = route["rmeta"].astype(jnp.int32)
        leaf_p = None
    else:
        leaf_p = jnp.pad(label.astype(jnp.int32), (0, n_pad - N),
                         constant_values=nslots)[None, :]
    iota_bins = (jnp.arange(B * fblk, dtype=jnp.int32)
                 // fblk).astype(jnp.float32)[None, :]

    def padf(a, cv, dtype=jnp.int32):
        return jnp.pad(a.astype(dtype), (0, f_pad - F),
                       constant_values=cv)[None, :]

    nb_p = padf(meta.num_bins, 1)
    mt_p = padf(meta.missing_type, 0)
    nanb_p = padf(meta.nan_bin, -1)
    zb_p = padf(meta.zero_bin, 0)
    us_p = padf(meta.usable, 0)
    mono_p = padf(meta.monotone_type, 0)
    has_contri = meta.contri is not None
    contri_p = padf(meta.contri, 1.0, jnp.float32) if has_contri else None
    mask_p = jnp.pad(mask.astype(jnp.int8), ((0, 0), (0, f_pad - F)))
    parent_p = (jnp.pad(parent.astype(jnp.float32),
                        ((0, 0), (0, f_pad - F), (0, 0), (0, 0)))
                if sub else None)
    csums2 = csums.astype(jnp.float32)
    constr2 = constr.astype(jnp.float32)
    depth2 = depth.astype(jnp.int32)[:, None]
    pout2 = pout.astype(jnp.float32)[:, None]
    sml2 = sml.astype(jnp.int32)[:, None] if sub else None
    child_scale = cscale is not None

    kern = functools.partial(
        _fused_kernel, nrt=nrt, num_bins=B, fblk=fblk,
        precision=precision, interpret=interpret, params=params,
        use_mc=use_mc, monotone_penalty=monotone_penalty,
        has_contri=has_contri, sub=sub, apply_scale=apply_scale,
        child_scale=child_scale, nslots=nslots, nchildren=C, fpb=fpb)

    def full_spec(shape):
        nd = len(shape)
        return pl.BlockSpec(shape, lambda fb, rt, _n=nd: (0,) * _n)

    res_blocks, hs_blocks = [], []
    new_leaf = None
    for fb in range(nfb):
        route_blk = route is not None and fb == 0
        sl = slice(fb * fblk, (fb + 1) * fblk)
        bsl = slice(fb * tile_cols, (fb + 1) * tile_cols)
        ins = [iota_bins, binned_rm[:, bsl], g3t]
        specs = [
            pl.BlockSpec((1, fblk * B), lambda fb_, rt: (0, 0)),
            pl.BlockSpec((T, tile_cols), lambda fb_, rt: (rt, 0)),
            pl.BlockSpec((3, T), lambda fb_, rt: (0, rt)),
        ]
        if route_blk:
            # block 0 routes: decision bins + old leaf ids per row tile,
            # packed slot metadata resident; the label it emits becomes
            # the remaining blocks' ``leaf`` input below
            ins += [dbin_p, oleaf_p, rmeta]
            specs += [pl.BlockSpec((1, T), lambda fb_, rt: (0, rt)),
                      pl.BlockSpec((1, T), lambda fb_, rt: (0, rt)),
                      full_spec(rmeta.shape)]
        else:
            ins.append(leaf_p)
            specs.append(pl.BlockSpec((1, T), lambda fb_, rt: (0, rt)))
        ins += [nb_p[:, sl], mt_p[:, sl], nanb_p[:, sl], zb_p[:, sl],
                us_p[:, sl], mono_p[:, sl]]
        specs += [full_spec((1, fblk))] * 6
        if has_contri:
            ins.append(contri_p[:, sl])
            specs.append(full_spec((1, fblk)))
        ins.append(mask_p[:, sl])
        specs.append(full_spec((C, fblk)))
        for a in (csums2, constr2, depth2, pout2):
            ins.append(a)
            specs.append(full_spec(a.shape))
        if child_scale:
            ins.append(cscale.astype(jnp.float32))
            specs.append(full_spec((C, 3)))
        if sub and apply_scale:
            ins.append(sscale.astype(jnp.float32))
            specs.append(full_spec((nslots, 3)))
        if sub:
            ins += [sml2, parent_p[:, sl]]
            specs += [full_spec((nslots, 1)),
                      full_spec((nslots, fblk, B, 3))]
        out_shape = [jax.ShapeDtypeStruct((C, fblk, RES_COLS),
                                          jnp.float32)]
        out_specs = [full_spec((C, fblk, RES_COLS))]
        if sub:
            out_shape.append(
                jax.ShapeDtypeStruct((nslots, fblk, B, 3), jnp.float32))
            out_specs.append(full_spec((nslots, fblk, B, 3)))
        if route_blk:
            out_shape += [jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
                          jax.ShapeDtypeStruct((1, n_pad), jnp.int32)]
            out_specs += [pl.BlockSpec((1, T), lambda fb_, rt: (0, rt)),
                          pl.BlockSpec((1, T), lambda fb_, rt: (0, rt))]
        out = pl.pallas_call(
            functools.partial(kern, route_blk=route_blk),
            grid=(1, nrt),
            in_specs=specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((1, out_rows, fblk * B),
                                       jnp.float32)],
            interpret=interpret,
        )(*ins)
        res_blocks.append(out[0])
        if sub:
            hs_blocks.append(out[1])
        if route_blk:
            leaf_p = out[2 if sub else 1]         # the emitted label
            new_leaf = out[3 if sub else 2][0, :N]
    residue = (jnp.concatenate(res_blocks, axis=1)
               if nfb > 1 else res_blocks[0])[:, :F]
    hsmall = None
    if sub:
        hsmall = (jnp.concatenate(hs_blocks, axis=1)
                  if nfb > 1 else hs_blocks[0])[:, :F]
    return residue, hsmall, new_leaf


def _route_only_kernel(dbin_ref, oleaf_ref, rmeta_ref, out_ref):
    """One routing-only tile: the fused decision stage (``route_tile``)
    with no histogram behind it — the valid-set lane."""
    new_leaf, _ = route_tile(dbin_ref[...], oleaf_ref[...],
                             rmeta_ref[...], nslots=0, sub=False,
                             want_label=False)
    out_ref[...] = new_leaf


def fused_route_rows(binned, lids, *, feats, thrs, dls, leafs, nls,
                     num_leaves, meta, interpret, row_tile=1024,
                     packed=False):
    """Route one row set through a round's committed splits with the
    SAME kernel decision stage the megakernel runs on the train rows —
    the valid-set lane of the single-pass round (ISSUE 15).

    Replaces the staged gather chain (per-split bin gather + (S, N)
    masks in HBM): one O(N) decision-bin gather feeds a routing-only
    Pallas grid whose tiles evaluate ``route_tile`` in VMEM and emit
    only the updated leaf ids.  Every update term is int32, so the
    result is bit-identical to the staged ``go_left_s``/
    ``route_pending`` routing (pinned in tests/test_wave_fused.py).
    ``packed``: ``binned`` is the 4-bit matrix — the decision-bin
    gather decodes nibbles (``decision_bins``), same int32 values.
    """
    N = lids.shape[0]
    if N == 0:
        return lids
    dbin = decision_bins(binned, lids, feats, leafs, num_leaves,
                         packed=packed)
    rmeta = pack_route_meta(feats, thrs, dls, leafs, nls, meta)
    T = min(row_tile, max(128, -(-N // 128) * 128))
    nrt = -(-N // T)
    n_pad = nrt * T
    dbin_p = jnp.pad(dbin, (0, n_pad - N))[None, :]
    oleaf_p = jnp.pad(lids.astype(jnp.int32), (0, n_pad - N),
                      constant_values=-1)[None, :]
    out = pl.pallas_call(
        _route_only_kernel,
        grid=(nrt,),
        in_specs=[
            pl.BlockSpec((1, T), lambda rt: (0, rt)),
            pl.BlockSpec((1, T), lambda rt: (0, rt)),
            pl.BlockSpec(rmeta.shape, lambda rt: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, T), lambda rt: (0, rt)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
        interpret=interpret,
    )(dbin_p, oleaf_p, rmeta)
    return out[0, :N]


def _pick_pack(residue_c, shift_c, parent_sum_c, meta, num_bins):
    """Cross-feature half of ``scan_pick`` on one child's O(F) residue,
    plus the non-categorical tail of ``_find_best_split`` (right sums,
    missing default direction) — the packed per-slot SplitInfo the round
    emits.  Formula-for-formula the staged code, evaluated on identical
    inputs, so the pick is bit-identical."""
    fbest = residue_c[:, 0]
    gsel = residue_c[:, 1]
    sel = residue_c[:, 2].astype(jnp.int32)
    gbest = jnp.max(fbest)
    feature = jnp.argmax(fbest >= gbest - tie_tol(gbest, shift_c)) \
        .astype(jnp.int32)                   # first in band = min feature
    best_gain = gsel[feature]
    sc = sel[feature]
    direction = (sc // num_bins).astype(jnp.int32)
    threshold = (sc % num_bins).astype(jnp.int32)
    left = residue_c[feature, 3:6]
    right = parent_sum_c - left
    mtype = meta.missing_type[feature]
    default_left = jnp.where(
        (mtype == MISSING_NAN) | (mtype == MISSING_ZERO),
        direction == 1, False)
    rel_gain = jnp.where(jnp.isfinite(best_gain), best_gain, NEG_INF)
    return jnp.concatenate([
        jnp.stack([rel_gain.astype(jnp.float32),
                   feature.astype(jnp.float32),
                   threshold.astype(jnp.float32),
                   default_left.astype(jnp.float32)]),
        left.astype(jnp.float32), right.astype(jnp.float32)])


def pack_children(res: SplitResult) -> jnp.ndarray:
    """Batched SplitResult -> the (C, PACK_COLS) wire rows (no bitset —
    the fused path never produces categorical splits)."""
    return jnp.concatenate([
        res.gain[:, None],
        res.feature.astype(jnp.float32)[:, None],
        res.threshold_bin.astype(jnp.float32)[:, None],
        res.default_left.astype(jnp.float32)[:, None],
        res.left_sum, res.right_sum], axis=1)


def unpack_children(packed: jnp.ndarray, num_bins: int) -> SplitResult:
    """(C, PACK_COLS) rows -> batched SplitResult (is_cat False, zero
    bitset — the fused gate excludes categorical datasets)."""
    W = -(-num_bins // 32)
    C = packed.shape[0]
    return SplitResult(
        gain=packed[:, 0],
        feature=packed[:, 1].astype(jnp.int32),
        threshold_bin=packed[:, 2].astype(jnp.int32),
        default_left=packed[:, 3] != 0,
        left_sum=packed[:, 4:7],
        right_sum=packed[:, 7:10],
        is_cat=jnp.zeros(C, bool),
        cat_bitset=jnp.zeros((C, W), jnp.uint32),
    )


def make_fused_round(*, meta, params, num_bins, precision, deep_precision,
                     monotone_penalty=0.0, interpret=False,
                     axis_name=None, packed=False):
    """Build the grower-facing ``fused_round_fn``.

    ``fused_round(binned, g3, label, S, *, deep, quant_key, scaled,
    mask, csums, constr, depth, pout, sml, parent, meta_override,
    feature_rebase, route) -> (packed (2S, PACK_COLS), hsmall or None,
    slot_scales (nslots, 3))`` — plus ``new_leaf (N,)`` when routed.

    * ``route`` non-None (dict ``leaf_id (N,) / feats / thrs / dls /
      leafs / nls (S,) / num_leaves``) folds the round's PARTITION into
      the kernel (ISSUE 15): ``label`` must be None — the kernel
      evaluates the committed splits' go-left decisions in VMEM
      (``route_tile`` + the staged partition's own
      ``split.go_left_rule``) while sweeping the rows for the
      histograms, and the call returns the updated per-row leaf ids as
      a fourth output.  The decision-bin gather (``decision_bins``,
      O(N) bytes) is the routing stage's only extra touch of the binned
      matrix — the round reads the binned rows ONCE.  The builder marks
      the returned callable ``supports_route=True`` and hangs the
      valid-set router on it as ``route_rows`` (same decision stage
      over a valid binned matrix); the feature-parallel trainer wrapper
      deliberately has neither (its shard sees only a feature slice —
      the partition-specific fallback of the module taxonomy).

    * ``deep`` — sustained-bucket round: the kernel accumulates at
      ``deep_precision`` (the staged deep-dtype policy, so precision per
      bucket cannot drift between the paths).
    * ``quant_key`` non-None — an int8sr-eligible bucket
      (models/grower_wave.py quant gate: the sustained bucket and the
      16-slot ramp of a K>16 wave; root and <=4-slot ramps never reach
      here): the gradients are stochastic-round quantized with the SAME
      ``sr_quantize_g3`` call the staged pass makes, and the dequantize
      multiply folds into the in-VMEM subtraction (or the scan's integer
      cumsum pool-free) exactly where the staged path folds it.
    * ``scaled`` — quant buckets exist this grow (the staged path then
      applies identity scales on non-quant rounds too; mirrored for bit
      parity).
    * ``meta_override``/``feature_rebase`` — the feature-parallel
      learner passes its (traced) per-shard meta slice and block offset;
      packed feature ids come back shard-local and are rebased by the
      caller after the SplitInfo election.
    * ``packed`` (builder-static, ISSUE 18) — the binned matrix is the
      4-bit ``(ceil(F/2), N)`` layout; the kernel unpacks nibbles in
      VMEM and the routing stage (train AND valid: ``route_rows`` binds
      it too) decodes decision bins from the packed bytes.
    """
    from .quantize import sr_quantize_g3

    use_mc = bool(np.asarray(meta.monotone_type).any())

    def fused_round(binned, g3, label, S, *, deep=False, quant_key=None,
                    scaled=False, mask=None, csums=None, constr=None,
                    depth=None, pout=None, sml=None, parent=None,
                    meta_override=None, route=None):
        sub = parent is not None
        C = 2 * S
        nslots = S if sub else C
        m = meta_override if meta_override is not None else meta
        if quant_key is not None:
            # routed rounds have no precomputed label; sr_quantize_g3's
            # global-scale implementation ignores it (per-pass scales),
            # so the rounding stream — and int8sr bit-reproducibility —
            # is identical to the staged pass either way
            q3, scales = sr_quantize_g3(
                g3, route["leaf_id"] if route is not None else label,
                nslots, quant_key, axis_name=axis_name)
            g3u, prec = q3, "int8sr"
        else:
            scales = jnp.ones((nslots, 3), jnp.float32)
            g3u = g3
            prec = deep_precision if deep else precision
        route_in = None
        if route is not None:
            route_in = dict(
                dbin=decision_bins(binned, route["leaf_id"],
                                   route["feats"], route["leafs"],
                                   route["num_leaves"], packed=packed),
                oleaf=route["leaf_id"],
                rmeta=pack_route_meta(route["feats"], route["thrs"],
                                      route["dls"], route["leafs"],
                                      route["nls"], m, sml=sml))
        with jax.named_scope("lgbm.fused_round"):
            residue, hsmall, new_leaf = fused_wave_scan(
                binned, g3u, label, nslots=nslots, nchildren=C,
                num_bins=num_bins, precision=prec, interpret=interpret,
                meta=m, params=params, use_mc=use_mc,
                monotone_penalty=monotone_penalty, mask=mask,
                csums=csums, constr=constr, depth=depth, pout=pout,
                cscale=(scales if (scaled and not sub) else None),
                sscale=(scales if (scaled and sub) else None),
                sml=sml, parent=parent, apply_scale=(scaled and sub),
                route=route_in, packed=packed)
            shift = jax.vmap(
                lambda ps, po: gain_shift(ps, po, params))(csums, pout)
            ptab = jax.vmap(
                lambda rc, sh, ps: _pick_pack(rc, sh, ps, m, num_bins)
            )(residue, shift, csums)
        if route is not None:
            return ptab, hsmall, scales, new_leaf
        return ptab, hsmall, scales

    fused_round.supports_route = True
    fused_round.packed = packed
    fused_round.route_rows = functools.partial(
        fused_route_rows, meta=meta, interpret=interpret, packed=packed)
    return fused_round


class _ValRef:
    """Minimal ref-shaped adapter over a VALUE so kernel helpers written
    against Pallas refs (``_hist_tile``'s g3/leaf inputs) can consume
    values the loop kernel computed in-register — the quantized gradient
    rows and the routing label — without a scratch round-trip."""

    def __init__(self, v):
        self._v = v

    @property
    def shape(self):
        return self._v.shape

    @property
    def dtype(self):
        return self._v.dtype

    def __getitem__(self, idx):
        return self._v[idx]


_LOOP_MAX_ROUNDS = 64
_LOOP_VMEM_BUDGET = 14 * 2 ** 20


def plan_wave_loop(*, rounds, N, F, num_bins, K, L, use_sub, slot_buckets,
                   quant_buckets=(), precision="f32", deep_precision="f32",
                   use_mc=False, packed=False,
                   vmem_budget=_LOOP_VMEM_BUDGET):
    """Static VMEM-budget planner for the persistent wave loop.

    Decides — entirely at trace/build time, from shapes and knobs — how
    many consecutive rounds ``R`` one launch may run and whether the
    loop is eligible at all; the returned dict is recorded verbatim in
    the BENCH record (``measure_fused_waveloop``) so a capture shows WHY
    a shape ran looped or fell back.  The resident-state footprint is
    R-independent (the packed SplitInfo tables stream out per round), so
    R is capped only by the sanity bound ``_LOOP_MAX_ROUNDS``; the
    budget decides looped-vs-single-round, and the slot-bucket LADDER
    constraint below decides whether the staged bucket dispatch can be
    mimicked bit-exactly inside one kernel:

    * the row tile must be IDENTICAL for every ladder bucket — the loop
      accumulates every round at the K-slot tile, and a bucket whose
      staged tile differs would change the f32 accumulation order;
    * int8sr rounds inside the loop require ``precision == "f32"``: the
      loop accumulates the exact-integer quantized rows through the f32
      MXU path, which matches the staged int8 path bit-for-bit BECAUSE
      both are exact (|q| <= 127, <= 1024 rows per tile => every per-tile
      partial sum < 2^24), but a bf16 base precision would not be;
    * a reachable deep bucket (K >= 32, multi-bucket ladder, no quant)
      requires ``deep_precision == precision`` — one static accumulate
      dtype for the whole loop.

    ``packed`` (ISSUE 18): the loop keeps the 4-bit PACKED matrix
    resident — the bins row tile is priced on packed bytes (HALF), and
    the kernel feature width is the even ``2*ceil(F/2)`` nibble span
    (the phantom odd-F feature rides masked-unusable).  The row tile
    itself is still derived from the UNPACKED lane count, so packed and
    unpacked loops share the accumulation partition (bit parity).
    """
    B = num_bins
    Fk = 2 * -(-F // 2) if packed else F    # kernel feature width
    Fb = -(-F // 2) if packed else F        # stored bins columns

    def staged_tile(S):
        """The row tile of the staged pass of bucket ``S`` (a quantized
        bucket's is the int8sr kernel's)."""
        rows = pass_rows(S if use_sub else 2 * S,
                         "int8sr" if S in quant_buckets else precision)[2]
        return _row_tile_for(rows, F * B, B)

    out_rows = pass_rows(K if use_sub else 2 * K, precision)[2]
    T = _row_tile_for(out_rows, F * B, B)
    nrt = -(-max(N, 1) // T)
    n_pad = nrt * T
    acc_bytes = out_rows * Fk * B * 4
    # the one-hot working set _row_tile_for budgets for, per row tile,
    # plus the resident bins row tile (packed bytes when packed — the
    # layout's VMEM dividend)
    stream_bytes = T * (14 * min(Fk * B, 512) + 16 * out_rows) + T * Fb
    state_bytes = (L * 12 * 4 + n_pad * 4
                   + (L * Fk * B * 3 * 4 if use_sub else 0))
    total_bytes = acc_bytes + stream_bytes + state_bytes
    plan = dict(eligible=False, rounds=1, reason="",
                acc_bytes=int(acc_bytes), state_bytes=int(state_bytes),
                stream_bytes=int(stream_bytes),
                total_bytes=int(total_bytes), row_tile=int(T),
                ladder=tuple(int(s) for s in slot_buckets),
                vmem_budget=int(vmem_budget),
                packed=bool(packed),
                binned_bytes=int(Fb * max(N, 1)),
                binned_tile_bytes=int(T * Fb))
    if rounds <= 1:
        plan["reason"] = "wave_loop_rounds=1 (single-round dispatch)"
        return plan
    if Fk * B > MAX_LANES:
        plan["reason"] = ("F*num_bins > MAX_LANES: multi-feature-block "
                          "rounds keep the single-round kernel")
        return plan
    if use_mc:
        plan["reason"] = ("monotone constraints propagate per-round "
                          "bounds outside the kernel")
        return plan
    if quant_buckets and precision != "f32":
        plan["reason"] = ("int8sr-in-loop needs the exact-integer f32 "
                          "accumulate (hist_dtype=f32)")
        return plan
    if (not quant_buckets and K >= 32 and len(slot_buckets) > 1
            and deep_precision != precision):
        plan["reason"] = ("deep-precision drop would change the "
                          "accumulate dtype mid-loop")
        return plan
    if {staged_tile(S) for S in slot_buckets} != {T}:
        plan["reason"] = ("slot-bucket ladder changes the row tile "
                          "(accumulation order would differ)")
        return plan
    if total_bytes > vmem_budget:
        plan["reason"] = (
            f"resident state + accumulator ({total_bytes} B) exceeds the "
            f"VMEM budget ({vmem_budget} B)")
        return plan
    plan["eligible"] = True
    plan["rounds"] = int(min(rounds, _LOOP_MAX_ROUNDS))
    return plan


def _loop_kernel(*refs, R, nrt, T, num_bins, fblk, N, K, L,
                 precision, interpret, params, monotone_penalty,
                 has_contri, sub, scaled, ladder, quant_ladder, max_depth,
                 topk_fn, qmax, packed=False):
    """Grid ``(R, row_tiles)`` — R consecutive wave rounds in ONE launch,
    the frontier state resident in VMEM scratch between them:

    * ``ft_scr`` (L, 12) — the frontier table: per-leaf [gain, feature,
      threshold, default_left, left sums (3), right sums (3), output,
      depth], exactly the split-store columns the staged round boundary
      reads back from HBM;
    * ``pool_scr`` (L, F, B, 3) — the histogram pool (subtraction mode);
    * ``leaf_scr`` (1, n_pad) — row -> leaf routing labels;
    * ``nl_scr`` (1, 1) — the leaf count;
    * ``acc`` — the per-round histogram accumulator (re-zeroed by
      ``_hist_tile``'s own ``program_id(1) == 0`` guard each round).

    Every tile RECOMPUTES the round boundary (top-k over the frontier
    gains, slot compaction, routing metadata) from ``ft_scr`` — the
    table is frozen for the whole round (the commit below only runs on
    the last tile, after this recompute in program order), so all tiles
    derive identical values: O(K) math against an O(N/nrt) row sweep,
    and it saves a per-round metadata scratch plus an init-ordering
    hazard.  The boundary math is the staged round's own code objects
    (``_topk_by_rank``, ``route_tile``/``pack_route_meta``,
    ``child_scan_residue``, ``child_leaf_output``, ``_pick_pack``) on
    the same values, so the emitted per-round packed SplitInfo — all
    the host replay consumes — is bit-identical to R staged rounds.

    Staged-bucket mimicry: the staged ``round_pass`` dispatches a
    slot-bucket ladder (``lax.switch``) and decides int8sr per bucket;
    the loop always accumulates at the K-slot shape but computes the
    bucket the staged path WOULD have picked (``S_eff``) to reproduce
    its quant decision per round.  Real slot rows are invariant to the
    bucket width (each accumulator row's one-hot matmul and each
    child's scan are per-row independent), which the planner's uniform
    row-tile gate makes exact — dead-slot rows differ but are never
    read.  An exhausted frontier makes every remaining round a bit-exact
    no-op (all scatters drop, the leaf count stays put)."""
    quant = bool(quant_ladder)
    names = ["iota", "bins", "g3"]
    if quant:
        names.append("zq")
    names += ["oleaf0", "ft0", "nl0"]
    if quant:
        names += ["qkey", "qscale"]
    names += ["nb", "mt", "nanb", "zb", "usbl", "mono"]
    if has_contri:
        names.append("contri")
    names.append("mask")
    if sub:
        names.append("pool0")
    names += ["packed", "nleaf"]
    if sub:
        names.append("pool")
    names += ["acc", "ft_scr", "nl_scr", "leaf_scr"]
    if sub:
        names.append("pool_scr")
    r = dict(zip(names, refs))

    ri = pl.program_id(0)
    rt = pl.program_id(1)
    B = num_bins
    C = 2 * K
    nsl = K if sub else C

    @pl.when((ri == 0) & (rt == 0))
    def _init():
        r["ft_scr"][...] = r["ft0"][...]
        r["nl_scr"][...] = r["nl0"][...]
        if sub:
            r["pool_scr"][...] = r["pool0"][...]

    # ---- round boundary, recomputed per tile from the frozen table ----
    ft = r["ft_scr"][...]                               # (L, 12)
    nl = r["nl_scr"][0, 0]
    vals, leafs = topk_fn(ft[:, 0], K)
    kiota = jnp.arange(K, dtype=jnp.int32)
    budget = L - nl
    valid = (vals > 0) & (kiota < budget)
    n_split = jnp.sum(valid.astype(jnp.int32))
    order = jnp.cumsum(valid.astype(jnp.int32)) - 1
    nls = nl + order
    order_c = jnp.clip(order, 0, K - 1)
    rows = ft[leafs]                                    # (K, 12)
    feats = rows[:, 1].astype(jnp.int32)
    thrs = rows[:, 2].astype(jnp.int32)
    dls = rows[:, 3] != 0
    lsums = rows[:, 4:7]
    rsums = rows[:, 7:10]
    pout = rows[:, 10]
    d = rows[:, 11].astype(jnp.int32) + 1               # child depth
    sm_left = lsums[:, 2] <= rsums[:, 2]
    sidx = jnp.where(valid, order_c, K)

    def to_slot(v, fill):
        base = jnp.full((K,) + v.shape[1:], fill, v.dtype)
        return base.at[sidx].set(v, mode="drop")

    feats_s = to_slot(feats, 0)
    thrs_s = to_slot(thrs, 0)
    dls_s = to_slot(dls, False)
    leafs_s = to_slot(leafs, L)
    nls_s = to_slot(nls, 0)
    sml_s = to_slot(sm_left, False)

    # the slot bucket the STAGED round_pass would dispatch decides the
    # round's quant treatment (the lax.switch index, mirrored)
    s_idx = jnp.zeros((), jnp.int32)
    for S in ladder[:-1]:
        s_idx = s_idx + (n_split > S).astype(jnp.int32)
    # scalar-literal select (a constant ladder array would be a captured
    # const, which pallas_call rejects)
    S_eff = jnp.full((), ladder[0], jnp.int32)
    for i, S in enumerate(ladder[1:], 1):
        S_eff = jnp.where(s_idx >= i, jnp.int32(S), S_eff)
    quant_r = jnp.zeros((), bool)
    for S in quant_ladder:
        quant_r = quant_r | (S_eff == S)

    meta_blk = FeatureMeta(
        num_bins=r["nb"][...][0],
        missing_type=r["mt"][...][0],
        nan_bin=r["nanb"][...][0],
        zero_bin=r["zb"][...][0],
        is_categorical=jnp.zeros(fblk, bool),
        usable=r["usbl"][...][0] != 0,
        monotone_type=r["mono"][...][0],
        contri=(r["contri"][...][0] if has_contri else None),
    )

    # ---- routing: round 0 reads the input leaf ids, later rounds the
    # resident ones; every (round, tile) rewrites its slice + output ----
    oleaf = jnp.where(ri == 0, r["oleaf0"][...],
                      r["leaf_scr"][:, pl.ds(rt * T, T)])
    tab = jnp.zeros(L + 1, jnp.int32) \
        .at[leafs_s].set(feats_s, mode="drop")
    f_of = tab[oleaf[0]]
    bins_t = r["bins"][...].astype(jnp.int32)     # (T, fblk | fblk//2)
    if packed:
        # nibble-decode decision lane (packed_bins_of_rows' layout, in
        # VMEM): gather the packed byte, select by feature parity — the
        # select form avoids a variable-amount vector shift
        byte = jnp.take_along_axis(bins_t, (f_of >> 1)[:, None],
                                   axis=1)[:, 0]
        dbin = (jnp.where((f_of & 1) == 1, byte >> 4, byte)
                & 15)[None, :]
    else:
        dbin = jnp.take_along_axis(bins_t, f_of[:, None],
                                   axis=1)[:, 0][None, :]
    rmeta = pack_route_meta(feats_s, thrs_s, dls_s, leafs_s, nls_s,
                            meta_blk, sml=sml_s)
    new_leaf, label = route_tile(dbin, oleaf, rmeta, nslots=nsl, sub=sub)
    r["leaf_scr"][:, pl.ds(rt * T, T)] = new_leaf
    r["nleaf"][...] = new_leaf

    # ---- histogram accumulate (quant rounds: the staged int8sr stream,
    # drawn here per (iteration, round) key — exact integers through the
    # f32 path, see plan_wave_loop) ----
    g3v = r["g3"][...]                                  # (3, T)
    if quant:
        kdat = r["qkey"][...][0]                        # (2,) uint32
        rkey = jax.random.fold_in(kdat, 8_000_011 + nl)
        u = jax.random.uniform(rkey, (N, 2), dtype=jnp.float32)
        u_pad = jnp.zeros((nrt * T, 2), jnp.float32).at[:N].set(u)
        u_t = lax.dynamic_slice(u_pad, (rt * T, 0), (T, 2))
        zq = r["zq"][...]                               # (3, T)
        q = jnp.clip(jnp.floor(zq[:2] + u_t.T), -qmax, qmax)
        val3 = jnp.where(quant_r,
                         jnp.concatenate([q, zq[2:3]], axis=0), g3v)
    else:
        val3 = g3v
    _hist_tile(r["iota"], r["bins"], _ValRef(val3), _ValRef(label),
               r["acc"], num_leaves=nsl, num_bins=B, fblk=fblk,
               precision=precision, interpret=interpret, packed=packed)

    @pl.when(rt == nrt - 1)
    def _commit():
        acc = r["acc"][0, :3 * nsl]
        h = acc.reshape(3, nsl, B, fblk).transpose(1, 3, 2, 0)
        if packed:
            # [lo nibbles | hi nibbles] -> natural feature order BEFORE
            # the order-sensitive tie-band pick (and the pool commit,
            # which the host replay reads in natural order)
            h = jnp.stack([h[:, :fblk // 2], h[:, fblk // 2:]], axis=2) \
                .reshape(nsl, fblk, B, 3)
        ones3 = jnp.ones((1, 3), jnp.float32)
        scale3 = (jnp.where(quant_r, r["qscale"][...], ones3)
                  if quant else ones3)                  # (1, 3)
        if sub:
            hsm = h[:K]
            # power-of-two scales (ops/quantize.py) make the dequant
            # product exact, so the parent subtraction below rounds
            # identically to the host grower's subtract_child_hists in
            # any fusion context (fma or separate mul+sub)
            hsm_sc = hsm * scale3[:, None, None, :] if scaled else hsm
            pr = jnp.zeros((K,) + h.shape[1:], jnp.float32) \
                .at[sidx].set(r["pool_scr"][...][leafs], mode="drop")
            smlb = sml_s[:, None, None, None]
            h_left = jnp.where(smlb, hsm_sc, pr - hsm_sc)
            h_right = pr - h_left
            ch = jnp.stack([h_left, h_right], axis=1).reshape(
                (C,) + h_left.shape[1:])
        else:
            ch = h[:C]


        csidx = (2 * sidx[:, None]
                 + jnp.arange(2, dtype=jnp.int32)[None, :]).reshape(C)

        def to_cslot(v, fill):
            base = jnp.full((C,) + v.shape[1:], fill, v.dtype)
            return base.at[csidx].set(v, mode="drop")

        cleafs = jnp.stack([leafs, nls], axis=1).reshape(C)
        csums = jnp.stack([lsums, rsums], axis=1).reshape(C, 3)
        def no_con(n):
            # built from scalar literals — a (2,) constant array would be
            # a captured const, which pallas_call rejects
            return jnp.stack(
                [jnp.full((n,), NO_CONSTRAINT[0], jnp.float32),
                 jnp.full((n,), NO_CONSTRAINT[1], jnp.float32)], axis=1)

        pconstr = no_con(K)
        clamp = jax.vmap(lambda s, c, p: child_leaf_output(
            s, c, p, params, use_mc=False))
        out_l = clamp(lsums, pconstr, pout)
        out_r = clamp(rsums, pconstr, pout)
        couts = jnp.stack([out_l, out_r], axis=1).reshape(C)
        dd = jnp.stack([d, d], axis=1).reshape(C)
        depth_ok = (max_depth <= 0) | (dd < max_depth)
        cconstr = no_con(C)
        mask_row = r["mask"][...][0] != 0
        cmask = jnp.broadcast_to(mask_row[None, :], (C, fblk))
        mask_c = to_cslot(cmask, False)
        csums_c = to_cslot(csums, 1.0)
        constr_c = to_cslot(cconstr, 0.0)
        depth_c = to_cslot(dd, 1)
        pout_c = to_cslot(couts, 0.0)

        child_scale = scaled and not sub
        cscale_c = (jnp.broadcast_to(scale3, (C, 3)) if child_scale
                    else jnp.zeros((C, 3), jnp.float32))
        scan_fn = functools.partial(
            child_scan_residue, meta_blk=meta_blk, params=params,
            use_mc=False, monotone_penalty=monotone_penalty,
            child_scale=child_scale, num_bins=B, fblk=fblk)
        residue = jax.vmap(scan_fn)(ch, mask_c, csums_c, constr_c,
                                    depth_c, pout_c, cscale_c)
        shift = jax.vmap(
            lambda ps, po: gain_shift(ps, po, params))(csums_c, pout_c)
        ptab = jax.vmap(
            lambda rc, sh, ps: _pick_pack(rc, sh, ps, meta_blk, B)
        )(residue, shift, csums_c)
        r["packed"][...] = ptab[None]

        # frontier + pool commit — slot->rank gather then scatter-by-
        # child-leaf, the staged store.write's index math
        ch_idx = jnp.stack([2 * order_c, 2 * order_c + 1],
                           axis=1).reshape(C)
        cvalid = jnp.stack([valid, valid], axis=1).reshape(C)
        cidx = jnp.where(cvalid, cleafs, L + 1)
        pk = ptab[ch_idx]
        cgain = jnp.where(depth_ok, pk[:, 0], -jnp.inf)
        crows = jnp.concatenate([
            cgain[:, None], pk[:, 1:4], pk[:, 4:10], couts[:, None],
            dd.astype(jnp.float32)[:, None]], axis=1)
        r["ft_scr"][...] = ft.at[cidx].set(crows, mode="drop")
        r["nl_scr"][0, 0] = nl + n_split
        if sub:
            pool_new = r["pool_scr"][...].at[cidx].set(
                ch[ch_idx], mode="drop")
            r["pool_scr"][...] = pool_new

            @pl.when(ri == R - 1)
            def _flush():
                r["pool"][...] = pool_new


def make_fused_wave_loop(*, meta, params, num_bins, precision,
                         deep_precision, rounds, monotone_penalty=0.0,
                         interpret=False, packed=False):
    """Build the grower-facing persistent wave-loop driver (ROADMAP
    item 1's endpoint: R consecutive wave rounds per launch, frontier
    state resident in VMEM — the R-1 intermediate kernel launches and
    their leaf-id / hist-pool / split-table HBM round-trips disappear).

    ``fused_loop(binned, g3, leaf_id, ft12, num_leaves, key, *, K,
    slot_buckets, quant_buckets, max_depth, base_mask, pool=None)
    -> (packed (R, 2K, PACK_COLS), new_leaf (N,), pool or None)``:

    * ``ft12`` (L, 12) f32 — the frontier table snapshot (store columns
      gain..depth, models/grower_wave assembles it store-agnostically);
    * ``pool`` non-None selects subtraction mode and seeds the resident
      histogram pool; the updated pool comes back as the third output;
    * the per-round packed SplitInfo tables are ALL the host replay
      needs — the grower re-runs the R rounds' bookkeeping (store
      writes, valid-set routing, done flag) from them, bit-identically.

    Eligibility is decided by ``fused_loop.plan`` (``plan_wave_loop``
    with this builder's statics bound); the trainer keys the dispatch
    and the BENCH record off the same plan.  ``rounds == 1`` never
    builds a loop — the trainer dispatches the PR 15 single-round
    kernel, the exact degeneration the tests pin."""
    from ..models.grower_wave import _topk_by_rank
    from .quantize import INT8_QMAX, sr_prequantize_g3

    has_contri = meta.contri is not None
    use_mc = bool(np.asarray(meta.monotone_type).any())
    B = num_bins

    def fused_loop(binned, g3, leaf_id, ft12, num_leaves, key, *, K,
                   slot_buckets, quant_buckets, max_depth, base_mask,
                   pool=None):
        sub = pool is not None
        if packed:
            # binned is the RESIDENT (ceil(F/2), N) packed matrix; the
            # kernel's feature width is the even nibble span — an odd-F
            # tail's phantom hi-nibble feature rides masked-unusable
            # through every round (pads below) and is sliced off the
            # returned pool
            Fb, N = binned.shape            # stored packed byte rows
            F0 = int(meta.num_bins.shape[0])
            F = 2 * Fb                      # kernel feature width
        else:
            F, N = binned.shape
            F0, Fb = F, F
        fpad = F - F0                       # 0 or 1 (phantom feature)
        L = ft12.shape[0]
        C = 2 * K
        nsl = K if sub else C
        out_rows = pass_rows(nsl, precision)[2]
        # row tile from the UNPACKED lane count (plan_wave_loop's rule):
        # same T => same row partition => bit-identical f32 accumulation
        T = _row_tile_for(out_rows, F0 * B, B)
        nrt = -(-N // T)
        n_pad = nrt * T
        R = rounds
        quant = bool(quant_buckets)

        def full_spec(shape):
            nd = len(shape)
            return pl.BlockSpec(shape, lambda ri, rt, _n=nd: (0,) * _n)

        def row(a, dtype=jnp.int32, cv=0):
            a = a.astype(dtype)
            if fpad:
                a = jnp.pad(a, (0, fpad), constant_values=cv)
            return a[None, :]

        binned_rm = jnp.pad(binned, ((0, 0), (0, n_pad - N)),
                            constant_values=0 if packed else 255).T
        # (n_pad, Fb)
        g3t = jnp.pad(g3.astype(jnp.float32),
                      ((0, n_pad - N), (0, 0))).T       # (3, n_pad)
        oleaf_p = jnp.pad(leaf_id.astype(jnp.int32), (0, n_pad - N),
                          constant_values=-1)[None, :]
        iota_bins = (jnp.arange(B * F, dtype=jnp.int32)
                     // F).astype(jnp.float32)[None, :]

        ins = [iota_bins, binned_rm, g3t]
        specs = [
            pl.BlockSpec((1, F * B), lambda ri, rt: (0, 0)),
            pl.BlockSpec((T, Fb), lambda ri, rt: (rt, 0)),
            pl.BlockSpec((3, T), lambda ri, rt: (0, rt)),
        ]
        if quant:
            # key-independent half hoisted (sr_prequantize_g3); the loop
            # draws each round's uniforms in-kernel from the same
            # fold_in(key, 8_000_011 + num_leaves) stream the staged
            # rounds use — int8sr stays bit-reproducible through the loop
            zg, qc, scales = sr_prequantize_g3(g3, nsl)
            zq = jnp.pad(jnp.concatenate([zg, qc[:, None]], axis=1),
                         ((0, n_pad - N), (0, 0))).T    # (3, n_pad)
            ins.append(zq)
            specs.append(pl.BlockSpec((3, T), lambda ri, rt: (0, rt)))
        ins += [oleaf_p, ft12.astype(jnp.float32),
                jnp.asarray(num_leaves, jnp.int32).reshape(1, 1)]
        specs += [pl.BlockSpec((1, T), lambda ri, rt: (0, rt)),
                  full_spec((L, 12)), full_spec((1, 1))]
        if quant:
            kd = key
            if jnp.issubdtype(kd.dtype, jax.dtypes.prng_key):
                kd = jax.random.key_data(kd)
            ins += [kd.reshape(1, 2).astype(jnp.uint32), scales[0:1]]
            specs += [full_spec((1, 2)), full_spec((1, 3))]
        ins += [row(meta.num_bins, cv=1), row(meta.missing_type),
                row(meta.nan_bin, cv=-1), row(meta.zero_bin),
                row(meta.usable), row(meta.monotone_type)]
        specs += [full_spec((1, F))] * 6
        if has_contri:
            ins.append(row(meta.contri, jnp.float32, cv=1.0))
            specs.append(full_spec((1, F)))
        ins.append(row(base_mask, jnp.int8))
        specs.append(full_spec((1, F)))
        if sub:
            pool_in = pool.astype(jnp.float32)
            if fpad:
                pool_in = jnp.pad(pool_in,
                                  ((0, 0), (0, fpad), (0, 0), (0, 0)))
            ins.append(pool_in)
            specs.append(full_spec(pool_in.shape))

        out_shape = [
            jax.ShapeDtypeStruct((R, C, PACK_COLS), jnp.float32),
            jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
        ]
        out_specs = [
            pl.BlockSpec((1, C, PACK_COLS), lambda ri, rt: (ri, 0, 0)),
            pl.BlockSpec((1, T), lambda ri, rt: (0, rt)),
        ]
        if sub:
            out_shape.append(
                jax.ShapeDtypeStruct(pool_in.shape, jnp.float32))
            out_specs.append(full_spec(pool_in.shape))

        scratch = [
            pltpu.VMEM((1, out_rows, F * B), jnp.float32),   # acc
            pltpu.VMEM((L, 12), jnp.float32),             # ft_scr
            pltpu.VMEM((1, 1), jnp.int32),                # nl_scr
            pltpu.VMEM((1, n_pad), jnp.int32),            # leaf_scr
        ]
        if sub:
            scratch.append(pltpu.VMEM(pool_in.shape, jnp.float32))

        kern = functools.partial(
            _loop_kernel, R=R, nrt=nrt, T=T, num_bins=B,
            fblk=F, N=N, K=K, L=L, precision=precision,
            interpret=interpret, params=params,
            monotone_penalty=monotone_penalty, has_contri=has_contri,
            sub=sub, scaled=quant, ladder=tuple(slot_buckets),
            quant_ladder=tuple(quant_buckets), max_depth=max_depth,
            topk_fn=_topk_by_rank, qmax=INT8_QMAX, packed=packed)
        out = pl.pallas_call(
            kern, grid=(R, nrt), in_specs=specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch,
            interpret=interpret)(*ins)
        pool_out = out[2] if sub else None
        if sub and fpad:
            pool_out = pool_out[:, :F0]     # drop the phantom feature
        return out[0], out[1][0, :N], pool_out

    fused_loop.rounds = rounds
    fused_loop.packed = packed
    fused_loop.plan = functools.partial(
        plan_wave_loop, rounds=rounds, num_bins=num_bins,
        precision=precision, deep_precision=deep_precision,
        use_mc=use_mc, packed=packed)
    return fused_loop


def fused_ineligible_reason(*, meta, params, bin_dtype, num_bins,
                            packed=False, bundled=False) -> str:
    """Static eligibility gate — returns the fallback reason (one line of
    the module-docstring taxonomy) or ``""`` when the fused kernel can
    run.  Learner/grower routing gates live in parallel/trainer.py."""
    if bundled:
        return ("EFB bundle-space histograms expand to original features "
                "before the scan")
    if packed and num_bins > 16:
        return "4-bit packed bins hold num_bins <= 16 only"
    if np.dtype(bin_dtype).itemsize > 1:
        return "int16 bins exceed the uint8 one-hot kernel family"
    if num_bins > 256:
        return "num_bins > 256 exceeds the uint8 kernel family"
    if bool(np.asarray(meta.is_categorical).any()):
        return ("categorical sorted-scan (per-feature argsort) has no "
                "kernel lowering")
    if params.extra_trees:
        return "extra_trees draws per-node randomness inside the scan"
    return ""
