"""Exclusive Feature Bundling (EFB) — TPU-first densification.

Re-design of the reference's FeatureGroup/EFB machinery
(reference: ``src/io/dataset.cpp:41-235`` — ``GetConflictCount`` :50,
``FindGroups`` :97, ``FastFeatureBundling`` :236;
``include/LightGBM/feature_group.h:21`` FeatureGroup with per-feature bin
offsets).  Mutually-exclusive sparse features (rarely nonzero on the same
row) are packed into one dense *bundle* column, so the histogram pass —
the hot loop — runs over ``num_bundles`` columns instead of
``num_features``.  On TPU this is exactly what the MXU wants: thousands of
mostly-zero columns become a handful of dense ones, and the binned-matrix
HBM footprint drops proportionally.

Differences from the reference's encoding (simplicity over slot packing):

* Bundle bin 0 means "every member feature at its zero bin"; member ``f``
  with a non-zero bin ``b`` maps to ``offset_f + b``.  The reference elides
  each feature's most-frequent bin from its range
  (``feature_group.h:36-48``); here members keep their full bin range, so
  one slot per member (its zero bin) is unused — the per-feature histogram
  view is then a pure slice, and the zero-bin count is recovered from the
  parent totals exactly like the reference's ``FixHistogram``
  (``src/io/dataset.cpp:1410``).
* The model is untouched: trees always record ORIGINAL feature indices and
  thresholds in original bin space; bundling is invisible outside training
  (same property as the reference).

The greedy conflict-count grouping follows the reference/EFB paper: order
features by non-zero count descending, first-fit into the bundle whose
conflict count stays within budget, subject to the uint8 bin-capacity cap.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np

from ..utils.log import log_info, log_warning

MAX_BUNDLE_BINS = 256      # uint8 bundles only — the Pallas kernel's domain
_CONFLICT_SAMPLE = 32768   # rows sampled for conflict counting


@dataclass
class BundleLayout:
    """Mapping between original features and bundle columns.

    bundle_of:   (F,) int32 — bundle column of each original feature
    offset:      (F,) int32 — bin offset of the feature inside its bundle
                  (0 for singleton bundles: bundle bin == original bin)
    is_bundled:  (F,) bool  — True when the feature shares a bundle (its
                  zero-bin count must be recovered from parent totals)
    bundle_nbins:(BF,) int32 — total bins of each bundle column
    """

    bundle_of: np.ndarray
    offset: np.ndarray
    is_bundled: np.ndarray
    bundle_nbins: np.ndarray

    @property
    def num_bundles(self) -> int:
        return len(self.bundle_nbins)

    @property
    def num_features(self) -> int:
        return len(self.bundle_of)


def find_bundles(
    nonzero_masks: np.ndarray,      # (F, S) bool — sampled rows, bin != zero_bin
    num_bins: Sequence[int],        # (F,) per-feature bin counts
    max_conflict_rate: float = 0.0,
    max_bundle_bins: int = MAX_BUNDLE_BINS,
) -> Optional[BundleLayout]:
    """Greedy conflict-bounded grouping (reference ``FindGroups``,
    src/io/dataset.cpp:97-235).  Returns None when bundling would not
    reduce the column count (e.g. all-dense data)."""
    F, S = nonzero_masks.shape
    num_bins = np.asarray(num_bins, dtype=np.int64)
    budget = int(max_conflict_rate * S)

    nnz = nonzero_masks.sum(axis=1, dtype=np.int64)
    order = np.argsort(-nnz, kind="stable")
    group_masks: List[np.ndarray] = []       # aggregated nonzero per bundle
    group_nnz: List[int] = []                # rows set in that mask
    group_conflicts: List[int] = []          # conflicts spent per bundle
    group_bins: List[int] = []               # bins used (incl. shared bin 0)
    group_members: List[List[int]] = []

    # bounded search, like the reference's max_search_group random fallback
    # (dataset.cpp:119-130): without a cap the greedy loop is
    # O(F * groups * S) and stalls on 100k-feature inputs
    MAX_SEARCH = 256
    rng = np.random.RandomState(3)

    for f in order:
        fm = nonzero_masks[f]
        nb = int(num_bins[f])
        placed = False
        n_groups = len(group_masks)
        if n_groups <= MAX_SEARCH:
            candidates = range(n_groups)
        else:
            candidates = rng.choice(n_groups, size=MAX_SEARCH, replace=False)
        for g in candidates:
            # (reference GetConflictCount, dataset.cpp:50): rows where both
            # the bundle and the candidate are non-zero
            if group_bins[g] + nb > max_bundle_bins:
                continue
            # two sets of a and b of the S rows share at least a + b - S:
            # dense columns are told apart without looking at their rows
            if group_conflicts[g] + group_nnz[g] + int(nnz[f]) - S > budget:
                continue
            cnt = int(np.count_nonzero(group_masks[g] & fm))
            if group_conflicts[g] + cnt <= budget:
                group_masks[g] |= fm
                group_nnz[g] += int(nnz[f]) - cnt
                group_conflicts[g] += cnt
                group_bins[g] += nb
                group_members[g].append(int(f))
                placed = True
                break
        if not placed:
            group_masks.append(fm.copy())
            group_nnz.append(int(nnz[f]))
            group_conflicts.append(0)
            # +1: bundle bin 0 is the shared all-zero slot
            group_bins.append(1 + nb)
            group_members.append([int(f)])

    BF = len(group_members)
    if BF >= F:
        return None

    bundle_of = np.zeros(F, np.int32)
    offset = np.zeros(F, np.int32)
    is_bundled = np.zeros(F, bool)
    bundle_nbins = np.zeros(BF, np.int32)
    for g, members in enumerate(group_members):
        if len(members) == 1:
            f = members[0]
            bundle_of[f] = g
            offset[f] = 0                      # identity: bin == bundle bin
            bundle_nbins[g] = num_bins[f]
        else:
            off = 1                            # bin 0 = all members zero
            for f in members:
                bundle_of[f] = g
                offset[f] = off
                is_bundled[f] = True
                off += int(num_bins[f])
            bundle_nbins[g] = off
    return BundleLayout(bundle_of, offset, is_bundled, bundle_nbins)


def conflict_masks_from_dense(
    binned: np.ndarray,             # (F, N)
    zero_bins: Sequence[int],
    sample_cnt: int = _CONFLICT_SAMPLE,
    seed: int = 1,
) -> np.ndarray:
    """(F, S) bool sampled non-zero masks from a dense binned matrix."""
    F, N = binned.shape
    rng = np.random.RandomState(seed)
    if N > sample_cnt:
        idx = rng.choice(N, size=sample_cnt, replace=False)
        sub = binned[:, idx]
    else:
        sub = binned
    zb = np.asarray(zero_bins, dtype=binned.dtype)[:, None]
    return sub != zb


def apply_bundles_dense(binned: np.ndarray, zero_bins: Sequence[int],
                        layout: BundleLayout) -> np.ndarray:
    """(F, N) -> (BF, N) bundled matrix.  Conflicting rows (two members
    non-zero — possible when max_conflict_rate > 0) keep the LAST member's
    value, mirroring the reference's push-order overwrite."""
    F, N = binned.shape
    dtype = np.uint8 if int(layout.bundle_nbins.max()) <= 256 else np.int16
    out = np.zeros((layout.num_bundles, N), dtype=dtype)
    zb = np.asarray(zero_bins)
    for f in range(F):
        g = int(layout.bundle_of[f])
        if not layout.is_bundled[f]:
            out[g] = binned[f].astype(dtype)
            continue
        nz = binned[f] != zb[f]
        out[g][nz] = (layout.offset[f] + binned[f][nz]).astype(dtype)
    return out


def apply_bundles_csr(
    indptr: np.ndarray, indices: np.ndarray, bin_values: np.ndarray,
    num_data: int, zero_bins: Sequence[int], layout: BundleLayout,
) -> np.ndarray:
    """Build the (BF, N) bundled matrix straight from binned CSR triplets
    (row-compressed; ``bin_values`` are already ORIGINAL bin indices) —
    the wide-sparse input path never materializes the dense (F, N) matrix
    (reference analog: sparse push into FeatureGroup bins,
    dataset_loader.cpp:1003-1100)."""
    dtype = np.uint8 if int(layout.bundle_nbins.max()) <= 256 else np.int16
    out = np.zeros((layout.num_bundles, num_data), dtype=dtype)
    zb = np.asarray(zero_bins)
    # absent CSR entries mean raw 0.0: bundle bin 0 for bundled members, but
    # the feature's zero_bin for singleton bundles
    for f in np.where(~layout.is_bundled)[0]:
        if zb[f] != 0:
            out[int(layout.bundle_of[f])][:] = zb[f]
    rows = np.repeat(np.arange(num_data), np.diff(indptr))
    feats = indices
    nz = bin_values != zb[feats]
    bundle_bin = np.where(layout.is_bundled[feats],
                          layout.offset[feats] + bin_values,
                          bin_values)
    # bundled members write only their non-zero bins; singletons write every
    # explicit entry (including explicit zeros, already equal to zero_bin)
    w = nz | (~layout.is_bundled[feats])
    out[layout.bundle_of[feats[w]], rows[w]] = bundle_bin[w].astype(dtype)
    return out


class BundleArrays:
    """Device-resident layout arrays consumed by jitted code."""

    def __init__(self, layout: BundleLayout, zero_bins, num_bins):
        import jax.numpy as jnp

        self.bundle_of = jnp.asarray(layout.bundle_of, jnp.int32)
        self.offset = jnp.asarray(layout.offset, jnp.int32)
        self.is_bundled = jnp.asarray(layout.is_bundled)
        self.zero_bin = jnp.asarray(np.asarray(zero_bins), jnp.int32)
        self.num_bins = jnp.asarray(np.asarray(num_bins), jnp.int32)


def expand_bundle_hist(hist_b, parent_sum, ba: BundleArrays, num_bins: int,
                       columns=None, first_column=0):
    """(BF, Bb, 3) bundle histogram -> (F, B, 3) per-original-feature view.

    Each feature's non-zero bins are a slice of its bundle's histogram; the
    zero-bin count of a bundled feature is recovered from the parent totals
    (the analog of the reference's most-freq-bin recovery ``FixHistogram``,
    src/io/dataset.cpp:1410).  Singleton bundles are identity slices, so
    unbundled features see exactly the histograms they would without EFB.

    ``columns`` (W,) expands those features only (traced ids; past the end
    = no feature, a row nobody reads) out of a ``hist_b`` that holds the
    bundle columns from ``first_column`` on: the slice a device keeps
    after the data-parallel reduce-scatter.
    """
    import jax.numpy as jnp

    def of(x, fill):
        return x if columns is None else jnp.take(
            x, columns, mode="fill", fill_value=fill)

    Bb = hist_b.shape[1]
    B = num_bins
    bundle_of = ba.bundle_of
    if columns is not None:     # a padding id reads whatever column 0 holds
        bundle_of = jnp.clip(of(bundle_of, 0) - first_column,
                             0, hist_b.shape[0] - 1)
    offset, zero_bin = of(ba.offset, 0), of(ba.zero_bin, 0)
    F = bundle_of.shape[0]
    bins_iota = jnp.arange(B, dtype=jnp.int32)
    idx = offset[:, None] + bins_iota[None, :]                   # (F, B)
    v = hist_b[bundle_of[:, None], jnp.clip(idx, 0, Bb - 1)]     # (F, B, 3)
    valid = (bins_iota[None, :] < of(ba.num_bins, 0)[:, None]) & (idx < Bb)
    v = jnp.where(valid[..., None], v, 0.0)
    zfix = parent_sum[None, :] - v.sum(axis=1)                   # (F, 3)
    zb = jnp.clip(zero_bin, 0, B - 1)
    cur = v[jnp.arange(F), zb]                                   # (F, 3)
    newz = jnp.where(of(ba.is_bundled, False)[:, None], zfix, cur)
    return v.at[jnp.arange(F), zb].set(newz)


def bundle_bins_of_feat(bundled, feat, ba: BundleArrays):
    """(BF, N) bundled matrix -> (N,) ORIGINAL bins of feature ``feat``
    (traced scalar).  Rows outside the feature's bundle range are at the
    feature's zero bin."""
    import jax.numpy as jnp

    bb = bundled[ba.bundle_of[feat]].astype(jnp.int32)           # (N,)
    inner = bb - ba.offset[feat]
    in_range = (inner >= 0) & (inner < ba.num_bins[feat])
    mapped = jnp.where(in_range, inner, ba.zero_bin[feat])
    return jnp.where(ba.is_bundled[feat], mapped, bb)


def bundle_bins_of_rows(bundled, f_row, ba: BundleArrays):
    """Per-row feature variant: ``f_row`` (N,) -> (N,) original bins (the
    level-wise grower's decision pass)."""
    import jax.numpy as jnp

    g_row = ba.bundle_of[f_row]                                   # (N,)
    bb = jnp.take_along_axis(bundled, g_row[None, :], axis=0)[0] \
        .astype(jnp.int32)
    off = ba.offset[f_row]
    inner = bb - off
    in_range = (inner >= 0) & (inner < ba.num_bins[f_row])
    mapped = jnp.where(in_range, inner, ba.zero_bin[f_row])
    return jnp.where(ba.is_bundled[f_row], mapped, bb)


def maybe_bundle(binned: np.ndarray, zero_bins, num_bins,
                 max_conflict_rate: float = 0.0,
                 min_saving: float = 0.2):
    """Decide + build bundles for a dense binned matrix.  Returns
    ``(bundled, layout)`` or ``(binned, None)`` when bundling saves less
    than ``min_saving`` of the columns (reference gates EFB behind
    ``enable_bundle``; all-dense data naturally yields no groups)."""
    F = binned.shape[0]
    if F < 3:
        return binned, None
    masks = conflict_masks_from_dense(binned, zero_bins)
    layout = find_bundles(masks, num_bins,
                          max_conflict_rate=max_conflict_rate)
    if layout is None or layout.num_bundles > F * (1.0 - min_saving):
        return binned, None
    bundled = apply_bundles_dense(binned, zero_bins, layout)
    log_info(f"EFB: bundled {F} features into {layout.num_bundles} dense "
             f"columns (max {int(layout.bundle_nbins.max())} bins/bundle)")
    return bundled, layout
