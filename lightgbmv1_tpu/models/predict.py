"""TPU-native batched inference engine.

The reference serves bulk prediction with an OMP row-parallel per-row
walker (``src/application/predictor.hpp:29-160``).  The first device port
(`models/tree.ensemble_predict_raw`) kept the reference's *tree*-sequential
structure — a ``lax.scan`` whose body is a data-dependent while-loop walk,
i.e. O(T) serialized dispatches of unvectorizable gathers.  This module
rebuilds inference the same way training was made TPU-native: the
sequential branchy loop becomes fixed-trip-count dense array ops.

Three layers:

* **Depth-stepped all-trees walk** — an ``(N, T)`` int32 node-pointer
  array advanced ``max_depth`` times (computed host-side from the actual
  ensemble) with batched gathers over the stacked SoA node tables; leaves
  self-loop so the trip count is static.  ~``max_depth`` fused steps
  replace T sequential tree walks (`serving_leaf_raw` on raw features,
  `serving_leaf_binned` on prebinned codes; both carry raw-space
  categorical bitsets).

* **Prebinned serving codes** — the serving analog of the training
  ``BinMapper``: every threshold the ensemble actually splits on becomes a
  per-feature sorted boundary list, rows are binned ONCE on the host (in
  float64, so decisions are bit-exact against the reference's double
  compares — the raw device walk compares f32), and the walk compares
  uint8/uint16 codes against per-node bin indices.  The feature matrix
  shrinks 4x (8x vs f64) in HBM, NaN/missing-type routing is carried by
  two reserved codes, and categorical splits use raw-value bitsets.

* **Compile-amortizing predictor cache** — ``BatchPredictor`` pads batches
  to power-of-two row buckets and caches the jitted walk per (bucket,
  output kind); repeated serving calls never retrace (`Booster.predict`
  keys the predictor itself on (slice, tree count, model version), so a
  refit/update invalidates it).  Large batches stream through fixed-size
  chunks with the next chunk's H2D issued before the current chunk's walk
  is consumed (double buffering via JAX's async dispatch).

* **Serving megakernel** (``predict_method=fused``,
  ops/predict_pallas.serving_fused_pallas) — one Pallas launch per row
  tile walks every tree AND accumulates the per-class scores in VMEM;
  ``plan_predict_tiles`` tiles oversized ensembles into VMEM-sized tree
  groups, and with <= 15 serving codes per feature the codes ship 4-bit
  PACKED (two per byte), halving the H2D stream.  Node-exactness is
  pinned against the staged walk on the CPU interpret lane.  A planner
  refusal (``plan_predict_tiles``) serves the staged walk with its
  reason; a lowering or compile failure of a kernel the caller asked
  for (``pallas`` / ``fused``) raises with the compiler's message.

Row-sharded multi-chip serving reuses the training mesh helpers
(`parallel/cluster.make_mesh` + `parallel/trainer.shard_rows`): rows are
split over the mesh, the model is replicated, and no collective runs at
all — `tools` dryrun_multichip asserts node-exact parity vs single-device.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from ..io.binning import K_ZERO_THRESHOLD, MISSING_NAN, MISSING_ZERO
from ..obs import xla as obs_xla
from ..utils import faults
from ..utils.log import log_info, log_warning
from .tree import HostTree, host_tree_depth, validate_host_tree

# widest raw category representable as a serving bitset (same bar as the
# native predictor pack, native/__init__.py build_ensemble_pack)
_MAX_CAT_BITSET = 1 << 22

# process-wide log-once keys (the resolve_hist_plan engage/refuse idiom):
# a chunked streaming predict hits the same fallback on every chunk and
# a server rebuilds predictors per publish — the reason is logged once
_logged_once: set = set()


def _log_once(key: str, msg: str, warn: bool = False) -> None:
    if key in _logged_once:
        return
    _logged_once.add(key)
    (log_warning if warn else log_info)(msg)


def pack_serving_codes(codes: np.ndarray) -> np.ndarray:
    """(N, F) serving codes <= 15 -> (N, ceil(F/2)) packed bytes, two
    features per byte in the ops/hist_pallas.pack4bit nibble layout (lo
    nibble = even feature 2p, hi = 2p+1) — halves the serving H2D
    payload and the kernel's per-tile code footprint."""
    codes = np.asarray(codes, np.uint8)
    n, f = codes.shape
    if f % 2:
        codes = np.concatenate([codes, np.zeros((n, 1), np.uint8)], axis=1)
    return (codes[:, 0::2] | (codes[:, 1::2] << 4)).astype(np.uint8)


def unpack_serving_codes(packed, num_features: int):
    """``pack_serving_codes``'s inverse, numpy or jnp — the staged
    fallback unpacks ON DEVICE so packed H2D transport still pays off
    when the fused kernel refuses or fails to lower."""
    import jax
    import jax.numpy as jnp

    xp = jnp if isinstance(packed, jax.Array) else np
    lo = packed & 15
    hi = packed >> 4
    un = xp.stack([lo, hi], axis=2).reshape(packed.shape[0], -1)
    return un[:, :num_features].astype(xp.uint8)


def _transform_scores(s, transform):
    """The objective epilogue (None | 'sigmoid' | 'softmax') applied
    OUTSIDE the megakernel — the staged path's equivalent of the fused
    kernel's in-launch epilogue (same f32 math)."""
    if transform is None:
        return s
    import jax.numpy as jnp

    if transform == "sigmoid":
        return 1.0 / (1.0 + jnp.exp(-s))
    m = jnp.max(s, axis=1, keepdims=True)
    e = jnp.exp(s - m)
    return e / jnp.sum(e, axis=1, keepdims=True)


class ServingArrays(NamedTuple):
    """Stacked (T, ...) SoA node tables of the whole ensemble, device side.

    ``threshold`` carries the REAL split values (raw-feature walk);
    ``threshold_bin`` the serving-bin index of the same split (prebinned
    walk); ``cat_bitset`` is in RAW category space (unlike training-time
    ``TreeArrays`` whose bitsets live in training-bin space), so serving
    needs no host-side category dictionary."""

    num_leaves: Any      # (T,) int32
    split_feature: Any   # (T, L1) int32
    threshold: Any       # (T, L1) f32
    threshold_bin: Any   # (T, L1) int32 — serving-bin index
    zero_bin: Any        # (T, L1) int32 — serving bin of 0.0 for the
                         #   node's feature (NaN-as-zero / zero-missing)
    default_left: Any    # (T, L1) bool
    missing_type: Any    # (T, L1) int32
    left_child: Any      # (T, L1) int32
    right_child: Any     # (T, L1) int32
    leaf_value: Any      # (T, L) f32
    is_cat: Any          # (T, L1) bool
    cat_bitset: Any      # (T, L1, W) uint32 — RAW-value membership


@dataclass
class ServingBinner:
    """Per-feature serving-bin boundaries derived from the ensemble's own
    thresholds (the model IS the bin mapper at serving time: two raw
    values that no tree distinguishes need no distinct codes).

    Codes per feature f:
      numeric   — ``searchsorted(thresholds[f], v, side='left')`` (count
                  of thresholds < v), so ``code(v) <= bin(t_j) == j`` iff
                  ``v <= t_j`` — the float64 compare happens ONCE here
                  instead of at every node;
      reserved  — ``zero_code`` for |v| <= kZeroThreshold (missing-type
                  Zero routing), ``nan_code`` for NaN;
      categorical — ``trunc(v)`` clipped to the feature's bitset range
                  (negatives/NaN/overflow map to a code outside every
                  left set, reference CategoricalDecision semantics).
    """

    thresholds: List[np.ndarray]      # per feature, sorted float64
    zero_bin: np.ndarray              # (F,) int32 — code of 0.0
    cat_feat: np.ndarray              # (F,) bool
    cat_limit: np.ndarray             # (F,) int64 — clip target (not in
                                      #   any left set)
    zero_code: int
    nan_code: int
    dtype: Any                        # np.uint8 | np.uint16 | np.int32
    ok: bool = True
    why_not: str = ""

    @property
    def packed_ok(self) -> bool:
        """4-bit packed serving codes are exact when every code —
        including the two reserved NaN/zero codes — fits a nibble."""
        return bool(self.ok and self.nan_code <= 15)

    def prebin(self, X: np.ndarray) -> np.ndarray:
        """(N, F) float -> (N, F) serving codes.  Float64 exact."""
        X = np.asarray(X, np.float64)
        N, F = X.shape
        codes = np.zeros((N, F), self.dtype)
        for f in range(min(F, len(self.thresholds))):
            col = X[:, f]
            isnan = np.isnan(col)
            if self.cat_feat[f]:
                lim = int(self.cat_limit[f])
                vi = np.where(isnan, -1.0, np.trunc(np.where(isnan, 0.0,
                                                             col)))
                code = np.where((vi < 0) | (vi > lim), lim, vi)
                codes[:, f] = code.astype(self.dtype)
            else:
                b = np.searchsorted(self.thresholds[f], col, side="left")
                b = b.astype(np.int64)
                b[np.abs(col) <= K_ZERO_THRESHOLD] = self.zero_code
                b[isnan] = self.nan_code
                codes[:, f] = b.astype(self.dtype)
        return codes


def build_serving_binner(trees: List[HostTree],
                         num_features: int) -> ServingBinner:
    """Collect every split threshold / category set in the ensemble into
    per-feature serving bins.  ``ok=False`` (with a reason) when the
    prebinned path cannot be EXACT — callers fall back to the raw walk."""
    th: List[set] = [set() for _ in range(num_features)]
    cat_feat = np.zeros(num_features, bool)
    num_feat = np.zeros(num_features, bool)
    cat_max = np.zeros(num_features, np.int64)
    ok, why = True, ""
    for t in trees:
        for i in range(t.num_leaves - 1):
            f = int(t.split_feature[i])
            if f >= num_features:
                ok, why = False, f"split feature {f} out of range"
                continue
            if bool(t.is_cat[i]):
                cat_feat[f] = True
                s = t.cat_sets[i]
                if s is None:
                    ok, why = False, "raw categorical sets unavailable"
                    continue
                if len(s):
                    cat_max[f] = max(cat_max[f], int(np.max(s)))
            else:
                num_feat[f] = True
                th[f].add(float(t.threshold[i]))
    if (cat_feat & num_feat).any():
        ok, why = False, "feature used both numeric and categorical"
    if (cat_max >= _MAX_CAT_BITSET).any():
        ok, why = False, "category value too large for a serving bitset"
    thresholds = [np.array(sorted(s), np.float64) for s in th]
    # exactness guard: a threshold STRICTLY inside the +-kZeroThreshold
    # band would make the zero-code collapse lossy (|v|<=kzero rows all
    # take the bin of 0.0).  Thresholds at EXACTLY +-kzero are routine —
    # the training binner bounds the zero bin there (io/binning.py) — and
    # stay exact for every input except a raw value of exactly
    # -kZeroThreshold on such a feature (the same collapse the training
    # bin space itself makes); real models never split strictly inside.
    for f, a in enumerate(thresholds):
        if len(a) and (np.abs(a) < K_ZERO_THRESHOLD).any():
            ok, why = False, "threshold within the zero-missing band"
    cat_limit = cat_max + 1
    n_codes = max([len(a) + 1 for a in thresholds] or [1])
    if cat_feat.any():
        n_codes = max(n_codes, int(cat_limit[cat_feat].max()) + 1)
    zero_code, nan_code = n_codes, n_codes + 1
    if nan_code < 256:
        dtype: Any = np.uint8
    elif nan_code < 65536:
        dtype = np.uint16
    else:
        dtype = np.int32
    zero_bin = np.array(
        [np.searchsorted(a, 0.0, side="left") for a in thresholds]
        + [0] * (num_features - len(thresholds)), np.int32)
    return ServingBinner(thresholds=thresholds, zero_bin=zero_bin,
                         cat_feat=cat_feat, cat_limit=cat_limit,
                         zero_code=zero_code, nan_code=nan_code,
                         dtype=dtype, ok=ok, why_not=why)


def build_serving_arrays(trees: List[HostTree], binner: ServingBinner,
                         num_features: int) -> Tuple[ServingArrays, int]:
    """HostTrees (real thresholds filled) -> stacked device tables +
    the ensemble's max depth (the static walk trip count)."""
    import jax.numpy as jnp

    for i, t in enumerate(trees):
        validate_host_tree(t, i)
    depth = max([host_tree_depth(t) for t in trees] or [0])
    L = max([max(t.num_leaves, 1) for t in trees] or [1])
    L1 = max(L - 1, 1)
    W = 1
    if binner.ok and binner.cat_feat.any():
        W = int(binner.cat_limit[binner.cat_feat].max()) // 32 + 1
    T = len(trees)

    def zeros(shape, dt):
        return np.zeros(shape, dt)

    num_leaves = zeros(T, np.int32)
    feat = zeros((T, L1), np.int32)
    thr = zeros((T, L1), np.float32)
    tbin = zeros((T, L1), np.int32)
    zbin = zeros((T, L1), np.int32)
    dl = zeros((T, L1), bool)
    mt = zeros((T, L1), np.int32)
    lc = np.full((T, L1), -1, np.int32)
    rc = np.full((T, L1), -2, np.int32)
    lv = zeros((T, L), np.float32)
    is_cat = zeros((T, L1), bool)
    bitset = zeros((T, L1, W), np.uint32)
    for ti, t in enumerate(trees):
        n = t.num_leaves
        nn = max(n - 1, 0)
        num_leaves[ti] = n
        if nn:
            feat[ti, :nn] = t.split_feature
            thr[ti, :nn] = t.threshold
            dl[ti, :nn] = t.default_left
            mt[ti, :nn] = t.missing_type
            lc[ti, :nn] = t.left_child
            rc[ti, :nn] = t.right_child
            is_cat[ti, :nn] = t.is_cat
            for i in range(nn):
                f = int(t.split_feature[i])
                if binner.ok and f < num_features:
                    zbin[ti, i] = binner.zero_bin[f]
                    if bool(t.is_cat[i]):
                        s = t.cat_sets[i]
                        if s is not None and len(s):
                            s = np.asarray(s, np.int64)
                            np.bitwise_or.at(
                                bitset[ti, i], s // 32,
                                np.uint32(1) << (s % 32).astype(np.uint32))
                    else:
                        j = int(np.searchsorted(binner.thresholds[f],
                                                float(t.threshold[i]),
                                                side="left"))
                        tbin[ti, i] = j
        lv[ti, :n] = t.leaf_value[:n]
    arrays = ServingArrays(
        num_leaves=jnp.asarray(num_leaves),
        split_feature=jnp.asarray(feat),
        threshold=jnp.asarray(thr),
        threshold_bin=jnp.asarray(tbin),
        zero_bin=jnp.asarray(zbin),
        default_left=jnp.asarray(dl),
        missing_type=jnp.asarray(mt),
        left_child=jnp.asarray(lc),
        right_child=jnp.asarray(rc),
        leaf_value=jnp.asarray(lv),
        is_cat=jnp.asarray(is_cat),
        cat_bitset=jnp.asarray(bitset),
    )
    return arrays, depth


# ---------------------------------------------------------------------------
# Depth-stepped serving walks (pure XLA; ops/predict_pallas.py is the
# VMEM-pinned variant, this is the bit-parity pin for it)
# ---------------------------------------------------------------------------


def _cat_go_left(sm: ServingArrays, ti, nd, code, go_left, has_cat: bool):
    import jax.numpy as jnp

    if not has_cat:
        return go_left
    W = sm.cat_bitset.shape[-1]
    bi = jnp.clip(code, 0, W * 32 - 1)
    word = sm.cat_bitset[ti, nd, bi >> 5]
    in_set = ((word >> (bi.astype(jnp.uint32) & 31)) & 1) == 1
    in_set = in_set & (code >= 0) & (code < W * 32)
    return jnp.where(sm.is_cat[ti, nd], in_set, go_left)


def serving_leaf_raw(sm: ServingArrays, X, n_steps: int,
                     has_cat: bool = False):
    """Depth-stepped walk on RAW float features (f32 compares).  With
    ``has_cat`` the categorical decision is ``trunc(v)`` membership in the
    node's raw bitset (reference CategoricalDecision, tree.h:302-320)."""
    import jax.numpy as jnp
    from jax import lax

    N = X.shape[0]
    T = sm.left_child.shape[0]
    ti = jnp.arange(T, dtype=jnp.int32)[None, :]

    def body(_, node):
        nd = jnp.maximum(node, 0)
        f = sm.split_feature[ti, nd]
        v = jnp.take_along_axis(X, f, axis=1)
        t = sm.threshold[ti, nd]
        dl = sm.default_left[ti, nd]
        mtype = sm.missing_type[ti, nd]
        is_nan = jnp.isnan(v)
        v0 = jnp.where(is_nan, 0.0, v)
        is_missing = jnp.where(
            mtype == MISSING_NAN, is_nan,
            jnp.where(mtype == MISSING_ZERO,
                      is_nan | (jnp.abs(v0) <= K_ZERO_THRESHOLD), False))
        go_left = jnp.where(is_missing, dl, v0 <= t)
        if has_cat:
            W = sm.cat_bitset.shape[-1]
            vc = jnp.clip(v0, -1.0, float(W * 32))
            vi = jnp.where(is_nan, -1, vc.astype(jnp.int32))  # C trunc
            go_left = _cat_go_left(sm, ti, nd, vi, go_left, True)
        nxt = jnp.where(go_left, sm.left_child[ti, nd],
                        sm.right_child[ti, nd])
        return jnp.where(node >= 0, nxt, node)

    node0 = jnp.where(sm.num_leaves[None, :] > 1,
                      jnp.zeros((N, T), jnp.int32),
                      jnp.full((N, T), -1, jnp.int32))
    node = lax.fori_loop(0, max(int(n_steps), 1), body, node0)
    return -node - 1


def serving_leaf_binned(sm: ServingArrays, codes, n_steps: int,
                        zero_code: int, nan_code: int,
                        has_cat: bool = False):
    """Depth-stepped walk on prebinned serving codes: every decision is an
    integer compare against the node's serving-bin threshold; NaN /
    zero-missing routing rides the two reserved codes (``b0`` restores the
    reference's NaN-as-0.0 compare via the precomputed zero bin)."""
    import jax.numpy as jnp
    from jax import lax

    N = codes.shape[0]
    T = sm.left_child.shape[0]
    ti = jnp.arange(T, dtype=jnp.int32)[None, :]

    def body(_, node):
        nd = jnp.maximum(node, 0)
        f = sm.split_feature[ti, nd]
        b = jnp.take_along_axis(codes, f, axis=1).astype(jnp.int32)
        is_nan = b == nan_code
        is_zero = b == zero_code
        b0 = jnp.where(is_nan | is_zero, sm.zero_bin[ti, nd], b)
        dl = sm.default_left[ti, nd]
        mtype = sm.missing_type[ti, nd]
        is_missing = jnp.where(
            mtype == MISSING_NAN, is_nan,
            jnp.where(mtype == MISSING_ZERO, is_nan | is_zero, False))
        go_left = jnp.where(is_missing, dl, b0 <= sm.threshold_bin[ti, nd])
        go_left = _cat_go_left(sm, ti, nd, b, go_left, has_cat)
        nxt = jnp.where(go_left, sm.left_child[ti, nd],
                        sm.right_child[ti, nd])
        return jnp.where(node >= 0, nxt, node)

    node0 = jnp.where(sm.num_leaves[None, :] > 1,
                      jnp.zeros((N, T), jnp.int32),
                      jnp.full((N, T), -1, jnp.int32))
    node = lax.fori_loop(0, max(int(n_steps), 1), body, node0)
    return -node - 1


# ---------------------------------------------------------------------------
# The predictor object: compile cache, buckets, chunk streaming, sharding
# ---------------------------------------------------------------------------


_obs_cache = {}


def _obs_cache_counter(event: str,
                       metric_name: str = "predict_cache_events_total"):
    """Process-wide predictor-cache counters in the unified registry
    (``predict_cache_events_total{event=hits|misses|evictions}``, and
    ``predict_shared_cache_events_total`` for the cross-instance
    executable cache) — the per-instance ``cache_info()`` integers stay
    the test surface; these aggregate across predictors for scraping."""
    c = _obs_cache.get((metric_name, event))
    if c is None:
        from ..obs.metrics import default_registry

        metric = default_registry().counter(
            metric_name,
            "Compiled-walk cache hits/misses/evictions",
            label_names=("event",))
        c = _obs_cache[(metric_name, event)] = metric.labels(event=event)
    return c


# ---------------------------------------------------------------------------
# Cross-instance shared executable cache (multi-tenant serving, ISSUE 20)
# ---------------------------------------------------------------------------
# The walk closures are pure in everything per-model — node tables and
# encoded rows arrive as ARGUMENTS — so two predictors whose traced
# program is byte-identical (same tree-shape signature: stacked table
# geometry, binner code geometry, walk statics) can share ONE
# InstrumentedJit and therefore ONE compiled executable per bucket.
# That is the multi-tenant compile-bucket sharing contract: the cache
# key is ``(shape_signature, bucket, kind)`` — TENANT IDENTITY IS NOT
# IN THE KEY.  Opt-in per predictor (``shared_cache=True``; the tenant
# platform enables it) so single-model deployments keep today's
# per-instance behavior bit-identically.  Entries hold only the jitted
# closure + small statics (never the model arrays), LRU-bounded.

_SHARED_CACHE_CAPACITY = 256
_shared_lock = threading.RLock()
_shared_cache: "OrderedDict[tuple, Any]" = OrderedDict()
_shared_stats = {"hits": 0, "misses": 0, "evictions": 0}


def shared_cache_stats() -> Dict[str, int]:
    """Point read of the cross-instance executable cache — bench.py's
    ``tenant_compile_share_frac`` is ``hits / (hits + misses)``."""
    with _shared_lock:
        out = dict(_shared_stats)
        out["entries"] = len(_shared_cache)
        out["capacity"] = _SHARED_CACHE_CAPACITY
    return out


def reset_shared_cache() -> None:
    """Drop every shared executable and zero the counters (tests and
    bench probes only — live predictors keep their adopted entries)."""
    with _shared_lock:
        _shared_cache.clear()
        for k in _shared_stats:
            _shared_stats[k] = 0


class _TraceCell:
    """Trace-time counter the walk closures bump instead of closing over
    the predictor — a shared executable must never keep its builder's
    model arrays alive through the cache."""

    __slots__ = ("n",)

    def __init__(self):
        self.n = 0

    def bump(self):
        self.n += 1


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


class BatchPredictor:
    """Device serving engine for one frozen ensemble slice.

    Owns the stacked node tables, the serving binner, and a jit cache
    keyed on (row bucket, output kind) so repeated `predict` calls at any
    batch size inside a bucket reuse one compiled executable —
    ``trace_count`` counts actual retraces and is asserted zero-growth by
    the cache tests.  The cache is LRU-bounded at ``cache_entries``
    executables (``cache_info()`` exposes hits/misses/evictions) so a
    long-running server seeing many batch shapes cannot accumulate
    compiled programs without limit.  `Booster.predict` holds one
    BatchPredictor per (start_iteration, tree count, model_version) — any
    ensemble mutation bumps ``model_version`` and drops the predictor
    wholesale."""

    def __init__(self, trees: List[HostTree], K: int, num_features: int, *,
                 method: str = "depthwise", prebin: str = "auto",
                 code_layout: str = "auto", num_shards: int = 0,
                 bucket_min: int = 256, chunk_rows: int = 1 << 17,
                 interpret: Optional[bool] = None, cache_entries: int = 64,
                 shared_cache: bool = False):
        import jax

        if not trees:
            raise ValueError("BatchPredictor needs at least one tree")
        if method not in ("depthwise", "pallas", "scan", "fused"):
            raise ValueError(f"predict_method={method!r}: expected "
                             "depthwise | pallas | scan | fused")
        if code_layout not in ("auto", "u8", "packed4"):
            raise ValueError(f"predict_code_layout={code_layout!r}: "
                             "expected auto | u8 | packed4")
        self.K = max(int(K), 1)
        self.T = len(trees)
        self.F = int(num_features)
        self.method = method
        self.num_shards = int(num_shards)
        self.bucket_min = max(int(bucket_min), 8)
        self.chunk_rows = max(int(chunk_rows), self.bucket_min)
        self.binner = build_serving_binner(trees, num_features)
        self.arrays, self.depth = build_serving_arrays(
            trees, self.binner, num_features)
        self.has_cat = bool(np.asarray(self.arrays.is_cat).any())
        if self.has_cat and not self.binner.ok:
            raise ValueError(
                "device serving of this categorical model is not possible: "
                + self.binner.why_not)
        if method == "scan" and self.has_cat:
            raise ValueError("predict_method=scan does not support "
                             "categorical splits")
        if method == "scan" and self.K != 1:
            raise ValueError("predict_method=scan supports K=1 ensembles")
        if prebin not in ("auto", "on", "off"):
            raise ValueError(f"predict_prebin={prebin!r}")
        self.prebin = (self.binner.ok and method != "scan") \
            if prebin == "auto" else (prebin == "on")
        if self.prebin and not self.binner.ok:
            log_warning("predict_prebin=on but the prebinned path cannot "
                        f"be exact ({self.binner.why_not}); using the raw "
                        "walk")
            self.prebin = False
        # -- 4-bit packed serving codes (the resolve_hist_plan engage/
        # refuse contract): "auto" engages exactly when eligible AND the
        # fused kernel consumes nibbles directly; an explicit "packed4"
        # engages on any prebinned walk (the staged path unpacks ON
        # DEVICE, keeping the halved H2D) or refuses with one reason
        self.code_layout = code_layout
        packed_able = bool(self.prebin and self.binner.packed_ok
                           and method != "scan")
        if code_layout == "packed4":
            if packed_able:
                self.packed = True
                _log_once("packed4:on",
                          "predict_code_layout=packed4: serving codes "
                          "packed two per byte")
            else:
                reason = (f"{self.binner.nan_code + 1} serving codes "
                          "exceed the 16 nibble values"
                          if self.prebin and self.binner.ok
                          else "prebinned serving codes not in play")
                _log_once(f"packed4:refuse:{reason}",
                          f"predict_code_layout=packed4: {reason}; "
                          "storing unpacked codes", warn=True)
                self.packed = False
        else:
            self.packed = bool(code_layout == "auto" and method == "fused"
                               and packed_able)
        # float64 leaf table for exact score reconstruction (the native
        # predictor / HostTree accumulate f64 in tree order)
        self._leaf_value64 = np.zeros((self.T, self.arrays.leaf_value.shape[1]),
                                      np.float64)
        for i, t in enumerate(trees):
            self._leaf_value64[i, : t.num_leaves] = t.leaf_value[: t.num_leaves]
        if interpret is None:
            interpret = jax.default_backend() == "cpu"
        self.interpret = bool(interpret)
        self._mesh = None
        if self.num_shards > 1:
            from ..parallel.cluster import make_mesh

            self._mesh = make_mesh(self.num_shards, "rows")
        # LRU-bounded jit cache over (bucket, kind) keys: a long-running
        # server seeing many batch shapes would otherwise accumulate
        # compiled executables without limit (each bucket x output kind is
        # its own XLA program).  Eviction drops the least-recently-used
        # executable; re-touching that bucket retraces (counted).
        self._cache: "OrderedDict[Tuple[int, str], Any]" = OrderedDict()
        self.cache_capacity = max(int(cache_entries), 2)
        # cross-instance executable sharing (multi-tenant serving): the
        # per-instance LRU stays the front line; on a miss the shared
        # cache is consulted under (shape signature, bucket, kind).
        # Row-sharded predictors are excluded (their walks close over a
        # per-instance mesh binding).
        self.shared_cache = bool(shared_cache) and self.num_shards <= 1
        self._shape_sig: Optional[tuple] = None
        self._tc = _TraceCell()
        self.call_count = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.cache_evictions = 0
        self._scan_stacked = None
        # -- serving-megakernel plan (static, recorded in BENCH): tiles
        # trees into VMEM-sized groups; refusal = staged walk + one
        # honest reason line
        self.fused_plan = None
        self._fused_tables = None
        if method == "fused":
            from ..ops.predict_pallas import plan_predict_tiles

            self.fused_plan = plan_predict_tiles(
                T=self.T, L1=self.arrays.split_feature.shape[1],
                L=self.arrays.leaf_value.shape[1], F=self.F, K=self.K,
                depth=self.depth, has_cat=self.has_cat,
                prebin=self.prebin, packed=self.packed)
            if self.fused_plan["eligible"]:
                from .tree import pad_tree_axis

                self._fused_tables = pad_tree_axis(
                    self.arrays, self.fused_plan["t_pad"])
            else:
                _log_once("fused:refuse:" + self.fused_plan["reason"],
                          f"predict_method=fused: "
                          f"{self.fused_plan['reason']}; serving the "
                          "staged depth-stepped walk", warn=True)

    # -- cache ----------------------------------------------------------
    @property
    def trace_count(self) -> int:
        """Traces this instance's walk builds triggered (the zero-retrace
        contract's per-instance surface).  A predictor that ADOPTS a
        shared executable never traces — its count stays 0, which is
        exactly the multi-tenant compile-sharing assertion."""
        return self._tc.n

    def bucket_for(self, n: int) -> int:
        b = _next_pow2(max(n, self.bucket_min))
        b = min(b, _next_pow2(self.chunk_rows))
        if self.num_shards > 1 and b % self.num_shards:
            b = self.num_shards * (-(-b // self.num_shards))
        return b

    def shape_signature(self) -> tuple:
        """Every static the traced walk program depends on — two
        predictors with equal signatures lower to byte-identical XLA
        programs per (bucket, kind), which is what makes the shared
        executable cache sound.  Covers the walk statics (method /
        prebin / packed / depth / categorical handling), the binner code
        geometry (zero/nan codes are baked into the trace as constants),
        the stacked table geometry (shape + dtype of every SoA field —
        they are jit ARGUMENTS, so shape/dtype is what the trace keys
        on), and the megakernel tiling plan."""
        if self._shape_sig is None:
            geom = tuple((tuple(v.shape), str(v.dtype))
                         for v in self.arrays)
            fused = None
            if self.fused_plan is not None and self.fused_plan["eligible"]:
                fused = (int(self.fused_plan["tree_tile"]),
                         int(self.fused_plan["t_pad"]))
            self._shape_sig = (
                self.method, self.prebin, self.packed, self.interpret,
                self.depth, self.has_cat, self.K, self.T, self.F,
                self.binner.zero_code, self.binner.nan_code,
                str(np.dtype(self.binner.dtype)), geom, fused)
        return self._shape_sig

    def _shared_jit(self, bucket: int, kind: str, build):
        """Fetch-or-build one instrumented jitted walk through the
        cross-instance shared cache (``shared_cache=True`` only) —
        keyed ``(shape_signature, bucket, kind)``, never on model or
        tenant identity.  ``build()`` must return a closure that is
        pure in everything per-model (tables arrive as arguments)."""
        if not self.shared_cache:
            return build()
        skey = (self.shape_signature(), bucket, kind)
        with _shared_lock:
            ent = _shared_cache.get(skey)
            if ent is not None:
                _shared_cache.move_to_end(skey)
                _shared_stats["hits"] += 1
        if ent is not None:
            _obs_cache_counter(
                "hits", "predict_shared_cache_events_total").inc()
            return ent
        _obs_cache_counter(
            "misses", "predict_shared_cache_events_total").inc()
        jfn = build()
        with _shared_lock:
            _shared_stats["misses"] += 1
            _shared_cache[skey] = jfn
            _shared_cache.move_to_end(skey)
            while len(_shared_cache) > _SHARED_CACHE_CAPACITY:
                _shared_cache.popitem(last=False)
                _shared_stats["evictions"] += 1
                _obs_cache_counter(
                    "evictions",
                    "predict_shared_cache_events_total").inc()
        return jfn

    def _cache_get(self, key):
        fn = self._cache.get(key)
        if fn is not None:
            self._cache.move_to_end(key)
            self.cache_hits += 1
            _obs_cache_counter("hits").inc()
        else:
            self.cache_misses += 1
            _obs_cache_counter("misses").inc()
        return fn

    def _cache_put(self, key, fn):
        self._cache[key] = fn
        self._cache.move_to_end(key)
        while len(self._cache) > self.cache_capacity:
            self._cache.popitem(last=False)
            self.cache_evictions += 1
            _obs_cache_counter("evictions").inc()
        return fn

    def cache_stats(self) -> Dict[str, int]:
        return {"traces": self.trace_count, "calls": self.call_count,
                "entries": len(self._cache)}

    def cache_info(self) -> Dict[str, int]:
        """functools.lru_cache-style accessor for the compiled-walk cache
        (serve metrics and the cache tests read this)."""
        return {"entries": len(self._cache),
                "capacity": self.cache_capacity,
                "hits": self.cache_hits, "misses": self.cache_misses,
                "evictions": self.cache_evictions,
                "traces": self.trace_count, "calls": self.call_count}

    def _leaf_fn(self, bucket: int):
        """Compiled (bucket, F) -> (bucket, T) leaf-index walk."""
        key = (bucket, "leaf")
        fn = self._cache_get(key)
        if fn is not None:
            return fn
        import jax

        method, prebin = self.method, self.prebin
        depth, has_cat = self.depth, self.has_cat
        zc, nc = self.binner.zero_code, self.binner.nan_code
        packed, F = self.packed, self.F
        interpret, tc = self.interpret, self._tc

        def build():
            def walk(arrays, xb):
                tc.bump()                # trace-time side effect only
                if packed:
                    xb = unpack_serving_codes(xb, F)
                if method == "pallas" and prebin and not has_cat:
                    from ..ops.predict_pallas import serving_leaf_pallas

                    return serving_leaf_pallas(
                        arrays, xb, n_steps=depth, zero_code=zc,
                        nan_code=nc, interpret=interpret)
                if prebin:
                    return serving_leaf_binned(arrays, xb, depth, zc, nc,
                                               has_cat)
                return serving_leaf_raw(arrays, xb, depth, has_cat)

            fn = walk
            if self._mesh is not None:
                from ..parallel.trainer import shard_rows

                fn = shard_rows(walk, self._mesh, "rows", n_replicated=1)
            # labeled compile telemetry (obs/xla.py): every (bucket,
            # kind) compile is an observed event, and the per-label
            # retrace counters are the serving zero-retrace contract's
            # instrument
            return obs_xla.instrument_jit(fn, "predict.leaf")

        return self._cache_put(key, self._shared_jit(bucket, "leaf", build))

    # -- serving megakernel (predict_method=fused) -----------------------
    def _fused_engaged(self) -> bool:
        return bool(self.method == "fused" and self.fused_plan is not None
                    and self.fused_plan["eligible"])

    def _fused_walk(self, mode: str = "scores", transform=None):
        """The raw (unjitted) megakernel call for one bucket — exposed
        separately so bench.py can ``jax.jit(...).lower()`` it for the
        single-read ``cost_analysis`` contract."""
        from ..ops.predict_pallas import serving_fused_pallas

        depth, K, T = self.depth, self.K, self.T
        zc, nc = self.binner.zero_code, self.binner.nan_code
        packed, interpret = self.packed, self.interpret
        tree_tile = self.fused_plan["tree_tile"]
        tc = self._tc

        def walk(tables, xb):
            tc.bump()
            out = serving_fused_pallas(
                tables, xb, n_steps=depth, zero_code=zc, nan_code=nc,
                K=K, tree_tile=tree_tile, mode=mode, packed=packed,
                transform=transform, interpret=interpret)
            if mode == "leaf":
                out = out[:, :T]      # slice the tree-tile pad away
            return out

        return walk

    def _fused_fn(self, bucket: int, mode: str = "scores", transform=None):
        """Compiled megakernel per (bucket, output kind): leaves for the
        node-exact / f64 lane, (N, K) scores — optionally with the
        in-launch sigmoid/softmax epilogue — for the fast lane."""
        kind = ("fused_leaf" if mode == "leaf"
                else f"fused:{transform or 'raw'}")
        key = (bucket, kind)
        cached = self._cache_get(key)
        if cached is not None:
            return cached

        def build():
            fn = self._fused_walk(mode=mode, transform=transform)
            if self._mesh is not None:
                from ..parallel.trainer import shard_rows

                fn = shard_rows(fn, self._mesh, "rows", n_replicated=1)
            return obs_xla.instrument_jit(fn, "predict.fused")

        return self._cache_put(key, self._shared_jit(bucket, kind, build))

    def _scan_fn(self, bucket: int):
        """The parity-pin scan walk (models/tree.ensemble_predict_raw) as
        a predict_method — per-tree while-loop walks, summed f32."""
        key = (bucket, "scan")
        cached = self._cache_get(key)
        if cached is not None:
            return cached
        import jax

        from .tree import ensemble_predict_raw

        tc = self._tc

        def build():
            def fwd(stacked, xb):
                tc.bump()
                return ensemble_predict_raw(stacked, xb)

            fn = fwd
            if self._mesh is not None:
                from ..parallel.trainer import shard_rows

                fn = shard_rows(fwd, self._mesh, "rows", n_replicated=1)
            return obs_xla.instrument_jit(fn, "predict.scan")

        return self._cache_put(key, self._shared_jit(
            bucket, "scan", build))

    # -- host <-> device ------------------------------------------------
    def encode(self, X: np.ndarray) -> np.ndarray:
        """Host-side input encoding for the device walk: prebinned codes
        (uint8/uint16, or 4-bit packed bytes when the nibble layout is
        engaged) or f32 raw features."""
        if self.prebin:
            codes = self.binner.prebin(X)
            if self.packed:
                return pack_serving_codes(codes)
            return codes
        return np.asarray(X, np.float32)

    def _pad(self, enc: np.ndarray, bucket: int) -> np.ndarray:
        n = enc.shape[0]
        if n == bucket:
            return enc
        pad = np.zeros((bucket - n, enc.shape[1]), enc.dtype)
        return np.concatenate([enc, pad], axis=0)

    # -- public API ------------------------------------------------------
    def predict_leaf(self, X: np.ndarray) -> np.ndarray:
        """(N, T) int32 leaf index per (row, tree) — node-exact vs the
        host walks (prebinned path; the raw walk compares f32)."""
        import jax

        X = np.asarray(X)
        n = X.shape[0]
        outs = []
        for lo in range(0, n, self.chunk_rows):
            chunk = X[lo: lo + self.chunk_rows]
            bucket = self.bucket_for(chunk.shape[0])
            enc = self._pad(self.encode(chunk), bucket)
            # chaos seam: a transient host->device transfer failure lands
            # here, before the walk dispatch (utils/faults.py) — the
            # serving retry loop must absorb it
            faults.fire("h2d", site="predict_leaf")
            self.call_count += 1
            if self._fused_engaged():
                leaf = self._fused_fn(bucket, mode="leaf")(
                    self._fused_tables, jax.numpy.asarray(enc))
            else:
                leaf = self._leaf_fn(bucket)(self.arrays,
                                             jax.numpy.asarray(enc))
            outs.append(jax.device_get(leaf)[: chunk.shape[0]])
        return np.concatenate(outs, axis=0)

    def predict_raw(self, X: np.ndarray, f64_exact: bool = False,
                    chunk_rows: Optional[int] = None) -> np.ndarray:
        """(N, K) raw scores.

        Default: leaf values summed on-device in f32 (fast serving path).
        ``f64_exact``: the device walk produces leaf indices and the
        scores are reconstructed host-side in float64 IN TREE ORDER —
        bit-identical to the native C++ predictor / HostTree path.
        Chunks stream with the next chunk's H2D enqueued before the
        current chunk's result is consumed (double-buffered via JAX async
        dispatch)."""
        import jax
        import jax.numpy as jnp

        X = np.asarray(X)
        n = X.shape[0]
        chunk_rows = chunk_rows or self.chunk_rows
        if f64_exact:
            leaf = self.predict_leaf(X)
            out = np.zeros((n, self.K), np.float64)
            for t in range(self.T):   # tree order = the reference's f64
                out[:, t % self.K] += self._leaf_value64[t][leaf[:, t]]
            return out

        if self.method == "scan":
            return self._predict_raw_scan(X, chunk_rows)

        chunks = [X[lo: lo + chunk_rows] for lo in range(0, n, chunk_rows)]
        pending = []
        nxt_dev = None
        for i, chunk in enumerate(chunks):
            faults.fire("h2d", site="predict_raw")
            bucket = self.bucket_for(chunk.shape[0])
            if nxt_dev is not None and nxt_dev[1] == bucket:
                enc_dev = nxt_dev[0]
            else:
                enc_dev = jnp.asarray(self._pad(self.encode(chunk), bucket))
            # enqueue the NEXT chunk's H2D before consuming this walk
            if i + 1 < len(chunks):
                nb = self.bucket_for(chunks[i + 1].shape[0])
                nxt_dev = (jax.device_put(
                    self._pad(self.encode(chunks[i + 1]), nb)), nb)
            self.call_count += 1
            if self._fused_engaged():
                # one launch: walk + accumulate, no (N, T) intermediate
                scores = self._fused_fn(bucket)(self._fused_tables,
                                                enc_dev)
            else:
                leaf = self._leaf_fn(bucket)(self.arrays, enc_dev)
                scores = self._scores_fn(bucket)(self.arrays.leaf_value,
                                                 leaf)
            pending.append((scores, chunk.shape[0]))
        return np.concatenate(
            [np.asarray(jax.device_get(s))[:m] for s, m in pending], axis=0)

    def predict_scores(self, X: np.ndarray, transform=None,
                       chunk_rows: Optional[int] = None) -> np.ndarray:
        """(N, K) scores with the optional objective epilogue
        (``transform``: None | 'sigmoid' | 'softmax').  When the
        megakernel is engaged the transform runs IN-KERNEL on the VMEM
        accumulator — the whole request is one launch; otherwise it is
        applied after the staged walk's score sum (same f32 math, one
        extra elementwise pass)."""
        import jax
        import jax.numpy as jnp

        if transform not in (None, "sigmoid", "softmax"):
            raise ValueError(f"transform={transform!r}: expected None | "
                             "sigmoid | softmax")
        X = np.asarray(X)
        if not self._fused_engaged():
            raw = jnp.asarray(self.predict_raw(X, chunk_rows=chunk_rows))
            return np.asarray(jax.device_get(
                _transform_scores(raw, transform)))
        n = X.shape[0]
        chunk_rows = chunk_rows or self.chunk_rows
        pending = []
        for lo in range(0, n, chunk_rows):
            chunk = X[lo: lo + chunk_rows]
            bucket = self.bucket_for(chunk.shape[0])
            enc_dev = jnp.asarray(self._pad(self.encode(chunk), bucket))
            self.call_count += 1
            s = self._fused_fn(bucket, transform=transform)(
                self._fused_tables, enc_dev)
            pending.append((s, chunk.shape[0]))
        return np.concatenate(
            [np.asarray(jax.device_get(s))[:m] for s, m in pending], axis=0)

    def _scores_fn(self, bucket: int):
        key = (bucket, "scores")
        cached = self._cache_get(key)
        if cached is not None:
            return cached
        import jax

        from .tree import leaves_to_scores

        K, tc = self.K, self._tc

        def build():
            def fn(leaf_value, leaf):
                tc.bump()
                return leaves_to_scores(leaf_value, leaf, K)

            return obs_xla.instrument_jit(fn, "predict.scores")

        return self._cache_put(key, self._shared_jit(
            bucket, "scores", build))

    def _predict_raw_scan(self, X, chunk_rows):
        import jax
        import jax.numpy as jnp

        if self.K != 1:
            raise ValueError("predict_method=scan supports K=1 ensembles")
        if self._scan_stacked is None:
            # a training-style stacked TreeArrays view over the serving
            # tables (the scan walk reads the same SoA fields)
            self._scan_stacked = self._as_tree_arrays()
        n = X.shape[0]
        outs = []
        for lo in range(0, n, chunk_rows):
            chunk = np.asarray(X[lo: lo + chunk_rows], np.float32)
            bucket = self.bucket_for(chunk.shape[0])
            xb = jnp.asarray(self._pad(chunk, bucket))
            self.call_count += 1
            out = self._scan_fn(bucket)(self._scan_stacked, xb)
            outs.append(np.asarray(jax.device_get(out))[: chunk.shape[0]])
        return np.concatenate(outs, axis=0)[:, None]

    def _as_tree_arrays(self):
        """Serving tables -> the TreeArrays layout the scan pin expects."""
        import jax.numpy as jnp

        from .tree import TreeArrays

        a = self.arrays
        T, L1 = a.split_feature.shape
        L = a.leaf_value.shape[1]
        zf = jnp.zeros((T, L1), jnp.float32)
        zl = jnp.zeros((T, L), jnp.float32)
        return TreeArrays(
            num_leaves=a.num_leaves, split_feature=a.split_feature,
            threshold_bin=a.threshold_bin, threshold=a.threshold,
            default_left=a.default_left, missing_type=a.missing_type,
            left_child=a.left_child, right_child=a.right_child,
            split_gain=zf, internal_value=zf, internal_weight=zf,
            internal_count=jnp.zeros((T, L1), jnp.int32),
            leaf_value=a.leaf_value, leaf_weight=zl,
            leaf_count=jnp.zeros((T, L), jnp.int32),
            leaf_parent=jnp.full((T, L), -1, jnp.int32),
            is_cat=a.is_cat, cat_bitset=a.cat_bitset,
        )

    def h2d_bytes(self, n_rows: int) -> int:
        """Host->device payload of one batch (the prebinned path's 4-8x
        shrink is the point; packed nibble codes halve it again —
        recorded by bench.py / dryrun_multichip)."""
        if self.prebin and self.packed:
            return int(n_rows) * (-(-self.F // 2))
        itemsize = (np.dtype(self.binner.dtype).itemsize if self.prebin
                    else 4)
        return int(n_rows) * self.F * itemsize
