"""Row-block streaming tree grower — out-of-core training (ROADMAP item 2).

Host-driven replica of the sequential masked leaf-wise grower
(models/grower.py ``make_leafwise_grower(partition=False)`` — the
reference's exact best-first split order) whose O(N) passes are streamed
over row blocks instead of touching a resident (F, N) device matrix:

* per-split **histogram passes** fold each block into a running device
  accumulator (ops/histogram.hist_one_leaf_accum) — scatter-add update
  order makes the streamed fold bit-identical to the resident full-matrix
  pass, so split decisions (and therefore the saved model text) match the
  in-memory trainer BYTE FOR BYTE at fixed block order
  (tests/test_stream_train.py pins this across binary/multiclass/DART);
* per-split **leaf routing** updates each block's host-side leaf-id shard
  with the same ``apply_decision`` ops the resident grower runs;
* blocks stream host→device **double-buffered**: the next block's
  ``device_put`` is issued before the current block's histogram pass is
  consumed (the PR-4 predict-path overlap pattern, applied to training);
* everything leaf-sized (histogram pool, split tables, tree arrays) stays
  on device — tiny, O(L·F·B), row-count-independent.

Peak streaming-owned device bytes are O(block_rows · F) + O(L·F·B) and
are accounted explicitly in a :class:`~lightgbmv1_tpu.data.DeviceLedger`
(asserted by the memory-guard test and the BENCH ``stream_ok`` field).

Scope: the streaming schedule is the sequential best-first order (the
parity configuration — ``tree_growth=leafwise_masked`` /
``leafwise_wave_size=1``); forced splits, CEGB and EFB bundles are
resident-trainer-only and are rejected loudly at construction
(models/gbdt_stream.py).  4-bit packed caches (block-cache v3
``bin_layout=packed4``, ISSUE 18) stream their PACKED shards: the H2D
transfer moves ``(ceil(F/2), rows)`` bytes and each per-block jit
unpacks nibbles on device first (``unpack4bit`` — exact, so the fold
stays bit-identical to the unpacked stream at fixed block order).
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..io.binning import MISSING_NAN, MISSING_ZERO
from ..ops.hist_pallas import unpack4bit
from ..ops.histogram import hist_one_leaf_accum, sums_accum
from ..ops.split import (NO_CONSTRAINT, FeatureMeta, SplitParams,
                         find_best_split, leaf_output, smooth_output)
from .grower import _node_feature_mask, allowed_features_for
from .tree import TreeArrays, empty_tree


class StreamState(NamedTuple):
    """Leaf-sized grower state (the GrowerState of models/grower.py minus
    every O(N) member — those live host-side in block shards)."""

    hist_pool: jax.Array      # (L, F, B, 3) or (1, 1, 1, 3) pool-free
    leaf_sums: jax.Array      # (L, 3)
    leaf_depth: jax.Array     # (L,)
    best_gain: jax.Array      # (L,)
    best_feat: jax.Array
    best_bin: jax.Array
    best_dl: jax.Array
    best_left: jax.Array      # (L, 3)
    best_right: jax.Array
    best_iscat: jax.Array
    best_bitset: jax.Array    # (L, W)
    leaf_constr: jax.Array    # (L, 2)
    leaf_out: jax.Array       # (L,)
    leaf_used: jax.Array      # (L, F)
    tree: TreeArrays
    leaf_is_left: jax.Array
    num_leaves: jax.Array


class StreamGrower:
    """grow(g3_host, base_mask, key) over a block source.

    ``source``: data/streaming block source (disk cache or in-memory
    wrap).  ``ledger``: DeviceLedger recording every device buffer this
    grower creates.  The numeric contract: identical ops, in identical
    order, to the resident masked grower — every formula below mirrors
    models/grower.py's ``make_leafwise_grower`` body (which stays the
    source of truth; the parity tests fail if they drift apart)."""

    def __init__(
        self,
        *,
        source,
        ledger,
        num_leaves: int,
        num_bins: int,
        meta: FeatureMeta,
        params: SplitParams,
        max_depth: int = -1,
        feature_fraction_bynode: float = 1.0,
        monotone_penalty: float = 0.0,
        interaction_groups=None,
        hist_method: str = "scatter",
        hist_precision: str = "bf16x2",
        hist_pool_mb: float = -1.0,
        prefetch: bool = True,
    ):
        self.source = source
        self.ledger = ledger
        self.L = num_leaves
        self.B = num_bins
        self.meta = meta
        self.params = params
        self.max_depth = max_depth
        self.ffbn = feature_fraction_bynode
        self.mono_penalty = monotone_penalty
        self.method = hist_method
        self.precision = hist_precision
        self.prefetch = prefetch
        self.F = int(np.asarray(meta.num_bins).shape[0])
        # packed cache shards: H2D moves the packed bytes; each per-block
        # jit decodes nibbles on device first (_unpack below)
        self.packed_src = (getattr(source, "bin_layout", "u8")
                           == "packed4")
        self.use_mc = bool(np.asarray(meta.monotone_type).any())
        self.groups = (jnp.asarray(interaction_groups)
                       if interaction_groups is not None else None)
        # pool sizing: the same 512 MB auto bound as the resident grower —
        # the pool/pool-free decision changes the subtraction arithmetic,
        # so parity requires the SAME decision on both sides
        pool_bytes = float(self.L) * self.F * self.B * 3 * 4
        cap_bytes = (hist_pool_mb * (1 << 20) if hist_pool_mb > 0
                     else 512.0 * (1 << 20))
        self.use_pool = pool_bytes <= cap_bytes
        self._decide_jit = jax.jit(self._decide)
        self._root_jit = jax.jit(self._root_init)
        self._read_jit = jax.jit(self._read_split)
        self._apply_jit = jax.jit(self._apply_block)
        # one dispatch per block per pass: partition + histogram fold(s)
        # fused (every op inside is exact — 0/1-mask multiplies, integer
        # compares, ordered scatter adds — so fusion cannot move a bit)
        self._root_block_jit = jax.jit(self._root_block)
        self._split_block_jit = jax.jit(self._split_block)

    # -- jitted pieces (each mirrors a slice of grower.py's body) -------
    def _split_fn(self, hist, parent, mask, key, uid, constraint, depth,
                  parent_output):
        rk = jax.random.fold_in(key, uid + 1_000_003 + self.params.extra_seed) \
            if self.params.extra_trees else None
        return find_best_split(hist, parent, self.meta, mask, self.params,
                               constraint, depth, self.mono_penalty,
                               parent_output, rk, None)

    def _clamp_out(self, sums, constr, parent_out=0.0):
        out = leaf_output(sums[0], sums[1], self.params)
        if self.params.path_smooth > 0:
            out = smooth_output(out, sums[2], parent_out, self.params)
        if not self.use_mc:
            return out
        return jnp.clip(out, constr[0], constr[1])

    def _allowed(self, used):
        return allowed_features_for(self.groups, used)

    def _apply_block(self, bins_blk, lid_blk, leaf, nl, feat, thr, dl,
                     iscat, bitset):
        """The masked grower's apply_decision, on one block's rows."""
        meta = self.meta
        with jax.named_scope("lgbm.partition"):
            bins_f = bins_blk[feat]
            is_na = ((meta.missing_type[feat] == MISSING_NAN)
                     & (bins_f == meta.nan_bin[feat])) | (
                (meta.missing_type[feat] == MISSING_ZERO)
                & (bins_f == meta.zero_bin[feat]))
            go_left = jnp.where(is_na, dl, bins_f <= thr)
            bi = bins_f.astype(jnp.int32)
            word = bitset[bi >> 5]
            in_set = ((word >> (bi.astype(jnp.uint32) & 31)) & 1) == 1
            go_left = jnp.where(iscat, in_set, go_left)
            return jnp.where((lid_blk == leaf) & (~go_left), nl, lid_blk)

    def _unpack(self, bins_blk):
        """Device-side nibble decode of a packed block — exact, so every
        downstream fold sees the same uint8 bins as an unpacked stream."""
        return (unpack4bit(bins_blk, self.F) if self.packed_src
                else bins_blk)

    def _root_block(self, acc, rs, bins_blk, g3_blk):
        """Root pass, one block, one dispatch: histogram fold + ordered
        root-sum fold."""
        bins_blk = self._unpack(bins_blk)
        acc = hist_one_leaf_accum(
            acc, bins_blk, g3_blk, jnp.zeros(g3_blk.shape[0], jnp.int32),
            jnp.asarray(0, jnp.int32), self.B, method=self.method,
            precision=self.precision)
        return acc, sums_accum(rs, g3_blk)

    def _split_block(self, acc_s, acc_l, bins_blk, g3_blk, lid_blk, leaf,
                     nl, feat, thr, dl, iscat, bitset, smaller, larger):
        """Split pass, one block, one dispatch: route the block's rows
        through the split, then fold the smaller (and, pool-free, the
        larger) child's histogram."""
        bins_blk = self._unpack(bins_blk)
        lid2 = self._apply_block(bins_blk, lid_blk, leaf, nl, feat, thr,
                                 dl, iscat, bitset)
        acc_s = hist_one_leaf_accum(acc_s, bins_blk, g3_blk, lid2,
                                    smaller, self.B, method=self.method,
                                    precision=self.precision)
        if not self.use_pool:
            acc_l = hist_one_leaf_accum(acc_l, bins_blk, g3_blk, lid2,
                                        larger, self.B,
                                        method=self.method,
                                        precision=self.precision)
        return lid2, acc_s, acc_l

    def _root_init(self, hist0, root_sum, base_mask, key):
        L, F = self.L, self.F
        mask0 = _node_feature_mask(key, 0, base_mask, self.ffbn)
        used0 = jnp.zeros(F, bool)
        mask0 = mask0 & self._allowed(used0)
        no_constr = jnp.asarray(NO_CONSTRAINT, jnp.float32)
        out0 = leaf_output(root_sum[0], root_sum[1], self.params)
        if self.params.path_smooth > 0:
            out0 = smooth_output(out0, root_sum[2], 0.0, self.params)
        res0 = self._split_fn(hist0, root_sum, mask0, key, 0, no_constr, 0,
                              out0)
        W = res0.cat_bitset.shape[0]
        return StreamState(
            hist_pool=(jnp.zeros((L,) + hist0.shape,
                                 jnp.float32).at[0].set(hist0)
                       if self.use_pool
                       else jnp.zeros((1, 1, 1, 3), jnp.float32)),
            leaf_sums=jnp.zeros((L, 3), jnp.float32).at[0].set(root_sum),
            leaf_depth=jnp.zeros(L, jnp.int32),
            best_gain=jnp.full(L, -jnp.inf,
                               jnp.float32).at[0].set(res0.gain),
            best_feat=jnp.zeros(L, jnp.int32).at[0].set(res0.feature),
            best_bin=jnp.zeros(L, jnp.int32).at[0].set(res0.threshold_bin),
            best_dl=jnp.zeros(L, bool).at[0].set(res0.default_left),
            best_left=jnp.zeros((L, 3), jnp.float32).at[0].set(res0.left_sum),
            best_right=jnp.zeros((L, 3),
                                 jnp.float32).at[0].set(res0.right_sum),
            best_iscat=jnp.zeros(L, bool).at[0].set(res0.is_cat),
            best_bitset=jnp.zeros((L, W),
                                  jnp.uint32).at[0].set(res0.cat_bitset),
            leaf_constr=jnp.tile(jnp.asarray(NO_CONSTRAINT, jnp.float32),
                                 (L, 1)),
            leaf_out=jnp.zeros(L, jnp.float32).at[0].set(out0),
            leaf_used=jnp.zeros((L, F), bool),
            tree=empty_tree(L, W),
            leaf_is_left=jnp.zeros(L, bool),
            num_leaves=jnp.asarray(1, jnp.int32),
        )

    def _read_split(self, st: StreamState, leaf):
        """Everything the host block pass needs about the chosen split."""
        return (st.best_feat[leaf], st.best_bin[leaf], st.best_dl[leaf],
                st.best_iscat[leaf], st.best_bitset[leaf],
                st.best_left[leaf], st.best_right[leaf], st.num_leaves)

    def _decide(self, st: StreamState, leaf, s, h_small, h_large,
                base_mask, key):
        """do_split minus the O(N) partition/histogram passes (already
        streamed by the caller); line-for-line with grower.py."""
        meta, params = self.meta, self.params
        nl = st.num_leaves
        node = nl - 1
        feat = st.best_feat[leaf]
        thr = st.best_bin[leaf]
        dl = st.best_dl[leaf]
        lsum = st.best_left[leaf]
        rsum = st.best_right[leaf]
        iscat = st.best_iscat[leaf]
        bitset = st.best_bitset[leaf]
        gain = st.best_gain[leaf]
        parent_sum = st.leaf_sums[leaf]

        pconstr = st.leaf_constr[leaf]
        pout = st.leaf_out[leaf]
        out_l = self._clamp_out(lsum, pconstr, pout)
        out_r = self._clamp_out(rsum, pconstr, pout)
        if self.use_mc:
            mono = meta.monotone_type[feat]
            mid = 0.5 * (out_l + out_r)
            upd = (~iscat) & (mono != 0)
            new_max_l = jnp.where(upd & (mono > 0),
                                  jnp.minimum(pconstr[1], mid), pconstr[1])
            new_min_l = jnp.where(upd & (mono < 0),
                                  jnp.maximum(pconstr[0], mid), pconstr[0])
            new_max_r = jnp.where(upd & (mono < 0),
                                  jnp.minimum(pconstr[1], mid), pconstr[1])
            new_min_r = jnp.where(upd & (mono > 0),
                                  jnp.maximum(pconstr[0], mid), pconstr[0])
            constr_l = jnp.stack([new_min_l, new_max_l])
            constr_r = jnp.stack([new_min_r, new_max_r])
        else:
            constr_l = constr_r = pconstr

        smaller_is_left = lsum[2] <= rsum[2]
        if self.use_pool:
            h_parent = st.hist_pool[leaf]
            h_left = jnp.where(smaller_is_left, h_small,
                               h_parent - h_small)
            h_right = h_parent - h_left
            pool = st.hist_pool.at[leaf].set(h_left).at[nl].set(h_right)
        else:
            h_left = jnp.where(smaller_is_left, h_small, h_large)
            h_right = jnp.where(smaller_is_left, h_large, h_small)
            pool = st.hist_pool

        d = st.leaf_depth[leaf] + 1
        depth_ok = (self.max_depth <= 0) | (d < self.max_depth)

        used_child = st.leaf_used[leaf].at[feat].set(True)
        allow_child = self._allowed(used_child)
        mask_l = _node_feature_mask(key, 2 * s + 1, base_mask,
                                    self.ffbn) & allow_child
        mask_r = _node_feature_mask(key, 2 * s + 2, base_mask,
                                    self.ffbn) & allow_child
        res_l = self._split_fn(h_left, lsum, mask_l, key, 2 * s + 1,
                               constr_l, d, out_l)
        res_r = self._split_fn(h_right, rsum, mask_r, key, 2 * s + 2,
                               constr_r, d, out_r)
        gain_l = jnp.where(depth_ok, res_l.gain, -jnp.inf)
        gain_r = jnp.where(depth_ok, res_r.gain, -jnp.inf)

        t = st.tree
        p = t.leaf_parent[leaf]
        p_safe = jnp.maximum(p, 0)
        was_left = st.leaf_is_left[leaf]
        lc = t.left_child.at[p_safe].set(
            jnp.where((p >= 0) & was_left, node, t.left_child[p_safe]))
        rc = t.right_child.at[p_safe].set(
            jnp.where((p >= 0) & (~was_left), node, t.right_child[p_safe]))
        lc = lc.at[node].set(-(leaf + 1))
        rc = rc.at[node].set(-(nl + 1))
        tree = t._replace(
            num_leaves=nl + 1,
            split_feature=t.split_feature.at[node].set(feat),
            threshold_bin=t.threshold_bin.at[node].set(thr),
            default_left=t.default_left.at[node].set(dl),
            is_cat=t.is_cat.at[node].set(iscat),
            cat_bitset=t.cat_bitset.at[node].set(bitset),
            missing_type=t.missing_type.at[node].set(
                meta.missing_type[feat]),
            left_child=lc,
            right_child=rc,
            split_gain=t.split_gain.at[node].set(gain),
            internal_value=t.internal_value.at[node].set(pout),
            internal_weight=t.internal_weight.at[node].set(parent_sum[1]),
            internal_count=t.internal_count.at[node].set(
                parent_sum[2].astype(jnp.int32)),
            leaf_value=t.leaf_value.at[leaf].set(out_l).at[nl].set(out_r),
            leaf_weight=t.leaf_weight.at[leaf].set(lsum[1])
            .at[nl].set(rsum[1]),
            leaf_count=t.leaf_count.at[leaf].set(lsum[2].astype(jnp.int32))
            .at[nl].set(rsum[2].astype(jnp.int32)),
            leaf_parent=t.leaf_parent.at[leaf].set(node).at[nl].set(node),
        )

        return StreamState(
            hist_pool=pool,
            leaf_sums=st.leaf_sums.at[leaf].set(lsum).at[nl].set(rsum),
            leaf_depth=st.leaf_depth.at[leaf].set(d).at[nl].set(d),
            best_gain=st.best_gain.at[leaf].set(gain_l).at[nl].set(gain_r),
            best_feat=st.best_feat.at[leaf].set(res_l.feature)
            .at[nl].set(res_r.feature),
            best_bin=st.best_bin.at[leaf].set(res_l.threshold_bin)
            .at[nl].set(res_r.threshold_bin),
            best_dl=st.best_dl.at[leaf].set(res_l.default_left)
            .at[nl].set(res_r.default_left),
            best_left=st.best_left.at[leaf].set(res_l.left_sum)
            .at[nl].set(res_r.left_sum),
            best_right=st.best_right.at[leaf].set(res_l.right_sum)
            .at[nl].set(res_r.right_sum),
            best_iscat=st.best_iscat.at[leaf].set(res_l.is_cat)
            .at[nl].set(res_r.is_cat),
            best_bitset=st.best_bitset.at[leaf].set(res_l.cat_bitset)
            .at[nl].set(res_r.cat_bitset),
            leaf_constr=st.leaf_constr.at[leaf].set(constr_l)
            .at[nl].set(constr_r),
            leaf_out=st.leaf_out.at[leaf].set(out_l).at[nl].set(out_r),
            leaf_used=st.leaf_used.at[leaf].set(used_child)
            .at[nl].set(used_child),
            tree=tree,
            leaf_is_left=st.leaf_is_left.at[leaf].set(True)
            .at[nl].set(False),
            num_leaves=nl + 1,
        )

    # -- host-side block streaming --------------------------------------
    def _upload(self, i: int, g3_host, lid_host=None):
        """device_put one block's shards (async — the double-buffer leg);
        returns (bins, g3, lid, handles)."""
        from ..obs import trace

        a, b = self.source.ranges[i]
        with trace.span("stream.fetch_block", cat="stream",
                        args={"block": i} if trace.enabled() else None):
            blk = self.source.load_block(i)
        with trace.span("stream.h2d_block", cat="stream",
                        args={"block": i} if trace.enabled() else None):
            bins = jax.device_put(blk)
            g3 = jax.device_put(np.ascontiguousarray(g3_host[a:b]))
            handles = [self.ledger.hold_array("block_bins", bins),
                       self.ledger.hold_array("block_g3", g3)]
            lid = None
            if lid_host is not None:
                lid = jax.device_put(np.ascontiguousarray(lid_host[a:b]))
                handles.append(self.ledger.hold_array("block_lid", lid))
        return bins, g3, lid, handles

    def _release(self, handles):
        if handles is None:
            return
        if isinstance(handles, int):
            self.ledger.release(handles)
            return
        for h in handles:
            self.ledger.release(h)

    def _stream_blocks(self, g3_host, lid_host, fn):
        """Run ``fn(i, a, b, bins, g3, lid)`` per block with the next
        block's H2D transfer in flight behind the current block's compute
        (the PR-4 chunked double-buffer pattern)."""
        from ..obs import trace

        nb = self.source.num_blocks
        nxt = None
        for i in range(nb):
            cur = nxt if nxt is not None else self._upload(i, g3_host,
                                                           lid_host)
            nxt = (self._upload(i + 1, g3_host, lid_host)
                   if (self.prefetch and i + 1 < nb) else None)
            bins, g3, lid, handles = cur
            a, b = self.source.ranges[i]
            with trace.span("stream.accumulate", cat="stream",
                            args=({"block": i, "rows": b - a}
                                  if trace.enabled() else None)):
                fn(i, a, b, bins, g3, lid)
            self._release(handles)

    def _zero_hist(self, tag):
        acc = jnp.zeros((self.F, self.B, 3), jnp.float32)
        return acc, self.ledger.hold_array(tag, acc)

    def grow(self, g3_host: np.ndarray, base_mask, key):
        """-> (TreeArrays, leaf_id_host (N,) int32, root_sum).  Same split
        sequence and f32 values as the resident masked grower given the
        same g3."""
        L = self.L
        N = self.source.num_rows
        lid_host = np.zeros(N, np.int32)
        base_mask = jnp.asarray(base_mask)

        # root pass: full-matrix histogram + root-sum fold over blocks
        acc, h_acc = self._zero_hist("hist_acc")
        rs = jnp.zeros((1, 3), jnp.float32)

        def root_fn(i, a, b, bins, g3, lid):
            nonlocal acc, rs
            acc, rs = self._root_block_jit(acc, rs, bins, g3)

        self._stream_blocks(g3_host, None, root_fn)
        root_sum = rs[0]
        st = self._root_jit(acc, root_sum, base_mask, key)
        self._release(h_acc)
        pool_h = (self.ledger.hold_array("hist_pool", st.hist_pool)
                  if self.use_pool else None)

        if L > 1:
            for s in range(L - 1):
                best_gain = np.asarray(jax.device_get(st.best_gain))
                leaf = int(np.argmax(best_gain))
                if not (best_gain[leaf] > 0):
                    break   # the resident grower's done latch
                (feat, thr, dl, iscat, bitset, lsum, rsum,
                 nl) = jax.device_get(self._read_jit(st, leaf))
                nl = int(nl)
                smaller = leaf if float(lsum[2]) <= float(rsum[2]) else nl
                larger = nl if smaller == leaf else leaf

                acc_s, h_s = self._zero_hist("hist_acc")
                h_l = None
                if self.use_pool:
                    acc_l = jnp.zeros((1, 1, 3), jnp.float32)  # unused leg
                else:
                    acc_l, h_l = self._zero_hist("hist_acc")
                feat_d = jnp.asarray(int(feat), jnp.int32)
                thr_d = jnp.asarray(int(thr), jnp.int32)
                dl_d = jnp.asarray(bool(dl))
                iscat_d = jnp.asarray(bool(iscat))
                bitset_d = jnp.asarray(bitset)
                leaf_d = jnp.asarray(leaf, jnp.int32)
                nl_d = jnp.asarray(nl, jnp.int32)
                sm_d = jnp.asarray(smaller, jnp.int32)
                lg_d = jnp.asarray(larger, jnp.int32)

                def split_fn(i, a, b, bins, g3, lid):
                    nonlocal acc_s, acc_l
                    lid2, acc_s, acc_l = self._split_block_jit(
                        acc_s, acc_l, bins, g3, lid, leaf_d, nl_d, feat_d,
                        thr_d, dl_d, iscat_d, bitset_d, sm_d, lg_d)
                    lid_host[a:b] = np.asarray(jax.device_get(lid2))

                self._stream_blocks(g3_host, lid_host, split_fn)
                h_large = (acc_l if not self.use_pool
                           else jnp.zeros_like(acc_s))
                st = self._decide_jit(st, leaf_d, jnp.asarray(s, jnp.int32),
                                      acc_s, h_large, base_mask, key)
                self._release(h_s)
                self._release(h_l)
        self._release(pool_h)
        return st.tree, lid_host, root_sum
