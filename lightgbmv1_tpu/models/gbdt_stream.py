"""Row-block streaming boosting drivers — out-of-core training.

:class:`StreamingGBDT` / :class:`StreamingDART` subclass the resident
drivers (models/gbdt.py) and replace every O(N)-on-device pass with a
block-streamed equivalent:

* the **binned matrix** never lands on device whole — per-split passes
  stream verified cache blocks (models/grower_stream.py);
* **score / gradient / leaf-routing state** lives host-side as (N,·)
  numpy shards sliced per block (the reference keeps exactly this state
  in RAM; rows·features is the HBM-breaking term, not rows alone);
* per-block **gradients** run the real objective on device over sliced
  inputs (elementwise objectives: slice == full, bit-for-bit).

Parity contract (tests/test_stream_train.py): with a fixed block order,
streaming training produces **byte-identical model text** to the
resident trainer at the sequential best-first schedule
(``tree_growth=leafwise_masked`` — the parity configuration) across
binary / multiclass / DART including bagging, feature_fraction,
categorical/NaN and valid sets.  The mechanism is arithmetic-order
preservation, not luck: histogram scatter folds continue the resident
pass's update order, score updates are one-add-per-element on both
sides, and DART's drop matmul keeps the same padded (P, K) shape.

Not streamable (rejected loudly at construction): forced splits, CEGB,
EFB bundle-only data, ranking objectives (per-query state), objectives
with host leaf renewal (L1/quantile/MAPE/Huber), stochastic objectives,
custom ``fobj``, GOSS/RF boosting, parallel tree learners.
"""

from __future__ import annotations

import copy
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..data.streaming import DeviceLedger, block_source_for
from ..io.dataset import BinnedDataset
from ..utils.log import log_fatal, log_info, log_warning
from .gbdt import DART, GBDT
from .tree import leaf_lookup, tree_predict_binned


class _HostScoreStore:
    """Host-backed (N, K) f32 score cache — the streaming analog of
    _ScoreUpdater.  Same one-add-per-element update semantics (numpy f32
    adds are the same IEEE ops XLA emits for the resident cache)."""

    def __init__(self, num_data: int, num_class: int, init: np.ndarray):
        self.score = np.broadcast_to(
            init, (num_data, num_class)).astype(np.float32).copy()

    def add_leaf_values(self, leaf_values, leaf_id, k: int):
        self.score[:, k] += np.asarray(leaf_values)[np.asarray(leaf_id)]

    def add_pred(self, pred, k: int):
        self.score[:, k] += np.asarray(pred, np.float32)


class _ObjectiveSlicer:
    """Per-block views of a globally-initialized objective.

    ``init()`` ran ONCE over the full metadata, so global statistics
    (class balance weights, label transforms) are already baked into the
    instance; every (N,)-leading array attribute is then re-homed to host
    memory, and ``sliced(a, b)`` hands back a shallow copy whose arrays
    are the device slices for one block.  Elementwise objectives produce
    bit-identical per-row gradients this way."""

    def __init__(self, obj, num_data: int):
        self._obj = obj
        self._host = {}
        for k, v in list(vars(obj).items()):
            if isinstance(v, (np.ndarray, jax.Array)) \
                    and getattr(v, "ndim", 0) >= 1 \
                    and v.shape[0] == num_data:
                arr = np.asarray(v)
                self._host[k] = arr
                setattr(obj, k, arr)   # frees the device-resident copy

    def sliced(self, a: int, b: int):
        o = copy.copy(self._obj)
        for k, v in self._host.items():
            setattr(o, k, jnp.asarray(v[a:b]))
        return o


def _check_streamable(config: Config, train_set) -> None:
    if config.tree_learner not in ("serial", ""):
        log_fatal(f"streaming training requires tree_learner=serial "
                  f"(got {config.tree_learner}); ROADMAP item 1 composes "
                  "multi-host loading with this path")
    if config.tree_growth == "levelwise":
        log_fatal("streaming training implements the sequential leaf-wise "
                  "schedule; tree_growth=levelwise is resident-only")
    if config.forcedsplits_filename:
        log_fatal("forcedsplits_filename is not supported by the "
                  "streaming trainer")
    if (config.cegb_tradeoff * config.cegb_penalty_split > 0
            or config.cegb_penalty_feature_coupled
            or config.cegb_penalty_feature_lazy):
        log_fatal("CEGB penalties are not supported by the streaming "
                  "trainer (per-row feature marks are O(N*F) state)")
    if train_set.metadata.group is not None:
        log_fatal("ranking objectives (query groups) are not supported by "
                  "the streaming trainer: per-query gradients span blocks")
    if getattr(train_set, "bundle_layout", None) is not None \
            and train_set.binned is None:
        log_fatal("EFB bundle-only (sparse-path) datasets are not "
                  "streamable; load dense data or set enable_bundle=false")


class StreamingGBDT(GBDT):
    """Out-of-core GBDT: device working set O(stream_block_rows · F)."""

    _is_streaming = True

    def __init__(self, config, train_set, objective=None, metrics=None,
                 init_raw_scores=None):
        _check_streamable(config, train_set)
        self._source = block_source_for(train_set, config.stream_block_rows)
        self._ledger = DeviceLedger()
        self._bag_cache = None
        # a packed4 cache (block-cache v3) streams packed shards: the
        # booster's plan takes its layout from the manifest, the prediction
        # walker decodes nibbles (tree_predict_binned packed lane) and
        # add_valid packs valid matrices to match
        super().__init__(config, train_set, objective, metrics,
                         init_raw_scores)
        if self.objective is None:
            log_fatal("streaming training requires a built-in objective "
                      "(custom fobj needs full-matrix raw scores)")
        if self.objective.renew_percentile is not None:
            log_fatal(f"objective {config.objective} renews leaf values "
                      "host-side and is not supported by the streaming "
                      "trainer")
        if getattr(self.objective, "is_stochastic", False):
            log_fatal(f"objective {config.objective} draws per-row "
                      "randomness over the full matrix; not streamable")
        self._slicer = _ObjectiveSlicer(self.objective, self.num_data)
        self._guard_jit = jax.jit(self._stream_guard)
        self._drop_jit = jax.jit(
            lambda preds, w, sc: (preds.T @ w, sc - preds.T @ w))
        self._valid_jit = jax.jit(self._valid_update)
        log_info(
            f"Streaming trainer: {self._source.num_blocks} blocks of "
            f"{getattr(self._source, 'block_rows', 0)} rows "
            f"({self._source.num_rows} rows x {self._source.num_features} "
            "features; device working set bounded per block)")

    # -- plumbing overrides ---------------------------------------------
    @property
    def stream_peak_device_bytes(self) -> int:
        """Ledger peak of streaming-owned device allocations (the
        memory-guard contract's observable; data/streaming.DeviceLedger)."""
        return self._ledger.peak_bytes

    def _new_score_store(self, num_data, num_class, init):
        return _HostScoreStore(num_data, num_class, init)

    def _supports_fused_step(self) -> bool:
        return False

    def _build_trainer(self):
        from ..parallel.trainer import parse_interaction_constraints
        from .grower_stream import StreamGrower

        cfg = self.config
        method = self._hist.method
        if method == "pallas":
            log_warning("hist_method=pallas streams as per-block partial "
                        "sums: deterministic at fixed block order, but "
                        "not bit-identical to the resident kernel; use "
                        "scatter/onehot for the strict parity contract")
        if cfg.tree_growth == "leafwise":
            log_info("streaming trains the sequential best-first order "
                     "(the tree_growth=leafwise_masked / "
                     "leafwise_wave_size=1 parity schedule)")
        self._sgrow = StreamGrower(
            source=self._source,
            ledger=self._ledger,
            num_leaves=cfg.num_leaves,
            num_bins=self.num_bins,
            meta=self.meta,
            params=self.split_params,
            max_depth=cfg.max_depth,
            feature_fraction_bynode=cfg.feature_fraction_bynode,
            monotone_penalty=cfg.monotone_penalty,
            interaction_groups=parse_interaction_constraints(
                cfg.interaction_constraints, self.train_set.num_features),
            hist_method=method,
            hist_precision=cfg.hist_dtype,
            hist_pool_mb=cfg.histogram_pool_size,
            prefetch=cfg.stream_prefetch,
        )
        self._grow = None
        self._grow_binned = None
        self._step = None

    def _pred_with(self, tree, binned):
        return tree_predict_binned(tree, binned, self.meta.nan_bin,
                                   self.meta.missing_type, self._bundle,
                                   self._packed,
                                   zero_bins=self.meta.zero_bin)

    # -- streamed per-row passes ----------------------------------------
    def _stream_guard(self, grad, hess, iteration, row0):
        """_guard_grads with GLOBAL row indexing (the poison slice must
        hit the same rows regardless of block boundaries)."""
        if self._poison_iter is not None:
            n = grad.shape[0]
            rows = ((jnp.arange(n, dtype=jnp.int32) + row0) % 13) == 0
            bad = rows if grad.ndim == 1 else rows[:, None]
            firing = iteration == jnp.int32(self._poison_iter)
            poison = jnp.where(bad & firing, jnp.float32(jnp.nan),
                               jnp.float32(0.0))
            grad = grad + poison
            hess = hess + poison
        if self.config.finite_guard == "clamp":
            finite = jnp.isfinite(grad) & jnp.isfinite(hess)
            grad = jnp.where(finite, grad, 0.0)
            hess = jnp.where(finite, hess, 0.0)
        return grad, hess

    def _stream_gradients(self, score_np, iteration: int):
        """Per-block objective gradients -> host (N, K) f32 pair."""
        N, K = score_np.shape
        grad = np.empty((N, K), np.float32)
        hess = np.empty((N, K), np.float32)
        for a, b in self._source.ranges:
            s_dev = jnp.asarray(np.ascontiguousarray(score_np[a:b]))
            h = self._ledger.hold_array("grad_block", s_dev)
            s = s_dev[:, 0] if K == 1 else s_dev
            obj = self._slicer.sliced(a, b)
            g, hs = obj.get_gradients(s)
            g, hs = self._guard_jit(g, hs, jnp.asarray(iteration, jnp.int32),
                                    jnp.asarray(a, jnp.int32))
            g_np, h_np = jax.device_get((g, hs))
            grad[a:b] = np.asarray(g_np, np.float32).reshape(b - a, -1)
            hess[a:b] = np.asarray(h_np, np.float32).reshape(b - a, -1)
            self._ledger.release(h)
        return grad, hess

    def _stream_bagging_mask(self, iteration: int) -> Optional[np.ndarray]:
        """The fused step's in-jit Bernoulli draw, pulled host-side once
        per bagging period (one transient (N,) device draw — the only
        row-proportional device allocation streaming makes, 4N bytes)."""
        cfg = self.config
        use_pos_neg = (
            cfg.objective == "binary"
            and (cfg.pos_bagging_fraction < 1.0
                 or cfg.neg_bagging_fraction < 1.0))
        if cfg.bagging_freq <= 0 or (cfg.bagging_fraction >= 1.0
                                     and not use_pos_neg):
            return None
        period = iteration // max(cfg.bagging_freq, 1)
        if self._bag_cache is not None and self._bag_cache[0] == period:
            return self._bag_cache[1]
        mask = jax.jit(lambda it: self._bag_fraction_mask(None, it))(
            jnp.asarray(iteration, jnp.int32))
        h = self._ledger.hold_array("bag_mask", mask)
        mask_np = np.asarray(jax.device_get(mask), np.float32)
        self._ledger.release(h)
        self._bag_cache = (period, mask_np)
        return mask_np

    @staticmethod
    def _host_g3(grad_k, hess_k, bag):
        """_sample_g3 on host shards (f32 numpy ops are the same IEEE
        ops the fused step's jnp version emits)."""
        if bag is None:
            cnt = np.ones_like(grad_k)
        else:
            grad_k, hess_k, cnt = grad_k * bag, hess_k * bag, bag
        return np.stack([grad_k, hess_k, cnt], axis=1)

    # -- the iteration ---------------------------------------------------
    def _valid_update(self, vb, vscore, stacked_raw, rate):
        """The fused step's valid-set leg, op-for-op: shrinkage applied
        INSIDE the same jit as the walk and the one stacked add — the
        fusion context changes f32 rounding, so doing the multiply in a
        separate dispatch would break valid-score bit parity."""
        preds = []
        for k in range(self.num_class):
            tree_k = jax.tree_util.tree_map(lambda a: a[k], stacked_raw)
            shrunk = tree_k._replace(leaf_value=tree_k.leaf_value * rate)
            preds.append(self._pred_with(shrunk, vb))
        return vscore + jnp.stack(preds, axis=1)

    def _stream_plain_iter(self, shrinkage=None) -> List:
        K = self.num_class
        rate = (self.config.learning_rate if shrinkage is None
                else shrinkage)
        grad, hess = self._stream_gradients(self._train_scores.score,
                                            int(self.iter))
        bag = self._stream_bagging_mask(int(self.iter))
        raw_trees, new_trees, lids = [], [], []
        for k in range(K):
            g3 = self._host_g3(grad[:, k], hess[:, k], bag)
            key = jax.random.fold_in(self._rng_key,
                                     self.iter * K + k)
            base_mask = jnp.asarray(self._tree_feature_mask())
            tree_dev, leaf_id, _ = self._sgrow.grow(g3, base_mask, key)
            raw_trees.append(tree_dev)
            lids.append(leaf_id)
            shrunk = tree_dev._replace(
                leaf_value=tree_dev.leaf_value * rate)
            # train scores: host one-add-per-element (== the fused
            # step's leaf_lookup formulation)
            self._train_scores.add_leaf_values(shrunk.leaf_value,
                                               leaf_id, k)
            self._device_trees.append(shrunk)
            self.models.append(None)
            self._model_shrink.append(rate)
            self._model_bias.append(self._tree_bias(k))
            new_trees.append(shrunk)
        if self._valid_binned:
            stacked_raw = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *raw_trees)
            rate_dev = jnp.float32(rate)
            for vb, vs in zip(self._valid_binned, self._valid_scores):
                vs.score = self._valid_jit(vb, vs.score, stacked_raw,
                                           rate_dev)
        store = getattr(self, "_maybe_store_lids", None)
        if store is not None:
            store(np.stack(lids))
        return new_trees

    def train_one_iter(self, custom_grad=None, custom_hess=None,
                       check_stop: bool = True) -> bool:
        if custom_grad is not None:
            log_fatal("streaming training does not support custom "
                      "objectives (fobj): gradients stream per block "
                      "from the built-in objective")
        self._save_rollback_state()
        new_trees = self._stream_plain_iter()
        self.iter += 1
        if check_stop:
            stopped = all(int(t.num_leaves) <= 1 for t in new_trees)
            if stopped:
                log_warning(
                    "Stopped training because there are no more leaves "
                    "that meet the split requirements")
            return stopped
        return False

    # -- state management -------------------------------------------------
    def _save_rollback_state(self):
        # the host score array is mutated in place — the snapshot must be
        # a real copy (valid scores are immutable device arrays)
        self._prev_state = (self._train_scores.score.copy(),
                            [vs.score for vs in self._valid_scores],
                            len(self.models))

    def restore_state(self, manifest, arrays) -> None:
        super().restore_state(manifest, arrays)
        self._train_scores.score = np.asarray(arrays["train_score"],
                                              np.float32)
        self._bag_cache = None

    def check_finite_boundary(self) -> None:
        mode = self.config.finite_guard
        if mode not in ("warn", "raise"):
            return
        bad = not bool(np.isfinite(
            np.sum(self._train_scores.score, dtype=np.float64)))
        if not bad and self.objective is not None \
                and self._prev_state is not None and self.iter > 0:
            g, h = self._stream_gradients(self._prev_state[0],
                                          int(self.iter - 1))
            tot = np.sum(g, dtype=np.float64) + np.sum(h, dtype=np.float64)
            bad = not bool(np.isfinite(tot))
        if not bad:
            return
        from .gbdt import FiniteGuardError

        msg = (f"non-finite gradient/score state at iteration {self.iter} "
               f"boundary (finite_guard={mode}): the last iteration's "
               "trees are suspect — roll back or resume from the "
               "previous checkpoint")
        if mode == "raise":
            raise FiniteGuardError(msg)
        if not self._finite_warned:
            self._finite_warned = True
            log_warning(msg)


class StreamingDART(StreamingGBDT, DART):
    """Out-of-core DART: drop removal / restore stream per block through
    the recorded leaf-assignment tables (or per-block tree walks when no
    assignments were recorded), with the resident fused step's padded
    (P, K) drop matmul shape kept so the f32 reduction matches."""

    def train_one_iter(self, custom_grad=None, custom_hess=None,
                       check_stop: bool = True) -> bool:
        cfg = self.config
        if custom_grad is not None:
            log_fatal("streaming DART does not support custom objectives")
        self._save_rollback_state()
        self._prev_weights = (list(self._tree_weight), self._sum_weight)
        drop_iters = self._select_drops()
        if not drop_iters:
            new_trees = self._stream_plain_iter()
            if not cfg.uniform_drop:
                lr = cfg.learning_rate
                self._tree_weight.append(lr)
                self._sum_weight += lr
        else:
            new_trees = self._stream_dart_iter(drop_iters)
        self.iter += 1
        if check_stop:
            return all(int(t.num_leaves) <= 1 for t in new_trees)
        return False

    def _dart_valid_update(self, vb, vscore, drop_stack, w, old_factor,
                           stacked_raw, shrink_new):
        """The fused DART step's valid-set leg (models/gbdt.py full()):
        removal via the drop stack, restore at old_factor, then the new
        trees' predictions — identical op order, with the new trees'
        shrinkage applied INSIDE the jit exactly like step() does."""
        vp = jax.vmap(lambda t: self._pred_with(t, vb))(drop_stack)
        vd = vp.T @ w
        nv = (vscore - vd) + old_factor * vd
        for k in range(self.num_class):
            tree_k = jax.tree_util.tree_map(lambda a: a[k], stacked_raw)
            shrunk = tree_k._replace(
                leaf_value=tree_k.leaf_value * shrink_new)
            nv = nv.at[:, k].add(self._pred_with(shrunk, vb))
        return nv

    def _stream_dart_iter(self, drop_iters: List[int]) -> List:
        cfg = self.config
        K = self.num_class
        k_drop = len(drop_iters)
        shrink_new, old_factor, w_dec = self._normalization(k_drop)
        self._snapshot_dropped(drop_iters)

        n_real = k_drop * K
        P = next(b for b in (4, 16, 64, 256, 1024) if b >= n_real) \
            if n_real <= 1024 else n_real
        use_lids = self._drop_lids_usable()
        need_stack = (not use_lids) or bool(self._valid_binned)
        entries, weights = [], np.zeros((P, K), np.float32)
        lv_tables, lid_rows = [], []
        for j, it in enumerate(drop_iters):
            for k in range(K):
                idx = it * K + k
                t = self._device_trees[idx]
                b = self._model_bias[idx]
                if b:
                    t = t._replace(leaf_value=t.leaf_value + b)
                if need_stack:
                    entries.append(t)
                if use_lids:
                    lv_tables.append(t.leaf_value)
                    lid_rows.append(np.asarray(self._train_leaf_ids[it][k]))
                weights[j * K + k, k] = 1.0
        drop_stack = drop_lv = lid_rows_np = None
        if need_stack:
            while len(entries) < P:
                entries.append(entries[0])    # padding; weight row is 0
            drop_stack = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                                *entries)
        if use_lids:
            while len(lv_tables) < P:
                lv_tables.append(lv_tables[0])
                lid_rows.append(lid_rows[0])
            drop_lv = jnp.stack(lv_tables)
            lid_rows_np = np.stack(lid_rows)          # (P, N) host
        w_dev = jnp.asarray(weights)

        # drop removal, block by block -> host s_drop / delta shards
        score = self._train_scores.score
        s_drop = np.empty_like(score)
        delta = np.empty_like(score)
        for i, (a, b2) in enumerate(self._source.ranges):
            handles = []
            if use_lids:
                lid_blk = jnp.asarray(
                    np.ascontiguousarray(lid_rows_np[:, a:b2]))
                handles.append(self._ledger.hold_array("drop_lids",
                                                       lid_blk))
                preds = jax.vmap(leaf_lookup)(drop_lv, lid_blk)
            else:
                bins = jax.device_put(self._source.load_block(i))
                handles.append(self._ledger.hold_array("block_bins", bins))
                preds = jax.vmap(lambda t: self._pred_with(t, bins))(
                    drop_stack)
            sc_blk = jnp.asarray(np.ascontiguousarray(score[a:b2]))
            handles.append(self._ledger.hold_array("grad_block", sc_blk))
            d_blk, s_blk = self._drop_jit(preds, w_dev, sc_blk)
            d_np, s_np = jax.device_get((d_blk, s_blk))
            delta[a:b2] = np.asarray(d_np)
            s_drop[a:b2] = np.asarray(s_np)
            for h in handles:
                self._ledger.release(h)

        grad, hess = self._stream_gradients(s_drop, int(self.iter))
        bag = self._stream_bagging_mask(int(self.iter))
        shrink_dev = jnp.float32(shrink_new)
        raw_trees, trees, lids = [], [], []
        for k in range(K):
            g3 = self._host_g3(grad[:, k], hess[:, k], bag)
            key = jax.random.fold_in(self._rng_key, self.iter * K + k)
            base_mask = jnp.asarray(self._tree_feature_mask())
            tree_dev, leaf_id, _ = self._sgrow.grow(g3, base_mask, key)
            raw_trees.append(tree_dev)
            trees.append(tree_dev._replace(
                leaf_value=tree_dev.leaf_value * shrink_dev))
            lids.append(leaf_id)

        # train scores: restore at old_factor + the new trees' outputs
        # (the fused step's op order: one restore add, then one add per
        # class column)
        new_score = s_drop + np.float32(old_factor) * delta
        for k in range(K):
            lv = np.asarray(trees[k].leaf_value)
            new_score[:, k] += lv[lids[k]]
        self._train_scores.score = new_score

        if self._valid_binned:
            stacked_raw = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *raw_trees)
            of_dev = jnp.float32(old_factor)
            if not hasattr(self, "_dart_valid_jit"):
                self._dart_valid_jit = jax.jit(self._dart_valid_update)
            for vb, vs in zip(self._valid_binned, self._valid_scores):
                vs.score = self._dart_valid_jit(vb, vs.score, drop_stack,
                                                w_dev, of_dev,
                                                stacked_raw, shrink_dev)

        self._maybe_store_lids(np.stack(lids))
        for k in range(K):
            self._device_trees.append(trees[k])
            self.models.append(None)
            self._model_shrink.append(shrink_new)
            self._model_bias.append(self._tree_bias(k))

        self._rescale_dropped(drop_iters, old_factor, w_dec)
        if not cfg.uniform_drop:
            self._tree_weight.append(shrink_new)
            self._sum_weight += shrink_new
        return trees

    def _restore_extra(self, manifest, arrays) -> None:
        from ..io.checkpoint import decode_rng_state

        d = manifest["dart"]
        self._drop_rng.set_state(decode_rng_state(d["drop_rng"]))
        self._tree_weight = [float(v) for v in d["tree_weight"]]
        self._sum_weight = float(d["sum_weight"])
        self._train_leaf_ids.clear()
        if d.get("lids_kept") and "dart_lids" in arrays:
            lids = arrays["dart_lids"]
            # host shards (NOT device arrays): the drop gather slices them
            # per block
            self._train_leaf_ids.extend(
                np.asarray(lids[i]).astype(self._lid_dtype)
                for i in range(lids.shape[0]))
            self._keep_lids = True
            self._lids_aligned = True
        else:
            self._keep_lids = False
            self._lids_aligned = False
        self._prev_weights = None


def create_streaming_boosting(config: Config, train_set: BinnedDataset,
                              **kw) -> GBDT:
    """Streaming analog of create_boosting (gbdt.py dispatches here when
    the dataset is a block cache or stream_enable is set)."""
    kind = config.boosting
    if kind in ("gbdt", "gbrt"):
        return StreamingGBDT(config, train_set, **kw)
    if kind == "dart":
        return StreamingDART(config, train_set, **kw)
    log_fatal(f"boosting={kind} is not supported by the streaming "
              "trainer (supported: gbdt, dart)")
