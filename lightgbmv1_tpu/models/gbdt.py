"""GBDT boosting loop and variants (DART / GOSS / RF).

TPU-native re-design of the reference boosting layer
(reference: ``src/boosting/gbdt.cpp`` — ``TrainOneIter`` :337-419,
``BoostFromAverage`` :312-335, ``Bagging`` :209-243, ``UpdateScore``
:458-478, ``RollbackOneIter`` :421-437; variants ``dart.hpp:23-170``,
``goss.hpp:25-150``, ``rf.hpp:25``; score caching ``score_updater.hpp``).

Host/device split (SURVEY.md §3.3 note): the per-iteration loop stays on the
host (one compiled tree-build per tree, like the reference's Python-side
loop); everything inside an iteration — gradients, histograms, split search,
partition, score update — runs on device under jit.

Bagging is mask-based: excluded rows get zero grad/hess/count in the
histogram channels (equivalent to the reference's index-subset bagging for
every training statistic), and out-of-bag rows still receive score updates
because the partition covers all rows (the reference updates out-of-bag
scores explicitly, gbdt.cpp:458-478).
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..io.dataset import BinnedDataset, construct_phase
from ..metrics import Metric, create_metrics
from ..objectives import ObjectiveFunction, create_objective
from ..obs import trace as obs_trace
from ..obs import xla as obs_xla
from ..ops.hist_pallas import bin_matrix
from ..ops.split import SplitParams, make_feature_meta
from ..utils.log import log_fatal, log_info, log_warning
from ..utils.timer import global_timer
from .grower import make_leafwise_grower
from .tree import (HostTree, TreeArrays, leaf_lookup,
                   tree_predict_binned, tree_used_features)


def _round_counts(third):
    """A grower's third result as the iteration record's counts: the wave
    grower's rounds by slot bucket and those that ran no histogram pass
    (grower_wave.RootAndRounds); None from the others, which hand back the
    root's sums alone."""
    if hasattr(third, "rounds"):
        return third.rounds, third.hist_skipped
    return None


class FiniteGuardError(RuntimeError):
    """``finite_guard=raise``: non-finite training state (NaN/Inf
    gradients propagated into the score cache) detected at an iteration
    boundary — the poisoned iteration is the LAST one, so a caller can
    roll back or resume from the previous checkpoint instead of shipping
    silently corrupted trees."""


def _np_weighted_quantile_sorted(v, w, q):
    cw = np.cumsum(w)
    if cw[-1] <= 0:
        return 0.0
    idx = int(np.searchsorted(cw, q * cw[-1], side="left"))
    return float(v[min(idx, len(v) - 1)])


class _ScoreUpdater:
    """Cached raw scores for one dataset (reference: score_updater.hpp:21-130)."""

    def __init__(self, num_data: int, num_class: int, init: np.ndarray):
        self.score = jnp.asarray(
            np.broadcast_to(init, (num_data, num_class)).copy(), jnp.float32
        )

    def add_leaf_values(self, leaf_values: jax.Array, leaf_id: jax.Array, k: int):
        self.score = self.score.at[:, k].add(
            leaf_lookup(leaf_values, leaf_id))

    def add_pred(self, pred: jax.Array, k: int):
        self.score = self.score.at[:, k].add(pred)


class GBDT:
    """Gradient Boosting Decision Tree driver (reference: class GBDT, gbdt.h:34)."""

    # out-of-core row-block training (models/gbdt_stream.py sets True):
    # the binned matrix is NEVER uploaded whole — blocks stream per pass
    _is_streaming = False

    def __init__(
        self,
        config: Config,
        train_set: BinnedDataset,
        objective: Optional[ObjectiveFunction] = None,
        metrics: Optional[List[Metric]] = None,
        init_raw_scores: Optional[np.ndarray] = None,
    ):
        # init_raw_scores: (num_data, num_class) raw predictions of a loaded
        # model — continued training resumes boosting from them (reference:
        # continued training via input_model, application.cpp:90-93 predicts
        # the old model to seed the score cache)
        self._init_raw_scores = init_raw_scores
        self.config = config
        self.train_set = train_set
        self.num_data = train_set.num_data
        self.num_class = config.num_tree_per_iteration
        self.objective = objective if objective is not None else create_objective(config)
        if self.objective is not None:
            self.objective.init(train_set.metadata, self.num_data)
        self.train_metrics = metrics if metrics is not None else create_metrics(config)
        for m in self.train_metrics:
            m.init(train_set.metadata, self.num_data)

        # device-resident training data (the EFB bundle matrix when
        # bundling applied — trees and meta always speak ORIGINAL features)
        self._bundle = None
        if not self._is_streaming and train_set.bundle_layout is not None:
            from ..io.bundle import BundleArrays

            incompatible = (config.tree_learner in ("voting", "feature")
                            or bool(config.forcedsplits_filename))
            if incompatible and train_set.binned is None:
                log_fatal("tree_learner=voting/feature and forced splits do "
                          "not support EFB-bundled sparse datasets; load "
                          "dense data or drop the incompatible option")
            if incompatible:
                log_warning("EFB disabled (tree_learner=voting/feature and "
                            "forced splits run on unbundled features)")
                train_set.bundled = None
                train_set.bundle_layout = None
            else:
                self._bundle = BundleArrays(train_set.bundle_layout,
                                            train_set.zero_bins,
                                            train_set.num_bins)
        # 4-bit packing (reference DenseBin<..,IS_4BIT>, dense_bin.hpp:52):
        # two bins per byte when every feature fits 4 bits — halves the
        # stored matrix in HBM and what a partition round reads of it; the
        # histogram kernel's prepared operand is as wide either way
        # (ops/hist_pallas.pack4bit).
        # The histogram method and the stored layout are chosen here, once
        # per build, with the once-per-build logging:
        # parallel/trainer.resolve_hist_plan (config.hist_method,
        # config.bin_layout); every layer below reads ``self._hist``.
        from ..parallel.trainer import resolve_hist_plan

        if self._is_streaming:
            # the row bulk never lands on device whole: blocks stream per
            # histogram pass (models/grower_stream.py); EFB / 4-bit
            # packing are resident-trainer representations (the block
            # cache stores packed SHARDS separately, data/block_cache.py,
            # and its manifest says which)
            self._host_matrix = None
            self._hist = resolve_hist_plan(
                config, bin_dtype=self._source.block_dtype,
                stored=self._source.bin_layout)
        else:
            self._host_matrix = train_set.train_matrix
            self._hist = resolve_hist_plan(
                config, bin_dtype=self._host_matrix.dtype,
                num_total_bin=train_set.num_total_bin,
                bundled=self._bundle is not None)
            if self._hist.packed:
                from ..ops.hist_pallas import pack4bit

                with construct_phase("pack"):
                    self._host_matrix = pack4bit(self._host_matrix)
        if self._is_streaming:
            self.binned = None
        elif getattr(train_set, "is_row_sharded", False):
            # process-sharded training data: the global device array is
            # assembled from per-process shards by the trainer
            # (parallel/dist_data.py make_process_sharded)
            if config.tree_learner != "data":
                log_fatal("process-sharded datasets require "
                          "tree_learner=data")
            self.binned = None
        else:
            with construct_phase("place"):
                self.binned = jnp.asarray(self._host_matrix)
        self.meta = make_feature_meta(train_set, config.monotone_constraints,
                                      config.feature_contri)
        rv = getattr(train_set, "row_valid", None)
        self._row_valid = (jnp.asarray(rv, jnp.float32)
                           if rv is not None else None)
        self.num_bins = train_set.padded_bin
        self.split_params = SplitParams(
            lambda_l1=config.lambda_l1,
            lambda_l2=config.lambda_l2,
            min_data_in_leaf=float(config.min_data_in_leaf),
            min_sum_hessian_in_leaf=config.min_sum_hessian_in_leaf,
            min_gain_to_split=config.min_gain_to_split,
            max_delta_step=config.max_delta_step,
            cat_l2=config.cat_l2,
            cat_smooth=config.cat_smooth,
            max_cat_threshold=int(config.max_cat_threshold),
            max_cat_to_onehot=int(config.max_cat_to_onehot),
            min_data_per_group=float(config.min_data_per_group),
            path_smooth=float(config.path_smooth),
            extra_trees=bool(config.extra_trees),
            extra_seed=int(config.extra_seed),
            cegb_tradeoff=float(config.cegb_tradeoff),
            cegb_penalty_split=float(config.cegb_penalty_split),
        )

        self._build_trainer()

        # initial scores (reference: BoostFromAverage gbdt.cpp:312-335)
        self._init_scores = np.zeros(self.num_class, dtype=np.float64)
        meta_init = train_set.metadata.init_score
        if init_raw_scores is not None:
            base = np.asarray(init_raw_scores, dtype=np.float64).reshape(
                self.num_data, self.num_class)
            self._train_scores = self._new_score_store(
                self.num_data, self.num_class, base)
            self._used_init_score = True
        elif meta_init is not None:
            init = np.asarray(meta_init, dtype=np.float64).reshape(self.num_data, -1)
            base = np.zeros((self.num_data, self.num_class))
            base[:, : init.shape[1]] = init
            self._train_scores = self._new_score_store(
                self.num_data, self.num_class, base)
            self._used_init_score = True
        else:
            if self.objective is not None:
                for k in range(self.num_class):
                    self._init_scores[k] = self.objective.boost_from_score(k)
                if any(self._init_scores):
                    log_info(
                        "Start training from score "
                        + " ".join(f"{s:.6f}" for s in self._init_scores)
                    )
            self._train_scores = self._new_score_store(
                self.num_data, self.num_class, self._init_scores[None, :]
            )
            self._used_init_score = False

        self.models: List[Optional[HostTree]] = []  # flat: iter-major, class-minor
        self._device_trees: List[TreeArrays] = []
        # the last iteration's rounds by slot bucket and those of them that
        # ran no histogram pass, a pair a tree as the wave grower counted
        # them on the device: arrays to add up, read only when the
        # iteration's record is (round_counts_later); None where no grower
        # said
        self._round_counts = None
        self._model_shrink: List[float] = []
        self._model_bias: List[float] = []
        # Host trees are materialized lazily (one batched device_get at the
        # end) unless the objective renews leaf outputs on the host — keeps
        # the per-iteration loop free of device->host syncs, so the host
        # can enqueue iteration i+1 while the device still runs i.
        self._needs_host_tree = (
            self.objective is not None and self.objective.renew_percentile is not None
        )
        self.iter = 0
        self._valid_sets: List[BinnedDataset] = []
        self._valid_names: List[str] = []
        self._valid_binned: List[jax.Array] = []
        self._valid_scores: List[_ScoreUpdater] = []
        self._valid_metrics: List[List[Metric]] = []
        self._prev_state = None
        # CEGB model-level used-feature mask (reference
        # is_feature_used_in_split_, persists across trees) and, for
        # cegb_penalty_feature_lazy, the per-row feature marks (reference
        # feature_used_in_data_ bitset) — both persist across iterations
        self._cegb_lazy_active = (
            bool(config.cegb_penalty_feature_lazy)
            and config.tree_learner in ("serial", "")
            and config.tree_growth != "levelwise")
        self._cegb_enabled = (config.cegb_penalty_split > 0
                              or bool(config.cegb_penalty_feature_coupled)
                              or self._cegb_lazy_active)
        self._cegb_used = jnp.zeros(train_set.num_features, bool)
        if self._cegb_lazy_active:
            self._cegb_used = (
                self._cegb_used,
                jnp.zeros((self.num_data, train_set.num_features), bool))
        self._rng_key = jax.random.PRNGKey(config.seed)
        self._bag_mask: Optional[jax.Array] = None
        self._feat_rng = np.random.RandomState(config.feature_fraction_seed)
        # fault injection (utils/faults.py): an armed grad_poison fault is
        # baked in at trace time as a traced iteration==N select, so it
        # fires exactly once even inside a scanned multi-iteration dispatch
        from ..utils import faults as _faults

        self._poison_iter = _faults.grad_poison_iteration()
        self._finite_warned = False
        # score-cache buffer donation through the fused step
        # (donate_argnums): the iteration's score update runs in place —
        # no second (N, K) buffer per cache, no defensive copy at the
        # dispatch boundary.  XLA:CPU ignores donation (and warns), so
        # the knob arms only off-CPU; tests probe the lowered HLO's
        # aliasing directly (tests/test_wave_pipeline.py).
        self._donate = bool(config.donate_buffers) and \
            jax.default_backend() != "cpu"

    # ------------------------------------------------------------------
    def _new_score_store(self, num_data, num_class, init):
        """Train-score cache factory — the streaming trainer overrides
        this with a host-backed store (block-sharded per-row state)."""
        return _ScoreUpdater(num_data, num_class, init)

    # ------------------------------------------------------------------
    @property
    def iter(self) -> int:
        return self._iter

    @iter.setter
    def iter(self, v: int) -> None:
        # every ensemble mutation (tree append, rollback truncation, DART
        # drop-rescale of EXISTING trees) happens inside an update/rollback
        # flow that moves ``iter``; the monotone version counter is the
        # native-predictor cache invalidation key (with the tree count) —
        # object identity of host trees is not stable (they may be
        # re-materialized per call) and CPython id() can alias after GC
        self._iter = v
        self.model_version = getattr(self, "model_version", -1) + 1

    @property
    def _packed(self) -> bool:
        """The stored matrix holds two 4-bit bins a byte."""
        return self._hist.packed

    # ------------------------------------------------------------------
    def _build_trainer(self):
        from ..parallel.trainer import build_trainer

        # the learner places its own copy of the bins (sharded where the
        # learner is): host time of the transfer's enqueue, not its end
        with construct_phase("place"):
            self._grow, self._grow_binned, _ = build_trainer(
                self.config,
                self._hist,
                self._host_matrix,
                self.meta,
                self.split_params,
                self.num_bins,
                bin_mappers=self.train_set.bin_mappers,
                bundle=self._bundle,
                bundle_num_bins=(self.train_set.padded_bundle_bin
                                 if self._bundle is not None else None),
                row_sharded=getattr(self.train_set, "is_row_sharded", False),
            )
        if self.binned is None:     # process-sharded rows: the learner's own
            self.binned = bin_matrix(self._grow_binned)
        self._step = None  # fused per-iteration step, built lazily

    # ------------------------------------------------------------------
    # Fused iteration: gradients -> sampling -> K tree builds -> score
    # updates, all under ONE jit so an iteration is a single device
    # dispatch: XLA fuses across the stage boundaries and the host pays one
    # launch per iteration (SURVEY.md §3.3: one compiled step per
    # iteration).
    # ------------------------------------------------------------------
    def _supports_fused_step(self) -> bool:
        return (
            self.objective is not None
            and self.objective.renew_percentile is None
            and not self._needs_host_tree
        )

    def _bag_fraction_mask(self, key, iteration):
        """Traceable bagging mask (see _bagging_mask for semantics)."""
        cfg = self.config
        use_pos_neg = (
            cfg.objective == "binary"
            and (cfg.pos_bagging_fraction < 1.0 or cfg.neg_bagging_fraction < 1.0)
        )
        if cfg.bagging_freq <= 0 or (cfg.bagging_fraction >= 1.0 and not use_pos_neg):
            return None
        with jax.named_scope("lgbm.sample"):
            kk = jax.random.fold_in(
                jax.random.PRNGKey(cfg.bagging_seed),
                iteration // max(cfg.bagging_freq, 1),
            )
            if use_pos_neg:
                label = self.objective.label
                pos = jax.random.bernoulli(kk, cfg.pos_bagging_fraction, (self.num_data,))
                neg = jax.random.bernoulli(
                    jax.random.fold_in(kk, 1), cfg.neg_bagging_fraction, (self.num_data,)
                )
                mask = jnp.where(label > 0, pos, neg)
            else:
                mask = jax.random.bernoulli(kk, cfg.bagging_fraction, (self.num_data,))
            return mask.astype(jnp.float32)

    def _build_step(self):
        cfg = self.config
        K = self.num_class
        rate = cfg.learning_rate if not isinstance(self, RF) else 1.0

        def step(binned, valid_binned, train_score, valid_scores, iteration,
                 feat_masks, cegb_used):
            # binned/valid_binned ride as arguments (NOT closure constants):
            # closed-over process-spanning global arrays cannot be baked into
            # the jaxpr on multi-host meshes
            with jax.named_scope("lgbm.objective"):
                s = train_score[:, 0] if K == 1 else train_score
            grad, hess = self._objective_grads(s, iteration)
            if grad.ndim == 1:
                with jax.named_scope("lgbm.objective"):
                    grad, hess = grad[:, None], hess[:, None]
            bag = self._bag_fraction_mask(None, iteration)
            trees = []
            leaf_ids = []
            counts = []
            train_preds = []
            valid_preds = [[] for _ in valid_binned]
            grow_valids = getattr(self._grow, "_supports_valids", False)
            for k in range(K):
                g3 = self._sample_g3(grad[:, k], hess[:, k], bag, iteration)
                key = jax.random.fold_in(self._rng_key, iteration * K + k)
                if grow_valids and valid_binned:
                    # the wave grower routes valid rows through each
                    # round's splits: valid predictions become a
                    # leaf_value gather (no per-tree device walk)
                    tree_dev, leaf_id, third, vlids = self._grow(
                        binned, g3, feat_masks[k], key, cegb_used,
                        valids=tuple(valid_binned))
                else:
                    tree_dev, leaf_id, third = self._grow(
                        binned, g3, feat_masks[k], key, cegb_used
                    )
                    vlids = None
                counts.append(_round_counts(third))
                if self._cegb_enabled:
                    cegb_used = self._update_cegb_state(
                        cegb_used, tree_dev, leaf_id)
                with jax.named_scope("lgbm.score"):
                    shrunk = tree_dev._replace(leaf_value=tree_dev.leaf_value * rate)
                    train_preds.append(leaf_lookup(shrunk.leaf_value, leaf_id))
                    for vi, vb in enumerate(valid_binned):
                        if vlids is not None:
                            # native gather, NOT leaf_lookup: this path is
                            # pinned bit-exact against the tree walk
                            # (test_valid_row_routing_matches_tree_walk), and
                            # valid sets are small enough that the gather tax
                            # does not matter
                            valid_preds[vi].append(shrunk.leaf_value[vlids[vi]])
                        else:
                            valid_preds[vi].append(tree_predict_binned(
                                shrunk, vb, self.meta.nan_bin,
                                self.meta.missing_type, self._bundle,
                                self._packed, zero_bins=self.meta.zero_bin))
                trees.append(shrunk)
                leaf_ids.append(leaf_id)
            # Deferred score bookkeeping: every class's leaf values land in
            # ONE (N, K) elementwise add per score cache instead of K
            # column-slice updates — this step's gradients were computed
            # BEFORE the class loop, so deferral is bit-identical (score
            # columns are independent elements receiving the same single
            # add).  Together with the leaf_lookup formulation this keeps
            # the whole gradient -> g3 -> score-update chain a handful of
            # row-streaming ops inside the same fused dispatch as the
            # trees' round-0 histogram passes (tools/phase_attrib.py
            # itemizes the cost under grad_g3_ms / score_update_ms).
            with jax.named_scope("lgbm.score"):
                train_score = train_score + jnp.stack(train_preds, axis=1)
                if valid_binned:
                    valid_scores = tuple(
                        vs + jnp.stack(vp, axis=1)
                        for vs, vp in zip(valid_scores, valid_preds))
                stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)
                leaf_ids = jnp.stack(leaf_ids)
            # beside the stacked trees, not a field of them: ``bookkeep``
            # slices those field by field, and keeps this as it is
            counts = (None if None in counts else
                      tuple(sum(c) for c in zip(*counts)))
            return (train_score, valid_scores, stacked, leaf_ids,
                    cegb_used, counts)

        self._step_fn = step
        # args 2/3 are the train/valid score caches — the buffers the
        # fused step updates in place under donation.  The labeled
        # lower/compile wrapper (obs/xla.py) makes every compilation of
        # the fused step an observed event (compile_ms, retrace count,
        # cost/memory analysis) without touching its semantics.
        return obs_xla.instrument_jit(
            step, "train.step",
            donate_argnums=(2, 3) if self._donate else ())

    def _objective_grads(self, s, iteration=None):
        with jax.named_scope("lgbm.objective"):
            if getattr(self.objective, "is_stochastic", False):
                grad, hess = self.objective.get_gradients(s, iteration=iteration)
            else:
                grad, hess = self.objective.get_gradients(s)
            return self._guard_grads(grad, hess, iteration)

    def _guard_grads(self, grad, hess, iteration):
        """Finite-guard + fault-injection seam on the grad/hess pass.

        ``finite_guard=clamp`` zeroes non-finite grad/hess entries inside
        the traced step (a poisoned row behaves like a bagged-out row:
        zero weight in every histogram channel), so one bad pass cannot
        corrupt a tree.  ``warn``/``raise`` detect the propagated damage
        host-side at the iteration boundary (check_finite_boundary).
        The injected poison hits a deterministic ~8% row slice — enough
        to corrupt every histogram, small enough that clamp-mode training
        continues meaningfully on the surviving rows."""
        if self._poison_iter is not None and iteration is not None:
            n = grad.shape[0]
            rows = (jnp.arange(n, dtype=jnp.int32) % 13) == 0
            bad = rows if grad.ndim == 1 else rows[:, None]
            firing = jnp.asarray(iteration, jnp.int32) == jnp.int32(
                self._poison_iter)
            poison = jnp.where(bad & firing, jnp.float32(jnp.nan),
                               jnp.float32(0.0))
            grad = grad + poison
            hess = hess + poison
        if self.config.finite_guard == "clamp":
            finite = jnp.isfinite(grad) & jnp.isfinite(hess)
            grad = jnp.where(finite, grad, 0.0)
            hess = jnp.where(finite, hess, 0.0)
        return grad, hess

    def renewed_count_later(self, first_tree: int):
        """For the iteration record (obs/trace.py): a callable giving the
        nodes and leaves, over the trees from ``first_tree`` on, whose
        stored sums were measured again from the rows (models/renew.py).
        It reads the marks back from the trees' shape and counts when the
        record is read, so that nothing waits for the device here."""
        from .renew import count_marked

        policy = getattr(getattr(self, "_grow", None), "_renew_policy", None)
        trees = [t for t in self._device_trees[first_tree:] if t is not None]
        if policy is None or not trees:
            return 0
        return lambda: sum(count_marked(t, policy) for t in trees)

    def round_counts_later(self):
        """For the iteration record (obs/trace.py): two callables, one
        giving the rounds the last iteration's trees ran in each slot
        bucket of the wave grower, smallest bucket first (``(b4, b16, bK)``,
        ``(bK,)`` without a ladder: grower_wave.slot_buckets_for), the
        other how many of those rounds ran no histogram pass
        (grower_wave.children_can_split); None twice where the grower has
        no rounds.  The device counted them (``WaveState.rounds``,
        ``.hist_skipped``) and they stay there until the record is read:
        no host operation and no wait here.  Taken once: an iteration that
        counts none (DART's own step) does not hand on the one before
        it."""
        counted, self._round_counts = self._round_counts, None
        if counted is None:
            return None, None
        rounds, skipped = zip(*counted)
        return (lambda: tuple(int(n) for n in np.sum(
                    [np.asarray(c) for c in rounds], axis=0)),
                lambda: sum(int(c) for c in skipped))

    def check_finite_boundary(self) -> None:
        """Iteration-boundary finite check (``finite_guard=warn|raise``).

        Two detectors, both one scalar device read:

        1. the train score cache — catches NaN/Inf that PROPAGATED into
           the model (diverged training, poisoned leaf values);
        2. a re-run of the just-finished gradient pass on the saved
           pre-update scores (``_prev_state`` — the rollback snapshot
           taken before the iteration) — catches a poisoned pass even
           when the grower ABSORBED it (NaN gains compare false, the
           iteration silently trains a zero no-op tree: the quiet
           mistraining this guard exists to surface).

        Called by Booster.update() after each iteration; train_iters()
        checks at scanned-block boundaries (detector 1 only is exact
        there).  Cost: one extra gradient pass per iteration, only when
        the guard is armed."""
        mode = self.config.finite_guard
        if mode not in ("warn", "raise"):
            return
        bad = not bool(np.isfinite(np.asarray(
            jax.device_get(jnp.sum(self._train_scores.score)))))
        if not bad and self.objective is not None \
                and self._prev_state is not None and self.iter > 0:
            score = self._prev_state[0]
            s = score[:, 0] if self.num_class == 1 else score
            g, h = self._objective_grads(s, iteration=int(self.iter - 1))
            tot = jax.device_get(jnp.sum(g) + jnp.sum(h))
            bad = not bool(np.isfinite(np.asarray(tot)))
        if not bad:
            return
        msg = (f"non-finite gradient/score state at iteration {self.iter} "
               f"boundary (finite_guard={mode}): the last iteration's "
               "trees are suspect — roll back or resume from the "
               "previous checkpoint")
        from ..obs import dump, events

        events.publish("guard.finite_guard", msg,
                       severity="error" if mode == "raise" else "warning",
                       mode=mode, iteration=int(self.iter))
        if mode == "raise":
            # a tripped finite guard is a crash-grade moment: the armed
            # flight recorder dumps the state that explains WHICH
            # iteration poisoned the scores before the raise unwinds it
            dump.dump("finite_guard", error=msg)
            raise FiniteGuardError(msg)
        if not self._finite_warned:
            self._finite_warned = True
            log_warning(msg)

    # ------------------------------------------------------------------
    def train_iters(self, n: int) -> None:
        """Run ``n`` boosting iterations in a SINGLE device dispatch via
        ``lax.scan`` over the fused step — the 'scan over trees on device'
        option (SURVEY.md §3.3).  Amortizes the per-dispatch host cost
        (launch + result handling) over ``n`` iterations."""
        if n <= 0:
            return
        if not self._supports_fused_step():
            for _ in range(n):
                if self.train_one_iter(check_stop=False):
                    break
            return
        if self._step is None:
            self._step = self._build_step()
        if getattr(self, "_scan", None) is None:
            step_fn = self._step_fn

            def scan_fn(binned, valid_binned, train_score, valid_scores,
                        start_iter, feat_masks_all, cegb_used):
                def body(carry, fm):
                    ts, vs, it, cu = carry
                    ts, vs, stacked, _, cu, _ = step_fn(
                        binned, valid_binned, ts, vs, it, fm, cu)
                    return (ts, vs, it + 1, cu), stacked

                (ts, vs, _, cu), trees = jax.lax.scan(
                    body, (train_score, valid_scores, start_iter, cegb_used),
                    feat_masks_all
                )
                return ts, vs, trees, cu

            self._scan = obs_xla.instrument_jit(
                scan_fn, "train.scan",
                donate_argnums=(2, 3) if self._donate else ())

        K = self.num_class
        # the scanned block is ONE device dispatch — the host cannot see
        # iteration boundaries inside it, so the trace carries one block
        # span (its args say how many iterations it amortized) around the
        # block's own prepare / dispatch / bookkeep
        with obs_trace.bridged_span(
                "train.iterations", "train",
                {"n": n, "start_iter": int(self.iter)}):
            with obs_trace.phase_span("prepare"):
                feat_masks = jnp.asarray(np.stack([
                    np.stack([self._tree_feature_mask() for _ in range(K)])
                    for _ in range(n)
                ]))
                vscores = tuple(vs.score for vs in self._valid_scores)
                self._save_rollback_state()
            with obs_trace.phase_span("dispatch"):
                new_train, new_valid, trees, self._cegb_used = self._scan(
                    self._grow_binned, tuple(self._valid_binned),
                    self._train_scores.score, vscores,
                    jnp.asarray(self.iter, jnp.int32), feat_masks,
                    self._cegb_used,
                )
            with obs_trace.phase_span("bookkeep"):
                self._train_scores.score = new_train
                for vs, s in zip(self._valid_scores, new_valid):
                    vs.score = s
                for i in range(n):
                    for k in range(K):
                        self._device_trees.append(
                            jax.tree_util.tree_map(lambda a: a[i, k], trees)
                        )
                        self.models.append(None)
                        self._model_shrink.append(
                            self.config.learning_rate if not isinstance(self, RF) else 1.0
                        )
                        self._model_bias.append(self._tree_bias(k))
                    self.iter += 1
        self.check_finite_boundary()

    def _fused_train_one_iter(self, save_state: bool = True) -> None:
        """One fused iteration as three host phases (obs/trace.py): the
        ``train.prepare`` / ``train.dispatch`` / ``train.bookkeep`` spans
        of the tree; the caller adds ``train.wait`` where it reads the
        result.  ``save_state=False``: the caller took the rollback
        snapshot already (DART, before it selects its drops)."""
        with obs_trace.phase_span("prepare"):
            if save_state:
                self._save_rollback_state()
            if self._step is None:
                self._step = self._build_step()
            feat_masks = jnp.asarray(
                np.stack([self._tree_feature_mask() for _ in range(self.num_class)])
            )
            vscores = tuple(vs.score for vs in self._valid_scores)
            args = (self._grow_binned, tuple(self._valid_binned),
                    self._train_scores.score, vscores,
                    jnp.asarray(self.iter, jnp.int32), feat_masks,
                    self._cegb_used)
        with obs_trace.phase_span("dispatch"):
            (new_train, new_valid, stacked, leaf_ids,
             self._cegb_used, counts) = self._step(*args)
        with obs_trace.phase_span("bookkeep"):
            self._round_counts = None if counts is None else [counts]
            self._train_scores.score = new_train
            for vs, s in zip(self._valid_scores, new_valid):
                vs.score = s
            store = getattr(self, "_maybe_store_lids", None)
            if store is not None:
                # DART keeps each tree's training-row leaf assignment so a
                # later drop re-predicts via a cheap (L,)-table gather instead
                # of a per-row tree walk (see DART._fused_dart_iter)
                store(leaf_ids)
            for k in range(self.num_class):
                tree_k = jax.tree_util.tree_map(lambda a: a[k], stacked)
                self._device_trees.append(tree_k)
                self.models.append(None)
                self._model_shrink.append(
                    self.config.learning_rate if not isinstance(self, RF) else 1.0
                )
                self._model_bias.append(self._tree_bias(k))

    # ------------------------------------------------------------------
    def add_valid(self, valid_set: BinnedDataset, name: str,
                  init_raw: Optional[np.ndarray] = None) -> None:
        metrics = create_metrics(self.config)
        for m in metrics:
            m.init(valid_set.metadata, valid_set.num_data)
        if init_raw is not None:
            # continued training: valid scores also resume from the loaded
            # model's predictions
            init = np.asarray(init_raw, dtype=np.float64).reshape(
                valid_set.num_data, self.num_class)
        elif valid_set.metadata.init_score is not None:
            init = np.asarray(valid_set.metadata.init_score,
                              dtype=np.float64).reshape(valid_set.num_data, -1)
        else:
            init = self._init_scores[None, :]
        if self.iter > 0:
            log_fatal("Cannot add validation data after training started")
        self._valid_sets.append(valid_set)
        self._valid_names.append(name)
        if self._bundle is not None:
            # valid data must share the training bundle layout (the analog
            # of the reference's shared FeatureGroups for valid sets)
            if (valid_set.bundled is None
                    or valid_set.bundle_layout
                    is not self.train_set.bundle_layout):
                if valid_set.binned is None:
                    log_fatal("validation set was bundled with a different "
                              "EFB layout and has no dense bins to "
                              "re-bundle; construct it with "
                              "reference=<train dataset>")
                from ..io.bundle import apply_bundles_dense

                valid_set.bundled = apply_bundles_dense(
                    valid_set.binned, valid_set.zero_bins,
                    self.train_set.bundle_layout)
                valid_set.bundle_layout = self.train_set.bundle_layout
            self._valid_binned.append(jnp.asarray(valid_set.bundled))
        else:
            # sparse valid sets built against an unbundled reference carry
            # identity bundles: bundle bins == original bins
            vb = (valid_set.binned if valid_set.binned is not None
                  else valid_set.train_matrix)
            if self._packed:
                from ..ops.hist_pallas import pack4bit

                vb = pack4bit(vb)
            self._valid_binned.append(jnp.asarray(vb))
        self._valid_scores.append(
            _ScoreUpdater(valid_set.num_data, self.num_class, init)
        )
        self._valid_metrics.append(metrics)

    # ------------------------------------------------------------------
    def _tree_feature_mask(self) -> np.ndarray:
        """Per-tree column sampling (reference: ColSampler by-tree)."""
        usable = ~self.train_set.is_trivial
        frac = self.config.feature_fraction
        if frac >= 1.0:
            return usable
        idx = np.flatnonzero(usable)
        k = max(1, int(math.ceil(frac * len(idx))))
        chosen = self._feat_rng.choice(idx, size=k, replace=False)
        mask = np.zeros_like(usable)
        mask[chosen] = True
        return mask

    def _bagging_mask(self, iteration: int) -> Optional[jax.Array]:
        """reference: GBDT::Bagging gbdt.cpp:209-243 (+ balanced bagging
        :180-207). Mask-based Bernoulli sampling."""
        freq = self.config.bagging_freq
        if self._bag_mask is not None and freq > 0 and iteration % freq != 0:
            return self._bag_mask     # redrawn every bagging_freq trees
        self._bag_mask = self._bag_fraction_mask(None, iteration)
        return self._bag_mask

    # ------------------------------------------------------------------
    def _gradients(self) -> Tuple[jax.Array, jax.Array]:
        score = self._train_scores.score
        s = score[:, 0] if self.num_class == 1 else score
        grad, hess = self._objective_grads(s, int(self.iter))
        if grad.ndim == 1:
            grad, hess = grad[:, None], hess[:, None]
        return grad, hess

    def _update_cegb_state(self, state, tree_dev, leaf_id):
        """Post-tree CEGB bookkeeping. ``state`` is the (F,) used-feature
        mask, or ((F,), (N, F)) with the per-row lazy marks.  The marks
        update is exact: a row 'used' precisely the features on its final
        leaf's root path (the union over the tree of the reference's
        per-split row marking, cost_effective_gradient_boosting.hpp:110)."""
        with jax.named_scope("lgbm.select"):
            if isinstance(state, tuple):
                used, marks = state
                used = used | tree_used_features(tree_dev, used.shape[0])
                from .tree import leaf_path_features

                pf = leaf_path_features(tree_dev, marks.shape[1])
                marks = marks | pf[leaf_id]
                return (used, marks)
            return state | tree_used_features(tree_dev, state.shape[0])

    def _sample_g3(self, grad_k, hess_k, bag, iteration):
        """Assemble the (N, 3) [grad, hess, count] channels with bagging.
        Process-sharded datasets carry phantom pad rows (weight 0): they
        must also have count 0 so min_data_in_leaf gating and count-based
        smoothing see only real rows."""
        with jax.named_scope("lgbm.sample"):
            if bag is None:
                cnt = jnp.ones_like(grad_k)
            else:
                grad_k, hess_k, cnt = grad_k * bag, hess_k * bag, bag
            if self._row_valid is not None:
                cnt = cnt * self._row_valid
            return jnp.stack([grad_k, hess_k, cnt], axis=1)

    # ------------------------------------------------------------------
    def train_one_iter(
        self,
        custom_grad: Optional[np.ndarray] = None,
        custom_hess: Optional[np.ndarray] = None,
        check_stop: bool = True,
    ) -> bool:
        """Train one boosting iteration (num_class trees).
        Returns True if no tree could be grown (reference returns early-stop
        signal when the best gain is non-positive).  ``check_stop=False``
        skips the device->host sync — the benchmark path."""
        cfg = self.config
        if custom_grad is None and self._supports_fused_step():
            self._fused_train_one_iter()
            self.iter += 1
            return check_stop and self._stopped(
                self._device_trees[-self.num_class:])
        with obs_trace.phase_span("prepare"):
            self._save_rollback_state()
            if custom_grad is not None:
                grad = jnp.asarray(np.asarray(custom_grad).reshape(self.num_data, -1), jnp.float32)
                hess = jnp.asarray(np.asarray(custom_hess).reshape(self.num_data, -1), jnp.float32)
        # the host loop has no single dispatch: gradients, each class's
        # grower and its score update are enqueued one after another
        # (``_finish_tree`` syncs only where a leaf renewal needs the host)
        with obs_trace.phase_span("dispatch"):
            if custom_grad is None:
                grad, hess = self._gradients()
            bag = self._bagging_mask(self.iter)
            new_trees, counts = [], []
            for k in range(self.num_class):
                g3 = self._sample_g3(grad[:, k], hess[:, k], bag, self.iter)
                key = jax.random.fold_in(self._rng_key, self.iter * self.num_class + k)
                base_mask = jnp.asarray(self._tree_feature_mask())
                tree_dev, leaf_id, third = self._grow(
                    self._grow_binned, g3, base_mask, key, self._cegb_used)
                counts.append(_round_counts(third))
                if self._cegb_enabled:
                    self._cegb_used = self._update_cegb_state(
                        self._cegb_used, tree_dev, leaf_id)
                new_trees.append(self._finish_tree(tree_dev, leaf_id, k))
            self._round_counts = None if None in counts else counts
        self.iter += 1
        return check_stop and self._stopped(new_trees)

    def _stopped(self, new_trees) -> bool:
        """True when no tree of the iteration split.  ``train.wait`` times
        the read of ``num_leaves``, the host's one explicit sync of a
        tree; it holds the wait for the device only where nothing blocked
        before it.  On a TPU the runtime stops enqueueing a few ops into a
        running step, so the host blocks inside ``train.bookkeep``'s
        one-field slices and this read finds the step done (PERF.md §5)."""
        with obs_trace.phase_span("wait"):
            stopped = all(int(t.num_leaves) <= 1 for t in new_trees)
        if stopped:
            log_warning(
                "Stopped training because there are no more leaves that "
                "meet the split requirements"
            )
        return stopped

    # ------------------------------------------------------------------
    def _finish_tree(self, tree_dev: TreeArrays, leaf_id: jax.Array, k: int,
                     shrinkage: Optional[float] = None) -> TreeArrays:
        """Renew leaf outputs, apply shrinkage, update scores, store model
        (reference: gbdt.cpp:368-380 RenewTreeOutput → Shrinkage → UpdateScore).

        Sync-free unless the objective needs host-side leaf renewal: a
        single-leaf tree has all-zero leaf values, so unconditional score
        updates are correct no-ops and no ``num_leaves`` check is needed."""
        cfg = self.config
        rate = cfg.learning_rate if shrinkage is None else shrinkage
        # init score is embedded into the saved model via AddBias
        # (reference: gbdt.cpp:381-383), NOT into the score caches (those
        # already carry it from _ScoreUpdater init)
        bias = self._tree_bias(k)

        if self._needs_host_tree:
            q = self.objective.renew_percentile if self.objective else None
            if q is not None:
                # ONE batched transfer for everything the renewal reads
                # (tree arrays + per-row leaf ids + this class's scores)
                # instead of three round-trips — each one is a
                # device->host sync that stalls the dispatch queue
                arrays, lid_np, score_np = jax.device_get(
                    (tree_dev, leaf_id, self._train_scores.score[:, k]))
                host_tree = HostTree(arrays)
            else:
                host_tree = HostTree(jax.device_get(tree_dev))
            self._fill_real_thresholds(host_tree)
            if q is not None and host_tree.num_leaves > 1:
                new_vals = self._renew_leaf_values(host_tree, lid_np, k, q,
                                                   score_np)
                host_tree.set_leaf_values(new_vals)
                tree_dev = tree_dev._replace(
                    leaf_value=tree_dev.leaf_value.at[: host_tree.num_leaves].set(
                        jnp.asarray(new_vals, jnp.float32)
                    )
                )
            host_tree.apply_shrinkage(rate)
            host_tree.add_bias(bias)
            self.models.append(host_tree)
        else:
            self.models.append(None)  # materialized lazily in one batch

        self._model_shrink.append(rate)
        self._model_bias.append(bias)

        # score updates: train via partition gather, valid via binned predict
        with jax.named_scope("lgbm.score"):
            shrunk = tree_dev._replace(leaf_value=tree_dev.leaf_value * rate)
            self._train_scores.add_leaf_values(shrunk.leaf_value, leaf_id, k)
            for vb, vs in zip(self._valid_binned, self._valid_scores):
                pred = tree_predict_binned(
                    shrunk, vb, self.meta.nan_bin, self.meta.missing_type,
                    self._bundle, self._packed, zero_bins=self.meta.zero_bin
                )
                vs.add_pred(pred, k)

        self._device_trees.append(shrunk)
        return shrunk

    # ------------------------------------------------------------------
    def materialize_host_trees(self) -> List[HostTree]:
        """Fetch all not-yet-materialized trees in one batched transfer."""
        idxs = [i for i, m in enumerate(self.models) if m is None]
        if idxs:
            with obs_trace.span("train.materialize_host_trees",
                                cat="train"), \
                    global_timer.section("GBDT::MaterializeHostTrees"):
                fetched = jax.device_get([self._device_trees[i] for i in idxs])
            for i, arrays in zip(idxs, fetched):
                ht = HostTree(arrays)
                # device leaf values already include shrinkage
                ht.shrinkage = self._model_shrink[i]
                self._fill_real_thresholds(ht)
                ht.add_bias(self._model_bias[i])
                self.models[i] = ht
        return self.models

    def _tree_bias(self, k: int) -> float:
        """Constant folded into this tree's saved leaf values.  GBDT: the
        init score goes into the first tree of each class (gbdt.cpp:381)."""
        if self.iter == 0 and not self._used_init_score:
            return float(self._init_scores[k])
        return 0.0

    def _fill_real_thresholds(self, tree: HostTree) -> None:
        mappers = self.train_set.bin_mappers
        for i in range(tree.num_leaves - 1):
            m = mappers[tree.split_feature[i]]
            if tree.is_cat[i]:
                # bin-space bitset -> raw category values (the translation
                # the reference does in Tree::SplitCategorical, tree.cpp:70-86)
                cats = [m.bin_2_categorical[b] for b in tree.cat_bins_of(i)
                        if b < len(m.bin_2_categorical)]
                tree.cat_sets[i] = np.asarray(sorted(cats), dtype=np.int64)
                tree.threshold[i] = 0.0   # rewritten to the cat index on save
            else:
                tree.threshold[i] = m.bin_to_threshold(tree.threshold_bin[i])

    def _renew_leaf_values(self, tree: HostTree, leaf_id, k: int, q: float,
                           score=None):
        """reference: RenewTreeOutput (objective-specific, e.g. L1 median —
        regression_objective.hpp RenewTreeOutput + percentile helpers).
        ``leaf_id``/``score`` arrive as host arrays from the caller's single
        batched device_get."""
        label = np.asarray(self.objective._np_label)
        if score is None:
            score = self._train_scores.score[:, k]
        score = np.asarray(score, dtype=np.float64)
        resid = label - score
        lid = np.asarray(leaf_id)
        w = self.objective.renew_weights()
        out = np.array(tree.leaf_value[: tree.num_leaves])
        for leaf in range(tree.num_leaves):
            rows = lid == leaf
            if not rows.any():
                continue
            r = resid[rows]
            order = np.argsort(r)
            if w is None:
                ww = np.ones(len(r))
            else:
                ww = np.asarray(w)[rows]
            out[leaf] = _np_weighted_quantile_sorted(r[order], ww[order], q)
        return out

    # ------------------------------------------------------------------
    def _save_rollback_state(self):
        score = self._train_scores.score
        valid = [vs.score for vs in self._valid_scores]
        if self._donate:
            # the fused step donates these buffers (in-place update); the
            # rollback / finite-guard snapshot must survive the donation,
            # so it keeps explicit copies — one (N, K) device copy per
            # cache per iteration, noise next to the histogram pass
            score = jnp.copy(score)
            valid = [jnp.copy(v) for v in valid]
        self._prev_state = (score, valid, len(self.models))

    def rollback_one_iter(self):
        """reference: GBDT::RollbackOneIter gbdt.cpp:421-437."""
        if self._prev_state is None:
            return
        score, valid_scores, n_models = self._prev_state
        self._train_scores.score = score
        for vs, s in zip(self._valid_scores, valid_scores):
            vs.score = s
        self.models = self.models[:n_models]
        self._device_trees = self._device_trees[:n_models]
        self._model_shrink = self._model_shrink[:n_models]
        self._model_bias = self._model_bias[:n_models]
        self.iter -= 1
        self._prev_state = None

    # ------------------------------------------------------------------
    # Crash-consistent checkpointing (io/checkpoint.py).  The captured
    # state is everything a resumed trainer needs to continue BIT-EXACTLY
    # where the killed one stopped: the same device tree arrays (bin
    # space — no text roundtrip in the loop), the same f32 score caches,
    # the same host RNG states.  Per-iteration PRNG (bagging, GOSS,
    # extra_trees, tree keys) is fold_in-keyed on the iteration counter
    # and therefore stateless — only the sequentially-consumed
    # RandomStates (feature sampling, DART drops) need saving.
    # ------------------------------------------------------------------
    @staticmethod
    def _host_fetch(arr) -> np.ndarray:
        """Dtype-preserving host fetch of a possibly cross-process
        array (checkpoint capture under multi-process training): an
        addressable or fully-replicated array reads directly; a
        process-spanning sharded one is gathered through a jitted
        identity with replicated out-sharding.  NOTE the gather is a
        COLLECTIVE — under ``jax.process_count() > 1`` every process
        must call ``capture_state`` in lockstep (the elastic worker
        captures on all ranks and writes on rank 0)."""
        if getattr(arr, "is_fully_addressable", True) or \
                getattr(arr, "is_fully_replicated", False):
            return np.asarray(jax.device_get(arr))
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = jax.jit(
            lambda a: a,
            out_shardings=NamedSharding(arr.sharding.mesh, P()))(arr)
        return np.asarray(jax.device_get(rep))

    def capture_state(self):
        """-> (manifest dict, arrays dict) for io.checkpoint.write."""
        from ..io.checkpoint import encode_rng_state
        from .tree import TreeArrays

        arrays: Dict[str, np.ndarray] = {}
        for f in TreeArrays._fields:
            arrays[f"tree_{f}"] = np.stack(
                [self._host_fetch(getattr(t, f))
                 for t in self._device_trees])
        arrays["train_score"] = self._host_fetch(self._train_scores.score)
        for i, vs in enumerate(self._valid_scores):
            arrays[f"valid_score_{i}"] = self._host_fetch(vs.score)
        if isinstance(self._cegb_used, tuple):
            arrays["cegb_used"] = self._host_fetch(self._cegb_used[0])
            arrays["cegb_marks"] = self._host_fetch(self._cegb_used[1])
        else:
            arrays["cegb_used"] = self._host_fetch(self._cegb_used)
        # Row-sharded (padded) layouts record the TRUE row count and the
        # pad mask so a resumed trainer with a DIFFERENT fleet shape (pod
        # shrink: elastic.py shrink_on_loss) can remap per-row state —
        # the padded global count is world-dependent, the real rows are
        # not (contiguous rank shards keep true global row order under
        # the mask on both sides).
        if self._row_valid is not None:
            rv = self._host_fetch(self._row_valid) > 0.5
            arrays["row_valid"] = rv
            num_data_true = int(rv.sum())
        else:
            num_data_true = int(self.num_data)
        manifest = {
            "iteration": int(self.iter),
            "num_trees": len(self.models),
            "num_class": int(self.num_class),
            "num_data": int(self.num_data),
            "num_data_true": num_data_true,
            "n_valid": len(self._valid_scores),
            "boosting": type(self).__name__,
            "objective": self.config.objective,
            "seed": int(self.config.seed),
            "used_init_score": bool(self._used_init_score),
            "init_scores": [float(v) for v in self._init_scores],
            "model_shrink": [float(v) for v in self._model_shrink],
            "model_bias": [float(v) for v in self._model_bias],
            "feat_rng": encode_rng_state(self._feat_rng),
        }
        self._capture_extra(manifest, arrays)
        return manifest, arrays

    def _capture_extra(self, manifest, arrays) -> None:
        """Subclass hook (DART adds drop RNG / weights / leaf ids)."""

    def restore_state(self, manifest, arrays) -> None:
        """Restore a captured state into a FRESH trainer built on the
        same dataset/config (valid sets already attached).  Raises
        :class:`~lightgbmv1_tpu.io.checkpoint.CheckpointError` on any
        shape/identity mismatch rather than resuming wrong."""
        from ..io.checkpoint import CheckpointError, decode_rng_state
        from .tree import TreeArrays

        if self.iter != 0 or self.models:
            raise CheckpointError(
                "restore_state() needs a fresh trainer (training already "
                f"started: iteration {self.iter})")
        # num_data: tolerate a PADDED-count change iff both sides are
        # row-sharded layouts agreeing on the TRUE row count (elastic
        # shrink repartitions the same rows over fewer hosts, so the
        # per-rank pad — and with it the padded global count — moves);
        # everything per-row is then remapped old-mask -> new-mask below.
        remap = False
        mask_old = mask_new = None
        if int(manifest["num_data"]) != self.num_data:
            true_want = manifest.get("num_data_true")
            if (true_want is None or self._row_valid is None
                    or "row_valid" not in arrays):
                raise CheckpointError(
                    "checkpoint/trainer mismatch on num_data: checkpoint "
                    f"has {int(manifest['num_data'])!r}, trainer has "
                    f"{self.num_data!r}")
            mask_new = np.asarray(self._row_valid) > 0.5
            mask_old = np.asarray(arrays["row_valid"]).astype(bool)
            if int(mask_new.sum()) != int(true_want) \
                    or int(mask_old.sum()) != int(true_want):
                raise CheckpointError(
                    "checkpoint/trainer mismatch on num_data_true: "
                    f"checkpoint has {int(true_want or -1)!r} real rows, "
                    f"trainer has {int(mask_new.sum())!r}")
            remap = True

        def _remap_rows(a: np.ndarray) -> np.ndarray:
            """Old padded layout -> new padded layout via the two pad
            masks (real rows keep true global order on both sides); new
            pad rows keep the fresh trainer's value."""
            if not remap:
                return a
            if a.shape[0] != mask_old.shape[0]:
                raise CheckpointError(
                    f"per-row checkpoint array has {a.shape[0]} rows, "
                    f"expected {mask_old.shape[0]} (old padded layout)")
            out = np.zeros((self.num_data,) + a.shape[1:], a.dtype)
            out[mask_new] = a[mask_old]
            return out

        def _remap_score(a: np.ndarray) -> np.ndarray:
            """Like :func:`_remap_rows` but new pad rows keep the fresh
            trainer's (init) score instead of 0 — matching what a
            from-scratch run at the new world shape would hold there."""
            if not remap:
                return a
            if a.shape[0] != mask_old.shape[0]:
                raise CheckpointError(
                    f"train_score checkpoint has {a.shape[0]} rows, "
                    f"expected {mask_old.shape[0]} (old padded layout)")
            out = np.asarray(self._train_scores.score, a.dtype).copy()
            out[mask_new] = a[mask_old]
            return out

        for key, want, got in (
                ("num_class", int(manifest["num_class"]), self.num_class),
                ("boosting", manifest["boosting"], type(self).__name__),
                ("objective", manifest["objective"],
                 self.config.objective),
                ("seed", int(manifest["seed"]), int(self.config.seed)),
                ("n_valid", int(manifest["n_valid"]),
                 len(self._valid_scores))):
            if want != got:
                raise CheckpointError(
                    f"checkpoint/trainer mismatch on {key}: checkpoint "
                    f"has {want!r}, trainer has {got!r}")
        T = int(manifest["num_trees"])
        stacked = {f: arrays[f"tree_{f}"] for f in TreeArrays._fields}
        if any(v.shape[0] != T for v in stacked.values()):
            raise CheckpointError("tree array stack does not match the "
                                  "manifest tree count")
        self._device_trees = [
            # (a checkpoint from before the counts were int32 holds them
            # as float32)
            TreeArrays(**{f: jnp.asarray(
                stacked[f][i],
                jnp.int32 if f in ("internal_count", "leaf_count") else None)
                for f in TreeArrays._fields})
            for i in range(T)
        ]
        self.models = [None] * T
        self._model_shrink = [float(v) for v in manifest["model_shrink"]]
        self._model_bias = [float(v) for v in manifest["model_bias"]]
        self._train_scores.score = jnp.asarray(
            _remap_score(np.asarray(arrays["train_score"])))
        for i, vs in enumerate(self._valid_scores):
            vs.score = jnp.asarray(arrays[f"valid_score_{i}"])
        if "cegb_marks" in arrays:
            self._cegb_used = (jnp.asarray(arrays["cegb_used"]),
                               jnp.asarray(_remap_rows(
                                   np.asarray(arrays["cegb_marks"]))))
        else:
            self._cegb_used = jnp.asarray(arrays["cegb_used"])
        self._feat_rng.set_state(decode_rng_state(manifest["feat_rng"]))
        self._used_init_score = bool(manifest["used_init_score"])
        self._init_scores = np.asarray(manifest["init_scores"], np.float64)
        self._bag_mask = None
        self._prev_state = None
        self._restore_extra(manifest, arrays)
        self.iter = int(manifest["iteration"])   # last: bumps model_version

    def _restore_extra(self, manifest, arrays) -> None:
        """Subclass hook (DART)."""

    # ------------------------------------------------------------------
    @staticmethod
    def _host_array(arr) -> np.ndarray:
        """Fetch a (possibly cross-process-sharded) score array to host.
        With process-sharded training data the jitted score updates leave
        the scores row-sharded across processes; a jitted identity with a
        replicated out-sharding inserts the all-gather (the analog of the
        reference's score sync for metric evaluation)."""
        if getattr(arr, "is_fully_addressable", True) or \
                getattr(arr, "is_fully_replicated", False):
            return np.asarray(arr, dtype=np.float64)
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = jax.jit(
            lambda a: a,
            out_shardings=NamedSharding(arr.sharding.mesh, P()))(arr)
        return np.asarray(rep, dtype=np.float64)

    def _converted_pred(self, scores: _ScoreUpdater, objective) -> np.ndarray:
        raw = self._host_array(scores.score)
        s = raw[:, 0] if self.num_class == 1 else raw
        if objective is not None:
            s = objective.convert_output(s)
        return np.asarray(s, dtype=np.float64)

    def _raw_pred(self, scores: _ScoreUpdater) -> np.ndarray:
        """Raw margins for ``wants_raw`` metrics (reference: metrics reading
        score_ directly, e.g. AucMuMetric multiclass_metric.hpp:254)."""
        raw = self._host_array(scores.score)
        s = raw[:, 0] if self.num_class == 1 else raw
        return np.asarray(s, dtype=np.float64)

    def _eval_metrics(self, dataset_name, scores, metrics, out):
        pred = raw = None
        for m in metrics:
            if getattr(m, "wants_raw", False):
                if raw is None:
                    raw = self._raw_pred(scores)
                p = raw
            else:
                if pred is None:
                    pred = self._converted_pred(scores, self.objective)
                p = pred
            for name, value, hb in m.eval(p):
                out.append((dataset_name, name, value, hb))

    def eval_train(self):
        with global_timer.section("GBDT::EvalTrain"):
            return self._eval_train_inner()

    def _eval_train_inner(self):
        out = []
        self._eval_metrics("training", self._train_scores,
                           self.train_metrics, out)
        return out

    def eval_valid(self):
        with global_timer.section("GBDT::EvalValid"):
            return self._eval_valid_inner()

    def _eval_valid_inner(self):
        out = []
        for vname, vs, metrics in zip(
            self._valid_names, self._valid_scores, self._valid_metrics
        ):
            self._eval_metrics(vname, vs, metrics, out)
        return out

    # ------------------------------------------------------------------
    def raw_train_scores(self) -> np.ndarray:
        return self._host_array(self._train_scores.score)

    def num_trees(self) -> int:
        return len(self.models)

    @property
    def num_model_per_iteration(self) -> int:
        return self.num_class


# ---------------------------------------------------------------------------
# GOSS (reference: src/boosting/goss.hpp:25-150)
# ---------------------------------------------------------------------------


class GOSS(GBDT):
    """Gradient-based One-Side Sampling: keep the top_rate fraction of rows
    by |grad * hess|, sample other_rate of the rest, amplifying their
    grad/hess by (1 - top_rate) / other_rate."""

    def _sample_g3(self, grad_k, hess_k, bag, iteration):
        with jax.named_scope("lgbm.sample"):
            cfg = self.config
            n = self.num_data
            top_k = max(1, int(cfg.top_rate * n))
            other_k = max(1, int(cfg.other_rate * n))
            score = jnp.abs(grad_k * hess_k)
            thresh = jnp.sort(score)[-top_k]
            is_top = score >= thresh
            key = jax.random.fold_in(
                jax.random.PRNGKey(cfg.seed + 17), iteration
            )
            rest_prob = other_k / jnp.maximum(n - top_k, 1)
            sampled_rest = (~is_top) & jax.random.bernoulli(key, rest_prob, (n,))
            amp = (1.0 - cfg.top_rate) / cfg.other_rate
            w = jnp.where(is_top, 1.0, jnp.where(sampled_rest, amp, 0.0))
            cnt = (is_top | sampled_rest).astype(jnp.float32)
            if bag is not None:
                w = w * bag
                cnt = cnt * bag
            return jnp.stack([grad_k * w, hess_k * w, cnt], axis=1)


# ---------------------------------------------------------------------------
# DART (reference: src/boosting/dart.hpp:23-170)
# ---------------------------------------------------------------------------


class DART(GBDT):
    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._drop_rng = np.random.RandomState(self.config.drop_seed)
        # per-tree weights driving the weighted (non-uniform) drop
        # (reference: dart.hpp tree_weight_/sum_weight_, :67-68,103-115)
        self._tree_weight: List[float] = []
        self._sum_weight = 0.0
        self._dart_steps: dict = {}    # (P, use_lids) -> compiled step
        # per-iteration (K, N) leaf assignments of the TRAIN rows: a drop's
        # train-score removal becomes leaf_value[lid] — one small-table
        # gather — instead of a per-row tree walk (which random-gathers the
        # (F, N) matrix per node and dominates DART cost on TPU).  Bounded
        # to ~1 GB of HBM; beyond that drops fall back to tree walks.
        self._train_leaf_ids: List[jax.Array] = []
        L = self.config.num_leaves
        self._lid_dtype = (jnp.uint8 if L <= 256
                           else jnp.uint16 if L <= 65536 else jnp.int32)
        # dynamic ~1 GB budget (config.num_iterations is unreliable here:
        # engine.train moves the round count into num_boost_round); once
        # exhausted — or once any host-path iteration breaks the
        # per-iteration alignment — the list is freed and drops fall back
        # to tree walks for the rest of the run
        self._lid_per_iter_bytes = (self.num_data * self.num_class
                                    * jnp.dtype(self._lid_dtype).itemsize)
        self._lid_budget = 1 << 30
        self._keep_lids = True
        self._lids_aligned = True

    def _maybe_store_lids(self, leaf_ids) -> None:
        if not (self._keep_lids and self._lids_aligned):
            return
        if ((len(self._train_leaf_ids) + 1) * self._lid_per_iter_bytes
                > self._lid_budget):
            self._keep_lids = False
            self._train_leaf_ids.clear()
            return
        self._train_leaf_ids.append(leaf_ids.astype(self._lid_dtype))

    def _drop_lids_usable(self) -> bool:
        return (self._keep_lids and self._lids_aligned
                and len(self._train_leaf_ids)
                == len(self.models) // self.num_class)

    def _capture_extra(self, manifest, arrays) -> None:
        from ..io.checkpoint import encode_rng_state

        manifest["dart"] = {
            "drop_rng": encode_rng_state(self._drop_rng),
            "tree_weight": [float(v) for v in self._tree_weight],
            "sum_weight": float(self._sum_weight),
            "lids_kept": bool(self._drop_lids_usable()),
        }
        if self._drop_lids_usable() and self._train_leaf_ids:
            # the recorded per-iteration (K, N) leaf assignments: restoring
            # them keeps the resumed run on the SAME fused drop path
            # (leaf-table gather) the uninterrupted run compiles, so the
            # two runs execute identical programs — the strongest
            # bit-exactness guarantee, not just value equality
            arrays["dart_lids"] = np.stack(
                [np.asarray(a) for a in jax.device_get(
                    self._train_leaf_ids)])

    def _restore_extra(self, manifest, arrays) -> None:
        from ..io.checkpoint import decode_rng_state

        d = manifest["dart"]
        self._drop_rng.set_state(decode_rng_state(d["drop_rng"]))
        self._tree_weight = [float(v) for v in d["tree_weight"]]
        self._sum_weight = float(d["sum_weight"])
        self._train_leaf_ids.clear()
        if d.get("lids_kept") and "dart_lids" in arrays:
            lids = arrays["dart_lids"]
            self._train_leaf_ids.extend(
                jnp.asarray(lids[i]).astype(self._lid_dtype)
                for i in range(lids.shape[0]))
            self._keep_lids = True
            self._lids_aligned = True
        else:
            # no recorded assignments: drops fall back to tree walks
            # (value-equal; the compiled drop program differs)
            self._keep_lids = False
            self._lids_aligned = False
        self._prev_weights = None

    def _supports_fused_step(self) -> bool:
        # the scanned multi-iteration path cannot host the per-iteration
        # drop selection; DART fuses WITHIN an iteration instead
        return False

    def _select_drops(self) -> List[int]:
        """Host-side drop selection (reference: dart.hpp DroppingTrees
        :96-137 — uniform_drop drops at drop_rate; otherwise each tree's
        probability is weighted by its current normalized weight)."""
        cfg = self.config
        n_trees = len(self.models) // self.num_class
        drop_iters: List[int] = []
        if n_trees > 0 and self._drop_rng.rand() >= cfg.skip_drop:
            dr = cfg.drop_rate
            if not cfg.uniform_drop and self._sum_weight > 0:
                inv_avg = len(self._tree_weight) / self._sum_weight
                if cfg.max_drop > 0:
                    dr = min(dr, cfg.max_drop * inv_avg / self._sum_weight)
                for i in range(n_trees):
                    if self._drop_rng.rand() < dr * self._tree_weight[i] * inv_avg:
                        drop_iters.append(i)
                        if cfg.max_drop > 0 and len(drop_iters) >= cfg.max_drop:
                            break
            else:
                if cfg.max_drop > 0:
                    dr = min(dr, cfg.max_drop / float(n_trees))
                for i in range(n_trees):
                    if self._drop_rng.rand() < dr:
                        drop_iters.append(i)
                        if cfg.max_drop > 0 and len(drop_iters) >= cfg.max_drop:
                            break
        return drop_iters

    def _normalization(self, k_drop: int):
        """(shrink_new, old_factor, w_dec) — reference dart.hpp Normalize
        :158-196 and shrinkage_rate_ :138-146."""
        lr = self.config.learning_rate
        if self.config.xgboost_dart_mode:
            shrink_new = lr if k_drop == 0 else lr / (lr + k_drop)
            return shrink_new, k_drop / (k_drop + lr), 1.0 / (k_drop + lr)
        return (lr / (k_drop + 1.0), k_drop / (k_drop + 1.0),
                1.0 / (k_drop + 1.0))

    def _snapshot_dropped(self, drop_iters: List[int]) -> None:
        """Extend the rollback snapshot with the dropped trees' state (the
        permanent old_factor rescale must be undoable)."""
        self._prev_state = self._prev_state + (
            {
                it * self.num_class + kk: (
                    None if self.models[it * self.num_class + kk] is None
                    else (
                        self.models[it * self.num_class + kk].leaf_value.copy(),
                        self.models[it * self.num_class + kk].internal_value.copy(),
                        self.models[it * self.num_class + kk].shrinkage,
                    ),
                    self._device_trees[it * self.num_class + kk].leaf_value,
                    self._model_shrink[it * self.num_class + kk],
                    self._model_bias[it * self.num_class + kk],
                )
                for it in drop_iters
                for kk in range(self.num_class)
            },
        )

    def _rescale_dropped(self, drop_iters: List[int], old_factor: float,
                         w_dec: float) -> None:
        """Permanent rescale of the dropped trees (reference Normalize
        :158-196).  Works for lazily-materialized trees: the device leaf
        values carry the rescale; _model_shrink/_model_bias metadata scale
        with them."""
        for it in drop_iters:
            for k in range(self.num_class):
                idx = it * self.num_class + k
                if self.models[idx] is not None:
                    self.models[idx].apply_shrinkage(old_factor)
                self._device_trees[idx] = self._device_trees[idx]._replace(
                    leaf_value=self._device_trees[idx].leaf_value * old_factor
                )
                self._model_shrink[idx] *= old_factor
                self._model_bias[idx] *= old_factor
            if not self.config.uniform_drop:
                self._sum_weight -= self._tree_weight[it] * w_dec
                self._tree_weight[it] *= old_factor

    # ------------------------------------------------------------------
    # fused DART iteration: drop removal, gradients, K class trees, drop
    # restore, and every score update in ONE device dispatch (the host
    # keeps only drop selection and bookkeeping).  Semantics identical to
    # the host-loop path below (reference dart.hpp:23-170).
    # ------------------------------------------------------------------
    def _build_dart_step(self, P: int, use_lids: bool):
        K = self.num_class

        def pred_with(tree, b):
            return tree_predict_binned(tree, b, self.meta.nan_bin,
                                       self.meta.missing_type,
                                       self._bundle, self._packed,
                                       zero_bins=self.meta.zero_bin)

        def step(binned, valid_binned, train_score, valid_scores, iteration,
                 feat_masks, cegb_used, drop_stack, drop_weight, shrink_new,
                 drop_lv, drop_lids):
            # drop_weight: (P, K) f32 one-hot rows scaled by the slot's
            # validity (0 rows = padding).  With use_lids the TRAIN removal
            # gathers drop_lv (P, L) bias-carrying leaf tables through the
            # RECORDED leaf assignments drop_lids (P, N) — a small-table
            # gather instead of a per-row tree walk (the walk random-
            # gathers the (F, N) matrix per node and dominated DART cost);
            # drop_stack (full TreeArrays over P slots) is only needed for
            # valid-set removal, where no assignments were recorded.
            with jax.named_scope("lgbm.score"):
                if use_lids:
                    preds = jax.vmap(leaf_lookup)(drop_lv, drop_lids)  # (P, N)
                else:
                    preds = jax.vmap(lambda t: pred_with(
                        t, bin_matrix(binned)))(drop_stack)
                drop_delta = preds.T @ drop_weight                   # (N, K)
                s_drop = train_score - drop_delta
                v_drops, v_deltas = [], []
                for vb, vscore in zip(valid_binned, valid_scores):
                    vp = jax.vmap(lambda t: pred_with(t, vb))(drop_stack)
                    vd = vp.T @ drop_weight
                    v_deltas.append(vd)
                    v_drops.append(vscore - vd)

            s = s_drop[:, 0] if K == 1 else s_drop
            grad, hess = self._objective_grads(s, iteration)
            if grad.ndim == 1:
                grad, hess = grad[:, None], hess[:, None]
            bag = self._bag_fraction_mask(None, iteration)

            trees, leaf_ids = [], []
            for k in range(K):
                g3 = self._sample_g3(grad[:, k], hess[:, k], bag, iteration)
                key = jax.random.fold_in(self._rng_key, iteration * K + k)
                tree_dev, leaf_id, _ = self._grow(binned, g3, feat_masks[k],
                                                  key, cegb_used)
                if self._cegb_enabled:
                    cegb_used = self._update_cegb_state(cegb_used, tree_dev,
                                                        leaf_id)
                with jax.named_scope("lgbm.score"):
                    shrunk = tree_dev._replace(
                        leaf_value=tree_dev.leaf_value * shrink_new)
                trees.append(shrunk)
                leaf_ids.append(leaf_id)
            with jax.named_scope("lgbm.score"):
                stacked = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *trees)
                leaf_ids = jnp.stack(leaf_ids)
            return (s_drop, tuple(v_drops), drop_delta, tuple(v_deltas),
                    stacked, leaf_ids, cegb_used)

        def full(binned, valid_binned, train_score, valid_scores, iteration,
                 feat_masks, cegb_used, drop_stack, drop_weight, shrink_new,
                 old_factor, drop_lv=None, drop_lids=None):
            (s_drop, v_drops, d_delta, v_deltas, stacked, leaf_ids,
             cegb_used) = step(binned, valid_binned, train_score,
                               valid_scores, iteration, feat_masks,
                               cegb_used, drop_stack, drop_weight,
                               shrink_new, drop_lv, drop_lids)
            with jax.named_scope("lgbm.score"):
                new_train = s_drop + old_factor * d_delta
                new_valids = [vs + old_factor * vd
                              for vs, vd in zip(v_drops, v_deltas)]
                for k in range(K):
                    tree_k = jax.tree_util.tree_map(lambda a: a[k], stacked)
                    new_train = new_train.at[:, k].add(
                        leaf_lookup(tree_k.leaf_value, leaf_ids[k]))
                    new_valids = [
                        nv.at[:, k].add(pred_with(tree_k, vb))
                        for nv, vb in zip(new_valids, valid_binned)
                    ]
            return (new_train, tuple(new_valids), stacked, leaf_ids,
                    cegb_used)

        # same donation contract as the plain fused step: args 2/3 are the
        # score caches, updated in place (rollback snapshots keep copies)
        return obs_xla.instrument_jit(
            full, "train.dart_step",
            donate_argnums=(2, 3) if self._donate else ())

    def _dart_step_for(self, P: int, use_lids: bool):
        key = (P, use_lids)
        if key not in self._dart_steps:
            self._dart_steps[key] = self._build_dart_step(P, use_lids)
        return self._dart_steps[key]

    def _fused_dart_iter(self, drop_iters: List[int]) -> None:
        with obs_trace.phase_span("prepare"):
            cfg = self.config
            K = self.num_class
            k_drop = len(drop_iters)
            shrink_new, old_factor, w_dec = self._normalization(k_drop)
            self._snapshot_dropped(drop_iters)

            # padded drop stack: fixed bucket sizes keep the number of compiled
            # step variants tiny (each new P is a full recompile of the fused
            # iteration — the dominant DART cost if P tracked k_drop exactly)
            n_real = k_drop * K
            P = next(b for b in (4, 16, 64, 256, 1024) if b >= n_real) \
                if n_real <= 1024 else n_real
            # leaf-id fast path only while every past iteration recorded its
            # assignments (a host-path iteration, e.g. custom fobj, breaks the
            # alignment — then drops fall back to tree walks)
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
                        lid_rows.append(self._train_leaf_ids[it][k])
                    weights[j * K + k, k] = 1.0
            drop_stack = drop_lv = drop_lids = None
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
                drop_lids = jnp.stack(lid_rows)

            step = self._dart_step_for(P, use_lids)
            feat_masks = jnp.asarray(
                np.stack([self._tree_feature_mask() for _ in range(K)]))
            vscores = tuple(vs.score for vs in self._valid_scores)
        with obs_trace.phase_span("dispatch"):
            (new_train, new_valid, stacked, leaf_ids,
             self._cegb_used) = step(
                self._grow_binned, tuple(self._valid_binned),
                self._train_scores.score, vscores,
                jnp.asarray(self.iter, jnp.int32), feat_masks,
                self._cegb_used, drop_stack, jnp.asarray(weights),
                jnp.float32(shrink_new), jnp.float32(old_factor),
                drop_lv, drop_lids,
            )
        with obs_trace.phase_span("bookkeep"):
            self._train_scores.score = new_train
            for vs, s in zip(self._valid_scores, new_valid):
                vs.score = s
            self._maybe_store_lids(leaf_ids)
            for k in range(K):
                self._device_trees.append(
                    jax.tree_util.tree_map(lambda a: a[k], stacked))
                self.models.append(None)
                self._model_shrink.append(shrink_new)
                self._model_bias.append(self._tree_bias(k))

            self._rescale_dropped(drop_iters, old_factor, w_dec)
            if not cfg.uniform_drop:
                self._tree_weight.append(shrink_new)
                self._sum_weight += shrink_new

    def train_one_iter(self, custom_grad=None, custom_hess=None,
                       check_stop: bool = True) -> bool:
        cfg = self.config
        fused_ok = (custom_grad is None and self.objective is not None
                    and self.objective.renew_percentile is None
                    and not self._needs_host_tree)
        if fused_ok:
            with obs_trace.phase_span("prepare"):
                self._save_rollback_state()
                self._prev_weights = (list(self._tree_weight), self._sum_weight)
                drop_iters = self._select_drops()
            if not drop_iters:
                # no drop: exactly a plain GBDT iteration at rate lr
                self._fused_train_one_iter(save_state=False)
                if not cfg.uniform_drop:
                    lr = cfg.learning_rate
                    self._tree_weight.append(lr)
                    self._sum_weight += lr
            else:
                self._fused_dart_iter(drop_iters)
            self.iter += 1
            return check_stop and self._stopped(
                self._device_trees[-self.num_class:])
        return self._host_train_one_iter(custom_grad, custom_hess,
                                         check_stop)

    def _host_train_one_iter(self, custom_grad=None, custom_hess=None,
                             check_stop: bool = True) -> bool:
        cfg = self.config
        # this path records no leaf assignments: the per-iteration list
        # would misalign, so free it and use tree walks from here on
        self._lids_aligned = False
        self._train_leaf_ids.clear()
        self._save_rollback_state()
        self._prev_weights = (list(self._tree_weight), self._sum_weight)
        drop_iters = self._select_drops()
        k_drop = len(drop_iters)

        # remove dropped trees' contribution from scores, caching each
        # prediction so the restore pass below costs no second traversal
        dropped_preds = {}
        if k_drop:
            # rollback must be able to undo the permanent rescaling of
            # dropped trees, so snapshot their values
            self._snapshot_dropped(drop_iters)
            dropped_preds = self._remove_dropped(drop_iters)

        if custom_grad is not None:
            grad = jnp.asarray(np.asarray(custom_grad).reshape(self.num_data, -1), jnp.float32)
            hess = jnp.asarray(np.asarray(custom_hess).reshape(self.num_data, -1), jnp.float32)
        else:
            grad, hess = self._gradients()
        bag = self._bagging_mask(self.iter)

        shrink_new, old_factor, w_dec = self._normalization(k_drop)

        new_trees = []
        for k in range(self.num_class):
            g3 = self._sample_g3(grad[:, k], hess[:, k], bag, self.iter)
            key = jax.random.fold_in(self._rng_key, self.iter * self.num_class + k)
            base_mask = jnp.asarray(self._tree_feature_mask())
            tree_dev, leaf_id, _ = self._grow(
                self._grow_binned, g3, base_mask, key, self._cegb_used)
            if self._cegb_enabled:
                self._cegb_used = self._update_cegb_state(
                    self._cegb_used, tree_dev, leaf_id)
            new_trees.append(
                self._finish_tree(tree_dev, leaf_id, k, shrinkage=shrink_new)
            )
        stopped = all(int(t.num_leaves) <= 1 for t in new_trees)

        # scale dropped trees and restore their (rescaled) contribution —
        # reusing the cached removal predictions, scaled by old_factor
        if k_drop:
            for it in drop_iters:
                for k in range(self.num_class):
                    idx = it * self.num_class + k
                    if self.models[idx] is not None:
                        self.models[idx].apply_shrinkage(old_factor)
                    self._device_trees[idx] = self._device_trees[idx]._replace(
                        leaf_value=self._device_trees[idx].leaf_value * old_factor
                    )
                    # metadata scales with the tree (shrinkage for lazy
                    # materialization, the embedded init score always)
                    self._model_shrink[idx] *= old_factor
                    self._model_bias[idx] *= old_factor
                    pred, vpreds = dropped_preds[idx]
                    self._train_scores.add_pred(old_factor * pred, k)
                    for vs, vp in zip(self._valid_scores, vpreds):
                        vs.add_pred(old_factor * vp, k)
                if not cfg.uniform_drop:
                    # reference Normalize weight rescale (:173-175,:191-194)
                    self._sum_weight -= self._tree_weight[it] * w_dec
                    self._tree_weight[it] *= old_factor

        if not cfg.uniform_drop:
            self._tree_weight.append(shrink_new)
            self._sum_weight += shrink_new
        self.iter += 1
        return stopped

    def _remove_dropped(self, drop_iters: List[int]):
        """Subtract dropped trees from all score caches; return the cached
        per-tree predictions keyed by model index.

        Drops use the **bias-carrying** tree (the embedded init score included)
        exactly like the reference, which drops via the saved model trees
        (dart.hpp DroppingTrees uses models_, whose first tree absorbed the
        init via AddBias) — this keeps score caches and the saved model
        consistent under drop-normalization."""
        preds = {}
        for it in drop_iters:
            for k in range(self.num_class):
                idx = it * self.num_class + k
                tree = self._device_trees[idx]
                b = self._model_bias[idx]
                if b:
                    tree = tree._replace(leaf_value=tree.leaf_value + b)
                pred = tree_predict_binned(
                    tree, self.binned, self.meta.nan_bin,
                    self.meta.missing_type, self._bundle, self._packed,
                    zero_bins=self.meta.zero_bin)
                self._train_scores.add_pred(-pred, k)
                vpreds = []
                for vb, vs in zip(self._valid_binned, self._valid_scores):
                    vp = tree_predict_binned(
                        tree, vb, self.meta.nan_bin,
                        self.meta.missing_type, self._bundle, self._packed,
                        zero_bins=self.meta.zero_bin)
                    vs.add_pred(-vp, k)
                    vpreds.append(vp)
                preds[idx] = (pred, vpreds)
        return preds

    def rollback_one_iter(self):
        if self._prev_state is not None and len(self._prev_state) == 4:
            dropped = self._prev_state[3]
            for idx, (host_snap, dev_vals, shrink, bias) in dropped.items():
                if host_snap is not None and self.models[idx] is not None:
                    lv, iv, sh = host_snap
                    self.models[idx].leaf_value = lv
                    self.models[idx].internal_value = iv
                    self.models[idx].shrinkage = sh
                self._device_trees[idx] = self._device_trees[idx]._replace(
                    leaf_value=dev_vals
                )
                self._model_shrink[idx] = shrink
                self._model_bias[idx] = bias
            self._prev_state = self._prev_state[:3]
        if getattr(self, "_prev_weights", None) is not None:
            self._tree_weight, self._sum_weight = self._prev_weights
            self._prev_weights = None
        super().rollback_one_iter()
        keep = len(self.models) // self.num_class
        del self._train_leaf_ids[keep:]


# ---------------------------------------------------------------------------
# RF (reference: src/boosting/rf.hpp:25 — bagging-required, averaged outputs)
# ---------------------------------------------------------------------------


class RF(GBDT):
    def __init__(self, config, train_set, objective=None, metrics=None,
                 init_raw_scores=None):
        if config.bagging_freq <= 0 or config.bagging_fraction >= 1.0:
            log_fatal("RF mode requires bagging "
                      "(bagging_freq > 0 and bagging_fraction < 1)")
        if train_set.metadata.init_score is not None:
            log_fatal("RF mode does not support init_score (reference rf.hpp:44)")
        if init_raw_scores is not None:
            log_fatal("RF mode does not support continued training")
        super().__init__(config, train_set, objective, metrics)

    def _tree_bias(self, k: int) -> float:
        # reference rf.hpp:136: every tree absorbs the init score, and
        # prediction divides the summed output by the iteration count
        return float(self._init_scores[k])

    _cached_grads = None

    def _gradients(self):
        # gradients always computed at the constant init score — computed
        # once and reused (reference rf.hpp: "only boosting one time")
        if self._cached_grads is None:
            init = jnp.asarray(
                np.broadcast_to(self._init_scores[None, :],
                                (self.num_data, self.num_class)),
                jnp.float32,
            )
            s = init[:, 0] if self.num_class == 1 else init
            with jax.named_scope("lgbm.objective"):
                grad, hess = self.objective.get_gradients(s)
            if grad.ndim == 1:
                grad, hess = grad[:, None], hess[:, None]
            self._cached_grads = (grad, hess)
        return self._cached_grads

    def _objective_grads(self, s, iteration=None):
        # gradients always evaluated at the constant init score
        init = jnp.asarray(self._init_scores, jnp.float32)
        const = jnp.broadcast_to(init[None, :], (self.num_data, self.num_class))
        sc = const[:, 0] if self.num_class == 1 else const
        return super()._objective_grads(sc, iteration)

    def train_one_iter(self, custom_grad=None, custom_hess=None,
                       check_stop: bool = True) -> bool:
        # trees are unshrunk; scores hold the running *sum*, converted to an
        # average at eval time
        if custom_grad is None and self._supports_fused_step():
            return GBDT.train_one_iter(self, check_stop=check_stop)
        cfg = self.config
        self._save_rollback_state()
        grad, hess = (
            self._gradients()
            if custom_grad is None
            else (
                jnp.asarray(np.asarray(custom_grad).reshape(self.num_data, -1), jnp.float32),
                jnp.asarray(np.asarray(custom_hess).reshape(self.num_data, -1), jnp.float32),
            )
        )
        bag = self._bagging_mask(self.iter)
        new_trees = []
        for k in range(self.num_class):
            g3 = self._sample_g3(grad[:, k], hess[:, k], bag, self.iter)
            key = jax.random.fold_in(self._rng_key, self.iter * self.num_class + k)
            base_mask = jnp.asarray(self._tree_feature_mask())
            tree_dev, leaf_id, _ = self._grow(
                self._grow_binned, g3, base_mask, key, self._cegb_used)
            if self._cegb_enabled:
                self._cegb_used = self._update_cegb_state(
                    self._cegb_used, tree_dev, leaf_id)
            new_trees.append(self._finish_tree(tree_dev, leaf_id, k, shrinkage=1.0))
        self.iter += 1
        if custom_grad is None and check_stop:
            return all(int(t.num_leaves) <= 1 for t in new_trees)
        return False

    def _converted_pred(self, scores, objective):
        n_iter = max(self.iter, 1)
        init = jnp.asarray(self._init_scores[None, :], jnp.float32)
        raw = init + (scores.score - init) / n_iter
        s = raw[:, 0] if self.num_class == 1 else raw
        if objective is not None:
            s = objective.convert_output(s)
        return np.asarray(s, dtype=np.float64)

    def _raw_pred(self, scores):
        n_iter = max(self.iter, 1)
        init = jnp.asarray(self._init_scores[None, :], jnp.float32)
        raw = init + (scores.score - init) / n_iter
        s = raw[:, 0] if self.num_class == 1 else raw
        return np.asarray(s, dtype=np.float64)


def create_boosting(config: Config, train_set: BinnedDataset, **kw) -> GBDT:
    """reference: Boosting::CreateBoosting, src/boosting/boosting.cpp:37-44."""
    kind = config.boosting
    if getattr(train_set, "is_streaming", False) or config.stream_enable:
        # out-of-core row-block trainer (models/gbdt_stream.py): a block
        # cache streams from disk; stream_enable=true wraps resident data
        # into the same block path (bounded device working set)
        from .gbdt_stream import create_streaming_boosting

        return create_streaming_boosting(config, train_set, **kw)
    if kind in ("gbdt", "gbrt"):
        return GBDT(config, train_set, **kw)
    if kind == "dart":
        return DART(config, train_set, **kw)
    if kind == "goss":
        return GOSS(config, train_set, **kw)
    if kind in ("rf", "random_forest"):
        return RF(config, train_set, **kw)
    log_fatal(f"Unknown boosting type: {kind}")
