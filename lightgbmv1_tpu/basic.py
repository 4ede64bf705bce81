"""Dataset and Booster — the lightgbm-compatible Python API.

TPU-native re-design of the reference python-package core
(reference: ``python-package/lightgbm/basic.py`` — class Dataset :909 with
lazy construction and reference-alignment, class Booster :1930 with
``update`` :2315, custom-objective ``__boost`` :2381, ``predict`` :2816).

Where the reference marshals numpy through ctypes into C++, this package
keeps data in numpy/JAX arrays end to end; the Booster wraps the device
GBDT driver (models/gbdt.py) directly.
"""

from __future__ import annotations

import json
import os
from copy import deepcopy
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import numpy as np

from .config import Config
from .io.dataset import BinnedDataset, construct_phase
from .io.model_text import LoadedModel, dump_model_dict, model_from_string, model_to_string
from .io.parser import load_data_file
from .metrics import create_metrics
from .models.gbdt import GBDT, create_boosting
from .models.tree import HostTree
from .utils import fileio
from .utils.log import LightGBMError, log_fatal, log_info, log_warning


# rows * trees above which bulk prediction routes to the native C++
# predictor (below it the per-call pack/launch overhead beats the win)
_NATIVE_PREDICT_MIN_WORK = 500_000


class _IterObs:
    """Lazily bound per-iteration training telemetry (obs registry)."""

    __slots__ = ("hist", "count")

    def __init__(self):
        from .obs.metrics import default_registry

        reg = default_registry()
        self.hist = reg.histogram(
            "train_iteration_ms", "Wall time of one boosting iteration",
            buckets=(1, 2, 5, 10, 20, 50, 100, 200, 500, 1000, 2000,
                     5000, 10000, 60000))
        self.count = reg.counter(
            "train_iterations_total", "Boosting iterations completed")

    def observe(self, ms: float) -> None:
        self.hist.observe(ms)
        self.count.inc()


_obs_iter: Optional[_IterObs] = None


def _obs_iteration_metrics() -> _IterObs:
    global _obs_iter
    if _obs_iter is None:
        _obs_iter = _IterObs()
    return _obs_iter


def _is_scipy_sparse(data) -> bool:
    return type(data).__module__.split(".")[0] == "scipy" and hasattr(
        data, "tocsr")


def _to_2d_numpy(data, keep_float32: bool = False) -> np.ndarray:
    """``data`` as a 2-D float64 array; a float32 matrix stays as it is
    where the caller reads it a column at a time (binning widens each
    column itself, to the same values)."""
    if hasattr(data, "values") and not isinstance(data, np.ndarray):  # pandas
        data = data.values
    if hasattr(data, "toarray"):  # scipy sparse
        data = data.toarray()
    if (keep_float32 and isinstance(data, np.ndarray)
            and data.dtype == np.float32):
        arr = data
    else:
        arr = np.asarray(data, dtype=np.float64)
    if arr.ndim == 1:
        arr = arr.reshape(1, -1)
    return arr


def _objective_string(config: Config) -> str:
    """Objective line for the model file (reference gbdt.cpp ObjectiveName
    + per-objective ToString, e.g. 'binary sigmoid:1')."""
    obj = config.objective
    if obj == "binary":
        return f"binary sigmoid:{config.sigmoid:g}"
    if obj in ("multiclass", "multiclassova"):
        extra = f" sigmoid:{config.sigmoid:g}" if obj == "multiclassova" else ""
        return f"{obj} num_class:{config.num_class}{extra}"
    if obj == "lambdarank":
        return "lambdarank"
    if obj == "quantile":
        return f"quantile alpha:{config.alpha:g}"
    if obj == "huber":
        return f"huber alpha:{config.alpha:g}"
    if obj == "fair":
        return f"fair c:{config.fair_c:g}"
    if obj == "tweedie":
        return f"tweedie tweedie_variance_power:{config.tweedie_variance_power:g}"
    return obj


class Dataset:
    """Training data wrapper with lazy binning (reference basic.py:909)."""

    def __init__(
        self,
        data,
        label=None,
        reference: Optional["Dataset"] = None,
        weight=None,
        group=None,
        init_score=None,
        feature_name="auto",
        categorical_feature="auto",
        params: Optional[Dict[str, Any]] = None,
        free_raw_data: bool = False,
    ):
        self.params = dict(params or {})
        self.reference = reference
        self.free_raw_data = free_raw_data
        self._binned: Optional[BinnedDataset] = None
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature

        from .data.block_cache import is_block_cache

        if isinstance(data, (str, os.PathLike)) and is_block_cache(data):
            # sharded block cache (data/ subsystem): metadata + mappers
            # load resident, the binned row bulk streams per block during
            # training (models/gbdt_stream.py) — the out-of-core path
            from .data.streaming import StreamingDataset

            self._binned = StreamingDataset(str(data))
            self.data = None
            meta = self._binned.metadata
            label = meta.label if label is None else label
            weight = meta.weight if weight is None else weight
            group = meta.group if group is None else group
            init_score = meta.init_score if init_score is None else init_score
            self.feature_name = list(self._binned.feature_names)
        elif isinstance(data, (str, os.PathLike)) and \
                BinnedDataset.is_binary_file(str(data)):
            # binary dataset cache (reference LoadFromBinFile,
            # dataset_loader.cpp:273): skips parsing and binning entirely
            self._binned = BinnedDataset.load_binary(str(data))
            self.data = None
            meta = self._binned.metadata
            label = meta.label if label is None else label
            weight = meta.weight if weight is None else weight
            group = meta.group if group is None else group
            init_score = meta.init_score if init_score is None else init_score
            self.feature_name = list(self._binned.feature_names)
        elif isinstance(data, (str, os.PathLike)):
            cfg = Config.from_dict(self.params)
            if cfg.two_round and reference is None:
                # streaming two-pass load straight into bins (reference:
                # two_round=true, dataset_loader.cpp:208-235); valid sets
                # with a reference still use the in-memory path since they
                # must reuse the training bin mappers
                from .io.parser import load_two_round

                cat2 = []
                cat_named = []
                if categorical_feature not in ("auto", None):
                    cat_named = [c for c in categorical_feature
                                 if isinstance(c, str)]
                    cat2 = [int(c) for c in categorical_feature
                            if not isinstance(c, str)]
                if cat_named:
                    # name resolution needs the constructed header map; the
                    # in-memory path below handles it
                    log_warning(
                        "two_round with named categorical_feature columns "
                        "falls back to the in-memory loader")
                    binned = None
                else:
                    binned = load_two_round(str(data), cfg, cat2)
                if binned is not None:
                    self._binned = binned
                    self.data = None
                    meta = binned.metadata
                    label = meta.label if label is None else label
                    weight = meta.weight if weight is None else weight
                    group = meta.group if group is None else group
                    init_score = (meta.init_score if init_score is None
                                  else init_score)
                    self.feature_name = list(binned.feature_names)
            if self._binned is None:
                df = load_data_file(
                    str(data),
                    has_header=cfg.header,
                    label_column=cfg.label_column,
                    weight_column=cfg.weight_column,
                    group_column=cfg.group_column,
                    ignore_column=cfg.ignore_column,
                    num_threads=cfg.num_threads,
                    # initscore_filename describes the TRAINING data only;
                    # valid sets use valid_data_initscores (reference:
                    # config.h initscore_filename doc, application.cpp:90)
                    init_score_file=(cfg.initscore_filename
                                     if reference is None else ""),
                )
                self.data = df.X
                label = df.label if label is None else label
                weight = df.weight if weight is None else weight
                group = df.group if group is None else group
                init_score = getattr(df, "init_score", None) \
                    if init_score is None else init_score
                if df.feature_names and feature_name == "auto":
                    self.feature_name = df.feature_names
        elif _is_scipy_sparse(data):
            # kept sparse: construct() feeds the CSR triplets straight into
            # the EFB bundling path (reference: LGBM_DatasetCreateFromCSR)
            self.data = data.tocsr()
        elif data is not None:
            # a float64 copy of the caller's matrix, unless that is
            # float32 already (2.5 GB and as many seconds saved for 2.27M
            # x 137): a phase of its own
            with construct_phase("convert"):
                self.data = _to_2d_numpy(data, keep_float32=True)
        else:
            self.data = None

        self.label = None if label is None else np.asarray(label, dtype=np.float64).ravel()
        self.weight = None if weight is None else np.asarray(weight, dtype=np.float64).ravel()
        self.group = None if group is None else np.asarray(group, dtype=np.int64).ravel()
        self.init_score = None if init_score is None else np.asarray(init_score, dtype=np.float64)
        if self._binned is not None:
            # binary-cache path: explicit fields override the cached metadata
            if label is not None:
                self.set_label(self.label)
            if weight is not None:
                self.set_weight(self.weight)
            if group is not None:
                self.set_group(self.group)
            if init_score is not None:
                self.set_init_score(self.init_score)

    # ------------------------------------------------------------------
    @classmethod
    def from_binned(cls, binned: "BinnedDataset",
                    params: Optional[Dict[str, Any]] = None) -> "Dataset":
        """Wrap an ALREADY-binned :class:`BinnedDataset` (e.g. the
        distributed loader's process shard, ``parallel/dist_data.py``)
        in the Dataset surface the Booster consumes — no re-parse, no
        re-bin; ``construct()`` is a no-op."""
        ds = cls(None, params=params)
        ds._binned = binned
        return ds

    def construct(self) -> "Dataset":
        if self._binned is not None:
            return self
        if self.data is None:
            log_fatal("Cannot construct Dataset: raw data was freed")
        cfg = Config.from_dict(self.params)
        cat = []
        if self.categorical_feature not in ("auto", None):
            names = self._feature_names_list()
            for c in self.categorical_feature:
                if isinstance(c, str):
                    cat.append(names.index(c))
                else:
                    cat.append(int(c))
        ref_binned = self.reference.construct()._binned if self.reference is not None else None
        if _is_scipy_sparse(self.data):
            csr = self.data
            self._binned = BinnedDataset.from_csr(
                csr.indptr, csr.indices, csr.data,
                num_data=csr.shape[0], num_features=csr.shape[1],
                label=self.label,
                weight=self.weight,
                group=self.group,
                init_score=self.init_score,
                config=cfg,
                categorical_features=cat,
                feature_names=self._feature_names_list(),
                reference=ref_binned,
            )
        else:
            self._binned = BinnedDataset.from_numpy(
                self.data,
                label=self.label,
                weight=self.weight,
                group=self.group,
                init_score=self.init_score,
                config=cfg,
                categorical_features=cat,
                feature_names=self._feature_names_list(),
                reference=ref_binned,
            )
        if self.free_raw_data:
            self.data = None
        return self

    def _feature_names_list(self) -> Optional[List[str]]:
        if isinstance(self.feature_name, (list, tuple)):
            return list(self.feature_name)
        if self.data is not None:
            return [f"Column_{i}" for i in range(self.data.shape[1])]
        return None

    # ------------------------------------------------------------------
    def save_binary(self, filename: str) -> "Dataset":
        """Save the binned dataset cache (reference basic.py save_binary →
        Dataset::SaveBinaryFile)."""
        self.construct()
        self._binned.save_binary(str(filename))
        return self

    def save_block_cache(self, path: str,
                         block_rows: Optional[int] = None) -> "Dataset":
        """Write the sharded binary block cache (data/block_cache.py):
        parse-once, then train out-of-core from ``path`` with the
        row-block streaming trainer (``Dataset(path)`` streams it)."""
        from .data.block_cache import write_block_cache

        self.construct()
        cfg = Config.from_dict(self.params)
        if block_rows is None:
            block_rows = cfg.stream_block_rows
        write_block_cache(self._binned, str(path), block_rows=block_rows,
                          bin_layout=cfg.bin_layout)
        return self

    # ------------------------------------------------------------------
    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None) -> "Dataset":
        return Dataset(
            data, label=label, reference=self, weight=weight, group=group,
            init_score=init_score, params=params or self.params,
        )

    def set_label(self, label) -> "Dataset":
        self.label = np.asarray(label, dtype=np.float64).ravel()
        if self._binned is not None:
            self._binned.metadata.label = self.label.astype(np.float32)
        return self

    def set_weight(self, weight) -> "Dataset":
        self.weight = None if weight is None else np.asarray(weight, dtype=np.float64).ravel()
        if self._binned is not None:
            self._binned.metadata.weight = (
                None if weight is None else self.weight.astype(np.float32))
        return self

    def set_group(self, group) -> "Dataset":
        self.group = None if group is None else np.asarray(group, dtype=np.int64).ravel()
        if self._binned is not None:
            self._binned.metadata.set_group(self.group)
        return self

    def set_init_score(self, init_score) -> "Dataset":
        self.init_score = None if init_score is None else np.asarray(init_score, np.float64)
        if self._binned is not None:
            self._binned.metadata.init_score = self.init_score
        return self

    def set_field(self, field_name: str, data) -> "Dataset":
        return {
            "label": self.set_label,
            "weight": self.set_weight,
            "group": self.set_group,
            "init_score": self.set_init_score,
        }[field_name](data)

    def get_field(self, field_name: str):
        return {
            "label": self.label,
            "weight": self.weight,
            "group": self.group,
            "init_score": self.init_score,
        }[field_name]

    def get_label(self):
        return self.label

    def get_weight(self):
        return self.weight

    def get_group(self):
        return self.group

    def get_init_score(self):
        return self.init_score

    def num_data(self) -> int:
        if self._binned is not None:
            return self._binned.num_data
        return 0 if self.data is None else self.data.shape[0]

    def num_feature(self) -> int:
        if self._binned is not None:
            return self._binned.num_features
        return 0 if self.data is None else self.data.shape[1]

    def subset(self, used_indices, params=None) -> "Dataset":
        if self.data is None:
            log_fatal("Cannot subset: raw data was freed")
        idx = np.asarray(used_indices)
        sub = Dataset(
            self.data[idx],
            label=None if self.label is None else self.label[idx],
            weight=None if self.weight is None else self.weight[idx],
            init_score=None if self.init_score is None else self.init_score[idx],
            params=params or self.params,
            reference=self,
            feature_name=self.feature_name,
            categorical_feature=self.categorical_feature,
        )
        return sub


def _reference_capture_supported() -> bool:
    """Model-reference capture (obs/model.py) reads the raw score cache
    host-side; under multi-process training that array spans
    non-addressable devices and a single-rank read ABORTS inside the
    runtime rather than raising — so capture is a single-process
    feature until the multi-host collective capture lands."""
    try:
        import jax

        return jax.process_count() <= 1
    except Exception:  # noqa: BLE001 — no backend = no device arrays
        return True


class Booster:
    """Gradient boosting model handle (reference basic.py:1930)."""

    def __init__(
        self,
        params: Optional[Dict[str, Any]] = None,
        train_set: Optional[Dataset] = None,
        model_file: Optional[str] = None,
        model_str: Optional[str] = None,
        init_model: Optional[Union[str, "Booster"]] = None,
    ):
        self.params = dict(params or {})
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self._gbdt: Optional[GBDT] = None
        self._loaded: Optional[LoadedModel] = None
        self._loaded_str: Optional[str] = None   # source text of _loaded
                                                 # (checkpoint bundles
                                                 # re-embed it verbatim)
        self.train_set = train_set
        self._name_valid_sets: List[str] = []
        self._pred_objective = None
        # model-quality observability (ISSUE 14): the engine loop
        # appends metric curves here ({"dataset:metric": [values]});
        # capture_model_reference() caches its result
        self._metric_history: Dict[str, List[float]] = {}
        self._model_reference = None

        if train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError("train_set must be a Dataset")
            train_set.params = {**self.params, **train_set.params} \
                if train_set.params else dict(self.params)
            train_set.params.update(self.params)
            train_set.construct()
            self.config = Config.from_dict(self.params)
            init_raw = None
            if init_model is not None:
                # continued training (reference: CreateBoosting(type, file)
                # boosting.cpp:46+, init score from the old model's
                # prediction, application.cpp:90-93)
                if isinstance(init_model, Booster):
                    base_str = init_model.model_to_string()
                else:
                    with fileio.open_file(init_model) as fh:
                        base_str = fh.read()
                self._loaded = model_from_string(base_str)
                self._loaded_str = base_str
                if self._loaded.average_output:
                    log_fatal("Continued training from an RF (average_output)"
                              " model is not supported")
                init_raw = self._loaded_raw_scores(train_set,
                                                   "continued training")
                if train_set.init_score is not None:
                    # reference stacks the loaded model's scores ON TOP of
                    # the dataset init_score (ScoreUpdater ctor + AddScore)
                    init_raw = init_raw + np.asarray(
                        train_set.init_score, np.float64).reshape(
                            init_raw.shape[0], -1)
            self._gbdt = create_boosting(self.config, train_set._binned,
                                         init_raw_scores=init_raw)
        elif model_file is not None:
            with fileio.open_file(model_file) as fh:
                self._init_from_string(fh.read())
        elif model_str is not None:
            self._init_from_string(model_str)
        else:
            raise TypeError("Need at least one of train_set, model_file, model_str")

    # ------------------------------------------------------------------
    def _init_from_string(self, s: str) -> None:
        self._loaded = model_from_string(s)
        self._loaded_str = s
        params = {"objective": self._loaded.objective}
        if self._loaded.num_class > 1:
            params["num_class"] = self._loaded.num_class
        op = self._loaded.objective_params
        if "sigmoid" in op:
            params["sigmoid"] = float(op["sigmoid"])
        if "alpha" in op:
            params["alpha"] = float(op["alpha"])
        self.config = Config.from_dict(params)
        from .objectives import create_objective

        self._pred_objective = create_objective(self.config)

    # ------------------------------------------------------------------
    def add_valid(self, data: Dataset, name: str) -> "Booster":
        if self._gbdt is None:
            log_fatal("Cannot add validation data to a loaded model")
        data.construct()
        init_raw = None
        if self._loaded is not None and self._loaded.trees:
            # continued training: valid scores resume from the loaded trees
            init_raw = self._loaded_raw_scores(data, "continued training")
            if data.init_score is not None:
                init_raw = init_raw + np.asarray(
                    data.init_score, np.float64).reshape(init_raw.shape[0], -1)
        self._gbdt.add_valid(data._binned, name, init_raw=init_raw)
        self._name_valid_sets.append(name)
        return self

    def _loaded_raw_scores(self, dataset: Dataset, why: str) -> np.ndarray:
        """Raw predictions of the loaded trees on a dataset's raw features."""
        X = dataset.data
        if X is None:
            log_fatal(f"Raw data is required for {why} "
                      "(dataset was constructed with free_raw_data=True)")
        K = max(self._loaded.num_tree_per_iteration, 1)
        raw = np.zeros((X.shape[0], K), dtype=np.float64)
        for i, t in enumerate(self._loaded.trees):
            raw[:, i % K] += t.predict(X)
        return raw

    def update(self, train_set: Optional[Dataset] = None,
               fobj: Optional[Callable] = None) -> bool:
        """One boosting iteration; returns True when no further splits are
        possible (reference basic.py:2315 update / __boost :2381)."""
        from .obs import trace

        if self._gbdt is None:
            log_fatal("Cannot update a loaded model")
        if train_set is not None:
            log_fatal("Resetting train_set is not supported")
        # one tree = one ``train.iteration`` span (a profiler annotation
        # always, a ring event when the tracer is armed) whose closing
        # writes the tree's always-on record; its phases are the
        # ``train.*`` children opened inside ``train_one_iter``
        first_tree = len(self._gbdt._device_trees)
        with trace.iteration_span(self._gbdt.iter) as it:
            if fobj is None:
                finished = self._gbdt.train_one_iter()
            else:
                preds = self._gbdt.raw_train_scores()
                if self._gbdt.num_class == 1:
                    preds = preds[:, 0]
                grad, hess = fobj(preds, self.train_set)
                finished = self._gbdt.train_one_iter(
                    custom_grad=np.asarray(grad), custom_hess=np.asarray(hess)
                )
            # finite_guard=warn|raise: one scalar device read per iteration
            # boundary; off (default) costs nothing (models/gbdt.py)
            self._gbdt.check_finite_boundary()
            it.renewed = self._gbdt.renewed_count_later(first_tree)
            it.rounds, it.hist_skipped = self._gbdt.round_counts_later()
        # observability: per-iteration wall into the shared registry
        # (always on — one histogram observe vs a ms-scale iteration)
        _obs_iteration_metrics().observe(it.dur_ns / 1e6)
        return finished

    def rollback_one_iter(self) -> "Booster":
        if self._gbdt is not None:
            self._gbdt.rollback_one_iter()
        return self

    def current_iteration(self) -> int:
        n = 0
        if self._loaded is not None:
            n += self._loaded.num_iterations
        if self._gbdt is not None:
            n += self._gbdt.iter
        return n

    def num_trees(self) -> int:
        n = 0
        if self._loaded is not None:
            n += len(self._loaded.trees)
        if self._gbdt is not None:
            n += self._gbdt.num_trees()
        return n

    def num_model_per_iteration(self) -> int:
        if self._gbdt is not None:
            return self._gbdt.num_model_per_iteration
        return self._loaded.num_tree_per_iteration

    def num_feature(self) -> int:
        if self._gbdt is not None:
            return self._gbdt.train_set.num_features
        return self._loaded.max_feature_idx + 1

    def feature_name(self) -> List[str]:
        if self._gbdt is not None:
            return list(self._gbdt.train_set.feature_names)
        return list(self._loaded.feature_names)

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        self.params.update(params)
        if self._gbdt is not None:
            self._gbdt.config.update(params)
        return self

    # ------------------------------------------------------------------
    def eval_train(self, feval=None):
        out = [("training",) + tuple(r[1:]) for r in self._gbdt.eval_train()]
        return self._add_feval(out, feval, "training", self._gbdt.raw_train_scores(),
                               self.train_set)

    def eval_valid(self, feval=None):
        results = self._gbdt.eval_valid()
        out = list(results)
        if feval is not None:
            for i, name in enumerate(self._name_valid_sets):
                scores = np.asarray(self._gbdt._valid_scores[i].score)
                vs = self._gbdt._valid_sets[i]
                out = self._add_feval(out, feval, name, scores, vs)
        return out

    def _add_feval(self, out, feval, name, raw_scores, dataset):
        if feval is None:
            return out
        fevals = feval if isinstance(feval, (list, tuple)) else [feval]
        preds = raw_scores[:, 0] if raw_scores.shape[1] == 1 else raw_scores
        for f in fevals:
            res = f(preds, dataset)
            if isinstance(res, tuple):
                res = [res]
            for metric_name, value, hb in res:
                out.append((name, metric_name, value, hb))
        return out

    # ------------------------------------------------------------------
    def _all_trees(self) -> List[HostTree]:
        trees: List[HostTree] = []
        if self._loaded is not None:
            trees.extend(self._loaded.trees)
        if self._gbdt is not None:
            trees.extend(self._gbdt.materialize_host_trees())
        return trees

    def predict(
        self,
        data,
        start_iteration: int = 0,
        num_iteration: Optional[int] = None,
        raw_score: bool = False,
        pred_leaf: bool = False,
        pred_contrib: bool = False,
        **kwargs,
    ) -> np.ndarray:
        """Prediction on raw features (reference basic.py:2816 / Predictor)."""
        if _is_scipy_sparse(data) and data.shape[0] > 65536:
            # chunked densification bounds peak memory on wide-sparse input
            outs = [
                self.predict(data[i:i + 65536].toarray(),
                             start_iteration=start_iteration,
                             num_iteration=num_iteration,
                             raw_score=raw_score, pred_leaf=pred_leaf,
                             pred_contrib=pred_contrib, **kwargs)
                for i in range(0, data.shape[0], 65536)
            ]
            return np.concatenate(outs, axis=0)
        if isinstance(data, (str, os.PathLike)):
            df = load_data_file(str(data), is_predict=True)
            X = df.X
            # prediction files usually carry the label column like training
            # files do (reference Predictor convention); detect by column
            # count and strip it
            if X.shape[1] == self.num_feature() + 1:
                X = X[:, 1:]
        else:
            X = _to_2d_numpy(data)
        if X.shape[1] != self.num_feature():
            # reference predictor.hpp:170-174 / c_api predict shape guard
            disable = bool(kwargs.get(
                "predict_disable_shape_check",
                self.params.get("predict_disable_shape_check", False)))
            if not disable:
                from .utils.log import log_fatal

                log_fatal(
                    f"The number of features in data ({X.shape[1]}) is not "
                    f"the same as it was in training data "
                    f"({self.num_feature()}).\nYou can set "
                    f"``predict_disable_shape_check=true`` to discard this "
                    f"error, but please be aware what you are doing.")
        trees = self._all_trees()
        K = self.num_model_per_iteration()
        if num_iteration is None or num_iteration < 0:
            num_iteration = (self.best_iteration
                             if self.best_iteration and self.best_iteration > 0
                             else len(trees) // K)
        trees = trees[start_iteration * K: (start_iteration + num_iteration) * K]

        n = X.shape[0]
        es = bool(kwargs.get("pred_early_stop",
                             self.params.get("pred_early_stop", False)))
        es_freq = int(kwargs.get("pred_early_stop_freq",
                                 self.params.get("pred_early_stop_freq", 10)))
        es_margin = float(kwargs.get(
            "pred_early_stop_margin",
            self.params.get("pred_early_stop_margin", 10.0)))

        # device inference engine (models/predict.py): depth-stepped
        # all-trees walk / Pallas kernel / legacy scan pin, behind
        # predict_method; contrib and prediction-early-stop stay host-side
        method = str(kwargs.get("predict_method",
                                self.params.get("predict_method", "auto")))
        raw = None
        if method in ("depthwise", "pallas", "fused", "scan") and trees \
                and not pred_contrib and not (es and not raw_score):
            bp = self._device_predictor(trees, K, start_iteration, method,
                                        kwargs)
            if bp is not None:
                if pred_leaf:
                    return bp.predict_leaf(X)
                f64 = bool(kwargs.get(
                    "predict_f64_scores",
                    self.params.get("predict_f64_scores", False)))
                raw = np.asarray(bp.predict_raw(X, f64_exact=f64),
                                 np.float64)
                if raw.shape[1] != K:   # scan pin returns (N, 1)
                    raw = raw.reshape(n, K)

        if pred_leaf:
            out = np.stack([t.predict_leaf_index(X) for t in trees], axis=1)
            return out
        if pred_contrib:
            return self._predict_contrib(X, trees, K)

        if raw is not None:
            pass
        elif es and not raw_score:
            raw = np.zeros((n, K), dtype=np.float64)
            # reference: PredictionEarlyStopInstance
            # (src/boosting/prediction_early_stop.cpp:75) — every freq trees,
            # rows whose decision margin exceeds the threshold stop
            # accumulating further trees
            active = np.ones(n, dtype=bool)
            n_iters = len(trees) // K if K else 0
            for it in range(n_iters):
                idx = np.flatnonzero(active)
                if idx.size == 0:
                    break
                for k in range(K):
                    t = trees[it * K + k]
                    raw[idx, k] += t.predict(X[idx])
                if (it + 1) % es_freq == 0:
                    if K == 1:
                        margin = 2.0 * np.abs(raw[idx, 0])
                    else:
                        part = np.partition(raw[idx], K - 2, axis=1)
                        margin = part[:, K - 1] - part[:, K - 2]
                    active[idx[margin >= es_margin]] = False
        else:
            raw = np.zeros((n, K), dtype=np.float64)
            native = None
            if method == "native" or (
                    method != "host"
                    and n * len(trees) >= _NATIVE_PREDICT_MIN_WORK):
                # native C++ predictor (the reference Predictor role,
                # predictor.hpp:29-160): per-row walks over flattened
                # arrays, threaded; ~10x the vectorized numpy walk
                native = self._predict_raw_native(
                    X, trees, K, start_iteration)
            if native is not None:
                raw = native
            else:
                for i, t in enumerate(trees):
                    raw[:, i % K] += t.predict(X)
        # the boost-from-average constant lives inside tree leaf values
        # (AddBias, reference gbdt.cpp:381-383), so no base term is added
        from .models.gbdt import RF

        avg = (self._loaded.average_output if self._loaded is not None
               else isinstance(self._gbdt, RF))
        if avg and trees:
            raw = raw / (len(trees) // K)
        if raw_score:
            return raw[:, 0] if K == 1 else raw
        obj = self._gbdt.objective if self._gbdt is not None else self._pred_objective
        if obj is not None:
            converted = obj.convert_output(raw if K > 1 else raw[:, 0])
            return np.asarray(converted)
        return raw[:, 0] if K == 1 else raw

    def _predict_raw_native(self, X, trees, K, start_iteration=0):
        """Native bulk prediction; None -> numpy fallback.  The flattened
        ensemble pack is cached per (slice start, tree count, model
        version) — the version counter bumps on every ``iter`` move, and
        every in-place ensemble mutation (tree append, rollback
        truncation, DART drop-rescale of existing trees) happens inside an
        update/rollback that moves ``iter``; the slice start distinguishes
        same-length windows (start_iteration paging).  Tree object
        identity is deliberately NOT part of the key: host trees may be
        freshly materialized per call (id() would never hit) and CPython
        id() can alias after GC."""
        from .native import build_ensemble_pack, predict_ensemble

        key = (start_iteration, len(trees),
               self._gbdt.model_version if self._gbdt is not None else -1)
        cached = getattr(self, "_native_pred_cache", None)
        if cached is None or cached[0] != key:
            pack = build_ensemble_pack(trees, K)
            self._native_pred_cache = (key, pack)
        else:
            pack = cached[1]
        if pack is None or X.shape[1] <= pack["max_feat"]:
            # narrow X must fail loudly on the numpy path (IndexError),
            # never read out of bounds natively
            return None
        nt = int(self.params.get("num_threads", 0) or 0)
        return predict_ensemble(X, pack, num_threads=nt)

    def _device_predictor(self, trees, K, start_iteration, method, kwargs):
        """Device inference engine (models/predict.BatchPredictor), cached
        per (slice start, tree count, model version, method) — the same
        key discipline as the native pack cache: any ensemble mutation
        (update/rollback/DART drop-rescale) moves ``model_version`` and
        drops the predictor, its serving tables and its compiled-walk
        cache wholesale.  None -> host path, only for the predictor's
        own stated refusals (``ValueError``: a categorical model without
        raw category sets, scan with K>1); anything else — a device or
        compiler error — propagates instead of being answered from the
        host behind the caller's back."""
        key = (start_iteration, len(trees),
               self._gbdt.model_version if self._gbdt is not None else -1,
               method)
        cached = getattr(self, "_device_pred_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        from .models.predict import BatchPredictor

        def p(name, dflt):
            return kwargs.get(name, self.params.get(name, dflt))

        try:
            bp = BatchPredictor(
                trees, K, self.num_feature(), method=method,
                prebin=str(p("predict_prebin", "auto")),
                code_layout=str(p("predict_code_layout", "auto")),
                num_shards=int(p("predict_num_shards", 0)),
                bucket_min=int(p("predict_bucket_min", 256)),
                chunk_rows=int(p("predict_chunk_rows", 131072)),
                cache_entries=int(p("predict_cache_entries", 64)),
            )
        except ValueError as e:
            log_warning(f"device predict unavailable ({e}); using the "
                        "host path")
            bp = None
        self._device_pred_cache = (key, bp)
        return bp

    def refit(self, data, label, decay_rate: float = 0.9) -> "Booster":
        """Refit the existing model's leaf values on new data
        (reference: basic.py:2873 refit → GBDT::RefitTree gbdt.cpp:266-290 →
        FitByExistingTree; ``leaf_output = decay_rate * old +
        (1 - decay_rate) * new``).  Tree structures are kept; only outputs
        are re-estimated from the new data's gradients."""
        from copy import deepcopy

        from .objectives import create_objective

        X = _to_2d_numpy(data)
        y = np.asarray(label, dtype=np.float32).ravel()
        trees = [deepcopy(t) for t in self._all_trees()]
        if not trees:
            log_fatal("Cannot refit an empty model")
        K = self.num_model_per_iteration()
        cfg = getattr(self, "config", None) or Config.from_dict(self.params)
        obj = create_objective(cfg)
        if obj is None:
            raise LightGBMError("Cannot refit due to null objective function.")

        from .io.dataset import Metadata

        meta = Metadata()
        meta.label = y
        obj.init(meta, len(y))
        l1, l2 = cfg.lambda_l1, cfg.lambda_l2
        scores = np.zeros((len(y), K), dtype=np.float64)
        import jax

        for i, t in enumerate(trees):
            k = i % K
            s = scores[:, 0] if K == 1 else scores
            grad, hess = jax.device_get(obj.get_gradients(
                np.asarray(s, np.float32)))
            grad = np.asarray(grad).reshape(len(y), -1)[:, k]
            hess = np.asarray(hess).reshape(len(y), -1)[:, k]
            leaf = t.predict_leaf_index(X)
            for lf in range(t.num_leaves):
                rows = leaf == lf
                if not rows.any():
                    continue
                sg, sh = grad[rows].sum(), hess[rows].sum()
                thr = np.sign(sg) * max(abs(sg) - l1, 0.0)
                new_out = (-thr / (sh + l2)) * t.shrinkage
                t.leaf_value[lf] = (decay_rate * t.leaf_value[lf]
                                    + (1.0 - decay_rate) * new_out)
            scores[:, k] += t.leaf_value[leaf]

        new_booster = Booster.__new__(Booster)
        new_booster.params = dict(self.params)
        new_booster.best_iteration = -1
        new_booster.best_score = {}
        new_booster._gbdt = None
        new_booster.train_set = None
        new_booster._name_valid_sets = []
        new_booster._loaded_str = None
        if self._loaded is not None and self._gbdt is None:
            loaded = deepcopy(self._loaded)
        else:
            loaded = model_from_string(self.model_to_string())
        loaded.trees = trees
        new_booster._loaded = loaded
        new_booster.config = cfg
        new_booster._pred_objective = obj
        return new_booster

    def _predict_contrib(self, X, trees, K):
        """Exact TreeSHAP feature contributions (reference:
        Tree::PredictContrib tree.h:138, src/io/tree.cpp TreeSHAP); the
        last column per class is the expected value (base)."""
        from .models.treeshap import tree_shap

        n, F = X.shape
        out = np.zeros((n, K * (F + 1)), dtype=np.float64)
        for ti, t in enumerate(trees):
            k = ti % K
            contribs = tree_shap(t, X)
            out[:, k * (F + 1): k * (F + 1) + F + 1] += contribs
        return out[:, : F + 1] if K == 1 else out

    # ------------------------------------------------------------------
    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0) -> str:
        trees = self._all_trees()
        K = self.num_model_per_iteration()
        if num_iteration is None or num_iteration < 0:
            num_iteration = (self.best_iteration
                             if self.best_iteration and self.best_iteration > 0
                             else len(trees) // K)
        trees = trees[start_iteration * K: (start_iteration + num_iteration) * K]
        if self._gbdt is not None:
            cfg = self.config
            ds = self._gbdt.train_set
            feature_names = list(ds.feature_names)
            feature_infos = ds.feature_infos()
            objective_string = _objective_string(cfg)
            from .models.gbdt import RF

            average_output = isinstance(self._gbdt, RF)
            params = {
                "boosting": cfg.boosting, "objective": cfg.objective,
                "metric": ",".join(cfg.metric), "learning_rate": cfg.learning_rate,
                "num_leaves": cfg.num_leaves, "max_depth": cfg.max_depth,
                "min_data_in_leaf": cfg.min_data_in_leaf,
                "min_sum_hessian_in_leaf": cfg.min_sum_hessian_in_leaf,
                "bagging_fraction": cfg.bagging_fraction,
                "bagging_freq": cfg.bagging_freq,
                "feature_fraction": cfg.feature_fraction,
                "lambda_l1": cfg.lambda_l1, "lambda_l2": cfg.lambda_l2,
                "max_bin": cfg.max_bin, "seed": cfg.seed,
            }
        else:
            lm = self._loaded
            feature_names = lm.feature_names
            feature_infos = lm.feature_infos
            objective_string = lm.objective + "".join(
                f" {k}:{v}" for k, v in lm.objective_params.items())
            average_output = lm.average_output
            params = lm.parameters
        return model_to_string(
            trees,
            objective_string=objective_string,
            num_class=self.config.num_class if self._gbdt is not None else self._loaded.num_class,
            num_tree_per_iteration=K,
            feature_names=feature_names,
            feature_infos=feature_infos,
            average_output=average_output,
            parameters=params,
            # reference: saved_feature_importance_type selects split counts
            # (0) or total gains (1) in the model's importance block
            # (application.cpp:204, gbdt.cpp:779-800)
            importance_type=(self.config.saved_feature_importance_type
                             if self._gbdt is not None else 0),
        )

    def save_model(self, filename, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> "Booster":
        # crash-consistent by construction: tmp+fsync+rename, so a kill
        # mid-save leaves the previous model file intact instead of a
        # truncated one (the pre-PR-6 snapshot failure mode)
        fileio.atomic_write_text(
            str(filename), self.model_to_string(num_iteration,
                                                start_iteration),
            site=str(filename))
        return self

    # ------------------------------------------------------------------
    def capture_model_reference(self, score_bins: Optional[int] = None):
        """Training-time reference capture (ISSUE 14, obs/model.py):
        one pass over the already-binned training matrix (streamed per
        block on the out-of-core path) records per-feature
        bin-occupancy histograms over the ensemble's own BinMapper
        bins, NaN rates, and the raw training-score distribution.
        Returns the :class:`~lightgbmv1_tpu.obs.model.ModelReference`
        the serving side re-bins sampled requests against (and caches
        it on the Booster for checkpoint/publish plumbing)."""
        if self._gbdt is None:
            log_fatal("capture_model_reference() requires a training "
                      "Booster")
        from .obs.model import capture_reference

        if score_bins is None:
            score_bins = self.config.drift_score_bins
        self._model_reference = capture_reference(
            self._gbdt.train_set,
            np.asarray(self._gbdt.raw_train_scores()),
            score_bins=score_bins)
        return self._model_reference

    def quality_snapshot(self, top_k: int = 8) -> Dict:
        """Trainer quality telemetry (obs/model.py): per-iteration
        split-gain / leaf / depth aggregates, gain+split feature
        importance and the recorded train/valid metric curves —
        computed after the fact from host trees, never perturbing the
        training loop."""
        from .obs.model import quality_snapshot

        return quality_snapshot(self, top_k=top_k)

    # ------------------------------------------------------------------
    def save_checkpoint(self, path, write_file: bool = True,
                        with_reference: bool = True) -> "Booster":
        """Write a crash-consistent full-trainer-state bundle
        (io/checkpoint.py): model text + score caches + RNG/bagging/DART
        state + iteration counter, atomically.  A training run resumed
        from this bundle (:meth:`resume_from_checkpoint`) continues
        BIT-EXACTLY — the final model text matches the uninterrupted
        run's byte for byte (tests/test_checkpoint.py).

        Under multi-process training the state capture is a COLLECTIVE
        (cross-process score caches are gathered): every rank must call
        this in lockstep, with ``write_file=False`` on the non-writing
        ranks (parallel/elastic_worker.py — one bundle, rank 0's)."""
        if self._gbdt is None:
            log_fatal("save_checkpoint() requires a training Booster")
        from .io.checkpoint import write_checkpoint

        manifest, arrays = self._gbdt.capture_state()
        manifest["num_trees_total"] = self.num_trees()
        if write_file:
            ref_bytes = b""
            if with_reference and _reference_capture_supported():
                # the bundle carries the training reference (ISSUE 14)
                # so a resumed/served model keeps its drift baseline;
                # capture is host-side only (no collective), which is
                # why it runs on the WRITING rank alone — and why it is
                # SKIPPED under multi-process training: reading the
                # cross-process score cache from one rank aborts inside
                # the runtime (not a catchable Python error), and a
                # collective capture belongs to the multi-host item
                try:
                    ref_bytes = self.capture_model_reference().to_bytes()
                except Exception as e:  # noqa: BLE001 — e.g. sparse
                    # bundle-only datasets keep no per-feature matrix
                    log_warning(f"checkpoint: reference capture skipped "
                                f"({type(e).__name__}: {e})")
            write_checkpoint(str(path), manifest, arrays,
                             model_text=self.model_to_string(),
                             base_model_text=(self._loaded_str
                                              if self._loaded is not None
                                              else "") or "",
                             reference_bytes=ref_bytes)
        return self

    def resume_from_checkpoint(self, path_or_bundle) -> "Booster":
        """Restore a bundle into this FRESH training Booster (same data,
        same config, valid sets already attached).  Accepts a path or a
        pre-loaded ``io.checkpoint.load_checkpoint`` dict.  The bundle is
        fully validated (digests + ``validate_host_tree`` on the model
        text) before any state is touched; raises ``CheckpointError``
        otherwise."""
        if self._gbdt is None:
            log_fatal("resume_from_checkpoint() requires a training "
                      "Booster (construct with train_set=...)")
        from .io.checkpoint import load_checkpoint
        from .io.model_text import model_from_string

        bundle = (path_or_bundle
                  if isinstance(path_or_bundle, dict)
                  else load_checkpoint(str(path_or_bundle)))
        base = bundle.get("base_model_text", "")
        if base and self._loaded is None:
            # the checkpointed run itself continued from an input_model:
            # restore the loaded-tree prefix so tree indexing matches
            self._loaded = model_from_string(base)
            self._loaded_str = base
        self._gbdt.restore_state(bundle["manifest"], bundle["arrays"])
        return self

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> Dict:
        trees = self._all_trees()
        K = self.num_model_per_iteration()
        if num_iteration is None or num_iteration < 0:
            num_iteration = len(trees) // K
        trees = trees[start_iteration * K: (start_iteration + num_iteration) * K]
        if self._gbdt is not None:
            ds = self._gbdt.train_set
            names, infos = list(ds.feature_names), ds.feature_infos()
            objective_string = _objective_string(self.config)
            num_class = self.config.num_class
        else:
            names, infos = self._loaded.feature_names, self._loaded.feature_infos
            objective_string = self._loaded.objective
            num_class = self._loaded.num_class
        return dump_model_dict(
            trees, objective_string=objective_string, num_class=num_class,
            num_tree_per_iteration=K, feature_names=names, feature_infos=infos,
        )

    # ------------------------------------------------------------------
    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        trees = self._all_trees()
        K = self.num_model_per_iteration()
        if iteration is not None and iteration >= 0:
            trees = trees[: iteration * K]
        F = self.num_feature()
        out = np.zeros(F, dtype=np.float64)
        for t in trees:
            for i in range(t.num_leaves - 1):
                f = t.split_feature[i]
                if importance_type == "split":
                    out[f] += 1
                else:
                    out[f] += t.split_gain[i]
        if importance_type == "split":
            return out.astype(np.int64)
        return out

    def __copy__(self):
        return self

    def free_dataset(self) -> "Booster":
        return self

    def free_network(self) -> "Booster":
        return self
