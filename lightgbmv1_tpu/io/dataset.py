"""Binned dataset: the device-resident training representation.

TPU-native re-design of the reference Dataset/Metadata
(reference: ``include/LightGBM/dataset.h:332-713`` class Dataset,
``dataset.h:40-248`` class Metadata, ``src/io/dataset.cpp``).

Representation decisions (SURVEY.md §7):

* Binned matrix lives in HBM as ``(num_features, num_data)`` integer bins
  (uint8 when max bin count <= 256 else int16 — the analog of the reference's
  ``DenseBin<uint8_t>/DenseBin<uint16_t>`` family, src/io/dense_bin.hpp:52).
  There are no feature groups, no EFB, no sparse bins: density is what the
  MXU wants.
* Per-feature bin metadata is carried as small arrays (num_bins, missing
  type, nan/zero/default bin) consumed by the jitted split finder.
* The histogram-construction dispatch (the reference's col-wise vs row-wise
  auto-benchmark, dataset.cpp:590-684) becomes the ``hist_method`` config
  switch: scatter-add (CPU oracle) vs one-hot matmul vs Pallas kernel.
"""

from __future__ import annotations

import contextlib
import math
import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Union

import numpy as np

from ..config import Config
from ..utils.log import log_fatal, log_info, log_warning
from .binning import (
    BIN_CATEGORICAL,
    BIN_NUMERICAL,
    MISSING_NAN,
    MISSING_NONE,
    MISSING_ZERO,
    BinMapper,
)


def _next_pow2(x: int) -> int:
    return 1 << max(0, (x - 1)).bit_length()


# Columns are found and binned one by one in NumPy calls that release the
# GIL (sort, searchsorted, the dtype copies), so a pool of threads takes
# them side by side.  Below this many rows a column the interpreter's share
# of a column (the walk over at most ``max_bin`` boundaries) is the larger
# one, and threads only queue for the GIL.
_POOL_MIN_ROWS = 1 << 15
_POOL_THREADS = 8


def _map_columns(fn, columns: int, rows: int) -> list:
    """``[fn(j) for j in range(columns)]``, in threads where a column
    (``rows`` values) is long enough for a pool to pay."""
    workers = min(_POOL_THREADS, os.cpu_count() or 1, columns)
    if rows < _POOL_MIN_ROWS or workers < 2:
        return [fn(j) for j in range(columns)]
    with ThreadPoolExecutor(max_workers=workers,
                            thread_name_prefix="bin") as pool:
        return list(pool.map(fn, range(columns)))


def _apply_bins(mappers, X: np.ndarray, dtype=None) -> np.ndarray:
    """The (features, rows) bins of ``X`` under ``mappers``; ``dtype``
    None: uint8 where every mapper's bins fit."""
    if dtype is None:
        max_nb = max(m.num_bin for m in mappers) if mappers else 2
        dtype = np.uint8 if max_nb <= 256 else np.int16
    num_data = X.shape[0]
    binned = np.empty((len(mappers), num_data), dtype=dtype)

    def apply(j):
        binned[j] = mappers[j].value_to_bin(X[:, j])

    _map_columns(apply, len(mappers), num_data)
    return binned


@contextlib.contextmanager
def construct_phase(phase: str):
    """One phase of building a dataset (``convert``, where ``Dataset``
    copies the caller's matrix; ``sample`` / ``find_bins`` / ``apply_bins``
    / ``bundle``; ``place``, where a booster puts the bins on the
    device): a ``data.<phase>`` span in the profiler's host
    lane (obs/trace.py) and its seconds, always, in the registry's
    ``dataset_construct_seconds{phase=...}`` gauge, which adds up over
    the datasets a process builds."""
    from ..obs import trace
    from ..obs.metrics import default_registry

    with trace.bridged_span("data." + phase, "data") as sp:
        yield
    default_registry().gauge(
        "dataset_construct_seconds",
        "Seconds spent in each phase of dataset construction",
        label_names=("phase",)).labels(phase=phase).inc(sp.dur_ns / 1e9)


@dataclass
class Metadata:
    """Labels, weights, query boundaries, init scores
    (reference: class Metadata, include/LightGBM/dataset.h:40-248)."""

    label: Optional[np.ndarray] = None
    weight: Optional[np.ndarray] = None
    group: Optional[np.ndarray] = None          # per-query sizes
    query_boundaries: Optional[np.ndarray] = None  # cumulative, len num_queries+1
    init_score: Optional[np.ndarray] = None
    valid_rows: Optional[np.ndarray] = None     # bool mask: False marks the
                                                # phantom pad rows of process-
                                                # sharded datasets; None =
                                                # every row is real

    def set_group(self, group: Optional[np.ndarray]) -> None:
        if group is None:
            self.group = None
            self.query_boundaries = None
            return
        group = np.asarray(group, dtype=np.int64).ravel()
        self.group = group
        self.query_boundaries = np.concatenate([[0], np.cumsum(group)])

    def num_queries(self) -> int:
        return 0 if self.group is None else len(self.group)


class BinnedDataset:
    """Feature-binned training data + metadata.

    ``binned``: (num_features, num_data) np.uint8/np.int16 — bin indices.
    """

    def __init__(
        self,
        binned: Optional[np.ndarray],
        bin_mappers: List[BinMapper],
        metadata: Metadata,
        feature_names: Optional[List[str]] = None,
        max_bin: int = 255,
        num_data: Optional[int] = None,
    ):
        self.binned = binned          # (F, N) dense bins; None for the
                                      # sparse-input path (bundled only)
        self.bundled = None           # (BF, N) EFB matrix (io/bundle.py)
        self.bundle_layout = None
        self.bin_mappers = bin_mappers
        self.metadata = metadata
        self.num_features = len(bin_mappers)
        self.num_data = binned.shape[1] if binned is not None else num_data
        self.max_bin = max_bin
        self.feature_names = feature_names or [
            f"Column_{i}" for i in range(self.num_features)
        ]
        self._build_feature_meta()

    # ------------------------------------------------------------------
    @property
    def train_matrix(self) -> np.ndarray:
        """The matrix the trainer uploads: the EFB-bundled columns when
        bundling applied, else the plain (F, N) binned matrix."""
        return self.bundled if self.bundled is not None else self.binned

    def bundle_features(self, config: Config,
                        reference: Optional["BinnedDataset"] = None) -> None:
        """Apply Exclusive Feature Bundling (reference: enable_bundle,
        Dataset::Construct -> FindGroups/FastFeatureBundling,
        src/io/dataset.cpp:97-315).  Valid sets reuse the training layout."""
        from .bundle import apply_bundles_dense, maybe_bundle

        if self.binned is None:
            return  # sparse path bundles at construction time
        if reference is not None:
            if reference.bundle_layout is not None:
                self.bundle_layout = reference.bundle_layout
                self.bundled = apply_bundles_dense(
                    self.binned, self.zero_bins, self.bundle_layout)
            return
        bundled, layout = maybe_bundle(
            self.binned, self.zero_bins, self.num_bins,
            max_conflict_rate=config.max_conflict_rate)
        if layout is not None:
            self.bundled = bundled
            self.bundle_layout = layout

    @property
    def padded_bundle_bin(self) -> int:
        assert self.bundle_layout is not None
        return max(8, _next_pow2(int(self.bundle_layout.bundle_nbins.max())))

    # ------------------------------------------------------------------
    def _build_feature_meta(self) -> None:
        F = self.num_features
        self.num_bins = np.array([m.num_bin for m in self.bin_mappers], dtype=np.int32)
        self.missing_types = np.array(
            [m.missing_type for m in self.bin_mappers], dtype=np.int32
        )
        self.nan_bins = np.array([m.nan_bin for m in self.bin_mappers], dtype=np.int32)
        self.zero_bins = np.array([m.zero_bin for m in self.bin_mappers], dtype=np.int32)
        self.default_bins = np.array(
            [m.default_bin for m in self.bin_mappers], dtype=np.int32
        )
        self.is_categorical = np.array(
            [m.bin_type == BIN_CATEGORICAL for m in self.bin_mappers], dtype=bool
        )
        self.is_trivial = np.array([m.is_trivial for m in self.bin_mappers], dtype=bool)
        # padded bin-axis size for histogram arrays (TPU lane alignment)
        max_nb = int(self.num_bins.max()) if F else 2
        self.num_total_bin = max(2, max_nb)
        self.padded_bin = max(8, _next_pow2(self.num_total_bin))

    # ------------------------------------------------------------------
    @classmethod
    def from_numpy(
        cls,
        X: np.ndarray,
        label: Optional[np.ndarray] = None,
        weight: Optional[np.ndarray] = None,
        group: Optional[np.ndarray] = None,
        init_score: Optional[np.ndarray] = None,
        config: Optional[Config] = None,
        categorical_features: Optional[Sequence[int]] = None,
        feature_names: Optional[List[str]] = None,
        reference: Optional["BinnedDataset"] = None,
        bin_finder=None,
    ) -> "BinnedDataset":
        """Build a binned dataset from a dense float matrix (rows, features).

        ``reference``: reuse another dataset's bin mappers (validation sets
        must share the training bins — reference basic.py Dataset reference
        alignment semantics).
        ``bin_finder``: optional callable(list-of-sample-arrays, config) ->
        list[BinMapper] used by the distributed loader to sync mappers.
        """
        config = config or Config()
        X = np.asarray(X)
        if X.ndim != 2:
            raise ValueError("X must be 2-D (rows, features)")
        num_data, num_features = X.shape
        categorical = set(categorical_features or [])

        if reference is not None:
            mappers = reference.bin_mappers
            feature_names = feature_names or reference.feature_names
        else:
            with construct_phase("sample"):
                # sampling (reference: bin_construct_sample_cnt, dataset_loader.cpp:823)
                sample_cnt = min(num_data, config.bin_construct_sample_cnt)
                rng = np.random.RandomState(config.data_random_seed)
                if sample_cnt < num_data:
                    sample_idx = rng.choice(num_data, size=sample_cnt, replace=False)
                else:
                    sample_idx = np.arange(num_data)
                max_bins = list(config.max_bin_by_feature) or [config.max_bin] * num_features
                if len(max_bins) != num_features:
                    log_fatal("max_bin_by_feature length must equal number of features")

                def sample_of(j):
                    return np.asarray(X[sample_idx, j], dtype=np.float64)

                # a finder that syncs mappers takes every column's sample
                # at once; otherwise a column's is drawn where it is
                # binned, and 8 B x sample x columns are never held
                samples = ([sample_of(j) for j in range(num_features)]
                           if bin_finder is not None else None)
            with construct_phase("find_bins"):
                if bin_finder is not None:
                    mappers = bin_finder(samples, sample_cnt, max_bins, categorical,
                                         config, num_data)
                else:
                    from .binning import get_forced_bins

                    forced = get_forced_bins(config.forcedbins_filename,
                                             num_features, categorical)

                    def find(j):
                        return BinMapper.find_bin(
                            sample_of(j),
                            total_sample_cnt=sample_cnt,
                            max_bin=max_bins[j],
                            min_data_in_bin=config.min_data_in_bin,
                            bin_type=BIN_CATEGORICAL if j in categorical else BIN_NUMERICAL,
                            use_missing=config.use_missing,
                            zero_as_missing=config.zero_as_missing,
                            forced_bounds=forced[j],
                            pre_filter=config.feature_pre_filter,
                            filter_cnt=int(config.min_data_in_leaf * sample_cnt
                                           / max(num_data, 1)),
                        )

                    mappers = _map_columns(find, num_features, sample_cnt)

        with construct_phase("apply_bins"):
            binned = _apply_bins(mappers, X)

        meta = Metadata()
        if label is not None:
            meta.label = np.asarray(label, dtype=np.float32).ravel()
            if len(meta.label) != num_data:
                log_fatal("label length mismatch")
        if weight is not None:
            meta.weight = np.asarray(weight, dtype=np.float32).ravel()
        if init_score is not None:
            meta.init_score = np.asarray(init_score, dtype=np.float64)
        meta.set_group(group)
        ds = cls(binned, mappers, meta, feature_names, max_bin=config.max_bin)
        n_used = int((~ds.is_trivial).sum())
        log_info(
            f"Constructed binned dataset: {num_data} rows, {num_features} features "
            f"({n_used} informative), max {ds.num_total_bin} bins"
        )
        if config.enable_bundle:
            with construct_phase("bundle"):
                ds.bundle_features(config, reference=reference)
        return ds

    # ------------------------------------------------------------------
    @classmethod
    def from_csr(
        cls,
        indptr: np.ndarray,
        indices: np.ndarray,
        values: np.ndarray,
        num_data: int,
        num_features: int,
        label=None,
        weight=None,
        group=None,
        init_score=None,
        config: Optional[Config] = None,
        categorical_features: Optional[Sequence[int]] = None,
        feature_names: Optional[List[str]] = None,
        reference: Optional["BinnedDataset"] = None,
    ) -> "BinnedDataset":
        """Build from CSR triplets WITHOUT materializing the dense (F, N)
        matrix — the wide-sparse input path (reference:
        ``LGBM_DatasetCreateFromCSR`` src/c_api.cpp + sparse push into
        FeatureGroups).  Sampling uses the sparse contract of
        ``BinMapper.find_bin`` (absent entries are implicit zeros), and the
        training representation is built directly as EFB bundle columns
        (io/bundle.py), so peak memory is O(nnz + num_bundles * num_data).
        """
        from .bundle import BundleLayout, apply_bundles_csr, find_bundles

        config = config or Config()
        indptr = np.asarray(indptr, np.int64)
        indices = np.asarray(indices, np.int32)
        values = np.asarray(values, np.float64)
        categorical = set(categorical_features or [])
        rows = np.repeat(np.arange(num_data), np.diff(indptr))

        if reference is not None:
            mappers = reference.bin_mappers
            feature_names = feature_names or reference.feature_names
        else:
            with construct_phase("sample"):
                sample_cnt = min(num_data, config.bin_construct_sample_cnt)
                rng = np.random.RandomState(config.data_random_seed)
                samp = (rng.choice(num_data, size=sample_cnt, replace=False)
                        if sample_cnt < num_data else np.arange(num_data))
                in_sample = np.zeros(num_data, bool)
                in_sample[samp] = True
                sel = in_sample[rows]
                f_sel, v_sel = indices[sel], values[sel]
                order = np.argsort(f_sel, kind="stable")
                f_sorted, v_sorted = f_sel[order], v_sel[order]
                starts = np.searchsorted(f_sorted, np.arange(num_features + 1))
                max_bins = (list(config.max_bin_by_feature)
                            or [config.max_bin] * num_features)
                if len(max_bins) != num_features:
                    log_fatal("max_bin_by_feature length must equal number of "
                              "features")
            with construct_phase("find_bins"):
                from .binning import get_forced_bins

                forced = get_forced_bins(config.forcedbins_filename,
                                         num_features, categorical)
                mappers = [
                    BinMapper.find_bin(
                        v_sorted[starts[j]:starts[j + 1]],
                        total_sample_cnt=sample_cnt,
                        max_bin=max_bins[j],
                        min_data_in_bin=config.min_data_in_bin,
                        bin_type=(BIN_CATEGORICAL if j in categorical
                                  else BIN_NUMERICAL),
                        use_missing=config.use_missing,
                        zero_as_missing=config.zero_as_missing,
                        forced_bounds=forced[j],
                        pre_filter=config.feature_pre_filter,
                        filter_cnt=int(config.min_data_in_leaf * sample_cnt
                                       / max(num_data, 1)),
                    )
                    for j in range(num_features)
                ]

        meta = Metadata()
        if label is not None:
            meta.label = np.asarray(label, dtype=np.float32).ravel()
            if len(meta.label) != num_data:
                log_fatal("label length mismatch")
        if weight is not None:
            meta.weight = np.asarray(weight, dtype=np.float32).ravel()
        if init_score is not None:
            meta.init_score = np.asarray(init_score, dtype=np.float64)
        meta.set_group(group)

        ds = cls(None, mappers, meta, feature_names,
                 max_bin=config.max_bin, num_data=num_data)

        # bin the non-zero entries feature-by-feature (host, vectorized via
        # one stable sort over the nnz instead of F passes)
        with construct_phase("apply_bins"):
            bin_values = np.zeros(len(values), np.int32)
            order_all = np.argsort(indices, kind="stable")
            starts_all = np.searchsorted(indices[order_all],
                                         np.arange(num_features + 1))
            for j in range(num_features):
                seg = order_all[starts_all[j]:starts_all[j + 1]]
                if len(seg):
                    bin_values[seg] = mappers[j].value_to_bin(values[seg])

        with construct_phase("bundle"):
            if reference is not None and reference.bundle_layout is not None:
                layout = reference.bundle_layout
            elif reference is not None:
                # unbundled reference (e.g. dense training data that found no
                # exclusivity): identity bundles keep bundle bins == original
                # bins so the matrices stay directly comparable
                layout = BundleLayout(
                    bundle_of=np.arange(num_features, dtype=np.int32),
                    offset=np.zeros(num_features, np.int32),
                    is_bundled=np.zeros(num_features, bool),
                    bundle_nbins=np.asarray(ds.num_bins, np.int32),
                )
            else:
                # conflict masks from the sampled non-zero pattern
                sample_cnt_c = min(num_data, 32768)
                rng2 = np.random.RandomState(config.data_random_seed + 1)
                samp2 = (rng2.choice(num_data, size=sample_cnt_c, replace=False)
                         if sample_cnt_c < num_data else np.arange(num_data))
                pos = np.full(num_data, -1, np.int64)
                pos[samp2] = np.arange(len(samp2))
                masks = np.zeros((num_features, len(samp2)), bool)
                r_pos = pos[rows]
                hit = (r_pos >= 0) & (bin_values != ds.zero_bins[indices])
                masks[indices[hit], r_pos[hit]] = True
                layout = (find_bundles(masks, ds.num_bins,
                                       config.max_conflict_rate)
                          if config.enable_bundle else None)
                if layout is None:
                    # no exclusivity to exploit: fall back to identity bundles
                    layout = BundleLayout(
                        bundle_of=np.arange(num_features, dtype=np.int32),
                        offset=np.zeros(num_features, np.int32),
                        is_bundled=np.zeros(num_features, bool),
                        bundle_nbins=np.asarray(ds.num_bins, np.int32),
                    )
            built = apply_bundles_csr(indptr, indices, bin_values,
                                      num_data, ds.zero_bins, layout)
            if not layout.is_bundled.any():
                # identity layout: bundle bins == original bins, so this IS the
                # plain dense binned matrix — record it as such (no decode path,
                # no spurious EFB incompatibility gates)
                ds.binned = built
            else:
                ds.bundle_layout = layout
                ds.bundled = built
        log_info(
            f"Constructed sparse binned dataset: {num_data} rows, "
            f"{num_features} features -> {layout.num_bundles} bundle "
            f"columns ({len(values)} non-zeros)")
        return ds

    # ------------------------------------------------------------------
    # Binary dataset cache (reference: Dataset::SaveBinaryFile dataset.h:473,
    # DatasetLoader::LoadFromBinFile dataset_loader.cpp:273) — skips
    # re-parsing and re-binning on subsequent runs.  Serialized with numpy's
    # npz container; the bin mappers ride as flat arrays via
    # BinMapper.to_arrays/from_arrays (also the wire format a distributed
    # bin-finding allgather would exchange, dataset_loader.cpp:913-996).
    # ------------------------------------------------------------------
    BINARY_MAGIC = "lightgbmv1_tpu.dataset.v1"
    # format_version 2 (PR 8): per-section SHA-256 digests + atomic write
    # — a torn or bit-rotted cache fails LOUDLY at load instead of
    # training on garbage.  Version-1 caches (no digests) still load,
    # with a warning.
    BINARY_FORMAT_VERSION = 2

    @staticmethod
    def _section_digest(arr: np.ndarray) -> str:
        import hashlib

        return hashlib.sha256(np.ascontiguousarray(arr).tobytes()
                              ).hexdigest()

    def save_binary(self, path: str) -> None:
        ubounds = [np.asarray(m.bin_upper_bound, np.float64)
                   for m in self.bin_mappers]
        cats = [np.asarray(m.bin_2_categorical, np.int64)
                for m in self.bin_mappers]
        scalars = np.array(
            [[m.num_bin, m.missing_type, m.bin_type, int(m.is_trivial)]
             for m in self.bin_mappers], dtype=np.int64)
        floats = np.array(
            [[m.sparse_rate, m.min_value, m.max_value]
             for m in self.bin_mappers], dtype=np.float64)
        meta = self.metadata
        import io as _io

        from ..utils.fileio import atomic_write_bytes

        fh = _io.BytesIO()          # keep the exact filename (savez appends
                                    # .npz to bare string paths)
        bl = self.bundle_layout
        sections = dict(
            magic=np.frombuffer(self.BINARY_MAGIC.encode(), dtype=np.uint8),
            # sparse-path datasets carry only the EFB bundle matrix;
            # load_binary reconstructs whichever representation was saved
            binned=(self.binned if self.binned is not None
                    else np.zeros((0, 0), np.uint8)),
            # dense-path bundles are re-derived on load from binned + the
            # layout (writing both matrices would double the cache size);
            # only the sparse path persists the bundle matrix itself
            bundled=(self.bundled
                     if self.bundled is not None and self.binned is None
                     else np.zeros((0, 0), np.uint8)),
            bundle_of=(bl.bundle_of if bl is not None
                       else np.zeros(0, np.int32)),
            bundle_offset=(bl.offset if bl is not None
                           else np.zeros(0, np.int32)),
            bundle_is_bundled=(bl.is_bundled if bl is not None
                               else np.zeros(0, bool)),
            bundle_nbins=(bl.bundle_nbins if bl is not None
                          else np.zeros(0, np.int32)),
            num_data=np.int64(self.num_data),
            max_bin=np.int64(self.max_bin),
            feature_names=np.array(self.feature_names),
            mapper_scalars=scalars,
            mapper_floats=floats,
            ubound_flat=np.concatenate(ubounds) if ubounds else np.zeros(0),
            ubound_offsets=np.cumsum([0] + [len(u) for u in ubounds]),
            cat_flat=np.concatenate(cats) if cats else np.zeros(0, np.int64),
            cat_offsets=np.cumsum([0] + [len(c) for c in cats]),
            label=meta.label if meta.label is not None else np.zeros(0),
            weight=meta.weight if meta.weight is not None else np.zeros(0),
            group=meta.group if meta.group is not None else np.zeros(0, np.int64),
            init_score=(meta.init_score if meta.init_score is not None
                        else np.zeros(0)),
        )
        digest_keys = sorted(k for k in sections if k != "magic")
        digests = np.array([self._section_digest(sections[k])
                            for k in digest_keys])
        np.savez_compressed(
            fh,
            format_version=np.int64(self.BINARY_FORMAT_VERSION),
            digest_keys=np.array(digest_keys),
            digest_values=digests,
            **sections,
        )
        # atomic (tmp+fsync+rename): a kill mid-save leaves the previous
        # cache intact; the ``file_write`` fault-injection seam rides along
        # (tests/test_stream_cache.py corrupts/tears through it)
        atomic_write_bytes(path, fh.getvalue(), site=path)
        log_info(f"Saved binary dataset cache to {path} "
                 f"(format v{self.BINARY_FORMAT_VERSION}, "
                 f"{len(digest_keys)} digest-pinned sections)")

    @classmethod
    def is_binary_file(cls, path: str) -> bool:
        import zipfile

        from ..utils.fileio import exists, open_file

        if not exists(path):
            return False
        try:
            with open_file(path, "rb") as fh:
                if not zipfile.is_zipfile(fh):
                    return False
                fh.seek(0)
                with np.load(fh, allow_pickle=False) as z:
                    return ("magic" in z and
                            bytes(z["magic"]).decode() == cls.BINARY_MAGIC)
        except Exception:
            return False

    @classmethod
    def load_binary(cls, path: str) -> "BinnedDataset":
        import zipfile

        from ..utils.fileio import open_file
        from ..utils.log import LightGBMError

        try:
            return cls._load_binary_inner(path, open_file)
        except LightGBMError:
            raise
        except (zipfile.BadZipFile, ValueError, OSError, KeyError,
                EOFError) as e:
            # a torn/truncated/corrupt cache must fail LOUDLY here — the
            # pre-v2 reader could hand back garbage arrays from a half
            # written zip
            log_fatal(f"{path}: torn or corrupt binary dataset cache "
                      f"({type(e).__name__}: {e}); re-create it with "
                      "save_binary")

    @classmethod
    def _load_binary_inner(cls, path: str, open_file) -> "BinnedDataset":
        with open_file(path, "rb") as fh, \
                np.load(fh, allow_pickle=False) as z:
            if bytes(z["magic"]).decode() != cls.BINARY_MAGIC:
                log_fatal(f"{path} is not a lightgbmv1_tpu binary dataset")
            version = (int(z["format_version"])
                       if "format_version" in z else 1)
            if version > cls.BINARY_FORMAT_VERSION:
                log_fatal(
                    f"{path}: binary cache format v{version} is newer "
                    f"than this build reads "
                    f"(v{cls.BINARY_FORMAT_VERSION}); re-create it with "
                    "save_binary")
            if version >= 2:
                keys = [str(s) for s in z["digest_keys"]]
                vals = [str(s) for s in z["digest_values"]]
                for k, want in zip(keys, vals):
                    if k not in z or cls._section_digest(z[k]) != want:
                        log_fatal(
                            f"{path}: binary cache section {k!r} digest "
                            "mismatch — torn or corrupt cache; re-create "
                            "it with save_binary")
            else:
                log_warning(f"{path}: legacy v1 binary cache (no section "
                            "digests); re-save to enable corruption "
                            "detection")
            scalars = z["mapper_scalars"]
            floats = z["mapper_floats"]
            uoff = z["ubound_offsets"]
            coff = z["cat_offsets"]
            mappers = []
            for j in range(scalars.shape[0]):
                mappers.append(BinMapper.from_arrays({
                    "bin_upper_bound": z["ubound_flat"][uoff[j]:uoff[j + 1]],
                    "num_bin": scalars[j, 0],
                    "missing_type": scalars[j, 1],
                    "bin_type": scalars[j, 2],
                    "is_trivial": scalars[j, 3],
                    "sparse_rate": floats[j, 0],
                    "min_value": floats[j, 1],
                    "max_value": floats[j, 2],
                    "bin_2_categorical": z["cat_flat"][coff[j]:coff[j + 1]],
                }))
            meta = Metadata()
            if z["label"].size:
                meta.label = z["label"].astype(np.float32)
            if z["weight"].size:
                meta.weight = z["weight"].astype(np.float32)
            if z["group"].size:
                meta.set_group(z["group"])
            if z["init_score"].size:
                meta.init_score = z["init_score"]
            binned = z["binned"] if z["binned"].size else None
            num_data = (int(z["num_data"]) if "num_data" in z
                        else z["binned"].shape[1])
            ds = cls(binned, mappers, meta,
                     feature_names=[str(s) for s in z["feature_names"]],
                     max_bin=int(z["max_bin"]), num_data=num_data)
            if "bundle_of" in z and z["bundle_of"].size:
                from .bundle import BundleLayout, apply_bundles_dense

                ds.bundle_layout = BundleLayout(
                    bundle_of=z["bundle_of"], offset=z["bundle_offset"],
                    is_bundled=z["bundle_is_bundled"],
                    bundle_nbins=z["bundle_nbins"])
                ds.bundled = (z["bundled"] if z["bundled"].size
                              else apply_bundles_dense(
                                  ds.binned, ds.zero_bins,
                                  ds.bundle_layout))
        log_info(f"Loaded binary dataset cache from {path}: "
                 f"{ds.num_data} rows, {ds.num_features} features")
        return ds

    # ------------------------------------------------------------------
    def bin_raw_features(self, X: np.ndarray) -> np.ndarray:
        """Bin new raw data with this dataset's mappers → (F, N) bins."""
        return _apply_bins(
            self.bin_mappers, np.asarray(X),
            self.binned.dtype if self.binned is not None
            else (np.uint8 if self.num_total_bin <= 256 else np.int16))

    def feature_infos(self) -> List[str]:
        return [m.feature_info_str() for m in self.bin_mappers]

    @property
    def num_used_features(self) -> int:
        return int((~self.is_trivial).sum())
