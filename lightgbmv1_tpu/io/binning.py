"""Feature binning (host-side preprocessing).

TPU-native re-design of the reference BinMapper (reference:
``src/io/bin.cpp`` — ``BinMapper::FindBin`` bin.cpp:325, ``GreedyFindBin``
bin.cpp:78, ``FindBinWithZeroAsOneBin`` bin.cpp:256, ``ValueToBin``
include/LightGBM/bin.h:457-495).

Differences from the reference, by design (SURVEY.md §7 "Hard parts"):

* **Full bins, no most-frequent-bin elision.**  The reference reserves bin 0
  for the most frequent bin per feature group and recovers it later via
  ``FixHistogram`` (dataset.cpp:1410).  On TPU the histogram for every bin is
  free (dense MXU matmul), so we store every bin explicitly and never need
  FixHistogram.  This also removes the per-group ``bin_offsets`` bookkeeping.
* **Exclusive feature bundling (EFB) lives one layer up.**  The binned
  layout is a dense ``(num_features, num_data)`` integer matrix; when EFB is
  enabled (``enable_bundle``), ``io/bundle.py`` merges mutually-exclusive
  sparse features into shared columns of that matrix AFTER binning
  (reference: dataset.cpp:97-235), so this module stays bundling-agnostic.

Semantics preserved: greedy equal-count bin boundary search on a sample,
zero-straddling bins, missing handling (None/Zero/NaN with a trailing NaN
bin), categorical binning by descending frequency, trivial-feature detection.
"""

from __future__ import annotations

import bisect
import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np

# reference: include/LightGBM/bin.h kZeroThreshold
K_ZERO_THRESHOLD = 1e-35
# missing types (reference: enum MissingType, bin.h)
MISSING_NONE = 0
MISSING_ZERO = 1
MISSING_NAN = 2

BIN_NUMERICAL = 0
BIN_CATEGORICAL = 1


def _count_column(path: str) -> None:
    """``find_bin_columns_total{path}``: columns whose boundaries were
    found by bisection on the running counts (``array``) and by a walk in
    the interpreter (``loop``: categorical columns and forced bounds)."""
    from ..obs.metrics import default_registry

    default_registry().counter(
        "find_bin_columns_total",
        "Columns binned, by the path that found their boundaries",
        label_names=("path",)).labels(path=path).inc()


def _need_filter(cnt_in_bin: np.ndarray, total_cnt: int, filter_cnt: int,
                 bin_type: int) -> bool:
    """feature_pre_filter test (behavioral port of NeedFilter, reference
    src/io/bin.cpp:54-76): True when NO split point of this feature can put
    >= filter_cnt samples on both sides — such a feature can never satisfy
    min_data_in_leaf and is marked trivial up front."""
    cnt = np.asarray(cnt_in_bin, dtype=np.int64)
    if len(cnt) < 2:
        return True
    if bin_type == BIN_NUMERICAL:
        left = np.cumsum(cnt[:-1])
        return not bool(np.any((left >= filter_cnt)
                               & (total_cnt - left >= filter_cnt)))
    # categorical: the reference only filters 2-bin features (one-vs-rest
    # splits on >2 bins are not prefix sums, bin.cpp:63-73)
    if len(cnt) > 2:
        return False
    left = cnt[:-1]
    return not bool(np.any((left >= filter_cnt)
                           & (total_cnt - left >= filter_cnt)))


def _upper_bound_1ulp(a: float) -> float:
    """Common::GetDoubleUpperBound (reference utils/common.h:931)."""
    return float(np.nextafter(a, np.inf))


def _eq_ordered(a: float, b: float) -> bool:
    """Common::CheckDoubleEqualOrdered for sorted a <= b
    (reference utils/common.h:926): b within one ulp above a."""
    return b <= np.nextafter(a, np.inf)


def _greedy_find_bin(
    distinct_values: np.ndarray,
    counts: np.ndarray,
    max_bin: int,
    total_cnt: int,
    min_data_in_bin: int,
) -> List[float]:
    """Greedy equal-count boundary search — exact behavioral port of
    GreedyFindBin (reference src/io/bin.cpp:78-156), including the
    adaptive mean-bin-size recomputation, the big-count-value lookahead,
    and the one-ulp boundary dedupe, so bin boundaries agree with the
    reference bit-for-bit on the same sample."""
    bounds: List[float] = []
    nd = len(distinct_values)
    if nd == 0:
        return [math.inf]
    if nd <= max_bin:
        cur = 0
        for i in range(nd - 1):
            cur += int(counts[i])
            if cur >= min_data_in_bin:
                val = _upper_bound_1ulp(
                    (distinct_values[i] + distinct_values[i + 1]) / 2.0)
                if not bounds or not _eq_ordered(bounds[-1], val):
                    bounds.append(val)
                    cur = 0
        bounds.append(math.inf)
        return bounds

    if min_data_in_bin > 0:
        max_bin = max(1, min(max_bin, int(total_cnt) // min_data_in_bin))
    mean_bin_size = total_cnt / max_bin

    counts = np.asarray(counts, np.int64)
    is_big = counts >= mean_bin_size
    rest_bin_cnt = max_bin - int(is_big.sum())
    rest_sample_cnt = int(total_cnt) - int(counts[is_big].sum())

    def _mean(cnt, bins):
        if bins != 0:
            return cnt / bins
        return math.inf if cnt > 0 else math.nan

    mean_bin_size = _mean(rest_sample_cnt, rest_bin_cnt)
    # The reference walks the distinct values one by one, closing a bin at
    # value i when it is big, when the bin holds ``mean_bin_size`` samples,
    # or when the next value is big and the bin is half full.  Between two
    # closes nothing it compares against changes, so each close is found
    # by bisection on the running counts: at most ``max_bin`` steps a
    # column, not one step a distinct value.  (``bisect``, not
    # ``searchsorted``: a lookup of one number that gives the GIL up and
    # takes it back stalls every other column's thread.)
    cum = np.cumsum(counts)                           # samples through i
    cum_small = np.cumsum(np.where(is_big, 0, counts))
    big_at = np.flatnonzero(is_big).tolist()
    last = nd - 2                                     # the walk's last i
    upper: List[float] = []
    lower = [float(distinct_values[0])]
    start, base = 0, 0
    while start <= last:
        close = nd
        k = bisect.bisect_left(big_at, start)
        if k < len(big_at):
            close = big_at[k]                         # a big value closes
            # ... and so does the value before it, on a half-full bin
            # (Python's max(1.0, nan) is 1.0, as the walk's was)
            if close > start and cum[close - 1] - base >= max(
                    1.0, mean_bin_size * 0.5):
                close -= 1
        if not (math.isinf(mean_bin_size) or math.isnan(mean_bin_size)):
            close = min(close, bisect.bisect_left(
                cum, base + math.ceil(mean_bin_size), lo=start))
        if close > last:
            break
        upper.append(float(distinct_values[close]))
        lower.append(float(distinct_values[close + 1]))
        if len(upper) >= max_bin - 1:
            break
        if not is_big[close]:
            rest_bin_cnt -= 1
            mean_bin_size = _mean(rest_sample_cnt - int(cum_small[close]),
                                  rest_bin_cnt)
        start, base = close + 1, int(cum[close])
    for i in range(len(upper)):
        val = _upper_bound_1ulp((upper[i] + lower[i + 1]) / 2.0)
        if not bounds or not _eq_ordered(bounds[-1], val):
            bounds.append(val)
    bounds.append(math.inf)
    return bounds


def _find_bin_with_zero_as_one_bin(
    distinct_values: np.ndarray,
    counts: np.ndarray,
    max_bin: int,
    total_sample_cnt: int,
    min_data_in_bin: int,
) -> List[float]:
    """Ensure one bin straddles zero — exact behavioral port of
    FindBinWithZeroAsOneBin (reference src/io/bin.cpp:256-312): the
    negative range gets a count-proportional share of ``max_bin - 1`` bins
    (denominator excludes the zero count), the zero bin closes at
    ``kZeroThreshold``, and the positive range takes the remainder."""
    dv = np.asarray(distinct_values, np.float64)
    left_cnt_data = int(counts[dv <= -K_ZERO_THRESHOLD].sum())
    right_cnt_data = int(counts[dv > K_ZERO_THRESHOLD].sum())
    cnt_zero = int(total_sample_cnt) - left_cnt_data - right_cnt_data

    left_cnt = int(np.argmax(dv > -K_ZERO_THRESHOLD)) \
        if bool((dv > -K_ZERO_THRESHOLD).any()) else len(dv)

    bounds: List[float] = []
    if left_cnt > 0 and max_bin > 1:
        denom = total_sample_cnt - cnt_zero
        left_max_bin = int(left_cnt_data / max(denom, 1) * (max_bin - 1))
        left_max_bin = max(1, left_max_bin)
        bounds = _greedy_find_bin(dv[:left_cnt], counts[:left_cnt],
                                  left_max_bin, left_cnt_data,
                                  min_data_in_bin)
        if bounds:
            bounds[-1] = -K_ZERO_THRESHOLD

    right_pos = np.nonzero(dv[left_cnt:] > K_ZERO_THRESHOLD)[0]
    right_start = left_cnt + int(right_pos[0]) if len(right_pos) else -1

    right_max_bin = max_bin - 1 - len(bounds)
    # when positives exist but right_max_bin == 0 (tiny max_bin with data on
    # both sides of zero), the reference ALSO falls into the inf-only branch
    # (bin.cpp:302-309 appends infinity, not kZeroThreshold) — keep parity
    if right_start >= 0 and right_max_bin > 0:
        rb = _greedy_find_bin(dv[right_start:], counts[right_start:],
                              right_max_bin, right_cnt_data, min_data_in_bin)
        bounds.append(K_ZERO_THRESHOLD)
        bounds.extend(rb)
    else:
        bounds.append(math.inf)
    return bounds


def _distinct_with_zero(values_sorted: np.ndarray, zero_cnt: int):
    """Distinct values + counts from a SORTED non-NaN sample — behavioral
    port of the reference's construction (src/io/bin.cpp:352-390):
    neighbouring values within one ulp merge (keeping the larger value),
    and the implicit-zero count is spliced in where zero sorts (front /
    between the sign change / back)."""
    n = len(values_sorted)
    if n == 0:
        return np.array([0.0]), np.array([zero_cnt], np.int64)
    v = values_sorted
    # group boundaries: value i starts a new group when NOT within one ulp
    # of value i-1 (CheckDoubleEqualOrdered on consecutive sample values)
    new_grp = np.empty(n, bool)
    new_grp[0] = True
    new_grp[1:] = v[1:] > np.nextafter(v[:-1], np.inf)
    starts = np.flatnonzero(new_grp)
    counts = np.diff(np.append(starts, n)).astype(np.int64)
    distinct = np.asarray(v[starts + counts - 1], np.float64)  # reference
                                                     # keeps the LARGE value
    # where zero sorts: before all, after all, or at the one sign change
    # between consecutive sample values (there the reference pushes it
    # with zero_cnt even when that is 0)
    at = None
    if v[0] > 0.0:
        at = 0 if zero_cnt > 0 else None
    elif v[-1] < 0.0:
        at = len(distinct) if zero_cnt > 0 else None
    else:
        flip = np.flatnonzero((v[starts[1:] - 1] < 0.0)
                              & (v[starts[1:]] > 0.0))
        at = int(flip[0]) + 1 if len(flip) else None
    if at is not None:
        distinct = np.insert(distinct, at, 0.0)
        counts = np.insert(counts, at, zero_cnt)
    return distinct, counts


def _find_bin_with_predefined(
    distinct_values: np.ndarray,
    counts: np.ndarray,
    max_bin: int,
    total_sample_cnt: int,
    min_data_in_bin: int,
    forced_upper_bounds: Sequence[float],
) -> List[float]:
    """Bin boundaries honoring user-forced upper bounds (behavioral port of
    FindBinWithPredefinedBin, reference src/io/bin.cpp:157-255): seed the
    boundary list with the zero-straddle bounds plus the forced bounds, then
    subdivide each seeded range greedily with a bin budget proportional to
    its sample count."""
    bounds: List[float] = []
    # negative / zero / positive partition (reference :163-195)
    left_cnt = int(np.searchsorted(distinct_values, -K_ZERO_THRESHOLD,
                                   side="right"))
    has_left = left_cnt > 0
    right_start = int(np.searchsorted(distinct_values, K_ZERO_THRESHOLD,
                                      side="right"))
    has_right = right_start < len(distinct_values)
    if max_bin == 2:
        bounds.append(K_ZERO_THRESHOLD if left_cnt == 0 else -K_ZERO_THRESHOLD)
    elif max_bin >= 3:
        if has_left:
            bounds.append(-K_ZERO_THRESHOLD)
        if has_right:
            bounds.append(K_ZERO_THRESHOLD)
    bounds.append(math.inf)

    # insert forced bounds (nonzero only — zero bounds already seeded)
    max_to_insert = max_bin - len(bounds)
    inserted = 0
    for b in forced_upper_bounds:
        if inserted >= max_to_insert:
            break
        if abs(b) > K_ZERO_THRESHOLD:
            bounds.append(float(b))
            inserted += 1
    bounds.sort()

    # subdivide each seeded range with a count-proportional budget
    free_bins = max_bin - len(bounds)
    to_add: List[float] = []
    value_ind = 0
    for i, ub in enumerate(bounds):
        bin_start = value_ind
        cnt_in_bin = 0
        while (value_ind < len(distinct_values)
               and distinct_values[value_ind] < ub):
            cnt_in_bin += int(counts[value_ind])
            value_ind += 1
        remaining = max_bin - len(bounds) - len(to_add)
        # std::lround = half away from zero (Python round() would banker-round)
        num_sub = int(math.floor(
            cnt_in_bin * free_bins / max(total_sample_cnt, 1) + 0.5))
        num_sub = min(num_sub, remaining) + 1
        if i == len(bounds) - 1:
            num_sub = remaining + 1
        if num_sub > 1 and value_ind > bin_start:
            sub = _greedy_find_bin(
                distinct_values[bin_start:value_ind],
                counts[bin_start:value_ind],
                num_sub, cnt_in_bin, min_data_in_bin)
            to_add.extend(sub[:-1])          # last bound is +inf
    bounds.extend(to_add)
    return sorted(set(bounds))


def get_forced_bins(path: str, num_total_features: int,
                    categorical_features=None) -> List[List[float]]:
    """forcedbins_filename JSON -> per-feature forced upper bounds
    (behavioral port of DatasetLoader::GetForcedBins,
    reference src/io/dataset_loader.cpp:1200-1235; format:
    ``[{"feature": i, "bin_upper_bound": [..]}, ...]``)."""
    import json

    from ..utils.log import log_warning

    forced: List[List[float]] = [[] for _ in range(num_total_features)]
    if not path:
        return forced
    categorical = set(categorical_features or [])
    from ..utils.fileio import open_file

    try:
        with open_file(path) as fh:
            spec = json.load(fh)
    except OSError:
        log_warning(f"Could not open {path}. Will ignore.")
        return forced
    except json.JSONDecodeError as e:
        from ..utils.log import log_fatal
        log_fatal(f"Forced bins file {path} is not valid JSON: {e}")
    for entry in spec:
        f = int(entry["feature"])
        if f >= num_total_features or f < 0:
            # reference: CHECK_LT(forced_bins_arr[i]["feature"].int_value(),
            # num_total_features) aborts (dataset_loader.cpp:1217)
            from ..utils.log import log_fatal
            log_fatal(f"Forced bins feature index {f} is out of range "
                      f"(num features = {num_total_features})")
        if f in categorical:
            log_warning(f"Feature {f} is categorical. Will ignore forced "
                        "bins for this feature.")
            continue
        forced[f] = [float(b) for b in entry["bin_upper_bound"]]
    # remove consecutive duplicates (reference std::unique)
    for f in range(num_total_features):
        out: List[float] = []
        for b in forced[f]:
            if not out or b != out[-1]:
                out.append(b)
        forced[f] = out
    return forced


@dataclass
class BinMapper:
    """Maps raw feature values to small integer bins (one per feature)."""

    bin_upper_bound: np.ndarray = field(default_factory=lambda: np.array([np.inf]))
    num_bin: int = 1
    missing_type: int = MISSING_NONE
    bin_type: int = BIN_NUMERICAL
    is_trivial: bool = True
    sparse_rate: float = 0.0
    min_value: float = 0.0
    max_value: float = 0.0
    # categorical
    categorical_2_bin: Dict[int, int] = field(default_factory=dict)
    bin_2_categorical: List[int] = field(default_factory=list)

    # ------------------------------------------------------------------
    @property
    def nan_bin(self) -> int:
        """Bin index holding NaN values; -1 if none."""
        if self.bin_type == BIN_CATEGORICAL:
            return self.num_bin - 1  # the "other/unseen" bin also takes NaN
        return self.num_bin - 1 if self.missing_type == MISSING_NAN else -1

    @property
    def zero_bin(self) -> int:
        if self.bin_type == BIN_CATEGORICAL:
            return int(self.categorical_2_bin.get(0, self.num_bin - 1))
        return int(np.searchsorted(self.bin_upper_bound, 0.0, side="left"))

    @property
    def default_bin(self) -> int:
        """Bin that missing values fall into during training."""
        if self.missing_type == MISSING_NAN:
            return self.nan_bin
        return self.zero_bin

    # ------------------------------------------------------------------
    @classmethod
    def find_bin(
        cls,
        sample_values: np.ndarray,
        total_sample_cnt: int,
        max_bin: int,
        min_data_in_bin: int = 3,
        bin_type: int = BIN_NUMERICAL,
        use_missing: bool = True,
        zero_as_missing: bool = False,
        forced_bounds: Optional[Sequence[float]] = None,
        pre_filter: bool = False,
        filter_cnt: int = 0,
    ) -> "BinMapper":
        """Behavioral port of BinMapper::FindBin (reference src/io/bin.cpp:325-...).

        ``sample_values`` are the sampled non-implicit values; rows missing
        from the sample (sparse zeros) are accounted by
        ``total_sample_cnt - len(sample_values)`` extra zeros, mirroring the
        reference's sparse sampling contract.
        """
        m = cls()
        m.bin_type = bin_type
        _count_column("loop" if bin_type == BIN_CATEGORICAL or forced_bounds
                      else "array")
        vals = np.asarray(sample_values, dtype=np.float64)
        na_cnt = int(np.isnan(vals).sum())
        vals = vals[~np.isnan(vals)]
        implicit_zero_cnt = total_sample_cnt - len(vals) - na_cnt

        if bin_type == BIN_CATEGORICAL:
            m = cls._find_bin_categorical(m, vals, implicit_zero_cnt, max_bin,
                                          min_data_in_bin, use_missing, na_cnt)
            if not m.is_trivial and pre_filter:
                cnt_in_bin = np.asarray(m._cat_cnt_in_bin, dtype=np.int64)
                if _need_filter(cnt_in_bin, total_sample_cnt, filter_cnt,
                                BIN_CATEGORICAL):
                    m.is_trivial = True
            return m

        # resolve missing type (reference bin.cpp:351-380)
        if not use_missing:
            m.missing_type = MISSING_NONE
        elif zero_as_missing:
            m.missing_type = MISSING_ZERO
        elif na_cnt > 0:
            m.missing_type = MISSING_NAN
        else:
            m.missing_type = MISSING_NONE
        if m.missing_type != MISSING_NAN:
            # reference bin.cpp:336-352: na_cnt is only tracked in the NaN
            # branch; otherwise NaN samples fold into the implicit-zero
            # count (under zero_as_missing they ARE the missing zeros)
            implicit_zero_cnt += na_cnt
            na_cnt = 0

        if len(vals) == 0 and implicit_zero_cnt == 0:
            # all NaN
            m.bin_upper_bound = np.array([np.inf])
            m.num_bin = 2 if m.missing_type == MISSING_NAN else 1
            m.is_trivial = m.num_bin <= 1
            return m

        # distinct values with the implicit-zero splice, one-ulp merge
        # (reference bin.cpp:352-390)
        vals_sorted = np.sort(vals, kind="stable")
        distinct, counts = _distinct_with_zero(vals_sorted, implicit_zero_cnt)
        m.min_value = float(distinct[0])
        m.max_value = float(distinct[-1])

        # reference bin.cpp:395-408: the NaN missing type reserves one bin
        # and excludes the NaN count from the sample total
        if m.missing_type == MISSING_NAN:
            budget, total_eff = max_bin - 1, total_sample_cnt - na_cnt
        else:
            budget, total_eff = max_bin, total_sample_cnt
        budget = max(budget, 2)
        if forced_bounds:
            # reference bin.cpp:316-322: forced bounds switch the boundary
            # search to FindBinWithPredefinedBin
            bounds = _find_bin_with_predefined(
                distinct, counts, budget, total_eff, min_data_in_bin,
                forced_bounds)
        else:
            bounds = _find_bin_with_zero_as_one_bin(
                distinct, counts, budget, total_eff, min_data_in_bin
            )
        if m.missing_type == MISSING_ZERO and len(bounds) == 2:
            # reference bin.cpp:399-402: a 2-bin zero-as-missing feature
            # degenerates to no missing handling
            m.missing_type = MISSING_NONE
        m.bin_upper_bound = np.asarray(bounds, dtype=np.float64)
        m.num_bin = len(bounds)
        if m.missing_type == MISSING_NAN:
            m.num_bin += 1  # trailing NaN bin
        zero_total = int(counts[np.abs(distinct) <= K_ZERO_THRESHOLD].sum())
        m.sparse_rate = zero_total / max(len(vals) + implicit_zero_cnt, 1)
        m.is_trivial = m.num_bin <= 1
        if not m.is_trivial and pre_filter:
            # per-bin sample counts incl. the trailing NaN bin
            bin_of = np.searchsorted(m.bin_upper_bound, distinct, side="left")
            np.clip(bin_of, 0, len(m.bin_upper_bound) - 1, out=bin_of)
            cnt_in_bin = np.bincount(bin_of, weights=counts,
                                     minlength=m.num_bin).astype(np.int64)
            if m.missing_type == MISSING_NAN:
                cnt_in_bin[m.num_bin - 1] = na_cnt
            if _need_filter(cnt_in_bin, total_sample_cnt, filter_cnt,
                            BIN_NUMERICAL):
                m.is_trivial = True
        return m

    @staticmethod
    def _find_bin_categorical(m, vals, implicit_zero_cnt, max_bin,
                              min_data_in_bin, use_missing, na_cnt):
        # reference uses the C truncation cast for categorical values
        # (bin.cpp CategoricalBin / static_cast<int>), not rounding
        cats = np.trunc(vals).astype(np.int64)
        neg = cats < 0
        if neg.any():
            # reference warns and treats negatives as missing-ish; fold into "other"
            cats = cats[~neg]
        if implicit_zero_cnt > 0:
            cats = np.concatenate([cats, np.zeros(implicit_zero_cnt, dtype=np.int64)])
        distinct, counts = np.unique(cats, return_counts=True)
        order = np.argsort(-counts, kind="stable")
        distinct, counts = distinct[order], counts[order]
        # keep top max_bin-1 categories (reserve 1 bin for other/NaN/unseen),
        # dropping ultra-rare ones (reference uses min_data_in_bin-like cut)
        keep = min(len(distinct), max_bin - 1)
        m.bin_2_categorical = [int(c) for c in distinct[:keep]]
        m.categorical_2_bin = {int(c): i for i, c in enumerate(m.bin_2_categorical)}
        m.num_bin = keep + 1  # + other/unseen/NaN bin
        m._cat_cnt_in_bin = [int(c) for c in counts[:keep]] + [
            int(counts[keep:].sum()) + na_cnt]
        m.missing_type = MISSING_NAN if (use_missing and na_cnt > 0) else MISSING_NONE
        m.is_trivial = keep <= 1
        m.min_value = float(distinct.min()) if len(distinct) else 0.0
        m.max_value = float(distinct.max()) if len(distinct) else 0.0
        m.bin_upper_bound = np.array([np.inf])
        return m

    # ------------------------------------------------------------------
    def value_to_bin(self, values: np.ndarray) -> np.ndarray:
        """Vectorized ValueToBin (reference include/LightGBM/bin.h:457-495)."""
        v = np.asarray(values, dtype=np.float64)
        if self.bin_type == BIN_CATEGORICAL:
            out = np.full(v.shape, self.num_bin - 1, dtype=np.int32)  # other bin
            nan_mask = np.isnan(v)
            cats = np.trunc(np.where(nan_mask, -1, v)).astype(np.int64)
            for c, b in self.categorical_2_bin.items():
                out[cats == c] = b
            return out
        nan_mask = np.isnan(v)
        # NaN routed to the zero bin here; for MISSING_NAN it is overwritten
        # with the trailing NaN bin below
        v = np.where(nan_mask, 0.0, v)
        out = np.searchsorted(self.bin_upper_bound, v, side="left").astype(np.int32)
        n_real = len(self.bin_upper_bound)
        np.clip(out, 0, n_real - 1, out=out)
        if self.missing_type == MISSING_NAN:
            out[nan_mask] = self.num_bin - 1
        return out

    def bin_to_threshold(self, bin_idx: int) -> float:
        """Real-valued threshold stored in the model for a bin split
        (reference stores bin upper bound as the double threshold)."""
        n_real = len(self.bin_upper_bound)
        b = min(int(bin_idx), n_real - 1)
        ub = self.bin_upper_bound[b]
        if math.isinf(ub):
            # reference stores AvoidInf = ±1e300 (bin.cpp GetDoubleUpperBound)
            # so out-of-train-range raw values still go left at a NaN-vs-rest
            # split; max_value+1 would create train/serve skew beyond it
            ub = 1e300
        return float(ub)

    def feature_info_str(self) -> str:
        """feature_infos entry for the model text (reference gbdt_model_text.cpp)."""
        if self.is_trivial:
            return "none"
        if self.bin_type == BIN_CATEGORICAL:
            return ":".join(str(c) for c in self.bin_2_categorical)
        return f"[{self.min_value:g}:{self.max_value:g}]"

    # serialization used by the distributed bin-finding allgather
    def to_arrays(self):
        return {
            "bin_upper_bound": self.bin_upper_bound,
            "num_bin": self.num_bin,
            "missing_type": self.missing_type,
            "bin_type": self.bin_type,
            "is_trivial": self.is_trivial,
            "sparse_rate": self.sparse_rate,
            "min_value": self.min_value,
            "max_value": self.max_value,
            "bin_2_categorical": list(self.bin_2_categorical),
        }

    @classmethod
    def from_arrays(cls, d) -> "BinMapper":
        m = cls()
        m.bin_upper_bound = np.asarray(d["bin_upper_bound"], dtype=np.float64)
        m.num_bin = int(d["num_bin"])
        m.missing_type = int(d["missing_type"])
        m.bin_type = int(d["bin_type"])
        m.is_trivial = bool(d["is_trivial"])
        m.sparse_rate = float(d["sparse_rate"])
        m.min_value = float(d["min_value"])
        m.max_value = float(d["max_value"])
        m.bin_2_categorical = [int(c) for c in d.get("bin_2_categorical", [])]
        m.categorical_2_bin = {c: i for i, c in enumerate(m.bin_2_categorical)}
        return m
