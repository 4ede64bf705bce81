"""One choice of histogram method and stored bin layout a booster:
``parallel/trainer.resolve_hist_plan``, its record (``HistPlan``), the
``bin_layout_engaged`` gauge and the packing-refusal reasons it logs."""

import jax
import numpy as np
import pytest

from lightgbmv1_tpu.config import Config
from lightgbmv1_tpu.obs.metrics import default_registry
from lightgbmv1_tpu.parallel.trainer import HistPlan, resolve_hist_plan
from lightgbmv1_tpu.utils import log


def _engaged():
    snap = default_registry().snapshot()
    return {name: snap[f'bin_layout_engaged{{layout="{name}"}}']
            for name in ("u8", "packed4")}


# (backend, config, resolver arguments, the record, the packing-refusal
# reason an explicit bin_layout=packed4 logs; None: none is refused)
_PLANS = {
    "wide_bins": (
        "cpu", {"hist_method": "pallas"},
        dict(bin_dtype=np.int16, num_total_bin=300),
        HistPlan("pallas", False, "", True),
        "int16-binned data exceeds the 4-bit nibble"),
    "over_16_bins": (
        "cpu", {"hist_method": "pallas"},
        dict(bin_dtype=np.uint8, num_total_bin=17),
        HistPlan("pallas", False, "u8", True),
        "num_total_bin=17 needs more than 4 bits"),
    "efb": (
        "cpu", {"hist_method": "pallas"},
        dict(bin_dtype=np.uint8, num_total_bin=16, bundled=True),
        HistPlan("pallas", False, "", True),
        "EFB bundle offsets address unpacked byte bins"),
    "not_pallas": (
        "cpu", {"hist_method": "onehot"},
        dict(bin_dtype=np.uint8, num_total_bin=16),
        HistPlan("onehot", False, "u8", False),
        "hist method 'onehot' gathers unpacked bins"),
    "feature_learner": (
        "cpu", {"hist_method": "pallas", "tree_learner": "feature"},
        dict(bin_dtype=np.uint8, num_total_bin=16),
        HistPlan("pallas", False, "u8", True),
        "tree_learner=feature shards features"),
    "gpu_use_dp": (
        "cpu", {"hist_method": "pallas", "gpu_use_dp": True},
        dict(bin_dtype=np.uint8, num_total_bin=16),
        HistPlan("pallas", False, "u8", True),
        "gpu_use_dp requests the widest histogram datapath"),
    "packed4": (
        "cpu", {"hist_method": "pallas"},
        dict(bin_dtype=np.uint8, num_total_bin=16),
        HistPlan("pallas", True, "packed4", True), None),
    "tpu_u8": (
        "tpu", {}, dict(bin_dtype=np.uint8, num_total_bin=64),
        HistPlan("pallas", False, "u8", False),
        "num_total_bin=64 needs more than 4 bits"),
    "tpu_u8_packs": (
        "tpu", {}, dict(bin_dtype=np.uint8, num_total_bin=16),
        HistPlan("pallas", True, "packed4", False), None),
    "tpu_wide": (
        "tpu", {}, dict(bin_dtype=np.int16, num_total_bin=300),
        HistPlan("onehot", False, "", False),
        "int16-binned data exceeds the 4-bit nibble"),
    "cpu_auto": (
        "cpu", {}, dict(bin_dtype=np.uint8, num_total_bin=64),
        HistPlan("scatter", False, "u8", False),
        "num_total_bin=64 needs more than 4 bits"),
    "streamed_packed": (
        "cpu", {"hist_method": "pallas"},
        dict(bin_dtype=np.uint8, stored="packed4"),
        HistPlan("pallas", True, "packed4", True), None),
}


@pytest.mark.parametrize("bin_layout", ["auto", "packed4"])
@pytest.mark.parametrize("case", list(_PLANS))
def test_resolve_hist_plan(monkeypatch, case, bin_layout):
    """The whole record and the gauge for each packing-refusal reason, an
    engaged ``packed4``, a streamed cache's stored layout and the
    platform / bin width rule of ``hist_method=auto``; ``auto`` refuses
    silently, an explicit ``packed4`` logs the reason."""
    backend, over, kw, plan, reason = _PLANS[case]
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    config = Config(verbosity=1, bin_layout=bin_layout, **over)
    lines = []
    log.register_callback(lines.append)
    try:
        got = resolve_hist_plan(config, **kw)
    finally:
        log.register_callback(None)
    assert got == plan
    stored = "packed4" if plan.packed else "u8"
    assert _engaged() == {"u8": float(stored == "u8"),
                          "packed4": float(stored == "packed4")}
    warned = [ln for ln in lines if "storing u8 bins" in ln]
    if reason is None or bin_layout == "auto":
        assert warned == []
    else:
        assert len(warned) == 1 and reason in warned[0], lines
    assert any("4-bit packed bins engaged" in ln for ln in lines) == (
        plan.packed and "stored" not in kw)


def test_bin_layout_u8_stores_u8_without_a_reason(monkeypatch):
    """``bin_layout=u8`` is taken as asked, at an eligible width too."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got = resolve_hist_plan(Config(verbosity=-1, bin_layout="u8"),
                            bin_dtype=np.uint8, num_total_bin=16)
    assert got == HistPlan("pallas", False, "u8", False)
    assert _engaged() == {"u8": 1.0, "packed4": 0.0}


def test_auto_on_tpu_at_300_columns_times_nothing(monkeypatch):
    """A booster built with ``hist_method=auto`` on a TPU backend over a
    300-column ``u8`` matrix takes the kernel by the static rule: nothing
    is compiled or timed to choose it."""
    from lightgbmv1_tpu.io.dataset import BinnedDataset
    from lightgbmv1_tpu.models.gbdt import create_boosting
    from lightgbmv1_tpu.utils import timer

    def timed(*args, **kwargs):
        raise AssertionError("a histogram method was timed")

    monkeypatch.setattr(timer, "scan_differential_ms", timed)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rng = np.random.RandomState(0)
    X = rng.randn(256, 300)
    cfg = Config.from_dict({"objective": "binary", "verbosity": -1,
                            "num_leaves": 7, "max_bin": 63})
    ds = BinnedDataset.from_numpy(X, label=(X[:, 0] > 0).astype(np.float64),
                                  config=cfg)
    assert ds.train_matrix.shape[0] == 300
    assert ds.train_matrix.dtype == np.uint8
    gbdt = create_boosting(cfg, ds)
    assert gbdt._hist == HistPlan("pallas", False, "u8", False)


def test_config_rejects_hist_method_bench():
    with pytest.raises(ValueError,
                       match=r"expected auto \| scatter \| onehot \| pallas"):
        Config(hist_method="bench")
