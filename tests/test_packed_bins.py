"""Sub-byte bin residency (``bin_layout=packed4``) — bit-parity and gating
tests on ``hist_method=pallas`` (interpret mode on CPU).

The packed contract: two 4-bit bins per byte is a pure storage-layout
change.  The histogram kernel's operand is unpacked at placement onto the
identical arithmetic and the partition decodes the split feature's nibble
per row, so ``bin_layout=packed4`` trees are BIT-IDENTICAL to
``bin_layout=u8`` trees.  Model text equality is the pin — structure, thresholds, leaf
values and metadata all byte-compare.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import lightgbmv1_tpu.models.grower_wave as gw
from conftest import make_binary_problem
from lightgbmv1_tpu.basic import _objective_string
from lightgbmv1_tpu.config import Config
from lightgbmv1_tpu.io.dataset import BinnedDataset
from lightgbmv1_tpu.io.model_text import model_to_string
from lightgbmv1_tpu.models.gbdt import create_boosting

_PACKED_ENGAGED = "4-bit packed bins engaged"
_BASE = {"objective": "binary", "num_leaves": 31, "min_data_in_leaf": 5,
         "verbosity": -1, "tree_growth": "leafwise",
         "leafwise_wave_size": 8, "hist_method": "pallas"}


def _valid_problem(seed=7, n=500, f=8):
    rng = np.random.RandomState(seed)
    Xv = rng.randn(n, f)
    yv = (1.2 * Xv[:, 0] - Xv[:, 1] + rng.randn(n) * 0.3 > 0) \
        .astype(np.float64)
    return Xv, yv


def _train(over, X, y, iters=3, valid=None):
    """Model text of ``iters`` trees (and the valid metrics, if a valid
    set is given) under ``_BASE`` + ``over``."""
    cfg = Config.from_dict({**_BASE, **over})
    ds = BinnedDataset.from_numpy(X, label=y, config=cfg)
    gb = create_boosting(cfg, ds)
    if valid is not None:
        gb.add_valid(BinnedDataset.from_numpy(
            valid[0], label=valid[1], config=cfg, reference=ds), "v")
    for _ in range(iters):
        gb.train_one_iter(check_stop=False)
    text = model_to_string(
        gb.materialize_host_trees(),
        objective_string=_objective_string(cfg),
        num_class=cfg.num_tree_per_iteration,
        num_tree_per_iteration=cfg.num_tree_per_iteration,
        feature_names=list(ds.feature_names),
        feature_infos=ds.feature_infos())
    if valid is None:
        return text
    return text, [(name, float(v)) for (_, name, v, _) in gb.eval_valid()]


def _packed_parity(over=None, problem=None, iters=3):
    X, y = problem if problem is not None else make_binary_problem()
    over = {"max_bin": 15, **(over or {})}
    u8 = _train({**over, "bin_layout": "u8"}, X, y, iters=iters)
    packed = _train({**over, "bin_layout": "packed4"}, X, y, iters=iters)
    assert packed == u8, "packed4 trees diverged from the u8 trees"


def _log_lines(fn):
    """Run ``fn`` capturing log lines; returns the captured list."""
    from lightgbmv1_tpu.utils import log

    lines = []
    log.register_callback(lines.append)
    try:
        fn()
    finally:
        log.register_callback(None)
    return lines


def test_pack4bit_roundtrip_and_odd_tail(rng):
    """pack/unpack inverse across even and odd F; an odd-F tail's
    phantom hi nibble is ZERO (the inert feature the kernel pads meta
    for) and unpack slices it away."""
    from lightgbmv1_tpu.ops.hist_pallas import pack4bit, unpack4bit

    for F in (1, 2, 7, 8):
        a = rng.randint(0, 16, (F, 33)).astype(np.uint8)
        p = pack4bit(a)
        assert p.shape == (-(-F // 2), 33)
        np.testing.assert_array_equal(unpack4bit(p, F), a)
        np.testing.assert_array_equal(
            np.asarray(unpack4bit(jnp.asarray(p), F)), a)
        if F % 2:
            np.testing.assert_array_equal(np.asarray(p[-1] >> 4),
                                          np.zeros(33, np.uint8))


def test_kernel_width_ladder():
    # the histogram16/64/256 rungs: callers specialize tiling on the
    # rung, and ONLY the <=16 rung admits nibble-packed bins
    from lightgbmv1_tpu.ops.hist_pallas import kernel_width

    assert kernel_width(2) == 16
    assert kernel_width(16) == 16
    assert kernel_width(17) == 64
    assert kernel_width(64) == 64
    assert kernel_width(65) == 256
    assert kernel_width(256) == 256
    with pytest.raises(ValueError, match="num_bins <= 256"):
        kernel_width(257)


def test_packed_parity_binary():
    _packed_parity(problem=make_binary_problem(n=700, f=6, seed=11), iters=2)


def test_packed_parity_odd_f():
    # odd F exercises the phantom hi-nibble feature end to end: it must
    # be inert in the scan (never picked) and in routing
    _packed_parity(problem=make_binary_problem(n=1000, f=7, seed=2))


def test_packed_parity_multiclass():
    rng = np.random.RandomState(3)
    n, f, k = 1200, 6, 3
    X = rng.randn(n, f)
    y = np.clip((np.abs(X[:, 0]) + X[:, 1] > 1).astype(np.float64)
                + (X[:, 2] > 0.3).astype(np.float64), 0, k - 1)
    _packed_parity({"objective": "multiclass", "num_class": k,
                    "num_leaves": 15, "leafwise_wave_size": 4,
                    "metric": "multi_logloss"}, problem=(X, y), iters=2)


def test_packed_parity_dart():
    _packed_parity({"boosting": "dart", "drop_rate": 0.3,
                    "drop_seed": 5}, iters=4)


def test_packed_parity_int8sr(monkeypatch):
    # the quantized lane consumes the UNPACKED operand — the same
    # sr_quantize_g3 stream, so packed int8sr == unpacked int8sr
    monkeypatch.setattr(gw, "_BUCKET_MIN_N", 1)
    _packed_parity({"num_leaves": 48, "leafwise_wave_size": 32,
                    "hist_dtype_deep": "int8sr"},
                   problem=make_binary_problem(n=1600), iters=2)


def _kernel_rounds():
    """``partition_rounds_traced_total`` by path, summed over buckets."""
    from lightgbmv1_tpu.obs.metrics import default_registry

    out = {}
    for key, value in default_registry().snapshot().items():
        if key.startswith("partition_rounds_traced_total{"):
            path = key.split('path="')[1].split('"')[0]
            out[path] = out.get(path, 0) + int(value)
    return out


def test_packed_parity_with_the_partition_kernel(monkeypatch):
    """``max_bin=15`` through ``lgb.Dataset`` / ``Booster.update`` with
    ``bin_layout`` at its default: ``packed4`` engages, every round's
    partition takes the kernel on the ``(ceil(F/2), N)`` bytes (counted
    under ``path="kernel"``, none under ``"gather"``), and the trees are
    the ``bin_layout=u8`` booster's bit for bit, an odd F among them."""
    import lightgbmv1_tpu as lgb
    from lightgbmv1_tpu.obs.metrics import default_registry

    monkeypatch.setattr(gw, "_BUCKET_MIN_N", 1)
    X, y = make_binary_problem(n=2500, f=9, seed=5)
    params = {**_BASE, "max_bin": 15, "num_leaves": 40,
              "leafwise_wave_size": 15}
    texts = {}
    for layout in ("auto", "u8"):
        before = _kernel_rounds()
        booster = lgb.Booster({**params, "bin_layout": layout},
                              lgb.Dataset(X, label=y, params=params))
        for _ in range(3):
            booster.update()
        since = {k: v - before.get(k, 0) for k, v in _kernel_rounds().items()}
        assert since.get("kernel", 0) >= 2 and not since.get("gather"), \
            (layout, since)
        engaged = default_registry().snapshot()
        assert engaged['bin_layout_engaged{layout="packed4"}'] == \
            (layout == "auto")
        assert engaged['bin_layout_engaged{layout="u8"}'] == (layout == "u8")
        texts[layout] = booster.model_to_string()
    assert "Tree=2" in texts["u8"]
    assert texts["auto"] == texts["u8"], "packed4 trees diverged from u8"


def test_packed_matrix_is_packed_under_its_own_span():
    """``GBDT.__init__`` packs the host matrix inside ``data.pack``: the
    phase's seconds land in ``dataset_construct_seconds{phase="pack"}``."""
    from lightgbmv1_tpu.obs.metrics import default_registry

    key = 'dataset_construct_seconds{phase="pack"}'
    before = default_registry().snapshot().get(key, 0.0)
    X, y = make_binary_problem(n=700, f=6, seed=11)
    _train({"max_bin": 15}, X, y, iters=1)
    assert default_registry().snapshot()[key] > before


def test_packed_valid_routing_parity():
    """Valid rows route through the packed decision lane (nibble decode
    of the split feature): valid METRICS and trees must be bit-equal
    across layouts."""
    X, y = make_binary_problem()
    over = {"max_bin": 15, "metric": "binary_logloss"}
    t_u, ev_u = _train({**over, "bin_layout": "u8"}, X, y,
                       valid=_valid_problem())
    t_p, ev_p = _train({**over, "bin_layout": "packed4"}, X, y,
                       valid=_valid_problem())
    assert t_u == t_p, "trees diverged"
    assert ev_u == ev_p, "valid metrics diverged"


def test_packed_num_bins_boundary():
    """num_bins 15/16 fit a nibble (no refusal, trees bit-equal to
    unpacked); 17 exceeds 4 bits — an explicit packed4 falls back to u8
    with the warning and trains unpacked."""
    X, y = make_binary_problem()
    for mb in (15, 16):
        texts = {}
        lines = _log_lines(lambda: texts.update(
            (bl, _train({"max_bin": mb, "bin_layout": bl, "verbosity": 0},
                        X, y, iters=2))
            for bl in ("u8", "packed4")))
        assert not any("storing u8 bins" in ln for ln in lines), (mb, lines)
        assert texts["u8"] == texts["packed4"], f"max_bin={mb} diverged"
    lines = _log_lines(lambda: _train(
        {"max_bin": 17, "bin_layout": "packed4", "verbosity": 0},
        X, y, iters=1))
    assert any("needs more than 4 bits" in ln
               and "storing u8 bins" in ln for ln in lines), lines


def test_packed_engagement_logged_once():
    X, y = make_binary_problem()
    lines = _log_lines(lambda: _train(
        {"bin_layout": "packed4", "max_bin": 15, "verbosity": 1},
        X, y, iters=3))
    hits = [ln for ln in lines if _PACKED_ENGAGED in ln]
    assert len(hits) == 1, lines


def test_packed_refused_by_gpu_use_dp():
    # gpu_use_dp requests the widest histogram datapath — packed4 refuses
    # with the warning and the run proceeds on u8 bins
    X, y = make_binary_problem()
    lines = _log_lines(lambda: _train(
        {"bin_layout": "packed4", "max_bin": 15, "gpu_use_dp": True,
         "verbosity": 0}, X, y, iters=1))
    assert any("gpu_use_dp" in ln and "storing u8 bins" in ln
               for ln in lines), lines


def test_packed_auto_engages_and_auto_refuses():
    # bin_layout=auto packs exactly when eligible: engagement info at
    # max_bin<=15, SILENT u8 fallback above (no warning — the user never
    # asked for packing)
    X, y = make_binary_problem()
    lines = _log_lines(lambda: _train(
        {"max_bin": 15, "verbosity": 1}, X, y, iters=1))
    assert any(_PACKED_ENGAGED in ln for ln in lines), lines
    lines = _log_lines(lambda: _train(
        {"max_bin": 63, "verbosity": 0}, X, y, iters=1))
    assert not any("storing u8 bins" in ln for ln in lines), lines


@pytest.mark.parametrize("value", ["warp", "fused"])
def test_config_rejects_unknown_hist_method(value):
    with pytest.raises(ValueError, match="hist_method"):
        Config.from_dict({"objective": "binary", "hist_method": value})


def test_config_rejects_unknown_bin_layout():
    with pytest.raises(ValueError, match="bin_layout"):
        Config.from_dict({"objective": "binary", "bin_layout": "packed2"})
