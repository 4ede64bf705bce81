"""Phase-attribution harness (tools/phase_attrib.py + utils/timer.py) and
the fused per-round bookkeeping it motivated (grower_wave _PackedStore).

Two contracts pinned here:

1. The named sub-phase decomposition of ``phase_other_ms`` is honest by
   construction: parts are non-negative, and named parts + the
   unattributed remainder reproduce the measured total EXACTLY — the
   record can therefore never claim more coverage than was measured, and
   the >10%-of-wall flag can never be silently dodged.
2. ``fused_bookkeeping`` (packed two-table state, one coalesced scatter
   each per round) grows trees BIT-IDENTICAL to the legacy per-field
   scatter layout on the exact-fp32 scatter histogram path — the same
   parity bar the slot-bucket change holds (tests/test_wave_bucket.py).
"""

import numpy as np
import pytest

import lightgbmv1_tpu as lgb
from lightgbmv1_tpu.utils.timer import PhaseBreakdown, scan_differential_ms


def make_problem(n=3000, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 7)
    X[::9, 2] = np.nan
    X[:, 6] = rng.randint(0, 6, n).astype(float)
    y = (X[:, 0] * 1.3 - X[:, 1] + np.isin(X[:, 6], [1, 4]) * 1.2
         + rng.randn(n) * 0.5 > 0.2).astype(float)
    return X, y


# ---------------------------------------------------------------------------
# fused-vs-unfused bit parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("params", [
    {"objective": "binary", "num_leaves": 63},
    {"objective": "regression", "num_leaves": 63,
     "bagging_fraction": 0.6, "bagging_freq": 1},
    {"objective": "binary", "num_leaves": 15,
     "monotone_constraints": [1, -1, 0, 0, 0, 0, 0]},
])
def test_fused_bookkeeping_bit_identical(params):
    """Packed-table state commits must reproduce the per-field layout's
    trees bit-for-bit on the exact-fp32 scatter path (CPU default)."""
    X, y = make_problem()
    base = {**params, "verbosity": -1, "tree_growth": "leafwise",
            "leafwise_wave_size": 16}
    cat = [] if "monotone_constraints" in params else [6]

    def run(fused):
        return lgb.train({**base, "fused_bookkeeping": fused},
                         lgb.Dataset(X, label=y, categorical_feature=cat),
                         num_boost_round=4)

    a, b = run(True), run(False)
    for ta, tb in zip(a._all_trees(), b._all_trees()):
        assert ta.num_leaves == tb.num_leaves
        np.testing.assert_array_equal(ta.split_feature, tb.split_feature)
        np.testing.assert_array_equal(ta.threshold_bin, tb.threshold_bin)
        np.testing.assert_array_equal(ta.left_child, tb.left_child)
        np.testing.assert_array_equal(ta.right_child, tb.right_child)
        np.testing.assert_array_equal(ta.leaf_count, tb.leaf_count)
        # bit-identical, not allclose: same adds in the same order
        np.testing.assert_array_equal(np.asarray(ta.leaf_value),
                                      np.asarray(tb.leaf_value))
        np.testing.assert_array_equal(np.asarray(ta.split_gain),
                                      np.asarray(tb.split_gain))
    np.testing.assert_array_equal(a.predict(X), b.predict(X))


def test_fused_bookkeeping_valid_routing_identical():
    """The packed store must not disturb the wave grower's valid-row
    routing (leaf_hist commits moved to one interleaved scatter)."""
    X, y = make_problem()
    Xv, yv = make_problem(n=800, seed=9)

    def run(fused):
        ds = lgb.Dataset(X, label=y)
        m = lgb.train({"objective": "binary", "num_leaves": 31,
                       "leafwise_wave_size": 8, "tree_growth": "leafwise",
                       "verbosity": -1, "fused_bookkeeping": fused},
                      ds, num_boost_round=3,
                      valid_sets=[lgb.Dataset(Xv, label=yv, reference=ds)],
                      valid_names=["v"])
        return m

    a, b = run(True), run(False)
    np.testing.assert_array_equal(a.predict(Xv), b.predict(Xv))


# ---------------------------------------------------------------------------
# decomposition honesty
# ---------------------------------------------------------------------------


def test_phase_breakdown_arithmetic_identity():
    bd = PhaseBreakdown()
    bd.add("a_ms", 3.2)
    bd.add("b_ms", 1.05)
    bd.add("c_ms", -0.4)          # noise clamps to 0, never negative
    assert bd.parts["c_ms"] == 0.0
    rec = bd.record(total_ms=5.0, wall_ms=100.0)
    # named parts + unattributed == total, exactly (by construction)
    s = sum(rec["phase_other_breakdown"].values())
    assert abs(s + rec["phase_other_unattributed_ms"] - 5.0) < 1e-6
    assert rec["phase_attrib_ok"]          # 0.75 <= 10% of 100
    rec2 = bd.record(total_ms=50.0, wall_ms=100.0)
    assert not rec2["phase_attrib_ok"]     # 45.75 > 10% of 100


def test_scan_differential_positive_and_finite():
    import jax
    import jax.numpy as jnp
    from jax import lax

    x = jnp.arange(4096, dtype=jnp.float32)

    def make(r):
        @jax.jit
        def reps():
            def body(c, i):
                return c + (x * (1.0 + 1e-6 * i.astype(jnp.float32))).sum(), None
            s, _ = lax.scan(body, jnp.float32(0), jnp.arange(r))
            return s
        return reps

    ms = scan_differential_ms(make, 4, 16, probes=3)
    assert np.isfinite(ms) and ms > 0


def test_other_breakdown_covers_and_sums(tmp_path):
    """End-to-end on a small CPU config: measure the real per-iteration
    wall, derive the residual the way bench.py does, and assert the
    harness's named sub-phases + remainder reproduce it exactly — the
    identity that makes the BENCH record's coverage flag trustworthy."""
    import time

    from tools.phase_attrib import measure_other_breakdown

    X, y = make_problem(n=6000)
    ds = lgb.Dataset(X[:, :6], label=y)
    params = {"objective": "binary", "num_leaves": 31,
              "leafwise_wave_size": 8, "tree_growth": "leafwise",
              "verbosity": -1}
    booster = lgb.train(params, ds, num_boost_round=3)  # warm compile
    t0 = time.perf_counter()
    booster.update()
    booster.update()
    wall_ms = (time.perf_counter() - t0) / 2 * 1e3

    bd = measure_other_breakdown(N=6000, F=6, B=32, L=31, K=8,
                                 rounds_per_iter=6.0, n_valid=0,
                                 probes=3)
    for name in ("grad_g3_ms", "score_update_ms", "topk_rank_ms",
                 "assembly_scatter_ms", "child_meta_ms", "loop_fixed_ms"):
        assert name in bd.parts and bd.parts[name] >= 0.0
    # bench.py derives other = wall - (hist+partition+split+...); here use
    # a synthetic residual of the measured wall to exercise the identity
    other_ms = 0.5 * wall_ms
    rec = bd.record(other_ms, wall_ms)
    s = sum(rec["phase_other_breakdown"].values())
    # record fields are rounded to 3 decimals — identity holds to that
    assert abs(s + rec["phase_other_unattributed_ms"] - other_ms) < 2e-3
    assert rec["phase_unattributed_frac_of_wall"] == pytest.approx(
        rec["phase_other_unattributed_ms"] / wall_ms, abs=1e-3)


def test_split_breakdown_names_fused_scan_stages():
    """The split-phase decomposition (PR 7) drives the REAL fused-scan
    stage helpers (ops/split.py scan_left_sums / scan_direction_gains /
    scan_pick — the code objects _find_best_split composes), returns the
    three named parts, and stays honest under PhaseBreakdown.record."""
    from tools.phase_attrib import measure_split_breakdown

    bd = measure_split_breakdown(F=6, B=16, K=4, rounds_per_iter=5.0,
                                 probes=2)
    for name in ("split_cumsum_ms", "split_gain_ms", "split_pick_ms"):
        assert name in bd.parts and np.isfinite(bd.parts[name])
        assert bd.parts[name] >= 0.0
    rec = bd.record(10.0, 100.0)
    s = sum(rec["phase_other_breakdown"].values())
    assert abs(s + rec["phase_other_unattributed_ms"] - 10.0) < 2e-3


def test_assembly_measures_real_store_codecs():
    """The assembly sub-phase must drive the SAME store code objects the
    grower runs — both layouts must execute and return sane times."""
    from tools.phase_attrib import measure_assembly_scatter_ms

    for fused in (True, False):
        ms = measure_assembly_scatter_ms(31, 8, 6, 16, fused=fused,
                                         probes=3)
        assert np.isfinite(ms) and ms >= 0


def test_phase_ms_from_fields_takes_the_canonical_phases():
    """Every positive canonical phase of a record lands as its own row of
    the cost split and the roofline join; absent rows and fields that are
    not phases do not."""
    from tools.phase_attrib import (PHASE_MS_KEYS, phase_ms_from_fields,
                                    roofline_attribution,
                                    split_cost_by_ms)

    assert PHASE_MS_KEYS[0] == "phase_hist_ms"
    fields = {"phase_hist_ms": 45.0, "phase_split_ms": 22.8,
              "phase_other_ms": 50.0,
              "phase_valid_route_ms": None,   # no valid set attached
              "phase_partition_ms": 0.0,
              "not_a_phase_ms": 3.0}
    pms = phase_ms_from_fields(fields)
    assert pms == {"hist": 45.0, "split": 22.8, "other": 50.0}
    cost = split_cost_by_ms(1e12, 1e9, pms)
    assert set(cost) == set(pms)
    rl = roofline_attribution(pms, cost, 1e12, peak_bytes_per_s=1e11)
    assert rl["hist"]["ms"] == 45.0
