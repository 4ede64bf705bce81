"""Device-phase attribution for the wave grower's per-iteration residual.

`BENCH` records through round 5 carried a ``phase_other_ms`` grab-bag —
"gradients, score updates, top-k, tree-assembly scatters, per-round fixed
costs" — that had grown to a THIRD of the measured iteration (46.7-50.5 ms
of ~152-166 ms/iter) with no number attached to any of its parts.  The
reference itemizes every phase under USE_TIMETAG
(include/LightGBM/utils/common.h:1054-1138); this module is the TPU-side
analog: it decomposes the residual into NAMED sub-phases, each measured
with the same two-length-scan differential the headline bench uses
(utils/timer.scan_differential_ms — one jitted ``lax.scan`` per probe so
dispatch latency cancels), priced over the REPLAYED wave round schedule.

Sub-phases (ms per iteration):

* ``grad_g3_ms``        — objective gradients + (N, 3) g3 assembly, once
                          per class per iteration (models/gbdt.py step).
* ``score_update_ms``   — train-score application via the gather-free
                          ``leaf_lookup`` + the valid-set leaf-value
                          gather adds (models/gbdt.py deferred updates).
* ``topk_rank_ms``      — ``_topk_by_rank`` frontier ranking, per round.
* ``assembly_scatter_ms`` — the per-round bookkeeping commit: the store
                          write (frontier + node tables — the REAL
                          ``_PackedStore``/``_FieldStore`` code objects
                          the grower's body calls) plus the per-leaf
                          histogram-state scatter.
* ``child_meta_ms``     — per-round frontier reads, smaller-child
                          subtraction + child interleave
                          (``subtract_child_hists``), and the child
                          metadata stacks.
* ``loop_fixed_ms``     — while-loop + slot-bucket ``lax.switch``
                          control overhead per round, measured on a
                          realistic small carry.

Everything not in this list stays in ``phase_other_unattributed_ms``;
``utils/timer.PhaseBreakdown`` computes that remainder by construction
and flags the record when it exceeds 10% of the measured per-iteration
wall — the residual can never silently regrow past the bar again.

Round 12 adds the SPLIT-phase decomposition (``measure_split_breakdown``)
— the 22.8 ms/iter ``phase_split_ms`` target from r05 broken into the
fused scan's stages (ops/split.py ``scan_left_sums`` /
``scan_direction_gains`` / ``scan_pick``), timed on the same real code
objects the split search composes.

Standalone: ``JAX_PLATFORMS=cpu python tools/phase_attrib.py`` prints a
small-shape JSON breakdown (the CPU test drives the same entry point).
"""

from __future__ import annotations

import json
import os
import sys
from types import SimpleNamespace

import numpy as np

# standalone invocation from anywhere: make the repo root importable
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    try:
        import lightgbmv1_tpu  # noqa: F401
    except ImportError:
        sys.path.insert(0, _ROOT)


def _fake_split_result(rng, n, W, scalar=False):
    """SplitResult-shaped namespace for driving the store codecs at bench
    shapes (only the fields the stores read)."""
    import jax.numpy as jnp

    def arr(v, dtype):
        a = jnp.asarray(v, dtype)
        return a[0] if scalar else a

    return SimpleNamespace(
        gain=arr(np.abs(rng.randn(n)).astype(np.float32), jnp.float32),
        feature=arr(rng.randint(0, 28, n), jnp.int32),
        threshold_bin=arr(rng.randint(0, 63, n), jnp.int32),
        default_left=arr(rng.rand(n) < 0.5, bool),
        left_sum=jnp.asarray(rng.randn(n, 3).astype(np.float32))[0 if scalar
                                                                 else slice(None)],
        right_sum=jnp.asarray(rng.randn(n, 3).astype(np.float32))[0 if scalar
                                                                  else slice(None)],
        is_cat=arr(np.zeros(n, bool), bool),
        cat_bitset=(jnp.zeros(W, jnp.uint32) if scalar
                    else jnp.zeros((n, W), jnp.uint32)),
    )


def measure_grad_g3_ms(N, objective=None, label=None, reps=(4, 16),
                       probes=5):
    """Gradient + g3 assembly at N rows (one class).  With ``objective``
    (an initialized objectives.ObjectiveFunction) the REAL gradient op is
    timed; otherwise the binary-logistic formula at the same shapes."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from lightgbmv1_tpu.utils.timer import scan_differential_ms

    rng = np.random.RandomState(5)
    score = jnp.asarray(rng.randn(N).astype(np.float32))
    if label is None:
        label = jnp.asarray((rng.rand(N) < 0.5).astype(np.float32))

    def grads(s):
        if objective is not None:
            return objective.get_gradients(s)
        p = jax.nn.sigmoid(s)
        return p - label, p * (1.0 - p)

    def make(r):
        @jax.jit
        def reps_fn():
            def body(c, i):
                s = score * (1.0 + 1e-6 * i.astype(jnp.float32))
                g, h = grads(s)
                g3 = jnp.stack([g, h, jnp.ones_like(g)], axis=1)
                return c + g3.sum(), None
            s, _ = lax.scan(body, jnp.float32(0), jnp.arange(r))
            return s
        return reps_fn

    return scan_differential_ms(make, *reps, probes=probes)


def measure_score_update_ms(N, L, n_valid=0, reps=(4, 16), probes=5):
    """Train-score application (gather-free leaf_lookup + add) plus the
    valid-set leaf-value gather add — the deferred score bookkeeping of
    models/gbdt.py's fused step, one class."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from lightgbmv1_tpu.models.tree import leaf_lookup
    from lightgbmv1_tpu.utils.timer import scan_differential_ms

    rng = np.random.RandomState(6)
    table = jnp.asarray(rng.randn(L).astype(np.float32))
    lids = jnp.asarray(rng.randint(0, L, N).astype(np.int32))
    score = jnp.asarray(rng.randn(N).astype(np.float32))
    vlids = (jnp.asarray(rng.randint(0, L, n_valid).astype(np.int32))
             if n_valid else None)
    vscore = (jnp.asarray(rng.randn(n_valid).astype(np.float32))
              if n_valid else None)

    def make(r):
        @jax.jit
        def reps_fn():
            def body(c, i):
                t = table * (1.0 + 1e-6 * i.astype(jnp.float32))
                out = score + leaf_lookup(t, lids)
                acc = out.sum()
                if vlids is not None:
                    acc = acc + (vscore + t[vlids]).sum()
                return c + acc, None
            s, _ = lax.scan(body, jnp.float32(0), jnp.arange(r))
            return s
        return reps_fn

    return scan_differential_ms(make, *reps, probes=probes)


def measure_topk_rank_ms(L, K, reps=(8, 64), probes=5):
    """One ``_topk_by_rank`` frontier ranking (per wave round).  Small op
    — high rep counts keep the differential above timer noise."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from lightgbmv1_tpu.models.grower_wave import _topk_by_rank
    from lightgbmv1_tpu.utils.timer import scan_differential_ms

    rng = np.random.RandomState(7)
    gains = jnp.asarray(rng.randn(L).astype(np.float32))

    def make(r):
        @jax.jit
        def reps_fn():
            def body(c, i):
                vals, leafs = _topk_by_rank(
                    gains * (1.0 + 1e-6 * i.astype(jnp.float32)), K)
                return c + vals.sum() + leafs.sum().astype(jnp.float32), None
            s, _ = lax.scan(body, jnp.float32(0), jnp.arange(r))
            return s
        return reps_fn

    return scan_differential_ms(make, *reps, probes=probes)


def _round_write_inputs(rng, L, L1, K, W, F, B):
    """Synthetic per-round write record at bench shapes (indices fixed
    across reps; values perturbed by the caller to defeat CSE)."""
    import jax.numpy as jnp

    leafs = jnp.asarray(rng.choice(L // 2, K, replace=False).astype(np.int32))
    nls = jnp.asarray((L // 2 + np.arange(K)).astype(np.int32))
    nodes = jnp.asarray((L // 2 - 1 + np.arange(K)).astype(np.int32))
    cidx = jnp.stack([leafs, nls], axis=1).reshape(2 * K)
    res = _fake_split_result(rng, 2 * K, W)
    k3 = rng.randn(K, 3).astype(np.float32)
    return dict(
        res=res,
        cgain=res.gain,
        cidx=cidx, nidx=nodes,
        lidx=leafs, nlidx=nls,
        fix_l=jnp.asarray(rng.randint(0, L1, K).astype(np.int32)),
        fix_r=jnp.asarray(rng.randint(0, L1, K).astype(np.int32)),
        leafs=leafs, nls=nls,
        feats=jnp.asarray(rng.randint(0, F, K).astype(np.int32)),
        thrs=jnp.asarray(rng.randint(0, B, K).astype(np.int32)),
        dls=jnp.asarray(rng.rand(K) < 0.5),
        iscats=jnp.zeros(K, bool),
        bitsets=jnp.zeros((K, W), jnp.uint32),
        mtypes=jnp.zeros(K, jnp.int32),
        vals=jnp.asarray(np.abs(rng.randn(K)).astype(np.float32)),
        pout=jnp.asarray(rng.randn(K).astype(np.float32)),
        psum=jnp.asarray(np.abs(k3)),
        lsums=jnp.asarray(np.abs(k3) * 0.5),
        rsums=jnp.asarray(np.abs(k3) * 0.5),
        csums=jnp.asarray(np.abs(rng.randn(2 * K, 3).astype(np.float32))),
        out_l=jnp.asarray(rng.randn(K).astype(np.float32)),
        out_r=jnp.asarray(rng.randn(K).astype(np.float32)),
        couts=jnp.asarray(rng.randn(2 * K).astype(np.float32)),
        cdepth=jnp.asarray(rng.randint(1, 12, 2 * K).astype(np.int32)),
        cconstr=jnp.zeros((2 * K, 2), jnp.float32),
        num_leaves_new=jnp.asarray(L, jnp.int32),
    )


def measure_assembly_scatter_ms(L, K, F, B, fused=True, use_sub=True,
                                reps=(4, 16), probes=5):
    """One per-round bookkeeping commit: the REAL store write path
    (grower_wave._PackedStore / _FieldStore — the same code objects the
    grower's while-loop body calls) plus the per-leaf histogram-state
    scatter.  This is the sub-phase the fused_bookkeeping lever targets:
    the packed store commits in 3 coalesced scatters, the legacy store in
    ~30 per-field ones."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from lightgbmv1_tpu.models.grower_wave import _FieldStore, _PackedStore
    from lightgbmv1_tpu.utils.timer import scan_differential_ms

    L1 = max(L - 1, 1)
    W = -(-B // 32)
    store = (_PackedStore if fused else _FieldStore)(L, L1, W, False, False)
    rng = np.random.RandomState(8)
    s0 = store.init(_fake_split_result(rng, 1, W, scalar=True),
                    jnp.float32(0.1))
    r0 = _round_write_inputs(rng, L, L1, K, W, F, B)
    hist = jnp.asarray(rng.randn(2 * K, F, B, 3).astype(np.float32))
    leaf_hist0 = jnp.zeros((L, F, B, 3), jnp.float32) if use_sub else None

    def make(r):
        @jax.jit
        def reps_fn():
            def body(carry, i):
                s, lh = carry
                pert = 1.0 + 1e-6 * i.astype(jnp.float32)
                rr = dict(r0)
                rr["vals"] = r0["vals"] * pert
                rr["cgain"] = r0["cgain"] * pert
                s = store.write(s, rr)
                if lh is not None:
                    if store.fused:
                        lh = lh.at[r0["cidx"]].set(hist * pert, mode="drop")
                    else:
                        lh = lh.at[r0["lidx"]].set(hist[0::2] * pert,
                                                   mode="drop")
                        lh = lh.at[r0["nlidx"]].set(hist[1::2] * pert,
                                                    mode="drop")
                return (s, lh), None
            (s, lh), _ = lax.scan(body, (s0, leaf_hist0), jnp.arange(r))
            out = store.gains(s).sum()
            if lh is not None:
                out = out + lh.sum()
            return out
        return reps_fn

    return scan_differential_ms(make, *reps, probes=probes)


def measure_child_meta_ms(L, K, F, B, fused=True, reps=(4, 16), probes=5):
    """Per-round frontier reads + smaller-child subtraction/interleave +
    child metadata stacks (grower_wave body between the histogram pass
    and split finding)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from lightgbmv1_tpu.models.grower_wave import (_FieldStore, _PackedStore,
                                                   subtract_child_hists)
    from lightgbmv1_tpu.utils.timer import scan_differential_ms

    L1 = max(L - 1, 1)
    W = -(-B // 32)
    store = (_PackedStore if fused else _FieldStore)(L, L1, W, False, False)
    rng = np.random.RandomState(9)
    s0 = store.init(_fake_split_result(rng, 1, W, scalar=True),
                    jnp.float32(0.1))
    leafs = jnp.asarray(rng.choice(L // 2, K, replace=False).astype(np.int32))
    order_c = jnp.arange(K, dtype=jnp.int32)
    h_slot = jnp.asarray(rng.randn(K, F, B, 3).astype(np.float32))
    leaf_hist = jnp.asarray(rng.randn(L, F, B, 3).astype(np.float32))
    nls = jnp.asarray((L // 2 + np.arange(K)).astype(np.int32))

    def make(r):
        @jax.jit
        def reps_fn():
            def body(c, i):
                pert = 1.0 + 1e-6 * i.astype(jnp.float32)
                rd = store.read(s0, leafs)
                sm_left = rd["lsums"][:, 2] <= rd["rsums"][:, 2]
                hist, _, _ = subtract_child_hists(
                    h_slot * pert, leaf_hist, leafs, order_c, sm_left)
                csums = jnp.stack([rd["lsums"], rd["rsums"]],
                                  axis=1).reshape(2 * K, 3)
                d = rd["pdepth"] + 1
                cdepth = jnp.stack([d, d], axis=1).reshape(2 * K)
                cleafs = jnp.stack([leafs, nls], axis=1).reshape(2 * K)
                return (c + hist.sum() + csums.sum()
                        + cdepth.sum().astype(jnp.float32)
                        + cleafs.sum().astype(jnp.float32)
                        + rd["pout"].sum()), None
            s, _ = lax.scan(body, jnp.float32(0), jnp.arange(r))
            return s
        return reps_fn

    return scan_differential_ms(make, *reps, probes=probes)


def measure_loop_fixed_ms(L, n_buckets=3, n_rounds=10, reps=(4, 16),
                          probes=5):
    """While-loop + slot-bucket lax.switch control overhead, per round:
    one while_loop of ``n_rounds`` iterations whose body evaluates the
    cond-style frontier max and a ``lax.switch`` over ``n_buckets``
    branches on a small carry — the schedule scaffolding the real round
    body runs around its compute."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from lightgbmv1_tpu.utils.timer import scan_differential_ms

    rng = np.random.RandomState(10)
    gains0 = jnp.asarray(np.abs(rng.randn(L)).astype(np.float32) + 1.0)

    def one_loop(gains):
        def cond(carry):
            i, g = carry
            return (i < n_rounds) & (jnp.max(g) > 0)

        def body(carry):
            i, g = carry
            s_idx = jnp.clip(i % n_buckets, 0, n_buckets - 1)
            g = lax.switch(s_idx, [
                (lambda gg, f=float(b + 1): gg * (1.0 + 1e-7 * f))
                for b in range(n_buckets)
            ], g)
            return i + 1, g

        _, g = lax.while_loop(cond, body, (jnp.int32(0), gains))
        return g

    def make(r):
        @jax.jit
        def reps_fn():
            def body(c, i):
                g = one_loop(gains0 * (1.0 + 1e-6 * i.astype(jnp.float32)))
                return c + g.sum(), None
            s, _ = lax.scan(body, jnp.float32(0), jnp.arange(r))
            return s
        return reps_fn

    return scan_differential_ms(make, *reps, probes=probes) / n_rounds


def measure_split_breakdown(*, F, B, K, rounds_per_iter, meta=None,
                            params=None, num_class=1, reps=(8, 64),
                            probes=5):
    """Named decomposition of ``phase_split_ms`` into the fused scan's
    three stages (ops/split.py — the REAL module-level code objects the
    split search composes, so the attribution cannot drift from what
    training runs), each vmapped over the 2K children of a wave round and
    priced over the round schedule:

    * ``split_cumsum_ms`` — ``scan_left_sums``: the cumulative-sum pass +
      missing-mass adjustments building the (2, F, B, 3) stacked left
      sums (the int8sr dequantize multiply folds here).
    * ``split_gain_ms``   — ``scan_direction_gains``: the stacked
      both-direction gain evaluation + penalty chain.
    * ``split_pick_ms``   — ``scan_pick``: the tie-band preference argmax
      and winner decode.

    Returns a utils.timer.PhaseBreakdown; bench.py records it against
    the measured ``phase_split_ms`` so the remainder (vmap plumbing,
    result assembly, categorical search when present) is explicit."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from lightgbmv1_tpu.ops.split import (NO_CONSTRAINT, scan_direction_gains,
                                          scan_left_sums, scan_pick)
    from lightgbmv1_tpu.utils.timer import PhaseBreakdown, scan_differential_ms

    if meta is None or params is None:
        from lightgbmv1_tpu.ops.split import FeatureMeta, SplitParams

        if params is None:
            params = SplitParams()
        if meta is None:
            meta = FeatureMeta(
                num_bins=jnp.full(F, B, jnp.int32),
                missing_type=jnp.zeros(F, jnp.int32),
                nan_bin=jnp.full(F, -1, jnp.int32),
                zero_bin=jnp.zeros(F, jnp.int32),
                is_categorical=jnp.zeros(F, bool),
                usable=jnp.ones(F, bool),
                monotone_type=jnp.zeros(F, jnp.int32),
            )
    rng = np.random.RandomState(12)
    C = 2 * K                                  # children per round
    h2k = jnp.asarray(np.abs(rng.randn(C, F, B, 3)).astype(np.float32))
    parents = h2k.sum(axis=(1, 2))             # (C, 3)
    mask = jnp.ones(F, bool)
    nc = jnp.asarray(NO_CONSTRAINT, jnp.float32)
    left2 = jax.vmap(lambda h: scan_left_sums(h, meta)[0])(h2k)
    gains0, shift0 = jax.vmap(
        lambda l2, p: scan_direction_gains(l2, p, meta, mask, params, nc)
    )(left2, parents)

    def make_stage(fn):
        def make(r):
            @jax.jit
            def reps_fn():
                def body(c, i):
                    return c + fn(1.0 + 1e-6 * i.astype(jnp.float32)), None
                s, _ = lax.scan(body, jnp.float32(0), jnp.arange(r))
                return s
            return reps_fn
        return make

    def cumsum_stage(pert):
        l2, _ = jax.vmap(lambda h: scan_left_sums(h * pert, meta))(h2k)
        return l2.sum()

    def gain_stage(pert):
        g, _ = jax.vmap(
            lambda l2, p: scan_direction_gains(l2 * pert, p, meta, mask,
                                               params, nc)
        )(left2, parents)
        return jnp.where(jnp.isfinite(g), g, 0.0).sum()

    def pick_stage(pert):
        bg, ft, th, dr = jax.vmap(
            lambda g, s: scan_pick(g * pert, s, meta))(gains0, shift0)
        return (jnp.where(jnp.isfinite(bg), bg, 0.0).sum()
                + (ft + th + dr).sum().astype(jnp.float32))

    R = float(rounds_per_iter) * num_class
    bd = PhaseBreakdown()
    bd.add("split_cumsum_ms",
           scan_differential_ms(make_stage(cumsum_stage), *reps,
                                probes=probes) * R)
    bd.add("split_gain_ms",
           scan_differential_ms(make_stage(gain_stage), *reps,
                                probes=probes) * R)
    bd.add("split_pick_ms",
           scan_differential_ms(make_stage(pick_stage), *reps,
                                probes=probes) * R)
    return bd


def measure_other_breakdown(*, N, F, B, L, K, rounds_per_iter,
                            n_buckets=3, n_valid=0, num_class=1,
                            objective=None, fused=True, use_sub=True,
                            reps=(4, 16), probes=5):
    """Full named decomposition of the per-iteration ``phase_other_ms``
    residual at the given shapes.  Returns a utils.timer.PhaseBreakdown;
    callers (bench.py) pass it the measured residual + wall to emit the
    ``phase_other_breakdown`` record fields."""
    from lightgbmv1_tpu.utils.timer import PhaseBreakdown

    R = float(rounds_per_iter)
    bd = PhaseBreakdown()
    bd.add("grad_g3_ms",
           measure_grad_g3_ms(N, objective=objective, reps=reps,
                              probes=probes) * num_class)
    bd.add("score_update_ms",
           measure_score_update_ms(N, L, n_valid=n_valid, reps=reps,
                                   probes=probes) * num_class)
    topk_reps = (reps[0] * 2, reps[1] * 4)   # small ops: longer scans
    bd.add("topk_rank_ms",
           measure_topk_rank_ms(L, K, reps=topk_reps, probes=probes)
           * R * num_class)
    bd.add("assembly_scatter_ms",
           measure_assembly_scatter_ms(L, K, F, B, fused=fused,
                                       use_sub=use_sub, reps=reps,
                                       probes=probes) * R * num_class)
    bd.add("child_meta_ms",
           measure_child_meta_ms(L, K, F, B, fused=fused, reps=reps,
                                 probes=probes) * R * num_class)
    bd.add("loop_fixed_ms",
           measure_loop_fixed_ms(L, n_buckets=n_buckets, reps=topk_reps,
                                 probes=probes) * R * num_class)
    return bd


# Canonical per-iteration phase fields (BENCH record keys).  The single
# source of truth for "what counts as a phase" — bench.py's phase
# profile and the roofline join both build their {phase: ms} dicts from
# this list, so a new phase lands as its own labeled row everywhere
# instead of silently pooling into phase_other.  Order is render order.
PHASE_MS_KEYS = (
    "phase_hist_ms",
    "phase_partition_ms",
    "phase_valid_route_ms",
    "phase_split_ms",
    "phase_other_ms",
)


def phase_ms_from_fields(fields):
    """``{phase: ms}`` from a BENCH record's phase fields, stripping the
    ``phase_``/``_ms`` wrapping — every positive canonical phase.
    Consumers (bench.py's trace phase profile and the roofline join) go
    through here so the phase list cannot drift per call site."""
    out = {}
    fields = fields or {}
    for k in PHASE_MS_KEYS:
        v = fields.get(k)
        if isinstance(v, (int, float)) and v > 0:
            out[k[len("phase_"):-len("_ms")]] = v
    return out


def split_cost_by_ms(total_flops, total_bytes, phase_ms):
    """Attribute ONE compiled executable's cost analysis (flops, bytes
    accessed — obs/xla.py compile telemetry of the fused/scanned train
    step) over the measured per-phase milliseconds, proportionally.

    This is an ESTIMATE by construction (XLA reports whole-executable
    totals; the proportionality assumption is that arithmetic intensity
    is uniform across phases) — the honest per-phase ground truth is the
    profiler lane, but the proportional table is what makes the roofline
    column computable from an always-on capture.  Returns the
    ``{phase: {"flops", "bytes"}}`` cost table
    :func:`roofline_attribution` consumes, or ``{}`` when either input
    is missing."""
    total_ms = sum(v for v in (phase_ms or {}).values()
                   if isinstance(v, (int, float)) and v > 0)
    if not total_ms or not (total_flops or total_bytes):
        return {}
    table = {}
    for phase, ms in phase_ms.items():
        if not isinstance(ms, (int, float)) or ms <= 0:
            continue
        frac = ms / total_ms
        table[phase] = {
            "flops": float(total_flops) * frac if total_flops else None,
            "bytes": float(total_bytes) * frac if total_bytes else None,
        }
    return table


def roofline_attribution(phase_ms, cost_table, peak_flops_per_s,
                         peak_bytes_per_s=None):
    """Per-phase achieved-fraction-of-peak: join cost-analysis flops /
    bytes (``cost_table`` — ``{phase: {"flops", "bytes"}}``, e.g. from
    :func:`split_cost_by_ms` or a per-phase profiler capture) with the
    MEASURED phase milliseconds against the device ceilings.

    Per phase: ``achieved_tf_s = flops / s / 1e12`` and
    ``frac_of_peak_flops`` against ``peak_flops_per_s``;
    ``achieved_gb_s`` / ``frac_of_peak_bw`` against ``peak_bytes_per_s``
    when given.  ``frac_of_peak`` is the max of the two (the roofline:
    a kernel is as good as its binding resource) and ``bound`` names
    which resource binds.  Phases missing ms or cost rows are omitted —
    absent truth is absent, never zero-filled."""
    rows = {}
    for phase, ms in (phase_ms or {}).items():
        if not isinstance(ms, (int, float)) or ms <= 0:
            continue
        cost = (cost_table or {}).get(phase) or {}
        flops = cost.get("flops")
        nbytes = cost.get("bytes")
        if not flops and not nbytes:
            continue
        sec = ms / 1e3
        row = {"ms": round(float(ms), 3)}
        frac_f = frac_b = None
        if flops and peak_flops_per_s:
            row["achieved_tf_s"] = round(flops / sec / 1e12, 4)
            frac_f = flops / sec / float(peak_flops_per_s)
            row["frac_of_peak_flops"] = round(frac_f, 4)
        if nbytes and peak_bytes_per_s:
            row["achieved_gb_s"] = round(nbytes / sec / 1e9, 3)
            frac_b = nbytes / sec / float(peak_bytes_per_s)
            row["frac_of_peak_bw"] = round(frac_b, 4)
        candidates = [f for f in (frac_f, frac_b) if f is not None]
        if not candidates:
            continue
        row["frac_of_peak"] = round(max(candidates), 4)
        row["bound"] = ("compute"
                        if frac_f is not None
                        and (frac_b is None or frac_f >= frac_b)
                        else "memory")
        rows[phase] = row
    return rows


def main():
    """Standalone small-shape run (CPU-safe); prints one JSON line."""
    bd = measure_other_breakdown(N=20_000, F=8, B=16, L=31, K=8,
                                 rounds_per_iter=6.0, n_valid=2_000,
                                 probes=3)
    sbd = measure_split_breakdown(F=8, B=16, K=8, rounds_per_iter=6.0,
                                  probes=3)
    print(json.dumps({"phase_other_breakdown": bd.parts,
                      "attributed_ms": round(bd.total_attributed(), 3),
                      "phase_split_breakdown": sbd.parts,
                      "split_attributed_ms": round(
                          sbd.total_attributed(), 3)}))


if __name__ == "__main__":
    main()
