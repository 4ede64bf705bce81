"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once through the entry points a user
calls, at the full width of the cell the repo has always headlined
(HIGGS-shaped binary, 1,000,000 x 28, 255 leaves, 63 bins), and checks
what comes out by the repo's own means.  It is NOT a benchmark: depth is
cut to 20 trees, every time it prints is a smoke figure, and the summary
ends with ``"claim": null``.

    python chip_smoke.py                 # needs a TPU; exits non-zero without
    python chip_smoke.py --rehearse-cpu  # tiny CPU / Pallas-interpret
                                         # rehearsal of the same control flow

Legs, in order (every leg runs unguarded — a failure is a traceback and a
non-zero exit; the only ``except`` is the one that DEFINES the opt-in leg,
where "the kernel raised" is one of the two passing outcomes):

  wide       one 255-leaf tree at 2,000 columns x 4,096 rows: the split
             scan's widest compiled shape (126 children x 2,000 x 64), first,
             so that a scan the chip's compiler refuses shows in seconds
  train      lightgbmv1_tpu.train, 20 iterations, 100k held-out rows
  hist       hist_leaves_pallas vs numpy.bincount at the train shape
  hist256    the same at max_bin=255, the library's default: two trees
             through lightgbmv1_tpu.train, then the kernel's 256-bin rung
             on the lane-dense operand the trainer placed, 1-64 slots
  hist64dense  the 64-bin rung on the lane-dense operand (the form the
             bytes rule gives a table whose blocks are over a quarter of the
             device: hist_pallas.hist_bins_form) against one array a block,
             67 columns at 63 bins, 1 and 63 slots: ``equal`` or ``differs``
  partition  the row-tiled partition kernel vs the gather form it stands
             for, 1-64 slots on 28 and 137 columns: integers equal, and the
             ms a round of both (what ``partition_path``'s rule is fitted
             from, at this leg's rows)
  predict    Booster.predict depthwise (device) vs host
  serve      build_server + ServeHTTP on port 0 in this process
  variants   kernels that engage on their own: packed4, int8sr
  optin      kernels behind a knob: each ran-and-matched, or raised
  multichip  tree_learner=data over four chips when four are visible; on
             the chip every device holds the kernel's bin operand as it
             laid its own shard out at placement (128-lane blocks)

The last two stdout lines are JSON objects: the summary (``"leg":
"summary"``, every figure and parity delta, ending ``"claim": null``), then
the verdict the driver reads, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from types import SimpleNamespace

import numpy as np

import jax

import lightgbmv1_tpu as lgb
from lightgbmv1_tpu.config import Config
from lightgbmv1_tpu.metrics import AUCMetric
from lightgbmv1_tpu.models.grower_wave import auto_wave_size, slot_buckets_for
from lightgbmv1_tpu.obs import xla as obs_xla
from lightgbmv1_tpu.ops.hist_pallas import (MAX_ROW_TILE, HistBins,
                                            bin_matrix, hist_leaves_pallas,
                                            kernel_width, prepare_hist_bins)
from lightgbmv1_tpu.ops.partition_pallas import (partition_gather,
                                                 partition_pallas,
                                                 partition_path)
from lightgbmv1_tpu.parallel.trainer import resolve_deep_dtype
from lightgbmv1_tpu.serve import ServeHTTP
from lightgbmv1_tpu.serve.server import build_server

F = 28
ITERS = 20
PARAMS = {"objective": "binary", "num_leaves": 255, "max_bin": 63,
          "learning_rate": 0.1, "min_data_in_leaf": 20, "metric": "auc",
          "verbosity": -1}

# Held-out AUC floor at iteration 20.  Obtained from the CPU run of this
# same leg at reduced rows (100,000 train / 100,000 held-out, scatter
# histograms in exact f32): AUC 0.89228 at iteration 20.  Twenty trees
# are capacity-bound, not data-bound (the 1M-row v5e run of PR 21 read
# 0.89186), so the floor is the reduced-row figure less a margin for the
# bf16x2/bf16 histogram datapath.
AUC_FLOOR = 0.885
# the tiny rehearsal (20,000 rows, 5 iterations) only has to learn at all
AUC_FLOOR_REHEARSAL = 0.75

# Histogram tolerances against float64 numpy.bincount, per cell, as a
# fraction of that cell's sum of |values| (hard rounding bounds, not
# statistics).  bf16 keeps 8 significant bits: each addend is off by at
# most 2^-8 of itself.  bf16x2 adds the bf16 of the residual: 2^-16.  Both
# get headroom for the f32 accumulation across ~1000 row tiles.  Counts
# (1.0 per row, f32 accumulate, < 2^24) must be exact.
HIST_TOL = {"bf16x2": 2.0 ** -14, "bf16": 2.0 ** -7}


def make_data(n, seed):
    """bench.make_data's formula: HIGGS-shaped synthetic from a seed."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    logit = (X[:, 0] * 1.2 - X[:, 1] + 0.6 * X[:, 2] * X[:, 3]
             + 0.4 * X[:, 4] + 0.3 * np.sin(3.0 * X[:, 5]))
    y = (logit + rng.randn(n).astype(np.float32) > 0).astype(np.float64)
    return X, y


def auc(y, score):
    """AUC by the repo's own metric (metrics.AUCMetric: exact, half credit
    for ties) — the same code that scores the device's valid set."""
    metric = AUCMetric(Config())
    metric.init(SimpleNamespace(label=y, weight=None), len(y))
    return float(metric.eval(np.asarray(score))[0][1])


def tree_signature(booster):
    """Structure + leaf values per tree, tests/test_parallel.py's view."""
    return [(t.num_leaves, tuple(t.split_feature), tuple(t.threshold_bin),
             np.asarray(t.leaf_value, np.float64))
            for t in booster._all_trees()]


def assert_trees_agree(a, b, what, exact=False):
    """Agreement as tests/test_parallel.py defines it: structure exact,
    leaf values to rtol 1e-3 / atol 1e-5 (``exact``: bit-equal).  Returns
    the largest leaf value difference."""
    sa, sb = tree_signature(a), tree_signature(b)
    assert len(sa) == len(sb), f"{what}: {len(sa)} vs {len(sb)} trees"
    worst = 0.0
    for i, (ta, tb) in enumerate(zip(sa, sb)):
        assert ta[:3] == tb[:3], f"{what}: tree {i} structure differs"
        if exact:
            assert np.array_equal(ta[3], tb[3]), \
                f"{what}: tree {i} leaf values not bit-equal"
        np.testing.assert_allclose(ta[3], tb[3], rtol=1e-3, atol=1e-5,
                                   err_msg=f"{what}: tree {i} leaf values")
        if len(ta[3]):
            worst = max(worst, float(np.max(np.abs(ta[3] - tb[3]))))
    return worst


class Smoke:
    def __init__(self, rehearse: bool):
        dev = jax.devices()[0]
        self.device = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())}
        self.rehearse = rehearse
        self.n, self.n_valid, self.iters = (
            (20_000, 5_000, 5) if rehearse else (1_000_000, 100_000, ITERS))
        self.out = {}
        self.t0 = time.perf_counter()

    def say(self, leg, **fields):
        """One line per leg, stamped with the platform it ran on."""
        fields = {"leg": leg, "platform": self.device["platform"],
                  "t_s": round(time.perf_counter() - self.t0, 1), **fields}
        print(json.dumps(fields, default=str), flush=True)
        self.out[leg] = {k: v for k, v in fields.items()
                         if k not in ("leg", "platform")}

    # -- the wide shape -----------------------------------------------------
    def wide(self):
        """One tree over 2,000 dense columns (the width of
        ``epsilon-2000f-63b``): a round's scan covers 126 children x 2,000
        columns x 64 bins, which the channel-minor scan could not compile
        for the chip at all (PERF.md, PR 32)."""
        from lightgbmv1_tpu.obs.metrics import default_registry

        columns, rows = 2_000, 512 if self.rehearse else 4_096
        rng = np.random.RandomState(7)
        X = rng.randn(rows, columns).astype(np.float32)
        y = (X[:, 0] - X[:, 1_999] + 0.5 * X[:, 1_000]
             + 0.3 * rng.randn(rows) > 0).astype(np.float64)
        params = {**PARAMS, "min_data_in_leaf": 1}
        t = time.perf_counter()
        booster = lgb.train(
            params, lgb.Dataset(X, label=y, params=dict(params)),
            num_boost_round=1, verbose_eval=False)
        leaves = int(booster._all_trees()[0].num_leaves)
        assert leaves > 8, f"the wide tree grew {leaves} leaves"
        scanned = default_registry().get("split_scan_columns").labels(
            what="scanned").get()
        assert scanned == columns, scanned
        self.say("wide", rows=rows, columns=columns, leaves=leaves,
                 scan_columns=int(scanned),
                 tree_s_with_compile=round(time.perf_counter() - t, 2))

    # -- train ------------------------------------------------------------
    def train(self):
        self.X, self.y = make_data(self.n, 0)
        self.Xv, self.yv = make_data(self.n_valid, 1)
        compiled_before_ms = obs_xla.compile_ms_total()   # the wide leg's
        t = time.perf_counter()
        self.dtrain = lgb.Dataset(self.X, label=self.y,
                                  params=dict(PARAMS)).construct()
        self.dvalid = lgb.Dataset(self.Xv, label=self.yv,
                                  reference=self.dtrain,
                                  params=dict(PARAMS)).construct()
        bin_s = time.perf_counter() - t

        evals, ticks = {}, [time.perf_counter()]
        self.booster = lgb.train(
            dict(PARAMS), self.dtrain, num_boost_round=self.iters,
            valid_sets=[self.dvalid], evals_result=evals,
            callbacks=[lambda env: ticks.append(time.perf_counter())],
            verbose_eval=False)
        # each iteration ends in the valid-set AUC on the host, so the
        # ticks are synchronized with the device
        per_iter = np.diff(ticks)
        self.auc_curve = evals["valid_0"]["auc"]
        auc_dev = float(self.auc_curve[-1])
        floor = AUC_FLOOR_REHEARSAL if self.rehearse else AUC_FLOOR
        assert len(self.auc_curve) == self.iters
        assert auc_dev > floor, f"held-out AUC {auc_dev} <= floor {floor}"

        # the host oracle: model text -> HostTree walk in float64
        self.oracle = lgb.Booster(
            model_str=self.booster.model_to_string())
        self.raw_host = np.asarray(self.oracle.predict(
            self.Xv, raw_score=True, predict_method="host"), np.float64)
        auc_host = auc(self.yv, self.raw_host)
        # device scores are f32 sums over binned routing, the oracle f64
        # sums over real thresholds: the ranking may differ only where two
        # rows are within f32 round-off of each other
        assert abs(auc_host - auc_dev) < 1e-5, (auc_host, auc_dev)

        stats = obs_xla.compile_stats()
        fallbacks = {k: v["fallbacks"] for k, v in stats.items()}
        assert not any(fallbacks.values()), fallbacks
        self.say("train", rows=self.n, features=F, iters=self.iters,
                 bin_s=round(bin_s, 2),
                 first_iter_s=round(float(per_iter[0]), 2),
                 iter_s_after_warmup_smoke_figure=round(
                     float(np.median(per_iter[2:])), 4),
                 compile_s=round((obs_xla.compile_ms_total()
                                  - compiled_before_ms) / 1e3, 2),
                 auc_device=auc_dev, auc_host_oracle=auc_host,
                 auc_delta=abs(auc_host - auc_dev),
                 compile_labels=sorted(stats), fallbacks=0)

    # -- histogram kernel vs numpy.bincount --------------------------------
    def check_hist(self, binned, bins_np, B, L, precisions, packed=False,
                   dead=False):
        """hist_leaves_pallas over ``binned`` with ``L`` slots against
        float64 numpy.bincount over ``bins_np`` (the same bins, (F, N),
        unpacked).  ``dead``: as the wave grower calls it —
        ``L`` live slots and rows labelled ``L`` (some far above it) that
        must land nowhere.  Returns {precision: worst error / sum|values|}."""
        rng = np.random.RandomState(100 + L + 1000 * dead)
        label = rng.randint(0, L + dead, self.n).astype(np.int32)
        live = label < L
        if dead:
            label[::97] = np.where(live[::97], label[::97], L + 1000)
        base = np.where(live, label, 0).astype(np.int64) * B
        interpret = self.device["platform"] == "cpu"

        def reference(w):
            out = np.empty((L, F, B, w.shape[1]))
            for f in range(F):
                idx = base + bins_np[f]
                for c in range(w.shape[1]):
                    out[:, f, :, c] = np.bincount(
                        idx, weights=w[:, c] * live, minlength=L * B
                    ).reshape(L, B)
            return out

        def kernel(g3, precision):
            h = np.asarray(hist_leaves_pallas(
                binned, jax.numpy.asarray(g3), jax.numpy.asarray(label), L,
                B, precision=precision, interpret=interpret, packed=packed,
                num_features=F if packed else 0))
            assert h.shape == (L, F, B, 3) and np.isfinite(h).all()
            return h

        worst = {}
        g3 = np.stack([rng.randn(self.n), rng.rand(self.n) + 0.1,
                       np.ones(self.n)], axis=1).astype(np.float32)
        ref = ref_abs = None
        for precision in precisions:
            if precision == "int8sr":
                # rows arrive pre-quantized to integers in [-127, 127]: the
                # int8 MXU path must reproduce the integer sums exactly
                q3 = g3.copy()
                q3[:, :2] = rng.randint(-127, 128, (self.n, 2))
                assert np.array_equal(kernel(q3, precision),
                                      reference(q3.astype(np.float64))), \
                    f"int8sr L={L}: integer histogram not exact"
                worst[precision] = 0.0
                continue
            if ref is None:
                ref = reference(g3.astype(np.float64))
                ref_abs = reference(np.abs(g3[:, :2]).astype(np.float64))
            h, tol = kernel(g3, precision), HIST_TOL[precision]
            assert np.array_equal(h[..., 2], ref[..., 2]), \
                f"{precision} L={L}: counts not exact"
            err = np.abs(h[..., :2] - ref[..., :2])
            worst[precision] = float(np.max(err / np.maximum(ref_abs, 1e-30)))
            assert np.all(err <= tol * ref_abs + 1e-6), \
                f"{precision} L={L}: worst {worst[precision]} > {tol}"
        return worst

    def ladder(self):
        """The slot buckets of the wave ladder the 255-leaf grower runs."""
        return slot_buckets_for(auto_wave_size(PARAMS["num_leaves"]), self.n)

    def hist(self):
        gb = self.booster._gbdt
        B = int(gb.num_bins)
        bins_np = np.asarray(gb.binned)
        # 1 slot = the root pass; then every bucket of the wave ladder the
        # 255-leaf grower runs, once with a slot more for its dead rows
        # (the scatter / onehot methods' form, every slot live) and once as
        # the trainer asks the kernel: the live slots, dead rows dropped
        ladder = self.ladder()
        worst, worst_live = {}, {}
        for L in [1] + [S + 1 for S in ladder]:
            precisions = ["bf16x2", "bf16"] + (["int8sr"] if L > 5 else [])
            worst[f"slots{L}"] = self.check_hist(gb.binned, bins_np, B, L,
                                                 precisions)
        for S in ladder:
            precisions = ["bf16x2", "bf16"] + (["int8sr"] if S > 4 else [])
            worst_live[f"live{S}"] = self.check_hist(
                gb.binned, bins_np, B, S, precisions, dead=True)
        self.say("hist", shape=[F, self.n], num_bins=B, ladder=ladder,
                 counts="exact", int8sr="exact",
                 worst_error_over_sum_abs=worst,
                 live_slots_worst_error_over_sum_abs=worst_live,
                 tolerance={k: float(v) for k, v in HIST_TOL.items()})

    # -- device predict vs host -------------------------------------------
    def hist256(self):
        p255 = {**PARAMS, "max_bin": 255}
        d255 = lgb.Dataset(self.X, label=self.y,
                           params=dict(p255)).construct()
        booster = lgb.train(dict(p255), d255, num_boost_round=2,
                            verbose_eval=False)
        gb = booster._gbdt
        B = int(gb.num_bins)
        assert kernel_width(B) == 256 and B > 64, B
        # on the chip the serial learner hands the kernel the operand it
        # laid out at placement: the 28 columns side by side in one array
        operand = gb._grow_binned
        prepared = isinstance(operand, HistBins)
        assert prepared == (self.device["platform"] == "tpu"), type(operand)
        if prepared:
            assert (operand.tile_cols, operand.windows,
                    [b.shape[1] for b in operand.blocks]) == (8, 16, [128])
        bins_np = np.asarray(gb.binned)
        ladder = self.ladder()
        worst = {f"slots{L}": self.check_hist(operand, bins_np, B, L,
                                              ["bf16x2", "bf16"])
                 for L in (1, 64)}
        worst_live = {f"live{S}": self.check_hist(
            operand, bins_np, B, S, ["bf16x2", "bf16"], dead=True)
            for S in ladder}
        self.say("hist256", shape=[F, self.n], num_bins=B, ladder=ladder,
                 prepared_operand=prepared, counts="exact",
                 worst_error_over_sum_abs=worst,
                 live_slots_worst_error_over_sum_abs=worst_live)

    # -- the 64 rung's two operand forms ------------------------------------
    def hist64dense(self):
        """A pass of the 64-bin rung over the lane-dense operand (67 columns
        in one ``u8[n_pad, 128]`` array, three 32-column windows picked by
        the MXU) and over one array a block: the same one-hot, the same
        left operand, the same row tiles, so the same bits."""
        columns, B = 67, 64
        rows = 3_000 if self.rehearse else 200_000
        rng = np.random.RandomState(64)
        bins = jax.numpy.asarray(
            rng.randint(0, B - 1, (columns, rows)).astype(np.uint8))
        g3 = jax.numpy.asarray(np.stack(
            [rng.randn(rows), rng.rand(rows) + 0.1, np.ones(rows)],
            axis=1).astype(np.float32))
        dense = prepare_hist_bins(bins, B, dense=True)
        block = prepare_hist_bins(bins, B)
        assert (dense.windows, len(dense.blocks)) == (4, 1)
        assert (block.windows, len(block.blocks)) == (1, 3)
        interpret = self.device["platform"] == "cpu"
        verdict = {}
        for slots, precision in ((1, "bf16x2"), (63, "bf16")):
            label = jax.numpy.asarray(
                rng.randint(0, slots + 1, rows).astype(np.int32))
            got, want = (np.asarray(hist_leaves_pallas(
                operand, g3, label, slots, B, precision=precision,
                interpret=interpret)) for operand in (dense, block))
            assert got.shape == (slots, columns, B, 3)
            assert got[..., 2].sum() == float((np.asarray(label) < slots).sum()
                                              * columns)
            verdict[f"slots{slots}_{precision}"] = (
                "equal" if np.array_equal(got, want) else "differs")
        self.say("hist64dense", shape=[columns, rows], num_bins=B,
                 lane_dense_vs_block=verdict)
        assert set(verdict.values()) == {"equal"}, verdict

    # -- partition kernel vs the gather form --------------------------------
    def partition(self):
        """One round's partition of the train leg's rows in both memory
        forms (ops/partition_pallas.py): the new leaf ids and the labels
        must be the same integers at every slot count and both labelings;
        the ms a round are a smoke figure, printed for the width rule."""
        import jax.numpy as jnp

        interpret = self.device["platform"] == "cpu"
        rounds = 2 if self.rehearse else 10
        rng = np.random.RandomState(35)
        B = int(self.booster._gbdt.num_bins)
        matrices = {
            F: jnp.asarray(np.asarray(self.booster._gbdt.binned)),
            137: jnp.asarray(rng.randint(0, B, (137, self.n), np.uint8))}

        def ms(fn, *args):
            """Median ms a round over three runs of ``rounds`` chained
            rounds in one program (no dispatch in the figure)."""
            @jax.jit
            def chain(bins, leaf_id, cols):
                def body(_, carry):
                    new, label = fn(bins, carry[0], cols)
                    return new % 8, carry[1] + label    # ids the slots split
                return jax.lax.fori_loop(
                    0, rounds, body, (leaf_id, jnp.zeros_like(leaf_id)))

            jax.block_until_ready(chain(*args))
            ticks = []
            for _ in range(3):
                t = time.perf_counter()
                jax.block_until_ready(chain(*args))
                ticks.append((time.perf_counter() - t) * 1e3 / rounds)
            return round(float(np.median(ticks)), 4)

        table = {}
        for columns, bins in matrices.items():
            assert bins.shape == (columns, self.n) and bins.dtype == jnp.uint8
            for S in (1, 4, 16, 63, 64):
                live = max(1, S - 2)
                leaf_id = jnp.asarray(
                    rng.randint(0, live + 3, self.n).astype(np.int32))
                cols = dict(
                    feats=rng.randint(0, columns, S),
                    thrs=rng.randint(0, B, S), dls=rng.rand(S) < 0.5,
                    leafs=np.where(np.arange(S) < live, np.arange(S), 255),
                    nls=100 + np.arange(S), sml=rng.rand(S) < 0.5,
                    mt=rng.randint(0, 3, S), nan=np.full(S, B - 1),
                    zero=rng.randint(0, B, S))
                cols = {k: jnp.asarray(v if v.dtype == bool
                                       else v.astype(np.int32))
                        for k, v in cols.items()}
                row = {"rule": partition_path(columns, S, self.n,
                                              pallas=True, layout="u8",
                                              use_cat=False)}
                for use_sub in (True, False):
                    gather = functools.partial(partition_gather,
                                               use_sub=use_sub)
                    kernel = functools.partial(partition_pallas,
                                               use_sub=use_sub,
                                               interpret=interpret)
                    want = gather(bins, leaf_id, cols)
                    got = kernel(bins, leaf_id, cols)
                    for g, w in zip(got, want):
                        assert np.array_equal(np.asarray(g), np.asarray(w)), \
                            f"partition kernel differs: {columns} columns, " \
                            f"{S} slots, use_sub={use_sub}"
                    assert int(jnp.max(got[1])) == (S if use_sub else 2 * S)
                row["gather_ms"] = ms(gather, bins, leaf_id, cols)
                row["kernel_ms"] = ms(kernel, bins, leaf_id, cols)
                table[f"{columns}x{S}"] = row
        self.say("partition", rows=self.n, integers="equal",
                 labelings=["smaller_child", "pool_free"],
                 ms_a_round_smoke_figure=table)

    def predict(self):
        before = obs_xla.compile_counts().get("predict.leaf", 0)
        leaf_dev = self.booster.predict(self.Xv, pred_leaf=True,
                                        predict_method="depthwise")
        assert obs_xla.compile_counts().get("predict.leaf", 0) > before, \
            "depthwise predict did not compile a device walk"
        leaf_host = self.booster.predict(self.Xv, pred_leaf=True,
                                         predict_method="host")
        assert np.array_equal(leaf_dev, leaf_host), "leaf indices differ"
        raw_dev = np.asarray(self.booster.predict(
            self.Xv, raw_score=True, predict_method="depthwise"))
        assert raw_dev.shape == (self.n_valid,) and np.isfinite(raw_dev).all()
        delta = float(np.max(np.abs(raw_dev - self.raw_host)))
        # f32 sum of `iters` leaf values against the f64 oracle
        assert delta < 1e-5, delta
        self.say("predict", rows=self.n_valid, leaf_indices="equal",
                 raw_score_max_delta=delta)

    # -- in-process server --------------------------------------------------
    def serve(self):
        cfg = Config.from_dict({"task": "serve", "predict_f64_scores": True,
                                "verbosity": -1})
        server = build_server(self.oracle, cfg)
        http = ServeHTTP(server, port=0).start()
        sizes, got = (1, 7, 64, 300, 1000), {}

        def client():
            lo = 0
            for n in sizes:
                body = json.dumps(
                    {"rows": self.Xv[lo: lo + n].tolist()}).encode()
                req = urllib.request.Request(
                    f"http://127.0.0.1:{http.port}/predict", data=body,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=120) as resp:
                    got[(lo, n)] = json.loads(resp.read())
                lo += n

        with ThreadPoolExecutor(max_workers=1,
                                thread_name_prefix="smoke-client") as pool:
            pool.submit(client).result(timeout=600)   # re-raises its error
        http.shutdown()
        server.close()
        assert len(got) == len(sizes)
        for (lo, n), payload in got.items():
            vals = np.asarray(payload["values"], np.float64)[:, 0]
            # predict_f64_scores: device leaf indices, scores rebuilt in
            # f64 in tree order — bit-equal to the host oracle
            assert np.array_equal(vals, self.raw_host[lo: lo + n]), \
                f"serve answer for rows {lo}:{lo + n} differs from oracle"
        assert not server.dispatcher_alive(), "dispatcher outlived close()"
        self.say("serve", requests=len(got), rows=int(sum(sizes)),
                 answers="equal to host oracle", shutdown="clean")

    # -- kernels that engage without being named ---------------------------
    def variants(self):
        # max_bin=15: bin_layout=auto packs two bins per byte when the
        # Pallas kernel family is in play (TPU); its twin stores u8
        p15 = {**PARAMS, "max_bin": 15}
        d15 = lgb.Dataset(self.X, label=self.y, params=dict(p15)).construct()
        packed = lgb.train(dict(p15), d15, num_boost_round=2,
                           verbose_eval=False)
        twin = lgb.train({**p15, "bin_layout": "u8"}, d15,
                         num_boost_round=2, verbose_eval=False)
        engaged = bool(packed._gbdt._packed)
        assert engaged == (self.device["platform"] == "tpu"), engaged
        assert not twin._gbdt._packed
        # same row tiles, independent output cells: bit-identical trees
        # (tests/test_packed_bins.py)
        assert_trees_agree(packed, twin, "packed4 vs u8", exact=True)
        packed_hist = None
        if engaged:
            # the pass over the packed matrix against the plain reference too
            packed_hist = self.check_hist(
                packed._gbdt.binned,
                np.asarray(twin._gbdt.binned),
                int(packed._gbdt.num_bins), 64, ["bf16x2", "bf16", "int8sr"],
                packed=True)

        # hist_dtype_deep=auto: int8sr deep rounds on tpu, bf16x2 on cpu.
        # Its bf16 twin is the main run (same data, params and seed), so
        # the twin's 2-iteration AUC is already on the curve.
        deep = resolve_deep_dtype("auto", "bf16x2", self.device["platform"])
        ev = {}
        q = lgb.train({**PARAMS, "hist_dtype_deep": "auto"}, self.dtrain,
                      num_boost_round=2, valid_sets=[self.dvalid],
                      evals_result=ev, verbose_eval=False)
        auc_q, auc_twin = float(ev["valid_0"]["auc"][-1]), self.auc_curve[1]
        # the CPU tests hold int8sr to "trains to a sane AUC"
        # (tests/test_int8sr.py); at 2 trees a quantized deep pass may
        # move the held-out AUC by a few 1e-3 at most
        assert abs(auc_q - auc_twin) < 5e-3, (auc_q, auc_twin)
        differs = q.model_to_string(num_iteration=2) != \
            self.booster.model_to_string(num_iteration=2)
        if deep == "int8sr":
            assert differs, "int8sr resolved but trees equal the bf16 twin"
        self.say("variants", packed4_engaged=engaged,
                 packed4_vs_u8="bit-equal trees",
                 packed4_hist_worst_error_over_sum_abs=packed_hist,
                 deep_dtype_auto=deep, int8sr_trees_differ_from_twin=differs,
                 auc_deep_auto_2it=auc_q, auc_bf16_twin_2it=auc_twin)

    # -- kernels behind a knob ---------------------------------------------
    def optin(self):
        """One attempt per opt-in kernel.  Passing outcomes: the kernel
        RAN (engagement read from the program: the predictor's method and
        plan) and matched the host walk — or it RAISED.  A result that came
        back by another path fails."""
        Xs = self.Xv[:4096]

        leaf_host = self.oracle.predict(Xs, pred_leaf=True,
                                        predict_method="host")

        def walk(method, label):
            n0 = obs_xla.compile_counts().get(label, 0)
            leaf = self.oracle.predict(Xs, pred_leaf=True,
                                       predict_method=method)
            bp = self.oracle._device_pred_cache[1]
            assert bp is not None and bp.method == method
            if method == "fused" and not bp._fused_engaged():
                return {"outcome": "planner refused: "
                                   + bp.fused_plan["reason"]}
            assert obs_xla.compile_counts().get(label, 0) > n0
            assert np.array_equal(leaf, leaf_host), f"{method}: leaves differ"
            return {"outcome": "ran and matched staged"}

        attempts = {
            "predict_method=pallas": lambda: walk("pallas", "predict.leaf"),
            "predict_method=fused": lambda: walk("fused", "predict.fused"),
        }
        table = {}
        for name, attempt in attempts.items():
            try:
                table[name] = attempt()
            except AssertionError:
                raise       # a wrong or re-routed result fails the smoke
            except Exception as e:  # the kernel raised: a passing outcome
                msg = " ".join(str(e).split())
                table[name] = {"outcome": "raised",
                               "error": f"{type(e).__name__}: {msg[:300]}"}
        self.say("optin", table=table)

    # -- four chips ----------------------------------------------------------
    def check_shard_operand(self, gbdt, widths):
        """The data learner's placed bins: the matrix's shards on four
        devices and, on the chip, the prepared operand (``HistBins``) every
        chip laid out from its own shard: blocks ``widths`` wide, 4 x a
        shard's padded rows tall, their shards on the same four devices."""
        operand = gbdt._grow_binned
        matrix = bin_matrix(operand)
        devs = {s.device for s in matrix.addressable_shards}
        assert len(devs) == 4, f"binned matrix sits on {len(devs)} device(s)"
        prepared = isinstance(operand, HistBins)
        assert prepared == (self.device["platform"] == "tpu"), type(operand)
        if prepared:
            n_loc = matrix.shape[1] // 4
            rows = 4 * (-(-n_loc // MAX_ROW_TILE) * MAX_ROW_TILE)
            assert [b.shape for b in operand.blocks] == [
                (rows, w) for w in widths], [b.shape for b in operand.blocks]
            for b in operand.blocks:
                assert {s.device for s in b.addressable_shards} == devs
                assert {s.data.shape[0] for s in b.addressable_shards} == {
                    rows // 4}
        return devs

    def multichip(self):
        """tree_learner=data over four chips.  Two comparisons with the
        serial learner:

        * at tests/test_parallel.py's own shape (1000 x 7, default leaves,
          5 iterations) agreement is what that file defines: structure
          exact, leaf values to rtol 1e-3 / atol 1e-5;
        * at the train leg's shape that definition cannot hold, on any
          backend: 22 of the 28 features are noise and 255 leaves reach
          splits whose best two candidates tie to f32 round-off, so the
          shard-order of the histogram sum picks the other one (seen on
          the CPU mesh at tree 0).  What is invariant there: the trees
          are identical up to the first such node, the two gains AT that
          node agree to 1e-3 relative (a tie, not an error) where the
          node was split in a full-precision round, and the held-out AUC
          agrees to 2e-3.  The rounds of the ladder's largest bucket sum
          single-bfloat16 addends on the chip and the scan ranks splits on
          those sums (PERF.md, Open question 0 (i)), while a stored gain is
          measured again from the rows (models/renew.py): a flip there is
          between candidates the scan could not tell apart, whose stored
          gains need not tie (2.2% apart at node 183 on four v5e chips,
          2026-10-04).  It is recorded, and the AUC holds the model.
        """
        n_dev = self.device["count"]
        if n_dev < 4:
            self.say("multichip", multichip=f"not run: {n_dev} device(s)")
            return
        dp = {"tree_learner": "data", "num_shards": 4}

        rng = np.random.RandomState(5)
        Xs = rng.randn(1000, 7)
        ys = (Xs[:, 0] - Xs[:, 1] + 0.5 * Xs[:, 2]
              + 0.3 * rng.randn(1000) > 0).astype(np.float64)
        small = {"objective": "binary", "min_data_in_leaf": 5,
                 "verbosity": -1}
        ds = lgb.Dataset(Xs, label=ys, params=dict(small)).construct()
        serial_s = lgb.train(dict(small), ds, num_boost_round=5,
                             verbose_eval=False)
        par_s = lgb.train({**small, **dp}, ds, num_boost_round=5,
                          verbose_eval=False)
        small_delta = assert_trees_agree(par_s, serial_s,
                                         "data-parallel x4, 1000 x 7")

        ev, ticks = {}, [time.perf_counter()]
        par = lgb.train({**PARAMS, **dp}, self.dtrain,
                        num_boost_round=self.iters, valid_sets=[self.dvalid],
                        evals_result=ev, verbose_eval=False,
                        callbacks=[lambda env: ticks.append(
                            time.perf_counter())])
        devs = self.check_shard_operand(par._gbdt, [128])
        assert par._gbdt._grow.label == "grow.data"
        # the four-chip cell's width: 67 columns are three 32-column blocks
        Xw = rng.randn(40_000, 67)
        wide = lgb.train(
            {**small, **dp, "max_bin": 63},
            lgb.Dataset(Xw, label=(Xw[:, 0] > Xw[:, 1]).astype(np.float64)),
            num_boost_round=2, verbose_eval=False)
        assert self.check_shard_operand(wide._gbdt, [128] * 3) == devs
        # tree 0 grows from the same gradients on both sides
        a, b = par._all_trees()[0], self.booster._all_trees()[0]
        n_nodes = min(a.num_leaves, b.num_leaves) - 1
        same = (np.asarray(a.split_feature[:n_nodes])
                == np.asarray(b.split_feature[:n_nodes])) \
            & (np.asarray(a.threshold_bin[:n_nodes])
               == np.asarray(b.threshold_bin[:n_nodes]))
        first_diff = None if same.all() else int(np.argmin(same))
        # nodes of the full-precision rounds: the frontier doubles until a
        # round's splits pass the ladder's second-largest bucket
        ladder = self.ladder()
        full = (n_nodes if len(ladder) < 2 or self.device["platform"] != "tpu"
                else sum(2 ** r for r in range(ladder[-2].bit_length())))
        flip_gap = None
        if first_diff is not None:
            ga, gb = (float(t.split_gain[first_diff]) for t in (a, b))
            flip_gap = abs(ga - gb) / max(ga, gb)
            assert first_diff >= full or flip_gap <= 1e-3, \
                f"tree 0 node {first_diff}: gains {ga} vs {gb} are no tie"
        auc_par = float(ev["valid_0"]["auc"][-1])
        auc_ser = float(self.auc_curve[-1])
        assert abs(auc_par - auc_ser) < 2e-3, (auc_par, auc_ser)
        self.say("multichip",
                 multichip="ran and agreed with serial",
                 shard_devices=sorted(str(d) for d in devs),
                 prepared_operand=isinstance(par._gbdt._grow_binned,
                                             HistBins),
                 small_shape="structure exact, leaf values allclose",
                 small_shape_max_leaf_value_delta=small_delta,
                 tree0_first_tie_flip_node=first_diff,
                 tree0_flip_gain_gap=flip_gap,
                 tree0_full_precision_nodes=full,
                 tree0_nodes=n_nodes, auc=auc_par, auc_serial=auc_ser,
                 iter_s_after_warmup_smoke_figure=round(
                     float(np.median(np.diff(ticks)[2:])), 4))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="allow a tiny CPU / interpret-mode rehearsal; "
                         "everything printed is stamped platform=cpu")
    args = ap.parse_args(argv)

    smoke = Smoke(rehearse=args.rehearse_cpu)
    platform = smoke.device["platform"]
    if platform != "tpu" and not (args.rehearse_cpu and platform == "cpu"):
        # stdout stays empty: no accelerator, no result
        print(f"chip_smoke: {json.dumps(smoke.device)} — platform is not "
              "'tpu'; refusing to run (--rehearse-cpu allows the CPU "
              "rehearsal)", file=sys.stderr)
        return 1
    print(json.dumps({"leg": "device", **smoke.device}), flush=True)

    for leg in (smoke.wide, smoke.train, smoke.hist, smoke.hist256,
                smoke.hist64dense, smoke.partition, smoke.predict,
                smoke.serve, smoke.variants, smoke.optin, smoke.multichip):
        leg()

    tr = smoke.out["train"]
    summary = {
        "leg": "summary",
        "platform": platform,
        "device_kind": smoke.device["kind"],
        "device_count": smoke.device["count"],
        "rehearsal": bool(args.rehearse_cpu),
        "rows": smoke.n, "features": F, "iters": smoke.iters,
        "cold_compile_s": tr["compile_s"],
        "compile_s_all_legs": round(obs_xla.compile_ms_total() / 1e3, 2),
        "compile_s_by_label": {
            k: round(v["compile_ms_total"] / 1e3, 2)
            for k, v in sorted(obs_xla.compile_stats().items())},
        "first_iter_s": tr["first_iter_s"],
        "iter_s_after_warmup_smoke_figure_not_a_benchmark":
            tr["iter_s_after_warmup_smoke_figure"],
        "auc": tr["auc_device"],
        "parity": {
            "auc_host_oracle_delta": tr["auc_delta"],
            "hist_worst_error_over_sum_abs":
                smoke.out["hist"]["worst_error_over_sum_abs"],
            "hist_counts": "exact",
            "hist256_worst_error_over_sum_abs":
                smoke.out["hist256"]["worst_error_over_sum_abs"],
            "hist64_lane_dense_vs_block":
                smoke.out["hist64dense"]["lane_dense_vs_block"],
            "partition_kernel_vs_gather": "equal",
            "predict_leaf_indices": "equal",
            "predict_raw_score_max_delta":
                smoke.out["predict"]["raw_score_max_delta"],
            "serve_answers": "equal",
            "packed4_vs_u8": "bit-equal trees",
            "deep_auto_auc_delta_2it": abs(
                smoke.out["variants"]["auc_deep_auto_2it"]
                - smoke.out["variants"]["auc_bf16_twin_2it"]),
        },
        "optin": smoke.out["optin"]["table"],
        "multichip": smoke.out["multichip"]["multichip"],
        "multichip_detail": {k: v for k, v in smoke.out["multichip"].items()
                             if k not in ("multichip", "t_s")},
        "wall_s": round(time.perf_counter() - smoke.t0, 1),
        "claim": None,
    }
    print(json.dumps(summary), flush=True)
    # the verdict: these keys and no others, as JAX reports the device
    print(json.dumps({"ok": True, "device": smoke.device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
