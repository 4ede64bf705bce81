"""Microbenchmark: TPU costs of the ops the round-5 wave redesign leans on.

Differential two-length-scan timing (cancels per-dispatch fixed cost):
per-op seconds = (wall(R2) - wall(R1)) / (R2 - R1), median of 3.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

N = 1_000_000
F = 28
B = 64
L = 255
K = 64

rng = np.random.RandomState(0)
binned_cm = jnp.asarray(rng.randint(0, B, size=(F, N), dtype=np.uint8))
binned_rm = jnp.asarray(np.asarray(binned_cm).T.copy())
g3 = jnp.asarray(rng.randn(N, 3).astype(np.float32))
lids = jnp.asarray(rng.randint(0, L, size=N).astype(np.int32))
tab = jnp.asarray(rng.randint(0, 1 << 28, size=L).astype(np.int32))
feats_k = jnp.asarray(rng.randint(0, F, size=K).astype(np.int32))
thrs_k = jnp.asarray(rng.randint(0, B, size=K).astype(np.int32))
leafs_k = jnp.asarray(rng.randint(0, L, size=K).astype(np.int32))
CAP = N // 2

out = {}


def rec(k, v):
    out[k] = v
    print(k, round(v, 3), flush=True)


def timed(make, r1=4, r2=16):
    f1 = jax.jit(make(r1))
    f2 = jax.jit(make(r2))
    jax.block_until_ready(f1())
    jax.block_until_ready(f2())
    vals = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(f1())
        t1 = time.perf_counter()
        jax.block_until_ready(f2())
        t2 = time.perf_counter()
        vals.append(((t2 - t1) - (t1 - t0)) / (r2 - r1))
    return float(np.median(vals))


def scan_make(body):
    def make(r):
        def f():
            def step(c, i):
                return body(c, i), None
            s, _ = lax.scan(step, jnp.float32(0), jnp.arange(r))
            return s
        return f
    return make


def s_of(x):
    return jnp.sum(x.astype(jnp.float32) if x.dtype != jnp.float32 else x)


rec("A_table_gather_ms", 1e3 * timed(scan_make(
    lambda c, i: c + s_of(tab[(lids + i) % L]))))

rec("C_rowmajor_bin_gather_ms", 1e3 * timed(scan_make(
    lambda c, i: c + s_of(jnp.take_along_axis(
        binned_rm, ((lids + i) % F)[:, None], axis=1)[:, 0]))))

rec("D_colmajor_bin_gather_ms", 1e3 * timed(scan_make(
    lambda c, i: c + s_of(jnp.take_along_axis(
        binned_cm, ((lids + i) % F)[None, :], axis=0)[0]))))


def compact_idx(c, i):
    live = ((lids + i) % 2) == 0
    pos = jnp.cumsum(live.astype(jnp.int32)) - 1
    idx = jnp.zeros(CAP, jnp.int32).at[
        jnp.where(live, pos, CAP)].set(jnp.arange(N, dtype=jnp.int32),
                                       mode="drop")
    return c + s_of(idx)


rec("E_compact_index_ms", 1e3 * timed(scan_make(compact_idx)))


def row_gather(c, i):
    idx = (jnp.arange(CAP, dtype=jnp.int32) * 2 + i) % N
    bc = jnp.take(binned_rm, idx, axis=0)
    gc = jnp.take(g3, idx, axis=0)
    return c + s_of(bc) + s_of(gc)


rec("F_row_gather_half_ms", 1e3 * timed(scan_make(row_gather)))


def old_decision(c, i):
    fk = (feats_k + i) % F
    bk = jax.vmap(lambda f: binned_cm[f])(fk).astype(jnp.int32)   # (K, N)
    gl = bk <= thrs_k[:, None]
    mine = lids[None, :] == leafs_k[:, None]
    upd = jnp.sum(jnp.where(mine & (~gl), 1, 0), axis=0)
    return c + s_of(upd)


rec("G_oldKN_decision_ms", 1e3 * timed(scan_make(old_decision)))

rec("I_transpose_ms", 1e3 * timed(scan_make(
    lambda c, i: c + s_of((binned_cm + i.astype(jnp.uint8)).T))))

from lightgbmv1_tpu.ops.hist_pallas import hist_leaves_pallas  # noqa: E402

for slots, rows in [(64, N), (64, N // 2), (16, N // 2), (4, N // 2),
                    (4, N)]:
    bm = binned_rm[:rows].T.copy() if rows != N else binned_cm
    g3r = g3[:rows]
    lab = (lids[:rows] % (slots + 1)).astype(jnp.int32)

    def hist_body(c, i, bm=bm, g3r=g3r, lab=lab, slots=slots):
        h = hist_leaves_pallas(bm, g3r + i, lab, slots + 1, B,
                               precision="bf16x2")
        return c + jnp.sum(h[0, 0, 0])

    rec(f"H_hist_s{slots}_n{rows}_ms", 1e3 * timed(scan_make(hist_body), 2, 8))

print(json.dumps({k: round(v, 3) for k, v in out.items()}, indent=1))
