"""Wave-K best-first tree growth — the TPU-native leaf-wise schedule.

The reference grows leaf-wise strictly sequentially: pick the single
frontier leaf with the best gain, split it, histogram the smaller child,
repeat ``num_leaves - 1`` times (``SerialTreeLearner::Train``,
src/treelearner/serial_tree_learner.cpp:152-202).  That schedule is hostile
to a TPU: each step is a tiny histogram job (3 MXU rows) plus a dynamic-size
partition, and the device pays a full dispatch-pipeline of latency per
split.

This module keeps the reference's *policy* — frontier leaves ranked by best
split gain, global across depths, stopped by the ``num_leaves`` budget and
positive-gain test (serial_tree_learner.cpp:192-195) — but changes the
*schedule*: each round splits the top-``K`` frontier leaves at once and
computes the histograms of all ``2K`` children in ONE batched device pass:

* the per-split ``DataPartition::Split`` scatter (data_partition.hpp:101)
  becomes one vectorized decision pass over all rows for all K splits,
* the smaller-child + subtraction trick (``BeforeFindBestSplit``
  serial_tree_learner.cpp:274-314, ``FeatureHistogram::Subtract``
  feature_histogram.hpp:79) is kept, batched: rows of the SMALLER child of
  each of the K splits are labeled with their slot and all K smaller-child
  histograms are built in one masked one-hot-matmul pass
  (ops/histogram.py); the larger children come from the per-leaf histogram
  state by subtraction.  This halves the MXU pass (K+1 slots instead of
  2K+1) and, in data-parallel mode, the histogram psum volume.  Wide-F
  configs whose (L, F, B, 3) state would exceed 512 MB fall back to the
  pool-free 2K-slot pass,
* split finding for the 2K children is one ``vmap`` of the vectorized scan
  (ops/split.py), the analog of ``FindBestSplitsFromHistograms``' OMP loop
  (serial_tree_learner.cpp:358-425).

At ``K = 1`` the schedule IS the reference's best-first order (one leaf per
round, ranked by argmax over the frontier) and reproduces the sequential
grower's trees split-for-split (both use parent subtraction; fp summation
noise can still flip exact near-ties, tests/test_wave_grower.py).  At ``K > 1`` the tree
can deviate from strict best-first only through the budget boundary: a
round commits its top-K leaves together, so children created inside the
round cannot displace the round's lower-ranked picks.  Rounds are
while-looped until the budget is exhausted or no frontier leaf has positive
gain — identical stopping semantics to the reference.

Distribution composes exactly like the sequential grower, but with one
collective per ROUND instead of per split: the data-parallel learner wraps
``hist_wave_fn`` in a ``lax.psum`` (the analog of the reference's
ReduceScatter of histograms, data_parallel_tree_learner.cpp:155-173), the
feature-/voting-parallel learners substitute ``split_fn``.

Quantized rounds (round 7): with ``hist_dtype_deep="int8sr"`` the
sustained bucket and the 16-slot ramp bucket of a K>16 wave run a
stochastic-rounded int8 histogram pass (ops/quantize.py + the int8 MXU
path of ops/hist_pallas.py); the pass returns INTEGER histograms plus
per-slot scales, and dequantization is folded into the smaller-child
subtraction (``subtract_child_hists(slot_scale=...)``) or handed to the
split scan (``find_best_split(hist_scale=...)``) — the histogram never
takes a separate dequantize round-trip.  Rounding is keyed per
(iteration, round) by folding the tree key with the round's leaf count,
so grown trees are bit-reproducible given the seed.

Async wave pipelining (round 12): the sequential round body ends with
commits the NEXT round only partially depends on — the per-leaf
histogram-state scatter and the valid-row routing — yet the
``lax.while_loop`` body boundary is a barrier, so they serialize against
the next round's critical path (top-k → partition decision → histogram
MXU pass → split scan) anyway.  With ``async_wave_pipeline`` (default)
those commits are DEFERRED one round through a pending carry: round r's
child-histogram stack + scatter indices + split metadata ride the carry,
and round r+1 issues the scatter and the valid routing inside ITS
computation, where the scheduler can overlap them with the MXU pass.
The subtraction's parent reads are value-forwarded (gather from the
one-round-stale table, patched from the pending stack — identical
values, no data dependence on the drained scatter), which also lets the
subtracted sibling's split scan start before the partition's leaf-id
reduction drains.  A post-loop drain applies the final round's routing,
so everything a caller (or a checkpoint) can observe is bit-identical
to the sequential schedule — pinned across binary/multiclass/DART in
tests/test_wave_pipeline.py; ``async_wave_pipeline=false`` keeps the
fully-serialized body as the pin.

Round bookkeeping (round 6): the per-leaf frontier state and the tree
arrays under construction live behind a store codec.  The default
``_PackedStore`` keeps them in two packed f32 tables committed with one
coalesced scatter each per round; ``_FieldStore`` is the legacy
one-array-per-field layout (~30 small scatters per round) kept for the
bit-parity test and attribution A/Bs (config ``fused_bookkeeping``).
The phase-attribution harness (tools/phase_attrib.py) measured the
legacy scatter storm as the dominant slice of the per-iteration
``phase_other_ms`` residual; both layouts grow bit-identical trees on
the exact-fp32 histogram path (tests/test_phase_attrib.py).
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.hist_pallas import bin_matrix
from ..ops.partition_pallas import (MAX_LEAF_IDS, assign_rows,
                                    count_partition_bytes,
                                    count_partition_round, partition_pallas,
                                    partition_path)
from ..ops.split import (
    NO_CONSTRAINT,
    FeatureMeta,
    SplitParams,
    find_best_split,
    go_left_rule,
    leaf_output,
    smooth_output,
)
from .grower import _node_feature_mask, allowed_features_for
from .tree import TreeArrays, empty_tree

# Slot bucketing kicks in above this many rows: each extra bucket traces
# one more (S, N) partition + (S+1)-slot histogram variant, which is pure
# compile-time cost at test sizes (the CPU suite stays on the single
# full-wave path).  Lowered by tests to exercise the bucketed branches.
_BUCKET_MIN_N = 1 << 16

# Smaller-child + subtraction mode is skipped when the (L, F, B, 3)
# per-leaf histogram state would exceed this cap (wide-F configs fall back
# to the pool-free 2K-slot pass).  Module-level so tests can force the
# pool-free path on small shapes (e.g. the integer-domain voting
# collective only exists there, tests/test_parallel.py).
_SUB_STATE_CAP_BYTES = 512 * (1 << 20)

# ``jax.named_scope`` of what reads or writes that state, always inside an
# ``lgbm.select`` block: the state's first fill, a round's parent read
# (value-forwarded or not), the sibling subtraction, the scatter of the
# children's histograms (drained a round late when pipelined), and the pass
# result's padding to the width the subtraction takes.  Its traffic grows
# with the columns; the rest of ``lgbm.select`` does not.
POOL_SCOPE = "lgbm.pool"


def replay_wave_schedule(trees, K: int):
    """Per-round split counts of the wave policy, replayed EXACTLY from
    grown trees' recorded structure + gains.

    The device ranks frontier leaves by best gain and commits the top-K
    per round; a leaf's ranking gain equals the ``split_gain`` recorded on
    the node it became, and every candidate that ever wins a budget race
    IS an internal node of the final tree — so replaying the ranked
    commit order over internal nodes reproduces the executed round
    grouping without any device round-trip or host callback in the
    timed program (the live count is the grower's own: ``WaveState.rounds``,
    handed back as ``RootAndRounds.rounds``; the parity test ties the two
    together, tests/test_wave_bucket.py).
    Every replayed round partitioned its rows; the rounds among them that
    also ran a histogram pass are ``measured_rounds``.
    Caveats: fp-equal gain ties replay by node index (the device breaks
    ties by leaf index), and the intermediate-monotone same-round
    deferral is not modeled — neither occurs in the bench configs."""
    out = []
    for t in trees:
        gains = np.asarray(t.split_gain)
        lc = np.asarray(t.left_child)
        rc = np.asarray(t.right_child)
        if int(t.num_leaves) <= 1:
            out.append([])
            continue
        sched = []
        cand = [0]
        while cand:
            cand.sort(key=lambda n: (-gains[n], n))
            take, cand = cand[:K], cand[K:]
            sched.append(len(take))
            cand += [int(c) for n in take for c in (lc[n], rc[n]) if c >= 0]
        out.append(sched)
    return out


def rounds_by_bucket(schedule, slot_buckets):
    """One replayed tree's rounds counted by the slot bucket each ran in,
    in ``WaveState.rounds``' order: the grower's own rule (``s_idx``), a
    round of ``n`` splits takes the smallest bucket that holds them.  A
    round counts whether or not its histogram pass ran
    (``measured_rounds``)."""
    counts = [0] * len(slot_buckets)
    for n in schedule:
        counts[sum(n > S for S in slot_buckets[:-1])] += 1
    return tuple(counts)


def measured_rounds(schedule, num_leaves: int):
    """The rounds of one replayed tree that ran a histogram pass: all but
    the one that spends the ``num_leaves`` budget, whose children nothing
    can split (``children_can_split``; ``len(schedule) - len(...)`` is the
    tree's ``hist_skipped``).  What prices a tree's passes or its
    histogram exchange sums over these; its partitions, over the whole
    schedule.  A depth limit's last level is not modeled, like the
    caveats of ``replay_wave_schedule``: no bench config sets one."""
    spent = 1 + sum(schedule) >= num_leaves
    return list(schedule[:-1] if spent else schedule)


def auto_wave_size(num_leaves: int) -> int:
    """The auto (leafwise_wave_size=0) wave size policy — num_leaves // 4
    (measured optimum with the smaller-child subtraction pass, PERF.md).
    Single source of truth for the trainer AND bench.py's round-schedule
    replay/pricing (a mismatched K would silently re-derive the wrong
    schedule)."""
    return max(1, num_leaves // 4)


def slot_buckets_for(K: int, N: int):
    """The wave grower's slot-bucket ladder for wave size ``K`` over ``N``
    rows — the single source of truth, shared with bench.py's round-cost
    derivation (each probed round is priced at its bucket's measured pass
    time)."""
    if K > 4 and N >= _BUCKET_MIN_N:
        return sorted({4, min(16, K), K})
    return [K]


# ``jax.named_scope`` of the root's histogram pass, once a tree
ROOT_ROUND_SCOPE = "lgbm.round.root"


def round_scope(S: int, slot_buckets) -> str:
    """``jax.named_scope`` of a round taken in slot bucket ``S`` of the
    ladder ``slot_buckets`` (``slot_buckets_for``): ``lgbm.round.b4`` /
    ``lgbm.round.b16``, and ``lgbm.round.bK`` for the largest bucket
    whatever its K (the only one where there is no ladder).  It goes round
    the whole of ``round_pass``, outside the scopes that has, which stay
    components of the ops' paths: a bucket's device time is read beside
    the rounds the tree ran in it (``WaveState.rounds``)."""
    return "lgbm.round." + ("bK" if S == slot_buckets[-1] else f"b{S}")


def children_can_split(num_leaves, n_split, L: int, depth_ok, cvalid):
    """Whether a round's histogram pass has a reader: some child of the
    round may be split later.  ``num_leaves`` leaves before the round,
    ``n_split`` splits in it, ``L`` the tree's budget; ``depth_ok`` and
    ``cvalid`` (2K,): a child is above ``max_depth``, a child is a real
    split's.  False for the round that spends the budget (the loop ends
    after it) and for a round whose children all sit at ``max_depth``
    (their gains are stored as ``-inf``, so they are never picked): the
    pass's result would feed the subtraction, the children's scan and
    their rows of the histogram state only, and nothing of the returned
    tree or leaf ids.  Replicated under a row-sharded learner, as
    ``n_split`` is."""
    return (num_leaves + n_split < L) & jnp.any(depth_ok & cvalid)


class RootAndRounds(NamedTuple):
    """What the wave grower hands back third, where the other growers hand
    back the root's sums alone."""
    root_sum: jax.Array       # (3,) grad / hess / count over the rows
    rounds: jax.Array         # (len(slot_buckets_for(K, N)),) int32: the
                              # rounds this tree ran in each slot bucket,
                              # smallest bucket first
    hist_skipped: jax.Array   # () int32: those of them that ran no
                              # histogram pass (``children_can_split``)


def _box_adjacency_per_feature(lo, hi, feats):
    """Yield ``(f, adj_up, adj_dn)`` pairwise adjacency matrices for leaf
    boxes along each feature in ``feats``: A→B adjacent-up along f means
    hi_A[f] == lo_B[f] with the boxes overlapping in EVERY other feature.
    Overlap counts are accumulated in feature blocks so peak residency is
    (L, L, 256), not (L, L, F).  Shared by the per-round constraint
    recomputation and the same-round split deferral so the adjacency
    definition cannot drift between them."""
    L, F = lo.shape
    ov_cnt = jnp.zeros((L, L), jnp.int32)
    FB = 256
    for c0 in range(0, F, FB):
        c1 = min(c0 + FB, F)
        ovb = (lo[:, None, c0:c1] < hi[None, :, c0:c1]) & \
              (lo[None, :, c0:c1] < hi[:, None, c0:c1])
        ov_cnt = ov_cnt + ovb.sum(axis=2).astype(jnp.int32)
    for f in feats:
        ov_f = (lo[:, None, f] < hi[None, :, f]) & \
               (lo[None, :, f] < hi[:, None, f])
        other = (ov_cnt - ov_f.astype(jnp.int32)) == (F - 1)
        adj_up = (hi[:, None, f] == lo[None, :, f]) & other
        adj_dn = (lo[:, None, f] == hi[None, :, f]) & other
        yield f, adj_up, adj_dn


def intermediate_constraints(boxes, outs, num_leaves, mono_feats,
                             mono_types):
    """Vectorized re-design of the reference's IntermediateLeafConstraints
    (src/treelearner/monotone_constraints.hpp:125-310).

    The reference walks the tree recursively after every split
    (GoUpToFindLeavesToUpdate / GoDownToFindLeavesToUpdate) to find leaves
    whose region is CONTIGUOUS to the new children along a monotone feature
    and tightens their bounds against the new outputs.  Here every leaf
    carries its bin-space box (``boxes`` (L, F, 2) [lo, hi)), and all
    constraints are recomputed from scratch each round as a pairwise
    adjacency reduction: leaf A's upper bound along an increasing feature f
    is the min output over leaves adjacent above it (hi_A[f] == lo_B[f],
    overlapping in every other feature) — O(L²·F) vectorized ops, trivial
    per round, no recursion.  Bounds come from neighbouring leaf OUTPUTS
    instead of the basic mode's split midpoints, which is the point of the
    intermediate mode: tighter leaves, better gains.
    """
    L, F, _ = boxes.shape
    lo = boxes[..., 0]
    hi = boxes[..., 1]
    iota = jnp.arange(L, dtype=jnp.int32)
    valid_b = (iota[None, :] < num_leaves) & (iota[:, None] != iota[None, :])
    max_c = jnp.full(L, NO_CONSTRAINT[1], jnp.float32)
    min_c = jnp.full(L, NO_CONSTRAINT[0], jnp.float32)
    types = dict(zip(mono_feats, mono_types))
    for f, adj_up, adj_dn in _box_adjacency_per_feature(lo, hi, mono_feats):
        adj_up = adj_up & valid_b
        adj_dn = adj_dn & valid_b
        if types[f] < 0:           # decreasing: roles of up/down swap
            adj_up, adj_dn = adj_dn, adj_up
        max_c = jnp.minimum(max_c, jnp.min(
            jnp.where(adj_up, outs[None, :], jnp.inf), axis=1))
        min_c = jnp.maximum(min_c, jnp.max(
            jnp.where(adj_dn, outs[None, :], -jnp.inf), axis=1))
    return jnp.stack([min_c, max_c], axis=1)           # (L, 2)


class WaveState(NamedTuple):
    leaf_id: jax.Array        # (N,) int32 — current leaf of every row
    valid_lids: tuple         # per valid set: (Nv,) int32 leaf of every
                              # VALID row, routed through the same per-round
                              # decisions — valid-set score updates become a
                              # leaf_value gather instead of a per-tree
                              # root-to-leaf walk; () when no valid sets
    leaf_hist: jax.Array      # (L, F, B, 3) — per-leaf histograms enabling
                              # the smaller-child + subtraction trick
                              # (reference BeforeFindBestSplit +
                              # FeatureHistogram::Subtract); (1, F, B, 3)
                              # dummy when the state would exceed the cap
    store: dict               # codec-owned frontier + tree bookkeeping —
                              # _PackedStore (fused, two coalesced tables)
                              # or _FieldStore (legacy per-field arrays)
    leaf_box: jax.Array       # (L, F, 2) — bin-space region per leaf
                              # (intermediate monotone mode; (1, 1, 2) dummy)
    leaf_used: jax.Array      # (L, F) bool — branch features; (1, 1) dummy
                              # unless interaction constraints are on
    num_leaves: jax.Array     # () int32
    done: jax.Array           # () bool
    rounds: jax.Array         # (buckets,) int32 — rounds run so far in each
                              # slot bucket (the per-tree record's count
                              # beside ``lgbm.round.*``'s device time)
    hist_skipped: jax.Array   # () int32 — rounds so far whose histogram
                              # pass had no reader and did not run
    pending: dict = {}        # async_wave_pipeline: the previous round's
                              # DEFERRED commits — the (2K, F, B, 3) child
                              # histograms + their scatter indices and the
                              # (K,) split metadata for the valid-row
                              # routing — applied at the START of the next
                              # body (or by the post-loop drain), where the
                              # scheduler can overlap them with that
                              # round's partition + histogram pass; {} on
                              # the sequential path


def subtract_child_hists(h_slot, leaf_hist, leafs, order_c, sm_left,
                         slot_scale=None, h_parent=None):
    """Smaller-child + parent-subtraction child histograms of one wave
    round (reference BeforeFindBestSplit smaller-leaf trick +
    FeatureHistogram::Subtract): ``h_slot`` holds the measured smaller
    children in slot order; the larger sibling is the stored parent
    histogram minus the smaller.  Returns the rank-order interleaved
    ``(2K, F, B, 3)`` child stack plus the separate left/right halves.
    Module-level so tools/phase_attrib.py can time exactly the ops the
    grower's round body runs.

    ``slot_scale`` (K, 3): when the round's histogram pass ran quantized
    (stochastic-rounded int8, ops/quantize.py), ``h_slot`` carries exact
    integer counts and the per-slot dequantization is folded HERE — one
    broadcast multiply fused into the gather/subtract pipeline the round
    already pays, so the kernel never writes a dequantized copy and the
    quantized histogram is read from HBM exactly once.

    ``h_parent`` (K, F, B, 3): pre-gathered parent histograms — the
    pipelined schedule passes the value-forwarded rows (one-round-stale
    table patched from the pending commit) so the subtraction never waits
    on the deferred scatter; None gathers from ``leaf_hist`` as before."""
    h_small = h_slot[order_c]              # slot-order -> rank-order
    if slot_scale is not None:
        # exact multiply: every dequantization scale is a power of two
        # (ops/quantize.sr_prequantize_g3), so the subtraction below
        # rounds identically whether or not the compiler contracts this
        # product into it (fma): reproducible trees do not depend on
        # fusion heuristics.
        h_small = h_small * slot_scale[order_c][:, None, None, :]
    if h_parent is None:
        h_parent = leaf_hist[leafs]
    smL = sm_left[:, None, None, None]
    h_left = jnp.where(smL, h_small, h_parent - h_small)
    h_right = h_parent - h_left
    hist = jnp.stack([h_left, h_right], axis=1).reshape(
        (2 * h_left.shape[0],) + h_left.shape[1:])
    return hist, h_left, h_right


# ---------------------------------------------------------------------------
# Per-round bookkeeping stores.
#
# The round body computes one set of values either way; the store decides
# HOW they are kept between rounds.  tools/phase_attrib.py instantiates
# both stores directly to time their write paths — the same code objects
# the grower's while-loop body calls.
# ---------------------------------------------------------------------------


class _FieldStore:
    """Legacy (unfused) bookkeeping: every frontier / tree field is its
    own array and every round writes each with its own K- or 2K-row
    scatter (~30 small scatters per round).  Selectable via
    ``fused_bookkeeping=false`` — the reference layout for the
    fused-vs-unfused bit-parity test (tests/test_phase_attrib.py) and for
    attribution A/Bs."""

    fused = False

    def __init__(self, L, L1, W, use_mc, use_cat):
        self.L, self.L1, self.W = L, L1, W
        self.use_mc, self.use_cat = use_mc, use_cat

    def init(self, res0, out0):
        L, W = self.L, self.W
        return dict(
            best_gain=jnp.full(L, -jnp.inf, jnp.float32).at[0]
            .set(res0.gain),
            best_feat=jnp.zeros(L, jnp.int32).at[0].set(res0.feature),
            best_bin=jnp.zeros(L, jnp.int32).at[0].set(res0.threshold_bin),
            best_dl=jnp.zeros(L, bool).at[0].set(res0.default_left),
            best_left=jnp.zeros((L, 3), jnp.float32).at[0]
            .set(res0.left_sum),
            best_right=jnp.zeros((L, 3), jnp.float32).at[0]
            .set(res0.right_sum),
            best_iscat=jnp.zeros(L, bool).at[0].set(res0.is_cat),
            best_bitset=jnp.zeros((L, W), jnp.uint32).at[0]
            .set(res0.cat_bitset),
            leaf_constr=jnp.tile(jnp.asarray(NO_CONSTRAINT, jnp.float32),
                                 (L, 1)),
            leaf_out=jnp.zeros(L, jnp.float32).at[0].set(out0),
            leaf_depth=jnp.zeros(L, jnp.int32),
            leaf_is_left=jnp.zeros(L, bool),
            tree=empty_tree(L, W),
        )

    def gains(self, s):
        return s["best_gain"]

    def leaf_out_full(self, s):
        return s["leaf_out"]

    def read(self, s, leafs):
        t = s["tree"]
        return dict(
            feats=s["best_feat"][leafs],
            thrs=s["best_bin"][leafs],
            dls=s["best_dl"][leafs],
            iscats=s["best_iscat"][leafs],
            bitsets=s["best_bitset"][leafs],
            lsums=s["best_left"][leafs],
            rsums=s["best_right"][leafs],
            pconstr=s["leaf_constr"][leafs],
            pout=s["leaf_out"][leafs],
            pdepth=s["leaf_depth"][leafs],
            was_left=s["leaf_is_left"][leafs],
            parent=t.leaf_parent[leafs],
        )

    def write(self, s, r):
        res = r["res"]
        t = s["tree"]
        lc = t.left_child.at[r["fix_l"]].set(r["nidx"], mode="drop")
        rc = t.right_child.at[r["fix_r"]].set(r["nidx"], mode="drop")
        lc = lc.at[r["nidx"]].set(-(r["leafs"] + 1), mode="drop")
        rc = rc.at[r["nidx"]].set(-(r["nls"] + 1), mode="drop")
        tree = t._replace(
            num_leaves=r["num_leaves_new"],
            split_feature=t.split_feature.at[r["nidx"]]
            .set(r["feats"], mode="drop"),
            threshold_bin=t.threshold_bin.at[r["nidx"]]
            .set(r["thrs"], mode="drop"),
            default_left=t.default_left.at[r["nidx"]]
            .set(r["dls"], mode="drop"),
            is_cat=t.is_cat.at[r["nidx"]].set(r["iscats"], mode="drop"),
            cat_bitset=t.cat_bitset.at[r["nidx"]]
            .set(r["bitsets"], mode="drop"),
            missing_type=t.missing_type.at[r["nidx"]]
            .set(r["mtypes"], mode="drop"),
            left_child=lc,
            right_child=rc,
            split_gain=t.split_gain.at[r["nidx"]]
            .set(r["vals"], mode="drop"),
            internal_value=t.internal_value.at[r["nidx"]]
            .set(r["pout"], mode="drop"),
            internal_weight=t.internal_weight.at[r["nidx"]]
            .set(r["psum"][:, 1], mode="drop"),
            internal_count=t.internal_count.at[r["nidx"]]
            .set(r["psum"][:, 2].astype(jnp.int32), mode="drop"),
            leaf_value=t.leaf_value.at[r["lidx"]]
            .set(r["out_l"], mode="drop")
            .at[r["nlidx"]].set(r["out_r"], mode="drop"),
            leaf_weight=t.leaf_weight.at[r["lidx"]]
            .set(r["lsums"][:, 1], mode="drop")
            .at[r["nlidx"]].set(r["rsums"][:, 1], mode="drop"),
            leaf_count=t.leaf_count.at[r["lidx"]]
            .set(r["lsums"][:, 2].astype(jnp.int32), mode="drop")
            .at[r["nlidx"]].set(r["rsums"][:, 2].astype(jnp.int32),
                               mode="drop"),
            leaf_parent=t.leaf_parent.at[r["lidx"]]
            .set(r["nidx"], mode="drop")
            .at[r["nlidx"]].set(r["nidx"], mode="drop"),
        )
        cidx = r["cidx"]
        return dict(
            best_gain=s["best_gain"].at[cidx].set(r["cgain"], mode="drop"),
            best_feat=s["best_feat"].at[cidx]
            .set(res.feature, mode="drop"),
            best_bin=s["best_bin"].at[cidx]
            .set(res.threshold_bin, mode="drop"),
            best_dl=s["best_dl"].at[cidx]
            .set(res.default_left, mode="drop"),
            best_left=s["best_left"].at[cidx]
            .set(res.left_sum, mode="drop"),
            best_right=s["best_right"].at[cidx]
            .set(res.right_sum, mode="drop"),
            best_iscat=s["best_iscat"].at[cidx]
            .set(res.is_cat, mode="drop"),
            best_bitset=s["best_bitset"].at[cidx]
            .set(res.cat_bitset, mode="drop"),
            leaf_constr=s["leaf_constr"].at[cidx]
            .set(r["cconstr"], mode="drop"),
            leaf_out=s["leaf_out"].at[cidx].set(r["couts"], mode="drop"),
            leaf_depth=s["leaf_depth"].at[cidx]
            .set(r["cdepth"], mode="drop"),
            leaf_is_left=s["leaf_is_left"].at[r["lidx"]]
            .set(True, mode="drop")
            .at[r["nlidx"]].set(False, mode="drop"),
            tree=tree,
        )

    def finalize(self, s, num_leaves):
        return s["tree"]._replace(num_leaves=num_leaves)


class _PackedStore:
    """Fused per-round bookkeeping (``fused_bookkeeping=true``, default).

    All per-leaf frontier + tree-leaf state lives in ONE ``(L, CF)`` f32
    table and all per-node tree state in ONE ``(L1, 10)`` f32 table.  A
    round commits with one coalesced 2K-row scatter into the frontier
    table, one K-row scatter into the node table, and one two-column
    child-pointer fixup — three scatters instead of the legacy layout's
    ~30 per-field scatters per round (the phase-attribution harness
    measured that scatter storm as the largest slice of the
    per-iteration ``phase_other_ms`` residual, tools/phase_attrib.py).

    Integers and booleans ride as exact small f32 values (every id, bin,
    depth and child index is far below 2^24), so packing is bit-lossless
    and the grown trees are bit-identical to the unfused layout on the
    exact-fp32 histogram path (tests/test_phase_attrib.py pins this).
    Categorical state (uint32 bitsets) keeps separate arrays — f32
    storage cannot carry arbitrary 32-bit patterns by value — and the
    monotone constraint bounds add two columns only when constraints are
    on, so the common no-cat/no-mono config pays for neither."""

    fused = True

    # frontier-table columns (per leaf)
    GAIN, FEAT, BIN, DL = 0, 1, 2, 3
    LS, RS = 4, 7                    # [4:7) left sums, [7:10) right sums
    OUT, DEPTH, ISLEFT = 10, 11, 12
    LVAL, LWEIGHT, LCNT, LPAR = 13, 14, 15, 16
    CMIN, CMAX = 17, 18              # only materialized when use_mc
    # node-table columns (per internal node)
    NFEAT, NBIN, NDL, NMT, NGAIN, NIVAL, NIW, NIC, NLC, NRC = range(10)

    def __init__(self, L, L1, W, use_mc, use_cat):
        self.L, self.L1, self.W = L, L1, W
        self.use_mc, self.use_cat = use_mc, use_cat
        self.CF = 19 if use_mc else 17

    def init(self, res0, out0):
        L, L1, W = self.L, self.L1, self.W
        z = jnp.float32(0.0)
        ft = jnp.zeros((L, self.CF), jnp.float32)
        ft = ft.at[:, self.GAIN].set(-jnp.inf)
        ft = ft.at[:, self.LPAR].set(-1.0)
        if self.use_mc:
            ft = ft.at[:, self.CMIN].set(float(NO_CONSTRAINT[0]))
            ft = ft.at[:, self.CMAX].set(float(NO_CONSTRAINT[1]))
        root = jnp.stack([
            res0.gain,
            res0.feature.astype(jnp.float32),
            res0.threshold_bin.astype(jnp.float32),
            res0.default_left.astype(jnp.float32),
            res0.left_sum[0], res0.left_sum[1], res0.left_sum[2],
            res0.right_sum[0], res0.right_sum[1], res0.right_sum[2],
            out0, z, z, z, z, z, jnp.float32(-1.0),
        ] + ([jnp.float32(NO_CONSTRAINT[0]),
              jnp.float32(NO_CONSTRAINT[1])] if self.use_mc else []))
        ft = ft.at[0].set(root)
        nt = jnp.zeros((L1, 10), jnp.float32)
        nt = nt.at[:, self.NLC].set(-1.0).at[:, self.NRC].set(-2.0)
        out = {"ft": ft, "nt": nt}
        if self.use_cat:
            out["f_iscat"] = jnp.zeros(L, bool).at[0].set(res0.is_cat)
            out["f_bitset"] = jnp.zeros((L, W), jnp.uint32).at[0] \
                .set(res0.cat_bitset)
            out["n_iscat"] = jnp.zeros(L1, bool)
            out["n_bitset"] = jnp.zeros((L1, W), jnp.uint32)
        return out

    def gains(self, s):
        return s["ft"][:, self.GAIN]

    def leaf_out_full(self, s):
        return s["ft"][:, self.OUT]

    def read(self, s, leafs):
        rows = s["ft"][leafs]                      # ONE gather for all fields
        K = leafs.shape[0]
        return dict(
            feats=rows[:, self.FEAT].astype(jnp.int32),
            thrs=rows[:, self.BIN].astype(jnp.int32),
            dls=rows[:, self.DL] != 0,
            lsums=rows[:, self.LS:self.LS + 3],
            rsums=rows[:, self.RS:self.RS + 3],
            pout=rows[:, self.OUT],
            pdepth=rows[:, self.DEPTH].astype(jnp.int32),
            was_left=rows[:, self.ISLEFT] != 0,
            parent=rows[:, self.LPAR].astype(jnp.int32),
            pconstr=(rows[:, self.CMIN:self.CMAX + 1] if self.use_mc
                     else jnp.tile(jnp.asarray(NO_CONSTRAINT, jnp.float32),
                                   (K, 1))),
            iscats=(s["f_iscat"][leafs] if self.use_cat
                    else jnp.zeros(K, bool)),
            bitsets=(s["f_bitset"][leafs] if self.use_cat
                     else jnp.zeros((K, self.W), jnp.uint32)),
        )

    def write(self, s, r):
        res = r["res"]
        n2 = r["cidx"].shape[0]                    # 2K
        K = n2 // 2
        # -- frontier + tree-leaf state: ONE coalesced 2K-row scatter ----
        crows = jnp.concatenate([
            r["cgain"][:, None],
            res.feature.astype(jnp.float32)[:, None],
            res.threshold_bin.astype(jnp.float32)[:, None],
            res.default_left.astype(jnp.float32)[:, None],
            res.left_sum, res.right_sum,
            r["couts"][:, None],
            r["cdepth"].astype(jnp.float32)[:, None],
            jnp.tile(jnp.asarray([1.0, 0.0], jnp.float32), K)[:, None],
            r["couts"][:, None],                  # leaf_value == leaf_out
            r["csums"][:, 1:2], r["csums"][:, 2:3],
            jnp.stack([r["nidx"], r["nidx"]], axis=1).reshape(n2)
            .astype(jnp.float32)[:, None],
        ] + ([r["cconstr"]] if self.use_mc else []), axis=1)
        ft = s["ft"].at[r["cidx"]].set(crows, mode="drop")
        # -- node state: one K-row scatter + one 2-column pointer fixup --
        nrows = jnp.concatenate([
            r["feats"].astype(jnp.float32)[:, None],
            r["thrs"].astype(jnp.float32)[:, None],
            r["dls"].astype(jnp.float32)[:, None],
            r["mtypes"].astype(jnp.float32)[:, None],
            r["vals"][:, None],
            r["pout"][:, None],
            r["psum"][:, 1:2], r["psum"][:, 2:3],
            (-(r["leafs"] + 1)).astype(jnp.float32)[:, None],
            (-(r["nls"] + 1)).astype(jnp.float32)[:, None],
        ], axis=1)
        nt = s["nt"]
        # parents are strictly OLDER nodes than this round's new rows, so
        # the fixup and the row write never collide and order is free
        rows2 = jnp.concatenate([r["fix_l"], r["fix_r"]])
        cols2 = jnp.concatenate([jnp.full(K, self.NLC, jnp.int32),
                                 jnp.full(K, self.NRC, jnp.int32)])
        vals2 = jnp.concatenate([r["nidx"], r["nidx"]]).astype(jnp.float32)
        nt = nt.at[rows2, cols2].set(vals2, mode="drop")
        nt = nt.at[r["nidx"]].set(nrows, mode="drop")
        out = {"ft": ft, "nt": nt}
        if self.use_cat:
            out["f_iscat"] = s["f_iscat"].at[r["cidx"]] \
                .set(res.is_cat, mode="drop")
            out["f_bitset"] = s["f_bitset"].at[r["cidx"]] \
                .set(res.cat_bitset, mode="drop")
            out["n_iscat"] = s["n_iscat"].at[r["nidx"]] \
                .set(r["iscats"], mode="drop")
            out["n_bitset"] = s["n_bitset"].at[r["nidx"]] \
                .set(r["bitsets"], mode="drop")
        return out

    def finalize(self, s, num_leaves):
        ft, nt = s["ft"], s["nt"]
        L1, W = self.L1, self.W
        return TreeArrays(
            num_leaves=num_leaves,
            split_feature=nt[:, self.NFEAT].astype(jnp.int32),
            threshold_bin=nt[:, self.NBIN].astype(jnp.int32),
            threshold=jnp.zeros(L1, jnp.float32),
            default_left=nt[:, self.NDL] != 0,
            missing_type=nt[:, self.NMT].astype(jnp.int32),
            left_child=nt[:, self.NLC].astype(jnp.int32),
            right_child=nt[:, self.NRC].astype(jnp.int32),
            split_gain=nt[:, self.NGAIN],
            internal_value=nt[:, self.NIVAL],
            internal_weight=nt[:, self.NIW],
            internal_count=nt[:, self.NIC].astype(jnp.int32),
            leaf_value=ft[:, self.LVAL],
            leaf_weight=ft[:, self.LWEIGHT],
            leaf_count=ft[:, self.LCNT].astype(jnp.int32),
            leaf_parent=ft[:, self.LPAR].astype(jnp.int32),
            is_cat=(s["n_iscat"] if self.use_cat
                    else jnp.zeros(L1, bool)),
            cat_bitset=(s["n_bitset"] if self.use_cat
                        else jnp.zeros((L1, W), jnp.uint32)),
        )


def _topk_by_rank(gains: jax.Array, K: int):
    """Top-K (descending, ties by lower index — lax.top_k semantics) via an
    O(L²) rank matrix instead of lax.top_k: on TPU the sort-based top_k
    lowering costs ~13 ms even on a 255-element array, while this is a
    handful of vectorized compares (L ≤ a few thousand here)."""
    L = gains.shape[0]
    iota = jnp.arange(L, dtype=jnp.int32)
    g_l = gains[:, None]
    g_i = gains[None, :]
    beats = (g_l > g_i) | ((g_l == g_i) & (iota[:, None] < iota[None, :]))
    rank = jnp.sum(beats, axis=0).astype(jnp.int32)          # (L,)
    jk = jnp.arange(K, dtype=jnp.int32)
    sel = rank[None, :] == jk[:, None]                       # (K, L)
    leafs = jnp.sum(jnp.where(sel, iota[None, :], 0), axis=1)
    vals = jnp.sum(jnp.where(sel, gains[None, :], 0.0), axis=1)
    # rows whose rank never matched (can't happen: ranks are a permutation)
    return vals, leafs


def make_wave_grower(
    *,
    num_leaves: int,
    num_bins: int,
    meta: FeatureMeta,
    params: SplitParams,
    max_depth: int = -1,
    feature_fraction_bynode: float = 1.0,
    monotone_penalty: float = 0.0,
    monotone_mode: str = "basic",
    interaction_groups=None,
    wave_size: int = 32,
    fused_bookkeeping: bool = True,
    async_wave_pipeline: bool = True,
    hist_wave_fn: Callable = None,
    hist_wave_quant_fn: Callable = None,
    split_fn: Callable = None,
    sums_fn: Callable = None,
    bins_of_fn: Callable = None,
    hist_method: str = "",
    pallas_interpret: bool = False,
    stored_layout: str = "",
):
    """Build the jittable ``grow(binned, g3, base_mask, key)`` function.

    ``hist_wave_fn(binned, g3, label, nslots, deep=False) ->
    (nslots, F, B, 3)`` — histograms of the rows labeled ``0..nslots-1``
    (label ``nslots`` = dead); globally summed in distributed mode.
    ``deep=True`` marks a sustained (largest-bucket) round of a big wave —
    the implementation may drop to the configured cheaper histogram dtype
    there (config.hist_dtype_deep).
    ``hist_wave_quant_fn(binned, g3, label, nslots, key) ->
    ((nslots, F, B, 3), (nslots, 3))`` — optional stochastic-rounded
    quantized pass (hist_dtype_deep="int8sr"): integer histogram plus
    per-slot dequant scales (all-ones when the implementation already
    dequantized, e.g. the data-parallel dequantize-then-psum wrapper).
    Eligible rounds — the sustained largest bucket (the existing deep
    gate) AND the 16-slot ramp bucket of a K>16 wave (VERDICT r5 priced
    ramp rounds at 11.7 ms vs 7.7 deep: the 16-slot bucket is the next
    harvest) — route here with a per-round fold-in of the tree key, so
    the rounding stream is deterministic per (iteration, round).  The
    root pass and the small (<=4 slot) ramp buckets NEVER quantize:
    their per-bin sums are large and precision-critical, and their cost
    is dispatch-dominated anyway.
    ``split_fn(hist, parent, mask, key, uid, constraint, depth,
    parent_output) -> SplitResult`` — vmapped over the 2K children.
    ``sums_fn(g3) -> (3,)`` — root totals (psum over the row axis when
    data-parallel).
    ``bins_of_fn(binned, feat) -> (N,)`` — ORIGINAL bins of a feature; the
    EFB path substitutes the bundle-column decode (io/bundle.py
    bundle_bins_of_feat), so ``binned`` may be the (BF, N) bundled matrix,
    and the 4-bit path ``hist_pallas.packed_bins_of_feat``, whose
    ``(ceil(F/2), N)`` layout the partition kernel decodes too.
    ``fused_bookkeeping`` selects the per-round state layout: packed
    tables with one coalesced scatter each (_PackedStore, default) or the
    legacy per-field scatters (_FieldStore); trees are bit-identical
    either way on the exact-fp32 histogram path.
    ``hist_method`` is the trainer's resolved histogram method,
    ``pallas_interpret`` whether its kernels run under the Pallas
    interpreter and ``stored_layout`` what a row of ``binned`` holds
    (``parallel/trainer.HistPlan.layout``: ``"u8"``, ``"packed4"`` or
    ``""``): where the method is ``"pallas"`` and the kernel decodes the
    layout a round's partition of the training rows may take the
    row-tiled kernel (ops/partition_pallas.py ``partition_path``: a rule
    on shapes).
    ``async_wave_pipeline`` (default on) software-pipelines the round
    loop: the per-leaf histogram-state scatter and the valid-row routing
    of round r are DEFERRED into a pending carry and applied at the
    start of round r+1 — off round r+1's critical path (top-k →
    partition decision → histogram MXU pass → split scan), so the
    scheduler can overlap them with it instead of serializing at the
    while-loop body barrier.  The subtraction's parent-histogram read is
    value-forwarded (one-round-stale table patched from the pending
    commit), and a post-loop drain applies the final round's routing, so
    grown trees, leaf ids and valid routings are bit-identical to the
    sequential schedule (tests/test_wave_pipeline.py pins this; the
    sequential path is the pin, config ``async_wave_pipeline=false``).
    """
    L = num_leaves
    if L >= MAX_LEAF_IDS:
        raise ValueError(f"num_leaves={L}: a round's partition packs a leaf "
                         f"id into {MAX_LEAF_IDS.bit_length() - 1} bits")
    L1 = max(L - 1, 1)
    K = max(1, min(wave_size, L1))
    B = num_bins
    W = -(-B // 32)
    use_mc = bool(np.asarray(meta.monotone_type).any())
    use_cat = bool(np.asarray(meta.is_categorical).any())
    has_missing = bool(np.asarray(meta.missing_type).any())
    use_inter = use_mc and monotone_mode == "intermediate"
    use_groups = interaction_groups is not None
    if use_inter:
        _mt = np.asarray(meta.monotone_type)
        inter_feats = [int(f) for f in np.where(_mt != 0)[0]]
        inter_types = [int(_mt[f]) for f in inter_feats]
    groups = (jnp.asarray(interaction_groups)
              if interaction_groups is not None else None)
    store = (_PackedStore if fused_bookkeeping else _FieldStore)(
        L, L1, W, use_mc, use_cat)
    # the default split accepts a per-child hist_scale (dequantize-aware
    # scan, ops/split.py), as do custom split_fns that declare
    # ``accepts_hist_scale = True`` (the sharded data-/voting-parallel
    # collectives, parallel/trainer.py — keeping the histogram integer
    # until AFTER their cross-chip reduce is the point of the int8sr
    # integer-domain collective); other custom split_fns (EFB bundle
    # decode, feature-parallel all_gather) keep their narrower signature
    # and get pre-dequantized histograms instead
    default_split = split_fn is None
    takes_scale = default_split or getattr(split_fn, "accepts_hist_scale",
                                           False)
    if split_fn is None:
        def split_fn(hist, parent, mask, key, uid, constraint, depth,
                     parent_output, hist_scale=None):
            rk = jax.random.fold_in(key, uid + 1_000_003 + params.extra_seed) \
                if params.extra_trees else None
            return find_best_split(hist, parent, meta, mask, params,
                                   constraint, depth, monotone_penalty,
                                   parent_output, rk, None,
                                   hist_scale=hist_scale)

    if sums_fn is None:
        def sums_fn(g3):
            return g3.sum(axis=0)

    if bins_of_fn is None:
        def bins_of_fn(binned, feat):
            return binned[feat]

    def allowed_features(used):
        return allowed_features_for(groups, used)

    def clamp_out(sums, constr, parent_out):
        out = leaf_output(sums[0], sums[1], params)
        if params.path_smooth > 0:
            out = smooth_output(out, sums[2], parent_out, params)
        if not use_mc:
            return out
        return jnp.clip(out, constr[0], constr[1])

    def grow(binned, g3, base_mask, key, cegb_used=None, valids=()):
        # ``binned`` may arrive prepared for the histogram kernel
        # (hist_pallas.HistBins): the passes take it as it is, everything
        # else reads its (F, N) matrix
        bins = bin_matrix(binned)
        N = bins.shape[1]
        F = base_mask.shape[0]    # ORIGINAL feature count (binned may be
                                  # the narrower EFB bundle matrix)
        del cegb_used  # CEGB routes to the sequential grower (order-exact)

        # Slot buckets: the wave frontier RAMPS (1, 2, 4, ... splits per
        # round before reaching K), but a fixed-K round pays the full
        # 3*(K+1)-row MXU pass and the (K, N) partition regardless.  Rounds
        # with few splits therefore run a SLICED variant: the round's
        # n_split <= S splits are compacted to slots 0..n_split-1 and the
        # partition + histogram run at (S, N) / (S+1) slots — measured ~2x
        # cheaper at S=4 vs S=64 on the bench config (the remaining floor
        # is the slot-count-independent in-VMEM one-hot build).  Selection
        # is by the replicated n_split, so row shards stay in lockstep.
        slot_buckets = slot_buckets_for(K, N)

        def partition_path_of(S):
            return partition_path(
                bins.shape[0], S, N, pallas=hist_method == "pallas",
                layout=stored_layout, use_cat=use_cat)

        count_partition_bytes(partition_path_of(slot_buckets[-1]),
                              bins.shape[0], slot_buckets[-1], N)
        # Quantized-pass eligibility (hist_dtype_deep="int8sr"): the
        # sustained largest bucket (the depth-adaptive deep gate) and the
        # 16-slot ramp bucket of a K>16 wave.  Root (the nslots=1 call
        # below) and the <=4-slot ramp buckets never quantize.
        quant_buckets = ()
        if hist_wave_quant_fn is not None and len(slot_buckets) > 1:
            quant_buckets = tuple(
                S for S in slot_buckets
                if (S == K and K >= 32) or (S == 16 and S < K))

        with jax.named_scope("lgbm.select"):
            leaf_id0 = jnp.zeros(N, jnp.int32)
        with jax.named_scope(ROOT_ROUND_SCOPE):
            hist0 = hist_wave_fn(binned, g3, leaf_id0, 1, deep=False)[0]
        # smaller-child + subtraction mode: build K child histograms per
        # round instead of 2K (halves the one-hot MXU pass and, in
        # data-parallel mode, the psum volume — the reference's
        # smaller-leaf trick, serial_tree_learner.cpp:274-314), deriving
        # the larger child from the per-leaf histogram state.  Skipped
        # when that state would exceed 512 MB (wide-F configs).
        use_sub = (L * int(np.prod(hist0.shape)) * 4) <= _SUB_STATE_CAP_BYTES
        # async wave pipelining: active whenever there is deferred work to
        # overlap — the per-leaf histogram-state scatter (use_sub) and/or
        # the valid-row routing.  With neither, the sequential body IS the
        # pipelined one (nothing to defer), so the pending carry is
        # skipped entirely and the paths are the same trace.
        pipeline = async_wave_pipeline and (use_sub or bool(valids))
        with jax.named_scope("lgbm.select"):
            root_sum = sums_fn(g3)
            mask0 = _node_feature_mask(key, 0, base_mask, feature_fraction_bynode)
            mask0 = mask0 & allowed_features(jnp.zeros(F, bool))
            no_constr = jnp.asarray(NO_CONSTRAINT, jnp.float32)
            out0 = leaf_output(root_sum[0], root_sum[1], params)
            if params.path_smooth > 0:
                out0 = smooth_output(out0, root_sum[2], 0.0, params)
        res0 = split_fn(hist0, root_sum, mask0, key, 0, no_constr, 0, out0)

        # round-invariant work hoisted out of the while-loop body: with
        # per-node column sampling off and no interaction constraints the
        # children's feature mask is the same every round, and with no
        # monotone constraints every child's constraint is the NO_CONSTRAINT
        # constant — neither needs per-round gathers/scatters
        with jax.named_scope("lgbm.select"):
            cmask_const = (jnp.broadcast_to(base_mask, (2 * K, F))
                           if feature_fraction_bynode >= 1.0 and not use_groups
                           else None)
            pconstr_const = (None if use_mc
                             else jnp.tile(no_constr, (K, 1)))
            cconstr_const = (None if use_mc
                             else jnp.tile(no_constr, (2 * K, 1)))

            # pipelined schedule: the pending no-op of round -1 — every index
            # is a drop slot and every routing slot is dead (leaf id L matches
            # no row), so the first body's drain is a bit-exact no-op
            pend0 = {}
            if pipeline:
                pend0 = dict(
                    cidx=jnp.full(2 * K, L + 1, jnp.int32),
                    feats=jnp.zeros(K, jnp.int32),
                    thrs=jnp.zeros(K, jnp.int32),
                    dls=jnp.zeros(K, bool),
                    leafs=jnp.full(K, L, jnp.int32),
                    nls=jnp.zeros(K, jnp.int32),
                )
                if use_sub:
                    with jax.named_scope(POOL_SCOPE):
                        pend0["hist"] = jnp.zeros((2 * K,) + hist0.shape,
                                                  jnp.float32)
                if use_cat:
                    pend0["iscats"] = jnp.zeros(K, bool)
                    pend0["bitsets"] = jnp.zeros((K, W), jnp.uint32)

        def route_pending(p, vb, vl):
            """Apply one pending round's split decisions to a valid set's
            leaf ids — the DEFERRED analog of the in-round ``go_left_s``
            valid routing, evaluated over the rank-order (K,) split
            metadata (dead slots carry leaf id L and match no row).  The
            per-row update terms are int32 — exact and summation-order
            free — so deferral is bit-identical to in-round routing."""
            with jax.named_scope("lgbm.partition"):
                feats_k, thrs_k, dls_k = p["feats"], p["thrs"], p["dls"]
                leafs_k, nls_k = p["leafs"], p["nls"]
                mt_k = meta.missing_type[feats_k][:, None]
                bk = jax.vmap(lambda f: bins_of_fn(vb, f))(feats_k)
                bk = bk.astype(jnp.int32)
                g = go_left_rule(bk, thrs_k[:, None], dls_k[:, None], mt_k,
                                 meta.nan_bin[feats_k][:, None],
                                 meta.zero_bin[feats_k][:, None])
                if use_cat:
                    word = jnp.zeros(bk.shape, jnp.uint32)
                    for wv in range(W):
                        word = jnp.where((bk >> 5) == wv,
                                         p["bitsets"][:, wv][:, None], word)
                    in_set = ((word >> (bk.astype(jnp.uint32) & 31)) & 1) == 1
                    g = jnp.where(p["iscats"][:, None], in_set, g)
                mine = vl[None, :] == leafs_k[:, None]
                go_rv = mine & (~g)
                return vl + jnp.sum(
                    jnp.where(go_rv, nls_k[:, None] - vl[None, :], 0), axis=0)

        with jax.named_scope("lgbm.select"):
            with jax.named_scope(POOL_SCOPE):
                leaf_hist0 = (jnp.zeros((L,) + hist0.shape,
                                        jnp.float32).at[0].set(hist0)
                              if use_sub
                              else jnp.zeros((1,) + hist0.shape, jnp.float32))
            st = WaveState(
                leaf_id=leaf_id0,
                valid_lids=tuple(jnp.zeros(v.shape[1], jnp.int32)
                                 for v in valids),
                leaf_hist=leaf_hist0,
                store=store.init(res0, out0),
                leaf_box=(jnp.zeros((L, F, 2), jnp.int32)
                          .at[0, :, 1].set(meta.num_bins)
                          if use_inter else jnp.zeros((1, 1, 2), jnp.int32)),
                leaf_used=(jnp.zeros((L, F), bool) if use_groups
                           else jnp.zeros((1, 1), bool)),
                num_leaves=jnp.asarray(1, jnp.int32),
                done=jnp.asarray(L <= 1),
                rounds=jnp.zeros(len(slot_buckets), jnp.int32),
                hist_skipped=jnp.zeros((), jnp.int32),
                pending=pend0,
            )

            kiota = jnp.arange(K, dtype=jnp.int32)

        def cond(st: WaveState):
            # max(best_gain) > 0 stops BEFORE a zero-split round: the old
            # `done | (n_split == 0)` exit ran one full (partition + hist)
            # pass just to discover nothing splits — a wasted round on
            # every gain-exhausted tree, and a trailing 0 the tree-replay
            # schedule (replay_wave_schedule) could not see.  A positive
            # frontier gain guarantees n_split >= 1 (the intermediate-
            # monotone deferral never clears the FIRST valid pick).
            with jax.named_scope("lgbm.select"):
                return (~st.done) & (st.num_leaves < L) & \
                    (jnp.max(store.gains(st.store)) > 0)

        def body(st: WaveState) -> WaveState:
            # ---- pipelined drain of the PREVIOUS round's deferred work ----
            # The leaf-histogram scatter and the valid-row routing of round
            # r-1 are issued HERE, inside round r's computation: both are
            # data-independent of this round's critical path (top-k →
            # partition decision → histogram MXU pass → split scan), so the
            # scheduler can overlap them with it — at the tail of body r-1
            # the while-loop barrier would have serialized them instead.
            # The subtraction below never waits on the drained scatter: its
            # parent rows are value-forwarded from the pending commit.
            if pipeline:
                p_hist = st.pending.get("hist")
                with jax.named_scope("lgbm.select"), \
                        jax.named_scope(POOL_SCOPE):
                    leaf_hist_in = (st.leaf_hist.at[st.pending["cidx"]]
                                    .set(p_hist, mode="drop")
                                    if use_sub else st.leaf_hist)
                vlids_in = tuple(
                    route_pending(st.pending, vb, vl)
                    for vb, vl in zip(valids, st.valid_lids))
            else:
                leaf_hist_in = st.leaf_hist
                vlids_in = st.valid_lids
            with jax.named_scope("lgbm.select"):
                budget = L - st.num_leaves
                vals, leafs = _topk_by_rank(store.gains(st.store), K)  # (K,)
                valid = (vals > 0) & (kiota < budget)
                if use_inter and K > 1:
                    # soundness: two leaves ADJACENT along a monotone feature
                    # must not split in the same round — each child would be
                    # clamped against the neighbour's stale pre-round output
                    # and monotonicity could break between the new children.
                    # Defer the lower-ranked leaf of any adjacent pair to a
                    # later round (it stays in the queue); the sequential
                    # reference orders such splits implicitly.
                    kb = st.leaf_box[leafs]                        # (K, F, 2)
                    adj = jnp.zeros((K, K), bool)
                    for _f, adj_up, adj_dn in _box_adjacency_per_feature(
                            kb[..., 0], kb[..., 1], inter_feats):
                        adj = adj | adj_up | adj_dn
                    kept = valid
                    for j in range(1, K):
                        clash = jnp.any(adj[j, :j] & kept[:j])
                        kept = kept.at[j].set(kept[j] & (~clash))
                    valid = kept
                n_split = valid.sum()
                order = jnp.cumsum(valid.astype(jnp.int32)) - 1
                nodes = st.num_leaves - 1 + order                 # (K,) int32
                nls = st.num_leaves + order                       # new right leaves

                # one store read for every frontier field of the K split leaves
                # (the packed store turns 10+ per-field gathers into a single
                # (K, CF) table row gather)
                rd = store.read(st.store, leafs)
                feats, thrs, dls = rd["feats"], rd["thrs"], rd["dls"]
                iscats, bitsets = rd["iscats"], rd["bitsets"]     # (K,), (K, W)
                lsums, rsums = rd["lsums"], rd["rsums"]           # (K, 3)
                sm_left = lsums[:, 2] <= rsums[:, 2]              # (K,) smaller
                order_c = jnp.clip(order, 0, K - 1)
                # per-round rounding key for the quantized pass: the per-tree
                # key (unique per iteration x class) folded with the round's
                # leaf count, which strictly increases every round — the
                # (iteration, round) legs of the counter-based PRNG contract
                # (ops/quantize.py); the row block is the third leg, drawn
                # inside sr_quantize_g3
                rkey = (jax.random.fold_in(key, 8_000_011 + st.num_leaves)
                        if quant_buckets else None)

                # value-forwarded parent histogram rows, hoisted ahead of the
                # slot-bucket switch
                h_parent = None
                if use_sub and pipeline:
                    # value forwarding: gather the parents from the ONE-
                    # ROUND-STALE table and patch rows whose slot was
                    # (over)written by the pending commit — identical
                    # values to a post-scatter gather, but the subtracted
                    # sibling's split scan starts without waiting for the
                    # drained scatter (or the partition) to complete
                    with jax.named_scope(POOL_SCOPE):
                        h_parent = st.leaf_hist[leafs]
                        match = leafs[:, None] == st.pending["cidx"][None, :]
                        hit = jnp.any(match, axis=1)
                        src = jnp.argmax(match, axis=1)
                        h_parent = jnp.where(hit[:, None, None, None],
                                             p_hist[src], h_parent)

                # ---- children metadata --------------------------------------
                # (depends only on the store read; the split reads it after
                # the slot-bucket switch)
                cleafs = jnp.stack([leafs, nls], axis=1).reshape(2 * K)
                csums = jnp.stack([lsums, rsums], axis=1).reshape(2 * K, 3)
                if use_inter:
                    # fresh per-round constraints from leaf-region adjacency —
                    # the outputs of neighbouring leaves may have changed since
                    # this leaf's constraint was stored (the reference's
                    # leaves_to_update_ propagation, monotone_constraints.hpp)
                    constr_tab = intermediate_constraints(
                        st.leaf_box, store.leaf_out_full(st.store),
                        st.num_leaves, inter_feats, inter_types)
                    pconstr = constr_tab[leafs]                   # (K, 2)
                elif use_mc:
                    pconstr = rd["pconstr"]                       # (K, 2)
                else:
                    pconstr = pconstr_const     # hoisted NO_CONSTRAINT rows
                pout = rd["pout"]                                 # (K,)
                out_l = jax.vmap(clamp_out)(lsums, pconstr, pout)
                out_r = jax.vmap(clamp_out)(rsums, pconstr, pout)
                if use_inter:
                    # children bounded by the SIBLING's actual output
                    # (UpdateConstraintsWithOutputs, monotone_constraints.hpp:154)
                    mono = meta.monotone_type[feats]
                    upd = (~iscats) & (mono != 0)
                    max_l = jnp.where(upd & (mono > 0),
                                      jnp.minimum(pconstr[:, 1], out_r),
                                      pconstr[:, 1])
                    min_l = jnp.where(upd & (mono < 0),
                                      jnp.maximum(pconstr[:, 0], out_r),
                                      pconstr[:, 0])
                    max_r = jnp.where(upd & (mono < 0),
                                      jnp.minimum(pconstr[:, 1], out_l),
                                      pconstr[:, 1])
                    min_r = jnp.where(upd & (mono > 0),
                                      jnp.maximum(pconstr[:, 0], out_l),
                                      pconstr[:, 0])
                    constr_l = jnp.stack([min_l, max_l], axis=1)
                    constr_r = jnp.stack([min_r, max_r], axis=1)
                elif use_mc:
                    # BasicLeafConstraints::Update (monotone_constraints.hpp:99)
                    mono = meta.monotone_type[feats]
                    mid = 0.5 * (out_l + out_r)
                    upd = (~iscats) & (mono != 0)
                    max_l = jnp.where(upd & (mono > 0),
                                      jnp.minimum(pconstr[:, 1], mid), pconstr[:, 1])
                    min_l = jnp.where(upd & (mono < 0),
                                      jnp.maximum(pconstr[:, 0], mid), pconstr[:, 0])
                    max_r = jnp.where(upd & (mono < 0),
                                      jnp.minimum(pconstr[:, 1], mid), pconstr[:, 1])
                    min_r = jnp.where(upd & (mono > 0),
                                      jnp.maximum(pconstr[:, 0], mid), pconstr[:, 0])
                    constr_l = jnp.stack([min_l, max_l], axis=1)
                    constr_r = jnp.stack([min_r, max_r], axis=1)
                if use_mc:
                    cconstr = jnp.stack([constr_l, constr_r],
                                        axis=1).reshape(2 * K, 2)
                else:
                    cconstr = cconstr_const     # hoisted NO_CONSTRAINT rows
                couts = jnp.stack([out_l, out_r], axis=1).reshape(2 * K)
                d = rd["pdepth"] + 1                              # (K,)
                cdepth = jnp.stack([d, d], axis=1).reshape(2 * K)
                depth_ok = (max_depth <= 0) | (cdepth < max_depth)
                cvalid = jnp.stack([valid, valid], axis=1).reshape(2 * K)
                # a round none of whose children can be split measures no
                # histograms: every cell's last round, and a depth-limited
                # tree's last level
                measure = children_can_split(st.num_leaves, n_split, L,
                                             depth_ok, cvalid)

                cuids = jnp.stack([2 * nodes + 1, 2 * nodes + 2],
                                  axis=1).reshape(2 * K)
                if use_groups:
                    # branch-feature tracking feeds ONLY the interaction-
                    # constraint mask — with no groups the whole block is
                    # hoisted away (dead per-round one-hot + scatter)
                    used_child = st.leaf_used[leafs] | jax.nn.one_hot(
                        feats, F, dtype=bool)                     # (K, F)
                    cused = jnp.stack([used_child, used_child], axis=1) \
                        .reshape(2 * K, F)
                    allow = jax.vmap(allowed_features)(cused)     # (2K, F)
                else:
                    cused = allow = None
                if feature_fraction_bynode < 1.0:
                    cmask = jax.vmap(
                        lambda u: _node_feature_mask(key, u, base_mask,
                                                     feature_fraction_bynode)
                    )(cuids)
                    if allow is not None:
                        cmask = cmask & allow
                elif allow is not None:
                    cmask = jnp.broadcast_to(base_mask, (2 * K, F)) & allow
                else:
                    cmask = cmask_const         # hoisted: same mask every round

                if use_inter:
                    # child regions: a numerical split cuts the parent's box at
                    # thr+1 along the split feature; categorical children keep
                    # the parent box (conservative: more adjacency, never less)
                    pbox = st.leaf_box[leafs]                     # (K, F, 2)
                    kio = jnp.arange(K)
                    cut = jnp.where(iscats, pbox[kio, feats, 1], thrs + 1)
                    box_l = pbox.at[kio, feats, 1].set(cut)
                    cut_lo = jnp.where(iscats, pbox[kio, feats, 0], thrs + 1)
                    box_r = pbox.at[kio, feats, 0].set(cut_lo)

            # ---- decision + labeling + histogram, sliced to S slots -------
            # One vectorized (S, N) decision pass (the analog of K
            # DataPartition::Split scatters) + one (S+1)-slot histogram.
            # ``round_pass(S)`` is traced per slot bucket; the round's
            # n_split <= S splits are compacted to slots 0..n_split-1 via
            # ``order`` (cumsum of valid — dense even when the intermediate-
            # monotone deferral clears mid-prefix picks).
            def round_pass(S):
                with jax.named_scope(round_scope(S, slot_buckets)):
                    with jax.named_scope("lgbm.select"):
                        sidx = jnp.where(valid, order_c, S)  # (K,) slot|drop

                        def to_slot(v, fill):
                            base = jnp.full((S,) + v.shape[1:], fill, v.dtype)
                            return base.at[sidx].set(v, mode="drop")

                        feats_s = to_slot(feats, 0)
                        thrs_s = to_slot(thrs, 0)
                        dls_s = to_slot(dls, False)
                        # empty slots carry leaf id L: matches no row's leaf
                        leafs_s = to_slot(leafs, L)
                        nls_s = to_slot(nls, 0)
                        sml_s = to_slot(sm_left, False)
                        iscats_s = to_slot(iscats, False) if use_cat else None
                        bitsets_s = to_slot(bitsets, 0) if use_cat else None

                        mt_s = meta.missing_type[feats_s]
                        nan_s = meta.nan_bin[feats_s]
                        zero_s = meta.zero_bin[feats_s]

                    def go_left_s(matrix):
                        """(S, rows) left-decision of this round's splits —
                        shared by the train partition and valid routing
                        (``go_left_rule`` is the single decision source)."""
                        bk = jax.vmap(lambda f: bins_of_fn(matrix, f))(feats_s)
                        bk = bk.astype(jnp.int32)
                        g = go_left_rule(bk, thrs_s[:, None], dls_s[:, None],
                                         mt_s[:, None], nan_s[:, None],
                                         zero_s[:, None])
                        if use_cat:  # categorical bitset membership
                            word = jnp.zeros(bk.shape, jnp.uint32)
                            for wv in range(W):
                                word = jnp.where(
                                    (bk >> 5) == wv,
                                    bitsets_s[:, wv][:, None], word)
                            in_set = ((word >> (bk.astype(jnp.uint32) & 31))
                                      & 1) == 1
                            g = jnp.where(iscats_s[:, None], in_set, g)
                        return g

                    # the train rows' partition: one algorithm (go_left_rule,
                    # then assign_rows) in two memory forms, chosen from the
                    # shapes (ops/partition_pallas.py)
                    path = partition_path_of(S)
                    count_partition_round(path, S)
                    with jax.named_scope("lgbm.partition"):
                        if path == "kernel":
                            leaf_id, label = partition_pallas(
                                bins, st.leaf_id,
                                dict(feats=feats_s, thrs=thrs_s, dls=dls_s,
                                     leafs=leafs_s, nls=nls_s, sml=sml_s,
                                     mt=mt_s, nan=nan_s, zero=zero_s),
                                use_sub=use_sub, missing=has_missing,
                                packed=stored_layout == "packed4",
                                interpret=pallas_interpret)
                        else:
                            leaf_id, label = (v[0] for v in assign_rows(
                                go_left_s(bins), st.leaf_id[None, :],
                                leafs_s[:, None], nls_s[:, None],
                                sml_s[:, None], S, use_sub))
                        vl_new = []
                        if not pipeline:
                            # pipelined rounds defer valid routing to the
                            # next body's drain (route_pending) — off this
                            # round's critical path, bit-identical updates
                            for vb, vl in zip(valids, st.valid_lids):
                                gv = go_left_s(vb)
                                mine_v = vl[None, :] == leafs_s[:, None]
                                go_rv = mine_v & (~gv)
                                vl_new.append(vl + jnp.sum(
                                    jnp.where(go_rv,
                                              nls_s[:, None] - vl[None, :],
                                              0),
                                    axis=0))

                    # sustained rounds (the LARGEST bucket of a big wave) may
                    # run the configured cheaper deep precision; ramp rounds
                    # and the root pass always keep full precision.  With
                    # bucketing off (small N) there ARE no separate ramp
                    # variants — everything stays full precision
                    deep = S == K and K >= 32 and len(slot_buckets) > 1
                    nsl = S if use_sub else 2 * S
                    full = 2 * K if not use_sub else K

                    def measured():
                        if S in quant_buckets:
                            # stochastic-rounded int8 pass: integer
                            # histogram + per-slot dequant scales, rounding
                            # stream keyed per (tree, round)
                            h, hsc = hist_wave_quant_fn(binned, g3, label,
                                                        nsl, rkey)
                        else:
                            h = hist_wave_fn(binned, g3, label, nsl,
                                             deep=deep)
                            hsc = jnp.ones((nsl, 3), jnp.float32)
                        if h.shape[0] < full:   # the bucket-invariant width
                            with jax.named_scope("lgbm.select"), \
                                    jax.named_scope(POOL_SCOPE):
                                h = jnp.concatenate(
                                    [h, jnp.zeros((full - h.shape[0],)
                                                  + h.shape[1:], h.dtype)],
                                    axis=0)
                                # padded slots dequantize as identity
                                hsc = jnp.concatenate(
                                    [hsc, jnp.ones((full - hsc.shape[0], 3),
                                                   hsc.dtype)], axis=0)
                        return h, hsc

                    def unmeasured():
                        with jax.named_scope("lgbm.select"), \
                                jax.named_scope(POOL_SCOPE):
                            return (jnp.zeros((full,) + hist0.shape,
                                              hist0.dtype),
                                    jnp.ones((full, 3), jnp.float32))

                    h, hsc = lax.cond(measure, measured, unmeasured)
                    return (h, hsc, leaf_id) + tuple(vl_new)

            if len(slot_buckets) > 1:
                with jax.named_scope("lgbm.select"):
                    s_idx = jnp.zeros((), jnp.int32)
                    for S in slot_buckets[:-1]:
                        s_idx = s_idx + (n_split > S).astype(jnp.int32)
                    # the bucket this round is taken in, counted where it
                    # is chosen: exact by construction
                    rounds = st.rounds + (
                        jnp.arange(len(slot_buckets)) == s_idx)
                outs = lax.switch(
                    s_idx,
                    [lambda S=S: round_pass(S) for S in slot_buckets])
            else:
                with jax.named_scope("lgbm.select"):
                    rounds = st.rounds + 1
                outs = round_pass(slot_buckets[0])
            h_slot, hscale, leaf_id = outs[0], outs[1], outs[2]
            new_vlids = vlids_in if pipeline else tuple(outs[3:])

            cscale = None                   # per-child dequant (quant rounds)
            with jax.named_scope("lgbm.select"):
                if use_sub:
                    # ---- smaller-child histograms + subtraction --------------
                    # quant rounds fold the per-slot dequantization into the
                    # subtraction pass (slot_scale); non-quant rounds carry
                    # all-ones scales and skip the multiply entirely
                    with jax.named_scope(POOL_SCOPE):
                        hist, h_left, h_right = subtract_child_hists(
                            h_slot, leaf_hist_in, leafs, order_c, sm_left,
                            slot_scale=hscale if quant_buckets else None,
                            h_parent=h_parent)
                else:
                    ch_idx = jnp.stack([2 * order_c, 2 * order_c + 1],
                                       axis=1).reshape(2 * K)
                    hist = h_slot[ch_idx]              # slot-order -> rank-order
                    if quant_buckets:
                        # children come straight from the (possibly quantized)
                        # pass: hand the split scan the integer histograms +
                        # per-child scales (dequantize-aware scan) when the
                        # split accepts them, else dequantize here
                        cscale = hscale[ch_idx]                       # (2K, 3)
                        if not takes_scale:
                            hist = hist * cscale[:, None, None, :]
                            cscale = None

            # ---- batched split finding over the 2K children ---------------
            if cscale is not None:
                # dequantize-aware scan: integer histograms + per-child
                # scales go straight into the gain cumsum (ops/split.py)
                res = jax.vmap(
                    lambda h, hs, p, m, u, c, dd, po: split_fn(
                        h, p, m, key, u, c, dd, po, hist_scale=hs)
                )(hist, cscale, csums, cmask, cuids, cconstr, cdepth, couts)
            else:
                res = jax.vmap(
                    lambda h, p, m, u, c, dd, po: split_fn(h, p, m, key, u,
                                                           c, dd, po)
                )(hist, csums, cmask, cuids, cconstr, cdepth, couts)
            with jax.named_scope("lgbm.select"):
                cgain = jnp.where(depth_ok, res.gain, -jnp.inf)
                cidx = jnp.where(cvalid, cleafs, L + 1)           # drop slot

                # ---- tree assembly + frontier commit ------------------------
                # One store.write per round: the packed store coalesces the
                # whole commit into a 2K-row frontier-table scatter, a K-row
                # node-table scatter and a 2-column pointer fixup; the legacy
                # store reproduces the historical ~30 per-field scatters.
                nidx = jnp.where(valid, nodes, L1 + 1)
                lidx = jnp.where(valid, leafs, L + 1)
                nlidx = jnp.where(valid, nls, L + 1)
                p = rd["parent"]
                was_left = rd["was_left"]
                fix_l = jnp.where(valid & (p >= 0) & was_left,
                                  jnp.maximum(p, 0), L1 + 1)
                fix_r = jnp.where(valid & (p >= 0) & (~was_left),
                                  jnp.maximum(p, 0), L1 + 1)
                psum_k = lsums + rsums                            # parent sums
                new_store = store.write(st.store, dict(
                    res=res, cgain=cgain, cidx=cidx, nidx=nidx,
                    lidx=lidx, nlidx=nlidx, fix_l=fix_l, fix_r=fix_r,
                    leafs=leafs, nls=nls,
                    feats=feats, thrs=thrs, dls=dls,
                    iscats=iscats, bitsets=bitsets,
                    mtypes=meta.missing_type[feats],
                    vals=vals, pout=pout, psum=psum_k,
                    lsums=lsums, rsums=rsums, csums=csums,
                    out_l=out_l, out_r=out_r, couts=couts,
                    cdepth=cdepth, cconstr=cconstr,
                    num_leaves_new=st.num_leaves + n_split,
                ))

                if pipeline:
                    # this round's commits become the NEXT round's pending:
                    # the (already drained-in) table rides forward unchanged
                    # and the scatter + valid routing defer one round
                    leaf_hist = leaf_hist_in
                    new_pending = dict(
                        cidx=cidx,
                        feats=feats, thrs=thrs, dls=dls,
                        leafs=jnp.where(valid, leafs, L), nls=nls,
                    )
                    if use_sub:
                        new_pending["hist"] = hist
                    if use_cat:
                        new_pending["iscats"] = iscats
                        new_pending["bitsets"] = bitsets
                elif use_sub:
                    # packed: ONE interleaved scatter at cidx (hist is already
                    # the rank-interleaved (2K, ...) child stack); legacy: the
                    # historical two half-scatters
                    with jax.named_scope(POOL_SCOPE):
                        leaf_hist = (
                            st.leaf_hist.at[cidx].set(hist, mode="drop")
                            if store.fused else
                            st.leaf_hist.at[lidx].set(h_left, mode="drop")
                            .at[nlidx].set(h_right, mode="drop"))
                    new_pending = st.pending
                else:
                    leaf_hist = st.leaf_hist
                    new_pending = st.pending

                return WaveState(
                    leaf_id=leaf_id,
                    valid_lids=new_vlids,
                    leaf_hist=leaf_hist,
                    store=new_store,
                    leaf_box=(st.leaf_box.at[lidx].set(box_l, mode="drop")
                              .at[nlidx].set(box_r, mode="drop")
                              if use_inter else st.leaf_box),
                    leaf_used=(st.leaf_used.at[cidx].set(cused, mode="drop")
                               if use_groups else st.leaf_used),
                    num_leaves=st.num_leaves + n_split,
                    done=st.done | (n_split == 0),
                    rounds=rounds,
                    hist_skipped=st.hist_skipped + (~measure),
                    pending=new_pending,
                )

        if L > 1:
            st = lax.while_loop(cond, body, st)
        with jax.named_scope("lgbm.select"):
            tree = store.finalize(st.store, st.num_leaves)
        vlids_out = st.valid_lids
        if pipeline and valids:
            # drain: the final round's valid routing is still pending when
            # the loop exits (the histogram-state scatter is dead — the
            # table is intra-growth state).  After this the returned
            # routing is exactly the sequential schedule's, so checkpoint
            # and snapshot boundaries see fully-applied state and PR 6's
            # kill-at-k bit-exact resume guarantee is unchanged.
            vlids_out = tuple(route_pending(st.pending, vb, vl)
                              for vb, vl in zip(valids, vlids_out))
        third = RootAndRounds(root_sum, st.rounds, st.hist_skipped)
        if valids:
            return tree, st.leaf_id, third, vlids_out
        return tree, st.leaf_id, third

    grow._supports_valids = True
    return grow
