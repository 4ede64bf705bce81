"""The row-tiled partition kernel (ops/partition_pallas.py) under the
interpreter against the gather form it replaces: integers, so equal or
wrong.  The ``packed4`` layout (two features' nibbles a byte) against a
plain numpy reference.  And ``partition_path``, the rule on shapes that
picks the form, with the counter and the gauge that say what it picked."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbmv1_tpu as lgb
from lightgbmv1_tpu.models import grower_wave as gw
from lightgbmv1_tpu.obs.metrics import default_registry
from lightgbmv1_tpu.ops.hist_pallas import (pack4bit, packed_bins_of_feat,
                                            unpack4bit)
from lightgbmv1_tpu.ops.histogram import hist_wave
from lightgbmv1_tpu.ops.partition_pallas import (partition_bytes,
                                                 partition_gather,
                                                 partition_pallas,
                                                 partition_path)
from lightgbmv1_tpu.ops.split import (MISSING_NAN, MISSING_NONE,
                                      MISSING_ZERO, FeatureMeta, SplitParams)

B = 16                       # bins: every id turns up in every column
LEAVES = 255                 # an empty slot's leaf id


def _round(rng, F, S, N, offset):
    """One round's operands: ``live`` of the ``S`` slots split a leaf, the
    rest are empty; slot s is of missing type (s + offset) % 3 and default
    direction ((s + offset) // 3) % 2."""
    live = max(1, S - 2)
    s = np.arange(S) + offset
    cols = dict(
        feats=rng.randint(0, F, S), thrs=rng.randint(0, B - 1, S),
        dls=(s // 3) % 2 == 1,
        leafs=np.where(np.arange(S) < live, np.arange(S), LEAVES),
        nls=100 + np.arange(S), sml=rng.rand(S) < 0.5,
        mt=np.asarray([MISSING_NONE, MISSING_ZERO, MISSING_NAN])[s % 3],
        nan=np.full(S, B - 1), zero=rng.randint(0, B - 1, S))
    bins = rng.randint(0, B, (F, N)).astype(np.uint8)
    # rows of leaves no slot splits too
    leaf_id = rng.randint(0, live + 3, N).astype(np.int32)
    for slot in range(live):   # a row in the nan_bin and one in the zero_bin
        at = np.flatnonzero(leaf_id == slot)[:2]
        bins[cols["feats"][slot], at] = [B - 1, cols["zero"][slot]]
    return bins, leaf_id, {
        k: jnp.asarray(v if v.dtype == bool else v.astype(np.int32))
        for k, v in cols.items()}


@pytest.mark.parametrize("use_sub", [True, False], ids=["sub", "pool_free"])
@pytest.mark.parametrize("F", [1, 28, 67, 137])
@pytest.mark.parametrize("S", [1, 4, 16, 63])
def test_kernel_equals_gather(S, F, use_sub):
    """1 / 4 / 16 / 63 slots with empty ones among them, widths that are
    no multiple of a ``u8`` tile's 32 rows, a row count that is no multiple
    of the block (an edge block) nor of the chunk, every ``missing_type``
    under both default directions with rows in the ``nan_bin`` and the
    ``zero_bin`` of the split leaf, both labelings."""
    rng = np.random.RandomState(1000 * S + F)
    N = 2048 + 300 + 37
    seen, live = set(), max(1, S - 2)
    for offset in range(0, 6, min(live, 6)):
        bins, leaf_id, cols = _round(rng, F, S, N, offset)
        mt, dls = np.asarray(cols["mt"]), np.asarray(cols["dls"])
        for s in range(live):
            col = bins[int(cols["feats"][s])][leaf_id == s]
            assert (col == B - 1).any() and \
                (col == int(cols["zero"][s])).any()
            seen.add((int(mt[s]), bool(dls[s])))
        want = partition_gather(jnp.asarray(bins), jnp.asarray(leaf_id),
                                cols, use_sub=use_sub)
        # 2,048-row blocks: two chunks a block, the second block an edge
        got = partition_pallas(jnp.asarray(bins), jnp.asarray(leaf_id), cols,
                               use_sub=use_sub, row_block=2048,
                               interpret=True)
        for g, w in zip(got, want):
            assert g.dtype == jnp.int32 and g.shape == (N,)
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        new, label = (np.asarray(v) for v in got)
        dead = S if use_sub else 2 * S
        assert label.max() == dead and label.min() == 0
        assert ((label == dead) | (leaf_id < live)).all()
        assert set(np.unique(new[new != leaf_id])) <= set(
            range(100, 100 + S))
    assert len(seen) == 6, seen


def _reference_round(bins, leaf_id, cols, use_sub):
    """What a round does, in numpy on the unpacked ``(F, N)`` bins: a row
    of the leaf slot s splits reads its bin at the slot's feature, goes
    left where ``go_left_rule`` says (NaN / zero rows follow the default
    direction, the rest ``bin <= thr``), stays in its leaf or moves to the
    slot's new right leaf, and takes the label ``assign_rows`` documents;
    a row of a leaf no slot splits keeps its leaf and the dead label."""
    c = {k: np.asarray(v).astype(np.int64) for k, v in cols.items()}
    S = len(c["feats"])
    dead = S if use_sub else 2 * S
    new, label = leaf_id.astype(np.int64).copy(), np.full(len(leaf_id), dead)
    for s in range(S):
        mine = leaf_id == c["leafs"][s]
        b = bins[c["feats"][s]].astype(np.int64)
        na = (((c["mt"][s] == MISSING_NAN) & (b == c["nan"][s]))
              | ((c["mt"][s] == MISSING_ZERO) & (b == c["zero"][s])))
        left = np.where(na, c["dls"][s] != 0, b <= c["thrs"][s])
        new[mine & ~left] = c["nls"][s]
        if use_sub:
            label[mine & (left == (c["sml"][s] != 0))] = s
        else:
            label[mine] = 2 * s + (~left[mine])
    return new, label


@pytest.mark.parametrize("missing", [True, False], ids=["missing", "none"])
@pytest.mark.parametrize("F", [7, 28, 67])
@pytest.mark.parametrize("S", [1, 4, 16, 63])
def test_packed_kernel_equals_reference(S, F, missing):
    """The kernel on the ``packed4`` matrix (``(ceil(F/2), N)``: the
    selection picks byte row ``f >> 1``, a shift and a mask the nibble)
    against the numpy reference on the unpacked bins, and beside it the
    gather form on the same packed matrix and the ``u8`` kernel on the
    unpacked one: an odd F (the last feature's byte has a phantom hi
    nibble), a slot on the last feature and one splitting at bin 0, rows
    in every slot's nan and zero bins, an edge block, both labelings,
    every missing type or none (the kernel's static fold)."""
    rng = np.random.RandomState(7000 + 100 * S + F)
    N = 2048 + 300 + 37
    bins, leaf_id, cols = _round(rng, F, S, N, offset=S % 3)
    cols["feats"] = cols["feats"].at[0].set(F - 1)
    if S > 1:
        cols["thrs"] = cols["thrs"].at[1].set(0)
    if not missing:
        cols["mt"] = jnp.full(S, MISSING_NONE, jnp.int32)
    packed = jnp.asarray(pack4bit(bins))
    assert packed.shape == (-(-F // 2), N)
    np.testing.assert_array_equal(unpack4bit(np.asarray(packed), F), bins)
    for use_sub in (True, False):
        want = _reference_round(bins, leaf_id, cols, use_sub)
        forms = {
            "packed kernel": partition_pallas(
                packed, jnp.asarray(leaf_id), cols, use_sub=use_sub,
                missing=missing, packed=True, row_block=2048,
                interpret=True),
            "packed gather": partition_gather(
                packed, jnp.asarray(leaf_id), cols, use_sub=use_sub,
                packed=True),
            "u8 kernel": partition_pallas(
                jnp.asarray(bins), jnp.asarray(leaf_id), cols,
                use_sub=use_sub, missing=missing, row_block=2048,
                interpret=True)}
        for form, got in forms.items():
            for g, w in zip(got, want):
                assert g.dtype == jnp.int32 and g.shape == (N,)
                np.testing.assert_array_equal(np.asarray(g), w,
                                              err_msg=form)
        # the round moved rows and labelled rows of every live slot
        assert (want[0] != leaf_id).any()


def _meta(F, rng=None):
    kinds = (np.zeros(F, np.int32) if rng is None
             else rng.randint(0, 3, F).astype(np.int32))
    return FeatureMeta(
        num_bins=jnp.full(F, B, jnp.int32),
        missing_type=jnp.asarray(kinds),
        nan_bin=jnp.where(jnp.asarray(kinds) == MISSING_NAN, B - 1, -1),
        zero_bin=jnp.asarray(np.arange(F, dtype=np.int32) % (B - 1)),
        is_categorical=jnp.zeros(F, bool),
        usable=jnp.ones(F, bool),
        monotone_type=jnp.zeros(F, jnp.int32),
    )


def _forced(monkeypatch, path):
    monkeypatch.setattr(gw, "partition_path", lambda *a, **k: path)


@pytest.mark.parametrize("use_sub", [True, False], ids=["sub", "pool_free"])
def test_whole_grow_kernel_against_gather(monkeypatch, use_sub):
    """One 40-leaf tree over 4- and 15-slot buckets with the kernel in
    every round and with the gather form in every round: the same tree,
    leaf ids and, round for round, histogram labels (of the rounds that
    measure their children: the one that spends the last leaves runs no
    pass, so the tree has leaves for a round after the first of 15
    splits)."""
    rng = np.random.RandomState(7)
    N, F = 3000, 28
    bins = jnp.asarray(rng.randint(0, B, (F, N)).astype(np.uint8))
    informative = np.asarray(bins[:4]).astype(np.float32).sum(axis=0)
    g3 = jnp.asarray(np.stack(
        [informative - informative.mean() + rng.randn(N),
         np.ones(N), np.ones(N)], axis=1).astype(np.float32))
    monkeypatch.setattr(gw, "_BUCKET_MIN_N", 1)
    if not use_sub:
        monkeypatch.setattr(gw, "_SUB_STATE_CAP_BYTES", 0)

    def run(path):
        labels = []

        def hist(b, g, l, n, deep=False):
            jax.debug.callback(lambda v: labels.append(np.asarray(v)), l,
                               ordered=True)
            return hist_wave(b, g, l, n, B, method="pallas",
                             precision="f32", interpret=True)

        _forced(monkeypatch, path)
        grow = gw.make_wave_grower(
            num_leaves=40, num_bins=B, meta=_meta(F, rng=np.random.RandomState(1)),
            params=SplitParams(min_data_in_leaf=2.0), wave_size=15,
            hist_wave_fn=hist, hist_method="pallas", pallas_interpret=True,
            stored_layout="u8")
        tree, leaf_id, _ = jax.block_until_ready(jax.jit(grow)(
            bins, g3, jnp.ones(F, bool), jax.random.PRNGKey(0)))
        return tree, np.asarray(leaf_id), labels

    before = _traced()
    tree_k, leaf_k, labels_k = run("kernel")
    assert _traced_since(before) == {("kernel", 4): 1, ("kernel", 15): 1}
    tree_g, leaf_g, labels_g = run("gather")
    assert int(tree_k.num_leaves) == 40
    for a, b in zip(tree_k, tree_g):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(leaf_k, leaf_g)
    assert len(labels_k) == len(labels_g) > 3
    for a, b in zip(labels_k, labels_g):
        np.testing.assert_array_equal(a, b)
    # the last rounds leave rows behind: the 15-slot bucket's dead label
    assert (15 if use_sub else 30) in {int(l.max()) for l in labels_k}


def test_booster_with_the_kernel_is_the_booster_without(monkeypatch):
    """``lgb.train`` with NaN in the data, ``hist_method=pallas`` (the
    interpreter on this backend): the model text with the kernel in every
    round is the model text with the gather form in every round."""
    rng = np.random.RandomState(3)
    rows, F = 3000, 28
    X = rng.randn(rows, F).astype(np.float32)
    X[rng.rand(rows, F) < 0.05] = np.nan
    y = (1.2 * np.nan_to_num(X[:, 0]) - np.nan_to_num(X[:, 1])
         + rng.randn(rows) > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
              "min_data_in_leaf": 5, "hist_method": "pallas",
              "verbosity": -1}
    monkeypatch.setattr(gw, "_BUCKET_MIN_N", 1)
    texts = []
    for path in ("kernel", "gather"):
        _forced(monkeypatch, path)
        texts.append(lgb.train(dict(params), lgb.Dataset(X, label=y),
                               num_boost_round=2).model_to_string())
    assert texts[0] == texts[1] and "Tree=1" in texts[0]


# ---- the rule --------------------------------------------------------------

def _path(columns, slots, rows=1_000_000, **kw):
    kw = {"pallas": True, "layout": "u8", "use_cat": False, **kw}
    return partition_path(columns, slots, rows, **kw)


@pytest.mark.parametrize("columns,slots,path", [
    (28, 4, "kernel"), (28, 16, "kernel"), (28, 63, "kernel"),     # higgs
    (67, 4, "kernel"), (67, 16, "kernel"), (67, 63, "kernel"),     # criteo
    (137, 4, "gather"), (137, 16, "kernel"), (137, 63, "kernel"),  # mslr
    (2000, 4, "gather"), (2000, 16, "gather"), (2000, 63, "gather"),
    # a block of 1,024 rows of a taller matrix is over the VMEM budget
    (2048, 128, "kernel"), (2049, 128, "gather"),
])
def test_partition_path_at_the_cells_shapes(columns, slots, path):
    assert _path(columns, slots) == path


def test_partition_path_keeps_the_gather_form_for_what_the_kernel_cannot_read():
    assert _path(28, 63) == "kernel"
    assert _path(28, 63, use_cat=True) == "gather"
    assert _path(28, 63, layout="bundle") == "gather"   # an EFB column
    assert _path(28, 63, layout="wide") == "gather"     # 16-bit bins
    assert _path(28, 63, pallas=False) == "gather"      # XLA:CPU's methods
    # under a chunk of rows Mosaic and the compiler tile a 1-D array apart
    assert _path(28, 63, rows=1024) == "kernel"
    assert _path(28, 63, rows=1023) == "gather"


@pytest.mark.parametrize("slots", [1, 4, 16, 63])
def test_partition_path_admits_packed4(slots):
    """``higgs-15b-train``'s matrix, 28 features as 14 stored rows at
    10,500,000 rows: the kernel in every bucket, priced on the stored rows
    (14 pad to a tile's 32, as 28 do); categorical columns and too few
    rows still keep the gather form."""
    assert _path(14, slots, rows=10_500_000, layout="packed4") == "kernel"
    assert _path(14, slots, rows=10_500_000, layout="packed4") == \
        _path(28, slots, rows=10_500_000)
    assert _path(14, slots, layout="packed4", use_cat=True) == "gather"
    assert _path(14, slots, rows=1023, layout="packed4") == "gather"


def _traced():
    snap = default_registry().snapshot()
    out = {}
    for key, value in snap.items():
        if key.startswith("partition_rounds_traced_total{"):
            path = key.split('path="')[1].split('"')[0]
            slots = int(key.split('slots="')[1].split('"')[0])
            out[path, slots] = int(value)
    return out


def _traced_since(before):
    return {k: v - before.get(k, 0) for k, v in _traced().items()
            if v != before.get(k, 0)}


def _bytes_gauge(path):
    return int(default_registry().snapshot()[
        'partition_bytes_per_round{path="%s"}' % path])


def _trace_grow(matrix_shape, dtype=jnp.uint8, features=None, **grower):
    """Trace (nothing runs, nothing is compiled) a 255-leaf wave grower's
    ``grow`` on a matrix of that shape; histograms of zeros."""
    F = features or matrix_shape[0]
    N = matrix_shape[1]
    grow = gw.make_wave_grower(
        num_leaves=255, num_bins=64, meta=grower.pop("meta", _meta(F)),
        params=SplitParams(), wave_size=63,
        hist_wave_fn=lambda b, g, l, n, deep=False: jnp.zeros(
            (n, F, 64, 3), jnp.float32),
        **{"hist_method": "pallas", "stored_layout": "u8", **grower})
    jax.eval_shape(grow, jax.ShapeDtypeStruct(matrix_shape, dtype),
                   jax.ShapeDtypeStruct((N, 3), jnp.float32),
                   jax.ShapeDtypeStruct((F,), jnp.bool_),
                   jax.random.PRNGKey(0))


def test_counter_and_gauge_at_higgs_and_epsilon_shapes():
    """``partition_rounds_traced_total`` and ``partition_bytes_per_round``
    against the shapes' arithmetic: 10,500,000 x 28 takes the kernel in its
    three buckets and moves the padded matrix and 12 bytes a row; 400,000 x
    2,000 the gather form in its three and 10 bytes a slot-row."""
    before = _traced()
    _trace_grow((28, 10_500_000))
    assert _traced_since(before) == {
        ("kernel", 4): 1, ("kernel", 16): 1, ("kernel", 63): 1}
    assert _bytes_gauge("kernel") == 32 * 10_500_000 + 12 * 10_500_000 \
        == partition_bytes("kernel", 28, 63, 10_500_000)

    before = _traced()
    _trace_grow((2000, 400_000))
    assert _traced_since(before) == {
        ("gather", 4): 1, ("gather", 16): 1, ("gather", 63): 1}
    assert _bytes_gauge("gather") == 63 * 400_000 * 10 \
        == partition_bytes("gather", 2000, 63, 400_000)

    # mslr-train: the kernel where the slots pay for 137 columns
    before = _traced()
    _trace_grow((137, 2_270_296))
    assert _traced_since(before) == {
        ("gather", 4): 1, ("kernel", 16): 1, ("kernel", 63): 1}
    assert _bytes_gauge("kernel") == 160 * 2_270_296 + 12 * 2_270_296


def test_counter_and_gauge_at_the_packed_higgs_shape():
    """``higgs-15b-train``'s packed matrix (14 stored rows of 28 features
    at 10,500,000 rows): every bucket's round counted under
    ``path="kernel"``, the bytes those of the stored rows padded to a tile
    and 12 a row."""
    N = 10_500_000
    before = _traced()
    _trace_grow((14, N), features=28, bins_of_fn=packed_bins_of_feat,
                stored_layout="packed4")
    assert _traced_since(before) == {
        ("kernel", 4): 1, ("kernel", 16): 1, ("kernel", 63): 1}
    assert _bytes_gauge("kernel") == 32 * N + 12 * N \
        == partition_bytes("kernel", 14, 63, N)


def test_grower_keeps_the_gather_form_off_the_plain_u8_matrix():
    """What the booster's plan says a stored row holds decides
    (``parallel/trainer.resolve_hist_plan``, its ``layout``): a
    categorical column, a bundled matrix (fewer stored columns than
    features, its own ``bins_of_fn``), 16-bit bins, a histogram method
    that is not the Pallas one keep the gather form; the plain ``u8``
    matrix and the ``packed4`` one (``packed_bins_of_feat`` over
    ``ceil(F/2)`` rows) take the kernel."""
    from lightgbmv1_tpu.config import Config
    from lightgbmv1_tpu.parallel.trainer import resolve_hist_plan

    def layout(**kw):
        return resolve_hist_plan(Config(hist_method="pallas"),
                                 **{"bin_dtype": np.uint8, **kw}).layout

    gather = {("gather", 4): 1, ("gather", 16): 1, ("gather", 63): 1}
    N = 100_000
    cat = _meta(28)._replace(
        is_categorical=jnp.zeros(28, bool).at[3].set(True))
    packed = pack4bit(np.zeros((28, 256), np.uint8))
    assert layout(num_total_bin=16) == "packed4"
    for kw in (dict(matrix_shape=(28, N), meta=cat),
               dict(matrix_shape=(packed.shape[0], N), features=28,
                    bins_of_fn=packed_bins_of_feat, meta=cat,
                    stored_layout="packed4"),
               dict(matrix_shape=(9, N), features=28,
                    bins_of_fn=lambda b, f: b[f % 9],
                    stored_layout=layout(bundled=True)),
               dict(matrix_shape=(28, N), dtype=jnp.uint16,
                    stored_layout=layout(bin_dtype=np.uint16)),
               dict(matrix_shape=(28, N), hist_method="onehot")):
        before = _traced()
        _trace_grow(**kw)
        assert _traced_since(before) == gather, kw
    for kw in (dict(matrix_shape=(28, N),
                    stored_layout=layout(num_total_bin=64)),
               dict(matrix_shape=(packed.shape[0], N), features=28,
                    bins_of_fn=packed_bins_of_feat,
                    stored_layout=layout(num_total_bin=16))):
        before = _traced()
        _trace_grow(**kw)
        assert set(_traced_since(before)) == {
            ("kernel", 4), ("kernel", 16), ("kernel", 63)}, kw


def test_trainer_on_xla_cpu_keeps_the_gather_form(monkeypatch):
    """``hist_method=auto`` resolves to an XLA method on this backend: a
    booster's rounds are all of the gather form."""
    rng = np.random.RandomState(0)
    X = rng.randn(600, 5)
    monkeypatch.setattr(gw, "_BUCKET_MIN_N", 1)
    before = _traced()
    lgb.train({"objective": "binary", "num_leaves": 31, "verbosity": -1,
               "min_data_in_leaf": 2},
              lgb.Dataset(X, label=(X[:, 0] > 0).astype(float)),
              num_boost_round=1)
    since = _traced_since(before)
    assert since and {path for path, _ in since} == {"gather"}
