"""Cross-lower every Pallas kernel the package ships for ``tpu`` — from
the CPU suite, no chip needed.

``jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",))`` runs the
Pallas -> Mosaic lowering only (the Mosaic compiler proper runs on the
chip; chip_smoke.py covers that).  Lowering is therefore necessary, not
sufficient — but it is where the two serving kernels stop on the
installed JAX, and nothing else in tier-1 would notice: on CPU they all
run in interpret mode.

A kernel that cannot lower is a STRICT xfail carrying the compiler's
message, so a JAX upgrade or a repair flips it visibly (XPASS fails the
suite until the marker is removed).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbmv1_tpu as lgb
from lightgbmv1_tpu.models.predict import BatchPredictor
from lightgbmv1_tpu.ops.hist_pallas import hist_leaves_pallas, pack4bit
from lightgbmv1_tpu.ops.predict_pallas import (serving_fused_pallas,
                                               serving_leaf_pallas)
from lightgbmv1_tpu.ops.split import FeatureMeta, SplitParams

GATHER = ("Only 2D gather is supported (jnp.take of the flattened 1-D "
          "node table, ops/predict_pallas.py walk body)")


def lower_for_tpu(fn, *args):
    txt = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in txt, "no Mosaic kernel in the lowering"
    return txt


@pytest.fixture(scope="module")
def rows():
    rng = np.random.RandomState(0)
    N = 2048
    return (rng, N, jnp.asarray(rng.randn(N, 3).astype(np.float32)))


@pytest.mark.parametrize("precision",
                         ["bf16x2", "bf16", "f32", "int8", "int8sr"])
@pytest.mark.parametrize("num_bins", [16, 64, 256])
def test_hist_kernel_lowers(rows, num_bins, precision):
    """The default-on-TPU histogram kernel at every kernel-width rung,
    every precision, and the live slots the trainer asks it for: 1 = root
    pass, 4 / 16 / 63 = the wave ladder's buckets (no dead slot: the
    kernel drops a row whose label is no slot's), 64 = a full frontier."""
    rng, N, g3 = rows
    binned = jnp.asarray(rng.randint(0, num_bins, (28, N)).astype(np.uint8))
    for slots in (1, 4, 16, 63, 64):
        leaf = jnp.asarray(rng.randint(0, slots, N).astype(np.int32))
        lower_for_tpu(
            lambda b, g, l: hist_leaves_pallas(b, g, l, slots, num_bins,
                                               precision=precision),
            binned, g3, leaf)


@pytest.mark.parametrize("precision", ["bf16x2", "bf16", "int8sr"])
def test_hist_kernel_packed4_lowers(rows, precision):
    rng, N, g3 = rows
    packed = jnp.asarray(pack4bit(
        rng.randint(0, 16, (27, N)).astype(np.uint8)))     # odd F tail
    for slots in (1, 4, 16, 63):
        leaf = jnp.asarray(rng.randint(0, slots + 1, N).astype(np.int32))
        lower_for_tpu(
            lambda b, g, l: hist_leaves_pallas(
                b, g, l, slots, 16, precision=precision, packed=True,
                num_features=27),
            packed, g3, leaf)


def _big_u8_relayouts(txt, min_elems):
    """``(op, operand shape, result shape)`` of the ``pad`` / ``transpose``
    / ``slice`` ops in a lowered module whose result is a ``ui8`` tensor
    of at least ``min_elems`` elements."""
    found = []
    for line in txt.splitlines():
        m = re.search(r"stablehlo\.(pad|transpose|slice)\b.*\(tensor<"
                      r"([0-9x]+)xui8>.*->\s*tensor<([0-9x]+)xui8>", line)
        if m and np.prod([int(d) for d in m.group(3).split("x")]) >= min_elems:
            found.append(m.groups())
    return found


def _kernel_u8_operands(txt):
    """The ``ui8`` operand types of every ``tpu_custom_call``."""
    out = []
    for line in txt.splitlines():
        if "stablehlo.custom_call @tpu_custom_call" not in line:
            continue
        operands = line.rsplit(" : (", 1)[1].split(") -> ")[0]
        out.append(re.findall(r"tensor<([0-9x]+)xui8>", operands))
    return out


@pytest.mark.parametrize("what", ["pass", "grow"])
def test_prepared_bins_leave_no_relayout(rows, monkeypatch, what):
    """With bins prepared at placement (hist_pallas.HistBins) no pad or
    transposition of a ``ui8`` tensor as large as one feature block of
    every row is left in the lowered histogram pass, nor anywhere in a
    wave grower's ``grow`` (so none in its while loop); the only slice
    cuts a stored block's lane padding off (a bitcast once compiled:
    test_prepared_pass_compiles_without_relayout); and every kernel call
    takes a 2-D ``ui8`` operand whose first dimension is the padded row
    count — what benchmarks/roofline.hist_call_shapes reads as the call's
    rows.  A raw matrix still gets pad, transposition and the cut into
    blocks in every pass."""
    from lightgbmv1_tpu.models import grower_wave as gw
    from lightgbmv1_tpu.ops.histogram import hist_wave
    from lightgbmv1_tpu.ops.hist_pallas import (MAX_ROW_TILE,
                                                prepare_hist_bins)

    rng, N, g3 = rows
    N, F, B = N - 100, 37, 64            # 2 blocks of 32; rows pad to 2048
    g3 = g3[:N]
    raw = jnp.asarray(rng.randint(0, B, (F, N)).astype(np.uint8))
    prepared = prepare_hist_bins(raw, B)
    n_pad = -(-N // MAX_ROW_TILE) * MAX_ROW_TILE
    block_elems = N * 32                 # one feature block of every row
    if what == "pass":
        leaf = jnp.asarray(rng.randint(0, 17, N).astype(np.int32))

        def fn(b):
            return hist_leaves_pallas(b, g3, leaf, 17, B, precision="bf16x2")
    else:
        # every slot bucket (4 / 16 / K) in the while body, at a small N
        monkeypatch.setattr(gw, "_BUCKET_MIN_N", 1)
        grow = gw.make_wave_grower(
            num_leaves=63, num_bins=B, meta=_probe_meta(F, B),
            params=SplitParams(min_data_in_leaf=2.0), wave_size=31,
            hist_wave_fn=lambda b, g, l, n, deep=False: hist_wave(
                b, g, l, n, B, method="pallas",
                precision="bf16" if deep else "bf16x2"))

        def fn(b):
            return grow(b, g3, jnp.ones(F, bool), jax.random.PRNGKey(0))

    txt = lower_for_tpu(fn, prepared)
    assert "stablehlo.while" in txt or what == "pass"
    assert set(_big_u8_relayouts(txt, block_elems)) == {
        ("slice", f"{n_pad}x128", f"{n_pad}x32")}
    calls = _kernel_u8_operands(txt)
    assert calls and all(c == [f"{n_pad}x32"] for c in calls), calls

    txt_raw = lower_for_tpu(fn, raw)
    assert {(op, res) for op, _, res in _big_u8_relayouts(
        txt_raw, block_elems)} == {("pad", f"64x{n_pad}"),
                                   ("transpose", f"{n_pad}x64"),
                                   ("slice", f"{n_pad}x32")}
    assert all(len(c) == 1 and c[0].endswith("x32")
               for c in _kernel_u8_operands(txt_raw))


@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) v5e chip: the TPU compiler proper."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def compile_for_chip(fn, *shapes):
    """``fn`` compiled for the described chip, the compile cache off
    around it: an entry compiled for a described chip cannot be read back
    (it warns)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return jax.jit(fn).lower(*shapes).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def test_prepared_pass_compiles_without_relayout(one_chip):
    """Compiled for the chip, the pass over prepared bins holds no
    temporary as large as one stored block: the blocks' default device
    layout is the row-major one the kernel's call takes and the cut of
    their lane padding is a bitcast.  The raw matrix's pass holds the
    padded transposition and every block (what ``bytes_probe.py`` reads)."""
    from lightgbmv1_tpu.ops.hist_pallas import prepare_hist_bins

    N, F, B = 1_000_000, 37, 64         # too large for on-chip memory
    n_pad, block_bytes = 1_000_448, 1_000_448 * 128

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    def fn(b, g, l):
        return hist_leaves_pallas(b, g, l, 64, B, precision="bf16")

    raw = shape((F, N), jnp.uint8)
    prepared = jax.tree_util.tree_map(
        lambda x: shape(x.shape, x.dtype),
        jax.eval_shape(lambda b: prepare_hist_bins(b, B), raw))
    rest = (shape((N, 3), jnp.float32), shape((N,), jnp.int32))
    got = compile_for_chip(fn, prepared, *rest)
    base = compile_for_chip(fn, raw, *rest)
    assert got.memory_analysis().temp_size_in_bytes < block_bytes
    assert base.memory_analysis().temp_size_in_bytes >= 2 * block_bytes
    txt = got.as_text()
    operand = rf"u8\[{n_pad},32\]\{{1,0:"
    assert len(re.findall(rf"= {operand}\S* bitcast\(", txt)) == 2
    assert not re.search(rf"= {operand}\S* (copy|slice|fusion)\(", txt)
    assert re.search(r"%hist_leaves_pallas[.0-9]* = f32\[1,192,2048\]", txt)


@pytest.mark.parametrize("columns", [137, 2000])
def test_split_scan_temporaries_at_126_children(one_chip, columns):
    """A wave round's scan (``find_best_split`` under ``vmap`` over 126
    children x ``columns`` x 64 bins), compiled for the chip: bytes only.
    On channel-minor arrays the chip tiled the 3 channels to 128 lanes:
    1.13 GB of temporaries at 137 columns and, at 2,000, one array of
    15.4 GB (``RESOURCE_EXHAUSTED``, PERF.md PR 32).  On channel planes
    the 2,000-column scan stays under 2 GB, and no array of it has a
    minor dimension of 3."""
    from lightgbmv1_tpu.ops.split import find_best_split

    K, B = 126, 64
    meta = _probe_meta(columns, B)
    params = SplitParams(min_data_in_leaf=1.0, min_sum_hessian_in_leaf=100.0)

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    def scan(hist, parents, masks):
        return jax.vmap(lambda h, p, m: find_best_split(
            h, p, meta, m, params))(hist, parents, masks)

    got = compile_for_chip(
        scan, shape((K, columns, B, 3), jnp.float32),
        shape((K, 3), jnp.float32), shape((K, columns), jnp.bool_))
    hist_bytes = K * columns * B * 3 * 4
    assert got.memory_analysis().temp_size_in_bytes < min(
        2 * 1024 ** 3, 4 * hist_bytes)
    channel_minor = re.findall(
        rf"f32\[{K},(?:[12],)?{columns},{B},3\]\{{4,3,2,1,0|"
        rf"f32\[{K},{columns},{B},3\]\{{3,2,1,0", got.as_text())
    assert not channel_minor


@pytest.mark.parametrize("queries,width", [(273, 1536), (3276, 128)])
def test_lambdarank_chunk_compiles_heads_by_documents(one_chip, queries,
                                                      width):
    """``LambdarankNDCG._chunk_grads`` compiled for the chip at
    ``mslr-train``'s widest window (12 rows of 128 documents, as many
    queries as the chunk budget admits) and at its commonest (one row, a
    full chunk), 20 heads: the temporaries stay under the budget's bytes
    (8 f32 arrays of ``_PAIRWISE_CHUNK_ELEMS``) and no array is documents
    x documents."""
    from lightgbmv1_tpu import objectives
    from lightgbmv1_tpu.config import Config
    from lightgbmv1_tpu.io.dataset import Metadata

    heads = 20
    assert queries == objectives._PAIRWISE_CHUNK_ELEMS // (width * heads)
    obj = objectives.LambdarankNDCG(Config.from_dict(
        {"objective": "lambdarank", "verbosity": -1,
         "lambdarank_truncation_level": heads}))
    meta = Metadata(label=np.arange(12, dtype=np.float32) % 3)
    meta.set_group(np.array([5, 7]))
    obj.init(meta, 12)

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    got = compile_for_chip(
        obj._chunk_grads, shape((queries, width), jnp.float32),
        shape((queries,), jnp.int32), shape((queries,), jnp.int32),
        shape((queries, width), jnp.float32), shape((queries,), jnp.float32))
    assert got.memory_analysis().temp_size_in_bytes < (
        8 * 4 * objectives._PAIRWISE_CHUNK_ELEMS)
    assert not re.search(rf"\[{queries},{width},{width}\]", got.as_text())


def _kernel_result_rows(txt):
    """Second dimension of every ``tpu_custom_call``'s f32 result."""
    return [int(m) for m in re.findall(
        r"stablehlo\.custom_call @tpu_custom_call.*-> tensor<[0-9]+x([0-9]+)"
        r"x[0-9]+xf32>", txt)]


def test_wave_grow_calls_hold_live_slots_only(rows, monkeypatch):
    """A 255-leaf wave grower's lowered ``grow`` (root pass and the 4 / 16
    / 63 ladder): every histogram call's result block is 3 rows a LIVE
    slot rounded up to 8 — 8 / 16 / 48 / 192 — and none has the parent's
    72 rows (17 slots stored as 24) or 24 (2 and 5 slots stored as 8)."""
    from lightgbmv1_tpu.models import grower_wave as gw
    from lightgbmv1_tpu.ops.histogram import hist_wave
    from lightgbmv1_tpu.ops.hist_pallas import prepare_hist_bins

    rng, N, g3 = rows
    F, B = 37, 64
    monkeypatch.setattr(gw, "_BUCKET_MIN_N", 1)
    grow = gw.make_wave_grower(
        num_leaves=255, num_bins=B, meta=_probe_meta(F, B),
        params=SplitParams(min_data_in_leaf=2.0), wave_size=63,
        hist_wave_fn=lambda b, g, l, n, deep=False: hist_wave(
            b, g, l, n, B, method="pallas",
            precision="bf16" if deep else "bf16x2"))
    prepared = prepare_hist_bins(
        jnp.asarray(rng.randint(0, B, (F, N)).astype(np.uint8)), B)
    txt = lower_for_tpu(
        lambda b: grow(b, g3, jnp.ones(F, bool), jax.random.PRNGKey(0)),
        prepared)
    assert set(_kernel_result_rows(txt)) == {8, 16, 48, 192}


# benchmarks/roofline.py's pattern and reading of a call's HLO text, copied:
# the benchmark is not this PR's to import or edit, and its reader must keep
# finding ``f32[n, 3 x stored slots, block_features x bins]`` in the result
_ROOFLINE_SHAPE = re.compile(r"(u8|s8|s32|f32|bf16)\[([0-9,]+)\]")


def _roofline_call_shapes(long_name, bins):
    head, _, tail = long_name.partition("custom-call(")
    res = _ROOFLINE_SHAPE.search(head.split(" = ", 1)[-1])
    if not res:
        return None
    out = [int(x) for x in res.group(2).split(",")]
    if len(out) != 3:
        return None
    shapes = {"features": out[2] // bins, "slots": out[1] // 3}
    u8 = [m for m in _ROOFLINE_SHAPE.finditer(tail)
          if m.group(1) in ("u8", "s8")]
    if u8:
        shapes["rows"] = int(u8[0].group(2).split(",")[0])
    return shapes


def _hist_calls(txt):
    """The ``hist_leaves_pallas`` custom calls of a compiled module."""
    return [ln.strip() for ln in txt.splitlines()
            if re.match(r"\s*%hist_leaves_pallas[.0-9]* = ", ln)
            and "custom-call(" in ln]


@pytest.mark.parametrize("slots,precision,credited", [
    (1, "bf16x2", 2), (4, "bf16x2", 5), (16, "bf16x2", 16),
    (63, "bf16", 64), (63, "int8sr", 64)])
def test_ladder_pass_compiles_and_the_roofline_reader_parses_it(
        one_chip, slots, precision, credited):
    """Compiled for the chip at the live slots the trainer runs, over
    prepared bins: the call keeps the name ``hist_leaves_pallas`` and a
    three-dimensional f32 result with the lanes last, from which the
    roofline reader's pattern credits at least the live slots (3 rows a
    live slot rounded up to 8, over 3)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from lightgbmv1_tpu.ops.histogram import hist_wave
    from lightgbmv1_tpu.ops.hist_pallas import prepare_hist_bins

    N, F, B = 200_000, 37, 64
    n_pad = 200_704

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    prepared = jax.tree_util.tree_map(
        lambda x: shape(x.shape, x.dtype),
        jax.eval_shape(lambda b: prepare_hist_bins(b, B),
                       shape((F, N), jnp.uint8)))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        txt = jax.jit(lambda b, g, l: hist_wave(
            b, g, l, slots, B, method="pallas", precision=precision)).lower(
                prepared, shape((N, 3), jnp.float32),
                shape((N,), jnp.int32)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    calls = _hist_calls(txt)
    assert len(calls) == 2, calls                 # two blocks of 32 features
    for ln in calls:
        assert _roofline_call_shapes(ln, B) == {
            "features": 32, "slots": credited, "rows": n_pad}
    assert credited >= slots


@pytest.mark.parametrize("precision", ["bf16x2", "bf16"])
@pytest.mark.parametrize("slots,credited", [(1, 2), (4, 5), (16, 16),
                                            (63, 64)])
def test_dense_256_rung_compiles_at_higgs_size(one_chip, slots, credited,
                                               precision):
    """The 256-bin rung over its lane-dense prepared operand, compiled for
    the chip at ``higgs-255b-train``'s own shapes (10,500,000 x 28 ->
    one ``u8[10500096,128]`` array, four calls of 8 x 256 lanes): every
    call takes the stored array whole (no copy, slice or fusion makes a
    ``u8`` operand), keeps the name and the result shape the roofline
    reader parses, and the pass's temporaries are the transposed g3 and
    leaf ids alone."""
    from lightgbmv1_tpu.ops.hist_pallas import prepare_hist_bins

    N, F, B, n_pad = 10_500_000, 28, 256, 10_500_096

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    prepared = jax.tree_util.tree_map(
        lambda x: shape(x.shape, x.dtype),
        jax.eval_shape(lambda b: prepare_hist_bins(b, B),
                       shape((F, N), jnp.uint8)))
    assert [b.shape for b in prepared.blocks] == [(n_pad, 128)]
    got = compile_for_chip(
        lambda b, g, l: hist_leaves_pallas(b, g, l, slots, B,
                                           precision=precision),
        prepared, shape((N, 3), jnp.float32), shape((N,), jnp.int32))
    assert got.memory_analysis().temp_size_in_bytes < n_pad * 20
    txt = got.as_text()
    calls = _hist_calls(txt)
    assert len(calls) == 4, calls
    for ln in calls:
        assert _roofline_call_shapes(ln, B) == {
            "features": 8, "slots": credited, "rows": n_pad}
    assert not re.search(rf"= u8\[{n_pad},\d+\]\S* "
                         r"(copy|slice|fusion|transpose)\(", txt)


@pytest.mark.parametrize("slots,precision,credited", [
    (1, "bf16x2", 2), (4, "bf16x2", 5), (16, "bf16x2", 16), (63, "bf16", 64)])
def test_dense_64_rung_compiles_at_criteo_tall_size(one_chip, slots,
                                                    precision, credited):
    """The 64-bin rung over its lane-dense prepared operand, compiled for
    the chip at ``criteo-tall-train``'s own shapes (26,562,500 x 67 -> one
    ``u8[26562560,128]`` array of 3.4e9 elements, three calls of 32 x 64
    lanes): every call takes the stored array whole (no pad, transposition,
    copy, slice or fusion makes a ``u8`` operand), keeps the name and the
    result shape the roofline reader parses (rows off the operand, 32
    features off the result), and the pass's temporaries are the
    transposed g3 and leaf ids alone."""
    from lightgbmv1_tpu.ops.hist_pallas import prepare_hist_bins

    N, F, B, n_pad = 26_562_500, 67, 64, 26_562_560

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    prepared = jax.tree_util.tree_map(
        lambda x: shape(x.shape, x.dtype),
        jax.eval_shape(lambda b: prepare_hist_bins(b, B, dense=True),
                       shape((F, N), jnp.uint8)))
    assert [b.shape for b in prepared.blocks] == [(n_pad, 128)]
    assert (prepared.tile_cols, prepared.windows) == (32, 4)

    def fn(b, g, l):
        return hist_leaves_pallas(b, g, l, slots, B, precision=precision)

    rest = (shape((N, 3), jnp.float32), shape((N,), jnp.int32))
    lowered = jax.jit(fn).trace(prepared, *rest).lower(
        lowering_platforms=("tpu",)).as_text()
    assert _big_u8_relayouts(lowered, n_pad) == []
    assert _kernel_u8_operands(lowered) == [[f"{n_pad}x128"]] * 3
    got = compile_for_chip(fn, prepared, *rest)
    assert got.memory_analysis().temp_size_in_bytes < n_pad * 24
    txt = got.as_text()
    calls = _hist_calls(txt)
    assert len(calls) == 3, calls
    for ln in calls:
        assert _roofline_call_shapes(ln, B) == {
            "features": 32, "slots": credited, "rows": n_pad}
    assert not re.search(rf"= u8\[{n_pad},\d+\]\S* "
                         r"(copy|slice|fusion|transpose|pad)\(", txt)


def test_prepared_bytes_of_the_cells():
    """The bytes rule's arithmetic (``hist_pallas.hist_bins_form``): the
    256 rung stores 128 byte columns an array; the 64 rung one array a
    block where that fits, **unchanged**: ``mslr-train`` 5 blocks,
    ``epsilon-train`` 63; ``criteo-tall-train``'s 3 blocks are 2.4x the
    rule and its 67 columns fit one lane-dense array."""
    from lightgbmv1_tpu.ops.hist_pallas import (_feature_blocks,
                                                prepared_bins_bytes)

    assert prepared_bins_bytes(28, 10_500_000, 256) == 1_344_012_288
    assert prepared_bins_bytes(137, 2_270_296, 64) == 1_453_588_480
    assert prepared_bins_bytes(2000, 400_000, 64) == 63 * 400_384 * 128
    # one array a block, the 256 rung's form before: 16x the bins, over a
    # quarter of the v5e's 16.9 GB
    _, tile_cols, nfb = _feature_blocks(28, 256, False)
    assert nfb * 10_500_096 * 128 == 5_376_049_152 > 16_909_336_064 // 4
    # a matrix wider than one array's 128 columns takes two
    assert prepared_bins_bytes(137, 10_500_000, 256) == 2 * 1_344_012_288
    # one machine's rows of criteo-tall-67f: the block form is over the
    # quarter, the lane-dense form a third of it
    assert prepared_bins_bytes(67, 26_562_500, 64) == 10_200_023_040 \
        > 16_909_336_064 // 4
    assert prepared_bins_bytes(67, 26_562_500, 64, dense=True) == \
        3_400_007_680 < 16_909_336_064 // 4


def _probe_meta(F, B):
    return FeatureMeta(
        num_bins=jnp.full(F, B, jnp.int32),
        missing_type=jnp.zeros(F, jnp.int32),
        nan_bin=jnp.full(F, -1, jnp.int32),
        zero_bin=jnp.zeros(F, jnp.int32),
        is_categorical=jnp.zeros(F, bool),
        usable=jnp.ones(F, bool),
        monotone_type=jnp.zeros(F, jnp.int32),
    )


@pytest.mark.parametrize("precision", ["bf16x2", "bf16", "f32", "int8"])
@pytest.mark.parametrize("leaves", [31, 255, 2048])
def test_leaf_sums_kernel_lowers(rows, leaves, precision):
    """PR 28's leaf-sum kernel (ops/leaf_sums.py): the leaves' one-hot
    against the rows' values, every precision the passes have."""
    from lightgbmv1_tpu.ops.leaf_sums import leaf_sums_pallas

    rng, N, g3 = rows
    leaf = jnp.asarray(rng.randint(0, leaves, N).astype(np.int32))
    lower_for_tpu(
        lambda l, g: leaf_sums_pallas(l, g, leaves, precision=precision),
        leaf, g3)


def test_leaf_sums_kernel_compiles_at_the_cells_sizes(one_chip):
    """Compiled for the chip at the rows a device sums in the benchmark's
    two cells (4,000,000 and 2,270,296) and at the most leaves the
    renewal takes: what the chip's compiler would refuse, it refuses
    here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from lightgbmv1_tpu.ops.leaf_sums import leaf_sums_pallas

    # an entry compiled for a described chip cannot be read back (it warns)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        for N, L in ((4_000_000, 255), (2_270_296, 255), (4_000_000, 2048)):
            jax.jit(lambda l, g: leaf_sums_pallas(l, g, L)).lower(
                jax.ShapeDtypeStruct((N,), jnp.int32, sharding=one_chip),
                jax.ShapeDtypeStruct((N, 3), jnp.float32,
                                     sharding=one_chip),
            ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.mark.parametrize("use_sub", [True, False], ids=["sub", "pool_free"])
@pytest.mark.parametrize("slots", [1, 4, 16, 63])
def test_partition_kernel_lowers(rows, slots, use_sub):
    """PR 35's row-tiled partition kernel (ops/partition_pallas.py) at the
    wave ladder's buckets, both labelings, a width that is no multiple of
    a ``u8`` tile's 32 rows and a row count that leaves an edge block."""
    from lightgbmv1_tpu.ops.partition_pallas import _COLS, partition_pallas

    F, N = 137, rows[1] - 100
    lower_for_tpu(
        lambda b, l, c: partition_pallas(b, l, c, use_sub=use_sub),
        jnp.zeros((F, N), jnp.uint8), jnp.zeros(N, jnp.int32),
        {name: jnp.zeros(slots, bool if name in ("dls", "sml") else jnp.int32)
         for name in _COLS})


@pytest.mark.parametrize("slots", [1, 4, 16, 63])
def test_partition_kernel_packed4_lowers(rows, slots):
    """The partition kernel on a ``packed4`` matrix (27 features: an odd
    tail) at the wave ladder's buckets: the byte row's selection and the
    nibble's shift and mask lower."""
    from lightgbmv1_tpu.ops.partition_pallas import _COLS, partition_pallas

    rng, N, _ = rows
    packed = jnp.asarray(pack4bit(
        rng.randint(0, 16, (27, N - 100)).astype(np.uint8)))
    lower_for_tpu(
        lambda b, l, c: partition_pallas(b, l, c, use_sub=True, packed=True),
        packed, jnp.zeros(N - 100, jnp.int32),
        {name: jnp.zeros(slots, bool if name in ("dls", "sml") else jnp.int32)
         for name in _COLS})


def test_packed_pass_and_step_compile_at_the_cell_s_size(one_chip):
    """``higgs-15b-train``'s shapes compiled for the described v5e: 28
    features at 16 bins packed into 14 stored rows of 10,500,000.
    Placement lays them out unpacked and repeated (one ``u8[10500096,128]``
    array, four copies of a 32-feature block) in a loop of row steps that
    compiles in seconds and holds no temporary as large as the array; the
    16 rung's pass takes that array whole (no pad, transposition, slice or
    copy makes a ``u8`` operand), its result ``f32[1,192,512]``; the wave
    grower's ``grow`` calls ``partition_pallas`` in each of its three
    buckets and leaves no ``(slots, rows)`` op under ``lgbm.partition``,
    and its temporaries stay under one 63 x rows array of bytes."""
    import time

    from lightgbmv1_tpu.models import grower_wave as gw
    from lightgbmv1_tpu.ops.hist_pallas import (packed_bins_of_feat,
                                                prepare_hist_bins)
    from lightgbmv1_tpu.ops.histogram import hist_wave
    from lightgbmv1_tpu.ops.partition_pallas import KERNEL_NAME

    F, N, B, n_pad = 28, 10_500_000, 16, 10_500_096

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    packed = shape((F // 2, N), jnp.uint8)
    t0 = time.perf_counter()
    placed = compile_for_chip(lambda b: prepare_hist_bins(b, B, packed=True),
                              packed)
    assert time.perf_counter() - t0 < 60
    assert placed.memory_analysis().temp_size_in_bytes < n_pad * 128 // 4
    prepared = jax.tree_util.tree_map(
        lambda x: shape(x.shape, x.dtype),
        jax.eval_shape(lambda b: prepare_hist_bins(b, B, packed=True),
                       packed))
    assert [b.shape for b in prepared.blocks] == [(n_pad, 128)]
    g3 = shape((N, 3), jnp.float32)

    def fn(b, g, l):
        return hist_leaves_pallas(b, g, l, 63, B, precision="bf16",
                                  packed=True, num_features=F)

    lowered = jax.jit(fn).trace(prepared, g3, shape((N,), jnp.int32)).lower(
        lowering_platforms=("tpu",)).as_text()
    assert _big_u8_relayouts(lowered, N) == []
    assert _kernel_u8_operands(lowered) == [[f"{n_pad}x128"]]
    txt = compile_for_chip(fn, prepared, g3, shape((N,), jnp.int32)).as_text()
    assert not re.search(rf"= u8\[{n_pad},\d+\]\S* "
                         r"(copy|slice|fusion|transpose|pad|bitcast)\(", txt)
    calls = _hist_calls(txt)
    assert len(calls) == 1, calls
    assert re.search(r"%hist_leaves_pallas[.0-9]* = f32\[1,192,512\]", txt)

    grow = gw.make_wave_grower(
        num_leaves=255, num_bins=B, meta=_probe_meta(F, B),
        params=SplitParams(min_data_in_leaf=1.0,
                           min_sum_hessian_in_leaf=100.0), wave_size=63,
        hist_wave_fn=lambda b, g, l, n, deep=False: hist_wave(
            b, g, l, n, B, method="pallas",
            precision="bf16" if deep else "bf16x2", packed=True,
            num_features=F),
        bins_of_fn=packed_bins_of_feat, hist_method="pallas",
        stored_layout="packed4")
    got = compile_for_chip(
        lambda b, g: grow(b, g, jnp.ones(F, bool), jax.random.PRNGKey(0)),
        prepared, g3)
    txt = got.as_text()
    assert len(re.findall(
        rf"%{KERNEL_NAME}[.0-9]* = .*custom_call_target=\"tpu_custom_call\"",
        txt)) == 3
    for line in txt.splitlines():
        m = re.search(rf"= \w+\[(\d+),{N}\]", line)
        assert not (m and "lgbm.partition/" in line), line
    assert got.memory_analysis().temp_size_in_bytes < 63 * N


@pytest.mark.parametrize("columns,rows_,bins,prepared", [
    (28, 10_500_000, 256, True),      # higgs-255b-train
    (67, 4_000_000, 64, False),       # criteo-dp4-train, a chip's shard
    (137, 2_270_296, 64, True),       # mslr-train
])
def test_partition_kernel_compiles_in_the_step_at_the_cells_sizes(
        one_chip, columns, rows_, bins, prepared):
    """A 255-leaf wave grower's ``grow`` compiled for the described v5e at
    the three cells' sizes, each bucket's partition in the form
    ``partition_path`` gives it: a bucket that takes the kernel leaves no
    op under ``lgbm.partition`` with a ``(slots, rows)`` result (the
    gather form's ``bitcast_dynamic-update-slice_fusion``, ``convert`` and
    ``select_reduce_fusion`` were three such), and where every bucket takes
    it, on prepared bins (a raw matrix is laid out in every histogram
    pass), the whole program's temporaries are smaller than one 63 x rows
    array of bytes."""
    from lightgbmv1_tpu.models import grower_wave as gw
    from lightgbmv1_tpu.ops.histogram import hist_wave
    from lightgbmv1_tpu.ops.hist_pallas import prepare_hist_bins
    from lightgbmv1_tpu.ops.partition_pallas import KERNEL_NAME, partition_path

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    grow = gw.make_wave_grower(
        num_leaves=255, num_bins=bins, meta=_probe_meta(columns, bins),
        params=SplitParams(min_data_in_leaf=1.0,
                           min_sum_hessian_in_leaf=100.0), wave_size=63,
        hist_wave_fn=lambda b, g, l, n, deep=False: hist_wave(
            b, g, l, n, bins, method="pallas",
            precision="bf16" if deep else "bf16x2"),
        hist_method="pallas", stored_layout="u8")
    matrix = shape((columns, rows_), jnp.uint8)
    if prepared:
        matrix = jax.tree_util.tree_map(
            lambda x: shape(x.shape, x.dtype),
            jax.eval_shape(lambda b: prepare_hist_bins(b, bins), matrix))
    got = compile_for_chip(
        lambda b, g: grow(b, g, jnp.ones(columns, bool),
                          jax.random.PRNGKey(0)),
        matrix, shape((rows_, 3), jnp.float32))
    txt = got.as_text()
    paths = {S: partition_path(columns, S, rows_, pallas=True,
                               layout="u8", use_cat=False)
             for S in (4, 16, 63)}
    assert paths[63] == "kernel"
    kernels = sum(p == "kernel" for p in paths.values())
    assert len(re.findall(
        rf"%{KERNEL_NAME}[.0-9]* = .*custom_call_target=\"tpu_custom_call\"",
        txt)) == kernels
    tall = {}       # first dimension -> ops under lgbm.partition of rows_ wide
    for line in txt.splitlines():
        m = re.search(rf"= \w+\[(\d+),{rows_}\]", line)
        if m and "lgbm.partition/" in line:
            tall.setdefault(int(m.group(1)), []).append(line.split(" = ")[0])
    for S, path in paths.items():
        pad8 = -(-S // 8) * 8
        if path == "kernel":
            assert S not in tall and pad8 not in tall, (S, tall.get(S))
        else:
            assert S in tall
    if kernels == 3 and prepared:
        assert got.memory_analysis().temp_size_in_bytes < 63 * rows_


@pytest.fixture(scope="module")
def predictor():
    rng = np.random.RandomState(4)
    X = rng.randn(400, 6)
    y = (X[:, 0] - X[:, 1] + 0.3 * X[:, 2] > 0).astype(float)
    booster = lgb.train({"objective": "binary", "num_leaves": 7,
                         "min_data_in_leaf": 5, "verbosity": -1},
                        lgb.Dataset(X, label=y), num_boost_round=3,
                        verbose_eval=False)
    bp = BatchPredictor(booster._all_trees(), 1, 6, method="fused",
                        code_layout="u8", bucket_min=256)
    assert bp.fused_plan["eligible"], bp.fused_plan
    return bp, jnp.asarray(bp.encode(X[:256]))


@pytest.mark.xfail(strict=True, raises=NotImplementedError, reason=GATHER)
def test_serving_leaf_pallas_lowers(predictor):
    """predict_method=pallas: VMEM-pinned node tables."""
    bp, codes = predictor
    lower_for_tpu(
        lambda a, c: serving_leaf_pallas(
            a, c, n_steps=bp.depth, zero_code=bp.binner.zero_code,
            nan_code=bp.binner.nan_code),
        bp.arrays, codes)


@pytest.mark.parametrize("mode", ["leaf", "scores"])
@pytest.mark.xfail(strict=True, raises=NotImplementedError, reason=GATHER)
def test_serving_fused_pallas_lowers(predictor, mode):
    """predict_method=fused: the walk + accumulate megakernel."""
    bp, codes = predictor
    lower_for_tpu(
        lambda t, c: serving_fused_pallas(
            t, c, n_steps=bp.depth, zero_code=bp.binner.zero_code,
            nan_code=bp.binner.nan_code, K=1,
            tree_tile=bp.fused_plan["tree_tile"], mode=mode),
        bp._fused_tables, codes)
