"""Sharded binary block cache — the out-of-core training format.

The binned matrix is written ONCE (from the existing parse/bin pipeline or
an in-memory :class:`~lightgbmv1_tpu.io.dataset.BinnedDataset`) as
fixed-row-count block shards under a cache directory:

    <dir>/manifest.json     format version, shapes, block table with
                            per-block SHA-256 digests, schema digest
    <dir>/meta.npz          bin mappers + label/weight/group/init_score
                            (the reference Metadata, small — rows are the
                            bulk, per-row 4-byte fields stay host-sized)
    <dir>/block_00000.bin   raw C-order bytes of binned[:, a:b] (F, rows)

Every file goes through ``fileio.atomic_write_bytes`` (tmp+fsync+rename),
so a torn cache FAILS LOUDLY at load instead of training on garbage: the
manifest names every section's digest, and readers verify before use
(reference: Dataset::SaveBinaryFile / LoadFromBinFile,
src/io/dataset_loader.cpp:273 — which trusted the file; this format does
not).  Blocks load independently — the streaming trainer's device working
set is O(block_rows · F) regardless of dataset rows.
"""

from __future__ import annotations

import hashlib
import io
import json
import os
from typing import Dict, List, Optional

import numpy as np

from ..utils.fileio import atomic_write_bytes, exists, open_file
from ..utils.log import log_info, log_warning

BLOCK_CACHE_MAGIC = "lightgbmv1_tpu.block_cache"
# format history: v1/v2 — unpacked (F, rows) uint8/uint16 block shards
# (legacy; load unchanged, bin_layout implicitly "u8"); v3 (ISSUE 18) —
# the manifest carries ``bin_layout`` and ``packed4`` shards store the
# 4-bit (ceil(F/2), rows) layout (ops/hist_pallas.pack4bit), halving
# disk and H2D bytes for max_bin <= 15 datasets.  Digests always cover
# the STORED bytes, so corruption detection is layout-independent.
BLOCK_CACHE_VERSION = 3
BLOCK_CACHE_LEGACY_VERSIONS = (1, 2)
MANIFEST_NAME = "manifest.json"
META_NAME = "meta.npz"


class BlockCacheError(RuntimeError):
    """Torn, corrupted, or incompatible block cache — raised at open/load
    time so a damaged cache can never silently train garbage.  Every
    construction publishes a first-class structured event (the forensic
    bundle of a run that died on a damaged cache names the damage)."""

    def __init__(self, msg: str):
        super().__init__(msg)
        try:
            from ..obs import events

            events.publish("data.block_cache_error", str(msg),
                           severity="error")
        except Exception:   # noqa: BLE001 — the raise must proceed
            pass


def _sha256(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


def _mapper_arrays(ds) -> Dict[str, np.ndarray]:
    """Flat-array serialization of the bin mappers (the same wire format
    BinnedDataset.save_binary uses — BinMapper.to_arrays/from_arrays)."""
    ubounds = [np.asarray(m.bin_upper_bound, np.float64)
               for m in ds.bin_mappers]
    cats = [np.asarray(m.bin_2_categorical, np.int64)
            for m in ds.bin_mappers]
    scalars = np.array(
        [[m.num_bin, m.missing_type, m.bin_type, int(m.is_trivial)]
         for m in ds.bin_mappers], dtype=np.int64)
    floats = np.array(
        [[m.sparse_rate, m.min_value, m.max_value]
         for m in ds.bin_mappers], dtype=np.float64)
    meta = ds.metadata
    return dict(
        mapper_scalars=scalars,
        mapper_floats=floats,
        ubound_flat=(np.concatenate(ubounds) if ubounds else np.zeros(0)),
        ubound_offsets=np.cumsum([0] + [len(u) for u in ubounds]),
        cat_flat=(np.concatenate(cats) if cats else np.zeros(0, np.int64)),
        cat_offsets=np.cumsum([0] + [len(c) for c in cats]),
        feature_names=np.array(ds.feature_names),
        max_bin=np.int64(ds.max_bin),
        label=(meta.label if meta.label is not None else np.zeros(0)),
        weight=(meta.weight if meta.weight is not None else np.zeros(0)),
        group=(meta.group if meta.group is not None
               else np.zeros(0, np.int64)),
        init_score=(meta.init_score if meta.init_score is not None
                    else np.zeros(0)),
    )


def packed4_eligible(ds) -> str:
    """Why ``ds`` cannot store ``packed4`` shards — ``""`` when it can.
    The storage-side gate: every feature must fit a nibble
    (``num_total_bin <= 16``) and bins must be uint8."""
    if np.dtype(ds.binned.dtype).itemsize > 1:
        return "int16-binned data exceeds the 4-bit nibble"
    if int(getattr(ds, "num_total_bin", 256)) > 16:
        return (f"num_total_bin={ds.num_total_bin} needs more than 4 "
                "bits per bin")
    return ""


def write_block_cache(ds, path: str, block_rows: int = 65536,
                      bin_layout: str = "auto") -> dict:
    """Write ``ds`` (a dense-binned BinnedDataset) as a sharded block
    cache at directory ``path``; returns the manifest dict.

    The binned matrix must be the plain dense (F, N) representation: EFB
    bundle-only (sparse-path) datasets are refused — the streaming trainer
    speaks original features (bundling trades HBM for compute the
    streaming path already bounds).

    ``bin_layout``: ``"packed4"`` stores 4-bit packed shards —
    ``(ceil(F/2), rows)`` bytes per block (ops/hist_pallas.pack4bit), so
    disk and the streaming trainer's H2D transfers halve; requires
    ``num_total_bin <= 16`` (raises ``BlockCacheError`` otherwise — the
    storage API fails loudly; config-driven refusal-with-warning lives in
    parallel/trainer.resolve_hist_plan).  ``"auto"`` packs exactly when
    eligible; ``"u8"`` always stores unpacked bytes."""
    if ds.binned is None:
        raise BlockCacheError(
            "write_block_cache requires a dense-binned dataset (EFB "
            "bundle-only sparse datasets are not streamable); load dense "
            "data or set enable_bundle=false")
    if block_rows < 1:
        raise BlockCacheError(f"block_rows must be >= 1 (got {block_rows})")
    if bin_layout not in ("auto", "u8", "packed4"):
        raise BlockCacheError(
            f"bin_layout={bin_layout!r}: expected auto | u8 | packed4")
    if bin_layout == "packed4":
        reason = packed4_eligible(ds)
        if reason:
            raise BlockCacheError(f"bin_layout=packed4: {reason}")
    elif bin_layout == "auto":
        bin_layout = "packed4" if not packed4_eligible(ds) else "u8"
    os.makedirs(path, exist_ok=True)

    buf = io.BytesIO()
    np.savez_compressed(buf, **_mapper_arrays(ds))
    meta_bytes = buf.getvalue()
    atomic_write_bytes(os.path.join(path, META_NAME), meta_bytes,
                       site="block_cache_meta")

    N = ds.num_data
    binned = np.ascontiguousarray(ds.binned)
    if bin_layout == "packed4":
        # pack ONCE over the full matrix: packing pairs feature ROWS, so
        # slicing the packed matrix per block equals packing per block
        from ..ops.hist_pallas import pack4bit

        binned = pack4bit(binned)
    blocks: List[dict] = []
    for i, a in enumerate(range(0, N, block_rows)):
        b = min(a + block_rows, N)
        blk = np.ascontiguousarray(binned[:, a:b])
        data = blk.tobytes()
        fname = f"block_{i:05d}.bin"
        atomic_write_bytes(os.path.join(path, fname), data,
                           site=f"block_cache_block_{i}")
        blocks.append({"file": fname, "row_begin": int(a),
                       "rows": int(b - a), "sha256": _sha256(data),
                       "nbytes": len(data)})

    manifest = {
        "magic": BLOCK_CACHE_MAGIC,
        "format_version": BLOCK_CACHE_VERSION,
        "num_rows": int(N),
        "num_features": int(ds.num_features),
        "block_rows": int(block_rows),
        "dtype": str(binned.dtype),
        "bin_layout": bin_layout,
        "meta_file": META_NAME,
        "meta_sha256": _sha256(meta_bytes),
        # schema digest: load-time incompatibility (different binning of
        # the "same" data) fails loudly instead of mis-binning predictions
        "schema_digest": _sha256(meta_bytes)[:16],
        "blocks": blocks,
    }
    atomic_write_bytes(os.path.join(path, MANIFEST_NAME),
                       json.dumps(manifest, indent=1).encode(),
                       site="block_cache_manifest")
    log_info(f"Wrote block cache to {path}: {N} rows x {ds.num_features} "
             f"features in {len(blocks)} blocks of {block_rows} rows"
             + (" (4-bit packed shards)" if bin_layout == "packed4"
                else ""))
    return manifest


def is_block_cache(path) -> bool:
    """True when ``path`` is a directory holding a block-cache manifest."""
    p = os.path.join(str(path), MANIFEST_NAME)
    if not exists(p):
        return False
    try:
        with open_file(p) as fh:
            return json.load(fh).get("magic") == BLOCK_CACHE_MAGIC
    except Exception:
        return False


def load_manifest(path: str) -> dict:
    """Load + validate the manifest and the meta shard's digest."""
    mp = os.path.join(str(path), MANIFEST_NAME)
    if not exists(mp):
        raise BlockCacheError(f"{path}: no {MANIFEST_NAME} (not a block "
                              "cache)")
    try:
        with open_file(mp) as fh:
            manifest = json.load(fh)
    except (json.JSONDecodeError, UnicodeDecodeError) as e:
        raise BlockCacheError(f"{mp}: torn or corrupt manifest ({e})")
    if manifest.get("magic") != BLOCK_CACHE_MAGIC:
        raise BlockCacheError(f"{mp}: wrong magic "
                              f"{manifest.get('magic')!r}")
    version = int(manifest.get("format_version", -1))
    if version in BLOCK_CACHE_LEGACY_VERSIONS:
        # legacy caches predate the bin_layout field: unpacked shards,
        # loaded unchanged (the digests cover the same stored bytes)
        log_warning(
            f"{mp}: legacy block-cache format_version {version} "
            f"(current is {BLOCK_CACHE_VERSION}); unpacked u8 shards — "
            "rewrite with save_block_cache to store 4-bit packed shards "
            "for max_bin <= 15 data")
    elif version != BLOCK_CACHE_VERSION:
        raise BlockCacheError(
            f"{mp}: unsupported format_version {version} (this build "
            f"reads versions {BLOCK_CACHE_LEGACY_VERSIONS} and "
            f"{BLOCK_CACHE_VERSION})")
    for key in ("num_rows", "num_features", "dtype", "blocks",
                "meta_sha256"):
        if key not in manifest:
            raise BlockCacheError(f"{mp}: missing manifest field {key!r}")
    layout = manifest_bin_layout(manifest)
    if layout not in ("u8", "packed4"):
        raise BlockCacheError(f"{mp}: unknown bin_layout {layout!r}")
    if layout == "packed4" and np.dtype(manifest["dtype"]).itemsize != 1:
        raise BlockCacheError(
            f"{mp}: packed4 shards must be uint8 "
            f"(manifest dtype {manifest['dtype']!r})")
    return manifest


def manifest_bin_layout(manifest: dict) -> str:
    """The cache's stored layout (legacy manifests are implicitly u8)."""
    return str(manifest.get("bin_layout", "u8"))


def validate_block_table(path: str, manifest: dict) -> List[tuple]:
    """Validate the manifest's block table and return its row ranges.

    The table must be ordered, non-empty-per-block, gap-free and
    overlap-free, and must cover exactly ``num_rows`` — an overlap would
    silently double-count rows in every histogram and a gap would
    silently drop them, so both FAIL LOUDLY here (the host-shard
    derivation below trusts these ranges to partition the dataset)."""
    ranges = [(int(e["row_begin"]), int(e["row_begin"]) + int(e["rows"]))
              for e in manifest["blocks"]]
    pos = 0
    for a, b in ranges:
        if b <= a:
            raise BlockCacheError(
                f"{path}: empty or negative block at row {a}")
        if a < pos:
            raise BlockCacheError(
                f"{path}: block table OVERLAPS at row {a} (previous "
                f"block ends at {pos}); rows would be double-read")
        if a > pos:
            raise BlockCacheError(
                f"{path}: block table has a GAP at rows [{pos}, {a}); "
                "rows would be silently dropped")
        pos = b
    n = int(manifest["num_rows"])
    if pos != n:
        raise BlockCacheError(
            f"{path}: block table covers {pos} rows, manifest says {n}")
    return ranges


def shard_blocks(manifest, rank: int, world: int,
                 path: str = "<cache>") -> dict:
    """Derive THIS rank's host shard from the manifest: a contiguous run
    of whole blocks (block-aligned so every process still reads verified
    whole shards), balanced by block count, ragged tail on the last
    ranks' runs.  Deterministic in (manifest, rank, world) — every
    process derives the same partition without communicating, and the
    elastic path re-derives it after a mesh shrink.

    Returns ``{"block_lo", "block_hi", "row_begin", "row_end"}`` (empty
    run => row_begin == row_end when world > num_blocks)."""
    if not (0 <= rank < world):
        raise BlockCacheError(
            f"{path}: shard rank {rank} out of range for world {world}")
    ranges = validate_block_table(path, manifest)
    nb = len(ranges)
    lo = rank * nb // world
    hi = (rank + 1) * nb // world
    row_begin = ranges[lo][0] if lo < hi else int(manifest["num_rows"])
    row_end = ranges[hi - 1][1] if lo < hi else row_begin
    return {"block_lo": lo, "block_hi": hi,
            "row_begin": row_begin, "row_end": row_end}


def read_meta_arrays(path: str, manifest: dict) -> Dict[str, np.ndarray]:
    mp = os.path.join(str(path), manifest.get("meta_file", META_NAME))
    with open_file(mp, "rb") as fh:
        raw = fh.read()
    if _sha256(raw) != manifest["meta_sha256"]:
        raise BlockCacheError(f"{mp}: meta shard digest mismatch (torn or "
                              "corrupt cache)")
    with np.load(io.BytesIO(raw), allow_pickle=False) as z:
        return {k: z[k] for k in z.files}


def read_block(path: str, manifest: dict, index: int) -> np.ndarray:
    """Load ONE block shard -> (F, rows) array, digest-verified."""
    blocks = manifest["blocks"]
    if not (0 <= index < len(blocks)):
        raise BlockCacheError(f"block index {index} out of range "
                              f"(cache has {len(blocks)} blocks)")
    entry = blocks[index]
    bp = os.path.join(str(path), entry["file"])
    with open_file(bp, "rb") as fh:
        raw = fh.read()
    if len(raw) != int(entry["nbytes"]) or _sha256(raw) != entry["sha256"]:
        raise BlockCacheError(
            f"{bp}: block digest mismatch (torn or corrupt cache); "
            "rebuild with task=save_binary")
    F = int(manifest["num_features"])
    if manifest_bin_layout(manifest) == "packed4":
        F = -(-F // 2)      # stored byte rows: two features per byte
    rows = int(entry["rows"])
    return np.frombuffer(raw, dtype=np.dtype(manifest["dtype"])) \
        .reshape(F, rows)
