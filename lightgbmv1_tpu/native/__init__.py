"""Native (C++) runtime components, loaded via ctypes.

The reference implements its IO stack in C++ (Parser/TextReader/
DatasetLoader); this package does the same for the dense-table fast path:
``text_parser.cpp`` is compiled on first use with the system toolchain into
a cached shared library and consumed through a C ABI.  Everything degrades
gracefully to the pure-Python parser when no compiler is available.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

from ..utils.log import log_info, log_warning

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "text_parser.cpp")
_LIB_PATH = os.path.join(_DIR, "_libtpugbdt_io.so")
_lock = threading.Lock()
_lib = None
_lib_failed = False


def _compile_and_load(src_path: str, lib_path: str, what: str):
    """Compile ``src_path`` into ``lib_path`` (if stale) and CDLL it.
    Builds into a unique temp file + atomic rename so concurrent first-use
    builds from multiple processes never expose a half-written library.
    Returns the loaded CDLL or None (no compiler / build error)."""
    fresh = (os.path.exists(lib_path)
             and os.path.getmtime(lib_path) >= os.path.getmtime(src_path))
    if not fresh:
        tmp = f"{lib_path}.{os.getpid()}.tmp"
        cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
               "-o", tmp, src_path]
        try:
            res = subprocess.run(cmd, capture_output=True, text=True,
                                 timeout=120)
        except (OSError, subprocess.TimeoutExpired) as e:
            # each library's loader caches the failure, so this is one
            # line per library per process
            log_warning(f"native {what}: g++ could not be run "
                        f"({type(e).__name__}: {e}); using the Python "
                        "fallback")
            return None
        if res.returncode != 0:
            log_warning(f"native {what} build failed; using the Python "
                        f"fallback ({res.stderr.strip().splitlines()[-1:]})")
            return None
        try:
            os.replace(tmp, lib_path)
        except OSError:
            if not os.path.exists(lib_path):
                return None
    try:
        return ctypes.CDLL(lib_path)
    except OSError:
        return None


def _load():
    global _lib, _lib_failed
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        lib = _compile_and_load(_SRC, _LIB_PATH, "text parser")
        if lib is None:
            _lib_failed = True
            return None
        lib.tp_open.restype = ctypes.c_void_p
        lib.tp_open.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_int]
        lib.tp_rows.restype = ctypes.c_long
        lib.tp_rows.argtypes = [ctypes.c_void_p]
        lib.tp_cols.restype = ctypes.c_long
        lib.tp_cols.argtypes = [ctypes.c_void_p]
        lib.tp_fill.restype = ctypes.c_long
        lib.tp_fill.argtypes = [ctypes.c_void_p,
                                ctypes.POINTER(ctypes.c_double),
                                ctypes.c_long]
        lib.tp_close.restype = None
        lib.tp_close.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def parse_dense_file(path: str, has_header: bool, sep: Optional[str],
                     num_threads: int = 0) -> Optional[np.ndarray]:
    """Parse a dense numeric table natively; None -> caller falls back to
    the Python parser (no compiler, malformed rows, etc.).
    ``num_threads`` <= 0 uses hardware concurrency (reference: num_threads
    caps the OMP pool; here it caps the parser's thread count)."""
    lib = _load()
    if lib is None:
        return None
    sep_char = ord(sep) if sep else 0
    h = lib.tp_open(path.encode(), 1 if has_header else 0, sep_char)
    if not h:
        return None
    try:
        rows, cols = lib.tp_rows(h), lib.tp_cols(h)
        if rows <= 0 or cols <= 0:
            return None
        out = np.empty((rows, cols), dtype=np.float64)
        bad = lib.tp_fill(h, out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                          int(num_threads))
        if bad != 0:
            return None   # ragged rows: let the Python parser report it
        return out
    finally:
        lib.tp_close(h)


# ---------------------------------------------------------------------------
# Native batch predictor (predictor.cpp) — the reference Predictor role
# (src/application/predictor.hpp:29-160): per-row tree walks over flattened
# arrays, row-partitioned across threads.
# ---------------------------------------------------------------------------

_PRED_SRC = os.path.join(_DIR, "predictor.cpp")
_PRED_LIB_PATH = os.path.join(_DIR, "_libtpugbdt_pred.so")
_pred_lib = None
_pred_failed = False


def _pred_load():
    global _pred_lib, _pred_failed
    with _lock:
        if _pred_lib is not None or _pred_failed:
            return _pred_lib
        lib = _compile_and_load(_PRED_SRC, _PRED_LIB_PATH, "predictor")
        if lib is None:
            _pred_failed = True
            return None
        c = ctypes
        # int64 numpy arrays map to int64_t on BOTH sides (c_long would
        # only agree on LP64; Windows/mingw long is 32-bit)
        lib.pd_predict.restype = c.c_int64
        lib.pd_predict.argtypes = [
            c.POINTER(c.c_double), c.c_int64, c.c_int64, c.c_int, c.c_int,
            c.POINTER(c.c_int64), c.POINTER(c.c_int64), c.POINTER(c.c_int),
            c.POINTER(c.c_double), c.POINTER(c.c_ubyte), c.POINTER(c.c_int),
            c.POINTER(c.c_int), c.POINTER(c.c_double), c.POINTER(c.c_int64),
            c.POINTER(c.c_int), c.POINTER(c.c_uint), c.POINTER(c.c_int),
            c.POINTER(c.c_double), c.c_int,
        ]
        _pred_lib = lib
        return _pred_lib


def build_ensemble_pack(trees, K: int):
    """Flatten HostTrees into the predictor's C arrays; None when the
    ensemble is not representable (raw categorical sets unavailable or a
    category too large for a bitset)."""
    if _pred_load() is None:
        return None
    node_off = [0]
    leaf_off = [0]
    feat, thr, flags, lc, rc, lv = [], [], [], [], [], []
    cat_off, cat_len, cat_words = [], [], []
    for t in trees:
        n_nodes = max(t.num_leaves - 1, 0)
        for i in range(n_nodes):
            fl = (1 if t.default_left[i] else 0) | (
                int(t.missing_type[i]) << 1)
            co, cl = -1, 0
            if bool(t.is_cat[i]):
                s = t.cat_sets[i]
                if s is None:
                    return None
                s = np.asarray(s, np.int64)
                if len(s) and s.max() >= (1 << 22):
                    return None          # bitset would be absurdly wide
                fl |= 8
                words = np.zeros((int(s.max()) >> 5) + 1 if len(s) else 1,
                                 np.uint32)
                for cval in s:
                    words[cval >> 5] |= np.uint32(1) << np.uint32(cval & 31)
                co = len(cat_words)
                cl = len(words)
                cat_words.extend(words.tolist())
            feat.append(int(t.split_feature[i]))
            thr.append(float(t.threshold[i]))
            flags.append(fl)
            lc.append(int(t.left_child[i]))
            rc.append(int(t.right_child[i]))
            cat_off.append(co)
            cat_len.append(cl)
        lv.extend(np.asarray(t.leaf_value[: t.num_leaves],
                             np.float64).tolist())
        node_off.append(len(feat))
        leaf_off.append(len(lv))
    tree_k = [i % K for i in range(len(trees))]
    max_feat = max(feat) if feat else -1
    return dict(
        max_feat=max_feat,
        node_off=np.asarray(node_off, np.int64),
        leaf_off=np.asarray(leaf_off, np.int64),
        feat=np.asarray(feat, np.int32),
        thr=np.asarray(thr, np.float64),
        flags=np.asarray(flags, np.uint8),
        lc=np.asarray(lc, np.int32),
        rc=np.asarray(rc, np.int32),
        leaf_val=np.asarray(lv, np.float64),
        cat_off=np.asarray(cat_off, np.int64),
        cat_len=np.asarray(cat_len, np.int32),
        cat_words=np.asarray(cat_words if cat_words else [0], np.uint32),
        tree_k=np.asarray(tree_k, np.int32),
        T=len(trees), K=K,
    )


def predict_ensemble(X: np.ndarray, pack, num_threads: int = 0):
    """Run the native predictor; (n, K) float64 output, or None."""
    lib = _pred_load()
    if lib is None or pack is None:
        return None
    X = np.ascontiguousarray(X, np.float64)
    n, F = X.shape
    out = np.zeros((n, pack["K"]), np.float64)
    c = ctypes

    def p(a, ty):
        return a.ctypes.data_as(c.POINTER(ty))

    rc_ = lib.pd_predict(
        p(X, c.c_double), n, F, pack["T"], pack["K"],
        p(pack["node_off"], c.c_int64), p(pack["leaf_off"], c.c_int64),
        p(pack["feat"], c.c_int), p(pack["thr"], c.c_double),
        p(pack["flags"], c.c_ubyte), p(pack["lc"], c.c_int),
        p(pack["rc"], c.c_int), p(pack["leaf_val"], c.c_double),
        p(pack["cat_off"], c.c_int64), p(pack["cat_len"], c.c_int),
        p(pack["cat_words"], c.c_uint), p(pack["tree_k"], c.c_int),
        p(out, c.c_double), int(num_threads))
    if rc_ != 0:
        return None
    return out
