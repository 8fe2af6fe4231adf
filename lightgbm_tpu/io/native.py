"""ctypes binding for the native C++ text parser.

The parser itself lives in ``native/fast_parser.cpp`` (the reference's
IO layer is C++, src/io/parser.cpp — ours follows for the same reason:
tokenizing an 11M-row HIGGS file at Python string speed is minutes,
at C speed seconds). The shared object is compiled lazily with g++ into
the package directory and cached; every call site falls back to the
pure-Python parser (io/parser.py) when the toolchain or binary is
unavailable, and the Python parser stays the semantic oracle
(tests/test_native_parser.py asserts bitwise agreement).
"""
from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional, Tuple

import numpy as np

from ..utils import log

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, os.pardir, os.pardir, "native",
                    "fast_parser.cpp")
_SO = os.path.join(_HERE, "_fast_parser.so")

_lib = None
_tried = False


def _build(src: str) -> None:
    subprocess.run(
        ["g++", "-O3", "-shared", "-fPIC", "-pthread", "-o", _SO, src],
        check=True, capture_output=True, timeout=120)


def _load() -> Optional[ctypes.CDLL]:
    """The native library, built on first use from
    ``native/fast_parser.cpp`` (a clean checkout carries no binary).
    Whichever implementation ends up serving this process is logged
    once: the native one at info level, the python fallback — slower by
    orders of magnitude on big files — at warning level with the
    reason."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    src = os.path.normpath(_SRC)
    try:
        built = False
        if not os.path.exists(_SO) or (
                os.path.exists(src)
                and os.path.getmtime(src) > os.path.getmtime(_SO)):
            _build(src)
            built = True
        lib = ctypes.CDLL(_SO)
        try:
            _bind(lib)
        except AttributeError:
            # stale cached .so from an older version missing a symbol:
            # rebuild once
            _build(src)
            built = True
            lib = ctypes.CDLL(_SO)
            _bind(lib)
    except (OSError, subprocess.SubprocessError, AttributeError) as e:
        detail = getattr(e, "stderr", b"") or b""
        log.warning("native parser unavailable (%s: %s%s); using the "
                    "python parser and binner (io/parser.py)",
                    type(e).__name__, e,
                    " — " + detail.decode(errors="replace")[-300:]
                    if detail else "")
        return None
    log.info("native parser in use: %s (%s from %s)", _SO,
             "built just now" if built else "already built", src)
    _lib = lib
    return _lib


def _bind(lib) -> None:
    lib.lgbm_tpu_parse_count.argtypes = [
        ctypes.c_char_p, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32)]
    lib.lgbm_tpu_parse_count.restype = ctypes.c_int
    lib.lgbm_tpu_parse_fill.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_int32, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_float),
        ctypes.c_int64, ctypes.c_int32]
    lib.lgbm_tpu_parse_fill.restype = ctypes.c_int
    lib.lgbm_tpu_bin_columns.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int32,
        ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int32]
    lib.lgbm_tpu_bin_columns.restype = ctypes.c_int


def available() -> bool:
    return _load() is not None


def parse_file_native(filename: str, header: bool, label_idx: int
                      ) -> Optional[Tuple[np.ndarray,
                                          Optional[np.ndarray], int]]:
    """Parse with the native tokenizer.

    Returns (values [N, C], labels [N] or None, format) or None when
    the native path is unavailable. ``C`` excludes the label column.
    """
    lib = _load()
    if lib is None:
        return None
    rows = ctypes.c_int64(0)
    cols = ctypes.c_int32(0)
    fmt = ctypes.c_int32(0)
    rc = lib.lgbm_tpu_parse_count(
        filename.encode(), 1 if header else 0,
        ctypes.byref(rows), ctypes.byref(cols), ctypes.byref(fmt))
    if rc != 0:
        return None
    n, c, f = rows.value, cols.value, fmt.value
    # delimited: a label column only exists when label_idx is in range
    # (the python oracle's `width > label_idx` guard)
    has_label = label_idx >= 0 and (f == 2 or label_idx < c)
    feat_cols = c - (1 if (has_label and f != 2) else 0)
    feat_cols = max(feat_cols, 0)
    values = np.empty((n, feat_cols), np.float64)
    # zeros, not empty: rows without a label token (libsvm) keep 0.0
    # like the python oracle
    labels = np.zeros(n, np.float32) if has_label else None
    rc = lib.lgbm_tpu_parse_fill(
        filename.encode(), 1 if header else 0,
        np.int32(label_idx if has_label else -1), np.int32(f),
        values.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        (labels.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
         if labels is not None else None),
        np.int64(n), np.int32(feat_cols))
    if rc != 0:
        # rc 3 = ragged rows: the python parser pads and warns
        return None
    return values, labels, f


def bin_columns_native(X: np.ndarray, col_idx: np.ndarray,
                       bounds_list, r_len: np.ndarray,
                       nan_bin: np.ndarray) -> "Optional[np.ndarray]":
    """Bulk BinMapper::ValueToBin over numerical columns (threaded C++).

    X row-major [n, ncol] f32/f64; col_idx [f] source column per used
    feature; bounds_list: per-feature float64 upper-bound arrays;
    r_len[f]: searchsorted range; nan_bin[f]: NaN's bin or -1.
    Returns [n, f] uint8 or None when the native library is absent.
    """
    lib = _load()
    if lib is None:
        return None
    X = np.ascontiguousarray(X)
    if X.dtype == np.float32:
        xdtype = 1
    elif X.dtype == np.float64:
        xdtype = 0
    else:
        return None
    n, ncol = X.shape
    f = len(bounds_list)
    bounds = (np.concatenate(bounds_list).astype(np.float64)
              if f else np.zeros(0, np.float64))
    off = np.zeros(f + 1, np.int64)
    np.cumsum([len(b) for b in bounds_list], out=off[1:])
    out = np.empty((n, f), np.uint8)
    col_idx = np.ascontiguousarray(col_idx, np.int32)
    r_len = np.ascontiguousarray(r_len, np.int32)
    nan_bin = np.ascontiguousarray(nan_bin, np.int32)
    bounds = np.ascontiguousarray(bounds)
    off = np.ascontiguousarray(off)
    nthreads = min(16, os.cpu_count() or 1)
    rc = lib.lgbm_tpu_bin_columns(
        X.ctypes.data_as(ctypes.c_void_p), np.int64(n), np.int32(ncol),
        np.int32(xdtype),
        col_idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        np.int32(f),
        bounds.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        off.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        r_len.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        nan_bin.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        np.int32(nthreads))
    return out if rc == 0 else None
