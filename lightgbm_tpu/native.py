"""ctypes bridge to the native (C++) host runtime.

The reference keeps its data plane in C++ behind a C ABI consumed by the
bindings (src/c_api.cpp, python-package _load_lib basic.py:25); this module
is that seam for lightgbm_tpu. The shared library is built on demand from
``native/`` with the baked-in toolchain; every entry point has a pure-Python
fallback, so a missing compiler only costs speed, never functionality.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import List, Optional, Tuple

import numpy as np

from .log import Log

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
_LIB_NAME = "liblgbm_tpu_native.so"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
_origin = ""


class _ParseResult(ctypes.Structure):
    _fields_ = [("data", ctypes.POINTER(ctypes.c_double)),
                ("label", ctypes.POINTER(ctypes.c_double)),
                ("rows", ctypes.c_long),
                ("cols", ctypes.c_long),
                ("header", ctypes.c_char_p),
                ("format", ctypes.c_int)]


def _build() -> Optional[str]:
    global _origin
    so = os.path.join(_NATIVE_DIR, _LIB_NAME)
    srcs = [os.path.join(_NATIVE_DIR, "src", f)
            for f in ("text_parser.cpp", "binning.cpp")]
    srcs = [f for f in srcs if os.path.exists(f)]
    if not srcs:
        return None
    if os.path.exists(so) and \
            os.path.getmtime(so) >= max(os.path.getmtime(f) for f in srcs):
        _origin = "native library found on disk, newer than native/src"
        return so
    try:
        r = subprocess.run(["make", "-C", _NATIVE_DIR],
                           capture_output=True, text=True, timeout=120)
        if r.returncode != 0:
            Log.warning("native build failed, using Python fallbacks:\n%s",
                        r.stderr[-500:])
            return None
    except Exception as e:  # no make/g++ — pure-Python mode
        Log.warning("native build unavailable (%s); using Python fallbacks", e)
        return None
    _origin = "native library built by make from native/src"
    return so if os.path.exists(so) else None


def origin() -> str:
    """Which host runtime this process uses: the native library (built
    now, or found on disk) or the pure-Python fallbacks — so that a
    report can say whose ingest time it shows."""
    return _origin if get_lib() is not None else "pure-Python fallbacks"


def get_lib() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        so = _build()
        if so is None:
            return None
        try:
            lib = ctypes.CDLL(so)
            lib.LGBMT_ParseFile.restype = ctypes.c_int
            lib.LGBMT_ParseFile.argtypes = [
                ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
                ctypes.POINTER(_ParseResult), ctypes.c_char_p, ctypes.c_int]
            lib.LGBMT_FreeParseResult.argtypes = [ctypes.POINTER(_ParseResult)]
            lib.LGBMT_BinNumeric.restype = None
            lib.LGBMT_BinNumeric.argtypes = [
                ctypes.POINTER(ctypes.c_double), ctypes.c_int64,
                ctypes.POINTER(ctypes.c_double), ctypes.c_int32,
                ctypes.c_int32, ctypes.POINTER(ctypes.c_int32)]
            lib.LGBMT_BinCategorical.restype = None
            lib.LGBMT_BinCategorical.argtypes = [
                ctypes.POINTER(ctypes.c_double), ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int32), ctypes.c_int32,
                ctypes.POINTER(ctypes.c_int32)]
            _lib = lib
        except (OSError, AttributeError) as e:
            # AttributeError: a stale prebuilt .so from before a symbol was
            # added — fall back to Python rather than crash dataset loading
            Log.warning("cannot load native library: %s", e)
            _lib = None
        return _lib


def parse_file_native(path: str, has_header: bool, label_idx: int
                      ) -> Optional[Tuple[np.ndarray, np.ndarray,
                                          Optional[List[str]], int]]:
    """Parse a data file with the C++ parser.

    Returns (X [N, F] float64, label [N], header tokens or None, format) or
    None when the native library is unavailable (caller falls back).
    Raises on parse errors reported by the library.
    """
    lib = get_lib()
    if lib is None:
        return None
    res = _ParseResult()
    err = ctypes.create_string_buffer(512)
    rc = lib.LGBMT_ParseFile(path.encode(), int(has_header), int(label_idx),
                             ctypes.byref(res), err, len(err))
    if rc != 0:
        from .log import LightGBMError
        raise LightGBMError(err.value.decode())
    try:
        n, f = int(res.rows), int(res.cols)
        X = np.ctypeslib.as_array(res.data, shape=(n, f)).copy()
        y = np.ctypeslib.as_array(res.label, shape=(n,)).copy()
        header = res.header.decode() if res.header else None
        fmt = int(res.format)
    finally:
        lib.LGBMT_FreeParseResult(ctypes.byref(res))
    tokens = None
    if header is not None:
        delim = "\t" if "\t" in header else ("," if "," in header else " ")
        tokens = header.strip().split(delim)
    return X, y, tokens, fmt


def bin_numeric_native(values: np.ndarray, bounds: np.ndarray,
                       nan_bin: int) -> Optional[np.ndarray]:
    """Assign bins for a numeric column with the OpenMP binner
    (native/src/binning.cpp); None when the library is unavailable.

    ``bounds`` are the numeric upper bounds excluding the +inf sentinel;
    ``nan_bin`` >= 0 routes NaN there, < 0 treats NaN as 0.0. Matches
    BinMapper.values_to_bins (searchsorted "left") exactly.
    """
    lib = get_lib()
    if lib is None:
        return None
    values = np.ascontiguousarray(values, dtype=np.float64)
    bounds = np.ascontiguousarray(bounds, dtype=np.float64)
    out = np.empty(len(values), dtype=np.int32)
    lib.LGBMT_BinNumeric(
        values.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        ctypes.c_int64(len(values)),
        bounds.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        ctypes.c_int32(len(bounds)), ctypes.c_int32(nan_bin),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    return out


def bin_categorical_native(values: np.ndarray, keys: np.ndarray,
                           bins: np.ndarray) -> Optional[np.ndarray]:
    """Assign bins for a categorical column with the OpenMP binner
    (native/src/binning.cpp); None when the library is unavailable.

    ``keys`` are the kept category ids in ascending order, ``bins`` their
    bins; every other value (NaN, negative, not kept) lands in bin 0.
    Matches BinMapper.values_to_bins' numpy path value for value.
    """
    lib = get_lib()
    if lib is None:
        return None
    values = np.ascontiguousarray(values, dtype=np.float64)
    keys = np.ascontiguousarray(keys, dtype=np.int64)
    bins = np.ascontiguousarray(bins, dtype=np.int32)
    if len(keys) != len(bins) or np.any(keys[1:] <= keys[:-1]):
        raise ValueError("bin_categorical_native: one bin for each of the "
                         "ascending keys")
    out = np.empty(len(values), dtype=np.int32)
    lib.LGBMT_BinCategorical(
        values.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        ctypes.c_int64(len(values)),
        keys.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        bins.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ctypes.c_int32(len(keys)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    return out
