"""ctypes bindings for the native host-runtime kernels (ingest.cpp).

Loads libgsnative-<hash>.so, building it on first use from the
committed ingest.cpp when the toolchain is available. The file name
carries a hash of the source, so an edited ingest.cpp is rebuilt and a
library built from another revision is never loaded. The Makefile
builds for the generic target of the host architecture (no
-march=native): the library must run on whichever machine loads the
checkout. Every entry point has a numpy/python fallback so the
framework works without a compiler. `available()` reports which path
is active.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Optional, Tuple

import numpy as np

from ..utils import telemetry

_DIR = os.path.dirname(os.path.abspath(__file__))


def _lib_path() -> str:
    """The library built from the ingest.cpp on disk now."""
    with open(os.path.join(_DIR, "ingest.cpp"), "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_DIR, "libgsnative-%s.so" % digest)


_lib: Optional[ctypes.CDLL] = None
_tried = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    path = _lib_path()
    if not os.path.exists(path):
        # build under a per-process name, then rename into place:
        # concurrent first loads never see a half-written library
        tmp = "%s.tmp%d" % (path, os.getpid())
        try:
            subprocess.run(["make", "-C", _DIR, "-s",
                            "LIB=" + os.path.basename(tmp)], check=True,
                           capture_output=True, timeout=300)
            os.replace(tmp, path)
        except Exception as e:
            telemetry.event("native.build_failed", durable=True,
                            error="%s: %s" % (type(e).__name__, e))
            return None
    try:
        lib = ctypes.CDLL(path)
    except OSError:
        return None
    lib.gs_parse_edges.restype = ctypes.c_int64
    lib.gs_parse_edges.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.gs_assign_windows.restype = None
    lib.gs_assign_windows.argtypes = [
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.gs_interner_new.restype = ctypes.c_void_p
    lib.gs_interner_free.argtypes = [ctypes.c_void_p]
    lib.gs_interner_size.restype = ctypes.c_int64
    lib.gs_interner_size.argtypes = [ctypes.c_void_p]
    lib.gs_interner_intern.restype = None
    lib.gs_interner_intern.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.gs_interner_lookup.restype = None
    lib.gs_interner_lookup.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.gs_triangle_count_stream.restype = ctypes.c_int64
    lib.gs_triangle_count_stream.argtypes = [
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64, ctypes.c_int64, ctypes.POINTER(ctypes.c_int64),
    ]
    lib.gs_windowed_reduce.restype = ctypes.c_int64
    lib.gs_windowed_reduce.argtypes = [
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
        ctypes.c_int32, ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.gs_windowed_reduce_i32.restype = ctypes.c_int64
    lib.gs_windowed_reduce_i32.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
        ctypes.c_int32, ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.gs_windowed_reduce_i32o.restype = ctypes.c_int64
    lib.gs_windowed_reduce_i32o.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
        ctypes.c_int32, ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.gs_windowed_reduce_i64i32o.restype = ctypes.c_int64
    lib.gs_windowed_reduce_i64i32o.argtypes = [
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
        ctypes.c_int32, ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.gs_snapshot_windows.restype = ctypes.c_int64
    lib.gs_snapshot_windows.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_uint8),
    ]
    _lib = lib
    return _lib


def available() -> bool:
    return _load() is not None


def _i64ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _i32ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def _u8ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


# ----------------------------------------------------------------------
def parse_edge_bytes(data: bytes) -> Tuple[np.ndarray, np.ndarray,
                                           np.ndarray]:
    """Parse a byte buffer of 'src dst [ts]' lines into int64 COO
    arrays (ts = -1 when missing). Native fast path with a behavior-
    identical Python fallback."""
    lib = _load()
    if lib is not None:
        max_edges = data.count(b"\n") + 1
        src = np.empty(max_edges, np.int64)
        dst = np.empty(max_edges, np.int64)
        ts = np.empty(max_edges, np.int64)
        n = lib.gs_parse_edges(data, len(data), max_edges,
                               _i64ptr(src), _i64ptr(dst), _i64ptr(ts))
        return src[:n].copy(), dst[:n].copy(), ts[:n].copy()
    return _parse_edge_bytes_py(data)


def parse_edge_file(path: str) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Parse 'src dst [ts]' lines into int64 COO arrays (ts = -1 when
    missing). Native fast path; numpy loadtxt-style fallback."""
    with open(path, "rb") as f:
        return parse_edge_bytes(f.read())


def _parse_edge_bytes_py(data: bytes):
    """Pure-Python parser; must stay behaviorally identical to
    gs_parse_edges (ingest.cpp) so results never depend on whether the
    native library is available."""
    src_l, dst_l, ts_l = [], [], []
    for line in data.decode().splitlines():
        fields = line.split()
        if len(fields) >= 2:
            try:  # parse the whole line before appending anything, so a
                # malformed field can't leave the arrays misaligned
                row = (int(fields[0]), int(fields[1]),
                       int(fields[2]) if len(fields) > 2 else -1)
            except ValueError:
                continue
            src_l.append(row[0])
            dst_l.append(row[1])
            ts_l.append(row[2])
    return (np.array(src_l, np.int64), np.array(dst_l, np.int64),
            np.array(ts_l, np.int64))


def assign_windows(ts: np.ndarray, size_ms: int) -> np.ndarray:
    """Tumbling window starts per timestamp (Flink TimeWindow floor)."""
    ts = np.ascontiguousarray(ts, np.int64)
    lib = _load()
    if lib is not None:
        out = np.empty(len(ts), np.int64)
        lib.gs_assign_windows(_i64ptr(ts), len(ts), size_ms, _i64ptr(out))
        return out
    return ts - np.mod(ts, size_ms)


def triangles_available() -> bool:
    lib = _load()
    return lib is not None and hasattr(lib, "gs_triangle_count_stream")


def triangle_count_stream(src: np.ndarray, dst: np.ndarray,
                          eb: int) -> Optional[np.ndarray]:
    """Exact triangle counts of every tumbling `eb`-sized window of the
    stream via the C++ compact-forward counter (ingest.cpp
    gs_triangle_count_stream), a parity oracle and profiler row beside
    ops/triangles.count_stream. Returns None when the library (or the
    symbol, for a stale build) is unavailable. Counting invariant and
    results are identical to the numpy host twin and the device
    program (asserted in tests/library/test_triangles.py)."""
    if not triangles_available():
        return None
    src = np.ascontiguousarray(src, np.int64)
    dst = np.ascontiguousarray(dst, np.int64)
    num_w = (len(src) + eb - 1) // eb
    counts = np.empty(max(num_w, 1), np.int64)
    w = _lib.gs_triangle_count_stream(_i64ptr(src), _i64ptr(dst),
                                      len(src), eb, _i64ptr(counts))
    return counts[:w]


_REDUCE_OPS = {"sum": 0, "min": 1, "max": 2}
_REDUCE_DIRS = {"out": 0, "in": 1, "all": 2}


def windowed_reduce_available() -> bool:
    lib = _load()
    return lib is not None and hasattr(lib, "gs_windowed_reduce")


def windowed_reduce(src: np.ndarray, dst: np.ndarray, val: np.ndarray,
                    eb: int, vbp: int, name: str, direction: str,
                    ident: int):
    """Fused (cells, counts) windowed reduce via the C++ kernel
    (ingest.cpp gs_windowed_reduce*) — the C++ form of
    ops/windowed_reduce.WindowedEdgeReduce for integer values. Returns
    (cells [num_w, vbp], counts [num_w, vbp]); cells pre-filled with
    `ident`. The slab dtype is int32 when the fast forms apply (int32
    values whose worst-case cell sum provably fits int32 — evaluated
    PER CALL, so a chunked stream can legitimately return int32 rows
    for one chunk and int64 for another; every consumer of the
    (cells, counts) contract is dtype-agnostic, and the engine casts
    cells back to the value dtype) and int64 otherwise. None when the
    library/symbol is unavailable (callers fall back to the numpy
    tier)."""
    if not windowed_reduce_available():
        return None
    n = len(src)
    num_w = -(-n // eb) if n else 0
    src, dst, val = (np.asarray(a) for a in (src, dst, val))
    ids_i32 = src.dtype == np.int32 and dst.dtype == np.int32
    ids_i64 = src.dtype == np.int64 and dst.dtype == np.int64
    # int32-output fast forms (gs_windowed_reduce_i32o /
    # _i64i32o): int32 output slabs halve the faulted/written output
    # bytes and make the engine's astype-back a no-op. Gated on the
    # same worst-case-sum bound as the numpy tier's exact_bincount
    # guard: max|val| × the most contributions one cell can receive
    # must fit int32 (min/max outputs are input values — always
    # safe). ident fits int32 by construction for int32 values. Ids
    # stay their own width — the i64-id form keeps the unsigned bound
    # check exact for ids beyond int32 (reported, never wrapped).
    out_i32 = (val.dtype == np.int32 and (ids_i32 or ids_i64)
               and getattr(_lib, "gs_windowed_reduce_i64i32o", None)
               is not None)
    per_cell = eb * (2 if direction == "all" else 1)
    # the COUNTS slab shares the output dtype, and one cell can
    # receive up to per_cell (= 2·eb for direction 'all')
    # contributions regardless of the reduce op — so the int32 form
    # needs 2*eb <= INT32_MAX for min/max and for the all-zero-sum
    # case too, where the old value-only gate (0 × per_cell) passed
    # vacuously while the counts could still wrap
    if out_i32:
        out_i32 = per_cell <= np.iinfo(np.int32).max
    if out_i32 and name == "sum" and n:
        # exact max|val| via two scans in Python ints (np.abs wraps on
        # INT32_MIN and would pass the gate with a negative bound)
        maxabs = max(int(val.max()), -int(val.min()))
        out_i32 = maxabs * per_cell <= np.iinfo(np.int32).max
    out_dt = np.int32 if out_i32 else np.int64
    if ident == 0:
        # calloc-backed zeros: the kernel touches only real cells, so
        # the identity fill is free (np.full writes the whole slab)
        cells = np.zeros((max(num_w, 1), vbp), out_dt)
    else:
        cells = np.full((max(num_w, 1), vbp), ident, out_dt)
    counts = np.zeros((max(num_w, 1), vbp), out_dt)
    if out_i32 and ids_i32:
        s32, d32, v32 = (np.ascontiguousarray(a)
                         for a in (src, dst, val))
        oob = _lib.gs_windowed_reduce_i32o(
            _i32ptr(s32), _i32ptr(d32), _i32ptr(v32), n, eb,
            vbp, _REDUCE_OPS[name], _REDUCE_DIRS[direction],
            _i32ptr(cells), _i32ptr(counts))
    elif out_i32:
        s64, d64 = np.ascontiguousarray(src), np.ascontiguousarray(dst)
        v32 = np.ascontiguousarray(val)
        oob = _lib.gs_windowed_reduce_i64i32o(
            _i64ptr(s64), _i64ptr(d64), _i32ptr(v32), n, eb, vbp,
            _REDUCE_OPS[name], _REDUCE_DIRS[direction],
            _i32ptr(cells), _i32ptr(counts))
    elif ids_i32 and val.dtype == np.int32:
        s32, d32, v32 = (np.ascontiguousarray(a)
                         for a in (src, dst, val))
        oob = _lib.gs_windowed_reduce_i32(
            _i32ptr(s32), _i32ptr(d32), _i32ptr(v32), n, eb,
            vbp, _REDUCE_OPS[name], _REDUCE_DIRS[direction],
            _i64ptr(cells), _i64ptr(counts))
    else:
        src = np.ascontiguousarray(src, np.int64)
        dst = np.ascontiguousarray(dst, np.int64)
        val = np.ascontiguousarray(val, np.int64)
        oob = _lib.gs_windowed_reduce(
            _i64ptr(src), _i64ptr(dst), _i64ptr(val), n, eb, vbp,
            _REDUCE_OPS[name], _REDUCE_DIRS[direction],
            _i64ptr(cells), _i64ptr(counts))
    if oob:
        # other tiers fail loudly on bad ids (bincount raises); the
        # C++ kernel skips them and reports — surface it identically
        raise ValueError(
            "%d vertex id(s) outside [0, %d) in windowed_reduce input"
            % (oob, vbp))
    return cells[:num_w], counts[:num_w]


def snapshot_available() -> bool:
    lib = _load()
    return lib is not None and hasattr(lib, "gs_snapshot_windows")


def snapshot_windows(src: np.ndarray, dst: np.ndarray,
                     offsets: np.ndarray, vb: int,
                     deg: np.ndarray = None, cc: np.ndarray = None,
                     cov: np.ndarray = None):
    """Carried-state windowed snapshot analytics via the C++ kernel
    (ingest.cpp gs_snapshot_windows) — the host tier of the driver's
    batched snapshot scan. Window w is the [offsets[w], offsets[w+1])
    slice of the flat COO arrays (varying lengths — event-time
    windows). `deg`/`cc`/`cov` are the caller-owned carried arrays
    (int32 [vb], [vb], [2·vb] — the driver's host mirror layouts, so
    checkpoints stay tier-interchangeable), updated in place; pass
    None to skip an analytic. Returns {"deg": [W, vb] int32,
    "labels": [W, vb] int32, "odd": [W, vb] bool} snapshot stacks for
    the enabled analytics, the cover as its odd flag, plus the chunk's
    final cover labels once as a fresh copy ({"cover_final": [2·vb]
    int32}) — the scan tier's `outs` contract — or None when the
    library/symbol is unavailable."""
    if not snapshot_available():
        return None
    src = np.ascontiguousarray(src, np.int32)
    dst = np.ascontiguousarray(dst, np.int32)
    offsets = np.ascontiguousarray(offsets, np.int64)
    num_w = len(offsets) - 1
    if num_w < 0 or int(offsets[-1]) != len(src):
        raise ValueError("offsets must span the flat edge arrays")
    nullp = ctypes.POINTER(ctypes.c_int32)()

    def ptr(a):
        return _i32ptr(a) if a is not None else nullp

    for name, a, ln in (("deg", deg, vb), ("cc", cc, vb),
                        ("cov", cov, 2 * vb)):
        if a is not None and (a.dtype != np.int32 or len(a) != ln
                              or not a.flags["C_CONTIGUOUS"]):
            raise ValueError("carried %s must be contiguous int32[%d]"
                             % (name, ln))
    flags = ((1 if deg is not None else 0)
             | (2 if cc is not None else 0)
             | (4 if cov is not None else 0))
    od = np.empty((num_w, vb), np.int32) if deg is not None else None
    oc = np.empty((num_w, vb), np.int32) if cc is not None else None
    oo = np.empty((num_w, vb), bool) if cov is not None else None
    w = _lib.gs_snapshot_windows(
        _i32ptr(src), _i32ptr(dst), _i64ptr(offsets), num_w, vb, flags,
        ptr(deg), ptr(cc), ptr(cov), ptr(od), ptr(oc),
        _u8ptr(oo) if oo is not None else ctypes.POINTER(ctypes.c_uint8)())
    if w != num_w:
        # not an assert: a short write must fail under `python -O` too
        raise RuntimeError("native snapshot_windows wrote %d of %d "
                           "windows" % (w, num_w))
    out = {}
    if od is not None:
        out["deg"] = od
    if oc is not None:
        out["labels"] = oc
    if oo is not None:
        out["odd"] = oo
        out["cover_final"] = cov.copy()
    return out


class NativeInterner:
    """Incremental int64-id interner backed by the C++ hash map, with
    the same contract as utils.interning.IncrementalInterner."""

    def __init__(self):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._handle = lib.gs_interner_new()

    def __len__(self) -> int:
        return int(self._lib.gs_interner_size(self._handle))

    def intern_array(self, ids: np.ndarray) -> np.ndarray:
        ids = np.ascontiguousarray(ids, np.int64)
        out = np.empty(len(ids), np.int32)
        self._lib.gs_interner_intern(self._handle, _i64ptr(ids), len(ids),
                                     _i32ptr(out))
        return out

    def ids_of(self, dense: np.ndarray) -> np.ndarray:
        dense = np.ascontiguousarray(dense, np.int32)
        out = np.empty(len(dense), np.int64)
        self._lib.gs_interner_lookup(self._handle, _i32ptr(dense),
                                     len(dense), _i64ptr(out))
        return out

    def id_of(self, dense: int) -> int:
        return int(self.ids_of(np.array([dense], np.int32))[0])

    def __del__(self):
        try:
            self._lib.gs_interner_free(self._handle)
        except Exception:  # gslint: disable=except-hygiene (interpreter teardown: lib/handle may already be unloaded)
            pass
