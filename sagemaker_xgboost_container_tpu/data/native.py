"""ctypes bindings for the native data plane (native/fastdata.cpp).

The shared library is compiled lazily on first use (g++ -O3, cached under the
package build dir) — no pybind11 in the image, so the interface is a plain C
ABI driven from ctypes with preallocated numpy buffers (two-pass: count, then
fill). ``parse_libsvm_native`` returns the same tuple as the pure-Python
tokenizer in readers.py and is None-able: callers fall back to Python when no
compiler is available.
"""

import ctypes
import logging
import os
import subprocess
import tempfile
import threading

import numpy as np

logger = logging.getLogger(__name__)

_SOURCE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
    "fastdata.cpp",
)
_CACHE_DIR = os.path.join(tempfile.gettempdir(), "sm_xgb_tpu_native")
_LIB_PATH = os.path.join(_CACHE_DIR, "libfastdata.so")


def _packaged_extension():
    """Path of the wheel-shipped _fastdata extension, or None.

    setup.py builds native/fastdata.cpp into
    ``sagemaker_xgboost_container_tpu/_fastdata*.so`` so installed images get
    the C++ parser without a compiler. It is a plain
    C-ABI object — loaded with ctypes, never imported.
    """
    import glob

    pkg_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    hits = sorted(glob.glob(os.path.join(pkg_dir, "_fastdata*.so")))
    return hits[0] if hits else None


def _resolve_lib_path():
    """Pick the shared object to load (pure decision, no side effects).

    Returns ("packaged", path) for the wheel-shipped extension, or
    ("rebuild", path) when the lazy tempdir build should be (re)used — a dev
    tree whose source is fresher than the shipped object rebuilds so edits
    take effect.
    """
    packaged = _packaged_extension()
    if packaged is not None and (
        not os.path.exists(_SOURCE)
        or os.path.getmtime(_SOURCE) <= os.path.getmtime(packaged)
    ):
        return "packaged", packaged
    return "rebuild", _LIB_PATH

_lock = threading.Lock()
_lib = None
_tried = False


class _LibsvmInfo(ctypes.Structure):
    _fields_ = [
        ("n_rows", ctypes.c_int64),
        ("nnz", ctypes.c_int64),
        ("max_index", ctypes.c_int64),
        ("has_weights", ctypes.c_int32),
        ("has_qids", ctypes.c_int32),
        ("error_line", ctypes.c_int64),
    ]


def _build():
    os.makedirs(_CACHE_DIR, exist_ok=True)
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-pthread", "-o", _LIB_PATH, _SOURCE
    ]
    subprocess.run(cmd, check=True, capture_output=True)


def _load():
    global _lib, _tried
    # lock-free steady state: _lib/_tried are only ever written under the
    # lock, and the serving hot path calls this per request
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        try:
            kind, lib_path = _resolve_lib_path()
            if kind == "rebuild":
                if not os.path.exists(lib_path) or (
                    os.path.exists(_SOURCE)
                    and os.path.getmtime(_SOURCE) > os.path.getmtime(lib_path)
                ):
                    _build()
            lib = ctypes.CDLL(lib_path)
            lib.libsvm_count.restype = ctypes.c_int
            lib.libsvm_count.argtypes = [
                ctypes.c_char_p,
                ctypes.c_int64,
                ctypes.POINTER(_LibsvmInfo),
            ]
            lib.libsvm_fill.restype = ctypes.c_int
            lib.libsvm_fill.argtypes = [ctypes.c_char_p, ctypes.c_int64] + [
                ctypes.c_void_p
            ] * 6
            try:
                lib.libsvm_count_mt.restype = ctypes.c_int
                lib.libsvm_count_mt.argtypes = [
                    ctypes.c_char_p,
                    ctypes.c_int64,
                    ctypes.c_int32,
                    ctypes.POINTER(_LibsvmInfo),
                    ctypes.POINTER(_LibsvmInfo),
                ]
                lib.libsvm_fill_mt.restype = ctypes.c_int
                lib.libsvm_fill_mt.argtypes = [
                    ctypes.c_char_p,
                    ctypes.c_int64,
                    ctypes.c_int32,
                    ctypes.POINTER(_LibsvmInfo),
                ] + [ctypes.c_void_p] * 6
            except AttributeError:  # stale cached single-thread .so
                lib.libsvm_count_mt = None
            try:
                lib.forest_leaf_values.restype = ctypes.c_int
                lib.forest_leaf_values.argtypes = (
                    [ctypes.c_void_p] * 9
                    + [ctypes.c_int64] * 3
                    + [ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                       ctypes.c_int32, ctypes.c_void_p]
                )
            except AttributeError:  # stale cached pre-r5 .so
                lib.forest_leaf_values = None
            _lib = lib
            logger.info("native data plane loaded from %s (%s)", lib_path, kind)
        except Exception as e:  # no compiler / load failure -> python fallback
            logger.warning(
                "native data plane unavailable (%s); using the python "
                "parser and the numpy forest traversal", e
            )
            _lib = None
        finally:
            # set only AFTER the attempt: the unlocked fast path above must
            # not return None to concurrent callers while a first build is
            # still running behind the lock
            _tried = True
    return _lib


def native_available():
    return _load() is not None


def forest_predictor_available():
    """True when the loaded library carries the r5 forest traversal symbol
    (a stale cached pre-r5 .so can be native_available() without it)."""
    lib = _load()
    return lib is not None and getattr(lib, "forest_leaf_values", None) is not None


def parse_libsvm_native(data):
    """bytes -> (csr pieces, labels, weights|None, qids|None) or None.

    Returns None when the native library is unavailable; raises ValueError on
    malformed input (with the failing line number, matching the python
    parser's UserError contract at the caller).
    """
    lib = _load()
    if lib is None:
        return None
    if isinstance(data, str):
        data = data.encode("utf-8")

    nthreads = _parse_threads(len(data))
    mt = nthreads > 1 and getattr(lib, "libsvm_count_mt", None) is not None
    info = _LibsvmInfo()
    if mt:
        per_chunk = (_LibsvmInfo * nthreads)()
        rc = lib.libsvm_count_mt(
            data, len(data), nthreads, ctypes.byref(info), per_chunk
        )
        if rc != 0:
            # error lines from chunks are chunk-local; re-run the
            # single-threaded counter for the exact global line number
            lib.libsvm_count(data, len(data), ctypes.byref(info))
            raise ValueError("Malformed LIBSVM line {}".format(info.error_line))
    else:
        rc = lib.libsvm_count(data, len(data), ctypes.byref(info))
        if rc != 0:
            raise ValueError("Malformed LIBSVM line {}".format(info.error_line))
    n, nnz = info.n_rows, info.nnz
    labels = np.empty(n, np.float32)
    weights = np.empty(n, np.float32)
    qids = np.empty(n, np.int64) if info.has_qids else None
    indices = np.empty(nnz, np.int64)
    values = np.empty(nnz, np.float32)
    indptr = np.empty(n + 1, np.int64)
    bufs = [
        labels.ctypes.data_as(ctypes.c_void_p),
        weights.ctypes.data_as(ctypes.c_void_p),
        qids.ctypes.data_as(ctypes.c_void_p) if qids is not None else None,
        indices.ctypes.data_as(ctypes.c_void_p),
        values.ctypes.data_as(ctypes.c_void_p),
        indptr.ctypes.data_as(ctypes.c_void_p),
    ]
    if mt:
        rc = lib.libsvm_fill_mt(data, len(data), nthreads, per_chunk, *bufs)
    else:
        rc = lib.libsvm_fill(data, len(data), *bufs)
    if rc != 0:
        raise ValueError("Malformed LIBSVM input")
    return (
        (values, indices, indptr),
        labels,
        weights if info.has_weights else None,
        qids,
    )


def _parse_threads(nbytes):
    """Thread count for the parallel parse: one per ~8MB, capped by the host
    (GRAFT_PARSE_THREADS overrides; <=1 forces the single-threaded path)."""
    env = os.environ.get("GRAFT_PARSE_THREADS")
    if env is not None:
        return max(1, int(env))
    per_thread = 8 << 20
    return max(1, min(os.cpu_count() or 1, 16, nbytes // per_thread))


def forest_leaf_values_native(stacked, x):
    """Stacked forest + [n, d] float32 rows -> [n, T] per-tree leaf values
    via the C++ traversal (native/fastdata.cpp::forest_leaf_values), or None
    when the native library (or, for stale cached builds, the symbol) is
    unavailable — callers fall back to the numpy twin.

    The ctypes-ready operand tuple is cached ON the stacked dict (memoized
    per forest slice in Forest._stack), so steady-state serving requests do
    zero dtype conversions.
    """
    lib = _load()
    if lib is None or getattr(lib, "forest_leaf_values", None) is None:
        return None
    args = stacked.get("_native_args")
    if isinstance(args, str):  # "invalid": corrupt indices, numpy handles it
        return None
    if args is None:
        def prep(key, dtype):
            a = np.asarray(stacked[key])
            if a.dtype == np.bool_ and dtype == np.uint8:
                a = a.view(np.uint8)  # same itemsize: free
            return np.ascontiguousarray(a, dtype)

        feature = prep("feature", np.int32)
        T, N = feature.shape
        if "cat_split" in stacked:
            cat_split = prep("cat_split", np.uint8)
            cat_mask = np.ascontiguousarray(stacked["cat_mask"], np.uint32)
            W = cat_mask.shape[2]
        else:
            cat_split = cat_mask = None
            W = 0
        left = prep("left", np.int32)
        right = prep("right", np.int32)
        # index sanity, checked ONCE per stacked forest: the numpy twin
        # raises IndexError on a corrupt BYO model's out-of-range node ids;
        # the C++ loop would read out of bounds — refuse and let the caller
        # fall back to numpy (which fails loudly and safely)
        if feature.size == 0 or (
            left.min() < 0 or left.max() >= N
            or right.min() < 0 or right.max() >= N
            or feature.min() < 0
        ):
            # zero-node dicts (never produced by Forest._stack) also refuse:
            # the numpy twin is the one with defined empty-input semantics
            stacked["_native_args"] = "invalid"
            return None
        arrays = (
            feature, prep("threshold", np.float32),
            prep("default_left", np.uint8), left, right,
            prep("is_leaf", np.uint8),
            prep("leaf_value", np.float32), cat_split, cat_mask,
        )
        # pointers precomputed as plain ints: ndarray.ctypes.data_as costs
        # ~2 us each and there are nine forest operands per call — the
        # `arrays` tuple cached alongside keeps the buffers alive
        ptrs = tuple(
            a.__array_interface__["data"][0] if a is not None else None
            for a in arrays
        )
        fmax = int(feature.max())  # non-empty: the guard above refused size 0
        args = (arrays, ptrs, T, N, W, int(stacked["depth"]), fmax)
        stacked["_native_args"] = args
    _arrays, ptrs, T, N, W, depth, fmax = args
    x = np.ascontiguousarray(x, np.float32)
    n, d = x.shape
    if fmax >= d:  # feature id beyond payload width: numpy raises cleanly
        return None
    out = np.empty((n, T), np.float32)
    rc = lib.forest_leaf_values(
        *ptrs, T, N, W,
        x.__array_interface__["data"][0], n, d, depth,
        out.__array_interface__["data"][0],
    )
    if rc != 0:  # pragma: no cover - the traversal cannot fail today
        return None
    return out
