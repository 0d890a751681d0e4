"""Auto-built C++ host GAR library, loaded via ctypes.

The framework's counterpart of the reference's self-compiling native layer:
sources in this directory are compiled into one shared library on first
use, rebuilt whenever the sources' hash changes (the reference rebuilds on
mtimes: native/__init__.py:190-206,
aggregators/deprecated_native/__init__.py:43-68).
The toolchain is plain ``c++ -std=c++17 -O3`` — no TF/TPU headers, because
this tier is pure host code: the accelerator path is jnp/Pallas, and this
library serves host-side aggregation, large-scale oracles, and CPU-only
deployments.

Public API (all take/return numpy arrays, float32 or float64, row-major):
  ``average(g)  average_nan(g)  median(g)  averaged_median(g, f)``
  ``pairwise_sq_distances(g)  krum(g, f, m=None)  bulyan(g, f)``
plus ``available()`` / ``load()`` / ``build(force=...)`` and
``num_threads()``.  Set ``AGTPU_NATIVE_CXX`` to override the compiler and
``AGTPU_NUM_THREADS`` to bound the pool.
"""

import ctypes
import hashlib
import os
import subprocess
import tempfile

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCES = ("kernels.cpp", "auth.cpp", "io.cpp", "threadpool.hpp")
_COMPILE_UNITS = ("kernels.cpp", "auth.cpp", "io.cpp")

_lib = None
_load_error = None


def _source_hash():
    """Hash of every source the library is built from."""
    digest = hashlib.sha256()
    for src in _SOURCES:
        with open(os.path.join(_DIR, src), "rb") as fd:
            digest.update(src.encode() + b"\0" + fd.read() + b"\0")
    return digest.hexdigest()[:16]


def _lib_path():
    """The library's name records the hash of its sources, so a library no
    commit's sources produced (a copied tree, a checkout with fresh mtimes)
    is never loaded: a source change is a different file name."""
    return os.path.join(_DIR, "libagtpu_host-%s.so" % _source_hash())


def build(force=False):
    """Compile the shared library unless the one these sources produce is
    already there; returns its path.

    Atomic: compiles to a temp file in the same directory, then renames —
    concurrent importers either see no library or the complete one.
    A library of another source hash is simply never loaded again.
    """
    target = _lib_path()
    if not force and os.path.exists(target):
        return target
    compiler = os.environ.get("AGTPU_NATIVE_CXX", "c++")
    fd, tmp = tempfile.mkstemp(suffix=".so", prefix=".build-", dir=_DIR)
    os.close(fd)
    cmd = [
        compiler, "-std=c++17", "-O3", "-fPIC", "-shared", "-pthread",
        "-Wall", "-Wextra",
        *[os.path.join(_DIR, unit) for unit in _COMPILE_UNITS],
        "-o", tmp,
    ]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                "native build failed (%s):\n%s" % (" ".join(cmd), proc.stderr.strip())
            )
        os.replace(tmp, target)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return target


def _declare(lib):
    """Attach ctypes signatures for every exported symbol."""
    i64 = ctypes.c_int64
    f32p = ctypes.POINTER(ctypes.c_float)
    f64p = ctypes.POINTER(ctypes.c_double)
    lib.agtpu_num_threads.restype = i64
    lib.agtpu_num_threads.argtypes = []
    for suffix, ptr in (("f32", f32p), ("f64", f64p)):
        for name, extra in (
            ("average", ()),
            ("average_nan", ()),
            ("median", ()),
            ("averaged_median", (i64,)),
            ("krum", (i64, i64)),
            ("bulyan", (i64,)),
        ):
            fn = getattr(lib, "agtpu_%s_%s" % (name, suffix))
            fn.restype = None
            fn.argtypes = [ptr, i64, i64] + list(extra) + [ptr]
        fn = getattr(lib, "agtpu_pairwise_sqdist_%s" % suffix)
        fn.restype = None
        fn.argtypes = [ptr, i64, i64, f64p]
    u8p = ctypes.POINTER(ctypes.c_uint8)
    size_t = ctypes.c_size_t
    i64p = ctypes.POINTER(i64)
    lib.agtpu_crc32c.restype = ctypes.c_uint32
    lib.agtpu_crc32c.argtypes = [u8p, size_t]
    lib.agtpu_tfrecord_index.restype = i64
    lib.agtpu_tfrecord_index.argtypes = [u8p, i64, i64p, i64p, i64, ctypes.c_int]
    lib.agtpu_sha256.restype = None
    lib.agtpu_sha256.argtypes = [u8p, size_t, u8p]
    lib.agtpu_hmac_sha256.restype = None
    lib.agtpu_hmac_sha256.argtypes = [u8p, size_t, u8p, size_t, u8p]
    lib.agtpu_hmac_verify.restype = ctypes.c_int
    lib.agtpu_hmac_verify.argtypes = [u8p, size_t, u8p, size_t, u8p]


def load():
    """Build if needed and load the library (cached); raises on failure."""
    global _lib, _load_error
    if _lib is not None:
        return _lib
    if _load_error is not None:
        raise _load_error
    try:
        lib = ctypes.CDLL(build())
        _declare(lib)
    except Exception as exc:  # compiler missing, unsupported platform, ...
        _load_error = RuntimeError("native GAR library unavailable: %s" % exc)
        raise _load_error from exc
    _lib = lib
    return lib


def available():
    """True when the native library builds and loads on this host."""
    try:
        load()
        return True
    except Exception:
        return False


def num_threads():
    return int(load().agtpu_num_threads())


# --------------------------------------------------------------------------- #
# numpy wrappers

def _prepare(grads):
    """Contiguous 2-D float32/float64 view + (suffix, ctype) dispatch info."""
    g = np.asarray(grads)
    if g.ndim != 2:
        raise ValueError("expected an (n, d) gradient matrix, got shape %r" % (g.shape,))
    if g.dtype == np.float32:
        suffix, ctype = "f32", ctypes.c_float
    else:
        g = g.astype(np.float64, copy=False)
        suffix, ctype = "f64", ctypes.c_double
    return np.ascontiguousarray(g), suffix, ctype


def _ptr(arr, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def _rowwise(name, grads, *extra):
    lib = load()
    g, suffix, ctype = _prepare(grads)
    n, d = g.shape
    out = np.empty(d, dtype=g.dtype)
    fn = getattr(lib, "agtpu_%s_%s" % (name, suffix))
    fn(_ptr(g, ctype), n, d, *[ctypes.c_int64(int(e)) for e in extra], _ptr(out, ctype))
    return out


def average(grads):
    return _rowwise("average", grads)


def average_nan(grads):
    return _rowwise("average_nan", grads)


def median(grads):
    return _rowwise("median", grads)


def averaged_median(grads, f):
    return _rowwise("averaged_median", grads, f)


def krum(grads, f, m=None):
    n = np.asarray(grads).shape[0]
    if m is None:
        m = n - int(f) - 2
    if not 1 <= int(m) <= n:
        raise ValueError("krum selection size m=%d out of range [1, n=%d] (f=%d)" % (m, n, f))
    return _rowwise("krum", grads, f, m)


def bulyan(grads, f):
    return _rowwise("bulyan", grads, f)


def pairwise_sq_distances(grads):
    """(n, n) float64 all-pairs squared distances (non-finite -> +inf)."""
    lib = load()
    g, suffix, ctype = _prepare(grads)
    n, d = g.shape
    out = np.empty((n, n), dtype=np.float64)
    fn = getattr(lib, "agtpu_pairwise_sqdist_%s" % suffix)
    fn(_ptr(g, ctype), n, d, _ptr(out, ctypes.c_double))
    return out


# --------------------------------------------------------------------------- #
# TFRecord IO (io.cpp; the fast path behind models/tfrecord.py)

def crc32c(data):
    """CRC32C (Castagnoli) of bytes/uint8 array — the TFRecord checksum."""
    lib = load()
    _, ptr, length = _u8(data)
    return int(lib.agtpu_crc32c(ptr, length))


def tfrecord_index(buf, verify=True):
    """Index a whole TFRecord shard held in ``buf`` (bytes/mmap/uint8 array).

    Returns (offsets, lengths) int64 arrays — payload i is
    ``buf[offsets[i]:offsets[i]+lengths[i]]``.  With ``verify`` all framing
    CRCs are checked (payloads in parallel on the thread pool).  Raises
    ValueError at the first corrupt byte offset.
    """
    lib = load()
    arr, ptr, length = _u8(buf)
    # every record is >= 16 bytes of framing
    cap = max(1, length // 16 + 1)
    offsets = np.empty(cap, dtype=np.int64)
    lengths = np.empty(cap, dtype=np.int64)
    i64p = ctypes.POINTER(ctypes.c_int64)
    count = int(lib.agtpu_tfrecord_index(
        ptr, length,
        offsets.ctypes.data_as(i64p), lengths.ctypes.data_as(i64p),
        cap, 1 if verify else 0,
    ))
    if count < 0:
        raise ValueError("corrupt TFRecord framing at byte %d" % (-count - 1))
    # copies: slicing views would pin the file-sized scratch allocation
    return offsets[:count].copy(), lengths[:count].copy()


# --------------------------------------------------------------------------- #
# host authentication (auth.cpp; see parallel/auth.py for the policy layer)

def _u8(buf):
    arr = np.frombuffer(buf, dtype=np.uint8) if not isinstance(buf, np.ndarray) else buf
    arr = np.ascontiguousarray(arr, dtype=np.uint8).ravel()
    return arr, arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), arr.size


def sha256(data):
    """32-byte SHA-256 digest of ``data`` (bytes or uint8 array)."""
    lib = load()
    _, dptr, dlen = _u8(data)
    out = np.empty(32, dtype=np.uint8)
    lib.agtpu_sha256(dptr, dlen, out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    return out.tobytes()


def hmac_sha256(key, data):
    """32-byte HMAC-SHA256 tag of ``data`` under ``key``."""
    lib = load()
    _, kptr, klen = _u8(key)
    _, dptr, dlen = _u8(data)
    out = np.empty(32, dtype=np.uint8)
    lib.agtpu_hmac_sha256(kptr, klen, dptr, dlen, out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    return out.tobytes()


def hmac_verify(key, data, tag):
    """Constant-time verification of a 32-byte tag."""
    if len(tag) != 32:
        return False
    lib = load()
    _, kptr, klen = _u8(key)
    _, dptr, dlen = _u8(data)
    _, tptr, _tlen = _u8(tag)
    return bool(lib.agtpu_hmac_verify(kptr, klen, dptr, dlen, tptr))
