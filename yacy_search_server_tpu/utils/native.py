"""ctypes bindings to the native C++ data-plane kernels (native/yacytpu.cpp).

The compute path of this framework is JAX/XLA/Pallas on device; this module
is the native *runtime* around it — the host-side feeding kernels that the
reference implements as concurrent Java (per-word MD5+base64 hashing,
Word.java:113-130; posting-row sorts and hash-probe joins,
ReferenceContainer.java:397-489). Loading is best-effort:

- `YACYTPU_NATIVE=0` disables the native path entirely;
- if `native/libyacytpu.so` is missing, it is compiled once with g++
  (the `.so` is a build product: git-ignored, never shipped);
- on any failure `LIB` stays None, a WARNING names the reason once, and
  callers fall back to numpy — the native path and the fallback are
  interchangeable call-for-call (parity is enforced by
  tests/test_native.py).
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

import numpy as np

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libyacytpu.so")
_SRC_PATH = os.path.join(_NATIVE_DIR, "yacytpu.cpp")

_i64p = ctypes.POINTER(ctypes.c_int64)
_i32p = ctypes.POINTER(ctypes.c_int32)
_u8p = ctypes.POINTER(ctypes.c_uint8)

log = logging.getLogger("yacy.native")

_load_lock = threading.Lock()
_loaded = False
LIB: ctypes.CDLL | None = None


# below these sizes the ctypes call overhead beats the kernel win; wrappers
# return None and callers stay on their numpy/Python path
MIN_BATCH = 64
MIN_HASH_BATCH = 16


def _build() -> None:
    """Compile the library; raises OSError naming the reason."""
    # compile to a temp path + atomic rename: another process scanning the
    # directory must never dlopen a half-written ELF
    tmp = f"{_SO_PATH}.tmp.{os.getpid()}"
    try:
        res = subprocess.run(
            ["g++", "-O3", "-fPIC", "-shared", "-std=c++17",
             "-o", tmp, _SRC_PATH],
            capture_output=True, timeout=120)
        if res.returncode != 0 or not os.path.exists(tmp):
            raise OSError(
                f"g++ rc {res.returncode}: "
                f"{res.stderr.decode('utf-8', 'replace')[-400:]}")
        os.replace(tmp, _SO_PATH)
    except subprocess.SubprocessError as e:
        raise OSError(f"g++: {e!r}") from e
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass


def _bind(lib: ctypes.CDLL) -> None:
    lib.ytn_abi_version.restype = ctypes.c_int32
    lib.ytn_word_hash_batch.argtypes = [_u8p, _i64p, ctypes.c_int64, _u8p]
    lib.ytn_word_hash_batch.restype = None
    lib.ytn_sort_dedupe.argtypes = [_i32p, ctypes.c_int64, _i64p]
    lib.ytn_sort_dedupe.restype = ctypes.c_int64
    lib.ytn_intersect.argtypes = [_i32p, ctypes.c_int64, _i32p, ctypes.c_int64,
                                  _i64p, _i64p]
    lib.ytn_intersect.restype = ctypes.c_int64
    lib.ytn_remove_docids.argtypes = [_i32p, ctypes.c_int64, _i32p,
                                      ctypes.c_int64, _u8p]
    lib.ytn_remove_docids.restype = None


def load() -> ctypes.CDLL | None:
    """Load (building if needed) the native library; None on any failure."""
    global _loaded, LIB
    if _loaded:
        return LIB
    with _load_lock:
        if _loaded:
            return LIB
        if os.environ.get("YACYTPU_NATIVE", "1") == "0":
            _loaded = True
            return None
        try:
            if not os.path.exists(_SO_PATH) or (
                    os.path.exists(_SRC_PATH)
                    and os.path.getmtime(_SRC_PATH) > os.path.getmtime(_SO_PATH)):
                if not os.path.exists(_SRC_PATH):
                    raise OSError(f"{_SRC_PATH} missing")
                _build()
            lib = ctypes.CDLL(_SO_PATH)
            _bind(lib)
            if lib.ytn_abi_version() != 1:
                raise OSError("abi mismatch")
            LIB = lib
        except (OSError, AttributeError) as e:  # AttributeError: missing symbol
            log.warning("native library unavailable (%s): the numpy "
                        "fallbacks serve hashing/sort/join", e)
            LIB = None
        _loaded = True
        return LIB


def available() -> bool:
    return load() is not None


def _as_i32(a: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(a, dtype=np.int32)


# -- wrappers (callers must check available() or handle None LIB) ------------

def word_hash_batch(words: list[str]) -> list[bytes] | None:
    """12-char word hashes for a batch of (not yet lowercased) tokens.

    Bit-compatible with utils/hashes.word2hash. Returns None when the
    native library is unavailable or the batch is too small to pay the
    call overhead (caller falls back to the Python path).
    """
    if len(words) < MIN_HASH_BATCH:
        return None
    lib = load()
    if lib is None:
        return None
    enc = [w.lower().encode("utf-8") for w in words]
    n = len(enc)
    if n == 0:
        return []
    blob = b"".join(enc)
    offs = np.zeros(n + 1, dtype=np.int64)
    np.cumsum([len(e) for e in enc], out=offs[1:])
    buf = np.frombuffer(blob, dtype=np.uint8) if blob else np.zeros(1, np.uint8)
    buf = np.ascontiguousarray(buf)
    out = np.empty(n * 12, dtype=np.uint8)
    lib.ytn_word_hash_batch(
        buf.ctypes.data_as(_u8p), offs.ctypes.data_as(_i64p),
        ctypes.c_int64(n), out.ctypes.data_as(_u8p))
    raw = out.tobytes()
    return [raw[12 * i: 12 * i + 12] for i in range(n)]


def sort_dedupe_order(docids: np.ndarray,
                      min_batch: int = MIN_BATCH) -> np.ndarray | None:
    """Original-row indices of surviving postings in ascending-docid order
    (last-wins dedupe); None when native is unavailable or input is small."""
    if len(docids) < min_batch:
        return None
    lib = load()
    if lib is None:
        return None
    d = _as_i32(docids)
    order = np.empty(len(d), dtype=np.int64)
    m = lib.ytn_sort_dedupe(d.ctypes.data_as(_i32p), ctypes.c_int64(len(d)),
                            order.ctypes.data_as(_i64p))
    return order[:m]


def intersect(a: np.ndarray, b: np.ndarray) -> tuple[np.ndarray, np.ndarray] | None:
    """(indices into a, indices into b) of the sorted-unique intersection."""
    if min(len(a), len(b)) < MIN_BATCH:
        return None
    lib = load()
    if lib is None:
        return None
    aa, bb = _as_i32(a), _as_i32(b)
    cap = min(len(aa), len(bb))
    ia = np.empty(cap, dtype=np.int64)
    ib = np.empty(cap, dtype=np.int64)
    m = lib.ytn_intersect(aa.ctypes.data_as(_i32p), ctypes.c_int64(len(aa)),
                          bb.ctypes.data_as(_i32p), ctypes.c_int64(len(bb)),
                          ia.ctypes.data_as(_i64p), ib.ctypes.data_as(_i64p))
    return ia[:m], ib[:m]


def alive_mask(docids: np.ndarray, dead_sorted: np.ndarray) -> np.ndarray | None:
    """Boolean mask of postings NOT tombstoned (dead_sorted ascending)."""
    if len(docids) < MIN_BATCH:
        return None
    lib = load()
    if lib is None:
        return None
    d, dd = _as_i32(docids), _as_i32(dead_sorted)
    out = np.empty(len(d), dtype=np.uint8)
    lib.ytn_remove_docids(d.ctypes.data_as(_i32p), ctypes.c_int64(len(d)),
                          dd.ctypes.data_as(_i32p), ctypes.c_int64(len(dd)),
                          out.ctypes.data_as(_u8p))
    return out.view(bool)
