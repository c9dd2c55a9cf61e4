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

import _ctypes
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
# the same library through a handle whose calls KEEP the interpreter lock:
# for a kernel of some 10-100 us on a request's thread, where letting go of
# the lock means waiting a switch interval to have it back (tools/
# hostrank_harness.py; PERF.md section 6, PR 34)
LIB_HELD: ctypes.PyDLL | None = None
# what ytn_abi_version() of a library built from this tree's source says
ABI_VERSION = 2


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
             "-ffp-contract=off", "-o", tmp, _SRC_PATH],
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
    _bind_scorer(lib)


def _bind_scorer(lib: ctypes.CDLL) -> None:
    lib.ytn_cardinal_scores.argtypes = [
        _i32p, ctypes.c_int64, ctypes.c_int32, _i32p, ctypes.c_int32,
        ctypes.c_int64, _i64p, _i64p]
    lib.ytn_cardinal_scores.restype = ctypes.c_int32


def _open() -> ctypes.CDLL:
    """dlopen, check the version, bind. A library that fails either is
    closed again: for the same path the loader would hand the same
    mapping back after a rebuild."""
    lib = ctypes.CDLL(_SO_PATH)
    try:
        lib.ytn_abi_version.restype = ctypes.c_int32
        got = lib.ytn_abi_version()
        if got != ABI_VERSION:
            raise OSError(f"abi version {got}, expected {ABI_VERSION}")
        _bind(lib)
    except (OSError, AttributeError):   # AttributeError: missing symbol
        _ctypes.dlclose(lib._handle)
        raise
    return lib


def load() -> ctypes.CDLL | None:
    """Load (building if needed) the native library; None on any failure."""
    global _loaded, LIB, LIB_HELD
    if _loaded:
        return LIB
    with _load_lock:
        if _loaded:
            return LIB
        if os.environ.get("YACYTPU_NATIVE", "1") == "0":
            _loaded = True
            return None
        try:
            built = False
            if not os.path.exists(_SO_PATH) or (
                    os.path.exists(_SRC_PATH)
                    and os.path.getmtime(_SRC_PATH) > os.path.getmtime(_SO_PATH)):
                if not os.path.exists(_SRC_PATH):
                    raise OSError(f"{_SRC_PATH} missing")
                _build()
                built = True
            try:
                LIB = _open()
            except (OSError, AttributeError) as e:
                # a stale library NEWER than the source (a checkout that
                # moved back, a copied tree) would put every kernel on
                # NumPy: build once from the source that is here
                if built or not os.path.exists(_SRC_PATH):
                    raise
                log.info("native library stale (%s): rebuilding", e)
                _build()
                LIB = _open()
            LIB_HELD = ctypes.PyDLL(_SO_PATH)   # the mapping _open() made
            _bind_scorer(LIB_HELD)
        except (OSError, AttributeError) as e:
            log.warning("native library unavailable (%s): the numpy "
                        "fallbacks serve hashing/sort/join/ranking", e)
            LIB = LIB_HELD = None
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


def cardinal_topk(feats: np.ndarray, consts: np.ndarray, language_pref: int,
                  k: int) -> tuple[np.ndarray, np.ndarray] | None:
    """(int64 [n] scores, int64 [min(k, n)] best-first row order) of a
    candidate block, in one call: ops/ranking.cardinal_scores_host and its
    stable argsort. `consts` is the profile as ops/ranking._native_consts
    packs it. None when the library is unavailable."""
    if load() is None:
        return None
    f = _as_i32(feats)
    n, nf = f.shape
    if len(consts) != 10 + 2 * nf + 2 * int(consts[9]):
        return None         # not the layout the library reads
    scores = np.empty(n, dtype=np.int64)
    order = np.empty(min(k, n), dtype=np.int64)
    rc = LIB_HELD.ytn_cardinal_scores(
        f.ctypes.data_as(_i32p), n, nf, consts.ctypes.data_as(_i32p),
        language_pref, len(order), scores.ctypes.data_as(_i64p),
        order.ctypes.data_as(_i64p))
    return (scores, order) if rc == 0 else None
