"""Parity tests: native C++ kernels (native/yacytpu.cpp) vs the numpy/Python
reference paths. The native library is built on demand by utils/native.load();
g++ is part of the baked environment, so availability is asserted, not
skipped — a silent fallback would hide a broken native build forever."""

import numpy as np
import pytest

from yacy_search_server_tpu.utils import native
from yacy_search_server_tpu.utils.hashes import word2hash, word_hashes
from yacy_search_server_tpu.index import postings as P


def test_native_builds_and_loads():
    assert native.available(), "native library failed to build/load"


def test_word_hash_batch_parity():
    words = ["hello", "World", "Straße", "ÅNGSTRÖM", "x" * 128, "a",
             "foo_bar", "123abc", "日本語テスト", "mixedCASE", "tpu",
             "peer", "search", "index", "crawler", "ranking", "dht"]
    got = native.word_hash_batch(words)
    assert got is not None
    assert got == [word2hash(w) for w in words]


def test_word_hashes_wrapper_uses_batch():
    words = [f"word{i}" for i in range(100)]
    assert word_hashes(words) == [word2hash(w) for w in words]


def test_sort_dedupe_parity_last_wins():
    rng = np.random.default_rng(3)
    for n in (1, 5, 64, 1000):
        d = rng.integers(0, max(2, n // 2), n).astype(np.int32)
        f = np.arange(n * P.NF, dtype=np.int32).reshape(n, P.NF)
        order = native.sort_dedupe_order(d, min_batch=1)
        assert order is not None
        # python reference: stable sort, keep last of equal runs
        ref = {}
        for i in range(n):
            ref[int(d[i])] = i
        exp_ids = sorted(ref)
        assert list(d[order]) == exp_ids
        assert [int(o) for o in order] == [ref[k] for k in exp_ids]
        # and through the public API (threshold 64 routes to native)
        pl = P.sort_dedupe(d, f)
        assert list(pl.docids) == exp_ids
        assert all(pl.feats[i, 0] == f[ref[k], 0]
                   for i, k in enumerate(exp_ids))


@pytest.mark.parametrize("n", (2, 3, 1000))
def test_sort_dedupe_takes_an_ascending_list_as_it_is(n):
    """A list already ascending without repeats (a bulk import's) skips
    the sort: the same rows, in new arrays the caller's do not alias."""
    rng = np.random.default_rng(n)
    d = np.cumsum(rng.integers(1, 5, n)).astype(np.int64)
    f = rng.integers(0, 100, (n, P.NF)).astype(np.int32)
    pl = P.sort_dedupe(d, f)
    assert pl.docids.dtype == np.int32 and pl.feats.dtype == np.int32
    np.testing.assert_array_equal(pl.docids, d)
    np.testing.assert_array_equal(pl.feats, f)
    f[0, 0] += 1
    assert pl.feats[0, 0] != f[0, 0]
    # a repeat takes the sort and keeps the last row
    d[1] = d[0]
    pl = P.sort_dedupe(d, f)
    assert len(pl) == n - 1 and pl.feats[0, 0] == f[1, 0]


def test_intersect_parity():
    rng = np.random.default_rng(11)
    for na, nb in ((100, 100), (1000, 500), (64, 4096)):
        a = np.unique(rng.integers(0, 3000, na).astype(np.int32))
        b = np.unique(rng.integers(0, 3000, nb).astype(np.int32))
        out = native.intersect(a, b)
        assert out is not None
        ia, ib = out
        exp = np.intersect1d(a, b, assume_unique=True)
        assert np.array_equal(a[ia], exp)
        assert np.array_equal(b[ib], exp)
    # below the batch threshold the wrapper declines (numpy path takes over)
    assert native.intersect(np.arange(3, dtype=np.int32),
                            np.arange(3, dtype=np.int32)) is None


def test_alive_mask_parity():
    rng = np.random.default_rng(7)
    d = np.unique(rng.integers(0, 500, 300).astype(np.int32))
    dead = np.unique(rng.integers(0, 500, 50).astype(np.int32))
    mask = native.alive_mask(d, dead)
    assert mask is not None
    assert np.array_equal(mask, ~np.isin(d, dead))
    pl = P.PostingsList(d, np.zeros((len(d), P.NF), np.int32))
    out = P.remove_docids(pl, dead)
    assert np.array_equal(out.docids, d[~np.isin(d, dead)])


def test_md5_block_boundaries():
    # exercise the 55/56/63/64/119-byte padding boundaries of the C++ MD5
    for ln in (0, 1, 54, 55, 56, 57, 63, 64, 65, 118, 119, 120, 200):
        w = "z" * max(ln, 1)
        got = native.word_hash_batch([w] * 16)
        assert got is not None and got[0] == word2hash(w)


@pytest.mark.parametrize("n", [0, 1, 15, 16, 17])
def test_word_hashes_thresholds(n):
    words = [f"tok{i}" for i in range(n)]
    assert word_hashes(words) == [word2hash(w) for w in words]
