"""M4 — sharded mesh query path: parity with the single-device kernels.

The sharded kernels must produce results identical to the single-device
path (same stats-merge math, SURVEY.md §7 build plan M4 "ranking parity
tests vs M2"). Runs on the 8-device virtual CPU pool (conftest).
"""

import numpy as np
import pytest

import jax

from yacy_search_server_tpu.index import postings as P
from yacy_search_server_tpu.index.postings import PostingsList
from yacy_search_server_tpu.ops.ranking import (CardinalRanker,
                                                RankingProfile,
                                                bm25_scores_np)
from yacy_search_server_tpu.parallel.mesh import (MeshBM25, MeshRanker,
                                                  make_mesh, pad_to_shards)


def _cpu8():
    devs = jax.devices("cpu")
    if len(devs) < 8:
        pytest.skip("needs 8 virtual CPU devices")
    return devs


def _random_postings(n, seed=0):
    rng = np.random.default_rng(seed)
    docids = np.arange(n, dtype=np.int32)
    feats = rng.integers(0, 500, (n, P.NF)).astype(np.int32)
    feats[:, P.F_FLAGS] = rng.integers(0, 2**20, n)
    feats[:, P.F_LANGUAGE] = np.where(rng.random(n) < 0.5,
                                      P.pack_language("en"),
                                      P.pack_language("de"))
    feats[:, P.F_DOMLENGTH] = rng.integers(0, 256, n)
    hosts = [bytes([i % 13, 7]) for i in range(n)]
    return PostingsList(docids, feats), hosts


def test_pad_to_shards():
    assert pad_to_shards(1, 8) == 8 * 128
    assert pad_to_shards(8 * 128, 8) == 8 * 128
    assert pad_to_shards(8 * 128 + 1, 8) == 8 * 256


@pytest.mark.parametrize("n_term,n_doc", [(1, 8), (2, 4)])
def test_cardinal_parity_across_mesh_shapes(n_term, n_doc):
    devs = _cpu8()
    pl, hosts = _random_postings(1000, seed=1)
    s1, d1 = CardinalRanker().rank(pl, hosts, k=10)
    mesh = make_mesh(n_doc=n_doc, n_term=n_term, devices=devs)
    s2, d2 = MeshRanker(mesh).rank(pl, hosts, k=10)
    np.testing.assert_array_equal(s1, s2)
    np.testing.assert_array_equal(d1, d2)


def test_cardinal_parity_with_profile():
    devs = _cpu8()
    pl, hosts = _random_postings(600, seed=2)
    prof = RankingProfile(authority=15, language=5)  # authority kernel active
    s1, d1 = CardinalRanker(prof).rank(pl, hosts, k=20)
    mesh = make_mesh(n_doc=8, devices=devs)
    s2, d2 = MeshRanker(mesh, prof).rank(pl, hosts, k=20)
    np.testing.assert_array_equal(s1, s2)
    np.testing.assert_array_equal(d1, d2)


def test_bm25_sharded_matches_numpy_oracle():
    devs = _cpu8()
    rng = np.random.default_rng(3)
    n, t = 777, 6
    tf = rng.integers(0, 9, (n, t)).astype(np.float32)
    dl = rng.integers(40, 800, n).astype(np.int32)
    df = rng.integers(1, n, t).astype(np.int32)
    docids = np.arange(n, dtype=np.int32)
    mesh = make_mesh(n_doc=4, n_term=2, devices=devs)
    s, d = MeshBM25(mesh).topk(tf, dl, df, n, docids, k=15)
    ref = bm25_scores_np(tf, dl, df, n)
    order = np.argsort(-ref)[:15]
    assert set(d.tolist()) == set(order.tolist())
    np.testing.assert_allclose(np.sort(s)[::-1], np.sort(ref[order])[::-1],
                               rtol=1e-4)


def test_small_input_smaller_than_k():
    devs = _cpu8()
    pl, hosts = _random_postings(5, seed=4)
    mesh = make_mesh(n_doc=8, devices=devs)
    s, d = MeshRanker(mesh).rank(pl, hosts, k=10)
    assert len(s) == 5 and len(d) == 5
    assert set(d.tolist()) <= set(range(5))


def test_empty_postings():
    devs = _cpu8()
    mesh = make_mesh(n_doc=8, devices=devs)
    s, d = MeshRanker(mesh).rank(PostingsList.empty(), None, k=10)
    assert len(s) == 0 and len(d) == 0


# -- fused all-gather+top-k collective (ISSUE 12b) ---------------------------

def _gather_fns(mesh, k):
    """(legacy gather, fused collective) as jitted shard_map programs
    over the SAME local inputs."""
    import jax.numpy as jnp
    from functools import partial
    from jax import lax
    from jax.sharding import PartitionSpec as PS

    from yacy_search_server_tpu.parallel.mesh import (all_gather_topk,
                                                      tie_topk)

    def legacy(s, d):
        ls, li = lax.top_k(s, min(k, s.shape[0]))
        gs = lax.all_gather(ls, "doc", tiled=True)
        gd = lax.all_gather(d[li], "doc", tiled=True)
        ts, ti = lax.top_k(gs, min(k, gs.shape[0]))
        return ts, gd[ti]

    def fused(s, d):
        ls, ld = tie_topk(s, d, min(k, s.shape[0]))
        return all_gather_topk(ls, ld, "doc", k)

    mk = lambda body: jax.jit(jax.shard_map(     # noqa: E731
        body, mesh=mesh, in_specs=(PS("doc"), PS("doc")),
        out_specs=(PS(), PS()), check_vma=False))
    return mk(legacy), mk(fused)


def test_fused_collective_bit_identical_to_legacy_gather():
    """Satellite: local-top-k-then-gather replaces gather-then-top-k;
    on distinct scores the two fusions must be bit-identical (the tie
    cases, where the legacy path was layout-dependent, are pinned
    separately below)."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as PS
    devs = _cpu8()
    mesh = make_mesh(n_doc=8, devices=devs)
    rng = np.random.default_rng(5)
    n, k = 8 * 128, 10
    scores = rng.permutation(n).astype(np.int32)     # all distinct
    docids = np.arange(n, dtype=np.int32)
    sh1 = NamedSharding(mesh, PS("doc"))
    sa = jax.device_put(scores, sh1)
    da = jax.device_put(docids, sh1)
    legacy, fused = _gather_fns(mesh, k)
    ls, ld = legacy(sa, da)
    fs, fd = fused(sa, da)
    np.testing.assert_array_equal(np.asarray(ls), np.asarray(fs))
    np.testing.assert_array_equal(np.asarray(ld), np.asarray(fd))


def test_fused_collective_pins_cross_shard_tie_discipline():
    """Equal scores on DIFFERENT shards fuse as (score DESC, docid ASC)
    — checked against the numpy lexsort oracle; gather-position order
    (what the legacy merge produced) must not leak through."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as PS
    devs = _cpu8()
    mesh = make_mesh(n_doc=8, devices=devs)
    rng = np.random.default_rng(6)
    n, k = 8 * 128, 16
    # few distinct score values → ties everywhere, within and across
    # shards; docids SHUFFLED so positional order ≠ docid order
    scores = rng.integers(0, 5, n).astype(np.int32) * 1000
    docids = rng.permutation(n).astype(np.int32)
    sh1 = NamedSharding(mesh, PS("doc"))
    _legacy, fused = _gather_fns(mesh, k)
    fs, fd = fused(jax.device_put(scores, sh1),
                   jax.device_put(docids, sh1))
    fs, fd = np.asarray(fs), np.asarray(fd)
    # oracle: global exact two-key order over ALL rows.  The fused
    # collective only sees each shard's local top-k, but local
    # selection is tie-exact too, so the global top-k set matches.
    order = np.lexsort((docids, -scores))[:k]
    np.testing.assert_array_equal(fs, scores[order])
    np.testing.assert_array_equal(fd, docids[order])
    # the returned order itself satisfies the discipline
    assert all(fs[i] > fs[i + 1] or (fs[i] == fs[i + 1]
               and fd[i] < fd[i + 1]) for i in range(k - 1))


def test_tie_topk_matches_lexsort_oracle():
    from yacy_search_server_tpu.parallel.mesh import tie_topk
    rng = np.random.default_rng(8)
    for dtype in (np.int32, np.float32):
        s = rng.integers(0, 7, 100).astype(dtype)
        d = rng.permutation(100).astype(np.int32)
        ts, td = jax.jit(lambda a, b: tie_topk(a, b, 20))(s, d)
        order = np.lexsort((d, -s))[:20]
        np.testing.assert_array_equal(np.asarray(ts), s[order])
        np.testing.assert_array_equal(np.asarray(td), d[order])
