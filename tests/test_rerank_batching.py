"""Batched hybrid dense rerank through the pipelined batcher (ISSUE 6).

The hybrid second stage is now a first-class devstore kernel family:
concurrent queries' rerank requests coalesce into one
`_rerank_fwd_batch_packed_kernel` MXU dispatch that gathers candidate
doc vectors from a device-resident forward index
(index/dense.DenseVectorStore.device_block) — no per-query host
`get_block` gather, one packed transfer each way. These tests pin:

- parity of the packed kernel against its CPU oracle over mixed batch
  sizes and RAGGED candidate counts (pad slots, pad lanes,
  out-of-coverage docids): same candidate set, per-docid scores within
  the dot-product's accumulation-order rounding (the oracle caveat
  dense_boost_topk_np states), and the pinned tie ordering;
- solo (rerankBatching=off) vs batched (on, concurrent threads) answers
  bit-identical — the `index.device.rerankBatching` contract;
- the pinned tie discipline (score DESC, then docid ASC) on every
  rerank path, so equal-scored candidates can never flap the top-k
  cache between bit-different answers (arxiv 1807.05798);
- hybrid top-k cache: hits bit-identical with ZERO device work,
  invalidated by an encoder swap, a vector write, and an arena-epoch
  bump — each through the key/epoch, never served stale;
- EXACT rerank counters for the new part kind under a 32-thread hammer
  (the same `_ms_lock`/`_lock` discipline as the other families).
"""

import threading

import numpy as np
import pytest

import jax

from yacy_search_server_tpu.index import postings as P
from yacy_search_server_tpu.index.dense import DenseVectorStore
from yacy_search_server_tpu.index.devstore import DeviceSegmentStore
from yacy_search_server_tpu.index.postings import PostingsList
from yacy_search_server_tpu.index.rwi import RWIIndex
from yacy_search_server_tpu.ops import dense as DN
from yacy_search_server_tpu.ops.ranking import RankingProfile
from yacy_search_server_tpu.utils import tracing

TH = b"rerankterm0A"


def _plist(rng, n, base=0):
    docids = np.arange(base, base + n, dtype=np.int32)
    feats = rng.integers(0, 1000, (n, P.NF)).astype(np.int32)
    feats[:, P.F_FLAGS] = rng.integers(0, 2 ** 20, n)
    feats[:, P.F_DOMLENGTH] = rng.integers(0, 256, n)
    feats[:, P.F_LANGUAGE] = P.pack_language("en")
    return PostingsList(docids, feats)


def _store(n=4000, n_vec=1024, batching=True, rerank_batching=True,
           max_batch=4):
    idx = RWIIndex()
    idx.add_many(TH, _plist(np.random.default_rng(1), n))
    idx.flush()
    ds = DeviceSegmentStore(idx)
    dense = DenseVectorStore(dim=DN.DIM)
    rng = np.random.default_rng(2)
    for i in range(0, n_vec, 2):        # half coverage: absent vectors
        dense.put(i, rng.standard_normal(DN.DIM).astype(np.float32))
    ds.attach_dense(dense)
    if batching:
        ds.enable_batching(max_batch=max_batch, dispatchers=2,
                           prewarm=False, rerank_batching=rerank_batching)
    return ds


def _assert_oracle_close(ks, kd, es, ed, tol=64):
    """Kernel vs CPU oracle: identical candidate set, per-docid scores
    within the bf16-dot accumulation-order budget (`tol` cardinal units
    against ~2^28-scale boosted scores, ~1e-7 relative), and the kernel's ordering consistent
    with its OWN scores (the oracle's order can legally differ where
    near-equal scores land on the other side of a rounding unit)."""
    assert set(np.asarray(kd).tolist()) == set(np.asarray(ed).tolist())
    kmap = dict(zip(np.asarray(kd).tolist(), np.asarray(ks).tolist()))
    emap = dict(zip(np.asarray(ed).tolist(), np.asarray(es).tolist()))
    for docid, sc in kmap.items():
        assert abs(sc - emap[docid]) <= tol, (docid, sc, emap[docid])


def _assert_tie_discipline(scores, docids):
    """(score DESC, then docid ASC) — strictly, over the whole prefix."""
    s = np.asarray(scores, np.int64)
    d = np.asarray(docids, np.int64)
    assert np.all(s[:-1] >= s[1:]), "scores not descending"
    same = s[:-1] == s[1:]
    assert np.all(d[:-1][same] < d[1:][same]), \
        "equal scores not ordered by ascending docid"


# -- packed kernel vs CPU oracle ---------------------------------------------

@pytest.mark.parametrize("bs,ns", (
    (4, (3, 16, 13, 16)),               # ragged within one nb=16 bucket
    (8, (100, 128, 1, 77, 128, 5, 64, 99)),   # nb=128, very ragged
    (2, (500, 333)),                    # nb=512
))
def test_packed_kernel_matches_oracle_ragged(bs, ns):
    rng = np.random.default_rng(3)
    cap = 1 << 10
    fwd = rng.standard_normal((cap, DN.DIM)).astype(np.float16)
    nb = max(DN.rerank_bucket(n) for n in ns)
    qi = np.zeros((bs, 2 + 2 * nb + DN.DIM), np.int32)
    slots = []
    for i, n in enumerate(ns):
        q = rng.standard_normal(DN.DIM).astype(np.float32)
        sp = rng.integers(0, 1 << 20, n).astype(np.int32)
        # duplicate scores force tie decisions; docids beyond cap are
        # out of coverage (zero boost, never dropped)
        sp[: n // 3] = sp[0]
        dd = rng.choice(cap + 64, size=n, replace=False).astype(np.int32)
        qi[i] = DN.pack_rerank_row(q, sp, dd, 0.7, nb)
        slots.append((q, sp, dd))
    out = np.asarray(DN._rerank_fwd_batch_packed_kernel(
        jax.device_put(fwd), qi, nb=nb, bs=bs))
    for i, (q, sp, dd) in enumerate(slots):
        n = len(dd)
        ks, kd = out[i, :n], out[i, nb:nb + n]
        es, ed = DN.rerank_fwd_np(q, fwd, sp, dd, 0.7)
        _assert_oracle_close(ks, kd, es, ed)
        _assert_tie_discipline(ks, kd)
        # pad lanes stay strictly behind every real candidate
        assert np.all(out[i, n:nb] < ks.min())


def test_out_of_coverage_keeps_sparse_score():
    """A candidate with no stored vector (docid beyond the forward
    index, or a zero row) keeps its sparse score with zero boost —
    vector absence must never drop a sparse result."""
    fwd = np.random.default_rng(4).standard_normal(
        (256, DN.DIM)).astype(np.float16)
    q = np.ones(DN.DIM, np.float32)
    sp = np.array([1000, 2000, 3000], np.int32)
    dd = np.array([5000, -1, 300], np.int32)    # all outside [0, 256)
    nb = DN.rerank_bucket(3)
    qi = DN.pack_rerank_row(q, sp, dd, 0.9, nb)[None, :]
    out = np.asarray(DN._rerank_fwd_batch_packed_kernel(
        jax.device_put(fwd), qi, nb=nb, bs=1))
    np.testing.assert_array_equal(out[0, :3], [3000, 2000, 1000])
    np.testing.assert_array_equal(out[0, nb:nb + 3], [300, -1, 5000])


# -- devstore: solo vs batched parity, tie discipline ------------------------

def _queries(ds, n_q, rng):
    """n_q (qvec, sparse, docids) rerank inputs over the store's docs."""
    qs = []
    for _ in range(n_q):
        n = int(rng.integers(5, 200))
        dd = rng.choice(2048, size=n, replace=False).astype(np.int32)
        sp = rng.integers(0, 1 << 20, n).astype(np.int32)
        sp[: n // 4] = sp[0] if n >= 4 else sp[0]   # forced ties
        qv = rng.standard_normal(DN.DIM).astype(np.float32)
        qs.append((qv, sp, dd))
    return qs


def test_solo_vs_batched_bit_identical_and_oracle():
    solo = _store(rerank_batching=False)
    batched = _store(rerank_batching=True)
    try:
        rng = np.random.default_rng(5)
        qs = _queries(solo, 12, rng)
        # warm the compile shapes through the solo path first so the
        # batched hammer below never times out inside a compile window
        for qv, sp, dd in qs:
            assert solo.rerank_boost(qv, sp, dd, 0.5) is not None
        for qv, sp, dd in qs[:1]:
            batched.rerank_boost(qv, sp, dd, 0.5)

        expected = [solo.rerank_boost(qv, sp, dd, 0.5) for qv, sp, dd
                    in qs]
        got = [None] * len(qs)

        def worker(i):
            qv, sp, dd = qs[i]
            got[i] = batched.rerank_boost(qv, sp, dd, 0.5)

        ts = [threading.Thread(target=worker, args=(i,))
              for i in range(len(qs))]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        fwd = np.asarray(batched._dense.device_block(
            batched.arena.device)[0])
        for i, (es, ed) in enumerate(expected):
            gs, gd = got[i]
            np.testing.assert_array_equal(np.asarray(es), np.asarray(gs))
            np.testing.assert_array_equal(np.asarray(ed), np.asarray(gd))
            _assert_tie_discipline(gs, gd)
            qv, sp, dd = qs[i]
            os_, od = DN.rerank_fwd_np(qv, fwd, sp, dd, 0.5)
            _assert_oracle_close(gs, gd, os_, od)
        cs, cb = solo.counters(), batched.counters()
        assert cs["rerank_queries"] == 2 * len(qs)  # warm + measured
        assert cs["rerank_dispatches"] == cs["rerank_queries"]  # all solo
        assert cb["rerank_queries"] == len(qs) + 1
        assert cb["rerank_fallbacks"] == 0
    finally:
        solo.close()
        batched.close()


def test_rerank_rides_the_batcher_with_trace_spans():
    """A traced rerank query carries the issue/device/fetch child spans
    (the same decomposition every other kernel family emits)."""
    ds = _store()
    try:
        rng = np.random.default_rng(6)
        qv, sp, dd = _queries(ds, 1, rng)[0]
        assert ds.rerank_boost(qv, sp, dd, 0.5) is not None   # warm
        tracing.clear()
        with tracing.trace("rerank-query") as r:
            tid = r.ctx[0]
            assert ds.rerank_boost(qv, sp, dd, 0.5) is not None
        rec = tracing.get_trace(tid)
        names = {s.name for s in rec.spans}
        assert "devstore.batch" in names, names
        for stage in ("kernel.issue", "kernel.device", "kernel.fetch"):
            assert stage in names, names
    finally:
        ds.close()


def test_rerank_counters_exact_under_32_thread_hammer():
    """The new part kind keeps the exact-counter contract: 32 threads x
    4 reranks each => rerank_queries is EXACTLY 128, every query either
    batched or solo-after-timeout (dispatches <= queries), none lost."""
    ds = _store(max_batch=8)
    try:
        rng = np.random.default_rng(7)
        qv0, sp0, dd0 = _queries(ds, 1, rng)[0]
        assert ds.rerank_boost(qv0, sp0, dd0, 0.5) is not None  # warm
        threads, per = 32, 4
        qs = _queries(ds, threads, np.random.default_rng(8))
        errs = []

        def worker(t):
            qv, sp, dd = qs[t]
            for _ in range(per):
                try:
                    assert ds.rerank_boost(qv, sp, dd, 0.5) is not None
                except Exception as e:      # noqa: BLE001
                    errs.append(e)

        ts = [threading.Thread(target=worker, args=(t,))
              for t in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs, errs
        c = ds.counters()
        assert c["rerank_queries"] == threads * per + 1
        # a watchdog timeout serves the query solo while its late
        # batched dispatch still lands (the documented bounded cost of
        # never hanging) — so dispatches may exceed queries by at most
        # the timeout count, never by silent duplication
        assert 1 <= c["rerank_dispatches"] \
            <= c["rerank_queries"] + c["batch_timeouts"]
        assert c["rerank_fallbacks"] == 0
        assert c["batch_exceptions"] == 0
    finally:
        ds.close()


def test_no_forward_index_is_a_counted_fallback():
    """Candidate sets past RERANK_MAX_N (and stores with no attached
    dense store) decline with a counted fallback, never a wrong
    answer — the caller keeps the host-gather legacy path."""
    ds = _store(batching=False)
    try:
        rng = np.random.default_rng(9)
        n = DN.RERANK_MAX_N + 1
        dd = np.arange(n, dtype=np.int32)
        sp = rng.integers(0, 1 << 20, n).astype(np.int32)
        qv = rng.standard_normal(DN.DIM).astype(np.float32)
        assert ds.rerank_boost(qv, sp, dd, 0.5) is None
        assert ds.counters()["rerank_fallbacks"] == 1
        ds._dense = None
        assert ds.rerank_boost(qv, sp[:10], dd[:10], 0.5) is None
    finally:
        ds.close()


# -- hybrid top-k cache ------------------------------------------------------

def test_hybrid_cache_hit_bit_identical_zero_device_work():
    ds = _store()
    try:
        prof = RankingProfile()
        rng = np.random.default_rng(10)
        qv, sp, dd = _queries(ds, 1, rng)[0]
        s, d = ds.rerank_boost(qv, sp, dd, 0.5)
        epoch0 = ds.arena_epoch
        ds.hybrid_cache_put(TH, prof, "en", 80, 0.5, epoch0, s, d,
                            len(dd))
        c0 = ds.counters()
        got = ds.hybrid_cache_get(TH, prof, "en", 80, 0.5)
        c1 = ds.counters()
        assert got is not None
        hs, hd, hc = got
        np.testing.assert_array_equal(np.asarray(hs), np.asarray(s))
        np.testing.assert_array_equal(np.asarray(hd), np.asarray(d))
        assert hc == len(dd)
        assert c1["rerank_cache_hits"] == c0["rerank_cache_hits"] + 1
        # zero device work on the hit
        assert c1["device_round_trips"] == c0["device_round_trips"]
        assert c1["rerank_dispatches"] == c0["rerank_dispatches"]
        # a different alpha is a different key: miss, not a wrong hit
        assert ds.hybrid_cache_get(TH, prof, "en", 80, 0.9) is None
        # a different k is a different answer (the rerank input is the
        # sparse [:k] trim): exact-k keying, no kk-bucket sharing
        assert ds.hybrid_cache_get(TH, prof, "en", 79, 0.5) is None
    finally:
        ds.close()


def test_hybrid_cache_invalidated_by_encoder_swap(monkeypatch):
    ds = _store()
    try:
        prof = RankingProfile()
        ds.hybrid_cache_put(TH, prof, "en", 80, 0.5, ds.arena_epoch,
                            np.arange(5, dtype=np.int32),
                            np.arange(5, dtype=np.int32), 5)
        assert ds.hybrid_cache_get(TH, prof, "en", 80, 0.5) is not None
        monkeypatch.setattr(DN, "ENCODER_VERSION",
                            DN.ENCODER_VERSION + 1)
        assert ds.hybrid_cache_get(TH, prof, "en", 80, 0.5) is None
    finally:
        ds.close()


def test_hybrid_cache_invalidated_by_vector_write_and_epoch_bump():
    ds = _store()
    try:
        prof = RankingProfile()

        def put_entry():
            ds.hybrid_cache_put(TH, prof, "en", 80, 0.5, ds.arena_epoch,
                                np.arange(5, dtype=np.int32),
                                np.arange(5, dtype=np.int32), 5)

        put_entry()
        assert ds.hybrid_cache_get(TH, prof, "en", 80, 0.5) is not None
        # ANY vector write moves the content version -> key miss (the
        # cached blend read the old vector)
        ds._dense.put(3, np.ones(DN.DIM, np.float32))
        assert ds.hybrid_cache_get(TH, prof, "en", 80, 0.5) is None
        # arena-epoch bump (flush of new postings) -> stale, never served
        put_entry()
        assert ds.hybrid_cache_get(TH, prof, "en", 80, 0.5) is not None
        ds.rwi.add_many(TH, _plist(np.random.default_rng(11), 300,
                                   base=100_000))
        c0 = ds.counters()
        # unflushed RAM delta: the cache DECLINES (neither hit nor stale)
        assert ds.hybrid_cache_get(TH, prof, "en", 80, 0.5) is None
        assert ds.counters()["rank_cache_stale"] == c0["rank_cache_stale"]
        ds.rwi.flush()
        assert ds.hybrid_cache_get(TH, prof, "en", 80, 0.5) is None
        assert ds.counters()["rank_cache_stale"] > c0["rank_cache_stale"]
    finally:
        ds.close()


# -- the serving path end to end ---------------------------------------------

def test_searchevent_hybrid_served_batched_and_cached(tmp_path):
    """A hybrid SearchEvent on a device-serving segment reranks through
    the devstore kernel family (no host-gather fallback), and an
    identical repeat serves the FULL two-stage answer from the hybrid
    cache with zero device work, bit-identically."""
    from yacy_search_server_tpu.index.segment import Segment
    from yacy_search_server_tpu.search.query import QueryParams
    from yacy_search_server_tpu.search.searchevent import (
        TOPK_OVERSAMPLE, SearchEvent)
    from yacy_search_server_tpu.utils.hashes import word2hash

    seg = Segment(max_ram_postings=10 ** 9)
    th = word2hash("hybridserve")
    seg.rwi.ingest_run({th: _plist(np.random.default_rng(12), 4096)})
    rng = np.random.default_rng(13)
    for i in range(0, 1024, 2):
        seg.dense.put(i, rng.standard_normal(DN.DIM).astype(np.float32))
    ds = seg.enable_device_serving()
    ds.small_rank_n = 0          # small corpus still takes the device path
    ds.enable_batching(max_batch=4, dispatchers=1, prewarm=False)
    try:
        def run():
            q = QueryParams.parse("hybridserve")
            q.hybrid = True
            ev = SearchEvent(q, seg)
            return ev

        c0 = ds.counters()
        run()
        c1 = ds.counters()
        assert c1["rerank_queries"] == c0["rerank_queries"] + 1
        assert c1["rerank_fallbacks"] == c0["rerank_fallbacks"]
        k_need = 10 * TOPK_OVERSAMPLE
        cached = ds.hybrid_cache_get(th, QueryParams.parse(
            "hybridserve").profile, "en", k_need, 0.5)
        assert cached is not None, "the computed hybrid answer was cached"
        _assert_tie_discipline(cached[0], cached[1])

        run()                       # identical repeat: full-answer hit
        c2 = ds.counters()
        assert c2["rerank_cache_hits"] >= c1["rerank_cache_hits"] + 1
        assert c2["rerank_dispatches"] == c1["rerank_dispatches"]
        assert c2["device_round_trips"] == c1["device_round_trips"]

        # cold recompute parity: clear and rerun -> the re-cached answer
        # is bit-identical to the first one
        ds._topk_cache.clear()
        run()
        re = ds.hybrid_cache_get(th, QueryParams.parse(
            "hybridserve").profile, "en", k_need, 0.5)
        assert re is not None
        np.testing.assert_array_equal(np.asarray(re[0]),
                                      np.asarray(cached[0]))
        np.testing.assert_array_equal(np.asarray(re[1]),
                                      np.asarray(cached[1]))

        # a vector write invalidates the cached hybrid answer: the next
        # event recomputes (rerank runs again)
        seg.dense.put(2, np.ones(DN.DIM, np.float32))
        c3 = ds.counters()
        run()
        c4 = ds.counters()
        assert c4["rerank_queries"] == c3["rerank_queries"] + 1
    finally:
        seg.close()


def test_host_fallback_tie_discipline(tmp_path):
    """The legacy host-gather path (store without a device forward
    index) re-asserts the SAME tie discipline as the kernel paths: equal
    final scores order by ascending docid, not by sparse rank."""
    from yacy_search_server_tpu.index.segment import Segment
    from yacy_search_server_tpu.search.query import QueryParams
    from yacy_search_server_tpu.search.searchevent import SearchEvent

    seg = Segment(max_ram_postings=10 ** 9)
    try:
        q = QueryParams.parse("tietest")
        q.hybrid = True
        q.hybrid_alpha = 0.5
        ev = SearchEvent.__new__(SearchEvent)
        ev.query = q
        ev.segment = seg
        # no doc vectors stored: every candidate is out of coverage,
        # boost is 0, and the duplicated sparse scores are pure ties
        scores = np.array([900, 500, 900, 500, 900], np.int64)
        docids = np.array([40, 31, 7, 22, 19], np.int64)
        s, d = ev._dense_rerank(scores, docids)
        np.testing.assert_array_equal(s, [900, 900, 900, 500, 500])
        np.testing.assert_array_equal(d, [7, 19, 40, 22, 31])
        _assert_tie_discipline(s, d)
    finally:
        seg.close()
