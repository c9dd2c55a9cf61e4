"""Batched exact stream scans (ISSUE 1 satellite — VERDICT r5 weak #1).

The r5 modifier mix's 104 exact filtered scans rode solo dispatches
while the pruned and join paths batched; `index.device.scanBatching`
routes them through the shared _QueryBatcher as one vmapped
_rank_scan_batch_kernel dispatch per (profile, language, k) group.
These tests pin bit-parity against the solo scan path and the
eligibility fences (RAM deltas and facet bitmaps stay solo).
"""

import threading

import numpy as np

from yacy_search_server_tpu.index import postings as P
from yacy_search_server_tpu.index.devstore import DeviceSegmentStore
from yacy_search_server_tpu.index.postings import PostingsList
from yacy_search_server_tpu.index.rwi import RWIIndex
from yacy_search_server_tpu.ops.ranking import RankingProfile
from yacy_search_server_tpu.utils.profiler import PROFILER

TERMS = [b"scanterm0AAA", b"scanterm1AAA"]


def _build(n=3000):
    idx = RWIIndex()
    rng = np.random.default_rng(7)
    for t, th in enumerate(TERMS):
        feats = rng.integers(0, 1000, (n, P.NF)).astype(np.int32)
        feats[:, P.F_FLAGS] = rng.integers(0, 2 ** 20, n)
        feats[:, P.F_DOMLENGTH] = rng.integers(0, 256, n)
        feats[:, P.F_LANGUAGE] = P.pack_language("en" if t == 0 else "de")
        idx.add_many(th, PostingsList(np.arange(n, dtype=np.int32), feats))
    idx.flush()
    return DeviceSegmentStore(idx)


def test_batched_scan_matches_solo_and_actually_batches():
    solo = _build()
    batched = _build()
    try:
        batched.enable_batching(max_batch=8, dispatchers=2, prewarm=False,
                                scan_batching=True)
        prof = RankingProfile()
        en = P.pack_language("en")
        filters = [
            {"lang_filter": en},                      # /language/ modifier
            {"from_days": 100, "to_days": 900},       # daterange
            {"lang_filter": en, "from_days": 50},
        ]
        # warm: first use compiles the batch-scan shape (prewarm covers
        # this in deployments; the watchdog withdraws cold queries and
        # serves them solo — still correct, not batched, and the
        # compile-window timeouts land in the stall bucket, so the
        # healthy-serving assertions below measure from post-warm state)
        for kw in filters:
            batched.rank_term(TERMS[0], prof, k=10, **kw)
        b = batched._batcher
        while not b._q.empty():        # let the compile dispatch drain
            import time
            time.sleep(0.05)
        stall0 = b.timeout_worker_stall
        exc0 = b.exceptions
        expected = {}
        for ti, th in enumerate(TERMS):
            for fi, kw in enumerate(filters):
                expected[(ti, fi)] = solo.rank_term(th, prof, k=10, **kw)
        assert solo.stream_scans == len(expected)

        results = {}
        lock = threading.Lock()

        def worker(ti, fi):
            out = batched.rank_term(TERMS[ti], prof, k=10, **filters[fi])
            with lock:
                results[(ti, fi)] = out

        ts = [threading.Thread(target=worker, args=key)
              for key in expected]
        for t in ts:
            t.start()
        for t in ts:
            t.join()

        for key, (es, ed, ec) in expected.items():
            gs, gd, gc = results[key]
            np.testing.assert_array_equal(np.asarray(es), np.asarray(gs))
            np.testing.assert_array_equal(np.asarray(ed), np.asarray(gd))
            assert ec == gc
        c = batched.counters()
        # served through the batcher's scan kernel, and healthily: once
        # the shape is warm no dispatch wedges (the stall cause bucket
        # must not move past the compile window)
        assert batched.stream_scans >= len(expected)
        assert c["batch_exceptions"] == exc0
        assert c["batch_timeout_worker_stall"] == stall0
        # the scan dispatches filed their silicon-accounting samples
        util = PROFILER.query_util()
        assert util["util_pct_p50"] > 0
        assert util["util_pct_p95"] >= util["util_pct_p50"]
        assert util["bound"] in ("memory", "compute")
        assert c["batch_timeouts"] == (c["batch_timeout_queue_full"]
                                       + c["batch_timeout_flush_deadline"]
                                       + c["batch_timeout_worker_stall"])
    finally:
        solo.close()
        batched.close()


def test_scan_batching_delta_stays_solo_and_correct():
    """A term with unflushed RAM postings is ineligible for the batched
    scan (its delta block has no shared batch shape) — the solo kernel
    must serve it, with the delta's rows included."""
    ds = _build()
    try:
        ds.enable_batching(max_batch=4, dispatchers=1, prewarm=False,
                           scan_batching=True)
        rng = np.random.default_rng(9)
        extra = rng.integers(0, 1000, (64, P.NF)).astype(np.int32)
        extra[:, P.F_LANGUAGE] = P.pack_language("en")
        ds.rwi.add_many(TERMS[0], PostingsList(
            np.arange(5000, 5064, dtype=np.int32), extra))
        scans0 = ds.stream_scans
        out = ds.rank_term(TERMS[0], RankingProfile(), k=10,
                           lang_filter=P.pack_language("en"))
        assert out is not None
        s, d, considered = out
        assert considered == 3064          # 3000 packed + 64 delta rows
        assert len(s) == 10
        # served by the SOLO scan (delta queries never enter the batch),
        # and the batcher never dispatched a scan kernel for it
        assert ds.stream_scans == scans0 + 1
        assert ds._batcher.dispatches == 0
    finally:
        ds.close()


def test_scan_batching_off_by_default():
    ds = _build()
    try:
        ds.enable_batching(max_batch=4, dispatchers=1, prewarm=False)
        assert ds._scan_batching is False
        out = ds.rank_term(TERMS[0], RankingProfile(), k=10,
                           lang_filter=P.pack_language("en"))
        assert out is not None
    finally:
        ds.close()
