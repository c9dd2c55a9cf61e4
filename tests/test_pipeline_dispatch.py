"""Pipelined dispatch (ISSUE 3 tentpole) + batcher counter exactness.

The batcher's dispatcher threads now ISSUE kernel calls asynchronously
and a completer pool performs the blocking fetch — these tests pin:

- bit-parity of the pipelined path against the host oracle, and against
  the same batcher with pipelining off (`index.device.pipeline`);
- the issue/device/fetch span decomposition on traced queries;
- counter EXACTNESS under a 32-thread hammer (the satellite fix: the
  batcher counters were bare `+=` from many threads — now under
  `_ms_lock`, so `counters()` totals must be exact, not approximate);
- `_split_parts` fragmentation (plain / scan-group / join-family
  isolation and the per-family batch cap), previously untested.
"""

import threading
import time

import numpy as np

from yacy_search_server_tpu.index import postings as P
from yacy_search_server_tpu.index.devstore import (DeviceSegmentStore,
                                                   _QueryBatcher)
from yacy_search_server_tpu.index.postings import PostingsList
from yacy_search_server_tpu.index.rwi import RWIIndex
from yacy_search_server_tpu.ops.ranking import CardinalRanker, RankingProfile
from yacy_search_server_tpu.utils import tracing

TH = b"pipetermAAAA"


def _built_store(n=30_000):
    idx = RWIIndex()
    rng = np.random.default_rng(11)
    docids = np.arange(n, dtype=np.int32)
    feats = rng.integers(0, 1000, (n, P.NF)).astype(np.int32)
    feats[:, P.F_FLAGS] = rng.integers(0, 2 ** 20, n)
    feats[:, P.F_DOMLENGTH] = rng.integers(0, 256, n)
    feats[:, P.F_LANGUAGE] = P.pack_language("en")
    idx.add_many(TH, PostingsList(docids, feats))
    idx.flush()
    return DeviceSegmentStore(idx)


def _oracle(idx, k):
    return CardinalRanker(RankingProfile(), "en").rank(idx.get(TH), None,
                                                       k=k)


def test_pipelined_batch_parity_and_span_decomposition():
    """A batched query through the pipelined issue->complete path is
    bit-identical to the host oracle, and a traced query carries the
    issue/device/fetch child spans the waterfall renders."""
    ds = _built_store()
    try:
        ds.enable_batching(max_batch=4, dispatchers=2, prewarm=False)
        assert ds._batcher.pipeline is True
        out = ds.rank_term(TH, RankingProfile(), k=10)
        assert out is not None
        ws, wd = _oracle(ds.rwi, 10)
        np.testing.assert_array_equal(np.asarray(out[0]), ws)
        np.testing.assert_array_equal(np.asarray(out[1]), wd)
        c = ds.counters()
        assert c["batch_dispatches"] >= 1
        assert c["device_round_trips"] >= 1

        # traced repeat rides the batcher again (cache cleared) and the
        # submitter re-emits the completer-stamped decomposition
        ds._topk_cache.clear()
        tracing.clear()
        with tracing.trace("pipe-query") as r:
            tid = r.ctx[0]
            assert ds.rank_term(TH, RankingProfile(), k=10) is not None
        rec = tracing.get_trace(tid)
        names = {s.name for s in rec.spans}
        for stage in ("kernel.issue", "kernel.device", "kernel.fetch"):
            assert stage in names, names
    finally:
        ds.close()


def test_pipeline_off_is_bit_identical():
    """`index.device.pipeline` off: pipeline=False completes inline (the
    pre-pipeline behavior) with bit-identical results."""
    ds = _built_store()
    try:
        ds.enable_batching(max_batch=4, dispatchers=1, prewarm=False,
                           pipeline=False)
        ds._topk_cache.enabled = False
        out1 = ds.rank_term(TH, RankingProfile(), k=10)
        ds._batcher.pipeline = True
        out2 = ds.rank_term(TH, RankingProfile(), k=10)
        np.testing.assert_array_equal(np.asarray(out1[0]),
                                      np.asarray(out2[0]))
        np.testing.assert_array_equal(np.asarray(out1[1]),
                                      np.asarray(out2[1]))
    finally:
        ds.close()


def test_counters_exact_under_32_thread_hammer():
    """The satellite contract: hammer `submit` from 32 threads and the
    batcher's counters() totals are EXACT — `dispatches` equals the
    number of _dispatch calls, and the timeout total always equals the
    sum of its cause buckets."""
    ds = _built_store(n=40_000)
    try:
        ds.enable_batching(max_batch=8, dispatchers=4, prewarm=False)
        ds._topk_cache.enabled = False    # hammer the DISPATCH path
        assert ds.rank_term(TH, RankingProfile(), k=10) is not None
        b = ds._batcher
        calls = []
        lk = threading.Lock()
        orig = b._dispatch

        def counting(batch):
            with lk:
                calls.append(len(batch))
            orig(batch)

        b._dispatch = counting
        with b._ms_lock:
            d0 = b.dispatches
        threads, per = 32, 4

        def worker():
            for _ in range(per):
                assert ds.rank_term(TH, RankingProfile(), k=10) \
                    is not None

        ts = [threading.Thread(target=worker) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        # dispatchers increment AFTER issuing; give the tail a moment
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            with b._ms_lock:
                if b.dispatches - d0 == len(calls):
                    break
            time.sleep(0.02)
        with b._ms_lock:
            assert b.dispatches - d0 == len(calls), \
                (b.dispatches - d0, len(calls))
        c = ds.counters()
        assert c["batch_exceptions"] == 0
        assert c["batch_timeouts"] == (c["batch_timeout_queue_full"]
                                       + c["batch_timeout_flush_deadline"]
                                       + c["batch_timeout_worker_stall"])
    finally:
        ds.close()


def test_exception_counter_exact_under_hammer():
    """Every raising dispatch counts exactly once, even with 32
    submitters racing the increment."""
    ds = _built_store()
    try:
        ds.enable_batching(max_batch=8, dispatchers=4, prewarm=False)
        ds._topk_cache.enabled = False
        assert ds.rank_term(TH, RankingProfile(), k=10) is not None
        b = ds._batcher
        calls = []
        lk = threading.Lock()

        def boom(batch):
            with lk:
                calls.append(len(batch))
            raise RuntimeError("injected dispatch failure")

        b._dispatch = boom
        with b._ms_lock:
            e0 = b.exceptions

        def worker():
            for _ in range(2):
                assert ds.rank_term(TH, RankingProfile(), k=10) \
                    is not None    # answered by the solo retry

        ts = [threading.Thread(target=worker) for _ in range(32)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            with b._ms_lock:
                if b.exceptions - e0 == len(calls):
                    break
            time.sleep(0.02)
        with b._ms_lock:
            assert b.exceptions - e0 == len(calls), \
                (b.exceptions - e0, len(calls))
    finally:
        ds.close()


# -- _split_parts (satellite: previously untested fragmentation) -----------

def _bare_batcher(max_batch=16) -> _QueryBatcher:
    """A _QueryBatcher shell for the pure _split_parts logic — no
    threads, no store."""
    b = _QueryBatcher.__new__(_QueryBatcher)
    b.max_batch = max_batch
    return b


def _item(kind=None, statics=None, joincap=None, kk=16, lang="en",
          prof=None):
    it = {"profile": prof or RankingProfile(), "lang": lang, "kk": kk}
    if kind is not None:
        it["kind"] = kind
    if statics is not None:
        it["statics"] = statics
    if joincap is not None:
        it["joincap"] = joincap
    return it


def test_split_parts_mixed_batch_family_isolation_and_caps():
    """A mixed plain + scan + two-join-family batch splits into: one
    plain part, one scan group per (profile, lang, k), and one part per
    join family CHUNK (family A: 9 items at cap 4 -> 4+4+1)."""
    b = _bare_batcher()
    plain = [_item() for _ in range(3)]
    scans16 = [_item(kind="scan", kk=16) for _ in range(2)]
    scans32 = [_item(kind="scan", kk=32)]
    statA = (16, 1, 0, 1024, (256,), (), (False,), ())
    statB = (16, 2, 0, 2048, (256, 256), (), (True, True), ())
    famA = [_item(kind="join", statics=statA, joincap=4)
            for _ in range(9)]
    famB = [_item(kind="join", statics=statB, joincap=4)
            for _ in range(2)]
    batch = plain + scans16 + scans32 + famA + famB
    parts = b._split_parts(batch)

    # plain part first, intact
    assert parts[0] == plain
    # scan groups: one per (profile, lang, kk) key
    scan_parts = [p for p in parts
                  if p and p[0].get("kind") == "scan"]
    assert len(scan_parts) == 2
    assert sorted(len(p) for p in scan_parts) == [1, 2]
    # every part is homogeneous: one kind, one join family
    for p in parts:
        kinds = {it.get("kind") for it in p}
        assert len(kinds) == 1
        fams = {it["statics"] for it in p if it.get("kind") == "join"}
        assert len(fams) <= 1
    # family A chunks respect the per-family cap (4, 4, 1); B is one part
    a_parts = [p for p in parts
               if p and p[0].get("kind") == "join"
               and p[0]["statics"] == statA]
    assert sorted(len(p) for p in a_parts) == [1, 4, 4]
    b_parts = [p for p in parts
               if p and p[0].get("kind") == "join"
               and p[0]["statics"] == statB]
    assert [len(p) for p in b_parts] == [2]
    # nothing lost, nothing duplicated
    assert sum(len(p) for p in parts) == len(batch)


def test_split_parts_plain_only_single_part():
    b = _bare_batcher()
    batch = [_item() for _ in range(5)]
    assert b._split_parts(batch) == [batch]
