"""Device-side conjunctive join over placed spans (SURVEY §7.1:
'conjunctive join becomes sorted-id intersection on device').

Oracle parity against the host join path (segment.join_constructive +
CardinalRanker) on randomized corpora: multi-term conjunction, exclusion,
tombstones, constraint filters, and the SearchEvent end-to-end wiring.
"""

import numpy as np
import pytest

from yacy_search_server_tpu.index import postings as P
from yacy_search_server_tpu.index.postings import PostingsList
from yacy_search_server_tpu.index.segment import Segment
from yacy_search_server_tpu.ops.ranking import CardinalRanker, RankingProfile
from yacy_search_server_tpu.utils.hashes import word2hash


def _plist(rng, n, id_pool):
    docids = np.sort(rng.choice(id_pool, n, replace=False)).astype(np.int32)
    feats = np.zeros((n, P.NF), np.int32)
    feats[:, P.F_HITCOUNT] = rng.integers(1, 60, n)
    feats[:, P.F_WORDS_IN_TEXT] = rng.integers(50, 3000, n)
    feats[:, P.F_LASTMOD] = rng.integers(18000, 21000, n)
    feats[:, P.F_POSINTEXT] = rng.integers(1, 4000, n)
    feats[:, P.F_WORDS_IN_TITLE] = rng.integers(0, 10, n)
    feats[:, P.F_LANGUAGE] = np.where(
        rng.random(n) < 0.7, P.pack_language("en"), P.pack_language("de"))
    feats[:, P.F_FLAGS] = rng.integers(0, 2**26, n)
    return PostingsList(docids, feats)


@pytest.fixture()
def seg3():
    """Three overlapping terms in one frozen, device-placed run."""
    seg = Segment(max_ram_postings=10)
    rng = np.random.default_rng(3)
    pool = np.arange(60_000)
    seg.rwi.ingest_run({
        word2hash("aa"): _plist(rng, 20_000, pool),
        word2hash("bb"): _plist(rng, 9_000, pool),
        word2hash("cc"): _plist(rng, 5_000, pool),
    })
    seg.enable_device_serving()
    yield seg
    seg.close()


def _host_oracle(seg, inc, exc, k=50, profile=None):
    joined = seg.term_search(include_hashes=inc, exclude_hashes=exc)
    if len(joined) == 0:
        return np.empty(0, np.int64), np.empty(0, np.int32)
    hs, hd = CardinalRanker(profile or RankingProfile()).rank(joined, k=k)
    return np.asarray(hs, np.int64), np.asarray(hd)


def _assert_join_matches(seg, inc, exc, k=50, **kw):
    out = seg.devstore.rank_join(inc, exc, RankingProfile(), "en", k=k,
                                 **kw)
    assert out is not None, f"unexpected fallback ({seg.devstore.fallbacks})"
    s, d, _considered = out
    hs, hd = _host_oracle(seg, inc, exc, k=k)
    np.testing.assert_array_equal(np.asarray(d)[:len(hd)], hd)
    np.testing.assert_array_equal(np.asarray(s, np.int64)[:len(hs)], hs)
    return out


def test_two_term_parity(seg3):
    _assert_join_matches(seg3, [word2hash("aa"), word2hash("bb")], [])


def test_three_term_parity(seg3):
    _assert_join_matches(
        seg3, [word2hash("aa"), word2hash("bb"), word2hash("cc")], [])


def test_exclusion_parity(seg3):
    _assert_join_matches(seg3, [word2hash("aa"), word2hash("bb")],
                         [word2hash("cc")])


def test_join_with_tombstones(seg3):
    # tombstone a slice of docids that appear in the join
    joined = seg3.term_search(include_hashes=[word2hash("aa"),
                                              word2hash("bb")])
    victims = joined.docids[:40]
    for docid in victims.tolist():
        seg3.rwi.delete_doc(int(docid))
    out = _assert_join_matches(seg3, [word2hash("aa"), word2hash("bb")], [])
    s, d, _c = out
    assert not set(victims.tolist()) & set(np.asarray(d).tolist())


def test_join_language_filter(seg3):
    inc = [word2hash("aa"), word2hash("bb")]
    out = seg3.devstore.rank_join(
        inc, [], RankingProfile(), "en", k=50,
        lang_filter=P.pack_language("de"))
    s, d, _c = out
    # every hit's rare-term row is German (host recheck)
    joined = seg3.term_search(include_hashes=inc)
    langmap = dict(zip(joined.docids.tolist(),
                       joined.feats[:, P.F_LANGUAGE].tolist()))
    for docid in np.asarray(d).tolist():
        assert langmap[docid] == P.pack_language("de")


def test_empty_intersection(seg3):
    seg = seg3
    rng = np.random.default_rng(9)
    # a term over a disjoint docid range: conjunction is empty
    seg.rwi.ingest_run({word2hash("zz"): _plist(rng, 6_000,
                                                np.arange(10**6, 10**6 + 50_000))})
    out = seg.devstore.rank_join([word2hash("aa"), word2hash("zz")], [],
                                 RankingProfile(), "en", k=20)
    s, d, _c = out
    assert len(d) == 0


def test_fallback_on_unpacked_term(seg3):
    # a term living only in the RAM buffer is not joinable on device
    seg3.rwi.add(word2hash("fresh"), 7,
                 np.zeros(P.NF, np.int32))
    out = seg3.devstore.rank_join([word2hash("aa"), word2hash("fresh")],
                                  [], RankingProfile(), "en", k=10)
    assert out is None


def test_searchevent_uses_device_join(monkeypatch, seg3):
    from yacy_search_server_tpu.ops import ranking as mod
    from yacy_search_server_tpu.search.query import QueryParams
    from yacy_search_server_tpu.search.searchevent import SearchEvent
    monkeypatch.setattr(mod, "SMALL_RANK_N", 0)
    served0 = seg3.devstore.queries_served
    q = QueryParams.parse("x")          # build then override the goal
    q.goal._include_hashes_override = [word2hash("aa"), word2hash("bb")]
    q.goal._exclude_hashes_override = [word2hash("cc")]
    ev = SearchEvent(q, seg3)
    assert seg3.devstore.queries_served == served0 + 1
    # page scores match the host oracle's top scores
    hs, hd = _host_oracle(seg3, q.goal.include_hashes,
                          q.goal.exclude_hashes, k=30)
    pending = dict((docid, score)
                   for score, docid in ev._pending)
    for docid, score in zip(hd[:10].tolist(), hs[:10].tolist()):
        # entries either drained already or still pending with the score
        assert pending.get(docid, score) == score


def test_single_include_with_exclusion(seg3):
    """1-include + exclusion is a served device shape (review fix)."""
    out = _assert_join_matches(seg3, [word2hash("aa")], [word2hash("cc")])
    assert out is not None


def test_plain_single_term_not_joined(seg3):
    assert seg3.devstore.rank_join([word2hash("aa")], [],
                                   RankingProfile(), "en", k=10) is None


def test_batched_joins_parity_under_concurrency(seg3):
    """Concurrent conjunctions coalesce into lax.map batches (VERDICT r2
    weak #2) and return exactly the solo kernel's results."""
    import threading

    ds = seg3.devstore
    inc = [word2hash("aa"), word2hash("bb")]
    exc = [word2hash("cc")]
    prof = RankingProfile()
    solo = ds.rank_join(inc, exc, prof, "en", k=25)
    assert solo is not None
    ds.enable_batching(max_batch=8)
    served0 = ds.join_served
    results = [None] * 12

    def worker(i):
        results[i] = ds.rank_join(inc, exc, prof, "en", k=25)

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(12)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    for out in results:
        assert out is not None
        np.testing.assert_array_equal(np.asarray(out[1]),
                                      np.asarray(solo[1]))
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      np.asarray(solo[0]))
    assert ds.join_served - served0 == 12


def test_multispan_fallback_requests_merge():
    """A conjunction over a term split across runs falls back AND flags
    merge_wanted; after the merge the device join serves it."""
    seg = Segment(max_ram_postings=10)
    rng = np.random.default_rng(9)
    pool = np.arange(40_000)
    # same term frozen twice -> two spans
    seg.rwi.ingest_run({word2hash("aa"): _plist(rng, 4_000, pool[:20_000]),
                        word2hash("bb"): _plist(rng, 3_000, pool)})
    seg.rwi.ingest_run({word2hash("aa"): _plist(rng, 4_000, pool[20_000:])})
    seg.enable_device_serving()
    ds = seg.devstore
    try:
        assert ds.rank_join([word2hash("aa"), word2hash("bb")], [],
                            RankingProfile(), "en", k=10) is None
        assert ds.merge_wanted and ds.join_fallbacks >= 1
        assert seg.rwi.merge_runs(max_runs=1)
        ds.merge_wanted = False
        out = ds.rank_join([word2hash("aa"), word2hash("bb")], [],
                           RankingProfile(), "en", k=10)
        assert out is not None and ds.join_served >= 1
    finally:
        seg.close()


# -- join-bitmap membership (r5: VERDICT r4 #1) ---------------------------
#
# Terms at/above DeviceSegmentStore.JOIN_BITMAP_MIN rows get a docid
# bitmap + rank prefix at pack time; membership against them is 2
# gathers/lane (vmappable) instead of an (r+m) sort. These fixtures lower
# the threshold so test-sized corpora exercise every mode combination,
# asserting bit-parity with the host oracle (and hence with the
# sort-merge kernel, which the untouched fixtures above still cover).

@pytest.fixture()
def seg_bm(monkeypatch):
    """All three terms bitmap-eligible (all-bitmap -> vmapped kernel)."""
    from yacy_search_server_tpu.index.devstore import DeviceSegmentStore
    monkeypatch.setattr(DeviceSegmentStore, "JOIN_BITMAP_MIN", 1_000)
    seg = Segment(max_ram_postings=10)
    rng = np.random.default_rng(11)
    pool = np.arange(60_000)
    seg.rwi.ingest_run({
        word2hash("aa"): _plist(rng, 20_000, pool),
        word2hash("bb"): _plist(rng, 9_000, pool),
        word2hash("cc"): _plist(rng, 5_000, pool),
    })
    seg.enable_device_serving()
    yield seg
    seg.close()


@pytest.fixture()
def seg_mixed(monkeypatch):
    """Only the big partner bitmap-eligible (mixed-mode lax.map path)."""
    from yacy_search_server_tpu.index.devstore import DeviceSegmentStore
    monkeypatch.setattr(DeviceSegmentStore, "JOIN_BITMAP_MIN", 15_000)
    seg = Segment(max_ram_postings=10)
    rng = np.random.default_rng(12)
    pool = np.arange(60_000)
    seg.rwi.ingest_run({
        word2hash("aa"): _plist(rng, 20_000, pool),
        word2hash("bb"): _plist(rng, 9_000, pool),
        word2hash("cc"): _plist(rng, 5_000, pool),
    })
    seg.enable_device_serving()
    yield seg
    seg.close()


def _bm_slots(seg):
    return {th: sp[0].jslot
            for th, sp in ((t, seg.devstore.spans_for(word2hash(t)))
                           for t in ("aa", "bb", "cc"))}


def test_bitmap_spans_assigned(seg_bm, seg_mixed):
    slots = _bm_slots(seg_bm)
    assert all(s >= 0 for s in slots.values()), slots
    mixed = _bm_slots(seg_mixed)
    assert mixed["aa"] >= 0 and mixed["bb"] < 0 and mixed["cc"] < 0


def test_bitmap_two_term_parity(seg_bm):
    _assert_join_matches(seg_bm, [word2hash("aa"), word2hash("bb")], [])


def test_bitmap_three_term_exclusion_parity(seg_bm):
    _assert_join_matches(seg_bm, [word2hash("bb"), word2hash("aa")],
                         [word2hash("cc")])


def test_bitmap_tombstone_parity(seg_bm):
    joined = seg_bm.term_search(include_hashes=[word2hash("aa"),
                                                word2hash("bb")])
    for docid in joined.docids[:40].tolist():
        seg_bm.rwi.delete_doc(int(docid))
    _assert_join_matches(seg_bm, [word2hash("aa"), word2hash("bb")], [])


def test_mixed_mode_parity(seg_mixed):
    # rare=cc (sort partner bb, bitmap partner aa) exercises both
    # memberships inside ONE kernel call
    _assert_join_matches(
        seg_mixed, [word2hash("aa"), word2hash("bb"), word2hash("cc")], [])
    _assert_join_matches(seg_mixed, [word2hash("bb"), word2hash("cc")],
                         [word2hash("aa")])


def test_bitmap_batched_concurrency_parity(seg_bm):
    """All-bitmap conjunctions batch to max_batch and vmap; results must
    equal the solo kernel's bit for bit."""
    import threading

    ds = seg_bm.devstore
    inc = [word2hash("aa"), word2hash("bb")]
    exc = [word2hash("cc")]
    prof = RankingProfile()
    solo = ds.rank_join(inc, exc, prof, "en", k=25)
    assert solo is not None
    ds.enable_batching(max_batch=16)
    served0 = ds.join_served
    results = [None] * 24

    def worker(i):
        results[i] = ds.rank_join(inc, exc, prof, "en", k=25)

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(24)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    for out in results:
        assert out is not None
        np.testing.assert_array_equal(np.asarray(out[1]),
                                      np.asarray(solo[1]))
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      np.asarray(solo[0]))
    assert ds.join_served - served0 == 24


def test_bitmap_repack_rebuilds_slots(seg_bm):
    ds = seg_bm.devstore
    before = _bm_slots(seg_bm)
    ds.repack()
    after = _bm_slots(seg_bm)
    assert all(s >= 0 for s in after.values()), after
    assert before  # repack kept every term bitmap-served
    _assert_join_matches(seg_bm, [word2hash("aa"), word2hash("cc")], [])


# -- progressive intersection (the later memberships over the first one's
# survivors, a bucket of r/4 lanes at a time) ------------------------------
#
# One rare list of 4,000 rows (window r = 4,096, bucket 1,024) and
# partners of every density: the first membership (the SHORTEST partner,
# whatever the word order) leaves 0 lanes (a disjoint list), ~500 (one
# bucket), ~1,000 or ~3,200 (past one bucket: four buckets) for the later
# ones. Every answer is held to the host join; the counters to the lanes
# a host intersection of the first membership leaves, where the join
# compacts (three include partners or more), and to nothing where it does
# not.

RARE_ROWS = 4_000
# name -> (rows, docid pool): the pool is the rare list's 40,000 ids
# but for `dz`, which shares none of them
MANY = {"rr": (RARE_ROWS, None), "dz": (4_500, "disjoint"),
        "p12": (5_000, None), "p25": (10_000, None), "x30": (12_000, None),
        "p50": (20_000, None), "p80": (32_000, None), "p90": (36_000, None),
        "p95": (38_000, None)}
MODES = {"bitmap": 1_000, "sortmerge": 10 ** 9, "mixed": 15_000}


@pytest.fixture(scope="module",
                params=[(m, dead) for m in MODES for dead in (False, True)],
                ids=[m + dead for m in MODES for dead in ("", "-dead")])
def seg_many(request):
    """(segment, {term: docids}, tombstoned docids): the partners of a
    join on bitmaps, on the sort-merge, or both (the lists of 15,000 rows
    or more on bitmaps); with or without a slice of the rare list's
    docids tombstoned."""
    from yacy_search_server_tpu.index.devstore import DeviceSegmentStore
    mode, tombstones = request.param
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(DeviceSegmentStore, "JOIN_BITMAP_MIN", MODES[mode])
        seg = Segment(max_ram_postings=10)
        rng = np.random.default_rng(40)
        pool = np.arange(40_000)
        lists = {t: _plist(rng, n, pool if where is None
                           else np.arange(10 ** 6, 10 ** 6 + 40_000))
                 for t, (n, where) in MANY.items()}
        seg.rwi.ingest_run({word2hash(t): pl for t, pl in lists.items()})
        seg.enable_device_serving()
        bm = {t: seg.devstore.spans_for(word2hash(t))[0].jslot >= 0
              for t in MANY}
        assert any(bm.values()) is (mode != "sortmerge")
        if mode == "mixed":
            assert bm["p80"] and not bm["p25"]
        rare = lists["rr"].docids
        dead = (rare[::97].tolist() + rare[-40:].tolist()
                if tombstones else [])
        for docid in dead:
            seg.rwi.delete_doc(int(docid))
        yield seg, {t: pl.docids for t, pl in lists.items()}, dead
        seg.close()


def _first_survivors(docids, inc, exc, dead=()):
    """Lanes live after a join's first membership: the rare list's live
    docids in its shortest partner, or, with no include partner, out of
    its first exclude."""
    rare, *partners = sorted(inc, key=lambda t: len(docids[t]))
    live = set(docids[rare].tolist()) - set(dead)
    if partners:
        return len(live & set(docids[partners[0]].tolist()))
    return len(live - set(docids[exc[0]].tolist()))


def _compact_deltas(ds, before):
    c = ds.counters()
    return {k: c[k] - before[k] for k in (
        "join_compacted", "join_survivors", "join_compact_rows",
        "join_compact_overflow", "join_multi_served",
        "join_onegather_served")}


PROGRESSIVE = [
    # (includes in word order, excludes): 1 to 5 partners, 2 to 6
    # memberships, the shortest partner anywhere in the order
    (["p50", "rr", "p12"], []),                   # 2 memberships
    (["p80", "p90", "rr"], []),                   # two partners: no bucket
    (["rr", "p90", "p80", "p50"], []),
    (["p25", "p90", "rr", "p80", "p50"], []),
    (["p90", "p50", "p80", "p25", "p12", "rr"], []),
    (["p50", "dz", "rr", "p12"], []),             # 0 live after the first
    (["rr", "p80"], ["x30"]),                     # 1 partner + 1 exclude
    (["p90", "rr", "p25"], ["x30", "p12"]),
    (["rr"], ["x30", "p12"]),                     # excludes only
    (["p80", "rr", "p90", "p50"], ["x30", "p25"]),  # 6 memberships
    (["rr", "p80"], ["x30", "p12"]),              # 1 partner + 2 excludes
    (["p12", "rr", "p25"], ["dz"]),
    (["p80", "p90", "rr", "p95"], ["x30"]),       # ~3,200 live, 3 partners
]


@pytest.mark.parametrize("inc,exc", PROGRESSIVE,
                         ids=["-".join(i) + ("~" + "-".join(e) if e else "")
                              for i, e in PROGRESSIVE])
def test_progressive_join_parity(seg_many, inc, exc):
    from yacy_search_server_tpu.index.devstore import _join_bucket
    seg, docids, dead = seg_many
    ds = seg.devstore
    c0 = ds.counters()
    _assert_join_matches(seg, [word2hash(t) for t in inc],
                         [word2hash(t) for t in exc])
    bucket = _join_bucket(4_096, len(inc) - 1, len(exc))
    assert bool(bucket) is (len(inc) - 1 >= 3)
    n = _first_survivors(docids, inc, exc, dead) if bucket else 0
    assert _compact_deltas(ds, c0) == {
        "join_compacted": int(bool(bucket)), "join_survivors": n,
        "join_compact_rows": RARE_ROWS if bucket else 0,
        "join_compact_overflow": int(bool(bucket) and n > bucket),
        "join_multi_served": int(len(inc) >= 3),
        "join_onegather_served": int(len(inc) >= 3)}


def test_an_abandoned_wave_counts_its_join_once(monkeypatch):
    """A query whose waiter gives up on its wave is served, and counted,
    solo: the wave's late finish neither delivers it nor counts its
    survivors a second time."""
    import threading
    import time
    from yacy_search_server_tpu.index.devstore import DeviceSegmentStore
    monkeypatch.setattr(DeviceSegmentStore, "JOIN_BITMAP_MIN",
                        MODES["bitmap"])
    seg = Segment(max_ram_postings=10)
    rng = np.random.default_rng(40)
    pool = np.arange(40_000)
    seg.rwi.ingest_run({word2hash(t): _plist(rng, n, pool)
                        for t, (n, where) in MANY.items() if where is None})
    seg.enable_device_serving()
    ds = seg.devstore
    ds.enable_batching(max_batch=4, dispatchers=1, prewarm=False)
    inc = [word2hash(t) for t in ("rr", "p90", "p80", "p50")]
    try:
        # compile the wave's and the solo shapes first
        assert ds.rank_join(inc, [], RankingProfile(), k=10) is not None
        b = ds._batcher
        landed = threading.Event()
        issue = b._submit_completion

        def late(out, finish, *rest):
            def slow(host):
                time.sleep(0.5)
                finish(host)
                landed.set()
            issue(out, slow, *rest)

        monkeypatch.setattr(b, "_submit_completion", late)
        monkeypatch.setattr(b, "WATCHDOG_S", 0.05)
        c0 = ds.counters()
        assert ds.rank_join(inc, [], RankingProfile(), k=10) is not None
        assert landed.wait(10.0)
        c = ds.counters()
        assert b.timeouts >= 1
        assert {k: c[k] - c0[k] for k in ("join_compacted", "join_served")
                } == {"join_compacted": 1, "join_served": 1}
    finally:
        seg.close()


def test_a_two_word_join_does_not_compact(seg_many):
    seg, _docids, _mode = seg_many
    ds = seg.devstore
    for inc, exc in ((["p80", "rr"], []), (["rr"], ["x30"])):
        c0 = ds.counters()
        _assert_join_matches(seg, [word2hash(t) for t in inc],
                             [word2hash(t) for t in exc])
        assert set(_compact_deltas(ds, c0).values()) == {0}


def test_the_matrix_meets_every_survivor_count(seg_many):
    """Joins that compact with 0 lanes, one bucket's worth, and more
    than two buckets."""
    from yacy_search_server_tpu.index.devstore import _join_bucket
    _seg, docids, dead = seg_many
    n = sorted(_first_survivors(docids, i, e, dead) for i, e in PROGRESSIVE
               if _join_bucket(4_096, len(i) - 1, len(e)))
    bucket = _join_bucket(4_096, 3, 0)
    assert n[0] == 0 and 0 < n[1] <= bucket and n[-1] > 2 * bucket


WAVE = [["rr", "p12", "p50", "p95"], ["rr", "p80", "p90", "p95"],
        ["rr", "dz", "p50", "p95"], ["rr", "p25", "p90", "p95"]]
# ~500, ~3,200, 0 and ~1,000 live; three partners, so each compacts


@pytest.mark.parametrize("seg_many", [("bitmap", False), ("bitmap", True)],
                         indirect=True, ids=["bitmap", "bitmap-dead"])
def test_a_wave_of_four_with_unequal_survivors(seg_many, monkeypatch):
    """Four conjunctions of one compile shape in ONE vmapped dispatch
    (all-bitmap joins are the ones that batch past four) run the program
    a single query runs, each over its own number of buckets: each row
    equals its query's solo answer bit for bit, survivors included."""
    from yacy_search_server_tpu.index import devstore as DS
    seg, docids, dead = seg_many
    ds = seg.devstore
    real = DS._rank_join_bm_batch_packed_kernel
    calls = []

    def spy(*args, **kw):
        calls.append((args, kw))
        return real(*args, **kw)
    monkeypatch.setattr(DS, "_rank_join_bm_batch_packed_kernel", spy)
    solo = []
    for inc in WAVE:
        c0 = ds.counters()
        _assert_join_matches(seg, [word2hash(t) for t in inc], [])
        solo.append(_compact_deltas(ds, c0)["join_survivors"])
        assert solo[-1] == _first_survivors(docids, inc, [], dead)
    kw = calls[0][1]
    bucket = DS._join_bucket(kw["r"], kw["n_inc"], kw["n_exc"])
    assert len(set(solo)) == 4 and 0 in solo and max(solo) > 2 * bucket
    assert len(calls) == 4 and all(kw == calls[0][1] for _a, kw in calls)
    args, kw = calls[0]
    qpos = 7                                    # (..., bmtab, qargs, ...)
    wave = np.concatenate([a[qpos] for a, _kw in calls])
    out = np.asarray(real(*args[:qpos], wave, *args[qpos + 1:], **kw))
    s, d, n = DS._split_join_out(out, bucket)
    assert n.tolist() == solo
    for i, (a, _kw) in enumerate(calls):
        s1, d1, n1 = DS._split_join_out(np.asarray(real(*a, **kw)), bucket)
        assert n1.tolist() == [solo[i]]
        np.testing.assert_array_equal(s[i], s1[0])
        np.testing.assert_array_equal(d[i], d1[0])


# -- the payload read: a program of two include partners or more reads a
# partner's position, hit count and flags in ONE gather of its payload
# row (DeviceArena's jpay, beside the join side table) -----------------------
#
# Each answer is held to the program as it was before the payload read
# and the compaction: every membership over the whole window, each
# partner read through jpos and the arena's columns (the program a join
# of one partner still runs), traced afresh here with both rules turned
# off. Scores and docids are compared as the program returns them, pad
# lanes included.


def _hashes(words):
    return [word2hash(t) for t in words]


@pytest.fixture()
def spied(monkeypatch):
    """[(program, args, kw, output)] of every packed join program the
    test runs."""
    from yacy_search_server_tpu.index import devstore as DS
    calls = []
    for name in ("_rank_join_bm_batch_packed_kernel",
                 "_rank_join_batch_packed_kernel"):
        def spy(*args, real=getattr(DS, name), **kw):
            out = real(*args, **kw)
            calls.append((real, args, kw, np.asarray(out)))
            return out
        monkeypatch.setattr(DS, name, spy)
    return calls


def _one_phase_rows(args, kw, jpos):
    """(scores, docids) a row of the wave `args` / `kw` (a packed join
    program's call) as the one-phase program over jpos answers it."""
    import jax
    from yacy_search_server_tpu.index import devstore as DS
    bm = "inc_bm" in kw
    arrays = args[:5] + (jpos,)
    bmtab = args[6] if bm else None
    q = args[7] if bm else args[6]
    consts = args[8:] if bm else args[7:]

    def run(arrays, bmtab, q, consts):
        return jax.vmap(lambda one: DS._join_topk(
            *arrays, one, *consts, **kw, bmtab=bmtab))(q)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(DS, "_join_bucket", lambda r, n_inc, n_exc: 0)
        mp.setattr(DS, "_join_partner_read", lambda n_inc: "rows")
        s, d = jax.jit(run)(arrays, bmtab, q, consts)
    return np.asarray(s), np.asarray(d)


def _assert_as_one_phase(ds, call, wave=None):
    """The program's answer to `call` (or to the rows `wave` stacked
    into its qargs) equals the one-phase program's over jpos; returns
    the survivors, None where the program does not compact."""
    from yacy_search_server_tpu.index import devstore as DS
    real, args, kw, out = call
    _jd, jpos, jpay = ds.arena.join_arrays()
    assert args[5] is jpay
    if wave is not None:
        qpos = 7 if "inc_bm" in kw else 6
        args = args[:qpos] + (wave,) + args[qpos + 1:]
        out = np.asarray(real(*args, **kw))
    bucket = DS._join_bucket(kw["r"], kw["n_inc"], kw["n_exc"])
    s, d, survivors = DS._split_join_out(out, bucket)
    rs, rd = _one_phase_rows(args, kw, jpos)
    np.testing.assert_array_equal(s, rs)
    np.testing.assert_array_equal(d, rd)
    return survivors


PAYLOAD = [
    # (includes in word order, excludes, language filter): 2 to 6
    # include partners
    (["p50", "rr", "p12"], [], None),
    (["rr", "p90", "p80"], ["x30"], None),
    (["p80", "rr", "p90", "p50"], [], "de"),
    (["p25", "p90", "rr", "p80", "p50"], ["p12"], None),
    (["p90", "p50", "p80", "p25", "p12", "rr"], ["x30"], "en"),
    (["p95", "p90", "p50", "p80", "p25", "p12", "rr"], [], None),
    (["p50", "dz", "rr", "p12"], [], None),      # 0 live after the first
]


@pytest.mark.parametrize("inc,exc,lang", PAYLOAD,
                         ids=["-".join(i) + ("~" + "-".join(e) if e else "")
                              + (f"@{g}" if g else "")
                              for i, e, g in PAYLOAD])
def test_payload_read_parity(seg_many, spied, monkeypatch, inc, exc, lang):
    """Solo, on bitmaps, on the sort-merge and both, with and without
    tombstones (the fixture's six), excludes and a language filter."""
    seg, _docids, _dead = seg_many
    ds = seg.devstore
    monkeypatch.setattr(ds, "MAX_JOIN_TERMS", 7)     # six partners
    c0 = ds.counters()
    if lang is None:
        _assert_join_matches(seg, _hashes(inc), _hashes(exc))
    else:
        assert ds.rank_join(_hashes(inc), _hashes(exc), RankingProfile(),
                            "en", k=50,
                            lang_filter=P.pack_language(lang)) is not None
    assert len(spied) == 1 and spied[0][2]["n_inc"] == len(inc) - 1
    _assert_as_one_phase(ds, spied[0])
    c = ds.counters()
    assert (c["join_onegather_served"] - c0["join_onegather_served"]
            == c["join_multi_served"] - c0["join_multi_served"] == 1)


WAVES = {
    # one compile shape a wave (all-bitmap: inc_ms are all 0)
    "2x2": [["rr", "p12", "p50"], ["rr", "p80", "p90"]],
    "2x3": WAVE[:2],
    "4x3": WAVE,            # ~500, ~3,200, 0 and ~1,000 live
}


@pytest.mark.parametrize("seg_many", [("bitmap", False), ("bitmap", True)],
                         indirect=True, ids=["bitmap", "bitmap-dead"])
@pytest.mark.parametrize("wave", list(WAVES))
def test_payload_wave_parity(seg_many, spied, wave):
    """Waves of two and four under vmap, each row over its own number
    of buckets where the program compacts."""
    seg, docids, dead = seg_many
    ds = seg.devstore
    for inc in WAVES[wave]:
        _assert_join_matches(seg, _hashes(inc), [])
    assert len(spied) == len(WAVES[wave])
    kw = spied[0][2]
    assert all(call[2] == kw for call in spied)
    qpos = 7
    rows = np.concatenate([call[1][qpos] for call in spied])
    survivors = _assert_as_one_phase(ds, spied[0], wave=rows)
    if survivors is not None:
        want = [_first_survivors(docids, inc, [], dead)
                for inc in WAVES[wave]]
        assert survivors.tolist() == want and len(set(want)) == len(want)


@pytest.mark.parametrize("inc,exc,side", [
    (["p80", "rr"], [], "jpos"), (["rr"], ["x30", "p12"], "jpos"),
    (["rr", "p90"], ["p12"], "jpos"), (["rr", "p80", "p90"], [], "jpay")])
def test_a_program_takes_the_side_table_its_partner_count_names(
        seg_many, spied, inc, exc, side):
    """A one-partner program (or none: excludes only) takes jpos, a
    program of two partners jpay, and either refuses the other."""
    from yacy_search_server_tpu.index import devstore as DS
    seg, _docids, _dead = seg_many
    ds = seg.devstore
    _assert_join_matches(seg, _hashes(inc), _hashes(exc))
    real, args, kw, _out = spied[-1]
    _jd, jpos, jpay = ds.arena.join_arrays()
    assert DS._join_partner_read(kw["n_inc"]) == (
        "payload" if side == "jpay" else "rows")
    assert args[5] is (jpay if side == "jpay" else jpos)
    wrong = jpos if side == "jpay" else jpay
    with pytest.raises(ValueError, match="include partners takes"):
        real(*args[:5], wrong, *args[6:], **kw)


def _assert_payload_rows(ds):
    """The invariant the payload read rests on, on the host copy:
    jpay[p] is (position, hit count, flags) of arena row jpos[p] at
    every position the side table has handed out."""
    from yacy_search_server_tpu.index import devstore as DS
    arena = ds.arena
    n = arena._jused
    _jd, jpos, jpay = (np.asarray(a)[:n] for a in arena.join_arrays())
    f16, fl, _dd = (np.asarray(a) for a in arena.arrays())
    pos, hit, flags = DS._unpack_join_payload(jpay)
    assert n > 0 and jpay.shape == (n, 2)
    np.testing.assert_array_equal(pos, f16[jpos, P.F_POSINTEXT])
    np.testing.assert_array_equal(hit, f16[jpos, P.F_HITCOUNT])
    np.testing.assert_array_equal(flags, fl[jpos])


def _extreme_plist(rng, n, id_pool):
    """_plist with the payload's extremes among its rows: the largest
    position and hit count (and past them, which the arena clips), the
    smallest, and flags with every bit."""
    pl = _plist(rng, n, id_pool)
    f = pl.feats
    f[:8, P.F_POSINTEXT] = [32767, 40000, 0, 1, 32767, 2, 32766, 0]
    f[:8, P.F_HITCOUNT] = [32767, 1, 70000, 0, 32767, 32766, 1, 0]
    f[:33, P.F_FLAGS] = [-1] + [np.int32(np.uint32(1 << b).view(np.int32))
                                for b in range(32)]
    return pl


def test_payload_rows_after_growth_and_repack(monkeypatch, spied):
    """Runs packed one after another grow the side table (a pad and a
    write of each of its three arrays); a repack rebuilds it. After
    each, jpay agrees with the arena row by row, and joins of bitmap and
    sort-merge partners from both runs answer as the host join does and
    as the one-phase program over jpos does."""
    from yacy_search_server_tpu.index.devstore import DeviceSegmentStore
    monkeypatch.setattr(DeviceSegmentStore, "JOIN_BITMAP_MIN", 15_000)
    seg = Segment(max_ram_postings=10)
    rng = np.random.default_rng(43)
    pool = np.arange(40_000)
    seg.rwi.ingest_run({word2hash("rr"): _extreme_plist(rng, 4_000, pool),
                        word2hash("p50"): _plist(rng, 20_000, pool)})
    seg.enable_device_serving()
    ds = seg.devstore
    try:
        cap0 = ds.arena._jcap
        seg.rwi.ingest_run({word2hash("p80"): _extreme_plist(rng, 32_000,
                                                             pool),
                            word2hash("p12"): _plist(rng, 5_000, pool)})
        assert ds.arena._jcap > cap0
        for step in ("grown", "repacked"):
            if step == "repacked":
                ds.repack()
            _assert_payload_rows(ds)
            # two bitmap partners; then a sort-merge one beside them
            for inc, modes in ((["rr", "p50", "p80"], {True}),
                               (["p12", "rr", "p80", "p50"], {True, False})):
                spied.clear()
                _assert_join_matches(seg, _hashes(inc), [])
                assert len(spied) == 1
                assert set(spied[0][2]["inc_bm"]) == modes, step
                _assert_as_one_phase(ds, spied[0])
    finally:
        seg.close()


def test_the_payload_pair_is_exact_at_its_extremes():
    """Every int16 extreme of position and hit count beside every flag
    bit packs and unpacks to the integers the arena's columns widen to,
    on the host and on the device."""
    import itertools

    import jax.numpy as jnp
    from yacy_search_server_tpu.index import devstore as DS
    edge = (-32768, -32767, -1, 0, 1, 32766, 32767)
    flag = [0, -1] + [int(np.uint32(1 << b).view(np.int32))
                      for b in range(32)]
    pos, hit, flags = (np.asarray(c) for c in zip(
        *itertools.product(edge, edge, flag)))
    pos16, hit16 = pos.astype(np.int16), hit.astype(np.int16)
    pay = DS._pack_join_payload(pos16, hit16, flags.astype(np.int32))
    assert pay.dtype == np.int32 and pay.shape == (len(pos), 2)
    for got in (DS._unpack_join_payload(pay),
                DS._unpack_join_payload(jnp.asarray(pay))):
        got = [np.asarray(g) for g in got]
        assert all(g.dtype == np.int32 for g in got)
        np.testing.assert_array_equal(got[0], pos16.astype(np.int32))
        np.testing.assert_array_equal(got[1], hit16.astype(np.int32))
        np.testing.assert_array_equal(got[2], flags)
