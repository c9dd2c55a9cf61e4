"""Device-resident serving path: parity, lifecycle, and integration.

VERDICT r1 #1: the served query path must rank placed device blocks — not
re-upload candidates per query — and must return exactly what the host
CardinalRanker path returns on the same candidates.
"""

import numpy as np
import pytest

from yacy_search_server_tpu.index import postings as P
from yacy_search_server_tpu.index.devstore import (NO_FLAG, NO_LANG,
                                                   DeviceSegmentStore, TILE)
from yacy_search_server_tpu.index.postings import PostingsList
from yacy_search_server_tpu.index.rwi import RWIIndex
from yacy_search_server_tpu.ops.ranking import CardinalRanker, RankingProfile

TH = b"devtermAAAAA"


def _plist(rng, n, base=0, lang="en"):
    docids = np.arange(base, base + n, dtype=np.int32)
    feats = rng.integers(0, 1000, (n, P.NF)).astype(np.int32)
    feats[:, P.F_FLAGS] = rng.integers(0, 2 ** 20, n)
    feats[:, P.F_DOMLENGTH] = rng.integers(0, 256, n)
    feats[:, P.F_LANGUAGE] = P.pack_language(lang)
    return PostingsList(docids, feats)


def _oracle(idx: RWIIndex, th: bytes, k: int, profile=None, lang="en"):
    """Host-path oracle: full merged postings through CardinalRanker."""
    p = idx.get(th)
    r = CardinalRanker(profile or RankingProfile(), lang)
    return r.rank(p, None, k=k)


def _assert_same_ranking(got, want):
    gs, gd = got[0], got[1]
    ws, wd = want
    np.testing.assert_array_equal(np.sort(gs)[::-1], gs)  # best-first
    np.testing.assert_array_equal(gs, ws)                 # same score ladder
    # docids may differ only among equal scores; map score->docids
    for s in np.unique(ws):
        np.testing.assert_array_equal(np.sort(gd[gs == s]),
                                      np.sort(wd[ws == s]))


def _store(idx, **kw):
    return DeviceSegmentStore(idx, **kw)


def test_single_run_parity():
    rng = np.random.default_rng(0)
    idx = RWIIndex()
    idx.add_many(TH, _plist(rng, 500))
    idx.flush()
    ds = _store(idx)
    got = ds.rank_term(TH, RankingProfile(), k=50)
    assert got is not None and got[2] == 500
    _assert_same_ranking(got, _oracle(idx, TH, 50))


def test_multi_tile_span_parity():
    """Spans longer than one TILE exercise the fori_loop streaming."""
    rng = np.random.default_rng(1)
    idx = RWIIndex()
    idx.add_many(TH, _plist(rng, TILE + 5_000))
    idx.flush()
    ds = _store(idx)
    got = ds.rank_term(TH, RankingProfile(), k=30)
    _assert_same_ranking(got, _oracle(idx, TH, 30))


def test_multi_run_spans_and_delta():
    rng = np.random.default_rng(2)
    idx = RWIIndex()
    for i in range(3):
        idx.add_many(TH, _plist(rng, 200, base=i * 200))
        idx.flush()
    ds = _store(idx)
    # plus an unflushed RAM delta
    idx.add_many(TH, _plist(rng, 77, base=900))
    got = ds.rank_term(TH, RankingProfile(), k=40)
    assert got[2] == 3 * 200 + 77
    _assert_same_ranking(got, _oracle(idx, TH, 40))


def test_flush_packs_automatically_and_merge_repacks():
    rng = np.random.default_rng(3)
    idx = RWIIndex()
    ds = _store(idx)
    for i in range(10):
        idx.add_many(TH, _plist(rng, 100, base=i * 100))
        idx.flush()
    got = ds.rank_term(TH, RankingProfile(), k=20)
    assert got is None  # 10 spans > MAX_SPANS: host fallback
    assert idx.merge_runs(max_runs=2)
    got = ds.rank_term(TH, RankingProfile(), k=20)
    assert got is not None
    _assert_same_ranking(got, _oracle(idx, TH, 20))


def test_persisted_merge_keeps_device_serving(tmp_path):
    """Merge with a data_dir swaps the merged FrozenRun for its PagedRun;
    the packed extents must follow the swap (r2 regression: the listener
    ran after the swap and the merged run was never reachable)."""
    rng = np.random.default_rng(9)
    idx = RWIIndex(str(tmp_path))
    ds = _store(idx)
    for i in range(10):
        idx.add_many(TH, _plist(rng, 100, base=i * 100))
        idx.flush()
    assert idx.merge_runs(max_runs=2)
    got = ds.rank_term(TH, RankingProfile(), k=20)
    assert got is not None, "merged PagedRun lost its packed extents"
    _assert_same_ranking(got, _oracle(idx, TH, 20))
    idx.close()


def test_dead_bitmap_does_not_alias_high_docids():
    """Tombstoning the last in-bitmap docid must not delete every docid
    beyond the bitmap (r2 regression: clip aliased them onto one slot)."""
    rng = np.random.default_rng(10)
    n = 70_000  # > the 65536 initial bitmap capacity
    idx = RWIIndex()
    idx.add_many(TH, _plist(rng, n))
    idx.flush()
    ds = _store(idx)
    idx.delete_doc(65_535)
    got = ds.rank_term(TH, RankingProfile(), k=n)
    ids = set(got[1].tolist())
    assert 65_535 not in ids
    assert len(ids) == n - 1, "high docids were aliased onto the tombstone"


def test_tombstones_mask_dead_docs():
    rng = np.random.default_rng(4)
    idx = RWIIndex()
    idx.add_many(TH, _plist(rng, 300))
    idx.flush()
    ds = _store(idx)
    for d in (5, 17, 250):
        idx.delete_doc(d)
    got = ds.rank_term(TH, RankingProfile(), k=300)
    assert got is not None
    assert not (set(got[1].tolist()) & {5, 17, 250})
    _assert_same_ranking(got, _oracle(idx, TH, 300))


def test_constraint_filters_in_kernel():
    rng = np.random.default_rng(5)
    idx = RWIIndex()
    p = _plist(rng, 400)
    p.feats[:200, P.F_LANGUAGE] = P.pack_language("de")
    p.feats[:, P.F_LASTMOD] = rng.integers(100, 300, 400)
    idx.add_many(TH, p)
    idx.flush()
    ds = _store(idx)

    # language filter
    got = ds.rank_term(TH, RankingProfile(), k=400,
                       lang_filter=P.pack_language("de"))
    assert set(got[1].tolist()) <= set(range(200))
    # oracle on the same masked candidate set
    mask = p.feats[:, P.F_LANGUAGE] == P.pack_language("de")
    want = CardinalRanker(RankingProfile(), "en").rank(p.select(mask), None,
                                                       k=400)
    _assert_same_ranking(got, want)

    # daterange filter
    got = ds.rank_term(TH, RankingProfile(), k=400,
                       from_days=150, to_days=200)
    lastmod = p.feats[:, P.F_LASTMOD]
    want_ids = set(np.where((lastmod >= 150) & (lastmod <= 200))[0].tolist())
    assert set(got[1].tolist()) == want_ids


def test_restart_seeds_tombstones(tmp_path):
    rng = np.random.default_rng(6)
    idx = RWIIndex(str(tmp_path))
    idx.add_many(TH, _plist(rng, 100))
    idx.flush()
    idx.delete_doc(7)
    idx.close()
    idx2 = RWIIndex(str(tmp_path))
    ds = _store(idx2)
    got = ds.rank_term(TH, RankingProfile(), k=100)
    assert 7 not in set(got[1].tolist())
    idx2.close()


def test_budget_skip_falls_back():
    rng = np.random.default_rng(7)
    idx = RWIIndex()
    ds = _store(idx, budget_bytes=100_000)  # ~2.6k rows
    idx.add_many(TH, _plist(rng, 10_000))
    idx.flush()
    assert ds.rank_term(TH, RankingProfile(), k=10) is None


def test_pruning_exact_and_skips_tiles():
    """Default-profile query over a proxy-sorted multi-tile span must
    return the exact oracle top-k while reading only the first tile."""
    rng = np.random.default_rng(20)
    idx = RWIIndex()
    idx.add_many(TH, _plist(rng, 4 * TILE + 123))
    idx.flush()
    ds = _store(idx)
    got = ds.rank_term(TH, RankingProfile(), k=100)
    _assert_same_ranking(got, _oracle(idx, TH, 100))
    assert ds.prune_rounds >= 1
    assert ds.pruned_tiles >= 3, "tail tiles were not pruned"


def test_pruning_exact_under_nondefault_profile():
    """A profile with boosted coefficients shifts the bound (possible
    escalations) but the returned top-k must still be oracle-exact."""
    rng = np.random.default_rng(21)
    idx = RWIIndex()
    idx.add_many(TH, _plist(rng, 2 * TILE + 77))
    idx.flush()
    ds = _store(idx)
    prof = RankingProfile(worddistance=2, appemph=15, urllength=12, tf=3)
    got = ds.rank_term(TH, prof, k=60)
    _assert_same_ranking(got, _oracle(idx, TH, 60, profile=prof))


def test_pruning_exact_under_language_preference():
    rng = np.random.default_rng(22)
    idx = RWIIndex()
    p = _plist(rng, TILE + 500)
    p.feats[::3, P.F_LANGUAGE] = P.pack_language("de")
    idx.add_many(TH, p)
    idx.flush()
    ds = _store(idx)
    got = ds.rank_term(TH, RankingProfile(), language="de", k=50)
    _assert_same_ranking(got, _oracle(idx, TH, 50, lang="de"))


def test_tombstone_disables_pruning_until_merge():
    """Deletes after packing must force the exact live-stats kernel (frozen
    pack stats would drift); the next merge folds them and re-arms."""
    rng = np.random.default_rng(23)
    idx = RWIIndex()
    idx.add_many(TH, _plist(rng, 2 * TILE))
    idx.flush()
    ds = _store(idx)
    ds.rank_term(TH, RankingProfile(), k=10)
    rounds0 = ds.prune_rounds
    assert rounds0 >= 1
    idx.delete_doc(3)
    got = ds.rank_term(TH, RankingProfile(), k=10)
    assert ds.prune_rounds == rounds0, "pruned while tombstones postdate span"
    _assert_same_ranking(got, _oracle(idx, TH, 10))
    # second run, then fold everything: pruning re-arms
    idx.add_many(TH, _plist(rng, 100, base=10 ** 6))
    idx.flush()
    assert idx.merge_runs(max_runs=1)
    got = ds.rank_term(TH, RankingProfile(), k=10)
    assert ds.prune_rounds > rounds0
    _assert_same_ranking(got, _oracle(idx, TH, 10))


def test_searchevent_device_vs_host_identical(monkeypatch):
    """End-to-end: SearchEvent with devstore enabled returns the same page
    as with it disabled."""
    from yacy_search_server_tpu.document.document import Document
    from yacy_search_server_tpu.index.segment import Segment
    from yacy_search_server_tpu.ops import ranking
    from yacy_search_server_tpu.search.query import QueryParams
    from yacy_search_server_tpu.search.searchevent import SearchEvent

    # the small-candidate gate would route this tiny corpus to the host
    # path; the device-vs-host identity is exactly what this test checks,
    # so force the device path
    monkeypatch.setattr(ranking, "SMALL_RANK_N", 0)

    seg = Segment(max_ram_postings=50)
    rng = np.random.default_rng(8)
    for i in range(60):
        seg.store_document(Document(
            url=f"http://h{i % 7}.example/p{i}.html",
            title=f"gondola {i}",
            text=f"gondola lift station {i} " * (1 + int(rng.integers(1, 5)))))
    seg.rwi.flush()
    # fold the many small flush runs (the merge busy thread's job): more
    # than MAX_SPANS runs per term is a legitimate device-path fallback
    while seg.rwi.merge_runs(max_runs=2):
        pass

    host = SearchEvent(QueryParams.parse("gondola", item_count=10), seg)
    host_page = [(r.docid, r.score) for r in host.results()]

    seg.enable_device_serving()
    dev = SearchEvent(QueryParams.parse("gondola", item_count=10), seg)
    dev_page = [(r.docid, r.score) for r in dev.results()]
    assert seg.devstore.queries_served >= 1
    assert dev_page == host_page

    # multi-term queries fall back to the host join path and still work
    ev = SearchEvent(QueryParams.parse("gondola lift", item_count=5), seg)
    assert len(ev.results()) == 5


def test_facet_filter_bitmap_parity():
    """site:/tld:/filetype:/protocol queries serve ON DEVICE through a
    cached facet docid bitmap (VERDICT r3 #5 widening), returning the
    host path's exact results."""
    import tempfile

    from yacy_search_server_tpu.switchboard import Switchboard
    from yacy_search_server_tpu.utils.config import Config
    from yacy_search_server_tpu.utils.hashes import word2hash

    cfg = Config()
    cfg.set("index.device.mesh", "off")
    sb = Switchboard(data_dir=tempfile.mkdtemp() + "/DATA", config=cfg,
                     transport=lambda u, h: (404, {}, b""))
    try:
        n, hosts = 30_000, 16
        exts = ["html", "pdf"]
        sb.index.metadata.bulk_load(
            [f"{i:06d}h{i % hosts:05d}".encode() for i in range(n)],
            sku=[f"http{'s' if i % 2 else ''}://h{i % hosts}.example/"
                 f"d{i}.{exts[i % 2]}" for i in range(n)],
            title=[f"doc {i}" for i in range(n)],
            host_s=[f"h{i % hosts}.example" for i in range(n)],
            url_file_ext_s=[exts[i % 2] for i in range(n)],
            url_protocol_s=["https" if i % 2 else "http"
                            for i in range(n)],
            size_i=[1000] * n, wordcount_i=[100] * n)
        rng = np.random.default_rng(3)
        feats = rng.integers(0, 1000, (n, P.NF)).astype(np.int32)
        feats[:, P.F_LANGUAGE] = P.pack_language("en")
        sb.index.rwi.ingest_run({word2hash("fterm"): PostingsList(
            np.arange(n, dtype=np.int32), feats)})
        ds = sb.index.devstore
        assert ds is not None and ds.supports_filter_bitmap

        for qs in ("fterm site:h3.example", "fterm filetype:pdf",
                   "fterm tld:example", "fterm protocol:https",
                   "fterm site:h3.example filetype:pdf"):
            served0 = ds.queries_served
            ev = sb.search(qs, count=10)
            dev = [(r.url, r.score) for r in ev.results()]
            assert ds.queries_served == served0 + 1, qs
            assert ds.filtered_served >= 1
            sb.search_cache.clear()
            # host-path oracle: detach the device store for this query
            sb.index.devstore = None
            ev2 = sb.search(qs, count=10)
            host = [(r.url, r.score) for r in ev2.results()]
            sb.index.devstore = ds
            sb.search_cache.clear()
            assert [u for u, _ in dev] == [u for u, _ in host], qs
            for u, _s in dev:
                if "site:h3" in qs:
                    assert "//h3.example" in u, (qs, u)
                if "filetype:pdf" in qs:
                    assert u.endswith(".pdf"), (qs, u)
                if "protocol:https" in qs:
                    assert u.startswith("https:"), (qs, u)

        # the bitmap CACHES per modifier combo: a repeat query reuses
        # it; after a mutation the stale entry survives only within
        # FILTER_TTL_S (bounded soft-commit lag — stale false positives
        # die in the materialization recheck), then rebuilds with the
        # new facet version
        combo = (("site", "h3.example"),)
        ver0, _built, _dev = ds._filter_cache[combo]
        sb.search("fterm site:h3.example", count=10).results()
        assert ds._filter_cache[combo][0] == ver0     # reused
        from yacy_search_server_tpu.index.metadata import \
            metadata_from_parsed
        sb.index.metadata.put(metadata_from_parsed(
            b"zzznewdoc000", "http://h3.example/new.html", "n", "t",
            host_s="h3.example"))
        # force TTL expiry so the rebuild happens now, not 2s later
        v, _b, dv = ds._filter_cache[combo]
        ds._filter_cache[combo] = (v, -1e9, dv)
        sb.search_cache.clear()
        sb.search("fterm site:h3.example", count=10).results()
        assert ds._filter_cache[combo][0] > ver0      # rebuilt, new ver
    finally:
        sb.close()


def test_filtered_stats_cache_hit_is_bit_identical():
    """The repeated-modifier fast path (cached filtered stats skip the
    stream scan's stats pass) returns exactly the cold path's results,
    and tombstones invalidate it (snapshot identity keying)."""
    rng = np.random.default_rng(9)
    idx = RWIIndex()
    p = _plist(rng, 3000)
    p.feats[:1500, P.F_LANGUAGE] = P.pack_language("de")
    idx.add_many(TH, p)
    idx.flush()
    ds = _store(idx)
    de = P.pack_language("de")
    cold = ds.rank_term(TH, RankingProfile(), k=50, lang_filter=de)
    assert ds._span_stats_cache, "stats were not cached"
    hot = ds.rank_term(TH, RankingProfile(), k=50, lang_filter=de)
    assert np.array_equal(cold[0], hot[0])
    assert np.array_equal(cold[1], hot[1])
    # tombstone moves the snapshot: the stale entry must not be used
    victim = int(cold[1][0])
    idx.delete_doc(victim)
    after = ds.rank_term(TH, RankingProfile(), k=50, lang_filter=de)
    assert victim not in after[1].tolist()


# -- the arena: admission and growth by one sizing rule ----------------------

# block sequences whose rows end within a TILE of a power of two, with
# the writes each takes: where a block's size bucket would cross the
# capacity admission checked, it goes in pieces
EDGE_SEQUENCES = {
    "fits_to_the_edge": ([(1 << 19) - TILE], 1),
    "two_halves": ([1 << 18, (1 << 18) - TILE], 2),
    "crosses_in_two_writes": ([819_199, 196_609], 3),
    "crosses_in_three_writes": ([524_289, 491_519], 4),
    "the_full_configuration_at_1_64": ([32_768] * 14 + [4_096] * 50
                                       + [319_488], 66),
}


def _rows(n, base, salt):
    """A block of n rows whose every cell says where it belongs."""
    docids = np.arange(base, base + n, dtype=np.int32)
    feats = np.zeros((n, P.NF), np.int32)
    feats[:, 0] = (docids + salt) % 30_000
    feats[:, P.F_FLAGS] = docids ^ salt
    return docids, feats


def _chunk(n, base, salt):
    """_rows as the arena takes them: (docids, f16, flags)."""
    from yacy_search_server_tpu.ops.ranking import compact_feats
    docids, feats = _rows(n, base, salt)
    return (docids, *compact_feats(feats))


def _append_admitted(arena, blocks):
    """Append each block as the store does (would_fit first); returns
    the capacity admission budgeted for each."""
    admitted, base = [], 0
    for i, n in enumerate(blocks):
        assert arena.would_fit(n)
        admitted.append(arena.cap_for(n))
        assert arena.append_block([_chunk(n, base, 7 * i)]) == base
        assert arena.capacity_rows == admitted[-1]
        base += n
    return admitted


@pytest.mark.parametrize("name", sorted(EDGE_SEQUENCES))
def test_admission_and_growth_agree_at_the_capacity_edge(name):
    """An append that would_fit admitted never grows the arena past the
    capacity admission checked, its padded writes included."""
    from yacy_search_server_tpu.index.devstore import DeviceArena
    blocks, _writes = EDGE_SEQUENCES[name]
    arena = DeviceArena()
    admitted = _append_admitted(arena, blocks)
    used = sum(blocks)
    assert arena.used_rows == used
    # the final capacity is the least doubling that holds the rows and
    # the TILE past the last span
    want = 4 * TILE
    while want < used + TILE:
        want *= 2
    assert arena.capacity_rows == admitted[-1] == want
    assert arena.fits(arena.capacity_rows, arena._pw_cap)


@pytest.mark.parametrize("name", sorted(EDGE_SEQUENCES))
def test_edge_appends_keep_every_earlier_row(name):
    """A write near the capacity goes in pieces that stay inside it:
    dynamic_update_slice would clamp a start that does not fit and
    overwrite earlier rows in silence. Every row reads back as written,
    and every row past the used mark is inert (docid -1)."""
    from yacy_search_server_tpu.index.devstore import DeviceArena
    from yacy_search_server_tpu.utils import histogram
    blocks, writes = EDGE_SEQUENCES[name]
    arena = DeviceArena()
    h = histogram.histogram("arena.append")
    before = h.snapshot()["count"]
    _append_admitted(arena, blocks)
    assert h.snapshot()["count"] - before == writes
    feats16, flags, docids = (np.asarray(a) for a in arena.arrays())
    base = 0
    for i, n in enumerate(blocks):
        dd, ff = _rows(n, base, 7 * i)
        np.testing.assert_array_equal(docids[base:base + n], dd)
        np.testing.assert_array_equal(flags[base:base + n],
                                      ff[:, P.F_FLAGS])
        np.testing.assert_array_equal(feats16[base:base + n, 0], ff[:, 0])
        base += n
    assert (docids[base:] == -1).all()


def test_a_write_past_the_capacity_raises():
    """The clamp trap, pinned where it would spring: a write whose rows
    do not fit is refused, not clamped onto the rows before it."""
    from yacy_search_server_tpu.index.devstore import DeviceArena
    arena = DeviceArena()
    cap = arena.capacity_rows
    rows = np.zeros(512, np.int32)
    with pytest.raises(ValueError, match="crosses the capacity"):
        arena._write_rows(cap - 256, np.zeros((512, P.NF), np.int16),
                          rows, rows)
    assert (np.asarray(arena.arrays()[2]) == -1).all()


def test_growth_is_a_span_and_a_counted_wall():
    """A growth is one `arena.grow` span and one `arena_grow` event whose
    items are the rows it added; the arena's first allocation is an
    `arena_grow` event too, so their items sum to its capacity. The
    pads compile before the span: the span times the copies alone."""
    from yacy_search_server_tpu.index.devstore import DeviceArena
    from yacy_search_server_tpu.utils import eventtracker, histogram
    ev = (eventtracker.EClass.INDEX, "arena_grow")
    h = histogram.histogram("arena.grow")
    before, ev0 = h.snapshot()["count"], eventtracker.totals().get(
        ev, (0, 0, 0.0))
    arena = DeviceArena()
    arena.append_block([_chunk(5 * TILE, 0, 1)])
    assert arena.capacity_rows == 8 * TILE
    assert h.snapshot()["count"] == before + 1
    assert h.snapshot()["sum_ms"] > 0.0
    assert histogram.get("arena.append").snapshot()["count"] >= 1
    ev1 = eventtracker.totals()[ev]
    assert (ev1[0] - ev0[0], ev1[1] - ev0[1]) == (2, 8 * TILE)
    assert ev1[2] > ev0[2]


def test_a_refused_run_counts_its_rows_and_keeps_the_event():
    """A run the budget refuses stays host-served: the store counts its
    rows in arena_rows_skipped, and the event devstore_skip, which
    outlives the store in the process's totals, stays."""
    from yacy_search_server_tpu.utils import eventtracker
    ev = (eventtracker.EClass.INDEX, "devstore_skip")
    before = eventtracker.totals().get(ev, (0, 0, 0.0))
    rng = np.random.default_rng(11)
    idx = RWIIndex()
    ds = _store(idx, budget_bytes=8 * TILE * 42)
    idx.ingest_run({TH: _plist(rng, 2 * TILE)})
    idx.ingest_run({b"devtermBBBBB": _plist(rng, 5 * TILE)})
    c = ds.counters()
    assert c["arena_rows_packed"] == 2 * TILE
    assert c["arena_rows_skipped"] == 5 * TILE
    assert c["arena_used_rows"] == 2 * TILE
    assert c["arena_capacity_rows"] == 4 * TILE
    after = eventtracker.totals()[ev]
    assert (after[0] - before[0], after[1] - before[1]) == (1, 5 * TILE)
    assert ds.rank_term(b"devtermBBBBB", RankingProfile(), k=10) is None
    ds.close()
    assert eventtracker.totals()[ev] == after


def _tied_plist(rng, n):
    """A list whose features take three values each: many equal
    rankings, in a pack (pruning) order that is not document order under
    any profile but the pack's own."""
    p = _plist(rng, n)
    p.feats[:] = rng.integers(0, 3, p.feats.shape)
    p.feats[:, P.F_LANGUAGE] = P.pack_language("en")
    return p


def test_equal_rankings_come_back_by_ascending_docid():
    """The pinned tie order on every device answer, its cached sights
    included: lax.top_k breaks ties by candidate index, and an arena in
    pruning order (or a join's rare list) is not in document order. The
    second sight (rank_cache_get, as SearchEvent asks it first) is the
    first sight, which is the host path's answer."""
    rng = np.random.default_rng(0)
    idx = RWIIndex()
    idx.ingest_run({TH: _tied_plist(rng, 3000),
                    b"devtermBBBBB": _tied_plist(rng, 3000)})
    ds = _store(idx)
    prof = RankingProfile(domlength=0, date=15, hitcount=15)
    first = ds.rank_term(TH, prof, k=100)
    ws, wd = _oracle(idx, TH, 100, prof)
    assert len(np.unique(ws)) < len(ws)          # the answer holds ties
    np.testing.assert_array_equal(first[0], ws)
    np.testing.assert_array_equal(first[1], wd)
    hits = ds.counters()["rank_cache_hits"]
    again = ds.rank_cache_get(TH, prof, k=100)
    assert ds.counters()["rank_cache_hits"] == hits + 1
    np.testing.assert_array_equal(again[0], first[0])
    np.testing.assert_array_equal(again[1], first[1])
    s, d, _ = ds.rank_join([TH, b"devtermBBBBB"], [], prof, k=100)
    assert len(np.unique(s)) < len(s)
    ties = s[1:] == s[:-1]
    assert (s[1:] <= s[:-1]).all() and (d[1:][ties] > d[:-1][ties]).all()
    ds.close()


# -- the pack path's host work: each shortcut equals what it replaces --------

@pytest.mark.parametrize("n", (0, 1, 2, 7, 4096, 70_001))
@pytest.mark.parametrize("hi", (1, 3, 1 << 20, 1 << 32, 1 << 40))
def test_proxy_order_is_the_stable_descending_argsort(n, hi):
    from yacy_search_server_tpu.index.devstore import proxy_order
    proxy = np.random.default_rng(n + hi).integers(0, hi, n).astype(np.int64)
    np.testing.assert_array_equal(proxy_order(proxy),
                                  np.argsort(-proxy, kind="stable"))


@pytest.mark.parametrize("ascending", (True, False))
def test_docid_view_is_the_sorted_packed_view(ascending):
    from yacy_search_server_tpu.index.devstore import docid_view
    rng = np.random.default_rng(5)
    docids = np.cumsum(rng.integers(1, 9, 5000)).astype(np.int32)
    if not ascending:
        docids = rng.permutation(docids)
    order = rng.permutation(len(docids))
    packed = docids[order]
    jorder = np.argsort(packed, kind="stable")
    got_dd, got_j = docid_view(docids, order)
    np.testing.assert_array_equal(got_dd, packed[jorder])
    np.testing.assert_array_equal(got_j, jorder)
    assert got_dd.dtype == np.int32


@pytest.mark.filterwarnings("ignore::RuntimeWarning")   # the twin's 0 / 0
@pytest.mark.parametrize("shape", ("rows", "clipped", "tf_flat", "one"))
def test_pack_proxy_by_the_native_scorer_equals_the_numpy_twin(shape):
    """The prune layout's proxy worked out from the int32 rows by the
    native scorer is the NumPy twin's, score for score (the layout, and
    with it every pruned answer, is the same either way)."""
    from yacy_search_server_tpu.index.devstore import pack_prune_stats
    from yacy_search_server_tpu.ops.ranking import compact_feats
    rng = np.random.default_rng(len(shape))
    feats = _plist(rng, 1 if shape == "one" else 3000).feats.copy()
    if shape == "clipped":
        feats[::5] = rng.integers(-100_000, 100_000, feats[::5].shape)
    elif shape == "tf_flat":
        feats[:, P.F_HITCOUNT] = 0
    f16, fl = compact_feats(feats)
    stats, twin = pack_prune_stats(f16, fl)
    stats_n, native_proxy = pack_prune_stats(f16, fl, feats)
    np.testing.assert_array_equal(native_proxy, twin)
    assert native_proxy.dtype == np.int64
    for k in stats:
        np.testing.assert_array_equal(stats_n[k], stats[k])


@pytest.mark.parametrize("name", sorted(EDGE_SEQUENCES))
def test_a_block_in_many_chunks_fills_the_rows_of_one(name):
    """A block streamed as several chunks fills the same rows as the one
    chunk, across chunk and write boundaries alike."""
    from yacy_search_server_tpu.index.devstore import DeviceArena
    blocks, _writes = EDGE_SEQUENCES[name]
    arenas = DeviceArena(), DeviceArena()
    base = 0
    for i, n in enumerate(blocks):
        whole = _chunk(n, base, 3 * i)
        cuts = [0, min(n, 1), n // 3, n // 3 + 1, n]
        parts = [tuple(a[lo:hi] for a in whole)
                 for lo, hi in zip(cuts, cuts[1:])]
        assert arenas[0].append_block([whole]) == \
            arenas[1].append_block(parts) == base
        base += n
    for a, b in zip(arenas[0].arrays(), arenas[1].arrays()):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert arenas[0].capacity_rows == arenas[1].capacity_rows


def test_a_prewarm_pass_waits_for_the_arena_to_be_still(monkeypatch):
    """Packs that keep coming (a bulk load) hold the prewarm back until
    PREWARM_QUIET_S after the last one; then one pass runs."""
    import time
    rng = np.random.default_rng(9)
    idx = RWIIndex()
    idx.add_many(TH, _plist(rng, 300))
    idx.flush()
    ds = _store(idx)
    passes = []
    monkeypatch.setattr(ds, "prewarm_kernels",
                        lambda: passes.append(time.monotonic()))
    monkeypatch.setattr(DeviceSegmentStore, "PREWARM_QUIET_S", 0.3)
    ds._prewarm_on = True
    ds._maybe_prewarm()
    for _ in range(4):
        time.sleep(0.1)
        ds._maybe_prewarm()
    last = time.monotonic()
    assert ds.prewarm_wait(5.0)
    assert len(passes) == 1 and passes[0] >= last + 0.25
