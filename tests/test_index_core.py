"""Index-core tests: postings blocks, RWI LSM, metadata store, Segment.

Mirrors the reference's embedded-integration style (SURVEY.md §4:
SegmentTest boots a real Segment on a temp dir, indexes synthetic docs and
runs TermSearch queries; ReferenceContainerTest exercises add/search/join).
"""

import numpy as np
import pytest

from yacy_search_server_tpu.document.condenser import Condenser, words_of, phrases_of
from yacy_search_server_tpu.document.document import Anchor, Document
from yacy_search_server_tpu.index import postings as P
from yacy_search_server_tpu.index.metadata import DocumentMetadata, MetadataStore
from yacy_search_server_tpu.index.postings import PostingsList, merge, remove_docids
from yacy_search_server_tpu.index.rwi import RWIIndex
from yacy_search_server_tpu.index.segment import (
    Segment, exclude_destructive, join_constructive,
)
from yacy_search_server_tpu.utils.bitfield import (
    Bitfield, FLAG_APP_DC_IDENTIFIER, FLAG_APP_DC_TITLE, FLAG_CAT_HASIMAGE,
)
from yacy_search_server_tpu.utils.hashes import url2hash, word2hash


def plist(ids, cols=None):
    """Helper: postings list with given docids and {feature col: values}."""
    d = np.asarray(ids, dtype=np.int32)
    f = np.zeros((len(d), P.NF), dtype=np.int32)
    for col, vals in (cols or {}).items():
        f[:, col] = vals
    return PostingsList(d, f)


class TestPostings:
    def test_sort_dedupe_last_wins(self):
        pl = PostingsList.from_rows(
            [5, 3, 5], np.array([[1] * P.NF, [2] * P.NF, [9] * P.NF]))
        assert pl.docids.tolist() == [3, 5]
        assert pl.feats[1, 0] == 9  # later row for docid 5 won

    def test_merge_override(self):
        a = plist([1, 2], {P.F_HITCOUNT: [10, 10]})
        b = plist([2, 3], {P.F_HITCOUNT: [99, 7]})
        m = merge([a, b])
        assert m.docids.tolist() == [1, 2, 3]
        assert m.feats[1, P.F_HITCOUNT] == 99  # b overrides a for docid 2

    def test_remove_docids(self):
        pl = plist([1, 2, 3, 4])
        out = remove_docids(pl, np.array([2, 4], dtype=np.int32))
        assert out.docids.tolist() == [1, 3]

    def test_language_pack(self):
        assert P.unpack_language(P.pack_language("en")) == "en"
        assert P.pack_language("") == 0


class TestRWI:
    def test_add_flush_get(self, tmp_path):
        rwi = RWIIndex(str(tmp_path / "rwi"), max_ram_postings=10)
        th = word2hash("hello")
        for docid in [4, 1, 7]:
            rwi.add(th, docid, np.full(P.NF, docid, dtype=np.int32))
        got = rwi.get(th)
        assert got.docids.tolist() == [1, 4, 7]
        rwi.flush()
        assert rwi.ram_postings_count == 0
        assert rwi.get(th).docids.tolist() == [1, 4, 7]

    def test_persistence_roundtrip(self, tmp_path):
        d = str(tmp_path / "rwi")
        rwi = RWIIndex(d)
        th = word2hash("persist")
        rwi.add(th, 42, np.arange(P.NF, dtype=np.int32))
        rwi.close()  # flushes
        rwi2 = RWIIndex(d)
        got = rwi2.get(th)
        assert got.docids.tolist() == [42]
        assert got.feats[0].tolist() == list(range(P.NF))

    def test_ram_overrides_run(self, tmp_path):
        rwi = RWIIndex(None)
        th = word2hash("w")
        rwi.add(th, 1, np.full(P.NF, 1, dtype=np.int32))
        rwi.flush()
        rwi.add(th, 1, np.full(P.NF, 2, dtype=np.int32))  # re-index same doc
        assert rwi.get(th).feats[0, 0] == 2

    def test_tombstone_and_merge(self):
        rwi = RWIIndex(None)
        th = word2hash("w")
        for i in range(6):
            rwi.add(th, i, np.zeros(P.NF, dtype=np.int32))
            rwi.flush()  # 6 runs of 1 posting
        rwi.delete_doc(3)
        assert rwi.get(th).docids.tolist() == [0, 1, 2, 4, 5]
        assert rwi.merge_runs(max_runs=2) is True
        assert rwi.run_count() <= 2
        assert rwi.get(th).docids.tolist() == [0, 1, 2, 4, 5]

    def test_remove_term_ownership_move(self):
        rwi = RWIIndex(None)
        th = word2hash("moved")
        rwi.add(th, 1, np.zeros(P.NF, dtype=np.int32))
        rwi.flush()
        rwi.add(th, 2, np.zeros(P.NF, dtype=np.int32))
        taken = rwi.remove_term(th)
        assert taken.docids.tolist() == [1, 2]
        assert rwi.count(th) == 0  # delete-on-select: gone locally

    def test_ring_segment_selection(self):
        rwi = RWIIndex(None)
        hashes = [word2hash(w) for w in ("alpha", "beta", "gamma", "delta")]
        for th in hashes:
            rwi.add(th, 1, np.zeros(P.NF, dtype=np.int32))
        from yacy_search_server_tpu.parallel.distribution import horizontal_dht_position
        positions = sorted(horizontal_dht_position(th) for th in hashes)
        sel = rwi.terms_in_ring_segment(positions[0], positions[2])
        assert len(sel) == 2  # two of four fall in [p0, p2)


class TestJoin:
    def test_conjunction_intersects(self):
        a = plist([1, 2, 3], {P.F_POSINTEXT: [10, 20, 30]})
        b = plist([2, 3, 4], {P.F_POSINTEXT: [25, 31, 99]})
        j = join_constructive([a, b])
        assert j.docids.tolist() == [2, 3]
        # worddistance = span of posintext across terms
        assert j.feats[:, P.F_WORDDISTANCE].tolist() == [5, 1]

    def test_exclusion(self):
        j = exclude_destructive(plist([1, 2, 3]), plist([2]))
        assert j.docids.tolist() == [1, 3]

    def test_flags_or_merged(self):
        a = plist([1], {P.F_FLAGS: [1 << FLAG_APP_DC_TITLE]})
        b = plist([1], {P.F_FLAGS: [1 << FLAG_CAT_HASIMAGE]})
        j = join_constructive([a, b])
        assert j.feats[0, P.F_FLAGS] == (1 << FLAG_APP_DC_TITLE) | (1 << FLAG_CAT_HASIMAGE)


class TestMetadata:
    def test_put_get_overwrite(self, tmp_path):
        # re-put allocates a NEW docid (versioned append): the old version's
        # identity stays dead so stale RWI postings can never answer for the
        # re-indexed document
        m = MetadataStore(str(tmp_path / "meta"))
        uh = url2hash("http://a.com/x")
        d1 = m.put(DocumentMetadata(uh, sku="http://a.com/x", title="one"))
        d2 = m.put(DocumentMetadata(uh, sku="http://a.com/x", title="two"))
        assert d2 != d1
        assert m.docid(uh) == d2
        assert m.is_deleted(d1)
        assert m.get(d2).get("title") == "two"
        assert len(m) == 1

    def test_journal_replay(self, tmp_path):
        p = str(tmp_path / "meta")
        m = MetadataStore(p)
        uh = url2hash("http://a.com/x")
        m.put(DocumentMetadata(uh, title="hello", wordcount_i=7))
        m.delete(url2hash("http://a.com/x"))
        m.put(DocumentMetadata(url2hash("http://b.com/y"), title="b"))
        m.close()
        m2 = MetadataStore(p)
        assert m2.get_by_urlhash(uh) is None          # delete survived
        assert m2.get_by_urlhash(url2hash("http://b.com/y")).get("title") == "b"

    def test_int_column(self):
        m = MetadataStore()
        m.put(DocumentMetadata(url2hash("http://a.com/1"), wordcount_i=5))
        m.put(DocumentMetadata(url2hash("http://a.com/2"), wordcount_i=9))
        assert m.int_column("wordcount_i").tolist() == [5, 9]


class TestCondenser:
    def make_doc(self):
        return Document(
            url="http://example.com/products/page.html",
            title="Example products",
            description="All the example products",
            text="This page lists products. Products are examples! Contact us.",
            anchors=[Anchor("http://example.com/about", "about"),
                     Anchor("http://other.org/x", "elsewhere")],
        )

    def test_word_stats(self):
        c = Condenser(self.make_doc())
        assert "products" in c.words
        st = c.words["products"]
        assert st.count == 2            # body occurrences counted
        assert st.posintext == 4        # first occurrence position
        assert c.phrase_count == 3

    def test_appearance_flags(self):
        c = Condenser(self.make_doc())
        assert c.words["products"].flags.get(FLAG_APP_DC_TITLE)
        assert c.words["example"].flags.get(FLAG_APP_DC_TITLE)
        assert c.words["page"].flags.get(FLAG_APP_DC_IDENTIFIER)  # in url
        assert not c.words["contact"].flags.get(FLAG_APP_DC_TITLE)

    def test_postings_rows_shape(self):
        c = Condenser(self.make_doc())
        hashes, rows = c.postings_rows()
        assert len(hashes) == len(c.words)
        assert rows.shape == (len(c.words), P.NF)
        assert rows[0, P.F_LOTHER] == 1 and rows[0, P.F_LLOCAL] == 1

    def test_tokenizer(self):
        assert words_of("Hello, World! 42 foo_bar") == ["hello", "world", "foo_bar"]
        assert len(phrases_of("One. Two! Three?")) == 3


class TestSegment:
    def docs(self):
        return [
            Document(url="http://alpha.com/jax", title="JAX on TPU",
                     text="JAX compiles numerical programs for TPU hardware. "
                          "The compiler fuses operations."),
            Document(url="http://beta.org/tpu", title="TPU architecture",
                     text="A TPU has a systolic array. Matrix units do the work.",
                     anchors=[Anchor("http://alpha.com/jax", "jax article")]),
            Document(url="http://gamma.net/cpu", title="CPU history",
                     text="The CPU is a general processor. History is long."),
        ]

    def test_store_and_search(self, tmp_path):
        seg = Segment(str(tmp_path / "seg"))
        for d in self.docs():
            seg.store_document(d)
        assert seg.doc_count() == 3

        hits = seg.term_search(include_words=["tpu"])
        assert len(hits) == 2
        # "jax" also matches beta via its anchor text pointing at alpha —
        # anchor-text words are indexed on the citing page with the
        # description flag; "compiler" is body-only on alpha
        hits = seg.term_search(include_words=["tpu", "compiler"])
        assert len(hits) == 1
        meta = seg.get_metadata(int(hits.docids[0]))
        assert meta.get("sku") == "http://alpha.com/jax"

    def test_all_or_nothing_rule(self, tmp_path):
        seg = Segment(None)
        for d in self.docs():
            seg.store_document(d)
        # "tpu" matches but "zebra" has no postings -> empty (TermSearch:56-58)
        assert len(seg.term_search(include_words=["tpu", "zebra"])) == 0

    def test_exclusion(self):
        seg = Segment(None)
        for d in self.docs():
            seg.store_document(d)
        hits = seg.term_search(include_words=["tpu"], exclude_words=["systolic"])
        assert len(hits) == 1  # beta excluded, alpha remains

    def test_citation_postprocessing(self):
        seg = Segment(None)
        for d in self.docs():
            seg.store_document(d)
        # beta.org/tpu cites alpha.com/jax after alpha was indexed; the
        # reference-count postprocessing must have updated alpha's row
        uh = url2hash("http://alpha.com/jax")
        meta = seg.metadata.get_by_urlhash(uh)
        assert meta.get("references_i") == 1
        assert meta.get("references_exthosts_i") == 1

    def test_remove_document(self):
        seg = Segment(None)
        for d in self.docs():
            seg.store_document(d)
        assert seg.remove_document(url2hash("http://beta.org/tpu"))
        assert len(seg.term_search(include_words=["tpu"])) == 1
        assert seg.doc_count() == 2

    def test_reindex_same_url_no_dup(self):
        seg = Segment(None)
        d = self.docs()[0]
        seg.store_document(d)
        seg.store_document(d)
        assert seg.doc_count() == 1
        assert len(seg.term_search(include_words=["jax"])) == 1

    def test_persistence(self, tmp_path):
        p = str(tmp_path / "seg")
        seg = Segment(p)
        for d in self.docs():
            seg.store_document(d)
        seg.close()
        seg2 = Segment(p)
        assert seg2.doc_count() == 3
        assert len(seg2.term_search(include_words=["tpu"])) == 2


class TestRWIRegressions:
    """Regressions for review findings: empty-bucket flush, merge ordering,
    deletion persistence, counter integrity, malformed urls."""

    def test_flush_after_delete_emptied_bucket(self):
        rwi = RWIIndex(None)
        th = word2hash("w")
        rwi.add(th, 1, np.zeros(P.NF, dtype=np.int32))
        rwi.delete_doc(1)
        assert rwi.ram_postings_count == 0      # counter decremented
        rwi.flush()                              # must not raise
        assert rwi.count(th) == 0

    def test_merge_preserves_newest_write(self):
        rwi = RWIIndex(None)
        th = word2hash("w")
        rwi.add(th, 5, np.full(P.NF, 111, dtype=np.int32)); rwi.flush()
        rwi.add(th, 9, np.zeros(P.NF, dtype=np.int32)); rwi.flush()  # big run
        rwi.add(th, 5, np.full(P.NF, 222, dtype=np.int32)); rwi.flush()
        assert rwi.get(th).feats[0, 0] == 222
        rwi.merge_runs(max_runs=2)
        assert rwi.get(th).feats[0, 0] == 222   # newest write survives merge

    def test_deletions_survive_restart(self, tmp_path):
        d = str(tmp_path / "rwi")
        rwi = RWIIndex(d)
        th = word2hash("w")
        rwi.add(th, 1, np.zeros(P.NF, dtype=np.int32))
        rwi.add(th, 2, np.zeros(P.NF, dtype=np.int32))
        rwi.flush()
        rwi.delete_doc(1)
        rwi.close()
        rwi2 = RWIIndex(d)
        assert rwi2.get(th).docids.tolist() == [2]

    def test_term_removal_survives_restart_and_readd(self, tmp_path):
        d = str(tmp_path / "rwi")
        rwi = RWIIndex(d)
        th = word2hash("moved")
        rwi.add(th, 1, np.zeros(P.NF, dtype=np.int32))
        rwi.flush()
        rwi.remove_term(th)                      # DHT handoff
        rwi.add(th, 7, np.zeros(P.NF, dtype=np.int32))  # re-added later
        rwi.close()
        rwi2 = RWIIndex(d)
        assert rwi2.get(th).docids.tolist() == [7]  # removal held, re-add kept

    def test_merge_persists_correct_order(self, tmp_path):
        d = str(tmp_path / "rwi")
        rwi = RWIIndex(d)
        th = word2hash("w")
        for val in (1, 2, 3):
            rwi.add(th, 5, np.full(P.NF, val, dtype=np.int32))
            rwi.flush()
        rwi.merge_runs(max_runs=2)
        rwi.close()
        rwi2 = RWIIndex(d)
        assert rwi2.get(th).feats[0, 0] == 3    # manifest kept history order


class TestMetadataRegressions:
    def test_set_field_survives_restart(self, tmp_path):
        p = str(tmp_path / "meta")
        m = MetadataStore(p)
        uh = url2hash("http://a.com/x")
        d = m.put(DocumentMetadata(uh, title="a", references_i=0))
        m.set_field(d, "references_i", 5)
        m.close()
        m2 = MetadataStore(p)
        assert m2.get_by_urlhash(uh).get("references_i") == 5


class TestMalformedUrls:
    def test_store_document_with_bad_anchor(self):
        from yacy_search_server_tpu.utils.hashes import url2hash as u2h
        seg = Segment(None)
        seg.store_document(Document(
            url="http://ok.com/x", title="t", text="body words here.",
            anchors=[Anchor("http://[broken", "bad"),
                     Anchor("http://example.com:99999/y", "bad port")]))
        assert seg.doc_count() == 1

    def test_url2hash_malformed(self):
        assert len(url2hash("http://[broken")) == 12
        assert len(url2hash("http://example.com:bad/x")) == 12


# -- the host conjunction reads what it needs (ISSUE 26) ----------------------
# term_search fetches its driving list and PROBES the far longer ones; the
# oracle is what it did before: join_constructive over rwi.get of every term

_UNIVERSE = 40_000


def _th(name: str) -> bytes:
    return name.encode("ascii").ljust(12, b"_")


def _drawn(rng, n: int, among=None) -> PostingsList:
    """n sorted docids (of `among`, else of the universe) under random rows."""
    pool = _UNIVERSE if among is None else among
    d = np.sort(rng.choice(pool, n, replace=False)).astype(np.int32)
    return PostingsList(d, rng.integers(0, 1 << 20, (n, P.NF)).astype(np.int32))


def _holding(rng, n: int, core: np.ndarray) -> PostingsList:
    """n random docids and, besides, every docid of `core`."""
    rows = rng.integers(0, 1 << 20, (len(core), P.NF)).astype(np.int32)
    return merge([_drawn(rng, n), PostingsList(core, rows)])


def _oracle_term_search(seg, inc, exc):
    lists = [seg.rwi.get(th) for th in inc]
    if any(len(c) == 0 for c in lists):
        return PostingsList.empty()
    joined = join_constructive(lists)
    for th in exc:
        ex = seg.rwi.get(th)
        if len(joined) and len(ex):
            joined = exclude_destructive(joined, ex)
    return joined


def _shape_two(seg, rng):
    seg.rwi.ingest_run({_th("long"): _drawn(rng, 20_000)})
    seg.rwi.ingest_run({_th("short"): _drawn(rng, 200)})
    return [_th("long"), _th("short")], [], "probe"


def _shape_three(seg, rng):
    core = _drawn(rng, 100).docids
    for name in ("long1", "long2"):
        seg.rwi.ingest_run({_th(name): _holding(rng, 20_000, core)})
    seg.rwi.ingest_run({_th("short"): _holding(rng, 300, core)})
    return [_th("long1"), _th("short"), _th("long2")], [], "probe"


def _shape_six(seg, rng):
    # two short lists of a size and a middling one are merged, three long
    # ones probed: the base is the shorter of the two short lists
    sizes = {"long1": 20_000, "short_b": 250, "mid": 1_000, "long2": 30_000,
             "short_a": 200, "long3": 25_000}
    core = _drawn(rng, 120).docids
    for name, n in sizes.items():
        seg.rwi.ingest_run({_th(name): _holding(rng, n - 120, core)})
    return [_th(name) for name in sizes], [], "probe"


def _shape_exclusion(seg, rng):
    for name, n in (("long1", 20_000), ("long2", 20_000), ("short", 300),
                    ("short2", 300)):
        seg.rwi.ingest_run({_th(name): _drawn(rng, n)})
    return [_th("short"), _th("long1")], [_th("long2"), _th("short2"),
                                          _th("absent")], "probe"


def _shape_generations(seg, rng):
    # the long term in three generations; the later ones override rows of
    # the earlier and add their own
    first = _drawn(rng, 20_000)
    seg.rwi.ingest_run({_th("long"): first})
    seg.rwi.ingest_run({_th("short"): _drawn(rng, 300, among=first.docids)})
    for _ in range(2):
        over = _drawn(rng, 3_000, among=first.docids)
        new = _drawn(rng, 2_000)
        seg.rwi.ingest_run({_th("long"): merge([new, over])})
    return [_th("short"), _th("long")], [], "probe"


def _shape_ram_delta(seg, rng):
    first = _drawn(rng, 20_000)
    seg.rwi.ingest_run({_th("long"): first})
    short = _drawn(rng, 300, among=first.docids)
    seg.rwi.ingest_run({_th("short"): short})
    # RAM rows over the runs: overriding rows of both terms, one docid
    # written twice (the last wins), and docids the runs do not hold
    seg.rwi.add_many(_th("long"), _drawn(rng, 150, among=short.docids))
    seg.rwi.add_many(_th("long"), _drawn(rng, 150, among=short.docids))
    seg.rwi.add_many(_th("long"), _drawn(rng, 500))
    seg.rwi.add_many(_th("short"), _drawn(rng, 40, among=first.docids))
    return [_th("long"), _th("short")], [], "probe"


def _shape_tombstones_short(seg, rng):
    long = _drawn(rng, 20_000)
    short = _drawn(rng, 300, among=long.docids)
    seg.rwi.ingest_run({_th("long"): long})
    seg.rwi.ingest_run({_th("short"): short})
    for d in short.docids[::7].tolist():
        seg.rwi.delete_doc(d)
    return [_th("short"), _th("long")], [], "probe"


def _shape_tombstones_long(seg, rng):
    long = _drawn(rng, 20_000)
    short = _drawn(rng, 300)
    seg.rwi.ingest_run({_th("long"): long})
    seg.rwi.ingest_run({_th("short"): short})
    for d in long.docids[::150].tolist():    # rows of the long list alone
        seg.rwi.delete_doc(d)
    return [_th("short"), _th("long")], [], "probe"


def _shape_long_tombstoned_short(seg, rng):
    # the list that is longer by its extents is the SHORTER one once the
    # tombstones are applied: the bounds cannot say, the old path decides
    a = _drawn(rng, 3_000)
    b = _drawn(rng, 300)
    seg.rwi.ingest_run({_th("a"): a})
    seg.rwi.ingest_run({_th("b"): b})
    alive = set(a.docids[::20].tolist()) | set(b.docids.tolist())
    for d in a.docids.tolist():
        if d not in alive:
            seg.rwi.delete_doc(d)
    return [_th("a"), _th("b")], [], "merge"


def _shape_absent(seg, rng):
    seg.rwi.ingest_run({_th("long"): _drawn(rng, 20_000)})
    seg.rwi.ingest_run({_th("short"): _drawn(rng, 200)})
    return [_th("short"), _th("nowhere"), _th("long")], [], "merge"


def _shape_equal(seg, rng):
    # lists of one length: the first in the query is the base
    first = _drawn(rng, 2_000)
    seg.rwi.ingest_run({_th("one"): first})
    seg.rwi.ingest_run({_th("two"): _drawn(rng, 2_000, among=first.docids)})
    return [_th("two"), _th("one")], [], "merge"


def _shape_extents_mislead(seg, rng):
    # `b` has the larger extents (three generations of the same 40 docids)
    # and the SHORTER list: the rows must come from it
    a = _drawn(rng, 100)
    seg.rwi.ingest_run({_th("a"): a})
    among = a.docids[:60]
    for _ in range(3):
        seg.rwi.ingest_run({_th("b"): PostingsList(
            among[:40].copy(),
            rng.integers(0, 1 << 20, (40, P.NF)).astype(np.int32))})
    return [_th("a"), _th("b")], [], "merge"


def _shape_same_term_twice(seg, rng):
    seg.rwi.ingest_run({_th("long"): _drawn(rng, 20_000)})
    seg.rwi.ingest_run({_th("short"): _drawn(rng, 200)})
    return [_th("short"), _th("long"), _th("short")], [], "probe"


def _shape_resident(seg, rng):
    # the long list already materialized (a paged index serves the probe
    # from the TermCache's copy)
    inc, exc, path = _shape_two(seg, rng)
    seg.rwi.get(_th("long"))
    return inc, exc, path


_SHAPES = [_shape_two, _shape_three, _shape_six, _shape_exclusion,
           _shape_generations, _shape_ram_delta, _shape_tombstones_short,
           _shape_tombstones_long, _shape_long_tombstoned_short,
           _shape_absent, _shape_equal, _shape_extents_mislead,
           _shape_same_term_twice, _shape_resident]


@pytest.mark.parametrize("paged", [False, True], ids=["ram", "paged"])
@pytest.mark.parametrize("shape", _SHAPES,
                         ids=[f.__name__[7:] for f in _SHAPES])
def test_term_search_probe_equals_join_over_get(shape, paged, tmp_path):
    seg = Segment(str(tmp_path / "seg") if paged else None)
    try:
        for seed in (1, 2):
            rng = np.random.default_rng(seed)
            inc, exc, path = shape(seg, rng)
            for order in (inc, inc[::-1]):
                how = {}
                got = seg.term_search(include_hashes=order,
                                      exclude_hashes=exc, how=how)
                want = _oracle_term_search(seg, order, exc)
                assert how["path"] == path
                assert got.docids.dtype == want.docids.dtype == np.int32
                assert got.feats.dtype == want.feats.dtype == np.int32
                np.testing.assert_array_equal(got.docids, want.docids)
                np.testing.assert_array_equal(got.feats, want.feats)
                assert len(want) or shape is _shape_absent
    finally:
        seg.close()


@pytest.mark.parametrize("paged", [False, True], ids=["ram", "paged"])
@pytest.mark.parametrize("shape", _SHAPES,
                         ids=[f.__name__[7:] for f in _SHAPES])
def test_rwi_probe_equals_rows_of_get(shape, paged, tmp_path):
    """rwi.probe at any docids (tombstoned ones and strangers among them)
    says what rwi.get holds there, generation for generation."""
    seg = Segment(str(tmp_path / "seg") if paged else None)
    try:
        rng = np.random.default_rng(3)
        inc, exc, _ = shape(seg, rng)
        at = np.sort(rng.choice(_UNIVERSE, 5_000, replace=False)
                     ).astype(np.int32)
        for th in set(inc + exc):
            found, rows = seg.rwi.probe(th, at)
            whole = seg.rwi.get(th)
            np.testing.assert_array_equal(found, np.isin(at, whole.docids))
            np.testing.assert_array_equal(
                rows, whole.feats[np.isin(whole.docids, at)])
            assert rows.dtype == np.int32 and rows.shape[1] == P.NF
            only, none = seg.rwi.probe(th, at, want_feats=False)
            assert none is None
            np.testing.assert_array_equal(only, found)
    finally:
        seg.close()


def test_term_search_at_cell_lengths_probes_and_caches_no_long_list(tmp_path):
    """The benchmark cell's And HighLow: 2,048 rows against 524,288. The
    long list is neither materialized nor put into the TermCache, and the
    answer is the old path's."""
    seg = Segment(str(tmp_path / "seg"))
    try:
        rng = np.random.default_rng(26)
        high = PostingsList(
            np.sort(rng.choice(2_500_000, 524_288, replace=False)
                    ).astype(np.int32),
            rng.integers(0, 1 << 20, (524_288, P.NF)).astype(np.int32))
        low = _drawn(rng, 2_048, among=high.docids[::64])
        low.docids[1::2] += 1       # half of them miss the long list
        run = seg.rwi.ingest_run({_th("high"): high})
        seg.rwi.ingest_run(
            {_th("low"): P.sort_dedupe(low.docids, low.feats)})
        how = {}
        got = seg.term_search(include_hashes=[_th("high"), _th("low")],
                              how=how)
        assert how["path"] == "probe"
        assert how["rows"] < 2 * 2_048
        assert seg.rwi.term_cache.peek((run.path, _th("high"))) is None
        assert seg.rwi.term_cache.resident_bytes < 1 << 20
        want = _oracle_term_search(seg, [_th("high"), _th("low")], [])
        assert 512 <= len(want) <= 2_048
        np.testing.assert_array_equal(got.docids, want.docids)
        np.testing.assert_array_equal(got.feats, want.feats)
    finally:
        seg.close()
