"""The search event's one-gather metadata join against the per-row join
it replaced (ISSUE 28).

The ORACLE is the parent commit's body, kept here and not in the package:
`_fill_results` counting the navigators per row, `_drain` popping one
candidate at a time, `_make_entry` reading nine fields through
`LazyRow.get`. Both events run the same ranking on the same segment, so
every page, every navigator count and the drain's own counters must be
equal.
"""

import random

import pytest

from yacy_search_server_tpu.document.document import Anchor, Document
from yacy_search_server_tpu.index.segment import Segment
from yacy_search_server_tpu.search.navigator import accumulate
from yacy_search_server_tpu.search.query import QueryParams
from yacy_search_server_tpu.search.searchevent import (ENTRY_FIELDS,
                                                       ResultEntry,
                                                       SearchEvent)
from yacy_search_server_tpu.utils import tracing

COMPARED = ("docid", "urlhash", "score", "url", "title", "host", "filetype",
            "language", "size", "wordcount", "lastmod_days", "references")


class OracleEvent(SearchEvent):
    """The parent's per-row metadata join."""

    def _fill_results(self, scores, docids):
        self._pending = list(zip(scores.tolist(), docids.tolist()))
        self._pending.reverse()
        meta = self.segment.metadata
        for d in docids.tolist():
            if not meta.is_deleted(int(d)) and int(d) < meta.capacity():
                accumulate(self.navigators, meta.row(int(d)))
        self._drain(self.query.offset + self.query.item_count)

    def _drain(self, need, facets=False):
        cushion = need * 2 + 6
        with self._lock:
            while self._pending and self._drained < cushion:
                score, docid = self._pending.pop()
                entry = self._row_entry(int(docid), int(score))
                if entry is None:
                    self.local_rwi_evicted += 1
                    continue
                self._drained += 1
                self._insert(entry)

    def _row_entry(self, docid, score):
        q = self.query
        m = self.segment.metadata.row(docid)
        if m is None:
            return None
        url = m.get("sku", "")
        title = m.get("title", "") or url
        if q.url_filter is not None and q.url_filter(url):
            return None
        if q.modifier.inurl and q.modifier.inurl.lower() not in url.lower():
            return None
        if q.modifier.intitle \
                and q.modifier.intitle.lower() not in title.lower():
            return None
        if q.modifier.author and q.modifier.author.lower() \
                not in (m.get("author") or "").lower():
            return None
        mod = q.modifier
        if mod.sitehost or mod.tld or mod.filetype or mod.protocol:
            host = (m.get("host_s") or "").lower()
            if mod.sitehost:
                want = mod.sitehost.lower()
                if host != want and not host.endswith("." + want):
                    return None
            if mod.tld and not host.endswith("." + mod.tld.lower()):
                return None
            if mod.filetype and (m.get("url_file_ext_s") or "").lower() \
                    != mod.filetype.lower():
                return None
            if mod.protocol and not url.lower().startswith(
                    mod.protocol.lower() + ":"):
                return None
        if q.modifier.keyword and q.modifier.keyword.lower() \
                not in (m.get("keywords") or "").lower():
            return None
        if q.goal.phrases:
            tl = m.get("text_t", "").lower()
            for ph in q.goal.phrases:
                if ph not in tl and ph not in title.lower():
                    return None
        return ResultEntry(
            docid=docid, urlhash=self.segment.metadata.urlhash_of(docid),
            score=score, url=url, title=title, snippet="",
            host=m.get("host_s", ""), filetype=m.get("url_file_ext_s", ""),
            language=m.get("language_s", ""), size=m.get("size_i", 0),
            wordcount=m.get("wordcount_i", 0),
            lastmod_days=m.get("last_modified_days_i", 0),
            references=m.get("references_i", 0))


@pytest.fixture(scope="module")
def segment(tmp_path_factory):
    """180 seeded documents over 30 hosts: 120 frozen in two metadata
    snapshots, 60 in the RAM tail, citations (references_i overrides on
    frozen rows), one document deleted."""
    rnd = random.Random(28)
    seg = Segment(str(tmp_path_factory.mktemp("seg")),
                  max_ram_postings=1_000_000)
    fruits = ["apple", "banana", "cherry"]
    for i in range(180):
        fruit = fruits[i % 3]
        host = f"h{rnd.randrange(30)}.example.{'org' if i % 4 else 'de'}"
        ext = "pdf" if i % 5 == 0 else "html"
        extra = " ".join(rnd.choice(["pie", "juice", "tart", "cake"])
                         for _ in range(rnd.randrange(1, 6)))
        anchors = ([Anchor(f"http://{host}/apple/{i - 3}.html", "see")]
                   if i % 7 == 0 and i >= 3 else [])
        seg.store_document(Document(
            url=f"http://{host}/{fruit}/{i}.{ext}",
            title=f"{fruit.title()} {'recipes' if i % 2 else 'notes'} {i}",
            text=f"the {fruit} and the apple {extra}. sweet {fruit} pie "
                 f"number {i}." * (1 + i % 3),
            author=f"Jane Doe {i % 6}" if i % 3 else "",
            keywords=["dessert"] if i % 4 == 0 else ["fruit"],
            mime_type="text/html", language="de" if i % 4 == 0 else "en",
            publish_date_days=18000 + i, anchors=anchors))
        if i in (59, 119):
            seg.metadata.snapshot()
    assert len(seg.metadata._segs) == 2 and seg.metadata._tail_hashes
    seg.metadata.delete(seg.metadata.urlhash_of(30))
    yield seg
    seg.close()


def _page(ev, offset, count=10):
    return [tuple(getattr(e, f) for f in COMPARED)
            for e in ev.results(offset=offset, count=count,
                                with_snippets=False)]


def _facets(ev):
    return {name: sorted(ev.facet(name, 1000)) for name in ev.navigators}


QUERIES = [
    "apple",                              # pages 1-3 of a term query
    "apple pie",                          # a conjunction
    "apple inurl:cherry",
    "apple intitle:recipes",
    "apple site:h3.example.org",
    "apple filetype:pdf",
    '"apple tart"',
    "apple author:(jane doe 2)",
    "apple keyword:dessert",
    "apple tld:de",
]


@pytest.mark.parametrize("querystring", QUERIES)
def test_pages_navigators_and_counters_equal_the_per_row_join(
        segment, querystring):
    new = SearchEvent(QueryParams.parse(querystring), segment)
    old = OracleEvent(QueryParams.parse(querystring), segment)
    assert _facets(new) == _facets(old)
    assert any(_facets(new).values())
    for offset in (0, 10, 20):            # later pages drain increments
        got, want = _page(new, offset), _page(old, offset)
        assert got == want
        assert (new.local_rwi_evicted, new._drained, len(new._pending)) \
            == (old.local_rwi_evicted, old._drained, len(old._pending))
    assert _page(new, 0)                   # every query has an answer
    assert _facets(new) == _facets(old)    # paging counts no facet twice


def test_rechecks_evict_and_the_drain_refills(segment):
    """inurl: is rechecked on the gathered values only: the first gather
    loses most of its cushion and the drain gathers again."""
    ev = SearchEvent(QueryParams.parse("apple inurl:cherry"), segment)
    assert ev.local_rwi_evicted > 0
    assert all("cherry" in e.url for e in ev.results(with_snippets=False))


def test_filter_only_columns_are_read_only_when_asked(segment):
    plain = SearchEvent(QueryParams.parse("apple"), segment)
    assert plain._entry_fields == ENTRY_FIELDS
    asked = SearchEvent(QueryParams.parse(
        'apple author:jane keyword:fruit "apple pie"'), segment)
    assert asked._entry_fields == ENTRY_FIELDS + ("author", "keywords",
                                                  "text_t")


def test_a_row_deleted_between_gather_and_drain_keeps_its_entry(segment):
    """The race a reader always had with a writer (row() then get()):
    the entry is built from what the gather read."""
    want = _page(SearchEvent(QueryParams.parse("banana"), segment), 0)
    meta = segment.metadata
    doomed = want[0][0]

    class Racing(SearchEvent):
        def _gather(self, docids, head, facets):
            rows = super()._gather(docids, head, facets)
            meta._deleted.add(doomed)      # the writer wins the race here
            return rows

    try:
        got = _page(Racing(QueryParams.parse("banana"), segment), 0)
    finally:
        meta._deleted.discard(doomed)
    assert got == want and got[0][0] == doomed


def test_dead_candidates_are_evicted_and_counted_nowhere(segment):
    """Docid 30 (deleted) and a docid past the end reach _fill_results
    the way a stale device answer hands them over."""
    import numpy as np
    docids = np.asarray([33, 30, 10_000, 36, 39], np.int32)
    scores = np.asarray([50, 40, 30, 20, 10], np.int64)

    evs = []
    for cls in (SearchEvent, OracleEvent):
        ev = cls(QueryParams.parse("zzznothingzzz"), segment)
        assert not ev._pending and ev._drained == 0
        ev._fill_results(scores, docids)
        evs.append(ev)
    new, old = evs
    assert new.local_rwi_evicted == old.local_rwi_evicted == 2
    assert new._drained == old._drained == 3
    assert _page(new, 0) == _page(old, 0)
    assert [e[0] for e in _page(new, 0)] == [33, 36, 39]
    assert _facets(new) == _facets(old)
    assert sum(c for _, c in new.facet("hosts", 100)) == 3


def test_spans_name_the_join(segment):
    """`search.metajoin` around the one gather (items = candidates read),
    `search.resultlist` with `rows` (entries built) and `gathered`."""
    with tracing.trace("test.metajoin") as root:
        ev = SearchEvent(QueryParams.parse("apple"), segment)
    spans = {s.name: s for s in tracing.get_trace(root.ctx[0]).spans}
    join, gather = spans["search.resultlist"], spans["search.metajoin"]
    n_cand = ev._drained + ev.local_rwi_evicted + len(ev._pending)
    assert join.attrs["gathered"] == n_cand > 26
    assert join.attrs["rows"] == ev._drained == 26
    assert gather.parent == join.sid
