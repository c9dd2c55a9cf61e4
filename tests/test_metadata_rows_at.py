"""MetadataStore.rows_at — the search event's one gather (ISSUE 28).

The oracle is the single-value read path the store has always had
(LazyRow.get / MetadataStore.get / urlhash_of): rows_at must give the
same value field by field over frozen segments, the RAM tail, overrides,
deletions and docids past the end.
"""

import threading

import pytest

from yacy_search_server_tpu.index.metadata import (DOUBLE_FIELDS,
                                                   INT_FIELDS, TEXT_FIELDS,
                                                   DocumentMetadata,
                                                   MetadataStore)

TEXTS = ("sku", "title", "host_s", "language_s", "author", "text_t")
INTS = ("size_i", "wordcount_i", "references_i", "last_modified_days_i")


def _doc(i):
    # short and 12-char hashes both: the segment pads to S12 with NULs
    uh = (f"h{i}" if i % 3 == 0 else f"{i:07d}hash{i % 9}").encode()
    return DocumentMetadata(
        uh, sku=f"http://h{i % 4}.example/d{i}.html",
        title=f"titel {i} äö", host_s=f"h{i % 4}.example",
        language_s="de" if i % 2 else "",       # empty values between full
        text_t=f"körper {i} " * (i % 3),
        size_i=100 + i, wordcount_i=i, last_modified_days_i=19000 + i,
        lat_d=i / 7.0)


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    """Two snapshots (the second lacks `author` and `references_i`,
    which the first stores), a RAM tail, overrides on frozen and tail
    rows, deletions in a segment and in the tail."""
    st = MetadataStore(str(tmp_path_factory.mktemp("meta")))
    for i in range(12):
        d = _doc(i)
        d.fields.update(author=f"autor {i}", references_i=i * 3)
        st.put(d)
    st.snapshot()
    for i in range(12, 20):
        st.put(_doc(i))
    st.snapshot()
    for i in range(20, 26):
        st.put(_doc(i))
    assert len(st._segs) == 2 and len(st._tail_hashes) == 6
    assert st._segs[0].has_text("author") and not st._segs[1].has_text("author")
    assert not st._segs[1].has_array("references_i")
    st.set_field(3, "title", "overridden title")
    st.set_field(14, "references_i", 77)
    st.set_field(15, "host_s", "moved.example")
    st.set_field(22, "title", "tail title")
    st.delete(st.urlhash_of(5))
    st.delete(st.urlhash_of(23))
    yield st
    st.close()


# every docid kind in one list, unsorted, one repeated, three dead
DOCIDS = [21, 3, 0, 14, 5, 19, 12, 25, 23, 15, 11, 26, 1000, 3, 22]


@pytest.mark.parametrize("field", TEXTS + INTS + ("lat_d",))
def test_rows_at_reads_what_the_row_reads(store, field):
    rows = store.rows_at(DOCIDS, (field,))
    assert list(rows.cols) == [field]
    default = "" if field in TEXT_FIELDS else (
        0 if field in INT_FIELDS else 0.0)
    assert field in TEXT_FIELDS + INT_FIELDS + DOUBLE_FIELDS
    for pos, d in enumerate(DOCIDS):
        row = store.row(d)
        assert rows.alive[pos] == (row is not None)
        want = default if row is None else row.get(field)
        got = rows.cols[field][pos]
        assert got == want and type(got) is type(want), (d, got, want)
        if row is not None:
            assert got == store.get(d).get(field)


def test_rows_at_url_hashes_and_head(store):
    head_fields = ("sku", "title", "size_i")
    rows = store.rows_at(DOCIDS, ("host_s", "title"), head_fields, 9)
    assert len(rows.alive) == len(rows.urlhashes) == len(DOCIDS)
    # a field asked for both ways covers every docid, read once
    assert len(rows.cols["host_s"]) == len(rows.cols["title"]) == len(DOCIDS)
    assert len(rows.cols["sku"]) == len(rows.cols["size_i"]) == 9
    for pos, d in enumerate(DOCIDS):
        row = store.row(d)
        if pos < 9 and row is not None:
            assert rows.urlhashes[pos] == store.urlhash_of(d) == row.urlhash
            assert rows.cols["sku"][pos] == row.get("sku")
        else:
            assert rows.urlhashes[pos] == b""
    assert store.urlhash_of(0) == b"h0"        # NUL padding stripped
    assert rows.alive == [store.row(d) is not None for d in DOCIDS]
    assert rows.alive.count(False) == 4


@pytest.mark.parametrize("docids,fields,head_fields,head", [
    ([], ("title",), ("sku",), 5),              # nothing to read
    ([4, 2], (), (), 0),                        # liveness alone
    ([4, 2], (), ("sku",), 99),                 # head past the end
    ([1000, 26, 5], ("title", "size_i"), ("sku",), 3),   # all dead
])
def test_rows_at_edges(store, docids, fields, head_fields, head):
    rows = store.rows_at(docids, fields, head_fields, head)
    assert len(rows.alive) == len(docids)
    for f in fields:
        assert len(rows.cols[f]) == len(docids)
    for f in head_fields:
        assert len(rows.cols[f]) == min(head, len(docids))
    for pos, d in enumerate(docids):
        row = store.row(d)
        for f in fields:
            assert rows.cols[f][pos] == (
                row.get(f) if row else ("" if f in TEXT_FIELDS else 0))


def test_rows_at_absent_columns_and_unknown_field(store):
    # segment 1 stores neither: has_text false -> "", has_array false -> 0
    rows = store.rows_at([13, 2, 16], ("author", "references_i"))
    assert rows.cols["author"] == ["", "autor 2", ""]
    assert rows.cols["references_i"] == [0, 6, 0]
    with pytest.raises(KeyError):
        store.rows_at([1], ("no_such_field",))


def test_rows_at_ram_only_store():
    st = MetadataStore()            # a fresh crawl before its first snapshot
    for i in range(5):
        st.put(_doc(i))
    st.delete(st.urlhash_of(1))
    rows = st.rows_at([4, 1, 0, 7], ("host_s", "size_i"), ("title",), 4)
    assert rows.alive == [True, False, True, False]
    assert rows.cols["host_s"] == ["h0.example", "", "h0.example", ""]
    assert rows.cols["size_i"] == [104, 0, 100, 0]
    assert rows.cols["title"][0] == st.row(4).get("title")
    assert rows.urlhashes == [st.urlhash_of(4), b"", b"h0", b""]


def test_rows_at_under_threads_while_the_store_snapshots(tmp_path):
    """Readers gather while a writer puts and snapshots (segments appear,
    the tail empties): every gather is a consistent answer."""
    import sys
    st = MetadataStore(str(tmp_path / "meta"))
    for i in range(40):
        st.put(_doc(i))
    want = {d: (st.row(d).get("title"), st.row(d).get("size_i"),
                st.urlhash_of(d)) for d in range(40)}
    stop = threading.Event()
    bad: list = []

    def read(seed):
        docids = [(seed * 7 + k * 3) % 40 for k in range(16)]
        while not stop.is_set():
            rows = st.rows_at(docids, ("title",), ("size_i",), 16)
            for pos, d in enumerate(docids):
                got = (rows.cols["title"][pos], rows.cols["size_i"][pos],
                       rows.urlhashes[pos])
                if got != want[d] or not rows.alive[pos]:
                    bad.append((d, got))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        readers = [threading.Thread(target=read, args=(s,))
                   for s in range(6)]
        for t in readers:
            t.start()
        for i in range(40, 100):
            st.put(_doc(i))
            if i % 10 == 9:
                st.snapshot()
        stop.set()
        for t in readers:
            t.join(30)
        assert not any(t.is_alive() for t in readers)
    finally:
        stop.set()
        sys.setswitchinterval(old)
    assert not bad, bad[:3]
    assert len(st._segs) >= 6
    st.close()
