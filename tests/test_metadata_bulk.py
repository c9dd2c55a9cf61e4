"""A bulk import into the disk-backed metadata store, and merges.

`bulk_load` into an empty tail writes its batch as the next frozen
segment at once (what bulk_load + snapshot() leave behind), and a merge
of two segments no delete, override or facet suppression touches joins
their text columns and facet tables as stored. Both must read exactly as
the row-by-row paths they stand in for.
"""

import numpy as np
import pytest

from yacy_search_server_tpu.index import colstore
from yacy_search_server_tpu.index.metadata import (MAX_SEGMENTS,
                                                   MetadataStore,
                                                   metadata_from_parsed)


def _batch(lo, n, hosts=7):
    r = range(lo, lo + n)
    return ([f"{i:07d}{i % hosts:05d}".encode("ascii") for i in r],
            dict(sku=[f"http://h{i % hosts}.example/d{i}" for i in r],
                 title=[f"doc {i}" if i % 3 else "" for i in r],
                 host_s=[f"H{i % hosts}.Example" for i in r],
                 size_i=[1000 + i for i in r], wordcount_i=[0] * n))


def _load(st, batches, snapshot=True):
    for lo, n in batches:
        hashes, cols = _batch(lo, n)
        assert st.bulk_load(hashes, **cols) == lo
        if snapshot:
            st.snapshot()


def _reads(st, n):
    return ([st.text_value(i, "sku") for i in range(n)],
            [st.text_value(i, "title") for i in range(n)],
            [st.get(i).get("size_i") for i in range(n)],
            [st.docid(f"{i:07d}{i % 7:05d}".encode("ascii"))
             for i in range(n)],
            {h: st.facet_docids("host_s", f"h{h}.example").tolist()
             for h in range(7)})


def _expected(n):
    return ([f"http://h{i % 7}.example/d{i}" for i in range(n)],
            [f"doc {i}" if i % 3 else "" for i in range(n)],
            [1000 + i for i in range(n)], list(range(n)),
            {h: list(range(h, n, 7)) for h in range(7)})


@pytest.mark.parametrize("snapshot", (True, False))
def test_bulk_load_into_an_empty_tail_freezes_a_segment(tmp_path, snapshot):
    d = str(tmp_path / "meta")
    st = MetadataStore(d)
    _load(st, [(0, 40), (40, 25)], snapshot)
    assert len(st._segs) == 2 and not st._tail_hashes
    # a column the batch leaves at its default is not stored at all
    assert not st._segs[0].has_array("wordcount_i")
    assert st._segs[0].has_array("size_i")
    assert _reads(st, 65) == _expected(65)
    st.close()
    st2 = MetadataStore(d)          # the segments are in the manifest
    assert st2.capacity() == 65 and _reads(st2, 65) == _expected(65)
    st2.close()


def test_bulk_load_behind_a_tail_stays_in_the_tail(tmp_path):
    """Rows put before a bulk load hold the docids below it: the batch
    joins the tail, and the next snapshot freezes both in order."""
    st = MetadataStore(str(tmp_path / "meta"))
    hashes, cols = _batch(0, 6)
    one = {k: v[0] for k, v in cols.items()}
    assert st.put(metadata_from_parsed(hashes[0], one["sku"], one["title"],
                                       "", host_s=one["host_s"],
                                       size_i=one["size_i"])) == 0
    assert st.bulk_load(hashes[1:], **{k: v[1:] for k, v in cols.items()}) \
        == 1
    assert not st._segs and len(st._tail_hashes) == 6
    st.snapshot()
    assert len(st._segs) == 1 and _reads(st, 6) == _expected(6)
    st.close()


def test_a_ram_only_store_keeps_a_bulk_load_in_its_tail():
    st = MetadataStore(None)
    hashes, cols = _batch(0, 10)
    assert st.bulk_load(hashes, **cols) == 0
    assert not st._segs and len(st._tail_hashes) == 10
    assert _reads(st, 10) == _expected(10)


def _merged(st, untouched):
    st._rows_untouched_locked = lambda lo, hi: untouched
    st._merge_smallest_locked()
    st._persist_state_locked()
    return st._segs[0]


@pytest.mark.parametrize("sizes", ((5, 9), (1, 1), (64, 3)))
def test_a_merge_of_untouched_segments_reads_as_the_fold(tmp_path, sizes):
    """The joined segment equals, column for column, what the row-by-row
    fold writes for the same two segments."""
    segs = []
    for k, untouched in enumerate((True, False)):
        st = MetadataStore(str(tmp_path / f"m{k}"))
        _load(st, [(0, sizes[0]), (sizes[0], sizes[1])])
        segs.append((st, _merged(st, untouched)))
    (fast_st, fast), (slow_st, slow) = segs
    n = sum(sizes)
    assert fast.n == slow.n == n
    for f in ("sku", "title", "host_s"):
        assert fast.text_column(f) == slow.text_column(f)
    for f in ("urlhashes", "uh_sorted", "uh_order", "size_i",
              "facet_rows:host_s"):
        assert np.array_equal(fast.array(f), slow.array(f)), f
    assert fast.meta == slow.meta
    assert _reads(fast_st, n) == _reads(slow_st, n) == _expected(n)
    for st, _ in segs:
        st.close()


def test_a_merge_with_a_deleted_row_folds(tmp_path):
    """A deleted row is blanked by the fold: the join must not be taken."""
    st = MetadataStore(str(tmp_path / "meta"))
    _load(st, [(0, 4), (4, 4)])
    assert st._rows_untouched_locked(0, 8)
    st.delete(st.urlhash_of(2))
    assert not st._rows_untouched_locked(0, 8)
    assert st._rows_untouched_locked(3, 8)
    st._merge_smallest_locked()
    st._persist_state_locked()
    assert st._segs[0].text("sku", 2) == ""
    assert 2 not in st.facet_docids("host_s", "h2.example").tolist()
    st.close()


def test_many_bulk_batches_stay_under_the_segment_cap(tmp_path):
    d = str(tmp_path / "meta")
    st = MetadataStore(d)
    _load(st, [(i * 6, 6) for i in range(MAX_SEGMENTS + 4)])
    n = 6 * (MAX_SEGMENTS + 4)
    assert len(st._segs) <= MAX_SEGMENTS
    assert _reads(st, n) == _expected(n)
    st.close()
    st2 = MetadataStore(d)
    assert _reads(st2, n) == _expected(n)
    st2.close()


@pytest.mark.parametrize("col", (["a", "", "ünï", "dd"], ["", ""], ["x"]))
def test_write_segment_takes_a_text_column_as_encoded(tmp_path, col):
    listed, encoded = str(tmp_path / "l.seg"), str(tmp_path / "e.seg")
    colstore.write_segment(listed, len(col), {}, {"t": col})
    raw = colstore.SegmentReader(listed).text_raw("t")
    colstore.write_segment(encoded, len(col), {}, {"t": raw})
    assert colstore.SegmentReader(encoded).text_column("t") == col
    with pytest.raises(ValueError):
        colstore.write_segment(encoded, len(col) + 1, {}, {"t": raw})
