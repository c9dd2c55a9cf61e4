"""Read-side integrity (ISSUE 10 tentpole a + satellites).

- PagedRun.open on truncated/garbage ``.tix``/``.dat`` raises a TYPED
  ``CorruptRunError`` (never an unhandled struct/mmap crash).
- A span failing its read-time checksum QUARANTINES the run: the query
  answers from surviving generations/RAM, the run's TermCache entries
  are invalidated, and the corruption counters attribute it.
- Colstore segments scrub at open and verify columns lazily on first
  read.
- Journal lines are crc-prefixed; replay counts torn tails
  (``yacy_journal_torn_tail_total``) and legacy prefix-free journals
  stay readable.
- ``io.torn_write`` / ``io.error`` faultpoints exercise the durable
  write helpers' crash artifacts.
"""

import os

import numpy as np
import pytest

from yacy_search_server_tpu.index import colstore, integrity
from yacy_search_server_tpu.index import postings as P
from yacy_search_server_tpu.index.integrity import (CorruptRunError,
                                                    CorruptSegmentError)
from yacy_search_server_tpu.index.pagedrun import PagedRun, TermCache
from yacy_search_server_tpu.index.postings import PostingsList
from yacy_search_server_tpu.index.rwi import RWIIndex
from yacy_search_server_tpu.utils import faultinject


@pytest.fixture(autouse=True)
def _clean():
    integrity.reset_counters()
    integrity.set_verify_on_read(True)
    faultinject.clear()
    yield
    integrity.reset_counters()
    integrity.set_verify_on_read(True)
    faultinject.clear()


def _terms(n_terms=3, n=50, seed=0):
    rng = np.random.default_rng(seed)
    out = {}
    for i in range(n_terms):
        th = bytes(f"term{i:08d}", "ascii")
        docids = np.arange(n, dtype=np.int32)
        feats = rng.integers(0, 100, (n, P.NF)).astype(np.int32)
        out[th] = PostingsList(docids, feats)
    return out


def _write_run(tmp_path, name="run-000000.dat", **kw):
    path = str(tmp_path / name)
    return path, PagedRun.write(path, _terms(**kw))


# -- PagedRun open scrub (satellite: typed errors, not struct crashes) ------

def test_open_truncated_dat_raises_typed(tmp_path):
    path, _run = _write_run(tmp_path)
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) // 2)
    with pytest.raises(CorruptRunError, match="truncated"):
        PagedRun.open(path)
    assert integrity.corruption_counts()[("run", "error")] >= 1


def test_open_garbage_tix_raises_typed(tmp_path):
    path, _run = _write_run(tmp_path)
    with open(path[:-4] + ".tix", "w") as f:
        f.write("\x00\x01 not a run index \x02")
    with pytest.raises(CorruptRunError):
        PagedRun.open(path)


def test_open_tix_footer_crc_mismatch_raises(tmp_path):
    path, _run = _write_run(tmp_path)
    tix = path[:-4] + ".tix"
    raw = open(tix).read()
    # corrupt a span line but leave the footer: the footer crc catches
    raw = raw.replace(" 0 50 ", " 0 51 ", 1)
    open(tix, "w").write(raw)
    with pytest.raises(CorruptRunError, match="checksum"):
        PagedRun.open(path)


def test_open_missing_tix_raises_typed(tmp_path):
    path, _run = _write_run(tmp_path)
    os.remove(path[:-4] + ".tix")
    with pytest.raises(CorruptRunError):
        PagedRun.open(path)


def test_legacy_pr1_file_still_opens(tmp_path):
    """A PR1 .tix (no checksums) opens and serves — no claim, no
    verification."""
    terms = _terms(n_terms=1)
    path = str(tmp_path / "run-000000.dat")
    th = list(terms)[0]
    p = terms[th]
    with open(path, "wb") as f:
        f.write(np.ascontiguousarray(p.docids, "<i4").tobytes())
        f.write(np.ascontiguousarray(p.feats, "<i4").tobytes())
    with open(path[:-4] + ".tix", "w") as f:
        f.write(f"PR1 {len(p)} -1\n{th.decode()} 0 {len(p)}\n")
    run = PagedRun.open(path)
    got = run.get(th)
    np.testing.assert_array_equal(got.docids, p.docids)


# -- lazy verify-on-read + quarantine ---------------------------------------

def _flip_dat_bytes(path, offset=16):
    with open(path, "r+b") as f:
        f.seek(offset)
        b = f.read(4)
        f.seek(offset)
        f.write(bytes(x ^ 0xFF for x in b))


def test_span_read_detects_flipped_bytes(tmp_path):
    path, run = _write_run(tmp_path)
    run.close()
    _flip_dat_bytes(path)
    run = PagedRun.open(path)           # scrub passes: sizes are fine
    with pytest.raises(CorruptRunError, match="span checksum"):
        run.get(b"term00000000")


def test_verify_off_serves_unchecked(tmp_path):
    path, run = _write_run(tmp_path)
    run.close()
    _flip_dat_bytes(path)
    integrity.set_verify_on_read(False)
    run = PagedRun.open(path)
    assert run.get(b"term00000000") is not None   # no claim made


def test_rwi_quarantines_corrupt_run_and_serves_survivors(tmp_path):
    """The tentpole contract: a corrupt span NEVER crashes a query —
    the run quarantines (TermCache invalidated, counters bumped) and
    the term answers from the surviving generations + RAM."""
    th = b"sharedterm00"
    idx = RWIIndex(data_dir=str(tmp_path / "rwi"))
    rng = np.random.default_rng(7)
    # generation 1 (will be corrupted) and generation 2 (survivor)
    idx.add_many(th, PostingsList(
        np.arange(100, dtype=np.int32),
        rng.integers(0, 100, (100, P.NF)).astype(np.int32)))
    run1 = idx.flush()
    idx.add_many(th, PostingsList(
        np.arange(100, 200, dtype=np.int32),
        rng.integers(0, 100, (200 - 100, P.NF)).astype(np.int32)))
    idx.flush()
    assert idx.run_count() == 2
    survivors = idx.get(th)
    # corrupt generation 1 on disk and drop its cached postings
    _flip_dat_bytes(run1.path)
    idx.term_cache.invalidate_run(run1.path)
    out = idx.get(th)                   # NOT an exception
    assert idx.run_count() == 1, "corrupt run must leave serving"
    # the survivor generation's rows still serve
    assert set(out.docids.tolist()) == set(range(100, 200))
    assert integrity.corruption_counts()[("run", "quarantined")] == 1
    assert integrity.corruption_counts()[("run", "error")] >= 1
    # quarantined run's TermCache entries are gone
    assert idx.term_cache.get((run1.path, th)) is None
    # stable: the next read answers identically, no double-quarantine
    out2 = idx.get(th)
    np.testing.assert_array_equal(out.docids, out2.docids)
    assert integrity.corruption_counts()[("run", "quarantined")] == 1
    assert np.array_equal(np.sort(out.docids),
                          np.sort(survivors.docids[survivors.docids >= 100]))


# -- the probe path (ISSUE 26): a span reached only through a conjunction ----

def test_span_probe_detects_flipped_bytes(tmp_path):
    path, run = _write_run(tmp_path)
    run.close()
    _flip_dat_bytes(path)
    run = PagedRun.open(path)
    with pytest.raises(CorruptRunError, match="span checksum"):
        run.probe(b"term00000000", np.arange(5, dtype=np.int32))
    assert integrity.corruption_counts()[("run", "error")] == 1


def test_span_probe_verify_off_serves_unchecked(tmp_path):
    path, run = _write_run(tmp_path)
    run.close()
    _flip_dat_bytes(path)
    integrity.set_verify_on_read(False)
    run = PagedRun.open(path)
    found, rows = run.probe(b"term00000000", np.arange(5, dtype=np.int32))
    assert len(rows) == int(found.sum()) > 0      # no claim made
    assert integrity.verified_total() == 0


def test_span_probe_verifies_once_per_open_run(tmp_path):
    """A span is held to its crc before its first row is served and not
    again while the run stays open; drop_term forgets it; a term the
    TermCache holds is served from that (verified) copy."""
    path, run = _write_run(tmp_path)
    run.close()
    cache = TermCache()
    run = PagedRun.open(path, cache)
    at = np.arange(0, 50, 5, dtype=np.int32)
    base = integrity.verified_total()
    run.probe(b"term00000000", at)
    assert integrity.verified_total() == base + 1
    for _ in range(3):
        found, rows = run.probe(b"term00000000", at)
        assert found.all() and rows.shape == (10, P.NF)
    assert integrity.verified_total() == base + 1
    run.probe(b"term00000001", at, want_feats=False)
    assert integrity.verified_total() == base + 2
    assert cache.resident_bytes == 0, "a probe materializes nothing"
    # a flip AFTER the check goes unseen until the run is opened again:
    # the granularity this path states (get() checks each materialization)
    _flip_dat_bytes(path)
    with pytest.raises(CorruptRunError):
        PagedRun.open(path, cache).probe(b"term00000000", at)
    run.drop_term(b"term00000001")
    assert run.probe(b"term00000001", at) is None
    assert b"term00000001" not in run._verified


def _conjunction_over_two_generations(tmp_path):
    """A long term in two paged generations and a short one in a third."""
    from yacy_search_server_tpu.index.segment import Segment
    seg = Segment(str(tmp_path / "seg"))
    rng = np.random.default_rng(26)
    long_th, short_th = b"longterm0000", b"shortterm000"
    gen1 = PostingsList(np.arange(0, 8_000, dtype=np.int32),
                        rng.integers(0, 100, (8_000, P.NF)).astype(np.int32))
    gen2 = PostingsList(np.arange(4_000, 12_000, dtype=np.int32),
                        rng.integers(0, 100, (8_000, P.NF)).astype(np.int32))
    short = PostingsList(np.arange(0, 12_000, 100, dtype=np.int32),
                         rng.integers(0, 100, (120, P.NF)).astype(np.int32))
    run1 = seg.rwi.ingest_run({long_th: gen1})
    seg.rwi.ingest_run({long_th: gen2})
    seg.rwi.ingest_run({short_th: short})
    return seg, run1, [long_th, short_th]


def test_conjunction_quarantines_corrupt_probed_run(tmp_path):
    """Flipped bytes in the long term's span, reached only by the probe
    of a conjunction: never a crash, the run quarantines and is counted
    exactly as through get(), the survivors answer."""
    seg, run1, inc = _conjunction_over_two_generations(tmp_path)
    try:
        _flip_dat_bytes(run1.path)
        how = {}
        out = seg.term_search(include_hashes=inc, how=how)   # NOT an exception
        assert how["path"] == "probe"
        assert seg.rwi.run_count() == 2, "corrupt run must leave serving"
        assert integrity.corruption_counts()[("run", "quarantined")] == 1
        assert integrity.corruption_counts()[("run", "error")] == 1
        # the surviving generation's rows of the long term still join
        assert out.docids.tolist() == list(range(4_000, 12_000, 100))
        # stable: the next conjunction answers identically, no second
        # quarantine
        again = seg.term_search(include_hashes=inc)
        np.testing.assert_array_equal(out.docids, again.docids)
        np.testing.assert_array_equal(out.feats, again.feats)
        assert integrity.corruption_counts()[("run", "quarantined")] == 1
    finally:
        seg.close()


def test_conjunction_checksums_a_probed_span_once(tmp_path):
    seg, run1, inc = _conjunction_over_two_generations(tmp_path)
    try:
        base = integrity.verified_total()
        first = seg.term_search(include_hashes=inc)
        # the short list materialized (1) + two spans of the long term
        assert integrity.verified_total() == base + 3
        again = seg.term_search(include_hashes=inc)
        assert integrity.verified_total() == base + 3
        np.testing.assert_array_equal(first.feats, again.feats)
        assert seg.rwi.term_cache.peek((run1.path, inc[0])) is None
    finally:
        seg.close()


def test_conjunction_verify_off_serves_unchecked(tmp_path):
    seg, run1, inc = _conjunction_over_two_generations(tmp_path)
    try:
        _flip_dat_bytes(run1.path)
        integrity.set_verify_on_read(False)
        out = seg.term_search(include_hashes=inc)
        assert len(out) == 120 and seg.rwi.run_count() == 3
        assert integrity.corruption_counts().get(("run", "error"), 0) == 0
    finally:
        seg.close()


def test_rwi_open_quarantines_corrupt_run(tmp_path):
    """A run that fails open-scrub at startup quarantines instead of
    refusing to start the node."""
    d = str(tmp_path / "rwi")
    idx = RWIIndex(data_dir=d)
    idx.add_many(b"opentermAAAA", PostingsList(
        np.arange(10, dtype=np.int32),
        np.ones((10, P.NF), np.int32)))
    run = idx.flush()
    idx.close()
    with open(run.path, "r+b") as f:
        f.truncate(8)
    idx2 = RWIIndex(data_dir=d)
    assert idx2.run_count() == 0
    assert len(idx2.get(b"opentermAAAA")) == 0     # served (empty), no crash
    assert integrity.corruption_counts()[("run", "quarantined")] == 1


# -- colstore segments -------------------------------------------------------

def test_segment_open_scrub_truncation(tmp_path):
    path = str(tmp_path / "t.seg")
    colstore.write_segment(path, 4,
                           {"a": np.arange(4, dtype=np.int64)},
                           {"t": ["x", "y", "z", "w"]})
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) - 8)
    with pytest.raises(CorruptSegmentError):
        colstore.SegmentReader(path)
    assert integrity.corruption_counts()[("segment", "error")] >= 1


def test_segment_column_crc_mismatch_serves_degraded_counted(tmp_path):
    """A content crc mismatch on a segment column SERVES the data
    (there is no redundant generation to quarantine to; raising would
    turn every query touching the column into a permanent 500) but is
    loudly counted — the storage_corruption rule's critical edge dumps
    the incident."""
    path = str(tmp_path / "t.seg")
    colstore.write_segment(path, 8,
                           {"a": np.arange(8, dtype=np.int64)}, {})
    r = colstore.SegmentReader(path)
    spec = r.header["arrays"]["a"]
    # flip a payload byte of column a
    with open(path, "r+b") as f:
        f.seek(r._payload + spec["off"])
        f.write(b"\xff")
    v0 = integrity.verified_total()
    got = colstore.SegmentReader(path).array("a")
    assert got is not None                      # served, not raised
    assert integrity.corruption_counts()[
        ("segment", "served_degraded")] == 1
    # a clean reopen verifies exactly once per column
    with open(path, "r+b") as f:
        f.seek(r._payload + spec["off"])
        f.write(b"\x00")
    r2 = colstore.SegmentReader(path)
    r2.array("a")
    r2.array("a")
    assert integrity.verified_total() >= v0 + 1


def test_segment_garbage_header_is_typed(tmp_path):
    path = str(tmp_path / "junk.seg")
    with open(path, "wb") as f:
        f.write(b"YTCS0001" + b"\xff" * 64)
    with pytest.raises(CorruptSegmentError):
        colstore.SegmentReader(path)


# -- journal crc lines + torn-tail accounting --------------------------------

def test_crc_line_roundtrip_and_detection():
    line = integrity.crc_line('{"a": 1}')
    payload, ok = integrity.check_line(line)
    assert ok and payload == '{"a": 1}'
    bad = line[:-2] + ("0" if line[-2] != "0" else "1") + line[-1]
    _, ok = integrity.check_line(bad)
    assert not ok
    # legacy line: no prefix, no claim
    payload, ok = integrity.check_line('{"legacy": true}')
    assert ok and payload == '{"legacy": true}'


def test_metadata_torn_tail_is_counted(tmp_path):
    from yacy_search_server_tpu.index.metadata import (MetadataStore,
                                                       metadata_from_parsed)
    from yacy_search_server_tpu.utils.hashes import url2hash
    d = str(tmp_path / "meta")
    st = MetadataStore(data_dir=d)
    st.put(metadata_from_parsed(url2hash("http://a.example/"),
                                "http://a.example/", "A", "text a"))
    st.put(metadata_from_parsed(url2hash("http://b.example/"),
                                "http://b.example/", "B", "text b"))
    jname = st._journal_name
    st._journal.close()
    st._journal = None
    with open(os.path.join(d, jname), "a", encoding="utf-8") as f:
        f.write('deadbeef {"_id": "torn half rec')     # torn tail
    before = integrity.torn_tail_counts()["metadata"]
    st2 = MetadataStore(data_dir=d)
    assert len(st2) == 2                              # both docs intact
    assert integrity.torn_tail_counts()["metadata"] == before + 1


def test_unicode_line_separators_do_not_shatter_records(tmp_path):
    """ensure_ascii=False payloads can carry U+2028/U+2029 (real web
    text); the replay scaffold must split records on \\n ONLY —
    str.splitlines() would shatter the record into crc-failing
    fragments, dropping the row and raising a FALSE corruption alarm
    on every restart."""
    import json
    p = str(tmp_path / "u.jsonl")
    rec = {"source_id_s": "AAAAAAAAAAAA",
           "target_linktext_s": "line one line two end"}
    with open(p, "w", encoding="utf-8") as f:
        f.write(integrity.crc_line(
            json.dumps(rec, ensure_ascii=False)) + "\n")
    got = list(integrity.journal_records(p, "webgraph"))
    assert got == [rec]
    assert integrity.corruption_counts()[("journal", "error")] == 0
    assert integrity.torn_tail_counts()["webgraph"] == 0


def test_non_utf8_bytes_classified_not_crashing(tmp_path):
    """A bit-flipped byte that breaks UTF-8 decoding must surface as a
    classified (counted) damaged record — never an uncaught
    UnicodeDecodeError that refuses startup."""
    import json
    p = str(tmp_path / "b.jsonl")
    with open(p, "wb") as f:
        f.write(integrity.crc_line(json.dumps({"n": 1})).encode() + b"\n")
        f.write(b'\xff\xfe garbage bytes \xff\n')
        f.write(integrity.crc_line(json.dumps({"n": 2})).encode() + b"\n")
    got = list(integrity.journal_records(p, "frontier"))
    assert got == [{"n": 1}, {"n": 2}]
    assert integrity.corruption_counts()[("journal", "error")] == 1


def test_rwi_damaged_legacy_term_line_does_not_refuse_startup(tmp_path):
    """A damaged crc-less legacy 'T' record must classify like the 'D'
    branch, not raise ValueError out of RWIIndex open."""
    d = str(tmp_path / "rwi")
    os.makedirs(d)
    with open(os.path.join(d, "deletions.log"), "w",
              encoding="ascii") as f:
        f.write("D 3\nT abcdef123456 4x7\nD 5\n")
    idx = RWIIndex(data_dir=d)              # must not raise
    assert {3, 5} <= idx._tombstones
    assert integrity.corruption_counts()[("journal", "error")] >= 1


def test_rwi_deletion_journal_crc_and_torn_tail(tmp_path):
    d = str(tmp_path / "rwi")
    idx = RWIIndex(data_dir=d)
    idx.add_many(b"delj_termAAA", PostingsList(
        np.arange(10, dtype=np.int32), np.ones((10, P.NF), np.int32)))
    idx.flush()
    idx.delete_doc(3)
    idx.close()
    with open(os.path.join(d, "deletions.log"), "a",
              encoding="ascii") as f:
        f.write("00000000 D 9")                       # bad crc tail
    idx2 = RWIIndex(data_dir=d)
    assert 3 in idx2._tombstones
    assert 9 not in idx2._tombstones                  # torn line dropped
    assert integrity.torn_tail_counts()["rwi"] >= 1


# -- io faultpoints (satellite: every registered point exercised) ------------

def test_io_torn_write_leaves_target_untouched(tmp_path):
    path = str(tmp_path / "state.json")
    colstore.write_durable(path, '{"v": 1}', encoding="utf-8")
    faultinject.set_fault("io.torn_write", "state.json:3")
    with pytest.raises(faultinject.InjectedFault):
        colstore.write_durable(path, '{"v": 2}', encoding="utf-8")
    # the rename never happened: the previous durable state survives
    assert open(path).read() == '{"v": 1}'


def test_io_error_nth_matching_write_raises(tmp_path):
    path = str(tmp_path / "x.json")
    faultinject.set_fault("io.error", "x.json:2")
    colstore.write_durable(path, "one", encoding="utf-8")     # 1st: ok
    with pytest.raises(faultinject.InjectedFault):
        colstore.write_durable(path, "two", encoding="utf-8")  # 2nd: boom
    assert open(path).read() == "one"
    colstore.write_durable(path, "three", encoding="utf-8")   # consumed
    assert open(path).read() == "three"


def test_torn_journal_append_recovers_counted(tmp_path):
    """A journal append torn mid-line is exactly the kill−9 artifact:
    replay keeps every complete record and counts the torn tail."""
    p = str(tmp_path / "j.jsonl")
    f = open(p, "a", encoding="utf-8")
    colstore.journal_append(f, '{"n": 1}')
    faultinject.set_fault("io.torn_write", "j.jsonl:12")
    with pytest.raises(faultinject.InjectedFault):
        colstore.journal_append(f, '{"n": 2}')
    f.close()
    lines = open(p).read().splitlines()
    assert len(lines) == 2 and not lines[1].endswith("}")
    payload, ok = integrity.check_line(lines[0])
    assert ok
    _, ok = integrity.check_line(lines[1])
    assert not ok                                     # detected as torn
