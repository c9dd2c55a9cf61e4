"""A store whose vocabulary outgrows its join-bitmap slots (the
deployment `lucene-wikimedium10m-longlists`, cell `wiki.long`).

Six lists of bitmap size for two slots, on seeded lists drawn by the
benchmark's own corpus at the configuration's shapes cut 64-fold: the
lists past the cap carry `jslot` -1 and join by sort-merge, and every
combination of memberships answers what `Segment.term_search` and the
plain reference (benchmarks/reference.py) answer, solo and through the
batcher. The counters say which membership served.
"""

import threading

import numpy as np
import pytest

from benchmarks import corpus, reference, run
from yacy_search_server_tpu.index.devstore import (DeviceArena,
                                                    DeviceSegmentStore)
from yacy_search_server_tpu.index.postings import PostingsList
from yacy_search_server_tpu.index.segment import Segment
from yacy_search_server_tpu.ops.ranking import CardinalRanker, RankingProfile
from yacy_search_server_tpu.utils.hashes import word2hash

SEED = 2 ** 31 + 27
SLOTS = 2
HIGH = 6
K = 128         # the page looks at the best 80 (reference.page)

# (query, sort-merge?): the rare list is the FIRST of the shortest, a
# partner without a slot makes the conjunction a sort-merge one
CASES = [
    ("zh2 zh3", True),          # slot-less rare, slot-less partner
    ("zh3 zh0", False),         # slot-less rare, slotted partner
    ("zh0 zh1", False),         # both slotted: the accepted cell's join
    ("zh1 zh4", True),          # slotted rare, slot-less partner
    ("zh5 zm0", True),          # Med rare, slot-less High partner
    ("zh0 zm1", False),         # Med rare, slotted High partner
    ("zh0 zh3 zm2", True),      # three terms, one partner of each kind
    ("zh2 zh4 zm3", True),      # three terms, both partners slot-less
]


def _layout():
    """The configuration's corpus cut 64-fold as a rehearsal cuts it, and
    to a handful of lists."""
    cfg = run.scaled(corpus.load_config("lucene-wikimedium10m-longlists"),
                     64)
    for tier, lists in (("high", HIGH), ("med", 4), ("low", 4)):
        cfg["corpus"]["tiers"][tier]["lists"] = lists
    assert cfg["corpus"]["tiers"]["high"]["length"] == 1024
    return corpus.layout(cfg, SEED)


@pytest.fixture(scope="module")
def store():
    """(segment, layout, reference), the lists loaded in layout order, one
    run each, as benchmarks/run.py loads them."""
    lay = _layout()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(DeviceSegmentStore, "JOIN_BITMAP_MIN", 1024)
        mp.setattr(DeviceArena, "JOIN_BITMAP_SLOTS", SLOTS)
        seg = Segment(max_ram_postings=10)
        seg.enable_device_serving()
        for term in lay.terms:
            docids, feats = corpus.term_list(lay, term, SEED)
            seg.rwi.ingest_run(
                {word2hash(term.name): PostingsList(docids, feats)})
        yield seg, lay, reference.Reference(lay, SEED)
        seg.close()


def _hashes(query):
    return [word2hash(w) for w in query.split()]


def _page(docids, scores, lay):
    return reference.page(np.asarray(docids), np.asarray(scores, np.int64),
                          lay.hosts)


def _host_page(seg, query, lay):
    joined = seg.term_search(include_hashes=_hashes(query))
    s, d = CardinalRanker(RankingProfile()).rank(joined, k=K)
    return _page(d, s, lay)


def _device_page(seg, query, lay):
    out = seg.devstore.rank_join(_hashes(query), [], RankingProfile(), "en",
                                 k=K)
    assert out is not None, f"the store declined {query!r}"
    return _page(out[1], out[0], lay)


def test_lists_past_the_cap_carry_no_slot_and_are_counted(store):
    seg, lay, _ref = store
    ds = seg.devstore
    slots = [ds.spans_for(word2hash(f"zh{i}"))[0].jslot
             for i in range(HIGH)]
    assert slots == [0, 1] + [-1] * (HIGH - SLOTS)
    # a Med list is under JOIN_BITMAP_MIN: it never asks
    assert ds.spans_for(word2hash("zm0"))[0].jslot == -1
    c = ds.counters()
    assert c["join_bitmap_slots"] == SLOTS
    assert c["join_bitmap_refused"] == HIGH - SLOTS


@pytest.mark.parametrize("query,sortmerge", CASES,
                         ids=[q.replace(" ", "+") for q, _ in CASES])
def test_a_conjunction_equals_the_host_and_the_reference(store, query,
                                                         sortmerge):
    seg, lay, ref = store
    ds = seg.devstore
    c0 = ds.counters()
    got = _device_page(seg, query, lay)
    want = ref.answer(query)
    assert len(want) == reference.PAGE
    assert got == want
    assert _host_page(seg, query, lay) == want
    c1 = ds.counters()
    assert c1["join_served"] - c0["join_served"] == 1
    assert c1["join_sm_served"] - c0["join_sm_served"] == int(sortmerge)
    assert c1["fallbacks"] == c0["fallbacks"]


def test_through_the_batcher_under_8_threads(store):
    """Every case from 8 threads at once: the answers of the solo path,
    and the two memberships still add up to `join_served`."""
    seg, lay, ref = store
    ds = seg.devstore
    want = {q: ref.answer(q) for q, _ in CASES}
    ds.enable_batching(max_batch=16)
    c0 = ds.counters()
    got, errors = {}, []

    def worker(t):
        try:
            for q, _ in CASES[t % len(CASES):] + CASES[:t % len(CASES)]:
                got[t, q] = _device_page(seg, q, lay)
        except BaseException as e:      # re-raised on the test's thread
            errors.append(e)

    ts = [threading.Thread(target=worker, args=(t,)) for t in range(8)]
    for th in ts:
        th.start()
    for th in ts:
        th.join()
    assert not errors, errors
    assert len(got) == 8 * len(CASES)
    for (_t, q), page in got.items():
        assert page == want[q], q
    c1 = ds.counters()
    served = c1["join_served"] - c0["join_served"]
    sm = c1["join_sm_served"] - c0["join_sm_served"]
    assert served == 8 * len(CASES)
    assert sm == 8 * sum(s for _, s in CASES)
    assert served - sm == 8 * sum(not s for _, s in CASES)   # bitmap-only
    assert c1["join_fallbacks"] == c0["join_fallbacks"]


def test_a_window_the_join_table_cannot_cover_declines_to_the_host(store):
    """`mode()` needs the partner's whole sorted segment inside the join
    table it snapshotted. A consistent snapshot always covers (jstart +
    count <= rows written <= capacity), so the table is cut short here,
    under the last High list: that conjunction is declined, counted, and
    the host answers the reference's page."""
    seg, lay, ref = store
    ds, arena = seg.devstore, seg.devstore.arena
    last = ds.spans_for(word2hash(f"zh{HIGH - 1}"))[0]
    assert last.jslot == -1
    full = arena._jdocids, arena._jpos
    cut = last.jstart + last.count - 1
    arena._jdocids, arena._jpos = full[0][:cut], full[1][:cut]
    query = f"zh2 zh{HIGH - 1}"
    try:
        c0 = ds.counters()
        assert ds.rank_join(_hashes(query), [], RankingProfile(), "en",
                            k=K) is None
        c1 = ds.counters()
    finally:
        arena._jdocids, arena._jpos = full
    assert c1["fallbacks"] - c0["fallbacks"] == 1
    assert c1["join_fallbacks"] - c0["join_fallbacks"] == 1
    assert c1["join_served"] == c0["join_served"]
    assert c1["join_sm_served"] == c0["join_sm_served"]
    assert _host_page(seg, query, lay) == ref.answer(query)
    assert _device_page(seg, query, lay) == ref.answer(query)   # restored


def test_the_slot_gauges_and_the_membership_counter_are_scraped(tmp_path):
    """/metrics and DeviceStore_p list the three beside `join_served`."""
    from yacy_search_server_tpu.server.servlets.monitoring import (
        prometheus_text)
    from yacy_search_server_tpu.server.servlets.operator import device_store
    from yacy_search_server_tpu.server.objects import ServerObjects
    from yacy_search_server_tpu.switchboard import Switchboard
    from yacy_search_server_tpu.utils.config import Config
    from yacy_search_server_tpu.utils.health import parse_exposition
    cfg = Config()
    cfg.set("index.device.mesh", "off")
    sb = Switchboard(data_dir=str(tmp_path / "DATA"), config=cfg)
    try:
        ds = sb.index.devstore
        ds.join_sm_served = 7
        ds.arena._bm_used, ds.arena._bm_refused = 64, 128
        samples = parse_exposition(prometheus_text(sb))
        assert samples[
            'yacy_device_serving_total{counter="join_sm_served"}'] == 7
        assert 'yacy_device_serving_total{counter="join_served"}' in samples
        assert samples['yacy_devstore_join_bitmaps{state="slots"}'] == 64
        assert samples['yacy_devstore_join_bitmaps{state="refused"}'] == 128
        prop = device_store({}, ServerObjects(), sb)
        rows = {prop.get(f"rows_{i}_key"): prop.get(f"rows_{i}_value")
                for i in range(int(prop.get("rows", 0)))}
        assert str(rows["join_sm_served"]) == "7"
        assert str(rows["join_bitmap_slots"]) == "64"
        assert str(rows["join_bitmap_refused"]) == "128"
    finally:
        sb.close()
