"""A default peer on a four-chip host (the deployment
`lucene-wikimedium10m-mesh4`, cell `mesh4.tasks`).

The configuration's corpus cut 64-fold as a rehearsal cuts it and to a
handful of lists, drawn from a seed by the benchmark's own corpus, served
by `MeshSegmentStore` on a 1 x 4 mesh (four of the suite's eight virtual
CPU devices): every page of all six task categories equals the plain
reference (benchmarks/reference.py) and what `DeviceSegmentStore` gives
for the whole index on one device; `join_served` + `join_fallbacks`
account for every conjunction sent; the solo SPMD programs record a
family of their own name; and the store compiles the pinned set of
programs and no other (no prewarm, no new shape).
"""

import jax
import numpy as np
import pytest

from benchmarks import corpus, reference, run
from yacy_search_server_tpu.index.postings import PostingsList
from yacy_search_server_tpu.index.segment import Segment
from yacy_search_server_tpu.ops.ranking import RankingProfile
from yacy_search_server_tpu.utils import histogram
from yacy_search_server_tpu.utils.hashes import word2hash

SEED = 2 ** 31 + 33
CHIPS = 4
K = 128         # the page looks at the best 80 (reference.page)
JOIN_FAMILY = "kernel._mesh_join_shard"

# luceneutil's six nightly categories, one task each, as
# generators/tasks.py writes them (a pair of one tier in list order)
TASKS = {
    "TermHigh": "zh0",
    "TermMed": "zm1",
    "TermLow": "zl2",
    "AndHighHigh": "zh1 zh3",
    "AndHighMed": "zh2 zm0",
    "AndHighLow": "zh0 zl1",
}
MORE_PAIRS = ["zh0 zh1", "zh3 zm3", "zh1 zm2", "zh2 zl3"]

# what a store that answered the six tasks one at a time through its
# batcher has compiled: one pruned wave of one, one join program per
# (rare window, partner window). A prewarm pass or a new shape adds a key
# and fails this list (ISSUE 33: the cell runs the parent's programs).
PINNED_FNS = [("pruned_batch", 128, 1, 1)]
PINNED_JFNS = [
    (128, 1, 0, 192, (3072,), (), False),       # And HighLow
    (128, 1, 0, 384, (3072,), (), False),       # And HighMed
    (128, 1, 0, 3072, (3072,), (), False),      # And HighHigh
]


def _devices():
    devs = jax.devices("cpu")
    if len(devs) < CHIPS:
        pytest.skip(f"need {CHIPS} cpu devices "
                    "(xla_force_host_platform_device_count)")
    return devs[:CHIPS]


def _layout():
    cfg = run.scaled(corpus.load_config("lucene-wikimedium10m-mesh4"), 64)
    for tier in corpus.TIERS:
        cfg["corpus"]["tiers"][tier]["lists"] = 4
    assert cfg["corpus"]["tiers"]["high"]["length"] == 8192
    return corpus.layout(cfg, SEED)


def _load(seg, lay):
    """The lists in layout order, one run each, as benchmarks/run.py
    loads the High and Med ones."""
    for term in lay.terms:
        docids, feats = corpus.term_list(lay, term, SEED)
        seg.rwi.ingest_run(
            {word2hash(term.name): PostingsList(docids, feats)})


@pytest.fixture(scope="module")
def stores():
    """(mesh segment, one-device segment, layout, reference) over twin
    indexes of the same lists."""
    lay = _layout()
    mesh, single = Segment(max_ram_postings=10), Segment(max_ram_postings=10)
    mesh.enable_mesh_serving(devices=_devices(), n_term=1)
    single.enable_device_serving(device=_devices()[0])
    _load(mesh, lay)
    _load(single, lay)
    assert (mesh.devstore.n_term, mesh.devstore.n_doc) == (1, CHIPS)
    yield mesh, single, lay, reference.Reference(lay, SEED)
    mesh.close()
    single.close()


def _page(ds, query, lay):
    hashes = [word2hash(w) for w in query.split()]
    if len(hashes) == 1:
        out = ds.rank_term(hashes[0], RankingProfile(), "en", k=K)
    else:
        out = ds.rank_join(hashes, [], RankingProfile(), "en", k=K)
    assert out is not None, f"the store declined {query!r}"
    return reference.page(np.asarray(out[1]),
                          np.asarray(out[0], np.int64), lay.hosts)


@pytest.mark.parametrize("category", list(TASKS))
def test_a_task_on_the_mesh_equals_the_reference(stores, category):
    mesh, _single, lay, ref = stores
    want = ref.answer(TASKS[category])
    # a 32-row Low list meets a High one in a handful of documents
    assert len(want) == reference.PAGE or (
        category == "AndHighLow" and len(want) >= 3)
    assert _page(mesh.devstore, TASKS[category], lay) == want


@pytest.mark.parametrize("query", [*TASKS.values(), *MORE_PAIRS],
                         ids=lambda q: q.replace(" ", "+"))
def test_four_columns_fuse_to_what_one_chip_gives(stores, query):
    """Every document lives in exactly one doc column for every term, so
    the columns' pages fuse to the page of the whole index."""
    mesh, single, lay, _ref = stores
    assert _page(mesh.devstore, query, lay) \
        == _page(single.devstore, query, lay)


def _family_count(name):
    h = histogram.get(name)
    return 0 if h is None else h.count


def test_every_conjunction_is_served_or_declined_and_one_family_a_join(
        stores):
    mesh, _single, lay, _ref = stores
    ms = mesh.devstore
    pairs = [q for q in [*TASKS.values(), *MORE_PAIRS] if " " in q]
    c0, f0 = ms.counters(), _family_count(JOIN_FAMILY)
    for q in pairs:
        _page(ms, q, lay)
    c1 = ms.counters()
    assert c1["join_served"] - c0["join_served"] == len(pairs)
    assert c1["join_fallbacks"] == c0["join_fallbacks"]
    assert c1["fallbacks"] == c0["fallbacks"]
    assert _family_count(JOIN_FAMILY) - f0 == len(pairs)
    # a Term query is no join: neither counter, nor the family, moves
    ms._topk_cache.clear()
    _page(ms, "zh3", lay)
    c2 = ms.counters()
    assert (c2["join_served"], c2["join_fallbacks"]) \
        == (c1["join_served"], c1["join_fallbacks"])
    assert c2["queries_served"] == c1["queries_served"] + 1
    assert _family_count(JOIN_FAMILY) - f0 == len(pairs)
    assert _family_count("kernel._mesh_pruned_shard") >= 1


def test_the_stale_guarantee_is_read_from_the_mesh_store(stores):
    mesh, _single, _lay, _ref = stores
    ms = mesh.devstore
    assert ms.counters()["rank_cache_stale_served"] == 0
    ms._topk_cache.stale_served = 3     # what a rung-3 answer would count
    try:
        assert ms.counters()["rank_cache_stale_served"] == 3
    finally:
        ms._topk_cache.stale_served = 0


def _small_mesh():
    """A store of its own for the declines, so that the shared one keeps
    its single spans."""
    lay = _layout()
    seg = Segment(max_ram_postings=10)
    seg.enable_mesh_serving(devices=_devices(), n_term=1)
    _load(seg, lay)
    return seg, lay


def _declines(ms, query):
    c0 = ms.counters()
    out = ms.rank_join([word2hash(w) for w in query.split()], [],
                       RankingProfile(), "en", k=K)
    c1 = ms.counters()
    return out, {k: c1[k] - c0[k] for k in
                 ("join_served", "join_fallbacks", "fallbacks",
                  "queries_served")}


def test_each_decline_counts_one_join_fallback():
    seg, lay = _small_mesh()
    try:
        ms = seg.devstore
        out, d = _declines(ms, "zh0 zh1")
        assert out is not None and d == {
            "join_served": 1, "join_fallbacks": 0, "fallbacks": 0,
            "queries_served": 1}
        declined = {"join_served": 0, "join_fallbacks": 1, "fallbacks": 1,
                    "queries_served": 0}
        # a RAM delta on one of the terms
        term = lay.by_name()["zh1"]
        docids, feats = corpus.term_list(lay, term, SEED + 1)
        seg.rwi.add_many(word2hash("zh1"),
                         PostingsList(docids[:5], feats[:5]))
        assert _declines(ms, "zh0 zh1") == (None, declined)
        # flushed, the list holds two spans: a multi-span term
        seg.rwi.flush()
        assert len(ms.spans_for(word2hash("zh1"))) == 2
        assert _declines(ms, "zh0 zh1") == (None, declined)
        assert _declines(ms, "zh0 zh2")[1]["join_served"] == 1
        # a lost mesh
        ms.device_lost = True
        try:
            assert _declines(ms, "zh0 zh2") == (None, declined)
            assert ms.counters()["device_lost_queries"] == 1
        finally:
            ms.device_lost = False
        # a shape rank_join does not take is no conjunction: not counted
        out, d = _declines(ms, "zh0")
        assert out is None and d["join_fallbacks"] == 0
    finally:
        seg.close()


def test_the_six_tasks_compile_the_pinned_programs_and_no_other():
    """The "no new program" rule of ISSUE 33 as a test: a fresh store
    with its batcher on (as a Switchboard starts it) answers the six
    categories one at a time and has compiled exactly these."""
    seg, lay = _small_mesh()
    try:
        ms = seg.devstore
        ms.enable_batching(max_batch=16, dispatchers=8)
        assert ms._fns == {} and ms._jfns == {}     # no prewarm pass
        for query in TASKS.values():
            _page(ms, query, lay)
        assert sorted(ms._fns) == sorted(PINNED_FNS)
        assert sorted(ms._jfns) == sorted(PINNED_JFNS)
        assert "prewarm_failures" not in ms.counters()
    finally:
        seg.close()


def test_metrics_and_the_store_page_list_them_for_a_mesh_store(tmp_path):
    from yacy_search_server_tpu.index.meshstore import MeshSegmentStore
    from yacy_search_server_tpu.server.objects import ServerObjects
    from yacy_search_server_tpu.server.servlets.monitoring import (
        prometheus_text)
    from yacy_search_server_tpu.server.servlets.operator import device_store
    from yacy_search_server_tpu.switchboard import Switchboard
    from yacy_search_server_tpu.utils.health import parse_exposition
    sb = Switchboard(data_dir=str(tmp_path / "DATA"))   # shipped defaults
    try:
        ds = sb.index.devstore
        assert isinstance(ds, MeshSegmentStore)     # `auto`, > 1 device
        ds.join_served, ds.join_fallbacks = 11, 2
        ds._topk_cache.stale_served = 1
        histogram.observe(JOIN_FAMILY, 19.0)
        joins = _family_count(JOIN_FAMILY)
        samples = parse_exposition(prometheus_text(sb))
        for counter, want in (("join_served", 11), ("join_fallbacks", 2),
                              ("rank_cache_stale_served", 1)):
            assert samples['yacy_device_serving_total{counter="%s"}'
                           % counter] == want
        assert any("_mesh_join_shard" in name for name in samples)
        prop = device_store({}, ServerObjects(), sb)
        rows = {prop.get(f"rows_{i}_key"): str(prop.get(f"rows_{i}_value"))
                for i in range(int(prop.get("rows", 0)))}
        assert rows["join_served"] == "11"
        assert rows["join_fallbacks"] == "2"
        assert rows["rank_cache_stale_served"] == "1"
        assert rows[JOIN_FAMILY] == str(joins)
    finally:
        sb.close()


# -- the cell's readers: what they take from a trace and from the lists ---

_HLO = {
    "all-gather": ("%all-gather.1 = s32[4,1,128]{2,1,0:T(1,128)S(1)} "
                   "all-gather(s32[1,1,128]{2,1,0:T(1,128)S(1)} %slice_bi"),
    # the instruction is named after the JAX primitive, not the operation
    "all-reduce": ("%pmax.14 = s32[17]{0:T(128)S(1)} all-reduce(s32[17]"
                   "{0:T(128)S(1)} %get-tuple-element.51), channel_id"),
    # an operand that IS a collective does not make the fusion one
    "fusion": ("%fusion.1 = s16[196608,17]{0,1:T(8,128)(2,1)S(1)} "
               "fusion(s16[4227072,17]{0,1} %all-gather.9)"),
    "sort": ("%sort.101 = (s32[393216]{0:T(1024)S(1)}, s32[393216]"
             "{0:T(1024)}) sort(s32[393216]{0:T(1024)} %x)"),
    "all-gather-done": "%ag = s32[4]{0} all-gather-done(%s)",
    "all-reduce-start": "all-reduce-start.3",       # a plain event name
}


@pytest.mark.parametrize("operation", list(_HLO))
def test_a_device_operation_is_named_by_its_hlo_text(operation):
    from benchmarks.layer_metrics import _mesh
    assert _mesh.operation(_HLO[operation]) == operation
    assert _mesh.is_collective(_HLO[operation]) \
        == operation.startswith(("all-gather", "all-reduce"))


@pytest.mark.parametrize("rare,partner,want", [
    (524288, 524288, 131072 * 43 + 131072 * 8 + 1024),  # And HighHigh
    (65536, 524288, 16384 * 43 + 131072 * 8 + 1024),    # And HighMed
])
def test_the_bytes_a_chip_reads_for_a_conjunction(rare, partner, want):
    from benchmarks import costs_mesh
    assert costs_mesh.mesh_join_bytes(rare, [partner], chips=CHIPS) == want
    assert costs_mesh.mesh_join_bytes(rare, [partner], chips=1) \
        == rare * 43 + partner * 8 + 1024
