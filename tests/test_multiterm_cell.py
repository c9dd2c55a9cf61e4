"""Questions of three to six words (the deployment
`lucene-wikimedium10m-multiterm`, cell `wiki.multi`).

The configuration's corpus cut 64-fold as a rehearsal cuts it and to a
handful of lists, drawn from a seed by the benchmark's own corpus and
loaded as benchmarks/run.py loads it, asked through `Switchboard.search`
the questions the cell's generator writes: every class of the cell and
questions of five and six words equal the plain reference
(benchmarks/reference.py) exactly, with every partner of a device join on
a bitmap, with every partner on the sort-merge (no slot left), and on
the host path (`index.device.serving=false`); the controls put in the
program's place do not. One word set in two orders through the event
cache is pinned, and the counters, span attrs and families that say how
many lists a conjunction had are read back.
"""

import json
import os

import pytest

from benchmarks import corpus, reference, run
from benchmarks.generators import multiterm
from yacy_search_server_tpu.index.devstore import (DeviceArena,
                                                    DeviceSegmentStore)
from yacy_search_server_tpu.switchboard import Switchboard
from yacy_search_server_tpu.utils import eventtracker, histogram, tracing
from yacy_search_server_tpu.utils.config import Config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2 ** 31 + 36
SCALE = 64
GATE = run.SHIPPED_HOST_GATE_ROWS // SCALE     # the host gate, cut alike
PER_CLASS = 10
MODES = ("bitmap", "sortmerge", "host")

with open(os.path.join(ROOT, "benchmarks", "workloads", "wiki.multi.json"),
          encoding="utf-8") as _f:
    CELL = json.load(_f)
# slots [high, med, low]: the cell's three, then five and six words on
# the device (MAX_JOIN_TERMS 6) and under the host gate
CLASSES = {name: c["slots"] for name, c in CELL["params"]["classes"].items()}
CLASSES.update({"And5Terms": [2, 3, 0], "And6Terms": [2, 4, 0],
                "And5TermsRare": [1, 3, 1], "And6TermsRare": [2, 3, 1]})
DEVICE = [c for c, s in CLASSES.items() if not s[2]]


def _layout():
    cfg = run.scaled(corpus.load_config(CELL["config"]), SCALE)
    tiers = cfg["corpus"]["tiers"]
    assert (tiers["high"]["length"], tiers["med"]["length"]) == (20480, 1024)
    tiers["med"]["lists"] = 16      # 4 a topic: a six-word question's
    tiers["low"]["lists"] = 128     # one a topic
    return corpus.layout(cfg, SEED)


def _questions(lay, cls, n=PER_CLASS):
    params = {"classes": {cls: {"slots": CLASSES[cls], "weight": 1}},
              "block": n}
    return multiterm.generate(lay, params, SEED, n)


def _served(sb, queries, use_cache=False):
    """[(query, [(link, ranking)])] as a client reads a page."""
    return [(q, [(r.url, int(r.score)) for r in sb.search(
        q, count=10, use_cache=use_cache).results(offset=0, count=10)])
        for q in queries]


@pytest.fixture(scope="module", params=MODES)
def node(request, tmp_path_factory):
    """(switchboard, layout, reference, mode): one node a membership
    mode, the lists in layout order as benchmarks/run.py loads them."""
    mode = request.param
    lay = _layout()
    cfg = Config()
    cfg.set("index.device.mesh", "off")
    if mode == "host":
        cfg.set("index.device.serving", "false")
    with pytest.MonkeyPatch.context() as mp:
        # a Med list of the cut corpus earns a bitmap as the real one does
        mp.setattr(DeviceSegmentStore, "JOIN_BITMAP_MIN", 1024)
        if mode == "sortmerge":
            mp.setattr(DeviceArena, "JOIN_BITMAP_SLOTS", 0)
        sb = Switchboard(str(tmp_path_factory.mktemp(mode) / "DATA"),
                         config=cfg)
        run.load_corpus(sb, lay, SEED)
        ds = sb.index.devstore
        if ds is not None:
            ds.small_rank_n = GATE
        yield sb, lay, reference.Reference(lay, SEED), mode
        sb.close()


def test_the_lists_hold_the_slots_the_mode_says(node):
    sb, lay, _ref, mode = node
    ds = sb.index.devstore
    if mode == "host":
        assert ds is None
        return
    c = ds.counters()
    long_lists = len(lay.tier("high")) + len(lay.tier("med"))
    assert (c["join_bitmap_slots"], c["join_bitmap_refused"]) == (
        (long_lists, 0) if mode == "bitmap" else (0, long_lists))


@pytest.mark.parametrize("cls", list(CLASSES))
def test_a_class_equals_the_reference(node, cls):
    sb, lay, ref, mode = node
    ds = sb.index.devstore
    queries = _questions(lay, cls)
    assert all(len(q.split()) == sum(CLASSES[cls]) for q in queries)
    c0 = ds.counters() if ds is not None else None
    answers = _served(sb, queries)
    numbers = reference.compare(ref, answers, lay.hosts)["numbers"]
    assert numbers == {"wrong_answers": 0, "max_rank_gap": 0,
                       "max_miss_gap": 0, "tie_order_answers": 0}
    if cls in DEVICE:
        # regular terms and stop words: a page of title documents
        assert all(len(page) == reference.PAGE for _q, page in answers)
    if ds is None:
        return
    d = {k: v - c0[k] for k, v in ds.counters().items()
         if k.startswith(("join_", "fallbacks"))}
    assert (d["join_fallbacks"], d["fallbacks"]) == (0, 0)
    if cls in DEVICE:
        partners = sum(CLASSES[cls]) - 1
        assert d["join_served"] == len(queries)
        assert d["join_partners"] == partners * len(queries)
        assert d["join_multi_served"] == len(queries)
        # two partners or more: each read in one gather of its payload
        assert d["join_onegather_served"] == len(queries)
        assert d["join_sm_served"] == (
            len(queries) if mode == "sortmerge" else 0)
        # three partners or more: every later membership over the first
        # one's survivors, ~1/8 of the rare list (a regular term holds
        # 1/8 of its topic), one bucket of a quarter; two: none
        compacts = partners >= 3
        assert d["join_compacted"] == (len(queries) if compacts else 0)
        assert d["join_compact_overflow"] == 0
        if compacts:
            assert 0.1 < d["join_survivors"] / d["join_compact_rows"] < 0.25
        else:
            assert d["join_survivors"] == d["join_compact_rows"] == 0
    else:                       # a rare word: the host gate keeps it
        assert d["join_served"] == 0


@pytest.mark.parametrize("control", list(reference.CONTROLS))
def test_a_control_in_the_programs_place_is_not_correct(node, control):
    """The same sample through the same comparison, the reference with
    one thing broken standing where the program stood."""
    sb, lay, ref, _mode = node
    sample = [q for cls in CELL["params"]["classes"]
              for q in _questions(lay, cls)]
    served = _served(sb, sample)
    right = reference.compare(ref, served, lay.hosts)["numbers"]
    assert reference.decide({**right, "stale_served": 0}, len(served))
    broken = reference.Reference(lay, SEED, control=control)
    got = reference.compare(ref, broken.served(sample), lay.hosts)["numbers"]
    assert not reference.decide({**got, "stale_served": 0}, len(sample))


def test_one_word_set_in_two_orders_is_one_event_and_two_answers(node):
    """The node takes a conjunction's features from the FIRST of its
    shortest lists in word order, and its event cache keys on the
    unordered set: the second order is served the first one's page. So
    the cell's generator writes a word set in one order only."""
    sb, lay, ref, _mode = node
    a, b = "zm0 zm4 zm8", "zm4 zm0 zm8"
    want_a, want_b = ref.served([a])[0][1], ref.served([b])[0][1]
    assert want_a != want_b                     # two right answers
    assert _served(sb, [a])[0][1] == want_a
    assert _served(sb, [b])[0][1] == want_b
    sb.search_cache.clear()
    assert _served(sb, [a], use_cache=True)[0][1] == want_a
    assert _served(sb, [b], use_cache=True)[0][1] == want_a    # one entry
    by = lay.by_name()
    assert multiterm.written(by[w] for w in b.split()) == a
    stream = multiterm.generate(lay, CELL["params"], SEED, 600)
    assert len({frozenset(q.split()) for q in stream}) == len(set(stream))


def _trace_of(sb, query):
    """The spans of one uncached search, by name."""
    tracing.clear()
    _served(sb, [query])
    rec = next(r for r in tracing.traces(8)
               if r.root_name == "switchboard.search")
    by = {}
    for s in rec.spans:
        by.setdefault(s.name, []).append(s)
    return rec, by


ON_DEVICE = pytest.mark.parametrize("node", MODES[:2], indirect=True)


@ON_DEVICE
@pytest.mark.parametrize("cls,partners", [("And3Terms", 2),
                                          ("And2Terms2StopWords", 3)])
def test_a_device_join_says_how_many_partners_it_had(node, cls, partners):
    sb, lay, _ref, mode = node
    ds = sb.index.devstore
    query = _questions(lay, cls, 1)[0]
    histogram.reset_windows()
    shapes0 = ds.counters()["join_shapes"]
    rec, by = _trace_of(sb, query)
    batch = by["devstore.batch"][0]
    assert batch.attrs["partners"] == partners
    assert batch.attrs["membership"] == (
        "bitmap" if mode == "bitmap" else "sortmerge")
    assert batch.attrs["partner_read"] == "payload"
    kernel = "kernel." + batch.attrs["kernel"]
    for name in (kernel, "kernel.issue", "kernel.fetch", "kernel.device",
                 "kernel.join_multi"):
        assert by[name][0].attrs["partners"] == partners, name
        assert by[name][0].attrs["partner_read"] == "payload", name
    # the family of its own beside kernel.<name>: one observation a join
    assert histogram.get("kernel.join_multi").windowed_count() == 1
    assert by["kernel.join_multi"][0].dur_ms == by[kernel][0].dur_ms
    assert ds.counters()["join_shapes"] >= max(shapes0, 1)
    # Performance_Trace_p lists the attr on the span and the family in
    # its stage table
    from yacy_search_server_tpu.server.objects import ServerObjects
    from yacy_search_server_tpu.server.servlets.monitoring import (
        respond_trace)
    prop = respond_trace({}, ServerObjects({"trace": rec.trace_id}), sb)
    attrs = {prop.get(f"spans_{i}_name"): prop.get(f"spans_{i}_attrs")
             for i in range(int(prop.get("spans")))}
    assert f"partners={partners}" in attrs["kernel.join_multi"]
    assert f"partners={partners}" in attrs["devstore.batch"]
    table = respond_trace({}, ServerObjects(), sb)
    assert "kernel.join_multi" in {
        table.get(f"stages_{i}_name")
        for i in range(int(table.get("stages")))}
    # two words: one partner, and no observation of the multi family
    histogram.reset_windows()
    compacted = ds.counters()["join_compacted"]
    _rec, by = _trace_of(sb, "zm0 zm4")
    assert by["devstore.batch"][0].attrs["partners"] == 1
    assert by["devstore.batch"][0].attrs["partner_read"] == "rows"
    assert ds.counters()["join_compacted"] == compacted
    assert "kernel.join_multi" not in by
    assert histogram.get("kernel.join_multi").windowed_count() == 0


def test_a_host_join_says_its_terms_and_probes(node):
    """A question with a rare word stays under the host gate: its
    `search.join` carries `terms` and `probes`, a join that probed two
    lists or more a family of its own, the stage counters one series a
    word count."""
    sb, lay, _ref, _mode = node
    query = _questions(lay, "And3TermsRare", 1)[0]
    histogram.reset_windows()
    t0 = eventtracker.totals()
    _rec, by = _trace_of(sb, query)
    join = by["search.join"][0]
    assert (join.attrs["path"], join.attrs["terms"],
            join.attrs["probes"]) == ("probe", 3, 2)
    multi = by["search.join.multiprobe"][0]
    assert (multi.attrs["terms"], multi.attrs["probes"]) == (3, 2)
    assert multi.dur_ms >= join.dur_ms
    assert histogram.get("search.join.multiprobe").windowed_count() == 1
    t1 = eventtracker.totals()
    key = (eventtracker.EClass.SEARCH, "JOIN_TERMS_3")
    assert t1[key][0] - t0.get(key, (0, 0, 0.0))[0] == 1
    assert t1[key][1] - t0.get(key, (0, 0, 0.0))[1] == join.attrs["rows"]
    # one probe is no multiprobe
    histogram.reset_windows()
    low = next(w for w in query.split() if w.startswith("zl"))
    med = next(w for w in query.split() if w.startswith("zm"))
    _rec, by = _trace_of(sb, f"{low} {med}")
    assert (by["search.join"][0].attrs["terms"],
            by["search.join"][0].attrs["probes"]) == (2, 1)
    assert "search.join.multiprobe" not in by
    assert histogram.get("search.join.multiprobe").windowed_count() == 0
    # a hostile word count mints no series of its own
    from yacy_search_server_tpu.search import searchevent
    words = " ".join(t.name for t in lay.tier("low")[:9])
    _served(sb, [words])
    assert (eventtracker.EClass.SEARCH, "JOIN_TERMS_MANY") \
        in eventtracker.totals()
    assert searchevent.JOIN_TERMS_LABELS == DeviceSegmentStore.MAX_JOIN_TERMS


@ON_DEVICE
def test_the_partner_counters_are_scraped(node):
    """/metrics and DeviceStore_p list them beside `join_served`."""
    sb, _lay, _ref, _mode = node
    from yacy_search_server_tpu.server.objects import ServerObjects
    from yacy_search_server_tpu.server.servlets.monitoring import (
        prometheus_text)
    from yacy_search_server_tpu.server.servlets.operator import device_store
    from yacy_search_server_tpu.utils.health import parse_exposition
    c = sb.index.devstore.counters()
    assert c["join_multi_served"] > 0 and c["join_shapes"] > 0
    assert c["join_onegather_served"] == c["join_multi_served"]
    assert c["join_partners"] >= 2 * c["join_multi_served"]
    samples = parse_exposition(prometheus_text(sb))
    assert 0 < c["join_compacted"] <= c["join_multi_served"]
    assert c["join_survivors"] > 0 and c["join_compact_rows"] > 0
    compact = ("join_compacted", "join_survivors", "join_compact_rows",
               "join_compact_overflow")
    for key in ("join_partners", "join_multi_served",
                "join_onegather_served") + compact:
        assert samples[
            f'yacy_device_serving_total{{counter="{key}"}}'] == c[key]
    assert samples["yacy_devstore_join_shapes"] == c["join_shapes"]
    prop = device_store({}, ServerObjects(), sb)
    rows = {prop.get(f"rows_{i}_key"): prop.get(f"rows_{i}_value")
            for i in range(int(prop.get("rows", 0)))}
    for key in ("join_partners", "join_multi_served",
                "join_onegather_served", "join_shapes") + compact:
        assert str(rows[key]) == str(c[key])
