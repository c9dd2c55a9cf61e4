"""The deployment `lucene-wikimedium10m-multiterm` and its cell
`wiki.multi` (and `wiki.head`, which came with it): the file's
arithmetic, the stream, the four readers the cell brings, each on a
context without what it reads and on a recorded one, and a CPU rehearsal
of both cells."""

import collections
import importlib
import itertools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import corpus, costs, costs_multi, generators, reference
from benchmarks.layer_metrics import _multi
from yacy_search_server_tpu.utils import histogram

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = "lucene-wikimedium10m-multiterm"
ACCEPTED = "lucene-wikimedium10m-default"
SEED = 2 ** 31 + 36
READERS = {"join_partners_per_query": ("n", "qps", "kernels"),
           "join_multi_ms_p50": ("ms", "p50_ms", "batcher"),
           "join_multi_roofline": ("%", "qps", "kernels"),
           "gate_probe_ms_p50": ("ms", "p50_ms", "search event")}


def reader(name):
    return importlib.import_module("benchmarks.layer_metrics." + name).read


def _json(*parts):
    with open(os.path.join(ROOT, *parts), encoding="utf-8") as f:
        return json.load(f)


def _cell(name="wiki.multi", seed=SEED):
    wl = _json("benchmarks", "workloads", name + ".json")
    return wl, corpus.layout(corpus.load_config(wl["config"]), seed)


def test_the_configuration_loads_and_its_tiers_sum_to_the_arena():
    cfg, old = corpus.load_config(CONFIG), corpus.load_config(ACCEPTED)
    for key in ("source", "deployment", "published", "guarantees",
                "reduced", "reduced_why", "assumed"):
        assert cfg[key], key
    for key in ("why_assumed", "tier_reading", "stop_words", "tier_counts",
                "load_order", "what_the_guess_decides", "title_documents"):
        assert cfg["assumed"][key], key
    assert len(cfg["source"]) <= 200
    assert cfg["guarantees"] == old["guarantees"]       # word for word
    assert cfg["deployment"] == old["deployment"]
    assert cfg["published"] == {**old["published"], "task_categories": [
        "And3Terms", "And2Terms2StopWords"]}
    assert cfg["yacy_conf"] == [] and cfg["reduced"] == old["reduced"]
    assert cfg["reduced_why"] == old["reduced_why"]
    assert (cfg["docs"], cfg["corpus"]["hosts"]) == (2_500_000, 4096)
    assert cfg["corpus"]["stars"] == {"per_med_window": 48, "dropped": 6}
    _wl, lay = _cell()
    rows = {t: sum(x.length for x in lay.tier(t)) for t in corpus.TIERS}
    assert rows == {"high": 4 * 1_310_720, "med": 60 * 65_536,
                    "low": 3_200 * 2_048}
    assert lay.postings == cfg["resident_postings"] == 15_728_640 \
        == corpus.layout(old, SEED).postings        # the siblings' arena
    # 64 lists of bitmap size for the store's 64 slots: one membership
    assert sum(t.length >= 65_536 for t in lay.terms) == 64
    assert max(t.length for t in lay.tier("low")) <= 4_096  # the host gate
    # load order: the stop words first, then the regular terms
    assert [t.name for t in lay.terms[:5]] == ["zh0", "zh1", "zh2", "zh3",
                                               "zm0"]
    # 15 regular and 25 rare terms cover each of the 4 x 32 topics
    assert (lay.n_med_windows, lay.n_sub_windows) == (4, 32)
    for mw, sw in itertools.product(range(4), range(32)):
        assert len(lay.covering("med", mw, sw)) == 15
        assert len(lay.covering("low", mw, sw)) == 25
    bench = _json("BENCHMARK.json")
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    cells = {w["name"]: w for w in bench["workloads"]}
    assert {k: cells["wiki.multi"][k] for k in ("config", "traffic",
                                                "chips")} \
        == {"config": CONFIG, "traffic": "multi", "chips": 1}
    assert {k: cells["wiki.head"][k] for k in ("config", "traffic",
                                               "chips")} \
        == {"config": ACCEPTED, "traffic": "head", "chips": 1}


def test_any_four_lists_of_a_topic_share_24_title_documents():
    """A list leaves out a run of 6 of its window's 48: four lists at
    most 24, wherever their runs start. On the lists as drawn: the two
    stop words and two regular terms of a question, every window."""
    _wl, lay = _cell()
    assert lay.star_dropped == 6 and all(len(d) == 48
                                         for d in lay.star_docs)
    fewest = 48
    for mw in range(lay.n_med_windows):
        meds = lay.covering("med", mw, 0)
        held = {t.name: set(corpus._stars_of(lay, t, SEED)[0].tolist())
                & set(lay.star_docs[mw].tolist())
                for t in [*lay.tier("high"), *meds]}
        assert all(len(h) == 42 for h in held.values())
        for four in itertools.combinations(held, 4):
            fewest = min(fewest, len(set.intersection(
                *(held[n] for n in four))))
    assert fewest >= 24


def test_every_block_of_the_stream_holds_20_of_each_class():
    wl, lay = _cell()
    g = generators.load(wl["generator"])
    by = lay.by_name()
    qs = g.generate(lay, wl["params"], SEED, 600)

    def tiers(q):
        return tuple(by[w].tier for w in q.split())

    shapes = {("med", "med", "med"): 20,                  # And3Terms
              ("med", "med", "high", "high"): 20,         # ..2StopWords
              ("low", "med", "med"): 20}                  # And3TermsRare
    for lo in range(0, 600, 60):
        assert collections.Counter(map(tiers, qs[lo:lo + 60])) == shapes
    # the words of a question cover one topic, no word twice
    for q in qs:
        terms = [by[w] for w in q.split()]
        assert len(set(q.split())) == len(terms)
        assert len({t.med_window for t in terms if t.tier != "high"}) == 1
    # a word set is written in ONE order: rarest tier first, then by list
    for q in qs:
        terms = [by[w] for w in q.split()]
        assert terms == sorted(terms, key=lambda t: (
            ("low", "med", "high").index(t.tier), t.index))
    assert len({frozenset(q.split()) for q in qs}) == len(set(qs))
    # another seed: the same sizes in another order
    assert g.generate(lay, wl["params"], SEED + 1, 60) != qs[:60]
    # the word sets of the classes (PERF.md section 4)
    assert 4 * 455 == 1_820 and 4 * 105 * 6 == 2_520


def test_the_warm_up_meets_every_class_in_every_window():
    wl, lay = _cell()
    g = generators.load(wl["generator"])
    by = lay.by_name()
    warm = g.warm(lay, wl["params"], SEED)
    assert len(warm) == 3 * lay.n_med_windows + 96
    seen = {(tuple(by[w].tier for w in q.split()),
             next(by[w].med_window for w in q.split()
                  if by[w].tier != "high")) for q in warm[:12]}
    assert len(seen) == 3 * lay.n_med_windows
    # run.py sends the first 48 from one thread: every join family
    assert {len(q.split()) for q in warm[:48]} == {3, 4}
    assert warm[12:] != g.generate(lay, wl["params"], SEED, 96)
    with pytest.raises(ValueError):     # more slots than a topic has lists
        g.generate(lay, {"classes": {"x": {"slots": [5, 1, 0],
                                           "weight": 1}}}, SEED, 1)


def test_the_conjunctions_are_the_sizes_the_configuration_says():
    """~1,024 / ~2,200 / ~32 candidates, from the lists as drawn."""
    wl, lay = _cell()
    ref = reference.Reference(lay, SEED)
    sizes = {"zm0 zm4 zm8": (900, 1_250), "zm0 zm4 zh0 zh1": (1_950, 2_600),
             "zl0 zm0 zm4": (15, 60)}
    for q, (lo, hi) in sizes.items():
        docids, scores = ref.scored(q)
        assert lo <= len(docids) <= hi, (q, len(docids))
        page = reference.page(docids, scores, lay.hosts)
        assert len(page) == reference.PAGE
        if "zl" not in q:       # a device answer: ten title documents
            stars = set(np.concatenate(lay.star_docs).tolist())
            assert all(d in stars for d, _s in page)
            assert len({s for _d, s in page}) == reference.PAGE


def _ctx(queries, lengths, counters, trace_counters=None, seconds=None):
    rows = [[i, 0.0, 0.01, 200, False, [], None]
            for i in range(len(queries))]
    return {"workload": "wiki.multi", "rows": rows, "queries": queries,
            "trace_rows": rows, "lengths": lambda qi: lengths[qi],
            "host_gate_rows": 4096, "attempted": len(rows),
            "counters": counters, "trace_counters": trace_counters or {},
            "trace": None if seconds is None else {"by_program": seconds},
            "peak": costs.peak("TPU v5 lite")}


QUERIES = ["zm0 zm4 zm8", "zm1 zm5 zh0 zh2", "zl3 zm3 zm7", "zm2 zm6"]
LENGTHS = [[65536] * 3, [65536, 65536, 1310720, 1310720],
           [2048, 65536, 65536], [65536, 65536]]


def test_the_readers_leave_a_program_without_the_counters_out():
    ctx = _ctx(QUERIES, LENGTHS, {"queries_served": 3, "join_served": 3})
    assert reader("join_partners_per_query")(ctx) is None   # the parent
    assert reader("join_multi_roofline")(ctx) is None       # no trace
    ctx = _ctx(QUERIES, LENGTHS, {"join_served": 3},
               trace_counters={"join_served": 3},
               seconds={"_rank_join_bm_batch_packed_kernel": 0.02})
    assert reader("join_multi_roofline")(ctx) is None       # not counted
    histogram.reset()
    try:
        for name in ("kernel.join_multi", "search.join.multiprobe"):
            histogram._REG.pop(name, None)
        assert reader("join_multi_ms_p50")({}) is None      # no family
        assert reader("gate_probe_ms_p50")({}) is None
    finally:
        histogram.reset()


def test_the_partner_count_on_a_recorded_context():
    ctx = _ctx(QUERIES, LENGTHS, {"join_served": 3, "join_partners": 6})
    assert reader("join_partners_per_query")(ctx) == 2.0
    assert reader("join_partners_per_query")(_ctx(QUERIES, LENGTHS, {
        "join_served": 0, "join_partners": 0})) is None


def test_the_roofline_share_of_the_multi_partner_join():
    ctx = _ctx(QUERIES, LENGTHS, {})
    dens = _multi.densities(ctx)
    assert dens == {1_310_720: 1_310_720 / 2_500_000, 65_536: 0.125,
                    2_048: 0.125}
    # the rare list is the first of the shortest; a partner's hits are
    # the rare rows it is expected to hold; two words and a question
    # under the host gate are not this reader's
    assert _multi.multi_shapes(ctx, ctx["rows"]) == [
        (65536, [8192.0, 8192.0]),
        (65536, [8192.0, 65536 * dens[1_310_720], 65536 * dens[1_310_720]])]
    b3 = costs_multi.join_multi_bytes(65536, [8192, 8192])
    assert b3 == 43 * 65536 + 2 * (8 * 65536 + 12 * 8192) + 8 * 128
    b4 = costs_multi.join_multi_bytes(65536, [8192.0] + [34359.73824] * 2)
    with pytest.raises(ValueError):
        costs_multi.join_multi_bytes(65536, [8192])     # one partner
    with pytest.raises(ValueError):
        costs_multi.join_multi_bytes(100, [50, 101])
    seconds = {"_rank_join_bm_batch_packed_kernel": 0.010,
               "_rank_join_batch_packed_kernel": 5.0,       # not its own
               "_rank_pruned_batch1_packed_kernel": 1.0}
    ctx = _ctx(QUERIES, LENGTHS, {}, {"join_multi_served": 2}, seconds)
    least = 2 * (b3 + b4) / 2 / 819e9
    assert reader("join_multi_roofline")(ctx) == pytest.approx(
        100.0 * least / 0.010)
    # over 100 is a fault of the count and raises, never clipped
    seconds["_rank_join_bm_batch_packed_kernel"] = 1e-6
    with pytest.raises(ValueError):
        reader("join_multi_roofline")(ctx)
    del seconds["_rank_join_bm_batch_packed_kernel"]
    assert reader("join_multi_roofline")(ctx) is None


@pytest.mark.parametrize("name,fam", [
    ("join_multi_ms_p50", "kernel.join_multi"),
    ("gate_probe_ms_p50", "search.join.multiprobe")])
def test_a_span_reader_is_the_median_of_its_family(name, fam):
    histogram.reset()
    try:
        histogram.observe(fam, 900.0)                       # the warm-up
        histogram.reset_windows()
        assert reader(name)({}) is None                     # empty window
        for ms in (5.0, 11.0, 12.0, 12.5, 13.0, 40.0, 90.0):
            histogram.observe(fam, ms)
        bounds = [0.0, *histogram.BUCKET_BOUNDS_MS]
        lo, hi = next((a, b) for a, b in zip(bounds, bounds[1:])
                      if a < 12.5 <= b)
        assert lo <= reader(name)({}) <= hi
    finally:
        histogram.reset()


def test_the_benchmark_declares_the_four_and_a_reader_each():
    bench = _json("BENCHMARK.json")
    declared = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(READERS)
    for name, (unit, moves, layer) in READERS.items():
        m = declared[name]
        assert (m["unit"], m["moves"], m["layer"]) == (unit, moves, layer)
        assert m["workloads"] == ["wiki.multi"]
        assert callable(reader(name))
    # the accepted metrics without a list are read in both new cells
    for name in ("servlet_ms_p50", "device_answer_pct", "device_idle_pct"):
        assert "workloads" not in declared[name]
    for w in bench["workloads"]:
        assert len(w["why"]) <= 200


def test_the_head_cell_is_the_generator_as_written():
    wl, lay = _cell("wiki.head")
    assert wl["params"] == {"strings": 64, "and_share": 0.5, "s": 1.1}
    g = generators.load(wl["generator"])
    qs = g.generate(lay, wl["params"], SEED, 4000)
    strings = g.warm(lay, wl["params"], SEED)
    assert len(strings) == len(set(strings)) == 64
    assert set(qs) <= set(strings)
    assert sum(" " in s for s in strings) == 32
    top = collections.Counter(qs).most_common(1)[0]
    assert top[0] == strings[0] and top[1] > 4000 * 0.15


@pytest.mark.parametrize("cell", ["wiki.multi", "wiki.head"])
def test_a_cpu_rehearsal_is_correct_and_the_controls_are_not(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell,
         "--seed", str(SEED), "--seconds", "4", "--trace", "1",
         "--cpu-rehearsal", "--control", "all"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert all(v["value"] == 0 for k, v in line["compared"].items()
               if k != "answers_compared")
    said = [ln for ln in p.stderr.splitlines() if ln.startswith("control ")]
    assert len(said) == len(reference.CONTROLS)
    assert all(" correct false " in ln for ln in said), said
    assert " 0 clients ran dry" in p.stderr
    # (compilations inside the window are the chip runs' to hold to 0:
    # on the CPU the store skips the join families' wave-bucket prewarm)
    assert line["device"]["platform"] == "cpu"      # never a device number
    metrics = line["metrics"]
    assert "join_multi_roofline" not in metrics     # no device plane here
    if cell == "wiki.multi":
        assert 2.4 <= metrics["join_partners_per_query"]["value"] <= 2.6
        assert metrics["join_multi_ms_p50"]["unit"] == "ms"
        assert metrics["gate_probe_ms_p50"]["value"] > 0
        assert 63.0 <= metrics["device_answer_pct"]["value"] <= 67.0
    else:
        assert not set(metrics) & set(READERS)
