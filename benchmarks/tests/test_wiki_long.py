"""The deployment `lucene-wikimedium10m-longlists` and its cell
`wiki.long`: the file's arithmetic, the stream, and the four readers the
cell brings, each on a context without what it reads and on a recorded
one."""

import collections
import importlib
import json
import os

import pytest

from benchmarks import corpus, costs, costs_join, generators
from benchmarks.layer_metrics import _join
from yacy_search_server_tpu.utils import histogram

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = "lucene-wikimedium10m-longlists"
ACCEPTED = "lucene-wikimedium10m-default"


def reader(name):
    return importlib.import_module("benchmarks.layer_metrics." + name).read


def _cell():
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           "wiki.long.json"), encoding="utf-8") as f:
        wl = json.load(f)
    return wl, corpus.layout(corpus.load_config(wl["config"]), 2 ** 31 + 3)


def test_the_configuration_loads_and_its_tiers_sum_to_the_arena():
    cfg, old = corpus.load_config(CONFIG), corpus.load_config(ACCEPTED)
    for key in ("source", "deployment", "published", "guarantees",
                "reduced", "reduced_why", "assumed"):
        assert cfg[key], key
    assert "what_the_guess_decides" in cfg["assumed"]
    assert len(cfg["source"]) <= 200
    assert cfg["guarantees"] == old["guarantees"]       # word for word
    assert cfg["corpus"]["stars"] == old["corpus"]["stars"]
    assert cfg["yacy_conf"] == [] and cfg["reduced"] == old["reduced"]
    assert (cfg["docs"], cfg["corpus"]["hosts"]) == (2_500_000, 4096)
    _wl, lay = _cell()
    rows = {t: sum(x.length for x in lay.tier(t)) for t in corpus.TIERS}
    assert rows == {"high": 12_582_912, "med": 2_097_152, "low": 1_048_576}
    assert lay.postings == cfg["resident_postings"] == 15_728_640
    assert len(lay.terms) == 768
    # 192 lists ask for one of 64 slots; no Med list is of bitmap size
    assert len(lay.tier("high")) == 192 > _join.BITMAP_SLOTS
    assert {t.length for t in lay.tier("high")} == {65_536}
    assert max(t.length for t in lay.tier("med")) < 65_536
    # load order: the High lists first, by number
    assert [t.name for t in lay.terms[:3]] == ["zh0", "zh1", "zh2"]
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"]
    assert {w["name"]: w for w in bench["workloads"]}["wiki.long"] == {
        "name": "wiki.long", "config": CONFIG, "traffic": "long",
        "chips": 1, "why": bench["workloads"][-1]["why"]}


def test_every_block_of_the_stream_holds_20_of_each_category():
    wl, lay = _cell()
    g = generators.load(wl["generator"])
    by = lay.by_name()
    qs = g.generate(lay, wl["params"], 2 ** 31 + 3, 600)

    def tiers(q):
        return tuple(by[w].tier for w in q.split())

    for lo in range(0, 600, 60):
        assert collections.Counter(map(tiers, qs[lo:lo + 60])) == {
            ("high", "high"): 20, ("high", "med"): 20, ("high", "low"): 20}
    # the partner of an And HighHigh is the higher-numbered list: without
    # a slot in 1 - 64*63 / (192*191) = 89% of draws; of an And HighMed,
    # the High list: 67%
    hh = [q.split() for q in g.generate(lay, wl["params"], 5, 6000)
          if tiers(q) == ("high", "high")]
    assert all(by[a].index < by[b].index for a, b in hh)
    share = sum(by[b].index >= 64 for _a, b in hh) / len(hh)
    assert share == pytest.approx(0.89, abs=0.03)
    # the warm-up sends the bitmap pair first and sort-merge pairs after
    warm = g.warm(lay, wl["params"], 2 ** 31 + 3)
    assert warm[:3] == ["zh0 zh1", "zh0 zl1", "zh0 zm1"]
    assert any(tiers(q) == ("high", "high") and by[q.split()[1]].index >= 64
               for q in warm[:48])
    assert any(tiers(q) == ("high", "med") and by[q.split()[0]].index >= 64
               for q in warm[:48])


def _ctx(queries, lengths, counters, trace_counters=None, seconds=None):
    rows = [[i, 0.0, 0.01, 200, False, [], None]
            for i in range(len(queries))]
    return {"rows": rows, "queries": queries, "trace_rows": rows,
            "lengths": lambda qi: lengths[qi], "host_gate_rows": 4096,
            "attempted": len(rows), "counters": counters,
            "trace_counters": trace_counters or {},
            "trace": None if seconds is None else {"by_program": seconds},
            "peak": costs.peak("TPU v5 lite")}


QUERIES = ["zh3 zh70", "zh5 zm2", "zh100 zm7", "zh9 zl1", "zh64 zh65"]
LENGTHS = [[65536, 65536], [65536, 32768], [65536, 32768],
           [65536, 2048], [65536, 65536]]


def test_the_counter_readers_leave_a_program_without_counters_out():
    ctx = _ctx(QUERIES, LENGTHS, {"queries_served": 4})     # the parent
    assert reader("join_sortmerge_pct")(ctx) is None
    assert reader("join_declined_pct")(ctx) is None
    assert reader("join_sm_roofline")(ctx) is None          # no trace
    ctx = _ctx(QUERIES, LENGTHS, {"join_served": 4},
               trace_counters={"join_served": 4},
               seconds={"_rank_join_batch_packed_kernel": 0.02})
    assert reader("join_sm_roofline")(ctx) is None          # not counted


def test_the_counter_readers_on_a_recorded_context():
    ctx = _ctx(QUERIES, LENGTHS, {"join_served": 4, "join_sm_served": 3,
                                  "join_fallbacks": 1})
    assert reader("join_sortmerge_pct")(ctx) == 75.0
    # 4 of the 5 are device-eligible conjunctions (one is under the gate)
    assert reader("join_declined_pct")(ctx) == 25.0
    with pytest.raises(ValueError):         # more than were served
        reader("join_sortmerge_pct")(_ctx(QUERIES, LENGTHS, {
            "join_served": 2, "join_sm_served": 3}))
    assert reader("join_sortmerge_pct")(_ctx(QUERIES, LENGTHS, {
        "join_served": 0, "join_sm_served": 0})) is None


def test_the_roofline_share_of_the_sort_merge_kernel():
    # the shapes: partner = every list but the first of the shortest
    ctx = _ctx(QUERIES, LENGTHS, {})
    assert _join.sortmerge_shapes(ctx, ctx["rows"]) == [
        (65536, [65536]),       # zh3 zh70: partner zh70 holds no slot
        (32768, [65536]),       # zh100 zm7: rare zm7, partner zh100
        (65536, [65536])]       # zh64 zh65
    b_hh = costs_join.join_sortmerge_bytes(65536, [65536])
    b_hm = costs_join.join_sortmerge_bytes(32768, [65536])
    assert b_hh == 43 * 65536 + 12 * 65536 + 8 * 65536 + 8 * 128
    assert b_hm == 43 * 32768 + 12 * 32768 + 8 * 65536 + 8 * 128
    with pytest.raises(ValueError):
        costs_join.join_sortmerge_bytes(65536, [])
    seconds = {"_rank_join_batch_packed_kernel": 0.010,
               "_rank_join_bm_batch_packed_kernel": 5.0,   # not its own
               "_rank_pruned_batch1_packed_kernel": 1.0}
    ctx = _ctx(QUERIES, LENGTHS, {}, {"join_sm_served": 3}, seconds)
    least = 3 * (2 * b_hh + b_hm) / 3 / 819e9
    assert reader("join_sm_roofline")(ctx) == pytest.approx(
        100.0 * least / 0.010)
    # over 100 is a fault of the count and raises, never clipped
    seconds["_rank_join_batch_packed_kernel"] = 1e-6
    with pytest.raises(ValueError):
        reader("join_sm_roofline")(ctx)
    # the bitmap kernel alone on the device: nothing to read
    del seconds["_rank_join_batch_packed_kernel"]
    assert reader("join_sm_roofline")(ctx) is None


def test_the_kernel_wall_is_the_median_of_its_family():
    fam = "kernel._rank_join_batch_packed_kernel"
    histogram.reset()
    try:
        histogram._REG.pop(fam, None)
        assert reader("join_sm_kernel_ms_p50")({}) is None  # no family
        histogram.observe(fam, 900.0)                       # the warm-up
        histogram.reset_windows()
        assert reader("join_sm_kernel_ms_p50")({}) is None  # empty window
        for ms in (20.0, 41.0, 42.0, 43.0, 44.0, 90.0, 200.0):
            histogram.observe(fam, ms)
        lo, hi = _bucket(43.0)
        assert lo <= reader("join_sm_kernel_ms_p50")({}) <= hi
    finally:
        histogram.reset()


def _bucket(ms):
    bounds = [0.0, *histogram.BUCKET_BOUNDS_MS]
    return next((lo, hi) for lo, hi in zip(bounds, bounds[1:])
                if lo < ms <= hi)


def test_the_benchmark_declares_the_four_and_a_reader_each():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, moves, layer) in {
            "join_sortmerge_pct": ("%", "qps", "kernels"),
            "join_sm_roofline": ("%", "qps", "kernels"),
            "join_sm_kernel_ms_p50": ("ms", "p50_ms", "batcher"),
            "join_declined_pct": ("%", "qps", "search event")}.items():
        m = declared[name]
        assert (m["unit"], m["moves"], m["layer"]) == (unit, moves, layer)
        assert m["workloads"] == ["wiki.long"]
        assert callable(reader(name))
    # the accepted metrics without a list are read in the new cell too;
    # the top-k cache answers no conjunction
    assert declared["topk_cache_hit_pct"]["workloads"] == ["wiki.tasks"]
    for name in ("servlet_ms_p50", "device_answer_pct", "device_idle_pct"):
        assert "workloads" not in declared[name]
