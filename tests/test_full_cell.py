"""The source's own scale on one chip (the deployment
`lucene-wikimedium10m-full`, cell `wiki.full`).

The configuration's block sequence cut 64-fold in rows with the same
ratios, loaded into a store as benchmarks/run.py loads it, under a budget
that admits the arena's capacity C but not 2C: admission and growth size
alike, so the whole index lands and the capacity is C. The readers of the
arena read the process's event totals after the store is gone, and
nothing where the program wrote none. The cell's rehearsal is `correct` against the plain
reference.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import corpus, run
from benchmarks.layer_metrics import (arena_fill_pct, arena_grow_s,
                                      arena_resident_pct,
                                      full_join_roofline)
from yacy_search_server_tpu.index.devstore import (TILE, DeviceArena,
                                                    DeviceSegmentStore)
from yacy_search_server_tpu.index.postings import PostingsList
from yacy_search_server_tpu.index.rwi import RWIIndex
from yacy_search_server_tpu.ops.ranking import RankingProfile
from yacy_search_server_tpu.utils import eventtracker
from yacy_search_server_tpu.utils.hashes import word2hash

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "lucene-wikimedium10m-full"
SCALE = 64


def _load(name):
    with open(os.path.join(ROOT, name), encoding="utf-8") as f:
        return json.load(f)


def _blocks(cfg):
    """Rows of each run in run.py's order: each High and Med list a run,
    the Low lists one run."""
    t = cfg["corpus"]["tiers"]
    return [t["high"]["length"]] * t["high"]["lists"] \
        + [t["med"]["length"]] * t["med"]["lists"] \
        + [t["low"]["length"] * t["low"]["lists"]]


def _cut(cfg, scale):
    """Every list `scale` times shorter, the counts kept: the same
    ratios of the blocks to each other and to the capacity."""
    cfg = json.loads(json.dumps(cfg))
    c = cfg["corpus"]
    c["docs"] //= scale
    for spec in c["tiers"].values():
        spec["length"] //= scale
        spec["window"] //= scale
    return cfg


def _capacity_for(rows):
    cap = 4 * TILE
    while cap < rows + TILE:
        cap *= 2
    return cap


def test_the_configuration_is_the_source_at_its_own_document_count():
    cfg = corpus.load_config(CONFIG)
    default = corpus.load_config("lucene-wikimedium10m-default")
    assert cfg["docs"] == cfg["corpus"]["docs"] \
        == cfg["published"]["docs"] == 10_000_000
    assert cfg["reduced"] == ["resident_postings"]
    assert cfg["resident_postings"] == sum(_blocks(cfg)) \
        == 4 * default["resident_postings"] == 62_914_560
    assert set(cfg["guarantees"]) == set(default["guarantees"]) | {
        "resident"}
    assert cfg["corpus"]["stars"] == default["corpus"]["stars"]
    assert cfg["corpus"]["hosts"] * default["corpus"]["docs"] \
        == default["corpus"]["hosts"] * 4 * default["corpus"]["docs"]
    # the tier fractions are -default's, four times the documents
    for tier, spec in cfg["corpus"]["tiers"].items():
        d = default["corpus"]["tiers"][tier]
        assert spec["lists"] == d["lists"]
        assert spec["length"] == 4 * d["length"]
        assert spec["window"] == 4 * d["window"]
    # the Low class is over the host gate at this document count
    assert cfg["corpus"]["tiers"]["low"]["length"] \
        > run.SHIPPED_HOST_GATE_ROWS
    assert "index.device.budgetBytes=12884901888" in cfg["yacy_conf"]
    # the whole index lands at 2^26 rows
    assert _capacity_for(cfg["resident_postings"]) == 1 << 26


def test_the_cell_sends_wiki_tasks_traffic_word_for_word():
    full = _load("benchmarks/workloads/wiki.full.json")
    tasks = _load("benchmarks/workloads/wiki.tasks.json")
    assert full["config"] == CONFIG
    for key in ("generator", "params", "loop", "clients",
                "client_processes", "stream_per_second", "compare_sample"):
        assert full[key] == tasks[key], key
    bench = _load("BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}["wiki.full"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "tasks", 1)
    assert CONFIG in {c["name"] for c in bench["configs"]}
    metrics = {m["name"]: m for m in bench["per_layer"]}
    for name in ("arena_resident_pct", "arena_grow_s", "full_join_roofline"):
        assert metrics[name]["workloads"] == ["wiki.full"]
    assert metrics["arena_fill_pct"]["workloads"] == [
        "wiki.tasks", "wiki.long", "wiki.multi", "wiki.head", "wiki.full"]
    assert {metrics[n]["layer"] for n in (
        "arena_resident_pct", "arena_fill_pct", "arena_grow_s")} == {
        "device arena"}


@pytest.fixture(scope="module")
def full_store():
    """The configuration's block sequence at 1/64 in rows, loaded as
    run.py loads it (largest lists first, one run each, the Low lists in
    one run), under a budget that admits the capacity C the rows need
    and not 2C."""
    cfg = _cut(corpus.load_config(CONFIG), SCALE)
    blocks = _blocks(cfg)
    assert sum(blocks) * SCALE == 62_914_560
    cap = _capacity_for(sum(blocks))
    assert cap == 1 << 20
    probe = DeviceArena()
    budget = int(cap * probe.device_row_bytes) + probe._pw_cap * 4 \
        + probe._doc_cap
    lay = corpus.layout(cfg, 2 ** 31 + 42)
    idx = RWIIndex()
    # the event totals of this store's load alone, as a cell's process
    # holds them (the suite's other stores write the same totals)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(eventtracker, "_totals", {})
        ds = DeviceSegmentStore(idx, budget_bytes=budget)
        assert ds.arena.fits(cap, ds.arena._pw_cap)
        assert not ds.arena.fits(2 * cap, ds.arena._pw_cap)
        low = {}
        for term in lay.terms:
            plist = PostingsList(*corpus.term_list(lay, term, 2 ** 31 + 42))
            if term.tier == "low":
                low[word2hash(term.name)] = plist
            else:
                idx.ingest_run({word2hash(term.name): plist})
        idx.ingest_run(low)
        totals = dict(eventtracker._totals)
    yield ds, lay, blocks, cap, totals
    ds.close()


def test_a_budget_of_c_and_not_2c_takes_the_whole_index(full_store):
    ds, lay, blocks, cap, _totals = full_store
    c = ds.counters()
    assert c["arena_rows_skipped"] == 0
    assert c["arena_rows_packed"] == sum(blocks) == lay.postings
    assert c["arena_used_rows"] == ds.live_rows() == sum(blocks)
    assert c["arena_capacity_rows"] == cap
    # every list serves from the device, the Low run's last one too
    last = lay.tier("low")[-1]
    assert ds.spans_for(word2hash(last.name))
    assert ds.rank_term(word2hash(last.name), RankingProfile(),
                        k=10) is not None


def test_the_readers_take_the_arena_after_the_store(full_store,
                                                   monkeypatch):
    """The process's event totals after the load say what the store's
    own counters and gauges say."""
    ds, lay, blocks, cap, totals = full_store
    c = ds.counters()
    monkeypatch.setattr(eventtracker, "_totals", dict(totals))
    ctx = {"workload": "wiki.full"}
    assert arena_resident_pct.read(ctx) == 100.0
    assert arena_fill_pct.read(ctx) == pytest.approx(
        100.0 * c["arena_used_rows"] / c["arena_capacity_rows"])
    assert arena_fill_pct.read(ctx) == pytest.approx(
        100.0 * sum(blocks) / cap)
    assert (full_join_roofline.index_items("join_bitmap_slots") or 0) \
        == c["join_bitmap_slots"]
    assert arena_grow_s.read(ctx) > 0.0


@pytest.mark.parametrize("reader", [arena_resident_pct, arena_fill_pct,
                                    full_join_roofline])
def test_a_program_without_the_figures_reads_nothing(monkeypatch, reader):
    """A program that wrote none of the arena's events (the parent of
    `arena_grow` and `join_bitmap_slots`, before it packs a run): each
    reader leaves its metric out and does not raise."""
    monkeypatch.setattr(eventtracker, "_totals", {})
    ctx = {"workload": "wiki.full", "trace": {"by_program": {}}}
    assert reader.read(ctx) is None


def test_without_the_span_family_grow_seconds_read_nothing(monkeypatch):
    from yacy_search_server_tpu.utils import histogram
    monkeypatch.setattr(histogram, "get", lambda name: None)
    assert arena_grow_s.read({}) is None


def _index_totals(**items):
    return {(eventtracker.EClass.INDEX, label): [1, n, 0.0]
            for label, n in items.items()}


def test_a_refused_run_reads_under_100(monkeypatch):
    monkeypatch.setattr(eventtracker, "_totals", _index_totals(
        devstore_pack=750, devstore_skip=250, arena_grow=1024))
    assert arena_resident_pct.read({}) == 75.0
    assert arena_fill_pct.read({}) == pytest.approx(100.0 * 750 / 1024)


def _roofline_ctx(queries, served, served_sm, seconds):
    lay = corpus.layout(corpus.load_config(CONFIG), 1)
    by = lay.by_name()
    rows = [[i, 0.0, 0.1, 200, False, [], None]
            for i in range(len(queries))]
    return {"workload": "wiki.full", "rows": rows, "trace_rows": rows,
            "queries": queries, "host_gate_rows": run.SHIPPED_HOST_GATE_ROWS,
            "lengths": lambda qi: [by[w].length
                                   for w in queries[qi].split()],
            "trace": {"by_program": {"jit__rank_join_bm_x": seconds}},
            "trace_counters": {"join_served": served,
                               "join_sm_served": served_sm},
            "peak": {"bytes_per_s": 819e9}}


def test_the_roofline_takes_each_partners_mode_from_the_slots(monkeypatch):
    """32 slots at 10M documents: the High lists and the first 18 Med
    lists hold one. A Med partner numbered 18 or more joins by
    sort-merge; a High partner always holds a bitmap."""
    from benchmarks import costs, costs_join
    monkeypatch.setattr(eventtracker, "_totals", _index_totals(
        devstore_pack=1, arena_grow=1 << 26, join_bitmap_slots=32))
    holders = full_join_roofline.slot_holders({"workload": "wiki.full"}, 32)
    assert holders == {f"zh{i}" for i in range(14)} | {
        f"zm{i}" for i in range(18)}
    ctx = _roofline_ctx(["zh0 zh5", "zm3 zh2", "zl7 zh1", "zm2 zm30"],
                        served=4, served_sm=1, seconds=1.0)
    bm = [costs.join_bitmap_bytes(2_097_152, 1),
          costs.join_bitmap_bytes(262_144, 1),
          costs.join_bitmap_bytes(8_192, 1)]
    sm = costs_join.join_sortmerge_bytes(262_144, [262_144])
    want = 100.0 * (3 * sum(bm) / 3 + sm) / 819e9
    assert full_join_roofline.read(ctx) == pytest.approx(want)
    # a sort-merge join served where none was sent: no attribution
    ctx = _roofline_ctx(["zh0 zh5"], served=2, served_sm=1, seconds=1.0)
    assert full_join_roofline.read(ctx) is None
    # a CPU trace has no device plane
    ctx["trace"] = None
    assert full_join_roofline.read(ctx) is None


def test_the_cells_rehearsal_is_correct_against_the_reference():
    """run.py end to end at the rehearsal's 1/64 on the CPU: the answers
    equal the plain reference's, every posting is resident, and the
    arena's readers report."""
    # one device, as the cell has one chip (the suite's eight virtual
    # devices would start the mesh store)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "wiki.full",
         "--seed", str(2 ** 31 + 4201), "--seconds", "4", "--trace", "1",
         "--cpu-rehearsal"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    m = line["metrics"]
    assert m["arena_resident_pct"]["value"] == 100.0
    assert 50.0 < m["arena_fill_pct"]["value"] <= 100.0
    assert m["arena_grow_s"]["value"] > 0.0
    # a CPU trace has no device plane: no device metric is invented
    assert "full_join_roofline" not in m
    assert "device_idle_pct" not in m
    assert line["device"]["platform"] == "cpu"
    assert np.isfinite(m["device_answer_pct"]["value"])
