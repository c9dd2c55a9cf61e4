"""The deployment `lucene-wikimedium10m-mesh4` and its cell
`mesh4.tasks`: the file's arithmetic, the four readers the cell brings,
each on a context without what it reads and on a hand-made one, and a
rehearsal of the cell with a mesh store attached."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmarks import corpus, costs, costs_mesh, trace_reduce
from benchmarks.layer_metrics import _mesh
from yacy_search_server_tpu.utils import histogram

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = "lucene-wikimedium10m-mesh4"
ACCEPTED = "lucene-wikimedium10m-default"
CELL = "mesh4.tasks"


def reader(name):
    return importlib.import_module("benchmarks.layer_metrics." + name).read


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def test_the_configuration_loads_and_lays_out_the_accepted_corpus():
    cfg, old = corpus.load_config(CONFIG), corpus.load_config(ACCEPTED)
    for key in ("source", "deployment", "layout", "published",
                "guarantees", "reduced", "reduced_why", "assumed"):
        assert cfg[key], key
    assert len(cfg["source"]) <= 200
    assert cfg["corpus"] == old["corpus"]               # word for word
    assert cfg["guarantees"] == old["guarantees"]
    assert cfg["published"] == old["published"]
    assert cfg["yacy_conf"] == [] and cfg["reduced"] == old["reduced"]
    assert set(old["assumed"]) < set(cfg["assumed"])    # plus the layout
    lay = corpus.layout(cfg, 2 ** 31 + 3)
    rows = {t: sum(x.length for x in lay.tier(t)) for t in corpus.TIERS}
    assert rows == {"high": 14 * 524_288, "med": 50 * 65_536,
                    "low": 2_496 * 2_048}
    assert lay.postings == cfg["resident_postings"] == 15_728_640
    assert (lay.docs, lay.hosts, len(lay.terms)) == (2_500_000, 4096, 2560)
    bench = _bench()
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "tasks", 4)
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           CELL + ".json"), encoding="utf-8") as f:
        wl = json.load(f)
    assert (wl["config"], wl["generator"], wl["clients"]) \
        == (CONFIG, "tasks", 4)
    assert set(wl["params"]["categories"].values()) == {1}
    assert len(wl["params"]["categories"]) == 6


def _ctx(queries, lengths, counters, trace_counters=None, trace=None):
    rows = [[i, 0.0, 0.01, 200, False, [], None]
            for i in range(len(queries))]
    return {"workload": CELL, "rows": rows, "queries": queries,
            "trace_rows": rows, "lengths": lambda qi: lengths[qi],
            "host_gate_rows": 4096, "attempted": len(rows),
            "counters": counters, "trace_counters": trace_counters or {},
            "trace": trace, "peak": costs.peak("TPU v5 lite")}


QUERIES = ["zh3 zh7", "zh5 zm2", "zh9 zl1", "zm4", "zh1 zh2"]
LENGTHS = [[524288, 524288], [524288, 65536], [524288, 2048], [65536],
           [524288, 524288]]


def test_the_bytes_a_chip_reads_for_a_conjunction():
    # And HighHigh: a quarter of 524,288 rare rows at 43 B, a quarter of
    # the partner's 524,288 side-table entries at 8 B, 128 rows of 8 B out
    assert costs_mesh.mesh_join_bytes(524288, [524288]) \
        == 131072 * 43 + 131072 * 8 + 1024 == 6_685_696
    # And HighMed: the Med list is the rare one, the High list the partner
    assert costs_mesh.mesh_join_bytes(65536, [524288]) \
        == 16384 * 43 + 131072 * 8 + 1024 == 1_754_112
    assert costs_mesh.mesh_join_bytes(65536, [524288], chips=1) \
        == 65536 * 43 + 524288 * 8 + 1024
    with pytest.raises(ValueError):
        costs_mesh.mesh_join_bytes(65536, [])
    ctx = _ctx(QUERIES, LENGTHS, {})
    assert _mesh.conjunction_shapes(ctx, ctx["rows"]) == [
        (524288, [524288]), (65536, [524288]), (524288, [524288])]
    assert _mesh.cell_chips(ctx) == 4


def test_the_readers_leave_a_program_without_the_counters_out():
    ctx = _ctx(QUERIES, LENGTHS, {"queries_served": 4})     # the parent
    assert reader("mesh_declined_pct")(ctx) is None
    assert reader("mesh_join_roofline")(ctx) is None        # no trace
    assert reader("mesh_collective_pct")(ctx) is None
    trace = {"by_program": {"_mesh_join_shard": 0.5}, "by_op": {},
             "busy_s": 0.5, "planes": []}
    ctx = _ctx(QUERIES, LENGTHS, {"queries_served": 4},
               trace_counters={"queries_served": 4}, trace=trace)
    assert reader("mesh_join_roofline")(ctx) is None        # not counted
    assert reader("mesh_collective_pct")(ctx) is None       # no ops line


def test_the_declined_share_and_the_roofline_share():
    ctx = _ctx(QUERIES, LENGTHS, {"join_served": 2, "join_fallbacks": 1})
    # 3 of the 5 are device-eligible conjunctions
    assert reader("mesh_declined_pct")(ctx) == pytest.approx(100 / 3)
    with pytest.raises(ValueError):             # more than were sent
        reader("mesh_declined_pct")(_ctx(QUERIES, LENGTHS,
                                         {"join_fallbacks": 4}))
    seconds = {"_mesh_join_shard": 0.050, "packed": 3.0,
               "_mesh_xjoin_shard": 9.0, "_mesh_pruned_shard": 1.0}
    trace = {"by_program": seconds, "by_op": {}, "busy_s": 1.0,
             "planes": []}
    ctx = _ctx(QUERIES, LENGTHS, {}, {"join_served": 3}, trace)
    least = 3 * (2 * 6_685_696 + 1_754_112) / 3 / 819e9
    assert reader("mesh_join_roofline")(ctx) == pytest.approx(
        100.0 * least / 0.050)
    seconds["_mesh_join_shard"] = 1e-6          # over 100: never clipped
    with pytest.raises(ValueError):
        reader("mesh_join_roofline")(ctx)
    del seconds["_mesh_join_shard"]             # no such program ran
    assert reader("mesh_join_roofline")(ctx) is None


def _ops(*events):
    return [(name, start, dur) for name, start, dur in events]


def test_the_collective_share_of_a_hand_made_trace():
    gather = ("%all-gather.1 = s32[4,1,128]{2,1,0:T(1,128)S(1)} "
              "all-gather(s32[1,1,128]{2,1,0:T(1,128)S(1)} %slice_bi")
    reduce_ = ("%pmax.14 = s32[17]{0:T(128)S(1)} all-reduce(s32[17]"
               "{0:T(128)S(1)} %get-tuple-element.51), channel_id")
    fusion = ("%fusion.1 = s16[196608,17]{0,1:T(8,128)(2,1)S(1)} "
              "fusion(s16[4227072,17]{0,1} %all-gather.9)")
    sort = ("%sort.101 = (s32[393216]{0:T(1024)S(1)}, s32[393216]"
            "{0:T(1024)}) sort(s32[393216]{0:T(1024)} %x)")
    assert _mesh.operation(gather) == "all-gather"
    assert _mesh.operation(reduce_) == "all-reduce"
    assert _mesh.operation(fusion) == "fusion"      # its operand's name
    assert _mesh.operation(sort) == "sort"          # does not count
    assert _mesh.operation("all-reduce-start.3") == "all-reduce-start"
    assert _mesh.is_collective("%ag = s32[4]{0} all-gather-done(%s)")
    assert not _mesh.is_collective(fusion)
    one = [("XLA Modules", [("jit__mesh_join_shard(1)", 0, 1000)]),
           ("XLA Ops", _ops((fusion, 0, 900), (gather, 900, 60),
                            (reduce_, 960, 40)))]
    planes = [("/device:TPU:%d" % i, one) for i in range(4)] \
        + [("/device:CUSTOM:Megascale Trace", []), ("/host:CPU", [])]
    tr = trace_reduce.combine([trace_reduce.reduce_planes(planes)])
    # five planes named /device:, one of them empty: the average reads
    # 4/5 of a chip's busy time, and the share is untouched by it
    assert tr["busy_s"] == pytest.approx(4 * 1000e-9 / 5)
    ctx = _ctx(QUERIES, LENGTHS, {}, trace=tr)
    assert reader("mesh_collective_pct")(ctx) == pytest.approx(10.0)
    planes = [("/device:TPU:0", [one[0], ("XLA Ops",
                                          _ops((fusion, 0, 1000)))])]
    tr = trace_reduce.combine([trace_reduce.reduce_planes(planes)])
    assert reader("mesh_collective_pct")(
        _ctx(QUERIES, LENGTHS, {}, trace=tr)) == 0.0


def test_the_join_wall_is_the_median_of_its_family():
    fam = "kernel._mesh_join_shard"
    histogram.reset()
    try:
        histogram._REG.pop(fam, None)
        assert reader("mesh_join_ms_p50")({}) is None       # no family
        histogram.observe(fam, 900.0)                       # the warm-up
        histogram.reset_windows()
        assert reader("mesh_join_ms_p50")({}) is None       # empty window
        for ms in (8.0, 18.0, 19.0, 19.5, 20.0, 35.0, 90.0):
            histogram.observe(fam, ms)
        bounds = [0.0, *histogram.BUCKET_BOUNDS_MS]
        lo, hi = next((a, b) for a, b in zip(bounds, bounds[1:])
                      if a < 19.5 <= b)
        assert lo <= reader("mesh_join_ms_p50")({}) <= hi
    finally:
        histogram.reset()


def test_the_benchmark_declares_the_four_and_a_reader_each():
    declared = {m["name"]: m for m in _bench()["per_layer"]}
    for name, (unit, moves, layer, source) in {
            "mesh_join_ms_p50": ("ms", "p50_ms", "batcher", "program_span"),
            "mesh_join_roofline": ("%", "qps", "kernels", "device_trace"),
            "mesh_collective_pct": ("%", "p50_ms", "kernels",
                                    "device_trace"),
            "mesh_declined_pct": ("%", "qps", "search event",
                                  "program_counter")}.items():
        m = declared[name]
        assert (m["unit"], m["moves"], m["layer"], m["source"]) \
            == (unit, moves, layer, source)
        assert m["workloads"] == [CELL]
        assert callable(reader(name))
    for name in ("servlet_ms_p50", "device_answer_pct", "device_idle_pct"):
        assert "workloads" not in declared[name]


def test_a_rehearsal_of_the_cell_serves_from_a_mesh_store():
    """With ONE CPU device `index.device.mesh` `auto` picks the one-chip
    store, so the rehearsal is given four virtual devices; the peer then
    builds MeshSegmentStore 1 x 4 by itself, as on a v5e-4 host."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = (
        "import sys; sys.argv = ['run.py', '--workload', 'mesh4.tasks', "
        "'--seed', '2147483655', '--seconds', '6', '--trace', '1', "
        "'--cpu-rehearsal']\n"
        "from benchmarks import run\n"
        "kinds = []\n"
        "stop = run.stop_node\n"
        "def seen(node, http, lock):\n"
        "    ds = node.sb.index.devstore\n"
        "    kinds.append((type(ds).__name__, ds.n_term, ds.n_doc, "
        "sorted(map(str, ds._fns)), len(ds._jfns)))\n"
        "    stop(node, http, lock)\n"
        "run.stop_node = seen\n"
        "rc = run.main()\n"
        "print('STORE', kinds, file=sys.stderr)\n"
        "sys.exit(rc)\n")
    got = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert got.returncode == 0, got.stderr[-3000:]
    line = json.loads(got.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"] == {**line["device"], "platform": "cpu",
                              "count": 4}
    for name in ("servlet_ms_p50", "device_answer_pct", "mesh_join_ms_p50",
                 "mesh_declined_pct"):
        assert name in line["metrics"], name
    assert line["metrics"]["mesh_declined_pct"]["value"] == 0.0
    for k in ("wrong_answers", "max_rank_gap", "max_miss_gap",
              "tie_order_answers", "stale_served"):
        assert line["compared"][k] == {"value": 0, "limit": 0}
    store = next(ln for ln in got.stderr.splitlines()
                 if ln.startswith("STORE"))
    assert "'MeshSegmentStore', 1, 4" in store
    # the pruned family alone in _fns: no prewarm compiled a b = 8 program
    assert "'pruned', 128, 8" not in store
    assert "'pruned_batch', 128, 8" not in store
