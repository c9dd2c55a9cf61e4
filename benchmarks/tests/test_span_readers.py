"""The readers of the program's own spans: each on a hand-made family,
None on an absent one, a share outside 0-100 raising; and one rehearsal
through run.run in which every one of them prints a number."""

import argparse
import importlib
import json
import os

import pytest

from benchmarks.layer_metrics import _spans
from yacy_search_server_tpu.utils import histogram, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MEDIANS = {"servlet_cpu_ms_p50": "servlet.cpu",
           "page_ms_p50": "search.page",
           "host_rank_ms_p50": "search.route.host_gate",
           "batch_queue_ms_p50": "batcher.queue",
           "device_roundtrip_ms_p50": "devstore.batch"}
NEW = [*MEDIANS, "route_host_gate_pct", "route_event_cache_pct",
       "runtime_stolen_ms_per_s"]


def reader(name):
    return importlib.import_module("benchmarks.layer_metrics." + name).read


@pytest.fixture(autouse=True)
def _fresh_families():
    histogram.reset()
    yield
    histogram.reset()


def drop(name):
    histogram._REG.pop(name, None)      # a program without that family


@pytest.mark.parametrize("metric,fam", MEDIANS.items())
def test_a_median_reader_reads_its_family_over_the_window(metric, fam):
    drop(fam)
    assert reader(metric)({}) is None               # no such family
    histogram.histogram(fam)
    assert reader(metric)({}) is None               # nothing recorded
    histogram.observe(fam, 900.0)                   # the warm-up's
    histogram.reset_windows()                       # run.py, at t0
    for ms in (1.0, 2.0, 4.0, 4.1, 4.2, 40.0, 80.0):
        histogram.observe(fam, ms)
    got = reader(metric)({})
    assert 4.0 <= got <= 5.0                        # the bucket of 4.x


@pytest.mark.parametrize("metric,route", [
    ("route_host_gate_pct", "host_gate"),
    ("route_event_cache_pct", "event_cache")])
def test_a_route_share_is_a_count_over_all_five(metric, route):
    assert reader(metric)({}) is None               # no route counted
    for r, n in zip(_spans.ROUTES, (30, 14, 46, 9, 1)):
        for _ in range(n):
            histogram.observe("search.route." + r, 1.0)
    assert reader(metric)({}) == {"host_gate": 9.0, "event_cache": 30.0}[
        route]
    assert _spans.route_counts() == {
        "event_cache": 30, "topk_cache": 14, "device": 46, "host_gate": 9,
        "host_other": 1}
    # a route that was never taken still has a share: 0 of the others
    histogram.reset()
    histogram.observe("search.route.device", 1.0)
    assert reader(metric)({}) == 0.0


def test_a_share_outside_0_to_100_raises(monkeypatch):
    # a count gone wrong is a fault, never a clipped reading
    monkeypatch.setattr(_spans, "route_counts", lambda: {
        "event_cache": 5, "topk_cache": 0, "device": -4, "host_gate": 0,
        "host_other": 0})
    with pytest.raises(ValueError):
        reader("route_event_cache_pct")({})


def test_stolen_time_is_a_sum_over_the_seconds_covered():
    read = reader("runtime_stolen_ms_per_s")
    assert read({}) is None
    fams = ("runtime.gc", "runtime.sampler_tick", "runtime.health_tick")
    for f in fams:
        histogram.observe(f, 500.0)                 # before the window
    histogram.reset_windows()
    for f, ms in zip(fams, (6.0, 3.0, 1.0)):
        histogram.observe(f, ms)
    spans = [histogram.get(f).windowed_span_s() for f in fams]
    got = read({})
    # 10 ms over the fraction of a second since the reset
    assert 10.0 / max(spans) * 0.5 <= got <= 10.0 / min(
        histogram.get(f).windowed_span_s() for f in fams) * 2.0
    # queued collections are filed before the reading
    tracing._gc_pending.append((2.0, None, 0.0, 0, 0))
    before = histogram.get("runtime.gc").windowed_sum()
    read({})
    assert histogram.get("runtime.gc").windowed_sum() == before + 2.0


def test_a_traced_rehearsal_prints_every_new_metric(capsys):
    """One --cpu-rehearsal --trace 1 run through run.run: the eight
    metrics, each a number; the five routes sum to the requests sent
    within what four clients may have in flight."""
    from benchmarks import run
    os.environ["JAX_PLATFORMS"] = "cpu"
    args = argparse.Namespace(workload="wiki.tasks", seed=2 ** 31 + 25,
                              seconds=4.0, trace=1, cpu_rehearsal=True,
                              control=None)
    assert run.run(args) == 0
    routes = _spans.route_counts()
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    for name in NEW:
        m = line["metrics"][name]
        assert isinstance(m["value"], float) and m["value"] >= 0.0, name
    assert 0 <= sum(routes.values()) - line["attempted"] <= 4
    assert routes["host_gate"] > 0 and routes["device"] > 0
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW:
        assert declared[name]["workloads"] == ["wiki.tasks"]
        assert line["metrics"][name]["unit"] == declared[name]["unit"]
