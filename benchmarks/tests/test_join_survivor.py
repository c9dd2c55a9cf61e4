"""The reader of the progressive device join (`join_survivor_pct`, cell
`wiki.multi`): None on a program without the counters, the share on a
window with them, and its declaration."""

import importlib
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(counters):
    mod = importlib.import_module("benchmarks.layer_metrics.join_survivor_pct")
    return mod.read({"workload": "wiki.multi", "counters": counters})


def test_a_program_without_the_counters_reads_none():
    # the parent: joins served and counted by partners, no survivors
    assert read({"join_served": 30, "join_partners": 75,
                 "join_multi_served": 30}) is None
    # counted, but no join of two memberships in the window
    assert read({"join_survivors": 0, "join_compact_rows": 0,
                 "join_compacted": 0}) is None


def test_the_share_on_a_window_with_counters():
    # 40 joins of a 65,536-row list, a regular term first: 1/8 and the
    # title documents
    rows = 40 * 65_536
    survivors = 40 * (8_192 + 40)
    assert read({"join_compacted": 40, "join_survivors": survivors,
                 "join_compact_rows": rows, "join_compact_overflow": 0}) \
        == pytest.approx(100.0 * survivors / rows)
    # more survivors than rows is a fault of the count, never clipped
    with pytest.raises(ValueError):
        read({"join_survivors": rows + 1, "join_compact_rows": rows})


def test_the_benchmark_declares_it_last_for_wiki_multi():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    m = bench["per_layer"][-1]
    assert m == {"name": "join_survivor_pct", "unit": "%",
                 "better": "lower", "source": "program_counter",
                 "layer": "kernels", "moves": "qps",
                 "workloads": ["wiki.multi"]}
    assert m["layer"] in {x["layer"] for x in bench["per_layer"][:-1]}
