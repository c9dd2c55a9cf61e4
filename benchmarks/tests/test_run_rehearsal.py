"""run.py end to end at the rehearsal's toy size, on the CPU (about a
minute each): the last line's keys, and `correct` false when the timed
path is broken underneath."""

import argparse
import json
import os
import subprocess
import sys

import pytest

from benchmarks import reference, run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REQUIRED = ["correct", "attempted", "failed", "metrics", "device"]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    return env


def test_rehearsal_prints_the_contracts_last_line():
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "wiki.tasks",
         "--seed", str(2 ** 31 + 5), "--seconds", "3", "--trace", "1",
         "--cpu-rehearsal", "--control", "all"], cwd=ROOT, env=_env(), capture_output=True,
        text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line)[:5] == REQUIRED
    assert list(line)[-1] == "compared" and set(line) <= set(
        REQUIRED + ["breakdown", "compared"])
    assert line["correct"] is True and line["attempted"] > 0
    # every control, in the program's place, comes out as not correct
    said = [ln for ln in p.stderr.splitlines() if ln.startswith("control ")]
    assert len(said) == len(reference.CONTROLS)
    assert all(" correct false " in ln for ln in said), said
    assert sum(ln.startswith("probe ") for ln in p.stderr.splitlines()) \
        == len(reference.PROBES)
    assert line["device"]["platform"] == "cpu"      # never a device number
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"}, name
    # a CPU trace has no device plane: no trace metric is invented
    assert "join_roofline" not in line["metrics"]
    assert "device_idle_pct" not in line["metrics"]
    assert p.stderr.strip().splitlines()[-1].startswith("compared ")
    for v in line["compared"].values():
        assert set(v) == {"value", "limit"}


def test_without_a_chip_the_command_refuses_and_prints_no_result():
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "wiki.tasks",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=ROOT,
        env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def _altered(plain):
    def fill(self, scores, docids):
        return plain(self, scores + 1, docids)
    return fill


def _best_left_out(plain):
    def fill(self, scores, docids):
        return plain(self, scores[1:], docids[1:])
    return fill


@pytest.mark.parametrize("fault,number", [
    (_altered, "max_rank_gap"), (_best_left_out, "max_miss_gap")],
    ids=["ranking_altered", "best_candidate_left_out"])
def test_a_broken_timed_path_is_not_correct(capsys, fault, number):
    """The harness's look for a chip skipped (the rehearsal), the rest of
    a run driven, with the answer broken where the search event takes it
    from the ranker: every ranking altered by one, or the best candidate
    of every query left out."""
    from yacy_search_server_tpu.search import searchevent
    plain = searchevent.SearchEvent._fill_results

    def plant(node):
        searchevent.SearchEvent._fill_results = fault(plain)

    os.environ["JAX_PLATFORMS"] = "cpu"
    args = argparse.Namespace(workload="wiki.tasks", seed=2 ** 31 + 9,
                              seconds=3.0, trace=0, cpu_rehearsal=True,
                              control=None)
    try:
        assert run.run(args, fault=plant) == 0
    finally:
        searchevent.SearchEvent._fill_results = plain
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["compared"]["wrong_answers"]["value"] > 0
    assert line["compared"][number]["value"] >= 1
