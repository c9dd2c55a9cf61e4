import os

import pytest

from benchmarks import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small_tpu.xplane.pb")


def test_union_gaps_and_programs_on_a_hand_made_plane():
    ops = [("fusion.1", 0, 100), ("fusion.2", 50, 100),      # overlap
           ("copy.3", 400, 100), ("fusion.1", 1000, 50)]
    mods = [("jit__rank_join_bm(123)", 0, 150),
            ("jit__rank_join_bm(123)", 400, 100),
            ("jit__rank_pruned_x(9)", 1000, 50)]
    out = trace_reduce.reduce_planes([
        ("/host:CPU", []),
        ("/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", mods)])])
    assert out["busy_s"] == pytest.approx(300e-9)
    assert out["span_s"] == pytest.approx(1050e-9)
    assert out["by_program"]["_rank_join_bm"] == pytest.approx(250e-9)
    assert out["program_runs"] == {"_rank_join_bm": 2, "_rank_pruned_x": 1}
    assert out["by_op"]["fusion.1"] == pytest.approx(150e-9)
    assert [g for _n, g in out["idle_gaps"]] == pytest.approx(
        [500e-9, 250e-9])
    assert out["device_ops"][0][0] == "_rank_join_bm"


def test_busy_is_averaged_over_device_planes():
    one = [("XLA Ops", [("a", 0, 100)]), ("XLA Modules", [("jit_f(1)", 0, 100)])]
    out = trace_reduce.reduce_planes([("/device:TPU:0", one),
                                      ("/device:TPU:1", [("XLA Ops", [])])])
    assert out["busy_s"] == pytest.approx(50e-9)


def test_a_trace_without_a_device_plane_reads_nothing():
    out = trace_reduce.reduce_planes([("/host:CPU", [])])
    assert out["busy_s"] == 0.0 and out["device_ops"] == []


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_the_trace_recorded_on_the_chip():
    out = trace_reduce.reduce(DATA)
    assert any(p.startswith("/device:TPU") for p in out["planes"])
    runs = out["program_runs"]
    assert runs["bench_trace_probe_sum"] == 3
    assert runs["bench_trace_probe_sort"] == 3
    assert 0 < out["busy_s"] <= out["span_s"]
    # the pause before the third round is the longest idle gap
    assert out["idle_gaps"][0][1] >= 0.04
    total = sum(out["by_program"].values())
    assert out["busy_s"] <= total * 1.05


def test_slices_of_one_window_add_up():
    a = trace_reduce.reduce_planes([("/device:TPU:0", [
        ("XLA Ops", [("f", 0, 100), ("f", 300, 100)]),
        ("XLA Modules", [("jit_k(1)", 0, 100), ("jit_k(1)", 300, 100)])])])
    b = trace_reduce.reduce_planes([("/device:TPU:0", [
        ("XLA Ops", [("g", 0, 50), ("g", 1050, 50)]),
        ("XLA Modules", [("jit_j(2)", 0, 50), ("jit_j(2)", 1050, 50)])])])
    out = trace_reduce.combine([a, b])
    assert out["busy_s"] == pytest.approx(300e-9)
    assert out["program_runs"] == {"k": 2, "j": 2}
    assert out["device_ops"] == [["k", pytest.approx(200e-9)],
                                 ["j", pytest.approx(100e-9)]]
    # a gap is an idle stretch INSIDE a slice, never the time between two
    assert [g for _n, g in out["idle_gaps"]] == pytest.approx(
        [1000e-9, 200e-9])
