"""host_spans.label_gaps / brackets on hand-made planes."""

import pytest

from benchmarks import host_spans


def planes(host_lines, ops, mods=None):
    return [("/host:CPU", [("python3", evs) for evs in host_lines]),
            ("/device:TPU:0", [("XLA Ops", ops),
                               ("XLA Modules", mods or [])])]


# two device operations 1,000 ns apart: one gap, [100, 1100)
OPS = [("fusion.1", 0, 100), ("fusion.2", 1100, 100)]


def labels(host_lines, ops=OPS, **kw):
    got = host_spans.label_gaps(planes(host_lines, ops), **kw)
    return got[0]["labels"], got


def test_a_gap_under_one_span_names_the_most_specific_one():
    lab, got = labels([[("servlet.serving", 0, 2000),
                        ("search.join", 200, 700)]])
    assert got[0]["gap_s"] == pytest.approx(1000e-9)
    assert got[0]["at_s"] == pytest.approx(100e-9)
    # both cover half; the shorter says where the thread was
    assert lab["request"] == ["search.join", 0.7]
    assert lab["former"] == lab["dispatcher"] == lab["completer"] \
        == lab["runtime"] == ["none", 0.0]


def test_a_gap_under_two_threads_is_covered_by_their_union():
    # two request threads in the same stage one after the other: 40% each
    lab, _ = labels([[("search.normalizing", 100, 400)],
                     [("search.normalizing", 600, 400)],
                     [("kernel.fetch", 0, 1200)],
                     [("runtime.gc", 150, 900)]])
    assert lab["request"] == ["search.normalizing", 0.8]
    assert lab["completer"] == ["kernel.fetch", 1.0]
    assert lab["runtime"] == ["runtime.gc", 0.9]


def test_a_gap_no_span_covers_half_of_reads_none():
    lab, _ = labels([[("search.page", 100, 300)],         # 30%
                     [("PjitFunction(f)", 0, 2000)]])      # not the program's
    assert all(v == ["none", 0.0] for v in lab.values())


def test_a_span_that_straddles_the_gaps_edge_counts_its_inside_alone():
    # starts 400 before the gap, reaches 450 into it: 45%, under half
    lab, _ = labels([[("batcher.handoff", -300, 850)]])
    assert lab["former"] == ["none", 0.0]
    # reaches 500 into it: half
    lab, _ = labels([[("batcher.handoff", -300, 900)]])
    assert lab["former"] == ["batcher.handoff", 0.5]


def test_gaps_come_longest_first_and_only_the_top_ones():
    ops = [("a", 0, 10), ("a", 110, 10), ("a", 1120, 10), ("a", 1180, 10)]
    got = host_spans.label_gaps(planes([[]], ops), top=2)
    assert [g["gap_s"] for g in got] == pytest.approx([1000e-9, 100e-9])


def test_roles_come_from_the_spans_name():
    assert host_spans.role_of("devstore.batch") == "request"
    assert host_spans.role_of("batcher.form") == "former"
    assert host_spans.role_of("kernel.issue") == "dispatcher"
    assert host_spans.role_of("kernel.fetch") == "completer"
    assert host_spans.role_of("runtime.sampler_tick") == "runtime"
    assert host_spans.role_of("PjitFunction(<lambda>)") is None


def test_brackets_on_one_clock_and_on_two():
    mods = [("jit_k(1)", 1000, 500), ("jit_k(1)", 5000, 500)]
    host = [[("kernel.issue", 900, 50), ("kernel.issue", 4800, 100)],
            [("kernel.fetch", 1000, 700), ("kernel.fetch", 4950, 800)]]
    got = host_spans.brackets(planes(host, [], mods))
    assert got["programs"] == got["bracketed"] == 2
    assert got["issue_lead_us"] == pytest.approx([0.1, 0.2, 0.2])
    assert got["fetch_lag_us"] == pytest.approx([0.2, 0.25, 0.25])
    # the host's clock ahead of the device's: nothing issued before the
    # first program
    late = [[(n, s + 10_000, d) for n, s, d in evs] for evs in host]
    got = host_spans.brackets(planes(late, [], mods))
    assert got["bracketed"] == 0


def test_span_counts_say_which_threads_ran_annotated():
    got = host_spans.span_counts(planes(
        [[("kernel.issue", 0, 5), ("kernel.issue", 9, 5)],
         [("servlet.render", 0, 5)]], OPS))
    assert got["dispatcher"] == {"kernel.issue": 2}
    assert got["request"] == {"servlet.render": 1}
    assert got["former"] == {}
