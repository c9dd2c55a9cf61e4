"""Windowed log-bucket histograms (ISSUE 4): bucket math, windowed
rotation, percentile agreement against the shared nearest-rank
implementation, mergeability, exemplar policy, and the single-percentile
-implementation contract."""

import math

import numpy as np
import pytest

from yacy_search_server_tpu.utils import histogram as hg
from yacy_search_server_tpu.utils import tracing


@pytest.fixture(autouse=True)
def _fresh_registry():
    hg.reset()
    hg.set_enabled(True)
    yield
    hg.reset()
    hg.set_enabled(True)


def test_bucket_bounds_monotonic_and_log_scale():
    b = hg.BUCKET_BOUNDS_MS
    assert len(b) == hg.N_BUCKETS - 1
    assert all(b[i] < b[i + 1] for i in range(len(b) - 1))
    # log-linear: sub-bucket width within any octave is <= 25% of the
    # octave base — the resolution that backs the percentile agreement
    # bound in BASELINE.md
    for i in range(1, len(b)):
        assert (b[i] - b[i - 1]) / b[i - 1] <= 0.25 + 1e-9


def test_bucket_index_places_values_under_their_bound():
    for ms in (0.001, 0.05, 0.9, 1.0, 3.7, 100.0, 5000.0, 1e6, 1e9):
        i = hg.bucket_index(ms)
        if i < hg.N_BUCKETS - 1:
            assert ms <= hg.BUCKET_BOUNDS_MS[i] * (1 + 1e-12), (ms, i)
        if 0 < i < hg.N_BUCKETS - 1:
            assert ms >= hg.BUCKET_BOUNDS_MS[i - 1] * (1 - 1e-12), (ms, i)
    assert hg.bucket_index(0.0) == 0
    assert hg.bucket_index(-5.0) == 0
    assert hg.bucket_index(float(2 ** 40)) == hg.N_BUCKETS - 1


def test_percentiles_agree_with_nearest_rank_within_bucket_resolution():
    """The histogram-derived p50/p95 must agree with the shared
    nearest-rank percentile over the raw samples within the bucket
    resolution (~12.5%)."""
    rng = np.random.default_rng(7)
    samples = np.exp(rng.normal(math.log(20.0), 1.0, 20_000))  # lognormal
    h = hg.histogram("agree.test")
    for v in samples:
        h.record(float(v))
    sv = sorted(float(v) for v in samples)
    for q in (0.50, 0.90, 0.95, 0.99):
        true = hg.pctl(sv, q)
        est = h.percentile(q)
        assert abs(est - true) / true < 0.15, (q, est, true)


def test_shared_percentile_implementation():
    # ONE nearest-rank convention across the observability layer: the
    # tracing/profiler alias must BE the histogram module's pctl
    assert tracing._pctl is hg.pctl
    from yacy_search_server_tpu.utils.profiler import RooflineProfiler
    assert RooflineProfiler._pctl is hg.pctl


def test_windowed_rotation_forgets_old_load():
    h = hg.histogram("rot.test")
    for _ in range(100):
        h.record(500.0)
    assert h.percentile(0.5) > 300.0
    assert h.count == 100
    for _ in range(hg.WINDOWS):
        h.rotate()
    # the window forgot; the cumulative (Prometheus) counts did not
    assert h.windowed_count() == 0
    assert h.percentile(0.5) == 0.0
    assert h.count == 100
    assert sum(h.snapshot()["counts"]) == 100


def test_windowed_percentile_covers_only_recent_windows():
    h = hg.histogram("win.test")
    for _ in range(100):
        h.record(1000.0)          # old slow load
    h.rotate()
    for _ in range(100):
        h.record(1.0)             # recent fast load
    assert h.percentile(0.5, last=1) < 5.0
    assert h.percentile(0.95) > 500.0   # both windows: tail is the old load


def test_bucket_bounds_are_inclusive_le_edges():
    """Prometheus `le` semantics: a value exactly on a bound belongs to
    the bucket whose `le` it equals — and fraction_over must not count
    threshold-equal samples as over."""
    for b in (hg.BUCKET_BOUNDS_MS[0], 1.0, 2.0, 256.0,
              hg.BUCKET_BOUNDS_MS[37]):
        i = hg.bucket_index(b)
        assert hg.BUCKET_BOUNDS_MS[i] == b, (b, i)
    h = hg.histogram("le.test")
    for v in (1.0, 2.0, 3.0, 100.0):
        h.record(v)
    frac, total = h.fraction_over(2.0)
    assert total == 4
    assert abs(frac - 0.5) < 1e-9, frac


def test_fraction_over_burn_numerator():
    h = hg.histogram("frac.test")
    for _ in range(90):
        h.record(10.0)
    for _ in range(10):
        h.record(1000.0)
    frac, total = h.fraction_over(100.0)
    assert total == 100
    assert 0.08 <= frac <= 0.12


def test_merge_counts_is_additive():
    a = hg.histogram("merge.a")
    b = hg.histogram("merge.b")
    for _ in range(60):
        a.record(5.0)
    for _ in range(40):
        b.record(500.0)
    merged = hg.merge_counts([a.windowed_counts(), b.windowed_counts()])
    assert sum(merged) == 100
    p50 = hg.percentile_from_counts(merged, 0.50)
    p95 = hg.percentile_from_counts(merged, 0.95)
    assert p50 < 50.0 < p95


def test_exemplar_policy_prefers_slow_observations():
    h = hg.histogram("ex.test")
    # build a window whose p95 is ~10ms, then rotate so the gate arms
    for _ in range(200):
        h.record(10.0)
    h.rotate()
    assert h._p95_cache > 0.0
    h.record(5000.0, trace_id="slowtrace01")
    h.record(1.0, trace_id="fasttrace01")
    exes = {e[0] for e in h.snapshot()["exemplars"] if e is not None}
    assert "slowtrace01" in exes
    # the fast value lands only because its bucket had no exemplar yet —
    # a second fast record must NOT displace it with churn
    h.record(1.0, trace_id="fasttrace02")
    exes = [e for e in h.snapshot()["exemplars"] if e is not None]
    by_bucket = {hg.bucket_index(1.0)}
    fast = [e for e in exes if e[1] < 5.0]
    assert len(fast) == 1 and fast[0][0] == "fasttrace01"
    assert by_bucket  # (bucket sanity anchor)


def test_observe_registry_and_disable_gate():
    hg.observe("gate.test", 3.0)
    assert hg.get("gate.test").count == 1
    hg.set_enabled(False)
    hg.observe("gate.test", 3.0)
    assert hg.get("gate.test").count == 1
    hg.set_enabled(True)
    # canonical families survive reset (health rules reference them)
    hg.reset()
    assert hg.get("servlet.serving") is not None
    assert hg.get("gate.test") is None


def test_span_record_feeds_histograms_with_exemplar():
    """The tracing bridge: every completed span lands in the histogram
    for its name, carrying the trace id as the exemplar."""
    tracing.set_enabled(True)
    tracing.clear()
    with tracing.trace("histbridge.root") as r:
        tid = r.ctx[0]
        tracing.emit("histbridge.stage", 77.0)
    h = hg.get("histbridge.stage")
    assert h is not None and h.count == 1
    exes = [e for e in h.snapshot()["exemplars"] if e is not None]
    assert exes and exes[0][0] == tid
    assert hg.get("histbridge.root").count == 1
    tracing.clear()


def test_stage_table_excludes_wrappers_and_roots_from_dominance():
    hg.observe("servlet.yacysearch", 100.0)
    hg.observe("switchboard.search", 90.0)
    hg.observe("search.fast", 1.0)
    hg.observe("search.slow", 50.0)
    hg.observe("index.parsedocument", 500.0)
    t = hg.stage_table()
    assert t["tail_dominant_stage"] == "search.slow"
    assert "index.parsedocument" not in t["stages"]
    assert "servlet.yacysearch" in t["stages"]   # listed, never dominant
    t_all = hg.stage_table(exclude_prefixes=())
    assert t_all["tail_dominant_stage"] == "index.parsedocument"


def test_windowed_sum_and_covered_seconds_follow_the_ring():
    """Beside the windowed counts: the sum of the retained values (a
    mean over windowed_count(), a rate over windowed_span_s()) and the
    seconds the retained windows cover."""
    import time
    h = hg.histogram("sum.test")
    t0 = time.monotonic()
    h.record(3.0)
    h.record(5.0)
    assert h.windowed_sum() == 8.0
    assert 0.0 <= h.windowed_span_s() <= time.monotonic() - t0 + 0.5
    h.rotate()
    h.record(2.0)
    assert h.windowed_sum() == 10.0 and h.windowed_count() == 3
    for _ in range(hg.WINDOWS - 1):
        h.rotate()                  # the first window falls off the ring
    assert h.windowed_sum() == 2.0 and h.sum_ms == 10.0
    time.sleep(0.05)
    covered = h.windowed_span_s()
    h.reset_window()                # the harness's boundary: from now
    assert h.windowed_sum() == 0.0
    assert h.windowed_span_s() < min(covered, 0.05)
