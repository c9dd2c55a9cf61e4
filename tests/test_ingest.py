"""Streaming-ingest write path tests (ISSUE 13).

Covers the four pillars of the `ingest/` subsystem:

- **device-side index build** — `pack_block_batch` bit-identical to the
  host `ops/packed.pack_block` over adversarial ranges (all-equal, full
  int16, negatives, 30-bit flags, ragged counts, mixed-size batches),
  through both the vmapped kernel and the MIN/MAX_DEV_ROWS host-policy
  routing;
- **crawl-to-searchable SLO** — stamps flow entry → searchable →
  flushed → device, the histogram families are canonical (always on
  /metrics), the pending-stamp bounds hold, and the
  `ingest_slo_searchable` health rule fires on a sustained freshness
  burn;
- **bounded-buffer backpressure** — writers block (counted,
  SLO-visible) at the hard cap instead of growing the RAM buffer
  unboundedly, and the flush is single-flight under concurrent
  writers;
- **merge/promotion scheduler** — deferral parks the cleanup job's
  merge ask (smallest max_runs wins) and the devstore's promotions;
  the `merge_scheduler` actuator defers on a serving burn and catches
  up after hysteresis, with breadcrumbs; the Performance_Ingest_p
  panel renders the whole loop.
"""

import threading
import time
import types

import numpy as np
import pytest

from yacy_search_server_tpu.index import postings as P
from yacy_search_server_tpu.ingest import devbuild
from yacy_search_server_tpu.ingest import slo as ingest_slo
from yacy_search_server_tpu.ingest.scheduler import MergeScheduler
from yacy_search_server_tpu.ops import packed as PK
from yacy_search_server_tpu.utils import histogram


# -- device-side index build: the parity contract ----------------------------

def _rand_block(rng, n, lo=-32768, hi=32767, flagbits=30):
    f16 = rng.integers(lo, hi, size=(n, P.NF)).astype(np.int16)
    fl = rng.integers(0, 1 << flagbits, size=n).astype(np.int32)
    dd = np.sort(rng.choice(2 ** 31 - 1, size=n,
                            replace=False)).astype(np.int32)
    return f16, fl, dd


def _assert_block_equal(b, ref, what):
    assert np.array_equal(b.words, ref.words), f"{what}: words"
    assert np.array_equal(b.word_offs, ref.word_offs), f"{what}: offs"
    assert np.array_equal(b.widths, ref.widths), f"{what}: widths"
    assert np.array_equal(b.mins, ref.mins), f"{what}: mins"
    assert b.count == ref.count, f"{what}: count"


def test_pack_block_batch_kernel_parity_adversarial(monkeypatch):
    """The vmapped kernel's output is BIT-IDENTICAL to the host packer
    on every adversarial shape — including sub-MIN_DEV_ROWS blocks,
    forced through the kernel so the policy routing cannot hide a
    lay-down bug."""
    monkeypatch.setattr(devbuild, "MIN_DEV_ROWS", 1)
    rng = np.random.default_rng(7)
    cases = [_rand_block(rng, n) for n in (1, 3, 63, 64, 255, 256,
                                           257, 1000)]
    # all-equal columns (w=1 floor), zeros, negatives, 30-bit flags
    cases.append((np.zeros((5, P.NF), np.int16),
                  np.zeros(5, np.int32),
                  np.arange(5, dtype=np.int32)))
    cases.append((np.full((7, P.NF), -5, np.int16),
                  np.full(7, (1 << 30) - 1, np.int32),
                  np.arange(7, dtype=np.int32)))
    blocks = devbuild.pack_block_batch(cases)
    for i, ((f16, fl, dd), b) in enumerate(zip(cases, blocks)):
        ref = PK.pack_block(f16, fl, dd)
        _assert_block_equal(b, ref, f"case {i}")
        uf, ufl, udd = PK.unpack_block(b)
        assert np.array_equal(uf, f16) and np.array_equal(ufl, fl) \
            and np.array_equal(udd, dd), f"case {i}: round trip"


def test_pack_block_batch_policy_routing_stays_bit_identical():
    """With the production MIN/MAX_DEV_ROWS policy live, a mixed batch
    (host-packed stubs + device-packed run-scale blocks, input order
    preserved) is still bit-identical throughout."""
    rng = np.random.default_rng(11)
    sizes = (2, 128, 30, 512, devbuild.MIN_DEV_ROWS,
             devbuild.MIN_DEV_ROWS - 1, 0, 700)
    cases = [_rand_block(rng, n) if n else
             (np.zeros((0, P.NF), np.int16), np.zeros(0, np.int32),
              np.zeros(0, np.int32))
             for n in sizes]
    blocks = devbuild.pack_block_batch(cases)
    assert len(blocks) == len(cases)
    for i, ((f16, fl, dd), b) in enumerate(zip(cases, blocks)):
        ref = PK.pack_block(f16, fl, dd)
        _assert_block_equal(b, ref, f"size {sizes[i]}")


def test_rows_bucket_is_pow2_and_bounded():
    assert devbuild.rows_bucket(1) == 256
    assert devbuild.rows_bucket(256) == 256
    assert devbuild.rows_bucket(257) == 512
    assert devbuild.rows_bucket(5000) == 8192
    for n in (1, 100, 256, 999, 4097):
        b = devbuild.rows_bucket(n)
        assert b >= max(256, n) and (b & (b - 1)) == 0


def test_pack_kernel_registered_in_roofline():
    from yacy_search_server_tpu.ops import roofline as RF
    assert "_pack_block_batch_kernel" in RF.KERNELS
    c = RF.cost("_pack_block_batch_kernel", bs=8, rows=1024)
    assert c.flops > 0 and c.bytes > 0


# -- crawl-to-searchable SLO --------------------------------------------------

def _fresh_tracker(monkeypatch):
    t = ingest_slo.IngestTracker()
    monkeypatch.setattr(ingest_slo, "TRACKER", t)
    return t


def test_slo_families_are_canonical_and_background():
    """Every ingest family is pre-registered (health rule + exposition
    always resolve) and prefixed background (freshness walls must never
    decide a SERVING latency verdict)."""
    for name, help_ in ingest_slo.FAMILIES.items():
        assert name in histogram.CANONICAL, name
        assert histogram.get(name) is not None
        assert any(name.startswith(p)
                   for p in histogram.BACKGROUND_PREFIXES), name


def test_tracker_stamp_flow_entry_to_device(monkeypatch):
    t = _fresh_tracker(monkeypatch)
    rwi = object()
    run = object()
    t0 = t.stamp() - 0.050                    # entered 50 ms ago
    t.note_stored(rwi, t0)
    assert t.counters()["docs_searchable"] == 1
    stamps = t.flush_begin(rwi)
    assert stamps == [t0]
    assert t.flush_begin(rwi) == []           # claimed exactly once
    t.run_pending(run, stamps)
    t.flush_done(stamps)
    assert t.counters()["docs_flushed"] == 1
    t.device_packed(run)
    assert t.counters()["docs_device"] == 1
    t.device_packed(run)                      # idempotent: stamps gone
    assert t.counters()["docs_device"] == 1


def test_tracker_forget_and_counted_discard(monkeypatch):
    t = _fresh_tracker(monkeypatch)
    rwi = object()
    t.note_stored(rwi, t.stamp())
    t.forget(rwi)                             # the close() hook
    assert t.flush_begin(rwi) == []           # nothing inherited
    t.discard([1.0, 2.0])                     # empty-flush path
    assert t.counters()["stamps_dropped"] == 2


def test_tracker_pending_rwi_bound_evicts_oldest(monkeypatch):
    t = _fresh_tracker(monkeypatch)
    monkeypatch.setattr(ingest_slo, "MAX_PENDING_RWIS", 2)
    stores = [object() for _ in range(3)]
    for s in stores:
        t.note_stored(s, t.stamp())
    # the oldest store's list aged out, counted; the newest two stand
    assert t.counters()["stamps_dropped"] == 1
    assert t.flush_begin(stores[0]) == []
    assert len(t.flush_begin(stores[2])) == 1


def test_tracker_pending_run_bound_ages_out(monkeypatch):
    t = _fresh_tracker(monkeypatch)
    monkeypatch.setattr(ingest_slo, "MAX_PENDING_RUNS", 2)
    runs = [object() for _ in range(3)]
    for r in runs:
        # 3 stamps per run: an evicted run must count EVERY stamp it
        # carried (the never-silent contract), not one per run
        t.run_pending(r, [t.stamp(), t.stamp(), t.stamp()])
    assert t.counters()["stamps_dropped"] == 3
    t.device_packed(runs[0])                  # aged out: no observation
    assert t.counters()["docs_device"] == 0
    t.device_packed(runs[2])
    assert t.counters()["docs_device"] == 3


def test_segment_store_document_observes_searchable_and_flushed(
        tmp_path):
    from yacy_search_server_tpu.document.parser.registry import \
        parse_source
    from yacy_search_server_tpu.index.segment import Segment

    h_search = histogram.get("ingest.searchable")
    h_flush = histogram.get("ingest.flushed")
    c0 = ingest_slo.TRACKER.counters()
    n0_search, n0_flush = h_search.count, h_flush.count
    seg = Segment(data_dir=str(tmp_path / "seg"), max_ram_postings=40)
    try:
        entry = ingest_slo.TRACKER.stamp()
        for i in range(8):
            html = (f"<html><head><title>t{i}</title></head><body>"
                    f"<p>alpha beta gamma{i} delta</p></body>"
                    f"</html>").encode()
            doc = parse_source(f"http://s{i}.t/d{i}.html",
                               "text/html", html)[0]
            seg.store_document(doc, ingest_stamp=entry)
        seg.rwi.flush()
    finally:
        seg.close()
    c1 = ingest_slo.TRACKER.counters()
    assert c1["docs_searchable"] - c0["docs_searchable"] == 8
    assert c1["docs_flushed"] - c0["docs_flushed"] == 8
    assert h_search.count - n0_search == 8
    assert h_flush.count - n0_flush == 8


def test_ingest_slo_health_rule_burns_and_recovers(tmp_path):
    from yacy_search_server_tpu.switchboard import Switchboard

    # the rule judges a FRACTION of the windowed documents: fast stamps
    # an earlier test of this worker left in the windows would dilute
    # the burn below the budget
    histogram.reset()
    sb = Switchboard(data_dir=str(tmp_path / "DATA"))
    try:
        sb.health.tick()
        st = sb.health.states["ingest_slo_searchable"]
        assert st.state == "ok"              # below the traffic floor
        # a sustained freshness burn: every doc far over the objective,
        # across enough rotations that fast AND slow windows burn
        for _ in range(40):
            histogram.observe("ingest.searchable", 60_000.0)
        sb.health.tick()
        st = sb.health.states["ingest_slo_searchable"]
        assert st.state == "critical", (st.state, st.cause)
        assert "crawl-to-searchable" in st.cause
        # traffic drains out of the windows -> verdict recovers
        for _ in range(histogram.WINDOWS + 1):
            histogram.rotate_all()
        sb.health.tick()
        assert sb.health.states["ingest_slo_searchable"].state == "ok"
    finally:
        sb.close()


# -- bounded-buffer backpressure ---------------------------------------------

def test_wait_capacity_blocks_counted_at_hard_cap():
    from yacy_search_server_tpu.index.rwi import RWIIndex

    rwi = RWIIndex(max_ram_postings=40)
    assert rwi.hard_max_ram_postings() == 80
    real_flush = rwi.flush

    def slow_flush():
        time.sleep(0.05)                     # a real flush wall
        return real_flush()
    rwi.flush = slow_flush

    waits0 = ingest_slo.TRACKER.counters()["backpressure_waits"]
    feats = np.ones(P.NF, np.int32)
    max_seen = [0]
    threads = 6

    def writer(t):
        for i in range(80):
            rwi.wait_capacity()
            rwi.add(bytes([t]) * 12, t * 1000 + i, feats)
            max_seen[0] = max(max_seen[0], rwi._ram_count)

    ts = [threading.Thread(target=writer, args=(t,))
          for t in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    # bounded: between a writer's capacity check and its add, at most
    # the other writers slip one posting each past the cap
    assert max_seen[0] <= rwi.hard_max_ram_postings() + threads, \
        f"RAM buffer grew to {max_seen[0]} past the hard cap"
    assert ingest_slo.TRACKER.counters()["backpressure_waits"] > waits0
    assert histogram.get("ingest.backpressure").count > 0


def test_maybe_flush_is_single_flight():
    from yacy_search_server_tpu.index.rwi import RWIIndex

    rwi = RWIIndex(max_ram_postings=10)
    feats = np.ones(P.NF, np.int32)
    for i in range(20):
        rwi.add(b"term00000000", i, feats)
    assert rwi.needs_flush()
    inside = [0]
    max_inside = [0]
    gate = threading.Lock()
    real_flush = rwi.flush

    def tracked_flush():
        with gate:
            inside[0] += 1
            max_inside[0] = max(max_inside[0], inside[0])
        time.sleep(0.03)
        out = real_flush()
        with gate:
            inside[0] -= 1
        return out
    rwi.flush = tracked_flush

    ts = [threading.Thread(target=rwi.maybe_flush) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert max_inside[0] == 1, "duplicate flushes stacked"
    assert rwi._ram_count == 0


# -- merge/promotion scheduler ------------------------------------------------

def _stub_sb():
    calls = []

    def merge_runs(max_runs=8):
        calls.append(max_runs)
        return True
    sb = types.SimpleNamespace(
        index=types.SimpleNamespace(
            rwi=types.SimpleNamespace(merge_runs=merge_runs),
            devstore=None))
    return sb, calls


def test_scheduler_defers_smallest_ask_wins_and_catches_up():
    sb, calls = _stub_sb()
    sched = MergeScheduler(sb)
    assert sched.request_merge(max_runs=4)    # not deferred: runs now
    assert calls == [4]
    sched.set_deferred(True)
    assert sched.defer_promotions()
    assert not sched.request_merge(max_runs=8)
    assert not sched.request_merge(max_runs=2)
    assert not sched.request_merge(max_runs=5)
    assert calls == [4]                       # nothing ran while deferred
    assert sched.pending_merge() == 2         # the smallest ask wins
    assert sched.counters()["merge_deferrals"] == 3
    sched.set_deferred(False)
    ev = sched.catch_up()
    assert calls == [4, 2]
    assert ev["pending_merge_ran"] and ev["pending_max_runs"] == 2
    assert sched.counters()["merge_catch_ups"] == 1
    assert sched.pending_merge() is None


def test_devstore_promotions_park_and_resume(tmp_path):
    """A promotion submitted while the scheduler defers PARKS (counted,
    no batcher submit); resume_promotions resubmits the parked set."""
    from yacy_search_server_tpu.index.devstore import DeviceSegmentStore
    from yacy_search_server_tpu.index.postings import PostingsList
    from yacy_search_server_tpu.index.rwi import RWIIndex
    from yacy_search_server_tpu.utils.hashes import word2hash

    rwi = RWIIndex()
    rng = np.random.default_rng(3)
    th = word2hash("parkterm")
    feats = rng.integers(1, 100, (128, P.NF)).astype(np.int32)
    rwi.ingest_run({th: PostingsList(
        np.arange(128, dtype=np.int32), feats)})
    ds = DeviceSegmentStore(rwi, packed_residency=True)
    try:
        run = rwi._runs[0]
        key = (id(run), th)
        sched = types.SimpleNamespace(
            deferred=True,
            defer_promotions=lambda: True,
            note_promote_deferred=lambda: None)
        ds.ingest_scheduler = sched
        ds._submit_promote(key, run)
        assert ds.tier_promote_deferred == 1
        assert key in ds._deferred_promotes
        sched.defer_promotions = lambda: False
        assert ds.resume_promotions() == 1
        assert not ds._deferred_promotes
    finally:
        ds.close()


def test_merge_scheduler_actuator_defer_and_catch_up(tmp_path):
    from yacy_search_server_tpu.switchboard import Switchboard
    from yacy_search_server_tpu.utils.config import Config

    cfg = Config()
    cfg.set("actuator.recoverTicks", "2")
    sb = Switchboard(data_dir=str(tmp_path / "DATA"), config=cfg)
    try:
        sched = sb.ingest_scheduler
        sb.health.states["slo_serving_p95"].state = "critical"
        sb.actuators.tick()
        assert sched.deferred
        assert sb.config.get_int("ingest.mergeDeferred", 0) == 1
        # the cleanup job's merge entry parks while deferred
        assert not sched.request_merge(max_runs=3)
        assert sched.counters()["merge_deferrals"] == 1
        # hysteresis: one healthy tick is not recovery
        sb.health.states["slo_serving_p95"].state = "ok"
        sb.actuators.tick()
        assert sched.deferred
        sb.actuators.tick()
        assert not sched.deferred             # catch-up ran
        assert sb.config.get_int("ingest.mergeDeferred", 1) == 0
        assert sched.counters()["merge_catch_ups"] == 1
        crumbs = [c for c in sb.actuators.recent_breadcrumbs()
                  if c.get("actuator") == "merge_scheduler"]
        assert [c["dir"] for c in crumbs] == ["down", "up"]
        assert "deferred" in crumbs[0]["to"]
    finally:
        sb.close()


# -- observability surfaces ---------------------------------------------------

def test_metrics_and_panel_render_the_write_path(tmp_path):
    from yacy_search_server_tpu.server import servlets
    from yacy_search_server_tpu.server.objects import ServerObjects
    from yacy_search_server_tpu.server.servlets.monitoring import \
        prometheus_text
    from yacy_search_server_tpu.switchboard import Switchboard

    sb = Switchboard(data_dir=str(tmp_path / "DATA"))
    try:
        text = prometheus_text(sb, include_buckets=False)
        for key in ("docs_stamped", "docs_searchable", "docs_flushed",
                    "docs_device", "stamps_dropped",
                    "backpressure_waits", "merge_deferrals",
                    "promote_deferrals", "merge_catch_ups"):
            assert f'yacy_ingest_total{{counter="{key}"}}' in text, key
        assert "yacy_ingest_deferred " in text
        for fam in ingest_slo.FAMILIES:
            assert histogram.prom_name(fam) + "_count" in text, fam
        fn = servlets.lookup("Performance_Ingest_p")
        assert fn is not None
        prop = fn({}, ServerObjects(), sb)
        assert int(prop.get("families")) == 4
        assert int(prop.get("scheduler")) == 1
        assert prop.get("rule_state") in ("ok", "warn", "critical")
        assert "tracker_docs_stamped" in prop
    finally:
        sb.close()
