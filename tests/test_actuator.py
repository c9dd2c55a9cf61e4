"""Self-defending serving (ISSUE 9): the actuator layer end to end.

- e2e ladder: an injected SLO burn makes the burn-rate rule fire, the
  ladder descends ONE RUNG PER SUSTAINED-BURN TICK in order, recovery
  ascends with hysteresis, and exactly one rate-limited flight-recorder
  incident names the actuator.
- 32-thread token-bucket exactness + refill-derived Retry-After.
- auto-tuner bounds: never exceeds configured min/max, bounded step per
  tick, and the floor (1 dispatcher x depth 1) never wedges a drained
  pipeline.
- sick-peer avoidance: a blackholed peer whose digest reports critical
  is SKIPPED by the scatter (counters attribute the skip) while healthy
  peers are asked; per-peer timeouts derive from digest-reported p95
  with floor/ceiling, static fallback for digest-less peers.
- degraded-mode determinism: every rung serves a prefix of the full
  pipeline bit-identically (rung 2 == the sparse stage, rung 3 == a
  previous full answer stale-ok).
- hygiene: no dead actuators (every pinned series resolves on the live
  exposition), transition counters zero-filled on /metrics.
"""

import json
import threading
import time

import numpy as np
import pytest

from yacy_search_server_tpu.index import postings as P
from yacy_search_server_tpu.index.devstore import DeviceSegmentStore
from yacy_search_server_tpu.index.postings import PostingsList
from yacy_search_server_tpu.index.rwi import RWIIndex
from yacy_search_server_tpu.ops.ranking import CardinalRanker, RankingProfile
from yacy_search_server_tpu.switchboard import Switchboard
from yacy_search_server_tpu.utils import faultinject
from yacy_search_server_tpu.utils import histogram as hg
from yacy_search_server_tpu.utils import tracing
from yacy_search_server_tpu.utils.actuator import (ActuatorEngine,
                                                   TokenBucketTable)
from yacy_search_server_tpu.utils.config import Config

TH = b"acttermAAAAA"


@pytest.fixture(autouse=True)
def _fresh_observability():
    hg.reset()
    hg.set_enabled(True)
    tracing.set_enabled(True)
    tracing.clear()
    faultinject.clear()
    yield
    hg.reset()
    hg.set_enabled(True)
    tracing.set_enabled(True)
    tracing.clear()
    faultinject.clear()


def _config(**kw) -> Config:
    cfg = Config()
    for k, v in kw.items():
        cfg.set(k, v)
    return cfg


def _burn(n: int = 200, ms: float = 900.0) -> None:
    """Fill the SLO histogram with requests far over the 250 ms
    objective — the same burn signal test_health drives."""
    h = hg.histogram("servlet.serving")
    for _ in range(n):
        h.record(ms)


def _cool() -> None:
    """Rotate every retained window out so the burn disappears (traffic
    stops; the rule drops below its qps floor -> ok)."""
    for _ in range(hg.WINDOWS + 1):
        for h in hg.all_histograms():
            h.rotate()


# -- e2e: injected burn -> ladder descends -> recovery with hysteresis ------

def test_ladder_descends_in_order_and_recovers_with_hysteresis(tmp_path):
    sb = Switchboard(data_dir=str(tmp_path / "DATA"),
                     config=_config(**{"actuator.recoverTicks": 2}))
    try:
        act = sb.actuators
        assert act.level == 0
        _burn()
        # one rung per sustained-burn tick, in order: 1, 2, 3, 4
        for want in (1, 2, 3, 4):
            sb.health.tick()
            assert sb.health.states["slo_serving_p95"].state == "critical"
            assert act.level == want, f"expected rung {want}"
            assert sb.config.get_int("serving.degradeLevel", -1) == want
        # the ladder is capped: further burn ticks hold the top rung
        sb.health.tick()
        assert act.level == 4
        # recovery with HYSTERESIS (recoverTicks=2): the first healthy
        # tick must NOT ascend; the second does — per rung
        _cool()
        for want in (4, 3, 3, 2, 2, 1, 1, 0):
            sb.health.tick()
            assert sb.health.states["slo_serving_p95"].state == "ok"
            assert act.level == want
        counts = act.transition_counts()
        assert counts[("serving_ladder", "down")] == 4
        assert counts[("serving_ladder", "up")] == 4
        # every transition left a breadcrumb naming the actuator
        crumbs = [c for c in act.recent_breadcrumbs()
                  if c["actuator"] == "serving_ladder"]
        assert len(crumbs) == 8
        assert all(c["knob"] == "serving.degradeLevel" for c in crumbs)
        # the transitions are visible on /metrics
        from yacy_search_server_tpu.server.servlets.monitoring import (
            prometheus_text)
        text = prometheus_text(sb)
        assert ('yacy_actuator_transitions_total{'
                'actuator="serving_ladder",dir="down"} 4') in text
        assert ('yacy_actuator_transitions_total{'
                'actuator="serving_ladder",dir="up"} 4') in text
        # ... and a degraded query leaves a trace span naming its stage
        act.level = 3
        ev = sb.search("tracedapple")
        assert ev.degrade_level == 3
        spans = [s.name for rec in tracing.traces(5) for s in rec.spans]
        assert "search.degraded" in spans
    finally:
        sb.close()


def test_burn_incident_names_the_actuator_exactly_once(tmp_path):
    sb = Switchboard(data_dir=str(tmp_path / "DATA"),
                     config=_config(**{"actuator.recoverTicks": 1}))
    try:
        _burn()
        for _ in range(4):
            sb.health.tick()
        # rate-limited: ONE incident despite four critical ticks
        assert len(sb.health.incidents) == 1
        body = sb.health.incidents[0]["body"]
        lines = [json.loads(ln) for ln in body.splitlines()]
        acts = [ln for ln in lines if ln.get("kind") == "actuator"]
        assert acts, "incident carries no actuator breadcrumbs"
        assert any(a["actuator"] == "serving_ladder" and a["dir"] == "down"
                   for a in acts)
        # the dump happened AFTER the first ladder step: the incident
        # already names the defense the burn triggered
        assert lines[0]["kind"] == "incident"
        assert "slo_serving_p95" in lines[0]["entered_critical"]
    finally:
        sb.close()


def test_degraded_queries_histogram_counts_per_rung(tmp_path):
    sb = Switchboard(data_dir=str(tmp_path / "DATA"))
    try:
        act = sb.actuators
        sb.search("plainquery")
        act.level = 2
        sb.search("plainquery two")
        assert act.degraded_queries[0] == 1
        assert act.degraded_queries[2] == 1
    finally:
        sb.close()


# -- admission control: token-bucket exactness + honest Retry-After ----------

def test_token_bucket_32_thread_exactness():
    tb = TokenBucketTable(capacity=100, refill_per_s=0.0)
    allowed = [0] * 32

    def worker(i):
        for _ in range(20):
            ok, _retry = tb.acquire("1.2.3.4")
            if ok:
                allowed[i] += 1

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(32)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    # EXACT: 32 threads x 20 tries against capacity 100 admit precisely
    # 100, lose none, leak none
    assert sum(allowed) == 100
    assert tb.denied == 32 * 20 - 100
    # an unrelated client has its own bucket
    ok, _ = tb.acquire("5.6.7.8")
    assert ok


def test_token_bucket_bounded_under_unique_ip_spray():
    """A spray of unique client IPs faster than the refill keeps every
    bucket non-full — the table must still stay bounded (forced
    eviction of the fullest buckets), and an evicted client returns
    with a FULL bucket, never locked out."""
    tb = TokenBucketTable(capacity=10, refill_per_s=0.01,
                          max_clients=100)
    for i in range(1000):
        tb.acquire(f"ip{i}")
    assert len(tb) <= 100
    ok, _ = tb.acquire("ip5")        # evicted client: fresh full bucket
    assert ok
    # the prune-triggering client's OWN bucket survives with its spend
    # recorded (evicting it would orphan the deduction): capacity 1,
    # no refill — the second request from the same spray client denies
    tb2 = TokenBucketTable(capacity=1, refill_per_s=0.0, max_clients=10)
    for i in range(50):
        assert tb2.acquire(f"spray{i}")[0] is True
    assert tb2.acquire("spray49")[0] is False


def test_window_retry_after_admits_the_honoring_retry():
    """The legacy-window Retry-After must account for the retry itself
    (it appends to the window before the hits > limit check): a client
    that honors the header exactly must be ADMITTED, not 429'd again
    by an off-by-one."""
    from collections import deque
    from yacy_search_server_tpu.search.accesstracker import AccessTracker
    at = AccessTracker()
    now = time.time()
    at._host_access["c"] = deque([now - 500, now - 400, now - 300,
                                  now - 10])
    r = at.retry_after_s("c", limit=3)
    # TWO oldest must age out (not one): at now+r the window holds
    # [now-300, now-10] and the retry's own append makes 3 <= limit
    assert r == pytest.approx(200.0, abs=1.0)
    assert at.retry_after_s("c", limit=10) == 0.0
    assert at.retry_after_s("unknown", limit=3) == 0.0


def test_token_bucket_retry_after_is_refill_derived():
    tb = TokenBucketTable(capacity=2, refill_per_s=0.5)
    now = 1000.0
    assert tb.acquire("c", now=now) == (True, 0.0)
    assert tb.acquire("c", now=now) == (True, 0.0)
    ok, retry = tb.acquire("c", now=now)
    assert not ok
    # empty bucket at 0.5 tokens/s: one token needs 2 s (>= the 1 s floor)
    assert retry == pytest.approx(2.0)
    # after 2 s the bucket admits again
    ok, _ = tb.acquire("c", now=now + 2.1)
    assert ok
    # refill_eta answers the same math WITHOUT charging the bucket
    # (the Retry-After for denials decided by the legacy host window)
    # (the admit above left 0.05 tokens: (1-0.05)/0.5 = 1.9 s to one)
    assert tb.refill_eta("c", now=now + 2.1) == pytest.approx(1.9)
    assert tb.refill_eta("c", now=now + 4.2) == pytest.approx(1.0)
    assert tb.refill_eta("unknown-client") == pytest.approx(1.0)


# -- batcher auto-tune: bounds, bounded step, floor never wedges -------------

def _plist(rng, n, base=0):
    docids = np.arange(base, base + n, dtype=np.int32)
    feats = rng.integers(0, 1000, (n, P.NF)).astype(np.int32)
    feats[:, P.F_FLAGS] = rng.integers(0, 2 ** 20, n)
    feats[:, P.F_DOMLENGTH] = rng.integers(0, 256, n)
    feats[:, P.F_LANGUAGE] = P.pack_language("en")
    return PostingsList(docids, feats)


def _built_store(n=20_000, dispatchers=2):
    idx = RWIIndex()
    idx.add_many(TH, _plist(np.random.default_rng(1), n))
    idx.flush()
    ds = DeviceSegmentStore(idx)
    ds.enable_batching(max_batch=4, dispatchers=dispatchers,
                       prewarm=False)
    return ds


def test_autotuner_respects_bounds_and_steps_by_one(tmp_path):
    sb = Switchboard(
        data_dir=str(tmp_path / "DATA"),
        config=_config(**{"actuator.recoverTicks": 1,
                          "actuator.dispatcherMin": 1,
                          "actuator.dispatcherMax": 9,
                          "actuator.completerDepthMin": 1,
                          "actuator.completerDepthMax": 3,
                          "index.device.dispatchers": 8}))
    try:
        act = sb.actuators
        # pin the test to the real dispatcher-pool batcher: under the
        # 8-virtual-device conftest the switchboard mounts the MESH
        # store (single-dispatcher by construction) — mount a devstore
        # so the dispatcher axis is actually tunable
        old_store = sb.index.devstore
        ds = _built_store(dispatchers=8)
        sb.index.devstore = ds
        b = ds._batcher
        assert b is not None
        real_tuning = b.tuning
        forced = {"depth": 100}

        def fake_tuning():
            t = real_tuning()
            t["queue_incoming"] = forced["depth"]
            return t

        b.tuning = fake_tuning
        seen = [real_tuning()["dispatchers"]]
        for _ in range(12):
            act.tick()
            seen.append(real_tuning()["dispatchers"])
        # bounded step: +1 per tick, never past the configured max
        assert all(b2 - a2 <= 1 for a2, b2 in zip(seen, seen[1:]))
        assert max(seen) == 9
        assert real_tuning()["dispatchers"] == 9
        # past the dispatcher max the tuner grows completer depth, also
        # capped
        assert real_tuning()["completer_depth"] == 3
        # sustained idle scales down — never below the configured floor
        forced["depth"] = 0
        for _ in range(30):
            act.tick()
        assert real_tuning()["dispatchers"] == 1
        assert real_tuning()["completer_depth"] == 1
        counts = act.transition_counts()
        assert counts[("batcher_autotune", "up")] > 0
        assert counts[("batcher_autotune", "down")] > 0
        # config knob follows the actuation
        assert sb.config.get_int("index.device.dispatchers", -1) == 1
        ds.close()
        sb.index.devstore = old_store
    finally:
        sb.close()


def test_disabled_engine_is_inert_on_the_serving_path(tmp_path):
    """actuator.enabled=false must disarm EVERY surface, not just the
    tick: admission admits everything and a frozen ladder rung stops
    applying."""
    sb = Switchboard(data_dir=str(tmp_path / "DATA"))
    try:
        act = sb.actuators
        act.level = 4                      # frozen mid-degradation
        act._avoid_peers = frozenset({"SICKPEERAAAA"})
        act.enabled = False
        assert act.effective_level() == 0
        # the frozen state must not keep actuating anywhere: peers
        # unavoided, workers told full service
        assert act.avoided_peers() == frozenset()
        assert act.serving_state() == {"level": 0, "retry_after_s": 0.0}
        act.bucket = TokenBucketTable(capacity=2, refill_per_s=0.0)
        for _ in range(10):                # far past the bucket capacity
            assert act.admit("9.9.9.9") == (True, 0.0)
        assert act.tick() == 0
        act.enabled = True
        assert act.effective_level() == 4
        assert act.avoided_peers() == frozenset({"SICKPEERAAAA"})
        assert act.admit("9.9.9.9")[0] is True   # 1st real acquire
    finally:
        sb.close()


def test_autotuner_grows_mesh_depth_without_phantom_transitions(tmp_path):
    """On a mesh store the dispatcher axis is structurally fixed at 1:
    a sustained backlog must grow the completer depth instead — and a
    saturated knob must emit NO transition (every transition is a real
    state change)."""
    from types import SimpleNamespace
    from yacy_search_server_tpu.index.meshstore import _MeshQueryBatcher
    sb = Switchboard(
        data_dir=str(tmp_path / "DATA"),
        config=_config(**{"actuator.recoverTicks": 1,
                          "actuator.completerDepthMax": 4}))
    try:
        act = sb.actuators
        old_store = sb.index.devstore
        mb = _MeshQueryBatcher(SimpleNamespace())
        sb.index.devstore = SimpleNamespace(_batcher=mb)
        real = mb.tuning
        mb.tuning = lambda: {**real(), "queue_incoming": 100}
        for _ in range(10):
            act.tick()
        assert real()["completer_depth"] == 4     # grew to the max
        counts = act.transition_counts()
        # exactly the 2 real changes (2 -> 3 -> 4); the saturated ticks
        # after that emitted NOTHING
        assert counts[("batcher_autotune", "up")] == 2
        mb.close()
        sb.index.devstore = old_store
    finally:
        sb.close()


def test_worker_shed_retry_relays_the_owner_estimate(tmp_path):
    """A rank-service worker shedding at the OWNER's rung must answer
    with the owner's recovery estimate, not its own level-0 math."""
    sb = Switchboard(data_dir=str(tmp_path / "DATA"))
    try:
        act = sb.actuators
        import time as _time
        act._remote_state = (_time.monotonic(), 4, 120.0)
        assert act.level == 0
        assert act.shed_retry_after_s() == pytest.approx(120.0)
    finally:
        sb.close()


def test_mesh_batcher_depth_tunes_with_the_same_surface():
    """The mesh batcher exposes the same tuning surface (dispatchers
    structurally 1; completer depth = the in-flight bound), so one
    actuator serves both store kinds."""
    from yacy_search_server_tpu.index.meshstore import _MeshQueryBatcher

    class _Stub:
        pass

    b = _MeshQueryBatcher(_Stub())
    try:
        t = b.tuning()
        assert t["dispatchers"] == 1 and t["completer_depth"] == 2
        t = b.set_tuning(completer_depth=4)
        assert t["completer_depth"] == 4
        t = b.set_tuning(dispatchers=7, completer_depth=0)
        assert t["dispatchers"] == 1      # structurally fixed
        assert t["completer_depth"] == 1  # floored, never a wedge
    finally:
        b.close()


def test_tuning_floor_never_wedges_a_drained_pipeline():
    ds = _built_store(dispatchers=3)
    try:
        ds._topk_cache.enabled = False
        oracle_s, _ = CardinalRanker(RankingProfile(), "en").rank(
            ds.rwi.get(TH), None, k=10)
        # scale down to the absolute floor while idle, then serve
        t = ds._batcher.set_tuning(dispatchers=1, completer_depth=1)
        assert t["dispatchers"] == 1 and t["completer_depth"] == 1
        results = []

        def worker():
            results.append(ds.rank_term(TH, RankingProfile(), k=10))

        ts = [threading.Thread(target=worker) for _ in range(8)]
        for th in ts:
            th.start()
        for th in ts:
            th.join(timeout=30)
        assert len(results) == 8
        for got in results:
            assert got is not None
            np.testing.assert_array_equal(np.asarray(got[0]), oracle_s)
        # scale back up mid-life: growth spawns live threads that serve
        t = ds._batcher.set_tuning(dispatchers=4, completer_depth=2)
        assert t["dispatchers"] == 4
        got = ds.rank_term(TH, RankingProfile(), k=10)
        assert got is not None
        np.testing.assert_array_equal(np.asarray(got[0]), oracle_s)
        # zero / negative targets clamp to the floor, never to a wedge
        t = ds._batcher.set_tuning(dispatchers=0, completer_depth=0)
        assert t["dispatchers"] == 1 and t["completer_depth"] == 1
        assert ds.rank_term(TH, RankingProfile(), k=10) is not None
    finally:
        ds.close()


def test_faultinject_dispatch_stall_drives_worker_stall_bucket():
    """The batcher.dispatch failpoint wedges a real dispatcher: the
    watchdog withdraws the query, serves it solo, and attributes the
    stall bucket — the deterministic driver the worker_stall rule tests
    ride (no organic wedge needed)."""
    ds = _built_store(dispatchers=1)
    try:
        ds._topk_cache.enabled = False
        assert ds.rank_term(TH, RankingProfile(), k=10) is not None
        b = ds._batcher
        b.WATCHDOG_S = 0.2
        faultinject.set_fault("batcher.dispatch", 2000.0)
        t0 = time.perf_counter()
        got = ds.rank_term(TH, RankingProfile(), k=10)
        dt = time.perf_counter() - t0
        assert got is not None           # solo retry served it
        assert dt < 1.5
        assert b.timeout_worker_stall >= 1
    finally:
        faultinject.clear()
        ds.close()


# -- fleet-aware remote search: sick-peer skip + adaptive timeouts -----------

class _StubProtocol:
    """Records search RPCs; answers empty result lists."""

    def __init__(self, fleet):
        self.fleet = fleet
        self.calls = []

    def search(self, target, include, exclude, **kw):
        self.calls.append((target.hash, kw.get("timeout_ms")))
        return True, {"links": [], "abstracts": {}}


def _digest(peer: str, health: int = 0, seq: int = 1, hist=None) -> dict:
    return {"v": 1, "peer": peer, "seq": seq,
            "ts": round(time.time(), 1), "hist": hist or {},
            "rules": {}, "health": health,
            "cache": {}, "queues": {}, "epoch": 0}


def test_sick_peer_skipped_and_counters_attribute_it(tmp_path):
    from yacy_search_server_tpu.peers.remotesearch import RemoteSearch
    from yacy_search_server_tpu.peers.seed import Seed
    sb = Switchboard(data_dir=str(tmp_path / "DATA"))
    try:
        fl = sb.fleet
        fl.my_hash = "MYSELFAAAAAA"
        sick_hash, ok_hash = "SICKPEERAAAA", "GOODPEERAAAA"
        # the sick peer's digest reports critical; blackhole its RPC so
        # an accidental call is LOUD (fails), not just slow
        assert fl.ingest(_digest(sick_hash, health=2))
        assert fl.ingest(_digest(ok_hash, health=0))
        faultinject.blackhole_peer(sick_hash)
        sb.actuators.tick()
        assert sb.actuators.avoided_peers() == frozenset({sick_hash})
        assert sb.config.get("remotesearch.avoidPeers") == sick_hash

        event = sb.search("remoteterm")
        proto = _StubProtocol(fl)
        rs = RemoteSearch(event, seeddb=None, dist=None, protocol=proto,
                          avoid_hashes=set(sb.actuators.avoided_peers()))
        targets = [Seed(sick_hash.encode(), name="sick"),
                   Seed(ok_hash.encode(), name="good")]
        asked = rs.start_fixed(targets, with_abstracts=False)
        rs.join(2.0)
        # the blackholed sick peer was SKIPPED, the healthy one asked
        assert asked == 1
        assert rs.peers_skipped_sick == 1
        called = {h for h, _t in proto.calls}
        assert called == {ok_hash.encode()}
        rc = fl.remote_counter_snapshot()
        assert rc["skipped_sick"] == 1
        assert rc["asked"] == 1
        # the skip is visible on /metrics
        from yacy_search_server_tpu.server.servlets.monitoring import (
            prometheus_text)
        text = prometheus_text(sb)
        assert ('yacy_remotesearch_peers_total{outcome="skipped_sick"} 1'
                in text)
        # recovery: the peer's next digest reports healthy -> unavoided
        assert fl.ingest(_digest(sick_hash, health=0, seq=2))
        sb.actuators.tick()
        assert sb.actuators.avoided_peers() == frozenset()
        counts = sb.actuators.transition_counts()
        assert counts[("remote_peer_guard", "down")] == 1
        assert counts[("remote_peer_guard", "up")] == 1
        # equal-size membership CHURN (one heals, another sickens in
        # the same tick) is a protective step, never a recovery
        assert fl.ingest(_digest(sick_hash, health=2, seq=3))
        sb.actuators.tick()                  # -> {sick}: down
        assert fl.ingest(_digest(sick_hash, health=0, seq=4))
        assert fl.ingest(_digest(ok_hash, health=2, seq=2))
        sb.actuators.tick()                  # {sick} -> {ok}: still down
        counts = sb.actuators.transition_counts()
        assert counts[("remote_peer_guard", "down")] == 3
        assert counts[("remote_peer_guard", "up")] == 1
    finally:
        sb.close()


def test_secondary_round_honors_the_sick_peer_guard(tmp_path):
    """The abstract-driven secondary round must not re-contact a peer
    the primary scatter avoided: a sick peer listed as an abstract
    holder would drag the join round for its full timeout."""
    from yacy_search_server_tpu.peers.remotesearch import RemoteSearch
    from yacy_search_server_tpu.peers.seed import Seed, SeedDB
    sb = Switchboard(data_dir=str(tmp_path / "DATA"))
    try:
        fl = sb.fleet
        fl.my_hash = "MYSELFAAAAAA"
        sick, good = b"SICKPEERAAAA", b"GOODPEERAAAA"
        seeddb = SeedDB(Seed(b"MYSELFAAAAAA", name="me"))
        seeddb.connected(Seed(sick, name="sick"))
        seeddb.connected(Seed(good, name="good"))
        event = sb.search("apple banana")       # two-word join
        proto = _StubProtocol(fl)
        rs = RemoteSearch(event, seeddb=seeddb, dist=None,
                          protocol=proto,
                          avoid_hashes={sick.decode("ascii")})
        uh = b"URLHASHAAAAA"
        for wh in event.query.goal.include_hashes:
            rs._abstracts[wh][uh] = {sick, good}   # join spans peers
        started = rs.secondary_search()
        rs.join(2.0)
        assert started == 1
        assert {h for h, _t in proto.calls} == {good}
        assert rs.peers_skipped_sick == 1
        assert fl.remote_counter_snapshot()["skipped_sick"] == 1
    finally:
        sb.close()


def test_per_peer_timeout_derives_from_digest_p95(tmp_path):
    from yacy_search_server_tpu.peers.remotesearch import RemoteSearch
    from yacy_search_server_tpu.peers.seed import Seed
    sb = Switchboard(data_dir=str(tmp_path / "DATA"))
    try:
        fl = sb.fleet
        fl.my_hash = "MYSELFAAAAAA"
        fast_hash, slow_hash, mute_hash = \
            "FASTPEERAAAA", "SLOWPEERAAAA", "MUTEPEERAAAA"
        # digest-reported RPC walls: fast ~60 ms, slow ~2000 ms
        fast_counts = [0] * hg.N_BUCKETS
        fast_counts[hg.bucket_index(60.0)] = 50
        slow_counts = [0] * hg.N_BUCKETS
        slow_counts[hg.bucket_index(2000.0)] = 50
        assert fl.ingest(_digest(
            fast_hash, hist={"dht.transfer":
                             hg.counts_to_sparse(fast_counts)}))
        assert fl.ingest(_digest(
            slow_hash, hist={"dht.transfer":
                             hg.counts_to_sparse(slow_counts)}))
        event = sb.search("timeoutterm")
        proto = _StubProtocol(fl)
        rs = RemoteSearch(event, seeddb=None, dist=None, protocol=proto,
                          timeout_s=3.0)
        fast_t = rs._peer_timeout_s(Seed(fast_hash.encode()))
        slow_t = rs._peer_timeout_s(Seed(slow_hash.encode()))
        mute_t = rs._peer_timeout_s(Seed(mute_hash.encode()))
        # fast peer: 3 x ~60 ms clamps up to the 0.5 s floor
        assert fast_t == pytest.approx(0.5)
        # slow peer: 3 x ~2 s clamps DOWN to the static ceiling
        assert slow_t == pytest.approx(3.0)
        # digest-less peer: the static fallback, unchanged
        assert mute_t == pytest.approx(3.0)
        # only the budget that actually DIFFERED counts as adaptive
        # (the slow peer's clamp back to the ceiling changed nothing)
        assert fl.remote_counter_snapshot()["adaptive_timeout"] == 1
    finally:
        sb.close()


def test_blackholed_rpc_fails_like_a_dead_network_path(tmp_path):
    """The peer.blackhole failpoint at the Protocol layer: calls to the
    blackholed peer return (False, {}) — the same contract as a
    transport failure — without a real dead network."""
    from yacy_search_server_tpu.peers.protocol import Protocol
    from yacy_search_server_tpu.peers.seed import Seed, SeedDB
    me = Seed(b"MEPEERAAAAAA", name="me")
    other = Seed(b"DARKPEERAAAA", name="dark")
    seeddb = SeedDB(me)
    seeddb.connected(other)
    proto = Protocol(seeddb, transport=None)   # transport never reached
    faultinject.blackhole_peer(other.hash)
    ok, reply = proto._call(other, "hello", {})
    assert not ok and reply == {}


# -- degraded-mode determinism (every rung = a prefix of the pipeline) -------

def test_rung2_answer_is_bit_identical_to_the_sparse_stage():
    from yacy_search_server_tpu.index.segment import Segment
    from yacy_search_server_tpu.search.query import QueryParams
    from yacy_search_server_tpu.search.searchevent import SearchEvent
    from yacy_search_server_tpu.document.document import Document
    seg = Segment(max_ram_postings=1_000_000)
    try:
        for i in range(30):
            seg.store_document(Document(
                url=f"http://h{i % 5}.example.org/p{i}",
                title=f"apple page {i}",
                text=f"apple content number {i} " + "filler " * (i % 7),
                mime_type="text/html", language="en"))
        sparse = SearchEvent(QueryParams.parse("apple"), seg)
        hybrid_q = QueryParams.parse("apple")
        hybrid_q.hybrid = True
        hybrid_q.degrade_level = 2
        degraded = SearchEvent(hybrid_q, seg)
        # rung 2 skips the rerank stage: the hybrid query's answer IS
        # the sparse stage's answer — same docs, same scores, same order
        a = [(r.urlhash, r.score) for r in sparse.results(count=10)]
        b = [(r.urlhash, r.score) for r in degraded.results(count=10)]
        assert a == b and len(a) > 0
    finally:
        seg.close()


def test_rung3_cache_only_serves_stale_ok_bit_identical():
    ds = _built_store()
    try:
        prof = RankingProfile()
        full = ds.rank_term(TH, prof, "en", k=10)   # warms the cache
        assert full is not None
        # the index moves: epoch bumps
        ds._bump_epoch()
        # rung 3 (stale-ok): the previous FULL answer serves, ordered
        # exactly as computed (tie discipline included), zero device work
        c0 = ds.counters()
        got = ds.rank_cache_get(TH, prof, "en", 10, stale_ok=True)
        c1 = ds.counters()
        assert got is not None
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(full[0]))
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(full[1]))
        assert c1["device_round_trips"] == c0["device_round_trips"]
        assert c1["rank_cache_stale_served"] == \
            c0["rank_cache_stale_served"] + 1
        # full service stays strict: the same lookup WITHOUT stale_ok
        # refuses (and evicts) the stale entry — degradation never
        # weakens the normal path's freshness contract
        assert ds.rank_cache_get(TH, prof, "en", 10) is None
        assert ds.counters()["rank_cache_stale"] == \
            c0["rank_cache_stale"] + 1
    finally:
        ds.close()


def test_rung3_event_without_cache_answers_empty_and_counts():
    from yacy_search_server_tpu.index.segment import Segment
    from yacy_search_server_tpu.search.query import QueryParams
    from yacy_search_server_tpu.search.searchevent import SearchEvent
    from yacy_search_server_tpu.document.document import Document
    from yacy_search_server_tpu.utils.eventtracker import EClass, totals
    seg = Segment(max_ram_postings=1_000_000)
    try:
        seg.store_document(Document(
            url="http://x.example.org/a", title="apple",
            text="apple text", mime_type="text/html", language="en"))
        q = QueryParams.parse("apple")
        q.degrade_level = 3
        ev = SearchEvent(q, seg)
        # no devstore cache to serve from: the rung answers EMPTY
        # instead of paying ranking work — and the miss is counted
        assert ev.results() == []
        tot = totals()
        assert tot.get((EClass.SEARCH, "DEGRADED_CACHE_ONLY_MISS"),
                       (0,))[0] >= 1
    finally:
        seg.close()


def test_rung1_skips_live_snippets_and_counts(tmp_path):
    from yacy_search_server_tpu.index.segment import Segment
    from yacy_search_server_tpu.search.query import QueryParams
    from yacy_search_server_tpu.search.searchevent import (ResultEntry,
                                                           SearchEvent)
    from yacy_search_server_tpu.document.document import Document
    from yacy_search_server_tpu.utils.eventtracker import EClass, totals
    seg = Segment(max_ram_postings=1_000_000)
    try:
        seg.store_document(Document(
            url="http://x.example.org/a", title="apple",
            text="apple text", mime_type="text/html", language="en"))

        class _NeverLoader:                  # a live fetch would explode
            def load(self, *a, **kw):
                raise AssertionError("rung 1 must not fetch live")

        q = QueryParams.parse("apple")
        q.degrade_level = 1
        q.snippet_strategy = "ifexist"       # would verify live at rung 0
        ev = SearchEvent(q, seg, loader=_NeverLoader())
        # a remote entry with no snippet would need a live fetch
        ev.add_remote_results([ResultEntry(
            docid=-1, urlhash=b"remoteAAAAAA", score=5,
            url="http://peer.example.net/r", title="remote apple",
            source="PEERAAAAAAAA")])
        got = ev.results(count=10, with_snippets=True)
        urls = {r.url for r in got}
        # the remote entry SURVIVES un-verified (no eviction while
        # degraded) and nothing fetched live
        assert "http://peer.example.net/r" in urls
        tot = totals()
        assert tot.get((EClass.SEARCH, "DEGRADED_SNIPPETS"),
                       (0,))[0] >= 1
    finally:
        seg.close()


# -- httpd surface: computed Retry-After, degrade header, shed rung ----------

@pytest.fixture
def served(tmp_path):
    import urllib.request
    from yacy_search_server_tpu.server import YaCyHttpServer
    sb = Switchboard(data_dir=str(tmp_path / "DATA"))
    srv = YaCyHttpServer(sb, port=0).start()

    def get(path):
        req = urllib.request.Request(srv.base_url + path)
        try:
            r = urllib.request.urlopen(req, timeout=10)
            return r.status, dict(r.headers), r.read()
        except urllib.error.HTTPError as e:
            return e.code, dict(e.headers), e.read()

    yield sb, get
    srv.close()
    sb.close()


def test_shed_rung_refuses_search_with_computed_retry_after(served):
    sb, get = served
    sb.actuators.level = 4
    status, headers, body = get("/yacysearch.json?query=apple")
    assert status == 429
    retry = int(headers["Retry-After"])
    # computed from the ladder's recovery math, not the legacy 600
    assert retry == int(sb.actuators.shed_retry_after_s())
    assert headers["X-YaCy-Degraded"] == "4"
    assert sb.actuators.shed_count >= 1
    # observability NEVER sheds: a degraded node must stay inspectable
    status, _h, body = get("/metrics")
    assert status == 200
    assert b"yacy_degrade_level 4" in body
    assert b'yacy_shed_requests_total' in body


def test_degraded_answers_carry_the_level_header(served):
    sb, get = served
    sb.actuators.level = 1
    status, headers, _ = get("/yacysearch.json?query=apple")
    assert status == 200
    assert headers["X-YaCy-Degraded"] == "1"
    # full service carries no degrade stamp
    sb.actuators.level = 0
    status, headers, _ = get("/yacysearch.json?query=apple")
    assert status == 200
    assert "X-YaCy-Degraded" not in headers


def test_servlet_latency_failpoint_lands_in_the_slo_histogram(served):
    """The servlet.serving failpoint injects latency INSIDE the measured
    wall: the SLO histogram sees genuinely slow requests, which is what
    lets ladder tests drive real burns without organic load."""
    _sb, get = served
    h = hg.histogram("servlet.serving")
    before = h.windowed_count()
    faultinject.set_fault("servlet.serving", 80.0)
    try:
        status, _h, _b = get("/yacysearch.json?query=apple")
        assert status == 200
    finally:
        faultinject.clear()
    counts = h.windowed_counts()
    assert sum(counts) > before
    # at least one observation at/above the injected 80 ms
    slow_from = hg.bucket_index(80.0)
    assert sum(counts[slow_from:]) >= 1


# -- worker propagation (rankservice serving_state) --------------------------

def test_rank_service_propagates_the_owner_ladder(tmp_path):
    from yacy_search_server_tpu.server.rankservice import (
        RankServiceClient, RankServiceServer)
    sock = str(tmp_path / "rank.sock")
    server = RankServiceServer(
        None, sock, state_fn=lambda: {"level": 3, "retry_after_s": 30.0})
    try:
        client = RankServiceClient(sock)
        st = client.serving_state()
        assert st["level"] == 3
        client.close()
    finally:
        server.close()


# -- hygiene: no dead actuators, zero-filled transition series ---------------

def test_every_actuator_references_only_live_metric_series(tmp_path):
    sb = Switchboard(data_dir=str(tmp_path / "DATA"))
    try:
        assert sb.actuators.undefined_series() == []
    finally:
        sb.close()


def test_transition_counters_zero_filled_on_metrics(tmp_path):
    from yacy_search_server_tpu.server.servlets.monitoring import (
        prometheus_text)
    sb = Switchboard(data_dir=str(tmp_path / "DATA"))
    try:
        text = prometheus_text(sb)
        for name in ("serving_ladder", "batcher_autotune",
                     "remote_peer_guard"):
            for d in ("down", "up"):
                assert (f'yacy_actuator_transitions_total{{'
                        f'actuator="{name}",dir="{d}"}} 0') in text
        for lvl in range(5):
            assert f'yacy_degraded_queries_total{{level="{lvl}"}}' in text
        assert "yacy_degrade_level 0" in text
        assert 'yacy_batcher_tuning{param="dispatchers"}' in text
    finally:
        sb.close()
