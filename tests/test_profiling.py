"""Whitebox straggler forensics (ISSUE 20): the always-on sampling
profiler (role-tagged folded stacks), the lock-wait observatory
(ObservedLock/ObservedRLock into the canonical lock.wait.*/lock.hold.*
families with over-p95 holder-stack capture), the do_profsnap wire
endpoint + Protocol.fetch_profile, and the conviction-edge auto-fetch
that embeds the convicted member's own profile in the incident.

The sampler tests drive NAMED dummy threads so the role tagging is
pinned against the real pool-name prefixes, not synthetic roles."""

import threading
import time
import types

import pytest

from yacy_search_server_tpu.utils import histogram, profiling, tailattr

REQUIRED_SNAPSHOT_KEYS = {"ts", "pid", "samples_total", "window_s",
                          "stacks", "roles", "locks"}


@pytest.fixture(autouse=True)
def _fresh():
    profiling.set_enabled(True)
    profiling.reset()
    tailattr.reset()
    tailattr.set_enabled(True)
    yield
    profiling.set_enabled(True)
    profiling.reset()
    tailattr.reset()


def _spin_until(stop: threading.Event) -> None:
    while not stop.is_set():
        sum(range(200))


def _run_named_threads(names, duration_s: float = 0.6) -> None:
    stop = threading.Event()
    ts = [threading.Thread(target=_spin_until, args=(stop,), name=n,
                           daemon=True) for n in names]
    for t in ts:
        t.start()
    time.sleep(duration_s)
    stop.set()
    for t in ts:
        t.join()


# -- role tagging ------------------------------------------------------------

def test_thread_role_prefixes_cover_the_real_pools():
    cases = {
        "devstore-batcher-0": "dispatcher",
        "meshstore-batcher-1": "dispatcher",
        "devstore-completer-0": "completer",
        "meshstore-completer-2": "completer",
        "devstore-former": "flusher",
        "devstore-rebuild": "flusher",
        "mesh-runloop-1": "member-runloop",
        "15_health": "health-tick",
        "federated-search-3": "search-feeder",
        "prof-sampler": "sampler",
        "MainThread": "other",
        "ThreadPoolExecutor-0_0": "other",
    }
    for name, want in cases.items():
        assert profiling.thread_role(name) == want, name
    # every pattern's role is a wire-contract member of ROLES
    for _pat, role in profiling._ROLE_PATTERNS:
        assert role in profiling.ROLES


def test_sampler_covers_roles_of_named_pool_threads():
    s = profiling.ensure_sampler()
    old = s.base_hz
    s.base_hz = 200.0
    try:
        _run_named_threads(["devstore-batcher-0", "mesh-runloop-1",
                            "devstore-former"])
    finally:
        s.base_hz = old
    roles = s.role_samples()
    # zero-filled over the full wire contract
    assert set(roles) == set(profiling.ROLES)
    for role in ("dispatcher", "member-runloop", "flusher"):
        assert roles[role] > 0, (role, roles)
    assert profiling.stats()["samples_total"] > 0
    # the folded stacks name the spinning site with the leaf line
    stacks = s.stacks(50)
    mine = [r for r in stacks if "_spin_until" in r["stack"]]
    assert mine, stacks[:5]
    assert any(":_spin_until:" in r["stack"].rsplit(";", 1)[-1] + ";"
               or "_spin_until:" in r["stack"].rsplit(";", 1)[-1]
               for r in mine)


def test_snapshot_and_report_are_wire_shaped():
    s = profiling.ensure_sampler()
    old = s.base_hz
    s.base_hz = 200.0
    try:
        _run_named_threads(["devstore-batcher-9"], duration_s=0.3)
    finally:
        s.base_hz = old
    snap = profiling.snapshot(top_n=5)
    assert REQUIRED_SNAPSHOT_KEYS <= set(snap)
    assert len(snap["stacks"]) <= 5
    assert set(snap["roles"]) == set(profiling.ROLES)
    rep = profiling.report()
    assert {"stacks", "locks", "last_capture"} <= set(rep)
    # compact digest index round-trips through decode_role
    idx = profiling.top_role_index()
    assert profiling.decode_role(idx) in profiling.ROLES
    assert profiling.decode_role(999) == "other"
    assert profiling.decode_role(None) == "other"


def test_triggered_capture_burst_window():
    s = profiling.ensure_sampler()
    s.reset()
    assert profiling.trigger("tail.lock_wait") is True
    # re-trigger while armed is coalesced, not stacked
    assert profiling.trigger("tail.queue_wait") is False
    stop = threading.Event()
    t = threading.Thread(target=_spin_until, args=(stop,),
                         name="devstore-batcher-5", daemon=True)
    t.start()
    deadline = time.time() + s.CAPTURE_S + 3.0
    while s.last_capture is None and time.time() < deadline:
        time.sleep(0.05)
    stop.set()
    t.join()
    assert s.last_capture is not None, "capture window never finalized"
    assert s.last_capture["reason"] == "tail.lock_wait"
    assert s.last_capture["samples"] > 0
    assert profiling.stats()["capture_windows_total"] >= 1


# -- the lock-wait observatory -----------------------------------------------

def test_observed_lock_records_wait_and_hold_families():
    lk = profiling.ObservedLock("devstore")
    hw0 = histogram.get("lock.wait.devstore")
    before_w = sum(hw0.windowed_counts()) if hw0 is not None else 0
    with lk:
        time.sleep(0.002)
    # a non-trivial hold records; the uncontended ~0.3us wait is below
    # the RECORD_MIN_MS floor and must NOT have recorded
    hh = histogram.get("lock.hold.devstore")
    assert hh is not None and sum(hh.windowed_counts()) >= 1
    hw = histogram.get("lock.wait.devstore")
    after_w = sum(hw.windowed_counts()) if hw is not None else 0
    assert after_w == before_w, "sub-floor wait polluted the family"
    # a CONTENDED acquire records its wait
    held = threading.Event()
    release = threading.Event()

    def holder():
        with lk:
            held.set()
            release.wait(2.0)

    t = threading.Thread(target=holder, daemon=True)
    t.start()
    held.wait(2.0)
    threading.Timer(0.01, release.set).start()
    with lk:
        pass
    t.join()
    hw = histogram.get("lock.wait.devstore")
    assert hw is not None
    assert sum(hw.windowed_counts()) == before_w + 1
    row = [r for r in profiling.lock_table() if r["name"] == "devstore"]
    assert row and row[0]["hold"]["count"] >= 1
    assert row[0]["wait"]["count"] >= 1
    # canonical families render under the yacy_ prefix
    assert histogram.prom_name("lock.wait.devstore") == \
        "yacy_lock_wait_devstore_ms"


def test_holder_stack_captured_over_threshold():
    # the capture gate is the family's cached p95: holds that other tests
    # of this worker recorded on the same lock name must not raise it
    histogram.reset()
    lk = profiling.ObservedLock("dense_fwd")
    lk.holder_stacks.clear()

    def hold_long():
        with lk:
            time.sleep((profiling.HOLDER_MIN_MS + 4.0) / 1000.0)

    hold_long()
    assert lk.holder_stacks, "over-threshold hold captured no stack"
    cap = lk.holder_stacks[-1]
    assert cap["hold_ms"] >= profiling.HOLDER_MIN_MS
    assert "hold_long" in cap["stack"]


def test_contended_acquire_emits_the_tail_marker_span():
    """Satellite 2 parity: the ObservedLock measurement point IS the
    tail classifier's lock-wait evidence — one contended acquire under
    an active trace yields exactly one tail.lock_wait marker span
    carrying the lock name (what devstore's hand-rolled timing used to
    emit is now emitted here, once)."""
    from yacy_search_server_tpu.utils import tracing
    tracing.set_enabled(True)
    tracing.clear()
    lk = profiling.ObservedLock("devstore")
    held = threading.Event()
    release = threading.Event()

    def holder():
        with lk:
            held.set()
            release.wait(2.0)

    t = threading.Thread(target=holder, daemon=True)
    t.start()
    held.wait(2.0)

    with tracing.trace("contended") as r:
        tid = r.ctx[0]
        # contend for longer than the marker threshold
        threading.Timer(
            (tailattr.LOCK_WAIT_MIN_MS + 20.0) / 1000.0,
            release.set).start()
        with lk:
            pass
    t.join()
    rec = tracing.get_trace(tid)
    assert rec is not None
    spans = [s for s in rec.spans
             if s.name == tailattr.MARKER_LOCK_WAIT]
    assert len(spans) == 1, [s.name for s in rec.spans]
    assert spans[0].attrs.get("lock") == "devstore"
    assert spans[0].dur_ms >= tailattr.LOCK_WAIT_MIN_MS


def test_observed_rlock_reentrant_and_condition_protocol():
    lk = profiling.ObservedRLock("rwi")
    with lk:
        with lk:           # reentrant: no deadlock, depth tracked
            assert lk._depth == 2
        assert lk._depth == 1
    assert lk._depth == 0

    cond = threading.Condition(lk)
    got = []

    def waiter():
        with cond:
            got.append(cond.wait(timeout=3.0))

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    time.sleep(0.05)
    with cond:
        cond.notify()
    t.join(3.0)
    assert got == [True], "Condition(ObservedRLock) wait/notify broke"
    assert lk._depth == 0


def test_disabled_mode_records_nothing():
    profiling.set_enabled(False)
    lk = profiling.ObservedLock("search_cache")
    h = histogram.get("lock.wait.search_cache")
    before = sum(h.windowed_counts()) if h is not None else 0
    s_before = profiling.stats()["samples_total"]
    for _ in range(50):
        with lk:
            pass
    time.sleep(0.15)
    h = histogram.get("lock.wait.search_cache")
    after = sum(h.windowed_counts()) if h is not None else 0
    assert after == before, "disabled observatory still recorded"
    assert lk.contended_total == 0
    assert profiling.stats()["samples_total"] == s_before, \
        "disabled sampler still folded stacks"
    assert profiling.trigger("tail.lock_wait") is False


def test_canonical_families_mirror_the_hot_lock_census():
    """Every census lock name owns BOTH canonical families (hygiene:
    adding a census entry without its histograms would silently skip
    /metrics zero-fill and the lock table quantiles)."""
    for name in sorted(set(profiling.HOT_LOCK_CENSUS.values())):
        assert f"lock.wait.{name}" in histogram.CANONICAL, name
        assert f"lock.hold.{name}" in histogram.CANONICAL, name
    # every census key parses as file::Class::attr and names a real file
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for key in profiling.HOT_LOCK_CENSUS:
        rel, cls, attr = key.split("::")
        assert os.path.exists(os.path.join(repo, rel)), key
        assert cls and attr.startswith("_"), key


# -- conviction hook (the edge-triggered auto-fetch seam) --------------------

def _convict(conv, member=1):
    for seq in range(4):
        tailattr.MESH.note_step(seq, f"t{seq:031d}", (0, 1, 2),
                                "collective")
        for m in (0, 1, 2):
            late = 120.0 if m == member else 1.0
            tailattr.MESH.add_segment({
                "seq": seq, "m": m, "q_ms": late / 2,
                "entry_ms": late / 2, "exec_ms": 5.0,
                "commit_ms": 0.0, "mode": "collective"})
    now = 1_000_000.0
    assert conv.observe(now) == []
    for seq in range(4, 8):
        tailattr.MESH.note_step(seq, f"t{seq:031d}", (0, 1, 2),
                                "collective")
        for m in (0, 1, 2):
            late = 120.0 if m == member else 1.0
            tailattr.MESH.add_segment({
                "seq": seq, "m": m, "q_ms": late / 2,
                "entry_ms": late / 2, "exec_ms": 5.0,
                "commit_ms": 0.0, "mode": "collective"})
    return conv.observe(now + conv.window_s + 1)


def test_conviction_hook_fires_once_per_edge_and_mutates_crumb():
    conv = tailattr.ConvictionTracker()
    seen = []

    def hook(crumb):
        seen.append(crumb["member"])
        crumb["profile"] = {"stacks": [], "marker": "attached"}

    conv.set_conviction_hook(hook)
    crumbs = _convict(conv, member=1)
    assert len(crumbs) == 1 and seen == ["mesh1"]
    # the hook's mutation is visible to whoever embeds the crumb
    assert conv.recent()[0]["profile"]["marker"] == "attached"


def test_conviction_hook_exceptions_are_swallowed():
    conv = tailattr.ConvictionTracker()

    def hook(_crumb):
        raise RuntimeError("boom")

    conv.set_conviction_hook(hook)
    crumbs = _convict(conv, member=2)
    assert len(crumbs) == 1, "hook failure must not eat the conviction"
    conv.reset()
    assert conv._on_convicted is None


# -- the wire (do_profsnap + fetch_profile + coordinator auto-fetch) ---------

@pytest.fixture
def duo(tmp_path):
    from yacy_search_server_tpu.peers.node import P2PNode
    from yacy_search_server_tpu.peers.transport import LoopbackNetwork
    net = LoopbackNetwork()
    nodes = []
    for name in ("prof-origin", "prof-remote"):
        n = P2PNode(name, net, data_dir=str(tmp_path / name),
                    partition_exponent=1, redundancy=1)
        nodes.append(n)
    for n in nodes:
        n.bootstrap([m.seed for m in nodes if m is not n])
        n.ping()
    yield nodes
    for n in nodes:
        n.close()


def test_profsnap_roundtrip_over_loopback(duo):
    a, b = duo
    ok, rep = a.protocol.fetch_profile(b.seed)
    assert ok, rep
    assert rep["peer"] == b.seed.hash.decode("ascii")
    prof = rep["profile"]
    assert REQUIRED_SNAPSHOT_KEYS <= set(prof)
    assert set(prof["roles"]) == set(profiling.ROLES)
    # n clamps: never more than 32 stacks regardless of the ask
    ok, rep = a.protocol.fetch_profile(b.seed, n=10_000)
    assert ok and len(rep["profile"]["stacks"]) <= 32


def test_profsnap_over_real_http(tmp_path):
    from yacy_search_server_tpu.peers.node import P2PNode
    from yacy_search_server_tpu.peers.transport import HttpTransport
    nodes = []
    for name in ("profhttp-a", "profhttp-b"):
        n = P2PNode(name, HttpTransport(timeout_s=10.0),
                    data_dir=str(tmp_path / name),
                    partition_exponent=1, redundancy=1)
        n.serve_http()
        nodes.append(n)
    a, b = nodes
    try:
        a.bootstrap([b.seed])
        b.bootstrap([a.seed])
        a.ping()
        ok, rep = a.protocol.fetch_profile(b.seed, n=4)
        assert ok, rep
        assert isinstance(rep["profile"]["pid"], int)
        assert len(rep["profile"]["stacks"]) <= 4
    finally:
        for n in nodes:
            n.close()


def test_conviction_edge_auto_fetches_remote_profile(duo):
    """The coordinator seam end-to-end WITHOUT a 3-process mesh: drive
    MeshMember._on_convicted against a loopback peer — the convicted
    member's profile must arrive over the wire and land both in the
    crumb (what health embeds) and in the dedicated incident."""
    from yacy_search_server_tpu.parallel.distributed import MeshMember
    a, b = duo
    fake = types.SimpleNamespace(
        process_id=0, peers={1: b.seed}, node=a,
        _plock=threading.Lock(), _incident_seq=0, incidents=[],
        _data_dir=None)
    crumb = {"member": "mesh1", "windows": 2, "slowest_frac": 1.0}
    MeshMember._on_convicted(fake, crumb)
    assert "profile" in crumb, "remote profile not attached"
    assert REQUIRED_SNAPSHOT_KEYS <= set(crumb["profile"])
    assert len(fake.incidents) == 1
    inc = fake.incidents[0]
    assert inc["name"] == "straggler_convicted"
    assert inc["member_id"] == 1
    assert inc["crumb"]["profile"] is crumb["profile"]

    # self-conviction reads the local snapshot, no wire call
    crumb0 = {"member": "mesh0"}
    MeshMember._on_convicted(fake, crumb0)
    assert "profile" in crumb0
    # unknown member: incident still recorded, profile absent
    crumbx = {"member": "mesh7"}
    MeshMember._on_convicted(fake, crumbx)
    assert "profile" not in crumbx
    assert len(fake.incidents) == 3


def test_prof_metrics_and_servlet(tmp_path):
    from yacy_search_server_tpu.server.servlets.monitoring import (
        prometheus_text, respond_prof)
    from yacy_search_server_tpu.server.objects import ServerObjects
    from yacy_search_server_tpu.switchboard import Switchboard
    sb = Switchboard(data_dir=str(tmp_path / "DATA"))
    try:
        text = prometheus_text(sb, include_buckets=False)
        assert "yacy_prof_samples_total" in text
        assert "yacy_prof_sampler_hz" in text
        for role in profiling.ROLES:
            assert f'yacy_prof_role_samples_total{{role="{role}"}}' \
                in text, role
        view = respond_prof({"ext": "json"},
                            ServerObjects({"format": "json"}), sb)
        import json as _json
        snap = _json.loads(view.raw_body)
        assert REQUIRED_SNAPSHOT_KEYS <= set(snap)
        png = respond_prof({"ext": "png"},
                           ServerObjects({"format": "png"}), sb)
        assert png.raw_body[:8] == b"\x89PNG\r\n\x1a\n"
        prop = respond_prof({}, ServerObjects(), sb)
        assert prop.get_int("locks", -1) >= 0
    finally:
        sb.close()
