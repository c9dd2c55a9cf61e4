"""Game day (ISSUE 19): the workload-realism layer, the chaos
conductor's fault schedule, the verdict engine's joins, the straggler
conviction tracker (ROADMAP 1c read-only slice), the faultinject wire
schedule metadata, and the Performance_GameDay_p panel.

The verdict-engine tests feed SYNTHETIC evidence — the engine is pure
joins by contract, which is exactly what makes the incident→fault
attribution testable without a 3-process soak."""

import json
import os

import pytest

from yacy_search_server_tpu.utils import faultinject, tailattr
from yacy_search_server_tpu.utils.gameday import (
    SCHEDULABLE_FAULTS, ClientPool, Conductor, Phase, RateEnvelope,
    ScheduledFault, VerdictEngine, ZipfSampler, default_envelope,
    default_schedule)


@pytest.fixture(autouse=True)
def _clean():
    faultinject.clear()
    tailattr.reset()
    tailattr.set_enabled(True)
    yield
    faultinject.clear()
    tailattr.reset()


# -- workload realism --------------------------------------------------------

def test_zipf_sampler_is_seeded_and_head_heavy():
    a = ZipfSampler(["w0", "w1", "w2", "w3"], seed=7)
    b = ZipfSampler(["w0", "w1", "w2", "w3"], seed=7)
    draws_a = [a.sample() for _ in range(500)]
    assert draws_a == [b.sample() for _ in range(500)]
    counts = {w: draws_a.count(w) for w in set(draws_a)}
    # rank-0 dominates and the tail still appears (zipf, not constant)
    assert counts["w0"] == max(counts.values())
    assert counts["w0"] >= 2 * counts.get("w3", 0)
    assert len(counts) == 4


def test_rate_envelope_piecewise_phases():
    env = RateEnvelope([Phase(0.0, 2.0, "base"),
                        Phase(10.0, 5.0, "spike", servlet_qps=1.0),
                        Phase(20.0, 1.0, "tail")])
    assert env.at(0.0).name == "base"
    assert env.at(9.9).qps == 2.0
    assert env.at(10.0).name == "spike"
    assert env.at(15.0).servlet_qps == 1.0
    assert env.at(99.0).name == "tail"
    assert [p["name"] for p in env.to_json()] == ["base", "spike",
                                                  "tail"]


def test_client_pool_identities():
    pool = ClientPool(n=4, seed=3)
    assert pool.clients == ["203.0.113.1", "203.0.113.2",
                            "203.0.113.3", "203.0.113.4"]
    picks = {pool.pick() for _ in range(200)}
    assert picks <= set(pool.clients) and len(picks) > 1


# -- the fault schedule ------------------------------------------------------

def test_default_schedule_overlaps_and_registry():
    sched = default_schedule()
    # every scheduled point is a REAL faultpoint and every conductor-
    # schedulable fault has at least one window (no dead schedulable
    # faults — the satellite-5 hygiene gate)
    for f in sched:
        assert f.point in faultinject.REGISTERED_FAULTPOINTS, f.point
        assert f.t_clear > f.t_arm
    assert {f.point for f in sched} == set(SCHEDULABLE_FAULTS)
    cond = Conductor.__new__(Conductor)
    cond.schedule = sched
    overlaps = cond._overlaps()
    assert ["F1", "F2"] in overlaps and ["F2", "F3"] in overlaps


def test_default_schedule_scale_compresses():
    full = default_schedule()
    smoke = default_schedule(scale=0.2)
    for f_full, f_smoke in zip(full, smoke):
        assert f_smoke.t_arm == round(f_full.t_arm * 0.2, 1)
        assert f_smoke.t_clear < f_full.t_clear
    env = default_envelope(scale=0.2)
    assert env.at(0.0).qps > 0


# -- faultinject wire schedule metadata (satellite 1) ------------------------

def test_faultinject_schedule_records_arm_clear_expire():
    base = len(faultinject.schedule())
    faultinject.set_fault("mesh.step", 250)
    faultinject.set_fault("device.transfer_fail", 2)
    snap = faultinject.snapshot()
    assert snap["mesh.step"] == 250
    assert snap["device.transfer_fail"] == 2
    json.dumps(snap)                      # JSON-safe by contract
    faultinject.clear("mesh.step")
    assert faultinject.take("device.transfer_fail") is True  # 2 -> 1
    assert faultinject.take("device.transfer_fail") is True  # final;
    assert faultinject.take("device.transfer_fail") is False  # back
    events = faultinject.schedule()[base:]
    acts = [(e["action"], e["point"]) for e in events]
    assert ("arm", "mesh.step") in acts
    assert ("clear", "mesh.step") in acts
    # the self-disarm ("the device comes back") is a schedule event
    # even though no one called clear()
    assert ("expired", "device.transfer_fail") in acts
    # monotonic seq + pid on every event (the cross-process join keys)
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert all(e["pid"] == os.getpid() for e in events)
    assert faultinject.snapshot() == {}   # everything disarmed again
    n2 = faultinject.schedule(2)
    assert len(n2) == 2 and n2 == faultinject.schedule()[-2:]


# -- the verdict engine (synthetic evidence: pure joins) ---------------------

def _fault(point: str, member: int, armed: float,
           cleared: float) -> ScheduledFault:
    f = ScheduledFault("FX", point, member, 1, 0.0, 10.0,
                       scenario="unit")
    f.armed_ts, f.cleared_ts = armed, cleared
    return f


def _engine(f, **evidence):
    ev = {"queries": [], "probes": [], "tail_verdicts": [],
          "mesh_incidents": [], "health_incidents": [],
          "convictions": {}, "bit_identity": {"identical": True},
          "baseline_ms": {"mesh": 40.0, "servlet": 2.0}}
    ev.update(evidence)
    return VerdictEngine([f], ev)


def test_verdict_tail_attributes_right_member():
    f = _fault("mesh.step", 1, 100.0, 140.0)
    good = _engine(
        f,
        tail_verdicts=[{"ts": 110.0, "cause": "collective_straggler",
                        "member": "mesh1"}],
        probes=[{"ts": 120.0,
                 "causes": {"collective_straggler": 5, "compile": 1},
                 "scoreboard": [{"member": "mesh1",
                                 "slowest_frac": 0.9},
                                {"member": "mesh2",
                                 "slowest_frac": 0.1}]}])
    row = good.verdicts()[0]
    assert row["detected"] and row["attributed"], row
    # same evidence but the verdicts name the WRONG member: detected,
    # NOT attributed — the gate is right-label AND right-member
    bad = _engine(
        f,
        tail_verdicts=[{"ts": 110.0, "cause": "collective_straggler",
                        "member": "mesh2"}],
        probes=[{"ts": 120.0,
                 "causes": {"collective_straggler": 5},
                 "scoreboard": [{"member": "mesh1",
                                 "slowest_frac": 0.9}]}])
    row = bad.verdicts()[0]
    assert row["detected"] and not row["attributed"], row


def test_verdict_mesh_incident_needs_lost_and_recovered():
    f = _fault("device.transfer_fail", 2, 100.0, 200.0)
    incs = [{"name": "mesh_member_lost", "member": "mesh2",
             "cause": "lost", "ts": 120.0, "incident_seq": 1},
            {"name": "mesh_member_recovered", "member": "mesh2",
             "cause": "ok", "ts": 205.0, "incident_seq": 2}]
    row = _engine(f, mesh_incidents=incs).verdicts()[0]
    assert row["detected"] and row["attributed"], row
    # lost incident outside the window: not this fault's evidence
    row = _engine(f, mesh_incidents=[
        dict(incs[0], ts=500.0)]).verdicts()[0]
    assert not row["detected"], row
    # no recovery edge: detected but not attributed (the contract is
    # the ROUND TRIP — the recorder must see the member come back)
    row = _engine(f, mesh_incidents=incs[:1]).verdicts()[0]
    assert row["detected"] and not row["attributed"], row


def test_verdict_slo_incident_joins_armed_snapshot():
    f = _fault("servlet.serving", 0, 100.0, 160.0)
    inc = {"name": "incident", "ts": 130.0, "seq": 3,
           "rules": ["slo_serving_p95"],
           "armed_faults": {"servlet.serving": 300}}
    row = _engine(f, health_incidents=[inc]).verdicts()[0]
    assert row["detected"] and row["attributed"], row
    # an SLO incident with an EMPTY armed snapshot cannot name the
    # injected cause: detected, not attributed
    row = _engine(f, health_incidents=[
        dict(inc, armed_faults={})]).verdicts()[0]
    assert row["detected"] and not row["attributed"], row
    # a non-SLO incident in the window proves nothing for this fault
    row = _engine(f, health_incidents=[
        dict(inc, rules=["heap_pressure"])]).verdicts()[0]
    assert not row["detected"], row


def test_verdict_answered_counts_degraded_never_500():
    f = _fault("mesh.step", 1, 100.0, 140.0)
    qs = [{"ts": 110.0, "kind": "mesh", "status": 200, "dur_ms": 50},
          {"ts": 115.0, "kind": "mesh", "status": 429, "dur_ms": 1},
          {"ts": 150.0, "kind": "mesh", "status": 500, "dur_ms": 1}]
    row = _engine(f, queries=qs).verdicts()[0]
    # the 500 lands OUTSIDE the window; inside it: 1x200 + 1x429 = 100%
    assert row["answered"], row
    assert row["answered_detail"] == {"in_window": 2, "ok_200": 1,
                                      "degraded_429": 1, "errors": 0}
    row = _engine(f, queries=[
        dict(qs[2], ts=120.0)]).verdicts()[0]
    assert not row["answered"], row


def test_verdict_recovery_bounded_after_clear():
    f = _fault("mesh.step", 1, 100.0, 140.0)
    fast = [{"ts": 141.0 + i, "kind": "mesh", "status": 200,
             "dur_ms": 45.0} for i in range(4)]
    row = _engine(f, queries=fast).verdicts()[0]
    assert row["slo_recovery"], row
    assert row["recovery"]["recovered_s"] == pytest.approx(1.0)
    # walls stay over the bound until past the recovery deadline
    slow = [{"ts": 141.0 + 70 * i, "kind": "mesh", "status": 200,
             "dur_ms": 400.0} for i in range(4)]
    row = _engine(f, queries=slow).verdicts()[0]
    assert not row["slo_recovery"], row


def test_verdict_row_is_complete_and_fails_closed():
    """Every row carries every gate + the verdict; with NO evidence at
    all the row fails (detection is proven, never presumed)."""
    f = _fault("servlet.serving", 0, 100.0, 160.0)
    row = _engine(f, bit_identity={"identical": False}).verdicts()[0]
    for key in ("detected", "attributed", "answered", "slo_recovery",
                "bit_identical", "verdict", "evidence", "recovery",
                "answered_detail", "scenario", "target"):
        assert key in row
    assert row["verdict"].startswith("fail:")
    assert "detected" in row["verdict"]
    assert "bit_identical" in row["verdict"]


# -- straggler convictions (ROADMAP 1c read-only slice) ----------------------

def _complete_step(seq: int, late_member: int, late_ms: float,
                   members=(0, 1, 2)) -> None:
    tailattr.MESH.note_step(seq, f"t{seq:031d}", members, "collective")
    for m in members:
        late = late_ms if m == late_member else 1.0
        tailattr.MESH.add_segment({
            "seq": seq, "m": m, "q_ms": late / 2, "entry_ms": late / 2,
            "exec_ms": 5.0, "commit_ms": 0.0, "mode": "collective"})


def test_conviction_needs_consecutive_windows():
    conv = tailattr.ConvictionTracker()
    now = 1_000_000.0
    for seq in range(4):
        _complete_step(seq, late_member=1, late_ms=120.0)
    # first guilty window: streak 1, NO conviction (one slow window —
    # a GC pause — never convicts)
    assert conv.observe(now) == []
    assert conv.conviction_totals() == {"mesh0": 0, "mesh1": 0,
                                        "mesh2": 0}
    for seq in range(4, 8):
        _complete_step(seq, late_member=1, late_ms=120.0)
    crumbs = conv.observe(now + conv.window_s + 1)
    assert len(crumbs) == 1
    crumb = crumbs[0]
    assert crumb["member"] == "mesh1"
    assert crumb["windows"] == conv.windows_needed
    assert crumb["conviction_total"] == 1
    assert crumb["slowest_frac"] >= 0.6
    # zero-filled totals over every member the timeline scattered to
    assert conv.conviction_totals() == {"mesh0": 0, "mesh1": 1,
                                        "mesh2": 0}
    assert conv.recent() == [crumb]
    # edge-triggered: a THIRD guilty window extends the streak but does
    # not re-convict
    for seq in range(8, 12):
        _complete_step(seq, late_member=1, late_ms=120.0)
    assert conv.observe(now + 2 * (conv.window_s + 1)) == []
    assert conv.conviction_totals()["mesh1"] == 1


def test_conviction_streak_breaks_on_clean_window():
    conv = tailattr.ConvictionTracker()
    now = 1_000_000.0
    for seq in range(4):
        _complete_step(seq, late_member=1, late_ms=120.0)
    assert conv.observe(now) == []
    # the fault clears: the next window is clean, the streak re-arms
    tailattr.MESH.reset()
    for seq in range(4, 8):
        _complete_step(seq, late_member=1, late_ms=2.0)  # sub-margin
    assert conv.observe(now + conv.window_s + 1) == []
    assert conv._streaks == {}
    assert conv.conviction_totals().get("mesh1", 0) == 0


def test_conviction_ticks_faster_than_windows_eval_once():
    conv = tailattr.ConvictionTracker()
    now = 1_000_000.0
    for seq in range(4):
        _complete_step(seq, late_member=1, late_ms=120.0)
    assert conv.observe(now) == []
    streak = dict(conv._streaks)
    # health ticks every ~5s; only one eval per window may advance the
    # streak, or a 40s fault would convict off a single window
    for dt in (1.0, 5.0, 10.0, conv.window_s - 1.0):
        conv.observe(now + dt)
    assert conv._streaks == streak


def test_conviction_singleton_in_metrics_exposition(tmp_path):
    """The zero-filled yacy_mesh_straggler_convictions_total family
    rides the monitoring servlet (satellite 2's metric surface)."""
    from yacy_search_server_tpu.server.servlets.monitoring import \
        prometheus_text
    from yacy_search_server_tpu.switchboard import Switchboard

    for seq in range(4):
        _complete_step(seq, late_member=2, late_ms=150.0)
    tailattr.CONVICTIONS.observe(1_000_000.0)
    for seq in range(4, 8):
        _complete_step(seq, late_member=2, late_ms=150.0)
    tailattr.CONVICTIONS.observe(
        1_000_000.0 + tailattr.CONVICTIONS.window_s + 1)
    sb = Switchboard(data_dir=str(tmp_path / "DATA"))
    try:
        text = prometheus_text(sb, include_buckets=False)
    finally:
        sb.close()
    assert 'yacy_mesh_straggler_convictions_total{member="mesh2"} 1' \
        in text
    # innocents are zero-filled, not absent
    assert 'yacy_mesh_straggler_convictions_total{member="mesh0"} 0' \
        in text


# -- the servlet -------------------------------------------------------------

@pytest.mark.parametrize("source", ("none", "live"))
def test_gameday_servlet_renders_artifact(source, monkeypatch):
    """With no drill in this process the panel says so (no rows); a
    `gameday.LAST_RUN` renders one row per scheduled fault with the
    verdict engine's gates."""
    from yacy_search_server_tpu.server import servlets
    from yacy_search_server_tpu.server.objects import ServerObjects
    from yacy_search_server_tpu.utils import gameday

    last_run = None
    if source == "live":
        f = _fault("device.transfer_fail", 2, 100.0, 200.0)
        rows = _engine(f, mesh_incidents=[
            {"name": "mesh_member_lost", "member": "mesh2",
             "cause": "lost", "ts": 120.0, "incident_seq": 1},
            {"name": "mesh_member_recovered", "member": "mesh2",
             "cause": "ok", "ts": 205.0, "incident_seq": 2}]).verdicts()
        last_run = {
            "schedule": rows, "overlaps": [["F1", "F2"]],
            "verdict_summary": {"faults": len(rows), "passed": 0,
                                "all_pass": False, "never_500": True,
                                "unattributed_verdicts": 0},
            "workload": {"queries_total": 7, "duration_s": 3}}
    monkeypatch.setattr(gameday, "LAST_RUN", last_run)
    fn = servlets.lookup("Performance_GameDay_p")
    assert fn is not None
    view = json.loads(fn({}, ServerObjects({"format": "json"}),
                         None).raw_body)
    assert view["source"] == source
    prop = fn({}, ServerObjects(), None)
    assert prop.get_int("rows") == len(view["schedule"])
    assert prop.get("source") == source
    if source == "none":
        assert prop.get_int("rows") == 0 and prop.get_int("faults") == 0
        assert prop.get("note") == "no drill has run in this process"
        return
    assert prop.get("note") == ""
    assert prop.get_int("rows") == 1 and prop.get_int("faults") == 1
    assert prop.get("rows_0_point") == "device.transfer_fail"
    assert prop.get("rows_0_target") == "mesh2"
    assert prop.get_int("rows_0_detected") == 1
    assert prop.get_int("rows_0_attributed") == 1
    assert prop.get("rows_0_verdict") == rows[0]["verdict"]
    assert prop.get_int("overlaps") == 1
    assert prop.get("overlaps_0_pair") == "F1+F2"
    assert prop.get_int("queries_total") == 7
    assert prop.get_int("never_500") == 1
