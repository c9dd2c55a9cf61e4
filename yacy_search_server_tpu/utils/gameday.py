"""Game day — the workload-realistic chaos conductor + incident→fault
attribution verdict engine (ISSUE 19, ROADMAP item 2).

The defense stack is proven piecewise (actuators, crash/device-loss
recovery, one-member-down survival, merge deferral) and M89 gave every
slow query exactly one attributed cause — but nothing yet proved the
observability stack *explains the right thing* when faults OVERLAP
under realistic load.  This module closes that loop with three layers:

- **Workload realism** — :class:`ZipfSampler` term popularity (a few
  head terms dominate, the tail is long), :class:`RateEnvelope`
  burst/diurnal phases (base load, a traffic spike, a quiet tail), and
  :class:`ClientPool` per-client identity shipped as X-Forwarded-For
  from the loopback generator — so the access tracker and the
  admission token buckets key on real client identities and actually
  engage (a denied client sees a counted 429 + Retry-After, never an
  error).
- **The chaos conductor** — :class:`Conductor` drives a scheduled set
  of OVERLAPPING :class:`ScheduledFault` windows against a live
  :class:`~..parallel.launcher.MeshFleet`, arming and clearing each
  fault cross-process through the ``do_meshfault`` wire (the same
  faultinject registry every robustness test uses; the member's own
  timestamped schedule — ``do_meshfault?list=1`` — is the shared
  source of truth).  While faults run, the conductor keeps issuing the
  zipfian workload, drives the coordinator's health engine, and
  snapshots the tail/scoreboard/conviction surfaces.
- **The verdict engine** — :class:`VerdictEngine` joins the
  machine-readable fault schedule against the flight-recorder incident
  stream (mesh member incidents + health incidents, both carrying
  ``incident_seq`` and the armed-fault snapshot), the
  ``yacy_tail_cause_total`` verdict stream and the straggler
  scoreboard, and renders one verdict row per scheduled fault:
  detected?  attributed to the RIGHT cause label and member?  bounded
  SLO recovery after clear?  100% answered during the fault (degraded
  + counted, never 500)?  bit-identical rankings after full recovery
  (the arxiv 1807.05798 tie discipline: the recovered fleet must rank
  EXACTLY as before)?

Scenario canon (:data:`SCHEDULABLE_FAULTS` / :func:`default_schedule`):
every conductor-schedulable fault has a detection contract — how its
incident must name it — and at least one scheduled window in the
default game day (the no-dead-schedulable-faults gate in
tests/test_gameday.py):

- ``mesh.step`` straggle during the traffic spike → dominant
  ``collective_straggler`` verdicts + the scoreboard (and a
  conviction) naming the slowed member, embedded in the SLO incident.
- ``device.transfer_fail`` (device loss) overlapping both neighbours →
  the coordinator's ``mesh_member_lost`` / ``mesh_member_recovered``
  incidents naming the member; queries degrade to the committed host
  answer, bit-identical, 100% answered.
- ``servlet.serving`` latency on the coordinator's regular dispatch →
  the ``slo_serving_p95`` incident whose armed-fault snapshot names
  the injected point.  (A fourth candidate — span corruption under a
  deferred merge — is not wire-schedulable against the frozen
  in-memory mesh corpus: there is no durable read path a remote arm
  could corrupt, so it stays with the crash-consistency harness.)

Jax-free by contract (the conductor talks HTTP to the fleet; the
verdict engine is pure joins), so a drill runner and the
``Performance_GameDay_p`` servlet can import this from any process.
Nothing in the tree runs the :class:`Conductor` over a live fleet
today (ROADMAP R6 carries the drill's gate list); tests/test_gameday.py
holds the schedule, the bookkeeping and every verdict join.
"""

from __future__ import annotations

import bisect
import random
import time
from dataclasses import dataclass, field

# the last completed run's result in THIS process (what the
# Performance_GameDay_p servlet serves)
LAST_RUN: dict | None = None

# every fault the conductor may schedule, with its detection contract —
# the verdict engine dispatches on `detect`, and the
# no-dead-schedulable-faults gate requires each point to carry at least
# one scheduled window in default_schedule()
SCHEDULABLE_FAULTS = {
    "mesh.step": {
        "detect": "tail",
        "expect_cause": "collective_straggler",
        "contract": "dominant collective_straggler verdicts + "
                    "scoreboard/conviction naming the slowed member",
    },
    "device.transfer_fail": {
        "detect": "mesh_incident",
        "expect_cause": "lost",
        "contract": "coordinator mesh_member_lost/_recovered incidents "
                    "naming the member; host-mode degraded answers",
    },
    "servlet.serving": {
        "detect": "slo_incident",
        "expect_cause": "servlet.serving",
        "contract": "slo_serving_p95 incident whose armed-fault "
                    "snapshot names the injected point",
    },
}


# -- workload realism --------------------------------------------------------

class ZipfSampler:
    """Seeded zipfian sampler over a fixed item list: weight of the
    rank-i item is 1/(i+1)^s — a few head terms dominate, the tail is
    long (the shape of real query logs)."""

    def __init__(self, items, s: float = 1.1, seed: int = 7):
        assert items, "zipf needs at least one item"
        self.items = list(items)
        self.s = float(s)
        self._rng = random.Random(seed)
        weights = [1.0 / (i + 1) ** self.s
                   for i in range(len(self.items))]
        total = sum(weights)
        self._cdf, acc = [], 0.0
        for w in weights:
            acc += w / total
            self._cdf.append(acc)
        self._cdf[-1] = 1.0

    def sample(self):
        return self.items[bisect.bisect_left(self._cdf,
                                             self._rng.random())]

    def weight(self, i: int) -> float:
        prev = self._cdf[i - 1] if i > 0 else 0.0
        return self._cdf[i] - prev


@dataclass
class Phase:
    """One piecewise-constant stretch of the rate envelope."""

    t: float                   # phase start, seconds from workload t0
    qps: float                 # mesh-query target rate
    name: str = "base"
    servlet_qps: float = 0.0   # regular-servlet GET side-load


class RateEnvelope:
    """Burst/diurnal rate envelope: piecewise-constant phases (base
    load → spike → quiet tail), queried by relative time."""

    def __init__(self, phases: list[Phase]):
        assert phases and phases[0].t <= 0.0, \
            "the envelope must cover t=0"
        self.phases = sorted(phases, key=lambda p: p.t)

    def at(self, t: float) -> Phase:
        cur = self.phases[0]
        for p in self.phases:
            if p.t <= t:
                cur = p
            else:
                break
        return cur

    def to_json(self) -> list[dict]:
        return [{"t": p.t, "name": p.name, "qps": p.qps,
                 "servlet_qps": p.servlet_qps} for p in self.phases]


class ClientPool:
    """Synthetic per-client identities (TEST-NET-3 addresses) with
    zipfian popularity: the hot client is what drains its token bucket
    while the tail clients stay admitted — per-client admission is the
    thing this exercises."""

    def __init__(self, n: int = 8, s: float = 1.1, seed: int = 11):
        self.clients = [f"203.0.113.{i + 1}" for i in range(n)]
        self._zipf = ZipfSampler(self.clients, s=s, seed=seed)

    def pick(self) -> str:
        return self._zipf.sample()


# -- the fault schedule ------------------------------------------------------

@dataclass
class ScheduledFault:
    """One fault window the conductor will arm and clear, plus the
    runtime bookkeeping the verdict engine joins on."""

    fault_id: str            # F1, F2, ... (stable row key)
    point: str               # faultinject registry name
    member: int              # target mesh process
    value: object            # armed value (ms, count, ...)
    t_arm: float             # planned, seconds from workload t0
    t_clear: float
    scenario: str = ""       # human-readable what/why
    # filled by the conductor:
    armed_ts: float = 0.0    # absolute wall time of the arm ack
    cleared_ts: float = 0.0
    arm_ack: dict = field(default_factory=dict)
    clear_ack: dict = field(default_factory=dict)

    def detect(self) -> str:
        return SCHEDULABLE_FAULTS[self.point]["detect"]

    def row(self) -> dict:
        return {"fault_id": self.fault_id, "point": self.point,
                "member": self.member, "target": f"mesh{self.member}",
                "value": self.value if isinstance(
                    self.value, (int, float, str)) else str(self.value),
                "t_arm": self.t_arm, "t_clear": self.t_clear,
                "armed_ts": round(self.armed_ts, 3),
                "cleared_ts": round(self.cleared_ts, 3),
                "scenario": self.scenario,
                "detect": self.detect(),
                "expect_cause":
                    SCHEDULABLE_FAULTS[self.point]["expect_cause"],
                "arm_ack": self.arm_ack, "clear_ack": self.clear_ack}


def default_schedule(straggle_ms: float = 250.0,
                     servlet_ms: float = 300.0,
                     scale: float = 1.0) -> list[ScheduledFault]:
    """The default game day: three overlapping fault windows (F2
    overlaps both F1 and F3).  `scale` compresses the timeline for
    smoke runs."""
    def t(x):
        return round(x * scale, 1)
    return [
        ScheduledFault(
            "F1", "mesh.step", 1, straggle_ms, t(10), t(48),
            scenario="straggling mesh member during the traffic "
                     "spike (zipf head terms, burst envelope)"),
        ScheduledFault(
            "F2", "device.transfer_fail", 2, 100000, t(35), t(140),
            scenario="device loss in one member while the straggle "
                     "is still live, held across the servlet fault "
                     "(overlaps F1 and F3)"),
        ScheduledFault(
            "F3", "servlet.serving", 0, servlet_ms, t(130), t(170),
            scenario="coordinator servlet-dispatch latency under "
                     "regular-servlet side-load while the fleet is "
                     "still in degraded host mode"),
    ]


def default_envelope(scale: float = 1.0) -> RateEnvelope:
    """Base load → spike (over F1) → sustained base with a regular-
    servlet side-load bracketing F3 → quiet tail for recovery
    evidence."""
    def t(x):
        return round(x * scale, 1)
    return RateEnvelope([
        Phase(0.0, 2.5, "base"),
        Phase(t(8), 5.0, "spike"),
        Phase(t(50), 2.5, "base"),
        Phase(t(100), 2.0, "servlet-burst", servlet_qps=2.0),
        Phase(t(180), 1.5, "recovery-tail"),
    ])


# -- the verdict engine ------------------------------------------------------

def _dominant(causes: dict) -> str:
    if not causes:
        return ""
    best = max(causes, key=lambda c: causes[c])
    return best if causes[best] > 0 else ""


class VerdictEngine:
    """Pure joins: the fault schedule × the incident streams × the
    tail-cause/scoreboard windows × the query log → one verdict row
    per scheduled fault.  No wall-clock ordering assumptions across
    processes: incidents are matched by window + (pid, incident_seq)
    identity, never by sort order."""

    def __init__(self, schedule: list[ScheduledFault], evidence: dict,
                 grace_s: float = 25.0, recovery_bound_s: float = 60.0):
        self.schedule = schedule
        self.ev = evidence
        self.grace_s = grace_s
        self.recovery_bound_s = recovery_bound_s

    # -- per-gate judges -----------------------------------------------------

    def _in_window(self, ts: float, f: ScheduledFault,
                   grace: float | None = None) -> bool:
        g = self.grace_s if grace is None else grace
        return f.armed_ts - 2.0 <= ts <= f.cleared_ts + g

    def _judge_tail(self, f: ScheduledFault) -> tuple[bool, bool, dict]:
        """mesh.step: the verdict stream must carry
        collective_straggler rows NAMING the member, the windowed cause
        histogram must be dominated by it while the fault is live, and
        the scoreboard/conviction must convict the same member."""
        want = SCHEDULABLE_FAULTS[f.point]["expect_cause"]
        target = f"mesh{f.member}"
        named = [v for v in self.ev.get("tail_verdicts", [])
                 if v.get("cause") == want
                 and self._in_window(v.get("ts", 0.0), f)]
        member_ok = any(v.get("member") == target for v in named)
        dominant, board_top = "", ""
        for p in self.ev.get("probes", []):
            if not self._in_window(p.get("ts", 0.0), f, grace=5.0):
                continue
            d = _dominant(p.get("causes", {}))
            if d:
                dominant = d
            rows = p.get("scoreboard", [])
            if rows:
                top = max(rows, key=lambda r: r.get("slowest_frac", 0))
                if top.get("slowest_frac", 0) > 0:
                    board_top = top.get("member", "")
        convictions = self.ev.get("convictions", {})
        evidence = {
            "straggler_verdicts_in_window": len(named),
            "named_member_ok": member_ok,
            "dominant_cause_in_window": dominant,
            "scoreboard_top_in_window": board_top,
            "convictions": convictions.get(target, 0)}
        detected = bool(named)
        attributed = (member_ok and dominant == want
                      and board_top == target)
        return detected, attributed, evidence

    def _judge_mesh_incident(self, f: ScheduledFault
                             ) -> tuple[bool, bool, dict]:
        """device.transfer_fail: the coordinator's flight recorder must
        carry mesh_member_lost naming the member inside the window and
        mesh_member_recovered after the clear."""
        target = f"mesh{f.member}"
        incs = self.ev.get("mesh_incidents", [])
        lost = [i for i in incs if i.get("name") == "mesh_member_lost"
                and i.get("member") == target
                and self._in_window(i.get("ts", 0.0), f)]
        recovered = [i for i in incs
                     if i.get("name") == "mesh_member_recovered"
                     and i.get("member") == target
                     and i.get("ts", 0.0) >= f.cleared_ts - 2.0]
        evidence = {
            "lost_incidents": [{"seq": i.get("incident_seq"),
                                "ts": i.get("ts"),
                                "cause": i.get("cause")} for i in lost],
            "recovered_incidents": len(recovered)}
        detected = bool(lost)
        attributed = detected and bool(recovered) \
            and all(i.get("cause") == "lost" for i in lost)
        return detected, attributed, evidence

    def _judge_slo_incident(self, f: ScheduledFault
                            ) -> tuple[bool, bool, dict]:
        """servlet.serving: a health incident must fire inside the
        window with an SLO rule critical AND its armed-fault snapshot
        naming the injected point — the join that makes 'p95 burning'
        read 'p95 burning because servlet.serving=300 was armed'."""
        hits = []
        for i in self.ev.get("health_incidents", []):
            if not self._in_window(i.get("ts", 0.0), f):
                continue
            if not any("slo" in r for r in i.get("rules", [])):
                continue
            armed = i.get("armed_faults", {}) or {}
            hits.append({"seq": i.get("seq"), "ts": i.get("ts"),
                         "rules": i.get("rules"),
                         "names_point": f.point in armed,
                         "armed": armed})
        evidence = {"slo_incidents_in_window": hits}
        detected = bool(hits)
        attributed = any(h["names_point"] for h in hits)
        return detected, attributed, evidence

    def _judge_answered(self, f: ScheduledFault) -> tuple[bool, dict]:
        """100% answered while the fault is live: every workload
        request got an HTTP answer — 200 (full or degraded) or a
        counted 429 with Retry-After — never a 5xx, never a hang."""
        total = ok = degraded = errors = 0
        for q in self.ev.get("queries", []):
            if not (f.armed_ts <= q.get("ts", 0.0) <= f.cleared_ts):
                continue
            total += 1
            st = q.get("status", 0)
            if st == 200:
                ok += 1
            elif st == 429:
                degraded += 1
            else:
                errors += 1
        return (total > 0 and errors == 0), {
            "in_window": total, "ok_200": ok, "degraded_429": degraded,
            "errors": errors}

    def _judge_recovery(self, f: ScheduledFault) -> tuple[bool, dict]:
        """Bounded SLO recovery: after the clear, the workload's own
        walls must come back under the bound within recovery_bound_s
        (3 consecutive under-bound requests of the fault's kind mark
        the recovery point)."""
        kind = "servlet" if f.point == "servlet.serving" else "mesh"
        base = self.ev.get("baseline_ms", {}).get(kind, 50.0)
        bound_ms = max(250.0, 3.0 * base)
        walls = [(q["ts"], q.get("dur_ms", 0.0))
                 for q in self.ev.get("queries", [])
                 if q.get("kind") == kind and q.get("status") == 200
                 and q.get("ts", 0.0) >= f.cleared_ts]
        recovered_s = None
        for i in range(len(walls)):
            run = walls[i:i + 3]
            # a FULL window only: a 1-2 sample tail slice must not let
            # one lucky fast request mark the recovery point
            if len(run) == 3 and all(w <= bound_ms for _, w in run):
                recovered_s = walls[i][0] - f.cleared_ts
                break
        ok = recovered_s is not None \
            and recovered_s <= self.recovery_bound_s
        return ok, {"bound_ms": round(bound_ms, 1),
                    "recovery_bound_s": self.recovery_bound_s,
                    "recovered_s": (round(recovered_s, 2)
                                    if recovered_s is not None
                                    else None),
                    "post_clear_samples": len(walls)}

    # -- the table -----------------------------------------------------------

    def verdicts(self) -> list[dict]:
        judges = {"tail": self._judge_tail,
                  "mesh_incident": self._judge_mesh_incident,
                  "slo_incident": self._judge_slo_incident}
        bit = self.ev.get("bit_identity", {})
        rows = []
        for f in self.schedule:
            detected, attributed, evidence = judges[f.detect()](f)
            answered, answered_ev = self._judge_answered(f)
            recovered, recovery_ev = self._judge_recovery(f)
            bit_ok = bool(bit.get("identical"))
            gates = {"detected": detected, "attributed": attributed,
                     "answered": answered, "slo_recovery": recovered,
                     "bit_identical": bit_ok}
            failed = [g for g, ok in gates.items() if not ok]
            rows.append({**f.row(), **gates,
                         "evidence": evidence,
                         "answered_detail": answered_ev,
                         "recovery": recovery_ev,
                         "verdict": "pass" if not failed
                         else "fail:" + "+".join(failed)})
        return rows


# -- the conductor -----------------------------------------------------------

class Conductor:
    """Drives one game day against a live MeshFleet: the zipfian
    workload under the rate envelope with per-client identity, the
    fault schedule armed/cleared over the wire, periodic health ticks
    + evidence snapshots, then the post-run recovery wait, the
    bit-identity probe and the verdict join."""

    def __init__(self, fleet, schedule: list[ScheduledFault],
                 terms: list[str], envelope: RateEnvelope,
                 duration_s: float, clients: ClientPool | None = None,
                 zipf_s: float = 1.1, probe_every_s: float = 5.0,
                 servlet_page: str = "Status.html",
                 recovery_bound_s: float = 60.0, k: int = 10):
        self.fleet = fleet
        self.schedule = schedule
        self.terms = list(terms)
        self.envelope = envelope
        self.duration_s = float(duration_s)
        self.clients = clients or ClientPool()
        self.zipf = ZipfSampler(self.terms, s=zipf_s, seed=7)
        self.probe_every_s = probe_every_s
        self.servlet_page = servlet_page
        self.recovery_bound_s = recovery_bound_s
        self.k = k
        self.queries: list[dict] = []
        self.probes: list[dict] = []
        # the wire info() view exposes only the newest few verdicts, so
        # the conductor accumulates the union across probes (keyed by
        # trace id) — F1-window evidence must survive to the final join
        self.tail_verdicts: dict[str, dict] = {}
        self.baseline: dict[str, dict] = {}
        self.baseline_ms: dict[str, float] = {}

    # -- pieces --------------------------------------------------------------

    def warm_and_baseline(self) -> None:
        """Compile-warm every term's shapes, then pin the pre-fault
        reference rankings (loopback identity — the baseline and the
        final bit-identity probe must never be admission-denied)."""
        walls = []
        for _ in range(2):
            for w in self.terms:
                t0 = time.perf_counter()
                rep = self.fleet.search(w, k=self.k)
                walls.append((time.perf_counter() - t0) * 1000.0)
                assert rep.get("scores") is not None, rep
        for w in self.terms:
            rep = self.fleet.search(w, k=self.k)
            assert rep["mode"] == "collective", (
                f"baseline must be collective, got {rep['mode']}")
            self.baseline[w] = {"scores": rep["scores"],
                                "docids": rep["docids"]}
        walls.sort()
        self.baseline_ms["mesh"] = walls[len(walls) // 2]
        st, wall = self.fleet.get(0, self.servlet_page)
        assert st == 200, f"servlet baseline GET failed: {st}"
        self.baseline_ms["servlet"] = wall
        # warmup/measurement boundary: drop the windowed histogram
        # samples recorded so far — the compile-era warmup walls are
        # orders of magnitude above the live workload and would hold
        # the classifier's cached-p95 exemplar gate above every
        # fault-slowed query for WINDOWS*30s.  The workload starts
        # against the `tail.minMs` floor and the gate re-learns from
        # live windows only.
        self.fleet.info(0, prime_tail_gate=True)

    def _fire_due(self, t: float) -> None:
        for f in self.schedule:
            if f.armed_ts == 0.0 and t >= f.t_arm:
                f.arm_ack = self.fleet.fault(f.member, f.point, f.value)
                f.armed_ts = time.time()
                assert f.arm_ack.get("result") == "ok", (f, f.arm_ack)
            elif f.armed_ts and f.cleared_ts == 0.0 \
                    and t >= f.t_clear:
                f.clear_ack = self.fleet.fault(f.member, f.point, None,
                                               clear=True)
                f.cleared_ts = time.time()
                assert f.clear_ack.get("result") == "ok", \
                    (f, f.clear_ack)

    def _probe(self, t: float) -> None:
        info = self.fleet.info(0, tick_health=True)
        tail = info.get("tail", {})
        for v in tail.get("verdicts", []):
            self.tail_verdicts[v.get("trace_id", str(v.get("ts")))] = v
        self.probes.append({
            "t": round(t, 2), "ts": time.time(),
            "causes": tail.get("causes", {}),
            "scoreboard": tail.get("scoreboard", []),
            "convictions": tail.get("convictions", {}),
            "health_incidents": len(info.get("health_incidents", [])),
            "mesh_incidents": len(info.get("incidents", []))})

    def _one_query(self, t: float) -> None:
        term = self.zipf.sample()
        client = self.clients.pick()
        t0 = time.perf_counter()
        try:
            status, rep = self.fleet.search_ex(term, k=self.k,
                                               client=client)
        except Exception as e:   # transport failure = NOT answered
            status, rep = -1, {"error": repr(e)}
        self.queries.append({
            "t": round(t, 2), "ts": time.time(), "kind": "mesh",
            "term": term, "client": client, "status": status,
            "mode": rep.get("mode", ""),
            "dur_ms": round((time.perf_counter() - t0) * 1000.0, 2)})

    def _one_get(self, t: float) -> None:
        client = self.clients.pick()
        try:
            status, wall = self.fleet.get(0, self.servlet_page,
                                          client=client)
        except Exception as e:
            status, wall = -1, 0.0
        self.queries.append({
            "t": round(t, 2), "ts": time.time(), "kind": "servlet",
            "page": self.servlet_page, "client": client,
            "status": status, "dur_ms": round(wall, 2)})

    def run_workload(self) -> None:
        t0 = time.monotonic()
        next_mesh = next_servlet = 0.0
        next_probe = self.probe_every_s
        while True:
            t = time.monotonic() - t0
            if t >= self.duration_s:
                break
            self._fire_due(t)
            if t >= next_probe:
                self._probe(t)
                next_probe = t + self.probe_every_s
            ph = self.envelope.at(t)
            did = False
            if t >= next_mesh:
                self._one_query(t)
                gap = 1.0 / max(0.1, ph.qps)
                # bounded catch-up: a straggled query may owe several
                # ticks; burst at most 2 gaps behind real time (a real
                # client retries, it does not replay its whole backlog)
                next_mesh = max(next_mesh + gap,
                                time.monotonic() - t0 - 2 * gap)
                did = True
            if ph.servlet_qps > 0 and t >= next_servlet:
                self._one_get(t)
                sgap = 1.0 / ph.servlet_qps
                next_servlet = max(next_servlet + sgap,
                                   time.monotonic() - t0 - 2 * sgap)
                did = True
            if not did:
                wake = min(next_mesh, next_probe,
                           next_servlet if ph.servlet_qps > 0
                           else next_mesh)
                time.sleep(min(0.05, max(0.005,
                                         wake - (time.monotonic()
                                                 - t0))))
        # anything still armed clears at the horizon (the schedule is
        # the contract: the run ends with every fault cleared); twice,
        # so a window the loop never reached arms and then clears
        self._fire_due(self.duration_s + 1e9)
        self._fire_due(self.duration_s + 1e9)

    def wait_full_recovery(self, timeout_s: float = 120.0) -> dict:
        """After every clear: wait for lost members to rebuild and for
        collectives to resume — the precondition of the bit-identity
        probe (host answers are bit-identical too, but the acceptance
        gate is the RECOVERED fleet ranking exactly as before)."""
        out = {"lost_cleared": {}, "collective_resumed": False,
               "wall_s": 0.0}
        t0 = time.monotonic()
        lost_members = {f.member for f in self.schedule
                        if f.point == "device.transfer_fail"}
        for m in sorted(lost_members):
            while time.monotonic() - t0 < timeout_s:
                if not self.fleet.info(m).get("lost"):
                    out["lost_cleared"][f"mesh{m}"] = True
                    break
                time.sleep(0.5)
            else:
                out["lost_cleared"][f"mesh{m}"] = False
        while time.monotonic() - t0 < timeout_s:
            rep = self.fleet.search(self.terms[0], k=self.k)
            if rep.get("mode") == "collective":
                out["collective_resumed"] = True
                break
            time.sleep(0.5)
        out["wall_s"] = round(time.monotonic() - t0, 2)
        return out

    def bit_identity_probe(self) -> dict:
        """Re-rank every term on the recovered fleet and compare
        bit-for-bit against the pre-fault baseline."""
        per_term, identical = {}, True
        for w in self.terms:
            rep = self.fleet.search(w, k=self.k)
            same = (rep["scores"] == self.baseline[w]["scores"]
                    and rep["docids"] == self.baseline[w]["docids"])
            per_term[w] = {"identical": same, "mode": rep["mode"]}
            identical = identical and same
        return {"identical": identical, "terms": per_term}

    # -- the whole day -------------------------------------------------------

    def run(self) -> dict:
        global LAST_RUN
        self.warm_and_baseline()
        self.run_workload()
        recovery = self.wait_full_recovery()
        bit = self.bit_identity_probe()
        info = self.fleet.info(0, tick_health=True)
        tail = info.get("tail", {})
        for v in tail.get("verdicts", []):
            self.tail_verdicts[v.get("trace_id", str(v.get("ts")))] = v
        all_verdicts = sorted(self.tail_verdicts.values(),
                              key=lambda v: v.get("ts", 0.0))
        evidence = {
            "queries": self.queries,
            "probes": self.probes,
            "tail_verdicts": all_verdicts,
            "mesh_incidents": info.get("incidents", []),
            "health_incidents": info.get("health_incidents", []),
            "convictions": tail.get("convictions", {}),
            "bit_identity": bit,
            "baseline_ms": self.baseline_ms,
        }
        rows = VerdictEngine(
            self.schedule, evidence,
            recovery_bound_s=self.recovery_bound_s).verdicts()
        statuses: dict[str, int] = {}
        for q in self.queries:
            key = str(q["status"])
            statuses[key] = statuses.get(key, 0) + 1
        mesh_q = [q for q in self.queries if q["kind"] == "mesh"]
        # the ISSUE 19 gate is zero unattributed verdicts UNDER THE
        # SCHEDULED FAULTS: every tail query inside an armed window
        # must name its injected cause.  Outside the windows a
        # CPU-contended environment can legitimately produce slow-but-
        # uniform queries with nothing to attribute; the run-wide
        # cumulative count stays in the result (unattributed_total)
        # for diagnosability but does not gate.
        def _in_fault_window(ts: float) -> bool:
            return any(f.armed_ts <= ts <= f.cleared_ts
                       for f in self.schedule)
        unattr_all = [v for v in all_verdicts
                      if v.get("cause") == "unattributed"]
        unattr_in_window = [v for v in unattr_all
                            if _in_fault_window(v.get("ts", 0.0))]
        result = {
            "drill": "game_day",
            "workload": {
                "terms": self.terms,
                "zipf_s": self.zipf.s,
                "clients": self.clients.clients,
                "phases": self.envelope.to_json(),
                "duration_s": self.duration_s,
                "queries_total": len(self.queries),
                "mesh_queries": len(mesh_q),
                "servlet_gets": len(self.queries) - len(mesh_q),
                "by_status": statuses,
                "baseline_ms": {k: round(v, 2) for k, v
                                in self.baseline_ms.items()},
            },
            "schedule": rows,
            "overlaps": self._overlaps(),
            "verdict_summary": {
                "faults": len(rows),
                "passed": sum(1 for r in rows
                              if r["verdict"] == "pass"),
                "all_pass": all(r["verdict"] == "pass" for r in rows),
                "unattributed_verdicts": len(unattr_in_window),
                "unattributed_total": int(
                    tail.get("cause_totals", {})
                    .get("unattributed", 0)),
                # any unattributed verdict the probes caught, verbatim
                # (in-window ones first) — the zero-unattributed gate
                # must be diagnosable from the result alone when it
                # trips
                "unattributed_sample":
                    (unattr_in_window or unattr_all)[:10],
                "never_500": all(200 <= q["status"] < 500
                                 for q in self.queries),
            },
            "tail": {
                "cause_totals": tail.get("cause_totals", {}),
                "stragglers": tail.get("stragglers", {}),
                "scoreboard": tail.get("scoreboard", []),
                "convictions": tail.get("convictions", {}),
                "conviction_crumbs": tail.get("conviction_crumbs", []),
            },
            "incidents": {
                "mesh": info.get("incidents", []),
                "health": info.get("health_incidents", []),
            },
            "fault_wire_schedule": {
                f"mesh{i}": self.fleet.fault_list(i).get("schedule", [])
                for i in range(self.fleet.procs)
            },
            "recovery": recovery,
            "bit_identity": bit,
        }
        LAST_RUN = result
        return result

    def _overlaps(self) -> list[list[str]]:
        out = []
        sched = sorted(self.schedule, key=lambda f: f.t_arm)
        for i, a in enumerate(sched):
            for b in sched[i + 1:]:
                if b.t_arm < a.t_clear and a.t_arm < b.t_clear:
                    out.append([a.fault_id, b.fault_id])
        return out
