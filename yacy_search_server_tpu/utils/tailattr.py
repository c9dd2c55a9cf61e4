"""Tail forensics — the p99 cause-attribution engine (ISSUE 15).

The observability spine built in rounds 6–10 *measures* a slow query
(windowed histograms, exemplars, burn-rate rules) but never *explains*
it: a cold-tier page-in, a deferred merge, a straggling mesh member all
land in the same anonymous fat p99 bucket, and a flight-recorder
incident names the symptom (``slo_serving_p95 critical``), not the
cause.  This module promotes the per-stage attribution discipline of
the trace spine into a CAUSAL layer: every over-threshold query gets
exactly one classified verdict.

Three parts:

- :class:`TailAttributor` — the classifier.  Hooked to root-span
  completion (``tracing.add_root_hook``), it reuses the cached-window-
  p95 gating from :mod:`utils.histogram` (the same gate that elects
  exemplars): a serving root at/above its family's window p95 (floored
  at ``MIN_MS``) is exemplar-worthy, so it gets classified.  The walk
  reads the trace's spans — cause markers emitted by the product paths
  (``tail.host_fallback`` / ``tail.cold_miss`` / ``tail.lock_wait`` /
  ``search.degraded``), the per-wave stamps the batchers attach to
  ``devstore.batch`` / ``mesh.batch`` spans, and the kernel span
  decomposition — and emits ONE dominant cause from :data:`CAUSES`
  into a zero-filled counter canon (``yacy_tail_cause_total{cause}``)
  plus a bounded verdict ring served by ``Performance_Tail_p``.
- :class:`MeshTimeline` — cross-process scatter assembly.  Mesh members
  return their step's span segment (queue wait, commit/collective-entry
  wait, local execution wall) inline on the next scatter reply (zero
  extra RPCs); the coordinator assembles a complete per-member timeline
  for every collective query, merges it into the trace ring (the
  ``assemble=1`` waterfall shows the whole mesh), finalizes verdicts
  that had to wait for segments (``collective_straggler`` NAMES the
  slowest member) and maintains the windowed straggler scoreboard (how
  often each member was the slowest leg, by how much).
- The wave log — a bounded ring of the batchers' dispatch-wave stamps
  (queue depth at enqueue, wave occupancy, compile-vs-reuse, tier/
  deferral state) so a query's slowness is attributable to *its wave*,
  not just its own spans.

Cause precedence under overlapping faults (ISSUE 19): two armed faults
can both plausibly explain one slow query — a cold-tier miss during a
mesh straggle, a compile charge on a degraded rung.  The classifier
emits exactly ONE cause, resolved by a fixed priority ladder
(:data:`PRECEDENCE`, pinned by the table-driven test in
tests/test_tailattr.py):

1. ``collective_straggler`` — the assembled mesh timeline NAMES the
   late member; cross-process evidence outranks every local marker.
2. ``host_fallback`` — the store KNOWS the device was lost; the query
   was answered on the host no matter what else was slow around it.
3. ``merge_deferral`` / ``tier_cold`` — the first cold-miss marker;
   one rung, split by the marker's ``deferred`` attr (the scheduler
   parked the promotion vs a plain cold miss).
4. ``compile`` — the wave stamp's compile-vs-reuse bit.
5. ``queue_wait`` — measured pre-issue wait >= 40% of the wall.
6. ``lock_wait`` — measured lock-acquisition wall >= 30% of the wall.
7. ``degraded_rung`` — served under a ladder rung with nothing above
   claiming the wall.
8. ``unattributed`` — no detector claimed it (the zero-unattributed
   game-day gate counts these).

Explicit markers outrank inferred dominance shares because the product
path that emitted the marker KNOWS why it slowed; dominance thresholds
are heuristics.

Straggler convictions (ISSUE 19 / ROADMAP 1c first slice, read-only):
:class:`ConvictionTracker` watches the windowed scoreboard; a member
that is the slowest leg of most steps for N consecutive windows is
CONVICTED — a flight-recorder breadcrumb + the zero-filled
``yacy_mesh_straggler_convictions_total{member}`` series.  Observation
only: steering/shedding on a conviction stays future work.

Jax-free by contract (imported by the wire layer and the chaos
children); zero-alloc when disabled — every product hook bails on one
module-flag read (:func:`set_enabled`).
"""

from __future__ import annotations

import logging
import statistics
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field

from . import histogram, tracing

log = logging.getLogger("tailattr")

# the cause canon (zero-filled on /metrics so alert expressions and the
# fleet digest's top-1 field always resolve).  collective_straggler
# verdicts additionally NAME the member (verdict ring + scoreboard +
# yacy_tail_straggler_total{member}).
CAUSES = (
    "queue_wait",            # batcher wait dominated (pool saturated /
    #                          dispatcher wedged / backlog)
    "compile",               # the wave paid a first-use kernel compile
    "collective_straggler",  # one mesh member's step straggled the fleet
    "tier_cold",             # cold/warm tier miss: the query host-served
    #                          while its term's promotion was kicked
    "merge_deferral",        # the miss was parked by the merge/promotion
    #                          scheduler's serving-SLO deferral
    "lock_wait",             # measured lock-acquisition wall dominated
    "degraded_rung",         # the query served under a degradation rung
    "host_fallback",         # device lost / transfer failure: counted
    #                          host answer
    "unattributed",          # over threshold, no detector claimed it
)

# the classifier's tie-break ladder under overlapping faults, highest
# priority first (merge_deferral and tier_cold share one rung — the
# cold marker's `deferred` attr splits them).  classify() must consult
# detectors in exactly this order; the table-driven precedence test
# cross-references this tuple.
PRECEDENCE = (
    "collective_straggler",
    "host_fallback",
    "merge_deferral", "tier_cold",
    "compile",
    "queue_wait",
    "lock_wait",
    "degraded_rung",
    "unattributed",
)

# cause-marker span families the product paths emit (each creates a
# histogram family through the one span-record wiring point; the
# markers are 0 ms except lock_wait, which is a real measured wall)
MARKER_HOST_FALLBACK = "tail.host_fallback"
MARKER_COLD_MISS = "tail.cold_miss"
MARKER_LOCK_WAIT = "tail.lock_wait"
MARKER_DEGRADED = "search.degraded"        # emitted by SearchEvent (M83)

# histogram families the classifier consumes or gates on — the
# yacylint `tail-reach` checker requires any family a servlet wall
# observes to appear here (or carry a reasoned tail-ok lint
# exemption): a serving wall the classifier cannot reach is a p99
# bucket nothing can ever explain.
CLASSIFIER_FAMILIES = frozenset({
    "servlet.serving",
    "switchboard.search", "mesh.serve",
    "devstore.batch", "mesh.batch", "mesh.collective",
    "kernel.issue", "kernel.device", "kernel.fetch",
    MARKER_HOST_FALLBACK, MARKER_COLD_MISS, MARKER_LOCK_WAIT,
    MARKER_DEGRADED,
})

# roots eligible for classification: query-serving walls only — a
# pipeline/crawl root must never claim a tail verdict (the same
# discipline as histogram.BACKGROUND_PREFIXES)
SERVING_ROOT_PREFIXES = ("servlet.",)
SERVING_ROOT_NAMES = frozenset({"switchboard.search", "mesh.serve"})

# classification gate floor: the cached window p95 starts at 0 on a
# fresh family, and a microsecond root crossing a 0 gate would classify
# every healthy request
MIN_MS = 25.0
# a lock wait under this never emits a marker (uncontended acquires are
# the overwhelming hot path)
LOCK_WAIT_MIN_MS = 1.0
# dominance thresholds (fractions of the root wall).  Queue dominance
# judges the batcher-MEASURED pre-issue wait (submit -> wave issue),
# which excludes the query's own kernel work by construction — 40% of
# the wall spent purely waiting is a queue verdict.
QUEUE_DOMINANCE = 0.4
LOCK_DOMINANCE = 0.3
# a member is a straggler when its exec wall exceeds the median of the
# other members' by this factor AND carries a material share of the wall
STRAGGLER_FACTOR = 2.0
STRAGGLER_MIN_SHARE = 0.25

VERDICT_RING = 256
WAVE_RING = 128
SCOREBOARD_RING = 1024
MESH_RECORDS = 256

_enabled = True


def set_enabled(on: bool) -> None:
    """Global gate: disables classification AND the batchers' wave
    stamping in one flag."""
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


def configure(cfg) -> None:
    """Read the tail.* knobs once at switchboard construction (the
    health-engine model for performance knobs)."""
    global MIN_MS
    set_enabled(cfg.get_bool("tail.enabled", True))
    MIN_MS = cfg.get_float("tail.minMs", MIN_MS)
    CONVICTIONS.configure(cfg)


@dataclass
class Verdict:
    """One classified over-threshold query."""

    ts: float
    trace_id: str
    root: str
    dur_ms: float
    cause: str
    member: str = ""
    evidence: dict = field(default_factory=dict)

    def to_json(self) -> dict:
        out = {"ts": round(self.ts, 3), "trace_id": self.trace_id,
               "root": self.root, "dur_ms": round(self.dur_ms, 3),
               "cause": self.cause, "evidence": self.evidence}
        if self.member:
            out["member"] = self.member
        return out


def _p95_gate_ms(family: str) -> float:
    """The cached-window-p95 gate for a family (the histogram's
    exemplar election threshold), floored at MIN_MS."""
    h = histogram.get(family)
    return max(MIN_MS, h.p95_cache if h is not None else 0.0)


class TailAttributor:
    """The classifier + verdict ring + cause counters (process-global
    like the histogram registry it gates on)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.ring: deque = deque(maxlen=VERDICT_RING)
        self.cause_totals: dict[str, int] = {c: 0 for c in CAUSES}
        self.straggler_totals: dict[str, int] = {}
        self.classified_total = 0
        self.waves: deque = deque(maxlen=WAVE_RING)

    # -- recording surface ---------------------------------------------------

    def note_root(self, trace_id: str, name: str, dur_ms: float) -> None:
        """Root-span completion hook (tracing.add_root_hook): classify
        the trace when its wall clears the family's cached-window-p95
        exemplar gate."""
        if not _enabled:
            return
        if not (name in SERVING_ROOT_NAMES
                or name.startswith(SERVING_ROOT_PREFIXES)):
            return
        if dur_ms < _p95_gate_ms(name):
            return
        rec = tracing.get_trace(trace_id)
        if rec is None:
            return
        if name == "mesh.serve":
            # mesh verdicts need the members' span segments, which
            # arrive on the NEXT scatter reply: hand off to the
            # timeline, which finalizes (or defers) the verdict
            MESH.mark_pending(trace_id, dur_ms)
            return
        self.record(self.classify(rec, dur_ms))

    def note_wave(self, wave: dict) -> None:
        """One dispatch wave's stamp into the bounded wave log (the
        Performance_Tail_p wave table)."""
        if not _enabled:
            return
        with self._lock:
            self.waves.append(wave)

    # -- classification ------------------------------------------------------

    def classify(self, rec, dur_ms: float,
                 mesh_info: dict | None = None) -> Verdict:
        """Walk one trace's spans (+ the optional assembled mesh
        timeline) and emit exactly one dominant cause.  Detector order
        is a priority ladder: explicit markers (the product path KNOWS
        why it slowed) outrank inferred dominance shares."""
        host_fb = False
        cold = None                      # attrs of the first cold marker
        lock_ms = 0.0
        degraded_level = 0
        batch_ms = 0.0
        kernel_ms = 0.0
        queue_ms = 0.0
        wave_compile = False
        q_depth = 0
        wave_occ = 0.0
        for s in rec.spans:
            n = s.name
            if n == MARKER_HOST_FALLBACK:
                host_fb = True
            elif n == MARKER_COLD_MISS and cold is None:
                cold = s.attrs
            elif n == MARKER_LOCK_WAIT:
                lock_ms += s.dur_ms
            elif n == MARKER_DEGRADED:
                try:
                    degraded_level = max(degraded_level,
                                         int(s.attrs.get("level", 0)))
                except (TypeError, ValueError):
                    pass
            elif n in ("devstore.batch", "mesh.batch"):
                batch_ms += s.dur_ms
                a = s.attrs
                wave_compile = wave_compile or bool(a.get("wave_compile"))
                try:
                    q_depth = max(q_depth, int(a.get("wave_qdepth", 0)))
                    wave_occ = max(wave_occ,
                                   float(a.get("wave_occ", 0.0)))
                    # MEASURED pre-issue wait stamped by the batcher
                    # (submit -> wave issue) — never inferred by
                    # subtracting overlapping kernel spans
                    queue_ms += float(a.get("wave_queue_ms", 0.0))
                except (TypeError, ValueError):
                    pass
            elif n.startswith("kernel."):
                kernel_ms += s.dur_ms
        ev = {"batch_ms": round(batch_ms, 3),
              "kernel_ms": round(kernel_ms, 3),
              "queue_ms": round(queue_ms, 3),
              "lock_ms": round(lock_ms, 3),
              "wave_qdepth": q_depth, "wave_occ": round(wave_occ, 3),
              "gate_ms": round(_p95_gate_ms(rec.root_name), 3)}
        cause, member = "unattributed", ""
        if mesh_info is not None:
            ev.update(mesh_info.get("evidence", {}))
            if mesh_info.get("straggler"):
                cause, member = "collective_straggler", \
                    mesh_info["straggler"]
            elif mesh_info.get("host_fallback"):
                # the collective could not form (a member lost/down) or
                # declined the step: the answer came from the host
                # mirror.  Attributed to the member whose state forced
                # the fallback — a game-day loss window must never read
                # `unattributed` on the coordinator
                host_fb = True
                member = str(mesh_info.get("culprit", ""))
        if cause == "unattributed":
            if host_fb:
                cause = "host_fallback"
            elif cold is not None:
                cause = "merge_deferral" if cold.get("deferred") \
                    else "tier_cold"
                ev["tier"] = str(cold.get("tier", "?"))
            elif wave_compile:
                cause = "compile"
            elif queue_ms >= QUEUE_DOMINANCE * dur_ms:
                cause = "queue_wait"
            elif lock_ms >= LOCK_DOMINANCE * dur_ms:
                cause = "lock_wait"
            elif degraded_level > 0:
                cause = "degraded_rung"
                ev["level"] = degraded_level
        return Verdict(time.time(), rec.trace_id, rec.root_name,
                       dur_ms, cause, member, ev)

    def record(self, v: Verdict) -> None:
        with self._lock:
            self.ring.append(v)
            self.cause_totals[v.cause] = \
                self.cause_totals.get(v.cause, 0) + 1
            self.classified_total += 1
            if v.member:
                self.straggler_totals[v.member] = \
                    self.straggler_totals.get(v.member, 0) + 1
        # whitebox deep capture (ISSUE 20c): a contention/queueing/
        # straggler verdict arms one bounded high-rate profiler window
        # so the NEXT incident embeds what the process was doing while
        # the tail burned.  Lazy import (profiling imports this module);
        # trigger() is rate-limited and a no-op when disabled.
        if v.cause in ("lock_wait", "queue_wait", "collective_straggler"):
            from . import profiling
            profiling.trigger(f"tail.{v.cause}")

    # -- reading -------------------------------------------------------------

    def verdicts(self, n: int = 50) -> list:
        with self._lock:
            return list(self.ring)[-max(0, n):][::-1]

    def windowed_causes(self, horizon_s: float = 180.0) -> dict:
        """Cause -> count over the last `horizon_s` (zero-filled over
        the canon) — the histogram an incident embeds."""
        cut = time.time() - horizon_s
        out = {c: 0 for c in CAUSES}
        with self._lock:
            for v in self.ring:
                if v.ts >= cut:
                    out[v.cause] = out.get(v.cause, 0) + 1
        return out

    def top_cause(self, horizon_s: float = 180.0) -> str:
        """The windowed dominant cause (the fleet digest's top-1 field);
        'unattributed' when the window is empty — always a canon member,
        so the digest_series mapping resolves."""
        w = self.windowed_causes(horizon_s)
        best = max(w, key=lambda c: w[c])
        return best if w[best] > 0 else "unattributed"

    def counters(self) -> dict:
        with self._lock:
            return {"classified_total": self.classified_total,
                    "causes": dict(self.cause_totals),
                    "stragglers": dict(self.straggler_totals)}

    def wave_log(self, n: int = 50) -> list:
        with self._lock:
            return list(self.waves)[-max(0, n):][::-1]

    def reset(self) -> None:
        with self._lock:
            self.ring.clear()
            self.waves.clear()
            self.cause_totals = {c: 0 for c in CAUSES}
            self.straggler_totals = {}
            self.classified_total = 0


class MeshTimeline:
    """Coordinator-side assembly of the per-member step segments
    (ISSUE 15a).  One record per scattered step; segments arrive inline
    on later scatter replies and complete the record with zero extra
    RPCs.  Complete records feed the straggler scoreboard; records the
    classifier marked pending finalize their verdict the moment the
    last segment lands."""

    def __init__(self):
        self._lock = threading.Lock()
        self._by_seq: "OrderedDict[int, dict]" = OrderedDict()
        self._by_trace: dict[str, int] = {}
        self.segments_merged = 0
        # pending verdicts finalized from PARTIAL segments (a lull in
        # traffic means the missing members' segments have no later
        # scatter reply to ride) — counted, never silently dropped
        self.pending_partial = 0
        # (ts, slowest_member, margin_ms, exec_by_member) per COMPLETE
        # step — the scoreboard is windowed over this ring
        self._board: deque = deque(maxlen=SCOREBOARD_RING)
        # every member id this timeline has ever scattered to — the
        # zero-fill domain for the conviction series (a member with no
        # convictions must still expose a 0 sample)
        self.known: set[int] = set()

    def note_step(self, seq: int, trace_id: str, members,
                  mode: str, culprit: str = "") -> None:
        """Register a scattered step (called by the coordinator BEFORE
        its mesh.serve root closes, so a pending classification can
        find the record).  `culprit` names the member whose lost/down
        state forced a host-mode step — the later verdict attributes
        the host fallback to it."""
        if not _enabled:
            return
        with self._lock:
            self._by_seq[seq] = {
                "seq": int(seq), "trace_id": trace_id, "ts": time.time(),
                "members": set(int(m) for m in members), "mode": mode,
                "culprit": culprit,
                "segs": {}, "pending_ms": None, "dur_ms": 0.0}
            self.known.update(self._by_seq[seq]["members"])
            self._by_trace[trace_id] = int(seq)
            evicted = []
            while len(self._by_seq) > MESH_RECORDS:
                _, old = self._by_seq.popitem(last=False)
                self._by_trace.pop(old.get("trace_id", ""), None)
                evicted.append(old)
        # an evicted record still owing a verdict finalizes from its
        # PARTIAL segments (counted) — never a silent drop; the lull
        # case (no later scatter to carry the missing segments at all)
        # is flushed by flush_pending from the tail read surfaces
        for old in evicted:
            if old.get("pending_ms") is not None:
                self._finalize(old)

    def finish_step(self, seq: int, dur_ms: float) -> None:
        with self._lock:
            rec = self._by_seq.get(int(seq))
            if rec is not None:
                rec["dur_ms"] = float(dur_ms)

    def mark_pending(self, trace_id: str, dur_ms: float) -> None:
        """The classifier's deferred-verdict hand-off: finalize now if
        every segment already arrived, else when the last one lands."""
        with self._lock:
            seq = self._by_trace.get(trace_id)
            rec = self._by_seq.get(seq) if seq is not None else None
            if rec is None:
                return
            rec["pending_ms"] = float(dur_ms)
            complete = set(rec["segs"]) >= rec["members"]
        if complete:
            self._finalize(rec)

    def add_segment(self, seg: dict) -> None:
        """One member's step segment (q_ms / commit_ms / exec_ms /
        mode), shipped inline on a scatter reply or produced locally by
        the coordinator's own runloop."""
        if not _enabled or not isinstance(seg, dict):
            return
        try:
            seq = int(seg["seq"])
            member = int(seg["m"])
        except (KeyError, TypeError, ValueError):
            return
        with self._lock:
            rec = self._by_seq.get(seq)
            if rec is None or member in rec["segs"]:
                return
            rec["segs"][member] = {
                "m": member,
                "q_ms": float(seg.get("q_ms", 0.0)),
                "commit_ms": float(seg.get("commit_ms", 0.0)),
                "entry_ms": float(seg.get("entry_ms", 0.0)),
                "exec_ms": float(seg.get("exec_ms", 0.0)),
                "mode": str(seg.get("mode", "?")),
                "ts0": float(seg.get("ts0", 0.0))}
            self.segments_merged += 1
            complete = set(rec["segs"]) >= rec["members"]
            if complete:
                # straggler signal = LOCAL lateness (queue backlog +
                # pre-dispatch wall): in an SPMD collective every
                # member's exec wall inflates identically when one
                # member is late, so exec cannot name the culprit —
                # the member that ENTERED latest can (distributed.py
                # stamps entry_ms exactly for this)
                lates = {m: s["q_ms"] + s["entry_ms"]
                         for m, s in rec["segs"].items()}
                slowest = max(lates, key=lambda m: lates[m])
                others = [v for m, v in lates.items() if m != slowest]
                margin = lates[slowest] - (statistics.median(others)
                                           if others else 0.0)
                self._board.append((time.time(), slowest,
                                    max(0.0, margin),
                                    {m: s["exec_ms"]
                                     for m, s in rec["segs"].items()}))
        if complete:
            self._merge_into_trace(rec)
            if rec["pending_ms"] is not None:
                self._finalize(rec)

    def _merge_into_trace(self, rec: dict) -> None:
        """Inject the assembled per-member timeline into the trace ring
        so `Performance_Trace_p?trace=<id>&assemble=1` renders the mesh
        waterfall.  Rides merge_remote_spans: idempotent dedup, and the
        spans never re-feed the histograms (the members observed their
        own walls)."""
        tid = rec.get("trace_id", "")
        if not tracing.valid_trace_id(tid):
            return
        for m, s in sorted(rec["segs"].items()):
            ts0 = s["ts0"] or rec["ts"]
            spans = []
            t = ts0
            for short, name in (("q_ms", "mesh.member.queue_wait"),
                                ("commit_ms", "mesh.member.commit_wait"),
                                ("entry_ms", "mesh.member.local_entry"),
                                ("exec_ms", "mesh.member.exec")):
                spans.append({"sid": f"m{m}q{rec['seq']}{short[:-3]}",
                              "parent": "", "name": name, "ts": t,
                              "dur_ms": round(s[short], 3),
                              "attrs": {"member": f"mesh{m}",
                                        "mode": s["mode"]}})
                t += s[short] / 1000.0
            tracing.merge_remote_spans(tid, spans, source=f"mesh{m}")

    def _finalize(self, rec: dict) -> None:
        """Classify a pending over-threshold mesh step now that its
        timeline is complete: collective_straggler names the slowest
        member when its exec wall dominates.  Idempotent: the pending
        wall is claimed under the lock, so a mark_pending racing the
        last add_segment produces exactly one verdict."""
        with self._lock:
            claimed = rec["pending_ms"]
            rec["pending_ms"] = None
        if claimed is None:
            return
        partial = not (set(rec["segs"]) >= rec["members"])
        if partial:
            with self._lock:
                self.pending_partial += 1
        lates = {m: s["q_ms"] + s["entry_ms"]
                 for m, s in rec["segs"].items()}
        slowest = max(lates, key=lambda m: lates[m]) if lates else None
        straggler = ""
        dur = claimed
        if slowest is not None:
            others = [v for m, v in lates.items() if m != slowest]
            med = statistics.median(others) if others else 0.0
            if lates[slowest] >= max(STRAGGLER_FACTOR * med,
                                     STRAGGLER_MIN_SHARE * dur):
                straggler = f"mesh{slowest}"
        info = {"straggler": straggler,
                "evidence": {
                    "seq": rec["seq"], "mode": rec["mode"],
                    "late_ms_by_member": {f"mesh{m}": round(v, 3)
                                          for m, v in lates.items()},
                    "exec_ms_by_member": {
                        f"mesh{m}": round(s["exec_ms"], 3)
                        for m, s in rec["segs"].items()}}}
        # a step that answered from the host mirror (collective refused
        # or individually declined) is host_fallback, not unattributed:
        # no member ENTERED late, so the lateness test above can't fire,
        # but the coordinator knows exactly why the collective broke
        host_modes = sorted(m for m, s in rec["segs"].items()
                            if s["mode"] in ("host", "error"))
        if not straggler and (rec.get("mode") == "host" or host_modes):
            info["host_fallback"] = True
            info["culprit"] = rec.get("culprit", "")
            info["evidence"]["host_members"] = [
                f"mesh{m}" for m in host_modes]
        if partial:
            info["evidence"]["segments_partial"] = sorted(
                rec["members"] - set(rec["segs"]))
        trace = tracing.get_trace(rec.get("trace_id", ""))
        if trace is None:
            return
        ATTR.record(ATTR.classify(trace, dur, mesh_info=info))

    def flush_pending(self, max_age_s: float = 5.0) -> int:
        """Finalize pending verdicts whose segments never fully arrived
        — a straggled query at the END of a burst has no later scatter
        reply to carry the missing members' segments, and the contract
        is EVERY over-threshold query gets exactly one verdict.  After
        `max_age_s` the record finalizes from whatever segments exist
        (counted in `pending_partial`; with two or more the straggler
        can still be named).  Called from the tail read surfaces
        (MeshMember.info / Performance_Tail_p) — the operator asking is
        exactly when an owed verdict must stop waiting."""
        cut = time.time() - max_age_s
        with self._lock:
            due = [r for r in self._by_seq.values()
                   if r["pending_ms"] is not None and r["ts"] < cut]
        for rec in due:
            self._finalize(rec)
        return len(due)

    # -- reading -------------------------------------------------------------

    def scoreboard(self, horizon_s: float = 600.0) -> list:
        """Windowed per-member straggler rows: how often each member
        was the slowest leg of a complete step, and by how much."""
        cut = time.time() - horizon_s
        with self._lock:
            rows = [r for r in self._board if r[0] >= cut]
        steps = len(rows)
        members: dict[int, dict] = {}
        for _ts, slowest, margin, execs in rows:
            for m, v in execs.items():
                agg = members.setdefault(m, {
                    "member": f"mesh{m}", "steps": 0, "slowest": 0,
                    "margin_ms_sum": 0.0, "margin_ms_max": 0.0,
                    "exec_ms_sum": 0.0})
                agg["steps"] += 1
                agg["exec_ms_sum"] += v
            agg = members[slowest]
            agg["slowest"] += 1
            agg["margin_ms_sum"] += margin
            agg["margin_ms_max"] = max(agg["margin_ms_max"], margin)
        out = []
        for m in sorted(members):
            a = members[m]
            out.append({
                "member": a["member"], "steps": a["steps"],
                "slowest_count": a["slowest"],
                "slowest_frac": round(a["slowest"] / max(1, steps), 3),
                "mean_margin_ms": round(
                    a["margin_ms_sum"] / max(1, a["slowest"]), 3),
                "max_margin_ms": round(a["margin_ms_max"], 3),
                "mean_exec_ms": round(
                    a["exec_ms_sum"] / max(1, a["steps"]), 3)})
        return out

    def waterfall(self, seq: int | None = None) -> dict | None:
        """One assembled step's per-member timeline (newest complete
        record when `seq` is None) — the artifact/servlet rendering."""
        with self._lock:
            recs = list(self._by_seq.values())
        if seq is not None:
            recs = [r for r in recs if r["seq"] == int(seq)]
        for rec in reversed(recs):
            if rec["segs"] and set(rec["segs"]) >= rec["members"]:
                return {"seq": rec["seq"], "trace_id": rec["trace_id"],
                        "mode": rec["mode"],
                        "dur_ms": round(rec["dur_ms"], 3),
                        "members": [rec["segs"][m]
                                    for m in sorted(rec["segs"])]}
        return None

    def reset(self) -> None:
        with self._lock:
            self._by_seq.clear()
            self._by_trace.clear()
            self._board.clear()
            self.known.clear()
            self.segments_merged = 0


class ConvictionTracker:
    """ROADMAP 1c first slice, read-only (ISSUE 19): a member that is
    the slowest leg of most complete steps for N CONSECUTIVE scoreboard
    windows is *convicted* — one edge-triggered breadcrumb into the
    flight recorder plus the zero-filled
    ``yacy_mesh_straggler_convictions_total{member}`` series.  A single
    slow window (GC pause, one cold step) never convicts; a cleared
    fault breaks the streak and re-arms the edge.  Observation only:
    nothing reads a conviction to steer or shed — that is future work,
    and keeping this slice read-only is what makes it safe to land
    under the game-day soak."""

    def __init__(self):
        self._lock = threading.Lock()
        self.window_s = 30.0      # one evaluation window
        self.windows_needed = 2   # consecutive guilty windows to convict
        self.slowest_frac = 0.6   # guilty: slowest leg of >= this share
        self.min_steps = 3        # ... over at least this many steps
        self.min_margin_ms = 20.0  # ... by a material margin
        self._last_eval = 0.0
        self._streaks: dict[str, int] = {}
        self.totals: dict[str, int] = {}
        self.breadcrumbs: deque = deque(maxlen=64)
        # conviction hook (ISSUE 20d): the coordinator registers a
        # callable(crumb) here; observe() drives it OUTSIDE the tracker
        # lock on every conviction edge so the hook may do wire RPCs
        # (fetch the convicted member's profile snapshot) and attach
        # evidence to the crumb before it rides the flight recorder
        self._on_convicted = None

    def configure(self, cfg) -> None:
        self.window_s = cfg.get_float("tail.convictionWindowS",
                                      self.window_s)
        self.windows_needed = max(1, cfg.get_int(
            "tail.convictionWindows", self.windows_needed))
        self.slowest_frac = cfg.get_float("tail.convictionFrac",
                                          self.slowest_frac)
        self.min_steps = cfg.get_int("tail.convictionMinSteps",
                                     self.min_steps)
        self.min_margin_ms = cfg.get_float("tail.convictionMarginMs",
                                           self.min_margin_ms)

    def observe(self, now: float | None = None) -> list[dict]:
        """One health-tick hook: evaluate at most once per window
        (ticks are faster than windows), judge the last window's
        scoreboard, advance streaks, emit conviction breadcrumbs on the
        streak-reaches-N edge.  Members with no scoreboard rows (no
        mesh, or a member down) contribute nothing — absence of
        evidence never convicts, and it never ACQUITS either: a streak
        only resets when the member shows up in a window and is judged
        not guilty, so an idle window does not launder a straggler."""
        now = time.time() if now is None else now
        with self._lock:
            if now - self._last_eval < self.window_s:
                return []
            self._last_eval = now
        rows = MESH.scoreboard(self.window_s)
        guilty = {r["member"] for r in rows
                  if r["steps"] >= self.min_steps
                  and r["slowest_frac"] >= self.slowest_frac
                  and r["mean_margin_ms"] >= self.min_margin_ms}
        seen = {r["member"] for r in rows}
        convicted = []
        with self._lock:
            for member in seen | set(self._streaks):
                if member in guilty:
                    self._streaks[member] = \
                        self._streaks.get(member, 0) + 1
                    if self._streaks[member] == self.windows_needed:
                        self.totals[member] = \
                            self.totals.get(member, 0) + 1
                        row = next((r for r in rows
                                    if r["member"] == member), {})
                        crumb = {
                            "ts": round(now, 3), "member": member,
                            "windows": self.windows_needed,
                            "window_s": self.window_s,
                            "slowest_frac": row.get("slowest_frac"),
                            "mean_margin_ms": row.get("mean_margin_ms"),
                            "conviction_total": self.totals[member]}
                        self.breadcrumbs.append(crumb)
                        convicted.append(crumb)
                        log.warning("straggler convicted: %s", crumb)
                elif member in seen:
                    # present in the window but not guilty: the streak
                    # breaks.  Absent members keep theirs — no evidence
                    # either way.
                    self._streaks.pop(member, None)
        hook = self._on_convicted
        if hook is not None:
            for crumb in convicted:
                try:
                    hook(crumb)
                except Exception:   # lint: broad-except-ok(a failing
                    # evidence fetch must never break the conviction
                    # edge itself — the crumb still records)
                    log.exception("conviction hook failed: %s",
                                  crumb.get("member"))
        return convicted

    def set_conviction_hook(self, fn) -> None:
        """Register the coordinator's conviction-edge callback (ISSUE
        20d): called with each fresh conviction crumb, outside the
        tracker lock, before health embeds the crumb in an incident —
        the hook may mutate the crumb (attach the member's profile)."""
        self._on_convicted = fn

    def known_members(self) -> list[str]:
        """The zero-fill domain: every member the timeline ever
        scattered to, plus anyone already convicted."""
        with self._lock:
            out = set(self.totals)
        out.update(f"mesh{m}" for m in sorted(MESH.known))
        return sorted(out)

    def conviction_totals(self) -> dict:
        """member -> convictions, zero-filled over known members."""
        out = {m: 0 for m in self.known_members()}
        with self._lock:
            out.update(self.totals)
        return out

    def recent(self, n: int = 20) -> list[dict]:
        with self._lock:
            return list(self.breadcrumbs)[-max(0, n):]

    def reset(self) -> None:
        with self._lock:
            self._streaks.clear()
            self.totals.clear()
            self.breadcrumbs.clear()
            self._last_eval = 0.0
            self._on_convicted = None


# -- process-global singletons (the histogram-registry model) ----------------

ATTR = TailAttributor()
MESH = MeshTimeline()
CONVICTIONS = ConvictionTracker()


def stamp_wave(items: list, kernel: str, max_batch: int,
               first_use: bool, issue_ms: float,
               extra: dict | None = None) -> dict:
    """Build ONE dispatch wave's timeline stamp and attach it to every
    item — the shared builder both batchers call (devstore
    `_stamp_wave`, meshstore `_dispatch`), so wave evidence cannot
    diverge between them.  The per-item pre-issue wait is MEASURED by
    the batcher, not here: `queue_ms`, the one stamp the
    `batcher.queue` family records too (a batcher that has not taken
    it yet, the mesh's, is measured submit -> now).  `q_depth` comes
    from the submit path; `extra` is the store's tier/deferral
    snapshot."""
    now = time.perf_counter()
    for it in items:
        if "queue_ms" not in it and "t_submit" in it:
            it["queue_ms"] = (now - it["t_submit"]) * 1000.0
    waits = [it["queue_ms"] for it in items if "queue_ms" in it]
    wave = {"ts": round(time.time(), 3), "kernel": kernel,
            "n": len(items),
            "occ": round(len(items) / max(1, max_batch), 3),
            "qdepth": max((it.get("q_depth", 0) for it in items),
                          default=0),
            "queue_wait_ms": round(max(waits, default=0.0), 3),
            "issue_ms": round(issue_ms, 3),
            "compile": bool(first_use),
            **(extra or {})}
    for it in items:
        it["wave"] = wave
    ATTR.note_wave(wave)
    return wave


def note_lock_wait(name: str, t0: float) -> None:
    """Called as the FIRST statement inside a `with lock:` body with a
    perf_counter taken just before the `with`: the elapsed wall IS the
    acquisition wait.  Emits the lock-wait marker span (a real measured
    wall) when contended and a trace is active; the uncontended cost is
    one perf_counter read."""
    if not _enabled:
        return
    wait_ms = (time.perf_counter() - t0) * 1000.0
    if wait_ms >= LOCK_WAIT_MIN_MS and tracing.current() is not None:
        tracing.emit(MARKER_LOCK_WAIT, wait_ms, lock=name)


def _root_hook(trace_id: str, name: str, dur_ms: float) -> None:
    ATTR.note_root(trace_id, name, dur_ms)


tracing.add_root_hook(_root_hook)


# module-level conveniences (the surfaces health/monitoring import)

def windowed_causes(horizon_s: float = 180.0) -> dict:
    return ATTR.windowed_causes(horizon_s)


def cause_totals() -> dict:
    return dict(ATTR.counters()["causes"])


def straggler_totals() -> dict:
    return dict(ATTR.counters()["stragglers"])


def top_cause(horizon_s: float = 180.0) -> str:
    return ATTR.top_cause(horizon_s)


def verdicts(n: int = 50) -> list:
    return ATTR.verdicts(n)


def scoreboard(horizon_s: float = 600.0) -> list:
    return MESH.scoreboard(horizon_s)


def conviction_totals() -> dict:
    return CONVICTIONS.conviction_totals()


def conviction_breadcrumbs(n: int = 20) -> list:
    return CONVICTIONS.recent(n)


def reset() -> None:
    """Test isolation: drop verdicts, waves and mesh records."""
    ATTR.reset()
    MESH.reset()
    CONVICTIONS.reset()
