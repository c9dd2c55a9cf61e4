"""Node health engine — rules, SLO burn rates, and a flight recorder.

The survey's coordinator-free P2P premise means no central control plane
ever notices a sick peer: each node must watch itself (SURVEY §1; the
reference's PerformanceQueues_p/PerformanceMemory_p pages are the
Java-era, human-polled version).  PRs 2–3 built the raw signals — trace
spine, `/metrics` counters, batcher cause buckets, result-cache and
round-trip counters — but nothing CONSUMED them: a degrading node looked
healthy until a human loaded a servlet.  This module is the consumer
(ISSUE 4 tentpole):

- **Declarative rules** evaluated by a switchboard busy-thread tick.
  Each rule reads only series that exist on the `/metrics` exposition
  (hygiene-tested: a rule referencing a dead series fails the build)
  and yields ``ok | warn | critical`` with a human-readable cause and
  the evidence values that justify it.
- **SLO burn rates.** The serving objective (p95 ≤ X ms, i.e. ≤ budget%
  of requests over X) is judged over a FAST window (the newest histogram
  rotation) and a SLOW window (all retained rotations): paging only when
  both burn — the standard multiwindow discipline that ignores blips but
  catches real burns fast ("Repeatability Corner Cases in Document
  Ranking": detection must compare distributions, not single samples).
- **Flight recorder.** Every tick appends the parsed `/metrics` sample
  set to a bounded ring; when any rule ENTERS ``critical`` (edge, rate
  limited) the ring is dumped as a JSONL incident file — snapshots,
  firing rules, histogram exemplar trace ids, and recent traces — so a
  postmortem never depends on someone having been watching.

The engine deliberately evaluates rules against the same exposition
pipeline the `/metrics` endpoint serves, rendered WITHOUT the
per-bucket histogram samples (no rule reads buckets, and ~100 bucket
lines per family would dominate the tick's cost): every counter, gauge
and histogram `_sum`/`_count` a rule can reference carries exactly the
value a concurrent scrape would see.
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable

from . import faultinject, histogram, tracing

OK, WARN, CRITICAL = "ok", "warn", "critical"
_SEVERITY = {OK: 0, WARN: 1, CRITICAL: 2}

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*(?:\{[^}]*\})?)\s+(-?[0-9.eE+-]+)"
    r"(?:\s+#.*)?$")


def parse_exposition(text: str) -> dict:
    """Prometheus text -> {'family{labels}': value}.  Keys are the exact
    sample prefixes the exposition rendered (exemplar suffixes
    stripped), so rule series references are checked against reality."""
    out: dict = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if m:
            out[m.group(1)] = float(m.group(2))
    return out


@dataclass
class RuleState:
    state: str = OK
    cause: str = ""
    since: float = 0.0
    evidence: dict = field(default_factory=dict)


@dataclass(frozen=True)
class Rule:
    """One detector: `series` lists every exposition sample the
    evaluator reads (the hygiene contract), `evaluate` maps the
    snapshot history to (state, cause, evidence)."""

    name: str
    description: str
    series: tuple
    evaluate: Callable


class RuleCtx:
    """What a rule may look at: the snapshot history (newest last), the
    windowed histograms, and the fleet digest table (ISSUE 5 — the
    fleet_* rules judge the MESH, not just this node)."""

    def __init__(self, history, trend_ticks: int, fleet=None):
        self._hist = history
        self.trend_ticks = trend_ticks
        self.fleet = fleet

    def value(self, key: str, default: float = 0.0) -> float:
        if not self._hist:
            return default
        return self._hist[-1][1].get(key, default)

    def ago(self, key: str, n: int, default: float = 0.0) -> float:
        """Value n ticks back (clamped to the oldest retained)."""
        if not self._hist:
            return default
        i = max(0, len(self._hist) - 1 - n)
        return self._hist[i][1].get(key, default)

    def delta(self, key: str, n: int | None = None) -> float:
        n = self.trend_ticks if n is None else n
        return self.value(key) - self.ago(key, n)

    def ticks(self) -> int:
        return len(self._hist)

    @staticmethod
    def hist(name: str):
        return histogram.get(name)


# ---------------------------------------------------------------------------
# the rule set
# ---------------------------------------------------------------------------

def build_rules(cfg) -> list:
    """The node's detectors.  Thresholds read config once at build time
    (the engine is rebuilt on config edits via `Switchboard` restart —
    the reference's model for performance knobs)."""
    g = cfg.get_float
    gi = cfg.get_int
    slo_ms = g("health.sloServingP95Ms", 250.0)
    budget = max(1e-6, g("health.sloBudgetPct", 5.0) / 100.0)
    min_qps = g("health.sloMinQps", 1.0)
    fast_crit = g("health.sloFastBurnCritical", 6.0)
    slow_crit = g("health.sloSlowBurnCritical", 3.0)
    stall_ticks = gi("health.stallRecoveryTicks", 3)
    backlog_warn = gi("health.backlogWarnDepth", 4)
    backlog_crit = gi("health.backlogCriticalDepth", 16)
    drops_crit = gi("health.logDropsCritical", 100)
    min_act = gi("health.cacheMinActivity", 50)

    def slo_serving(ctx: RuleCtx):
        h = ctx.hist("servlet.serving")
        # fast = the current slot + the last closed one: the current
        # slot alone is near-empty right after each rotation and would
        # flap the qps floor mid-burn
        frac_fast, n_fast = h.fraction_over(slo_ms, last=2)
        frac_slow, n_slow = h.fraction_over(slo_ms)
        qps_fast = n_fast / h.window_seconds(2)
        ev = {"slo_ms": slo_ms, "qps_fast": round(qps_fast, 3),
              "frac_over_fast": round(frac_fast, 4),
              "frac_over_slow": round(frac_slow, 4),
              "requests_windowed": n_slow}
        if qps_fast < min_qps:
            return OK, "below SLO traffic floor", ev
        fast_burn = frac_fast / budget
        slow_burn = frac_slow / budget
        ev["fast_burn"] = round(fast_burn, 2)
        ev["slow_burn"] = round(slow_burn, 2)
        if fast_burn >= fast_crit and slow_burn >= slow_crit:
            return CRITICAL, (
                f"serving SLO burning {fast_burn:.1f}x budget (fast) / "
                f"{slow_burn:.1f}x (slow): p95 objective {slo_ms}ms"), ev
        if fast_burn >= 1.0 and slow_burn >= 1.0:
            return WARN, (
                f"serving error budget burning at {slow_burn:.1f}x "
                f"sustainable rate"), ev
        return OK, "within SLO", ev

    _hits = 'yacy_device_serving_total{counter="rank_cache_hits"}'
    _served = 'yacy_device_serving_total{counter="queries_served"}'
    _stale = 'yacy_device_serving_total{counter="rank_cache_stale"}'
    _epoch = "yacy_device_arena_epoch"
    _stallkey = 'yacy_batch_timeouts_total{cause="worker_stall"}'
    _qin = 'yacy_batcher_queue_depth{queue="incoming"}'
    _qfl = 'yacy_batcher_queue_depth{queue="inflight"}'
    _drops = "yacy_log_dropped_records_total"
    _frontier = 'yacy_crawler_queue_depth{stack="local"}'
    _fetches = "yacy_crawler_fetch_ms_count"

    def cache_collapse(ctx: RuleCtx):
        dq = ctx.delta(_served)
        dh = ctx.delta(_hits)
        tot_q = ctx.value(_served)
        tot_h = ctx.value(_hits)
        longterm = tot_h / tot_q if tot_q > 0 else 0.0
        recent = dh / dq if dq > 0 else 0.0
        ev = {"recent_hit_ratio": round(recent, 4),
              "longterm_hit_ratio": round(longterm, 4),
              "queries_in_window": int(dq)}
        if dq < min_act or longterm < 0.2:
            return OK, "cache not load-bearing / low activity", ev
        if recent < 0.1 * longterm:
            return CRITICAL, (
                f"result-cache hit ratio collapsed: {recent:.0%} recent "
                f"vs {longterm:.0%} lifetime"), ev
        if recent < 0.25 * longterm:
            return WARN, (
                f"result-cache hit ratio degrading: {recent:.0%} recent "
                f"vs {longterm:.0%} lifetime"), ev
        return OK, "cache hit ratio steady", ev

    def stale_spike(ctx: RuleCtx):
        dq = ctx.delta(_served)
        ds = ctx.delta(_stale)
        de = ctx.delta(_epoch)
        ratio = ds / dq if dq > 0 else 0.0
        ev = {"stale_in_window": int(ds), "epoch_moves": int(de),
              "stale_ratio": round(ratio, 4),
              "queries_in_window": int(dq)}
        if dq < min_act or ratio <= 0.2:
            return OK, "stale rate nominal", ev
        if de > 0:
            return WARN, (
                f"stale spike ({ratio:.0%}) during arena-epoch churn "
                f"({int(de)} moves) — expected invalidation storm"), ev
        return CRITICAL, (
            f"stale rate {ratio:.0%} with NO epoch movement — "
            f"unexplained cache invalidation"), ev

    def backlog(ctx: RuleCtx):
        depth = ctx.value(_qin) + ctx.value(_qfl)
        before = (ctx.ago(_qin, ctx.trend_ticks)
                  + ctx.ago(_qfl, ctx.trend_ticks))
        ev = {"depth": int(depth), "depth_before": int(before),
              "incoming": int(ctx.value(_qin)),
              "inflight": int(ctx.value(_qfl))}
        growing = depth > before
        if depth >= backlog_crit and growing:
            return CRITICAL, (
                f"batcher backlog {int(depth)} and growing "
                f"(was {int(before)})"), ev
        if depth >= backlog_warn and growing:
            return WARN, (
                f"batcher queues growing: {int(before)} -> "
                f"{int(depth)}"), ev
        return OK, "queues draining", ev

    def worker_stall(ctx: RuleCtx):
        cur = ctx.value(_stallkey)
        recent = cur - ctx.ago(_stallkey, stall_ticks)
        ev = {"worker_stall_total": int(cur),
              "new_in_window": int(recent)}
        if recent > 0:
            return CRITICAL, (
                f"{int(recent)} worker_stall timeout(s) in the last "
                f"{stall_ticks} ticks — a kernel call is wedged"), ev
        return OK, "no recent stalls", ev

    def log_drops(ctx: RuleCtx):
        d = ctx.delta(_drops)
        ev = {"dropped_in_window": int(d),
              "dropped_total": int(ctx.value(_drops))}
        if d >= drops_crit:
            return CRITICAL, (
                f"{int(d)} log records dropped in the window — the "
                f"async log writer cannot keep up"), ev
        if d > 0:
            return WARN, f"{int(d)} log records dropped in the window", ev
        return OK, "no log drops", ev

    # -- fleet rules (ISSUE 5): the mesh view over gossiped digests ----------

    fleet_min_qps = g("health.fleetSloMinQps", 1.0)
    outlier_factor = g("health.fleetOutlierFactor", 3.0)
    outlier_min_mesh = gi("health.fleetOutlierMinSamples", 50)
    outlier_min_peer = gi("health.fleetOutlierMinPeerSamples", 20)

    def fleet_slo(ctx: RuleCtx):
        fl = ctx.fleet
        peers = fl.fresh() if fl is not None else []
        if not peers:
            return OK, "no fleet peers gossiping", {"peers": 0}
        counts = fl.merged_counts("servlet.serving")
        total = sum(counts)
        window_s = histogram.WINDOWS * histogram.ROTATE_EVERY_S
        qps = total / window_s
        frac = histogram.fraction_over_counts(counts, slo_ms)
        ev = {"peers": len(peers), "mesh_requests": total,
              "mesh_qps": round(qps, 3), "frac_over": round(frac, 4),
              "slo_ms": slo_ms,
              "mesh_p95_ms": round(
                  histogram.percentile_from_counts(counts, 0.95), 1)}
        if qps < fleet_min_qps:
            return OK, "below mesh SLO traffic floor", ev
        burn = frac / budget
        ev["burn"] = round(burn, 2)
        if burn >= slow_crit:
            return CRITICAL, (
                f"mesh serving SLO burning {burn:.1f}x budget across "
                f"{len(peers) + 1} nodes (p95 objective {slo_ms}ms)"), ev
        if burn >= 1.0:
            return WARN, (f"mesh error budget burning at {burn:.1f}x "
                          f"sustainable rate"), ev
        return OK, "mesh within SLO", ev

    def fleet_outlier(ctx: RuleCtx):
        fl = ctx.fleet
        peers = fl.fresh() if fl is not None else []
        if not peers:
            return OK, "no fleet peers gossiping", {"peers": 0}
        merged = fl.merged_counts("servlet.serving")
        total = sum(merged)
        ev = {"peers": len(peers), "mesh_requests": total}
        if total < outlier_min_mesh:
            return OK, "insufficient mesh traffic", ev
        mesh_p95 = histogram.percentile_from_counts(merged, 0.95)
        ev["mesh_p95_ms"] = round(mesh_p95, 2)
        rows = [(fl.my_hash, fl.local_counts("servlet.serving"))] \
            if fl.my_hash else []
        rows += [(e["peer"], e["hist"].get("servlet.serving"))
                 for e in peers]
        worst = None
        for phash, counts in rows:
            if not counts or sum(counts) < outlier_min_peer:
                continue        # absent/thin family: no verdict, not zero
            # leave-one-out baseline: judge the peer against the REST of
            # the mesh, not a merged p95 its own samples already drag —
            # a high-traffic outlier would otherwise mask itself (its
            # samples set the merged tail, so local/merged stays ~1x)
            rest = [max(0, m - c) for m, c in zip(merged, counts)]
            if sum(rest) < outlier_min_peer:
                continue        # no baseline to judge against
            rest_p95 = histogram.percentile_from_counts(rest, 0.95)
            p95 = histogram.percentile_from_counts(counts, 0.95)
            if p95 > outlier_factor * rest_p95 \
                    and (worst is None or p95 > worst[1]):
                worst = (phash, p95, rest_p95)
        if worst is not None:
            ev["outlier_peer"] = worst[0]
            ev["outlier_p95_ms"] = round(worst[1], 2)
            ev["rest_p95_ms"] = round(worst[2], 2)
            return CRITICAL, (
                f"peer {worst[0]} drags the mesh tail: local p95 "
                f"{worst[1]:.0f}ms vs rest-of-mesh p95 {worst[2]:.0f}ms "
                f"(> {outlier_factor:g}x)"), ev
        return OK, "no peer outlier", ev

    def fleet_critical(ctx: RuleCtx):
        fl = ctx.fleet
        peers = fl.fresh() if fl is not None else []
        crit = sorted(e["peer"] for e in peers if e.get("health") == 2)
        stalls = sorted(e["peer"] for e in peers
                        if e.get("rules", {}).get("worker_stall") == 2)
        ev = {"peers": len(peers), "critical_peers": len(crit),
              "worker_stall_peers": len(stalls),
              "names": ",".join(sorted(set(crit + stalls))[:8])}
        if not peers:
            return OK, "no fleet peers gossiping", ev
        if stalls:
            return CRITICAL, (
                f"{len(stalls)} peer(s) report a wedged kernel "
                f"(worker_stall): {ev['names']}"), ev
        if len(crit) * 2 >= len(peers):
            return CRITICAL, (f"{len(crit)}/{len(peers)} fleet peers "
                              f"critical: {ev['names']}"), ev
        if crit:
            return WARN, (f"{len(crit)} fleet peer(s) critical: "
                          f"{ev['names']}"), ev
        return OK, "fleet peers healthy", ev

    # -- crash-consistency / device-loss rules (ISSUE 10) --------------------

    _corr_keys = tuple(
        f'yacy_storage_corruption_total{{kind="{k}",action="{a}"}}'
        for k, a in (("run", "quarantined"), ("run", "error"),
                     ("segment", "error"),
                     ("segment", "served_degraded"),
                     ("journal", "error")))
    _lost = "yacy_device_lost"
    _recov = 'yacy_device_loss_total{event="recoveries"}'
    _losses = 'yacy_device_loss_total{event="losses"}'

    def storage_corruption(ctx: RuleCtx):
        total = sum(ctx.value(k) for k in _corr_keys)
        # counters are process-local: on the FIRST tick everything on
        # record happened since start — a delta would read 0 and the
        # critical edge (and its incident) would never fire for
        # corruption detected before the engine's first evaluation
        new = total if ctx.ticks() <= 1 \
            else sum(ctx.delta(k) for k in _corr_keys)
        ev = {"new_in_window": int(new), "total": int(total),
              "by_kind": {k.split('kind="')[1].split('"')[0]
                          + "/" + k.split('action="')[1].split('"')[0]:
                          int(ctx.value(k)) for k in _corr_keys
                          if ctx.value(k)}}
        if new > 0:
            # the critical EDGE dumps a flight-recorder incident — the
            # corruption's evidence (which kind, which action) is in the
            # record even if the operator looks hours later
            return CRITICAL, (
                f"{int(new)} storage corruption event(s) detected in "
                f"the window (checksum mismatch / quarantine)"), ev
        if total > 0:
            return OK, (f"no new corruption ({int(total)} historical "
                        f"event(s) on record)"), ev
        return OK, "no storage corruption detected", ev

    def device_loss(ctx: RuleCtx):
        lost = ctx.value(_lost)
        recovered = ctx.delta(_recov)
        ev = {"device_lost": int(lost),
              "losses_total": int(ctx.value(_losses)),
              "recoveries_total": int(ctx.value(_recov)),
              "recovered_in_window": int(recovered)}
        if lost >= 1:
            return CRITICAL, (
                "device LOST: queries served via counted host fallback "
                "(X-YaCy-Degraded: device-loss); background rebuild "
                "re-uploading the hot tier"), ev
        if recovered > 0:
            return WARN, (f"device serving resumed after rebuild "
                          f"({int(recovered)} recovery(ies) in the "
                          f"window)"), ev
        return OK, "device serving", ev

    # -- crawl-to-searchable SLO (ISSUE 13a) ---------------------------------

    ingest_p95_ms = g("health.ingestSearchableP95Ms", 2000.0)
    ingest_budget = max(1e-6,
                        g("health.ingestSloBudgetPct", 5.0) / 100.0)
    ingest_min_docs = gi("health.ingestSloMinDocs", 10)

    def ingest_slo(ctx: RuleCtx):
        """Freshness burn rate: the fraction of documents whose
        crawl-to-searchable wall exceeded the objective, judged with
        the same fast/slow multiwindow discipline as slo_serving_p95.
        Backpressure needs no separate term — a writer's blocked wall
        lands inside its documents' own searchable latency by
        construction (rwi.wait_capacity runs before the store)."""
        h = ctx.hist("ingest.searchable")
        frac_fast, n_fast = h.fraction_over(ingest_p95_ms, last=2)
        frac_slow, n_slow = h.fraction_over(ingest_p95_ms)
        bp = ctx.hist("ingest.backpressure")
        _bpf, bp_n = bp.fraction_over(0.0)
        ev = {"objective_ms": ingest_p95_ms,
              "docs_fast": n_fast, "docs_windowed": n_slow,
              "frac_over_fast": round(frac_fast, 4),
              "frac_over_slow": round(frac_slow, 4),
              "backpressure_waits_windowed": bp_n}
        if n_fast < ingest_min_docs:
            return OK, "below ingest traffic floor", ev
        fast_burn = frac_fast / ingest_budget
        slow_burn = frac_slow / ingest_budget
        ev["fast_burn"] = round(fast_burn, 2)
        ev["slow_burn"] = round(slow_burn, 2)
        if fast_burn >= fast_crit and slow_burn >= slow_crit:
            return CRITICAL, (
                f"crawl-to-searchable SLO burning {fast_burn:.1f}x "
                f"budget (fast) / {slow_burn:.1f}x (slow): p95 "
                f"objective {ingest_p95_ms}ms — the write path cannot "
                f"keep the index fresh"), ev
        if fast_burn >= 1.0 and slow_burn >= 1.0:
            return WARN, (
                f"crawl-to-searchable budget burning at "
                f"{slow_burn:.1f}x sustainable rate"), ev
        return OK, "index freshness within SLO", ev

    def frontier_starvation(ctx: RuleCtx):
        def starving(i: int) -> bool:
            # at tick `i` ago: frontier empty while that tick still
            # fetched — the frontier isn't keeping the fetcher fed
            return (ctx.ago(_frontier, i) == 0
                    and ctx.ago(_fetches, i) - ctx.ago(_fetches, i + 1)
                    > 0)
        ev = {"frontier_local": int(ctx.value(_frontier)),
              "fetches_in_window": int(ctx.delta(_fetches))}
        # TWO consecutive starving ticks: a finished crawl legitimately
        # drains the frontier to 0 while its last fetches land, but its
        # fetching stops within one tick — only a crawl that KEEPS
        # fetching against an empty frontier is starving
        if ctx.ticks() >= 3 and starving(0) and starving(1):
            return WARN, (
                "crawler kept fetching across two ticks with an empty "
                "local frontier — crawl starving"), ev
        return OK, "frontier fed or crawl idle", ev

    return [
        Rule("slo_serving_p95",
             f"servlet serving p95 <= {slo_ms}ms at >= {min_qps} qps "
             "(fast <=60s / slow ~3min burn-rate windows)",
             ("yacy_servlet_serving_ms_count",), slo_serving),
        Rule("rank_cache_collapse",
             "top-k result-cache hit ratio collapse vs lifetime",
             (_hits, _served), cache_collapse),
        Rule("stale_rate_spike",
             "cache stale-rate spike judged against arena-epoch churn",
             (_stale, _served, _epoch), stale_spike),
        Rule("batcher_backlog",
             "batcher incoming/in-flight queue growth trend",
             (_qin, _qfl), backlog),
        Rule("worker_stall",
             "batcher worker_stall timeouts (wedged kernel call)",
             (_stallkey,), worker_stall),
        Rule("log_drops",
             "async logging queue drops",
             (_drops,), log_drops),
        Rule("crawler_frontier_starvation",
             "active crawl with an empty local frontier",
             (_frontier, _fetches), frontier_starvation),
        Rule("ingest_slo_searchable",
             f"crawl-to-searchable p95 <= {ingest_p95_ms}ms over "
             f">= {ingest_min_docs} docs/window (fast/slow burn-rate "
             "windows; backpressure walls land inside the latency)",
             ("yacy_ingest_searchable_ms_count",
              "yacy_ingest_backpressure_ms_count"), ingest_slo),
        Rule("storage_corruption",
             "checksum-detected storage corruption (runs / segments / "
             "journals) — critical on any new event; the edge dumps a "
             "flight-recorder incident",
             _corr_keys, storage_corruption),
        Rule("device_loss",
             "device declared lost after a transfer-failure streak "
             "(host fallback serving, background rebuild)",
             (_lost, _recov, _losses), device_loss),
        Rule("fleet_slo_serving",
             f"mesh-wide serving SLO burn rate over MERGED peer digests "
             f"(p95 objective {slo_ms}ms; coordinator-free federation)",
             ("yacy_fleet_peers",
              'yacy_fleet_merged_latency_ms{family="servlet.serving",'
              'quantile="p95"}'), fleet_slo),
        Rule("fleet_peer_outlier",
             f"peer whose local serving p95 exceeds the merged mesh p95 "
             f"by > {outlier_factor:g}x (names the dragging seed)",
             ("yacy_fleet_peers",
              'yacy_fleet_merged_latency_ms{family="servlet.serving",'
              'quantile="p95"}'), fleet_outlier),
        Rule("fleet_critical_peers",
             "fleet peers whose digests report critical health or a "
             "wedged kernel (worker_stall)",
             ("yacy_fleet_peers", "yacy_fleet_peer_reported_critical"),
             fleet_critical),
    ]


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class HealthEngine:
    """Owns the rule set, the snapshot ring, and the incident dumper.
    Constructed cheaply at switchboard init; all work happens in
    `tick()` (driven by the `15_health` busy thread, or directly by
    tests/operators)."""

    def __init__(self, sb, incidents_dir: str | None = None):
        self.sb = sb
        cfg = sb.config
        self.rules = build_rules(cfg)
        self.trend_ticks = cfg.get_int("health.trendTicks", 6)
        self.cooldown_s = cfg.get_float("health.incidentCooldownS", 300.0)
        self.snapshots: deque = deque(
            maxlen=cfg.get_int("health.flightSnapshots", 240))
        self.snapshot_dump_max = cfg.get_int(
            "health.incidentSnapshotMax", 60)
        # DATA/HEALTH retention cap (ISSUE 5 satellite): incident writes
        # are rate-limited but the directory grew unboundedly — keep the
        # newest N files, delete older on every write
        self.incident_keep = cfg.get_int("health.incidentKeepFiles", 50)
        self.states: dict[str, RuleState] = {
            r.name: RuleState(since=time.time()) for r in self.rules}
        self.incidents: deque = deque(maxlen=32)
        self.incident_count = 0          # monotonic (the deque is a ring)
        self.tick_count = 0
        self.last_tick = 0.0
        self._last_incident_ts = 0.0
        self._lock = threading.Lock()
        self._dir = incidents_dir
        if incidents_dir:
            os.makedirs(incidents_dir, exist_ok=True)

    # -- evaluation ----------------------------------------------------------

    def _exposition(self) -> str:
        # bucket-free: no rule reads per-bucket samples, and rendering
        # ~100 bucket lines per family each tick would dominate the
        # tick's cost (the <2% --health-overhead budget)
        from ..server.servlets.monitoring import prometheus_text
        return prometheus_text(self.sb, include_buckets=False)

    def tick(self, now: float | None = None) -> str:
        """One evaluation pass: snapshot `/metrics`, evaluate every
        rule, drive the actuator engine on the fresh rule states, dump
        an incident on an ok/warn->critical edge (rate limited).
        Returns the overall state."""
        # the evaluation + actuator wall (`runtime.health_tick`): the
        # exposition render and the rules run on this thread, under the
        # interpreter lock the serving threads wait for
        with tracing.timed("runtime.health_tick"):
            return self._tick(now)

    def _tick(self, now: float | None) -> str:
        now = time.time() if now is None else now
        tracing.flush_gc()
        # idle histogram families must not freeze their windows (a
        # sticky SLO verdict after traffic stops): the tick drives
        # rotation for whatever recording's lazy rotation missed
        histogram.rotate_due()
        # straggler conviction pass (ISSUE 19 / ROADMAP 1c, read-only):
        # self-limits to one evaluation per conviction window, no-op on
        # nodes without a mesh timeline (empty scoreboard)
        from . import tailattr
        tailattr.CONVICTIONS.observe(now)
        # bucket-free exposition: the ring (and incident dumps) keep the
        # _sum/_count + counter/gauge granularity
        snap = parse_exposition(self._exposition())
        with self._lock:
            self.snapshots.append((now, snap))
            ctx = RuleCtx(list(self.snapshots), self.trend_ticks,
                          fleet=getattr(self.sb, "fleet", None))
            entered_critical = []
            for rule in self.rules:
                try:
                    state, cause, ev = rule.evaluate(ctx)
                except Exception as e:  # a broken rule must be VISIBLE
                    state, cause, ev = WARN, f"rule error: {e!r}", {}
                st = self.states[rule.name]
                if state != st.state:
                    if state == CRITICAL:
                        entered_critical.append(rule.name)
                    st.since = now
                st.state, st.cause, st.evidence = state, cause, ev
            self.tick_count += 1
            self.last_tick = now
            do_dump = entered_critical and \
                now - self._last_incident_ts >= self.cooldown_s
            if do_dump:
                self._last_incident_ts = now
        # actuators run on the JUST-evaluated rule states, outside the
        # engine lock (they take config/batcher locks of their own) and
        # BEFORE the incident dump — the incident that pages on a burn
        # must already name the ladder step the burn triggered (ISSUE 9)
        act = getattr(self.sb, "actuators", None)
        if act is not None:
            try:
                act.tick(now)
            except Exception:
                import logging
                logging.getLogger("health").warning(
                    "actuator tick failed", exc_info=True)
        if entered_critical:
            # whitebox deep capture (ISSUE 20c): the ok->critical edge
            # arms one bounded high-rate profiler window — the NEXT
            # incident (or servlet read) embeds what the process was
            # doing while the rule burned.  Rate-limited inside.
            from . import profiling
            profiling.trigger(f"health.{entered_critical[0]}")
        if do_dump:
            with self._lock:
                self._dump_incident_locked(now, entered_critical)
        return self.overall()

    def tick_job(self) -> bool:
        """BusyThread adapter: busy pacing while the node is unhealthy."""
        return self.tick() != OK

    def overall(self) -> str:
        worst = max((_SEVERITY[s.state] for s in self.states.values()),
                    default=0)
        return [OK, WARN, CRITICAL][worst]

    def status_value(self) -> int:
        """0 ok / 1 warn / 2 critical — the `health_status` gauge."""
        return _SEVERITY[self.overall()]

    def rule_table(self) -> list:
        """(name, description, state, cause, since, evidence) rows for
        the servlet and the exposition."""
        return [(r.name, r.description, self.states[r.name])
                for r in self.rules]

    # -- hygiene -------------------------------------------------------------

    def undefined_series(self) -> list:
        """Rule series references that do NOT resolve against the live
        exposition — must be empty (the no-dead-rules build gate)."""
        keys = set(parse_exposition(self._exposition()))
        missing = []
        for r in self.rules:
            for s in r.series:
                if s not in keys:
                    missing.append(f"{r.name}: {s}")
        return missing

    # -- flight recorder -----------------------------------------------------

    def _dump_incident_locked(self, now: float, entered: list) -> None:
        """Serialize the ring + firing rules + exemplars + recent traces
        as one JSONL incident (called under `_lock`, edge-triggered and
        rate-limited by the caller)."""
        # post-hoc join keys (ISSUE 19): a monotonic per-process
        # incident_seq (wall clocks skew across mesh processes; the
        # verdict engine orders by (pid, seq)) and the armed-fault
        # snapshot AT DUMP TIME — the incident names the injections
        # that were live when it fired, which is what lets a game-day
        # verdict match this incident to its scheduled fault
        seq = self.incident_count + 1
        armed = faultinject.snapshot()
        lines = [json.dumps({
            "kind": "incident", "ts": round(now, 3),
            "incident_seq": seq, "pid": os.getpid(),
            "armed_faults": armed,
            "entered_critical": entered,
            "rules": [{
                "name": name, "state": st.state, "cause": st.cause,
                "since": round(st.since, 3), "evidence": st.evidence,
            } for name, _d, st in self.rule_table()],
        })]
        snaps = list(self.snapshots)[-self.snapshot_dump_max:]
        for ts, samples in snaps:
            lines.append(json.dumps({
                "kind": "snapshot", "ts": round(ts, 3),
                "series": samples}))
        # tail forensics (ISSUE 15c): when a SERVING SLO rule is what
        # went critical, the incident embeds the windowed cause
        # histogram and the straggler scoreboard — so it reads "p95
        # burn, 71% collective_straggler mesh1" instead of "p95 burn"
        if any(r in ("slo_serving_p95", "fleet_slo_serving")
               for r in entered):
            from . import tailattr
            lines.append(json.dumps({
                "kind": "tail_causes",
                "window": tailattr.windowed_causes(),
                "verdicts": [v.to_json()
                             for v in tailattr.verdicts(10)]}))
            lines.append(json.dumps({
                "kind": "straggler_scoreboard",
                "rows": tailattr.scoreboard()}))
        # straggler convictions (ISSUE 19 / ROADMAP 1c): every recent
        # conviction edge rides the incident like actuator breadcrumbs
        # — the postmortem reads "mesh1 convicted over 2 windows" next
        # to the burn it explains
        from . import tailattr as _ta
        for crumb in _ta.conviction_breadcrumbs():
            lines.append(json.dumps(
                {"kind": "straggler_convicted", **crumb}))
        # whitebox profile (ISSUE 20c): the incident embeds the top
        # folded stacks + per-lock wait/hold table + the last triggered
        # deep capture — the postmortem reads WHAT the process was doing
        # next to the burn, like the cause histogram above reads WHY
        from . import profiling
        lines.append(json.dumps(
            {"kind": "profile", **profiling.report()}))
        # actuator breadcrumbs (ISSUE 9): the incident names every
        # actuation around the edge — which ladder rung, which tuning
        # step, which peers were avoided — so a postmortem reads the
        # defense next to the burn that triggered it
        act = getattr(self.sb, "actuators", None)
        if act is not None:
            for crumb in act.recent_breadcrumbs():
                lines.append(json.dumps({"kind": "actuator", **crumb}))
        for h in histogram.all_histograms():
            for ex in h.snapshot()["exemplars"]:
                if ex is not None:
                    lines.append(json.dumps({
                        "kind": "exemplar", "family": h.name,
                        "trace_id": ex[0], "value_ms": round(ex[1], 3),
                        "ts": round(ex[2], 3)}))
        for t in tracing.traces(20):
            lines.append(json.dumps({"kind": "trace", **t.to_json()}))
        body = "\n".join(lines) + "\n"
        name = f"incident-{int(now)}-{entered[0]}.jsonl"
        path = None
        if self._dir:
            path = os.path.join(self._dir, name)
            try:
                with open(path, "w", encoding="utf-8") as f:
                    f.write(body)
            except OSError:
                path = None   # a full disk must not kill the tick; the
                # in-memory copy below still serves the servlet download
            self._prune_incident_files()
        self.incident_count += 1
        self.incidents.append({
            "name": name, "ts": now, "seq": seq,
            "armed_faults": armed, "rules": list(entered),
            "path": path, "body": body})

    def _prune_incident_files(self) -> None:
        """Enforce the DATA/HEALTH retention cap: newest
        `health.incidentKeepFiles` incident files stay, older ones go
        (oldest-mtime first; name-embedded timestamps break ties)."""
        if not self._dir or self.incident_keep <= 0:
            return
        try:
            names = [f for f in os.listdir(self._dir)
                     if f.startswith("incident-") and f.endswith(".jsonl")]
            names.sort(key=lambda f: (
                os.path.getmtime(os.path.join(self._dir, f)), f))
            for f in names[:-self.incident_keep]:
                os.remove(os.path.join(self._dir, f))
        except OSError:
            return    # retention must never kill the tick; the next
            # successful write retries the prune

    def incident_body(self, name: str) -> str | None:
        """Download surface: by registry name only (never a caller
        path — no traversal)."""
        for inc in self.incidents:
            if inc["name"] == name:
                return inc["body"]
        return None
