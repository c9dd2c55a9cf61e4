"""Self-defending serving — the actuator layer that closes the loop.

Five rounds of observability (roofline → tracing → histograms → health
rules → fleet gossip) built a node that can *diagnose* itself in detail
and *do* nothing about it: the burn-rate rules page, the queues grow,
the sick peer drags every global query, and a human is still the only
actuator.  ROADMAP item 3: at millions of users the rules must defend
the serving SLO themselves.  This module is the decision half of that
loop, with the same declarative discipline as `utils/health.py` rules
(ISSUE 9 tentpole):

- Each :class:`Actuator` pins the exact `/metrics` series it reads, the
  config knob it writes, and an ``evaluate`` that maps the current
  signals to a bounded state change.  A state change emits a
  flight-recorder breadcrumb (dumped inside health incidents) and bumps
  ``yacy_actuator_transitions_total{actuator,dir}`` — every actuation
  is attributable after the fact, and the no-dead-actuators hygiene
  gate (`undefined_series`) fails any actuator referencing a series the
  exposition does not serve.  Knob semantics: ``index.device.*`` is a
  REAL config knob (re-read at switchboard init, so tuning persists a
  restart); ``serving.degradeLevel`` and ``remotesearch.avoidPeers``
  are write-only operator-visible mirrors — the live serving path
  reads the engine (`effective_level()` / `avoided_peers()`), never
  the config, so a restart always comes up at full service with an
  empty avoid set.
- **serving_ladder** — the degradation ladder, driven by the
  ``slo_serving_p95`` burn-rate state: full → skip live snippets →
  skip dense rerank → rank-cache/stale-ok only → shed with a computed
  ``Retry-After``.  One rung DOWN per sustained-burn tick, one rung UP
  only after ``actuator.recoverTicks`` consecutive healthy ticks
  (hysteresis: a flapping rule must not oscillate the serving mode).
  Every degraded answer stays deterministically ordered: each rung
  serves exactly a prefix of the full pipeline's stages, whose tie
  discipline (score DESC, docid ASC) is already pinned per stage
  (arxiv 1807.05798 — ties that flap across serving modes defeat the
  versioned top-k cache and surface as result churn).
- **batcher_autotune** — adapts the dispatcher count and completer
  depth of the live batcher (`devstore._QueryBatcher` /
  `meshstore._MeshQueryBatcher`) within configured bounds from the same
  queue-depth gauges the backlog rule reads.  Bounded step-per-window:
  at most ±1 per tick, and only on a `recoverTicks`-sustained signal —
  a healthy soak must show ZERO transitions.  The
  floor (1 dispatcher, depth 1) can never deadlock the pipeline.
- **remote_peer_guard** — writes the ``remotesearch.avoidPeers`` knob
  from the fleet table's digest-reported health: peers reporting
  critical (or a leave-one-out serving-p95 outlier) are skipped by the
  scatter until their digests recover, so one sick peer stops dragging
  every global query.

Admission control (the per-client token buckets `server/httpd.py`
consults, layered on `accesstracker.track_access` host accounting)
lives here too: the bucket's refill time is what turns the hard-coded
``Retry-After: 600`` into an honest number.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Callable

# ladder rungs (serving.degradeLevel): each rung serves a PREFIX of the
# full pipeline, so degraded answers are bit-identical in ordering to
# the corresponding non-degraded stage outputs
LEVEL_FULL = 0                  # everything: snippets, rerank, device
LEVEL_NO_LIVE_SNIPPETS = 1      # skip live snippet fetches (cache-local only)
LEVEL_NO_RERANK = 2             # skip the dense rerank stage (sparse order)
LEVEL_CACHE_ONLY = 3            # serve the rank cache (stale-ok); miss = empty
LEVEL_SHED = 4                  # shed search requests with Retry-After

# dense-first candidate generation (ISSUE 11) sheds at rung 1 — ONE
# rung BEFORE the rerank: the ANN probe is the more expensive dense
# stage, and shedding it still serves a full hybrid (sparse + rerank)
# answer.  An alias of the snippet rung, not a new rung: the ladder's
# metric/name surface (LEVEL_NAMES, zero-filled series) is unchanged.
LEVEL_NO_DENSE_FIRST = LEVEL_NO_LIVE_SNIPPETS

LEVEL_NAMES = ("full", "no_live_snippets", "no_rerank", "cache_only",
               "shed")
N_LEVELS = len(LEVEL_NAMES)


class TokenBucketTable:
    """Per-client token buckets for admission control — EXACT under one
    lock (the 32-thread exactness test pins it): with refill disabled,
    precisely ``capacity`` acquires succeed per client no matter the
    thread count.  `acquire` returns the refill-derived ``Retry-After``
    on denial, which is what replaces httpd's hard-coded 600."""

    def __init__(self, capacity: float, refill_per_s: float,
                 max_clients: int = 20_000):
        self.capacity = float(max(1.0, capacity))
        self.refill_per_s = float(max(0.0, refill_per_s))
        self.max_clients = max_clients
        self._lock = threading.Lock()
        # client -> [tokens, last_refill_monotonic]
        self._buckets: dict[str, list] = {}
        self._calls = 0
        self.denied = 0

    def acquire(self, client: str, cost: float = 1.0,
                now: float | None = None) -> tuple[bool, float]:
        """Take `cost` tokens; returns (allowed, retry_after_s) where
        retry_after_s is the time until the bucket refills enough for
        one more request (0.0 when allowed)."""
        now = time.monotonic() if now is None else now
        with self._lock:
            b = self._buckets.get(client)
            if b is None:
                b = self._buckets[client] = [self.capacity, now]
                self._calls += 1
                if len(self._buckets) > self.max_clients:
                    self._prune_locked(now, keep=client)
            tokens, last = b
            tokens = min(self.capacity,
                         tokens + (now - last) * self.refill_per_s)
            if tokens >= cost:
                b[0], b[1] = tokens - cost, now
                return True, 0.0
            b[0], b[1] = tokens, now
            self.denied += 1
            if self.refill_per_s <= 0.0:
                return False, 600.0          # no refill: the legacy cap
            return False, max(1.0, (cost - tokens) / self.refill_per_s)

    def refill_eta(self, client: str, cost: float = 1.0,
                   now: float | None = None) -> float:
        """Time until `client` could pass one request, WITHOUT charging
        the bucket — the honest Retry-After for denials decided by
        other policies (httpd's legacy windowed host count)."""
        now = time.monotonic() if now is None else now
        with self._lock:
            b = self._buckets.get(client)
            if b is None:
                return 1.0
            tokens = min(self.capacity,
                         b[0] + (now - b[1]) * self.refill_per_s)
            if tokens >= cost:
                return 1.0
            if self.refill_per_s <= 0.0:
                return 600.0
            return max(1.0, (cost - tokens) / self.refill_per_s)

    def _prune_locked(self, now: float, keep: str | None = None) -> None:
        """Bound the table: drop refilled-to-capacity buckets (idle
        clients), and if a unique-IP spray keeps every bucket non-full,
        force-evict the FULLEST ones down to 90% of the cap — an
        evicted client returns with a fresh full bucket, so eviction
        can only ever be generous, never a lockout; the 10% slack
        amortizes the scan instead of re-running it per new client.
        `keep` is the caller whose just-created (full) bucket triggered
        the prune: evicting it would orphan the spend acquire() is
        about to write."""
        full = [c for c, (t, last) in self._buckets.items()
                if c != keep
                and t + (now - last) * self.refill_per_s
                >= self.capacity - 1e-9]
        for c in full:
            del self._buckets[c]
        excess = len(self._buckets) - int(self.max_clients * 0.9)
        if excess > 0:
            victims = sorted(
                ((c, b) for c, b in self._buckets.items() if c != keep),
                key=lambda kv: -(kv[1][0]
                                 + (now - kv[1][1]) * self.refill_per_s)
            )[:excess]
            for c, _b in victims:
                del self._buckets[c]

    def __len__(self) -> int:
        with self._lock:
            return len(self._buckets)


@dataclass(frozen=True)
class Actuator:
    """One closed-loop controller: `series` lists every exposition
    sample the evaluator reads (the no-dead-actuators hygiene
    contract), `knob` names the config key it writes, `evaluate` maps
    the engine's current signals to a transition dict or None."""

    name: str
    description: str
    series: tuple
    knob: str
    evaluate: Callable


def build_actuators(cfg) -> list:
    """The three controllers (thresholds read once at build time, like
    `health.build_rules`)."""
    recover_ticks = max(1, cfg.get_int("actuator.recoverTicks", 3))
    max_level = min(LEVEL_SHED, cfg.get_int("actuator.maxDegradeLevel",
                                            LEVEL_SHED))
    disp_min = max(1, cfg.get_int("actuator.dispatcherMin", 2))
    disp_max = max(disp_min, cfg.get_int("actuator.dispatcherMax", 16))
    depth_min = max(1, cfg.get_int("actuator.completerDepthMin", 1))
    depth_max = max(depth_min, cfg.get_int("actuator.completerDepthMax",
                                           4))
    backlog_factor = cfg.get_float("actuator.backlogFactor", 2.0)
    # same thresholds as the fleet_peer_outlier RULE: the actuation must
    # never avoid a peer the diagnostic layer would refuse to judge
    outlier_factor = cfg.get_float("health.fleetOutlierFactor", 3.0)
    outlier_min_mesh = cfg.get_int("health.fleetOutlierMinSamples", 50)
    outlier_min_peer = cfg.get_int("health.fleetOutlierMinPeerSamples",
                                   20)

    def serving_ladder(eng: "ActuatorEngine"):
        st = eng.rule_state("slo_serving_p95")
        old = eng.level
        new = old
        if st == "critical":
            eng._ok_streak = 0
            new = min(max_level, old + 1)
        elif st == "ok":
            eng._ok_streak += 1
            if eng._ok_streak >= recover_ticks and old > 0:
                eng._ok_streak = 0
                new = old - 1
        else:                       # warn (or unknown): hold the rung
            eng._ok_streak = 0
        if new == old:
            return None
        eng.level = new
        eng.sb.config.set("serving.degradeLevel", new)
        return {
            "dir": "down" if new > old else "up",
            "from": LEVEL_NAMES[old], "to": LEVEL_NAMES[new],
            "cause": (f"slo_serving_p95 {st}: ladder "
                      f"{LEVEL_NAMES[old]} -> {LEVEL_NAMES[new]}"),
            "evidence": {"rule_state": st, "level": new,
                         "ok_streak": eng._ok_streak},
        }

    def batcher_autotune(eng: "ActuatorEngine"):
        b = eng._live_batcher()
        if b is None or not hasattr(b, "set_tuning"):
            return None
        tun = b.tuning()
        disp, depth = tun["dispatchers"], tun["completer_depth"]
        qdepth = tun["queue_incoming"] + tun["queue_inflight"]
        dispatches = tun["dispatches"]
        busy = dispatches > eng._last_dispatches
        eng._last_dispatches = dispatches
        # sustained-signal discipline (one sampled instant must never
        # actuate): a backlog streak scales up, an idle streak scales
        # down — both bounded to ±1 per tick inside [min, max].  Idle
        # is judged on incoming work + dispatch progress, NOT the
        # in-flight queue (a just-retired pool thread's sentinel — or a
        # wave completing right now — must not read as load)
        if qdepth > backlog_factor * disp:
            eng._backlog_streak += 1
            eng._idle_streak = 0
        elif tun["queue_incoming"] == 0 and not busy:
            eng._idle_streak += 1
            eng._backlog_streak = 0
        else:
            eng._backlog_streak = 0
            eng._idle_streak = 0
        applied, dir_ = None, None
        if eng._backlog_streak >= recover_ticks:
            eng._backlog_streak = 0
            dir_ = "up"
            # prefer another dispatcher; a batcher whose dispatcher
            # axis is structurally fixed (the mesh runs ONE program at
            # a time) or saturated grows completer depth instead
            if disp < disp_max:
                applied = b.set_tuning(dispatchers=disp + 1,
                                       completer_depth=depth)
            if (applied is None or applied["dispatchers"] == disp) \
                    and depth < depth_max:
                applied = b.set_tuning(completer_depth=depth + 1)
        elif eng._idle_streak >= recover_ticks:
            eng._idle_streak = 0
            dir_ = "down"
            if depth > depth_min:
                applied = b.set_tuning(completer_depth=depth - 1)
            if (applied is None or applied["completer_depth"] == depth) \
                    and disp > disp_min:
                applied = b.set_tuning(dispatchers=disp - 1,
                                       completer_depth=depth)
        # a transition is a REAL state change: a saturated/structurally
        # fixed knob (or a deferred pool retire) emits nothing
        if applied is None or (applied["dispatchers"],
                               applied["completer_depth"]) == (disp,
                                                               depth):
            return None
        new_disp = applied["dispatchers"]
        new_depth = applied["completer_depth"]
        eng.sb.config.set("index.device.dispatchers", new_disp)
        eng.sb.config.set("index.device.completerDepth", new_depth)
        return {
            "dir": dir_,
            "from": f"{disp}x{depth}",
            "to": f"{new_disp}x{new_depth}",
            "cause": (f"batcher queue depth {qdepth} vs {disp} "
                      f"dispatchers: {disp}x{depth} -> "
                      f"{new_disp}x{new_depth}"),
            "evidence": {"queue_depth": qdepth, "dispatchers": new_disp,
                         "completer_depth": new_depth},
        }

    def remote_peer_guard(eng: "ActuatorEngine"):
        fl = getattr(eng.sb, "fleet", None)
        sick = frozenset(fl.sick_peers(outlier_factor,
                                       min_mesh=outlier_min_mesh,
                                       min_peer=outlier_min_peer)) \
            if fl is not None else frozenset()
        old = eng._avoid_peers
        if sick == old:
            return None
        eng._avoid_peers = sick
        eng.sb.config.set("remotesearch.avoidPeers",
                          ",".join(sorted(sick)))
        added, healed = sorted(sick - old), sorted(old - sick)
        return {
            # any NEWLY avoided peer makes this a protective step, even
            # when another peer healed in the same tick (equal-size
            # membership churn must never read as a recovery)
            "dir": "down" if added else "up",
            "from": f"{len(old)} avoided", "to": f"{len(sick)} avoided",
            "cause": ("sick peers avoided: "
                      + (f"+{','.join(added)}" if added else "")
                      + (f" -{','.join(healed)}" if healed else "")),
            "evidence": {"avoided": sorted(sick), "added": added,
                         "healed": healed},
        }

    def device_rebuild(eng: "ActuatorEngine"):
        """Device-loss watchdog (ISSUE 10c): while the device is lost,
        ensure the store's background rebuild loop is actually alive
        (declaration starts it; a died thread restarts here), and emit
        one breadcrumb per loss/recovery EDGE — the incident that pages
        on the loss names the recovery machinery next to it."""
        ds = getattr(eng.sb.index, "devstore", None)
        lost = bool(getattr(ds, "device_lost", False)) \
            if ds is not None else False
        if lost and ds is not None:
            fn = getattr(ds, "start_rebuild", None)
            if fn is not None:
                fn()            # idempotent: no-op while alive
        was = eng._device_lost_seen
        if lost == was:
            return None
        eng._device_lost_seen = lost
        # operator-visible mirror (the live path reads the store flag)
        eng.sb.config.set("index.device.lost", 1 if lost else 0)
        recoveries = getattr(ds, "device_loss_recoveries", 0) \
            if ds is not None else 0
        losses = getattr(ds, "device_losses", 0) if ds is not None else 0
        return {
            "dir": "down" if lost else "up",
            "from": "serving" if lost else "lost",
            "to": "lost" if lost else "serving",
            "cause": ("device lost: host fallback + background rebuild"
                      if lost else
                      f"device serving resumed (recovery "
                      f"#{recoveries})"),
            "evidence": {"losses": losses, "recoveries": recoveries},
        }

    def merge_scheduler(eng: "ActuatorEngine"):
        """Write-path deferral (ISSUE 13c): while the serving SLO
        burns, the ingest scheduler parks compactions and tier
        promotions (the node's two heavy background moves); after
        `recoverTicks` consecutive healthy ticks it catches up —
        running the most aggressive deferred merge ask and resubmitting
        every parked promotion.  Same hysteresis discipline as the
        serving ladder: a flapping rule must not thrash the merge
        schedule."""
        sched = getattr(eng.sb, "ingest_scheduler", None)
        if sched is None:
            return None
        st = eng.rule_state("slo_serving_p95")
        if not sched.deferred:
            if st != "critical":
                return None
            sched.set_deferred(True)
            eng._merge_ok_streak = 0
            eng.sb.config.set("ingest.mergeDeferred", 1)
            return {
                "dir": "down", "from": "scheduling", "to": "deferred",
                "cause": ("slo_serving_p95 critical: compactions and "
                          "tier promotions deferred to protect "
                          "serving"),
                "evidence": {"rule_state": st,
                             **sched.counters()},
            }
        if st == "ok":
            eng._merge_ok_streak += 1
        else:
            eng._merge_ok_streak = 0
            return None
        if eng._merge_ok_streak < recover_ticks:
            return None
        eng._merge_ok_streak = 0
        sched.set_deferred(False)
        eng.sb.config.set("ingest.mergeDeferred", 0)
        ev = sched.catch_up()
        return {
            "dir": "up", "from": "deferred", "to": "scheduling",
            "cause": (f"serving recovered: catch-up ran "
                      f"(merge={ev['pending_merge_ran']}, "
                      f"{ev['promotions_resumed']} promotion(s) "
                      f"resumed)"),
            "evidence": {"rule_state": st, **ev},
        }

    return [
        Actuator("serving_ladder",
                 "degradation ladder driven by the slo_serving_p95 "
                 "burn-rate state (one rung down per sustained-burn "
                 f"tick, up after {recover_ticks} healthy ticks)",
                 ('yacy_health_rule{rule="slo_serving_p95"}',),
                 "serving.degradeLevel", serving_ladder),
        Actuator("batcher_autotune",
                 "dispatcher-count / completer-depth auto-tuning within "
                 f"[{disp_min},{disp_max}]x[{depth_min},{depth_max}] "
                 "from the batcher queue-depth gauges",
                 ('yacy_batcher_queue_depth{queue="incoming"}',
                  'yacy_batcher_queue_depth{queue="inflight"}',
                  'yacy_device_serving_total{counter="batch_dispatches"}'),
                 "index.device.dispatchers", batcher_autotune),
        Actuator("remote_peer_guard",
                 "skip remote-search peers whose gossiped digests report "
                 "critical health or an outlier serving p95",
                 ("yacy_fleet_peers",
                  "yacy_fleet_peer_reported_critical"),
                 "remotesearch.avoidPeers", remote_peer_guard),
        Actuator("device_rebuild",
                 "device-loss watchdog: keeps the background rebuild "
                 "alive while the device is lost; breadcrumbs every "
                 "loss/recovery edge (down=lost, up=serving resumed)",
                 ("yacy_device_lost",
                  'yacy_device_loss_total{event="recoveries"}'),
                 "index.device.lost", device_rebuild),
        Actuator("merge_scheduler",
                 "write-path deferral: parks RWI compactions and tier "
                 "promotions while the serving SLO burns, catches up "
                 f"after {recover_ticks} healthy ticks (down=deferred, "
                 "up=catch-up ran)",
                 ('yacy_health_rule{rule="slo_serving_p95"}',
                  "yacy_ingest_deferred"),
                 "ingest.mergeDeferred", merge_scheduler),
    ]


class ActuatorEngine:
    """Owns the actuator set and its transition bookkeeping.  Ticked by
    `HealthEngine.tick` right after rule evaluation (the sensing and
    the actuation share one cadence and one busy thread) — or directly
    by tests."""

    def __init__(self, sb):
        cfg = sb.config
        self.sb = sb
        self.enabled = cfg.get_bool("actuator.enabled", True)
        self.recover_ticks = max(1, cfg.get_int("actuator.recoverTicks", 3))
        self.tick_s = cfg.get_float("health.tickS", 5.0)
        self.actuators = build_actuators(cfg)
        # admission control: sustained rate = the existing host-access
        # limit (httpd.maxAccessPerHost.600s accesses per 600 s window),
        # burst = the SAME full windowed allowance — the bucket is the
        # old sliding-window policy restated, never tighter (a NAT'd
        # office or a busy peer that the old limit admitted must not
        # start seeing 429s); what changes is that denials now carry
        # the bucket's true refill time as Retry-After
        limit = max(1, cfg.get_int("httpd.maxAccessPerHost.600s", 6000))
        rate = limit / 600.0
        self.bucket = TokenBucketTable(
            capacity=cfg.get_float("actuator.admissionBurst",
                                   float(limit)),
            refill_per_s=rate)
        # ladder / autotune / peer-guard state (mutated by evaluators
        # under self._lock via tick)
        self.level = LEVEL_FULL
        self._ok_streak = 0
        self._backlog_streak = 0
        self._idle_streak = 0
        self._last_dispatches = 0
        self._avoid_peers: frozenset = frozenset()
        self._device_lost_seen = False    # device_rebuild edge memory
        self._merge_ok_streak = 0         # merge_scheduler hysteresis
        self.tick_count = 0
        self.shed_count = 0
        self.degraded_queries = [0] * N_LEVELS
        self._transitions: dict[tuple, int] = {}
        self.breadcrumbs: deque = deque(maxlen=256)
        # two locks on purpose: _tick_lock serializes whole decision
        # passes (evaluators block on batcher/config work — holding the
        # counter lock across them would stall every concurrent
        # note_query() on the serving path and every /metrics scrape);
        # _lock guards only the counter/breadcrumb mutations
        self._tick_lock = threading.Lock()
        self._lock = threading.Lock()
        # (mono ts, owner ladder level, owner retry_after_s) — the
        # rank-service worker's cached view of the owner's rung
        self._remote_state = (-1e9, 0, 0.0)

    # -- evaluation ----------------------------------------------------------

    def rule_state(self, rule_name: str) -> str:
        eng = getattr(self.sb, "health", None)
        if eng is None:
            return "ok"
        st = eng.states.get(rule_name)
        return st.state if st is not None else "ok"

    def _live_batcher(self):
        ds = getattr(self.sb.index, "devstore", None)
        return getattr(ds, "_batcher", None) if ds is not None else None

    def tick(self, now: float | None = None) -> int:
        """One decision pass over every actuator; returns the number of
        transitions taken this tick."""
        if not self.enabled:
            return 0
        now = time.time() if now is None else now
        taken = 0
        with self._tick_lock:
            self.tick_count += 1
            for act in self.actuators:
                try:
                    tr = act.evaluate(self)
                except Exception as e:   # a broken actuator must be VISIBLE
                    with self._lock:
                        self.breadcrumbs.append({
                            "ts": round(now, 3), "actuator": act.name,
                            "dir": "error",
                            "cause": f"actuator error: {e!r}",
                            "knob": act.knob})
                    continue
                if tr is None:
                    continue
                taken += 1
                key = (act.name, tr["dir"])
                with self._lock:
                    self._transitions[key] = \
                        self._transitions.get(key, 0) + 1
                    self.breadcrumbs.append({
                        "ts": round(now, 3), "actuator": act.name,
                        "dir": tr["dir"], "from": tr.get("from", ""),
                        "to": tr.get("to", ""), "knob": act.knob,
                        "cause": tr.get("cause", ""),
                        "evidence": tr.get("evidence", {})})
        return taken

    # -- serving-path surface ------------------------------------------------

    def effective_level(self) -> int:
        """The ladder rung the CURRENT request serves under: the local
        rung, or the owner process's rung when this node is a
        rank-service worker (the owner's ladder governs the shared
        arena; TTL-cached so the socket is asked at most 1/s).
        A disabled engine is INERT: level 0, regardless of whatever
        rung was in force when it was switched off."""
        if not self.enabled:
            return 0
        lvl = self.level
        ds = getattr(self.sb.index, "devstore", None)
        fn = getattr(ds, "serving_state", None)
        if fn is not None:
            now = time.monotonic()
            ts, remote, retry = self._remote_state
            if now - ts > 1.0:
                try:
                    st = fn()
                    if isinstance(st, dict):
                        remote = int(st.get("level", 0))
                        retry = float(st.get("retry_after_s", 0.0))
                    else:
                        remote, retry = 0, 0.0
                except Exception:
                    remote, retry = 0, 0.0
                self._remote_state = (now, remote, retry)
            lvl = max(lvl, remote)
        return lvl

    def serving_state(self) -> dict:
        """The owner-side answer to a worker's rank-service
        `serving_state` call.  A disabled owner reports full service —
        its frozen rung must not keep degrading the workers."""
        if not self.enabled:
            return {"level": 0, "retry_after_s": 0.0}
        return {"level": self.level,
                "retry_after_s": self.shed_retry_after_s()}

    def admit(self, client: str) -> tuple[bool, float]:
        """Admission-control gate for one request from `client`;
        (allowed, retry_after_s).  A disabled engine admits everything
        — the pre-actuator windowed host limit in httpd still stands."""
        if not self.enabled:
            return True, 0.0
        return self.bucket.acquire(client)

    def shed_retry_after_s(self) -> float:
        """Honest Retry-After while shedding: the hysteresis time the
        ladder needs to climb back even if the burn stops NOW (recovery
        ticks x tick cadence per rung above full), clamped sane.  A
        worker shedding at the OWNER's rung relays the owner's own
        recovery estimate (its local rung is typically 0)."""
        rungs = max(1, self.level)
        local = min(300.0, max(5.0,
                               rungs * self.recover_ticks * self.tick_s))
        _ts, remote_lvl, remote_retry = self._remote_state
        if remote_lvl > self.level and remote_retry > 0.0:
            return min(300.0, max(local, remote_retry))
        return local

    def note_query(self, level: int) -> None:
        """Per-level served-query accounting
        (`yacy_degraded_queries_total{level}`)."""
        with self._lock:
            self.degraded_queries[min(max(level, 0), N_LEVELS - 1)] += 1

    def note_shed(self) -> None:
        with self._lock:
            self.shed_count += 1

    # -- observability -------------------------------------------------------

    def transition_counts(self) -> dict:
        """(actuator, dir) -> count, zero-filled for every registered
        actuator x {down, up} so the /metrics series always resolve."""
        out = {}
        with self._lock:
            for act in self.actuators:
                for d in ("down", "up"):
                    out[(act.name, d)] = self._transitions.get(
                        (act.name, d), 0)
            for key, v in self._transitions.items():
                out[key] = v
        return out

    def recent_breadcrumbs(self, n: int = 64) -> list:
        with self._lock:
            return list(self.breadcrumbs)[-n:]

    def avoided_peers(self) -> frozenset:
        """Peers the remote scatter should skip; empty when the engine
        is disabled (a frozen avoid set must not keep skipping peers
        the guard can no longer heal)."""
        if not self.enabled:
            return frozenset()
        with self._lock:
            return self._avoid_peers

    # -- hygiene -------------------------------------------------------------

    def undefined_series(self) -> list:
        """Actuator series references that do NOT resolve against the
        live exposition — must be empty (the no-dead-actuators gate,
        mirroring `HealthEngine.undefined_series`)."""
        from .health import parse_exposition
        from ..server.servlets.monitoring import prometheus_text
        keys = set(parse_exposition(
            prometheus_text(self.sb, include_buckets=False)))
        missing = []
        for act in self.actuators:
            for s in act.series:
                if s not in keys:
                    missing.append(f"{act.name}: {s}")
        return missing
