"""Coordinator-free fleet observability — gossiped metric digests.

The paper's single load-bearing architectural fact is that YaCy has NO
central coordinator: there is no scrape target list, no federation
server, no node that "owns" the fleet view.  Every observability layer
built so far (roofline accounting, the trace spine, the health engine)
is strictly node-local — a node can tell *itself* it is sick, but no
node can see the mesh.  This module closes that gap the P2P way
(ISSUE 5 tentpole):

- **Metric digest.** Each node periodically renders a compact (<2 KiB)
  JSON table: sparse windowed bucket-count vectors for the key
  histogram families (`DIGEST_FAMILIES`), its health-rule states, cache
  hit counters, batcher queue depths, the arena epoch and a digest
  sequence number.  Every field maps to a series on the node's OWN
  `/metrics` exposition (`digest_series` — the no-dead-digest-fields
  hygiene gate), so a digest is exactly a compressed remote scrape.
- **Piggyback gossip.** Digests ride the wire exchanges the DHT already
  pays for: `peers/protocol.Protocol._call` attaches the digest to
  outgoing RPCs (hello pings, remote searches, transferRWI chunks) at a
  per-peer rate limit, and `peers/server.PeerServer.handle` answers a
  digest-bearing caller with its own — no new RPC, no scrape loop.
  `peers/javawire.py` carries the same digest as an `xdigest` multipart
  part on the Java wire.
- **Mergeable mesh percentiles.** Because every histogram shares ONE
  fixed bucket grid (`histogram.merge_counts` is lossless integer
  addition by construction), any node can compute mesh-wide p50/p95/p99
  by merging its peers' digest vectors with its own windowed counts —
  every node converges on the same (eventually consistent) fleet view
  without a coordinator, the way Prometheus federation does WITH one.
- **Staleness semantics.** Received digests are kept per peer (keyed by
  seed hash) and evicted after `fleet.staleS` seconds without a fresh
  one; per-peer sequence numbers drop replayed/reordered digests.  A
  stale peer simply leaves the merged view — absence, not zeros.

Version-skew tolerance is a wire contract (ISSUE 5 satellite): unknown
digest fields are ignored, missing histogram families merge as ABSENT
(never as zero-filled vectors), and malformed families are dropped
individually without rejecting the rest of the digest.
"""

from __future__ import annotations

import json
import threading
import time

from . import histogram, profiling, tailattr

# payload key carrying the digest on every in-band transport (the
# fleet-table analogue of tracing.PAYLOAD_KEY); the Java wire carries it
# as the `xdigest` multipart part (peers/javawire.DIGEST_PART)
PAYLOAD_KEY = "_digest"

DIGEST_VERSION = 1

# the histogram families a digest ships: the serving tail (the SLO
# surface), the device-execution window (the silicon surface) and the
# DHT transfer wall (the P2P surface)
DIGEST_FAMILIES = ("servlet.serving", "kernel.device", "dht.transfer")

DEFAULT_BYTE_BUDGET = 2048          # the <2 KiB wire budget
DEFAULT_STALE_S = 300.0
DEFAULT_SEND_INTERVAL_S = 10.0
DEFAULT_RENDER_TTL_S = 2.0
MAX_TS_SKEW_S = 600.0               # inbound ts clamp (anti-lockout)

STATE_NAMES = ("ok", "warn", "critical")


def peer_key(peer_hash) -> str:
    """THE canonical str form of a peer hash.  Seed hashes are bytes,
    digest/table keys are str; avoid-set membership, blackhole lookups
    and RTT notes all compare through this one normalization — a
    second hand-rolled copy drifting (different errors= mode, raw
    str()) would silently break peer matching across the avoidance
    path."""
    return peer_hash.decode("ascii", "replace") \
        if isinstance(peer_hash, bytes) else str(peer_hash)


def encode_digest(digest: dict) -> str:
    """Compact JSON — the one wire encoding all three transports share
    (the JSON transports embed the dict itself; the Java wire carries
    this string as a part)."""
    return json.dumps(digest, separators=(",", ":"), sort_keys=True)


def digest_bytes(digest: dict) -> int:
    return len(encode_digest(digest))


def decode_act_cause(act: dict) -> str:
    """Tolerant decode of the digest's cause index back to its canon
    label; out-of-range/absent (version skew) reads as unattributed."""
    try:
        i = int(act.get("c", -1))
    except (TypeError, ValueError):
        i = -1
    return tailattr.CAUSES[i] if 0 <= i < len(tailattr.CAUSES) \
        else "unattributed"


def digest_series(digest: dict) -> dict:
    """Map every field a digest emits to the `/metrics` sample key it
    summarizes.  THE hygiene contract (ISSUE 5 satellite, mirroring the
    no-dead-rules gate): a digest field that resolves to no series on
    the local exposition is dead weight on every wire exchange."""
    out: dict[str, str] = {}
    for fam in digest.get("hist", {}):
        out[f"hist.{fam}"] = histogram.prom_name(fam) + "_count"
    for rule in digest.get("rules", {}):
        out[f"rules.{rule}"] = f'yacy_health_rule{{rule="{rule}"}}'
    if "health" in digest:
        out["health"] = "yacy_health_status"
    if "cache" in digest:
        out["cache.hits"] = \
            'yacy_device_serving_total{counter="rank_cache_hits"}'
        out["cache.served"] = \
            'yacy_device_serving_total{counter="queries_served"}'
    if "queues" in digest:
        out["queues.incoming"] = 'yacy_batcher_queue_depth{queue="incoming"}'
        out["queues.inflight"] = 'yacy_batcher_queue_depth{queue="inflight"}'
    if "epoch" in digest:
        out["epoch"] = "yacy_device_arena_epoch"
    if "proc" in digest:
        # multi-process mesh identity (ISSUE 12); zero-filled defaults
        # on single-process nodes so the series resolve everywhere
        out["proc.pid"] = 'yacy_mesh_process{field="pid"}'
        out["proc.id"] = 'yacy_mesh_process{field="process_id"}'
        out["proc.n"] = 'yacy_mesh_process{field="num_processes"}'
        out["proc.lost"] = "yacy_device_lost"
    if "act" in digest:
        # per-member serving rung + tail-cause top-1 (ISSUE 15
        # satellite): a degraded member is visible in Network_Health_p
        # BEFORE it becomes a straggler verdict.  The cause travels as
        # an index into the zero-filled tailattr.CAUSES canon, so its
        # labeled series resolves on every node's exposition.
        out["act.l"] = "yacy_degrade_level"
        out["act.c"] = ('yacy_tail_cause_total{cause="'
                        + decode_act_cause(digest["act"]) + '"}')
        if "p" in digest["act"]:
            # whitebox top-role index (ISSUE 20d): resolves against the
            # zero-filled per-role sample counters; version skew (an
            # old digest without the field) simply omits the mapping
            out["act.p"] = (
                'yacy_prof_role_samples_total{role="'
                + profiling.decode_role(digest["act"].get("p")) + '"}')
    if "tiers" in digest:
        # compact tier occupancy (ISSUE 8): KiB per residency tier +
        # total promotions — the mesh view of who is paging
        out["tiers.h"] = 'yacy_device_hbm_bytes{tier="hot"}'
        out["tiers.w"] = 'yacy_device_hbm_bytes{tier="warm"}'
        out["tiers.c"] = 'yacy_device_hbm_bytes{tier="cold"}'
        out["tiers.p"] = \
            'yacy_tier_promotions_total{src="warm",dst="hot"}'
        out["tiers.d"] = 'yacy_device_hbm_bytes{tier="dense"}'
        out["tiers.ah"] = 'yacy_device_hbm_bytes{tier="ann_hot"}'
        out["tiers.aw"] = 'yacy_device_hbm_bytes{tier="ann_warm"}'
        out["tiers.ac"] = 'yacy_device_hbm_bytes{tier="ann_cold"}'
    return out


class FleetTable:
    """One node's fleet view: its own digest renderer plus the per-peer
    store of received digests.  Constructed on every Switchboard (cheap:
    no threads, no I/O); the peer stack wires itself in via
    `peers/node.P2PNode` (sets `my_hash`, hands the table to the
    Protocol client)."""

    def __init__(self, sb):
        cfg = sb.config
        self.sb = sb
        self.my_hash = ""               # set by P2PNode (seed hash str)
        self.enabled = cfg.get_bool("fleet.enabled", True)
        self.stale_s = cfg.get_float("fleet.staleS", DEFAULT_STALE_S)
        self.send_interval_s = cfg.get_float(
            "fleet.sendIntervalS", DEFAULT_SEND_INTERVAL_S)
        self.render_ttl_s = cfg.get_float(
            "fleet.renderTtlS", DEFAULT_RENDER_TTL_S)
        self.byte_budget = cfg.get_int(
            "fleet.byteBudget", DEFAULT_BYTE_BUDGET)
        self._lock = threading.Lock()
        # peer hash -> sanitized digest entry (decoded hist vectors,
        # receive timestamps, wire size)
        self._peers: dict[str, dict] = {}
        self._sent: dict[str, float] = {}       # peer hash -> last attach
        # peer hash -> (last RPC wall ms, noted-at monotonic)
        self._rtt_ms: dict[str, tuple[float, float]] = {}
        self._seq = 0
        self._last_evict = -1e9
        self._cached: dict | None = None
        self._cached_mono = -1e9
        self.last_digest_bytes = 0
        self.rendered_count = 0
        self.received_count = 0
        self.ignored_count = 0
        # test seam: per-node local count vectors.  Histograms are
        # process-global, so N co-hosted loopback nodes would otherwise
        # all digest the SAME vectors; production single-node processes
        # never set this.
        self._local_counts_fn = None
        # remote-search actuation counters (ISSUE 9): every skip /
        # adaptive-timeout decision the fleet view drives must be
        # attributable — exported as yacy_remotesearch_peers_total
        self.remote_counters = {"asked": 0, "skipped_sick": 0,
                                "adaptive_timeout": 0}

    # -- local side ----------------------------------------------------------

    def set_local_counts_fn(self, fn) -> None:
        """Override the local windowed-count source (loopback tests run
        N nodes against ONE process-global histogram registry)."""
        with self._lock:
            self._local_counts_fn = fn
            self._cached = None

    def local_counts(self, family: str) -> list:
        fn = self._local_counts_fn
        if fn is not None:
            got = fn(family)
            return list(got) if got is not None else []
        h = histogram.get(family)
        return h.windowed_counts() if h is not None else []

    def render(self) -> dict:
        """The node's current digest (TTL-cached: gossip may attach it
        to many concurrent RPCs without re-walking the histograms)."""
        now = time.monotonic()
        with self._lock:
            if self._cached is not None and \
                    now - self._cached_mono < self.render_ttl_s:
                return self._cached
            self._seq += 1
            seq = self._seq
        hist: dict[str, dict] = {}
        for fam in DIGEST_FAMILIES:
            counts = self.local_counts(fam)
            if counts and sum(counts) > 0:
                hist[fam] = histogram.counts_to_sparse(counts)
        eng = getattr(self.sb, "health", None)
        rules = {}
        health = 0
        if eng is not None:
            sev = {"ok": 0, "warn": 1, "critical": 2}
            rules = {name: sev.get(st.state, 0)
                     for name, _d, st in eng.rule_table()
                     if not name.startswith("fleet_")}
            health = eng.status_value()
        ds = getattr(self.sb.index, "devstore", None)
        c = ds.counters() if ds is not None else {}
        b = getattr(ds, "_batcher", None) if ds is not None else None
        # multi-process mesh identity (ISSUE 12): the digest names the OS
        # process behind this node — pid always (the CI hygiene gate
        # asserts distinct pids over the wire), mesh process id / fleet
        # size when this node is a jax.distributed mesh member, and its
        # device-lost flag so the coordinator's Network_Health_p renders
        # a REAL multi-process mesh, not a simulated one
        mm = getattr(self.sb, "mesh_member", None)
        import os as _os
        proc = {"pid": _os.getpid(),
                "id": mm.process_id if mm is not None else 0,
                "n": mm.num_processes if mm is not None else 1,
                "lost": (1 if getattr(ds, "device_lost", False) else 0)}
        act = getattr(self.sb, "actuators", None)
        digest = {
            "v": DIGEST_VERSION,
            "peer": self.my_hash,
            "seq": seq,
            "ts": round(time.time(), 1),
            "hist": hist,
            "rules": rules,
            "health": health,
            "cache": {"hits": int(c.get("rank_cache_hits", 0)),
                      "served": int(c.get("queries_served", 0))},
            "queues": {"incoming": b._q.qsize() if b is not None else 0,
                       "inflight": b._inflight.qsize()
                       if b is not None else 0},
            "proc": proc,
            # serving rung + windowed dominant tail cause (ISSUE 15):
            # the fleet sees WHO is degraded and WHY its tail is fat.
            # The cause travels as its INDEX into the tailattr.CAUSES
            # canon (~6 bytes vs ~30 for the label — the digest's
            # byte budget is a wire contract)
            "act": {
                "l": int(act.effective_level())
                if act is not None else 0,
                "c": tailattr.CAUSES.index(tailattr.top_cause()),
                # whitebox top-frame role (ISSUE 20d): which thread
                # role this node burns most samples in, as an index
                # into the zero-filled profiling.ROLES canon — a peer
                # whose dispatcher pool pegs is visible fleet-wide
                # before it straggles (~8 bytes, the act.c model)
                "p": profiling.top_role_index(),
            },
            "epoch": int(c.get("arena_epoch", 0)),
            # tier occupancy in KiB (compact: ~30 B inside the 2 KiB
            # budget) + warm->hot promotions — a peer whose w/c grow
            # while p churns is paging, visible mesh-wide
            "tiers": {
                "h": int(c.get("tier_hot_bytes", 0)) >> 10,
                "w": int(c.get("tier_warm_bytes", 0)) >> 10,
                "c": int(c.get("tier_cold_bytes", 0)) >> 10,
                "p": int(c.get("tier_promotions_warm_hot", 0)),
                # vector-side residency (ISSUE 11): dense f16 forward
                # block + the ANN slab ladder, KiB like the postings
                "d": int(c.get("dense_fwd_bytes", 0)) >> 10,
                "ah": int(c.get("ann_hot_bytes", 0)) >> 10,
                "aw": int(c.get("ann_warm_bytes", 0)) >> 10,
                "ac": int(c.get("ann_cold_bytes", 0)) >> 10,
            },
        }
        # wire budget: a digest must never bloat the exchanges it rides.
        # Dropping the largest family degrades the mesh view gracefully
        # (absent merges as absent); tests/test_fleet.py pins that a
        # full digest fits and that over budget families go, not the wire.
        size = digest_bytes(digest)
        while size > self.byte_budget and digest["hist"]:
            fat = max(digest["hist"],
                      key=lambda f: len(encode_digest(digest["hist"][f])))
            del digest["hist"][fat]
            digest["trimmed"] = 1
            size = digest_bytes(digest)
        with self._lock:
            self.rendered_count += 1
            # two TTL-expired renders can race: only the NEWEST seq may
            # own the cache, or a stale-seq digest would gossip for the
            # next TTL and be dropped by receivers as a replay
            if self._cached is None or seq >= self._cached.get("seq", 0):
                self._cached = digest
                self._cached_mono = now
                self.last_digest_bytes = size
        return digest

    def outgoing_digest(self, peer_hash) -> dict | None:
        """The digest to piggyback on an RPC to `peer_hash`, or None if
        that peer got one inside `fleet.sendIntervalS` (the per-peer
        rate limit that keeps gossip amortized over existing traffic)."""
        if not self.enabled:
            return None
        key = peer_key(peer_hash)
        now = time.monotonic()
        with self._lock:
            if now - self._sent.get(key, -1e9) < self.send_interval_s:
                return None
            self._sent[key] = now
        return self.render()

    def send_failed(self, peer_hash) -> None:
        """Release the rate-limit slot `outgoing_digest` charged for an
        RPC that then failed: the digest never arrived, so the next
        successful exchange with that peer should carry one instead of
        waiting out `fleet.sendIntervalS` on a phantom delivery."""
        key = peer_key(peer_hash)
        with self._lock:
            self._sent.pop(key, None)

    # -- receive side --------------------------------------------------------

    def ingest(self, digest) -> bool:
        """Store a peer's digest.  Tolerant by contract: unknown fields
        are ignored, malformed histogram families are dropped
        individually, missing families stay absent.  Rejected outright
        (counted in `ignored_count`): non-dict payloads, digests without
        a peer hash, our own digest reflected back, and per-peer
        seq/ts replays."""
        if not self.enabled or not isinstance(digest, dict):
            self._ignore()
            return False
        peer = digest.get("peer")
        if not isinstance(peer, str) or not peer or peer == self.my_hash:
            self._ignore()
            return False
        try:
            seq = int(digest.get("seq", 0))
            ts = float(digest.get("ts", 0.0))
        except (TypeError, ValueError):
            self._ignore()
            return False
        # The wire is unauthenticated (the same trust level as seed
        # gossip itself), so digest CONTENT is only as trustworthy as
        # the mesh — but a forged future `ts` must never lock a
        # victim's real digests out of the replay gate below.  Two
        # guards: egregiously future timestamps are rejected outright,
        # and every ACCEPTED ts is CLAMPED to the receiver's clock —
        # so no stored ts ever exceeds its ingest time, and a genuine
        # later digest (fresh ts > any past ingest time) always passes
        # `ts > prev.ts` no matter what an attacker stored first.
        if ts > time.time() + MAX_TS_SKEW_S:
            self._ignore()
            return False
        ts = min(ts, time.time())
        hist: dict[str, list] = {}
        raw_hist = digest.get("hist")
        if isinstance(raw_hist, dict):
            for fam, sp in raw_hist.items():
                counts = histogram.counts_from_sparse(sp)
                if counts is not None:
                    hist[str(fam)] = counts
        rules: dict[str, int] = {}
        raw_rules = digest.get("rules")
        if isinstance(raw_rules, dict):
            for name, v in raw_rules.items():
                if isinstance(v, int) and 0 <= v <= 2:
                    rules[str(name)] = v
        entry = {
            "peer": peer,
            "seq": seq,
            "ts": ts,
            "hist": hist,
            "rules": rules,
            "health": digest.get("health")
            if digest.get("health") in (0, 1, 2) else 0,
            "cache": digest.get("cache")
            if isinstance(digest.get("cache"), dict) else {},
            "queues": digest.get("queues")
            if isinstance(digest.get("queues"), dict) else {},
            "epoch": digest.get("epoch")
            if isinstance(digest.get("epoch"), int) else 0,
            "proc": digest.get("proc")
            if isinstance(digest.get("proc"), dict) else {},
            "act": digest.get("act")
            if isinstance(digest.get("act"), dict) else {},
            "recv_mono": time.monotonic(),
            "recv_ts": time.time(),
            "bytes": digest_bytes(digest),
        }
        with self._lock:
            prev = self._peers.get(peer)
            if prev is not None and seq <= prev["seq"] and ts <= prev["ts"]:
                self.ignored_count += 1     # replay / out-of-order
                return False
            self._peers[peer] = entry
            self.received_count += 1
        self.evict_stale()
        return True

    def _ignore(self) -> None:
        with self._lock:
            self.ignored_count += 1

    def note_rtt(self, peer_hash, ms: float) -> None:
        """Last observed RPC wall against this peer (remote searches,
        DHT transfers) — the peer table's liveness column."""
        key = peer_key(peer_hash)
        with self._lock:
            self._rtt_ms[key] = (float(ms), time.monotonic())

    def evict_stale(self, now: float | None = None) -> int:
        """Drop digests older than `fleet.staleS` — a silent peer leaves
        the merged view (absence, not zeros).  The per-peer send/RTT
        bookkeeping ages out on the same horizon, so a churning open
        mesh never grows these maps without bound."""
        now = time.monotonic() if now is None else now
        with self._lock:
            # every read path (fresh/merged_counts/peer_rows) drives
            # eviction, so one scrape or health tick would re-scan these
            # maps ~10 times within milliseconds; against a 300s
            # staleness horizon that is pure lock-held waste — time-gate
            # re-scans (scaled down with stale_s so tests that shrink
            # the horizon still evict immediately)
            if now - self._last_evict < min(1.0, self.stale_s / 10.0):
                return 0
            self._last_evict = now
            dead = [h for h, e in self._peers.items()
                    if now - e["recv_mono"] > self.stale_s]
            for h in dead:
                del self._peers[h]
            horizon = max(self.stale_s, self.send_interval_s)
            for h in [h for h, t in self._sent.items()
                      if now - t > horizon]:
                del self._sent[h]
            for h in [h for h, (_ms, t) in self._rtt_ms.items()
                      if now - t > self.stale_s]:
                del self._rtt_ms[h]
        return len(dead)

    def fresh(self) -> list:
        """Current (non-stale) peer digest entries, stably ordered."""
        self.evict_stale()
        with self._lock:
            return [self._peers[h] for h in sorted(self._peers)]

    # -- the mesh view -------------------------------------------------------

    def merged_counts(self, family: str) -> list:
        """Mesh-wide bucket vector: own windowed counts + every fresh
        peer's digest vector.  Lossless by construction (integer sums on
        one shared bucket grid), so the percentile any node computes
        from it is EXACTLY the percentile over the union of samples."""
        vecs = []
        own = self.local_counts(family)
        if own:
            vecs.append(own)
        for e in self.fresh():
            counts = e["hist"].get(family)
            if counts is not None:          # absent stays absent
                vecs.append(counts)
        return histogram.merge_counts(vecs) if vecs \
            else [0] * histogram.N_BUCKETS

    def mesh_percentile(self, family: str, q: float) -> float:
        return histogram.percentile_from_counts(
            self.merged_counts(family), q)

    def critical_peers(self) -> list:
        return [e["peer"] for e in self.fresh() if e.get("health") == 2]

    # -- remote-search actuation surface (ISSUE 9) ---------------------------

    def note_remote(self, event: str, n: int = 1) -> None:
        """Count one remote-search actuation decision (asked /
        skipped_sick / adaptive_timeout) — the counters that attribute
        every peer skip in `/metrics`."""
        with self._lock:
            if event in self.remote_counters:
                self.remote_counters[event] += n

    def remote_counter_snapshot(self) -> dict:
        with self._lock:
            return dict(self.remote_counters)

    def sick_peers(self, outlier_factor: float = 3.0,
                   min_mesh: int = 50, min_peer: int = 20) -> list:
        """Peer hashes the remote scatter should avoid: digests
        reporting critical health or a wedged kernel, plus serving-p95
        outliers judged leave-one-out against the rest of the mesh (the
        fleet_peer_outlier rule's discipline — a high-traffic outlier
        must not mask itself inside the merged tail).  `min_mesh`/
        `min_peer` are the SAME statistical gates the rule applies
        (health.fleetOutlierMinSamples / MinPeerSamples — callers pass
        the configured values so the actuation never judges data the
        diagnostic layer would refuse to judge); the digest-reported
        critical/stall verdicts are explicit, not statistical, and
        stay ungated."""
        fresh = self.fresh()
        if not fresh:
            return []
        sick: set[str] = set()
        for e in fresh:
            if e.get("health") == 2 or \
                    e.get("rules", {}).get("worker_stall") == 2:
                sick.add(e["peer"])
        merged = self.merged_counts("servlet.serving")
        if sum(merged) < min_mesh:
            return sorted(sick)     # insufficient mesh traffic for the
            #                         outlier verdict (rule parity)
        for e in fresh:
            counts = e["hist"].get("servlet.serving")
            if e["peer"] in sick or not counts \
                    or sum(counts) < min_peer:
                continue        # thin family: no verdict
            rest = [max(0, m - c) for m, c in zip(merged, counts)]
            if sum(rest) < min_peer:
                continue        # no baseline to judge against
            p95 = histogram.percentile_from_counts(counts, 0.95)
            rest_p95 = histogram.percentile_from_counts(rest, 0.95)
            if p95 > outlier_factor * rest_p95:
                sick.add(e["peer"])
        return sorted(sick)

    def peer_rpc_p95_ms(self, peer_hash,
                        min_samples: int = 20) -> float | None:
        """This peer's digest-reported RPC wall p95 (`dht.transfer`
        family); None for digest-less peers or digests with fewer than
        `min_samples` observations — the caller keeps its static
        timeout for those.  Same statistical discipline as sick_peers:
        actuation never judges data thinner than the diagnostic layer
        would accept (one fast RPC must not collapse a healthy peer's
        search timeout)."""
        key = peer_key(peer_hash)
        with self._lock:
            entry = self._peers.get(key)
        if entry is not None:
            counts = entry["hist"].get("dht.transfer")
            if counts and sum(counts) >= min_samples:
                return histogram.percentile_from_counts(counts, 0.95)
        return None

    def peer_rows(self) -> list:
        """Per-peer table rows for `Network_Health_p`: state, windowed
        percentiles per digest family (None where the family is absent
        — version skew shows as '-', never as fake zeros), staleness
        age, sequence number and wire size."""
        now = time.monotonic()
        rows = []
        fresh = self.fresh()
        with self._lock:
            rtts = dict(self._rtt_ms)
        for e in fresh:
            got = rtts.get(e["peer"])
            rtt = got[0] if got is not None else None
            quantiles = {}
            for fam in DIGEST_FAMILIES:
                counts = e["hist"].get(fam)
                if counts is None or sum(counts) == 0:
                    quantiles[fam] = None
                else:
                    quantiles[fam] = tuple(
                        histogram.percentile_from_counts(counts, q)
                        for q in (0.50, 0.95, 0.99))
            rows.append({
                "hash": e["peer"],
                "health": e.get("health", 0),
                "state": STATE_NAMES[e.get("health", 0)],
                "age_s": round(now - e["recv_mono"], 1),
                "seq": e["seq"],
                "bytes": e["bytes"],
                "rtt_ms": rtt,
                "quantiles": quantiles,
                "queues": e.get("queues", {}),
                "epoch": e.get("epoch", 0),
                "proc": e.get("proc", {}),
                # serving rung + tail-cause top-1 (ISSUE 15 satellite),
                # decoded for Network_Health_p's degraded-member columns
                "act": ({"lvl": e["act"].get("l", 0),
                         "cause": decode_act_cause(e["act"])}
                        if e.get("act") else {}),
            })
        return rows
