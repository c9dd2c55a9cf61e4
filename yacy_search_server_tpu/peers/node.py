"""P2PNode — a Switchboard plus the full peer stack, one per network node.

The composition the reference builds inside Switchboard's constructor
(reference: source/net/yacy/search/Switchboard.java:668 Dispatcher wiring,
:1218-1230 peer ping deploy, :4133-4207 dhtTransferJob with its guard
rails) — factored out so N nodes can live in one process over a
LoopbackNetwork (the simulated multi-peer harness) or over HTTP (server/).
"""

from __future__ import annotations

import random
import time

from ..parallel.distribution import LONG_MAX, Distribution
from ..search.searchevent import SearchEvent
from ..switchboard import Switchboard
from .dispatcher import Dispatcher
from .network import Network
from .news import CAT_CRAWL_START, NewsPool
from .protocol import Protocol
from .remotesearch import RemoteSearch
from .seed import PeerType, Seed, SeedDB, make_seed_hash
from .server import PeerServer
from .transport import Transport

# freeworld defaults (reference: defaults/yacy.network.freeworld.unit)
DEFAULT_PARTITION_EXPONENT = 4     # 2^4 = 16 vertical partitions
DEFAULT_REDUNDANCY = 3             # dhtredundancy.senior
# dhtTransferJob guards (Switchboard.java:4147-4160)
MIN_PEERS_FOR_DHT = 1


class P2PNode:
    """One peer: switchboard + seed identity + protocol client/server +
    DHT dispatcher + membership gossip + remote search."""

    def __init__(self, name: str, p2p_transport: Transport,
                 data_dir: str | None = None,
                 crawl_transport=None,
                 port: int = 8090,
                 partition_exponent: int = DEFAULT_PARTITION_EXPONENT,
                 redundancy: int = DEFAULT_REDUNDANCY,
                 peer_type: str = PeerType.SENIOR,
                 accept_remote_index: bool = True,
                 accept_remote_crawl: bool = False,
                 cluster_peers: list[str] | None = None,
                 config=None):
        # `config` (utils/config.Config) must reach the Switchboard's
        # constructor: the index.device.* keys are read there, once
        self.sb = Switchboard(data_dir=data_dir, config=config,
                              transport=crawl_transport)
        self.seed = Seed(make_seed_hash(name, "127.0.0.1", port), name=name,
                         port=port, peer_type=peer_type)
        self.seed.flags_accept_remote_index = accept_remote_index
        self.seed.flags_accept_remote_crawl = accept_remote_crawl
        self.seeddb = SeedDB(self.seed, data_dir)
        self.sb.seeddb = self.seeddb     # status/graphics servlets read it
        # servlet-level P2P access: yacysearch's resource=global fan-out
        # and /metrics' DHT counters reach the peer stack through the
        # switchboard (httpd's *.yacy rewrite already expects sb.node)
        self.sb.node = self
        self.dist = Distribution(partition_exponent)
        self.redundancy = redundancy
        self.news = NewsPool(data_dir)
        self.sb.news = self.news     # feed servlet reads the pool from sb
        # fleet observability (ISSUE 5): the switchboard's fleet table
        # learns this node's identity and rides every protocol exchange
        self.sb.fleet.my_hash = self.seed.hash.decode("ascii", "replace")
        self.protocol = Protocol(self.seeddb, p2p_transport,
                                 news=self.news, fleet=self.sb.fleet)
        self.server = PeerServer(self.sb, self.seeddb,
                                 accept_remote_index=accept_remote_index,
                                 accept_remote_crawl=accept_remote_crawl,
                                 news=self.news)
        p2p_transport.register(self.seed.hash, self.server.handle)
        self._transport = p2p_transport
        self.dispatcher = Dispatcher(self.sb.index, self.seeddb, self.dist,
                                     self.protocol, redundancy)
        self.network = Network(self.seeddb, self.protocol)
        # active network definition; ctor args override its DHT geometry
        from ..utils.config import NetworkUnit
        self.network_unit = NetworkUnit("freeworld", {
            "network.unit.dht.partitionExponent": str(partition_exponent),
            "network.unit.dhtredundancy.senior": str(redundancy)})
        self.cluster_peers = list(cluster_peers or [])
        self._rng = random.Random(self.seed.ring_position())

    # -- network definition ---------------------------------------------------

    def switch_network(self, unit_name: str, overrides=None) -> None:
        """Re-wire DHT + crawl behavior to another network definition at
        runtime (reference: Switchboard.switchNetwork selected by
        `network.unit.definition`): partition exponent, redundancy and
        remote-search budgets come from the unit; buffered outbound
        postings return to the local index first (their vertical split
        depends on the partition count)."""
        from ..utils.config import NETWORK_UNITS, NetworkUnit
        if unit_name not in NETWORK_UNITS:
            # a typo must not silently rewire the node onto the PUBLIC net
            raise ValueError(f"unknown network unit: {unit_name!r} "
                             f"(have: {sorted(NETWORK_UNITS)})")
        unit = NetworkUnit(unit_name, overrides)
        self.dispatcher.restore_buffer_to_index()
        self.dist = Distribution(unit.partition_exponent)
        self.redundancy = unit.redundancy_senior
        self.dispatcher = Dispatcher(self.sb.index, self.seeddb, self.dist,
                                     self.protocol, self.redundancy)
        self.network_unit = unit
        self.sb.config.set("network.unit.definition", unit.name)

    # -- membership ----------------------------------------------------------

    def bootstrap(self, seeds: list[Seed]) -> None:
        self.network.bootstrap = [s for s in seeds
                                  if s.hash != self.seed.hash]
        # over HTTP, bootstrap seeds carry the initial address book (the
        # reference's seed-list files carry IP:port the same way)
        if hasattr(self._transport, "set_address"):
            for s in self.network.bootstrap:
                self._transport.set_address(
                    s.hash, f"http://{s.ip}:{s.port}")

    def ping(self) -> int:
        return self.network.peer_ping()

    # -- DHT distribution (the dhtTransferJob busy thread) -------------------

    def dht_transfer_job(self, max_containers: int = 32,
                         max_refs: int = 2000,
                         segment_fraction: float = 1 / 64) -> bool:
        """One transfer cycle over a random ring segment; returns True if
        anything was shipped (BusyThread contract). Guards mirror
        Switchboard.dhtShallTransfer: enough peers, something to send,
        buffer not overfull."""
        if len(self.seeddb.active) < MIN_PEERS_FOR_DHT:
            return False
        if self.sb.index.rwi_size() == 0 and self.dispatcher.buffer_size() == 0:
            return False
        if self.dispatcher.buffer_size() < self.dist.vertical_partitions():
            start = self._rng.randrange(LONG_MAX)
            span = max(1, int(LONG_MAX * segment_fraction))
            limit = (start + span) % LONG_MAX
            self.dispatcher.select_containers_to_buffer(
                start, limit, max_containers, max_refs)
        txs = self.dispatcher.dequeue_transmissions()
        if not txs:
            return False
        return self.dispatcher.transmit_all(txs) > 0

    def distribute_all(self, rounds: int = 512) -> int:
        """Drive transfer to completion (test/CLI surface): sweep the whole
        ring deterministically, then flush the buffer."""
        total = 0
        parts = 16
        for i in range(parts):
            start = i * (LONG_MAX // parts)
            limit = (i + 1) * (LONG_MAX // parts) - 1
            self.dispatcher.select_containers_to_buffer(
                start, limit, max_containers=10**6, max_refs=10**9)
        for _ in range(rounds):
            txs = self.dispatcher.dequeue_transmissions(max_chunks=64)
            if not txs:
                break
            total += self.dispatcher.transmit_all(txs)
            if self.dispatcher.buffer_size() == 0:
                break
        return total

    # -- crawl (news-announcing wrapper + remote crawl delegation) -----------

    def start_crawl(self, start_url: str, depth: int = 0, **kw):
        """Start a crawl and announce it on the news channel
        (reference: Switchboard publishes a crwlstrt record on crawl start)."""
        profile = self.sb.start_crawl(start_url, depth=depth, **kw)
        self.news.publish(CAT_CRAWL_START,
                          self.seed.hash.decode("ascii", "replace"),
                          {"startURL": start_url, "intention":
                           kw.get("name", ""), "generalDepth": str(depth)})
        return profile

    def remote_crawl_loader_job(self, max_urls: int = 10) -> bool:
        """Pull delegated crawl work from a peer that publishes it, load
        the pages into MY index, and report receipts back (reference:
        CrawlQueues.remoteCrawlLoaderJob:444 + crawlReceipt round-trip).
        Returns True if any URL was processed (BusyThread contract)."""
        providers = [s for s in self.seeddb.active_seeds()
                     if s.flags_accept_remote_crawl]
        if not providers:
            return False
        provider = self._rng.choice(providers)
        requests = self.protocol.pull_crawl_urls(provider, count=max_urls)
        worked = False
        from ..crawler.loader import CacheStrategy
        from ..crawler.request import Request
        for rd in requests:
            try:
                req = Request.from_dict(rd)
            except (KeyError, ValueError):
                continue
            try:
                resp = self.sb.loader.load(req, CacheStrategy.IFFRESH)
            except Exception:
                self.protocol.crawl_receipt(provider, req.urlhash(),
                                            "exception", "load failed")
                continue
            if resp.status == 200:
                # the delegator's profile handle never resolves here (handles
                # hash node-local creation state); fall back to the dedicated
                # "remote" default profile, not an arbitrary one
                profile = self.sb.profiles.get(req.profile_handle) or \
                    next((p for p in self.sb.profiles.values()
                          if p.name == "remote"),
                         next(iter(self.sb.profiles.values())))
                self.sb.to_indexer(resp, profile)
                self.protocol.crawl_receipt(provider, req.urlhash(), "fill")
                worked = True
            else:
                self.protocol.crawl_receipt(provider, req.urlhash(),
                                            "reject", f"status {resp.status}")
        return worked

    # -- search --------------------------------------------------------------

    def search(self, query_string: str, count: int = 10,
               remote: bool = True, timeout_s: float | None = None,
               secondary: bool = True) -> SearchEvent:
        """Local batched search + remote scatter-gather into one event
        (the yacysearch entry: local threads + primaryRemoteSearches).
        The per-peer budget defaults to the active network unit's
        remotesearch.maxtime/maxcount.

        Cluster mode (reference: cluster.peers.yacydomain allowlist ->
        Searchdom.CLUSTER): when `cluster_peers` is set, the scatter goes to
        exactly that fixed peer set instead of DHT-selected targets."""
        event = self.sb.search(query_string, count=count)
        if remote:
            self.scatter(event, count, timeout_s=timeout_s,
                         secondary=secondary)
        return event

    def scatter(self, event: SearchEvent, count: int,
                timeout_s: float | None = None,
                secondary: bool = True) -> int:
        """Remote scatter-gather into a live event — THE fan-out used by
        both node.search and the servlet's resource=global path, so
        cluster mode (the cluster_peers allowlist) and the secondary
        abstract-join round apply no matter which surface asked.
        Returns the number of peers asked."""
        if not self.seeddb.active:
            return 0
        # a CACHED event carries the trace of the request that created
        # it (possibly long finished): this scatter belongs to the
        # request driving it NOW, so its fan-out spans re-parent here
        from ..utils import tracing
        cur = tracing.current()
        if cur is not None:
            event.trace_ctx = cur
        if timeout_s is None:
            timeout_s = self.network_unit.remotesearch_maxtime_ms / 1000.0
        per_peer = max(count, self.network_unit.remotesearch_maxcount)
        # fleet-aware avoidance (ISSUE 9): the remote_peer_guard
        # actuator maintains the avoided-peer set from gossiped digests
        act = getattr(self.sb, "actuators", None)
        avoid = set(act.avoided_peers()) if act is not None else None
        rs = RemoteSearch(event, self.seeddb, self.dist, self.protocol,
                          redundancy=self.redundancy,
                          per_peer_count=per_peer, timeout_s=timeout_s,
                          avoid_hashes=avoid)
        if self.cluster_peers:
            allowed = {n.lower() for n in self.cluster_peers}
            targets = [s for s in self.seeddb.active_seeds()
                       if s.name.lower() in allowed]
            asked = rs.start_fixed(targets)
        else:
            asked = rs.start()
        rs.join()
        if secondary and rs.secondary_search():
            rs.join(timeout_s / 2)
        return asked

    # -- cross-peer trace assembly (ISSUE 5) ----------------------------------

    def assemble_trace(self, trace_id: str, max_peers: int = 16,
                       timeout_s: float = 5.0) -> int:
        """Fetch the remote segments of `trace_id` from active peers and
        merge them into the local ring (Performance_Trace_p's assemble
        affordance): the originator of a resource=global search renders
        the FULL distributed waterfall instead of an opaque fan-out gap.
        Fetches run CONCURRENTLY against a deadline (the RemoteSearch
        fan-out discipline) so one slow/dead peer costs one timeout, not
        a serial sum across the whole page load.  The peers the traced
        search ACTUALLY asked come first (their hashes ride the
        `peers.remotesearch` span attrs), so a large mesh never
        exhausts `max_peers` on uninvolved nodes; remaining slots fall
        back to active peers (remote segments can exist on peers whose
        fan-out span was lost).  Returns the number of spans merged (0
        when every peer's segment was already present — the idempotence
        contract)."""
        import threading

        from ..utils import tracing
        merged = [0]
        lock = threading.Lock()

        def fetch(seed):
            ok, reply = self.protocol.fetch_trace(seed, trace_id)
            if not ok:
                return
            spans = reply.get("spans")
            src = reply.get("peer") or seed.hash.decode("ascii", "replace")
            if spans:
                n = tracing.merge_remote_spans(trace_id, spans, src)
                with lock:
                    merged[0] += n

        targets: list = []
        seen: set = set()
        rec = tracing.get_trace(trace_id)
        if rec is not None:
            for s in rec.spans:
                ph = s.attrs.get("peer_hash")
                if not isinstance(ph, str):
                    continue
                seed = self.seeddb.get(ph.encode("ascii", "replace"))
                if seed is not None and seed.hash not in seen:
                    seen.add(seed.hash)
                    targets.append(seed)
        for seed in self.seeddb.active_seeds():
            if len(targets) >= max_peers:
                break
            if seed.hash not in seen:
                seen.add(seed.hash)
                targets.append(seed)

        threads = []
        for seed in targets[:max_peers]:
            th = threading.Thread(target=fetch, args=(seed,),
                                  name=f"tracefetch-{seed.name}",
                                  daemon=True)
            th.start()
            threads.append(th)
        t_end = time.monotonic() + timeout_s
        for th in threads:
            left = t_end - time.monotonic()
            if left <= 0:
                break
            th.join(left)
        return merged[0]

    # -- HTTP face (DCN deployment) ------------------------------------------

    def serve_http(self, host: str = "127.0.0.1", port: int = 0):
        """Expose this node's UI/API + /yacy/* wire endpoints over a real
        socket and advertise the bound address in the seed DNA (the
        reference's Jetty startup + Seed IP/port publication). When the
        node's transport is an HttpTransport without a resolver, wire the
        SeedDB in as the address book — gossiped seeds become reachable."""
        from ..server.httpd import YaCyHttpServer
        from .transport import HttpTransport

        self.http = YaCyHttpServer(self.sb, port=port, host=host,
                                   peer_server=self.server).start()
        self.seed.ip = host
        self.seed.port = self.http.port
        if isinstance(self._transport, HttpTransport) \
                and self._transport.resolver is None:
            def resolve(peer_hash: bytes) -> str | None:
                s = self.seeddb.get(peer_hash)
                return f"http://{s.ip}:{s.port}" if s else None
            self._transport.resolver = resolve
        return self.http

    # -- lifecycle -----------------------------------------------------------

    def close(self) -> None:
        if getattr(self, "http", None) is not None:
            self.http.close()
            self.http = None
        self.dispatcher.restore_buffer_to_index()
        self._transport.unregister(self.seed.hash)
        self.seeddb.close()
        self.sb.close()

    def deploy_threads(self) -> None:
        """Busy threads incl. the P2P jobs (deployThread parity)."""
        from ..utils.workflow import BusyThread
        self.sb.deploy_threads()
        self.sb.threads.deploy(BusyThread(
            "30_peerping", lambda: self.ping() > 0,
            idle_sleep_s=30.0, busy_sleep_s=30.0))
        self.sb.threads.deploy(BusyThread(
            "70_dht_distribution", self.dht_transfer_job,
            idle_sleep_s=15.0, busy_sleep_s=1.0))
        self.sb.threads.deploy(BusyThread(
            "62_remotetriggeredcrawl", self.remote_crawl_loader_job,
            idle_sleep_s=10.0, busy_sleep_s=1.0))
