"""Mesh-sharded postings serving — the DHT axes as arena partitions.

Multi-chip product serving (VERDICT r2 missing #1): the single-device
``DeviceSegmentStore`` pins one chip; this store partitions the SAME
packed-extent arena across a ``('term', 'doc')`` ``jax.sharding.Mesh`` and
executes every eligible query as ONE SPMD program over all devices:

    per-device streaming scan of its extent slice
    → lax.pmin/pmax merge of normalization stats (ReferenceOrder's
      global min/max, computed once per query across the whole mesh)
    → per-device score + local top-k
    → lax.all_gather over both mesh axes + global top-k (replicated)

Placement IS the DHT math (reference:
source/net/yacy/cora/federate/yacy/Distribution.java:35-93 mapped over
kelondro/rwi/IndexCell.java:65-283):

- **term axis** (horizontal ring): a term's postings live only on the
  term row ``(horizontal_dht_position(termhash) * n_term) >> 63`` — the
  base64-cardinal ring position of ``parallel/distribution.py`` scaled to
  the axis size. Other term rows hold a zero-count extent and contribute
  neutral stats/candidates.
- **doc axis** (vertical partitions): each posting lands on doc column
  ``docid % n_doc``. Docids are the metadata store's bijective alias of
  url hashes, so this is the same equivalence the reference's
  url-hash vertical split provides (one url → one column for EVERY
  term), which is what makes conjunctions column-local.

Queries whose terms all live on one term row join device-side per doc
column (docid-sorted side tables are column-local by the invariant
above); terms on different rows fall back to the host join — the same
boundary the reference has, where a cross-ring join ships candidate doc
lists between peers (SecondarySearchSuperviser).

The RAM-buffer delta (postings newer than the last flush) replicates to
every device for the query: min/max stats are idempotent under
duplication, and duplicate candidates in the gathered top-k dedup
host-side (the existing cross-run duplicate rule of the single-chip
store).

Block-max pruning composes with the sharding: each cell packs its slice
proxy-sorted with a per-tile bound table against GLOBAL frozen pack
stats, and an eligible query scores only a prefix of every device's
tiles, verifying each device's unscored tail against its LOCAL k-th
score — an exact local top-k per device makes the all_gather merge
exact, and any failed bound escalates the prefix mesh-wide. Host
mirrors of each cell's buffers are kept so growth and repacking never
read back from device.
"""

from __future__ import annotations

import logging
import threading
import time
from functools import partial

log = logging.getLogger("index.meshstore")

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as PS

from ..ingest import slo as ingest_slo
from ..ops.ranking import (RankingProfile, cardinal_from_stats,
                           compact_feats, local_stats)
from ..ops.streaming import merge_stats
from ..parallel.distribution import horizontal_dht_position
from ..parallel.mesh import (all_gather_topk, all_gather_topk_full,
                             tie_topk)
from ..utils.eventtracker import EClass, update as track
from ..utils import histogram, tailattr, tracing
from . import postings as P
from ..utils import faultinject
from .integrity import CorruptRunError
from .devstore import (_PRUNE_B, DAYS_NONE_HI, DAYS_NONE_LO,
                       LOSS_STREAK, NEG_INF32, NO_FLAG, NO_LANG,
                       TILE, TRANSFER_BACKOFF_S, TRANSFER_RETRIES,
                       DeviceTransferError, _TopkCache, _bucket_delta,
                       _bucket_rows, _constraint_valid, _emit_rt_spans,
                       _pruned_span_topk, _tile_valid, measure_row_bytes,
                       pack_prune_stats, pmax_table, prune_bound_consts,
                       proxy_order)

INT32_MAX = 2 ** 31 - 1


def _my_process_index() -> int:
    try:
        return jax.process_index()
    except Exception:
        return 0


def term_shard(termhash: bytes, n_term: int) -> int:
    """Horizontal DHT ring position scaled to the term axis size."""
    return int((horizontal_dht_position(termhash) * n_term) >> 63)


class MeshSpan:
    """One run's extents for a term across every mesh cell."""

    __slots__ = ("starts", "counts", "total", "jstarts",
                 "tstarts", "tcounts", "stats", "dead_seq")

    def __init__(self, starts: np.ndarray, counts: np.ndarray,
                 jstarts: np.ndarray | None = None,
                 tstarts: np.ndarray | None = None,
                 tcounts: np.ndarray | None = None,
                 stats=None, dead_seq: int = -1):
        self.starts = starts          # int32 [n_cells] per-cell offsets
        self.counts = counts          # int32 [n_cells]
        self.jstarts = jstarts        # int32 [n_cells] join-table offsets
        self.tstarts = tstarts        # int32 [n_cells] pmax offsets
        self.tcounts = tcounts        # int32 [n_cells] pmax tile counts
        # GLOBAL pack-time normalization stats (whole term, all cells):
        # every device must prune/score in the same normalized space
        self.stats = stats
        self.dead_seq = dead_seq      # tombstone count at pack (devstore)
        self.total = int(counts.sum())


class _CellBuf:
    """Host mirror of one mesh cell's packed rows (+ join side-table).

    Appends accumulate CHUNKS and only concatenate at materialize time
    (once per device sync) — per-append concatenation would copy the
    whole cell per (term, column) and make run packing quadratic in term
    count (the pathology devstore's one-write-per-run pack avoids)."""

    __slots__ = ("_parts", "used", "_jparts", "jused",
                 "_tparts", "tused",
                 "feats16", "flags", "docids", "jdocids", "jpos", "pmax")

    def __init__(self):
        self.used = 0
        self.jused = 0
        self.tused = 0
        self._parts: list[tuple] = []       # (f16, fl, dd) chunks
        self._jparts: list[tuple] = []      # (jdocids, jpos) chunks
        self._tparts: list[np.ndarray] = []  # per-tile pmax chunks
        self.feats16 = np.zeros((0, P.NF), np.int16)
        self.flags = np.zeros(0, np.int32)
        self.docids = np.zeros(0, np.int32)
        self.jdocids = np.zeros(0, np.int32)
        self.jpos = np.zeros(0, np.int32)
        self.pmax = np.zeros(0, np.int32)

    def append(self, f16, fl, dd) -> int:
        start = self.used
        self._parts.append((f16, fl, dd))
        self.used += len(dd)
        return start

    def append_join(self, jd, jp) -> int:
        start = self.jused
        self._jparts.append((jd, jp))
        self.jused += len(jd)
        return start

    def append_pmax(self, pm: np.ndarray) -> int:
        start = self.tused
        self._tparts.append(pm)
        self.tused += len(pm)
        return start

    def materialize(self) -> None:
        if self._parts:
            self.feats16 = np.concatenate(
                [self.feats16] + [p[0] for p in self._parts])
            self.flags = np.concatenate(
                [self.flags] + [p[1] for p in self._parts])
            self.docids = np.concatenate(
                [self.docids] + [p[2] for p in self._parts])
            self._parts = []
        if self._jparts:
            self.jdocids = np.concatenate(
                [self.jdocids] + [p[0] for p in self._jparts])
            self.jpos = np.concatenate(
                [self.jpos] + [p[1] for p in self._jparts])
            self._jparts = []
        if self._tparts:
            self.pmax = np.concatenate([self.pmax] + self._tparts)
            self._tparts = []


class _MeshQueryBatcher:
    """Cross-query batching for the mesh pruned path: concurrent
    single-term searches that share (profile, language, k) ride ONE
    vmapped SPMD dispatch (VERDICT r4 #4 — the unbatched mesh paid one
    full dispatch per query, so 16 searchers serialized; the devstore
    batcher's former/claim/watchdog pattern applies unchanged, shrunk to
    the mesh's needs: one dispatcher is enough because the whole mesh is
    one program)."""

    WATCHDOG_S = 2.0
    MAX_BATCH = 8

    def __init__(self, store: "MeshSegmentStore",
                 max_batch: int = MAX_BATCH, pipeline: bool = True):
        import queue as _queue
        self.store = store
        self.max_batch = max_batch
        # lint: unbounded-ok(every queued item is a submitter thread
        # blocked awaiting its reply, so depth is capped by the server
        # thread pool + admission control — devstore._QueryBatcher
        # parity)
        self._q: "_queue.Queue" = _queue.Queue()
        self._stop = False
        # counters mutate UNDER _ctr_lock (devstore parity: the bare
        # `+=` from dispatcher + submitter threads could lose increments)
        self._ctr_lock = threading.Lock()
        self.dispatches = 0
        self.timeouts = 0
        # timeout cause buckets (devstore._QueryBatcher parity; the r5
        # artifacts' lone unexplained `batch_timeouts: 1` motivated
        # attributing every timeout): queue_full = never claimed off the
        # incoming queue; flush_deadline = backlog (forming, in-flight
        # queue wait, or a just-started fetch); worker_stall = wedged in
        # the dispatcher's issue or in a fetch older than a watchdog
        # window (must stay zero in healthy serving — asserted by the
        # batcher stall tests)
        self.timeout_queue_full = 0
        self.timeout_flush_deadline = 0
        self.timeout_worker_stall = 0
        self.exceptions = 0
        # compile-vs-reuse bit of the per-wave stamp (ISSUE 15b,
        # devstore parity): first dispatch of a (kernel, bucket) shape
        # by this batcher pays its jit compile in issue_ms
        self._seen_kernels: set[tuple] = set()
        # pipelined dispatch (devstore parity, shrunk to one completer:
        # the mesh runs ONE SPMD program at a time): the dispatcher
        # ISSUES the first-bucket kernel and hands the in-flight buffer
        # here; the completer fetches, distributes, and walks the rare
        # escalation ladder synchronously. BOUNDED queue: backpressure
        # caps in-flight device memory (hygiene-tested).
        self.pipeline = bool(pipeline)
        self._inflight: "_queue.Queue" = _queue.Queue(maxsize=2)
        self._completer = threading.Thread(target=self._completer_loop,
                                           name="meshstore-completer",
                                           daemon=True)
        self._completer.start()
        self._thread = threading.Thread(target=self._loop,
                                        name="meshstore-batcher",
                                        daemon=True)
        self._thread.start()

    @staticmethod
    def _claim(item: dict, stage: str | None = None) -> bool:
        with item["lk"]:
            if item["taken"]:
                return False
            item["taken"] = True
            if stage is not None:
                item["stage"] = stage
            return True

    def submit(self, termhash: bytes, profile, language: str, kk: int):
        """Blocking; ("ok", scores, docids) | ("prune_fail",) |
        ("ineligible",) | ("timeout",). Traced like the devstore
        batcher: one "mesh.batch" span on the submitter's trace, plus
        the dispatcher-stamped kernel wall as a child span."""
        item = {"th": termhash, "profile": profile, "lang": language,
                "kk": kk, "ev": threading.Event(), "res": ("ineligible",),
                "lk": threading.Lock(), "taken": False}
        sp = tracing.timed("mesh.batch")
        with sp:
            res = self._submit_wait(item)
            km = item.get("kernel_ms")
            # withdrawn dispatch: the solo retry owns the kernel span
            # (the mesh.collective histogram records once per SPMD
            # program in _complete, NOT here — per-query recording
            # would inflate it by the batch factor)
            if km is not None and res[0] != "timeout":
                tracing.emit(f"kernel.{item.get('kernel_name', '?')}",
                             km, batch=item.get("batch_n", 0))
                for stage in ("issue", "device", "fetch"):
                    ms = item.get(f"{stage}_ms")
                    if ms is not None:
                        tracing.record(f"kernel.{stage}", ms)
            sp.set(outcome=res[0])
            wave = item.get("wave")
            if wave is not None:
                # per-wave stamp on the batch span (ISSUE 15b,
                # devstore parity): the tail classifier's evidence
                sp.set(wave_n=wave["n"], wave_occ=wave["occ"],
                       wave_qdepth=wave["qdepth"],
                       wave_compile=wave["compile"],
                       wave_kernel=wave["kernel"],
                       wave_queue_ms=round(
                           item.get("queue_ms", 0.0), 3))
        return res

    def _submit_wait(self, item: dict):
        if tailattr.enabled():
            item["q_depth"] = self._q.qsize()
            item["t_submit"] = time.perf_counter()
        self._q.put(item)
        if item["ev"].wait(timeout=self.WATCHDOG_S):
            return item["res"]
        if self._claim(item):
            # never claimed off the queue: backlog, not a wedge
            with self._ctr_lock:
                self.timeouts += 1
                self.timeout_queue_full += 1
            return ("timeout",)
        if item["ev"].wait(timeout=self.WATCHDOG_S):
            return item["res"]
        with self._ctr_lock:
            self.timeouts += 1
            # devstore attribution parity: stall = wedged in issue or in
            # a fetch older than a watchdog window; in-flight queue wait
            # and fresh fetches are backlog (flush_deadline)
            st = item.get("stage")
            ft = item.get("fetch_t0")
            if st == "dispatch" or (
                    st == "fetch" and ft is not None
                    and time.perf_counter() - ft > self.WATCHDOG_S):
                self.timeout_worker_stall += 1
            else:
                self.timeout_flush_deadline += 1
        return ("timeout",)

    def close(self) -> None:
        import queue as _queue
        self._stop = True
        self._q.put(None)
        try:
            # bounded: a full queue behind a wedged fetch must not hang
            # close() (the completer is a daemon either way)
            self._inflight.put(None, timeout=5.0)
        except _queue.Full:
            pass
        completer = getattr(self, "_completer", None)
        if completer is not None:
            completer.join(timeout=10.0)

    # -- runtime tuning (ISSUE 9: batcher auto-tune, devstore parity) --------

    def tuning(self) -> dict:
        """The mesh runs ONE SPMD program at a time, so the dispatcher
        count is structurally 1; completer depth IS the in-flight bound
        here."""
        with self._ctr_lock:
            dispatches = self.dispatches
        return {"dispatchers": 1,
                "completer_depth": self._inflight.maxsize,
                "queue_incoming": self._q.qsize(),
                "queue_inflight": self._inflight.qsize(),
                "dispatches": dispatches}

    def set_tuning(self, dispatchers: int | None = None,
                   completer_depth: int | None = None) -> dict:
        """Adjust the in-flight bound (the only tunable axis of a
        single-program mesh — `dispatchers` is accepted for surface
        parity and ignored).  Floor 1: the minimal still-flowing
        configuration, never a wedge."""
        if completer_depth is not None:
            new_max = max(1, int(completer_depth))
            with self._inflight.mutex:
                self._inflight.maxsize = new_max
                self._inflight.not_full.notify_all()
        return self.tuning()

    @staticmethod
    def _bucket(n: int) -> int:
        return 1 if n <= 1 else (4 if n <= 4 else _MeshQueryBatcher
                                 .MAX_BATCH)

    def _loop(self) -> None:
        import queue as _queue
        while True:
            item = self._q.get()
            if item is None:
                return
            if not self._claim(item, stage="form"):
                continue
            batch = [item]
            while len(batch) < self.max_batch:
                try:
                    nxt = self._q.get_nowait()
                except _queue.Empty:
                    break
                if nxt is None:
                    self._q.put(None)
                    break
                if self._claim(nxt, stage="form"):
                    batch.append(nxt)
            for it in batch:    # timeout attribution: now dispatching
                it["stage"] = "dispatch"
            try:
                self._dispatch(batch)
            except Exception:
                with self._ctr_lock:
                    self.exceptions += 1
                log.exception("mesh batch dispatch failed (%d queries "
                              "retry solo)", len(batch))
                for it in batch:
                    # issued items belong to the completer (forcing them
                    # ineligible here would double-dispatch the query)
                    if not it.get("issued") and not it["ev"].is_set():
                        it["res"] = ("ineligible",)
                        it["ev"].set()

    def _dispatch(self, batch: list[dict]) -> None:
        """Issue-only half of the pipelined dispatch: groups the batch,
        ISSUES each group's first-bucket SPMD kernel (async dispatch)
        and hands the in-flight buffers to the completer — the
        dispatcher is back forming the next wave while this one's round
        trip is in the air."""
        store = self.store
        with store._lock:
            arrays = store._device_arrays()
            dead = store._dead_array()
            pmax = store._dev_pmax
            spans = {it["th"]: store.spans_for(it["th"]) for it in batch}
        with store.rwi._lock:
            tomb = len(store.rwi._tombstones)
            has_delta = {th: bool(store.rwi._ram.get(th))
                         for th in spans}
        groups: dict[tuple, list[dict]] = {}
        for it in batch:
            sp = spans[it["th"]]
            if (sp is None or len(sp) != 1 or sp[0].tcounts is None
                    or sp[0].tcounts.max() <= 0
                    or sp[0].dead_seq != tomb or has_delta[it["th"]]):
                it["ev"].set()       # ("ineligible",): caller goes solo
                continue
            it["span"] = sp[0]
            key = (it["profile"].to_external_string(), it["lang"],
                   it["kk"])
            groups.setdefault(key, []).append(it)
        for (_, lang, kk), items in groups.items():
            prof = items[0]["profile"]
            consts = store._profile_consts(prof, lang)
            shift, lang_term = prune_bound_consts(prof)
            bs = self._bucket(len(items))
            nc = store.n_cells
            qargs = np.zeros((nc, bs, 4), np.int32)   # pad: count 0
            cmin = np.zeros((bs, P.NF), np.int32)
            cmax = np.zeros((bs, P.NF), np.int32)
            tmin = np.zeros(bs, np.float32)
            tmax = np.zeros(bs, np.float32)
            for i, it in enumerate(items):
                sp = it["span"]
                qargs[:, i, 0] = sp.starts
                qargs[:, i, 1] = sp.counts
                qargs[:, i, 2] = sp.tstarts
                qargs[:, i, 3] = sp.tcounts
                cmin[i] = sp.stats["col_min"]
                cmax[i] = sp.stats["col_max"]
                tmin[i] = sp.stats["tf_min"]
                tmax[i] = sp.stats["tf_max"]
            t0k = time.perf_counter()
            out = store._pbfn(kk, _PRUNE_B[0], bs)(
                *arrays, dead, pmax, qargs, cmin, cmax, tmin, tmax,
                shift, lang_term, *consts)
            rec = {"out": out, "items": items, "qargs": qargs,
                   "stats": (cmin, cmax, tmin, tmax),
                   "consts": consts, "shift": shift,
                   "lang_term": lang_term, "kk": kk, "bs": bs,
                   "arrays": arrays, "dead": dead, "pmax": pmax,
                   "t0k": t0k,
                   "issue_ms": (time.perf_counter() - t0k) * 1000.0}
            if tailattr.enabled():
                kkey = ("_mesh_pruned_kernel", kk, bs)
                with self._ctr_lock:
                    first_use = kkey not in self._seen_kernels
                    self._seen_kernels.add(kkey)
                tailattr.stamp_wave(items, "_mesh_pruned_kernel",
                                    self.max_batch, first_use,
                                    rec["issue_ms"])
            for it in items:
                it["stage"] = "inflight"   # issued, awaiting the completer
                it["issued"] = True        # the completer owns the answer
            if self.pipeline:
                self._inflight.put(rec)
            else:
                self._complete(rec)

    def _completer_loop(self) -> None:
        while True:
            rec = self._inflight.get()
            if rec is None:
                return
            self._complete(rec)

    def _complete(self, rec: dict) -> None:
        """Blocking half: fetch the in-flight first-bucket result (ONE
        packed transfer), distribute, and walk the rare escalation
        ladder synchronously for any slot whose bound failed."""
        store = self.store
        items = rec["items"]
        kk, bs = rec["kk"], rec["bs"]
        qargs = rec["qargs"]
        cmin, cmax, tmin, tmax = rec["stats"]
        pending = list(range(len(items)))
        out, t0k = rec["out"], rec["t0k"]
        issued_at = t0k + rec["issue_ms"] / 1e3
        try:
            for b in _PRUNE_B:
                if out is None:     # escalation bucket: issue inline
                    t0k = time.perf_counter()
                    out = store._pbfn(kk, b, bs)(
                        *rec["arrays"], rec["dead"], rec["pmax"], qargs,
                        cmin, cmax, tmin, tmax, rec["shift"],
                        rec["lang_term"], *rec["consts"])
                    issued_at = time.perf_counter()
                tf0 = time.perf_counter()
                for it in items:   # timeout attribution: fetch running
                    it["fetch_t0"] = tf0
                    it["stage"] = "fetch"
                host = store.device_fetch(out)   # ONE packed fetch
                out = None
                store.count_round_trip()
                fetch_ms = (time.perf_counter() - tf0) * 1000.0
                device_ms = (tf0 - issued_at) * 1000.0
                s = host[:, :kk]
                d = host[:, kk:2 * kk]
                ok = host[:, 2 * kk] != 0
                wall_ms = (time.perf_counter() - t0k) * 1000.0
                # ONE record per SPMD program execution — recording at
                # the submitters would inflate count/sum by the batch
                # factor (every batched query carries the same wall)
                histogram.observe("mesh.collective", wall_ms)
                with self._ctr_lock:
                    self.dispatches += 1
                with store._lock:   # completer + query threads write
                    store.prune_rounds += 1
                still = []
                for i in pending:
                    if bool(ok[i]):
                        sp = items[i]["span"]
                        with store._lock:
                            store.pruned_tiles += int(
                                np.maximum(sp.tcounts - b, 0).sum())
                        items[i]["res"] = ("ok", s[i], d[i])
                        items[i]["kernel_ms"] = wall_ms
                        items[i]["kernel_name"] = "_mesh_pruned_kernel"
                        items[i]["batch_n"] = len(items)
                        items[i]["issue_ms"] = rec["issue_ms"]
                        items[i]["device_ms"] = device_ms
                        items[i]["fetch_ms"] = fetch_ms
                        items[i]["ev"].set()
                        # satisfied slot becomes a free pad slot for the
                        # escalation rounds (count/tcount 0): the next
                        # bucket must not re-score it
                        qargs[:, i, :] = 0
                    else:
                        still.append(i)
                pending = still
                if not pending:
                    break
            for i in pending:          # bound never held: solo full scan
                items[i]["res"] = ("prune_fail",)
                items[i]["ev"].set()
        except Exception:
            with self._ctr_lock:
                self.exceptions += 1
            log.exception("mesh batch completion failed (%d queries "
                          "retry solo)", len(items))
            for it in items:
                if not it["ev"].is_set():
                    it["res"] = ("ineligible",)
                    it["ev"].set()


class MeshSegmentStore:
    """Span registry + SPMD query dispatch over a sharded arena.

    Drop-in for ``DeviceSegmentStore`` behind ``Segment.devstore``: same
    RWI listener protocol, same ``rank_term``/``rank_join`` signatures,
    chosen by the Switchboard whenever the host has more than one device.
    """

    MAX_SPANS = 8   # matches the RWI merge policy's max_runs
    # SearchEvent's small-candidate gate threshold; None = the default
    # (ops/ranking.SMALL_RANK_N). A store whose measured dispatch floor
    # is small can lower it.
    small_rank_n: int | None = None

    def __init__(self, rwi, devices=None, n_term: int = 1,
                 budget_bytes: int = 2 << 30):
        devs = list(devices) if devices is not None else list(jax.devices())
        if n_term < 1 or len(devs) % n_term:
            raise ValueError(f"{len(devs)} devices not divisible by "
                             f"n_term={n_term}")
        self.n_term = n_term
        self.n_doc = len(devs) // n_term
        self.n_cells = len(devs)
        self.mesh = Mesh(np.asarray(devs).reshape(self.n_term, self.n_doc),
                         axis_names=("term", "doc"))
        # TRUE multi-process SPMD mode (ISSUE 12): the mesh spans devices
        # owned by OTHER OS processes (jax.distributed).  Every process
        # runs this same store over identical host mirrors; collectives
        # cross process boundaries.  Two local conveniences must then be
        # OFF, because they make collective-entry decisions from
        # process-local state (thread timing, cache residency) and a
        # process skipping — or adding — one SPMD program while its
        # peers run it deadlocks the whole mesh:
        #   * the cross-query batcher (enable_batching becomes a no-op);
        #   * the versioned top-k result cache (get/put are skipped).
        # Step ordering is owned by parallel/distributed.py's two-phase
        # scatter/commit protocol instead.
        self.multiprocess = any(
            getattr(d, "process_index", 0) != _my_process_index()
            for d in devs)
        self.rwi = rwi
        self.budget_bytes = budget_bytes
        # probed on a device THIS process owns (a multi-process mesh
        # lists other members' devices first)
        self.device_row_bytes = measure_row_bytes(
            next(d for d in devs
                 if getattr(d, "process_index", 0) == _my_process_index()))
        self._cells = [_CellBuf() for _ in range(self.n_cells)]
        self._packed: dict[int, dict[bytes, MeshSpan]] = {}
        self._lock = threading.RLock()
        self._garbage_rows = 0
        self.queries_served = 0
        self.fallbacks = 0
        # the join coverage partition (devstore parity): every
        # join-shaped query rank_join is asked lands in exactly one
        self.join_served = 0
        self.join_fallbacks = 0
        # device-loss recovery (ISSUE 10c, devstore parity): a streak of
        # retry-exhausted transfers declares the MESH lost (any one chip
        # or its interconnect failing fails the whole SPMD program);
        # queries host-serve, and the rebuild re-uploads every cell from
        # the host mirrors (_CellBuf) once a probe round-trips
        self.device_lost = False
        self.device_losses = 0
        self.device_loss_recoveries = 0
        self.device_lost_queries = 0
        self.transfer_failures = 0
        self.transfer_retries = 0
        self._transfer_fail_streak = 0
        self.loss_streak = LOSS_STREAK
        self.transfer_retry_limit = TRANSFER_RETRIES
        self.rebuild_backoff_s = 0.5
        self._rebuild_thread: threading.Thread | None = None
        # versioned top-k result cache + its epoch (devstore parity):
        # bumps on every flush/merge/repack/delete so a cached answer is
        # served only against the snapshot it was computed on
        self.arena_epoch = 0
        self._topk_cache = _TopkCache()
        self.device_round_trips = 0
        # device state (rebuilt lazily from the host mirrors)
        self._dev_arrays = None       # (feats16, flags, docids) sharded
        self._dev_join = None         # (jdocids, jpos) sharded
        self._dev_pmax = None         # per-cell prune side-table
        self._dirty = True
        self.prune_rounds = 0
        self.pruned_tiles = 0
        self._dead_host = np.zeros(1 << 16, bool)
        self._dev_dead = None
        self._dirty_dead = True
        self._consts = None
        self._profile_key = None
        self._fns: dict[tuple, object] = {}
        self._jfns: dict[tuple, object] = {}
        self._batcher: _MeshQueryBatcher | None = None
        for docid in rwi._tombstones:
            self.mark_dead(docid)
        for run in list(rwi._runs):
            self.on_run_added(run)
        rwi.listener = self

    # -- placement math ------------------------------------------------------

    def _cell_of(self, t: int, d: int) -> int:
        return t * self.n_doc + d

    def row_bytes(self) -> int:
        return P.NF * 2 + 4 + 4

    def _would_fit(self, extra_rows: int) -> bool:
        # worst case the whole run lands on one cell; budget the padded
        # global buffer that cell size would force, in the bytes a row
        # really occupies on these devices (devstore.measure_row_bytes)
        worst = max(c.used for c in self._cells) + extra_rows
        cap = _bucket_rows(worst + TILE) + TILE
        return (cap * self.n_cells * self.device_row_bytes
                <= self.budget_bytes)

    # -- packing (listener protocol) ----------------------------------------

    def _bump_epoch(self) -> None:
        with self._lock:
            self.arena_epoch += 1

    def count_round_trip(self) -> None:
        with self._lock:
            self.device_round_trips += 1

    def on_run_added(self, run) -> None:
        # epoch bumps land AFTER their mutation (devstore parity): a
        # racing result-cache insert is then born-stale, never live-stale
        try:
            self._on_run_added_inner(run)
        except CorruptRunError as e:
            # corrupt span found while packing: quarantine instead of
            # crashing the flush/startup path (devstore parity)
            log.error("corrupt run during mesh pack: %s", e)
            self.rwi._quarantine_run(run, e)
        finally:
            self._bump_epoch()

    def _on_run_added_inner(self, run) -> None:
        with self._lock:
            rid = id(run)
            if rid in self._packed:
                return
            rows = run.n_postings
            if rows == 0:
                self._packed[rid] = {}
                return
            if not self._would_fit(rows):
                track(EClass.INDEX, "meshstore_skip", rows)
                return
            spans: dict[bytes, MeshSpan] = {}
            for th in list(run.term_hashes()):
                p = run.get(th)
                if p is None or len(p) == 0:
                    continue
                f16, fl = compact_feats(p.feats)
                dd = p.docids.astype(np.int32)
                # GLOBAL frozen stats + proxy scores over the WHOLE term:
                # all cells prune/score in one normalized space, and the
                # per-device tail bound stays a true upper bound
                gstats, proxy = pack_prune_stats(f16, fl, p.feats)
                t = term_shard(th, self.n_term)
                d_shard = dd % self.n_doc
                starts = np.zeros(self.n_cells, np.int32)
                counts = np.zeros(self.n_cells, np.int32)
                jstarts = np.zeros(self.n_cells, np.int32)
                tstarts = np.zeros(self.n_cells, np.int32)
                tcounts = np.zeros(self.n_cells, np.int32)
                for d in range(self.n_doc):
                    sel = d_shard == d
                    n = int(sel.sum())
                    if n == 0:
                        continue
                    cell = self._cell_of(t, d)
                    buf = self._cells[cell]
                    # rows pack PROXY-SORTED (block-max prune layout)
                    order = proxy_order(proxy[sel])
                    cell_dd = dd[sel][order]
                    start = buf.append(f16[sel][order], fl[sel][order],
                                       cell_dd)
                    n_tiles = (n + TILE - 1) // TILE
                    tstarts[cell] = buf.append_pmax(
                        pmax_table(proxy[sel][order]))
                    tcounts[cell] = n_tiles
                    # column-local docid-sorted view (device join table):
                    # the j-th PACKED posting sits at cell row start+j
                    jorder = np.argsort(cell_dd, kind="stable")
                    jstarts[cell] = buf.append_join(
                        cell_dd[jorder].astype(np.int32),
                        (start + jorder).astype(np.int32))
                    starts[cell], counts[cell] = start, n
                spans[th] = MeshSpan(starts, counts, jstarts,
                                     tstarts, tcounts, gstats,
                                     getattr(run, "dead_seq", -1))
            self._packed[rid] = spans
            self._dirty = True
            track(EClass.INDEX, "meshstore_pack", rows)
        # crawl-to-searchable `ingest.device` tier (ISSUE 13a): the
        # run's rows are packed into the mesh cells — on a mesh node
        # this IS the device tier (rwi.flush attaches stamps to every
        # run; without this pop the bounded run-stamp FIFO would age
        # every entry out through stamps_dropped on healthy nodes)
        ingest_slo.TRACKER.device_packed(run)

    def on_run_removed(self, run) -> None:
        with self._lock:
            spans = self._packed.pop(id(run), None)
            if spans:
                self._garbage_rows += sum(sp.total for sp in spans.values())
            self._bump_epoch()
            used = sum(c.used for c in self._cells)
            if (self._garbage_rows * 2 > max(used, 1)
                    and self._garbage_rows > 4 * TILE):
                self.repack()

    def on_run_swapped(self, old_run, new_run) -> None:
        with self._lock:
            spans = self._packed.pop(id(old_run), None)
            if spans is not None:
                live = set(new_run.term_hashes())
                self._packed[id(new_run)] = {
                    th: sp for th, sp in spans.items() if th in live}
            self._bump_epoch()

    def on_doc_deleted(self, docid: int) -> None:
        self.mark_dead(docid)

    def on_term_dropped(self, run, termhash: bytes) -> None:
        with self._lock:
            spans = self._packed.get(id(run))
            if spans is not None:
                sp = spans.pop(termhash, None)
                if sp is not None:
                    self._garbage_rows += sp.total
            self._bump_epoch()

    def mark_dead(self, docid: int) -> None:
        with self._lock:
            if docid >= len(self._dead_host):
                cap = len(self._dead_host)
                while cap <= docid:
                    cap *= 2
                grown = np.zeros(cap, bool)
                grown[:len(self._dead_host)] = self._dead_host
                self._dead_host = grown
            self._dead_host[docid] = True
            self._dirty_dead = True
            self._bump_epoch()

    def live_rows(self) -> int:
        with self._lock:
            return sum(sp.total for spans in self._packed.values()
                       for sp in spans.values())

    def repack(self) -> None:
        with self._lock:
            self._cells = [_CellBuf() for _ in range(self.n_cells)]
            self._packed.clear()
            self._garbage_rows = 0
            self._dirty = True
            for run in list(self.rwi._runs):
                self.on_run_added(run)      # bumps the epoch per run
            self._bump_epoch()              # incl. the zero-run rebuild

    def enable_batching(self, max_batch: int = 8,
                        pipeline: bool = True, **_kw) -> None:
        """Cross-query batching for the pruned path (r5): concurrent
        eligible searches share one vmapped SPMD dispatch, now issued
        asynchronously and fetched by a completer (devstore parity).
        Extra devstore kwargs (dispatchers, completer_depth) are
        accepted and ignored — the mesh runs one program, so one
        dispatcher + one completer drain the queue.

        Multi-process mode: NO-OP.  Batch grouping is thread-timing
        dependent, so two processes would batch different query sets and
        enter different SPMD programs — a deadlock, not a perf bug.  The
        distributed runtime serializes steps instead (ISSUE 12)."""
        if self.multiprocess:
            return
        if self._batcher is None:
            self._batcher = _MeshQueryBatcher(
                self, max_batch=min(max_batch,
                                    _MeshQueryBatcher.MAX_BATCH),
                pipeline=pipeline)

    def rank_cache_get(self, termhash: bytes, profile,
                       language: str = "en", k: int = 100):
        """Versioned top-k cache lookup (devstore parity): the full
        final answer of a previous identical query, valid only while the
        arena epoch is unchanged and the term carries no RAM delta.

        Multi-process mode: always a miss — a cache hit would skip the
        committed collective this process's peers are entering."""
        if self.multiprocess:
            return None
        kk = max(16, 1 << (max(k, 1) - 1).bit_length())
        key = (termhash, profile.to_external_string(), language, kk)
        with self.rwi._lock:
            if self.rwi._ram.get(termhash):
                return None
        with self._lock:
            epoch = self.arena_epoch
        got = self._topk_cache.get(key, epoch)
        if got is None:
            return None
        s, d, considered = got
        with self._lock:
            self.queries_served += 1
        return s[:k], d[:k], considered

    # -- device-loss recovery (ISSUE 10c, devstore parity) -------------------

    def device_fetch(self, out):
        """``jax.device_get`` with transfer-failure classification —
        same ladder as ``DeviceSegmentStore.device_fetch``."""
        delay = TRANSFER_BACKOFF_S
        for attempt in range(self.transfer_retry_limit + 1):
            try:
                if faultinject.take("device.transfer_fail"):
                    raise DeviceTransferError(
                        "injected device.transfer_fail")
                host = jax.device_get(out)
            except Exception as e:
                if attempt < self.transfer_retry_limit:
                    with self._lock:
                        self.transfer_retries += 1
                    time.sleep(delay)
                    delay *= 2
                    continue
                self._note_transfer_failure(e)
                raise DeviceTransferError(
                    f"mesh transfer failed after "
                    f"{self.transfer_retry_limit + 1} attempts: "
                    f"{e!r}") from e
            with self._lock:
                self._transfer_fail_streak = 0
            return host
        raise DeviceTransferError(
            "unreachable: empty retry ladder")   # retry_limit < 0 guard

    def _note_transfer_failure(self, err) -> None:
        declare = False
        with self._lock:
            self.transfer_failures += 1
            self._transfer_fail_streak += 1
            if (not self.device_lost
                    and self._transfer_fail_streak >= self.loss_streak):
                declare = True
        if declare:
            self._declare_device_loss(err)

    def _declare_device_loss(self, err) -> None:
        with self._lock:
            if self.device_lost:
                return
            self.device_lost = True
            self.device_losses += 1
            self._transfer_fail_streak = 0
        self._bump_epoch()
        log.error("MESH LOST after %d consecutive failed transfers "
                  "(%r): serving host-fallback; background rebuild "
                  "started", self.loss_streak, err)
        track(EClass.INDEX, "device_loss", 1)
        self.start_rebuild()

    def start_rebuild(self) -> None:
        with self._lock:
            if not self.device_lost:
                return
            t = self._rebuild_thread
            if t is not None and t.is_alive():
                return
            t = threading.Thread(target=self._rebuild_loop,
                                 name="meshstore-rebuild", daemon=True)
            self._rebuild_thread = t
        t.start()

    def _rebuild_loop(self) -> None:
        delay = self.rebuild_backoff_s
        while True:
            with self._lock:
                if not self.device_lost:
                    return
            time.sleep(delay)
            delay = min(delay * 2, 30.0)
            try:
                if faultinject.take("device.transfer_fail"):
                    raise DeviceTransferError(
                        "injected device.transfer_fail")
                # multi-process: probe THIS process's own devices only —
                # a mesh-wide device_put from one process alone would
                # strand it in a collective its peers never enter (the
                # peers keep serving; only OUR shard's health is ours
                # to probe)
                if self.multiprocess:
                    mine = [d for d in self.mesh.devices.flat
                            if getattr(d, "process_index", 0)
                            == _my_process_index()]
                    probe = jax.device_put(np.zeros(1, np.int32),
                                           mine[0])
                else:
                    probe = jax.device_put(np.zeros(1, np.int32),
                                           NamedSharding(self.mesh, PS()))
                jax.device_get(probe)
            except Exception as e:
                log.warning("mesh rebuild probe failed: %r", e)
                continue
            # drop every device buffer under a SHORT lock; the host
            # mirrors (_CellBuf) are the source of truth and the lazy
            # `_device_arrays()` path re-uploads on the first device
            # query — exactly what every flush already does.  Holding
            # the lock across the full multi-second re-upload here
            # would stall the very host-fallback queries the loss mode
            # promises to keep answering.
            with self._lock:
                self._dev_arrays = None
                self._dev_join = None
                self._dev_pmax = None
                self._dev_dead = None
                self._dirty = True
                self._dirty_dead = True
            with self._lock:
                self.device_lost = False
                self.device_loss_recoveries += 1
                self._transfer_fail_streak = 0
            self._bump_epoch()
            log.warning("mesh serving RESUMED after rebuild "
                        "(recovery #%d)", self.device_loss_recoveries)
            track(EClass.INDEX, "device_recovery", 1)
            return

    def counters(self) -> dict:
        """Serving-health counters (devstore interface parity)."""
        b = self._batcher
        with self._lock:     # reentrant: one consistent counter view
            return {
                "queries_served": self.queries_served,
                "fallbacks": self.fallbacks,
                "join_served": self.join_served,
                "join_fallbacks": self.join_fallbacks,
                "device_lost": 1 if self.device_lost else 0,
                "device_losses": self.device_losses,
                "device_loss_recoveries": self.device_loss_recoveries,
                "device_lost_queries": self.device_lost_queries,
                "transfer_failures": self.transfer_failures,
                "transfer_retries": self.transfer_retries,
                "rank_cache_hits": self._topk_cache.hits,
                "rank_cache_stale": self._topk_cache.stale,
                "rank_cache_stale_served": self._topk_cache.stale_served,
                "arena_epoch": self.arena_epoch,
                "device_round_trips": self.device_round_trips,
                "prune_rounds": self.prune_rounds,
                "pruned_tiles": self.pruned_tiles,
                "batch_dispatches": b.dispatches if b else 0,
                "batch_timeouts": b.timeouts if b else 0,
                "batch_timeout_queue_full":
                    b.timeout_queue_full if b else 0,
                "batch_timeout_flush_deadline":
                    b.timeout_flush_deadline if b else 0,
                "batch_timeout_worker_stall":
                    b.timeout_worker_stall if b else 0,
                "batch_exceptions": b.exceptions if b else 0,
            }

    def close(self) -> None:
        if self._batcher is not None:
            self._batcher.close()
            self._batcher = None
        if self.rwi.listener is self:
            self.rwi.listener = None

    # -- device sync ---------------------------------------------------------

    def _put(self, arr, spec):
        """Upload a host array under `spec` over the store's mesh.

        Single-process: plain ``jax.device_put``.  Multi-process:
        ``jax.make_array_from_callback`` — each process materializes
        ONLY its addressable shards, with NO cross-process transfer.
        This is load-bearing, not an optimization: ``device_put`` onto
        a multi-process sharding issues an implicit collective, so any
        upload one process runs alone (the post-recovery re-upload, the
        rebuild probe) would strand that process inside a gloo
        all-reduce its peers never enter.  The host mirrors are
        identical on every process by the SPMD corpus contract, so the
        callback's local reads reconstruct the same global array."""
        sh = NamedSharding(self.mesh, spec)
        if not self.multiprocess:
            return jax.device_put(arr, sh)
        arr = np.asarray(arr)
        return jax.make_array_from_callback(arr.shape, sh,
                                            lambda idx: arr[idx])

    def _sync_device(self):
        """Rebuild the sharded global arrays from the host mirrors.

        Runs once per flush/merge (packs are rare); queries between packs
        reuse the placed buffers — steady-state per-query traffic is the
        span descriptor vector only."""
        for c in self._cells:
            c.materialize()
        C = _bucket_rows(max(max(c.used for c in self._cells), 1)
                         + TILE) + TILE
        feats = np.zeros((self.n_cells, C, P.NF), np.int16)
        flags = np.zeros((self.n_cells, C), np.int32)
        docids = np.full((self.n_cells, C), -1, np.int32)
        for i, c in enumerate(self._cells):
            feats[i, :c.used] = c.feats16
            flags[i, :c.used] = c.flags
            docids[i, :c.used] = c.docids
        # join-table width pads to twice the bucket of the largest cell:
        # a query's static membership window (bucket of the segment
        # size) must fit after ANY segment start — lo + bucket(seg) <=
        # jused + bucket(jused) <= 2*bucket(jused) — so windows never
        # overrun (dynamic_slice would clamp the start and misalign)
        JC = 2 * _bucket_rows(
            max(max((c.jused for c in self._cells), default=1), 1))
        jdocids = np.full((self.n_cells, JC), INT32_MAX, np.int32)
        jpos = np.zeros((self.n_cells, JC), np.int32)
        for i, c in enumerate(self._cells):
            jdocids[i, :c.jused] = c.jdocids
            jpos[i, :c.jused] = c.jpos
        TC = max(max((c.tused for c in self._cells), default=1), 1)
        pmax = np.full((self.n_cells, TC), INT32_MAX, np.int32)
        for i, c in enumerate(self._cells):
            pmax[i, :c.tused] = c.pmax
        sp3 = PS(("term", "doc"), None, None)
        sp2 = PS(("term", "doc"), None)
        self._dev_arrays = (self._put(feats, sp3),
                            self._put(flags, sp2),
                            self._put(docids, sp2))
        self._dev_join = (self._put(jdocids, sp2),
                          self._put(jpos, sp2))
        self._dev_pmax = self._put(pmax, sp2)
        self._dirty = False

    def _device_arrays(self):
        if self._dirty or self._dev_arrays is None:
            self._sync_device()
        return self._dev_arrays

    def _dead_array(self):
        with self._lock:     # reentrant: rank paths already hold it
            if self._dirty_dead or self._dev_dead is None:
                self._dev_dead = self._put(self._dead_host, PS())
                self._dirty_dead = False
            return self._dev_dead

    def _profile_consts(self, profile, language: str):
        key = (profile.to_external_string(), language)
        with self._lock:
            if self._profile_key != key:
                put = lambda a: self._put(np.asarray(a), PS())  # noqa: E731
                bits, shifts = profile.flag_coeffs()
                self._consts = (put(profile.norm_coeffs()), put(bits),
                                put(shifts),
                                put(np.int32(profile.domlength)),
                                put(np.int32(profile.tf)),
                                put(np.int32(profile.language)),
                                put(np.int32(profile.authority)),
                                put(np.int32(P.pack_language(language))))
                self._profile_key = key
            return self._consts

    # -- query dispatch ------------------------------------------------------

    def spans_for(self, termhash: bytes) -> list[MeshSpan] | None:
        with self._lock:
            out: list[MeshSpan] = []
            for run in list(self.rwi._runs):
                if not run.has(termhash):
                    continue
                spans = self._packed.get(id(run))
                if spans is None:
                    return None
                sp = spans.get(termhash)
                if sp is None:
                    return None
                out.append(sp)
            return out

    def _pfn(self, kk: int, b: int):
        key = ("pruned", kk, b)
        if key not in self._fns:
            self._fns[key] = jax.jit(jax.shard_map(
                partial(_mesh_pruned_shard, k=kk, b=b),
                mesh=self.mesh,
                in_specs=(PS(("term", "doc"), None, None),   # feats16
                          PS(("term", "doc"), None),         # flags
                          PS(("term", "doc"), None),         # docids
                          PS(),                              # dead
                          PS(("term", "doc"), None),         # pmax
                          PS(("term", "doc"), None),         # qargs
                          PS(), PS(), PS(), PS(),            # frozen stats
                          PS(), PS(),                        # shift, lang
                          PS(), PS(), PS(), PS(), PS(), PS(), PS(), PS()),
                out_specs=(PS(), PS(), PS()),
                check_vma=False,
            ))
        return self._fns[key]

    def _pbfn(self, kk: int, b: int, bs: int):
        key = ("pruned_batch", kk, b, bs)
        if key not in self._fns:
            fn = jax.shard_map(
                partial(_mesh_pruned_batch_shard, k=kk, b=b),
                mesh=self.mesh,
                in_specs=(PS(("term", "doc"), None, None),   # feats16
                          PS(("term", "doc"), None),         # flags
                          PS(("term", "doc"), None),         # docids
                          PS(),                              # dead
                          PS(("term", "doc"), None),         # pmax
                          PS(("term", "doc"), None, None),   # qargs [C,bs,4]
                          PS(), PS(), PS(), PS(),            # per-q stats
                          PS(), PS(),                        # shift, lang
                          PS(), PS(), PS(), PS(), PS(), PS(), PS(), PS()),
                out_specs=(PS(), PS(), PS()),
                check_vma=False,
            )

            # packed [bs, 2k+1] output (scores ++ docids ++ ok): the
            # batch path fetches ONE replicated buffer per wave instead
            # of three (each separately fetched array is a round trip)
            def packed(*args, _fn=fn):
                s, d, ok = _fn(*args)
                return jnp.concatenate(
                    [s, d, ok[:, None].astype(jnp.int32)], axis=1)

            self._fns[key] = jax.jit(packed)
        return self._fns[key]

    def _fn(self, kk: int, with_delta: bool):
        key = (kk, with_delta)
        if key not in self._fns:
            self._fns[key] = jax.jit(jax.shard_map(
                partial(_mesh_rank_shard, k=kk, with_delta=with_delta),
                mesh=self.mesh,
                in_specs=(PS(("term", "doc"), None, None),   # feats16
                          PS(("term", "doc"), None),         # flags
                          PS(("term", "doc"), None),         # docids
                          PS(("term", "doc"), None),         # starts
                          PS(("term", "doc"), None),         # counts
                          PS(),                              # dead
                          PS(), PS(), PS(),                  # delta
                          PS(),                              # qfilters
                          PS(), PS(), PS(), PS(), PS(), PS(), PS(), PS()),
                out_specs=(PS(), PS()),
                check_vma=False,   # replicated by the all_gather+top_k
            ))
        return self._fns[key]

    def rank_term(self, termhash: bytes, profile, language: str = "en",
                  k: int = 100,
                  lang_filter: int = NO_LANG, flag_bit: int = NO_FLAG,
                  from_days: int | None = None, to_days: int | None = None):
        """Single-term ranked top-k as one SPMD program over the mesh.

        Same contract as ``DeviceSegmentStore.rank_term``: returns
        (scores, docids, considered) or None for host fallback — and
        None (counted) while the mesh is declared lost or a transfer
        dies under this query (ISSUE 10c): NEVER an exception."""
        # lint: unlocked-ok(racy bool read by design: a stale False
        # costs one failed transfer that re-classifies; locking here
        # would serialize every rank entry behind store mutations)
        if self.device_lost:
            with self._lock:
                self.device_lost_queries += 1
                self.fallbacks += 1
            tracing.emit(tailattr.MARKER_HOST_FALLBACK, 0.0,
                         why="device_lost")
            return None
        try:
            return self._rank_term_impl(termhash, profile, language, k,
                                        lang_filter, flag_bit,
                                        from_days, to_days)
        except DeviceTransferError:
            with self._lock:
                self.device_lost_queries += 1
                self.fallbacks += 1
            tracing.emit(tailattr.MARKER_HOST_FALLBACK, 0.0,
                         why="transfer_fail")
            return None

    def rank_term_mp(self, termhash: bytes, profile,
                     language: str = "en", k: int = 100):
        """Committed-entry rank for the multi-process runtime
        (parallel/distributed.py).  The two-phase scatter/commit
        protocol has decided that EVERY process enters this step's
        collective, so the local ``device_lost`` early-return of
        ``rank_term`` must NOT apply here — a process that skips a
        committed SPMD program strands its peers inside the collective
        (the hang the protocol exists to prevent).  A process whose
        device is genuinely failing still participates in the dispatch;
        only its own fetch fails, which degrades THIS process to the
        host answer (counted) while the others complete normally.
        Returns None for host fallback; NEVER raises, NEVER hangs
        beyond the collective's own bounded timeout."""
        try:
            return self._rank_term_impl(termhash, profile, language, k)
        except DeviceTransferError:
            with self._lock:
                self.device_lost_queries += 1
                self.fallbacks += 1
            tracing.emit(tailattr.MARKER_HOST_FALLBACK, 0.0,
                         why="transfer_fail")
            return None
        except Exception:
            # a mid-collective failure (a peer process died underneath
            # the gather) surfaces as a runtime error after the
            # collective's timeout: degrade to host, never crash the
            # serving loop (the coordinator will mark the member down
            # on its next scatter and stop committing collectives)
            log.exception("multi-process mesh rank failed; host fallback")
            with self._lock:
                self.fallbacks += 1
            return None

    def _rank_term_impl(self, termhash: bytes, profile,
                        language: str = "en", k: int = 100,
                        lang_filter: int = NO_LANG,
                        flag_bit: int = NO_FLAG,
                        from_days: int | None = None,
                        to_days: int | None = None):
        cacheable = (lang_filter == NO_LANG and flag_bit == NO_FLAG
                     and from_days is None and to_days is None)
        if cacheable:
            got = self.rank_cache_get(termhash, profile, language, k)
            if got is not None:
                return got
        with self._lock:
            spans = self.spans_for(termhash)
            if spans is None or len(spans) > self.MAX_SPANS:
                self.fallbacks += 1
                return None
            arrays = self._device_arrays()
            dead = self._dead_array()
            pmax = self._dev_pmax     # same snapshot as the arrays
            epoch0 = self.arena_epoch
        with self.rwi._lock:
            delta = self.rwi._ram_postings(termhash)
        if not spans and delta is None:
            return np.empty(0, np.int32), np.empty(0, np.int32), 0
        with_delta = delta is not None and len(delta) > 0
        considered = sum(sp.total for sp in spans) + (
            len(delta) if with_delta else 0)
        kk0 = max(16, 1 << (max(k, 1) - 1).bit_length())

        def cache_put(s, d):
            """Insert the FINAL (post keep/dedup) answer under the
            snapshot's epoch (a concurrent flush leaves it born-stale)."""
            if cacheable and not with_delta and not self.multiprocess:
                self._topk_cache.put(
                    (termhash, profile.to_external_string(), language,
                     kk0), epoch0, np.asarray(s), np.asarray(d),
                    considered)

        # per-cell block-max PRUNED path: one merged span, no delta, no
        # constraint filters, no tombstones newer than the pack. Each
        # device scores a prefix of its proxy-sorted tiles and verifies
        # its OWN tail bound against its LOCAL k-th score — exact local
        # top-k per device makes the global merge exact; a failed bound
        # on any device escalates the prefix for all.
        no_filters = (lang_filter == NO_LANG and flag_bit == NO_FLAG
                      and from_days is None and to_days is None)
        if (no_filters and len(spans) == 1 and not with_delta
                and spans[0].tcounts is not None
                and spans[0].tcounts.max() > 0
                and spans[0].dead_seq == len(self.rwi._tombstones)):
            # batched dispatch first: concurrent eligible queries ride
            # one vmapped SPMD program (r4 #4 — the per-query dispatch
            # serialized concurrent searchers)
            if (self._batcher is not None
                    and threading.current_thread()
                    is not self._batcher._thread):
                res = self._batcher.submit(termhash, profile, language,
                                           kk0)
                if res[0] == "ok":
                    s, d = res[1], res[2]
                    keep = (d >= 0) & (s > NEG_INF32)
                    s, d = s[keep], d[keep]
                    with self._lock:   # exact under concurrency
                        self.queries_served += 1
                    cache_put(s, d)
                    return s[:k], d[:k], considered
                # prune_fail: the batch already walked the full bucket
                # ladder — go straight to the exact full scan below;
                # ineligible/timeout continue into the solo ladder
                batch_prune_failed = res[0] == "prune_fail"
            else:
                batch_prune_failed = False
            sp = spans[0]
            st = sp.stats
            consts = self._profile_consts(profile, language)
            shift, lang_term = prune_bound_consts(profile)
            qargs = np.stack([sp.starts, sp.counts,
                              sp.tstarts, sp.tcounts], axis=1
                             ).astype(np.int32)
            for b in () if batch_prune_failed else _PRUNE_B:
                t0s = time.perf_counter()
                out = self._pfn(kk0, b)(
                    arrays[0], arrays[1], arrays[2], dead, pmax, qargs,
                    st["col_min"], st["col_max"],
                    np.float32(st["tf_min"]), np.float32(st["tf_max"]),
                    shift, lang_term, *consts)
                t1s = time.perf_counter()
                s, d, ok = self.device_fetch(out)
                self.count_round_trip()
                _emit_rt_spans((t1s - t0s) * 1e3,
                               (time.perf_counter() - t1s) * 1e3,
                               kernel="_mesh_pruned_shard")
                # solo SPMD program wall: one mesh.collective record per
                # dispatch (the batched path records in _complete)
                histogram.observe("mesh.collective",
                                  (time.perf_counter() - t0s) * 1e3,
                                  tracing.current_trace_id())
                with self._lock:   # completer writes these too
                    self.prune_rounds += 1
                    if bool(ok):
                        self.pruned_tiles += int(
                            np.maximum(sp.tcounts - b, 0).sum())
                if bool(ok):
                    keep = (d >= 0) & (s > NEG_INF32)
                    s, d = s[keep], d[keep]
                    with self._lock:   # exact under concurrency
                        self.queries_served += 1
                    cache_put(s, d)
                    return s[:k], d[:k], considered
            # every bucket failed (pathological profile): full scan below

        starts = np.zeros((self.n_cells, self.MAX_SPANS), np.int32)
        counts = np.zeros((self.n_cells, self.MAX_SPANS), np.int32)
        for i, sp in enumerate(spans):
            starts[:, i] = sp.starts
            counts[:, i] = sp.counts
        if with_delta:
            n = len(delta)
            b = _bucket_delta(n)
            df = np.zeros((b, P.NF), np.int16)
            dfl = np.zeros(b, np.int32)
            ddd = np.full(b, -1, np.int32)
            cf, cfl = compact_feats(delta.feats)
            df[:n], dfl[:n], ddd[:n] = cf, cfl, delta.docids
            d_args = (df, dfl, ddd)
        else:
            d_args = (np.zeros((1, P.NF), np.int16),
                      np.zeros(1, np.int32), np.full(1, -1, np.int32))
        qfilters = np.asarray(
            [lang_filter, flag_bit,
             DAYS_NONE_LO if from_days is None else from_days,
             DAYS_NONE_HI if to_days is None else to_days], np.int32)
        consts = self._profile_consts(profile, language)
        t0f = time.perf_counter()
        out = self._fn(kk0, with_delta)(
            *arrays, starts, counts, dead, *d_args, qfilters, *consts)
        t1f = time.perf_counter()
        s, d = self.device_fetch(out)
        self.count_round_trip()
        _emit_rt_spans((t1f - t0f) * 1e3,
                       (time.perf_counter() - t1f) * 1e3,
                       kernel="_mesh_rank_shard")
        histogram.observe("mesh.collective",
                          (time.perf_counter() - t0f) * 1e3,
                          tracing.current_trace_id())
        keep = (d >= 0) & (s > NEG_INF32)
        s, d = s[keep], d[keep]
        # gathered candidates may repeat a docid (replicated delta rows;
        # cross-run re-pushes): keep the best-scored instance
        _, first = np.unique(d, return_index=True)
        if len(first) != len(d):
            sel = np.sort(first)
            s, d = s[sel], d[sel]
        with self._lock:   # exact under concurrency
            self.queries_served += 1
        cache_put(s, d)
        return s[:k], d[:k], considered

    MAX_JOIN_TERMS = 6

    def _jfn(self, kk: int, n_inc: int, n_exc: int, r: int,
             inc_ms: tuple, exc_ms: tuple, cross_row: bool = False):
        """cross_row=False: all terms share a term row (column-local
        join); True: the kernel exchanges the rare row's candidates
        along the term axis (VERDICT r3 #3). The rare ROW rides in
        qargs as a traced scalar, so one compile serves every row."""
        key = (kk, n_inc, n_exc, r, inc_ms, exc_ms, cross_row)
        if key not in self._jfns:
            body = (partial(_mesh_xjoin_shard if cross_row
                            else _mesh_join_shard, k=kk, n_inc=n_inc,
                            n_exc=n_exc, r=r, inc_ms=inc_ms, exc_ms=exc_ms))
            self._jfns[key] = jax.jit(jax.shard_map(
                body,
                mesh=self.mesh,
                in_specs=(PS(("term", "doc"), None, None),   # feats16
                          PS(("term", "doc"), None),         # flags
                          PS(("term", "doc"), None),         # docids
                          PS(("term", "doc"), None),         # jdocids
                          PS(("term", "doc"), None),         # jpos
                          PS(),                              # dead
                          PS(("term", "doc"), None),         # qargs
                          PS(), PS(), PS(), PS(), PS(), PS(), PS(), PS()),
                out_specs=(PS(), PS()),
                check_vma=False,
            ))
        return self._jfns[key]

    def rank_join(self, include_hashes, exclude_hashes, profile,
                  language: str = "en", k: int = 100,
                  lang_filter: int = NO_LANG, flag_bit: int = NO_FLAG,
                  from_days: int | None = None, to_days: int | None = None):
        """Multi-term conjunctive ranked top-k as one SPMD program.

        The vertical-partition invariant (one docid → one doc column for
        EVERY term) makes the conjunction at worst COLUMN-LOCAL: a
        partner term on the SAME term row joins against column-local
        docid-sorted side tables directly; a partner on a DIFFERENT term
        row joins by a collective exchange WITHIN the doc column — the
        rare row's candidate docids broadcast along the term axis
        (all_gather), every row membership-tests them against its local
        tables, and the owning row's per-candidate features reduce back
        (psum/pmin/pmax with neutral fills). This is the mesh-native
        version of the reference's cross-ring join-gap protocol, where
        peers ship candidate doc lists to each other
        (SecondarySearchSuperviser.java:198, Distribution.java:47-62) —
        here the shipment is ~20 bytes/candidate over ICI instead of an
        HTTP round trip (VERDICT r3 #3). Host fallback remains only for
        multi-span terms, unflushed RAM deltas — and a lost mesh
        (ISSUE 10c: counted, never an exception). Every join-shaped
        query lands in exactly one of join_served / join_fallbacks, as
        ``DeviceSegmentStore.rank_join`` documents it; `fallbacks`
        keeps counting the same declines beside them."""
        # lint: unlocked-ok(racy bool read by design: a stale False
        # costs one failed transfer that re-classifies; locking here
        # would serialize every rank entry behind store mutations)
        if self.device_lost:
            with self._lock:   # one consistent counter view
                self.device_lost_queries += 1
                self._join_declined()
            return None
        try:
            return self._rank_join_impl(include_hashes, exclude_hashes,
                                        profile, language, k,
                                        lang_filter, flag_bit,
                                        from_days, to_days)
        except DeviceTransferError:
            with self._lock:   # one consistent counter view
                self.device_lost_queries += 1
                self._join_declined()
            return None

    def _join_declined(self) -> None:
        """An eligible-shaped conjunction goes to the host join."""
        with self._lock:
            self.fallbacks += 1
            self.join_fallbacks += 1

    def _rank_join_impl(self, include_hashes, exclude_hashes, profile,
                        language: str = "en", k: int = 100,
                        lang_filter: int = NO_LANG,
                        flag_bit: int = NO_FLAG,
                        from_days: int | None = None,
                        to_days: int | None = None):
        include_hashes = list(include_hashes)
        exclude_hashes = list(exclude_hashes or [])
        if not include_hashes \
                or (len(include_hashes) == 1 and not exclude_hashes) \
                or len(include_hashes) > self.MAX_JOIN_TERMS \
                or len(exclude_hashes) > self.MAX_JOIN_TERMS:
            return None
        with self._lock:
            rows = set()
            inc_spans = []
            for th in include_hashes:
                spans = self.spans_for(th)
                if spans is None or len(spans) != 1:
                    self._join_declined()
                    return None
                rows.add(term_shard(th, self.n_term))
                inc_spans.append(spans[0])
            exc_spans = []
            for th in exclude_hashes:
                spans = self.spans_for(th)
                if spans is None:
                    if self.rwi.has_term(th):
                        self._join_declined()
                        return None
                    continue
                if len(spans) > 1:
                    self._join_declined()
                    return None
                if spans:
                    rows.add(term_shard(th, self.n_term))
                    exc_spans.append(spans[0])
            arrays = self._device_arrays()
            jdocids, jpos = self._dev_join
            dead = self._dead_array()
            JC = int(jdocids.shape[1])
            C = int(arrays[0].shape[1])
        # counter bump outside the rwi lock (store->rwi lock order)
        with self.rwi._lock:
            ram_delta = any(self.rwi._ram.get(th)
                            for th in include_hashes + exclude_hashes)
        if ram_delta:
            self._join_declined()
            return None

        rare_i = min(range(len(inc_spans)),
                     key=lambda i: inc_spans[i].total)
        rare = inc_spans[rare_i]
        partners = [sp for i, sp in enumerate(inc_spans) if i != rare_i]
        considered = rare.total

        r = _bucket_rows(max(int(rare.counts.max()), 1))
        if int((rare.starts + r).max()) > C:
            self._join_declined()
            return None

        def window(sp):
            m = _bucket_rows(max(int(sp.counts.max()), 1))
            return m if int((sp.jstarts + m).max()) <= JC else None

        inc_ms = tuple(window(sp) for sp in partners)
        exc_ms = tuple(window(sp) for sp in exc_spans)
        if any(m is None for m in inc_ms + exc_ms):
            self._join_declined()
            return None

        n_inc, n_exc = len(partners), len(exc_spans)
        qargs = np.zeros((self.n_cells, 6 + 2 * (n_inc + n_exc)), np.int32)
        qargs[:, 0] = rare.starts
        qargs[:, 1] = rare.counts
        qargs[:, 2] = lang_filter
        qargs[:, 3] = flag_bit
        qargs[:, 4] = DAYS_NONE_LO if from_days is None else from_days
        qargs[:, 5] = DAYS_NONE_HI if to_days is None else to_days
        base = 6
        for t, sp in enumerate(partners):
            qargs[:, base + t] = sp.jstarts
            qargs[:, base + n_inc + t] = sp.counts
        for e, sp in enumerate(exc_spans):
            qargs[:, base + 2 * n_inc + e] = sp.jstarts
            qargs[:, base + 2 * n_inc + n_exc + e] = sp.counts

        consts = self._profile_consts(profile, language)
        kk = max(16, 1 << (max(k, 1) - 1).bit_length())
        # cross-row conjunction: the kernel exchanges candidates along
        # the term axis, anchored at the rare term's row (VERDICT r3 #3);
        # the row is a TRACED qargs scalar (no per-row compile)
        cross_row = len(rows) > 1
        if cross_row:
            qargs = np.concatenate(
                [qargs, np.full((self.n_cells, 1),
                                term_shard(include_hashes[rare_i],
                                           self.n_term), np.int32)], axis=1)
        t0j = time.perf_counter()
        out = self._jfn(kk, n_inc, n_exc, r, inc_ms, exc_ms,
                        cross_row=cross_row)(
            *arrays, jdocids, jpos, dead, qargs, *consts)
        t1j = time.perf_counter()
        s, d = self.device_fetch(out)
        self.count_round_trip()
        _emit_rt_spans((t1j - t0j) * 1e3,
                       (time.perf_counter() - t1j) * 1e3,
                       kernel="_mesh_xjoin_shard" if cross_row
                       else "_mesh_join_shard")
        histogram.observe("mesh.collective",
                          (time.perf_counter() - t0j) * 1e3,
                          tracing.current_trace_id())
        keep = (d >= 0) & (s > NEG_INF32)
        with self._lock:   # exact under concurrency
            self.queries_served += 1
            self.join_served += 1
        return s[keep][:k], d[keep][:k], considered


def _mesh_join_shard(feats16, flags, docids, jdocids, jpos, dead, qargs,
                     norm_coeffs, flag_bits, flag_shifts,
                     domlength_coeff, tf_coeff, language_coeff,
                     authority_coeff, language_pref,
                     *, k: int, n_inc: int, n_exc: int, r: int,
                     inc_ms: tuple, exc_ms: tuple):
    """Per-device body of the sharded conjunction: column-local
    sort-merge membership (devstore._membership_sorted), host-join
    feature merge semantics (worddistance = position span, hitcount =
    min, flags = OR — segment.join_constructive), mesh-wide stats merge,
    all_gather + global top-k."""
    from .devstore import _membership_sorted
    feats16 = feats16[0]
    flags = flags[0]
    docids = docids[0]
    jdocids = jdocids[0]
    jpos = jpos[0]
    q = qargs[0]
    start, count = q[0], q[1]
    lang_filter, flag_bit = q[2], q[3]
    from_days, to_days = q[4], q[5]
    base = 6
    f = lax.dynamic_slice(feats16, (start, 0), (r, P.NF)).astype(jnp.int32)
    fl = lax.dynamic_slice(flags, (start,), (r,))
    dd = lax.dynamic_slice(docids, (start,), (r,))
    v = _tile_valid(dd, dead, jnp.arange(r) < count)

    pos_min = f[:, P.F_POSINTEXT]
    pos_max = f[:, P.F_POSINTEXT]
    hit_min = f[:, P.F_HITCOUNT]
    flags_or = fl
    for t in range(n_inc):
        lo = q[base + t]
        cnt = q[base + n_inc + t]
        found, prow = _membership_sorted(jdocids, jpos, lo, inc_ms[t],
                                         dd, v, cnt)
        v &= found
        pf = feats16[prow].astype(jnp.int32)
        pos_min = jnp.minimum(pos_min, pf[:, P.F_POSINTEXT])
        pos_max = jnp.maximum(pos_max, pf[:, P.F_POSINTEXT])
        hit_min = jnp.minimum(hit_min, pf[:, P.F_HITCOUNT])
        flags_or = flags_or | jnp.where(found, flags[prow], 0)
    for e in range(n_exc):
        lo = q[base + 2 * n_inc + e]
        cnt = q[base + 2 * n_inc + n_exc + e]
        found, _prow = _membership_sorted(jdocids, jpos, lo, exc_ms[e],
                                          dd, v, cnt)
        v &= ~found

    return _join_score_gather(
        f, pos_min, pos_max, hit_min, flags_or, v, dd,
        lang_filter, flag_bit, from_days, to_days,
        norm_coeffs, flag_bits, flag_shifts, domlength_coeff, tf_coeff,
        language_coeff, authority_coeff, language_pref, k=k, r=r)


def _join_score_gather(f, pos_min, pos_max, hit_min, flags_or, v, dd,
                       lang_filter, flag_bit, from_days, to_days,
                       norm_coeffs, flag_bits, flag_shifts,
                       domlength_coeff, tf_coeff, language_coeff,
                       authority_coeff, language_pref, *, k: int, r: int):
    """Shared join epilogue (column-local AND cross-row kernels): merge
    features with the host join's semantics, mesh-wide stats bounds
    (ReferenceOrder.normalizeWith — one global min/max over ALL
    survivors), score, and fuse per-device top-k by all_gather + global
    top-k. One body so the two join paths can never diverge."""
    axes = ("term", "doc")
    merged = f.at[:, P.F_WORDDISTANCE].set(pos_max - pos_min)
    merged = merged.at[:, P.F_HITCOUNT].set(hit_min)
    v &= _constraint_valid(merged, flags_or, lang_filter, flag_bit,
                           from_days, to_days)
    stats = local_stats(merged, v, jnp.zeros(r, jnp.int32),
                        num_hosts=1, with_host_counts=False)
    stats = {"col_min": lax.pmin(stats["col_min"], axes),
             "col_max": lax.pmax(stats["col_max"], axes),
             "tf_min": lax.pmin(stats["tf_min"], axes),
             "tf_max": lax.pmax(stats["tf_max"], axes),
             "host_counts": stats["host_counts"]}
    sc = cardinal_from_stats(
        merged, v, jnp.zeros(r, jnp.int32), stats,
        norm_coeffs, flag_bits, flag_shifts, domlength_coeff,
        tf_coeff, language_coeff, authority_coeff, language_pref,
        flags=flags_or)
    # local exact top-k under the pinned (score DESC, docid ASC) tie
    # discipline, fused by the shared all-gather+top-k collective —
    # k rows per cell cross the interconnect (parallel/mesh.py)
    top_s, top_d = tie_topk(sc, dd, min(k, r))
    return all_gather_topk(top_s, top_d, axes, k)


def _mesh_xjoin_shard(feats16, flags, docids, jdocids, jpos, dead, qargs,
                      norm_coeffs, flag_bits, flag_shifts,
                      domlength_coeff, tf_coeff, language_coeff,
                      authority_coeff, language_pref,
                      *, k: int, n_inc: int, n_exc: int, r: int,
                      inc_ms: tuple, exc_ms: tuple):
    """Per-device body of the CROSS-ROW conjunction (VERDICT r3 #3).

    Terms on different term rows share doc columns (docid % n_doc is
    term-independent), so the join becomes a term-axis exchange inside
    each column — the TPU-native form of the reference's cross-ring
    candidate shipment (SecondarySearchSuperviser.java:198):

    1. the rare row broadcasts its candidate docids + validity along
       the term axis (all_gather, ~5 B/candidate); the rare row index
       is a TRACED qargs scalar, so one compile serves every row;
    2. EVERY row membership-tests the candidates against its local
       column join tables — non-owner cells carry count-0 windows, so
       exactly one row per partner term finds anything;
    3. the owner's per-candidate partner features flow back as neutral-
       filled reductions (pmin/pmax for positions, pmin for hitcount,
       psum for membership and flags — one nonzero contributor each,
       ~16 B/candidate);
    4. only the rare row scores (axis_index mask), so the global
       all_gather top-k sees each surviving docid exactly once.
    """
    from .devstore import _membership_sorted
    feats16 = feats16[0]
    flags = flags[0]
    docids = docids[0]
    jdocids = jdocids[0]
    jpos = jpos[0]
    q = qargs[0]
    start, count = q[0], q[1]
    lang_filter, flag_bit = q[2], q[3]
    from_days, to_days = q[4], q[5]
    base = 6
    row_rare = q[base + 2 * (n_inc + n_exc)]
    f = lax.dynamic_slice(feats16, (start, 0), (r, P.NF)).astype(jnp.int32)
    fl = lax.dynamic_slice(flags, (start,), (r,))
    dd = lax.dynamic_slice(docids, (start,), (r,))
    v = _tile_valid(dd, dead, jnp.arange(r) < count)

    # (1) candidates ride the term axis: every row of this doc column
    # sees the rare row's docids (non-rare rows hold count-0 slices)
    gdd = lax.dynamic_index_in_dim(lax.all_gather(dd, "term"), row_rare,
                                   0, keepdims=False)
    gv = lax.dynamic_index_in_dim(lax.all_gather(v, "term"), row_rare,
                                  0, keepdims=False)

    big = jnp.int32(INT32_MAX)
    pos_min = f[:, P.F_POSINTEXT]
    pos_max = f[:, P.F_POSINTEXT]
    hit_min = f[:, P.F_HITCOUNT]
    flags_or = fl
    for t in range(n_inc):
        lo = q[base + t]
        cnt = q[base + n_inc + t]
        # (2) local membership — count-0 windows on non-owner rows
        found, prow = _membership_sorted(jdocids, jpos, lo, inc_ms[t],
                                         gdd, gv, cnt)
        pf = feats16[prow].astype(jnp.int32)
        # (3) owner-row contributions reduce along the term axis
        hit = lax.psum(found.astype(jnp.int32), "term")
        p_min = lax.pmin(jnp.where(found, pf[:, P.F_POSINTEXT], big),
                         "term")
        p_max = lax.pmax(jnp.where(found, pf[:, P.F_POSINTEXT], -big),
                         "term")
        h_min = lax.pmin(jnp.where(found, pf[:, P.F_HITCOUNT], big),
                         "term")
        fl_p = lax.psum(jnp.where(found, flags[prow], 0), "term")
        gv &= hit > 0
        pos_min = jnp.minimum(pos_min, p_min)
        pos_max = jnp.maximum(pos_max, p_max)
        hit_min = jnp.minimum(hit_min, h_min)
        flags_or = flags_or | fl_p
    for e in range(n_exc):
        lo = q[base + 2 * n_inc + e]
        cnt = q[base + 2 * n_inc + n_exc + e]
        found, _prow = _membership_sorted(jdocids, jpos, lo, exc_ms[e],
                                          gdd, gv, cnt)
        gv &= lax.psum(found.astype(jnp.int32), "term") == 0

    # (4) only the rare row's cells score — its f/fl are the real rare
    # features, and uniqueness keeps the gathered top-k duplicate-free
    gv &= lax.axis_index("term") == row_rare
    return _join_score_gather(
        f, pos_min, pos_max, hit_min, flags_or, gv, gdd,
        lang_filter, flag_bit, from_days, to_days,
        norm_coeffs, flag_bits, flag_shifts, domlength_coeff, tf_coeff,
        language_coeff, authority_coeff, language_pref, k=k, r=r)


def _mesh_pruned_shard(feats16, flags, docids, dead, pmax, qargs,
                       col_min, col_max, tf_min, tf_max,
                       bound_shift, lang_term,
                       norm_coeffs, flag_bits, flag_shifts,
                       domlength_coeff, tf_coeff, language_coeff,
                       authority_coeff, language_pref,
                       *, k: int, b: int):
    """Per-device body of the block-max PRUNED mesh rank: each device
    runs devstore's prefix-scored, tail-verified top-k over ITS slice of
    the proxy-sorted span (frozen GLOBAL pack stats), then candidates
    fuse by all_gather + global top-k. ok = every device's bound held —
    a single failure escalates the prefix for the whole mesh (the merge
    is exact iff every local top-k is exact)."""
    feats16 = feats16[0]
    flags = flags[0]
    docids = docids[0]
    pmax = pmax[0]
    q = qargs[0]
    axes = ("term", "doc")
    run_s, run_d, ok = _pruned_span_topk(
        feats16, flags, docids, dead, pmax,
        q[0], q[1], q[2], q[3],
        col_min, col_max, tf_min, tf_max, bound_shift, lang_term,
        norm_coeffs, flag_bits, flag_shifts, domlength_coeff, tf_coeff,
        language_coeff, authority_coeff, language_pref, k=k, b=b)
    top_s, top_d = all_gather_topk(run_s, run_d, axes, k)
    all_ok = lax.pmin(ok.astype(jnp.int32), axes) > 0
    return top_s, top_d, all_ok


def _mesh_pruned_batch_shard(feats16, flags, docids, dead, pmax, qargs,
                             col_min, col_max, tf_min, tf_max,
                             bound_shift, lang_term,
                             norm_coeffs, flag_bits, flag_shifts,
                             domlength_coeff, tf_coeff, language_coeff,
                             authority_coeff, language_pref,
                             *, k: int, b: int):
    """Batched per-device body of the pruned mesh rank: `bs` concurrent
    queries vmap over ONE shard_map program — qargs [1, bs, 4] carries
    each query's local span window on this cell, per-query pack stats
    ride replicated [bs, ...] rows. Cross-mesh fusion then runs
    all_gather once for the whole batch (tiled=False keeps the query
    axis intact) and a vmapped global top-k per slot. This is the mesh
    form of the devstore batcher's one-round-trip-per-wave contract
    (VERDICT r4 #4: each mesh query used to pay its own SPMD dispatch,
    serializing 16 searchers on the dispatch path)."""
    feats16 = feats16[0]
    flags = flags[0]
    docids = docids[0]
    pmax = pmax[0]
    q = qargs[0]                         # [bs, 4]
    axes = ("term", "doc")

    def one(qrow, cmin, cmax, tmin, tmax):
        return _pruned_span_topk(
            feats16, flags, docids, dead, pmax,
            qrow[0], qrow[1], qrow[2], qrow[3],
            cmin, cmax, tmin, tmax, bound_shift, lang_term,
            norm_coeffs, flag_bits, flag_shifts, domlength_coeff,
            tf_coeff, language_coeff, authority_coeff, language_pref,
            k=k, b=b)

    run_s, run_d, ok = jax.vmap(one)(q, col_min, col_max, tf_min, tf_max)
    gs = lax.all_gather(run_s, axes)     # [n_dev, bs, k]
    gd = lax.all_gather(run_d, axes)
    gs = jnp.moveaxis(gs, 0, 1).reshape(run_s.shape[0], -1)  # [bs, n_dev*k]
    gd = jnp.moveaxis(gd, 0, 1).reshape(run_d.shape[0], -1)
    # per-slot tie-pinned merge (the batched form of all_gather_topk):
    # batched and solo fusion must rank ties identically
    top_s, top_d = jax.vmap(
        lambda s, d: tie_topk(s, d, min(k, s.shape[0])))(gs, gd)
    all_ok = lax.pmin(ok.astype(jnp.int32), axes) > 0        # [bs]
    return top_s, top_d, all_ok


def _mesh_rank_shard(feats16, flags, docids, starts, counts, dead,
                     d_feats16, d_flags, d_docids, qfilters,
                     norm_coeffs, flag_bits, flag_shifts,
                     domlength_coeff, tf_coeff, language_coeff,
                     authority_coeff, language_pref,
                     *, k: int, with_delta: bool):
    """Per-device body of the sharded rank: streaming two-pass scan of the
    local extent slices, cross-mesh stats merge, all_gather + global
    top-k. Mirrors devstore._rank_spans_kernel semantics exactly — the
    parity tests compare against it and the host oracle."""
    feats16 = feats16[0]          # [C, NF]  this device's cell
    flags = flags[0]
    docids = docids[0]
    starts = starts[0]            # [n_spans]
    counts = counts[0]
    n_spans = starts.shape[0]
    C = feats16.shape[0]
    tile = min(TILE, C)
    lang_filter, flag_bit = qfilters[0], qfilters[1]
    from_days, to_days = qfilters[2], qfilters[3]
    axes = ("term", "doc")

    def tile_of(span_start, span_count, i):
        off = span_start + i * tile
        f = lax.dynamic_slice(feats16, (off, 0), (tile, P.NF))
        fl = lax.dynamic_slice(flags, (off,), (tile,))
        dd = lax.dynamic_slice(docids, (off,), (tile,))
        in_span = jnp.arange(tile) < (span_count - i * tile)
        v = _tile_valid(dd, dead, in_span)
        v &= _constraint_valid(f, fl, lang_filter, flag_bit,
                               from_days, to_days)
        return f, fl, dd, v

    def stats_of(f, v):
        return local_stats(f, v, jnp.zeros(f.shape[0], jnp.int32),
                           num_hosts=1, with_host_counts=False)

    def span_stats(carry, s):
        start, count = starts[s], counts[s]
        n_tiles = (count + tile - 1) // tile

        def body(i, st):
            f, fl, dd, v = tile_of(start, count, i)
            return merge_stats(st, stats_of(f, v))
        return lax.fori_loop(0, n_tiles, body, carry)

    big, small = jnp.int32(INT32_MAX), jnp.int32(-INT32_MAX)
    stats = {"col_min": jnp.full((P.NF,), big),
             "col_max": jnp.full((P.NF,), small),
             "tf_min": jnp.float32(jnp.inf),
             "tf_max": jnp.float32(-jnp.inf),
             "host_counts": jnp.zeros((1,), jnp.int32)}
    for s in range(n_spans):
        stats = span_stats(stats, s)
    if with_delta:
        d_v = _tile_valid(d_docids, dead, jnp.ones(d_docids.shape[0], bool))
        d_v &= _constraint_valid(d_feats16, d_flags, lang_filter, flag_bit,
                                 from_days, to_days)
        stats = merge_stats(stats, stats_of(d_feats16, d_v))

    # the reference computes ONE global min/max before scoring
    # (ReferenceOrder.normalizeWith); on the mesh that is a pmin/pmax
    # over both DHT axes — idempotent, so replicated delta rows and
    # empty term rows merge neutrally
    stats = {"col_min": lax.pmin(stats["col_min"], axes),
             "col_max": lax.pmax(stats["col_max"], axes),
             "tf_min": lax.pmin(stats["tf_min"], axes),
             "tf_max": lax.pmax(stats["tf_max"], axes),
             "host_counts": stats["host_counts"]}

    def score_rows(f, fl, v):
        return cardinal_from_stats(f, v, jnp.zeros(f.shape[0], jnp.int32),
                                   stats, norm_coeffs, flag_bits,
                                   flag_shifts, domlength_coeff, tf_coeff,
                                   language_coeff, authority_coeff,
                                   language_pref, fast_div=True, flags=fl)

    def merge_topk(run, tile_s, tile_d):
        # tie-pinned running merge: the per-tile winners fold in under
        # (score DESC, docid ASC), so the local top-k is EXACT under
        # ties and the fused gather below can never rank equal-score
        # candidates by tile-arrival order
        run_s, run_d = run
        s = jnp.concatenate([run_s, tile_s])
        d = jnp.concatenate([run_d, tile_d])
        return tie_topk(s, d, k)

    init = (jnp.full((k,), NEG_INF32, jnp.int32),
            jnp.full((k,), -1, jnp.int32))

    def span_score(carry, s):
        start, count = starts[s], counts[s]
        n_tiles = (count + tile - 1) // tile

        def body(i, run):
            f, fl, dd, v = tile_of(start, count, i)
            sc = score_rows(f, fl, v)
            tile_s, tile_d = tie_topk(sc, dd, min(k, tile))
            return merge_topk(run, tile_s, tile_d)
        return lax.fori_loop(0, n_tiles, body, carry)

    run = init
    for s in range(n_spans):
        run = span_score(run, s)
    if with_delta:
        sc = score_rows(d_feats16, d_flags, d_v)
        tile_s, tile_d = tie_topk(sc, d_docids, min(k, sc.shape[0]))
        run = merge_topk(run, tile_s, tile_d)

    # candidate fusion across the whole mesh — the fused
    # all-gather+top-k collective (parallel/mesh.py), the TPU
    # replacement of the reference's per-peer heap-insert merge
    # (SearchEvent.java:444-497), k rows per device on the wire.
    # With a delta the gathered set holds up to n_devices copies of each
    # delta row (replicated upload); return the WHOLE sorted gather so
    # the host-side dedup still has k unique docids left (the gather is
    # only n_devices*k rows).
    if with_delta:
        return all_gather_topk_full(run[0], run[1], axes)
    return all_gather_topk(run[0], run[1], axes, k)
