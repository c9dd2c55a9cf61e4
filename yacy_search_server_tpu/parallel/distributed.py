"""True multi-process SPMD mesh serving — the `jax.distributed` runtime.

Every multi-chip number in this repo used to be produced by ONE
interpreter (`tests/test_dryrun_multichip.py` drives the whole mesh
in-process).  This module brings a fleet of OS processes up as ONE
logical SPMD mesh (ISSUE 12 / ROADMAP item 1 — the gap that survived
every re-anchor since round 5):

* **Bootstrap** — ``jax.distributed.initialize`` with the coordinator
  address / process id / process count from env (``YACY_MESH_*``), the
  CPU backend's per-process device pool from
  ``XLA_FLAGS=--xla_force_host_platform_device_count`` (the documented
  CI pattern), and gloo cross-process collectives.
* **Global mesh** — ``jax.devices()`` after distributed init is the
  process-ordered GLOBAL pool; each process owns its local shard of the
  (term, doc) grid.  The partition math (``meshstore.term_shard`` +
  ``docid % n_doc``) is pure arithmetic over the hashes, and
  :func:`partition_fingerprint` digests it over a probe set so the
  processes can ASSERT they agree before serving (a process with a
  divergent placement would silently return wrong rankings, not crash).
* **SPMD discipline over the real HTTP wire** — pjit's multi-process
  contract (SNIPPETS [2]): every process must execute the same program
  in the same order.  Queries arrive at the coordinator over HTTP
  (``/yacy/meshsearch``), and a two-phase scatter keeps the fleet in
  lockstep: phase 1 POSTs the step to every member (the reply carries
  pid + health — the wire IS the liveness probe), phase 2 commits a
  single go/no-go verdict.  Only a committed ``go`` enters the
  cross-process collective (``MeshSegmentStore.rank_term_mp``); any
  member down or device-lost flips the WHOLE fleet to the host answer
  for that step — degraded and counted, never a hang.  Fleet metric
  digests and trace ids ride the same RPCs for free
  (``peers/protocol.Protocol._call``).
* **Per-process survival** — the M82–M84 machinery holds per process:
  ``device.transfer_fail`` injected into ONE member fails only that
  member's fetches; its loss streak declares ITS device lost, the
  coordinator sees the flag on the next scatter, the fleet degrades to
  host serving (100% answered), a flight-recorder incident names the
  member, and the member's background rebuild brings collectives back.

The launcher/supervisor lives in :mod:`yacy_search_server_tpu.parallel.
launcher`; ``python -m yacy_search_server_tpu.parallel.launcher
--procs 3`` is the one-command bring-up.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import queue as _queue
import threading
import time
from collections import deque

import numpy as np

from ..utils import faultinject, histogram, profiling, tailattr, tracing

log = logging.getLogger("parallel.distributed")

# -- environment contract (set by the launcher before the child's
#    interpreter starts, so XLA flags precede backend discovery) -------------
ENV_COORDINATOR = "YACY_MESH_COORDINATOR"     # host:port of jax coordinator
ENV_NPROCS = "YACY_MESH_NPROCS"
ENV_PROC_ID = "YACY_MESH_PROC_ID"
ENV_LOCAL_DEVICES = "YACY_MESH_LOCAL_DEVICES"
ENV_HTTP_PORTS = "YACY_MESH_HTTP_PORTS"       # comma list, index = proc id
ENV_NDOCS = "YACY_MESH_NDOCS"
ENV_SEED = "YACY_MESH_SEED"
ENV_NTERM = "YACY_MESH_NTERM"
ENV_DATA_DIR = "YACY_MESH_DATA_DIR"
ENV_TESTING = "YACY_MESH_TESTING"             # gates the fault-arming RPC

COMMIT_TIMEOUT_S = 20.0      # commit that never arrives -> host mode
STEP_KINDS = ("rank_term",)

# the deterministic corpus every process builds identically (SPMD: same
# program, same data; device_put then materializes only local shards)
CORPUS_TERMS = ("meshterm", "papaya", "quokka", "banana")
TIE_TERM = "tieterm"         # identical feature rows -> equal scores
                             # spread across doc columns (tie discipline
                             # across process boundaries)


def bootstrap_from_env():
    """``jax.distributed.initialize`` from the YACY_MESH_* contract.
    Must run before any other jax API touches the backend.  Returns
    (process_id, num_processes)."""
    import jax

    from ..utils import compilecache
    compilecache.ensure()
    coord = os.environ[ENV_COORDINATOR]
    nprocs = int(os.environ[ENV_NPROCS])
    pid = int(os.environ[ENV_PROC_ID])
    # gloo is the CPU cross-process collective fabric
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=nprocs, process_id=pid)
    want = int(os.environ.get(ENV_LOCAL_DEVICES, "0"))
    if want and jax.local_device_count() != want:
        raise RuntimeError(
            f"process {pid}: {jax.local_device_count()} local devices, "
            f"want {want} (XLA_FLAGS must be set before jax imports)")
    return pid, nprocs


def global_mesh_devices():
    """The process-ordered global device pool (jax.devices() after
    distributed init spans every process)."""
    import jax
    return list(jax.devices())


# -- partition-math determinism ---------------------------------------------

def partition_fingerprint(n_term: int, n_doc: int,
                          probes: int = 64) -> str:
    """Digest of the (term, doc) placement over a fixed probe set —
    identical on every process and across restarts iff the partition
    math is deterministic (asserted by the scatter handshake and
    property-tested in tests/test_mesh_multiproc.py)."""
    from ..index.meshstore import term_shard
    from ..utils.hashes import word2hash
    h = hashlib.sha256(f"{n_term}x{n_doc}".encode("ascii"))
    for i in range(probes):
        th = word2hash(f"fingerprint-probe-{i}")
        t = term_shard(th, n_term)
        d = i * 2654435761 % n_doc          # deterministic probe docids
        h.update(bytes([t, d % 251]))
        h.update(th)
    return h.hexdigest()[:16]


# -- the deterministic corpus ------------------------------------------------

def build_corpus(sb, ndocs: int, seed: int, n_doc: int) -> None:
    """Identical on every process for a given (ndocs, seed): metadata
    rows + ONE frozen RWI run with the CORPUS_TERMS and the constructed
    tie term (two identical feature rows whose docids land in DIFFERENT
    doc columns — equal scores must cross a process boundary and still
    fuse as (score DESC, docid ASC))."""
    from ..index import postings as P
    from ..index.postings import PostingsList
    from ..utils.hashes import word2hash
    rng = np.random.default_rng(seed)
    sb.index.metadata.bulk_load(
        [f"{i:06d}h{i % 7:05d}".encode("ascii") for i in range(ndocs)],
        sku=[f"http://h{i % 7}.example/d{i}.html" for i in range(ndocs)],
        title=[f"doc {i}" for i in range(ndocs)],
        host_s=[f"h{i % 7}.example" for i in range(ndocs)],
        size_i=[1000] * ndocs, wordcount_i=[100] * ndocs)
    run: dict = {}
    for t_i, term in enumerate(CORPUS_TERMS):
        n = ndocs - (t_i * ndocs // 8)      # distinct span sizes
        feats = rng.integers(0, 1000, (n, P.NF)).astype(np.int32)
        feats[:, P.F_FLAGS] = rng.integers(0, 2 ** 20, n)
        feats[:, P.F_LANGUAGE] = P.pack_language("en")
        run[word2hash(term)] = PostingsList(
            np.arange(n, dtype=np.int32), feats)
    # the tie construction: 2*n_doc docids carrying the SAME feature
    # row — one per doc column twice over, so equal-score candidates
    # arrive at the fusion collective from every process
    n_tie = 2 * max(n_doc, 1)
    feats = rng.integers(0, 1000, (1, P.NF)).astype(np.int32)
    feats[:, P.F_FLAGS] = 0
    feats[:, P.F_LANGUAGE] = P.pack_language("en")
    run[word2hash(TIE_TERM)] = PostingsList(
        np.arange(n_tie, dtype=np.int32),
        np.repeat(feats, n_tie, axis=0))
    sb.index.rwi.ingest_run(run)


def host_rank(index, termhash: bytes, profile, language: str,
              k: int):
    """The degraded-mode answer: the host ranker over the full merged
    postings — same math, same tie discipline (postings are docid-
    ordered, so positional ties ARE docid ties), bit-identical to the
    mesh answer on a frozen corpus (pinned by the multiproc tests)."""
    from ..ops.ranking import CardinalRanker
    plist = index.rwi.get(termhash)
    if plist is None or len(plist) == 0:
        return np.empty(0, np.int32), np.empty(0, np.int32), 0
    s, d = CardinalRanker(profile, language).rank(plist, None, k=k)
    return s, d, len(plist)


# -- the member runtime ------------------------------------------------------

class MeshMember:
    """One OS process of the logical mesh: a P2PNode speaking the real
    HTTP wire + the shared MeshSegmentStore over the GLOBAL device mesh
    + the step runloop that keeps this process in SPMD lockstep."""

    def __init__(self, process_id: int, num_processes: int,
                 http_ports: list[int], ndocs: int = 512,
                 seed: int = 3, n_term: int = 1,
                 data_dir: str | None = None, devices=None):
        from ..peers.node import P2PNode
        from ..peers.seed import Seed, make_seed_hash
        from ..peers.transport import HttpTransport

        self.process_id = process_id
        self.num_processes = num_processes
        self.http_ports = list(http_ports)
        self.name = f"mesh{process_id}"
        self._stop = threading.Event()
        # bounded: a flooding (or buggy) peer scattering steps faster
        # than the runloop executes them must hit backpressure at the
        # wire, not grow an unbounded step backlog (the coordinator
        # serializes on _serve_lock, so a handful is the healthy depth)
        self._steps: "_queue.Queue" = _queue.Queue(maxsize=512)
        self._pending: dict[int, dict] = {}
        self._plock = profiling.ObservedLock("mesh_plock")
        self._serve_lock = threading.Lock()
        self._seq = 0
        # per-process serving counters (the ISSUE 12 availability
        # contract: every step answers, collective or host)
        self.queries_total = 0
        self.answered_collective = 0
        self.answered_host = 0
        self.step_errors = 0
        self.member_down_steps = 0
        self.commit_timeouts = 0
        self.incidents: list[dict] = []
        self._incident_seq = 0    # monotonic per process (ISSUE 19)
        self._member_state: dict[int, str] = {}     # id -> ok|lost|down
        # tail forensics (ISSUE 15a): every executed step produces a
        # span segment (queue wait / commit [collective-entry] wait /
        # local execution wall).  The coordinator feeds its own
        # segments straight into the process-global MeshTimeline;
        # members park theirs here and ship them INLINE on the next
        # meshstep/meshcommit reply — zero extra RPCs.
        self.timeline = tailattr.MESH if process_id == 0 else None
        self._segs_out: deque = deque(maxlen=128)

        t = HttpTransport(timeout_s=10.0)
        self.node = P2PNode(self.name, t, data_dir=data_dir,
                            port=http_ports[process_id],
                            partition_exponent=1, redundancy=1)
        self.sb = self.node.sb
        self.sb.mesh_member = self       # the PeerServer mesh endpoints
        self.node.serve_http(port=http_ports[process_id])
        # the member address book is fully determined by the env
        # contract (name + 127.0.0.1 + port IS the seed hash)
        self.peers = {}
        for j, port in enumerate(http_ports):
            if j == process_id:
                continue
            s = Seed(make_seed_hash(f"mesh{j}", "127.0.0.1", port),
                     name=f"mesh{j}", ip="127.0.0.1", port=port)
            self.node.seeddb.connected(s)
            t.set_address(s.hash, f"http://127.0.0.1:{port}")
            self.peers[j] = s

        devs = devices if devices is not None else global_mesh_devices()
        self.n_term = n_term
        self.n_doc = len(devs) // n_term
        build_corpus(self.sb, ndocs, seed, self.n_doc)
        self.store = self.sb.index.enable_mesh_serving(
            devices=devs, n_term=n_term)
        self.store.small_rank_n = 0
        self.fingerprint = partition_fingerprint(n_term, self.n_doc)
        self._data_dir = data_dir
        self._runner = threading.Thread(target=self._runloop,
                                        name=f"mesh-runloop-{process_id}",
                                        daemon=True)
        self._runner.start()
        # whitebox conviction evidence (ISSUE 20d): the coordinator
        # hooks the conviction tracker so every conviction edge fetches
        # the convicted member's OWN profile snapshot over the wire and
        # writes a conviction incident embedding it — the verdict stops
        # being "mesh1 was slowest" and starts being "mesh1 was slowest
        # and here is the stack it was burning on"
        if self.timeline is not None:
            tailattr.CONVICTIONS.set_conviction_hook(self._on_convicted)
        self.ready = True
        log.info("mesh member %d/%d up: pid=%d http=%d cells=%d fp=%s",
                 process_id, num_processes, os.getpid(),
                 self.node.http.port, len(devs), self.fingerprint)

    # -- step plumbing (every process, coordinator included) ----------------

    def _health(self) -> dict:
        return {"pid": os.getpid(), "proc": self.process_id,
                "n": self.num_processes, "ready": self.ready,
                "lost": bool(self.store.device_lost),
                "fp": self.fingerprint}

    def _enqueue_local(self, payload: dict) -> dict:
        rec = {"payload": dict(payload),
               "commit": threading.Event(), "go": False,
               "done": threading.Event(), "result": None,
               "mode": "host",
               "t_enq": time.perf_counter(), "ts0": time.time()}
        with self._plock:
            self._pending[int(payload["seq"])] = rec
        self._steps.put(rec)
        return rec

    def _drain_segments(self) -> list[dict]:
        with self._plock:
            segs = list(self._segs_out)
            self._segs_out.clear()
        return segs

    def _note_segment(self, rec: dict) -> None:
        """One executed step's span segment: the coordinator assembles
        it immediately; members park it for the next scatter reply."""
        if not tailattr.enabled():
            return
        seg = {"seq": int(rec["payload"].get("seq", -1)),
               "m": self.process_id,
               "q_ms": round(rec.get("q_ms", 0.0), 3),
               "commit_ms": round(rec.get("commit_ms", 0.0), 3),
               "entry_ms": round(rec.get("entry_ms", 0.0), 3),
               "exec_ms": round(rec.get("exec_ms", 0.0), 3),
               "mode": rec.get("mode", "?"),
               "ts0": round(rec.get("ts0", 0.0), 6)}
        if self.timeline is not None:
            self.timeline.add_segment(seg)
        else:
            with self._plock:
                self._segs_out.append(seg)

    def enqueue_step(self, payload: dict) -> dict:
        """Phase 1 (wire): enqueue, ack with health + any pending step
        segments (ISSUE 15a — completed steps' timelines ride the
        scatter the coordinator already pays for)."""
        self._enqueue_local(payload)
        return {**self._health(), "segs": self._drain_segments()}

    def commit_step(self, seq: int, go: bool) -> dict:
        with self._plock:
            rec = self._pending.get(int(seq))
        if rec is None:
            return {"error": f"unknown seq {seq}", **self._health()}
        rec["go"] = bool(go)
        rec["commit"].set()
        return {**self._health(), "segs": self._drain_segments()}

    def _runloop(self) -> None:
        while not self._stop.is_set():
            try:
                rec = self._steps.get(timeout=0.25)
            except _queue.Empty:
                continue
            if rec is None:
                return
            # segment timing (ISSUE 15a): queue wait = enqueue ->
            # runloop pickup (steps serialized behind earlier ones);
            # commit wait = pickup -> go/no-go decided (the collective-
            # entry wait: no process enters the SPMD program before the
            # fleet-wide verdict lands)
            t_deq = time.perf_counter()
            rec["q_ms"] = (t_deq - rec.get("t_enq", t_deq)) * 1000.0
            if not rec["commit"].wait(timeout=COMMIT_TIMEOUT_S):
                # the commit never arrived (coordinator died between
                # phases): decide LOCALLY for host mode — bounded, and
                # a peer that entered the collective without us errors
                # out of it on the fabric timeout (rank_term_mp catches)
                with self._plock:
                    self.commit_timeouts += 1
                rec["go"] = False
            rec["commit_ms"] = (time.perf_counter() - t_deq) * 1000.0
            try:
                self._execute(rec)
            except Exception:
                # a malformed step (bad hex / profile string off the
                # wire) must cost ONE empty answer, never the runloop
                # thread — a dead runloop wedges every later query on
                # every process (the availability contract's worst
                # enemy is a daemon thread dying quietly)
                log.exception("mesh step execution failed (seq=%s)",
                              rec["payload"].get("seq"))
                rec["result"] = (np.empty(0, np.int32),
                                 np.empty(0, np.int32), 0)
                rec["mode"] = "error"
                with self._plock:
                    self.queries_total += 1
                    self.step_errors += 1
                    self._pending.pop(int(rec["payload"].get("seq", -1)),
                                      None)
            finally:
                self._note_segment(rec)
                rec["done"].set()

    def _execute(self, rec: dict) -> None:
        from ..ops.ranking import RankingProfile
        p = rec["payload"]
        termhash = bytes.fromhex(p["term"])
        profile = RankingProfile.from_external_string(p["profile"])
        lang = p.get("lang", "en")
        k = int(p.get("k", 10))
        t_ex = time.perf_counter()
        # env-gated straggler injection (ISSUE 15): a latency armed in
        # ONE member (via do_meshfault) slows exactly that member's
        # step execution — the deterministic driver for the
        # collective_straggler verdict and the scoreboard tests
        faultinject.sleep("mesh.step")
        # segment split (ISSUE 15a): `entry_ms` is this member's LOCAL
        # pre-dispatch wall — a late member shows its lateness HERE,
        # while the others' stalls land in their exec wall as they
        # block at the collective entry.  In an SPMD collective every
        # member's exec wall inflates identically when one straggles,
        # so entry lateness is the signal that NAMES the straggler.
        t_disp = time.perf_counter()
        rec["entry_ms"] = (t_disp - t_ex) * 1000.0
        out = None
        if rec["go"]:
            out = self.store.rank_term_mp(termhash, profile, lang, k)
        if out is not None:
            rec["mode"] = "collective"
            with self._plock:
                self.answered_collective += 1
        else:
            s, d, considered = host_rank(self.sb.index, termhash,
                                         profile, lang, k)
            out = (s, d, considered)
            rec["mode"] = "host"
            with self._plock:
                self.answered_host += 1
        rec["exec_ms"] = (time.perf_counter() - t_disp) * 1000.0
        with self._plock:
            self.queries_total += 1
            self._pending.pop(int(p["seq"]), None)
        rec["result"] = out

    # -- the coordinator's scatter (process 0) -------------------------------

    def serve_query(self, term_hex: str, profile_ext: str,
                    lang: str = "en", k: int = 10) -> dict:
        """scatter → score → fuse → respond, across process boundaries.

        Phase 1 scatters the step to every member over the HTTP wire
        (the reply doubles as the liveness/health probe and carries the
        partition fingerprint), phase 2 commits one fleet-wide go/no-go,
        then every process — this one included — executes the step: a
        cross-process SPMD collective when committed, the host answer
        when degraded.  100% of queries answer either way."""
        # lint: blocking-ok(SPMD lockstep: the coordinator scatter is
        # deliberately serialized — _serve_lock IS the fleet-wide step
        # ordering, so the RPCs and the step wait belong inside it)
        with self._serve_lock, tracing.trace("mesh.serve"):
            t_q0 = time.perf_counter()
            seq = self._seq
            self._seq += 1
            step = {"seq": seq, "kind": "rank_term", "term": term_hex,
                    "profile": profile_ext, "lang": lang, "k": k}
            pids = {self.process_id: os.getpid()}
            go = not self.store.device_lost
            for j, seed in sorted(self.peers.items()):
                ok, rep = self.node.protocol.mesh_rpc(
                    seed, "meshstep", dict(step))
                if not ok:
                    self._note_member(j, "down", None)
                    self.member_down_steps += 1
                    go = False
                    continue
                self._ingest_segments(rep)
                pids[j] = int(rep.get("pid", -1))
                if rep.get("fp") != self.fingerprint:
                    # divergent partition math would return WRONG
                    # rankings silently: refuse collectives with it
                    self._note_member(j, "down",
                                      rep.get("pid"),
                                      cause="partition_fingerprint")
                    go = False
                elif rep.get("lost"):
                    self._note_member(j, "lost", rep.get("pid"))
                    go = False
                else:
                    self._note_member(j, "ok", rep.get("pid"))
            # cross-process scatter assembly (ISSUE 15a): register the
            # step's timeline record over EXACTLY the processes that
            # acked phase 1 (+ self) — a down member must not hold the
            # waterfall/verdict incomplete forever
            if self.timeline is not None:
                culprit = ""
                if not go:
                    # name the member whose state broke the collective,
                    # self first — the host-fallback verdict carries it
                    if self.store.device_lost:
                        culprit = f"mesh{self.process_id}"
                    else:
                        bad = sorted(j for j, st
                                     in self._member_state.items()
                                     if st != "ok")
                        culprit = f"mesh{bad[0]}" if bad else ""
                self.timeline.note_step(
                    seq, tracing.current_trace_id() or "",
                    pids.keys(), "collective" if go else "host",
                    culprit=culprit)
            for j, seed in sorted(self.peers.items()):
                ok, rep = self.node.protocol.mesh_rpc(
                    seed, "meshcommit", {"seq": seq, "go": go})
                if ok:
                    self._ingest_segments(rep)
            lrec = self._enqueue_local(step)
            self.commit_step(seq, go)
            lrec["done"].wait(timeout=COMMIT_TIMEOUT_S + 40.0)
            if self.timeline is not None:
                self.timeline.finish_step(
                    seq, (time.perf_counter() - t_q0) * 1000.0)
            # deliberately NO mesh.serve histogram family: a scheduled
            # mesh.step straggle slows EVERY collective step, so a
            # cached-p95 exemplar gate would adapt to the fault within
            # one rotation and stop classifying exactly the queries the
            # game day must attribute.  mesh.serve roots gate on the
            # fixed `tail.minMs` floor; deployments whose healthy
            # collective wall exceeds the default floor raise the knob.
            s, d, considered = lrec["result"] or \
                (np.empty(0, np.int32), np.empty(0, np.int32), 0)
            return {"seq": seq, "mode": lrec["mode"], "go": bool(go),
                    "scores": np.asarray(s).tolist(),
                    "docids": np.asarray(d).tolist(),
                    "considered": int(considered),
                    "pids": {str(j): p for j, p in pids.items()},
                    "trace": tracing.current_trace_id()}

    def _ingest_segments(self, rep: dict) -> None:
        """Feed step segments a member shipped inline on a scatter
        reply into the coordinator's timeline (members: no-op)."""
        if self.timeline is None or not isinstance(rep, dict):
            return
        segs = rep.get("segs")
        if isinstance(segs, list):
            for seg in segs:
                self.timeline.add_segment(seg)

    def _note_member(self, j: int, state: str, pid,
                     cause: str | None = None) -> None:
        """Edge-triggered member-state tracking: the ok->lost/down edge
        dumps a flight-recorder incident NAMING the member (the ISSUE 12
        acceptance trail); the recovery edge records the return."""
        prev = self._member_state.get(j, "ok")
        self._member_state[j] = state
        if state == prev:
            return
        # post-hoc join keys (ISSUE 19): monotonic per-process seq +
        # the armed-fault snapshot at dump time — wall clocks skew
        # across mesh processes, so the game-day verdict engine orders
        # by (pid, incident_seq) and matches the incident to its
        # scheduled fault by what was armed when it fired
        with self._plock:
            self._incident_seq += 1
            seq_no = self._incident_seq
        inc = {"kind": "incident",
               "name": f"mesh_member_{state}" if state != "ok"
               else "mesh_member_recovered",
               "member": f"mesh{j}", "member_id": j, "pid": pid,
               "cause": cause or state, "ts": round(time.time(), 3),
               "incident_seq": seq_no,
               "armed_faults": faultinject.snapshot()}
        self.incidents.append(inc)
        log.warning("mesh member incident: %s", inc)
        if self._data_dir:
            try:
                hdir = os.path.join(self._data_dir, "HEALTH")
                os.makedirs(hdir, exist_ok=True)
                path = os.path.join(
                    hdir, f"mesh-incident-{int(inc['ts'])}-mesh{j}.jsonl")
                with open(path, "a", encoding="utf-8") as f:
                    f.write(json.dumps(inc) + "\n")
            except OSError:
                log.exception("incident dump failed")

    # -- info / lifecycle -----------------------------------------------------

    def info(self, tick_health: bool = False,
             prime_tail_gate: bool = False) -> dict:
        eng = getattr(self.sb, "health", None)
        if prime_tail_gate:
            # warmup/measurement boundary: drop every family's
            # windowed samples so compile-era warmup walls (orders of
            # magnitude above the live workload) cannot sit in the
            # merged ring and hold the cached-p95 exemplar gate — and
            # the SLO burn windows — above everything the workload
            # will ever produce.  Until the first live window rotates
            # the tail gate sits at the `tail.minMs` floor.
            histogram.reset_windows()
        if tick_health and eng is not None:
            # node switchboards under the mesh runtime do not run the
            # 15_health busy thread; the wire caller (a drill or a test) drives
            # evaluation explicitly so burn-rate rules and the flight
            # recorder fire on the member's real histograms
            eng.tick()
        h = histogram.get("mesh.collective")
        hist = {"count": h.count if h else 0,
                "sum_ms": round(h.sum_ms, 3) if h else 0.0,
                "p50_ms": round(h.percentile(0.50), 3) if h else 0.0,
                "p95_ms": round(h.percentile(0.95), 3) if h else 0.0}
        fl = getattr(self.sb, "fleet", None)
        rows = fl.peer_rows() if fl is not None else []
        with self._plock:
            runtime = {
                "queries_total": self.queries_total,
                "answered_collective": self.answered_collective,
                "answered_host": self.answered_host,
                "step_errors": self.step_errors,
                "member_down_steps": self.member_down_steps,
                "commit_timeouts": self.commit_timeouts}
        # tail forensics (ISSUE 15): the coordinator's assembled view —
        # windowed cause histogram, verdict ring, straggler scoreboard
        # and the newest complete cross-process waterfall; members
        # report their local verdicts too
        if self.timeline is not None:
            # an owed verdict whose segments never fully arrived (lull
            # after a burst) finalizes from partial segments now — the
            # info caller is exactly who must not see a silent drop
            self.timeline.flush_pending()
        verdicts = tailattr.verdicts(8)
        strag_wf = None
        if self.timeline is not None:
            # the assembled waterfall OF an over-threshold straggled
            # query (what tools/tail_report.py shows), not just the
            # newest complete step
            for v in verdicts:
                if v.cause == "collective_straggler":
                    strag_wf = self.timeline.waterfall(
                        v.evidence.get("seq"))
                    break
        tail = {
            "causes": tailattr.windowed_causes(),
            "cause_totals": tailattr.cause_totals(),
            "stragglers": tailattr.straggler_totals(),
            "verdicts": [v.to_json() for v in verdicts],
            "scoreboard": tailattr.scoreboard(),
            "waterfall": (self.timeline.waterfall()
                          if self.timeline is not None else None),
            "straggled_waterfall": strag_wf,
            "segments_merged": (self.timeline.segments_merged
                                if self.timeline is not None else 0),
            "pending_partial": (self.timeline.pending_partial
                                if self.timeline is not None else 0),
            # ROADMAP 1c read-only slice (ISSUE 19): conviction edges
            # (member slowest over N consecutive windows) + zero-filled
            # totals over every member this timeline scattered to
            "convictions": tailattr.conviction_totals(),
            "conviction_crumbs": tailattr.conviction_breadcrumbs(10),
        }
        health_incs = []
        incident_tail = None
        if eng is not None:
            for inc in eng.incidents:
                health_incs.append({"name": inc["name"],
                                    "ts": inc.get("ts"),
                                    "seq": inc.get("seq"),
                                    "armed_faults":
                                        inc.get("armed_faults", {}),
                                    "rules": list(inc["rules"])})
            if eng.incidents:
                # the newest incident's embedded tail evidence (the
                # ISSUE 15 acceptance surface: incidents carry causes)
                body = eng.incidents[-1]["body"]
                incident_tail = {}
                for line in body.splitlines():
                    try:
                        obj = json.loads(line)
                    except ValueError:
                        continue
                    if obj.get("kind") in ("tail_causes",
                                           "straggler_scoreboard"):
                        incident_tail[obj["kind"]] = obj
        return {**self._health(),
                "counters": self.store.counters(),
                "runtime": runtime,
                "collective_hist": hist,
                "digest_bytes": fl.last_digest_bytes if fl else 0,
                "fleet_peers": len(rows),
                # the gossiped process identities + arena epochs of the
                # OTHER mesh members (Network_Health_p's mesh columns)
                "peers_proc": [r.get("proc", {}) for r in rows],
                "peers_epoch": [r.get("epoch", 0) for r in rows],
                "incidents": list(self.incidents),
                "tail": tail,
                "health_incidents": health_incs,
                "incident_tail": incident_tail}

    def _on_convicted(self, crumb: dict) -> None:
        """Conviction-edge hook (ISSUE 20d, coordinator only): fetch
        the convicted member's whitebox profile over the wire (or read
        it locally for self-convictions), attach it to the crumb —
        health's flight recorder embeds crumbs verbatim — and write a
        dedicated conviction incident (the _note_member model)."""
        member = str(crumb.get("member", ""))
        try:
            j = int(member[4:]) if member.startswith("mesh") else -1
        except ValueError:
            j = -1
        prof = None
        if j == self.process_id:
            from ..utils import profiling
            prof = profiling.snapshot()
        elif j in self.peers:
            ok, rep = self.node.protocol.fetch_profile(self.peers[j])
            if ok and isinstance(rep.get("profile"), dict):
                prof = rep["profile"]
        if prof is not None:
            crumb["profile"] = prof
        with self._plock:
            self._incident_seq += 1
            seq_no = self._incident_seq
        inc = {"kind": "incident", "name": "straggler_convicted",
               "member": member, "member_id": j,
               "ts": round(time.time(), 3), "incident_seq": seq_no,
               "armed_faults": faultinject.snapshot(),
               "crumb": crumb}
        self.incidents.append(inc)
        log.warning("straggler conviction incident: %s (profile %s)",
                    member, "attached" if prof is not None else "absent")
        if self._data_dir:
            try:
                hdir = os.path.join(self._data_dir, "HEALTH")
                os.makedirs(hdir, exist_ok=True)
                path = os.path.join(
                    hdir,
                    f"mesh-conviction-{int(inc['ts'])}-{member}.jsonl")
                with open(path, "a", encoding="utf-8") as f:
                    f.write(json.dumps(inc) + "\n")
            except OSError:
                log.warning("conviction incident write failed")

    def close(self) -> None:
        self._stop.set()
        self._steps.put(None)
        self._runner.join(timeout=5.0)
        try:
            self.node.close()
        except Exception:
            log.exception("mesh member close failed")

    def run_until_stopped(self) -> None:
        """Child-process main: serve until the stop flag (wire shutdown
        or parent death) flips."""
        while not self._stop.is_set():
            time.sleep(0.2)


def _parent_death_watch(original_ppid: int, member: MeshMember) -> None:
    """Orphan safety net (ISSUE 12 satellite): if the supervisor dies,
    this process must not linger holding ports and a jax coordinator
    slot — poll the parent pid and exit hard on reparenting."""
    def watch():
        while True:
            if os.getppid() != original_ppid:
                log.error("parent died; mesh member exiting")
                os._exit(3)
            if member._stop.is_set():
                return
            time.sleep(0.5)
    threading.Thread(target=watch, name="mesh-ppid-watch",
                     daemon=True).start()


def main() -> int:
    """Child entry: ``python -m yacy_search_server_tpu.parallel.
    distributed`` with the YACY_MESH_* env contract set (the launcher
    does this; see parallel/launcher.py for the one-command bring-up)."""
    logging.basicConfig(level=logging.INFO)
    ppid = os.getppid()
    pid, nprocs = bootstrap_from_env()
    ports = [int(p) for p in os.environ[ENV_HTTP_PORTS].split(",")]
    member = MeshMember(
        pid, nprocs, ports,
        ndocs=int(os.environ.get(ENV_NDOCS, "512")),
        seed=int(os.environ.get(ENV_SEED, "3")),
        n_term=int(os.environ.get(ENV_NTERM, "1")),
        data_dir=os.environ.get(ENV_DATA_DIR) or None)
    _parent_death_watch(ppid, member)
    print(f"MESH_MEMBER_READY {pid} {os.getpid()} "
          f"{member.node.http.port}", flush=True)
    try:
        member.run_until_stopped()
    finally:
        member.close()
        try:
            import jax
            jax.distributed.shutdown()
        except Exception as e:
            log.debug("jax.distributed shutdown failed: %r", e)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
