"""Device-resident IVF ANN index — dense-first candidate generation.

The vector-side twin of the M82 compressed-residency story: doc
embeddings live **int8-quantized** (per-vector f16 scale, dequant fused
into the scoring matmul — ops/ann.py) in contiguous **per-cluster
slabs**, so probing a cluster is a contiguous gather window, and 10M+
vectors fit the HBM budget the f16 forward index never could
(dim 256: 262 B/vector quantized vs 512 B f16).

Residency is the M82 hot/warm/cold ladder applied to vectors:

- **hot** — clusters resident on device in one preallocated int8 arena
  (slab + scales + docids), probed by the batched fuse kernel;
- **warm** — cluster row blocks cached in host RAM (byte-budget LRU)
  after a cold read, scored host-side by the NumPy oracle (the same
  quantized math — ops/ann.ann_fuse_np);
- **cold** — the full slab on its mmap (``data_dir``); without a
  data_dir the slab is host RAM and the cold tier is empty.

Hot promotion rides the devstore batcher's existing ``promote`` part
kind (devstore._dispatch_promotes → _ann_promote_now →
:meth:`promote_cluster`): a warm/cold cluster accessed PROMOTE_AFTER
times is uploaded into free hot-arena rows asynchronously — the
triggering query serves host-side once, later queries probe it on
device.  The hot arena never evicts (vectors are immutable between
rebuilds; the greedy build-time fill plus promotion is the whole
policy).

``centroid_version`` bumps on every (re)build — it rides the hybrid
top-k cache key (devstore._hybrid_cache_key), so a cached dense-first
answer can never survive a centroid-set change.
"""

from __future__ import annotations

import threading
from collections import OrderedDict

import numpy as np

from ..ops.ann import (ANN_DEFAULT_NPROBE, ANN_DEFAULT_PROBE_LANES,
                       ann_assign_np, ann_fuse_np, merge_fused)


def quantize_rows(vecs: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Per-vector symmetric int8 quantization: ``q = round(v/scale)``
    with ``scale = max|v| / 127`` (f16-rounded so device and host
    dequantize identically). Zero vectors quantize to zeros, scale 0."""
    v = np.asarray(vecs, np.float32)
    amax = np.abs(v).max(axis=1)
    scale = (amax / 127.0).astype(np.float16)
    s32 = scale.astype(np.float32)
    safe = np.where(s32 > 0, s32, 1.0)
    q = np.clip(np.round(v / safe[:, None]), -127, 127).astype(np.int8)
    return q, scale


class AnnVectorIndex:
    """Clustered int8 vector index over one segment's doc embeddings."""

    # host-scored accesses before a warm/cold cluster is promotion
    # material (1 would promote on first touch — a scan-once workload
    # would churn the arena for nothing)
    PROMOTE_AFTER = 2
    # share of the hot arena the greedy build-time fill may consume;
    # the rest stays free for access-driven promotion, so the ladder
    # adapts to the observed probe distribution instead of freezing
    # the build-order prefix forever (there is no eviction — vectors
    # are immutable between rebuilds)
    HOT_FILL_FRACTION = 0.75

    def __init__(self, dim: int, data_dir: str | None = None,
                 device_budget_bytes: int = 1 << 30,
                 warm_budget_bytes: int = 1 << 28):
        self.dim = dim
        self.data_dir = data_dir
        self.device_budget_bytes = int(device_budget_bytes)
        self.warm_budget_bytes = int(warm_budget_bytes)
        self._lock = threading.RLock()
        # serializes device uploads/patches WITHOUT holding the index
        # lock across the transfer: plan()/cluster_rows must never
        # stall behind a (possibly seconds-long) hot-arena upload
        self._upload_lock = threading.Lock()
        self.built = False
        # bumps on every (re)build AND on every hot promotion: part of
        # the dense-first cache key — a promotion moves a cluster's
        # scoring venue (host oracle -> device kernel), whose fused
        # scores can differ by a float ulp of rounded boost, so cached
        # fused lists must be re-keyed rather than ever diverging from
        # recomputation
        self.centroid_version = 0
        # bumps ONLY on rebuild (the slab/centroid arrays were
        # replaced): snapshot-consistency key for in-flight host
        # scoring — promotions leave it unchanged
        self.layout_version = 0
        self.centroids: np.ndarray | None = None    # (C, dim) f32
        self._cent_dev = None
        self._cent_dev_device = None
        self._cent_dev_version = -1
        self._slab = None            # (n, dim) int8 — ndarray or memmap
        self._scales = None          # (n,) f16
        self._sdocids = None         # (n,) int32 slab row -> docid
        self._cstart = None          # (C,) int64
        self._ccount = None          # (C,) int64
        self._row_of = None          # (max_docid+1,) int32 docid -> row
        # hot arena (host mirror + lazy device copies)
        self._hot_cap = 0
        self._hot_used = 0
        self._hot_slab = None
        self._hot_scales = None
        self._hot_docids = None
        self._hot_map: dict[int, int] = {}    # cid -> hot start row
        self._hot_dev = None                  # (slab, scales, docids)
        self._hot_dev_device = None
        self._hot_pending: list[tuple[int, int]] = []   # un-uploaded
        # warm tier: cid -> int8 rows, byte-budget LRU (only populated
        # when the slab is mmap-backed; a RAM slab IS the warm tier)
        self._warm: OrderedDict[int, np.ndarray] = OrderedDict()
        self._warm_bytes = 0
        self._access: dict[int, int] = {}
        self._promote_inflight: set[int] = set()
        # counters (surfaced via devstore.counters -> yacy_ann_*)
        self.tier_hot_hits = 0
        self.tier_warm_hits = 0
        self.tier_cold_hits = 0
        self.promotions = 0
        self.promote_failures = 0
        self.lane_drops = 0          # whole clusters dropped by the
        #                              probe-lane budget (counted, never
        #                              a silent mid-cluster truncation)

    # -- build ---------------------------------------------------------------

    @property
    def row_bytes(self) -> int:
        return self.dim + 2 + 4      # int8 row + f16 scale + int32 docid

    def n_vectors(self) -> int:
        return 0 if self._sdocids is None else len(self._sdocids)

    def n_clusters(self) -> int:
        with self._lock:
            return 0 if self._ccount is None else len(self._ccount)

    def build_from_dense(self, dense, n_clusters: int | None = None,
                         **kw) -> None:
        """Build over a DenseVectorStore's live vectors (docid-aligned:
        slab row i of docid d carries dense._vecs[d])."""
        with dense._lock:
            n = dense._n
            vecs = dense._vecs[:n].astype(np.float32)
        self.build(lambda i0, i1: vecs[i0:i1], n,
                   n_clusters=n_clusters, **kw)

    def build(self, source, n: int, docids: np.ndarray | None = None,
              n_clusters: int | None = None, sample_n: int = 65536,
              iters: int = 3, seed: int = 0,
              chunk: int = 1 << 18) -> None:
        """(Re)build the IVF layout. ``source(i0, i1) -> (i1-i0, dim)``
        float32 — a chunk reader, so a 10M-vector corpus never has to
        materialize as one f32 matrix. Deterministic for a given
        (source, seed). Clusters lay out as contiguous slab row runs
        ordered by cluster id; within a cluster, source order."""
        if n <= 0:
            raise ValueError("cannot build an ANN index over 0 vectors")
        dim = self.dim
        ids = (np.arange(n, dtype=np.int64) if docids is None
               else np.asarray(docids, np.int64))
        C = n_clusters if n_clusters else max(1, min(4096, n // 2048))
        C = min(C, n)
        rng = np.random.default_rng(seed)
        # strided block sample for k-means (source order must not bias
        # the centroids toward the head of the corpus; contiguous
        # blocks keep the source-chunk reads cheap)
        sn = min(sample_n, n)
        bsz = min(256, sn)
        nblocks = (sn + bsz - 1) // bsz
        blocks = []
        for bi in range(nblocks):
            off = ((bi * max(n - bsz, 0)) // max(1, nblocks - 1)
                   if nblocks > 1 else 0)
            blocks.append(np.asarray(source(off, min(off + bsz, n)),
                                     np.float32))
        sample = np.concatenate(blocks)[:sn]
        cent = sample[rng.choice(len(sample), C, replace=False)] \
            .astype(np.float32)
        for _ in range(max(0, iters)):
            a = np.argmax(sample @ cent.T, axis=1)
            for c in range(C):
                rows = sample[a == c]
                if len(rows):
                    m = rows.mean(axis=0)
                    nm = float(np.linalg.norm(m))
                    cent[c] = m / nm if nm > 0 else m
        # full assignment, chunked (the one O(n*C*dim) pass)
        cids = np.empty(n, np.int32)
        for i0 in range(0, n, chunk):
            i1 = min(i0 + chunk, n)
            v = np.asarray(source(i0, i1), np.float32)
            cids[i0:i1] = np.argmax(v @ cent.T, axis=1)
        ccount = np.bincount(cids, minlength=C).astype(np.int64)
        cstart = np.zeros(C, np.int64)
        np.cumsum(ccount[:-1], out=cstart[1:])
        if self.data_dir:
            import os
            os.makedirs(self.data_dir, exist_ok=True)
            slab = np.lib.format.open_memmap(
                os.path.join(self.data_dir, "ann_slab.npy"), mode="w+",
                dtype=np.int8, shape=(n, dim))
        else:
            slab = np.zeros((n, dim), np.int8)
        scales = np.zeros(n, np.float16)
        sdocids = np.zeros(n, np.int32)
        cursor = cstart.copy()
        for i0 in range(0, n, chunk):
            i1 = min(i0 + chunk, n)
            q, s = quantize_rows(np.asarray(source(i0, i1), np.float32))
            cc = cids[i0:i1]
            # vectorized scatter: group the chunk's rows by cluster,
            # hand each group the next run of its cluster's slab rows
            order = np.argsort(cc, kind="stable")
            uniq, uidx, ucnt = np.unique(cc[order], return_index=True,
                                         return_counts=True)
            dst = np.empty(i1 - i0, np.int64)
            for u, st, cnt in zip(uniq.tolist(), uidx.tolist(),
                                  ucnt.tolist()):
                grp = order[st:st + cnt]
                dst[grp] = cursor[u] + np.arange(cnt, dtype=np.int64)
                cursor[u] += cnt
            slab[dst] = q
            scales[dst] = s
            sdocids[dst] = ids[i0:i1]
        row_of = np.full(int(ids.max()) + 1, -1, np.int32)
        row_of[sdocids] = np.arange(n, dtype=np.int32)
        # greedy hot fill (cluster id ASC) until the device budget;
        # promotion fills the remainder by observed access
        hot_cap = max(0, self.device_budget_bytes // self.row_bytes)
        with self._lock:
            self.centroids = cent
            self._slab, self._scales, self._sdocids = slab, scales, \
                sdocids
            self._cstart, self._ccount, self._row_of = cstart, ccount, \
                row_of
            self._hot_cap = hot_cap
            self._hot_slab = np.zeros((hot_cap, dim), np.int8) \
                if hot_cap else None
            self._hot_scales = np.zeros(hot_cap, np.float16) \
                if hot_cap else None
            self._hot_docids = np.full(hot_cap, 2 ** 31 - 1, np.int32) \
                if hot_cap else None
            self._hot_map.clear()
            self._hot_used = 0
            self._hot_dev = None
            self._hot_dev_device = None
            self._hot_pending = []
            self._warm.clear()
            self._warm_bytes = 0
            self._access.clear()
            self._promote_inflight.clear()
            fill_cap = int(hot_cap * self.HOT_FILL_FRACTION)
            for c in range(C):
                cnt = int(ccount[c])
                if cnt and self._hot_used + cnt > fill_cap:
                    break
                self._hot_place_locked(c)
            self._cent_dev = None
            self._cent_dev_version = -1
            self.built = True
            self.centroid_version += 1
            self.layout_version += 1

    def _hot_place_locked(self, cid: int) -> bool:
        """Copy one cluster's rows into the host hot mirror; the device
        patch uploads lazily (hot_block) or via promote_cluster."""
        cnt = int(self._ccount[cid])
        if cid in self._hot_map:
            return True
        if cnt == 0:
            self._hot_map[cid] = self._hot_used
            return True
        if self._hot_used + cnt > self._hot_cap:
            return False
        s = int(self._cstart[cid])
        h0 = self._hot_used
        self._hot_slab[h0:h0 + cnt] = self._slab[s:s + cnt]
        self._hot_scales[h0:h0 + cnt] = self._scales[s:s + cnt]
        self._hot_docids[h0:h0 + cnt] = self._sdocids[s:s + cnt]
        self._hot_map[cid] = h0
        self._hot_used = h0 + cnt
        self._hot_pending.append((h0, h0 + cnt))
        return True

    # -- device residency ----------------------------------------------------

    def centroid_block(self, device):
        """Device-resident f16 centroid matrix (C_pad pow2 rows; pad
        rows are zero vectors — their sims tie at 0 and the dispatcher
        drops ids >= n_clusters)."""
        import jax
        # found by the lint lock-blocking pass: the upload used to run
        # under the index lock, stalling plan()/cluster_rows behind the
        # transfer — snapshot under the lock, upload under the
        # dedicated upload lock, publish under the lock (hot_block's
        # discipline)
        # lint: blocking-ok(serializing uploads is _upload_lock's sole
        # purpose; the index lock is released for the transfer)
        with self._upload_lock:
            with self._lock:
                if (self._cent_dev is not None
                        and self._cent_dev_device is device
                        and self._cent_dev_version
                        == self.centroid_version):
                    return self._cent_dev
                C = len(self.centroids)
                cp = 1 << max(4, (C - 1).bit_length())
                buf = np.zeros((cp, self.dim), np.float16)
                buf[:C] = self.centroids.astype(np.float16)
                ver = self.centroid_version
            dev = jax.device_put(buf, device)
            with self._lock:
                self._cent_dev = dev
                self._cent_dev_device = device
                self._cent_dev_version = ver
                return self._cent_dev

    def hot_block(self, device):
        """The device-resident hot arena, as an atomic snapshot:
        ``((slab int8 [cap, dim], scales f16 [cap], docids int32
        [cap]), rows_covered)`` — full-capacity arrays (ONE compile
        shape per store) uploaded once, then patched with pending
        promoted ranges. Returns None when no hot arena exists.

        ``rows_covered`` is the row prefix the returned arrays are
        guaranteed to contain: a caller planning probe lanes against
        this snapshot must treat only clusters inside it as hot (a
        promotion landing AFTER the snapshot patches a LATER arena
        generation — its rows would be garbage in this one).

        The device transfers run under a dedicated upload lock with
        the index lock released: plan()/cluster_rows never stall
        behind an upload.  Host ranges are copied out under the index
        lock first, so a concurrent promotion appending to the host
        mirror can never tear a patch."""
        import jax
        # lint: blocking-ok(serializing uploads is _upload_lock's sole
        # purpose; the index lock is released for the transfer)
        with self._upload_lock:
            with self._lock:
                if self._hot_cap == 0:
                    return None
                fresh = (self._hot_dev is None
                         or self._hot_dev_device is not device)
                used = self._hot_used
                if fresh:
                    # full-capacity upload: rows beyond `used` may
                    # still be written by a racing promotion, but they
                    # are outside rows_covered and their pending range
                    # (appended under this lock AFTER the rows were
                    # written) re-patches them on the next call
                    host = (self._hot_slab, self._hot_scales,
                            self._hot_docids)
                    self._hot_pending = []
                    copies = []
                else:
                    copies = [(a, b, self._hot_slab[a:b].copy(),
                               self._hot_scales[a:b].copy(),
                               self._hot_docids[a:b].copy())
                              for a, b in self._hot_pending]
                    self._hot_pending = []
                    dev = self._hot_dev
            if fresh:
                dev = (jax.device_put(host[0], device),
                       jax.device_put(host[1], device),
                       jax.device_put(host[2], device))
            else:
                sl, sc, dd = dev
                for a, b, cs, cc, cd in copies:
                    sl = sl.at[a:b].set(jax.device_put(cs, device))
                    sc = sc.at[a:b].set(jax.device_put(cc, device))
                    dd = dd.at[a:b].set(jax.device_put(cd, device))
                dev = (sl, sc, dd)
            with self._lock:
                self._hot_dev = dev
                self._hot_dev_device = device
            return dev, used

    def promote_cluster(self, cid: int, device):
        """Upload one warm/cold cluster into free hot-arena rows —
        called from the devstore batcher's ``promote`` part dispatch
        (async, off the query path; the device patch runs OUTSIDE the
        index lock via hot_block). Bumps the centroid version: the
        cluster's scoring venue moved (host oracle -> device kernel),
        so cached fused lists re-key instead of ever diverging from a
        recomputation by a rounded-boost ulp. Returns a small
        fetchable device token confirming the upload landed, or None
        when the cluster is already hot / the arena is full
        (counted)."""
        with self._lock:
            self._promote_inflight.discard(cid)
            if cid in self._hot_map or self._hot_cap == 0:
                return None
            if not self._hot_place_locked(cid):
                self.promote_failures += 1
                return None
            self.promotions += 1
            self.centroid_version += 1
            had_dev = (self._hot_dev is not None
                       and self._hot_dev_device is device)
        if not had_dev:
            return None
        got = self.hot_block(device)
        return got[0][2][:1] if got is not None else None

    # -- probing -------------------------------------------------------------

    def assign_host(self, qvecs: np.ndarray, nprobe: int) -> np.ndarray:
        """Host centroid assignment (the device-loss fallback and the
        tiny-index path): same bf16-rounded math as the kernel."""
        with self._lock:     # centroid ref snapshot (replaced by build)
            cents = self.centroids
        return ann_assign_np(cents, np.atleast_2d(qvecs), nprobe)

    def _snapshot_locked(self) -> dict:
        """One consistent view of the slab-layout arrays (replaced
        wholesale by build(), never mutated in place) — in-flight host
        scoring pairs offsets with THESE refs, so a concurrent rebuild
        can never mix generations mid-query."""
        return {"layout": self.layout_version, "slab": self._slab,
                "scales": self._scales, "sdocids": self._sdocids,
                "cstart": self._cstart, "ccount": self._ccount}

    def plan(self, cids, sparse_docids, sparse_scores,
             lanes_budget: int | None = None,
             hot_limit: int | None = None) -> dict:
        """Turn one slot's probed cluster ids + sparse candidates into
        lane lists: hot probe rows (device kernel lanes), host-scored
        clusters (warm/cold), sparse lanes split the same way, plus the
        promotion wish-list. Counts tier hits here — the plan IS the
        access.  ``hot_limit`` bounds the hot-arena row prefix the
        caller's device snapshot covers (hot_block's rows_covered): a
        cluster promoted after that snapshot plans as warm, never as a
        gather into rows the snapshot does not contain.  The returned
        plan carries the layout snapshot its offsets are valid
        against."""
        budget = lanes_budget or ANN_DEFAULT_PROBE_LANES
        hot_rows: list[np.ndarray] = []
        host_cids: list[int] = []
        promote: list[int] = []
        lanes = 0
        with self._lock:
            snap = self._snapshot_locked()
            C = self.n_clusters()
            limit = self._hot_used if hot_limit is None else hot_limit
            for cid in dict.fromkeys(int(c) for c in cids):
                if cid < 0 or cid >= C:
                    continue        # assignment pad lane
                cnt = int(self._ccount[cid])
                if cnt == 0:
                    continue
                if lanes + cnt > budget:
                    self.lane_drops += 1
                    continue        # whole-cluster drop, counted
                lanes += cnt
                h0 = self._hot_map.get(cid)
                hot = (h0 is not None and self._hot_dev is not None
                       and h0 + cnt <= limit)
                if hot:
                    self.tier_hot_hits += 1
                    hot_rows.append(
                        np.arange(h0, h0 + cnt, dtype=np.int32))
                else:
                    host_cids.append(cid)
                    self._access[cid] = self._access.get(cid, 0) + 1
                    if (h0 is None
                            and self._access[cid] >= self.PROMOTE_AFTER
                            and self._hot_used + cnt <= self._hot_cap
                            and cid not in self._promote_inflight):
                        self._promote_inflight.add(cid)
                        promote.append(cid)
            # sparse candidates: hot rows ride the kernel (their vector
            # gathers are free lanes), the rest score host-side
            sp_hot_rows: list[int] = []
            sp_hot_docids: list[int] = []
            sp_hot_scores: list[int] = []
            sp_host_rows: list[int] = []
            sp_host_docids: list[int] = []
            sp_host_scores: list[int] = []
            nrow = len(self._row_of)
            for d, sc in zip(np.asarray(sparse_docids).tolist(),
                             np.asarray(sparse_scores).tolist()):
                r = int(self._row_of[d]) if 0 <= d < nrow else -1
                hr = -1
                if r >= 0:
                    cid = int(np.searchsorted(self._cstart, r,
                                              side="right") - 1)
                    h0 = self._hot_map.get(cid)
                    cnt = int(self._ccount[cid])
                    if (h0 is not None and self._hot_dev is not None
                            and h0 + cnt <= limit):
                        hr = h0 + (r - int(self._cstart[cid]))
                if hr >= 0 or (r < 0 and self._hot_dev is not None):
                    # hot vector — or no vector at all (scores
                    # sparse+0 on device; absence must not drop it)
                    sp_hot_rows.append(hr)
                    sp_hot_docids.append(d)
                    sp_hot_scores.append(int(sc))
                else:
                    # warm/cold vector — or vectorless with NO device
                    # arena to ride: the host oracle scores sparse+0
                    sp_host_rows.append(r)
                    sp_host_docids.append(d)
                    sp_host_scores.append(int(sc))
        return {
            "hot_rows": (np.concatenate(hot_rows)
                         if hot_rows else np.empty(0, np.int32)),
            "host_cids": host_cids,
            "sp_hot": (np.asarray(sp_hot_rows, np.int32),
                       np.asarray(sp_hot_docids, np.int32),
                       np.asarray(sp_hot_scores, np.int32)),
            "sp_host": (np.asarray(sp_host_rows, np.int32),
                        np.asarray(sp_host_docids, np.int32),
                        np.asarray(sp_host_scores, np.int32)),
            "promote": promote,
            "snap": snap,
        }

    def cluster_rows(self, cid: int,
                     snap: dict | None = None) -> tuple[np.ndarray, int]:
        """One cluster's int8 rows (and its slab start) through the
        warm tier: a RAM slab serves directly (warm); an mmap slab
        fills the byte-budget LRU on first read (cold), then serves
        from it (warm).  With a `snap` from an OLDER layout generation
        (a rebuild landed since the plan), the rows read straight off
        the snapshot's own arrays — consistent with the plan's
        offsets, bypassing the (new-generation) warm cache."""
        with self._lock:
            if snap is not None \
                    and snap["layout"] != self.layout_version:
                s = int(snap["cstart"][cid])
                cnt = int(snap["ccount"][cid])
                return np.asarray(snap["slab"][s:s + cnt]), s
            s = int(self._cstart[cid])
            cnt = int(self._ccount[cid])
            if not isinstance(self._slab, np.memmap):
                self.tier_warm_hits += 1
                return self._slab[s:s + cnt], s
            got = self._warm.get(cid)
            if got is not None:
                self._warm.move_to_end(cid)
                self.tier_warm_hits += 1
                return got, s
            rows = np.asarray(self._slab[s:s + cnt])
            self.tier_cold_hits += 1
            self._warm[cid] = rows
            self._warm_bytes += rows.nbytes
            while self._warm_bytes > self.warm_budget_bytes and \
                    len(self._warm) > 1:
                _, old = self._warm.popitem(last=False)
                self._warm_bytes -= old.nbytes
            return rows, s

    def host_score_parts(self, plan: dict, qvec, alpha: float,
                         k: int) -> list:
        """Score a plan's warm/cold clusters + host-side sparse lanes
        with the NumPy oracle (the exact same quantized math as the
        kernel) — returns fused (scores, docids) part lists for
        ops/ann.merge_fused.  All array reads go through the plan's
        layout snapshot, so a rebuild racing an in-flight query can
        never pair old offsets with new arrays."""
        snap = plan["snap"]
        parts = []
        for cid in plan["host_cids"]:
            rows, s = self.cluster_rows(cid, snap=snap)
            cnt = len(rows)
            if cnt == 0:
                continue
            parts.append(ann_fuse_np(
                rows, snap["scales"][s:s + cnt],
                snap["sdocids"][s:s + cnt],
                np.arange(cnt, dtype=np.int32),
                np.full(cnt, -1, np.int32), np.zeros(cnt, np.int32),
                qvec, alpha, k))
        rr, dd, ss = plan["sp_host"]
        if len(dd):
            parts.append(ann_fuse_np(snap["slab"], snap["scales"],
                                     snap["sdocids"], rr, dd, ss,
                                     qvec, alpha, k))
        return parts

    def search_host(self, qvec, sparse_docids, sparse_scores,
                    alpha: float, k: int,
                    nprobe: int = ANN_DEFAULT_NPROBE,
                    lanes_budget: int | None = None):
        """Full host dense-first answer (device loss / no devstore):
        host assignment + oracle scoring of every probed cluster +
        sparse lanes, merged under the pinned tie discipline. The
        hot/warm split is ignored — everything reads host-side (hot
        clusters score from the host mirror via the slab)."""
        with self._lock:
            snap = self._snapshot_locked()
            row_of = self._row_of
            cent = self.centroids
            C = self.n_clusters()
        cids = ann_assign_np(cent, np.atleast_2d(qvec), nprobe)[0]
        parts = []
        budget = lanes_budget or ANN_DEFAULT_PROBE_LANES
        lanes = 0
        for cid in dict.fromkeys(int(c) for c in cids):
            if cid < 0 or cid >= C:
                continue
            rows, s = self.cluster_rows(cid, snap=snap)
            cnt = len(rows)
            if cnt == 0:
                continue
            if lanes + cnt > budget:
                with self._lock:
                    self.lane_drops += 1
                continue
            lanes += cnt
            parts.append(ann_fuse_np(
                rows, snap["scales"][s:s + cnt],
                snap["sdocids"][s:s + cnt],
                np.arange(cnt, dtype=np.int32),
                np.full(cnt, -1, np.int32), np.zeros(cnt, np.int32),
                qvec, alpha, k))
        dd = np.asarray(sparse_docids, np.int64)
        if len(dd):
            nrow = len(row_of)
            rr = np.where((dd >= 0) & (dd < nrow),
                          row_of[np.clip(dd, 0, nrow - 1)], -1)
            parts.append(ann_fuse_np(
                snap["slab"], snap["scales"], snap["sdocids"],
                rr.astype(np.int32), dd.astype(np.int32),
                np.asarray(sparse_scores, np.int32), qvec, alpha, k))
        return merge_fused(parts, k)

    def exact_topk(self, qvec, k: int, chunk: int = 1 << 19):
        """The exact host oracle over the WHOLE quantized corpus
        (chunked full scan) — the recall denominator of the recall tests
        (tests/test_ann.py). Same quantized score
        domain as the probe path; (score DESC, docid ASC) ties."""
        q = np.asarray(qvec, np.float32)
        # one consistent ref snapshot: build() replaces these arrays
        # wholesale, so the chunk loop must not mix generations
        with self._lock:
            slab, scales, sdocids = self._slab, self._scales, \
                self._sdocids
            n = 0 if sdocids is None else len(sdocids)
        best_s = np.empty(0, np.float64)
        best_d = np.empty(0, np.int64)
        for i0 in range(0, n, chunk):
            i1 = min(i0 + chunk, n)
            sims = (np.asarray(slab[i0:i1], np.float32) @ q) \
                * np.asarray(scales[i0:i1], np.float32)
            dd = sdocids[i0:i1].astype(np.int64)
            s = np.concatenate([best_s, sims])
            d = np.concatenate([best_d, dd])
            order = np.lexsort((d, -s))[:k]
            best_s, best_d = s[order], d[order]
        return best_s, best_d.astype(np.int32)

    # -- accounting ----------------------------------------------------------

    def tier_bytes(self) -> dict:
        with self._lock:
            hot = self._hot_used * self.row_bytes
            n = self.n_vectors()
            if isinstance(self._slab, np.memmap):
                warm = self._warm_bytes
                cold = n * self.row_bytes
            else:
                warm = n * self.row_bytes
                cold = 0
        return {"hot": hot, "warm": warm, "cold": cold}

    def counters(self) -> dict:
        tb = self.tier_bytes()
        with self._lock:
            return {
                "ann_vectors": self.n_vectors(),
                "ann_clusters": self.n_clusters(),
                "ann_centroid_version": self.centroid_version,
                "ann_hot_bytes": tb["hot"],
                "ann_warm_bytes": tb["warm"],
                "ann_cold_bytes": tb["cold"],
                "ann_tier_hot_hits": self.tier_hot_hits,
                "ann_tier_warm_hits": self.tier_warm_hits,
                "ann_tier_cold_hits": self.tier_cold_hits,
                "ann_promotions": self.promotions,
                "ann_promote_failures": self.promote_failures,
                "ann_lane_drops": self.lane_drops,
            }
