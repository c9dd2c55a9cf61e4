"""Device-mesh query execution — the DHT axes as a 2-D TPU mesh.

TPU-first re-design of the reference's inter-node parallelism
(reference: source/net/yacy/cora/federate/yacy/Distribution.java:35-93 —
horizontal term ring x vertical doc partitions; scatter-gather merge in
source/net/yacy/search/query/SearchEvent.java:444-497 and
peers/RemoteSearch.java:172). Instead of one thread per remote peer feeding
a bounded heap, a query executes as ONE jitted SPMD program over a
`jax.sharding.Mesh` with axes:

    term : horizontal DHT axis — query-term columns of the dense tf block
           (BM25 partial scores combine with a psum over this axis)
    doc  : vertical DHT axis — postings rows partitioned by url-hash
           (normalization stats combine with pmin/pmax/psum; candidates
           combine with all_gather + global top-k)

so the reference's per-peer heap inserts become ICI collectives: the
"16 vertical partitions" of the freeworld network are 16-way `doc`
parallelism, and redundancy groups become replica submeshes. The WAN peer
layer (peers/) reuses the same fusion kernel for asynchronous remote
results.

Parity contract: the sharded kernels reuse ops/ranking.local_stats /
cardinal_from_stats, merging the shard-local statistics with
lax.pmin/pmax/psum — results are identical to the single-device
CardinalRanker (tested on the 8-device virtual CPU mesh).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as PS

from ..index import postings as P
from ..ops import ranking as R

NEG_INF_I32 = -(2**31 - 1)


def best_devices(need: int | None = None, prefer_cpu: bool = False):
    """Device pool for an n-way mesh — for TESTS and
    ``__graft_entry__.dryrun_multichip`` only. No serving path calls
    this: ``MeshSegmentStore`` takes ``jax.devices()``, so a node on a
    chip never lands on the virtual CPU pool.

    Default policy: the default backend, falling back to the virtual CPU
    pool when the default backend has fewer devices than requested
    (xla_force_host_platform_device_count — the documented test pattern
    for multi-chip shardings).

    prefer_cpu=True inverts the preference: take the CPU pool whenever it
    satisfies `need` (the driver's multichip dryrun contract — CPU
    validation that must not couple to default-backend health)."""
    try:
        cpu = jax.devices("cpu")
    except RuntimeError:
        cpu = []
    if prefer_cpu and need is not None and len(cpu) >= need:
        return cpu
    devs = jax.devices()
    if need is not None and len(devs) < need and len(cpu) >= need:
        devs = cpu
    return devs


def make_mesh(n_doc: int | None = None, n_term: int = 1,
              devices=None) -> Mesh:
    """Build a ('term', 'doc') mesh; defaults to all devices on one doc axis."""
    need = n_term * n_doc if n_doc is not None else None
    devs = np.asarray(devices if devices is not None else best_devices(need))
    if n_doc is None:
        n_doc = len(devs) // n_term
    use = devs[: n_term * n_doc].reshape(n_term, n_doc)
    return Mesh(use, axis_names=("term", "doc"))


def pad_to_shards(n: int, shards: int, tile: int = 128) -> int:
    """Round n up so every shard holds a whole number of tiles (min 1)."""
    per = max(tile, ((n + shards - 1) // shards + tile - 1) // tile * tile)
    return per * shards


# ---------------------------------------------------------------------------
# Fused all-gather + top-k — the candidate-fusion collective (ISSUE 12b)
# ---------------------------------------------------------------------------
# The TPU replacement of the reference's per-peer heap-insert merge
# (SearchEvent.java:444-497), factored out of the shard bodies so every
# fusion site shares ONE implementation — and ONE tie discipline.  Each
# shard contributes only its exact local top-k (the meshstore docstring's
# exactness argument: an exact local top-k per shard makes the gathered
# merge exact), so the collective moves k rows per shard, never full
# score rows.  The merge is pinned to (score DESC, docid ASC) — the
# two-key lax.sort idiom the rerank/ANN family pinned node-locally
# (arxiv 1807.05798) — so equal-score candidates arriving from
# DIFFERENT shards (or, through parallel/distributed.py, different OS
# processes) fuse in one deterministic order instead of gather-position
# order, which would flap with the mesh layout.


def tie_topk(scores, docids, k: int):
    """Exact top-k of (scores, docids) under (score DESC, docid ASC).

    Two-key ascending sort on (-score, docid); works for int32 cardinal
    scores and float32 BM25 scores alike (pad rows carry -inf/NEG_INF
    scores, so they sort last regardless of their docid)."""
    _sk, _tk, s, d = lax.sort((-scores, docids, scores, docids),
                              num_keys=2)
    kk = min(k, s.shape[0])
    return s[:kk], d[:kk]


def all_gather_topk(local_s, local_d, axes, k: int):
    """THE candidate-fusion collective (every fusion site calls it;
    there is no second implementation — the Pallas remote-DMA ring that
    used to sit beside it was refused by jax 0.9.0 at trace time on four
    v5e chips and was deleted in PR 21): gather each shard's (already
    exact, already tie-ordered) local top-k along `axes` and merge
    under the pinned tie discipline.  Gathered bytes
    scale with k·n_shards (8 B per candidate), not with corpus rows —
    the cost model in ops/roofline.KERNELS counts exactly that."""
    gs = lax.all_gather(local_s, axes, tiled=True)
    gd = lax.all_gather(local_d, axes, tiled=True)
    return tie_topk(gs, gd, k)


def all_gather_topk_full(local_s, local_d, axes):
    """Variant returning the WHOLE tie-ordered gather (no trim): the
    delta-carrying meshstore path needs every gathered row so host-side
    dedup still has k unique docids left."""
    gs = lax.all_gather(local_s, axes, tiled=True)
    gd = lax.all_gather(local_d, axes, tiled=True)
    return tie_topk(gs, gd, gs.shape[0])


# ---------------------------------------------------------------------------
# Sharded cardinal ranking (ReferenceOrder.cardinal over the doc axis)
# ---------------------------------------------------------------------------

def _cardinal_shard(feats, docids, valid, hostids, norm_coeffs, flag_bits,
                    flag_shifts, domlength_coeff, tf_coeff, language_coeff,
                    authority_coeff, language_pref, *, k: int,
                    num_hosts: int):
    st = R.local_stats(feats, valid, hostids, num_hosts=num_hosts)
    st = {
        "col_min": lax.pmin(st["col_min"], "doc"),
        "col_max": lax.pmax(st["col_max"], "doc"),
        "tf_min": lax.pmin(st["tf_min"], "doc"),
        "tf_max": lax.pmax(st["tf_max"], "doc"),
        "host_counts": lax.psum(st["host_counts"], "doc"),
    }
    scores = R.cardinal_from_stats(
        feats, valid, hostids, st, norm_coeffs, flag_bits, flag_shifts,
        domlength_coeff, tf_coeff, language_coeff, authority_coeff,
        language_pref)
    # local EXACT top-k under the pinned tie discipline, then the fused
    # all-gather+top-k collective — k rows per shard cross the
    # interconnect, the TPU replacement of the reference's per-peer
    # heap-insert merge (heap semantics: only each peer's best k travel)
    local_s, local_d = tie_topk(scores, docids, min(k, scores.shape[0]))
    return all_gather_topk(local_s, local_d, "doc", k)


def build_sharded_cardinal(mesh: Mesh, k: int, num_hosts: int):
    """jit-compiled sharded cardinal+top-k over `mesh` ('doc' axis)."""
    fn = jax.shard_map(
        partial(_cardinal_shard, k=k, num_hosts=num_hosts),
        mesh=mesh,
        in_specs=(PS("doc"), PS("doc"), PS("doc"), PS("doc"),
                  PS(), PS(), PS(), PS(), PS(), PS(), PS(), PS()),
        out_specs=(PS(), PS()),
        check_vma=False,  # outputs are replicated by the all_gather+top_k
    )
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# Sharded BM25 (dense doc x term block over the full 2-D mesh)
# ---------------------------------------------------------------------------

def _bm25_shard(tf, doclen, df, ndocs, valid, docids, *, k: int,
                k1: float, b: float):
    tf = tf.astype(jnp.float32)
    dl = doclen.astype(jnp.float32)
    sum_dl = lax.psum(jnp.sum(jnp.where(valid, dl, 0.0)), "doc")
    cnt = lax.psum(jnp.sum(valid.astype(jnp.float32)), "doc")
    avgdl = sum_dl / jnp.maximum(cnt, 1.0)
    idf = jnp.log(1.0 + (ndocs.astype(jnp.float32) - df + 0.5) / (df + 0.5))
    denom = tf + k1 * (1.0 - b + b * (dl / jnp.maximum(avgdl, 1e-6))[:, None])
    partial_score = jnp.sum(
        idf[None, :] * tf * (k1 + 1.0) / jnp.maximum(denom, 1e-9), axis=1)
    score = lax.psum(partial_score, "term")
    score = jnp.where(valid, score, -jnp.inf)
    local_s, local_d = tie_topk(score, docids, min(k, score.shape[0]))
    return all_gather_topk(local_s, local_d, "doc", k)


def build_sharded_bm25(mesh: Mesh, k: int, k1: float = 1.2, b: float = 0.75):
    """jit-compiled sharded BM25+top-k over the ('term','doc') mesh."""
    fn = jax.shard_map(
        partial(_bm25_shard, k=k, k1=k1, b=b),
        mesh=mesh,
        in_specs=(PS("doc", "term"), PS("doc"), PS("term"), PS(),
                  PS("doc"), PS("doc")),
        out_specs=(PS(), PS()),
        check_vma=False,  # outputs are replicated by the all_gather+top_k
    )
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# Host-side wrappers
# ---------------------------------------------------------------------------

class MeshRanker:
    """Sharded CardinalRanker: pad to shard tiles, place, run, trim.

    The mesh analog of ops/ranking.CardinalRanker; used by the sharded
    segment store.
    """

    def __init__(self, mesh: Mesh, profile: R.RankingProfile | None = None,
                 language: str = "en"):
        self.mesh = mesh
        self.n_doc = mesh.shape["doc"]
        self.profile = profile or R.RankingProfile()
        # Every constant is pinned to the mesh's devices with an explicit
        # replicated sharding.  A bare jnp.asarray/jnp.int32 would place on
        # the DEFAULT backend — which may be a (possibly broken/busy) TPU
        # while the mesh is the virtual CPU pool, hermetically coupling a
        # CPU dryrun to TPU health.
        rep = NamedSharding(mesh, PS())
        put = lambda a: jax.device_put(np.asarray(a), rep)  # noqa: E731
        self._norm = put(self.profile.norm_coeffs())
        bits, shifts = self.profile.flag_coeffs()
        self._bits, self._shifts = put(bits), put(shifts)
        self._dl = put(np.int32(self.profile.domlength))
        self._tf = put(np.int32(self.profile.tf))
        self._lang_c = put(np.int32(self.profile.language))
        self._auth = put(np.int32(self.profile.authority))
        self._lang = put(np.int32(P.pack_language(language)))
        self._fns: dict[tuple[int, int], object] = {}

    def _fn(self, k: int, num_hosts: int):
        key = (k, num_hosts)
        if key not in self._fns:
            self._fns[key] = build_sharded_cardinal(self.mesh, k, num_hosts)
        return self._fns[key]

    def place(self, plist: "P.PostingsList", hosthashes=None):
        """Pad + device_put a PostingsList across the doc axis; returns the
        device-resident tuple reused across queries (steady-state path)."""
        n = len(plist)
        npad = pad_to_shards(max(n, 1), self.n_doc)
        feats = np.zeros((npad, P.NF), np.int32)
        docids = np.full(npad, -1, np.int32)
        valid = np.zeros(npad, bool)
        hostids = np.zeros(npad, np.int32)
        if n:
            feats[:n] = plist.feats
            docids[:n] = plist.docids
            valid[:n] = True
            if hosthashes is not None:
                hostids[:n] = R.hostid_array(plist.docids, hosthashes)
        sh_doc = NamedSharding(self.mesh, PS("doc"))
        sh_doc2 = NamedSharding(self.mesh, PS("doc", None))
        return (jax.device_put(feats, sh_doc2),
                jax.device_put(docids, sh_doc),
                jax.device_put(valid, sh_doc),
                jax.device_put(hostids, sh_doc),
                npad)

    def rank_placed(self, placed, k: int = 10):
        feats, docids, valid, hostids, npad = placed
        fn = self._fn(k, npad)
        s, d = fn(feats, docids, valid, hostids, self._norm, self._bits,
                  self._shifts, self._dl, self._tf, self._lang_c, self._auth,
                  self._lang)
        s, d = np.asarray(s), np.asarray(d)
        keep = (d >= 0) & (s > NEG_INF_I32)
        return s[keep][:k], d[keep][:k]

    def rank(self, plist: "P.PostingsList", hosthashes=None, k: int = 10):
        return self.rank_placed(self.place(plist, hosthashes), k=k)


class MeshBM25:
    """Sharded BM25 over a dense [docs, terms] tf block on the 2-D mesh."""

    def __init__(self, mesh: Mesh, k1: float = 1.2, b: float = 0.75):
        self.mesh = mesh
        self.n_doc = mesh.shape["doc"]
        self.n_term = mesh.shape["term"]
        self.k1, self.b = k1, b
        self._fns: dict[int, object] = {}

    def _fn(self, k: int):
        if k not in self._fns:
            self._fns[k] = build_sharded_bm25(self.mesh, k, self.k1, self.b)
        return self._fns[k]

    def place(self, tf: np.ndarray, doclen: np.ndarray, df: np.ndarray,
              ndocs: int, docids: np.ndarray):
        n, t = tf.shape
        npad = pad_to_shards(max(n, 1), self.n_doc)
        tpad = max(self.n_term, ((t + self.n_term - 1) // self.n_term)
                   * self.n_term)
        tf_p = np.zeros((npad, tpad), np.float32)
        tf_p[:n, :t] = tf
        dl_p = np.zeros(npad, np.int32)
        dl_p[:n] = doclen
        df_p = np.zeros(tpad, np.int32)
        df_p[:t] = df
        # padded term columns must not contribute idf: df=ndocs makes
        # idf=log(1 + 0.5/(ndocs+0.5)) ~ 0 but tf=0 zeroes them anyway
        valid = np.zeros(npad, bool)
        valid[:n] = True
        did_p = np.full(npad, -1, np.int32)
        did_p[:n] = docids
        sh = NamedSharding(self.mesh, PS("doc", "term"))
        sh_doc = NamedSharding(self.mesh, PS("doc"))
        sh_term = NamedSharding(self.mesh, PS("term"))
        sh_rep = NamedSharding(self.mesh, PS())
        return (jax.device_put(tf_p, sh),
                jax.device_put(dl_p, sh_doc),
                jax.device_put(df_p, sh_term),
                jax.device_put(np.int32(ndocs), sh_rep),
                jax.device_put(valid, sh_doc),
                jax.device_put(did_p, sh_doc))

    def topk_placed(self, placed, k: int = 10):
        fn = self._fn(k)
        s, d = fn(*placed)
        s, d = np.asarray(s), np.asarray(d)
        keep = (d >= 0) & np.isfinite(s)
        return s[keep][:k], d[keep][:k]

    def topk(self, tf, doclen, df, ndocs, docids, k: int = 10):
        return self.topk_placed(self.place(tf, doclen, df, ndocs, docids), k=k)
