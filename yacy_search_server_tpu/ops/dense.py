"""Dense semantic encoding + hybrid rerank kernel — M7 (BASELINE config #5).

New capability beyond the reference (aligned with PAPERS.md efficient
neural-ranking techniques): a first-stage sparse search (RWI/BM25 or
cardinal) followed by a dense cosine rerank on device.  TPU-first design:

- document/query embeddings are fixed-dim float vectors; doc embeddings
  live as one dense ``[n, dim]`` block per segment (MXU-friendly),
- the rerank is ONE fused kernel: bf16 matmul (query x doc block on the
  MXU) -> blend with the normalized sparse score -> top-k,
- the encoder is a deterministic hashed n-gram projection (a linear
  "SBERT-shaped" text encoder with no learned weights — zero-egress
  substitute; any [text -> dim-vector] model drops in, e.g. a flax
  sentence encoder, without touching the kernel).
"""

from __future__ import annotations

import functools
from zlib import crc32

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

DIM = 256
_SEED = 0x5EED
# bump when the feature hash/embedding scheme changes: persisted doc
# vectors must be re-encoded to stay comparable with query vectors
# (migration._d_reencode_dense)
ENCODER_VERSION = 2


def _stable_hash(s: str) -> int:
    """Deterministic 32-bit hash, C-speed (zlib.crc32 — python's hash()
    is salted per process; a pure-python FNV was the indexing write
    path's single largest cost at ~1M calls per 800 documents)."""
    return crc32(s.encode("utf-8"))


class HashingEncoder:
    """Signed feature-hashing of word + char-trigram features into `dim`
    buckets, L2-normalized — deterministic across processes/peers (doc
    vectors computed at index time on one node must match query vectors
    computed on another).

    Vectorized (ISSUE 11 satellite): the per-feature python accumulate
    loop is now ONE ``np.add.at`` scatter per text — and one per BATCH
    in ``encode_batch`` — with a bounded (feature -> bucket, sign)
    cache in front of the crc32, since a corpus's word/trigram
    vocabulary repeats massively across documents.  Bit-deterministic
    with the loop it replaces: ``np.add.at`` is unbuffered and applies
    updates in index order, which IS the old accumulation order, and
    ``_stable_hash`` still decides every bucket/sign."""

    # bounded word cache: a corpus's vocabulary repeats massively, but
    # a crawl's long tail must not grow an unbounded dict (cleared
    # wholesale at the cap — correctness never depends on a hit)
    _CACHE_MAX = 1 << 18

    def __init__(self, dim: int = DIM):
        self.dim = dim
        self._cache: dict[str, tuple[np.ndarray, np.ndarray]] = {}

    def _features(self, text: str):
        words = [w for w in text.lower().split() if w]
        for w in words[:512]:
            yield "w:" + w, 1.0
            padded = f"^{w}$"
            for i in range(len(padded) - 2):
                yield "t:" + padded[i:i + 3], 0.5

    def _word_arrays(self, w: str):
        """One word's (buckets, signed weights) — the word feature then
        its char trigrams, exactly the _features order — cached: the
        crc32 + modulo per trigram runs once per distinct word, not
        once per occurrence."""
        got = self._cache.get(w)
        if got is not None:
            return got
        feats = ["w:" + w]
        wts = [1.0]
        padded = f"^{w}$"
        for i in range(len(padded) - 2):
            feats.append("t:" + padded[i:i + 3])
            wts.append(0.5)
        dim = self.dim
        bs = np.empty(len(feats), dtype=np.int64)
        sg = np.empty(len(feats), dtype=np.float32)
        for j, f in enumerate(feats):
            h = _stable_hash(f)
            bs[j] = (h >> 1) % dim
            sg[j] = (1.0 if (h & 1) else -1.0) * wts[j]
        if len(self._cache) > self._CACHE_MAX:
            self._cache.clear()
        got = (bs, sg)
        self._cache[w] = got
        return got

    def _feature_arrays(self, text: str):
        """(buckets, signed weights) for one text, in feature order —
        the scatter input whose in-order application matches the legacy
        accumulate loop bit for bit."""
        words = [w for w in text.lower().split() if w][:512]
        if not words:
            return (np.empty(0, dtype=np.int64),
                    np.empty(0, dtype=np.float32))
        parts = [self._word_arrays(w) for w in words]
        return (np.concatenate([p[0] for p in parts]),
                np.concatenate([p[1] for p in parts]))

    def encode(self, text: str) -> np.ndarray:
        v = np.zeros(self.dim, dtype=np.float32)
        b, w = self._feature_arrays(text)
        if len(b):
            np.add.at(v, b, w)
        n = float(np.linalg.norm(v))
        return v / n if n > 0 else v

    def encode_batch(self, texts: list[str]) -> np.ndarray:
        """Batched encode: ONE 2-d np.add.at scatter for the whole
        batch (the flattened per-text feature runs keep each row's
        update order, so every row is bit-identical to encode())."""
        if not texts:
            return np.zeros((0, self.dim), dtype=np.float32)
        v = np.zeros((len(texts), self.dim), dtype=np.float32)
        rows: list[np.ndarray] = []
        cols: list[np.ndarray] = []
        wts: list[np.ndarray] = []
        for i, t in enumerate(texts):
            b, w = self._feature_arrays(t)
            if len(b):
                rows.append(np.full(len(b), i, dtype=np.int64))
                cols.append(b)
                wts.append(w)
        if rows:
            np.add.at(v, (np.concatenate(rows), np.concatenate(cols)),
                      np.concatenate(wts))
        for i in range(len(texts)):
            n = float(np.linalg.norm(v[i]))
            if n > 0:
                v[i] /= n
        return v


# -- fused rerank kernel -----------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k",))
def hybrid_rerank_topk(qvec: jnp.ndarray, doc_vecs: jnp.ndarray,
                       sparse_scores: jnp.ndarray, valid: jnp.ndarray,
                       alpha: jnp.ndarray, k: int):
    """One fused device step: cosine(q, docs) on the MXU in bf16, blended
    with min/max-normalized sparse scores, masked top-k.

        final = (1-alpha) * norm(sparse) + alpha * cosine

    Returns (scores[k], indices[k]).  Replaces nothing in the reference —
    this is the hybrid second stage the reference lacks.
    """
    sims = jnp.dot(doc_vecs.astype(jnp.bfloat16),
                   qvec.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    s = sparse_scores.astype(jnp.float32)
    big = jnp.float32(1e30)
    smin = jnp.min(jnp.where(valid, s, big))
    smax = jnp.max(jnp.where(valid, s, -big))
    span = jnp.maximum(smax - smin, 1e-6)
    s_norm = jnp.where(valid, (s - smin) / span, 0.0)
    final = (1.0 - alpha) * s_norm + alpha * sims
    final = jnp.where(valid, final, -jnp.inf)
    # lint: tie-ok(lax.top_k breaks ties by lowest input index and the candidate rows are docid-ordered, so equal scores surface docid-ASC — the pinned discipline, asserted by the tie tests in test_dense/test_ranking)
    return jax.lax.top_k(final, k)


@functools.partial(jax.jit, static_argnames=("k",))
def hybrid_rerank_topk_batch(qvecs: jnp.ndarray, doc_vecs: jnp.ndarray,
                             sparse_scores: jnp.ndarray,
                             valid: jnp.ndarray, alpha: jnp.ndarray,
                             k: int):
    """Batched hybrid rerank: B concurrent queries against ONE shared
    doc matrix in a single (B,dim)x(dim,N) bf16 matmul — the MXU shape a
    single matvec can't reach (VERDICT r4 #5: a lone query's cosine is
    HBM-bound at ~1% MXU utilization; a 16-wide batch amortizes the doc
    matrix read across every slot). Per-slot normalize/blend/top-k vmap.

    qvecs (B,dim); sparse_scores, valid (B,N). Returns
    (scores[B,k], indices[B,k]) — slot i identical to the solo kernel on
    (qvecs[i], sparse_scores[i], valid[i])."""
    sims = jnp.dot(qvecs.astype(jnp.bfloat16),
                   doc_vecs.astype(jnp.bfloat16).T,
                   preferred_element_type=jnp.float32)   # (B, N)

    def one(sim, s, v):
        big = jnp.float32(1e30)
        smin = jnp.min(jnp.where(v, s, big))
        smax = jnp.max(jnp.where(v, s, -big))
        span = jnp.maximum(smax - smin, 1e-6)
        s_norm = jnp.where(v, (s - smin) / span, 0.0)
        final = (1.0 - alpha) * s_norm + alpha * sim
        # lint: tie-ok(lax.top_k breaks ties by lowest input index and the candidate rows are docid-ordered, so equal scores surface docid-ASC — the pinned discipline, asserted by the tie tests in test_dense; the vmapped
        # per-slot kernel shares the outer kernel's row order)
        return jax.lax.top_k(jnp.where(v, final, -jnp.inf), k)

    return jax.vmap(one)(sims, sparse_scores.astype(jnp.float32), valid)


# one score domain: dense similarity maps into the CARDINAL integer
# domain as an additive boost with a FIXED scale (the magnitude of one
# maxed-out cardinal signal, 255 << 15) — never rescaled by the local
# batch's score range, so fusion ordering across peers/batches is stable
# (VERDICT r1 weak #6: the old path stretched blended [0,2) scores by
# max(scores)/2, making remote fusion depend on the local batch max)
DENSE_BOOST_SCALE = float(255 << 15)


@functools.partial(jax.jit, static_argnames=("k",))
def dense_boost_topk(qvec: jnp.ndarray, doc_vecs: jnp.ndarray,
                     sparse_scores: jnp.ndarray, valid: jnp.ndarray,
                     alpha: jnp.ndarray, k: int):
    """Fused cosine + fixed-scale cardinal boost + masked top-k.

        final = sparse_cardinal + round(cosine * alpha * DENSE_BOOST_SCALE)

    Input and output scores live in the same cardinal integer domain as
    the sparse first stage; (scores[k], indices[k]) best-first."""
    sims = jnp.dot(doc_vecs.astype(jnp.bfloat16),
                   qvec.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    # int32 domain (x64 is off): cardinal scores stay < 2^28 and the
    # boost < 2^23, so the sum never wraps
    boost = jnp.round(sims * alpha * DENSE_BOOST_SCALE).astype(jnp.int32)
    final = sparse_scores.astype(jnp.int32) + boost
    final = jnp.where(valid, final, jnp.int32(-(2**31 - 1)))
    # lint: tie-ok(lax.top_k breaks ties by lowest input index and the
    # candidate rows are docid-ordered, so equal scores surface
    # docid-ASC — the pinned discipline, asserted by the tie tests in
    # test_dense)
    return jax.lax.top_k(final, k)


# -- batched serving rerank over the device-resident forward index ----------
#
# The serving path's rerank (cardinal-domain boost, one score domain with
# the sparse first stage) as a BATCHED kernel family: B concurrent
# queries' candidate sets gather their doc vectors from one device-
# resident forward index (index/dense.DenseVectorStore.device_block) and
# contract against their query vectors in a single bf16 MXU dispatch —
# the (B,dim)x(dim,N) shape hybrid_rerank_topk_batch proved (7.08x CPU)
# finally wired into serving, riding the devstore _QueryBatcher's
# issue→completer pipeline like every other kernel family.
#
# Tie discipline (arxiv 1807.05798): the final order is (score DESC,
# then internal docid ASC) — pinned so solo/batched/packed/cached rerank
# paths can never disagree on ties, which would flap the versioned
# top-k result cache between bit-different answers of equal score.

# candidate-count buckets (pow2, min 16) bound the compile-shape count;
# pad lanes carry docid -1 and are masked by the per-slot valid count
RERANK_MAX_N = 1 << 14


def rerank_bucket(n: int) -> int:
    """Static candidate-lane bucket for one rerank slot."""
    return 1 << max(4, (max(n, 1) - 1).bit_length())


def pack_rerank_row(qvec: np.ndarray, sparse_scores: np.ndarray,
                    docids: np.ndarray, alpha: float, nb: int) -> np.ndarray:
    """ONE fused int32 descriptor for one rerank slot — qvec (bit-cast
    float32), sparse cardinal scores, candidate docids and the blend
    alpha ride a single host buffer, so a dispatch wave is one
    host->device transfer (each separate argument is its own transfer —
    the M78 packing lesson).

    Layout: [n_valid, alpha_bits, docids[nb], sparse[nb], qvec_bits[dim]].
    """
    n = len(docids)
    dim = len(qvec)
    row = np.zeros(2 + 2 * nb + dim, np.int32)
    row[0] = n
    row[1] = np.float32(alpha).view(np.int32)
    row[2:2 + n] = np.asarray(docids, np.int32)
    row[2 + nb:2 + nb + n] = np.asarray(sparse_scores, np.int32)
    row[2 + 2 * nb:] = np.asarray(qvec, np.float32).view(np.int32)
    return row


@functools.partial(jax.jit, static_argnames=("nb", "bs"))
def _rerank_fwd_batch_packed_kernel(fwd, qi, nb: int, bs: int):
    """Batched cardinal-domain dense rerank against the device-resident
    forward index, packed I/O: `qi` [bs, 2 + 2*nb + dim] fused
    descriptors (pack_rerank_row), output [bs, 2*nb] = scores ++ docids
    per slot — ONE transfer each way per dispatch wave.

    Each slot gathers its candidates' doc vectors from `fwd`
    ([cap, dim] float16), contracts them against its query vector in
    bf16 (f32 accumulate — the MXU shape), adds the fixed-scale boost
    into the sparse cardinal scores (dense_boost_topk semantics, slot
    for slot), and sorts by (score DESC, docid ASC) — the pinned tie
    discipline. Candidates OUTSIDE the forward index's coverage (no
    vector stored yet) keep their sparse score with zero boost — vector
    absence must never drop a sparse result. Pad lanes (beyond a slot's
    n_valid) sort last with NEG_INF scores."""
    dim = fwd.shape[1]
    cap = fwd.shape[0]
    nvalid = qi[:, 0]
    alpha = lax.bitcast_convert_type(qi[:, 1], jnp.float32)
    docids = qi[:, 2:2 + nb]
    sparse = qi[:, 2 + nb:2 + 2 * nb]
    qvecs = lax.bitcast_convert_type(qi[:, 2 + 2 * nb:], jnp.float32)
    dv = fwd[jnp.clip(docids, 0, cap - 1)]          # (bs, nb, dim) gather
    sims = jnp.einsum("bd,bnd->bn", qvecs.astype(jnp.bfloat16),
                      dv.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
    in_cov = (docids >= 0) & (docids < cap)
    sims = jnp.where(in_cov, sims, 0.0)
    boost = jnp.round(sims * alpha[:, None]
                      * DENSE_BOOST_SCALE).astype(jnp.int32)
    lanes = jnp.arange(nb)[None, :]
    valid = lanes < nvalid[:, None]
    neg = jnp.int32(-(2 ** 31 - 1))
    final = jnp.where(valid, sparse + boost, neg)
    # (score DESC, docid ASC): ascending two-key sort on (-score, docid);
    # pad lanes tie-key to INT32_MAX so they stay behind real candidates
    skey = -final
    tkey = jnp.where(valid, docids, jnp.int32(2 ** 31 - 1))

    def one(sk, tk, f, d):
        _sk, _tk, fs, ds = lax.sort((sk, tk, f, d), num_keys=2)
        return fs, ds

    fs, ds = jax.vmap(one)(skey, tkey, final, docids)
    return jnp.concatenate([fs, ds], axis=1)


def rerank_fwd_np(qvec, fwd, sparse_scores, docids, alpha):
    """CPU oracle for _rerank_fwd_batch_packed_kernel (one slot):
    bf16-rounded matmul inputs like the kernel, float32 accumulation,
    and the SAME (score DESC, docid ASC) tie discipline. Accumulation
    order may still differ from the device dot (a few units of rounded
    boost) — compare closeness per docid, not bit-exact scores; device
    paths among THEMSELVES are bit-exact at a shared compile shape."""
    import ml_dtypes
    docids = np.asarray(docids, np.int64)
    in_cov = (docids >= 0) & (docids < fwd.shape[0])
    dv = fwd[np.clip(docids, 0, fwd.shape[0] - 1)]
    sims = (dv.astype(ml_dtypes.bfloat16).astype(np.float32)
            @ np.asarray(qvec).astype(ml_dtypes.bfloat16)
            .astype(np.float32))
    sims = np.where(in_cov, sims, 0.0)
    boost = np.round(sims * np.float32(alpha)
                     * np.float32(DENSE_BOOST_SCALE)).astype(np.int32)
    final = np.asarray(sparse_scores, np.int32) + boost
    order = np.lexsort((docids, -final.astype(np.int64)))
    return final[order], np.asarray(docids, np.int32)[order]


def dense_boost_topk_np(qvec, doc_vecs, sparse_scores, valid, alpha, k):
    """CPU oracle for dense_boost_topk: bf16-rounded inputs like the
    kernel's MXU matmul, float32 accumulation. Accumulation order may
    still differ from the device — compare orderings/closeness, not
    bit-exact scores."""
    import ml_dtypes
    sims = (doc_vecs.astype(ml_dtypes.bfloat16).astype(np.float32)
            @ qvec.astype(ml_dtypes.bfloat16).astype(np.float32))
    boost = np.round(sims * np.float32(alpha)
                     * np.float32(DENSE_BOOST_SCALE)).astype(np.int32)
    final = sparse_scores.astype(np.int32) + boost
    final = np.where(valid, final, np.int32(-(2**31 - 1)))
    idx = np.argsort(-final, kind="stable")[:k]
    return final[idx], idx


def hybrid_rerank_topk_np(qvec, doc_vecs, sparse_scores, valid, alpha, k):
    """CPU oracle with identical math (float32 cosine)."""
    sims = doc_vecs.astype(np.float32) @ qvec.astype(np.float32)
    s = sparse_scores.astype(np.float32)
    sv = s[valid]
    smin = sv.min() if sv.size else 0.0
    smax = sv.max() if sv.size else 0.0
    span = max(smax - smin, 1e-6)
    s_norm = np.where(valid, (s - smin) / span, 0.0)
    final = (1.0 - alpha) * s_norm + alpha * sims
    final = np.where(valid, final, -np.inf)
    idx = np.argsort(-final, kind="stable")[:k]
    return final[idx], idx
