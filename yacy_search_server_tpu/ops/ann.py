"""IVF ANN kernel family — dense-first candidate generation (ISSUE 11).

M81 made dense vectors a *rescoring* signal: the forward-index rerank
can only reorder candidates the sparse stage already found, so a query
that sparse retrieval misses can never be recovered by the dense path.
This family inverts that (arxiv 2110.06051): a clustered (IVF-style)
device-resident index makes dense a first-class candidate *generator*,
with the compact-index discipline of arxiv 1406.3170 applied to the
vectors themselves — int8 quantization with a per-vector scale keeps
10M+ vectors inside the same HBM budget as the postings.

Two kernels, both riding the devstore issue→completer pipeline as the
``ann`` part kind (index/devstore._dispatch_anns):

- **centroid assignment** — ONE (B,dim)×(dim,C) bf16 MXU matmul per
  dispatch wave: every queued dense-first query's vector contracts
  against the shared centroid matrix in a single dispatch, returning
  each slot's ``nprobe`` nearest cluster ids.
- **probe + fuse** — batched gathers over the contiguous per-cluster
  int8 vector slabs (index/annstore.AnnVectorIndex lays clusters out
  as contiguous row runs, so probe lanes are arange windows, not
  scattered indices), f16 dequant fused into the scoring matmul
  (``sims = (q·int8_rows) * scale``), the fixed-scale cardinal boost
  (ops/dense.DENSE_BOOST_SCALE — one score domain with the sparse
  first stage), and a (score DESC, docid ASC) two-key sort: the pinned
  tie discipline, so solo/batched/cached dense-first answers can never
  disagree on ties.  Sparse candidates ride the SAME kernel as extra
  lanes carrying their cardinal scores — the fused list is one kernel
  output, not a host merge of two score domains.

NumPy oracles (``ann_assign_np`` / ``ann_fuse_np``) pin bit-parity at
the exact-scoring stage (the matmul over the quantized vectors is
exact — only the IVF candidate restriction is approximate) and double
as the host-fallback path during device loss.  ``ANN_ORACLES`` is the
hygiene registry: tests/test_code_hygiene.py demands an entry — and a
roofline cost model — for every ``_ann_*`` jit kernel here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .dense import DENSE_BOOST_SCALE

# default probe width: clusters scored per query. The serving knob is
# index.ann.nprobe (devstore.ann_nprobe); this is the anchor the
# recall gate (tests/test_ann.py) is stated at.
ANN_DEFAULT_NPROBE = 8
# per-query probe lane budget (pow2): bounds the gather width of one
# fuse dispatch — the index.ann.probeLanes knob. Probes past the budget
# are dropped whole-cluster (counted, never silently truncated mid-
# cluster, which would make the candidate set depend on slab order).
ANN_DEFAULT_PROBE_LANES = 1 << 15
# pad lanes/keys
_NEG = -(2 ** 31 - 1)
_INT_MAX = 2 ** 31 - 1


def ann_lane_bucket(n: int, cap: int) -> int:
    """Static pow2 lane bucket (>=256) for one fuse slot, capped at the
    probe-lane budget's bucket — bounded compile shapes, like
    ops/dense.rerank_bucket."""
    b = 1 << max(8, (max(n, 1) - 1).bit_length())
    return min(b, 1 << max(8, (max(cap, 1) - 1).bit_length()))


def ann_topk_bucket(k: int, nb: int) -> int:
    """Static pow2 output bucket for the fused top-k: oversampled 2x so
    the host-side dedup (a docid reachable both as a probe lane and a
    sparse lane) still fills k, clamped to the lane bucket."""
    return min(nb, 1 << max(4, (2 * max(k, 1) - 1).bit_length()))


# -- centroid assignment -----------------------------------------------------

@functools.partial(jax.jit, static_argnames=("np_", "c_real"))
def _ann_assign_batch_kernel(cent, qv, np_: int, c_real: int):
    """ONE (B,dim)×(dim,C) bf16 MXU matmul per dispatch wave: the whole
    wave's query vectors against the device-resident centroid matrix,
    top-``np_`` centroid ids per slot (f32 accumulate; ties resolve by
    centroid id ASC — lax.top_k orders ties by input position, which IS
    the centroid id).  Pad slots (zero vectors) cost nothing extra and
    their ids are ignored by the dispatcher.  ``c_real`` masks the
    pow2-pad centroid rows to -inf: a zero pad row's sim (0.0) would
    otherwise outrank every real cluster with NEGATIVE similarity and
    silently shrink the probe set for anti-aligned queries."""
    sims = jnp.dot(qv.astype(jnp.bfloat16),
                   cent.astype(jnp.bfloat16).T,
                   preferred_element_type=jnp.float32)    # (B, C)
    sims = jnp.where(jnp.arange(cent.shape[0])[None, :] < c_real,
                     sims, -jnp.inf)
    # lint: tie-ok(ties resolve by centroid id ASC: top_k orders
    # ties by input position, which IS the centroid id — see the
    # docstring)
    _s, ids = lax.top_k(sims, np_)
    return ids.astype(jnp.int32)


def ann_assign_np(cent, qv, nprobe: int) -> np.ndarray:
    """CPU oracle for _ann_assign_batch_kernel (and the host-fallback
    assignment during device loss): bf16-rounded inputs like the MXU
    matmul, f32 accumulation, ties by centroid id ASC."""
    import ml_dtypes
    sims = (np.asarray(qv).astype(ml_dtypes.bfloat16).astype(np.float32)
            @ np.asarray(cent).astype(ml_dtypes.bfloat16)
            .astype(np.float32).T)
    # argsort on (-sim, id): stable sort gives id-ASC ties like top_k
    return np.argsort(-sims, axis=-1, kind="stable")[..., :nprobe] \
        .astype(np.int32)


# -- probe + fuse ------------------------------------------------------------

def pack_ann_fuse_row(qvec: np.ndarray, rows: np.ndarray,
                      docids: np.ndarray, sparse: np.ndarray,
                      alpha: float, nb: int) -> np.ndarray:
    """ONE fused int32 descriptor for one dense-first slot (the
    pack_rerank_row discipline: a dispatch wave is one host->device
    transfer, not one per argument).

    Layout: ``[n_valid, alpha_bits, rows[nb], docids[nb], sparse[nb],
    qvec_bits[dim]]``.  Three lane kinds share the arrays:

    - probe lane: ``rows[i] >= 0`` into the hot slab, ``docids[i] = -1``
      (the kernel resolves the docid from the resident slab docid
      column), ``sparse[i] = 0``;
    - sparse-candidate lane: ``docids[i] >= 0`` with its cardinal score
      in ``sparse[i]``; ``rows[i]`` is its hot-slab row or -1 when the
      vector is outside the hot tier (scores sparse+0 — vector absence
      must never drop a sparse result);
    - pad lane (``i >= n_valid``): masked to NEG_INF/INT32_MAX keys.
    """
    n = len(rows)
    dim = len(qvec)
    row = np.zeros(2 + 3 * nb + dim, np.int32)
    row[0] = n
    row[1] = np.float32(alpha).view(np.int32)
    row[2:2 + n] = np.asarray(rows, np.int32)
    row[2 + nb:2 + nb + n] = np.asarray(docids, np.int32)
    row[2 + 2 * nb:2 + 2 * nb + n] = np.asarray(sparse, np.int32)
    row[2 + 3 * nb:] = np.asarray(qvec, np.float32).view(np.int32)
    return row


@functools.partial(jax.jit, static_argnames=("nb", "bs", "k"))
def _ann_fuse_batch_packed_kernel(slab, scales, sdocids, qi,
                                  nb: int, bs: int, k: int):
    """Batched IVF probe + dense/sparse fusion against the hot int8
    slab, packed I/O: ``qi`` [bs, 2+3*nb+dim] descriptors
    (pack_ann_fuse_row), output [bs, 2*k] = fused scores ++ docids.

    Each slot gathers its lanes' int8 vectors, dequantizes INSIDE the
    scoring matmul (bf16 contract × per-vector f16 scale — the int8
    rows never materialize as f16 in HBM), adds the fixed-scale
    cardinal boost to the lanes' sparse scores (dense_boost_topk
    semantics: one score domain with the sparse first stage), and sorts
    by (score DESC, docid ASC) — the pinned tie discipline. Lanes
    outside the slab (row -1: a sparse candidate without a hot vector)
    score sparse+0; pad lanes sort last."""
    dim = slab.shape[1]
    cap = slab.shape[0]
    nvalid = qi[:, 0]
    alpha = lax.bitcast_convert_type(qi[:, 1], jnp.float32)
    rows = qi[:, 2:2 + nb]
    docids = qi[:, 2 + nb:2 + 2 * nb]
    sparse = qi[:, 2 + 2 * nb:2 + 3 * nb]
    qvecs = lax.bitcast_convert_type(qi[:, 2 + 3 * nb:], jnp.float32)
    cr = jnp.clip(rows, 0, cap - 1)
    g = slab[cr]                                   # (bs, nb, dim) int8
    sims = jnp.einsum("bd,bnd->bn", qvecs.astype(jnp.bfloat16),
                      g.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
    in_slab = (rows >= 0) & (rows < cap)
    sims = jnp.where(in_slab, sims * scales[cr].astype(jnp.float32), 0.0)
    # probe lanes resolve their docid from the resident slab column;
    # sparse lanes carry theirs explicitly
    dd = jnp.where(docids >= 0, docids,
                   jnp.where(in_slab, sdocids[cr], jnp.int32(_INT_MAX)))
    boost = jnp.round(sims * alpha[:, None]
                      * DENSE_BOOST_SCALE).astype(jnp.int32)
    lanes = jnp.arange(nb)[None, :]
    valid = (lanes < nvalid[:, None]) & (dd != _INT_MAX)
    final = jnp.where(valid, sparse + boost, jnp.int32(_NEG))
    skey = -final
    # masked lanes carry INT32_MAX as BOTH tie key and output docid —
    # consumers drop them by docid, so a pad lane can never leak a
    # real docid with a NEG score
    tkey = jnp.where(valid, dd, jnp.int32(_INT_MAX))

    def one(sk, tk, f):
        # two-key (score DESC, docid ASC) sort; tkey doubles as payload
        _sk, _tk, fs, ds = lax.sort((sk, tk, f, tk), num_keys=2)
        return fs[:k], ds[:k]

    fs, ds = jax.vmap(one)(skey, tkey, final)
    return jnp.concatenate([fs, ds], axis=1)


def ann_fuse_np(slab, scales, sdocids, rows, docids, sparse, qvec,
                alpha: float, k: int):
    """CPU oracle for one _ann_fuse_batch_packed_kernel slot — and the
    host scoring path for warm/cold (non-device-resident) probe lanes
    and the device-loss fallback: bf16-rounded matmul inputs like the
    kernel, f32 accumulation, identical fixed-scale boost and the SAME
    (score DESC, docid ASC) tie discipline.  Accumulation order may
    differ from the device dot by a few float ulps (compare rounded-
    boost closeness per docid, not bit-exact scores); device paths
    among THEMSELVES are bit-exact at a shared compile shape.

    Returns (scores[<=k], docids[<=k]) over the VALID lanes only."""
    import ml_dtypes
    rows = np.asarray(rows, np.int64)
    docids = np.asarray(docids, np.int64)
    sparse = np.asarray(sparse, np.int64)
    cap = slab.shape[0]
    in_slab = (rows >= 0) & (rows < cap)
    cr = np.clip(rows, 0, cap - 1)
    g = np.asarray(slab[cr]).astype(ml_dtypes.bfloat16).astype(np.float32)
    q = np.asarray(qvec).astype(ml_dtypes.bfloat16).astype(np.float32)
    sims = g @ q
    sims = np.where(in_slab,
                    sims * np.asarray(scales[cr], np.float32), 0.0)
    dd = np.where(docids >= 0, docids,
                  np.where(in_slab, np.asarray(sdocids)[cr], _INT_MAX))
    boost = np.round(sims * np.float32(alpha)
                     * np.float32(DENSE_BOOST_SCALE)).astype(np.int64)
    final = sparse + boost
    ok = dd != _INT_MAX
    final, dd = final[ok], dd[ok]
    order = np.lexsort((dd, -final))[:k]
    return final[order].astype(np.int64), dd[order].astype(np.int32)


def fuse_dedup(scores: np.ndarray, docids: np.ndarray,
               k: int) -> tuple[np.ndarray, np.ndarray]:
    """Collapse duplicate docids in a (score DESC, docid ASC)-ordered
    fused list, keeping the FIRST (= best-scored: a docid reachable
    both as a probe lane and as a sparse lane keeps its
    sparse+boost entry, which dominates its boost-only twin), then trim
    to k. Stable, so the tie discipline survives."""
    seen: set = set()
    keep = np.zeros(len(docids), bool)
    for i, d in enumerate(docids.tolist()):
        if d not in seen:
            seen.add(d)
            keep[i] = True
    return scores[keep][:k], docids[keep][:k]


def merge_fused(parts: list, k: int) -> tuple[np.ndarray, np.ndarray]:
    """Merge independently-ordered fused (scores, docids) part lists
    (device lanes + host-scored warm/cold lanes) under the pinned
    (score DESC, docid ASC) discipline, dedup best-first, trim to k."""
    if not parts:
        return np.empty(0, np.int64), np.empty(0, np.int32)
    s = np.concatenate([np.asarray(p[0], np.int64) for p in parts])
    d = np.concatenate([np.asarray(p[1], np.int32) for p in parts])
    order = np.lexsort((d, -s))
    return fuse_dedup(s[order], d[order], k)


# hygiene registry (tests/test_code_hygiene.py): every _ann_* jit
# kernel must carry a NumPy oracle here AND a roofline cost model in
# ops/roofline.KERNELS — a new ANN kernel cannot land unregistered.
ANN_ORACLES: dict[str, object] = {
    "_ann_assign_batch_kernel": ann_assign_np,
    "_ann_fuse_batch_packed_kernel": ann_fuse_np,
}
