"""Roofline cost accounting — every serving kernel gets a silicon number.

How far from the HARDWARE's ceiling does a kernel run? This module is
the analytical half of that accounting:

- a **cost model registry**: for each named serving kernel, closed-form
  FLOPs / bytes-moved as functions of its shape parameters.

  * ``bytes`` — COMPULSORY traffic: operands that must stream from HBM
    plus results written back, assuming perfect fusion (the roofline
    denominator — achieved GB/s against the HBM peak is only meaningful
    over bytes that physically must move). Counted from array shapes;
    it does not move with the compiler.
  * ``flops`` follows XLA's arithmetic-op counting (elementwise int ops
    count as flops). The coefficients are calibrated against
    ``jax.jit(...).lower().compile().cost_analysis()`` on the CPU
    backend and PINNED BY TEST (tests/test_roofline.py: within 10% on 3
    representative shapes per kernel) — a kernel edit that changes the
    arithmetic breaks the pin and forces the model to be re-derived.

- a **per-device peak table** (TPU generations + the CPU test backend),
  overridable via config/env — utilization is stated against a DECLARED
  peak, never a guessed one.

- the **roofline verdict**: arithmetic intensity (flops/byte) against the
  device ridge point classifies each kernel compute- vs memory-bound;
  ``util_pct`` is achieved-vs-peak along the BINDING axis.

Loop-carried kernels (lax.scan / fori_loop bodies) are modeled per
executed step and multiplied by the trip count — XLA's cost analysis
counts a loop body ONCE regardless of trip count, so the cross-check for
those kernels compares the per-step body cost (see tests).

References: Williams et al., "Roofline: an insightful visual performance
model" (CACM 2009); arXiv:2110.06051 and arXiv:1406.3170 frame the dense
rerank and postings/top-k efficiency in exactly these absolute
compute/byte terms.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

import numpy as np

from ..index import postings as P

# compact-block row: int16 feats + int32 flags + int32 docids
ROW_BYTES = P.NF * 2 + 4 + 4
# + the tombstone-bitmap gather (bool per row)
ROW_BYTES_DEAD = ROW_BYTES + 1


@dataclass(frozen=True)
class Cost:
    """One kernel execution's analytical cost."""

    flops: float       # arithmetic ops (XLA counting conventions)
    bytes: float       # compulsory HBM traffic (roofline denominator)

    @property
    def intensity(self) -> float:
        """Arithmetic intensity in FLOPs per compulsory byte."""
        return self.flops / max(self.bytes, 1.0)


@dataclass(frozen=True)
class DevicePeak:
    """Declared hardware ceilings for one device kind."""

    name: str
    flops_per_s: float     # dense-compute peak (bf16 MXU on TPU)
    bytes_per_s: float     # HBM bandwidth peak

    @property
    def ridge(self) -> float:
        """Intensity (flops/byte) where the roofline bends."""
        return self.flops_per_s / self.bytes_per_s


# Published peaks per device generation (the `device_kind` strings jax
# reports). v5e: 197 TFLOP/s bf16, 819 GB/s HBM. The CPU entry is a
# deliberately conservative single-core envelope for the test backend —
# utilization numbers on CPU are for plumbing tests, not claims.
PEAKS: dict[str, DevicePeak] = {
    "tpu v5 lite": DevicePeak("TPU v5e", 197e12, 819e9),
    "tpu v5e": DevicePeak("TPU v5e", 197e12, 819e9),
    "tpu v4": DevicePeak("TPU v4", 275e12, 1228e9),
    "tpu v3": DevicePeak("TPU v3", 123e12, 900e9),
    "tpu v2": DevicePeak("TPU v2", 46e12, 700e9),
    "cpu": DevicePeak("CPU (1-core envelope)", 5e10, 2.5e10),
}


def device_peak(device=None) -> DevicePeak:
    """The peak table entry for a jax device (default: the first device
    of the default backend). A `device_kind` that is not in PEAKS is an
    error, not a default: a deployment on unlisted silicon DECLARES its
    ceilings with YACY_ROOFLINE_PEAK_FLOPS / YACY_ROOFLINE_PEAK_GBPS
    (both, for an unknown kind; either overrides a listed one)."""
    if device is None:
        import jax
        device = jax.devices()[0]
    kind = device.device_kind.lower()
    peak = PEAKS.get(kind)
    env_f = os.environ.get("YACY_ROOFLINE_PEAK_FLOPS")
    env_b = os.environ.get("YACY_ROOFLINE_PEAK_GBPS")
    if peak is None:
        if not (env_f and env_b):
            raise KeyError(
                f"no roofline peak for device_kind {kind!r}: add it to "
                f"ops/roofline.PEAKS (have: {sorted(PEAKS)}) or declare "
                f"YACY_ROOFLINE_PEAK_FLOPS and YACY_ROOFLINE_PEAK_GBPS")
        return DevicePeak(f"{device.device_kind} (declared)",
                          float(env_f), float(env_b) * 1e9)
    if env_f or env_b:
        peak = DevicePeak(
            peak.name + " (overridden)",
            float(env_f) if env_f else peak.flops_per_s,
            float(env_b) * 1e9 if env_b else peak.bytes_per_s)
    return peak


@dataclass(frozen=True)
class RooflinePoint:
    """A kernel execution placed on the roofline."""

    kernel: str
    flops: float
    bytes: float
    wall_s: float
    achieved_flops_per_s: float
    achieved_bytes_per_s: float
    intensity: float
    bound: str          # "memory" | "compute"
    util_pct: float     # achieved vs peak along the binding axis


def roofline_point(kernel: str, cost: Cost, wall_s: float,
                   peak: DevicePeak) -> RooflinePoint:
    """Place one measured execution against the device roofline."""
    wall_s = max(wall_s, 1e-9)
    af = cost.flops / wall_s
    ab = cost.bytes / wall_s
    bound = "memory" if cost.intensity < peak.ridge else "compute"
    if bound == "memory":
        util = 100.0 * ab / peak.bytes_per_s
    else:
        util = 100.0 * af / peak.flops_per_s
    # 6 decimals: the fusion collectives move a few KiB behind a
    # multi-device dispatch wall — 3 digits rounds their util to 0.0.
    return RooflinePoint(kernel, cost.flops, cost.bytes, wall_s,
                         af, ab, cost.intensity, bound, round(util, 6))


# ---------------------------------------------------------------------------
# Cost models
# ---------------------------------------------------------------------------
# Per-row coefficient provenance: compulsory bytes are counted from the
# arrays the kernel streams (ROW_BYTES per candidate row, plus gathers /
# side-tables / outputs); flops coefficients are calibrated against the
# CPU-backend HloCostAnalysis and pinned by tests/test_roofline.py —
# each entry's comment records the fit.
#
# Loop-carried kernels (lax.scan / fori_loop / lax.map bodies) are modeled
# PER EXECUTED STEP × trip count; HloCostAnalysis counts a loop body once
# regardless of trip count, so their cross-check compares the unit-trip
# cost (tests pass the one-step shape).

# Every scorer computes the term frequency and its normalisation as IEEE
# float32 division gives them (ops/ranking._ieee_div_nearest,
# _ieee_trunc_div: exact remainders from split products), ~150 flops a
# row in a stats pass and ~105 in a score pass; the fits below hold them.
# cardinal scorer over a compact block (ops/ranking.cardinal_scores16):
# stats + normalize + shifted sum + tf + flags. XLA (no-authority trace):
# 635 flops/row, constant over n in [4k, 131k]
_CARDINAL_FLOPS_ROW = 635.0
# + fused lax.top_k (score_topk16): 647-652 per row at serving k's
_TOPK16_FLOPS_ROW = 650.0
# int32 twin (score_topk): 560 flops/row (no int16 widening ops)
_TOPK32_FLOPS_ROW = 560.0
# scan_score_topk loop body (stats precomputed; score + merge per tile)
_SCAN_FLOPS_ROW = 544.0
# streaming stats pass (ops/ranking.local_stats, no host counts)
_STATS_FLOPS_ROW = 261.0
# devstore streamed spans kernel: stats + score passes per tile plus the
# constraint mask; each span's fori body counts once: 927 flops per
# (span, TILE-row)
_SPANS_FLOPS_ROW = 927.0
# b=1 vmapped pruned kernel: one scored tile per slot; vmap (unlike
# lax.map) scales the count with bs: 559 flops/row
_PRUNED1_FLOPS_ROW = 559.0
# pruned escalation kernel body (lax.map slot × fori tile, counted once)
_PRUNEDB_FLOPS_ROW = 555.0
# sort-merge join: fit over (r, m) at n_inc=1/n_exc=0, bs=1:
# flops = 656·r + 42·m
_JOIN_FLOPS_R, _JOIN_FLOPS_M = 656.0, 42.0
# bitmap-membership join: 710 flops/row·slot
_JOINBM_FLOPS_ROW = 710.0
# a join that compacts (index/devstore._join_bucket gives the caller its
# bucket) runs its later memberships over a bucket of the first one's
# survivors: the sort that packs them and the fold of what they found
# into the window cost 47 flops a window lane, a later bitmap membership
# 70 a bucket lane, a later sort-merge one 30 a bucket lane + 45 a
# segment key (fit at r 65,536-131,072 and two to four later
# memberships); compulsory bytes: the sort's key and docid in and out a
# window lane, a bucket lane's key and docid read and its five merged
# columns written
_JOIN_COMPACT_FLOPS_ROW = 47.0
_JOINBM_LATER_FLOPS_LANE = 70.0
_JOIN_LATER_FLOPS_LANE, _JOIN_LATER_FLOPS_M = 30.0, 45.0
_JOIN_COMPACT_BYTES_ROW, _JOIN_COMPACT_BYTES_LANE = 16, 28
# a join that reads its partners' payload rows (index/devstore.
# _join_partner_read: two include partners or more) gathers one 8 B row
# a lane where the rows read gathers jpos and three columns (12 B), and
# copies no arena column: a bitmap membership after the first costs 20
# flops a window lane, a later one 40 a bucket lane (fit at r 65,536 and
# 131,072, one to four later memberships, bs 1 and 4); a sort-merge
# membership sorts the side-table position it makes, so its segment
# streams docids alone
_JOINBM_PAYLOAD_FLOPS_LANE = 20.0
_JOINBM_PAYLOAD_LATER_FLOPS_LANE = 40.0
_JOIN_PAYLOAD_BYTES_LANE, _JOIN_ROWS_BYTES_LANE = 8, 12


def _c_cardinal_scores16(n: int) -> Cost:
    return Cost(flops=_CARDINAL_FLOPS_ROW * n,
                bytes=ROW_BYTES * n + 4 * n)      # feats+flags + i32 out


def _c_score_topk16(n: int, k: int = 16) -> Cost:
    return Cost(flops=_TOPK16_FLOPS_ROW * n,
                bytes=ROW_BYTES * n + 8 * k)


def _c_score_topk(n: int, k: int = 16) -> Cost:
    return Cost(flops=_TOPK32_FLOPS_ROW * n,
                bytes=(P.NF * 4 + 8) * n + 8 * k)


def _c_scan_score_topk(n: int, k: int = 16, tile: int = 1 << 20) -> Cost:
    steps = max(1, -(-n // tile))
    rows = steps * tile
    return Cost(flops=_SCAN_FLOPS_ROW * rows,
                bytes=ROW_BYTES * rows + 8 * k)


def _c_stream_score_topk(n: int, k: int = 100, chunk: int = 1 << 21) -> Cost:
    # host driver, not a jit kernel: two device passes (stats, then
    # score+merge) over every chunk — the composition of the calibrated
    # local_stats and scan-body coefficients
    return Cost(flops=(_STATS_FLOPS_ROW + _SCAN_FLOPS_ROW) * n,
                bytes=2 * ROW_BYTES * n + 8 * k)


def _c_rank_spans(rows: int, n_spans: int = 8, k: int = 16,
                  with_stats_pass: bool = True) -> Cost:
    """The exact streaming scan (_rank_spans_kernel): stats + score
    passes over `rows` tile-rows (sum of span counts rounded up to whole
    tiles). The cross-check shape is rows = n_spans × TILE (one fori
    step per unrolled span slot). `with_stats_pass=False` models the
    cached-ext-stats twin: pass 1 skipped, half the streamed reads
    (927 = 261 stats + 666 score per row — the coefficients compose)."""
    if with_stats_pass:
        flops, passes = _SPANS_FLOPS_ROW, 2
    else:
        flops, passes = _SPANS_FLOPS_ROW - _STATS_FLOPS_ROW, 1
    return Cost(flops=flops * rows,
                bytes=passes * ROW_BYTES_DEAD * rows + 8 * k)


def _c_rank_pruned_batch1(bs: int, tile: int = 32_768, maxt: int = 64,
                          k: int = 16) -> Cost:
    """The steady-state b=1 batched pruned kernel: each slot scores ONE
    proxy-best tile and bound-walks its pmax tail."""
    rows = bs * tile
    return Cost(flops=_PRUNED1_FLOPS_ROW * rows,
                bytes=ROW_BYTES_DEAD * rows + 4 * bs * maxt + 8 * bs * k)


def _c_rank_pruned(b: int, tile: int = 32_768, bs: int = 1,
                   k: int = 16) -> Cost:
    """The escalation pruned kernel: `b` scored tiles per slot (lax.map
    over slots; unit-trip cost = one tile body)."""
    rows = bs * b * tile
    return Cost(flops=_PRUNEDB_FLOPS_ROW * rows,
                bytes=ROW_BYTES_DEAD * rows + 8 * bs * k)


def _c_rank_join(r: int, m: int = 0, n_inc: int = 1, n_exc: int = 0,
                 bs: int = 1, k: int = 16, bucket: int = 0,
                 payload: bool = False) -> Cost:
    """Sort-merge device conjunction: rare span of `r` rows, one (r+m)
    sort-merge membership per partner segment of `m` rows (`n_inc` +
    `n_exc` partner memberships, the kernel statics' counts); with a
    `bucket`, every membership after the first over (bucket+m) keys
    (one bucket: the survivors fit it); `payload`: the partners' payload
    rows read in place of their arena rows."""
    partners = max(n_inc + n_exc, 1)
    # per partner the gathered columns a lane, and the segment streamed
    # for the sort a key: (docid, pos), or the docid alone
    lane = _JOIN_PAYLOAD_BYTES_LANE if payload else _JOIN_ROWS_BYTES_LANE
    seg = 4 if payload else 8
    if not bucket:
        flops = bs * r * (_JOIN_FLOPS_R + 146.0 * (partners - 1)) \
            + bs * _JOIN_FLOPS_M * m * partners
        # compulsory: rare rows once; per partner its lanes' columns and
        # its segment
        comp = bs * (ROW_BYTES_DEAD * r + partners * (lane * r + seg * m)
                     + 8 * k)
        return Cost(flops=flops, bytes=comp)
    later = partners - 1
    flops = ((_JOIN_FLOPS_R + _JOIN_COMPACT_FLOPS_ROW) * r
             + _JOIN_FLOPS_M * m
             + later * (_JOIN_LATER_FLOPS_LANE * bucket
                        + _JOIN_LATER_FLOPS_M * m))
    comp = ((ROW_BYTES_DEAD + lane + _JOIN_COMPACT_BYTES_ROW) * r
            + (lane * later + _JOIN_COMPACT_BYTES_LANE) * bucket
            + partners * seg * m + 8 * k)
    return Cost(flops=bs * flops, bytes=bs * comp)


def _c_rank_join_bm(r: int, n_inc: int = 1, n_exc: int = 0, bs: int = 1,
                    k: int = 16, bucket: int = 0,
                    payload: bool = False) -> Cost:
    """Bitmap-membership conjunction: 2 gathers per lane per partner
    instead of the (r+m) sort — O(r) regardless of partner size; with a
    `bucket`, every membership after the first over the bucket. A lane
    of a partner reads its bitmap row (8 B), then its arena row from
    jpos and three columns of it (12 B), or with `payload` its payload
    row (8 B)."""
    partners = max(n_inc + n_exc, 1)
    per = 8 + (_JOIN_PAYLOAD_BYTES_LANE if payload
               else _JOIN_ROWS_BYTES_LANE)
    if not bucket:
        more = _JOINBM_PAYLOAD_FLOPS_LANE if payload else 160.0
        flops = bs * r * (_JOINBM_FLOPS_ROW + more * (partners - 1))
        comp = bs * (ROW_BYTES_DEAD * r + partners * per * r + 8 * k)
        return Cost(flops=flops, bytes=comp)
    later = partners - 1
    per_lane = (_JOINBM_PAYLOAD_LATER_FLOPS_LANE if payload
                else _JOINBM_LATER_FLOPS_LANE)
    flops = ((_JOINBM_FLOPS_ROW + _JOIN_COMPACT_FLOPS_ROW) * r
             + later * per_lane * bucket)
    comp = ((ROW_BYTES_DEAD + per + _JOIN_COMPACT_BYTES_ROW) * r
            + (per * later + _JOIN_COMPACT_BYTES_LANE) * bucket + 8 * k)
    return Cost(flops=bs * flops, bytes=bs * comp)


def _c_bm25_topk(n: int, t: int = 3, k: int = 16) -> Cost:
    # XLA fit: flops = (6t + 10)/row, exact at t in {3, 5, 8}
    return Cost(flops=(6.0 * t + 10.0) * n,
                bytes=(4 * t + 8) * n + 8 * k)


def _c_hybrid_rerank(n: int, dim: int = 256, k: int = 100) -> Cost:
    # matvec (2·dim) + normalize/blend/top_k; XLA: (4·dim + 11) flops
    # per row at dim 256. Compulsory traffic is the f32 doc-matrix read
    # (bf16 cast happens in registers)
    return Cost(flops=(4.0 * dim + 11.0) * n,
                bytes=4 * n * dim + 5 * n + 8 * k)


def _c_hybrid_rerank_batch(n: int, b: int = 16, dim: int = 256,
                           k: int = 100) -> Cost:
    """The MXU case: B queries amortize one doc-matrix read. XLA fit:
    flops = 2·b·n·dim + 11·b·n + 2·dim·n."""
    return Cost(flops=2.0 * b * n * dim + 11.0 * b * n + 2.0 * dim * n,
                bytes=4 * n * dim + b * (5 * n + 8 * k))


def _c_dense_boost(n: int, dim: int = 256, k: int = 100) -> Cost:
    return Cost(flops=(4.0 * dim + 22.0) * n,
                bytes=4 * n * dim + 9 * n + 8 * k)


# batched forward-index rerank (the hybrid second stage as a batcher
# kernel family): per candidate lane one dim-wide bf16 dot (2·dim) +
# blend/round + the two-key (score, docid) tie sort ≈ 545, plus a
# per-slot descriptor decode ≈ 650 — exact at (nb, bs) in
# {16..1024}×{4..16}, dim 256
_RERANK_FLOPS_LANE_EXTRA = 545.0
_RERANK_FLOPS_SLOT = 650.0


def _c_rerank_fwd_batch(bs: int = 16, nb: int = 128,
                        dim: int = 256) -> Cost:
    """_rerank_fwd_batch_packed_kernel: bs slots × nb candidate lanes
    gathering from a [cap, dim] f16 forward index. Compulsory traffic:
    the gathered doc vectors (2·dim B/lane), the fused descriptor in,
    the packed scores++docids out."""
    lanes = bs * nb
    return Cost(flops=(2.0 * dim + _RERANK_FLOPS_LANE_EXTRA) * lanes
                + _RERANK_FLOPS_SLOT * bs,
                bytes=2 * dim * lanes + 4 * (2 + 2 * nb + dim) * bs
                + 8 * lanes)


# bit-packed (*_bp) fused-decode scorers: the compulsory HBM stream is
# the PACKED bytes (row_bits/8 per row — the whole point of the format)
# plus the tombstone gather and outputs; decode adds ~6 int ops per
# value (two word reads folded by shifts/masks) on top of the scoring
# flops.  Both coefficients are fits to the module AS LOWERED (see
# `xla_cost`): after the CPU pipeline has expanded the decode gathers,
# HloCostAnalysis charges each one per element of the packed-words
# operand (5 flops per arena word at jax 0.4.37, 26 at 0.9.0) — a cost
# that follows arena capacity and the host's XLA build, not the work.
# Lowered, the gather's charge is 1 per arena word (0.2-0.5% of the
# pinned shapes) and is left out.
_PRUNED1_BP_FLOPS_ROW = 1018.0
_SCAN_BP_FLOPS_ROW = 1713.0


def _c_rank_pruned_batch1_bp(bs: int, tile: int = 32_768, maxt: int = 64,
                             k: int = 16,
                             row_bits: float = 160.0) -> Cost:
    """The b=1 pruned kernel over bit-packed spans: each slot decodes +
    scores ONE tile straight from the packed words. Compulsory bytes =
    packed payload (row_bits/8 per row) — compression is throughput on
    a memory-bound roofline."""
    rows = bs * tile
    return Cost(flops=_PRUNED1_BP_FLOPS_ROW * rows,
                bytes=(row_bits / 8.0 + 1) * rows + 4 * bs * maxt
                + 8 * bs * k)


def _c_rank_scan_batch_bp(rows: int, k: int = 16, bs: int = 1,
                          row_bits: float = 160.0) -> Cost:
    """Exact two-pass scan over bit-packed spans (stats, then score):
    the packed payload streams twice, like the int16 scan's two passes
    over ROW_BYTES."""
    return Cost(flops=_SCAN_BP_FLOPS_ROW * rows,
                bytes=2 * (row_bits / 8.0 + 1) * rows + 8 * k)


# device-side index build (ingest/devbuild.py, ISSUE 13b): the vmapped
# bit-pack of B posting blocks.  Per value: min/max reduce share, width
# derivation, offset/shift math and the two scatter-add lanes — ~43.5
# flops/value × NCOLS values/row ≈ 826 flops/row, plus per-ROW reduce
# setup XLA amortizes across lanes (76/row) and per-LANE meta/clz work
# (5277/lane); <1% over bs in {2..16} × rows in {256..4096}.
# Compulsory traffic: the block rows once in (ROW_BYTES + 8) and the
# PACKED payload out (row_bits/8 per row) — the same accounting the
# *_bp scorers state their reads in.
_PACK_FLOPS_ROW = 826.0
_PACK_FLOPS_ROWS = 76.0
_PACK_FLOPS_LANE = 5277.0
_PACK_FLOPS_CONST = 418.0


def _c_pack_block_batch(bs: int, rows: int,
                        row_bits: float = 160.0) -> Cost:
    """_pack_block_batch_kernel: bs vmap lanes bit-packing rows-row
    blocks (ingest device build)."""
    n = bs * rows
    return Cost(flops=_PACK_FLOPS_ROW * n + _PACK_FLOPS_ROWS * rows
                + _PACK_FLOPS_LANE * bs + _PACK_FLOPS_CONST,
                bytes=(ROW_BYTES + 8) * n + (row_bits / 8.0) * n
                + 4.0 * (3 * (P.NF + 2) + 1) * bs)


# dense-first IVF ANN family (ops/ann.py, ISSUE 11).  Assignment is
# the (B,dim)×(dim,C) bf16 matmul (+ per-element top-k overhead XLA
# counts as 2·dim·(C+bs)); fuse is per-lane work (int8 gather + dequant
# matmul + fused boost + two-key sort — the per-lane constant fits to
# <0.5% at dim 256 over bs in {4..16} × nb in {1k..16k}).  The
# quantized residency IS the byte win (arxiv 1406.3170 applied to
# vectors).
_ANN_FUSE_FLOPS_LANE = 1078.0


def _c_ann_assign(bs: int, dim: int = 256, C: int = 1024,
                  np_: int = 8) -> Cost:
    """Centroid assignment: ONE (B,dim)×(dim,C) bf16 matmul per wave."""
    return Cost(flops=2.0 * dim * (bs * C + C + bs),
                bytes=2 * C * dim + 4 * bs * dim + 4 * bs * np_)


def _c_ann_fuse(bs: int, nb: int, dim: int = 256, k: int = 16) -> Cost:
    """IVF probe + dense/sparse fusion: batched int8 gathers over the
    hot slab with dequant fused into the scoring matmul. Compulsory
    bytes = the gathered quantized lanes + packed descriptors + fused
    top-k out."""
    lanes = bs * nb
    desc = 4.0 * (2 + 3 * nb + dim) * bs
    return Cost(flops=_ANN_FUSE_FLOPS_LANE * lanes,
                bytes=(dim + 6.0) * lanes + desc + 8.0 * bs * k)


# fused all-gather+top-k fusion collective (parallel/mesh.py, ISSUE 12b):
# each shard ships its exact local top-k — the wire payload is
# 8 B x k x n_shards (score+docid), never full score rows — and the
# tie-pinned two-key merge sorts the G = n_shards*k gathered rows, both
# sorts with the n*log2(n) comparison count a sort costs (empirical CPU
# fit over k in {16..128} x ndev in {4,8} x rows in {256..4096}).


def _log2(n: float) -> float:
    import math
    return math.log2(max(n, 2.0))


def _c_all_gather_topk(k: int, ndev: int, rows: int = 256) -> Cost:
    g = ndev * k
    return Cost(flops=1.08 * rows * _log2(rows) + 1.1 * g * _log2(g)
                + 120.0,
                bytes=8.0 * rows + 8.0 * g + 8.0 * k)


def _c_power_iterate(n: int, edges: int, iters: int = 1) -> Cost:
    """BlockRank power iteration (ops/blockrank._power_iterate_sparse):
    per-iteration segment-sum over the edge list, × the trip count (the
    while body counts once in the XLA model; iters=1 is the cross-check
    shape). Fit to the module as lowered (the CPU pipeline's scatter
    expansion moves the optimised count by host): flops = 5·e + 8·n + 5."""
    return Cost(flops=(5.0 * edges + 8.0 * n + 5.0) * iters,
                bytes=(12 * edges + 8 * n) * iters)


# kernel name -> cost fn; names match the python symbol the kernel is
# defined under (tests/test_code_hygiene.py walks the sources and demands
# an entry — or an explicit exemption — for every named jit kernel in
# ops/ and index/devstore.py)
KERNELS: dict[str, object] = {
    "cardinal_scores16": _c_cardinal_scores16,
    "score_topk16": _c_score_topk16,
    "score_topk": _c_score_topk,
    "scan_score_topk": _c_scan_score_topk,
    "stream_score_topk": _c_stream_score_topk,
    "bm25_topk": _c_bm25_topk,
    "hybrid_rerank_topk": _c_hybrid_rerank,
    "hybrid_rerank_topk_batch": _c_hybrid_rerank_batch,
    "dense_boost_topk": _c_dense_boost,
    "_power_iterate_sparse": _c_power_iterate,
    "_rank_spans_kernel": _c_rank_spans,
    "_rank_pruned_kernel": _c_rank_pruned,
    "_rank_pruned_batch1_kernel": _c_rank_pruned_batch1,
    "_rank_pruned_batch_kernel": _c_rank_pruned,
    "_rank_scan_batch_kernel": _c_rank_spans,
    "_rank_join_batch_kernel": _c_rank_join,
    "_rank_join_bm_batch_kernel": _c_rank_join_bm,
    # packed-I/O variants (one transfer each way per dispatch): the
    # wrapped body IS the unpacked kernel, so the cost model is shared —
    # the concat epilogue is noise against the row streams
    "score_topk16_packed": _c_score_topk16,
    "_rerank_fwd_batch_packed_kernel": _c_rerank_fwd_batch,
    "_rank_spans_packed_kernel": _c_rank_spans,
    "_rank_pruned_batch1_packed_kernel": _c_rank_pruned_batch1,
    "_rank_scan_batch_packed_kernel": _c_rank_spans,
    "_rank_join_batch_packed_kernel": _c_rank_join,
    "_rank_join_bm_batch_packed_kernel": _c_rank_join_bm,
    # bit-packed fused-decode variants (compressed residency): cost
    # models count the PACKED bytes — the compression ratio is the
    # roofline-visible win
    "_rank_pruned_batch1_bp_kernel": _c_rank_pruned_batch1_bp,
    "_rank_scan_batch_bp_kernel": _c_rank_scan_batch_bp,
    # dense-first IVF ANN family (ISSUE 11): assignment matmul + the
    # probe/fuse gather kernel — the hygiene gate additionally demands
    # a NumPy oracle in ops/ann.ANN_ORACLES for every _ann_* kernel
    "_ann_assign_batch_kernel": _c_ann_assign,
    "_ann_fuse_batch_packed_kernel": _c_ann_fuse,
    # device-side index build (ISSUE 13b): the write path's vmapped
    # bit-pack — fresh runs land pre-packed, parity-pinned bit-identical
    # to ops/packed.pack_block (tests/test_ingest.py)
    "_pack_block_batch_kernel": _c_pack_block_batch,
    # fused all-gather+top-k fusion collective (ISSUE 12b), the one
    # implementation every mesh fusion site shares — gathered bytes
    # scale with k, not corpus rows (the r5 motivation: full score rows
    # shipped)
    "all_gather_topk": _c_all_gather_topk,
}

# jit-compiled functions that are NOT serving kernels used to be
# exempted here; that second suppression registry is gone — the lint
# engine's one exemption grammar (a costmodel-ok lint comment on the
# kernel def, see utils/lint) carries them now, so every exemption in
# the repo audits with a single grep.  The dict stays (empty) because
# the kernel-cost-model checker still unions it, which keeps old
# branches linting.
EXEMPT: dict[str, str] = {}


def cost(kernel: str, **shape) -> Cost:
    """The analytical cost of one `kernel` execution at `shape`."""
    fn = KERNELS.get(kernel)
    if fn is None:
        raise KeyError(f"no cost model registered for kernel {kernel!r}")
    return fn(**shape)


def registered() -> list[str]:
    return sorted(KERNELS)


def xla_cost(jitfn, *args, lowered: bool = False, **kwargs) -> float:
    """XLA's flop count for one call of `jitfn`: the independent count
    of operations the cost models are pinned to (nan when the backend
    doesn't expose it). By default the compiled module's; `lowered`
    reads the module before any backend's optimisation, which no CPU
    feature or XLA pipeline can change — the pin for kernels whose
    optimised count follows how a backend expands gathers/scatters."""
    try:
        low = jitfn.lower(*args, **kwargs)
        analysis = (low if lowered else low.compile()).cost_analysis()
    except Exception:
        return float("nan")
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else {}
    return float((analysis or {}).get("flops", float("nan")))


def ascii_table(points: list[RooflinePoint], peak: DevicePeak) -> str:
    """The achieved-vs-peak table as plain text."""
    head = (f"device peak: {peak.name} — "
            f"{peak.flops_per_s / 1e12:.1f} TFLOP/s, "
            f"{peak.bytes_per_s / 1e9:.0f} GB/s, "
            f"ridge {peak.ridge:.1f} flops/byte")
    rows = [head,
            f"{'kernel':<28}{'GFLOPs':>9}{'MB':>9}{'int.':>7}"
            f"{'GF/s':>9}{'GB/s':>8}{'bound':>9}{'util%':>8}"]
    for p in points:
        rows.append(
            f"{p.kernel:<28}{p.flops / 1e9:>9.3f}{p.bytes / 1e6:>9.1f}"
            f"{p.intensity:>7.1f}{p.achieved_flops_per_s / 1e9:>9.2f}"
            f"{p.achieved_bytes_per_s / 1e9:>8.2f}{p.bound:>9}"
            f"{p.util_pct:>8.2f}")
    return "\n".join(rows)
