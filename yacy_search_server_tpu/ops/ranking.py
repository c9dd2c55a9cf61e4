"""Batched ranking kernel — ReferenceOrder as one XLA program.

Capability equivalent of the reference's query-time scorer (reference:
source/net/yacy/search/ranking/ReferenceOrder.java:51-265 and
RankingProfile.java:82-341). The reference normalizes posting attributes
with a distributor thread + N NormalizeWorker threads that stream-decode
rows and accumulate global min/max under benign races, then scores each
posting with `cardinal` = sum over ~25 signals of
(normalized-to-0..255 value << coefficient). Here the entire construct is
one batched kernel:

    min/max  = masked column reduce over the postings block
    norm     = (x - min) * 256 // (max - min)        (0 when max == min)
    cardinal = sum_s (norm_s or 255-flag) << coeff_s
    top-k    = jax.lax.top_k over the scores

which XLA fuses into a few passes over HBM; there are no threads, no
poison pills, and no tolerated min/max races (SURVEY.md §5: the reference
catches ArithmeticException from concurrent min/max mutation —
SearchEvent.java:811-815; batching removes the race by construction).

Scores are int32: max single signal is 256 << 15 (~8.4e6), ~30 signals
never exceeds 2^31. Integer division matches Java semantics for the
non-negative attribute values involved (both truncate toward zero).

A BM25 kernel (ops/bm25.py semantics inline here) complements cardinal for
the BASELINE.json configs: the reference has no BM25 of its own (scoring
is cardinal + Solr-side relevance); BM25 over the same dense blocks is the
TPU build's first-stage text relevance.
"""

from __future__ import annotations

from dataclasses import dataclass, field, fields
from functools import partial
from operator import attrgetter

import jax
import jax.numpy as jnp
import numpy as np

from ..index import postings as P
from ..utils import native
from ..utils.bitfield import (
    FLAG_APP_DC_CREATOR, FLAG_APP_DC_DESCRIPTION, FLAG_APP_DC_IDENTIFIER,
    FLAG_APP_DC_SUBJECT, FLAG_APP_DC_TITLE, FLAG_APP_EMPHASIZED,
    FLAG_CAT_HASAPP, FLAG_CAT_HASAUDIO, FLAG_CAT_HASIMAGE,
    FLAG_CAT_HASVIDEO, FLAG_CAT_INDEXOF,
)

# content domains (reference: cora/document/analysis/Classification.ContentDomain)
CD_ALL, CD_TEXT, CD_IMAGE, CD_AUDIO, CD_VIDEO, CD_APP = -1, 0, 1, 2, 3, 4


@dataclass
class RankingProfile:
    """The 32 shift coefficients, defaults per content domain.

    Names and default values follow the reference
    (RankingProfile.java:92-124); (de)serialization uses the same
    `name=value,...` external form so profiles survive the P2P search wire
    (reference: toExternalString, used in Protocol.java:957).
    """

    domlength: int = 10
    date: int = 9
    wordsintitle: int = 2
    wordsintext: int = 3
    phrasesintext: int = 0
    llocal: int = 0
    lother: int = 7
    urllength: int = 6
    urlcomps: int = 7
    hitcount: int = 1
    posintext: int = 4
    posofphrase: int = 0
    posinphrase: int = 0
    authority: int = 5
    worddistance: int = 10
    appurl: int = 12
    appdescr: int = 14      # app_dc_title ("description of page" legacy name)
    appauthor: int = 1      # app_dc_creator
    apptags: int = 2        # app_dc_subject
    appref: int = 10        # app_dc_description (anchor text)
    appemph: int = 5
    catindexof: int = 0
    cathasimage: int = 0
    cathasaudio: int = 0
    cathasvideo: int = 0
    cathasapp: int = 0
    tf: int = 8
    language: int = 2
    citation: int = 10
    # post-ranking predicates (applied host-side in SearchEvent.post_ranking)
    urlcompintoplist: int = 2
    descrcompintoplist: int = 2
    prefer: int = 0

    @staticmethod
    def for_contentdom(cd: int) -> "RankingProfile":
        p = RankingProfile()
        p.cathasapp = 15 if cd == CD_APP else 0
        p.cathasaudio = 15 if cd == CD_AUDIO else 0
        p.cathasimage = 15 if cd == CD_IMAGE else 0
        p.cathasvideo = 15 if cd == CD_VIDEO else 0
        p.catindexof = 0 if cd in (CD_TEXT, CD_ALL) else 15
        return p

    def to_external_string(self) -> str:
        return ",".join(f"{f.name}={getattr(self, f.name)}" for f in fields(self))

    @staticmethod
    def from_external_string(s: str) -> "RankingProfile":
        p = RankingProfile()
        if not s:
            return p
        s = s.strip()
        if s.startswith("{") and s.endswith("}"):
            s = s[1:-1].strip()
        parts = s.split("&") if "&" in s else s.split(",")
        valid = {f.name for f in fields(p)}
        for part in parts:
            if "=" not in part:
                continue
            k, _, v = part.strip().partition("=")
            if k in valid:
                try:
                    setattr(p, k, max(0, min(15, int(v))))
                except ValueError:
                    pass
        return p

    # -- kernel parameter vectors -------------------------------------------

    def norm_coeffs(self) -> np.ndarray:
        """int32 [NF]-aligned shift coefficients for normalized attributes.

        Index i applies to feature column i of index/postings.py. Sign
        convention: positive = higher-is-better (direct), negative =
        lower-is-better (the reference's `256 - norm` inversion).
        """
        c = np.zeros(P.NF, dtype=np.int32)
        c[P.F_LASTMOD] = self.date
        c[P.F_WORDS_IN_TITLE] = self.wordsintitle
        c[P.F_WORDS_IN_TEXT] = self.wordsintext
        c[P.F_PHRASES_IN_TEXT] = self.phrasesintext
        c[P.F_LLOCAL] = self.llocal
        c[P.F_LOTHER] = self.lother
        c[P.F_URL_LENGTH] = -self.urllength
        c[P.F_URL_COMPS] = -self.urlcomps
        c[P.F_HITCOUNT] = self.hitcount
        c[P.F_POSINTEXT] = -self.posintext
        c[P.F_POSINPHRASE] = -self.posinphrase
        c[P.F_POSOFPHRASE] = -self.posofphrase
        c[P.F_WORDDISTANCE] = -self.worddistance
        return c

    def flag_coeffs(self) -> tuple[np.ndarray, np.ndarray]:
        """(flag bit positions, shift coefficients) for the 255<<coeff terms."""
        pairs = [
            (FLAG_APP_DC_IDENTIFIER, self.appurl),
            (FLAG_APP_DC_TITLE, self.appdescr),
            (FLAG_APP_DC_CREATOR, self.appauthor),
            (FLAG_APP_DC_SUBJECT, self.apptags),
            (FLAG_APP_DC_DESCRIPTION, self.appref),
            (FLAG_APP_EMPHASIZED, self.appemph),
            (FLAG_CAT_INDEXOF, self.catindexof),
            (FLAG_CAT_HASIMAGE, self.cathasimage),
            (FLAG_CAT_HASAUDIO, self.cathasaudio),
            (FLAG_CAT_HASVIDEO, self.cathasvideo),
            (FLAG_CAT_HASAPP, self.cathasapp),
        ]
        bits = np.array([b for b, _ in pairs], dtype=np.int32)
        shifts = np.array([s for _, s in pairs], dtype=np.int32)
        return bits, shifts


# direct (higher-is-better) columns never invert; flags column is special
_NORM_DIRECT = np.zeros(P.NF, dtype=bool)
for _i in (P.F_LASTMOD, P.F_WORDS_IN_TITLE, P.F_WORDS_IN_TEXT,
           P.F_PHRASES_IN_TEXT, P.F_LLOCAL, P.F_LOTHER, P.F_HITCOUNT):
    _NORM_DIRECT[_i] = True


def _masked_minmax(feats: jnp.ndarray, valid: jnp.ndarray):
    """Column-wise min/max over valid rows (int32 sentinels elsewhere)."""
    big = jnp.int32(2**31 - 1)
    small = jnp.int32(-(2**31 - 1))
    v = valid[:, None]
    col_min = jnp.min(jnp.where(v, feats, big), axis=0)
    col_max = jnp.max(jnp.where(v, feats, small), axis=0)
    return col_min, col_max


def local_stats(feats: jnp.ndarray, valid: jnp.ndarray, hostids: jnp.ndarray,
                num_hosts: int, with_host_counts: bool = True) -> dict:
    """Per-block normalization statistics (pure shard-local reduces).

    Returned stats combine across shards with (min, max, min, max, sum):
    the sharded path (parallel/mesh.py) runs this per doc-shard, merges via
    lax.pmin/pmax/psum over the mesh axis, and feeds the merged stats to
    `cardinal_from_stats` — bitwise identical to the single-device path.

    `with_host_counts=False` skips the (expensive) per-host scatter-add —
    legitimate whenever the profile's authority guard is off (the
    reference also skips the domain-count accumulation then,
    ReferenceOrder.java:255)."""
    col_min, col_max = _masked_minmax(feats, valid)
    tfv = _term_frequency(feats)
    tf_min = jnp.min(jnp.where(valid, tfv, jnp.inf))
    tf_max = jnp.max(jnp.where(valid, tfv, -jnp.inf))
    if with_host_counts:
        host_counts = jax.ops.segment_sum(valid.astype(jnp.int32), hostids,
                                          num_segments=num_hosts)
    else:
        host_counts = jnp.zeros(1, dtype=jnp.int32)
    return {"col_min": col_min, "col_max": col_max,
            "tf_min": tf_min, "tf_max": tf_max, "host_counts": host_counts}


def _term_frequency(feats: jnp.ndarray) -> jnp.ndarray:
    """hitcount / (wordsintext + wordsintitle + 1)
    (WordReferenceVars.termFrequency semantics)."""
    return feats[:, P.F_HITCOUNT].astype(jnp.float32) / (
        feats[:, P.F_WORDS_IN_TEXT].astype(jnp.int32)
        + feats[:, P.F_WORDS_IN_TITLE].astype(jnp.int32) + 1
    ).astype(jnp.float32)


def _norm_div_exact_fast(prod: jnp.ndarray, safe_span: jnp.ndarray) -> jnp.ndarray:
    """floor(prod / span) without integer division (TPUs emulate int div
    expensively): f32-reciprocal estimate + /-1 integer correction.

    EXACT when prod <= 2^23 (f32 represents the product exactly and the
    estimate is within +-1 of the true quotient) — guaranteed for compact
    int16 blocks where prod = diff * 256 <= 2^15 * 256 = 2^23."""
    q0 = (prod.astype(jnp.float32)
          * (1.0 / safe_span.astype(jnp.float32))[None, :]).astype(jnp.int32)
    r = prod - q0 * safe_span[None, :]
    return q0 + (r >= safe_span[None, :]).astype(jnp.int32) \
        - (r < 0).astype(jnp.int32)


def cardinal_from_stats(feats: jnp.ndarray, valid: jnp.ndarray,
                        hostids: jnp.ndarray, stats: dict,
                        norm_coeffs: jnp.ndarray,
                        flag_bits: jnp.ndarray, flag_shifts: jnp.ndarray,
                        domlength_coeff: jnp.ndarray, tf_coeff: jnp.ndarray,
                        language_coeff: jnp.ndarray,
                        authority_coeff: jnp.ndarray,
                        language_pref: jnp.ndarray,
                        fast_div: bool = False,
                        flags: jnp.ndarray | None = None) -> jnp.ndarray:
    """Score rows against precomputed (possibly cross-shard) statistics.

    `feats` may be int16 (compact block) — expressions promote to int32
    elementwise, so XLA reads the narrow array from HBM and widens in
    registers; `flags` then carries the int32 bitfields separately (the
    compact block zeroes that column). No full-width copy is ever
    materialized."""
    col_min, col_max = stats["col_min"], stats["col_max"]
    span = col_max - col_min
    safe_span = jnp.maximum(span, 1)

    prod = (feats.astype(jnp.int32) - col_min[None, :]) * 256
    if fast_div:
        norm = _norm_div_exact_fast(prod, safe_span)
    else:
        norm = prod // safe_span[None, :]
    norm = jnp.where(span[None, :] == 0, 0, norm)
    direct = jnp.asarray(_NORM_DIRECT)
    # inverted attributes score (256 - norm), but stay 0 when span == 0
    inv = jnp.where(span[None, :] == 0, 0, 256 - norm)
    contrib = jnp.where(direct[None, :], norm, inv)
    shifts = jnp.abs(norm_coeffs)
    per_col = contrib << shifts[None, :]
    # columns with no coefficient at all (flags, doctype, language, domlength)
    active = jnp.asarray(
        np.array([True] * P.NF, dtype=bool)
        & ~np.isin(np.arange(P.NF), [P.F_FLAGS, P.F_DOCTYPE, P.F_LANGUAGE,
                                     P.F_DOMLENGTH]))
    score = jnp.sum(jnp.where(active[None, :], per_col, 0), axis=1)

    # domlength: stored pre-normalized 0..255; (256 - v) << coeff
    score = score + ((256 - feats[:, P.F_DOMLENGTH].astype(jnp.int32))
                     << domlength_coeff)

    # term frequency: hitcount / (wordsintext + wordsintitle + 1), min/max
    # normalized to 0..255 (WordReferenceVars.termFrequency semantics)
    tf = _term_frequency(feats)
    tf_min, tf_max = stats["tf_min"], stats["tf_max"]
    tf_span = tf_max - tf_min
    tf_norm = jnp.where(
        tf_span > 0, ((tf - tf_min) * 256.0 / jnp.maximum(tf_span, 1e-9)),
        0.0).astype(jnp.int32)
    score = score + (tf_norm << tf_coeff)

    # language preference match: 255 << coeff
    score = score + jnp.where(
        feats[:, P.F_LANGUAGE].astype(jnp.int32) == language_pref,
        jnp.int32(255) << language_coeff, 0)

    # appearance/category flags: 255 << coeff each
    if flags is None:
        flags = feats[:, P.F_FLAGS].astype(jnp.int32)
    flag_hit = (flags[:, None] >> flag_bits[None, :]) & 1
    score = score + jnp.sum(flag_hit * (255 << flag_shifts[None, :]), axis=1)

    # authority: domain-frequency score, only when coeff > 12
    # (ReferenceOrder.java:255 guard); counts precomputed in stats so they
    # can be psum'd across doc shards. A single-entry counts array means
    # the caller disabled authority at trace time (the guard is false):
    # skip the gather+divide entirely instead of computing a dead branch.
    counts = stats["host_counts"]
    if counts.shape[0] > 1:
        maxdom = jnp.max(counts)
        auth = (counts[hostids] << 8) // (1 + maxdom)
        score = score + jnp.where(authority_coeff > 12,
                                  auth << authority_coeff, 0)

    return jnp.where(valid, score, jnp.int32(-(2**31 - 1)))


def cardinal_scores(feats: jnp.ndarray, valid: jnp.ndarray,
                    hostids: jnp.ndarray, norm_coeffs: jnp.ndarray,
                    flag_bits: jnp.ndarray, flag_shifts: jnp.ndarray,
                    domlength_coeff: jnp.ndarray, tf_coeff: jnp.ndarray,
                    language_coeff: jnp.ndarray, authority_coeff: jnp.ndarray,
                    language_pref: jnp.ndarray) -> jnp.ndarray:
    """int32 cardinal score per posting row (invalid rows score MIN).

    Vectorized ReferenceOrder.cardinal (ReferenceOrder.java:223-265):
    every `(x-min)<<8 / (max-min) << coeff` term becomes a masked column
    op; the authority signal's ConcurrentScoreMap of host counts
    (ReferenceOrder.java:213-216) becomes a segment-sum over hostids.
    Single-device composition of local_stats + cardinal_from_stats.
    """
    stats = local_stats(feats, valid, hostids, num_hosts=feats.shape[0])
    return cardinal_from_stats(feats, valid, hostids, stats, norm_coeffs,
                               flag_bits, flag_shifts, domlength_coeff,
                               tf_coeff, language_coeff, authority_coeff,
                               language_pref)


# ---------------------------------------------------------------------------
# Compact device blocks — int16 features + separate int32 flags
# ---------------------------------------------------------------------------
# The scorer is HBM-bandwidth-bound: a 10M-row int32 block is 680 MB per
# scan. Every posting attribute except the flag bitfield is small by
# construction (hitcount <= 255, positions <= 2^15, day counts < 2^15), so
# the device-resident form halves the bytes: int16 [n, NF] with the flags
# column zeroed, plus one int32 [n] flags array. Values are clipped into
# int16 range at pack time — part of the block format, applied identically
# on every read path.

INT16_MAX = 32767


def compact_feats(feats: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """int32 [n, NF] -> (int16 [n, NF] with flags zeroed, int32 [n] flags)."""
    flags = np.ascontiguousarray(feats[:, P.F_FLAGS]).astype(np.int32)
    small = np.clip(feats, -INT16_MAX - 1, INT16_MAX).astype(np.int16)
    small[:, P.F_FLAGS] = 0
    return small, flags


def cardinal_scores16(feats16: jnp.ndarray, flags: jnp.ndarray,
                      valid: jnp.ndarray, hostids: jnp.ndarray,
                      stats: dict | None, norm_coeffs: jnp.ndarray,
                      flag_bits: jnp.ndarray, flag_shifts: jnp.ndarray,
                      domlength_coeff: jnp.ndarray, tf_coeff: jnp.ndarray,
                      language_coeff: jnp.ndarray,
                      authority_coeff: jnp.ndarray,
                      language_pref: jnp.ndarray,
                      with_authority: bool = True) -> jnp.ndarray:
    """Compact-block scorer: reads half the bytes of the int32 path and
    normalizes with the exact fast division. Identical scores to
    cardinal_scores over `compact_feats`-clipped int32 input.

    `with_authority` is the TRACE-TIME authority guard (profile.authority
    > 12, known host-side): when False the per-host scatter/gather is
    never built into the program."""
    if stats is None:
        # NB: the flags column's min/max come out 0 (the compact block
        # zeroes that column) — harmless: normalization masks the flags
        # column out entirely; the bitfield scores via `flags` below
        stats = local_stats(feats16, valid, hostids,
                            num_hosts=feats16.shape[0],
                            with_host_counts=with_authority)
    return cardinal_from_stats(feats16, valid, hostids, stats, norm_coeffs,
                               flag_bits, flag_shifts, domlength_coeff,
                               tf_coeff, language_coeff, authority_coeff,
                               language_pref, fast_div=True, flags=flags)


@partial(jax.jit, static_argnames=("k", "with_authority"))
def score_topk16(feats16: jnp.ndarray, flags: jnp.ndarray,
                 docids: jnp.ndarray, valid: jnp.ndarray,
                 hostids: jnp.ndarray, norm_coeffs: jnp.ndarray,
                 flag_bits: jnp.ndarray, flag_shifts: jnp.ndarray,
                 domlength_coeff: jnp.ndarray, tf_coeff: jnp.ndarray,
                 language_coeff: jnp.ndarray, authority_coeff: jnp.ndarray,
                 language_pref: jnp.ndarray, k: int,
                 with_authority: bool = True):
    """Fused compact-block cardinal + top-k (bandwidth-halved score_topk)."""
    scores = cardinal_scores16(feats16, flags, valid, hostids, None,
                               norm_coeffs, flag_bits, flag_shifts,
                               domlength_coeff, tf_coeff, language_coeff,
                               authority_coeff, language_pref,
                               with_authority=with_authority)
    # lint: tie-ok(lax.top_k breaks ties by lowest input index and the candidate rows are docid-ordered, so equal scores surface docid-ASC — the pinned discipline, asserted by the tie tests in test_ranking)
    top_scores, top_idx = jax.lax.top_k(scores, k)
    return top_scores, docids[top_idx], top_idx


@partial(jax.jit, static_argnames=("k", "with_authority"))
def score_topk16_packed(feats16: jnp.ndarray, flags: jnp.ndarray,
                        docids: jnp.ndarray, valid: jnp.ndarray,
                        hostids: jnp.ndarray, norm_coeffs: jnp.ndarray,
                        flag_bits: jnp.ndarray, flag_shifts: jnp.ndarray,
                        domlength_coeff: jnp.ndarray,
                        tf_coeff: jnp.ndarray,
                        language_coeff: jnp.ndarray,
                        authority_coeff: jnp.ndarray,
                        language_pref: jnp.ndarray, k: int,
                        with_authority: bool = True):
    """score_topk16 with a packed [2k] int32 output (scores ++ docids):
    ONE device->host transfer per query — every separately fetched
    array is its own device round trip, and the upload path
    (CardinalRanker.rank over a candidate block) paid two."""
    s, d, _ = score_topk16(feats16, flags, docids, valid, hostids,
                           norm_coeffs, flag_bits, flag_shifts,
                           domlength_coeff, tf_coeff, language_coeff,
                           authority_coeff, language_pref, k,
                           with_authority=with_authority)
    return jnp.concatenate([s, d])


@partial(jax.jit, static_argnames=("k",))
def score_topk(feats: jnp.ndarray, docids: jnp.ndarray, valid: jnp.ndarray,
               hostids: jnp.ndarray, norm_coeffs: jnp.ndarray,
               flag_bits: jnp.ndarray, flag_shifts: jnp.ndarray,
               domlength_coeff: jnp.ndarray, tf_coeff: jnp.ndarray,
               language_coeff: jnp.ndarray, authority_coeff: jnp.ndarray,
               language_pref: jnp.ndarray, k: int):
    """Fused cardinal + top-k: the device replacement for the rwiStack heap
    (reference: SearchEvent.java:809 bounded WeakPriorityBlockingQueue)."""
    scores = cardinal_scores(feats, valid, hostids, norm_coeffs, flag_bits,
                             flag_shifts, domlength_coeff, tf_coeff,
                             language_coeff, authority_coeff, language_pref)
    # lint: tie-ok(lax.top_k breaks ties by lowest input index and the candidate rows are docid-ordered, so equal scores surface docid-ASC — the pinned discipline, asserted by the tie tests in test_ranking)
    top_scores, top_idx = jax.lax.top_k(scores, k)
    return top_scores, docids[top_idx], top_idx


def pad_to(n: int, tile: int = 128) -> int:
    """Round up to a tile multiple (lane dimension friendly); min one tile."""
    return max(tile, ((n + tile - 1) // tile) * tile)


def hostid_array(docids: np.ndarray, hosthashes: list[bytes] | np.ndarray) -> np.ndarray:
    """Map per-row host hashes to dense int ids (for the authority kernel)."""
    _, ids = np.unique(np.asarray(hosthashes), return_inverse=True)
    return ids.astype(np.int32)


# below this candidate count the host ranks: a device round trip costs
# more than scoring the block. Measured on the chip's host (PERF.md 5,
# ledger PR 33): the NumPy twin took 4.3-8.8 ms a query there, sixty array
# calls that each hand the interpreter lock away; the fused native scorer
# (CardinalRanker.rank, PR 34) is one call. Where the crossover to the
# device lies now is ROADMAP R11.
SMALL_RANK_N = 4096


# columns carrying normalized contributions (flags/doctype/language/
# domlength are handled by their own terms)
_ACTIVE_COLS = ~np.isin(
    np.arange(P.NF), [P.F_FLAGS, P.F_DOCTYPE, P.F_LANGUAGE, P.F_DOMLENGTH])


def pack_stats_host(feats16: np.ndarray, flags: np.ndarray) -> dict:
    """Normalization stats over a compact block (numpy twin of
    local_stats, all rows valid) — float32 tf to match the kernel."""
    f = feats16.astype(np.int32)
    tf = f[:, P.F_HITCOUNT].astype(np.float32) / (
        f[:, P.F_WORDS_IN_TEXT] + f[:, P.F_WORDS_IN_TITLE] + 1
    ).astype(np.float32)
    return {
        "col_min": f.min(axis=0).astype(np.int32),
        "col_max": f.max(axis=0).astype(np.int32),
        "tf_min": np.float32(tf.min()),
        "tf_max": np.float32(tf.max()),
    }


def cardinal_from_stats_host(feats16: np.ndarray, flags: np.ndarray,
                             stats: dict, prof: "RankingProfile",
                             language_pref: int,
                             hostids: np.ndarray | None = None) -> np.ndarray:
    """Numpy twin of cardinal_from_stats over a compact block. Integer
    parts are bit-exact vs the device kernel; tf normalization runs in
    float32 like the kernel (so host and device agree on the same input).
    The single canonical host twin: CardinalRanker's small-candidate fast
    path and devstore's pack-time proxy ordering both call this."""
    f = feats16.astype(np.int32)
    col_min, col_max = stats["col_min"], stats["col_max"]
    span = col_max - col_min
    safe = np.maximum(span, 1)
    norm = ((f - col_min[None, :]) * 256) // safe[None, :]
    norm = np.where(span[None, :] == 0, 0, norm)
    inv = np.where(span[None, :] == 0, 0, 256 - norm)
    contrib = np.where(_NORM_DIRECT[None, :], norm, inv)
    per_col = contrib << np.abs(prof.norm_coeffs())[None, :]
    score = np.where(_ACTIVE_COLS[None, :], per_col, 0).sum(
        axis=1, dtype=np.int64)
    score += (256 - f[:, P.F_DOMLENGTH]) << prof.domlength
    tf = f[:, P.F_HITCOUNT].astype(np.float32) / (
        f[:, P.F_WORDS_IN_TEXT] + f[:, P.F_WORDS_IN_TITLE] + 1
    ).astype(np.float32)
    tf_span = stats["tf_max"] - stats["tf_min"]
    tf_norm = np.where(
        tf_span > 0,
        (tf - stats["tf_min"]) * np.float32(256.0) / max(tf_span, 1e-9),
        0.0).astype(np.int32)
    score += tf_norm.astype(np.int64) << prof.tf
    score += np.where(f[:, P.F_LANGUAGE] == language_pref,
                      255 << prof.language, 0)
    bits, shifts = prof.flag_coeffs()
    hit = (flags[:, None] >> bits[None, :]) & 1
    score += (hit * (255 << shifts[None, :])).sum(axis=1, dtype=np.int64)
    if prof.authority > 12 and hostids is not None and len(f):
        counts = np.bincount(hostids, minlength=int(hostids.max()) + 1)
        auth = (counts[hostids].astype(np.int64) << 8) // (1 + counts.max())
        score += auth << prof.authority
    return score.astype(np.int64)


def cardinal_scores_host(feats: np.ndarray, profile: "RankingProfile",
                         language: str = "en",
                         hostids: np.ndarray | None = None) -> np.ndarray:
    """Pure-numpy scorer for small candidate sets (the P2P fan-out's
    per-peer searches and tiny-term queries, where a device dispatch per
    query would dominate end-to-end latency). Scores the SAME compact
    int16 representation the device path scores (compact_feats clip +
    float32 tf), so host and device agree on every input."""
    feats16, flags = compact_feats(np.asarray(feats, dtype=np.int32))
    stats = pack_stats_host(feats16, flags)
    return cardinal_from_stats_host(feats16, flags, stats, profile,
                                    P.pack_language(language), hostids)


# the profile fields the native scorer's constants are made of
_NATIVE_FIELDS = attrgetter(
    "date", "wordsintitle", "wordsintext", "phrasesintext", "llocal",
    "lother", "urllength", "urlcomps", "hitcount", "posintext",
    "posinphrase", "posofphrase", "worddistance", "domlength", "tf",
    "language", "appurl", "appdescr", "appauthor", "apptags", "appref",
    "appemph", "catindexof", "cathasimage", "cathasaudio", "cathasvideo",
    "cathasapp")
_native_consts_cache: dict[tuple, np.ndarray | None] = {}


def _native_consts(prof: RankingProfile) -> np.ndarray | None:
    """The profile as native/yacytpu.cpp ytn_cardinal_scores reads it
    (layout there), built once per distinct profile; None for a shift
    outside 0..15, which only the NumPy twin defines."""
    key = _NATIVE_FIELDS(prof)
    try:
        return _native_consts_cache[key]
    except KeyError:
        pass
    consts = None
    if 0 <= min(key) and max(key) <= 15:
        coeffs = prof.norm_coeffs()
        bits, shifts = prof.flag_coeffs()
        consts = np.concatenate([
            [P.F_FLAGS, P.F_HITCOUNT, P.F_WORDS_IN_TEXT, P.F_WORDS_IN_TITLE,
             P.F_LANGUAGE, P.F_DOMLENGTH,
             prof.domlength, prof.tf, prof.language, len(bits)],
            np.abs(coeffs), np.where(_ACTIVE_COLS,
                                     np.where(_NORM_DIRECT, 1, 2), 0),
            bits, shifts]).astype(np.int32)
    if len(_native_consts_cache) >= 64:   # profiles arrive on the wire
        _native_consts_cache.clear()
    _native_consts_cache[key] = consts
    return consts


class CardinalRanker:
    """Host-side wrapper: pad → upload → score_topk, profile baked in."""

    def __init__(self, profile: RankingProfile | None = None,
                 language: str = "en"):
        self.profile = profile or RankingProfile()
        self._lang_str = language
        self._consts = None   # device constants, built on first device rank

    def _device_consts(self):
        """Lazy device upload of the profile constants: a ranker whose
        every query takes the small-n host path (tiny peers, sparse terms)
        must never pay the 11 per-constant transfers at construction —
        SearchEvent builds one ranker per query."""
        if self._consts is None:
            bits, shifts = self.profile.flag_coeffs()
            self._consts = (
                jnp.asarray(self.profile.norm_coeffs()),
                jnp.asarray(bits), jnp.asarray(shifts),
                jnp.int32(self.profile.domlength),
                jnp.int32(self.profile.tf),
                jnp.int32(self.profile.language),
                jnp.int32(self.profile.authority),
                jnp.int32(P.pack_language(self._lang_str)))
        return self._consts

    # constant accessors (kernel call sites and the multichip dryrun read
    # these; they trigger the lazy device upload)
    @property
    def _norm(self):
        return self._device_consts()[0]

    @property
    def _bits(self):
        return self._device_consts()[1]

    @property
    def _shifts(self):
        return self._device_consts()[2]

    @property
    def _dl(self):
        return self._device_consts()[3]

    @property
    def _tf(self):
        return self._device_consts()[4]

    @property
    def _lang_c(self):
        return self._device_consts()[5]

    @property
    def _auth(self):
        return self._device_consts()[6]

    @property
    def _lang(self):
        return self._device_consts()[7]

    def rank(self, plist, hosthashes=None, k: int = 10,
             how: dict | None = None):
        """(scores, docids) best-first over a PostingsList. `how`, if
        given, is filled with the ranker that answered: `native` / `numpy`
        (the host's two, equal bit for bit) or `device`."""
        n = len(plist)
        if n == 0:
            return np.empty(0, np.int32), np.empty(0, np.int32)
        if how is None:
            how = {}
        if n <= SMALL_RANK_N:
            # host fast path: no kernel dispatch for tiny candidate sets.
            # ONE native call where the library is loaded; the authority
            # term needs the host counts and stays on the NumPy twin
            consts = (_native_consts(self.profile)
                      if self.profile.authority <= 12 else None)
            got = None if consts is None else native.cardinal_topk(
                plist.feats, consts, P.pack_language(self._lang_str), k)
            if got is not None:
                how["ranker"] = "native"
                s, order = got
            else:
                how["ranker"] = "numpy"
                hostids = (hostid_array(plist.docids, hosthashes)
                           if hosthashes is not None else None)
                s = cardinal_scores_host(plist.feats, self.profile,
                                         self._lang_str, hostids)
                order = np.argsort(-s, kind="stable")[:k]
            return s[order], plist.docids[order]
        how["ranker"] = "device"
        npad = pad_to(n)
        feats = np.zeros((npad, P.NF), np.int32)
        feats[:n] = plist.feats
        docids = np.full(npad, -1, np.int32)
        docids[:n] = plist.docids
        valid = np.zeros(npad, bool)
        valid[:n] = True
        hostids = np.zeros(npad, np.int32)
        if hosthashes is not None:
            hostids[:n] = hostid_array(plist.docids, hosthashes)
        kk = min(k, npad)
        feats16, flags = compact_feats(feats)
        norm, bits, shifts, dl, tf, lang_c, auth, lang = self._device_consts()
        out = score_topk16_packed(
            jnp.asarray(feats16), jnp.asarray(flags),
            jnp.asarray(docids), jnp.asarray(valid),
            jnp.asarray(hostids),
            norm, bits, shifts, dl, tf, lang_c, auth, lang, kk,
            with_authority=self.profile.authority > 12)
        host = np.asarray(out)       # one packed fetch (scores ++ docids)
        s, d = host[:kk], host[kk:]
        keep = d >= 0
        keep &= s > -(2**31 - 1)
        return s[keep][:k], d[keep][:k]


# ---------------------------------------------------------------------------
# BM25 — dense doc×term first-stage relevance (BASELINE.json configs)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("k",))
def bm25_topk(tf: jnp.ndarray, doclen: jnp.ndarray, df: jnp.ndarray,
              ndocs: jnp.ndarray, valid: jnp.ndarray, docids: jnp.ndarray,
              k: int, k1: float = 1.2, b: float = 0.75):
    """BM25 over a dense [docs, terms] tf block + top-k.

    tf:     float32/int32 [n, t] term frequencies for the query terms
    doclen: int32 [n] document lengths (words)
    df:     int32 [t] document frequencies of the query terms
    ndocs:  scalar corpus size
    """
    tf = tf.astype(jnp.float32)
    dl = doclen.astype(jnp.float32)
    avgdl = jnp.sum(jnp.where(valid, dl, 0.0)) / jnp.maximum(
        jnp.sum(valid.astype(jnp.float32)), 1.0)
    idf = jnp.log(1.0 + (ndocs.astype(jnp.float32) - df + 0.5) / (df + 0.5))
    denom = tf + k1 * (1.0 - b + b * (dl / jnp.maximum(avgdl, 1e-6))[:, None])
    score = jnp.sum(idf[None, :] * tf * (k1 + 1.0) / jnp.maximum(denom, 1e-9),
                    axis=1)
    score = jnp.where(valid, score, -jnp.inf)
    # lint: tie-ok(lax.top_k breaks ties by lowest input index and the candidate rows are docid-ordered, so equal scores surface docid-ASC — the pinned discipline, asserted by the tie tests in test_ranking)
    top_scores, top_idx = jax.lax.top_k(score, k)
    return top_scores, docids[top_idx]


def bm25_scores_np(tf: np.ndarray, doclen: np.ndarray, df: np.ndarray,
                   ndocs: int, k1: float = 1.2, b: float = 0.75) -> np.ndarray:
    """Numpy oracle for tests/benchmarks (identical math)."""
    tf = tf.astype(np.float64)
    dl = doclen.astype(np.float64)
    avgdl = dl.mean() if len(dl) else 1.0
    idf = np.log(1.0 + (ndocs - df + 0.5) / (df + 0.5))
    denom = tf + k1 * (1.0 - b + b * (dl / max(avgdl, 1e-6))[:, None])
    return (idf[None, :] * tf * (k1 + 1.0) / np.maximum(denom, 1e-9)).sum(axis=1)
