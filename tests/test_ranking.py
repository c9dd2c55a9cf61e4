"""Ranking kernel tests — device cardinal/BM25 vs pure-Python oracles.

Mirrors the reference's ReferenceOrderTest style (monotonicity between a
default and an all-zero ranking profile,
test/java/net/yacy/search/ranking/ReferenceOrderTest.java:24-52) plus
bit-exact comparison of the batched kernel against a per-row loop oracle
implementing ReferenceOrder.cardinal semantics.
"""

import numpy as np
import pytest

from yacy_search_server_tpu.index import postings as P
from yacy_search_server_tpu.index.postings import PostingsList
from yacy_search_server_tpu.ops import ranking as R
from yacy_search_server_tpu.utils.bitfield import (
    FLAG_APP_DC_TITLE, FLAG_APP_DC_IDENTIFIER, FLAG_CAT_HASIMAGE,
)


def _rand_plist(n, seed=0):
    rng = np.random.default_rng(seed)
    docids = np.arange(n, dtype=np.int32)
    feats = np.zeros((n, P.NF), np.int32)
    feats[:, P.F_LASTMOD] = rng.integers(18000, 21000, n)
    feats[:, P.F_WORDS_IN_TITLE] = rng.integers(0, 12, n)
    feats[:, P.F_WORDS_IN_TEXT] = rng.integers(10, 5000, n)
    feats[:, P.F_PHRASES_IN_TEXT] = rng.integers(1, 300, n)
    feats[:, P.F_LANGUAGE] = np.where(rng.random(n) < 0.5,
                                      P.pack_language("en"),
                                      P.pack_language("de"))
    feats[:, P.F_LLOCAL] = rng.integers(0, 50, n)
    feats[:, P.F_LOTHER] = rng.integers(0, 50, n)
    feats[:, P.F_URL_LENGTH] = rng.integers(10, 255, n)
    feats[:, P.F_URL_COMPS] = rng.integers(1, 12, n)
    feats[:, P.F_FLAGS] = (
        (rng.random(n) < 0.3) * (1 << FLAG_APP_DC_TITLE)
        | (rng.random(n) < 0.2) * (1 << FLAG_APP_DC_IDENTIFIER)
        | (rng.random(n) < 0.4) * (1 << FLAG_CAT_HASIMAGE)).astype(np.int32)
    feats[:, P.F_HITCOUNT] = rng.integers(1, 100, n)
    feats[:, P.F_POSINTEXT] = rng.integers(1, 4000, n)
    feats[:, P.F_POSINPHRASE] = rng.integers(1, 40, n)
    feats[:, P.F_POSOFPHRASE] = rng.integers(0, 200, n)
    feats[:, P.F_WORDDISTANCE] = rng.integers(0, 500, n)
    feats[:, P.F_DOMLENGTH] = rng.integers(0, 256, n)
    return PostingsList(docids, feats)


def oracle_cardinal(feats, profile: R.RankingProfile, lang="en",
                    hostids=None):
    """Per-row loop implementing the reference's cardinal formula."""
    n = len(feats)
    fmin = feats.min(axis=0)
    fmax = feats.max(axis=0)

    def norm(row, col):
        lo, hi = fmin[col], fmax[col]
        if hi == lo:
            return 0
        return (int(row[col]) - int(lo)) * 256 // (int(hi) - int(lo))

    tfv = feats[:, P.F_HITCOUNT] / (
        feats[:, P.F_WORDS_IN_TEXT] + feats[:, P.F_WORDS_IN_TITLE] + 1)
    tf_lo, tf_hi = tfv.min(), tfv.max()

    counts = None
    if hostids is not None:
        counts = np.bincount(hostids, minlength=n)

    out = np.zeros(n, dtype=np.int64)
    for i, row in enumerate(feats):
        s = 0
        s += (256 - int(row[P.F_DOMLENGTH])) << profile.domlength
        for col, coeff, invert in [
            (P.F_URL_COMPS, profile.urlcomps, True),
            (P.F_URL_LENGTH, profile.urllength, True),
            (P.F_POSINTEXT, profile.posintext, True),
            (P.F_POSOFPHRASE, profile.posofphrase, True),
            (P.F_POSINPHRASE, profile.posinphrase, True),
            (P.F_WORDDISTANCE, profile.worddistance, True),
            (P.F_LASTMOD, profile.date, False),
            (P.F_WORDS_IN_TITLE, profile.wordsintitle, False),
            (P.F_WORDS_IN_TEXT, profile.wordsintext, False),
            (P.F_PHRASES_IN_TEXT, profile.phrasesintext, False),
            (P.F_LLOCAL, profile.llocal, False),
            (P.F_LOTHER, profile.lother, False),
            (P.F_HITCOUNT, profile.hitcount, False),
        ]:
            if fmax[col] == fmin[col]:
                continue
            v = norm(row, col)
            s += ((256 - v) if invert else v) << coeff
        if tf_hi > tf_lo:
            s += int((tfv[i] - tf_lo) * 256.0 / (tf_hi - tf_lo)) << profile.tf
        if row[P.F_LANGUAGE] == P.pack_language(lang):
            s += 255 << profile.language
        flags = int(row[P.F_FLAGS])
        for bit, coeff in zip(*profile.flag_coeffs()):
            if flags >> int(bit) & 1:
                s += 255 << int(coeff)
        if profile.authority > 12 and counts is not None:
            s += ((int(counts[hostids[i]]) << 8) // (1 + int(counts.max()))) \
                << profile.authority
        out[i] = s
    return out


def _kernel_scores(plist, profile, lang="en", hostids=None):
    import jax.numpy as jnp
    n = len(plist)
    r = R.CardinalRanker(profile, lang)
    feats = jnp.asarray(plist.feats)
    valid = jnp.ones(n, bool)
    hi = jnp.asarray(hostids if hostids is not None else np.zeros(n, np.int32))
    s = R.cardinal_scores(feats, valid, hi, r._norm, r._bits, r._shifts,
                          r._dl, r._tf, r._lang_c, r._auth, r._lang)
    return np.asarray(s)


def test_cardinal_matches_oracle():
    plist = _rand_plist(500, seed=1)
    prof = R.RankingProfile()
    got = _kernel_scores(plist, prof)
    want = oracle_cardinal(plist.feats, prof)
    np.testing.assert_array_equal(got.astype(np.int64), want)


def test_cardinal_authority_matches_oracle():
    plist = _rand_plist(300, seed=2)
    rng = np.random.default_rng(3)
    hostids = rng.integers(0, 12, len(plist)).astype(np.int32)
    prof = R.RankingProfile()
    prof.authority = 13  # above the >12 activation guard
    got = _kernel_scores(plist, prof, hostids=hostids)
    want = oracle_cardinal(plist.feats, prof, hostids=hostids)
    np.testing.assert_array_equal(got.astype(np.int64), want)


def test_default_profile_dominates_zero_profile():
    # ReferenceOrderTest monotonicity: all-zero coefficients rank lower
    plist = _rand_plist(100, seed=4)
    default = _kernel_scores(plist, R.RankingProfile())
    zero_prof = R.RankingProfile(**{f.name: 0 for f in
                                    __import__("dataclasses").fields(R.RankingProfile)})
    zero = _kernel_scores(plist, zero_prof)
    assert (default >= zero).all()
    assert default.sum() > zero.sum()


def test_topk_returns_best_first():
    plist = _rand_plist(1000, seed=5)
    ranker = R.CardinalRanker()
    scores, docids = ranker.rank(plist, k=10)
    assert len(scores) == 10
    assert (np.diff(scores) <= 0).all()
    all_scores = _kernel_scores(plist, R.RankingProfile())
    np.testing.assert_array_equal(np.sort(all_scores)[-10:][::-1], scores)


def test_topk_k_larger_than_n():
    plist = _rand_plist(5, seed=6)
    scores, docids = R.CardinalRanker().rank(plist, k=50)
    assert len(scores) == 5
    assert set(docids) == set(plist.docids)


def test_profile_roundtrip():
    p = R.RankingProfile()
    p.worddistance = 3
    p.cathasimage = 15
    q = R.RankingProfile.from_external_string(p.to_external_string())
    assert q == p


def test_profile_contentdom_presets():
    img = R.RankingProfile.for_contentdom(R.CD_IMAGE)
    assert img.cathasimage == 15 and img.catindexof == 15
    txt = R.RankingProfile.for_contentdom(R.CD_TEXT)
    assert txt.cathasimage == 0 and txt.catindexof == 0


def test_bm25_matches_numpy_oracle():
    import jax.numpy as jnp
    rng = np.random.default_rng(7)
    n, t = 400, 3
    tf = rng.integers(0, 20, (n, t)).astype(np.int32)
    doclen = rng.integers(20, 3000, n).astype(np.int32)
    df = rng.integers(1, n, t).astype(np.int32)
    docids = np.arange(n, dtype=np.int32)
    want = R.bm25_scores_np(tf, doclen, df, n)
    s, d = R.bm25_topk(jnp.asarray(tf), jnp.asarray(doclen), jnp.asarray(df),
                       jnp.int32(n), jnp.ones(n, bool), jnp.asarray(docids),
                       10)
    order = np.argsort(-want)[:10]
    np.testing.assert_array_equal(np.asarray(d), docids[order])
    np.testing.assert_allclose(np.asarray(s), want[order], rtol=1e-4)


def test_bm25_invalid_rows_never_win():
    import jax.numpy as jnp
    n, t = 64, 2
    tf = np.full((n, t), 5, np.int32)
    valid = np.zeros(n, bool)
    valid[:3] = True
    s, d = R.bm25_topk(jnp.asarray(tf), jnp.full(n, 100, np.int32),
                       jnp.asarray(np.array([2, 2], np.int32)), jnp.int32(n),
                       jnp.asarray(valid), jnp.arange(n, dtype=jnp.int32), 5)
    assert set(np.asarray(d)[:3]) == {0, 1, 2}
    assert np.isinf(np.asarray(s)[3:]).all()


def test_compact16_scores_bit_identical():
    """Compact int16 block + exact fast division == int32 path exactly."""
    import jax.numpy as jnp
    plist = _rand_plist(800, seed=9)
    # include values near the int16 boundary and big flags
    plist.feats[:5, P.F_POSINTEXT] = 32767
    plist.feats[5:10, P.F_FLAGS] = (1 << 30) - 1
    prof = R.RankingProfile()
    r = R.CardinalRanker(prof, "en")
    n = len(plist)
    valid = jnp.ones(n, bool)
    hi = jnp.zeros(n, jnp.int32)
    want = np.asarray(R.cardinal_scores(
        jnp.asarray(plist.feats), valid, hi, r._norm, r._bits, r._shifts,
        r._dl, r._tf, r._lang_c, r._auth, r._lang))
    f16, flags = R.compact_feats(plist.feats)
    got = np.asarray(R.cardinal_scores16(
        jnp.asarray(f16), jnp.asarray(flags), valid, hi, None,
        r._norm, r._bits, r._shifts, r._dl, r._tf, r._lang_c, r._auth,
        r._lang))
    np.testing.assert_array_equal(got, want)


def test_compact_feats_clipping_and_flags():
    feats = np.zeros((3, P.NF), np.int32)
    feats[0, P.F_WORDS_IN_TEXT] = 1_000_000     # clips to 32767
    feats[1, P.F_FLAGS] = (1 << 29) | 5         # preserved exactly
    f16, flags = R.compact_feats(feats)
    assert f16.dtype == np.int16
    assert f16[0, P.F_WORDS_IN_TEXT] == 32767
    assert (f16[:, P.F_FLAGS] == 0).all()
    assert flags[1] == (1 << 29) | 5


def test_cardinal_host_twin_matches_oracle():
    """The small-candidate numpy path (cardinal_scores_host) must score
    exactly like the per-row oracle (and hence like the device kernel)."""
    plist = _rand_plist(700, seed=9)
    prof = R.RankingProfile()
    got = R.cardinal_scores_host(plist.feats, prof)
    want = oracle_cardinal(plist.feats, prof)
    np.testing.assert_array_equal(got.astype(np.int64), want)


def test_cardinal_host_twin_authority():
    plist = _rand_plist(300, seed=10)
    rng = np.random.default_rng(11)
    hostids = rng.integers(0, 9, len(plist)).astype(np.int32)
    prof = R.RankingProfile()
    prof.authority = 13
    got = R.cardinal_scores_host(plist.feats, prof, hostids=hostids)
    want = oracle_cardinal(plist.feats, prof, hostids=hostids)
    np.testing.assert_array_equal(got.astype(np.int64), want)


def test_rank_small_path_matches_device_path():
    """CardinalRanker.rank must return the same page whether the small-n
    host path or the padded device kernel runs."""
    plist = _rand_plist(900, seed=12)
    prof = R.RankingProfile()
    r = R.CardinalRanker(prof)
    s_host, d_host = r.rank(plist, k=20)          # n < SMALL_RANK_N: host
    import yacy_search_server_tpu.ops.ranking as mod
    saved = mod.SMALL_RANK_N
    try:
        mod.SMALL_RANK_N = 0                       # force device path
        s_dev, d_dev = R.CardinalRanker(prof).rank(plist, k=20)
    finally:
        mod.SMALL_RANK_N = saved
    np.testing.assert_array_equal(np.asarray(d_host), np.asarray(d_dev))
    np.testing.assert_array_equal(np.asarray(s_host, dtype=np.int64),
                                  np.asarray(s_dev, dtype=np.int64))


def test_host_twin_matches_device_on_overflow_feats():
    """Features beyond int16 must clip identically on both paths (the
    compact block format is THE scoring representation)."""
    plist = _rand_plist(900, seed=13)
    plist.feats[5, P.F_WORDS_IN_TEXT] = 40000    # > int16 max
    prof = R.RankingProfile()
    s_host, d_host = R.CardinalRanker(prof).rank(plist, k=30)
    import yacy_search_server_tpu.ops.ranking as mod
    saved = mod.SMALL_RANK_N
    try:
        mod.SMALL_RANK_N = 0
        s_dev, d_dev = R.CardinalRanker(prof).rank(plist, k=30)
    finally:
        mod.SMALL_RANK_N = saved
    np.testing.assert_array_equal(np.asarray(d_host), np.asarray(d_dev))
    np.testing.assert_array_equal(np.asarray(s_host, dtype=np.int64),
                                  np.asarray(s_dev, dtype=np.int64))


def test_host_twin_f32_tf_matches_device_across_seeds():
    """float32 tf normalization: host and device must agree on every
    input (the f64 variant drifted by 1<<tf on ~4% of random blocks)."""
    prof = R.RankingProfile()
    import yacy_search_server_tpu.ops.ranking as mod
    for seed in range(25):
        plist = _rand_plist(400, seed=100 + seed)
        s_host, d_host = R.CardinalRanker(prof).rank(plist, k=400)
        saved = mod.SMALL_RANK_N
        try:
            mod.SMALL_RANK_N = 0
            s_dev, d_dev = R.CardinalRanker(prof).rank(plist, k=400)
        finally:
            mod.SMALL_RANK_N = saved
        np.testing.assert_array_equal(
            np.asarray(s_host, dtype=np.int64),
            np.asarray(s_dev, dtype=np.int64), err_msg=f"seed {seed}")


# -- the device's divisions, held to IEEE float32 -----------------------------

# a quotient an ulp off the correctly rounded one, as a device's division
# may give it (the v5e gave the largest normalised tf 255.99998 for 256)
QUOTIENTS = {
    "exact": lambda q: q,
    "an_ulp_high": lambda q: np.nextafter(q, np.float32(np.inf)),
    "an_ulp_low": lambda q: np.nextafter(q, np.float32(0)),
}


@pytest.mark.parametrize("off", sorted(QUOTIENTS))
def test_term_frequency_division_is_ieee(off):
    """hitcount / words as IEEE float32 rounds it, whatever ulp the
    device's own division lands on: every hit count up to 64 against
    every word count up to 4,096, and a random draw over the int16
    columns' whole range."""
    import jax
    h = np.arange(0, 65, dtype=np.float32)
    w = np.arange(1, 4097, dtype=np.float32)
    H, W = (a.ravel() for a in np.meshgrid(h, w, indexing="ij"))
    rng = np.random.default_rng(42)
    H = np.concatenate([H, rng.integers(0, 32768, 200_000).astype(
        np.float32)])
    W = np.concatenate([W, rng.integers(1, 65536, 200_000).astype(
        np.float32)])
    want = H / W
    got = np.asarray(jax.jit(R._ieee_div_nearest)(
        H, W, QUOTIENTS[off](want).astype(np.float32)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("off", sorted(QUOTIENTS))
def test_tf_normalisation_truncates_as_ieee(off):
    """trunc((tf - tf_min) * 256 / span) as IEEE division gives it, from
    a quotient an ulp off: the largest tf reads 256, and every quotient
    within a few ulps of a whole number floors as IEEE's does."""
    import jax
    rng = np.random.default_rng(7)
    n = 1_000_000
    d = (rng.uniform(1e-4, 1.0, n)
         * 10.0 ** rng.integers(-3, 1, n)).astype(np.float32)
    k = rng.integers(0, 257, n).astype(np.float32)
    num = (k * d).astype(np.float32)
    step = rng.integers(-3, 4, n).astype(np.int32)
    num = np.maximum(num.view(np.int32) + step, 0).astype(
        np.int32).view(np.float32)
    num = np.minimum(num, (np.float32(256) * d).astype(np.float32))
    want = (num / d).astype(np.int32)
    got = np.asarray(jax.jit(R._ieee_trunc_div)(
        num, d, QUOTIENTS[off](num / d).astype(np.float32)))
    np.testing.assert_array_equal(got, want)
    # the largest term frequency of a candidate set: exactly 256
    tf = (np.arange(1, 33, dtype=np.float32)
          / np.float32(301)).astype(np.float32)
    span = tf.max() - tf.min()
    top = (tf.max() - tf.min()) * np.float32(256.0)
    assert int(R._ieee_trunc_div(top, span, QUOTIENTS[off](
        np.float32(top / span)))) == 256
    assert np.asarray(R._tf_norm(tf, tf.min(), tf.max()))[-1] == 256
