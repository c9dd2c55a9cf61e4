// yacytpu native runtime — host-side data-plane kernels.
//
// The reference implements its data plane as concurrent Java (row codecs,
// per-entry MD5+base64 hashing in Word.java:113-130, hash-probe joins in
// ReferenceContainer.java:397-489). Here the TPU owns the scoring FLOPs
// (JAX/XLA/Pallas) and this library owns the host-side feeding paths that
// would otherwise be Python loops:
//
//   - ytn_word_hash_batch : MD5 + enhanced-base64 12-char word hashes
//     (bit-compatible with utils/hashes.word2hash, including the
//     '_____' private-prefix rotation rule) for whole token batches.
//   - ytn_sort_dedupe     : fused stable argsort + last-wins dedupe order
//     for postings blocks (index/postings.sort_dedupe).
//   - ytn_intersect       : two-pointer sorted-docid intersection returning
//     gather indices into both sides (the conjunctive join primitive,
//     index/segment.join_constructive).
//   - ytn_remove_docids   : tombstone mask over sorted dead-id array.
//   - ytn_cardinal_scores : the host gate's ranking of a small candidate
//     block in ONE call (ops/ranking.cardinal_scores_host bit for bit,
//     plus its stable top-k order).
//
// Exposed as a plain C ABI for ctypes (no pybind11 in this image). Every
// entry point is pure (no globals, no allocation ownership transfer): the
// caller allocates outputs, so the Python fallback and the native path are
// interchangeable call-for-call.

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <vector>

extern "C" {

// ---------------------------------------------------------------------------
// MD5 (RFC 1321), compact single-shot implementation.
// ---------------------------------------------------------------------------

namespace {

struct MD5Ctx {
    uint32_t a = 0x67452301u, b = 0xefcdab89u, c = 0x98badcfeu, d = 0x10325476u;
};

inline uint32_t rotl(uint32_t x, int n) { return (x << n) | (x >> (32 - n)); }

const uint32_t K[64] = {
    0xd76aa478u, 0xe8c7b756u, 0x242070dbu, 0xc1bdceeeu, 0xf57c0fafu,
    0x4787c62au, 0xa8304613u, 0xfd469501u, 0x698098d8u, 0x8b44f7afu,
    0xffff5bb1u, 0x895cd7beu, 0x6b901122u, 0xfd987193u, 0xa679438eu,
    0x49b40821u, 0xf61e2562u, 0xc040b340u, 0x265e5a51u, 0xe9b6c7aau,
    0xd62f105du, 0x02441453u, 0xd8a1e681u, 0xe7d3fbc8u, 0x21e1cde6u,
    0xc33707d6u, 0xf4d50d87u, 0x455a14edu, 0xa9e3e905u, 0xfcefa3f8u,
    0x676f02d9u, 0x8d2a4c8au, 0xfffa3942u, 0x8771f681u, 0x6d9d6122u,
    0xfde5380cu, 0xa4beea44u, 0x4bdecfa9u, 0xf6bb4b60u, 0xbebfbc70u,
    0x289b7ec6u, 0xeaa127fau, 0xd4ef3085u, 0x04881d05u, 0xd9d4d039u,
    0xe6db99e5u, 0x1fa27cf8u, 0xc4ac5665u, 0xf4292244u, 0x432aff97u,
    0xab9423a7u, 0xfc93a039u, 0x655b59c3u, 0x8f0ccc92u, 0xffeff47du,
    0x85845dd1u, 0x6fa87e4fu, 0xfe2ce6e0u, 0xa3014314u, 0x4e0811a1u,
    0xf7537e82u, 0xbd3af235u, 0x2ad7d2bbu, 0xeb86d391u};

const int S[64] = {7, 12, 17, 22, 7, 12, 17, 22, 7, 12, 17, 22, 7, 12, 17, 22,
                   5, 9,  14, 20, 5, 9,  14, 20, 5, 9,  14, 20, 5, 9,  14, 20,
                   4, 11, 16, 23, 4, 11, 16, 23, 4, 11, 16, 23, 4, 11, 16, 23,
                   6, 10, 15, 21, 6, 10, 15, 21, 6, 10, 15, 21, 6, 10, 15, 21};

void md5_block(MD5Ctx& ctx, const uint8_t* p) {
    uint32_t m[16];
    for (int i = 0; i < 16; i++)
        m[i] = (uint32_t)p[4 * i] | ((uint32_t)p[4 * i + 1] << 8) |
               ((uint32_t)p[4 * i + 2] << 16) | ((uint32_t)p[4 * i + 3] << 24);
    uint32_t a = ctx.a, b = ctx.b, c = ctx.c, d = ctx.d;
    for (int i = 0; i < 64; i++) {
        uint32_t f;
        int g;
        if (i < 16) {
            f = (b & c) | (~b & d);
            g = i;
        } else if (i < 32) {
            f = (d & b) | (~d & c);
            g = (5 * i + 1) & 15;
        } else if (i < 48) {
            f = b ^ c ^ d;
            g = (3 * i + 5) & 15;
        } else {
            f = c ^ (b | ~d);
            g = (7 * i) & 15;
        }
        uint32_t tmp = d;
        d = c;
        c = b;
        b = b + rotl(a + f + K[i] + m[g], S[i]);
        a = tmp;
    }
    ctx.a += a;
    ctx.b += b;
    ctx.c += c;
    ctx.d += d;
}

void md5(const uint8_t* data, uint64_t len, uint8_t out[16]) {
    MD5Ctx ctx;
    uint64_t i = 0;
    for (; i + 64 <= len; i += 64) md5_block(ctx, data + i);
    uint8_t tail[128];
    uint64_t rem = len - i;
    std::memcpy(tail, data + i, rem);
    tail[rem] = 0x80;
    uint64_t padlen = (rem < 56) ? 64 : 128;
    std::memset(tail + rem + 1, 0, padlen - rem - 1 - 8);
    uint64_t bits = len * 8;
    for (int j = 0; j < 8; j++) tail[padlen - 8 + j] = (uint8_t)(bits >> (8 * j));
    md5_block(ctx, tail);
    if (padlen == 128) md5_block(ctx, tail + 64);
    uint32_t regs[4] = {ctx.a, ctx.b, ctx.c, ctx.d};
    for (int j = 0; j < 4; j++)
        for (int k = 0; k < 4; k++) out[4 * j + k] = (uint8_t)(regs[j] >> (8 * k));
}

// enhanced (filename-safe) base64 alphabet — Base64Order.java:38
const char B64E[65] =
    "ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789-_";

// First 12 enhanced-base64 chars of a 16-byte digest (= first 9 bytes).
void b64_12(const uint8_t d[16], uint8_t out[12]) {
    for (int g = 0; g < 3; g++) {
        uint32_t x = ((uint32_t)d[3 * g] << 16) | ((uint32_t)d[3 * g + 1] << 8) |
                     (uint32_t)d[3 * g + 2];
        out[4 * g + 0] = (uint8_t)B64E[(x >> 18) & 0x3F];
        out[4 * g + 1] = (uint8_t)B64E[(x >> 12) & 0x3F];
        out[4 * g + 2] = (uint8_t)B64E[(x >> 6) & 0x3F];
        out[4 * g + 3] = (uint8_t)B64E[x & 0x3F];
    }
}

}  // namespace

// words: concatenated UTF-8 bytes of already-lowercased tokens;
// offsets: int64[n+1] prefix offsets into `words`;
// out: uint8[n*12] — 12-char hashes, matching utils/hashes.word2hash.
void ytn_word_hash_batch(const uint8_t* words, const int64_t* offsets,
                         int64_t n, uint8_t* out) {
    uint8_t digest[16];
    for (int64_t i = 0; i < n; i++) {
        const uint8_t* w = words + offsets[i];
        uint64_t len = (uint64_t)(offsets[i + 1] - offsets[i]);
        md5(w, len, digest);
        uint8_t* h = out + 12 * i;
        b64_12(digest, h);
        // private-range rotation: '_____'-prefixed hashes are reserved for
        // local/private use (utils/hashes._PRIVATE_PREFIX rule)
        while (h[0] == '_' && h[1] == '_' && h[2] == '_' && h[3] == '_' &&
               h[4] == '_') {
            std::memmove(h, h + 1, 11);
            h[11] = 'A';
        }
    }
}

// ---------------------------------------------------------------------------
// Postings kernels
// ---------------------------------------------------------------------------

// Fused stable-sort + last-wins dedupe: writes into order_out the original
// indices of the surviving rows, in ascending docid order; returns count.
int64_t ytn_sort_dedupe(const int32_t* docids, int64_t n, int64_t* order_out) {
    if (n == 0) return 0;
    std::vector<int64_t> idx(n);
    for (int64_t i = 0; i < n; i++) idx[i] = i;
    std::stable_sort(idx.begin(), idx.end(), [&](int64_t x, int64_t y) {
        return docids[x] < docids[y];
    });
    int64_t m = 0;
    for (int64_t i = 0; i < n; i++) {
        // keep the LAST of each equal-docid run (newest write wins)
        if (i + 1 < n && docids[idx[i]] == docids[idx[i + 1]]) continue;
        order_out[m++] = idx[i];
    }
    return m;
}

// Two-pointer intersection of sorted-unique id arrays; writes gather
// indices for both sides; returns match count.
int64_t ytn_intersect(const int32_t* a, int64_t na, const int32_t* b,
                      int64_t nb, int64_t* ia_out, int64_t* ib_out) {
    int64_t i = 0, j = 0, m = 0;
    while (i < na && j < nb) {
        int32_t va = a[i], vb = b[j];
        if (va < vb)
            i++;
        else if (vb < va)
            j++;
        else {
            ia_out[m] = i;
            ib_out[m] = j;
            m++;
            i++;
            j++;
        }
    }
    return m;
}

// alive_out[i] = 1 unless docids[i] occurs in sorted `dead`.
void ytn_remove_docids(const int32_t* docids, int64_t n, const int32_t* dead,
                       int64_t ndead, uint8_t* alive_out) {
    for (int64_t i = 0; i < n; i++) {
        const int32_t* p = std::lower_bound(dead, dead + ndead, docids[i]);
        alive_out[i] = (p == dead + ndead || *p != docids[i]) ? 1 : 0;
    }
}

// ---------------------------------------------------------------------------
// Cardinal ranking of a small candidate block
// ---------------------------------------------------------------------------

// The int64 scores of ops/ranking.cardinal_scores_host (compact_feats +
// pack_stats_host + cardinal_from_stats_host, authority term apart) over
// feats[n, nf], bit for bit, in two passes over the rows; then the first k
// of its order (score DESC, input index ASC: np.argsort(-s, kind="stable")).
// `consts` is the profile as ops/ranking._native_consts lays it out:
//   [0..6)   columns: flags, hitcount, words_in_text, words_in_title,
//            language, domlength
//   [6..9)   coefficients: domlength, tf, language
//   [9]      number of flag terms F
//   [10..)   nf column shifts, nf column modes (0 none, 1 direct,
//            2 inverted), F flag bits, F flag shifts
// Every shift is 0..15 (the caller's guard). Returns 0, or -1 for more than
// MAX_NF columns or flag terms (the caller then scores with NumPy).
namespace {
constexpr int MAX_NF = 32;
inline int32_t clip16(int32_t v) {
    return v < -32768 ? -32768 : (v > 32767 ? 32767 : v);
}
inline int32_t shl32(int32_t v, int32_t s) {   // int32 `<<` as NumPy wraps it
    return (int32_t)((uint32_t)v << s);
}
}  // namespace

int32_t ytn_cardinal_scores(const int32_t* feats, int64_t n, int32_t nf,
                            const int32_t* consts, int32_t language_pref,
                            int64_t k, int64_t* scores_out,
                            int64_t* order_out) {
    if (nf > MAX_NF || consts[9] > MAX_NF) return -1;
    const int32_t c_flags = consts[0], c_hit = consts[1], c_text = consts[2],
                  c_title = consts[3], c_lang = consts[4], c_dom = consts[5];
    const int32_t k_dom = consts[6], k_tf = consts[7], k_lang = consts[8];
    const int32_t nflag = consts[9];
    const int32_t* col_shift = consts + 10;
    const int32_t* col_mode = col_shift + nf;
    const int32_t* flag_bit = col_mode + nf;
    const int32_t* flag_shift = flag_bit + nflag;

    auto tf_of = [&](const int32_t* row) {    // float32, as the twin's
        return (float)clip16(row[c_hit]) /
            (float)(clip16(row[c_text]) + clip16(row[c_title]) + 1);
    };

    // pass 1: the block's statistics over the int16-clipped rows, the flags
    // column read as 0; float32 tf, a NaN carried as np.min / np.max carry it
    int32_t col_min[MAX_NF], col_max[MAX_NF];
    for (int32_t c = 0; c < nf; c++) {
        col_min[c] = INT32_MAX;
        col_max[c] = INT32_MIN;
    }
    float tf_min = 0.0f, tf_max = 0.0f;
    bool tf_nan = false;
    for (int64_t i = 0; i < n; i++) {
        const int32_t* row = feats + i * nf;
        for (int32_t c = 0; c < nf; c++) {
            const int32_t v = clip16(row[c]);
            col_min[c] = v < col_min[c] ? v : col_min[c];
            col_max[c] = v > col_max[c] ? v : col_max[c];
        }
        const float tf = tf_of(row);
        if (tf != tf) tf_nan = true;
        if (i == 0 || tf < tf_min) tf_min = tf;
        if (i == 0 || tf > tf_max) tf_max = tf;
    }
    col_min[c_flags] = col_max[c_flags] = 0;
    const float tf_span = tf_max - tf_min;
    const bool tf_on = !tf_nan && tf_span > 0.0f;
    const float tf_div = 1e-9f > tf_span ? 1e-9f : tf_span;
    // span 0 where the column adds nothing: all rows equal, or no term
    int32_t span[MAX_NF];
    for (int32_t c = 0; c < nf; c++)
        span[c] = col_mode[c] == 0 ? 0 : col_max[c] - col_min[c];
    const int64_t lang_term = (int64_t)255 << k_lang;
    int64_t flag_term[MAX_NF];
    for (int32_t j = 0; j < nflag; j++)
        flag_term[j] = shl32(255, flag_shift[j]);

    // pass 2: the score of every row
    for (int64_t i = 0; i < n; i++) {
        const int32_t* row = feats + i * nf;
        int64_t score = 0;
        for (int32_t c = 0; c < nf; c++) {
            if (span[c] == 0) continue;
            const int32_t norm =
                ((clip16(row[c]) - col_min[c]) * 256) / span[c];
            score += shl32(col_mode[c] == 1 ? norm : 256 - norm,
                           col_shift[c]);
        }
        score += shl32(256 - clip16(row[c_dom]), k_dom);
        if (tf_on) {
            score += (int64_t)(int32_t)(
                (tf_of(row) - tf_min) * 256.0f / tf_div)
                * ((int64_t)1 << k_tf);
        }
        if (clip16(row[c_lang]) == language_pref) score += lang_term;
        const int32_t flags = row[c_flags];
        for (int32_t j = 0; j < nflag; j++)
            score += (int64_t)((flags >> flag_bit[j]) & 1) * flag_term[j];
        scores_out[i] = score;
    }

    if (k > n) k = n;
    if (k > 0) {
        std::vector<int64_t> idx(n);
        for (int64_t i = 0; i < n; i++) idx[i] = i;
        std::partial_sort(idx.begin(), idx.begin() + k, idx.end(),
                          [&](int64_t x, int64_t y) {
            return scores_out[x] != scores_out[y]
                ? scores_out[x] > scores_out[y] : x < y;
        });
        std::copy(idx.begin(), idx.begin() + k, order_out);
    }
    return 0;
}

// Library identity probe for the loader (utils/native.ABI_VERSION).
int32_t ytn_abi_version() { return 2; }

}  // extern "C"
