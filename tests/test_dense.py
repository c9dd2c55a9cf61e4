"""M7 — dense encoder, hybrid rerank kernel, end-to-end hybrid search."""

import numpy as np
import pytest

from yacy_search_server_tpu.document.document import Document
from yacy_search_server_tpu.index.dense import DenseVectorStore
from yacy_search_server_tpu.index.segment import Segment
from yacy_search_server_tpu.ops.dense import (HashingEncoder,
                                              hybrid_rerank_topk,
                                              hybrid_rerank_topk_np)


def test_encoder_deterministic_and_normalized():
    e = HashingEncoder()
    a = e.encode("distributed tpu search kernels")
    b = e.encode("distributed tpu search kernels")
    assert np.array_equal(a, b)
    assert abs(np.linalg.norm(a) - 1.0) < 1e-5


def test_encoder_similarity_orders_topics():
    e = HashingEncoder()
    q = e.encode("tpu kernel ranking")
    near = e.encode("fast tpu kernels for ranking documents")
    far = e.encode("gardening tomatoes in spring weather")
    assert float(q @ near) > float(q @ far)


def test_rerank_kernel_matches_numpy_oracle():
    rng = np.random.default_rng(3)
    n, dim, k = 300, 64, 10
    docs = rng.normal(size=(n, dim)).astype(np.float32)
    docs /= np.linalg.norm(docs, axis=1, keepdims=True)
    q = docs[17] * 0.9 + 0.1 * rng.normal(size=dim).astype(np.float32)
    sparse = rng.integers(0, 1000, n).astype(np.float32)
    valid = np.ones(n, bool)
    import jax.numpy as jnp
    s_dev, i_dev = hybrid_rerank_topk(
        jnp.asarray(q), jnp.asarray(docs), jnp.asarray(sparse),
        jnp.asarray(valid), jnp.float32(0.5), k)
    s_np, i_np = hybrid_rerank_topk_np(q, docs, sparse, valid, 0.5, k)
    # bf16 matmul tolerance: top sets must agree on >=8/10 and scores close
    assert len(set(np.asarray(i_dev).tolist())
               & set(i_np.tolist())) >= 8
    assert np.allclose(np.asarray(s_dev)[:3], s_np[:3], atol=2e-2)


def test_rerank_alpha_extremes():
    import jax.numpy as jnp
    n, dim = 50, 32
    rng = np.random.default_rng(0)
    docs = rng.normal(size=(n, dim)).astype(np.float32)
    docs /= np.linalg.norm(docs, axis=1, keepdims=True)
    sparse = np.arange(n, dtype=np.float32)
    valid = np.ones(n, bool)
    # alpha=0: pure sparse -> best is index n-1
    _, idx = hybrid_rerank_topk(jnp.asarray(docs[7]), jnp.asarray(docs),
                                jnp.asarray(sparse), jnp.asarray(valid),
                                jnp.float32(0.0), 1)
    assert int(idx[0]) == n - 1
    # alpha=1: pure dense -> best is the query's own doc
    _, idx = hybrid_rerank_topk(jnp.asarray(docs[7]), jnp.asarray(docs),
                                jnp.asarray(sparse), jnp.asarray(valid),
                                jnp.float32(1.0), 1)
    assert int(idx[0]) == 7


def test_vector_store_roundtrip(tmp_path):
    st = DenseVectorStore(str(tmp_path / "dense"), dim=16)
    v = np.arange(16, dtype=np.float32) / 16.0
    st.put(5, v)
    st.put(900, v * 2)          # forces growth
    assert len(st) == 901
    got = st.get_block(np.array([5, 900]))
    assert np.allclose(got[0], v.astype(np.float16))
    st.close()
    st2 = DenseVectorStore(str(tmp_path / "dense"), dim=16)
    assert len(st2) == 901
    assert np.allclose(st2.get_block(np.array([900]))[0],
                       (v * 2).astype(np.float16))


def _doc(url, title, text):
    return Document(url=url, title=title, text=text, mime_type="text/html",
                    language="en")


def test_hybrid_search_end_to_end(tmp_path):
    from yacy_search_server_tpu.search.query import QueryParams
    from yacy_search_server_tpu.search.searchevent import SearchEvent

    seg = Segment(str(tmp_path / "idx"))
    # both docs match the conjunctive query "fast kernels"; the OFF doc
    # wins the sparse stage (query words in its title), the ON doc is the
    # dense topical match (its text is almost entirely query n-gram mass)
    seg.store_document(_doc("http://a.test/on", "page twelve",
                            "fast kernels fast kernels fast kernels"))
    seg.store_document(_doc(
        "http://a.test/off", "Fast kernels cookbook",
        "fast kernels " + " ".join(
            f"unrelated word{i} gardening recipe" for i in range(40))))

    sparse_q = QueryParams.parse("fast kernels")
    sparse_first = SearchEvent(sparse_q, seg).results(count=2)[0].url

    q = QueryParams.parse("fast kernels")
    q.hybrid = True
    q.hybrid_alpha = 0.95
    res = SearchEvent(q, seg).results(count=2)
    assert len(res) == 2
    assert res[0].url == "http://a.test/on"
    # the dense stage actually changed the decision
    assert sparse_first == "http://a.test/off"
    seg.close()


def test_encoder_version_migration(tmp_path):
    """Vectors hashed by an older encoder re-encode on upgrade (the
    feature hash changed in ENCODER_VERSION 2)."""
    import os

    import numpy as np

    from yacy_search_server_tpu.document.document import Document
    from yacy_search_server_tpu.index.segment import Segment
    from yacy_search_server_tpu.migration import migrate_data
    d = str(tmp_path / "seg")
    seg = Segment(data_dir=d)
    docid = seg.store_document(Document(
        url="http://v.test/", title="Versioned", text="encoder text body"))
    seg.close()
    # simulate a store written by the v1 encoder: corrupt the vector and
    # stamp the old version
    os.remove(os.path.join(d, "dense", "ENCODER_VERSION"))
    seg2 = Segment(data_dir=d)
    seg2.dense._vecs[docid] = 0.0
    assert seg2.dense.stale_encoder
    touched = migrate_data(seg2, d, "0.3.2")
    assert touched >= 1
    assert not seg2.dense.stale_encoder
    want = seg2.encoder.encode("Versioned\nencoder text body")
    np.testing.assert_allclose(
        np.asarray(seg2.dense.get_block(np.asarray([docid]))[0],
                   np.float32), want, atol=2e-3)
    seg2.close()


def test_stale_store_never_stamps_mid_migration(tmp_path):
    """Auto-flushes during re-encode must not advance the encoder
    version; a crash mid-migration stays re-runnable (review fix)."""
    import os

    from yacy_search_server_tpu.index.dense import DenseVectorStore
    d = str(tmp_path / "dense")
    st = DenseVectorStore(d)
    st.put(0, np.ones(st.dim, np.float32))
    st.close()
    os.remove(os.path.join(d, "ENCODER_VERSION"))    # v1-era store
    st2 = DenseVectorStore(d)
    assert st2.stale_encoder
    st2.put(1, np.ones(st2.dim, np.float32))
    st2.flush()                                       # mid-migration flush
    assert not os.path.exists(os.path.join(d, "ENCODER_VERSION"))
    st2.close()
    assert DenseVectorStore(d).stale_encoder          # still re-runnable
    st3 = DenseVectorStore(d)
    st3.mark_encoder_current()
    assert not DenseVectorStore(d).stale_encoder


def test_hybrid_rerank_batch_matches_solo():
    """Each batch slot is bit-identical in ORDER to the solo kernel on
    the same inputs (scores compare approximately: bf16 matmul)."""
    import jax.numpy as jnp
    import numpy as np
    from yacy_search_server_tpu.ops.dense import (hybrid_rerank_topk,
                                                  hybrid_rerank_topk_batch)
    rng = np.random.default_rng(7)
    n, dim, b, k = 2048, 64, 4, 10
    docs = rng.standard_normal((n, dim)).astype(np.float32)
    docs /= np.linalg.norm(docs, axis=1, keepdims=True)
    qs = docs[rng.integers(0, n, b)] \
        + 0.1 * rng.standard_normal((b, dim)).astype(np.float32)
    # distinct, well-separated sparse scores at a small alpha: the blend
    # gap between adjacent ranks (~1e-3) dwarfs bf16 accumulation-order
    # divergence between the matvec and matmul shapes (~2e-4), so the
    # ORDER comparison is deterministic on any backend
    alpha = 0.01
    sparse = np.stack([rng.permutation(n) * 1000.0 for _ in range(b)]
                      ).astype(np.float32)
    valid = rng.random((b, n)) > 0.1
    bs, bi = hybrid_rerank_topk_batch(
        jnp.asarray(qs), jnp.asarray(docs), jnp.asarray(sparse),
        jnp.asarray(valid), jnp.float32(alpha), k)
    for i in range(b):
        ss, si = hybrid_rerank_topk(
            jnp.asarray(qs[i]), jnp.asarray(docs), jnp.asarray(sparse[i]),
            jnp.asarray(valid[i]), jnp.float32(alpha), k)
        assert np.array_equal(np.asarray(bi[i]), np.asarray(si))
        np.testing.assert_allclose(np.asarray(bs[i]), np.asarray(ss),
                                   rtol=2e-2, atol=2e-2)


def test_get_block_zero_fills_missing_vectors(tmp_path):
    # a docid with postings but no stored vector (dense.put not landed,
    # or never stored) must gather zeros — the host-gather legacy rerank
    # feeds get_block raw candidate docids and a crash here fails the
    # whole hybrid query
    st = DenseVectorStore(str(tmp_path / "dense"), dim=16)
    st.put(3, np.ones(16, np.float32))
    got = st.get_block(np.array([3, 10_000, -1]))
    assert got.shape == (3, 16)
    assert np.allclose(got[0], 1.0)
    assert not got[1].any() and not got[2].any()


def test_device_block_patch_matches_full_upload(tmp_path):
    import jax
    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    st = DenseVectorStore(str(tmp_path / "dense"), dim=16)
    for i in range(40):
        st.put(i, rng.normal(size=16).astype(np.float32))
    fwd0, v0 = st.device_block(dev)
    # writes move the version; the next device_block PATCHES the
    # resident block (only dirty rows cross the wire) and must be
    # bit-identical to a from-scratch upload
    for i in (2, 7, 39, 41):
        st.put(i, rng.normal(size=16).astype(np.float32))
    fwd1, v1 = st.device_block(dev)
    assert v1 > v0
    st2 = DenseVectorStore(dim=16)
    st2._vecs = st._vecs.copy()
    st2._n = st._n
    fwd_ref, _ = st2.device_block(dev)
    np.testing.assert_array_equal(np.asarray(fwd1), np.asarray(fwd_ref))
    # cached: same version answers without a transfer
    fwd2, v2 = st.device_block(dev)
    assert v2 == v1 and fwd2 is fwd1


def test_device_block_over_budget_releases_block(tmp_path):
    import jax
    dev = jax.devices()[0]
    st = DenseVectorStore(str(tmp_path / "dense"), dim=16)
    st.put(0, np.ones(16, np.float32))
    assert st.device_block(dev) is not None
    assert st._fwd is not None
    # the index grows past the residency budget (now the
    # index.dense.deviceBudgetBytes knob, ISSUE 11 satellite): the
    # block can never be served again and must not stay pinned
    st.device_budget_bytes = 1
    assert st.device_block(dev) is None
    assert st._fwd is None and st._fwd_device is None


def test_device_budget_knob_flows_from_config(tmp_path):
    from yacy_search_server_tpu.switchboard import Switchboard
    from yacy_search_server_tpu.utils.config import Config
    cfg = Config()
    cfg.set("index.dense.deviceBudgetBytes", str(1 << 20))
    sb = Switchboard(data_dir=str(tmp_path / "DATA"), config=cfg)
    try:
        assert sb.index.dense.device_budget_bytes == 1 << 20
    finally:
        sb.close()


# -- encoder vectorization parity (ISSUE 11 satellite) -----------------------

def _reference_encode(text: str, dim: int) -> np.ndarray:
    """The pre-vectorization per-feature accumulate loop, verbatim —
    the bit-parity anchor for the np.add.at rewrite."""
    from zlib import crc32
    v = np.zeros(dim, dtype=np.float32)
    words = [w for w in text.lower().split() if w]
    for w in words[:512]:
        feats = [("w:" + w, 1.0)]
        padded = f"^{w}$"
        for i in range(len(padded) - 2):
            feats.append(("t:" + padded[i:i + 3], 0.5))
        for feat, weight in feats:
            h = crc32(feat.encode("utf-8"))
            v[(h >> 1) % dim] += (1.0 if (h & 1) else -1.0) * weight
    n = float(np.linalg.norm(v))
    return v / n if n > 0 else v


MULTILINGUAL = [
    "the quick brown fox jumps over the lazy dog",
    "schnelle braune Füchse springen über faule Hunde im Wald",
    "los rápidos zorros marrones saltan sobre perros perezosos",
    "快速的棕色狐狸跳过懒狗 分布式 搜索 引擎 排名",
    "быстрые коричневые лисы прыгают через ленивых собак",
    "الثعلب البني السريع يقفز فوق الكلب الكسول",
    "तेज़ भूरी लोमड़ी आलसी कुत्ते के ऊपर कूदती है",
    "素早い茶色の狐が怠け者の犬を飛び越える 検索",
    "", "   ", "a", "ein",
    "repeated repeated repeated word word word",
    "word " * 600,          # the 512-word truncation boundary
]


def test_vectorized_encoder_bit_parity_with_reference():
    """The np.add.at/word-cache encoder is BIT-identical to the legacy
    per-feature loop on a multilingual sample (same buckets, same signs,
    same f32 accumulation order — np.add.at applies in index order)."""
    e = HashingEncoder()
    for t in MULTILINGUAL:
        got = e.encode(t)
        want = _reference_encode(t, e.dim)
        assert np.array_equal(got, want), t[:40]
    # and again with a warm word cache (hits must not change anything)
    for t in MULTILINGUAL:
        assert np.array_equal(e.encode(t), _reference_encode(t, e.dim))


def test_encode_batch_bit_identical_to_encode():
    e = HashingEncoder()
    batch = e.encode_batch(MULTILINGUAL)
    assert batch.shape == (len(MULTILINGUAL), e.dim)
    for i, t in enumerate(MULTILINGUAL):
        assert np.array_equal(batch[i], e.encode(t)), i
    assert e.encode_batch([]).shape == (0, e.dim)


def test_encoder_word_cache_bounded():
    e = HashingEncoder()
    e._CACHE_MAX = 8
    e.encode_batch([f"word{i} unique{i}" for i in range(64)])
    assert len(e._cache) <= 8 + 2       # cleared wholesale at the cap
    # correctness never depends on a hit
    assert np.array_equal(e.encode("word3 unique3"),
                          _reference_encode("word3 unique3", e.dim))


# -- dense snapshot integrity (ISSUE 11 satellite, M84 discipline) -----------

def test_dense_snapshot_crc_footer_roundtrip(tmp_path):
    d = str(tmp_path / "dense")
    st = DenseVectorStore(d, dim=16)
    rng = np.random.default_rng(0)
    for i in range(5):
        st.put(i, rng.standard_normal(16).astype(np.float32))
    st.close()
    st2 = DenseVectorStore(d, dim=16)
    assert len(st2) == 5
    np.testing.assert_array_equal(
        st2.get_block(np.arange(5)), st.get_block(np.arange(5)))


def test_dense_snapshot_corruption_quarantined(tmp_path):
    """A flipped byte in the snapshot: typed detection, the file
    quarantined, the counter bumped, the store opens EMPTY (sparse-only
    serving) — never a crash."""
    import os

    from yacy_search_server_tpu.index import integrity
    d = str(tmp_path / "dense")
    st = DenseVectorStore(d, dim=16)
    st.put(0, np.ones(16, np.float32))
    st.close()
    p = os.path.join(d, "vectors.npy")
    raw = bytearray(open(p, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    open(p, "wb").write(bytes(raw))
    with pytest.raises(integrity.CorruptDenseError):
        DenseVectorStore._read_checked(p)
    before = integrity.corruption_counts().get(("dense", "quarantined"),
                                               0)
    st2 = DenseVectorStore(d, dim=16)      # quarantines, never raises
    assert len(st2) == 0
    assert integrity.corruption_counts()[("dense", "quarantined")] \
        == before + 1
    assert os.path.exists(p + ".corrupt")
    assert not os.path.exists(p)
    # the store keeps serving (and re-persists) after quarantine
    st2.put(0, np.ones(16, np.float32))
    st2.close()
    assert len(DenseVectorStore(d, dim=16)) == 1


def test_dense_snapshot_legacy_footer_free_loads(tmp_path):
    """A pre-footer vectors.npy (no YDV1 tail) stays readable — no
    claim is made, nothing quarantined."""
    import os
    d = str(tmp_path / "dense")
    os.makedirs(d)
    arr = np.ones((3, 16), np.float16)
    with open(os.path.join(d, "vectors.npy"), "wb") as f:
        np.save(f, arr)                    # legacy writer: no footer
    st = DenseVectorStore(d, dim=16)
    assert len(st) == 3
    np.testing.assert_array_equal(
        np.asarray(st.get_block(np.arange(3)), np.float16), arr)


def test_dense_snapshot_verify_switch_respected(tmp_path):
    """VERIFY_ON_READ off: a corrupt-crc file still loads — detection is read-side only, writers always stamp."""
    import os

    from yacy_search_server_tpu.index import integrity
    d = str(tmp_path / "dense")
    st = DenseVectorStore(d, dim=16)
    st.put(0, np.ones(16, np.float32))
    st.close()
    p = os.path.join(d, "vectors.npy")
    raw = bytearray(open(p, "rb").read())
    raw[-2] ^= 0xFF                        # corrupt the stored crc
    open(p, "wb").write(bytes(raw))
    integrity.set_verify_on_read(False)
    try:
        assert len(DenseVectorStore(d, dim=16)) == 1
    finally:
        integrity.set_verify_on_read(True)
    assert len(DenseVectorStore(d, dim=16)) == 0   # verified: quarantined
