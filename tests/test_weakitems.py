"""Round-2 weak-item cleanup (VERDICT r1 weak #6/#7/#8/#10 + §5 logging).

- one score domain for the dense rerank (fixed-scale cardinal boost)
- persistent ErrorCache with journal compaction
- versioned data-store migration (signature backfill)
- async bounded logging subsystem
"""

import logging
import os

import numpy as np

from yacy_search_server_tpu.crawler.queues import ErrorCache


# -- dense rerank: one score domain -------------------------------------


def test_dense_boost_fixed_scale_batch_independent():
    """The boost must not depend on the local batch's score range: the
    same (doc, score) pair ranks identically inside different batches."""
    import jax.numpy as jnp

    from yacy_search_server_tpu.ops.dense import (dense_boost_topk,
                                                  dense_boost_topk_np)
    rng = np.random.default_rng(0)
    dim = 64
    vecs = rng.standard_normal((8, dim)).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    q = vecs[0]
    scores_small = np.arange(8, dtype=np.int32) * 100
    scores_big = scores_small + 50_000_000      # shifted batch
    valid = np.ones(8, bool)

    s1, i1 = dense_boost_topk(jnp.asarray(q), jnp.asarray(vecs),
                              jnp.asarray(scores_small),
                              jnp.asarray(valid), jnp.float32(0.5), 8)
    s2, i2 = dense_boost_topk(jnp.asarray(q), jnp.asarray(vecs),
                              jnp.asarray(scores_big),
                              jnp.asarray(valid), jnp.float32(0.5), 8)
    # a uniform shift of the sparse domain must not change the ordering
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    # the boost itself is the same absolute quantity in both batches
    np.testing.assert_array_equal(
        np.asarray(s2) - np.asarray(s1),
        np.full(8, 50_000_000, dtype=np.int64))
    # oracle parity: same ordering, scores within bf16 rounding
    so, io = dense_boost_topk_np(q, vecs, scores_small, valid, 0.5, 8)
    np.testing.assert_array_equal(np.asarray(i1), io)
    np.testing.assert_allclose(np.asarray(s1, dtype=np.float64), so,
                               rtol=0.02, atol=2000)


def test_hybrid_search_scores_stay_cardinal(tmp_path):
    """End-to-end hybrid query returns scores in the cardinal int domain
    (no batch-max rescaling artifacts)."""
    from yacy_search_server_tpu.document.document import Document
    from yacy_search_server_tpu.index.segment import Segment
    from yacy_search_server_tpu.search.query import QueryParams
    from yacy_search_server_tpu.search.searchevent import SearchEvent
    seg = Segment()
    for i in range(20):
        seg.store_document(Document(
            url=f"http://d.test/{i}", title=f"doc {i}",
            text=f"hybrid corpus document number {i} " * 3))
    q = QueryParams.parse("hybrid")
    q.hybrid = True
    ev = SearchEvent(q, seg)
    results = ev.results()
    assert results
    plain = SearchEvent(QueryParams.parse("hybrid"), seg).results()
    # one domain: hybrid score = sparse cardinal + bounded fixed boost
    from yacy_search_server_tpu.ops.dense import DENSE_BOOST_SCALE
    sparse_by_doc = {r.docid: r.score for r in plain}
    for r in results:
        if r.docid in sparse_by_doc:
            diff = abs(r.score - sparse_by_doc[r.docid])
            assert diff <= DENSE_BOOST_SCALE * q.hybrid_alpha + 1
    seg.close()


# -- persistent ErrorCache ----------------------------------------------


def test_errorcache_survives_restart(tmp_path):
    d = str(tmp_path / "ec")
    ec = ErrorCache(data_dir=d)
    ec.push(b"AAAAAAAAAAAA", "http://x.test/a", "bad status 404")
    ec.push(b"BBBBBBBBBBBB", "http://x.test/b", "parser: broken")
    ec.close()
    ec2 = ErrorCache(data_dir=d)
    assert len(ec2) == 2
    assert ec2.has(b"AAAAAAAAAAAA")
    assert ec2.reason(b"BBBBBBBBBBBB") == "parser: broken"
    ec2.close()


def test_errorcache_journal_compacts(tmp_path):
    d = str(tmp_path / "ec")
    ec = ErrorCache(max_entries=5, data_dir=d)
    for i in range(50):
        ec.push(f"H{i:011d}".encode(), f"http://x.test/{i}", "r")
    ec.close()
    ec2 = ErrorCache(max_entries=5, data_dir=d)
    assert len(ec2) == 5
    ec2.close()
    # the journal was rewritten to the retained entries, not 50 lines
    with open(os.path.join(d, "errors.jsonl")) as f:
        assert len(f.readlines()) == 5


# -- data-store migration -----------------------------------------------


def test_migrate_data_backfills_signatures(tmp_path):
    from yacy_search_server_tpu.document.document import Document
    from yacy_search_server_tpu.index.segment import Segment
    from yacy_search_server_tpu.migration import migrate_data

    seg = Segment(data_dir=str(tmp_path / "seg"))
    docid = seg.store_document(Document(
        url="http://m.test/", title="T", text="migration target text"))
    # simulate rows journaled by a pre-signature release
    seg.metadata.set_fields(docid, exact_signature_l=0, fuzzy_signature_l=0)

    store = str(tmp_path / "seg")
    touched = migrate_data(seg, store, "0.3.0")
    assert touched == 1
    row = seg.metadata.row(docid)
    assert row.get("exact_signature_l") > 0
    assert row.get("fuzzy_signature_l") > 0
    with open(os.path.join(store, "STORE_VERSION")) as f:
        assert f.read() == "0.3.0"
    # idempotent: second run touches nothing
    assert migrate_data(seg, store, "0.3.0") == 0
    seg.close()


def test_switchboard_runs_data_migration(tmp_path):
    from yacy_search_server_tpu.switchboard import Switchboard
    d = str(tmp_path / "DATA")
    sb = Switchboard(data_dir=d, transport=lambda u, h: (404, {}, b""))
    try:
        with open(os.path.join(d, "STORE_VERSION")) as f:
            assert f.read().strip() != ""
    finally:
        sb.close()


# -- async bounded logging ----------------------------------------------


def test_async_logging_writes_and_bounds(tmp_path):
    from yacy_search_server_tpu.utils import logging as ylog
    root = ylog.setup(str(tmp_path), level=logging.INFO, console=False)
    log = ylog.get("test.module")
    for i in range(100):
        log.info("message %d", i)
    ylog.shutdown()      # drains the queue
    path = tmp_path / "LOG" / "yacy.log"
    assert path.exists()
    content = path.read_text()
    assert "message 0" in content and "test.module" in content
    # handlers detached after shutdown-reconfigure cycle leaves no dupes
    root2 = ylog.setup(str(tmp_path), console=False)
    assert len(root2.handlers) == 1
    ylog.shutdown()


def test_migrate_data_backfills_url_protocol(tmp_path):
    from yacy_search_server_tpu.document.document import Document
    from yacy_search_server_tpu.index.segment import Segment
    from yacy_search_server_tpu.migration import migrate_data
    seg = Segment(data_dir=str(tmp_path / "p"))
    docid = seg.store_document(Document(
        url="https://p.test/x", title="T", text="protocol row"))
    seg.metadata.set_fields(docid, url_protocol_s="")   # pre-0.3.1 row
    migrate_data(seg, str(tmp_path / "p"), "0.3.1")
    assert seg.metadata.row(docid).get("url_protocol_s") == "https"
    # the facet index follows the backfill (protocol: filter works)
    assert docid in seg.metadata.facet_docids(
        "url_protocol_s", "https").tolist()
    seg.close()
