#!/usr/bin/env python
"""Headline benchmark — batched cardinal ranking + top-k over a 10M-posting
index block on device, vs a vectorized-numpy CPU baseline of the same math.

The measured path is the BASELINE.json north star: the replacement of the
reference's query-time RWI scorer (ReferenceOrder.normalizeWith +
cardinal + the SearchEvent rwiStack heap — reference:
source/net/yacy/search/ranking/ReferenceOrder.java:70-265,
source/net/yacy/search/query/SearchEvent.java:673-836) with one fused
device kernel: min/max stats -> normalize -> weighted sum -> top-k.

The CPU baseline is *vectorized numpy* — strictly faster than the
reference's per-row Java decode loop, so `vs_baseline` understates the
win over the actual reference implementation.

Prints ONE json line:
  {"metric": ..., "value": N, "unit": "queries/sec", "vs_baseline": N}
"""

import argparse
import json
import os
import sys
import time

import numpy as np


def np_cardinal_topk(feats, valid, hostids, prof, lang_pref, k, ranking, P):
    """CPU oracle: same math as the device kernel, vectorized numpy."""
    n = feats.shape[0]
    v = valid[:, None]
    col_min = np.where(v, feats, 2**31 - 1).min(axis=0)
    col_max = np.where(v, feats, -(2**31 - 1)).max(axis=0)
    span = col_max - col_min
    safe = np.maximum(span, 1)
    norm = ((feats - col_min[None, :]) * 256) // safe[None, :]
    norm = np.where(span[None, :] == 0, 0, norm)
    direct = ranking._NORM_DIRECT
    inv = np.where(span[None, :] == 0, 0, 256 - norm)
    contrib = np.where(direct[None, :], norm, inv)
    shifts = np.abs(prof.norm_coeffs())
    per_col = contrib << shifts[None, :]
    active = ~np.isin(np.arange(P.NF),
                      [P.F_FLAGS, P.F_DOCTYPE, P.F_LANGUAGE, P.F_DOMLENGTH])
    score = np.where(active[None, :], per_col, 0).sum(axis=1)
    score = score + ((256 - feats[:, P.F_DOMLENGTH]) << prof.domlength)
    tf = feats[:, P.F_HITCOUNT].astype(np.float32) / (
        feats[:, P.F_WORDS_IN_TEXT] + feats[:, P.F_WORDS_IN_TITLE] + 1)
    tf_min = np.where(valid, tf, np.inf).min()
    tf_max = np.where(valid, tf, -np.inf).max()
    tf_span = tf_max - tf_min
    tf_norm = (np.where(tf_span > 0, (tf - tf_min) * 256.0 /
                        max(tf_span, 1e-9), 0.0)).astype(np.int32)
    score = score + (tf_norm << prof.tf)
    score = score + np.where(feats[:, P.F_LANGUAGE] == lang_pref,
                             255 << prof.language, 0)
    bits, fshifts = prof.flag_coeffs()
    flag_hit = (feats[:, P.F_FLAGS, None] >> bits[None, :]) & 1
    score = score + (flag_hit * (255 << fshifts[None, :])).sum(axis=1)
    score = np.where(valid, score, -(2**31 - 1))
    idx = np.argpartition(-score, min(k, n - 1))[:k]
    idx = idx[np.argsort(-score[idx])]
    return score[idx], idx


def _emit(metric, value, unit, vs_baseline):
    print(json.dumps({"metric": metric, "value": round(value, 3),
                      "unit": unit, "vs_baseline": round(vs_baseline, 3)}))


def _synth_bm25_corpus(ndocs: int, terms: int = 3):
    """One shared synthetic corpus recipe so every BM25 config measures
    the same workload shape (tf, doclen, df)."""
    import numpy as np
    rng = np.random.default_rng(0)
    tf = rng.poisson(0.4, (ndocs, terms)).astype(np.float32)
    doclen = rng.integers(50, 3000, ndocs).astype(np.int32)
    df = np.maximum((tf > 0).sum(axis=0), 1).astype(np.int32)
    return tf, doclen, df


def _cpu_qps(fn, iters: int = 3) -> float:
    """Warmed multi-iteration CPU timing (one warmup, then `iters`)."""
    fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return iters / (time.perf_counter() - t0)


def _config1_bm25_cpu_baseline(k=10, ndocs=10_000, iters=20):
    """BASELINE config #1: 10k-doc corpus, BM25 top-10, CPU numpy — the
    single-peer baseline every device config is compared against."""
    import numpy as np
    from yacy_search_server_tpu.ops import ranking
    tf, doclen, df = _synth_bm25_corpus(ndocs)

    def one():
        s = ranking.bm25_scores_np(tf, doclen, df, ndocs)
        idx = np.argpartition(-s, k)[:k]
        return idx[np.argsort(-s[idx])]

    qps = _cpu_qps(one, iters)
    _emit(f"bm25_top{k}_qps_{ndocs // 1000}k_docs_cpu", qps,
          "queries/sec", 1.0)


def _config2_bm25_tpu(k=100, ndocs=1_000_000, iters=20):
    """Config #2: 1M-doc BM25 top-100 on one TPU core vs the same-size
    numpy baseline."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from yacy_search_server_tpu.ops import ranking
    tf, doclen, df = _synth_bm25_corpus(ndocs)

    def cpu_one():     # same work as the device path: score + top-k
        s = ranking.bm25_scores_np(tf, doclen, df, ndocs)
        idx = np.argpartition(-s, k)[:k]
        return idx[np.argsort(-s[idx])]

    cpu_qps = _cpu_qps(cpu_one)
    dev = jax.devices()[0]
    args = [jax.device_put(x, dev) for x in
            (tf, doclen, df)] + [jnp.int32(ndocs),
                                 jax.device_put(np.ones(ndocs, bool), dev),
                                 jax.device_put(
                                     np.arange(ndocs, dtype=np.int32), dev)]
    out = ranking.bm25_topk(*args, k)
    np.asarray(out[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = ranking.bm25_topk(*args, k)
    np.asarray(out[0])
    qps = iters / (time.perf_counter() - t0)
    _emit(f"bm25_top{k}_qps_1M_docs_tpu", qps, "queries/sec", qps / cpu_qps)


def _config4_p2p_fusion(peers=16, iters=10):
    """Config #4: 16 simulated DHT peers, query fan-out + result fusion."""
    import tempfile
    from yacy_search_server_tpu.document.document import Document
    from yacy_search_server_tpu.peers.node import P2PNode
    from yacy_search_server_tpu.peers.transport import LoopbackNetwork
    net = LoopbackNetwork()
    with tempfile.TemporaryDirectory() as tmp:
        nodes = [P2PNode(f"bench{i}", net, data_dir=f"{tmp}/n{i}")
                 for i in range(peers)]
        seeds = [n.seed for n in nodes]
        for n in nodes:
            n.bootstrap(seeds)
            n.ping()
        for i, n in enumerate(nodes):
            for j in range(20):
                n.sb.index.store_document(Document(
                    url=f"http://p{i}.test/d{j}.html", title=f"doc {i}-{j}",
                    text=f"fusionword shared corpus {i} {j}"))
        t0 = time.perf_counter()
        got = 0
        for _ in range(iters):
            ev = nodes[0].search("fusionword", count=10, timeout_s=10.0)
            got = len(ev.results())
            nodes[0].sb.search_cache.clear()
        qps = iters / (time.perf_counter() - t0)
        for n in nodes:
            n.close()
        # no CPU twin of the full P2P fan-out exists: vs_baseline is
        # undefined (0.0), the page-fill `got` is asserted, not reported
        assert got == 10, f"fusion underfilled: {got}"
        _emit(f"p2p_fusion_qps_{peers}peers", qps, "queries/sec", 0.0)


def _config5_hybrid(k=100, ndocs=100_000, iters=20):
    """Config #5: BM25-style sparse first stage + dense rerank blend."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from yacy_search_server_tpu.ops import dense
    rng = np.random.default_rng(0)
    dim = 256
    doc_vecs = rng.standard_normal((ndocs, dim)).astype(np.float32)
    doc_vecs /= np.linalg.norm(doc_vecs, axis=1, keepdims=True)
    qvec = doc_vecs[17] + 0.1 * rng.standard_normal(dim).astype(np.float32)
    sparse = rng.integers(0, 10**6, ndocs).astype(np.float32)
    valid = np.ones(ndocs, bool)

    def cpu_one():
        # same work as the device path: cosine + blend + PARTIAL top-k
        # (the oracle's full argsort would unfairly slow the baseline)
        sims = doc_vecs @ qvec
        smin, smax = sparse.min(), sparse.max()
        final = (1 - 0.5) * ((sparse - smin) / max(smax - smin, 1e-6)) \
            + 0.5 * sims
        idx = np.argpartition(-final, k)[:k]
        return idx[np.argsort(-final[idx])]

    cpu_qps = _cpu_qps(cpu_one)
    dev = jax.devices()[0]
    a = [jax.device_put(x, dev) for x in (qvec, doc_vecs, sparse, valid)]
    out = dense.hybrid_rerank_topk(*a, jnp.float32(0.5), k)
    np.asarray(out[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = dense.hybrid_rerank_topk(*a, jnp.float32(0.5), k)
    np.asarray(out[0])
    qps = iters / (time.perf_counter() - t0)
    _emit(f"hybrid_rerank_top{k}_qps_{ndocs // 1000}k_docs", qps,
          "queries/sec", qps / cpu_qps)

    # batched rerank (VERDICT r4 #5): B concurrent queries share one
    # (B,dim)x(dim,N) MXU matmul — the serving shape under load (the
    # batcher already groups concurrent searches into one dispatch)
    B = 16
    qvecs = doc_vecs[rng.integers(0, ndocs, B)] \
        + 0.1 * rng.standard_normal((B, dim)).astype(np.float32)
    sparse_b = rng.integers(0, 10**6, (B, ndocs)).astype(np.float32)
    valid_b = np.ones((B, ndocs), bool)
    ab = [jax.device_put(x, dev)
          for x in (qvecs, doc_vecs, sparse_b, valid_b)]
    out = dense.hybrid_rerank_topk_batch(*ab, jnp.float32(0.5), k)
    np.asarray(out[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = dense.hybrid_rerank_topk_batch(*ab, jnp.float32(0.5), k)
    np.asarray(out[0])
    bqps = iters * B / (time.perf_counter() - t0)
    _emit(f"hybrid_rerank_top{k}_qps_{ndocs // 1000}k_docs_batch{B}",
          bqps, "queries/sec", bqps / cpu_qps)


def _build_served_switchboard(n: int, n_terms: int = 8, hosts: int = 4096,
                              mesh: str = "auto", batch_size: int | None = None,
                              config_extra: dict | None = None):
    """A Switchboard whose index holds `n_terms` hot terms with `n`
    postings each, plus real metadata rows for every doc — the served-path
    workload (distinct query strings so the event cache never aliases).
    `mesh`: the index.device.mesh mode — "off" pins the single-device
    store, "on" forces the mesh-sharded store, "auto" is the product
    default (mesh when >1 device)."""
    import numpy as np
    from yacy_search_server_tpu.index import postings as P
    from yacy_search_server_tpu.index.postings import PostingsList
    from yacy_search_server_tpu.switchboard import Switchboard
    from yacy_search_server_tpu.utils.config import Config

    from yacy_search_server_tpu.utils.hashes import word2hash

    cfg = Config()
    cfg.set("index.device.mesh", mesh)
    if batch_size is not None:
        cfg.set("index.device.batchSize", str(batch_size))
    for _k, _v in (config_extra or {}).items():
        cfg.set(_k, _v)
    # the PRODUCT store topology: disk-backed metadata (mmap segments).
    # A RAM-only tail at 10M docs means 30M+ live Python strings, and a
    # major-GC pass over that heap holds the GIL for SECONDS — the last
    # r3-class stall source (uniform ~7 s latency clusters, waiters'
    # 1 s timeouts unable to even expire). The product serves from mmap
    # segments, so the bench must too.
    import atexit
    import shutil
    import tempfile
    data_dir = tempfile.mkdtemp(prefix="yacytpu-bench-")
    atexit.register(shutil.rmtree, data_dir, ignore_errors=True)
    sb = Switchboard(data_dir=data_dir, config=cfg)
    rng = np.random.default_rng(0)
    # synthetic 12-char urlhashes: positional layout (6:12 = host part)
    # with `hosts` distinct hosts so host-diversity drain has real work
    sb.index.metadata.bulk_load(
        [(f"{i:06d}h{i % hosts:05d}").encode("ascii") for i in range(n)],
        sku=[f"http://h{i % hosts}.example/d{i}.html" for i in range(n)],
        title=[f"doc {i}" for i in range(n)],
        host_s=[f"h{i % hosts}.example" for i in range(n)],
        size_i=[1000] * n, wordcount_i=[100] * n)
    # freeze the tail into mmap segments: reads page in from disk, the
    # Python-object heap stays small, and major GC stays sub-ms
    sb.index.metadata.snapshot()
    docids = np.arange(n, dtype=np.int32)
    for t in range(n_terms):
        feats = rng.integers(0, 1000, (n, P.NF)).astype(np.int32)
        feats[:, P.F_FLAGS] = rng.integers(0, 2**20, n)
        feats[:, P.F_DOMLENGTH] = rng.integers(0, 256, n)
        feats[:, P.F_LANGUAGE] = P.pack_language("en")
        sb.index.rwi.ingest_run({word2hash(f"benchterm{t}"):
                                 PostingsList(docids, feats)})
    # a deployment that can warm at startup should (and the bench must):
    # a background kernel compile landing mid-traffic stalls the wave
    # that needs it — the r3 stall's third ingredient
    pw = getattr(sb.index.devstore, "prewarm_wait", None)
    if pw is not None:
        pw(timeout=900.0)
    return sb


def _served_qps(sb, k=10, threads=32, per_thread=4, n_terms=8,
                latencies=None, duration_s: float = 0.0,
                skip_warm: bool = False, hybrid: bool = False):
    """Aggregate q/s of `threads` searcher threads through
    Switchboard.search(); counts only device-ranked queries. When
    `latencies` is a list, per-query BATCHED-WINDOW latencies are
    appended — the p50 the north star is stated in, falsifiable on
    locally-attached hardware (VERDICT r2 weak #4). With `duration_s`
    set, workers loop until the deadline instead of a fixed per-thread
    count — the SOAK protocol (VERDICT r4 #2: a sub-second window
    cannot demonstrate stall-proofness; the r3 stall class emerged
    under sustained load)."""
    import gc
    import threading
    import time
    if not skip_warm:
        for t in range(n_terms):              # warm every term's extents
            ev = sb.search(f"benchterm{t}", count=k, hybrid=hybrid)
            assert len(ev.results()) == k
        sb.search_cache.clear()
        # the build's garbage is history: collect once, then move
        # survivors to the permanent generation so no major-GC pass (a
        # GIL hold that freezes every searcher AND dispatcher thread)
        # lands mid-run — the CPython equivalent of the reference's
        # young-gen tuning
        gc.collect()
        gc.freeze()
    served0 = sb.index.devstore.queries_served
    deadline = time.perf_counter() + duration_s if duration_s else None
    done = [0] * threads

    def worker(t):
        i = 0
        while True:
            sb.search_cache.clear()
            q0 = time.perf_counter()
            # use_cache=False: every measured query must RANK (the
            # rank-path cache hits still count as ranked). With the
            # event cache consulted, a clear/insert race between
            # searcher threads served a few queries from a neighbor's
            # just-created EVENT — invisible before the result cache
            # made event creation sub-ms, and a coverage false-negative
            # for the ranked >= total assertion below
            ev = sb.search(f"benchterm{t % n_terms}", count=k,
                           hybrid=hybrid, use_cache=False)
            assert len(ev.results()) == k
            if latencies is not None:
                latencies.append(time.perf_counter() - q0)
            i += 1
            done[t] = i
            if deadline is None:
                if i >= per_thread:
                    return
            elif time.perf_counter() >= deadline:
                return

    ts = [threading.Thread(target=worker, args=(t,)) for t in range(threads)]
    t0 = time.perf_counter()
    for th in ts:
        th.start()
    for th in ts:
        th.join()
    dt = time.perf_counter() - t0
    total = sum(done)
    ranked = sb.index.devstore.queries_served - served0
    # 100% device coverage: a headline where ANY query silently took the
    # host path would overstate nothing but hide a serving defect
    # (VERDICT r3 weak #3)
    assert ranked >= total, \
        f"only {ranked}/{total} queries were device-ranked"
    return ranked / dt


def _config6_served_path(k=10, ndocs=1_000_000, threads=16):
    """Config #6: q/s THROUGH Switchboard.search() at 1M postings —
    query parse, batched device rank over placed blocks, metadata join,
    host-diversity drain, result page (the no-arg headline runs this same
    protocol at 10M; this config is the quick 1M point).

    Concurrent throughput (`threads` searcher threads) is how the threaded
    HTTP server actually runs: single-stream latency is floored by the
    device round trip while concurrent dispatches batch and pipeline."""
    sb = _build_served_switchboard(ndocs, n_terms=8, mesh="off")
    assert sb.index.devstore is not None, "device serving must be on"
    qps = _served_qps(sb, k=k, threads=threads, per_thread=5, n_terms=8)
    _emit(f"served_search_top{k}_qps_{ndocs // 1_000_000}M_postings"
          f"_x{threads}", qps, "queries/sec", 0.0)


def _config13_modifier_mix(k=10, ndocs=1_000_000, threads=32):
    """Config #13: BLENDED throughput of a modifier-heavy mix (VERDICT
    r3 #5) — 50% of queries carry operators. Device-eligible shapes
    (/language/, daterange:, 2-term conjunctions) rank on device;
    site:/filetype: need metadata columns and take the host path by
    design (devstore docstring). The emitted metrics report the blend
    AND the measured device fraction, so the product's real mixed-load
    number is on the record, not just the plain-query headline."""
    import threading as _th
    import time as _t
    sb = _build_served_switchboard(ndocs, n_terms=8, hosts=256, mesh="off")
    assert sb.index.devstore is not None
    shapes = [
        "benchterm{t}",                               # plain (device)
        "benchterm{t}",                               # plain (device)
        "benchterm{t} /language/en",                  # device (kernel filter)
        "daterange:1970-01-02..1972-09-27 benchterm{t}",  # device
        "site:h7.example benchterm{t}",               # host (metadata join)
        "filetype:html benchterm{t}",                 # host
        "benchterm{t} benchterm{u}",                  # device conjunction
        "benchterm{t} -nosuchword",                   # device join shape
    ]
    # warm TWICE with a prewarm wait in between: the first pass compiles
    # the cold paths and populates caches (facet bitmaps, filtered
    # stats); the wait covers the background prewarm those caches
    # re-keyed; the second pass rides the cache-hit paths so ANY compile
    # the best-effort prewarm missed (a refused shape is skipped and
    # counted) lands in warmup, never mid-measurement — a deployment
    # warms through its caches before taking traffic
    for rnd in range(2):
        for i, s in enumerate(shapes):
            sb.search_cache.clear()
            sb.search(s.format(t=i % 8, u=(i + 1) % 8), count=k).results()
        if rnd == 0:
            sb.index.devstore.prewarm_wait(timeout=900.0)
            sb.index.devstore.join_prewarm_wait()
    sb.search_cache.clear()
    served0 = sb.index.devstore.queries_served
    join0 = sb.index.devstore.join_served
    done = [0]
    lk = _th.Lock()

    def worker(tid):
        for j in range(6):
            sb.search_cache.clear()
            s = shapes[(tid + j) % len(shapes)]
            ev = sb.search(s.format(t=tid % 8, u=(tid + 1) % 8), count=k)
            ev.results()
            with lk:
                done[0] += 1

    ts = [_th.Thread(target=worker, args=(i,)) for i in range(threads)]
    t0 = _t.perf_counter()
    for th in ts:
        th.start()
    for th in ts:
        th.join()
    dt = _t.perf_counter() - t0
    total = done[0]
    dev = sb.index.devstore.queries_served - served0
    _emit(f"modifier_mix_qps_{ndocs // 1_000_000}M_x{threads}",
          total / dt, "queries/sec", 0.0)
    _emit("modifier_mix_device_fraction", dev / max(total, 1),
          "fraction", 0.0)
    _emit("modifier_mix_device_joins",
          sb.index.devstore.join_served - join0, "queries", 0.0)


def _config10_mesh_served(k=10, ndocs=1_000_000, threads=16):
    """Config #10: the SERVED path over the MESH-SHARDED arena (VERDICT
    r2 #1) — Switchboard.search() end-to-end with every query one SPMD
    program over all available devices (8-way on the virtual CPU mesh /
    a v5e-8; degenerates to 1 cell on a single chip). Same protocol as
    config 6, so the two numbers are directly comparable."""
    import jax
    ndev = len(jax.devices())
    sb = _build_served_switchboard(ndocs, n_terms=8, mesh="on")
    from yacy_search_server_tpu.index.meshstore import MeshSegmentStore
    assert isinstance(sb.index.devstore, MeshSegmentStore)
    qps = _served_qps(sb, k=k, threads=threads, per_thread=5, n_terms=8)
    _emit(f"mesh_served_search_top{k}_qps_{ndocs // 1_000_000}M"
          f"_x{ndev}dev", qps, "queries/sec", 0.0)


def _config3_sharded(k=100, iters=10):
    """Config #3: doc-sharded BM25 under shard_map over every available
    device (8-way on a v5e-8 / the CPU test mesh; degenerates gracefully
    on one chip). With JAX_PLATFORMS=cpu +
    --xla_force_host_platform_device_count=N the run uses the virtual
    N-device CPU mesh."""
    import jax
    import numpy as np
    from yacy_search_server_tpu.parallel import mesh as M
    ndev = len(jax.devices())
    mesh = M.make_mesh(n_doc=ndev)
    fn = M.build_sharded_bm25(mesh, k=k)
    ndocs = M.pad_to_shards(1_000_000, ndev)
    tf, doclen, df = _synth_bm25_corpus(ndocs)
    valid = np.ones(ndocs, bool)
    docids = np.arange(ndocs, dtype=np.int32)
    out = fn(tf, doclen, df, np.int32(ndocs), valid, docids)
    np.asarray(out[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(tf, doclen, df, np.int32(ndocs), valid, docids)
    np.asarray(out[0])
    qps = iters / (time.perf_counter() - t0)
    # vs_baseline is a speedup ratio everywhere: no single-way twin is
    # measured here, so it is reported as undefined (0.0); the way-count
    # is in the metric name
    _emit(f"bm25_sharded_{ndev}way_qps_1M_docs", qps, "queries/sec", 0.0)


def _config8_device_join(iters=10):
    """Config #8: multi-term conjunction served from placed device spans
    (M44) vs the host join+rank path, 1M x 300k postings with an 80k
    exclusion term."""
    import numpy as np
    from yacy_search_server_tpu.index.segment import Segment
    from yacy_search_server_tpu.index.postings import PostingsList
    from yacy_search_server_tpu.index import postings as P
    from yacy_search_server_tpu.ops.ranking import (CardinalRanker,
                                                    RankingProfile)
    from yacy_search_server_tpu.utils.hashes import word2hash
    seg = Segment(max_ram_postings=10**9)
    rng = np.random.default_rng(0)

    def plist(n, pool):
        docids = np.sort(rng.choice(pool, n, replace=False)).astype(np.int32)
        feats = np.zeros((n, P.NF), np.int32)
        feats[:, P.F_HITCOUNT] = rng.integers(1, 50, n)
        feats[:, P.F_WORDS_IN_TEXT] = rng.integers(50, 3000, n)
        feats[:, P.F_LASTMOD] = rng.integers(18000, 21000, n)
        feats[:, P.F_POSINTEXT] = rng.integers(1, 4000, n)
        return PostingsList(docids, feats)

    pool = np.arange(3_000_000)
    inc = [word2hash("alpha"), word2hash("beta")]
    exc = [word2hash("gamma")]
    seg.rwi.ingest_run({inc[0]: plist(1_000_000, pool),
                        inc[1]: plist(300_000, pool),
                        exc[0]: plist(80_000, pool)})
    prof = RankingProfile()

    # host twin: join + rank (the pre-M44 serving path)
    t0 = time.perf_counter()
    for _ in range(3):
        joined = seg.term_search(include_hashes=inc, exclude_hashes=exc)
        CardinalRanker(prof).rank(joined, k=100)
    host_s = (time.perf_counter() - t0) / 3

    ds = seg.enable_device_serving()
    out = ds.rank_join(inc, exc, prof, "en", k=100)
    assert out is not None
    t0 = time.perf_counter()
    for _ in range(iters):
        ds.rank_join(inc, exc, prof, "en", k=100)
    dev_s = (time.perf_counter() - t0) / iters
    _emit("device_join_qps_1Mx300k", 1.0 / dev_s, "queries/sec",
          host_s / dev_s)

    # concurrent joins through the batcher (VERDICT r2 weak #2): 16
    # threads sharing lax.map dispatches; coverage counters prove the
    # device served them (served vs fallback in a mixed load)
    import threading as _th
    ds.enable_batching()
    # one query under batching triggers the join-family prewarm (buckets
    # 1/4/16); wait it out like a deployment warming before traffic —
    # a compile landing mid-round convoys the watchdog
    ds.rank_join(inc, exc, prof, "en", k=100)
    ds.join_prewarm_wait()
    threads, per_thread = 16, 4

    def worker():
        for _ in range(per_thread):
            ds.rank_join(inc, exc, prof, "en", k=100)

    def run_round():
        ts = [_th.Thread(target=worker) for _ in range(threads)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return time.perf_counter() - t0

    run_round()      # warm the batch-bucket compile shapes (twice: the
    run_round()      # buckets formed depend on queue-drain timing)
    served0, fb0 = ds.join_served, ds.join_fallbacks
    dt = run_round()
    served = ds.join_served - served0
    fellback = ds.join_fallbacks - fb0
    seg.close()
    _emit(f"device_join_qps_1Mx300k_x{threads}thr",
          served / dt, "queries/sec", (served / dt) * dev_s)
    _emit(f"device_join_coverage_x{threads}thr",
          served / max(served + fellback, 1), "served/total", 1.0)


def _mp_bench_client(port, n_terms, n_queries, out_q, go):
    """Client PROCESS for config 12: sequential keep-alive requests (the
    measuring side must not be GIL-bound, or it measures itself). `go`
    barrier-synchronizes all clients so their loops overlap — process
    startup skew must not serialize the load."""
    import http.client
    import json as _json
    import time as _t
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request("GET", "/yacysearch.json?query=benchterm0")
    conn.getresponse().read()          # connection + worker warm
    go.wait()
    ok = 0
    t0 = _t.perf_counter()
    try:
        for i in range(n_queries):
            conn.request("GET", f"/yacysearch.json?query=benchterm"
                                f"{i % n_terms}")
            r = conn.getresponse()
            body = r.read()
            items = _json.loads(body)["channels"][0]["items"]
            assert items, "empty page"
            ok += 1
    finally:
        # ALWAYS report — a dying client must not stall measure() in
        # out_q.get for its full timeout with orphaned processes behind
        out_q.put((ok, _t.perf_counter() - t0))
        conn.close()


def _config12_multiproc(ndocs=1_000_000, queries=4000, client_procs=8):
    """Config #12: multi-process serving (VERDICT r2 weak #5) — 1 worker
    vs 4 worker processes behind one SO_REUSEPORT port, all device
    ranking through the owner's arena over the rank-service socket.
    vs_baseline on the 4-worker line is the scaling over 1 worker."""
    import json as _json
    import multiprocessing
    import os
    import socket as _socket
    import tempfile
    import urllib.request

    import numpy as np
    from yacy_search_server_tpu.index import postings as P
    from yacy_search_server_tpu.index.postings import PostingsList
    from yacy_search_server_tpu.server.rankservice import (
        RankServiceServer, spawn_worker)
    from yacy_search_server_tpu.switchboard import Switchboard
    from yacy_search_server_tpu.utils.config import Config
    from yacy_search_server_tpu.utils.hashes import word2hash

    tmp = tempfile.mkdtemp()
    cfg = Config()
    cfg.set("index.device.mesh", "off")
    sb = Switchboard(data_dir=f"{tmp}/DATA", config=cfg,
                     transport=lambda u, h: (404, {}, b""))
    rng = np.random.default_rng(0)
    n_terms, hosts = 8, 4096
    sb.index.metadata.bulk_load(
        [(f"{i:06d}h{i % hosts:05d}").encode("ascii")
         for i in range(ndocs)],
        sku=[f"http://h{i % hosts}.example/d{i}.html" for i in range(ndocs)],
        title=[f"doc {i}" for i in range(ndocs)],
        host_s=[f"h{i % hosts}.example" for i in range(ndocs)],
        size_i=[1000] * ndocs, wordcount_i=[100] * ndocs)
    docids = np.arange(ndocs, dtype=np.int32)
    for t in range(n_terms):
        feats = rng.integers(0, 1000, (ndocs, P.NF)).astype(np.int32)
        feats[:, P.F_FLAGS] = rng.integers(0, 2**20, ndocs)
        feats[:, P.F_LANGUAGE] = P.pack_language("en")
        sb.index.rwi.ingest_run({word2hash(f"benchterm{t}"):
                                 PostingsList(docids, feats)})
    sb.index.metadata.snapshot()
    sb.index.devstore.enable_batching()
    sock = f"{tmp}/rank.sock"
    server = RankServiceServer(sb.index.devstore, sock,
                               state_fn=sb.actuators.serving_state)
    ctx = multiprocessing.get_context("spawn")

    def measure(n_workers: int) -> float:
        probe = _socket.socket()
        probe.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEPORT, 1)
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
        probe.close()
        stop = ctx.Event()
        procs = []
        for _ in range(n_workers):
            ready = ctx.Event()
            p = spawn_worker(ctx, f"{tmp}/DATA", sock, port,
                             ready=ready, stop=stop)
            procs.append((p, ready))
        for p, ready in procs:
            assert ready.wait(timeout=180), "worker failed to start"

        # warm: every term's event on every worker (device rank through
        # the owner happens here; the measured load is the host-bound
        # cached-page path whose GIL ceiling this config breaks)
        for i in range(n_terms * 2 * n_workers):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/yacysearch.json"
                    f"?query=benchterm{i % n_terms}", timeout=120) as r:
                assert _json.loads(
                    r.read())["channels"][0]["items"], "empty page"
        # measuring side runs as PROCESSES too (a threaded python client
        # is itself GIL-bound and would measure itself)
        out_q = ctx.Queue()
        go = ctx.Event()
        clients = [ctx.Process(target=_mp_bench_client,
                               args=(port, n_terms,
                                     queries // client_procs, out_q, go),
                               daemon=True)
                   for _ in range(client_procs)]
        for c in clients:
            c.start()
        time.sleep(8)      # all clients connected + warmed
        go.set()
        try:
            total_ok, dts = 0, []
            for _ in clients:
                ok, dt = out_q.get(timeout=600)
                total_ok += ok
                dts.append(dt)
        finally:
            for c in clients:
                c.join(timeout=20)
                if c.is_alive():
                    c.terminate()
            stop.set()
            for p, _ in procs:
                p.join(timeout=20)
                if p.is_alive():
                    p.terminate()
        # each client times its own request loop: process-spawn startup
        # must not count against the server
        return total_ok / max(dts)

    try:
        one = measure(1)
        four = measure(4)
    finally:
        server.close()
        sb.close()
    # scaling is bounded by PHYSICAL CORES: on a 1-core host the workers
    # time-slice and the ratio stays ~1.0 by construction — the cores
    # count rides in the metric name so the number reads honestly
    cores = os.cpu_count() or 1
    _emit(f"multiproc_served_qps_{ndocs // 1_000_000}M_x1worker"
          f"_{cores}cores", one, "queries/sec", 1.0)
    _emit(f"multiproc_served_qps_{ndocs // 1_000_000}M_x4workers"
          f"_{cores}cores", four, "queries/sec", four / max(one, 1e-9))


def _config11_metadata_startup(ndocs=1_000_000):
    """Config #11: metadata-store restart time at 1M docs (VERDICT r2 #2
    'Done' criterion). Builds a snapshotted segmented store, then times a
    cold open — which loads the manifest + segment headers and replays
    only the journal tail, NOT the 1M-row history. vs_baseline compares
    against the round-2 behavior (full jsonl replay), measured on a 20k
    sample and scaled linearly (the replay was strictly O(rows))."""
    import tempfile
    import time as _t

    from yacy_search_server_tpu.index.metadata import (MetadataStore,
                                                       metadata_from_parsed)
    with tempfile.TemporaryDirectory() as tmp:
        d = f"{tmp}/meta"
        st = MetadataStore(d)
        hashes = [f"{i:07d}hash0".encode()[:12].ljust(12, b"0")
                  for i in range(ndocs)]
        st.bulk_load(
            hashes,
            sku=[f"http://h{i % 4096}.example/d{i}.html" for i in range(ndocs)],
            title=[f"doc {i}" for i in range(ndocs)],
            text_t=[f"body text of document {i}" for i in range(ndocs)],
            host_s=[f"h{i % 4096}.example" for i in range(ndocs)],
            size_i=[1000] * ndocs, wordcount_i=[100] * ndocs)
        st.snapshot()
        st.close()
        t0 = _t.perf_counter()
        st2 = MetadataStore(d)
        assert st2.capacity() == ndocs
        assert st2.text_value(ndocs // 2, "title") == f"doc {ndocs // 2}"
        dt = _t.perf_counter() - t0

        # round-2 twin: time a 20k-row journal replay, scale to ndocs
        sample = 20_000
        d2 = f"{tmp}/legacy"
        import json as _json
        import os as _os
        _os.makedirs(d2)
        with open(f"{d2}/metadata.jsonl", "w") as f:
            for i in range(sample):
                doc = metadata_from_parsed(
                    hashes[i], f"http://h{i % 97}.example/d{i}.html",
                    f"doc {i}", f"body text of document {i}")
                rec = {"_id": doc.urlhash.decode()}
                rec.update(doc.fields)
                f.write(_json.dumps(rec) + "\n")
        t0 = _t.perf_counter()
        legacy = MetadataStore(d2)
        replay_s = (_t.perf_counter() - t0) * (ndocs / sample)
        legacy.close()
        st2.close()
    _emit(f"metadata_startup_s_{ndocs // 1_000_000}M_docs", dt, "seconds",
          replay_s / max(dt, 1e-9))


def _config9_indexing(ndocs=2000):
    """Config #9: indexing write-path throughput — parse + condense +
    store_document (RWI append, metadata, citations, webgraph, dense
    vector) for realistic small HTML pages, docs/sec."""
    import tempfile

    from yacy_search_server_tpu.document.parser.registry import parse_source
    from yacy_search_server_tpu.index.segment import Segment

    pages = []
    for i in range(ndocs):
        body = " ".join(f"word{(i * 37 + j) % 5000}" for j in range(150))
        pages.append((
            f"http://h{i % 97}.bench/p{i}.html",
            (f"<html><head><title>Page {i}</title></head><body>"
             f"<h1>Heading {i}</h1><p>{body}</p>"
             f"<a href='/p{(i + 1) % ndocs}.html'>next</a>"
             f"<a href='http://ext{i % 13}.bench/'>out</a>"
             f"</body></html>").encode()))
    with tempfile.TemporaryDirectory() as tmp:
        seg = Segment(data_dir=f"{tmp}/seg")
        t0 = time.perf_counter()
        for url, html in pages:
            doc = parse_source(url, "text/html", html)[0]
            seg.store_document(doc, crawldepth=1)
        dt = time.perf_counter() - t0
        seg.close()
    dps = ndocs / dt
    # reference anchor: default remote-crawl budget is 60 pages/minute
    # (Switchboard.java:1271) = 1 doc/sec
    _emit("indexing_docs_per_sec", dps, "docs/sec", dps / 1.0)


def _roofline_mode(n: int, k: int = 16):
    """--roofline: silicon accounting over every registered kernel
    (ISSUE 1). Each kernel in ops/roofline.KERNELS is dispatched
    directly against an `n`-row synthetic arena (min-of-3 warm timing),
    paired with its analytical cost model, and emitted as one JSON line
    carrying analytical FLOPs/bytes, achieved FLOP/s and GB/s, util_pct
    vs the configured device peak, and the compute-/memory-bound
    verdict. A summary line carries the per-query p50/p95 util_pct the
    rank-service counters also report. The human-readable
    achieved-vs-peak table goes to stderr (BASELINE/README form)."""
    import jax
    import jax.numpy as jnp

    from yacy_search_server_tpu.index import devstore as DS
    from yacy_search_server_tpu.index import postings as P
    from yacy_search_server_tpu.ops import blockrank as B
    from yacy_search_server_tpu.ops import dense as DN
    from yacy_search_server_tpu.ops import ranking as R
    from yacy_search_server_tpu.ops import roofline as RF
    from yacy_search_server_tpu.ops import streaming as S
    from yacy_search_server_tpu.utils.profiler import PROFILER

    peak = RF.device_peak()
    PROFILER.set_peak(peak)
    PROFILER.clear()
    rng = np.random.default_rng(0)
    TILE = DS.TILE
    rows = max(TILE, ((n + TILE - 1) // TILE) * TILE)
    cap = rows + TILE                     # spare tile (arena contract)
    feats = rng.integers(0, 1000, (cap, P.NF), dtype=np.int32)
    feats[:, P.F_FLAGS] = rng.integers(0, 2 ** 20, cap, dtype=np.int32)
    feats[:, P.F_DOMLENGTH] = rng.integers(0, 256, cap, dtype=np.int32)
    feats[:, P.F_LANGUAGE] = P.pack_language("en")
    f16_np, fl_np = R.compact_feats(feats)
    dev = jax.devices()[0]
    put = lambda a: jax.device_put(a, dev)   # noqa: E731
    f16, fl = put(f16_np), put(fl_np)
    dd = put(np.arange(cap, dtype=np.int32))
    valid = put(np.ones(cap, bool))
    hostids = put(np.zeros(cap, np.int32))
    doc_cap = 1 << 16
    dead = put(np.zeros(doc_cap, bool))
    n_tiles = rows // TILE
    tcap = max(1 << 12, n_tiles)
    pmax = put(np.full(tcap, 2 ** 31 - 1, np.int32))
    jcap = 1 << max(17, (rows - 1).bit_length())
    jd_np = np.full(jcap, 2 ** 31 - 1, np.int32)
    jd_np[:rows] = np.arange(rows, dtype=np.int32)
    jd, jp = put(jd_np), put(np.zeros(jcap, np.int32))
    nwords = 1 << 15
    bmtab = put(np.zeros((2, nwords, 2), np.int32))
    prof = R.RankingProfile()
    bits, shifts = prof.flag_coeffs()
    consts = (put(prof.norm_coeffs()), put(bits), put(shifts),
              put(np.int32(prof.domlength)), put(np.int32(prof.tf)),
              put(np.int32(prof.language)), put(np.int32(prof.authority)),
              put(np.int32(P.pack_language("en"))))

    def timed(name, call, queries=1, **shape):
        jax.block_until_ready(call())          # compile + warm
        wall = min(_t_one(call) for _ in range(3))
        PROFILER.record(name, wall, queries=queries, **shape)

    def _t_one(call):
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        return time.perf_counter() - t0

    # block scorer kernels over the full n-row block
    cj = jax.jit(lambda *a: R.cardinal_scores16(*a, with_authority=False))
    timed("cardinal_scores16",
          lambda: cj(f16, fl, valid, hostids, None, *consts), n=cap)
    timed("score_topk16",
          lambda: R.score_topk16(f16, fl, dd, valid, hostids, *consts,
                                 k=k, with_authority=False), n=cap, k=k)
    timed("score_topk16_packed",
          lambda: R.score_topk16_packed(f16, fl, dd, valid, hostids,
                                        *consts, k=k,
                                        with_authority=False), n=cap, k=k)
    f32 = put(feats)
    timed("score_topk",
          lambda: R.score_topk(f32, dd, valid, hostids, *consts, k=k),
          n=cap, k=k)
    del f32
    tile = min(1 << 20, rows)
    timed("scan_score_topk",
          lambda: S.scan_score_topk(
              f16, fl, dd, valid, hostids,
              {"col_min": put(f16_np.astype(np.int32).min(0)),
               "col_max": put(f16_np.astype(np.int32).max(0)),
               "tf_min": np.float32(0), "tf_max": np.float32(1),
               "host_counts": put(np.zeros(1, np.int32))},
              *consts, k=k, tile=tile), n=cap, k=k, tile=tile)
    def _stream_once():
        S.stream_score_topk(f16_np, fl_np,
                            np.arange(cap, dtype=np.int32),
                            np.zeros(cap, np.int32),
                            consts[:7], consts[7], k=100)
        return 0.0
    _stream_once()                      # compile the chunk shapes
    PROFILER.record("stream_score_topk",
                    min(_t_one(_stream_once) for _ in range(3)),
                    queries=1, n=cap, k=100)

    # BM25 + the dense rerank family (config-5 candidate-set sizes)
    t = 3
    timed("bm25_topk",
          lambda: R.bm25_topk(
              jnp.asarray(rng.integers(0, 8, (cap, t)).astype(np.float32)),
              dd, jnp.ones(t, jnp.int32), jnp.int32(cap), valid, dd, k=k),
          n=cap, t=t, k=k)
    nd = min(cap, 131072)
    dv = put(rng.standard_normal((nd, DN.DIM)).astype(np.float32))
    sp = put(rng.integers(0, 1 << 20, nd).astype(np.float32))
    vd = put(np.ones(nd, bool))
    qv = put(rng.standard_normal(DN.DIM).astype(np.float32))
    timed("hybrid_rerank_topk",
          lambda: DN.hybrid_rerank_topk(qv, dv, sp, vd, jnp.float32(0.5),
                                        k=100), n=nd, k=100)
    qb = put(rng.standard_normal((16, DN.DIM)).astype(np.float32))
    spb = put(rng.integers(0, 1 << 20, (16, nd)).astype(np.float32))
    vb = put(np.ones((16, nd), bool))
    timed("hybrid_rerank_topk_batch",
          lambda: DN.hybrid_rerank_topk_batch(qb, dv, spb, vb,
                                              jnp.float32(0.5), k=100),
          queries=16, n=nd, b=16, k=100)
    timed("dense_boost_topk",
          lambda: DN.dense_boost_topk(qv, dv,
                                      put(rng.integers(
                                          0, 1 << 20, nd).astype(np.int32)),
                                      vd, jnp.float32(0.5), k=100),
          n=nd, k=100)
    # the SERVING rerank family (ISSUE 6): bs slots gathering their
    # candidates from a device-resident forward index in one dispatch
    fwd_cap, nbq, bsq = 1 << 14, 128, 16
    fwd = put(rng.standard_normal((fwd_cap, DN.DIM)).astype(np.float16))
    qrows = np.stack([
        DN.pack_rerank_row(
            rng.standard_normal(DN.DIM).astype(np.float32),
            rng.integers(0, 1 << 20, nbq).astype(np.int32),
            rng.integers(0, fwd_cap, nbq).astype(np.int32), 0.5, nbq)
        for _ in range(bsq)])
    timed("_rerank_fwd_batch_packed_kernel",
          lambda: DN._rerank_fwd_batch_packed_kernel(fwd, qrows, nb=nbq,
                                                     bs=bsq),
          queries=bsq, bs=bsq, nb=nbq, dim=DN.DIM, cap=fwd_cap)
    # dense-first IVF ANN family (ISSUE 11): the wave assignment matmul
    # and the probe/fuse gather kernel over an int8 hot slab
    from yacy_search_server_tpu.ops import ann as AN
    ann_C, ann_np, ann_nb, ann_k = 1024, AN.ANN_DEFAULT_NPROBE, 2048, 256
    ann_cap = min(1 << 20, max(1 << 16, rows))
    cent = put(rng.standard_normal((ann_C, DN.DIM)).astype(np.float16))
    qvb = put(rng.standard_normal((bsq, DN.DIM)).astype(np.float32))
    timed("_ann_assign_batch_kernel",
          lambda: AN._ann_assign_batch_kernel(cent, qvb, np_=ann_np,
                                              c_real=ann_C),
          queries=bsq, bs=bsq, dim=DN.DIM, C=ann_C, np_=ann_np)
    slab = put(rng.integers(-127, 128, (ann_cap, DN.DIM))
               .astype(np.int8))
    ascales = put((rng.random(ann_cap).astype(np.float16) / 127))
    asdocids = put(np.arange(ann_cap, dtype=np.int32))
    ann_qi = np.stack([
        AN.pack_ann_fuse_row(
            rng.standard_normal(DN.DIM).astype(np.float32),
            rng.integers(0, ann_cap, ann_nb).astype(np.int32),
            np.full(ann_nb, -1, np.int32),
            np.zeros(ann_nb, np.int32), 0.5, ann_nb)
        for _ in range(bsq)])
    ann_qi_dev = put(ann_qi)
    timed("_ann_fuse_batch_packed_kernel",
          lambda: AN._ann_fuse_batch_packed_kernel(
              slab, ascales, asdocids, ann_qi_dev, nb=ann_nb, bs=bsq,
              k=ann_k),
          queries=bsq, bs=bsq, nb=ann_nb, dim=DN.DIM, cap=ann_cap,
          k=ann_k)

    # BlockRank power iteration (MAX_ITERS is the trip-count upper bound
    # — the kernel may converge earlier, so util is a floor)
    hosts, edges = 4096, 65536
    timed("_power_iterate_sparse",
          lambda: B._power_iterate_sparse(
              put(rng.integers(0, hosts, edges).astype(np.int32)),
              put(rng.integers(0, hosts, edges).astype(np.int32)),
              put(np.ones(edges, np.float32)),
              put(np.zeros(hosts, bool)), jnp.float32(B.DAMPING),
              n=hosts),
          n=hosts, edges=edges, iters=B.MAX_ITERS)

    # devstore serving kernels against the synthetic arena span
    ns = DS.DeviceSegmentStore.MAX_SPANS
    starts = np.zeros(ns, np.int32)
    counts = np.zeros(ns, np.int32)
    counts[0] = rows
    d_args = (np.zeros((1, P.NF), np.int16), np.zeros(1, np.int32),
              np.full(1, -1, np.int32))
    zero_ext = (np.zeros(P.NF, np.int32), np.zeros(P.NF, np.int32),
                np.float32(0), np.float32(0))
    timed("_rank_spans_kernel",
          lambda: DS._rank_spans_kernel(
              f16, fl, dd, dead, starts, counts, *d_args,
              np.zeros(1, np.uint32), np.int32(DS.NO_LANG),
              np.int32(DS.NO_FLAG), np.int32(DS.DAYS_NONE_LO),
              np.int32(DS.DAYS_NONE_HI), *zero_ext, *consts, k=k,
              n_spans=ns, with_delta=False),
          rows=rows, n_spans=ns, k=k)
    timed("_rank_spans_packed_kernel",
          lambda: DS._rank_spans_packed_kernel(
              f16, fl, dd, dead, starts, counts, *d_args,
              np.zeros(1, np.uint32), np.int32(DS.NO_LANG),
              np.int32(DS.NO_FLAG), np.int32(DS.DAYS_NONE_LO),
              np.int32(DS.DAYS_NONE_HI), *zero_ext, *consts, k=k,
              n_spans=ns, with_delta=False),
          rows=rows, n_spans=ns, k=k)
    bs = 16
    qi_scan = np.zeros((bs, 2 * ns + 4), np.int32)
    qi_scan[:, ns] = rows                    # every slot scans the span
    qi_scan[:, 2 * ns + 1] = DS.NO_FLAG
    qi_scan[:, 2 * ns + 2] = DS.DAYS_NONE_LO
    qi_scan[:, 2 * ns + 3] = DS.DAYS_NONE_HI
    timed("_rank_scan_batch_kernel",
          lambda: DS._rank_scan_batch_kernel(
              f16, fl, dd, dead, qi_scan, *consts, k=k, n_spans=ns,
              bs=bs),
          queries=bs, rows=bs * rows, n_spans=ns, k=k)
    timed("_rank_scan_batch_packed_kernel",
          lambda: DS._rank_scan_batch_packed_kernel(
              f16, fl, dd, dead, qi_scan, *consts, k=k, n_spans=ns,
              bs=bs),
          queries=bs, rows=bs * rows, n_spans=ns, k=k)
    st = DS.pack_prune_stats(f16_np[:rows], fl_np[:rows])[0]
    shift, lang_term = DS.prune_bound_consts(prof)
    sb1 = np.zeros(bs, np.int32)
    cnt1 = np.zeros(bs, np.int32)
    tst1 = np.zeros(bs, np.int32)
    tct1 = np.zeros(bs, np.int32)
    cnt1[:] = rows
    tct1[:] = n_tiles
    cmin = np.tile(st["col_min"], (bs, 1)).astype(np.int32)
    cmax = np.tile(st["col_max"], (bs, 1)).astype(np.int32)
    tmin = np.full(bs, st["tf_min"], np.float32)
    tmax = np.full(bs, st["tf_max"], np.float32)
    maxt = DS._pmax_window(n_tiles)
    qi, qf, nbs = DS._pack_batch1(sb1, cnt1, tst1, tct1, cmin, cmax,
                                  tmin, tmax, shift, lang_term)
    timed("_rank_pruned_batch1_kernel",
          lambda: DS._rank_pruned_batch1_kernel(
              f16, fl, dd, dead, pmax, qi, qf, *consts, k=k, maxt=maxt,
              bs=nbs),
          queries=bs, bs=bs, tile=TILE, maxt=maxt, k=k, cap=cap,
          doc_cap=doc_cap, tcap=tcap)
    qiq, _nbs = DS._pack_batch1_fused(sb1, cnt1, tst1, tct1, cmin, cmax,
                                      tmin, tmax, shift, lang_term)
    timed("_rank_pruned_batch1_packed_kernel",
          lambda: DS._rank_pruned_batch1_packed_kernel(
              f16, fl, dd, dead, pmax, qiq, *consts, k=k, maxt=maxt,
              bs=nbs),
          queries=bs, bs=bs, tile=TILE, maxt=maxt, k=k, cap=cap,
          doc_cap=doc_cap, tcap=tcap)
    timed("_rank_pruned_kernel",
          lambda: DS._rank_pruned_kernel(
              f16, fl, dd, dead, pmax, np.int32(0), np.int32(rows),
              np.int32(0), np.int32(n_tiles), st["col_min"],
              st["col_max"], st["tf_min"], st["tf_max"], shift,
              lang_term, *consts, k=k, b=1),
          b=1, tile=TILE, bs=1, k=k)
    b_esc = min(8, n_tiles)
    timed("_rank_pruned_batch_kernel",
          lambda: DS._rank_pruned_batch_kernel(
              f16, fl, dd, dead, pmax, sb1, cnt1, tst1, tct1, cmin,
              cmax, tmin, tmax, shift, lang_term, *consts, k=k, b=b_esc),
          queries=bs, b=b_esc, tile=TILE, bs=bs, k=k)
    # bit-packed (compressed-residency) fused-decode twins: the SAME
    # rows bit-packed (ops/packed.py), scored straight from the words
    from yacy_search_server_tpu.ops import packed as PK
    pb = PK.pack_block(f16_np[:rows], fl_np[:rows],
                       np.arange(rows, dtype=np.int32))
    pwords = put(pb.words)
    pw_cap = int(pb.words.shape[0])
    metas = np.tile(pb.meta_vector(), (bs, 1)).astype(np.int32)
    qiq_bp, _nbs = DS._pack_batch1_bp(sb1, cnt1, tst1, tct1, metas,
                                      cmin, cmax, tmin, tmax, shift,
                                      lang_term)
    timed("_rank_pruned_batch1_bp_kernel",
          lambda: DS._rank_pruned_batch1_bp_kernel(
              pwords, dead, pmax, qiq_bp, *consts, k=k, maxt=maxt,
              bs=nbs),
          queries=bs, bs=bs, tile=TILE, maxt=maxt, k=k,
          row_bits=pb.row_bits, pw_cap=pw_cap, doc_cap=doc_cap,
          tcap=tcap)
    qi_sbp = np.zeros((bs, 6 + PK.META_LEN), np.int32)
    qi_sbp[:, 1] = rows
    qi_sbp[:, 2:2 + PK.META_LEN] = pb.meta_vector()
    qi_sbp[:, 3 + PK.META_LEN] = DS.NO_FLAG
    qi_sbp[:, 4 + PK.META_LEN] = DS.DAYS_NONE_LO
    qi_sbp[:, 5 + PK.META_LEN] = DS.DAYS_NONE_HI
    timed("_rank_scan_batch_bp_kernel",
          lambda: DS._rank_scan_batch_bp_kernel(
              pwords, dead, qi_sbp, *consts, k=k, bs=bs),
          queries=bs, rows=bs * rows, k=k, bs=bs, row_bits=pb.row_bits,
          pw_cap=pw_cap, doc_cap=doc_cap)
    r_join = min(rows, DS.DeviceSegmentStore.MAX_JOIN_ROWS)
    m_join = min(r_join, 1 << 16)
    qargs = np.zeros((4, 9), np.int32)
    qargs[:, 1] = r_join
    timed("_rank_join_batch_kernel",
          lambda: DS._rank_join_batch_kernel(
              f16, fl, dd, dead, jd, jp, qargs, *consts, k=k, n_inc=1,
              n_exc=0, r=r_join, inc_ms=(m_join,), exc_ms=()),
          queries=4, r=r_join, m=m_join, n_inc=1, n_exc=0, bs=4, k=k)
    timed("_rank_join_bm_batch_kernel",
          lambda: DS._rank_join_bm_batch_kernel(
              f16, fl, dd, dead, jd, jp, bmtab, qargs, *consts, k=k,
              n_inc=1, n_exc=0, r=r_join, inc_ms=(0,), exc_ms=(),
              inc_bm=(True,), exc_bm=()),
          queries=4, r=r_join, n_inc=1, n_exc=0, bs=4, k=k,
          doc_cap=doc_cap, jcap=jcap, nslots=2, nwords=nwords)
    timed("_rank_join_batch_packed_kernel",
          lambda: DS._rank_join_batch_packed_kernel(
              f16, fl, dd, dead, jd, jp, qargs, *consts, k=k, n_inc=1,
              n_exc=0, r=r_join, inc_ms=(m_join,), exc_ms=()),
          queries=4, r=r_join, m=m_join, n_inc=1, n_exc=0, bs=4, k=k)
    timed("_rank_join_bm_batch_packed_kernel",
          lambda: DS._rank_join_bm_batch_packed_kernel(
              f16, fl, dd, dead, jd, jp, bmtab, qargs, *consts, k=k,
              n_inc=1, n_exc=0, r=r_join, inc_ms=(0,), exc_ms=(),
              inc_bm=(True,), exc_bm=()),
          queries=4, r=r_join, n_inc=1, n_exc=0, bs=4, k=k,
          doc_cap=doc_cap, jcap=jcap, nslots=2, nwords=nwords)

    # device-side index build (ISSUE 13b): the write path's vmapped
    # bit-pack — a steady ingest soak's one per-bucket dispatch shape
    from yacy_search_server_tpu.ingest import devbuild as IB
    pk_bs, pk_rows = 8, 1024
    pk_f16 = put(f16_np[:pk_bs * pk_rows].reshape(pk_bs, pk_rows, P.NF))
    pk_fl = put(fl_np[:pk_bs * pk_rows].astype(np.int32)
                .reshape(pk_bs, pk_rows))
    pk_dd = put(np.arange(pk_bs * pk_rows, dtype=np.int32)
                .reshape(pk_bs, pk_rows))
    pk_n = put(np.full(pk_bs, pk_rows, np.int32))
    timed("_pack_block_batch_kernel",
          lambda: IB._pack_block_batch_kernel(pk_f16, pk_fl, pk_dd,
                                              pk_n, rows=pk_rows),
          bs=pk_bs, rows=pk_rows)

    # fused all-gather+top-k fusion collective (ISSUE 12b): timed as ONE
    # shard_map program over the device pool (virtual CPU mesh in CI,
    # real ICI on TPU).
    from jax.sharding import Mesh as _Mesh
    from jax.sharding import NamedSharding as _NS
    from jax.sharding import PartitionSpec as _PS

    from yacy_search_server_tpu.parallel import mesh as M
    agdevs = M.best_devices(8, prefer_cpu=jax.default_backend() != "tpu")
    agdevs = agdevs[:max(1, min(8, len(agdevs)))]
    ag_mesh = _Mesh(np.asarray(agdevs), ("doc",))
    ag_ndev, ag_rows = len(agdevs), 256

    def _ag_body(s, d):
        ls, ld = M.tie_topk(s, d, k)
        return M.all_gather_topk(ls, ld, "doc", k)
    ag_s = jax.device_put(
        rng.integers(0, 1 << 20, ag_ndev * ag_rows).astype(np.int32),
        _NS(ag_mesh, _PS("doc")))
    ag_d = jax.device_put(
        np.arange(ag_ndev * ag_rows, dtype=np.int32),
        _NS(ag_mesh, _PS("doc")))
    # hoisted: jit caches per function instance, so rebuilding the
    # program inside the timed lambda would measure retrace+compile,
    # not the dispatch the cost model prices
    ag_fn = jax.jit(jax.shard_map(_ag_body, mesh=ag_mesh,
                                  in_specs=(_PS("doc"), _PS("doc")),
                                  out_specs=(_PS(), _PS()),
                                  check_vma=False))
    timed("all_gather_topk", lambda: ag_fn(ag_s, ag_d),
          k=k, ndev=ag_ndev, rows=ag_rows)

    points = {p.kernel: p for p in PROFILER.snapshot()}
    missing = [kn for kn in RF.registered() if kn not in points]
    assert not missing, f"kernels without roofline samples: {missing}"
    util = PROFILER.query_util()
    print(json.dumps({
        "metric": "roofline_summary", "device": peak.name,
        "peak_tflops": round(peak.flops_per_s / 1e12, 3),
        "peak_gbps": round(peak.bytes_per_s / 1e9, 1),
        "ridge_flops_per_byte": round(peak.ridge, 2),
        "rows": rows,
        "util_pct_p50": round(util["util_pct_p50"], 3),
        "util_pct_p95": round(util["util_pct_p95"], 3),
        "bound": util["bound"]}))
    for kn in RF.registered():
        p = points[kn]
        print(json.dumps({
            "metric": "roofline_kernel", "kernel": kn,
            "flops": round(p.flops, 1), "bytes": round(p.bytes, 1),
            "intensity": round(p.intensity, 3),
            # 6 decimals: the fusion collectives are a few kFLOPs behind
            # a multi-device dispatch wall — 3 digits rounds them to 0.0.
            "achieved_gflops_s": round(p.achieved_flops_per_s / 1e9, 6),
            "achieved_gbps": round(p.achieved_bytes_per_s / 1e9, 6),
            "util_pct": p.util_pct, "bound": p.bound}))
    print(RF.ascii_table(list(points.values()), peak), file=sys.stderr)


def _seed_dense_coverage(sb, seed: int = 17) -> None:
    """Vectors for a slice of the corpus (every 3rd docid in the first
    4096) — the ONE seeding recipe shared by --rerank-overhead and the
    headline hybrid soak, so their forward-index coverage can't
    silently diverge. Absent vectors legitimately score zero boost:
    hybrid serving must not require full coverage (at 10M docs that
    would be a 5 GB upload — ROADMAP item 4 territory)."""
    rng = np.random.default_rng(seed)
    dim = sb.index.dense.dim
    for i in range(0, 4096, 3):
        sb.index.dense.put(i, rng.standard_normal(dim).astype(np.float32))


def _ab_soak(sb, set_mode, threads: int = 16, per_thread: int = 10,
             windows: int = 3, k_page: int = 10, n_terms: int = 2,
             per_query=None, window_driver=None, after_warm=None,
             hybrid: bool = False):
    """Shared interleaved-window A/B soak harness — the scaffold the
    trace/health/pipeline/federation overhead modes each carried a
    private ~60-line copy of (the known PR-5 deferral), now also the
    base of --rerank-overhead.

    Protocol: warm BOTH modes outside the measured windows (kernel
    compiles, caches), gc.collect + gc.freeze (no major-GC GIL pause
    mid-window), then `windows` interleaved OFF→ON rounds of `threads`
    searcher threads × `per_thread` ranked queries each, use_cache=False
    so every query exercises the path under test. Asserts 100% device
    coverage over the measured queries.

    `set_mode(bool)` toggles the subsystem under test; `window_driver`
    (optional, mode -> context manager) runs a background driver /
    per-window accounting around each measured window; `per_query`
    (optional, wall_s -> None) runs after every query in every window;
    `after_warm` (optional) runs once between warmup and the measured
    windows (histogram resets etc.).

    Returns the per-mode medians and raw latency lists:
    p50_off/p50_on/p95_off/p95_on (ms), overhead_pct (p50 regression
    ON vs OFF), qps_off/qps_on/speedup_pct, queries_per_mode, lats."""
    import gc
    import threading as _threading
    from contextlib import nullcontext

    from yacy_search_server_tpu.utils import tracing

    def window(latencies):
        def worker(t):
            for _ in range(per_thread):
                sb.search_cache.clear()
                q0 = time.perf_counter()
                ev = sb.search(f"benchterm{t % n_terms}", count=k_page,
                               hybrid=hybrid, use_cache=False)
                assert len(ev.results()) == k_page
                wall = time.perf_counter() - q0
                latencies.append(wall)
                if per_query is not None:
                    per_query(wall)
        ts = [_threading.Thread(target=worker, args=(t,))
              for t in range(threads)]
        t0 = time.perf_counter()
        for th in ts:
            th.start()
        for th in ts:
            th.join()
        return threads * per_thread / (time.perf_counter() - t0)

    # warm both modes outside the measured windows
    set_mode(True)
    window([])
    set_mode(False)
    window([])
    if after_warm is not None:
        after_warm()
    gc.collect()
    gc.freeze()
    served0 = sb.index.devstore.queries_served

    p50s = {False: [], True: []}
    lats_all = {False: [], True: []}
    qps = {False: [], True: []}
    for _w in range(max(1, windows)):
        for mode in (False, True):          # interleaved: OFF then ON
            set_mode(mode)
            cm = (window_driver(mode) if window_driver is not None
                  else nullcontext())
            lats: list = []
            with cm:
                qps[mode].append(window(lats))
            lats.sort()
            p50s[mode].append(tracing._pctl(lats, 0.50) * 1000.0)
            lats_all[mode].extend(lats)
    set_mode(True)                          # the product default stays on
    total = 2 * max(1, windows) * threads * per_thread
    ranked = sb.index.devstore.queries_served - served0
    assert ranked >= total, \
        f"only {ranked}/{total} measured queries were device-ranked"
    for m in lats_all.values():
        m.sort()

    def med(sv):
        return sorted(sv)[len(sv) // 2]

    def pctl_ms(sv, q):
        return tracing._pctl(sv, q) * 1000.0

    p50_off, p50_on = med(p50s[False]), med(p50s[True])
    qps_off, qps_on = med(qps[False]), med(qps[True])
    return {
        "p50_off": p50_off, "p50_on": p50_on,
        "p95_off": pctl_ms(lats_all[False], 0.95),
        "p95_on": pctl_ms(lats_all[True], 0.95),
        "overhead_pct": (p50_on - p50_off) / max(p50_off, 1e-9) * 100.0,
        "qps_off": qps_off, "qps_on": qps_on,
        "speedup_pct": (qps_on / max(qps_off, 1e-9) - 1.0) * 100.0,
        "queries_per_mode": max(1, windows) * threads * per_thread,
        "lats": lats_all,
    }


def _pipeline_overhead_mode(n: int, threads: int = 16,
                            per_thread: int = 10, windows: int = 3):
    """--pipeline-overhead (ISSUE 3): served q/s with the batcher's
    PIPELINED dispatch (async issue + completer fetch) ON vs OFF on the
    shared interleaved-window harness (_ab_soak). Also exercises the
    repeated-term result cache: the repeat window must answer from
    cache with ZERO batcher dispatches and bit-identical results.

    The result cache is disabled during the QPS windows (every repeat
    would otherwise hit it and measure the cache, not the dispatch
    path) and re-enabled for the cache-contract assertions."""
    import numpy as np
    from yacy_search_server_tpu.ops.ranking import RankingProfile
    from yacy_search_server_tpu.utils.hashes import word2hash

    sb = _build_served_switchboard(n, n_terms=2, mesh="off")
    ds = sb.index.devstore
    assert ds is not None, "device serving must be on"
    b = ds._batcher
    assert b is not None, "batching must be on"
    ds._topk_cache.enabled = False
    k_page = 10

    def set_mode(mode):
        b.pipeline = mode

    r = _ab_soak(sb, set_mode, threads=threads, per_thread=per_thread,
                 windows=windows, k_page=k_page)
    qps_off, qps_on = r["qps_off"], r["qps_on"]
    speedup_pct = r["speedup_pct"]

    # ---- repeated-term cache contract (zero device work on repeats) ----
    ds._topk_cache.enabled = True
    ds._topk_cache.clear()
    th0 = word2hash("benchterm0")
    prof = RankingProfile()
    cold = ds.rank_term(th0, prof, "en", k=k_page)
    c0 = ds.counters()
    hit = ds.rank_term(th0, prof, "en", k=k_page)
    c1 = ds.counters()
    assert c1["rank_cache_hits"] - c0["rank_cache_hits"] >= 1, \
        "repeat window produced no cache hit"
    assert c1["batch_dispatches"] == c0["batch_dispatches"], \
        "cache hit dispatched the batcher"
    assert c1["device_round_trips"] == c0["device_round_trips"], \
        "cache hit paid a device round trip"
    np.testing.assert_array_equal(np.asarray(cold[0]), np.asarray(hit[0]))
    np.testing.assert_array_equal(np.asarray(cold[1]), np.asarray(hit[1]))

    c = ds.counters()
    rt_per_query = round(c["device_round_trips"]
                         / max(c["queries_served"], 1), 4)
    print(json.dumps({
        "metric": "pipeline_overhead",
        "n_postings": n,
        "threads": threads,
        "queries_per_mode": threads * per_thread * windows,
        "qps_unpipelined": round(qps_off, 3),
        "qps_pipelined": round(qps_on, 3),
        "speedup_pct": round(speedup_pct, 3),
        "rt_per_query": rt_per_query,
        "rank_cache_hits": c["rank_cache_hits"],
        "dispatch_rt_ms": ds.dispatch_rt_ms,
    }))
    # the >=25% acceptance gate only binds where round trips dominate
    # (a measured dispatch floor >= 5 ms); below that the pipeline win
    # is in the noise
    if ds.dispatch_rt_ms >= 5.0:
        assert speedup_pct >= 25.0, (
            f"pipelined dispatch won only {speedup_pct:.1f}% over the "
            f"non-pipelined path (dispatch_rt {ds.dispatch_rt_ms} ms)")


def _trace_overhead_mode(n: int, threads: int = 16, per_thread: int = 10,
                         windows: int = 3, budget_pct: float = 2.0):
    """--trace-overhead (ISSUE 2): serving p50/p95 with the tracing
    spine ON vs OFF on the shared interleaved-window harness (_ab_soak).
    The spine ships enabled by default, so the overhead budget is a
    pinned contract: p50 regression must stay under `budget_pct`%.
    Emits one JSON line carrying the measured pair."""
    from yacy_search_server_tpu.utils import tracing

    sb = _build_served_switchboard(n, n_terms=2, mesh="off")
    assert sb.index.devstore is not None, "device serving must be on"
    # the result cache would serve every repeat with zero device work —
    # this mode pins the kernel SPAN SPINE's overhead, so the measured
    # queries must actually rank (same reason as --pipeline-overhead)
    sb.index.devstore._topk_cache.enabled = False

    r = _ab_soak(sb, tracing.set_enabled, threads=threads,
                 per_thread=per_thread, windows=windows)
    print(json.dumps({
        "metric": "trace_overhead",
        "n_postings": n,
        "threads": threads,
        "queries_per_mode": r["queries_per_mode"],
        "p50_ms_tracing_off": round(r["p50_off"], 3),
        "p50_ms_tracing_on": round(r["p50_on"], 3),
        "p95_ms_tracing_off": round(r["p95_off"], 3),
        "p95_ms_tracing_on": round(r["p95_on"], 3),
        "overhead_pct": round(r["overhead_pct"], 3),
        "budget_pct": budget_pct,
    }))
    assert r["overhead_pct"] < budget_pct, (
        f"tracing overhead {r['overhead_pct']:.2f}% exceeds the "
        f"{budget_pct}% stay-on-by-default budget")


def _health_overhead_mode(n: int, threads: int = 16, per_thread: int = 10,
                          windows: int = 3, budget_pct: float = 2.0):
    """--health-overhead (ISSUE 4): serving p50/p95 with the histogram
    recording + health-rule tick ON vs OFF, interleaved windows (the
    --trace-overhead discipline).  The health engine ships enabled by
    default, so the budget is a pinned contract: p50 regression must
    stay under `budget_pct`%.  Also emits the HISTOGRAM-derived p50/p95
    of the ON windows next to the raw-sample percentiles so the two
    implementations cross-check each other (the BASELINE agreement
    bound)."""
    from yacy_search_server_tpu.utils import histogram, tracing

    import gc
    import threading as _threading

    from contextlib import contextmanager

    sb = _build_served_switchboard(n, n_terms=2, mesh="off")
    assert sb.index.devstore is not None, "device serving must be on"
    sb.index.devstore._topk_cache.enabled = False

    # the ON mode runs the real rule tick at an aggressive 1 Hz (the
    # product default is health.tickS=5): a pass at 5x cadence bounds
    # the deployed overhead a fortiori
    @contextmanager
    def driver(mode):
        if not mode:
            yield
            return
        tick_stop = _threading.Event()

        def ticker():
            while not tick_stop.wait(1.0):
                sb.health.tick()
        tick_thread = _threading.Thread(target=ticker, daemon=True)
        tick_thread.start()
        try:
            yield
        finally:
            tick_stop.set()
            tick_thread.join()

    r = _ab_soak(sb, histogram.set_enabled, threads=threads,
                 per_thread=per_thread, windows=windows,
                 window_driver=driver,
                 # ON-window percentiles cover measured queries only
                 after_warm=histogram.reset)
    # the windowed-histogram view of the same ON-window queries: the
    # switchboard.search family is fed by the span spine, so its
    # percentiles must agree with the raw-sample ones within the bucket
    # resolution (~12.5%) + concurrency noise — pinned at 30%
    h = histogram.get("switchboard.search")
    hist_p50 = h.percentile(0.50) if h is not None else 0.0
    hist_p95 = h.percentile(0.95) if h is not None else 0.0
    lat_p50_on = tracing._pctl(r["lats"][True], 0.50) * 1000.0
    lat_p95_on = r["p95_on"]
    agreement_pct = (abs(hist_p50 - lat_p50_on)
                     / max(lat_p50_on, 1e-9)) * 100.0
    print(json.dumps({
        "metric": "health_overhead",
        "n_postings": n,
        "threads": threads,
        "queries_per_mode": r["queries_per_mode"],
        "p50_ms_health_off": round(r["p50_off"], 3),
        "p50_ms_health_on": round(r["p50_on"], 3),
        "p95_ms_health_off": round(r["p95_off"], 3),
        "p95_ms_health_on": round(r["p95_on"], 3),
        "overhead_pct": round(r["overhead_pct"], 3),
        "budget_pct": budget_pct,
        "hist_p50_ms": round(hist_p50, 3),
        "hist_p95_ms": round(hist_p95, 3),
        "snapshot_p50_ms": round(lat_p50_on, 3),
        "snapshot_p95_ms": round(lat_p95_on, 3),
        "p50_agreement_pct": round(agreement_pct, 3),
        "health_rule_states": {name: st.state for name, _d, st
                               in sb.health.rule_table()},
    }))
    assert r["overhead_pct"] < budget_pct, (
        f"health-engine overhead {r['overhead_pct']:.2f}% exceeds the "
        f"{budget_pct}% stay-on-by-default budget")
    if h is not None and h.windowed_count() >= 100:
        assert agreement_pct < 30.0, (
            f"histogram p50 {hist_p50:.2f}ms disagrees with raw-sample "
            f"p50 {lat_p50_on:.2f}ms by {agreement_pct:.1f}% — one of "
            f"the two percentile paths is broken")


def _actuator_overhead_mode(n: int, threads: int = 16,
                            per_thread: int = 10, windows: int = 3,
                            budget_pct: float = 2.0):
    """--actuator-overhead (ISSUE 9): serving p50/p95 with the actuator
    engine ENABLED-BUT-IDLE vs disabled, interleaved windows on the
    shared `_ab_soak` harness.  The ON mode runs the full health+
    actuator tick at 1 Hz (5x the deployed health.tickS=5 cadence, so
    the measured regression bounds the deployed overhead a fortiori)
    plus the per-query admission/ladder reads on the serving path.  Two
    gates: p50 regression < `budget_pct`%, and ZERO actuator
    transitions across the healthy soak — an actuator that moves
    without a real signal is a bug, not adaptation.  The emitted JSON
    carries the degrade_level histogram and the per-actuator transition
    counters the headline artifact also gains."""
    import threading as _threading
    from contextlib import contextmanager

    sb = _build_served_switchboard(n, n_terms=2, mesh="off")
    assert sb.index.devstore is not None, "device serving must be on"
    sb.index.devstore._topk_cache.enabled = False
    act = sb.actuators

    def set_mode(mode):
        act.enabled = mode

    # ON windows drive the REAL sensing->decision loop at 1 Hz: the
    # health tick evaluates every rule and ticks every actuator
    @contextmanager
    def driver(mode):
        if not mode:
            yield
            return
        stop = _threading.Event()

        def ticker():
            while not stop.wait(1.0):
                sb.health.tick()
        th = _threading.Thread(target=ticker, daemon=True)
        th.start()
        try:
            yield
        finally:
            stop.set()
            th.join()

    r = _ab_soak(sb, set_mode, threads=threads, per_thread=per_thread,
                 windows=windows, window_driver=driver)
    transitions = act.transition_counts()
    total_transitions = act.transitions_total()
    levels = {str(i): v for i, v in enumerate(act.degraded_queries)}
    print(json.dumps({
        "metric": "actuator_overhead",
        "n_postings": n,
        "threads": threads,
        "queries_per_mode": r["queries_per_mode"],
        "p50_ms_actuators_off": round(r["p50_off"], 3),
        "p50_ms_actuators_on": round(r["p50_on"], 3),
        "p95_ms_actuators_off": round(r["p95_off"], 3),
        "p95_ms_actuators_on": round(r["p95_on"], 3),
        "overhead_pct": round(r["overhead_pct"], 3),
        "budget_pct": budget_pct,
        "degrade_level_queries": levels,
        "actuator_transitions": {f"{a}:{d}": v for (a, d), v
                                 in sorted(transitions.items())},
        "actuator_transitions_total": total_transitions,
        "degrade_level": act.level,
    }))
    assert r["overhead_pct"] < budget_pct, (
        f"actuator-layer overhead {r['overhead_pct']:.2f}% exceeds the "
        f"{budget_pct}% stay-on-by-default budget")
    assert total_transitions == 0, (
        f"{total_transitions} actuator transition(s) during a HEALTHY "
        f"soak: {transitions} — actuators must hold still without a "
        f"real signal")
    assert act.level == 0, "ladder moved during a healthy soak"


def _tail_overhead_mode(n: int, threads: int = 8, per_thread: int = 10,
                        windows: int = 3, budget_pct: float = 2.0,
                        emit: bool = True) -> dict:
    """--tail-overhead (ISSUE 15): serving p50/p95 with the tail-
    attribution engine (classifier + per-wave stamping) ON vs OFF on
    the shared `_ab_soak` harness.  The engine ships enabled by
    default, so the budget is a pinned contract: p50 regression under
    `budget_pct`%.  After the A/B windows a FAULT-INJECTED window
    (batcher.dispatch stall through the real faultinject registry)
    asserts the engine's non-vacuity the way the ISSUE demands: at
    least one classified verdict, and ZERO `unattributed` among them —
    an injected stall the classifier cannot name would make every
    production verdict suspect."""
    import threading as _threading

    from yacy_search_server_tpu.utils import faultinject, tailattr

    sb = _build_served_switchboard(n, n_terms=2, mesh="off")
    assert sb.index.devstore is not None, "device serving must be on"
    sb.index.devstore._topk_cache.enabled = False

    r = _ab_soak(sb, tailattr.set_enabled, threads=threads,
                 per_thread=per_thread, windows=windows)

    # the fault-injected verdict window: a real dispatcher stall makes
    # every riding query's batch wall queue residue — the classifier
    # must name it queue_wait, never shrug unattributed.  The soak's
    # own contended tail cached a fat window p95 (the gate working as
    # designed: only exemplar-worthy queries classify); expire the
    # soak's windows first so the stall is judged against a quiet node.
    from yacy_search_server_tpu.utils import histogram as _hg
    for _ in range(_hg.WINDOWS + 1):
        _hg.rotate_all()
    tailattr.reset()
    tailattr.set_enabled(True)
    faultinject.set_fault("batcher.dispatch", 300)
    try:
        def worker(t):
            for _ in range(2):
                sb.search_cache.clear()
                ev = sb.search(f"benchterm{t % 2}", count=10,
                               use_cache=False)
                assert len(ev.results()) == 10
        ts = [_threading.Thread(target=worker, args=(t,))
              for t in range(4)]
        for th in ts:
            th.start()
        for th in ts:
            th.join()
    finally:
        faultinject.clear()
    verdicts = [v.to_json() for v in tailattr.verdicts(100)]
    causes: dict = {}
    for v in verdicts:
        causes[v["cause"]] = causes.get(v["cause"], 0) + 1
    art = {
        "metric": "tail_overhead",
        "n_postings": n,
        "threads": threads,
        "queries_per_mode": r["queries_per_mode"],
        "p50_ms_tail_off": round(r["p50_off"], 3),
        "p50_ms_tail_on": round(r["p50_on"], 3),
        "p95_ms_tail_off": round(r["p95_off"], 3),
        "p95_ms_tail_on": round(r["p95_on"], 3),
        "overhead_pct": round(r["overhead_pct"], 3),
        "budget_pct": budget_pct,
        "injected_verdicts": len(verdicts),
        "injected_causes": causes,
        "injected_unattributed": causes.get("unattributed", 0),
    }
    if emit:
        print(json.dumps(art))
    assert r["overhead_pct"] < budget_pct, (
        f"tail-attribution overhead {r['overhead_pct']:.2f}% exceeds "
        f"the {budget_pct}% stay-on-by-default budget")
    assert len(verdicts) >= 1, (
        "no classified verdict under an injected dispatcher stall — "
        "the engine is vacuous")
    assert causes.get("unattributed", 0) == 0, (
        f"unattributed verdicts under injection: {causes} — the "
        f"classifier failed to name a KNOWN fault")
    sb.close()
    return art


def _prof_overhead_mode(n: int, threads: int = 8, per_thread: int = 10,
                        windows: int = 6, budget_pct: float = 2.0,
                        emit: bool = True) -> dict:
    """--prof-overhead (ISSUE 20): serving p50/p95 with the whitebox
    profiler — the sampling thread at 2x the deployed 25 Hz rate PLUS
    the lock-wait observatory on every hot lock — ON vs OFF on the
    shared `_ab_soak` harness.  The profiler ships enabled by default,
    so the budget is a pinned contract like --trace/--health/--tail:
    p50 regression under `budget_pct`% WITH MARGIN (the deployed rate
    is half the measured one).  Non-vacuity gates: the ON windows must
    actually fold stack samples, and the devstore store lock's wait
    histogram must have recorded (the observatory was live on the
    serving path), or the 0% would be the overhead of nothing.
    windows=6 (vs --tail-overhead's 3): a no-op-toggle calibration of
    this harness at 3 windows showed a ~1.5% noise floor — too coarse
    to resolve a 2% gate — and doubling the interleaved window count
    is what tightens the p50 pairing, not longer windows."""
    from yacy_search_server_tpu.utils import histogram as _hg
    from yacy_search_server_tpu.utils import profiling

    sb = _build_served_switchboard(n, n_terms=2, mesh="off")
    assert sb.index.devstore is not None, "device serving must be on"
    sb.index.devstore._topk_cache.enabled = False

    samp = profiling.ensure_sampler()
    deployed_hz = samp.base_hz
    samp.base_hz = deployed_hz * 2.0     # 2x: the margin IS the gate
    profiling.reset()
    wait_h = _hg.get("lock.wait.devstore")
    wait_before = wait_h.snapshot()["count"] if wait_h is not None else 0
    try:
        r = _ab_soak(sb, profiling.set_enabled, threads=threads,
                     per_thread=per_thread, windows=windows)
    finally:
        samp.base_hz = deployed_hz
        profiling.set_enabled(True)
    st = profiling.stats()
    wait_h = _hg.get("lock.wait.devstore")
    wait_n = (wait_h.snapshot()["count"] if wait_h is not None
              else 0) - wait_before
    art = {
        "metric": "prof_overhead",
        "n_postings": n,
        "threads": threads,
        "queries_per_mode": r["queries_per_mode"],
        "sample_hz_measured": deployed_hz * 2.0,
        "sample_hz_deployed": deployed_hz,
        "p50_ms_prof_off": round(r["p50_off"], 3),
        "p50_ms_prof_on": round(r["p50_on"], 3),
        "p95_ms_prof_off": round(r["p95_off"], 3),
        "p95_ms_prof_on": round(r["p95_on"], 3),
        "overhead_pct": round(r["overhead_pct"], 3),
        "budget_pct": budget_pct,
        "samples_folded": st["samples_total"],
        "store_lock_waits_recorded": wait_n,
    }
    if emit:
        print(json.dumps(art))
    assert st["samples_total"] > 0, (
        "the sampler folded no stacks during the ON windows — the "
        "measured overhead is the overhead of nothing")
    assert wait_n > 0, (
        "the lock-wait observatory recorded no devstore store-lock "
        "acquisitions during the soak — the observatory was not live")
    assert r["overhead_pct"] < budget_pct, (
        f"whitebox profiler overhead {r['overhead_pct']:.2f}% at 2x "
        f"deployed rate exceeds the {budget_pct}% "
        f"stay-on-by-default budget")
    sb.close()
    if emit:
        out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "PROF_r01.json")
        with open(out, "w", encoding="utf-8") as f:
            json.dump(art, f, indent=1)
            f.write("\n")
        print(f"committed {out}", file=sys.stderr)
    return art


def _tail_forensics_mode(nprocs: int = 3, ndocs: int = 256,
                         straggle_ms: float = 350.0,
                         soak_queries: int = 80,
                         n: int = 200_000) -> None:
    """--tail-forensics (ISSUE 15 acceptance): a `nprocs`-process mesh
    soak with ONE member slowed via the wire-level do_meshfault
    (mesh.step latency) must produce, in one committed artifact
    (TAIL_r01.json):

    1. an assembled cross-process waterfall for an over-threshold query
       (per-member queue/commit/local-entry/exec segments, zero extra
       RPCs — they ride the scatter replies);
    2. `yacy_tail_cause_total{cause="collective_straggler"}` DOMINANT,
       with the straggler scoreboard naming the slowed member;
    3. a flight-recorder incident (slo_serving_p95 burning on the
       coordinator's real serving histogram) EMBEDDING the windowed
       cause histogram + scoreboard;
    4. the --tail-overhead gate (<2% p50, zero unattributed under
       injection) measured on the same tree.
    """
    import tempfile

    from yacy_search_server_tpu.parallel import distributed as D
    from yacy_search_server_tpu.parallel.launcher import MeshFleet

    run_dir = tempfile.mkdtemp(prefix="tailforensics-")
    terms = list(D.CORPUS_TERMS)
    slowed = 1
    with MeshFleet(procs=nprocs, local_devices=2, ndocs=ndocs,
                   run_dir=run_dir) as fleet:
        for w in terms:                     # compile-warm every shape
            fleet.search(w)
        for w in terms:                     # flush warm-step segments
            fleet.search(w)
        fleet.fault(slowed, "mesh.step", straggle_ms)
        t0 = time.perf_counter()
        answered = 0
        for i in range(soak_queries):
            rep = fleet.search(terms[i % len(terms)])
            if rep["scores"]:
                answered += 1
            # drive the coordinator's health evaluation alongside the
            # soak (mesh runtimes run no busy threads): the burn-rate
            # rule sees the straggled serving walls as they land
            if i % 5 == 4:
                fleet.info(0, tick_health=True)
        soak_s = time.perf_counter() - t0
        fleet.fault(slowed, "mesh.step", 0, clear=True)
        for w in terms[:2]:                 # flush the last segments
            fleet.search(w)
        info = fleet.info(0, tick_health=True)
    tail = info["tail"]
    causes = tail["cause_totals"]
    straggler_n = causes.get("collective_straggler", 0)
    others = sum(v for c, v in causes.items()
                 if c != "collective_straggler")
    board_row = next((r for r in tail["scoreboard"]
                      if r["member"] == f"mesh{slowed}"), None)
    # the waterfall OF an over-threshold straggled query (acceptance
    # exhibit 1); the newest healthy step's as fallback context
    wf = tail.get("straggled_waterfall") or tail["waterfall"]
    inc_tail = info.get("incident_tail") or {}

    overhead = _tail_overhead_mode(n, emit=False)

    art = {
        "metric": "tail_forensics",
        "procs": nprocs, "ndocs": ndocs,
        "straggled_member": f"mesh{slowed}",
        "straggle_ms": straggle_ms,
        "soak_queries": soak_queries, "answered": answered,
        "soak_s": round(soak_s, 3),
        "qps": round(soak_queries / soak_s, 3),
        "cause_totals": causes,
        "straggler_verdicts": straggler_n,
        "straggler_counts_by_member": tail["stragglers"],
        "scoreboard": tail["scoreboard"],
        "waterfall": wf,
        "segments_merged": tail["segments_merged"],
        "verdicts_sample": tail["verdicts"][:5],
        "health_incidents": info.get("health_incidents", []),
        "incident_tail_causes": inc_tail.get("tail_causes"),
        "incident_scoreboard": inc_tail.get("straggler_scoreboard"),
        "tail_overhead": overhead,
        "ok": bool(
            answered == soak_queries
            and straggler_n > others
            and board_row is not None
            and board_row["slowest_count"] >= 1
            and wf is not None and len(wf["members"]) == nprocs
            and inc_tail.get("tail_causes") is not None),
    }
    print(json.dumps(art, indent=1))
    # validation gates (the committed-artifact discipline)
    assert answered == soak_queries, "availability: every query answers"
    assert straggler_n > others, (
        f"collective_straggler must DOMINATE the cause histogram under "
        f"injection: {causes}")
    assert board_row is not None and board_row["slowest_count"] >= 1, (
        f"scoreboard must name mesh{slowed}: {tail['scoreboard']}")
    assert board_row["slowest_frac"] >= 0.5, (
        f"slowed member must be the slowest leg of most steps: "
        f"{board_row}")
    assert wf is not None and len(wf["members"]) == nprocs, (
        "assembled cross-process waterfall incomplete")
    assert inc_tail.get("tail_causes") is not None, (
        "flight-recorder incident must embed the cause histogram "
        f"(incidents: {info.get('health_incidents')})")
    emb = inc_tail["tail_causes"]["window"]
    assert emb.get("collective_straggler", 0) > 0, (
        f"the embedded cause histogram must carry the straggler: {emb}")
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "TAIL_r01.json")
    with open(out, "w", encoding="utf-8") as f:
        json.dump(art, f, indent=1)
        f.write("\n")
    print(f"committed {out}", file=sys.stderr)


def _game_day_mode(nprocs: int = 3, ndocs: int = 192,
                   scale: float = 1.0, smoke: bool = False) -> None:
    """--game-day (ISSUE 19 acceptance): a `nprocs`-process mesh under
    a workload-realistic soak (zipfian term popularity, burst/diurnal
    rate envelope, per-client identity so admission token buckets
    engage) while the chaos conductor schedules three OVERLAPPING
    faults from the faultinject registry over the do_meshfault wire:

    - F1 mesh.step straggle on member 1 during the traffic spike;
    - F2 device loss on member 2, held across F1's tail and F3's start;
    - F3 servlet.serving latency on the coordinator under a regular-
      servlet side-load.

    The verdict engine then joins the machine-readable fault schedule
    against the flight-recorder incident stream, the tail-cause
    verdicts and the straggler scoreboard, and CHAOS_r02.json commits
    one verdict row per fault: detected, attributed to the RIGHT cause
    label and member, 100%% answered during the window (degraded +
    counted, never a 5xx), bounded SLO recovery after the clear, and
    bit-identical rankings on the fully recovered fleet.

    `smoke` compresses the timeline; sub-rotation fault windows cannot
    drive the 30s-fixed histogram/conviction machinery, so smoke keeps
    only the availability and wire-plumbing gates.
    """
    import tempfile

    from yacy_search_server_tpu.parallel import distributed as D
    from yacy_search_server_tpu.parallel.launcher import MeshFleet
    from yacy_search_server_tpu.utils import gameday

    if smoke:
        scale = min(scale, 0.2)
    run_dir = tempfile.mkdtemp(prefix="gameday-")
    terms = list(D.CORPUS_TERMS)
    schedule = gameday.default_schedule(scale=scale)
    envelope = gameday.default_envelope(scale=scale)
    duration_s = round(215.0 * scale, 1)
    # construction-time knobs for the spawned members: a game-day-sized
    # incident cooldown (two distinct SLO incidents ~100s apart), an
    # admission bucket small enough that the zipf-head client actually
    # drains it during the spike, and a conviction window that fits two
    # evaluations inside F1's straggle
    overrides = {
        "health.incidentCooldownS": 35,
        "httpd.maxAccessPerHost.600s": 600,
        "actuator.admissionBurst": 15,
        "tail.convictionWindowS": 14,
        # mesh.serve roots gate on the FIXED tail.minMs floor (no
        # cached-p95 family — it would adapt to a fleet-wide straggle
        # and stop classifying it).  Float the floor above this CPU-
        # contended envelope's healthy collective wall (~75-90ms) and
        # safely below the 250/300ms scheduled faults, so baseline
        # traffic never floods `unattributed` while every fault-slowed
        # query still classifies.
        "tail.minMs": 150,
    }
    with MeshFleet(procs=nprocs, local_devices=2, ndocs=ndocs,
                   run_dir=run_dir, config=overrides) as fleet:
        cond = gameday.Conductor(fleet, schedule, terms, envelope,
                                 duration_s=duration_s)
        res = cond.run()
    art = {"metric": "game_day", "procs": nprocs, "ndocs": ndocs,
           "scale": scale, "smoke": smoke,
           "config_overrides": overrides, **res}
    print(json.dumps(art, indent=1))
    rows = art["schedule"]
    summary = art["verdict_summary"]
    # availability + plumbing gates hold at any scale: every request
    # answered (never a 5xx, never a hang), every scheduled fault has
    # armed/cleared wire acks and a wire-readable schedule trail
    assert summary["never_500"], art["workload"]["by_status"]
    assert len(rows) >= 3, rows
    for r in rows:
        assert r["armed_ts"] and r["cleared_ts"], r
        assert r["arm_ack"].get("result") == "ok", r
    assert art["overlaps"], "the schedule must overlap faults"
    wire = art["fault_wire_schedule"]
    for f in schedule:
        trail = wire.get(f"mesh{f.member}", [])
        assert any(e["point"] == f.point and e["action"] == "arm"
                   for e in trail), (f.point, trail)
    assert art["recovery"]["collective_resumed"], art["recovery"]
    assert art["bit_identity"]["identical"], art["bit_identity"]
    if smoke:
        print("smoke game day: availability + wire gates held",
              file=sys.stderr)
        return
    # the full acceptance: every scheduled fault's verdict row passes
    # (detected + attributed + answered + bounded recovery + bit-
    # identical) and the run produced zero unattributed verdicts
    for r in rows:
        assert r["verdict"] == "pass", json.dumps(r, indent=1)
    assert summary["all_pass"], summary
    assert summary["unattributed_verdicts"] == 0, summary
    # run-over-run trend (ISSUE 20 satellite): number this run as the
    # NEXT round after the committed CHAOS_r*.json artifacts and embed
    # the drill_trend diff against the newest committed round that has
    # a fault schedule (pre-M90 residues without one don't qualify) —
    # a verdict that regressed since the last drill is visible in the
    # artifact itself, not only to whoever remembers the old numbers
    import glob as _glob
    import re as _re

    from tools import drill_trend

    root = os.path.dirname(os.path.abspath(__file__))
    prior = sorted(_glob.glob(os.path.join(root, "CHAOS_r*.json")))
    rounds = [int(m.group(1)) for p in prior
              if (m := _re.search(r"CHAOS_r(\d+)\.json$", p))]
    art["round"] = max(rounds, default=0) + 1
    for p in reversed(prior):
        prev = drill_trend.load(p)
        if prev.get("schedule"):
            art["trend"] = drill_trend.trend(prev, art)
            art["trend"]["prev_artifact"] = os.path.basename(p)
            break
    out = os.path.join(root, f"CHAOS_r{art['round']:02d}.json")
    with open(out, "w", encoding="utf-8") as f:
        json.dump(art, f, indent=1)
        f.write("\n")
    print(f"committed {out}", file=sys.stderr)
    t = art.get("trend")
    if t:
        print(f"trend vs {t['prev_artifact']}: "
              f"{t['regressions']} regression(s), "
              f"{t['improvements']} improvement(s)", file=sys.stderr)


def _integrity_overhead_mode(n: int, threads: int = 16,
                             per_thread: int = 10, windows: int = 3,
                             budget_pct: float = 2.0):
    """--integrity-overhead (ISSUE 10): serving p50/p95 with read-side
    checksum verification (integrity.VERIFY_ON_READ) ON vs OFF on the
    shared `_ab_soak` harness.  Verification ships ON by default, so the
    budget is a pinned contract: p50 regression < `budget_pct`%.

    The measured windows run the DEPLOYED verification profile: lazy
    one-pass column checks on the metadata segments the result drain
    reads (the store is snapshotted so segments exist), span checksums
    on cold-tier materializations, and the per-read flag checks on
    every hot-path access.  Three gates: the p50 budget, a non-vacuous
    ON mode (verifications actually ran), and ZERO corruption /
    torn-tail events across the healthy soak — the same counters the
    headline artifact now carries."""
    from yacy_search_server_tpu.index import integrity
    from yacy_search_server_tpu.utils.hashes import word2hash

    sb = _build_served_switchboard(n, n_terms=2, mesh="off")
    assert sb.index.devstore is not None, "device serving must be on"
    sb.index.devstore._topk_cache.enabled = False
    # freeze the metadata tail: the drain then reads mmap'd segment
    # columns, whose lazy crc verification is part of the ON cost
    sb.index.metadata.snapshot()
    integrity.reset_counters()
    # prove the read-side machinery is live before measuring: a cold
    # span materialization (run span crc) and a run-index reopen
    # (footer crc) must both verify
    th0 = word2hash("benchterm0")
    for run in sb.index.rwi._runs:
        if run.path:
            sb.index.rwi.term_cache.invalidate((run.path, th0))
    sb.index.rwi.get(th0)
    assert integrity.verified_total() > 0, \
        "verification never ran — the ON windows would be vacuous"

    r = _ab_soak(sb, integrity.set_verify_on_read, threads=threads,
                 per_thread=per_thread, windows=windows)
    c = sb.index.devstore.counters()
    print(json.dumps({
        "metric": "integrity_overhead",
        "n_postings": n,
        "threads": threads,
        "queries_per_mode": r["queries_per_mode"],
        "p50_ms_verify_off": round(r["p50_off"], 3),
        "p50_ms_verify_on": round(r["p50_on"], 3),
        "p95_ms_verify_off": round(r["p95_off"], 3),
        "p95_ms_verify_on": round(r["p95_on"], 3),
        "overhead_pct": round(r["overhead_pct"], 3),
        "budget_pct": budget_pct,
        "verified_total": integrity.verified_total(),
        "storage_corruptions": c["storage_corruptions"],
        "journal_torn_tails": c["journal_torn_tails"],
        "device_losses": c["device_losses"],
        "device_loss_recoveries": c["device_loss_recoveries"],
    }))
    assert r["overhead_pct"] < budget_pct, (
        f"verify-on-read overhead {r['overhead_pct']:.2f}% exceeds the "
        f"{budget_pct}% stay-on-by-default budget")
    assert c["storage_corruptions"] == 0, \
        "corruption events on a healthy soak"
    assert c["journal_torn_tails"] == 0, \
        "torn-tail recoveries on a healthy soak"
    assert c["device_losses"] == 0 and c["device_lost_queries"] == 0, \
        "device-loss events on a healthy soak"


def _device_loss_soak_mode(n: int, threads: int = 8,
                           per_thread: int = 10):
    """--device-loss-soak (ISSUE 10c acceptance): inject a device loss
    under a concurrent serving soak and prove the acceptance shape on
    the REAL serving path — 100%% of queries answer (counted host
    fallback), the background rebuild returns to device serving
    automatically, and the post-recovery ranking is bit-identical to
    pre-loss.  Emits one JSON artifact block with the loss/recovery
    counters."""
    import threading as _threading

    from yacy_search_server_tpu.ops.ranking import RankingProfile
    from yacy_search_server_tpu.utils import faultinject
    from yacy_search_server_tpu.utils.hashes import word2hash

    sb = _build_served_switchboard(n, n_terms=2, mesh="off")
    ds = sb.index.devstore
    assert ds is not None, "device serving must be on"
    ds._topk_cache.enabled = False
    ds.transfer_retry_limit = 0
    ds.loss_streak = 1
    ds.rebuild_backoff_s = 0.2
    k_page = 10
    th0 = word2hash("benchterm0")
    prof = RankingProfile()
    pre = ds.rank_term(th0, prof, "en", k=k_page)
    assert pre is not None, "healthy device serving must work first"

    # declare the loss deterministically: the declaring fetch burns one
    # charge; once lost, queries short-circuit (no device work), so the
    # remaining charges only feed the rebuild's backoff probes
    faultinject.set_fault("device.transfer_fail", 6)
    assert ds.rank_term(th0, prof, "en", k=k_page) is None
    assert ds.device_lost, "loss must be declared"

    answered = []
    def worker(t):
        for _ in range(per_thread):
            sb.search_cache.clear()
            ev = sb.search(f"benchterm{t % 2}", count=k_page,
                           use_cache=False)
            assert len(ev.results()) == k_page, \
                "a query went unanswered during the loss"
            answered.append(1)
    ts = [_threading.Thread(target=worker, args=(t,))
          for t in range(threads)]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    soak_s = time.perf_counter() - t0
    total = threads * per_thread
    assert len(answered) == total
    lost_q = ds.device_lost_queries
    assert lost_q > 0, "the soak never exercised the host fallback"

    # automatic recovery: the rebuild drains the charges and re-uploads
    deadline = time.monotonic() + 60.0
    while ds.device_lost and time.monotonic() < deadline:
        time.sleep(0.1)
    assert not ds.device_lost, "rebuild never restored device serving"
    post = ds.rank_term(th0, prof, "en", k=k_page)
    assert post is not None, "post-recovery query must serve on device"
    np.testing.assert_array_equal(np.asarray(post[0]),
                                  np.asarray(pre[0]))
    np.testing.assert_array_equal(np.asarray(post[1]),
                                  np.asarray(pre[1]))
    c = ds.counters()
    print(json.dumps({
        "metric": "device_loss_soak",
        "n_postings": n,
        "threads": threads,
        "queries_during_loss": total,
        "queries_answered": len(answered),
        "answered_pct": 100.0,
        "host_fallback_queries": lost_q,
        "soak_seconds": round(soak_s, 2),
        "device_losses": c["device_losses"],
        "device_loss_recoveries": c["device_loss_recoveries"],
        "transfer_failures": c["transfer_failures"],
        "recovered_ranking_bit_identical": True,
        "counters": c,
    }))


def _federation_overhead_mode(n: int, threads: int = 16,
                              per_thread: int = 10, windows: int = 3,
                              budget_pct: float = 2.0):
    """--federation-overhead (ISSUE 5): serving p50/p95 with the fleet
    digest gossip ON vs OFF, interleaved windows (the --trace-overhead
    discipline).  The ON mode runs a 10 Hz gossip driver — digest
    render + two synthetic peer-digest ingests + mesh-percentile merges
    + staleness eviction per tick, i.e. the full gossip work at ~300x
    the deployed 30 s ping cadence — so the measured regression bounds
    the deployed overhead a fortiori.  Also asserts the rendered digest
    stays inside the 2 KiB wire budget under real serving load (the
    digest rides every peer exchange; bloat would tax the whole DHT)."""
    import gc
    import json as _json
    import threading as _threading

    from yacy_search_server_tpu.utils import fleet as fleet_mod
    from yacy_search_server_tpu.utils import histogram, tracing

    from contextlib import contextmanager

    sb = _build_served_switchboard(n, n_terms=2, mesh="off")
    assert sb.index.devstore is not None, "device serving must be on"
    sb.index.devstore._topk_cache.enabled = False
    fl = sb.fleet
    fl.my_hash = "benchnode000"
    fl.render_ttl_s = 0.0        # every gossip tick renders for real
    fl.send_interval_s = 0.0
    fl.stale_s = 10.0

    synth_seq = [0]

    def gossip_tick():
        synth_seq[0] += 1
        own = fl.render()
        # two synthetic peers echo realistically-shaped digests back
        # (the shape of a 3-node mesh under identical load)
        for i in (1, 2):
            d = _json.loads(fleet_mod.encode_digest(own))
            d["peer"] = f"benchpeer{i:03d}"
            d["seq"] = synth_seq[0]
            d["ts"] = time.time()
            fl.ingest(d)
        for fam in fleet_mod.DIGEST_FAMILIES:
            fl.mesh_percentile(fam, 0.95)
        fl.evict_stale()

    @contextmanager
    def driver(mode):
        if not mode:
            yield
            return
        gossip_stop = _threading.Event()

        def gossiper():
            while not gossip_stop.wait(0.1):
                gossip_tick()
        gthread = _threading.Thread(target=gossiper, daemon=True)
        gthread.start()
        try:
            yield
        finally:
            gossip_stop.set()
            gthread.join()

    def set_mode(mode):
        fl.enabled = mode

    # the serving wall as httpd records it (the bench hits
    # Switchboard.search directly, below the servlet layer): the
    # digest's SLO family must carry the measured windows' load
    r = _ab_soak(sb, set_mode, threads=threads, per_thread=per_thread,
                 windows=windows, window_driver=driver,
                 per_query=lambda wall: histogram.observe(
                     "servlet.serving", wall * 1000.0))
    # the digest rendered under full serving load (every window's
    # requests are in the histogram windows it compresses)
    gossip_tick()
    digest = fl.render()
    digest_bytes = fl.last_digest_bytes
    print(json.dumps({
        "metric": "federation_overhead",
        "n_postings": n,
        "threads": threads,
        "queries_per_mode": r["queries_per_mode"],
        "p50_ms_gossip_off": round(r["p50_off"], 3),
        "p50_ms_gossip_on": round(r["p50_on"], 3),
        "p95_ms_gossip_off": round(r["p95_off"], 3),
        "p95_ms_gossip_on": round(r["p95_on"], 3),
        "overhead_pct": round(r["overhead_pct"], 3),
        "budget_pct": budget_pct,
        "digest_bytes": digest_bytes,
        "digest_byte_budget": fl.byte_budget,
        "digest_families": sorted(digest.get("hist", {})),
        "digest_trimmed": bool(digest.get("trimmed")),
        "fleet_peers": len(fl.fresh()),
        "mesh_p95_ms": round(
            fl.mesh_percentile("servlet.serving", 0.95), 3),
    }))
    assert r["overhead_pct"] < budget_pct, (
        f"fleet gossip overhead {r['overhead_pct']:.2f}% exceeds the "
        f"{budget_pct}% stay-on-by-default budget")
    assert 0 < digest_bytes <= fl.byte_budget, (
        f"rendered digest {digest_bytes}B exceeds the "
        f"{fl.byte_budget}B wire budget")
    assert "servlet.serving" in digest.get("hist", {}), (
        "digest under serving load must carry the servlet.serving "
        "family (the mesh SLO surface)")
    assert not digest.get("trimmed"), (
        "real serving load must fit the digest budget without trimming")


def _rerank_overhead_mode(n: int, threads: int = 32, per_thread: int = 10,
                          windows: int = 3, noise_budget_pct: float = 15.0):
    """--rerank-overhead (ISSUE 6): hybrid serving p50 with the dense
    rerank routed through the pipelined batcher (batched, ON) vs solo
    dispatches of the same packed kernel (OFF), on the shared
    interleaved-window harness (_ab_soak). Every measured query runs
    hybrid=True, so each one pays a real rerank dispatch.

    Gates: (a) batched p50 is NO WORSE than solo — strict where round
    trips dominate (dispatch_rt >= 5 ms, where coalescing is the whole
    point), within a noise budget on locally-attached/CPU backends
    (dispatch floor is microseconds; the batcher adds bounded handoff
    cost); (b) the ON windows' counters show genuine coalescing — mean
    queries per rerank dispatch > 1 under the concurrent load."""
    from contextlib import contextmanager

    import numpy as np

    sb = _build_served_switchboard(n, n_terms=2, mesh="off")
    ds = sb.index.devstore
    assert ds is not None, "device serving must be on"
    assert ds._batcher is not None, "batching must be on"
    assert getattr(ds, "_dense", None) is not None, \
        "dense store must be attached (hybrid rerank path)"
    # every measured query must rank AND rerank: a topk-cache hit would
    # serve the full hybrid answer with zero device work
    ds._topk_cache.enabled = False
    _seed_dense_coverage(sb)

    on_disp = [0]
    on_queries = [0]

    @contextmanager
    def driver(mode):
        if not mode:
            yield
            return
        d0, q0 = ds.rerank_dispatches, ds.rerank_queries
        try:
            yield
        finally:
            on_disp[0] += ds.rerank_dispatches - d0
            on_queries[0] += ds.rerank_queries - q0

    def set_mode(mode):
        ds._rerank_batching = mode

    r = _ab_soak(sb, set_mode, threads=threads, per_thread=per_thread,
                 windows=windows, window_driver=driver, hybrid=True)
    mean_qpd = on_queries[0] / max(on_disp[0], 1)
    c = ds.counters()
    print(json.dumps({
        "metric": "rerank_overhead",
        "n_postings": n,
        "threads": threads,
        "queries_per_mode": r["queries_per_mode"],
        "p50_ms_solo": round(r["p50_off"], 3),
        "p50_ms_batched": round(r["p50_on"], 3),
        "p95_ms_solo": round(r["p95_off"], 3),
        "p95_ms_batched": round(r["p95_on"], 3),
        "overhead_pct": round(r["overhead_pct"], 3),
        "qps_solo": round(r["qps_off"], 3),
        "qps_batched": round(r["qps_on"], 3),
        "rerank_dispatches_batched_windows": on_disp[0],
        "rerank_queries_batched_windows": on_queries[0],
        "mean_queries_per_rerank_dispatch": round(mean_qpd, 3),
        "rerank_fallbacks": c["rerank_fallbacks"],
        "dispatch_rt_ms": ds.dispatch_rt_ms,
    }))
    assert on_disp[0] > 0, "batched windows produced no rerank dispatches"
    assert mean_qpd > 1.0, (
        f"batched windows coalesced {mean_qpd:.2f} queries per rerank "
        f"dispatch — batching is not forming under concurrent load")
    assert c["rerank_fallbacks"] == 0, (
        "hybrid queries fell back to the host-gather rerank path")
    # batched must be no worse than solo; where round trips dominate the
    # gate binds strictly, otherwise within the measurement-noise budget
    budget = 0.0 if ds.dispatch_rt_ms >= 5.0 else noise_budget_pct
    assert r["overhead_pct"] <= budget, (
        f"batched rerank p50 regressed {r['overhead_pct']:.2f}% vs solo "
        f"(budget {budget}%, dispatch_rt {ds.dispatch_rt_ms} ms)")


def _dense_first_mode(n_vec: int, threads: int = 16,
                      soak_s: float = 60.0, k: int = 10,
                      n_clusters: int = 2048, seed: int = 0):
    """--dense-first (ISSUE 11 acceptance): the IVF ANN candidate
    generator at corpus scale. Builds a served switchboard whose doc
    space carries `n_vec` synthetic clustered embeddings, indexes them
    int8-quantized into the hot(device)/warm(host LRU)/cold(mmap)
    ladder under the standard 2 GiB resident budget (1 GiB device hot
    arena + 1 GiB warm cache; the full slab lives on its mmap), then:

    - recall@k vs the EXACT host oracle (full chunked scan over the
      same quantized domain) across an nprobe ladder — the
      recall-vs-latency curve, gated >= 0.9 at the default nprobe;
    - a `soak_s` concurrent soak of hybrid dense-first queries through
      Switchboard.search (sparse rank + batched ann probe + fusion +
      result materialization), with the standard counters and the ANN
      kernels' roofline util_pct carried in the artifact.

    The fused-list tie discipline across solo/batched/cached paths is
    pinned by tests/test_ann.py, referenced from the artifact."""
    import atexit
    import os
    import shutil
    import socket
    import tempfile
    import threading as _th

    from yacy_search_server_tpu.index.annstore import AnnVectorIndex
    from yacy_search_server_tpu.ops.ann import ANN_DEFAULT_NPROBE
    from yacy_search_server_tpu.ops.dense import DIM
    from yacy_search_server_tpu.utils import tracing
    from yacy_search_server_tpu.utils.profiler import PROFILER

    t_start = time.time()
    dim = DIM
    hot_budget = 1 << 30
    warm_budget = 1 << 30
    resident_budget = 2 << 30           # the standard 2 GiB budget
    print(f"# building served switchboard: {n_vec} docs / 2 terms",
          file=sys.stderr, flush=True)
    sb = _build_served_switchboard(n_vec, n_terms=2, mesh="off")
    ds = sb.index.devstore
    assert ds is not None and ds._batcher is not None
    ds._topk_cache.enabled = False      # every query probes
    ds.ann_probe_lanes = 1 << 16
    # slow-envelope watchdog: a dense-first wave's fused gather is a
    # multi-second kernel on a 1-core CPU box — honest progress the
    # default 2 s watchdog would misread as worker_stall and churn
    # into timeout/solo retries (the stall-zero gate below still
    # binds, now against REAL wedges)
    watchdog_s = 60.0
    ds._batcher.WATCHDOG_S = watchdog_s
    threads = min(threads, 8)
    _seed_dense_coverage(sb)

    # synthetic clustered corpus (f16 RAM staging; the quantized slab
    # the index builds is what serves). Cluster structure stands in for
    # the topical locality a real embedding corpus has — IVF recall on
    # structureless noise is a property of noise, not of the index.
    print(f"# generating {n_vec} clustered vectors (dim {dim})",
          file=sys.stderr, flush=True)
    rng = np.random.default_rng(seed)
    gen_c = 1024
    centers = rng.standard_normal((gen_c, dim)).astype(np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    lab = rng.integers(0, gen_c, n_vec)
    vecs = np.empty((n_vec, dim), np.float16)
    chunk = 1 << 19
    # per-dim noise scaled so the noise VECTOR's norm is ~0.5 of the
    # unit center (cos to the center ~0.9) — the topical-locality
    # strength a real embedding corpus has; a dimension-independent
    # scalar here would bury the structure in dim-256 noise
    sigma = 0.5 / float(np.sqrt(dim))
    for i0 in range(0, n_vec, chunk):
        i1 = min(i0 + chunk, n_vec)
        v = centers[lab[i0:i1]] \
            + sigma * rng.standard_normal((i1 - i0, dim)) \
            .astype(np.float32)
        v /= np.linalg.norm(v, axis=1, keepdims=True)
        vecs[i0:i1] = v.astype(np.float16)
    ann_dir = tempfile.mkdtemp(prefix="yacytpu-ann-")
    atexit.register(shutil.rmtree, ann_dir, ignore_errors=True)
    ann = AnnVectorIndex(dim, data_dir=ann_dir,
                         device_budget_bytes=hot_budget,
                         warm_budget_bytes=warm_budget)
    print(f"# k-means + assignment + slab build (C={n_clusters})",
          file=sys.stderr, flush=True)
    t0 = time.perf_counter()
    ann.build(lambda a, b: vecs[a:b], n_vec, n_clusters=n_clusters,
              sample_n=65536, iters=2, seed=seed + 1, chunk=chunk)
    build_s = time.perf_counter() - t0
    sb.index.ann = ann
    ds.attach_ann(ann)
    ann.hot_block(ds.arena.device)      # upload the hot arena once
    del vecs                            # the slab serves from here on
    tb = ann.tier_bytes()
    resident = tb["hot"] + tb["warm"]
    print(f"# ann built in {build_s:.0f}s: hot {tb['hot'] >> 20} MiB, "
          f"cold(mmap) {tb['cold'] >> 20} MiB", file=sys.stderr,
          flush=True)

    # -- recall-vs-latency curve vs the exact host oracle -------------
    nq = 20
    qs = centers[rng.integers(0, gen_c, nq)] \
        + sigma * rng.standard_normal((nq, dim)).astype(np.float32)
    qs /= np.linalg.norm(qs, axis=1, keepdims=True)
    print("# exact oracle pass (full chunked scan)", file=sys.stderr,
          flush=True)
    t0 = time.perf_counter()
    exact = [set(ann.exact_topk(q, k)[1].tolist()) for q in qs]
    oracle_s = time.perf_counter() - t0
    curve = []
    for nprobe in (1, 2, 4, ANN_DEFAULT_NPROBE, 16):
        hits = 0
        walls = []
        for qi, q in enumerate(qs):
            t0 = time.perf_counter()
            got = ds.dense_first_topk(q, [], [], 1.0, k, nprobe=nprobe)
            walls.append((time.perf_counter() - t0) * 1000.0)
            hits += len(set(got[1].tolist()) & exact[qi])
        walls.sort()
        curve.append({
            "nprobe": nprobe,
            "recall_at_k": round(hits / (nq * k), 4),
            "p50_ms": round(tracing._pctl(walls, 0.50), 2),
            "p95_ms": round(tracing._pctl(walls, 0.95), 2),
        })
        print(f"# nprobe {nprobe}: recall@{k} "
              f"{curve[-1]['recall_at_k']}, p50 {curve[-1]['p50_ms']} "
              f"ms", file=sys.stderr, flush=True)
    recall_default = next(c["recall_at_k"] for c in curve
                          if c["nprobe"] == ANN_DEFAULT_NPROBE)

    # -- the serving soak: hybrid dense-first through sb.search -------
    print(f"# {threads}-thread dense-first soak, {soak_s:.0f}s",
          file=sys.stderr, flush=True)
    for t in range(2):                  # warm both terms' compile shapes
        ev = sb.search(f"benchterm{t}", count=k, dense_first=True,
                       use_cache=False)
        assert len(ev.results()) == k
    import gc
    gc.collect()
    gc.freeze()
    PROFILER.clear()
    c0 = ds.counters()
    annq0, annd0 = c0["ann_queries"], c0["ann_dispatches"]
    lats: list = []
    lat_lock = _th.Lock()
    deadline = time.perf_counter() + soak_s
    done = [0] * threads

    def worker(t):
        while time.perf_counter() < deadline:
            sb.search_cache.clear()
            q0 = time.perf_counter()
            ev = sb.search(f"benchterm{t % 2}", count=k,
                           dense_first=True, use_cache=False)
            assert len(ev.results()) == k
            wall = time.perf_counter() - q0
            with lat_lock:
                lats.append(wall)
            done[t] += 1

    ts = [_th.Thread(target=worker, args=(t,)) for t in range(threads)]
    t0 = time.perf_counter()
    for th in ts:
        th.start()
    for th in ts:
        th.join()
    wall_s = time.perf_counter() - t0
    lats.sort()
    c = ds.counters()
    ann_queries = c["ann_queries"] - annq0
    util = {p.kernel: {"util_pct": round(p.util_pct, 3),
                       "bound": p.bound}
            for p in PROFILER.snapshot()
            if p.kernel.startswith("_ann_")}
    out = {
        "metric": "dense_first",
        "host": socket.gethostname(),
        "envelope": f"{os.cpu_count()}-core CPU (JAX_PLATFORMS="
                    f"{os.environ.get('JAX_PLATFORMS', 'default')}; "
                    f"batcher watchdog {watchdog_s:.0f}s for the "
                    "multi-second 1-core kernel walls)",
        "n_vectors": n_vec,
        "dim": dim,
        "n_clusters": ann.n_clusters(),
        "quantization": "int8 + f16 per-vector scale "
                        f"({ann.row_bytes} B/vector vs {2 * dim} B "
                        "f16: "
                        f"{round(2 * dim / ann.row_bytes, 2)}x)",
        "budget": {
            "resident_budget_bytes": resident_budget,
            "hot_device_bytes": tb["hot"],
            "warm_host_bytes": tb["warm"],
            "cold_mmap_bytes": tb["cold"],
            "resident_bytes": resident,
        },
        "build_s": round(build_s, 1),
        "oracle_scan_s": round(oracle_s, 1),
        "recall_curve": curve,
        "recall_at_k_default_nprobe": recall_default,
        "nprobe_default": ANN_DEFAULT_NPROBE,
        "soak": {
            "threads": threads,
            "duration_s": round(wall_s, 1),
            "queries": len(lats),
            "qps": round(len(lats) / wall_s, 2),
            "p50_ms": round(tracing._pctl(lats, 0.50) * 1000.0, 2),
            "p95_ms": round(tracing._pctl(lats, 0.95) * 1000.0, 2),
            "ann_queries": ann_queries,
            "ann_dispatches": c["ann_dispatches"] - annd0,
            "mean_queries_per_ann_dispatch": round(
                ann_queries / max(c["ann_dispatches"] - annd0, 1), 2),
        },
        "counters": {key: c[key] for key in (
            "ann_fallbacks", "ann_host_queries", "ann_tier_hot_hits",
            "ann_tier_warm_hits", "ann_tier_cold_hits",
            "ann_promotions", "ann_promote_failures", "ann_lane_drops",
            "batch_timeout_worker_stall", "storage_corruptions",
            "device_lost")},
        "ann_kernel_util": util,
        "tie_discipline": "(score DESC, docid ASC) pinned across "
                          "solo/batched/cached dense-first paths by "
                          "tests/test_ann.py",
        "total_wall_s": round(time.time() - t_start, 1),
    }
    print(json.dumps(out, indent=1))
    assert recall_default >= 0.9, (
        f"recall@{k} {recall_default} < 0.9 at the default nprobe")
    assert resident <= resident_budget, (
        f"resident ladder bytes {resident} exceed the 2 GiB budget")
    assert c["batch_timeout_worker_stall"] == 0
    assert c["storage_corruptions"] == 0
    assert ann_queries >= len(lats), \
        "some soak queries skipped the dense-first probe"


def _capacity_feats(rng, n: int) -> "np.ndarray":
    """Posting attributes with REALISTIC column ranges (the semantics of
    index/postings.py: counts, clipped positions, day stamps, small
    bitfields). The classic bench corpus draws uniform 0..1000 in every
    column — a 10-bit-entropy-everywhere adversary no crawl produces —
    so the capacity corpus states the compression claim on honest
    ranges. All values stay inside the int16 compact-block domain, so
    the int16 and packed paths score identical inputs."""
    from yacy_search_server_tpu.index import postings as P
    feats = np.zeros((n, P.NF), np.int32)
    feats[:, P.F_LASTMOD] = rng.integers(18000, 20000, n)  # ~5y window
    feats[:, P.F_WORDS_IN_TITLE] = rng.integers(0, 24, n)
    feats[:, P.F_WORDS_IN_TEXT] = rng.integers(0, 2000, n)
    feats[:, P.F_PHRASES_IN_TEXT] = rng.integers(0, 200, n)
    feats[:, P.F_DOCTYPE] = rng.integers(0, 8, n)
    feats[:, P.F_LANGUAGE] = P.pack_language("en")
    feats[:, P.F_LLOCAL] = rng.integers(0, 100, n)
    feats[:, P.F_LOTHER] = rng.integers(0, 100, n)
    feats[:, P.F_URL_LENGTH] = rng.integers(10, 200, n)
    feats[:, P.F_URL_COMPS] = rng.integers(1, 16, n)
    feats[:, P.F_FLAGS] = rng.integers(0, 2 ** 20, n)
    feats[:, P.F_HITCOUNT] = rng.integers(1, 255, n)
    feats[:, P.F_POSINTEXT] = rng.integers(1, 4096, n)
    feats[:, P.F_POSINPHRASE] = rng.integers(0, 128, n)
    feats[:, P.F_POSOFPHRASE] = rng.integers(0, 128, n)
    feats[:, P.F_WORDDISTANCE] = rng.integers(0, 64, n)
    feats[:, P.F_DOMLENGTH] = rng.integers(0, 256, n)
    return feats


def _capacity_row(total: int, threads: int, soak_s: float, k: int,
                  batch_size: int, budget_bytes: int,
                  per_term: int = 5_000_000) -> dict:
    """One --capacity measurement row: a `total`-posting packed-residency
    devstore under the shared 2 GiB arena budget, soaked with `threads`
    rank_term searchers (top-k cache disabled: every query dispatches).
    Returns p50/p95/qps + the compression + roofline + tier surfaces."""
    import threading as _th

    from yacy_search_server_tpu.index import postings as P
    from yacy_search_server_tpu.index.devstore import DeviceSegmentStore
    from yacy_search_server_tpu.index.postings import PostingsList
    from yacy_search_server_tpu.index.rwi import RWIIndex
    from yacy_search_server_tpu.ops.ranking import RankingProfile
    from yacy_search_server_tpu.utils.hashes import word2hash
    from yacy_search_server_tpu.utils.profiler import PROFILER

    rng = np.random.default_rng(41)
    rwi = RWIIndex()
    terms = []
    left = total
    ti = 0
    while left > 0:
        n = min(per_term, left)
        th = word2hash(f"capterm{ti}")
        docids = np.arange(n, dtype=np.int32)
        rwi.ingest_run({th: PostingsList(docids, _capacity_feats(rng, n))})
        terms.append(th)
        left -= n
        ti += 1
    t_pack = time.perf_counter()
    ds = DeviceSegmentStore(rwi, budget_bytes=budget_bytes,
                            packed_residency=True)
    pack_s = time.perf_counter() - t_pack
    ds.enable_batching(max_batch=batch_size, dispatchers=4, prewarm=False)
    ds._topk_cache.enabled = False
    prof = RankingProfile()
    hot = sum(1 for e in ds._pblocks.values() if e["hot"])
    print(json.dumps({"metric": "capacity_pack", "postings": total,
                      "terms": len(terms), "hot_terms": hot,
                      "pack_seconds": round(pack_s, 1)}),
          file=sys.stderr)
    # warm every term's compile shapes + promote any warm overflow
    # (bounded: a term the budget cannot hold hot stays warm and its
    # queries fall back — counted, never crashed on)
    for th in terms:
        warm_deadline = time.monotonic() + 30.0
        while time.monotonic() < warm_deadline:
            if ds.rank_term(th, prof, "en", k=k) is not None:
                break
            time.sleep(0.2)
    PROFILER.clear()
    lats: list = []
    misses = [0]
    lk = _th.Lock()
    served0 = ds.queries_served
    rt0 = ds.device_round_trips
    deadline = time.perf_counter() + soak_s

    def worker(t):
        i = 0
        while time.perf_counter() < deadline:
            th = terms[(t + i) % len(terms)]
            q0 = time.perf_counter()
            r = ds.rank_term(th, prof, "en", k=k)
            with lk:
                if r is None:
                    # warm/cold term: the product's host path would
                    # serve it — here it counts as a paging miss and
                    # stays in the latency record as the tier ladder's
                    # cost, not a crash
                    misses[0] += 1
                else:
                    assert len(r[0]) == k
                lats.append(time.perf_counter() - q0)
            i += 1

    ts = [_th.Thread(target=worker, args=(t,)) for t in range(threads)]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    dt = time.perf_counter() - t0
    lats.sort()
    served = ds.queries_served - served0
    # roofline: the packed pruned kernel's achieved GB/s vs peak
    pt = next((p for p in PROFILER.snapshot()
               if p.kernel == "_rank_pruned_batch1_bp_kernel"), None)
    with ds._lock:
        packed_bytes = sum(e["block"].packed_bytes
                           for e in ds._pblocks.values())
        int16_bytes = sum(e["block"].int16_bytes
                          for e in ds._pblocks.values())
        row_bits = [e["block"].row_bits for e in ds._pblocks.values()]
    c = ds.counters()
    row = {
        "postings": total,
        "terms": len(terms),
        "qps": round(served / dt, 3),
        "p50_ms": round(lats[len(lats) // 2] * 1000, 2) if lats else 0.0,
        "p95_ms": round(lats[int(len(lats) * 0.95)] * 1000, 2)
        if lats else 0.0,
        "queries": served,
        "soak_seconds": round(dt, 1),
        "pack_seconds": round(pack_s, 1),
        "compression_ratio": c["packed_compression_ratio"],
        "bytes_per_posting_packed": round(packed_bytes / total, 2),
        "bytes_per_posting_int16": round(int16_bytes / total, 2),
        "row_bits_mean": round(sum(row_bits) / max(len(row_bits), 1), 1),
        "achieved_gbps": round(pt.achieved_bytes_per_s / 1e9, 4)
        if pt else 0.0,
        "util_pct": pt.util_pct if pt else 0.0,
        "bound": pt.bound if pt else "",
        "rt_per_query": round((ds.device_round_trips - rt0)
                              / max(served, 1), 4),
        "host_fallbacks": misses[0],
        "tier_counters": {kk: c[kk] for kk in c
                          if kk.startswith("tier_")},
    }
    ds.close()
    return row


def _capacity_mode(n_max: int, threads: int, soak_s: float, k: int,
                   batch_size: int):
    """--capacity (ISSUE 8): the compressed-residency capacity soak.
    Measures the 10M reference row and the >=50M capacity row on the
    same silicon, same budget — corpus size as a tiering decision, not
    an HBM ceiling. Gates: p95(50M) <= 2x p95(10M); measured HBM
    bytes/posting reduced >= 2x vs the int16 block format; the artifact
    always carries the compression ratio and per-tier counters
    (tests/test_code_hygiene.py validates the committed file)."""
    import jax

    budget = 2 << 30
    n_max = max(n_max, 50_000_000)
    rows = [_capacity_row(10_000_000, threads, soak_s, k, batch_size,
                          budget),
            _capacity_row(n_max, threads, soak_s, k, batch_size, budget)]
    p95_ratio = rows[1]["p95_ms"] / max(rows[0]["p95_ms"], 1e-9)
    # int16 residency at the capacity point, modeled the way the arena
    # actually admits rows (doubling growth from the 4*TILE initial
    # capacity, one spare tile): raw bytes/posting alone understates the
    # footprint the budget check sees
    from yacy_search_server_tpu.index.devstore import DeviceArena
    cap_rows = 4 * 32_768
    while cap_rows < n_max + 32_768:
        cap_rows *= 2
    int16_need = cap_rows * DeviceArena.row_bytes()
    out = {
        "metric": "capacity",
        "device": jax.devices()[0].platform,
        "threads": threads,
        "budget_bytes": budget,
        "rows": rows,
        "p95_ratio_vs_10m": round(p95_ratio, 3),
        "gate_p95_2x": bool(p95_ratio <= 2.0),
        # the point of the exercise, stated in the artifact: the int16
        # format could not hold the capacity row under this budget
        "int16_bytes_at_max": int16_need,
        "int16_fits_budget": bool(int16_need <= budget),
        "bytes_reduction_vs_int16": round(
            rows[1]["bytes_per_posting_int16"]
            / max(rows[1]["bytes_per_posting_packed"], 1e-9), 3),
    }
    print(json.dumps(out))
    assert out["gate_p95_2x"], (
        f"capacity p95 {rows[1]['p95_ms']} ms is "
        f"{p95_ratio:.2f}x the 10M row (budget 2x)")
    assert out["bytes_reduction_vs_int16"] >= 2.0, (
        f"packed bytes/posting only {out['bytes_reduction_vs_int16']}x "
        f"below int16 (claim needs >= 2x)")
    return out


def _tier_overhead_mode(n: int, threads: int = 8, per_thread: int = 12,
                        windows: int = 5,
                        noise_budget_pct: float = 15.0):
    """--tier-overhead (ISSUE 8): serving p50 with the tier ladder's
    BOOKKEEPING (per-query LRU touch, miss-path tier lookups, promotion
    triggers) on vs off, on the shared interleaved-window harness
    (_ab_soak), with a fully hot-tier working set — the idle-path gate:
    when nothing needs paging, tiering must cost < 2% p50 (strict where
    round trips dominate; a noise budget on CPU/local backends, same
    discipline as --rerank-overhead). Thread count stays below the
    other modes' 16: the bookkeeping under test is nanoseconds per
    query, and a 1-core box's 16-thread dispatch convoy swamps it with
    multi-second scheduling variance (median-of-5 windows at 8 threads
    keeps the A/B honest)."""
    cfg_extra = {"index.device.packedResidency": "true"}
    sb = _build_served_switchboard(n, n_terms=2, mesh="off",
                                   config_extra=cfg_extra)
    ds = sb.index.devstore
    assert ds is not None and ds.packed_residency
    assert all(e["hot"] for e in ds._pblocks.values()), \
        "tier-overhead gate needs a fully hot working set"
    ds._topk_cache.enabled = False

    def set_mode(mode):
        ds._tiering_enabled = mode

    r = _ab_soak(sb, set_mode, threads=threads, per_thread=per_thread,
                 windows=windows)
    c = ds.counters()
    print(json.dumps({
        "metric": "tier_overhead",
        "n_postings": n,
        "threads": threads,
        "queries_per_mode": r["queries_per_mode"],
        "p50_ms_off": round(r["p50_off"], 3),
        "p50_ms_on": round(r["p50_on"], 3),
        "p95_ms_off": round(r["p95_off"], 3),
        "p95_ms_on": round(r["p95_on"], 3),
        "overhead_pct": round(r["overhead_pct"], 3),
        "tier_hot_hits": c["tier_hot_hits"],
        "tier_promotions_warm_hot": c["tier_promotions_warm_hot"],
        "compression_ratio": c["packed_compression_ratio"],
        "dispatch_rt_ms": ds.dispatch_rt_ms,
    }))
    assert c["tier_promotions_warm_hot"] == 0, \
        "hot-only working set must not promote"
    budget = 2.0 if ds.dispatch_rt_ms >= 5.0 else noise_budget_pct
    assert r["overhead_pct"] <= budget, (
        f"tier bookkeeping p50 overhead {r['overhead_pct']:.2f}% "
        f"(budget {budget}%, dispatch_rt {ds.dispatch_rt_ms} ms)")


def _mesh_procs_mode(nprocs: int, ndocs: int, soak_s: float,
                     k: int = 10, local_devices: int = 2):
    """--mesh-procs (ISSUE 12 acceptance): drive a REAL multi-process
    SPMD mesh — N OS processes brought up via jax.distributed by the
    launcher, queries over the HTTP wire, fusion as cross-process
    collectives — through a sustained soak, and commit
    MULTICHIP_r06.json with per-process q/s, the fusion-collective wall
    from the mesh.collective histogram, digest bytes and the
    zero-worker_stall gate (the --capacity committed-artifact
    validation discipline)."""
    import os
    import tempfile

    import jax as _jax

    from yacy_search_server_tpu.ops.ranking import RankingProfile
    from yacy_search_server_tpu.parallel import distributed as D
    from yacy_search_server_tpu.parallel.launcher import MeshFleet
    from yacy_search_server_tpu.switchboard import Switchboard
    from yacy_search_server_tpu.utils.config import Config
    from yacy_search_server_tpu.utils.hashes import word2hash

    cells = nprocs * local_devices
    # the single-process reference over the SAME cell layout: the
    # artifact's bit-identity gate is measured, not asserted from faith
    cfg = Config()
    cfg.set("index.device.serving", "false")
    sb = Switchboard(data_dir=None, config=cfg)
    D.build_corpus(sb, ndocs, 3, n_doc=cells)
    ref_devs = _jax.devices("cpu")[:cells]
    # the bit-identity gate is "same cell layout, different process
    # count" — a silently smaller reference mesh would pass the gate
    # for the wrong reason (tie-discipline layout-independence)
    assert len(ref_devs) == cells, (
        f"need {cells} virtual CPU devices for the single-process "
        f"reference (set XLA_FLAGS=--xla_force_host_platform_"
        f"device_count={cells}), have {len(ref_devs)}")
    ms = sb.index.enable_mesh_serving(devices=ref_devs, n_term=1)
    ms.small_rank_n = 0
    terms = list(D.CORPUS_TERMS)
    ref = {}
    for w in terms:
        out = ms.rank_term(word2hash(w), RankingProfile(), k=k)
        ref[w] = (np.asarray(out[0]).tolist(),
                  np.asarray(out[1]).tolist())
    sb.close()

    run_dir = tempfile.mkdtemp(prefix="meshprocs-")
    with MeshFleet(procs=nprocs, local_devices=local_devices,
                   ndocs=ndocs, run_dir=run_dir) as fleet:
        for w in terms:                      # warm every compile shape
            fleet.search(w, k=k)
        bit_identical = all(
            (lambda r: r["scores"] == ref[w][0]
             and r["docids"] == ref[w][1])(fleet.search(w, k=k))
            for w in terms)
        # per-process counters snapshot AFTER warmup/bit-identity:
        # qps must be soak-only (warmup + compile queries divided by
        # the soak wall would inflate every per-process rate)
        pre = {i: fleet.info(i)["runtime"]["queries_total"]
               for i in range(nprocs)}
        pre_hist = fleet.info(0)["collective_hist"]["count"]
        t0 = time.perf_counter()
        asked = answered = collective = 0
        deadline = t0 + soak_s
        while time.perf_counter() < deadline:
            rep = fleet.search(terms[asked % len(terms)], k=k)
            asked += 1
            if rep["scores"]:
                answered += 1
            if rep["mode"] == "collective":
                collective += 1
        wall = time.perf_counter() - t0
        infos = [fleet.info(i) for i in range(nprocs)]
    per_process = [{
        "proc": inf["proc"], "pid": inf["pid"],
        "qps": round((inf["runtime"]["queries_total"]
                      - pre[inf["proc"]]) / wall, 3),
        "soak_queries": inf["runtime"]["queries_total"]
        - pre[inf["proc"]],
        **inf["runtime"],
        "collective_hist": inf["collective_hist"],
        "worker_stall":
            inf["counters"]["batch_timeout_worker_stall"],
        "arena_epoch": inf["counters"]["arena_epoch"],
    } for inf in infos]
    pids = {p["pid"] for p in per_process}
    worker_stall = sum(p["worker_stall"] for p in per_process)
    art = {
        "metric": "mesh_procs_soak",
        "procs": nprocs, "local_devices": local_devices,
        "cells": cells, "ndocs": ndocs, "k": k,
        "soak_s": round(wall, 3),
        "queries": asked, "answered": answered,
        "collective_served": collective,
        "qps": round(asked / wall, 3),
        "bit_identical_vs_single_process": bool(bit_identical),
        "distinct_pids": len(pids),
        # the histogram count includes warmup/compile dispatches; the
        # soak-only share is stated next to it so percentiles are read
        # in context
        "fusion_collective_ms": {
            **infos[0]["collective_hist"],
            "soak_count": infos[0]["collective_hist"]["count"]
            - pre_hist},
        "digest_bytes": infos[0]["digest_bytes"],
        "worker_stall": worker_stall,
        "incidents": infos[0]["incidents"],
        "per_process": per_process,
        "ok": bool(bit_identical and answered == asked
                   and len(pids) == nprocs and worker_stall == 0),
    }
    print(json.dumps(art, indent=1))
    # validation gates (the --capacity committed-artifact discipline:
    # a failing soak must not commit a green-looking artifact)
    assert answered == asked, "availability gate: every query answers"
    assert bit_identical, "bit-identity gate vs single-process mesh"
    assert len(pids) == nprocs, "PID gate: fleet must span processes"
    assert worker_stall == 0, "zero worker_stall gate"
    assert infos[0]["collective_hist"]["count"] > 0, \
        "fusion collective histogram is empty"
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "MULTICHIP_r06.json")
    with open(out, "w", encoding="utf-8") as f:
        json.dump(art, f, indent=1)
        f.write("\n")
    print(f"committed {out}", file=sys.stderr)


def _ingest_soak_mode(n: int, docs_per_s: float, soak_s: float,
                      threads: int = 8, k: int = 10,
                      smoke: bool = False):
    """--ingest-soak (ISSUE 13 acceptance): sustained indexing at
    `docs_per_s` THROUGH the product write path (parse → condense →
    store → bounded-buffer flush → device pack) under the standard
    query soak, against a packed-residency devstore with the device
    index build on.  Four proofs in one run:

    1. **serving under ingest** — query p95 with the ingest stream live
       must stay within 1.25x of the no-ingest baseline measured
       seconds earlier on the same store;
    2. **crawl-to-searchable SLO** — every ingested doc is stamped at
       pipeline entry; the artifact reports windowed p50/p95 per tier
       (searchable / flushed / device) plus the backpressure wall;
    3. **zero acked-doc loss under concurrent serving** — the M84
       kill−9 barriers `rwi.flush.before_manifest` and
       `rwi.manifest.mid_write` fire MID-SOAK in chaos subprocesses
       whose own query thread is live through the kill, and recovery
       (with live query threads) must preserve every acked batch with
       zero query errors;
    4. **the merge-deferral actuator engaging** — an injected
       servlet-latency burst over the real HTTP wire burns the serving
       SLO, the health tick flips `merge_scheduler` to deferred (the
       cleanup job's merge ask parks, counted), recovery runs the
       catch-up — both breadcrumbs gated.

    `--smoke` is the tier-1 variant (seconds); the full run commits
    INGEST_r01.json (the --capacity committed-artifact discipline)."""
    import os
    import signal as _signal
    import subprocess
    import tempfile
    import threading as _th
    import urllib.request

    from yacy_search_server_tpu.document.parser.registry import \
        parse_source
    from yacy_search_server_tpu.ingest import slo as ingest_slo
    from yacy_search_server_tpu.server.httpd import YaCyHttpServer
    from yacy_search_server_tpu.utils import faultinject, histogram
    from yacy_search_server_tpu.utils.histogram import \
        percentile_from_counts

    window_s = max(2.0, soak_s)
    sb = _build_served_switchboard(
        n, n_terms=4, mesh="off",
        config_extra={"index.device.packedResidency": "true",
                      "ingest.deviceBuild": "true",
                      "health.sloMinQps": "0.05",
                      "actuator.recoverTicks": "2"})
    ds = sb.index.devstore
    assert ds is not None and ds.packed_residency \
        and ds.ingest_device_build
    seed_builds = ds.ingest_device_builds
    assert seed_builds > 0, \
        "seed corpus must pack through the device build kernel"
    # fresh docs draw their 60 body words from a 12-term space, so one
    # flush's per-term blocks are RUN-scale (comfortably above
    # devbuild.MIN_DEV_ROWS — the device build lays them down, not the
    # long-tail host path) — a crawl focused on a topic, not 1-posting
    # stubs.  The buffer freezes every ~96 docs (~15 postings/doc), so
    # a full soak window sees flush+pack cycles at a steady cadence.
    def fresh_doc(i: int, prefix: str = "fresh"):
        body = " ".join(f"{prefix}{(i * 7 + j) % 12}"
                        for j in range(60))
        html = (f"<html><head><title>{prefix} {i}</title></head>"
                f"<body><p>{body}</p></body></html>").encode()
        return parse_source(f"http://{prefix}{i % 23}.soak/d{i}.html",
                            "text/html", html)[0]

    rwi = sb.index.rwi
    rwi.max_ram_postings = 96 * 15

    qlock = _th.Lock()

    def query_soak(duration: float) -> tuple[float, float, float]:
        """`threads` searchers through Switchboard.search for
        `duration` s; returns (qps, p50_ms, p95_ms)."""
        lats: list = []
        deadline = time.perf_counter() + duration
        done = [0] * threads

        def worker(t):
            i = 0
            while time.perf_counter() < deadline:
                sb.search_cache.clear()
                q0 = time.perf_counter()
                ev = sb.search(f"benchterm{t % 4}", count=k,
                               use_cache=False)
                assert len(ev.results()) == k
                with qlock:
                    lats.append(time.perf_counter() - q0)
                i += 1
                done[t] = i

        ts = [_th.Thread(target=worker, args=(t,))
              for t in range(threads)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        dt = time.perf_counter() - t0
        lats.sort()
        return (sum(done) / dt,
                lats[len(lats) // 2] * 1000 if lats else 0.0,
                lats[int(len(lats) * 0.95)] * 1000 if lats else 0.0)

    # -- warmup: the full write cycle, twice ---------------------------------
    # two ingest->flush->device-pack rounds at the soak's own flush
    # granularity compile the pack kernel's pow2 (batch, rows) bucket
    # shapes BEFORE any measured window — otherwise the first mid-soak
    # flush pays a multi-second XLA compile that says nothing about
    # steady-state ingest (the same reason _build_served_switchboard
    # prewarms the serving kernels)
    wi = 0
    for _round in range(2):
        flushed0 = ingest_slo.TRACKER.counters()["docs_flushed"]
        deadline = time.monotonic() + 60.0
        while ingest_slo.TRACKER.counters()["docs_flushed"] == flushed0 \
                and time.monotonic() < deadline:
            sb.index.store_document(fresh_doc(wi, prefix="warm"),
                                    crawldepth=1)
            wi += 1
        assert ingest_slo.TRACKER.counters()["docs_flushed"] \
            > flushed0, "warmup never reached a flush"
    warm_builds = ds.ingest_device_builds
    # the artifact's SLO table must describe the SOAK, not the warmup's
    # store-time-stamped docs (near-zero walls that dilute percentiles)
    histogram.reset()

    # -- phases A/B: interleaved no-ingest / ingest windows ------------------
    # the A/B gate rides the median of interleaved windows (the
    # _ab_soak discipline every overhead mode uses): a single pair of
    # windows on a busy box flaps the 1.25x verdict on scheduler noise
    stop = _th.Event()
    running = _th.Event()                    # cleared = ingest paused
    ingested = [0]
    ingest_errors = [0]

    def ingest_worker():
        i = 0
        i0, t0 = 0, time.perf_counter()
        while not stop.is_set():
            if not running.is_set():
                running.wait(0.05)
                # re-base the pacing on resume: the paced target must
                # never make the stream SPRINT to repay a paused window
                i0, t0 = i, time.perf_counter()
                continue
            target = i0 + (time.perf_counter() - t0) * docs_per_s
            if i >= target:
                time.sleep(min(0.02, (i - target + 1) / docs_per_s))
                continue
            # the clock starts HERE — the crawler's handoff to the
            # pipeline (Switchboard.to_indexer stamps at the same spot)
            stamp = ingest_slo.TRACKER.stamp()
            try:
                sb.index.store_document(fresh_doc(i), crawldepth=1,
                                        ingest_stamp=stamp)
            except Exception:
                ingest_errors[0] += 1
            i += 1
            ingested[0] = i

    crash_results: list = []

    def crash_legs():
        """The M84 kill−9 barriers, fired mid-soak: each leg is a
        chaos-child subprocess with its OWN live query thread, killed
        at the armed barrier, then recovered under live query threads
        (tests/chaos_child.py write_serving / verify_serving)."""
        repo = os.path.dirname(os.path.abspath(__file__))
        child = os.path.join(repo, "tests", "chaos_child.py")
        env = {**os.environ, "PYTHONPATH": repo}
        env.pop("YACY_FAULTS", None)
        for cp in ("rwi.flush.before_manifest",
                   "rwi.manifest.mid_write"):
            d = tempfile.mkdtemp(prefix="ingest-crash-")
            w = subprocess.run(
                [sys.executable, child, "write_serving", d, "4", cp],
                capture_output=True, text=True, timeout=120, env=env)
            killed = w.returncode == -_signal.SIGKILL
            with open(os.path.join(d, "acked.txt")) as f:
                acked = len(f.read().split())
            v = subprocess.run(
                [sys.executable, child, "verify_serving", d],
                capture_output=True, text=True, timeout=120, env=env)
            rec = {"crashpoint": cp, "killed_at_barrier": killed,
                   "acked_batches": acked, "recovered": False,
                   "recovered_acked": 0, "queries_during_recovery": 0,
                   "query_errors": -1}
            for line in v.stdout.splitlines():
                if line.startswith("ACKED "):
                    rec["recovered_acked"] = int(line.split()[1])
                elif line.startswith("QUERIES "):
                    rec["queries_during_recovery"] = \
                        int(line.split()[1])
                elif line.startswith("ERRORS "):
                    rec["query_errors"] = int(line.split()[1])
            rec["recovered"] = (v.returncode == 0
                                and rec["recovered_acked"] == acked)
            crash_results.append(rec)

    ing = _th.Thread(target=ingest_worker)
    cr = _th.Thread(target=crash_legs)
    ing.start()
    for t in range(4):                       # warm every compile shape
        ev = sb.search(f"benchterm{t}", count=k, use_cache=False)
        assert len(ev.results()) == k
    n_windows = 2 if smoke else 3
    base_w, ing_w, docs_w = [], [], []
    for _w in range(n_windows):
        running.clear()                      # A: no-ingest baseline
        base_w.append(query_soak(window_s))
        d0 = ingested[0]
        running.set()                        # B: ingest stream live
        ing_w.append(query_soak(window_s))
        docs_w.append(ingested[0] - d0)
    base_w.sort(key=lambda r: r[2])
    ing_w.sort(key=lambda r: r[2])
    qps_base, p50_base, p95_base = base_w[len(base_w) // 2]
    qps_ing, p50_ing, p95_ing = ing_w[len(ing_w) // 2]
    # the sustained-rate claim is measured over the windows it names —
    # the stream keeps running through the crash legs below, and those
    # docs must not inflate a rate divided by the window wall
    docs_in_window = sum(docs_w)
    # the soak CONTINUES (ingest + a background query loop) while the
    # kill−9 legs fire — "mid-soak under concurrent load" without the
    # subprocesses' own CPU burn polluting the measured p95 windows
    cr.start()
    crash_queries = [0]
    crash_t0 = time.perf_counter()

    def bg_queries():
        i = 0
        while cr.is_alive():
            ev = sb.search(f"benchterm{i % 4}", count=k,
                           use_cache=False)
            assert len(ev.results()) == k
            i += 1
            crash_queries[0] = i
    bg = _th.Thread(target=bg_queries)
    bg.start()
    cr.join(timeout=300)
    bg.join(timeout=30)
    crash_window_s = time.perf_counter() - crash_t0
    stop.set()
    ing.join()
    # the flush covering the tail of the stream (and its device pack)
    rwi.flush()
    docs_ingested = ingested[0]

    def tier(name: str) -> dict:
        h = histogram.get(f"ingest.{name}")
        counts = h.windowed_counts()
        return {"count": sum(counts),
                "p50_ms": round(percentile_from_counts(counts, 0.50), 2),
                "p95_ms": round(percentile_from_counts(counts, 0.95), 2)}

    tiers = {nm: tier(nm) for nm in ("searchable", "flushed", "device",
                                     "backpressure")}
    tracker = ingest_slo.TRACKER.counters()

    # -- phase C: injected burst -> deferral -> catch-up ---------------------
    # over the REAL wire: the injected latency lands inside the measured
    # servlet.serving wall, exactly the round-13 burn recipe
    srv = YaCyHttpServer(sb, port=0)
    srv.start()
    sched = sb.ingest_scheduler
    try:
        faultinject.set_fault("servlet.serving", 300.0)
        for i in range(30):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/yacysearch.json"
                    f"?query=benchterm{i % 4}&nocache=true",
                    timeout=30) as r:
                r.read()
        for _ in range(4):
            sb.health.tick()
            if sched.deferred:
                break
        assert sched.deferred, (
            "merge_scheduler did not defer under the injected burst: "
            f"slo rule = {sb.health.states['slo_serving_p95'].state}")
        # the cleanup job's merge entry while deferred: the ask PARKS
        deferred_ran = sched.request_merge(max_runs=2)
        assert not deferred_ran and sched.merge_deferrals >= 1
        faultinject.clear("servlet.serving")
        # the burn leaves the windows, then hysteresis recovers
        for _ in range(histogram.WINDOWS + 1):
            histogram.rotate_all()
        for _ in range(6):
            sb.health.tick()
            if not sched.deferred:
                break
    finally:
        faultinject.clear()
        srv.close()
    crumbs = [c for c in sb.actuators.recent_breadcrumbs(64)
              if c.get("actuator") == "merge_scheduler"]
    defer_crumbs = [c for c in crumbs if c["dir"] == "down"]
    catchup_crumbs = [c for c in crumbs if c["dir"] == "up"]
    sched_counters = sched.counters()

    p95_ratio = p95_ing / max(p95_base, 1e-9)
    # the committed acceptance artifact gates at 1.25x; the tier-1
    # smoke variant runs on whatever CI box hosts the suite, where a
    # concurrent job burning cores during the B windows (but not A)
    # flaps a tight wall-clock ratio with no product defect — the
    # smoke keeps every FUNCTIONAL gate strict and gives the latency
    # ratio noise headroom instead
    p95_gate = 2.0 if smoke else 1.25
    crash_ok = (len(crash_results) >= 2
                and all(r["killed_at_barrier"] and r["recovered"]
                        and r["query_errors"] == 0
                        for r in crash_results))
    art = {
        "metric": "ingest_soak",
        "smoke": bool(smoke),
        "n_seed_postings": n * 4,
        "threads": threads,
        "window_s": round(window_s, 1),
        "windows": n_windows,
        "docs_per_s_target": docs_per_s,
        "docs_ingested": docs_ingested,
        "docs_in_measured_window": docs_in_window,
        "ingest_docs_per_s": round(
            docs_in_window / (n_windows * window_s), 2),
        "ingest_errors": ingest_errors[0],
        "serving": {
            "qps_baseline": round(qps_base, 2),
            "qps_ingest": round(qps_ing, 2),
            "p50_ms_baseline": round(p50_base, 2),
            "p50_ms_ingest": round(p50_ing, 2),
            "p95_ms_baseline": round(p95_base, 2),
            "p95_ms_ingest": round(p95_ing, 2),
            "p95_ratio": round(p95_ratio, 3),
            "p95_gate": p95_gate,
            "gate_p95": bool(p95_ratio <= p95_gate),
            "gate_p95_1_25x": bool(p95_ratio <= 1.25),
        },
        "crawl_to_searchable_ms": tiers,
        "tracker": tracker,
        "device_builds": ds.ingest_device_builds,
        "device_builds_seed": seed_builds,
        "device_builds_soak": ds.ingest_device_builds - warm_builds,
        "rwi_runs": len(rwi._runs),
        "deferral": {
            **sched_counters,
            "defer_breadcrumbs": len(defer_crumbs),
            "catchup_breadcrumbs": len(catchup_crumbs),
            "gate_engaged": bool(defer_crumbs and catchup_crumbs
                                 and sched_counters["merge_deferrals"]
                                 >= 1),
        },
        "crash": crash_results,
        "crash_window_s": round(crash_window_s, 1),
        "queries_during_crash_window": crash_queries[0],
        "gate_zero_acked_loss": bool(crash_ok),
    }
    art["ok"] = bool(art["serving"]["gate_p95"]
                     and art["deferral"]["gate_engaged"]
                     and art["gate_zero_acked_loss"]
                     and tiers["searchable"]["count"] > 0
                     and tiers["flushed"]["count"] > 0
                     and tiers["device"]["count"] > 0
                     and ds.ingest_device_builds > seed_builds
                     and ingest_errors[0] == 0)
    print(json.dumps(art, indent=1))
    # validation gates (--capacity discipline: a failing soak must not
    # commit a green-looking artifact)
    assert tiers["searchable"]["count"] > 0, "no searchable-tier stamps"
    assert tiers["flushed"]["count"] > 0, "no flushed-tier stamps"
    assert tiers["device"]["count"] > 0, \
        "no device-tier stamps (fresh runs never packed)"
    assert ds.ingest_device_builds > seed_builds, \
        "fresh flushes did not route through the device build kernel"
    assert ingest_errors[0] == 0, \
        f"{ingest_errors[0]} store_document error(s) during the soak"
    assert crash_ok, f"crash legs failed: {crash_results}"
    assert art["deferral"]["gate_engaged"], (
        f"merge-deferral actuator did not engage+catch up: {crumbs}")
    assert p95_ratio <= p95_gate, (
        f"serving p95 under ingest {p95_ing:.1f} ms is "
        f"{p95_ratio:.2f}x the no-ingest baseline {p95_base:.1f} ms "
        f"(gate {p95_gate}x)")
    sb.close()
    if not smoke:
        out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "INGEST_r01.json")
        with open(out, "w", encoding="utf-8") as f:
            json.dump(art, f, indent=1)
            f.write("\n")
        print(f"committed {out}", file=sys.stderr)
    return art


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=10_000_000,
                    help="postings in the index block (default 10M)")
    ap.add_argument("--k", type=int, default=100)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--cpu-iters", type=int, default=3)
    ap.add_argument("--soak-seconds", type=float, default=60.0,
                    help="headline: length of each measurement window")
    ap.add_argument("--windows", type=int, default=3,
                    help="headline: median-of-N measurement windows "
                         "(3 keeps an end-of-round run inside its "
                         "budget while still a genuine "
                         ">=60s-per-window soak)")
    ap.add_argument("--threads", type=int, default=112)
    ap.add_argument("--batch-size", type=int, default=32,
                    help="headline: devstore batcher max_batch")
    ap.add_argument("--config", type=int,
                    choices=list(range(1, 14)),
                    help="run a BASELINE.md benchmark config instead of "
                         "the headline metric")
    ap.add_argument("--roofline", action="store_true",
                    help="silicon accounting: dispatch every registered "
                         "kernel against an --n-row block and emit "
                         "analytical FLOPs/bytes, achieved FLOP/s / "
                         "GB/s, util%% vs the device peak, and the "
                         "compute-/memory-bound verdict (ISSUE 1)")
    ap.add_argument("--trace-overhead", action="store_true",
                    help="serving p50/p95 with the tracing spine on vs "
                         "off, interleaved windows; asserts the p50 "
                         "regression stays < 2%% so tracing can ship "
                         "enabled by default (ISSUE 2)")
    ap.add_argument("--pipeline-overhead", action="store_true",
                    help="served q/s with pipelined dispatch on vs off "
                         "(interleaved windows, --trace-overhead style) "
                         "plus the repeated-term cache contract: hits "
                         "answer with zero batcher dispatches, "
                         "bit-identical to the cold path (ISSUE 3)")
    ap.add_argument("--federation-overhead", action="store_true",
                    help="serving p50/p95 with the fleet digest gossip "
                         "on vs off, interleaved windows; asserts the "
                         "p50 regression stays < 2%% and the rendered "
                         "digest stays under the 2 KiB wire budget "
                         "(ISSUE 5)")
    ap.add_argument("--rerank-overhead", action="store_true",
                    help="hybrid serving p50 with the dense rerank "
                         "batched through the pipelined batcher vs solo "
                         "dispatches of the same kernel (interleaved "
                         "windows); asserts batched p50 is no worse and "
                         "that the batched windows coalesce >1 mean "
                         "queries per rerank dispatch (ISSUE 6)")
    ap.add_argument("--dense-first", action="store_true",
                    help="ISSUE 11 acceptance: IVF ANN dense-first "
                         "retrieval at --n resident vectors (default "
                         "10M) under the standard 2 GiB resident "
                         "budget — recall@k-vs-latency curve vs the "
                         "exact host oracle across an nprobe ladder, "
                         "plus a concurrent serving soak with tier "
                         "counters and ANN-kernel util_pct")
    ap.add_argument("--mesh-procs", type=int, default=0,
                    help="ISSUE 12 acceptance: bring up a REAL "
                         "N-OS-process SPMD mesh via jax.distributed "
                         "(the parallel/launcher supervisor), serve a "
                         "sustained soak over the HTTP wire with "
                         "cross-process fusion collectives, gate "
                         "bit-identity vs the single-process mesh / "
                         "100%% answered / distinct PIDs / zero "
                         "worker_stall, and commit MULTICHIP_r06.json "
                         "with per-process q/s and the fusion-"
                         "collective histogram")
    ap.add_argument("--ingest-soak", action="store_true",
                    help="ISSUE 13 acceptance: sustained indexing at "
                         "--ingest-docs-per-s through the product "
                         "write path under the standard query soak — "
                         "gates serving p95 <= 1.25x the no-ingest "
                         "baseline, crawl-to-searchable p95 per tier, "
                         "zero acked-doc loss across mid-soak kill-9 "
                         "crash points with live query threads, and "
                         "the merge-deferral actuator engaging under "
                         "an injected burst; commits INGEST_r01.json "
                         "(--smoke: the seconds-scale tier-1 variant, "
                         "no artifact commit)")
    ap.add_argument("--ingest-docs-per-s", type=float, default=50.0,
                    help="ingest-soak: target sustained indexing rate")
    ap.add_argument("--smoke", action="store_true",
                    help="ingest-soak: short tier-1 variant (seconds)")
    ap.add_argument("--capacity", action="store_true",
                    help="compressed-residency capacity soak (ISSUE 8): "
                         "bit-packed residency at 10M and >=--n postings "
                         "under one 2 GiB budget; gates p95 <= 2x the "
                         "10M row and packed bytes/posting <= half the "
                         "int16 format; emits compression ratio, "
                         "achieved GB/s, util%% and per-tier counters")
    ap.add_argument("--tier-overhead", action="store_true",
                    help="tier-ladder bookkeeping p50 on vs off with a "
                         "fully hot working set (interleaved windows); "
                         "asserts the idle-path overhead stays < 2%% "
                         "(noise budget on CPU backends)")
    ap.add_argument("--actuator-overhead", action="store_true",
                    help="serving p50/p95 with the actuator engine "
                         "(admission buckets, degradation ladder, "
                         "batcher auto-tune, peer guard) enabled-but-"
                         "idle vs disabled, interleaved windows; "
                         "asserts < 2%% p50 regression AND zero "
                         "transitions across the healthy soak "
                         "(ISSUE 9)")
    ap.add_argument("--device-loss-soak", action="store_true",
                    help="inject a device loss under a concurrent "
                         "serving soak: asserts 100%% of queries answer "
                         "via the counted host fallback, automatic "
                         "rebuild back to device serving, and "
                         "bit-identical post-recovery ranking "
                         "(ISSUE 10c acceptance)")
    ap.add_argument("--integrity-overhead", action="store_true",
                    help="serving p50/p95 with read-side checksum "
                         "verification ON vs OFF (interleaved windows; "
                         "gate <2%% p50, zero corruption/loss counters "
                         "on the healthy soak)")
    ap.add_argument("--tail-overhead", action="store_true",
                    help="serving p50/p95 with the tail-attribution "
                         "engine (classifier + wave stamping) on vs "
                         "off (_ab_soak), gate <2%% p50, plus a "
                         "fault-injected window asserting >=1 "
                         "classified verdict and zero unattributed "
                         "(ISSUE 15)")
    ap.add_argument("--prof-overhead", action="store_true",
                    help="serving p50/p95 with the whitebox profiler "
                         "(sampling thread at 2x deployed rate + lock-"
                         "wait observatory) on vs off (_ab_soak), gate "
                         "<2%% p50 with non-vacuity checks that stacks "
                         "folded and the devstore store lock recorded; "
                         "commits PROF_r01.json (ISSUE 20)")
    ap.add_argument("--tail-forensics", action="store_true",
                    help="3-process mesh soak with one member slowed "
                         "via do_meshfault: assembled cross-process "
                         "waterfall, collective_straggler dominant + "
                         "scoreboard naming the member, incident "
                         "embedding the cause histogram, and the "
                         "--tail-overhead gate; commits TAIL_r01.json "
                         "(ISSUE 15 acceptance)")
    ap.add_argument("--game-day", action="store_true",
                    help="3-process mesh game day: zipf/burst/per-"
                         "client workload while the chaos conductor "
                         "schedules OVERLAPPING faults (mesh.step "
                         "straggle, device loss, servlet latency) "
                         "over do_meshfault; the verdict engine joins "
                         "the schedule against incidents/tail-causes/"
                         "scoreboard and commits the next CHAOS_rNN "
                         "round with a drill_trend run-over-run block "
                         "(ISSUE 19 acceptance; --smoke compresses)")
    ap.add_argument("--health-overhead", action="store_true",
                    help="serving p50/p95 with the histogram recording "
                         "+ health-rule tick on vs off, interleaved "
                         "windows; asserts the p50 regression stays "
                         "< 2%% and cross-checks the histogram-derived "
                         "percentiles against the raw samples (ISSUE 4)")
    args = ap.parse_args()

    if args.roofline:
        _roofline_mode(args.n, k=16)
        return
    if args.mesh_procs:
        _mesh_procs_mode(args.mesh_procs,
                         ndocs=args.n if args.n != 10_000_000 else 512,
                         soak_s=args.soak_seconds, k=10)
        return
    if args.ingest_soak:
        # scale the load to the box: on a 1-core CI runner a parse
        # stream sized for a pod host would swamp the measured window
        # with GIL contention that says nothing about the write path
        cores = os.cpu_count() or 4
        if args.smoke:
            _ingest_soak_mode(
                args.n if args.n != 10_000_000 else 20_000,
                docs_per_s=min(args.ingest_docs_per_s, 8.0 * cores),
                soak_s=min(args.soak_seconds, 3.0),
                threads=min(args.threads, max(2, min(8, cores))),
                smoke=True)
        else:
            _ingest_soak_mode(
                args.n if args.n != 10_000_000 else 200_000,
                docs_per_s=min(args.ingest_docs_per_s, 8.0 * cores),
                soak_s=args.soak_seconds,
                threads=min(args.threads, max(2, min(16, cores))))
        return
    if args.capacity:
        _capacity_mode(args.n if args.n != 10_000_000 else 50_000_000,
                       threads=min(args.threads, 16),
                       soak_s=args.soak_seconds, k=10,
                       batch_size=args.batch_size)
        return
    if args.dense_first:
        _dense_first_mode(args.n, threads=min(args.threads, 16),
                          soak_s=args.soak_seconds)
        return
    if args.tier_overhead:
        _tier_overhead_mode(args.n if args.n != 10_000_000 else 200_000)
        return
    if args.trace_overhead:
        _trace_overhead_mode(args.n if args.n != 10_000_000 else 200_000)
        return
    if args.tail_overhead:
        _tail_overhead_mode(args.n if args.n != 10_000_000 else 200_000)
        return
    if args.prof_overhead:
        _prof_overhead_mode(args.n if args.n != 10_000_000 else 200_000)
        return
    if args.tail_forensics:
        _tail_forensics_mode(
            nprocs=args.mesh_procs or 3,
            n=args.n if args.n != 10_000_000 else 200_000)
        return
    if args.game_day:
        _game_day_mode(nprocs=args.mesh_procs or 3, smoke=args.smoke)
        return
    if args.health_overhead:
        _health_overhead_mode(args.n if args.n != 10_000_000 else 200_000)
        return
    if args.integrity_overhead:
        _integrity_overhead_mode(
            args.n if args.n != 10_000_000 else 200_000,
            threads=min(args.threads, 16), windows=args.windows)
        return
    if args.device_loss_soak:
        _device_loss_soak_mode(
            args.n if args.n != 10_000_000 else 200_000,
            threads=min(args.threads, 8))
        return
    if args.actuator_overhead:
        _actuator_overhead_mode(
            args.n if args.n != 10_000_000 else 200_000)
        return
    if args.federation_overhead:
        _federation_overhead_mode(
            args.n if args.n != 10_000_000 else 200_000)
        return
    if args.pipeline_overhead:
        _pipeline_overhead_mode(
            args.n if args.n != 10_000_000 else 200_000)
        return
    if args.rerank_overhead:
        _rerank_overhead_mode(
            args.n if args.n != 10_000_000 else 200_000)
        return
    if args.config in (6, 10):
        fn = _config6_served_path if args.config == 6 \
            else _config10_mesh_served
        fn(ndocs=args.n if args.n != 10_000_000 else 1_000_000)
        return
    if args.config:
        {1: _config1_bm25_cpu_baseline, 2: _config2_bm25_tpu,
         3: _config3_sharded, 4: _config4_p2p_fusion,
         5: _config5_hybrid, 7: _config7_kernel,
         8: _config8_device_join,
         9: _config9_indexing,
         11: _config11_metadata_startup,
         12: _config12_multiproc,
         13: _config13_modifier_mix}[args.config]()
        return

    # ------------------------------------------------------------------
    # HEADLINE: the SERVED product path. q/s through Switchboard.search()
    # over a 10M-posting term -- query parse, batched+pruned device rank
    # over placed postings blocks, metadata join, host-diversity drain,
    # result page -- measured as concurrent throughput (32 searcher
    # threads, the threaded-HTTP-server execution model). vs_baseline is
    # the same ranking math as a single-threaded numpy full scan + top-k
    # (strictly faster than the reference's per-row Java decode loop).
    # Round 1's headline measured the kernel against pre-placed arrays;
    # this one measures what the product delivers (VERDICT r1 weak #1);
    # the kernel-only protocol survives as --config 7.
    # ------------------------------------------------------------------
    from yacy_search_server_tpu.index import postings as P
    from yacy_search_server_tpu.ops import ranking

    n = args.n
    rng = np.random.default_rng(0)
    feats = rng.integers(0, 1000, (n, P.NF), dtype=np.int32)
    feats[:, P.F_FLAGS] = rng.integers(0, 2**20, n, dtype=np.int32)
    feats[:, P.F_DOMLENGTH] = rng.integers(0, 256, n, dtype=np.int32)
    feats[:, P.F_LANGUAGE] = P.pack_language("en")
    valid = np.ones(n, bool)
    hostids = np.zeros(n, dtype=np.int32)
    prof = ranking.RankingProfile()
    lang = P.pack_language("en")
    # WARMED >=3-iter CPU twin (VERDICT r3 weak #3: a single cold numpy
    # pass understated the denominator); the protocol is pinned — keep
    # it fixed across rounds so vs_baseline stays comparable
    np_cardinal_topk(feats, valid, hostids, prof, lang, args.k, ranking, P)
    cpu_iters = 3
    t0 = time.perf_counter()
    for _ in range(cpu_iters):
        np_cardinal_topk(feats, valid, hostids, prof, lang, args.k,
                         ranking, P)
    cpu_qps = cpu_iters / (time.perf_counter() - t0)
    del feats, valid, hostids

    # pinned to the single-device store: the headline metric's protocol
    # (pruned+batched placed-block serving) must stay comparable across
    # rounds; the mesh-sharded serving number is config 10
    sb = _build_served_switchboard(n, n_terms=2, mesh="off",
                                   batch_size=args.batch_size)
    assert sb.index.devstore is not None, "device serving must be on"
    # SOAK protocol (VERDICT r4 #2): the headline is the MEDIAN of
    # `--windows` sustained measurement windows of `--soak-seconds`
    # each — a sub-second burst cannot demonstrate stall-proofness (the
    # r3 stall class emerged under sustained load, and a 10-40 s jit
    # stall would not even fit inside a 0.9 s window). The band of all
    # windows is in the artifact, so a lucky draw can't be the headline.
    lats: list = []
    window_qps: list = []
    for w in range(max(1, args.windows)):
        qps = _served_qps(sb, k=10, threads=args.threads, n_terms=2,
                          latencies=lats, duration_s=args.soak_seconds,
                          skip_warm=(w > 0))
        window_qps.append(round(qps, 3))
    qps_median = sorted(window_qps)[len(window_qps) // 2]
    lats.sort()
    p50 = lats[len(lats) // 2] * 1000 if lats else 0.0
    p95 = lats[int(len(lats) * 0.95)] * 1000 if lats else 0.0
    # the windowed-histogram view of the same soak (ISSUE 4 satellite):
    # emitted NEXT TO the raw-sample percentiles so the two percentile
    # implementations cross-check in every headline artifact (BASELINE
    # pins the agreement bound)
    from yacy_search_server_tpu.utils import histogram as _hg
    _h = _hg.get("switchboard.search")
    hist_p50 = round(_h.percentile(0.50), 1) if _h is not None else 0.0
    hist_p95 = round(_h.percentile(0.95), 1) if _h is not None else 0.0
    # ---- hybrid-mode soak (ISSUE 6): same protocol, hybrid=True -------
    # The batched dense rerank's serving numbers land in the SAME
    # artifact as the sparse headline: qps, latency band, batched
    # rerank dispatch counters (mean queries/dispatch > 1 under the
    # threaded load) and the rerank family's roofline util_pct. The
    # top-k cache is disabled for this window so every query pays a
    # real rerank dispatch (a hybrid-cache hit serves with zero device
    # work and would measure the cache, not the kernel family).
    ds = sb.index.devstore
    hybrid_soak = None
    if getattr(ds, "_dense", None) is not None:
        _seed_dense_coverage(sb, seed=23)
        ds._topk_cache.enabled = False
        hd0, hq0 = ds.rerank_dispatches, ds.rerank_queries
        hyb_lats: list = []
        hyb_qps = _served_qps(
            sb, k=10, threads=args.threads, n_terms=2,
            latencies=hyb_lats,
            duration_s=max(10.0, args.soak_seconds / 3), hybrid=True)
        ds._topk_cache.enabled = True
        hyb_lats.sort()
        hdisp = ds.rerank_dispatches - hd0
        hqueries = ds.rerank_queries - hq0
        from yacy_search_server_tpu.utils.profiler import PROFILER
        rk = next((p for p in PROFILER.snapshot()
                   if p.kernel == "_rerank_fwd_batch_packed_kernel"),
                  None)
        hybrid_soak = {
            "qps": round(hyb_qps, 3),
            "p50_ms": round(hyb_lats[len(hyb_lats) // 2] * 1000, 1)
            if hyb_lats else 0.0,
            "p95_ms": round(hyb_lats[int(len(hyb_lats) * 0.95)] * 1000,
                            1) if hyb_lats else 0.0,
            "rerank_dispatches": hdisp,
            "rerank_queries": hqueries,
            "mean_queries_per_rerank_dispatch":
                round(hqueries / max(hdisp, 1), 3),
            "rerank_util_pct": rk.util_pct if rk is not None else 0.0,
            "rerank_bound": rk.bound if rk is not None else "",
        }

    # ONE counters snapshot: rt_per_query must be recomputable from the
    # adjacent counters block of the same artifact
    counters = sb.index.devstore.counters()
    # the fleet digest rendered over this soak's histogram windows: the
    # gossip wire cost of this node's observability, pinned per headline
    # (BASELINE.md federation discipline; budget fleet.byteBudget=2048)
    sb.fleet.render()
    fleet_digest_bytes = sb.fleet.last_digest_bytes
    print(json.dumps({
        "metric": f"served_search_top10_qps_{n // 1_000_000}M_postings",
        "value": qps_median,
        "unit": "queries/sec",
        "vs_baseline": round(qps_median / cpu_qps, 3),
        "windows_qps": window_qps,
        "soak_seconds_per_window": args.soak_seconds,
        "threads": args.threads,
        # batched-window latency under the threaded load, floored by
        # the device round trip: the falsifiable p50<=50ms north-star
        # surface (VERDICT r2 weak #4)
        "p50_ms": round(p50, 1),
        "p95_ms": round(p95, 1),
        # the same soak through the windowed histograms (last ~3 min of
        # steady state; must agree with p50_ms/p95_ms within the pinned
        # BASELINE bound)
        "hist_p50_ms": hist_p50,
        "hist_p95_ms": hist_p95,
        "max_ms": round(lats[-1] * 1000, 1) if lats else 0.0,
        # device round trips per served query (BASELINE.md discipline:
        # every perf claim carries rt_per_query alongside util_pct —
        # <1 under batching, ->0 as the repeated-term cache serves)
        "rt_per_query": round(counters["device_round_trips"]
                              / max(counters["queries_served"], 1), 4),
        # wire size of the metric digest this node would gossip to the
        # fleet after this soak (<= 2048 by the federation discipline)
        "fleet_digest_bytes": fleet_digest_bytes,
        # self-defending serving (ISSUE 9): the per-rung served-query
        # histogram and the actuator transition counters — BOTH must
        # read as a healthy soak (every query at level 0, zero
        # transitions); a degraded headline is not a headline
        "degrade_level_queries": {
            str(i): v
            for i, v in enumerate(sb.actuators.degraded_queries)},
        "actuator_transitions": {
            f"{a}:{d}": v for (a, d), v
            in sorted(sb.actuators.transition_counts().items())},
        # the hybrid-mode soak (batched dense rerank through the
        # pipelined batcher; cache disabled so every query reranks)
        "hybrid": hybrid_soak,
        # serving-health counters (VERDICT r3 #1: the r3 regression hid
        # behind a silent batch-dispatch failure; these make any repeat
        # visible in the artifact itself), incl. per-query kernel/
        # dispatch percentiles and the measured dispatch round trip
        # (VERDICT r4 #3: p50_local = host + kernel, computable)
        "counters": counters,
    }))


def _config7_kernel(k=100, n=10_000_000, iters=20, cpu_iters=3):
    """Config #7: the round-1 headline protocol -- fused cardinal kernel
    over a pre-placed 10M block, Q queries per dispatch via lax.map (the
    kernel-only number; the no-arg headline measures the served path)."""
    import jax
    import jax.numpy as jnp

    from yacy_search_server_tpu.index import postings as P
    from yacy_search_server_tpu.ops import ranking

    rng = np.random.default_rng(0)
    feats = rng.integers(0, 1000, (n, P.NF), dtype=np.int32)
    feats[:, P.F_FLAGS] = rng.integers(0, 2**20, n, dtype=np.int32)
    feats[:, P.F_DOMLENGTH] = rng.integers(0, 256, n, dtype=np.int32)
    feats[:, P.F_LANGUAGE] = P.pack_language("en")
    docids = np.arange(n, dtype=np.int32)
    valid = np.ones(n, bool)
    hostids = rng.integers(0, 1 << 16, n, dtype=np.int32)

    prof = ranking.RankingProfile()
    lang = P.pack_language("en")

    # --- CPU baseline (vectorized numpy, generous to the reference) ---
    t0 = time.perf_counter()
    for _ in range(cpu_iters):
        np_cardinal_topk(feats, valid, hostids, prof, lang, k, ranking, P)
    cpu_qps = cpu_iters / (time.perf_counter() - t0)

    # --- device steady state: postings resident, queries stream in.
    # Q queries execute as ONE dispatch (lax.map) and results are fetched
    # to host, so the measurement includes real device execution and the
    # full transfer round-trip.
    from functools import partial as _partial

    dev = jax.devices()[0]
    consts = (jnp.asarray(prof.norm_coeffs()),
              *map(jnp.asarray, prof.flag_coeffs()),
              jnp.int32(prof.domlength), jnp.int32(prof.tf),
              jnp.int32(prof.language), jnp.int32(prof.authority))
    # device-resident COMPACT block (int16 features + int32 flags): the
    # scorer is HBM-bound, so the block format halves bytes per scan --
    # scores are bit-identical to the int32 path (exact fast division)
    feats16, flags = ranking.compact_feats(feats)
    d_feats16 = jax.device_put(feats16, dev)
    d_flags = jax.device_put(flags, dev)
    d_docids = jax.device_put(docids, dev)
    d_valid = jax.device_put(valid, dev)
    d_hostids = jax.device_put(hostids, dev)

    @_partial(jax.jit, static_argnames=("k",))
    def multi_query(feats16_, flags_, docids_, valid_, hostids_, langs, k):
        def one(lang_pref):
            s = ranking.cardinal_scores16(feats16_, flags_, valid_,
                                          hostids_, None, *consts, lang_pref,
                                          with_authority=prof.authority > 12)
            # approx_max_k: the TPU-optimized top-k (recall ~0.95 at
            # default config) -- the heap replacement runs at HBM speed
            top_s, top_i = jax.lax.approx_max_k(s.astype(jnp.float32), k)
            return top_s, docids_[top_i]
        return jax.lax.map(one, langs)

    q = iters
    langs = jnp.full((q,), lang, dtype=jnp.int32)
    out = multi_query(d_feats16, d_flags, d_docids, d_valid, d_hostids,
                      langs, k)
    np.asarray(out[0])          # compile + warm

    t0 = time.perf_counter()
    out = multi_query(d_feats16, d_flags, d_docids, d_valid, d_hostids,
                      langs, k)
    np.asarray(out[0])          # force execution + fetch
    tpu_qps = q / (time.perf_counter() - t0)

    print(json.dumps({
        "metric": f"cardinal_rank_topk{k}_qps_{n // 1_000_000}M_postings",
        "value": round(tpu_qps, 3),
        "unit": "queries/sec",
        "vs_baseline": round(tpu_qps / cpu_qps, 3),
    }))


if __name__ == "__main__":
    sys.exit(main())
