"""Distributed query tracing (ISSUE 2): the span spine across servlet →
SearchEvent → device/mesh kernels → P2P fan-out, the `/metrics`
exposition, and the Performance_Trace_p surface.

The acceptance shape: ONE search against a two-node loopback network
must yield ONE trace — the originator's trace id — containing servlet,
SearchEvent, device-kernel and remote-peer spans, with the remote
node's spans carrying the originator's id over the wire propagation
path (payload `_trace` / the X-YaCy-Trace header)."""

import threading

import pytest

from yacy_search_server_tpu.document.document import Document
from yacy_search_server_tpu.peers.node import P2PNode
from yacy_search_server_tpu.peers.transport import LoopbackNetwork
from yacy_search_server_tpu.server.objects import ServerObjects
from yacy_search_server_tpu.utils import tracing


@pytest.fixture(autouse=True)
def _fresh_ring():
    tracing.set_enabled(True)
    tracing.clear()
    yield
    tracing.set_enabled(True)
    tracing.clear()


# -- spine unit behavior -----------------------------------------------------

def test_span_nesting_and_ring():
    with tracing.trace("root", q="x") as r:
        tid = r.ctx[0]
        with tracing.span("child"):
            tracing.emit("kernel.fake", 2.5, batch=4)
    rec = tracing.get_trace(tid)
    assert rec is not None and rec.done
    names = {s.name for s in rec.spans}
    assert names == {"root", "child", "kernel.fake"}
    by = {s.name: s for s in rec.spans}
    assert by["child"].parent == by["root"].sid
    assert by["kernel.fake"].parent == by["child"].sid
    assert by["kernel.fake"].dur_ms == 2.5
    assert rec.duration_ms() >= by["child"].dur_ms


def test_disabled_and_untraced_are_noop_singletons():
    # outside any trace: the shared no-op object, nothing recorded
    s1 = tracing.span("a")
    s2 = tracing.span("b")
    assert s1 is s2
    tracing.emit("orphan", 1.0)
    assert tracing.traces(10) == []
    # disabled: trace() itself is the no-op too
    tracing.set_enabled(False)
    assert tracing.trace("root") is tracing.span("x")
    with tracing.trace("root"):
        pass
    assert tracing.traces(10) == []


def test_ring_and_span_bounds():
    for i in range(tracing.MAX_TRACES + 20):
        with tracing.trace(f"t{i}"):
            pass
    assert len(tracing.traces(10_000)) == tracing.MAX_TRACES
    assert tracing.dropped_traces == 20


def test_cross_thread_span_in():
    with tracing.trace("root") as r:
        ctx = r.ctx

        def worker():
            with tracing.span_in(ctx, "other-thread"):
                pass
        th = threading.Thread(target=worker)
        th.start()
        th.join()
    rec = tracing.get_trace(ctx[0])
    assert "other-thread" in {s.name for s in rec.spans}


def test_remote_trace_rejects_junk_ids():
    assert tracing.remote_trace("x", "peer.search") is tracing.span("n")
    assert tracing.remote_trace("a" * 200, "peer.search") \
        is tracing.span("n")
    with tracing.remote_trace("deadbeef1234", "peer.search", peer="p"):
        pass
    rec = tracing.get_trace("deadbeef1234")
    assert rec is not None
    assert rec.spans[0].attrs["peer"] == "p"


def test_spans_feed_the_windowed_stage_table():
    """The stage p50/p95 verdict (formerly a per-call trace-ring walk)
    is maintained incrementally: every recorded span lands in the
    windowed histogram for its name, and histogram.stage_table names
    the tail-dominant stage — wrappers and background workloads
    excluded (full dominance semantics pinned in test_histogram)."""
    from yacy_search_server_tpu.utils import histogram as hg
    hg.reset()
    for _ in range(4):
        with tracing.trace("req"):
            # the request wrapper covers everything but must never be
            # named as the dominant STAGE
            tracing.emit("switchboard.search", 60.0)
            tracing.emit("search.fast", 1.0)
            tracing.emit("search.slow", 50.0)
    # pipeline/indexing stages are a different workload: excluded by
    # default from the serving verdict
    with tracing.trace("pipeline.index"):
        tracing.emit("index.storedocumentindex", 500.0)
    s = hg.stage_table()
    assert s["tail_dominant_stage"] == "search.slow"
    assert s["stages"]["search.slow"]["p95_ms"] >= 50.0
    assert s["stages"]["search.slow"]["count"] == 4
    assert "index.storedocumentindex" not in s["stages"]
    # the all-workload view folds the pipeline back in
    s_all = hg.stage_table(exclude_prefixes=())
    assert s_all["tail_dominant_stage"] == "index.storedocumentindex"
    hg.reset()


def test_export_jsonl():
    import json
    with tracing.trace("req") as r:
        tid = r.ctx[0]
        tracing.emit("stage", 3.0)
    lines = tracing.export_jsonl(10).splitlines()
    rows = [json.loads(ln) for ln in lines]
    assert any(row["trace_id"] == tid and
               any(s["name"] == "stage" for s in row["spans"])
               for row in rows)


# -- pipeline tracing --------------------------------------------------------

SITE = {
    "http://trace.test/": (
        b"<html><head><title>Trace Home</title></head>"
        b"<body>tracing pipeline document flow</body></html>"),
    "http://trace.test/robots.txt": b"",
}


def _transport(url, headers):
    if url in SITE:
        return 200, {"content-type": "text/html"}, SITE[url]
    return 404, {}, b""


def test_indexing_pipeline_emits_one_trace_per_document(tmp_path):
    from yacy_search_server_tpu.switchboard import Switchboard
    sb = Switchboard(data_dir=str(tmp_path / "DATA"), transport=_transport)
    sb.latency.min_delta_s = 0.0
    try:
        sb.start_crawl("http://trace.test/", depth=0)
        sb.crawl_until_idle(timeout_s=30)
        recs = [r for r in tracing.traces(100)
                if r.root_name == "pipeline.index"]
        assert recs, "no pipeline trace recorded"
        rec = recs[0]
        names = {s.name for s in rec.spans}
        # ONE span per stage: the StageTimer bridge records it under the
        # attached entry context (no duplicate span_in wrapper)
        stages = {"index.parsedocument", "index.condensedocument",
                  "index.webstructureanalysis", "index.storedocumentindex"}
        assert stages | {"pipeline.index"} <= names
        # exactly ONE span per pipeline stage (nested segment-level
        # spans like index.storedocument may ride along, duplicates not)
        all_names = [s.name for s in rec.spans]
        for st in stages:
            assert all_names.count(st) == 1, all_names
        assert rec.done
    finally:
        sb.close()


# -- two-node loopback: the acceptance trace ---------------------------------

def _doc(url, title, text):
    return Document(url=url, title=title, text=text,
                    mime_type="text/html", language="en")


@pytest.fixture
def duo(tmp_path):
    net = LoopbackNetwork()
    nodes = []
    for name in ("origin", "remote"):
        port = 8000 + sum(name.encode()) % 1000
        n = P2PNode(name, net, data_dir=str(tmp_path / name), port=port,
                    partition_exponent=2, redundancy=1)
        nodes.append(n)
    for n in nodes:
        n.bootstrap([m.seed for m in nodes if m is not n])
        n.ping()
    for n in nodes:
        n.ping()
    yield nodes
    for n in nodes:
        n.close()


def _index_docs(node, tag, n=30):
    for i in range(n):
        node.sb.index.store_document(_doc(
            f"http://{tag}{i % 3}.example/d{i}.html",
            f"{tag} doc {i} tracing",
            f"distributed tracing span spine document {tag} " * 4))
    node.sb.index.rwi.flush()


def test_cross_peer_trace_assembly(duo):
    """One servlet search on the originator fans out to the remote peer;
    every layer's spans land under ONE trace id, including the remote
    node's — the wire propagation contract."""
    a, b = duo
    _index_docs(a, "alpha")
    _index_docs(b, "beta")
    if a.sb.index.devstore is not None:
        # tiny index: drop the small-candidate gate so the device path
        # serves (the production gate would host-serve 30 postings)
        a.sb.index.devstore.small_rank_n = 0
        # warm the kernels OUTSIDE the traced request so the batcher
        # watchdog isn't spent on first-use compiles
        a.sb.search("tracing", count=5, use_cache=False)
        a.sb.search_cache.clear()
        # the warm query populated the top-k result cache: clear it so
        # the traced request exercises the kernel span spine (a cache
        # hit would — correctly — record no kernel span at all)
        cache = getattr(a.sb.index.devstore, "_topk_cache", None)
        if cache is not None:
            cache.clear()
        tracing.clear()

    from yacy_search_server_tpu.server.servlets.yacysearch import respond
    header = {"ext": "json"}
    post = ServerObjects({"query": "tracing", "resource": "global"})
    prop = respond(header, post, a.sb)
    assert prop.get("items", 0) or prop.get("found", 0)

    recs = [r for r in tracing.traces(50)
            if r.root_name == "servlet.yacysearch"]
    assert len(recs) == 1, "one search must be one trace"
    rec = recs[0]
    names = {s.name for s in rec.spans}
    # servlet + SearchEvent layers
    assert "servlet.yacysearch" in names
    assert "switchboard.search" in names
    assert names & {"search.devrank", "search.join", "search.presort",
                    "search.normalizing"}, names
    # device kernel span (batched stamp or the profiler bridge)
    if a.sb.index.devstore is not None:
        assert any(n.startswith("kernel.") for n in names), names
        assert "search.devrank" in names, names
    # P2P fan-out + the REMOTE node's segment under the SAME trace id
    assert "peers.fanout" in names
    assert "peers.remotesearch" in names
    remote_spans = [s for s in rec.spans if s.name == "peer.search"]
    assert remote_spans, "remote peer recorded no span under the trace"
    b_hash = b.seed.hash.decode("ascii")
    assert any(s.attrs.get("peer") == b_hash for s in remote_spans)
    # the remote peer's own SearchEvent stages nest under its segment
    remote_sids = {s.sid for s in remote_spans}
    assert any(s.parent in remote_sids for s in rec.spans
               if s.name.startswith("search.")), \
        "remote SearchEvent stages must parent under peer.search"
    # fusion of the remote results back into the live event
    assert "search.fusion_remote" in names

    # rendered by Performance_Trace_p: the span table and the waterfall
    from yacy_search_server_tpu.server.servlets.monitoring import (
        respond_trace)
    tprop = respond_trace({"ext": "json"},
                          ServerObjects({"trace": rec.trace_id}), a.sb)
    assert tprop.get_int("spans", 0) == len(rec.spans)
    png = respond_trace({"ext": "png"},
                        ServerObjects({"trace": rec.trace_id,
                                       "format": "png"}), a.sb)
    assert isinstance(png.raw_body, bytes)
    assert png.raw_body[:8] == b"\x89PNG\r\n\x1a\n"


def test_trace_servlet_lists_recent_and_summary(duo):
    a, _b = duo
    _index_docs(a, "gamma", n=6)
    a.sb.search("tracing", count=3)
    from yacy_search_server_tpu.server.servlets.monitoring import (
        respond_trace)
    prop = respond_trace({"ext": "json"}, ServerObjects({}), a.sb)
    assert prop.get_int("traces", 0) >= 1
    assert prop.get_int("enabled", 0) == 1
    assert prop.get("tail_dominant_stage", "") != ""
    jl = respond_trace({"ext": "jsonl"},
                       ServerObjects({"format": "jsonl"}), a.sb)
    assert jl.raw_body and "trace_id" in jl.raw_body


# -- /metrics exposition -----------------------------------------------------

def _parse_exposition(text):
    """Minimal format check: every non-comment line is `name[{labels}]
    value` with an optional OpenMetrics exemplar suffix on histogram
    buckets, HELP/TYPE precede their family's samples (histogram
    families declare TYPE on the base name; their samples carry the
    `_bucket`/`_sum`/`_count` suffixes)."""
    import re
    samples = []
    seen_type = set()
    hist_families = set()
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            if line.startswith("# TYPE "):
                name, kind = line.split()[2:4]
                assert kind in ("counter", "gauge", "histogram", "summary")
                seen_type.add(name)
                if kind == "histogram":
                    hist_families.add(name)
            continue
        m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
                     r"(\{[^}]*\})?\s+(-?[0-9.eE+-]+|\+Inf)"
                     r"(\s+#\s+\{[^}]*\}\s+-?[0-9.eE+-]+"
                     r"(\s+-?[0-9.eE+-]+)?)?$", line)
        assert m, f"bad exposition line: {line!r}"
        name = m.group(1)
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        assert name in seen_type or base in hist_families, \
            f"sample before TYPE: {line!r}"
        if m.group(4):
            assert base in hist_families, \
                f"exemplar on a non-histogram family: {line!r}"
        samples.append((name, m.group(2) or "", float(m.group(3))))
    return samples


def test_metrics_exposition(duo):
    a, _b = duo
    _index_docs(a, "delta", n=6)
    a.sb.search("tracing", count=3)
    from yacy_search_server_tpu.server.servlets.monitoring import (
        prometheus_text)
    text = prometheus_text(a.sb)
    samples = _parse_exposition(text)
    names = {s[0] for s in samples}
    assert "yacy_log_dropped_records_total" in names
    assert "yacy_stage_events_total" in names
    assert "yacy_crawler_queue_depth" in names
    assert "yacy_pipeline_processed_total" in names
    assert "yacy_index_documents" in names
    # node-level DHT counters (the switchboard belongs to a P2PNode)
    assert "yacy_dht_transferred_postings_total" in names
    # batcher cause buckets when the device store serves
    if a.sb.index.devstore is not None:
        causes = {lbl for (n, lbl, _v) in samples
                  if n == "yacy_batch_timeouts_total"}
        assert {'{cause="queue_full"}', '{cause="flush_deadline"}',
                '{cause="worker_stall"}'} <= causes


def test_metrics_servlet_content_type(duo):
    a, _b = duo
    from yacy_search_server_tpu.server.servlets.monitoring import (
        respond_metrics)
    prop = respond_metrics({"ext": "html"}, ServerObjects({}), a.sb)
    assert prop.raw_ctype.startswith("text/plain; version=0.0.4")
    assert prop.raw_body.endswith("\n")


def test_queues_servlet_exposes_log_drops(duo):
    a, _b = duo
    from yacy_search_server_tpu.server.servlets.admin import respond_queues
    prop = respond_queues({"ext": "json"}, ServerObjects({}), a.sb)
    assert prop.get("log_dropped_records") is not None


# -- mesh path ---------------------------------------------------------------

def test_mesh_batcher_emits_spans_under_one_trace():
    import numpy as np
    import jax
    devs = jax.devices("cpu")
    if len(devs) < 8:
        pytest.skip("need 8 cpu devices")
    from yacy_search_server_tpu.index import postings as P
    from yacy_search_server_tpu.index.meshstore import MeshSegmentStore
    from yacy_search_server_tpu.index.postings import PostingsList
    from yacy_search_server_tpu.index.rwi import RWIIndex
    from yacy_search_server_tpu.ops.ranking import RankingProfile
    from yacy_search_server_tpu.utils.hashes import word2hash

    rng = np.random.default_rng(3)
    n = 20_000
    th = word2hash("meshtraceterm")
    feats = rng.integers(0, 1000, (n, P.NF)).astype(np.int32)
    feats[:, P.F_FLAGS] = rng.integers(0, 2 ** 20, n)
    feats[:, P.F_DOMLENGTH] = rng.integers(0, 256, n)
    feats[:, P.F_LANGUAGE] = P.pack_language("en")
    rwi = RWIIndex()
    rwi.ingest_run({th: PostingsList(np.arange(n, dtype=np.int32), feats)})
    ms = MeshSegmentStore(rwi, devices=devs[:8], n_term=2)
    try:
        ms.enable_batching(max_batch=4)
        prof = RankingProfile()
        ms.rank_term(th, prof, k=10)        # warm: compile outside trace
        ms._topk_cache.clear()   # a cache hit would bypass the batcher
        tracing.clear()
        with tracing.trace("mesh-query") as r:
            tid = r.ctx[0]
            got = ms.rank_term(th, prof, k=10)
        assert got is not None
        rec = tracing.get_trace(tid)
        names = {s.name for s in rec.spans}
        assert "mesh.batch" in names, names
        assert any(nm.startswith("kernel.") for nm in names), names
    finally:
        ms.close()


# -- X-YaCy-Trace over real HTTP sockets -------------------------------------

def test_trace_header_propagates_over_http(tmp_path):
    """The originator's trace id crosses a REAL socket as the
    X-YaCy-Trace header (HttpTransport emits it, httpd parses it back,
    PeerServer roots the remote segment under it)."""
    from yacy_search_server_tpu.peers.transport import HttpTransport
    nodes = []
    for name in ("httptrace-a", "httptrace-b"):
        t = HttpTransport(timeout_s=10.0)
        n = P2PNode(name, t, data_dir=str(tmp_path / name),
                    partition_exponent=1, redundancy=1)
        n.serve_http()
        nodes.append(n)
    a, b = nodes
    try:
        a.bootstrap([b.seed])
        b.bootstrap([a.seed])
        a.ping()
        b.ping()
        _index_docs(b, "htb", n=6)
        tracing.clear()
        with tracing.trace("http-search") as r:
            tid = r.ctx[0]
            ev = a.search("tracing", count=3)
        assert ev.remote_peers_asked >= 1
        rec = tracing.get_trace(tid)
        assert rec is not None
        remote = [s for s in rec.spans if s.name == "peer.search"]
        assert remote, "remote segment missing under the trace"
        assert remote[0].attrs.get("peer") == b.seed.hash.decode("ascii")
    finally:
        for n in nodes:
            n.close()


# -- one clock: the profiler-annotation bridge (ISSUE 25) -------------------

def test_annotation_bridge_without_a_profiler_session_is_free():
    """No session records: a span opens no annotation (nothing is
    allocated for it), `annotation()` is the shared no-op, nothing
    raises; disabled, `span()` is the shared no-op too."""
    import jax  # noqa: F401  (the bridge binds once JAX is loaded)
    assert tracing._recording() is None and tracing._annotate("x") is None
    assert tracing.annotation("batcher.form") is tracing.span("nothing")
    with tracing.annotation("kernel.issue", kernel="k", batch_n=2):
        pass
    with tracing.trace("root") as r:
        assert r._ann is None
        with tracing.span("child") as c:
            assert c._ann is None
    tracing.set_enabled(False)
    assert tracing.span("x") is tracing.trace("y") \
        is tracing.span_in(("t", "s"), "z")


def test_spans_land_on_the_profilers_timeline_from_their_own_thread(
        tmp_path):
    """With a session recording (the options benchmarks/run.py sets),
    every live span, `timed()` wall and bare `annotation()` is an event
    of the host plane — the worker thread's on a line of its own — and
    an annotation's attributes are the event's stats."""
    import jax
    from jax.profiler import ProfileData
    po = jax.profiler.ProfileOptions()
    po.python_tracer_level = 0
    po.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=po)
    try:
        def worker():
            with tracing.annotation("kernel.issue", kernel="_k",
                                    batch_n=3):
                pass
            with tracing.timed("batcher.untraced_wall"):
                pass
        with tracing.trace("servlet.fake"):
            with tracing.span("search.fake_stage"):
                th = threading.Thread(target=worker)
                th.start()
                th.join()
    finally:
        jax.profiler.stop_trace()
    import glob
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[-1]
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.split(".")[0] in ("servlet", "search",
                                                 "kernel", "batcher"):
                        lines.setdefault(i, {})[ev.name] = dict(ev.stats)
    by_line = sorted(lines.values(), key=sorted)
    assert [sorted(names) for names in by_line] == [
        ["batcher.untraced_wall", "kernel.issue"],
        ["search.fake_stage", "servlet.fake"]], lines
    assert by_line[0]["kernel.issue"] == {"kernel": "_k", "batch_n": 3}


def test_timed_and_record_always_reach_the_family():
    from yacy_search_server_tpu.utils import histogram
    histogram.reset()
    # outside a trace: the family alone, no ring, no context
    with tracing.timed("stage.always") as sp:
        assert tracing.current() is None
        sp.set(ignored=1)
    tracing.record("stage.after", 2.0, why="x")
    assert tracing.traces(10) == []
    assert histogram.get("stage.always").count == 1
    assert histogram.get("stage.after").sum_ms == 2.0
    # under a trace: spans (which feed the family), parented on the
    # active context; emit stays a no-op outside one
    tracing.emit("stage.marker", 0.0)
    assert histogram.get("stage.marker") is None
    with tracing.trace("root") as r:
        with tracing.timed("stage.always"):
            tracing.record("stage.after", 3.0, ts=123.0, sid="fixed")
    by = {s.name: s for s in tracing.get_trace(r.ctx[0]).spans}
    assert by["stage.always"].parent == by["root"].sid
    assert by["stage.after"].parent == by["stage.always"].sid
    assert (by["stage.after"].sid, by["stage.after"].ts) == ("fixed", 123.0)
    assert histogram.get("stage.always").count == 2
    assert histogram.get("stage.after").sum_ms == 5.0
    # disabled: still measured, nothing traced
    tracing.set_enabled(False)
    with tracing.timed("stage.always"):
        pass
    tracing.record("stage.after", 1.0, ctx=r.ctx)
    assert histogram.get("stage.always").count == 3
    assert histogram.get("stage.after").count == 3
    assert len(tracing.get_trace(r.ctx[0]).spans) == 3


def test_a_span_renamed_inside_closes_under_its_outcome():
    from yacy_search_server_tpu.utils import histogram
    histogram.reset()
    with tracing.trace("root") as r:
        with tracing.timed("search.route") as rt:
            rt.rename("search.route.host_gate")
    with tracing.timed("search.route") as rt:       # untraced twin
        rt.rename("search.route.device")
    assert "search.route.host_gate" in {
        s.name for s in tracing.get_trace(r.ctx[0]).spans}
    assert histogram.get("search.route") is None
    assert histogram.get("search.route.host_gate").count == 1
    assert histogram.get("search.route.device").count == 1


def test_envelope_joins_the_trace_rooted_beneath_it():
    """httpd's wall around any servlet: always the two families; the
    callee's trace, when it rooted one, gets the wall as a parentless
    span and lends its id as the exemplar and as the parent context of
    what runs after it closed."""
    import time
    from yacy_search_server_tpu.utils import histogram
    histogram.reset()
    with tracing.envelope("servlet.serving", "servlet.cpu") as sv:
        assert sv.ctx is None
    assert tracing.traces(10) == []                 # no root: no span
    with tracing.envelope("servlet.serving", "servlet.cpu") as sv:
        with tracing.trace("servlet.fake") as r:
            t_end = time.thread_time() + 0.01
            while time.thread_time() < t_end:       # 10 ms of CPU
                pass
        time.sleep(0.02)                            # 20 ms of waiting
        assert sv.ctx[0] == r.ctx[0]
        with tracing.timed("servlet.render", sv.ctx):
            pass
    by = {s.name: s for s in tracing.get_trace(r.ctx[0]).spans}
    serving = by["servlet.serving"]
    assert serving.parent == "" and by["servlet.fake"].parent == ""
    assert by["servlet.render"].parent == serving.sid
    assert 9.0 <= serving.attrs["cpu_ms"] < serving.dur_ms - 15.0
    assert histogram.get("servlet.serving").count == 2
    assert histogram.get("servlet.cpu").count == 2
    ex = [e for e in histogram.get("servlet.serving").exemplars if e]
    assert ex and ex[-1][0] == r.ctx[0]


def test_collector_hook_takes_no_lock_and_files_later(monkeypatch):
    import gc
    from yacy_search_server_tpu.utils import histogram
    histogram.reset()
    tracing.watch_gc(False)
    tracing.watch_gc()
    tracing.watch_gc()                              # idempotent
    assert gc.callbacks.count(tracing._on_gc) == 1
    monkeypatch.setattr(tracing, "GC_SPAN_MIN_MS", 0.0)
    tracing._gc_pending.clear()
    try:
        # the hook runs INSIDE the spine's and a family's locked
        # sections without deadlock: it only queues
        with tracing._lock, histogram.histogram(tracing.GC_FAMILY)._lock:
            gc.collect()
        assert len(tracing._gc_pending) == 1
        with tracing.trace("root") as r:
            gc.collect()                            # interrupts the trace
        # the root's own record flushed both: the untraced one to the
        # family alone, the traced one as a span of the trace too
        spans = tracing.get_trace(r.ctx[0]).spans
        pause = [s for s in spans if s.name == "runtime.gc"]
        assert len(pause) == 1 and pause[0].attrs["generation"] == 2
        assert pause[0].parent == spans[-1].sid
        assert histogram.get("runtime.gc").count == 2
    finally:
        tracing.watch_gc(False)
    assert tracing._on_gc not in gc.callbacks
    gc.collect()
    assert not tracing._gc_pending


# -- the request's span tree, route by route (ISSUE 25) ----------------------

ROUTES = ("event_cache", "topk_cache", "device", "host_gate", "host_other")
_HTTP = {"servlet.serving", "servlet.render", "servlet.yacysearch",
         "switchboard.search", "search.page", "search.resultlist",
         "search.snippets"}
_HOST = {"search.join", "search.presort", "search.normalizing"}
_BATCH = {"devstore.batch", "batcher.queue", "kernel.issue",
          "kernel.device", "kernel.fetch"}
# query, what the route adds to _HTTP (+ "search.route.<route>")
# (a NEW event gathers its metadata: `search.metajoin`, PR 28; a cached
# event's page finds its cushion drained and reads nothing)
_NEW = {"search.metajoin"}
ROUTE_CASES = {
    "device": ("bigterm", {"search.devrank"} | _BATCH | _NEW),
    "event_cache": ("bigterm", set()),
    "topk_cache": ("bigterm&nocache=true", _NEW),
    "host_gate": ("small", _HOST | _NEW),
    "host_other": ("bigterm+site:h1.example+bigtwo", _HOST | _NEW),
}
PARENTS = {
    "search.metajoin": "search.resultlist",
    "servlet.serving": "", "servlet.yacysearch": "",
    "servlet.render": "servlet.serving",
    "switchboard.search": "servlet.yacysearch",
    "search.page": "servlet.yacysearch",
    "search.snippets": "search.page",
    "search.devrank": "search.route.device",
    "devstore.batch": "search.devrank",
    **{n: "devstore.batch" for n in _BATCH - {"devstore.batch"}},
}


@pytest.fixture(scope="module")
def served():
    """One node over real HTTP on a single-device store: two lists over
    the host gate (lowered to 100 rows), one under it."""
    import json
    import tempfile
    import urllib.request

    import numpy as np
    from yacy_search_server_tpu.index import postings as P
    from yacy_search_server_tpu.index.postings import PostingsList
    from yacy_search_server_tpu.server.httpd import YaCyHttpServer
    from yacy_search_server_tpu.switchboard import Switchboard
    from yacy_search_server_tpu.utils.config import Config
    from yacy_search_server_tpu.utils.hashes import word2hash
    cfg = Config()
    cfg.set("index.device.mesh", "off")
    sb = Switchboard(tempfile.mkdtemp(prefix="yacy-routes-"), config=cfg)
    n = 6000
    sb.index.metadata.bulk_load(
        [f"{i:07d}{i % 7:05d}".encode() for i in range(n)],
        sku=[f"http://h{i % 7}.example/d{i}" for i in range(n)],
        title=[f"doc {i}" for i in range(n)],
        host_s=[f"h{i % 7}.example" for i in range(n)],
        size_i=[1000] * n, wordcount_i=[100] * n)
    rng = np.random.default_rng(25)

    def plist(m):
        return PostingsList(
            np.sort(rng.choice(n, m, replace=False)).astype(np.int32),
            rng.integers(1, 50, (m, P.NF)).astype(np.int32))
    sb.index.rwi.ingest_run({word2hash("bigterm"): plist(5000),
                             word2hash("bigtwo"): plist(4000),
                             word2hash("small"): plist(40)})
    ds = sb.index.devstore
    ds.small_rank_n = 100
    srv = YaCyHttpServer(sb, port=0).start()

    def get(query):
        url = f"http://127.0.0.1:{srv.port}/yacysearch.json?query={query}"
        with urllib.request.urlopen(url) as r:
            ch = json.loads(r.read())["channels"][0]
        assert len(ch["items"]) == 10
        rec = None
        for _ in range(200):            # the envelope closes after the
            rec = tracing.get_trace(ch["traceID"])      # body is built
            if rec and any(s.name == "servlet.serving" for s in rec.spans):
                break
            threading.Event().wait(0.01)
        return rec

    get("bigterm&nocache=true")         # compile outside the traced runs
    yield sb, ds, get
    srv.close()
    sb.close()


def _fresh(sb, ds):
    from yacy_search_server_tpu.utils import histogram
    sb.search_cache.clear()
    ds._topk_cache.clear()
    tracing.clear()
    histogram.reset_windows()


def _route_counts():
    from yacy_search_server_tpu.utils import histogram
    out = {}
    for r in ROUTES:
        h = histogram.get("search.route." + r)
        out[r] = h.windowed_count() if h is not None else 0
    return out


@pytest.mark.parametrize("route", ROUTES)
def test_one_request_closes_its_routes_span_tree(served, route):
    """GET /yacysearch.json through each of the five routes: exactly the
    listed spans under ONE trace id, the right parents, no child
    outside its parent, exactly one `search.route.*`."""
    sb, ds, get = served
    _fresh(sb, ds)
    if route in ("event_cache", "topk_cache"):
        get("bigterm")                  # the answer the caches repeat
    query, adds = ROUTE_CASES[route]
    rec = get(query)
    spans = [s for s in rec.spans
             if not s.name.startswith(("runtime.", "tail."))]
    names = {s.name for s in spans}
    kernels = {n for n in names if n.startswith("kernel._")}
    assert len(kernels) == (route == "device")      # the kernel's wall
    assert names - kernels == _HTTP | adds | {"search.route." + route}
    assert [s.name for s in spans if s.name.startswith("search.route")] \
        == ["search.route." + route]
    by_sid = {s.sid: s for s in rec.spans}
    for s in spans:
        want = PARENTS.get(s.name)
        if s.name.startswith("search.route."):
            want = "switchboard.search"
        elif s.name in _HOST:
            want = "search.route." + route
        elif s.name in kernels:
            want = "devstore.batch"
        got = by_sid[s.parent].name if s.parent else ""
        if s.name == "search.resultlist":   # the event's join, the page's
            assert got in ("switchboard.search", "search.page")
            continue
        assert got == want, (s.name, got, want)
    # no child starts before or ends after its parent (1 ms for the two
    # clocks a span is stamped with), so child cover never exceeds it
    for s in spans:
        outer = by_sid.get(s.parent) or next(
            x for x in spans if x.name == "servlet.serving")
        if s is outer:
            continue
        assert s.ts >= outer.ts - 0.001, (s.name, outer.name)
        assert s.ts + s.dur_ms / 1e3 <= outer.ts + outer.dur_ms / 1e3 \
            + 0.001, (s.name, outer.name)
    serving = next(s for s in spans if s.name == "servlet.serving")
    assert 0.0 < serving.attrs["cpu_ms"] <= serving.dur_ms + 1.0
    if route == "device":
        batch = next(s for s in spans if s.name == "devstore.batch")
        assert batch.attrs["batch_n"] == 1
        assert batch.attrs["kernel"].startswith("_rank_pruned")
        assert "kernel." + batch.attrs["kernel"] in kernels


def test_route_counts_tie_to_the_stores_counters(served):
    """The identity, from the code: a `topk_cache` route is one
    rank_cache_get hit (`rank_cache_hits` and `queries_served` move), a
    `device` route one rank_term / rank_join answer (`queries_served`
    moves), a declined device attempt is `fallbacks` and a `host_other`
    route; `event_cache` and `host_gate` never reach the store. So
    topk_cache + device == d(queries_served), topk_cache ==
    d(rank_cache_hits), d(fallbacks) <= host_other, and the five sum to
    the searches made."""
    sb, ds, get = served
    _fresh(sb, ds)
    c0 = ds.counters()
    plan = ["bigterm", "bigterm", "bigterm&nocache=true", "small",
            "bigterm+bigtwo", "bigterm+bigtwo&nocache=true", "small",
            "bigterm+site:h1.example+bigtwo", "bigtwo", "bigtwo"]
    for q in plan:
        get(q)
    # a store that declines: the device attempt falls to the host
    ds._topk_cache.clear()
    ds.device_lost = True
    try:
        get("bigtwo&nocache=true")
    finally:
        ds.device_lost = False
    c1 = ds.counters()
    d = {k: c1[k] - c0[k] for k in ("queries_served", "rank_cache_hits",
                                    "fallbacks")}
    routes = _route_counts()
    assert routes == {"event_cache": 3, "topk_cache": 1, "device": 4,
                      "host_gate": 1, "host_other": 2}, routes
    assert sum(routes.values()) == len(plan) + 1
    assert routes["topk_cache"] + routes["device"] == d["queries_served"]
    assert routes["topk_cache"] == d["rank_cache_hits"]
    assert 1 == d["fallbacks"] <= routes["host_other"]


def test_the_queue_wait_is_measured_with_tail_attribution_off(served):
    """`batcher.queue` is stamped by the dispatcher that takes the part,
    always; the tail classifier reads that one stamp when it is on."""
    from yacy_search_server_tpu.utils import histogram, tailattr
    sb, ds, get = served
    _fresh(sb, ds)
    was = tailattr.enabled()
    tailattr.set_enabled(False)
    try:
        rec = get("bigterm")
    finally:
        tailattr.set_enabled(was)
    by = {s.name: s for s in rec.spans}
    assert "wave_n" not in by["devstore.batch"].attrs
    assert 0.0 <= by["batcher.queue"].dur_ms <= by["devstore.batch"].dur_ms
    assert histogram.get("batcher.queue").windowed_count() == 1
    _fresh(sb, ds)
    rec = get("bigterm")
    by = {s.name: s for s in rec.spans}
    assert by["devstore.batch"].attrs["wave_queue_ms"] == pytest.approx(
        by["batcher.queue"].dur_ms, abs=0.001)
