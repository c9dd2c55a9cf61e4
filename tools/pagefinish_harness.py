"""What a result page's finish costs under threads (ISSUE 31).

Not a test and not a benchmark cell: a measurement of HOST work, run by
hand (here, or on the chip's host through the chip tool), in the form of
``tools/metajoin_harness.py`` and with its ``measure``. Between the
ranked page and the response bytes a request (a) asks the page cache for
ten snippets it does not hold, as ``SearchEvent._produce_snippets`` does
for a corpus that stores no ``text_t`` (``SnippetProducer.produce_many``,
strategy ``cacheonly``, over a node's ``HTCache`` on disk and empty), and
(b) renders ``yacysearch.json`` with ten items and the navigators a
``wiki.long`` page carries (``hosts`` with ten elements, ``year`` with
one; ``--navs`` for other shapes). Both are timed from 1 / 4 / 8 threads,
alone and together, next to the bare calls under suspicion. The other
side of (a), a peer that crawled what it indexed, is ``produce_many
(ten hits)``: a second ``HTCache`` holds every page's ten URLs on disk
(``--hit-bytes`` of HTML each, its RAM buffer emptied), so every job
opens, reads, unpacks and parses. ``--tree``
names the checkout to import the package from, so the parent and the
change are measured on one machine:

    python tools/pagefinish_harness.py --tree /path/to/parent --out p.json
    python tools/pagefinish_harness.py --out c.json

``--front`` (ISSUE 38) measures the other end of the request instead:
what an answer of the EVENT CACHE costs the interpreter in the front. A
host-only node (no device store) whose event cache holds the page (ten
items, the navigators a ``wiki.head`` page carries: ``hosts`` with ten
elements, ``year``, ``language``, ``filetype``), its ``YaCyHttpServer``'s
REAL ``Handler`` driven with the keep-alive request the benchmark's client
sends, through an in-memory socket, from 1 / 4 / 8 threads: calls/s; the
thread CPU and the wall of a call on one thread, piece by piece; and the
census of release points: a sampler reads ``sys._current_frames()`` every
2 ms while four threads drive the request, and counts, per line, the
worker threads that stand there. Whoever the sampler finds is not running
(the sampler is): it stands where it last let go of the interpreter lock,
by itself (a lock, a file, ``os.urandom``) or made to at the end of a
switch interval (those spread over every line; the others pile up).

    JAX_PLATFORMS=cpu python tools/pagefinish_harness.py --front \
        [--tree /path/to/parent] [--out f.json]

Nothing here touches the chip; only ``--front`` starts a node.
"""

from __future__ import annotations

import argparse
import collections
import io
import json
import os
import random
import sys
import tempfile
import threading
import time

from metajoin_harness import build_store, measure

PAGE = 10
# pages the filled cache holds (a `store` is two files: on the chip's
# host, filling takes longer than measuring)
HIT_PAGES = 64
WORDS = ["alpha", "beta"]
FILLER = ("search peer index crawl word page link rank host term list "
          "merge cache shard query result title").split()


def page_props(objects, rnd: random.Random, navs: list[int]):
    """The property map `servlets/yacysearch.py` fills for one page."""
    esc = objects.escape_json
    p = objects.ServerObjects()
    p.put("former", esc("alpha beta"))
    p.put("count", PAGE)
    p.put("offset", 0)
    p.put("searchtime", rnd.randrange(5, 40))
    p.put("totalcount", rnd.randrange(100, 5000))
    p.put("found", 1)
    p.put("contentdom_image", 0)
    p.put("eventID", esc("%016x" % rnd.getrandbits(64)))
    p.put("traceID", esc("%032x" % rnd.getrandbits(128)))
    p.put("items", PAGE)
    urls = []
    for i in range(PAGE):
        d = rnd.randrange(2_500_000)
        url = f"http://h{d % 4096}.example/wiki/doc{d}.html"
        urls.append(url)
        q = f"items_{i}_"
        p.put(q + "title", esc(f"doc {d}"))
        p.put(q + "link", esc(url))
        p.put(q + "description", esc(""))
        p.put(q + "size", 1000)
        p.put(q + "sizename", "1000 bytes")
        p.put(q + "urlhash", esc(f"{d:07d}{d % 4096:05d}"))
        p.put(q + "host", esc(f"h{d % 4096}.example"))
        p.put(q + "ranking", rnd.randrange(1 << 20))
        p.put(q + "source", "local")
        p.put(q + "eol", 1 if i < PAGE - 1 else 0)
    p.put("navigation", len(navs))
    for i, n in enumerate(navs):
        q = f"navigation_{i}_"
        p.put(q + "facetname", esc(f"facet{i}"))
        p.put(q + "elements", n)
        for j in range(n):
            e = f"{q}elements_{j}_"
            p.put(e + "name", esc(f"h{rnd.randrange(4096)}.example"))
            p.put(e + "count", rnd.randrange(1, 9))
            p.put(e + "url", "yacysearch.html?query=alpha%20beta")
            p.put(e + "eol", 1 if j < n - 1 else 0)
        p.put(q + "eol", 1 if i < len(navs) - 1 else 0)
    return urls, p


def html_page(url: str, size: int) -> bytes:
    """A crawled page of about `size` bytes: paragraphs of plain words,
    the query's two in one sentence somewhere in the middle."""
    rnd = random.Random(url)
    paras, n = [], 0
    while n < size:
        words = [rnd.choice(FILLER) for _ in range(rnd.randrange(30, 90))]
        paras.append("<p>" + " ".join(words).capitalize() + ".</p>")
        n += len(paras[-1]) + 1
    paras.insert(len(paras) // 2, "<p>Where alpha meets beta.</p>")
    return ("<html><head><title>%s</title></head><body>\n%s\n</body></html>"
            % (url, "\n".join(paras))).encode("utf-8")


def pieces(tmp: str, rows: int, pages: list, hit_bytes: int) -> dict:
    """name -> callable((urls, props)); imports come from --tree."""
    from yacy_search_server_tpu.crawler.cache import HTCache
    from yacy_search_server_tpu.crawler.loader import LoaderDispatcher
    from yacy_search_server_tpu.index import metadata
    from yacy_search_server_tpu.search import snippet
    from yacy_search_server_tpu.server import httpd, templates
    from yacy_search_server_tpu.utils import hashes

    cache = HTCache(os.path.join(tmp, "HTCACHE"))
    loader = LoaderDispatcher(cache)

    # what YaCyHttpServer._render reads of self
    front = argparse.Namespace(
        templates=templates.TemplateEngine([httpd.DEFAULT_HTROOT]))

    def snippets(page):
        got = snippet.SnippetProducer(loader, "cacheonly").produce_many(
            page[0], WORDS)
        assert len(got) == PAGE and not got[0][0]

    def render(page):
        body = httpd.YaCyHttpServer._render(front, "yacysearch", "json",
                                            page[1])
        return body.encode("utf-8")

    out = {"produce_many": snippets, "_render": render,
           "both": lambda page: (snippets(page), render(page))}

    # the same ten jobs where the cache HOLDS every page, on disk
    filled = HTCache(os.path.join(tmp, "HTCACHE-FILLED"))
    held = LoaderDispatcher(filled)
    for urls, _props in pages[:HIT_PAGES]:
        for u in urls:
            filled.store(u, html_page(u, hit_bytes),
                         {"content-type": "text/html"})
    with filled._lock:
        filled._ram.clear()

    def hits(page):
        got = snippet.SnippetProducer(held, "cacheonly").produce_many(
            page[0], WORDS)
        assert len(got) == PAGE and all(s for s, _o in got)

    out["produce_many (ten hits)"] = hits
    # the same ten, one after another on the calling thread
    out["produce x 10 (hits on the caller)"] = lambda page: [
        snippet.SnippetProducer(held, "cacheonly").produce(u, WORDS)
        for u in page[0]]

    # the suspects, bare
    absent = os.path.join(tmp, "HTCACHE", "zz", "never-written.gz")
    pool = snippet._pool()
    store = build_store(metadata, rows, rows, os.path.join(tmp, "META"))
    out["HTCache.get miss x 10"] = lambda page: [
        cache.get(u) for u in page[0]]
    out["os.path.exists (absent) x 10"] = lambda page: [
        os.path.exists(absent) for _ in page[0]]
    out["pool.map no-op x 10"] = lambda page: list(
        pool.map(len, page[0]))
    out["url2hash x 10"] = lambda page: [
        hashes.url2hash(u) for u in page[0]]
    out["text_value(text_t) x 10"] = lambda page: [
        store.text_value(hash(u) % rows, "text_t") for u in page[0]]
    return out


# -- the front: a cached answer through the real Handler (ISSUE 38) ----------

FRONT_DOCS = 14
FRONT_WORDS = ("numbat", "quokka", "wombat", "bilby", "potoroo", "dunnart",
               "bettong", "kowari")
CENSUS_TICK_S = 0.002


class _Sink(io.RawIOBase):
    """Where a response's bytes go: the last write is kept."""

    last = b""

    def writable(self):
        return True

    def write(self, b):
        self.last = bytes(b)
        return len(b)


class _Socket:
    """What `StreamRequestHandler.setup` asks of a connection."""

    def __init__(self):
        self.sink = _Sink()

    def setsockopt(self, *_a):
        pass

    def makefile(self, mode, bufsize=-1):
        if "r" in mode:
            return io.BytesIO()
        return io.BufferedWriter(self.sink, bufsize)


def connection(handler_cls, server):
    """A kept-alive connection of the server's handler class: `setup()`
    as `socketserver` runs it, and no request yet."""
    h = handler_cls.__new__(handler_cls)
    h.request, h.client_address, h.server = (
        _Socket(), ("127.0.0.1", 50_000), server)
    h.setup()
    return h


def serve(h, wire: bytes) -> bytes:
    """One request of a kept-alive connection: the loop body of
    `BaseHTTPRequestHandler.handle`. Returns headers and body."""
    h.rfile = io.BytesIO(wire)
    h.handle_one_request()
    return h.request.sink.last


def front_node(tmp: str):
    """A host-only node of FRONT_DOCS documents on as many hosts, each
    holding every word of FRONT_WORDS, and its HTTP front (not started:
    nothing listens)."""
    from yacy_search_server_tpu.document.document import Document
    from yacy_search_server_tpu.server import YaCyHttpServer
    from yacy_search_server_tpu.switchboard import Switchboard
    from yacy_search_server_tpu.utils.config import Config
    cfg = Config()
    cfg.set("index.device.serving", "false")
    sb = Switchboard(data_dir=os.path.join(tmp, "DATA"), config=cfg,
                     transport=lambda u, h: (404, {}, b""))
    for i in range(FRONT_DOCS):
        sb.index.store_document(Document(
            url=f"http://h{i}.example/wiki/doc{i}.html", title=f"doc {i}",
            text=(" ".join(FRONT_WORDS) + f" page {i}. ") * 4,
            mime_type="text/html", language="en",
            publish_date_days=19_000 + i))
    # the benchmark's four clients are one host, and past
    # httpd.maxAccessPerHost.600s (6,000) eight seconds into a run: the
    # front takes the over-the-limit branch for all but that start
    for _ in range(6_001):
        sb.access_tracker.track_access("127.0.0.1")
    return sb, YaCyHttpServer(sb, port=0)


def front_requests(sb, srv) -> list:
    """One request a word, as `benchmarks/client.py` sends it
    (`http.client`'s header block), with what the pieces need of it; the
    first answer of each fills the event cache."""
    from urllib.parse import urlencode
    from yacy_search_server_tpu.server import objects, servlets
    out = []
    h = connection(srv.httpd.RequestHandlerClass, srv.httpd)
    for word in FRONT_WORDS:
        params = {"query": word, "maximumRecords": "10"}
        wire = (f"GET /yacysearch.json?{urlencode(params)} HTTP/1.1\r\n"
                f"Host: 127.0.0.1:{srv.port}\r\n"
                "Accept-Encoding: identity\r\n\r\n").encode("ascii")
        head, _, body = serve(h, wire).partition(b"\r\n\r\n")
        assert head.startswith(b"HTTP/1.1 200"), head
        page = json.loads(body)["channels"][0]
        assert len(page["items"]) == PAGE, len(page["items"])
        event = sb.search(word, count=PAGE)
        out.append(argparse.Namespace(
            wire=wire, query=word, params=params, body=body, event=event,
            results=event.results(offset=0, count=PAGE),
            navigation={n["facetname"]: len(n["elements"])
                        for n in page["navigation"]},
            prop=servlets.lookup("yacysearch")(
                {"ext": "json", "path": "/yacysearch.json"},
                objects.ServerObjects(params), sb)))
    return out


def front_pieces(sb, srv, requests: list) -> dict:
    """name -> callable(request); imports come from --tree. The whole
    request first, then what it is made of, each alone."""
    from yacy_search_server_tpu.search.accesstracker import QueryLogEntry
    from yacy_search_server_tpu.search.query import QueryParams
    from yacy_search_server_tpu.server import objects, servlets
    from yacy_search_server_tpu.server.servlets import yacysearch
    from yacy_search_server_tpu.utils import tracing

    handler = srv.httpd.RequestHandlerClass
    body = requests[0].body

    class Bare(handler):
        """The same parse and the same send around no work at all."""

        def do_GET(self):
            srv._send(self, 200, "application/json; charset=utf-8", body)

    local = threading.local()

    def conn(cls):
        h = getattr(local, cls.__name__, None)
        if h is None:
            h = connection(cls, srv.httpd)
            setattr(local, cls.__name__, h)
        return h

    # what one page escapes and puts, recorded from one servlet call
    servlet = servlets.lookup("yacysearch")
    header = {"ext": "json", "path": "/yacysearch.json",
              "client_ip": "127.0.0.1", "method": "GET", "degrade": 0,
              "admin": True, "accept": "", "host": "127.0.0.1"}
    escaped, puts = [], []
    esc, put = yacysearch.escape_json, objects.ServerObjects.put

    def spy_esc(v):
        escaped.append(v)
        return esc(v)

    def spy_put(self, k, v):
        puts.append((k, v))
        put(self, k, v)

    yacysearch.escape_json, objects.ServerObjects.put = spy_esc, spy_put
    try:
        servlet(dict(header), objects.ServerObjects(requests[0].params), sb)
    finally:
        yacysearch.escape_json, objects.ServerObjects.put = esc, put
    tracker = sb.access_tracker
    suffix = f"&maximumRecords={PAGE}"

    def spans(rq):
        with tracing.envelope("servlet.serving", "servlet.cpu") as sv:
            with tracing.trace("servlet.yacysearch", ext="json"):
                with tracing.trace("switchboard.search", q=rq.query,
                                   count=PAGE, offset=0):
                    tracing.record("search.route.event_cache", 0.01)
                with tracing.timed("search.page"):
                    pass
                tracing.current_trace_id()
            with tracing.timed("servlet.render", sv.ctx):
                pass

    def prop_put(_rq):
        p = objects.ServerObjects()
        for k, v in puts:
            p.put(k, v)

    asked = QueryParams.parse(requests[0].query)

    # the front around a page that is ready: admission, the query
    # string, the security checks, the envelope, the send
    ready = objects.ServerObjects()
    ready.raw_body = body.decode("utf-8")
    servlets.servlet("harnessready")(lambda _h, _p, _sb: ready)

    return {
        "request": lambda rq: serve(conn(handler), rq.wire),
        "http.server parse + send": lambda rq: serve(conn(Bare), rq.wire),
        "_handle around a ready body": lambda rq: serve(
            conn(handler), rq.wire.replace(b"/yacysearch.", b"/harnessready.")),
        "servlet yacysearch": lambda rq: servlet(
            dict(header), objects.ServerObjects(rq.params), sb),
        "Switchboard.search (event cache)": lambda rq: sb.search(
            rq.query, count=PAGE, offset=0),
        "QueryParams.parse": lambda rq: QueryParams.parse(rq.query),
        # (until PR 37 a request asked twice: the cache's lookup and the
        # page's `eventID`)
        "query_id": lambda _rq: asked.query_id(),
        "event.results": lambda rq: rq.event.results(offset=0, count=PAGE),
        f"escape_json x {len(escaped)} (the page's strings)":
            lambda _rq: [esc(v) for v in escaped],
        "_fill_items (escape_json)": lambda rq: yacysearch._fill_items(
            objects.ServerObjects(), rq.results, esc),
        "_fill_items (str)": lambda rq: yacysearch._fill_items(
            objects.ServerObjects(), rq.results, str),
        "_fill_navigation": lambda rq: yacysearch._fill_navigation(
            objects.ServerObjects(), rq.event, esc, base_query=rq.query,
            url_suffix=suffix),
        f"ServerObjects.put x {len(puts)} (as the page calls it)": prop_put,
        "_render": lambda rq: srv._render(
            "yacysearch", "json", rq.prop).encode("utf-8"),
        "tracing (envelope, two roots, three spans)": spans,
        "new_trace_id": lambda _rq: tracing.new_trace_id(),
        "track_access + retry_after_s + add": lambda rq: (
            tracker.track_access("127.0.0.1"),
            tracker.retry_after_s("127.0.0.1", 6_000),
            tracker.add(QueryLogEntry(
                query=rq.query, timestamp=time.time(), query_count=1,
                result_count=FRONT_DOCS, time_ms=0.1))),
    }


def census(fn, cands: list, threads: int, seconds: float,
           tree: str) -> dict:
    """Where the worker threads stand while `threads` of them call `fn`:
    per line, its share of all the worker frames the sampler met."""
    import linecache
    stop = threading.Event()
    done: list[int] = []

    def work(seed):
        rnd, n = random.Random(seed), 0
        while not stop.is_set():
            fn(cands[rnd.randrange(len(cands))])
            n += 1
        done.append(n)

    ts = [threading.Thread(target=work, args=(s,)) for s in range(threads)]
    for t in ts:
        t.start()
    mine = {t.ident for t in ts}
    seen: collections.Counter = collections.Counter()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        time.sleep(CENSUS_TICK_S)
        for tid, frame in sys._current_frames().items():
            if tid in mine:
                seen[(frame.f_code.co_filename, frame.f_lineno)] += 1
    wall = time.perf_counter() - t0
    stop.set()
    for t in ts:
        t.join(60)
    total = sum(seen.values())
    lines = []
    for (path, line), n in seen.most_common(16):
        text = linecache.getline(path, line).strip()
        where = os.path.relpath(path, tree) if path.startswith(tree) \
            else os.path.join("<lib>", os.path.basename(path))
        lines.append({"at": f"{where}:{line}", "line": text[:72],
                      "share_pct": round(100.0 * n / total, 1)})
    return {"threads": threads, "frames": total,
            "calls_per_s": round(sum(done) / wall, 1), "lines": lines}


def front(args) -> dict:
    with tempfile.TemporaryDirectory() as tmp:
        sb, srv = front_node(tmp)
        try:
            requests = front_requests(sb, srv)
            made = front_pieces(sb, srv, requests)
            only = [n for n in args.only.split(",") if n]
            result = {"tree": args.tree,
                      "body_bytes": len(requests[0].body),
                      "navigation": requests[0].navigation,
                      "switch_interval_s": sys.getswitchinterval(),
                      "cpus": os.cpu_count(), "pieces": {}, "census": []}
            for name, fn in made.items():
                if only and name not in only:
                    continue
                for rq in requests * 8:
                    fn(rq)
                # the whole request from every thread count (and the
                # tracker's three calls: its lock is the suspect); a
                # piece of it alone, where wall and thread CPU agree
                spread = name == "request" or name.startswith("track_")
                result["pieces"][name] = [
                    measure(fn, requests, int(t), args.calls)
                    for t in (args.threads.split(",") if spread else ["1"])]
                print(name, json.dumps(result["pieces"][name]), flush=True)
            for t in args.census.split(","):
                if t and not only:
                    result["census"].append(census(
                        made["request"], requests, int(t), args.census_s,
                        args.tree))
                    print("census", json.dumps(result["census"][-1]),
                          flush=True)
        finally:
            srv.httpd.server_close()
            sb.close()
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tree", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--navs", default="10,1",
                    help="elements of each navigator on the page")
    ap.add_argument("--rows", type=int, default=100_000)
    ap.add_argument("--threads", default="1,4,8")
    ap.add_argument("--calls", type=int, default=1000)
    ap.add_argument("--hit-bytes", type=int, default=20_000,
                    help="HTML bytes of each page the filled cache holds")
    ap.add_argument("--only", default="",
                    help="comma-separated piece names (default: all)")
    ap.add_argument("--front", action="store_true",
                    help="measure a cached answer through the real "
                         "Handler instead (ISSUE 38)")
    ap.add_argument("--census", default="4",
                    help="thread counts of the census of release points")
    ap.add_argument("--census-s", type=float, default=6.0)
    ap.add_argument("--out")
    args = ap.parse_args()
    args.tree = os.path.abspath(args.tree)
    sys.path.insert(0, args.tree)
    if args.front:
        write_out(args.out, front(args))
        return
    from yacy_search_server_tpu.server import objects
    rnd = random.Random(31)
    navs = [int(n) for n in args.navs.split(",") if n]
    pages = [page_props(objects, rnd, navs) for _ in range(256)]
    with tempfile.TemporaryDirectory() as tmp:
        made = pieces(tmp, args.rows, pages, args.hit_bytes)
        only = [n for n in args.only.split(",") if n]
        result = {"tree": args.tree, "navs": navs,
                  "body_bytes": len(made["_render"](pages[0])),
                  "switch_interval_s": sys.getswitchinterval(),
                  "cpus": os.cpu_count(), "pieces": {}}
        for name, fn in made.items():
            if only and name not in only:
                continue
            for page in pages[:32]:
                fn(page)         # template compiled, pool started
            result["pieces"][name] = [
                measure(fn, pages[:HIT_PAGES] if "hits" in name else pages,
                        int(t), args.calls)
                for t in args.threads.split(",")]
            print(name, json.dumps(result["pieces"][name]), flush=True)
    write_out(args.out, result)


def write_out(path: str | None, result: dict) -> None:
    if path:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
