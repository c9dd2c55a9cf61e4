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

Nothing here starts a node or touches the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import tempfile

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
    ap.add_argument("--out")
    args = ap.parse_args()
    sys.path.insert(0, args.tree)
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
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
