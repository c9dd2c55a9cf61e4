"""Snippet extraction — best sentence window for the query words.

Capability equivalent of the reference's snippet machinery (reference:
source/net/yacy/search/snippet/TextSnippet.java and
source/net/yacy/document/SnippetExtractor.java): pick the shortest
sentence combination containing the most query words, trim to a maximum
length around the match, and mark whether all words matched.

``SnippetProducer`` is the live half (VERDICT r2 missing #4): when the
stored ``text_t`` is gone (blanked row, remote result, imported
metadata), the page is fetched through the crawler's LoaderDispatcher
under the query's cacheStrategy — CACHEONLY by default (never hit the
network at query time, the reference's p2p default), IFEXIST for
intranet deployments — parsed, and the snippet extracted from the live
text. Results whose snippet cannot be produced are EVICTED from the
page and, when the fetch proved the URL dead (4xx/5xx, not a transport
error), deleted from the local index — the reference's
``deleteIfSnippetFail`` result-quality mechanism
(SearchEvent.java:1862-1948).
"""

from __future__ import annotations

import re
from concurrent.futures import ThreadPoolExecutor

_SENTENCE_RE = re.compile(r"[^.!?\n\r]+[.!?]?")
MAX_SNIPPET_LENGTH = 220


def extract_snippet(text: str, words: list[str],
                    max_length: int = MAX_SNIPPET_LENGTH) -> tuple[str, bool]:
    """(snippet, all_words_matched) — best-coverage shortest sentence set."""
    if not text or not words:
        return text[:max_length], False
    lw = [w.lower() for w in words]
    best, best_hits, best_len = "", 0, 1 << 30
    for m in _SENTENCE_RE.finditer(text):
        s = m.group().strip()
        if not s:
            continue
        sl = s.lower()
        hits = sum(1 for w in lw if w in sl)
        if hits > best_hits or (hits == best_hits and 0 < hits
                                and len(s) < best_len):
            best, best_hits, best_len = s, hits, len(s)
            if hits == len(lw) and len(s) <= max_length:
                break
    if not best:
        best = text[:max_length]
    if len(best) > max_length:
        # center the window on the first matching word
        pos = min((best.lower().find(w) for w in lw
                   if best.lower().find(w) >= 0), default=0)
        start = max(0, pos - max_length // 3)
        best = ("..." if start else "") + best[start:start + max_length] + "..."
    return best, best_hits == len(lw)


# outcomes of a live snippet attempt
SNIPPET_OK = "ok"            # snippet produced
SNIPPET_UNVERIFIED = "unverified"   # nothing cached / transport error —
#                                     the URL is not proven dead
SNIPPET_DEAD = "dead"        # the fetch proved the URL gone (4xx/5xx)

MAX_SNIPPET_WORKERS = 4

# ONE shared pool for all page renders, for the strategies that may
# wait for the network: per-query ThreadPoolExecutor construction + join
# cost ~2 ms/query on the serving path (profiled in r4)
_POOL: ThreadPoolExecutor | None = None


def _pool() -> ThreadPoolExecutor:
    global _POOL
    if _POOL is None:
        _POOL = ThreadPoolExecutor(max_workers=MAX_SNIPPET_WORKERS,
                                   thread_name_prefix="snippet")
    return _POOL


class SnippetProducer:
    """Live snippet production through the crawler's loader.

    One per SearchEvent page render; `produce_many` fetches the page's
    missing snippets with a small worker pool (the reference's
    concurrent snippet workers, SearchEvent.java:1862-1930) where the
    strategy may go to the network, and on the calling thread where it
    cannot. Under CACHEONLY a miss is a set lookup, and a hit is some
    file reads around a parse in pure Python: ten hits a page read the
    same from the pool and from the caller, 1 to 8 threads (PERF.md,
    PR 31), so a hand-off buys neither anything and costs two switches
    a URL. `pooled` says how many jobs of the last `produce_many` went
    through the pool."""

    def __init__(self, loader, strategy: str = "cacheonly"):
        self.loader = loader
        self.strategy = strategy
        self.pooled = 0

    def produce(self, url: str, words: list[str]) -> tuple[str, str]:
        """(snippet, outcome) for one URL under the cacheStrategy."""
        from ..crawler.request import Request
        if self.loader is None:
            return "", SNIPPET_UNVERIFIED
        try:
            resp = self.loader.load(Request(url=url), self.strategy)
        except Exception:
            return "", SNIPPET_UNVERIFIED
        status = resp.status or 0
        if "x-error" in resp.headers:
            # synthetic response (cache miss under CACHEONLY, transport
            # failure): the document was never actually answered for —
            # proves nothing about the URL
            return "", SNIPPET_UNVERIFIED
        if status in (404, 410):
            # the server answered that the document is GONE — the
            # deleteIfSnippetFail signal. Access-denied (401/403 — WAFs
            # routinely 403 crawler-shaped fetches of live pages),
            # transient statuses (429, 5xx), and transport errors prove
            # nothing and must never purge a live document.
            return "", SNIPPET_DEAD
        if status != 200 or not resp.content:
            return "", SNIPPET_UNVERIFIED
        try:
            from ..document.parser.registry import parse_source
            ctype = resp.headers.get("content-type", "text/html")
            docs = parse_source(url, ctype.split(";")[0].strip(),
                                resp.content)
            text = "\n".join(d.text for d in docs if d.text)
        except Exception:
            return "", SNIPPET_UNVERIFIED
        if not text:
            return "", SNIPPET_UNVERIFIED
        snippet, _all = extract_snippet(text, words)
        return snippet, SNIPPET_OK

    def produce_many(self, urls: list[str],
                     words: list[str]) -> list[tuple[str, str]]:
        # one job never needed a worker
        if self.strategy == "cacheonly" or len(urls) <= 1:
            self.pooled = 0
            return [self.produce(u, words) for u in urls]
        self.pooled = len(urls)
        return list(_pool().map(lambda u: self.produce(u, words), urls))
