"""Operator surface — the admin-servlet breadth pass (VERDICT r2 #5).

~26 additional admin/UI servlets covering the most-used reference pages
(reference: htroot/ConfigAppearance_p.java, ConfigSearchPage_p.java,
ConfigRobotsTxt_p.java, AccessGrid_p.java, Connections_p.java,
ViewLog_p.java, Threaddump_p.java, Performance_p.java,
PerformanceSearch_p.java, CrawlCheck_p.java, RemoteCrawl_p.java,
Autocrawl_p.java, IndexSchema_p.java, IndexDeletion_p.java,
IndexImport*_p.java, Translator_p.java, ConfigHTCache_p.java,
RegexTest.java, BlacklistTest_p.java, SearchAccessRate_p.java,
yacyinteractive.java, robots.java, Help.java).

Every servlet fills a property map; pages with a bespoke template in
htroot/ render it, the rest render through the generic admin page
(env/generic_page.html) — real HTML chrome either way.
"""

from __future__ import annotations

import os
import re
import sys
import threading
import time
import traceback

from ..objects import ServerObjects, escape_html
from . import servlet

# -- appearance / search page / portal -------------------------------------


@servlet("ConfigAppearance_p")
def config_appearance(header, post, sb):
    prop = ServerObjects()
    cfg = sb.config
    if post.get("set", ""):
        for key in ("promoteSearchPageGreeting", "locale.language",
                    "appearance.skin"):
            if post.get(key, "") != "":
                cfg.set(key, post.get(key))
    prop.put("greeting", escape_html(
        cfg.get("promoteSearchPageGreeting", "YaCy TPU P2P Web Search")))
    prop.put("language", escape_html(cfg.get("locale.language", "default")))
    prop.put("skin", escape_html(cfg.get("appearance.skin", "default")))
    return prop


_SEARCHPAGE_FLAGS = (
    "search.result.show.date", "search.result.show.size",
    "search.result.show.metadata", "search.result.show.proxy",
    "search.result.show.hostbrowser", "search.result.show.tags",
    "search.navigation.hosts", "search.navigation.filetype",
    "search.navigation.authors", "search.navigation.language",
)


@servlet("ConfigSearchPage_p")
def config_searchpage(header, post, sb):
    """Which elements the search result page renders (reference:
    ConfigSearchPage_p.java writes the same flag family)."""
    prop = ServerObjects()
    cfg = sb.config
    if post.get("set", ""):
        for key in _SEARCHPAGE_FLAGS:
            cfg.set(key, "true" if post.get_bool(key, False) else "false")
    prop.put("flags", len(_SEARCHPAGE_FLAGS))
    for i, key in enumerate(_SEARCHPAGE_FLAGS):
        prop.put(f"flags_{i}_name", key)
        prop.put(f"flags_{i}_value", 1 if cfg.get_bool(key, True) else 0)
        prop.put(f"flags_{i}_eol", 1 if i < len(_SEARCHPAGE_FLAGS) - 1 else 0)
    return prop


@servlet("ConfigRobotsTxt_p")
def config_robotstxt(header, post, sb):
    """What this NODE's own /robots.txt denies to visiting crawlers
    (reference: ConfigRobotsTxt_p.java -> RobotsTxtConfig)."""
    prop = ServerObjects()
    cfg = sb.config
    parts = ("all", "blog", "bookmarks", "network", "news", "status",
             "wiki", "dirs", "profile")
    if post.get("set", ""):
        for p in parts:
            cfg.set(f"httpd.robots.txt.{p}",
                    "true" if post.get_bool(p, False) else "false")
    prop.put("parts", len(parts))
    for i, p in enumerate(parts):
        prop.put(f"parts_{i}_name", p)
        prop.put(f"parts_{i}_value",
                 1 if cfg.get_bool(f"httpd.robots.txt.{p}", False) else 0)
        prop.put(f"parts_{i}_eol", 1 if i < len(parts) - 1 else 0)
    return prop


_ROBOTS_PART_PATHS = {
    "blog": "/Blog.html", "bookmarks": "/Bookmarks.html",
    "network": "/Network.html", "news": "/News.html",
    "status": "/Status.html", "wiki": "/Wiki.html",
    "dirs": "/htroot/", "profile": "/ViewProfile.html",
}


@servlet("robots")
def robots_txt(header, post, sb):
    """The node's own robots.txt (reference: htroot/robots.java)."""
    prop = ServerObjects()
    lines = ["User-agent: *"]
    cfg = sb.config
    if cfg.get_bool("httpd.robots.txt.all", False):
        lines.append("Disallow: /")
    else:
        for part, path in _ROBOTS_PART_PATHS.items():
            if cfg.get_bool(f"httpd.robots.txt.{part}", False):
                lines.append(f"Disallow: {path}")
    prop.raw_body = "\n".join(lines) + "\n"
    prop.raw_ctype = "text/plain; charset=utf-8"
    return prop


# -- access / connections ---------------------------------------------------


@servlet("AccessGrid_p")
def access_grid(header, post, sb):
    """Per-client access counts over the sliding window (reference:
    AccessGrid_p.java over serverAccessTracker)."""
    prop = ServerObjects()
    hosts = sb.access_tracker.access_hosts(600.0)[:200]
    prop.put("hosts", len(hosts))
    for i, (h, n) in enumerate(hosts):
        prop.put(f"hosts_{i}_host", escape_html(h))
        prop.put(f"hosts_{i}_count", n)
        prop.put(f"hosts_{i}_eol", 1 if i < len(hosts) - 1 else 0)
    prop.put("limit", sb.config.get_int("httpd.maxAccessPerHost.600s", 6000))
    return prop


@servlet("Connections_p")
def connections(header, post, sb):
    """Live server/loader activity (reference: Connections_p.java)."""
    prop = ServerObjects()
    threads = [t for t in threading.enumerate()]
    http_threads = [t for t in threads if "Thread-" in t.name
                    or "http" in t.name.lower()]
    prop.put("threadcount", len(threads))
    prop.put("httpthreads", len(http_threads))
    inflight = list(getattr(sb.loader, "_inflight", {}))[:50]
    prop.put("loading", len(inflight))
    for i, url in enumerate(inflight):
        prop.put(f"loading_{i}_url", escape_html(url))
        prop.put(f"loading_{i}_eol", 1 if i < len(inflight) - 1 else 0)
    return prop


@servlet("SearchAccessRate_p")
def search_access_rate(header, post, sb):
    """Abuse-throttle limits (reference: SearchAccessRate_p.java)."""
    prop = ServerObjects()
    cfg = sb.config
    if post.get("set", ""):
        for key in ("httpd.maxAccessPerHost.600s",):
            if post.get(key, ""):
                cfg.set(key, post.get(key))
    prop.put("maxAccessPerHost", cfg.get_int(
        "httpd.maxAccessPerHost.600s", 6000))
    prop.put("accesscalls", getattr(sb.access_tracker, "_access_calls", 0))
    return prop


# -- observability ----------------------------------------------------------


@servlet("ViewLog_p")
def view_log(header, post, sb):
    """Tail of the node log file (reference: ViewLog_p.java)."""
    prop = ServerObjects()
    n = min(post.get_int("lines", 100), 1000)
    lines: list[str] = []
    data_dir = getattr(sb, "data_dir", None)
    path = os.path.join(data_dir, "LOG", "yacy.log") if data_dir else None
    if path and os.path.exists(path):
        with open(path, "rb") as f:
            f.seek(0, 2)
            size = f.tell()
            f.seek(max(0, size - 256 * 1024))
            raw = f.read().decode("utf-8", "replace")
        lines = raw.splitlines()[-n:]
    from ...utils import logging as ylog
    prop.put("dropped", ylog.dropped_count())
    prop.put("lines", len(lines))
    for i, line in enumerate(lines):
        prop.put(f"lines_{i}_line", escape_html(line))
        prop.put(f"lines_{i}_eol", 1 if i < len(lines) - 1 else 0)
    return prop


@servlet("Threaddump_p")
def threaddump(header, post, sb):
    """Stack dump of every live thread (reference: Threaddump_p.java)."""
    prop = ServerObjects()
    frames = sys._current_frames()
    threads = sorted(threading.enumerate(), key=lambda t: t.name)
    prop.put("threads", len(threads))
    for i, t in enumerate(threads):
        p = f"threads_{i}_"
        prop.put(p + "name", escape_html(t.name))
        prop.put(p + "daemon", 1 if t.daemon else 0)
        frame = frames.get(t.ident)
        stack = "".join(traceback.format_stack(frame)) if frame else ""
        prop.put(p + "stack", escape_html(stack[-4000:]))
        prop.put(p + "eol", 1 if i < len(threads) - 1 else 0)
    return prop


@servlet("Performance_p")
def performance(header, post, sb):
    """Busy-thread overview (reference: Performance_p.java over the
    deployed BusyThreads; steer with Steering_p)."""
    prop = ServerObjects()
    names = sb.threads.names()
    prop.put("jobs", len(names))
    for i, name in enumerate(names):
        t = sb.threads.get(name)
        p = f"jobs_{i}_"
        prop.put(p + "name", escape_html(name))
        prop.put(p + "busy", getattr(t, "busy_cycles", 0))
        prop.put(p + "idle", getattr(t, "idle_cycles", 0))
        prop.put(p + "alive", 1 if t and t._thread
                 and t._thread.is_alive() else 0)
        prop.put(p + "eol", 1 if i < len(names) - 1 else 0)
    return prop


@servlet("PerformanceConcurrency_p")
def performance_concurrency(header, post, sb):
    """Indexing pipeline queue/worker metrics (reference:
    PerformanceConcurrency_p.java over WorkflowProcessor)."""
    prop = ServerObjects()
    procs = [getattr(sb, a, None) for a in
             ("_parse_proc", "_condense_proc", "_structure_proc",
              "_store_proc")]
    procs = [p for p in procs if p is not None]
    prop.put("processors", len(procs))
    for i, p in enumerate(procs):
        q = f"processors_{i}_"
        m = getattr(p, "metrics", None)
        prop.put(q + "name", escape_html(getattr(p, "name", f"stage{i}")))
        prop.put(q + "queued", p.queue_size())
        prop.put(q + "processed", getattr(m, "processed", 0) if m else 0)
        prop.put(q + "avgms", round(m.avg_exec_ms, 2) if m else 0)
        prop.put(q + "eol", 1 if i < len(procs) - 1 else 0)
    return prop


@servlet("PerformanceSearch_p")
def performance_search(header, post, sb):
    """Per-stage search timings (reference: PerformanceSearch_p.java over
    EventTracker SEARCH events)."""
    from ...utils.eventtracker import EClass, events
    prop = ServerObjects()
    evs = events(EClass.SEARCH)[-200:]
    by_stage: dict[str, list[float]] = {}
    for e in evs:
        by_stage.setdefault(e.label, []).append(e.duration_ms)
    stages = sorted(by_stage)
    prop.put("stages", len(stages))
    for i, s in enumerate(stages):
        durs = by_stage[s]
        p = f"stages_{i}_"
        prop.put(p + "name", escape_html(s))
        prop.put(p + "count", len(durs))
        prop.put(p + "avgms", round(sum(durs) / max(len(durs), 1), 2))
        prop.put(p + "maxms", round(max(durs), 2) if durs else 0)
        prop.put(p + "eol", 1 if i < len(stages) - 1 else 0)
    return prop


# -- crawl tools ------------------------------------------------------------


@servlet("CrawlCheck_p")
def crawl_check(header, post, sb):
    """Pre-crawl URL check: robots verdict + blacklist + cache state
    (reference: CrawlCheck_p.java)."""
    prop = ServerObjects()
    url = post.get("crawlingURL", post.get("url", "")).strip()
    prop.put("url", escape_html(url))
    prop.put("checked", 1 if url else 0)
    if url:
        try:
            allowed = sb.robots.is_allowed(url)
        except Exception:
            allowed = True
        prop.put("robotsallowed", 1 if allowed else 0)
        reason = sb.blacklist.crawler_reason(url)
        prop.put("blacklisted", 0 if reason is None else 1)
        prop.put("blacklistreason", escape_html(reason or ""))
        prop.put("cached", 1 if sb.htcache.has(url) else 0)
    return prop


@servlet("RemoteCrawl_p")
def remote_crawl(header, post, sb):
    """Remote-crawl participation settings (reference: RemoteCrawl_p.java)."""
    prop = ServerObjects()
    cfg = sb.config
    if post.get("set", ""):
        cfg.set("crawlResponse",
                "true" if post.get_bool("crawlResponse", False) else "false")
        if post.get("acceptCrawlLimit", ""):
            cfg.set("crawlResponse.ppm", post.get("acceptCrawlLimit"))
    prop.put("crawlResponse",
             1 if cfg.get_bool("crawlResponse", False) else 0)
    prop.put("ppm", cfg.get_int("crawlResponse.ppm", 60))
    return prop


@servlet("Autocrawl_p")
def autocrawl(header, post, sb):
    """Autocrawl configuration (reference: Autocrawl_p.java)."""
    prop = ServerObjects()
    cfg = sb.config
    if post.get("set", ""):
        cfg.set("autocrawl",
                "true" if post.get_bool("autocrawl", False) else "false")
        for key in ("autocrawl.rows", "autocrawl.days",
                    "autocrawl.deep.depth"):
            if post.get(key, ""):
                cfg.set(key, post.get(key))
    prop.put("autocrawl", 1 if cfg.get_bool("autocrawl", False) else 0)
    prop.put("rows", cfg.get_int("autocrawl.rows", 100))
    prop.put("days", cfg.get_int("autocrawl.days", 30))
    prop.put("depth", cfg.get_int("autocrawl.deep.depth", 3))
    return prop


# -- index tools ------------------------------------------------------------


@servlet("IndexSchema_p")
def index_schema(header, post, sb):
    """The live collection schema (reference: IndexSchema_p.java)."""
    from ...index.metadata import DOUBLE_FIELDS, INT_FIELDS, TEXT_FIELDS
    prop = ServerObjects()
    rows = [(f, "text") for f in TEXT_FIELDS] \
        + [(f, "int") for f in INT_FIELDS] \
        + [(f, "double") for f in DOUBLE_FIELDS]
    prop.put("fieldcount", len(rows))
    prop.put("fields", len(rows))
    for i, (name, kind) in enumerate(rows):
        prop.put(f"fields_{i}_name", name)
        prop.put(f"fields_{i}_type", kind)
        prop.put(f"fields_{i}_eol", 1 if i < len(rows) - 1 else 0)
    return prop


@servlet("IndexDeletion_p")
def index_deletion(header, post, sb):
    """Delete by URL or whole host (reference: IndexDeletion_p.java)."""
    from ...utils.hashes import url2hash
    prop = ServerObjects()
    deleted = 0
    url = post.get("urldelete", "").strip()
    host = post.get("hostdelete", "").strip().lower()
    if post.get("deleteIndex") and post.get("agree"):
        # the full wipe (reference IndexDeletion_p "delete the index"
        # with its are-you-sure gate; bin/clearindex.sh)
        meta = sb.index.metadata
        for d in range(meta.capacity()):
            if not meta.is_deleted(d) and sb.index.remove_document(
                    meta.urlhash_of(d)):
                deleted += 1
    if url:
        if sb.index.remove_document(url2hash(url)):
            deleted += 1
    if host:
        meta = sb.index.metadata
        suffix = "." + host
        docids = meta.facet_docids(
            "host_s", lambda h: h == host or h.endswith(suffix))
        for d in docids.tolist():
            if sb.index.remove_document(meta.urlhash_of(int(d))):
                deleted += 1
    prop.put("deleted", deleted)
    prop.put("doccount", sb.index.doc_count())
    return prop


@servlet("IndexImportWarc_p")
def import_warc(header, post, sb):
    """WARC dump import (reference: IndexImportWarc_p.java). The file
    must already be on the node (surrogates dir or an absolute path
    under DATA)."""
    prop = ServerObjects()
    path = post.get("file", "").strip()
    prop.put("imported", 0)
    prop.put("error", "")
    if path:
        resolved = _surrogate_path(sb, path)
        if resolved is None:
            prop.put("error", "file must live under DATA")
        else:
            try:
                from ...document.importer import WarcImporter
                imported = [0]

                def sink(doc):
                    sb.index.store_document(doc, collection="import")
                    imported[0] += 1
                WarcImporter(sink).import_file(resolved)
                prop.put("imported", imported[0])
            except Exception as e:
                prop.put("error", escape_html(str(e)))
    return prop


def _surrogate_path(sb, path: str) -> str | None:
    """Imports only read files inside the node's own DATA dir."""
    data_dir = getattr(sb, "data_dir", None)
    if not data_dir:
        return path if os.path.exists(path) else None
    resolved = os.path.realpath(os.path.join(data_dir, path))
    root = os.path.realpath(data_dir)
    return resolved if resolved.startswith(root + os.sep) else None


@servlet("IndexImportOAIPMH_p")
def import_oaipmh(header, post, sb):
    """OAI-PMH harvest trigger (reference: IndexImportOAIPMH_p.java)."""
    prop = ServerObjects()
    endpoint = post.get("urlstartone", post.get("url", "")).strip()
    prop.put("imported", 0)
    prop.put("error", "")
    if endpoint:
        try:
            from ...crawler.request import Request
            from ...document.importer.oaipmh import OAIPMHHarvester
            imported = [0]

            def sink(doc):
                sb.index.store_document(doc, collection="oaipmh")
                imported[0] += 1

            def fetcher(u):
                resp = sb.loader.load(Request(url=u))
                return resp.content if resp.status == 200 else b""
            OAIPMHHarvester(endpoint, fetcher, sink).harvest()
            prop.put("imported", imported[0])
        except Exception as e:
            prop.put("error", escape_html(str(e)))
    return prop


@servlet("IndexImportMediawiki_p")
def import_mediawiki(header, post, sb):
    """MediaWiki XML dump import (reference: IndexImportMediawiki_p.java)."""
    prop = ServerObjects()
    path = post.get("file", "").strip()
    prop.put("imported", 0)
    prop.put("error", "")
    if path:
        resolved = _surrogate_path(sb, path)
        if resolved is None:
            prop.put("error", "file must live under DATA")
        else:
            try:
                from ...document.importer import MediawikiImporter
                imported = [0]

                def sink(doc):
                    sb.index.store_document(doc, collection="import")
                    imported[0] += 1
                MediawikiImporter(sink).import_file(resolved)
                prop.put("imported", imported[0])
            except Exception as e:
                prop.put("error", escape_html(str(e)))
    return prop


# -- misc tools -------------------------------------------------------------


@servlet("Translator_p")
def translator(header, post, sb):
    """Loaded UI translation table (reference: Translator_p.java)."""
    from ..translation import load_locale
    prop = ServerObjects()
    lang = post.get("lang", sb.config.get("locale.language", "default"))
    locales = os.path.join(sb.data_dir, "LOCALES") \
        if getattr(sb, "data_dir", None) else None
    table = load_locale(locales, lang)
    entries = sorted({(src, dst)
                      for pairs in table._sections.values()
                      for src, dst in pairs})[:500]
    prop.put("lang", escape_html(lang))
    prop.put("entries", len(entries))
    for i, (src, dst) in enumerate(entries):
        prop.put(f"entries_{i}_source", escape_html(src))
        prop.put(f"entries_{i}_target", escape_html(dst))
        prop.put(f"entries_{i}_eol", 1 if i < len(entries) - 1 else 0)
    return prop


_HTCACHE_STATS: dict = {}


@servlet("ConfigHTCache_p")
def config_htcache(header, post, sb):
    """Page-cache settings + stats (reference: ConfigHTCache_p.java)."""
    prop = ServerObjects()
    cfg = sb.config
    if post.get("set", "") and post.get("maxCacheSize", ""):
        cfg.set("proxyCacheSize", post.get("maxCacheSize"))
    if post.get("clear"):
        prop.put("cleared", sb.htcache.clear())
        _HTCACHE_STATS.pop(getattr(sb.htcache, "data_dir", None), None)
    data_dir = getattr(sb.htcache, "data_dir", None)
    # the full-walk stat is expensive on big caches: cache it briefly
    cached = _HTCACHE_STATS.get(data_dir)
    if cached and time.time() - cached[0] < 30.0:
        files, size = cached[1], cached[2]
    else:
        files = size = 0
        if data_dir and os.path.isdir(data_dir):
            for root, _dirs, names in os.walk(data_dir):
                for n in names:
                    files += 1
                    try:
                        size += os.path.getsize(os.path.join(root, n))
                    except OSError:
                        pass
        _HTCACHE_STATS[data_dir] = (time.time(), files, size)
    prop.put("entries", files)
    prop.put("sizemb", round(size / (1 << 20), 2))
    prop.put("maxsize", cfg.get_int("proxyCacheSize", 4096))
    return prop


@servlet("RegexTest")
def regex_test(header, post, sb):
    """must-match/must-not-match pattern tester (reference: RegexTest.java).

    Admin-gated by default (security.DEFAULT_ADMIN_PATHS — CPython's
    backtracking engine has no timeout); input caps stay as defense in
    depth for operators who re-open the mount."""
    prop = ServerObjects()
    text = post.get("text", "")[:4096]
    pattern = post.get("regex", "")[:1024]
    prop.put("text", escape_html(text))
    prop.put("regex", escape_html(pattern))
    matched = error = ""
    if pattern:
        try:
            matched = "1" if re.fullmatch(pattern, text) else "0"
        except re.error as e:
            error = str(e)
    prop.put("matches", matched)
    prop.put("error", escape_html(error))
    return prop


@servlet("BlacklistTest_p")
def blacklist_test(header, post, sb):
    """Test one URL against the active blacklists (reference:
    BlacklistTest_p.java)."""
    prop = ServerObjects()
    url = post.get("testurl", post.get("url", "")).strip()
    prop.put("url", escape_html(url))
    prop.put("tested", 1 if url else 0)
    if url:
        reason = sb.blacklist.crawler_reason(url)
        prop.put("listed", 0 if reason is None else 1)
        prop.put("reason", escape_html(reason or ""))
        types = [t for t in ("crawler", "dht", "search", "surftips",
                             "news", "proxy")
                 if sb.blacklist.is_listed(t, url)]
        prop.put("types", escape_html(",".join(types)))
    return prop


@servlet("Help")
def help_page(header, post, sb):
    prop = ServerObjects()
    prop.put("version", escape_html(
        sb.config.get("version", "")))
    return prop


@servlet("yacyinteractive")
def yacy_interactive(header, post, sb):
    """The JS live-search page (reference: yacyinteractive.java — the
    template drives /suggest + /yacysearch.json from the browser)."""
    prop = ServerObjects()
    prop.put("promoteSearchPageGreeting", escape_html(
        sb.config.get("promoteSearchPageGreeting",
                      "YaCy TPU P2P Web Search")))
    prop.put("former", escape_html(post.get("query", "")))
    return prop


@servlet("DeviceStore_p")
def device_store(header, post, sb):
    """The serving-store dashboard: arena occupancy, prune/batch/join
    coverage, mesh layout (observability for the device path — the
    reference's PerformanceMemory table-tracker idea applied to the
    TPU arena). Counts and bytes only: the per-kernel cost-model table
    is Performance_Roofline_p, dispatch and kernel walls are the
    `devstore.batch` / `kernel.*` stages of Performance_Trace_p."""
    prop = ServerObjects()
    ds = sb.index.devstore
    if ds is None:
        prop.put("enabled", 0)
        prop.put("kind", "none")
        prop.put("rows", 0)
        return prop
    prop.put("enabled", 1)
    kind = type(ds).__name__
    prop.put("kind", kind)
    rows: list[tuple[str, object]] = [
        ("queries_served", getattr(ds, "queries_served", 0)),
        ("fallbacks", getattr(ds, "fallbacks", 0)),
        ("join_served", getattr(ds, "join_served", 0)),
        ("join_fallbacks", getattr(ds, "join_fallbacks", 0)),
    ]
    if kind == "DeviceSegmentStore":
        c = ds.counters()
        rows += [
            ("join_sm_served", c["join_sm_served"]),
            ("join_partners", c["join_partners"]),
            ("join_multi_served", c["join_multi_served"]),
            ("join_onegather_served", c["join_onegather_served"]),
            ("join_compacted", c["join_compacted"]),
            ("join_survivors", c["join_survivors"]),
            ("join_compact_rows", c["join_compact_rows"]),
            ("join_compact_overflow", c["join_compact_overflow"]),
            ("join_shapes", c["join_shapes"]),
            ("join_bitmap_slots", c["join_bitmap_slots"]),
            ("join_bitmap_refused", c["join_bitmap_refused"]),
            ("arena_rows_used", ds.arena.used_rows),
            ("arena_rows_capacity", ds.arena.capacity_rows),
            ("arena_rows_packed", c["arena_rows_packed"]),
            ("arena_rows_skipped", c["arena_rows_skipped"]),
            ("arena_bytes", ds.arena.bytes_used()),
            ("live_rows", ds.live_rows()),
            ("prune_rounds", ds.prune_rounds),
            ("pruned_tiles", ds.pruned_tiles),
            ("batching", 1 if ds._batcher is not None else 0),
            # versioned top-k result cache + round-trip accounting
            ("rank_cache_hits", c["rank_cache_hits"]),
            ("rank_cache_stale", c["rank_cache_stale"]),
            ("arena_epoch", c["arena_epoch"]),
            ("device_round_trips", c["device_round_trips"]),
            ("rt_per_query",
             round(c["device_round_trips"]
                   / max(c["queries_served"], 1), 3)),
            # compressed residency + tier ladder (ISSUE 8): per-tier
            # occupancy, hit attribution and the promotion flow
            ("packed_residency", 1 if ds.packed_residency else 0),
            ("compression_ratio", c["packed_compression_ratio"]),
            ("tier_hot_bytes", c["tier_hot_bytes"]),
            ("tier_warm_bytes", c["tier_warm_bytes"]),
            ("tier_cold_bytes", c["tier_cold_bytes"]),
            ("tier_hits_hot_warm_cold",
             f"{c['tier_hot_hits']}/{c['tier_warm_hits']}"
             f"/{c['tier_cold_hits']}"),
            ("tier_promotions_warm_hot", c["tier_promotions_warm_hot"]),
            ("tier_promotions_cold_hot", c["tier_promotions_cold_hot"]),
            ("tier_demotions_hot_warm", c["tier_demotions_hot_warm"]),
            ("term_cache_hits", c["term_cache_hits"]),
            ("term_cache_evictions", c["term_cache_evictions"]),
            # dense-first ANN (ISSUE 11): candidate-generation coverage
            # + the vector side of the residency ledger — with
            # dense_fwd_bytes, every resident byte is on this dashboard
            ("ann_vectors", c["ann_vectors"]),
            ("ann_clusters", c["ann_clusters"]),
            ("ann_queries", c["ann_queries"]),
            ("ann_dispatches", c["ann_dispatches"]),
            ("ann_host_queries", c["ann_host_queries"]),
            ("ann_bytes_hot_warm_cold",
             f"{c['ann_hot_bytes']}/{c['ann_warm_bytes']}"
             f"/{c['ann_cold_bytes']}"),
            ("ann_hits_hot_warm_cold",
             f"{c['ann_tier_hot_hits']}/{c['ann_tier_warm_hits']}"
             f"/{c['ann_tier_cold_hits']}"),
            ("ann_promotions", c["ann_promotions"]),
            ("dense_fwd_bytes", c["dense_fwd_bytes"]),
        ]
    elif kind == "MeshSegmentStore":
        from ...utils import histogram
        c = ds.counters()
        rows += [
            ("mesh_term_axis", ds.n_term),
            ("mesh_doc_axis", ds.n_doc),
            ("mesh_cells", ds.n_cells),
            ("live_rows", ds.live_rows()),
            ("cell_rows_max", max((cb.used for cb in ds._cells),
                                  default=0)),
            ("rank_cache_hits", c["rank_cache_hits"]),
            ("rank_cache_stale", c["rank_cache_stale"]),
            ("rank_cache_stale_served", c["rank_cache_stale_served"]),
            ("arena_epoch", c["arena_epoch"]),
            ("device_round_trips", c["device_round_trips"]),
        ]
        # dispatches per solo SPMD program (the families' walls are on
        # Performance_Trace_p and /metrics)
        for fam in ("kernel._mesh_join_shard", "kernel._mesh_xjoin_shard",
                    "kernel._mesh_pruned_shard", "kernel._mesh_rank_shard"):
            h = histogram.get(fam)
            rows.append((fam, h.count if h is not None else 0))
    prop.put("rows", len(rows))
    for i, (name, v) in enumerate(rows):
        prop.put(f"rows_{i}_key", name)
        prop.put(f"rows_{i}_value", v)
    return prop
