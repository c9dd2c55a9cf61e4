"""Search servlets — HTML/JSON/OpenSearch-RSS search surface + GSA XML.

Capability equivalent of the reference's search UI/API servlets
(reference: htroot/yacysearch.java:1059 — query parsing, event lookup,
result paging, template fill; htroot/yacysearch.json + yacysearch.rss
templates for the machine formats;
source/net/yacy/http/servlets/GSAsearchServlet.java for the
Google-Search-Appliance-compatible XML).  One `respond` backs all output
formats — the template chosen by extension renders the same property set.
"""

from __future__ import annotations

import time
from urllib.parse import quote

from ...utils import tracing
from ..objects import (ServerObjects, escape_html, escape_json, escape_xml)
from . import servlet


def _fill_items(prop: ServerObjects, results, esc) -> None:
    # every value is a str when it is written: the map takes them as
    # they are, in one update (ServerObjects.put_strings)
    last = len(results) - 1
    m = {"items": str(len(results))}
    for i, r in enumerate(results):
        p = f"items_{i}_"
        m[p + "title"] = esc(r.title or r.url)
        m[p + "link"] = esc(r.url)
        m[p + "description"] = esc(r.snippet)
        m[p + "urlhash"] = r.urlhash.decode("ascii", "replace")
        m[p + "host"] = esc(r.host)
        m[p + "size"] = str(r.size)
        m[p + "sizename"] = _sizename(r.size)
        m[p + "ranking"] = str(int(r.score))
        m[p + "source"] = esc(str(r.source))
        m[p + "filetype"] = esc(r.filetype)
        m[p + "eol"] = "1" if i < last else "0"
    prop.put_strings(m)


def _fill_image_items(prop: ServerObjects, images, esc) -> None:
    """Image-mode item properties (own result shape: the image URL plus
    source-page attribution — reference yacysearchitem.java image
    branch)."""
    last = len(images) - 1
    m = {"items": str(len(images))}
    for i, im in enumerate(images):
        p = f"items_{i}_"
        m[p + "image"] = esc(im.image_url)
        m[p + "alt"] = esc(im.alt)
        m[p + "title"] = esc(im.alt or im.source_title)
        m[p + "link"] = esc(im.image_url)
        m[p + "description"] = esc(im.alt)
        m[p + "sourcelink"] = esc(im.source_url)
        m[p + "sourcetitle"] = esc(im.source_title)
        m[p + "urlhash"] = im.source_urlhash.decode("ascii", "replace")
        m[p + "host"] = esc(im.host)
        m[p + "size"] = "0"
        m[p + "sizename"] = ""
        m[p + "ranking"] = str(int(im.score))
        m[p + "source"] = esc(str(im.source))
        m[p + "filetype"] = esc(im.filetype)
        m[p + "eol"] = "1" if i < last else "0"
    prop.put_strings(m)


def _sizename(n: int) -> str:
    for unit in ("bytes", "kB", "MB", "GB"):
        if n < 1024:
            return f"{n} {unit}"
        n //= 1024
    return f"{n} TB"


def _mod_value(prefix: str, v: str) -> str:
    """modifier:value, parenthesized when the value has whitespace (the
    parser's `prefix:(multi word)` form, query.py _strip_prefix_op)."""
    return f"{prefix}:({v})" if " " in v else f"{prefix}:{v}"


# facet dimension -> query modifier producing the refinement
# (yacysearchtrailer semantics: facet clicks append a modifier)
_FACET_MODIFIER = {
    "hosts": lambda v: _mod_value("site", v),
    "filetype": lambda v: _mod_value("filetype", v),
    "authors": lambda v: _mod_value("author", v),
    "language": lambda v: f"/language/{v}",
    "year": lambda v: f"daterange:{v}0101..{v}1231",
    "collections": lambda v: _mod_value("keyword", v),
}


def _fill_navigation(prop: ServerObjects, event, esc,
                     base_query: str = "", url_suffix: str = "") -> None:
    navs = [(name, nav.top(10)) for name, nav in event.navigators.items()
            if len(nav) > 0]
    m = {"navigation": str(len(navs))}
    for i, (name, entries) in enumerate(navs):
        p = f"navigation_{i}_"
        m[p + "facetname"] = esc(name)
        m[p + "elements"] = str(len(entries))
        mod = _FACET_MODIFIER.get(name)
        last = len(entries) - 1
        for j, (value, count) in enumerate(entries):
            q = f"{p}elements_{j}_"
            m[q + "name"] = esc(str(value))
            m[q + "count"] = str(count)
            refined = (f"{base_query} {mod(value)}".strip()
                       if mod and base_query else base_query)
            m[q + "url"] = ("yacysearch.html?query=" + quote(refined)
                            + url_suffix)
            m[q + "eol"] = "1" if j < last else "0"
        m[p + "eol"] = "1" if i < len(navs) - 1 else "0"
    prop.put_strings(m)


def _esc_for(ext: str):
    return {"json": escape_json, "rss": escape_xml, "xml": escape_xml,
            }.get(ext, escape_html)


def _remote_fanout(sb, event, count: int) -> None:
    """Scatter to the P2P network when this switchboard belongs to a
    node (P2PNode publishes itself as sb.node) — the reference's
    resource=global search (yacysearch.java local/global resource
    param). Fired once per event: paging over the cached event must not
    re-ask the network. Delegates to P2PNode.scatter so cluster mode
    and the secondary abstract-join round behave exactly like
    node.search."""
    node = getattr(sb, "node", None)
    if node is None or event.remote_peers_asked:
        return
    with tracing.span("peers.fanout"):
        node.scatter(event, count)


@servlet("yacysearch")
def respond(header: dict, post: ServerObjects, sb) -> ServerObjects:
    with tracing.trace("servlet.yacysearch", ext=header.get("ext", "")):
        return _respond_search(header, post, sb)


def _respond_search(header: dict, post: ServerObjects, sb) -> ServerObjects:
    prop = ServerObjects()
    query = post.get("query", post.get("search", "")).strip()
    count = min(max(post.get_int("maximumRecords", post.get_int("count", 10)), 1), 100)
    offset = max(post.get_int("startRecord", post.get_int("offset", 0)), 0)
    ext = header.get("ext", "html")
    esc = _esc_for(ext)

    prop.put("promoteSearchPageGreeting",
             esc(sb.config.get("promoteSearchPageGreeting",
                               "YaCy TPU P2P Web Search")))
    prop.put("former", esc(query))
    prop.put("count", count)
    prop.put("offset", offset)
    prop.put("searchtime", 0)
    if not query:
        prop.put("items", 0)
        prop.put("found", 0)
        prop.put("navigation", 0)
        prop.put("totalcount", 0)
        return prop

    t0 = time.time()
    contentdom = post.get("contentdom", "").lower()
    image_mode = contentdom == "image"
    hybrid = post.get_bool("hybrid", False)
    dense_first = post.get_bool("densefirst", False)
    event = sb.search(query, count=count, offset=offset,
                      hybrid=hybrid, contentdom=contentdom,
                      use_cache=not post.get_bool("nocache", False),
                      dense_first=dense_first)
    if post.get("resource", "") == "global":
        _remote_fanout(sb, event, count)
    if image_mode:
        # image serving mode: ranked pages expand into per-image entries
        # (reference SearchEvent.java:2178-2280 + the yacysearchitem
        # image branch); own item shape with source-page attribution.
        # One extra entry makes the hasnext check exact.
        images = event.image_results(offset=offset, count=count + 1)
        image_more = len(images) > count
        images = images[:count]
        results = []
        prop.put("searchtime", int((time.time() - t0) * 1000))
        prop.put("totalcount",
                 event.local_rwi_considered + event.remote_results)
        prop.put("found", 1 if images else 0)
        _fill_image_items(prop, images, esc)
    else:
        results = event.results(offset=offset, count=count)
        prop.put("searchtime", int((time.time() - t0) * 1000))
        prop.put("totalcount",
                 event.local_rwi_considered + event.remote_results)
        prop.put("found", 1 if results else 0)
        _fill_items(prop, results, esc)
    prop.put("contentdom_image", 1 if image_mode else 0)
    # the ranking mode must survive a tab switch, and the page size and
    # the content domain with it must survive navigation, or page 2
    # would re-rank differently and repeat/skip results (dense-first
    # sheds a rung of its own: it rides like the hybrid flag)
    mode = ("&hybrid=true" if hybrid else "") \
        + ("&densefirst=true" if dense_first else "")
    suffix = f"&maximumRecords={count}{mode}"
    if contentdom:
        suffix += f"&contentdom={quote(contentdom)}"
    _fill_navigation(prop, event, esc, base_query=query, url_suffix=suffix)
    # pagination (yacysearch paging over the cached event) and the
    # content-domain tabs (the reference's Text/Images/... search tabs)
    here = "yacysearch.html?query=" + quote(query)
    tabs = f"{here}&maximumRecords={count}{mode}"
    active = contentdom or "text"
    m = {}
    for name in ("text", "image", "audio", "video", "app"):
        m[f"tab_{name}_url"] = \
            tabs + (f"&contentdom={name}" if name != "text" else "")
        m[f"tab_{name}_active"] = "1" if active == name else "0"
    prop.put_strings(m)
    prop.put("hasprev", 1 if offset > 0 else 0)
    prop.put("prevurl",
             f"{here}&startRecord={max(0, offset - count)}{suffix}")
    got_n = len(images) if image_mode else len(results)
    if image_mode:
        more = image_more
    else:
        # snippet-evicted heap slots never render: count live ones only
        more = event.results_available() > offset + got_n
    prop.put("hasnext", 1 if (more and got_n) else 0)
    prop.put("nexturl", f"{here}&startRecord={offset + count}{suffix}")
    # progressive delivery handle: the page's script can pull items
    # one-by-one from /yacysearchitem.html?eventID=...&item=N while
    # remote feeders are still filling the event
    prop.put("eventID", esc(event.event_id))
    # the request's trace id: paste into Performance_Trace_p?trace=...
    # to see this exact search's waterfall
    prop.put("traceID", esc(tracing.current_trace_id() or ""))
    return prop


@servlet("yacysearchitem")
def respond_item(header: dict, post: ServerObjects, sb) -> ServerObjects:
    """ONE result item of a cached search event, as a standalone
    fragment — progressive per-item result delivery (reference:
    htroot/yacysearchitem.java reading SearchEventCache while feeders
    run, SearchEvent.java:534-543). `item` indexes into the event's
    ranked results; remote results that arrived since the page rendered
    become visible here without re-running the query."""
    with tracing.trace("servlet.yacysearchitem"):
        return _respond_item(header, post, sb)


def _respond_item(header: dict, post: ServerObjects, sb) -> ServerObjects:
    prop = ServerObjects()
    eid = post.get("eventID", "")
    item = max(post.get_int("item", 0), 0)
    ext = header.get("ext", "html")
    esc = _esc_for(ext)
    prop.put("found", 0)
    prop.put("eventID", esc(eid))
    prop.put("item", item)
    ev = sb.search_cache.event_by_id(eid) if eid else None
    if ev is None:
        return prop
    rs = ev.results(offset=item, count=1)
    prop.put("total", ev.results_available())
    if not rs:
        return prop
    r = rs[0]
    prop.put("found", 1)
    prop.put("link", esc(r.url))
    prop.put("title", esc(r.title or r.url))
    prop.put("description", esc(r.snippet or ""))
    prop.put("host", esc(r.host or ""))
    prop.put("score", r.score)
    return prop


@servlet("gsasearch")
def respond_gsa(header: dict, post: ServerObjects, sb) -> ServerObjects:
    """GSA-compatible parameter mapping: q, num, start → the same search
    (reference: GSAsearchServlet.java maps the GSA request onto an
    internal search and emits <GSP> XML)."""
    with tracing.trace("servlet.gsasearch"):
        return _respond_gsa(header, post, sb)


def _respond_gsa(header: dict, post: ServerObjects, sb) -> ServerObjects:
    prop = ServerObjects()
    query = post.get("q", "").strip()
    count = min(max(post.get_int("num", 10), 1), 100)
    offset = max(post.get_int("start", 0), 0)
    prop.put("q", escape_xml(query))
    prop.put("count", count)
    prop.put("offset", offset)
    if not query:
        prop.put("items", 0)
        prop.put("totalcount", 0)
        return prop
    t0 = time.time()
    event = sb.search(query, count=count, offset=offset)
    results = event.results(offset=offset, count=count)
    prop.put("searchtime", f"{time.time() - t0:.6f}")
    prop.put("totalcount", event.local_rwi_considered + event.remote_results)
    prop.put("items", len(results))
    for i, r in enumerate(results):
        p = f"items_{i}_"
        prop.put(p + "rank", offset + i + 1)
        prop.put(p + "link", escape_xml(r.url))
        prop.put(p + "title", escape_xml(r.title or r.url))
        prop.put(p + "description", escape_xml(r.snippet))
        prop.put(p + "size", r.size)
    return prop


@servlet("suggest")
def respond_suggest(header: dict, post: ServerObjects, sb) -> ServerObjects:
    """Word-completion suggestions against the indexed vocabulary
    (reference: htroot/suggest.java backed by data/DidYouMean.java)."""
    from ...search.didyoumean import DidYouMean
    prop = ServerObjects()
    q = post.get("query", post.get("q", "")).strip()
    prop.put("query", escape_json(q))
    sugg = DidYouMean(sb.index).suggest(q, count=10) if q else []
    prop.put("suggestions", len(sugg))
    for i, s in enumerate(sugg):
        prop.put(f"suggestions_{i}_word", escape_json(s))
        prop.put(f"suggestions_{i}_eol", 1 if i < len(sugg) - 1 else 0)
    return prop
