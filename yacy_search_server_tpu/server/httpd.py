"""Threaded HTTP server with servlet dispatch, templates, auth, and the
P2P wire endpoints.

Capability equivalent of the reference's Jetty embedding (reference:
source/net/yacy/http/Jetty9HttpServerImpl.java:112-233 handler chain;
source/net/yacy/http/servlets/YaCyDefaultServlet.java — static files +
template dispatch; source/net/yacy/http/Jetty9YaCySecurityHandler.java —
admin auth with localhost auto-admin).  Dispatch rules:

- ``/yacy/<endpoint>.html``  → the node's PeerServer RPC handler (the
  htroot/yacy/* wire servlets), JSON body in/out (our DCN wire format)
- ``/<Name>.<ext>``          → registered servlet ``Name``; the response
  property map fills template ``<Name>.<ext>`` from the htroot template
  roots; a missing template for ``.json`` serializes the map directly
- anything else             → static file from the template roots
- names ending ``_p``       → admin-only (localhost auto-admin or
  HTTP Basic against config ``adminAccountName``/``adminAccountPassword``)
"""

from __future__ import annotations

import base64
import json
import math
import os
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qsl, unquote, urlsplit

from ..utils import faultinject, tracing
from .objects import ServerObjects
from .templates import TemplateEngine
from . import servlets

# the servlets the degradation ladder's shed rung refuses with a
# computed Retry-After: the query-serving surface — the load the ladder
# exists to defend.  The live rung is read from the actuator engine
# (act.effective_level(); the serving.degradeLevel config key is its
# write-only operator-visible mirror).  Observability and admin pages
# stay reachable: an operator must be able to SEE a shedding node
# (utils/actuator.py).
SHED_SERVLETS = frozenset({"yacysearch", "gsasearch", "yacysearchitem",
                           "suggest"})

_CONTENT_TYPES = {
    "html": "text/html; charset=utf-8",
    "json": "application/json; charset=utf-8",
    "rss": "application/rss+xml; charset=utf-8",
    "xml": "text/xml; charset=utf-8",
    "csv": "text/plain; charset=utf-8",
    "css": "text/css",
    "js": "application/javascript",
    "png": "image/png",
    "ico": "image/x-icon",
    "txt": "text/plain; charset=utf-8",
}

DEFAULT_HTROOT = os.path.join(os.path.dirname(__file__), "htroot")


class YaCyHttpServer:
    """One node's HTTP face: UI/API servlets + P2P wire endpoints."""

    def __init__(self, sb, port: int = 8090, host: str = "127.0.0.1",
                 peer_server=None, htroot_dirs: list[str] | None = None,
                 https_port: int | None = None,
                 certfile: str | None = None, keyfile: str | None = None,
                 reuse_port: bool = False):
        self.sb = sb
        self.peer_server = peer_server
        roots = list(htroot_dirs or [])
        roots.append(DEFAULT_HTROOT)
        self.templates = TemplateEngine(roots)
        from .security import SecurityHandler
        self.security = SecurityHandler(sb.config)
        outer = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            # one buffered write per response + TCP_NODELAY: the default
            # unbuffered handler emits each header line as its own tiny
            # segment, and Nagle x delayed-ACK stalls every keep-alive
            # response ~40 ms — which silently capped the whole served
            # path (a request costs ~6 ms of actual work)
            wbufsize = 64 * 1024
            disable_nagle_algorithm = True

            def log_message(self, fmt, *args):  # quiet
                pass

            def do_GET(self):
                self._javawire = False
                outer._handle(self, {})

            def do_POST(self):
                # reset per REQUEST: one handler serves a whole
                # keep-alive connection
                self._javawire = False
                length = int(self.headers.get("content-length", 0) or 0)
                body = self.rfile.read(length) if length else b""
                ctype = self.headers.get("content-type", "")
                if "application/json" in ctype:
                    try:
                        post = json.loads(body.decode("utf-8"))
                    except ValueError:
                        post = {}
                elif "multipart/form-data" in ctype:
                    # the Java wire posts multipart key=value parts
                    # (reference Protocol.java basicRequestParts). The
                    # marker is OUT-OF-BAND (handler attribute): an
                    # in-band param could be forged via query string
                    from ..peers.javawire import multipart_decode
                    post = multipart_decode(body, ctype)
                    self._javawire = True
                else:
                    post = dict(parse_qsl(body.decode("utf-8", "replace"),
                                          keep_blank_values=True))
                outer._handle(self, post)

        if reuse_port:
            # multi-process serving: N worker processes bind the same
            # port and the kernel load-balances accepts across them
            # (server/rankservice.py)
            import socket as _socket

            class _ReusePortServer(ThreadingHTTPServer):
                def server_bind(self):
                    self.socket.setsockopt(_socket.SOL_SOCKET,
                                           _socket.SO_REUSEPORT, 1)
                    ThreadingHTTPServer.server_bind(self)
            server_cls = _ReusePortServer
        else:
            server_cls = ThreadingHTTPServer
        self.httpd = server_cls((host, port), Handler)
        self.port = self.httpd.server_address[1]
        self.host = host
        self._thread: threading.Thread | None = None

        # HTTPS listener (reference: Jetty9HttpServerImpl.java:112-233
        # mounts an SSL connector beside the plain one when server.https
        # is on). Cert/key paths come from arguments or config; both
        # listeners share the one Handler/dispatch.
        self.httpsd = None
        self.https_port = None
        self.https_error: str | None = None
        self._https_thread: threading.Thread | None = None
        cfg = sb.config
        from_config = https_port is None
        if https_port is None and cfg.get_bool("server.https", False):
            https_port = cfg.get_int("port.ssl", 8443)
        if https_port is not None:
            import ssl
            certfile = certfile or cfg.get("ssl.certPath", "")
            keyfile = keyfile or cfg.get("ssl.keyPath", "") or None
            try:
                ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
                ctx.load_cert_chain(certfile, keyfile)
                self.httpsd = ThreadingHTTPServer((host, https_port),
                                                  Handler)
                self.httpsd.socket = ctx.wrap_socket(self.httpsd.socket,
                                                     server_side=True)
                self.https_port = self.httpsd.server_address[1]
            except Exception as e:
                # a misconfigured cert must not kill the plain-HTTP node
                # (the reference's Jetty setup degrades to HTTP-only too);
                # an explicit https_port argument is a programming contract
                # and still raises
                if not from_config:
                    self.httpd.server_close()
                    raise
                self.https_error = f"https disabled: {e}"
                self.httpsd = None

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "YaCyHttpServer":
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, name="httpd", daemon=True)
        self._thread.start()
        if self.httpsd is not None:
            self._https_thread = threading.Thread(
                target=self.httpsd.serve_forever, name="httpsd", daemon=True)
            self._https_thread.start()
        # recorded-API replay goes through our own HTTP surface (the
        # reference's WorkTables.execAPICall self-call), so the recorded
        # URL stays the replayable action across restarts
        if getattr(self.sb, "api_executor", None) is None:
            def _exec(path: str) -> bool:
                import urllib.request
                url = self.base_url + (path if path.startswith("/")
                                       else "/" + path)
                try:
                    with urllib.request.urlopen(url, timeout=60) as r:
                        return r.status == 200
                except Exception:
                    return False
            self.sb.api_executor = _exec
        return self

    def close(self) -> None:
        # shutdown() blocks on the serve_forever loop acknowledging — it
        # must only run when that loop was actually started
        if self._thread:
            self.httpd.shutdown()
        self.httpd.server_close()
        if self._thread:
            self._thread.join(timeout=5)
        if self.httpsd is not None:
            if self._https_thread:
                self.httpsd.shutdown()
            self.httpsd.server_close()
            if self._https_thread:
                self._https_thread.join(timeout=5)

    @property
    def base_url(self) -> str:
        return f"http://{self.host}:{self.port}"

    @property
    def https_url(self) -> str | None:
        return (f"https://{self.host}:{self.https_port}"
                if self.https_port else None)

    # -- auth ----------------------------------------------------------------

    def _is_admin(self, handler) -> bool:
        """Basic/digest/localhost admin check (server/security.py)."""
        return self.security.is_admin(
            handler.client_address[0], handler.headers,
            method=handler.command, uri=urlsplit(handler.path).path)

    def _send_401(self, handler) -> None:
        handler.send_response(401)
        body = b"admin authorization required"
        handler.send_header("Content-Type", "text/plain")
        handler.send_header("Content-Length", str(len(body)))
        # both schemes offered: one WWW-Authenticate header per scheme
        for challenge in self.security.challenges():
            handler.send_header("WWW-Authenticate", challenge)
        handler.end_headers()
        handler.wfile.write(body)

    # -- dispatch ------------------------------------------------------------

    def _handle(self, handler, post_params: dict) -> None:
        try:
            # client allowlist + abuse throttle run before EVERY branch —
            # including the proxy and *.yacy rewrites below, which fetch
            # attacker-supplied URLs and must never be reachable by a
            # client the allowlist rejects (serverAccessTracker +
            # serverClient parity; the reference's Jetty chain puts the
            # monitor/security handlers ahead of the proxy handler)
            tracker = getattr(self.sb, "access_tracker", None)
            act = getattr(self.sb, "actuators", None)
            client_ip = handler.client_address[0]
            # per-client identity behind a LOCAL front (ISSUE 19): when
            # the direct peer is loopback — a reverse proxy on the node,
            # or the game-day workload generator — X-Forwarded-For
            # names the real client for the access tracker and the
            # admission token buckets, which also makes that identity
            # subject to 429 (loopback itself stays exempt).  Never
            # honored from a non-loopback peer, and only the LAST
            # comma-separated entry counts: proxies APPEND the peer
            # they saw, so the last entry is the one written by the
            # trusted proxy on this node, while earlier entries arrive
            # attacker-supplied and would let a remote client spoof an
            # allowlisted identity or launder past the rate limits.
            if client_ip in ("127.0.0.1", "::1"):
                fwd = handler.headers.get(
                    "X-Forwarded-For", "").split(",")[-1].strip()
                if fwd:
                    client_ip = fwd
            if not self.security.client_allowed(client_ip):
                self._send(handler, 403, "text/plain",
                           b"client not allowed")
                return
            if tracker is not None:
                hits = tracker.track_access(client_ip)
                limit = self.sb.config.get_int(
                    "httpd.maxAccessPerHost.600s", 6000)
                # admission control (ISSUE 9): the per-client token
                # bucket decides alongside the windowed host count, and
                # the hard-coded Retry-After 600 becomes the honest
                # wait of WHICHEVER policy denied — the window's own
                # drain time (when the oldest over-limit hit ages out)
                # or the bucket's refill ETA; both tripping takes the
                # longer wait
                # (loopback is counted and never denied: its wait is
                # not asked for, which would take the window's lock a
                # second time for a number nobody reads)
                exempt = client_ip in ("127.0.0.1", "::1")
                over, retry_s = hits > limit, 0.0
                if over and not exempt:
                    retry_s = max(1.0, tracker.retry_after_s(
                        client_ip, limit))
                if act is not None:
                    admitted, bucket_retry = act.admit(client_ip)
                    if not admitted:
                        over = True
                        retry_s = max(retry_s, bucket_retry)
                if over and not exempt:
                    # ceil, never truncate: a client honoring the
                    # header exactly must be admitted on its retry
                    self._send(handler, 429, "text/plain",
                               b"too many requests",
                               extra={"Retry-After":
                                      str(max(1, math.ceil(retry_s)))})
                    return

            # forward-proxy request line (GET http://host/path) — the
            # transparent indexing proxy (reference:
            # server/http/HTTPDProxyHandler.java, config proxyURL /
            # proxyIndexing)
            if handler.path.startswith(("http://", "https://")):
                self._handle_forward_proxy(handler, handler.path)
                return
            # *.yacy virtual domains resolve to peers by name (reference:
            # the Jetty domain-rewrite handler + HTTPDProxyHandler)
            host_header = handler.headers.get("Host", "").split(":")[0]
            if host_header.endswith(".yacy"):
                self._handle_yacy_domain(handler, host_header, handler.path)
                return

            parts = urlsplit(handler.path)
            path = unquote(parts.path)
            params = dict(parse_qsl(parts.query, keep_blank_values=True))
            params.update(post_params)

            if path.startswith("/yacy/"):
                self._handle_wire(handler, path, params)
                return

            if path in ("", "/"):
                path = "/index.html"
            name, _, ext = path.lstrip("/").rpartition(".")
            if not name:
                name, ext = ext, "html"

            # per-path protection applies to servlets AND static files
            # (an admin template source must not leak via static serving)
            if self.security.admin_required(name, path) \
                    and not self._is_admin(handler):
                self._send_401(handler)
                return
            fn = servlets.lookup(name)
            if fn is None:
                self._serve_static(handler, path.lstrip("/"))
                return

            # degradation ladder (ISSUE 9): the shed rung refuses the
            # query-serving servlets outright with the recovery-derived
            # Retry-After; lower rungs thread the level through to the
            # search path and stamp every downgraded answer
            lvl = act.effective_level() if act is not None else 0
            if lvl >= 4 and name in SHED_SERVLETS:
                act.note_shed()
                self._send(handler, 429, "text/plain",
                           b"shedding load: serving degraded",
                           extra={"Retry-After": str(max(1, math.ceil(
                               act.shed_retry_after_s()))),
                               "X-YaCy-Degraded": str(lvl)})
                return

            post = ServerObjects(params)
            header = {"ext": ext, "path": path,
                      "client_ip": handler.client_address[0],
                      "method": handler.command,
                      # the ladder rung this request serves under
                      # (searchevent reads it off QueryParams; servlets
                      # may inspect it here)
                      "degrade": lvl,
                      # servlets mounted both public and _p can tighten
                      # behavior for non-admin callers (getpageinfo SSRF
                      # classes, RegexTest limits)
                      "admin": self._is_admin(handler),
                      # content negotiation (the /metrics endpoint
                      # upgrades to OpenMetrics + exemplars on it)
                      "accept": handler.headers.get("Accept", ""),
                      "host": handler.headers.get(
                          "Host", f"{self.host}:{self.port}")}
            # servlet serving wall -> windowed histogram (ISSUE 4): the
            # full dispatch+render wall of EVERY servlet — including
            # ones that raise into the 500 handler below (the envelope
            # records on the way out: a wedged endpoint must not vanish
            # from the very SLO histogram that would page on it), with
            # the thread's CPU time of the same interval beside it
            # (`servlet.cpu`).  When the servlet rooted a trace, the
            # wall joins it and its id becomes the histogram exemplar,
            # so a slow bucket on /metrics links to the waterfall
            # lint: tail-ok(servlet.cpu is the CPU share of the
            # servlet.serving wall beside it, which the classifier
            # reaches: a breakdown of that wall, not a wall of its own)
            with tracing.envelope("servlet.serving", "servlet.cpu") as sv:
                # env-gated failpoint INSIDE the measured wall: injected
                # latency lands in the very SLO histogram the burn-rate
                # rules read, so ladder tests drive real burns
                faultinject.sleep("servlet.serving")
                prop = fn(header, post, self.sb)
                if isinstance(prop.raw_body, bytes):  # binary (PNG etc.)
                    body = prop.raw_body
                    ctype = prop.raw_ctype or "application/octet-stream"
                else:
                    # lint: tail-ok(a child span of servlet.serving,
                    # which the classifier reaches)
                    with tracing.timed("servlet.render", sv.ctx) as rs:
                        body = self._render(name, ext, prop,
                                            rs).encode("utf-8")
                    ctype = prop.raw_ctype or _CONTENT_TYPES.get(
                        ext, "text/html; charset=utf-8")
            # any downgraded answer is stamped (ISSUE 9 satellite): a
            # client/load balancer can tell a degraded 200 from a full
            # one without parsing the body.  A lost device (ISSUE 10c)
            # marks too: results are host-fallback-served until the
            # background rebuild restores device parity.
            ds = getattr(self.sb.index, "devstore", None)
            dlost = ds is not None and getattr(ds, "device_lost", False)
            degr = None
            if lvl > 0:
                degr = (f"{lvl}+device-loss" if dlost else str(lvl))
            elif dlost:
                degr = "device-loss"
            self._send(handler, 200, ctype, body,
                       extra={"X-YaCy-Degraded": degr} if degr else None)
        except BrokenPipeError:
            pass
        except Exception as e:  # CrashProtectionHandler parity
            try:
                self._send(handler, 500, "text/plain",
                           f"server error: {e}".encode("utf-8"))
            except (OSError, ValueError):
                pass  # client hung up (or its wfile closed) before the 500

    def _translation(self):
        """Lazy-loaded translation table for the configured UI language
        (config `locale.language`; reloaded when the setting changes)."""
        from .translation import load_locale
        lang = self.sb.config.get("locale.language", "default")
        cached = getattr(self, "_i18n", None)
        if cached is None or cached.lang != lang:
            locales = os.path.join(self.sb.data_dir, "LOCALES") \
                if getattr(self.sb, "data_dir", None) else None
            cached = load_locale(locales, lang)
            cached.lang = lang
            self._i18n = cached
        return cached

    def _render(self, name: str, ext: str, prop: ServerObjects,
                span=None) -> str:
        if prop.raw_body is not None:
            return prop.raw_body
        # the template's tree, compiled once and held while its files
        # stay as they are (.html: per translation table as well)
        got = self.templates.lookup(
            f"{name}.{ext}", self._translation() if ext == "html" else None)
        if got is not None:
            if span is not None:
                span.set(template=got[1])
            return self.templates.render_tree(got[0], prop)
        if ext == "html":
            # no bespoke template: render the GENERIC admin page — real
            # chrome + nav + a live property table, so every registered
            # servlet is operator-usable in a browser (VERDICT r2 #5;
            # the reference ships a full HTML page per servlet).
            # CONTRACT: this path ALWAYS html-escapes values. Props a
            # servlet pre-escaped show entity text here (cosmetic); the
            # alternative — trusting every servlet to have escaped —
            # would turn one unescaped put() into stored XSS.
            gen = self.templates.lookup("env/generic_page.html",
                                        self._translation(), f"{name}.html")
            if gen is not None:
                from .objects import escape_html
                if span is not None:
                    span.set(template=gen[1])
                page = ServerObjects()
                page.put("servletname", escape_html(name))
                items = sorted(prop.items())
                page.put("rows", len(items))
                for i, (k, v) in enumerate(items):
                    page.put(f"rows_{i}_key", escape_html(str(k)))
                    page.put(f"rows_{i}_value", escape_html(str(v)))
                return self.templates.render_tree(gen[0], page)
        # No template: serialize the property map directly. Values follow
        # the template contract — the servlet already escaped them for the
        # output medium — so insert them verbatim (json.dumps would
        # double-escape what escape_json produced).
        rows = ",\n".join(f' {json.dumps(k)}: "{v}"'
                          for k, v in sorted(prop.items()))
        return "{\n" + rows + "\n}"

    # -- transparent proxy ---------------------------------------------------

    def _proxy_profile(self):
        """The crawl profile proxied pages are indexed under (reference:
        the defaultProxyProfile in CrawlSwitchboard)."""
        for p in self.sb.profiles.values():
            if p.name == "proxy":
                return p
        from ..crawler.profile import CrawlProfile
        profile = CrawlProfile("proxy", depth=0, remote_indexing=False)
        self.sb.add_profile(profile)
        return profile

    def _loopback_target(self, url: str) -> bool:
        """Shared SSRF predicate (server/netguard.py): a proxied fetch
        FROM localhost would be granted localhost auto-admin by the
        target, so a remote client must never aim the node at itself."""
        from .netguard import loopback_target
        return loopback_target(url, self.sb.loader)

    def _private_target(self, url: str) -> bool:
        """Non-admin SSRF predicate: also refuses link-local (cloud
        metadata) and RFC1918 targets (server/netguard.py)."""
        from .netguard import private_target
        return private_target(url, self.sb.loader)

    def _handle_forward_proxy(self, handler, url: str) -> None:
        cfg = self.sb.config
        if not cfg.get_bool("proxyURL", False):
            self._send(handler, 403, "text/plain",
                       b"forward proxy disabled (config proxyURL)")
            return
        is_admin = self._is_admin(handler)
        # non-admin clients may not aim the proxy at loopback, link-local
        # (cloud metadata) or LAN targets (netguard; ADVICE r4)
        if self._private_target(url) and not is_admin:
            self._send(handler, 403, "text/plain",
                       b"proxy to this node refused")
            return
        from ..crawler.loader import CacheStrategy
        from ..crawler.request import Request
        # the same guard rides every redirect hop, and the addr_guard
        # pins each connection to a vetted resolution (a hostname that
        # passed the check must not re-resolve to loopback at fetch time)
        url_filter = None if is_admin \
            else (lambda u: not self._private_target(u))
        from .netguard import refuse_addr
        addr_guard = None if is_admin \
            else (lambda a: refuse_addr(a, allow_private=False))
        try:
            resp = self.sb.loader.load(Request(url=url),
                                       CacheStrategy.IFFRESH,
                                       url_filter=url_filter,
                                       addr_guard=addr_guard)
        except Exception as e:
            self._send(handler, 502, "text/plain",
                       f"proxy fetch failed: {e}".encode())
            return
        if resp.status != 200:
            # relay the upstream response (redirects need their Location
            # header to keep browsing working through the proxy)
            extra = {k: v for k, v in resp.headers.items()
                     if k.lower() in ("location", "content-type",
                                      "cache-control", "expires",
                                      "set-cookie", "last-modified")
                     and k.lower() != "content-type"}
            ctype = resp.headers.get("content-type", "text/plain")
            self._send(handler, resp.status or 502, ctype,
                       resp.content or b"", extra=extra)
            return
        # indexing side effect (HTTPDProxyHandler hands fetched pages to
        # the indexer when proxyIndexing is on)
        if cfg.get_bool("proxyIndexing", False) \
                and resp.indexable() is None:
            try:
                self.sb.to_indexer(resp, self._proxy_profile())
            except Exception:
                import logging
                logging.getLogger("httpd.proxy").warning(
                    "proxy page not handed to indexer: %s", resp.url,
                    exc_info=True)
        ctype = resp.headers.get("content-type",
                                 "application/octet-stream")
        self._send(handler, 200, ctype, resp.content)

    def _handle_yacy_domain(self, handler, host: str, path: str) -> None:
        """<peername>.yacy resolves through the seed directory."""
        peer_name = host[:-len(".yacy")]
        # P2PNode publishes the seed directory on the switchboard
        # (peers/node.py: self.sb.seeddb = ...)
        seeddb = getattr(self.sb, "seeddb", None) \
            or getattr(getattr(self.sb, "node", None), "seeddb", None)
        seed = None
        if seeddb is not None:
            for s in seeddb.all_seeds():
                if s.name == peer_name:
                    seed = s
                    break
        if seed is None:
            self._send(handler, 502, "text/plain",
                       f"unknown peer: {peer_name}".encode())
            return
        from ..crawler.loader import CacheStrategy
        from ..crawler.request import Request
        target = f"http://{seed.ip}:{seed.port}{path}"
        # same rule as the forward proxy: a seed claiming a loopback
        # address would make the node fetch localhost services (itself —
        # where auto-admin applies — or anything co-located); non-admin
        # clients are refused
        if self._loopback_target(target) and not self._is_admin(handler):
            self._send(handler, 403, "text/plain",
                       b"peer resolves to this node")
            return
        try:
            resp = self.sb.loader.load(Request(url=target),
                                       CacheStrategy.NOCACHE)
        except Exception as e:
            self._send(handler, 502, "text/plain",
                       f"peer fetch failed: {e}".encode())
            return
        ctype = resp.headers.get("content-type", "text/html")
        self._send(handler, resp.status or 200, ctype, resp.content)

    def _handle_wire(self, handler, path: str, params: dict) -> None:
        if self.peer_server is None:
            self._send(handler, 404, "text/plain", b"p2p disabled")
            return
        # distributed tracing: the originator's trace id arrives in the
        # X-YaCy-Trace header (peers/transport.HttpTransport emits it);
        # hand it to the PeerServer in-band so loopback and HTTP wires
        # share one code path (peers/server.py roots the remote spans)
        from ..utils import tracing
        wire_tid = handler.headers.get(tracing.TRACE_HEADER)
        if wire_tid and tracing.PAYLOAD_KEY not in params:
            params = {**params, tracing.PAYLOAD_KEY: wire_tid}
        endpoint = path[len("/yacy/"):]
        if endpoint.endswith(".html"):
            endpoint = endpoint[:-5]
        if getattr(handler, "_javawire", False) and endpoint == "hello":
            # a REAL YaCy peer greeting us: answer in the Java key=value
            # table format (htroot/yacy/hello.java), with the caller's
            # seed ingested into our directory like our native hello
            from ..peers import javawire
            from ..peers.seed import Seed as _Seed
            # network-unit admission (reference hello.java via
            # Protocol.authentifyRequest:2109): a peer from a foreign
            # network must not pollute this seed directory. An absent
            # netid defaults to "freeworld" EXACTLY like the reference
            # (post.get(NETWORK_NAME, Seed.DFLT_NETWORK_UNIT)).
            cfg = self.sb.config
            unit = cfg.get("network.unit.name", "freeworld")
            if params.get("netid", "freeworld") != unit:
                self._send(handler, 200, "text/plain; charset=utf-8",
                           b"message=wrong network\n")
                return
            magic = cfg.get(
                "network.unit.protocol.request.authentication.essentials",
                "")
            if magic and params.get("magicmd5", "") != javawire.magic_md5(
                    params.get("key", ""), params.get("iam", ""), magic):
                self._send(handler, 200, "text/plain; charset=utf-8",
                           b"message=authentication failed\n")
                return
            # a fleet digest riding the Java wire as the xdigest part
            # (peers/javawire.DIGEST_PART) lands in the fleet table the
            # same way the in-band `_digest` key does on the JSON wire
            fl = getattr(self.sb, "fleet", None)
            if fl is not None and params.get(javawire.DIGEST_PART):
                dig = javawire.decode_digest_part(
                    params[javawire.DIGEST_PART])
                if dig is not None:
                    fl.ingest(dig)
            # translate the Java formats at the edge, then delegate to
            # THE hello implementation (PeerServer.do_hello owns seed
            # ingest, live counts, and the gossip batch)
            payload: dict = {}
            client_seed = None
            try:
                client_seed = javawire.decode_seed(params.get("seed", ""))
                # patch the address to what we actually saw (the
                # reference anti-spoofing rule, Protocol.java:246)
                client_seed.ip = handler.client_address[0]
                payload["seed"] = client_seed.dna()
            except ValueError:
                pass
            reply = self.peer_server.do_hello(payload)
            me = _Seed.from_dna(reply["seed"])
            extra = []
            for dna in reply.get("seeds", []):
                try:
                    s = _Seed.from_dna(dna)
                except (KeyError, ValueError):
                    continue
                if s.hash != me.hash:
                    extra.append(s)
            body = javawire.java_hello_response(
                me, extra, handler.client_address[0], client_seed)
            self._send(handler, 200, "text/plain; charset=utf-8", body)
            return
        if endpoint == "meshsearch":
            # the mesh coordinator's external query entry IS a serving
            # surface (ISSUE 15): its wall lands in the same SLO
            # histogram the burn-rate rules read, with the mesh.serve
            # trace id as the exemplar — so a straggling member burns
            # slo_serving_p95 and the incident can name the cause.
            # Other wire RPCs (DHT shipping, digests, scatter internals)
            # stay out: they are not query serving.
            # lint: tail-ok(servlet.cpu: the CPU share of the
            # servlet.serving wall, see handle())
            with tracing.envelope("servlet.serving", "servlet.cpu"):
                result = self.peer_server.handle(endpoint, params)
        else:
            result = self.peer_server.handle(endpoint, params)
        body = json.dumps(result, default=_wire_default).encode("utf-8")
        self._send(handler, 200, "application/json", body)

    def _serve_static(self, handler, relpath: str) -> None:
        if ".." in relpath:
            self._send(handler, 403, "text/plain", b"forbidden")
            return
        path = self.templates.resolve(relpath)
        if path is None:
            self._send(handler, 404, "text/plain", b"not found")
            return
        ext = relpath.rpartition(".")[2]
        with open(path, "rb") as f:
            data = f.read()
        if ext == "html" and (b"#%" in data
                              or not self._translation().is_empty()):
            # static html that uses template includes (the shared
            # chrome), or any page under a non-default locale, is a
            # template like any other (expand -> translate -> expand ->
            # parse, once). Plain static pages under the default locale
            # are served BYTE-FOR-BYTE — an operator-dropped file must
            # not be re-encoded or have literal template-syntax text
            # stripped.
            try:
                got = self.templates.lookup(relpath, self._translation(),
                                            os.path.basename(relpath))
            except UnicodeDecodeError:
                got = None          # not UTF-8: serve verbatim
            if got is not None:
                data = self.templates.render_tree(
                    got[0], ServerObjects()).encode("utf-8")
        self._send(handler, 200, _CONTENT_TYPES.get(ext, "application/octet-stream"), data)

    @staticmethod
    def _send(handler, status: int, ctype: str, body: bytes,
              extra: dict | None = None) -> None:
        handler.send_response(status)
        handler.send_header("Content-Type", ctype)
        handler.send_header("Content-Length", str(len(body)))
        for k, v in (extra or {}).items():
            handler.send_header(k, v)
        handler.end_headers()
        handler.wfile.write(body)


def _wire_default(obj):
    """JSON fallback for wire payloads: bytes → base64 strings, numpy →
    lists (the HTTP DCN transport's serialization rules)."""
    import numpy as np
    if isinstance(obj, bytes):
        return base64.b64encode(obj).decode("ascii")
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    raise TypeError(f"not serializable: {type(obj)}")
