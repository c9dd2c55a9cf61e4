"""Distributed query tracing — the span spine across every serving layer.

Where `utils/eventtracker.py` records FLAT (label, count, duration)
tuples with no causality, this module carries a trace id through the
whole request path — servlet → SearchEvent → device/mesh batcher +
kernel → P2P fan-out → remote peer — so a slow query's wall can be
attributed to the stage that actually spent it ("Repeatability Corner
Cases in Document Ranking": tail behavior hides in stage interactions,
not stage averages; PAPERS.md).

Design rules (the EventTracker discipline, applied to spans):

- **Zero-alloc when disabled / untraced.** `span()` returns ONE shared
  no-op object unless tracing is enabled AND a trace is active on the
  calling context. A hot path outside any trace pays a contextvar read.
- **One clock with the chip.** Every live span also opens a
  `jax.profiler.TraceAnnotation` of its name on the thread where the
  work runs, while a profiler session is recording: the spans then sit
  in the host planes of the same `.xplane.pb` as the device operations
  (`benchmarks/host_spans.py` labels device idle gaps with them).
  Outside a session a span pays one `is_enabled()` read; a process that
  never loaded JAX pays a dict lookup. `annotation()` is the same for
  work that has no span of its own (the batcher's threads).
- **One call for a wall that is always measured.** `timed()` is a live
  span under a trace and a plain timer outside one; `record()` is the
  same for a wall measured elsewhere. Either way the family in
  `utils/histogram.py` gets the observation, so a distribution covers
  the whole workload and not its traced share.
- **Context-carried.** The active (trace_id, span_id) rides a
  contextvar, so nested spans parent correctly across the synchronous
  call tree; explicit `attach()` / `span_in()` / `emit()` carry the
  context across thread handoffs (batcher items, pipeline stages,
  remote fan-out threads).
- **Bounded per-node ring.** Completed spans accumulate per trace in an
  insertion-ordered dict capped at `MAX_TRACES` traces of `MAX_SPANS`
  spans each; overflow increments drop counters instead of growing.
  Late spans (straggler peers merging after the root closed) still land
  in the ring — the same late-merge discipline as the result heap.
- **Wire-propagated.** `peers/protocol.py` stamps the active trace id
  into every RPC payload (`_trace`); `HttpTransport` moves it into the
  ``X-YaCy-Trace`` header, `server/httpd.py` parses it back, and
  `peers/server.py` opens the remote segment under the ORIGINATOR's
  trace id — so a scatter-gather search is one trace network-wide.
"""

from __future__ import annotations

import gc
import itertools
import json
import os
import secrets
import sys
import threading
import time
from collections import OrderedDict, deque
from contextvars import ContextVar
from dataclasses import dataclass, field

from . import histogram

# wire header carrying the trace id between peers (parsed in
# server/httpd.py for HTTP, peers/javawire.py part "xtrace" for the
# Java wire, payload key "_trace" for the in-band transports)
TRACE_HEADER = "X-YaCy-Trace"
PAYLOAD_KEY = "_trace"

MAX_TRACES = 256          # completed-trace ring size per node/process
MAX_SPANS = 1024          # spans retained per trace

_enabled = True
_lock = threading.Lock()
_ctx: ContextVar = ContextVar("yacy_trace_ctx", default=None)
_span_seq = itertools.count(1)

# traces dropped from the ring / spans dropped at the per-trace cap
dropped_traces = 0
dropped_spans = 0


@dataclass
class Span:
    """One completed span. `ts` is wall-clock start (epoch seconds),
    `dur_ms` the measured wall; `parent` is "" for trace-root and
    remote-segment roots."""

    sid: str
    parent: str
    name: str
    ts: float
    dur_ms: float
    attrs: dict = field(default_factory=dict)

    def to_json(self) -> dict:
        return {"sid": self.sid, "parent": self.parent, "name": self.name,
                "ts": round(self.ts, 6), "dur_ms": round(self.dur_ms, 3),
                **({"attrs": self.attrs} if self.attrs else {})}


@dataclass
class TraceRecord:
    trace_id: str
    root_name: str
    created: float
    spans: list = field(default_factory=list)
    done: bool = False
    dropped: int = 0

    def duration_ms(self) -> float:
        """Wall covered by the trace: root span duration when recorded,
        else the spread of whatever spans exist (remote segments)."""
        for s in self.spans:
            if s.parent == "" and s.name == self.root_name:
                return s.dur_ms
        if not self.spans:
            return 0.0
        t0 = min(s.ts for s in self.spans)
        t1 = max(s.ts + s.dur_ms / 1000.0 for s in self.spans)
        return (t1 - t0) * 1000.0

    def to_json(self) -> dict:
        return {"trace_id": self.trace_id, "root": self.root_name,
                "created": round(self.created, 6),
                "duration_ms": round(self.duration_ms(), 3),
                "dropped_spans": self.dropped,
                "spans": [s.to_json() for s in self.spans]}


_ring: "OrderedDict[str, TraceRecord]" = OrderedDict()


def enabled() -> bool:
    return _enabled


def set_enabled(on: bool) -> None:
    global _enabled
    _enabled = bool(on)


# A trace id is what `do_tracefetch` (peers/server.py) asks of a peer
# before it hands out a trace's spans, the query's text among them, so
# it stays unguessable: 64 bits of the system's entropy.  They are read
# 512 ids at a time: `os.urandom` lets go of the interpreter lock, and
# once a request is where a request's threads queue (ISSUE 38).  A
# forked child drops what it inherited, or it would hand out its
# parent's ids.
_ID_HEX = 16
_id_pool: list[str] = []
os.register_at_fork(after_in_child=_id_pool.clear)


def new_trace_id() -> str:
    try:
        return _id_pool.pop()
    except IndexError:
        raw = secrets.token_hex(512 * _ID_HEX // 2)
        # (a list, not a generator: one `extend` that no `pop` cuts into)
        _id_pool.extend([raw[i:i + _ID_HEX]
                         for i in range(_ID_HEX, len(raw), _ID_HEX)])
        return raw[:_ID_HEX]


def valid_trace_id(tid) -> bool:
    """Inbound (wire) ids are untrusted: bound length + charset so a
    hostile peer cannot flood the ring with junk keys."""
    return (isinstance(tid, str) and 4 <= len(tid) <= 64
            and all(c.isalnum() or c in "-_" for c in tid))


def _new_sid() -> str:
    return f"s{next(_span_seq)}"


def _register(trace_id: str, root_name: str) -> TraceRecord:
    global dropped_traces
    with _lock:
        rec = _ring.get(trace_id)
        if rec is None:
            rec = TraceRecord(trace_id, root_name, time.time())
            _ring[trace_id] = rec
            while len(_ring) > MAX_TRACES:
                _ring.popitem(last=False)
                dropped_traces += 1
        return rec


def _record(trace_id: str, span: Span) -> None:
    global dropped_spans
    if _gc_pending:
        flush_gc()
    # every completed span ALSO lands in the windowed histogram for its
    # name, carrying its trace id as the exemplar — the one wiring point
    # that gives every traced wall (servlet roots, StageTimer bridge
    # spans, batcher spans, kernel emits, remote segments) a
    # distribution on /metrics with a link back to the waterfall
    # (ISSUE 4).  Recorded even when the ring drops the span: the
    # histogram measures the workload, the ring retains evidence.
    histogram.observe(span.name, span.dur_ms, trace_id)
    with _lock:
        rec = _ring.get(trace_id)
        if rec is None:
            # late span for an evicted trace: count it, don't resurrect
            dropped_spans += 1
            return
        if len(rec.spans) >= MAX_SPANS:
            rec.dropped += 1
            dropped_spans += 1
            return
        rec.spans.append(span)


# -- context -----------------------------------------------------------------

# trace id of the most recent ROOT span completed on this context: lets
# a caller that wraps traced work (httpd's servlet dispatch wall, see
# `envelope`) join the request's trace and stamp its histogram exemplar
# even though the trace closed inside the callee.  Per-context
# (thread-per-request), cleared by the envelope on entry.
_last_root: ContextVar = ContextVar("yacy_last_root_trace", default=None)

# root-completion hooks (ISSUE 15): the tail-attribution engine
# registers here to classify every over-threshold serving root.  Kept
# as a registration surface (not an import) so bare tracing users pay
# nothing and there is no tracing -> tailattr import cycle.
_root_hooks: list = []


def add_root_hook(fn) -> None:
    """Register fn(trace_id, root_name, dur_ms), called after every
    ROOT span completes.  Idempotent per function object."""
    if fn not in _root_hooks:
        _root_hooks.append(fn)


def _fire_root_hooks(tid: str, name: str, dur_ms: float) -> None:
    for fn in _root_hooks:
        try:
            fn(tid, name, dur_ms)
        except Exception:  # lint: broad-except-ok(a broken classifier
            # hook must cost a log line, never the serving request
            # whose root span just closed)
            import logging
            logging.getLogger("tracing").warning(
                "root hook failed for %s", name, exc_info=True)


def current() -> tuple[str, str] | None:
    """The active (trace_id, span_id), or None."""
    return _ctx.get()


def current_trace_id() -> str | None:
    ctx = _ctx.get()
    return ctx[0] if ctx else None


def attach(ctx: tuple[str, str] | None):
    """Set the active context (cross-thread handoff); returns the token
    for `detach`."""
    return _ctx.set(ctx)


def detach(token) -> None:
    _ctx.reset(token)


# -- the profiler's clock ------------------------------------------------------

_TraceMe = None     # jax.profiler.TraceAnnotation, once JAX is loaded


def _recording():
    """The annotation class while a profiler session records, else None
    (then nothing is allocated). JAX is never imported from here: the
    jax-free children (crash tests, clients) stay jax-free."""
    global _TraceMe
    tm = _TraceMe
    if tm is None:
        if "jax" not in sys.modules:
            return None
        try:
            from jax.profiler import TraceAnnotation as tm
        except ImportError:         # JAX is mid-import on another thread
            return None
        _TraceMe = tm
    return tm if tm.is_enabled() else None


def _annotate(name: str):
    """An ENTERED annotation for a span to close on its way out, or
    None while no session records."""
    tm = _recording()
    if tm is None:
        return None
    ann = tm(name)
    ann.__enter__()
    return ann


def annotation(name: str, **attrs):
    """A block on the profiler's timeline and nowhere else: for work
    whose wall a submitter re-emits as a span later (the batcher's
    former, dispatchers and completers carry no trace of their own).
    `attrs` become the event's stats."""
    tm = _recording()
    return _NOOP if tm is None else tm(name, **attrs)


# -- span context managers ---------------------------------------------------

class _NoopSpan:
    """Shared do-nothing span: the zero-alloc path when tracing is off
    or no trace is active."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass

    def rename(self, name: str) -> None:
        pass


_NOOP = _NoopSpan()


class _LiveSpan:
    __slots__ = ("_tid", "_sid", "_parent", "_name", "_attrs",
                 "_t0", "_ts", "_token", "_root", "_end_trace", "_ann")

    def __init__(self, tid: str, parent: str, name: str, attrs: dict,
                 root: bool = False, end_trace: bool = False):
        self._tid = tid
        self._sid = _new_sid()
        self._parent = parent
        self._name = name
        self._attrs = attrs
        self._root = root
        self._end_trace = end_trace

    def __enter__(self):
        self._ann = _annotate(self._name)
        self._ts = time.time()
        self._t0 = time.perf_counter()
        self._token = _ctx.set((self._tid, self._sid))
        return self

    def __exit__(self, etype, exc, tb):
        _ctx.reset(self._token)
        if etype is not None:
            self._attrs["error"] = etype.__name__
        dur_ms = (time.perf_counter() - self._t0) * 1000.0
        if self._ann is not None:
            self._ann.__exit__(etype, exc, tb)
        _record(self._tid, Span(
            self._sid, self._parent, self._name, self._ts,
            dur_ms, self._attrs))
        if self._root:
            _last_root.set(self._tid)
            _fire_root_hooks(self._tid, self._name, dur_ms)
        if self._end_trace:
            with _lock:
                rec = _ring.get(self._tid)
                if rec is not None:
                    rec.done = True
        return False

    def set(self, **attrs) -> None:
        self._attrs.update(attrs)

    def rename(self, name: str) -> None:
        """Name the span by an outcome known only inside it (the route
        a search took). The profiler's annotation keeps the name it was
        opened under."""
        self._name = name

    @property
    def ctx(self) -> tuple[str, str]:
        return (self._tid, self._sid)


class _Timed:
    """`timed()` outside a trace: the wall still reaches its family and
    the profiler's timeline; no span, no context."""

    __slots__ = ("_name", "_t0", "_ann")

    def __init__(self, name: str):
        self._name = name

    def __enter__(self):
        self._ann = _annotate(self._name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, etype, exc, tb):
        dur_ms = (time.perf_counter() - self._t0) * 1000.0
        if self._ann is not None:
            self._ann.__exit__(etype, exc, tb)
        histogram.observe(self._name, dur_ms)
        return False

    def set(self, **attrs) -> None:
        pass

    def rename(self, name: str) -> None:
        self._name = name


def trace(name: str, trace_id: str | None = None, **attrs):
    """Root span: starts a new trace (and registers it in the ring).
    If a trace is already active on this context, degrades to a child
    span — one request is one trace, however the layers nest."""
    if not _enabled:
        return _NOOP
    cur = _ctx.get()
    if cur is not None:
        return _LiveSpan(cur[0], cur[1], name, attrs)
    tid = trace_id or new_trace_id()
    _register(tid, name)
    return _LiveSpan(tid, "", name, attrs, root=True, end_trace=True)


def span(name: str, **attrs):
    """Child span under the active trace; no-op (shared object, zero
    alloc) when tracing is off or no trace is active."""
    if not _enabled:
        return _NOOP
    cur = _ctx.get()
    if cur is None:
        return _NOOP
    return _LiveSpan(cur[0], cur[1], name, attrs)


def span_in(ctx: tuple[str, str] | None, name: str, **attrs):
    """Child span under an EXPLICIT context (cross-thread handoff:
    pipeline entries, batcher items, remote fan-out threads). The
    context is attached for the span's duration so nested spans and the
    profiler bridge parent correctly."""
    if not _enabled or ctx is None:
        return _NOOP
    return _LiveSpan(ctx[0], ctx[1], name, attrs)


def timed(name: str, ctx: tuple[str, str] | None = None, **attrs):
    """A wall that is ALWAYS measured: a child span under `ctx` or the
    active trace, and outside any trace (or with tracing disabled) a
    timer that still feeds the family `name` — the one call for a stage
    whose distribution must cover the whole workload (StageTimer, the
    batcher's submit wait, the page, the route)."""
    c = ctx if ctx is not None else _ctx.get()
    if not _enabled or c is None:
        return _Timed(name)
    return _LiveSpan(c[0], c[1], name, attrs)


def attached(ctx: tuple[str, str] | None):
    """Attach a context for a block WITHOUT recording a span of its own
    — for code that already times itself through a bridged surface
    (StageTimer): the bridge's span lands under `ctx`, and nothing is
    double-recorded."""
    return _Attached(ctx)


class _Attached:
    __slots__ = ("_ctx", "_token")

    def __init__(self, ctx):
        self._ctx = ctx

    def __enter__(self):
        self._token = _ctx.set(self._ctx)
        return self

    def __exit__(self, *exc):
        _ctx.reset(self._token)
        return False


def remote_trace(trace_id: str, name: str, **attrs):
    """Server side of wire propagation: open THIS node's segment of a
    trace that originated elsewhere. Registers the originator's trace id
    in the local ring (so the segment is inspectable here too) and roots
    a span under it."""
    if not _enabled or not valid_trace_id(trace_id):
        return _NOOP
    _register(trace_id, name)
    return _LiveSpan(trace_id, "", name, attrs)


def emit(name: str, dur_ms: float, ctx: tuple[str, str] | None = None,
         ts: float | None = None, **attrs) -> None:
    """Record an already-measured wall as a completed span — the bridge
    for timings taken elsewhere (the roofline profiler's kernel walls)
    and for zero-length markers. Uses the active context unless an
    explicit one is given; silently a no-op outside any trace."""
    if _enabled and (ctx is not None or _ctx.get() is not None):
        record(name, dur_ms, ctx, ts, **attrs)


def record(name: str, dur_ms: float, ctx: tuple[str, str] | None = None,
           ts: float | None = None, sid: str | None = None,
           **attrs) -> None:
    """An already-measured wall, ALWAYS into its family: as a completed
    span under `ctx` or the active trace (the span record feeds the
    family, exemplar included), else straight into the family — the
    after-the-fact twin of `timed()` (the batcher's stamps a submitter
    re-emits, the route of a cache hit, the servlet's wall)."""
    c = ctx if ctx is not None else _ctx.get()
    if not _enabled or c is None:
        histogram.observe(name, dur_ms)
        return
    if ts is None:
        ts = time.time() - dur_ms / 1000.0
    _record(c[0], Span(sid or _new_sid(), c[1], name, ts, dur_ms, attrs))


class _Envelope:
    """The wall AROUND a request whose trace is rooted beneath it (see
    `envelope`)."""

    __slots__ = ("_name", "_cpu", "_sid", "_ts", "_t0", "_c0", "_ann")

    def __init__(self, name: str, cpu_family: str):
        self._name = name
        self._cpu = cpu_family
        self._sid = _new_sid()

    def __enter__(self):
        _last_root.set(None)
        self._ann = _annotate(self._name)
        self._ts = time.time()
        self._c0 = time.thread_time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, etype, exc, tb):
        dur_ms = (time.perf_counter() - self._t0) * 1000.0
        cpu_ms = (time.thread_time() - self._c0) * 1000.0
        if self._ann is not None:
            self._ann.__exit__(etype, exc, tb)
        tid = _last_root.get()
        histogram.observe(self._cpu, cpu_ms, tid)
        record(self._name, dur_ms, (tid, "") if tid else None, self._ts,
               self._sid, cpu_ms=round(cpu_ms, 3))
        return False

    @property
    def ctx(self) -> tuple[str, str] | None:
        """Parent context for work done inside the envelope AFTER the
        trace beneath it closed (the template render); None until a
        root has completed on this context."""
        tid = _last_root.get()
        return (tid, self._sid) if tid else None


def envelope(name: str, cpu_family: str):
    """A wall measured by a caller that cannot know whether the callee
    will root a trace (httpd around any servlet): always into the family
    `name`, with the thread's CPU time (`time.thread_time()`) of the same
    interval into `cpu_family` — wall less CPU is what the thread spent
    waiting: for the interpreter lock, a lock, the device. When a root
    span completed inside, the wall also joins that trace as a
    parentless span (like a remote segment's root) and carries its id as
    the exemplar, so a slow bucket links to the waterfall."""
    return _Envelope(name, cpu_family)


# -- the collector ------------------------------------------------------------

GC_FAMILY = "runtime.gc"
GC_SPAN_MIN_MS = 1.0
_gc_t0 = 0.0
_gc_ann = None
# (ms, interrupted context, start, generation, collected) of collections
# not yet in the family; bounded, so a process that closes no span loses
# the oldest
_gc_pending: deque = deque(maxlen=4096)


def _on_gc(phase: str, info: dict) -> None:
    """`gc.callbacks` hook. The collector runs on whichever thread
    tripped the threshold, between two of its bytecodes and with the
    interpreter lock held: the pause is that thread's, and every other
    thread's wait. The thread may be INSIDE one of this module's or a
    histogram's locked sections, so the hook takes no lock: it stamps
    two clock reads and queues the observation; `flush_gc` (the next
    span to close, the health tick, a reader) files it."""
    global _gc_t0, _gc_ann
    if phase == "start":
        _gc_ann = _annotate(GC_FAMILY)
        _gc_t0 = time.perf_counter()
        return
    dur_ms = (time.perf_counter() - _gc_t0) * 1000.0
    if _gc_ann is not None:
        _gc_ann.__exit__(None, None, None)
        _gc_ann = None
    if dur_ms >= GC_SPAN_MIN_MS:
        _gc_pending.append((dur_ms, _ctx.get(),
                            time.time() - dur_ms / 1000.0,
                            info.get("generation", 0),
                            info.get("collected", 0)))
    else:
        _gc_pending.append((dur_ms, None, 0.0, 0, 0))


def flush_gc() -> None:
    """File the queued collections: every one an observation of
    `runtime.gc`, one over GC_SPAN_MIN_MS that interrupted a trace also
    a span of it (so a young collection costs two clock reads, an
    append and, here, a bucket increment)."""
    while _gc_pending:
        try:
            dur_ms, ctx, ts, gen, collected = _gc_pending.popleft()
        except IndexError:
            return
        if ctx is None:
            histogram.observe(GC_FAMILY, dur_ms)
        else:
            record(GC_FAMILY, dur_ms, ctx, ts, generation=gen,
                   collected=collected)


def watch_gc(on: bool = True) -> None:
    """Install (or remove) the collector hook; idempotent."""
    if on and _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    elif not on and _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


# -- pipeline (begin/end across async stages) --------------------------------

class PipelineTrace:
    """Explicit begin/end trace handle for work that flows through
    queue-decoupled stages (the 4-stage indexing pipeline): the handle
    travels on the work item, each stage opens `span_in(handle.ctx,...)`,
    and the last stage (or a drop) calls `end()`."""

    __slots__ = ("tid", "sid", "name", "attrs", "_ts", "_t0", "_done")

    def __init__(self, tid: str, name: str, attrs: dict):
        self.tid = tid
        self.sid = _new_sid()
        self.name = name
        self.attrs = attrs
        self._ts = time.time()
        self._t0 = time.perf_counter()
        self._done = False

    @property
    def ctx(self) -> tuple[str, str]:
        return (self.tid, self.sid)

    def end(self, **attrs) -> None:
        if self._done:
            return
        self._done = True
        self.attrs.update(attrs)
        _record(self.tid, Span(
            self.sid, "", self.name, self._ts,
            (time.perf_counter() - self._t0) * 1000.0, self.attrs))
        with _lock:
            rec = _ring.get(self.tid)
            if rec is not None:
                rec.done = True


def begin(name: str, **attrs) -> PipelineTrace | None:
    """Start a detached trace (see PipelineTrace); None when disabled —
    callers pass the handle around and every span_in(None, ...) is
    free."""
    if not _enabled:
        return None
    t = PipelineTrace(new_trace_id(), name, attrs)
    _register(t.tid, name)
    return t


# -- reading -----------------------------------------------------------------

def traces(n: int = 50) -> list[TraceRecord]:
    """Most recent `n` traces, newest first."""
    with _lock:
        recs = list(_ring.values())
    return recs[::-1][:max(0, n)]


def get_trace(trace_id: str) -> TraceRecord | None:
    with _lock:
        return _ring.get(trace_id)


def clear() -> None:
    global dropped_traces, dropped_spans
    with _lock:
        _ring.clear()
        dropped_traces = 0
        dropped_spans = 0


def export_jsonl(n: int = 50) -> str:
    """Recent traces as JSONL, one trace per line (the export surface
    Performance_Trace_p serves with format=jsonl)."""
    return "\n".join(json.dumps(t.to_json()) for t in traces(n))


# -- cross-peer trace assembly (ISSUE 5) -------------------------------------
#
# A distributed search is ONE trace id network-wide (the wire
# propagation above), but each peer's spans live in ITS ring: the
# originator sees an opaque `peers.remotesearch` gap where the remote
# work happened.  The `tracefetch` wire endpoint (peers/server.py)
# serves a trace's local segment by id; the originator merges fetched
# segments back into its record (P2PNode.assemble_trace), and
# Performance_Trace_p renders the full distributed waterfall.

def trace_segment(trace_id: str,
                  max_spans: int = MAX_SPANS) -> dict | None:
    """This node's retained segment of a trace, wire-serializable (the
    server side of the `tracefetch` endpoint).  `truncated` counts
    spans NOT shipped (ring-side drops + any cap applied here): an
    assembled waterfall must be able to say it is incomplete rather
    than silently omit the tail."""
    with _lock:
        rec = _ring.get(trace_id)
        if rec is None:
            return None
        return {"trace_id": rec.trace_id, "root": rec.root_name,
                "truncated": rec.dropped
                + max(0, len(rec.spans) - max_spans),
                "spans": [s.to_json() for s in rec.spans[:max_spans]]}


def merge_remote_spans(trace_id: str, spans, source: str) -> int:
    """Merge a fetched remote segment into the local ring; returns the
    number of spans actually added.

    Dedup + collision rules: a span whose (sid, name, start) already
    exists locally is the SAME span seen through a co-hosted ring and is
    skipped; a colliding sid with different content (two processes both
    count spans from s1) is renamed under a `source`-derived prefix,
    with parent links inside the fetched batch remapped consistently.
    Merged spans do NOT feed the windowed histograms — the remote node
    already observed them into its own, and they arrive in its digest.
    """
    global dropped_spans
    if not _enabled or not valid_trace_id(trace_id) \
            or not isinstance(spans, list) or not spans:
        return 0
    incoming = []
    for sj in spans[:MAX_SPANS]:
        if not isinstance(sj, dict):
            continue
        try:
            sid = str(sj["sid"])
            name = str(sj["name"])
            ts = float(sj.get("ts", 0.0))
            dur = float(sj.get("dur_ms", 0.0))
            parent = str(sj.get("parent", ""))
            attrs = sj.get("attrs")
            attrs = dict(attrs) if isinstance(attrs, dict) else {}
        except (KeyError, TypeError, ValueError):
            continue
        incoming.append((sid, parent, name, ts, dur, attrs))
    if not incoming:
        return 0
    root_name = next((n for _s, p, n, _t, _d, _a in incoming if p == ""),
                     incoming[0][2])
    rec = _register(trace_id, root_name)
    src = "".join(c for c in str(source) if c.isalnum())[:6] or "remote"
    merged = 0
    with _lock:
        existing = {s.sid: s for s in rec.spans}
        remap: dict[str, str] = {}
        fresh = []
        for sid, parent, name, ts, dur, attrs in incoming:
            ex = existing.get(sid)
            if ex is not None and ex.name == name \
                    and abs(ex.ts - ts) < 0.002:
                continue                    # same span, co-hosted ring
            nsid = sid if ex is None else f"{src}.{sid}"
            ex2 = existing.get(nsid)
            if ex2 is not None and ex2.name == name \
                    and abs(ex2.ts - ts) < 0.002:
                # merged by an earlier fetch (idempotence) — but still
                # record the rename: a NEW span in this batch may
                # parent on the colliding sid and must follow it to the
                # renamed copy, not the originator's unrelated local span
                remap[sid] = nsid
                continue
            remap[sid] = nsid
            attrs.setdefault("fetched_from", str(source))
            fresh.append(Span(nsid, parent, name, ts, dur, attrs))
        for s in fresh:
            s.parent = remap.get(s.parent, s.parent)
            if len(rec.spans) >= MAX_SPANS:
                rec.dropped += 1
                dropped_spans += 1
                continue
            rec.spans.append(s)
            merged += 1
    return merged


# the one nearest-rank convention across the observability layer lives
# in utils/histogram.py; this alias survives for the callers that
# learned it here (the profiler).  The per-stage p50/p95 summary
# (formerly stage_summary, a full ring walk per call) lives in
# histogram.stage_table now: every span feeds the windowed histograms
# at record time, so the table is maintained incrementally and covers
# untraced work too.
_pctl = histogram.pctl
