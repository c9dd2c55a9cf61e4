"""Monitoring + inspection servlets: memory dashboard, crawl results,
cached-page viewer, profiling graph.

Capability equivalents of the reference's operations pages (reference:
htroot/PerformanceMemory_p.java — heap/tables memory dashboard backed by
MemoryControl; htroot/CrawlResults.java — per-origin crawl outcome lists
incl. the error cache; htroot/ViewFile.java — render a cached page's
text/metadata from the HTCache; htroot/PerformanceGraph.java — the
EventTracker time-series rendered as a PNG via ProfilingGraph)."""

from __future__ import annotations

import os

from ...utils import histogram, tracing
from ...utils.eventtracker import EClass, events
from ...utils.memory import MemoryControl
from ..objects import ServerObjects, escape_json
from . import servlet


@servlet("PerformanceMemory_p")
def respond_memory(header: dict, post: ServerObjects, sb) -> ServerObjects:
    prop = ServerObjects()
    prop.put("used_bytes", MemoryControl.used())
    prop.put("available_bytes", MemoryControl.available())
    prop.put("short_status", 1 if MemoryControl.short_status() else 0)
    # per-store accounting (the reference's table/heap trackers)
    rows = [
        ("rwi.ram_postings", sb.index.rwi.ram_postings_count),
        ("rwi.total_postings", sb.index.rwi.total_postings()),
        ("rwi.runs", sb.index.rwi.run_count()),
        ("metadata.docs", len(sb.index.metadata)),
        ("search.cached_events", len(sb.search_cache)),
        ("frontier.local", _frontier_size(sb)),
        ("tables", len(sb.tables.tables())),
    ]
    prop.put("stores", len(rows))
    for i, (name, v) in enumerate(rows):
        prop.put(f"stores_{i}_name", name)
        prop.put(f"stores_{i}_value", int(v))
    return prop


def _frontier_size(sb) -> int:
    from ...crawler.frontier import StackType
    return sb.noticed.size(StackType.LOCAL)


@servlet("CrawlResults")
def respond_crawl_results(header: dict, post: ServerObjects,
                          sb) -> ServerObjects:
    prop = ServerObjects()
    prop.put("indexed_count", sb.indexed_count)
    errors = sb.crawl_queues.error_cache.recent(post.get_int("count", 50))
    prop.put("errors", len(errors))
    for i, (url, reason, ts) in enumerate(errors):
        prop.put(f"errors_{i}_url", escape_json(url))
        prop.put(f"errors_{i}_reason", escape_json(reason))
        prop.put(f"errors_{i}_time", int(ts))
    return prop


@servlet("ViewFile")
def respond_viewfile(header: dict, post: ServerObjects, sb) -> ServerObjects:
    """Inspect a document as the index sees it: cached raw content,
    extracted text, or metadata row (ViewFile.java viewMode semantics)."""
    prop = ServerObjects()
    url = post.get("url", "")
    mode = post.get("viewMode", "parsed")
    if not url:
        prop.put("info", "missing url")
        return prop
    from ...utils.hashes import url2hash
    docid = sb.index.metadata.docid(url2hash(url))
    if mode == "raw":
        got = sb.htcache.get(url)
        if got is None:
            prop.put("info", "not in cache")
            return prop
        content, headers = got
        prop.raw_body = content
        prop.raw_ctype = headers.get("content-type",
                                     "application/octet-stream")
        return prop
    if docid is None:
        prop.put("info", "not indexed")
        return prop
    m = sb.index.metadata.get(docid)
    prop.put("url", escape_json(url))
    prop.put("title", escape_json(m.get("title", "")))
    prop.put("docid", docid)
    if mode == "metadata":
        for k, v in sorted(m.fields.items()):
            if k != "text_t":
                prop.put(f"field_{k}", escape_json(str(v)))
    else:   # parsed text
        prop.put("text", escape_json(m.get("text_t", "")[:20000]))
        prop.put("wordcount", m.get("wordcount_i", 0))
    return prop


# lint: trace-ok(renders PROFILER aggregates to a dashboard; serves no
# query and measures no request wall of its own)
@servlet("Performance_Roofline_p")
def respond_roofline(header: dict, post: ServerObjects,
                     sb) -> ServerObjects:
    """Silicon accounting dashboard (ISSUE 1): every serving kernel's
    achieved FLOP/s / GB/s placed against the device roofline, plus the
    per-query utilization percentiles the rank-service counters carry.
    `format=png` renders the log-log roofline chart via the raster
    layer; the default response is the numeric table (template/API
    form, like DeviceStore_p)."""
    from ...ops import roofline as RF
    from ...utils.profiler import PROFILER

    peak = PROFILER.peak
    points = PROFILER.snapshot()
    if post.get("format", "") == "png":
        prop = ServerObjects()
        prop.raw_body = _roofline_png(points, peak)
        prop.raw_ctype = "image/png"
        return prop
    prop = ServerObjects()
    prop.put("device", escape_json(peak.name))
    prop.put("peak_tflops", round(peak.flops_per_s / 1e12, 3))
    prop.put("peak_gbps", round(peak.bytes_per_s / 1e9, 1))
    prop.put("ridge_flops_per_byte", round(peak.ridge, 2))
    util = PROFILER.query_util()
    prop.put("util_pct_p50", util["util_pct_p50"])
    prop.put("util_pct_p95", util["util_pct_p95"])
    prop.put("bound", util["bound"])
    prop.put("kernels", len(points))
    for i, p in enumerate(points):
        prop.put(f"kernels_{i}_name", p.kernel)
        prop.put(f"kernels_{i}_gflops", round(p.flops / 1e9, 3))
        prop.put(f"kernels_{i}_mbytes", round(p.bytes / 1e6, 2))
        prop.put(f"kernels_{i}_intensity", round(p.intensity, 2))
        prop.put(f"kernels_{i}_achieved_gflops_s",
                 round(p.achieved_flops_per_s / 1e9, 3))
        prop.put(f"kernels_{i}_achieved_gbytes_s",
                 round(p.achieved_bytes_per_s / 1e9, 3))
        prop.put(f"kernels_{i}_bound", p.bound)
        prop.put(f"kernels_{i}_util_pct", p.util_pct)
    return prop


def _roofline_png(points, peak, w: int = 640, h: int = 360) -> bytes:
    """Log-log roofline: the memory-bandwidth diagonal and the compute
    ceiling, with one dot per profiled kernel at (intensity, achieved
    FLOP/s)."""
    import math

    from ...visualization.raster import RasterPlotter
    img = RasterPlotter(w, h, background=(10, 10, 30))
    x0, y0, x1, y1 = 56, 24, w - 16, h - 44
    lx_min, lx_max = -2.0, 4.0                 # intensity 0.01..10^4 f/B
    ly_max = math.log10(max(peak.flops_per_s, 1.0))
    ly_min = ly_max - 8.0                      # 8 decades of FLOP/s

    def px(v):
        lv = min(max(math.log10(max(v, 1e-9)), lx_min), lx_max)
        return int(x0 + (lv - lx_min) / (lx_max - lx_min) * (x1 - x0))

    def py(v):
        lv = min(max(math.log10(max(v, 1.0)), ly_min), ly_max)
        return int(y1 - (lv - ly_min) / (ly_max - ly_min) * (y1 - y0))

    img.rect(x0, y0, x1, y1, (60, 60, 90))
    # the two roofs meet at the ridge point
    ridge = peak.ridge
    img.line(px(10 ** lx_min), py(10 ** lx_min * peak.bytes_per_s),
             px(ridge), py(peak.flops_per_s), (230, 180, 60))
    img.line(px(ridge), py(peak.flops_per_s),
             px(10 ** lx_max), py(peak.flops_per_s), (230, 180, 60))
    img.text(x0 + 4, y0 + 4,
             f"{peak.name}  {peak.flops_per_s / 1e12:.0f} TF/S  "
             f"{peak.bytes_per_s / 1e9:.0f} GB/S", (200, 200, 220))
    for i, p in enumerate(points):
        x, y = px(p.intensity), py(p.achieved_flops_per_s)
        color = (120, 200, 255) if p.bound == "memory" else (255, 140, 160)
        img.dot(x, y, color, radius=3)
        img.text(min(x + 6, w - 120), max(y - 4, y0 + 2),
                 f"{p.kernel[:16].upper()} {p.util_pct:.1f}", color)
    img.text(x0, h - 32, "X: FLOPS/BYTE   Y: FLOP/S   "
             "BLUE: MEMORY-BOUND  RED: COMPUTE-BOUND", (160, 160, 180))
    return img.png_bytes()


@servlet("PerformanceGraph")
def respond_perfgraph(header: dict, post: ServerObjects, sb) -> ServerObjects:
    """EventTracker time-series as a PNG bar graph (ProfilingGraph)."""
    from ...visualization.raster import RasterPlotter
    try:
        ecl = EClass[post.get("set", "SEARCH").upper()]
    except KeyError:
        ecl = EClass.SEARCH
    evs = events(ecl)[-60:]
    w, h = 640, 240
    img = RasterPlotter(w, h, background=(10, 10, 30))
    img.text(8, 6, f"{ecl.name} EVENTS: {len(evs)}", (200, 200, 220))
    if evs:
        maxd = max(max(e.duration_ms for e in evs), 1.0)
        bw = max(2, (w - 20) // max(len(evs), 1))
        for i, e in enumerate(evs):
            bh = int((e.duration_ms / maxd) * (h - 60))
            x = 10 + i * bw
            img.rect(x, h - 20 - bh, x + bw - 2, h - 20,
                     (90, 200, 140), fill=True)
        img.text(8, h - 12, f"MAX {maxd:.1f} MS", (160, 160, 180))
    prop = ServerObjects()
    prop.raw_body = img.png_bytes()
    prop.raw_ctype = "image/png"
    return prop


# ---------------------------------------------------------------------------
# distributed tracing surface (ISSUE 2)
# ---------------------------------------------------------------------------

# span-name prefix -> waterfall bar color (one hue per layer)
_TRACE_COLORS = [
    ("servlet.", (120, 200, 255)),
    ("switchboard.", (160, 220, 160)),
    ("search.", (90, 200, 140)),
    ("devstore.", (255, 190, 90)),
    ("mesh.", (255, 190, 90)),
    ("kernel.", (255, 140, 160)),
    ("peers.", (200, 160, 255)),
    ("peer.", (200, 160, 255)),
    ("index.", (180, 180, 120)),
]


def _span_color(name: str):
    for prefix, color in _TRACE_COLORS:
        if name.startswith(prefix):
            return color
    return (170, 170, 190)


@servlet("Performance_Trace_p")
def respond_trace(header: dict, post: ServerObjects, sb) -> ServerObjects:
    """Per-request stage attribution (ISSUE 2): the recent-trace table,
    per-stage p50/p95 with the tail-dominant stage named, and — for one
    trace — the span list or a waterfall PNG rendered on the raster
    layer. `format=jsonl` exports the retained ring for offline
    analysis."""
    fmt = post.get("format", "")
    tid = post.get("trace", "")
    if fmt == "jsonl":
        prop = ServerObjects()
        prop.raw_body = tracing.export_jsonl(post.get_int("count", 50))
        prop.raw_ctype = "application/jsonl; charset=utf-8"
        return prop
    if tid and fmt == "png":
        rec = tracing.get_trace(tid)
        prop = ServerObjects()
        prop.raw_body = _trace_waterfall_png(rec)
        prop.raw_ctype = "image/png"
        return prop
    prop = ServerObjects()
    prop.put("enabled", 1 if tracing.enabled() else 0)
    prop.put("dropped_traces", tracing.dropped_traces)
    prop.put("dropped_spans", tracing.dropped_spans)
    if tid:
        # cross-peer assembly (ISSUE 5): fetch the trace's remote
        # segments out of the asked peers' rings and merge them here, so
        # the waterfall below shows the WHOLE distributed request
        # instead of an opaque resource=global gap
        if post.get("assemble", "") == "1":
            node = getattr(sb, "node", None)
            prop.put("assembled_spans",
                     node.assemble_trace(tid) if node is not None else 0)
        rec = tracing.get_trace(tid)
        if rec is None:
            prop.put("info", "unknown trace")
            prop.put("spans", 0)
            return prop
        prop.put("trace_id", escape_json(rec.trace_id))
        prop.put("root", escape_json(rec.root_name))
        prop.put("duration_ms", round(rec.duration_ms(), 3))
        t0 = min((s.ts for s in rec.spans), default=rec.created)
        prop.put("spans", len(rec.spans))
        for i, s in enumerate(rec.spans):
            p = f"spans_{i}_"
            prop.put(p + "name", escape_json(s.name))
            prop.put(p + "offset_ms", round((s.ts - t0) * 1000.0, 3))
            prop.put(p + "dur_ms", round(s.dur_ms, 3))
            prop.put(p + "parent", escape_json(s.parent))
            prop.put(p + "attrs", escape_json(
                " ".join(f"{k}={v}" for k, v in s.attrs.items())))
        return prop
    recs = tracing.traces(post.get_int("count", 25))
    prop.put("traces", len(recs))
    for i, rec in enumerate(recs):
        p = f"traces_{i}_"
        prop.put(p + "trace_id", escape_json(rec.trace_id))
        prop.put(p + "root", escape_json(rec.root_name))
        prop.put(p + "duration_ms", round(rec.duration_ms(), 3))
        prop.put(p + "spans", len(rec.spans))
        prop.put(p + "done", 1 if rec.done else 0)
    # serving-stage summary by default; workload=all folds the sampled
    # per-document pipeline stages in too.  Answered from the WINDOWED
    # histograms (ISSUE 4 satellite): the old path re-walked every span
    # of the 256-trace ring per page load to recompute the same p50/p95
    # the histograms now maintain incrementally — and these percentiles
    # cover the last ~3 minutes of the whole workload, not whatever the
    # ring happens to retain
    summary = histogram.stage_table(
        exclude_prefixes=() if post.get("workload", "") == "all"
        else histogram.BACKGROUND_PREFIXES)
    stages = sorted(summary["stages"].items(),
                    key=lambda kv: -kv[1]["p95_ms"])
    prop.put("tail_dominant_stage",
             escape_json(summary["tail_dominant_stage"]))
    prop.put("stages", len(stages))
    for i, (name, st) in enumerate(stages):
        p = f"stages_{i}_"
        prop.put(p + "name", escape_json(name))
        prop.put(p + "count", st["count"])
        prop.put(p + "p50_ms", st["p50_ms"])
        prop.put(p + "p95_ms", st["p95_ms"])
    return prop


def _trace_waterfall_png(rec, w: int = 760, h: int = 0) -> bytes:
    """One trace as a waterfall: a bar per span, x = offset within the
    trace, width = duration, one color per layer prefix."""
    from ...visualization.raster import RasterPlotter
    spans = sorted(rec.spans, key=lambda s: s.ts) if rec else []
    row_h = 14
    h = h or max(80, 48 + row_h * len(spans))
    img = RasterPlotter(w, h, background=(10, 10, 30))
    if rec is None or not spans:
        img.text(8, 8, "NO SUCH TRACE / NO SPANS", (200, 200, 220))
        return img.png_bytes()
    t0 = min(s.ts for s in spans)
    t1 = max(s.ts + s.dur_ms / 1000.0 for s in spans)
    total_ms = max((t1 - t0) * 1000.0, 1e-3)
    img.text(8, 6, f"TRACE {rec.trace_id}  {total_ms:.1f} MS  "
             f"{len(spans)} SPANS", (200, 200, 220))
    x0, x1 = 200, w - 12
    for i, s in enumerate(spans):
        y = 28 + i * row_h
        color = _span_color(s.name)
        img.text(8, y, s.name[:24].upper(), color)
        bx0 = x0 + int((s.ts - t0) * 1000.0 / total_ms * (x1 - x0))
        bx1 = bx0 + max(2, int(s.dur_ms / total_ms * (x1 - x0)))
        img.rect(bx0, y + 2, min(bx1, x1), y + row_h - 4, color,
                 fill=True)
    img.text(8, h - 12, f"SCALE: {total_ms:.1f} MS ACROSS", (160, 160, 180))
    return img.png_bytes()


# ---------------------------------------------------------------------------
# /metrics — Prometheus text exposition (ISSUE 2): one endpoint unifying
# every counter the codebase keeps but scatters
# ---------------------------------------------------------------------------


def _prom_escape(v: str) -> str:
    return (str(v).replace("\\", r"\\").replace('"', r'\"')
            .replace("\n", r"\n"))


class _Prom:
    """Tiny exposition builder: families declared once, samples appended
    in declaration order (the text-format contract: all samples of a
    family are consecutive, HELP/TYPE precede them).  In OpenMetrics
    mode counter families are declared on the suffix-free base name
    (the spec reserves `_total` for the sample and forbids it on the
    family), and only then may bucket samples carry exemplars."""

    def __init__(self, openmetrics: bool = False):
        self.lines: list[str] = []
        self.openmetrics = openmetrics

    def family(self, name: str, kind: str, help_: str):
        if self.openmetrics and kind == "counter" \
                and name.endswith("_total"):
            name = name[:-len("_total")]
        self.lines.append(f"# HELP {name} {help_}")
        self.lines.append(f"# TYPE {name} {kind}")

    def sample(self, name: str, value, labels: dict | None = None,
               exemplar: tuple | None = None):
        if labels:
            lbl = ",".join(f'{k}="{_prom_escape(v)}"'
                           for k, v in labels.items())
            name = f"{name}{{{lbl}}}"
        if isinstance(value, float):
            value = round(value, 6)
        line = f"{name} {value}"
        if exemplar is not None:
            # OpenMetrics exemplar syntax: `# {trace_id="..."} value ts`
            # — the link from a slow histogram bucket straight to its
            # Performance_Trace_p waterfall (ISSUE 4)
            tid, ex_v, ex_ts = exemplar
            line += (f' # {{trace_id="{_prom_escape(tid)}"}} '
                     f"{round(ex_v, 6)} {round(ex_ts, 3)}")
        self.lines.append(line)

    def text(self) -> str:
        return "\n".join(self.lines) + "\n"


def prometheus_text(sb, include_buckets: bool = True,
                    openmetrics: bool = False) -> str:
    """Assemble the node's unified metric surface: eventtracker series,
    roofline utilization, device/mesh batcher health (incl. the
    queue_full/flush_deadline/worker_stall cause buckets), crawler
    queue depths, pipeline stages, DHT transfer counts, the logging
    drop counter (counted at utils/logging.py but surfaced nowhere
    until now), the windowed latency histograms and the tracing ring's
    own accounting.  `include_buckets=False` skips the per-bucket
    histogram samples (every family still exposes `_sum`/`_count`) —
    the health tick's evaluation surface, which reads no buckets and
    must stay cheap at its 5 s cadence.  `openmetrics=True` switches to
    the OpenMetrics dialect: suffix-free counter family declarations,
    `# {trace_id=...}` bucket exemplars and the `# EOF` trailer —
    features the classic 0.0.4 expfmt parser rejects, so they never
    appear on the default form."""
    from ...crawler.frontier import StackType
    from ...utils import logging as ylog
    from ...utils.eventtracker import totals
    from ...utils.profiler import PROFILER

    p = _Prom(openmetrics=openmetrics)

    p.family("yacy_log_dropped_records_total", "counter",
             "log records dropped by the bounded async logging queue")
    p.sample("yacy_log_dropped_records_total", ylog.dropped_count())

    p.family("yacy_stage_events_total", "counter",
             "eventtracker stage executions per (class,label)")
    tot = totals()
    for (ecl, label), (n_ev, _items, _ms) in sorted(
            tot.items(), key=lambda kv: (kv[0][0].value, kv[0][1])):
        p.sample("yacy_stage_events_total", n_ev,
                 {"class": ecl.value, "label": label})
    p.family("yacy_stage_duration_ms_total", "counter",
             "cumulative wall per eventtracker stage")
    for (ecl, label), (_n, _items, ms) in sorted(
            tot.items(), key=lambda kv: (kv[0][0].value, kv[0][1])):
        p.sample("yacy_stage_duration_ms_total", ms,
                 {"class": ecl.value, "label": label})

    from ..templates import render_counts
    p.family("yacy_template_renders_total", "counter",
             "template-file renders by what the engine held: a compiled "
             "tree (hit) or none yet / an edited file (compiled)")
    for how, n in sorted(render_counts().items()):
        p.sample("yacy_template_renders_total", n, {"template": how})

    util = PROFILER.query_util()
    p.family("yacy_roofline_util_pct", "gauge",
             "per-query achieved utilization vs device peak")
    p.sample("yacy_roofline_util_pct", util["util_pct_p50"],
             {"quantile": "p50"})
    p.sample("yacy_roofline_util_pct", util["util_pct_p95"],
             {"quantile": "p95"})
    p.family("yacy_roofline_kernel_util_pct", "gauge",
             "per-kernel achieved utilization vs device peak")
    for pt in PROFILER.snapshot():
        p.sample("yacy_roofline_kernel_util_pct", pt.util_pct,
                 {"kernel": pt.kernel, "bound": pt.bound})

    # device families are emitted even when no device store serves (all
    # zeros): the health rules reference these series by exact key, and
    # the no-dead-rules hygiene gate requires every reference to resolve
    # on every node configuration
    ds = sb.index.devstore
    c = ds.counters() if ds is not None else {}
    p.family("yacy_batch_timeouts_total", "counter",
             "batcher watchdog timeouts by cause bucket "
             "(worker_stall must stay 0 in healthy serving)")
    for cause in ("queue_full", "flush_deadline", "worker_stall"):
        p.sample("yacy_batch_timeouts_total",
                 c.get(f"batch_timeout_{cause}", 0), {"cause": cause})
    p.family("yacy_device_serving_total", "counter",
             "device store serving counters")
    for key in ("queries_served", "fallbacks", "stream_scans",
                "filtered_served", "join_served", "join_sm_served",
                "join_partners", "join_multi_served", "join_fallbacks",
                "batch_dispatches", "batch_exceptions",
                "batch_ineligible", "prune_rounds",
                # kernel shapes the start-up prewarm could not compile
                # (must stay 0: a refused shape fails at first live use)
                "prewarm_failures",
                # versioned top-k result cache (hits serve with zero
                # device work; stale = correct epoch invalidations;
                # stale_served = answers the ladder let through past
                # their epoch, which must stay 0 outside rung 3)
                "rank_cache_hits", "rank_cache_stale",
                "rank_cache_stale_served",
                # batched hybrid rerank: queries/dispatches = mean
                # coalescing factor; cache hits = full hybrid answers
                # served without touching the device
                "rerank_dispatches", "rerank_queries",
                "rerank_cache_hits", "rerank_fallbacks",
                # tier ladder hit attribution (compressed residency)
                "tier_hot_hits", "tier_warm_hits", "tier_cold_hits",
                "device_round_trips"):
        p.sample("yacy_device_serving_total", c.get(key, 0),
                 {"counter": key})
    p.family("yacy_devstore_join_bitmaps", "gauge",
             "join-bitmap slots of the serving arena: in use, and lists "
             "of bitmap size refused one (they join by sort-merge: "
             "join_sm_served beside join_served)")
    p.sample("yacy_devstore_join_bitmaps", c.get("join_bitmap_slots", 0),
             {"state": "slots"})
    p.sample("yacy_devstore_join_bitmaps", c.get("join_bitmap_refused", 0),
             {"state": "refused"})
    p.family("yacy_devstore_join_shapes", "gauge",
             "distinct join static keys (partners, rare bucket, "
             "membership modes) dispatched since the serving arena was "
             "built: a compile family each")
    p.sample("yacy_devstore_join_shapes", c.get("join_shapes", 0))
    # HBM accounting for the fleet (ISSUE 8 satellite): per-tier byte
    # occupancy and the promotion/demotion flow — always emitted (zeros
    # without a devstore) so the fleet digest's tier fields and any
    # future health rule resolve on every node configuration
    p.family("yacy_device_hbm_bytes", "gauge",
             "postings bytes resident per tier (hot=device packed/int16, "
             "warm=host-RAM packed blocks, cold=paged-run mmap), plus "
             "the vector side (ISSUE 11): dense=f16 forward-index "
             "block, ann_hot/warm/cold=the IVF slab ladder — every "
             "resident byte accounted")
    for tier in ("hot", "warm", "cold"):
        p.sample("yacy_device_hbm_bytes", c.get(f"tier_{tier}_bytes", 0),
                 {"tier": tier})
    p.sample("yacy_device_hbm_bytes", c.get("dense_fwd_bytes", 0),
             {"tier": "dense"})
    for tier in ("hot", "warm", "cold"):
        p.sample("yacy_device_hbm_bytes",
                 c.get(f"ann_{tier}_bytes", 0), {"tier": f"ann_{tier}"})
    # dense-first IVF ANN (ISSUE 11): candidate-generation coverage +
    # the vector tier ladder's traffic — always emitted (zeros without
    # an index) so fleet digests and health rules resolve everywhere
    p.family("yacy_ann_total", "counter",
             "dense-first ANN counters: queries/dispatches = mean "
             "coalescing factor, host_queries = device-loss host path, "
             "fallbacks = no index (plain rerank served), tier hits = "
             "probe traffic per residency tier, promotions = clusters "
             "uploaded into the hot arena, lane_drops = whole-cluster "
             "probe-budget drops")
    for key in ("ann_dispatches", "ann_queries", "ann_fallbacks",
                "ann_host_queries", "ann_tier_hot_hits",
                "ann_tier_warm_hits", "ann_tier_cold_hits",
                "ann_promotions", "ann_promote_failures",
                "ann_lane_drops"):
        p.sample("yacy_ann_total", c.get(key, 0),
                 {"counter": key[4:]})
    p.family("yacy_ann_centroid_version", "gauge",
             "ANN centroid-set version (bumps on rebuild AND on hot "
             "promotion — scoring-venue moves re-key cached fused "
             "lists; keys the dense-first top-k cache)")
    p.sample("yacy_ann_centroid_version",
             c.get("ann_centroid_version", 0))
    p.family("yacy_ann_resident_vectors", "gauge",
             "vectors resident in the IVF slab ladder")
    p.sample("yacy_ann_resident_vectors", c.get("ann_vectors", 0))
    p.family("yacy_tier_promotions_total", "counter",
             "tier ladder transitions (src->dst; demotions/evictions "
             "ride the same family)")
    for src, dst, key in (("warm", "hot", "tier_promotions_warm_hot"),
                          ("cold", "hot", "tier_promotions_cold_hot"),
                          ("hot", "warm", "tier_demotions_hot_warm"),
                          ("warm", "cold", "tier_evictions_warm_cold")):
        p.sample("yacy_tier_promotions_total", c.get(key, 0),
                 {"src": src, "dst": dst})
    p.family("yacy_device_compression_ratio", "gauge",
             "measured int16-bytes/packed-bytes over resident packed "
             "blocks (1.0 = int16 residency)")
    p.sample("yacy_device_compression_ratio",
             c.get("packed_compression_ratio", 1.0))
    # cold-tier paging cache (index/pagedrun.TermCache): byte-budget LRU
    # behavior must be attributable when paging storms hit the host path
    p.family("yacy_term_cache_total", "counter",
             "paged-run term cache events (the cold tier's LRU)")
    for ev in ("hits", "misses", "evictions"):
        p.sample("yacy_term_cache_total", c.get(f"term_cache_{ev}", 0),
                 {"event": ev})
    p.family("yacy_term_cache_bytes", "gauge",
             "resident bytes in the paged-run term cache")
    p.sample("yacy_term_cache_bytes", c.get("term_cache_bytes", 0))
    p.family("yacy_device_arena_epoch", "gauge",
             "arena epoch (bumps on flush/merge/repack/delete; the "
             "stale-spike health rule reads its churn)")
    p.sample("yacy_device_arena_epoch", c.get("arena_epoch", 0))
    # -- multi-process mesh identity (ISSUE 12): which OS process this
    # node is.  Always emitted (pid everywhere; process_id/num_processes
    # zero-filled off-mesh) so the fleet digest's proc fields resolve on
    # every node configuration — the coordinator's Network_Health_p
    # renders the REAL process grid from its peers' digests.
    mm = getattr(sb, "mesh_member", None)
    p.family("yacy_mesh_process", "gauge",
             "multi-process mesh identity: this node's OS pid, its "
             "jax.distributed process id and the mesh process count "
             "(0/1 when not a mesh member)")
    p.sample("yacy_mesh_process", os.getpid(), {"field": "pid"})
    p.sample("yacy_mesh_process",
             mm.process_id if mm is not None else 0,
             {"field": "process_id"})
    p.sample("yacy_mesh_process",
             mm.num_processes if mm is not None else 1,
             {"field": "num_processes"})
    # -- device-loss recovery (ISSUE 10c): always emitted (zeros
    # without a devstore) — the device_loss health rule and the
    # device_rebuild actuator reference these series by exact key
    p.family("yacy_device_lost", "gauge",
             "1 while the device is declared lost (queries host-"
             "fallback, background rebuild running), else 0")
    p.sample("yacy_device_lost", c.get("device_lost", 0))
    p.family("yacy_device_loss_total", "counter",
             "device-loss lifecycle counters: declared losses, "
             "completed rebuilds back to device serving, host-fallback "
             "answers while lost, retry-exhausted transfer failures, "
             "bounded in-ladder transfer retries")
    for key in ("losses", "recoveries", "lost_queries",
                "transfer_failures", "transfer_retries"):
        ck = {"losses": "device_losses",
              "recoveries": "device_loss_recoveries",
              "lost_queries": "device_lost_queries"}.get(key, key)
        p.sample("yacy_device_loss_total", c.get(ck, 0),
                 {"event": key})
    # -- read-side integrity (ISSUE 10a): corruption detections by
    # (kind, action) and journal torn-tail recoveries per store —
    # zero-filled over the canonical sets so alert expressions and the
    # storage_corruption rule always resolve
    from ...index import integrity as _integ
    p.family("yacy_storage_corruption_total", "counter",
             "storage corruption events: kind=run/segment/journal, "
             "action=error (detection) / quarantined (run pulled from "
             "serving, terms answered from surviving generations)")
    for (kind, action), v in sorted(_integ.corruption_counts().items()):
        p.sample("yacy_storage_corruption_total", v,
                 {"kind": kind, "action": action})
    p.family("yacy_journal_torn_tail_total", "counter",
             "journal replays that dropped a torn tail line (the "
             "expected kill-9 artifact: recovered, counted)")
    for store, v in sorted(_integ.torn_tail_counts().items()):
        p.sample("yacy_journal_torn_tail_total", v, {"store": store})
    p.family("yacy_integrity_verified_total", "counter",
             "checksum verifications performed on the read path "
             "(spans, segment columns, run indexes)")
    p.sample("yacy_integrity_verified_total", _integ.verified_total())
    p.family("yacy_batcher_queue_depth", "gauge",
             "batcher incoming / in-flight queue depths (the backlog "
             "health rule watches the growth trend)")
    b = getattr(ds, "_batcher", None) if ds is not None else None
    p.sample("yacy_batcher_queue_depth",
             b._q.qsize() if b is not None else 0, {"queue": "incoming"})
    p.sample("yacy_batcher_queue_depth",
             b._inflight.qsize() if b is not None else 0,
             {"queue": "inflight"})
    if ds is not None:
        p.family("yacy_device_latency_ms", "gauge",
                 "the trivial device round trip measured at start "
                 "(dispatch and kernel walls: the devstore.batch / "
                 "kernel.* span families)")
        if "dispatch_rt_ms" in c:
            p.sample("yacy_device_latency_ms", c["dispatch_rt_ms"],
                     {"stat": "dispatch_rt_ms"})

    p.family("yacy_crawler_queue_depth", "gauge",
             "frontier stack depths")
    for stack in (StackType.LOCAL, StackType.GLOBAL, StackType.REMOTE,
                  StackType.NOLOAD):
        p.sample("yacy_crawler_queue_depth", sb.noticed.size(stack),
                 {"stack": stack})

    p.family("yacy_pipeline_processed_total", "counter",
             "documents through each indexing pipeline stage")
    p.family("yacy_pipeline_errors_total", "counter",
             "stage handler errors")
    p.family("yacy_pipeline_queued", "gauge", "stage queue depth")
    procs = [sb._parse_proc, sb._condense_proc, sb._structure_proc,
             sb._store_proc]
    for proc in procs:
        p.sample("yacy_pipeline_processed_total", proc.metrics.processed,
                 {"stage": proc.name})
    for proc in procs:
        p.sample("yacy_pipeline_errors_total", proc.metrics.errors,
                 {"stage": proc.name})
    for proc in procs:
        p.sample("yacy_pipeline_queued", proc.queue.qsize(),
                 {"stage": proc.name})

    p.family("yacy_index_documents", "gauge", "documents in the index")
    p.sample("yacy_index_documents", sb.index.doc_count())
    p.family("yacy_index_rwi_postings", "gauge",
             "postings in the reverse word index")
    p.sample("yacy_index_rwi_postings", sb.index.rwi_size())
    p.family("yacy_search_cached_events", "gauge",
             "live events in the search event cache")
    p.sample("yacy_search_cached_events", len(sb.search_cache))
    p.family("yacy_indexed_documents_total", "counter",
             "documents stored by this node since start")
    p.sample("yacy_indexed_documents_total", sb.indexed_count)

    node = getattr(sb, "node", None)
    if node is not None:
        p.family("yacy_dht_transferred_postings_total", "counter",
                 "postings shipped to DHT target peers")
        p.sample("yacy_dht_transferred_postings_total",
                 node.dispatcher.transferred_postings)
        p.family("yacy_dht_received_total", "counter",
                 "index transfer receipts by kind")
        p.sample("yacy_dht_received_total", node.server.received_rwi_count,
                 {"kind": "rwi"})
        p.sample("yacy_dht_received_total", node.server.received_url_count,
                 {"kind": "url"})
        p.family("yacy_peers", "gauge", "seed directory population")
        p.sample("yacy_peers", len(node.seeddb.active), {"state": "active"})
        p.sample("yacy_peers", len(node.seeddb.passive),
                 {"state": "passive"})
        p.sample("yacy_peers", len(node.seeddb.potential),
                 {"state": "potential"})

    # -- fleet observability (ISSUE 5): the coordinator-free mesh view.
    # Emitted on EVERY node (zeros without peers): the fleet_* health
    # rules reference these series by exact key, and the no-dead-rules
    # hygiene gate requires every reference to resolve everywhere.
    from ...utils import fleet as fleetdigest
    fl = getattr(sb, "fleet", None)
    if fl is not None:
        fl.render()       # keep the digest-size gauge honest per scrape
    peers_fresh = fl.fresh() if fl is not None else []
    p.family("yacy_fleet_peers", "gauge",
             "fresh peer metric digests retained in the fleet table")
    p.sample("yacy_fleet_peers", len(peers_fresh))
    p.family("yacy_fleet_digests_total", "counter",
             "digest gossip traffic (rendered locally, received from "
             "peers, ignored as invalid/replayed)")
    for kind, v in (("rendered", fl.rendered_count if fl else 0),
                    ("received", fl.received_count if fl else 0),
                    ("ignored", fl.ignored_count if fl else 0)):
        p.sample("yacy_fleet_digests_total", v, {"kind": kind})
    p.family("yacy_fleet_digest_bytes", "gauge",
             "wire size of the last rendered local digest "
             "(budget: fleet.byteBudget, default 2048)")
    p.sample("yacy_fleet_digest_bytes",
             fl.last_digest_bytes if fl else 0)
    p.family("yacy_fleet_merged_latency_ms", "gauge",
             "mesh-wide percentiles from merged local+peer digest "
             "bucket vectors (lossless merge, no coordinator)")
    for fam in fleetdigest.DIGEST_FAMILIES:
        counts = fl.merged_counts(fam) if fl is not None else None
        for q, lbl in ((0.50, "p50"), (0.95, "p95"), (0.99, "p99")):
            v = histogram.percentile_from_counts(counts, q) \
                if counts else 0.0
            p.sample("yacy_fleet_merged_latency_ms", round(v, 3),
                     {"family": fam, "quantile": lbl})
    p.family("yacy_fleet_peer_reported_critical", "gauge",
             "fresh peers whose digest reports critical health")
    p.sample("yacy_fleet_peer_reported_critical",
             len([e for e in peers_fresh if e.get("health") == 2]))

    # -- tail forensics (ISSUE 15): the cause-attribution canon.  Every
    # over-threshold serving query gets exactly one classified verdict;
    # the cause counters are ZERO-FILLED over the canon so alert
    # expressions and the fleet digest's top-1 mapping always resolve.
    from ...utils import tailattr
    p.family("yacy_tail_cause_total", "counter",
             "classified p99 verdicts by dominant cause (one verdict "
             "per over-threshold serving query; collective_straggler "
             "verdicts additionally name the member in "
             "yacy_tail_straggler_total)")
    tc = tailattr.cause_totals()
    for cause in tailattr.CAUSES:
        p.sample("yacy_tail_cause_total", tc.get(cause, 0),
                 {"cause": cause})
    p.family("yacy_tail_straggler_total", "counter",
             "collective_straggler verdicts by the named mesh member")
    for member, v in sorted(tailattr.straggler_totals().items()):
        p.sample("yacy_tail_straggler_total", v, {"member": member})
    # straggler convictions (ISSUE 19 / ROADMAP 1c, read-only): the
    # member was the slowest leg over N consecutive scoreboard windows.
    # ZERO-FILLED over every member the coordinator's timeline has
    # scattered to, so alert expressions resolve before (and without)
    # any conviction ever firing.
    p.family("yacy_mesh_straggler_convictions_total", "counter",
             "straggler-scoreboard convictions (member slowest over N "
             "consecutive windows; observation only — no steering)")
    for member, v in sorted(tailattr.conviction_totals().items()):
        p.sample("yacy_mesh_straggler_convictions_total", v,
                 {"member": member})
    p.family("yacy_tail_verdicts_total", "counter",
             "over-threshold serving queries classified by the "
             "tail-attribution engine")
    p.sample("yacy_tail_verdicts_total",
             tailattr.ATTR.counters()["classified_total"])

    # -- whitebox profiler (ISSUE 20): sampler counters + per-role
    # sample totals ZERO-FILLED over the profiling.ROLES canon (the
    # fleet digest's top-role index maps into these, so the series must
    # resolve on every node before any sampling happens)
    from ...utils import profiling
    pstats = profiling.stats()
    p.family("yacy_prof_samples_total", "counter",
             "thread-stack samples folded by the in-process profiler")
    p.sample("yacy_prof_samples_total", pstats["samples_total"])
    p.family("yacy_prof_capture_windows_total", "counter",
             "triggered high-rate deep-capture windows completed")
    p.sample("yacy_prof_capture_windows_total",
             pstats["capture_windows_total"])
    p.family("yacy_prof_holder_captures_total", "counter",
             "over-p95 lock holds whose holder stack was captured")
    p.sample("yacy_prof_holder_captures_total",
             pstats["holder_captures_total"])
    p.family("yacy_prof_sampler_hz", "gauge",
             "current profiler sampling cadence (burst while a "
             "capture window is armed)")
    p.sample("yacy_prof_sampler_hz", round(pstats["sampler_hz"], 1))
    p.family("yacy_prof_role_samples_total", "counter",
             "profiler samples by thread role (named-pool canon; "
             "windowed over the retained sample ring)")
    samp = profiling.sampler()
    roles = samp.role_samples() if samp is not None \
        else {r: 0 for r in profiling.ROLES}
    for role in profiling.ROLES:
        p.sample("yacy_prof_role_samples_total", roles.get(role, 0),
                 {"role": role})

    p.family("yacy_traces_retained", "gauge",
             "completed traces in the tracing ring")
    p.sample("yacy_traces_retained", len(tracing.traces(tracing.MAX_TRACES)))
    p.family("yacy_trace_drops_total", "counter",
             "traces/spans dropped at the ring bounds")
    p.sample("yacy_trace_drops_total", tracing.dropped_traces,
             {"kind": "traces"})
    p.sample("yacy_trace_drops_total", tracing.dropped_spans,
             {"kind": "spans"})

    # -- windowed latency histograms (ISSUE 4): one Prometheus histogram
    # family per registered Histogram — cumulative _bucket/_sum/_count
    # (monotonic by contract) with trace-id exemplars on the buckets the
    # slow requests landed in.  EVERY registered histogram appears here
    # by construction (iterating the registry is the hygiene gate).
    for h in histogram.all_histograms():
        fam = histogram.prom_name(h.name)
        snap = h.snapshot()
        p.family(fam, "histogram", h.help)
        if include_buckets:
            exs = snap["exemplars"] if openmetrics \
                else [None] * len(snap["exemplars"])
            cum = 0
            for i, le in enumerate(histogram.BUCKET_BOUNDS_MS):
                cum += snap["counts"][i]
                p.sample(fam + "_bucket", cum, {"le": f"{le:g}"},
                         exemplar=exs[i])
            cum += snap["counts"][-1]
            p.sample(fam + "_bucket", cum, {"le": "+Inf"},
                     exemplar=exs[-1])
        p.sample(fam + "_sum", round(snap["sum_ms"], 3))
        p.sample(fam + "_count", snap["count"])

    # -- health engine (ISSUE 4): the overall gauge + one gauge per rule
    # (0 ok / 1 warn / 2 critical) so an alertmanager can page on the
    # same states Performance_Health_p shows
    eng = getattr(sb, "health", None)
    if eng is not None:
        p.family("yacy_health_status", "gauge",
                 "overall node health (0 ok / 1 warn / 2 critical)")
        p.sample("yacy_health_status", eng.status_value())
        p.family("yacy_health_rule", "gauge",
                 "per-rule health state (0 ok / 1 warn / 2 critical)")
        for name, _desc, st in eng.rule_table():
            p.sample("yacy_health_rule",
                     {"ok": 0, "warn": 1, "critical": 2}[st.state],
                     {"rule": name})
        p.family("yacy_health_incidents_total", "counter",
                 "flight-recorder incident dumps since start")
        p.sample("yacy_health_incidents_total", eng.incident_count)

    # -- actuator layer (ISSUE 9): every closed-loop state change is a
    # counted transition, the current ladder rung is a gauge, and the
    # per-level served-query histogram attributes degradation coverage.
    # Zero-filled per (actuator, dir) so alert expressions always
    # resolve (the no-dead-actuators gate mirrors the rules').
    act = getattr(sb, "actuators", None)
    p.family("yacy_actuator_transitions_total", "counter",
             "actuator state changes by direction along each "
             "actuator's own axis (serving_ladder: down=degrade/"
             "up=recover; batcher_autotune: up=grow pool/down=shrink; "
             "remote_peer_guard: down=peers newly avoided/up=healed); "
             "zero during healthy serving")
    if act is not None:
        for (aname, d), v in sorted(act.transition_counts().items()):
            p.sample("yacy_actuator_transitions_total", v,
                     {"actuator": aname, "dir": d})
    p.family("yacy_degrade_level", "gauge",
             "current degradation-ladder rung this node SERVES under "
             "(0 full .. 4 shed; a rank-service worker reports the "
             "owner-propagated rung it actually applies)")
    p.sample("yacy_degrade_level",
             act.effective_level() if act is not None else 0)
    p.family("yacy_degraded_queries_total", "counter",
             "queries served per degradation-ladder rung")
    for lvl in range(5):
        p.sample("yacy_degraded_queries_total",
                 act.degraded_queries[lvl] if act is not None else 0,
                 {"level": str(lvl)})
    p.family("yacy_shed_requests_total", "counter",
             "requests refused by the ladder's shed rung")
    p.sample("yacy_shed_requests_total",
             act.shed_count if act is not None else 0)
    bt = getattr(ds, "_batcher", None) if ds is not None else None
    tun = bt.tuning() if bt is not None and hasattr(bt, "tuning") \
        else {"dispatchers": 0, "completer_depth": 0}
    p.family("yacy_batcher_tuning", "gauge",
             "live batcher pool geometry (the auto-tuner's actuation "
             "surface)")
    for param in ("dispatchers", "completer_depth"):
        p.sample("yacy_batcher_tuning", tun.get(param, 0),
                 {"param": param})
    # -- streaming-ingest write path (ISSUE 13): crawl-to-searchable
    # doc counts per tier, backpressure waits, and the merge/promotion
    # scheduler's deferral bookkeeping.  Always emitted (the tracker is
    # process-global; scheduler counters zero-fill without one) so the
    # ingest_slo_searchable rule and the merge_scheduler actuator
    # resolve on every node configuration.  The latency tiers
    # themselves ride the ingest.* histogram families above.
    from ...ingest import slo as ingest_slo
    ic = dict(ingest_slo.TRACKER.counters())
    sched = getattr(sb, "ingest_scheduler", None)
    sc = sched.counters() if sched is not None else {}
    p.family("yacy_ingest_total", "counter",
             "write-path counters: docs stamped/searchable/flushed/"
             "device per crawl-to-searchable tier, dropped stamps, "
             "counted backpressure waits, and the merge/promotion "
             "scheduler's deferrals + catch-ups")
    for key in ("docs_stamped", "docs_searchable", "docs_flushed",
                "docs_device", "stamps_dropped", "backpressure_waits"):
        p.sample("yacy_ingest_total", ic.get(key, 0), {"counter": key})
    for key in ("merge_deferrals", "promote_deferrals",
                "merge_catch_ups", "catch_up_merges",
                "catch_up_promotions"):
        p.sample("yacy_ingest_total", sc.get(key, 0), {"counter": key})
    p.family("yacy_ingest_deferred", "gauge",
             "1 while the merge/promotion scheduler is deferring "
             "(serving SLO burning), else 0")
    p.sample("yacy_ingest_deferred", sc.get("deferred", 0))
    p.family("yacy_ingest_deferred_promotions", "gauge",
             "tier promotions currently parked by the deferral")
    p.sample("yacy_ingest_deferred_promotions",
             sc.get("deferred_promotions_parked", 0))
    p.family("yacy_remotesearch_peers_total", "counter",
             "remote-search peer decisions (asked / skipped_sick / "
             "adaptive_timeout) — attributes every fleet-driven skip")
    rc = fl.remote_counter_snapshot() if fl is not None else {}
    for outcome in ("asked", "skipped_sick", "adaptive_timeout"):
        p.sample("yacy_remotesearch_peers_total", rc.get(outcome, 0),
                 {"outcome": outcome})
    return p.text() + ("# EOF\n" if openmetrics else "")


@servlet("metrics")
def respond_metrics(header: dict, post: ServerObjects,
                    sb) -> ServerObjects:
    """GET /metrics — Prometheus text exposition.  Classic 0.0.4 by
    default; an Accept header naming openmetrics-text (what a
    Prometheus server with exemplar support negotiates) or
    `format=openmetrics` upgrades to OpenMetrics WITH the trace-id
    exemplars — which a classic parser would reject, so they never
    appear on the 0.0.4 form."""
    om = ("openmetrics" in header.get("accept", "")
          or post.get("format", "") == "openmetrics")
    prop = ServerObjects()
    prop.raw_body = prometheus_text(sb, openmetrics=om)
    prop.raw_ctype = (
        "application/openmetrics-text; version=1.0.0; charset=utf-8"
        if om else "text/plain; version=0.0.4; charset=utf-8")
    return prop


# -- whitebox profiler dashboard (ISSUE 20) -----------------------------------


def _flame_png(stacks: list, w: int = 800, h: int = 360) -> bytes:
    """Icicle-layout flamegraph over the top folded stacks: row 0 is
    all samples, each deeper row splits a frame's width among its
    children proportionally to sample counts.  Rendered on the raster
    layer like the roofline/waterfall charts."""
    from ...visualization.raster import RasterPlotter

    img = RasterPlotter(w, h, background=(10, 10, 30))
    total = sum(s.get("count", 0) for s in stacks)
    if total <= 0:
        img.text(16, 16, "NO SAMPLES", (200, 200, 200))
        return img.png_bytes()
    row_h = 16
    max_depth = (h - 24) // row_h

    # prefix tree: node = {count, children{frame: node}}
    root = {"count": total, "children": {}}
    for s in stacks:
        node = root
        for frame in s["stack"].split(";")[:max_depth]:
            kids = node["children"]
            if frame not in kids:
                kids[frame] = {"count": 0, "children": {}}
            node = kids[frame]
            node["count"] += s["count"]

    palette = [(205, 92, 52), (224, 138, 56), (198, 66, 66),
               (226, 170, 62), (182, 102, 38)]

    def draw(node, depth, x0, x1):
        if depth >= max_depth or x1 - x0 < 2:
            return
        x = x0
        for i, (frame, child) in enumerate(sorted(
                node["children"].items(),
                key=lambda kv: -kv[1]["count"])):
            width = (x1 - x0) * child["count"] / max(1, node["count"])
            cx1 = min(x1, x + width)
            if cx1 - x >= 2:
                color = palette[(depth + i) % len(palette)]
                y = 20 + depth * row_h
                img.rect(int(x), y, int(cx1) - 1, y + row_h - 2,
                         color, fill=True)
                label = frame.split(":")[-1] if depth else frame
                if (cx1 - x) >= 6 * len(label[:10]) + 4:
                    img.text(int(x) + 2, y + 4, label[:24], (0, 0, 0))
                draw(child, depth + 1, x, cx1)
            x = cx1
    img.text(16, 4, f"PROFILE {total} SAMPLES", (220, 220, 220))
    draw(root, 0, 16, w - 16)
    return img.png_bytes()


@servlet("Performance_Prof_p")
def respond_prof(header: dict, post: ServerObjects, sb) -> ServerObjects:
    """Whitebox profiler dashboard (ISSUE 20): top folded stacks with
    role tags, the per-lock wait/hold table with recent over-p95
    holder stacks, and the last triggered deep capture.  `format=json`
    exports the full wire snapshot (what do_profsnap ships);
    `format=png` renders the raster flamegraph."""
    import json as _json

    from ...utils import profiling

    n = post.get_int("n", 12)
    snap = profiling.snapshot(n)
    fmt = post.get("format", "")
    if fmt == "png":
        prop = ServerObjects()
        prop.raw_body = _flame_png(snap["stacks"])
        prop.raw_ctype = "image/png"
        return prop
    if fmt == "json":
        prop = ServerObjects()
        prop.raw_body = _json.dumps(snap, indent=1)
        prop.raw_ctype = "application/json; charset=utf-8"
        return prop
    prop = ServerObjects()
    prop.put("enabled", 1 if snap["enabled"] else 0)
    prop.put("sampler_hz", snap["sampler_hz"])
    prop.put("samples_total", snap["samples_total"])
    prop.put("capture_windows_total", snap["capture_windows_total"])
    prop.put("holder_captures_total", snap["holder_captures_total"])
    prop.put("stacks", len(snap["stacks"]))
    for i, st in enumerate(snap["stacks"]):
        p = f"stacks_{i}_"
        prop.put(p + "role", escape_json(st["role"]))
        prop.put(p + "count", st["count"])
        prop.put(p + "stack", escape_json(st["stack"]))
    for role in profiling.ROLES:
        prop.put(f"role_{role.replace('-', '_')}_samples",
                 snap["roles"].get(role, 0))
    prop.put("locks", len(snap["locks"]))
    for i, row in enumerate(snap["locks"]):
        p = f"locks_{i}_"
        prop.put(p + "name", escape_json(row["name"]))
        prop.put(p + "contended_total", row["contended_total"])
        prop.put(p + "wait_count", row["wait"]["count"])
        prop.put(p + "wait_p50_ms", row["wait"]["p50_ms"])
        prop.put(p + "wait_p95_ms", row["wait"]["p95_ms"])
        prop.put(p + "hold_count", row["hold"]["count"])
        prop.put(p + "hold_p50_ms", row["hold"]["p50_ms"])
        prop.put(p + "hold_p95_ms", row["hold"]["p95_ms"])
        prop.put(p + "holder_stacks", len(row["holder_stacks"]))
        for k, hs in enumerate(row["holder_stacks"]):
            prop.put(f"{p}holder_{k}_hold_ms", hs["hold_ms"])
            prop.put(f"{p}holder_{k}_stack", escape_json(hs["stack"]))
    cap = snap.get("last_capture")
    prop.put("capture", 1 if cap else 0)
    if cap:
        prop.put("capture_reason", escape_json(cap["reason"]))
        prop.put("capture_samples", cap["samples"])
        prop.put("capture_stacks", len(cap["stacks"]))
        for i, st in enumerate(cap["stacks"]):
            p = f"capture_stacks_{i}_"
            prop.put(p + "role", escape_json(st["role"]))
            prop.put(p + "count", st["count"])
            prop.put(p + "stack", escape_json(st["stack"]))
    return prop
