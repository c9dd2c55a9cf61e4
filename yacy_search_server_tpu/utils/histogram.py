"""Windowed log-bucket latency histograms — the node's percentile spine.

Before this module every surface that wanted a p50/p95 recomputed it
from raw samples at read time: `Performance_Trace_p` iterated the whole
trace ring per page load, the devstore kept 20k-entry deques, and
`/metrics` exposed no distribution at all — a Prometheus scraper saw
counters and gauges but could never ask "how slow is slow".  This module
gives every hot wall ONE cheap recording surface (ISSUE 4 tentpole):

- **HDR-style fixed buckets.** Log-linear: octaves of 2 from 2^-5 ms to
  2^20 ms, each split into 4 linear sub-buckets (≤ 25 % bucket width, so
  an interpolated percentile is within ~12.5 % of the true sample — the
  agreement bound BASELINE.md pins against the raw-sample percentiles).
  Bucket index is a `math.frexp` + two integer ops: zero alloc.
- **Windowed ring rotation.** Counts land in the current of `WINDOWS`
  ring slots; the slot advances every `ROTATE_EVERY_S` (lazily on
  record, or from the health tick), so `percentile()` answers from the
  last ~WINDOWS×ROTATE_EVERY_S minutes, not process lifetime.  Separate
  CUMULATIVE counts back the Prometheus `_bucket/_sum/_count` series,
  which must be monotonic by contract.
- **Trace-id exemplars.** A recording at or above the window p95 (cached
  at rotation, so the check is one compare) stamps its trace id on its
  bucket — `/metrics` exposes it OpenMetrics-style and
  `Performance_Health_p`/`Performance_Trace_p` link the slow bucket
  straight to the waterfall.
- **Mergeable.** Fixed shared bounds mean bucket-count vectors add;
  `merge_counts` + `percentile_from_counts` serve cross-store and
  cross-window aggregation.

`pctl` here is THE nearest-rank percentile convention — tracing and
the profiler delegate to it (one implementation, satellite of ISSUE 4).
"""

from __future__ import annotations

import math
import threading
import time
from collections import OrderedDict

# window geometry: 6 slots × 30 s = percentiles over the last ~3 minutes
WINDOWS = 6
ROTATE_EVERY_S = 30.0

# log-linear bucket grid: octaves [2^e, 2^(e+1)) ms for e in
# [_EXP_LO, _EXP_HI), each split into _SUBS linear sub-buckets
_EXP_LO = -5                 # 2^-5 ms = 31.25 µs
_EXP_HI = 20                 # 2^20 ms ≈ 17.5 min; above → +Inf bucket
_SUBS = 4
N_BUCKETS = (_EXP_HI - _EXP_LO) * _SUBS + 1      # +1: the +Inf bucket

# upper bound (`le`) of every finite bucket, in ms
BUCKET_BOUNDS_MS: tuple = tuple(
    (1.0 + (s + 1) / _SUBS) * (2.0 ** e)
    for e in range(_EXP_LO, _EXP_HI) for s in range(_SUBS))


def bucket_index(ms: float) -> int:
    """Bucket for a value (clamped into [0, N_BUCKETS-1]); ~4 float ops.
    Bounds are INCLUSIVE upper edges (`le` semantics, the Prometheus
    contract): a value exactly on a bound lands in the bucket whose
    `le` it equals."""
    if ms <= 0.0:
        return 0
    frac, exp = math.frexp(ms)          # ms = frac * 2^exp, frac ∈ [0.5, 1)
    idx = (exp - 1 - _EXP_LO) * _SUBS + int((frac - 0.5) * (2 * _SUBS))
    if idx <= 0:
        return 0
    if idx >= N_BUCKETS - 1:
        return N_BUCKETS - 1
    # frexp treats a bound as the exclusive low edge of the NEXT bucket;
    # pull exact-boundary values back into their `le` bucket
    if ms <= BUCKET_BOUNDS_MS[idx - 1]:
        idx -= 1
    return idx


def pctl(sorted_values: list, q: float) -> float:
    """Nearest-rank percentile over a SORTED list — the one convention
    shared by tracing and the profiler."""
    if not sorted_values:
        return 0.0
    return sorted_values[min(len(sorted_values) - 1,
                             int(len(sorted_values) * q))]


def percentile_from_counts(counts, q: float) -> float:
    """Percentile from a bucket-count vector (windowed or merged), with
    linear interpolation inside the straddling bucket.  The +Inf bucket
    answers with the largest finite bound (a floor, never an invention)."""
    total = sum(counts)
    if total <= 0:
        return 0.0
    rank = min(total - 1, int(total * q))
    cum = 0
    for i, c in enumerate(counts):
        if c <= 0:
            continue
        if cum + c > rank:
            if i >= N_BUCKETS - 1:
                return BUCKET_BOUNDS_MS[-1]
            lo = BUCKET_BOUNDS_MS[i - 1] if i > 0 else 0.0
            hi = BUCKET_BOUNDS_MS[i]
            return lo + (hi - lo) * ((rank - cum) + 0.5) / c
        cum += c
    return BUCKET_BOUNDS_MS[-1]


def merge_counts(count_vectors) -> list:
    """Sum bucket-count vectors (all histograms share one bound grid, so
    counts are mergeable by construction)."""
    out = [0] * N_BUCKETS
    for vec in count_vectors:
        for i, c in enumerate(vec):
            out[i] += c
    return out


def fraction_over_counts(counts, threshold_ms: float) -> float:
    """Fraction of a bucket-count vector above `threshold_ms` (the
    straddling bucket contributes linearly) — the burn-rate numerator,
    shared by the per-node SLO rule (via Histogram.fraction_over) and
    the fleet-level rule over MERGED peer digests (utils/fleet.py)."""
    total = sum(counts)
    if total <= 0:
        return 0.0
    ti = bucket_index(threshold_ms)
    bad = float(sum(counts[ti + 1:]))
    lo = BUCKET_BOUNDS_MS[ti - 1] if ti > 0 else 0.0
    hi = BUCKET_BOUNDS_MS[ti] if ti < N_BUCKETS - 1 \
        else BUCKET_BOUNDS_MS[-1]
    if hi > lo:
        bad += counts[ti] * max(0.0, min(1.0, (hi - threshold_ms)
                                         / (hi - lo)))
    return bad / total


def counts_to_sparse(counts) -> dict:
    """Bucket-count vector -> the digest wire form `{"i": [...], "c":
    [...]}` (indices + counts of the non-empty buckets only).  Lossless:
    `counts_from_sparse` reconstructs the exact vector, so merged
    mesh-wide percentiles equal the ones computed from the raw vectors
    (the ISSUE 5 acceptance property)."""
    idx: list[int] = []
    cts: list[int] = []
    for i, c in enumerate(counts):
        if c:
            idx.append(i)
            cts.append(int(c))
    return {"i": idx, "c": cts}


def counts_from_sparse(obj) -> list | None:
    """Tolerant decode of the digest wire form; None on malformed input
    (the caller drops the family, never the whole digest).  Indices
    outside this build's grid — a future version with more buckets —
    clamp into the edge buckets instead of failing the merge."""
    if not isinstance(obj, dict):
        return None
    idx, cts = obj.get("i"), obj.get("c")
    if not isinstance(idx, (list, tuple)) or \
            not isinstance(cts, (list, tuple)) or len(idx) != len(cts):
        return None
    out = [0] * N_BUCKETS
    try:
        for i, c in zip(idx, cts):
            i, c = int(i), int(c)
            if c < 0:
                return None
            out[min(max(i, 0), N_BUCKETS - 1)] += c
    except (TypeError, ValueError):
        return None
    return out


class Histogram:
    """One latency family: cumulative counts (Prometheus) + a windowed
    ring (operator percentiles) + per-bucket trace-id exemplars."""

    __slots__ = ("name", "help", "_lock", "counts", "sum_ms", "count",
                 "_win", "_wsum", "_wt0", "_wi", "_next_rot", "_p95_cache",
                 "exemplars")

    def __init__(self, name: str, help_: str = ""):
        self.name = name
        self.help = help_ or f"latency of {name} in ms"
        self._lock = threading.Lock()
        self.counts = [0] * N_BUCKETS          # cumulative (monotonic)
        self.sum_ms = 0.0
        self.count = 0
        self._win = [[0] * N_BUCKETS for _ in range(WINDOWS)]
        # per slot: the sum of its values (a windowed mean, a rate of
        # time spent) and when it was opened (what the ring covers)
        self._wsum = [0.0] * WINDOWS
        self._wt0 = [time.monotonic()] * WINDOWS
        self._wi = 0
        self._next_rot = time.monotonic() + ROTATE_EVERY_S
        self._p95_cache = 0.0                  # refreshed at rotation
        # bucket -> (trace_id, value_ms, unix_ts); only values at/above
        # the cached window p95 claim a slot (slow buckets link to traces)
        self.exemplars: list = [None] * N_BUCKETS

    # -- recording -----------------------------------------------------------

    def record(self, ms: float, trace_id: str | None = None) -> None:
        idx = bucket_index(ms)
        now = time.monotonic()
        with self._lock:
            if now >= self._next_rot:
                self._rotate_locked(now)
            self.counts[idx] += 1
            self.sum_ms += ms
            self.count += 1
            self._win[self._wi][idx] += 1
            self._wsum[self._wi] += ms
            if trace_id is not None and (
                    ms >= self._p95_cache or self.exemplars[idx] is None):
                self.exemplars[idx] = (trace_id, ms, time.time())

    def _rotate_locked(self, now: float) -> None:
        # cache p95 BEFORE clearing the next slot: the exemplar gate
        # compares against the window that just closed
        self._p95_cache = percentile_from_counts(
            merge_counts(self._win), 0.95)
        steps = 1 + min(WINDOWS - 1,
                        int((now - self._next_rot) / ROTATE_EVERY_S))
        for _ in range(steps):
            self._wi = (self._wi + 1) % WINDOWS
            self._win[self._wi] = [0] * N_BUCKETS
            self._wsum[self._wi] = 0.0
            self._wt0[self._wi] = now
        self._next_rot = now + ROTATE_EVERY_S
        # exemplars age out at the window horizon: a bucket must never
        # keep pointing at a trace from hours ago (likely evicted from
        # the bounded trace ring by then)
        cut = time.time() - WINDOWS * ROTATE_EVERY_S
        self.exemplars = [e if e is not None and e[2] >= cut else None
                          for e in self.exemplars]

    def rotate(self) -> None:
        """Force a window advance (the health tick's rotation driver)."""
        with self._lock:
            self._rotate_locked(time.monotonic())

    def reset_window(self) -> None:
        """Drop every retained window sample and the cached p95 (the
        cumulative Prometheus counters stay monotonic).  A harness
        calls this at the warmup/measurement boundary: compile-era
        walls would otherwise sit in the merged ring for
        WINDOWS*ROTATE_EVERY_S and hold the exemplar gate far above
        the live workload."""
        with self._lock:
            self._win = [[0] * N_BUCKETS for _ in range(WINDOWS)]
            self._wsum = [0.0] * WINDOWS
            self._wt0 = [time.monotonic()] * WINDOWS
            self._wi = 0
            self._p95_cache = 0.0
            self._next_rot = time.monotonic() + ROTATE_EVERY_S

    # -- reading -------------------------------------------------------------

    @property
    def p95_cache(self) -> float:
        """The window p95 cached at the last rotation — the exemplar
        election threshold, reused by the tail-attribution gate
        (ISSUE 15): a value at/above it is exemplar-worthy, so it gets
        classified.  Unlocked read of an atomically-replaced float (the
        same discipline record() uses for its compare)."""
        # lint: unlocked-ok(float replaced atomically under _lock at
        # rotation; a stale read only shifts one gating decision by a
        # rotation interval)
        return self._p95_cache

    def windowed_counts(self, last: int | None = None) -> list:
        """Merged bucket counts over the newest `last` windows (default:
        all retained)."""
        with self._lock:
            k = WINDOWS if last is None else max(1, min(last, WINDOWS))
            vecs = [self._win[(self._wi - i) % WINDOWS] for i in range(k)]
            return merge_counts(vecs)

    def percentile(self, q: float, last: int | None = None) -> float:
        """Windowed percentile (the last ~N minutes, not process life)."""
        return percentile_from_counts(self.windowed_counts(last), q)

    def windowed_count(self, last: int | None = None) -> int:
        return sum(self.windowed_counts(last))

    def windowed_sum(self) -> float:
        """Sum in ms of the retained windows' values: over
        `windowed_count()` a mean, over `windowed_span_s()` the time
        spent per second."""
        with self._lock:
            return sum(self._wsum)

    def windowed_span_s(self) -> float:
        """Seconds the retained windows cover: since the oldest slot was
        opened — the family's creation, its last `reset_window()`, or
        the rotation that dropped what came before."""
        with self._lock:
            return time.monotonic() - min(self._wt0)

    def window_seconds(self, last: int | None = None) -> float:
        """Wall time the newest `last` windows actually cover: the
        CURRENT slot counts only its elapsed fill (a rate computed over
        the full ROTATE_EVERY_S right after a rotation would
        under-state qps and flap threshold gates)."""
        k = WINDOWS if last is None else max(1, min(last, WINDOWS))
        with self._lock:
            elapsed = ROTATE_EVERY_S - max(
                0.0, self._next_rot - time.monotonic())
        return max(1.0, min(elapsed, ROTATE_EVERY_S)) \
            + (k - 1) * ROTATE_EVERY_S

    def fraction_over(self, threshold_ms: float,
                      last: int | None = None) -> tuple[float, int]:
        """(fraction of windowed observations above `threshold_ms`,
        windowed total) — the burn-rate numerator for SLO rules.  The
        straddling bucket contributes linearly."""
        counts = self.windowed_counts(last)
        total = sum(counts)
        if total <= 0:
            return 0.0, 0
        return fraction_over_counts(counts, threshold_ms), total

    def snapshot(self) -> dict:
        """Cumulative view for the Prometheus exposition."""
        with self._lock:
            return {"counts": list(self.counts), "sum_ms": self.sum_ms,
                    "count": self.count,
                    "exemplars": list(self.exemplars)}


# -- registry ----------------------------------------------------------------

_reg_lock = threading.Lock()
_REG: "OrderedDict[str, Histogram]" = OrderedDict()
_enabled = True


def set_enabled(on: bool) -> None:
    """Global record gate: off, `observe` records nothing."""
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


def histogram(name: str, help_: str = "") -> Histogram:
    """Get-or-create; families are created once and live forever (the
    exposition iterates this registry, so every histogram registered is
    exported by construction — hygiene-tested)."""
    h = _REG.get(name)
    if h is None:
        with _reg_lock:
            h = _REG.get(name)
            if h is None:
                h = _REG[name] = Histogram(name, help_)
    return h


def observe(name: str, ms: float, trace_id: str | None = None) -> None:
    """Record one wall into the named family (the one call every
    instrumented site makes)."""
    if not _enabled:
        return
    histogram(name).record(ms, trace_id)


def acquire_observed(lock, family: str) -> None:
    """Take `lock`; where another thread holds it, time the wait into
    `family`. An uncontended take costs one try-acquire, what `with`
    costs: for a lock taken many times a request that no census entry
    wraps (`lock.wait.tracing`, `lock.wait.config`). The caller
    releases it."""
    if lock.acquire(False):
        return
    t0 = time.perf_counter()
    lock.acquire()
    observe(family, (time.perf_counter() - t0) * 1000.0)


def get(name: str) -> Histogram | None:
    return _REG.get(name)


def all_histograms() -> list:
    with _reg_lock:
        return list(_REG.values())


def rotate_all() -> None:
    for h in all_histograms():
        h.rotate()


def reset_windows() -> None:
    """Drop the windowed samples of every family (cumulative counters
    untouched) — the warmup/measurement boundary reset."""
    for h in all_histograms():
        h.reset_window()


def rotate_due() -> None:
    """Advance the window ring of every histogram whose rotation
    deadline has passed — the health tick's rotation driver.  Recording
    rotates lazily, but an IDLE family would otherwise freeze its last
    windows forever (a sticky SLO verdict after traffic stops)."""
    now = time.monotonic()
    for h in all_histograms():
        with h._lock:
            if now >= h._next_rot:
                h._rotate_locked(now)


def reset() -> None:
    """Drop every family's data (test isolation).  The canonical
    families are re-registered empty: health rules and the exposition
    reference them unconditionally."""
    with _reg_lock:
        _REG.clear()
    for _n, _h in CANONICAL.items():
        histogram(_n, _h)


def prom_name(name: str) -> str:
    """`servlet.serving` -> `yacy_servlet_serving_ms` (the exposition
    family name)."""
    safe = "".join(c if (c.isalnum() or c == "_") else "_" for c in name)
    return f"yacy_{safe}_ms"


# span names that wrap (nearly) the whole request: real walls, but never
# a *stage* verdict — excluded from tail dominance in stage_table
# `interp.wait` is a probe, not a stage: the sampler's lateness (PR 39)
WRAPPER_FAMILIES = frozenset({"switchboard.search", "servlet.serving",
                              "interp.wait"})
# trace-root / segment-root families (they cover their children)
ROOT_PREFIXES = ("servlet.", "peer.", "pipeline.")
# background-workload families (crawl fetches, DHT shipping, per-doc
# indexing, the device arena's growth and writes): real walls, but they
# must never decide a SERVING latency verdict — the trace-ring summary
# they replace only ever saw serving
# traces, and a multi-second crawl fetch would otherwise headline the
# Performance_Trace_p stage table of a node that merely crawls
BACKGROUND_PREFIXES = ("index.", "pipeline.", "crawler.", "crawl.",
                       "dht.", "ingest.", "arena.")


def stage_table(exclude_prefixes: tuple = BACKGROUND_PREFIXES) -> dict:
    """Per-family windowed count/p50/p95 plus the tail-dominant stage —
    the `Performance_Trace_p` summary, now answered from the windowed
    histograms instead of re-walking the trace ring per page load
    (ISSUE 4 satellite).  `exclude_prefixes` drops whole workload
    classes from the table (default: the per-document indexing stages,
    whose walls would skew a search-latency verdict)."""
    out = {}
    for h in all_histograms():
        if any(h.name.startswith(p) for p in exclude_prefixes):
            continue
        counts = h.windowed_counts()
        n = sum(counts)
        if n == 0:
            continue
        out[h.name] = {
            "count": n,
            "p50_ms": round(percentile_from_counts(counts, 0.50), 3),
            "p95_ms": round(percentile_from_counts(counts, 0.95), 3)}
    inner = {k: v for k, v in out.items()
             if k not in WRAPPER_FAMILIES
             and not k.startswith(ROOT_PREFIXES)}
    tail = max(inner, key=lambda k: inner[k]["p95_ms"]) if inner else ""
    return {"stages": out, "tail_dominant_stage": tail}


# canonical families (pre-registered so health rules and the exposition
# never reference a family that does not exist yet — hygiene-tested):
# every hot wall ISSUE 4 names records into one of these
CANONICAL = {
    "servlet.serving": "full servlet dispatch+render wall per request",
    "devstore.batch": "device batcher enqueue→dispatch→result wall",
    "mesh.batch": "mesh batcher enqueue→dispatch→result wall",
    "mesh.collective": "mesh SPMD collective program wall per dispatch",
    "kernel.issue": "host-side async kernel issue wall",
    "kernel.device": "issue to a completer taking the wave: the "
                     "in-flight queue and the completer's wake "
                     "(interp.wake.completer)",
    "kernel.fetch": "blocking device→host result fetch wall",
    "crawler.fetch": "crawler document fetch wall",
    "dht.transfer": "DHT index-transfer RPC wall",
    "index.parsedocument": "indexing pipeline stage 1 wall",
    "index.condensedocument": "indexing pipeline stage 2 wall",
    "index.webstructureanalysis": "indexing pipeline stage 3 wall",
    "index.storedocumentindex": "indexing pipeline stage 4 wall",
    # crawl-to-searchable SLO (ISSUE 13a, ingest/slo.py — its FAMILIES
    # dict mirrors these entries and a hygiene test pins the mirror):
    # write-path latency tiers + the bounded-buffer backpressure wall.
    # "ingest." is a BACKGROUND prefix: freshness walls must never
    # decide a SERVING latency verdict
    "ingest.searchable": "crawl-to-searchable: pipeline entry -> doc "
                         "servable from the RWI RAM buffer",
    "ingest.flushed": "pipeline entry -> RWI flush covering the doc "
                      "returned (immutable/durable run)",
    "ingest.device": "pipeline entry -> run bit-packed onto the device "
                     "tier (serves from placed blocks)",
    "ingest.backpressure": "writer wall blocked in the bounded RWI RAM "
                           "buffer (counted backpressure)",
    # lock-wait observatory (ISSUE 20b, utils/profiling.py): wait+hold
    # walls per instrumented hot lock — one wait/hold pair per entry of
    # profiling.HOT_LOCK_CENSUS (a hygiene test pins the mirror), so
    # the yacy_lock_wait_*/yacy_lock_hold_* series zero-fill before any
    # contention ever happens
    "lock.wait.devstore": "acquisition wait on the devstore store lock",
    "lock.hold.devstore": "hold wall on the devstore store lock",
    "lock.wait.devstore_tune": "acquisition wait on the batcher tune lock",
    "lock.hold.devstore_tune": "hold wall on the batcher tune lock",
    "lock.wait.rwi": "acquisition wait on the RWI store lock",
    "lock.hold.rwi": "hold wall on the RWI store lock",
    "lock.wait.dense_fwd": "acquisition wait on the dense forward-block "
                           "upload lock",
    "lock.hold.dense_fwd": "hold wall on the dense forward-block "
                           "upload lock",
    "lock.wait.mesh_plock": "acquisition wait on the mesh member's "
                            "pending-step lock",
    "lock.hold.mesh_plock": "hold wall on the mesh member's "
                            "pending-step lock",
    "lock.wait.search_cache": "acquisition wait on the search-event "
                              "cache lock",
    "lock.hold.search_cache": "hold wall on the search-event cache lock",
    # contended takes of the two locks a request takes many times, no
    # census entry (histogram.acquire_observed; PR 39): an uncontended
    # take records nothing
    "lock.wait.tracing": "contended acquisition wait on the span "
                         "ring's lock (tracing._record / _register)",
    "lock.wait.config": "contended acquisition wait on Config.get's "
                        "lock",
    # who waits for the interpreter (PR 39). A wake is the time from
    # "runnable" to "running": the producer stamps when it makes an
    # item available, the consumer when it blocks and when it runs,
    # wake = run - max(ready, block); ~0 where the item was there
    # first. Recorded straight into the family, never through a span
    "interp.wait": "stack sampler's lateness past its tick deadline: "
                   "OS wake + wait for the interpreter lock, as any "
                   "thread that asks for it meets it",
    "interp.wake.former": "batch former's wake after a submitter "
                          "enqueued a query (producer: the submitter)",
    "interp.wake.dispatcher": "dispatcher's wake after the former "
                              "handed it a part (producer: the former)",
    "interp.wake.completer": "completer's wake after a dispatcher "
                             "issued a wave (producer: the dispatcher)",
    "interp.wake.request": "submitter's wake after its answer was "
                           "delivered (producer: the dispatcher or "
                           "completer that delivered it)",
    "batcher.starved": "device idle while a query waited in the "
                       "batcher: issue onto an empty device minus the "
                       "later of the device's emptying and the part's "
                       "first submit (a lower bound)",
}

for _name, _help in CANONICAL.items():
    histogram(_name, _help)
