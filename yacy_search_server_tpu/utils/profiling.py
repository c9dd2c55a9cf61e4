"""Whitebox in-process forensics (ISSUE 20, ROADMAP 1c evidence side).

M89/M90 taught the fleet to name WHICH member straggled; this layer
explains what that member was *doing*.  Three instruments, one module:

1. **Sampling profiler** — a single daemon thread walking
   ``sys._current_frames()`` at an adaptive 25–100 Hz, folding each
   thread's Python stack into ``root;...;leaf`` strings aggregated per
   rotating 30 s window (6 retained, the histogram-window cadence).
   Every sample is tagged with the thread's ROLE resolved from the
   named-pool canon below, so "the completer pool is pegged in
   ``fetch_topk``" is one dict read, fleet-wide.

2. **Lock-wait observatory** — :class:`ObservedLock` /
   :class:`ObservedRLock` wrap the hot named locks (the
   ``HOT_LOCK_CENSUS`` below, policed by yacylint's ``raw-hot-lock``)
   and record acquisition wait + hold walls into the canonical
   ``lock.wait.{name}`` / ``lock.hold.{name}`` histogram families.  A
   hold exceeding the family's cached window p95 captures the HOLDER's
   stack — the postmortem reads who held the lock, not just that it was
   held.  The wrapper is also the single measurement point for the
   tail classifier's ``tail.lock_wait`` marker spans (it calls
   :func:`tailattr.note_lock_wait`), replacing the hand-rolled timing
   pairs that used to sit at individual ``with`` sites.

3. **Triggered deep capture** — tail verdicts (``lock_wait``,
   ``queue_wait``, ``collective_straggler``) and health ok→critical
   edges arm a bounded 100 Hz capture window; its top folded stacks +
   the lock table embed in flight-recorder incidents exactly like M89
   embeds the cause histogram.

The whole module follows the tracing discipline: with
:func:`set_enabled` off, the lock fast path is ONE extra attribute
read and the sampler parks — zero allocation, nothing recorded.
:func:`snapshot` is the wire form ``do_profsnap`` ships so a convicted
member's own profile can ride its conviction incident.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from collections import deque

from . import histogram, tailattr, tracing

# -- thread-role canon --------------------------------------------------------

# the named-pool census: every long-lived pool/loop thread the runtime
# spawns maps to one role, so folded stacks and the fleet digest speak
# roles, not thread ids.  ZERO-FILLED in /metrics and indexed into the
# digest (like tailattr.CAUSES), so the tuple order is a wire contract:
# append only.
ROLES = ("dispatcher", "completer", "flusher", "member-runloop",
         "health-tick", "search-feeder", "sampler", "other")

# thread-name prefix -> role (first match wins)
_ROLE_PATTERNS = (
    ("devstore-batcher", "dispatcher"),
    ("meshstore-batcher", "dispatcher"),
    ("devstore-completer", "completer"),
    ("meshstore-completer", "completer"),
    ("devstore-former", "flusher"),
    ("devstore-rebuild", "flusher"),
    ("devstore-prewarm", "flusher"),
    ("meshstore-rebuild", "flusher"),
    ("mesh-runloop", "member-runloop"),
    ("15_health", "health-tick"),
    ("federated-search", "search-feeder"),
    ("prof-sampler", "sampler"),
)


def thread_role(name: str) -> str:
    for prefix, role in _ROLE_PATTERNS:
        if name.startswith(prefix):
            return role
    return "other"


# -- instrumented-lock census -------------------------------------------------

# "file::Class::attr" -> canonical lock name.  THE census yacylint's
# raw-hot-lock checker polices: each entry must exist in the named
# class and be constructed as ObservedLock/ObservedRLock (or carry a
# rawlock-ok exemption), and an entry matching nothing is a finding —
# the census cannot rot.
HOT_LOCK_CENSUS = {
    "yacy_search_server_tpu/index/devstore.py::DeviceSegmentStore::_lock":
        "devstore",
    "yacy_search_server_tpu/index/devstore.py::_QueryBatcher::_tune_lock":
        "devstore_tune",
    "yacy_search_server_tpu/index/rwi.py::RWIIndex::_lock": "rwi",
    "yacy_search_server_tpu/index/dense.py::DenseVectorStore::_fwd_lock":
        "dense_fwd",
    "yacy_search_server_tpu/parallel/distributed.py::MeshMember::_plock":
        "mesh_plock",
    "yacy_search_server_tpu/search/searchevent.py::SearchEventCache::_lock":
        "search_cache",
}

# the canonical lock names, in census order (zero-fill domain for the
# per-lock metrics; mirrored by the lock.wait/lock.hold families in
# histogram.CANONICAL — hygiene-tested)
LOCK_NAMES = tuple(sorted(set(HOT_LOCK_CENSUS.values())))

# a hold always captures the holder stack past this floor even before
# the first window rotation primes the p95 cache
HOLDER_MIN_MS = 1.0

# recording floor for the observatory's histogram families: below 10 us
# a wait/hold is the lock's own bookkeeping (an uncontended acquire is
# ~0.3 us), not contention evidence — skipping it keeps the enabled
# fast path at ~4 clock reads per acquire/release pair instead of two
# full Histogram.record calls, which is what holds --prof-overhead
# under its 2% budget on lock-heavy serving
RECORD_MIN_MS = 0.01

_enabled = True
_lock = threading.Lock()          # module state (windows, capture, registry)
_LOCKS: dict[str, "ObservedLock"] = {}

# counters (monotonic; /metrics + snapshot read them)
samples_total = 0
capture_windows_total = 0
holder_captures_total = 0


def set_enabled(on: bool) -> None:
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


def configure(cfg) -> None:
    """Read the prof.* knobs once at switchboard construction (the
    tailattr.configure model) and start the always-on sampler."""
    set_enabled(cfg.get_bool("prof.enabled", True))
    s = ensure_sampler()
    s.base_hz = cfg.get_float("prof.sampleHz", s.base_hz)
    s.burst_hz = cfg.get_float("prof.burstHz", s.burst_hz)


# -- folded stacks ------------------------------------------------------------

_MAX_DEPTH = 24          # leaf-most frames kept per stack
_MAX_STACKS = 256        # distinct folded stacks per window
_OWN_FILE = __file__


# code object -> "module:function" label; code objects are effectively
# permanent, so caching on them (which keeps them alive) trades a few
# KB for skipping basename+format work on every frame of every sample
_label_cache: dict = {}


def _fold(frame, leaf_line: bool = True) -> str:
    """``root;...;leaf`` with ``module:function`` frames (the leaf also
    carries its line — the straggling SITE, not just the function)."""
    parts: list[str] = []
    f = frame
    cache = _label_cache
    while f is not None and len(parts) < _MAX_DEPTH:
        code = f.f_code
        if code.co_filename != _OWN_FILE:
            lbl = cache.get(code)
            if lbl is None:
                mod = os.path.basename(code.co_filename)
                if mod.endswith(".py"):
                    mod = mod[:-3]
                lbl = f"{mod}:{code.co_name}"
                if len(cache) < 4096:
                    cache[code] = lbl
            if leaf_line and not parts:
                parts.append(f"{lbl}:{f.f_lineno}")
            else:
                parts.append(lbl)
        f = f.f_back
    return ";".join(reversed(parts))


class _Window:
    __slots__ = ("start", "samples", "stacks", "roles", "dropped")

    def __init__(self, start: float):
        self.start = start
        self.samples = 0
        # (role, folded) -> count
        self.stacks: dict[tuple[str, str], int] = {}
        self.roles: dict[str, int] = {}
        self.dropped = 0


class SamplingProfiler:
    """The always-on sampler: one daemon thread, adaptive cadence —
    ``base_hz`` (deployed: 25) in steady state, ``burst_hz`` (100)
    while a triggered capture window is armed."""

    WINDOW_S = 30.0
    RETAIN = 6
    CAPTURE_S = 2.0
    CAPTURE_COOLDOWN_S = 5.0

    def __init__(self, base_hz: float = 25.0, burst_hz: float = 100.0):
        self.base_hz = base_hz
        self.burst_hz = burst_hz
        self._stop = threading.Event()
        self._cur = _Window(time.monotonic())
        self._ring: deque[_Window] = deque(maxlen=self.RETAIN)
        self._capture: dict | None = None      # armed capture window
        self._last_capture_end = 0.0
        # thread NAME -> role (never ident-keyed: the OS recycles
        # idents, so a dead completer's ident can come back as a
        # batcher and a stale ident cache would mislabel it forever);
        # spares the prefix matching, while the ident -> Thread hop
        # rides threading's own _active registry instead of an
        # enumerate() list build per sample
        self._role_cache: dict[str, str] = {}
        # ident -> (id(leaf frame), lineno, folded): most threads are
        # PARKED (queue.get, selectors.select) and their leaf frame
        # object + line do not move between samples — reuse the folded
        # string instead of re-walking the whole stack; any execution
        # progress changes the lineno (or the frame object) and misses
        self._stack_memo: dict[int, tuple] = {}
        self.last_capture: dict | None = None  # finalized, wire-shaped
        self._thread = threading.Thread(
            target=self._run, name="prof-sampler", daemon=True)
        self._thread.start()

    # -- the sampling loop ---------------------------------------------------

    def _run(self) -> None:
        while True:
            cap = self._capture is not None
            hz = self.burst_hz if cap else self.base_hz
            if self._stop.wait(1.0 / max(1.0, hz)):
                return
            if _enabled:
                try:
                    # the tick's own busy wall: it walks every thread's
                    # stack under the interpreter lock, so this is time
                    # taken from the serving threads
                    with tracing.timed("runtime.sampler_tick"):
                        self._sample()
                except Exception:   # lint: broad-except-ok(the sampler
                    # must survive any racing interpreter state — a dead
                    # sampler silently ends all whitebox evidence)
                    pass

    def _sample(self) -> None:
        global samples_total, capture_windows_total
        now = time.monotonic()
        me = threading.get_ident()
        frames = sys._current_frames()
        rc = self._role_cache
        memo = self._stack_memo
        active = getattr(threading, "_active", None)
        names = None if active is not None else \
            {t.ident: t.name for t in threading.enumerate()}
        with _lock:
            if now - self._cur.start >= self.WINDOW_S:
                self._ring.append(self._cur)
                self._cur = _Window(now)
            cap = self._capture
            if cap is not None and now >= cap["until"]:
                self._finalize_capture_locked(cap)
                cap = None
            for ident, frame in frames.items():
                if ident == me:
                    continue
                if active is not None:
                    th = active.get(ident)
                    name = th.name if th is not None else ""
                else:
                    name = names.get(ident, "")
                role = rc.get(name)
                if role is None:
                    role = thread_role(name)
                    if len(rc) < 512:
                        rc[name] = role
                fid = id(frame)
                lineno = frame.f_lineno
                ent = memo.get(ident)
                if ent is not None and ent[0] == fid \
                        and ent[1] == lineno:
                    folded = ent[2]
                else:
                    folded = _fold(frame)
                    if len(memo) < 1024:
                        memo[ident] = (fid, lineno, folded)
                    else:
                        memo.clear()
                if not folded:
                    continue
                w = self._cur
                w.samples += 1
                w.roles[role] = w.roles.get(role, 0) + 1
                key = (role, folded)
                if key in w.stacks or len(w.stacks) < _MAX_STACKS:
                    w.stacks[key] = w.stacks.get(key, 0) + 1
                else:
                    w.dropped += 1
                if cap is not None:
                    cap["samples"] += 1
                    cap["stacks"][key] = cap["stacks"].get(key, 0) + 1
                samples_total += 1
        del frames

    def _finalize_capture_locked(self, cap: dict) -> None:
        global capture_windows_total
        capture_windows_total += 1
        self.last_capture = {
            "reason": cap["reason"],
            "ts": cap["ts"],
            "samples": cap["samples"],
            "hz": self.burst_hz,
            "window_s": self.CAPTURE_S,
            "stacks": _top_stacks(cap["stacks"], 10),
        }
        self._capture = None
        self._last_capture_end = time.monotonic()

    # -- triggered deep capture ---------------------------------------------

    def trigger(self, reason: str) -> bool:
        """Arm one bounded high-rate capture window (no-op while one is
        armed or cooling down — a verdict storm must not pin the
        sampler at burst rate)."""
        if not _enabled:
            return False
        now = time.monotonic()
        with _lock:
            if self._capture is not None or \
                    now - self._last_capture_end < self.CAPTURE_COOLDOWN_S:
                return False
            self._capture = {"reason": reason, "ts": round(time.time(), 3),
                             "until": now + self.CAPTURE_S,
                             "samples": 0, "stacks": {}}
        return True

    # -- reading -------------------------------------------------------------

    def stacks(self, n: int = 12) -> list[dict]:
        """Top-N folded stacks aggregated over the retained windows."""
        agg: dict[tuple[str, str], int] = {}
        with _lock:
            for w in list(self._ring) + [self._cur]:
                for key, c in w.stacks.items():
                    agg[key] = agg.get(key, 0) + c
        return _top_stacks(agg, n)

    def role_samples(self) -> dict[str, int]:
        """samples per role over the retained windows, zero-filled over
        the ROLES canon (the /metrics + digest domain)."""
        out = {r: 0 for r in ROLES}
        with _lock:
            for w in list(self._ring) + [self._cur]:
                for role, c in w.roles.items():
                    out[role] = out.get(role, 0) + c
        return out

    def reset(self) -> None:
        with _lock:
            self._ring.clear()
            self._cur = _Window(time.monotonic())
            self._capture = None
            self._last_capture_end = 0.0
            self.last_capture = None

    def stop(self) -> None:
        self._stop.set()


def _top_stacks(agg: dict, n: int) -> list[dict]:
    top = sorted(agg.items(), key=lambda kv: (-kv[1], kv[0]))[:max(0, n)]
    return [{"role": role, "stack": folded, "count": c}
            for (role, folded), c in top]


_SAMPLER: SamplingProfiler | None = None


def ensure_sampler() -> SamplingProfiler:
    """Start (once) and return the process-global sampler."""
    global _SAMPLER
    with _lock:
        if _SAMPLER is None:
            _SAMPLER = SamplingProfiler()
    return _SAMPLER


def sampler() -> SamplingProfiler | None:
    return _SAMPLER


def trigger(reason: str) -> bool:
    """Arm a deep-capture window on the running sampler (no-op when the
    sampler was never started or profiling is disabled — callers are
    hot paths and must stay zero-cost)."""
    s = _SAMPLER
    return s.trigger(reason) if s is not None and _enabled else False


# -- lock-wait observatory ----------------------------------------------------


class ObservedLock:
    """A named ``threading.Lock`` recording acquisition-wait and hold
    walls into the canonical ``lock.wait.{name}`` / ``lock.hold.{name}``
    families (non-trivial walls only — the ``RECORD_MIN_MS`` floor
    keeps uncontended bookkeeping out of the histograms AND off the hot
    path), emitting the tail classifier's lock-wait marker span on
    contended acquires (the ONE measurement point), and capturing the
    holder's stack when a hold exceeds the family's cached window p95.
    Disabled fast path: one module-flag read, straight delegation."""

    _reentrant = False

    def __init__(self, name: str):
        self.name = name
        self._lk = self._make_inner()
        self._wait_fam = "lock.wait." + name
        self._hold_fam = "lock.hold." + name
        self._t_hold = 0.0
        self.contended_total = 0
        self.holder_stacks: deque = deque(maxlen=4)
        with _lock:
            _LOCKS[name] = self

    def _make_inner(self):
        return threading.Lock()

    # -- lock protocol -------------------------------------------------------

    def acquire(self, blocking: bool = True, timeout: float = -1):
        if not _enabled:
            return self._lk.acquire(blocking, timeout)
        t0 = time.perf_counter()
        got = self._lk.acquire(blocking, timeout)
        wait_ms = (time.perf_counter() - t0) * 1000.0
        if wait_ms >= RECORD_MIN_MS:
            # unified verdict labels: the marker span the tail
            # classifier sums into lock_ms rides the same measurement
            tailattr.note_lock_wait(self.name, t0)
            histogram.observe(self._wait_fam, wait_ms)
            if wait_ms >= tailattr.LOCK_WAIT_MIN_MS:
                self.contended_total += 1
        if got:
            self._begin_hold()
        return got

    def release(self) -> None:
        if _enabled:
            self._end_hold()
        self._lk.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def locked(self) -> bool:
        return self._lk.locked()

    # -- hold accounting (called only by the holding thread) -----------------

    def _begin_hold(self) -> None:
        self._t_hold = time.perf_counter()

    def _end_hold(self) -> None:
        global holder_captures_total
        t0 = self._t_hold
        if not t0:
            return
        self._t_hold = 0.0
        hold_ms = (time.perf_counter() - t0) * 1000.0
        if hold_ms < RECORD_MIN_MS:
            return
        histogram.observe(self._hold_fam, hold_ms)
        h = histogram.get(self._hold_fam)
        gate = max(h.p95_cache if h is not None else 0.0, HOLDER_MIN_MS)
        if hold_ms >= gate:
            # over-threshold hold: capture the HOLDER's stack (we still
            # hold the lock — the release site is exactly the evidence)
            try:
                stack = _fold(sys._getframe())
            except Exception:   # lint: broad-except-ok(forensics must
                # never break the release path of a hot lock)
                return
            holder_captures_total += 1
            self.holder_stacks.append({
                "ts": round(time.time(), 3),
                "hold_ms": round(hold_ms, 3),
                "stack": stack})


class ObservedRLock(ObservedLock):
    """Reentrant variant: hold walls span the OUTERMOST acquire/release
    pair, and the ``_release_save``/``_acquire_restore``/``_is_owned``
    protocol is forwarded so ``threading.Condition(lock)`` keeps
    working (rwi wraps its store lock in a capacity Condition)."""

    _reentrant = True

    def __init__(self, name: str):
        super().__init__(name)
        self._depth = 0

    def _make_inner(self):
        return threading.RLock()

    def _begin_hold(self) -> None:
        # only the owning thread runs this (the lock is held)
        if self._depth == 0:
            self._t_hold = time.perf_counter()
        self._depth += 1

    def _end_hold(self) -> None:
        if self._depth > 0:
            self._depth -= 1
            if self._depth == 0:
                super()._end_hold()

    def locked(self) -> bool:
        # RLock has no .locked() before 3.12; owned-by-me is the useful
        # question for a reentrant lock anyway
        return self._lk._is_owned()

    # Condition(lock) protocol: wait() drops ALL recursion levels via
    # _release_save and reacquires them via _acquire_restore — hold
    # accounting must end/restart with them or a cond.wait would count
    # as a giant hold
    def _is_owned(self):
        return self._lk._is_owned()

    def _release_save(self):
        depth, self._depth = self._depth, 0
        t0, self._t_hold = self._t_hold, 0.0
        if _enabled and t0:
            hold_ms = (time.perf_counter() - t0) * 1000.0
            if hold_ms >= RECORD_MIN_MS:
                histogram.observe(self._hold_fam, hold_ms)
        return (self._lk._release_save(), depth)

    def _acquire_restore(self, state):
        inner, depth = state
        self._lk._acquire_restore(inner)
        self._depth = depth
        self._t_hold = time.perf_counter()


def observed_locks() -> list["ObservedLock"]:
    with _lock:
        return [v for _k, v in sorted(_LOCKS.items())]


def lock_table() -> list[dict]:
    """Per-lock wait/hold quantiles + contention + recent over-p95
    holder stacks — the table Performance_Prof_p and incident bodies
    render."""
    out = []
    for lk in observed_locks():
        row = {"name": lk.name, "contended_total": lk.contended_total,
               "holder_stacks": list(lk.holder_stacks)}
        for kind, fam in (("wait", lk._wait_fam), ("hold", lk._hold_fam)):
            h = histogram.get(fam)
            counts = h.windowed_counts() if h is not None else []
            n = sum(counts)
            row[kind] = {
                "count": n,
                "p50_ms": round(histogram.percentile_from_counts(
                    counts, 0.50), 3) if n else 0.0,
                "p95_ms": round(histogram.percentile_from_counts(
                    counts, 0.95), 3) if n else 0.0}
        out.append(row)
    return out


# -- wire form ----------------------------------------------------------------


def stats() -> dict:
    """The /metrics counters (zero-filled roles via role_samples)."""
    s = _SAMPLER
    return {
        "enabled": _enabled,
        "sampler_running": s is not None,
        "sampler_hz": (s.burst_hz if s is not None and
                       s._capture is not None else
                       s.base_hz if s is not None else 0.0),
        "samples_total": samples_total,
        "capture_windows_total": capture_windows_total,
        "holder_captures_total": holder_captures_total,
    }


def snapshot(top_n: int = 12) -> dict:
    """The whole whitebox picture in one wire-safe dict: what
    ``do_profsnap`` ships, what a conviction incident embeds, what
    Performance_Prof_p renders."""
    s = _SAMPLER
    return {
        "ts": round(time.time(), 3),
        "pid": os.getpid(),
        **stats(),
        "window_s": SamplingProfiler.WINDOW_S,
        "stacks": s.stacks(top_n) if s is not None else [],
        "roles": s.role_samples() if s is not None
        else {r: 0 for r in ROLES},
        "locks": lock_table(),
        "last_capture": s.last_capture if s is not None else None,
    }


def report(top_n: int = 8) -> dict:
    """The flight-recorder embed (ISSUE 20c): compact — top folded
    stacks + lock table + the last deep capture, no role zero-fill."""
    s = _SAMPLER
    return {
        "stacks": s.stacks(top_n) if s is not None else [],
        "locks": lock_table(),
        "last_capture": s.last_capture if s is not None else None,
    }


def top_role_index() -> int:
    """The fleet-digest compact form (the tailattr.CAUSES-index model):
    index into ROLES of the role with the most samples over the
    retained windows; 'other' when the sampler never ran."""
    s = _SAMPLER
    if s is None:
        return ROLES.index("other")
    roles = s.role_samples()
    top = max(ROLES, key=lambda r: (roles.get(r, 0), r != "other"))
    return ROLES.index(top)


def decode_role(i) -> str:
    """Tolerant decode of a digest's role index (version skew reads as
    'other' — which is zero-filled, so the series always resolves)."""
    try:
        i = int(i)
    except (TypeError, ValueError):
        i = -1
    return ROLES[i] if 0 <= i < len(ROLES) else "other"


def reset() -> None:
    """Test isolation: drop windows, captures and counters (the
    sampler thread itself survives — it is process-global)."""
    global samples_total, capture_windows_total, holder_captures_total
    s = _SAMPLER
    if s is not None:
        s.reset()
    with _lock:
        samples_total = 0
        capture_windows_total = 0
        holder_captures_total = 0
        for lk in _LOCKS.values():
            lk.holder_stacks.clear()
            lk.contended_total = 0
