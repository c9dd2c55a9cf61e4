"""Per-stage event tracking — the tracing surface of the framework.

Capability equivalent of the reference's EventTracker (reference:
source/net/yacy/search/EventTracker.java:41): bounded in-memory time-series
per event class; every pipeline/search stage reports (label, count,
duration) and dashboards render them. Kept deliberately cheap: a deque per
class, no locks on the hot path beyond deque's own thread safety.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass
from enum import Enum

from . import tracing


class EClass(Enum):
    SEARCH = "search"
    WORDCACHE = "wordcache"
    MEMORY = "memory"
    PPM = "ppm"
    INDEX = "index"
    DHT = "dht"
    PEERPING = "peerping"
    CRAWL = "crawl"


@dataclass(frozen=True)
class Event:
    ts: float
    label: str
    count: int
    duration_ms: float


_MAX_EVENTS = 4096
_series: dict[EClass, deque] = {c: deque(maxlen=_MAX_EVENTS) for c in EClass}
# cumulative (events, items, duration_ms) per (class, label): the
# monotonic counters /metrics exposes — the bounded deques above are a
# WINDOW, which a Prometheus counter must never be scraped from.
# Locked: += on a shared cell is a read-modify-write, and a Prometheus
# COUNTER that loses increments under thread interleaving is broken by
# contract (update() runs per stage, not per row — the lock is cold)
import threading as _threading

_totals: dict[tuple[EClass, str], list] = {}
_totals_lock = _threading.Lock()


def update(eclass: EClass, label: str, count: int = 0, duration_ms: float = 0.0) -> None:
    _series[eclass].append(Event(time.time(), label, count, duration_ms))
    with _totals_lock:
        tot = _totals.get((eclass, label))
        if tot is None:
            _totals[(eclass, label)] = [1, count, duration_ms]
        else:
            tot[0] += 1
            tot[1] += count
            tot[2] += duration_ms


def events(eclass: EClass) -> list[Event]:
    return list(_series[eclass])


def totals() -> dict[tuple[EClass, str], tuple[int, int, float]]:
    """Cumulative (events, items, duration_ms) per series since process
    start (the /metrics exposition surface)."""
    with _totals_lock:
        return {k: (v[0], v[1], v[2]) for k, v in _totals.items()}


def clear(eclass: EClass | None = None) -> None:
    if eclass is None:
        for d in _series.values():
            d.clear()
    else:
        _series[eclass].clear()


class StageTimer:
    """Context manager reporting one stage's wall time on exit.

    Doubles as the eventtracker→tracing bridge: the stage is ALSO a
    `tracing.timed` wall named ``<class>.<label>`` — a span (and, while
    a profiler session records, an annotation) when a trace is active
    on the calling context, and either way one observation of the
    windowed family of that name, so every existing StageTimer site
    (search stages, pipeline stages, crawl stages) joins the trace
    waterfall without a second timing call and the per-stage p50/p95 on
    `/metrics` covers the whole workload, not just the traced slice."""

    def __init__(self, eclass: EClass, label: str, count: int = 0):
        self.eclass, self.label, self.count = eclass, label, count

    def __enter__(self):
        self._span = tracing.timed(
            f"{self.eclass.value}.{self.label.lower()}")
        self._span.__enter__()
        self._t0 = time.monotonic()
        return self

    def set(self, **attrs) -> None:
        """Attributes for the stage's span, known only inside it."""
        self._span.set(**attrs)

    def __exit__(self, *exc):
        ms = (time.monotonic() - self._t0) * 1000.0
        update(self.eclass, self.label, self.count, ms)
        self._span.__exit__(*exc)
        return False
