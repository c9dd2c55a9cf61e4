"""Roofline profiler — measured kernel walls paired with cost models.

The measurement half of the silicon accounting (ops/roofline.py is the
analytical half): serving paths report (kernel, wall, shape) here; the
profiler converts each report into achieved FLOP/s, achieved GB/s and a
%-of-peak number against the device's declared ceiling, and keeps bounded
per-kernel series for the Performance_Roofline_p servlet. The walls are
HOST walls around a dispatch, so `util_pct` is a cost model over a host
clock: the chip's own roofline share is read from the device trace
(`join_roofline` / `join_sm_roofline`, PERF.md section 3).

Design constraints:

- **Hot-path cheap**: one `record()` is a cost-model closure call (a few
  float ops) + a deque append under a lock — the profiler-overhead test
  pins < 1% added latency on a 1k-query microbench. No jax, no syscalls.
- **Pairs with the event tracker**: wall times the serving path already
  measures (devstore's per-dispatch kernel walls, eventtracker
  StageTimer stages) feed `record()` directly; nothing is re-timed.
- **Per-query attribution**: a batched dispatch serving `queries` slots
  records the batch once for kernel aggregates AND per-query utilization
  samples (each query's share of the dispatch), which is what
  `query_util()`'s `util_pct` p50/p95 summarizes.
"""

from __future__ import annotations

import threading
import time
from collections import deque

from ..ops import roofline
from ..ops.roofline import Cost, DevicePeak, RooflinePoint, roofline_point
from . import tracing


class RooflineProfiler:
    """Bounded per-kernel roofline series over measured walls."""

    def __init__(self, peak: DevicePeak | None = None, maxlen: int = 4096):
        self._peak = peak
        self._lock = threading.Lock()
        self._series: dict[str, deque] = {}   # kernel -> (wall_s, Cost)
        self._query_util: deque = deque(maxlen=20_000)  # (util, bound)
        self._maxlen = maxlen
        # serving shapes are highly repetitive (same bs/tile/k dispatch
        # after dispatch): memoizing the cost closure keeps record() at
        # ~1-2 µs — the <1%-overhead contract on a sub-ms query path
        self._cost_memo: dict = {}
        self.enabled = True

    @property
    def peak(self) -> DevicePeak:
        if self._peak is None:
            self._peak = roofline.device_peak()
        return self._peak

    # -- recording -----------------------------------------------------------

    def record(self, kernel: str, wall_s: float, queries: int = 0,
               **shape) -> None:
        """One measured kernel execution. `shape` feeds the kernel's cost
        model; `queries` > 0 additionally files per-query utilization
        samples (each query in the batch experienced this dispatch)."""
        if not self.enabled:
            return
        # tracing bridge: a kernel wall measured under an active trace
        # becomes a child span — nothing is re-timed (solo dispatches run
        # on the query's own thread; batched dispatches have no trace
        # context here and emit theirs from the submitter instead).
        # Guarded here so the untraced hot path pays one contextvar
        # read, not a name allocation (record() is pinned < 10 µs)
        if tracing.current() is not None:
            tracing.emit(f"kernel.{kernel}", wall_s * 1000.0,
                         queries=queries)
        # insertion order is stable per call site, so the unsorted item
        # tuple memoizes just as well (worst case: one extra entry per
        # distinct kwarg order)
        key = (kernel, tuple(shape.items()))
        c = self._cost_memo.get(key)
        if c is None:
            try:
                c = roofline.cost(kernel, **shape)
            except (KeyError, TypeError):
                return  # unregistered kernel/shape must never hurt serving
            if len(self._cost_memo) > 4096:   # unbounded shapes can't leak
                self._cost_memo.clear()
            self._cost_memo[key] = c
        peak = self._peak
        if peak is None:
            peak = self.peak
        with self._lock:
            d = self._series.get(kernel)
            if d is None:
                d = self._series[kernel] = deque(maxlen=self._maxlen)
            d.append((wall_s, c))
            if queries > 0:
                # inline roofline_point: this is the per-query hot path
                w = wall_s if wall_s > 1e-9 else 1e-9
                if c.flops * peak.bytes_per_s < c.bytes * peak.flops_per_s:
                    util = 100.0 * c.bytes / w / peak.bytes_per_s
                    bound = "memory"
                else:
                    util = 100.0 * c.flops / w / peak.flops_per_s
                    bound = "compute"
                self._query_util.extend([(util, bound)] * queries)

    def time(self, kernel: str, queries: int = 0, **shape):
        """Context manager measuring a block's wall into `record`."""
        return _Timed(self, kernel, queries, shape)

    # -- reading -------------------------------------------------------------

    # one nearest-rank convention across the observability layer
    _pctl = staticmethod(tracing._pctl)

    def query_util(self) -> dict:
        """Per-query utilization summary (Performance_Roofline_p)."""
        with self._lock:
            samples = list(self._query_util)
        if not samples:
            return {"util_pct_p50": 0.0, "util_pct_p95": 0.0, "bound": ""}
        utils = sorted(u for u, _ in samples)
        mem = sum(1 for _, b in samples if b == "memory")
        return {
            "util_pct_p50": round(self._pctl(utils, 0.50), 3),
            "util_pct_p95": round(self._pctl(utils, 0.95), 3),
            "bound": "memory" if 2 * mem >= len(samples) else "compute",
        }

    def snapshot(self) -> list[RooflinePoint]:
        """One aggregate roofline point per kernel (totals over the
        retained window: total flops/bytes over total wall — the
        throughput view, robust to per-dispatch noise)."""
        with self._lock:
            series = {k: list(d) for k, d in self._series.items()}
        points = []
        for kernel in sorted(series):
            rows = series[kernel]
            wall = sum(w for w, _ in rows)
            fl = sum(c.flops for _, c in rows)
            by = sum(c.bytes for _, c in rows)
            points.append(roofline_point(
                kernel, Cost(fl, by), wall, self.peak))
        return points

    def clear(self) -> None:
        with self._lock:
            self._series.clear()
            self._query_util.clear()


class _Timed:
    __slots__ = ("_p", "_kernel", "_queries", "_shape", "_t0")

    def __init__(self, profiler, kernel, queries, shape):
        self._p = profiler
        self._kernel = kernel
        self._queries = queries
        self._shape = shape

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._p.record(self._kernel, time.perf_counter() - self._t0,
                       self._queries, **self._shape)
        return False


# the process-wide profiler every serving path reports into (mirrors the
# eventtracker's module-global series)
PROFILER = RooflineProfiler()
