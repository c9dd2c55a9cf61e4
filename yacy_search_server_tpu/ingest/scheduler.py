"""Merge/promotion scheduler — the write path's actuator body (ISSUE 13c).

Compactions (RWI run merges) and tier promotions are the write path's
two heavy background moves: a full merge rewrites the run set, and a
promotion ships packed blocks over the same host-to-device link the
query waves ride.  Until now their timing was ad hoc (the cleanup busy thread
merged whenever a device join flagged a hot term; promotions fired on
every tier miss) — under a serving burn they pile exactly the work the
node can least afford.

This scheduler closes that gap with the M83 actuator discipline: the
``merge_scheduler`` actuator (utils/actuator.py) flips it to DEFERRED
while the ``slo_serving_p95`` burn-rate rule is critical and back after
the engine's hysteresis, emitting a breadcrumb per transition.  While
deferred:

- ``request_merge`` (the cleanup job's merge path) records the ask and
  returns without merging — the SMALLEST ``max_runs`` asked for wins,
  so the catch-up performs the most aggressive compaction requested;
- the devstore's ``_submit_promote`` parks promotions in a deferred set
  (counted; the triggering queries host-serve, which they were already
  doing — a miss never waits on a promotion).

``catch_up()`` (the actuator's recovery edge) runs the pending merge
and resubmits every parked promotion.  Every deferral and catch-up is
counted and exported (``yacy_ingest_total{counter=...}`` +
``yacy_ingest_deferred``), so the no-dead-actuators hygiene gate holds
and a postmortem reads the deferral next to the burn that caused it.

Jax-free by contract (see the package docstring).
"""

from __future__ import annotations

import logging
import threading
import time

log = logging.getLogger("yacy.ingest")


class MergeScheduler:
    """Owns the defer/catch-up state for ONE node's write path.  All
    decisions are taken by the ``merge_scheduler`` actuator on the
    health tick; the write path only ever asks ``defer_promotions()``
    / ``request_merge()`` — one lock-free-ish read on the hot path."""

    def __init__(self, sb):
        self.sb = sb
        self._lock = threading.Lock()
        self.deferred = False
        self.defer_since = 0.0
        # the deferred merge ask: None, or the smallest max_runs asked
        self._pending_merge: int | None = None
        self.merge_deferrals = 0
        self.promote_deferrals = 0     # bumped by devstore._submit_promote
        self.merge_catch_ups = 0
        self.catch_up_merges = 0
        self.catch_up_promotions = 0

    # -- actuation surface (merge_scheduler actuator) ------------------------

    def set_deferred(self, on: bool) -> None:
        with self._lock:
            self.deferred = bool(on)
            self.defer_since = time.monotonic() if on else 0.0

    def defer_promotions(self) -> bool:
        """The devstore's gate: park promotions instead of submitting
        (the hot path reads one attribute; no lock)."""
        return self.deferred

    def note_promote_deferred(self) -> None:
        with self._lock:
            self.promote_deferrals += 1

    def catch_up(self) -> dict:
        """The recovery edge: run the pending merge (smallest-max_runs
        ask wins — the most aggressive compaction requested while
        deferred) and resubmit every parked promotion.  Returns the
        evidence dict the actuator breadcrumb carries."""
        with self._lock:
            pending = self._pending_merge
            self._pending_merge = None
        merged = False
        if pending is not None:
            try:
                merged = bool(self.sb.index.rwi.merge_runs(
                    max_runs=pending))
            except Exception:
                log.warning("catch-up RWI merge failed", exc_info=True)
        ds = getattr(self.sb.index, "devstore", None)
        resumed = 0
        fn = getattr(ds, "resume_promotions", None)
        if fn is not None:
            try:
                resumed = fn()
            except Exception:
                log.warning("catch-up promotion resume failed",
                            exc_info=True)
        with self._lock:
            self.merge_catch_ups += 1
            self.catch_up_merges += int(merged)
            self.catch_up_promotions += resumed
        return {"pending_merge_ran": merged,
                "pending_max_runs": pending,
                "promotions_resumed": resumed}

    # -- write-path surface --------------------------------------------------

    def request_merge(self, max_runs: int = 8) -> bool:
        """The cleanup job's merge entry: defer (counted, smallest ask
        retained) while the serving SLO burns, else merge now.
        Returns True when a merge actually ran."""
        with self._lock:
            if self.deferred:
                self.merge_deferrals += 1
                self._pending_merge = max_runs \
                    if self._pending_merge is None \
                    else min(self._pending_merge, max_runs)
                return False
        return bool(self.sb.index.rwi.merge_runs(max_runs=max_runs))

    # -- observability -------------------------------------------------------

    def pending_merge(self) -> int | None:
        with self._lock:
            return self._pending_merge

    def counters(self) -> dict:
        ds = getattr(self.sb.index, "devstore", None)
        with self._lock:
            return {
                "merge_deferrals": self.merge_deferrals,
                "promote_deferrals": self.promote_deferrals,
                "merge_catch_ups": self.merge_catch_ups,
                "catch_up_merges": self.catch_up_merges,
                "catch_up_promotions": self.catch_up_promotions,
                "deferred": int(self.deferred),
                "pending_merge": int(self._pending_merge is not None),
                "deferred_promotions_parked":
                    len(getattr(ds, "_deferred_promotes", ()) or ()),
            }
