"""Streaming ingest — the write path as a first-class subsystem (ISSUE 13).

Every headline before this package measured a FROZEN index; the paper's system is a crawler-indexer first: every
node crawls, parses, flushes, merges and tier-promotes *while* serving.
This package gives that write path the same production discipline the
read path earned over rounds 6–16:

- :mod:`ingest.slo` — the **crawl-to-searchable SLO**: documents are
  stamped at pipeline entry (``Switchboard.to_indexer``), the stamp
  rides the IndexingEntry through parse → store → RWI flush → device
  tier pack, and time-to-first-serve lands in its own histogram
  families (``ingest.searchable`` / ``ingest.flushed`` /
  ``ingest.device``) with an ``ingest_slo_searchable`` health rule in
  the M79 engine.  The bounded RAM buffer's blocking backpressure wall
  (``ingest.backpressure``) is counted here too, so a stalled write
  path is attributable, never silent.
- :mod:`ingest.devbuild` — **device-side index build**: the vmapped
  ``_pack_block_batch_kernel`` bit-packs whole runs of posting blocks
  in one dispatch per pow2 row bucket, bit-identical to the host
  ``ops/packed.pack_block`` (parity-pinned), with a registered roofline
  cost model like every kernel family — fresh runs land pre-packed and
  the flush/merge pack stall becomes device work.
- :mod:`ingest.scheduler` — the **merge/promotion scheduler**, actuated
  by the M83 ``merge_scheduler`` actuator: compactions and tier
  promotions DEFER while the serving SLO burns and CATCH UP when the
  node is healthy again, with pinned series, breadcrumbs and the
  no-dead-actuators hygiene gate.

Held by tests/test_ingest.py (tracker, scheduler, deferral, SLO rule)
and tests/test_crash_consistency.py (no acknowledged write lost across
kill−9 crash points).  The timed soak of writes under reads — serving
p95 and crawl-to-searchable p95 per tier while indexing at N docs/s —
has no benchmark cell yet: ROADMAP R2 carries its gate list.

Import discipline: this package root (and :mod:`slo` / :mod:`scheduler`)
stays jax-free — the crash-chaos subprocess harness imports the RWI
write path in dozens of short-lived interpreters.  Only
:mod:`devbuild` touches jax, and only its call sites import it.
"""

from . import scheduler, slo  # noqa: F401  (jax-free by contract)
