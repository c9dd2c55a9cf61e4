"""Env-gated failpoints — deterministic fault injection for robustness
tests (ISSUE 9 satellite; extended by ISSUE 10 into the crash/chaos
harness substrate).

The self-defending serving loop (utils/actuator.py) only transitions on
REAL signals: a burn-rate rule firing, a batcher queue growing, a peer
digest reporting critical.  Testing those transitions organically means
sleeping until enough slow requests accumulate in 30 s histogram
windows — minutes per test.  Failpoints let a test drive the exact same
product code paths deterministically:

- ``servlet.serving`` latency injection: the httpd dispatch sleeps the
  configured milliseconds INSIDE the measured serving wall, so the SLO
  histogram fills with genuinely slow requests and the burn-rate rules
  fire on real data.
- ``batcher.dispatch`` forced worker_stall: a dispatcher sleeps inside
  its dispatch, so the watchdog's stall attribution and the
  worker_stall health rule see a real wedge.
- ``peer.blackhole``: RPCs to the listed peer hashes fail after an
  optional delay — the sick-peer avoidance path sees a genuinely
  unresponsive peer without a real network.

Crash/IO faults (ISSUE 10 tentpole b — the chaos harness drives the
durability claims through the REAL write paths instead of trusting the
fsync comments):

- ``proc.crashpoint``: named SIGKILL barriers inside flush / merge /
  journal-truncate / manifest-switch.  Armed with a crashpoint NAME;
  when execution reaches :func:`crashpoint` with that name the process
  kills itself with ``SIGKILL`` — no atexit, no flush, the honest
  kill−9.  The subprocess harness (tests/test_crash_consistency.py)
  arms each registered name in a child indexer and asserts the restart
  recovers every acked document bit-identically.
- ``io.torn_write``: ``<path_frag>:<n>`` — the next durable write whose
  target path contains ``path_frag`` persists only its first ``n``
  bytes, then raises (the on-disk artifact of a crash mid-write).
- ``io.error``: ``<path_frag>[:<nth>]`` — the nth matching durable
  write raises ``OSError`` (a full disk / dying device at exactly the
  op under test).
- ``device.transfer_fail``: a COUNT of device transfers to fail.  Each
  guarded fetch/upload consumes one charge and raises; at zero the
  device "comes back" — which is how the device-loss tests hold the
  device down across the retry ladder and then let the background
  rebuild succeed (index/devstore.py).  In a multi-process mesh
  (ISSUE 12) the same point armed INSIDE one member process — via the
  ``YACY_FAULTS`` env at spawn or the test-fleet-gated ``meshfault``
  wire endpoint — fails exactly that member's transfers, driving the
  one-member-down survival contract (tests/test_mesh_multiproc.py).

Every faultpoint name is declared in :data:`REGISTERED_FAULTPOINTS`;
the no-dead-faultpoints hygiene gate (tests/test_code_hygiene.py)
fails any registered name no test exercises, and :func:`crashpoint` /
the io helpers refuse unregistered names loudly — a typo'd site must
not silently never fire.

Two gates keep this production-inert: the module is OFF unless
``YACY_FAULTS`` is set in the environment (parsed once at import) or a
test calls :func:`set_fault` explicitly, and every injection site
checks a single module flag before doing any work — the disabled cost
is one attribute read.
"""

from __future__ import annotations

import os
import signal
import threading
import time
from collections import deque

_lock = threading.Lock()
_faults: dict[str, object] = {}
# fast-path gate: injection sites bail on this before touching the dict
_active = False

# schedule metadata (ISSUE 19): every arm/clear/expire is a timestamped
# event in a bounded ring, so the game-day conductor and the verdict
# engine join against ONE source of truth (wire-readable via
# do_meshfault?list=1) instead of parallel bookkeeping.  Monotonic per
# process — cross-process joins key on (pid, seq), never wall-clock
# ordering.
_schedule: deque = deque(maxlen=256)
_schedule_seq = 0

# every faultpoint name a production site may reach, with the site it
# lives at.  proc.crashpoint values (the named SIGKILL barriers) are
# listed in CRASHPOINTS below and are faultpoints in their own right
# for the hygiene gate.
REGISTERED_FAULTPOINTS = {
    "servlet.serving": "httpd dispatch latency inside the SLO wall",
    "batcher.dispatch": "forced dispatcher stall (worker_stall path)",
    "mesh.step": "mesh member step-execution latency (straggler "
                 "injection for the collective_straggler verdict)",
    "peer.blackhole": "RPCs to listed peer hashes fail",
    "proc.crashpoint": "named SIGKILL barrier (see CRASHPOINTS)",
    "io.torn_write": "durable write truncated at byte N, then raises",
    "io.error": "nth matching durable write raises OSError",
    "device.transfer_fail": "next N device transfers raise",
}

# the named kill−9 barriers inside the storage state machines.  Each is
# a REACHABLE site (crashpoint(name) in product code) and each must be
# exercised by the subprocess harness — the no-dead-faultpoints gate
# cross-references this tuple against tests/.
CRASHPOINTS = (
    # pagedrun.PagedRun.write: .dat renamed into place, .tix still .tmp
    "pagedrun.write.dat_renamed",
    # rwi.RWIIndex._swap_run: paged file pair on disk, manifest not yet
    # rewritten to reference it
    "rwi.flush.before_manifest",
    # rwi.RWIIndex._write_manifest: manifest .tmp written, not renamed
    "rwi.manifest.mid_write",
    # rwi.RWIIndex.merge_runs: merged run live in the manifest, victim
    # run files not yet unlinked
    "rwi.merge.before_unlink",
    # colstore.write_segment: payload partially written to .tmp
    "colstore.segment.mid_write",
    # metadata.MetadataStore._persist_state: new journal generation
    # created, manifest still names the old one
    "metadata.snapshot.before_manifest",
    # metadata.MetadataStore._persist_state: manifest switched, stale
    # segment/journal files not yet removed
    "metadata.snapshot.after_manifest",
)


class InjectedFault(Exception):
    """Raised by io.* and device.* faultpoints — typed so product code
    can treat an injected failure exactly like the real one while tests
    can still tell them apart in logs."""


def _parse_env() -> None:
    """``YACY_FAULTS="servlet.serving=250,peer.blackhole=abc:1.5"`` —
    comma-separated ``point=value`` pairs; blackhole values are
    ``hash[:delay_s]`` and may repeat."""
    spec = os.environ.get("YACY_FAULTS", "")
    for part in spec.split(","):
        part = part.strip()
        if not part or "=" not in part:
            continue
        name, _, val = part.partition("=")
        if name == "peer.blackhole":
            h, _, delay = val.partition(":")
            blackhole_peer(h, float(delay) if delay else 0.0)
        else:
            try:
                set_fault(name, float(val))
            except ValueError:
                set_fault(name, val)


def _jsonable(value):
    return value if isinstance(value, (int, float, str, bool)) \
        or value is None else str(value)


def _note_event_locked(action: str, point: str, value=None) -> None:
    """Append one schedule event (caller holds _lock)."""
    global _schedule_seq
    _schedule_seq += 1
    _schedule.append({"seq": _schedule_seq,
                      "ts": round(time.time(), 3),
                      "action": action, "point": point,
                      "value": _jsonable(value),
                      "pid": os.getpid()})


def snapshot() -> dict:
    """The armed faults RIGHT NOW (JSON-safe values) — the flight
    recorder stamps this into every incident header so a post-hoc join
    reads which injections were live at dump time."""
    if not _active:
        return {}
    with _lock:
        return {k: _jsonable(v) for k, v in _faults.items()}


def schedule(n: int = 0) -> list[dict]:
    """The arm/clear/expire event history (newest last; `n` > 0 limits
    to the newest n) — the verdict engine's join key."""
    with _lock:
        evs = list(_schedule)
    return evs[-n:] if n > 0 else evs


def set_fault(name: str, value) -> None:
    """Arm one failpoint (tests; the env var feeds through here too)."""
    global _active
    base = name.split("=", 1)[0]
    if base not in REGISTERED_FAULTPOINTS:
        raise KeyError(f"unregistered faultpoint {name!r} — add it to "
                       "faultinject.REGISTERED_FAULTPOINTS")
    with _lock:
        _faults[name] = value
        _active = True
        _note_event_locked("arm", name, value)


def clear(name: str | None = None) -> None:
    """Disarm one failpoint, or all of them (test teardown)."""
    global _active
    with _lock:
        if name is None:
            for k in _faults:
                _note_event_locked("clear", k)
            _faults.clear()
        else:
            if name in _faults:
                _note_event_locked("clear", name)
            _faults.pop(name, None)
        _active = bool(_faults)


def get(name: str, default=None):
    if not _active:
        return default
    with _lock:
        return _faults.get(name, default)


def latency_ms(point: str) -> float:
    """Configured injected latency for a point (0.0 when unarmed)."""
    if not _active:
        return 0.0
    v = get(point, 0.0)
    try:
        return max(0.0, float(v))
    except (TypeError, ValueError):
        return 0.0


def sleep(point: str) -> float:
    """Injection site entry: sleep the configured latency (no-op when
    the point is unarmed); returns the ms slept."""
    if not _active:          # the production-path cost: one flag read
        return 0.0
    ms = latency_ms(point)
    if ms > 0.0:
        time.sleep(ms / 1000.0)
    return ms


# -- peer RPC blackhole ------------------------------------------------------

def blackhole_peer(peer_hash, delay_s: float = 0.0) -> None:
    """Arm the blackhole for one peer: RPCs to it fail after `delay_s`
    (0 = fail fast — the deterministic default for tests that assert
    the peer is SKIPPED, so an accidental call is loud, not slow)."""
    from .fleet import peer_key
    key = peer_key(peer_hash)
    holes = dict(get("peer.blackhole", {}) or {})
    holes[key] = float(delay_s)
    set_fault("peer.blackhole", holes)


def blackholed(peer_hash) -> bool:
    if not _active:
        return False
    from .fleet import peer_key
    key = peer_key(peer_hash)
    holes = get("peer.blackhole")
    return isinstance(holes, dict) and key in holes


def blackhole_delay_s(peer_hash) -> float:
    from .fleet import peer_key
    key = peer_key(peer_hash)
    holes = get("peer.blackhole")
    if not isinstance(holes, dict):
        return 0.0
    return float(holes.get(key, 0.0))


# -- crash barriers (ISSUE 10: the kill−9 chaos harness) ---------------------

def crashpoint(name: str) -> None:
    """Named SIGKILL barrier: when ``proc.crashpoint`` is armed with
    this name the process kills itself — no cleanup, no flush, the
    exact artifact a power-yanked node leaves behind.  Disabled cost:
    one module-flag read."""
    if not _active:
        return
    assert name in CRASHPOINTS, \
        f"unregistered crashpoint {name!r} — add it to CRASHPOINTS"
    armed = get("proc.crashpoint")
    if armed == name:
        os.kill(os.getpid(), signal.SIGKILL)
        time.sleep(60)      # pragma: no cover — SIGKILL is not deferrable


def _match_path_spec(point: str, path: str):
    """Parse ``<frag>[:<n>]`` specs; returns the int suffix (default 1)
    when `path` contains the fragment, else None."""
    spec = get(point)
    if not isinstance(spec, str) or not spec:
        return None
    frag, _, n = spec.partition(":")
    if frag and frag in path:
        try:
            return int(n) if n else 1
        except ValueError:
            return 1
    return None


def torn_write_bytes(path: str):
    """``io.torn_write`` site: byte count to persist before the
    simulated crash-mid-write, or None when unarmed / non-matching.
    One-shot: the armed spec is consumed so recovery paths (the very
    thing under test) can write cleanly afterwards."""
    if not _active:
        return None
    n = _match_path_spec("io.torn_write", path)
    if n is not None:
        clear("io.torn_write")
    return n


def io_error(path: str) -> None:
    """``io.error`` site: the nth matching durable write raises.  The
    armed spec counts down; the failing occurrence consumes it."""
    if not _active:
        return
    with _lock:
        spec = _faults.get("io.error")
        if not isinstance(spec, str) or not spec:
            return
        frag, _, n = spec.partition(":")
        if not frag or frag not in path:
            return
        nth = int(n) if n else 1
        if nth > 1:
            _faults["io.error"] = f"{frag}:{nth - 1}"
            return
        _faults.pop("io.error", None)
        _note_event_locked("expired", "io.error")
    raise InjectedFault(f"injected io.error on {path}")


def take(point: str) -> bool:
    """Consume one charge of a COUNTED faultpoint (device.transfer_fail
    semantics: armed with N, the next N calls return True, then the
    point disarms itself — 'the device comes back')."""
    global _active
    if not _active:
        return False
    with _lock:
        v = _faults.get(point)
        if v is None:
            return False
        try:
            n = int(float(v))
        except (TypeError, ValueError):
            return False
        if n <= 0:
            _faults.pop(point, None)
            _active = bool(_faults)
            _note_event_locked("expired", point)
            return False
        if n == 1:
            # the counted point self-disarms ("the device comes back")
            # — a schedule event, so the verdict engine can see the
            # recovery edge even when no one ever called clear()
            _faults.pop(point, None)
            _active = bool(_faults)
            _note_event_locked("expired", point)
        else:
            _faults[point] = n - 1
        return True


_parse_env()
