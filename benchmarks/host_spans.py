#!/usr/bin/env python3
"""The device's idle gaps of a traced run, labelled with the program's
host spans.

    python benchmarks/host_spans.py <trace dir> [<trace dir> ...]

The program (`utils/tracing.py`) opens a `jax.profiler.TraceAnnotation`
for every live span on the thread where the work runs, so a `--trace 1`
run's `.xplane.pb` holds them in its host planes beside the device
operations, on one clock. For each of the longest gaps between two
device operations this names, per thread role, the most specific span
of the program that covers at least half of the gap (its share beside
it), or `none`:

    request     the request's thread: `servlet.*`, `switchboard.*`,
                `search.*`, and its wait on the batcher, `devstore.batch`
    former      `batcher.form`, `batcher.handoff`
    dispatcher  `kernel.issue`
    completer   `kernel.fetch`
    runtime     `runtime.gc`, `runtime.sampler_tick`, `runtime.health_tick`

The role comes from the span's NAME: every host line of a trace is named
after the process (`python3`), not after the Python thread. "Covers" is
the union over all events of that name, on whatever threads: four
request threads in `search.normalizing` one after the other cover a gap
together. "Most specific" is the name whose events are shortest: of
`servlet.serving` (100% of any gap under load) and `search.join` (60%)
the second says where the threads were.

`brackets()` checks the one clock: in every traced dispatch a
dispatcher's `kernel.issue` begins before, and a completer's
`kernel.fetch` ends after, the device program they bracket.

benchmarks/run.py deletes its trace directories before the readers run,
so the result line's `breakdown.idle_gaps` does not come from here yet:
keep the directories (a scratch copy of run.py) and run this on them.
The arithmetic (`label_gaps`, `brackets`) takes the same
[(plane, [(line, [(name, start_ns, duration_ns)])])] as
trace_reduce.reduce_planes, so a hand-made list tests it.
"""

from __future__ import annotations

import bisect
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.trace_reduce import (MODULES_LINE, OPS_LINE,    # noqa: E402
                                     _union, find_xplane)

ROLES = ("request", "former", "dispatcher", "completer", "runtime")
_REQUEST = ("servlet.", "switchboard.", "search.", "devstore.", "peers.")
MIN_COVER = 0.5


def role_of(name: str) -> str | None:
    """The thread role a span of the program runs on; None for an event
    that is not the program's (JAX's own `PjitFunction(...)`)."""
    if name.startswith("batcher."):
        return "former"
    if name == "kernel.issue":
        return "dispatcher"
    if name == "kernel.fetch":
        return "completer"
    if name.startswith("runtime."):
        return "runtime"
    if name.startswith(_REQUEST):
        return "request"
    return None


def _device_busy(planes):
    """[(merged busy intervals, program events)] per device plane."""
    out = []
    for name, lines in planes:
        if not name.startswith("/device:"):
            continue
        lines = dict(lines)
        mods = lines.get(MODULES_LINE, [])
        ops = lines.get(OPS_LINE)
        if ops is None:
            ops = mods
        out.append((_union((s, s + d) for _n, s, d in ops if d > 0), mods))
    return out


def _host_spans(planes):
    """{role: {name: [(start, end)]}} of the program's spans on every
    host line."""
    out = {r: {} for r in ROLES}
    for name, lines in planes:
        if not name.startswith("/host:"):
            continue
        for _line, events in lines:
            for ev, s, d in events:
                role = role_of(ev)
                if role is not None and d > 0:
                    out[role].setdefault(ev, []).append((s, s + d))
    return out


def label_gaps(planes, top: int = 10, min_cover: float = MIN_COVER) -> list:
    """The `top` longest gaps between two device operations, longest
    first: {"gap_s", "at_s" (from the first device operation),
    "labels": {role: [span name, share of the gap] or ["none", 0.0]}}."""
    gaps, t_first = [], None
    for merged, _mods in _device_busy(planes):
        if merged:
            t_first = merged[0][0] if t_first is None \
                else min(t_first, merged[0][0])
        gaps += [(b[0] - a[1], a[1], b[0]) for a, b in zip(merged, merged[1:])]
    gaps.sort(reverse=True)
    spans = _host_spans(planes)
    out = []
    for length, lo, hi in gaps[:top]:
        labels = {}
        for role in ROLES:
            best = None                 # (mean event length, name, share)
            for name, ivs in spans[role].items():
                inside = _union((max(s, lo), min(e, hi)) for s, e in ivs
                                if s < hi and e > lo)
                share = sum(e - s for s, e in inside) / length
                if share < min_cover:
                    continue
                hit = [e - s for s, e in ivs if s < hi and e > lo]
                key = (sum(hit) / len(hit), name, share)
                if best is None or key < best:
                    best = key
            labels[role] = ["none", 0.0] if best is None \
                else [best[1], round(best[2], 3)]
        out.append({"gap_s": length / 1e9, "at_s": (lo - t_first) / 1e9,
                    "labels": labels})
    return out


def brackets(planes) -> dict:
    """Host and device on one clock: for every executed device program,
    the latest `kernel.issue` that begins before it and the earliest
    `kernel.fetch` that ends after it. {"programs", "bracketed" (both
    found), "issue_lead_us" / "fetch_lag_us": [min, median, max] of
    program start less issue start, fetch end less program end}. A clock
    apart by an offset shows as leads or lags of that size, or as
    programs nothing brackets."""
    spans = _host_spans(planes)
    issues = sorted(s for ivs in spans["dispatcher"].values() for s, _e in ivs)
    fetches = sorted(e for ivs in spans["completer"].values() for _s, e in ivs)
    n = ok = 0
    leads, lags = [], []
    for _merged, mods in _device_busy(planes):
        for _name, s, d in mods:
            n += 1
            i = bisect.bisect_right(issues, s)          # issues[:i] <= s
            j = bisect.bisect_left(fetches, s + d)      # fetches[j:] >= end
            if i > 0 and j < len(fetches):
                ok += 1
                leads.append((s - issues[i - 1]) / 1e3)
                lags.append((fetches[j] - (s + d)) / 1e3)

    def three(vals):
        vals = sorted(vals)
        return [vals[0], vals[len(vals) // 2], vals[-1]] if vals else None

    return {"programs": n, "bracketed": ok,
            "issue_lead_us": three(leads), "fetch_lag_us": three(lags)}


def span_counts(planes) -> dict:
    """{role: {span name: events}}: which threads ran annotated."""
    return {role: {n: len(ivs) for n, ivs in names.items()}
            for role, names in _host_spans(planes).items()}


def read_planes(path: str) -> list:
    """The device lines trace_reduce reads, and of the host planes the
    program's spans alone."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            if plane.name.startswith("/device:"):
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                evs = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                       for ev in line.events]
            elif plane.name.startswith("/host:"):
                evs = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                       for ev in line.events if role_of(ev.name)]
                if not evs:
                    continue
            else:
                continue
            lines.append((line.name, evs))
        planes.append((plane.name, lines))
    return planes


def main(argv=None) -> int:
    dirs = (argv if argv is not None else sys.argv[1:])
    if not dirs:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    rows = []
    for d in dirs:
        path = d if d.endswith(".pb") else find_xplane(d)
        if path is None:
            print(f"host_spans.py: no .xplane.pb under {d}", file=sys.stderr)
            return 2
        planes = read_planes(path)
        print(json.dumps({"trace": d, "brackets": brackets(planes),
                          "spans": span_counts(planes)}))
        rows += [dict(g, trace=d) for g in label_gaps(planes)]
    rows.sort(key=lambda g: -g["gap_s"])
    print("| gap s | at s | " + " | ".join(ROLES) + " |")
    print("| --- | --- | " + " | ".join("---" for _ in ROLES) + " |")
    for g in rows[:10]:
        print(f"| {g['gap_s']:.4f} | {g['at_s']:.3f} | " + " | ".join(
            "none" if g["labels"][r][0] == "none"
            else f"{g['labels'][r][0]} {100 * g['labels'][r][1]:.0f}%"
            for r in ROLES) + " |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
