"""Reduction of a JAX profiler trace (.xplane.pb) to device numbers.

    combine([reduce(path), ...]) -> the same over several slices
    reduce(path) -> {"busy_s", "span_s", "by_program": {name: seconds},
                     "by_op": {name: seconds}, "program_runs": {name: n},
                     "device_ops": [[name, s]],
                     "idle_gaps": [[label, s]], "planes": [...]}

A device plane is one whose name starts with "/device:" (TPU, GPU). On
a TPU plane the line "XLA Ops" carries one event per executed HLO
operation and "XLA Modules" one per executed program (a jitted
function, named "jit_<function>(<fingerprint>)"). Busy time is the
union of the "XLA Ops" intervals (of "XLA Modules" where a plane has no
ops line), averaged over the device planes; an idle gap is the distance
between two consecutive busy intervals. Host planes are not read: the
program writes no host span on the profiler's clock yet, so a gap can
be labelled only `unknown`.

Reads the file with nothing but JAX's own ProfileData.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_FINGERPRINT = re.compile(r"\(\d+\)$")


def find_xplane(log_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def program_name(event_name: str) -> str:
    """"jit__rank_join_bm(123)" -> "_rank_join_bm"."""
    name = _FINGERPRINT.sub("", event_name)
    return name[4:] if name.startswith("jit_") else name


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def reduce_planes(planes) -> dict:
    """planes: [(plane name, [(line name, [(event name, start_ns,
    duration_ns)])])] — the shape reduce() reads out of ProfileData,
    separate so that the arithmetic can be checked on a hand-made list."""
    dev = [(n, lines) for n, lines in planes if n.startswith("/device:")]
    busy, by_program, by_op, gaps, runs = [], {}, {}, [], {}
    lo, hi = None, None
    for _name, lines in dev:
        lines = dict(lines)
        ops = lines.get(OPS_LINE)
        mods = lines.get(MODULES_LINE, [])
        if ops is None:
            ops = mods
        merged = _union((s, s + d) for _n, s, d in ops if d > 0)
        busy.append(sum(e - s for s, e in merged))
        for a, b in zip(merged, merged[1:]):
            gaps.append(b[0] - a[1])
        if merged:
            lo = merged[0][0] if lo is None else min(lo, merged[0][0])
            hi = merged[-1][1] if hi is None else max(hi, merged[-1][1])
        for n, _s, d in mods:
            key = program_name(n)
            by_program[key] = by_program.get(key, 0) + d
            runs[key] = runs.get(key, 0) + 1
        for n, _s, d in ops:
            by_op[n] = by_op.get(n, 0) + d
    n_dev = max(len(dev), 1)
    top = sorted((by_program or by_op).items(), key=lambda kv: -kv[1])[:10]
    gaps.sort(reverse=True)
    return {
        "busy_s": sum(busy) / n_dev / 1e9,
        "span_s": 0.0 if lo is None else (hi - lo) / 1e9,
        "by_program": {k: v / n_dev / 1e9 for k, v in by_program.items()},
        "by_op": {k: v / n_dev / 1e9 for k, v in by_op.items()},
        "program_runs": runs,
        "device_ops": [[k, v / n_dev / 1e9] for k, v in top],
        "idle_gaps": [["unknown", g / 1e9] for g in gaps[:10]],
        "planes": [n for n, _ in planes],
    }


def combine(parts) -> dict:
    """Several reduced traces (slices of one window) as one: times and
    runs added up, the longest gaps and the top operations of them all."""
    out = {"busy_s": 0.0, "span_s": 0.0, "by_program": {}, "by_op": {},
           "program_runs": {}, "idle_gaps": [], "planes": []}
    for p in parts:
        out["busy_s"] += p["busy_s"]
        out["span_s"] += p["span_s"]
        for key in ("by_program", "by_op", "program_runs"):
            for k, v in p[key].items():
                out[key][k] = out[key].get(k, 0) + v
        out["idle_gaps"] += p["idle_gaps"]
        out["planes"] = out["planes"] or p["planes"]
    out["idle_gaps"] = sorted(out["idle_gaps"], key=lambda g: -g[1])[:10]
    top = sorted((out["by_program"] or out["by_op"]).items(),
                 key=lambda kv: -kv[1])[:10]
    out["device_ops"] = [[k, v] for k, v in top]
    return out


def reduce(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            planes.append((plane.name, []))
            continue
        lines = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            lines.append((line.name, [
                (ev.name, int(ev.start_ns), int(ev.duration_ns))
                for ev in line.events]))
        planes.append((plane.name, lines))
    return reduce_planes(planes)
