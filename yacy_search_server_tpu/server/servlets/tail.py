"""Performance_Tail_p — the tail-forensics operator panel (ISSUE 15).

Performance_Trace_p shows WHERE a slow query spent its wall;
Performance_Health_p shows THAT the SLO is burning.  This panel shows
WHY: the verdict ring (every over-threshold serving query with its one
classified cause), the windowed cause histogram, the cross-process
straggler scoreboard (which mesh member was the slowest leg, how often,
by how much), the newest assembled mesh waterfall, and the dispatch-
wave log (queue depth / occupancy / compile-vs-reuse / tier state per
wave).  ``format=json`` exports the whole view for ``tools/
tail_report.py`` and offline analysis."""

from __future__ import annotations

import json

from ...utils import tailattr
from ..objects import ServerObjects, escape_json
from . import servlet


def tail_view(sb) -> dict:
    """The full forensics view as one JSON-serializable dict (the
    servlet's format=json export, which tools/tail_report.py renders)."""
    # finalize any owed mesh verdicts whose segments never fully
    # arrived (lull after a burst): the operator asking is exactly
    # when a pending verdict must stop waiting
    tailattr.MESH.flush_pending()
    ctr = tailattr.ATTR.counters()
    mesh = getattr(sb, "mesh_member", None)
    return {
        "enabled": tailattr.enabled(),
        "min_ms": tailattr.MIN_MS,
        "classified_total": ctr["classified_total"],
        "cause_totals": ctr["causes"],
        "causes_windowed": tailattr.windowed_causes(),
        "top_cause": tailattr.top_cause(),
        "stragglers": ctr["stragglers"],
        "verdicts": [v.to_json() for v in tailattr.verdicts(50)],
        "scoreboard": tailattr.scoreboard(),
        "waterfall": tailattr.MESH.waterfall(),
        "segments_merged": tailattr.MESH.segments_merged,
        "pending_partial": tailattr.MESH.pending_partial,
        "waves": tailattr.ATTR.wave_log(30),
        "mesh_member": mesh.process_id if mesh is not None else None,
    }


@servlet("Performance_Tail_p")
def respond_tail(header: dict, post: ServerObjects, sb) -> ServerObjects:
    view = tail_view(sb)
    if post.get("format", "") == "json":
        prop = ServerObjects()
        prop.raw_body = json.dumps(view, indent=1)
        prop.raw_ctype = "application/json; charset=utf-8"
        return prop
    prop = ServerObjects()
    prop.put("enabled", 1 if view["enabled"] else 0)
    prop.put("min_ms", view["min_ms"])
    prop.put("classified_total", view["classified_total"])
    prop.put("top_cause", escape_json(view["top_cause"]))
    prop.put("segments_merged", view["segments_merged"])

    causes = [(c, view["causes_windowed"].get(c, 0),
               view["cause_totals"].get(c, 0)) for c in tailattr.CAUSES]
    prop.put("causes", len(causes))
    for i, (cause, win, tot) in enumerate(causes):
        pre = f"causes_{i}_"
        prop.put(pre + "cause", escape_json(cause))
        prop.put(pre + "windowed", win)
        prop.put(pre + "total", tot)

    verdicts = view["verdicts"]
    prop.put("verdicts", len(verdicts))
    for i, v in enumerate(verdicts):
        pre = f"verdicts_{i}_"
        prop.put(pre + "ts", v["ts"])
        prop.put(pre + "trace_id", escape_json(v["trace_id"]))
        prop.put(pre + "root", escape_json(v["root"]))
        prop.put(pre + "dur_ms", v["dur_ms"])
        prop.put(pre + "cause", escape_json(v["cause"]))
        prop.put(pre + "member", escape_json(v.get("member", "")))
        prop.put(pre + "evidence", escape_json(
            " ".join(f"{k}={v2}" for k, v2 in v["evidence"].items())))

    board = view["scoreboard"]
    prop.put("scoreboard", len(board))
    for i, row in enumerate(board):
        pre = f"scoreboard_{i}_"
        for key in ("member", "steps", "slowest_count", "slowest_frac",
                    "mean_margin_ms", "max_margin_ms", "mean_exec_ms"):
            v = row[key]
            prop.put(pre + key, escape_json(v) if isinstance(v, str)
                     else v)

    wf = view["waterfall"]
    prop.put("waterfall", 1 if wf else 0)
    if wf:
        prop.put("waterfall_seq", wf["seq"])
        prop.put("waterfall_trace", escape_json(wf["trace_id"]))
        prop.put("waterfall_mode", escape_json(wf["mode"]))
        prop.put("waterfall_dur_ms", wf["dur_ms"])
        prop.put("waterfall_members", len(wf["members"]))
        for i, m in enumerate(wf["members"]):
            pre = f"waterfall_members_{i}_"
            prop.put(pre + "member", m["m"])
            prop.put(pre + "q_ms", m["q_ms"])
            prop.put(pre + "commit_ms", m["commit_ms"])
            # entry_ms IS the straggler signal (the slowed member's
            # lateness lands here while the innocents' exec inflates
            # blocking at collective entry) — the panel must show it
            prop.put(pre + "entry_ms", m.get("entry_ms", 0.0))
            prop.put(pre + "exec_ms", m["exec_ms"])
            prop.put(pre + "mode", escape_json(m["mode"]))

    waves = view["waves"]
    prop.put("waves", len(waves))
    for i, w in enumerate(waves):
        pre = f"waves_{i}_"
        prop.put(pre + "kernel", escape_json(w.get("kernel", "?")))
        prop.put(pre + "n", w.get("n", 0))
        prop.put(pre + "occ", w.get("occ", 0.0))
        prop.put(pre + "qdepth", w.get("qdepth", 0))
        prop.put(pre + "issue_ms", w.get("issue_ms", 0.0))
        prop.put(pre + "compile", 1 if w.get("compile") else 0)
        prop.put(pre + "merge_deferred",
                 1 if w.get("merge_deferred") else 0)
        prop.put(pre + "cold_hits", w.get("tier_cold_hits", 0))
    return prop
