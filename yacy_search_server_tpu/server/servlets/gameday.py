"""Performance_GameDay_p — the game-day verdict table (ISSUE 19).

Performance_Tail_p explains WHY individual queries were slow;
Performance_Health_p shows THAT the SLO is burning.  This panel closes
the loop on the chaos drill itself: for the most recent
:class:`~...utils.gameday.Conductor` run IN THIS PROCESS
(:data:`~...utils.gameday.LAST_RUN`) it renders one row per SCHEDULED
fault — was it detected, was the incident attributed to the RIGHT cause
label and member, did the SLO recover inside the bound after the clear,
was every request during the window answered (degraded + counted, never
a 5xx), and did the recovered fleet rank bit-identically to the
pre-fault baseline.  With no run in this process the panel says so
(``source`` ``none``, no rows): a verdict from another machine is not
this node's.  ``format=json`` exports the full result.
"""

from __future__ import annotations

import json

from ...utils import gameday
from ..objects import ServerObjects, escape_json
from . import servlet

GATES = ("detected", "attributed", "answered", "slo_recovery",
         "bit_identical")


def gameday_view() -> dict:
    """This process's last game-day result, else an empty shell."""
    if gameday.LAST_RUN is not None:
        return {"source": "live", **gameday.LAST_RUN}
    return {"source": "none", "schedule": [], "overlaps": [],
            "verdict_summary": {}, "workload": {}}


@servlet("Performance_GameDay_p")
def respond_gameday(header: dict, post: ServerObjects,
                    sb) -> ServerObjects:
    view = gameday_view()
    if post.get("format", "") == "json":
        prop = ServerObjects()
        prop.raw_body = json.dumps(view, indent=1)
        prop.raw_ctype = "application/json; charset=utf-8"
        return prop
    prop = ServerObjects()
    prop.put("source", escape_json(view.get("source", "none")))
    prop.put("note", "" if view.get("source") != "none"
             else "no drill has run in this process")
    summary = view.get("verdict_summary", {})
    prop.put("faults", summary.get("faults", 0))
    prop.put("passed", summary.get("passed", 0))
    prop.put("all_pass", 1 if summary.get("all_pass") else 0)
    prop.put("unattributed", summary.get("unattributed_verdicts", 0))
    prop.put("never_500", 1 if summary.get("never_500") else 0)
    wl = view.get("workload", {})
    prop.put("queries_total", wl.get("queries_total", 0))
    prop.put("duration_s", wl.get("duration_s", 0))

    overlaps = view.get("overlaps", [])
    prop.put("overlaps", len(overlaps))
    for i, pair in enumerate(overlaps):
        prop.put(f"overlaps_{i}_pair", escape_json("+".join(pair)))

    rows = view.get("schedule", [])
    prop.put("rows", len(rows))
    for i, r in enumerate(rows):
        pre = f"rows_{i}_"
        prop.put(pre + "fault_id", escape_json(r.get("fault_id", "")))
        prop.put(pre + "point", escape_json(r.get("point", "")))
        prop.put(pre + "target", escape_json(r.get("target", "")))
        prop.put(pre + "value", escape_json(str(r.get("value", ""))))
        prop.put(pre + "window",
                 escape_json(f"[{r.get('t_arm', 0)}s, "
                             f"{r.get('t_clear', 0)}s]"))
        prop.put(pre + "scenario", escape_json(r.get("scenario", "")))
        for g in GATES:
            prop.put(pre + g, 1 if r.get(g) else 0)
        prop.put(pre + "verdict", escape_json(r.get("verdict", "")))
        rec = r.get("recovery", {}) or {}
        rs = rec.get("recovered_s")
        prop.put(pre + "recovered_s",
                 "-" if rs is None else f"{rs:.1f}")
        ans = r.get("answered_detail", {}) or {}
        prop.put(pre + "answered_detail", escape_json(
            f"{ans.get('ok_200', 0)}x200 "
            f"{ans.get('degraded_429', 0)}x429 "
            f"{ans.get('errors', 0)}xERR"))
    return prop
