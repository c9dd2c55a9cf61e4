#!/usr/bin/env python3
"""Render a tail-forensics view as operator tables (ISSUE 15 tooling).

Input: a ``Performance_Tail_p?format=json`` export (live, or saved to
a file): the verdict ring, cause histogram, scoreboard and waterfall.

    python tools/tail_report.py tail.json
    curl -s 'http://localhost:8090/Performance_Tail_p.html?format=json' \
        | python tools/tail_report.py -
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _bar(n: int, total: int, width: int = 30) -> str:
    if total <= 0:
        return ""
    return "#" * max(0, round(width * n / total))


def _table(rows: list[list], headers: list[str]) -> str:
    cells = [headers] + [[str(c) for c in r] for r in rows]
    widths = [max(len(r[i]) for r in cells) for i in range(len(headers))]
    lines = []
    for j, r in enumerate(cells):
        lines.append("  ".join(c.ljust(w) for c, w in zip(r, widths)))
        if j == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)


def render(view: dict) -> str:
    out = []
    causes = view.get("cause_totals") or view.get("causes_windowed") \
        or {}
    total = sum(causes.values())
    out.append(f"== cause histogram ({total} classified verdicts) ==")
    rows = [[c, n, f"{n / total:.0%}" if total else "-", _bar(n, total)]
            for c, n in sorted(causes.items(), key=lambda kv: -kv[1])
            if n > 0] or [["(none)", 0, "-", ""]]
    out.append(_table(rows, ["cause", "count", "share", ""]))

    board = view.get("scoreboard") or []
    if board:
        out.append("\n== straggler scoreboard (windowed) ==")
        out.append(_table(
            [[r["member"], r["steps"], r["slowest_count"],
              f"{r['slowest_frac']:.0%}", r["mean_margin_ms"],
              r["max_margin_ms"], r["mean_exec_ms"]] for r in board],
            ["member", "steps", "slowest", "frac", "mean_margin_ms",
             "max_margin_ms", "mean_exec_ms"]))

    wf = view.get("waterfall")
    if wf:
        out.append(f"\n== mesh waterfall: seq={wf['seq']} "
                   f"mode={wf['mode']} wall={wf['dur_ms']}ms "
                   f"trace={wf['trace_id']} ==")
        scale = max((m["q_ms"] + m["commit_ms"] + m.get("entry_ms", 0.0)
                     + m["exec_ms"]) for m in wf["members"]) or 1.0
        rows = []
        for m in wf["members"]:
            parts = [m["q_ms"], m["commit_ms"], m.get("entry_ms", 0.0),
                     m["exec_ms"]]
            bar = ""
            for v, ch in zip(parts, "qce#"):
                bar += ch * max(0, round(28 * v / scale))
            rows.append([f"mesh{m['m']}", m["mode"], *[round(v, 1)
                         for v in parts], bar])
        out.append(_table(rows, ["member", "mode", "q_ms", "commit_ms",
                                 "entry_ms", "exec_ms",
                                 "q=queue c=commit e=entry #=exec"]))

    verdicts = view.get("verdicts") or view.get("verdicts_sample") or []
    if verdicts:
        out.append("\n== verdict ring (newest first) ==")
        rows = []
        for v in verdicts[:20]:
            age = f"{max(0.0, time.time() - v['ts']):.0f}s"
            rows.append([age, v["trace_id"][:16], v["root"],
                         round(v["dur_ms"], 1), v["cause"],
                         v.get("member", "")])
        out.append(_table(rows, ["age", "trace", "root", "dur_ms",
                                 "cause", "member"]))

    ov = view.get("tail_overhead")
    if ov:
        out.append("\n== --tail-overhead gate ==")
        out.append(_table([[ov["p50_ms_tail_off"], ov["p50_ms_tail_on"],
                            f"{ov['overhead_pct']:+.2f}%",
                            f"<{ov['budget_pct']}%",
                            ov["injected_verdicts"],
                            ov["injected_unattributed"]]],
                          ["p50_off_ms", "p50_on_ms", "overhead",
                           "budget", "inj_verdicts", "inj_unattr"]))
    inc = view.get("incident_tail_causes")
    if inc:
        dom = max(inc["window"], key=lambda c: inc["window"][c])
        out.append(f"\n== incident embed: dominant cause {dom!r} "
                   f"({inc['window'][dom]} in window) ==")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("path", help="a Performance_Tail_p json export, "
                                 "or - for stdin")
    args = ap.parse_args(argv)
    if args.path == "-":
        view = json.load(sys.stdin)
    else:
        with open(args.path, encoding="utf-8") as f:
            view = json.load(f)
    print(render(view))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
