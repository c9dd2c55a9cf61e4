#!/usr/bin/env python3
"""One load-generator process: closed-loop HTTP clients, stdlib only.

Never imports jax or the program: the parent holds the chip, and the
clients must not share the server's interpreter lock. Started by
benchmarks/run.py as

    python benchmarks/client.py <job.json>

job: {"host", "port", "path", "queries": [[index, string], ...] per
client, "t0": monotonic start, "seconds", "grace_s", "out"}. Each client
thread keeps one keep-alive connection and sends its next query when the
reply to the last one is in. A request that was sent inside the window
is waited for (up to grace_s past the close) and its latency counts the
wait. Bodies are parsed after the window closes.
"""

import http.client
import json
import sys
import threading
import time
import urllib.parse


def _client(job, queries, out):
    conn = None
    end = job["t0"] + job["seconds"]
    deadline = end + job["grace_s"]
    while time.monotonic() < job["t0"]:
        time.sleep(min(0.002, max(0.0, job["t0"] - time.monotonic())))
    for qi, query in queries:
        t_send = time.monotonic()
        if t_send >= end:
            break
        path = job["path"] + "?" + urllib.parse.urlencode(
            {"query": query, "maximumRecords": 10})
        status, degraded, body, err = 0, None, b"", None
        try:
            if conn is None:
                conn = http.client.HTTPConnection(
                    job["host"], job["port"],
                    timeout=max(1.0, deadline - t_send))
            conn.request("GET", path)
            r = conn.getresponse()
            body = r.read()
            status, degraded = r.status, r.getheader("X-YaCy-Degraded")
            if r.will_close:
                conn.close()
                conn = None
        except (OSError, http.client.HTTPException) as e:
            err = f"{type(e).__name__}: {e}"
            if conn is not None:
                conn.close()
            conn = None
        out.append([qi, t_send, time.monotonic(), status, degraded, body,
                    err])
    else:
        out.append(None)        # the stream ran dry inside the window
    if conn is not None:
        conn.close()


def main(argv):
    with open(argv[1], encoding="utf-8") as f:
        job = json.load(f)
    outs = [[] for _ in job["queries"]]
    threads = [threading.Thread(target=_client, args=(job, q, o))
               for q, o in zip(job["queries"], outs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rows, dry = [], 0
    for o in outs:
        for rec in o:
            if rec is None:
                dry += 1
                continue
            qi, t_send, t_done, status, degraded, body, err = rec
            items = None
            if status == 200:
                try:
                    items = [[it["link"], int(it["ranking"])] for it in
                             json.loads(body)["channels"][0]["items"]]
                except (ValueError, KeyError, IndexError, TypeError) as e:
                    err = f"malformed page: {type(e).__name__}: {e}"
            rows.append([qi, t_send, t_done, status, degraded, items, err])
    with open(job["out"], "w", encoding="utf-8") as f:
        json.dump({"rows": rows, "dry": dry}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
