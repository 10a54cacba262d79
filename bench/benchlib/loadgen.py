#!/usr/bin/env python3
"""Open-loop HTTP load generator, standard library only.

    python3 bench/benchlib/loadgen.py <plan.json> <result.json>

Run as a child of the serving benchmark. It never imports JAX, so it
neither holds the chip nor shares the server's interpreter lock. The plan
holds the server's address, the arrival times (seconds from the start),
and one JSON request body per arrival. A scheduler thread releases each
request at its due time into a queue that ``connections`` worker threads
drain over keep-alive connections; a request's latency runs from its due
time to the end of its response, so a stall also delays every request
behind it. The result holds, per request, the latency (``None`` when it
failed), the HTTP status and the response body, and how late the
scheduler released requests.
"""
from __future__ import annotations

import http.client
import json
import queue
import sys
import threading
import time


def main(plan_path: str, result_path: str) -> int:
    with open(plan_path) as f:
        plan = json.load(f)
    host, port = plan["host"], plan["port"]
    due, bodies = plan["due"], [json.dumps(b).encode() for b in plan["requests"]]
    n = len(due)
    latency, status, answer = [None] * n, [0] * n, [None] * n
    late = [0.0] * n
    work: queue.Queue = queue.Queue()
    t0 = time.perf_counter() + plan.get("lead_s", 0.2)

    def worker() -> None:
        conn = http.client.HTTPConnection(host, port, timeout=plan.get("timeout_s", 60))
        while True:
            i = work.get()
            if i is None:
                conn.close()
                return
            try:
                conn.request("POST", "/query", bodies[i], {"Content-Type": "application/json"})
                resp = conn.getresponse()
                data = resp.read()
                status[i] = resp.status
                if resp.status == 200:
                    answer[i] = json.loads(data)
                    latency[i] = time.perf_counter() - (t0 + due[i])
            except (OSError, http.client.HTTPException, ValueError):
                conn.close()
                conn = http.client.HTTPConnection(host, port, timeout=plan.get("timeout_s", 60))

    threads = [threading.Thread(target=worker, daemon=True) for _ in range(plan["connections"])]
    for t in threads:
        t.start()
    for i in range(n):
        wait = t0 + due[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        late[i] = time.perf_counter() - (t0 + due[i])
        work.put(i)
    for _ in threads:
        work.put(None)
    for t in threads:
        t.join()
    end = time.perf_counter() - t0
    with open(result_path, "w") as f:
        json.dump({"latency_s": latency, "status": status, "answers": answer,
                   "late_s": late, "end_s": end}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2]))
