#!/usr/bin/env python3
"""Find a serving cell's knee once: the highest offered rate whose
completions keep up with arrivals.

    python3 bench/knee.py --workload <cell> --seed <n> --seconds 8 --rates 200,400,800

One server, set up as a run of the cell sets it up, is driven at each rate
in turn by the cell's load generator. A rate keeps up when no request
failed and the last answer came within ``--slack`` seconds of the last
arrival (no backlog left to drain). Prints one JSON line per rate; the
cell's ``rate_per_s`` is then set by hand to about 0.8 x the knee.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

import run as harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--slack", type=float, default=0.5)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.device_check(cell["chips"])
    from repro.launch.hostdevices import enable_compile_cache

    enable_compile_cache()
    serve = harness.load_module(os.path.join(BENCH, "kinds", "serve.py"), "kind_serve")
    cfg, traffic = cell["config_data"], cell["traffic_data"]
    with tempfile.TemporaryDirectory(prefix="bench-knee-") as work:
        server, _, _ = serve.start_server(cfg, traffic, args.seed, work, harness.log)
        try:
            host, port = server.address
            for rate in (float(r) for r in args.rates.split(",")):
                p = serve.plan(cfg, traffic, dict(cell, rate_per_s=rate), args.seed, args.seconds)
                p.update(host=host, port=port)
                before = server.stats()["batcher"]
                t = time.perf_counter()
                res = serve.send(p, work, args.seconds + 120)
                wall = time.perf_counter() - t
                after = server.stats()["batcher"]
                lat = res["latency_s"]
                failed = sum(1 for v in lat if v is None)
                drain_s = res["end_s"] - (p["due"][-1] if p["due"] else 0.0)
                print(json.dumps({
                    "rate_per_s": rate, "requests": len(lat), "failed": failed,
                    "p50_ms": 1e3 * serve.percentile(lat, 0.5),
                    "p99_ms": 1e3 * serve.percentile(lat, 0.99),
                    "drain_s": drain_s, "keeps_up": failed == 0 and drain_s < args.slack,
                    "occupancy": (after["requests"] - before["requests"])
                    / max(1, after["cycles"] - before["cycles"]),
                    "sender_late_p99_ms": 1e3 * float(np.quantile(res["late_s"], 0.99)),
                    "wall_s": wall}), flush=True)
        finally:
            server.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
