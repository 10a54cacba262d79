#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything the run needs is found by name: the cell in
``bench/workloads/<cell>.json``, which names its configuration
(``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<traffic>.json``) and its kind, whose driver is
``bench/kinds/<kind>.py``; the per-layer metrics of ``BENCHMARK.json`` that
list the cell are read by ``bench/metrics/<metric>.py``. Adding a cell,
configuration, mix or metric adds files and entries; it edits none.

The run refuses a host without a TPU, or with fewer chips than the cell
asks for, before it measures anything. Its last stdout line is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``:
every number compared with the reference beside its limit, which are also
the last lines on stderr.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # keep libtpu's logs out of /tmp


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, bench_dir: str = BENCH) -> dict:
    """The cell's file, with its configuration and traffic mix attached."""
    cell = load_json(bench_dir, "workloads", f"{name}.json")
    cell["name"] = name
    cell["config_data"] = load_json(bench_dir, "configs", f"{cell['config']}.json")
    cell["traffic_data"] = load_json(bench_dir, "traffic", f"{cell['traffic']}.json")
    return cell


def cell_metrics(spec: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics of ``BENCHMARK.json`` that this cell reports."""
    def listed(m):
        return cell in m["workloads"] if "workloads" in m else None

    e2e = [m for m in spec["end_to_end"] if listed(m) is not False]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if listed(m) or (listed(m) is None and m["moves"] in names)]


def device_check(chips: int) -> list:
    """The devices this run uses; exits 2 on a host without enough TPU chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"bench: no TPU found (platform {devices[0].platform!r}); refusing to run")
        sys.exit(2)
    if len(devices) < chips:
        log(f"bench: the cell needs {chips} chips, found {len(devices)}; refusing to run")
        sys.exit(2)
    return devices


def measure(cell: dict, seed: int, seconds: float, trace: bool, spec: dict,
            devices: list) -> dict:
    """Drive the cell's kind and assemble the result line."""
    from benchlib import peaks, trace as tr

    kind = load_module(os.path.join(BENCH, "kinds", f"{cell['kind']}.py"), f"kind_{cell['kind']}")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        res = kind.run(cell, seed, seconds, trace_dir, T0, log)
        events = tr.load(trace_dir) if trace else None
        if trace and not events["devices"]:
            raise RuntimeError(f"trace: no {tr.OPS_LINE!r} line on any device plane; "
                               f"planes {events['planes']}")
        reduced = tr.reduce(events) if trace else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    limits = cell["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in res["numbers"].items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    d0 = devices[0]
    metrics = {}
    for m in cell_metrics(spec, cell["name"], trace):
        if trace:
            ctx = {"cell": cell, "layer": res["layer"], "trace": reduced,
                   "chips": res["devices_used"], "peaks": peaks.peaks(d0.device_kind)}
            reader = load_module(os.path.join(BENCH, "metrics", f"{m['name']}.py"),
                                 f"metric_{m['name'].replace('.', '_')}")
            value = reader.read(ctx)
        else:
            value = res["end_to_end"].get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": correct, "attempted": res["attempted"], "failed": res["failed"],
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = reduced["breakdown"]
        log(f"trace: {reduced['devices']} devices, busy {reduced['busy_s']} s of "
            f"{reduced['window_s']} s, collectives {reduced['collective_s']} s "
            f"({reduced['exposed_collective_s']} s exposed)")
    out["checks"] = checks
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = load_json(ROOT, "BENCHMARK.json")
    cell = load_cell(args.workload)
    devices = device_check(cell["chips"])
    from repro.launch.hostdevices import enable_compile_cache

    log(f"bench: {args.workload} seed {args.seed} on {len(devices)} x "
        f"{devices[0].device_kind}; compile cache {enable_compile_cache()}")
    out = measure(cell, args.seed, args.seconds, bool(args.trace), spec, devices)
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
