#!/usr/bin/env python3
"""One traced run of a training cell, split by the program's named layers.

    python3 bench/layers.py --workload <cell> --seed <n> --seconds <s> [--keep <path>]

Runs the cell's kind as ``bench/run.py --trace 1`` does, then prepares a
second engine on the same data for what the first one freed: its
``layout_stats()`` and the HLO text of its block program, compiled again
(a compile-cache hit). From these and the trace (``benchlib/layers.py``):
device seconds per named layer and per sweep, the Gram's roofline share
(``benchlib/gram.py``), idle time by the run loop's host span, and the
Gram's padding share. Its last stdout line is one JSON object. ``--keep``
writes a trimmed copy of the trace (``layers.trim``), the form of the
tests' recorded v5e trace. Not part of a benchmark run.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import run as harness  # noqa: E402
from benchlib import gram, layers, peaks, synth, trace as tr  # noqa: E402


def program(cell: dict, seed: int, kind) -> tuple[dict, str]:
    """``layout_stats()`` of an engine prepared as the kind prepares it, and
    its block program's compiled HLO text."""
    from repro.bpmf import BPMFEngine
    from repro.data.sparse import RatingsCOO

    cfg = cell["config_data"]
    rows, cols, vals = synth.ratings(cfg, seed, harness.log)
    engine = BPMFEngine(kind.engine_config(cfg, cell["traffic_data"]))
    engine.prepare(RatingsCOO(rows, cols, vals, cfg["num_users"], cfg["num_movies"]))
    stats = engine.layout_stats()
    hlo = engine.lower_block().compile().as_text()
    del engine
    gc.collect()
    return stats, hlo


def split(res: dict, cell: dict, lay: dict, busy_s: float, layout_stats: dict,
          device_kind: str) -> dict:
    """Per-sweep layer times, the Gram's roofline share and padding share."""
    sweeps, nnz, K = res["layer"]["sweeps"], res["layer"]["num_train"], cell["config_data"]["K"]
    named = sum(lay["layers"].values())
    least_s, bound = gram.roofline_s(nnz, K, peaks.peaks(device_kind))
    gram_s = lay["layers"]["bpmf_gram"] / sweeps
    ratings = sum(s["ratings"] for s in layout_stats.values())
    slots = sum(s["gram_slots"] for s in layout_stats.values())
    return {
        "ms_per_sweep": {k: 1e3 * v / sweeps for k, v in lay["layers"].items()},
        "unattributed_ms_per_sweep": 1e3 * lay["unattributed"] / sweeps,
        "named_share_of_busy": named / busy_s if busy_s else None,
        "gram_ms_per_sweep": 1e3 * gram_s,
        "draw_ms_per_sweep": 1e3 * lay["layers"]["posterior_draw"] / sweeps,
        "gram_roofline": 100.0 * least_s / gram_s if gram_s else None,
        "gram_roofline_bound": bound,
        "gram_pad_share": 100.0 * (1.0 - ratings / slots),
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", help="write a trimmed copy of the trace here (.json.gz)")
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    devices = harness.device_check(cell["chips"])
    from repro.launch.hostdevices import enable_compile_cache

    harness.log(f"layers: {args.workload} seed {args.seed}; compile cache {enable_compile_cache()}")
    kind = harness.load_module(os.path.join(BENCH, "kinds", f"{cell['kind']}.py"), "kind")
    trace_dir = tempfile.mkdtemp(prefix="bench-layers-")
    try:
        res = kind.run(cell, args.seed, args.seconds, trace_dir, T0, harness.log)
        events = tr.load(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    layout_stats, hlo = program(cell, args.seed, kind)
    names = layers.op_names(hlo)
    if args.keep:
        tr.save(layers.trim(events, names), args.keep)
    events = layers.with_scopes(events, names)
    whole = tr.reduce(events)
    lay = layers.reduce(events)
    out = {
        "workload": args.workload, "seed": args.seed,
        "correct": all(v <= cell["limits"][k] for k, v in res["numbers"].items()),
        "end_to_end": res["end_to_end"], "numbers": res["numbers"],
        "memory_peak_bytes": res["memory_peak_bytes"],
        "sweeps": res["layer"]["sweeps"],
        "busy_s": whole["busy_s"], "window_s": whole["window_s"],
        "layers_s": lay["layers"], "unattributed_s": lay["unattributed"],
        "unattributed_ops": lay["unattributed_ops"], "no_scope_path_s": lay["no_scope_path"],
        "idle_spans_s": lay["idle_spans"],
        "breakdown": whole["breakdown"],
        "layout": layout_stats,
    }
    out.update(split(res, cell, lay, whole["busy_s"], layout_stats, devices[0].device_kind))
    harness.log(f"idle_spans: {lay['idle_spans']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
