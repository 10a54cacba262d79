#!/usr/bin/env python3
"""Readings of the control of a tensor-model cell, for setting its limits.

    python3 bench/control_bptf.py --workload <cell> --seeds 11,12,13

For each seed the plain reference (``bench/reference/bptf.py``) at the
configuration's precision is compared, by the numbers the cell's
``correct`` uses (``kinds/train_bptf.py``), with the same reference one
precision step below it: float32 ``highest`` -> ``high``, three bfloat16
passes per product, as ``bench/control.py`` does for the matrix cells.

Prints one JSON line per seed and a summary line last, the least reading
of each number. Not part of a benchmark run; the limits in
``bench/workloads`` come from it and from the program's own readings.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import run as harness  # noqa: E402
from benchlib import synth_time  # noqa: E402


def readings(cell: dict, seed: int, log=harness.log) -> dict:
    """The control's numbers against the reference for one seed."""
    cfg, traffic = cell["config_data"], cell["traffic_data"]
    ref = harness.load_module(os.path.join(BENCH, "reference", f"{cfg['model']}.py"), "ref")
    kind = harness.load_module(os.path.join(BENCH, "kinds", f"{cell['kind']}.py"), "kind")
    rows, cols, times, vals = synth_time.ratings(cfg, seed, log)
    prob = ref.Problem(rows, cols, times, vals, cfg["num_users"], cfg["num_movies"],
                       cfg["num_times"], cfg["test_fraction"], cfg["run_seed"])
    args = (prob, cfg["K"], cfg["alpha"], cfg["beta0"], cfg["run_seed"],
            traffic["sweeps_per_block"], traffic["burn_in"], traffic["keep_factor_samples"])
    t = time.perf_counter()
    want = ref.run(*args)
    got = ref.run(*args, precision="high")
    log(f"reference and control: seed {seed} {time.perf_counter() - t:.3f} s")
    return kind.compare(got, want)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.device_check(cell["chips"])
    worst = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        numbers = readings(cell, seed)
        print(json.dumps({"standin": "control", "seed": seed, **numbers}), flush=True)
        for k, v in numbers.items():
            worst.setdefault(k, []).append(v)
    print(json.dumps({"summary": {"control": {k: min(v) for k, v in worst.items()}}}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
