"""Benchmark harness entry point: one benchmark per paper figure/claim.

    PYTHONPATH=src python -m benchmarks.run [--full]

Default is smoke scale (CI-sized, minutes); --full runs the paper-scale
variants. Every benchmark runs in its own subprocess and this process never
imports jax, so a child can claim the one device of a TPU host; the
multi-device ones (fig4/fig5/rmse) get forced host device counts.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks.common import run_with_devices

# name -> (title, module, forced host devices or None, output tail)
SECTIONS = {
    "fig2": ("fig2: per-item update cost vs nnz", "benchmarks.fig2_item_update",
             None, 1200),
    "fig3": ("fig3: single-node updates/s (bucketing variants)", "benchmarks.fig3_multicore",
             None, 1200),
    "fig4": ("fig4: distributed strong scaling (8 host devices)", "benchmarks.fig4_scaling",
             8, 1200),
    "fig5": ("fig5: compute/comm overlap (ring vs allgather)", "benchmarks.fig5_overlap",
             8, 800),
    "rmse": ("rmse: accuracy parity across all versions", "benchmarks.rmse_convergence",
             4, 800),
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale (slow)")
    ap.add_argument("--only", help="comma list: " + ",".join(SECTIONS))
    args = ap.parse_args(argv)
    smoke = not args.full
    only = set(args.only.split(",")) if args.only else None

    failures = []
    for name, (title, module, devices, tail) in SECTIONS.items():
        if only is not None and name not in only:
            continue
        print(f"\n=== {title} {'(smoke)' if smoke else '(full)'} ===", flush=True)
        t0 = time.time()
        try:
            out = run_with_devices(module, devices, smoke=smoke)
            print(out[-tail:])
        except Exception:
            failures.append(name)
            traceback.print_exc()
        print(f"=== {name} done in {time.time()-t0:.1f}s ===", flush=True)

    print("\n==== benchmark summary ====")
    print("FAILURES:", failures or "none")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
