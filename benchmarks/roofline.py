"""§Roofline aggregator: experiments/dryrun JSONs -> the per-cell table.

    python -m benchmarks.roofline [--mesh pod16x16] [--smoke | --out PATH]

Prints (and saves; --smoke to a temp path) per (arch x shape): the three roofline terms in seconds,
the dominant term, MODEL_FLOPS/HLO_FLOPS, HBM fit, and the roofline
fraction. No jax needed — pure JSON aggregation.
"""
from __future__ import annotations

import argparse
import glob
import json
import os

from benchmarks.common import save_result, smoke_out_path

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments", "dryrun")


def load_cells(mesh: str) -> list[dict]:
    cells = []
    for path in sorted(glob.glob(os.path.join(DRYRUN_DIR, mesh, "*.json"))):
        with open(path) as f:
            cells.append(json.load(f))
    return cells


def table(mesh: str = "pod16x16") -> tuple[list[dict], str]:
    cells = load_cells(mesh)
    rows, lines = [], []
    hdr = (f"| arch | shape | compute s | memory s | collective s | dominant | "
           f"useful | HBM GB | fits | roofline frac |")
    lines += [hdr, "|" + "---|" * 10]
    for c in cells:
        if c.get("status") != "ok":
            lines.append(f"| {c['arch']} | {c['shape']} | FAILED: {c.get('error','')[:60]} |" + " |" * 7)
            rows.append({"arch": c["arch"], "shape": c["shape"], "status": "error"})
            continue
        r = c["roofline"]
        mem_gb = r["memory"]["peak_bytes_est"] / 1e9
        rows.append({
            "arch": c["arch"], "shape": c["shape"], "status": "ok",
            "compute_s": r["compute_s"], "memory_s": r["memory_s"],
            "collective_s": r["collective_s"], "dominant": r["dominant"],
            "useful_flops_ratio": r["useful_flops_ratio"],
            "hbm_gb": mem_gb, "fits_hbm": r["fits_hbm"],
            "roofline_fraction": r["roofline_fraction"],
        })
        u = r["useful_flops_ratio"]
        lines.append(
            f"| {c['arch']} | {c['shape']} | {r['compute_s']:.3e} | {r['memory_s']:.3e} "
            f"| {r['collective_s']:.3e} | {r['dominant'].replace('_s','')} "
            f"| {u:.3f} | {mem_gb:.2f} | {'Y' if r['fits_hbm'] else 'N'} "
            f"| {r['roofline_fraction']:.4f} |"
        )
    return rows, "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="pod16x16")
    ap.add_argument("--smoke", action="store_true",
                    help="write to a temp path, never experiments/bench")
    ap.add_argument("--out", help="write the table here instead")
    args = ap.parse_args(argv)
    rows, md = table(args.mesh)
    print(md)
    name = f"roofline_{args.mesh}"
    path = save_result(name, {"rows": rows, "markdown": md},
                       out=smoke_out_path(name, args.smoke, args.out))
    print(f"wrote {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
