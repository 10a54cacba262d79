"""Paper Figure 4: distributed strong scaling — updates/s vs node count.

Two sweeps on an ml-100k-shaped synthetic:

  * in-process width sweep — ring meshes of 1/2/4/8 forced host devices
    (subsets of one 8-device process), updates/s for both comm modes
    (``ring`` = the paper's async pipelined version, ``allgather`` = the
    synchronous GraphLab-like baseline);
  * process-count sweep — the *same global device total* re-split across
    1/2/4 OS processes via ``scripts/launch_multiproc.py`` (DESIGN.md §14),
    sweeps/s per layout plus modelled vs trace-measured ring bytes per
    sweep. The compiled program is layout-independent (the multi-process
    parity claim), so the wire bytes are modelled once per global width and
    only the *cross-process* share varies with the process count.

The paper's >32-node degradation (BlueGene rack boundary) corresponds here
to the pod boundary; the projection to 256/512 chips comes from the dry-run
roofline terms (``python -m repro.launch.dryrun``), not wall time.

Run me via: python -m benchmarks.fig4_scaling (inside an
XLA_FLAGS=--xla_force_host_platform_device_count=8 process; benchmarks.run
does this automatically).
"""
from __future__ import annotations

import os
import re
import subprocess
import sys
import time

import jax
import numpy as np

from benchmarks.common import save_result, smoke_out_path
from repro.core.distributed import build_distributed_data, make_ring_mesh, run_distributed
from repro.core.types import BPMFConfig
from repro.data.synthetic import SyntheticSpec, synthetic_ratings

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_FINAL_RE = re.compile(
    r"after (\d+) sweeps \((\d+) this run\) in ([0-9.]+)s"
)


def _ring_bytes_per_sweep(coo, K: int, S: int) -> dict:
    """Modelled vs trace-measured ``ppermute`` traffic of one ring sweep.

    Modelled: each half-sweep issues ``S - 1`` rotations of the opposite
    side's shard buffer on every device, so one sweep moves
    ``S * (S-1) * (cap_u + cap_v) * K * 4`` bytes around the ring. Measured:
    ``jax.lax.ppermute`` is metered during a fresh trace of the sweep — each
    traced call rotates every device's local block once, i.e.
    ``S * block_bytes`` on the wire — then the patch is removed. The two
    must agree; ``model_matches`` records that they do.
    """
    data, plan = build_distributed_data(coo, num_shards=S, seed=0)
    mesh = make_ring_mesh(jax.devices()[:S])
    cap_u, cap_v = plan.part_users.cap, plan.part_movies.cap
    modelled = S * (S - 1) * (cap_u + cap_v) * K * 4

    meter = {"bytes": 0, "calls": 0}
    real_ppermute = jax.lax.ppermute

    def metered(x, axis_name, perm):
        for leaf in jax.tree_util.tree_leaves(x):
            meter["bytes"] += int(np.prod(leaf.shape)) * leaf.dtype.itemsize * S
        meter["calls"] += 1
        return real_ppermute(x, axis_name, perm)

    # a 1-sweep cfg is a fresh jit static key, so the trace (and the meter
    # hits) actually happen even if the width sweep compiled other cfgs
    cfg = BPMFConfig(K=K, num_sweeps=1, burn_in=0, comm_mode="ring")
    jax.lax.ppermute = metered
    try:
        run_distributed(jax.random.key(0), data, cfg, mesh)
    finally:
        jax.lax.ppermute = real_ppermute
    measured = meter["bytes"]
    return {
        "cap_u": int(cap_u),
        "cap_v": int(cap_v),
        "ppermute_calls_traced": meter["calls"],
        "modelled": int(modelled),
        "measured": int(measured),
        "model_matches": bool(measured == modelled),
    }


def _run_layout(procs: int, dev_per_proc: int, spec: SyntheticSpec, K: int,
                sweeps: int, timeout: float) -> dict:
    """One launcher run at ``procs x dev_per_proc``; parse sweeps/s.

    The launcher's children run on the host CPU (``JAX_PLATFORMS=cpu``), so
    none of them needs the device this process may hold.
    """
    cmd = [
        sys.executable, os.path.join(REPO_ROOT, "scripts", "launch_multiproc.py"),
        "--num-processes", str(procs), "--devices-per-process", str(dev_per_proc),
        "--timeout", str(timeout), "--",
        "--backend", "ring", "--dataset", "synthetic",
        "--users", str(spec.num_users), "--movies", str(spec.num_movies),
        "--nnz", str(spec.nnz), "--K", str(K), "--sweeps", str(sweeps),
        "--burn-in", "1", "--log-every", "0",
    ]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(REPO_ROOT, "src"), env.get("PYTHONPATH", "")]
    ).rstrip(os.pathsep)
    t0 = time.time()
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=timeout + 60)
    wall = time.time() - t0
    if r.returncode != 0:
        raise RuntimeError(
            f"layout {procs}x{dev_per_proc} failed rc={r.returncode}:\n"
            f"{r.stdout[-2000:]}\n{r.stderr[-1000:]}"
        )
    m = _FINAL_RE.search(r.stdout)
    if not m:
        raise RuntimeError(
            f"layout {procs}x{dev_per_proc}: no final line in\n{r.stdout[-2000:]}"
        )
    total, this_run, seconds = int(m.group(1)), int(m.group(2)), float(m.group(3))
    return {
        "processes": procs,
        "devices_per_process": dev_per_proc,
        "sweeps": this_run,
        "seconds": seconds,
        # in-loop time of a cold process: first-sweep compile included
        # (documented in experiments/bench/README.md), so layouts compare
        # like-for-like — every child compiles its own program
        "sweeps_per_s": this_run / max(seconds, 1e-9),
        "wall_s": wall,
    }


def run(smoke: bool = False, out: str | None = None) -> dict:
    spec = SyntheticSpec(
        num_users=600 if smoke else 3_000,
        num_movies=300 if smoke else 900,
        nnz=8_000 if smoke else 90_000,
        discretize=False,
    )
    coo, _ = synthetic_ratings(spec)
    K = 8 if smoke else 16
    sweeps = 2 if smoke else 5
    devices = jax.devices()
    widths = [w for w in (1, 2, 4, 8) if w <= len(devices)]

    results: dict = {"widths": widths, "modes": {}, "smoke": bool(smoke)}
    for mode in ("ring", "allgather"):
        rows = []
        for w in widths:
            cfg = BPMFConfig(K=K, num_sweeps=sweeps, burn_in=1, comm_mode=mode)
            data, _plan = build_distributed_data(coo, num_shards=w, seed=0)
            mesh = make_ring_mesh(devices[:w])
            t0 = time.time()  # includes first-sweep compile; subtract below
            state, pred, hist = run_distributed(jax.random.key(0), data, cfg, mesh)
            t_total = time.time() - t0
            # steady-state: time sweeps after compile
            t0 = time.time()
            state, pred, hist = run_distributed(jax.random.key(1), data, cfg, mesh)
            t_steady = time.time() - t0
            ups = (coo.num_users + coo.num_movies) * sweeps / t_steady
            rows.append({
                "devices": w, "seconds": t_steady, "updates_per_s": ups,
                "rmse_final": hist[-1].rmse_avg, "compile_plus_run_s": t_total,
            })
            print(f"[fig4] {mode} w={w}: {ups:,.0f} updates/s rmse={hist[-1].rmse_avg:.4f}")
        base = rows[0]["updates_per_s"]
        for r in rows:
            r["speedup"] = r["updates_per_s"] / base
        results["modes"][mode] = rows

    # ---- process-count sweep: same global width, re-split across processes
    S = 4 if smoke else 8
    S = min(S, len(devices))
    proc_spec = SyntheticSpec(
        num_users=240 if smoke else 800,
        num_movies=160 if smoke else 400,
        nnz=3_000 if smoke else 12_000,
        discretize=False,
    )
    proc_coo, _ = synthetic_ratings(proc_spec)
    proc_sweeps = 2 if smoke else 4
    bytes_info = _ring_bytes_per_sweep(proc_coo, K, S)
    per_edge = bytes_info["modelled"] // S  # one ring edge's bytes per sweep
    layouts = [(p, S // p) for p in (1, 2, 4) if p <= S and S % p == 0]
    rows = []
    for procs, dev in layouts:
        row = _run_layout(procs, dev, proc_spec, K, proc_sweeps,
                          timeout=240 if smoke else 600)
        # process-major contiguous blocks: exactly `procs` of the S ring
        # edges cross a process boundary (none for a single process — the
        # wraparound edge stays on-host)
        row["cross_process_bytes_per_sweep"] = per_edge * procs if procs > 1 else 0
        rows.append(row)
        print(f"[fig4] procs={procs}x{dev}: {row['sweeps_per_s']:.3f} sweeps/s "
              f"cross-proc {row['cross_process_bytes_per_sweep']:,} B/sweep")
    results["process_sweep"] = {
        "global_devices": S,
        "K": K,
        "dataset": {"num_users": proc_coo.num_users,
                    "num_movies": proc_coo.num_movies, "nnz": int(proc_coo.nnz)},
        "ring_bytes_per_sweep": bytes_info,
        "layouts": rows,
    }

    path = save_result("fig4_scaling", results, out=out)
    print(f"[fig4] wrote {path}")
    return results


if __name__ == "__main__":
    smoke = "--smoke" in sys.argv
    out = None
    if "--out" in sys.argv:
        out = sys.argv[sys.argv.index("--out") + 1]
    run(smoke=smoke, out=smoke_out_path("fig4_scaling", smoke, out))
