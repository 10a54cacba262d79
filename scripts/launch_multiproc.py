#!/usr/bin/env python
"""N-local-process launcher for multi-process BPMF runs (DESIGN.md §14).

Spawns N copies of ``python -m repro.launch.bpmf`` on this host, wires them
into one jax.distributed job via the ``REPRO_*`` environment (coordinator on
a freshly-bound localhost port, process ids 0..N-1), and gives each child
``--devices M`` host CPU devices so the global ring mesh spans N*M devices.
Everything after ``--`` is forwarded to every child verbatim::

    PYTHONPATH=src python scripts/launch_multiproc.py \
        --num-processes 2 --devices-per-process 4 -- \
        --backend ring --sweeps 8 --checkpoint-dir /tmp/ck --checkpoint-every 2

With ``--elastic``, a dying child triggers the restart policy
(repro.runtime.elastic.RestartPolicy): the survivors are killed, and the
job respawns with ``--resume`` at the largest smaller process count that
still divides the same global device total — S is preserved, so the
checkpointed ring carries reshard onto the new process-spanning mesh and
the samples continue bitwise-identically. ``--num-processes 1`` runs the
child directly with no coordinator (plain single-process path).

This is a CPU-gang tool: every child runs with ``JAX_PLATFORMS=cpu``, also
on a host with a TPU, because N processes cannot share one chip.
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import threading
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python scripts/launch_multiproc.py",
        description="Run repro.launch.bpmf as N local jax processes on "
                    "the host CPU (JAX_PLATFORMS=cpu in every child, also "
                    "on a TPU host); args after -- are forwarded to every "
                    "process.",
    )
    p.add_argument("--num-processes", type=int, default=2)
    p.add_argument("--devices-per-process", type=int, default=4,
                   help="host (CPU) devices per process; the ring mesh "
                        "spans num-processes * devices-per-process")
    p.add_argument("--elastic", action="store_true",
                   help="on a child failure, respawn at a smaller process "
                        "count (same global device total) with --resume; "
                        "requires --checkpoint-dir in the forwarded args")
    p.add_argument("--max-restarts", type=int, default=2,
                   help="elastic restart budget before giving up")
    p.add_argument("--timeout", type=float, default=600.0,
                   help="seconds before the whole job is killed")
    return p


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _pump(proc: subprocess.Popen, tag: str) -> None:
    """Forward one child's output line-by-line under a [pI] prefix."""
    for line in proc.stdout:  # type: ignore[union-attr]
        sys.stdout.write(f"[{tag}] {line}")
        sys.stdout.flush()


def run_once(num_processes: int, devices: int, forward: list[str],
             timeout: float) -> int:
    """One launch at a fixed layout; returns the first nonzero child rc (or 0).

    A child dying does not tear down its peers by itself — they block in the
    next gloo collective — so any nonzero exit kills the rest of the gang
    immediately (the cluster-manager behavior the restart policy assumes).
    """
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # N processes cannot share one chip
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(REPO_ROOT, "src"), env.get("PYTHONPATH", "")]
    ).rstrip(os.pathsep)
    if num_processes > 1:
        env["REPRO_COORDINATOR"] = f"127.0.0.1:{_free_port()}"
        env["REPRO_NUM_PROCESSES"] = str(num_processes)
    else:
        for k in ("REPRO_COORDINATOR", "REPRO_NUM_PROCESSES", "REPRO_PROCESS_ID"):
            env.pop(k, None)

    procs: list[subprocess.Popen] = []
    pumps: list[threading.Thread] = []
    for i in range(num_processes):
        child_env = dict(env)
        if num_processes > 1:
            child_env["REPRO_PROCESS_ID"] = str(i)
        proc = subprocess.Popen(
            [sys.executable, "-m", "repro.launch.bpmf",
             "--devices", str(devices), *forward],
            env=child_env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        procs.append(proc)
        t = threading.Thread(target=_pump, args=(proc, f"p{i}"), daemon=True)
        t.start()
        pumps.append(t)

    rc = 0
    try:
        remaining = {i: p for i, p in enumerate(procs)}
        t0 = time.time()
        while remaining:
            for i, p in list(remaining.items()):
                child_rc = p.poll()
                if child_rc is None:
                    continue
                del remaining[i]
                if child_rc != 0 and rc == 0:
                    rc = child_rc
                    print(f"[launcher] process {i} exited rc={child_rc}; "
                          "killing peers", flush=True)
            if rc != 0:
                break
            if time.time() - t0 > timeout:
                print("[launcher] timeout; killing job", flush=True)
                rc = 124
                break
            time.sleep(0.05)
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGKILL)
        for p in procs:
            p.wait()
        for t in pumps:
            t.join(timeout=5)
    return rc


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--" in argv:
        split = argv.index("--")
        own, forward = argv[:split], argv[split + 1:]
    else:
        own, forward = argv, []
    args = build_parser().parse_args(own)

    if args.elastic and "--checkpoint-dir" not in forward:
        print("--elastic needs --checkpoint-dir (and --checkpoint-every) in "
              "the forwarded args so the respawn has something to resume",
              file=sys.stderr)
        return 2

    from repro.runtime.elastic import RestartPolicy  # light import, no jax

    num_processes = args.num_processes
    devices = args.devices_per_process
    policy = RestartPolicy(
        total_devices=num_processes * devices, max_restarts=args.max_restarts
    )

    rc = run_once(num_processes, devices, forward, args.timeout)
    while rc != 0 and args.elastic:
        layout = policy.next_layout(num_processes)
        if layout is None:
            print("[launcher] restart policy exhausted", flush=True)
            return rc
        num_processes, devices = layout
        print(f"[launcher] elastic restart: {num_processes} processes x "
              f"{devices} devices, resuming", flush=True)
        resumed = forward if "--resume" in forward else [*forward, "--resume"]
        rc = run_once(num_processes, devices, resumed, args.timeout)
    return rc


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(REPO_ROOT, "src"))
    sys.exit(main())
