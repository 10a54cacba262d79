"""Shared benchmark utilities: result IO, argument scaling."""
from __future__ import annotations

import json
import os
from typing import Any

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments", "bench")


def save_result(name: str, payload: dict[str, Any], out: str | None = None) -> str:
    """Write a benchmark payload as JSON.

    Default target is the committed ``experiments/bench/<name>.json``; pass
    ``out`` to redirect (smoke runs MUST redirect so they never clobber the
    committed full-size numbers — see smoke_out_path)."""
    path = out or os.path.join(OUT_DIR, f"{name}.json")
    os.makedirs(os.path.dirname(os.path.abspath(path)) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=str)
    return os.path.normpath(path)


def smoke_out_path(name: str, smoke: bool, out: str | None) -> str | None:
    """Resolve a benchmark's output path honouring the smoke contract.

    Smoke runs never write the committed ``experiments/bench/<name>.json``:
    with ``smoke`` set and no explicit ``--out``, results go to a temp file
    (path printed by the benchmark). An explicit ``out`` always wins.
    """
    if out:
        return out
    if smoke:
        import tempfile

        return os.path.join(tempfile.mkdtemp(prefix=f"bench-{name}-"), f"{name}.json")
    return None


def run_with_devices(
    module: str, num_devices: int | None, timeout: int = 1200, smoke: bool = False,
) -> str:
    """Run ``python -m <module>`` in a subprocess, with N forced host devices
    unless ``num_devices`` is None (the device count is locked at jax init,
    so multi-device benchmarks need their own process). The caller must not
    have touched jax: a parent that holds the chip leaves none for the child.
    """
    import subprocess
    import sys

    env = dict(os.environ)
    if num_devices is not None:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={num_devices}"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         os.path.join(os.path.dirname(__file__), ".."),
         env.get("PYTHONPATH", "")]
    )
    cmd = [sys.executable, "-m", module] + (["--smoke"] if smoke else [])
    r = subprocess.run(cmd, env=env, capture_output=True, text=True, timeout=timeout)
    if r.returncode != 0:
        raise RuntimeError(f"{module} failed:\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}")
    return r.stdout
