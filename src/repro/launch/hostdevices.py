"""Force a host (CPU) device count / join a multi-process job before jax init.

jax locks the device count at first backend init, so every CLI that offers
``--devices N`` must rewrite ``XLA_FLAGS`` *before* any jax import — which
is why this helper imports nothing heavy at module scope and why the CLIs
parse arguments first. Shared by ``repro.launch.bpmf`` and
``repro.launch.serve`` (tests/conftest.py keeps its own copy because it
edits a subprocess env dict, not this process).

Multi-process path (DESIGN.md §14): :func:`init_multiprocess` wires this
process into a ``jax.distributed`` job — coordinator address plus process
count/id from CLI flags or the ``REPRO_COORDINATOR`` / ``REPRO_NUM_PROCESSES``
/ ``REPRO_PROCESS_ID`` environment (the env route is what
``scripts/launch_multiproc.py`` uses). Call order matters: the host device
count must be forced first, then the distributed service initialized, and
only then may any jax backend spin up.

:func:`enable_compile_cache` is the one place the persistent compilation
cache is configured; entry points call it, library code and tests do not.
"""
from __future__ import annotations

import os
import re
import sys

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def multiprocess_active() -> bool:
    """True once ``jax.distributed.initialize`` has run in this process."""
    if "jax" not in sys.modules:
        return False  # jax never imported -> distributed cannot be active
    try:
        from jax._src import distributed

        return distributed.global_state.coordinator_address is not None
    except Exception:  # pragma: no cover - internal layout moved
        return False


def force_host_device_count(n: int) -> None:
    """Rewrite ``XLA_FLAGS`` so jax sees ``n`` host devices.

    Strips any inherited ``--xla_force_host_platform_device_count`` flag so
    the requested count always wins. Must run before jax initializes; a
    no-op for ``n <= 0``. Refused outright once ``jax.distributed`` is
    active: the global device list is already agreed across processes at
    that point, and a silent per-process rewrite would fail far away from
    the cause (mismatched meshes mid-collective).

    Args:
        n: Host device count to force.
    """
    if n <= 0:
        return
    if multiprocess_active():
        raise RuntimeError(
            "cannot force the host device count after jax.distributed is "
            "initialized — pass the per-process device count to "
            "init_multiprocess(local_devices=...) (CLI: put --devices before "
            "the coordinator flags are acted on, which the repro CLIs do)"
        )
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+",
        "",
        os.environ.get("XLA_FLAGS", ""),
    ).strip()
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}".strip()
    )


def init_multiprocess(
    coordinator: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    local_devices: int = 0,
) -> bool:
    """Join a multi-process jax job if one is configured; else no-op.

    Flag values win over the ``REPRO_COORDINATOR`` / ``REPRO_NUM_PROCESSES``
    / ``REPRO_PROCESS_ID`` environment. Returns True when the distributed
    service was initialized (after which ``jax.devices()`` is the global,
    process-major device list), False for a plain single-process run.

    ``local_devices`` forces the per-process host (CPU) device count and is
    applied *before* the backend initializes — the only ordering jax
    accepts. CPU cross-process collectives are routed through gloo, which
    must also be configured pre-backend.
    """
    coordinator = coordinator or os.environ.get("REPRO_COORDINATOR") or None
    if num_processes is None and os.environ.get("REPRO_NUM_PROCESSES"):
        num_processes = int(os.environ["REPRO_NUM_PROCESSES"])
    if process_id is None and os.environ.get("REPRO_PROCESS_ID"):
        process_id = int(os.environ["REPRO_PROCESS_ID"])

    if coordinator is None:
        if num_processes not in (None, 1) or process_id not in (None, 0):
            raise ValueError(
                "got --num-processes/--process-id without a --coordinator "
                "address (or REPRO_COORDINATOR)"
            )
        force_host_device_count(local_devices)
        return False
    if num_processes is None or process_id is None:
        raise ValueError(
            "multi-process init needs all of coordinator, num_processes and "
            f"process_id (got {coordinator=}, {num_processes=}, {process_id=})"
        )

    force_host_device_count(local_devices)
    import jax

    # CPU backend: cross-process collectives need the gloo implementation,
    # selected before the backend exists. No-op for TPU/GPU backends.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    return True


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is jax's own setting: it is
    left to jax and nothing is set here. Otherwise the cache lives at the
    fixed ``<repo>/.jax_cache`` (git-ignored), so a later run of the same
    program finds its entries. Called by the entry points only
    (``repro.launch.bpmf``, ``repro.launch.serve``,
    ``repro.launch.serve_server``, ``chip_smoke.py``).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
