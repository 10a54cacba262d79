"""Engine configuration: the model / run / backend split.

The legacy ``repro.core.types.BPMFConfig`` mixed three concerns into one
flat dataclass: what the model *is* (K, alpha, prior), how long to *run*
(sweeps, burn-in) and *where/how* to execute (comm_mode, gram_impl).
The engine API separates them so that switching execution backends —
sequential, ring, allgather, Pallas on or off — is a config knob with no
model or schedule implications:

  * :class:`ModelConfig`   — the statistical model (paper §III)
  * :class:`RunConfig`     — schedule, data split, checkpointing
  * :class:`BackendConfig` — execution: backend name, shard count, kernels

``BPMFConfig`` (this module's, not ``core.types``') bundles the three and
lowers to the legacy flat config via :meth:`BPMFConfig.core` for the
kernel-level code, which stays untouched.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any

import jax.numpy as jnp

from repro.core import types as core_types

_GRAM_IMPLS = ("auto", "pallas_fused", "pallas", "xla")
MODEL_FAMILIES = ("bpmf", "bptf")
_USE_PALLAS_WARNED = False


def _warn_use_pallas_once() -> None:
    """Emit the ``use_pallas`` deprecation warning exactly once per process."""
    global _USE_PALLAS_WARNED
    if not _USE_PALLAS_WARNED:
        _USE_PALLAS_WARNED = True
        warnings.warn(
            "BackendConfig.use_pallas is deprecated; use gram_impl="
            '"auto" | "pallas" | "xla" instead (use_pallas=True -> "pallas", '
            'False -> "xla")',
            DeprecationWarning,
            stacklevel=3,
        )


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """The BPMF model itself (paper §III): rank, noise and prior.

    Attributes:
        family: ``"bpmf"``, the two-sided matrix model, or ``"bptf"``, the
            temporal tensor model ``R_ijk ~ <U_i, V_j, T_k>`` of Xiong et al.
            (SDM 2010), whose time factors follow a Markov chain; it needs
            ratings with time bins and runs on the ``sequential`` backend.
        K: Latent rank of the factorization ``R ~ U @ V.T``.
        alpha: Rating noise precision (likelihood ``N(r | u·v, 1/alpha)``).
        beta0: Normal-Wishart prior strength on the factor means.
        sample_dtype: dtype of the stored factor samples.
        compute_dtype: dtype of the Gram contraction operands: f32 (the
            default) or bf16; accumulation is f32 either way.
    """

    family: str = "bpmf"  # bpmf | bptf
    K: int = 32
    alpha: float = 2.0  # rating noise precision
    beta0: float = 2.0  # Normal-Wishart prior strength
    sample_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32  # Gram contraction dtype (f32 or bf16)

    def __post_init__(self) -> None:
        if self.family not in MODEL_FAMILIES:
            raise ValueError(
                f"ModelConfig.family must be one of {MODEL_FAMILIES}, got {self.family!r}"
            )


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Schedule, data split and checkpoint policy for one fit.

    Attributes:
        num_sweeps: Total Gibbs sweeps for :meth:`BPMFEngine.fit`.
        burn_in: Sweeps discarded before the posterior-mean accumulator
            starts averaging predictions.
        seed: Seeds both the train/test split and the sampler key, so one
            integer pins the whole run.
        sweeps_per_block: Gibbs sweeps executed per jitted device block
            (DESIGN.md §10). The engine's run loop dispatches blocks of this
            many sweeps through one ``lax.scan`` with **no host sync inside
            the block** — posterior-mean sums, the recent-sample window and
            the prediction accumulator all fold on-device, and each block
            returns its per-sweep metrics in a single ``[block, 3]``
            transfer. ``1`` reproduces the historical per-sweep dispatch
            cadence; samples and artifacts are bitwise identical at every
            value. Blocks shrink automatically to land exactly on
            ``checkpoint_every`` boundaries and the final sweep.
        test_fraction: Held-out fraction for RMSE tracking.
        checkpoint_dir: Where :meth:`BPMFEngine.save` writes; ``None``
            disables checkpointing.
        checkpoint_every: Sweeps between auto-saves; 0 = explicit
            ``save()`` only.
        keep_checkpoints: Retention window (older steps are pruned).
        pipeline_blocks: Depth of the engine's block dispatch queue
            (DESIGN.md §13). With depth d > 1 the run loop launches the
            next device block on the still-on-device carry *before*
            fetching the previous block's metrics, so the host never sits
            between blocks; metric transfers complete asynchronously and
            drain d-1 blocks behind the dispatch front. The queue drains
            fully at ``checkpoint_every`` boundaries, at user ``save()`` /
            ``export()`` calls and at the end of the run, so the
            one-``SweepMetrics``-per-sweep iterator contract, history
            ordering and checkpoint cadence are bitwise identical at every
            depth. ``1`` reproduces the synchronous PR-5 loop.
        async_checkpoint_writes: Write checkpoints on the manager's
            background thread (DESIGN.md §13): ``save()`` snapshots host
            arrays and returns without waiting for the filesystem commit,
            keeping checkpoints off the dispatch critical path. The commit
            itself stays atomic (tmp-dir rename + ``LATEST`` replace);
            ``export()`` / ``restore()`` / process exit drain pending
            writes. ``False`` restores fully synchronous saves.
        keep_factor_samples: Most recent post-burn-in ``(U, V)`` samples
            retained for the serving artifact's predictive-std output
            (DESIGN.md §9); 0 keeps only the running posterior mean and
            disables ``return_std`` on the exported predictor.
    """

    num_sweeps: int = 50
    burn_in: int = 8
    seed: int = 0  # seeds both the train/test split and the sampler key
    sweeps_per_block: int = 8  # sweeps per jitted device block (1 = per-sweep)
    pipeline_blocks: int = 1  # block dispatch queue depth (1 = synchronous)
    async_checkpoint_writes: bool = True  # background checkpoint commit
    test_fraction: float = 0.1
    checkpoint_dir: str | None = None
    checkpoint_every: int = 0  # sweeps between auto-saves; 0 = explicit save() only
    keep_checkpoints: int = 3
    keep_factor_samples: int = 8  # recent post-burn-in samples for predictive std

    def __post_init__(self) -> None:
        if self.keep_factor_samples < 0:
            raise ValueError(
                f"RunConfig.keep_factor_samples must be >= 0, "
                f"got {self.keep_factor_samples}"
            )
        if self.sweeps_per_block < 1:
            raise ValueError(
                f"RunConfig.sweeps_per_block must be >= 1, "
                f"got {self.sweeps_per_block}"
            )
        if self.pipeline_blocks < 1:
            raise ValueError(
                f"RunConfig.pipeline_blocks must be >= 1, "
                f"got {self.pipeline_blocks}"
            )


@dataclasses.dataclass(frozen=True)
class BackendConfig:
    """Execution backend selection — the knob the paper's §V compares.

    ``name`` picks an entry from the backend registry
    (:mod:`repro.bpmf.backends`): ``"sequential"`` (single-program oracle),
    ``"ring"`` (paper §IV-C overlap schedule), ``"ring_async"`` (depth-d
    pipelined ring, arXiv:1705.10633 / DESIGN.md §7), ``"allgather"``
    (synchronous baseline) or ``"posterior_merge"`` (embarrassingly-parallel
    partition chains + subset-posterior merge, arXiv:1703.00734 /
    DESIGN.md §12).

    Attributes:
        name: Backend registry key; see
            :func:`repro.bpmf.available_backends`.
        num_shards: Ring length for the distributed backends; 0 means one
            shard per visible device. Ignored by ``"sequential"``.
        pipeline_depth: ``ring_async`` only — number of shard rotations
            kept in flight (d >= 1). d=1 reproduces the ``"ring"``
            schedule; larger d hides more link latency at the cost of d
            resident opposite-shard buffers per device. Clamped to the
            ring length; samples are bit-identical for every d.
        gram_impl: Gram hot-path dispatch (DESIGN.md §8): ``"auto"``
            (default — per-shape autotune cache, deterministic heuristic
            fallback: Pallas where it wins on TPU, XLA on CPU),
            ``"pallas"`` (force the per-bucket kernel), ``"xla"`` (force
            the gather+einsum path). ``"pallas_fused"`` forces the fused
            one-kernel-per-ring-step path (mainly tests/benchmarks —
            ``"auto"`` selects it when profitable).
        use_pallas: **Deprecated** boolean forerunner of ``gram_impl``;
            passing it warns once and maps ``True -> "pallas"``,
            ``False -> "xla"``.
        bucket_pads: Neighbor-count pad classes for the dense bucketed
            layout (``data/sparse.py``); items bucket into the smallest
            pad >= their rating count.
        partition_strategy: Cost-model load balancing of items onto
            shards (paper §IV-B): ``"lpt"`` (longest-processing-time) or
            ``"block"`` (contiguous). ``posterior_merge`` reuses it to
            balance users across chains.
        num_partitions: ``posterior_merge`` only — number of independent
            partition chains; 0 means one chain per visible device.
            Ignored by every other backend.
        merge_method: ``posterior_merge`` only — subset-posterior
            combination: ``"precision"`` (precision-weighted Gaussian
            product estimated from the chains' sample windows,
            arXiv:1703.00734; falls back to pooling when fewer than two
            window samples exist) or ``"pool"`` (uniform-weight pooling).
        donate_blocks: Whether the engine's block programs donate their
            carry buffers (``donate_argnums`` on state / prediction /
            posterior accumulators, DESIGN.md §13) so XLA writes each
            block's outputs into the previous block's buffers instead of
            doubling peak factor memory: ``"auto"`` (default — donate;
            samples are unaffected, only buffer reuse changes),
            ``"on"``, or ``"off"`` (the fallback path: every block
            allocates fresh outputs, inputs stay readable — use when
            wrapping ``sweep_block`` with code that re-reads its inputs).
    """

    name: str = "sequential"
    num_shards: int = 0  # 0 = one shard per visible device (distributed only)
    pipeline_depth: int = 1  # ring_async: rotations in flight (d >= 1)
    gram_impl: str = "auto"  # Gram dispatch: auto | pallas_fused | pallas | xla
    use_pallas: bool | None = None  # deprecated: use gram_impl
    bucket_pads: tuple[int, ...] = (8, 32, 128, 512, 2048)
    partition_strategy: str = "lpt"  # cost-model balancing (paper §IV-B)
    num_partitions: int = 0  # posterior_merge: chains (0 = one per device)
    merge_method: str = "precision"  # posterior_merge: precision | pool
    donate_blocks: str = "auto"  # block carry donation: auto | on | off

    def __post_init__(self) -> None:
        if self.donate_blocks not in ("auto", "on", "off"):
            raise ValueError(
                f'BackendConfig.donate_blocks must be "auto", "on" or "off", '
                f"got {self.donate_blocks!r}"
            )
        if self.pipeline_depth < 1:
            raise ValueError(
                f"BackendConfig.pipeline_depth must be >= 1, got {self.pipeline_depth}"
            )
        if self.num_partitions < 0:
            raise ValueError(
                f"BackendConfig.num_partitions must be >= 0, got {self.num_partitions}"
            )
        if self.merge_method not in ("precision", "pool"):
            raise ValueError(
                f'BackendConfig.merge_method must be "precision" or "pool", '
                f"got {self.merge_method!r}"
            )
        if self.use_pallas is not None:
            if self.gram_impl != "auto":
                raise ValueError(
                    f"BackendConfig: both gram_impl={self.gram_impl!r} and the "
                    f"deprecated use_pallas={self.use_pallas} were given — drop "
                    "use_pallas"
                )
            _warn_use_pallas_once()
            object.__setattr__(self, "gram_impl", "pallas" if self.use_pallas else "xla")
            # consume the legacy flag so later replace(gram_impl=...) calls
            # are not silently clobbered by the retained boolean (and
            # use_pallas=True == gram_impl="pallas" configs hash equal)
            object.__setattr__(self, "use_pallas", None)
        if self.gram_impl not in _GRAM_IMPLS:
            raise ValueError(
                f"BackendConfig.gram_impl must be one of {_GRAM_IMPLS}, "
                f"got {self.gram_impl!r}"
            )


@dataclasses.dataclass(frozen=True)
class BPMFConfig:
    """Everything :class:`repro.bpmf.BPMFEngine` needs, in one object."""

    model: ModelConfig = ModelConfig()
    run: RunConfig = RunConfig()
    backend: BackendConfig = BackendConfig()

    def core(self) -> core_types.BPMFConfig:
        """Lower to the legacy flat (hashable) config used by the kernels.

        Returns:
            A :class:`repro.core.types.BPMFConfig` suitable as a jit
            static argument. Backend names that are also core comm modes
            (``ring`` / ``ring_async`` / ``allgather``) pass through as
            ``comm_mode``; anything else (e.g. ``sequential``) lowers to
            ``"ring"``, which the sequential sampler ignores.
        """
        comm_modes = ("ring", "ring_async", "allgather")
        comm_mode = self.backend.name if self.backend.name in comm_modes else "ring"
        return core_types.BPMFConfig(
            K=self.model.K,
            alpha=self.model.alpha,
            num_sweeps=self.run.num_sweeps,
            burn_in=self.run.burn_in,
            beta0=self.model.beta0,
            bucket_pads=tuple(self.backend.bucket_pads),
            comm_mode=comm_mode,
            pipeline_depth=self.backend.pipeline_depth,
            sample_dtype=self.model.sample_dtype,
            compute_dtype=self.model.compute_dtype,
            gram_impl=self.backend.gram_impl,
        )

    def replace(self, **kw: Any) -> "BPMFConfig":
        """`dataclasses.replace` that also reaches one level down.

        Keys matching a sub-config field are routed there, so
        ``cfg.replace(name="ring_async", pipeline_depth=2, num_sweeps=10)``
        works without spelling out the nesting.

        Args:
            **kw: Field overrides; each key must name a ``BPMFConfig``
                field or a field of exactly one sub-config.

        Returns:
            A new :class:`BPMFConfig` with the overrides applied.

        Raises:
            TypeError: If a key matches no field anywhere.
        """
        subs = {"model": self.model, "run": self.run, "backend": self.backend}
        updates: dict[str, dict[str, Any]] = {k: {} for k in subs}
        top: dict[str, Any] = {}
        for key, val in kw.items():
            if key in subs:
                top[key] = val
                continue
            for sub_name, sub in subs.items():
                if any(f.name == key for f in dataclasses.fields(sub)):
                    updates[sub_name][key] = val
                    break
            else:
                raise TypeError(f"unknown BPMFConfig field: {key!r}")
        for sub_name, up in updates.items():
            if up:
                top[sub_name] = dataclasses.replace(subs[sub_name], **up)
        return dataclasses.replace(self, **top)
