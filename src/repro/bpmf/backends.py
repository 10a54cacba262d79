"""Backend registry: one sampler, several execution strategies.

The paper's §V-B claim — sequential, shared-memory and distributed BPMF are
the *same sampler* — is encoded here as a small protocol: every backend
prepares its own data layout from the same :class:`RatingsCOO`, but draws
identical posterior samples for identical ``(key, data)`` (up to float
reduction order). ``BPMFEngine`` dispatches to a registry entry by
``BackendConfig.name``; later scaling PRs add entries instead of new entry
points.

Registered backends:

  * ``"sequential"`` — wraps :mod:`repro.core.gibbs` (single program)
  * ``"ring"``       — wraps :mod:`repro.core.distributed`, §IV-C overlap
  * ``"ring_async"`` — same, with ``BackendConfig.pipeline_depth`` ring
    rotations kept in flight (arXiv:1705.10633; DESIGN.md §7)
  * ``"allgather"``  — same, synchronous all-gather baseline
  * ``"posterior_merge"`` — embarrassingly-parallel partition chains with a
    subset-posterior merge at export (arXiv:1703.00734 / 2004.02561;
    DESIGN.md §12) — zero inter-chain traffic during sampling
"""
from __future__ import annotations

import abc
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.bpmf.config import BPMFConfig
from repro.checkpoint import ShardedHostLeaf
from repro.core import distributed as dist
from repro.core import gibbs
from repro.core import subset_merge
from repro.core.gibbs import SweepMetrics
from repro.core.prediction import PredictionState
from repro.core.subset_merge import MergeAccum
from repro.core.types import BPMFState, HyperParams, PosteriorAccum
from repro.data.sparse import (
    ChunkedRatings,
    RatingsCOO,
    build_bpmf_data,
    build_bpmf_data_presplit,
    train_test_split,
)

BACKENDS: dict[str, type["Backend"]] = {}

_EMPTY_SUM = np.zeros((0, 0), np.float32)
_EMPTY_STACK = np.zeros((0, 0, 0), np.float32)


def _window_slots(count: int, keep: int, available: int) -> np.ndarray:
    """Rotating-buffer slots of the most recent samples, oldest first.

    Post-burn-in sample ``i`` lives at slot ``i % keep``; the ``S`` most
    recent retained samples are global indices ``count - S .. count - 1``.
    ``available`` caps ``S`` (a restored checkpoint may carry fewer samples
    than the window holds).
    """
    S = min(count, keep, available)
    return np.arange(count - S, count, dtype=np.int64) % max(keep, 1)


def accum_host_tree(
    accum: PosteriorAccum,
    u_order: np.ndarray | None = None,
    v_order: np.ndarray | None = None,
) -> dict:
    """Host view of a device accumulator in the PR-4 checkpoint schema.

    Returns the fixed-key ``{"U_sum", "V_sum", "count", "U_samples",
    "V_samples"}`` dict the ``"posterior"`` checkpoint subtree has always
    used: sums are ``(0, 0)``-shaped until the first post-burn-in sample,
    and the sample stacks are chronological (oldest kept draw first) —
    bitwise what the old host-side accumulator checkpointed, so pre-block
    checkpoints restore and new checkpoints match old readers.

    Args:
        accum: Device accumulator (any sharding; gathered here).
        u_order / v_order: Optional relabeled->original permutations
            (``plan.part_*.perm``) applied to the item axis, for the
            distributed backends. Pass both or neither.
    """
    if (u_order is None) != (v_order is None):
        raise ValueError("accum_host_tree: pass both u_order and v_order, or neither")
    count = int(accum.count)
    keep = accum.keep
    if count == 0:
        U_sum, V_sum = _EMPTY_SUM, _EMPTY_SUM
    else:
        # fetch_global: a collective host gather when the accumulator is
        # sharded across processes (every process calls accum_host together)
        U_sum = dist.fetch_global(accum.U_sum)
        V_sum = dist.fetch_global(accum.V_sum)
        if u_order is not None:
            U_sum, V_sum = U_sum[u_order], V_sum[v_order]
    slots = _window_slots(count, keep, int(accum.filled))
    if slots.size:
        Us = dist.fetch_global(accum.U_window)[slots]
        Vs = dist.fetch_global(accum.V_window)[slots]
        if u_order is not None:
            Us, Vs = Us[:, u_order], Vs[:, v_order]
    else:
        Us, Vs = _EMPTY_STACK, _EMPTY_STACK
    return {
        "U_sum": U_sum,
        "V_sum": V_sum,
        "count": np.asarray(count, np.int32),
        "U_samples": Us,
        "V_samples": Vs,
    }


def accum_from_host_tree(
    tree: dict,
    template: PosteriorAccum,
    u_scatter: np.ndarray | None = None,
    v_scatter: np.ndarray | None = None,
) -> PosteriorAccum:
    """Rebuild a device accumulator from :func:`accum_host_tree` output.

    Inverse of the host view: chronological sample stacks go back to their
    rotating-buffer slots (``(count - S + j) % keep``), so a restore at any
    sweep reproduces bitwise the window an uninterrupted device run holds.
    Checkpoints written with a different ``keep`` restore the most recent
    ``min(S, keep)`` samples.

    Args:
        tree: Host arrays (np or device) in the checkpoint schema.
        template: Zeroed accumulator in the backend's internal layout
            (shapes/sharding to restore into).
        u_scatter / v_scatter: Optional original->relabeled permutations
            (``plan.part_*.perm``) mapping host rows into shard slots.
            Pass both or neither.
    """
    if (u_scatter is None) != (v_scatter is None):
        raise ValueError(
            "accum_from_host_tree: pass both u_scatter and v_scatter, or neither"
        )
    count = int(np.asarray(tree["count"]))
    keep = template.keep
    shape_u = template.U_sum.shape  # internal layout [M or S*cap, K]
    shape_v = template.V_sum.shape

    def to_internal(host: np.ndarray, shape, scatter) -> np.ndarray:
        out = np.zeros(shape, np.float32)
        host = np.asarray(host, np.float32)
        if scatter is None:
            out[: host.shape[0]] = host
        else:
            out[scatter] = host
        return out

    U_sum = np.zeros(shape_u, np.float32)
    V_sum = np.zeros(shape_v, np.float32)
    if count:
        U_sum = to_internal(tree["U_sum"], shape_u, u_scatter)
        V_sum = to_internal(tree["V_sum"], shape_v, v_scatter)
    Us = np.asarray(tree["U_samples"], np.float32)
    Vs = np.asarray(tree["V_samples"], np.float32)
    U_win = np.zeros((keep,) + shape_u, np.float32)
    V_win = np.zeros((keep,) + shape_v, np.float32)
    S = min(Us.shape[0], keep, count)
    slots = _window_slots(count, keep, S)
    for j, slot in enumerate(slots):
        # the stacks hold the last Us.shape[0] draws; take their tail
        src = Us.shape[0] - S + j
        U_win[slot] = to_internal(Us[src], shape_u, u_scatter)
        V_win[slot] = to_internal(Vs[src], shape_v, v_scatter)
    return PosteriorAccum(
        U_sum=U_sum,
        V_sum=V_sum,
        count=np.asarray(count, np.int32),
        # only the S slots actually placed are valid: a checkpoint that
        # retained fewer samples than min(count, keep) (e.g. written with a
        # smaller keep) must not report zero-filled slots as samples
        filled=np.asarray(S, np.int32),
        U_window=U_win,
        V_window=V_win,
    )


def register_backend(name: str) -> Callable[[type["Backend"]], type["Backend"]]:
    """Class decorator adding a backend under ``name`` (last wins).

    This is the extension point the ROADMAP's scaling PRs use instead of
    new entry points: subclass :class:`Backend` (or, for shard_map-based
    strategies, :class:`DistributedBackend`), register it, and it becomes
    reachable from the engine, CLI and tests purely through
    ``BackendConfig.name``::

        from repro.bpmf import DistributedBackend, register_backend

        @register_backend("ring_traced")
        class TracedRingBackend(DistributedBackend):
            def sweep_block(self, key, state, pred, accum, block_size):
                out = super().sweep_block(key, state, pred, accum, block_size)
                print(f"block of {block_size} sweeps done")
                return out

        BPMFEngine(BPMFConfig().replace(name="ring_traced")).fit(coo)

    Args:
        name: Registry key; re-registering an existing name replaces it.

    Returns:
        The class decorator; it sets ``cls.name`` and returns the class
        unchanged.
    """

    def deco(cls: type["Backend"]) -> type["Backend"]:
        cls.name = name
        BACKENDS[name] = cls
        return cls

    return deco


def get_backend(cfg: BPMFConfig) -> "Backend":
    """Instantiate the backend named by ``cfg.backend.name``.

    Args:
        cfg: Full engine config; the new backend keeps a reference.

    Returns:
        An unprepared :class:`Backend` instance.

    Raises:
        ValueError: If the name is not in the registry.
    """
    name = cfg.backend.name
    if name not in BACKENDS:
        raise ValueError(f"unknown backend {name!r}; available: {sorted(BACKENDS)}")
    return BACKENDS[name](cfg)


def available_backends() -> list[str]:
    """Sorted registry names (``["allgather", "ring", "ring_async", ...]``)."""
    return sorted(BACKENDS)


class Backend(abc.ABC):
    """Execution strategy for the BPMF Gibbs sampler.

    Lifecycle: ``prepare(coo)`` once (host-side layout), then
    ``init_state(key)`` / ``sweep(key, state, pred)`` repeatedly.
    State pytrees are backend-specific (dense vs ring-sharded) but
    checkpointable as-is; ``factors(state)`` recovers (U, V) in original
    item order for prediction and cross-backend comparison.
    """

    name: str = "?"
    #: Whether the backend draws the exact same posterior samples as
    #: ``sequential`` for the same ``(seed, data)`` (the paper's §V-B
    #: parity claim, enforced by the cross-backend parity tests).
    #: Approximate-inference backends (``posterior_merge``) set it False
    #: and are gated by the statistical harness instead.
    exact_parity: bool = True
    #: The model families (``ModelConfig.family``) the backend can run.
    families: tuple[str, ...] = ("bpmf",)

    def __init__(self, cfg: BPMFConfig):
        self.cfg = cfg
        self.core_cfg = cfg.core()
        self._prepared = False
        self._layout: dict[str, dict[str, int]] | None = None
        # "auto" donates: XLA reuses the block carry's buffers on every
        # platform we run on, and samples are unaffected. "off" is the
        # fallback path for callers that re-read a block's inputs.
        self.donate_blocks = cfg.backend.donate_blocks in ("auto", "on")

    # ------------------------------------------------------------------
    @abc.abstractmethod
    def prepare(self, coo: RatingsCOO) -> None:
        """Build the backend's data layout (split, center, bucket, shard)."""

    @abc.abstractmethod
    def init_state(self, key: jax.Array):
        """Prior-predictive state; layout-independent per original item id."""

    @abc.abstractmethod
    def sweep(self, key: jax.Array, state, pred: PredictionState):
        """One Gibbs sweep -> (state, pred, SweepMetrics). Legacy per-sweep
        dispatch; the engine run loop goes through :meth:`sweep_block`."""

    @abc.abstractmethod
    def sweep_block(
        self, key: jax.Array, state, pred: PredictionState,
        accum: PosteriorAccum, block_size: int,
    ):
        """``block_size`` sweeps in one jitted call, no host sync inside.

        The engine's run loop primitive (DESIGN.md §10): posterior and
        prediction accumulation happen on-device in the block's scan carry.

        Returns:
            ``(state, pred, accum, metrics)`` — ``metrics`` a
            ``[block_size, 3]`` f32 device array of per-sweep
            ``(rmse_sample, rmse_avg, sweep)`` rows.
        """

    def lower_block(
        self, key: jax.Array, state, pred: PredictionState,
        accum: PosteriorAccum, block_size: int,
    ) -> jax.stages.Lowered:
        """The program :meth:`sweep_block` dispatches, lowered, not run.

        ``.compile()`` of the result gives the compile time, the HLO and
        ``memory_analysis()`` of the block the run loop executes.
        """
        raise NotImplementedError(f"backend {self.name!r} runs no single block program")

    @abc.abstractmethod
    def factors(self, state) -> tuple[np.ndarray, np.ndarray]:
        """(U, V) as host arrays in *original* item order."""

    def layout_stats(self) -> dict[str, dict[str, int]]:
        """Per side: training ``ratings`` and the ``gram_slots`` that hold
        them, counted at :meth:`prepare` (``BPMFEngine.layout_stats``)."""
        if self._layout is None:
            raise NotImplementedError(f"backend {self.name!r} does not count its layout")
        return self._layout

    # ------------------------------------------------------------------
    @abc.abstractmethod
    def init_accum(self) -> PosteriorAccum:
        """Zeroed device posterior accumulator in this backend's layout
        (window depth = ``RunConfig.keep_factor_samples``)."""

    @abc.abstractmethod
    def accum_host(self, accum: PosteriorAccum) -> dict:
        """Host view of the accumulator in original item order — the
        ``"posterior"`` checkpoint subtree schema (see
        :func:`accum_host_tree`)."""

    @abc.abstractmethod
    def accum_from_host(self, tree: dict) -> PosteriorAccum:
        """Rebuild the device accumulator from an :meth:`accum_host` tree
        (checkpoint restore path)."""

    def posterior_template(self) -> dict:
        """Empty-leaf restore target naming the ``"posterior"`` checkpoint
        subtree's leaves (:meth:`accum_host`'s schema — the restore loads
        whatever shapes the checkpoint holds, so only leaf *names* matter).
        Backends with a different subtree shape (``posterior_merge``'s
        per-chain dicts) override this."""
        return {
            "U_sum": np.zeros((0, 0), np.float32),
            "V_sum": np.zeros((0, 0), np.float32),
            "count": np.zeros((), np.int32),
            "U_samples": np.zeros((0, 0, 0), np.float32),
            "V_samples": np.zeros((0, 0, 0), np.float32),
        }

    def posterior_export(self, accum) -> dict:
        """Global posterior summary feeding the serving artifact.

        Returns ``{"count", "U_samples", "V_samples"}`` plus ``"U_mean"`` /
        ``"V_mean"`` when ``count > 0`` — host float32 arrays in original
        item order, chronological sample stacks. The default derives it from
        the single :meth:`accum_host` tree (bitwise the arithmetic the
        engine has always exported); ``posterior_merge`` overrides it with
        the subset-posterior combination.
        """
        tree = self.accum_host(accum)
        count = int(np.asarray(tree["count"]))
        out: dict = {
            "count": count,
            "U_samples": np.asarray(tree["U_samples"], np.float32),
            "V_samples": np.asarray(tree["V_samples"], np.float32),
        }
        if count:
            n = np.float32(count)
            out["U_mean"] = np.asarray(tree["U_sum"] / n, np.float32)
            out["V_mean"] = np.asarray(tree["V_sum"] / n, np.float32)
        return out

    # ------------------------------------------------------------------
    @property
    def prepared(self) -> bool:
        """Whether ``prepare()`` has built this backend's data layout."""
        return self._prepared

    def init_pred(self) -> PredictionState:
        """Zeroed posterior-mean prediction accumulator for the test set."""
        return PredictionState.init(self.num_test)

    @property
    @abc.abstractmethod
    def num_test(self) -> int:
        """Number of held-out ratings."""

    @property
    @abc.abstractmethod
    def test_vals(self) -> jax.Array:
        """Held-out rating values, ``[num_test]`` f32 (uncentered)."""

    @property
    @abc.abstractmethod
    def mean_rating(self) -> float:
        """Training-set mean subtracted before sampling, re-added at predict."""

    @property
    @abc.abstractmethod
    def rating_range(self) -> tuple[float, float]:
        """(lo, hi) clip range for predictions."""


# --------------------------------------------------------------------------
# Sequential (the single-program oracle)
# --------------------------------------------------------------------------


@register_backend("sequential")
class SequentialBackend(Backend):
    """Single-program Algorithm 1 via :mod:`repro.core.gibbs`; also the
    tensor model (BPTF), whose data carries a time side."""

    families = ("bpmf", "bptf")

    def prepare(self, coo: RatingsCOO | ChunkedRatings) -> None:
        if isinstance(coo, ChunkedRatings):  # no per-host path: concatenate
            coo = coo.materialize()
        if self.cfg.model.family == "bptf":
            if coo.times is None:
                raise ValueError("model family 'bptf' needs ratings with time bins "
                                 "(RatingsCOO.times)")
        else:
            coo = coo.without_times()
        self.data = build_bpmf_data(
            coo,
            pads=self.cfg.backend.bucket_pads,
            test_fraction=self.cfg.run.test_fraction,
            seed=self.cfg.run.seed,
        )
        self._layout = {"users": self.data.users.layout_stats(),
                        "movies": self.data.movies.layout_stats()}
        if self.data.times is not None:
            self._layout["times"] = self.data.times.layout_stats()
        self._prepared = True

    def init_state(self, key: jax.Array):
        return gibbs.init_state(key, self.data.num_users, self.data.num_movies, self.core_cfg,
                                self.data.num_times)

    def sweep(self, key: jax.Array, state, pred: PredictionState):
        return gibbs.gibbs_sweep(key, state, pred, self.data, self.core_cfg)

    def _block_fn(self):
        if self.donate_blocks:
            return gibbs.gibbs_sweep_block_donated
        return gibbs.gibbs_sweep_block

    def sweep_block(
        self, key: jax.Array, state, pred: PredictionState,
        accum: PosteriorAccum, block_size: int,
    ):
        return self._block_fn()(key, state, pred, accum, self.data, self.core_cfg, block_size)

    def lower_block(
        self, key: jax.Array, state, pred: PredictionState,
        accum: PosteriorAccum, block_size: int,
    ) -> jax.stages.Lowered:
        return self._block_fn().lower(
            key, state, pred, accum, self.data, self.core_cfg, block_size
        )

    def factors(self, state) -> tuple[np.ndarray, np.ndarray]:
        return np.asarray(state.U), np.asarray(state.V)

    def init_accum(self) -> PosteriorAccum:
        return PosteriorAccum.init(
            self.data.num_users, self.data.num_movies,
            self.core_cfg.K, self.cfg.run.keep_factor_samples,
        )

    def accum_host(self, accum: PosteriorAccum) -> dict:
        return accum_host_tree(accum)

    def accum_from_host(self, tree: dict) -> PosteriorAccum:
        host = accum_from_host_tree(tree, self.init_accum())
        return jax.tree_util.tree_map(jax.numpy.asarray, host)

    @property
    def num_test(self) -> int:
        return int(self.data.test.rows.shape[0])

    @property
    def test_vals(self) -> jax.Array:
        return self.data.test.vals

    @property
    def mean_rating(self) -> float:
        return float(self.data.mean_rating)

    @property
    def rating_range(self) -> tuple[float, float]:
        return self.data.min_rating, self.data.max_rating


# --------------------------------------------------------------------------
# Distributed (ring / allgather over a device mesh)
# --------------------------------------------------------------------------


class DistributedBackend(Backend):
    """Shared machinery for the shard_map backends (paper §IV).

    Subclass this (and :func:`register_backend` the subclass) to add new
    distributed execution strategies: it owns the mesh construction,
    host-side data distribution, sharded init/sweep dispatch and factor
    gathering; subclasses typically only pick a ``comm_mode`` via
    ``BackendConfig.name`` or override :meth:`sweep`.
    """

    def prepare(self, coo: RatingsCOO | ChunkedRatings) -> None:
        devices = jax.devices()
        procs = jax.process_count()
        S = self.cfg.backend.num_shards or len(devices)
        if S > len(devices):
            raise ValueError(
                f"BackendConfig.num_shards={S} exceeds the {len(devices)} visible "
                f"device(s); lower it or force more host devices "
                f"(XLA_FLAGS=--xla_force_host_platform_device_count=N)"
            )
        if procs > 1 and S != len(devices):
            raise ValueError(
                f"multi-process runs must ring all {len(devices)} global "
                f"devices (got num_shards={S}); vary --devices per process "
                f"instead"
            )
        self.mesh = dist.make_ring_mesh(devices[:S])
        if procs > 1 or isinstance(coo, ChunkedRatings):
            # per-host loading (DESIGN.md §14): every process computes the
            # same global plan from the shared chunk stream but materializes
            # only its own shards' buckets/rating rows
            chunked = coo if isinstance(coo, ChunkedRatings) else coo.chunked()
            local = dist.local_shard_range(S, jax.process_index(), procs)
            data, self.plan = dist.build_distributed_data_per_host(
                chunked,
                num_shards=S,
                local_shards=local,
                pads=self.cfg.backend.bucket_pads,
                test_fraction=self.cfg.run.test_fraction,
                seed=self.cfg.run.seed,
                strategy=self.cfg.backend.partition_strategy,
            )
        else:
            data, self.plan = dist.build_distributed_data(
                coo,
                num_shards=S,
                pads=self.cfg.backend.bucket_pads,
                test_fraction=self.cfg.run.test_fraction,
                seed=self.cfg.run.seed,
                strategy=self.cfg.backend.partition_strategy,
            )
        self.data = dist.shard_data(data, self.mesh)
        self.num_shards = S
        self._layout = {
            name: dist.ring_layout_stats(side, S, self.plan.total_nnz)
            for name, side in (("users", self.data.users), ("movies", self.data.movies))
        }
        self._prepared = True

    def init_state(self, key: jax.Array):
        return dist.init_dist_state(key, self.data, self.core_cfg, self.mesh)

    def init_pred(self) -> PredictionState:
        return dist.replicate(self.mesh, super().init_pred())

    def sweep(self, key: jax.Array, state, pred: PredictionState):
        return dist.dist_gibbs_sweep(key, state, pred, self.data, self.core_cfg, self.mesh)

    def _block_fn(self):
        if self.donate_blocks:
            return dist.dist_gibbs_sweep_block_donated
        return dist.dist_gibbs_sweep_block

    def sweep_block(
        self, key: jax.Array, state, pred: PredictionState,
        accum: PosteriorAccum, block_size: int,
    ):
        return self._block_fn()(
            key, state, pred, accum, self.data, self.core_cfg, self.mesh, block_size
        )

    def lower_block(
        self, key: jax.Array, state, pred: PredictionState,
        accum: PosteriorAccum, block_size: int,
    ) -> jax.stages.Lowered:
        return self._block_fn().lower(
            key, state, pred, accum, self.data, self.core_cfg, self.mesh, block_size
        )

    def factors(self, state) -> tuple[np.ndarray, np.ndarray]:
        return dist.gather_factors(state, self.plan)

    def init_accum(self) -> PosteriorAccum:
        return dist.init_dist_accum(
            self.data, self.core_cfg, self.mesh, self.cfg.run.keep_factor_samples
        )

    def accum_host(self, accum: PosteriorAccum) -> dict:
        return accum_host_tree(
            accum,
            u_order=self.plan.part_users.perm,
            v_order=self.plan.part_movies.perm,
        )

    def accum_from_host(self, tree: dict) -> PosteriorAccum:
        host = accum_from_host_tree(
            tree,
            self.init_accum(),
            u_scatter=self.plan.part_users.perm,
            v_scatter=self.plan.part_movies.perm,
        )
        specs = dist.accum_specs()
        return jax.tree_util.tree_map(
            lambda x, s: dist.place_global(x, NamedSharding(self.mesh, s)), host, specs
        )

    @property
    def num_test(self) -> int:
        return int(self.data.test.rows.shape[0])

    @property
    def test_vals(self) -> jax.Array:
        return self.data.test.vals

    @property
    def mean_rating(self) -> float:
        return float(self.data.mean_rating)

    @property
    def rating_range(self) -> tuple[float, float]:
        return self.data.min_rating, self.data.max_rating


@register_backend("ring")
class RingBackend(DistributedBackend):
    """Paper §IV-C: ppermute rotation with compute/comm overlap."""


@register_backend("ring_async")
class AsyncRingBackend(DistributedBackend):
    """Depth-d pipelined ring (arXiv:1705.10633; DESIGN.md §7).

    Keeps ``BackendConfig.pipeline_depth`` shard rotations in flight in a
    rotating buffer queue instead of the synchronous ring's one, hiding up
    to d link latencies per Gram step at a memory cost of d resident
    opposite-shard buffers. Bit-identical samples to ``"ring"`` for every
    depth (the rotation schedule changes *when* transfers are issued,
    never the values each Gram step consumes).
    """


@register_backend("allgather")
class AllGatherBackend(DistributedBackend):
    """Synchronous baseline: blocking all-gather then local updates."""


# --------------------------------------------------------------------------
# Posterior merge (limited-communication subset posteriors)
# --------------------------------------------------------------------------


@register_backend("posterior_merge")
class PosteriorMergeBackend(Backend):
    """Embarrassingly-parallel partition chains + subset-posterior merge.

    The limited-communication regime of arXiv:1703.00734 / 2004.02561
    (DESIGN.md §12): one global train/test split, users partitioned into
    ``BackendConfig.num_partitions`` chains by the ring's nnz cost model,
    and one fully independent Gibbs chain per partition — each running the
    same device-resident blocked sweep loop the sequential backend uses,
    placed round-robin across the visible devices. Chains exchange **zero
    bytes per sweep** (no collectives at all — ``fig_merge_comm`` measures
    this on the compiled HLO); the subset posteriors meet only at
    export/serve time, combined per ``BackendConfig.merge_method``
    (:func:`repro.core.subset_merge.merge_chain_trees`).

    State / pred / accum are tuples of per-chain pytrees (checkpointed as
    ``chain_000``-keyed subtrees), chain c draws from the disjoint RNG
    stream ``fold_in(run_key, c)``, and user-factor rows are initialized by
    *original* user id, so the per-chain init matches the sequential
    backend's rows for the same seed.

    Multi-process (DESIGN.md §14): chains are placed round-robin over the
    *global* device list, so the first multi-host tenant costs only
    placement. Each process builds device data and runs the sweep loop for
    its own chains alone; a chain owned by another process travels through
    this process's pytrees as zero-shard :class:`ShardedHostLeaf`
    placeholders — structurally identical trees on every process, so the
    checkpoint commit protocol sees one global leaf set with each chain's
    bytes written by its owner. Per-sweep metrics and the export-time merge
    gather chain summaries with a zero-filled host allgather (each chain's
    slot filled only by its owner), and the merged artifact is written by
    process 0.
    """

    # approximate inference: merged posterior != sequential samples; gated
    # by the statistical harness (tests/test_posterior_quality.py)
    exact_parity = False

    def prepare(self, coo: RatingsCOO | ChunkedRatings) -> None:
        if isinstance(coo, ChunkedRatings):  # chains split users, not shards
            coo = coo.materialize()
        bk = self.cfg.backend
        devices = jax.devices()  # global, process-major
        P = bk.num_partitions or min(len(devices), coo.num_users)
        self.user_sets = subset_merge.partition_users(
            coo, P, strategy=bk.partition_strategy
        )
        # one GLOBAL split + centering, identical to the sequential
        # backend's, so cross-backend RMSE compares inference not data
        train, test = train_test_split(
            coo, self.cfg.run.test_fraction, self.cfg.run.seed
        )
        self._mean = float(train.vals.mean()) if train.nnz else 0.0
        self._range = (float(coo.vals.min()), float(coo.vals.max()))
        train_subs = subset_merge.split_by_users(train, self.user_sets)
        test_subs = subset_merge.split_by_users(test, self.user_sets)
        self.devices = [devices[c % len(devices)] for c in range(P)]
        self._owner = [int(d.process_index) for d in self.devices]
        self._test_counts = [int(t.nnz) for t in test_subs]
        self._test_vals = (
            np.concatenate([np.asarray(t.vals, np.float32) for t in test_subs])
            if test_subs
            else np.zeros(0, np.float32)
        )
        pid = jax.process_index()
        self._local_chains = [c for c in range(P) if self._owner[c] == pid]
        # per-host loading: only this process's chains get bucketed device
        # data; foreign chains stay host-side split metadata
        self.chain_data = {}
        for c in self._local_chains:
            data = build_bpmf_data_presplit(
                subset_merge.localize_users(train_subs[c], self.user_sets[c]),
                subset_merge.localize_users(test_subs[c], self.user_sets[c]),
                pads=bk.bucket_pads,
                mean_rating=self._mean,
                min_rating=self._range[0],
                max_rating=self._range[1],
            )
            self.chain_data[c] = jax.device_put(data, self.devices[c])
        self.num_partitions = P
        self._num_users = coo.num_users
        self._num_movies = coo.num_movies
        self._prepared = True

    @staticmethod
    def _chain_name(c: int) -> str:
        """Checkpoint subtree key of chain ``c`` (zero-padded, stable order)."""
        return f"chain_{c:03d}"

    # ------------------------------------------------------------------
    # cross-process plumbing (no-ops on a single process)
    # ------------------------------------------------------------------
    def _to_chain_device(self, tree, c: int):
        """Commit a host pytree to chain ``c``'s device.

        Local chains ``device_put`` as always; a chain owned by another
        process becomes a pytree of zero-shard :class:`ShardedHostLeaf`
        placeholders (global shape/dtype, no data) — never computed on
        here, but keeping every process's trees structurally identical for
        the checkpoint layer.
        """
        if self._owner[c] == jax.process_index():
            return jax.device_put(tree, self.devices[c])
        return jax.tree_util.tree_map(
            lambda a: ShardedHostLeaf(
                global_shape=tuple(int(d) for d in np.shape(a)),
                dtype=str(np.result_type(a)),
                shards=(),
            ),
            tree,
        )

    def _fetch(self, x, c: int) -> np.ndarray:
        """Host copy of chain ``c``'s array, on every process.

        A collective in multi-process jobs (all processes must call it in
        the same order): every process contributes a zero-filled slot
        except the owner, the slots are allgathered, and the owner's is
        selected — bitwise the owner's bytes, everywhere.
        """
        if jax.process_count() == 1:
            return np.asarray(jax.device_get(x))
        from jax.experimental import multihost_utils

        if isinstance(x, ShardedHostLeaf):
            local = np.zeros(x.global_shape, np.dtype(x.dtype))
        else:
            local = np.asarray(jax.device_get(x))
        gathered = multihost_utils.process_allgather(local)
        return np.asarray(gathered[self._owner[c]], local.dtype)

    def _host_accum(self, c: int, a) -> PosteriorAccum:
        """Chain ``c``'s accumulator as host numpy (collective, see
        :meth:`_fetch`)."""
        return PosteriorAccum(
            U_sum=self._fetch(a.U_sum, c),
            V_sum=self._fetch(a.V_sum, c),
            count=self._fetch(a.count, c),
            filled=self._fetch(a.filled, c),
            U_window=self._fetch(a.U_window, c),
            V_window=self._fetch(a.V_window, c),
        )

    def _global_rows(self, per_chain: np.ndarray) -> np.ndarray:
        """Sum each chain's metric rows over processes (owner contributes
        the values, everyone else zeros)."""
        if jax.process_count() == 1:
            return per_chain
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(per_chain)).sum(axis=0)

    # ------------------------------------------------------------------
    def init_state(self, key: jax.Array):
        """Per-chain prior-predictive states; U rows keyed by *original*
        user id (bitwise the sequential init's rows), V identical across
        chains."""
        dt = self.core_cfg.sample_dtype
        K = self.core_cfg.K
        ku, kv = jax.random.split(key)
        states = []
        for c, uids in enumerate(self.user_sets):
            st = BPMFState(
                U=gibbs.init_rows(ku, jnp.asarray(uids, jnp.int32), K, dt),
                V=gibbs.init_rows(
                    kv, jnp.arange(self._num_movies, dtype=jnp.int32), K, dt
                ),
                hyper_U=HyperParams.init(K, dt),
                hyper_V=HyperParams.init(K, dt),
                sweep=jnp.zeros((), jnp.int32),
            )
            states.append(self._to_chain_device(st, c))
        return tuple(states)

    def _combine_metric_rows(self, per_chain: np.ndarray) -> np.ndarray:
        """``[C, B, 3]`` per-chain metric rows -> ``[B, 3]`` global rows.

        Each chain's RMSE covers its own (disjoint) test subset, so the
        pooled global RMSE is the nnz-weighted quadratic mean
        ``sqrt(sum_c T_c * rmse_c^2 / T)``; chains with an empty test
        subset report NaN and are zero-weighted. The sweep column is shared
        (chains run in lock-step).
        """
        T = np.asarray(self._test_counts, np.float64)
        total = max(T.sum(), 1.0)
        sq = np.square(np.nan_to_num(per_chain[:, :, :2].astype(np.float64)))
        comb = np.sqrt((T[:, None, None] * sq).sum(axis=0) / total)
        rows = np.concatenate([comb, per_chain[0, :, 2:3].astype(np.float64)], axis=1)
        return rows.astype(np.float32)

    def sweep(self, key: jax.Array, state, pred):
        outs = {
            c: gibbs.gibbs_sweep(
                subset_merge.chain_key(key, c), state[c], pred[c],
                self.chain_data[c], self.core_cfg,
            )
            for c in self._local_chains
        }
        per_chain = np.zeros((self.num_partitions, 1, 3), np.float32)
        for c, (_, _, m) in outs.items():
            per_chain[c, 0] = np.asarray(
                jax.device_get(
                    jnp.stack([m.rmse_sample, m.rmse_avg, m.sweep.astype(jnp.float32)])
                )
            )
        row = self._combine_metric_rows(self._global_rows(per_chain))[0]
        metrics = SweepMetrics(float(row[0]), float(row[1]), float(row[2]))
        C = self.num_partitions
        return (
            tuple(outs[c][0] if c in outs else state[c] for c in range(C)),
            tuple(outs[c][1] if c in outs else pred[c] for c in range(C)),
            metrics,
        )

    def sweep_block(
        self, key: jax.Array, state, pred, accum: MergeAccum, block_size: int
    ):
        fn = (
            gibbs.gibbs_sweep_block_donated
            if self.donate_blocks
            else gibbs.gibbs_sweep_block
        )
        outs = {}
        for c in self._local_chains:
            outs[c] = fn(
                subset_merge.chain_key(key, c), state[c], pred[c],
                accum.chains[c], self.chain_data[c], self.core_cfg, block_size,
            )
        # all local chain blocks are dispatched (async) before the first
        # fetch; foreign chains' rows arrive through the allgather below
        per_chain = np.zeros((self.num_partitions, block_size, 3), np.float32)
        for c, o in outs.items():
            per_chain[c] = np.asarray(jax.device_get(o[3]))
        metrics = self._combine_metric_rows(self._global_rows(per_chain))
        C = self.num_partitions
        return (
            tuple(outs[c][0] if c in outs else state[c] for c in range(C)),
            tuple(outs[c][1] if c in outs else pred[c] for c in range(C)),
            MergeAccum(
                chains=tuple(
                    outs[c][2] if c in outs else accum.chains[c] for c in range(C)
                )
            ),
            metrics,
        )

    def factors(self, state) -> tuple[np.ndarray, np.ndarray]:
        """(U, V) of the current per-chain samples: U rows scatter from
        their owning chain; V (sampled by every chain) is the uniform mean
        of the chains' current draws."""
        K = self.core_cfg.K
        U = np.zeros((self._num_users, K), np.float32)
        Vs = []
        for c, uids in enumerate(self.user_sets):
            U[uids] = np.asarray(self._fetch(state[c].U, c), np.float32)
            Vs.append(np.asarray(self._fetch(state[c].V, c), np.float32))
        V = np.mean(np.stack(Vs), axis=0).astype(np.float32)
        return U, V

    def init_accum(self) -> MergeAccum:
        keep = self.cfg.run.keep_factor_samples
        K = self.core_cfg.K
        chains = []
        for c, uids in enumerate(self.user_sets):
            a = PosteriorAccum.init(len(uids), self._num_movies, K, keep)
            chains.append(self._to_chain_device(a, c))
        return MergeAccum(chains=tuple(chains))

    def init_pred(self):
        """Per-chain prediction accumulators, one per chain test subset."""
        return tuple(
            self._to_chain_device(PredictionState.init(self._test_counts[c]), c)
            for c in range(self.num_partitions)
        )

    def accum_host(self, accum: MergeAccum) -> dict:
        return {
            self._chain_name(c): accum_host_tree(self._host_accum(c, a))
            for c, a in enumerate(accum.chains)
        }

    def accum_from_host(self, tree: dict) -> MergeAccum:
        keep = self.cfg.run.keep_factor_samples
        K = self.core_cfg.K
        chains = []
        for c, uids in enumerate(self.user_sets):
            template = PosteriorAccum.init(len(uids), self._num_movies, K, keep)
            host = accum_from_host_tree(tree[self._chain_name(c)], template)
            chains.append(self._to_chain_device(host, c))
        return MergeAccum(chains=tuple(chains))

    def posterior_template(self) -> dict:
        return {
            self._chain_name(c): super(PosteriorMergeBackend, self).posterior_template()
            for c in range(self.num_partitions)
        }

    def posterior_export(self, accum: MergeAccum) -> dict:
        """The backend's single communication event: gather each chain's
        accumulator (collective across processes) and merge the subset
        posteriors (:func:`repro.core.subset_merge.merge_chain_trees`)."""
        trees = [
            accum_host_tree(self._host_accum(c, a))
            for c, a in enumerate(accum.chains)
        ]
        return subset_merge.merge_chain_trees(
            trees,
            self.user_sets,
            self._num_users,
            method=self.cfg.backend.merge_method,
        )

    @property
    def num_test(self) -> int:
        return sum(self._test_counts)

    @property
    def test_vals(self) -> jax.Array:
        return jnp.asarray(self._test_vals)

    @property
    def mean_rating(self) -> float:
        return self._mean

    @property
    def rating_range(self) -> tuple[float, float]:
        return self._range


# --------------------------------------------------------------------------
# Legacy driver (kept for repro.core.gibbs.run)
# --------------------------------------------------------------------------


def run_sequential_prepared(
    key: jax.Array,
    data,
    core_cfg,
    callback=None,
) -> tuple[object, PredictionState, list[SweepMetrics]]:
    """The pre-facade ``core.gibbs.run`` loop, over already-built BPMFData.

    Kept here so ``core.gibbs.run`` can stay a thin deprecation-safe wrapper
    while the engine owns all new run-loop features (checkpointing,
    streaming metrics). Dispatches per sweep through the same blocked scan
    the engine uses (block size 1), so legacy-loop samples stay bitwise
    identical to engine runs at any ``sweeps_per_block``.
    """
    k_init, k_run = jax.random.split(key)
    state = gibbs.init_state(k_init, data.num_users, data.num_movies, core_cfg)
    pred_state = PredictionState.init(data.test.rows.shape[0])
    accum = PosteriorAccum.init(data.num_users, data.num_movies, core_cfg.K, keep=0)
    history: list[SweepMetrics] = []
    for _ in range(core_cfg.num_sweeps):
        # non-donating on purpose: the callback may retain the state it is
        # handed, which the next iteration would otherwise consume
        state, pred_state, accum, rows = gibbs.gibbs_sweep_block(
            k_run, state, pred_state, accum, data, core_cfg, 1
        )
        metrics = SweepMetrics(*(float(v) for v in np.asarray(rows)[0]))
        history.append(metrics)
        if callback is not None:
            callback(state, metrics)
    return state, pred_state, history
