"""``BPMFEngine`` — the single front door to every BPMF sampler.

One facade over the sequential oracle and the distributed
ring/ring_async/allgather samplers (paper §V-B: they are the same
sampler), with the run loop,
sweep-level checkpointing and metric streaming factored out of the
backends::

    from repro.bpmf import BPMFConfig, BPMFEngine, load_dataset

    coo = load_dataset("synthetic", num_users=400, num_movies=300, nnz=12_000)
    cfg = BPMFConfig().replace(name="ring", K=16, num_sweeps=25)
    engine = BPMFEngine(cfg).fit(coo)
    print(engine.rmse)

Backend choice is config-only: the same ``(seed, data)`` run through
``"sequential"``, ``"ring"``, ``"ring_async"`` (any depth) and
``"allgather"`` yields the same posterior samples up to float reduction
order (tests/test_engine.py asserts this).

Determinism note: the sampler key is derived from ``RunConfig.seed`` and
per-sweep keys from ``(key, state.sweep)``, so a run restored from a
checkpoint continues with *identical* randomness to an uninterrupted one.

Run-loop note (DESIGN.md §10, §13): sweeps execute in jitted device blocks
of ``RunConfig.sweeps_per_block`` with one host sync per block —
posterior-mean sums, the recent-sample window and the prediction accumulator
fold on-device in the block's scan carry, and per-sweep metrics arrive as
one stacked transfer. With ``RunConfig.pipeline_blocks > 1`` the loop is
additionally *pipelined*: the next block dispatches on the still-on-device
carry before the previous block's metrics are fetched, the metric transfer
completes asynchronously, and checkpoint writes commit on a background
thread. Samples, metrics, checkpoints and exported artifacts are bitwise
identical at every block size and every pipeline depth.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Iterator, Optional

import jax
import numpy as np

from repro.bpmf.backends import Backend, get_backend
from repro.bpmf.config import BPMFConfig
from repro.checkpoint import CheckpointManager, CheckpointSchemaError
from repro.core.gibbs import SweepMetrics
from repro.data.sparse import RatingsCOO
from repro.serve import ArtifactMeta, PosteriorPredictor, save_artifact


class _PosteriorAccumulator:
    """Thin host *view* over the device-resident posterior accumulator.

    The accumulation itself happens on-device inside the blocked sweep loop
    (:class:`repro.core.types.PosteriorAccum`, DESIGN.md §10) — running
    float32 posterior-mean sums plus a rotating window of the
    ``keep_factor_samples`` most recent post-burn-in ``(U, V)`` draws,
    sharded like the factors on the distributed backends. This view only
    materializes host arrays at export/checkpoint time, in original item
    order and the same schema (chronological sample stacks) the old
    host-side accumulator used, so checkpoints and artifacts stay bitwise
    compatible across the refactor.
    """

    def __init__(self, engine: "BPMFEngine"):
        self._engine = engine

    @property
    def count(self) -> int:
        """Post-burn-in samples folded so far (0 before the first block)."""
        accum = self._engine._accum
        return int(accum.count) if accum is not None else 0

    def tree(self) -> dict:
        """Checkpointable host tree (fixed key set, shapes vary with count)."""
        return self._engine.backend.accum_host(self._engine._accum)

    def load_tree(self, tree: dict) -> None:
        """Restore the device accumulator from :meth:`tree` output (trims
        to this run's ``keep_factor_samples``)."""
        self._engine._accum = self._engine.backend.accum_from_host(tree)


class BPMFEngine:
    """Fit / sample / predict / save / restore / export over a pluggable backend."""

    def __init__(self, cfg: BPMFConfig | None = None):
        """Build an engine (and its backend) from a config.

        Args:
            cfg: Full engine config; ``None`` means all defaults
                (sequential backend, synthetic-friendly schedule).
        """
        self.cfg = cfg or BPMFConfig()
        self.backend: Backend = get_backend(self.cfg)
        self.history: list[SweepMetrics] = []
        self._state = None
        self._pred = None
        self._accum = None  # device-resident PosteriorAccum (DESIGN.md §10)
        self._sweeps_done = 0
        self._data_fingerprint: tuple[int, int, int] | None = None
        self._ckpt: Optional[CheckpointManager] = None
        self._posterior = _PosteriorAccumulator(self)
        self._predictor: Optional[PosteriorPredictor] = None
        self._predictor_sweep = -1
        # bytes fetched from device for metrics, summed over the run — what
        # benchmarks/sweep_throughput.py reports as host traffic per sweep
        self.host_metric_bytes = 0
        # seconds the host spent blocked on metric fetches, summed over the
        # run (the wait the pipelined dispatch queue exists to hide)
        self.host_blocked_s = 0.0
        # dispatched-but-not-yet-fetched blocks: (block_len, metrics rows)
        self._inflight: deque[tuple[int, object]] = deque()
        key = jax.random.key(self.cfg.run.seed)
        self._k_init, self._k_run = jax.random.split(key)

    # ------------------------------------------------------------------
    # data / state plumbing
    # ------------------------------------------------------------------
    def prepare(self, data: RatingsCOO) -> "BPMFEngine":
        """Host-side layout (split, center, bucket, shard). Idempotent.

        Re-passing the same dataset is a no-op; passing a *different* one
        (detected by shape/nnz) raises — an engine is bound to one dataset
        for its lifetime, so metrics and checkpoints stay coherent.

        Args:
            data: Raw ratings; the backend owns split/center/bucket/shard.

        Returns:
            ``self``, prepared.
        """
        family = self.cfg.model.family
        if family not in self.backend.families:
            raise ValueError(
                f"backend {self.backend.name!r} runs the model families "
                f"{self.backend.families}, not {family!r}; use backend 'sequential'"
            )
        fingerprint = (data.num_users, data.num_movies, data.nnz)
        if self.backend.prepared:
            if fingerprint != self._data_fingerprint:
                raise ValueError(
                    f"engine already prepared for R {self._data_fingerprint}; "
                    f"got different data {fingerprint} — build a new BPMFEngine"
                )
            return self
        with jax.profiler.TraceAnnotation("bpmf.prepare"):
            self.backend.prepare(data)
        self._data_fingerprint = fingerprint
        return self

    def layout_stats(self) -> dict[str, dict[str, int]]:
        """Per side (``"users"``, ``"movies"``): the training ``ratings`` and
        the ``gram_slots`` the Gram runs over to hold them, counted once at
        :meth:`prepare` from the backend's layout (summed over shards on the
        ring). ``1 - ratings / gram_slots`` is the Gram's padding share."""
        if not self.backend.prepared:
            raise RuntimeError("no data: call prepare(data) first")
        return self.backend.layout_stats()

    def _ensure_state(self) -> None:
        if not self.backend.prepared:
            raise RuntimeError("no data: call fit(data) / sample(data) / prepare(data) first")
        if self._state is None:
            self._state = self.backend.init_state(self._k_init)
            self._pred = self.backend.init_pred()
            self._accum = self.backend.init_accum()
            self._sweeps_done = 0

    def _manager(self) -> CheckpointManager:
        if self._ckpt is None:
            if not self.cfg.run.checkpoint_dir:
                raise ValueError("RunConfig.checkpoint_dir is not set")
            self._ckpt = CheckpointManager(
                self.cfg.run.checkpoint_dir,
                keep=self.cfg.run.keep_checkpoints,
                async_writes=self.cfg.run.async_checkpoint_writes,
            )
        return self._ckpt

    # ------------------------------------------------------------------
    # the run loop
    # ------------------------------------------------------------------
    def _next_block_len(self) -> int:
        """Sweeps in the next device block: ``sweeps_per_block``, shrunk so
        blocks land exactly on ``checkpoint_every`` boundaries and the final
        sweep (the partition never changes the samples — only how many
        sweeps run per host round-trip)."""
        run = self.cfg.run
        n = min(run.sweeps_per_block, run.num_sweeps - self._sweeps_done)
        if run.checkpoint_every:
            n = min(n, run.checkpoint_every - self._sweeps_done % run.checkpoint_every)
        return max(n, 1)

    def _drain_one(self) -> None:
        """Fetch the oldest in-flight block's metrics into ``history``.

        The single host materialization per block: ``np.asarray`` completes
        the transfer that ``copy_to_host_async`` started at dispatch time
        (a no-op view for backends that already returned host rows), and the
        byte counter sees that one buffer.
        """
        n, rows = self._inflight.popleft()
        with jax.profiler.TraceAnnotation("bpmf.drain"):
            t0 = time.perf_counter()
            rows = np.asarray(rows)
            self.host_blocked_s += time.perf_counter() - t0
        self.host_metric_bytes += int(rows.nbytes)
        self.history.extend(
            SweepMetrics(float(r[0]), float(r[1]), float(r[2])) for r in rows
        )

    def _drain_inflight(self) -> None:
        """Drain every dispatched block's metrics into ``history`` — the
        pipeline barrier ``save()`` / ``export()`` / checkpoint boundaries
        and the iterator end run through."""
        while self._inflight:
            self._drain_one()

    def sample(self, data: RatingsCOO | None = None) -> Iterator[SweepMetrics]:
        """Stream per-sweep metrics from the current sweep to ``num_sweeps``.

        Resumable: after ``restore()`` the iterator continues where the
        checkpoint left off, drawing the same randomness an uninterrupted
        run would have.

        Execution is *blocked* (DESIGN.md §10): sweeps run on-device in
        jitted blocks of ``RunConfig.sweeps_per_block`` with a single host
        sync per block, and the block's metrics are then yielded one per
        sweep. With ``RunConfig.pipeline_blocks = d > 1`` the loop is also
        *pipelined* (DESIGN.md §13): up to ``d`` blocks are dispatched ahead
        of the metrics drain, each block's metric transfer completes
        asynchronously while later blocks compute, and the queue drains
        fully at ``checkpoint_every`` boundaries and the end of the run.
        The public iterator contract is unchanged at every block size and
        depth — one :class:`SweepMetrics` per sweep, in sweep order, with
        identical history ordering and checkpoint cadence — but metrics for
        sweeps of the same block become available together, and abandoning
        the iterator mid-run leaves the engine advanced to the end of the
        last *dispatched* block (a later ``save()`` / ``export()`` /
        ``sample()`` call drains the remaining in-flight metrics).

        Args:
            data: Ratings to ``prepare()`` first, if not already prepared.

        Yields:
            One :class:`SweepMetrics` (sample / posterior-mean RMSE,
            sweep index) per completed sweep, as host floats.
        """
        if data is not None:
            self.prepare(data)
        self._ensure_state()
        run = self.cfg.run
        every = run.checkpoint_every
        depth = run.pipeline_blocks
        yielded = len(self.history)
        while self._sweeps_done < run.num_sweeps or self._inflight:
            # dispatch up to `depth` blocks ahead of the drain, stopping at
            # checkpoint boundaries so the boundary carry is still the
            # engine's current state when save() snapshots it
            while self._sweeps_done < run.num_sweeps and len(self._inflight) < depth:
                n = self._next_block_len()
                with jax.profiler.TraceAnnotation("bpmf.dispatch"):
                    self._state, self._pred, self._accum, rows = self.backend.sweep_block(
                        self._k_run, self._state, self._pred, self._accum, n
                    )
                    try:
                        rows.copy_to_host_async()  # start the metrics transfer now
                    except AttributeError:  # backend already returned host rows
                        pass
                self._inflight.append((n, rows))
                self._sweeps_done += n
                if every and self._sweeps_done % every == 0:
                    break
            at_ckpt = every and self._sweeps_done % every == 0
            final = self._sweeps_done >= run.num_sweeps
            keep = 0 if (at_ckpt or final) else depth - 1
            while len(self._inflight) > keep:
                self._drain_one()
            if at_ckpt:
                self.save()
            block = self.history[yielded:]
            yielded = len(self.history)
            yield from block

    def lower_block(self) -> jax.stages.Lowered:
        """The next device block's program, lowered for the current carry.

        Tracing happens here, so the Gram dispatch decisions of the block
        can be listed with ``repro.kernels.ops.record_gram_decisions``;
        ``.compile()`` gives the compile time, the HLO and
        ``memory_analysis()`` of what :meth:`sample` will run. Runs nothing.
        """
        self._ensure_state()
        return self.backend.lower_block(
            self._k_run, self._state, self._pred, self._accum, self._next_block_len()
        )

    def fit(self, data: RatingsCOO | None = None, resume: bool = False) -> "BPMFEngine":
        """Run (or finish) all sweeps.

        Args:
            data: Ratings to ``prepare()`` first, if not already prepared.
            resume: Restore the latest checkpoint from
                ``RunConfig.checkpoint_dir`` (if any) before continuing.

        Returns:
            ``self``, with ``history`` / ``rmse`` / ``factors()`` populated.
        """
        if data is not None:
            self.prepare(data)
        if resume and self.cfg.run.checkpoint_dir and self._manager().latest() is not None:
            self.restore()
        for _ in self.sample():
            pass
        return self

    # ------------------------------------------------------------------
    # results
    # ------------------------------------------------------------------
    @property
    def rmse(self) -> float:
        """Posterior-mean test RMSE after the last completed sweep."""
        if not self.history:
            raise RuntimeError("no sweeps run yet")
        return float(self.history[-1].rmse_avg)

    @property
    def num_sweeps_done(self) -> int:
        """Sweeps dispatched to the device so far (``restore()`` positions
        this at the checkpoint step). At ``pipeline_blocks > 1`` the last
        ``d - 1`` blocks' metrics may still be in flight; ``save()`` /
        ``export()`` / finishing the iterator drain them."""
        return self._sweeps_done

    @property
    def state(self):
        """Backend-specific Gibbs state pytree (``None`` before the first sweep)."""
        return self._state

    def factors(self) -> tuple[np.ndarray, np.ndarray]:
        """(U, V) of the current posterior sample, original item order."""
        self._ensure_state()
        return self.backend.factors(self._state)

    def predict(
        self, rows: np.ndarray, cols: np.ndarray, return_std: bool = False
    ) -> np.ndarray | tuple[np.ndarray, np.ndarray]:
        """Posterior-mean predictions for arbitrary (user, movie) pairs.

        Delegates to the same jitted :class:`repro.serve.PosteriorPredictor`
        program a ``BPMFEngine.export()`` artifact serves, so in-process and
        served predictions agree bitwise. Uses the posterior-mean factors
        once post-burn-in samples exist; before that, the current sample's.

        Args:
            rows: ``[N]`` user ids (original numbering).
            cols: ``[N]`` movie ids (original numbering).
            return_std: Also return the predictive std over the retained
                factor samples (``RunConfig.keep_factor_samples``).

        Returns:
            ``[N]`` predicted ratings, clipped to the training range — or
            ``(preds, std)`` when ``return_std``.
        """
        return self.predictor().predict(rows, cols, return_std=return_std)

    def predictor(self) -> PosteriorPredictor:
        """In-process serving predictor over the current posterior summary.

        Cached per completed sweep; rebuilt lazily after the state advances.

        Returns:
            A :class:`repro.serve.PosteriorPredictor` — also the gateway to
            ``top_k`` recommendations without an export round-trip.
        """
        self._ensure_state()
        if self._predictor is None or self._predictor_sweep != self._sweeps_done:
            self._predictor = PosteriorPredictor.from_engine(self)
            self._predictor_sweep = self._sweeps_done
        return self._predictor

    # ------------------------------------------------------------------
    # serving export
    # ------------------------------------------------------------------
    def _artifact_payload(self) -> tuple[ArtifactMeta, dict[str, np.ndarray]]:
        """(meta, arrays) of the serving artifact for the current posterior.

        Posterior-mean factors when post-burn-in samples have been
        accumulated, else the current raw sample (``num_mean_samples=0``).
        The backend's ``posterior_export`` hook supplies the global summary
        (one host gather per device accumulator; the ``posterior_merge``
        backend additionally runs its subset-posterior merge here — its
        only communication event).

        Raises:
            ValueError: For the tensor model (``family="bptf"``), whose
                predictions need the time factors the artifact cannot hold.
        """
        if self.cfg.model.family != "bpmf":
            raise ValueError(
                f"serving exports the matrix model only; model family "
                f"{self.cfg.model.family!r} cannot be exported or served"
            )
        self._ensure_state()
        summary = self.backend.posterior_export(self._accum)
        count = int(summary["count"])
        if count:
            U_mean = np.asarray(summary["U_mean"], np.float32)
            V_mean = np.asarray(summary["V_mean"], np.float32)
        else:
            U, V = self.factors()
            U_mean = np.asarray(U, np.float32)
            V_mean = np.asarray(V, np.float32)
        Us = np.asarray(summary["U_samples"], np.float32)
        Vs = np.asarray(summary["V_samples"], np.float32)
        S = Us.shape[0]
        if S == 0:  # canonical empty shapes for the artifact schema
            Us = np.zeros((0,) + U_mean.shape, np.float32)
            Vs = np.zeros((0,) + V_mean.shape, np.float32)
        lo, hi = self.backend.rating_range
        meta = ArtifactMeta(
            num_users=int(U_mean.shape[0]),
            num_movies=int(V_mean.shape[0]),
            K=int(U_mean.shape[1]),
            mean_rating=float(self.backend.mean_rating),
            min_rating=float(lo),
            max_rating=float(hi),
            num_mean_samples=count,
            num_kept_samples=S,
            backend=self.cfg.backend.name,
            num_sweeps_done=self._sweeps_done,
            seed=self.cfg.run.seed,
        )
        arrays = {"U_mean": U_mean, "V_mean": V_mean, "U_samples": Us, "V_samples": Vs}
        return meta, arrays

    def export(self, directory: str) -> str:
        """Write the versioned serving artifact for the current posterior.

        The export hook of the serving path (DESIGN.md §9): persists the
        posterior-mean factors, the retained per-sweep samples, the global
        mean/clip range and dataset metadata via the checkpoint layer, for
        :class:`repro.serve.PosteriorPredictor` / ``python -m
        repro.launch.serve`` to load without re-running MCMC.

        A pipeline barrier: in-flight metric blocks drain first, and any
        checkpoint writes still pending on the async writer commit before
        the artifact is written.

        Args:
            directory: Artifact directory (replaced if it already holds
                an artifact).

        Returns:
            The artifact directory.
        """
        self._drain_inflight()
        if self._ckpt is not None:
            self._ckpt.wait()
        meta, arrays = self._artifact_payload()
        if jax.process_count() > 1:
            # the payload gathers are collective (every process runs them);
            # the filesystem write is process 0's alone, and the barrier
            # keeps peers from racing ahead to read a half-written artifact
            from jax.experimental import multihost_utils

            if jax.process_index() == 0:
                save_artifact(directory, meta, arrays)
            multihost_utils.sync_global_devices(f"artifact-export-{directory}")
            return directory
        return save_artifact(directory, meta, arrays)

    # ------------------------------------------------------------------
    # checkpointing (sweep-level save / resume)
    # ------------------------------------------------------------------
    def save(self, step: int | None = None) -> int:
        """Checkpoint state, prediction accumulator and metric history.

        Drains in-flight pipeline blocks first, then snapshots host arrays;
        with ``RunConfig.async_checkpoint_writes`` (the default) the
        filesystem commit happens on the manager's background thread and
        this returns as soon as the snapshot is taken — the commit itself
        is atomic (tmp-dir rename, then ``LATEST`` replace), so a crash
        mid-write never leaves a torn checkpoint visible.

        Args:
            step: Sweep count to label the checkpoint with (default: the
                current sweep).

        Returns:
            The step the checkpoint was written at.
        """
        self._ensure_state()
        self._drain_inflight()
        step = self._sweeps_done if step is None else step
        hist = np.asarray(
            [[m.rmse_sample, m.rmse_avg, m.sweep] for m in self.history[:step]],
            np.float32,
        ).reshape(-1, 3)
        self._manager().save(
            step,
            {
                "state": self._state,
                "pred": self._pred,
                "history": hist,
                "posterior": self._posterior.tree(),
            },
        )
        return step

    def restore(self, data: RatingsCOO | None = None, step: int | None = None) -> int:
        """Load a checkpoint and position the run loop at its sweep count.

        The backend must be prepared (pass ``data`` here or call
        ``prepare`` first) so the restore target has the right shapes.
        Metric history up to the checkpointed sweep is restored too, so
        ``rmse`` and ``history`` are complete even in a fresh process.
        Checkpoints written before the serving subsystem (no ``posterior``
        subtree) still restore; the posterior accumulator just restarts
        empty, so a subsequent ``export()`` only reflects sweeps run after
        the resume.

        Args:
            data: Ratings to ``prepare()`` first, if not already prepared.
            step: Checkpoint step to load (default: latest).

        Returns:
            The restored sweep count.

        Raises:
            FileNotFoundError: If no checkpoint exists at ``step``.
        """
        if data is not None:
            self.prepare(data)
        self._ensure_state()
        # metrics still in flight belong to sweeps the restore rewinds past
        self._inflight.clear()
        mgr = self._manager()
        step = mgr.latest() if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {self.cfg.run.checkpoint_dir}")
        # posterior template: leaf names only (restore loads whatever shapes
        # the checkpoint holds) — cheaper than gathering the zeroed device
        # accumulator just to name its leaves. The backend owns the subtree
        # shape (posterior_merge checkpoints per-chain subtrees).
        posterior_target = self.backend.posterior_template()
        target = {
            "state": self._state,
            "pred": self._pred,
            "history": np.zeros((0, 3), np.float32),
            "posterior": posterior_target,
        }
        try:
            tree = mgr.restore(target, step=step)
            self._posterior.load_tree(tree["posterior"])
        except CheckpointSchemaError:
            # checkpoint written before the serving subsystem: no posterior
            # subtree. Restore everything else and start the accumulator
            # empty — export() degrades to the raw current sample until new
            # post-burn-in sweeps accumulate. (A genuinely damaged
            # checkpoint re-raises from the second restore.)
            tree = mgr.restore(
                {k: v for k, v in target.items() if k != "posterior"}, step=step
            )
            self._accum = self.backend.init_accum()
        self._state, self._pred = tree["state"], tree["pred"]
        self._predictor, self._predictor_sweep = None, -1
        self._sweeps_done = step
        self.history = [
            SweepMetrics(float(r[0]), float(r[1]), float(r[2]))
            for r in np.asarray(tree["history"])
        ]
        return step
