"""``PosteriorPredictor`` — jit-compiled, mesh-sharded posterior-mean serving.

Loads an exported artifact (or an engine's in-memory posterior) and answers
rating queries without touching the sampler:

* :meth:`PosteriorPredictor.predict` — batched ``(user, movie)`` point
  predictions from the posterior-mean factors, optionally with the
  predictive std estimated over the retained per-sweep samples,
* :meth:`PosteriorPredictor.top_k` — per-user catalog scoring + top-k.

Execution layout (DESIGN.md §9): the factor matrices are small relative to
query traffic, so they are **replicated** across a 1-D ``("serve",)`` device
mesh and the **query batch is sharded** along it — every device scores its
slice of the batch against its full local factor copy, so no collectives
appear on the hot path. Query batches are padded to a power-of-two pad class
(multiple of the mesh size), the serving analogue of the trainer's
nnz-bucketing: batch sizes 1..32 share one compiled program instead of
recompiling per request size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.serve.artifact import ArtifactMeta, load_artifact
from repro.serve.sharded_topk import build_local_topk, merge_topk, shard_items
from repro.utils import next_power_of_two, round_up

_MIN_PAD = 32  # smallest query pad class: batches 1..32 share one program
_AUTO_SHARD_MIN_ITEMS = 1024  # topk_mode="auto": shard catalogs at least this big


@functools.partial(jax.jit, static_argnames=("lo", "hi"))
def _predict_pairs(U, V, rows, cols, mean, lo, hi):
    """Clipped plug-in predictions for a padded (rows, cols) batch."""
    preds = jnp.sum(U[rows] * V[cols], axis=-1) + mean
    return jnp.clip(preds, lo, hi)


@functools.partial(jax.jit, static_argnames=("lo", "hi"))
def _predict_pairs_std(Us, Vs, rows, cols, mean, lo, hi):
    """Std of the clipped per-sample predictions over the sample axis."""
    preds = jnp.einsum(
        "sbk,sbk->sb", Us[:, rows], Vs[:, cols], precision=jax.lax.Precision.HIGHEST
    ) + mean
    return jnp.std(jnp.clip(preds, lo, hi), axis=0)


@functools.partial(jax.jit, static_argnames=("k", "lo", "hi"))
def _top_k(U, V, users, mean, k, lo, hi):
    """Per-user catalog scores -> (ids [B, k], scores [B, k])."""
    scores = jnp.matmul(U[users], V.T, precision=jax.lax.Precision.HIGHEST)
    scores = jnp.clip(scores + mean, lo, hi)
    vals, ids = jax.lax.top_k(scores, k)
    return ids.astype(jnp.int32), vals


def serve_mesh(max_devices: int = 0) -> Mesh:
    """1-D ``("serve",)`` mesh over the visible devices.

    Args:
        max_devices: Cap on the mesh size; 0 means every visible device.

    Returns:
        The mesh the predictor shards query batches over.
    """
    devices = jax.devices()
    if max_devices:
        devices = devices[:max_devices]
    return Mesh(np.asarray(devices), ("serve",))


class PosteriorPredictor:
    """Answer rating queries from an exported BPMF posterior.

    Construction paths:

    * :meth:`load` — from an on-disk artifact (the serving process),
    * :meth:`from_engine` — from a live engine's posterior summary, no
      disk round-trip (also what :meth:`repro.bpmf.BPMFEngine.predict`
      delegates to, so served and in-process predictions are computed by
      the *same* jitted program).
    """

    def __init__(
        self,
        meta: ArtifactMeta,
        arrays: dict[str, np.ndarray],
        mesh: Mesh | None = None,
        topk_mode: str = "auto",
    ):
        """Place the posterior summary on the serve mesh.

        Args:
            meta: Artifact metadata (shapes, clip range, mean rating).
            arrays: ``U_mean``/``V_mean``/``U_samples``/``V_samples`` host
                arrays in the shapes ``meta`` promises.
            mesh: Serve mesh; ``None`` builds one over all visible devices.
            topk_mode: Default ``top_k`` execution — ``"replicated"``
                (full-catalog scan on every device), ``"sharded"``
                (item-sharded ``V`` + per-shard top-k + host merge,
                DESIGN.md §11) or ``"auto"`` (sharded when the mesh has
                more than one device and the catalog is large enough for
                the shard pass to win). Per-call override via
                ``top_k(..., sharded=...)``.
        """
        if topk_mode not in ("auto", "replicated", "sharded"):
            raise ValueError(
                f"topk_mode must be auto|replicated|sharded, got {topk_mode!r}"
            )
        self.meta = meta
        self.mesh = mesh if mesh is not None else serve_mesh()
        self.topk_mode = topk_mode
        self._replicated = NamedSharding(self.mesh, P())
        self._batch_sharded = NamedSharding(self.mesh, P("serve"))
        put = functools.partial(jax.device_put, device=self._replicated)
        self._U = put(np.asarray(arrays["U_mean"], np.float32))
        self._V = put(np.asarray(arrays["V_mean"], np.float32))
        self._Us = put(np.asarray(arrays["U_samples"], np.float32))
        self._Vs = put(np.asarray(arrays["V_samples"], np.float32))
        self._mean = put(np.asarray(meta.mean_rating, np.float32))
        # item-sharded top-k state, built lazily on the first sharded call
        self._V_sharded: jax.Array | None = None
        self._local_topk = None

    # ------------------------------------------------------------------
    @classmethod
    def load(
        cls, directory: str, mesh: Mesh | None = None, topk_mode: str = "auto"
    ) -> "PosteriorPredictor":
        """Load a predictor from an artifact directory.

        Args:
            directory: Artifact written by ``BPMFEngine.export()``.
            mesh: Optional serve mesh (default: all visible devices).
            topk_mode: Default ``top_k`` execution mode (see
                :meth:`__init__`).

        Returns:
            A ready predictor.

        Raises:
            ArtifactError: Typed load failure — see
                :mod:`repro.serve.artifact`.
        """
        meta, arrays = load_artifact(directory)
        return cls(meta, arrays, mesh, topk_mode=topk_mode)

    @classmethod
    def from_engine(cls, engine, mesh: Mesh | None = None) -> "PosteriorPredictor":
        """Build a predictor from a live engine, without touching disk.

        Args:
            engine: A fitted :class:`repro.bpmf.BPMFEngine` (anything with
                an ``_artifact_payload()``).
            mesh: Optional serve mesh.

        Returns:
            A predictor over the engine's current posterior summary —
            bitwise the same predictions a save/load round-trip yields.
        """
        meta, arrays = engine._artifact_payload()
        return cls(meta, arrays, mesh)

    # ------------------------------------------------------------------
    @property
    def num_kept_samples(self) -> int:
        """Retained per-sweep factor samples (0 disables predictive std)."""
        return int(self._Us.shape[0])

    def _pad_class(self, n: int) -> int:
        size = self.mesh.devices.size
        return round_up(next_power_of_two(max(int(n), _MIN_PAD)), size)

    def _queries(self, ids: np.ndarray, limit: int, what: str) -> np.ndarray:
        ids = np.asarray(ids, np.int32).reshape(-1)
        if ids.size and (ids.min() < 0 or ids.max() >= limit):
            raise ValueError(
                f"{what} ids must be in [0, {limit}), got range "
                f"[{ids.min()}, {ids.max()}]"
            )
        return ids

    def _pad_sharded(self, ids: np.ndarray, pad: int) -> jax.Array:
        out = np.zeros((pad,), np.int32)
        out[: ids.size] = ids
        return jax.device_put(out, self._batch_sharded)

    # ------------------------------------------------------------------
    def predict(
        self, rows: np.ndarray, cols: np.ndarray, return_std: bool = False
    ) -> np.ndarray | tuple[np.ndarray, np.ndarray]:
        """Batched point predictions for ``(user, movie)`` pairs.

        Args:
            rows: ``[B]`` user ids (original numbering).
            cols: ``[B]`` movie ids (original numbering).
            return_std: Also return the predictive std over the retained
                factor samples.

        Returns:
            ``[B]`` predicted ratings clipped to the training range, or
            ``(preds, std)`` when ``return_std``.

        Raises:
            ValueError: Mismatched batch shapes, out-of-range ids, or
                ``return_std`` on an artifact with no retained samples.
        """
        rows = self._queries(rows, self.meta.num_users, "user")
        cols = self._queries(cols, self.meta.num_movies, "movie")
        if rows.shape != cols.shape:
            raise ValueError(f"rows/cols batch mismatch: {rows.shape} vs {cols.shape}")
        if return_std and self.num_kept_samples == 0:
            raise ValueError(
                "predictive std needs retained factor samples; this artifact "
                "was exported with num_kept_samples=0 "
                "(RunConfig.keep_factor_samples)"
            )
        B = rows.size
        pad = self._pad_class(B)
        r = self._pad_sharded(rows, pad)
        c = self._pad_sharded(cols, pad)
        lo, hi = self.meta.min_rating, self.meta.max_rating
        preds = np.asarray(_predict_pairs(self._U, self._V, r, c, self._mean, lo, hi))[:B]
        if not return_std:
            return preds
        std = np.asarray(
            _predict_pairs_std(self._Us, self._Vs, r, c, self._mean, lo, hi)
        )[:B]
        return preds, std

    def _use_sharded_topk(self, sharded: bool | None) -> bool:
        if sharded is not None:
            return bool(sharded)
        if self.topk_mode == "auto":
            return (
                self.mesh.devices.size > 1
                and self.meta.num_movies >= _AUTO_SHARD_MIN_ITEMS
            )
        return self.topk_mode == "sharded"

    def _top_k_sharded(
        self, users_padded: jax.Array, k: int
    ) -> tuple[np.ndarray, np.ndarray]:
        """Per-shard device top-k over the item-sharded catalog + host merge."""
        if self._V_sharded is None:
            self._V_sharded = shard_items(np.asarray(self._V), self.mesh)
            self._local_topk = build_local_topk(self.mesh, self.meta.num_movies)
        lo, hi = self.meta.min_rating, self.meta.max_rating
        cand_ids, cand_vals = self._local_topk(
            self._U, self._V_sharded, users_padded, self._mean, k, lo, hi
        )
        return merge_topk(np.asarray(cand_ids), np.asarray(cand_vals), k)

    def top_k(
        self, user: int | np.ndarray, k: int, sharded: bool | None = None
    ) -> tuple[np.ndarray, np.ndarray]:
        """Highest-scoring movies for one user (or a batch of users).

        Args:
            user: A user id, or a ``[B]`` array of user ids.
            k: Number of movies to return (clamped to the catalog size).
            sharded: Force the item-sharded (``True``) or replicated
                (``False``) program; ``None`` follows the constructor's
                ``topk_mode``. Both return the same ranking (sharded merge
                reproduces ``jax.lax.top_k`` ordering incl. tie-breaks).

        Returns:
            ``(ids, scores)`` — ``[k]`` arrays for a scalar ``user``,
            ``[B, k]`` for a batch. Scores are clipped predicted ratings.

        Raises:
            ValueError: Out-of-range user ids or ``k < 1``.
        """
        if k < 1:
            raise ValueError(f"top_k needs k >= 1, got {k}")
        k = min(int(k), self.meta.num_movies)
        scalar = np.ndim(user) == 0
        users = self._queries(np.atleast_1d(np.asarray(user)), self.meta.num_users, "user")
        pad = self._pad_class(users.size)
        if self._use_sharded_topk(sharded):
            # item-sharded path: the user batch is REPLICATED (every shard
            # scores all users against its item slab), so pad via the
            # replicated sharding instead of the batch-sharded one
            u_host = np.zeros((pad,), np.int32)
            u_host[: users.size] = users
            u = jax.device_put(u_host, self._replicated)
            ids, vals = self._top_k_sharded(u, k)
        else:
            u = self._pad_sharded(users, pad)
            lo, hi = self.meta.min_rating, self.meta.max_rating
            ids, vals = _top_k(self._U, self._V, u, self._mean, k, lo, hi)
        ids = np.asarray(ids)[: users.size]
        vals = np.asarray(vals)[: users.size]
        return (ids[0], vals[0]) if scalar else (ids, vals)


class PredictorHandle:
    """Atomically swappable reference to the live :class:`PosteriorPredictor`.

    The hot-swap primitive of the serving server (DESIGN.md §11): request
    handlers read the current predictor with :meth:`get` exactly once per
    coalesced batch, and :meth:`swap` replaces it in a single reference
    assignment (atomic under the GIL) — so every batch runs start-to-finish
    against one posterior, in-flight batches drain on the artifact they
    started with, and no request ever observes a half-loaded artifact
    (the new predictor is fully constructed *before* the swap).
    """

    def __init__(self, predictor: PosteriorPredictor):
        """Wrap the initial predictor at generation 0.

        Args:
            predictor: The predictor to serve until the first swap.
        """
        self._current: tuple[PosteriorPredictor, int] = (predictor, 0)

    @property
    def generation(self) -> int:
        """Completed swaps (0 = the artifact the server started with)."""
        return self._current[1]

    def get(self) -> PosteriorPredictor:
        """The live predictor (one atomic read — call once per batch)."""
        return self._current[0]

    def get_with_generation(self) -> tuple[PosteriorPredictor, int]:
        """Consistent ``(predictor, generation)`` pair in one atomic read."""
        return self._current

    def swap(self, predictor: PosteriorPredictor) -> int:
        """Atomically publish a new predictor.

        Args:
            predictor: Fully-constructed (validated + device-resident)
                replacement.

        Returns:
            The new generation number.
        """
        gen = self._current[1] + 1
        self._current = (predictor, gen)
        return gen
