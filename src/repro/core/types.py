"""Core pytree containers for BPMF state, priors and bucketed rating data.

The rating matrix ``R`` (M users x N movies, sparse) is factorized as
``R ~ U @ V.T`` with ``U: [M, K]`` and ``V: [N, K]``. Conditional
independence of items given the opposite factor matrix is the source of all
parallelism in the paper; the containers here encode the bucketed layout that
makes that parallelism dense enough for the MXU.

The tensor model (BPTF, Xiong et al. 2010) adds a third side of ``K_T``
time bins, ``R_ijk ~ sum_d U_id V_jd T_kd``: the state then holds ``T`` and
its hyper-parameters, and every side's buckets hold two neighbour indices.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils import pytree_dataclass, static_field

#: Neighbor slots (rows x pad) per Gram tile. Buckets are processed in row
#: tiles of at most this many slots, so the per-item temporaries — the gathered
#: ``[rows, P, K+1]`` block and the ``[rows, K, K]`` Gram and Cholesky terms —
#: are bounded by one tile and not by the biggest pad class of a side.
GRAM_TILE_SLOTS = 1 << 16


#: The sweep's layers, each a ``jax.named_scope`` in the program: the Gram
#: with its neighbour gather, the per-item posterior draw, the
#: hyper-parameter draw, the test predictions with the posterior summary, and
#: one ring exchange. A device op's ``op_name`` metadata carries the names of
#: the scopes it was traced under, so a profiler trace can be split by layer.
SWEEP_SCOPES = ("bpmf_gram", "posterior_draw", "hyper_draw", "sweep_predict", "ring_step")
GRAM_SCOPE, DRAW_SCOPE, HYPER_SCOPE, PREDICT_SCOPE, RING_SCOPE = SWEEP_SCOPES

#: The tensor model's sequential draw of the time factors, one bin after
#: another. A scope of its own and not a layer of :data:`SWEEP_SCOPES`: the
#: draws inside it stay under ``posterior_draw``, and a trace's split by
#: layer is the same for both models.
TIME_CHAIN_SCOPE = "time_chain"


def gram_tile_rows(P: int) -> int:
    """Rows per Gram tile for a bucket of pad ``P`` (:data:`GRAM_TILE_SLOTS`)."""
    return max(1, GRAM_TILE_SLOTS // max(int(P), 1))


def _tile_rows(B: int, P: int) -> int:
    return max(1, min(B, gram_tile_rows(P)))


def gram_slots(B: int, P: int) -> int:
    """Neighbour slots the Gram runs over for ``B`` rows at pad ``P``: every
    row tile of :meth:`Bucket.row_tiles` times ``P``, the dead rows that pad
    the last tile included."""
    rows = _tile_rows(B, P)
    return -(-B // rows) * rows * P


@pytree_dataclass
class NormalWishartPrior:
    """Fixed hyperprior p(mu, Lambda) = N(mu|mu0, (b0 Lam)^-1) W(Lam|W0, nu0)."""

    mu0: jax.Array  # [K]
    beta0: jax.Array  # scalar
    W0: jax.Array  # [K, K]
    nu0: jax.Array  # scalar

    @staticmethod
    def default(K: int, dtype: Any = jnp.float32) -> "NormalWishartPrior":
        return NormalWishartPrior(
            mu0=jnp.zeros((K,), dtype),
            beta0=jnp.asarray(2.0, dtype),
            W0=jnp.eye(K, dtype=dtype),
            nu0=jnp.asarray(float(K), dtype),
        )


@pytree_dataclass
class HyperParams:
    """Sampled (mu, Lambda) for one side (users or movies)."""

    mu: jax.Array  # [K]
    Lam: jax.Array  # [K, K] precision

    @staticmethod
    def init(K: int, dtype: Any = jnp.float32) -> "HyperParams":
        return HyperParams(mu=jnp.zeros((K,), dtype), Lam=jnp.eye(K, dtype=dtype))


@pytree_dataclass
class BPMFState:
    """Full Gibbs state; ``T`` and ``hyper_T`` only for the tensor model."""

    U: jax.Array  # [M, K] user latents
    V: jax.Array  # [N, K] movie latents
    hyper_U: HyperParams
    hyper_V: HyperParams
    sweep: jax.Array  # scalar int32, number of completed sweeps
    T: jax.Array | None = None  # [K_T, K] time-bin latents (BPTF)
    hyper_T: HyperParams | None = None  # (mu_T, Lam_T) of the time chain (BPTF)

    @property
    def K(self) -> int:
        return self.U.shape[-1]


@pytree_dataclass
class PosteriorAccum:
    """Device-resident posterior summary folded into the sweep loop carry.

    Replaces the engine's old host-side accumulator (which gathered the full
    (U, V) factors to the host after every post-burn-in sweep): the running
    posterior-mean sums and a rotating window of the ``keep`` most recent
    post-burn-in samples live next to the factors — sharded the same way on
    the distributed backends — and are updated inside the jitted block scan
    with an on-device burn-in predicate, so nothing crosses the host
    boundary until export/checkpoint time.

    Layout notes:
      * ``U_sum`` / ``V_sum`` accumulate float32 casts of the samples, so a
        resumed run folds bitwise the same values the old host path did.
      * ``U_window[count % keep]`` holds the sample drawn at post-burn-in
        index ``count`` (a rotating buffer); chronological order is
        reconstructed on the host from ``count`` when exporting.
      * ``count`` is the number of post-burn-in samples folded so far;
        ``filled`` is the number of *materialized* window entries
        (``min(count, keep)`` in an uninterrupted run, possibly fewer after
        restoring a checkpoint that retained fewer samples — e.g. one
        written with a smaller ``keep`` — so zero-filled slots are never
        reported as samples).
    """

    U_sum: jax.Array  # [M, K] f32 running sum of post-burn-in U samples
    V_sum: jax.Array  # [N, K] f32 running sum of post-burn-in V samples
    count: jax.Array  # scalar int32, post-burn-in samples folded
    filled: jax.Array  # scalar int32, valid window entries (<= keep)
    U_window: jax.Array  # [keep, M, K] f32 rotating recent-sample buffer
    V_window: jax.Array  # [keep, N, K] f32

    @property
    def keep(self) -> int:
        return self.U_window.shape[0]

    @staticmethod
    def init(num_users: int, num_movies: int, K: int, keep: int) -> "PosteriorAccum":
        return PosteriorAccum(
            U_sum=jnp.zeros((num_users, K), jnp.float32),
            V_sum=jnp.zeros((num_movies, K), jnp.float32),
            count=jnp.zeros((), jnp.int32),
            filled=jnp.zeros((), jnp.int32),
            U_window=jnp.zeros((keep, num_users, K), jnp.float32),
            V_window=jnp.zeros((keep, num_movies, K), jnp.float32),
        )


@pytree_dataclass
class Bucket:
    """A dense, padded group of items with similar rating counts.

    All arrays are device arrays; ``item_ids`` indexes the side being updated,
    ``nbr`` indexes the opposite side. Padded neighbor slots have index 0 and
    ``nnz`` masks them out. In the tensor model a slot has a second
    neighbour, of the third side, in ``nbr2``.
    """

    item_ids: jax.Array  # [B] int32
    nbr: jax.Array  # [B, P] int32, padded neighbor (opposite-side) indices
    val: jax.Array  # [B, P] f32, centered ratings, 0 in padding
    nnz: jax.Array  # [B] int32, true rating count per item
    nbr2: jax.Array | None = None  # [B, P] int32, second neighbour (BPTF)

    @property
    def B(self) -> int:
        return self.item_ids.shape[0]

    @property
    def P(self) -> int:
        return self.nbr.shape[1]

    def mask(self) -> jax.Array:
        return (jnp.arange(self.P, dtype=jnp.int32)[None, :] < self.nnz[:, None]).astype(self.val.dtype)

    def row_tiles(self) -> "Bucket":
        """This bucket as ``[n, rows]`` tiles for ``lax.map`` / ``lax.scan``,
        with ``rows = min(B, gram_tile_rows(P))``: a bucket within one tile
        is one tile of its own rows.

        The last tile is padded with dead rows: ``item_ids == -1`` and
        ``nnz == 0``, so their Gram terms are exact zeros. A caller that
        *sets* rows slices them off; ``.at[-1]`` would wrap to the last row.
        """
        rows = _tile_rows(self.B, self.P)
        n = -(-self.B // rows)
        extra = n * rows - self.B

        def tile(x: jax.Array, fill: int) -> jax.Array:
            pads = [(0, extra)] + [(0, 0)] * (x.ndim - 1)
            x = jnp.pad(x, pads, constant_values=fill)
            return x.reshape((n, rows) + x.shape[1:])

        return Bucket(
            item_ids=tile(self.item_ids, -1),
            nbr=tile(self.nbr, 0),
            val=tile(self.val, 0),
            nnz=tile(self.nnz, 0),
            nbr2=None if self.nbr2 is None else tile(self.nbr2, 0),
        )


@pytree_dataclass
class BucketedSide:
    """All buckets for one side (the per-user or per-movie CSR, padded).

    ``buckets`` is a tuple so the container stays a valid pytree with static
    structure; bucket shapes differ, which is fine — the per-bucket update is
    traced once per shape.
    """

    buckets: tuple[Bucket, ...]
    num_items: int = static_field(default=0)

    def total_ratings(self) -> int:
        return int(sum(np.sum(np.asarray(b.nnz)) for b in self.buckets))

    def layout_stats(self) -> dict[str, int]:
        """Training ratings of this side and the Gram slots that hold them."""
        return {"ratings": self.total_ratings(),
                "gram_slots": sum(gram_slots(b.B, b.P) for b in self.buckets)}


@pytree_dataclass
class TestSet:
    """Held-out ratings for RMSE tracking."""

    rows: jax.Array  # [T] int32 user ids
    cols: jax.Array  # [T] int32 movie ids
    vals: jax.Array  # [T] f32 raw (uncentered) ratings
    times: jax.Array | None = None  # [T] int32 time bins (BPTF)


@pytree_dataclass
class BPMFData:
    """Everything the Gibbs sweep needs besides the state.

    users / movies are each the bucketed neighbor lists for updating that
    side; ``times``, for the tensor model, those of the time bins.
    ``mean_rating`` recenters ratings; predictions add it back.
    """

    users: BucketedSide  # update U: neighbors are movies
    movies: BucketedSide  # update V: neighbors are users
    test: TestSet
    mean_rating: jax.Array  # scalar f32
    num_users: int = static_field(default=0)
    num_movies: int = static_field(default=0)
    min_rating: float = static_field(default=-np.inf)
    max_rating: float = static_field(default=np.inf)
    times: BucketedSide | None = None  # update T: neighbours are (user, movie)
    num_times: int = static_field(default=0)


@dataclasses.dataclass(frozen=True)
class BPMFConfig:
    """Static configuration of the sampler (python-side, hashable)."""

    K: int = 32
    alpha: float = 2.0  # rating noise precision
    num_sweeps: int = 50
    burn_in: int = 8
    beta0: float = 2.0
    # bucketing: pad sizes tried in order; items with nnz > last go to chunked path
    bucket_pads: Sequence[int] = (8, 32, 128, 512, 2048)
    # distributed
    # "ring" (paper async, 1 step in flight) | "allgather" (sync baseline)
    # | "ring_async" (pipelined ring, `pipeline_depth` steps in flight)
    comm_mode: str = "ring"
    pipeline_depth: int = 1  # ring_async only: ppermutes in flight (d >= 1)
    sample_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32  # Gram contraction dtype (f32 or bf16)
    # Gram dispatch: "auto" (autotune cache -> heuristic), "pallas_fused"
    # (one fused kernel per ring step), "pallas" (per-bucket kernel), "xla"
    gram_impl: str = "auto"

    def prior(self) -> NormalWishartPrior:
        p = NormalWishartPrior.default(self.K, self.sample_dtype)
        return dataclasses.replace(p, beta0=jnp.asarray(self.beta0, self.sample_dtype))  # type: ignore[arg-type]
