"""Per-item conditional posterior updates, bucketed for dense TPU compute.

For one item i of side X (say a movie) with neighbor latents {u_j} and
centered ratings {r_ij}:

    precision  P_i = Lambda + alpha * sum_j u_j u_j^T          [K, K]
    linear     l_i = Lambda mu + alpha * sum_j u_j r_ij        [K]
    sample     x_i = P_i^{-1} l_i + chol(P_i)^{-T} z,  z ~ N(0, I_K)

The paper's multi-core contribution is making the "for all items" loop fast
under skewed nnz; here each nnz-bucket is one dense [B, P, K] gather plus a
Gram contraction (Pallas kernel on TPU), and the Cholesky solve is batched
(``kernels.ops.posterior_draw``: items on the vector lanes on TPU).

Noise is generated per *global item id* with ``jax.random.fold_in`` so every
layout (single device, ring-distributed, re-balanced) produces the same
sample for the same item — the cross-version RMSE-parity claim of the paper
(§V-B) becomes an exact test instead of a statistical one.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from repro.core.types import DRAW_SCOPE, GRAM_SCOPE, Bucket, BucketedSide, HyperParams


@jax.named_scope(DRAW_SCOPE)
def item_noise(key: jax.Array, item_ids: jax.Array, K: int, dtype=jnp.float32) -> jax.Array:
    """Per-item N(0, I_K) noise, independent of batch layout."""

    def one(i: jax.Array) -> jax.Array:
        return jax.random.normal(jax.random.fold_in(key, i), (K,), dtype)

    return jax.vmap(one)(item_ids)


def _normalize_gram_impl(gram_impl) -> str:
    """Accept the legacy ``use_pallas`` boolean in ``gram_impl`` position."""
    if isinstance(gram_impl, bool):
        return "pallas" if gram_impl else "xla"
    return gram_impl


@jax.named_scope(GRAM_SCOPE)
def gram_terms(
    X_opp: jax.Array,
    bucket: Bucket,
    alpha: float,
    compute_dtype=jnp.float32,
    gram_impl: str | bool = "xla",
) -> tuple[jax.Array, jax.Array]:
    """(G, g) with G = alpha * sum_j x_j x_j^T  [B,K,K], g = alpha * sum_j x_j r_j [B,K].

    ``gram_impl`` selects the gather+Gram implementation — ``"auto"``
    (autotune cache → heuristic), ``"pallas"`` or ``"xla"``; a legacy
    boolean maps to pallas/xla. Every choice dispatches through
    ``kernels.ops.bpmf_gram`` so there is exactly one implementation per
    impl: the XLA path gathers the masked ``[B, P, K]`` neighbor block
    once and contracts the augmented ``[Xn | val]`` block against itself
    (``ops._bpmf_gram_xla``), the Pallas path is the one-hot MXU kernel.
    """
    from repro.kernels import ops as kops

    gram_impl = _normalize_gram_impl(gram_impl)
    G, g = kops.bpmf_gram(
        X_opp, bucket.nbr, bucket.val, bucket.nnz,
        compute_dtype=compute_dtype,
        impl="pallas" if gram_impl == "pallas_fused" else gram_impl,
    )
    a = jnp.asarray(alpha, jnp.float32)
    return a * G, a * g


@jax.named_scope(DRAW_SCOPE)
def sample_from_terms(
    key: jax.Array,
    item_ids: jax.Array,
    G: jax.Array,
    g: jax.Array,
    hyper: HyperParams,
) -> jax.Array:
    """Draw x_i ~ N(P^-1 l, P^-1) for a batch of items from accumulated terms.

    P = G + Lambda and l = g + Lambda mu; the factor and solves dispatch
    through ``kernels.ops.posterior_draw``. The noise is drawn per global
    item id here, so the sample does not depend on the implementation's
    layout.
    """
    from repro.kernels import ops as kops

    K = g.shape[-1]
    lam_mu = jnp.matmul(hyper.Lam, hyper.mu, precision=jax.lax.Precision.HIGHEST)
    z = item_noise(key, item_ids, K, dtype=g.dtype)
    return kops.posterior_draw(G, g, hyper.Lam, lam_mu, z)


def update_bucket(
    key: jax.Array,
    X_side: jax.Array,
    X_opp: jax.Array,
    bucket: Bucket,
    hyper: HyperParams,
    alpha: float,
    compute_dtype=jnp.float32,
    gram_impl: str | bool = "xla",
) -> jax.Array:
    """Sample all items of one bucket and scatter them into X_side.

    Bucket rows with ``item_ids == -1`` are padding and dropped by the
    scatter (mode="drop"). The bucket is drawn in row tiles
    (:meth:`~repro.core.types.Bucket.row_tiles`) under ``lax.map``; every
    item's draw depends only on its own row and id, so the samples are those
    of the bucket in one piece.
    """

    def draw(b: Bucket) -> jax.Array:
        G, g = gram_terms(X_opp, b, alpha, compute_dtype, gram_impl)
        return sample_from_terms(key, b.item_ids, G, g, hyper)

    tiled = jax.lax.map(draw, bucket.row_tiles())
    with jax.named_scope(DRAW_SCOPE):
        new = tiled.reshape(-1, tiled.shape[-1])[: bucket.B]  # drop dead rows
        return X_side.at[bucket.item_ids].set(new.astype(X_side.dtype), mode="drop")


def update_side(
    key: jax.Array,
    X_side: jax.Array,
    X_opp: jax.Array,
    side: BucketedSide,
    hyper: HyperParams,
    alpha: float,
    compute_dtype=jnp.float32,
    gram_impl: str | bool = "xla",
) -> jax.Array:
    """One half-sweep: resample every item of X_side given X_opp.

    Items are conditionally independent given (X_opp, hyper), so bucket order
    does not matter statistically; we loop buckets smallest-P first (the
    paper's cheap-items-first scheduling).
    """
    for bucket in side.buckets:
        X_side = update_bucket(
            key, X_side, X_opp, bucket, hyper, alpha, compute_dtype, gram_impl
        )
    return X_side


# --- reference (naive, un-bucketed) implementation for testing -----------------


def update_item_naive(
    key: jax.Array,
    item_id: int,
    nbr: jax.Array,
    val: jax.Array,
    X_opp: jax.Array,
    hyper: HyperParams,
    alpha: float,
) -> jax.Array:
    """Textbook single-item update (no padding, no bucketing) — test oracle."""
    Xn = X_opp[nbr]  # [n, K]
    K = Xn.shape[-1]
    prec = hyper.Lam + alpha * Xn.T @ Xn
    lin = hyper.Lam @ hyper.mu + alpha * Xn.T @ val
    L = jnp.linalg.cholesky(prec)
    y = solve_triangular(L, lin, lower=True)
    mean = solve_triangular(L.T, y, lower=False)
    z = jax.random.normal(jax.random.fold_in(key, item_id), (K,), dtype=mean.dtype)
    return mean + solve_triangular(L.T, z, lower=False)
