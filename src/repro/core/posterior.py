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

In the tensor model (BPTF) the neighbour vector of a rating is the Hadamard
product of the rows of its two other sides, ``x = V_j * T_k`` for a user,
and the time factors are drawn as a chain (:func:`update_time_chain`).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from repro.core.types import (
    DRAW_SCOPE, GRAM_SCOPE, TIME_CHAIN_SCOPE, Bucket, BucketedSide, HyperParams,
)

_F32 = jax.lax.Precision.HIGHEST


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
    X_opp2: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(G, g) with G = alpha * sum_j x_j x_j^T  [B,K,K], g = alpha * sum_j x_j r_j [B,K].

    ``x_j`` is the neighbour's row of ``X_opp``, times its row of ``X_opp2``
    (at ``bucket.nbr2``) when the bucket is three-way.

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
        X2=None if bucket.nbr2 is None else X_opp2, nbr2=bucket.nbr2,
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
    X_opp2: jax.Array | None = None,
) -> jax.Array:
    """Sample all items of one bucket and scatter them into X_side.

    Bucket rows with ``item_ids == -1`` are padding and dropped by the
    scatter (mode="drop"). The bucket is drawn in row tiles
    (:meth:`~repro.core.types.Bucket.row_tiles`) under ``lax.map``; every
    item's draw depends only on its own row and id, so the samples are those
    of the bucket in one piece.
    """

    def draw(b: Bucket) -> jax.Array:
        G, g = gram_terms(X_opp, b, alpha, compute_dtype, gram_impl, X_opp2)
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
    X_opp2: jax.Array | None = None,
) -> jax.Array:
    """One half-sweep: resample every item of X_side given X_opp (and, for
    a three-way side, ``X_opp2``).

    Items are conditionally independent given (X_opp, hyper), so bucket order
    does not matter statistically; we loop buckets smallest-P first (the
    paper's cheap-items-first scheduling).
    """
    for bucket in side.buckets:
        X_side = update_bucket(
            key, X_side, X_opp, bucket, hyper, alpha, compute_dtype, gram_impl, X_opp2
        )
    return X_side


def update_time_chain(
    key: jax.Array,
    T: jax.Array,
    U: jax.Array,
    V: jax.Array,
    side: BucketedSide,
    hyper: HyperParams,
    alpha: float,
    compute_dtype=jnp.float32,
    gram_impl: str | bool = "xla",
) -> jax.Array:
    """Draw the time factors ``T [K_T, K]`` in order, given U, V and the
    chain's ``(mu_T, Lam_T)`` (Xiong et al. 2010, section 4).

    Under the prior ``T_1 ~ N(mu_T, Lam_T^-1)``, ``T_k ~ N(T_{k-1},
    Lam_T^-1)`` the bins are not conditionally independent: bin ``k``'s
    conditional has precision ``Lam_T (1 + [k < K_T]) + G_k`` and linear term
    ``Lam_T p_k + [k < K_T] Lam_T T_{k+1} + g_k``, with ``p_1 = mu_T`` and
    ``p_k`` the *new* ``T_{k-1}``; ``(G_k, g_k)`` are the Gram terms of
    ``x = U_i * V_j`` over the bin's ratings. The Gram terms of every bin
    come first, bucketed as the other sides are; only the ``K x K`` draws
    run as a ``lax.scan`` over the bins, bin ``k`` with the noise
    ``normal(fold_in(key, k))``.
    """
    n, K = T.shape
    with jax.named_scope(GRAM_SCOPE):
        G = jnp.zeros((n, K, K), jnp.float32)
        g = jnp.zeros((n, K), jnp.float32)
        for bucket in side.buckets:
            Gb, gb = jax.lax.map(
                lambda b: gram_terms(U, b, alpha, compute_dtype, gram_impl, V),
                bucket.row_tiles())
            # dead rows of the last tile are sliced off: their id -1 would wrap
            G = G.at[bucket.item_ids].set(Gb.reshape(-1, K, K)[: bucket.B], mode="drop")
            g = g.at[bucket.item_ids].set(gb.reshape(-1, K)[: bucket.B], mode="drop")
    with jax.named_scope(TIME_CHAIN_SCOPE):
        from repro.kernels import ops as kops

        Lam = hyper.Lam
        after = (jnp.arange(n) < n - 1).astype(jnp.float32)  # [k < K_T]
        T_next = jnp.concatenate([T[1:], jnp.zeros((1, K), T.dtype)]).astype(jnp.float32)
        lam_next = jnp.matmul(T_next, Lam.T, precision=_F32)  # Lam_T T_{k+1}, old T
        z = item_noise(key, jnp.arange(n, dtype=jnp.int32), K)

        def draw(prev, xs):
            Gk, gk, ak, lk, zk = xs
            lam_prev = jnp.matmul(Lam, prev, precision=_F32)
            x = kops.posterior_draw((Gk + ak * Lam)[None], (gk + ak * lk)[None], Lam,
                                    lam_prev, zk[None])[0]
            return x, x

        _, T_new = jax.lax.scan(draw, hyper.mu.astype(jnp.float32),
                                (G, g, after, lam_next, z))
    return T_new.astype(T.dtype)


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
