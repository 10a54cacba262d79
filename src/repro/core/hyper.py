"""Normal-Wishart conditional sampling for the BPMF hyper-parameters.

Given the current latent matrix X ([n, K] rows = items of one side), the
conditional posterior of (mu, Lambda) is Normal-Wishart with updated
parameters (Salakhutdinov & Mnih 2008, eq. 14):

    beta* = beta0 + n              nu* = nu0 + n
    mu*   = (beta0 mu0 + n xbar) / (beta0 + n)
    W*^-1 = W0^-1 + n S + (beta0 n / (beta0 + n)) (mu0 - xbar)(mu0 - xbar)^T

with xbar the sample mean and S the (biased) sample covariance. We sample
Lambda ~ Wishart(W*, nu*) with the Bartlett decomposition and then
mu ~ N(mu*, (beta* Lambda)^-1).

The sampler is written over *sufficient statistics* (n, sum x, sum x x^T) so
the distributed version can psum the statistics across shards and then run
the identical math with the identical key — giving bitwise-comparable
hyper-samples between the single-device and distributed samplers (up to
reduction order in the psum).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from repro.core.types import HYPER_SCOPE, HyperParams, NormalWishartPrior

_F32 = jax.lax.Precision.HIGHEST  # f32 products on the TPU too, as on the CPU


def _sample_wishart(key: jax.Array, scale_chol: jax.Array, df: jax.Array) -> jax.Array:
    """Sample from Wishart(scale, df) given chol(scale) via Bartlett.

    Lambda = L A A^T L^T with L = chol(scale), A lower triangular,
    A_ii ~ sqrt(chi2(df - i)), A_ij ~ N(0, 1) for i > j.
    """
    K = scale_chol.shape[-1]
    kn, kc = jax.random.split(key)
    # chi2(k) = 2 * Gamma(k/2). df - arange(K) stays > 0 because df >= nu0 + n >= K.
    dfs = df - jnp.arange(K, dtype=scale_chol.dtype)
    chi2 = 2.0 * jax.random.gamma(kc, dfs / 2.0, dtype=scale_chol.dtype)
    diag = jnp.sqrt(chi2)
    normals = jax.random.normal(kn, (K, K), dtype=scale_chol.dtype)
    A = jnp.tril(normals, -1) + jnp.diag(diag)
    LA = jnp.matmul(scale_chol, A, precision=_F32)
    return jnp.matmul(LA, LA.T, precision=_F32)


def hyper_sufficient_stats(
    X: jax.Array, weights: jax.Array | None = None
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(n, sum_x, sum_xxT) — the distributable sufficient statistics.

    ``weights`` optionally masks rows (1 = real item, 0 = padding) so a
    sharded caller can include padded rows without biasing the posterior.
    """
    dtype = X.dtype
    if weights is None:
        n = jnp.asarray(X.shape[0], dtype)
        sx = jnp.sum(X, axis=0)
        sxx = jnp.matmul(X.T, X, precision=_F32)
    else:
        w = weights.astype(dtype)
        n = jnp.sum(w)
        Xw = X * w[:, None]
        sx = jnp.sum(Xw, axis=0)
        sxx = jnp.matmul(Xw.T, X, precision=_F32)
    return n, sx, sxx


def sample_hyper_from_stats(
    key: jax.Array,
    n: jax.Array,
    sum_x: jax.Array,
    sum_xxT: jax.Array,
    prior: NormalWishartPrior,
) -> HyperParams:
    """Sample (mu, Lambda) from the NW conditional given sufficient stats."""
    xbar = sum_x / n
    S = sum_xxT / n - jnp.outer(xbar, xbar)
    S = 0.5 * (S + S.T)

    beta_star = prior.beta0 + n
    nu_star = prior.nu0 + n
    mu_star = (prior.beta0 * prior.mu0 + n * xbar) / beta_star
    dm = prior.mu0 - xbar
    W0_inv = jnp.linalg.inv(prior.W0)
    Wstar_inv = W0_inv + n * S + (prior.beta0 * n / beta_star) * jnp.outer(dm, dm)
    return _sample_normal_wishart(key, mu_star, beta_star, nu_star, Wstar_inv)


def _sample_normal_wishart(
    key: jax.Array,
    mu_star: jax.Array,
    beta_star: jax.Array,
    nu_star: jax.Array,
    Wstar_inv: jax.Array,
) -> HyperParams:
    """(mu, Lambda) ~ NW(mu*, beta*, W*, nu*), given the inverse scale."""
    dtype = mu_star.dtype
    K = mu_star.shape[-1]
    Wstar_inv = 0.5 * (Wstar_inv + Wstar_inv.T)
    Wstar = jnp.linalg.inv(Wstar_inv)
    Wstar = 0.5 * (Wstar + Wstar.T)
    scale_chol = jnp.linalg.cholesky(Wstar + 1e-10 * jnp.eye(K, dtype=dtype))

    k_lam, k_mu = jax.random.split(key)
    Lam = _sample_wishart(k_lam, scale_chol, nu_star)
    Lam = 0.5 * (Lam + Lam.T)

    # mu ~ N(mu*, (beta* Lam)^-1): x = mu* + chol(Lam)^-T z / sqrt(beta*)
    L = jnp.linalg.cholesky(Lam + 1e-10 * jnp.eye(K, dtype=dtype))
    z = jax.random.normal(k_mu, (K,), dtype=dtype)
    mu = mu_star + solve_triangular(L.T, z, lower=False) / jnp.sqrt(beta_star)
    return HyperParams(mu=mu, Lam=Lam)


@jax.named_scope(HYPER_SCOPE)
def sample_hyper(
    key: jax.Array,
    X: jax.Array,
    prior: NormalWishartPrior,
    weights: jax.Array | None = None,
) -> HyperParams:
    """Sample (mu, Lambda) from the NW conditional given latent rows X."""
    n, sx, sxx = hyper_sufficient_stats(X, weights)
    return sample_hyper_from_stats(key, n, sx, sxx, prior)


@jax.named_scope(HYPER_SCOPE)
def sample_hyper_chain(key: jax.Array, T: jax.Array, prior: NormalWishartPrior) -> HyperParams:
    """Sample ``(mu_T, Lambda_T)`` of the time chain ``T_1 ~ N(mu_T,
    Lambda_T^-1)``, ``T_k ~ N(T_{k-1}, Lambda_T^-1)`` given its rows
    (Xiong et al. 2010, section 4)::

        beta* = beta0 + 1               nu* = nu0 + K_T
        mu*   = (beta0 mu0 + T_1) / (beta0 + 1)
        W*^-1 = W0^-1 + sum_{k>=2} d_k d_k^T + beta0 / (beta0 + 1) (T_1 - mu0)(T_1 - mu0)^T

    with ``d_k = T_k - T_{k-1}``; the draw is the one
    :func:`sample_hyper_from_stats` makes.
    """
    n = T.shape[0]
    D = T[1:] - T[:-1]
    d0 = T[0] - prior.mu0
    beta_star = prior.beta0 + 1.0
    mu_star = (prior.beta0 * prior.mu0 + T[0]) / beta_star
    Wstar_inv = (jnp.linalg.inv(prior.W0) + jnp.matmul(D.T, D, precision=_F32)
                 + (prior.beta0 / beta_star) * jnp.outer(d0, d0))
    return _sample_normal_wishart(key, mu_star, beta_star, prior.nu0 + n, Wstar_inv)
