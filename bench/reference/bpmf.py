"""Plain reference of the BPMF Gibbs sampler, for deciding ``correct``.

Salakhutdinov & Mnih (2008), Algorithm 1 of arXiv:1705.04159, written
from the equations in straightforward ``jax.numpy``: per item, the
conditional precision ``Lam + alpha * sum_j x_j x_j^T`` and linear term
``Lam mu + alpha * sum_j x_j r_ij``, a Cholesky factor, two solves for the
mean and one for the noise; per side, the Normal-Wishart draw of
``(mu, Lam)`` by the Bartlett decomposition. It imports nothing of the
program and takes nothing the program made: it splits, centres and lays
out the ratings itself, in groups of items by the power of two above
their rating count.

What it shares with the program is the *statement* of the randomness, so
that the two can be compared draw by draw: the run key is
``split(key(run_seed))``; factors start at ``0.1 * normal(fold_in(k, id))``
per item id; sweep ``s`` uses ``fold_in(fold_in(k_run, s), i)`` for the
movie hyper-parameters, movies, user hyper-parameters and users
(``i = 0..3``); an item's noise is ``normal(fold_in(k_side, id))``; the
Wishart draw splits its key as the Bartlett construction is written in
``core/hyper.py``, with the same ``1e-10`` diagonal jitter before each
Cholesky factor of the hyper-parameter draw.

``precision`` is ``"highest"`` (float32 products, as the configuration
states) or ``"high"``: every product of two float32 operands in a
contraction is computed as three bfloat16 passes (hi*hi + hi*lo + lo*hi),
which is what an MXU does at ``Precision.HIGH``. Emulated explicitly, so
the control reads the same on the CPU as on the chip.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.linalg import solve_triangular

HIGHEST = jax.lax.Precision.HIGHEST
TILE_SLOTS = 1 << 15  # neighbour slots per tile of a group: bounds the temporaries


def _split_bf16(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def contract(spec: str, a, b, precision: str):
    """``einsum(spec, a, b)`` in float32 or in three bfloat16 passes."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)
    e = partial(jnp.einsum, spec, precision=HIGHEST)  # bf16 x bf16 is exact in f32
    return e(ah, bh) + e(ah, bl) + e(al, bh)


# --------------------------------------------------------------------------
# data: split, centre, group by rating count
# --------------------------------------------------------------------------


def split_mask(nnz: int, test_fraction: float, run_seed: int) -> np.ndarray:
    """Held-out mask: one uniform draw per rating, in the ratings' order."""
    return np.random.default_rng(run_seed).random(nnz) < test_fraction


def groups(items: np.ndarray, nbrs: np.ndarray, vals: np.ndarray, n_items: int) -> list:
    """Items grouped by the power of two at or above their rating count.

    Returns ``[(ids, nbr, val, cnt)]`` host arrays shaped ``[T, R, P]``
    (tiles x rows x slots). Padding rows carry ``id == n_items`` (dropped
    on write) and ``cnt == 0``; padding slots are masked by ``cnt``.
    """
    order = np.lexsort((nbrs, items))
    items, nbrs, vals = items[order], nbrs[order], vals[order]
    cnt = np.bincount(items, minlength=n_items)
    start = np.zeros(n_items + 1, np.int64)
    np.cumsum(cnt, out=start[1:])
    P_of = np.ones(n_items, np.int64)
    nz = cnt > 0
    P_of[nz] = 1 << np.ceil(np.log2(cnt[nz])).astype(np.int64)
    out = []
    for P in np.unique(P_of):
        ids = np.nonzero(P_of == P)[0]
        R = int(max(1, min(len(ids), TILE_SLOTS // P)))
        T = -(-len(ids) // R)
        rows = T * R
        g_ids = np.full(rows, n_items, np.int32)
        g_ids[: len(ids)] = ids
        g_cnt = np.zeros(rows, np.int32)
        g_cnt[: len(ids)] = cnt[ids]
        g_nbr = np.zeros((rows, P), np.int32)
        g_val = np.zeros((rows, P), np.float32)
        r = np.repeat(np.arange(len(ids)), cnt[ids])
        k = np.arange(r.size) - np.repeat(np.cumsum(cnt[ids]) - cnt[ids], cnt[ids])
        src = np.repeat(start[ids], cnt[ids]) + k
        g_nbr[r, k] = nbrs[src]
        g_val[r, k] = vals[src]
        out.append((g_ids.reshape(T, R), g_nbr.reshape(T, R, P),
                    g_val.reshape(T, R, P), g_cnt.reshape(T, R)))
    return out


class Problem:
    """The ratings as the reference sees them: split, centred, grouped."""

    def __init__(self, rows, cols, vals, num_users, num_movies, test_fraction, run_seed):
        test = split_mask(rows.size, test_fraction, run_seed)
        tr = ~test
        self.num_users, self.num_movies = int(num_users), int(num_movies)
        self.num_train = int(tr.sum())
        self.mean = float(np.mean(vals[tr], dtype=np.float64))
        self.lo, self.hi = float(vals.min()), float(vals.max())
        c = (vals[tr] - np.float32(self.mean)).astype(np.float32)
        self.users = groups(rows[tr], cols[tr], c, self.num_users)
        self.movies = groups(cols[tr], rows[tr], c, self.num_movies)
        self.test = (rows[test], cols[test], vals[test])


# --------------------------------------------------------------------------
# the sampler
# --------------------------------------------------------------------------


def init_factors(key, n: int, K: int):
    return jax.vmap(lambda i: 0.1 * jax.random.normal(jax.random.fold_in(key, i), (K,)))(
        jnp.arange(n, dtype=jnp.int32))


def hyper_draw(key, X, beta0: float, precision: str):
    """(mu, Lam) ~ Normal-Wishart conditional given the rows of X; prior
    mu0 = 0, W0 = I, nu0 = K."""
    n, K = X.shape
    nf = jnp.float32(n)
    xbar = jnp.sum(X, axis=0) / nf
    S = contract("nk,nl->kl", X, X, precision) / nf - jnp.outer(xbar, xbar)
    S = 0.5 * (S + S.T)
    b = jnp.float32(beta0) + nf
    mu_star = nf * xbar / b
    eye = jnp.eye(K, dtype=jnp.float32)
    Winv = eye + nf * S + (jnp.float32(beta0) * nf / b) * jnp.outer(xbar, xbar)
    Winv = 0.5 * (Winv + Winv.T)
    W = jnp.linalg.inv(Winv)
    W = 0.5 * (W + W.T)
    Lw = jnp.linalg.cholesky(W + 1e-10 * eye)
    k_lam, k_mu = jax.random.split(key)
    kn, kc = jax.random.split(k_lam)
    dof = (jnp.float32(K) + nf) - jnp.arange(K, dtype=jnp.float32)
    A = jnp.tril(jax.random.normal(kn, (K, K)), -1) + jnp.diag(
        jnp.sqrt(2.0 * jax.random.gamma(kc, dof / 2.0)))
    LA = contract("ij,jk->ik", Lw, A, precision)
    Lam = contract("ik,jk->ij", LA, LA, precision)
    Lam = 0.5 * (Lam + Lam.T)
    Ll = jnp.linalg.cholesky(Lam + 1e-10 * eye)
    z = jax.random.normal(k_mu, (K,))
    mu = mu_star + solve_triangular(Ll.T, z, lower=False) / jnp.sqrt(b)
    return mu, Lam


@partial(jax.jit, static_argnames=("alpha", "precision"))
def half_sweep(key, X_side, X_opp, grps, mu, Lam, alpha: float, precision: str):
    """Draw every item of one side given the other side and (mu, Lam)."""
    lam_mu = contract("kl,l->k", Lam, mu, precision)
    a = jnp.float32(alpha)

    def draw(tile):
        ids, nbr, val, cnt = tile
        m = (jnp.arange(nbr.shape[1])[None, :] < cnt[:, None]).astype(jnp.float32)
        Xn = X_opp[nbr] * m[..., None]
        G = a * contract("rpk,rpl->rkl", Xn, Xn, precision)
        g = a * contract("rpk,rp->rk", Xn, val * m, precision)
        L = jnp.linalg.cholesky(G + Lam)
        y = solve_triangular(L, (g + lam_mu)[..., None], lower=True)
        Lt = jnp.swapaxes(L, -1, -2)
        mean = solve_triangular(Lt, y, lower=False)[..., 0]
        z = jax.vmap(lambda i: jax.random.normal(jax.random.fold_in(key, i), (X_side.shape[1],)))(ids)
        return mean + solve_triangular(Lt, z[..., None], lower=False)[..., 0]

    for grp in grps:
        new = jax.lax.map(draw, grp)
        X_side = X_side.at[grp[0].reshape(-1)].set(new.reshape(-1, new.shape[-1]), mode="drop")
    return X_side


@partial(jax.jit, static_argnames=("beta0", "precision"))
def _hyper(key, X, beta0: float, precision: str):
    return hyper_draw(key, X, beta0, precision)


@partial(jax.jit, static_argnames=("lo", "hi"))
def _predict(U, V, r, c, mean, lo: float, hi: float):
    return jnp.clip(jnp.sum(U[r] * V[c], axis=-1) + mean, lo, hi)


def run(prob: Problem, K: int, alpha: float, beta0: float, run_seed: int,
        num_sweeps: int, burn_in: int, keep: int, precision: str = "highest") -> dict:
    """``num_sweeps`` sweeps from the seed; the state and summaries after them.

    Returns host arrays: ``U``, ``V``, ``mu_U``, ``Lam_U``, ``mu_V``,
    ``Lam_V``, ``U_mean``, ``V_mean``, ``U_samples``, ``V_samples`` (the
    last ``keep`` post-burn-in draws, oldest first) and ``rmse`` rows of
    ``(rmse_sample, rmse_avg)`` per sweep.
    """
    k_init, k_run = jax.random.split(jax.random.key(run_seed))
    ku, kv = jax.random.split(k_init)
    U = init_factors(ku, prob.num_users, K)
    V = init_factors(kv, prob.num_movies, K)
    put = lambda gs: [tuple(jnp.asarray(a) for a in g) for g in gs]  # noqa: E731
    users, movies = put(prob.users), put(prob.movies)
    r, c, t = (jnp.asarray(a) for a in prob.test)
    mean = jnp.float32(prob.mean)
    mu_U = mu_V = jnp.zeros((K,), jnp.float32)  # the state's hyper-parameters before sweep 1
    Lam_U = Lam_V = jnp.eye(K, dtype=jnp.float32)
    pred_sum = jnp.zeros(t.shape, jnp.float32)
    U_sum, V_sum, count, window, rmse = 0.0, 0.0, 0, [], []
    for s in range(num_sweeps):
        k = jax.random.fold_in(k_run, s)
        k_hv, k_v, k_hu, k_u = (jax.random.fold_in(k, i) for i in range(4))
        mu_V, Lam_V = _hyper(k_hv, V, beta0, precision)
        V = half_sweep(k_v, V, U, movies, mu_V, Lam_V, alpha, precision)
        mu_U, Lam_U = _hyper(k_hu, U, beta0, precision)
        U = half_sweep(k_u, U, V, users, mu_U, Lam_U, alpha, precision)
        p = _predict(U, V, r, c, mean, prob.lo, prob.hi)
        r_sample = float(jnp.sqrt(jnp.mean((p - t) ** 2)))
        if s + 1 > burn_in:
            pred_sum = pred_sum + p
            count += 1
            U_sum, V_sum = U_sum + U, V_sum + V
            window = (window + [(U, V)])[-keep:] if keep else []
            r_avg = float(jnp.sqrt(jnp.mean((pred_sum / count - t) ** 2)))
        else:
            r_avg = r_sample
        rmse.append((r_sample, r_avg))
    host = lambda x: np.asarray(x, np.float32)  # noqa: E731
    out = {"U": host(U), "V": host(V), "mu_U": host(mu_U), "Lam_U": host(Lam_U),
           "mu_V": host(mu_V), "Lam_V": host(Lam_V), "rmse": np.asarray(rmse)}
    if count:
        out["U_mean"] = host(U_sum / count)
        out["V_mean"] = host(V_sum / count)
        out["U_samples"] = np.stack([host(u) for u, _ in window])
        out["V_samples"] = np.stack([host(v) for _, v in window])
    return out


# --------------------------------------------------------------------------
# serving: the posterior predictive from an artifact's summary
# --------------------------------------------------------------------------


def _dot(a: np.ndarray, b: np.ndarray, precision: str) -> np.ndarray:
    """Row-wise dot products in float64, or with each float32 product in
    three bfloat16 passes (as ``contract`` does) for ``high``."""
    if precision == "highest":
        return np.einsum("...k,...k->...", a.astype(np.float64), b.astype(np.float64))
    out = contract("...k,...k->...", jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32),
                   precision)
    return np.asarray(out, np.float64)


def serve_predict(U, V, rows, cols, mean, lo, hi, precision="highest"):
    """Clipped plug-in prediction ``U[r] . V[c] + mean``."""
    return np.clip(_dot(U[rows], V[cols], precision) + mean, lo, hi)


def serve_std(Us, Vs, rows, cols, mean, lo, hi, precision="highest"):
    """Population std over the kept samples of the clipped predictions."""
    p = np.clip(_dot(Us[:, rows], Vs[:, cols], precision) + mean, lo, hi)
    return p.std(axis=0)


def serve_scores(U, V, user, mean, lo, hi, precision="highest"):
    """Clipped scores of every movie for one user."""
    return np.clip(_dot(np.broadcast_to(U[user], V.shape), V, precision) + mean, lo, hi)
