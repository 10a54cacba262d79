"""Plain reference of the BPTF Gibbs sampler, for deciding ``correct``.

Bayesian Probabilistic Tensor Factorization (Xiong, Chen, Huang, Schneider
& Carbonell, "Temporal Collaborative Filtering with Bayesian Probabilistic
Tensor Factorization", SDM 2010, sections 3-4), written from the equations
in straightforward ``jax.numpy``. Ratings ``R_ijk`` of user ``i``, movie
``j`` in time bin ``k``, centred by the training mean:

* ``R_ijk ~ N(sum_d U_id V_jd T_kd, 1 / alpha)``;
* ``U_i ~ N(mu_U, Lam_U^-1)``, ``V_j ~ N(mu_V, Lam_V^-1)``, each
  ``(mu, Lam)`` Normal-Wishart ``NW(mu0 = 0, beta0, W0 = I, nu0 = K)``;
* ``T_1 ~ N(mu_T, Lam_T^-1)``, ``T_k ~ N(T_{k-1}, Lam_T^-1)`` for
  ``k = 2..K_T``, ``(mu_T, Lam_T) ~ NW(0, beta0, I, K)``.

A user's conditional has precision ``Lam_U + alpha sum x x^T`` and linear
term ``Lam_U mu_U + alpha sum r x`` over its ratings, with ``x = V_j * T_k``
(elementwise); a movie's the same with ``x = U_i * T_k``. Bin ``k`` is drawn
in order ``k = 1..K_T`` from precision ``Lam_T (1 + [k < K_T]) + alpha sum
x x^T``, ``x = U_i * V_j``, and linear term ``Lam_T p_k + [k < K_T] Lam_T
T_{k+1} + alpha sum r x``, where ``p_1 = mu_T`` and ``p_k`` is the new
``T_{k-1}``. ``(mu_T, Lam_T)`` has ``beta* = beta0 + 1``, ``mu* = (beta0 mu0 +
T_1) / (beta0 + 1)``, ``nu* = nu0 + K_T`` and ``W*^-1 = W0^-1 + sum_{k>=2}
(T_k - T_{k-1})(T_k - T_{k-1})^T + beta0 / (beta0 + 1) (T_1 - mu0)(T_1 -
mu0)^T``. Each sweep draws ``(mu_V, Lam_V)``, V, ``(mu_U, Lam_U)``, U,
``(mu_T, Lam_T)``, T, in that order.

Departures from Xiong et al.: ``alpha`` is fixed (2.0, as the BPMF
configurations fix it) where the paper gives it a Wishart prior and draws
it; ``T`` starts at ones, so the first half-sweeps are BPMF's; the
prediction is clipped to the rating range, as the program's RMSE is.

It imports nothing of the program and takes nothing the program made; it
splits, centres and groups the ratings itself, by the power of two above
each item's count, with ``bench/reference/bpmf.py``'s helpers for the
split, the contractions, the initial factors and the matrix sides'
Normal-Wishart draws. What it shares with the program is the *statement*
of the randomness, so that the two can be compared draw by draw: the run
key is ``split(key(run_seed))``; U and V start at ``0.1 *
normal(fold_in(k, id))`` per item id; sweep ``s`` uses
``fold_in(fold_in(k_run, s), i)`` for the movie hyper-parameters, movies,
user hyper-parameters, users, time hyper-parameters and time bins (``i =
0..5``); an item's noise, and bin ``k``'s, is ``normal(fold_in(k_side,
id))``; every Normal-Wishart draw splits its key as the Bartlett
construction is written in ``core/hyper.py``, with the same ``1e-10``
diagonal jitter before each Cholesky factor.

``precision`` is ``"highest"`` or ``"high"`` (three bfloat16 passes per
float32 product), as in ``bpmf.py``.
"""
from __future__ import annotations

import importlib.util
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.linalg import solve_triangular


def _load_bpmf():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "bpmf.py")
    spec = importlib.util.spec_from_file_location("reference_bpmf_for_bptf", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


bpmf = _load_bpmf()
contract, split_mask = bpmf.contract, bpmf.split_mask


def groups(items, nbrs, nbrs2, vals, n_items: int) -> list:
    """Items grouped by the power of two at or above their rating count.

    As ``bpmf.groups``, with a second neighbour per rating: returns
    ``[(ids, nbr, nbr2, val, cnt)]`` host arrays shaped ``[T, R, P]``.
    """
    order = np.lexsort((nbrs, items))
    items, nbrs, nbrs2, vals = items[order], nbrs[order], nbrs2[order], vals[order]
    cnt = np.bincount(items, minlength=n_items)
    start = np.zeros(n_items + 1, np.int64)
    np.cumsum(cnt, out=start[1:])
    P_of = np.ones(n_items, np.int64)
    nz = cnt > 0
    P_of[nz] = 1 << np.ceil(np.log2(cnt[nz])).astype(np.int64)
    out = []
    for P in np.unique(P_of):
        ids = np.nonzero(P_of == P)[0]
        R = int(max(1, min(len(ids), bpmf.TILE_SLOTS // P)))
        T = -(-len(ids) // R)
        rows = T * R
        g_ids = np.full(rows, n_items, np.int32)
        g_ids[: len(ids)] = ids
        g_cnt = np.zeros(rows, np.int32)
        g_cnt[: len(ids)] = cnt[ids]
        g_nbr = np.zeros((rows, P), np.int32)
        g_nbr2 = np.zeros((rows, P), np.int32)
        g_val = np.zeros((rows, P), np.float32)
        r = np.repeat(np.arange(len(ids)), cnt[ids])
        k = np.arange(r.size) - np.repeat(np.cumsum(cnt[ids]) - cnt[ids], cnt[ids])
        src = np.repeat(start[ids], cnt[ids]) + k
        g_nbr[r, k] = nbrs[src]
        g_nbr2[r, k] = nbrs2[src]
        g_val[r, k] = vals[src]
        out.append((g_ids.reshape(T, R), g_nbr.reshape(T, R, P), g_nbr2.reshape(T, R, P),
                    g_val.reshape(T, R, P), g_cnt.reshape(T, R)))
    return out


class Problem:
    """The ratings as the reference sees them: split, centred, grouped by
    each of the three sides."""

    def __init__(self, rows, cols, times, vals, num_users, num_movies, num_times,
                 test_fraction, run_seed):
        test = split_mask(rows.size, test_fraction, run_seed)
        tr = ~test
        self.num_users, self.num_movies = int(num_users), int(num_movies)
        self.num_times = int(num_times)
        self.num_train = int(tr.sum())
        self.mean = float(np.mean(vals[tr], dtype=np.float64))
        self.lo, self.hi = float(vals.min()), float(vals.max())
        c = (vals[tr] - np.float32(self.mean)).astype(np.float32)
        r, m, t = rows[tr], cols[tr], times[tr]
        self.users = groups(r, m, t, c, self.num_users)
        self.movies = groups(m, r, t, c, self.num_movies)
        self.times = groups(t, r, m, c, self.num_times)
        self.test = (rows[test], cols[test], times[test], vals[test])


# --------------------------------------------------------------------------
# the sampler
# --------------------------------------------------------------------------


def _normal_wishart(key, mu_star, b, nu, Winv, precision: str):
    """(mu, Lam) ~ NW(mu*, b, W*, nu) by the Bartlett decomposition."""
    K = mu_star.shape[0]
    eye = jnp.eye(K, dtype=jnp.float32)
    Winv = 0.5 * (Winv + Winv.T)
    W = jnp.linalg.inv(Winv)
    W = 0.5 * (W + W.T)
    Lw = jnp.linalg.cholesky(W + 1e-10 * eye)
    k_lam, k_mu = jax.random.split(key)
    kn, kc = jax.random.split(k_lam)
    dof = nu - jnp.arange(K, dtype=jnp.float32)
    A = jnp.tril(jax.random.normal(kn, (K, K)), -1) + jnp.diag(
        jnp.sqrt(2.0 * jax.random.gamma(kc, dof / 2.0)))
    LA = contract("ij,jk->ik", Lw, A, precision)
    Lam = contract("ik,jk->ij", LA, LA, precision)
    Lam = 0.5 * (Lam + Lam.T)
    Ll = jnp.linalg.cholesky(Lam + 1e-10 * eye)
    z = jax.random.normal(k_mu, (K,))
    return mu_star + solve_triangular(Ll.T, z, lower=False) / jnp.sqrt(b), Lam


@partial(jax.jit, static_argnames=("beta0", "precision"))
def chain_hyper_draw(key, T, beta0: float, precision: str):
    """(mu_T, Lam_T) given the chain's rows; prior mu0 = 0, W0 = I, nu0 = K."""
    n, K = T.shape
    D = T[1:] - T[:-1]
    b = jnp.float32(beta0) + 1.0
    Winv = (jnp.eye(K, dtype=jnp.float32) + contract("nk,nl->kl", D, D, precision)
            + (jnp.float32(beta0) / b) * jnp.outer(T[0], T[0]))
    return _normal_wishart(key, T[0] / b, b, jnp.float32(K + n), Winv, precision)


def _terms(X_opp, X_opp2, tile, alpha: float, precision: str):
    """alpha sum x x^T and alpha sum r x of a tile's rows, x = X_opp[j] * X_opp2[k]."""
    _, nbr, nbr2, val, cnt = tile
    m = (jnp.arange(nbr.shape[1])[None, :] < cnt[:, None]).astype(jnp.float32)
    Xn = X_opp[nbr] * X_opp2[nbr2] * m[..., None]
    a = jnp.float32(alpha)
    return (a * contract("rpk,rpl->rkl", Xn, Xn, precision),
            a * contract("rpk,rp->rk", Xn, val * m, precision))


def _noise(key, ids, K: int):
    return jax.vmap(lambda i: jax.random.normal(jax.random.fold_in(key, i), (K,)))(ids)


@partial(jax.jit, static_argnames=("alpha", "precision"))
def half_sweep(key, X_side, X_opp, X_opp2, grps, mu, Lam, alpha: float, precision: str):
    """Draw every item of a matrix side given the two other sides and (mu, Lam)."""
    lam_mu = contract("kl,l->k", Lam, mu, precision)

    def draw(tile):
        G, g = _terms(X_opp, X_opp2, tile, alpha, precision)
        L = jnp.linalg.cholesky(G + Lam)
        y = solve_triangular(L, (g + lam_mu)[..., None], lower=True)
        Lt = jnp.swapaxes(L, -1, -2)
        mean = solve_triangular(Lt, y, lower=False)[..., 0]
        z = _noise(key, tile[0], X_side.shape[1])
        return mean + solve_triangular(Lt, z[..., None], lower=False)[..., 0]

    for grp in grps:
        new = jax.lax.map(draw, grp)
        X_side = X_side.at[grp[0].reshape(-1)].set(new.reshape(-1, new.shape[-1]), mode="drop")
    return X_side


@partial(jax.jit, static_argnames=("n", "alpha", "precision"))
def time_terms(U, V, grps, n: int, alpha: float, precision: str):
    """Every bin's (alpha sum x x^T, alpha sum r x), x = U_i * V_j."""
    K = U.shape[1]
    G = jnp.zeros((n + 1, K, K), jnp.float32)  # row n takes the padding rows
    g = jnp.zeros((n + 1, K), jnp.float32)
    for grp in grps:
        Gt, gt = jax.lax.map(lambda tile: _terms(U, V, tile, alpha, precision), grp)
        ids = grp[0].reshape(-1)
        G = G.at[ids].set(Gt.reshape(-1, K, K))
        g = g.at[ids].set(gt.reshape(-1, K))
    return G[:n], g[:n]


@partial(jax.jit, static_argnames=("precision",))
def bin_draw(key, k, G, g, prev, nxt, last, mu, Lam, precision: str):
    """Bin k's draw given the previous bin's new value (``mu_T`` for the
    first) and the next bin's old one (absent for the last)."""
    c = jnp.where(last, 0.0, 1.0)
    P = G + Lam + c * Lam
    lin = g + contract("kl,l->k", Lam, prev, precision) + c * contract("kl,l->k", Lam, nxt,
                                                                         precision)
    L = jnp.linalg.cholesky(P)
    Lt = L.T
    mean = solve_triangular(Lt, solve_triangular(L, lin, lower=True), lower=False)
    z = jax.random.normal(jax.random.fold_in(key, k), (G.shape[0],))
    return mean + solve_triangular(Lt, z, lower=False)


def time_chain(key, T, U, V, grps, mu, Lam, alpha: float, precision: str):
    """T drawn bin by bin, in order."""
    n = T.shape[0]
    G, g = time_terms(U, V, grps, n, alpha, precision)
    rows, prev = [], mu
    for k in range(n):
        nxt = T[k + 1] if k + 1 < n else jnp.zeros_like(mu)
        prev = bin_draw(key, k, G[k], g[k], prev, nxt, k == n - 1, mu, Lam, precision)
        rows.append(prev)
    return jnp.stack(rows)


@partial(jax.jit, static_argnames=("lo", "hi"))
def _predict(U, V, T, r, c, t, mean, lo: float, hi: float):
    return jnp.clip(jnp.sum(U[r] * V[c] * T[t], axis=-1) + mean, lo, hi)


def run(prob: Problem, K: int, alpha: float, beta0: float, run_seed: int,
        num_sweeps: int, burn_in: int, keep: int, precision: str = "highest") -> dict:
    """``num_sweeps`` sweeps from the seed; the state and summaries after them.

    Returns host arrays: ``U``, ``V``, ``T``, the hyper-parameters ``mu_*``
    and ``Lam_*`` of the three sides, ``U_mean``, ``V_mean``, ``U_samples``,
    ``V_samples`` (the last ``keep`` post-burn-in draws, oldest first) and
    ``rmse`` rows of ``(rmse_sample, rmse_avg)`` per sweep.
    """
    k_init, k_run = jax.random.split(jax.random.key(run_seed))
    ku, kv = jax.random.split(k_init)
    U = bpmf.init_factors(ku, prob.num_users, K)
    V = bpmf.init_factors(kv, prob.num_movies, K)
    T = jnp.ones((prob.num_times, K), jnp.float32)
    put = lambda gs: [tuple(jnp.asarray(a) for a in g) for g in gs]  # noqa: E731
    users, movies, times = put(prob.users), put(prob.movies), put(prob.times)
    r, c, t, y = (jnp.asarray(a) for a in prob.test)
    mean = jnp.float32(prob.mean)
    zero, eye = jnp.zeros((K,), jnp.float32), jnp.eye(K, dtype=jnp.float32)
    mu_U = mu_V = mu_T = zero  # the state's hyper-parameters before sweep 1
    Lam_U = Lam_V = Lam_T = eye
    pred_sum = jnp.zeros(y.shape, jnp.float32)
    U_sum, V_sum, count, window, rmse = 0.0, 0.0, 0, [], []
    for s in range(num_sweeps):
        k = jax.random.fold_in(k_run, s)
        k_hv, k_v, k_hu, k_u, k_ht, k_t = (jax.random.fold_in(k, i) for i in range(6))
        mu_V, Lam_V = bpmf._hyper(k_hv, V, beta0, precision)
        V = half_sweep(k_v, V, U, T, movies, mu_V, Lam_V, alpha, precision)
        mu_U, Lam_U = bpmf._hyper(k_hu, U, beta0, precision)
        U = half_sweep(k_u, U, V, T, users, mu_U, Lam_U, alpha, precision)
        mu_T, Lam_T = chain_hyper_draw(k_ht, T, beta0, precision)
        T = time_chain(k_t, T, U, V, times, mu_T, Lam_T, alpha, precision)
        p = _predict(U, V, T, r, c, t, mean, prob.lo, prob.hi)
        r_sample = float(jnp.sqrt(jnp.mean((p - y) ** 2)))
        if s + 1 > burn_in:
            pred_sum = pred_sum + p
            count += 1
            U_sum, V_sum = U_sum + U, V_sum + V
            window = (window + [(U, V)])[-keep:] if keep else []
            r_avg = float(jnp.sqrt(jnp.mean((pred_sum / count - y) ** 2)))
        else:
            r_avg = r_sample
        rmse.append((r_sample, r_avg))
    host = lambda x: np.asarray(x, np.float32)  # noqa: E731
    out = {"U": host(U), "V": host(V), "T": host(T), "mu_U": host(mu_U),
           "Lam_U": host(Lam_U), "mu_V": host(mu_V), "Lam_V": host(Lam_V),
           "mu_T": host(mu_T), "Lam_T": host(Lam_T), "rmse": np.asarray(rmse)}
    if count:
        out["U_mean"] = host(U_sum / count)
        out["V_mean"] = host(V_sum / count)
        out["U_samples"] = np.stack([host(u) for u, _ in window])
        out["V_samples"] = np.stack([host(v) for _, v in window])
    return out
