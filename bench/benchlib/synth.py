"""Seeded rating matrices at a configuration's published shape.

The benchmark's own copy of the generator model in ``data/synthetic.py``
(low-rank truth plus Gaussian noise, movie popularity ~ rank^-exponent,
user activity ~ lognormal), so that the data a cell is judged on cannot
change with the program. Two parts:

* the *pattern* -- which (row, col) pairs are rated, in a fixed order -- is
  drawn once per configuration from its ``pattern_seed`` and cached under
  ``bench/.cache/data``, keyed by the sizes and parameters it is drawn from. Every ``--seed`` therefore sees the same per-item
  rating counts, the same train/test split and the same program shapes, so
  a run compiles nothing that an earlier run of the cell compiled;
* the *values* -- latent truth, noise, and so every rating -- come from
  ``--seed``.

Categorical draws use ``searchsorted`` over cumulative weights, which has
the distribution of ``rng.choice(p=...)`` at a fraction of its cost.
"""
from __future__ import annotations

import hashlib
import json
import os
import time

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE = os.path.join(BENCH, ".cache", "data")


def _categorical(rng: np.random.Generator, cum: np.ndarray, size: int) -> np.ndarray:
    return np.searchsorted(cum, rng.random(size) * cum[-1], side="right").astype(np.int64)


def _popularity(rng: np.random.Generator, n: int, exponent: float) -> np.ndarray:
    pop = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** exponent
    rng.shuffle(pop)
    return np.cumsum(pop)


def _pairs_free(cfg: dict, rng: np.random.Generator) -> np.ndarray:
    """Pairs drawn as independent (activity x popularity) categoricals,
    de-duplicated and cut to ``nnz`` at random: the program's model."""
    M, N, nnz = cfg["num_users"], cfg["num_movies"], cfg["nnz"]
    cum_pop = _popularity(rng, N, cfg["popularity_exponent"])
    cum_act = np.cumsum(rng.lognormal(sigma=cfg["activity_sigma"], size=M))
    keys = np.zeros(0, np.int64)
    while keys.size < nnz:
        need = int((nnz - keys.size) * 1.3) + 1024
        new = _categorical(rng, cum_act, need) * N + _categorical(rng, cum_pop, need)
        keys = np.union1d(keys, new)
    return np.sort(rng.choice(keys, size=nnz, replace=False))


def _pairs_floored(cfg: dict, rng: np.random.Generator) -> np.ndarray:
    """Pairs with an exact per-user count of at least ``min_user_ratings``:
    the floor, plus the remainder shared out in proportion to lognormal
    activity; each user's movies are distinct draws by popularity (drawn
    with replacement, de-duplicated, topped up, and cut to the count at
    random)."""
    M, N, nnz = cfg["num_users"], cfg["num_movies"], cfg["nnz"]
    floor = cfg["min_user_ratings"]
    cum_pop = _popularity(rng, N, cfg["popularity_exponent"])
    act = rng.lognormal(sigma=cfg["activity_sigma"], size=M)
    share = act / act.sum() * (nnz - floor * M)
    extra = np.floor(share).astype(np.int64)
    top = np.argsort(extra - share)[: nnz - floor * M - int(extra.sum())]
    extra[top] += 1  # largest remainders, so the counts sum to nnz exactly
    want = np.minimum(floor + extra, N)
    if int(want.sum()) != nnz:
        raise ValueError("per-user counts cannot reach nnz under the movie count")
    done, held = [], np.zeros(0, np.int64)
    users, draws = np.arange(M, dtype=np.int64), want.astype(np.float64) * 1.3 + 4
    while users.size:
        n = np.ceil(draws).astype(np.int64)
        new = np.repeat(users, n) * N + _categorical(rng, cum_pop, int(n.sum()))
        keys = np.unique(np.concatenate([held, new]))
        got = np.bincount(keys // N, minlength=M)
        full = got[keys // N] >= want[keys // N]
        sel = keys[full]
        # a random subset of `want` distinct movies of each user that has enough
        prio = (sel // N) * (1 << 31) + rng.integers(0, 1 << 31, sel.size)
        sel = sel[np.argsort(prio, kind="stable")]
        rows = sel // N
        starts = np.searchsorted(rows, rows, side="left")
        done.append(sel[np.arange(sel.size) - starts < want[rows]])
        held = keys[~full]
        users = np.unique(held // N)
        # these users kept got/n of their draws distinct: ask for the rest at that rate
        short = (want[users] - got[users]).astype(np.float64)
        draws = short * 1.3 * want[users] / got[users] + 8
    return np.sort(np.concatenate(done))


def pattern(cfg: dict) -> tuple[np.ndarray, np.ndarray]:
    """(rows, cols) int32 of the configuration's rated pairs, sorted row-major."""
    os.makedirs(CACHE, exist_ok=True)
    keys = ("num_users", "num_movies", "nnz", "pattern_seed", "min_user_ratings",
            "popularity_exponent", "activity_sigma")
    tag = hashlib.sha256(json.dumps([cfg.get(k) for k in keys]).encode()).hexdigest()[:12]
    path = os.path.join(CACHE, f"{cfg['name']}.{tag}.pattern.npz")
    if os.path.exists(path):
        with np.load(path) as z:
            return z["rows"], z["cols"]
    rng = np.random.default_rng(cfg["pattern_seed"])
    keys = _pairs_floored(cfg, rng) if cfg.get("min_user_ratings") else _pairs_free(cfg, rng)
    N = cfg["num_movies"]
    rows, cols = (keys // N).astype(np.int32), (keys % N).astype(np.int32)
    tmp = f"{path}.{os.getpid()}.npz"
    np.savez(tmp, rows=rows, cols=cols)
    os.replace(tmp, path)  # atomic: a concurrent reader sees all or nothing
    return rows, cols


def values(cfg: dict, rows: np.ndarray, cols: np.ndarray, seed: int) -> np.ndarray:
    """Ratings of the pattern's pairs for ``seed``: U* V*^T + noise, on the
    configuration's scale (1-5 stars, or clipped to its value range)."""
    rng = np.random.default_rng([seed, 1])
    K = cfg["true_rank"]
    U = (rng.standard_normal((cfg["num_users"], K), np.float32) / np.float32(np.sqrt(K)))
    V = rng.standard_normal((cfg["num_movies"], K), np.float32)
    vals = np.einsum("nk,nk->n", U[rows], V[cols])
    vals += rng.standard_normal(vals.size, np.float32) * np.float32(cfg["noise_std"])
    if cfg["discretize"]:
        vals = np.clip(np.round(vals * 1.2 + 3.0), 1.0, 5.0)
    else:
        lo, hi = cfg["value_range"]
        vals = np.clip(vals, lo, hi)
    return vals.astype(np.float32)


def ratings(cfg: dict, seed: int, log=print) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(rows, cols, vals) for one run, timing the pattern and the values."""
    t0 = time.perf_counter()
    rows, cols = pattern(cfg)
    t1 = time.perf_counter()
    vals = values(cfg, rows, cols, seed)
    t2 = time.perf_counter()
    lo, hi = float(vals.min()), float(vals.max())
    log(f"data: {cfg['name']} {cfg['num_users']} x {cfg['num_movies']}, {rows.size} ratings "
        f"in [{lo}, {hi}]; pattern {t1 - t0:.3f} s, values {t2 - t1:.3f} s")
    return rows, cols, vals
