"""Seeded timestamped ratings at a configuration's published shape.

The tensor configurations rate the same pairs as their matrix
configuration (``synth.pattern``) and add a time bin to each rating:

* the *months* are drawn once per configuration from its ``pattern_seed``:
  each user's first month is uniform over the ``num_times`` bins, and each
  of its ratings falls ``Geometric(burst_p) - 1`` months later, clipped to
  the last bin (users rate in bursts after they join);
* the *values* come from ``--seed``: ``<U*_i, V*_j, T*_k>`` plus Gaussian
  noise, on the configuration's scale as ``synth.values`` puts them, where
  ``T*`` is a random walk around 1 with steps of ``time_step_std``.
"""
from __future__ import annotations

import time

import numpy as np

from benchlib import synth


def months(cfg: dict, rows: np.ndarray) -> np.ndarray:
    """int32 time bin of each rated pair, fixed by ``pattern_seed``."""
    rng = np.random.default_rng([cfg["pattern_seed"], 2])
    n = cfg["num_times"]
    start = rng.integers(0, n, cfg["num_users"])
    late = rng.geometric(cfg["burst_p"], rows.size) - 1
    return np.minimum(start[rows] + late, n - 1).astype(np.int32)


def values(cfg: dict, rows: np.ndarray, cols: np.ndarray, times: np.ndarray,
           seed: int) -> np.ndarray:
    """Ratings of the pairs for ``seed``: <U*, V*, T*> + noise."""
    rng = np.random.default_rng([seed, 1])
    K = cfg["true_rank"]
    U = rng.standard_normal((cfg["num_users"], K), np.float32) / np.float32(np.sqrt(K))
    V = rng.standard_normal((cfg["num_movies"], K), np.float32)
    steps = rng.standard_normal((cfg["num_times"], K), np.float32)
    T = 1.0 + np.cumsum(steps * np.float32(cfg["time_step_std"]), axis=0, dtype=np.float32)
    vals = np.einsum("nk,nk,nk->n", U[rows], V[cols], T[times])
    vals += rng.standard_normal(vals.size, np.float32) * np.float32(cfg["noise_std"])
    if cfg["discretize"]:
        vals = np.clip(np.round(vals * 1.2 + 3.0), 1.0, 5.0)
    else:
        lo, hi = cfg["value_range"]
        vals = np.clip(vals, lo, hi)
    return vals.astype(np.float32)


def ratings(cfg: dict, seed: int, log=print):
    """(rows, cols, times, vals) for one run, timing each part."""
    t0 = time.perf_counter()
    rows, cols = synth.pattern(cfg)
    t1 = time.perf_counter()
    times = months(cfg, rows)
    t2 = time.perf_counter()
    vals = values(cfg, rows, cols, times, seed)
    t3 = time.perf_counter()
    log(f"data: {cfg['name']} {cfg['num_users']} x {cfg['num_movies']} x {cfg['num_times']}, "
        f"{rows.size} ratings in [{float(vals.min())}, {float(vals.max())}]; pattern "
        f"{t1 - t0:.3f} s, months {t2 - t1:.3f} s, values {t3 - t2:.3f} s")
    return rows, cols, times, vals
