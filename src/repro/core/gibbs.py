"""Single-program BPMF Gibbs sweep (paper Algorithm 1), jit-compiled.

Order per sweep (exactly Algorithm 1):
  1. sample movie hyper-parameters from V
  2. resample every movie from (U, R)
  3. sample user hyper-parameters from U
  4. resample every user from (new V, R)
  5. predict test points, update RMSE

The tensor model (BPTF, data with a time side) adds, before the
predictions, the time chain's hyper-parameters from T and the time factors
in order of their bins (``posterior.update_time_chain``); users and movies
then see ``V_j * T_k`` and ``U_i * T_k`` as their neighbours.

The distributed sampler in ``core/distributed.py`` reuses the same
sub-routines under ``shard_map``; this module is the sequential oracle.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import posterior
from repro.core.hyper import sample_hyper, sample_hyper_chain
from repro.core.prediction import (
    PredictionState,
    update_posterior_accum,
    update_predictions,
)
from repro.core.types import (
    BPMFConfig,
    BPMFData,
    BPMFState,
    HyperParams,
    PosteriorAccum,
)


class SweepMetrics(NamedTuple):
    rmse_sample: jax.Array
    rmse_avg: jax.Array
    sweep: jax.Array


def init_rows(key: jax.Array, ids: jax.Array, K: int, dtype=jnp.float32) -> jax.Array:
    """Per-item prior-predictive rows, keyed by item id.

    fold_in per id makes the init independent of array layout, so the
    distributed sampler (which stores relabeled, padded shards) starts from
    bitwise-identical factors — a precondition for the cross-version parity
    tests.
    """

    def one(i: jax.Array) -> jax.Array:
        return 0.1 * jax.random.normal(jax.random.fold_in(key, i), (K,), dtype)

    return jax.vmap(one)(ids)


def init_state(key: jax.Array, num_users: int, num_movies: int, cfg: BPMFConfig,
               num_times: int = 0) -> BPMFState:
    """Draw U, V from the prior predictive (standard normal scaled); with
    ``num_times`` bins, T starts at ones, so the first half-sweeps see
    BPMF's Gram terms."""
    ku, kv = jax.random.split(key)
    dt = cfg.sample_dtype
    tensor = num_times > 0
    return BPMFState(
        U=init_rows(ku, jnp.arange(num_users, dtype=jnp.int32), cfg.K, dt),
        V=init_rows(kv, jnp.arange(num_movies, dtype=jnp.int32), cfg.K, dt),
        hyper_U=HyperParams.init(cfg.K, dt),
        hyper_V=HyperParams.init(cfg.K, dt),
        sweep=jnp.zeros((), jnp.int32),
        T=jnp.ones((num_times, cfg.K), dt) if tensor else None,
        hyper_T=HyperParams.init(cfg.K, dt) if tensor else None,
    )


def sweep_keys(key: jax.Array, sweep: jax.Array, n: int = 4) -> tuple[jax.Array, ...]:
    """Deterministic per-sweep keys: (hyper_V, movies, hyper_U, users), and
    with ``n=6`` the time chain's (hyper_T, times).

    Keys depend only on (base key, sweep index) so any layout of the sampler
    draws identical randomness.
    """
    k = jax.random.fold_in(key, sweep)
    return tuple(jax.random.fold_in(k, i) for i in range(n))


def _sweep_body(
    key: jax.Array,
    state: BPMFState,
    pred_state: PredictionState,
    data: BPMFData,
    cfg: BPMFConfig,
) -> tuple[BPMFState, PredictionState, SweepMetrics]:
    """One Gibbs sweep (Algorithm 1), traceable — shared by the per-sweep
    jit entry point and the blocked ``lax.scan`` loop."""
    prior = cfg.prior()
    T, hyper_T = state.T, state.hyper_T
    tensor = data.times is not None
    keys = sweep_keys(key, state.sweep, 6 if tensor else 4)
    k_hv, k_v, k_hu, k_u = keys[:4]

    # movies given users (and times)
    hyper_V = sample_hyper(k_hv, state.V, prior)
    V = posterior.update_side(
        k_v, state.V, state.U, data.movies, hyper_V, cfg.alpha,
        cfg.compute_dtype, cfg.gram_impl, T,
    )
    # users given (updated) movies (and times)
    hyper_U = sample_hyper(k_hu, state.U, prior)
    U = posterior.update_side(
        k_u, state.U, V, data.users, hyper_U, cfg.alpha,
        cfg.compute_dtype, cfg.gram_impl, T,
    )
    if tensor:  # the time chain given (updated) users and movies
        k_ht, k_t = keys[4:]
        hyper_T = sample_hyper_chain(k_ht, T, prior)
        T = posterior.update_time_chain(
            k_t, T, U, V, data.times, hyper_T, cfg.alpha, cfg.compute_dtype, cfg.gram_impl,
        )

    sweep = state.sweep + 1
    new_state = BPMFState(U=U, V=V, hyper_U=hyper_U, hyper_V=hyper_V, sweep=sweep,
                          T=T, hyper_T=hyper_T)
    pred_state, r_sample, r_avg = update_predictions(
        pred_state, U, V, data, burned_in=sweep > cfg.burn_in, T=T
    )
    return new_state, pred_state, SweepMetrics(r_sample, r_avg, sweep)


@partial(jax.jit, static_argnames=("cfg",))
def gibbs_sweep(
    key: jax.Array,
    state: BPMFState,
    pred_state: PredictionState,
    data: BPMFData,
    cfg: BPMFConfig,
) -> tuple[BPMFState, PredictionState, SweepMetrics]:
    return _sweep_body(key, state, pred_state, data, cfg)


def _gibbs_sweep_block(
    key: jax.Array,
    state: BPMFState,
    pred_state: PredictionState,
    accum: PosteriorAccum,
    data: BPMFData,
    cfg: BPMFConfig,
    block_size: int,
) -> tuple[BPMFState, PredictionState, PosteriorAccum, jax.Array]:
    """``block_size`` Gibbs sweeps in one jitted ``lax.scan`` — no host sync.

    The posterior accumulator (running mean sums + rotating recent-sample
    window) and the prediction accumulator travel in the scan carry; the
    burn-in gate is the on-device ``sweep > burn_in`` predicate, so blocks
    may straddle burn-in. Per-sweep randomness is keyed by ``state.sweep``
    exactly as in :func:`gibbs_sweep`, so any partition of a run into blocks
    draws identical samples.

    Returns:
        ``(state, pred_state, accum, metrics)`` with ``metrics`` a
        ``[block_size, 3]`` float32 device array of per-sweep
        ``(rmse_sample, rmse_avg, sweep)`` rows — one host transfer fetches
        the whole block's metrics.
    """

    def body(carry, _):
        st, pr, ac = carry
        st, pr, m = _sweep_body(key, st, pr, data, cfg)
        ac = update_posterior_accum(ac, st.U, st.V, st.sweep > cfg.burn_in)
        row = jnp.stack(
            [m.rmse_sample, m.rmse_avg, m.sweep.astype(jnp.float32)]
        )
        return (st, pr, ac), row

    (state, pred_state, accum), metrics = jax.lax.scan(
        body, (state, pred_state, accum), None, length=block_size
    )
    return state, pred_state, accum, metrics


gibbs_sweep_block = jax.jit(_gibbs_sweep_block, static_argnames=("cfg", "block_size"))

#: Carry-donating variant of :func:`gibbs_sweep_block` (same traced body,
#: same samples): the state, prediction and posterior-accumulator inputs are
#: donated so XLA writes each block's carry into the previous block's
#: buffers instead of allocating a second factor-sized set (DESIGN.md §13).
#: The donated inputs are *consumed* — callers that re-read a block's inputs
#: after the call (or hold external references to them) must use the
#: non-donating entry point (``BackendConfig.donate_blocks="off"``).
gibbs_sweep_block_donated = jax.jit(
    _gibbs_sweep_block, static_argnames=("cfg", "block_size"), donate_argnums=(1, 2, 3)
)


def run(
    key: jax.Array,
    data: BPMFData,
    cfg: BPMFConfig,
    callback=None,
) -> tuple[BPMFState, PredictionState, list[SweepMetrics]]:
    """Deprecated entry point — prefer ``repro.bpmf.BPMFEngine``.

    Thin wrapper over the sequential backend's run loop
    (:func:`repro.bpmf.backends.run_sequential_prepared`); kept so existing
    imports keep working. New run-loop features (checkpointing, streaming
    metrics, backend selection) live only on the engine facade.
    """
    from repro.bpmf.backends import run_sequential_prepared

    return run_sequential_prepared(key, data, cfg, callback)
