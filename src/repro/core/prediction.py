"""Test-point prediction and RMSE tracking (paper Algorithm 1, last loop)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.types import PREDICT_SCOPE, BPMFData, PosteriorAccum, TestSet
from repro.utils import pytree_dataclass


@pytree_dataclass
class PredictionState:
    """Running posterior-mean predictions over post-burn-in samples."""

    sum_pred: jax.Array  # [T] accumulated clipped predictions
    num_samples: jax.Array  # scalar int32

    @staticmethod
    def init(num_test: int) -> "PredictionState":
        return PredictionState(
            sum_pred=jnp.zeros((num_test,), jnp.float32),
            num_samples=jnp.zeros((), jnp.int32),
        )


def predict(U: jax.Array, V: jax.Array, test: TestSet, mean_rating: jax.Array,
            min_rating: float, max_rating: float, T: jax.Array | None = None) -> jax.Array:
    """Point predictions for the test triples from one posterior sample:
    ``<u_i, v_j>``, or ``<u_i, v_j, t_k>`` with time factors ``T``."""
    prod = U[test.rows] * V[test.cols]
    if T is not None:
        prod = prod * T[test.times]
    preds = jnp.sum(prod, axis=-1) + mean_rating
    return jnp.clip(preds, min_rating, max_rating)


def rmse(preds: jax.Array, vals: jax.Array) -> jax.Array:
    return jnp.sqrt(jnp.mean((preds - vals) ** 2))


@jax.named_scope(PREDICT_SCOPE)
def update_predictions(
    pred_state: PredictionState,
    U: jax.Array,
    V: jax.Array,
    data: BPMFData,
    burned_in: jax.Array,
    T: jax.Array | None = None,
) -> tuple[PredictionState, jax.Array, jax.Array]:
    """Accumulate posterior mean after burn-in; return (state, rmse_sample, rmse_avg)."""
    preds = predict(U, V, data.test, data.mean_rating, data.min_rating, data.max_rating, T)
    r_sample = rmse(preds, data.test.vals)
    inc = burned_in.astype(jnp.int32)
    new_state = PredictionState(
        sum_pred=pred_state.sum_pred + preds * inc,
        num_samples=pred_state.num_samples + inc,
    )
    n = jnp.maximum(new_state.num_samples, 1).astype(jnp.float32)
    avg = new_state.sum_pred / n
    # before burn-in the average is empty; report the sample RMSE instead
    r_avg = jnp.where(new_state.num_samples > 0, rmse(avg, data.test.vals), r_sample)
    return new_state, r_sample, r_avg


@jax.named_scope(PREDICT_SCOPE)
def update_posterior_accum(
    accum: PosteriorAccum, U: jax.Array, V: jax.Array, burned_in: jax.Array
) -> PosteriorAccum:
    """Fold one sample into the device-resident posterior summary.

    Pure on-device (scan-body safe): ``burned_in`` is a traced predicate, so
    blocks that straddle burn-in gate per sweep without a host sync. Sums add
    ``x * 1.0f`` / ``x * 0.0f``, which is bitwise what the old host
    accumulator's conditional ``+=`` computed; the rotating window writes the
    sample at slot ``count % keep`` only when burned in (slot 0 is re-written
    with its own value otherwise, a no-op).
    """
    inc = burned_in.astype(jnp.int32)
    gate = inc.astype(jnp.float32)
    Uf = U.astype(jnp.float32)
    Vf = V.astype(jnp.float32)
    keep = accum.keep
    U_win, V_win = accum.U_window, accum.V_window
    if keep > 0:  # static: keep == 0 means no window is kept at all
        pos = jnp.where(burned_in, jnp.mod(accum.count, keep), 0)
        u_cur = jax.lax.dynamic_index_in_dim(U_win, pos, axis=0, keepdims=False)
        v_cur = jax.lax.dynamic_index_in_dim(V_win, pos, axis=0, keepdims=False)
        u_row = jnp.where(burned_in, Uf, u_cur)
        v_row = jnp.where(burned_in, Vf, v_cur)
        U_win = jax.lax.dynamic_update_index_in_dim(U_win, u_row, pos, axis=0)
        V_win = jax.lax.dynamic_update_index_in_dim(V_win, v_row, pos, axis=0)
    return PosteriorAccum(
        U_sum=accum.U_sum + Uf * gate,
        V_sum=accum.V_sum + Vf * gate,
        count=accum.count + inc,
        filled=jnp.minimum(accum.filled + inc, keep),
        U_window=U_win,
        V_window=V_win,
    )
