"""Pallas TPU kernel for the per-item posterior draw, items on the lanes.

For a batch of items with symmetric positive-definite precisions
``P_b [K, K]``, linear terms ``l_b [K]`` and standard-normal noise ``z_b [K]``
the draw is

    L_b = chol(P_b),   x_b = L_b^-T (L_b^-1 l_b) + L_b^-T z_b.

Every item's draw is independent of every other item's, so the kernel lays
the batch on the lane axis: a matrix entry ``P[i, j]`` of a block of ``bt``
items is one ``[bt]`` lane vector, and a column slice ``P[j:, j]`` is a
``[K - j, bt]`` array whose rows fill the sublanes. The column-by-column
Cholesky and the substitutions are then plain f32 vector arithmetic with no
cross-lane work at all, and an item's result is bitwise the same whatever
its lane, its block or the size of the batch.

The factor is left-looking, column by column::

    L[j, j] = sqrt(a[j, j] - sum_{k<j} L[j, k]^2)
    L[i, j] = (a[i, j] - sum_{k<j} L[i, k] L[j, k]) / L[j, j]      i > j

with each sum taken in order of ``k``, true ``sqrt`` and true division. It
is kept column-major in VMEM (``l[j, i] = L[i, j]``), so column ``j``'s rows
``i >= j`` are contiguous. The forward substitution ``L y = l`` runs by
columns, which subtracts the terms of each row in the same order; the two
back substitutions ``L^T m = y`` and ``L^T n = z`` read column ``i`` of
``L`` as row ``i`` of ``L^T``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import round_up

LANES = 128
BLOCK_ITEMS = (1024, 512, 256, 128)  # candidate items per grid step, largest first
VMEM_BUDGET = 24 * 2**20  # working set the block size is chosen against
VMEM_LIMIT = 32 * 2**20  # scoped VMEM granted to the kernel (a v5e core has 128 MiB)


def vmem_bytes(K: int, bt: int) -> int:
    """VMEM the kernel holds at rank ``K`` and ``bt`` items per grid step.

    The precision block and the two ``[K, bt]`` inputs are double-buffered,
    as is the output; the factor and three ``[K, bt]`` vectors are scratch.
    Sublane extents round up to the 8-row tile.
    """
    k8 = round_up(K, 8)
    row = bt * 4  # one f32 lane vector
    inputs = 2 * (K * k8 + 2 * k8) * row
    output = 2 * k8 * row
    scratch = (K * k8 + 3 * k8) * row
    return inputs + output + scratch


def block_items(K: int) -> int | None:
    """Largest candidate block whose working set fits the budget, or None."""
    for bt in BLOCK_ITEMS:
        if vmem_bytes(K, bt) <= VMEM_BUDGET:
            return bt
    return None


def _draw_kernel(a_ref, lin_ref, z_ref, out_ref, l_ref, y_ref, m_ref, n_ref):
    """One block of items: ``a [K, K, bt]``, ``lin``/``z`` ``[K, bt]`` in,
    ``out [K, bt]``; scratch ``l [K, K, bt]`` (column-major factor) and
    ``y``, ``m``, ``n`` ``[K, bt]``."""
    K = a_ref.shape[0]

    # left-looking Cholesky: column j from a's column j and columns k < j
    for j in range(K):
        def subtract(k, acc, j=j):
            return acc - l_ref[k, j:, :] * l_ref[k, j:j + 1, :]

        acc = jax.lax.fori_loop(0, j, subtract, a_ref[j, j:, :])
        d = jnp.sqrt(acc[0:1])
        l_ref[j, j:, :] = acc / d
        l_ref[j, j:j + 1, :] = d

    # forward substitution L y = lin, by columns
    y_ref[...] = lin_ref[...]
    for k in range(K):
        yk = y_ref[k:k + 1, :] / l_ref[k, k:k + 1, :]
        y_ref[k:k + 1, :] = yk
        if k + 1 < K:
            y_ref[k + 1:, :] = y_ref[k + 1:, :] - l_ref[k, k + 1:, :] * yk

    # back substitutions L^T m = y and L^T n = z, by rows of L^T
    for i in reversed(range(K)):
        sm, sn = y_ref[i:i + 1, :], z_ref[i:i + 1, :]
        if i + 1 < K:
            col = l_ref[i, i + 1:, :]  # L[k, i] for k > i
            sm = sm - jnp.sum(col * m_ref[i + 1:, :], axis=0, keepdims=True)
            sn = sn - jnp.sum(col * n_ref[i + 1:, :], axis=0, keepdims=True)
        d = l_ref[i, i:i + 1, :]
        m_ref[i:i + 1, :] = sm / d
        n_ref[i:i + 1, :] = sn / d
    out_ref[...] = m_ref[...] + n_ref[...]


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def chol_draw(
    prec: jax.Array, lin: jax.Array, z: jax.Array, *, bt: int, interpret: bool = False
) -> jax.Array:
    """``mean + noise`` for a batch: ``prec [B, K, K]`` symmetric, ``lin`` and
    ``z`` ``[B, K]``; returns ``[B, K]`` f32.

    The batch moves to the lane axis in one transpose each way, padded to a
    power of two of at least one lane vector: batches of similar size then
    share one traced and compiled kernel, of which a sweep calls several.
    """
    B, K = lin.shape
    Bp = pl.next_power_of_2(max(B, LANES))
    pad = ((0, 0),) * 2 + ((0, Bp - B),)
    a = jnp.pad(jnp.transpose(prec.astype(jnp.float32), (1, 2, 0)), pad)
    lin_t = jnp.pad(lin.astype(jnp.float32).T, pad[1:])
    z_t = jnp.pad(z.astype(jnp.float32).T, pad[1:])
    return _draw_lanes(a, lin_t, z_t, bt=min(bt, Bp), interpret=interpret)[:, :B].T


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def _draw_lanes(a: jax.Array, lin: jax.Array, z: jax.Array, *, bt: int, interpret: bool):
    """The kernel over ``a [K, K, Bp]``, ``lin``/``z`` ``[K, Bp]``, in blocks
    of ``bt`` items; ``bt`` divides ``Bp``."""
    K, Bp = lin.shape
    vec = pl.BlockSpec((K, bt), lambda b: (0, b))
    return pl.pallas_call(
        _draw_kernel,
        grid=(Bp // bt,),
        in_specs=[pl.BlockSpec((K, K, bt), lambda b: (0, 0, b)), vec, vec],
        out_specs=vec,
        out_shape=jax.ShapeDtypeStruct((K, Bp), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((K, K, bt), jnp.float32),
            pltpu.VMEM((K, bt), jnp.float32),
            pltpu.VMEM((K, bt), jnp.float32),
            pltpu.VMEM((K, bt), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT
        ),
        interpret=interpret,
    )(a, lin, z)
