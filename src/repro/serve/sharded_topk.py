"""Item-sharded catalog top-k: per-shard device top-k + host merge.

The replicated ``top_k`` program scores the *full* catalog on every device
(`U[users] @ V.T` over all M items) — fine for small catalogs, but the
recorded ``serve_latency.json`` shows the full scan is the serving p99 hot
spot, and a catalog too large to replicate cannot serve that way at all.
This module shards ``V`` along the **item axis** over the serve mesh:

1. each device scores its ``ceil(M/S)`` item rows against the (replicated)
   user batch and takes a *local* ``top_k'`` (``k' = min(k, M_shard)``) —
   an O(M/S) pass per device instead of O(M);
2. the ``[S, B, k']`` candidate slabs travel to the host (``S·B·k'`` floats
   — tiny next to the catalog) where a vectorized merge selects the global
   top-k with the same ordering contract as ``jax.lax.top_k``: scores
   descending, ties broken toward the lower item id.

A shard contributes at most ``k'`` candidates and can own at most ``k'`` of
the global top-k (``k' = k`` unless the shard is smaller than ``k``, in
which case it contributes everything it has), so the merge is exact. Pad
rows (``M`` rounded up to a mesh multiple) are masked to ``-inf`` before
the local top-k and can never surface: their ids lie outside ``[0, M)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.utils import round_up


def shard_items(V: np.ndarray, mesh: Mesh) -> jax.Array:
    """Place the item-factor matrix sharded along the item axis.

    Args:
        V: ``[M, K]`` item factors (host).
        mesh: 1-D ``("serve",)`` mesh to shard over.

    Returns:
        ``[M_pad, K]`` device array, ``M_pad = ceil(M/S)·S``, sharded
        ``P("serve", None)`` — each device holds one contiguous item slab.
    """
    V = np.asarray(V, np.float32)
    S = mesh.devices.size
    M_pad = round_up(max(V.shape[0], S), S)
    if M_pad != V.shape[0]:
        V = np.concatenate(
            [V, np.zeros((M_pad - V.shape[0], V.shape[1]), np.float32)]
        )
    return jax.device_put(V, NamedSharding(mesh, P("serve", None)))


def build_local_topk(mesh: Mesh, num_items: int):
    """Build the jitted per-shard scoring + local top-k program.

    Args:
        mesh: 1-D ``("serve",)`` mesh the item shards live on.
        num_items: True catalog size ``M`` (pad rows beyond it are masked).

    Returns:
        ``fn(U, V_sharded, users, mean, k, lo, hi) -> (ids, vals)`` with
        ``ids``/``vals`` shaped ``[S, B, k']`` — per-shard global item ids
        and clipped scores, ``k' = min(k, M_pad / S)``; compiled once per
        ``(pad class, k)``.
    """

    @functools.partial(jax.jit, static_argnames=("k", "lo", "hi"))
    def local_topk(U, V_sh, users, mean, k, lo, hi):
        m = V_sh.shape[0] // mesh.devices.size  # items per shard
        kl = min(k, m)

        def shard_fn(V_loc, U, users, mean):
            idx = jax.lax.axis_index("serve")
            gid = idx * m + jnp.arange(m, dtype=jnp.int32)
            scores = jnp.matmul(U[users], V_loc.T, precision=jax.lax.Precision.HIGHEST)
            scores = jnp.clip(scores + mean, lo, hi)
            scores = jnp.where(gid[None, :] < num_items, scores, -jnp.inf)
            vals, ids = jax.lax.top_k(scores, kl)
            return (gid[ids])[None], vals[None]

        return shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(P("serve", None), P(), P(), P()),
            out_specs=(P("serve", None, None), P("serve", None, None)),
            check_vma=False,
        )(V_sh, U, users, mean)

    return local_topk


def merge_topk(
    cand_ids: np.ndarray, cand_vals: np.ndarray, k: int
) -> tuple[np.ndarray, np.ndarray]:
    """Host-side exact merge of per-shard top-k candidates.

    Args:
        cand_ids: ``[S, B, k']`` global item ids from the shards.
        cand_vals: ``[S, B, k']`` matching scores.
        k: Global top-k size (``<=`` total candidates ``S·k'``).

    Returns:
        ``(ids [B, k], vals [B, k])`` with ``jax.lax.top_k`` ordering:
        scores descending, ties toward the lower item id.
    """
    S, B, kl = cand_ids.shape
    ids = np.ascontiguousarray(np.transpose(cand_ids, (1, 0, 2))).reshape(B, S * kl)
    vals = np.ascontiguousarray(np.transpose(cand_vals, (1, 0, 2))).reshape(B, S * kl)
    # primary key: score descending; secondary: item id ascending — the
    # tie-break jax.lax.top_k applies via positional order
    order = np.lexsort((ids, -vals), axis=1)[:, :k]
    rows = np.arange(B)[:, None]
    return ids[rows, order].astype(np.int32), vals[rows, order]
