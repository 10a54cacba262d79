"""Pallas TPU kernels for the BPMF gather + Gram accumulation hot loop.

For a bucket of items, each with up to P neighbors indexed into the
opposite-side latent shard ``X [Ns, K]``, compute per item

    G[b] = sum_p m[b,p] * x_{nbr[b,p]} x_{nbr[b,p]}^T        [K, K]
    g[b] = sum_p m[b,p] * val[b,p] * x_{nbr[b,p]}            [K]

TPU adaptation (DESIGN.md §2): a ragged HBM gather is the natural GPU
formulation; on TPU we exploit that the *ring-distributed* layout keeps the
per-step shard small enough for VMEM, so the gather becomes a one-hot MXU
contraction:

    W[b]  = onehot(nbr[b]) * mask[b]        [P, Ns]   (built in VREGs)
    Xg[b] = W[b] @ X                        [P, K]    (MXU)
    G[b]  = Xg[b]^T @ Xg[b]                 [K, K]    (MXU)
    g[b]  = Xg[b]^T @ (val[b] * mask[b])    [K]       (MXU)

Two kernels share this formulation (DESIGN.md §8):

* :func:`bpmf_gram_pallas` — the per-bucket kernel: grid over
  ``(item tiles, P chunks, Ns chunks)``, emitting per-bucket-row ``(G, g)``.
* :func:`bpmf_gram_fused` — the fused multi-bucket kernel: one
  ``pallas_call`` per ring step over a *flattened chunk layout* (every
  bucket row pre-split into width-``pc`` chunks, see ``ops.flatten_step``),
  scatter-accumulating directly into the per-local-item ``(G [cap,K,K],
  g [cap,K])`` running sums via ``input_output_aliases`` — no per-bucket
  Python loop, no XLA ``at[].add`` scatters.

Both kernels stream the opposite-side shard through VMEM in ``ns_chunk``-row
slices when it is too large to be resident (the Ns axis becomes a grid
dimension; the gathered rows are accumulated in a VMEM scratch buffer, which
is exact because each neighbor index hits exactly one Ns chunk — all other
chunks contribute exact zeros). FLOPs per item: P*Ns*K (gather) + P*K^2
(Gram) — the one-hot gather is profitable only when Ns is small (the sharded
case, which is exactly the paper's distributed hot loop).
``kernels.autotune`` owns the measured / heuristic choice between these
kernels and the XLA gather path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def mxu_precision(compute_dtype) -> jax.lax.Precision:
    """Contraction precision for a Gram of ``compute_dtype`` operands.

    f32 operands get ``HIGHEST``: at ``DEFAULT`` the TPU multiplies f32 in
    one bfloat16 pass, which rounds the gathered rows themselves. bf16
    operands are exact in one pass, so they keep ``DEFAULT``. The CPU
    computes f32 dots in f32 either way.
    """
    if jnp.dtype(compute_dtype) == jnp.float32:
        return jax.lax.Precision.HIGHEST
    return jax.lax.Precision.DEFAULT


def _gather_chunk(nbr, valid, x, base, compute_dtype):
    """One-hot MXU gather of one (rows, pc) chunk against one Ns slice.

    Args:
        nbr: ``[T, pc]`` int32 neighbor ids (global to the unchunked shard).
        valid: ``[T, pc]`` mask of in-range neighbor positions.
        x: ``[ns_chunk, K]`` slice of the shard, rows ``[base, base+ns_chunk)``.
        base: First shard row held in ``x``.
        compute_dtype: dtype of the one-hot contraction.

    Returns:
        ``[T, pc, K]`` f32 gathered rows; exact zeros where the neighbor lives
        in a different Ns chunk or the position is masked.
    """
    T, pc = nbr.shape
    ns = x.shape[0]
    row_ids = base + jax.lax.broadcasted_iota(jnp.int32, (T, pc, ns), 2)
    # mask and broadcast in f32: Mosaic cannot reshape packed bf16 vregs
    onehot = (nbr[:, :, None] == row_ids).astype(jnp.float32)
    onehot = (onehot * valid.astype(jnp.float32)[:, :, None]).astype(compute_dtype)
    return jax.lax.dot_general(
        onehot, x.astype(compute_dtype), (((2,), (0,)), ((), ())),
        precision=mxu_precision(compute_dtype), preferred_element_type=jnp.float32,
    )


def _gram(xg, compute_dtype):
    """``[T, pc, K]`` f32 gathered rows -> ``[T, K, K]`` f32 ``Xg^T Xg``."""
    xg = xg.astype(compute_dtype)
    return jax.lax.dot_general(
        xg, xg, (((1,), (1,)), ((0,), (0,))),
        precision=mxu_precision(compute_dtype), preferred_element_type=jnp.float32,
    )


def _linear(xg, val, mask, compute_dtype):
    """``[T, K]`` f32 ``Xg^T (val * mask)``; the broadcast stays in f32."""
    vm = (val * mask.astype(val.dtype))[:, :, None].astype(compute_dtype)
    return jax.lax.dot_general(
        xg.astype(compute_dtype), vm, (((1,), (1,)), ((0,), (0,))),
        precision=mxu_precision(compute_dtype), preferred_element_type=jnp.float32,
    )[:, :, 0]


def _gram_kernel(
    nbr_ref,  # [TB, PC] int32 (VMEM)
    val_ref,  # [TB, PC] f32 (VMEM)
    nnz_ref,  # [TB, 1] int32 (VMEM)
    x_ref,  # [ns_chunk, K] (VMEM slice of the shard)
    G_ref,  # [TB, K, K] f32 out (revisited across the P and Ns grid dims)
    g_ref,  # [TB, K] f32 out
    xg_ref,  # [TB, PC, K] f32 scratch: gather accumulator across Ns chunks
    *,
    pc: int,
    ns_chunk: int,
    num_ns: int,
    compute_dtype,
):
    TB = nbr_ref.shape[0]
    p = pl.program_id(1)
    n = pl.program_id(2)

    @pl.when((p == 0) & (n == 0))
    def _init_outputs():
        G_ref[...] = jnp.zeros_like(G_ref)
        g_ref[...] = jnp.zeros_like(g_ref)

    @pl.when(n == 0)
    def _init_gather():
        xg_ref[...] = jnp.zeros_like(xg_ref)

    nnz = nnz_ref[...]  # [TB, 1]
    pos = p * pc + jax.lax.broadcasted_iota(jnp.int32, (TB, pc), 1)
    mask = pos < nnz  # [TB, pc] valid neighbor positions of this P chunk
    xg_ref[...] += _gather_chunk(nbr_ref[...], mask, x_ref[...], n * ns_chunk, compute_dtype)

    @pl.when(n == num_ns - 1)
    def _contract():
        G_ref[...] += _gram(xg_ref[...], compute_dtype)
        g_ref[...] += _linear(xg_ref[...], val_ref[...], mask, compute_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("tb", "pc", "ns_chunk", "compute_dtype", "interpret"),
)
def bpmf_gram_pallas(
    X: jax.Array,  # [Ns, K]
    nbr: jax.Array,  # [B, P] int32, B % tb == 0, P % pc == 0
    val: jax.Array,  # [B, P]
    nnz: jax.Array,  # [B] int32
    *,
    tb: int = 8,
    pc: int = 128,
    ns_chunk: int | None = None,
    compute_dtype=jnp.float32,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Per-bucket gather+Gram kernel; returns ``(G [B,K,K], g [B,K])`` in f32.

    Grid: ``(B // tb, P // pc, Ns // ns_chunk)``. The ``(G, g)`` output tile
    is revisited across the last two grid dimensions; the gather is
    accumulated in VMEM scratch across Ns chunks so the shard streams
    through VMEM ``ns_chunk`` rows at a time (``ns_chunk=None`` keeps the
    whole shard resident — requires ``Ns % ns_chunk == 0``; ``ops.bpmf_gram``
    pads).
    """
    B, P = nbr.shape
    Ns, K = X.shape
    if ns_chunk is None:
        ns_chunk = Ns
    if B % tb:
        raise ValueError(f"B={B} not a multiple of tb={tb} (ops.py pads)")
    if P % pc:
        raise ValueError(f"P={P} not a multiple of pc={pc} (ops.py pads)")
    if Ns % ns_chunk:
        raise ValueError(f"Ns={Ns} not a multiple of ns_chunk={ns_chunk} (ops.py pads)")
    num_ns = Ns // ns_chunk
    grid = (B // tb, P // pc, num_ns)
    kernel = functools.partial(
        _gram_kernel, pc=pc, ns_chunk=ns_chunk, num_ns=num_ns, compute_dtype=compute_dtype
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, pc), lambda i, p, n: (i, p)),
            pl.BlockSpec((tb, pc), lambda i, p, n: (i, p)),
            pl.BlockSpec((tb, 1), lambda i, p, n: (i, 0)),
            pl.BlockSpec((ns_chunk, K), lambda i, p, n: (n, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tb, K, K), lambda i, p, n: (i, 0, 0)),
            pl.BlockSpec((tb, K), lambda i, p, n: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, K, K), jnp.float32),
            jax.ShapeDtypeStruct((B, K), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((tb, pc, K), jnp.float32)],
        interpret=interpret,
    )(nbr, val, nnz[:, None], X)


def _fused_kernel(
    G_in_ref,  # [cap, K, K] f32 (aliased with G_ref)
    g_in_ref,  # [cap, K] f32 (aliased with g_ref)
    item_ref,  # [TB, 1] int32 destination row per chunk (-1 = dead)
    cnt_ref,  # [TB, 1] int32 valid neighbors per chunk
    nbr_ref,  # [TB, PC] int32
    val_ref,  # [TB, PC] f32
    x_ref,  # [ns_chunk, K]
    G_ref,  # [cap, K, K] f32 out (whole-array block, revisited every step)
    g_ref,  # [cap, K] f32 out
    xg_ref,  # [TB, PC, K] f32 scratch: gather accumulator across Ns chunks
    *,
    tb: int,
    ns_chunk: int,
    num_ns: int,
    alpha: float,
    compute_dtype,
):
    i = pl.program_id(0)
    n = pl.program_id(1)

    @pl.when((i == 0) & (n == 0))
    def _init_outputs():
        G_ref[...] = G_in_ref[...]
        g_ref[...] = g_in_ref[...]

    @pl.when(n == 0)
    def _init_gather():
        xg_ref[...] = jnp.zeros_like(xg_ref)

    TB, pc = nbr_ref.shape
    cnt = cnt_ref[...]  # [TB, 1]
    pos = jax.lax.broadcasted_iota(jnp.int32, (TB, pc), 1)
    mask = pos < cnt
    xg_ref[...] += _gather_chunk(nbr_ref[...], mask, x_ref[...], n * ns_chunk, compute_dtype)

    @pl.when(n == num_ns - 1)
    def _contract_and_scatter():
        a = jnp.asarray(alpha, jnp.float32)
        Gp = a * _gram(xg_ref[...], compute_dtype)  # [TB, K, K]
        gp = a * _linear(xg_ref[...], val_ref[...], mask, compute_dtype)  # [TB, K]
        items = item_ref[...]
        for j in range(tb):  # tb is small and static: unrolled scatter
            idx = items[j, 0]
            # dead chunks (idx == -1) add exact zeros at a clamped slot —
            # no divergent control flow, and x + 0.0 is exact in f32
            ok = (idx >= 0).astype(jnp.float32)
            slot = jnp.maximum(idx, 0)
            G_ref[pl.ds(slot, 1), :, :] += (ok * Gp[j])[None]
            g_ref[pl.ds(slot, 1), :] += (ok * gp[j])[None]


@functools.partial(
    jax.jit,
    static_argnames=("tb", "ns_chunk", "alpha", "compute_dtype", "interpret"),
)
def bpmf_gram_fused(
    G: jax.Array,  # [cap, K, K] f32 running accumulator
    g: jax.Array,  # [cap, K] f32 running accumulator
    X: jax.Array,  # [Ns, K] opposite-side shard
    nbr: jax.Array,  # [C, pc] int32 flattened chunk neighbors, C % tb == 0
    val: jax.Array,  # [C, pc] f32
    item: jax.Array,  # [C] int32 destination row in [0, cap), -1 = dead chunk
    cnt: jax.Array,  # [C] int32 valid neighbors per chunk
    *,
    alpha: float = 1.0,
    tb: int = 8,
    ns_chunk: int | None = None,
    compute_dtype=jnp.float32,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused multi-bucket Gram step: one ``pallas_call`` per ring step.

    Consumes the flattened chunk layout built by ``ops.flatten_step`` (every
    bucket row of the step pre-split into width-``pc`` chunks) and
    accumulates ``alpha``-scaled contributions of *all* buckets directly
    into the per-local-item running sums::

        G[item[c]] += alpha * Xg_c^T Xg_c      g[item[c]] += alpha * Xg_c^T v_c

    ``(G, g)`` are donated via ``input_output_aliases`` and updated with
    in-kernel dynamic-row scatters, so the per-bucket ``pallas_call`` +
    two-``at[].add`` dispatch pattern collapses into a single kernel launch.
    Grid: ``(C // tb, Ns // ns_chunk)``; the Ns axis streams the shard
    through VMEM exactly as in :func:`bpmf_gram_pallas`.

    Returns:
        Updated ``(G, g)``, same shapes/dtypes as the inputs.
    """
    cap, K = g.shape
    C, pc = nbr.shape
    Ns = X.shape[0]
    if ns_chunk is None:
        ns_chunk = Ns
    if C % tb:
        raise ValueError(f"C={C} not a multiple of tb={tb} (ops.flatten_step pads)")
    if Ns % ns_chunk:
        raise ValueError(f"Ns={Ns} not a multiple of ns_chunk={ns_chunk} (ops pads)")
    num_ns = Ns // ns_chunk
    grid = (C // tb, num_ns)
    kernel = functools.partial(
        _fused_kernel,
        tb=tb,
        ns_chunk=ns_chunk,
        num_ns=num_ns,
        alpha=alpha,
        compute_dtype=compute_dtype,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((cap, K, K), lambda i, n: (0, 0, 0)),
            pl.BlockSpec((cap, K), lambda i, n: (0, 0)),
            pl.BlockSpec((tb, 1), lambda i, n: (i, 0)),
            pl.BlockSpec((tb, 1), lambda i, n: (i, 0)),
            pl.BlockSpec((tb, pc), lambda i, n: (i, 0)),
            pl.BlockSpec((tb, pc), lambda i, n: (i, 0)),
            pl.BlockSpec((ns_chunk, K), lambda i, n: (n, 0)),
        ],
        out_specs=[
            pl.BlockSpec((cap, K, K), lambda i, n: (0, 0, 0)),
            pl.BlockSpec((cap, K), lambda i, n: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((cap, K, K), jnp.float32),
            jax.ShapeDtypeStruct((cap, K), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((tb, pc, K), jnp.float32)],
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
    )(G, g, item[:, None], cnt[:, None], nbr, val, X)


def vmem_bytes_estimate(
    tb: int,
    pc: int,
    Ns: int,
    K: int,
    ns_chunk: int | None = None,
    compute_dtype=jnp.float32,
    cap: int = 0,
) -> int:
    """VMEM working-set estimate for one grid step of either Gram kernel.

    Reflects the actual block structure: ``nbr``/``val`` blocks are
    ``(tb, pc)`` (the P axis is a grid dimension, so full-P rows are never
    resident — the pre-restructure estimate undercounted those for
    ``P > 4096``), the shard block is ``(ns_chunk, K)``, and the gather
    scratch is ``(tb, pc, K)`` f32. ``cap > 0`` adds the fused kernel's
    whole-array ``(G, g)`` accumulator blocks (input + aliased output copy).
    """
    itemsize = jnp.dtype(compute_dtype).itemsize
    ns = Ns if ns_chunk is None else ns_chunk
    onehot = tb * pc * ns * itemsize
    x = ns * K * itemsize
    xg = tb * pc * K * 4
    blocks = tb * pc * (4 + 4)  # nbr + val chunk blocks
    if cap:
        acc = 2 * (cap * K * K * 4 + cap * K * 4)  # fused: in + out (G, g) windows
    else:
        acc = tb * K * K * 4 + tb * K * 4  # per-bucket: (tb, K, K) out tile
    return onehot + x + xg + blocks + acc
