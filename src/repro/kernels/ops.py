"""Jit'd dispatch wrappers around the Pallas kernels.

Each op picks the best implementation for the current shape and backend via
``kernels.autotune`` (measured cache entry → deterministic heuristic):

  - ``"pallas_fused"``: one fused ``pallas_call`` per ring step, scatter-
    accumulating every bucket into the per-item ``(G, g)`` running sums;
  - ``"pallas"``: the per-bucket one-hot MXU kernel;
  - ``"xla"``: gather + einsum (``ref.py`` is the semantic ground truth).

Off the TPU the Pallas paths run in interpret mode (:func:`pallas_interpret`)
for tests; the heuristic therefore defaults to ``"xla"`` off-TPU and only a
warmed autotune cache (or an explicit ``gram_impl``) selects a kernel. An
explicit kernel whose working set cannot be tiled raises instead of
silently running XLA; :func:`record_gram_decisions` lists what a trace
resolved to.

:func:`posterior_draw` dispatches the per-item draw the same way: the
lane-batched Cholesky kernel (``chol_draw.py``) on the TPU when the rank
fits its VMEM budget, XLA's batched Cholesky and triangular solves
otherwise; :func:`record_draw_decisions` lists what a trace resolved to.
"""
from __future__ import annotations

import contextlib
import contextvars

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from repro.core.types import DRAW_SCOPE, GRAM_SCOPE
from repro.kernels import autotune, chol_draw, ref
from repro.kernels.bpmf_gram import (
    bpmf_gram_fused, bpmf_gram_pallas, mxu_precision, vmem_bytes_estimate,
)
from repro.utils import round_up

# the lists record_gram_decisions() and record_draw_decisions() yield,
# while their blocks are open
_DECISIONS: contextvars.ContextVar[list | None] = contextvars.ContextVar(
    "gram_decisions", default=None
)
_DRAW_DECISIONS: contextvars.ContextVar[list | None] = contextvars.ContextVar(
    "draw_decisions", default=None
)


def pallas_interpret() -> bool:
    """Whether the Pallas kernels run in interpret mode: off the TPU only."""
    return jax.default_backend() != "tpu"


@contextlib.contextmanager
def record_gram_decisions():
    """Collect every Gram dispatch decision traced inside the ``with`` block.

    Yields a list that fills with ``(kind, (B, P, Ns, K), Decision)`` tuples:
    ``kind`` is ``"bucket"`` for one per-bucket (or per-row-tile) dispatch
    and ``"step"`` for one fused ring step. Only tracing records, so lower
    or first-call the program inside the block.
    """
    decisions: list = []
    token = _DECISIONS.set(decisions)
    try:
        yield decisions
    finally:
        _DECISIONS.reset(token)


@contextlib.contextmanager
def record_draw_decisions():
    """Collect every posterior-draw dispatch decision traced inside the block.

    Yields a list that fills with ``("draw", (B, K), Decision)`` tuples, one
    per traced :func:`posterior_draw`; a kernel decision carries its block
    of items in ``tb``. Only tracing records.
    """
    decisions: list = []
    token = _DRAW_DECISIONS.set(decisions)
    try:
        yield decisions
    finally:
        _DRAW_DECISIONS.reset(token)


def _record(kind: str, shape: tuple, dec: autotune.Decision) -> None:
    decisions = (_DRAW_DECISIONS if kind == "draw" else _DECISIONS).get()
    if decisions is not None:
        decisions.append((kind, shape, dec))


# re-exported for back-compat: the tiling choice lives with the autotuner now
pick_tiling = autotune.pick_tiling
_VMEM_BUDGET = autotune._VMEM_BUDGET


def _pad_axis(x: jax.Array, axis: int, multiple: int, fill=0) -> jax.Array:
    size = x.shape[axis]
    target = round_up(max(size, 1), multiple)
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads, constant_values=fill)


def _bpmf_gram_xla(
    X: jax.Array, nbr: jax.Array, val: jax.Array, nnz: jax.Array, compute_dtype,
    X2: jax.Array | None = None, nbr2: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """The production XLA path: gather once, one augmented contraction.

    The masked ``[B, P, K]`` neighbor block is materialized a single time
    and ``[Xn | val]`` is contracted against itself, so both the Gram
    matrix and the linear term come out of one einsum — XLA cannot
    rematerialize the gather per contraction (``ref.bpmf_gram_ref`` stays
    the naive two-einsum oracle)::

        Z = Y^T Y,  Y = [Xn, val]   →   G = Z[:K, :K],  g = Z[:K, K]

    With a second factor matrix and index per slot (the tensor model), the
    neighbour vector is the Hadamard product of two gathered rows,
    ``Xn = X[nbr] * X2[nbr2]``, and the contraction is the same.
    """
    P = nbr.shape[1]
    mask = (jnp.arange(P, dtype=jnp.int32)[None, :] < nnz[:, None]).astype(compute_dtype)
    Xn = jnp.take(X, nbr, axis=0).astype(compute_dtype)
    if X2 is not None:
        Xn = Xn * jnp.take(X2, nbr2, axis=0).astype(compute_dtype)
    Xn = Xn * mask[..., None]
    Y = jnp.concatenate([Xn, val.astype(compute_dtype)[..., None]], axis=-1)
    Z = jnp.einsum(
        "bpi,bpj->bij", Y, Y,
        precision=mxu_precision(compute_dtype), preferred_element_type=jnp.float32,
    )
    return Z[:, :-1, :-1].astype(jnp.float32), Z[:, :-1, -1].astype(jnp.float32)


def _fill_tiling(
    dec: autotune.Decision,
    B: int,
    P: int,
    Ns: int,
    K: int,
    compute_dtype,
    cap: int = 0,
) -> autotune.Decision:
    """Complete a pallas decision's missing (tb, pc, ns_chunk) fields.

    Explicit ``tb`` *and* ``pc`` are trusted verbatim (tests/benchmarks).

    Raises:
        ValueError: The working set cannot fit the VMEM budget even
            streamed (``chunked_tiling``'s contract). A kernel that was
            asked for never turns into XLA behind the caller's back.
    """
    tb, pc, ns = dec.tb, dec.pc, dec.ns_chunk
    if tb is not None and pc is not None:
        return dec
    tiling = autotune.pick_tiling(B, P, Ns, K, compute_dtype, cap)
    if tiling is not None:
        return autotune.Decision(dec.impl, tb or tiling[0], pc or tiling[1], ns)
    chunked = autotune.chunked_tiling(B, P, Ns, K, compute_dtype, cap)
    if chunked is None:
        raise ValueError(
            f"gram impl {dec.impl!r} cannot be tiled into the VMEM budget at "
            f"B={B} P={P} Ns={Ns} K={K} cap={cap}; use gram_impl='xla' or 'auto'"
        )
    return autotune.Decision(
        dec.impl, tb or chunked[0], pc or chunked[1], ns or chunked[2]
    )


@jax.named_scope(GRAM_SCOPE)
def bpmf_gram(
    X: jax.Array,
    nbr: jax.Array,
    val: jax.Array,
    nnz: jax.Array,
    *,
    compute_dtype=jnp.float32,
    impl: str = "auto",
    tb: int | None = None,
    pc: int | None = None,
    ns_chunk: int | None = None,
    force_pallas: bool | None = None,
    interpret: bool | None = None,
    X2: jax.Array | None = None,
    nbr2: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Dispatch the per-bucket gather+Gram op; returns (G [B,K,K], g [B,K]).

    ``impl`` is ``"auto"`` (autotune cache → heuristic), ``"pallas"`` or
    ``"xla"``; explicit ``tb``/``pc``/``ns_chunk`` override the decision's
    tiling. ``force_pallas`` is the legacy boolean override (maps to
    ``impl``). When the shard exceeds the VMEM budget the kernel streams it
    in ``ns_chunk`` rows; when even that cannot fit, a Pallas impl raises.

    ``X2`` and ``nbr2`` give each slot a second neighbour whose row
    multiplies the first (:func:`_bpmf_gram_xla`); such a three-way bucket
    runs on XLA (``auto``), and the two-way Pallas kernels refuse it.
    """
    B, P = nbr.shape
    Ns, K = X.shape
    if interpret is None:
        interpret = pallas_interpret()
    if force_pallas is not None:
        impl = "pallas" if force_pallas else "xla"
    if nbr2 is not None and impl != "xla":
        if impl != "auto":
            raise ValueError(f"gram impl {impl!r} is two-way only; a three-way bucket "
                             "runs on 'xla' or 'auto'")
        impl = "xla"
    if impl == "auto":
        dec = autotune.decide(autotune.bucket_key(B, P, Ns, K, compute_dtype))
    elif impl in ("pallas", "pallas_fused"):  # fused degenerates to per-bucket here
        dec = autotune.Decision("pallas", tb, pc, ns_chunk)
    elif impl == "xla":
        dec = autotune.Decision("xla")
    else:
        raise ValueError(f"unknown impl {impl!r}; one of auto|pallas|xla")
    if dec.impl != "xla":
        dec = _fill_tiling(
            autotune.Decision(
                dec.impl, tb or dec.tb, pc or dec.pc, ns_chunk or dec.ns_chunk
            ),
            B, P, Ns, K, compute_dtype,
        )
    _record("bucket", (B, P, Ns, K), dec)
    if dec.impl == "xla":
        return _bpmf_gram_xla(X, nbr, val, nnz, compute_dtype, X2, nbr2)
    nbr_p = _pad_axis(_pad_axis(nbr, 1, dec.pc), 0, dec.tb)
    val_p = _pad_axis(_pad_axis(val, 1, dec.pc), 0, dec.tb)
    nnz_p = _pad_axis(nnz, 0, dec.tb)
    X_p = _pad_axis(X, 0, dec.ns_chunk) if dec.ns_chunk else X
    G, g = bpmf_gram_pallas(
        X_p, nbr_p, val_p, nnz_p,
        tb=dec.tb, pc=dec.pc, ns_chunk=dec.ns_chunk,
        compute_dtype=compute_dtype, interpret=interpret,
    )
    return G[:B], g[:B]


def flatten_step(buckets, pc: int, tb: int):
    """Flatten a ring step's buckets into the fused kernel's chunk layout.

    Every bucket row is split into ``ceil(P / pc)`` width-``pc`` chunks
    (rows pad to a ``pc`` multiple with dead entries); chunks carry their
    destination item row and their own valid-count so the kernel needs no
    per-bucket metadata. Pure reshapes/concats — XLA fuses this into the
    surrounding sweep, and the layout is identical every sweep so it
    jit-caches with the step.

    Args:
        buckets: The step's ``Bucket`` tuple (``item_ids`` may contain -1
            padding rows, which become dead chunks).
        pc: Chunk width (the fused kernel's P tile).
        tb: Chunk-tile height; the flat axis pads to a multiple of it.

    Returns:
        ``(nbr [C, pc], val [C, pc], item [C], cnt [C])`` with
        ``C % tb == 0``; dead chunks have ``item == -1`` and ``cnt == 0``.
    """
    nbrs, vals, items, cnts = [], [], [], []
    for b in buckets:
        B, P = b.nbr.shape
        ck = round_up(P, pc) // pc
        nbrs.append(_pad_axis(b.nbr, 1, pc).reshape(B * ck, pc))
        vals.append(_pad_axis(b.val, 1, pc).reshape(B * ck, pc))
        items.append(jnp.repeat(b.item_ids, ck))
        offs = jnp.arange(ck, dtype=jnp.int32) * pc
        cnts.append(jnp.clip(b.nnz[:, None] - offs[None, :], 0, pc).reshape(B * ck))
    nbr = _pad_axis(jnp.concatenate(nbrs), 0, tb)
    val = _pad_axis(jnp.concatenate(vals), 0, tb)
    item = _pad_axis(jnp.concatenate(items), 0, tb, fill=-1)
    cnt = _pad_axis(jnp.concatenate(cnts), 0, tb)
    return nbr, val, item.astype(jnp.int32), cnt.astype(jnp.int32)


@jax.named_scope(GRAM_SCOPE)
def bpmf_gram_step(
    G: jax.Array,
    g: jax.Array,
    X_src: jax.Array,
    buckets,
    *,
    alpha: float,
    compute_dtype=jnp.float32,
    gram_impl: str = "auto",
    tb: int | None = None,
    pc: int | None = None,
    ns_chunk: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Accumulate one ring step's bucket contributions into ``(G, g)``.

    The distributed half-sweeps call this once per ring step.
    ``gram_impl="auto"`` resolves the step's :class:`~repro.kernels.autotune.ShapeKey`
    through the autotune cache/heuristic at trace time; a fused decision
    lowers the whole step to **one** ``pallas_call`` (flattened chunk
    layout + in-kernel scatter), while ``"pallas"``/``"xla"`` keep the
    per-bucket loop with ``at[].add`` scatters. ``"pallas_fused"`` forces
    the fused kernel (parity tests / benchmarks).

    When an auto step misses the step-key cache and the heuristic does not
    pick the fused kernel, each bucket re-resolves its **own** bucket-class
    key (``autotune.bucket_key``) instead of inheriting one step-wide
    choice — so a warmed per-bucket cache can mix implementations inside a
    single step (e.g. the big pad class on Pallas, the tail on XLA). An
    exact *step*-key cache hit still pins the whole step, so measured
    ``measure_step`` decisions keep their meaning.

    Args:
        G: ``[cap, K, K]`` f32 running Gram accumulator.
        g: ``[cap, K]`` f32 running linear-term accumulator.
        X_src: ``[Ns, K]`` opposite-side shard for this step.
        buckets: The step's ``Bucket`` tuple.
        alpha: Rating noise precision (scales both terms).
        compute_dtype: Contraction dtype.
        gram_impl: ``"auto" | "pallas_fused" | "pallas" | "xla"``.
        tb / pc / ns_chunk: Explicit tiling overrides (tests/benchmarks).
        interpret: Pallas interpret mode (default: :func:`pallas_interpret`).

    Returns:
        Updated ``(G, g)``.

    Raises:
        ValueError: An explicit Pallas ``gram_impl`` cannot be tiled.
    """
    if not buckets:
        return G, g
    Ns, K = X_src.shape
    cap = G.shape[0]
    if interpret is None:
        interpret = pallas_interpret()
    shapes = [(b.B, b.P) for b in buckets]
    per_bucket_auto = False
    if gram_impl == "auto":
        skey = autotune.step_key(shapes, Ns, K, cap, compute_dtype)
        dec = autotune.get_cache().lookup(skey)
        if dec is None:
            # no measured step entry: take the heuristic only for the
            # fused-vs-not call, and let each bucket resolve its own
            # bucket-class key below (one step may mix impls)
            dec = autotune.heuristic(skey)
            per_bucket_auto = dec.impl != "pallas_fused"
    elif gram_impl == "pallas_fused":
        dec = autotune.Decision("pallas_fused", tb, pc, ns_chunk)
    elif gram_impl in ("pallas", "xla"):
        dec = autotune.Decision(gram_impl, tb, pc, ns_chunk)
    else:
        raise ValueError(
            f"unknown gram_impl {gram_impl!r}; one of auto|pallas_fused|pallas|xla"
        )

    if dec.impl == "pallas_fused":
        B_tot = sum(b for b, _ in shapes)
        P_max = max(p for _, p in shapes)
        dec = _fill_tiling(
            autotune.Decision(dec.impl, tb or dec.tb, pc or dec.pc, ns_chunk or dec.ns_chunk),
            B_tot, P_max, Ns, K, compute_dtype, cap,
        )
        _record("step", (B_tot, P_max, Ns, K), dec)
        nbr, val, item, cnt = flatten_step(buckets, dec.pc, dec.tb)
        X_p = _pad_axis(X_src, 0, dec.ns_chunk) if dec.ns_chunk else X_src
        return bpmf_gram_fused(
            G, g, X_p, nbr, val, item, cnt,
            alpha=alpha, tb=dec.tb, ns_chunk=dec.ns_chunk,
            compute_dtype=compute_dtype, interpret=interpret,
        )

    a = jnp.asarray(alpha, jnp.float32)
    if per_bucket_auto:
        # bucket-class dispatch: bpmf_gram resolves this bucket's own
        # autotune.bucket_key (cache hit or heuristic), so different pad
        # classes of the same step can take different impls
        kw = dict(impl="auto", tb=tb, pc=pc, ns_chunk=ns_chunk)
    else:
        # dispatch per bucket so the decision's (tb, pc, ns_chunk) — from
        # the cache or explicit overrides — reaches the kernel
        kw = dict(impl=dec.impl, tb=tb or dec.tb, pc=pc or dec.pc,
                  ns_chunk=ns_chunk or dec.ns_chunk)

    def add(carry, b):
        G, g = carry
        Gb, gb = bpmf_gram(
            X_src, b.nbr, b.val, b.nnz,
            compute_dtype=compute_dtype, interpret=interpret, **kw,
        )
        G = G.at[b.item_ids].add(a * Gb, mode="drop")
        g = g.at[b.item_ids].add(a * gb, mode="drop")
        return (G, g), None

    for b in buckets:
        # buckets run in row tiles so the [rows, K, K] Gram terms never grow
        # with the bucket; each item occurs in one row, so the scatter-adds
        # commute and the sums are unchanged
        (G, g), _ = jax.lax.scan(add, (G, g), b.row_tiles())
    return G, g


def draw_decision(B: int, K: int, backend: str | None = None) -> autotune.Decision:
    """``auto`` for the posterior draw: the kernel on the TPU when the rank
    fits its VMEM budget and the batch is not empty, XLA otherwise."""
    bt = chol_draw.block_items(K)
    if (backend or jax.default_backend()) == "tpu" and bt is not None and B > 0:
        return autotune.Decision("pallas", tb=bt)
    return autotune.Decision("xla")


def _posterior_draw_xla(prec: jax.Array, lin: jax.Array, z: jax.Array) -> jax.Array:
    """XLA's batched Cholesky and three triangular solves."""
    L = jnp.linalg.cholesky(prec)
    # mean = P^-1 lin via two triangular solves
    y = solve_triangular(L, lin[..., None], lower=True)
    mean = solve_triangular(jnp.swapaxes(L, -1, -2), y, lower=False)[..., 0]
    noise = solve_triangular(jnp.swapaxes(L, -1, -2), z[..., None], lower=False)[..., 0]
    return mean + noise


@jax.named_scope(DRAW_SCOPE)
def posterior_draw(
    G: jax.Array,
    g: jax.Array,
    Lam: jax.Array,
    lam_mu: jax.Array,
    z: jax.Array,
    *,
    impl: str = "auto",
    interpret: bool | None = None,
) -> jax.Array:
    """Draw ``x = P^-1 l + chol(P)^-T z`` per item; returns ``[B, K]``.

    ``P = G + Lam`` (``G [B, K, K]``) and ``l = g + lam_mu`` (``g [B, K]``).
    ``impl`` is ``"auto"`` (:func:`draw_decision`), ``"pallas"`` (the
    lane-batched kernel) or ``"xla"``. Both factor ``P`` as
    ``jnp.linalg.cholesky`` does, symmetrized, so they draw the same
    numbers up to rounding.

    Raises:
        ValueError: ``impl="pallas"`` at a rank whose working set does not
            fit the kernel's VMEM budget.
    """
    B, K = g.shape
    if interpret is None:
        interpret = pallas_interpret()
    if impl == "auto":
        dec = draw_decision(B, K)
    elif impl == "pallas":
        bt = chol_draw.block_items(K)
        if bt is None:
            raise ValueError(f"draw kernel does not fit the VMEM budget at K={K}; use 'xla'")
        dec = autotune.Decision("pallas", tb=bt)
    elif impl == "xla":
        dec = autotune.Decision("xla")
    else:
        raise ValueError(f"unknown impl {impl!r}; one of auto|pallas|xla")
    _record("draw", (B, K), dec)
    prec = G + Lam
    lin = g + lam_mu
    if dec.impl == "xla":
        return _posterior_draw_xla(prec, lin, z)
    sym = (prec + jnp.swapaxes(prec, -1, -2)) / 2  # jnp.linalg.cholesky's symmetrize
    return chol_draw.chol_draw(sym, lin, z, bt=dec.tb, interpret=interpret)
