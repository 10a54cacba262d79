"""The lane-batched posterior-draw kernel (interpret mode) and its dispatch.

The kernel is checked against a float64 NumPy Cholesky and solves on
precisions built as the sampler builds them, for its bitwise independence
of an item's lane, block and batch, and for what ``auto`` picks. Batches
stay at a few hundred items: interpret mode runs every vector op on the
host.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import posterior
from repro.core.types import HyperParams
from repro.kernels import chol_draw, ops

ALPHA = 2.0
COUNTS = (0, 1, 2, 3, "K+5")  # ratings per item; the last one n >= K
PER_COUNT = 40


def _draw_inputs(rng, K: int, counts, per: int):
    """``(G, g, Lam, lam_mu, z)`` for ``per`` items of each rating count:
    ``G = alpha * sum_j v_j v_j^T`` over the item's ``n`` neighbours, as
    the Gram builds it, and a Wishart-like prior precision."""
    A = rng.normal(size=(K, K + 4))
    Lam = (A @ A.T / (K + 4) + 0.5 * np.eye(K)).astype(np.float32)
    Lam = 0.5 * (Lam + Lam.T)
    G, g = [], []
    for c in counts:
        n = K + 5 if c == "K+5" else c
        for _ in range(per):
            X = rng.normal(size=(n, K)).astype(np.float32) * 0.5
            r = rng.normal(size=n).astype(np.float32)
            G.append(ALPHA * X.T @ X)
            g.append(ALPHA * X.T @ r)
    B = len(G)
    lam_mu = (Lam @ rng.normal(size=K) * 0.3).astype(np.float32)
    z = rng.normal(size=(B, K)).astype(np.float32)
    return (np.stack(G).astype(np.float32), np.stack(g).astype(np.float32), Lam, lam_mu, z)


def _f64_draw(G, g, Lam, lam_mu, z):
    prec = G.astype(np.float64) + Lam
    L = np.linalg.cholesky(0.5 * (prec + np.swapaxes(prec, 1, 2)))
    y = np.linalg.solve(L, (g.astype(np.float64) + lam_mu)[..., None])
    return np.linalg.solve(np.swapaxes(L, 1, 2), y + z.astype(np.float64)[..., None])[..., 0]


def _kernel(G, g, Lam, lam_mu, z, bt: int | None = None):
    """The kernel path of ``ops.posterior_draw`` in interpret mode; ``bt``
    overrides the block of items to force several grid steps."""
    if bt is None:
        return ops.posterior_draw(G, g, Lam, lam_mu, z, impl="pallas", interpret=True)
    prec = jnp.asarray(G) + Lam
    sym = (prec + jnp.swapaxes(prec, -1, -2)) / 2
    return chol_draw.chol_draw(sym, jnp.asarray(g) + lam_mu, jnp.asarray(z), bt=bt, interpret=True)


@functools.lru_cache(maxsize=None)
def _against_f64(K: int):
    rng = np.random.default_rng(K)
    args = _draw_inputs(rng, K, COUNTS, PER_COUNT)
    got = np.asarray(_kernel(*map(jnp.asarray, args)), np.float64)
    return got, _f64_draw(*args)


@pytest.mark.parametrize("count", COUNTS, ids=lambda c: f"n{c}")
@pytest.mark.parametrize("K", [8, 32])
def test_kernel_matches_float64_draw(K, count):
    """Each item's draw is within ~1e-5 of a float64 Cholesky and solves,
    for items with 0-3 ratings (the ChEMBL regime) and with n >= K."""
    got, want = _against_f64(K)
    i = COUNTS.index(count)
    rows = slice(i * PER_COUNT, (i + 1) * PER_COUNT)
    np.testing.assert_allclose(got[rows], want[rows], rtol=1e-5, atol=1e-5 * np.abs(want).max())


def test_kernel_matches_xla_draw():
    """The kernel and XLA's Cholesky and solves draw the same numbers up to
    f32 rounding, through the same op."""
    args = tuple(map(jnp.asarray, _draw_inputs(np.random.default_rng(3), 8, (1, 2, 12), 30)))
    xla = np.asarray(ops.posterior_draw(*args, impl="xla"))
    np.testing.assert_allclose(np.asarray(_kernel(*args)), xla, rtol=1e-5, atol=1e-5)


@functools.lru_cache(maxsize=None)
def _ragged_case():
    """300 items in blocks of 256 lanes: two grid steps, the last one part
    padding."""
    args = tuple(map(jnp.asarray, _draw_inputs(np.random.default_rng(11), 8, (0, 1, 2, 3, 13), 60)))
    return args, np.asarray(_kernel(*args, bt=256))


@pytest.mark.parametrize("n", [1, 129, 255, 256, 299])
def test_kernel_bitwise_under_slicing(n):
    """``f(G)[:n] == f(G[:n])`` bitwise, across block and padding edges."""
    (G, g, Lam, lam_mu, z), whole = _ragged_case()
    part = np.asarray(_kernel(G[:n], g[:n], Lam, lam_mu, z[:n], bt=256))
    np.testing.assert_array_equal(part, whole[:n])


def test_kernel_bitwise_under_permutation():
    """An item's draw does not depend on its lane or block."""
    (G, g, Lam, lam_mu, z), whole = _ragged_case()
    perm = np.random.default_rng(0).permutation(G.shape[0])
    got = np.asarray(_kernel(G[perm], g[perm], Lam, lam_mu, z[perm], bt=256))
    np.testing.assert_array_equal(got, whole[perm])


def test_dead_row_draws_from_the_prior():
    """An item with no ratings (G = 0, so P = Lam) gets a finite draw, the
    prior's."""
    rng = np.random.default_rng(5)
    K, B = 8, 130
    G, g, Lam, lam_mu, z = _draw_inputs(rng, K, (2,), B)
    G[::3], g[::3] = 0.0, 0.0
    got = np.asarray(_kernel(*map(jnp.asarray, (G, g, Lam, lam_mu, z))))
    assert np.isfinite(got).all()
    want = _f64_draw(G[::3], g[::3], Lam, lam_mu, z[::3])
    np.testing.assert_allclose(got[::3], want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "B,K,backend,want",
    [
        (8192, 32, "cpu", "xla"),
        (8192, 32, "tpu", "pallas"),
        (120_880, 32, "tpu", "pallas"),  # a four-chip ring shard
        (4096, 100, "tpu", "pallas"),
        (8192, 512, "tpu", "xla"),  # too wide for the VMEM budget
        (0, 32, "tpu", "xla"),
    ],
)
def test_auto_picks_kernel_only_on_tpu_when_it_fits(B, K, backend, want):
    dec = ops.draw_decision(B, K, backend=backend)
    assert dec.impl == want
    if want == "pallas":
        assert dec.tb % chol_draw.LANES == 0
        assert chol_draw.vmem_bytes(K, dec.tb) <= chol_draw.VMEM_BUDGET


def test_sampler_draw_records_its_decision():
    """``sample_from_terms`` dispatches through ``posterior_draw``; off the
    TPU ``auto`` stays on XLA, and each trace records one decision."""
    K, B = 4, 16
    hyper = HyperParams(mu=jnp.zeros(K), Lam=jnp.eye(K))

    def draw(G, g):
        return posterior.sample_from_terms(jax.random.key(0), jnp.arange(B), G, g, hyper)

    with ops.record_draw_decisions() as decisions:
        jax.make_jaxpr(draw)(jnp.zeros((B, K, K)), jnp.zeros((B, K)))
    assert [(kind, shape, dec.impl) for kind, shape, dec in decisions] == [
        ("draw", (B, K), "xla")
    ]


def test_explicit_kernel_refuses_a_rank_it_cannot_hold():
    K = 512
    with pytest.raises(ValueError, match="VMEM"):
        ops.posterior_draw(
            jnp.zeros((1, K, K)), jnp.zeros((1, K)), jnp.eye(K), jnp.zeros(K),
            jnp.zeros((1, K)), impl="pallas",
        )
