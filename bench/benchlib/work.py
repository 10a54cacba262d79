"""The algorithm's work per Gibbs sweep, counted from shapes.

Only the sampler's own arithmetic counts: no bucket or tile padding, no
extra passes of a high-precision matmul, nothing an implementation adds.
Per side of ``n`` items and ``nnz`` training ratings, at rank ``K``:

* Gram terms, per training rating: ``x x^T`` and ``x r`` accumulated,
  ``2K^2 + 2K`` FLOP;
* per item: the precision ``G + Lam`` (``K^2``), its Cholesky factor
  (``K^3 / 3``), three triangular solves (``3 K^2``: two for the mean, one
  for the noise), ``l = g + Lam mu`` and ``mean + noise`` (``2K``);
* the hyper-parameter statistics ``sum x x^T`` over the side's rows
  (``2 n K^2``), plus ``Lam mu`` once (``2K^2``);

and per sweep the test predictions, ``2K`` per held-out rating. The
``O(K^3)`` work of the Wishart draw itself is per side, not per item, and
is left out (under 1e-4 of the total at K=32).
"""
from __future__ import annotations


def side_flops(n_items: int, nnz: int, K: int) -> float:
    gram = nnz * (2.0 * K * K + 2.0 * K)
    per_item = n_items * (K * K + K ** 3 / 3.0 + 3.0 * K * K + 2.0 * K)
    hyper = 2.0 * n_items * K * K + 2.0 * K * K
    return gram + per_item + hyper


def sweep_flops(num_users: int, num_movies: int, num_train: int, num_test: int, K: int) -> float:
    """FLOP of one sweep: both half-sweeps plus the test predictions."""
    return (side_flops(num_users, num_train, K) + side_flops(num_movies, num_train, K)
            + 2.0 * K * num_test)

