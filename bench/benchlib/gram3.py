"""The tensor model's Gram work per Gibbs sweep, counted from shapes.

Per training rating and side, the three-way Gram forms the neighbour
vector ``x = a * b`` from the rows of the two other sides (``K`` FLOP) and
accumulates ``x x^T`` and ``x r`` (``2K^2 + 2K``): ``2K^2 + 3K`` FLOP. It
reads both rows, both indices and the rating: ``8K + 12`` bytes in float32.
A sweep runs it on three sides, each over every training rating. No padded
slot counts, no extra pass of a high-precision matmul, and no ``G``
written out.

A sweep's FLOP add ``work.side_flops``' per-item and hyper-parameter terms
of the three sides and ``3K`` per test prediction (``<u, v, t>``).
"""
from __future__ import annotations

from benchlib import work

SIDES = 3


def gram_flops(nnz: int, K: int) -> float:
    """FLOP of one sweep's Gram over the three sides."""
    return SIDES * nnz * (2.0 * K * K + 3.0 * K)


def gram_bytes(nnz: int, K: int) -> float:
    """Bytes one sweep's Gram must read over the three sides."""
    return SIDES * nnz * (8.0 * K + 12.0)


def roofline_s(nnz: int, K: int, peaks: dict) -> tuple[float, str]:
    """The least time one sweep's Gram could take on a chip of ``peaks``
    (``peaks.peaks``), and which bound sets it: ``"flops"`` or ``"bytes"``."""
    t_flops = gram_flops(nnz, K) / peaks["bf16_flops"]
    t_bytes = gram_bytes(nnz, K) / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")


def sweep_flops(num_users: int, num_movies: int, num_times: int, num_train: int,
                num_test: int, K: int) -> float:
    """FLOP of one sweep: the three half-sweeps plus the test predictions."""
    items = sum(work.side_flops(n, 0, K) for n in (num_users, num_movies, num_times))
    return gram_flops(num_train, K) + items + 3.0 * K * num_test
