"""The Gram's own work per Gibbs sweep, counted from shapes, for its
roofline share.

Per training rating and side, the Gram accumulates ``x x^T`` and ``x r``:
``2K^2 + 2K`` FLOP (the Gram term of ``work.side_flops``), and reads the
neighbour's factor row, its index and the rating: ``4K + 8`` bytes in
float32. No padded slot counts, and no ``G`` written out. A sweep runs the
Gram on both sides, each over every training rating.
"""
from __future__ import annotations


def gram_flops(nnz: int, K: int) -> float:
    """FLOP of one sweep's Gram over both sides."""
    return 2.0 * nnz * (2.0 * K * K + 2.0 * K)


def gram_bytes(nnz: int, K: int) -> float:
    """Bytes one sweep's Gram must read over both sides."""
    return 2.0 * nnz * (4.0 * K + 8.0)


def roofline_s(nnz: int, K: int, peaks: dict) -> tuple[float, str]:
    """The least time one sweep's Gram could take on a chip of ``peaks``
    (``peaks.peaks``), and which bound sets it: ``"flops"`` or ``"bytes"``."""
    t_flops = gram_flops(nnz, K) / peaks["bf16_flops"]
    t_bytes = gram_bytes(nnz, K) / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
