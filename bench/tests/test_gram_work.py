"""The Gram's work counts against hand-worked values."""
from __future__ import annotations

import pytest

from helpers import load

gram = load("benchlib/gram.py", "bench_gram")
work = load("benchlib/work.py", "bench_work_for_gram")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_gram_flops_by_hand():
    # K=2, 4 ratings: 2 sides x 4 x (2*4 + 2*2) = 96
    assert gram.gram_flops(4, 2) == 96


def test_gram_flops_is_the_gram_term_of_side_flops():
    n, nnz, K = 7, 50, 32
    assert gram.gram_flops(nnz, K) == 2 * (work.side_flops(n, nnz, K) - work.side_flops(n, 0, K))


def test_gram_bytes_by_hand():
    # K=2, 4 ratings: 2 sides x 4 x (4*2 + 8) = 128
    assert gram.gram_bytes(4, 2) == 128


@pytest.mark.parametrize("K, bound", [(32, "bytes"), (4096, "flops")])
def test_roofline_names_its_bound(K, bound):
    # K=32: 136 B per 2112 FLOP, under the v5e's 240 FLOP/B balance -> bytes
    t, which = gram.roofline_s(1000, K, PEAKS)
    assert which == bound
    assert t == max(gram.gram_flops(1000, K) / 197e12, gram.gram_bytes(1000, K) / 819e9)
