"""Work counts against hand-worked values; the reference against the engine."""
from __future__ import annotations

from helpers import load, tiny_cell

work = load("benchlib/work.py", "bench_work")


def test_side_flops_by_hand():
    # K=2, 3 items, 4 ratings: Gram 4 x (2*4 + 2*2) = 48; per item
    # K^2 + K^3/3 + 3K^2 + 2K = 4 + 8/3 + 12 + 4 = 68/3, x3 = 68; hyper
    # statistics 2*3*4 = 24 plus Lam mu 2*4 = 8
    assert work.side_flops(3, 4, 2) == 48 + 68 + 32


def test_sweep_flops_by_hand():
    # both sides plus 2K per held-out rating: 5 test ratings at K=2 -> 20
    want = work.side_flops(3, 4, 2) + work.side_flops(2, 4, 2) + 20
    assert work.sweep_flops(3, 2, 4, 5, 2) == want


def test_reference_agrees_with_engine_on_cpu():
    cell = tiny_cell("chembl_k32.train")
    kind = load("kinds/train.py", "bench_kind_train")
    res = kind.run(cell, 2**31 + 11, 0.5, None, 0.0, lambda *a: None)
    assert res["attempted"] >= cell["traffic_data"]["sweeps_per_block"]
    for name, value in res["numbers"].items():
        assert value <= cell["limits"][name], (name, value)
    assert res["numbers"]["draw_gap"] < 1e-4
    assert res["end_to_end"]["ratings_per_s"] > 0
