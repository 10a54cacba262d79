"""The tensor-model cell's pieces: its work counts, its data, the split of
its trace, its metric readers, and runs at a size the CPU holds."""
from __future__ import annotations

import json
import os

import jax
import numpy as np
import pytest

from helpers import ROOT, harness, load, tiny_cell
from benchlib import synth, synth_time

gram3 = load("benchlib/gram3.py", "bench_gram3")
work = load("benchlib/work.py", "bench_work_for_gram3")
layers = load("benchlib/layers.py", "bench_layers_for_bptf")
kind = load("kinds/train_bptf.py", "bench_kind_train_bptf")
control = load("control_bptf.py", "bench_control_bptf")
CELL = "ml20m_bptf_k32.train_bptf"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2**31 + 29
SIZES = {"num_users": 600, "num_movies": 200, "nnz": 12000}


def small_cell(**sizes) -> dict:
    cell = tiny_cell(CELL, **(sizes or SIZES))
    cell["config_data"]["num_times"] = 24
    return cell


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_gram3_counts_by_hand():
    # K=2, 4 ratings: 3 sides x 4 x (2*4 + 3*2) = 168 FLOP, 3 x 4 x (8*2 + 12) = 336 B
    assert gram3.gram_flops(4, 2) == 168
    assert gram3.gram_bytes(4, 2) == 336


def test_gram3_sweep_flops_adds_the_item_terms_and_the_predictions():
    M, N, KT, nnz, test, K = 50, 40, 6, 700, 80, 8
    items = sum(work.side_flops(n, 0, K) for n in (M, N, KT))
    assert gram3.sweep_flops(M, N, KT, nnz, test, K) == gram3.gram_flops(nnz, K) + items + 3 * K * test


def test_gram3_is_bound_by_bytes_at_k32():
    # 268 B per 2,144 FLOP, under the v5e's ~240 FLOP/B
    t, which = gram3.roofline_s(1000, 32, PEAKS)
    assert which == "bytes" and t == gram3.gram_bytes(1000, 32) / 819e9


def test_rates_the_pairs_of_the_matrix_configuration():
    full = harness.load_cell(CELL)["config_data"]
    matrix = harness.load_cell("ml20m_k32.train")["config_data"]
    for k in ("num_users", "num_movies", "nnz", "pattern_seed", "min_user_ratings",
              "popularity_exponent", "activity_sigma", "K", "discretize", "noise_std"):
        assert full[k] == matrix[k], k
    a, b = tiny_cell(CELL)["config_data"], tiny_cell("ml20m_k32.train")["config_data"]
    for x, y in zip(synth.pattern(a), synth.pattern(b)):
        np.testing.assert_array_equal(x, y)


def test_months_come_from_the_pattern_seed_and_values_from_the_seed():
    cfg = small_cell()["config_data"]
    r1, c1, t1, v1 = synth_time.ratings(cfg, 5, lambda *a: None)
    r2, c2, t2, v2 = synth_time.ratings(cfg, 6, lambda *a: None)
    np.testing.assert_array_equal(t1, t2)
    assert not np.array_equal(v1, v2)
    assert t1.min() >= 0 and t1.max() < cfg["num_times"]
    other = synth_time.months(dict(cfg, pattern_seed=cfg["pattern_seed"] + 1), r1)
    assert not np.array_equal(t1, other)
    assert set(np.unique(v1)) <= {1.0, 2.0, 3.0, 4.0, 5.0}


JIT = "jit(_gibbs_sweep_block)/while/body/closed_call"
HLO = f"""
  %fusion.1 = f32[8,33]{{1,0}} fusion(%a), metadata={{op_name="{JIT}/bpmf_gram/dot_general"}}
  %while.2 = f32[4]{{0}} while(%b), metadata={{op_name="{JIT}/time_chain/while"}}
  %custom-call.3 = f32[4,128]{{1,0}} custom-call(%c), metadata={{op_name="{JIT}/time_chain/while/body/posterior_draw/pallas_call"}}
  %fusion.4 = f32[4]{{0}} fusion(%d), metadata={{op_name="{JIT}/hyper_draw/mul"}}
"""
EVENTS = {"/device:TPU:0": [
    ["%fusion.1 = f32[8,33]{1,0} fusion(%a)", 0, 300],
    ["%while.2 = f32[4]{0} while(%b)", 300, 400],
    ["%custom-call.3 = f32[4,128]{1,0} custom-call(%c)", 350, 100],
    ["%fusion.4 = f32[4]{0} fusion(%d)", 800, 100],
]}
HOST = [["python", "bench.window", 0, 1000]]


def test_time_chain_counts_every_op_under_it():
    t = layers.with_scopes({"devices": EVENTS, "host": HOST}, layers.op_names(HLO))
    assert kind.scope_seconds(t, "time_chain") == pytest.approx(400e-9)
    # the draw inside the chain is the draw's layer; the chain is no layer
    split = layers.reduce(t)
    assert split["layers"]["posterior_draw"] == pytest.approx(100e-9)
    assert split["layers"]["bpmf_gram"] == pytest.approx(300e-9)


def test_metric_readers():
    m_roof = load("metrics/gram3_roofline.py", "bench_metric_gram3")
    m_chain = load("metrics/time_chain_ms_per_sweep.py", "bench_metric_chain")
    lay = {"sweeps": 4, "num_train": 10_000, "K": 32, "gram_s": 0.5, "time_chain_s": 0.02}
    ctx = {"layer": lay, "peaks": PEAKS}
    least, _ = gram3.roofline_s(10_000, 32, PEAKS)
    assert m_roof.read(ctx) == pytest.approx(100 * least * 4 / 0.5)
    assert m_chain.read(ctx) == pytest.approx(5.0)
    # a run with no split of its trace reads nothing and does not raise
    bare = {"layer": {"sweeps": 4, "num_train": 10_000, "K": 32}, "peaks": PEAKS}
    assert m_roof.read(bare) is None and m_chain.read(bare) is None


def measure(spec, cell):
    jax.clear_caches()  # the block program is traced anew, with any fault in it
    try:
        return harness.measure(cell, SEED, 0.3, False, spec, jax.devices())
    finally:
        jax.clear_caches()


def test_unbroken_run_is_correct(spec):
    out = measure(spec, small_cell())
    assert out["correct"], out["checks"]
    assert out["metrics"]["ratings_per_s"]["value"] > 0 and out["attempted"] >= 2


def test_time_chain_left_out_is_not_correct(spec, monkeypatch):
    from repro.core import posterior

    monkeypatch.setattr(posterior, "update_time_chain", lambda key, T, *a, **k: T)
    assert not measure(spec, small_cell())["correct"]


@pytest.mark.parametrize("seed", [2**31 + 101, 7])
def test_control_fails_a_limit(seed):
    """The reference one precision step below the configuration's (three
    bfloat16 passes per product), put in the program's place, fails at
    least one of the cell's limits, at a size the CPU holds; on the chip
    ``control_bptf.py`` reads it at full size."""
    cell = small_cell(num_users=2000, num_movies=300, nnz=40000)
    cell["config_data"]["num_times"] = 6
    got = control.readings(cell, seed, lambda *a: None)
    limits = cell["limits"]
    assert any(got[k] > limits[k] for k in limits), (got, limits)
