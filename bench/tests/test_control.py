"""The control comes out not correct: the plain reference one precision
step below the configuration (float32 ``highest`` -> ``high``), put in the
program's place, fails at least one of each cell's limits. At a size the
CPU holds; ``bench/control.py`` reads the same on the chip at full size."""
from __future__ import annotations

import pytest

from helpers import harness, load, tiny_cell
from benchlib import synth

control = load("control.py", "bench_control")
SEEDS = [2**31 + 101, 7]


def readings(name: str, seed: int) -> dict:
    cell = tiny_cell(name, num_users=2000, num_movies=500, nnz=20000)
    cfg, traffic = cell["config_data"], cell["traffic_data"]
    ref = harness.load_module(f"{harness.BENCH}/reference/{cfg['model']}.py", "bench_ref")
    if cell["kind"] == "serve":
        (_, got), = control.serve_readings(cell, seed, ["control"], ref)
        return cell["limits"], got
    kind = harness.load_module(f"{harness.BENCH}/kinds/{cell['kind']}.py", "bench_kind")
    rows, cols, vals = synth.ratings(cfg, seed, lambda *a: None)
    prob = ref.Problem(rows, cols, vals, cfg["num_users"], cfg["num_movies"],
                       cfg["test_fraction"], cfg["run_seed"])
    want = ref.run(prob, cfg["K"], cfg["alpha"], cfg["beta0"], cfg["run_seed"],
                   traffic["sweeps_per_block"], traffic["burn_in"],
                   traffic["keep_factor_samples"])
    got = control.stand_in("control", ref, cfg, traffic, rows, cols, vals, want)
    return cell["limits"], kind.compare(got, want)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", ["chembl_k32.train", "ml20m_k32.train", "ml20m_k32.serve",
                                  "chembl_k32.ring4"])
def test_control_fails_a_limit(name, seed):
    limits, got = readings(name, seed)
    assert any(got[k] > limits[k] for k in limits), (got, limits)
