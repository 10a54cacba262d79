"""The tensor model (BPTF, user x movie x time bin) on the normal path.

At a small size on the CPU: the engine against the plain reference
``bench/reference/bptf.py`` draw by draw, the reduction to the two-sided
model when the time factors are ones and the chain is skipped, the chain
against a per-bin textbook update, the three-side layout, a checkpoint
round trip, and the refusals of the paths that cannot run the model.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.scipy.linalg import solve_triangular

from repro.bpmf import BPMFConfig, BPMFEngine, load_dataset
from repro.core import gibbs, posterior
from repro.core.prediction import PredictionState
from repro.core.types import BPMFConfig as CoreConfig
from repro.core.types import HyperParams
from repro.data.movielens import load_movielens
from repro.data.sparse import RatingsCOO, build_bpmf_data

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
M, N, KT, NNZ, K = 300, 200, 12, 6000, 8
SWEEPS, BURN_IN, KEEP = 2, 1, 2
# Largest draw gap over a leaf's RMS between the program and the reference,
# both float32. They add the same products in another order (the program's
# Gram over padded row tiles, the reference's over its own groups; the
# program's training mean in float32, the reference's in float64), so they
# part by float32 rounding grown through two sweeps: ~1e-6 here. bfloat16
# operands in the Gram, what Precision.DEFAULT computes on the TPU's MXU,
# round each product at 2^-9 and part by ~1e-2.
DRAW_TOL = 1e-4


def _load_reference():
    path = os.path.join(ROOT, "bench", "reference", "bptf.py")
    spec = importlib.util.spec_from_file_location("reference_bptf_for_tests", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tensor_coo(seed: int = 0) -> RatingsCOO:
    return load_dataset("synthetic", num_users=M, num_movies=N, nnz=NNZ, num_times=KT,
                        seed=seed)


def engine_cfg(**kw) -> BPMFConfig:
    base = dict(family="bptf", K=K, num_sweeps=SWEEPS, burn_in=BURN_IN,
                sweeps_per_block=SWEEPS, keep_factor_samples=KEEP)
    return BPMFConfig().replace(**{**base, **kw})


@pytest.fixture(scope="module")
def reference_run():
    ref = _load_reference()
    coo = tensor_coo()
    prob = ref.Problem(coo.rows, coo.cols, coo.times, coo.vals, M, N, KT, 0.1, 0)
    return ref.run(prob, K, 2.0, 2.0, 0, SWEEPS, BURN_IN, KEEP)


def rms_gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def program_gaps(want: dict, **kw) -> dict:
    engine = BPMFEngine(engine_cfg(**kw)).fit(tensor_coo())
    st = engine.state
    U, V = engine.factors()
    got = {"U": U, "V": V, "T": st.T, "mu_T": st.hyper_T.mu, "Lam_T": st.hyper_T.Lam,
           "mu_U": st.hyper_U.mu, "Lam_U": st.hyper_U.Lam}
    gaps = {k: rms_gap(v, want[k]) for k, v in got.items()}
    rmse = np.asarray([[m.rmse_sample, m.rmse_avg] for m in engine.history])
    gaps["rmse"] = float(np.max(np.abs(rmse - want["rmse"]) / want["rmse"]))
    return gaps


def test_program_matches_reference_draw_by_draw(reference_run):
    gaps = program_gaps(reference_run)
    assert max(gaps.values()) < DRAW_TOL, gaps


def test_bf16_gram_fails_the_tolerance(reference_run):
    """The Gram's operands in bfloat16 with float32 sums, as Precision.DEFAULT
    computes on the TPU (on the CPU DEFAULT is float32, so the variant
    states the rounding itself): the comparison catches it."""
    gaps = program_gaps(reference_run, compute_dtype=jnp.bfloat16)
    assert max(gaps[k] for k in ("U", "V", "T")) > DRAW_TOL, gaps


def test_time_factors_of_ones_reduce_to_bpmf():
    """With T pinned at ones and the chain skipped, the users' and movies'
    draws are the two-sided program's, bit for bit: multiplying by 1.0 is
    exact, so the shared Gram sees the same operands."""
    coo = tensor_coo()
    cfg = CoreConfig(K=K)
    tensor = dataclasses.replace(build_bpmf_data(coo), times=None)  # keep nbr2, skip the chain
    plain = build_bpmf_data(coo.without_times())
    key = jax.random.key(3)
    s3 = gibbs.init_state(key, M, N, cfg, KT)
    s2 = gibbs.init_state(key, M, N, cfg)
    assert np.all(np.asarray(s3.T) == 1.0)
    pred = PredictionState.init(int(plain.test.rows.shape[0]))
    for _ in range(2):
        s3, _, m3 = gibbs.gibbs_sweep(key, s3, pred, tensor, cfg)
        s2, _, m2 = gibbs.gibbs_sweep(key, s2, pred, plain, cfg)
        for a, b in ((s3.U, s2.U), (s3.V, s2.V), (s3.hyper_U.Lam, s2.hyper_U.Lam),
                     (s3.hyper_V.mu, s2.hyper_V.mu)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert float(m3.rmse_sample) == float(m2.rmse_sample)


def test_chain_matches_a_per_bin_textbook_update():
    """``update_time_chain`` against the conditional of each bin in turn,
    written out in float64: precision Lam (1 + [k < K_T]) + alpha sum x x^T,
    linear term Lam p_k + [k < K_T] Lam T_{k+1} + alpha sum r x, x = U_i * V_j."""
    coo = tensor_coo()
    data = build_bpmf_data(coo, test_fraction=0.0)
    rng = np.random.default_rng(1)
    U = rng.normal(size=(M, K)).astype(np.float32) * 0.5
    V = rng.normal(size=(N, K)).astype(np.float32) * 0.5
    T = (1.0 + 0.1 * rng.normal(size=(KT, K))).astype(np.float32)
    A = rng.normal(size=(K, K))
    Lam = (A @ A.T / K + np.eye(K)).astype(np.float32)
    mu = rng.normal(size=K).astype(np.float32)
    key, alpha = jax.random.key(7), 2.0
    got = posterior.update_time_chain(key, jnp.asarray(T), jnp.asarray(U), jnp.asarray(V),
                                      data.times, HyperParams(jnp.asarray(mu), jnp.asarray(Lam)),
                                      alpha)
    r, c, t = coo.rows, coo.cols, coo.times
    vals = (coo.vals - np.float32(coo.vals.mean())).astype(np.float64)
    L64, prev, want = Lam.astype(np.float64), mu.astype(np.float64), []
    for k in range(KT):
        sel = t == k
        X = U[r[sel]].astype(np.float64) * V[c[sel]]
        last = k == KT - 1
        P = L64 * (1 if last else 2) + alpha * X.T @ X
        lin = L64 @ prev + (0 if last else L64 @ T[k + 1]) + alpha * X.T @ vals[sel]
        Lc = np.linalg.cholesky(P)
        z = np.asarray(jax.random.normal(jax.random.fold_in(key, k), (K,)), np.float64)
        mean = np.linalg.solve(P, lin)
        prev = mean + solve_triangular(Lc.T, z, lower=False)
        want.append(prev)
    np.testing.assert_allclose(np.asarray(got), np.stack(want), rtol=2e-4, atol=2e-4)


def test_three_sides_hold_every_training_rating():
    """Each side's buckets hold each training rating once, as (item, first
    neighbour, second neighbour, value): users (movie, time), movies (user,
    time), time bins (user, movie)."""
    coo = tensor_coo()
    data = build_bpmf_data(coo, test_fraction=0.0)
    mean = np.float32(coo.vals.mean())
    triples = {"users": (coo.rows, coo.cols, coo.times), "movies": (coo.cols, coo.rows, coo.times),
               "times": (coo.times, coo.rows, coo.cols)}
    for name, side in (("users", data.users), ("movies", data.movies), ("times", data.times)):
        got = []
        for b in side.buckets:
            ids, nbr, nbr2, val, nnz = (np.asarray(x) for x in (b.item_ids, b.nbr, b.nbr2,
                                                                b.val, b.nnz))
            live = np.arange(b.P)[None, :] < nnz[:, None]
            rows = np.repeat(ids, nnz)
            got.append(np.stack([rows, nbr[live], nbr2[live], np.round(val[live] * 1e4)], 1))
        got = np.concatenate(got)
        item, n1, n2 = triples[name]
        want = np.stack([item, n1, n2, np.round((coo.vals - mean) * 1e4)], 1)
        assert sorted(map(tuple, got.tolist())) == sorted(map(tuple, want.tolist())), name
        assert side.layout_stats()["ratings"] == NNZ
    assert data.num_times == KT and data.times.num_items == KT


def test_checkpoint_round_trip_carries_the_time_factors(tmp_path):
    coo = tensor_coo()
    cfg = engine_cfg(checkpoint_dir=str(tmp_path), num_sweeps=4, checkpoint_every=2,
                     sweeps_per_block=1)
    whole = BPMFEngine(cfg).fit(coo)
    resumed = BPMFEngine(cfg).prepare(coo)
    assert resumed.restore(step=2) == 2
    assert resumed.state.T.shape == (KT, K)
    for _ in resumed.sample():
        pass
    for a, b in ((whole.state.T, resumed.state.T), (whole.state.hyper_T.Lam,
                                                    resumed.state.hyper_T.Lam),
                 (whole.state.U, resumed.state.U)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert whole.rmse == resumed.rmse


@pytest.mark.parametrize("backend", ["ring", "ring_async", "allgather", "posterior_merge"])
def test_other_backends_refuse_the_tensor_model(backend):
    engine = BPMFEngine(engine_cfg(name=backend))
    with pytest.raises(ValueError, match="use backend 'sequential'"):
        engine.prepare(tensor_coo())


def test_export_and_serving_refuse_the_tensor_model(tmp_path):
    engine = BPMFEngine(engine_cfg()).fit(tensor_coo())
    with pytest.raises(ValueError, match="cannot be exported or served"):
        engine.export(str(tmp_path / "artifact"))
    with pytest.raises(ValueError, match="cannot be exported or served"):
        engine.predict(np.array([0]), np.array([0]))


def test_tensor_model_needs_time_bins():
    coo = tensor_coo().without_times()
    with pytest.raises(ValueError, match="time bins"):
        BPMFEngine(engine_cfg()).prepare(coo)


def test_time_bins_out_of_range_are_refused():
    coo = tensor_coo()
    with pytest.raises(ValueError, match="time bins must be in"):
        dataclasses.replace(coo, num_times=KT - 1)


def test_movielens_csv_keeps_calendar_months(tmp_path):
    """The README's first and last rating months, Jan 1995 and Mar 2015,
    are 243 calendar months apart, bins 0 and 242."""
    path = tmp_path / "ratings.csv"
    path.write_text("userId,movieId,rating,timestamp\n"
                    "1,10,4.0,789652009\n"     # 1995-01-09
                    "1,20,3.5,789652010\n"
                    "2,10,5.0,1427784002\n"    # 2015-03-31
                    "3,30,1.0,1104537600\n")   # 2005-01-01
    coo = load_movielens(str(path), times=True)
    assert coo.num_times == 243
    assert coo.times.tolist() == [0, 0, 242, 120]
    assert load_movielens(str(path)).times is None


def test_cli_fits_the_tensor_model():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.bpmf", "--model", "bptf", "--times", "6",
         "--dataset", "synthetic", "--users", "80", "--movies", "40", "--nnz", "800",
         "--K", "4", "--sweeps", "3", "--burn-in", "1"],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr
    assert "model=bptf" in proc.stdout and "80 x 40 x 6" in proc.stdout
    assert "final rmse(avg)=" in proc.stdout
