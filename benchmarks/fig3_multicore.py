"""Paper Figure 3: single-node updates/second vs parallel width.

Paper variants -> this repo:
  * TBB (work stealing)      -> bucketed batched sweep, LPT-balanced buckets
  * OpenMP (static split)    -> bucketed sweep with naive contiguous buckets
  * GraphLab (generic graph) -> unbucketed vmap over max-padded items

"Parallel width" on one CPU host device maps to the batch dimension the MXU
(or CPU vector unit) sweeps per launch; we report updates/s for the full
half-sweep on a ChEMBL-shaped synthetic at several scales.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import save_result
from repro.core import posterior
from repro.core.types import BPMFConfig, HyperParams
from repro.data.sparse import build_bpmf_data
from repro.data.synthetic import SyntheticSpec, synthetic_ratings
from repro.utils import timeit


def run(smoke: bool = False) -> dict:
    spec = SyntheticSpec(
        num_users=2_000 if smoke else 20_000,
        num_movies=400 if smoke else 1_200,
        nnz=20_000 if smoke else 400_000,
        discretize=False,
    )
    coo, _ = synthetic_ratings(spec)
    K = 16 if smoke else 32
    cfg = BPMFConfig(K=K)
    iters = 3 if smoke else 8

    key = jax.random.key(0)
    hyper = HyperParams.init(K)

    results = {}
    for mode, pads in (
        ("bucketed_lpt", (8, 32, 128, 512, 2048)),   # TBB-like: size-classed buckets
        ("bucketed_coarse", (2048,)),                # OpenMP-like: one static class
    ):
        data = build_bpmf_data(coo, pads=pads, test_fraction=0.1, seed=0)
        U = jax.random.normal(key, (coo.num_users, K), jnp.float32)
        V = jax.random.normal(key, (coo.num_movies, K), jnp.float32)
        half = jax.jit(
            lambda V, U, d: posterior.update_side(key, V, U, d.movies, hyper, cfg.alpha)
        )
        t = timeit(half, V, U, data, iters=iters)
        results[mode] = {
            "seconds_per_halfsweep": t,
            "updates_per_s": coo.num_movies / t,
            "pads": list(pads),
        }

    # GraphLab-like: every item padded to the global max nnz (one giant launch,
    # no size classes) — the generic-framework overhead the paper measures
    import numpy as _np
    from repro.data.sparse import csr_from_coo as _csr
    indptr, _, _ = _csr(coo.cols, coo.rows, coo.vals, coo.num_movies)
    max_nnz = int((indptr[1:] - indptr[:-1]).max())
    maxpad = 1 << int(_np.ceil(_np.log2(max(max_nnz, 8))))
    data1 = build_bpmf_data(coo, pads=(maxpad,), test_fraction=0.1, seed=0)
    U = jax.random.normal(key, (coo.num_users, K), jnp.float32)
    V = jax.random.normal(key, (coo.num_movies, K), jnp.float32)
    half = jax.jit(
        lambda V, U, d: posterior.update_side(key, V, U, d.movies, hyper, cfg.alpha)
    )
    t = timeit(half, V, U, data1, iters=max(2, iters // 2))
    results["maxpad_graphlab_like"] = {
        "seconds_per_halfsweep": t,
        "updates_per_s": coo.num_movies / t,
    }

    results["speedup_bucketed_vs_maxpad"] = (
        results["maxpad_graphlab_like"]["seconds_per_halfsweep"]
        / results["bucketed_lpt"]["seconds_per_halfsweep"]
    )
    out = {"spec": vars(spec) | {"K": K}, "results": results}
    save_result("fig3_multicore", out)
    return out


if __name__ == "__main__":
    import sys

    r = run(smoke="--smoke" in sys.argv)
    for k, v in r["results"].items():
        print(k, v)
