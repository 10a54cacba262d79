"""A run with the timed path broken underneath comes out not correct.

Each test skips only the harness's look for a chip: it drives the rest of
a run (``bench/run.py``'s ``measure``) at a size the CPU holds, with one
fault planted in the program, and checks that ``correct`` is false; the
unbroken run beside them checks that it is true.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from helpers import BENCH, ROOT, harness, tiny_cell

SEED = 2**31 + 17


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def measure(spec, name="chembl_k32.train"):
    jax.clear_caches()  # the block program is traced anew, with the fault in it
    try:
        return harness.measure(tiny_cell(name), SEED, 0.3, False, spec, jax.devices())
    finally:
        jax.clear_caches()


def test_unbroken_run_is_correct(spec):
    out = measure(spec)
    assert out["correct"], out["checks"]


def test_state_returned_unchanged(spec, monkeypatch):
    from repro.core import posterior

    monkeypatch.setattr(posterior, "update_side", lambda key, X, *a, **k: X)
    assert not measure(spec)["correct"]


def test_half_of_each_items_ratings_left_out(spec, monkeypatch):
    from repro.kernels import ops

    orig = ops.bpmf_gram

    def half(X, nbr, val, nnz, **kw):
        G, g = orig(X, nbr, val, nnz // 2, **kw)
        return 2.0 * G, 2.0 * g  # the mean taken over the rest

    monkeypatch.setattr(ops, "bpmf_gram", half)
    assert not measure(spec)["correct"]


def test_one_draw_altered_where_it_is_produced(spec, monkeypatch):
    from repro.core import posterior

    orig = posterior.sample_from_terms

    def altered(*a, **k):
        return orig(*a, **k).at[0, 0].add(jnp.float32(1.0))

    monkeypatch.setattr(posterior, "sample_from_terms", altered)
    assert not measure(spec)["correct"]


RING = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, {tests!r})
    import jax
    from helpers import harness, tiny_cell
    spec = json.load(open(os.path.join({root!r}, "BENCHMARK.json")))
    if {broken}:
        jax.lax.ppermute = lambda x, axis_name, perm: x  # every shard keeps its own
    out = harness.measure(tiny_cell("chembl_k32.ring4"), {seed}, 0.3, False, spec,
                          jax.devices())
    print(json.dumps(out["correct"]))
""")


@pytest.mark.parametrize("broken", [False, True], ids=["unbroken", "exchange_left_out"])
def test_ring_exchange_left_out(broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = RING.format(tests=os.path.join(BENCH, "tests"), root=ROOT, broken=broken,
                       seed=SEED)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    assert json.loads(p.stdout.strip().splitlines()[-1]) is (not broken)
