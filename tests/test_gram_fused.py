"""Fused multi-bucket Gram kernel + autotune dispatch tests (DESIGN.md §8).

Bit-parity contract: with single-chunk buckets (``P <= pc``) the fused
kernel's per-item contribution is the *same* f32 dot the reference computes,
scattered as ``x + alpha*partial`` (exact for one contribution per item), so
``assert_array_equal`` holds. Multi-chunk rows (``P > pc``) accumulate chunk
partials in a different order than the single einsum and get tolerances.
"""
from __future__ import annotations

import functools
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.types import Bucket
from repro.kernels import autotune, ops, ref
from repro.kernels.bpmf_gram import bpmf_gram_pallas, vmem_bytes_estimate


def _bucket(rng, Ns, B, P, cap, dead_rows=(), nnz=None):
    """Random bucket with distinct scatter rows in [0, cap) (-1 for dead)."""
    if nnz is None:
        nnz = rng.integers(0, P + 1, B).astype(np.int32)
    nbr = rng.integers(0, Ns, (B, P)).astype(np.int32)
    val = rng.normal(size=(B, P)).astype(np.float32)
    val[np.arange(P)[None, :] >= nnz[:, None]] = 0.0
    item_ids = rng.permutation(cap)[:B].astype(np.int32)
    item_ids[list(dead_rows)] = -1
    return Bucket(
        item_ids=jnp.asarray(item_ids),
        nbr=jnp.asarray(nbr),
        val=jnp.asarray(val),
        nnz=jnp.asarray(nnz),
    )


def _emulate_step(G, g, X, buckets, alpha):
    """NumPy oracle: scatter-add ref.bpmf_gram_ref per bucket into (G, g)."""
    Ge = np.array(G, np.float32).copy()
    ge = np.array(g, np.float32).copy()
    a = np.float32(alpha)
    for b in buckets:
        Gb, gb = ref.bpmf_gram_ref(X, b.nbr, b.val, b.nnz)
        ids = np.asarray(b.item_ids)
        for r in range(b.B):
            if ids[r] >= 0:
                Ge[ids[r]] += a * np.asarray(Gb)[r]
                ge[ids[r]] += a * np.asarray(gb)[r]
    return Ge, ge


def _accs(rng, cap, K):
    G = jnp.asarray(rng.normal(size=(cap, K, K)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(cap, K)), jnp.float32)
    return G, g


def _fused(G, g, X, buckets, alpha=2.0, **kw):
    return ops.bpmf_gram_step(
        G, g, X, tuple(buckets), alpha=alpha, gram_impl="pallas_fused", **kw
    )


# ---------- bit-parity edge shapes (single-chunk: P <= pc) ----------


def test_fused_bit_parity_multibucket_step():
    """Three buckets, one pallas_call, bit-identical to the ref scatter."""
    rng = np.random.default_rng(0)
    Ns, K, cap = 96, 16, 64
    X = jnp.asarray(rng.normal(size=(Ns, K)), jnp.float32)
    buckets = [_bucket(rng, Ns, 16, 8, cap), _bucket(rng, Ns, 9, 32, cap),
               _bucket(rng, Ns, 4, 128, cap)]
    G, g = _accs(rng, cap, K)
    Gf, gf = _fused(G, g, X, buckets)
    Ge, ge = _emulate_step(G, g, X, buckets, 2.0)
    np.testing.assert_array_equal(np.asarray(Gf), Ge)
    np.testing.assert_array_equal(np.asarray(gf), ge)


def test_fused_bit_parity_B_not_multiple_of_tb():
    """B=13 with tb=8: flatten pads with dead chunks; output is untouched."""
    rng = np.random.default_rng(1)
    Ns, K, cap = 64, 8, 24
    X = jnp.asarray(rng.normal(size=(Ns, K)), jnp.float32)
    buckets = [_bucket(rng, Ns, 13, 64, cap)]
    G, g = _accs(rng, cap, K)
    Gf, gf = _fused(G, g, X, buckets, tb=8, pc=128)
    Ge, ge = _emulate_step(G, g, X, buckets, 2.0)
    np.testing.assert_array_equal(np.asarray(Gf), Ge)
    np.testing.assert_array_equal(np.asarray(gf), ge)


def test_fused_bit_parity_all_padding_bucket():
    """A bucket with nnz == 0 everywhere contributes exact zeros."""
    rng = np.random.default_rng(2)
    Ns, K, cap = 32, 8, 16
    X = jnp.asarray(rng.normal(size=(Ns, K)), jnp.float32)
    empty = _bucket(rng, Ns, 8, 16, cap, nnz=np.zeros(8, np.int32))
    live = _bucket(rng, Ns, 8, 16, cap)
    G, g = _accs(rng, cap, K)
    Gf, gf = _fused(G, g, X, [empty, live])
    Ge, ge = _emulate_step(G, g, X, [empty, live], 2.0)
    np.testing.assert_array_equal(np.asarray(Gf), Ge)
    np.testing.assert_array_equal(np.asarray(gf), ge)
    # the empty bucket alone must leave (G, g) bitwise untouched
    G2, g2 = _fused(G, g, X, [empty])
    np.testing.assert_array_equal(np.asarray(G2), np.asarray(G))
    np.testing.assert_array_equal(np.asarray(g2), np.asarray(g))


def test_fused_bit_parity_item_ids_minus_one_dropped():
    rng = np.random.default_rng(3)
    Ns, K, cap = 48, 16, 20
    X = jnp.asarray(rng.normal(size=(Ns, K)), jnp.float32)
    buckets = [_bucket(rng, Ns, 10, 32, cap, dead_rows=(0, 3, 9))]
    G, g = _accs(rng, cap, K)
    Gf, gf = _fused(G, g, X, buckets)
    Ge, ge = _emulate_step(G, g, X, buckets, 2.0)
    np.testing.assert_array_equal(np.asarray(Gf), Ge)
    np.testing.assert_array_equal(np.asarray(gf), ge)


def test_fused_bit_parity_ns_chunked():
    """Streaming the shard in ns_chunk slices is exact: every neighbor hits
    one chunk, all other chunks add exact zeros to the gather accumulator."""
    rng = np.random.default_rng(4)
    Ns, K, cap = 96, 16, 32
    X = jnp.asarray(rng.normal(size=(Ns, K)), jnp.float32)
    buckets = [_bucket(rng, Ns, 8, 64, cap), _bucket(rng, Ns, 8, 16, cap)]
    G, g = _accs(rng, cap, K)
    Gr, gr = _fused(G, g, X, buckets)  # resident shard
    Gc, gc = _fused(G, g, X, buckets, ns_chunk=32)  # 3 slices
    np.testing.assert_array_equal(np.asarray(Gr), np.asarray(Gc))
    np.testing.assert_array_equal(np.asarray(gr), np.asarray(gc))
    Ge, ge = _emulate_step(G, g, X, buckets, 2.0)
    np.testing.assert_array_equal(np.asarray(Gc), Ge)


def test_fused_multichunk_rows_tolerance():
    """P > pc accumulates chunk partials; order differs from one einsum."""
    rng = np.random.default_rng(5)
    Ns, K, cap = 64, 16, 16
    X = jnp.asarray(rng.normal(size=(Ns, K)), jnp.float32)
    buckets = [_bucket(rng, Ns, 8, 300, cap)]
    G, g = _accs(rng, cap, K)
    Gf, gf = _fused(G, g, X, buckets, pc=128)
    Ge, ge = _emulate_step(G, g, X, buckets, 2.0)
    np.testing.assert_allclose(np.asarray(Gf), Ge, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gf), ge, rtol=1e-4, atol=1e-4)


# ---------- per-bucket kernel: Ns streaming + large-P tiling ----------


def test_per_bucket_kernel_ns_chunked_bit_identical():
    rng = np.random.default_rng(6)
    Ns, K, B, P = 96, 16, 8, 64
    X = jnp.asarray(rng.normal(size=(Ns, K)), jnp.float32)
    b = _bucket(rng, Ns, B, P, cap=B)
    G0, g0 = bpmf_gram_pallas(X, b.nbr, b.val, b.nnz, tb=4, pc=64, interpret=True)
    G1, g1 = bpmf_gram_pallas(
        X, b.nbr, b.val, b.nnz, tb=4, pc=64, ns_chunk=32, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(G0), np.asarray(G1))
    np.testing.assert_array_equal(np.asarray(g0), np.asarray(g1))


def test_ops_bpmf_gram_explicit_ns_chunk_matches_ref():
    rng = np.random.default_rng(7)
    Ns, K, B, P = 100, 8, 5, 40  # Ns not a multiple: ops pads the shard
    X = jnp.asarray(rng.normal(size=(Ns, K)), jnp.float32)
    b = _bucket(rng, Ns, B, P, cap=B)
    G0, g0 = ref.bpmf_gram_ref(X, b.nbr, b.val, b.nnz)
    G1, g1 = ops.bpmf_gram(
        X, b.nbr, b.val, b.nnz, impl="pallas", ns_chunk=32
    )
    np.testing.assert_allclose(np.asarray(G0), np.asarray(G1), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g0), np.asarray(g1), rtol=1e-5, atol=1e-5)


def test_pick_tiling_large_P_fits_budget():
    """Satellite fix: the VMEM estimate must reflect the real block shapes.

    Pre-restructure, nbr/val blocks padded to the full P while the estimate
    capped P at 4096, so P > 4096 could select an overflowing tiling. The P
    axis is a grid dimension now — blocks are (tb, pc) — and the chosen
    tiling's estimate must fit the budget for any P.
    """
    for P in (4096, 8192, 32768, 1 << 20):
        tiling = ops.pick_tiling(8, P, 2048, 32)
        assert tiling is not None, P
        tb, pc = tiling
        assert vmem_bytes_estimate(tb, pc, 2048, 32) <= ops._VMEM_BUDGET


def test_per_bucket_kernel_beyond_old_P_cap_matches_ref():
    """P just above the old 4096 estimate cap still runs and agrees."""
    rng = np.random.default_rng(8)
    Ns, K, B, P = 32, 8, 2, 4224
    X = jnp.asarray(rng.normal(size=(Ns, K)), jnp.float32)
    b = _bucket(rng, Ns, B, P, cap=B)
    G0, g0 = ref.bpmf_gram_ref(X, b.nbr, b.val, b.nnz)
    G1, g1 = ops.bpmf_gram(X, b.nbr, b.val, b.nnz, force_pallas=True)
    np.testing.assert_allclose(np.asarray(G0), np.asarray(G1), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(g0), np.asarray(g1), rtol=1e-4, atol=1e-4)


# ---------- autotune: cache, heuristic, dispatch ----------


@pytest.fixture
def tmp_cache(tmp_path):
    cache = autotune.AutotuneCache(str(tmp_path / "gram.json"))
    autotune.set_cache(cache)
    yield cache
    autotune.set_cache(None)


def test_autotune_cache_roundtrip(tmp_cache):
    key = autotune.step_key([(16, 32), (8, 128)], 96, 16, 64)
    dec = autotune.Decision("pallas_fused", 8, 128, 32)
    tmp_cache.record(key, dec, timings_us={"xla": 10.0, "pallas_fused_tb8_pc128": 5.0})
    reloaded = autotune.AutotuneCache(tmp_cache.path)
    assert reloaded.lookup(key) == dec
    raw = json.load(open(tmp_cache.path))
    assert raw["version"] == 1 and key.encode() in raw["entries"]


def test_autotune_decide_prefers_cache_over_heuristic(tmp_cache):
    key = autotune.step_key([(8, 8)], 32, 8, 8)
    assert autotune.decide(key).impl == "xla"  # CPU heuristic: never Pallas
    tmp_cache.record(key, autotune.Decision("pallas_fused", 8, 128, None))
    assert autotune.decide(key) == autotune.Decision("pallas_fused", 8, 128, None)


def test_autotune_heuristic_off_tpu_is_xla():
    for kind in ("bucket", "step"):
        key = autotune.ShapeKey(kind, 64, 128, 256, 32, "float32", "cpu", cap=64)
        assert autotune.heuristic(key) == autotune.Decision("xla")


def test_autotune_heuristic_tpu_decision_tree():
    """On TPU: fused for step keys / per-bucket for bucket keys when the
    shard fits; ns-streaming when it doesn't; xla when the cost model says
    the one-hot gather loses (huge Ns/K ratio)."""
    step = autotune.ShapeKey("step", 64, 128, 512, 32, "float32", "tpu", cap=64)
    d = autotune.heuristic(step)
    assert d.impl == "pallas_fused" and d.tb and d.pc and d.ns_chunk is None
    bucket = autotune.ShapeKey("bucket", 64, 128, 512, 32, "float32", "tpu")
    assert autotune.heuristic(bucket).impl == "pallas"
    big = autotune.ShapeKey("step", 64, 128, 400_000, 128, "float32", "tpu", cap=64)
    d = autotune.heuristic(big)
    assert d.impl in ("pallas_fused", "pallas", "xla")
    if d.impl != "xla":  # streaming decision must carry a chunk size
        assert d.ns_chunk is not None and d.ns_chunk < 400_000
    # a scatter capacity too large for the fused accumulator windows
    # degrades to the per-bucket kernel, not straight to xla
    huge_cap = autotune.ShapeKey("step", 64, 128, 512, 32, "float32", "tpu", cap=8192)
    d = autotune.heuristic(huge_cap)
    assert d.impl == "pallas" and d.tb and d.pc
    huge_ratio = autotune.ShapeKey("bucket", 64, 2048, 1 << 22, 4, "float32", "tpu")
    assert autotune.heuristic(huge_ratio).impl == "xla"


def test_autotune_malformed_cache_ignored(tmp_path):
    path = tmp_path / "gram.json"
    path.write_text("{not json")
    cache = autotune.AutotuneCache(str(path))
    assert cache.lookup(autotune.bucket_key(8, 8, 32, 8)) is None
    path.write_text(json.dumps({"version": 999, "entries": {"x": {"impl": "pallas"}}}))
    assert autotune.AutotuneCache(str(path)).entries() == {}


def _iter_subjaxprs(v):
    from jax.extend.core import ClosedJaxpr, Jaxpr

    if isinstance(v, ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _iter_subjaxprs(x)


def _count_pallas_calls(jaxpr) -> int:
    """pallas_call eqns per invocation path (jit dedup-safe, unlike str())."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            total += 1
        for v in eqn.params.values():
            for sub in _iter_subjaxprs(v):
                total += _count_pallas_calls(sub)
    return total


def test_warm_cache_auto_issues_single_pallas_call_per_step(tmp_cache):
    """Acceptance: gram_impl="auto" + warm cache -> exactly one pallas_call
    per ring step (no per-bucket dispatch), verified on the jaxpr."""
    rng = np.random.default_rng(9)
    Ns, K, cap = 64, 8, 40
    X = jnp.asarray(rng.normal(size=(Ns, K)), jnp.float32)
    buckets = tuple(
        [_bucket(rng, Ns, 16, 8, cap), _bucket(rng, Ns, 8, 32, cap),
         _bucket(rng, Ns, 8, 64, cap)]
    )
    G, g = _accs(rng, cap, K)
    key = autotune.step_key([(b.B, b.P) for b in buckets], Ns, K, cap, jnp.float32)
    tmp_cache.record(key, autotune.Decision("pallas_fused", 8, 128, None))

    def trace(impl):
        fn = functools.partial(
            ops.bpmf_gram_step, alpha=2.0, gram_impl=impl
        )
        closed = jax.make_jaxpr(lambda G, g, X: fn(G, g, X, buckets))(G, g, X)
        return _count_pallas_calls(closed.jaxpr)

    assert trace("auto") == 1
    assert trace("pallas") == len(buckets)
    assert trace("xla") == 0
    # and the warm-cache auto result equals the xla result bitwise here
    Ga, ga = ops.bpmf_gram_step(G, g, X, buckets, alpha=2.0, gram_impl="auto")
    Gx, gx = ops.bpmf_gram_step(G, g, X, buckets, alpha=2.0, gram_impl="xla")
    np.testing.assert_array_equal(np.asarray(Ga), np.asarray(Gx))
    np.testing.assert_array_equal(np.asarray(ga), np.asarray(gx))


def test_workload_keys_engage_in_engine_trace(tmp_cache):
    """Keys from autotune.workload_step_keys are EXACTLY the keys
    ops.bpmf_gram_step builds inside the shard_map trace: warming the cache
    for a workload routes the real distributed sweep through the fused
    kernel (one pallas_call per ring step), with samples unchanged."""
    import functools

    from repro.bpmf import load_dataset
    from repro.core import distributed as dist
    from repro.core.prediction import PredictionState
    from repro.core.types import BPMFConfig as CoreConfig

    K = 6
    coo = load_dataset("synthetic", num_users=40, num_movies=30, nnz=400, seed=0)
    data, _ = dist.build_distributed_data(coo, num_shards=1)
    keys = autotune.workload_step_keys(data, K)
    num_steps = len(keys)  # S=1: one step per side
    for key, _shapes in keys:
        tmp_cache.record(key, autotune.Decision("pallas_fused", 8, 128, None))

    mesh = dist.make_ring_mesh(jax.devices()[:1])
    data = dist.shard_data(data, mesh)
    cfg = CoreConfig(K=K, comm_mode="ring", gram_impl="auto")
    state = dist.init_dist_state(jax.random.key(0), data, cfg, mesh)
    pred = PredictionState.init(int(data.test.rows.shape[0]))

    def sweep(cfg):
        fn = functools.partial(dist.dist_gibbs_sweep, cfg=cfg, mesh=mesh)
        return jax.make_jaxpr(fn)(jax.random.key(1), state, pred, data)

    assert _count_pallas_calls(sweep(cfg).jaxpr) == num_steps
    # cold cache (different dtype key) on CPU: pure XLA sweep
    cold = CoreConfig(K=K, comm_mode="ring", gram_impl="xla")
    assert _count_pallas_calls(sweep(cold).jaxpr) == 0
    # and the fused-dispatched sweep draws the same samples
    s1, p1, _ = dist.dist_gibbs_sweep(jax.random.key(1), state, pred, data, cfg, mesh)
    s2, p2, _ = dist.dist_gibbs_sweep(jax.random.key(1), state, pred, data, cold, mesh)
    np.testing.assert_array_equal(np.asarray(s1.U), np.asarray(s2.U))
    np.testing.assert_array_equal(np.asarray(s1.V), np.asarray(s2.V))


def test_cold_cache_auto_on_cpu_is_xla(tmp_cache):
    """No cache entry + CPU heuristic -> pure XLA step (CI never pays
    interpret-mode Pallas by default)."""
    rng = np.random.default_rng(10)
    Ns, K, cap = 32, 8, 16
    X = jnp.asarray(rng.normal(size=(Ns, K)), jnp.float32)
    buckets = (_bucket(rng, Ns, 8, 16, cap),)
    G, g = _accs(rng, cap, K)
    fn = functools.partial(ops.bpmf_gram_step, alpha=2.0, gram_impl="auto")
    closed = jax.make_jaxpr(lambda G, g, X: fn(G, g, X, buckets))(G, g, X)
    assert _count_pallas_calls(closed.jaxpr) == 0


def test_warm_bucket_cache_mixes_impls_within_step(tmp_cache):
    """Per-bucket-class keys: with no step-key entry, a warmed bucket cache
    routes each pad class independently — here the (16, 8) class through the
    Pallas kernel while the (8, 32) class falls to the CPU heuristic (XLA) —
    so ONE traced step mixes impls (exactly one pallas_call), and the mixed
    step agrees numerically with the pure-XLA step."""
    rng = np.random.default_rng(11)
    Ns, K, cap = 64, 8, 40
    X = jnp.asarray(rng.normal(size=(Ns, K)), jnp.float32)
    buckets = (_bucket(rng, Ns, 16, 8, cap), _bucket(rng, Ns, 8, 32, cap))
    G, g = _accs(rng, cap, K)
    tmp_cache.record(
        autotune.bucket_key(16, 8, Ns, K), autotune.Decision("pallas", 8, 128, None)
    )
    fn = functools.partial(ops.bpmf_gram_step, alpha=2.0, gram_impl="auto")
    closed = jax.make_jaxpr(lambda G, g, X: fn(G, g, X, buckets))(G, g, X)
    assert _count_pallas_calls(closed.jaxpr) == 1
    Gm, gm = ops.bpmf_gram_step(G, g, X, buckets, alpha=2.0, gram_impl="auto")
    Gx, gx = ops.bpmf_gram_step(G, g, X, buckets, alpha=2.0, gram_impl="xla")
    np.testing.assert_allclose(np.asarray(Gm), np.asarray(Gx), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gm), np.asarray(gx), rtol=1e-4, atol=1e-4)
    # an exact *step*-key entry still pins the whole step, overriding the
    # bucket entries — measured measure_step decisions keep their meaning
    skey = autotune.step_key([(b.B, b.P) for b in buckets], Ns, K, cap, jnp.float32)
    tmp_cache.record(skey, autotune.Decision("xla"))
    closed = jax.make_jaxpr(lambda G, g, X: fn(G, g, X, buckets))(G, g, X)
    assert _count_pallas_calls(closed.jaxpr) == 0


@pytest.mark.parametrize(
    "impl,K,cap",
    [("pallas_fused", 32, 4096), ("pallas", 600, 16)],
    ids=["fused-accumulator-windows", "per-bucket-K"],
)
def test_explicit_kernel_that_cannot_tile_raises(impl, K, cap):
    """An explicit Pallas impl whose working set cannot fit VMEM even with
    the shard streamed raises; it never turns into XLA silently."""
    rng = np.random.default_rng(12)
    Ns = 256
    X = jnp.asarray(rng.normal(size=(Ns, K)), jnp.float32)
    buckets = (_bucket(rng, Ns, 8, 8, cap),)
    G, g = jnp.zeros((cap, K, K), jnp.float32), jnp.zeros((cap, K), jnp.float32)
    with pytest.raises(ValueError, match="cannot be tiled"):
        ops.bpmf_gram_step(G, g, X, buckets, alpha=2.0, gram_impl=impl)


@pytest.mark.parametrize("path", ["draw", "ring_step"])
def test_row_tiled_bucket_is_bitwise_untiled(monkeypatch, path):
    """A bucket above one Gram tile runs in several row tiles (``lax.map``
    for the draw, ``lax.scan`` for a ring step's scatter-add), with a ragged
    last tile; the result is bitwise that of the bucket as one tile."""
    from repro.core import posterior, types
    from repro.core.types import HyperParams

    rng = np.random.default_rng(3)
    Ns, K, B, P = 300, 8, 1003, 8
    X = jnp.asarray(rng.normal(size=(Ns, K)), jnp.float32)
    b = _bucket(rng, Ns, B, P, cap=B)
    if path == "draw":
        A = rng.normal(size=(K, K))
        hyper = HyperParams(
            mu=jnp.asarray(rng.normal(size=K), jnp.float32),
            Lam=jnp.asarray(A @ A.T + K * np.eye(K), jnp.float32),
        )
        key = jax.random.key(1)

        def fn(Xs):
            return posterior.update_bucket(key, Xs, X, b, hyper, 2.0)

        args = (jnp.zeros((B, K), jnp.float32),)
    else:

        def fn(G, g):
            return ops.bpmf_gram_step(G, g, X, (b,), alpha=2.0, gram_impl="xla")

        args = _accs(rng, B, K)

    def run():  # a fresh function each time, so nothing reuses a trace
        f = lambda *a: fn(*a)  # noqa: E731
        return str(jax.make_jaxpr(f)(*args)), jax.jit(f)(*args)

    text, whole = run()
    assert re.search(r"\blength=1\b", text)
    monkeypatch.setattr(types, "GRAM_TILE_SLOTS", 250 * P)
    text, tiled = run()
    assert re.search(r"\blength=5\b", text)  # 1003 rows in tiles of 250, the last ragged
    for w, t in zip(jax.tree_util.tree_leaves(whole), jax.tree_util.tree_leaves(tiled)):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(t))


@pytest.mark.parametrize(
    "impl,want", [("xla", ["bucket", "bucket"]), ("pallas_fused", ["step"])]
)
def test_record_gram_decisions_lists_each_traced_dispatch(impl, want):
    """Tracing inside ``record_gram_decisions`` lists one entry per bucket
    dispatch, or one per fused step; outside the block nothing is kept."""
    rng = np.random.default_rng(5)
    Ns, K, cap = 64, 8, 32
    X = jnp.asarray(rng.normal(size=(Ns, K)), jnp.float32)
    buckets = (_bucket(rng, Ns, 8, 8, cap), _bucket(rng, Ns, 4, 32, cap))
    G, g = _accs(rng, cap, K)

    def step(G, g):
        return ops.bpmf_gram_step(G, g, X, buckets, alpha=2.0, gram_impl=impl)

    with ops.record_gram_decisions() as decisions:
        jax.make_jaxpr(step)(G, g)
    assert [kind for kind, _, _ in decisions] == want
    assert {dec.impl for _, _, dec in decisions} == {impl}
    assert all(shape[2:] == (Ns, K) for _, shape, _ in decisions)
    jax.make_jaxpr(lambda G, g: step(G, g))(G, g)
    assert len(decisions) == len(want)
