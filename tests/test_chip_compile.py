"""Compile the Gram and draw kernels and the one-chip sweep block for a described v5e.

Nothing runs here. The TPU compiler that ships with jax compiles for a
topology that is described, not attached, and refuses what the chip would
refuse: Pallas block shapes, VMEM overflow, a program larger than HBM. Code
that asks ``jax.default_backend()`` still sees the CPU, so every Gram
implementation is passed explicitly, and the draw's ``auto`` is steered to
its TPU decision by the test.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and a worker that imports this
file must collect the same tests as every other worker.
"""
from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import gibbs
from repro.core.prediction import PredictionState
from repro.core.types import BPMFConfig, Bucket, PosteriorAccum, gram_tile_rows
from repro.kernels import autotune, chol_draw, ops
from repro.kernels.bpmf_gram import bpmf_gram_fused, bpmf_gram_pallas
from repro.utils import compiled_hbm_bytes, round_up

V5E_HBM = int(15.75 * 2**30)  # what the compiler grants one v5e chip
HEADROOM = 2 * 2**30
K = 32
RING_NS = 1448  # opposite shard rows of the four-chip ChEMBL ring
RING_CAP_U = 120_880  # compound shard rows of the same ring
# shards on either side of the widest P chunk's one-hot limit
SMALL_NS = (256, 300)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # keep libtpu's logs off /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no described chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _abstract(tree, one_chip):
    """Shapes of a pytree of arrays, placed on the described chip."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree
    )


def _kernel_args(one_chip, variant, dtype):
    B, P = gram_tile_rows(8), 128  # one row tile of the 8-pad class, P padded to pc
    s = functools.partial(_sds, one_chip)
    if variant == "fused":
        cap = RING_NS  # a scatter capacity whose (G, g) windows fit VMEM
        args = (
            s((cap, K, K), jnp.float32), s((cap, K), jnp.float32),
            s((RING_NS, K), jnp.float32), s((B, P), jnp.int32),
            s((B, P), jnp.float32), s((B,), jnp.int32), s((B,), jnp.int32),
        )
        return bpmf_gram_fused, args, dict(tb=8, alpha=2.0, compute_dtype=dtype)
    ns, kw = RING_NS, dict(tb=8, pc=128, compute_dtype=dtype)
    if variant == "ns_chunk":  # the compound shard streamed through VMEM
        tb, pc, chunk = autotune.chunked_tiling(B, 8, RING_CAP_U, K, dtype)
        ns, kw = round_up(RING_CAP_U, chunk), dict(tb=tb, pc=pc, ns_chunk=chunk, compute_dtype=dtype)
    args = (s((ns, K), jnp.float32), s((B, P), jnp.int32), s((B, P), jnp.float32), s((B,), jnp.int32))
    return bpmf_gram_pallas, args, kw


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("variant", ["pallas", "ns_chunk", "fused"])
def test_gram_kernel_compiles_at_ring_step(one_chip, variant, dtype):
    """Each Gram kernel compiles for the v5e at the four-chip ChEMBL ring
    step (Ns=1448, K=32), tb=8, in f32 and in bf16."""
    fn, args, kw = _kernel_args(one_chip, variant, dtype)
    text = fn.lower(*args, interpret=False, **kw).compile().as_text()
    assert "tpu_custom_call" in text


def _heuristic_keys():
    """TPU keys across the regimes the heuristic distinguishes."""
    keys = []
    for P in (8, 512):
        for Ns in (*SMALL_NS, RING_NS, RING_CAP_U):
            keys.append(autotune.ShapeKey("bucket", 64, P, Ns, K, "float32", "tpu"))
            for cap in (64, RING_CAP_U):
                keys.append(autotune.ShapeKey("step", 64, P, Ns, K, "float32", "tpu", cap))
    return keys


def test_every_tpu_heuristic_decision_compiles(one_chip):
    """Whatever the heuristic returns on the TPU is a tiling Mosaic accepts,
    compiled through the same dispatcher the sweep calls."""
    compiled = set()
    for key in _heuristic_keys():
        dec = autotune.heuristic(key)
        if dec.impl == "xla":
            continue
        assert dec.tb % 8 == 0 and dec.pc % 128 == 0, (key, dec)
        sig = (dec, key.P, key.Ns, key.cap if dec.impl == "pallas_fused" else 0)
        if sig in compiled:
            continue
        compiled.add(sig)
        s = functools.partial(_sds, one_chip)
        bucket = Bucket(
            item_ids=s((key.B,), jnp.int32), nbr=s((key.B, key.P), jnp.int32),
            val=s((key.B, key.P), jnp.float32), nnz=s((key.B,), jnp.int32),
        )
        cap = key.cap or key.B
        step = jax.jit(functools.partial(
            ops.bpmf_gram_step, alpha=2.0, gram_impl=dec.impl, tb=dec.tb,
            pc=dec.pc, ns_chunk=dec.ns_chunk, interpret=False,
        ))
        lowered = step.lower(
            s((cap, K, K), jnp.float32), s((cap, K), jnp.float32),
            s((key.Ns, K), jnp.float32), (bucket,),
        )
        assert "tpu_custom_call" in lowered.compile().as_text(), (key, dec)
    assert compiled, "the heuristic chose XLA for every key: nothing was compiled"


def _chembl_block(one_chip):
    """Abstract arguments of the engine's one-chip sequential block at the
    paper's ChEMBL shape (483,500 x 5,775, 1,023,952 ratings), K=32."""
    from repro.bpmf import load_dataset
    from repro.data.sparse import build_bpmf_data

    coo = load_dataset("chembl")
    data = build_bpmf_data(coo)
    cfg = BPMFConfig(K=K, gram_impl="xla")
    key = jax.eval_shape(lambda: jax.random.key(0))
    state = jax.eval_shape(lambda k: gibbs.init_state(k, coo.num_users, coo.num_movies, cfg), key)
    pred = jax.eval_shape(lambda: PredictionState.init(int(data.test.rows.shape[0])))
    accum = jax.eval_shape(lambda: PosteriorAccum.init(coo.num_users, coo.num_movies, K, 8))
    args = (key, state, pred, accum, data)
    return coo, data, cfg, [_abstract(a, one_chip) for a in args]


@pytest.mark.parametrize(
    "B,K", [(gram_tile_rows(8), K), (RING_CAP_U, K), (1, K), (4096, 100)],
    ids=["chembl_tile", "ring_shard", "one_item", "k100"],
)
def test_draw_kernel_compiles(one_chip, B, K):
    """The lane-batched draw compiles at a ChEMBL row tile, a four-chip ring
    shard, a single item and the candidate K=100, at the block of items
    ``auto`` picks on the TPU."""
    dec = ops.draw_decision(B, K, backend="tpu")
    assert dec.impl == "pallas"
    s = functools.partial(_sds, one_chip)
    lowered = chol_draw.chol_draw.lower(
        s((B, K, K), jnp.float32), s((B, K), jnp.float32), s((B, K), jnp.float32),
        bt=dec.tb, interpret=False,
    )
    assert "tpu_custom_call" in lowered.compile().as_text()


CUSTOM_CALL = re.compile(r"= (\S+) custom-call\(.*custom_call_target=\"(\w+)\"")


def test_sequential_block_fits_one_chip_at_chembl(one_chip, monkeypatch):
    """The ChEMBL K=32 sweep block leaves >= 2 GiB of one v5e's HBM free,
    with every per-item draw on the lane-batched kernel.

    ``auto`` resolves every bucket of this shape to XLA on the TPU (checked
    here on TPU keys), so the block is compiled with that impl explicitly.
    The draw's ``auto`` is given its TPU decision. The only Cholesky left
    is the hyper draw's single K x K one per side.
    """
    coo, data, cfg, args = _chembl_block(one_chip)
    for side, Ns in ((data.users, coo.num_movies), (data.movies, coo.num_users)):
        for b in side.buckets:
            rows = min(b.B, gram_tile_rows(b.P))
            dec = autotune.heuristic(autotune.bucket_key(rows, b.P, Ns, K, backend="tpu"))
            assert dec.impl == "xla", (b.B, b.P, dec)
    monkeypatch.setattr(ops, "draw_decision", functools.partial(ops.draw_decision, backend="tpu"))
    monkeypatch.setattr(ops, "pallas_interpret", lambda: False)
    with ops.record_draw_decisions() as draws:
        lowered = gibbs.gibbs_sweep_block_donated.lower(*args, cfg, 8)
    compiled = lowered.compile()
    assert draws and {dec.impl for _, _, dec in draws} == {"pallas"}
    text = compiled.as_text()
    calls = [(m.group(1), m.group(2), line) for line in text.splitlines()
             if (m := CUSTOM_CALL.search(line))]
    kernels = [line for shape, target, line in calls if target == "tpu_custom_call"]
    assert kernels and all("posterior_draw" in line for line in kernels)
    cholesky = [shape for shape, target, _ in calls if target == "Cholesky"]
    assert all(re.fullmatch(rf"f32\[{K},{K}\]\S*", shape) for shape in cholesky), cholesky
    need = compiled_hbm_bytes(compiled)
    assert need + HEADROOM <= V5E_HBM, f"{need / 2**30:.2f} GiB of {V5E_HBM / 2**30} GiB"
    assert np.isfinite(need) and need > 0


def test_bptf_block_compiles_with_the_chain_on_the_kernel(one_chip, monkeypatch):
    """The tensor model's block compiles for the v5e, every draw on the
    lane-batched kernel: the users', movies' and time bins' tiles, and the
    time chain's one-bin draw inside its scan, under ``time_chain``."""
    from repro.bpmf import load_dataset
    from repro.data.sparse import build_bpmf_data

    M, N, KT = 500, 200, 12
    coo = load_dataset("synthetic", num_users=M, num_movies=N, nnz=5000, num_times=KT)
    data = build_bpmf_data(coo)
    cfg = BPMFConfig(K=K, gram_impl="xla")
    key = jax.eval_shape(lambda: jax.random.key(0))
    state = jax.eval_shape(lambda k: gibbs.init_state(k, M, N, cfg, KT), key)
    pred = jax.eval_shape(lambda: PredictionState.init(int(data.test.rows.shape[0])))
    accum = jax.eval_shape(lambda: PosteriorAccum.init(M, N, K, 8))
    args = [_abstract(a, one_chip) for a in (key, state, pred, accum, data)]
    monkeypatch.setattr(ops, "draw_decision", functools.partial(ops.draw_decision, backend="tpu"))
    monkeypatch.setattr(ops, "pallas_interpret", lambda: False)
    with ops.record_draw_decisions() as draws:
        lowered = gibbs.gibbs_sweep_block_donated.lower(*args, cfg, 2)
    assert {dec.impl for _, _, dec in draws} == {"pallas"}
    assert (1, K) in [shape for _, shape, _ in draws]
    kernels = [line for line in lowered.compile().as_text().splitlines()
               if (m := CUSTOM_CALL.search(line)) and m.group(2) == "tpu_custom_call"]
    assert any("/time_chain/" in line for line in kernels)
