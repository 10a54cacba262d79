"""What a profiler trace of the sampler can be split by.

The compiled sweep block carries the layer names (``SWEEP_SCOPES``) in its
ops' ``op_name`` metadata, the run loop writes its host spans into the
profiler's trace, and ``BPMFEngine.layout_stats()`` counts the Gram's
slots as they are laid out.
"""
from __future__ import annotations

import collections
import functools
import glob
import json
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from conftest import run_with_devices
from repro.bpmf import BPMFConfig, BPMFEngine, load_dataset
from repro.core import types
from repro.core.distributed import build_distributed_data, ring_layout_stats
from repro.core.types import SWEEP_SCOPES
from repro.data.sparse import RatingsCOO

OP_NAME = re.compile(r'op_name="([^"]*)"')


def innermost(path: str) -> str | None:
    return next((p for p in reversed(path.split("/")) if p in SWEEP_SCOPES), None)


def layers_by_primitive(hlo: str) -> dict[str, set]:
    """The innermost layer name of every op, by the primitive that made it
    (the last part of its ``op_name``)."""
    out = collections.defaultdict(set)
    for path in OP_NAME.findall(hlo):
        out[path.rsplit("/", 1)[-1]].add(innermost(path))
    return out


def small_engine(**kw) -> BPMFEngine:
    coo = load_dataset("synthetic", num_users=60, num_movies=40, nnz=600)
    cfg = BPMFConfig().replace(K=4, num_sweeps=4, sweeps_per_block=2, **kw)
    return BPMFEngine(cfg).prepare(coo)


def test_sequential_block_names_its_layers():
    hlo = small_engine(name="sequential").lower_block().compile().as_text()
    by_prim = layers_by_primitive(hlo)
    found = set().union(*by_prim.values())
    assert set(SWEEP_SCOPES) - {"ring_step"} <= found
    assert "ring_step" not in found
    # the neighbour gather and the Gram contraction are the Gram's
    assert "bpmf_gram" in by_prim["gather"]
    assert "bpmf_gram" in by_prim["dot_general"]
    # the per-item Cholesky factor and solves are the draw's (the
    # hyper-parameter draw has its own, under hyper_draw)
    for prim in ("cholesky", "triangular_solve"):
        assert by_prim[prim] <= {"posterior_draw", "hyper_draw"}, (prim, by_prim[prim])
        assert "posterior_draw" in by_prim[prim]
    # every gather and contraction of the sweep sits under some layer
    for prim in ("gather", "dot_general"):
        assert None not in by_prim[prim], prim


def test_bptf_block_names_the_chain_and_the_gram_of_three_sides():
    """The tensor model's block: its time chain runs under ``time_chain``,
    with its draws under ``posterior_draw`` inside it, and the Gram of
    each of the three sides (gathers of U, V and T rows) under
    ``bpmf_gram``; ``time_chain`` is not one of the sweep's layers, so a
    trace's split by layer stays as it was."""
    coo = load_dataset("synthetic", num_users=60, num_movies=40, nnz=600, num_times=6)
    cfg = BPMFConfig().replace(family="bptf", K=4, num_sweeps=4, sweeps_per_block=2)
    hlo = BPMFEngine(cfg).prepare(coo).lower_block().compile().as_text()
    chain = [p for p in OP_NAME.findall(hlo) if "time_chain" in p.split("/")]
    assert chain and {innermost(p) for p in chain} >= {"posterior_draw"}
    # a gather's operand is a parameter of its fused computation, whose
    # shape the computation's header states
    shapes = dict(re.findall(r"(param_[\d.]+): (f32\[[\d,]+\])", hlo))
    gathered = {shapes.get(m.group(1).lstrip("%")) for line in hlo.splitlines()
                if "/bpmf_gram/" in line and (m := re.search(r" gather\((%?[\w.]+),", line))}
    assert {"f32[60,4]", "f32[40,4]", "f32[6,4]"} <= gathered, gathered
    assert SWEEP_SCOPES == ("bpmf_gram", "posterior_draw", "hyper_draw", "sweep_predict",
                            "ring_step")
    assert types.TIME_CHAIN_SCOPE not in SWEEP_SCOPES


def pallas_call_paths(jaxpr) -> list[str]:
    """The full name-stack path of every ``pallas_call`` in a closed jaxpr,
    as it becomes the kernel custom-call's ``op_name``."""
    out = []

    def walk(jx, prefix):
        for eqn in jx.eqns:
            path = "/".join(p for p in (prefix, str(eqn.source_info.name_stack)) if p)
            if eqn.primitive.name == "pallas_call":
                out.append(f"{path}/pallas_call")
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, path)

    walk(jaxpr.jaxpr, "")
    return out


def test_draw_kernel_is_named_posterior_draw(monkeypatch):
    """On the TPU the per-item draw is one Pallas kernel; its custom-call
    carries ``posterior_draw`` as its innermost layer, so a trace's split
    gives its time to the draw and not to ``unattributed``."""
    from repro.core import gibbs
    from repro.core.prediction import PredictionState
    from repro.data.sparse import build_bpmf_data
    from repro.kernels import ops

    monkeypatch.setattr(ops, "draw_decision", functools.partial(ops.draw_decision, backend="tpu"))
    coo = load_dataset("synthetic", num_users=60, num_movies=40, nnz=600)
    data = build_bpmf_data(coo)
    cfg = types.BPMFConfig(K=4)
    state = gibbs.init_state(jax.random.key(0), coo.num_users, coo.num_movies, cfg)
    pred = PredictionState.init(int(data.test.rows.shape[0]))
    with ops.record_draw_decisions() as draws:
        jaxpr = jax.make_jaxpr(gibbs.gibbs_sweep, static_argnums=4)(
            jax.random.key(1), state, pred, data, cfg)
    assert draws and {dec.impl for _, _, dec in draws} == {"pallas"}
    paths = pallas_call_paths(jaxpr)
    assert len(paths) == len(draws)
    assert {innermost(p) for p in paths} == {"posterior_draw"}, paths


RING_CODE = """
import json, re, collections
from repro.bpmf import BPMFConfig, BPMFEngine, load_dataset
coo = load_dataset("synthetic", num_users=80, num_movies=48, nnz=900)
cfg = BPMFConfig().replace(name="ring_async", pipeline_depth=2, K=4, num_sweeps=2,
                           sweeps_per_block=2)
engine = BPMFEngine(cfg).prepare(coo)
print(json.dumps({"hlo": engine.lower_block().compile().as_text(),
                  "layout": engine.layout_stats()}))
"""


@pytest.mark.multidevice
def test_ring_async_block_names_its_layers():
    out = json.loads(run_with_devices(RING_CODE, num_devices=8).strip().splitlines()[-1])
    by_prim = layers_by_primitive(out["hlo"])
    assert set(SWEEP_SCOPES) <= set().union(*by_prim.values())
    assert by_prim["ppermute"] == {"ring_step"}
    assert "bpmf_gram" in by_prim["gather"]
    assert "posterior_draw" in by_prim["cholesky"]
    for side in ("users", "movies"):
        assert out["layout"][side]["ratings"] <= out["layout"][side]["gram_slots"]


def test_run_loop_writes_host_spans(tmp_path):
    engine = small_engine(name="sequential")
    with jax.profiler.trace(str(tmp_path)):
        for _ in engine.sample():
            pass
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    names = collections.Counter(
        e.name for plane in ProfileData.from_file(path).planes if plane.name == "/host:CPU"
        for line in plane.lines for e in line.events)
    assert names["bpmf.dispatch"] == 2 and names["bpmf.drain"] == 2  # two blocks of 2 sweeps


def test_prepare_writes_a_host_span(tmp_path):
    coo = load_dataset("synthetic", num_users=60, num_movies=40, nnz=600)
    engine = BPMFEngine(BPMFConfig().replace(K=4))
    with jax.profiler.trace(str(tmp_path)):
        engine.prepare(coo)
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    names = [e.name for plane in ProfileData.from_file(path).planes if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events]
    assert names.count("bpmf.prepare") == 1


# users 0..4 rate 2, 4, 6, 0 and 11 movies; movies then hold 4, 4, 3, 3, 2,
# 2, 1, 1, 1, 1, 1 and 0 ratings
HAND = {0: range(2), 1: range(4), 2: range(6), 3: range(0), 4: range(11)}


def hand_coo() -> RatingsCOO:
    rows = np.array([u for u, ms in HAND.items() for _ in ms], np.int32)
    cols = np.array([m for ms in HAND.values() for m in ms], np.int32)
    vals = np.linspace(1.0, 5.0, rows.size).astype(np.float32)
    return RatingsCOO(rows, cols, vals, 5, 12)


@pytest.mark.parametrize("tile_slots, user_slots", [(1 << 16, 36), (8, 40)])
def test_layout_stats_by_hand(monkeypatch, tile_slots, user_slots):
    """Pads (4, 8): users 0, 1, 3 at P=4, user 2 at P=8, user 4 above the
    last pad at P=16: 12 + 8 + 16 = 36 slots. In tiles of 8 slots the P=4
    class runs 2 tiles of 2 rows, one row dead: 16 + 8 + 16 = 40. Every
    movie sits at P=4: 12 x 4 = 48 slots, in either tiling."""
    monkeypatch.setattr(types, "GRAM_TILE_SLOTS", tile_slots)
    cfg = BPMFConfig().replace(K=4, bucket_pads=(4, 8), test_fraction=0.0)
    stats = BPMFEngine(cfg).prepare(hand_coo()).layout_stats()
    assert stats == {"users": {"ratings": 23, "gram_slots": user_slots},
                     "movies": {"ratings": 23, "gram_slots": 48}}


def test_ring_layout_stats_by_hand():
    """User i rates movies i, i+1, i+2 (mod 6): every count is 3, so each of
    the two ring steps holds one P=4 bucket of 8 rows per shard (rows are
    rounded up to 8), on both sides: 2 steps x 2 shards x 8 x 4 = 128."""
    rows = np.repeat(np.arange(6, dtype=np.int32), 3)
    cols = ((rows + np.tile(np.arange(3), 6)) % 6).astype(np.int32)
    coo = RatingsCOO(rows, cols, np.ones(18, np.float32), 6, 6)
    data, plan = build_distributed_data(coo, num_shards=2, pads=(4, 8), test_fraction=0.0)
    for side in (data.users, data.movies):
        assert [len(step) for step in side.steps] == [1, 1]
        assert ring_layout_stats(side, 2, plan.total_nnz) == {"ratings": 18, "gram_slots": 128}
