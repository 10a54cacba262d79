"""The split of a trace by the program's named layers, by hand and on a
trace recorded on a TPU v5e.

The hand-built trace has one chip whose ``while`` op holds a Gram fusion
and a Cholesky call, an op that starts inside the ``while`` and ends after
it, and host spans of the run loop over the idle time; its ops are named
as a v5e names them, by their instruction's HLO text. The recorded trace
(``data/trace_v5e_chembl.json.gz``) is ``layers.trim`` of a traced
``chembl_k32.train`` window: a few ops of each layer and one block
boundary, with the ``op_name`` of each op's instruction in the compiled
program.
"""
from __future__ import annotations

import os
import sys

import pytest

from helpers import BENCH, ROOT, load

layers = load("benchlib/layers.py", "bench_layers")
trace = load("benchlib/trace.py", "bench_trace_for_layers")
DATA = os.path.join(BENCH, "tests", "data")

JIT = "jit(_gibbs_sweep_block)/while/body/closed_call"
# a compiled program's HLO text, as ``compiled.as_text()`` prints it
HLO = f"""
%fused_computation.2 (p0: f32[8,33]) -> f32[33,33] {{
  %p0 = f32[8,33]{{1,0}} parameter(0)
  ROOT %dot.9 = f32[33,33]{{1,0}} dot(%p0, %p0), metadata={{op_name="{JIT}/bpmf_gram/dot_general"}}
}}
ENTRY %main.4 (a: f32[8,33]) -> f32[33,33] {{
  %while.1 = (s32[], f32[8,33]{{1,0}}) while(%tuple.0), condition=%c, body=%b, metadata={{op_name="jit(_gibbs_sweep_block)/while"}}
  %fusion.2 = f32[33,33]{{1,0}} fusion(%a), kind=kOutput, calls=%fused_computation.2, metadata={{op_name="{JIT}/while/body/closed_call/bpmf_gram/bpmf_gram/bpi,bpj->bij/dot_general"}}
  %custom-call.3 = f32[32,32]{{1,0}} custom-call(%x), custom_call_target="Cholesky", metadata={{op_name="{JIT}/while/body/closed_call/posterior_draw/cholesky"}}
  ROOT %copy.4 = f32[8,33]{{1,0}} copy(%y), metadata={{op_name="{JIT}/sweep_predict/hyper_draw/copy"}}
  %tuple.5 = (f32[8,33]{{1,0}}) tuple(%copy.4)
}}
"""
EVENTS = {"/device:TPU:0": [
    ["%while.1 = (s32[], f32[8,33]{1,0}) while(%tuple.0), condition=%c, body=%b", 0, 1000],
    ["%fusion.2 = f32[33,33]{1,0} fusion(%a), kind=kOutput, calls=%fused_computation.2", 100, 300],
    ['%custom-call.3 = f32[32,32]{1,0} custom-call(%x), custom_call_target="Cholesky"', 400, 500],
    ["%copy.4 = f32[8,33]{1,0} copy(%y)", 950, 100],
]}
HOST = [
    ["python", "bench.window", 0, 1200],
    ["python", "bpmf.dispatch", 1000, 100],
    ["python", "bpmf.drain", 1100, 50],
    ["python", "bpmf.prepare", 2000, 10],
]
HAND = layers.with_scopes({"devices": EVENTS, "host": HOST}, layers.op_names(HLO))


def test_scope_paths_by_instruction_name():
    names = layers.op_names(HLO)
    assert set(names) == {"dot.9", "while.1", "fusion.2", "custom-call.3", "copy.4"}
    assert [layers.layer_of(p) for p in HAND["scopes"]["/device:TPU:0"]] == [
        None, "bpmf_gram", "posterior_draw", "hyper_draw"]


@pytest.fixture(scope="module")
def hand():
    return layers.reduce(HAND)


def test_layers_by_innermost_op_and_innermost_name(hand):
    # fusion 300 ns of Gram, custom-call 500 ns of draw; copy [950, 1050)
    # is innermost over the while's end and is hyper_draw, the innermost
    # name of its path; the while keeps 1000 - 300 - 500 - 50 = 150 ns
    assert hand["layers"] == pytest.approx({
        "bpmf_gram": 300e-9, "posterior_draw": 500e-9, "hyper_draw": 100e-9,
        "sweep_predict": 0.0, "ring_step": 0.0})
    assert hand["unattributed"] == pytest.approx(150e-9)
    assert hand["unattributed_ops"] == [[EVENTS["/device:TPU:0"][0][0], pytest.approx(150e-9)]]
    assert hand["no_scope_path"] == 0


def test_layers_and_unattributed_add_up_to_busy(hand):
    busy = trace.reduce(HAND)["busy_s"]
    assert sum(hand["layers"].values()) + hand["unattributed"] == pytest.approx(busy)
    assert busy == pytest.approx(1050e-9)


def test_idle_time_by_run_loop_span(hand):
    # idle [1050, 1200): dispatch covers [1050, 1100), drain [1100, 1150)
    assert hand["idle_spans"] == pytest.approx(
        {"bpmf.dispatch": 50e-9, "bpmf.drain": 50e-9, "none": 50e-9})


def test_innermost_span_of_nested_spans():
    # a drain inside a dispatch takes its own part of the idle time
    t = dict(HAND, host=HOST + [["python", "bpmf.drain", 1060, 10]])
    assert layers.reduce(t)["idle_spans"]["bpmf.drain"] == pytest.approx(60e-9)


def test_op_missing_from_the_program_has_no_scope_path():
    t = layers.with_scopes({"devices": EVENTS, "host": HOST}, {})
    split = layers.reduce(t)
    assert split["unattributed"] == split["no_scope_path"] == pytest.approx(1050e-9)


def test_layer_names_are_the_programs():
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core.types import SWEEP_SCOPES

    assert layers.SCOPES == SWEEP_SCOPES


@pytest.fixture(scope="module")
def v5e():
    return trace.read(os.path.join(DATA, "trace_v5e_chembl.json.gz"))


def test_recorded_v5e_trace_names_ops_by_instruction(v5e):
    (dev,) = v5e["devices"]
    assert trace.DEVICE.match(dev)  # the chip's plane, as trace.load finds it
    ops = v5e["devices"][dev]
    # each op's name is its instruction's HLO text, found in the program
    assert all(n.startswith("%") and " = " in n for n, _, _ in ops)
    paths = [v5e["op_names"][layers.instruction(n)] for n, _, _ in ops]
    found = {layers.layer_of(p) for p in paths}
    assert {"bpmf_gram", "posterior_draw", "hyper_draw", "sweep_predict"} <= found


def test_recorded_v5e_trace_splits_by_layer(v5e):
    t = layers.with_scopes(v5e, v5e["op_names"])
    split = layers.reduce(t)
    busy = trace.reduce(t)["busy_s"]
    assert sum(split["layers"].values()) + split["unattributed"] == pytest.approx(busy)
    assert split["layers"]["bpmf_gram"] > 0 and split["layers"]["posterior_draw"] > 0
    # the block boundary: the run loop's spans are on the host plane's clock
    names = {h[1] for h in v5e["host"]}
    assert {"bench.window", "bpmf.dispatch", "bpmf.drain"} <= names
