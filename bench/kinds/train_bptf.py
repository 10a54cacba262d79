"""Tensor-model training cells: BPTF Gibbs sweeps through ``BPMFEngine.sample()``.

As ``kinds/train.py``, whose engine configuration it takes (loaded by
path), with the model family of the configuration (``"model": "bptf"``)
and timestamped ratings from ``benchlib/synth_time.py``. Set-up builds the
engine first, so a program that lacks the model refuses before any data
is made, then generates the ratings and drives the first block through
``sample()``; the window is the same: blocks of ``sweeps_per_block``
sweeps, from the first dispatch after set-up to the fetch of the metrics
of the first block that ends past ``--seconds``.

After the window the engine is freed and the plain reference
(``bench/reference/bptf.py``) runs the same first block from the seed;
the draws (``T`` among them), the hyper-parameters of the three sides,
the on-device posterior summaries and the per-sweep RMSEs of the
program's first block are compared with it.

With a trace, the block's compiled HLO text is taken before the engine is
freed, and once the profiler has stopped the trace is split by the
program's named layers (``benchlib/layers.py``): ``res["layer"]`` then
carries the device seconds under ``bpmf_gram`` (``gram_s``) and under
``time_chain``, every op whose scope path holds it (``time_chain_s``),
which ``metrics/gram3_roofline.py`` and
``metrics/time_chain_ms_per_sweep.py`` read.
"""
from __future__ import annotations

import gc
import importlib.util
import os
import time

import numpy as np

from benchlib import gram3, layers, synth_time, trace as tr
from benchlib.compare import gap, load_reference, rel_gap, rms_gap

TIME_CHAIN = "time_chain"


def _load_train():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "train.py")
    spec = importlib.util.spec_from_file_location("kind_train_for_bptf", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


train = _load_train()
DRAWS = train.DRAWS + ("T",)
HYPERS = train.HYPERS + ("mu_T", "Lam_T")


def engine_config(cfg: dict, traffic: dict):
    return train.engine_config(cfg, traffic).replace(family=cfg["model"])


def snapshot(engine) -> dict:
    """Host copy of what the first block produced, in original item order."""
    U, V = engine.factors()
    st = engine.state
    post = engine.backend.posterior_export(engine._accum)
    out = {"U": U, "V": V, "T": np.asarray(st.T),
           "rmse": np.asarray([[m.rmse_sample, m.rmse_avg] for m in engine.history])}
    for side in ("U", "V", "T"):
        hyper = getattr(st, f"hyper_{side}")
        out[f"mu_{side}"], out[f"Lam_{side}"] = np.asarray(hyper.mu), np.asarray(hyper.Lam)
    for k in ("U_mean", "V_mean", "U_samples", "V_samples"):
        out[k] = np.asarray(post[k], np.float32)
    return out


def compare(got: dict, want: dict) -> dict:
    """``kinds/train.py``'s numbers, over the tensor model's leaves."""
    def worst(keys, by=gap):
        vals = [by(got.get(k), want[k]) for k in keys if k in want]
        return max(vals) if vals else float("inf")

    rm = (rel_gap(got["rmse"], want["rmse"]) if got["rmse"].shape == want["rmse"].shape
          else float("inf"))
    return {"draw_gap": worst(DRAWS), "draw_rms_gap": worst(DRAWS, rms_gap),
            "hyper_gap": worst(HYPERS), "rmse_gap": rm}


def scope_seconds(trace: dict, name: str) -> float:
    """Device seconds of the window in which an op whose scope path holds
    ``name`` runs, mean over devices (``trace`` from ``layers.with_scopes``)."""
    w0, w1 = tr.window(trace)
    total = 0
    for dev, events in trace["devices"].items():
        spans = [[max(s, w0), min(s + d, w1)]
                 for (_, s, d), path in zip(events, trace["scopes"][dev])
                 if name in path.split("/") and s < w1 and s + d > w0]
        total += tr._length(tr._union(spans))
    return total / 1e9 / max(len(trace["devices"]), 1)


def split_trace(trace_dir: str, hlo: str) -> dict:
    """The traced window's device seconds by layer, and under the chain."""
    events = layers.with_scopes(tr.load(trace_dir), layers.op_names(hlo))
    split = layers.reduce(events)
    return {"gram_s": split["layers"]["bpmf_gram"],
            "time_chain_s": scope_seconds(events, TIME_CHAIN),
            "layers_s": split["layers"], "unattributed_s": split["unattributed"],
            "idle_spans_s": split["idle_spans"]}


def run(cell: dict, seed: int, seconds: float, trace_dir: str | None, t0: float, log) -> dict:
    import jax

    from repro.bpmf import BPMFEngine
    from repro.data.sparse import RatingsCOO
    from repro.kernels import ops

    cfg, traffic = cell["config_data"], cell["traffic_data"]
    spb = traffic["sweeps_per_block"]
    engine = BPMFEngine(engine_config(cfg, traffic))
    rows, cols, times, vals = synth_time.ratings(cfg, seed, log)
    coo = RatingsCOO(rows, cols, vals, cfg["num_users"], cfg["num_movies"], times,
                     cfg["num_times"])
    t = time.perf_counter()
    engine.prepare(coo)
    log(f"setup: host layout and device placement {time.perf_counter() - t:.3f} s; "
        f"layout {engine.layout_stats()}")

    t = time.perf_counter()
    it = engine.sample()
    with ops.record_gram_decisions() as decisions:
        for m in it:
            if int(m.sweep) == spb:
                break
    log(f"setup: first block (compile or cache load, and {spb} sweeps) "
        f"{time.perf_counter() - t:.3f} s; {len(decisions)} Gram dispatches, "
        f"impls {sorted({dec.impl for _, _, dec in decisions})}")
    got = snapshot(engine)

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *a, **k: compiles.append(name) if "compile" in name else None)
    profiler = jax.profiler.trace(trace_dir) if trace_dir else None
    if profiler:
        profiler.__enter__()
    t_start = time.perf_counter()
    setup_s = t_start - t0
    compiles.clear()
    with jax.profiler.TraceAnnotation("bench.window"):
        last = spb
        for m in it:
            sweep = int(m.sweep)
            if sweep % spb == 0:
                last = sweep
                t_end = time.perf_counter()
                if t_end - t_start >= seconds:
                    break
    in_window = len(compiles)
    if profiler:
        profiler.__exit__(None, None, None)
    window_s = t_end - t_start
    sweeps = last - spb
    hist = engine.history[spb:last]
    failed = sum(1 for m in hist if not np.isfinite(m.rmse_sample))
    log(f"window: {sweeps} sweeps in {window_s:.6f} s ({sweeps // spb} blocks); "
        f"compile events in window: {in_window}; last rmse(avg) {hist[-1].rmse_avg:.6f}")
    if in_window:
        log("window: WARNING: something compiled inside the measured window")

    used = engine.state.U.sharding.device_set
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
    hlo = engine.lower_block().compile().as_text() if trace_dir else None
    del it, engine
    gc.collect()

    layer = {}
    if trace_dir:
        layer = split_trace(trace_dir, hlo)
        log(f"layers: {layer}")

    ref = load_reference(cfg)
    t = time.perf_counter()
    prob = ref.Problem(rows, cols, times, vals, cfg["num_users"], cfg["num_movies"],
                       cfg["num_times"], cfg["test_fraction"], cfg["run_seed"])
    want = ref.run(prob, cfg["K"], cfg["alpha"], cfg["beta0"], cfg["run_seed"], spb,
                   traffic["burn_in"], traffic["keep_factor_samples"])
    log(f"reference: {spb} sweeps {time.perf_counter() - t:.3f} s")
    numbers = compare(got, want)

    flops = gram3.sweep_flops(cfg["num_users"], cfg["num_movies"], cfg["num_times"],
                              prob.num_train, prob.test[0].size, cfg["K"])
    layer.update({"sweeps": sweeps, "window_s": window_s, "sweep_flops": flops,
                  "num_train": prob.num_train, "K": cfg["K"]})
    return {
        "attempted": sweeps, "failed": failed,
        "end_to_end": {"ratings_per_s": prob.num_train * sweeps / window_s, "setup_s": setup_s},
        "numbers": numbers,
        "memory_peak_bytes": int(peak),
        "devices_used": len(used),
        "layer": layer,
    }
