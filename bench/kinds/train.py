"""Training cells: Gibbs sweeps through ``BPMFEngine.sample()``.

Set-up generates the ratings, builds the engine on the cell's backend and
drives its first block through ``sample()``: that block compiles the block
program and its state is the one the reference follows. The same iterator
then runs the window: blocks of ``sweeps_per_block`` sweeps, from the
first dispatch after set-up to the fetch of the metrics of the first block
that ends past ``--seconds``. Every sweep in between counts.

After the window the engine is freed and the plain reference
(``bench/reference/<model>.py``) runs the same first block from the seed;
the draws, hyper-parameters, on-device posterior summaries and per-sweep
RMSEs of the program's first block are compared with it.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchlib import synth, work
from benchlib.compare import gap, load_reference, rel_gap, rms_gap


def engine_config(cfg: dict, traffic: dict):
    from repro.bpmf import BPMFConfig

    extra = {k: traffic[k] for k in ("num_shards", "pipeline_depth") if k in traffic}
    return BPMFConfig().replace(
        name=traffic["backend"], K=cfg["K"], alpha=cfg["alpha"], beta0=cfg["beta0"],
        num_sweeps=1 << 30, burn_in=traffic["burn_in"],
        sweeps_per_block=traffic["sweeps_per_block"],
        keep_factor_samples=traffic["keep_factor_samples"],
        seed=cfg["run_seed"], test_fraction=cfg["test_fraction"],
        gram_impl=cfg["gram_impl"], **extra,
    )


def snapshot(engine) -> dict:
    """Host copy of what the first block produced, in original item order."""
    U, V = engine.factors()
    st = engine.state
    _, post = engine._artifact_payload()  # what export() writes, from the device summary
    out = {"U": U, "V": V,
           "mu_U": np.asarray(st.hyper_U.mu), "Lam_U": np.asarray(st.hyper_U.Lam),
           "mu_V": np.asarray(st.hyper_V.mu), "Lam_V": np.asarray(st.hyper_V.Lam),
           "rmse": np.asarray([[m.rmse_sample, m.rmse_avg] for m in engine.history])}
    for k in ("U_mean", "V_mean", "U_samples", "V_samples"):
        out[k] = np.asarray(post[k], np.float32)
    return out


DRAWS = ("U", "V", "U_mean", "V_mean", "U_samples", "V_samples")
HYPERS = ("mu_U", "Lam_U", "mu_V", "Lam_V")


def compare(got: dict, want: dict) -> dict:
    """The numbers ``correct`` is decided on, worst leaf first.

    ``draw_gap``: largest |program - reference| of any factor entry, over
    the root-mean-square of that leaf of the reference; ``draw_rms_gap``:
    the root-mean-square of the difference over the same, a number that
    does not grow with the count of entries as a largest gap does;
    ``hyper_gap``: the largest gap over the hyper-parameters; ``rmse_gap``:
    largest relative gap of a per-sweep test RMSE (sample and posterior
    mean).
    """
    def worst(keys, by=gap):
        vals = [by(got.get(k), want[k]) for k in keys if k in want]
        return max(vals) if vals else float("inf")

    rm = (rel_gap(got["rmse"], want["rmse"]) if got["rmse"].shape == want["rmse"].shape
          else float("inf"))
    return {"draw_gap": worst(DRAWS), "draw_rms_gap": worst(DRAWS, rms_gap),
            "hyper_gap": worst(HYPERS), "rmse_gap": rm}


def run(cell: dict, seed: int, seconds: float, trace_dir: str | None, t0: float, log) -> dict:
    import jax

    from repro.bpmf import BPMFEngine
    from repro.data.sparse import RatingsCOO
    from repro.kernels import ops

    cfg, traffic = cell["config_data"], cell["traffic_data"]
    spb = traffic["sweeps_per_block"]
    rows, cols, vals = synth.ratings(cfg, seed, log)
    coo = RatingsCOO(rows, cols, vals, cfg["num_users"], cfg["num_movies"])
    engine = BPMFEngine(engine_config(cfg, traffic))
    t = time.perf_counter()
    engine.prepare(coo)
    log(f"setup: host layout and device placement {time.perf_counter() - t:.3f} s")

    t = time.perf_counter()
    it = engine.sample()
    with ops.record_gram_decisions() as decisions:
        for m in it:
            if int(m.sweep) == spb:
                break
    log(f"setup: first block (compile or cache load, and {spb} sweeps) "
        f"{time.perf_counter() - t:.3f} s")
    seen = {}
    for kind, shape, dec in decisions:
        key = (kind, shape, dec.impl)
        seen[key] = seen.get(key, 0) + 1
    for (kind, (B, P, Ns, K), impl), n in sorted(seen.items()):
        log(f"gram: {kind} B={B} P={P} Ns={Ns} K={K} -> {impl} (x{n})")
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
        f"compile events in window: {in_window}; last rmse(avg) {hist[-1].rmse_avg:.6f}; "
        f"engine host_blocked_s {engine.host_blocked_s:.6f}, "
        f"host_metric_bytes {engine.host_metric_bytes} (whole run)")
    if in_window:
        log("window: WARNING: something compiled inside the measured window")

    used = engine.state.U.sharding.device_set
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
    del it, engine
    gc.collect()

    ref = load_reference(cfg)
    t = time.perf_counter()
    prob = ref.Problem(rows, cols, vals, cfg["num_users"], cfg["num_movies"],
                       cfg["test_fraction"], cfg["run_seed"])
    want = ref.run(prob, cfg["K"], cfg["alpha"], cfg["beta0"], cfg["run_seed"], spb,
                   traffic["burn_in"], traffic["keep_factor_samples"])
    log(f"reference: {spb} sweeps {time.perf_counter() - t:.3f} s")
    numbers = compare(got, want)

    flops = work.sweep_flops(cfg["num_users"], cfg["num_movies"], prob.num_train,
                             prob.test[0].size, cfg["K"])
    return {
        "attempted": sweeps, "failed": failed,
        "end_to_end": {"ratings_per_s": prob.num_train * sweeps / window_s, "setup_s": setup_s},
        "numbers": numbers,
        "memory_peak_bytes": int(peak),
        "devices_used": len(used),
        "layer": {"sweeps": sweeps, "window_s": window_s, "sweep_flops": flops,
                  "num_train": prob.num_train},
    }
