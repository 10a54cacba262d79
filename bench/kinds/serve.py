"""Serving cells: open-loop HTTP traffic against ``BPMFServer``.

Set-up makes a posterior artifact of the configuration's shape from the
seed (posterior means and ``kept_samples`` factor samples, drawn on the
device in one jitted call), saves it, starts ``BPMFServer`` on a loopback
port and compiles every batch program the traffic can reach: each
power-of-two query pad class up to twice ``max_batch``, with and without
the predictive std, and top-k.

The window is the arrival schedule: ``rate_per_s`` requests a second for
``--seconds``, Poisson arrivals from the mix's ``arrival_seed`` (the same
for every seed), request contents from ``--seed``. A child process that
never imports JAX sends them (``benchlib/loadgen.py``) and times each from
when it was due. After the window the server stops and a sample of the
answers, drawn from the seed, is compared with the plain reference
computed from the artifact's arrays.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

from benchlib.compare import abs_gap, load_reference

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_artifact(cfg: dict, traffic: dict, seed: int, directory: str) -> tuple:
    """Random posterior summary from the seed, saved as a serving artifact."""
    import jax

    from repro.serve.artifact import ArtifactMeta, save_artifact

    M, N, K, S = cfg["num_users"], cfg["num_movies"], cfg["K"], traffic["kept_samples"]
    sd = traffic["factor_std"]

    @jax.jit
    def draw(key):
        ku, kv, kus, kvs = jax.random.split(key, 4)
        U = sd * jax.random.normal(ku, (M, K))
        V = sd * jax.random.normal(kv, (N, K))
        # kept samples scatter about the mean, as posterior draws do
        Us = U + 0.3 * sd * jax.random.normal(kus, (S, M, K))
        Vs = V + 0.3 * sd * jax.random.normal(kvs, (S, N, K))
        return U, V, Us, Vs

    key = jax.random.key(int(np.random.SeedSequence(seed).generate_state(1)[0]))
    arrays = dict(zip(("U_mean", "V_mean", "U_samples", "V_samples"),
                      (np.asarray(a, np.float32) for a in draw(key))))
    lo, hi = traffic["rating_range"]
    meta = ArtifactMeta(num_users=M, num_movies=N, K=K, mean_rating=traffic["mean_rating"],
                        min_rating=lo, max_rating=hi, num_mean_samples=S,
                        num_kept_samples=S, backend="sequential", num_sweeps_done=S,
                        seed=0)
    save_artifact(directory, meta, arrays)
    return meta, arrays


def warm(predictor, max_batch: int, k: int) -> int:
    """Compile every batch program the traffic can reach; returns the count."""
    n, pad = 0, 1
    while pad <= 2 * max_batch:
        ids = np.zeros(pad, np.int32)
        predictor.predict(ids, ids)
        predictor.predict(ids, ids, return_std=True)
        predictor.top_k(ids, k)
        n, pad = n + 3, pad * 2
    return n


def plan(cfg: dict, traffic: dict, cell: dict, seed: int, seconds: float) -> dict:
    """Arrival times (fixed by the mix) and request bodies (from the seed)."""
    rate = cell["rate_per_s"]
    arr = np.random.default_rng(traffic["arrival_seed"])
    gaps = arr.exponential(1.0 / rate, int(rate * seconds * 1.5) + 16)
    due = np.cumsum(gaps)
    due = due[due < seconds]
    rng = np.random.default_rng([seed, 2])
    mix = traffic["mix"]
    share = np.cumsum([m["share"] for m in mix])
    pick = np.searchsorted(share / share[-1], arr.random(due.size), side="right")
    reqs = []
    for j in pick:
        m = mix[j]
        if m["op"] == "predict":
            reqs.append({"rows": rng.integers(0, cfg["num_users"], m["pairs"]).tolist(),
                         "cols": rng.integers(0, cfg["num_movies"], m["pairs"]).tolist(),
                         "std": m["std"]})
        else:
            reqs.append({"user": int(rng.integers(0, cfg["num_users"])), "k": m["k"]})
    return {"due": due.tolist(), "requests": reqs, "connections": traffic["connections"]}


def check(meta, arrays: dict, reqs: list, answers: list, idx: np.ndarray,
          ref, precision: str = "highest") -> dict:
    """Widest gaps of the sampled answers from the reference.

    ``pred_gap`` / ``std_gap``: largest |served - reference| of a
    prediction or a predictive std (rating units); ``topk_gap``: largest
    |served score - reference score of the served movie|, and by how much
    a served movie's reference score lies below the reference's k-th best;
    ``missing``: sampled requests with no answer.
    """
    U, V, Us, Vs = (arrays[k] for k in ("U_mean", "V_mean", "U_samples", "V_samples"))
    mean, lo, hi = meta.mean_rating, meta.min_rating, meta.max_rating
    out = {"pred_gap": 0.0, "std_gap": 0.0, "topk_gap": 0.0, "missing": 0}
    for i in idx:
        req, ans = reqs[i], answers[i]
        if ans is None:
            out["missing"] += 1
            continue
        if "rows" in req:
            r, c = np.asarray(req["rows"]), np.asarray(req["cols"])
            want = ref.serve_predict(U, V, r, c, mean, lo, hi, precision)
            got = np.asarray(ans.get("predictions", []), np.float64)
            out["pred_gap"] = max(out["pred_gap"], abs_gap(got, want))
            if req.get("std"):
                want = ref.serve_std(Us, Vs, r, c, mean, lo, hi, precision)
                got = np.asarray(ans.get("std", []), np.float64)
                out["std_gap"] = max(out["std_gap"], abs_gap(got, want))
        else:
            scores = ref.serve_scores(U, V, req["user"], mean, lo, hi, precision)
            kth = np.sort(scores)[-req["k"]]
            ids = np.asarray(ans.get("items", []), np.int64)
            got = np.asarray(ans.get("scores", []), np.float64)
            if ids.size != req["k"] or np.any(ids < 0) or np.any(ids >= scores.size):
                out["topk_gap"] = float("inf")
                continue
            out["topk_gap"] = max(out["topk_gap"], abs_gap(got, scores[ids]),
                                  float(np.max(kth - scores[ids])))
    return out


def start_server(cfg: dict, traffic: dict, seed: int, work: str, log):
    """Artifact from the seed, ``BPMFServer`` on a loopback port, warmed."""
    from repro.serve import BPMFServer

    t = time.perf_counter()
    meta, arrays = make_artifact(cfg, traffic, seed, os.path.join(work, "artifact"))
    log(f"setup: artifact {cfg['num_users']} x {cfg['num_movies']} K={cfg['K']} with "
        f"{traffic['kept_samples']} samples, made and saved in {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    server = BPMFServer(os.path.join(work, "artifact"), port=0, watch=False,
                        deadline_ms=traffic["deadline_ms"], max_batch=traffic["max_batch"])
    try:
        host, port = server.start()
        n = warm(server.handle.get(), traffic["max_batch"],
                 max(m.get("k", 1) for m in traffic["mix"]))
    except BaseException:
        server.shutdown()
        raise
    log(f"setup: server on {host}:{port}, {n} batch programs warmed in "
        f"{time.perf_counter() - t:.3f} s")
    return server, meta, arrays


def send(p: dict, work: str, timeout: float) -> dict:
    """Run the load generator on plan ``p`` in a child without JAX; its result."""
    plan_path, result_path = os.path.join(work, "plan.json"), os.path.join(work, "result.json")
    with open(plan_path, "w") as f:
        json.dump(p, f)
    env = {k: v for k, v in os.environ.items() if not k.startswith(("JAX", "XLA", "TPU"))}
    child = subprocess.Popen([sys.executable, os.path.join(BENCH, "benchlib", "loadgen.py"),
                              plan_path, result_path], env=env)
    try:
        rc = child.wait(timeout=timeout)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if rc != 0:
        raise RuntimeError(f"load generator exited with {rc}")
    with open(result_path) as f:
        return json.load(f)


def percentile(lat: list, q: float) -> float:
    """``q``-quantile of the latencies, a failed request (None) counting as
    slower than every success."""
    x = np.asarray([np.inf if v is None else v for v in lat], np.float64)
    return float(np.quantile(x, q, method="higher"))


def run(cell: dict, seed: int, seconds: float, trace_dir: str | None, t0: float, log) -> dict:
    import jax

    cfg, traffic = cell["config_data"], cell["traffic_data"]
    ref = load_reference(cfg)
    work = tempfile.mkdtemp(prefix="bench-serve-")
    try:
        server, meta, arrays = start_server(cfg, traffic, seed, work, log)
        try:
            host, port = server.address
            p = dict(plan(cfg, traffic, cell, seed, seconds), host=host, port=port)
            before = server.stats()["batcher"]
            profiler = jax.profiler.trace(trace_dir) if trace_dir else None
            if profiler:
                profiler.__enter__()
            t_start = time.perf_counter()
            setup_s = t_start - t0
            with jax.profiler.TraceAnnotation("bench.window"):
                res = send(p, work, seconds + 120)
            t_end = time.perf_counter()
            if profiler:
                profiler.__exit__(None, None, None)
            after = server.stats()["batcher"]
            used = list(server.handle.get().mesh.devices.flat)
            peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
        finally:
            server.shutdown()
        lat = res["latency_s"]
        failed = sum(1 for v in lat if v is None)
        late = np.asarray(res["late_s"])
        log(f"window: {len(lat)} requests due over {seconds} s at {cell['rate_per_s']}/s, "
            f"{failed} failed; sender late p50 {np.median(late) * 1e3:.3f} ms, "
            f"p99 {np.quantile(late, 0.99) * 1e3:.3f} ms, max {late.max() * 1e3:.3f} ms; "
            f"last answer at {res['end_s']:.3f} s")
        rng = np.random.default_rng([seed, 3])
        idx = rng.choice(len(lat), min(traffic["check_requests"], len(lat)), replace=False)
        numbers = check(meta, arrays, p["requests"], res["answers"], idx, ref)
        return {
            "attempted": len(lat), "failed": failed,
            "end_to_end": {"serve_p50_ms": 1e3 * percentile(lat, 0.50),
                           "serve_p99_ms": 1e3 * percentile(lat, 0.99),
                           "setup_s": setup_s},
            "numbers": numbers,
            "memory_peak_bytes": int(peak),
            "devices_used": len(used),
            "layer": {"requests": after["requests"] - before["requests"],
                      "cycles": after["cycles"] - before["cycles"],
                      "window_s": t_end - t_start,
                      "sender_late_p99_s": float(np.quantile(late, 0.99))},
        }
    finally:
        shutil.rmtree(work, ignore_errors=True)
