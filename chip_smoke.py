#!/usr/bin/env python
"""Smoke run of the BPMF sampler and its serving path on TPU chips.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the four-chip ring, and its reference

One chip: the paper's ChEMBL deployment at full width (483,500 compounds x
5,775 targets, 1,023,952 activities, K=32) trains a few blocks through
``BPMFEngine`` with a checkpoint save and ``restore()`` between blocks, is
exported, served from the artifact and through the in-process HTTP server
on a loopback port, and a small synthetic problem is sampled on the chip
and on the host CPU of the same process to compare the two. Four chips:
``ring`` and ``ring_async`` (depth 2) over the four chips against the
one-chip ``sequential`` run on the same data and seed.

Every phase prints what it did; times are labelled as a smoke run and are
not benchmark numbers. Any failed check raises, and the script exits
non-zero. The last line of stdout is ``{"ok": true, "device": {...}}``.
Without a TPU the script exits non-zero before doing any work. Everything
runs in this one process: a child would find the chip held.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # keep libtpu's logs off /tmp

CHEMBL_NOISE_STD = 0.6  # data.synthetic.CHEMBL_LIKE
RMSE_BOUND = 2.5 * CHEMBL_NOISE_STD  # examples/bpmf_chembl.py
PARITY_TOL = 1e-3  # the repo's cross-backend / cross-device RMSE contract
SMOKE = "[smoke run, not a benchmark]"


def say(*a) -> None:
    print(*a, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def lowered_block(engine, label: str) -> str:
    """Lower and compile the engine's next block; print how its Gram
    contractions and per-item draws resolved and whether the program holds
    a Pallas kernel for each. Returns a one-line summary for the report."""
    import re

    from repro.kernels import ops
    from repro.utils import compiled_hbm_bytes

    t0 = time.perf_counter()
    with ops.record_gram_decisions() as decisions, ops.record_draw_decisions() as draws:
        lowered = engine.lower_block()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    check(bool(decisions), f"{label}: tracing recorded no Gram dispatch")
    check(bool(draws), f"{label}: tracing recorded no posterior draw")
    seen = {}
    for kind, (B, P, Ns, K), dec in decisions:
        seen.setdefault((kind, B, P, Ns, K, dec), 0)
        seen[(kind, B, P, Ns, K, dec)] += 1
    for (kind, B, P, Ns, K, dec), n in seen.items():
        tiling = "" if dec.impl == "xla" else f" tb={dec.tb} pc={dec.pc} ns_chunk={dec.ns_chunk}"
        say(f"gram {label}: {kind} B={B} P={P} Ns={Ns} K={K} -> {dec.impl}{tiling} (x{n})")
    on_kernel = sum(dec.impl != "xla" for _, _, dec in draws)
    say(f"draw {label}: {on_kernel} of {len(draws)} traced draws on the kernel")
    text = compiled.as_text()
    calls = re.findall(r'custom_call_target="tpu_custom_call".*op_name="([^"]*)"', text)
    kernels = any("posterior_draw" not in name for name in calls)
    draw_kernels = any("posterior_draw" in name for name in calls)
    batched_chol = len(re.findall(
        r'= f32\[\d+,\d+,\d+[^ ]* custom-call\(.*custom_call_target="Cholesky"', text))
    wants = any(dec.impl != "xla" for _, _, dec in decisions)
    say(f"gram {label}: compiled block holds tpu_custom_call: {kernels}")
    say(f"draw {label}: draw kernel in HLO: {draw_kernels}; batched Cholesky calls: {batched_chol}")
    check(kernels == wants, f"{label}: decisions say kernel={wants}, HLO says {kernels}")
    check(draw_kernels == bool(on_kernel), f"{label}: draws on kernel={on_kernel}, HLO says {draw_kernels}")
    check(batched_chol == 0 or on_kernel < len(draws), f"{label}: batched Cholesky left beside the kernel")
    say(f"{SMOKE} {label}: lower+compile {compile_s:.3f} s, "
        f"block program needs {compiled_hbm_bytes(compiled) / 2**30:.3f} GiB")
    impls = sorted({dec.impl for _, _, dec in decisions})
    return (f"{label}: {len(decisions)} Gram dispatches -> {'+'.join(impls)}; tpu_custom_call={kernels}; "
            f"draws on kernel {on_kernel}/{len(draws)}")


def run_blocks(engine, label: str, stop_after: int | None = None) -> None:
    """Drive ``engine.sample()``, printing each block's wall time."""
    spb = engine.cfg.run.sweeps_per_block
    t0 = time.perf_counter()
    for m in engine.sample():
        sweep = int(m.sweep)
        if sweep % spb == 0 or sweep == engine.cfg.run.num_sweeps:
            t1 = time.perf_counter()
            say(f"{SMOKE} {label}: block ending at sweep {sweep}: {t1 - t0:.3f} s wall, "
                f"rmse(sample)={m.rmse_sample:.5f} rmse(avg)={m.rmse_avg:.5f}")
            t0 = t1
        if stop_after is not None and sweep >= stop_after:
            return


def phase_train(coo, work: str, num_sweeps: int = 6, sweeps_per_block: int = 2):
    """ChEMBL K=32 on one chip, with a checkpoint save and restore() between blocks."""
    import numpy as np

    from repro.bpmf import BPMFConfig, BPMFEngine

    cfg = BPMFConfig().replace(
        name="sequential", K=32, num_sweeps=num_sweeps, burn_in=2,
        sweeps_per_block=sweeps_per_block, seed=0,
        checkpoint_dir=os.path.join(work, "checkpoints"),
        checkpoint_every=2 * sweeps_per_block,
    )
    engine = BPMFEngine(cfg)
    t0 = time.perf_counter()
    engine.prepare(coo)
    say(f"{SMOKE} train: host layout + device placement {time.perf_counter() - t0:.3f} s")
    summary = lowered_block(engine, "train")
    run_blocks(engine, "train", stop_after=cfg.run.checkpoint_every)
    saved_at = engine.num_sweeps_done
    U_live, V_live = engine.factors()
    step = engine.restore()
    U_back, V_back = engine.factors()
    check(step == saved_at, f"restore() returned sweep {step}, checkpoint was at {saved_at}")
    check(np.array_equal(U_live, U_back) and np.array_equal(V_live, V_back),
          "restored factors differ from the checkpointed ones")
    say(f"train: checkpoint at sweep {saved_at} restored bitwise; continuing")
    run_blocks(engine, "train")
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    say(f"train: peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"bytes_limit={stats.get('bytes_limit')}")
    rmse = engine.rmse
    say(f"train: final rmse(avg)={rmse:.6f} after {engine.num_sweeps_done} sweeps "
        f"(bound {RMSE_BOUND})")
    check(bool(np.isfinite(rmse)) and rmse <= RMSE_BOUND, f"rmse {rmse} outside (0, {RMSE_BOUND}]")
    return engine, [summary]


def phase_reference(num_sweeps: int = 8) -> list[str]:
    """A small synthetic problem on the chip and on the host CPU. Its
    largest pad class spans several Gram row tiles, so the check covers the
    tiled path the ChEMBL run takes."""
    import jax

    from repro.bpmf import BPMFConfig, BPMFEngine, load_dataset
    from repro.core.types import gram_tile_rows
    from repro.data.sparse import build_bpmf_data

    small = load_dataset("synthetic", num_users=20_000, num_movies=300, nnz=40_000)
    data = build_bpmf_data(small)
    tiles = max(-(-b.B // gram_tile_rows(b.P))
                for side in (data.users, data.movies) for b in side.buckets)
    say(f"reference: {small.num_users} x {small.num_movies}, {small.nnz} ratings; "
        f"largest pad class runs in {tiles} Gram row tiles")
    check(tiles > 1, "the reference never reaches the row-tiled Gram path")
    cfg = BPMFConfig().replace(
        name="sequential", K=16, num_sweeps=num_sweeps, burn_in=2, sweeps_per_block=4, seed=0
    )
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        ref = BPMFEngine(cfg.replace(gram_impl="xla")).fit(small).rmse
    say(f"reference: host CPU, gram xla: rmse(avg)={ref:.6f}")
    summaries = []
    for impl in ("auto", "xla"):
        engine = BPMFEngine(cfg.replace(gram_impl=impl)).prepare(small)
        summaries.append(lowered_block(engine, f"reference-{impl}"))
        got = engine.fit().rmse
        say(f"reference: {jax.devices()[0].platform}, gram {impl}: rmse(avg)={got:.6f} "
            f"|diff|={abs(got - ref):.3e} (limit {PARITY_TOL})")
        check(abs(got - ref) <= PARITY_TOL, f"chip vs CPU rmse differ by {abs(got - ref)}")
    return summaries


def phase_serve(engine, work: str, n_pairs: int = 64, n_users: int = 4, k: int = 10) -> None:
    """Export, load, predict / top_k bitwise against the engine, then HTTP."""
    import numpy as np

    from repro.serve import BPMFServer, PosteriorPredictor, ServeClient
    from repro.serve.artifact import load_artifact

    t0 = time.perf_counter()
    art = engine.export(os.path.join(work, "artifact"))
    say(f"{SMOKE} serve: export {time.perf_counter() - t0:.3f} s -> {art}")
    rng = np.random.default_rng(0)
    meta, arrays = load_artifact(art)
    rows = rng.integers(0, meta.num_users, n_pairs).astype(np.int32)
    cols = rng.integers(0, meta.num_movies, n_pairs).astype(np.int32)
    users = rng.integers(0, meta.num_users, n_users).astype(np.int32)

    want_p, want_s = engine.predict(rows, cols, return_std=True)
    want_ids, want_sc = engine.predictor().top_k(users, k)

    U, V = arrays["U_mean"], arrays["V_mean"]
    host = np.clip(np.sum(U[rows] * V[cols], axis=-1) + np.float32(meta.mean_rating),
                   meta.min_rating, meta.max_rating)
    check(want_p.shape == (n_pairs,) and want_s.shape == (n_pairs,), "predict shapes")
    check(bool(np.all(np.isfinite(want_p)) and np.all(want_s >= 0)), "predict values")
    check(np.allclose(want_p, host, rtol=0, atol=1e-5), "predictions disagree with numpy")
    host_top = np.sort(np.clip(U[users] @ V.T + meta.mean_rating, meta.min_rating,
                               meta.max_rating), axis=1)[:, ::-1][:, :k]
    check(np.allclose(want_sc, host_top, rtol=0, atol=1e-5), "top-k scores disagree with numpy")

    predictor = PosteriorPredictor.load(art)
    got_p, got_s = predictor.predict(rows, cols, return_std=True)
    got_ids, got_sc = predictor.top_k(users, k)
    check(np.array_equal(got_p, want_p) and np.array_equal(got_s, want_s),
          "loaded predictor predict != engine.predict")
    check(np.array_equal(got_ids, want_ids) and np.array_equal(got_sc, want_sc),
          "loaded predictor top_k != engine top_k")
    say(f"serve: PosteriorPredictor.load matches engine.predict bitwise "
        f"({n_pairs} pairs with std, top-{k} for {n_users} users)")
    del predictor

    with BPMFServer(art, port=0, watch=False) as server:
        host_, port = server.address
        client = ServeClient(f"{host_}:{port}")
        try:
            t0 = time.perf_counter()
            sp, ss = client.predict(rows[:8], cols[:8], return_std=True)
            ids, sc = client.top_k(int(users[0]), k)
            health = client.health()
            say(f"{SMOKE} serve: 3 HTTP requests {time.perf_counter() - t0:.3f} s "
                f"on {host_}:{port}, generation={health.get('generation')}")
        finally:
            client.close()
    check(np.allclose(sp, want_p[:8], rtol=0, atol=1e-6)
          and np.allclose(ss, want_s[:8], rtol=0, atol=1e-6), "server predict")
    check(np.array_equal(ids, want_ids[0]), "server top_k ids")
    say("serve: in-process server answered predict, top_k and healthz")


def phase_four_chips(coo, num_sweeps: int = 4, sweeps_per_block: int = 2) -> list[str]:
    """ring and ring_async (depth 2) over four chips vs one-chip sequential."""
    import jax

    from repro.bpmf import BPMFConfig, BPMFEngine

    base = BPMFConfig().replace(
        K=32, num_sweeps=num_sweeps, burn_in=1, sweeps_per_block=sweeps_per_block, seed=0
    )
    runs = {
        "sequential": base.replace(name="sequential"),
        "ring": base.replace(name="ring", num_shards=4),
        "ring_async": base.replace(name="ring_async", num_shards=4, pipeline_depth=2),
    }
    rmse, summaries = {}, []
    for label, cfg in runs.items():
        engine = BPMFEngine(cfg)
        t0 = time.perf_counter()
        engine.prepare(coo)
        say(f"{SMOKE} {label}: host layout + device placement {time.perf_counter() - t0:.3f} s")
        summaries.append(lowered_block(engine, label))
        run_blocks(engine, label)
        U = engine.state.U
        placed = sorted(d.id for d in U.sharding.device_set)
        say(f"{label}: U {U.shape} on devices {placed}; bytes_in_use per device "
            f"{[(d.memory_stats() or {}).get('bytes_in_use') for d in jax.devices()]}")
        check(len(placed) == (1 if label == "sequential" else 4), f"{label} placement {placed}")
        rmse[label] = engine.rmse
        say(f"{label}: final rmse(avg)={rmse[label]:.6f}")
        del engine, U
    for label in ("ring", "ring_async"):
        diff = abs(rmse[label] - rmse["sequential"])
        say(f"parity: {label} vs sequential |diff|={diff:.3e} (limit {PARITY_TOL})")
        check(diff <= PARITY_TOL, f"{label} vs sequential rmse differ by {diff}")
    say(f"parity: ring == ring_async bitwise: {rmse['ring'] == rmse['ring_async']}")
    return summaries


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: ChEMBL train + serve on one chip (default); "
                         "4: ring / ring_async over four chips vs sequential")
    ap.add_argument("--work-dir", default=os.path.join(REPO, "chip_smoke_out"),
                    help="checkpoints and the exported artifact (git-ignored)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    d0 = devices[0]
    say(f"device: platform={d0.platform} kind={d0.device_kind} count={len(devices)}")
    if d0.platform != "tpu":
        print("chip_smoke: no TPU visible; refusing to run on "
              f"{d0.platform}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    from repro.bpmf import load_dataset
    from repro.kernels import ops
    from repro.launch.hostdevices import enable_compile_cache

    check(not ops.pallas_interpret(), "Pallas would run in interpret mode on the TPU")
    say(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    coo = load_dataset("chembl")
    say(f"{SMOKE} data: ChEMBL-shaped {coo.num_users} x {coo.num_movies}, "
        f"{coo.nnz} ratings generated in {time.perf_counter() - t0:.3f} s")

    if args.chips == 4:
        summaries = phase_four_chips(coo)
    else:
        os.makedirs(args.work_dir, exist_ok=True)
        engine, summaries = phase_train(coo, args.work_dir)
        summaries += phase_reference()
        phase_serve(engine, args.work_dir)
        del engine
    say(f"gram path (interpret={ops.pallas_interpret()}):")
    for line in summaries:
        say(f"  {line}")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
