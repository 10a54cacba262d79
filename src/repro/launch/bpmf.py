"""BPMF engine CLI: backend / dataset / schedule as flags, not imports.

    PYTHONPATH=src python -m repro.launch.bpmf \
        --backend ring --dataset synthetic --sweeps 50 \
        --devices 8 --checkpoint-dir /tmp/bpmf-ckpt

Prints per-sweep sample and posterior-mean RMSE. ``--model bptf`` fits the
temporal tensor model (user x movie x time bin) on the ``sequential``
backend: synthetic ratings get ``--times`` uniform bins, MovieLens
``ratings.csv`` its calendar months. ``--resume`` continues
from the latest checkpoint in ``--checkpoint-dir`` with randomness
identical to an uninterrupted run. ``--devices N`` forces N host devices
(CPU) so the ring/allgather backends exercise a real multi-device mesh —
it must be applied before jax initializes, which is why this module parses
arguments before importing anything heavy.

Multi-process: ``--coordinator host:port --num-processes N --process-id i``
(or the ``REPRO_*`` environment set by ``scripts/launch_multiproc.py``)
joins this process into one jax job whose ring mesh spans every process's
devices; ``--devices`` then means devices *per process*. Only process 0
prints and exports — peers run the same collective program silently.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

from repro.launch.hostdevices import enable_compile_cache, init_multiprocess


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m repro.launch.bpmf",
        description="Run BPMF Gibbs sampling through the repro.bpmf engine facade.",
    )
    p.add_argument("--backend", default="sequential",
                   help="sequential | ring | ring_async | allgather | "
                        "posterior_merge (registry name)")
    p.add_argument("--dataset", default="synthetic",
                   help="synthetic | movielens | chembl (registry name)")
    p.add_argument("--dataset-path", default=None, help="file for movielens/chembl loaders")
    p.add_argument("--users", type=int, default=400, help="synthetic: number of users")
    p.add_argument("--movies", type=int, default=300, help="synthetic: number of movies")
    p.add_argument("--nnz", type=int, default=12_000, help="synthetic: number of ratings")
    p.add_argument("--times", type=int, default=12,
                   help="synthetic with --model bptf: number of time bins")
    p.add_argument("--model", default="bpmf", choices=["bpmf", "bptf"],
                   help="bpmf (user x movie) or bptf (user x movie x time bin; "
                        "sequential backend, ratings with times)")
    p.add_argument("--K", type=int, default=16, help="latent rank")
    p.add_argument("--alpha", type=float, default=2.0, help="rating noise precision")
    p.add_argument("--sweeps", type=int, default=50)
    p.add_argument("--sweeps-per-block", type=int, default=8,
                   help="Gibbs sweeps per jitted device block (one host sync "
                        "per block; 1 = per-sweep dispatch, same samples)")
    p.add_argument("--pipeline-blocks", type=int, default=1,
                   help="block dispatch queue depth: launch the next device "
                        "block before fetching the previous block's metrics "
                        "(1 = synchronous; same samples at every depth)")
    p.add_argument("--donate-blocks", default="auto",
                   choices=["auto", "on", "off"],
                   help="donate the block carry buffers to XLA so blocks "
                        "reuse factor/accumulator memory (off = fallback "
                        "path, fresh outputs every block)")
    p.add_argument("--sync-checkpoint-writes", action="store_true",
                   help="commit checkpoints synchronously instead of on the "
                        "background writer thread")
    p.add_argument("--burn-in", type=int, default=8)
    p.add_argument("--seed", type=int, default=0, help="split + sampler seed")
    p.add_argument("--num-shards", type=int, default=0,
                   help="distributed shard count (0 = all visible devices)")
    p.add_argument("--pipeline-depth", type=int, default=1,
                   help="ring_async: ring rotations kept in flight (d >= 1)")
    p.add_argument("--num-partitions", type=int, default=0,
                   help="posterior_merge: independent partition chains "
                        "(0 = one per visible device)")
    p.add_argument("--merge-method", default="precision",
                   choices=["precision", "pool"],
                   help="posterior_merge: subset-posterior combination "
                        "(precision-weighted Gaussian product or uniform "
                        "pooling)")
    p.add_argument("--devices", type=int, default=0,
                   help="force N host (CPU) devices before jax init "
                        "(per process in a multi-process job)")
    p.add_argument("--coordinator", default=None,
                   help="host:port of process 0 — joins a multi-process jax "
                        "job (env fallback: REPRO_COORDINATOR)")
    p.add_argument("--num-processes", type=int, default=None,
                   help="process count of the multi-process job "
                        "(env fallback: REPRO_NUM_PROCESSES)")
    p.add_argument("--process-id", type=int, default=None,
                   help="this process's rank in [0, num-processes) "
                        "(env fallback: REPRO_PROCESS_ID)")
    p.add_argument("--inject-failure", type=int, default=None, metavar="SWEEP",
                   help="testing: raise a simulated NodeFailure on process 0 "
                        "after SWEEP completes (skipped under --resume so an "
                        "elastic restart does not re-fire it)")
    p.add_argument("--gram-impl", default="auto",
                   choices=["auto", "pallas_fused", "pallas", "xla"],
                   help="Gram hot-path dispatch: auto (autotune cache + "
                        "heuristic), pallas_fused, pallas, or xla")
    p.add_argument("--use-pallas", action="store_true",
                   help="deprecated alias for --gram-impl pallas (warns once)")
    p.add_argument("--export-artifact", default=None,
                   help="after the run, write the posterior serving artifact "
                        "here (consumed by python -m repro.launch.serve)")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=0,
                   help="sweeps between auto-saves (0 = none)")
    p.add_argument("--resume", action="store_true",
                   help="continue from the latest checkpoint in --checkpoint-dir")
    p.add_argument("--log-every", type=int, default=1, help="print every Nth sweep")
    return p


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)

    # joins the multi-process job when configured (flags or REPRO_* env);
    # otherwise just forces the host device count. Either way XLA_FLAGS is
    # settled before the heavy imports below.
    init_multiprocess(
        args.coordinator, args.num_processes, args.process_id,
        local_devices=args.devices,
    )

    import jax

    enable_compile_cache()

    from repro.bpmf import BPMFConfig, BPMFEngine, load_dataset
    from repro.runtime.elastic import FailureInjector, StepTimer

    main_proc = jax.process_index() == 0
    say = print if main_proc else (lambda *a, **kw: None)

    dataset_kw = {}
    tensor = args.model == "bptf"
    if args.dataset == "synthetic":
        dataset_kw = dict(num_users=args.users, num_movies=args.movies, nnz=args.nnz,
                          num_times=args.times if tensor else 0)
    elif args.dataset_path:
        dataset_kw = dict(path=args.dataset_path)
    if tensor and args.dataset == "movielens":
        dataset_kw["times"] = True
    coo = load_dataset(args.dataset, **dataset_kw)

    # pass both through: BackendConfig warns on the deprecated flag alone
    # and raises if it conflicts with an explicit --gram-impl
    gram_kw = {"gram_impl": args.gram_impl}
    if args.use_pallas:
        gram_kw["use_pallas"] = True
    cfg = BPMFConfig().replace(
        name=args.backend,
        num_shards=args.num_shards,
        pipeline_depth=args.pipeline_depth,
        num_partitions=args.num_partitions,
        merge_method=args.merge_method,
        **gram_kw,
        family=args.model,
        K=args.K,
        alpha=args.alpha,
        num_sweeps=args.sweeps,
        sweeps_per_block=args.sweeps_per_block,
        pipeline_blocks=args.pipeline_blocks,
        donate_blocks=args.donate_blocks,
        async_checkpoint_writes=not args.sync_checkpoint_writes,
        burn_in=args.burn_in,
        seed=args.seed,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
    )
    engine = BPMFEngine(cfg)
    engine.prepare(coo)
    resumed_at = 0
    if args.resume:
        resumed_at = engine.restore()
        say(f"resumed from checkpoint at sweep {resumed_at}")

    # elastic-runtime hooks: the straggler watchdog times every sweep, and
    # the injector simulates a preemption so the launcher's restart policy
    # can be exercised end to end (never re-fires on a resumed run)
    timer = StepTimer()
    injector = None
    if args.inject_failure is not None and main_proc and not args.resume:
        injector = FailureInjector({args.inject_failure: 1})

    say(
        f"backend={args.backend} devices={len(jax.devices())} "
        f"processes={jax.process_count()} "
        f"model={args.model} dataset={args.dataset} R: {coo.num_users} x {coo.num_movies}"
        f"{f' x {coo.num_times}' if tensor else ''}, "
        f"{coo.nnz} ratings; K={cfg.model.K} sweeps={cfg.run.num_sweeps}"
    )
    t0 = time.time()
    t_prev = t0
    for m in engine.sample():
        sweep = int(m.sweep)
        t_now = time.time()
        timer.record(sweep, t_now - t_prev)
        t_prev = t_now
        if args.log_every and (sweep % args.log_every == 0 or sweep == cfg.run.num_sweeps):
            say(
                f"  sweep {sweep:4d}  rmse(sample)={m.rmse_sample:.4f}  "
                f"rmse(avg)={m.rmse_avg:.4f}"
            )
        if injector is not None:
            try:
                injector.check(sweep)
            except Exception as e:
                # die like a preempted pod: hard exit, no jax.distributed
                # shutdown handshake, no atexit drains — only committed
                # checkpoints survive, which is exactly what the launcher's
                # restart policy resumes from
                print(f"injected failure at sweep {sweep}: {e}", flush=True)
                os._exit(1)
    dt = time.time() - t0
    swept = engine.num_sweeps_done - resumed_at  # only what this process ran
    updates = (coo.num_users + coo.num_movies) * swept
    say(
        f"final rmse(avg)={engine.rmse:.4f} after {engine.num_sweeps_done} sweeps "
        f"({swept} this run) in {dt:.2f}s ({updates / max(dt, 1e-9):,.0f} item updates/s)"
    )
    if args.export_artifact:
        # collective in a multi-process job (peers hit the export barrier);
        # only process 0 writes and reports
        path = engine.export(args.export_artifact)
        say(f"exported serving artifact to {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
