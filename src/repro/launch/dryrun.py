import os
os.environ["XLA_FLAGS"] = os.environ.get("REPRO_DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512")
# ^ MUST precede every other import: jax locks the device count on first init.
#
# Multi-pod dry-run: lower + compile every (architecture x input-shape) cell
# on the production meshes and extract the roofline terms from the compiled
# artifact. This is the proof that the distribution config is coherent —
# sharding mismatches, compile-time OOM and unsupported collectives all
# surface here.
#
# Usage:
#   python -m repro.launch.dryrun --arch yi-6b --shape train_4k
#   python -m repro.launch.dryrun --all                  # single-pod 16x16
#   python -m repro.launch.dryrun --all --multi-pod      # 2x16x16
#   python -m repro.launch.dryrun --bpmf                 # the paper's own program
#
# Results land in experiments/dryrun/<mesh>/<arch>__<shape>.json.

import argparse
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, get_config, list_archs
from repro.configs.registry import cell_runnable
from repro.launch.mesh import bpmf_ring_from, make_production_mesh
from repro.models.config import ModelConfig
from repro.models.model import LMModel, build_model
from repro.models.module import DECODE_RULES, SERVE_RULES, TRAIN_RULES, ZERO_RULES, ShardingRules
from repro.training.optimizer import AdamW
from repro.training.lm_serve import make_decode_step, make_prefill_step
from repro.training.train import (
    abstract_batch,
    abstract_train_state,
    batch_specs,
    make_train_step,
    state_specs,
)

# TPU v5e hardware constants (per chip / per link)
V5E = {"peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9}

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")


# ---------------------------------------------------------------------------
# Optimizer / rules defaults
# ---------------------------------------------------------------------------


def default_optimizer(cfg: ModelConfig, num_params: int) -> AdamW:
    """bf16 moments above 50B params — the HBM fit for nemotron/grok
    (DESIGN.md §6, optimizer.py header)."""
    moment_dtype = jnp.bfloat16 if num_params > 50e9 else jnp.float32
    return AdamW(learning_rate=1e-4, moment_dtype=moment_dtype)


def to_shardings(tree, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree, is_leaf=lambda x: isinstance(x, P)
    )


# ---------------------------------------------------------------------------
# Collective-bytes extraction from the partitioned HLO
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
}

_COLL_RE = re.compile(
    r"=\s*(?P<rtype>\(?[a-z0-9\[\],\{\} ]+?\)?)\s*"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|s64|u64|s32|u32|s16|u16|s8|u8|pred)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LEGACY_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _result_bytes(rtype: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(rtype):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LEGACY_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def collective_stats(hlo_text: str) -> dict:
    """Per-device collective byte counts from the partitioned module.

    ``wire_bytes`` estimates bytes that actually cross ICI per device with
    ring-algorithm costs: all-reduce 2(S-1)/S, all-gather (S-1)/S of the
    gathered result, reduce-scatter (S-1)/S of the scattered input,
    permute/all-to-all (S-1)/S of the payload.
    """
    by_op: dict[str, dict] = {}
    wire = 0.0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or "-done" in line:
            continue
        op = m.group("op")
        nbytes = _result_bytes(m.group("rtype"))
        S = _group_size(line)
        if S <= 1:
            w = 0.0
        elif op == "all-reduce":
            w = 2.0 * (S - 1) / S * nbytes
        elif op == "all-gather":
            w = (S - 1) / S * nbytes
        elif op == "reduce-scatter":
            w = (S - 1) * nbytes  # result is 1/S of the input
        else:  # all-to-all, collective-permute
            w = (S - 1) / S * nbytes if op == "all-to-all" else float(nbytes)
        d = by_op.setdefault(op, {"count": 0, "result_bytes": 0, "wire_bytes": 0.0})
        d["count"] += 1
        d["result_bytes"] += nbytes
        d["wire_bytes"] += w
        wire += w
    return {"by_op": by_op, "wire_bytes_per_device": wire}


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------


def train_plan(cfg: ModelConfig, mesh, global_batch: int) -> tuple[ShardingRules, int]:
    """(rules, microbatches) for a train cell.

    Small/medium dense + ssm/hybrid/encoder: pure-ZeRO (batch over every
    axis, weights gathered at use) — no per-layer activation all-reduces,
    and the per-layer gather is < ~1.5 GB bf16.

    MoE + >=100B dense (nemotron): the gathered per-layer weights (3-7 GB
    bf16) would dominate the 16 GB budget transiently, so weights stay
    tensor-parallel/resident.

    Microbatches are chosen so each device holds ONE sequence per
    microbatch under the batch sharding the mesh actually resolves (e.g.
    batch=256 on the 512-chip multi-pod mesh falls back to 32-way
    (pod,data) sharding -> 8 rows/device -> 8 microbatches).
    """
    from repro.models.module import resolve_spec

    model = build_model(cfg)
    per_layer_bytes = 2 * (model.num_params() - cfg.padded_vocab * cfg.d_model) / max(cfg.num_layers, 1)
    rules = TRAIN_RULES if (cfg.num_experts or per_layer_bytes > 1.5e9) else ZERO_RULES
    spec = resolve_spec((global_batch,), ("batch",), rules, mesh)
    names = spec[0] if spec else None
    names = (names,) if isinstance(names, str) else (names or ())
    ways = 1
    for n in names:
        ways *= mesh.shape[n]
    mb = max(1, global_batch // max(ways, 1))
    if cfg.num_experts:
        # §Perf H1: fewer microbatches amortize the per-microbatch expert-bank
        # re-gathers (collective -32%); grouped remat bounds the carries.
        mb = max(1, mb // 4)
    return rules, mb


def lower_cell(arch: str, shape_name: str, mesh, loss_chunk: int = 512,
               rules_train: ShardingRules | None = None,
               microbatches: int | None = None,
               rules_serve: ShardingRules = SERVE_RULES):
    """Build + lower one (arch x shape) cell on ``mesh``. Returns (lowered,
    meta) — compile happens in run_cell so failures are attributable."""
    cfg = get_config(arch)
    spec = SHAPES[shape_name]
    model = build_model(cfg)
    B, L = spec.global_batch, spec.seq_len
    n_params = model.num_params()

    if spec.kind == "train":
        plan_rules, plan_mb = train_plan(cfg, mesh, B)
        rules_train = rules_train or plan_rules
        mb = microbatches or plan_mb
        opt = default_optimizer(cfg, n_params)
        step = make_train_step(model, opt, rules_train, mesh, microbatches=mb,
                               loss_chunk=loss_chunk)
        state_abs = abstract_train_state(model, opt)
        sspec = to_shardings(state_specs(model, opt, rules_train, mesh), mesh)
        bspec = to_shardings(batch_specs(cfg, rules_train, mesh, B, L), mesh)
        lowered = jax.jit(
            step, in_shardings=(sspec, bspec), out_shardings=(sspec, None),
            donate_argnums=(0,),
        ).lower(state_abs, abstract_batch(cfg, B, L))
        tokens = B * L
        model_flops = 6.0 * model.matmul_params() * tokens

    elif spec.kind == "prefill":
        # sequence-parallel flash prefill (§Perf H2): q-block axis vmapped and
        # sharded over "model" instead of scanned
        cfg = cfg.replace(flash_q_parallel=True)
        model = build_model(cfg)
        params_abs = model.abstract()
        pspec = to_shardings(model.specs(rules_serve, mesh), mesh)
        if cfg.is_encoder:
            # encoder "prefill" = one batched forward over the 32k frames
            fwd = lambda p, x: model.forward(p, x, ctx=_ctx(mesh, rules_serve))[0]
            inp = jax.ShapeDtypeStruct((B, L, cfg.frame_dim), jnp.bfloat16)
            ispec = NamedSharding(mesh, _first_spec(rules_serve, mesh, (B, L, cfg.frame_dim)))
            lowered = jax.jit(fwd, in_shardings=(pspec, ispec)).lower(params_abs, inp)
        else:
            step = make_prefill_step(model, rules_serve, mesh)
            cache_abs = model.abstract_cache(B, L)
            cspec = to_shardings(model.cache_specs(rules_serve, mesh, B, L), mesh)
            inp = _abstract_tokens(cfg, B, L)
            ispec = NamedSharding(mesh, _first_spec(rules_serve, mesh, inp.shape))
            lowered = jax.jit(
                step, in_shardings=(pspec, ispec, cspec),
                out_shardings=(None, cspec), donate_argnums=(2,),
            ).lower(params_abs, inp, cache_abs)
        model_flops = 2.0 * model.matmul_params() * B * L

    elif spec.kind == "decode":
        rules_dec = DECODE_RULES if rules_serve is SERVE_RULES else rules_serve
        params_abs = model.abstract()
        pspec = to_shardings(model.specs(rules_dec, mesh), mesh)
        step = make_decode_step(model, rules_dec, mesh)
        cache_abs = model.abstract_cache(B, L)
        cspec = to_shardings(model.cache_specs(rules_dec, mesh, B, L), mesh)
        tok = _abstract_tokens(cfg, B, 1)
        tspec = NamedSharding(mesh, _first_spec(rules_dec, mesh, tok.shape))
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        key = jax.eval_shape(lambda: jax.random.key(0))
        lowered = jax.jit(
            step, in_shardings=(pspec, tspec, cspec, None, None),
            out_shardings=(tspec, cspec), donate_argnums=(2,),
        ).lower(params_abs, tok, cache_abs, pos, key)
        model_flops = 2.0 * model.matmul_params() * B

    else:
        raise ValueError(spec.kind)

    meta = {
        "arch": arch, "shape": shape_name, "kind": spec.kind,
        "global_batch": B, "seq_len": L,
        "num_params": n_params, "active_params": model.active_params(),
        "model_flops_global": model_flops,
    }
    return lowered, meta


def _ctx(mesh, rules):
    from repro.models.module import ShardingCtx

    return ShardingCtx(mesh=mesh, rules=rules)


def _first_spec(rules, mesh, shape):
    from repro.models.module import resolve_spec

    axes = ("batch", "seq", None)[: len(shape)]
    return resolve_spec(shape, axes, rules, mesh)


def _abstract_tokens(cfg: ModelConfig, B: int, L: int):
    if cfg.input_mode == "tokens":
        return jax.ShapeDtypeStruct((B, L), jnp.int32)
    return jax.ShapeDtypeStruct((B, L, cfg.frame_dim), jnp.bfloat16)


# ---------------------------------------------------------------------------
# Roofline terms
# ---------------------------------------------------------------------------


def roofline_terms(compiled, meta: dict, num_devices: int) -> dict:
    from repro.launch.hlo_analysis import analyze

    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    # loop-aware static analysis (XLA's cost_analysis counts while bodies
    # ONCE — wrong for every scanned program; see hlo_analysis.py)
    hlo = analyze(text)
    hlo_flops_dev = float(hlo["flops"])
    hlo_bytes_dev = float(hlo["bytes"])
    coll = {
        "by_op": hlo["collectives_by_op"],
        "wire_bytes_per_device": hlo["collective_wire_bytes"],
    }

    compute_s = hlo_flops_dev / V5E["peak_flops"]
    memory_s = hlo_bytes_dev / V5E["hbm_bw"]
    collective_s = coll["wire_bytes_per_device"] / V5E["ici_bw"]
    terms = {"compute_s": compute_s, "memory_s": memory_s, "collective_s": collective_s}
    dominant = max(terms, key=terms.get)

    model_flops_dev = meta["model_flops_global"] / num_devices
    mem = {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "peak_bytes_est": ma.argument_size_in_bytes + ma.output_size_in_bytes
        + ma.temp_size_in_bytes - ma.alias_size_in_bytes,
    }
    return {
        **terms,
        "dominant": dominant,
        "hlo_flops_per_device": hlo_flops_dev,
        "hlo_bytes_per_device": hlo_bytes_dev,
        "collectives": coll,
        "model_flops_per_device": model_flops_dev,
        "useful_flops_ratio": (model_flops_dev / hlo_flops_dev) if hlo_flops_dev > 0 else None,
        "memory": mem,
        "fits_hbm": mem["peak_bytes_est"] <= 16e9,
        "roofline_fraction": (model_flops_dev / V5E["peak_flops"])
        / max(max(terms.values()), 1e-30),
        "xla_cost_analysis": {  # reference only — undercounts loop bodies
            "flops": float(ca.get("flops", -1.0)),
            "bytes_accessed": float(ca.get("bytes accessed", -1.0)),
        },
    }


# ---------------------------------------------------------------------------
# BPMF dry-run (the paper's own program on the production mesh)
# ---------------------------------------------------------------------------


def abstract_bpmf_data(num_shards: int, num_users: int, num_movies: int, nnz: int,
                       K: int, pads=(32, 128, 512), steps_with_work: int = 8):
    """ShapeDtypeStruct stand-in for DistBPMFData: bucket shapes follow the
    paper's workload model (cost = a + b*nnz) for a ChEMBL-like skew, without
    the O(items x shards) host build. Ring steps beyond ``steps_with_work``
    carry one empty-ish bucket each (most remote shards contribute few
    ratings after the locality reordering — §IV-B)."""
    from repro.core.distributed import DistBPMFData, DistTestSet, RingSide
    from repro.core.types import Bucket

    S = num_shards
    sds = jax.ShapeDtypeStruct

    def side(num_items: int, nnz_side: int) -> RingSide:
        cap = -(-num_items // S)
        per_shard_nnz = nnz_side // S
        steps = []
        for t in range(S):
            buckets = []
            if t < steps_with_work:
                for pad in pads:
                    Bk = max(8, per_shard_nnz // (steps_with_work * pad * len(pads)))
                    Bk = -(-Bk // 8) * 8
                    buckets.append(
                        Bucket(
                            item_ids=sds((S * Bk,), jnp.int32),
                            nbr=sds((S * Bk, pad), jnp.int32),
                            val=sds((S * Bk, pad), jnp.float32),
                            nnz=sds((S * Bk,), jnp.int32),
                        )
                    )
            else:
                buckets.append(
                    Bucket(
                        item_ids=sds((S * 8,), jnp.int32),
                        nbr=sds((S * 8, pads[0]), jnp.int32),
                        val=sds((S * 8, pads[0]), jnp.float32),
                        nnz=sds((S * 8,), jnp.int32),
                    )
                )
            steps.append(tuple(buckets))
        return RingSide(
            steps=tuple(steps), orig_ids=sds((S * cap,), jnp.int32),
            cap=cap, num_items=num_items,
        )

    T = 10000
    return DistBPMFData(
        users=side(num_users, nnz),
        movies=side(num_movies, nnz),
        test=DistTestSet(rows=sds((T,), jnp.int32), cols=sds((T,), jnp.int32),
                         vals=sds((T,), jnp.float32)),
        mean_rating=sds((), jnp.float32),
        num_shards=S,
        min_rating=1.0,
        max_rating=5.0,
    )


def lower_bpmf(mesh, K: int = 32, comm_mode: str = "ring",
               num_users: int = 483_500, num_movies: int = 5_775, nnz: int = 1_023_952):
    """Lower the distributed Gibbs sweep (ChEMBL-20 scale by default) on the
    production mesh flattened to the BPMF ring."""
    from repro.core.distributed import DistState, data_specs, dist_gibbs_sweep
    from repro.core.prediction import PredictionState
    from repro.core.types import BPMFConfig, HyperParams

    ring = bpmf_ring_from(mesh)
    S = ring.devices.size
    cfg = BPMFConfig(K=K, comm_mode=comm_mode, gram_impl="xla")
    data = abstract_bpmf_data(S, num_users, num_movies, nnz, K)
    sds = jax.ShapeDtypeStruct
    cap_u, cap_v = data.users.cap, data.movies.cap
    state = DistState(
        U=sds((S * cap_u, K), jnp.float32),
        V=sds((S * cap_v, K), jnp.float32),
        hyper_U=HyperParams(mu=sds((K,), jnp.float32), Lam=sds((K, K), jnp.float32)),
        hyper_V=HyperParams(mu=sds((K,), jnp.float32), Lam=sds((K, K), jnp.float32)),
        sweep=sds((), jnp.int32),
    )
    T = data.test.rows.shape[0]
    pred = PredictionState(sum_pred=sds((T,), jnp.float32), num_samples=sds((), jnp.int32))
    key = sds((2,), jnp.uint32)

    lowered = jax.jit(
        dist_gibbs_sweep, static_argnames=("cfg", "mesh")
    ).lower(jax.random.key(0), state, pred, data, cfg, ring)
    meta = {
        "arch": "bpmf", "shape": f"chembl_K{K}_{comm_mode}", "kind": "bpmf_sweep",
        "num_users": num_users, "num_movies": num_movies, "nnz": nnz, "K": K,
        # one sweep updates every user+movie: gram (2K^2 flops/rating/side)
        # + per-item Cholesky solve ~ (2/3)K^3 + 4K^2
        "model_flops_global": 2 * (2.0 * K * K * nnz) + (num_users + num_movies)
        * ((2.0 / 3.0) * K**3 + 4.0 * K * K),
    }
    return lowered, meta


# ---------------------------------------------------------------------------
# Runner / CLI
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             loss_chunk: int = 512) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    try:
        if arch == "bpmf":
            lowered, meta = lower_bpmf(mesh, comm_mode=shape_name or "ring")
        else:
            lowered, meta = lower_cell(arch, shape_name, mesh, loss_chunk=loss_chunk)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        result = {
            **meta, "mesh": mesh_name, "num_devices": n_dev, "status": "ok",
            "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
            "roofline": roofline_terms(compiled, meta, n_dev),
        }
    except Exception as e:  # noqa: BLE001 — every failure is a recorded result
        result = {
            "arch": arch, "shape": shape_name, "mesh": mesh_name, "num_devices": n_dev,
            "status": "error", "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-2000:],
        }
    os.makedirs(os.path.join(out_dir, mesh_name), exist_ok=True)
    path = os.path.join(out_dir, mesh_name, f"{arch}__{shape_name}.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1, default=str)
    return result


def _print_result(r: dict) -> None:
    if r["status"] != "ok":
        print(f"[FAIL] {r['arch']:16s} {r['shape']:12s} {r['mesh']}: {r['error']}")
        return
    rf = r["roofline"]
    print(
        f"[ok] {r['arch']:16s} {r['shape']:12s} {r['mesh']:10s} "
        f"compute={rf['compute_s']:.3e}s memory={rf['memory_s']:.3e}s "
        f"coll={rf['collective_s']:.3e}s dom={rf['dominant']:9s} "
        f"useful={rf['useful_flops_ratio'] if rf['useful_flops_ratio'] is None else round(rf['useful_flops_ratio'], 3)} "
        f"hbm={rf['memory']['peak_bytes_est'] / 1e9:.2f}GB fit={rf['fits_hbm']} "
        f"(compile {r['compile_s']}s)"
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", help="architecture id (or 'bpmf')")
    ap.add_argument("--shape", help="shape id (or comm_mode for --arch bpmf)")
    ap.add_argument("--all", action="store_true", help="run every runnable cell")
    ap.add_argument("--multi-pod", action="store_true", help="2x16x16 mesh (else 16x16)")
    ap.add_argument("--out-dir", default=os.path.normpath(OUT_DIR))
    ap.add_argument("--loss-chunk", type=int, default=512)
    args = ap.parse_args(argv)

    cells: list[tuple[str, str]] = []
    if args.all:
        for arch in list_archs():
            cfg = get_config(arch)
            for shape in SHAPES.values():
                ok, why = cell_runnable(cfg, shape)
                if ok:
                    cells.append((arch, shape.name))
                else:
                    print(f"[skip] {arch:16s} {shape.name:12s} — {why}")
        cells.append(("bpmf", "ring"))
        cells.append(("bpmf", "allgather"))
    elif args.arch:
        cells.append((args.arch, args.shape or ("ring" if args.arch == "bpmf" else "train_4k")))
    else:
        ap.error("--arch or --all required")

    failures = 0
    for arch, shape in cells:
        r = run_cell(arch, shape, args.multi_pod, args.out_dir, args.loss_chunk)
        _print_result(r)
        failures += r["status"] != "ok"
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
