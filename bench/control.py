#!/usr/bin/env python3
"""Readings of the control and of planted faults, for setting limits.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 [--faults half,exchange]

For each seed the plain reference at the configuration's precision is
compared, by the numbers the cell's ``correct`` uses, with a stand-in put
in the program's place:

* ``control``: the reference one precision step below the configuration
  (float32 ``highest`` -> ``high``, three bfloat16 passes per product);
* ``half``: half of every item's ratings left out, the Gram terms of the
  rest doubled (the mean taken over the rest);
* ``exchange``: only ratings whose user and movie fall on the same one of
  four shards (by id mod 4): the ring's exchange left out;
* ``unchanged``: the state returned as it came in (the initial factors
  and the prior's hyper-parameters);
* ``altered``: one item's draw changed by 1 where it is produced.

Prints one JSON line per (stand-in, seed), and a summary line last. Not
part of a benchmark run; the limits in ``bench/workloads`` come from it
and from the program's own readings.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

import run as harness  # noqa: E402
from benchlib import synth  # noqa: E402

STANDINS = ("control", "half", "exchange", "unchanged", "altered")


def stand_in(name: str, ref, cfg: dict, traffic: dict, rows, cols, vals, want: dict) -> dict:
    """What the stand-in ``name`` puts in the program's place."""
    spb, burn, keep = traffic["sweeps_per_block"], traffic["burn_in"], traffic["keep_factor_samples"]
    K, alpha, beta0, seed = cfg["K"], cfg["alpha"], cfg["beta0"], cfg["run_seed"]

    def problem(keep_rating):
        # ratings left out of training only: the held-out split stays the same
        test = ref.split_mask(rows.size, cfg["test_fraction"], seed)
        prob = ref.Problem(rows, cols, vals, cfg["num_users"], cfg["num_movies"],
                           cfg["test_fraction"], seed)
        tr = ~test & keep_rating
        c = (vals[tr] - np.float32(prob.mean)).astype(np.float32)
        prob.users = ref.groups(rows[tr], cols[tr], c, prob.num_users)
        prob.movies = ref.groups(cols[tr], rows[tr], c, prob.num_movies)
        return prob

    if name == "control":
        prob = ref.Problem(rows, cols, vals, cfg["num_users"], cfg["num_movies"],
                           cfg["test_fraction"], seed)
        return ref.run(prob, K, alpha, beta0, seed, spb, burn, keep, precision="high")
    if name == "half":
        return ref.run(problem(np.arange(rows.size) % 2 == 0), K, 2 * alpha, beta0, seed,
                       spb, burn, keep)
    if name == "exchange":
        return ref.run(problem(rows % 4 == cols % 4), K, alpha, beta0, seed, spb, burn, keep)
    if name == "unchanged":
        got = ref.run(problem(np.ones(rows.size, bool)), K, alpha, beta0, seed, 0, burn, keep)
        got.update({"mu_U": np.zeros(K, np.float32), "Lam_U": np.eye(K, dtype=np.float32),
                    "mu_V": np.zeros(K, np.float32), "Lam_V": np.eye(K, dtype=np.float32)})
        for k in ("U", "V"):
            got[f"{k}_mean"] = got[k]
            got[f"{k}_samples"] = np.repeat(got[k][None], want[f"{k}_samples"].shape[0], 0)
        prob = ref.Problem(rows, cols, vals, cfg["num_users"], cfg["num_movies"],
                           cfg["test_fraction"], seed)
        r, c, t = prob.test
        p = np.clip(np.sum(got["U"][r] * got["V"][c], -1) + prob.mean, prob.lo, prob.hi)
        e = float(np.sqrt(np.mean((p - t) ** 2)))
        got["rmse"] = np.full_like(want["rmse"], e)
        return got
    if name == "altered":
        got = {k: np.array(v) for k, v in want.items()}
        got["U"][0] += 1.0
        got["U_samples"][-1][0] += 1.0
        got["U_mean"][0] += 1.0 / max(1, spb - burn)
        return got
    raise ValueError(name)


def serve_readings(cell: dict, seed: int, names: list, ref, seconds: float = 10.0) -> list:
    """Stand-ins for a serving cell: the reference's answers to the sampled
    requests, computed at ``high`` (control) or with one answer altered."""
    import tempfile

    serve = harness.load_module(os.path.join(BENCH, "kinds", "serve.py"), "kind_serve")
    cfg, traffic = cell["config_data"], cell["traffic_data"]
    with tempfile.TemporaryDirectory() as work:
        meta, arrays = serve.make_artifact(cfg, traffic, seed, os.path.join(work, "a"))
    p = serve.plan(cfg, traffic, cell, seed, seconds)
    idx = np.random.default_rng([seed, 3]).choice(
        len(p["due"]), min(traffic["check_requests"], len(p["due"])), replace=False)
    U, V, Us, Vs = (arrays[k] for k in ("U_mean", "V_mean", "U_samples", "V_samples"))
    mean, lo, hi = meta.mean_rating, meta.min_rating, meta.max_rating
    out = []
    for name in names:
        prec = "high" if name == "control" else "highest"
        answers = [None] * len(p["due"])
        for i in idx:
            req = p["requests"][i]
            if "rows" in req:
                r, c = np.asarray(req["rows"]), np.asarray(req["cols"])
                a = {"predictions": ref.serve_predict(U, V, r, c, mean, lo, hi, prec).tolist()}
                if req["std"]:
                    a["std"] = ref.serve_std(Us, Vs, r, c, mean, lo, hi, prec).tolist()
            else:
                sc = ref.serve_scores(U, V, req["user"], mean, lo, hi, prec)
                ids = np.argsort(-sc, kind="stable")[: req["k"]]
                a = {"items": ids.tolist(), "scores": sc[ids].tolist()}
            answers[i] = a
        if name == "altered":
            i = next(i for i in idx if "rows" in p["requests"][i])
            answers[i]["predictions"][0] += 0.5
        elif name != "control":
            raise ValueError(f"no stand-in {name!r} for a serving cell")
        out.append((name, serve.check(meta, arrays, p["requests"], answers, idx, ref)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--standins", default="control")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.device_check(cell["chips"])
    cfg, traffic = cell["config_data"], cell["traffic_data"]
    ref = harness.load_module(os.path.join(BENCH, "reference", f"{cfg['model']}.py"), "ref")
    kind = harness.load_module(os.path.join(BENCH, "kinds", f"{cell['kind']}.py"), "kind")
    names = args.standins.split(",")
    worst = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        if cell["kind"] == "serve":
            for name, numbers in serve_readings(cell, seed, names, ref):
                print(json.dumps({"standin": name, "seed": seed, **numbers}), flush=True)
                for k, v in numbers.items():
                    worst.setdefault(name, {}).setdefault(k, []).append(v)
            continue
        rows, cols, vals = synth.ratings(cfg, seed, harness.log)
        t = time.perf_counter()
        prob = ref.Problem(rows, cols, vals, cfg["num_users"], cfg["num_movies"],
                           cfg["test_fraction"], cfg["run_seed"])
        want = ref.run(prob, cfg["K"], cfg["alpha"], cfg["beta0"], cfg["run_seed"],
                       traffic["sweeps_per_block"], traffic["burn_in"],
                       traffic["keep_factor_samples"])
        harness.log(f"reference: seed {seed} {time.perf_counter() - t:.3f} s")
        for name in names:
            got = stand_in(name, ref, cfg, traffic, rows, cols, vals, want)
            numbers = kind.compare(got, want)
            print(json.dumps({"standin": name, "seed": seed, **numbers}), flush=True)
            for k, v in numbers.items():
                worst.setdefault(name, {}).setdefault(k, []).append(v)
    print(json.dumps({"summary": {n: {k: min(v) for k, v in d.items()}
                                  for n, d in worst.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
