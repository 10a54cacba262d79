"""Shared by the benchmark's tests: the harness loaded by path, and tiny cells."""
from __future__ import annotations

import importlib.util
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


def load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


harness = load("run.py", "bench_run")

TINY = {"num_users": 3000, "num_movies": 400, "nnz": 30000}


def tiny_cell(name: str, **sizes) -> dict:
    """The cell ``name`` as its files state it, at a size a CPU test holds."""
    cell = harness.load_cell(name)
    cfg = dict(cell["config_data"], **(sizes or TINY))
    cfg["name"] = f"test_{cfg['name']}_{cfg['num_users']}x{cfg['num_movies']}_{cfg['nnz']}"
    if cfg.get("min_user_ratings", 0) * cfg["num_users"] > cfg["nnz"]:
        cfg.pop("min_user_ratings")
    cell["config_data"] = cfg
    return cell
