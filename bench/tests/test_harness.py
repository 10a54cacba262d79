"""The harness itself: cells found by name, the file's rules, no TPU refused."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from helpers import BENCH, ROOT, harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_dropped_workload_file_is_found(tmp_path, spec):
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    with open(bench / "workloads" / "new_cell.json", "w") as f:
        json.dump({"config": "chembl_k32", "traffic": "train", "kind": "train",
                   "chips": 1, "limits": {"draw_gap": 1.0}}, f)
    cell = harness.load_cell("new_cell", str(bench))
    assert cell["config_data"]["name"] == "chembl_k32"
    assert cell["traffic_data"]["kind"] == "train"
    added = dict(spec, per_layer=spec["per_layer"] + [
        {"name": "new_metric", "unit": "%", "better": "higher", "source": "device_trace",
         "layer": "run loop", "moves": "ratings_per_s", "workloads": ["new_cell"]}])
    assert [m["name"] for m in harness.cell_metrics(added, "new_cell", True)] == ["new_metric"]


def test_every_cell_names_existing_files(spec):
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        assert os.path.exists(os.path.join(BENCH, "kinds", f"{cell['kind']}.py"))
        assert cell["traffic_data"]["kind"] == cell["kind"]
        model = cell["config_data"]["model"]
        assert os.path.exists(os.path.join(BENCH, "reference", f"{model}.py"))
    for c in spec["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["name"] == c["name"]
    for m in spec["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics", f"{m['name']}.py"))


def test_names_and_units_use_allowed_characters(spec):
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in spec[k]]
    names += [w[k] for w in spec["workloads"] for k in ("config", "traffic")]
    names += [r for c in spec["configs"] for r in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for k in ("end_to_end", "per_layer") for m in spec[k])
    for k in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len({x["name"] for x in spec[k]}) == len(spec[k])


def test_every_cell_reports_setup_another_end_to_end_and_a_layer(spec):
    for w in spec["workloads"]:
        e2e = {m["name"] for m in harness.cell_metrics(spec, w["name"], False)}
        assert "setup_s" in e2e and len(e2e) >= 2, (w["name"], e2e)
        assert harness.cell_metrics(spec, w["name"], True), w["name"]


def test_run_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload",
                        "chembl_k32.train", "--seed", "2147483659", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True, env=env,
                       cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
