#!/usr/bin/env bash
# Tier-1 test entry point.
#
# Runs on the CPU (JAX_PLATFORMS=cpu, also on a host with a TPU: the chip
# is reached by `python chip_smoke.py [--chips 4]`, not by the tests).
# Forces 8 host (CPU) devices so the distributed/ring code paths exercise a
# real multi-device mesh, and puts src/ on PYTHONPATH. Subprocess-based
# multidevice tests override the device count themselves
# (tests/conftest.py strips and re-appends the flag).
#
#   scripts/test.sh                     # full tier-1 suite
#   scripts/test.sh tests/test_engine.py -k parity
#   scripts/test.sh -m "not slow"       # skip the subprocess/multidevice tests
#   scripts/test.sh --bench-smoke       # + 2-sweep ring_async CLI smoke run
#   scripts/test.sh --autotune-smoke    # + fig2 autotune driver (2 shapes,
#                                       #   tiny budget) + JSON schema check
#                                       #   + use_pallas shim warns-once check
#   scripts/test.sh --serve-smoke       # + train 2 sweeps -> export artifact
#                                       #   -> serve one-shot + JSONL queries
#                                       #   -> serve_latency --smoke + schema
#   scripts/test.sh --block-smoke       # + 2-block ring run (8 sweeps,
#                                       #   sweeps_per_block=4) -> export ->
#                                       #   serve one-shot; sweep_throughput
#                                       #   --smoke + JSON schema check
#   scripts/test.sh --server-smoke      # + train -> export -> persistent
#                                       #   serve_server -> concurrent client
#                                       #   burst -> hot-swap re-export ->
#                                       #   clean shutdown; serve_load --smoke
#                                       #   + JSON schema check
#   scripts/test.sh --merge-smoke       # + 2-partition posterior_merge CLI
#                                       #   run -> export -> serve one-shot;
#                                       #   fig_merge_comm --smoke + JSON
#                                       #   schema check
#   scripts/test.sh --overlap-smoke     # + depth-2 pipelined CLI run with a
#                                       #   mid-run checkpoint -> resume ->
#                                       #   export; sweep_throughput --smoke
#                                       #   (overlap + save-latency columns)
#                                       #   + JSON schema check
#   scripts/test.sh --multiproc-smoke   # + 2-process gang (DESIGN.md §14):
#                                       #   ring run -> checkpoint -> restart
#                                       #   at 1 process -> export -> serve
#                                       #   one-shot; fig4_scaling --smoke
#                                       #   + JSON schema check
#
# Benchmark smoke runs write to temp --out paths (never the committed
# experiments/bench JSONs); each stanza schema-checks its temp output via
# --path AND re-checks the committed artifact, which must carry
# "smoke": false (scripts/check_bench_schema.py).
#
# Always runs the public-API docstring-coverage gate
# (scripts/check_docstrings.py) before pytest.
set -euo pipefail
cd "$(dirname "$0")/.."

if [[ "${XLA_FLAGS:-}" != *xla_force_host_platform_device_count* ]]; then
  export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"
fi
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS=cpu

BENCH_SMOKE=0
AUTOTUNE_SMOKE=0
SERVE_SMOKE=0
BLOCK_SMOKE=0
SERVER_SMOKE=0
MERGE_SMOKE=0
OVERLAP_SMOKE=0
MULTIPROC_SMOKE=0
ARGS=()
for a in "$@"; do
  if [[ "$a" == "--bench-smoke" ]]; then
    BENCH_SMOKE=1
  elif [[ "$a" == "--autotune-smoke" ]]; then
    AUTOTUNE_SMOKE=1
  elif [[ "$a" == "--serve-smoke" ]]; then
    SERVE_SMOKE=1
  elif [[ "$a" == "--block-smoke" ]]; then
    BLOCK_SMOKE=1
  elif [[ "$a" == "--server-smoke" ]]; then
    SERVER_SMOKE=1
  elif [[ "$a" == "--merge-smoke" ]]; then
    MERGE_SMOKE=1
  elif [[ "$a" == "--overlap-smoke" ]]; then
    OVERLAP_SMOKE=1
  elif [[ "$a" == "--multiproc-smoke" ]]; then
    MULTIPROC_SMOKE=1
  else
    ARGS+=("$a")
  fi
done

python scripts/check_docstrings.py

if [[ "$BENCH_SMOKE" == 1 ]]; then
  echo "== bench smoke: 2-sweep ring_async on synthetic =="
  python -m repro.launch.bpmf --backend ring_async --dataset synthetic \
    --pipeline-depth 2 --sweeps 2 --burn-in 1 --K 4 \
    --users 80 --movies 40 --nnz 800
fi

if [[ "$AUTOTUNE_SMOKE" == 1 ]]; then
  echo "== autotune smoke: fig2 driver, 2 shapes, tiny budget =="
  FIG2_TMP="$(mktemp -d)"
  python -m benchmarks.fig2_item_update --smoke --out "$FIG2_TMP/fig2_item_update.json"
  python scripts/check_bench_schema.py fig2_item_update --path "$FIG2_TMP/fig2_item_update.json"
  python scripts/check_bench_schema.py fig2_item_update
  rm -rf "$FIG2_TMP"
  echo "== use_pallas deprecation shim: must warn exactly once =="
  # intentionally a fresh process (unlike the pytest variant, which has to
  # monkeypatch the warn-once flag): checks the real once-per-process gate
  python - <<'PY'
import warnings
from repro.bpmf.config import BackendConfig
with warnings.catch_warnings(record=True) as w:
    warnings.simplefilter("always")
    a = BackendConfig(use_pallas=True)
    b = BackendConfig(use_pallas=False)
dep = [x for x in w if issubclass(x.category, DeprecationWarning)
       and "use_pallas" in str(x.message)]
assert len(dep) == 1, f"expected exactly 1 use_pallas warning, got {len(dep)}"
assert a.gram_impl == "pallas" and b.gram_impl == "xla", (a.gram_impl, b.gram_impl)
print("use_pallas shim OK: warned once, mapped to gram_impl")
PY
fi

if [[ "$SERVE_SMOKE" == 1 ]]; then
  echo "== serve smoke: 2-sweep train -> export -> serve queries =="
  SERVE_TMP="$(mktemp -d)"
  ART="$SERVE_TMP/artifact"
  python -m repro.launch.bpmf --backend sequential --dataset synthetic \
    --sweeps 2 --burn-in 1 --K 4 --users 80 --movies 40 --nnz 800 \
    --export-artifact "$ART"
  python -m repro.launch.serve --artifact "$ART" --rows 0,1,2 --cols 0,1,2 --std
  python -m repro.launch.serve --artifact "$ART" --user 0 --top-k 5
  printf '{"rows": [3, 4], "cols": [5, 6]}\n{"user": 1, "k": 3}\n' | \
    python -m repro.launch.serve --artifact "$ART" --jsonl
  echo "== serve latency smoke + schema check =="
  python -m benchmarks.serve_latency --smoke --artifact "$ART" \
    --out "$SERVE_TMP/serve_latency.json"
  python scripts/check_bench_schema.py serve_latency --path "$SERVE_TMP/serve_latency.json"
  python scripts/check_bench_schema.py serve_latency
  rm -rf "$SERVE_TMP"
fi

if [[ "$BLOCK_SMOKE" == 1 ]]; then
  echo "== block smoke: 2-block ring run -> export -> serve one-shot =="
  BLOCK_TMP="$(mktemp -d)"
  BART="$BLOCK_TMP/artifact"
  python -m repro.launch.bpmf --backend ring --dataset synthetic \
    --sweeps 8 --sweeps-per-block 4 --burn-in 2 --K 4 \
    --users 80 --movies 40 --nnz 800 \
    --export-artifact "$BART"
  python -m repro.launch.serve --artifact "$BART" --rows 0,1,2 --cols 0,1,2 --std
  echo "== sweep_throughput smoke + schema check =="
  python -m benchmarks.sweep_throughput --smoke --out "$BLOCK_TMP/sweep_throughput.json"
  python scripts/check_bench_schema.py sweep_throughput --path "$BLOCK_TMP/sweep_throughput.json"
  python scripts/check_bench_schema.py sweep_throughput
  rm -rf "$BLOCK_TMP"
fi

if [[ "$SERVER_SMOKE" == 1 ]]; then
  echo "== server smoke: train -> export -> persistent server =="
  SRV_TMP="$(mktemp -d)"
  SART="$SRV_TMP/artifact"
  python -m repro.launch.bpmf --backend sequential --dataset synthetic \
    --sweeps 2 --burn-in 1 --K 4 --users 80 --movies 40 --nnz 800 \
    --export-artifact "$SART"
  python -m repro.launch.serve_server --artifact "$SART" --port 0 \
    --poll-interval 0.2 >"$SRV_TMP/server.log" 2>&1 &
  SRV_PID=$!
  trap 'kill "$SRV_PID" 2>/dev/null || true' EXIT
  ADDR=""
  for _ in $(seq 150); do
    ADDR="$(sed -n 's,.*http://\([0-9.]*:[0-9]*\).*,\1,p' "$SRV_TMP/server.log" | head -1)"
    [[ -n "$ADDR" ]] && break
    kill -0 "$SRV_PID" 2>/dev/null || break
    sleep 0.2
  done
  if [[ -z "$ADDR" ]]; then
    echo "server did not start:"; cat "$SRV_TMP/server.log"; exit 1
  fi
  echo "== concurrent client burst against $ADDR =="
  python - "$ADDR" <<'PY'
import sys, threading
import numpy as np
from repro.serve import ServeClient

addr = sys.argv[1]
errors = []

def worker(i):
    c = ServeClient(addr)
    rng = np.random.default_rng(i)
    for _ in range(25):
        r = c.request({"rows": rng.integers(0, 80, 3).tolist(),
                       "cols": rng.integers(0, 40, 3).tolist()})
        if "error" in r or len(r.get("predictions", [])) != 3:
            errors.append(r)
        r = c.request({"user": int(rng.integers(0, 80)), "k": 5})
        if "error" in r or len(r.get("items", [])) != 5:
            errors.append(r)
    c.close()

threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
for t in threads: t.start()
for t in threads: t.join()
assert not errors, errors[:3]
st = ServeClient(addr).stats()["batcher"]
print(f"burst OK: {st['requests']} requests in {st['cycles']} cycles "
      f"(occupancy {st['occupancy']:.2f})")
PY
  python -m repro.launch.serve --server "$ADDR" --user 0 --top-k 5
  echo "== hot-swap: re-export into the live artifact dir =="
  python -m repro.launch.bpmf --backend sequential --dataset synthetic \
    --sweeps 4 --burn-in 1 --K 4 --users 80 --movies 40 --nnz 800 \
    --export-artifact "$SART"
  python - "$ADDR" <<'PY'
import sys, threading, time
import numpy as np
from repro.serve import ServeClient

addr = sys.argv[1]
stop = threading.Event()
errors = []

def hammer(i):
    c = ServeClient(addr)
    rng = np.random.default_rng(i)
    while not stop.is_set():
        r = c.request({"user": int(rng.integers(0, 80)), "k": 5})
        if "error" in r:
            errors.append(r)
    c.close()

threads = [threading.Thread(target=hammer, args=(i,)) for i in range(4)]
for t in threads: t.start()
probe = ServeClient(addr)
deadline = time.time() + 60
h = probe.health()
while h["generation"] < 1 and time.time() < deadline:
    time.sleep(0.2)
    h = probe.health()
stop.set()
for t in threads: t.join()
assert h["generation"] >= 1, f"no hot-swap observed: {h}"
assert h["swap_failures"] == 0, h
assert not errors, errors[:3]
print(f"hot-swap OK: generation {h['generation']}, "
      "zero request errors under concurrent load")
PY
  kill -TERM "$SRV_PID"
  wait "$SRV_PID"
  trap - EXIT
  grep -q "server stopped cleanly" "$SRV_TMP/server.log"
  echo "clean shutdown OK"
  echo "== serve_load smoke + schema check =="
  python -m benchmarks.serve_latency --smoke --load --out "$SRV_TMP/serve_load.json"
  python scripts/check_bench_schema.py serve_load --path "$SRV_TMP/serve_load.json"
  rm -rf "$SRV_TMP"
fi

if [[ "$MERGE_SMOKE" == 1 ]]; then
  echo "== merge smoke: 2-partition posterior_merge run -> export -> serve =="
  MERGE_TMP="$(mktemp -d)"
  MART="$MERGE_TMP/artifact"
  python -m repro.launch.bpmf --backend posterior_merge --num-partitions 2 \
    --dataset synthetic --sweeps 6 --sweeps-per-block 3 --burn-in 2 --K 4 \
    --users 80 --movies 40 --nnz 800 \
    --export-artifact "$MART"
  python -m repro.launch.serve --artifact "$MART" --rows 0,1,2 --cols 0,1,2 --std
  echo "== fig_merge_comm smoke + schema check =="
  python -m benchmarks.fig_merge_comm --smoke --out "$MERGE_TMP/fig_merge_comm.json"
  python scripts/check_bench_schema.py fig_merge_comm --path "$MERGE_TMP/fig_merge_comm.json"
  python scripts/check_bench_schema.py fig_merge_comm
  rm -rf "$MERGE_TMP"
fi

if [[ "$OVERLAP_SMOKE" == 1 ]]; then
  echo "== overlap smoke: depth-2 pipelined run -> checkpoint -> resume -> export =="
  OV_TMP="$(mktemp -d)"
  OART="$OV_TMP/artifact"
  python -m repro.launch.bpmf --backend ring --dataset synthetic \
    --sweeps 8 --sweeps-per-block 2 --pipeline-blocks 2 --burn-in 2 --K 4 \
    --users 80 --movies 40 --nnz 800 \
    --checkpoint-dir "$OV_TMP/ckpt" --checkpoint-every 3
  # a mid-run checkpoint exists (sweep 6: auto-save cadence held under the
  # pipeline); resume it with the overlapped loop, finish, export
  test -d "$OV_TMP/ckpt/step_00000006"
  python -m repro.launch.bpmf --backend ring --dataset synthetic \
    --sweeps 8 --sweeps-per-block 2 --pipeline-blocks 2 --burn-in 2 --K 4 \
    --users 80 --movies 40 --nnz 800 \
    --checkpoint-dir "$OV_TMP/ckpt" --resume \
    --export-artifact "$OART"
  python -m repro.launch.serve --artifact "$OART" --rows 0,1,2 --cols 0,1,2
  # donation fallback path stays runnable
  python -m repro.launch.bpmf --backend sequential --dataset synthetic \
    --sweeps 2 --burn-in 1 --K 4 --users 80 --movies 40 --nnz 800 \
    --pipeline-blocks 2 --donate-blocks off --sync-checkpoint-writes
  echo "== sweep_throughput smoke (overlap + save-latency columns) + schema check =="
  python -m benchmarks.sweep_throughput --smoke --out "$OV_TMP/sweep_throughput.json"
  python scripts/check_bench_schema.py sweep_throughput --path "$OV_TMP/sweep_throughput.json"
  python scripts/check_bench_schema.py sweep_throughput
  rm -rf "$OV_TMP"
fi

if [[ "$MULTIPROC_SMOKE" == 1 ]]; then
  echo "== multiproc smoke: 2-process ring gang -> ckpt -> 1-process restart -> serve =="
  MP_TMP="$(mktemp -d)"
  MPART="$MP_TMP/artifact"
  python scripts/launch_multiproc.py \
    --num-processes 2 --devices-per-process 4 --timeout 600 -- \
    --backend ring --dataset synthetic --sweeps 4 --sweeps-per-block 2 \
    --burn-in 2 --K 4 --users 80 --movies 40 --nnz 800 \
    --checkpoint-dir "$MP_TMP/ckpt" --checkpoint-every 2
  test -d "$MP_TMP/ckpt/step_00000004"
  # restart the same checkpoint at a different process count (same global
  # device total) and continue to the end, then export and serve
  python scripts/launch_multiproc.py \
    --num-processes 1 --devices-per-process 8 --timeout 600 -- \
    --backend ring --dataset synthetic --sweeps 8 --sweeps-per-block 2 \
    --burn-in 2 --K 4 --users 80 --movies 40 --nnz 800 \
    --checkpoint-dir "$MP_TMP/ckpt" --resume \
    --export-artifact "$MPART"
  python -m repro.launch.serve --artifact "$MPART" --rows 0,1,2 --cols 0,1,2
  echo "== fig4_scaling smoke + schema check =="
  python -m benchmarks.fig4_scaling --smoke --out "$MP_TMP/fig4_scaling.json"
  python scripts/check_bench_schema.py fig4_scaling --path "$MP_TMP/fig4_scaling.json"
  python scripts/check_bench_schema.py fig4_scaling
  rm -rf "$MP_TMP"
fi

exec python -m pytest -x -q ${ARGS[@]+"${ARGS[@]}"}
