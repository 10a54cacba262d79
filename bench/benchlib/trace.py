"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler.trace`` wrote into a
plain dict -- device op events per device, host events per thread --
which ``save``/``read`` keep as JSON (the tests' recorded trace is one).
``reduce`` then works on that dict alone:

* the window is the host span ``bench.window`` that the benchmark opens
  around its measured loop;
* a device is busy while any op of its ``XLA Ops`` line runs; busy time is
  the union of those intervals inside the window, idle share is
  ``1 - busy / window``;
* a collective op's *exposed* time is the part of it during which no
  non-collective op runs on that device;
* ``breakdown`` lists the ops that took most device time (mean over
  devices, by the name the trace gives them) and the longest idle gaps,
  each named by the innermost host event that covers the whole gap.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

OPS_LINE = "XLA Ops"
DEVICE = re.compile(r"^/device:[A-Z]+:\d+$")  # one plane per chip, not its non-core planes
WINDOW = "bench.window"
COLLECTIVE = re.compile(
    r"collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all|ppermute|"
    r"send|recv", re.IGNORECASE)


def load(trace_dir: str) -> dict:
    """Device op events and host events of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [e.name, int(e.start_ns), int(e.duration_ns)] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([line.name, e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events)
    return {"devices": devices, "host": host,
            "planes": [[p.name, [line.name for line in p.lines]] for p in data.planes]}


def save(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def read(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals: list) -> int:
    return sum(e - s for s, e in intervals)


def _minus(a: list, b: list) -> list:
    """Intervals of union ``a`` not covered by union ``b`` (both sorted)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def window(trace: dict) -> tuple[int, int]:
    spans = [(s, s + d) for _, name, s, d in trace["host"] if name == WINDOW]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW!r} host span")
    return max(spans, key=lambda x: x[1] - x[0])


def _gap_label(host: list, s: int, e: int) -> str:
    best = None
    for line, name, hs, hd in host:
        if hs <= s and hs + hd >= e and name != WINDOW and (best is None or hd < best[1]):
            best = (f"{line}: {name}", hd)
    return best[0] if best else "no host span"


def reduce(trace: dict, top: int = 10) -> dict:
    """Busy and idle time, exposed collectives and the breakdown, per window."""
    w0, w1 = window(trace)
    win_ns = w1 - w0
    per_dev, op_time, gaps = {}, {}, []
    for dev, events in sorted(trace["devices"].items()):
        clipped = [(n, max(s, w0), min(s + d, w1)) for n, s, d in events
                   if s < w1 and s + d > w0]
        busy = _union([[s, e] for _, s, e in clipped])
        coll = _union([[s, e] for n, s, e in clipped if COLLECTIVE.search(n)])
        comp = _union([[s, e] for n, s, e in clipped if not COLLECTIVE.search(n)])
        per_dev[dev] = {
            "busy_s": _length(busy) / 1e9,
            "collective_s": _length(coll) / 1e9,
            "exposed_collective_s": _length(_minus(coll, comp)) / 1e9,
        }
        for n, s, e in clipped:
            op_time[n] = op_time.get(n, 0) + (e - s)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps.extend((e - s, s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s)
    n_dev = max(len(per_dev), 1)
    mean = lambda k: sum(d[k] for d in per_dev.values()) / n_dev  # noqa: E731
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(gaps, reverse=True)[:top]
    return {
        "devices": len(per_dev),
        "window_s": win_ns / 1e9,
        "busy_s": mean("busy_s"),
        "collective_s": mean("collective_s"),
        "exposed_collective_s": mean("exposed_collective_s"),
        "per_device": per_dev,
        "breakdown": {
            "device_ops": [[n, t / 1e9 / n_dev] for n, t in ops],
            "idle_gaps": [[_gap_label(trace["host"], s, e), d / 1e9] for d, s, e in gaps],
        },
    }
