"""Device time of a traced window by the program's named layers.

The program names the layers of its sweep with ``jax.named_scope``
(``SCOPES``, the program's ``repro.core.types.SWEEP_SCOPES``), and XLA
keeps each op's scope path in the ``op_name`` metadata of its HLO
instruction. The profiler does not carry that path to the device: on a
TPU v5e an event of the ``XLA Ops`` line is named by its instruction's
HLO text (``%fusion.12 = f32[...] fusion(...), ...``) and has no stat but
``device_offset_ps``, ``device_duration_ps`` and ``Time Scale
Multiplier`` (the recorded trace ``tests/data/trace_v5e_chembl.json.gz``).
So the path is looked up by instruction name in the compiled program's
HLO text (``compiled.as_text()``), whose instruction names the trace
repeats. The run loop's host spans (``bpmf.dispatch``, ``bpmf.drain``,
``bpmf.prepare``) are on the host plane, on the same clock.

``with_scopes`` adds to the dict ``trace.load`` returns, per device, the
scope path of every op event. ``reduce`` works on that dict alone, inside
the window ``trace.window`` finds, mean over devices:

* ``layers``: each busy nanosecond goes to the innermost op event covering
  it, then to the innermost of the ``SCOPES`` in that op's scope path;
  busy time under none of them is ``unattributed``, so ``layers`` plus
  ``unattributed`` is the busy time ``trace.reduce`` gives; the part of it
  whose op has no scope path at all is ``no_scope_path``;
* ``idle_spans``: each idle nanosecond goes to the innermost ``bpmf.*``
  host span covering it, and to ``none`` where there is none.
"""
from __future__ import annotations

import heapq
import re

from benchlib import trace as tr

SCOPES = ("bpmf_gram", "posterior_draw", "hyper_draw", "sweep_predict", "ring_step")
HOST_SPAN = "bpmf."
NONE = "none"
_INSTRUCTION = re.compile(r'^\s*(?:ROOT )?%?([\w.-]+) = .*?op_name="([^"]*)"', re.M)


def op_names(hlo_text: str) -> dict[str, str]:
    """Instruction name -> ``op_name`` of every instruction of a compiled
    program's HLO text that has one."""
    return dict(_INSTRUCTION.findall(hlo_text))


def instruction(event_name: str) -> str:
    """The instruction name an ``XLA Ops`` event is named by."""
    return event_name.split(" ", 1)[0].lstrip("%")


def with_scopes(trace: dict, names: dict[str, str]) -> dict:
    """``trace`` (as ``trace.load`` returns it) plus ``scopes``: per device,
    the scope path of each op event in ``names``, or ``""``."""
    return dict(trace, scopes={
        dev: [names.get(instruction(n), "") for n, _, _ in evs]
        for dev, evs in trace["devices"].items()})


def layer_of(path: str) -> str | None:
    """The innermost of ``SCOPES`` in a scope path, or None."""
    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return None


def _innermost(intervals: list) -> list:
    """``[a, b, label]`` segments, each labelled by the innermost of the
    ``(start, end, label)`` intervals covering it: the one that started
    last (of two that started together, the one that ends first)."""
    ivs = sorted(intervals, key=lambda iv: (iv[0], iv[1]))
    cuts = sorted({x for s, e, _ in ivs for x in (s, e)})
    out, heap, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(ivs) and ivs[i][0] <= a:
            s, e, lab = ivs[i]
            heapq.heappush(heap, (-s, e, i, lab))
            i += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if heap:
            lab = heap[0][3]
            if out and out[-1][1] == a and out[-1][2] == lab:
                out[-1][1] = b
            else:
                out.append([a, b, lab])
    return out


def _overlap(segments: list, spans: list) -> list:
    """The parts of labelled ``segments`` inside the sorted union ``spans``."""
    out, j = [], 0
    for a, b, lab in segments:
        while j < len(spans) and spans[j][1] <= a:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < b:
            lo, hi = max(a, spans[k][0]), min(b, spans[k][1])
            if hi > lo:
                out.append([lo, hi, lab])
            k += 1
    return out


def _add(acc: dict, segments: list) -> None:
    for a, b, lab in segments:
        acc[lab] = acc.get(lab, 0) + (b - a)


def reduce(trace: dict, top: int = 5) -> dict:
    """Device seconds per layer and idle seconds per host span, per window."""
    w0, w1 = tr.window(trace)
    spans = [(max(s, w0), min(s + d, w1), name) for _, name, s, d in trace["host"]
             if name.startswith(HOST_SPAN) and s < w1 and s + d > w0]
    span_segs = _innermost(spans)
    layer_ns, idle_ns, loose_ops, unmatched = {}, {}, {}, 0
    for dev, events in sorted(trace["devices"].items()):
        ops = [(max(s, w0), min(s + d, w1), (layer_of(p), n, p))
               for (n, s, d), p in zip(events, trace["scopes"][dev]) if s < w1 and s + d > w0]
        segs = _innermost(ops)
        _add(layer_ns, [[a, b, lab] for a, b, (lab, _, _) in segs])
        _add(loose_ops, [[a, b, n] for a, b, (lab, n, _) in segs if lab is None])
        unmatched += sum(b - a for a, b, (_, _, p) in segs if not p)
        idle = tr._minus([[w0, w1]], tr._union([[s, e] for s, e, _ in ops]))
        named = _overlap(span_segs, idle)
        _add(idle_ns, named)
        idle_ns[NONE] = idle_ns.get(NONE, 0) + tr._length(idle) - sum(b - a for a, b, _ in named)
    n_dev = max(len(trace["devices"]), 1)
    sec = lambda ns: ns / 1e9 / n_dev  # noqa: E731
    loose = sorted(loose_ops.items(), key=lambda kv: -kv[1])[:top]
    return {
        "layers": {name: sec(layer_ns.get(name, 0)) for name in SCOPES},
        "unattributed": sec(layer_ns.get(None, 0)),
        "unattributed_ops": [[n, sec(t)] for n, t in loose],
        "no_scope_path": sec(unmatched),
        "idle_spans": {k: sec(v) for k, v in sorted(idle_ns.items(), key=lambda kv: -kv[1])},
    }


def trim(trace: dict, names: dict[str, str], per_layer: int = 3,
         around_ns: int = 1_000_000) -> dict:
    """A small copy of ``trace.load``'s dict: the window span and every
    ``bpmf.*`` host span in it, the first ``per_layer`` ops of each layer in
    the window, and every op within ``around_ns`` of the first block
    boundary (the end of the first ``bpmf.drain`` in the window); with
    ``op_names``, the entries of ``names`` for the ops kept."""
    w0, w1 = tr.window(trace)
    host = [h for h in trace["host"] if h[1] == tr.WINDOW
            or (h[1].startswith(HOST_SPAN) and h[2] < w1 and h[2] + h[3] > w0)]
    drains = sorted(h[2] + h[3] for h in host if h[1] == "bpmf.drain" and h[2] >= w0)
    b0, b1 = (drains[0] - around_ns, drains[0] + around_ns) if drains else (w0, w0)
    devices, kept = {}, {}
    for dev, evs in trace["devices"].items():
        seen, keep = {}, []
        for n, s, d in evs:
            if not (s < w1 and s + d > w0):
                continue
            path = names.get(instruction(n), "")
            lab = layer_of(path)
            if (s < b1 and s + d > b0) or seen.get(lab, 0) < per_layer:
                seen[lab] = seen.get(lab, 0) + 1
                keep.append([n, s, d])
                kept[instruction(n)] = path
        devices[dev] = keep
    return {"devices": devices, "host": host, "op_names": kept}
