"""Pallas TPU kernels for the paper's compute hot-spots.

bpmf_gram: the gather + Gram accumulation inside the per-item conditional
update (the dominant FLOPs of BPMF, paper SII) — a per-bucket kernel and a
fused multi-bucket kernel that lowers a whole ring step to one
``pallas_call``; chol_draw: the per-item posterior draw (K x K Cholesky and
three triangular solves) with the items on the vector lanes. ops.py
dispatches between the Pallas kernels and the jnp/XLA paths; autotune.py
owns the measured per-shape decision and its persistent cache (DESIGN.md §8).
"""
from repro.kernels import autotune, ops, ref

__all__ = ["autotune", "ops", "ref"]
