"""The plain reference of a configuration, and gaps from its answers."""
from __future__ import annotations

import importlib.util
import os

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_reference(cfg: dict):
    """``bench/reference/<model>.py`` of the configuration, as a module."""
    path = os.path.join(BENCH, "reference", f"{cfg['model']}.py")
    spec = importlib.util.spec_from_file_location(f"reference_{cfg['model']}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def gap(got, want) -> float:
    """Largest |got - want| over the root-mean-square of ``want``; inf when
    ``got`` is missing, misshapen or not finite."""
    if got is None:
        return float("inf")
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    scale = float(np.sqrt(np.mean(want * want))) or 1.0
    return float(np.max(np.abs(got - want))) / scale if got.size else 0.0


def rms_gap(got, want) -> float:
    """Root-mean-square of ``got - want`` over that of ``want``; inf when
    ``got`` is missing, misshapen or not finite."""
    if got is None:
        return float("inf")
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    scale = float(np.sqrt(np.mean(want * want))) or 1.0
    return float(np.sqrt(np.mean((got - want) ** 2))) / scale if got.size else 0.0


def abs_gap(got, want) -> float:
    """Largest |got - want|; inf when ``got`` is misshapen or not finite."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want))) if got.size else 0.0


def rel_gap(got, want) -> float:
    """Largest |got - want| / |want| entry by entry."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1e-30)))
