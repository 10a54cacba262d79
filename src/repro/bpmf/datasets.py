"""Dataset registry behind ``repro.bpmf.load_dataset(name, **kw)``.

Loaders return a :class:`repro.data.sparse.RatingsCOO`; the engine owns the
train/test split and layout so every backend sees the identical split.
New workloads register here instead of adding another ad-hoc script::

    @register_dataset("my-data")
    def _load(path=None):
        return RatingsCOO(...)
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np

from repro.data.movielens import load_chembl, load_movielens
from repro.data.sparse import RatingsCOO
from repro.data.synthetic import SyntheticSpec, synthetic_ratings

DATASETS: dict[str, Callable[..., RatingsCOO]] = {}


def register_dataset(name: str) -> Callable[[Callable[..., RatingsCOO]], Callable[..., RatingsCOO]]:
    """Function decorator adding a loader under ``name`` (last wins).

    Args:
        name: Registry key used by :func:`load_dataset` and the CLI's
            ``--dataset`` flag.

    Returns:
        The decorator; it registers the loader and returns it unchanged.
    """

    def deco(fn: Callable[..., RatingsCOO]) -> Callable[..., RatingsCOO]:
        DATASETS[name] = fn
        return fn

    return deco


def load_dataset(name: str, **kw) -> RatingsCOO:
    """Load a registered dataset by name.

    Args:
        name: Registry key (see :func:`available_datasets`).
        **kw: Forwarded to the loader (e.g. ``path=``, or the synthetic
            generator's ``num_users`` / ``num_movies`` / ``nnz``).

    Returns:
        The raw ratings; the engine owns the train/test split.

    Raises:
        ValueError: If ``name`` is not registered.
    """
    if name not in DATASETS:
        raise ValueError(f"unknown dataset {name!r}; available: {sorted(DATASETS)}")
    return DATASETS[name](**kw)


def available_datasets() -> list[str]:
    """Sorted registry names (``["chembl", "movielens", "synthetic", ...]``)."""
    return sorted(DATASETS)


@register_dataset("synthetic")
def _synthetic(
    num_users: int = 400,
    num_movies: int = 300,
    nnz: int = 12_000,
    true_rank: int = 8,
    noise_std: float = 0.5,
    discretize: bool = False,
    seed: int = 0,
    num_times: int = 0,
) -> RatingsCOO:
    """Low-rank + noise ratings with MovieLens-shaped degree skew; with
    ``num_times`` > 0, each rating gets a uniform time bin."""
    spec = SyntheticSpec(
        num_users=num_users,
        num_movies=num_movies,
        nnz=nnz,
        true_rank=true_rank,
        noise_std=noise_std,
        discretize=discretize,
        seed=seed,
    )
    coo, _ = synthetic_ratings(spec)
    if num_times:
        times = np.random.default_rng([seed, 1]).integers(0, num_times, coo.nnz)
        coo = dataclasses.replace(coo, times=times.astype(np.int32), num_times=num_times)
    return coo


@register_dataset("movielens")
def _movielens(path: str | None = None, variant: str = "ml-100k",
               times: bool = False) -> RatingsCOO:
    """Real ml-20m/ml-100k files when ``path`` exists, else synthetic stand-in;
    ``times`` keeps each rating's calendar month (a real file only)."""
    return load_movielens(path, variant, times=times)


@register_dataset("chembl")
def _chembl(path: str | None = None) -> RatingsCOO:
    """ChEMBL IC50 compound x target subset (paper §V workload)."""
    return load_chembl(path)
