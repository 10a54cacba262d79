"""Small shared utilities: pytree dataclasses, logging, timing, dtypes."""
from __future__ import annotations

import dataclasses
import functools
import logging
import time
from typing import Any, Callable, Iterator, TypeVar

import jax
import jax.numpy as jnp
import numpy as np

T = TypeVar("T")

logger = logging.getLogger("repro")
if not logger.handlers:
    _h = logging.StreamHandler()
    _h.setFormatter(logging.Formatter("[%(asctime)s %(levelname)s] %(message)s", "%H:%M:%S"))
    logger.addHandler(_h)
    logger.setLevel(logging.INFO)


def pytree_dataclass(cls: type[T]) -> type[T]:
    """A frozen dataclass registered as a JAX pytree.

    Fields annotated with ``static=True`` metadata are treated as aux data
    (hashable, not traced).
    """
    cls = dataclasses.dataclass(frozen=True)(cls)
    data_fields = [f.name for f in dataclasses.fields(cls) if not f.metadata.get("static", False)]
    meta_fields = [f.name for f in dataclasses.fields(cls) if f.metadata.get("static", False)]
    return jax.tree_util.register_dataclass(cls, data_fields=data_fields, meta_fields=meta_fields)


def static_field(**kwargs: Any) -> Any:
    """Dataclass field treated as static (aux) data in the pytree."""
    return dataclasses.field(metadata={"static": True}, **kwargs)


def tree_size_bytes(tree: Any) -> int:
    """Total size in bytes of all array leaves."""
    return sum(
        np.prod(x.shape) * np.dtype(x.dtype).itemsize
        for x in jax.tree_util.tree_leaves(tree)
        if hasattr(x, "shape")
    )


def tree_num_params(tree: Any) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree) if hasattr(x, "shape"))


def block_until_ready(tree: Any) -> Any:
    return jax.block_until_ready(tree)


class Timer:
    """Context-manager wall timer."""

    def __init__(self, name: str = "", log: bool = False):
        self.name, self.log = name, log
        self.elapsed = 0.0

    def __enter__(self) -> "Timer":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.elapsed = time.perf_counter() - self._t0
        if self.log:
            logger.info("%s: %.4fs", self.name, self.elapsed)


def timeit(fn: Callable[..., Any], *args: Any, iters: int = 10, warmup: int = 2, **kw: Any) -> float:
    """Median seconds per call of ``fn`` (blocks on results)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kw))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kw))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def next_power_of_two(n: int) -> int:
    return 1 if n <= 1 else 1 << (int(n - 1).bit_length())


def round_up(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


def chunked(seq: list, n: int) -> Iterator[list]:
    for i in range(0, len(seq), n):
        yield seq[i : i + n]


@functools.lru_cache(maxsize=None)
def cpu_count() -> int:
    import os

    return os.cpu_count() or 1


def cast_floating(tree: Any, dtype: Any) -> Any:
    """Cast floating-point leaves of a pytree to ``dtype``."""

    def _cast(x: Any) -> Any:
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x

    return jax.tree_util.tree_map(_cast, tree)


def compiled_hbm_bytes(compiled: Any) -> int:
    """Device bytes a compiled program needs, from ``memory_analysis()``.

    Arguments, plus outputs that do not alias (donated) arguments, plus
    temporaries and generated code. It counts this one program, not what
    else the process keeps on the device.
    """
    m = compiled.memory_analysis()
    return int(
        m.argument_size_in_bytes
        + m.output_size_in_bytes
        - m.alias_size_in_bytes
        + m.temp_size_in_bytes
        + m.generated_code_size_in_bytes
    )
