"""Measured per-shape dispatch for the Gram hot path (DESIGN.md §8).

The paper's speedups live or die on the per-item Gram update loop; which
implementation wins — the fused multi-bucket Pallas kernel, the per-bucket
Pallas kernel, or the XLA gather — depends on the bucket shape, the shard
size and the hardware. This module owns that choice:

* :func:`decide` — resolve a :class:`ShapeKey` to a :class:`Decision` at
  trace time: exact cache hit first, deterministic heuristic otherwise.
  Decisions are keyed per bucket *class* as well as per step:
  ``ops.bpmf_gram_step`` consults the step key first, and when it misses
  (and the heuristic is not fused) each bucket resolves its own
  :func:`bucket_key` — so one sweep step can mix Gram implementations
  across pad classes from a warmed per-bucket cache.
  The heuristic **never times anything**, so CPU/CI runs never block on
  measurement, and it consults the fitted :class:`~repro.core.balance.CostModel`
  from the fig2 microbenchmark — the same regression that weighs items
  during partitioning also steers kernel choice.
* :func:`measure_step` — the measured sweep over
  ``(tb, pc) × {pallas_fused, pallas, xla}`` for one step shape, recording
  the winner (with its timings) into the persistent cache. Driven by
  ``benchmarks/fig2_item_update.py``.
* :class:`AutotuneCache` — JSON persistence under ``experiments/autotune/``
  (override with ``REPRO_AUTOTUNE_DIR``). Entries are keyed by the encoded
  :class:`ShapeKey`, which bakes in every input that changes the choice —
  shape, dtype, backend and (for step keys) the scatter capacity — so a
  cache warmed on one machine is simply ignored (falls through to the
  heuristic) for shapes it has never seen.

Cache schema (``gram.json``)::

    {"version": 1,
     "entries": {"<key>": {"impl": "pallas_fused" | "pallas" | "xla",
                           "tb": 8, "pc": 128, "ns_chunk": null,
                           "timings_us": {"xla": 12.3, ...},   # optional
                           "source": "measured" | "recorded"}}}

Unknown versions or malformed files are ignored (heuristic fallback), which
is also the invalidation story: bump ``_CACHE_VERSION`` when a kernel
change makes old measurements meaningless.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.kernels.bpmf_gram import vmem_bytes_estimate
from repro.utils import round_up

_CACHE_VERSION = 1
_VMEM_BUDGET = 12 * 2**20  # leave headroom below the ~16 MB/core VMEM

# Deterministic heuristic priors (overridden by any measured cache entry):
# the MXU runs the one-hot gather at roughly this multiple of the XLA
# gather's effective per-MAC throughput, and the fused kernel amortizes the
# per-dispatch fixed cost over all of a step's buckets.
_MXU_GATHER_ADVANTAGE = 32.0
_FUSED_DISPATCH_DISCOUNT = 8.0

# What Mosaic accepts for the v5e (tests/test_chip_compile.py compiles every
# decision): the (tb, pc) index blocks tile in (8, 128) units, so tb below 8
# is refused. Chunks up to 256 wide gather without holding the one-hot; a
# wider one puts the whole f32 [tb, pc, Ns] one-hot on VMEM's stack, which
# is refused above 4 MiB even inside the working-set budget.
_TB_CANDIDATES = (8,)
_PC_CANDIDATES = (512, 256, 128)
_STACK_ONEHOT_BYTES = 4 * 2**20


def _onehot_fits_stack(tb: int, pc: int, ns: int) -> bool:
    return pc <= 256 or tb * pc * ns * 4 <= _STACK_ONEHOT_BYTES


def _dtype_name(compute_dtype: Any) -> str:
    return jnp.dtype(compute_dtype).name


@dataclasses.dataclass(frozen=True)
class ShapeKey:
    """Everything that changes which Gram implementation wins.

    ``kind`` is ``"bucket"`` (one ``[B, P]`` bucket, per-row output) or
    ``"step"`` (all buckets of one ring step, scatter into ``[cap, K, K]``).
    For step keys, ``B`` is the total row count over the step's buckets and
    ``P`` the largest pad class.
    """

    kind: str  # "bucket" | "step"
    B: int
    P: int
    Ns: int
    K: int
    dtype: str
    backend: str
    cap: int = 0  # step keys only: scatter target rows

    def encode(self) -> str:
        """Stable string form used as the JSON cache key."""
        s = f"{self.kind}_B{self.B}_P{self.P}_Ns{self.Ns}_K{self.K}_{self.dtype}_{self.backend}"
        return f"{s}_cap{self.cap}" if self.kind == "step" else s


@dataclasses.dataclass(frozen=True)
class Decision:
    """Resolved implementation choice for one :class:`ShapeKey`.

    ``impl`` is ``"pallas_fused"`` (one fused kernel launch per step),
    ``"pallas"`` (per-bucket kernel) or ``"xla"`` (gather + einsum).
    Tiling fields are ``None`` for ``"xla"``; ``ns_chunk=None`` means the
    whole shard stays resident in VMEM.
    """

    impl: str
    tb: int | None = None
    pc: int | None = None
    ns_chunk: int | None = None


def bucket_key(
    B: int, P: int, Ns: int, K: int, compute_dtype: Any = jnp.float32, backend: str | None = None
) -> ShapeKey:
    """Key for a single-bucket ``bpmf_gram`` dispatch."""
    return ShapeKey(
        "bucket", B, P, Ns, K, _dtype_name(compute_dtype), backend or jax.default_backend()
    )


def step_key(
    bucket_shapes: Sequence[tuple[int, int]],
    Ns: int,
    K: int,
    cap: int,
    compute_dtype: Any = jnp.float32,
    backend: str | None = None,
) -> ShapeKey:
    """Key for a whole ring step (``bucket_shapes``: per-bucket ``(B, P)``)."""
    B = sum(b for b, _ in bucket_shapes)
    P = max((p for _, p in bucket_shapes), default=0)
    return ShapeKey(
        "step", B, P, Ns, K, _dtype_name(compute_dtype), backend or jax.default_backend(), cap
    )


def workload_step_keys(
    data, K: int, compute_dtype: Any = jnp.float32, backend: str | None = None
) -> list[tuple[ShapeKey, list[tuple[int, int]]]]:
    """Exact engine step keys for every ring step of a distributed layout.

    Inside the shard_map trace, ``ops.bpmf_gram_step`` sees the per-device
    *local* bucket slices, ``Ns`` = the opposite side's padded shard
    capacity and ``cap`` = the updated side's capacity. This derives the
    same keys host-side from a ``DistBPMFData``, so cache entries recorded
    for them (e.g. by the fig2 driver's workload sweep, or a user tuning
    their own dataset) actually engage when the engine runs that workload.

    Args:
        data: ``repro.core.distributed.DistBPMFData`` (host- or device-side).
        K: Latent rank the run will use.
        compute_dtype: Contraction dtype of the run.
        backend: Key backend (default: the current jax backend).

    Returns:
        ``(key, local_bucket_shapes)`` per (side, ring step), in order;
        duplicates across steps are *not* removed.
    """
    S = data.num_shards
    out: list[tuple[ShapeKey, list[tuple[int, int]]]] = []
    for side, opp in ((data.users, data.movies), (data.movies, data.users)):
        for step in side.steps:
            shapes = [(int(b.item_ids.shape[0]) // S, int(b.P)) for b in step]
            out.append(
                (step_key(shapes, opp.cap, K, side.cap, compute_dtype, backend), shapes)
            )
    return out


# --------------------------------------------------------------------------
# Persistent cache
# --------------------------------------------------------------------------


def default_cache_dir() -> str:
    """``$REPRO_AUTOTUNE_DIR`` or ``<repo>/experiments/autotune``."""
    env = os.environ.get("REPRO_AUTOTUNE_DIR")
    if env:
        return env
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.normpath(os.path.join(here, "..", "..", "..", "experiments", "autotune"))


class AutotuneCache:
    """JSON-backed ``ShapeKey -> Decision`` store (see module docstring)."""

    def __init__(self, path: str | None = None):
        self.path = path or os.path.join(default_cache_dir(), "gram.json")
        self._entries: dict[str, dict] | None = None

    def entries(self) -> dict[str, dict]:
        """Lazily-loaded entry dict; malformed/old files load as empty."""
        if self._entries is None:
            self._entries = {}
            try:
                with open(self.path) as f:
                    raw = json.load(f)
                if isinstance(raw, dict) and raw.get("version") == _CACHE_VERSION:
                    self._entries = dict(raw.get("entries", {}))
            except (OSError, ValueError):
                pass
        return self._entries

    def lookup(self, key: ShapeKey) -> Decision | None:
        """Exact-key decision, or ``None`` (caller falls back to heuristic)."""
        e = self.entries().get(key.encode())
        if not e or e.get("impl") not in ("pallas_fused", "pallas", "xla"):
            return None
        return Decision(e["impl"], e.get("tb"), e.get("pc"), e.get("ns_chunk"))

    def record(
        self,
        key: ShapeKey,
        decision: Decision,
        timings_us: dict[str, float] | None = None,
        source: str = "recorded",
    ) -> None:
        """Insert/overwrite one entry and persist immediately."""
        entry: dict[str, Any] = {
            "impl": decision.impl,
            "tb": decision.tb,
            "pc": decision.pc,
            "ns_chunk": decision.ns_chunk,
            "source": source,
        }
        if timings_us:
            entry["timings_us"] = {k: float(v) for k, v in timings_us.items()}
        self.entries()[key.encode()] = entry
        self.save()

    def save(self) -> None:
        """Write the cache file (creates the directory if needed)."""
        os.makedirs(os.path.dirname(self.path), exist_ok=True)
        with open(self.path, "w") as f:
            json.dump({"version": _CACHE_VERSION, "entries": self.entries()}, f, indent=1)


_CACHE: AutotuneCache | None = None


def get_cache() -> AutotuneCache:
    """Process-wide cache singleton (path resolved on first use)."""
    global _CACHE
    if _CACHE is None:
        _CACHE = AutotuneCache()
    return _CACHE


def set_cache(cache: AutotuneCache | None) -> None:
    """Replace the singleton (``None`` re-resolves the path on next use)."""
    global _CACHE
    _CACHE = cache


# --------------------------------------------------------------------------
# Cost model plumbing (fig2 → partitioning → kernel choice)
# --------------------------------------------------------------------------

_COST_MODEL = None  # lazily loaded; False = tried and failed


def load_fig2_cost_model():
    """The fitted fig2 :class:`~repro.core.balance.CostModel`, or defaults.

    Reads ``experiments/bench/fig2_item_update.json`` (written by the fig2
    autotune driver); falls back to ``CostModel()`` defaults when the
    artifact is missing, so the heuristic stays deterministic either way.
    """
    global _COST_MODEL
    from repro.core.balance import CostModel

    if _COST_MODEL is None:
        path = os.path.normpath(
            os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "..", "..", "..", "experiments", "bench", "fig2_item_update.json",
            )
        )
        try:
            with open(path) as f:
                raw = json.load(f)
            cm = raw["cost_model"]
            _COST_MODEL = CostModel(
                fixed=float(cm["fixed_us"]), per_rating=float(cm["per_rating_us"])
            )
        except (OSError, ValueError, KeyError, TypeError):
            _COST_MODEL = False
    return _COST_MODEL if _COST_MODEL else CostModel()


# --------------------------------------------------------------------------
# Tiling + heuristic decision (deterministic, never measures)
# --------------------------------------------------------------------------


def pick_tiling(
    B: int, P: int, Ns: int, K: int, compute_dtype=jnp.float32, cap: int = 0
) -> tuple[int, int] | None:
    """Choose ``(tb, pc)`` with the whole shard VMEM-resident, or ``None``.

    Uses the post-restructure block estimate — ``nbr``/``val`` blocks are
    ``(tb, pc)`` regardless of P (the P axis is a grid dimension), so
    large-P buckets no longer undercount VMEM. ``None`` means the shard
    itself does not fit; callers then stream it via :func:`chunked_tiling`
    (or fall back to XLA).
    """
    for tb in _TB_CANDIDATES:
        for pc in _PC_CANDIDATES:
            if pc > round_up(max(P, 1), 128) and pc != _PC_CANDIDATES[-1]:
                continue  # don't tile wider than the (padded) row
            if (
                vmem_bytes_estimate(tb, pc, Ns, K, None, compute_dtype, cap) <= _VMEM_BUDGET
                and _onehot_fits_stack(tb, pc, Ns)
            ):
                return tb, pc
    return None


def chunked_tiling(
    B: int, P: int, Ns: int, K: int, compute_dtype=jnp.float32, cap: int = 0
) -> tuple[int, int, int] | None:
    """``(tb, pc, ns_chunk)`` streaming the shard through VMEM, or ``None``.

    Picks the largest power-of-two ``ns_chunk`` (≥ 128) whose working set
    fits the budget at a fixed ``(tb=8, pc=128)`` tile; ``None`` only when
    even the smallest chunk overflows (huge K·cap), in which case the
    caller must use XLA.
    """
    tb, pc = 8, 128
    ns = 1 << (max(int(Ns) - 1, 1)).bit_length()  # next pow2 >= Ns
    while ns >= 128:
        if (
            ns <= Ns
            and vmem_bytes_estimate(tb, pc, Ns, K, ns, compute_dtype, cap) <= _VMEM_BUDGET
        ):
            return tb, pc, ns
        ns //= 2
    return None


def heuristic(key: ShapeKey, cost_model=None) -> Decision:
    """Deterministic fallback decision — no timing, ever.

    Decision tree (DESIGN.md §8):

    1. Not on TPU → ``"xla"``. Interpret-mode Pallas exists for parity
       tests only; CI must never pay its cost by default.
    2. Cost-model gate: the fig2 fit estimates the XLA gather at
       ``fixed + per_rating·P`` µs/item; the one-hot kernel does
       ``Ns/K``× more MAC work at ``_MXU_GATHER_ADVANTAGE``× the
       throughput, with the fused kernel amortizing the fixed cost over
       the step (``_FUSED_DISPATCH_DISCOUNT``). XLA wins → ``"xla"``.
    3. Shard fits VMEM (:func:`pick_tiling`) → ``"pallas_fused"`` for step
       keys, ``"pallas"`` for bucket keys, with that tiling.
    4. Otherwise stream Ns (:func:`chunked_tiling`); if even that cannot
       fit, ``"xla"``.
    """
    if key.backend != "tpu":
        return Decision("xla")
    cm = cost_model or load_fig2_cost_model()
    fused = key.kind == "step"
    fixed = cm.fixed / (_FUSED_DISPATCH_DISCOUNT if fused else 1.0)
    est_xla = cm.fixed + cm.per_rating * key.P
    est_onehot = fixed + cm.per_rating * key.P * (key.Ns / max(key.K, 1)) / _MXU_GATHER_ADVANTAGE
    if est_onehot > est_xla:
        return Decision("xla")
    dtype = jnp.dtype(key.dtype)
    impls = [("pallas_fused", key.cap), ("pallas", 0)] if fused else [("pallas", 0)]
    for impl, cap in impls:
        # degrade fused -> per-bucket before xla: a scatter capacity too
        # large for the fused accumulator windows doesn't make the
        # per-bucket kernel (cap-independent working set) any less viable
        tiling = pick_tiling(key.B, key.P, key.Ns, key.K, dtype, cap)
        if tiling is not None:
            return Decision(impl, tiling[0], tiling[1], None)
        chunked = chunked_tiling(key.B, key.P, key.Ns, key.K, dtype, cap)
        if chunked is not None:
            return Decision(impl, chunked[0], chunked[1], chunked[2])
    return Decision("xla")


def decide(key: ShapeKey, cost_model=None, cache: AutotuneCache | None = None) -> Decision:
    """Trace-time dispatch decision: cache hit, else :func:`heuristic`."""
    cache = cache or get_cache()
    hit = cache.lookup(key)
    if hit is not None:
        return hit
    return heuristic(key, cost_model)


# --------------------------------------------------------------------------
# Measured sweep (the fig2 driver's workhorse)
# --------------------------------------------------------------------------


def _synthetic_step(bucket_shapes, Ns, K, cap, compute_dtype, seed=0):
    """Random but reproducible step data matching a :func:`step_key` shape."""
    import numpy as np

    from repro.core.types import Bucket

    rng = np.random.default_rng(seed)
    buckets = []
    slot = 0
    for B, P in bucket_shapes:
        nnz = rng.integers(1, P + 1, B).astype(np.int32)
        nbr = rng.integers(0, Ns, (B, P)).astype(np.int32)
        val = rng.normal(size=(B, P)).astype(np.float32)
        val[np.arange(P)[None, :] >= nnz[:, None]] = 0.0
        item_ids = (slot + np.arange(B)) % cap
        slot += B
        buckets.append(
            Bucket(
                item_ids=jnp.asarray(item_ids, jnp.int32),
                nbr=jnp.asarray(nbr),
                val=jnp.asarray(val),
                nnz=jnp.asarray(nnz),
            )
        )
    X = jnp.asarray(rng.normal(size=(Ns, K)), jnp.float32)
    G = jnp.zeros((cap, K, K), jnp.float32)
    g = jnp.zeros((cap, K), jnp.float32)
    return G, g, X, tuple(buckets)


def measure_step(
    bucket_shapes: Sequence[tuple[int, int]],
    Ns: int,
    K: int,
    cap: int | None = None,
    compute_dtype: Any = jnp.float32,
    alpha: float = 2.0,
    iters: int = 5,
    tilings: Sequence[tuple[int, int]] | None = None,
    cache: AutotuneCache | None = None,
) -> tuple[Decision, dict[str, float]]:
    """Time ``(tb, pc) × {pallas_fused, pallas, xla}`` for one step shape.

    Builds synthetic step data, times every candidate through the real
    dispatch path (``ops.bpmf_gram_step``), records the winner into the
    cache (``source="measured"``) and returns ``(winner, timings_us)``.
    Timing keys are ``"xla"``, ``"pallas_tb{tb}_pc{pc}"`` and
    ``"pallas_fused_tb{tb}_pc{pc}"``; the per-impl minima decide.

    Args:
        bucket_shapes: Per-bucket ``(B, P)`` of the step.
        Ns: Opposite-shard rows.
        K: Latent rank.
        cap: Scatter target rows (default: total B, rounded up to 8).
        compute_dtype: Contraction dtype.
        alpha: Noise precision folded into the fused kernel.
        iters: ``utils.timeit`` iterations per candidate (tiny budgets are
            fine — the cache only needs an ordering, not a clean number).
        tilings: Candidate ``(tb, pc)`` pairs (default: a small grid
            filtered by the VMEM estimate).
        cache: Cache to record into (default: the singleton).

    Returns:
        The winning :class:`Decision` and all candidate timings in µs. The
        winner is recorded into the cache unless an existing measured entry
        for the same key compared strictly more candidates (a tiny-budget
        smoke re-run must not degrade a full sweep's decision).
    """
    from repro.kernels import ops

    total_B = sum(b for b, _ in bucket_shapes)
    cap = cap or round_up(max(total_B, 1), 8)
    key = step_key(bucket_shapes, Ns, K, cap, compute_dtype)
    G, g, X, buckets = _synthetic_step(bucket_shapes, Ns, K, cap, compute_dtype)

    if tilings is None:
        tilings = [
            (tb, pc) for tb in _TB_CANDIDATES for pc in _PC_CANDIDATES
            if _onehot_fits_stack(tb, pc, Ns)
        ]

    import functools

    timings: dict[str, float] = {}
    candidates: dict[str, Decision] = {"xla": Decision("xla")}
    P_max = max((p for _, p in bucket_shapes), default=128)
    for tb, pc in tilings:
        # admit each candidate only if *its* working set fits — the fused
        # kernel additionally holds the (cap, K, K)/(cap, K) accumulator
        # windows (input + aliased output copy) resident
        if vmem_bytes_estimate(tb, pc, Ns, K, None, compute_dtype) <= _VMEM_BUDGET:
            candidates[f"pallas_tb{tb}_pc{pc}"] = Decision("pallas", tb, pc)
        if vmem_bytes_estimate(tb, pc, Ns, K, None, compute_dtype, cap) <= _VMEM_BUDGET:
            candidates[f"pallas_fused_tb{tb}_pc{pc}"] = Decision("pallas_fused", tb, pc)
    # shards too large to sit resident get one Ns-streaming candidate per
    # impl — otherwise the streaming mode could never win a measurement and
    # exactly the shapes it targets would record "xla" forever
    if not any(d.impl == "pallas" for d in candidates.values()):
        c = chunked_tiling(total_B, P_max, Ns, K, compute_dtype)
        if c is not None:
            candidates[f"pallas_tb{c[0]}_pc{c[1]}_ns{c[2]}"] = Decision("pallas", *c)
    if not any(d.impl == "pallas_fused" for d in candidates.values()):
        c = chunked_tiling(total_B, P_max, Ns, K, compute_dtype, cap)
        if c is not None:
            candidates[f"pallas_fused_tb{c[0]}_pc{c[1]}_ns{c[2]}"] = Decision(
                "pallas_fused", *c
            )

    import time

    import numpy as np

    fns = {}
    for label, dec in candidates.items():
        fns[label] = jax.jit(
            functools.partial(
                ops.bpmf_gram_step,
                alpha=alpha,
                compute_dtype=compute_dtype,
                gram_impl=dec.impl,
                tb=dec.tb,
                pc=dec.pc,
                ns_chunk=dec.ns_chunk,
            )
        )
        jax.block_until_ready(fns[label](G, g, X, buckets))  # compile + warm
    # interleave candidates round-robin so machine-level drift during the
    # sweep biases every candidate equally, then take per-candidate medians
    samples: dict[str, list[float]] = {label: [] for label in candidates}
    for _ in range(max(iters, 1)):
        for label, fn in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn(G, g, X, buckets))
            samples[label].append(time.perf_counter() - t0)
    timings = {label: float(np.median(ts)) * 1e6 for label, ts in samples.items()}

    best_label = min(timings, key=timings.get)
    best = candidates[best_label]
    store = cache or get_cache()
    prev = store.entries().get(key.encode())
    # never let a narrower sweep (e.g. the CI smoke's single tiling) clobber
    # a measured entry that compared more candidates for the same key
    if not (
        prev
        and prev.get("source") == "measured"
        and len(prev.get("timings_us", {})) > len(timings)
    ):
        store.record(key, best, timings_us=timings, source="measured")
    return best, timings
