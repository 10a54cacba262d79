"""Distributed BPMF Gibbs sampling (paper §IV) via ``shard_map``.

The paper distributes U and V across MPI ranks, balances work with a
cost-model-driven reorder of R, and overlaps communication with computation
using buffered MPI_Isend/Irecv. The TPU-native mapping (DESIGN.md §2, §4):

  * ranks            -> devices along one flattened mesh axis ("ring")
  * R reordering     -> `balance.partition_items` relabeling; shard s owns the
                        contiguous relabeled id range [s*cap, (s+1)*cap)
  * Isend/Irecv +    -> `comm_mode="ring"`: `lax.ppermute` rotates the
    send buffers        opposite-side factor shard around the ring while the
                        current shard's Gram contribution computes (the
                        permute for step t+1 is issued before step t's
                        compute so XLA's scheduler overlaps ICI and MXU)
  * deep pipelining  -> `comm_mode="ring_async"`: same rotation, but
    (1705.10633)        `pipeline_depth` permutes kept in flight through a
                        rotating buffer queue (prologue / steady-state /
                        drain), hiding d link latencies per step
  * synchronous      -> `comm_mode="allgather"`: one all-gather of the full
    baseline            opposite factor, then local updates (GraphLab-like)

Correctness contract: for identical (key, data), every comm_mode and every
shard count draws the *same* posterior samples as the sequential
``core.gibbs`` sampler, up to float reduction order — per-item noise is keyed
by original item id (`posterior.item_noise`) and hyper-parameter sampling
consumes cross-shard sufficient statistics reduced in a fixed order
(:func:`_psum_ordered`). This turns the paper's "all versions reach the same
RMSE" claim (§V-B) into an exact test, and makes the draws independent of
*how* the ring mesh is realized: a 2-process × 4-device mesh runs the same
per-shard program and the same reduction tree as 1 process × 8 devices, so
multi-process runs are bitwise-identical to single-process ones
(tests/test_multiproc.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import posterior
from repro.core.balance import CostModel, Partition, partition_items
from repro.core.gibbs import SweepMetrics, sweep_keys
from repro.core.hyper import hyper_sufficient_stats, sample_hyper_from_stats
from repro.core.prediction import PredictionState, rmse, update_posterior_accum
from repro.core.types import (
    HYPER_SCOPE, PREDICT_SCOPE, RING_SCOPE, BPMFConfig, Bucket, HyperParams, PosteriorAccum,
    gram_slots,
)
from repro.data.sparse import (
    ChunkedRatings, RatingsCOO, StableMeanAccumulator, csr_from_coo, stable_mean,
    train_test_split,
)
from repro.utils import pytree_dataclass, static_field

RING_AXIS = "ring"


# --------------------------------------------------------------------------
# Distributed data containers
# --------------------------------------------------------------------------


@pytree_dataclass
class RingSide:
    """Neighbor lists for updating one side, laid out for the ring schedule.

    ``steps[t]`` holds the buckets for ring step t: the contributions to each
    local item's Gram terms from opposite-side items owned by shard
    ``(d - t) mod S`` (which is exactly the shard resident in device d's
    buffer at step t). Every bucket array has a flat leading axis ``S * B``
    sharded along the ring; neighbor indices are *local to the source shard*.

    ``Bucket.item_ids`` here are LOCAL row ids into the [cap, K] shard
    (pad = -1); original item ids (for layout-independent noise) live in
    ``orig_ids``.
    """

    steps: tuple[tuple[Bucket, ...], ...]
    orig_ids: jax.Array  # [S * cap] int32 original item id per slot, -1 = pad
    cap: int = static_field(default=0)
    num_items: int = static_field(default=0)

    @property
    def num_steps(self) -> int:
        return len(self.steps)


@pytree_dataclass
class DistTestSet:
    """Held-out triples in *relabeled* coordinates, replicated."""

    rows: jax.Array  # [T] int32 relabeled user slot (shard*cap_u + row)
    cols: jax.Array  # [T] int32 relabeled movie slot
    vals: jax.Array  # [T] f32


@pytree_dataclass
class DistBPMFData:
    """Everything the distributed sweep needs besides the factor shards."""

    users: RingSide  # for updating U (neighbors: movies)
    movies: RingSide  # for updating V (neighbors: users)
    test: DistTestSet
    mean_rating: jax.Array
    num_shards: int = static_field(default=1)
    min_rating: float = static_field(default=-np.inf)
    max_rating: float = static_field(default=np.inf)


@pytree_dataclass
class DistState:
    """Sharded Gibbs state. U: [S*cap_u, K], V: [S*cap_v, K] (ring-sharded)."""

    U: jax.Array
    V: jax.Array
    hyper_U: HyperParams
    hyper_V: HyperParams
    sweep: jax.Array


@dataclasses.dataclass(frozen=True)
class DistPlan:
    """Host-side record of how the problem was partitioned (static).

    ``total_nnz`` is the count of training ratings. ``local_shards`` /
    ``local_nnz`` are populated by the per-host builder
    (:func:`build_distributed_data_per_host`): which ring shards this process
    materialized and how many training ratings it kept — the allocation
    guard tests assert ``local_nnz < total_nnz`` on every process of a
    multi-process run.
    """

    part_users: Partition
    part_movies: Partition
    num_shards: int
    strategy: str
    local_shards: tuple[int, ...] | None = None
    local_nnz: int = 0
    total_nnz: int = 0


@dataclasses.dataclass(frozen=True)
class LocalShardedArray:
    """Host stand-in for a ring-sharded array of which only one row block exists.

    The per-host data builder materializes bucket arrays only for this
    process's shards; placement turns the block into a global ``jax.Array``
    via ``make_array_from_callback`` without any process ever holding the
    full array. ``shape``/``dtype`` describe the *global* array; ``block``
    holds rows ``[row_offset, row_offset + block.shape[0])``.
    """

    block: np.ndarray
    global_rows: int
    row_offset: int

    @property
    def shape(self) -> tuple[int, ...]:
        return (self.global_rows,) + self.block.shape[1:]

    @property
    def dtype(self):
        return self.block.dtype

    def place(self, sharding: NamedSharding) -> jax.Array:
        def cb(idx):
            rows = idx[0]
            start = 0 if rows.start is None else rows.start
            stop = self.global_rows if rows.stop is None else rows.stop
            if start < self.row_offset or stop > self.row_offset + self.block.shape[0]:
                raise ValueError(
                    f"device shard rows [{start}, {stop}) are outside this "
                    f"process's materialized block "
                    f"[{self.row_offset}, {self.row_offset + self.block.shape[0]}) "
                    "— local_shards does not match the mesh's addressable devices"
                )
            sl = slice(start - self.row_offset, stop - self.row_offset)
            return self.block[(sl,) + tuple(idx[1:])]

        return jax.make_array_from_callback(self.shape, sharding, cb)


# --------------------------------------------------------------------------
# Host-side data distribution (paper §IV-B)
# --------------------------------------------------------------------------


def _neighbor_shard_counts(
    indptr: np.ndarray, indices: np.ndarray, part_opp: Partition, num_shards: int
) -> np.ndarray:
    """``[num_items, S]`` count of each item's neighbors per owning opposite shard."""
    nnz_all = (indptr[1:] - indptr[:-1]).astype(np.int64)
    row_of = np.repeat(np.arange(len(nnz_all), dtype=np.int64), nnz_all)
    src = part_opp.perm[indices] // part_opp.cap
    flat = np.bincount(row_of * num_shards + src, minlength=len(nnz_all) * num_shards)
    return flat.reshape(len(nnz_all), num_shards).astype(np.int32)


def _pad_class_of(counts: np.ndarray, pads_sorted: Sequence[int]) -> np.ndarray:
    """Vectorized pad class: smallest configured pad >= n, else next power of two."""
    pads_arr = np.asarray(pads_sorted, dtype=np.int64)
    idx = np.searchsorted(pads_arr, counts, side="left")
    out = pads_arr[np.minimum(idx, len(pads_arr) - 1)].copy()
    for i in np.nonzero(idx >= len(pads_arr))[0]:
        p = int(pads_arr[-1])
        while p < counts[i]:
            p *= 2
        out[i] = p
    return out


def _ring_side_buckets(
    indptr: np.ndarray,
    indices: np.ndarray,  # already relabeled opposite-side ids
    values: np.ndarray,
    part_self: Partition,
    part_opp: Partition,
    num_shards: int,
    pads: Sequence[int],
    bucket_multiple: int = 8,
    *,
    shard_counts: np.ndarray | None = None,
    local_shards: Sequence[int] | None = None,
) -> RingSide:
    """Build the per-step bucketed neighbor lists for one side.

    For item i (owned by shard d at local row r) and ring step t, collect the
    neighbors j with shard(j) == (d - t) mod S, store their *local* opposite
    indices. Bucket shapes are agreed globally (max over devices per step &
    pad class) so the SPMD program is identical on every device.

    Per-host mode: with ``local_shards`` a contiguous subset of shards, the
    bucket *shapes* are still computed globally — from ``shard_counts``, the
    ``[num_items, S]`` per-source-shard neighbor counts, which every process
    derives from the same deterministic partition — but the bucket *arrays*
    are materialized only for the local shards and wrapped in
    :class:`LocalShardedArray`. The CSR inputs then only need rows for
    locally-owned items (remote rows may be empty); slot order (ascending
    original id within each shard) and neighbor order (CSR order, i.e.
    sorted by original opposite id) are layout-invariant, so the local block
    is bitwise-identical to the corresponding rows of a full build.
    """
    S = num_shards
    cap = part_self.cap
    cap_opp = part_opp.cap
    num_items = len(indptr) - 1

    full = local_shards is None
    local = tuple(range(S)) if full else tuple(int(d) for d in local_shards)
    if list(local) != list(range(local[0], local[-1] + 1)):
        raise ValueError(f"local_shards must be contiguous ascending, got {local}")
    L = len(local)

    if shard_counts is None:
        shard_counts = _neighbor_shard_counts(indptr, indices, part_opp, S)

    pads_sorted = sorted(pads)
    d_of = (part_self.perm // cap).astype(np.int64)  # owning shard per item
    item_ids_all = np.arange(num_items, dtype=np.int64)

    steps: list[tuple[Bucket, ...]] = []
    for t in range(S):
        src_t = (d_of - t) % S
        cnt_t = shard_counts[item_ids_all, src_t].astype(np.int64)
        present = (cnt_t > 0) | (t == 0)  # t == 0 rows always present
        pc_t = _pad_class_of(cnt_t, pads_sorted)
        # global bucket plan: per pad class, B = max over ALL devices
        buckets_t: list[Bucket] = []
        for pc in sorted(int(p) for p in np.unique(pc_t[present])):
            in_class = present & (pc_t == pc)
            per_dev = np.bincount(d_of[in_class], minlength=S)
            B = -(-int(per_dev.max()) // bucket_multiple) * bucket_multiple
            item_ids = np.full((L, B), -1, dtype=np.int32)
            nbr = np.zeros((L, B, pc), dtype=np.int32)
            val = np.zeros((L, B, pc), dtype=np.float32)
            nnz = np.zeros((L, B), dtype=np.int32)
            for li, d in enumerate(local):
                # ascending original id == insertion order of the full build
                for slot, old_id in enumerate(np.nonzero(in_class & (d_of == d))[0]):
                    r = int(part_self.perm[old_id]) % cap
                    lo, hi = indptr[old_id], indptr[old_id + 1]
                    nbr_new = part_opp.perm[indices[lo:hi]]
                    sel = (nbr_new // cap_opp) == ((d - t) % S)
                    nb = (nbr_new % cap_opp)[sel]
                    item_ids[li, slot] = r
                    nnz[li, slot] = len(nb)
                    nbr[li, slot, : len(nb)] = nb
                    val[li, slot, : len(nb)] = values[lo:hi][sel]
            if full:
                buckets_t.append(
                    Bucket(
                        item_ids=jnp.asarray(item_ids.reshape(S * B)),
                        nbr=jnp.asarray(nbr.reshape(S * B, pc)),
                        val=jnp.asarray(val.reshape(S * B, pc)),
                        nnz=jnp.asarray(nnz.reshape(S * B)),
                    )
                )
            else:
                off = local[0] * B

                def wrap(a: np.ndarray) -> LocalShardedArray:
                    return LocalShardedArray(
                        block=a.reshape((L * B,) + a.shape[2:]),
                        global_rows=S * B,
                        row_offset=off,
                    )

                buckets_t.append(
                    Bucket(item_ids=wrap(item_ids), nbr=wrap(nbr), val=wrap(val), nnz=wrap(nnz))
                )
        steps.append(tuple(buckets_t))

    orig = np.asarray(part_self.inv_perm, dtype=np.int32)  # [S*cap], -1 pads
    return RingSide(
        steps=tuple(steps),
        orig_ids=jnp.asarray(orig),
        cap=cap,
        num_items=num_items,
    )


def ring_layout_stats(side: RingSide, num_shards: int, ratings: int) -> dict[str, int]:
    """Training ``ratings`` of one side and the Gram slots that hold them,
    summed over shards: each shard runs its ``B / S`` rows of every step's
    bucket in row tiles (:func:`repro.core.types.gram_slots`)."""
    slots = sum(num_shards * gram_slots(b.B // num_shards, b.P)
                for step in side.steps for b in step)
    return {"ratings": ratings, "gram_slots": slots}


def build_distributed_data(
    coo: RatingsCOO,
    num_shards: int,
    pads: Sequence[int] = (8, 32, 128, 512, 2048),
    test_fraction: float = 0.1,
    seed: int = 0,
    strategy: str = "lpt",
    cost_model: CostModel | None = None,
    min_rating: float | None = None,
    max_rating: float | None = None,
) -> tuple[DistBPMFData, DistPlan]:
    """Full host-side distribution pipeline (paper §IV-B).

    Splits train/test, computes the cost-balanced partition of both sides,
    relabels R accordingly and builds the per-ring-step neighbor lists.
    The centering mean uses the chunking-invariant accumulator so a
    per-host build of the same ratings (:func:`build_distributed_data_per_host`)
    centers bitwise-identically.
    """
    train, test = train_test_split(coo, test_fraction, seed)
    mean = stable_mean(train.vals) if train.nnz else 0.0
    centered = train.vals - np.float32(mean)

    u_indptr, u_idx, u_val = csr_from_coo(train.rows, train.cols, centered, coo.num_users)
    m_indptr, m_idx, m_val = csr_from_coo(train.cols, train.rows, centered, coo.num_movies)

    cm = cost_model or CostModel()
    part_u = partition_items(
        (u_indptr[1:] - u_indptr[:-1]).astype(np.int64), num_shards, cm, strategy
    )
    part_m = partition_items(
        (m_indptr[1:] - m_indptr[:-1]).astype(np.int64), num_shards, cm, strategy
    )

    users = _ring_side_buckets(u_indptr, u_idx, u_val, part_u, part_m, num_shards, pads)
    movies = _ring_side_buckets(m_indptr, m_idx, m_val, part_m, part_u, num_shards, pads)

    lo = float(coo.vals.min()) if min_rating is None else min_rating
    hi = float(coo.vals.max()) if max_rating is None else max_rating
    data = DistBPMFData(
        users=users,
        movies=movies,
        test=DistTestSet(
            rows=jnp.asarray(part_u.perm[test.rows], jnp.int32),
            cols=jnp.asarray(part_m.perm[test.cols], jnp.int32),
            vals=jnp.asarray(test.vals, jnp.float32),
        ),
        mean_rating=jnp.asarray(mean, jnp.float32),
        num_shards=num_shards,
        min_rating=lo,
        max_rating=hi,
    )
    return data, DistPlan(part_u, part_m, num_shards, strategy, total_nnz=train.nnz)


def local_shard_range(num_shards: int, process_index: int, num_processes: int) -> range:
    """The contiguous ring shards owned by one process.

    Global device order is process-major, so process p's addressable devices
    are exactly shards ``[p*S/P, (p+1)*S/P)`` of a ring mesh over all global
    devices.
    """
    if num_shards % num_processes:
        raise ValueError(
            f"num_shards={num_shards} must be divisible by num_processes={num_processes}"
        )
    per = num_shards // num_processes
    return range(process_index * per, (process_index + 1) * per)


def build_distributed_data_per_host(
    ratings: ChunkedRatings,
    num_shards: int,
    local_shards: Sequence[int],
    pads: Sequence[int] = (8, 32, 128, 512, 2048),
    test_fraction: float = 0.1,
    seed: int = 0,
    strategy: str = "lpt",
    cost_model: CostModel | None = None,
    min_rating: float | None = None,
    max_rating: float | None = None,
) -> tuple[DistBPMFData, DistPlan]:
    """Per-host distribution pipeline: global plan, local materialization.

    Every process streams the same rating chunks twice and computes the same
    deterministic global state — train/test split (the seeded RNG stream is
    consumed in chunk order, which equals the one-shot draw for PCG64),
    per-item rating counts, the cost-balanced partitions, the centering mean
    (chunking-invariant accumulator) and the global bucket shape plan — but
    only *retains* training ratings that touch one of its ``local_shards``
    and only materializes those shards' bucket arrays (as
    :class:`LocalShardedArray` blocks). No process ever holds the full
    training rating array; the guard below raises if the retention filter
    degenerates. The held-out test triples stay replicated (they are
    device-replicated at runtime anyway).

    With ``local_shards`` covering every shard this is bitwise-identical to
    :func:`build_distributed_data` on the materialized stream — asserted in
    tests/test_multiproc.py.
    """
    S = num_shards
    local = tuple(int(d) for d in local_shards)
    U, M = ratings.num_users, ratings.num_movies

    # -- pass 1: split + per-item train counts + mean + test triples ------
    rng = np.random.default_rng(seed)
    u_nnz = np.zeros(U, dtype=np.int64)
    m_nnz = np.zeros(M, dtype=np.int64)
    mean_acc = StableMeanAccumulator()
    test_rows, test_cols, test_vals = [], [], []
    vmin, vmax = np.inf, -np.inf
    total_train = 0
    for chunk in ratings.chunks():
        if chunk.nnz > ratings.chunk_rows:
            raise ValueError(
                f"chunk of {chunk.nnz} ratings exceeds chunk_rows={ratings.chunk_rows}"
            )
        t = rng.random(chunk.nnz) < test_fraction
        tr = ~t
        u_nnz += np.bincount(chunk.rows[tr], minlength=U)
        m_nnz += np.bincount(chunk.cols[tr], minlength=M)
        mean_acc.add(chunk.vals[tr])
        test_rows.append(chunk.rows[t])
        test_cols.append(chunk.cols[t])
        test_vals.append(chunk.vals[t])
        if chunk.nnz:
            vmin = min(vmin, float(chunk.vals.min()))
            vmax = max(vmax, float(chunk.vals.max()))
        total_train += int(tr.sum())
    mean = mean_acc.mean()

    cm = cost_model or CostModel()
    part_u = partition_items(u_nnz, S, cm, strategy)
    part_m = partition_items(m_nnz, S, cm, strategy)
    shard_of_u = (part_u.perm // part_u.cap).astype(np.int64)
    shard_of_m = (part_m.perm // part_m.cap).astype(np.int64)
    local_u = np.isin(shard_of_u, local)
    local_m = np.isin(shard_of_m, local)

    # -- pass 2: neighbor shard counts (global) + local rating retention --
    rng2 = np.random.default_rng(seed)
    cnt_u = np.zeros(U * S, dtype=np.int64)
    cnt_m = np.zeros(M * S, dtype=np.int64)
    keep_r, keep_c, keep_v = [], [], []
    for chunk in ratings.chunks():
        t = rng2.random(chunk.nnz) < test_fraction
        tr = ~t
        r, c, v = chunk.rows[tr], chunk.cols[tr], chunk.vals[tr]
        cnt_u += np.bincount(r.astype(np.int64) * S + shard_of_m[c], minlength=U * S)
        cnt_m += np.bincount(c.astype(np.int64) * S + shard_of_u[r], minlength=M * S)
        keep = local_u[r] | local_m[c]
        keep_r.append(r[keep])
        keep_c.append(c[keep])
        keep_v.append(v[keep])
    cnt_u = cnt_u.reshape(U, S).astype(np.int32)
    cnt_m = cnt_m.reshape(M, S).astype(np.int32)

    r = np.concatenate(keep_r) if keep_r else np.zeros(0, np.int32)
    c = np.concatenate(keep_c) if keep_c else np.zeros(0, np.int32)
    v = np.concatenate(keep_v) if keep_v else np.zeros(0, np.float32)
    local_nnz = int(r.shape[0])
    if len(local) < S and total_train and local_nnz >= total_train:
        raise RuntimeError(
            f"per-host retention kept all {total_train} training ratings on a "
            f"process owning only shards {local} of {S} — the locality filter "
            "is not reducing the resident rating array"
        )
    cv = v - np.float32(mean)

    own_u = local_u[r]  # ratings whose user is locally owned
    own_m = local_m[c]
    u_indptr, u_idx, u_val = csr_from_coo(r[own_u], c[own_u], cv[own_u], U)
    m_indptr, m_idx, m_val = csr_from_coo(c[own_m], r[own_m], cv[own_m], M)

    users = _ring_side_buckets(
        u_indptr, u_idx, u_val, part_u, part_m, S, pads,
        shard_counts=cnt_u, local_shards=local,
    )
    movies = _ring_side_buckets(
        m_indptr, m_idx, m_val, part_m, part_u, S, pads,
        shard_counts=cnt_m, local_shards=local,
    )

    trows = np.concatenate(test_rows) if test_rows else np.zeros(0, np.int32)
    tcols = np.concatenate(test_cols) if test_cols else np.zeros(0, np.int32)
    tvals = np.concatenate(test_vals) if test_vals else np.zeros(0, np.float32)
    lo = (vmin if np.isfinite(vmin) else -np.inf) if min_rating is None else min_rating
    hi = (vmax if np.isfinite(vmax) else np.inf) if max_rating is None else max_rating
    data = DistBPMFData(
        users=users,
        movies=movies,
        test=DistTestSet(
            rows=jnp.asarray(part_u.perm[trows], jnp.int32),
            cols=jnp.asarray(part_m.perm[tcols], jnp.int32),
            vals=jnp.asarray(tvals, jnp.float32),
        ),
        mean_rating=jnp.asarray(mean, jnp.float32),
        num_shards=S,
        min_rating=lo,
        max_rating=hi,
    )
    plan = DistPlan(
        part_u, part_m, S, strategy,
        local_shards=local, local_nnz=local_nnz, total_nnz=total_train,
    )
    return data, plan


# --------------------------------------------------------------------------
# Device-side sweep (inside shard_map; everything here sees LOCAL shards)
# --------------------------------------------------------------------------


def _accumulate_buckets(
    G: jax.Array,
    g: jax.Array,
    X_src: jax.Array,
    buckets: tuple[Bucket, ...],
    alpha: float,
    compute_dtype,
    gram_impl: str,
) -> tuple[jax.Array, jax.Array]:
    """Add one ring step's Gram contributions into the per-local-item (G, g).

    Dispatch is resolved at trace time by ``kernels.ops.bpmf_gram_step``:
    with a fused decision (autotune cache / heuristic / explicit
    ``gram_impl="pallas_fused"``) the whole step is one ``pallas_call``
    scatter-accumulating in-kernel; otherwise it is the per-bucket loop
    with ``at[].add`` scatters.
    """
    from repro.kernels import ops as kops

    return kops.bpmf_gram_step(
        G, g, X_src, buckets, alpha=alpha, compute_dtype=compute_dtype, gram_impl=gram_impl
    )


def _half_sweep_ring(
    key: jax.Array,
    X_opp_loc: jax.Array,  # [cap_opp, K] this device's opposite-side shard
    side: RingSide,  # LOCAL slices (leading S axis already split)
    hyper: HyperParams,
    cfg: BPMFConfig,
    num_shards: int,
) -> jax.Array:
    """Paper §IV-C: rotate opposite shards around the ring, overlap compute.

    The ppermute for step t+1 is issued *before* step t's Gram accumulation,
    so the ICI transfer proceeds while the MXU contracts — the paper's
    Isend/Irecv-with-buffering, with the whole shard as the maximal buffer.
    """
    cap = side.cap
    K = X_opp_loc.shape[-1]
    G = jnp.zeros((cap, K, K), jnp.float32)
    g = jnp.zeros((cap, K), jnp.float32)

    perm = [(i, (i + 1) % num_shards) for i in range(num_shards)]
    buf = X_opp_loc
    for t in range(num_shards):
        if t + 1 < num_shards:
            with jax.named_scope(RING_SCOPE):
                nxt = jax.lax.ppermute(buf, RING_AXIS, perm)  # in flight during gram
        G, g = _accumulate_buckets(
            G, g, buf, side.steps[t], cfg.alpha, cfg.compute_dtype, cfg.gram_impl
        )
        if t + 1 < num_shards:
            buf = nxt

    return posterior.sample_from_terms(key, side.orig_ids, G, g, hyper)


def _half_sweep_ring_async(
    key: jax.Array,
    X_opp_loc: jax.Array,  # [cap_opp, K] this device's opposite-side shard
    side: RingSide,  # LOCAL slices (leading S axis already split)
    hyper: HyperParams,
    cfg: BPMFConfig,
    num_shards: int,
) -> jax.Array:
    """Depth-d pipelined ring (Vander Aa et al. 1705.10633, DESIGN.md §7).

    Generalizes :func:`_half_sweep_ring` from one in-flight ``ppermute`` to a
    rotating queue of ``d = cfg.pipeline_depth`` buffers:

      * prologue — issue the rotations for steps 1..d-1 before the first
        Gram accumulation, so d shard buffers are live up front;
      * steady state — at step t, issue the rotation producing the buffer
        for step t+d, then accumulate step t from the queue head. Compute
        at step t therefore only waits on a transfer issued d steps
        earlier, hiding up to d link latencies instead of one;
      * drain — the last d steps issue nothing and consume the queue.

    Exactly ``num_shards - 1`` rotations are issued in total (same bytes as
    the synchronous ring), and the buffer consumed at step t holds shard
    ``(d_axis - t) mod S`` regardless of depth — rotations only reorder
    *when* transfers are issued, never the values — so the posterior draw
    is bit-identical to ``comm_mode="ring"`` for every depth. Memory cost:
    d opposite-shard buffers (d × cap_opp × K × itemsize bytes) live at
    once.
    """
    cap = side.cap
    K = X_opp_loc.shape[-1]
    G = jnp.zeros((cap, K, K), jnp.float32)
    g = jnp.zeros((cap, K), jnp.float32)

    if cfg.pipeline_depth < 1:
        raise ValueError(f"pipeline_depth must be >= 1, got {cfg.pipeline_depth}")
    depth = min(cfg.pipeline_depth, num_shards)  # > S-1 rotations can't exist

    perm = [(i, (i + 1) % num_shards) for i in range(num_shards)]
    queue = [X_opp_loc]  # queue[i] holds the buffer for step t + i

    def rotate() -> None:
        with jax.named_scope(RING_SCOPE):
            queue.append(jax.lax.ppermute(queue[-1], RING_AXIS, perm))

    for _ in range(depth - 1):  # prologue: pre-issue d-1 rotations
        rotate()
    for t in range(num_shards):
        if t + depth < num_shards:  # issue step t+d while accumulating step t
            rotate()
        buf = queue.pop(0)
        G, g = _accumulate_buckets(
            G, g, buf, side.steps[t], cfg.alpha, cfg.compute_dtype, cfg.gram_impl
        )

    return posterior.sample_from_terms(key, side.orig_ids, G, g, hyper)


def _half_sweep_allgather(
    key: jax.Array,
    X_opp_loc: jax.Array,
    side: RingSide,
    hyper: HyperParams,
    cfg: BPMFConfig,
    num_shards: int,
) -> jax.Array:
    """Synchronous baseline: one blocking all-gather, then local updates.

    Reuses the ring neighbor lists — at step t the slice of the gathered
    matrix standing in for the ring buffer is shard (d - t) mod S.
    """
    cap = side.cap
    K = X_opp_loc.shape[-1]
    cap_opp = X_opp_loc.shape[0]
    with jax.named_scope(RING_SCOPE):
        X_full = jax.lax.all_gather(X_opp_loc, RING_AXIS, tiled=True)  # [S*cap_opp, K]
    d = jax.lax.axis_index(RING_AXIS)

    G = jnp.zeros((cap, K, K), jnp.float32)
    g = jnp.zeros((cap, K), jnp.float32)
    for t in range(num_shards):
        o = (d - t) % num_shards
        shard = jax.lax.dynamic_slice(X_full, (o * cap_opp, 0), (cap_opp, K))
        G, g = _accumulate_buckets(
            G, g, shard, side.steps[t], cfg.alpha, cfg.compute_dtype, cfg.gram_impl
        )
    return posterior.sample_from_terms(key, side.orig_ids, G, g, hyper)


def _psum_ordered(x: jax.Array) -> jax.Array:
    """Ring-axis sum with a reduction order fixed by the program, not the fabric.

    ``lax.psum`` leaves the reduction tree to the collective backend, so a
    cross-process all-reduce (e.g. gloo's ring) and XLA's single-process
    all-reduce sum in different orders and differ in the last float bit —
    enough to break the multi-process == single-process bitwise contract.
    An ``all_gather`` moves bytes exactly; the axis-0 sum then runs inside
    the (identical) per-shard program, so every mesh realization reduces in
    the same order. Only worth the extra bytes for small operands — here the
    [K]/[K,K] hyper sufficient statistics.
    """
    return jnp.sum(jax.lax.all_gather(x, RING_AXIS), axis=0)


@jax.named_scope(HYPER_SCOPE)
def _sample_hyper_dist(
    key: jax.Array, X_loc: jax.Array, orig_ids: jax.Array, prior
) -> HyperParams:
    """NW conditional from globally-reduced sufficient statistics.

    Identical on all devices; uses the order-deterministic reduction so the
    draw does not depend on the process layout of the ring mesh.
    """
    weights = (orig_ids >= 0).astype(X_loc.dtype)
    n, sx, sxx = hyper_sufficient_stats(X_loc, weights)
    n = _psum_ordered(n)
    sx = _psum_ordered(sx)
    sxx = _psum_ordered(sxx)
    return sample_hyper_from_stats(key, n, sx, sxx, prior)


def _predict_dist(
    U_loc: jax.Array,
    V_loc: jax.Array,
    test: DistTestSet,
    mean_rating: jax.Array,
    min_rating: float,
    max_rating: float,
    num_shards: int,
) -> jax.Array:
    """Test predictions with factor rows scattered across the ring.

    Each test row/col lives on exactly one shard; a masked local gather
    followed by a psum reconstructs the [T, K] rows on every device — two
    small collectives per sweep, negligible next to the factor rotation.
    """
    d = jax.lax.axis_index(RING_AXIS)
    cap_u, K = U_loc.shape
    cap_v = V_loc.shape[0]

    def fetch(X_loc: jax.Array, ids: jax.Array, cap: int) -> jax.Array:
        shard = ids // cap
        local = ids % cap
        mine = (shard == d).astype(X_loc.dtype)
        rows = jnp.take(X_loc, local, axis=0, mode="clip") * mine[:, None]
        return jax.lax.psum(rows, RING_AXIS)

    u_rows = fetch(U_loc, test.rows, cap_u)
    v_rows = fetch(V_loc, test.cols, cap_v)
    preds = jnp.sum(u_rows * v_rows, axis=-1) + mean_rating
    return jnp.clip(preds, min_rating, max_rating)


def _sweep_step_device(
    key: jax.Array,
    U_loc: jax.Array,
    V_loc: jax.Array,
    sweep: jax.Array,
    pred_sum: jax.Array,
    pred_n: jax.Array,
    data: DistBPMFData,  # local slices of the sharded leaves
    cfg: BPMFConfig,
) -> tuple[jax.Array, jax.Array, HyperParams, HyperParams, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """One full Gibbs sweep on one device (Algorithm 1, distributed).

    Traceable body shared by the per-sweep ``shard_map`` entry point and the
    blocked scan loop; returns scalar ``(r_sample, r_avg)`` separately so
    callers stack metrics however they batch sweeps.
    """
    S = data.num_shards
    prior = cfg.prior()
    k_hv, k_v, k_hu, k_u = sweep_keys(key, sweep)
    halves = {
        "ring": _half_sweep_ring,
        "ring_async": _half_sweep_ring_async,
        "allgather": _half_sweep_allgather,
    }
    if cfg.comm_mode not in halves:
        raise ValueError(
            f"unknown comm_mode {cfg.comm_mode!r}; one of {sorted(halves)}"
        )
    half = halves[cfg.comm_mode]

    # movies given users
    hyper_V = _sample_hyper_dist(k_hv, V_loc, data.movies.orig_ids, prior)
    V_new = half(k_v, U_loc, data.movies, hyper_V, cfg, S)
    # users given updated movies
    hyper_U = _sample_hyper_dist(k_hu, U_loc, data.users.orig_ids, prior)
    U_new = half(k_u, V_new, data.users, hyper_U, cfg, S)

    new_sweep = sweep + 1
    with jax.named_scope(PREDICT_SCOPE):
        preds = _predict_dist(
            U_new, V_new, data.test, data.mean_rating, data.min_rating, data.max_rating, S
        )
        burned = (new_sweep > cfg.burn_in).astype(jnp.int32)
        pred_sum = pred_sum + preds * burned
        pred_n = pred_n + burned
        r_sample = rmse(preds, data.test.vals)
        avg = pred_sum / jnp.maximum(pred_n, 1).astype(jnp.float32)
        r_avg = jnp.where(pred_n > 0, rmse(avg, data.test.vals), r_sample)
    return U_new, V_new, hyper_U, hyper_V, new_sweep, pred_sum, pred_n, r_sample, r_avg


def _sweep_device_fn(
    key: jax.Array,
    U_loc: jax.Array,
    V_loc: jax.Array,
    sweep: jax.Array,
    pred_sum: jax.Array,
    pred_n: jax.Array,
    data: DistBPMFData,
    cfg: BPMFConfig,
) -> tuple[jax.Array, jax.Array, HyperParams, HyperParams, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-sweep ``shard_map`` body (legacy entry point)."""
    U, V, hU, hV, sweep, pred_sum, pred_n, r_sample, r_avg = _sweep_step_device(
        key, U_loc, V_loc, sweep, pred_sum, pred_n, data, cfg
    )
    return U, V, hU, hV, sweep, pred_sum, pred_n, jnp.stack([r_sample, r_avg])


def _sweep_block_device_fn(
    key: jax.Array,
    U_loc: jax.Array,
    V_loc: jax.Array,
    hyper_U: HyperParams,
    hyper_V: HyperParams,
    sweep: jax.Array,
    pred_sum: jax.Array,
    pred_n: jax.Array,
    accum: PosteriorAccum,  # local shard slices (windows sliced on axis 1)
    data: DistBPMFData,
    cfg: BPMFConfig,
    block_size: int,
) -> tuple[jax.Array, jax.Array, HyperParams, HyperParams, jax.Array, jax.Array, jax.Array, PosteriorAccum, jax.Array]:
    """``block_size`` sweeps in one on-device ``lax.scan`` (DESIGN.md §10).

    The posterior accumulator shards travel in the scan carry next to the
    factor shards they summarize: each device folds only its local rows, so
    accumulation adds zero communication and zero host traffic. The burn-in
    gate is the traced ``sweep > burn_in`` predicate — blocks may straddle
    burn-in. Per-sweep ``[3]`` metric rows stack into the ``[block_size, 3]``
    ys output, the block's single host transfer.
    """

    def body(carry, _):
        U, V, hU, hV, sw, ps, pn, ac = carry
        U, V, hU, hV, sw, ps, pn, r_sample, r_avg = _sweep_step_device(
            key, U, V, sw, ps, pn, data, cfg
        )
        ac = update_posterior_accum(ac, U, V, sw > cfg.burn_in)
        row = jnp.stack([r_sample, r_avg, sw.astype(jnp.float32)])
        return (U, V, hU, hV, sw, ps, pn, ac), row

    init = (U_loc, V_loc, hyper_U, hyper_V, sweep, pred_sum, pred_n, accum)
    (U, V, hU, hV, sw, ps, pn, ac), metrics = jax.lax.scan(
        body, init, None, length=block_size
    )
    return U, V, hU, hV, sw, ps, pn, ac, metrics


# --------------------------------------------------------------------------
# Public API
# --------------------------------------------------------------------------


def make_ring_mesh(devices: Sequence[jax.Device] | None = None) -> Mesh:
    """1-D ring mesh over all (or the given) devices.

    ``jax.devices()`` is the *global*, process-major device list, so after
    ``jax.distributed.initialize`` this mesh spans every process — shard d
    is addressable by process ``d // local_device_count``. The logical mesh
    (and therefore the compiled per-shard program) is identical however the
    devices are split across processes.
    """
    devices = list(devices) if devices is not None else jax.devices()
    return Mesh(np.asarray(devices), (RING_AXIS,))


def init_dist_state(
    key: jax.Array, data: DistBPMFData, cfg: BPMFConfig, mesh: Mesh
) -> DistState:
    """Prior-predictive init, bitwise-identical per original item id to
    `gibbs.init_state` (both key rows by original id via fold_in)."""
    from repro.core.gibbs import init_rows

    ku, kv = jax.random.split(key)
    dt = cfg.sample_dtype
    sharding = NamedSharding(mesh, P(RING_AXIS))
    init = jax.jit(functools.partial(init_rows, K=cfg.K, dtype=dt), out_shardings=sharding)
    U = init(ku, data.users.orig_ids)
    V = init(kv, data.movies.orig_ids)
    # replicated like the block's outputs, so the second block reuses the
    # first block's executable instead of compiling for new input shardings
    rep = replicate(mesh, (HyperParams.init(cfg.K, dt), HyperParams.init(cfg.K, dt),
                           jnp.zeros((), jnp.int32)))
    return DistState(U=U, V=V, hyper_U=rep[0], hyper_V=rep[1], sweep=rep[2])


def replicate(mesh: Mesh, tree):
    """Place every leaf of ``tree`` replicated over ``mesh``."""
    rep = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda x: place_global(x, rep), tree)


def _bucket_specs(side: RingSide) -> RingSide:
    """PartitionSpec tree matching RingSide: all flat leading axes ring-sharded."""
    ring = P(RING_AXIS)
    steps = tuple(
        tuple(Bucket(item_ids=ring, nbr=ring, val=ring, nnz=ring) for _ in bs)
        for bs in side.steps
    )
    return RingSide(steps=steps, orig_ids=ring, cap=side.cap, num_items=side.num_items)


def data_specs(data: DistBPMFData) -> DistBPMFData:
    rep = P()
    return DistBPMFData(
        users=_bucket_specs(data.users),
        movies=_bucket_specs(data.movies),
        test=DistTestSet(rows=rep, cols=rep, vals=rep),
        mean_rating=rep,
        num_shards=data.num_shards,
        min_rating=data.min_rating,
        max_rating=data.max_rating,
    )


def place_global(x, sharding: NamedSharding) -> jax.Array:
    """Place one host leaf under ``sharding``, multi-process aware.

    ``device_put`` of a host array requires every device to be addressable;
    in a multi-process mesh each process instead supplies only its local
    shards via ``make_array_from_callback``. :class:`LocalShardedArray`
    leaves (per-host builds) can *only* go through the callback path — the
    callback is invoked per addressable shard, which is exactly the row
    range the process materialized.
    """
    if isinstance(x, LocalShardedArray):
        return x.place(sharding)
    if jax.process_count() > 1:
        arr = np.asarray(x)
        return jax.make_array_from_callback(arr.shape, sharding, lambda idx: arr[idx])
    return jax.device_put(x, sharding)


def shard_data(data: DistBPMFData, mesh: Mesh) -> DistBPMFData:
    """Place the host-built data with its ring sharding."""
    specs = data_specs(data)
    return jax.tree_util.tree_map(
        lambda x, s: place_global(x, NamedSharding(mesh, s)),
        data,
        specs,
        is_leaf=lambda x: isinstance(x, (jax.Array, jnp.ndarray)) or hasattr(x, "shape"),
    )


@functools.partial(jax.jit, static_argnames=("cfg", "mesh"))
def dist_gibbs_sweep(
    key: jax.Array,
    state: DistState,
    pred_state: PredictionState,
    data: DistBPMFData,
    cfg: BPMFConfig,
    mesh: Mesh,
) -> tuple[DistState, PredictionState, SweepMetrics]:
    """jit entry point: one distributed sweep over the ring mesh."""
    ring = P(RING_AXIS)
    rep = P()
    hyper_spec = HyperParams(mu=rep, Lam=rep)

    fn = shard_map(
        functools.partial(_sweep_device_fn, cfg=cfg),
        mesh=mesh,
        in_specs=(
            rep,  # key
            ring,  # U
            ring,  # V
            rep,  # sweep
            rep,  # pred_sum (replicated test preds)
            rep,  # pred_n
            data_specs(data),
        ),
        out_specs=(ring, ring, hyper_spec, hyper_spec, rep, rep, rep, rep),
        check_vma=False,
    )
    U, V, hU, hV, sweep, psum_, pn, r = fn(
        key, state.U, state.V, state.sweep, pred_state.sum_pred, pred_state.num_samples, data
    )
    new_state = DistState(U=U, V=V, hyper_U=hU, hyper_V=hV, sweep=sweep)
    new_pred = PredictionState(sum_pred=psum_, num_samples=pn)
    return new_state, new_pred, SweepMetrics(r[0], r[1], sweep)


def accum_specs() -> PosteriorAccum:
    """PartitionSpec tree for the sharded posterior accumulator.

    Sums are ring-sharded like the factor shards they summarize; the
    rotating windows shard their *item* axis (axis 1) the same way, with the
    window axis replicated; ``count`` is replicated.
    """
    ring = P(RING_AXIS)
    return PosteriorAccum(
        U_sum=ring, V_sum=ring, count=P(), filled=P(),
        U_window=P(None, RING_AXIS), V_window=P(None, RING_AXIS),
    )


def init_dist_accum(
    data: DistBPMFData, cfg: BPMFConfig, mesh: Mesh, keep: int
) -> PosteriorAccum:
    """Zeroed posterior accumulator in the relabeled sharded layout.

    Sums/windows cover every slot of the ``[S*cap, K]`` shards (pad slots
    accumulate garbage that the host view never reads — ``gather_factors``'
    permutation only touches real items).
    """
    num_u = data.users.orig_ids.shape[0]
    num_v = data.movies.orig_ids.shape[0]
    accum = PosteriorAccum.init(num_u, num_v, cfg.K, keep)
    specs = accum_specs()
    return jax.tree_util.tree_map(
        lambda x, s: place_global(x, NamedSharding(mesh, s)), accum, specs
    )


def _dist_gibbs_sweep_block(
    key: jax.Array,
    state: DistState,
    pred_state: PredictionState,
    accum: PosteriorAccum,
    data: DistBPMFData,
    cfg: BPMFConfig,
    mesh: Mesh,
    block_size: int,
) -> tuple[DistState, PredictionState, PosteriorAccum, jax.Array]:
    """jit entry point: ``block_size`` distributed sweeps, one host sync.

    One ``shard_map`` enter/exit per block wraps the on-device scan of
    :func:`_sweep_block_device_fn`; factors, prediction sums and the
    posterior accumulator stay sharded on-device for the whole block.
    Returns per-sweep metrics as a replicated ``[block_size, 3]`` f32 array
    of ``(rmse_sample, rmse_avg, sweep)`` rows.
    """
    ring = P(RING_AXIS)
    rep = P()
    hyper_spec = HyperParams(mu=rep, Lam=rep)

    fn = shard_map(
        functools.partial(_sweep_block_device_fn, cfg=cfg, block_size=block_size),
        mesh=mesh,
        in_specs=(
            rep,  # key
            ring,  # U
            ring,  # V
            hyper_spec,
            hyper_spec,
            rep,  # sweep
            rep,  # pred_sum (replicated test preds)
            rep,  # pred_n
            accum_specs(),
            data_specs(data),
        ),
        out_specs=(ring, ring, hyper_spec, hyper_spec, rep, rep, rep, accum_specs(), rep),
        check_vma=False,
    )
    U, V, hU, hV, sweep, psum_, pn, accum, metrics = fn(
        key, state.U, state.V, state.hyper_U, state.hyper_V, state.sweep,
        pred_state.sum_pred, pred_state.num_samples, accum, data,
    )
    new_state = DistState(U=U, V=V, hyper_U=hU, hyper_V=hV, sweep=sweep)
    new_pred = PredictionState(sum_pred=psum_, num_samples=pn)
    return new_state, new_pred, accum, metrics


dist_gibbs_sweep_block = jax.jit(
    _dist_gibbs_sweep_block, static_argnames=("cfg", "mesh", "block_size")
)

#: Carry-donating variant of :func:`dist_gibbs_sweep_block` (same traced
#: body, same samples): donates the sharded state / prediction / posterior
#: accumulator inputs so each block's carry reuses the previous block's
#: shard buffers instead of doubling peak factor memory per device
#: (DESIGN.md §13). Donated inputs are consumed — callers that re-read a
#: block's inputs must use the non-donating entry point
#: (``BackendConfig.donate_blocks="off"``).
dist_gibbs_sweep_block_donated = jax.jit(
    _dist_gibbs_sweep_block,
    static_argnames=("cfg", "mesh", "block_size"),
    donate_argnums=(1, 2, 3),
)


def run_distributed(
    key: jax.Array,
    data: DistBPMFData,
    cfg: BPMFConfig,
    mesh: Mesh | None = None,
    callback=None,
) -> tuple[DistState, PredictionState, list[SweepMetrics]]:
    """Driver: init, shard, sweep ``cfg.num_sweeps`` times."""
    mesh = mesh or make_ring_mesh()
    k_init, k_run = jax.random.split(key)
    data = shard_data(data, mesh)
    state = init_dist_state(k_init, data, cfg, mesh)
    pred_state = PredictionState.init(data.test.rows.shape[0])
    history: list[SweepMetrics] = []
    for _ in range(cfg.num_sweeps):
        state, pred_state, metrics = dist_gibbs_sweep(k_run, state, pred_state, data, cfg, mesh)
        history.append(jax.tree_util.tree_map(float, metrics))
        if callback is not None:
            callback(state, metrics)
    return state, pred_state, history


def fetch_global(x) -> np.ndarray:
    """Host copy of a (possibly multi-process) jax array.

    ``np.asarray`` works for fully-addressable arrays; arrays sharded across
    processes go through ``process_allgather`` — a collective, so every
    process of the job must call this together.
    """
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    return np.asarray(x)


def gather_factors(
    state: DistState, plan: DistPlan
) -> tuple[np.ndarray, np.ndarray]:
    """Undo the relabeling: return (U, V) in original item order (host numpy)."""
    U = fetch_global(state.U)
    V = fetch_global(state.V)
    return U[plan.part_users.perm], V[plan.part_movies.perm]
