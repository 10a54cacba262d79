"""Sparse rating-matrix utilities: COO/CSR conversion and nnz-bucketing.

Bucketing is the SPMD replacement for the paper's work stealing: items are
grouped by rating count into power-of-two padded buckets so that each bucket
is one dense gather + Gram contraction. Padding waste is bounded by 2x per
item and is typically ~20-30% on MovieLens/ChEMBL-shaped skew (measured in
benchmarks/fig2_item_update.py).

Ratings may carry a time bin (``RatingsCOO.times``), which the tensor model
(BPTF) factorizes as a third side: each of its sides' bucket slots then
holds two neighbour indices, one of each other side.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Iterator, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core.types import BPMFData, Bucket, BucketedSide, TestSet
from repro.utils import next_power_of_two


@dataclasses.dataclass(frozen=True)
class RatingsCOO:
    """Raw ratings in coordinate format (host numpy)."""

    rows: np.ndarray  # [nnz] int32 user ids
    cols: np.ndarray  # [nnz] int32 movie ids
    vals: np.ndarray  # [nnz] float32 ratings
    num_users: int
    num_movies: int
    times: np.ndarray | None = None  # [nnz] int32 time bin of each rating, if any
    num_times: int = 0

    def __post_init__(self) -> None:
        t = self.times
        if t is not None and t.size and (t.min() < 0 or t.max() >= self.num_times):
            raise ValueError(
                f"time bins must be in [0, {self.num_times}), got [{t.min()}, {t.max()}]"
            )

    @property
    def nnz(self) -> int:
        return int(self.rows.shape[0])

    def transpose(self) -> "RatingsCOO":
        return dataclasses.replace(self, rows=self.cols, cols=self.rows,
                                   num_users=self.num_movies, num_movies=self.num_users)

    def select(self, keep: np.ndarray) -> "RatingsCOO":
        """The ratings where ``keep`` (a mask or index array) selects."""
        times = None if self.times is None else self.times[keep]
        return dataclasses.replace(self, rows=self.rows[keep], cols=self.cols[keep],
                                   vals=self.vals[keep], times=times)

    def without_times(self) -> "RatingsCOO":
        return dataclasses.replace(self, times=None, num_times=0)

    def chunked(self, chunk_rows: int = 1_000_000) -> "ChunkedRatings":
        """View this in-memory COO as a re-iterable chunk stream (for tests
        and synthetic datasets feeding the per-host loading path)."""

        def gen() -> Iterator[RatingsCOO]:
            for lo in range(0, max(self.nnz, 1), chunk_rows):
                hi = min(lo + chunk_rows, self.nnz)
                if hi > lo:
                    yield RatingsCOO(
                        self.rows[lo:hi], self.cols[lo:hi], self.vals[lo:hi],
                        self.num_users, self.num_movies,
                    )

        return ChunkedRatings(
            chunk_fn=gen, num_users=self.num_users, num_movies=self.num_movies,
            nnz=self.nnz, chunk_rows=chunk_rows,
        )


@dataclasses.dataclass(frozen=True)
class ChunkedRatings:
    """Re-iterable bounded-memory rating stream with known global dims.

    ``chunk_fn`` returns a *fresh* iterator of :class:`RatingsCOO` chunks on
    every call (the per-host plan builder makes two passes). Chunks must
    arrive in a deterministic order with at most ``chunk_rows`` ratings each
    — the chunk boundaries are part of the data contract, because the
    deterministic per-chunk train/test split consumes the seeded RNG stream
    sequentially.
    """

    chunk_fn: Callable[[], Iterator[RatingsCOO]]
    num_users: int
    num_movies: int
    nnz: int
    chunk_rows: int

    def chunks(self) -> Iterator[RatingsCOO]:
        return self.chunk_fn()

    def materialize(self) -> RatingsCOO:
        """Concatenate the stream (for backends without a per-host path)."""
        rows, cols, vals = [], [], []
        for c in self.chunks():
            rows.append(c.rows)
            cols.append(c.cols)
            vals.append(c.vals)
        empty = np.zeros(0)
        return RatingsCOO(
            np.concatenate(rows) if rows else empty.astype(np.int32),
            np.concatenate(cols) if cols else empty.astype(np.int32),
            np.concatenate(vals) if vals else empty.astype(np.float32),
            self.num_users, self.num_movies,
        )


#: Block size for :class:`StableMeanAccumulator` — the mean is defined as a
#: function of fixed value-position blocks, never of caller chunk boundaries.
MEAN_BLOCK = 1 << 20


class StableMeanAccumulator:
    """Streaming mean whose result is independent of feed chunk sizes.

    Values are regrouped into fixed ``MEAN_BLOCK``-sized position blocks;
    each complete block is summed with ``np.sum(..., dtype=float64)`` and the
    block sums are combined with ``math.fsum``. Any chunking of the same
    value sequence therefore produces bitwise-identical means — the property
    the per-host data loader needs to agree with the in-memory builder.
    """

    def __init__(self) -> None:
        self._buf: list[np.ndarray] = []
        self._pending = 0
        self._sums: list[float] = []
        self._count = 0

    def add(self, vals: np.ndarray) -> "StableMeanAccumulator":
        vals = np.asarray(vals, dtype=np.float32)
        self._count += len(vals)
        self._buf.append(vals)
        self._pending += len(vals)
        if self._pending >= MEAN_BLOCK:
            cat = np.concatenate(self._buf)
            while len(cat) >= MEAN_BLOCK:
                self._sums.append(float(np.sum(cat[:MEAN_BLOCK], dtype=np.float64)))
                cat = cat[MEAN_BLOCK:]
            self._buf = [cat]
            self._pending = len(cat)
        return self

    def mean(self) -> float:
        if not self._count:
            return 0.0
        sums = list(self._sums)
        if self._pending:
            tail = np.concatenate(self._buf)
            sums.append(float(np.sum(tail, dtype=np.float64)))
        return math.fsum(sums) / self._count


def stable_mean(vals: np.ndarray) -> float:
    """Chunking-invariant mean of a float32 array (see StableMeanAccumulator)."""
    return StableMeanAccumulator().add(vals).mean()


def csr_from_coo(
    rows: np.ndarray, cols: np.ndarray, vals: np.ndarray, num_items: int,
    cols2: np.ndarray | None = None,
) -> tuple[np.ndarray, ...]:
    """(indptr, indices, values) CSR over ``rows``; columns sorted within rows.

    With ``cols2`` (a second index per rating), its entries in the same
    order come fourth.
    """
    order = np.lexsort((cols, rows))
    r, c, v = rows[order], cols[order], vals[order]
    counts = np.bincount(r, minlength=num_items)
    indptr = np.zeros(num_items + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    out = (indptr, c.astype(np.int32), v.astype(np.float32))
    return out if cols2 is None else out + (cols2[order].astype(np.int32),)


def _concat_ranges(lengths: np.ndarray) -> np.ndarray:
    """[0..l0-1, 0..l1-1, ...] without a python loop."""
    total = int(lengths.sum())
    if total == 0:
        return np.zeros(0, dtype=np.int64)
    starts = np.zeros(len(lengths), dtype=np.int64)
    np.cumsum(lengths[:-1], out=starts[1:])
    return np.arange(total, dtype=np.int64) - np.repeat(starts, lengths)


def pad_group(
    ids: np.ndarray,
    indptr: np.ndarray,
    indices: np.ndarray,
    values: np.ndarray,
    pad: int,
    indices2: np.ndarray | None = None,
) -> Bucket:
    """Densify the CSR rows ``ids`` into a [B, pad] padded bucket (with
    ``indices2``, a second neighbour index per slot in ``nbr2``)."""
    ids = np.asarray(ids, dtype=np.int64)
    B = len(ids)
    nnz = (indptr[ids + 1] - indptr[ids]).astype(np.int64)
    if np.any(nnz > pad):
        raise ValueError(f"item with nnz {nnz.max()} does not fit pad {pad}")
    nbr = np.zeros((B, pad), dtype=np.int32)
    val = np.zeros((B, pad), dtype=np.float32)
    within = _concat_ranges(nnz)
    flat_dst = np.repeat(np.arange(B, dtype=np.int64) * pad, nnz) + within
    src = np.repeat(indptr[ids], nnz) + within
    nbr.reshape(-1)[flat_dst] = indices[src]
    val.reshape(-1)[flat_dst] = values[src]
    nbr2 = None
    if indices2 is not None:
        nbr2 = np.zeros((B, pad), dtype=np.int32)
        nbr2.reshape(-1)[flat_dst] = indices2[src]
        nbr2 = jnp.asarray(nbr2)
    return Bucket(
        item_ids=jnp.asarray(ids, jnp.int32),
        nbr=jnp.asarray(nbr),
        val=jnp.asarray(val),
        nnz=jnp.asarray(nnz, jnp.int32),
        nbr2=nbr2,
    )


def bucket_assignment(nnz: np.ndarray, pads: Sequence[int]) -> dict[int, np.ndarray]:
    """Map pad size -> item ids. Items above the largest pad get pow2 pads."""
    pads = sorted(pads)
    out: dict[int, list[np.ndarray]] = {}
    prev = -1
    for p in pads:
        sel = np.nonzero((nnz > prev) & (nnz <= p))[0]
        if sel.size:
            out.setdefault(p, []).append(sel)
        prev = p
    big = np.nonzero(nnz > pads[-1])[0]
    if big.size:
        for i in big:
            p = next_power_of_two(int(nnz[i]))
            out.setdefault(p, []).append(np.array([i]))
    return {p: np.concatenate(v) for p, v in out.items()}


def bucketize_side(
    indptr: np.ndarray,
    indices: np.ndarray,
    values: np.ndarray,
    pads: Sequence[int],
    *,
    include_empty: bool = True,
    indices2: np.ndarray | None = None,
) -> BucketedSide:
    """Bucket every CSR row (item) by nnz into padded dense groups.

    Items with zero ratings still get sampled (from the prior conditional),
    so they are included in the smallest bucket by default. ``indices2``
    gives each rating a second neighbour (:func:`pad_group`).
    """
    num_items = len(indptr) - 1
    nnz = (indptr[1:] - indptr[:-1]).astype(np.int64)
    items = np.arange(num_items)
    if not include_empty:
        items = items[nnz > 0]
    assign = bucket_assignment(nnz[items], pads)
    buckets = []
    for pad in sorted(assign):
        ids = items[assign[pad]]
        buckets.append(pad_group(ids, indptr, indices, values, pad, indices2))
    return BucketedSide(buckets=tuple(buckets), num_items=num_items)


def train_test_split(
    coo: RatingsCOO, test_fraction: float, seed: int
) -> tuple[RatingsCOO, RatingsCOO]:
    rng = np.random.default_rng(seed)
    t = rng.random(coo.nnz) < test_fraction
    return coo.select(~t), coo.select(t)


def build_bpmf_data(
    coo: RatingsCOO,
    pads: Sequence[int] = (8, 32, 128, 512, 2048),
    test_fraction: float = 0.1,
    seed: int = 0,
    min_rating: float | None = None,
    max_rating: float | None = None,
) -> BPMFData:
    """Full host-side pipeline: split, center, bucket both sides (and the
    time side, when the ratings carry times)."""
    train, test = train_test_split(coo, test_fraction, seed)
    lo = float(coo.vals.min()) if min_rating is None else min_rating
    hi = float(coo.vals.max()) if max_rating is None else max_rating
    return build_bpmf_data_presplit(train, test, pads, min_rating=lo, max_rating=hi)


def build_bpmf_data_presplit(
    train: RatingsCOO,
    test: RatingsCOO,
    pads: Sequence[int] = (8, 32, 128, 512, 2048),
    mean_rating: float | None = None,
    min_rating: float | None = None,
    max_rating: float | None = None,
) -> BPMFData:
    """Center and bucket an already-split (train, test) pair.

    The split-free tail of :func:`build_bpmf_data`, exposed so callers that
    partition the ratings *after* a global split — the ``posterior_merge``
    backend gives each chain a user-subset of one shared split — can build
    per-subset :class:`BPMFData` with globally consistent centering and
    clipping (pass the global ``mean_rating`` / ``min_rating`` /
    ``max_rating`` explicitly; defaults derive them from the pair given).
    """
    mean = (
        (float(train.vals.mean()) if train.nnz else 0.0)
        if mean_rating is None
        else float(mean_rating)
    )
    centered = train.vals - mean

    times, test_times = None, None
    if train.times is None:
        u_indptr, u_idx, u_val = csr_from_coo(train.rows, train.cols, centered, train.num_users)
        m_indptr, m_idx, m_val = csr_from_coo(train.cols, train.rows, centered, train.num_movies)
        users = bucketize_side(u_indptr, u_idx, u_val, pads)
        movies = bucketize_side(m_indptr, m_idx, m_val, pads)
    else:
        # each side's neighbours are the two other sides: users (movie,
        # time), movies (user, time), time bins (user, movie)
        r, c, t = train.rows, train.cols, train.times
        sides = []
        for items, nbrs, nbrs2, n in ((r, c, t, train.num_users), (c, r, t, train.num_movies),
                                      (t, r, c, train.num_times)):
            indptr, idx, val, idx2 = csr_from_coo(items, nbrs, centered, n, nbrs2)
            sides.append(bucketize_side(indptr, idx, val, pads, indices2=idx2))
        users, movies, times = sides
        test_times = jnp.asarray(test.times, jnp.int32)

    all_vals = np.concatenate([train.vals, test.vals]) if train.nnz or test.nnz else None
    lo = (float(all_vals.min()) if all_vals is not None else -np.inf) \
        if min_rating is None else min_rating
    hi = (float(all_vals.max()) if all_vals is not None else np.inf) \
        if max_rating is None else max_rating
    return BPMFData(
        users=users,
        movies=movies,
        test=TestSet(
            rows=jnp.asarray(test.rows, jnp.int32),
            cols=jnp.asarray(test.cols, jnp.int32),
            vals=jnp.asarray(test.vals, jnp.float32),
            times=test_times,
        ),
        mean_rating=jnp.asarray(mean, jnp.float32),
        num_users=train.num_users,
        num_movies=train.num_movies,
        min_rating=lo,
        max_rating=hi,
        times=times,
        num_times=train.num_times if times is not None else 0,
    )
