"""MovieLens / ChEMBL loaders with synthetic fallback (offline container).

``load_movielens`` parses the real ml-20m ``ratings.csv`` or ml-100k
``u.data`` formats when a path is given; otherwise it generates a
distribution-matched synthetic stand-in (documented in DESIGN.md §6).
"""
from __future__ import annotations

import itertools
import os

import numpy as np

from repro.data.sparse import ChunkedRatings, RatingsCOO
from repro.data.synthetic import CHEMBL_LIKE, ML20M_LIKE, ML100K_LIKE, synthetic_ratings
from repro.utils import logger

_CSV_CHUNK_ROWS = 1_000_000  # ~72 MB peak per chunk vs ~GBs for one-shot parse


def _iter_rating_chunks(
    path: str,
    *,
    delimiter: str | None,
    skip_header: int,
    chunk_rows: int,
    with_times: bool = False,
):
    """Yield ``(col0, col1, vals)`` raw-id chunks of a 3+-column rating file
    (``with_times``: and the fourth column, unix seconds, as int64).

    Parsing ``chunk_rows`` lines at a time bounds peak memory by the chunk
    size regardless of file length; chunk boundaries are deterministic
    (every ``chunk_rows`` non-blank source lines), which the per-host data
    loader relies on for its seeded per-chunk train/test split.
    """
    with open(path) as f:
        for _ in range(skip_header):
            f.readline()
        while True:
            lines = list(itertools.islice(f, chunk_rows))
            if not lines:
                break
            lines = [ln for ln in lines if ln.strip()]
            if not lines:  # chunk of blank lines (e.g. trailing newlines)
                continue
            cols = (0, 1, 2, 3) if with_times else (0, 1, 2)
            chunk = np.atleast_2d(
                np.genfromtxt(lines, delimiter=delimiter, usecols=cols, dtype=np.float64)
            )
            if chunk.size == 0:
                continue
            out = (
                chunk[:, 0].astype(np.int64),
                chunk[:, 1].astype(np.int64),
                chunk[:, 2].astype(np.float32),
            )
            yield out + (chunk[:, 3].astype(np.int64),) if with_times else out


def _read_rating_chunks(
    path: str,
    *,
    delimiter: str | None,
    skip_header: int,
    chunk_rows: int,
    with_times: bool = False,
) -> tuple[np.ndarray, ...]:
    """Materialize :func:`_iter_rating_chunks` into full arrays.

    The previous one-shot ``np.genfromtxt`` materialized the whole file as an
    ``[nnz, ncols]`` float64 table (plus the raw text) before any downcast —
    a multi-GB transient on ml-20m-scale inputs. Chunked parsing bounds the
    transient by the chunk size, with byte-identical output.

    Returns:
        ``(col0, col1, vals)`` — raw int64 ids and float32 ratings — and
        with ``with_times`` the int64 timestamps.
    """
    cols = None
    for chunk in _iter_rating_chunks(
        path, delimiter=delimiter, skip_header=skip_header, chunk_rows=chunk_rows,
        with_times=with_times,
    ):
        cols = [[] for _ in chunk] if cols is None else cols
        for acc, c in zip(cols, chunk):
            acc.append(c)
    if cols is None:
        raise ValueError(f"no ratings parsed from {path!r}")
    return tuple(np.concatenate(c) for c in cols)


def month_bins(timestamps: np.ndarray) -> tuple[np.ndarray, int]:
    """Calendar month of each unix timestamp (UTC), counted from the first
    month present: ``(int32 bins, number of months spanned)``."""
    months = timestamps.astype("datetime64[s]").astype("datetime64[M]").astype(np.int64)
    bins = months - months.min()
    return bins.astype(np.int32), int(bins.max()) + 1


def _parse_ratings_csv(path: str, chunk_rows: int = _CSV_CHUNK_ROWS,
                       times: bool = False) -> RatingsCOO:
    """ml-20m ratings.csv: userId,movieId,rating,timestamp (with header);
    ``times`` keeps the timestamp as each rating's calendar month."""
    users_raw, movies_raw, vals, *stamps = _read_rating_chunks(
        path, delimiter=",", skip_header=1, chunk_rows=chunk_rows, with_times=times
    )
    _, users = np.unique(users_raw, return_inverse=True)
    _, movies = np.unique(movies_raw, return_inverse=True)
    bins, num_times = month_bins(stamps[0]) if times else (None, 0)
    return RatingsCOO(
        users.astype(np.int32), movies.astype(np.int32), vals,
        int(users.max()) + 1, int(movies.max()) + 1, bins, num_times,
    )


def _parse_udata(path: str, chunk_rows: int = _CSV_CHUNK_ROWS) -> RatingsCOO:
    """ml-100k u.data: user \t item \t rating \t timestamp."""
    users_raw, movies_raw, vals = _read_rating_chunks(
        path, delimiter=None, skip_header=0, chunk_rows=chunk_rows
    )
    users = users_raw - 1
    movies = movies_raw - 1
    return RatingsCOO(
        users.astype(np.int32), movies.astype(np.int32), vals,
        int(users.max()) + 1, int(movies.max()) + 1,
    )


def load_movielens_chunked(
    path: str | None = None,
    variant: str = "ml-100k",
    chunk_rows: int = _CSV_CHUNK_ROWS,
) -> ChunkedRatings:
    """Streaming loader for the per-host data path: no full rating arrays.

    Two-pass protocol over the file: a scan pass derives the global id maps
    (the sorted set of raw user/movie ids, matching ``np.unique``'s inverse
    mapping in the one-shot loader bitwise) and the rating count; the
    returned :class:`ChunkedRatings` then re-reads the file in bounded
    chunks on every iteration, remapping raw ids per chunk via
    ``np.searchsorted``. Peak memory is O(chunk + num ids) per process.
    Falls back to chunking the synthetic stand-in when ``path`` is missing.
    """
    if not (path and os.path.exists(path)):
        logger.info("movielens file not found, generating %s-shaped synthetic data", variant)
        spec = ML20M_LIKE if variant == "ml-20m" else ML100K_LIKE
        coo, _ = synthetic_ratings(spec)
        return coo.chunked(chunk_rows)

    is_csv = path.endswith(".csv")
    delimiter = "," if is_csv else None
    skip_header = 1 if is_csv else 0

    uniq_u = np.zeros(0, dtype=np.int64)
    uniq_m = np.zeros(0, dtype=np.int64)
    nnz = 0
    for c0, c1, _ in _iter_rating_chunks(
        path, delimiter=delimiter, skip_header=skip_header, chunk_rows=chunk_rows
    ):
        uniq_u = np.union1d(uniq_u, c0)
        uniq_m = np.union1d(uniq_m, c1)
        nnz += len(c0)
    if not nnz:
        raise ValueError(f"no ratings parsed from {path!r}")

    if is_csv:  # ml-20m: dense remap via the sorted id set (== np.unique inverse)
        num_users, num_movies = len(uniq_u), len(uniq_m)

        def remap(c0, c1):
            return (
                np.searchsorted(uniq_u, c0).astype(np.int32),
                np.searchsorted(uniq_m, c1).astype(np.int32),
            )
    else:  # ml-100k u.data: ids are 1-based and already dense
        num_users, num_movies = int(uniq_u.max()), int(uniq_m.max())

        def remap(c0, c1):
            return (c0 - 1).astype(np.int32), (c1 - 1).astype(np.int32)

    def gen():
        for c0, c1, v in _iter_rating_chunks(
            path, delimiter=delimiter, skip_header=skip_header, chunk_rows=chunk_rows
        ):
            rows, cols = remap(c0, c1)
            yield RatingsCOO(rows, cols, v, num_users, num_movies)

    return ChunkedRatings(
        chunk_fn=gen, num_users=num_users, num_movies=num_movies,
        nnz=nnz, chunk_rows=chunk_rows,
    )


def load_movielens(path: str | None = None, variant: str = "ml-100k",
                   times: bool = False) -> RatingsCOO:
    """Ratings of a MovieLens file, or a synthetic stand-in; ``times`` keeps
    each rating's calendar month (``ratings.csv`` only)."""
    if path and os.path.exists(path):
        if path.endswith(".csv"):
            return _parse_ratings_csv(path, times=times)
        if times:
            raise ValueError("calendar months are read from an ml-20m ratings.csv only")
        return _parse_udata(path)
    if times:
        raise ValueError(f"no MovieLens file at {path!r} to read rating times from")
    logger.info("movielens file not found, generating %s-shaped synthetic data", variant)
    spec = ML20M_LIKE if variant == "ml-20m" else ML100K_LIKE
    coo, _ = synthetic_ratings(spec)
    return coo


def load_chembl(path: str | None = None) -> RatingsCOO:
    """ChEMBL IC50 subset (compound x target pIC50). Synthetic fallback."""
    if path and os.path.exists(path):
        data = np.loadtxt(path, delimiter=",", dtype=np.float64)
        rows = data[:, 0].astype(np.int32)
        cols = data[:, 1].astype(np.int32)
        vals = data[:, 2].astype(np.float32)
        return RatingsCOO(rows, cols, vals, int(rows.max()) + 1, int(cols.max()) + 1)
    logger.info("chembl file not found, generating ChEMBL-shaped synthetic data")
    coo, _ = synthetic_ratings(CHEMBL_LIKE)
    return coo
