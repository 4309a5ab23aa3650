"""Parallel query processing on SFC-partitioned point data (paper §V-A).

* Exact point location — queries are keyed by bit-interleaving their
  coordinates and binary-searched against the sorted keys; an in-run scan
  finds the exact match. O(log N) per query, vectorized over the batch.
* k-nearest neighbors — locate the query's bucket, then search the
  CUTOFF-neighborhood of buckets along the curve (the paper restricts
  CUTOFF to one bucket before/after) and select the k smallest distances.

Both run against a shared :class:`repro.core.curve_index.CurveIndex`
(built cold here, or refreshed incrementally from a ``Repartitioner``'s
cached keys). Every key search is ``jnp.searchsorted`` — over the
bucket directory to locate a query's bucket, over the live sorted keys
to bound its key-equal run.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import curve_index as _ci

# The index type is shared with the repartitioning engine and the
# partitioner; ``QueryIndex`` remains as a compatibility alias.
CurveIndex = _ci.CurveIndex
QueryIndex = _ci.CurveIndex


def build_index(
    points: jax.Array,
    ids: jax.Array | None = None,
    *,
    bucket_size: int = 32,
    bits: int | None = None,
) -> CurveIndex:
    """Cold-build a query index: Morton key-gen + sort + bucket carve.

    Uses Morton (the paper's point-location fast path works 'only with
    Morton SFC': key search needs key order == curve order, which the
    closed-form Morton keys give directly). Incremental consumers should
    prefer ``Repartitioner.curve_index()``, which reuses cached keys.
    """
    return _ci.build(points, ids, bucket_size=bucket_size, bits=bits, curve="morton")


def _rank(index: CurveIndex, qk: jax.Array, side: str) -> jax.Array:
    """``searchsorted`` over the live keys: clamped to ``n_valid`` so a
    query never counts the sentinel tail of dead slots."""
    r = jnp.searchsorted(index.keys, qk, side=side).astype(jnp.int32)
    return jnp.minimum(r, index.valid_count())


@jax.jit
def _locate_bucket(index: CurveIndex, queries: jax.Array) -> jax.Array:
    qk = _ci.query_keys(index, queries)
    b = jnp.searchsorted(index.bucket_keys, qk, side="right").astype(jnp.int32) - 1
    return jnp.clip(b, 0, index.num_buckets - 1)


def locate_bucket(index: CurveIndex, queries: jax.Array) -> jax.Array:
    """Bucket id per query: the last bucket whose first key is <= the
    query's key (clamped to 0)."""
    return _locate_bucket(index, queries)


class PointLocation(NamedTuple):
    found: jax.Array  # (q,) bool — exact coordinate match located
    ids: jax.Array    # (q,) int32 global/slot id, -1 when not found
    ok: jax.Array     # (q,) bool — False iff the key-equal run exceeded
    #                   bucket_cap without a hit, i.e. the miss is not
    #                   certified (raise bucket_cap to resolve)


@functools.partial(jax.jit, static_argnames=("bucket_cap",))
def _point_location(index: CurveIndex, queries: jax.Array, bucket_cap: int) -> PointLocation:
    qk = _ci.query_keys(index, queries)
    # Exact extent of the key-equal run in the sorted key array. Equal
    # coordinates imply equal keys, so every possible match lies in
    # [lo_i, hi_i) — unlike a single-bucket scan, this cannot silently
    # miss when duplicates spill a bucket (runs spanning bucket or even
    # partition boundaries are covered).
    lo_i = _rank(index, qk, "left")
    hi_i = _rank(index, qk, "right")
    run = hi_i - lo_i
    n = index.capacity
    offs = jnp.arange(bucket_cap, dtype=jnp.int32)
    pos = lo_i[:, None] + offs[None, :]
    cand = jnp.clip(pos, 0, n - 1)                              # (q, cap)
    cpts = index.points[cand]                                    # (q, cap, d)
    hit = jnp.all(cpts == queries[:, None, :], axis=-1) & (pos < hi_i[:, None])
    found = jnp.any(hit, axis=1)
    slot = jnp.argmax(hit, axis=1)
    gid = index.ids[cand[jnp.arange(queries.shape[0]), slot]].astype(jnp.int32)
    ok = found | (run <= bucket_cap)
    return PointLocation(found, jnp.where(found, gid, -1), ok)


def point_location(
    index: CurveIndex,
    queries: jax.Array,
    *,
    bucket_cap: int = 64,
) -> PointLocation:
    """Exact point location: (found, id or -1, ok).

    ``ok[i]`` is False only when query i missed *and* more than
    ``bucket_cap`` stored points share its SFC key (duplicate-heavy
    distributions) — the scan window was exhausted, so the miss is not a
    certificate of absence.
    """
    return _point_location(index, queries, bucket_cap)


@functools.partial(
    jax.jit, static_argnames=("k", "cutoff_buckets", "max_window")
)
def _knn(
    index: CurveIndex,
    queries: jax.Array,
    k: int,
    cutoff_buckets: int,
    max_window: int,
) -> tuple[jax.Array, jax.Array]:
    nb = index.num_buckets
    n = index.capacity
    b = _locate_bucket(index, queries)
    b0 = jnp.clip(b - cutoff_buckets, 0, nb - 1)
    b1 = jnp.clip(b + cutoff_buckets, 0, nb - 1)
    start = index.bucket_starts[b0]
    end = index.bucket_starts[b1 + 1]
    # Candidate window sized from the directory's true maximum bucket
    # extent (static metadata) — a fixed per-bucket cap undercovers
    # whenever carving produces buckets larger than the cap. max_window
    # bounds the (q, win, d) candidate tensor: one degenerate bucket
    # (duplicate-heavy cell) must not OOM the whole batch.
    win = max(k, min(n, index.max_bucket_len * (2 * cutoff_buckets + 1), max_window))
    offs = jnp.arange(win, dtype=jnp.int32)
    pos = start[:, None] + offs[None, :]
    cand = jnp.clip(pos, 0, n - 1)
    valid = pos < end[:, None]
    cpts = index.points[cand]
    d2 = jnp.sum((cpts - queries[:, None, :]) ** 2, axis=-1)
    d2 = jnp.where(valid, d2, jnp.inf)
    neg_top, idx = jax.lax.top_k(-d2, k)
    gids = index.ids[jnp.take_along_axis(cand, idx, axis=1)].astype(jnp.int32)
    return jnp.sqrt(-neg_top), gids


def knn(
    index: CurveIndex,
    queries: jax.Array,
    *,
    k: int = 3,
    cutoff_buckets: int = 1,
    max_window: int = 1024,
) -> tuple[jax.Array, jax.Array]:
    """Approximate k-NN: search the query's bucket ± cutoff_buckets along
    the curve (paper: 'CUTOFF restricted to one bucket before and after').

    The candidate window covers the true bucket extents up to
    ``max_window`` slots per query — raise it for duplicate-heavy data
    where one bucket exceeds that (at (q, max_window, d) memory cost).

    Returns (distances (q, k), global ids (q, k)).
    """
    return _knn(index, queries, k, cutoff_buckets, max_window)


def knn_bruteforce(points: jax.Array, queries: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Oracle for tests (O(nq) memory — small inputs only)."""
    d2 = jnp.sum((queries[:, None, :] - points[None, :, :]) ** 2, axis=-1)
    neg_top, idx = jax.lax.top_k(-d2, k)
    return jnp.sqrt(-neg_top), idx
