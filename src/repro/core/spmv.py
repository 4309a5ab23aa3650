"""General graph partitioning + distributed SpMV (paper §V-B).

A graph's adjacency matrix is partitioned by treating each nonzero (i, j)
as a 2-D point and running the SFC partitioner; the dense vector is
greedily partitioned into contiguous *owned* chunks. Every process derives
its *dependent* vector intervals from its nonzero set; partial products
are combined with reduce-scatter over per-chunk communication trees. A
one-pass *spanning set* improvement re-assigns chunk ownership to the
process with maximum overlap (ties -> min id), exactly as in the paper.

Reported metrics (paper Tables II–VII): AvgLoad, MaxLoad, MaxDegree (max
messages per process), MaxEdgeCut (max communication volume per process).
Baseline: row-wise decomposition (fixed rows per process).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

import numpy as np


@dataclass(frozen=True)
class SparsePartition:
    part_of_nnz: np.ndarray    # (nnz,) process owning each nonzero
    chunk_owner: np.ndarray    # (P,) process owning x-chunk c (spanning set)
    chunk_bounds: np.ndarray   # (P+1,) x index boundaries of chunks
    num_parts: int


# ---------------------------------------------------------------------------
# Partitioning strategies
# ---------------------------------------------------------------------------

def rowwise_partition(rows: np.ndarray, n: int, num_parts: int) -> np.ndarray:
    """Baseline: fixed number of rows per process."""
    rows_per = int(np.ceil(n / num_parts))
    return np.minimum(rows // rows_per, num_parts - 1).astype(np.int32)


def sfc_partition(
    rows: np.ndarray,
    cols: np.ndarray,
    n: int,
    num_parts: int,
    *,
    curve: str | None = None,
    weights: np.ndarray | None = None,
    cfg: "object | None" = None,
) -> np.ndarray:
    """SFC partition of nonzeros as 2-D points (row, col).

    Routed through ``partitioner.partition`` — SpMV rides the shared
    pipeline (Pallas key-gen kernels via ``cfg.use_pallas``, the bucket
    tree path via ``cfg.use_tree``) instead of a private key-gen →
    argsort → knapsack copy. ``cfg`` replaces the default 16-bit
    configuration wholesale (including its curve), so combining it with
    an explicit ``curve=`` is a conflict and raises — pass the curve
    inside the cfg instead. ``curve`` alone defaults to "hilbert"."""
    from repro.core import partitioner as _pt

    if cfg is not None and curve is not None:
        raise ValueError(
            "sfc_partition: pass either curve= or cfg=, not both — cfg "
            f"replaces the whole configuration (cfg.curve={cfg.curve!r} "
            f"would silently win over curve={curve!r})"
        )
    pts = jnp.stack(
        [jnp.asarray(rows, jnp.float32), jnp.asarray(cols, jnp.float32)], axis=1
    )
    if cfg is None:
        cfg = _pt.PartitionerConfig(curve=curve or "hilbert", bits=16)
    w = None if weights is None else jnp.asarray(weights, jnp.float32)
    res = _pt.partition(pts, w, num_parts, cfg)
    return np.asarray(res.part)


def vector_chunks(n: int, num_parts: int) -> np.ndarray:
    """Contiguous, load-balanced owned chunks of the dense vector."""
    return (np.arange(num_parts + 1) * n) // num_parts


# ---------------------------------------------------------------------------
# Communication structure + spanning-set improvement
# ---------------------------------------------------------------------------

def _needs_matrix(
    part: np.ndarray, rows: np.ndarray, cols: np.ndarray, chunk_bounds: np.ndarray,
    num_parts: int,
) -> tuple[np.ndarray, np.ndarray]:
    """needs[p, c] = # distinct x entries of chunk c needed by process p;
    prod[p, c] = # distinct y entries of chunk c produced by process p."""
    chunk_of = lambda idx: np.searchsorted(chunk_bounds, idx, side="right") - 1
    col_chunk = chunk_of(cols)
    row_chunk = chunk_of(rows)
    needs = np.zeros((num_parts, num_parts), dtype=np.int64)
    prod = np.zeros((num_parts, num_parts), dtype=np.int64)
    # distinct (p, chunk, col) triples
    pc = np.unique(np.stack([part, col_chunk, cols], axis=1), axis=0)
    np.add.at(needs, (pc[:, 0], pc[:, 1]), 1)
    pr = np.unique(np.stack([part, row_chunk, rows], axis=1), axis=0)
    np.add.at(prod, (pr[:, 0], pr[:, 1]), 1)
    return needs, prod


def improve_spanning_set(
    needs: np.ndarray, prod: np.ndarray, num_parts: int
) -> np.ndarray:
    """One improvement pass (paper): chunk c is owned by the process with
    maximum overlap (needs + produces); ties broken by minimum id."""
    overlap = needs + prod  # (P, C)
    owner = np.argmax(overlap, axis=0).astype(np.int32)  # argmax → min id on ties
    return owner


def communication_metrics(
    part: np.ndarray,
    rows: np.ndarray,
    cols: np.ndarray,
    n: int,
    num_parts: int,
    *,
    improve: bool = True,
) -> dict:
    """Paper Tables II–VII metrics for a given nonzero partition.

    Thin wrapper: derives the chunked communication structure (needs /
    produces / spanning-set owner) from the nonzero partition, then
    reports through the shared ``metrics.spanning_communication_metrics``
    implementation (one table-metric code path for mesh, graph, SpMV).
    """
    from repro.core import metrics as _metrics

    chunk_bounds = vector_chunks(n, num_parts)
    needs, prod = _needs_matrix(part, rows, cols, chunk_bounds, num_parts)
    owner = (
        improve_spanning_set(needs, prod, num_parts)
        if improve
        else np.arange(num_parts, dtype=np.int32)
    )
    return _metrics.spanning_communication_metrics(part, needs, prod, owner, num_parts)


# ---------------------------------------------------------------------------
# Executable distributed SpMV (shard_map reduce-scatter)
# ---------------------------------------------------------------------------

def spmv_reference(rows, cols, vals, x, n):
    """Dense oracle y = A x."""
    y = jnp.zeros((n,), dtype=jnp.result_type(vals, x))
    return y.at[jnp.asarray(rows)].add(jnp.asarray(vals) * x[jnp.asarray(cols)])


def distributed_spmv(
    mesh: jax.sharding.Mesh,
    axis: str,
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    part: np.ndarray,
    x: jax.Array,
    n: int,
):
    """Execute y = A x with nonzeros distributed per ``part``.

    Each shard computes partial sums for its nonzeros, then a
    reduce-scatter (psum_scatter) combines partials and leaves each shard
    its owned y-chunk — the paper's reduce + scatter of vector
    subintervals. nnz lists are padded to equal length per shard.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    nshards = mesh.shape[axis]
    # pad each shard's nnz to the max count
    counts = np.bincount(part, minlength=nshards)
    cap = int(counts.max())
    r_p = np.zeros((nshards, cap), dtype=np.int32)
    c_p = np.zeros((nshards, cap), dtype=np.int32)
    v_p = np.zeros((nshards, cap), dtype=np.float32)
    for p in range(nshards):
        sel = part == p
        k = int(sel.sum())
        r_p[p, :k] = rows[sel]
        c_p[p, :k] = cols[sel]
        v_p[p, :k] = vals[sel]  # padding has val=0 → no contribution

    n_pad = int(np.ceil(n / nshards)) * nshards
    sh = NamedSharding(mesh, P(axis))
    r_d = jax.device_put(jnp.asarray(r_p).reshape(nshards * cap), sh)
    c_d = jax.device_put(jnp.asarray(c_p).reshape(nshards * cap), sh)
    v_d = jax.device_put(jnp.asarray(v_p).reshape(nshards * cap), sh)
    x_pad = jnp.zeros((n_pad,), jnp.float32).at[:n].set(x)

    def kernel(r, c, v, xf):
        y_partial = jnp.zeros((n_pad,), jnp.float32).at[r].add(v * xf[c])
        mine = jax.lax.psum_scatter(y_partial, axis, scatter_dimension=0, tiled=True)
        return mine

    # shard_map must run under jit: eager execution dispatches every
    # traced op as its own SPMD program (see partitioner._reslice_fn)
    fn = jax.jit(jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P()),
        out_specs=P(axis),
        check_vma=False,
    ))
    y = fn(r_d, c_d, v_d, x_pad)
    return y[:n]


# ---------------------------------------------------------------------------
# Synthetic power-law graphs (SNAP stand-ins; offline container)
# ---------------------------------------------------------------------------

def powerlaw_graph(
    n: int, avg_degree: int, alpha: float = 2.1, seed: int = 0
) -> tuple[np.ndarray, np.ndarray]:
    """Directed power-law graph in COO (rows, cols), no self loops.

    Zipf out-degrees (the paper's social-network test cases follow the
    power law [23]); endpoints preferentially attached by degree weight.
    """
    rng = np.random.default_rng(seed)
    raw = rng.zipf(alpha, size=n).astype(np.int64)
    deg = np.minimum(raw * avg_degree // max(int(raw.mean()), 1), n // 2)
    deg = np.maximum(deg, 1)
    src = np.repeat(np.arange(n), deg)
    # preferential attachment for destinations
    w = deg.astype(np.float64) / deg.sum()
    dst = rng.choice(n, size=src.shape[0], p=w)
    keep = src != dst
    return src[keep].astype(np.int32), dst[keep].astype(np.int32)
