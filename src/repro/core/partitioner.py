"""The geometric partitioner — the paper's primary contribution, as a
composable JAX module.

Pipeline (paper §III): hierarchical decomposition → SFC ordering →
greedy-knapsack load balancing. The single-device path is pure jnp; the
distributed path runs under ``shard_map`` with a sample-sort (local sort →
sampled splitters → all_to_all exchange → local merge) and a global
weighted prefix for the knapsack slice — computation cost comparable to a
parallel sort, as the paper claims.

The partitioner requires unique global ids and returns a *permutation* of
those ids plus a part assignment; re-ordering the payload is left to the
application (paper §I), with `repro.core.migration` providing the
bounded-message exchange plan.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Literal, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import kdtree as _kdtree
from repro.core import knapsack as _knapsack
from repro.core import sfc as _sfc


@dataclass(frozen=True)
class HierarchyPlan:
    """First-class description of the two-level (node -> device) mesh.

    The paper's partitioner is *hybrid*: distributed across nodes,
    multi-threaded within a node. On a JAX mesh that is a 2-D
    ``(node_axis, device_axis)`` decomposition: a coarse knapsack assigns
    curve slices to nodes, then each node independently re-knapsacks its
    slice across ``devices_per_node`` local parts. ``num_nodes == 1`` is
    the flat path — every flat entry point delegates to the hierarchy
    with this trivial top level.

    ``inter_node_cost`` is the migration-cost multiplier for bytes that
    cross the node boundary (DCN vs ICI); ``summary_bins`` bounds the
    records each node contributes to the inter-node summary exchange
    (default: the per-shard bucket count, so the exchange is
    O(B * nodes), not O(B * devices)).

    Coupling to a mesh: ``num_nodes`` MUST equal the node axis size
    (the per-node aggregation happens on that axis — validated), while
    ``devices_per_node`` is the per-node *part* fan-out and is
    deliberately decoupled from the device axis size, exactly as the
    flat path's ``num_parts`` has always been decoupled from its shard
    count (parts are logical curve slices; only `apply_repartition`
    requires part ids to name real shards).
    """

    num_nodes: int = 1
    devices_per_node: int = 1
    node_axis: str = "node"
    device_axis: str = "device"
    inter_node_cost: float = 4.0
    summary_bins: int | None = None

    def __post_init__(self):
        if self.num_nodes < 1 or self.devices_per_node < 1:
            raise ValueError(f"degenerate hierarchy: {self}")

    @property
    def num_parts(self) -> int:
        return self.num_nodes * self.devices_per_node

    def node_of_part(self, part):
        """Node owning a (scalar or array) global part id."""
        return part // self.devices_per_node


class HierarchicalResult(NamedTuple):
    """Two-level partition: everything `PartitionResult` carries, plus the
    node level. ``part = node * devices_per_node + device`` everywhere."""

    part: jax.Array            # (n,) global part per ORIGINAL element
    node: jax.Array            # (n,) node id per ORIGINAL element
    keys: jax.Array            # (n,) SFC key (bucket-granular on the tree path)
    boundaries: jax.Array      # (P+1,) point-level slice starts per part
    node_boundaries: jax.Array  # (N+1,) point-level slice starts per node
    loads: jax.Array           # (P,) weight per part
    node_loads: jax.Array      # (N,) weight per node
    plan: HierarchyPlan
    # tree-path extras (None on the point path), as in PartitionResult:
    perm: jax.Array | None = None
    tree: "_kdtree.LinearKdTree | None" = None
    summary: "_kdtree.BucketSummary | None" = None
    bucket_order: "_kdtree.BucketOrder | None" = None
    bucket_rank: jax.Array | None = None
    bucket_part: jax.Array | None = None   # (M,) part per tree node
    bucket_node: jax.Array | None = None   # (M,) node per tree node


class PartitionResult(NamedTuple):
    perm: jax.Array | None  # (n,) int32 ids in SFC order; None on the tree
    #                         path (no per-point sort ran — see
    #                         ``materialize_perm``)
    part: jax.Array        # (n,) int32: part id per ORIGINAL element index
    keys: jax.Array        # (n,) uint32 (or (n,w)) SFC key per original element
    #                        (bucket-granular on the tree path)
    boundaries: jax.Array  # (P+1,) slice starts into the SFC order
    loads: jax.Array       # (P,) weight per part
    # tree-path extras (None on the point path):
    tree: "_kdtree.LinearKdTree | None" = None
    summary: "_kdtree.BucketSummary | None" = None
    bucket_order: "_kdtree.BucketOrder | None" = None
    bucket_rank: jax.Array | None = None   # (n,) int32 curve rank of each
    #                                        point's bucket
    bucket_part: jax.Array | None = None   # (M,) int32 part per tree node


def materialize_perm(res: PartitionResult) -> jax.Array:
    """Physical curve-order permutation of a ``PartitionResult``.

    The point path carries it already; the tree path deliberately never
    sorts points, so consumers that must reorder a payload (index
    materialization, migration staging) pay the one stable argsort of
    int32 bucket ranks here — outside the partition hot loop."""
    if res.perm is not None:
        return res.perm
    if res.bucket_rank is None:
        raise ValueError("result carries neither a permutation nor bucket ranks")
    return _kdtree.tree_perm(res.bucket_rank).astype(jnp.int32)


@dataclass(frozen=True)
class PartitionerConfig:
    curve: Literal["morton", "hilbert"] = "hilbert"
    stats: Literal["geometric", "rank"] = "geometric"
    bits: int | None = None
    words: int = 1
    splitter: _kdtree.Splitter = "midpoint"
    bucket_size: int = 32
    max_depth: int = 16
    use_tree: bool = False        # order via kd-tree buckets (paper's full path)
    use_pallas: bool = False      # use the Pallas key-gen kernels

    def __post_init__(self):
        if self.use_pallas and self.words != 1:
            raise ValueError("the Pallas key-gen kernels emit single-word keys: "
                             f"use_pallas=True needs words=1, got words={self.words}")


def _keys_for(points: jax.Array, cfg: PartitionerConfig) -> jax.Array:
    if cfg.use_pallas:
        from repro.kernels import ops as _kops

        if cfg.curve == "morton":
            return _kops.morton_key(points, cfg.bits, stats=cfg.stats)
        return _kops.hilbert_key(points, cfg.bits, stats=cfg.stats)
    fn = _sfc.morton_key if cfg.curve == "morton" else _sfc.hilbert_key
    return fn(points, cfg.bits, stats=cfg.stats, words=cfg.words)


def _point_order(points: jax.Array, cfg: PartitionerConfig) -> tuple[jax.Array, jax.Array]:
    """Point-path curve order: (perm, keys). The ONE key-gen + sort
    prelude shared by the flat and hierarchical partitions (so the
    (1, D)-is-bit-identical invariant cannot drift)."""
    if cfg.use_pallas:
        # Pallas key-gen kernels; same keys as the jnp path — asserted by
        # test_pallas_path_matches_jnp
        keys = _keys_for(points, cfg)
        return _sfc.argsort_keys(keys), keys
    return _sfc.sfc_order(
        points, curve=cfg.curve, bits=cfg.bits, stats=cfg.stats, words=cfg.words
    )


def _bucket_stage(
    tree: "_kdtree.LinearKdTree",
    points: jax.Array,
    weights: jax.Array,
    cfg: PartitionerConfig,
    summary: "_kdtree.BucketSummary | None" = None,
    frame: tuple[jax.Array, jax.Array] | None = None,
):
    """Tree-path prelude shared by the flat and hierarchical partitions:
    bucket summaries keyed + SFC-sorted on one frame. Returns
    (summary, border, w_rank, bits) with ``w_rank`` the bucket weights
    in curve order — the knapsack input of every tree-backed slice."""
    bits = cfg.bits if cfg.bits is not None else _sfc.max_bits_per_dim(points.shape[1])
    if summary is None:
        summary = _kdtree.bucket_summary(tree, points, weights)
    if frame is None:
        frame = (tree.bbox_lo[0], tree.bbox_hi[0])
    border = _kdtree.bucket_order(
        summary, frame_lo=frame[0], frame_hi=frame[1], bits=bits, curve=cfg.curve
    )
    return summary, border, summary.weight[border.order], bits


def partition(
    points: jax.Array,
    weights: jax.Array | None = None,
    num_parts: int = 8,
    cfg: PartitionerConfig = PartitionerConfig(),
) -> PartitionResult:
    """Single-process partition of (n, d) points into ``num_parts``.

    ``cfg.use_tree=True`` runs the paper's full pipeline (tree build →
    bucket statistics → bucket SFC order → knapsack over bucket
    weights): the partition is computed entirely from O(B) bucket
    summaries, each point inheriting its bucket's part through a
    ``leaf_id`` gather — **no O(n)-length sort runs** (``res.perm`` is
    None; see ``materialize_perm``). Otherwise the closed-form SFC keys
    order the points directly (per-element balance granularity, at the
    cost of an O(n) key sort every call).
    """
    n, d = points.shape
    if weights is None:
        weights = jnp.ones((n,), dtype=jnp.float32)

    if cfg.use_tree:
        tree = _kdtree.build(
            points,
            weights,
            max_depth=cfg.max_depth,
            bucket_size=cfg.bucket_size,
            splitter=cfg.splitter,
        )
        return partition_buckets(tree, points, weights, num_parts, cfg)

    perm, keys = _point_order(points, cfg)
    w_sorted = weights[perm]
    part_sorted = _knapsack.slice_weighted_curve(w_sorted, num_parts)
    boundaries = _knapsack.part_boundaries(w_sorted, num_parts)
    loads = _knapsack.part_loads(w_sorted, part_sorted, num_parts)
    # scatter part ids back to original element order
    part = jnp.zeros((n,), dtype=jnp.int32).at[perm].set(part_sorted)
    return PartitionResult(perm=perm, part=part, keys=keys, boundaries=boundaries, loads=loads)


def partition_buckets(
    tree: "_kdtree.LinearKdTree",
    points: jax.Array,
    weights: jax.Array | None = None,
    num_parts: int = 8,
    cfg: PartitionerConfig = PartitionerConfig(),
    *,
    summary: "_kdtree.BucketSummary | None" = None,
    frame: tuple[jax.Array, jax.Array] | None = None,
) -> PartitionResult:
    """Knapsack partition over an existing tree's bucket statistics.

    The shared core of every tree-backed layer: the local path builds a
    tree and calls this; the incremental engine calls it on its cached
    tree after a delta; the distributed path runs the same math on
    all_gathered summaries. All device work is O(B) plus gathers.
    """
    n = points.shape[0]
    if weights is None:
        weights = jnp.ones((n,), dtype=jnp.float32)
    summary, border, w_rank, _bits = _bucket_stage(
        tree, points, weights, cfg, summary=summary, frame=frame
    )
    M = summary.num_nodes
    # knapsack over bucket weights in curve order (non-buckets carry 0
    # weight and sentinel keys, so they sit inert at the tail)
    part_rank = _knapsack.slice_weighted_curve(w_rank, num_parts)
    loads = _knapsack.part_loads(w_rank, part_rank, num_parts)
    bucket_part = jnp.zeros((M,), jnp.int32).at[border.order].set(part_rank)
    # points inherit their bucket's rank/part/key — gathers only
    part = bucket_part[tree.leaf_id]
    rank_pp = border.rank[tree.leaf_id]
    keys_pp = border.node_keys[tree.leaf_id]
    # point-level slice starts: first curve index of the first bucket of
    # each part (part_rank is non-decreasing along the rank axis)
    first_rank = jnp.searchsorted(
        part_rank, jnp.arange(num_parts, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)
    boundaries = jnp.concatenate(
        [border.starts[first_rank], jnp.array([n], dtype=jnp.int32)]
    )
    return PartitionResult(
        perm=None,
        part=part,
        keys=keys_pp,
        boundaries=boundaries,
        loads=loads,
        tree=tree,
        summary=summary,
        bucket_order=border,
        bucket_rank=rank_pp,
        bucket_part=bucket_part,
    )


def hierarchical_partition(
    points: jax.Array,
    weights: jax.Array | None = None,
    plan: HierarchyPlan = HierarchyPlan(),
    cfg: PartitionerConfig = PartitionerConfig(use_tree=True),
) -> HierarchicalResult:
    """Single-process two-level partition of (n, d) points.

    Two nested applications of the flat core over ONE frozen frame and
    ONE curve order: the coarse knapsack assigns curve slices to
    ``plan.num_nodes`` nodes, then each node's slice is independently
    re-knapsacked into ``plan.devices_per_node`` parts
    (`knapsack.two_level_slice`). On the tree path both levels slice the
    same O(B) bucket weights; on the point path, the same sorted element
    weights. With ``num_nodes == 1`` the assignment is bit-identical to
    ``partition(..., num_parts=devices_per_node)`` — the flat partition
    is the trivial hierarchy.
    """
    n, d = points.shape
    if weights is None:
        weights = jnp.ones((n,), dtype=jnp.float32)
    N, D = plan.num_nodes, plan.devices_per_node

    if not cfg.use_tree:
        perm, keys = _point_order(points, cfg)
        w_sorted = weights[perm]
        node_s, _, part_s = _knapsack.two_level_slice(w_sorted, N, D)
        part = jnp.zeros((n,), jnp.int32).at[perm].set(part_s)
        node = jnp.zeros((n,), jnp.int32).at[perm].set(node_s)
        loads = _knapsack.part_loads(w_sorted, part_s, N * D)
        node_loads = _knapsack.part_loads(w_sorted, node_s, N)
        bounds = _level_boundaries(part_s, N * D, n)
        nbounds = _level_boundaries(node_s, N, n)
        return HierarchicalResult(
            part=part, node=node, keys=keys, boundaries=bounds,
            node_boundaries=nbounds, loads=loads, node_loads=node_loads,
            plan=plan, perm=perm,
        )

    tree = _kdtree.build(
        points, weights,
        max_depth=cfg.max_depth, bucket_size=cfg.bucket_size, splitter=cfg.splitter,
    )
    summary, border, w_rank, _bits = _bucket_stage(tree, points, weights, cfg)
    return _assemble_tree_hierarchy(
        tree, summary, border, w_rank,
        *_knapsack.two_level_slice(w_rank, N, D), plan, n,
    )


def hierarchical_reslice(
    res: HierarchicalResult,
    weights: jax.Array,
    *,
    level: Literal["full", "intra"] = "full",
) -> HierarchicalResult:
    """Re-slice an existing two-level partition under new weights, reusing
    the cached curve order (no key generation, no tree work, no sort).

    ``level="full"`` re-runs both knapsack levels; ``level="intra"``
    freezes the node assignment and re-knapsacks only the device slices
    inside each node — the cheap response to small drift, whose
    migrations are node-local by construction. Tree-path results
    re-aggregate live point weights onto the buckets (one segment_sum);
    point-path results re-slice the cached sorted order directly.
    """
    plan = res.plan
    N, D = plan.num_nodes, plan.devices_per_node
    n = res.part.shape[0]
    if res.tree is None:
        w_sorted = weights[res.perm]
        if level == "intra":
            node_s = res.node[res.perm]
            dev_s = _knapsack.device_slice_within_nodes(w_sorted, node_s, N, D)
            part_s = node_s * D + dev_s
        else:
            node_s, _, part_s = _knapsack.two_level_slice(w_sorted, N, D)
        part = jnp.zeros((n,), jnp.int32).at[res.perm].set(part_s)
        node = jnp.zeros((n,), jnp.int32).at[res.perm].set(node_s)
        return res._replace(
            part=part, node=node,
            loads=_knapsack.part_loads(w_sorted, part_s, N * D),
            node_loads=_knapsack.part_loads(w_sorted, node_s, N),
            boundaries=_level_boundaries(part_s, N * D, n),
            node_boundaries=_level_boundaries(node_s, N, n),
        )
    border = res.bucket_order
    M = border.order.shape[0]
    w_leaf = jax.ops.segment_sum(weights, res.tree.leaf_id, num_segments=M)
    w_rank = w_leaf[border.order]
    if level == "intra":
        node_rank = res.bucket_node[border.order]
        dev_rank = _knapsack.device_slice_within_nodes(w_rank, node_rank, N, D)
        part_rank = node_rank * D + dev_rank
    else:
        node_rank, _, part_rank = _knapsack.two_level_slice(w_rank, N, D)
    import dataclasses as _dc

    summary = _dc.replace(res.summary, weight=w_leaf)
    return _assemble_tree_hierarchy(
        res.tree, summary, border, w_rank, node_rank, None, part_rank, plan, n
    )


def _level_boundaries(level_sorted: jax.Array, num: int, n: int) -> jax.Array:
    """(num+1,) first sorted-order index of each slice (last entry = n)."""
    starts = jnp.searchsorted(
        level_sorted, jnp.arange(num, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)
    return jnp.concatenate([starts, jnp.array([n], dtype=jnp.int32)])


def _assemble_tree_hierarchy(
    tree, summary, border, w_rank, node_rank, dev_rank, part_rank, plan, n
) -> HierarchicalResult:
    """Scatter rank-order two-level assignments back to tree nodes and
    points — the shared tail of tree-path hierarchical (re)partitions."""
    del dev_rank  # implied by part_rank
    N, D = plan.num_nodes, plan.devices_per_node
    M = border.order.shape[0]
    loads = _knapsack.part_loads(w_rank, part_rank, N * D)
    node_loads = _knapsack.part_loads(w_rank, node_rank, N)
    bucket_part = jnp.zeros((M,), jnp.int32).at[border.order].set(part_rank)
    bucket_node = jnp.zeros((M,), jnp.int32).at[border.order].set(node_rank)
    part = bucket_part[tree.leaf_id]
    node = bucket_node[tree.leaf_id]
    rank_pp = border.rank[tree.leaf_id]
    keys_pp = border.node_keys[tree.leaf_id]
    first_rank = jnp.searchsorted(
        part_rank, jnp.arange(N * D, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)
    boundaries = jnp.concatenate(
        [border.starts[first_rank], jnp.array([n], dtype=jnp.int32)]
    )
    first_nrank = jnp.searchsorted(
        node_rank, jnp.arange(N, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)
    node_boundaries = jnp.concatenate(
        [border.starts[first_nrank], jnp.array([n], dtype=jnp.int32)]
    )
    return HierarchicalResult(
        part=part, node=node, keys=keys_pp, boundaries=boundaries,
        node_boundaries=node_boundaries, loads=loads, node_loads=node_loads,
        plan=plan, perm=None, tree=tree, summary=summary, bucket_order=border,
        bucket_rank=rank_pp, bucket_part=bucket_part, bucket_node=bucket_node,
    )


def partition_with_index(
    points: jax.Array,
    weights: jax.Array | None = None,
    num_parts: int = 8,
    cfg: PartitionerConfig = PartitionerConfig(),
    *,
    bucket_size: int = 32,
) -> tuple[PartitionResult, "object"]:
    """Partition and build the query-serving ``CurveIndex`` from ONE key
    generation: the index wraps the partition's keys and permutation, and
    ``result.boundaries`` indexes the same sorted order the index holds —
    ``curve_index.bucket_parts(index, result.boundaries)`` maps each
    directory bucket to its owning part.

    Returns (PartitionResult, CurveIndex). Point path: restricted to the
    configurations whose keys are addressable by query coordinates —
    geometric stats (rank re-keys by data order; a query point has no
    rank) and single-word keys. Tree path (``cfg.use_tree=True``): the
    index is **tree-backed** — its directory is exactly the tree's leaf
    buckets on the shared quantization frame, the one (O(B)) key
    generation is reused, and queries address it by the root→leaf walk.
    The only per-point costs are the rank argsort and gathers that
    materialize the sorted store.
    """
    from repro.core import curve_index as _ci

    if cfg.use_tree:
        res = partition(points, weights, num_parts, cfg)
        index = tree_index(res, points, cfg=cfg)
        return res, index
    if cfg.stats != "geometric" or cfg.words != 1:
        raise ValueError(
            "partition_with_index requires stats='geometric', words=1 "
            "(keys must be query-addressable)"
        )
    res = partition(points, weights, num_parts, cfg)
    bits = cfg.bits if cfg.bits is not None else _sfc.max_bits_per_dim(points.shape[1])
    index = _ci.from_partition(
        points, res.perm, res.keys, curve=cfg.curve, bits=bits, bucket_size=bucket_size
    )
    return res, index


def tree_index(
    res: PartitionResult,
    points: jax.Array,
    *,
    cfg: PartitionerConfig = PartitionerConfig(use_tree=True),
    version: int = 0,
    token: int = -1,
) -> "object":
    """Materialize the tree-backed ``CurveIndex`` from a tree-path
    ``PartitionResult``: points in bucket-major order, directory = tree
    leaf buckets, no new key generation (the partition's bucket keys ARE
    the index's keys). Bucket granularity is the tree's buckets."""
    from repro.core import curve_index as _ci

    if res.tree is None:
        raise ValueError("tree_index requires a tree-path PartitionResult")
    border = res.bucket_order
    perm = materialize_perm(res)
    nb = int(border.num_buckets)
    bits = cfg.bits if cfg.bits is not None else _sfc.max_bits_per_dim(points.shape[1])
    return _ci.from_buckets(
        points[perm],
        perm,
        res.keys[perm],
        border.starts[: nb + 1],
        border.node_keys[border.order[:nb]],
        frame_lo=res.tree.bbox_lo[0],
        frame_hi=res.tree.bbox_hi[0],
        bits=bits,
        curve=cfg.curve,
        version=version,
        token=token,
        tree=res.tree,
        node_keys=border.node_keys,
    )


# ---------------------------------------------------------------------------
# Distributed partition (shard_map sample-sort + global knapsack)
# ---------------------------------------------------------------------------

def _global_curve_slice(
    w_local: jax.Array,
    valid: jax.Array,
    axis: str,
    me: jax.Array,
    nshards: int,
    num_parts: int,
) -> jax.Array:
    """Greedy-knapsack slice of the *globally ordered* weighted curve.

    Runs inside shard_map: each shard holds a contiguous chunk of the
    curve (shard rank = curve rank). One all_gather of local weight sums
    gives every shard its exclusive global prefix; the slice itself is
    then local. This is the only collective a weight-only rebalance needs
    — the incremental path (`distributed_reslice`) calls it directly on
    cached keys, skipping key-gen and the sample-sort all_to_all.
    """
    w_masked = jnp.where(valid, w_local, 0.0)
    local_sum = jnp.sum(w_masked)
    sums = jax.lax.all_gather(local_sum, axis)  # (nshards,)
    offset = jnp.sum(jnp.where(jnp.arange(nshards) < me, sums, 0.0))
    total = jnp.sum(sums)
    prefix = offset + jnp.cumsum(w_masked) - w_masked
    ideal = jnp.maximum(total / num_parts, 1e-9)
    part = jnp.floor((prefix + 0.5 * w_masked) / ideal).astype(jnp.int32)
    part = jnp.clip(part, 0, num_parts - 1)
    return jnp.where(valid, part, -1)


def distributed_reslice(
    mesh: jax.sharding.Mesh,
    axis: str,
    weights_sorted: jax.Array,
    valid: jax.Array,
    num_parts: int,
) -> jax.Array:
    """Weight-only rebalance over an existing distributed curve order.

    ``weights_sorted``/``valid`` are laid out exactly as returned by
    `distributed_partition` (shard i holds the i-th contiguous chunk of
    the global SFC order; invalid = padding slots). Because the curve
    order is unchanged, no keys are generated and no sample-sort exchange
    runs — the cost is one all_gather of P scalars plus a local scan,
    versus the full partition's key-gen + sort + all_to_all.
    """
    return _reslice_fn(mesh, axis, num_parts)(weights_sorted, valid)


@functools.lru_cache(maxsize=64)
def _reslice_fn(mesh: jax.sharding.Mesh, axis: str, num_parts: int):
    """Jitted reslice executor, memoized per (mesh, axis, P).

    shard_map'd callables must run under jit: executed eagerly, every
    traced op dispatches as its own SPMD program (measured 42 s vs 2 s
    for the full partition kernel on 8 host devices). The lru_cache keeps
    the jitted closure alive so repeat calls hit jit's own cache.
    """
    nshards = mesh.shape[axis]

    def kernel(wts, val):
        me = jax.lax.axis_index(axis)
        return _global_curve_slice(wts, val, axis, me, nshards, num_parts)

    return jax.jit(jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    ))


def distributed_partition(
    mesh: jax.sharding.Mesh,
    axis: str,
    points: jax.Array,
    weights: jax.Array,
    num_parts: int,
    cfg: PartitionerConfig = PartitionerConfig(),
    oversample: int = 8,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Distributed SFC partition over mesh axis ``axis``.

    Input ``points`` (n, d) / ``weights`` (n,) are sharded on dim 0 across
    ``axis``. Returns (keys_sorted, weights_sorted, part_sorted) where the
    global concatenation over shards is in non-decreasing key order and
    ``part_sorted`` is the knapsack part id — i.e. shard i holds the i-th
    contiguous chunk of the global space-filling curve.

    Algorithm (the paper's distributed partitioner_init / point_order):
      1. local SFC keys
      2. sampled splitters (all_gather of a per-shard key sample, paper's
         "approximate median" applied across processes)
      3. all_to_all exchange into key ranges (fixed capacity + masking —
         the TPU analogue of MAX_MSG_SIZE rounds)
      4. local sort of received keys
      5. global weighted exclusive prefix (psum over lower-ranked shards)
         feeding the greedy-knapsack slice.
    """
    return _partition_fn(mesh, axis, num_parts, cfg, oversample)(points, weights)


@functools.lru_cache(maxsize=64)
def _partition_fn(
    mesh: jax.sharding.Mesh,
    axis: str,
    num_parts: int,
    cfg: PartitionerConfig,
    oversample: int,
):
    """Jitted sample-sort partition executor, memoized per static config
    (see `_reslice_fn` for why shard_map must run under jit)."""
    nshards = mesh.shape[axis]

    def kernel(pts, wts):
        # pts: (n_loc, d), wts: (n_loc,)
        n_loc = pts.shape[0]
        keys = _keys_for(pts, cfg)
        me = jax.lax.axis_index(axis)

        # --- sampled splitters -------------------------------------------
        samp_n = max(1, min(oversample * nshards, n_loc) // 1)
        stride = max(1, n_loc // samp_n)
        sample = jax.lax.sort(keys[::stride][:samp_n])
        all_samples = jax.lax.all_gather(sample, axis).reshape(-1)
        all_samples = jax.lax.sort(all_samples)
        m = all_samples.shape[0]
        # nshards-1 splitters at even quantiles
        qi = (jnp.arange(1, nshards) * m) // nshards
        splitters = all_samples[qi]

        # --- route to destination shards ---------------------------------
        dest = jnp.searchsorted(splitters, keys, side="right").astype(jnp.int32)
        # capacity per (src -> dst) lane; pad with sentinel keys
        cap = int(n_loc * 2 // nshards) + oversample * 4
        order = jnp.argsort(dest, stable=True)
        keys_s, wts_s, dest_s = keys[order], wts[order], dest[order]
        # position within destination bucket
        ones = jnp.ones_like(dest_s)
        pos_in_bucket = jnp.cumsum(ones) - 1
        bucket_start = jnp.searchsorted(dest_s, jnp.arange(nshards, dtype=jnp.int32))
        pos_in_bucket = pos_in_bucket - bucket_start[dest_s]
        SENT = jnp.uint32(0xFFFFFFFF)
        buf_k = jnp.full((nshards, cap), SENT, dtype=keys.dtype)
        buf_w = jnp.zeros((nshards, cap), dtype=wts.dtype)
        # out-of-capacity entries are dropped by mode="drop"; tests assert
        # the global valid count is conserved (capacity is ~2x fair share)
        idx = (dest_s, pos_in_bucket)
        buf_k = buf_k.at[idx].set(keys_s, mode="drop")
        buf_w = buf_w.at[idx].set(wts_s, mode="drop")

        # all_to_all: lane s of my buffer goes to shard s
        recv_k = jax.lax.all_to_all(buf_k, axis, split_axis=0, concat_axis=0, tiled=False)
        recv_w = jax.lax.all_to_all(buf_w, axis, split_axis=0, concat_axis=0, tiled=False)
        recv_k = recv_k.reshape(-1)
        recv_w = recv_w.reshape(-1)

        # --- local sort (sentinels go last) ------------------------------
        o2 = jnp.argsort(recv_k, stable=True)
        recv_k, recv_w = recv_k[o2], recv_w[o2]
        valid = recv_k != SENT

        # --- global weighted prefix + knapsack slice ----------------------
        part = _global_curve_slice(recv_w, valid, axis, me, nshards, num_parts)
        return recv_k, jnp.where(valid, recv_w, -1.0), part

    return jax.jit(jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis)),
        check_vma=False,
    ))


# ---------------------------------------------------------------------------
# Distributed bucket-summary exchange (tree path at scale)
#
# The sample-sort above moves O(n) raw points through an all_to_all every
# partition. The bucket path exchanges O(B) *summaries* instead: each
# shard builds a local kd-tree once, and every (re)partition after that
# is a summary gather, a tiny global sort of bucket records, the knapsack
# over bucket weights, and a leaf_id gather. Points never move for the
# computation ("point data follows its bucket" — the part assignment
# comes home, not the points), which is what makes the
# partition-recompute hot loop cheap (Borrell et al.'s aggregated-weights
# argument applied across shards).
#
# The exchange is HIERARCHICAL (paper's hybrid nodes-x-threads model,
# `HierarchyPlan`): the raw (M,) summaries are all_gathered intra-node
# only, and one inter-node exchange moves node-aggregated bins — the
# two-stage body lives in `distributed.sharding.two_stage_bucket_slice`.
# The flat entry points below delegate with the trivial (1, P) plan,
# which reduces bit-exactly to the single-stage gather + flat knapsack.
# ---------------------------------------------------------------------------

def _plan_axes(mesh: jax.sharding.Mesh, plan: HierarchyPlan) -> tuple[str, ...]:
    """Mesh axes a plan's kernels shard over. The node level is
    validated against the mesh (aggregation runs on that axis); the
    device level is a logical part fan-out and intentionally is not —
    see `HierarchyPlan`."""
    if plan.device_axis not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} lacks device axis {plan.device_axis!r}")
    if plan.num_nodes > 1 or plan.node_axis in mesh.axis_names:
        if mesh.shape.get(plan.node_axis, 1) != plan.num_nodes:
            raise ValueError(
                f"plan expects {plan.num_nodes} nodes on axis {plan.node_axis!r}; "
                f"mesh has {mesh.shape.get(plan.node_axis)}"
            )
        return (plan.node_axis, plan.device_axis)
    return (plan.device_axis,)


def hierarchical_bucket_partition(
    mesh: jax.sharding.Mesh,
    plan: HierarchyPlan,
    points: jax.Array,
    weights: jax.Array,
    cfg: PartitionerConfig = PartitionerConfig(use_tree=True),
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Cold two-level bucket-path distributed partition.

    Builds a local kd-tree per shard, keys its bucket centroids on ONE
    globally shared quantization frame (bbox all-reduced over every mesh
    axis), and runs the nested node->device knapsack over the two-stage
    summary exchange. Inputs are sharded on dim 0 over the plan's mesh
    axes (node-major); returns ``(part, leaf_id, node_keys)`` with
    ``part``/``leaf_id`` in the ORIGINAL element layout (elements do not
    move) and ``part = node * devices_per_node + device``. ``(leaf_id,
    node_keys)`` are the cached state that makes every later
    `hierarchical_bucket_reslice` O(B) in communication — O(B * nodes)
    of it inter-node.
    """
    return _hier_bucket_partition_fn(mesh, plan, cfg)(points, weights)


def hierarchical_bucket_reslice(
    mesh: jax.sharding.Mesh,
    plan: HierarchyPlan,
    leaf_id: jax.Array,
    weights: jax.Array,
    node_keys: jax.Array,
) -> jax.Array:
    """The partition-recompute hot loop: fresh two-level assignment for
    new weights over the cached per-shard trees.

    Local work is one segment_sum (points -> bucket weights) and one
    gather (bucket part -> point part); the communication is the
    two-stage summary exchange — raw summaries intra-node, aggregated
    bins inter-node. No key generation, no point sort, no all_to_all."""
    return _hier_bucket_reslice_fn(mesh, plan)(leaf_id, weights, node_keys)


def distributed_bucket_partition(
    mesh: jax.sharding.Mesh,
    axis: str,
    points: jax.Array,
    weights: jax.Array,
    num_parts: int,
    cfg: PartitionerConfig = PartitionerConfig(use_tree=True),
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Flat bucket-path distributed partition — the hierarchy with a
    trivial top level (``HierarchyPlan(1, num_parts, device_axis=axis)``);
    same contract as before: ``(part, leaf_id, node_keys)`` in the
    ORIGINAL element layout, one single-stage O(B) summary all_gather."""
    plan = HierarchyPlan(num_nodes=1, devices_per_node=num_parts, device_axis=axis)
    return hierarchical_bucket_partition(mesh, plan, points, weights, cfg)


def distributed_bucket_reslice(
    mesh: jax.sharding.Mesh,
    axis: str,
    leaf_id: jax.Array,
    weights: jax.Array,
    node_keys: jax.Array,
    num_parts: int,
) -> jax.Array:
    """Flat recompute hot loop — `hierarchical_bucket_reslice` with the
    trivial (1, P) plan: one O(B) summary all_gather, no key generation,
    no point sort, no all_to_all."""
    plan = HierarchyPlan(num_nodes=1, devices_per_node=num_parts, device_axis=axis)
    return hierarchical_bucket_reslice(mesh, plan, leaf_id, weights, node_keys)


@functools.lru_cache(maxsize=64)
def _hier_bucket_partition_fn(
    mesh: jax.sharding.Mesh, plan: HierarchyPlan, cfg: PartitionerConfig
):
    """Jitted cold bucket-partition executor (see `_reslice_fn` for why
    shard_map must run under jit)."""
    from repro.distributed import sharding as _shd

    axes = _plan_axes(mesh, plan)
    num_dev_shards = mesh.shape[plan.device_axis]

    def kernel(pts, wts):
        bits = cfg.bits if cfg.bits is not None else _sfc.max_bits_per_dim(pts.shape[1])
        # ONE shared quantization frame: the global bbox (reduced over
        # every mesh axis), so every shard's bucket keys live on the
        # same curve
        lo = jnp.min(jax.lax.all_gather(jnp.min(pts, axis=0), axes), axis=0)
        hi = jnp.max(jax.lax.all_gather(jnp.max(pts, axis=0), axes), axis=0)
        tree = _kdtree.build(
            pts,
            wts,
            max_depth=cfg.max_depth,
            bucket_size=cfg.bucket_size,
            splitter=cfg.splitter,
        )
        summary = _kdtree.bucket_summary(tree, pts, wts)
        node_keys = _kdtree.summary_keys(
            summary, frame_lo=lo, frame_hi=hi, bits=bits, curve=cfg.curve
        )
        bucket_part = _shd.two_stage_bucket_slice(
            summary.weight, node_keys, plan=plan, num_dev_shards=num_dev_shards
        )
        return bucket_part[tree.leaf_id], tree.leaf_id.astype(jnp.int32), node_keys

    spec = P(axes)
    return jax.jit(jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(spec, spec),
        out_specs=(spec, spec, spec),
        check_vma=False,
    ))


@functools.lru_cache(maxsize=64)
def _hier_bucket_reslice_fn(mesh: jax.sharding.Mesh, plan: HierarchyPlan):
    """Jitted two-level bucket-reslice executor, memoized per (mesh, plan)."""
    from repro.distributed import sharding as _shd

    axes = _plan_axes(mesh, plan)
    num_dev_shards = mesh.shape[plan.device_axis]

    def kernel(leaf_id, wts, node_keys):
        M = node_keys.shape[0]
        w_leaf = jax.ops.segment_sum(wts, leaf_id, num_segments=M)
        bucket_part = _shd.two_stage_bucket_slice(
            w_leaf, node_keys, plan=plan, num_dev_shards=num_dev_shards
        )
        return bucket_part[leaf_id]

    spec = P(axes)
    return jax.jit(jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    ))
