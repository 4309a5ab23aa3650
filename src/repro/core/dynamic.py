"""Dynamic partitioning and amortized load balancing (paper §IV).

Implements the paper's three dynamic-data mechanisms on the linearized
kd-tree:

* ``locate``/``insert``/``delete`` — the InsertDelete query path (walk
  split hyperplanes root→leaf, fully vectorized).
* ``adjustments`` — Algorithm 1: split *heavy* buckets (> 2*BUCKETSIZE),
  merge *light* sibling leaves (combined <= BUCKETSIZE), level-synchronous
  bottom-up/top-down passes instead of the paper's recursive DFS.
* ``AmortizedController`` — Algorithm 3's credit scheme: a load-balance
  phase banks credits equal to its cost; each iteration's *excess*
  computation cost (above the post-balance baseline) spends them; the next
  full balance triggers when credits are exhausted. The controller is a
  pure-python object reused by the MoE layer and the serving batcher.

Point storage uses fixed capacity + an ``active`` mask so every operation
is fixed-shape (XLA-friendly); this replaces the paper's concurrent
linked lists (see the hardware-adaptation table in ``DESIGN.md`` at the
repo root, which also documents how these primitives feed the
bucket-statistics partition pipeline: ``locate`` is the delta routing
step, ``adjustments`` repairs the bucket set before summaries are
re-keyed, and the tree counters maintained by insert/delete ARE the
incremental bucket statistics).
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro.core import kdtree as _kdtree
from repro.core.kdtree import LinearKdTree


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("points", "weights", "active", "leaf_id", "tree"),
    meta_fields=(),
)
@dataclasses.dataclass(frozen=True)
class DynamicPointSet:
    points: jax.Array   # (C, d) float32, C = capacity
    weights: jax.Array  # (C,) float32
    active: jax.Array   # (C,) bool
    leaf_id: jax.Array  # (C,) int32 heap id of owning leaf (undefined if !active)
    tree: LinearKdTree

    @property
    def capacity(self) -> int:
        return self.points.shape[0]

    def _replace(self, **kw) -> "DynamicPointSet":
        return dataclasses.replace(self, **kw)


def from_points(
    points: jax.Array,
    weights: jax.Array | None = None,
    *,
    capacity: int | None = None,
    max_depth: int = 14,
    bucket_size: int = 32,
    splitter: _kdtree.Splitter = "midpoint",
) -> DynamicPointSet:
    """Build the initial weighted kd-tree from archived data (paper §IV)."""
    n, d = points.shape
    if weights is None:
        weights = jnp.ones((n,), dtype=jnp.float32)
    capacity = capacity or 2 * n
    tree = _kdtree.build(
        points, weights, max_depth=max_depth, bucket_size=bucket_size, splitter=splitter
    )
    pts = jnp.zeros((capacity, d), dtype=jnp.float32).at[:n].set(points)
    wts = jnp.zeros((capacity,), dtype=jnp.float32).at[:n].set(weights)
    act = jnp.zeros((capacity,), dtype=bool).at[:n].set(True)
    lid = jnp.zeros((capacity,), dtype=jnp.int32).at[:n].set(tree.leaf_id)
    return DynamicPointSet(points=pts, weights=wts, active=act, leaf_id=lid, tree=tree)


@functools.partial(jax.jit, static_argnames=("max_depth",))
def locate(tree: LinearKdTree, pts: jax.Array, max_depth: int) -> jax.Array:
    """Vectorized root→leaf walk along split hyperplanes (InsertDelete /
    point-location path). Returns heap leaf id per query point.

    Each level gathers one row per point: the node's split dimension
    (-1 at a leaf) beside its split value's bits."""
    dim = jnp.where(tree.is_leaf | (tree.split_dim < 0), -1, tree.split_dim)
    table = jnp.stack([dim, jax.lax.bitcast_convert_type(tree.split_val, jnp.int32)], axis=1)

    def body(_, node):
        row = table[node]
        dim, val = row[:, 0], jax.lax.bitcast_convert_type(row[:, 1], jnp.float32)
        coord = pts[:, 0]
        for j in range(1, pts.shape[1]):
            coord = jnp.where(dim == j, pts[:, j], coord)
        nxt = 2 * node + 1 + (coord > val).astype(jnp.int32)
        return jnp.where(dim < 0, node, nxt)

    node0 = jnp.zeros((pts.shape[0],), dtype=jnp.int32)
    return jax.lax.fori_loop(0, max_depth, body, node0)


def padded_rows(k: int) -> int:
    """Rows a ``k``-row insert/delete batch is padded to: ``k`` itself
    below 16, else the least ``m * 2**e >= k`` with ``m`` in [8, 16).
    So a batch wastes less than ``k / 8`` rows, and the sizes a stream
    of varying batches compiles for are 8 an octave."""
    k = int(k)
    step = 1 << max(k.bit_length() - 4, 0)
    return -(-k // step) * step


def _insert_rows(dps: DynamicPointSet, new_pts, new_wts, valid, walk=None):
    """Insert the rows of a batch where ``valid`` is set into the lowest
    free slots, in row order, and count them into the tree. Returns the
    new set, each row's slot (``capacity`` where the row is padding or
    found no free slot: its scatters are dropped) and leaf (``num_nodes``
    there). ``walk`` is the point location (default ``locate``)."""
    C, M = dps.capacity, dps.tree.num_nodes
    free = jnp.nonzero(~dps.active, size=new_pts.shape[0], fill_value=C)[0]
    ok = valid & (free < C)
    slot = jnp.where(ok, free, C)
    lid = (walk or locate)(dps.tree, new_pts, dps.tree.max_depth)
    leaf = jnp.where(ok, lid, M)
    tree = _tree_add(dps.tree, leaf, ok.astype(jnp.int32), jnp.where(ok, new_wts, 0.0))
    out = DynamicPointSet(
        points=dps.points.at[slot].set(new_pts, mode="drop"),
        weights=dps.weights.at[slot].set(new_wts, mode="drop"),
        active=dps.active.at[slot].set(True, mode="drop"),
        leaf_id=dps.leaf_id.at[slot].set(lid, mode="drop"),
        tree=tree,
    )
    return out, slot, leaf


def _delete_rows(dps: DynamicPointSet, slot_ids, valid):
    """Deactivate the slots of the rows where ``valid`` is set. A row
    counts (``removed``) only at the first occurrence of a live slot, so
    duplicates and already-inactive slots are no-ops. Returns the new set,
    ``removed``, and each row's leaf (``num_nodes`` where not removed)
    and weight (0 where not removed)."""
    C, M = dps.capacity, dps.tree.num_nodes
    removed = valid & dps.active[slot_ids] & first_occurrence_mask(slot_ids)
    leaf = jnp.where(removed, dps.leaf_id[slot_ids], M)
    wts = jnp.where(removed, dps.weights[slot_ids], 0.0)
    tree = _tree_add(dps.tree, leaf, -removed.astype(jnp.int32), -wts)
    active = dps.active.at[jnp.where(valid, slot_ids, C)].set(False, mode="drop")
    return dps._replace(active=active, tree=tree), removed, leaf, wts


@jax.jit
def insert(dps: DynamicPointSet, new_pts: jax.Array, new_wts: jax.Array) -> DynamicPointSet:
    """Insert a batch of points into the lowest free slots and locate
    their buckets (one program). Points beyond the free capacity are
    dropped; ``Repartitioner.insert`` refuses such a batch."""
    valid = jnp.ones((new_pts.shape[0],), bool)
    return _insert_rows(dps, new_pts, new_wts, valid)[0]


def first_occurrence_mask(slot_ids: jax.Array) -> jax.Array:
    """(k,) bool: True at the first occurrence of each id in the batch.

    The dedup mask behind delete's no-op guarantee."""
    order = jnp.argsort(slot_ids, stable=True)
    sorted_ids = slot_ids[order]
    first_sorted = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]]
    )
    return jnp.zeros_like(first_sorted).at[order].set(first_sorted)


@jax.jit
def delete(dps: DynamicPointSet, slot_ids: jax.Array) -> DynamicPointSet:
    """Deactivate points by storage slot id (one program). Already-inactive
    ids and duplicates (within or across calls) are no-ops: the weight and
    count decrements are masked by ``active`` and a first-occurrence
    filter, so tree counters stay consistent with storage."""
    valid = jnp.ones(slot_ids.shape, bool)
    return _delete_rows(dps, slot_ids, valid)[0]


def _tree_add(tree: LinearKdTree, leaf_ids, counts, wts) -> LinearKdTree:
    """Add per-row (count, weight) deltas to the subtree counters of every
    node on each row's root→leaf path: one scatter of the rows onto their
    leaves, then the dense bottom-up pass of ``recount``. Rows with a leaf
    id of ``num_nodes`` (padding, no-ops) are dropped."""
    M = tree.num_nodes
    dc = jnp.zeros((M,), jnp.int32).at[leaf_ids].add(counts, mode="drop")
    dw = jnp.zeros((M,), jnp.float32).at[leaf_ids].add(wts, mode="drop")
    dc, dw = _subtree_sums(dc, dw, tree.max_depth)
    return tree._replace(count=tree.count + dc, weight=tree.weight + dw)


def _subtree_sums(cnt: jax.Array, wt: jax.Array, max_depth: int):
    """Per-node (count, weight) to subtree sums, bottom-up over the
    heap's levels: each level adds its children's (already summed)
    values."""
    for level in range(max_depth - 1, -1, -1):
        start, end = (1 << level) - 1, (1 << (level + 1)) - 1
        kids_c, kids_w = cnt[end:2 * end + 1], wt[end:2 * end + 1]
        cnt = cnt.at[start:end].add(kids_c[0::2] + kids_c[1::2])
        wt = wt.at[start:end].add(kids_w[0::2] + kids_w[1::2])
    return cnt, wt


# ---------------------------------------------------------------------------
# Algorithm 1 — Adjustments (split heavy / merge light)
# ---------------------------------------------------------------------------

def _node_depths(M: int) -> jax.Array:
    return jnp.floor(jnp.log2(jnp.arange(M, dtype=jnp.float32) + 1.0)).astype(jnp.int32)


def recount(dps: DynamicPointSet) -> DynamicPointSet:
    """Recompute exact subtree counts/weights bottom-up from the points."""
    tree = dps.tree
    M = tree.num_nodes
    leaf_cnt = jax.ops.segment_sum(
        dps.active.astype(jnp.int32), dps.leaf_id, num_segments=M
    )
    leaf_wt = jax.ops.segment_sum(
        jnp.where(dps.active, dps.weights, 0.0), dps.leaf_id, num_segments=M
    )
    cnt, wt = _subtree_sums(leaf_cnt, leaf_wt, tree.max_depth)
    return dps._replace(tree=tree._replace(count=cnt, weight=wt))


@functools.partial(jax.jit, static_argnames=())
def _merge_pass(dps: DynamicPointSet) -> DynamicPointSet:
    """Bottom-up merge of light subtrees (Alg. 1 merge branch).

    A node whose *subtree* count <= BUCKETSIZE becomes a leaf; its
    descendants are cleared and their points re-homed to it. One bottom-up
    sweep fully cascades (lower merges happen before upper checks).
    """
    dps = recount(dps)
    tree = dps.tree
    B = tree.bucket_size
    M = tree.num_nodes
    depths = _node_depths(M)
    is_leaf = tree.is_leaf
    leaf_id = dps.leaf_id
    leaf_depth = jnp.floor(jnp.log2(leaf_id.astype(jnp.float32) + 1.0)).astype(jnp.int32)

    for level in range(tree.max_depth - 1, -1, -1):
        start, end = (1 << level) - 1, (1 << (level + 1)) - 1
        nodes = jnp.arange(start, end)
        internal = (~is_leaf[nodes]) & (tree.count[nodes] > 0)
        mergeable = internal & (tree.count[nodes] <= B)
        # mark node a leaf, clear strict descendants' leaf flags
        is_leaf = is_leaf.at[nodes].set(is_leaf[nodes] | mergeable)
        # re-home points whose leaf ancestor at `level` is a merged node
        shift = jnp.maximum(leaf_depth - level, 0)
        anc = ((leaf_id + 1) >> shift) - 1
        anc_in_level = (anc >= start) & (anc < end) & (leaf_depth > level)
        merged_anc = anc_in_level & mergeable[jnp.clip(anc - start, 0, end - start - 1)]
        leaf_id = jnp.where(merged_anc & dps.active, anc, leaf_id)
        leaf_depth = jnp.where(merged_anc & dps.active, level, leaf_depth)

    # clear leaf flags of nodes that no longer hold any point and are below a merged leaf
    M_ids = jnp.arange(M)
    holds = jax.ops.segment_sum(dps.active.astype(jnp.int32), leaf_id, num_segments=M)
    is_leaf = is_leaf & ((holds > 0) | (tree.count == 0) | (M_ids == 0))
    tree = tree._replace(is_leaf=is_leaf)
    out = dps._replace(tree=tree, leaf_id=leaf_id)
    return recount(out)


@functools.partial(jax.jit, static_argnames=())
def _split_pass(dps: DynamicPointSet) -> DynamicPointSet:
    """Top-down split of heavy buckets (> 2*BUCKETSIZE), SplitLeaf loop.

    Points in heavy leaves flow further down with fresh midpoint split
    planes on tight bounding boxes, exactly like the static build but
    restricted to the heavy subtrees.
    """
    dps = recount(dps)
    tree = dps.tree
    B = tree.bucket_size
    points, active = dps.points, dps.active
    leaf_id = dps.leaf_id
    split_dim, split_val, is_leaf = tree.split_dim, tree.split_val, tree.is_leaf

    for level in range(tree.max_depth):
        start, end = (1 << level) - 1, (1 << (level + 1)) - 1
        S = end - start
        # points currently sitting in a leaf at this level
        here = active & (leaf_id >= start) & (leaf_id < end)
        seg = jnp.clip(leaf_id - start, 0, S - 1)
        cnt = jax.ops.segment_sum(jnp.where(here, 1, 0), seg, num_segments=S)
        leaf_lv = is_leaf[start:end]
        heavy = leaf_lv & (cnt > 2 * B)
        big = jnp.float32(3.4e38)
        plo = jnp.where(here[:, None], points, big)
        phi = jnp.where(here[:, None], points, -big)
        lo = jax.ops.segment_min(plo, seg, num_segments=S)
        hi = jax.ops.segment_max(phi, seg, num_segments=S)
        sdim = jnp.argmax(hi - lo, axis=1).astype(jnp.int32)
        lo_d = jnp.take_along_axis(lo, sdim[:, None], axis=1)[:, 0]
        hi_d = jnp.take_along_axis(hi, sdim[:, None], axis=1)[:, 0]
        sval = 0.5 * (lo_d + hi_d)

        split_dim = split_dim.at[start:end].set(jnp.where(heavy, sdim, split_dim[start:end]))
        split_val = split_val.at[start:end].set(jnp.where(heavy, sval, split_val[start:end]))
        is_leaf = is_leaf.at[start:end].set(jnp.where(heavy, False, is_leaf[start:end]))
        # children of freshly-split nodes become leaves
        heavy_nodes = jnp.arange(start, end)
        ch_lo = 2 * heavy_nodes + 1
        is_leaf = is_leaf.at[ch_lo].set(jnp.where(heavy, True, is_leaf[ch_lo]))
        is_leaf = is_leaf.at[ch_lo + 1].set(jnp.where(heavy, True, is_leaf[ch_lo + 1]))

        # route points of heavy leaves down one level
        pt_heavy = here & heavy[seg]
        dim_pp = sdim[seg]
        coord = jnp.take_along_axis(points, dim_pp[:, None], axis=1)[:, 0]
        side = (coord > sval[seg]).astype(jnp.int32)
        leaf_id = jnp.where(pt_heavy, 2 * leaf_id + 1 + side, leaf_id)

    out = dps._replace(
        tree=tree._replace(split_dim=split_dim, split_val=split_val, is_leaf=is_leaf),
        leaf_id=leaf_id,
    )
    return recount(out)


def adjustments(dps: DynamicPointSet, max_sweeps: int = 4) -> DynamicPointSet:
    """Algorithm 1: adjustment sweeps (split heavy, merge light).

    The paper's SplitLeaf recurses until every bucket fits; a single
    level-synchronous sweep descends each point at most one level per
    level-iteration, so pathological inserts (a dense burst into one
    bucket) may need another sweep. We iterate until occupancy fits or
    ``max_sweeps`` is reached (depth-capped leaves can legally stay heavy).
    """
    B = dps.tree.bucket_size
    for _ in range(max_sweeps):
        dps = _merge_pass(_split_pass(dps))
        if int(max_bucket_occupancy(dps)) <= 2 * B:
            break
    return dps


def num_buckets(dps: DynamicPointSet) -> jax.Array:
    return jnp.sum(dps.tree.is_leaf & (dps.tree.count > 0))


def max_bucket_occupancy(dps: DynamicPointSet) -> jax.Array:
    M = dps.tree.num_nodes
    holds = jax.ops.segment_sum(dps.active.astype(jnp.int32), dps.leaf_id, num_segments=M)
    return jnp.max(holds)


# ---------------------------------------------------------------------------
# Algorithm 3 — amortized load balancing controller
# ---------------------------------------------------------------------------

@dataclass
class AmortizedController:
    """Credit-based rebalance trigger (paper Algorithm 3).

    ``observe(cost_per_op, num_buckets)`` is called every step with the
    measured (or modeled) cost; it returns True when a full load balance
    should run. After running one, call ``balanced(lb_cost, num_buckets)``.

    The generalized cost metric is the paper's query-processing variant:
    cost = (max avg cost per op) * (max #buckets across processes).
    """

    credits: float = 0.0          # lbtime: bank from the last LB phase
    delta: float = 0.0            # spent-so-far excess
    base_cost: float = 0.0        # basebkt: baseline cost after last LB
    base_timeop: float = 0.0
    history: list = field(default_factory=list)

    def balanced(self, lb_cost: float, num_buckets: int, timeop: float | None = None) -> None:
        self.credits = float(lb_cost)
        self.delta = 0.0
        self.base_timeop = 0.0 if timeop is None else float(timeop)
        self.base_cost = self.base_timeop * num_buckets
        self.history.append(("lb", lb_cost))

    def observe(self, timeop: float, num_buckets: int) -> bool:
        cost = float(timeop) * num_buckets
        if self.base_timeop == 0.0:
            self.base_timeop = float(timeop)
            self.base_cost = cost
            self.history.append(("base", cost))
            return False
        if cost > self.base_cost:
            self.delta += cost - self.base_cost
        self.history.append(("obs", cost, self.delta))
        return self.delta > self.credits

    @property
    def exhausted(self) -> bool:
        return self.delta > self.credits
