"""Per-level reference of the tree-mode insert/delete (test-side oracle).

The engine runs each insert/delete as one compiled program whose tree
update is one scatter onto the leaves plus a bottom-up pass. This module
keeps the plain formulation it replaced, op by op: the point-location
walk with a gather per node array, a scatter-add per tree level along
every root→leaf path, and the bucket-summary delta as eager scatters.
Tests compare the two.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dynamic, kdtree


def bump_counts(tree, leaf_ids, wts, sign, counts=None):
    """Add +-(count, weight) along all root→leaf paths, one scatter-add
    per level (``counts`` overrides the count delta of 1 per id)."""
    count, weight = tree.count, tree.weight
    node = leaf_ids
    ones = (jnp.ones_like(leaf_ids) if counts is None else counts) * sign
    swts = wts * sign
    for _ in range(tree.max_depth + 1):
        count = count.at[node].add(ones)
        weight = weight.at[node].add(swts)
        done = node == 0
        node = jnp.where(done, -1, (node - 1) // 2)
        ones = jnp.where(done, 0, ones)
        swts = jnp.where(done, 0.0, swts)
    return tree._replace(count=count, weight=weight)


def path_sums64(num_nodes, leaf_ids, wts):
    """(M,) float64: each node's sum of ``wts`` over the rows whose leaf
    lies in its subtree (the exact value both formulations round)."""
    out = np.zeros(num_nodes)
    node, w = np.asarray(leaf_ids).astype(np.int64), np.asarray(wts, np.float64)
    while node.size:
        np.add.at(out, node, w)
        keep = node > 0
        node, w = (node[keep] - 1) // 2, w[keep]
    return out


def locate(tree, pts, max_depth):
    """Root→leaf walk gathering each node array, and the coordinate, apart."""

    def body(_, node):
        dim = tree.split_dim[node]
        leaf = tree.is_leaf[node] | (dim < 0)
        coord = jnp.take_along_axis(pts, jnp.maximum(dim, 0)[:, None], axis=1)[:, 0]
        return jnp.where(leaf, node, 2 * node + 1 + (coord > tree.split_val[node]).astype(jnp.int32))

    return jax.lax.fori_loop(0, max_depth, body, jnp.zeros((pts.shape[0],), jnp.int32))


def insert(dps, new_pts, new_wts):
    """(new set, slots, leaf ids) of an insert into the lowest free slots."""
    k = new_pts.shape[0]
    free = jnp.nonzero(~dps.active, size=k, fill_value=dps.capacity - 1)[0]
    lid = locate(dps.tree, new_pts, dps.tree.max_depth)
    out = dynamic.DynamicPointSet(
        dps.points.at[free].set(new_pts),
        dps.weights.at[free].set(new_wts),
        dps.active.at[free].set(True),
        dps.leaf_id.at[free].set(lid),
        bump_counts(dps.tree, lid, new_wts, sign=+1),
    )
    return out, free, lid


def delete(dps, slot_ids):
    """(new set, removed mask) of a delete; duplicates and inactive ids
    are no-ops."""
    removed = dps.active[slot_ids] & dynamic.first_occurrence_mask(slot_ids)
    wts = dps.weights[slot_ids] * removed
    tree = bump_counts(
        dps.tree, dps.leaf_id[slot_ids], wts, sign=-1, counts=removed.astype(jnp.int32)
    )
    return dps._replace(active=dps.active.at[slot_ids].set(False), tree=tree), removed


def summary_delta(s, is_leaf, pts, wts, leaf_ids, sign, counts=None):
    """The bucket summaries after a delta, by eager scatters."""
    ones = (jnp.ones_like(leaf_ids) if counts is None else counts) * sign
    cnt = s.count.at[leaf_ids].add(ones)
    wsum = s.weight.at[leaf_ids].add(jnp.float32(sign) * wts)
    csum = s.centroid * s.count[:, None].astype(jnp.float32)
    csum = csum.at[leaf_ids].add(
        jnp.float32(sign) * pts * (jnp.abs(ones))[:, None].astype(jnp.float32)
    )
    centroid = csum / jnp.maximum(cnt[:, None].astype(jnp.float32), 1.0)
    lo, hi = s.bbox_lo, s.bbox_hi
    if sign > 0:
        lo = lo.at[leaf_ids].min(pts)
        hi = hi.at[leaf_ids].max(pts)
    return kdtree.BucketSummary(
        count=cnt, weight=wsum, centroid=centroid, bbox_lo=lo, bbox_hi=hi,
        is_bucket=is_leaf & (cnt > 0),
    )
