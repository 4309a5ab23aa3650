"""Greedy knapsack on a weighted space-filling curve (paper §III-C).

The SFC lays the elements on a weighted line segment. A parallel prefix
sum gives each element its global rank/weight offset; slicing the segment
into ``P`` nearly equal weights (without violating the key order) yields
the partitions. The paper's guarantee — *"the load on any two processes
differs by at most the maximum weight of any point"* — is property-tested
in ``tests/test_knapsack.py``.

Everything here is fixed-shape, jit-able jnp.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("num_parts",))
def slice_weighted_curve(weights: jax.Array, num_parts: int) -> jax.Array:
    """Slice a weight sequence (already in SFC order) into contiguous parts.

    Returns part_id (n,) int32, non-decreasing. Part boundaries are the
    greedy choice: element i goes to part floor(prefix_exclusive(i) /
    (total / P)) clipped to P-1 — each part's load misses the ideal by at
    most one element weight.
    """
    w = weights.astype(jnp.float32)
    prefix = jnp.cumsum(w) - w  # exclusive prefix
    total = prefix[-1] + w[-1]
    ideal = total / num_parts
    ideal = jnp.where(ideal > 0, ideal, 1.0)
    # midpoint rule: assign by the element's center of mass on the segment
    part = jnp.floor((prefix + 0.5 * w) / ideal).astype(jnp.int32)
    # once an ulp of the float32 prefix exceeds half an element weight
    # (2^24 elements of weight ~1), the rounded centers are no longer
    # monotone: the running max keeps every part one contiguous slice
    return jax.lax.cummax(jnp.clip(part, 0, num_parts - 1))


@functools.partial(jax.jit, static_argnames=("num_parts",))
def part_boundaries(weights: jax.Array, num_parts: int) -> jax.Array:
    """First element index of each part (P+1 entries, last = n)."""
    part = slice_weighted_curve(weights, num_parts)
    n = weights.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    # boundary[p] = first i with part[i] >= p
    starts = jnp.searchsorted(part, jnp.arange(num_parts, dtype=jnp.int32), side="left")
    del idx
    return jnp.concatenate([starts.astype(jnp.int32), jnp.array([n], dtype=jnp.int32)])


@functools.partial(jax.jit, static_argnames=("num_parts",))
def part_loads(weights: jax.Array, part: jax.Array, num_parts: int) -> jax.Array:
    """Load (sum of weights) per part."""
    return jax.ops.segment_sum(
        weights.astype(jnp.float32), part, num_segments=num_parts
    )


# ---------------------------------------------------------------------------
# Two-level (node -> device) nested knapsack
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_nodes", "devices_per_node"))
def device_slice_within_nodes(
    weights: jax.Array,
    node: jax.Array,
    num_nodes: int,
    devices_per_node: int,
) -> jax.Array:
    """Fine level of the hierarchy: device id within each node's slice.

    ``node`` (n,) int32 must be non-decreasing along the curve — a coarse
    knapsack output, fresh (``slice_weighted_curve(w, num_nodes)``) or
    frozen from an earlier step (the intra-node-only re-slice keeps it).
    Each node's contiguous slice is re-sliced into ``devices_per_node``
    parts with the same midpoint rule as :func:`slice_weighted_curve`:
    node weight offsets are read off the SAME exclusive prefix the flat
    rule uses, so with ``num_nodes == 1`` the result is bit-identical to
    ``slice_weighted_curve(weights, devices_per_node)`` — the flat path
    IS the trivial hierarchy.
    """
    w = weights.astype(jnp.float32)
    prefix = jnp.cumsum(w) - w  # exclusive prefix
    total = prefix[-1] + w[-1]
    # first curve index of each node's slice -> its exclusive weight
    # offset; prefix extended by the total so empty tail nodes (start ==
    # n) read a consistent offset
    starts = jnp.searchsorted(
        node, jnp.arange(num_nodes, dtype=node.dtype), side="left"
    )
    prefix_ext = jnp.concatenate([prefix, total[None]])
    node_off = prefix_ext[starts]                      # (N,)
    node_end = jnp.concatenate([node_off[1:], total[None]])
    node_tot = node_end - node_off                     # (N,)
    local_prefix = prefix - node_off[node]
    ideal = node_tot[node] / devices_per_node
    ideal = jnp.where(ideal > 0, ideal, 1.0)
    dev = jnp.floor((local_prefix + 0.5 * w) / ideal).astype(jnp.int32)
    dev = jnp.clip(dev, 0, devices_per_node - 1)
    # contiguous within each node, as in slice_weighted_curve (node is
    # non-decreasing, so the running max never crosses a node boundary)
    base = node * devices_per_node
    return jax.lax.cummax(base + dev) - base


@functools.partial(jax.jit, static_argnames=("num_nodes", "devices_per_node"))
def two_level_slice(
    weights: jax.Array, num_nodes: int, devices_per_node: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Nested greedy knapsack of a weighted curve: coarse slices to
    ``num_nodes`` nodes, then each node's slice independently re-sliced
    across its ``devices_per_node`` devices.

    Returns ``(node, device, part)`` with ``part = node * devices_per_node
    + device``, all (n,) int32 and non-decreasing along the curve. The
    paper's balance guarantee nests: node loads differ by at most one max
    element weight, and within every node the device loads do too.
    """
    node = slice_weighted_curve(weights, num_nodes)
    dev = device_slice_within_nodes(weights, node, num_nodes, devices_per_node)
    return node, dev, node * devices_per_node + dev


def greedy_bins(weights: jax.Array, num_bins: int) -> jax.Array:
    """Non-contiguous greedy knapsack: heaviest-first into the lightest bin.

    Used where curve order need not be preserved (e.g. assigning top tree
    nodes to processes in partitioner_init, serving-batch admission).
    Host-side O(n log n + n·B); returns bin id per element.
    """
    import numpy as np

    w = np.asarray(weights, dtype=np.float64)
    order = np.argsort(-w, kind="stable")
    loads = np.zeros(num_bins)
    out = np.zeros(w.shape[0], dtype=np.int32)
    for i in order:
        b = int(np.argmin(loads))
        loads[b] += w[i]
        out[i] = b
    return jnp.asarray(out)


@functools.partial(jax.jit, static_argnames=("num_parts",))
def incremental_reslice(
    weights: jax.Array, old_part: jax.Array, num_parts: int
) -> tuple[jax.Array, jax.Array]:
    """Incremental load balancing (paper §IV): keep the existing curve
    order, recompute ranks on the new weighted segment, re-slice.

    Returns (new_part, moved_mask). Because the order is preserved, an
    element can only move to a rank-adjacent part in the best case —
    migration is restricted to neighbors P±1 for small load deltas (the
    paper's locality claim, asserted in tests).
    """
    new_part = slice_weighted_curve(weights, num_parts)
    return new_part, new_part != old_part
