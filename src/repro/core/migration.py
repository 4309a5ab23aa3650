"""Data migration with bounded message sizes (paper §III-C, transfer_t_l_t).

The paper exchanges data in *rounds*, capping the largest message at
MAX_MSG_SIZE to bound buffer memory and avoid network congestion. On TPU
the analogue is a sequence of fixed-capacity ``all_to_all`` chunks. This
module computes the plan (who sends how much to whom, in how many rounds)
and provides both a host-side simulator (used by tests/benchmarks to
check conservation and round counts) and a shard_map executor.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

import numpy as np


@dataclass(frozen=True)
class MigrationPlan:
    send_counts: np.ndarray   # (P, P) elements moving src -> dst
    rounds: int               # number of bounded all_to_all rounds
    chunk: int                # per-pair element capacity per round
    total_moved: int
    max_pair: int

    @property
    def stay_fraction(self) -> float:
        total = self.send_counts.sum()
        stay = np.trace(self.send_counts)
        return float(stay) / max(float(total), 1.0)


@dataclass(frozen=True)
class HierarchicalMigrationPlan:
    """Level-aware exchange plan over a node -> device hierarchy.

    Parts group into nodes of ``devices_per_node`` consecutive ids
    (``part = node * D + device``, the `partitioner.HierarchyPlan`
    layout). Moves inside a node's diagonal block ride the fast
    intra-node fabric; off-block moves cross the node boundary, where
    every byte costs ``inter_node_cost`` times as much — so the
    MAX_MSG_SIZE round capping is applied per level, with the inter-node
    chunk shrunk by the multiplier (same byte budget on a costlier
    link). The two levels schedule independently (disjoint fabrics):
    ``rounds`` is their max, not their sum.
    """

    send_counts: np.ndarray   # (P, P) elements moving src part -> dst part
    num_nodes: int
    devices_per_node: int
    inter_node_cost: float
    chunk: int                # intra-node per-pair capacity per round
    inter_chunk: int          # inter-node per-pair capacity per round
    intra_rounds: int
    inter_rounds: int
    intra_moved: int          # moved within a node (off-diagonal, same block)
    inter_moved: int          # moved across nodes (off-block)
    max_intra_pair: int
    max_inter_pair: int

    @property
    def rounds(self) -> int:
        return max(self.intra_rounds, self.inter_rounds)

    @property
    def total_moved(self) -> int:
        return self.intra_moved + self.inter_moved

    @property
    def max_pair(self) -> int:
        return max(self.max_intra_pair, self.max_inter_pair)

    @property
    def stay_fraction(self) -> float:
        """Device level: fraction not moving at all (diagonal)."""
        total = self.send_counts.sum()
        return float(np.trace(self.send_counts)) / max(float(total), 1.0)

    @property
    def stay_fraction_node(self) -> float:
        """Node level: fraction staying on its node (diagonal blocks) —
        what a hierarchy-aware re-slice keeps high under small drift."""
        total = self.send_counts.sum()
        stay = total - self.inter_moved
        return float(stay) / max(float(total), 1.0)

    def cost(self, bytes_per_elem: int = 16) -> float:
        """Weighted byte cost: intra bytes + multiplier * inter bytes —
        the objective a level-aware migration minimizes."""
        return bytes_per_elem * (
            self.intra_moved + self.inter_node_cost * self.inter_moved
        )


def _node_block_mask(num_parts: int, devices_per_node: int) -> np.ndarray:
    node_of = np.arange(num_parts) // max(1, devices_per_node)
    return node_of[:, None] == node_of[None, :]


def plan_from_counts(
    send: np.ndarray,
    *,
    max_msg_bytes: int = 4 << 20,
    bytes_per_elem: int = 16,
    hierarchy=None,
    inter_node_cost: float | None = None,
) -> "MigrationPlan | HierarchicalMigrationPlan":
    """Build the round schedule from a precomputed (P, P) count matrix
    (e.g. one reduced on-device by the repartitioning engine).

    With ``hierarchy`` (a `partitioner.HierarchyPlan`, or anything with
    ``num_nodes`` / ``devices_per_node`` / ``inter_node_cost``), the plan
    is level-aware: intra-node and inter-node pairs are capped into
    rounds separately, and the inter-node per-round chunk is divided by
    the cost multiplier (``inter_node_cost`` overrides the hierarchy's)
    so the bounded message honors the same byte budget on the costlier
    link. ``num_parts`` must equal the hierarchy's ``num_nodes *
    devices_per_node``.
    """
    send = np.asarray(send, dtype=np.int64)
    off_diag = send.copy()
    np.fill_diagonal(off_diag, 0)
    chunk = max(1, max_msg_bytes // bytes_per_elem)
    if hierarchy is None:
        max_pair = int(off_diag.max()) if off_diag.size else 0
        rounds = int(np.ceil(max_pair / chunk)) if max_pair else 0
        return MigrationPlan(
            send_counts=send,
            rounds=rounds,
            chunk=chunk,
            total_moved=int(off_diag.sum()),
            max_pair=max_pair,
        )
    N, D = int(hierarchy.num_nodes), int(hierarchy.devices_per_node)
    if send.shape[0] != N * D:
        raise ValueError(
            f"count matrix is {send.shape[0]}x{send.shape[0]}, hierarchy "
            f"expects {N} nodes x {D} devices = {N * D} parts"
        )
    mult = float(
        hierarchy.inter_node_cost if inter_node_cost is None else inter_node_cost
    )
    if mult < 1.0:
        raise ValueError(f"inter_node_cost must be >= 1, got {mult}")
    same_node = _node_block_mask(N * D, D)
    intra = np.where(same_node, off_diag, 0)
    inter = np.where(same_node, 0, off_diag)
    max_intra = int(intra.max()) if intra.size else 0
    max_inter = int(inter.max()) if inter.size else 0
    inter_chunk = max(1, int(max_msg_bytes / (bytes_per_elem * mult)))
    return HierarchicalMigrationPlan(
        send_counts=send,
        num_nodes=N,
        devices_per_node=D,
        inter_node_cost=mult,
        chunk=chunk,
        inter_chunk=inter_chunk,
        intra_rounds=int(np.ceil(max_intra / chunk)) if max_intra else 0,
        inter_rounds=int(np.ceil(max_inter / inter_chunk)) if max_inter else 0,
        intra_moved=int(intra.sum()),
        inter_moved=int(inter.sum()),
        max_intra_pair=max_intra,
        max_inter_pair=max_inter,
    )


def migration_plan(
    old_part: np.ndarray,
    new_part: np.ndarray,
    num_parts: int,
    *,
    max_msg_bytes: int = 4 << 20,
    bytes_per_elem: int = 16,
    hierarchy=None,
) -> "MigrationPlan | HierarchicalMigrationPlan":
    """Count matrix + round schedule honoring MAX_MSG_SIZE — the ONE
    assignment-pair -> count-matrix builder; all schedule semantics
    (including the level-aware ``hierarchy`` mode) live in
    `plan_from_counts`."""
    send = np.zeros((num_parts, num_parts), dtype=np.int64)
    np.add.at(send, (np.asarray(old_part), np.asarray(new_part)), 1)
    return plan_from_counts(
        send,
        max_msg_bytes=max_msg_bytes,
        bytes_per_elem=bytes_per_elem,
        hierarchy=hierarchy,
    )


def neighbor_locality(plan: MigrationPlan) -> float:
    """Fraction of moved elements that travel to a rank-adjacent part.

    The paper's incremental load balancing claims migration is restricted
    to P±1 neighbors for small load deltas; tests assert this is 1.0 after
    an `incremental_reslice` with modest weight changes.
    """
    P = plan.send_counts.shape[0]
    moved = 0
    near = 0
    for s in range(P):
        for d in range(P):
            if s == d:
                continue
            moved += plan.send_counts[s, d]
            if abs(s - d) == 1:
                near += plan.send_counts[s, d]
    return float(near) / max(float(moved), 1.0)


def simulate_rounds(plan: "MigrationPlan | HierarchicalMigrationPlan") -> list[np.ndarray]:
    """Split the send matrix into per-round matrices, each pair <= its
    level's chunk. Hierarchical plans cap intra-node pairs at ``chunk``
    and inter-node pairs at the multiplier-shrunk ``inter_chunk`` — the
    two fabrics schedule independently, so round r carries both levels'
    r-th bounded message."""
    remaining = plan.send_counts.copy()
    np.fill_diagonal(remaining, 0)
    if isinstance(plan, HierarchicalMigrationPlan):
        same_node = _node_block_mask(plan.send_counts.shape[0], plan.devices_per_node)
        cap = np.where(same_node, plan.chunk, plan.inter_chunk)
    else:
        cap = np.full(remaining.shape, plan.chunk, dtype=np.int64)
    out = []
    for _ in range(plan.rounds):
        step = np.minimum(remaining, cap)
        out.append(step)
        remaining -= step
    assert remaining.sum() == 0 or plan.rounds == 0
    return out


def execute_shard_exchange(
    mesh: jax.sharding.Mesh,
    axis: str,
    payload: jax.Array,
    dest: jax.Array,
    capacity: int,
    fill_value=0,
):
    """shard_map executor: move rows of ``payload`` (sharded on dim 0 over
    ``axis``) to the shard given by ``dest`` using one padded all_to_all.

    Returns (received_payload (nshards*capacity, ...), valid_mask). The
    caller picks ``capacity`` from the migration plan (chunk size); calling
    this in a loop over rounds gives the paper's bounded-message exchange.
    """
    return _exchange_fn(mesh, axis, capacity, fill_value)(payload, dest)


def stage_rows_by_dest(
    dest: jax.Array,
    payloads: tuple,
    nshards: int,
    capacity: int,
    fills: tuple,
) -> list:
    """Stage local rows into fixed-capacity (nshards, capacity, ...) lane
    buffers by destination shard — the shared body of every padded
    all_to_all exchange (payload migration, query routing). Must be
    called inside shard_map; rows beyond a lane's capacity are dropped
    (callers size capacity so that cannot happen, or assert conservation).

    Returns (staged buffers, one per payload; per-ORIGINAL-row staging
    position). Row i went to buffer slot [dest[i], pos[i]] — a caller
    exchanging answers back can therefore gather its own results locally
    from the reply buffer instead of round-tripping slot ids."""
    n_loc = dest.shape[0]
    order = jnp.argsort(dest, stable=True)
    ds = dest[order]
    pos = jnp.arange(n_loc, dtype=jnp.int32) - jnp.searchsorted(
        ds, jnp.arange(nshards, dtype=ds.dtype)
    ).astype(jnp.int32)[ds]
    out = []
    for x, fill in zip(payloads, fills):
        buf = jnp.full((nshards, capacity) + x.shape[1:], fill, x.dtype)
        out.append(buf.at[ds, pos].set(x[order], mode="drop"))
    pos_of_row = jnp.zeros((n_loc,), jnp.int32).at[order].set(pos)
    return out, pos_of_row


@functools.lru_cache(maxsize=64)
def _exchange_fn(mesh: jax.sharding.Mesh, axis: str, capacity: int, fill_value):
    """Jitted exchange executor, memoized per static config. shard_map'd
    callables must run under jit — eager execution dispatches every traced
    op as its own SPMD program (see partitioner._reslice_fn)."""
    from jax.sharding import PartitionSpec as P

    nshards = mesh.shape[axis]

    def kernel(x, d):
        (buf, val), _ = stage_rows_by_dest(
            d, (x, jnp.ones(d.shape, bool)), nshards, capacity, (fill_value, False)
        )
        rbuf = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0)
        rval = jax.lax.all_to_all(val, axis, split_axis=0, concat_axis=0)
        return rbuf.reshape((-1,) + x.shape[1:]), rval.reshape(-1)

    return jax.jit(jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=(P(axis), P(axis)),
        check_vma=False,
    ))
