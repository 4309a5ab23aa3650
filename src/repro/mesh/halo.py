"""Halo (ghost-cell) exchange plans over the partition core.

A mesh partition's communication structure is *static between partition
events*: which cells each part must read from its neighbors (the ghost
set) follows entirely from the face-adjacency graph and the part
assignment. This module compiles that structure — once per repartition
event, on the host — into fixed-shape send/recv index tables that the
jitted ``shard_map`` executors in :mod:`repro.mesh.stencil` replay every
stencil step with zero routing logic on device.

Two plan flavors, mirroring PR 4's two-level machinery:

* **flat** (1-D mesh): one all_to_all; lane (o, p) carries the cells of
  owner o that part p ghosts.
* **hierarchical** ((node, device) mesh, `partitioner.HierarchyPlan`):
  two hops. Hop A runs over the NODE axis only and is deduplicated per
  destination node — a cell ghosted by three devices of node m crosses
  the inter-node boundary once. Hop B fans the values out over the
  DEVICE axis inside the destination node. Ghosts whose owner sits on
  the requester's own node ride hop A's self-lane, which never leaves
  the node — node-local ghosts never cross the inter-node boundary, by
  construction.

Ghost *ownership* is resolved against the ``CurveIndex`` directory
(:func:`owners_from_index`): a face neighbor's key is looked up in the
O(B) bucket directory and the bucket's part is read off — the same
directory hop the query layer uses, and the lookup a real distributed
mesh would do (no global part array required). ``build_halo_plan``
accepts the resulting (or any) part vector.

Migration rides the same machinery: :func:`build_move_plan` compiles the
state exchange for a partition change — moved-only rows for an
incremental re-slice (a single intra-node hop when the migration plan
certifies zero inter-node movement), or the full redistribute a rebuild
pays — with `repro.core.migration` providing the level-aware accounting.

**Plan cost is a hot-path cost.** Plans are rebuilt on every
repartition event, so host-side construction bounds how *dynamic* a
dynamic workload can be (the paper's "minimal partitioning cost"
requirement). The builders therefore contain **zero per-part
and zero per-cell Python loops**: every table is produced by numpy
segment operations — one ``lexsort`` over (part, slot) defines the
owned layout, sorted-run ranks fill the lane tables, ``searchsorted``
over a packed (part, slot-rank) key replaces the per-part ghost
position dicts, and the hop-A dedup is a sorted-unique over
(owner, dest-node, cell). The canonical ascending-slot ordering makes
the output a pure function of ``(slot, part, nbr, coeff)``, so the
builders are **bit-identical** to straightforward per-part loop
builders (the equivalence-test oracle in ``tests/_plan_reference.py``).
Every builder records its own walltime as ``PlanBuildSeconds`` in
``plan.metrics``; ``with_metrics=False`` skips the O(n*K) quality pass
(``partition_report`` over the face-edge list) for hot-loop callers
that do not read it — the returned index tables are identical either
way (:func:`plan_quality_metrics` recovers the skipped report).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field

import numpy as np

from repro.core import metrics as _metrics
from repro.core import migration as _migration
from repro.mesh import amr as _amr

# merge sentinel: sorts after every real storage-slot id
GID_SENTINEL = np.int32(2**31 - 1)


def _roundup(x: int, q: int = 8) -> int:
    """Round capacities up so nearby plans share compiled executors."""
    return max(q, ((int(x) + q - 1) // q) * q)


@dataclass(frozen=True)
class Stage:
    """One all_to_all hop of a routing plan.

    ``idx`` (S, lanes, cap) int32 holds, per device, the source position
    of each (lane, slot) entry in the device's PREVIOUS buffer (the
    owned value array for hop 0, the previous hop's receive buffer
    after); -1 pads. ``lanes`` equals the mesh extent of ``axis``."""

    axis: str
    lanes: int
    cap: int
    idx: np.ndarray


@dataclass(frozen=True)
class HaloPlan:
    """Compiled ghost-exchange + stencil tables for one partition.

    Per-device canonical order is ascending storage-slot id — for owned
    cells and ghosts alike — so the layout is reproducible from
    ``(slot, part)`` alone and migration merges can realign by sorting
    on slot ids.
    """

    axes: tuple[str, ...]          # mesh axes the executors shard over
    num_parts: int
    cap: int                       # owned cells per device (padded)
    gcap: int                      # ghost cells per device (padded)
    K: int                         # neighbor slots per cell
    owned_idx: np.ndarray          # (S, cap) int32 cell index, -1 pad
    owned_slot: np.ndarray         # (S, cap) int64 slot id, -1 pad
    nbr_local: np.ndarray          # (S, cap, K) int32 into [0, cap+gcap)
    nbr_valid: np.ndarray          # (S, cap, K) bool
    coeff: np.ndarray              # (S, cap, K) float32
    stages: tuple[Stage, ...]      # value-routing hops
    ghost_fetch: np.ndarray        # (S, gcap) int32 into final recv, -1 pad
    # rows with a valid slot past the first of a face (list_finer_rows)
    finer_rows: np.ndarray         # (S, fcap) int32 ascending, -1 pad
    metrics: dict = field(default_factory=dict)

    @property
    def stage_meta(self) -> tuple:
        """Static executor signature: ((axis, lanes, cap), ...)."""
        return tuple((s.axis, s.lanes, s.cap) for s in self.stages)

    @property
    def fcap(self) -> int:
        """Listed finer rows per device (padded)."""
        return self.finer_rows.shape[1]

    def pack_cells(self, u_cells: np.ndarray) -> np.ndarray:
        """Global cell-order field (n,) or fields (n, V) -> the owned
        device layout (S*cap,) or (S*cap, V)."""
        u = np.asarray(u_cells, np.float32)
        out = np.zeros((self.owned_idx.shape[0], self.cap) + u.shape[1:], np.float32)
        m = self.owned_idx >= 0
        out[m] = u[self.owned_idx[m]]
        return out.reshape((-1,) + u.shape[1:])

    def unpack_cells(self, u_dev: np.ndarray, n_cells: int) -> np.ndarray:
        """Owned device layout (S*cap,) or (S*cap, V) -> global cell order."""
        u = np.asarray(u_dev, np.float32)
        u = u.reshape(self.owned_idx.shape + u.shape[1:])
        out = np.zeros((n_cells,) + u.shape[2:], np.float32)
        m = self.owned_idx >= 0
        out[self.owned_idx[m]] = u[m]
        return out

    @property
    def caps(self) -> dict:
        """The padded sizes that shape the compiled executors."""
        return {"cap": self.cap, "gcap": self.gcap, "fcap": self.fcap,
                "stages": tuple(s.cap for s in self.stages)}

    def padded(self, caps: dict) -> "HaloPlan":
        """The same plan with every padded size raised to ``caps`` (none
        may shrink): the executors then compile one program for every
        plan padded alike, and compute the same values."""
        S, cap, K = self.nbr_local.shape
        C = caps["cap"]
        owned_idx = _pad_last(self.owned_idx, C, -1)
        owned_slot = _pad_last(self.owned_slot, C, -1)
        # ghost references (>= cap) follow the ghost block to C
        nl = np.where(self.nbr_valid & (self.nbr_local >= cap),
                      self.nbr_local + (C - cap), self.nbr_local)
        nbr_local = np.zeros((S, C, K), np.int32)
        nbr_local[:, :cap] = nl
        nbr_valid = np.zeros((S, C, K), bool)
        nbr_valid[:, :cap] = self.nbr_valid
        coeff = np.zeros((S, C, K), np.float32)
        coeff[:, :cap] = self.coeff
        stages, fetch = _pad_stages(self.stages, caps["stages"], self.ghost_fetch)
        return HaloPlan(
            axes=self.axes, num_parts=self.num_parts, cap=C, gcap=caps["gcap"], K=K,
            owned_idx=owned_idx, owned_slot=owned_slot, nbr_local=nbr_local,
            nbr_valid=nbr_valid, coeff=coeff, stages=stages,
            ghost_fetch=_pad_last(fetch, caps["gcap"], -1),
            finer_rows=_pad_last(self.finer_rows, caps["fcap"], -1),
            metrics=self.metrics,
        )


def _pad_last(a: np.ndarray, size: int, fill) -> np.ndarray:
    """``a`` with its last axis padded with ``fill`` to ``size``."""
    if size < a.shape[-1]:
        raise ValueError(f"cannot pad {a.shape[-1]} down to {size}")
    out = np.full(a.shape[:-1] + (size,), fill, a.dtype)
    out[..., : a.shape[-1]] = a
    return out


def list_finer_rows(nbr_valid: np.ndarray) -> np.ndarray:
    """The finer-row list of (S, cap, K) stencil tables: per device, the
    rows with a valid slot past the first of a face (``face_group(K) >
    1``; those slots hold finer neighbours only), ascending and padded
    with -1 to the largest count rounded up (no column where no row has
    one, as where ``face_group(K) == 1``). The V-wide kernel runs them
    with every slot's values and the other rows with one value a face."""
    S, cap, K = nbr_valid.shape
    g = _amr.face_group(K)
    finer = np.zeros((S, cap), bool)
    if g > 1:
        # a face's g flags are g adjacent bytes, and 8 % g == 0 (K is 8
        # or 24): OR a row's bytes as 64-bit words and keep the bytes
        # that are not the first of their face (5x faster on the host
        # than a reduction over the (S, cap, K / g, g - 1) view)
        words = np.ascontiguousarray(nbr_valid).view("<u8")
        later = np.uint64(sum(0xFF << 8 * b for b in range(8) if b % g))
        finer = (functools.reduce(np.bitwise_or, [words[..., i] for i in range(K // 8)])
                 & later) != 0
    counts = finer.sum(axis=1)
    dev, row = np.nonzero(finer)                   # ascending rows per device
    rank = np.arange(dev.size) - np.concatenate(([0], np.cumsum(counts)[:-1]))[dev]
    rows = np.full((S, _roundup(int(counts.max())) if counts.any() else 0), -1, np.int32)
    rows[dev, rank] = row
    return rows


def _pad_stages(stages, caps, fetch=None):
    """Stages padded to lane capacities ``caps``. A hop's receive buffer
    is (lanes, cap) flat, so the positions the next hop (or ``fetch``)
    reads from it move from ``lane * cap + t`` to ``lane * cap' + t``."""
    out = []
    prev = None       # (old cap, new cap) of the previous hop's lanes
    for st, c in zip(stages, caps):
        idx = st.idx
        if prev is not None:
            idx = np.where(idx >= 0, (idx // prev[0]) * prev[1] + idx % prev[0], -1)
        out.append(Stage(axis=st.axis, lanes=st.lanes, cap=int(c),
                         idx=_pad_last(idx.astype(np.int32), int(c), -1)))
        prev = (st.cap, int(c))
    if fetch is not None and prev is not None:
        fetch = np.where(fetch >= 0, (fetch // prev[0]) * prev[1] + fetch % prev[0], -1)
    return tuple(out), None if fetch is None else fetch.astype(np.int32)


def layout_plan(slot: np.ndarray, part: np.ndarray, *, hierarchy=None,
                num_parts: int | None = None, device_axis: str = "device") -> HaloPlan:
    """A plan that only places cells: each part's owned cells in
    ascending-slot order, as :func:`build_halo_plan` lays them out, with
    no stencil tables and no exchange. For placing state that a
    :func:`build_move_plan` then carries to a full plan's layout."""
    slot = np.asarray(slot, np.int64)
    part64 = np.asarray(part).astype(np.int64)
    n = slot.shape[0]
    N, D, S, axes = _plan_shape(part64, hierarchy, num_parts, device_axis)
    ocells = np.lexsort((slot, part64))
    ocounts = np.bincount(part64, minlength=S)
    ostarts = np.concatenate(([0], np.cumsum(ocounts)))
    cap = _roundup(int(ocounts.max()) if n else 0)
    drow = part64[ocells] * cap + np.arange(n) - ostarts[part64[ocells]]
    owned_idx = np.full((S * cap,), -1, np.int32)
    owned_slot = np.full((S * cap,), -1, np.int64)
    owned_idx[drow] = ocells
    owned_slot[drow] = slot[ocells]
    return HaloPlan(
        axes=axes, num_parts=S, cap=cap, gcap=1, K=0,
        owned_idx=owned_idx.reshape(S, cap), owned_slot=owned_slot.reshape(S, cap),
        nbr_local=np.zeros((S, cap, 0), np.int32), nbr_valid=np.zeros((S, cap, 0), bool),
        coeff=np.zeros((S, cap, 0), np.float32), stages=(),
        ghost_fetch=np.full((S, 1), -1, np.int32), finer_rows=np.zeros((S, 0), np.int32),
    )


def owners_from_index(index, part_by_slot: np.ndarray, centers) -> np.ndarray:
    """Owning part of each query center, resolved through the
    ``CurveIndex`` directory (key -> bucket -> part).

    ``part_by_slot`` is the engine's per-slot assignment; parts are
    constant within a directory bucket on the tree-backed path (buckets
    are the knapsack units), so the bucket's first sorted entry carries
    its part. This is the halo layer's routing view of the partition —
    O(B) directory state instead of an O(n) global part array — and
    tests hold it equal to the direct per-cell lookup.
    """
    import jax.numpy as jnp

    from repro.core import curve_index as _ci

    part_sorted = np.asarray(part_by_slot)[np.asarray(index.ids)]
    bucket_part = part_sorted[np.asarray(index.bucket_starts)[:-1]]
    qk = _ci.query_keys(index, jnp.asarray(centers, jnp.float32))
    b = np.asarray(_ci.bucket_lookup(index, qk))
    return bucket_part[b].astype(np.int32)


# ---------------------------------------------------------------------------
# shared plan geometry
# ---------------------------------------------------------------------------

def _plan_shape(part, hierarchy, num_parts, device_axis):
    """Resolve (N, D, S, axes) of a plan over ``hierarchy`` or a flat axis."""
    if hierarchy is not None and hierarchy.num_nodes > 1:
        N, D = int(hierarchy.num_nodes), int(hierarchy.devices_per_node)
        axes = (hierarchy.node_axis, hierarchy.device_axis)
    else:
        N = 1
        if hierarchy is not None:
            D = int(hierarchy.num_parts)
            device_axis = hierarchy.device_axis
        else:
            D = int(num_parts) if num_parts is not None else int(part.max()) + 1
        axes = (device_axis,)
    return N, D, N * D, axes


def _run_ranks(keys_sorted: np.ndarray) -> np.ndarray:
    """Rank of each element within its run of equal keys (keys sorted)."""
    m = keys_sorted.shape[0]
    if m == 0:
        return np.zeros((0,), np.int64)
    start = np.ones((m,), bool)
    start[1:] = keys_sorted[1:] != keys_sorted[:-1]
    starts = np.nonzero(start)[0]
    run_id = np.cumsum(start) - 1
    return np.arange(m, dtype=np.int64) - starts[run_id]


def plan_quality_metrics(part, nbr, num_parts, weights=None) -> dict:
    """The O(n*K) partition-quality report a ``with_metrics=False`` plan
    skipped: the paper's table columns (`metrics.partition_report`) over
    the face-edge list. Callers that build plans on the hot loop run it
    once for reporting instead of on every repartition event."""
    part = np.asarray(part)
    n = part.shape[0]
    w = np.ones((n,), np.float64) if weights is None else np.asarray(weights, np.float64)
    return _metrics.partition_report(
        part, w, int(num_parts), edges=_amr.neighbor_edges(nbr)
    )


# ---------------------------------------------------------------------------
# plan construction
# ---------------------------------------------------------------------------

def build_halo_plan(
    slot: np.ndarray,
    part: np.ndarray,
    nbr: np.ndarray,
    coeff: np.ndarray,
    *,
    hierarchy=None,
    num_parts: int | None = None,
    device_axis: str = "device",
    weights: np.ndarray | None = None,
    with_metrics: bool = True,
    cache=None,
    topo_token=None,
    _topo=None,
    _capture: dict | None = None,
) -> HaloPlan:
    """Compile the ghost exchange + local stencil tables for one
    partition of one mesh.

    ``slot`` (n,) storage-slot ids (stable identity), ``part`` (n,) the
    owning part per cell (parts name shards), ``nbr``/``coeff`` the
    (n, K) face tables from :mod:`repro.mesh.amr`. ``hierarchy`` (a
    `partitioner.HierarchyPlan` with num_nodes > 1) selects the two-hop
    node-aware exchange; otherwise the plan is flat over
    ``device_axis``. ``weights`` feed the load columns of the quality
    metrics (default: unit cell cost). ``with_metrics=False`` skips the
    O(n*K) `partition_report` quality pass (recoverable later via
    :func:`plan_quality_metrics`); every other output — including the
    cheap segment-sum halo metrics — is identical.

    ``cache`` (a :class:`repro.mesh.plan_cache.PlanCache`) persists the
    construction intermediates across repartition events and
    delta-patches only the part segments whose owner set changed — the
    output is bit-identical to the from-scratch build (see
    ``plan_cache``). ``topo_token`` keys the cached topology state (pass
    the engine's ``topology_version``); a changed token forces a
    topology refresh. ``_topo``/``_capture`` are the cache's private
    handshake with the scratch builder.

    The construction is pure numpy segment ops (no per-part or per-cell
    Python loops).
    """
    if cache is not None:
        from repro.mesh import plan_cache as _plan_cache

        return _plan_cache.cached_build_halo_plan(
            cache, slot, part, nbr, coeff, hierarchy=hierarchy,
            num_parts=num_parts, device_axis=device_axis, weights=weights,
            with_metrics=with_metrics, topo_token=topo_token,
        )
    t_build = time.perf_counter()
    slot = np.asarray(slot, np.int64)
    part = np.asarray(part)
    n, K = nbr.shape
    N, D, S, axes = _plan_shape(part, hierarchy, num_parts, device_axis)
    part64 = part.astype(np.int64)
    if n and (part64.min() < 0 or part64.max() >= S):
        raise ValueError(f"part ids must lie in [0, {S})")

    # slot-rank compression: ordering by slot == ordering by rank, and
    # ranks stay < n so packed (part, rank) keys cannot overflow int64.
    # All three arrays are pure functions of the topology (slot, nbr) —
    # the cache hands them back via ``_topo`` on AMR-free events.
    if _topo is not None:
        srank, valid, nbc = _topo
    else:
        sorder = np.argsort(slot, kind="stable")
        srank = np.empty((n,), np.int64)
        srank[sorder] = np.arange(n, dtype=np.int64)
        valid = nbr >= 0
        nbc = np.where(valid, nbr, 0).astype(np.int64)
        if _capture is not None:
            _capture["sorder"] = sorder

    # --- owned layout: one lexsort over (part, slot) -----------------------
    ocells = np.lexsort((slot, part64))            # cells by (part, slot)
    oprow = part64[ocells]                          # owning part per row
    ocounts = np.bincount(oprow, minlength=S)
    ostarts = np.concatenate(([0], np.cumsum(ocounts)))
    orank = np.arange(n, dtype=np.int64) - ostarts[oprow]
    local_pos = np.empty((n,), np.int64)
    local_pos[ocells] = orank

    # one (n, K) gather of the neighbor's owner, shared by the ghost
    # pass and the stencil tables (the dominant cost at ~1M cells)
    pn = part64[nbc]                                # neighbor's owner
    same = valid & (pn == part64[:, None])
    other = valid & ~same                           # ghost-reading lanes

    # --- ghost sets: cross-part face pairs, deduped per (part, slot) ------
    grow, gcol = np.nonzero(other)
    gp, gc = part64[grow], nbc[grow, gcol]
    gr = srank[gc]
    gord = np.lexsort((gr, gp))
    gp, gc, gr = gp[gord], gc[gord], gr[gord]
    if gp.size:
        keep = np.ones((gp.size,), bool)
        keep[1:] = (gp[1:] != gp[:-1]) | (gr[1:] != gr[:-1])
        gp, gc, gr = gp[keep], gc[keep], gr[keep]
    gcounts = np.bincount(gp, minlength=S)
    gstarts = np.concatenate(([0], np.cumsum(gcounts)))
    grank = np.arange(gp.size, dtype=np.int64) - gstarts[gp]

    cap = _roundup(int(ocounts.max()) if n else 0)
    gcap = _roundup(max(int(gcounts.max()) if gcounts.size else 0, 1))

    # flat destination row of every cell in its owner's (cap-padded) block
    drow = part64 * cap + local_pos
    owned_idx = np.full((S * cap,), -1, np.int32)
    owned_slot = np.full((S * cap,), -1, np.int64)
    owned_idx[drow] = np.arange(n, dtype=np.int32)
    owned_slot[drow] = slot

    # --- local stencil tables: one global (part, slot-rank) ghost lookup --
    loc = np.zeros((n, K), np.int64)
    loc[same] = local_pos[nbc[same]]
    if gp.size:
        gkey = gp * n + gr                          # ascending by build order
        pos = np.searchsorted(gkey, part64[grow] * n + srank[nbc[grow, gcol]])
        loc[grow, gcol] = cap + grank[pos]
    nbr_local = np.zeros((S * cap, K), np.int32)
    nbr_valid = np.zeros((S * cap, K), bool)
    coeff_l = np.zeros((S * cap, K), np.float32)
    nbr_local[drow] = np.where(valid, loc, 0)
    nbr_valid[drow] = valid
    coeff_l[drow] = coeff
    owned_idx = owned_idx.reshape(S, cap)
    owned_slot = owned_slot.reshape(S, cap)
    nbr_local = nbr_local.reshape(S, cap, K)
    nbr_valid = nbr_valid.reshape(S, cap, K)
    coeff_l = coeff_l.reshape(S, cap, K)

    # --- routing stages ----------------------------------------------------
    if N == 1:
        stages, ghost_fetch = _flat_stages_vec(
            axes[0], S, n, gp, gc, gr, grank, part64, local_pos, gcap
        )
    else:
        stages, ghost_fetch = _two_hop_stages_vec(
            axes, N, D, n, gp, gc, gr, grank, part64, local_pos, gcap
        )

    mets = _halo_metrics_vec(
        part, nbr, ocounts, gcounts, gp, gc, D, stages, weights,
        with_quality=with_metrics,
    )
    _row_counts(mets, other)
    mets["PlanBuildSeconds"] = time.perf_counter() - t_build
    if _capture is not None:
        _capture.update(
            part64=part64, srank=srank, valid=valid, nbc=nbc,
            ocells=ocells, okey=oprow * n + srank[ocells],
            ocounts=ocounts, local_pos=local_pos, same=same, other=other,
            gp=gp, gc=gc, gr=gr, gcounts=gcounts, cap=cap, gcap=gcap,
        )
    return HaloPlan(
        axes=axes,
        num_parts=S,
        cap=cap,
        gcap=gcap,
        K=K,
        owned_idx=owned_idx,
        owned_slot=owned_slot,
        nbr_local=nbr_local,
        nbr_valid=nbr_valid,
        coeff=coeff_l,
        stages=stages,
        ghost_fetch=ghost_fetch,
        finer_rows=list_finer_rows(nbr_valid),
        metrics=mets,
    )


def _row_counts(mets: dict, other: np.ndarray) -> None:
    """``BoundaryCells``: owned rows with a ghost neighbor (a row of the
    (n, K) ``other`` lane flags set); ``InteriorCells``: the rest."""
    bnd = int(other.any(axis=1).sum())
    mets["InteriorCells"] = other.shape[0] - bnd
    mets["BoundaryCells"] = bnd


def _flat_stages_vec(axis, S, n, gp, gc, gr, grank, part64, local_pos, gcap):
    """One all_to_all, filled by sorted-run ranks: lane (o -> p) carries
    o's cells that p ghosts, in p's ghost order (ascending slot)."""
    gowner = part64[gc]
    counts = np.bincount(gowner * S + gp, minlength=S * S)
    hcap = _roundup(int(counts.max()) if counts.size else 1)
    ord2 = np.lexsort((gr, gowner, gp))             # (p, o, slot) runs
    t = _run_ranks((gp * S + gowner)[ord2])
    idx = np.full((S, S, hcap), -1, np.int32)
    idx[gowner[ord2], gp[ord2], t] = local_pos[gc[ord2]]
    fetch = np.full((S, gcap), -1, np.int32)
    fetch[gp[ord2], grank[ord2]] = gowner[ord2] * hcap + t
    return (Stage(axis=axis, lanes=S, cap=hcap, idx=idx),), fetch


def _two_hop_stages_vec(axes, N, D, n, gp, gc, gr, grank, part64, local_pos, gcap):
    """Node-aware exchange via segment ops: hop A (node axis,
    per-destination-node dedup = sorted-unique over (owner, dest node,
    cell)), hop B (device axis, fan-out inside the node).

    Shard ids are node-major (shard = node * D + device). Hop A: owner
    (n_o, d_o) stages each cell once per destination NODE m; after the
    node-axis all_to_all the value sits on intermediate device (m, d_o)
    at flat position n_o * capA + t. Hop B: (m, d_o) restages into
    device lanes; requester (m, d') fetches at d_o * capB + t2. Ghosts
    with m == n_o use hop A's self-lane — intra-node by construction.
    """
    node_axis, device_axis = axes
    S = N * D
    gowner = part64[gc]
    gnode = gp // D                                  # destination node m
    # hop A dedup: unique (owner, dest node, slot), ranked by slot
    ordA = np.lexsort((gr, gnode, gowner))
    keyA = (gowner * N + gnode) * n + gr             # unique per (o, m, cell)
    kA = keyA[ordA]
    keep = np.ones((kA.size,), bool)
    keep[1:] = kA[1:] != kA[:-1]
    Ao = gowner[ordA][keep]
    Am = gnode[ordA][keep]
    Ac = gc[ordA][keep]
    Akey = kA[keep]
    grpA = Ao * N + Am
    tA = _run_ranks(grpA)
    sizesA = np.bincount(grpA, minlength=S * N)
    capA = _roundup(int(sizesA.max()) if Ao.size else 1)
    idxA = np.full((S, N, capA), -1, np.int32)
    idxA[Ao, Am, tA] = local_pos[Ac]
    # per-ghost hop-A slot via one searchsorted on the dedup keys
    posA = np.searchsorted(Akey, keyA)
    srcA = (gowner // D) * capA + tA[posA]           # position in q's recvA

    # hop B: intermediate (m, d_o) restages recvA entries to device lanes
    d_o = gowner % D
    q = gnode * D + d_o                              # intermediate shard
    d_req = gp % D
    ordB = np.lexsort((gr, d_o, gp))                 # (p, d_o, slot) runs
    t2 = _run_ranks((gp * D + d_o)[ordB])
    capB = _roundup(int(t2.max()) + 1 if t2.size else 1)
    idxB = np.full((S, D, capB), -1, np.int32)
    idxB[q[ordB], d_req[ordB], t2] = srcA[ordB]
    fetch = np.full((S, gcap), -1, np.int32)
    fetch[gp[ordB], grank[ordB]] = d_o[ordB] * capB + t2
    return (
        Stage(axis=node_axis, lanes=N, cap=capA, idx=idxA),
        Stage(axis=device_axis, lanes=D, cap=capB, idx=idxB),
    ), fetch


def _halo_metrics_vec(
    part, nbr, ocounts, gcounts, gp, gc, D, stages, weights, *, with_quality=True
):
    """Halo metrics by masked sums over the ghost arrays and lane
    tables; the O(n*K) `partition_report` pass only when requested."""
    S = ocounts.shape[0]
    rep = {}
    if with_quality:
        rep = plan_quality_metrics(part, nbr, S, weights)
    rep.update(_metrics.surface_index(ocounts, gcounts))
    owner_node = np.asarray(part)[gc] // D
    inter = int((owner_node != gp // D).sum())
    rep["IntraNodeGhosts"] = int(gp.size - inter)
    rep["InterNodeGhosts"] = inter
    # inter-node float32 payload of ONE exchange (hop A lanes leaving the
    # node; the flat plan's lanes crossing nodes)
    st = stages[0]
    cnt = (st.idx >= 0).sum(axis=2)                  # (S, lanes)
    o = np.arange(S, dtype=np.int64)[:, None]
    lane = np.arange(st.lanes, dtype=np.int64)[None, :]
    mask = (lane // D != o // D) if len(stages) == 1 else (lane != o // D)
    ib = int(cnt[mask].sum())
    rep["InterNodeValuesPerExchange"] = ib
    rep["InterNodeBytesPerExchange"] = 4 * ib
    return rep


# ---------------------------------------------------------------------------
# migration (state-move) plans
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MovePlan:
    """Compiled state exchange for one partition change.

    ``kind``: "none" (assignments identical), "device" (all moves
    node-local — a single device-axis hop that provably never crosses
    the inter-node boundary), "flat" (one hop on a 1-D mesh), or "hier"
    (two-hop over a (node, device) mesh). ``keep`` marks old-layout rows
    staying put; routed rows merge in by storage-slot sort.
    ``migration`` is the `repro.core.migration` plan (level-aware on
    hierarchies) for round/byte accounting.
    """

    kind: str
    axes: tuple[str, ...]
    cap_old: int
    cap_new: int
    keep: np.ndarray               # (S, cap_old) bool
    stages: tuple[Stage, ...]
    migration: object
    metrics: dict = field(default_factory=dict)

    @property
    def stage_meta(self) -> tuple:
        return tuple((s.axis, s.lanes, s.cap) for s in self.stages)

    def padded(self, cap_old: int, cap_new: int, stage_caps: tuple) -> "MovePlan":
        """The same move between layouts padded to ``cap_old`` /
        ``cap_new`` rows, its hops to ``stage_caps`` (see
        :meth:`HaloPlan.padded`)."""
        stages, _ = _pad_stages(self.stages, stage_caps)
        return MovePlan(
            kind=self.kind, axes=self.axes, cap_old=int(cap_old), cap_new=int(cap_new),
            keep=_pad_last(self.keep, int(cap_old), False), stages=stages,
            migration=self.migration, metrics=self.metrics,
        )


def build_move_plan(
    old: HaloPlan,
    new: HaloPlan,
    *,
    hierarchy=None,
    full: bool = False,
    cache=None,
) -> MovePlan:
    """Compile the owned-state exchange from ``old``'s layout to
    ``new``'s (same cells, new part assignment).

    Incremental mode (default) routes only the rows whose owner changed
    and, when the level-aware migration plan certifies zero inter-node
    movement on a hierarchy, runs the single intra-node hop. ``full``
    stages EVERY row to its (possibly unchanged) owner — the
    redistribute a cold rebuild pays, carried by the same machinery so
    the walltime comparison is apples-to-apples.

    Vectorized: the old and new layouts are joined on ``owned_slot`` by
    one sort + ``searchsorted`` (no per-slot dicts), and the lane
    tables fill by sorted-run ranks.

    ``cache`` (the same :class:`~repro.mesh.plan_cache.PlanCache` the
    halo builds used) shares the per-event owner gather: when ``old``
    and ``new`` are the cache's last two halo builds, the slot-sorted
    (old owner, new owner, old row, slot) join is read from the cached
    layout state instead of re-deriving it from ``owned_slot`` — one
    gather per partition event, not two. The output is bit-identical
    either way (the join is a pure function of the two layouts).
    """
    t_build = time.perf_counter()
    S = old.owned_idx.shape[0]
    pro = cache.move_prologue(old, new) if cache is not None else None
    if pro is not None:
        old_part, new_part, ot_r, oslot = pro
    else:
        # old layout rows, joined to the new owner by slot sort (slots
        # are unique, so ascending slot is the canonical merge order)
        op_r, ot_r = np.nonzero(old.owned_slot >= 0)
        oslot = old.owned_slot[op_r, ot_r]
        oo = np.argsort(oslot, kind="stable")
        op_r, ot_r, oslot = op_r[oo].astype(np.int64), ot_r[oo].astype(np.int64), oslot[oo]
        np_r, nt_r = np.nonzero(new.owned_slot >= 0)
        nslot = new.owned_slot[np_r, nt_r]
        no = np.argsort(nslot, kind="stable")
        np_r, nslot = np_r[no].astype(np.int64), nslot[no]
        pos = np.searchsorted(nslot, oslot)
        hit = (pos < nslot.size) & (nslot[np.minimum(pos, max(nslot.size - 1, 0))] == oslot)
        if not hit.all():
            raise KeyError(int(oslot[~hit][0]))
        old_part = op_r
        new_part = np_r[pos]
    mig = _migration.migration_plan(
        old_part, new_part, S,
        hierarchy=hierarchy if (hierarchy is not None and hierarchy.num_nodes > 1) else None,
    )
    keep = np.zeros((S, old.cap), bool)
    if full:
        mm = np.ones((oslot.size,), bool)
    else:
        stay = new_part == old_part
        keep[old_part[stay], ot_r[stay]] = True
        mm = ~stay
    msrc, mdst, mt, mslot = old_part[mm], new_part[mm], ot_r[mm], oslot[mm]
    mets_extra = {} if pro is None else {"PlanCacheHits": cache.stats.move_hits}
    if msrc.size == 0:
        return MovePlan(
            kind="none", axes=old.axes, cap_old=old.cap, cap_new=new.cap,
            keep=keep, stages=(), migration=mig,
            metrics={**mets_extra, "PlanBuildSeconds": time.perf_counter() - t_build},
        )

    if hierarchy is not None and hierarchy.num_nodes > 1:
        N, D = int(hierarchy.num_nodes), int(hierarchy.devices_per_node)
        node_local = bool((msrc // D == mdst // D).all())
        if node_local and not full:
            # intra-node only: one device-axis hop, lanes = dest device.
            # The compiled program contains no node-axis collective at
            # all — node-local migration cannot cross the boundary.
            lane = mdst % D
            cap = _roundup(int(np.bincount(msrc * D + lane, minlength=S * D).max()))
            ordm = np.lexsort((mslot, lane, msrc))
            r = _run_ranks((msrc * D + lane)[ordm])
            idx = np.full((S, D, cap), -1, np.int32)
            idx[msrc[ordm], lane[ordm], r] = mt[ordm]
            stages = (Stage(axis=hierarchy.device_axis, lanes=D, cap=cap, idx=idx),)
            kind = "device"
        else:
            # two hops: dest node, then dest device inside it
            m_node = mdst // D
            capA = _roundup(int(np.bincount(msrc * N + m_node, minlength=S * N).max()))
            ordA = np.lexsort((mslot, m_node, msrc))
            tA = _run_ranks((msrc * N + m_node)[ordA])
            idxA = np.full((S, N, capA), -1, np.int32)
            idxA[msrc[ordA], m_node[ordA], tA] = mt[ordA]
            srcA = np.empty((msrc.size,), np.int64)
            srcA[ordA] = (msrc[ordA] // D) * capA + tA
            q = m_node * D + msrc % D            # intermediate shard
            lane = mdst % D
            capB = _roundup(int(np.bincount(q * D + lane, minlength=S * D).max()))
            ordB = np.lexsort((mslot, lane, q))
            t2 = _run_ranks((q * D + lane)[ordB])
            idxB = np.full((S, D, capB), -1, np.int32)
            idxB[q[ordB], lane[ordB], t2] = srcA[ordB]
            stages = (
                Stage(axis=hierarchy.node_axis, lanes=N, cap=capA, idx=idxA),
                Stage(axis=hierarchy.device_axis, lanes=D, cap=capB, idx=idxB),
            )
            kind = "hier"
    else:
        cap = _roundup(int(np.bincount(msrc * S + mdst, minlength=S * S).max()))
        ordm = np.lexsort((mslot, mdst, msrc))
        r = _run_ranks((msrc * S + mdst)[ordm])
        idx = np.full((S, S, cap), -1, np.int32)
        idx[msrc[ordm], mdst[ordm], r] = mt[ordm]
        stages = (Stage(axis=old.axes[-1], lanes=S, cap=cap, idx=idx),)
        kind = "flat"
    return MovePlan(
        kind=kind, axes=old.axes, cap_old=old.cap, cap_new=new.cap,
        keep=keep, stages=stages, migration=mig,
        metrics={**mets_extra, "PlanBuildSeconds": time.perf_counter() - t_build},
    )


# re-export: the cross-event cache lives in its own module but is part
# of this layer's public surface (`build_halo_plan(..., cache=...)`)
from repro.mesh.plan_cache import PlanCache, PlanCacheStats  # noqa: E402,F401
