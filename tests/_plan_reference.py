"""Per-part loop builders of halo and move plans: the oracle that
``test_plan_equivalence.py`` holds `repro.mesh.halo`'s segment-op
builders (and the `PlanCache` patches) equal to, field by field.

Straight-line Python over parts, cells and slots, in the canonical
ascending-slot order the product builders define; host numpy only.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import metrics as _metrics
from repro.core import migration as _migration
from repro.mesh.amr import face_group
from repro.mesh.halo import (
    HaloPlan,
    MovePlan,
    Stage,
    _plan_shape,
    _roundup,
    plan_quality_metrics,
)


def _owned_layout(slot: np.ndarray, part: np.ndarray, num_parts: int):
    """Per-part owned cell lists in ascending-slot order + local position
    of every cell on its owner."""
    n = slot.shape[0]
    owned = []
    local_pos = np.full((n,), -1, np.int64)
    for p in range(num_parts):
        cells = np.nonzero(part == p)[0]
        cells = cells[np.argsort(slot[cells], kind="stable")]
        owned.append(cells)
        local_pos[cells] = np.arange(cells.size)
    return owned, local_pos


def _ghost_sets(owned, part: np.ndarray, nbr: np.ndarray, slot: np.ndarray, num_parts: int):
    """Per-part ghost cell lists (ascending slot): cells owned elsewhere
    that neighbor at least one owned cell."""
    ghosts = []
    for p in range(num_parts):
        nb = nbr[owned[p]]
        cand = np.unique(nb[nb >= 0])
        g = cand[part[cand] != p]
        ghosts.append(g[np.argsort(slot[g], kind="stable")])
    return ghosts


def build_halo_plan_legacy(
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
) -> HaloPlan:
    """Per-part reference implementation of `halo.build_halo_plan`:
    straight-line Python loops over parts/cells, O(parts * cells) host
    work per event."""
    t_build = time.perf_counter()
    slot = np.asarray(slot, np.int64)
    part = np.asarray(part)
    n, K = nbr.shape
    N, D, S, axes = _plan_shape(part, hierarchy, num_parts, device_axis)

    owned, local_pos = _owned_layout(slot, part, S)
    ghosts = _ghost_sets(owned, part, nbr, slot, S)
    cap = _roundup(max(o.size for o in owned))
    gcap = _roundup(max(max(g.size for g in ghosts), 1))

    owned_idx = np.full((S, cap), -1, np.int32)
    owned_slot = np.full((S, cap), -1, np.int64)
    for p in range(S):
        owned_idx[p, : owned[p].size] = owned[p]
        owned_slot[p, : owned[p].size] = slot[owned[p]]

    # local stencil tables: neighbor j of owned cell -> local position in
    # [u_own (cap) | ghosts (gcap)]
    ghost_pos = [
        {int(c): i for i, c in enumerate(g)} for g in ghosts
    ]
    nbr_local = np.zeros((S, cap, K), np.int32)
    nbr_valid = np.zeros((S, cap, K), bool)
    coeff_l = np.zeros((S, cap, K), np.float32)
    for p in range(S):
        cells = owned[p]
        nb = nbr[cells]
        coeff_l[p, : cells.size] = coeff[cells]
        valid = nb >= 0
        nbr_valid[p, : cells.size] = valid
        loc = np.zeros_like(nb, dtype=np.int64)
        same = valid & (part[np.maximum(nb, 0)] == p)
        loc[same] = local_pos[nb[same]]
        other = valid & ~same
        if other.any():
            gp = ghost_pos[p]
            loc[other] = np.array([cap + gp[int(c)] for c in nb[other]], np.int64)
        nbr_local[p, : cells.size] = np.where(valid, loc, 0)

    # finer-row list: per part, each row with a valid slot that is not
    # the first of its face, in row order
    g = face_group(K)
    listed = [[t for t in range(owned[p].size)
               if any(nbr_valid[p, t, k] for k in range(K) if k % g)] for p in range(S)]
    most = max(len(r) for r in listed)
    finer_rows = np.full((S, _roundup(most) if most else 0), -1, np.int32)
    for p in range(S):
        finer_rows[p, : len(listed[p])] = listed[p]

    # a real row is a boundary row iff a valid lane reaches a ghost
    reads_ghost = (nbr_valid & (nbr_local >= cap)).any(axis=2)  # (S, cap)
    real = owned_idx >= 0

    if N == 1:
        stages, ghost_fetch = _flat_stages(
            axes[0], S, owned, ghosts, part, local_pos, gcap
        )
    else:
        stages, ghost_fetch = _two_hop_stages(
            axes, N, D, owned, ghosts, part, slot, local_pos, gcap
        )

    mets = _halo_metrics(
        part, nbr, owned, ghosts, N, D, stages, weights, with_quality=with_metrics
    )
    mets["InteriorCells"] = int((real & ~reads_ghost).sum())
    mets["BoundaryCells"] = int((real & reads_ghost).sum())
    mets["PlanBuildSeconds"] = time.perf_counter() - t_build
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
        finer_rows=finer_rows,
        metrics=mets,
    )


def _flat_stages(axis, S, owned, ghosts, part, local_pos, gcap):
    """One all_to_all: lane (o -> p) carries o's cells that p ghosts,
    ordered by p's ghost order (ascending slot)."""
    counts = np.zeros((S, S), np.int64)
    for p in range(S):
        for c in ghosts[p]:
            counts[part[c], p] += 1
    hcap = _roundup(int(counts.max()) if counts.size else 1)
    idx = np.full((S, S, hcap), -1, np.int32)
    fetch = np.full((S, gcap), -1, np.int32)
    for p in range(S):
        fill = np.zeros((S,), np.int64)
        for gpos, c in enumerate(ghosts[p]):
            o = int(part[c])
            t = fill[o]
            fill[o] += 1
            idx[o, p, t] = local_pos[c]
            fetch[p, gpos] = o * hcap + t
    return (Stage(axis=axis, lanes=S, cap=hcap, idx=idx),), fetch


def _two_hop_stages(axes, N, D, owned, ghosts, part, slot, local_pos, gcap):
    """Node-aware exchange: hop A (node axis, per-destination-node
    deduplicated), hop B (device axis, fan-out inside the node)."""
    node_axis, device_axis = axes
    S = N * D
    # hop A dedup: (owner shard, dest node) -> ordered cell list
    a_members: dict[tuple[int, int], dict[int, int]] = {}
    for p in range(S):
        m = p // D
        for c in ghosts[p]:
            key = (int(part[c]), m)
            a_members.setdefault(key, {})
            a_members[key].setdefault(int(c), -1)
    for key, cells in a_members.items():
        order = sorted(cells, key=lambda c: int(slot[c]))
        for t, c in enumerate(order):
            cells[c] = t
    capA = _roundup(max((len(v) for v in a_members.values()), default=1))
    idxA = np.full((S, N, capA), -1, np.int32)
    for (o, m), cells in a_members.items():
        for c, t in cells.items():
            idxA[o, m, t] = local_pos[c]

    # hop B: intermediate (m, d_o) restages recvA entries to device lanes
    b_fill = np.zeros((S, D), np.int64)
    b_entries: dict[tuple[int, int], list[tuple[int, int]]] = {}
    fetch = np.full((S, gcap), -1, np.int32)
    capB_needed = 1
    fetch_tmp = []
    for p in range(S):
        m, d_req = p // D, p % D
        for gpos, c in enumerate(ghosts[p]):
            o = int(part[c])
            n_o, d_o = o // D, o % D
            q = m * D + d_o                      # intermediate shard
            tA = a_members[(o, m)][int(c)]
            srcA = n_o * capA + tA               # position in q's recvA
            t2 = b_fill[q, d_req]
            b_fill[q, d_req] += 1
            b_entries.setdefault((q, d_req), []).append((t2, srcA))
            fetch_tmp.append((p, gpos, d_o, t2))
            capB_needed = max(capB_needed, t2 + 1)
    capB = _roundup(capB_needed)
    idxB = np.full((S, D, capB), -1, np.int32)
    for (q, d_req), entries in b_entries.items():
        for t2, srcA in entries:
            idxB[q, d_req, t2] = srcA
    for p, gpos, d_o, t2 in fetch_tmp:
        fetch[p, gpos] = d_o * capB + t2
    return (
        Stage(axis=node_axis, lanes=N, cap=capA, idx=idxA),
        Stage(axis=device_axis, lanes=D, cap=capB, idx=idxB),
    ), fetch


def _halo_metrics(part, nbr, owned, ghosts, N, D, stages, weights, *, with_quality=True):
    """Partition quality of this halo: the paper's table columns through
    the ONE `repro.core.metrics` implementation, plus surface index and
    the per-level ghost/byte split the hierarchy targets."""
    S = N * D
    rep = {}
    if with_quality:
        rep = plan_quality_metrics(part, nbr, S, weights)
    owned_counts = np.array([o.size for o in owned])
    ghost_counts = np.array([g.size for g in ghosts])
    rep.update(_metrics.surface_index(owned_counts, ghost_counts))
    intra = inter = 0
    for p in range(S):
        if ghosts[p].size:
            owner_node = part[ghosts[p]] // D
            inter += int((owner_node != p // D).sum())
            intra += int((owner_node == p // D).sum())
    rep["IntraNodeGhosts"] = intra
    rep["InterNodeGhosts"] = inter
    # inter-node float32 payload of ONE exchange (hop A lanes leaving the
    # node; the flat plan's lanes crossing nodes)
    ib = 0
    st = stages[0]
    for o in range(S):
        for lane in range(st.lanes):
            cnt = int((st.idx[o, lane] >= 0).sum())
            if len(stages) == 1:
                if lane // D != o // D:
                    ib += cnt
            else:
                if lane != o // D:
                    ib += cnt
    rep["InterNodeValuesPerExchange"] = ib
    rep["InterNodeBytesPerExchange"] = 4 * ib
    return rep


def build_move_plan_legacy(
    old: HaloPlan,
    new: HaloPlan,
    *,
    hierarchy=None,
    full: bool = False,
) -> MovePlan:
    """Per-slot dict reference implementation of `halo.build_move_plan`."""
    t_build = time.perf_counter()
    S = old.owned_idx.shape[0]
    # old shard + local position per slot
    slot_old: dict[int, tuple[int, int]] = {}
    for p in range(S):
        for t, s in enumerate(old.owned_slot[p]):
            if s >= 0:
                slot_old[int(s)] = (p, t)
    part_of_slot: dict[int, int] = {}
    for p in range(S):
        for s in new.owned_slot[p]:
            if s >= 0:
                part_of_slot[int(s)] = p
    slots = sorted(slot_old)
    old_part = np.array([slot_old[s][0] for s in slots], np.int64)
    new_part = np.array([part_of_slot[s] for s in slots], np.int64)
    mig = _migration.migration_plan(
        old_part, new_part, S,
        hierarchy=hierarchy if (hierarchy is not None and hierarchy.num_nodes > 1) else None,
    )
    keep = np.zeros((S, old.cap), bool)
    moved: list[tuple[int, int, int, int]] = []  # (slot, src, dst, src_pos)
    for s in slots:
        p_old, t = slot_old[s]
        p_new = part_of_slot[s]
        if p_new == p_old and not full:
            keep[p_old, t] = True
        else:
            moved.append((s, p_old, p_new, t))
    if not moved:
        return MovePlan(
            kind="none", axes=old.axes, cap_old=old.cap, cap_new=new.cap,
            keep=keep, stages=(), migration=mig,
            metrics={"PlanBuildSeconds": time.perf_counter() - t_build},
        )

    if hierarchy is not None and hierarchy.num_nodes > 1:
        N, D = int(hierarchy.num_nodes), int(hierarchy.devices_per_node)
        node_local = all(src // D == dst // D for _, src, dst, _ in moved)
        if node_local and not full:
            counts = np.zeros((S, D), np.int64)
            for _, src, dst, _ in moved:
                counts[src, dst % D] += 1
            cap = _roundup(int(counts.max()))
            idx = np.full((S, D, cap), -1, np.int32)
            fill = np.zeros((S, D), np.int64)
            for _, src, dst, t in sorted(moved):
                lane = dst % D
                idx[src, lane, fill[src, lane]] = t
                fill[src, lane] += 1
            stages = (Stage(axis=hierarchy.device_axis, lanes=D, cap=cap, idx=idx),)
            kind = "device"
        else:
            # two hops: dest node, then dest device inside it
            cntA = np.zeros((S, N), np.int64)
            for _, src, dst, _ in moved:
                cntA[src, dst // D] += 1
            capA = _roundup(int(cntA.max()))
            idxA = np.full((S, N, capA), -1, np.int32)
            fillA = np.zeros((S, N), np.int64)
            posA: dict[int, tuple[int, int, int]] = {}  # slot -> (inter q, srcA, dst)
            for s, src, dst, t in sorted(moved):
                m = dst // D
                tA = fillA[src, m]
                fillA[src, m] += 1
                idxA[src, m, tA] = t
                q = m * D + src % D
                posA[s] = (q, (src // D) * capA + tA, dst)
            cntB = np.zeros((S, D), np.int64)
            for q, _, dst in posA.values():
                cntB[q, dst % D] += 1
            capB = _roundup(int(cntB.max()))
            idxB = np.full((S, D, capB), -1, np.int32)
            fillB = np.zeros((S, D), np.int64)
            for s in sorted(posA):
                q, srcA, dst = posA[s]
                lane = dst % D
                idxB[q, lane, fillB[q, lane]] = srcA
                fillB[q, lane] += 1
            stages = (
                Stage(axis=hierarchy.node_axis, lanes=N, cap=capA, idx=idxA),
                Stage(axis=hierarchy.device_axis, lanes=D, cap=capB, idx=idxB),
            )
            kind = "hier"
    else:
        counts = np.zeros((S, S), np.int64)
        for _, src, dst, _ in moved:
            counts[src, dst] += 1
        cap = _roundup(int(counts.max()))
        idx = np.full((S, S, cap), -1, np.int32)
        fill = np.zeros((S, S), np.int64)
        for _, src, dst, t in sorted(moved):
            idx[src, dst, fill[src, dst]] = t
            fill[src, dst] += 1
        stages = (Stage(axis=old.axes[-1], lanes=S, cap=cap, idx=idx),)
        kind = "flat"
    return MovePlan(
        kind=kind, axes=old.axes, cap_old=old.cap, cap_new=new.cap,
        keep=keep, stages=stages, migration=mig,
        metrics={"PlanBuildSeconds": time.perf_counter() - t_build},
    )
