"""Event-cached plan construction: persist the expensive intermediates
of :func:`repro.mesh.halo.build_halo_plan` across repartition events and
delta-patch instead of rebuilding.

Plan construction after PR 8 is pure segment ops, but every event still
pays the full (n, K) neighbor-owner gather, the global (part, slot)
lexsort, and the global ghost dedup from scratch — even an intra-node
reslice that moves <5% of the cells. This module splits the build state
into two tiers and patches the second:

**Topology tier** (valid while the mesh itself is unchanged — keyed on
an optional ``topo_token`` such as the engine's ``topology_version``,
plus value equality of ``slot``/``nbr``/``coeff``):

* ``srank``/``sorder`` — the slot-rank compression (one global argsort),
* ``valid``/``nbc`` — the clamped (n, K) neighbor table,
* a reverse-CSR *incidence* index: for each cell c, the flat positions
  j into the (n·K) neighbor table with ``nbr.flat[j] == c``. This is
  the "CSR ghost-pair cache": it answers *whose stencil rows mention a
  moved cell* in O(degree) instead of an O(n·K) rescan.

**Partition tier** (patched per event): the (part, slot)-sorted owned
layout (``ocells``/``okey``/``ocounts``/``local_pos``), the
same/other lane flags, the deduped ghost pair lists (``gp``/``gc``/
``gr``), and the compiled stencil tables of the last plan.

Patch rule for a reslice that moves cell set M: let T be the union of
old and new owners of M. Only rows of parts in T can change — a row of
an untouched part keeps its owner, its lane flags (its neighbors'
owners moved only between *other* parts, which flips no same/other
bit... except where a neighbor IS a moved cell, which the incidence
index localizes), its ghost list as a set, and (because ghost keys are
(part, slot-rank) and the owned layout of untouched parts is
unchanged) every compiled index. So the patch: (1) flip same/other at
the incident positions of M plus all lanes of M's own rows; (2) merge
M's rows out of/into the sorted owned layout with one
``searchsorted`` (O(n) memmove instead of an O(n log n) lexsort);
(3) recompute ghost pairs for T's rows only and splice them against
the retained pairs of untouched parts; (4) rewrite the stencil-table
blocks of T's parts with the *same formulas* the scratch builder uses;
(5) re-pack the routing stages (O(G log G) on the small ghost set).
Because every retained array region is provably what the scratch
builder would produce and every rewritten region uses the scratch
formulas on identical inputs, the patched plan is **bit-identical**
(``np.array_equal``, every field) to a fresh scratch build — which is
itself bit-identical to a per-part loop builder, a two-deep oracle
chain exercised in ``tests/test_plan_equivalence.py``.

When the owned capacity crosses a roundup quantum the padded table
shapes change; the patch then copies each part block into the
re-padded shape and shifts the ghost-lane offsets (the only
cap-dependent values) — same memcpy cost as the aligned patch.
Fallbacks keep the fast path honest: the cache rebuilds from scratch
(reusing the topology tier) when the moved fraction exceeds
``max_patch_frac`` (default 25% — past that the patch does more work
than the lexsort it replaces) or when the plan shape (hierarchy /
part count) changes. A changed topology token or changed
``slot``/``nbr``/``coeff`` values refresh the topology tier.

The same cache also serves :func:`~repro.mesh.halo.build_move_plan`:
the slot-sorted (old owner, new owner, old row) join that the move
builder needs is exactly the cached layout state of the last two halo
builds, read back through :meth:`PlanCache.move_prologue` — one owner
gather per partition event, shared by both builders.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from repro.mesh import halo as _halo


@dataclass
class PlanCacheStats:
    """Cumulative cache behavior over a run (reported into SimStats)."""

    halo_hits: int = 0       # halo builds served by patch or no-op reuse
    halo_misses: int = 0     # halo builds that fell back to scratch
    move_hits: int = 0       # move prologues served from cached layout
    move_misses: int = 0     # move builds that re-derived the join
    topo_refreshes: int = 0  # topology-tier rebuilds (AMR / first build)
    patched_rows: int = 0    # owned rows rewritten by segment patches


def _expand_segments(ptr: np.ndarray, sel: np.ndarray) -> np.ndarray:
    """Concatenated positions ``ptr[s]:ptr[s+1]`` for every s in ``sel``
    (the vectorized form of ``hstack([arange(ptr[s], ptr[s+1]) ...])``)."""
    sel = np.asarray(sel, np.int64)
    lens = ptr[sel + 1] - ptr[sel]
    total = int(lens.sum())
    if total == 0:
        return np.zeros((0,), np.int64)
    starts = np.repeat(ptr[sel], lens)
    seg_base = np.repeat(np.concatenate(([0], np.cumsum(lens)[:-1])), lens)
    return np.arange(total, dtype=np.int64) - seg_base + starts


class PlanCache:
    """Cross-event cache for halo/move plan construction.

    One instance per simulation run (or per mesh stream). Thread-safe
    only for serial use — plan construction is host-side and serial by
    design. Returned plans never alias mutable cache state: the patch
    path copies before writing, and the cache never mutates arrays it
    has handed out.
    """

    def __init__(self, max_patch_frac: float = 0.25):
        self.max_patch_frac = float(max_patch_frac)
        self.stats = PlanCacheStats()
        self._topo: dict | None = None
        self._state: dict | None = None
        self._last_plan = None
        self._prev_plan = None
        self._prev_part64 = None
        self._prev_local_pos = None

    # -- invalidation -------------------------------------------------------

    def reset(self) -> None:
        """Drop everything (topology + partition tiers)."""
        self._topo = None
        self._drop_partition_state()

    def invalidate_topology(self) -> None:
        """Alias of :meth:`reset`: a topology change invalidates both
        tiers (the partition state indexes cells of the old mesh)."""
        self.reset()

    def _drop_partition_state(self) -> None:
        self._state = None
        self._last_plan = None
        self._prev_plan = None
        self._prev_part64 = None
        self._prev_local_pos = None

    # -- topology tier ------------------------------------------------------

    def _topo_valid(self, slot, nbr, coeff, topo_token) -> bool:
        t = self._topo
        if t is None:
            return False
        if topo_token is not None and t["token"] != topo_token:
            return False
        n, K = nbr.shape
        if t["n"] != n or t["K"] != K or slot.shape[0] != n:
            return False
        # identity is the fast path (trajectories reuse array objects
        # between AMR events); fall back to value equality
        for ref_key, val_key, arr in (
            ("slot_ref", "slot64", slot),
            ("nbr_ref", "nbr", nbr),
            ("coeff_ref", "coeff", coeff),
        ):
            if arr is t[ref_key]:
                continue
            if not np.array_equal(np.asarray(arr), t[val_key]):
                return False
            t[ref_key] = arr
        return True

    def _refresh_topology(self, slot, nbr, coeff, topo_token, cap: dict) -> None:
        n, K = nbr.shape
        valid, nbc = cap["valid"], cap["nbc"]
        # reverse-CSR incidence: flat neighbor-table positions per
        # mentioned cell, grouped by cell id
        jpos = np.flatnonzero(valid.ravel())
        ckey = nbc.ravel()[jpos]
        inc_flat = jpos[np.argsort(ckey, kind="stable")]
        inc_ptr = np.zeros((n + 1,), np.int64)
        inc_ptr[1:] = np.cumsum(np.bincount(ckey, minlength=n))
        self._topo = dict(
            token=topo_token, n=n, K=K,
            slot_ref=slot, slot64=np.asarray(slot, np.int64).copy(),
            nbr_ref=nbr, nbr=np.array(nbr),
            coeff_ref=coeff, coeff=np.array(coeff),
            srank=cap["srank"], sorder=cap["sorder"],
            valid=valid, nbc=nbc,
            inc_ptr=inc_ptr, inc_flat=inc_flat,
        )
        self.stats.topo_refreshes += 1

    # -- partition tier -----------------------------------------------------

    def _stash_prev(self) -> None:
        """Keep one generation of layout state for the move prologue."""
        if self._state is not None:
            self._prev_plan = self._last_plan
            self._prev_part64 = self._state["part64"]
            self._prev_local_pos = self._state["local_pos"]
        else:
            self._prev_plan = None
            self._prev_part64 = None
            self._prev_local_pos = None

    def _install_state(self, plan, shape_key, d: dict) -> None:
        self._state = dict(
            shape_key=shape_key,
            part64=d["part64"], ocells=d["ocells"], okey=d["okey"],
            ocounts=d["ocounts"], local_pos=d["local_pos"],
            same=d["same"], other=d["other"],
            gp=d["gp"], gc=d["gc"], gr=d["gr"], gcounts=d["gcounts"],
            cap=d["cap"], gcap=d["gcap"],
        )
        self._last_plan = plan

    # -- move-plan sharing --------------------------------------------------

    def move_prologue(self, old_plan, new_plan):
        """Slot-sorted (old_part, new_part, old_row, slot) join for
        :func:`~repro.mesh.halo.build_move_plan`, read from the cached
        layout of the last two halo builds. Returns None (a miss) when
        ``old``/``new`` are not this cache's plans — the builder then
        re-derives the join from ``owned_slot`` as before."""
        t, st = self._topo, self._state
        if t is None or st is None or new_plan is not self._last_plan:
            self.stats.move_misses += 1
            return None
        if old_plan is self._last_plan:
            old_part64, old_lp = st["part64"], st["local_pos"]
        elif old_plan is self._prev_plan and self._prev_part64 is not None:
            old_part64, old_lp = self._prev_part64, self._prev_local_pos
        else:
            self.stats.move_misses += 1
            return None
        so = t["sorder"]
        self.stats.move_hits += 1
        return old_part64[so], st["part64"][so], old_lp[so], t["slot64"][so]


def cached_build_halo_plan(
    cache: PlanCache, slot, part, nbr, coeff, *,
    hierarchy=None, num_parts=None, device_axis="device", weights=None,
    with_metrics=True, topo_token=None,
):
    """:func:`~repro.mesh.halo.build_halo_plan` through a
    :class:`PlanCache` — bit-identical output, patched construction."""
    t_build = time.perf_counter()
    slot_a = np.asarray(slot)
    part_a = np.asarray(part)
    n, K = nbr.shape
    N, D, S, axes = _halo._plan_shape(part_a, hierarchy, num_parts, device_axis)
    shape_key = (N, D, S, axes)

    topo_ok = cache._topo_valid(slot_a, nbr, coeff, topo_token)
    st = cache._state if topo_ok else None
    if st is not None and st["shape_key"] != shape_key:
        st = None
    if st is None:
        return _full_build(
            cache, slot_a, part_a, nbr, coeff, hierarchy, num_parts,
            device_axis, weights, with_metrics, topo_ok, topo_token,
            shape_key,
        )

    part64 = part_a.astype(np.int64)
    if n and (part64.min() < 0 or part64.max() >= S):
        raise ValueError(f"part ids must lie in [0, {S})")
    moved = np.flatnonzero(part64 != st["part64"])
    if moved.size == 0:
        # identical partition -> identical plan; reuse every compiled
        # array (the cache never mutates them) under fresh metrics
        cache.stats.halo_hits += 1
        old = cache._last_plan
        mets = _halo._halo_metrics_vec(
            part_a, nbr, st["ocounts"], st["gcounts"], st["gp"], st["gc"],
            D, old.stages, weights, with_quality=with_metrics,
        )
        mets["InteriorCells"] = old.metrics["InteriorCells"]
        mets["BoundaryCells"] = old.metrics["BoundaryCells"]
        mets["PlanCacheHits"] = cache.stats.halo_hits
        mets["PatchedRows"] = 0
        mets["PlanBuildSeconds"] = time.perf_counter() - t_build
        plan = _halo.HaloPlan(
            axes=old.axes, num_parts=old.num_parts, cap=old.cap,
            gcap=old.gcap, K=old.K, owned_idx=old.owned_idx,
            owned_slot=old.owned_slot, nbr_local=old.nbr_local,
            nbr_valid=old.nbr_valid, coeff=old.coeff, stages=old.stages,
            ghost_fetch=old.ghost_fetch, finer_rows=old.finer_rows, metrics=mets,
        )
        cache._stash_prev()
        cache._last_plan = plan
        return plan
    if moved.size > cache.max_patch_frac * n:
        return _full_build(
            cache, slot_a, part_a, nbr, coeff, hierarchy, num_parts,
            device_axis, weights, with_metrics, topo_ok, topo_token,
            shape_key,
        )
    return _patched_build(
        cache, part_a, part64, moved, nbr, weights, with_metrics,
        shape_key, t_build,
    )


def _full_build(
    cache, slot_a, part_a, nbr, coeff, hierarchy, num_parts, device_axis,
    weights, with_metrics, topo_ok, topo_token, shape_key,
):
    """Scratch build through the cache: reuse the topology tier when it
    is still valid, capture the intermediates for the next event."""
    topo = None
    if topo_ok:
        t = cache._topo
        topo = (t["srank"], t["valid"], t["nbc"])
    cap_d: dict = {}
    plan = _halo.build_halo_plan(
        slot_a, part_a, nbr, coeff, hierarchy=hierarchy, num_parts=num_parts,
        device_axis=device_axis, weights=weights, with_metrics=with_metrics,
        _topo=topo, _capture=cap_d,
    )
    if topo_ok:
        cache._stash_prev()
    else:
        cache._refresh_topology(slot_a, nbr, coeff, topo_token, cap_d)
        # prev layout indexes the old topology — unusable for moves
        cache._prev_plan = None
        cache._prev_part64 = None
        cache._prev_local_pos = None
    cache.stats.halo_misses += 1
    plan.metrics["PlanCacheHits"] = cache.stats.halo_hits
    plan.metrics["PatchedRows"] = 0
    cache._install_state(plan, shape_key, cap_d)
    return plan


def _patched_build(
    cache, part_a, part64, moved, nbr, weights, with_metrics, shape_key,
    t_build,
):
    """Delta-patch the cached build state for a reslice that moved cell
    set ``moved`` (bit-identical to a scratch build, see module doc)."""
    topo, st = cache._topo, cache._state
    n, K = topo["n"], topo["K"]
    N, D, S, axes = shape_key
    srank, valid, nbc = topo["srank"], topo["valid"], topo["nbc"]
    cap, gcap_old = st["cap"], st["gcap"]
    old_part64 = st["part64"]
    oldp_m = old_part64[moved]
    newp_m = part64[moved]
    in_T = np.zeros((S,), bool)
    in_T[oldp_m] = True
    in_T[newp_m] = True
    T = np.flatnonzero(in_T)

    # (1) same/other flags change only at lanes that mention a moved
    # cell (found via the reverse-CSR incidence) or belong to a moved
    # row; recompute those with the scratch formula
    aff = topo["inc_flat"][_expand_segments(topo["inc_ptr"], moved)]
    own_lanes = (moved[:, None] * K + np.arange(K, dtype=np.int64)[None, :]).ravel()
    aff = np.concatenate([aff, own_lanes])
    same = st["same"].copy()
    other = st["other"].copy()
    va = valid.ravel()[aff]
    nb_aff = nbc.ravel()[aff]
    s_new = va & (part64[nb_aff] == part64[aff // K])
    same.ravel()[aff] = s_new
    other.ravel()[aff] = va & ~s_new

    # (2) merge the moved rows out of / into the sorted owned layout:
    # one searchsorted over the retained keys replaces the global
    # lexsort. Keys are part*n + srank, the scratch sort order.
    moved_mask = np.zeros((n,), bool)
    moved_mask[moved] = True
    keepm = ~moved_mask[st["ocells"]]
    kept_cells = st["ocells"][keepm]
    kept_keys = st["okey"][keepm]
    mkey = newp_m * n + srank[moved]
    mo = np.argsort(mkey, kind="stable")
    mcells = moved[mo]
    mkeys = mkey[mo]
    posm = np.searchsorted(kept_keys, mkeys) + np.arange(mkeys.size, dtype=np.int64)
    fill = np.ones((n,), bool)
    fill[posm] = False
    ocells = np.empty((n,), np.int64)
    okey = np.empty((n,), np.int64)
    ocells[fill] = kept_cells
    okey[fill] = kept_keys
    ocells[posm] = mcells
    okey[posm] = mkeys
    ocounts = st["ocounts"].copy()
    np.subtract.at(ocounts, oldp_m, 1)
    np.add.at(ocounts, newp_m, 1)
    cap2 = _halo._roundup(int(ocounts.max()) if n else 0)
    ostarts = np.concatenate(([0], np.cumsum(ocounts)))
    orank = np.arange(n, dtype=np.int64) - ostarts[okey // n]
    local_pos = np.empty((n,), np.int64)
    local_pos[ocells] = orank

    # (3) ghost pairs: recompute for the touched parts' rows only,
    # splice against the retained pairs of untouched parts, and re-sort
    # the (small) concatenation — bit-identical because the deduped
    # pair set and its (part, slot-rank) sort key are unchanged
    cells_T = ocells[_expand_segments(ostarts, T)]
    other_T = other[cells_T]
    rr, cc = np.nonzero(other_T)
    gp_t = part64[cells_T[rr]]
    gc_t = nbc[cells_T[rr], cc]
    gr_t = srank[gc_t]
    keep_old = ~in_T[st["gp"]]
    gp2 = np.concatenate([st["gp"][keep_old], gp_t])
    gc2 = np.concatenate([st["gc"][keep_old], gc_t])
    gr2 = np.concatenate([st["gr"][keep_old], gr_t])
    gord = np.lexsort((gr2, gp2))
    gp2, gc2, gr2 = gp2[gord], gc2[gord], gr2[gord]
    if gp2.size:
        kp = np.ones((gp2.size,), bool)
        kp[1:] = (gp2[1:] != gp2[:-1]) | (gr2[1:] != gr2[:-1])
        gp2, gc2, gr2 = gp2[kp], gc2[kp], gr2[kp]
    gcounts = np.bincount(gp2, minlength=S)
    gstarts = np.concatenate(([0], np.cumsum(gcounts)))
    grank = np.arange(gp2.size, dtype=np.int64) - gstarts[gp2]
    gcap = _halo._roundup(max(int(gcounts.max()) if gcounts.size else 0, 1))

    # (4) stencil tables: reset the touched parts' padded blocks and
    # refill them with the scratch formulas; untouched blocks are
    # provably what a scratch build would produce. When the owned
    # capacity crosses a roundup quantum the padded block shapes
    # change: copy each block into the re-padded shape (same memcpy
    # the equal-cap patch pays) and shift the ghost-lane entries —
    # they encode ``cap + ghost_rank``, the only cap-dependent values
    # in an untouched block.
    old = cache._last_plan
    if cap2 == cap:
        owned_idx = old.owned_idx.reshape(-1).copy()
        owned_slot = old.owned_slot.reshape(-1).copy()
        nbr_localf = old.nbr_local.reshape(S * cap, K).copy()
        nbr_validf = old.nbr_valid.reshape(S * cap, K).copy()
        coeff_f = old.coeff.reshape(S * cap, K).copy()
    else:
        c = min(cap, cap2)
        oi = np.full((S, cap2), -1, np.int32)
        osl = np.full((S, cap2), -1, np.int64)
        nl = np.zeros((S, cap2, K), np.int32)
        nv = np.zeros((S, cap2, K), bool)
        cf = np.zeros((S, cap2, K), np.float32)
        oi[:, :c] = old.owned_idx[:, :c]
        osl[:, :c] = old.owned_slot[:, :c]
        nl[:, :c] = old.nbr_local[:, :c]
        nv[:, :c] = old.nbr_valid[:, :c]
        cf[:, :c] = old.coeff[:, :c]
        nl[nv & (nl >= cap)] += cap2 - cap
        owned_idx = oi.reshape(-1)
        owned_slot = osl.reshape(-1)
        nbr_localf = nl.reshape(S * cap2, K)
        nbr_validf = nv.reshape(S * cap2, K)
        coeff_f = cf.reshape(S * cap2, K)
        cap = cap2
    blk = (T[:, None] * cap + np.arange(cap, dtype=np.int64)[None, :]).ravel()
    owned_idx[blk] = -1
    owned_slot[blk] = -1
    nbr_localf[blk] = 0
    nbr_validf[blk] = False
    coeff_f[blk] = 0.0
    drow = part64[cells_T] * cap + local_pos[cells_T]
    owned_idx[drow] = cells_T.astype(np.int32)
    owned_slot[drow] = topo["slot64"][cells_T]
    va_T = valid[cells_T]
    nb_T = nbc[cells_T]
    same_T = same[cells_T]
    loc = np.zeros((cells_T.size, K), np.int64)
    loc[same_T] = local_pos[nb_T[same_T]]
    if gp2.size:
        gkey = gp2 * n + gr2
        qk = part64[cells_T[rr]] * n + srank[nb_T[rr, cc]]
        loc[rr, cc] = cap + grank[np.searchsorted(gkey, qk)]
    nbr_localf[drow] = np.where(va_T, loc, 0)
    nbr_validf[drow] = va_T
    coeff_f[drow] = topo["coeff"][cells_T]

    owned_idx = owned_idx.reshape(S, cap)
    owned_slot = owned_slot.reshape(S, cap)
    nbr_local = nbr_localf.reshape(S, cap, K)
    nbr_valid = nbr_validf.reshape(S, cap, K)
    coeff_l = coeff_f.reshape(S, cap, K)

    # (5) routing stages re-pack over the (small) ghost pair lists
    if N == 1:
        stages, ghost_fetch = _halo._flat_stages_vec(
            axes[0], S, n, gp2, gc2, gr2, grank, part64, local_pos, gcap
        )
    else:
        stages, ghost_fetch = _halo._two_hop_stages_vec(
            axes, N, D, n, gp2, gc2, gr2, grank, part64, local_pos, gcap
        )

    mets = _halo._halo_metrics_vec(
        part_a, nbr, ocounts, gcounts, gp2, gc2, D, stages, weights,
        with_quality=with_metrics,
    )
    _halo._row_counts(mets, other)
    cache.stats.halo_hits += 1
    cache.stats.patched_rows += int(cells_T.size)
    mets["PlanCacheHits"] = cache.stats.halo_hits
    mets["PatchedRows"] = int(cells_T.size)
    mets["PlanBuildSeconds"] = time.perf_counter() - t_build
    plan = _halo.HaloPlan(
        axes=axes, num_parts=S, cap=cap, gcap=gcap, K=K,
        owned_idx=owned_idx, owned_slot=owned_slot, nbr_local=nbr_local,
        nbr_valid=nbr_valid, coeff=coeff_l, stages=stages,
        ghost_fetch=ghost_fetch, finer_rows=_halo.list_finer_rows(nbr_valid),
        metrics=mets,
    )
    cache._stash_prev()
    cache._install_state(plan, shape_key, dict(
        part64=part64, ocells=ocells, okey=okey, ocounts=ocounts,
        local_pos=local_pos, same=same, other=other,
        gp=gp2, gc=gc2, gr=gr2, gcounts=gcounts, cap=cap, gcap=gcap,
    ))
    return plan
