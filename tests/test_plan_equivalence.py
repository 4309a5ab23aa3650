"""Vectorized plan builders vs the per-part legacy oracle — bit-identity.

The contract that makes the segment-op rewrite of `repro.mesh.halo` a
pure perf change: every output field of `build_halo_plan` /
`build_move_plan` is ``np.array_equal`` to the legacy loop builders'
in ``tests/_plan_reference.py`` (the ascending-slot canonical order and
stable fills are deterministic, so exact equality is the spec, not a
tolerance). The matrix covers flat
and (N, D) hierarchies, scattered and SFC-compact partitions,
non-contiguous slot ids, empty-ghost and empty parts, cap-rounding
boundaries, and every move-plan kind (incremental / full /
``kind="none"`` / node-local device-certified).

No jax required: plan construction is host-side numpy.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import pytest

from tests._hypothesis_compat import given, settings, strategies as st
from tests._plan_reference import build_halo_plan_legacy, build_move_plan_legacy

from repro.mesh import amr
from repro.mesh import halo


@dataclass(frozen=True)
class _Hier:
    """Hierarchy stand-in with the fields the halo/move builders read
    (matches `partitioner.HierarchyPlan` without importing jax)."""

    num_nodes: int
    devices_per_node: int
    node_axis: str = "node"
    device_axis: str = "device"
    inter_node_cost: float = 4.0

    @property
    def num_parts(self) -> int:
        return self.num_nodes * self.devices_per_node


def _mesh(seed: int, adapt_steps: int, base_level: int = 3):
    mesh = amr.uniform_mesh(2, base_level, base_level + 2)
    rng = np.random.default_rng(seed)
    for _ in range(adapt_steps):
        c = rng.random(2).astype(np.float64)
        ref, coar = amr.adapt_masks(mesh, c)
        mesh, _ = amr.refine_coarsen(mesh, ref, coar)
    nbr = amr.face_neighbors(mesh)
    coeff = amr.stencil_coeffs(mesh, nbr, amr.stable_dt(mesh))
    return mesh, nbr, coeff


def _slots(n: int, seed: int, contiguous: bool) -> np.ndarray:
    if contiguous:
        return np.arange(n, dtype=np.int64)
    rng = np.random.default_rng(seed + 1000)
    return rng.choice(3 * n, size=n, replace=False).astype(np.int64)


def _partition(mesh, S: int, seed: int, sfc: bool) -> np.ndarray:
    rng = np.random.default_rng(seed + 2000)
    if sfc:
        order = np.argsort(amr._pack(mesh.level, mesh.ij), kind="stable")
        part = np.empty((mesh.n,), np.int32)
        bounds = np.sort(rng.choice(mesh.n + 1, size=S - 1, replace=True))
        bounds = np.concatenate(([0], bounds, [mesh.n]))
        for p in range(S):
            part[order[bounds[p] : bounds[p + 1]]] = p
        return part
    return rng.integers(0, S, mesh.n).astype(np.int32)


# metric keys that legitimately differ between a cached and a scratch
# build of the same plan (timings + cache accounting)
_CACHE_METRICS = frozenset(
    {"PlanBuildSeconds", "PlanCacheHits", "PatchedRows"}
)


def assert_halo_equal(
    a: halo.HaloPlan, b: halo.HaloPlan, *, ignore=frozenset({"PlanBuildSeconds"})
) -> None:
    assert (a.axes, a.num_parts, a.cap, a.gcap, a.K) == (
        b.axes, b.num_parts, b.cap, b.gcap, b.K
    )
    for f in (
        "owned_idx", "owned_slot", "nbr_local", "nbr_valid", "coeff",
        "ghost_fetch", "finer_rows",
    ):
        assert np.array_equal(getattr(a, f), getattr(b, f)), f
    assert a.stage_meta == b.stage_meta
    for sa, sb in zip(a.stages, b.stages):
        assert np.array_equal(sa.idx, sb.idx), sa.axis
    ma = {k: v for k, v in a.metrics.items() if k not in ignore}
    mb = {k: v for k, v in b.metrics.items() if k not in ignore}
    assert ma.keys() == mb.keys()
    for k in ma:
        assert np.allclose(ma[k], mb[k]), k


def assert_move_equal(a: halo.MovePlan, b: halo.MovePlan) -> None:
    assert (a.kind, a.axes, a.cap_old, a.cap_new) == (
        b.kind, b.axes, b.cap_old, b.cap_new
    )
    assert np.array_equal(a.keep, b.keep)
    assert a.stage_meta == b.stage_meta
    for sa, sb in zip(a.stages, b.stages):
        assert np.array_equal(sa.idx, sb.idx), sa.axis
    assert np.array_equal(a.migration.send_counts, b.migration.send_counts)
    assert a.migration.total_moved == b.migration.total_moved
    assert getattr(a.migration, "inter_moved", None) == getattr(
        b.migration, "inter_moved", None
    )


def _build_pair(slot, part, nbr, coeff, hier, S):
    kw = dict(hierarchy=hier) if hier is not None else dict(num_parts=S)
    return (
        halo.build_halo_plan(slot, part, nbr, coeff, **kw),
        build_halo_plan_legacy(slot, part, nbr, coeff, **kw),
    )


@settings(max_examples=8, deadline=None)
@given(
    seed=st.integers(0, 5),
    adapt=st.integers(0, 2),
    nodes=st.sampled_from([1, 2]),
    dev=st.sampled_from([2, 4]),
    sfc=st.booleans(),
    contiguous=st.booleans(),
)
def test_halo_plan_bit_identical(seed, adapt, nodes, dev, sfc, contiguous):
    mesh, nbr, coeff = _mesh(seed, adapt)
    S = nodes * dev
    hier = _Hier(nodes, dev) if nodes > 1 else None
    slot = _slots(mesh.n, seed, contiguous)
    part = _partition(mesh, S, seed, sfc)
    pv, pl = _build_pair(slot, part, nbr, coeff, hier, S)
    assert_halo_equal(pv, pl)


@settings(max_examples=8, deadline=None)
@given(
    seed=st.integers(0, 5),
    nodes=st.sampled_from([1, 2]),
    dev=st.sampled_from([2, 4]),
    full=st.booleans(),
    frac=st.floats(0.0, 0.4),
)
def test_move_plan_bit_identical(seed, nodes, dev, full, frac):
    mesh, nbr, coeff = _mesh(seed, 1)
    S = nodes * dev
    hier = _Hier(nodes, dev) if nodes > 1 else None
    slot = _slots(mesh.n, seed, contiguous=False)
    part = _partition(mesh, S, seed, sfc=True)
    rng = np.random.default_rng(seed + 3000)
    part2 = part.copy()
    sw = rng.random(mesh.n) < frac
    part2[sw] = rng.integers(0, S, int(sw.sum()))
    pv, pl = _build_pair(slot, part, nbr, coeff, hier, S)
    pv2, pl2 = _build_pair(slot, part2, nbr, coeff, hier, S)
    kw = dict(hierarchy=hier, full=full)
    assert_move_equal(
        halo.build_move_plan(pv, pv2, **kw),
        build_move_plan_legacy(pl, pl2, **kw),
    )


def test_move_plan_kind_none():
    mesh, nbr, coeff = _mesh(0, 1)
    slot = _slots(mesh.n, 0, contiguous=True)
    part = _partition(mesh, 4, 0, sfc=True)
    pv, pl = _build_pair(slot, part, nbr, coeff, None, 4)
    mv, ml = halo.build_move_plan(pv, pv), build_move_plan_legacy(pl, pl)
    assert mv.kind == ml.kind == "none"
    assert_move_equal(mv, ml)


def test_move_plan_node_local_device_certified():
    # moves stay within each part's node -> the single device-axis hop
    mesh, nbr, coeff = _mesh(1, 1)
    hier = _Hier(2, 4)
    slot = _slots(mesh.n, 1, contiguous=False)
    part = _partition(mesh, 8, 1, sfc=True)
    rng = np.random.default_rng(7)
    part2 = part.copy()
    sw = rng.random(mesh.n) < 0.2
    part2[sw] = (part[sw] // 4) * 4 + rng.integers(0, 4, int(sw.sum()))
    pv, pl = _build_pair(slot, part, nbr, coeff, hier, 8)
    pv2, pl2 = _build_pair(slot, part2, nbr, coeff, hier, 8)
    mv = halo.build_move_plan(pv, pv2, hierarchy=hier)
    ml = build_move_plan_legacy(pl, pl2, hierarchy=hier)
    assert mv.kind == ml.kind
    if int(mv.migration.total_moved):
        assert mv.kind == "device"
    assert_move_equal(mv, ml)


def test_empty_ghost_and_empty_parts():
    # one part owns everything: other parts are empty, nobody has ghosts
    mesh, nbr, coeff = _mesh(2, 0)
    slot = np.arange(mesh.n, dtype=np.int64)
    part = np.zeros((mesh.n,), np.int32)
    pv, pl = _build_pair(slot, part, nbr, coeff, None, 4)
    assert_halo_equal(pv, pl)
    assert pv.metrics["InterNodeGhosts"] == 0
    assert pv.metrics["IntraNodeGhosts"] == 0
    # hierarchical shape of the same degenerate assignment
    pvh, plh = _build_pair(slot, part, nbr, coeff, _Hier(2, 2), 4)
    assert_halo_equal(pvh, plh)


@pytest.mark.parametrize("split", [(8, 8), (7, 9), (9, 7)])
def test_cap_rounding_boundaries(split):
    # 16 cells split right at / around the q=8 rounding quantum
    mesh = amr.uniform_mesh(2, 2, 4)   # 16 cells
    nbr = amr.face_neighbors(mesh)
    coeff = amr.stencil_coeffs(mesh, nbr, amr.stable_dt(mesh))
    slot = np.arange(mesh.n, dtype=np.int64)
    a, _ = split
    part = np.zeros((mesh.n,), np.int32)
    part[a:] = 1
    pv, pl = _build_pair(slot, part, nbr, coeff, None, 2)
    assert_halo_equal(pv, pl)


def test_with_metrics_false_identical_otherwise():
    mesh, nbr, coeff = _mesh(3, 1)
    hier = _Hier(2, 4)
    slot = _slots(mesh.n, 3, contiguous=False)
    part = _partition(mesh, 8, 3, sfc=True)
    full = halo.build_halo_plan(slot, part, nbr, coeff, hierarchy=hier)
    lean = halo.build_halo_plan(
        slot, part, nbr, coeff, hierarchy=hier, with_metrics=False
    )
    # quality report absent, everything else identical
    assert "MaxEdgeCut" in full.metrics and "MaxEdgeCut" not in lean.metrics
    for f in (
        "owned_idx", "owned_slot", "nbr_local", "nbr_valid", "coeff",
        "ghost_fetch", "finer_rows",
    ):
        assert np.array_equal(getattr(full, f), getattr(lean, f)), f
    assert full.stage_meta == lean.stage_meta
    for sa, sb in zip(full.stages, lean.stages):
        assert np.array_equal(sa.idx, sb.idx)
    # the cheap halo metrics stay, and the skipped report is recoverable
    for k in ("MaxSurfaceIndex", "InterNodeGhosts", "InterNodeBytesPerExchange"):
        assert lean.metrics[k] == full.metrics[k]
    rec = halo.plan_quality_metrics(part, nbr, 8)
    assert rec["MaxEdgeCut"] == full.metrics["MaxEdgeCut"]
    # the legacy builder honors the same flag
    lean_l = build_halo_plan_legacy(
        slot, part, nbr, coeff, hierarchy=hier, with_metrics=False
    )
    assert_halo_equal(lean, lean_l)


def test_plan_build_seconds_recorded():
    mesh, nbr, coeff = _mesh(4, 0)
    slot = np.arange(mesh.n, dtype=np.int64)
    part = _partition(mesh, 4, 4, sfc=True)
    pv = halo.build_halo_plan(slot, part, nbr, coeff, num_parts=4)
    assert pv.metrics["PlanBuildSeconds"] > 0
    part2 = _partition(mesh, 4, 5, sfc=True)
    pv2 = halo.build_halo_plan(slot, part2, nbr, coeff, num_parts=4)
    mv = halo.build_move_plan(pv, pv2)
    assert mv.metrics["PlanBuildSeconds"] > 0
    # the "none" early return records it too
    assert halo.build_move_plan(pv, pv).metrics["PlanBuildSeconds"] > 0


# ---------------------------------------------------------------------------
# PlanCache: cached/patched builds vs fresh vectorized builds
# ---------------------------------------------------------------------------


def _amr_step(mesh, slot, next_id, rng):
    """One refine/coarsen step, tracking slot identity across it the way
    the simulation driver does: kept cells inherit their slot through the
    transfer map, born cells get fresh ids."""
    ref, coar = amr.adapt_masks(mesh, rng.random(2))
    mesh2, tr = amr.refine_coarsen(mesh, ref, coar)
    slot2 = np.empty(mesh2.n, np.int64)
    kept = ~tr.born
    slot2[kept] = slot[tr.src[kept, 0]]
    nb = int(tr.born.sum())
    slot2[tr.born] = next_id + np.arange(nb)
    nbr2 = amr.face_neighbors(mesh2)
    coeff2 = amr.stencil_coeffs(mesh2, nbr2, amr.stable_dt(mesh2))
    return mesh2, slot2, nbr2, coeff2, next_id + nb


@settings(max_examples=6, deadline=None)
@given(
    seed=st.integers(0, 5),
    nodes=st.sampled_from([1, 2]),
    dev=st.sampled_from([2, 4]),
)
def test_cached_event_sequence_bit_identical(seed, nodes, dev):
    """Randomized reslice / AMR / rebuild interleavings: every event's
    cached (patched) plan must be field-by-field identical to a fresh
    vectorized build, for both halo and move plans."""
    schedule = [
        "init", "reslice", "reslice", "amr", "reslice", "rebuild", "reslice",
    ]
    rng = np.random.default_rng(seed + 9000)
    mesh, nbr, coeff = _mesh(seed, 1)
    S = nodes * dev
    hier = _Hier(nodes, dev) if nodes > 1 else None
    kw = dict(hierarchy=hier) if hier is not None else dict(num_parts=S)
    mkw = dict(hierarchy=hier) if hier is not None else {}
    slot = _slots(mesh.n, seed, contiguous=False)
    next_id = int(slot.max()) + 1
    part = _partition(mesh, S, seed, sfc=True)
    cache = halo.PlanCache()
    token = 0
    prev_f = prev_c = None
    for op in schedule:
        if op == "reslice":
            part = part.copy()
            sw = rng.random(mesh.n) < 0.08
            part[sw] = rng.integers(0, S, int(sw.sum()))
        elif op == "rebuild":
            part = _partition(mesh, S, int(rng.integers(1 << 30)), sfc=True)
        elif op == "amr":
            mesh, slot, nbr, coeff, next_id = _amr_step(mesh, slot, next_id, rng)
            part = _partition(mesh, S, int(rng.integers(1 << 30)), sfc=True)
            token += 1  # cells were inserted/deleted
        fresh = halo.build_halo_plan(slot, part, nbr, coeff, **kw)
        cached = halo.build_halo_plan(
            slot, part, nbr, coeff, **kw, cache=cache, topo_token=token
        )
        assert_halo_equal(fresh, cached, ignore=_CACHE_METRICS)
        # move plans are only defined within one topology: across an AMR
        # event the driver moves state through the transfer map instead
        if prev_f is not None and op != "amr":
            for full in (False, True):
                assert_move_equal(
                    halo.build_move_plan(prev_f, fresh, full=full, **mkw),
                    halo.build_move_plan(
                        prev_c, cached, full=full, cache=cache, **mkw
                    ),
                )
        prev_f, prev_c = fresh, cached
    assert cache.stats.halo_hits + cache.stats.halo_misses == len(schedule)
    assert cache.stats.halo_hits >= 1          # small reslices take the patch path
    assert cache.stats.topo_refreshes >= 2     # init + each AMR step


def test_cache_pure_hit_and_reset():
    mesh, nbr, coeff = _mesh(0, 1)
    slot = _slots(mesh.n, 0, contiguous=False)
    part = _partition(mesh, 4, 0, sfc=True)
    cache = halo.PlanCache()
    kw = dict(num_parts=4, cache=cache, topo_token=0)
    p1 = halo.build_halo_plan(slot, part, nbr, coeff, **kw)
    assert (cache.stats.halo_misses, cache.stats.halo_hits) == (1, 0)
    # identical partition again: pure hit, nothing patched
    p2 = halo.build_halo_plan(slot, part, nbr, coeff, **kw)
    assert (cache.stats.halo_misses, cache.stats.halo_hits) == (1, 1)
    assert p2.metrics["PatchedRows"] == 0
    assert_halo_equal(p1, p2, ignore=_CACHE_METRICS)
    # reset drops both tiers: the next build is a miss again
    cache.reset()
    p3 = halo.build_halo_plan(slot, part, nbr, coeff, **kw)
    assert cache.stats.halo_misses == 2
    assert_halo_equal(p1, p3, ignore=_CACHE_METRICS)


def test_cache_topo_token_bump_refreshes_topology():
    mesh, nbr, coeff = _mesh(1, 1)
    slot = _slots(mesh.n, 1, contiguous=False)
    part = _partition(mesh, 4, 1, sfc=True)
    cache = halo.PlanCache()
    halo.build_halo_plan(
        slot, part, nbr, coeff, num_parts=4, cache=cache, topo_token=0
    )
    r0 = cache.stats.topo_refreshes
    # same arrays, bumped token: the topology tier must be rebuilt even
    # though nothing actually changed (the token is the authority)
    p = halo.build_halo_plan(
        slot, part, nbr, coeff, num_parts=4, cache=cache, topo_token=1
    )
    assert cache.stats.topo_refreshes == r0 + 1
    fresh = halo.build_halo_plan(slot, part, nbr, coeff, num_parts=4)
    assert_halo_equal(fresh, p, ignore=_CACHE_METRICS)


def test_cache_large_move_fraction_falls_back_to_scratch():
    mesh, nbr, coeff = _mesh(2, 1)
    slot = _slots(mesh.n, 2, contiguous=False)
    cache = halo.PlanCache(max_patch_frac=0.25)
    part = _partition(mesh, 4, 2, sfc=True)
    kw = dict(num_parts=4, cache=cache, topo_token=0)
    halo.build_halo_plan(slot, part, nbr, coeff, **kw)
    # rotate every cell's owner: 100% moved > 25% threshold
    part2 = ((part.astype(np.int64) + 1) % 4).astype(np.int32)
    p = halo.build_halo_plan(slot, part2, nbr, coeff, **kw)
    assert cache.stats.halo_misses == 2 and cache.stats.halo_hits == 0
    fresh = halo.build_halo_plan(slot, part2, nbr, coeff, num_parts=4)
    assert_halo_equal(fresh, p, ignore=_CACHE_METRICS)
    # ...and the scratch fallback still primes the cache for patching
    part3 = part2.copy()
    part3[:8] = (part3[:8] + 1) % 4
    p3 = halo.build_halo_plan(slot, part3, nbr, coeff, **kw)
    assert cache.stats.halo_hits == 1
    assert_halo_equal(
        halo.build_halo_plan(slot, part3, nbr, coeff, num_parts=4),
        p3, ignore=_CACHE_METRICS,
    )


def test_cache_shape_change_is_a_miss_but_equal():
    mesh, nbr, coeff = _mesh(3, 1)
    slot = _slots(mesh.n, 3, contiguous=False)
    part8 = _partition(mesh, 8, 3, sfc=True)
    cache = halo.PlanCache()
    halo.build_halo_plan(
        slot, part8, nbr, coeff, hierarchy=_Hier(2, 4), cache=cache, topo_token=0
    )
    # same cells, different hierarchy shape: partition tier can't patch
    p = halo.build_halo_plan(
        slot, part8, nbr, coeff, hierarchy=_Hier(4, 2), cache=cache, topo_token=0
    )
    assert cache.stats.halo_misses == 2
    fresh = halo.build_halo_plan(slot, part8, nbr, coeff, hierarchy=_Hier(4, 2))
    assert_halo_equal(fresh, p, ignore=_CACHE_METRICS)


def test_cache_cap_quantum_crossing_patched():
    # engineer a reslice that drags the max part population across the
    # cap rounding quantum in both directions; the patch must re-pad
    mesh = amr.uniform_mesh(2, 4, 6)   # 256 cells
    nbr = amr.face_neighbors(mesh)
    coeff = amr.stencil_coeffs(mesh, nbr, amr.stable_dt(mesh))
    slot = np.arange(mesh.n, dtype=np.int64)
    n = mesh.n
    cache = halo.PlanCache()
    kw = dict(num_parts=2, cache=cache, topo_token=0)
    for hi in (n // 2, n // 2 + 9, n // 2 - 7):   # 128 -> 137 -> 121 owned
        part = np.zeros((n,), np.int32)
        part[hi:] = 1
        p = halo.build_halo_plan(slot, part, nbr, coeff, **kw)
        fresh = halo.build_halo_plan(slot, part, nbr, coeff, num_parts=2)
        assert_halo_equal(fresh, p, ignore=_CACHE_METRICS)
        assert p.cap == fresh.cap
    assert cache.stats.halo_hits == 2   # both crossings took the patch path


def test_move_prologue_requires_cache_lineage():
    # a move between plans the cache has never seen must fall back to the
    # generic derivation (and still be correct)
    mesh, nbr, coeff = _mesh(4, 1)
    slot = _slots(mesh.n, 4, contiguous=False)
    part = _partition(mesh, 4, 4, sfc=True)
    part2 = part.copy()
    part2[:16] = (part2[:16] + 1) % 4
    old = halo.build_halo_plan(slot, part, nbr, coeff, num_parts=4)
    new = halo.build_halo_plan(slot, part2, nbr, coeff, num_parts=4)
    cache = halo.PlanCache()   # empty: no lineage for either plan
    mv = halo.build_move_plan(old, new, cache=cache)
    assert cache.stats.move_misses == 1 and cache.stats.move_hits == 0
    assert_move_equal(halo.build_move_plan(old, new), mv)
