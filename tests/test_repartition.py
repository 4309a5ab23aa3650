"""Incremental repartitioning engine (repro.core.repartition)."""
import jax.numpy as jnp
import numpy as np
import pytest

import _delta_reference as ref
from repro.core import dynamic
from repro.core.repartition import Repartitioner, _live_loads_kernel


def _mk(rng, n=1024, parts=8, **kw):
    pts = jnp.asarray(rng.random((n, 3)), jnp.float32)
    w = jnp.asarray(1.0 + rng.random(n), jnp.float32)
    kw.setdefault("max_depth", 8)
    return pts, w, Repartitioner(pts, w, parts, **kw)


def _active_parts(rp):
    part = np.asarray(rp.part)
    act = np.asarray(rp.dps.active)
    return part, act


# --- cached-key reuse ---------------------------------------------------------

def test_incremental_matches_cold_rebuild(rng):
    """A weight-only incremental re-slice must produce exactly the parts a
    cold engine built from the same (points, weights) produces — cached
    keys change nothing about the result, only about the cost."""
    n = 1024
    pts = jnp.asarray(rng.random((n, 3)), jnp.float32)
    w0 = jnp.ones((n,), jnp.float32)
    w1 = jnp.asarray(1.0 + 3.0 * rng.random(n), jnp.float32)

    warm = Repartitioner(pts, w0, 8, max_depth=8)
    keygen_before = warm.stats.keygen_points
    warm.update_weights(w1)
    step = warm.rebalance()
    assert step.reused_keys and warm.stats.keygen_points == keygen_before

    cold = Repartitioner(pts, w1, 8, max_depth=8)
    np.testing.assert_array_equal(np.asarray(step.part), np.asarray(cold.part))


def test_weight_update_never_regenerates_keys(rng):
    _, _, rp = _mk(rng)
    before = rp.stats.keygen_points
    for i in range(5):
        rp.update_weights(jnp.asarray(1.0 + np.random.default_rng(i).random(1024), jnp.float32))
        rp.rebalance()
    assert rp.stats.keygen_points == before
    assert rp.stats.incremental_steps == 5


def test_insert_only_keygens_the_delta(rng):
    _, _, rp = _mk(rng)
    before = rp.stats.keygen_points
    rp.insert(jnp.asarray(rng.random((64, 3)), jnp.float32), jnp.ones(64, jnp.float32))
    assert rp.stats.keygen_points == before + 64  # delta batch only
    part, act = _active_parts(rp)
    assert rp.num_active() == 1024 + 64


def test_topology_version_tracks_point_population_only(rng):
    """`topology_version` is the plan caches' invalidation key: it must
    bump on insert/delete (the tracked population changed) and stay put
    across re-slices and rebuilds (same cells, new owners)."""
    _, _, rp = _mk(rng)
    assert rp.topology_version == 0
    rp.update_weights(jnp.asarray(1.0 + rng.random(1024), jnp.float32))
    rp.rebalance()
    assert rp.topology_version == 0          # re-slice: same population
    rp.rebuild()
    assert rp.topology_version == 0          # rebuild: same population
    slots = rp.insert(jnp.asarray(rng.random((16, 3)), jnp.float32),
                      jnp.ones(16, jnp.float32))
    assert rp.topology_version == 1
    rp.delete(slots[:4])
    assert rp.topology_version == 2


# --- amortized controller (Alg. 3) -------------------------------------------

def test_controller_triggers_rebuild_exactly_on_credit_exhaustion(rng):
    """Drive `step` with a scripted timeop sequence: the rebuild must fire
    on exactly the step where spent excess exceeds banked credits."""
    _, _, rp = _mk(rng, rebuild_cost=10.0)
    nb = int(dynamic.num_buckets(rp.dps))
    rp.controller.balanced(lb_cost=9.0, num_buckets=nb, timeop=1.0)
    # base cost = nb; timeop 1 + 2/nb costs nb+2 -> excess 2.0/step; credits 9
    kinds = [rp.step(timeop=1.0 + 2.0 / nb).kind for _ in range(5)]
    # delta after k steps: 2k; fires when 2k > 9 -> k=5 (and not before:
    # the credit boundary sits between integers, so float jitter is safe)
    assert kinds == ["incremental"] * 4 + ["rebuild"], kinds


def test_rebuild_rebanks_credits(rng):
    _, _, rp = _mk(rng, rebuild_cost=4.5)
    nb = int(dynamic.num_buckets(rp.dps))
    rp.controller.balanced(lb_cost=4.5, num_buckets=nb, timeop=1.0)
    # excess 1/step, credits 4.5 (a non-integer boundary, safe under float
    # jitter): first rebuild on the 5th step...
    fired = [rp.step(timeop=1.0 + 1.0 / nb).kind for _ in range(5)]
    assert fired == ["incremental"] * 4 + ["rebuild"], fired
    # ...and the cycle repeats after the rebuild re-banks credits
    nb2 = int(dynamic.num_buckets(rp.dps))
    base2 = rp.controller.base_timeop
    fired2 = [rp.step(timeop=base2 + 1.0 / nb2).kind for _ in range(5)]
    assert "rebuild" in fired2, fired2
    assert rp.stats.rebuilds >= 3  # constructor build + two credit exhaustions


def test_step_default_timeop_uses_live_imbalance(rng):
    """Without a measured timeop, sustained weight drift alone must
    eventually exhaust credits and trigger a rebuild."""
    n = 1024
    pts = jnp.asarray(rng.random((n, 3)), jnp.float32)
    rp = Repartitioner(pts, jnp.ones((n,), jnp.float32), 8, max_depth=8,
                       rebuild_cost=2.0)
    kinds = []
    for t in range(12):
        hot = np.zeros(n, np.float32)
        hot[: n // 4] = 40.0 * (t + 1)  # one region heats up without bound
        rp.update_weights(jnp.asarray(1.0 + hot))
        kinds.append(rp.step().kind)
    assert "rebuild" in kinds


# --- imbalance fallback on the device -----------------------------------------

def _host_loads(rp) -> np.ndarray:
    """The fallback's former host formula, kept as a float64 oracle: the
    current assignment's loads under the live weights, slot by slot."""
    part = np.asarray(rp.part)
    w = np.asarray(rp.dps.weights, np.float64) * np.asarray(rp.dps.active)
    loads = np.zeros(rp.num_parts)
    np.add.at(loads, np.maximum(part, 0), np.where(part >= 0, w, 0.0))
    return loads


def _host_imbalance(rp) -> float:
    loads = _host_loads(rp)
    return float(loads.max() / max(loads.mean(), 1e-12))


def _fallback_case(case: str, rng):
    from repro.core import partitioner as pt
    from repro.core.repartition import HierarchicalRepartitioner

    n = 2048
    pts = jnp.asarray(rng.random((n, 3)), jnp.float32)
    w = jnp.asarray(0.5 + rng.random(n), jnp.float32)
    drift = jnp.asarray(1.0 + 3.0 * (rng.random(n) < 0.2), jnp.float32)
    tree = pt.PartitionerConfig(use_tree=True)
    if case == "hierarchical":
        rp = HierarchicalRepartitioner(pts, w, pt.HierarchyPlan(2, 4), max_depth=8)
    else:
        rp = Repartitioner(pts, w, 8, tree if case.startswith("tree") else pt.PartitionerConfig(),
                           max_depth=8, capacity=n if case == "tree_full_churn" else None)
    rp.update_weights(w * drift)

    def insert(k):
        rp.insert(jnp.asarray(rng.random((k, 3)), jnp.float32),
                  jnp.asarray(2.0 + rng.random(k), jnp.float32))

    def delete(k):
        rp.delete(jnp.asarray(rng.choice(n, k, replace=False).astype(np.int32)))

    if case in ("key", "hierarchical"):
        insert(400)   # into free slots: part -1, left out
    if case in ("tree_delete", "key", "hierarchical"):
        delete(300)   # inactive, their part still >= 0: charged 0
    if case == "tree_full_churn":
        # a full store refills the slots just freed: their stale part is
        # charged the new point's weight
        delete(128)
        insert(128)
    return rp


@pytest.mark.parametrize(
    "case", ["tree_weights", "tree_delete", "tree_full_churn", "key", "hierarchical"])
def test_step_fallback_matches_host_formula(case):
    """``step()`` without a timeop reads the loads of the current
    assignment under the new weights from one device program: the same
    numbers, slot by slot, as the float64 host formula it replaced."""
    rp = _fallback_case(case, np.random.default_rng(11))
    part, act = _active_parts(rp)
    deleted, fresh = ((part >= 0) & ~act).any(), ((part < 0) & act).any()
    assert (deleted, fresh) == {"tree_weights": (False, False), "tree_delete": (True, False),
                                "tree_full_churn": (False, False)}.get(case, (True, True))
    want = _host_loads(rp)
    loads, nb = _live_loads_kernel(rp.part, rp.dps, rp.num_parts)
    assert loads.dtype == jnp.float32 and loads.shape == (rp.num_parts,)
    np.testing.assert_allclose(np.asarray(loads), want, rtol=1e-5)
    assert int(nb) == int(dynamic.num_buckets(rp.dps))
    seen = len(rp.controller.history)
    rp.step()
    tag, cost, *_ = rp.controller.history[seen]
    assert tag in ("base", "obs")
    np.testing.assert_allclose(cost / int(nb), want.max() / want.mean(), rtol=1e-5)


def test_scripted_drift_takes_the_host_formulas_decisions():
    """A drift run with churn on a full store: the engine that computes
    the fallback on the device takes, step for step, the decisions of a
    twin fed the float64 host formula as its timeop."""
    from repro.core import partitioner as pt

    n, k = 4096, 256
    rng = np.random.default_rng(5)
    pts = rng.random((n, 3)).astype(np.float32)
    w0 = (0.5 + rng.random(n)).astype(np.float32)
    cfg = pt.PartitionerConfig(use_tree=True)
    twins = [Repartitioner(jnp.asarray(pts), jnp.asarray(w0), 8, cfg, capacity=n,
                           max_depth=8, rebuild_cost=40.0) for _ in range(2)]
    kinds = ([], [])
    for t in range(16):
        if t % 4 == 3:
            slots = np.sort(rng.choice(n, k, replace=False)).astype(np.int32)
            pts[slots] = rng.random((k, 3))
            w0[slots] = 0.5 + rng.random(k)
            for rp in twins:
                rp.delete(jnp.asarray(slots))
                rp.insert(jnp.asarray(pts[slots]), jnp.asarray(w0[slots]))
        centre = np.array([0.2 + 0.04 * t, 0.5, 0.5], np.float32)
        hot = w0 * (1.0 + 3.0 * np.exp(-((pts - centre) ** 2).sum(1) / 0.02))
        for rp in twins:
            rp.update_weights(jnp.asarray(hot, jnp.float32))
        kinds[0].append(twins[0].step().kind)
        kinds[1].append(twins[1].step(timeop=_host_imbalance(twins[1])).kind)
    assert kinds[0] == kinds[1]
    assert "rebuild" in kinds[0] and "incremental" in kinds[0], kinds[0]
    device, host = (rp.controller.history for rp in twins)
    assert [h[0] for h in device] == [h[0] for h in host]
    np.testing.assert_allclose([h[1:] for h in device if h[0] != "obs"],
                               [h[1:] for h in host if h[0] != "obs"], rtol=1e-5)
    np.testing.assert_allclose([h[1:] for h in device if h[0] == "obs"],
                               [h[1:] for h in host if h[0] == "obs"], rtol=1e-5, atol=1e-3)


# --- migration plans ----------------------------------------------------------

def test_migration_plans_conserve_elements(rng):
    _, _, rp = _mk(rng)
    w = 1.0 + 5.0 * rng.random(1024).astype(np.float32)
    rp.update_weights(jnp.asarray(w))
    step = rp.rebalance()
    send = step.plan.send_counts
    # every active element is accounted for exactly once in the send matrix
    assert send.sum() == rp.num_active()
    part, act = _active_parts(rp)
    new_loads = np.bincount(part[act], minlength=rp.num_parts)
    np.testing.assert_array_equal(send.sum(axis=0), new_loads)


def test_migration_restricted_to_neighbors_for_small_drift(rng):
    """Curve order is preserved, so a small weight delta moves elements
    only between rank-adjacent parts (paper's locality claim)."""
    from repro.core.migration import neighbor_locality

    _, w, rp = _mk(rng)
    rp.update_weights(w * jnp.asarray(1.0 + 0.05 * rng.random(1024), jnp.float32))
    step = rp.rebalance()
    if step.plan.total_moved:
        assert neighbor_locality(step.plan) == 1.0


def test_guards_reject_silent_corruption(rng):
    """The fixed-shape kernels silently misroute out-of-contract inputs
    (scatter into slot 0 / last slot), so the engine must reject them."""
    import pytest as _pytest

    _, _, rp = _mk(rng)
    with _pytest.raises(ValueError, match="exceeds free capacity"):
        rp.insert(jnp.asarray(rng.random((2000, 3)), jnp.float32),
                  jnp.ones(2000, jnp.float32))
    with _pytest.raises(ValueError, match="matches neither"):
        rp.update_weights(jnp.ones(100, jnp.float32))


def test_double_delete_is_noop(rng):
    _, _, rp = _mk(rng)
    rp.delete(jnp.arange(10))
    rp.delete(jnp.arange(10))           # repeat across calls
    rp.delete(jnp.asarray([20, 20, 20]))  # duplicates within one call
    assert rp.num_active() == 1024 - 11
    # tree counters track storage exactly (no unconditional decrements)
    assert int(rp.dps.tree.count[0]) == rp.num_active()


def test_insert_delete_keep_assignment_total(rng):
    _, _, rp = _mk(rng)
    slots = rp.insert(jnp.asarray(rng.random((100, 3)), jnp.float32),
                      jnp.ones(100, jnp.float32))
    rp.delete(slots[:50])
    rp.rebalance()
    part, act = _active_parts(rp)
    assert (part[act] >= 0).all()
    assert (part[~act] == -1).all()
    assert act.sum() == 1024 + 50
    # tree counters stayed consistent with storage
    assert int(rp.dps.tree.count[0]) == 1024 + 50


# --- full rebuild path --------------------------------------------------------

def test_rebuild_refreshes_frame_and_repairs_buckets():
    rng = np.random.default_rng(7)  # local: the repair bound depends on draws
    _, _, rp = _mk(rng, bucket_size=32)
    # dense burst into one region makes buckets heavy (0.3 wide: resolvable
    # within max_depth=8; narrower clusters legally stay heavy, see
    # dynamic.adjustments)
    burst = jnp.asarray(0.4 + 0.3 * rng.random((600, 3)), jnp.float32)
    rp.insert(burst, jnp.ones(600, jnp.float32))
    assert int(dynamic.max_bucket_occupancy(rp.dps)) > 2 * 32
    token_before = rp.cache_token
    step = rp.rebuild()
    assert step.kind == "rebuild" and not step.reused_keys
    assert rp.cache_token == token_before + 1  # cached keys invalidated
    assert int(dynamic.max_bucket_occupancy(rp.dps)) <= 2 * 32


# --- tree-backed mode (bucket-statistics substrate) ---------------------------

def _mk_tree(rng, n=1024, parts=8, **kw):
    from repro.core import partitioner as pt

    pts = jnp.asarray(rng.random((n, 3)), jnp.float32)
    w = jnp.asarray(1.0 + rng.random(n), jnp.float32)
    kw.setdefault("max_depth", 8)
    cfg = pt.PartitionerConfig(use_tree=True)
    return pts, w, Repartitioner(pts, w, parts, cfg, **kw)


def test_tree_mode_never_keygens_points(rng):
    """The bucket substrate generates keys for O(B) bucket centroids
    only — across build, weight drift, insert, delete and rebuild, zero
    storage slots go through point key generation."""
    _, _, rp = _mk_tree(rng)
    assert rp.stats.keygen_points == 0 and rp.stats.keygen_buckets > 0
    rp.update_weights(jnp.asarray(1.0 + rng.random(1024), jnp.float32))
    rp.rebalance()
    slots = rp.insert(jnp.asarray(rng.random((64, 3)), jnp.float32),
                      jnp.ones(64, jnp.float32))
    rp.delete(slots[:16])
    rp.rebuild()
    assert rp.stats.keygen_points == 0
    assert rp.stats.summary_refreshes == 64 + 16  # dirtied deltas only


def test_tree_mode_points_follow_their_bucket(rng):
    _, w, rp = _mk_tree(rng)
    rp.update_weights(w * jnp.asarray(1.0 + 2.0 * rng.random(1024), jnp.float32))
    step = rp.rebalance()
    part = np.asarray(step.part)
    act = np.asarray(rp.dps.active)
    leaf = np.asarray(rp.dps.leaf_id)
    assert (part[act] >= 0).all() and (part[~act] == -1).all()
    for l in np.unique(leaf[act]):
        assert len(np.unique(part[act & (leaf == l)])) == 1
    # loads equal exact point-weight sums per part
    oracle = np.zeros(rp.num_parts)
    np.add.at(oracle, part[act], np.asarray(rp.dps.weights)[act])
    np.testing.assert_allclose(step.loads, oracle, rtol=1e-4)


def test_tree_mode_summary_tracks_deltas(rng):
    _, _, rp = _mk_tree(rng)
    s0 = rp.summary()
    assert int(np.asarray(s0.count).sum()) == 1024
    new = jnp.asarray(rng.random((50, 3)), jnp.float32)
    slots = rp.insert(new, jnp.full((50,), 2.0, jnp.float32))
    s1 = rp.summary()
    assert int(np.asarray(s1.count).sum()) == 1074
    np.testing.assert_allclose(
        float(np.asarray(s1.weight).sum()),
        float(np.asarray(s0.weight).sum()) + 100.0, rtol=1e-5,
    )
    rp.delete(slots)
    rp.delete(slots)  # double delete is a no-op in the summary too
    s2 = rp.summary()
    assert int(np.asarray(s2.count).sum()) == 1024
    np.testing.assert_allclose(
        float(np.asarray(s2.weight).sum()),
        float(np.asarray(s0.weight).sum()), rtol=1e-5,
    )
    # summaries agree with the tree's own counters at the leaves
    np.testing.assert_array_equal(
        np.asarray(s2.count).sum(), int(rp.dps.tree.count[0])
    )


def test_tree_mode_matches_cold_tree_engine(rng):
    """Weight-only drift: the incremental bucket re-slice must equal a
    cold tree-mode engine built from the same state (same tree, same
    bucket order => identical knapsack input)."""
    from repro.core import partitioner as pt

    n = 1024
    pts = jnp.asarray(rng.random((n, 3)), jnp.float32)
    w1 = jnp.asarray(1.0 + 3.0 * rng.random(n), jnp.float32)
    cfg = pt.PartitionerConfig(use_tree=True)
    warm = Repartitioner(pts, jnp.ones((n,), jnp.float32), 8, cfg, max_depth=8)
    warm.update_weights(w1)
    step = warm.rebalance()
    cold = Repartitioner(pts, w1, 8, cfg, max_depth=8)
    np.testing.assert_array_equal(np.asarray(step.part), np.asarray(cold.part))


def test_tree_mode_curve_index_serves_queries(rng):
    from repro.core import queries

    _, _, rp = _mk_tree(rng)
    slots = rp.insert(jnp.asarray(rng.random((32, 3)), jnp.float32),
                      jnp.ones(32, jnp.float32))
    rp.delete(slots[:8])
    v0 = rp.index_version
    idx = rp.curve_index()
    assert idx.tree is not None and int(idx.version) == v0
    assert rp.curve_index() is idx  # memoized per version
    act = np.asarray(rp.dps.active)
    live = np.flatnonzero(act)[:200]
    q = jnp.asarray(np.asarray(rp.dps.points)[live])
    found, ids, ok = queries.point_location(idx, q, bucket_cap=256)
    assert bool(np.asarray(found).all())
    # deleted slots are not found
    dq = jnp.asarray(np.asarray(rp.dps.points)[np.asarray(slots[:8])])
    f2, _, _ = queries.point_location(idx, dq, bucket_cap=2048)
    assert not bool(np.asarray(f2).any())
    # controller still drives incremental-vs-rebuild
    kind = rp.step().kind
    assert kind in ("incremental", "rebuild")


def test_pallas_key_cache_token_roundtrip(rng):
    """kernels.ops key cache: same token hits, bumped token misses."""
    from repro.kernels import ops

    pts = jnp.asarray(rng.random((256, 3)), jnp.float32)
    ops.invalidate_key_cache()
    k1 = ops.cached_sfc_key(pts, token=0, curve="morton")
    k2 = ops.cached_sfc_key(pts, token=0, curve="morton")
    assert k1 is k2  # cache hit returns the same buffer
    k3 = ops.cached_sfc_key(pts, token=1, curve="morton")
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(k3))
    assert ops.invalidate_key_cache(0) == 1  # token-scoped invalidation
    assert ops.key_cache_stats()["entries"] == 1
    ops.invalidate_key_cache()


# --- insert/delete as one compiled program ------------------------------------

CHURN_N = 131072


def _big_tree_engine(seed, n, capacity):
    from repro.core import partitioner as pt

    r = np.random.default_rng(seed)
    pts = jnp.asarray(r.random((n, 3)), jnp.float32)
    w = jnp.asarray(0.5 + r.random(n), jnp.float32)
    cfg = pt.PartitionerConfig(use_tree=True)
    return r, Repartitioner(pts, w, 8, cfg, capacity=capacity, max_depth=10)


def _assert_summary(got, want):
    for name in ("count", "centroid", "bbox_lo", "bbox_hi", "is_bucket"):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(want, name)), err_msg=name)
    np.testing.assert_allclose(np.asarray(got.weight), np.asarray(want.weight),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("k", [1, 7, 3000, 65536])
def test_tree_mode_delta_matches_per_level_reference(k):
    """Engine insert, then a delete of inserted, live, inactive and repeated
    slots, each against the reference: store, tree counts and the bucket
    summaries' count/centroid/bbox exactly, the refresh count as before."""
    r, rp = _big_tree_engine(k, 81920, 163840)
    pts = jnp.asarray(r.random((k, 3)), jnp.float32)
    w = jnp.asarray(0.5 + r.random(k), jnp.float32)
    before, s0 = rp.dps, rp.summary()
    refreshes = rp.stats.summary_refreshes
    want, free, lid = ref.insert(before, pts, w)
    got = rp.insert(pts, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(free))
    for name in ("points", "weights", "active", "leaf_id"):
        np.testing.assert_array_equal(np.asarray(getattr(rp.dps, name)),
                                      np.asarray(getattr(want, name)), err_msg=name)
    np.testing.assert_array_equal(np.asarray(rp.dps.tree.count), np.asarray(want.tree.count))
    _assert_summary(rp.summary(), ref.summary_delta(s0, want.tree.is_leaf, pts, w, lid, +1))
    assert rp.stats.summary_refreshes == refreshes + k

    ids = np.concatenate([np.asarray(got)[: (k + 1) // 2],
                          r.integers(0, 163840, k).astype(np.int32)])
    ids[-1] = ids[0]
    ids = jnp.asarray(ids)
    before, s1 = rp.dps, rp.summary()
    want, removed = ref.delete(before, ids)
    rp.delete(ids)
    for name in ("active", "leaf_id", "points"):
        np.testing.assert_array_equal(np.asarray(getattr(rp.dps, name)),
                                      np.asarray(getattr(want, name)), err_msg=name)
    np.testing.assert_array_equal(np.asarray(rp.dps.tree.count), np.asarray(want.tree.count))
    _assert_summary(rp.summary(), ref.summary_delta(
        s1, want.tree.is_leaf, before.points[ids], jnp.where(removed, before.weights[ids], 0.0),
        before.leaf_id[ids], -1, counts=removed.astype(jnp.int32)))
    assert rp.stats.summary_refreshes == refreshes + k + int(removed.sum())


def test_padding_rows_touch_no_slot_or_node():
    """A 3,000-row delete and insert run 3,072 rows: the 72 padding rows
    leave the last slot (live at the delete, free at the insert) and every
    node as the unpadded batch leaves them."""
    from repro.core.dynamic import padded_rows

    r, rp = _big_tree_engine(5, 4096, 8192)
    k = 3000
    assert padded_rows(k) == 3072
    live = jnp.asarray(r.choice(4096, k, replace=False).astype(np.int32))
    want = dynamic.delete(rp.dps, live)
    rp.delete(live)
    assert rp.stats.delta_pad_rows == 72
    for name in ("active", "points", "weights", "leaf_id"):
        np.testing.assert_array_equal(np.asarray(getattr(rp.dps, name)),
                                      np.asarray(getattr(want, name)), err_msg=name)
    for name in ("count", "weight"):
        np.testing.assert_array_equal(np.asarray(getattr(rp.dps.tree, name)),
                                      np.asarray(getattr(want.tree, name)), err_msg=name)

    # 4096 - 3000 live, so 7096 free slots: the padding rows would take
    # free slots up to 4096 + 3000 + 72 if they were not masked
    pts = jnp.asarray(r.random((k, 3)), jnp.float32)
    w = jnp.asarray(0.5 + r.random(k), jnp.float32)
    want = dynamic.insert(rp.dps, pts, w)
    rp.insert(pts, w)
    assert rp.stats.delta_pad_rows == 144
    assert int(rp.dps.active.sum()) == 4096
    assert not bool(rp.dps.active[-1]) and float(rp.dps.weights[-1]) == 0.0
    for name in ("active", "points", "weights", "leaf_id"):
        np.testing.assert_array_equal(np.asarray(getattr(rp.dps, name)),
                                      np.asarray(getattr(want, name)), err_msg=name)
    for name in ("count", "weight"):
        np.testing.assert_array_equal(np.asarray(getattr(rp.dps.tree, name)),
                                      np.asarray(getattr(want.tree, name)), err_msg=name)


def test_repeated_churn_compiles_one_size():
    """Eight 65,536-row churns of a full store: one padded size, no
    padding, two programs a churn, and the store stays consistent."""
    r, rp = _big_tree_engine(6, CHURN_N, CHURN_N)
    sizes = set(rp.stats.delta_sizes)
    for _ in range(8):
        slots = jnp.asarray(np.sort(r.choice(CHURN_N, 65536, replace=False)).astype(np.int32))
        rp.delete(slots)
        got = rp.insert(jnp.asarray(r.random((65536, 3)), jnp.float32),
                        jnp.ones(65536, jnp.float32))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(slots))
    assert rp.stats.delta_sizes - sizes == {65536} and len(rp.stats.delta_sizes) == len(sizes) + 1
    assert rp.stats.delta_pad_rows == 0 and rp.stats.delta_programs == 16
    assert int(rp.dps.tree.count[0]) == CHURN_N == int(rp.dps.active.sum())
