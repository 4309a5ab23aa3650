"""Hierarchical (node -> device) partition core.

Local tests cover the nested knapsack and the two-level engine; the
distributed equivalence and the two-level serving path run in a
subprocess with 8 fake host devices (see test_distributed.py for why).
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import knapsack, migration, partitioner
from repro.core.repartition import HierarchicalRepartitioner

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}"
        " --xla_backend_optimization_level=0"
    )
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"  # fake host devices; never a chip the parent holds
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


# ---------------------------------------------------------------------------
# nested knapsack
# ---------------------------------------------------------------------------

def test_two_level_slice_trivial_top_is_bit_identical(rng):
    """nodes=1 must reduce bit-exactly to the flat knapsack — the flat
    path IS the trivial hierarchy, so the reduction cannot be 'close'."""
    w = jnp.asarray((0.1 + rng.random(20_000)).astype(np.float32))
    for parts in (1, 7, 64):
        node, dev, part = knapsack.two_level_slice(w, 1, parts)
        np.testing.assert_array_equal(
            np.asarray(part), np.asarray(knapsack.slice_weighted_curve(w, parts))
        )
        assert int(np.asarray(node).max()) == 0


def test_two_level_slice_nested_balance_bounds(rng):
    """Both levels obey the paper's knapsack guarantee at their own
    granularity: node spread and per-node device spread are each bounded
    by ~2x the max element weight."""
    w_h = (0.1 + rng.random(16_384)).astype(np.float32)
    node, dev, part = knapsack.two_level_slice(jnp.asarray(w_h), 4, 4)
    nh, ph = np.asarray(node), np.asarray(part)
    assert (np.diff(nh) >= 0).all() and (np.diff(ph) >= 0).all()
    np.testing.assert_array_equal(ph, nh * 4 + np.asarray(dev))
    nl = np.zeros(4)
    np.add.at(nl, nh, w_h)
    assert nl.max() - nl.min() <= 2 * w_h.max() + 1e-3
    pl = np.zeros(16)
    np.add.at(pl, ph, w_h)
    for j in range(4):
        d = pl[4 * j : 4 * (j + 1)]
        assert d.max() - d.min() <= 2 * w_h.max() + 1e-3


def test_device_slice_within_frozen_nodes_rebalances_locally(rng):
    """The intra-node level: node assignment frozen, drifted weights —
    devices rebalance within each node and no element changes node."""
    w0 = (0.5 + rng.random(8_192)).astype(np.float32)
    node, _, _ = knapsack.two_level_slice(jnp.asarray(w0), 2, 4)
    w1 = w0 * (1 + 4 * (np.arange(8_192) % 9 == 0)).astype(np.float32)
    dev = knapsack.device_slice_within_nodes(jnp.asarray(w1), node, 2, 4)
    part = np.asarray(node) * 4 + np.asarray(dev)
    pl = np.zeros(8)
    np.add.at(pl, part, w1)
    for j in range(2):
        d = pl[4 * j : 4 * (j + 1)]
        assert d.max() - d.min() <= 2 * w1.max() + 1e-3


# ---------------------------------------------------------------------------
# local hierarchical partition / reslice
# ---------------------------------------------------------------------------

def test_hierarchical_partition_trivial_top_matches_flat_tree_path(rng):
    """Acceptance: a (1, D) hierarchy is bit-identical to the flat
    partition on both substrates — part, boundaries and loads."""
    pts = jnp.asarray(rng.random((4096, 3)), jnp.float32)
    w = jnp.asarray((0.5 + rng.random(4096)).astype(np.float32))
    for cfg in (
        partitioner.PartitionerConfig(use_tree=True, max_depth=8),
        partitioner.PartitionerConfig(),
    ):
        flat = partitioner.partition(pts, w, 8, cfg)
        hier = partitioner.hierarchical_partition(
            pts, w, partitioner.HierarchyPlan(1, 8), cfg
        )
        np.testing.assert_array_equal(np.asarray(flat.part), np.asarray(hier.part))
        np.testing.assert_array_equal(
            np.asarray(flat.boundaries), np.asarray(hier.boundaries)
        )
        np.testing.assert_array_equal(np.asarray(flat.loads), np.asarray(hier.loads))


def test_hierarchical_partition_two_level_invariants(rng):
    pts = jnp.asarray(rng.random((4096, 3)), jnp.float32)
    w_h = (0.5 + rng.random(4096)).astype(np.float32)
    plan = partitioner.HierarchyPlan(2, 4)
    cfg = partitioner.PartitionerConfig(use_tree=True, max_depth=8)
    res = partitioner.hierarchical_partition(pts, jnp.asarray(w_h), plan, cfg)
    part, node = np.asarray(res.part), np.asarray(res.node)
    # the two levels are consistent everywhere
    np.testing.assert_array_equal(node, part // 4)
    np.testing.assert_array_equal(node, plan.node_of_part(part))
    # loads are exact per level and nest
    oracle = np.zeros(8)
    np.add.at(oracle, part, w_h)
    np.testing.assert_allclose(np.asarray(res.loads), oracle, rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(res.loads).reshape(2, 4).sum(1), np.asarray(res.node_loads),
        rtol=1e-4,
    )
    # node balance at bucket granularity
    maxbw = float(np.asarray(res.summary.weight).max())
    nl = np.asarray(res.node_loads)
    assert nl.max() - nl.min() <= 2 * maxbw + 1e-3
    # boundaries cover the curve at both levels
    assert np.asarray(res.node_boundaries)[0] == 0
    assert np.asarray(res.node_boundaries)[-1] == 4096
    # every 4th part boundary IS a node boundary (slices nest)
    np.testing.assert_array_equal(
        np.asarray(res.boundaries)[::4], np.asarray(res.node_boundaries)
    )


def test_hierarchical_reslice_intra_keeps_nodes(rng):
    pts = jnp.asarray(rng.random((2048, 3)), jnp.float32)
    w0 = (0.5 + rng.random(2048)).astype(np.float32)
    plan = partitioner.HierarchyPlan(2, 4)
    cfg = partitioner.PartitionerConfig(use_tree=True, max_depth=8)
    res = partitioner.hierarchical_partition(pts, jnp.asarray(w0), plan, cfg)
    w1 = w0 * (1 + 3 * (np.arange(2048) % 7 == 0)).astype(np.float32)
    r_intra = partitioner.hierarchical_reslice(res, jnp.asarray(w1), level="intra")
    # frozen node level: zero cross-node movement by construction
    np.testing.assert_array_equal(np.asarray(r_intra.node), np.asarray(res.node))
    oracle = np.zeros(8)
    np.add.at(oracle, np.asarray(r_intra.part), w1)
    np.testing.assert_allclose(np.asarray(r_intra.loads), oracle, rtol=1e-4)
    # full reslice on the cached order == fresh partition (midpoint
    # splitters ignore weights, so the tree is identical)
    r_full = partitioner.hierarchical_reslice(res, jnp.asarray(w1), level="full")
    fresh = partitioner.hierarchical_partition(pts, jnp.asarray(w1), plan, cfg)
    np.testing.assert_array_equal(np.asarray(r_full.part), np.asarray(fresh.part))


# ---------------------------------------------------------------------------
# hierarchical incremental engine (two-level Alg. 3 trigger)
# ---------------------------------------------------------------------------

def test_hierarchical_engine_small_drift_fires_intra(rng):
    pts = jnp.asarray(rng.random((4096, 3)), jnp.float32)
    w = (0.5 + rng.random(4096)).astype(np.float32)
    plan = partitioner.HierarchyPlan(2, 4, inter_node_cost=4.0)
    rp = HierarchicalRepartitioner(
        pts, jnp.asarray(w), plan, max_depth=8, capacity=4096
    )
    rp.update_weights(jnp.asarray(w * (1 + 0.05 * rng.random(4096)).astype(np.float32)))
    step = rp.rebalance()
    assert step.level == "intra"
    assert rp.stats.intra_reslices == 1 and rp.stats.inter_reslices == 0
    # an intra step's migration plan has zero inter-node movement and a
    # node-level stay fraction of exactly 1
    assert isinstance(step.plan, migration.HierarchicalMigrationPlan)
    assert step.plan.inter_moved == 0
    assert step.plan.stay_fraction_node == 1.0
    assert step.node_loads.shape == (2,)


def test_hierarchical_engine_node_skew_fires_inter(rng):
    pts = jnp.asarray(rng.random((4096, 3)), jnp.float32)
    w = (0.5 + rng.random(4096)).astype(np.float32)
    plan = partitioner.HierarchyPlan(2, 4)
    rp = HierarchicalRepartitioner(
        pts, jnp.asarray(w), plan, max_depth=8, capacity=4096
    )
    # node-skewed drift: 5x the weight of everything on node 0
    node_pp = np.asarray(rp.node_part)
    w2 = w * np.where(node_pp == 0, 5.0, 1.0).astype(np.float32)
    rp.update_weights(jnp.asarray(w2))
    assert rp.node_imbalance() > rp.node_threshold
    step = rp.rebalance()
    assert step.level == "inter"
    assert rp.stats.inter_reslices == 1
    # the inter-node re-slice actually fixed the node imbalance
    assert step.node_imbalance < 1.05
    assert step.plan.inter_moved > 0
    assert step.plan.stay_fraction_node < 1.0
    # element conservation through the count matrix (stable slots only)
    assert step.plan.send_counts.sum() == 4096


def test_hierarchical_engine_step_and_deltas(rng):
    """step() keeps Alg. 3 semantics; insert/delete ride the bucket
    substrate unchanged."""
    pts = jnp.asarray(rng.random((2048, 3)), jnp.float32)
    w = jnp.asarray((0.5 + rng.random(2048)).astype(np.float32))
    rp = HierarchicalRepartitioner(
        pts, w, partitioner.HierarchyPlan(2, 2), max_depth=8, capacity=2048 + 128
    )
    s = rp.step()
    assert s.kind in ("incremental", "rebuild")
    slots = rp.insert(
        jnp.asarray(rng.random((64, 3)), jnp.float32), jnp.ones(64, jnp.float32)
    )
    rp.delete(slots[:32])
    s2 = rp.rebalance()
    part = np.asarray(s2.part)
    assert (part[np.asarray(rp.dps.active)] >= 0).all()
    assert rp.num_active() == 2048 + 32
    # the engine never generated a per-point key
    assert rp.stats.keygen_points == 0


def test_parse_inter_node_bytes_classifies_replica_groups():
    """The bench gate's measurement: collective traffic split by node
    from replica groups (pure HLO-text parsing, no devices needed)."""
    from repro.launch import dryrun

    hlo = """
  %all-gather.1 = f32[4,16]{1,0} all-gather(f32[1,16]{1,0} %x), channel_id=1, replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
  %all-gather.2 = f32[2,16]{1,0} all-gather(f32[1,16]{1,0} %y), channel_id=2, replica_groups={{0,4},{1,5},{2,6},{3,7}}, dimensions={0}
"""
    out = dryrun.parse_inter_node_bytes(hlo, [g // 4 for g in range(8)])
    # gather 1 (intra-node groups): per-peer 64 B, 4 members x 3 peers
    # x 2 groups; gather 2 (node-pair groups): 8 members x 1 cross peer
    assert out["intra_node_bytes"] == 2 * 4 * 3 * 64
    assert out["inter_node_bytes"] == 8 * 64
    assert out["collectives"] == 2 and out["unparsed"] == 0


# ---------------------------------------------------------------------------
# distributed equivalence + two-level serving (8 fake devices, subprocess)
# ---------------------------------------------------------------------------

def test_distributed_hierarchy_trivial_top_equals_flat_and_two_level_balances():
    """Acceptance: `hierarchical_bucket_partition` on a (1, D) mesh is
    bit-identical to the flat `distributed_bucket_partition` (a true 2-D
    mesh vs a 1-D mesh — different shard_map topologies, same math), and
    on a (2, 4) mesh the two-level path conserves mass, balances at
    bucket granularity, and its cached-tree reslice equals a fresh
    partition on drifted weights."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import partitioner as pt
        from repro.core.repartition import DistributedBucketRepartitioner
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_mesh
        rng = np.random.default_rng(0)
        n, PARTS = 4096, 8
        pts_h = rng.random((n,3)).astype(np.float32)
        pts_h[: n // 2] = 0.45 + 0.1 * pts_h[: n // 2]
        wts_h = (0.1 + rng.random(n)).astype(np.float32)
        cfg = pt.PartitionerConfig(use_tree=True, max_depth=8, bucket_size=16)

        mesh_f = make_mesh((8,), ("data",))
        sh_f = NamedSharding(mesh_f, P("data"))
        part_f, leaf_f, keys_f = pt.distributed_bucket_partition(
            mesh_f, "data", jax.device_put(jnp.asarray(pts_h), sh_f),
            jax.device_put(jnp.asarray(wts_h), sh_f), PARTS, cfg=cfg)

        mesh_18 = shd.make_node_device_mesh(1, 8)
        sh_18 = NamedSharding(mesh_18, P(("node", "device")))
        part_h, leaf_h, keys_h = pt.hierarchical_bucket_partition(
            mesh_18, pt.HierarchyPlan(1, PARTS),
            jax.device_put(jnp.asarray(pts_h), sh_18),
            jax.device_put(jnp.asarray(wts_h), sh_18), cfg=cfg)
        np.testing.assert_array_equal(np.asarray(part_f), np.asarray(part_h))
        np.testing.assert_array_equal(np.asarray(leaf_f), np.asarray(leaf_h))
        np.testing.assert_array_equal(np.asarray(keys_f), np.asarray(keys_h))

        mesh_24 = shd.make_node_device_mesh(2, 4)
        plan = pt.HierarchyPlan(2, 4)
        sh_24 = NamedSharding(mesh_24, P(("node", "device")))
        pts_d = jax.device_put(jnp.asarray(pts_h), sh_24)
        wts_d = jax.device_put(jnp.asarray(wts_h), sh_24)
        eng = DistributedBucketRepartitioner(mesh_24, cfg=cfg, plan=plan)
        part = eng.partition(pts_d, wts_d)
        p = np.asarray(part)
        assert p.shape[0] == n and (p >= 0).all() and (p < PARTS).all()
        loads = np.zeros(PARTS); np.add.at(loads, p, wts_h)
        np.testing.assert_allclose(loads.sum(), wts_h.sum(), rtol=1e-5)
        # node loads balance within the aggregated-bin granularity: a bin
        # merges up to S_d raw records, so the bound scales accordingly
        lid = np.asarray(eng.leaf_id).reshape(8, -1)
        wsh = wts_h.reshape(8, -1)
        maxbw = 0.0
        for s in range(8):
            bw = np.zeros(lid[s].max() + 1); np.add.at(bw, lid[s], wsh[s])
            maxbw = max(maxbw, bw.max())
        nl = loads.reshape(2, 4).sum(1)
        assert nl.max() - nl.min() <= 2 * 4 * maxbw + 1e-3, (nl, maxbw)
        # device level slices the same aggregated bins: within every
        # node, device spread is bounded at bin granularity too
        for j in range(2):
            dl = loads[4 * j : 4 * (j + 1)]
            assert dl.max() - dl.min() <= 2 * 4 * maxbw + 1e-3, (dl, maxbw)
        # regression: summary_bins that does NOT divide the stage-1
        # record count (bin boundary key = ceil, not floor) — the
        # partition must stay a valid conserving assignment
        plan_nb = pt.HierarchyPlan(2, 4, summary_bins=48)
        p_nb = np.asarray(pt.hierarchical_bucket_partition(
            mesh_24, plan_nb, pts_d, wts_d, cfg=cfg)[0])
        assert (p_nb >= 0).all() and (p_nb < PARTS).all()
        loads_nb = np.zeros(PARTS); np.add.at(loads_nb, p_nb, wts_h)
        np.testing.assert_allclose(loads_nb.sum(), wts_h.sum(), rtol=1e-5)
        # reslice on cached trees == fresh partition on drifted weights
        w2_h = wts_h * (1.0 + 2.0 * (np.arange(n) % 5 == 0)).astype(np.float32)
        w2 = jax.device_put(jnp.asarray(w2_h), sh_24)
        p_re = np.asarray(eng.rebalance(w2))
        p_fresh = np.asarray(pt.hierarchical_bucket_partition(
            mesh_24, plan, pts_d, w2, cfg=cfg)[0])
        np.testing.assert_array_equal(p_re, p_fresh)
        assert eng.reslices == 1 and eng.full_partitions == 1
        # level-aware migration accounting from the engine
        mplan = eng.migration_between(p, p_re)
        assert mplan.intra_moved + mplan.inter_moved + np.trace(mplan.send_counts) == n
        # the byte accounting the bench gates on
        m = np.asarray(eng.node_keys).shape[0] // 8
        acct = shd.summary_exchange_bytes(plan, m)
        assert acct["two_level_inter_node_bytes"] < acct["flat_inter_node_bytes"]
        print("OK")
    """)
    assert "OK" in out


def test_two_level_serving_matches_flat_routing():
    """DistributedQueryEngine on a (node, device) mesh: the hierarchical
    key -> node -> device routing answers exactly like flat routing and
    like the local oracle."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import partitioner as pt
        from repro.core.repartition import Repartitioner
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_mesh
        from repro.serve.query_engine import DistributedQueryEngine
        rng = np.random.default_rng(0)
        n, Q = 4096, 512
        pts = jnp.asarray(rng.random((n,3)), jnp.float32)
        wts = jnp.asarray(0.5 + rng.random(n), jnp.float32)
        rp = Repartitioner(pts, wts, 16, pt.PartitionerConfig(curve="morton"),
                           max_depth=10, capacity=n)
        q_hit = pts[jnp.asarray(rng.choice(n, Q, replace=True))]
        q_rand = jnp.asarray(rng.random((Q,3)), jnp.float32)
        eng2 = DistributedQueryEngine(
            rp.curve_index(), shd.make_node_device_mesh(2, 4), ("node", "device"))
        eng1 = DistributedQueryEngine(
            rp.curve_index(), make_mesh((8,), ("data",)), "data")
        eng0 = DistributedQueryEngine(rp.curve_index())
        f2, i2, ok2 = eng2.point_location(q_hit)
        f1, i1, ok1 = eng1.point_location(q_hit)
        f0, i0, ok0 = eng0.point_location(q_hit)
        np.testing.assert_array_equal(np.asarray(f2), np.asarray(f1))
        np.testing.assert_array_equal(np.asarray(i2), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(ok2), np.asarray(ok1))
        np.testing.assert_array_equal(np.asarray(i2), np.asarray(i0))
        assert np.asarray(f2).all()
        d2, g2 = eng2.knn(q_rand, 3)
        d1, g1 = eng1.knn(q_rand, 3)
        np.testing.assert_array_equal(np.asarray(g2), np.asarray(g1))
        np.testing.assert_allclose(np.asarray(d2), np.asarray(d1), rtol=1e-6)
        # live refresh in two-level mode
        rp.rebuild()
        assert eng2.maybe_refresh(rp)
        f3, i3, _ = eng2.point_location(q_hit)
        np.testing.assert_array_equal(np.asarray(i3), np.asarray(i0))
        print("OK")
    """)
    assert "OK" in out
