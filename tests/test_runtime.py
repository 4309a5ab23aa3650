"""runtime/: heartbeat failure detection, knapsack reslice conservation,
elastic mesh-shape planning, and (in a fake-device subprocess) a live
device-count change served through ElasticServingController with no cold
restart."""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.partitioner import HierarchyPlan, PartitionerConfig
from repro.core.repartition import HierarchicalRepartitioner, Repartitioner
from repro.runtime.elastic import replacement_plan, viable_mesh_shapes
from repro.runtime.fault_tolerance import (
    HeartbeatMonitor,
    reslice_for_stragglers,
    reslice_on_failure,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# HeartbeatMonitor (injected clock — fully deterministic)
# ---------------------------------------------------------------------------

def test_heartbeat_failure_detection():
    mon = HeartbeatMonitor(num_workers=4, timeout=10.0)
    for w in range(4):
        mon.beat(w, now=0.0)
    assert mon.failed(now=5.0) == []
    mon.beat(0, now=20.0)
    mon.beat(1, now=20.0)
    # 2 and 3 last seen at t=0: 25 - 0 > 10
    assert mon.failed(now=25.0) == [2, 3]


def test_heartbeat_stragglers_at_factor_of_median():
    mon = HeartbeatMonitor(num_workers=4, straggler_factor=2.0)
    for step in range(6):
        now = float(step)
        for w in range(4):
            mon.beat(w, now, step_time=0.5 if w == 3 else 0.1)
    assert mon.stragglers() == [3]
    # a single worker can never be a straggler (no population to compare)
    solo = HeartbeatMonitor(num_workers=1)
    solo.beat(0, 0.0, step_time=9.0)
    assert solo.stragglers() == []


# ---------------------------------------------------------------------------
# Reslice plans
# ---------------------------------------------------------------------------

def test_reslice_on_failure_conservation_and_survivors_only(rng):
    units = 256
    old = np.repeat(np.arange(8), units // 8)
    w = rng.random(units).astype(np.float32) + 0.1
    rp = reslice_on_failure(old, w, failed=[2, 5], num_workers=8)
    assert rp.survivors == [0, 1, 3, 4, 6, 7]
    # every unit lands on a survivor, none stranded on the failed ranks
    assert set(np.unique(rp.assignment)) <= set(rp.survivors)
    stay = int((old == rp.assignment).sum())
    assert stay + rp.plan.total_moved == units
    # everything on the failed ranks moved
    assert rp.plan.total_moved >= int(np.isin(old, [2, 5]).sum())


def test_reslice_for_stragglers_proportional(rng):
    w = np.ones(400, np.float32)
    tp = np.array([1.0, 1.0, 4.0, 1.0])
    part = reslice_for_stragglers(w, tp)
    counts = np.bincount(part, minlength=4)
    assert counts.sum() == 400
    # the 4x-throughput worker gets the biggest share, ~4x a slow one
    assert counts[2] == counts.max()
    assert counts[2] > 2.5 * counts[0]


def test_replacement_plan_shrink_conserves_units(rng):
    old = np.repeat(np.arange(8), 4)           # 32 units on 8 parts
    w = np.ones(32, np.float32)
    new, plan = replacement_plan(old, w, new_num_parts=3)
    assert new.max() == 2 and new.min() == 0
    stay = int((old == new).sum())
    assert stay + plan.total_moved == 32       # nothing lost leaving parts 3..7


def test_replacement_plan_empty_old_parts_is_fresh_placement():
    # regression: old_parts.max() used to crash on the empty bootstrap case
    new, plan = replacement_plan(np.array([], np.int64), np.ones(16, np.float32), 4)
    assert new.shape == (16,) and new.max() == 3
    assert plan.total_moved == 0               # nothing existed, nothing moves


def test_viable_mesh_shapes_products_and_preference():
    for n in (1, 6, 8, 12, 16):
        shapes = viable_mesh_shapes(n)
        assert all(a * b == n for a, b in shapes)
        assert len(set(shapes)) == len(shapes)
    assert viable_mesh_shapes(16)[0] == (4, 4)         # square-ish first
    assert set(viable_mesh_shapes(12)[0]) == {3, 4}
    assert viable_mesh_shapes(8, min_model=2)[0][1] >= 2


# ---------------------------------------------------------------------------
# Elastic resize on the repartitioners (single-device: pure re-slice math)
# ---------------------------------------------------------------------------

def _conserved(old, new, moved):
    act = old >= 0
    assert int(((old == new) & act).sum()) + moved == int(act.sum())


def test_flat_resize_conserves_and_bumps_version(rng):
    pts = jnp.asarray(rng.random((2000, 2)), jnp.float32)
    rp = Repartitioner(pts, None, num_parts=8, cfg=PartitionerConfig(curve="morton"))
    v0, old = rp.index_version, np.asarray(rp.part).copy()
    rebuilds0 = rp.stats.rebuilds            # the initial fit counts as one
    step = rp.resize(5)
    new = np.asarray(rp.part)
    assert new.max() == 4 and rp.num_parts == 5
    _conserved(old, new, step.plan.total_moved)
    assert rp.index_version == v0 + 1 and rp.stats.resizes == 1
    assert step.reused_keys and rp.stats.rebuilds == rebuilds0
    # growth after shrink round-trips
    step2 = rp.resize(8)
    _conserved(new, np.asarray(rp.part), step2.plan.total_moved)
    assert np.asarray(rp.part).max() == 7


def test_hierarchical_resize_is_hierarchy_aware(rng):
    import dataclasses

    pts = jnp.asarray(rng.random((3000, 2)), jnp.float32)
    plan = HierarchyPlan(num_nodes=4, devices_per_node=2)
    hrp = HierarchicalRepartitioner(pts, None, plan)
    v0, old = hrp.index_version, np.asarray(hrp.part).copy()
    rebuilds0 = hrp.stats.rebuilds
    step = hrp.resize(dataclasses.replace(plan, num_nodes=3))
    new = np.asarray(hrp.part)
    assert new.max() == 5 and hrp.plan.num_nodes == 3
    _conserved(old, new, step.plan.total_moved)
    assert hrp.index_version == v0 + 1 and hrp.stats.rebuilds == rebuilds0
    # the two-level slice re-ran: fresh node loads for the new node count
    assert step.node_loads.shape == (3,)
    assert step.node_imbalance < 1.5


def test_elastic_reshard_mid_serve_subprocess():
    """Drop two devices under a live serving engine: the controller
    re-slices hierarchy-aware, re-places chunks on the survivors, swaps
    the index version — answers stay bit-equal and the owner never cold
    rebuilds."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8"
        " --xla_backend_optimization_level=0"
    )
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"  # fake host devices; never a chip the parent holds
    code = textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import queries
        from repro.core.partitioner import HierarchyPlan
        from repro.core.repartition import HierarchicalRepartitioner
        from repro.runtime.elastic import ElasticServingController, mesh_from_devices
        from repro.serve.query_engine import DistributedQueryEngine

        rng = np.random.default_rng(11)
        pts = jnp.asarray(rng.random((4096, 2)), jnp.float32)
        plan = HierarchyPlan(num_nodes=4, devices_per_node=2)
        hrp = HierarchicalRepartitioner(pts, None, plan)
        rebuilds0 = hrp.stats.rebuilds      # initial fit only
        idx = hrp.curve_index(32)
        mesh = mesh_from_devices(jax.devices(), (4, 2), ('node', 'device'))
        eng = DistributedQueryEngine(idx, mesh, ('node', 'device'), bucket_cap=32)

        sel = rng.choice(4096, 300, replace=False)
        q = jnp.concatenate([pts[jnp.asarray(sel)],
                             jnp.asarray(rng.random((212, 2)) + 1.5, jnp.float32)])
        ref = queries.point_location(idx, q, bucket_cap=eng._scan_cap)
        r0 = eng.point_location(q)
        for a, b in zip(r0, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        ctl = ElasticServingController(hrp, eng, heartbeat_timeout=10.0)
        for w in range(8):
            ctl.beat(w, now=0.0)
        for w in range(6):
            ctl.beat(w, now=20.0)          # 6 and 7 went silent
        ev = ctl.check(now=25.0)
        assert ev is not None and (ev.n_before, ev.n_after) == (8, 6)
        assert ev.mesh_shape[0] * ev.mesh_shape[1] == 6
        assert ev.rebuilds_during == 0      # live reshard, not a cold restart
        assert eng.stats.reshards == 1 and eng.stats.index_swaps >= 1
        assert ctl.check(now=26.0) is None  # fresh monitor: no double-fire

        r1 = eng.point_location(q)          # same data, smaller mesh
        for a, b in zip(r1, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        ev2 = ctl.apply_device_change(jax.devices())   # grow back to 8
        assert ev2.n_after == 8 and ev2.rebuilds_during == 0
        r2 = eng.point_location(q)
        for a, b in zip(r2, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert hrp.stats.rebuilds == rebuilds0

        # a slow-but-alive worker: throughput-weighted re-cut of the
        # live layout (run-aligned), answers stay bit-equal
        for step in range(6):
            for w in range(8):
                ctl.beat(w, 30.0 + step, step_time=0.6 if w == 7 else 0.1)
        assert ctl.monitor.stragglers() == [7]
        assert ctl.check(now=36.0) is None   # no reshard, just the re-cut
        assert eng.stats.weighted_reslices >= 1
        r3 = eng.point_location(q)
        for a, b in zip(r3, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print('OK elastic', ev.mesh_shape, ev.moved_units)
    """)
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "OK elastic" in out.stdout


def test_straggler_mitigation_recuts_serving_layout():
    """Slow-but-alive workers trigger a throughput-weighted re-cut of
    the engine's chunk layout (no mesh change): the straggler's share of
    rows shrinks, the engine records a weighted reslice, and check()
    returns no ReshardEvent. Deterministic via the injected clock."""
    from repro.runtime.elastic import ElasticServingController
    from repro.serve.query_engine import DistributedQueryEngine

    rng = np.random.default_rng(5)
    pts = jnp.asarray(rng.random((2048, 2)), jnp.float32)
    hrp = HierarchicalRepartitioner(
        pts, None, HierarchyPlan(num_nodes=2, devices_per_node=2)
    )
    idx = hrp.curve_index(32)
    eng = DistributedQueryEngine(idx, None, ("node", "device"), bucket_cap=32)
    ctl = ElasticServingController(
        hrp, eng, devices=list(range(4)),
        heartbeat_timeout=100.0, straggler_factor=2.0,
    )

    # no straggler yet: mitigation is a no-op
    assert ctl.mitigate_stragglers() is None
    assert eng._row_targets is None

    for step in range(6):
        now = float(step)
        for w in range(4):
            ctl.beat(w, now, step_time=0.5 if w == 3 else 0.1)
    assert ctl.monitor.stragglers() == [3]
    assert ctl.check(now=5.0) is None       # alive => no reshard event

    assignment = ctl.mitigate_stragglers()
    counts = np.bincount(assignment, minlength=4)
    assert (np.diff(assignment) >= 0).all()  # contiguous shard runs
    assert counts.sum() == idx.bucket_starts.shape[0] - 1
    assert counts[3] == counts.min() < counts[:3].min()  # straggler holds least
    assert eng._row_targets is not None and eng._row_targets.shape == (3,)
    assert (np.diff(eng._row_targets) >= 0).all()
    # cuts land on directory bucket boundaries
    assert np.isin(eng._row_targets, np.asarray(idx.bucket_starts)).all()
    assert eng.stats.weighted_reslices >= 2   # check() fired one too

    # index swap (new version) drops the stale weighted cuts
    eng.swap(hrp.curve_index(32))
    assert eng._row_targets is None
