"""Distributed-path tests: run in a subprocess with 8 fake host devices
(the fake-device flag must be set before jax initializes, so these cannot
run in the main pytest process)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}"
        " --xla_backend_optimization_level=0"  # match conftest: compile-bound
    )
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"  # fake host devices; never a chip the parent holds
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_distributed_partition_sample_sort():
    """Properties of `distributed_partition` through the fixed-capacity
    all_to_all, on *clustered*, non-uniformly weighted input (the regime
    that stresses the ~2x fair-share lane capacity):

      1. element conservation — no silent drops at capacity
      2. weight conservation — the global weight mass survives the exchange
      3. non-decreasing global key order across shards
      4. near-ideal weighted load balance from the knapsack slice
    """
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import partitioner as pt
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ('data',))
        rng = np.random.default_rng(0)
        n = 4096
        # half the mass in a tight cluster: many shards route to few lanes
        pts_h = rng.random((n,3)).astype(np.float32)
        pts_h[: n // 2] = 0.45 + 0.1 * pts_h[: n // 2]
        wts_h = (0.1 + rng.random(n)).astype(np.float32)
        pts = jax.device_put(jnp.asarray(pts_h), NamedSharding(mesh, P('data')))
        wts = jax.device_put(jnp.asarray(wts_h), NamedSharding(mesh, P('data')))
        keys, w, part = pt.distributed_partition(mesh, 'data', pts, wts, num_parts=16)
        keys_h, w_h, part_h = np.asarray(keys), np.asarray(w), np.asarray(part)
        valid = part_h >= 0
        assert valid.sum() == n, (valid.sum(), n)                    # (1)
        np.testing.assert_allclose(                                  # (2)
            w_h[valid].sum(), wts_h.sum(), rtol=1e-5)
        ks = keys_h.reshape(8, -1)
        prev = -1
        for s in range(8):
            kv = ks[s][ks[s] != 0xFFFFFFFF].astype(np.int64)
            assert (np.diff(kv) >= 0).all()                          # (3)
            if kv.size:
                assert kv[0] >= prev
                prev = kv[-1]
        loads = np.zeros(16); np.add.at(loads, part_h[valid], w_h[valid])
        assert loads.max() / loads.mean() < 1.05                     # (4)
        print('OK')
    """)
    assert "OK" in out


def test_distributed_reslice_matches_full_repartition():
    """Weight-only rebalance on cached keys must produce the same slice as
    a full re-partition with the new weights (and the engine must count it
    as a reslice, not a key-gen)."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import partitioner as pt
        from repro.core.repartition import DistributedRepartitioner
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ('data',))
        rng = np.random.default_rng(3)
        n = 2048
        sh = NamedSharding(mesh, P('data'))
        pts = jax.device_put(jnp.asarray(rng.random((n,3)), jnp.float32), sh)
        wts_h = (0.5 + rng.random(n)).astype(np.float32)
        wts = jax.device_put(jnp.asarray(wts_h), sh)
        eng = DistributedRepartitioner(mesh, 'data', num_parts=16)
        keys, w_sorted, part0 = eng.partition(pts, wts)
        # weight-only drift, applied in the cached sorted layout
        w2 = jnp.where(w_sorted >= 0, w_sorted * (1.0 + 2.0 * (np.asarray(keys) % 7 == 0)), 0.0)
        part1 = eng.rebalance(w2)
        valid = np.asarray(w_sorted) >= 0
        p1 = np.asarray(part1)
        assert (p1[valid] >= 0).all() and (p1[~valid] == -1).all()
        # exact oracle: the global curve order is unchanged, so the slice
        # must equal the single-process knapsack over the valid weights
        from repro.core import knapsack
        w2_h = np.asarray(w2)
        expect = np.asarray(knapsack.slice_weighted_curve(jnp.asarray(w2_h[valid]), 16))
        # float32 prefix-sum association differs between the sharded and
        # host scans: tolerate a +-1 part flip on a vanishing fraction of
        # boundary elements, nothing else
        mism = p1[valid] != expect
        assert np.abs(p1[valid] - expect).max() <= 1
        assert mism.mean() < 1e-2, mism.mean()
        # conservation + balance of the resliced assignment
        loads = np.zeros(16); np.add.at(loads, p1[valid], w2_h[valid])
        assert abs(loads.sum() - w2_h[valid].sum()) < 1e-3 * max(loads.sum(), 1)
        assert loads.max() / loads.mean() < 1.1
        assert eng.reslices == 1 and eng.full_partitions == 1
        print('OK')
    """)
    assert "OK" in out


def test_distributed_bucket_summary_matches_sample_sort():
    """The bucket-summary exchange path vs the sample-sort path on the
    same clustered, non-uniformly weighted input:

      1. every element is assigned a valid part in the ORIGINAL layout
         (the bucket path moves no points)
      2. both paths conserve the global weight mass exactly
      3. both meet the knapsack balance bound for their granularity
         (element weight for sample-sort, bucket weight for summaries)
      4. the cached-tree reslice equals a fresh bucket partition on the
         drifted weights (same trees => identical knapsack input)
    """
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import partitioner as pt
        from repro.core.repartition import DistributedBucketRepartitioner
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ('data',))
        rng = np.random.default_rng(0)
        n, PARTS = 4096, 16
        pts_h = rng.random((n,3)).astype(np.float32)
        pts_h[: n // 2] = 0.45 + 0.1 * pts_h[: n // 2]
        wts_h = (0.1 + rng.random(n)).astype(np.float32)
        sh = NamedSharding(mesh, P('data'))
        pts = jax.device_put(jnp.asarray(pts_h), sh)
        wts = jax.device_put(jnp.asarray(wts_h), sh)
        cfg = pt.PartitionerConfig(use_tree=True, max_depth=8, bucket_size=16)
        part, leaf_id, node_keys = pt.distributed_bucket_partition(
            mesh, 'data', pts, wts, PARTS, cfg=cfg)
        p = np.asarray(part)
        assert p.shape[0] == n and (p >= 0).all() and (p < PARTS).all()   # (1)
        loads_b = np.zeros(PARTS); np.add.at(loads_b, p, wts_h)
        np.testing.assert_allclose(loads_b.sum(), wts_h.sum(), rtol=1e-5) # (2)
        # (3) bucket-granularity balance: spread <= 2 * max bucket weight
        lid = np.asarray(leaf_id).reshape(8, -1)
        maxbw = 0.0
        wsh = wts_h.reshape(8, -1)
        for s in range(8):
            bw = np.zeros(lid[s].max() + 1); np.add.at(bw, lid[s], wsh[s])
            maxbw = max(maxbw, bw.max())
        assert loads_b.max() - loads_b.min() <= 2 * maxbw + 1e-3
        # sample-sort on the same input meets its per-element bound
        keys, w_srt, part_srt = pt.distributed_partition(
            mesh, 'data', pts, wts, PARTS)
        w_h, ps_h = np.asarray(w_srt), np.asarray(part_srt)
        valid = ps_h >= 0
        loads_s = np.zeros(PARTS); np.add.at(loads_s, ps_h[valid], w_h[valid])
        np.testing.assert_allclose(loads_s.sum(), wts_h.sum(), rtol=1e-5) # (2)
        assert loads_s.max() / loads_s.mean() < 1.05
        assert loads_b.max() / loads_b.mean() < 1.25
        # (4) cached-tree reslice == fresh bucket partition on new weights
        w2_h = wts_h * (1.0 + 2.0 * (np.arange(n) % 5 == 0))
        w2 = jax.device_put(jnp.asarray(w2_h), sh)
        eng = DistributedBucketRepartitioner(mesh, 'data', PARTS, cfg)
        eng.partition(pts, wts)
        p_re = np.asarray(eng.rebalance(w2))
        p_fresh = np.asarray(pt.distributed_bucket_partition(
            mesh, 'data', pts, w2, PARTS, cfg=cfg)[0])
        np.testing.assert_array_equal(p_re, p_fresh)
        assert eng.reslices == 1 and eng.full_partitions == 1
        print('OK')
    """)
    assert "OK" in out


def test_shard_exchange_conserves():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import migration
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ('data',))
        rng = np.random.default_rng(1)
        n = 8 * 128
        payload = jax.device_put(jnp.arange(n, dtype=jnp.float32)[:, None], NamedSharding(mesh, P('data')))
        dest = jax.device_put(jnp.asarray(rng.integers(0, 8, n), jnp.int32), NamedSharding(mesh, P('data')))
        recv, valid = migration.execute_shard_exchange(mesh, 'data', payload, dest, capacity=64)
        got = np.asarray(recv)[np.asarray(valid)]
        want_count = sum(min(int((np.asarray(dest).reshape(8,-1)[s]==d).sum()), 64) for s in range(8) for d in range(8))
        assert got.shape[0] == want_count

        # apply_repartition: default capacity must never drop a row, and
        # invalid rows (part < 0) must park on their current shard
        from repro.distributed import sharding as shd
        part = jnp.where(jnp.arange(n) % 11 == 0, -1, dest)
        recv2, valid2 = shd.apply_repartition(mesh, 'data', payload, part)
        got2 = np.asarray(recv2)[np.asarray(valid2)]
        assert got2.shape[0] == n, (got2.shape[0], n)   # full conservation
        assert sorted(got2[:, 0].astype(int).tolist()) == list(range(n))
        print('OK', got.shape[0])
    """)
    assert "OK" in out


@pytest.mark.slow
def test_train_step_sharded_small_mesh():
    """A real sharded train step executes (not just lowers) on 8 devices."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import ARCHS, reduced
        from repro.configs.base import RunConfig, ShapeConfig, ShardingRules
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_mesh
        from repro.train import step as ts
        from repro.models import model as M
        mesh = make_mesh((4, 2), ('data', 'model'))
        cfg = reduced(ARCHS['smollm-135m'])
        run = RunConfig(model=cfg, shape=ShapeConfig('t', 32, 8, 'train'))
        rules = ShardingRules(batch=('data',))
        params, opt = ts.init_all(run, jax.random.PRNGKey(0))
        pshapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        psh = shd.param_shardings(mesh, cfg, rules, pshapes)
        params = jax.device_put(params, psh)
        osh = shd.opt_state_shardings(mesh, cfg, rules, None, psh)
        opt = jax.device_put(opt, osh)
        batch = M.synthetic_batch(cfg, 8, 32, jax.random.PRNGKey(1))
        bsh = shd.batch_shardings(mesh, cfg, rules, jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch))
        batch = jax.device_put(batch, bsh)
        with shd.activation_mesh(mesh, rules):
            # no donation here: zeros-dedup can alias m/v buffers at runtime;
            # compile-time donation is exercised by the dry-run tests
            step = jax.jit(ts.make_train_step(run, 100), in_shardings=(psh, osh, bsh))
            params, opt, metrics = step(params, opt, batch)
        loss = float(metrics['loss'])
        assert np.isfinite(loss) and loss > 0
        print('OK loss', loss)
    """)
    assert "OK" in out


@pytest.mark.slow
def test_dryrun_entry_on_8_devices():
    """dryrun.build_cell_fn lowers+compiles a reduced cell on a small mesh
    (the full 512-device sweep runs out-of-band; results in EXPERIMENTS.md)."""
    out = _run("""
        import jax, dataclasses
        from repro.configs import ARCHS, SHAPES, reduced
        from repro.configs.base import ShapeConfig, ShardingRules
        from repro.launch import dryrun
        from repro.launch.mesh import make_mesh
        from repro.distributed import sharding as shd
        import repro.launch.dryrun as dr
        mesh = make_mesh((4, 2), ('data', 'model'))
        cfg = reduced(ARCHS['qwen3-moe-30b-a3b'])
        shape = ShapeConfig('t', 64, 8, 'train')
        rules = ShardingRules(batch=('data',))
        fn, args, in_sh, out_sh = dr.build_cell_fn(cfg, shape, mesh, rules)
        with shd.activation_mesh(mesh, rules):
            compiled = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(*args).compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):  # jax 0.4.x returns [dict]
            cost = cost[0]
        assert cost.get('flops', 0) > 0
        coll = dr.parse_collectives(compiled.as_text())
        print('OK flops', cost['flops'], 'coll', coll['total_bytes'])
    """)
    assert "OK" in out


@pytest.mark.slow
def test_elastic_restore_to_different_mesh(tmp_path):
    out = _run(f"""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import checkpoint as ckpt
        from repro.launch.mesh import make_mesh
        mesh8 = make_mesh((8,), ('data',))
        w = jax.device_put(jnp.arange(64.0).reshape(8, 8), NamedSharding(mesh8, P('data')))
        ckpt.save({tmp_path.as_posix()!r}, 5, {{'w': w}})
        # restore onto a 4-device mesh (elastic shrink)
        mesh4 = make_mesh((4,), ('data',))
        like = {{'w': jax.ShapeDtypeStruct((8, 8), jnp.float32)}}
        sh = {{'w': NamedSharding(mesh4, P('data'))}}
        tree, _ = ckpt.restore({tmp_path.as_posix()!r}, 5, like, shardings=sh)
        assert tree['w'].sharding.num_devices == 4
        np.testing.assert_array_equal(np.asarray(tree['w']), np.arange(64.0).reshape(8, 8))
        print('OK')
    """)
    assert "OK" in out
