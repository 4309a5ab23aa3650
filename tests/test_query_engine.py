"""DistributedQueryEngine: knapsack-batched serving, live index swaps,
and (in a fake-device subprocess) sharded all_to_all query routing."""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import queries
from repro.core.partitioner import PartitionerConfig
from repro.core.repartition import Repartitioner
from repro.serve.query_engine import DistributedQueryEngine, QueryRequest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MORTON = PartitionerConfig(curve="morton")


def _engine(rng, n=2048, **kw):
    pts = jnp.asarray(rng.random((n, 3)), jnp.float32)
    rp = Repartitioner(pts, None, num_parts=8, capacity=2 * n, cfg=MORTON)
    return pts, rp, DistributedQueryEngine(rp.curve_index(), None, **kw)


def test_local_serving_matches_queries(rng):
    pts, rp, eng = _engine(rng)
    q = pts[:256]
    got = eng.point_location(q)
    want = queries.point_location(rp.curve_index(), q)
    np.testing.assert_array_equal(np.asarray(got.found), np.asarray(want.found))
    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(want.ids))
    qq = jnp.asarray(rng.random((64, 3)), jnp.float32)
    d_a, g_a = eng.knn(qq, k=3)
    d_b, g_b = queries.knn(rp.curve_index(), qq, k=3, cutoff_buckets=1)
    np.testing.assert_allclose(np.asarray(d_a), np.asarray(d_b), atol=1e-6)


def test_knapsack_batched_run_serves_all(rng):
    pts, rp, eng = _engine(rng, max_batch_rows=512)
    sizes = [700, 30, 301, 1200, 64, 256, 17, 903]
    reqs = []
    for i, m in enumerate(sizes):
        if i % 2:
            reqs.append(QueryRequest(i, rng.random((m, 3)).astype(np.float32), "knn", k=3))
        else:
            sel = rng.choice(2048, m, replace=True)
            reqs.append(QueryRequest(i, np.asarray(pts)[sel], "pl"))
    res = eng.run(reqs)
    assert set(res) == set(r.rid for r in reqs)
    for r in reqs:
        if r.kind == "pl":
            assert res[r.rid].found.shape == (r.rows,)
            assert bool(res[r.rid].found.all())  # stored points all located
        else:
            d, g = res[r.rid]
            assert d.shape == (r.rows, 3) and np.isfinite(np.asarray(d)).all()
    # admission actually split the queue into multiple balanced rounds
    assert eng.stats.rounds > 1
    assert eng.stats.queries_served == sum(sizes)


def test_submit_mid_flight_is_served(rng):
    """Work appended to the engine's live queue before/while running is
    admitted and answered — never silently dropped."""
    pts, rp, eng = _engine(rng, max_batch_rows=128)
    eng.submit([QueryRequest(100, np.asarray(pts[:50]), "pl")])
    res = eng.run([QueryRequest(101, rng.random((40, 3)).astype(np.float32), "knn")])
    assert set(res) == {100, 101}
    assert bool(res[100].found.all())
    assert not eng.queue  # drained


def test_duplicate_requests_do_not_crash(rng):
    """list.remove on the pending queue must match by identity — with
    dataclass __eq__, same-shaped ndarray fields raise ValueError."""
    pts, rp, eng = _engine(rng, max_batch_rows=64)
    q = rng.random((96, 3)).astype(np.float32)
    reqs = [QueryRequest(7, q.copy()), QueryRequest(7, rng.random((96, 3)).astype(np.float32))]
    res = eng.run(reqs)  # duplicates overwrite; must not raise
    assert 7 in res


def test_live_version_swap(rng):
    pts, rp, eng = _engine(rng)
    v0 = eng.version
    assert not eng.maybe_refresh(rp)  # fresh: no swap
    new_pts = jnp.asarray(rng.random((100, 3)), jnp.float32)
    slots = rp.insert(new_pts, jnp.ones(100))
    assert eng.maybe_refresh(rp)      # stale after geometry change
    assert eng.version == rp.index_version != v0
    f = eng.point_location(new_pts)
    assert bool(f.found.all())
    assert set(np.asarray(f.ids).tolist()) == set(np.asarray(slots).tolist())


def test_distributed_routing_subprocess():
    """Sharded serving on 8 fake devices: exact point location through
    the two-all_to_all route, certified misses, kNN recall, live swap."""
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
        from repro.core.partitioner import PartitionerConfig
        from repro.core.repartition import Repartitioner
        from repro.launch.mesh import make_mesh
        from repro.serve.query_engine import DistributedQueryEngine
        mesh = make_mesh((8,), ('data',))
        rng = np.random.default_rng(3)
        n = 4096
        pts_h = rng.random((n, 3)).astype(np.float32)
        pts_h[: n // 2] = 0.45 + 0.1 * pts_h[: n // 2]   # routing skew
        pts = jnp.asarray(pts_h)
        rp = Repartitioner(pts, None, num_parts=8, capacity=n,
                           cfg=PartitionerConfig(curve='morton'))
        eng = DistributedQueryEngine(rp.curve_index(), mesh, 'data')
        # exact point location across shards (odd batch exercises padding)
        sel = rng.choice(n, 511, replace=False)
        q = pts[jnp.asarray(sel)]
        f, ids, ok = eng.point_location(q)
        assert bool(f.all()), int(f.sum())
        np.testing.assert_array_equal(np.asarray(pts)[np.asarray(ids)], np.asarray(q))
        # misses stay certified misses
        f2, i2, ok2 = eng.point_location(jnp.asarray(rng.random((128, 3)) + 2.0, jnp.float32))
        assert not bool(f2.any()) and bool(ok2.all())
        # kNN recall vs bruteforce + self-query exactness
        qq = jnp.asarray(rng.random((256, 3)), jnp.float32)
        d_e, g_e = eng.knn(qq, k=3)
        d_b, g_b = queries.knn_bruteforce(pts, qq, k=3)
        recall = float(np.mean(np.any(
            np.asarray(g_e)[:, :, None] == np.asarray(g_b)[:, None, :], axis=1)))
        assert recall > 0.6, recall
        d_s, _ = eng.knn(q[:64], k=1)
        assert float(np.asarray(d_s).max()) <= 1e-6
        # live swap after a full rebuild (fresh keys, fresh frame)
        rp.update_weights(jnp.asarray(0.5 + rng.random(n), jnp.float32))
        rp.rebuild()
        assert eng.maybe_refresh(rp)
        f3, i3, ok3 = eng.point_location(q)
        assert bool(f3.all())
        print('OK recall', recall)
    """)
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "OK" in out.stdout


def test_tree_backed_index_serves_locally(rng):
    """Local serving path with a tree-backed index: swap must accept it
    (regression — it used to raise ValueError) and the run-scan cap must
    widen to the real max bucket length."""
    pts = jnp.asarray(rng.random((2048, 3)), jnp.float32)
    rp = Repartitioner(pts, None, num_parts=8, capacity=4096,
                       cfg=PartitionerConfig(curve="morton", use_tree=True))
    idx = rp.curve_index()
    assert idx.tree is not None
    eng = DistributedQueryEngine(idx, None)      # no ValueError
    got = eng.point_location(pts[:256])
    want = queries.point_location(idx, pts[:256], bucket_cap=eng._scan_cap)
    np.testing.assert_array_equal(np.asarray(got.found), np.asarray(want.found))
    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(want.ids))
    assert bool(got.found.all())


def test_replicate_hot_requires_mesh(rng):
    pts, rp, eng = _engine(rng)
    with pytest.raises(ValueError):
        eng.replicate_hot(4)


def test_admission_queue_rejects_overflow(rng):
    pts, rp, eng = _engine(rng, max_queue_rows=200)
    ok = QueryRequest(1, rng.random((150, 3)).astype(np.float32), "pl")
    big = QueryRequest(2, rng.random((100, 3)).astype(np.float32), "pl")
    rejected = eng.submit([ok, big])             # 150 + 100 > 200
    assert rejected == [big] and eng.queue == [ok]
    assert eng.stats.rejected_requests == 1
    assert eng.stats.rejected_rows == 100
    res = eng.run([])                            # queue drains, bound frees
    assert set(res) == {1}
    assert eng.submit([big]) == []               # admitted now
    assert set(eng.run([])) == {2}


def test_adaptive_round_rows_and_latency_stats(rng):
    pts, rp, eng = _engine(
        rng, max_batch_rows=1024, min_batch_rows=64, target_round_s=1e-9
    )
    reqs = [QueryRequest(i, rng.random((200, 3)).astype(np.float32), "pl")
            for i in range(4)]
    res = eng.run(reqs)
    assert set(res) == {0, 1, 2, 3}
    # an absurdly tight latency target drives the round budget to the floor
    assert eng.round_rows == eng.min_batch_rows
    assert len(eng.stats.request_latency_s) == 4
    assert all(t >= 0.0 for t in eng.stats.request_latency_s)


def test_tree_backed_and_skew_replication_subprocess():
    """The headline fix plus the skew machinery on 8 fake devices:

    * a tree-backed (kd-bucket ordered) index serves on a mesh and
      matches the local tree walk bit for bit — hits, misses, certs;
    * Zipf-hot queries under a tight lane budget take many routing
      rounds; replicating the hot buckets collapses them and the annex
      answers are bit-identical;
    * padding rows never pollute the hit counters.
    """
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
        from repro.core.partitioner import PartitionerConfig
        from repro.core.repartition import Repartitioner
        from repro.launch.mesh import make_mesh
        from repro.serve.query_engine import DistributedQueryEngine

        mesh = make_mesh((8,), ('data',))
        rng = np.random.default_rng(7)
        n = 4096
        pts_h = rng.random((n, 2)).astype(np.float32)
        pts_h[:64] = pts_h[0]        # duplicate run: key collisions
        pts = jnp.asarray(pts_h)

        # --- tree-backed index on the mesh vs the local tree walk -------
        rp = Repartitioner(pts, None, num_parts=8, capacity=n,
                           cfg=PartitionerConfig(curve='hilbert', use_tree=True))
        idx = rp.curve_index(32)
        assert idx.tree is not None
        eng = DistributedQueryEngine(idx, mesh, 'data', bucket_cap=32,
                                     hit_decay=1.0)
        sel = rng.choice(n, 300, replace=False)
        q = jnp.concatenate([pts[jnp.asarray(sel)],
                             jnp.asarray(rng.random((211, 2)) + 1.5, jnp.float32)])
        ref = queries.point_location(idx, q, bucket_cap=eng._scan_cap)
        got = eng.point_location(q)
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # padding rows (511 -> 512) never reach the hit counters
        assert float(eng.bucket_hits.sum()) == float(q.shape[0])
        print('OK tree-backed')

        # --- Zipf skew: bounded lanes, then hot-bucket replication ------
        eng2 = DistributedQueryEngine(idx, mesh, 'data', bucket_cap=32,
                                      lane_rows=16, hit_decay=1.0)
        B = idx.num_buckets
        zipf = 1.0 / np.arange(1, B + 1)
        hot_bucket = rng.permutation(B)
        bw = np.zeros(B); bw[hot_bucket] = zipf / zipf.sum()
        starts = np.asarray(idx.bucket_starts)
        rows = []
        for b in rng.choice(B, 1024, p=bw):
            lo, hi = int(starts[b]), int(starts[b + 1])
            if hi > lo:
                rows.append(int(rng.integers(lo, hi)))
        qz = jnp.asarray(np.asarray(idx.points)[rows], jnp.float32)
        refz = queries.point_location(idx, qz, bucket_cap=eng2._scan_cap)

        gz = eng2.point_location(qz)
        rounds_contig = eng2.stats.route_rounds
        for a, b in zip(gz, refz):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert rounds_contig > 1     # lane overflow forced re-dispatch

        hot = eng2.replicate_hot(top_k=12)
        assert hot and eng2.stats.replications == 1
        gz2 = eng2.point_location(qz)
        rounds_repl = eng2.stats.route_rounds - rounds_contig
        for a, b in zip(gz2, refz):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert eng2.stats.annex_served > 0
        assert rounds_repl < rounds_contig
        eng2.replicate_hot(top_k=0)  # clears the annex
        gz3 = eng2.point_location(qz)
        for a, b in zip(gz3, refz):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print('OK skew', rounds_contig, rounds_repl,
              int(eng2.stats.annex_served))
    """)
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "OK tree-backed" in out.stdout and "OK skew" in out.stdout


def test_lane_subset_replication_subprocess():
    """``replicate_hot(shards=...)`` on 8 fake devices: lane-hit
    counters see the skewed traffic, a top-k lane subset annex serves
    only those lanes' queries bit-equal to the reference, to the
    engine-wide annex, and to plain routing; explicit lane ids work;
    and a reshard drops the placement-addressed subset annex."""
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
        from repro.core.partitioner import PartitionerConfig
        from repro.core.repartition import Repartitioner
        from repro.launch.mesh import make_mesh
        from repro.serve.query_engine import DistributedQueryEngine

        mesh = make_mesh((8,), ('data',))
        rng = np.random.default_rng(11)
        n = 4096
        pts_h = rng.random((n, 2)).astype(np.float32)
        pts = jnp.asarray(pts_h)
        rp = Repartitioner(pts, None, num_parts=8, capacity=n,
                           cfg=PartitionerConfig(curve='hilbert', use_tree=True))
        idx = rp.curve_index(32)

        def fresh():
            return DistributedQueryEngine(idx, mesh, 'data', bucket_cap=32,
                                          lane_rows=16, hit_decay=1.0)

        # Zipf-hot traffic concentrated on a few buckets -> a few lanes
        B = idx.num_buckets
        zipf = 1.0 / np.arange(1, B + 1) ** 1.5
        hot_bucket = rng.permutation(B)
        bw = np.zeros(B); bw[hot_bucket] = zipf / zipf.sum()
        starts = np.asarray(idx.bucket_starts)
        rows = []
        for b in rng.choice(B, 1024, p=bw):
            lo, hi = int(starts[b]), int(starts[b + 1])
            if hi > lo:
                rows.append(int(rng.integers(lo, hi)))
        qz = jnp.asarray(np.asarray(idx.points)[rows], jnp.float32)
        ref = queries.point_location(idx, qz, bucket_cap=fresh()._scan_cap)

        def check(eng):
            got = eng.point_location(qz)
            for a, b in zip(got, ref):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            return got

        # 1) warm the counters, then annex the 2 hottest lanes only
        eng = fresh()
        check(eng)
        assert float(eng.lane_hits.sum()) == float(qz.shape[0])
        hot_lanes = np.argsort(eng.lane_hits)[::-1][:2]
        assert eng.replicate_hot(top_k=12, shards=2)
        assert set(eng._hot['lanes']) == set(int(l) for l in hot_lanes)
        served0 = eng.stats.annex_served
        check(eng)
        assert eng.stats.annex_served > served0
        # only selected lanes' copies exist, on those lanes' devices
        devs = eng._lane_devices()
        for l, copy in eng._hot['copies'].items():
            assert copy[0].devices() == {devs[l]}

        # 2) subset answers == engine-wide annex answers (bit-equal)
        eng_full = fresh()
        check(eng_full)
        eng_full.replicate_hot(top_k=12)
        check(eng_full)
        assert eng_full.stats.annex_served > 0
        assert eng_full._hot['lanes'] is None

        # 3) explicit lane ids; out-of-range rejected
        eng2 = fresh()
        check(eng2)
        assert eng2.replicate_hot(top_k=12, shards=[int(hot_lanes[0])])
        served0 = eng2.stats.annex_served
        check(eng2)
        assert eng2.stats.annex_served > served0
        try:
            eng2.replicate_hot(top_k=12, shards=[99])
        except ValueError:
            pass
        else:
            raise AssertionError('bad lane id accepted')

        # 4) reshard drops the placement-addressed subset annex but
        #    keeps serving correct; shards=0 selects no lanes
        eng2.reshard(mesh, 'data')
        assert eng2._hot is None
        check(eng2)
        assert eng2.replicate_hot(top_k=12, shards=0) == []
        check(eng2)
        print('OK lane subset', int(eng.stats.annex_served))
    """)
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "OK lane subset" in out.stdout
