"""miniAMR's block-structured AMR on the cell octree: the 3-D key at
miniAMR's depth, block refinement against spheroid surfaces, its
7-point coefficients, V-field transfers, the V-wide stencil kernel, the
distributed run of V fields, and the ``mesh.*`` spans and counters."""
import glob
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.mesh import amr, simulate

REPO = Path(__file__).resolve().parents[1]
SPHERES = [amr.Spheroid((-1.10, -1.10, -1.10), (0.030, 0.030, 0.030), (1.5, 1.5, 1.5)),
           amr.Spheroid((0.5, 0.5, 1.76), (0.0, 0.0, -0.025), (0.75, 0.75, 0.75))]
TINY = dict(root_level=2, block_bits=1, num_refine=2, block_change=2)


def _tiny_events():
    return simulate.miniamr_events(SPHERES, 20, 25, **TINY)


def test_3d_key_at_level_9_round_trips():
    rng = np.random.default_rng(0)
    level = rng.integers(5, 10, 20000).astype(np.int32)
    ij = rng.integers(0, 1 << 9, (20000, 3)) >> (9 - level[:, None])
    keys = amr._pack(level, ij)
    _, first = np.unique(keys, return_index=True)
    level, ij = level[first], ij[first]
    # distinct cells keep distinct keys, and each key finds its own cell
    assert np.unique(np.stack([level, *ij.T], 1), axis=0).shape[0] == level.size
    look = amr._CellLookup(level, ij)
    np.testing.assert_array_equal(look.find(level, ij), np.arange(level.size))
    # the far corner at level 9 and its ancestors are different cells
    corner = np.array([[511, 511, 511]] * 5) >> np.arange(5)[:, None]
    assert np.unique(amr._pack(np.arange(9, 4, -1), corner)).size == 5
    m = amr.uniform_mesh(3, 5, 9)
    assert m.max_level == 9 and m.n == 1 << 15


def test_block_refinement_matches_a_brute_force_block_test():
    a, b_from_a, b, a_from_b = _tiny_events()
    for ev, t in ((a, 20), (b, 25)):
        blocks, cell_block = amr.blocks_of(ev.mesh, TINY["block_bits"])
        # every block holds all its cells: the masks stayed block-constant
        assert np.all(np.bincount(cell_block) == 1 << (3 * TINY["block_bits"]))
        # brute force: sample each block's box densely; a block whose
        # samples lie on both sides of a surface is crossed by it and
        # must be at the finest block level
        h = 0.5 ** blocks.level.astype(np.float64)
        g = np.linspace(0.0, 1.0, 9)
        pts = np.stack(np.meshgrid(g, g, g, indexing="ij"), -1).reshape(-1, 3)
        x = blocks.ij[:, None, :] * h[:, None, None] + pts[None] * h[:, None, None]
        crossed = np.zeros(blocks.n, bool)
        for obj in SPHERES:
            c, r = obj.at(t)
            f = np.sum(((x - c) / r) ** 2, axis=2)
            crossed |= (f.min(1) < 1.0) & (f.max(1) > 1.0)
        assert crossed.any()
        finest = blocks.level == blocks.max_level
        assert np.all(finest[crossed])
        np.testing.assert_array_equal(amr.surface_hit(blocks, SPHERES, t) | ~crossed,
                                      np.ones(blocks.n, bool))
        # 2:1 across block faces
        nb = amr.face_neighbors(blocks)
        lv = blocks.level[np.maximum(nb, 0)]
        assert np.all(np.abs(np.where(nb >= 0, lv - blocks.level[:, None], 0)) <= 1)


def test_miniamr_coefficients_average_and_split_graded_faces():
    m = amr.uniform_mesh(3, 2, 3)
    nbr = amr.face_neighbors(m)
    c = amr.miniamr_coeffs(m, nbr)
    interior = (nbr >= 0).sum(1) == 6
    assert np.all(c[interior][nbr[interior] >= 0] == np.float32(1 / 7))
    u = np.random.default_rng(1).random(m.n)
    got = u + np.sum(np.where(nbr >= 0, c * (u[np.maximum(nbr, 0)] - u[:, None]), 0), 1)
    want = (u + np.sum(np.where(nbr >= 0, u[np.maximum(nbr, 0)], 0), 1)) / 7
    np.testing.assert_allclose(got[interior], want[interior], rtol=1e-6)
    # a graded face: a coarse cell next to four finer ones splits 1/7
    a = _tiny_events()[0]
    c = a.coeff
    finer = a.mesh.level[np.maximum(a.nbr, 0)] > a.mesh.level[:, None]
    assert finer.any()
    np.testing.assert_array_equal(c[finer & (a.nbr >= 0)], np.float32(1 / 28))
    rows = np.flatnonzero(finer.any(1))
    for f in range(6):
        sub = finer[rows, 4 * f:4 * f + 4]
        full = sub.all(1)
        assert np.all(full | ~sub.any(1))          # all four finer, or none
        np.testing.assert_allclose(c[rows[full], 4 * f:4 * f + 4].sum(1), 1 / 7, rtol=1e-6)


def test_apply_transfer_of_many_fields_is_the_per_column_transfer():
    a, b_from_a, b, a_from_b = _tiny_events()
    rng = np.random.default_rng(2)
    u = rng.standard_normal((a.mesh.n, 5)).astype(np.float32)
    u[::7, 2] = -0.0
    for tr in b_from_a.transfer:
        want = np.stack([amr.apply_transfer(u[:, v], tr) for v in range(5)], 1)
        got = amr.apply_transfer(u, tr)
        assert got.dtype == np.float32
        np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
        u = got
    src0, died = amr.lineage(b_from_a.transfer, a.mesh.n)
    assert src0.size == b.mesh.n and amr.same_cells(b.mesh, b_from_a.mesh)
    # cells kept through every round keep their geometry
    kept = src0 >= 0
    np.testing.assert_array_equal(b.mesh.level[kept], a.mesh.level[src0[kept]])
    np.testing.assert_array_equal(b.mesh.ij[kept], a.mesh.ij[src0[kept]])
    assert np.intersect1d(died, src0[kept]).size == 0


@pytest.mark.parametrize("V,K,R,block,finer", [
    (40, 24, 700, None, 1.0), (3, 8, 1300, None, 1.0), (5, 24, 1300, 512, 1.0),
    (40, 24, 3000, None, 0.03), (5, 8, 2600, 512, 0.03),
    (6, 24, 900, None, 0.0), (6, 24, 1500, None, 256), (4, 5, 700, None, 1.0)])
def test_v_wide_pallas_kernel_bit_equal_in_interpret_mode(V, K, R, block, finer, monkeypatch):
    """Bit-equal to the jnp definition, and to it column by column.
    ``finer``: share of rows whose faces may hold finer neighbours (the
    other rows fill only the first slot of each face, as a mesh's table
    does on most rows), or their exact count; at 1.0 every row is on the
    finer-row list, at 0.03 the face pass and the finer-row pass both
    run, at 0.0 the list is empty, at 256 it has no pad. K = 5 is no
    face table: one pass with every slot, and an empty list."""
    from repro.kernels import ops
    from repro.kernels import stencil_update as su
    from repro.mesh import halo

    if block:   # several row blocks, the last overlapping the one before
        monkeypatch.setattr(su, "GATHER_ROWS", block)
    rng = np.random.default_rng(V + R)
    M = R + 300
    nbr = rng.integers(0, M, (R, K)).astype(np.int32)
    valid = rng.random((R, K)) < 0.8
    g = su.face_group(K)
    first = np.arange(K) % g == 0
    if isinstance(finer, int):
        has = np.zeros(R, bool)
        has[rng.choice(R, finer, replace=False)] = True
        valid[has, 1] = True
    else:
        has = rng.random(R) < finer
    valid[~has] &= first
    coeff = (rng.random((R, K)) / K).astype(np.float32)
    vals = rng.random((M, V)).astype(np.float32)
    u = vals[:R] * 0.5
    rows = halo.list_finer_rows(valid[None])[0]
    listed = rows[rows >= 0]
    np.testing.assert_array_equal(listed, np.flatnonzero(has & (g > 1) & valid[:, ~first].any(1)))
    if finer == 0.0 or g == 1:
        assert rows.size == 0
    if isinstance(finer, int):
        assert rows.size == finer == listed.size
    take = lambda a: np.take(a, rows, axis=0, mode="clip")
    got = np.asarray(ops.stencil_update(vals, u, nbr, valid, coeff, use_pallas=True,
                                        finer=(rows, take(nbr), take(valid), take(coeff))))
    ref = np.asarray(su.stencil_update_ref(vals, u, nbr, valid, coeff))
    np.testing.assert_array_equal(got, ref)
    # the face pass alone already gives every row the list leaves out
    face = np.asarray(su.fused_stencil_update_v(vals, u, nbr, valid, coeff, interpret=True))
    rest = np.ones(R, bool)
    rest[listed] = False
    np.testing.assert_array_equal(face[rest], ref[rest])
    per_col = np.stack([np.asarray(su.stencil_update_ref(vals[:, v], u[:, v], nbr, valid, coeff))
                        for v in range(V)], 1)
    np.testing.assert_array_equal(ref, per_col)


def test_finer_row_list_is_a_plan_table():
    """Each device's rows with a valid slot past the first of a face are
    listed once, ascending, -1 padded; a padded plan keeps the list, and
    the rows it names keep their tables, ghost references shifted as
    ``nbr_local``'s are."""
    from repro.core import partitioner as pt
    from repro.mesh import halo
    from repro.mesh.amr import face_group

    _, _, b, _ = _tiny_events()
    n, K = b.nbr.shape
    first = np.arange(K) % face_group(K) == 0
    part = (np.arange(n) * 4 // n).astype(np.int32)
    plan = halo.build_halo_plan(np.arange(n), part, b.nbr, b.coeff,
                                hierarchy=pt.HierarchyPlan(2, 2))
    caps = dict(plan.caps, cap=plan.cap + 40, gcap=plan.gcap + 16, fcap=plan.fcap + 24)
    x = plan.padded(caps)
    assert x.fcap == caps["fcap"] and x.caps == caps
    counts = []
    ghost_reads = 0
    for p in range(4):
        want = np.flatnonzero(plan.nbr_valid[p][:, ~first].any(1))
        k = want.size
        counts.append(k)
        cells = plan.owned_idx[p, want]
        assert (cells >= 0).all() and (b.nbr[cells][:, ~first] >= 0).any(1).all()
        for q in (plan, x):
            np.testing.assert_array_equal(q.finer_rows[p, :k], want)
            assert (q.finer_rows[p, k:] == -1).all()
        valid = plan.nbr_valid[p, want]
        ghost = valid & (plan.nbr_local[p, want] >= plan.cap)
        ghost_reads += int(ghost.sum())
        np.testing.assert_array_equal(x.nbr_valid[p, x.finer_rows[p, :k]], valid)
        np.testing.assert_array_equal(x.coeff[p, x.finer_rows[p, :k]], plan.coeff[p, want])
        np.testing.assert_array_equal(x.nbr_local[p, x.finer_rows[p, :k]],
                                      plan.nbr_local[p, want] + ghost * (x.cap - plan.cap))
    assert sum(counts) == int((b.nbr[:, ~first] >= 0).any(1).sum()) > 0
    assert plan.fcap == halo._roundup(max(counts))
    # listed rows read ghosts, so the padding's shift is exercised
    assert ghost_reads > 0
    # a table with no face groups lists nothing
    assert halo.list_finer_rows(plan.nbr_valid[..., :5]).shape == (4, 0)


def test_plan_with_fewer_finer_rows_reuses_the_compiled_sweep():
    """Two plans of one mesh, padded by one ``DistributedSim``, the
    second with fewer finer rows on its busiest chip: one compiled
    sweep serves both, bit-equal to the single-device sweep."""
    code = textwrap.dedent("""
        import numpy as np
        from repro.core import partitioner as pt
        from repro.distributed import sharding as shd
        from repro.mesh import amr, halo, simulate
        from repro.mesh.amr import face_group
        from repro.mesh import stencil as st
        S = [amr.Spheroid((-1.10, -1.10, -1.10), (0.03, 0.03, 0.03), (1.5, 1.5, 1.5)),
             amr.Spheroid((0.5, 0.5, 1.76), (0.0, 0.0, -0.025), (0.75, 0.75, 0.75))]
        _, _, b, _ = simulate.miniamr_events(S, 20, 25, root_level=2, block_bits=1,
                                             num_refine=2, block_change=2)
        n, K = b.nbr.shape
        listed = (b.nbr[:, np.arange(K) % face_group(K) != 0] >= 0).any(1)
        # chip 1 owns cells [c, c + n / 2): the finer rows lie near the
        # start of the cell order, so c moves them between the chips
        parts = {c: ((np.arange(n) - c) % n < n // 2).astype(np.int32) for c in range(n // 8)}
        most = {c: max(np.bincount(p[listed], minlength=2)) for c, p in parts.items()}
        c1, c2 = max(most, key=most.get), min(most, key=most.get)
        assert most[c2] < most[c1]
        hplan = pt.HierarchyPlan(1, 2)
        jm = shd.make_node_device_mesh(1, 2)
        u = np.random.default_rng(0).random((n, 3)).astype(np.float32)
        sim = simulate.DistributedSim(b, u, jm, hplan, capacity=4 * n, use_pallas=True,
                                      cfg=simulate.SimConfig(bucket_size=8, engine_max_depth=10))
        x1, x2 = (sim._pad(halo.build_halo_plan(np.arange(n), parts[c], b.nbr, b.coeff,
                                                hierarchy=hplan)) for c in (c1, c2))
        assert x1.caps == x2.caps
        assert (x2.finer_rows >= 0).sum(1).max() == most[c2] < most[c1]
        want = np.asarray(st.reference_stencil(u, b.nbr, b.nbr >= 0, b.coeff, 2))
        st._stencil_fn.cache_clear()
        for x in (x1, x2):
            out = st.stencil_steps(jm, x, st.put_state(jm, x, u), st.halo_args(jm, x), 2,
                                   use_pallas=True)
            assert np.array_equal(x.unpack_cells(np.asarray(out), n), want)
        info = st._stencil_fn.cache_info()
        assert (info.hits, info.misses) == (1, 1), info
        assert st._stencil_fn(jm, x2.axes, x2.stage_meta, True, True)._cache_size() == 1
        print("OK")
    """)
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         env=env, timeout=600)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-3000:]


def test_distributed_v3_run_bit_equal_to_single_device():
    code = textwrap.dedent("""
        import numpy as np
        from repro.core import partitioner as pt
        from repro.distributed import sharding as shd
        from repro.mesh import amr, halo, simulate
        from repro.mesh import stencil as st
        S = [amr.Spheroid((-1.10, -1.10, -1.10), (0.03, 0.03, 0.03), (1.5, 1.5, 1.5)),
             amr.Spheroid((0.5, 0.5, 1.76), (0.0, 0.0, -0.025), (0.75, 0.75, 0.75))]
        a, b_a, b, a_b = simulate.miniamr_events(S, 20, 25, root_level=2, block_bits=1,
                                                 num_refine=2, block_change=2)
        events = [a, b_a, b, a_b, a]
        u0 = np.random.default_rng(0).random((a.mesh.n, 3)).astype(np.float32)
        ref = simulate.run_reference(events, u0, 4)
        hplan = pt.HierarchyPlan(num_nodes=2, devices_per_node=4)
        jm = shd.make_node_device_mesh(2, 4)
        cfg = simulate.SimConfig(bucket_size=8, engine_max_depth=10)
        got, stats = simulate.run_distributed(events, u0, 4, jm, hplan, cfg=cfg,
                                              use_pallas=True)
        assert np.array_equal(got, ref), np.abs(got - ref).max()
        assert stats.amr_events == 2 and stats.moved_total > 0
        sim = simulate.DistributedSim(a, u0, jm, hplan, cfg=cfg, capacity=4 * b.mesh.n)
        for ev in events:
            sim.advance(ev, 4, rebalance=ev.transfer is not None, checksum_every=2)
        assert np.array_equal(sim.fields(), ref)
        assert sim.finish().checksums == 10 and len(sim.checksums) == 10
        # padded plans: a move across nodes between two partitions of b
        rng = np.random.default_rng(1)
        slots = np.arange(b.mesh.n)
        p1 = (np.arange(b.mesh.n) * 8 // b.mesh.n).astype(np.int32)
        p2 = np.roll(p1, b.mesh.n // 3)
        h1 = halo.build_halo_plan(slots, p1, b.nbr, b.coeff, hierarchy=hplan)
        h2 = halo.build_halo_plan(slots, p2, b.nbr, b.coeff, hierarchy=hplan)
        mv = halo.build_move_plan(h1, h2, hierarchy=hplan)
        assert mv.kind == "hier"
        grow = lambda c: {k: (tuple(x + 40 for x in v) if isinstance(v, tuple) else v + 40)
                          for k, v in c.items()}
        caps = grow({k: (tuple(map(max, h1.caps[k], h2.caps[k])) if k == "stages"
                         else max(h1.caps[k], h2.caps[k])) for k in h1.caps})
        x1, x2 = h1.padded(caps), h2.padded(caps)
        mvx = mv.padded(x1.cap, x2.cap, tuple(s.cap + 24 for s in mv.stages))
        u = rng.random((b.mesh.n, 3)).astype(np.float32)
        moved = st.move_state(jm, mvx, x1, st.put_state(jm, x1, u))
        assert np.array_equal(x2.unpack_cells(np.asarray(moved), b.mesh.n), u)
        want = st.reference_stencil(u, b.nbr, b.nbr >= 0, b.coeff, 3)
        for plan in (h2, x2):
            out = st.stencil_steps(jm, plan, st.put_state(jm, plan, u),
                                   st.halo_args(jm, plan), 3, use_pallas=True)
            assert np.array_equal(plan.unpack_cells(np.asarray(out), b.mesh.n), np.asarray(want))
        sums = np.asarray(st.checksum(jm, x2, st.put_state(jm, x2, u)))
        np.testing.assert_allclose(sums, u.astype(np.float64).sum(0), rtol=1e-6)
        print("OK")
    """)
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         env=env, timeout=600)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-3000:]


def _load(path: Path):
    name = "bench_" + path.stem.replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = sys.modules[name] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_mesh_spans_and_stats_are_emitted(tmp_path):
    from repro.core import partitioner as pt
    from repro.distributed import sharding as shd

    devtrace = _load(REPO / "bench" / "devtrace.py")
    a, b_from_a, b, a_from_b = _tiny_events()
    u0 = np.random.default_rng(3).random((a.mesh.n, 2)).astype(np.float32)
    sim = simulate.DistributedSim(
        a, u0, shd.make_node_device_mesh(1, 1), pt.HierarchyPlan(1, 1),
        cfg=simulate.SimConfig(bucket_size=8, engine_max_depth=10), capacity=4 * b.mesh.n)
    sim.advance(a, 2)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            sim.advance(b_from_a, 4, checksum_every=2)
            sim.advance(b, 4, rebalance=False, checksum_every=2)
    files = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    prof = devtrace.Profile.from_data(jax.profiler.ProfileData.from_file(sorted(files)[-1]))
    names = [sp[0] for sp in prof.spans]
    assert names.count("mesh.timestep") == 2
    for name in ("mesh.adapt", "mesh.engine", "mesh.plan", "mesh.move", "mesh.sweep",
                 "mesh.checksum", "repartition.step"):
        assert name in names, name
    assert names.count("mesh.checksum") == 4 and names.count("mesh.sweep") == 4
    # the engine's spans nest inside mesh.engine
    [eng] = [sp for sp in prof.spans if sp[0] == "mesh.engine" and any(
        q[0] == "repartition.step" and sp[1] <= q[1] and q[2] <= sp[2] for q in prof.spans)]
    st = sim.finish()
    assert st.ghost_cells == 0 and st.halo_bytes_stage == 0      # one device: no exchange
    # the plan of mesh b lists every row with a finer neighbour, under fcap
    K = b.nbr.shape[1]
    assert st.finer_rows == int((b.nbr[:, np.arange(K) % 4 != 0] >= 0).any(1).sum()) > 0
    assert st.finer_rows <= sim.xplan.fcap == sim.floors["fcap"]
    assert st.checksums == 4 and st.amr_events == 1 and len(sim.checksums) == 4
    # the span readers of the spheres cell read them
    run = SimpleNamespace(profile=prof, layer={"halo_bytes_stage": 4096})
    for metric in ("sweep", "adapt", "plan", "move", "engine"):
        value = _load(REPO / "bench" / "metrics" / f"spheres.{metric}_ms.py").read(run)
        assert value is not None and value > 0, metric
    assert _load(REPO / "bench" / "metrics" / "spheres.halo_mb.py").read(run) == 4096e-6
    assert eng[2] > eng[1]
