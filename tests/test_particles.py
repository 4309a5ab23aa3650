"""Particle application layer: cutoff interaction lists, the fused pair
kernel, slot-tracked registration, and the distributed N-body / coupled
particle-mesh loops' bit-equality to their single-device references.

Local tests cover the host-side table construction and kernel physics;
the closed distributed loops run in a subprocess with 8 fake host
devices (see test_distributed.py for why the flag must be set before
jax initializes).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from _hypothesis_compat import given, settings, strategies as st
from repro.particles import interact, state

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


def _dense_table(n: int) -> np.ndarray:
    """The O(n^2) oracle table: every j != i, ascending, K = n-1 padded."""
    K = interact._roundup(n - 1, 8)
    nbr = np.full((n, K), -1, np.int32)
    for i in range(n):
        row = np.delete(np.arange(n, dtype=np.int32), i)
        nbr[i, : n - 1] = row
    return nbr


# ---------------------------------------------------------------------------
# cutoff neighbor lists vs the brute-force oracle
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(
    d=st.sampled_from([2, 3]),
    n=st.integers(24, 96),
    seed=st.integers(0, 7),
    radius=st.sampled_from([0.08, 0.12, 0.2, 0.35, 0.5]),
)
def test_cutoff_neighbors_complete_and_symmetric(d, n, seed, radius):
    """Every strictly-in-range pair appears (the probe-walk coverage
    claim), the table is symmetric, deterministic lane order holds, and
    no self pairs leak in."""
    rng = np.random.default_rng(seed)
    pos = rng.random((n, d)).astype(np.float32)
    nbr = interact.cutoff_neighbors(pos, radius)
    assert nbr.dtype == np.int32 and nbr.shape[0] == n and nbr.shape[1] % 8 == 0

    diff = pos[:, None, :].astype(np.float64) - pos[None, :, :].astype(np.float64)
    d2 = np.einsum("ijk,ijk->ij", diff, diff)
    in_range = (d2 < radius * radius) & ~np.eye(n, dtype=bool)

    pairs = {(i, int(j)) for i in range(n) for j in nbr[i] if j >= 0}
    for i, j in zip(*np.nonzero(in_range)):
        assert (int(i), int(j)) in pairs, "in-range pair missing from table"
    assert all((j, i) in pairs for (i, j) in pairs), "table not symmetric"
    assert all(i != j for (i, j) in pairs), "self pair leaked"
    for i in range(n):
        lane = nbr[i][nbr[i] >= 0]
        assert (np.diff(lane) > 0).all(), "lanes not in ascending id order"


@settings(max_examples=8, deadline=None)
@given(d=st.sampled_from([2, 3]), seed=st.integers(0, 7))
def test_cutoff_forces_match_dense_oracle(d, seed):
    """Accelerations through the cutoff table agree with the full O(n^2)
    table: out-of-range lanes weigh exactly 0, so only accumulation
    order can differ — allclose at float32 tightness."""
    rng = np.random.default_rng(seed)
    n, radius = 48, 0.3
    pos = rng.random((n, d)).astype(np.float32)
    mass = (0.5 + rng.random(n)).astype(np.float32)
    rc2 = np.float32(radius * radius)

    nbr = interact.cutoff_neighbors(pos, radius)
    dense = _dense_table(n)
    a_cut = np.asarray(interact._ops.pair_accel(
        pos, mass, pos, nbr, nbr >= 0, rc2))
    a_all = np.asarray(interact._ops.pair_accel(
        pos, mass, pos, dense, dense >= 0, rc2))
    np.testing.assert_allclose(a_cut, a_all, rtol=1e-5, atol=1e-6)


def test_cutoff_neighbors_rejects_bad_radius():
    pos = np.random.default_rng(0).random((8, 2)).astype(np.float32)
    for r in (0.0, -0.1, 0.6):
        with pytest.raises(ValueError, match="radius"):
            interact.cutoff_neighbors(pos, r)


# ---------------------------------------------------------------------------
# pair kernel physics
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(d=st.sampled_from([2, 3]), seed=st.integers(0, 7))
def test_pair_accel_antisymmetric_two_body(d, seed):
    """Equal masses, one pair: a_i is the exact bitwise negation of a_j
    (IEEE: (xj - xi) == -(xi - xj) and both rows see the identical d2)."""
    rng = np.random.default_rng(seed)
    pos = (0.45 + 0.1 * rng.random((2, d))).astype(np.float32)
    mass = np.full(2, np.float32(1.25))
    nbr = np.full((2, 8), -1, np.int32)
    nbr[0, 0], nbr[1, 0] = 1, 0
    acc = np.asarray(interact._ops.pair_accel(
        pos, mass, pos, nbr, nbr >= 0, np.float32(0.25)))
    assert np.array_equal(acc[0], -acc[1])
    assert (acc[0] != 0).any(), "pair out of range — test config broken"


@settings(max_examples=6, deadline=None)
@given(d=st.sampled_from([2, 3]), seed=st.integers(0, 7))
def test_pair_kick_conserves_momentum(d, seed):
    """General masses: the pairwise impulse m_i * a_i sums to ~0 (the
    force law is antisymmetric in (i, j), so momentum transfers cancel
    up to float32 accumulation)."""
    rng = np.random.default_rng(seed)
    n, radius = 64, 0.25
    pos = rng.random((n, d)).astype(np.float32)
    mass = (0.5 + rng.random(n)).astype(np.float32)
    nbr = interact.cutoff_neighbors(pos, radius)
    acc = np.asarray(interact._ops.pair_accel(
        pos, mass, pos, nbr, nbr >= 0, np.float32(radius * radius)))
    impulse = (mass[:, None].astype(np.float64) * acc.astype(np.float64)).sum(0)
    scale = np.abs(mass[:, None] * acc).sum()
    assert np.abs(impulse).max() <= 1e-5 * max(scale, 1.0)


def test_pair_accel_pallas_bit_equal_to_jnp():
    """The Pallas kernel (interpret mode) and the jnp fallback are the
    same expression — bit-equal on random tables, pads included, when
    compared in the same jit context (the executors' regime; eager
    dispatch would fuse fma differently and is not the contract)."""
    import jax

    fn = jax.jit(interact._ops.pair_accel, static_argnames=("use_pallas",))
    rng = np.random.default_rng(3)
    for d in (2, 3):
        n = 96
        pos = rng.random((n, d)).astype(np.float32)
        mass = (0.5 + rng.random(n)).astype(np.float32)
        nbr = interact.cutoff_neighbors(pos, 0.2)
        rc2 = np.float32(0.04)
        a_j = np.asarray(fn(pos, mass, pos, nbr, nbr >= 0, rc2,
                            use_pallas=False))
        a_p = np.asarray(fn(pos, mass, pos, nbr, nbr >= 0, rc2,
                            use_pallas=True))
        assert np.array_equal(a_j, a_p)


def test_leapfrog_momentum_drift_small_away_from_walls():
    """A short reference trajectory with generous wall clearance: total
    momentum (float64) drifts only at float32 accumulation scale."""
    ps = state.random_particles(128, 2, seed=5, v0=0.05, margin=0.35)
    nbr = interact.cutoff_neighbors(ps.pos, 0.15)
    x, v = interact.reference_leapfrog(
        ps.pos, ps.vel, ps.mass, nbr, 4, 0.005, 0.15)
    p0 = (ps.mass[:, None].astype(np.float64) * ps.vel.astype(np.float64)).sum(0)
    p1 = (ps.mass[:, None].astype(np.float64) * np.asarray(v, np.float64)).sum(0)
    assert np.abs(p1 - p0).max() <= 1e-4
    assert (np.asarray(x) >= 0).all() and (np.asarray(x) <= 1).all()


# ---------------------------------------------------------------------------
# slot-tracked registration
# ---------------------------------------------------------------------------

def test_particle_engine_reregisters_crossers_and_keeps_anchor_prefix():
    """Moving particles across part boundaries re-registers exactly the
    crossers through delete+insert, reuses only particle slots (anchors
    are never recycled), and leaves partition() consistent with the
    engine's own directory."""
    from repro.core import partitioner as pt
    from repro.mesh import halo

    rng = np.random.default_rng(0)
    n_anchor, n = 32, 96
    anchors = rng.random((n_anchor, 2)).astype(np.float32)
    ps = state.random_particles(n, 2, seed=1)
    pts = np.concatenate([anchors, ps.pos])
    eng = state.ParticleEngine(
        pts, np.ones(n_anchor + n, np.float32),
        plan=pt.HierarchyPlan(num_nodes=2, devices_per_node=4),
        n_anchor=n_anchor, capacity=2 * (n_anchor + n),
    )
    assert np.array_equal(eng.slots, np.arange(n_anchor + n))

    # drag a third of the particles into the far-x band — most cross.
    # (A band, not a point cluster: near-identical positions can share a
    # curve bucket that a re-slice cut later splits, making directory
    # ownership legitimately coarser than the per-slot assignment.)
    pos2 = ps.pos.copy()
    pos2[: n // 3, 0] = 0.85 + 0.13 * rng.random(n // 3).astype(np.float32)
    w = np.ones(n, np.float32)
    moved = eng.reregister(pos2, w)
    assert 0 < moved <= n // 3 + 5
    assert eng.registrations == 1 and eng.crossers_total == moved
    assert eng.particle_slots.min() >= n_anchor
    assert np.array_equal(eng.slots[:n_anchor], np.arange(n_anchor))
    assert np.unique(eng.slots).size == eng.slots.size

    # after the next engine step emits a fresh assignment (the driver's
    # sequencing), the directory view and the slot assignment agree up
    # to bucket granularity: the band's worth of crossers is re-homed,
    # leaving at most a cut-straddling-bucket residue. The detector is a
    # placement heuristic — trajectory bit-equality never depends on it.
    eng.step()
    idx = eng.rp.curve_index(eng.bucket_size)
    owner = halo.owners_from_index(idx, np.asarray(eng.rp.part), pos2)
    mismatch = int((owner != eng.rp.partition_of(eng.particle_slots)).sum())
    assert mismatch < moved // 2
    # a second pass re-registers only that residue, not the band again
    assert eng.reregister(pos2, w) == mismatch


# ---------------------------------------------------------------------------
# distributed execution (8 fake devices, subprocess)
# ---------------------------------------------------------------------------

def test_distributed_nbody_bit_equal_both_drivers():
    out = _run("""
        import numpy as np
        from repro.core import partitioner as pt
        from repro.distributed import sharding as shd
        from repro.particles import simulate

        cfg = simulate.ParticleSimConfig(n=192, events=6, substeps=2)
        ref = simulate.run_reference(cfg)
        hplan = pt.HierarchyPlan(num_nodes=2, devices_per_node=4)
        mesh = shd.make_node_device_mesh(2, 4)
        for driver in ("incremental", "rebuild"):
            out, st = simulate.run_distributed(cfg, mesh, hplan, driver=driver)
            assert np.array_equal(ref.pos, out.pos), driver
            assert np.array_equal(ref.vel, out.vel), driver
            assert st.events == 6
            assert st.repartition_events >= 1
            assert st.registration_events >= 1 and st.crossers_total >= 1
        print("OK", st.repartition_events)
    """)
    assert "OK" in out


def test_distributed_pic_coupled_bit_equal():
    out = _run("""
        import numpy as np
        from repro.core import partitioner as pt
        from repro.distributed import sharding as shd
        from repro.particles import pic

        cfg = pic.PICSimConfig(n=128, events=5, substeps=2, mesh_level=3)
        u_ref, ps_ref = pic.run_reference_coupled(cfg)
        hplan = pt.HierarchyPlan(num_nodes=2, devices_per_node=4)
        mesh = shd.make_node_device_mesh(2, 4)
        u, ps, st = pic.run_distributed_coupled(
            cfg, mesh, hplan, driver="incremental")
        assert np.array_equal(u_ref, u)
        assert np.array_equal(ps_ref.pos, ps.pos)
        assert np.array_equal(ps_ref.vel, ps.vel)
        # mass is carried through every migration untouched
        assert np.array_equal(ps_ref.mass, ps.mass)
        assert st.n_cells == 64 and st.events == 5
        assert st.registration_events >= 1
        print("OK")
    """)
    assert "OK" in out
