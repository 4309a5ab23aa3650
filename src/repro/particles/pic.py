"""Coupled particle-mesh (PIC) step on ONE shared partition.

Mesh cells and particles register in a single
`state.ParticleEngine` (cells as the static anchor prefix, particles
behind them), so one knapsack slice owns both entity kinds, ONE
`halo.build_halo_plan` over the union row set compiles both the
stencil halo and the pairwise interaction exchange, and ONE
`interact.move_rows` migration carries the combined state matrix
``[u | pos | vel | mass]`` between partitions.

The union (n_u, K) table concatenates each row's lanes by entity kind:
cell rows carry their `mesh.amr.face_neighbors` lanes (with heat-flux
coefficients), particle rows their `interact.cutoff_neighbors` lanes
(offset by the cell count). A per-row particle flag splits the lane
masks on device — cell rows run the fused stencil update on column 0,
particle rows the fused pair acceleration on the position columns, and
both phases share the routed ghost matrix and the traced-substep
``fori_loop``. The reference and the distributed executor run the one
substep expression, :func:`_pic_body`, over every row at once.

Deposit (particle -> containing cell, ``u += kappa * mass``) and
interpolate (cell -> particle, a drag ``vel *= 1 - gamma * u``) are
host-side transfer maps applied at event boundaries on both backends
in the same deterministic order — `np.add.at` in global particle row
order — so the coupled trajectory stays bitwise comparable.

Honest scope notes: the mesh is static and uniform (no refine/coarsen
during the coupled run — AMR rebirth of *cell* slots composes with
particle re-registration but is not exercised here), and coupling
happens at event boundaries, not per substep.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.kernels import ops as _ops
from repro.mesh import amr as _amr
from repro.mesh import halo as _halo
from repro.mesh import stencil as _st
from repro.mesh.halo import _roundup
from repro.particles import interact as _ia
from repro.particles import state as _ps
from repro.particles.simulate import ParticleSimStats, _degree_weights


@dataclass(frozen=True)
class PICSimConfig:
    d: int = 2
    n: int = 256                # particles
    mesh_level: int = 3         # static uniform mesh: 2**(d*level) cells
    events: int = 8
    substeps: int = 2
    dt: float = 0.01            # particle kick-drift step
    radius: float = 0.15
    seed: int = 0
    v0: float = 0.8
    margin: float = 0.1
    kappa: float = 0.05         # deposit strength (mass -> cell field)
    gamma: float = 0.2          # interpolate strength (field -> drag)
    couple_every: int = 2       # deposit/interp every k-th event
    reregister_every: int = 2
    dt_safety: float = 0.25     # mesh stencil stability factor
    bucket_size: int = 8
    engine_max_depth: int = 10
    node_threshold: float = 1.20


# ---------------------------------------------------------------------------
# union tables + transfer maps
# ---------------------------------------------------------------------------

def union_tables(
    mesh_nbr: np.ndarray, mesh_coeff: np.ndarray, pair_nbr: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Concatenate cell face lanes and particle pair lanes into one
    (n_u, K) neighbor/coefficient table over union row order
    ``[cells; particles]`` (particle targets offset by the cell count)."""
    nc, Km = mesh_nbr.shape
    npart, Kp = pair_nbr.shape
    K = _roundup(max(Km, Kp), 8)
    nbr = np.full((nc + npart, K), -1, np.int32)
    nbr[:nc, :Km] = mesh_nbr
    nbr[nc:, :Kp] = np.where(pair_nbr >= 0, pair_nbr + nc, -1)
    coeff = np.zeros((nc + npart, K), np.float32)
    coeff[:nc, :Km] = mesh_coeff
    return nbr, coeff


def cell_lookup(mesh: _amr.AMRMesh):
    """Position -> containing-cell map for a static uniform mesh."""
    level = int(mesh.level[0])
    assert (mesh.level == level).all(), "cell_lookup requires a uniform mesh"
    side = 1 << level
    lut = np.full((side,) * mesh.d, -1, np.int64)
    lut[tuple(mesh.ij.T)] = np.arange(mesh.n, dtype=np.int64)

    def locate(pos: np.ndarray) -> np.ndarray:
        ip = np.clip(
            (np.asarray(pos, np.float64) * side).astype(np.int64), 0, side - 1
        )
        return lut[tuple(ip.T)]

    return locate


def apply_coupling(
    u: np.ndarray,
    vel: np.ndarray,
    mass: np.ndarray,
    cell_of: np.ndarray,
    kappa: float,
    gamma: float,
) -> tuple[np.ndarray, np.ndarray]:
    """Deposit then interpolate, in one deterministic host pass.

    ``np.add.at`` accumulates sequentially in particle row order, so
    both backends (which call this on bit-identical inputs) produce
    bit-identical fields; the drag reads the POST-deposit field.
    """
    dep = np.zeros_like(u)
    np.add.at(dep, cell_of, np.float32(kappa) * mass)
    u2 = u + dep
    f = np.float32(1.0) - np.float32(gamma) * u2[cell_of]
    return u2, vel * f[:, None]


def initial_field(mesh: _amr.AMRMesh) -> np.ndarray:
    """A heat blob at the domain center."""
    c = np.full((mesh.d,), 0.5)
    d2 = np.sum((mesh.centers().astype(np.float64) - c[None, :]) ** 2, axis=1)
    return np.exp(-d2 / 0.02).astype(np.float32)


# ---------------------------------------------------------------------------
# the fused coupled substep (stencil + pair accel share one exchange)
# ---------------------------------------------------------------------------

def _pic_body(U, A, isp, nbr, valid, coeff, rc2, dt, d, use_pallas):
    """One coupled substep of the rows ``U`` whose tables index ``A``:
    ``U`` itself in the reference (global order), ``concat([U,
    ghosts])`` in the distributed executor — the same expressions, so
    identical bits per row."""
    u = U[:, 0]
    x = U[:, 1:1 + d]
    v = U[:, 1 + d:1 + 2 * d]
    m = U[:, 1 + 2 * d]
    cval = valid & (~isp)[:, None]
    pval = valid & isp[:, None]
    u_new = _ops.stencil_update(A[:, 0], u, nbr, cval, coeff, use_pallas=use_pallas)
    acc = _ops.pair_accel(A[:, 1:1 + d], A[:, 1 + 2 * d], x, nbr, pval, rc2,
                          use_pallas=use_pallas)
    x2, v2 = _ia._integrate(x, v, acc, dt)
    return jnp.concatenate([u_new[:, None], x2, v2, m[:, None]], axis=1)


@functools.lru_cache(maxsize=4)
def _pic_reference_fn(d: int, use_pallas: bool):
    @jax.jit
    def fn(steps, dt, rc2, U, isp, nbr, valid, coeff):
        def body(_, U):
            return _pic_body(U, U, isp, nbr, valid, coeff, rc2, dt, d, use_pallas)
        return jax.lax.fori_loop(0, steps, body, U)
    return fn


def reference_pic_steps(U, isp, nbr, coeff, steps, dt, radius,
                        *, use_pallas=False):
    """``steps`` coupled substeps on one device, union row order."""
    d = (U.shape[1] - 2) // 2
    nbr = jnp.asarray(nbr)
    return _pic_reference_fn(int(d), bool(use_pallas))(
        jnp.int32(steps), jnp.float32(dt), jnp.float32(float(radius) ** 2),
        jnp.asarray(U, jnp.float32), jnp.asarray(isp), nbr, nbr >= 0,
        jnp.asarray(coeff, jnp.float32),
    )


@functools.lru_cache(maxsize=64)
def _pic_fn(
    mesh: jax.sharding.Mesh,
    axes: tuple,
    stage_meta: tuple,
    d: int,
    use_pallas: bool,
):
    """Jitted coupled executor: ONE ghost exchange of the full state
    matrix per substep feeds both the stencil and the pair phase."""

    def kernel(steps, dt, rc2, U, isp, nbr, valid, coeff, fetch, *stage_idx):
        def body(_, U):
            recv = _ia._route_cols(U, stage_meta, stage_idx, jnp.float32(0.0))
            A = jnp.concatenate([U, _st._ghosts(recv, fetch)], axis=0)
            return _pic_body(U, A, isp, nbr, valid, coeff, rc2, dt, d, use_pallas)
        return jax.lax.fori_loop(0, steps, body, U)

    spec = P(axes)
    in_specs = (P(), P(), P()) + (spec,) * (6 + len(stage_meta))
    return jax.jit(jax.shard_map(
        kernel, mesh=mesh, in_specs=in_specs, out_specs=spec, check_vma=False,
    ))


def pic_steps(jax_mesh, plan, U_dev, isp_dev, hargs: _st.HaloArgs,
              steps: int, dt: float, radius: float, *, use_pallas=False):
    """Run ``steps`` distributed coupled substeps over the plan's layout."""
    d = (int(U_dev.shape[-1]) - 2) // 2
    fn = _pic_fn(jax_mesh, plan.axes, plan.stage_meta, d, bool(use_pallas))
    return fn(
        jnp.int32(steps), jnp.float32(dt), jnp.float32(float(radius) ** 2),
        U_dev, isp_dev, *hargs.core, *hargs.stages,
    )


# ---------------------------------------------------------------------------
# closed-loop coupled drivers
# ---------------------------------------------------------------------------

def _setup(cfg: PICSimConfig):
    mesh = _amr.uniform_mesh(cfg.d, cfg.mesh_level, cfg.mesh_level)
    dt_mesh = _amr.stable_dt(mesh, cfg.dt_safety)
    mesh_nbr = _amr.face_neighbors(mesh)
    mesh_coeff = _amr.stencil_coeffs(mesh, mesh_nbr, dt_mesh)
    ps = _ps.random_particles(
        cfg.n, cfg.d, seed=cfg.seed, v0=cfg.v0, margin=cfg.margin
    )
    u0 = initial_field(mesh)
    return mesh, mesh_nbr, mesh_coeff, ps, u0


def _host_state(u, pos, vel, mass, nc, n, d):
    """Union-row state matrix [u | pos | vel | mass] (cells zero-pad the
    particle columns and vice versa)."""
    C = 2 * d + 2
    U = np.zeros((nc + n, C), np.float32)
    U[:nc, 0] = u
    U[nc:, 1:1 + d] = pos
    U[nc:, 1 + d:1 + 2 * d] = vel
    U[nc:, 1 + 2 * d] = mass
    return U


def run_reference_coupled(
    cfg: PICSimConfig, *, use_pallas: bool = False
) -> tuple[np.ndarray, _ps.ParticleSet]:
    """Single-device coupled integration (the bitwise oracle). Returns
    the final cell field and particle state."""
    mesh, mesh_nbr, mesh_coeff, ps, u = _setup(cfg)
    locate = cell_lookup(mesh)
    nc, n, d = mesh.n, cfg.n, cfg.d
    pos, vel = ps.pos, ps.vel
    for t in range(cfg.events):
        if cfg.couple_every and t % cfg.couple_every == 0 and t > 0:
            u, vel = apply_coupling(
                u, vel, ps.mass, locate(pos), cfg.kappa, cfg.gamma
            )
        pair = _ia.cutoff_neighbors(pos, cfg.radius)
        nbr, coeff = union_tables(mesh_nbr, mesh_coeff, pair)
        isp = np.arange(nc + n) >= nc
        U = _host_state(u, pos, vel, ps.mass, nc, n, d)
        U = np.asarray(reference_pic_steps(
            U, isp, nbr, coeff, cfg.substeps, cfg.dt, cfg.radius,
            use_pallas=use_pallas,
        ))
        u, pos, vel = U[:nc, 0], U[nc:, 1:1 + d], U[nc:, 1 + d:1 + 2 * d]
    return u, _ps.ParticleSet(pos=pos, vel=vel, mass=ps.mass)


def run_distributed_coupled(
    cfg: PICSimConfig,
    jax_mesh,
    hplan,
    *,
    driver: str = "incremental",
    use_pallas: bool = False,
) -> tuple[np.ndarray, _ps.ParticleSet, ParticleSimStats]:
    """Coupled integration on a device mesh: cells + particles in ONE
    engine, one plan, one migration for the combined state matrix."""
    if driver not in ("incremental", "rebuild"):
        raise ValueError(f"unknown driver {driver!r}")
    mesh, mesh_nbr, mesh_coeff, ps, u = _setup(cfg)
    locate = cell_lookup(mesh)
    nc, n, d = mesh.n, cfg.n, cfg.d
    n_u = nc + n
    eng = _ps.ParticleEngine(
        np.concatenate([mesh.centers(), ps.pos], axis=0),
        np.ones((n_u,), np.float32),
        plan=hplan,
        n_anchor=nc,
        node_threshold=cfg.node_threshold,
        capacity=2 * n_u,
        bucket_size=cfg.bucket_size,
        max_depth=cfg.engine_max_depth,
    )
    plan_cache = _halo.PlanCache()
    sh_put = None

    st = ParticleSimStats()
    st.n_cells = nc
    pos, vel, mass = ps.pos, ps.vel, ps.mass
    U_dev = None
    prev_plan = None
    quality_args = None
    part_by_slot = np.full((eng.rp.capacity,), -1, np.int64)

    for t in range(cfg.events):
        st.events += 1
        if U_dev is not None:
            host_U = _ia.unpack_rows(prev_plan, U_dev, n_u)
            u = host_U[:nc, 0]
            pos = host_U[nc:, 1:1 + d]
            vel = host_U[nc:, 1 + d:1 + 2 * d]
        coupled_event = bool(cfg.couple_every and t % cfg.couple_every == 0 and t > 0)
        if coupled_event:
            u, vel = apply_coupling(u, vel, mass, locate(pos), cfg.kappa, cfg.gamma)

        t0 = time.perf_counter()
        pair = _ia.cutoff_neighbors(pos, cfg.radius)
        st.neighbor_s += time.perf_counter() - t0
        nbr, coeff = union_tables(mesh_nbr, mesh_coeff, pair)
        st.k_max = max(st.k_max, nbr.shape[1])
        w_p = _degree_weights(pair)
        w = np.concatenate([np.ones((nc,), np.float32), w_p])

        t0 = time.perf_counter()
        ncross = 0
        if cfg.reregister_every and t % cfg.reregister_every == 0 and t > 0:
            ncross = eng.reregister(pos, w_p)
        eng.update_weights(w)
        if driver == "incremental":
            eng.step()
        else:
            eng.rebuild()
        st.engine_s += time.perf_counter() - t0

        part = eng.partition()
        had_prev = part_by_slot[eng.slots] >= 0
        changed = bool((part_by_slot[eng.slots][had_prev] != part[had_prev]).any())
        if changed:
            st.repartition_events += 1
        part_by_slot[:] = -1
        part_by_slot[eng.slots] = part

        plan = _halo.build_halo_plan(
            eng.slots, part, nbr, coeff,
            hierarchy=hplan, weights=w, with_metrics=False,
            cache=plan_cache, topo_token=(eng.rp.topology_version, t),
        )
        st.plan_build_s += plan.metrics["PlanBuildSeconds"]
        quality_args = (part, nbr, w)
        hargs = _st.halo_args(jax_mesh, plan)
        isp = np.arange(n_u) >= nc
        if sh_put is None:
            sh_put = NamedSharding(jax_mesh, P(plan.axes))
        isp_dev = jax.device_put(
            jnp.asarray(_ia.pack_rows(plan, isp, fill=False)), sh_put
        )

        host_U = _host_state(u, pos, vel, mass, nc, n, d)
        if U_dev is None or ncross or coupled_event:
            U_dev = _ia.put_rows(jax_mesh, plan, host_U)
        elif changed or driver == "rebuild":
            mv = _halo.build_move_plan(
                prev_plan, plan, hierarchy=hplan, full=driver == "rebuild",
                cache=plan_cache,
            )
            st.plan_build_s += mv.metrics["PlanBuildSeconds"]
            t0 = time.perf_counter()
            U_dev = jax.block_until_ready(
                _ia.move_rows(jax_mesh, mv, prev_plan, U_dev)
            )
            st.move_s += time.perf_counter() - t0
            mig = mv.migration
            st.moved_total += int(mig.total_moved)
            st.moved_inter_node += int(getattr(mig, "inter_moved", 0))
            if mv.kind == "device":
                st.node_local_moves += 1
        elif plan.cap != prev_plan.cap:
            U_dev = _ia.put_rows(jax_mesh, plan, host_U)

        t0 = time.perf_counter()
        U_dev = jax.block_until_ready(pic_steps(
            jax_mesh, plan, U_dev, isp_dev, hargs,
            cfg.substeps, cfg.dt, cfg.radius, use_pallas=use_pallas,
        ))
        st.force_s += time.perf_counter() - t0
        prev_plan = plan

    st.registration_events = eng.registrations
    st.crossers_total = eng.crossers_total
    st.intra_reslices = eng.rp.stats.intra_reslices
    st.inter_reslices = eng.rp.stats.inter_reslices
    st.rebuilds = eng.rp.stats.rebuilds
    st.plan_cache_hits = plan_cache.stats.halo_hits + plan_cache.stats.move_hits
    st.plan_cache_misses = (
        plan_cache.stats.halo_misses + plan_cache.stats.move_misses
    )
    st.halo_metrics = dict(prev_plan.metrics)
    if quality_args is not None:
        qp, qn, qw = quality_args
        st.halo_metrics.update(
            _halo.plan_quality_metrics(qp, qn, prev_plan.num_parts, weights=qw)
        )
    host_U = _ia.unpack_rows(prev_plan, U_dev, n_u)
    out = _ps.ParticleSet(
        pos=host_U[nc:, 1:1 + d], vel=host_U[nc:, 1 + d:1 + 2 * d], mass=mass
    )
    return host_U[:nc, 0], out, st
