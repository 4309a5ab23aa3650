"""End-to-end N-body simulation: the particle consumer that closes the
loop partitioner -> repartition -> migration -> sharding -> metrics.

A short-range force loop (paper §V-C) driven exactly like
`repro.mesh.simulate.run_distributed`: per event the cutoff interaction
table is rebuilt from the current positions, crossers re-register
through the engine's insert/delete path, the `HierarchicalRepartitioner`
answers load drift through the Alg. 3 trigger, a compiled interaction
plan replaces the halo plan, and a multi-column move plan migrates
position+velocity+mass under ONE routing (``interact.move_rows``).
Between events the leapfrog executor runs ``substeps`` kick-drift
sweeps, each a ghost-position exchange and then every owned row.

Bit-equality: :func:`run_reference` and :func:`run_distributed` start
from the same `state.random_particles` draw and rebuild the interaction
table with the same :func:`interact.cutoff_neighbors` call per event —
positions agree bitwise by induction, so the tables agree, so the
trajectories agree (``np.array_equal`` on final position AND velocity),
across registration, re-slice and rebuild events alike. That gate is
what ``bench_particles`` holds.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from repro.mesh import halo as _halo
from repro.particles import interact as _ia
from repro.particles import state as _ps


@dataclass(frozen=True)
class ParticleSimConfig:
    d: int = 2
    n: int = 512
    events: int = 12            # outer timesteps (table + partition refresh)
    substeps: int = 4           # kick-drift sweeps per event
    dt: float = 0.01
    radius: float = 0.12        # interaction cutoff (unit box)
    seed: int = 0
    v0: float = 0.8             # initial velocity scale
    margin: float = 0.1         # initial wall clearance
    # crossers re-register every k-th event: the off events exercise the
    # pure device-side migration path (slot sets unchanged), the on
    # events the engine's insert/delete registration path
    reregister_every: int = 2
    # engine knobs
    bucket_size: int = 8
    engine_max_depth: int = 10
    node_threshold: float = 1.20


def initial_particles(cfg: ParticleSimConfig) -> _ps.ParticleSet:
    return _ps.random_particles(
        cfg.n, cfg.d, seed=cfg.seed, v0=cfg.v0, margin=cfg.margin
    )


def _degree_weights(nbr: np.ndarray) -> np.ndarray:
    """Per-particle cost model: 1 + interaction degree (the pair loop's
    actual work), the load the Alg. 3 trigger meters."""
    return (1.0 + (nbr >= 0).sum(axis=1)).astype(np.float32)


def run_reference(
    cfg: ParticleSimConfig, *, use_pallas: bool = False
) -> _ps.ParticleSet:
    """Single-device integration of the schedule (the bitwise oracle)."""
    ps = initial_particles(cfg)
    pos, vel = ps.pos, ps.vel
    for _ in range(cfg.events):
        nbr = _ia.cutoff_neighbors(pos, cfg.radius)
        x, v = _ia.reference_leapfrog(
            pos, vel, ps.mass, nbr, cfg.substeps, cfg.dt, cfg.radius,
            use_pallas=use_pallas,
        )
        pos, vel = np.asarray(x), np.asarray(v)
    return _ps.ParticleSet(pos=pos, vel=vel, mass=ps.mass)


@dataclass
class ParticleSimStats:
    events: int = 0
    repartition_events: int = 0     # events whose assignment changed
    registration_events: int = 0    # events with >= 1 crosser re-registered
    crossers_total: int = 0
    intra_reslices: int = 0
    inter_reslices: int = 0
    rebuilds: int = 0
    moved_total: int = 0
    moved_inter_node: int = 0
    node_local_moves: int = 0
    engine_s: float = 0.0
    move_s: float = 0.0
    force_s: float = 0.0            # leapfrog substep walltime
    neighbor_s: float = 0.0         # host cutoff-table construction
    plan_build_s: float = 0.0
    plan_cache_hits: int = 0
    plan_cache_misses: int = 0
    k_max: int = 0                  # widest interaction table seen
    n_cells: int = 0                # coupled runs: anchor mesh cells
    halo_metrics: dict = field(default_factory=dict)


def run_distributed(
    cfg: ParticleSimConfig,
    jax_mesh,
    hplan,
    *,
    driver: str = "incremental",
    use_pallas: bool = False,
) -> tuple[_ps.ParticleSet, ParticleSimStats]:
    """Integrate the schedule on a device mesh under one driver.

    ``driver="incremental"`` answers drift with Alg. 3 re-slices and
    moved-rows-only migrations; ``driver="rebuild"`` forces a full
    engine rebuild plus a full redistribute every event — the cold
    baseline the incremental economics are gated against.
    """
    import jax
    import jax.numpy as jnp

    if driver not in ("incremental", "rebuild"):
        raise ValueError(f"unknown driver {driver!r}")
    ps = initial_particles(cfg)
    n, d = ps.n, cfg.d
    eng = _ps.ParticleEngine(
        ps.pos, np.ones((n,), np.float32),
        plan=hplan,
        node_threshold=cfg.node_threshold,
        capacity=2 * n,
        bucket_size=cfg.bucket_size,
        max_depth=cfg.engine_max_depth,
    )
    plan_cache = _halo.PlanCache()

    st = ParticleSimStats()
    pos, vel, mass = ps.pos, ps.vel, ps.mass
    U_dev = None                    # (S*cap, 2d+1) device state [x | v | m]
    prev_plan: "_halo.HaloPlan | None" = None
    quality_args = None
    part_by_slot = np.full((eng.rp.capacity,), -1, np.int64)

    for t in range(cfg.events):
        st.events += 1
        if U_dev is not None:
            # host mirror of the device state (same bits) for the table
            # build, crossing detection and any relayout below
            host_U = _ia.unpack_rows(prev_plan, U_dev, n)
            pos, vel = host_U[:, :d], host_U[:, d:2 * d]

        t0 = time.perf_counter()
        nbr = _ia.cutoff_neighbors(pos, cfg.radius)
        st.neighbor_s += time.perf_counter() - t0
        st.k_max = max(st.k_max, nbr.shape[1])
        w = _degree_weights(nbr)

        # --- engine: re-register crossers, drift weights, Alg. 3 -----------
        t0 = time.perf_counter()
        ncross = 0
        if cfg.reregister_every and t % cfg.reregister_every == 0 and t > 0:
            ncross = eng.reregister(pos, w)
        eng.update_weights(w)
        if driver == "incremental":
            eng.step()
        else:
            eng.rebuild()
        st.engine_s += time.perf_counter() - t0

        part = eng.partition()
        had_prev = part_by_slot[eng.slots] >= 0
        changed = bool(
            (part_by_slot[eng.slots][had_prev] != part[had_prev]).any()
        )
        if changed:
            st.repartition_events += 1
        part_by_slot[:] = -1
        part_by_slot[eng.slots] = part

        # the table changes every event (particles moved), so the plan is
        # rebuilt per event; the per-event token keeps the cache's
        # topology tier honest while move plans share its owner gather
        plan = _ia.build_interact_plan(
            eng.slots, part, nbr,
            hierarchy=hplan, weights=w, with_metrics=False,
            cache=plan_cache, topo_token=(eng.rp.topology_version, t),
        )
        st.plan_build_s += plan.metrics["PlanBuildSeconds"]
        quality_args = (part, nbr, w)
        args = _ia.interact_args(jax_mesh, plan)

        # --- state placement: one migration carries every payload ----------
        host_U = np.concatenate(
            [pos, vel, mass[:, None]], axis=1
        ).astype(np.float32)
        if U_dev is None or ncross:
            # registration events change slot ids — relayout from the host
            # mirror (bit-identical values, rows only re-home)
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

        # --- leapfrog substeps ---------------------------------------------
        x_dev = U_dev[:, :d]
        v_dev = U_dev[:, d:2 * d]
        m_dev = U_dev[:, 2 * d]
        mgh_dev = _ia.exchange_rows(jax_mesh, plan, m_dev, args)
        t0 = time.perf_counter()
        x_dev, v_dev = jax.block_until_ready(_ia.leapfrog_steps(
            jax_mesh, plan, x_dev, v_dev, m_dev, mgh_dev, args,
            cfg.substeps, cfg.dt, cfg.radius, use_pallas=use_pallas,
        ))
        st.force_s += time.perf_counter() - t0
        U_dev = jnp.concatenate([x_dev, v_dev, m_dev[:, None]], axis=1)
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
    host_U = _ia.unpack_rows(prev_plan, U_dev, n)
    out = _ps.ParticleSet(
        pos=host_U[:, :d], vel=host_U[:, d:2 * d], mass=mass
    )
    return out, st
