"""End-to-end AMR simulation: the first consumer that closes the loop
partitioner -> repartition -> migration -> sharding -> metrics.

A moving load feature drives the adaptive mesh (refine/coarsen) and the
per-cell cost field; the `HierarchicalRepartitioner` (paper Alg. 3)
re-slices as the feature moves; `repro.core.migration`-accounted move
plans carry the cell state to its new owners on device; the compiled
halo plans execute the distributed heat stencil between events.

The trajectory (mesh sequence, neighbor tables, coefficients, weights,
transfer maps) is a pure function of the config — built ONCE and shared
by every backend — so the single-device reference and the distributed
runs integrate the *identical* discrete system and their fields are
bitwise comparable at every event boundary.

Two distributed drivers, the benchmark's comparison axis:

* ``driver="incremental"`` — ``engine.step()``: the Alg. 3 credit
  trigger answers drift with (mostly intra-node) re-slices; state moves
  are moved-rows-only, over a single intra-node hop whenever the
  level-aware migration plan certifies zero inter-node movement.
* ``driver="rebuild"`` — ``engine.rebuild()`` every event plus a full
  redistribute (every row staged through the exchange), the cold path
  the paper's incremental economics are measured against.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from repro.mesh import amr as _amr
from repro.mesh import halo as _halo


@dataclass(frozen=True)
class SimConfig:
    d: int = 2
    base_level: int = 3
    max_level: int = 5
    events: int = 12            # outer timesteps (weight drift per event)
    amr_every: int = 4          # refine/coarsen every k-th event
    substeps: int = 2           # stencil sweeps per event
    # feature path (dim 0 walk; confine [x0, x1] to one node's curve span
    # to exercise the provably node-local regime)
    x0: float = 0.15
    x1: float = 0.85
    amp: float = 4.0
    sigma: float = 0.12
    r_refine: float = 0.15
    r_coarsen: float = 0.30
    # engine knobs
    bucket_size: int = 8
    engine_max_depth: int = 10
    node_threshold: float = 1.20
    dt_safety: float = 0.2


@dataclass(frozen=True)
class Event:
    t: int
    center: np.ndarray
    mesh: _amr.AMRMesh
    nbr: np.ndarray
    coeff: np.ndarray
    weights: np.ndarray
    transfer: "_amr.Transfer | None"   # None: same cells as previous event


def build_trajectory(cfg: SimConfig) -> list[Event]:
    """The mesh/load schedule both backends integrate (deterministic)."""
    mesh = _amr.uniform_mesh(cfg.d, cfg.base_level, cfg.max_level)
    dt = _amr.stable_dt(0.5 ** cfg.max_level, cfg.dt_safety) / max(cfg.d, 2) * 2
    events: list[Event] = []
    denom = max(cfg.events - 1, 1)
    nbr = coeff = None
    for t in range(cfg.events):
        c = _amr.feature_center(t / denom, cfg.d, x0=cfg.x0, x1=cfg.x1)
        transfer = None
        if t > 0 and cfg.amr_every and t % cfg.amr_every == 0:
            ref, coar = _amr.adapt_masks(
                mesh, c, r_refine=cfg.r_refine, r_coarsen=cfg.r_coarsen
            )
            mesh, transfer = _amr.refine_coarsen(mesh, ref, coar)
        if transfer is not None or nbr is None:
            # the adjacency and coefficients depend only on the mesh —
            # recompute them only when the cells actually changed
            nbr = _amr.face_neighbors(mesh)
            coeff = _amr.stencil_coeffs(mesh, nbr, dt)
        w = _amr.feature_weights(mesh.centers(), c, amp=cfg.amp, sigma=cfg.sigma)
        events.append(Event(t, c, mesh, nbr, coeff, w, transfer))
    return events


def initial_field(mesh: _amr.AMRMesh, cfg: SimConfig) -> np.ndarray:
    """A heat blob at the feature's starting position."""
    c = _amr.feature_center(0.0, cfg.d, x0=cfg.x0, x1=cfg.x1)
    d2 = np.sum((mesh.centers().astype(np.float64) - c[None, :]) ** 2, axis=1)
    return np.exp(-d2 / 0.02).astype(np.float32)


def run_reference(events: list[Event], u0: np.ndarray, substeps: int) -> np.ndarray:
    """Single-device integration of the trajectory (the bitwise oracle)."""
    from repro.mesh import stencil as _st

    u = np.asarray(u0, np.float32)
    for ev in events:
        if ev.transfer is not None:
            u = _amr.apply_transfer(u, ev.transfer)
        u = np.asarray(
            _st.reference_stencil(u, ev.nbr, ev.nbr >= 0, ev.coeff, substeps)
        )
    return u


@dataclass
class SimStats:
    events: int = 0
    amr_events: int = 0
    repartition_events: int = 0     # events whose assignment changed
    intra_reslices: int = 0
    inter_reslices: int = 0
    rebuilds: int = 0
    moved_total: int = 0
    moved_inter_node: int = 0
    node_local_moves: int = 0       # moves executed on the device-axis-only hop
    engine_s: float = 0.0
    move_s: float = 0.0
    stencil_s: float = 0.0
    # host-side plan construction (halo + move), summed from the
    # builders' own PlanBuildSeconds — the cost that bounds how often
    # repartitioning can pay off
    plan_build_s: float = 0.0
    # cross-event plan-cache behavior (repro.mesh.plan_cache): builds
    # served by delta patching / scratch fallbacks / owned rows the
    # patches rewrote (vs n_cells * events a scratch build would touch)
    plan_cache_hits: int = 0
    plan_cache_misses: int = 0
    plan_patched_rows: int = 0
    # per-phase attribution of the sweep, measured once per compiled
    # plan by the single-phase probes (reporting only: the hot loop runs
    # the one fused overlapped program, where interior compute hides
    # behind the in-flight exchange)
    stencil_exchange_s: float = 0.0
    stencil_interior_s: float = 0.0
    stencil_boundary_s: float = 0.0
    cells_final: int = 0
    halo_metrics: dict = field(default_factory=dict)


def run_distributed(
    events: list[Event],
    u0: np.ndarray,
    substeps: int,
    jax_mesh,
    hplan,
    *,
    driver: str = "incremental",
    cfg: SimConfig = SimConfig(),
    phase_probes: bool = False,
    use_pallas: bool = False,
) -> tuple[np.ndarray, SimStats]:
    """Integrate the trajectory on a device mesh under one driver.

    ``hplan`` is the `partitioner.HierarchyPlan`; its ``num_parts`` must
    equal the device count of ``jax_mesh`` (parts name shards). Returns
    the final field in global cell order plus phase timings/accounting.
    ``phase_probes`` additionally attributes sweep walltime to its
    exchange/interior/boundary phases via the single-phase probe
    executors (extra per-event probe calls — reporting, not the gate).
    ``use_pallas`` runs the sweeps' row update through the Pallas stencil
    kernel (bit-equal to the jnp definition, so to ``run_reference``).
    """
    import jax
    import jax.numpy as jnp

    from repro.core import partitioner as _pt
    from repro.core.repartition import HierarchicalRepartitioner
    from repro.mesh import stencil as _st

    if driver not in ("incremental", "rebuild"):
        raise ValueError(f"unknown driver {driver!r}")
    max_n = max(ev.mesh.n for ev in events)
    ev0 = events[0]
    pcfg = _pt.PartitionerConfig(use_tree=True, curve="hilbert")
    rp = HierarchicalRepartitioner(
        jnp.asarray(ev0.mesh.centers()),
        jnp.asarray(ev0.weights),
        plan=hplan,
        cfg=pcfg,
        node_threshold=cfg.node_threshold,
        capacity=2 * max_n,
        bucket_size=cfg.bucket_size,
        max_depth=cfg.engine_max_depth,
    )
    slots = np.arange(ev0.mesh.n, dtype=np.int64)  # from_points fills 0..n-1
    # one plan cache per run: reslice events delta-patch the previous
    # event's construction state instead of rebuilding from scratch;
    # the engine's topology_version keys the AMR-sensitive tier
    plan_cache = _halo.PlanCache()

    st = SimStats()
    u_host = np.asarray(u0, np.float32)
    u_dev = None
    prev_plan: "_halo.HaloPlan | None" = None
    prev_args = None
    prev_n = ev0.mesh.n
    quality_args = None   # (part, nbr, weights) of the last-built plan
    # per-slot view of the previous assignment: slots survive AMR events,
    # so "did the partition change" is answerable across cell rebirths
    part_by_slot = np.full((rp.capacity,), -1, np.int64)

    for ev in events:
        st.events += 1
        if ev.transfer is not None:
            st.amr_events += 1
            # state comes home once per AMR event (cells change identity)
            if u_dev is not None:
                u_host = prev_plan.unpack_cells(np.asarray(u_dev), prev_n)
            u_host = _amr.apply_transfer(u_host, ev.transfer)
            died = slots[ev.transfer.died_idx]
            if died.size:
                rp.delete(jnp.asarray(died))
            slots_new = np.full((ev.mesh.n,), -1, np.int64)
            kept = ~ev.transfer.born
            slots_new[kept] = slots[ev.transfer.src[kept, 0]]
            born_idx = np.nonzero(ev.transfer.born)[0]
            if born_idx.size:
                got = rp.insert(
                    jnp.asarray(ev.mesh.centers()[born_idx]),
                    jnp.asarray(ev.weights[born_idx]),
                )
                slots_new[born_idx] = np.asarray(got)
            slots = slots_new
            u_dev = None  # relayout from host below

        # --- engine: weights drift, Alg. 3 answers ------------------------
        t0 = time.perf_counter()
        rp.update_weights(jnp.asarray(ev.weights), slot_ids=jnp.asarray(slots))
        if driver == "incremental":
            rp.step()
        else:
            rp.rebuild()
        st.engine_s += time.perf_counter() - t0

        part_cells = rp.partition_of(slots)
        # changed = any surviving slot owned by a different part than at
        # the previous event (slots are the stable identity, so this is
        # well-defined across AMR rebirths too)
        had_prev = part_by_slot[slots] >= 0
        changed = bool((part_by_slot[slots][had_prev] != part_cells[had_prev]).any())
        if changed:
            st.repartition_events += 1
        part_by_slot[:] = -1
        part_by_slot[slots] = part_cells
        if ev.transfer is None and not changed and prev_plan is not None:
            # same cells, same assignment: the compiled plan (and its
            # device-resident tables) is identical — reuse it instead of
            # re-running the host-side plan construction. Its quality
            # metrics keep the weights of the event that built it.
            plan, args = prev_plan, prev_args
        else:
            # hot path: skip the O(n*K) quality report — the loop never
            # reads it; the final report is recovered once after the loop
            plan = _halo.build_halo_plan(
                slots, part_cells, ev.nbr, ev.coeff,
                hierarchy=hplan, weights=ev.weights, with_metrics=False,
                cache=plan_cache, topo_token=rp.topology_version,
            )
            st.plan_build_s += plan.metrics["PlanBuildSeconds"]
            quality_args = (part_cells, ev.nbr, ev.weights)
            args = _st.halo_args(jax_mesh, plan)

        # --- state placement ---------------------------------------------
        if u_dev is None:
            u_dev = _st.put_state(jax_mesh, plan, u_host)
        else:
            if changed or driver == "rebuild":
                mv = _halo.build_move_plan(
                    prev_plan, plan, hierarchy=hplan, full=driver == "rebuild",
                    cache=plan_cache,
                )
                st.plan_build_s += mv.metrics["PlanBuildSeconds"]
                t0 = time.perf_counter()
                u_dev = jax.block_until_ready(
                    _st.move_state(jax_mesh, mv, prev_plan, u_dev)
                )
                st.move_s += time.perf_counter() - t0
                mig = mv.migration
                st.moved_total += int(mig.total_moved)
                st.moved_inter_node += int(getattr(mig, "inter_moved", 0))
                if mv.kind == "device":
                    st.node_local_moves += 1
            elif plan.cap != prev_plan.cap:
                # same assignment, rounded capacity drifted: repack locally
                u_dev = _st.put_state(
                    jax_mesh, plan, prev_plan.unpack_cells(np.asarray(u_dev), prev_n)
                )

        # --- stencil sweeps ------------------------------------------------
        if phase_probes:
            ph = _st.stencil_phase_times(jax_mesh, plan, u_dev, args)
            st.stencil_exchange_s += substeps * ph["exchange"]
            st.stencil_interior_s += substeps * ph["interior"]
            st.stencil_boundary_s += substeps * ph["boundary"]
        t0 = time.perf_counter()
        u_dev = jax.block_until_ready(
            _st.stencil_steps(jax_mesh, plan, u_dev, args, substeps, use_pallas=use_pallas)
        )
        st.stencil_s += time.perf_counter() - t0

        prev_plan, prev_args, prev_n = plan, args, ev.mesh.n

    st.intra_reslices = rp.stats.intra_reslices
    st.inter_reslices = rp.stats.inter_reslices
    st.rebuilds = rp.stats.rebuilds
    st.plan_cache_hits = plan_cache.stats.halo_hits + plan_cache.stats.move_hits
    st.plan_cache_misses = plan_cache.stats.halo_misses + plan_cache.stats.move_misses
    st.plan_patched_rows = plan_cache.stats.patched_rows
    st.cells_final = prev_n
    st.halo_metrics = dict(prev_plan.metrics)
    if quality_args is not None:
        # recover the quality report the with_metrics=False builds
        # skipped — once, for the final plan, instead of per event
        qp, qn, qw = quality_args
        st.halo_metrics.update(
            _halo.plan_quality_metrics(qp, qn, prev_plan.num_parts, weights=qw)
        )
    return prev_plan.unpack_cells(np.asarray(u_dev), prev_n), st
