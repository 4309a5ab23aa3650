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
bitwise comparable at every event boundary. A cell carries one field or
V fields (n, V), all through transfer, placement, exchange, sweep and
moves.

:func:`miniamr_events` builds the events of Mantevo miniAMR's
block-structured refinement around moving spheroid surfaces
(``mesh.amr.miniamr_adapt``, 7-point coefficients); :class:`DistributedSim`
runs events one at a time, with profiler spans per phase.

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

import dataclasses
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
    # None: same cells as previous event; a tuple: several steps in order
    transfer: "_amr.Transfer | tuple | None"


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


def miniamr_events(objects, t0: float, t1: float, *, d: int = 3, root_level: int,
                   block_bits: int, num_refine: int, block_change: int):
    """The two meshes of miniAMR objects at timesteps ``t0`` and ``t1``
    and the adapts between them, as events: ``(a, b_from_a, b,
    a_from_b)``; ``a`` and ``b`` carry no transfer. Starting from the
    uniform root mesh, the objects are refined around until an adapt
    back and forth reproduces both meshes, cell for cell."""
    mesh = _amr.uniform_mesh(d, root_level, root_level + num_refine)
    adapt = lambda m, t: _amr.miniamr_adapt(
        m, objects, t, block_bits=block_bits, block_change=block_change)
    a, _ = adapt(mesh, t0)
    for _ in range(num_refine + 2):
        b, t_ab = adapt(a, t1)
        a2, t_ba = adapt(b, t0)
        if _amr.same_cells(a, a2) and _amr.same_cells(b, adapt(a2, t1)[0]):
            break
        a = a2
    else:
        raise RuntimeError("miniAMR adapts between the two timesteps do not settle")

    def event(m, t, tr):
        nbr = _amr.face_neighbors(m)
        return Event(t, np.zeros((d,)), m, nbr, _amr.miniamr_coeffs(m, nbr),
                     np.ones((m.n,), np.float32), tr)

    ea, eb = event(a, t0, None), event(b, t1, None)
    return (ea, dataclasses.replace(eb, transfer=t_ab), eb,
            dataclasses.replace(ea, transfer=t_ba))


def initial_field(mesh: _amr.AMRMesh, cfg: SimConfig) -> np.ndarray:
    """A heat blob at the feature's starting position."""
    c = _amr.feature_center(0.0, cfg.d, x0=cfg.x0, x1=cfg.x1)
    d2 = np.sum((mesh.centers().astype(np.float64) - c[None, :]) ** 2, axis=1)
    return np.exp(-d2 / 0.02).astype(np.float32)


def run_reference(events: list[Event], u0: np.ndarray, substeps: int) -> np.ndarray:
    """Single-device integration of the trajectory (the bitwise oracle).
    ``u0`` is one field (n,) or V fields (n, V)."""
    from repro.mesh import stencil as _st

    u = np.asarray(u0, np.float32)
    for ev in events:
        if ev.transfer is not None:
            u = _amr.apply_transfers(u, transfer_steps(ev.transfer))
        u = np.asarray(
            _st.reference_stencil(u, ev.nbr, ev.nbr >= 0, ev.coeff, substeps)
        )
    return u


def transfer_steps(transfer) -> tuple:
    """An event's transfer as a sequence of steps (a miniAMR adapt makes
    several; a plain refine/coarsen one)."""
    return transfer if isinstance(transfer, tuple) else (transfer,)


@dataclass
class SimStats:
    events: int = 0
    amr_events: int = 0
    repartition_events: int = 0     # events whose assignment changed
    intra_reslices: int = 0
    inter_reslices: int = 0
    rebuilds: int = 0
    moved_total: int = 0            # cells moved between chips, all moves
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
    # the current plan's exchange: bytes one stage's halo exchange sends
    # from the busiest chip (real entries of every hop, all fields), and
    # ghost cells over all chips
    halo_bytes_stage: int = 0
    ghost_cells: int = 0
    # the current plan's finer-row list: the most rows one chip lists
    # (against the padded ``fcap`` in ``DistributedSim.floors``)
    finer_rows: int = 0
    checksums: int = 0              # global checksums taken
    cells_final: int = 0
    halo_metrics: dict = field(default_factory=dict)


# the room a padded plan capacity leaves over what its first plan needed
SHAPE_SLACK = 0.1


def _span(name: str):
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


class DistributedSim:
    """The distributed simulation as a stepper: :meth:`advance` runs one
    event (one timestep) on a device mesh; :func:`run_distributed` runs
    a trajectory with it.

    Per event, each phase in a profiler span:

    * ``mesh.adapt`` (an event with a transfer): the fields come home,
      take the transfer on the host, and go back to the chips of their
      cells' parents (new cells start where their parent lived);
    * ``mesh.engine``: engine deletes and inserts of the cells that died
      and were born, the weights, and ``step()`` (``rebuild()`` for the
      rebuild driver); the ``repartition.*`` spans nest inside;
    * ``mesh.plan``: halo plan and move plan build (host);
    * ``mesh.move``: the state move to the new owners (device);
    * ``mesh.sweep``: the stencil stages (device);
    * ``mesh.checksum``: a per-field global sum, read on the host;
    * ``mesh.timestep``: the whole event.

    Every plan is padded to capacities that only grow, each
    ``SHAPE_SLACK`` more than first needed and rounded to 128, so that
    later plans of a similar mesh reuse the compiled executors (padded
    rows and lanes carry nothing: the results are bit-equal).
    """

    def __init__(
        self,
        ev0: Event,
        u0: np.ndarray,
        jax_mesh,
        hplan,
        *,
        driver: str = "incremental",
        cfg: SimConfig = SimConfig(),
        capacity: int | None = None,
        use_pallas: bool = False,
    ):
        import jax.numpy as jnp

        from repro.core import partitioner as _pt
        from repro.core.repartition import HierarchicalRepartitioner

        if driver not in ("incremental", "rebuild"):
            raise ValueError(f"unknown driver {driver!r}")
        self.jax_mesh, self.hplan, self.driver = jax_mesh, hplan, driver
        self.use_pallas = bool(use_pallas)
        self.floors: dict = {}
        pcfg = _pt.PartitionerConfig(use_tree=True, curve="hilbert")
        self.rp = HierarchicalRepartitioner(
            jnp.asarray(ev0.mesh.centers()),
            jnp.asarray(ev0.weights),
            plan=hplan,
            cfg=pcfg,
            node_threshold=cfg.node_threshold,
            capacity=capacity or 2 * ev0.mesh.n,
            bucket_size=cfg.bucket_size,
            max_depth=cfg.engine_max_depth,
        )
        self.slots = np.arange(ev0.mesh.n, dtype=np.int64)  # from_points fills 0..n-1
        # one plan cache per run: reslice events delta-patch the previous
        # event's construction state instead of rebuilding from scratch;
        # the engine's topology_version keys the AMR-sensitive tier
        self.plan_cache = _halo.PlanCache()
        self.st = SimStats()
        self.u_host = np.asarray(u0, np.float32)
        self.u_dev = None
        self.plan = None       # the current halo plan, as built
        self.xplan = None      # ... and as executed (padded)
        self.args = None
        self.n = ev0.mesh.n
        self.quality_args = None   # (part, nbr, weights) of the last-built plan
        # per-slot view of the previous assignment: slots survive AMR
        # events, so "did the partition change" is answerable across
        # cell rebirths
        self.part_by_slot = np.full((self.rp.capacity,), -1, np.int64)
        self.checksums: list = []   # (event index, stage, (V,) float32 sums)

    # -- shape floors ---------------------------------------------------------

    def _floor(self, key, need):
        """Raise the floor ``key`` to cover ``need`` (an int or a tuple)."""
        cur = self.floors.get(key)
        grow = lambda x: _halo._roundup(int(np.ceil(x * (1.0 + SHAPE_SLACK))), 128)
        if isinstance(need, tuple):
            cur = cur or (0,) * len(need)
            new = tuple(c if c >= x else grow(x) for c, x in zip(cur, need))
        else:
            new = cur if cur is not None and cur >= need else grow(need)
        self.floors[key] = new
        return new

    def _pad(self, plan):
        c = plan.caps
        f = {k: self._floor(k, c[k]) for k in ("cap", "gcap", "fcap")}
        f["stages"] = self._floor(("stages", len(c["stages"])), c["stages"])
        return plan.padded(f)

    def _pad_move(self, mv, old_x, new_x):
        caps = self._floor(("move", mv.kind), tuple(s.cap for s in mv.stages))
        return mv.padded(old_x.cap, new_x.cap, caps)

    # -- one event ---------------------------------------------------------------

    def advance(self, ev: Event, substeps: int, *, rebalance: bool = True,
                checksum_every: int = 0) -> None:
        """Run one event: adapt (if ``ev.transfer``), the engine (if
        ``rebalance``; always on the first event), plans and moves, then
        ``substeps`` stencil stages with a global checksum after every
        ``checksum_every``-th (0: none)."""
        with _span("mesh.timestep"):
            self._advance(ev, substeps, rebalance, checksum_every)

    def _advance(self, ev, substeps, rebalance, checksum_every):
        import jax
        import jax.numpy as jnp

        from repro.mesh import stencil as _st

        st, rp = self.st, self.rp
        st.events += 1
        pre = None   # (layout plan, cell fields) the state returns to
        if ev.transfer is not None:
            st.amr_events += 1
            steps = transfer_steps(ev.transfer)
            with _span("mesh.adapt"):
                # state comes home once per AMR event (cells change identity)
                if self.u_dev is not None:
                    self.u_host = self.xplan.unpack_cells(np.asarray(self.u_dev), self.n)
                self.u_host = _amr.apply_transfers(self.u_host, steps)
                src0, died_idx = _amr.lineage(steps, self.n)
                parent = None
                if self.plan is not None:
                    # a new cell starts on the chip its ancestor lived on
                    part_old = np.empty((self.n,), np.int64)
                    rows = self.plan.owned_idx >= 0
                    part_old[self.plan.owned_idx[rows]] = np.nonzero(rows)[0]
                    parent = part_old[_amr.ancestors(steps, self.n)]
            with _span("mesh.engine"):
                died = self.slots[died_idx]
                if died.size:
                    rp.delete(died)
                slots_new = np.full((ev.mesh.n,), -1, np.int64)
                kept = src0 >= 0
                slots_new[kept] = self.slots[src0[kept]]
                born_idx = np.nonzero(~kept)[0]
                if born_idx.size:
                    got = rp.insert(ev.mesh.centers()[born_idx], ev.weights[born_idx])
                    slots_new[born_idx] = np.asarray(got)
                self.slots = slots_new
            if parent is not None:
                pre = parent
            self.u_dev = None
            rebalance = True

        # --- engine: weights drift, Alg. 3 answers ----------------------------
        if rebalance or self.plan is None:
            t0 = time.perf_counter()
            with _span("mesh.engine"):
                rp.update_weights(jnp.asarray(ev.weights), slot_ids=jnp.asarray(self.slots))
                if self.driver == "incremental":
                    rp.step()
                else:
                    rp.rebuild()
                part_cells = rp.partition_of(self.slots)
            st.engine_s += time.perf_counter() - t0
            # changed = any surviving slot owned by a different part than
            # at the previous event (slots are the stable identity, so
            # this is well-defined across AMR rebirths too)
            had_prev = self.part_by_slot[self.slots] >= 0
            changed = bool((self.part_by_slot[self.slots][had_prev]
                            != part_cells[had_prev]).any())
            if changed:
                st.repartition_events += 1
            self.part_by_slot[:] = -1
            self.part_by_slot[self.slots] = part_cells
        else:
            changed = False

        prev_plan, prev_x = self.plan, self.xplan
        if ev.transfer is None and not changed and prev_plan is not None:
            # same cells, same assignment: the compiled plan (and its
            # device-resident tables) is identical — reuse it instead of
            # re-running the host-side plan construction. Its quality
            # metrics keep the weights of the event that built it.
            plan, xplan, args = prev_plan, prev_x, self.args
        else:
            with _span("mesh.plan"):
                # hot path: skip the O(n*K) quality report — the loop
                # never reads it; the final report is recovered once
                plan = _halo.build_halo_plan(
                    self.slots, part_cells, ev.nbr, ev.coeff,
                    hierarchy=self.hplan, weights=ev.weights, with_metrics=False,
                    cache=self.plan_cache, topo_token=rp.topology_version,
                )
                st.plan_build_s += plan.metrics["PlanBuildSeconds"]
                self.quality_args = (part_cells, ev.nbr, ev.weights)
                xplan = self._pad(plan)
                args = _st.halo_args(self.jax_mesh, xplan)
                if pre is not None:
                    # the state returns to its parents' chips, then moves
                    prev_plan = _halo.layout_plan(self.slots, pre, hierarchy=self.hplan)
                    prev_x = self._pad(prev_plan)
            self._stage_stats(xplan, self.u_host if self.u_dev is None else self.u_dev)

        # --- state placement ---------------------------------------------
        if pre is not None:
            with _span("mesh.adapt"):
                self.u_dev = _st.put_state(self.jax_mesh, prev_x, self.u_host)
        if self.u_dev is None:
            self.u_dev = _st.put_state(self.jax_mesh, xplan, self.u_host)
        elif pre is not None or changed or self.driver == "rebuild":
            with _span("mesh.plan"):
                mv = _halo.build_move_plan(
                    prev_plan, plan, hierarchy=self.hplan,
                    full=self.driver == "rebuild", cache=self.plan_cache,
                )
                st.plan_build_s += mv.metrics["PlanBuildSeconds"]
                mv = self._pad_move(mv, prev_x, xplan)
            t0 = time.perf_counter()
            with _span("mesh.move"):
                self.u_dev = jax.block_until_ready(
                    _st.move_state(self.jax_mesh, mv, prev_x, self.u_dev)
                )
            st.move_s += time.perf_counter() - t0
            mig = mv.migration
            st.moved_total += int(mig.total_moved)
            st.moved_inter_node += int(getattr(mig, "inter_moved", 0))
            if mv.kind == "device":
                st.node_local_moves += 1
        elif xplan.cap != prev_x.cap:
            # same assignment, rounded capacity drifted: repack locally
            self.u_dev = _st.put_state(
                self.jax_mesh, xplan, prev_x.unpack_cells(np.asarray(self.u_dev), self.n)
            )
        self.plan, self.xplan, self.args, self.n = plan, xplan, args, ev.mesh.n

        # --- stencil sweeps ------------------------------------------------
        every = checksum_every or substeps
        done = 0
        while done < substeps:
            k = min(every, substeps - done)
            t0 = time.perf_counter()
            with _span("mesh.sweep"):
                self.u_dev = jax.block_until_ready(_st.stencil_steps(
                    self.jax_mesh, xplan, self.u_dev, args, k, use_pallas=self.use_pallas))
            st.stencil_s += time.perf_counter() - t0
            done += k
            if checksum_every and done % checksum_every == 0:
                with _span("mesh.checksum"):
                    sums = np.asarray(_st.checksum(self.jax_mesh, xplan, self.u_dev))
                self.checksums.append((st.events - 1, done, sums))
                st.checksums += 1

    def _stage_stats(self, xplan, u) -> None:
        width = int(np.prod(np.shape(u)[1:]))   # fields per cell
        sent = sum((s.idx >= 0).sum(axis=(1, 2)) for s in xplan.stages)
        self.st.halo_bytes_stage = 4 * width * int(np.max(sent)) if xplan.stages else 0
        self.st.ghost_cells = int((xplan.ghost_fetch >= 0).sum())
        self.st.finer_rows = int((xplan.finer_rows >= 0).sum(axis=1).max())

    def fields(self) -> np.ndarray:
        """The current fields in the current mesh's cell order (host)."""
        if self.u_dev is None:
            return self.u_host
        return self.xplan.unpack_cells(np.asarray(self.u_dev), self.n)

    def finish(self) -> SimStats:
        """Fold the engine's and the plan cache's counters into the stats."""
        st, rp, pc = self.st, self.rp, self.plan_cache
        st.intra_reslices = rp.stats.intra_reslices
        st.inter_reslices = rp.stats.inter_reslices
        st.rebuilds = rp.stats.rebuilds
        st.plan_cache_hits = pc.stats.halo_hits + pc.stats.move_hits
        st.plan_cache_misses = pc.stats.halo_misses + pc.stats.move_misses
        st.plan_patched_rows = pc.stats.patched_rows
        st.cells_final = self.n
        st.halo_metrics = dict(self.plan.metrics)
        if self.quality_args is not None:
            # recover the quality report the with_metrics=False builds
            # skipped — once, for the final plan, instead of per event
            qp, qn, qw = self.quality_args
            st.halo_metrics.update(
                _halo.plan_quality_metrics(qp, qn, self.plan.num_parts, weights=qw)
            )
        return st


def run_distributed(
    events: list[Event],
    u0: np.ndarray,
    substeps: int,
    jax_mesh,
    hplan,
    *,
    driver: str = "incremental",
    cfg: SimConfig = SimConfig(),
    use_pallas: bool = False,
) -> tuple[np.ndarray, SimStats]:
    """Integrate the trajectory on a device mesh under one driver.

    ``hplan`` is the `partitioner.HierarchyPlan`; its ``num_parts`` must
    equal the device count of ``jax_mesh`` (parts name shards). ``u0``
    is one field (n,) or V fields (n, V). Returns the final fields in
    global cell order plus phase timings/accounting.
    ``use_pallas`` runs the sweeps' row update through the Pallas stencil
    kernel (bit-equal to the jnp definition, so to ``run_reference``).
    """
    sim = DistributedSim(
        events[0], u0, jax_mesh, hplan, driver=driver, cfg=cfg,
        capacity=2 * max(ev.mesh.n for ev in events),
        use_pallas=use_pallas,
    )
    for ev in events:
        sim.advance(ev, substeps)
    return sim.fields(), sim.finish()
