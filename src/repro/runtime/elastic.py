"""Elastic scaling: reshape the device mesh and re-place sharded state.

On mesh change (node loss / pool growth), parameters are restored from
the mesh-agnostic checkpoint onto the new mesh (checkpoint.restore with
new shardings). Expert placement and data shards are re-sliced with the
paper's knapsack; the expected migration volume is computed from the
migration plan so the launcher can decide between in-place reshard
(cheap, neighbors only) and full restart.

``ElasticServingController`` wires the pieces around a live
``DistributedQueryEngine``: heartbeats from ``fault_tolerance`` detect a
device-count change, the owner ``HierarchicalRepartitioner`` re-slices
its cached curve hierarchy-aware (``resize`` — no rebuild), and the
engine re-places chunks on a mesh over the surviving devices plus a live
index-version swap. A failure therefore costs one re-slice + one
placement pass, never a cold restart.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass

import numpy as np
import jax

from repro.core import knapsack, migration
from repro.runtime.fault_tolerance import HeartbeatMonitor, reslice_for_stragglers
import jax.numpy as jnp


def viable_mesh_shapes(n_devices: int, *, min_model: int = 1) -> list[tuple[int, int]]:
    """(data, model) factorizations of the surviving device count,
    preferring square-ish meshes (ICI locality)."""
    shapes = []
    for m in range(min_model, n_devices + 1):
        if n_devices % m == 0:
            shapes.append((n_devices // m, m))
    shapes.sort(key=lambda dm: abs(np.log(dm[0] / dm[1])))
    return shapes


def replacement_plan(
    old_parts: np.ndarray, weights: np.ndarray, new_num_parts: int
) -> tuple[np.ndarray, migration.MigrationPlan]:
    """Knapsack re-slice of weighted units onto a new part count.

    The count matrix spans ``max(old_parts.max()+1, new_num_parts)`` so
    the shrink path accounts for every unit leaving a vanished part
    (units are conserved: stay + moved == len(old_parts)). An empty
    ``old_parts`` is a fresh placement — every unit materializes in
    place, the plan moves nothing — instead of crashing on ``max()`` of
    an empty array."""
    old_parts = np.asarray(old_parts)
    new = np.asarray(
        knapsack.slice_weighted_curve(jnp.asarray(weights, jnp.float32), new_num_parts)
    )
    old_p = int(old_parts.max()) + 1 if old_parts.size else 0
    P = max(old_p, new_num_parts)
    plan = migration.migration_plan(old_parts if old_parts.size else new, new, P)
    return new, plan


def estimate_reshard_bytes(plan: migration.MigrationPlan, bytes_per_unit: int) -> int:
    return plan.total_moved * bytes_per_unit


# ---------------------------------------------------------------------------
# Live serving elasticity (paper §V-A under a changing device pool)
# ---------------------------------------------------------------------------

def mesh_from_devices(
    devices, shape: tuple[int, ...], axes: tuple[str, ...]
) -> jax.sharding.Mesh:
    """Mesh over an explicit device subset (survivors after a failure, or
    a grown pool) — `launch.mesh.make_mesh` always takes the default
    device order, which a shrunken pool no longer matches."""
    arr = np.asarray(devices, dtype=object).reshape(shape)
    return jax.sharding.Mesh(
        arr, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


@dataclass(frozen=True)
class ReshardEvent:
    """One completed elastic reshard, for the launcher's accounting."""

    n_before: int
    n_after: int
    mesh_shape: tuple[int, int]     # (num_nodes, devices_per_node)
    moved_units: int                # migration-plan volume of the re-slice
    seconds: float
    rebuilds_during: int            # MUST stay 0: elastic != cold restart


class ElasticServingController:
    """Heartbeat-driven elastic reshard around a serving engine.

    >>> ctl = ElasticServingController(hrp, eng, devices=jax.devices())
    >>> ctl.beat(worker=3, now=t)            # workers report liveness
    >>> ctl.check(now=t + 120.0)             # failed workers -> shrink
    >>> ctl.apply_device_change(jax.devices())   # explicit growth

    ``owner`` is a ``HierarchicalRepartitioner`` (hierarchy-aware
    re-slice via ``resize``; its tree-backed index serves on the mesh
    through the engine's host-side keying) or a flat ``Repartitioner``
    (``resize(n)``, 1-D mesh). On a device-count change the controller:

    1. picks the square-ish (nodes, devices_per_node) factorization of
       the survivor count (`viable_mesh_shapes`);
    2. ``owner.resize(...)`` — knapsack re-slice of the cached curve,
       bumping ``index_version`` (no tree/key/sort rebuild);
    3. ``engine.reshard(mesh_from_devices(...))`` + ``maybe_refresh`` —
       chunks re-place on the survivors and the refreshed index swaps in
       live.
    """

    def __init__(
        self,
        owner,
        engine,
        devices=None,
        *,
        heartbeat_timeout: float = 60.0,
        straggler_factor: float = 2.0,
    ):
        self.owner, self.engine = owner, engine
        self.devices = list(devices if devices is not None else jax.devices())
        self.heartbeat_timeout = float(heartbeat_timeout)
        self.straggler_factor = float(straggler_factor)
        self.monitor = self._fresh_monitor()
        self.events: list[ReshardEvent] = []

    def _fresh_monitor(self) -> HeartbeatMonitor:
        return HeartbeatMonitor(
            len(self.devices),
            timeout=self.heartbeat_timeout,
            straggler_factor=self.straggler_factor,
        )

    def beat(self, worker: int, now: float, step_time: float | None = None) -> None:
        self.monitor.beat(worker, now, step_time)

    def throughput(self) -> np.ndarray:
        """(workers,) relative speed from recent heartbeat step times
        (1/mean step time; workers without samples get the median speed)
        — the input `fault_tolerance.reslice_for_stragglers` expects."""
        speed = np.zeros(len(self.devices))
        for w, ts in self.monitor.step_times.items():
            if ts and 0 <= w < speed.shape[0]:
                speed[w] = 1.0 / max(float(np.mean(ts[-5:])), 1e-12)
        default = float(np.median(speed[speed > 0])) if (speed > 0).any() else 1.0
        speed[speed == 0] = default
        return speed

    def check(self, now: float) -> ReshardEvent | None:
        """Shrink to the surviving devices iff the monitor reports
        failures. With every worker alive, slow-but-responsive workers
        (stragglers) instead trigger a weighted re-cut of the serving
        chunk layout (:meth:`mitigate_stragglers`) — no mesh change, no
        ReshardEvent. Returns None when no failure fired."""
        failed = set(self.monitor.failed(now))
        if not failed:
            self.mitigate_stragglers()
            return None
        survivors = [d for i, d in enumerate(self.devices) if i not in failed]
        return self.apply_device_change(survivors)

    def mitigate_stragglers(self) -> np.ndarray | None:
        """Straggler-driven weighted re-slice of the serving layout.

        When the heartbeat monitor reports stragglers, feed the measured
        per-worker speeds (:meth:`throughput`) into
        `fault_tolerance.reslice_for_stragglers` over the index's
        directory buckets — each bucket weighted by its row count plus
        its decayed hit traffic — and re-cut the engine's chunk
        placement at the resulting bucket boundaries
        (``engine.set_chunk_targets``): slow shards hold fewer and
        colder rows, fast shards more, converging to
        proportional-throughput sharding under repeated observations.
        Cuts stay run-aligned inside the engine, so answers are
        bit-equal — only the load shares move. Returns the per-bucket
        shard assignment, or None when there are no stragglers."""
        if not self.monitor.stragglers():
            return None
        tp = self.throughput()
        idx = self.engine.index
        starts = np.asarray(idx.bucket_starts, np.int64)
        w = np.diff(starts).astype(np.float64) + self.engine.bucket_hits
        assignment = reslice_for_stragglers(np.maximum(w, 1e-9), tp)
        # first bucket of each shard s in 1..W-1 marks that shard's cut
        cuts = starts[np.searchsorted(assignment, np.arange(1, tp.shape[0]))]
        self.engine.set_chunk_targets(cuts)
        return assignment

    def apply_device_change(self, devices) -> ReshardEvent:
        """Re-slice + re-place + live swap onto an explicit device list
        (shrink or growth). Proves the no-cold-restart property in the
        returned event: ``rebuilds_during`` is the owner's rebuild-count
        delta across the whole operation."""
        devices = list(devices)
        if not devices:
            raise ValueError("cannot reshard onto zero devices")
        t0 = time.monotonic()
        rebuilds0 = self.owner.stats.rebuilds
        n = len(devices)
        nodes, dpn = viable_mesh_shapes(n)[0]
        plan = getattr(self.owner, "plan", None)
        if plan is not None:  # hierarchical: resize takes a HierarchyPlan
            new_plan = dataclasses.replace(
                plan, num_nodes=nodes, devices_per_node=dpn
            )
            step = self.owner.resize(new_plan)
            mesh = mesh_from_devices(
                devices, (nodes, dpn), (new_plan.node_axis, new_plan.device_axis)
            )
            self.engine.reshard(mesh, (new_plan.node_axis, new_plan.device_axis))
        else:
            step = self.owner.resize(n)
            axis = self.engine.axis if isinstance(self.engine.axis, str) else "data"
            mesh = mesh_from_devices(devices, (n,), (axis,))
            self.engine.reshard(mesh, axis)
        self.engine.maybe_refresh(self.owner)
        event = ReshardEvent(
            n_before=len(self.devices),
            n_after=n,
            mesh_shape=(nodes, dpn),
            moved_units=int(step.plan.total_moved),
            seconds=time.monotonic() - t0,
            rebuilds_during=self.owner.stats.rebuilds - rebuilds0,
        )
        self.devices = devices
        self.monitor = self._fresh_monitor()
        self.events.append(event)
        return event
