"""Distributed stencil execution over compiled halo plans.

The executors here are deliberately dumb: every routing decision was
made on the host when the `repro.mesh.halo` plan was compiled, so the
device programs are pure gathers + fixed-lane ``all_to_all`` hops + one
fused update — jitted ``shard_map`` closures memoized per static shape
signature (the same lru_cache pattern as ``partitioner._reslice_fn``;
shard_map must run under jit or every traced op dispatches as its own
SPMD program).

Each sweep is one form for one field (rows,) or V fields a row (rows,
V): route the owned rows along the plan's hops, fetch the ghosts, then
update every owned row in one pass over ``concat([u, ghosts])``. The
row update is the fused `kernels.ops.stencil_update` (gather + mask +
coeff*(v-u) + K-reduce in one pass; a Pallas kernel, scalar or V-wide
by the rank of ``u``, or the bit-equal jnp definition). The step loop
is a ``fori_loop`` over a *traced* step count, so ONE compiled executor
serves every sweep length — ``steps`` is not part of the cache
signature.

Bit-equality contract: :func:`reference_stencil` (single device, global
cell order) and :func:`stencil_steps` (sharded, owned+ghost layout)
evaluate the SAME per-cell expression — ``u_i += sum_k where(valid,
coeff_ik * (u_nbr - u_i), 0)`` with identical (n, K) coefficient rows,
identical slot order and identical float32 dtype — so a distributed
sweep is bitwise equal to the reference sweep, after any number of
repartition and migration events (``tests/test_mesh.py`` holds it).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.kernels import ops as _ops
from repro.mesh.halo import GID_SENTINEL, HaloPlan, MovePlan


def _a2a(buf, axis):
    r = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0, tiled=False)
    return r.reshape((-1,) + buf.shape[2:])


def _rows(mask, like):
    """A row mask broadcast over the fields of (rows, V) values."""
    return mask[:, None] if like.ndim == 2 else mask


def _route(prev, stage_meta, stage_idx, fill):
    """Replay the plan's hops: gather rows into lane buffers, exchange.
    ``prev`` is (rows,) or (rows, V): a row carries all its fields."""
    for (ax, lanes, scap), idx in zip(stage_meta, stage_idx):
        src = jnp.clip(idx, 0, prev.shape[0] - 1)
        buf = jnp.where(_rows(idx >= 0, prev), prev[src], fill)
        prev = _a2a(buf.reshape((lanes, scap) + prev.shape[1:]), ax)
    return prev


def _ghosts(recv, fetch):
    return jnp.where(_rows(fetch >= 0, recv), recv[jnp.clip(fetch, 0, recv.shape[0] - 1)], 0.0)


# ---------------------------------------------------------------------------
# the stencil sweep
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=4)
def _reference_fn():
    # ONE compile serves every sweep length: steps is a traced scalar
    # driving a fori_loop (per-iteration ops identical to the unrolled
    # loop, so results are bit-identical). The row update is the SAME
    # shared definition every distributed executor runs — its explicit
    # fixed-order K accumulation is what makes cross-program
    # bit-equality hold (see kernels.stencil_update).
    @jax.jit
    def fn(steps, u, nbr, valid, coeff):
        def body(_, u):
            return _ops.stencil_update(u, u, nbr, valid, coeff)
        return jax.lax.fori_loop(0, steps, body, u)
    return fn


def reference_stencil(u, nbr, valid, coeff, steps: int):
    """``steps`` explicit heat sweeps on one device, global cell order."""
    return _reference_fn()(
        jnp.int32(steps), jnp.asarray(u, jnp.float32), jnp.asarray(nbr),
        jnp.asarray(valid), jnp.asarray(coeff, jnp.float32),
    )


@functools.lru_cache(maxsize=64)
def _stencil_fn(
    mesh: jax.sharding.Mesh,
    axes: tuple,
    stage_meta: tuple,
    use_pallas: bool,
    listed: bool = False,
):
    """Jitted halo-exchange + fused-update executor, memoized per static
    (mesh, axes, hop shapes) — NOT per step count: ``steps`` is a traced
    argument, so one compiled program serves any sweep length.
    ``listed``: the V-wide kernel's form, which also takes the plan's
    finer-row list (after ``fetch``) and the table rows it names, taken
    once before the stages."""

    def kernel(steps, u, nbr, valid, coeff, fetch, *stage_idx):
        finer = None
        if listed:
            rows, stage_idx = stage_idx[0], stage_idx[1:]
            # a -1 pad takes row 0; its result is dropped
            finer = (rows,) + tuple(jnp.take(a, rows, axis=0, mode="clip")
                                    for a in (nbr, valid, coeff))

        def body(_, u):
            recv = _route(u, stage_meta, stage_idx, jnp.float32(0.0))
            vals_all = jnp.concatenate([u, _ghosts(recv, fetch)])
            return _ops.stencil_update(vals_all, u, nbr, valid, coeff, use_pallas=use_pallas,
                                       finer=finer)
        return jax.lax.fori_loop(0, steps, body, u)

    spec = P(axes)
    in_specs = (P(),) + (spec,) * (5 + listed + len(stage_meta))
    return jax.jit(jax.shard_map(
        kernel, mesh=mesh, in_specs=in_specs, out_specs=spec, check_vma=False,
    ))


@dataclass(frozen=True)
class HaloArgs:
    """Device-resident executor arguments for one halo plan. ``finer``
    is the plan's finer-row list (``HaloPlan.finer_rows``, made once per
    plan by ``halo.list_finer_rows``): the rows the V-wide kernel runs
    with every slot's values; the scalar sweep does not take it."""

    core: tuple     # (nbr, valid, coeff, fetch)
    stages: tuple   # one flat lane-index array per hop
    finer: jax.Array


def halo_args(jax_mesh: jax.sharding.Mesh, plan: HaloPlan) -> HaloArgs:
    """Device-resident executor arguments for one halo plan (placed once
    per plan, outside the timed sweep loop)."""
    sh = NamedSharding(jax_mesh, P(plan.axes))
    S = plan.owned_idx.shape[0]
    put = lambda a: jax.device_put(a, sh)   # host shards straight to their devices
    core = (
        put(plan.nbr_local.reshape(S * plan.cap, plan.K)),
        put(plan.nbr_valid.reshape(S * plan.cap, plan.K)),
        put(plan.coeff.reshape(S * plan.cap, plan.K)),
        put(plan.ghost_fetch.reshape(S * plan.gcap)),
    )
    stages = tuple(
        put(s.idx.reshape(S * s.lanes * s.cap)) for s in plan.stages
    )
    return HaloArgs(core=core, stages=stages, finer=put(plan.finer_rows.reshape(S * plan.fcap)))


def stencil_steps(
    jax_mesh,
    plan: HaloPlan,
    u_dev,
    args: HaloArgs,
    steps: int,
    *,
    use_pallas: bool = False,
):
    """Run ``steps`` distributed sweeps over the plan's layout.

    ``u_dev`` is the (S*cap,) owned field or (S*cap, V) owned fields
    (``plan.pack_cells`` layout); ``args`` from :func:`halo_args`. One
    compiled program serves every ``steps``."""
    listed = bool(use_pallas) and u_dev.ndim == 2
    fn = _stencil_fn(jax_mesh, plan.axes, plan.stage_meta, bool(use_pallas), listed)
    finer = (args.finer,) if listed else ()
    return fn(jnp.int32(steps), u_dev, *args.core, *finer, *args.stages)


def put_state(jax_mesh, plan: HaloPlan, u_cells: np.ndarray):
    """Host cell-order field (n,) or fields (n, V) -> device owned layout."""
    sh = NamedSharding(jax_mesh, P(plan.axes))
    return jax.device_put(plan.pack_cells(u_cells), sh)


# ---------------------------------------------------------------------------
# state migration between partitions
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _move_fn(
    mesh: jax.sharding.Mesh,
    axes: tuple,
    stage_meta: tuple,
    cap_new: int,
):
    """Jitted state-move executor: route moved (slot, value) rows along
    the plan's hops, then merge with the kept rows by slot sort — the
    new layout's canonical ascending-slot order falls out of the sort."""

    def kernel(u, gid, keep, *stage_idx):
        prev_u, prev_g = u, gid
        for (ax, lanes, scap), idx in zip(stage_meta, stage_idx):
            src = jnp.clip(idx, 0, prev_u.shape[0] - 1)
            sel = idx >= 0
            buf_u = jnp.where(_rows(sel, prev_u), prev_u[src], 0.0)
            buf_g = jnp.where(sel, prev_g[src], GID_SENTINEL).reshape(lanes, scap)
            prev_u = _a2a(buf_u.reshape((lanes, scap) + u.shape[1:]), ax)
            prev_g = _a2a(buf_g, ax)
        kept_g = jnp.where(keep, gid, GID_SENTINEL)
        if stage_meta:
            all_g = jnp.concatenate([kept_g, prev_g])
            all_u = jnp.concatenate([u, prev_u])
        else:
            all_g, all_u = kept_g, u
        # slots are unique but for the sentinel of empty rows, which end
        # last and read 0, so an unstable sort gives the same rows (and
        # XLA:TPU compiles it in two thirds of the time)
        order = jnp.argsort(all_g, stable=False)[:cap_new]
        out_g = all_g[order]
        return jnp.where(_rows(out_g != GID_SENTINEL, all_u), all_u[order], 0.0)

    spec = P(axes)
    in_specs = (spec,) * (3 + len(stage_meta))
    return jax.jit(jax.shard_map(
        kernel, mesh=mesh, in_specs=in_specs, out_specs=spec, check_vma=False,
    ))


def move_state(jax_mesh, mv: MovePlan, old: HaloPlan, u_dev):
    """Execute a compiled state move: ``u_dev`` in ``old``'s layout ->
    the new plan's layout (values bit-preserved; rows only travel)."""
    sh = NamedSharding(jax_mesh, P(mv.axes))
    S = old.owned_idx.shape[0]
    put = lambda a: jax.device_put(jnp.asarray(a), sh)
    gid = put(old.owned_slot.astype(np.int32).reshape(S * old.cap))
    keep = put(mv.keep.reshape(S * mv.cap_old))
    stages = tuple(put(s.idx.reshape(S * s.lanes * s.cap)) for s in mv.stages)
    fn = _move_fn(jax_mesh, mv.axes, mv.stage_meta, int(mv.cap_new))
    return fn(u_dev, gid, keep, *stages)


# ---------------------------------------------------------------------------
# global checksums
# ---------------------------------------------------------------------------

CHECKSUM_BLOCK = 1024   # rows summed together before the block sums


@functools.lru_cache(maxsize=16)
def _checksum_fn(mesh: jax.sharding.Mesh, axes: tuple):
    """Jitted per-field global sum: each device sums its owned rows (pad
    rows hold 0) in blocks of ``CHECKSUM_BLOCK``, then the block sums,
    then one ``psum`` over the mesh (so no float32 chain of adds is
    longer than a block or the block count)."""

    def kernel(u):
        pad = -u.shape[0] % CHECKSUM_BLOCK
        u = jnp.pad(u, ((0, pad),) + ((0, 0),) * (u.ndim - 1))
        blocks = jnp.sum(u.reshape((-1, CHECKSUM_BLOCK) + u.shape[1:]), axis=1)
        return jax.lax.psum(jnp.sum(blocks, axis=0), axes)

    return jax.jit(jax.shard_map(
        kernel, mesh=mesh, in_specs=(P(axes),), out_specs=P(), check_vma=False,
    ))


def checksum(jax_mesh, plan: HaloPlan, u_dev):
    """(V,) float32 sum of every field over all cells (a device array)."""
    return _checksum_fn(jax_mesh, plan.axes)(u_dev)
