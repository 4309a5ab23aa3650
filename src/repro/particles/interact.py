"""Cutoff-radius interaction plans + distributed leapfrog executors.

The mesh halo machinery, generalized from topology-based to
distance-based neighbor structure: :func:`cutoff_neighbors` resolves
candidate interaction sets through CurveIndex bucket lookups within
radius ``r`` (the 3^d probe-cell walk below) and emits the same padded
``(n, K)`` neighbor-table shape `repro.mesh.amr.face_neighbors`
produces — so :func:`build_interact_plan` is `halo.build_halo_plan`
wholesale (ghost dedup, flat and two-hop node routing, `PlanCache`
reuse where the topology tier applies), compiled ONCE per partition
event into fixed-shape interaction/exchange plans.

Executors mirror `repro.mesh.stencil`: jitted ``shard_map`` closures
memoized per static shape signature, one sweep form (route the owned
positions, fetch the ghosts, then the accelerations of every owned row
in one pass over ``concat([x, ghosts])``), and a ``fori_loop`` over a
traced substep count so ONE compiled program serves every sweep length.
The row update is the fused `kernels.ops.pair_accel` (Pallas + bit-equal
jnp fallback).

Bit-equality contract: :func:`reference_leapfrog` (single device,
global row order) and :func:`leapfrog_steps` (sharded, owned+ghost
layout) evaluate the SAME per-particle expressions — identical padded
(n, K) tables, identical fixed-order reductions, identical float32
integration (:func:`_integrate`) — so a distributed trajectory is
bitwise equal to the reference trajectory across repartition events.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import curve_index as _ci
from repro.kernels import ops as _ops
from repro.mesh import halo as _halo
from repro.mesh.halo import GID_SENTINEL, HaloPlan, MovePlan, _roundup
from repro.mesh.stencil import _ghosts, _route


# ---------------------------------------------------------------------------
# cutoff neighbor lists via CurveIndex cell probes
# ---------------------------------------------------------------------------

def cutoff_neighbors(pos: np.ndarray, radius: float) -> np.ndarray:
    """(n, K) int32 interaction table: every pair within ``radius``.

    A coarse Morton CurveIndex over the unit frame buckets the particles
    into grid cells of width ``2**-bits >= radius``; each particle
    probes the 3^d cells at ``x + o * radius`` (o in {-1, 0, 1}^d,
    clipped to the frame). Because the quantizer is monotone and the
    cell width is at least the radius, the three per-dimension probes
    cover every cell intersecting ``[x - r, x + r]`` — so the candidate
    union provably contains every in-range pair. Candidates are resolved
    by equal-key runs on the index's sorted key array, filtered by a
    float64 distance check with conservative slack (extra at-cutoff
    candidates are harmless: the force law weights them exactly 0.0),
    and emitted in deterministic ascending (row, neighbor-id) lane
    order with -1 pads — the same table contract as
    `mesh.amr.face_neighbors`, which is what lets `build_halo_plan`
    consume it unchanged.
    """
    pos = np.asarray(pos, np.float32)
    n, d = pos.shape
    r = float(radius)
    if not (0.0 < r <= 0.5):
        raise ValueError(f"cutoff radius must be in (0, 0.5], got {r}")
    bits = max(1, int(np.floor(np.log2(1.0 / r))))
    idx = _ci.build(
        jnp.asarray(pos),
        bits=bits,
        curve="morton",
        frame=(jnp.zeros((d,), jnp.float32), jnp.ones((d,), jnp.float32)),
        bucket_size=8,
    )
    keys_sorted = np.asarray(idx.keys)[:n].astype(np.uint64)
    ids_sorted = np.asarray(idx.ids)[:n].astype(np.int64)

    offs = np.stack(
        np.meshgrid(*([np.array([-1.0, 0.0, 1.0], np.float32)] * d), indexing="ij"),
        axis=-1,
    ).reshape(-1, d)
    probes = np.clip(pos[:, None, :] + offs[None, :, :] * np.float32(r), 0.0, 1.0)
    pk = np.asarray(
        _ci.query_keys(idx, jnp.asarray(probes.reshape(-1, d)))
    ).astype(np.uint64)
    row = np.repeat(np.arange(n, dtype=np.uint64), offs.shape[0])
    # dedup (row, cell): clipping and sub-radius offsets collide probes
    code = np.unique((row << np.uint64(32)) | pk)
    crow = (code >> np.uint64(32)).astype(np.int64)
    ckey = code & np.uint64(0xFFFFFFFF)
    lo = np.searchsorted(keys_sorted, ckey, side="left")
    hi = np.searchsorted(keys_sorted, ckey, side="right")
    lens = hi - lo
    occupied = lens > 0
    lo, lens, crow = lo[occupied], lens[occupied], crow[occupied]
    # ragged run expansion without a Python loop
    tot = int(lens.sum())
    base = np.repeat(lo, lens)
    starts = np.cumsum(lens) - lens
    within = np.arange(tot, dtype=np.int64) - np.repeat(starts, lens)
    cand = ids_sorted[base + within]
    prow = np.repeat(crow, lens)

    diff = pos[prow].astype(np.float64) - pos[cand].astype(np.float64)
    d2 = np.einsum("ij,ij->i", diff, diff)
    keep = (cand != prow) & (d2 <= (r * r) * (1.0 + 1e-5))
    prow, cand = prow[keep], cand[keep]

    order = np.argsort(prow * np.int64(n) + cand, kind="stable")
    prow, cand = prow[order], cand[order]
    counts = np.bincount(prow, minlength=n)
    K = _roundup(max(int(counts.max()) if counts.size else 0, 1), 8)
    nbr = np.full((n, K), -1, np.int32)
    starts = np.cumsum(counts) - counts
    within = np.arange(prow.shape[0], dtype=np.int64) - starts[prow]
    nbr[prow, within] = cand.astype(np.int32)
    return nbr


def build_interact_plan(
    slot: np.ndarray,
    part: np.ndarray,
    nbr: np.ndarray,
    *,
    hierarchy=None,
    num_parts: int | None = None,
    device_axis: str = "device",
    weights: np.ndarray | None = None,
    with_metrics: bool = True,
    cache=None,
    topo_token=None,
) -> HaloPlan:
    """Compile a cutoff interaction/exchange plan for one partition.

    Exactly `halo.build_halo_plan` over the distance-based table (the
    stencil coefficient lanes carry zeros — the pair executors never
    read them): ghost sets, local index remapping and the flat/two-hop
    routing stages all come from the shared builder, so everything the
    mesh application proved (bit-identity to the per-part oracle,
    `PlanCache` delta patching keyed on ``topo_token``) holds here
    unchanged.
    """
    coeff = np.zeros(nbr.shape, np.float32)
    return _halo.build_halo_plan(
        slot, part, nbr, coeff,
        hierarchy=hierarchy, num_parts=num_parts, device_axis=device_axis,
        weights=weights, with_metrics=with_metrics, cache=cache,
        topo_token=topo_token,
    )


# ---------------------------------------------------------------------------
# device layout helpers (row-keyed, any column count)
# ---------------------------------------------------------------------------

def pack_rows(plan: HaloPlan, arr: np.ndarray, fill=0.0) -> np.ndarray:
    """Global row-order array (n,) or (n, C) -> (S*cap, ...) owned layout."""
    a = np.asarray(arr)
    S = plan.owned_idx.shape[0]
    out = np.full((S, plan.cap) + a.shape[1:], fill, a.dtype)
    m = plan.owned_idx >= 0
    out[m] = a[plan.owned_idx[m]]
    return out.reshape((S * plan.cap,) + a.shape[1:])


def unpack_rows(plan: HaloPlan, dev, n: int) -> np.ndarray:
    """(S*cap, ...) owned layout -> global row-order array."""
    a = np.asarray(dev)
    S = plan.owned_idx.shape[0]
    a = a.reshape((S, plan.cap) + a.shape[1:])
    out = np.zeros((n,) + a.shape[2:], a.dtype)
    m = plan.owned_idx >= 0
    out[plan.owned_idx[m]] = a[m]
    return out


def put_rows(jax_mesh, plan: HaloPlan, arr: np.ndarray):
    """Host global row-order array -> sharded device owned layout."""
    sh = NamedSharding(jax_mesh, P(plan.axes))
    return jax.device_put(jnp.asarray(pack_rows(plan, arr)), sh)


@dataclass(frozen=True)
class InteractArgs:
    """Device-resident executor arguments for one interaction plan."""

    core: tuple     # (nbr, valid, fetch)
    stages: tuple   # one flat lane-index array per hop


def interact_args(jax_mesh, plan: HaloPlan) -> InteractArgs:
    """Device-resident executor arguments (placed once per plan, outside
    the timed substep loop) — `stencil.halo_args` minus the coefficient
    table the pair kernel has no use for."""
    sh = NamedSharding(jax_mesh, P(plan.axes))
    S = plan.owned_idx.shape[0]
    put = lambda a: jax.device_put(jnp.asarray(a), sh)
    core = (
        put(plan.nbr_local.reshape(S * plan.cap, plan.K)),
        put(plan.nbr_valid.reshape(S * plan.cap, plan.K)),
        put(plan.ghost_fetch.reshape(S * plan.gcap)),
    )
    stages = tuple(put(s.idx.reshape(S * s.lanes * s.cap)) for s in plan.stages)
    return InteractArgs(core=core, stages=stages)


# ---------------------------------------------------------------------------
# the shared physics (single definition, both backends)
# ---------------------------------------------------------------------------

def _reflect_walls(x, v):
    """Reflect at the unit-box walls — elementwise float32, so identical
    bits in any layout."""
    lo = x < jnp.float32(0.0)
    x = jnp.where(lo, -x, x)
    v = jnp.where(lo, -v, v)
    hi = x > jnp.float32(1.0)
    x = jnp.where(hi, jnp.float32(2.0) - x, x)
    v = jnp.where(hi, -v, v)
    return x, v


def _integrate(x, v, acc, dt):
    """Kick-drift step + wall reflection (the one integrator)."""
    v2 = v + dt * acc
    x2 = x + dt * v2
    return _reflect_walls(x2, v2)


def _route_cols(prev, stage_meta, stage_idx, fill):
    """Replay the plan's hops for a (rows, C) matrix payload — the value
    routing of `stencil._route` with every column riding one
    ``all_to_all``."""
    C = prev.shape[-1]
    for (ax, lanes, scap), idx in zip(stage_meta, stage_idx):
        src = jnp.clip(idx, 0, prev.shape[0] - 1)
        buf = jnp.where((idx >= 0)[:, None], prev[src], fill).reshape(lanes, scap, C)
        r = jax.lax.all_to_all(buf, ax, split_axis=0, concat_axis=0, tiled=False)
        prev = r.reshape(-1, C)
    return prev


# ---------------------------------------------------------------------------
# reference integrator (the bitwise oracle)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=4)
def _reference_fn(use_pallas: bool):
    @jax.jit
    def fn(steps, dt, rc2, x, v, m, nbr, valid):
        def body(_, carry):
            x, v = carry
            acc = _ops.pair_accel(x, m, x, nbr, valid, rc2, use_pallas=use_pallas)
            return _integrate(x, v, acc, dt)
        return jax.lax.fori_loop(0, steps, body, (x, v))
    return fn


def reference_leapfrog(x, v, m, nbr, steps: int, dt: float, radius: float,
                       *, use_pallas: bool = False):
    """``steps`` kick-drift substeps on one device, global row order.
    Consumes the SAME padded (n, K) table as the distributed executor —
    the precondition of their bit-equality."""
    nbr = jnp.asarray(nbr)
    return _reference_fn(bool(use_pallas))(
        jnp.int32(steps), jnp.float32(dt), jnp.float32(float(radius) ** 2),
        jnp.asarray(x, jnp.float32), jnp.asarray(v, jnp.float32),
        jnp.asarray(m, jnp.float32), nbr, nbr >= 0,
    )


# ---------------------------------------------------------------------------
# distributed leapfrog (ghost-position exchange, then every owned row)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _leapfrog_fn(
    mesh: jax.sharding.Mesh,
    axes: tuple,
    stage_meta: tuple,
    use_pallas: bool,
):
    """Jitted exchange + fused pair-accel + integrate executor, memoized
    per static (mesh, axes, hop shapes) — ``steps`` is traced, so one
    compiled program serves any substep count."""

    def kernel(steps, dt, rc2, x, v, m, m_gh, nbr, valid, fetch, *stage_idx):
        mass_all = jnp.concatenate([m, m_gh])

        def body(_, carry):
            x, v = carry
            recv = _route_cols(x, stage_meta, stage_idx, jnp.float32(0.0))
            pos_all = jnp.concatenate([x, _ghosts(recv, fetch)], axis=0)
            acc = _ops.pair_accel(pos_all, mass_all, x, nbr, valid, rc2,
                                  use_pallas=use_pallas)
            return _integrate(x, v, acc, dt)

        return jax.lax.fori_loop(0, steps, body, (x, v))

    spec = P(axes)
    in_specs = (P(), P(), P()) + (spec,) * (7 + len(stage_meta))
    return jax.jit(jax.shard_map(
        kernel, mesh=mesh, in_specs=in_specs, out_specs=(spec, spec),
        check_vma=False,
    ))


def leapfrog_steps(
    jax_mesh,
    plan: HaloPlan,
    x_dev,
    v_dev,
    m_dev,
    mgh_dev,
    args: InteractArgs,
    steps: int,
    dt: float,
    radius: float,
    *,
    use_pallas: bool = False,
):
    """Run ``steps`` distributed kick-drift substeps over the plan's
    layout. ``x_dev``/``v_dev`` are (S*cap, d), ``m_dev`` (S*cap,) and
    ``mgh_dev`` the (S*gcap,) ghost masses from :func:`exchange_rows`
    (masses are constant between migrations — fetched once per plan,
    positions every substep)."""
    fn = _leapfrog_fn(jax_mesh, plan.axes, plan.stage_meta, bool(use_pallas))
    return fn(
        jnp.int32(steps), jnp.float32(dt), jnp.float32(float(radius) ** 2),
        x_dev, v_dev, m_dev, mgh_dev, *args.core, *args.stages,
    )


@functools.lru_cache(maxsize=64)
def _exchange_fn(mesh: jax.sharding.Mesh, axes: tuple, stage_meta: tuple):
    """Jitted one-shot ghost fetch of a per-row scalar (the mass vector)."""

    def kernel(m, fetch, *stage_idx):
        return _ghosts(_route(m, stage_meta, stage_idx, jnp.float32(0.0)), fetch)

    spec = P(axes)
    in_specs = (spec,) * (2 + len(stage_meta))
    return jax.jit(jax.shard_map(
        kernel, mesh=mesh, in_specs=in_specs, out_specs=spec, check_vma=False,
    ))


def exchange_rows(jax_mesh, plan: HaloPlan, m_dev, args: InteractArgs):
    """Fetch the (S*gcap,) ghost copies of a per-row scalar along the
    plan's hops (once per plan for quantities that only change at
    migrations)."""
    fn = _exchange_fn(jax_mesh, plan.axes, plan.stage_meta)
    return fn(m_dev, args.core[2], *args.stages)


# ---------------------------------------------------------------------------
# multi-payload state migration (one plan, every column travels together)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _move_cols_fn(
    mesh: jax.sharding.Mesh,
    axes: tuple,
    stage_meta: tuple,
    cap_new: int,
    C: int,
):
    """`stencil._move_fn` generalized to a (cap, C) matrix payload: the
    slot ids route once and every state column rides the same hops, so
    position/velocity/mass (and the mesh field in the coupled run)
    migrate under ONE plan."""

    def kernel(u, gid, keep, *stage_idx):
        prev_u, prev_g = u, gid
        for (ax, lanes, scap), idx in zip(stage_meta, stage_idx):
            src = jnp.clip(idx, 0, prev_u.shape[0] - 1)
            sel = idx >= 0
            buf_u = jnp.where(sel[:, None], prev_u[src], 0.0).reshape(lanes, scap, C)
            buf_g = jnp.where(sel, prev_g[src], GID_SENTINEL).reshape(lanes, scap)
            prev_u = jax.lax.all_to_all(
                buf_u, ax, split_axis=0, concat_axis=0, tiled=False
            ).reshape(-1, C)
            prev_g = jax.lax.all_to_all(
                buf_g, ax, split_axis=0, concat_axis=0, tiled=False
            ).reshape(-1)
        kept_g = jnp.where(keep, gid, GID_SENTINEL)
        if stage_meta:
            all_g = jnp.concatenate([kept_g, prev_g])
            all_u = jnp.concatenate([u, prev_u], axis=0)
        else:
            all_g, all_u = kept_g, u
        order = jnp.argsort(all_g, stable=True)[:cap_new]
        out_g = all_g[order]
        return jnp.where((out_g != GID_SENTINEL)[:, None], all_u[order], 0.0)

    spec = P(axes)
    in_specs = (spec,) * (3 + len(stage_meta))
    return jax.jit(jax.shard_map(
        kernel, mesh=mesh, in_specs=in_specs, out_specs=spec, check_vma=False,
    ))


def move_rows(jax_mesh, mv: MovePlan, old: HaloPlan, u_dev):
    """Execute a compiled multi-column state move: ``u_dev`` (S*cap_old,
    C) in ``old``'s layout -> the new plan's layout (values
    bit-preserved; rows only travel)."""
    sh = NamedSharding(jax_mesh, P(mv.axes))
    S = old.owned_idx.shape[0]
    put = lambda a: jax.device_put(jnp.asarray(a), sh)
    gid = put(old.owned_slot.astype(np.int32).reshape(S * old.cap))
    keep = put(mv.keep.reshape(S * mv.cap_old))
    stages = tuple(put(s.idx.reshape(S * s.lanes * s.cap)) for s in mv.stages)
    fn = _move_cols_fn(
        jax_mesh, mv.axes, mv.stage_meta, int(mv.cap_new), int(u_dev.shape[-1])
    )
    return fn(u_dev, gid, keep, *stages)
