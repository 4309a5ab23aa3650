"""The control and planted faults of the miniAMR spheres cell, as
``bench/faults.py`` holds those of the drift cell: the timed path
replaced underneath a run, for showing that ``correct`` comes out false.

* ``control``: the sweep's row update computed one precision below the
  configuration's float32: bfloat16.
* ``ghost``: one lane of the halo exchange dropped.
* ``unrefined``: blocks a surface crosses left unrefined.
* ``shifted``: a move that shifts the variable columns.

Each is a context manager keyed by (driver, mode) that patches the
program in this process and restores it on exit.
"""
from __future__ import annotations

import contextlib

import numpy as np

from faults import _patched


@contextlib.contextmanager
def _fresh_executors():
    """Compiled stencil and move executors are memoized per shape, and
    the driver keeps meshes per process: drop them on entry and exit, so
    the patched code runs, and only inside."""
    import harness
    from repro.mesh import stencil

    def clear():
        stencil._stencil_fn.cache_clear()
        stencil._move_fn.cache_clear()
        harness.__dict__.get("amr_spheres_memo", {}).clear()

    clear()
    try:
        yield
    finally:
        clear()


@contextlib.contextmanager
def spheres_control():
    """The sweep's row update, u + sum_k c_k (u_k - u), computed in
    bfloat16 in place of the V-wide kernel."""
    import jax.numpy as jnp

    from repro.kernels import stencil_update

    def fused(vals_all, u_rows, nbr, valid, coeff, *, interpret=True):
        bf = jnp.bfloat16
        u = u_rows.astype(bf)
        acc = jnp.zeros_like(u)
        for k in range(nbr.shape[1]):
            acc = acc + jnp.where(valid[:, k, None],
                                  coeff[:, k, None].astype(bf) * (vals_all[nbr[:, k]].astype(bf) - u),
                                  bf(0))
        return (u + acc).astype(jnp.float32)

    with _fresh_executors(), _patched(stencil_update, "fused_stencil_update_v", fused):
        yield


@contextlib.contextmanager
def spheres_ghost():
    """One lane of the first hop of the halo exchange dropped: a chip's
    values for one other node never leave it (the ghosts read 0)."""
    from repro.mesh import halo

    def stages(axes, N, D, *a):
        (hop_a, hop_b), fetch = orig(axes, N, D, *a)
        idx = hop_a.idx.copy()
        for o in range(idx.shape[0]):
            m = next((m for m in range(N) if m != o // D and (idx[o, m] >= 0).any()), None)
            if m is not None:
                idx[o, m] = -1
                break
        return (halo.Stage(hop_a.axis, hop_a.lanes, hop_a.cap, idx), hop_b), fetch

    with _fresh_executors(), _patched(halo, "_two_hop_stages_vec", stages) as orig:
        yield


@contextlib.contextmanager
def spheres_unrefined():
    """The surface test never marks the blocks that hold one point of the
    second sphere's surface (its lowest point at the first position)."""
    from repro.mesh import amr

    point = None

    def surface_hit(blocks, objects, t):
        nonlocal point
        hit = orig(blocks, objects, t)
        if point is None:
            c, r = objects[-1].at(t)
            point = c - np.array([0.0, 0.0, r[2]]) + 1e-3
        h = (0.5 ** blocks.level.astype(np.float64))[:, None]
        lo = blocks.ij * h
        inside = np.all((lo <= point) & (point < lo + h), axis=1)
        return hit & ~inside

    with _fresh_executors(), _patched(amr, "surface_hit", surface_hit) as orig:
        yield


@contextlib.contextmanager
def spheres_shifted():
    """Every move carries the fields one variable column over."""
    import jax.numpy as jnp

    from repro.mesh import stencil

    def move_state(jax_mesh, mv, old, u_dev):
        out = orig(jax_mesh, mv, old, u_dev)
        return jnp.roll(out, 1, axis=1) if out.ndim == 2 else out

    with _fresh_executors(), _patched(stencil, "move_state", move_state) as orig:
        yield


SUBSTITUTES = {
    ("amr_spheres", "control"): spheres_control,
    ("amr_spheres", "ghost"): spheres_ghost,
    ("amr_spheres", "unrefined"): spheres_unrefined,
    ("amr_spheres", "shifted"): spheres_shifted,
}


def substitute(driver: str, mode: str):
    return SUBSTITUTES[(driver, mode)]()
