"""Pallas TPU kernel: Hilbert-like SFC key generation (Skilling transform).

Same VPU-bound structure and lane-dense (d, rows, 128) layout as the
Morton kernel, plus the Gray-code transpose (paper's Hilbert-like
look-ahead — a static O(bits * d) chain of shifts/xors/selects per
block, branch-free and fully vectorized). The kernel fuses transform +
interleave so cells are read from VMEM once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.morton import interleave, lane_dense_keys


def _hilbert_kernel(cells_ref, out_ref, *, bits: int, d: int):
    X = [cells_ref[i] for i in range(d)]   # d x (BLOCK_ROWS, 128) uint32

    # Skilling inverse-undo (static loops -> straight-line vector code)
    Q = 1 << (bits - 1)
    while Q > 1:
        Pm = jnp.uint32(Q - 1)
        Qm = jnp.uint32(Q)
        for i in range(d):
            cond = (X[i] & Qm) != 0
            t = (X[0] ^ X[i]) & Pm
            x0_if = X[0] ^ Pm
            x0_else = X[0] ^ t
            xi_else = X[i] ^ t
            X[0] = jnp.where(cond, x0_if, x0_else)
            if i != 0:
                X[i] = jnp.where(cond, X[i], xi_else)
        Q >>= 1

    # Gray encode
    for i in range(1, d):
        X[i] = X[i] ^ X[i - 1]
    t = jnp.zeros_like(X[0])
    Q = 1 << (bits - 1)
    while Q > 1:
        Qm = jnp.uint32(Q)
        t = jnp.where((X[d - 1] & Qm) != 0, t ^ jnp.uint32(Q - 1), t)
        Q >>= 1
    for i in range(d):
        X[i] = X[i] ^ t

    out_ref[...] = interleave(X, bits)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def hilbert_from_cells(cells: jax.Array, bits: int, *, interpret: bool = True) -> jax.Array:
    """(n, d) uint32 cells -> (n,) uint32 Hilbert-like keys via Pallas."""
    return lane_dense_keys(_hilbert_kernel, cells, bits, interpret)
