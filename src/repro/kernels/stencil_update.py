"""Pallas TPU kernel: fused stencil row update (paper §I mesh hot loop).

One pass over (K, rows) tiles fuses the validity mask, the
``coeff * (u_nbr - u)`` contribution and the K-reduction — the unfused
jnp path materializes the (n, K) ``contrib`` intermediate in HBM
between separate ops. The neighbor-value gather ``vals_all[nbr]`` runs
in XLA ahead of the kernel (Mosaic lowers no vector gather beyond one
vreg); the gathered tables are laid out (K, rows) so rows fill the 128
lanes and the K-chain is a sequence of sublane-row adds.

Bit-equality contract: :func:`stencil_update_ref` is THE definition of
the update — ``u_r + sum_k where(valid, coeff * (vals_all[nbr] - u_r),
0)`` with the K-reduction spelled as an *explicit unrolled chain* of
elementwise adds in ascending k. The unroll is load-bearing: a
``jnp.sum(axis=-1)`` lowers to an XLA Reduce whose accumulation order
is an implementation choice made per fusion context, so two programs
computing "the same" row can disagree in the last ulp (observed on
CPU: a standalone reduce vectorizes, the same reduce inside the
overlapped stencil executor runs sequentially). A fixed add chain is
ordinary float arithmetic XLA must not reassociate, so every caller —
reference executor, pre-split baseline, overlapped executor, Pallas
kernel — produces identical bits by construction. The distributed
stencil gates on this (``np.array_equal`` against the single-device
reference across repartition events).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_R = 512  # rows per grid step (lanes)


def stencil_update_ref(
    vals_all: jax.Array,
    u_rows: jax.Array,
    nbr: jax.Array,
    valid: jax.Array,
    coeff: jax.Array,
) -> jax.Array:
    """The one definition of the fused row update (jnp fallback).

    ``vals_all`` (V,) owned+ghost values, ``u_rows`` (R,) the center
    value of each row being updated, ``nbr``/``valid``/``coeff`` (R, K)
    the row-local stencil tables. Returns the (R,) updated centers.
    """
    # one (R,) gather per neighbor column: XLA:TPU compiles a scalar
    # gather with (R, K) indices ~60x slower (90 s at 1.4M rows), with
    # the same elementwise arithmetic either way. Fixed-order K
    # accumulation (see module docstring: NOT jnp.sum).
    acc = None
    for k in range(nbr.shape[1]):
        contrib = jnp.where(
            valid[:, k], coeff[:, k] * (vals_all[nbr[:, k]] - u_rows), jnp.float32(0.0)
        )
        acc = contrib if acc is None else acc + contrib
    return u_rows + acc


def _update_kernel(vals_ref, u_ref, valid_ref, coeff_ref, out_ref):
    # same expression as stencil_update_ref on one (K, BLOCK_R) tile
    u = u_ref[...]                                   # (1, BLOCK_R)
    contrib = jnp.where(
        valid_ref[...] != 0, coeff_ref[...] * (vals_ref[...] - u), jnp.float32(0.0)
    )
    acc = contrib[0:1]
    for k in range(1, contrib.shape[0]):
        acc = acc + contrib[k:k + 1]
    out_ref[...] = u + acc


def row_tiles(R: int) -> tuple[int, int]:
    """(block, padded rows) for a lane-major row axis of length R."""
    block = min(BLOCK_R, pl.cdiv(R, 128) * 128)
    return block, pl.cdiv(R, block) * block


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_stencil_update(
    vals_all: jax.Array,
    u_rows: jax.Array,
    nbr: jax.Array,
    valid: jax.Array,
    coeff: jax.Array,
    *,
    interpret: bool = True,
) -> jax.Array:
    """Fused mask + contribution + K-reduce, one kernel dispatch after
    the XLA gather.

    Pad rows (``valid`` all False) pass their center value through
    unchanged up to ``+0.0`` — exactly what the unfused path computes.
    """
    R, K = nbr.shape
    block, r_pad = row_tiles(R)

    def cols(a):   # (R, K) -> (K, r_pad), rows on lanes
        return jnp.pad(a.T, ((0, 0), (0, r_pad - R)))

    tile = pl.BlockSpec((K, block), lambda i: (0, i))
    row = pl.BlockSpec((1, block), lambda i: (0, i))
    out = pl.pallas_call(
        _update_kernel,
        grid=(r_pad // block,),
        in_specs=[tile, row, tile, tile],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((1, r_pad), jnp.float32),
        interpret=interpret,
    )(
        vals_all[cols(nbr)],
        jnp.pad(u_rows, (0, r_pad - R))[None, :],
        cols(valid.astype(jnp.int32)),
        cols(coeff),
    )
    return out[0, :R]
