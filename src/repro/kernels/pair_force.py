"""Pallas TPU kernel: fused pairwise short-range acceleration (paper
§V-C particle hot loop).

One pass over (K, rows) interaction tiles fuses the cutoff weight
``max(rc2 - |r|^2, 0) * m_j``, the displacement products and the
K-reduction into per-row accelerations — the unfused jnp path
materializes the (n, K, d) contribution intermediate in HBM between
separate ops. The neighbor position/mass gather runs in XLA ahead of
the kernel (Mosaic lowers no vector gather beyond one vreg), laid out
(d, K, rows) so rows fill the 128 lanes and both add chains are
sublane-row adds.

The force law is the bounded short-range attraction

    a_i = sum_j m_j * max(rc2 - |x_j - x_i|^2, 0) * (x_j - x_i)

smooth and exactly zero at the cutoff boundary, so an interaction table
may safely include candidates at or beyond the cutoff — their weight is
exactly ``0.0`` and a padded lane contributes a signed zero, identical
on every execution path that consumes the SAME (n, K) table.

Bit-equality contract: :func:`pair_accel_ref` is THE definition — both
the per-lane squared distance (dimension sum) and the K-reduction are
*explicit unrolled chains* of elementwise adds in ascending order, the
same discipline `kernels.stencil_update` established: a ``jnp.sum``
lowers to an XLA Reduce whose accumulation order is chosen per fusion
context, while a fixed add chain is ordinary float arithmetic XLA must
not reassociate. Every caller — single-device reference integrator,
distributed executor, Pallas kernel — produces
identical bits by construction, which is what the particle drivers gate
on (``np.array_equal`` across repartition events).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.stencil_update import row_tiles


def pair_accel_ref(
    pos_all: jax.Array,
    mass_all: jax.Array,
    x_rows: jax.Array,
    nbr: jax.Array,
    valid: jax.Array,
    rc2: jax.Array,
) -> jax.Array:
    """The one definition of the fused pair acceleration (jnp fallback).

    ``pos_all`` (V, d) owned+ghost positions, ``mass_all`` (V,) their
    masses, ``x_rows`` (R, d) the positions of the rows being updated,
    ``nbr``/``valid`` (R, K) the row-local interaction table, ``rc2``
    the squared cutoff radius. Returns the (R, d) accelerations.
    """
    # one row gather per table column (see `stencil_update_ref`: XLA:TPU
    # compiles the (R, K)-index gather slower and through an (R, K, d)
    # temporary), fixed-order dimension and K accumulation (NOT jnp.sum)
    acc = None
    for k in range(nbr.shape[1]):
        diff = pos_all[nbr[:, k]] - x_rows             # (R, d)
        d2 = diff[:, 0] * diff[:, 0]
        for a in range(1, diff.shape[1]):
            d2 = d2 + diff[:, a] * diff[:, a]
        w = jnp.where(valid[:, k],
                      jnp.maximum(rc2 - d2, jnp.float32(0.0)) * mass_all[nbr[:, k]],
                      jnp.float32(0.0))
        contrib = w[:, None] * diff
        acc = contrib if acc is None else acc + contrib
    return acc


def _accel_kernel(rc2_ref, pj_ref, mj_ref, x_ref, valid_ref, out_ref):
    # same expression as pair_accel_ref on one (K, BLOCK_R) tile:
    # pj (d, K, BLOCK_R), mj/valid (K, BLOCK_R), x/out (d, 1, BLOCK_R)
    d = pj_ref.shape[0]
    rc2 = rc2_ref[0, 0]
    diff = [pj_ref[a] - x_ref[a] for a in range(d)]
    d2 = diff[0] * diff[0]
    for a in range(1, d):
        d2 = d2 + diff[a] * diff[a]
    w = jnp.where(valid_ref[...] != 0,
                  jnp.maximum(rc2 - d2, jnp.float32(0.0)) * mj_ref[...],
                  jnp.float32(0.0))
    for a in range(d):
        contrib = w * diff[a]
        acc = contrib[0:1]
        for k in range(1, contrib.shape[0]):
            acc = acc + contrib[k:k + 1]
        out_ref[a] = acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_pair_accel(
    pos_all: jax.Array,
    mass_all: jax.Array,
    x_rows: jax.Array,
    nbr: jax.Array,
    valid: jax.Array,
    rc2: jax.Array,
    *,
    interpret: bool = True,
) -> jax.Array:
    """Fused cutoff weight + contribution + K-reduce, one kernel dispatch
    after the XLA gather. Pad rows (``valid`` all False) come out exactly
    zero — exactly what the unfused path computes for them."""
    R, K = nbr.shape
    d = pos_all.shape[1]
    block, r_pad = row_tiles(R)
    nbr_t = jnp.pad(nbr.T, ((0, 0), (0, r_pad - R)))          # (K, r_pad)
    x_t = jnp.pad(x_rows.T, ((0, 0), (0, r_pad - R)))[:, None, :]
    valid_t = jnp.pad(valid.T.astype(jnp.int32), ((0, 0), (0, r_pad - R)))
    tile = pl.BlockSpec((K, block), lambda i: (0, i))
    per_dim = lambda rows: pl.BlockSpec((d, rows, block), lambda i: (0, 0, i))
    out = pl.pallas_call(
        _accel_kernel,
        grid=(r_pad // block,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            per_dim(K),
            tile,
            per_dim(1),
            tile,
        ],
        out_specs=per_dim(1),
        out_shape=jax.ShapeDtypeStruct((d, 1, r_pad), jnp.float32),
        interpret=interpret,
    )(
        jnp.asarray(rc2, jnp.float32).reshape(1, 1),
        jnp.stack([pos_all[:, a][nbr_t] for a in range(d)]),
        mass_all[nbr_t],
        x_t,
        valid_t,
    )
    return out[:, 0, :R].T
