"""Pallas TPU kernel: Morton (bit-interleave) SFC key generation.

The partitioner's hottest loop is key generation over every element
(paper §III-B: traversals over 10M–8B points). On TPU this is a pure
VPU integer workload: bit-planes are extracted with shifts/masks and
OR-combined into the key word — no MXU, no cross-element communication.

Layout: the (n, d) cells are transposed to a lane-dense (d, rows, 128)
slab, so each coordinate of a block is a full (BLOCK_ROWS, 128) tile. An
(n, d) block would pad d to 128 lanes — 42x the VMEM and HBM traffic at
d=3. One grid step keys BLOCK_ROWS * 128 = 32768 points: 3 * 128 KiB of
cells in, 128 KiB of keys out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
BLOCK_ROWS = 256


def interleave(X: list, bits: int) -> jax.Array:
    """Bit-interleave per-dimension uint32 planes into one left-aligned
    key word — the layout of `sfc._interleave` with ``words=1``."""
    d = len(X)
    offset = 32 - bits * d
    key = jnp.zeros_like(X[0])
    for k in range(bits):
        src_bit = bits - 1 - k
        for i in range(d):
            bit_in_word = 31 - (offset + k * d + i)
            comp = (X[i] >> jnp.uint32(src_bit)) & jnp.uint32(1)
            key = key | (comp << jnp.uint32(bit_in_word))
    return key


def _morton_kernel(cells_ref, out_ref, *, bits: int, d: int):
    out_ref[...] = interleave([cells_ref[i] for i in range(d)], bits)


def lane_dense_keys(kernel, cells: jax.Array, bits: int, interpret: bool) -> jax.Array:
    """Run a per-point key kernel over (n, d) cells in the lane-dense
    layout: ``kernel(cells_ref (d, R, 128), out_ref (R, 128), bits=, d=)``.
    Returns the (n,) uint32 keys."""
    n, d = cells.shape
    assert bits * d <= 32, "single-word kernel: bits*d must fit 32 bits"
    rows = pl.cdiv(n, LANES)
    block = min(BLOCK_ROWS, pl.cdiv(rows, 8) * 8)
    rows_pad = pl.cdiv(rows, block) * block
    slab = jnp.pad(cells.T, ((0, 0), (0, rows_pad * LANES - n)))
    out = pl.pallas_call(
        functools.partial(kernel, bits=bits, d=d),
        grid=(rows_pad // block,),
        in_specs=[pl.BlockSpec((d, block, LANES), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((block, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, LANES), jnp.uint32),
        interpret=interpret,
    )(slab.reshape(d, rows_pad, LANES))
    return out.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def morton_from_cells(cells: jax.Array, bits: int, *, interpret: bool = True) -> jax.Array:
    """(n, d) uint32 cells -> (n,) uint32 Morton keys via Pallas."""
    return lane_dense_keys(_morton_kernel, cells, bits, interpret)
