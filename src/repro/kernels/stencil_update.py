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
CPU: a standalone reduce vectorizes, the same reduce inside a
distributed stencil executor runs sequentially). A fixed add chain is
ordinary float arithmetic XLA must not reassociate, so every caller —
reference executor, distributed executor, Pallas kernel — produces
identical bits by construction. The distributed
stencil gates on this (``np.array_equal`` against the single-device
reference across repartition events). The construction is the form of
the chain, not its algebra: XLA's CPU backend contracts a multiply and
the add after it into one fused multiply-add in some fusions and not
in others, so a rewrite that is exact in real arithmetic (summing only
a row's valid slots, say) can differ in the last bit.

V-wide form (V fields per cell, rows (R, V)): the same expression per
field. :func:`fused_stencil_update_v` works through the rows in blocks
of ``GATHER_ROWS``, so the gathered neighbour rows never exist for all
rows at once. A cell's fields sit on the lanes, padded to a whole 128
(XLA:TPU gathers 128-lane rows about 6x faster than 40-lane ones), and
the rows on sublanes: each neighbour slot's gathered (block, 128) rows
go to the kernel as they are, with no transpose. Most rows of a mesh's
table have one valid slot a face; the kernel gathers only that slot's
values for them and keeps the full K-slot chain. The few rows with a
valid slot past the first of a face come listed from the halo plan
(``mesh.halo.list_finer_rows``, made once per plan) and
:func:`finer_update_v` runs them again with every slot's values.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.mesh.amr import face_group

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
    # V-wide rows (R, V): the row's mask and coefficient apply to every
    # field (a trailing axis of 1 broadcasts them)
    wide = (slice(None), None) if u_rows.ndim == 2 else (slice(None),)
    acc = None
    for k in range(nbr.shape[1]):
        contrib = jnp.where(
            valid[:, k][wide], coeff[:, k][wide] * (vals_all[nbr[:, k]] - u_rows),
            jnp.float32(0.0),
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


# ---------------------------------------------------------------------------
# V-wide form: (R, V) rows, rows on sublanes, fields on 128 lanes
# ---------------------------------------------------------------------------

BLOCK_RV = 256        # rows per grid step of the V-wide kernel (sublanes)
GATHER_ROWS = 65536   # rows whose neighbour values are gathered at once


def _update_kernel_v(*refs):
    # stencil_update_ref's expression on BLOCK_RV rows: one (rows, 128)
    # tile of gathered values per slot, fields in its first V lanes; or
    # one per face, which then stands for every slot of the face (a row
    # whose other slots are all empty adds +0.0 for them, whatever the
    # value: the result is the same)
    *vals_refs, u_ref, valid_ref, coeff_ref, out_ref = refs
    u = u_ref[...]                                   # (BLOCK_RV, V)
    V = u.shape[1]
    valid = valid_ref[...] != 0                      # (BLOCK_RV, K)
    coeff = coeff_ref[...]
    K = valid.shape[1]
    group = K // len(vals_refs)
    vals = [r[:, :V] for r in vals_refs]
    acc = None
    for k in range(K):
        contrib = jnp.where(valid[:, k:k + 1], coeff[:, k:k + 1] * (vals[k // group] - u),
                            jnp.float32(0.0))
        acc = contrib if acc is None else acc + contrib
    out_ref[...] = u + acc


def _rows_pass(vals_p, u_rows, nbr, valid, coeff, interpret):
    """The kernel over every row of the tables it is given (all of a
    device's rows, or its listed finer rows): rows in blocks of
    ``GATHER_ROWS`` (fewer when R is small), each gathering one (block,
    128-lane) array of neighbour rows per column of ``nbr`` (every slot,
    or the first slot of each face: see :func:`_update_kernel_v`).
    The last block ends at row R and overlaps the one before it, whose
    rows it computes again from the same inputs, so no table is copied
    to pad it."""
    # a barrier keeps XLA from moving the lane pad past the gathers of a
    # pass it unrolls (one block): it then gathers 40-lane rows
    vals_p = jax.lax.optimization_barrier(vals_p)
    R, C = nbr.shape
    K = valid.shape[1]
    V = u_rows.shape[1]
    block = min(GATHER_ROWS, pl.cdiv(R, BLOCK_RV) * BLOCK_RV)
    if R < block:
        # pad rows: no valid slot, centre 0 -> 0 + 0 (dropped by the slice)
        pad = lambda a: jnp.pad(a, ((0, block - R), (0, 0)))
        nbr, valid, coeff, u_rows = pad(nbr), pad(valid), pad(coeff), pad(u_rows)
    n = nbr.shape[0]
    rows = lambda w: pl.BlockSpec((BLOCK_RV, w), lambda i: (i, 0))
    kernel = pl.pallas_call(
        _update_kernel_v,
        grid=(block // BLOCK_RV,),
        in_specs=[rows(vals_p.shape[1])] * C + [rows(V), rows(K), rows(K)],
        out_specs=rows(V),
        out_shape=jax.ShapeDtypeStruct((block, V), jnp.float32),
        name="stencil_update_v",
        interpret=interpret,
    )

    def body(i, out):
        start = jnp.minimum(i * block, n - block)
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, start, block)
        nbr_i = take(nbr)
        # an empty slot (-1) reads row 0, which the mask drops
        vals = [jnp.take(vals_p, nbr_i[:, c], axis=0, mode="clip") for c in range(C)]
        new = kernel(*vals, take(u_rows), take(valid).astype(jnp.int32), take(coeff))
        return jax.lax.dynamic_update_slice_in_dim(out, new, start, 0)

    out = jax.lax.fori_loop(0, pl.cdiv(n, block), body, jnp.zeros((n, V), jnp.float32))
    return out[:R]


def _lanes(vals_all, V):
    """(M, V) values padded to a whole 128 lanes, for the row gathers."""
    return jnp.pad(vals_all, ((0, 0), (0, pl.cdiv(V, 128) * 128 - V)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_stencil_update_v(
    vals_all: jax.Array,
    u_rows: jax.Array,
    nbr: jax.Array,
    valid: jax.Array,
    coeff: jax.Array,
    *,
    interpret: bool = True,
) -> jax.Array:
    """V-wide fused update, the face pass: ``vals_all`` (M, V),
    ``u_rows`` (R, V), tables (R, K); returns the (R, V) updated rows.

    A face-neighbour table has one valid slot a face on most rows: every
    row runs with the values of the first slot of each face only (K / g
    gathers a row, g = ``mesh.amr.face_group(K)``). A row is then
    bit-equal to :func:`stencil_update_ref` if no slot past the first of
    a face is valid, and every row is where g is 1; the other rows are
    the halo plan's finer-row list, which :func:`finer_update_v` runs
    again with every slot's values."""
    g = face_group(nbr.shape[1])
    return _rows_pass(_lanes(vals_all, u_rows.shape[1]), u_rows, nbr[:, ::g], valid, coeff,
                      interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def finer_update_v(
    vals_all: jax.Array,
    u_rows: jax.Array,
    out: jax.Array,
    rows: jax.Array,
    nbr: jax.Array,
    valid: jax.Array,
    coeff: jax.Array,
    *,
    interpret: bool = True,
) -> jax.Array:
    """The listed rows of the V-wide update: ``out`` (R, V) with each row
    of ``rows`` (fcap,) replaced by :func:`stencil_update_ref`'s value,
    run with all K slots' values in one block where fcap <=
    ``GATHER_ROWS``. ``rows`` is the halo plan's finer-row list
    (``mesh.halo.list_finer_rows``: ascending, -1 pads) and ``nbr`` /
    ``valid`` / ``coeff`` (fcap, K) the table rows it names."""
    if rows.shape[0] == 0:
        return out
    # a -1 pad reads row 0 and is dropped by the scatter
    fine = _rows_pass(_lanes(vals_all, u_rows.shape[1]),
                      jnp.take(u_rows, rows, axis=0, mode="clip"), nbr, valid, coeff, interpret)
    return out.at[rows].set(fine, mode="drop", wrap_negative_indices=False)
