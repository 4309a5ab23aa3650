"""jit'd public wrappers around the Pallas kernels.

Every wrapper compiles its kernel for the TPU when the default backend is
a TPU and runs it in Pallas interpret mode anywhere else (CPU tests).
The choice is made per call from ``jax.default_backend()``, never at
import, so importing this module does not initialize a backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import sfc as _sfc
from repro.kernels import hilbert as _hil
from repro.kernels import morton as _mor
from repro.kernels import pair_force as _pf
from repro.kernels import stencil_update as _su


def interpret() -> bool:
    """Interpret the kernels unless they run on a TPU."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# SFC key cache (repartitioning hot path)
#
# The incremental repartitioner re-slices the weighted curve many times
# between geometry changes; key generation is the dominant cost it can
# skip. Callers tag a key batch with an explicit ``token`` (bumped by the
# owner whenever the underlying points or quantization frame change) and
# the cache returns the stored keys for (token, curve, bits, stats,
# shape). Invalidation is explicit — there is no content hashing, so a
# caller that mutates points without bumping its token gets stale keys.
# ---------------------------------------------------------------------------

_KEY_CACHE: dict[tuple, jax.Array] = {}
_KEY_CACHE_STATS = {"hits": 0, "misses": 0}


def invalidate_key_cache(token=None) -> int:
    """Drop cached keys. ``token=None`` clears everything; otherwise only
    entries generated under that token. Returns the number of entries
    dropped."""
    if token is None:
        n = len(_KEY_CACHE)
        _KEY_CACHE.clear()
        return n
    drop = [k for k in _KEY_CACHE if k[0] == token]
    for k in drop:
        del _KEY_CACHE[k]
    return len(drop)


def key_cache_stats() -> dict:
    return dict(_KEY_CACHE_STATS, entries=len(_KEY_CACHE))


def cached_sfc_key(
    points: jax.Array,
    *,
    token,
    curve: str = "hilbert",
    bits: int | None = None,
    stats: str = "geometric",
    use_pallas: bool = False,
    lo: jax.Array | None = None,
    hi: jax.Array | None = None,
) -> jax.Array:
    """Key generation with token-based caching (see module note above).

    ``lo``/``hi`` quantize against a *fixed frame* instead of the data's
    own bounding box — the repartitioning engine's frozen-frame path,
    where the frame (and hence the cached keys) only changes when the
    owner bumps ``token``. The frame arrays are deliberately NOT part of
    the cache key: they are a function of the token by contract.
    """
    ck = (token, curve, bits, stats, points.shape, bool(use_pallas), lo is not None)
    hit = _KEY_CACHE.get(ck)
    if hit is not None:
        _KEY_CACHE_STATS["hits"] += 1
        return hit
    _KEY_CACHE_STATS["misses"] += 1
    if lo is not None:
        b = bits if bits is not None else _sfc.max_bits_per_dim(points.shape[1])
        # the ONE frozen-frame quantization convention (sfc.cells_in_frame)
        cells = _sfc.cells_in_frame(points, lo, hi, b)
        if use_pallas:
            fn = _mor.morton_from_cells if curve == "morton" else _hil.hilbert_from_cells
            keys = fn(cells, b, interpret=interpret())
        else:
            fn = (
                _sfc.morton_key_from_cells
                if curve == "morton"
                else _sfc.hilbert_key_from_cells
            )
            keys = fn(cells, b)
    elif use_pallas:
        fn = morton_key if curve == "morton" else hilbert_key
        keys = fn(points, bits, stats=stats)
    else:
        fn = _sfc.morton_key if curve == "morton" else _sfc.hilbert_key
        keys = fn(points, bits, stats=stats)
    _KEY_CACHE[ck] = keys
    return keys


def morton_key(points: jax.Array, bits: int | None = None, *, stats: str = "geometric") -> jax.Array:
    n, d = points.shape
    if bits is None:
        bits = _sfc.max_bits_per_dim(d)
    cells = _sfc.quantize(points, bits, stats)
    return _mor.morton_from_cells(cells, bits, interpret=interpret())


def hilbert_key(points: jax.Array, bits: int | None = None, *, stats: str = "geometric") -> jax.Array:
    n, d = points.shape
    if bits is None:
        bits = _sfc.max_bits_per_dim(d)
    cells = _sfc.quantize(points, bits, stats)
    return _hil.hilbert_from_cells(cells, bits, interpret=interpret())


def stencil_update(
    vals_all: jax.Array,
    u_rows: jax.Array,
    nbr: jax.Array,
    valid: jax.Array,
    coeff: jax.Array,
    *,
    use_pallas: bool = False,
    finer: tuple | None = None,
) -> jax.Array:
    """Fused stencil row update (gather + mask + coeff*(v-u) + K-reduce).

    The mesh stencil executors' inner loop; ``u_rows`` is (R,) for one
    field or (R, V) for V fields per cell. ``use_pallas`` dispatches the
    Pallas kernel; the default jnp path is bit-equal by construction —
    both evaluate `kernels.stencil_update.stencil_update_ref`'s
    expression. The V-wide kernel runs in two passes: every row with one
    value a face (`kernels.stencil_update.fused_stencil_update_v`), then
    the rows of ``finer`` = (rows, nbr, valid, coeff), the halo plan's
    finer-row list and the table rows it names, with every slot's
    (`kernels.stencil_update.finer_update_v`). It needs the list where
    the table has sub-slots a face (``mesh.amr.face_group(K) > 1``); the
    other forms take none.
    """
    if use_pallas and u_rows.ndim == 2:
        out = _su.fused_stencil_update_v(vals_all, u_rows, nbr, valid, coeff,
                                         interpret=interpret())
        if finer is None:
            if _su.face_group(nbr.shape[1]) > 1:
                raise ValueError("the V-wide kernel needs the finer-row list of a face table")
            return out
        return _su.finer_update_v(vals_all, u_rows, out, *finer, interpret=interpret())
    if use_pallas:
        return _su.fused_stencil_update(vals_all, u_rows, nbr, valid, coeff,
                                        interpret=interpret())
    return _su.stencil_update_ref(vals_all, u_rows, nbr, valid, coeff)


def pair_accel(
    pos_all: jax.Array,
    mass_all: jax.Array,
    x_rows: jax.Array,
    nbr: jax.Array,
    valid: jax.Array,
    rc2,
    *,
    use_pallas: bool = False,
) -> jax.Array:
    """Fused pairwise short-range acceleration (gather + cutoff weight +
    K-reduce) — the particle executors' inner loop. ``use_pallas``
    dispatches the Pallas kernel; the default jnp path is bit-equal by
    construction — both evaluate `kernels.pair_force.pair_accel_ref`'s
    expression.
    """
    if use_pallas:
        return _pf.fused_pair_accel(
            pos_all, mass_all, x_rows, nbr, valid,
            jnp.asarray(rc2, jnp.float32), interpret=interpret(),
        )
    return _pf.pair_accel_ref(
        pos_all, mass_all, x_rows, nbr, valid, jnp.asarray(rc2, jnp.float32)
    )
