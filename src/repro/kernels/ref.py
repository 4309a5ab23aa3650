"""Pure-jnp oracles for every Pallas kernel (tested in
tests/test_kernels.py across shape/dtype sweeps)."""
from __future__ import annotations

import jax

from repro.core import sfc as _sfc


def morton_from_cells(cells: jax.Array, bits: int) -> jax.Array:
    return _sfc.morton_key_from_cells(cells, bits)


def hilbert_from_cells(cells: jax.Array, bits: int) -> jax.Array:
    return _sfc.hilbert_key_from_cells(cells, bits)

