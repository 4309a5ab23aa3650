"""Pallas kernels vs their pure-jnp definitions: shape sweeps in
interpret mode, bit-equal. (Compilation for the TPU is checked in
tests/test_tpu_compile.py.)"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sfc
from repro.kernels import hilbert as hk
from repro.kernels import morton as mk
from repro.kernels import ref


@pytest.mark.parametrize(
    "n,d,bits",
    [
        (100, 2, 16),
        pytest.param(5000, 2, 16, marks=pytest.mark.slow),
        (2048, 2, 8),
        (100, 3, 10),
        pytest.param(5000, 3, 10, marks=pytest.mark.slow),
        (4096, 3, 5),
        (333, 5, 6),
        pytest.param(2047, 7, 4, marks=pytest.mark.slow),
        (1000, 10, 3),
    ],
)
def test_morton_kernel_sweep(n, d, bits, rng):
    pts = jnp.asarray(rng.random((n, d)), jnp.float32)
    cells = sfc.quantize(pts, bits)
    out = mk.morton_from_cells(cells, bits)
    expect = ref.morton_from_cells(cells, bits)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize(
    "n,d,bits",
    [
        (100, 2, 16),
        pytest.param(3000, 2, 12, marks=pytest.mark.slow),
        (100, 3, 10),
        (3000, 3, 10),
        pytest.param(511, 4, 8, marks=pytest.mark.slow),
        (777, 6, 5),
        (1000, 10, 3),
    ],
)
def test_hilbert_kernel_sweep(n, d, bits, rng):
    pts = jnp.asarray(rng.random((n, d)), jnp.float32)
    cells = sfc.quantize(pts, bits)
    out = hk.hilbert_from_cells(cells, bits)
    expect = ref.hilbert_from_cells(cells, bits)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


# ---------------------------------------------------------------------------
# fused stencil row update
# ---------------------------------------------------------------------------

from _hypothesis_compat import given, settings, strategies as st  # noqa: E402
from repro.kernels import stencil_update as su  # noqa: E402


def _stencil_case(rng, R, K, V, ghost_frac, invalid_rows):
    """Random row tables: V total values (owned+ghost), ghost_frac of
    neighbor slots pointing past the owned region, some rows all-invalid
    (pads) — the layouts the distributed executors feed the kernel."""
    vals_all = jnp.asarray(rng.standard_normal(V).astype(np.float32))
    u_rows = jnp.asarray(rng.standard_normal(R).astype(np.float32))
    nbr = rng.integers(0, V, (R, K))
    valid = rng.random((R, K)) < 0.8
    ghost = rng.random((R, K)) < ghost_frac
    cap = max(V // 2, 1)
    nbr = np.where(ghost, np.minimum(nbr % V, V - 1), nbr % cap)
    if invalid_rows:
        valid[rng.integers(0, R, max(R // 4, 1))] = False
    coeff = np.where(valid, rng.random((R, K)).astype(np.float32), 0.0)
    return (
        vals_all,
        u_rows,
        jnp.asarray(nbr.astype(np.int32)),
        jnp.asarray(valid),
        jnp.asarray(coeff.astype(np.float32)),
    )


@settings(max_examples=12, deadline=None)
@given(
    R=st.sampled_from([1, 7, 64, 1023, 1024, 1025]),
    K=st.sampled_from([4, 8]),
    ghost_frac=st.sampled_from([0.0, 0.3, 0.9]),
    invalid_rows=st.booleans(),
    seed=st.integers(0, 7),
)
def test_fused_stencil_update_bit_equal(R, K, ghost_frac, invalid_rows, seed):
    """Pallas kernel (interpret) vs the jnp definition: bit-equal across
    block-boundary row counts, K widths, ghost-heavy neighbor tables and
    all-invalid (pad) rows."""
    rng = np.random.default_rng(seed)
    V = max(2 * R, 8)
    case = _stencil_case(rng, R, K, V, ghost_frac, invalid_rows)
    expect = np.asarray(su.stencil_update_ref(*case))
    got = np.asarray(su.fused_stencil_update(*case, interpret=True))
    np.testing.assert_array_equal(got, expect)


def test_fused_stencil_update_pad_rows_identity():
    """An all-invalid row passes its center through up to +0.0 — pad
    slots must not acquire spurious values from the masked lanes."""
    rng = np.random.default_rng(3)
    vals_all, u_rows, nbr, valid, coeff = _stencil_case(rng, 16, 4, 32, 0.5, False)
    valid = jnp.zeros_like(valid)
    out = np.asarray(
        su.fused_stencil_update(vals_all, u_rows, nbr, valid, coeff, interpret=True)
    )
    np.testing.assert_array_equal(out, np.asarray(u_rows) + np.float32(0.0))
