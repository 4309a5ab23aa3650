"""Every Pallas kernel compiles for a TPU v5e at the sizes chip_smoke.py
(and, for the V-wide stencil, the miniAMR cell) runs, with no chip attached: the TPU compiler lowers the kernel for a
described v5e chip (``topologies.get_topology_desc``), which catches
what interpret mode cannot — layouts Mosaic refuses, scoped-VMEM
overflow, and padded HBM temporaries (a (n, 3) block padded to 128
lanes once made a 2^24-point key batch need an 8 GiB temporary).

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and it keeps it until it
exits, so only the test worker that runs this file may touch it."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import hilbert, morton, pair_force, stencil_update

N_POINTS = 1 << 24      # partition phase: keys of every point
MESH_ROWS = 1_397_674   # mesh phase: cells after the last refinement
PARTICLES, K_PAIR = 32768, 112   # particle phase: atoms, table width
AMR_ROWS, AMR_V = 1_400_064, 40   # miniAMR cell: rows a chip updates, fields
AMR_FINER = 35_840                # ... and its listed finer rows (fcap)

u32, i32, f32, b1 = jnp.uint32, jnp.int32, jnp.float32, jnp.bool_

CASES = {
    "morton": (lambda c: morton.morton_from_cells(c, 10, interpret=False),
               [((N_POINTS, 3), u32)]),
    "hilbert": (lambda c: hilbert.hilbert_from_cells(c, 10, interpret=False),
                [((N_POINTS, 3), u32)]),
    "stencil_update": (
        lambda v, u, n, m, c: stencil_update.fused_stencil_update(v, u, n, m, c, interpret=False),
        [((MESH_ROWS,), f32), ((MESH_ROWS,), f32), ((MESH_ROWS, 8), i32),
         ((MESH_ROWS, 8), b1), ((MESH_ROWS, 8), f32)]),
    "stencil_update_v": (
        lambda v, u, n, m, c, *finer: stencil_update.finer_update_v(
            v, u, stencil_update.fused_stencil_update_v(v, u, n, m, c, interpret=False),
            *finer, interpret=False),
        [((AMR_ROWS + 200_064, AMR_V), f32), ((AMR_ROWS, AMR_V), f32), ((AMR_ROWS, 24), i32),
         ((AMR_ROWS, 24), b1), ((AMR_ROWS, 24), f32), ((AMR_FINER,), i32),
         ((AMR_FINER, 24), i32), ((AMR_FINER, 24), b1), ((AMR_FINER, 24), f32)]),
    "pair_force": (
        lambda p, m, x, n, v, r: pair_force.fused_pair_accel(p, m, x, n, v, r, interpret=False),
        [((PARTICLES, 3), f32), ((PARTICLES,), f32), ((PARTICLES, 3), f32),
         ((PARTICLES, K_PAIR), i32), ((PARTICLES, K_PAIR), b1), ((), f32)]),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, specs = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    arg_bytes = sum(int(np.prod(s)) * np.dtype(d).itemsize for s, d in specs)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 4 * arg_bytes, (name, temp, arg_bytes)


def test_v_wide_passes_gather_full_lane_rows(one_chip):
    """Both passes of the V-wide update gather neighbour rows padded to
    128 lanes: XLA would otherwise move the pad of the one-block finer
    pass past its gathers and gather 40-lane rows. The only 40-lane
    gather is the listed rows' centres."""
    import re

    fn, specs = CASES["stencil_update_v"]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    text = jax.jit(fn).lower(*args).compile().as_text()
    gathers = re.findall(r"= f32\[(\d+),(\d+)\]\S* gather\(", text)
    assert gathers.count((str(AMR_FINER), "128")) == 24, gathers
    assert [g for g in gathers if g[1] != "128"] == [(str(AMR_FINER), str(AMR_V))], gathers
