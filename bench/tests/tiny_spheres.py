"""The spheres cell cut to a size a CPU test run holds (same code paths
as the chip's, on 4 fake devices)."""
from __future__ import annotations

import harness


def cell() -> "harness.Cell":
    """miniAMR's two spheres on 2^3 root blocks of 2^3 cells, two levels
    of refinement, 6 variables."""
    c = harness.find_cell("miniamr3d.spheres")
    c.config.update(npx=2, npy=2, npz=2, nx=2, ny=2, nz=2, num_refine=2, block_change=2,
                    num_vars=6, stages_per_ts=4, checksum_freq=2, refine_freq=3)
    c.config["engine"].update(bucket_size=8, max_depth=10)
    return c
