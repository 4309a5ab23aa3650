"""Cells cut to sizes a CPU test run holds (same code paths as the chip's)."""
from __future__ import annotations

import harness


def cell(name: str) -> "harness.Cell":
    c = harness.find_cell(name)
    c.config.update(points=4096, parts=8)
    c.config["engine"].update(max_depth=8)
    c.traffic.update(churn_points=256)
    return c
