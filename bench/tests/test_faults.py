"""The control and every planted fault make ``correct`` false; a sound run
makes it true. The harness's look for a chip is skipped; the rest of a
run is driven as on the chip, at a size a CPU test run holds."""
import pytest

import faults
import harness
import tiny

DRIFT_MODES = ["control", "stale", "half", "altered", "misfiled", "disorder"]


def _run(cell, mode=None):
    if mode is None:
        return harness.run_cell(cell, 2**31 + 5, 1.0, False)
    with faults.substitute(cell.traffic["driver"], mode):
        return harness.run_cell(cell, 2**31 + 5, 1.0, False)


def test_drift_sound_run_is_correct():
    out = _run(tiny.cell("points3d.drift"))
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def test_drift_run_with_controller_rebuilds_is_correct(monkeypatch):
    """Rebuilds fired by the controller inside the window re-key the
    curve; the steps after one are checked against it."""
    from repro.core import dynamic

    calls = iter(range(10**6))
    monkeypatch.setattr(dynamic.AmortizedController, "observe",
                        lambda self, timeop, num_buckets: next(calls) % 3 == 2)
    cell = tiny.cell("points3d.drift")
    cell.traffic.update(check_per_kind=8)
    out = _run(cell)
    assert out["correct"] and out["checks"]["curve_descents"]["value"] == 0


@pytest.mark.parametrize("mode", DRIFT_MODES)
def test_drift_fault_is_not_correct(mode):
    out = _run(tiny.cell("points3d.drift"), mode)
    assert not out["correct"]
