"""A cell, its configuration, traffic, driver and metrics, found from files."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import harness

ROOT = harness.ROOT
SPEC = harness.load_json(ROOT / "BENCHMARK.json")


def test_every_cell_resolves_from_its_files():
    for w in SPEC["workloads"]:
        cell = harness.find_cell(w["name"])
        assert cell.chips == w["chips"]
        assert hasattr(harness.driver_for(cell), "run")
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names
            assert hasattr(harness.load_module(harness.BENCH / "metrics" / f"{m['name']}.py"), "read")


def test_a_cell_added_by_an_entry_alone_resolves():
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "points3d.drift_again", "config": "points3d",
                              "traffic": "drift", "chips": 1, "why": "test"})
    cell = harness.find_cell("points3d.drift_again", spec)
    assert cell.config["points"] == harness.find_cell("points3d.drift").config["points"]
    # metrics listed for named cells only leave the new cell with setup_s
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "points3d.drift", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(ROOT)
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
