"""The miniAMR cell on the CPU at a tiny size: the plain reference
agrees with the program, a sound run is correct, and the bfloat16
control and every planted fault are not. The runs need 4 devices, so
they go in one subprocess with 4 fake host devices."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import harness
import ref_amr
import tiny_spheres

MODES = ["control", "ghost", "unrefined", "shifted"]


@pytest.fixture(scope="module")
def runs():
    code = textwrap.dedent(f"""
        import json, sys
        sys.path[:0] = [{str(harness.ROOT / "src")!r}, {str(harness.BENCH)!r},
                        {str(harness.BENCH / "tests")!r}]
        import faults_spheres, harness, tiny_spheres
        for mode in ["sound"] + {MODES!r}:
            cell = tiny_spheres.cell()
            if mode == "sound":
                out = harness.run_cell(cell, 2**31 + 5, 1.0, False)
            else:
                with faults_spheres.substitute("amr_spheres", mode):
                    out = harness.run_cell(cell, 2**31 + 5, 1.0, False)
            print("RESULT " + json.dumps(dict(out, mode=mode)), flush=True)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(x[7:]) for x in p.stdout.splitlines() if x.startswith("RESULT ")]
    return {r["mode"]: r for r in lines}


def test_spheres_sound_run_is_correct(runs):
    out = runs["sound"]
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    assert set(out["checks"]) == {"mesh_mismatch", "nbr_mismatch", "owned_once",
                                  "field_err", "checksum_gap"}
    assert list(out)[-2] == "checks" and "step_ms" in out["metrics"]


@pytest.mark.parametrize("mode", MODES)
def test_spheres_fault_is_not_correct(runs, mode):
    assert not runs[mode]["correct"]


def test_spheres_bfloat16_control_fails_field_err_tenfold(runs):
    c = runs["control"]["checks"]["field_err"]
    assert c["value"] >= 10 * c["limit"]


def test_reference_agrees_with_program_at_a_tiny_size():
    """Meshes, adjacency, one adapt's transfer and the 7-point stages of
    the reference against the program's (float32, bit-equal executors)."""
    import jax.numpy as jnp

    from repro.mesh import amr, simulate
    from repro.mesh import stencil

    cell = tiny_spheres.cell()
    cfg, tr = cell.config, cell.traffic
    drv = harness.load_module(harness.BENCH / "drivers" / "amr_spheres.py")
    g = drv.geometry(cfg)
    a, b_from_a, b, a_from_b = simulate.miniamr_events(
        drv.objects_of(cfg), tr["t_from"], tr["t_to"], root_level=g["root_level"],
        block_bits=g["block_bits"], num_refine=cfg["num_refine"], block_change=cfg["block_change"])
    geo = ref_amr.Geometry(cfg)
    ref_a, ref_b = ref_amr.pingpong_meshes(geo, tr["t_from"], tr["t_to"])
    bm_a, bm_b = ref_amr.BlockMesh(geo, ref_a), ref_amr.BlockMesh(geo, ref_b)
    assert ref_amr.mesh_mismatch(bm_a, a.mesh.level, a.mesh.ij) == 0
    assert ref_amr.mesh_mismatch(bm_b, b.mesh.level, b.mesh.ij) == 0
    assert ref_amr.nbr_mismatch(geo, a.mesh.level, a.mesh.ij, a.nbr) == 0
    assert ref_amr.nbr_mismatch(geo, b.mesh.level, b.mesh.ij, b.nbr) == 0
    # a neighbour table with one entry dropped is caught
    bad = a.nbr.copy()
    bad[np.argmax((bad >= 0).sum(1)), 0] = -1
    assert ref_amr.nbr_mismatch(geo, a.mesh.level, a.mesh.ij, bad) == 1

    u = np.random.default_rng(0).random((a.mesh.n, 4)).astype(np.float32)
    # the adapt a -> b, then 3 stages on b
    prog = amr.apply_transfers(u, b_from_a.transfer)
    prog = np.asarray(stencil.reference_stencil(prog, b.nbr, b.nbr >= 0, b.coeff, 3))
    blocks = bm_a.to_blocks(a.mesh.level, a.mesh.ij, u.astype(np.float64))
    d = ref_amr.adapt(geo, {k: blocks[i] for i, k in enumerate(bm_a.keys)}, tr["t_to"])
    assert sorted(d) == bm_b.keys
    ub = np.stack([d[k] for k in bm_b.keys])
    st = ref_amr.Stencil(bm_b, ref_b)
    for _ in range(3):
        ub = st.stage(ub)
    want = bm_b.from_blocks(b.mesh.level, b.mesh.ij, ub)
    err = np.max(np.abs(prog - want)) / np.max(np.abs(want))
    assert err < cfg["limits"]["field_err"] / 10
    # the same stages in bfloat16 read over the limit
    bf = np.asarray(stencil.reference_stencil(
        jnp.asarray(amr.apply_transfers(u, b_from_a.transfer), jnp.bfloat16).astype(jnp.float32),
        b.nbr, b.nbr >= 0, b.coeff, 3))
    assert np.max(np.abs(bf - want)) / np.max(np.abs(want)) > cfg["limits"]["field_err"]


def test_roofline_reads_the_kernel_bytes_over_its_time():
    """The V-wide kernel's events, named as the trace names the HLO
    instruction, give bytes from their own operand shapes."""
    from types import SimpleNamespace

    import devtrace
    import kernel_bytes

    rows = ", ".join(["f32[65536,128]{1,0}"] * 24)
    name = ("%stencil_update_v.3 = f32[65536,40]{1,0:T(8,128)} custom-call(%a, %b, %c, %d), "
            'custom_call_target="tpu_custom_call", operand_layout_constraints='
            f"{{{rows}, f32[65536,40]{{1,0}}, s32[65536,24]{{1,0}}, "
            "f32[65536,24]{1,0}}, frontend_attributes={kernel_metadata={}}")
    per_call = 4 * 65536 * (24 * 128 + 40 + 24 + 24 + 40)
    assert kernel_bytes.stencil_update_v(name) == per_call
    face = name.replace(rows, ", ".join(["f32[65536,128]{1,0}"] * 6))
    assert kernel_bytes.stencil_update_v(face) == 4 * 65536 * (6 * 128 + 40 + 24 + 24 + 40)
    assert kernel_bytes.stencil_update_v("%stencil_update_v.3 = f32[8,40]") is None
    ops = {"/device:TPU:0": [devtrace.Op(name, 100, 1e6), devtrace.Op("%fusion.2 = f32[8]", 0, 5)]}
    prof = devtrace.Profile(ops, [("bench.window", 0, 10**9)], (0, 10**9))
    run = SimpleNamespace(profile=prof, devices=[SimpleNamespace(device_kind="TPU v5 lite")])
    read = harness.load_module(harness.BENCH / "metrics" / "stencil_update_roofline.py").read
    want = 100 * (per_call / 819e9) / 1e-3
    assert read(run) == pytest.approx(want)
    prof.ops = {}
    assert read(run) is None
