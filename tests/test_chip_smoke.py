"""chip_smoke.py's contract off the chip, and the host references its
checks compare against."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.core import knapsack, queries  # noqa: E402
from repro.core import curve_index as ci  # noqa: E402


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return dict(env, **extra)


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_exits_nonzero_without_a_tpu(where, tmp_path):
    """No TPU: non-zero exit before any phase and no result line — also
    from a directory holding the script and nothing else of the repo."""
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    out = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                         env=_cpu_env(), capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout and "[partition]" not in out.stdout


@pytest.mark.parametrize("n,parts", [(1000, 7), (4096, 64), (50_000, 64)])
def test_knapsack_reference_is_the_device_knapsack_when_exact(n, parts, rng):
    """Small integer weights: every float32 prefix is exact, so the
    float64 host knapsack and ``slice_weighted_curve`` cut identically."""
    w = rng.integers(1, 4, n).astype(np.float32)
    got = np.asarray(knapsack.slice_weighted_curve(jnp.asarray(w), parts))
    ref, prefix = chip_smoke.knapsack_reference(w, parts)
    np.testing.assert_array_equal(got, ref)
    assert chip_smoke.cut_shift(got, ref, prefix, w, parts) == 0.0
    loads = np.bincount(ref, weights=w.astype(np.float64), minlength=parts)
    assert chip_smoke._spread(loads) <= 2 * w.max()


def test_host_references_match_queries(rng):
    """The numpy point location and kNN window agree with ``queries`` on
    hits, misses and a duplicate-heavy key run."""
    dup = np.full((100, 3), 0.5, np.float32) + rng.random((100, 3)).astype(np.float32) * 1e-6
    pts = jnp.asarray(np.concatenate([dup, rng.random((1948, 3)).astype(np.float32)]))
    idx = queries.build_index(pts, bucket_size=16)
    q = jnp.concatenate([pts[::8], jnp.asarray(rng.random((64, 3)), jnp.float32)])
    h = chip_smoke._HostIndex(idx)
    qk = np.asarray(ci.query_keys(idx, q))
    got = queries.point_location(idx, q, bucket_cap=64)
    want = chip_smoke.host_point_location(h, np.asarray(q), qk, 64)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w)
    d, _ = queries.knn(idx, q, k=4)
    d_ref = chip_smoke.host_knn_distances(h, np.asarray(q), qk, 4)
    assert chip_smoke._rel_err(np.asarray(d, np.float64), d_ref) <= 1e-5


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_compile_cache_dir(env_dir, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` wins and is left to JAX; otherwise
    the cache goes to ``<repo>/.jax_cache``."""
    extra = {} if env_dir is None else {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    code = ("import jax; from repro.launch.cache import enable_compile_cache as e; "
            "print(e()); print(jax.config.jax_compilation_cache_dir)")
    env = _cpu_env(PYTHONPATH=str(ROOT / "src"), **extra)
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=120, check=True)
    returned, configured = out.stdout.split("\n")[:2]
    want = str(tmp_path) if env_dir else str(ROOT / ".jax_cache")
    assert returned == want and configured == want
