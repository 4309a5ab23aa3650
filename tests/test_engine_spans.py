"""The engine's spans and sync counters, and the benchmark readers of them.

A small tree-mode ``Repartitioner`` runs a few plain steps and one churn
step under ``jax.profiler.trace`` (sizes of ``bench/tests/tiny.py``); the
trace is reduced by ``bench/devtrace.py`` as a ``--trace 1`` run of the
benchmark reduces it, and read by the ``drift.*`` per-layer metrics.
"""
import glob
import importlib.util
import math
import os
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import partitioner as pt
from repro.core.repartition import Repartitioner

BENCH = Path(__file__).resolve().parents[1] / "bench"
N, PARTS, DEPTH, CHURN, PLAIN_STEPS = 4096, 8, 8, 256, 3
READERS = ("drift.timeop_ms", "drift.slice_ms", "drift.plan_ms",
           "drift.step_sync_ms", "drift.step_syncs")
PHASES = ("repartition.timeop", "repartition.slice", "repartition.plan")


def _load(path: Path):
    name = "bench_" + path.stem.replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = sys.modules[name] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


devtrace = _load(BENCH / "devtrace.py")


def _engine(seed=0):
    rng = np.random.default_rng(seed)
    pts = jnp.asarray(rng.random((N, 3)), jnp.float32)
    w = jnp.asarray(0.5 + rng.random(N), jnp.float32)
    rp = Repartitioner(pts, w, PARTS, pt.PartitionerConfig(use_tree=True),
                       capacity=N, max_depth=DEPTH)
    return rng, w, rp


def _drift(rng, w):
    return w * jnp.asarray(1.0 + rng.random(N), jnp.float32)


def _sync_delta(rp, fn):
    syncs, nbytes = rp.stats.host_syncs, rp.stats.host_pull_bytes
    out = fn()
    return out, rp.stats.host_syncs - syncs, rp.stats.host_pull_bytes - nbytes


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Plain steps and one churn step inside a traced ``bench.window``;
    the loaded profile and each plain step's sync and byte counts."""
    rng, w, rp = _engine()
    # warm every shape outside the trace
    rp.update_weights(_drift(rng, w))
    rp.step()
    slots = jnp.asarray(np.sort(rng.choice(N, CHURN, replace=False)).astype(np.int32))
    rp.delete(slots)
    rp.insert(jnp.asarray(rng.random((CHURN, 3)), jnp.float32), jnp.ones(CHURN, jnp.float32))
    rp.step()
    jax.block_until_ready(rp.part)

    trace_dir = str(tmp_path_factory.mktemp("engine_trace"))
    plain = []
    with jax.profiler.trace(trace_dir):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(PLAIN_STEPS):
                rp.update_weights(_drift(rng, w))
                step, syncs, nbytes = _sync_delta(rp, rp.step)
                assert step.kind == "incremental"
                plain.append((syncs, nbytes))
            slots = jnp.asarray(np.sort(rng.choice(N, CHURN, replace=False)).astype(np.int32))
            rp.delete(slots)
            rp.insert(jnp.asarray(rng.random((CHURN, 3)), jnp.float32),
                      jnp.ones(CHURN, jnp.float32))
            rp.step().part.block_until_ready()
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    assert files
    prof = devtrace.Profile.from_data(jax.profiler.ProfileData.from_file(sorted(files)[-1]))
    return SimpleNamespace(profile=prof, plain=plain)


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_each_step_holds_one_of_each_phase_and_its_syncs(traced):
    spans = traced.profile.spans
    steps = [sp for sp in spans if sp[0] == "repartition.step"]
    assert len(steps) == PLAIN_STEPS + 1
    for step in steps:
        inner = [sp for sp in spans if sp is not step and _inside(sp, step)]
        for phase in PHASES:
            assert [sp[0] for sp in inner].count(phase) == 1, phase
        phases = [sp for sp in inner if sp[0] in PHASES]
        syncs = [sp for sp in inner if sp[0] == "repartition.sync"]
        assert len(syncs) == 3
        assert all(any(_inside(s, p) for p in phases) for s in syncs)
    for churn in ("repartition.delete", "repartition.insert"):
        [op] = [sp for sp in spans if sp[0] == churn]
        assert not any(_inside(op, step) for step in steps)
    [insert] = [sp for sp in spans if sp[0] == "repartition.insert"]
    assert sum(1 for sp in spans if sp[0] == "repartition.sync" and _inside(sp, insert)) == 1


def test_step_syncs_reads_three(traced):
    assert _load(BENCH / "metrics" / "drift.step_syncs.py").read(traced) == 3.0


@pytest.mark.parametrize("name", READERS)
def test_reader_is_positive_on_engine_spans(traced, name):
    value = _load(BENCH / "metrics" / f"{name}.py").read(traced)
    assert value is not None and math.isfinite(value) and value > 0


@pytest.mark.parametrize("name", READERS)
def test_reader_is_none_without_engine_spans(name):
    reader = _load(BENCH / "metrics" / f"{name}.py")
    bare = devtrace.Profile({}, [("bench.window", 0, 1000), ("drift.weights", 10, 20)], (0, 1000))
    assert reader.read(SimpleNamespace(profile=bare)) is None
    assert reader.read(SimpleNamespace(profile=None)) is None


def test_plain_step_syncs_and_bytes(traced):
    # the fallback's (P,) loads with the bucket count, the slice's loads,
    # the (P, P) counts: nothing that grows with the point count
    want = 8 * PARTS + 4 * PARTS**2 + 4
    assert traced.plain == [(3, want)] * PLAIN_STEPS


def test_churn_syncs_once_and_counts_refreshes_when_read():
    rng, _, rp = _engine(1)
    before = rp.stats.summary_refreshes
    slots = jnp.asarray(np.sort(rng.choice(N, CHURN, replace=False)).astype(np.int32))
    _, syncs_delete, _ = _sync_delta(rp, lambda: rp.delete(slots))
    _, syncs_insert, _ = _sync_delta(rp, lambda: rp.insert(
        jnp.asarray(rng.random((CHURN, 3)), jnp.float32), jnp.ones(CHURN, jnp.float32)))
    assert (syncs_delete, syncs_insert) == (0, 1)   # the insert's free-capacity guard
    refreshes, syncs_read, _ = _sync_delta(rp, lambda: rp.stats.summary_refreshes)
    assert refreshes - before == 2 * CHURN and syncs_read == 1
    again, syncs_again, _ = _sync_delta(rp, lambda: rp.stats.summary_refreshes)
    assert (again, syncs_again) == (refreshes, 0)   # folded into a host int


def test_refresh_count_folds_before_its_device_sum_could_overflow():
    _, _, rp = _engine(2)
    rp.delete(jnp.arange(10, dtype=jnp.int32))
    rp.stats._unread_bound = 2**31 - 1 - CHURN + 1   # one more delete batch would pass int32
    syncs = rp.stats.host_syncs
    rp.delete(jnp.arange(10, 10 + CHURN, dtype=jnp.int32))
    assert rp.stats.host_syncs == syncs + 1 and rp.stats._unread_bound == CHURN
    assert rp.stats.summary_refreshes == 10 + CHURN


def test_churn_after_the_first_compiles_nothing():
    """A churn step after the first reuses every program, the device sum
    of the refresh count included: nothing compiles in a measured window
    that was warmed up with one churn step."""
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    rng, _, rp = _engine(3)

    def churn():
        slots = jnp.asarray(np.sort(rng.choice(N, CHURN, replace=False)).astype(np.int32))
        rp.delete(slots)
        rp.insert(jnp.asarray(rng.random((CHURN, 3)), jnp.float32), jnp.ones(CHURN, jnp.float32))
        jax.block_until_ready(rp.dps)

    churn()
    warm = len(compiles)
    churn()
    churn()
    assert len(compiles) == warm
