"""Benchmark harness, driven by the names in ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration and a traffic mix.
The harness finds everything else by name, so a later cell, traffic mix
or per-layer metric is added with files of its own and no edit here:

* configuration: the ``file`` its ``configs`` entry gives (JSON);
* traffic mix: ``bench/traffic/<traffic>.json``, whose ``driver`` key
  names the general generator ``bench/drivers/<driver>.py`` that reads it;
* per-layer metric: ``bench/metrics/<metric name>.py``, a reader with
  ``read(run) -> float | None`` (None: nothing to read, left out).

A driver exposes ``run(run: Run) -> Outcome``. It builds its inputs from
``run.seed``, warms up every shape it will use, measures inside
``with run.window():`` for ``run.seconds``, then checks what the window
produced against the plain references under ``bench/``.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# JAX's persistent compilation cache: a fixed directory inside the
# checkout (the path is part of the cache key; one that moves never hits)
CACHE_DIR = ROOT / ".bench_jax_cache"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list


def _listed(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, spec: dict | None = None) -> Cell:
    """Resolve a workload name into its files and metric entries."""
    spec = spec or load_json(ROOT / "BENCHMARK.json")
    wl = {w["name"]: w for w in spec["workloads"]}
    if name not in wl:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    w = wl[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"] if _listed(m, name)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if m["moves"] in e2e_names and _listed(m, name)]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=load_json(ROOT / conf["file"]),
        traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        end_to_end=e2e,
        per_layer=layer,
    )


def driver_for(cell: Cell):
    return load_module(BENCH / "drivers" / f"{cell.traffic['driver']}.py")


def require_chips(chips: int) -> str | None:
    """None when JAX sees a TPU with at least ``chips`` devices, else why not."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return f"needs a TPU, JAX found {devs[0].platform!r}"
    if len(devs) < chips:
        return f"needs {chips} chips, JAX found {len(devs)}"
    return None


def enable_compile_cache() -> None:
    import jax

    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # every program, however quick to compile, comes from the cache in
    # later runs, so set-up does the same work each time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Programs obtained by the backend, from jax.monitoring: each one is
    compiled or loaded from the persistent cache (``cache_loads``)."""

    def __init__(self):
        import jax

        self.programs = 0
        self.cache_loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        # recorded around compile-or-load, so a cache load counts here too
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_loads += 1


@dataclass
class Check:
    """One number compared with its limit: correct needs value <= limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return not math.isnan(self.value) and self.value <= self.limit


@dataclass
class Outcome:
    end_to_end: dict          # metric name -> value, from the window
    attempted: int            # answers the window produced
    failed: int               # checked answers that were wrong
    checks: list              # [Check]


@dataclass
class Run:
    """One run of one cell: what a driver reads and what it records."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t0: float = field(default_factory=time.perf_counter)
    setup_s: float | None = None
    window_s: float | None = None
    window_compiles: int | None = None
    memory_peak_bytes: int | None = None
    profile: object = None    # devtrace.Profile of the window (--trace 1)
    layer: dict = field(default_factory=dict)   # raw readings for metric readers
    counter: CompileCounter = field(default_factory=CompileCounter)

    @property
    def devices(self):
        import jax

        return jax.devices()[: self.cell.chips]

    def span(self, name: str):
        """A host span in the profiler's trace (cheap when not tracing)."""
        import jax

        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        """The measured window. Set-up ends where it starts; the device's
        peak memory is read where it ends, before any reference runs."""
        import jax

        from devtrace import Profile

        self.setup_s = time.perf_counter() - self.t0
        before = self.counter.programs
        print(f"bench: set-up programs={before} of them cache_loads="
              f"{self.counter.cache_loads}", file=sys.stderr)
        tmp = tempfile.mkdtemp(prefix="bench_trace_") if self.trace else None
        if tmp:
            jax.profiler.start_trace(tmp)
        start = time.perf_counter()
        try:
            with self.span("bench.window"):
                yield
        finally:
            self.window_s = time.perf_counter() - start
            if tmp:
                jax.profiler.stop_trace()
        self.window_compiles = self.counter.programs - before
        self.memory_peak_bytes = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in self.devices
        )
        if tmp:
            try:
                self.profile = Profile.load(tmp)
            finally:
                shutil.rmtree(tmp, ignore_errors=True)


def p95(values) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), 95))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t0: float | None = None) -> dict:
    """Run one cell and assemble the result line (device numbers are only
    meaningful where ``require_chips`` passed). ``t0``: when the process
    started, so that set-up counts from there."""
    import jax

    run = Run(cell, seed, seconds, trace, t0=t0 or time.perf_counter())
    outcome = driver_for(cell).run(run)

    metrics = {}
    if trace:
        for m in cell.per_layer:
            value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        values = dict(outcome.end_to_end, setup_s=run.setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}

    dev = run.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(run.devices), "memory_peak_bytes": run.memory_peak_bytes}
    out = {
        "correct": bool(outcome.checks) and outcome.failed == 0
        and all(c.ok for c in outcome.checks),
        "attempted": int(outcome.attempted),
        "failed": int(outcome.failed),
        "metrics": metrics,
        "device": device,
    }
    if trace and run.profile is not None and run.profile.ops:
        device["busy_s"] = run.profile.busy_s(len(run.devices))
        device["window_s"] = run.profile.window_s
        out["breakdown"] = run.profile.breakdown()
    print(f"bench: setup_s={run.setup_s} window_s={run.window_s} "
          f"window_compiles={run.window_compiles} jax={jax.__version__}", file=sys.stderr)
    for c in outcome.checks:
        print(f"check {c.name}: {c.value} (limit {c.limit}) {'ok' if c.ok else 'FAILED'}",
              file=sys.stderr)
    sys.stderr.flush()
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in outcome.checks}
    return out
