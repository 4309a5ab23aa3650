#!/usr/bin/env python3
"""Smoke run of the partitioning system on TPU chips, through the same
entry points a user calls, each phase checked against the repo's own
reference.

    python chip_smoke.py [--seed S]          # one chip
    python chip_smoke.py --chips 4 [--seed S]  # (node, device) = (2, 2) mesh

One chip runs four phases:

* partition — 2^24 clustered 3-D points with random weights: the point
  path (jnp and Pallas Hilbert keys, which must agree bit for bit) into
  64 parts, its knapsack checked against a float64 host knapsack on the
  same curve order; the Pallas Morton keys against ``sfc.morton_key``;
  the kd-tree bucket path over the first 2^22 points within the
  README's bucket spread bound; then a tree-mode ``Repartitioner``
  through 5 weight-drift steps and one insert/delete batch, checking
  weight conservation and the bound after every step.
* mesh — the AMR heat-stencil simulation (2-D, 2^20 base cells) on a
  (1, 1) mesh with the Pallas stencil row update, bit-equal to its
  single-device jnp reference.
* particles — the N-body short-range loop (32,768 atoms, ~40
  neighbours) with the Pallas force kernel on a (1, 1) mesh against the
  jnp reference, bit-equal.
* queries — a point index over the partition phase's points; point
  location and kNN (k=8) for 2^20 queries, half of them hits, a sample
  of 4096 checked against plain numpy implementations of both.

``--chips 4`` runs only what exists across chips: the mesh simulation on
the (2, 2) mesh (base level 9, 8 events) against its reference, the
distributed bucket repartitioner against the single-device hierarchical partition, and the
two-level distributed query engine against local answers, then prints
every device's peak memory.

Each phase prints its sizes, wall and compile seconds, the device's
peak bytes and every comparison on lines of its own; the last stdout
line is one JSON object naming the device. Without a TPU the script
exits non-zero before any phase; a failed comparison exits non-zero
after the remaining phases have run. Exceptions are not caught.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

_COMPILE_S = [0.0]


def _on_duration(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += duration


class Phase:
    """Prints one phase's lines and collects its failed comparisons."""

    failures: list[str] = []

    def __init__(self, name: str):
        self.name = name

    def line(self, **kv) -> None:
        print(f"[{self.name}] " + " ".join(f"{k}={v}" for k, v in kv.items()), flush=True)

    def run(self, label: str, fn):
        """Call ``fn``, wait for its device work, print wall/compile s."""
        c0, t0 = _COMPILE_S[0], time.perf_counter()
        out = jax.block_until_ready(fn())
        self.line(step=label, wall_s=round(time.perf_counter() - t0, 3),
                  compile_s=round(_COMPILE_S[0] - c0, 3))
        return out

    def _record(self, label: str, ok: bool, **detail) -> None:
        self.line(check=label.replace(" ", "_"), ok=ok, **detail)
        if not ok:
            Phase.failures.append(f"{self.name}: {label}")

    def equal(self, label: str, got, want) -> None:
        """Bit-equality; on a mismatch, the count and max ulp distance."""
        g, w = np.asarray(got), np.asarray(want)
        if g.shape == w.shape and np.array_equal(g, w):
            self._record(label, True, n=g.size)
            return
        detail = {"shape": f"{g.shape}vs{w.shape}"}
        if g.shape == w.shape:
            detail["mismatches"] = int((g != w).sum())
            detail["max_ulp"] = _max_ulp(g, w)
        self._record(label, False, **detail)

    def at_most(self, label: str, value: float, bound: float) -> None:
        self._record(label, bool(value <= bound), value=value, bound=bound)

    def close(self, label: str, value: float, want: float, rtol: float) -> None:
        ok = abs(value - want) <= rtol * abs(want)
        self._record(label, bool(ok), value=value, want=want, rtol=rtol)

    def done(self) -> None:
        self.line(peak_bytes_in_use=_peak_bytes(jax.devices()[0]))


def _peak_bytes(dev):
    return (dev.memory_stats() or {}).get("peak_bytes_in_use")


def _max_ulp(a: np.ndarray, b: np.ndarray) -> int:
    if a.dtype.kind != "f":
        return int(np.abs(a.astype(np.int64) - b.astype(np.int64)).max())
    ia = a.astype(np.float32).view(np.int32).astype(np.int64)
    ib = b.astype(np.float32).view(np.int32).astype(np.int64)
    ia = np.where(ia < 0, -(2**31) - ia, ia)   # two's-complement float order
    ib = np.where(ib < 0, -(2**31) - ib, ib)
    return int(np.abs(ia - ib).max())


def _spread(loads) -> float:
    loads = np.asarray(loads, np.float64)
    return float(loads.max() - loads.min())


def _rel_err(got: np.ndarray, want: np.ndarray) -> float:
    return float((np.abs(got - want) / np.maximum(want, 1e-30)).max())


def _kernel_impl(use_pallas: bool) -> str:
    from repro.kernels import ops as kops

    if not use_pallas:
        return "xla"
    return "pallas-interpret" if kops.interpret() else "pallas-compiled"


@functools.partial(jax.jit, static_argnames=("n", "d", "clusters"))
def _clustered(key, n: int, d: int, clusters: int):
    k = jax.random.split(key, 5)
    centers = jax.random.uniform(k[0], (clusters, d), minval=0.1, maxval=0.9)
    which = jax.random.randint(k[1], (n,), 0, clusters)
    blob = centers[which] + 0.03 * jax.random.normal(k[2], (n, d))
    uniform = jax.random.uniform(k[3], (n, d))
    pts = jnp.where((jnp.arange(n) % 5 == 0)[:, None], uniform, blob)
    pts = jnp.clip(pts, 0.0, 1.0).astype(jnp.float32)
    w = jax.random.uniform(k[4], (n,), minval=0.5, maxval=1.5).astype(jnp.float32)
    return pts, w


def clustered_points(seed: int, n: int, d: int = 3, clusters: int = 32):
    """(n, d) points in the unit box, 80% in Gaussian clusters, 20%
    uniform, with weights in [0.5, 1.5) — generated on the device in one
    program (op by op, the 2^24-row gather alone compiles for a minute)."""
    return _clustered(jax.random.PRNGKey(seed), n, d, clusters)


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------

def knapsack_reference(w_sorted, parts: int) -> tuple[np.ndarray, np.ndarray]:
    """The greedy midpoint-rule knapsack of ``knapsack.slice_weighted_curve``
    in float64 on the host: (part per element, exclusive prefix)."""
    w = np.asarray(w_sorted, np.float64)
    prefix = np.cumsum(w) - w
    ideal = (prefix[-1] + w[-1]) / parts
    part = np.clip(np.floor((prefix + 0.5 * w) / ideal), 0, parts - 1).astype(np.int32)
    return part, prefix


def cut_shift(part_sorted, ref_part, prefix, w_sorted, parts: int) -> float:
    """Largest weight between a part's first element on the device and
    in the reference: how far float32 rounding moved any cut."""
    ext = np.append(prefix, prefix[-1] + float(np.asarray(w_sorted[-1], np.float64)))
    p = np.arange(parts)
    got = np.searchsorted(np.asarray(part_sorted), p, side="left")
    want = np.searchsorted(ref_part, p, side="left")
    return float(np.abs(ext[got] - ext[want]).max())


def _check_knapsack(ph: "Phase", w_sorted, part_sorted, loads, parts: int) -> None:
    """README: the knapsack's load spread is <= 2 x the largest element
    weight — in exact arithmetic, so it is checked on a float64 host
    knapsack over the same curve-sorted weights. The device knapsack
    works on a float32 prefix; its cuts must match the reference's to
    within one element plus 4 ulp of the total weight (DESIGN.md, "Load
    balance in float32")."""
    w_max = float(jnp.max(w_sorted))
    ref_part, prefix = knapsack_reference(w_sorted, parts)
    ref_loads = np.bincount(ref_part, weights=np.asarray(w_sorted, np.float64), minlength=parts)
    total = float(np.float32(prefix[-1]))
    tol = w_max + 4 * float(np.spacing(np.float32(total)))
    shift = cut_shift(part_sorted, ref_part, prefix, w_sorted, parts)
    ph.line(spread_device=_spread(loads), spread_reference=_spread(ref_loads),
            w_max=w_max, max_cut_shift=shift)
    ph.at_most("reference spread <= 2 max element weight", _spread(ref_loads), 2 * w_max)
    ph.at_most("device cuts within w_max + 4 ulp(total) of reference", shift, tol)
    ph._record("device parts non-decreasing along the curve",
               bool((np.diff(np.asarray(part_sorted)) >= 0).all()))


def phase_partition(seed: int, n: int = 1 << 24, tree_n: int = 1 << 22,
                    parts: int = 64, drift_steps: int = 5, delta: int = 1 << 16):
    from repro.core import partitioner as pt
    from repro.core import sfc
    from repro.core.repartition import Repartitioner
    from repro.kernels import ops as kops

    ph = Phase("partition")
    pts, w = ph.run("generate", lambda: clustered_points(seed, n))
    w_total = float(np.asarray(w, np.float64).sum())
    ph.line(n=n, d=3, parts=parts, weight_total=w_total)

    res_j = ph.run("point_path_xla", lambda: pt.partition(pts, w, parts, pt.PartitionerConfig()))
    res_p = ph.run("point_path_pallas", lambda: pt.partition(
        pts, w, parts, pt.PartitionerConfig(use_pallas=True)))
    ph.line(hilbert_keys=_kernel_impl(True))
    ph.equal("pallas hilbert keys == sfc.hilbert_key", res_p.keys, res_j.keys)
    ph.equal("pallas parts == xla parts", res_p.part, res_j.part)
    mk_p = ph.run("morton_keys_pallas", lambda: jax.jit(kops.morton_key)(pts))
    mk_x = ph.run("morton_keys_xla", lambda: jax.jit(sfc.morton_key)(pts))
    ph.line(morton_keys=_kernel_impl(True))
    ph.equal("pallas morton keys == sfc.morton_key", mk_p, mk_x)
    _check_knapsack(ph, w[res_j.perm], res_j.part[res_j.perm], res_j.loads, parts)
    ph.close("point loads conserve weight", float(np.sum(np.asarray(res_j.loads, np.float64))),
             w_total, 1e-4)

    # the kd-tree build keeps ~750 B of temporaries per point: at 2^24
    # points the tree partition needs more than the chip's 16 GB, so the
    # tree substrate and the engine take the first tree_n points
    t_pts, t_w = pts[:tree_n], w[:tree_n]
    t_total = float(np.asarray(t_w, np.float64).sum())
    ph.line(tree_n=tree_n, tree_weight_total=t_total)
    tree_cfg = pt.PartitionerConfig(use_tree=True)
    res_t = ph.run("tree_path", lambda: pt.partition(t_pts, t_w, parts, tree_cfg))
    b_max = float(jnp.max(res_t.summary.weight))
    ph.at_most("tree spread <= 2 max bucket weight", _spread(res_t.loads), 2 * b_max)
    ph.close("tree loads conserve weight", float(np.sum(np.asarray(res_t.loads, np.float64))),
             t_total, 1e-4)

    rp = ph.run("repartitioner_build", lambda: Repartitioner(
        t_pts, t_w, parts, tree_cfg, capacity=tree_n,
        max_depth=tree_cfg.max_depth, bucket_size=tree_cfg.bucket_size))

    def check_step(label, step):
        part = np.asarray(step.part)
        active = np.asarray(rp.dps.active)
        live_w = float(np.asarray(rp.dps.weights, np.float64)[active].sum())
        ok = bool(((part[active] >= 0) & (part[active] < parts)).all()
                  and (part[~active] == -1).all())
        ph._record(f"{label} assigns every live slot", ok)
        ph.close(f"{label} loads conserve weight",
                 float(np.sum(np.asarray(step.loads, np.float64))), live_w, 1e-4)
        b = float(jnp.max(rp.summary().weight))
        ph.at_most(f"{label} spread <= 2 max bucket weight", _spread(step.loads), 2 * b)
        ph.line(step=label, kind=step.kind, imbalance=round(step.imbalance, 6),
                moved=int(step.plan.total_moved))

    for t in range(drift_steps):
        c = jnp.asarray([0.2 + 0.15 * t, 0.5, 0.5], jnp.float32)
        bump = jnp.exp(-jnp.sum((t_pts - c) ** 2, axis=1) / 0.02)
        w_t = (t_w * (1.0 + 3.0 * bump)).astype(jnp.float32)
        rp.update_weights(w_t)
        check_step(f"drift{t}", ph.run(f"drift{t}", rp.step))

    new_pts, new_w = clustered_points(seed + 1, delta)
    ph.run("delete", lambda: rp.delete(jnp.arange(delta, dtype=jnp.int32)))
    ph.run("insert", lambda: rp.insert(new_pts, new_w))
    check_step("insert_delete", ph.run("insert_delete_step", rp.step))
    ph.done()
    return pts


def _mesh_config(base_level: int, events: int):
    from repro.mesh.simulate import SimConfig

    return SimConfig(d=2, base_level=base_level, max_level=base_level + 1,
                     events=events, amr_every=4, substeps=8)


def phase_mesh(jax_mesh, hplan, base_level: int = 10, events: int = 12, name: str = "mesh"):
    from repro.mesh import simulate as sim

    ph = Phase(name)
    cfg = _mesh_config(base_level, events)
    events = ph.run("trajectory_host", lambda: sim.build_trajectory(cfg))
    u0 = sim.initial_field(events[0].mesh, cfg)
    ph.line(d=cfg.d, base_level=cfg.base_level, max_level=cfg.max_level,
            cells_first=events[0].mesh.n, cells_last=events[-1].mesh.n,
            events=cfg.events, substeps=cfg.substeps, mesh=dict(jax_mesh.shape),
            stencil_row_update=_kernel_impl(True))
    ref = ph.run("reference_xla", lambda: sim.run_reference(events, u0, cfg.substeps))
    got, st = ph.run("distributed_incremental_pallas", lambda: sim.run_distributed(
        events, u0, cfg.substeps, jax_mesh, hplan, driver="incremental", cfg=cfg,
        use_pallas=True))
    ph.line(stencil_s=round(st.stencil_s, 3), plan_build_s=round(st.plan_build_s, 3),
            engine_s=round(st.engine_s, 3), repartition_events=st.repartition_events,
            amr_events=st.amr_events)
    ph.equal("distributed field == reference", got, ref)
    ph.done()


def phase_particles(jax_mesh, hplan, seed: int, n: int = 32768, events: int = 6,
                    substeps: int = 20):
    from repro.particles import simulate as psim

    ph = Phase("particles")
    cfg = psim.ParticleSimConfig(d=3, n=n, events=events, substeps=substeps,
                                 radius=0.066, seed=seed, margin=0.0)
    ph.line(n=n, d=3, radius=cfg.radius, events=events, substeps=substeps,
            force_kernel=_kernel_impl(True))
    ref = ph.run("reference_xla", lambda: psim.run_reference(cfg))
    got, st = ph.run("distributed_pallas", lambda: psim.run_distributed(
        cfg, jax_mesh, hplan, use_pallas=True))
    ph.line(k_max=st.k_max, force_s=round(st.force_s, 3),
            neighbor_s=round(st.neighbor_s, 3), plan_build_s=round(st.plan_build_s, 3))
    ph.equal("positions == reference", got.pos, ref.pos)
    ph.equal("velocities == reference", got.vel, ref.vel)
    ph.done()


def _query_batch(seed: int, pts, nq: int):
    """nq queries: the first half copies stored points, the rest are
    uniform in the unit box."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 7))
    hits = pts[jax.random.randint(k1, (nq // 2,), 0, pts.shape[0])]
    rand = jax.random.uniform(k2, (nq - nq // 2, pts.shape[1]), dtype=jnp.float32)
    return jnp.concatenate([hits, rand])


class _HostIndex:
    """The index's arrays on the host, for the plain numpy references."""

    def __init__(self, idx):
        self.keys = np.asarray(idx.keys)
        self.points = np.asarray(idx.points)
        self.ids = np.asarray(idx.ids)
        self.bucket_keys = np.asarray(idx.bucket_keys)
        self.starts = np.asarray(idx.bucket_starts).astype(np.int64)
        self.n_valid = int(self.starts[-1])
        self.max_bucket_len = idx.max_bucket_len


def host_point_location(h: _HostIndex, q: np.ndarray, qk: np.ndarray, cap: int):
    """Exact point location in numpy: scan the query key's run of the
    sorted live keys for an equal coordinate (first match wins)."""
    lo = np.searchsorted(h.keys[: h.n_valid], qk, side="left")
    hi = np.searchsorted(h.keys[: h.n_valid], qk, side="right")
    pos = lo[:, None] + np.arange(cap)[None, :]
    cand = np.minimum(pos, h.keys.shape[0] - 1)
    hit = (h.points[cand] == q[:, None, :]).all(-1) & (pos < hi[:, None])
    found = hit.any(1)
    gid = h.ids[cand[np.arange(q.shape[0]), hit.argmax(1)]]
    return found, np.where(found, gid, -1).astype(np.int32), found | (hi - lo <= cap)


def host_knn_distances(h: _HostIndex, q: np.ndarray, qk: np.ndarray, k: int,
                       cutoff: int = 1, max_window: int = 1024) -> np.ndarray:
    """k smallest float64 distances over the query's bucket +- cutoff
    along the curve (the window ``queries.knn`` searches), ascending."""
    nb = h.bucket_keys.shape[0]
    b = np.clip(np.searchsorted(h.bucket_keys, qk, side="right") - 1, 0, nb - 1)
    start = h.starts[np.clip(b - cutoff, 0, nb - 1)]
    end = h.starts[np.clip(b + cutoff, 0, nb - 1) + 1]
    win = max(k, min(h.keys.shape[0], h.max_bucket_len * (2 * cutoff + 1), max_window))
    pos = start[:, None] + np.arange(win)[None, :]
    cand = np.minimum(pos, h.keys.shape[0] - 1)
    d2 = ((h.points[cand].astype(np.float64) - q[:, None, :]) ** 2).sum(-1)
    d2 = np.where(pos < end[:, None], d2, np.inf)
    return np.sqrt(np.sort(d2, axis=1)[:, :k])


def phase_queries(seed: int, pts, nq: int = 1 << 20, k: int = 8, sample: int = 4096):
    """Point location and kNN over the whole batch; a sample of queries
    from both halves is checked against the numpy references."""
    from repro.core import curve_index as ci
    from repro.core import queries

    ph = Phase("queries")
    idx = ph.run("build_index", lambda: queries.build_index(pts))
    q = _query_batch(seed, pts, nq)
    ph.line(points=pts.shape[0], buckets=idx.num_buckets, queries=nq, k=k,
            key_search="xla-searchsorted", reference_sample=sample)
    pl = ph.run("point_location", lambda: queries.point_location(idx, q))
    dist, ids = ph.run("knn", lambda: queries.knn(idx, q, k=k))
    found = np.asarray(pl.found)
    ph._record("every hit query found", bool(found[: nq // 2].all()), found=int(found.sum()))

    half = sample // 2
    sel = np.concatenate([np.arange(half), nq // 2 + np.arange(half)])
    h = _HostIndex(idx)
    q_s = np.asarray(q)[sel]
    qk_s = np.asarray(ci.query_keys(idx, jnp.asarray(q_s)))
    want = host_point_location(h, q_s, qk_s, 64)
    for f, w in zip(("found", "ids", "ok"), want):
        ph.equal(f"point_location {f} == numpy reference", np.asarray(getattr(pl, f))[sel], w)
    d_got = np.asarray(dist)[sel].astype(np.float64)
    d_want = host_knn_distances(h, q_s, qk_s, k)
    # float32 distances: a few ulp from float64 (a wrong neighbour is off
    # by far more)
    ph.at_most("knn distances vs numpy float64 reference, relative",
               _rel_err(d_got, d_want), 1e-5)
    # every returned id is a stored point at the reported distance
    p_ids = np.asarray(pts)[np.asarray(ids)[sel]].astype(np.float64)
    d_ids = np.sqrt(((p_ids - q_s[:, None, :]) ** 2).sum(-1))
    ph.at_most("knn ids are at their distances, relative", _rel_err(d_got, d_ids), 1e-5)
    ph.done()


# ---------------------------------------------------------------------------
# four-chip phase
# ---------------------------------------------------------------------------

def phase_four_chips(seed: int, n: int = 1 << 21, nq: int = 1 << 18,
                     base_level: int = 9, events: int = 8):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import partitioner as pt
    from repro.core import queries
    from repro.core.repartition import DistributedBucketRepartitioner
    from repro.distributed import sharding as shd
    from repro.serve.query_engine import DistributedQueryEngine

    jm = shd.make_node_device_mesh(2, 2)
    plan = pt.HierarchyPlan(2, 2)
    phase_mesh(jm, plan, base_level=base_level, events=events, name="mesh_2x2")

    ph = Phase("bucket_repartitioner_2x2")
    pts, w = clustered_points(seed, n)
    sh = NamedSharding(jm, P(("node", "device")))
    pts_d, w_d = jax.device_put(pts, sh), jax.device_put(w, sh)
    cfg = pt.PartitionerConfig(use_tree=True, max_depth=8)
    eng = DistributedBucketRepartitioner(jm, cfg=cfg, plan=plan)
    part = np.asarray(ph.run("partition", lambda: eng.partition(pts_d, w_d)))
    w_h = np.asarray(w, np.float64)
    ph.line(n=n, parts=plan.num_parts, summary_records=int(eng.node_keys.shape[0]))
    ph._record("every point assigned", bool(((part >= 0) & (part < 4)).all()))
    loads = np.bincount(part, weights=w_h, minlength=4)
    ph.close("loads conserve weight", float(loads.sum()), float(w_h.sum()), 1e-6)
    single = ph.run("single_device_hierarchical_partition",
                    lambda: pt.hierarchical_partition(pts, w, plan, cfg))
    s_loads = np.asarray(single.loads, np.float64)
    # per-shard trees and one global tree cut the curve at different
    # buckets: the two assignments agree in balance, not bit for bit.
    # The distributed bound is the test suite's: 2 x (records merged per
    # summary bin = devices per node) x max per-shard bucket weight.
    lid = np.asarray(eng.leaf_id).reshape(4, -1)
    wsh = w_h.reshape(4, -1)
    b_max = max(np.bincount(lid[s], weights=wsh[s]).max() for s in range(4))
    ph.line(distributed_loads=loads.round(3).tolist(), single_loads=s_loads.round(3).tolist(),
            distributed_spread=_spread(loads), single_spread=_spread(s_loads))
    ph.at_most("node spread <= 2*2 max shard bucket weight",
               _spread(loads.reshape(2, 2).sum(1)), 2 * 2 * b_max)
    for j in range(2):
        ph.at_most(f"node{j} device spread <= 2*2 max shard bucket weight",
                   _spread(loads[2 * j: 2 * j + 2]), 2 * 2 * b_max)
    w2 = (w * (1.0 + 2.0 * (jnp.arange(n) % 5 == 0))).astype(jnp.float32)
    w2_d = jax.device_put(w2, sh)
    re = ph.run("rebalance", lambda: eng.rebalance(w2_d))
    fresh = ph.run("fresh_partition", lambda: pt.hierarchical_bucket_partition(
        jm, plan, pts_d, w2_d, cfg=cfg)[0])
    ph.equal("rebalance == fresh partition on drifted weights", re, fresh)
    ph.done()

    ph = Phase("query_engine_2x2")
    idx = ph.run("build_index", lambda: queries.build_index(pts))
    q = _query_batch(seed, pts, nq)
    ph.line(points=n, queries=nq)
    dist = DistributedQueryEngine(idx, jm, ("node", "device"))
    got = ph.run("distributed_point_location", lambda: dist.point_location(q))
    ref = ph.run("local_point_location", lambda: queries.point_location(idx, q))
    for f, g, r in zip(("found", "ids", "ok"), got, ref):
        ph.equal(f"distributed {f} == local", g, r)
    ph.done()

    for dev in jax.devices():
        print(f"[devices] id={dev.id} peak_bytes_in_use={_peak_bytes(dev)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {devices[0].platform!r}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.cache import enable_compile_cache

    print(f"[setup] compile_cache={enable_compile_cache()} "
          f"device_kind={devices[0].device_kind!r} devices={len(devices)} "
          f"jax={jax.__version__}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    if args.chips == 4:
        phase_four_chips(args.seed)
    else:
        from repro.core import partitioner as pt
        from repro.distributed import sharding as shd

        one = shd.make_node_device_mesh(1, 1)
        pts = phase_partition(args.seed)
        phase_mesh(one, pt.HierarchyPlan(1, 1))
        phase_particles(one, pt.HierarchyPlan(1, 1), args.seed)
        phase_queries(args.seed, pts)

    if Phase.failures:
        print("chip_smoke: failed: " + "; ".join(Phase.failures), file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
