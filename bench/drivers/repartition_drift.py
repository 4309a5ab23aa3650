"""Closed-loop dynamic repartitioning: a drifting hot spot and point churn.

One step at a time, on one tree-mode ``Repartitioner``:

1. ``update_weights``: every point's base weight times
   ``1 + hot_amp * exp(-|x - c|^2 / hot_width2)``, the centre ``c``
   moving ``hot_step`` along x per step, bouncing between ``hot_lo`` and
   ``hot_hi`` (phase and direction from the seed);
2. every ``churn_every``-th step: ``delete`` of ``churn_points`` seeded
   random live slots, then ``insert`` of as many new points drawn around
   the same clusters;
3. ``Repartitioner.step()``, timed until its ``part`` is ready.

The store is full (capacity = points), so an insert fills exactly the
slots just freed. The seed drives the points (around cluster centres
that are the same for every seed), the churn and the hot spot's phase;
every shape is fixed, so every seed runs the same compiled programs. After the window, sampled steps (a seeded reservoir
per step kind) are checked against ``ref_partition``: each with the
bucket structure and the curve order it sliced, the curve against the
buckets as they stood at the rebuild that keyed them.
"""
from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import harness
import ref_partition
from points import clustered_points


@jax.jit
def _hot_weights(pts, w0, c, amp, width2):
    bump = jnp.exp(-jnp.sum((pts - c) ** 2, axis=1) / width2)
    return (w0 * (1.0 + amp * bump)).astype(jnp.float32)


@jax.jit
def _churn_update(pts, w0, w_hot, slots, new_pts, new_w):
    """Inputs after a churn: store arrays and the weights the step sees
    (inserted points carry their base weight until the next update)."""
    return pts.at[slots].set(new_pts), w0.at[slots].set(new_w), w_hot.at[slots].set(new_w)


class HotSpot:
    def __init__(self, traffic: dict, rng: np.random.Generator):
        self.lo, self.hi, self.dx = traffic["hot_lo"], traffic["hot_hi"], traffic["hot_step"]
        self.x = self.lo + (self.hi - self.lo) * rng.random()
        self.dir = 1.0 if rng.random() < 0.5 else -1.0

    def advance(self) -> np.ndarray:
        x = self.x + self.dir * self.dx
        if not self.lo <= x <= self.hi:
            self.dir = -self.dir
            x = self.x + self.dir * self.dx
        self.x = x
        return np.array([x, 0.5, 0.5], np.float32)


class Reservoir:
    """Uniform sample of k items per kind over a stream, drawn from a seed."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.items, self.seen = k, rng, {}, {}

    def offer(self, kind: str, make) -> None:
        n = self.seen[kind] = self.seen.get(kind, 0) + 1
        items = self.items.setdefault(kind, [])
        if len(items) < self.k:
            items.append(make())
        else:
            j = int(self.rng.integers(n))
            if j < self.k:
                items[j] = make()

    def all(self) -> list:
        return [it for items in self.items.values() for it in items]


def run(run: "harness.Run") -> "harness.Outcome":
    from repro.core import partitioner as pt
    from repro.core.repartition import Repartitioner

    cfg, tr = run.cell.config, run.cell.traffic
    n, parts, eng = cfg["points"], cfg["parts"], cfg["engine"]
    k_churn, every = tr["churn_points"], tr["churn_every"]
    rng = np.random.default_rng([run.seed, 1])
    hot = HotSpot(tr, np.random.default_rng([run.seed, 2]))
    amp, width2 = jnp.float32(tr["hot_amp"]), jnp.float32(tr["hot_width2"])

    pts, w0 = clustered_points(run.seed, n, cfg)
    rp = Repartitioner(
        pts, w0, parts, pt.PartitionerConfig(use_tree=True, curve=eng["curve"]),
        capacity=n, max_depth=eng["max_depth"], bucket_size=eng["bucket_size"],
        frame_margin=eng["frame_margin"],
    )
    t = 0
    prev_part = rp.part
    rebuilt = None   # what the last rebuild keyed the curve from
    reservoir = Reservoir(tr["check_per_kind"], np.random.default_rng([run.seed, 3]))
    times = {"step": [], "engine_step": [], "churn": []}

    def one_step(record: bool):
        nonlocal pts, w0, t, prev_part, rebuilt
        t_start = time.perf_counter()
        with run.span("drift.weights"):
            w_step = _hot_weights(pts, w0, jnp.asarray(hot.advance()), amp, width2)
            rp.update_weights(w_step)
        churn = None
        if t % every == every - 1:
            slots = np.sort(rng.choice(n, k_churn, replace=False)).astype(np.int32)
            new_pts, new_w = clustered_points(run.seed, k_churn, cfg, batch=t + 1)
            t_churn = time.perf_counter()
            with run.span("drift.churn"):
                slots_d = jnp.asarray(slots)
                rp.delete(slots_d)
                got = rp.insert(new_pts, new_w)
            if record:
                times["churn"].append(time.perf_counter() - t_churn)
            pts, w0, w_step = _churn_update(pts, w0, w_step, slots_d, new_pts, new_w)
            churn = (slots, got)
        t_engine = time.perf_counter()
        with run.span("drift.engine_step"):
            step = rp.step()
            step.part.block_until_ready()
        t_end = time.perf_counter()
        if step.kind == "rebuild":
            rebuilt = _rebuilt(rp, pts)
        if record:
            times["engine_step"].append(t_end - t_engine)
            times["step"].append(t_end - t_start)
            kind = "rebuild" if step.kind == "rebuild" else ("churn" if churn else "plain")
            reservoir.offer(kind, functools.partial(
                _sample, step, prev_part, w_step, pts, rp, churn, rebuilt))
        prev_part = step.part
        t += 1

    # warm-up: plain steps, one churn step, the controller's rebuild path
    for _ in range(every):
        one_step(record=False)
    prev_part = rp.rebuild().part
    rebuilt = _rebuilt(rp, pts)
    one_step(record=False)
    jax.block_until_ready((pts, w0))

    with run.window():
        start = time.perf_counter()
        while time.perf_counter() - start < run.seconds:
            one_step(record=True)
    steps = len(times["step"])
    run.layer.update(engine_step_s=times["engine_step"], churn_s=times["churn"])
    rebuilds = sum(1 for kind, *_ in rp.stats.history[-steps:] if kind == "rebuild")
    print(f"drift: steps={steps} rebuilds={rebuilds} checked={len(reservoir.all())}",
          file=sys.stderr)

    host = {}
    samples = [_fetch(s, host) for s in reservoir.all()]
    del rp, pts, w0, prev_part, reservoir, rebuilt, host
    readings = _check(samples, cfg)
    limits = cfg["limits"]
    failed = sum(1 for r in readings
                 if not all(harness.Check(k, v, limits[k]).ok for k, v in r.items()))
    checks = [harness.Check(k, float(sum(r[k] for r in readings)), limits[k])
              for k in ("misassigned", "misfiled", "summary_off", "curve_descents",
                        "descents", "migration_gap")]
    checks += [harness.Check(k, max(r[k] for r in readings), limits[k])
               for k in ("cut_shift", "load_gap")]
    return harness.Outcome(
        end_to_end={"step_ms": 1e3 * run.window_s / steps,
                    "step_p95_ms": 1e3 * harness.p95(times["step"])},
        attempted=steps, failed=failed, checks=checks,
    )


def _rebuilt(rp, pts) -> dict:
    """Device references to the buckets a rebuild keyed the curve from."""
    tree, summary = rp.dps.tree, rp.summary()
    return {"points": pts, "leaf_id": rp.dps.leaf_id, "split_dim": tree.split_dim,
            "split_val": tree.split_val, "is_leaf": tree.is_leaf,
            "count": summary.count, "centroid": summary.centroid}


def _sample(step, prev_part, w_step, pts, rp, churn, rebuilt) -> dict:
    """Device references to what one step read and produced."""
    tree = rp.dps.tree
    return {
        "part": step.part, "prev_part": prev_part, "loads": step.loads,
        "send_counts": step.plan.send_counts, "weights": w_step, "points": pts,
        "leaf_id": rp.dps.leaf_id, "split_dim": tree.split_dim,
        "split_val": tree.split_val, "is_leaf": tree.is_leaf,
        "order": rp._border.order, "churn": churn, "rebuilt": rebuilt,
    }


def _fetch(tree, host: dict):
    """Host copies, one per device array however many samples share it
    (``host`` keeps each array, so its id stays its own)."""
    def get(a):
        if not isinstance(a, jax.Array):
            return a
        return host.setdefault(id(a), (a, np.asarray(a)))[1]
    return jax.tree.map(get, tree)


def _check(samples: list, cfg: dict) -> list:
    """Readings of every sampled step. A structure that several samples
    share (the same host arrays) is checked once."""
    memo = {}
    live = np.ones(samples[0]["part"].shape, bool)   # capacity == points: all live

    def once(what: str, arrays: tuple, make):
        key = (what, *map(id, arrays))
        if key not in memo:
            memo[key] = make()
        return memo[key]

    def misfiled(d):
        arrays = (d["points"], d["leaf_id"], d["split_dim"], d["split_val"], d["is_leaf"])
        return once("misfiled", arrays, lambda: ref_partition.misfiled(
            d["points"], live, d["leaf_id"], d["split_dim"], d["split_val"], d["is_leaf"]))

    def curve(rb):
        arrays = (rb["points"], rb["leaf_id"], rb["is_leaf"], rb["count"], rb["centroid"])
        return once("curve", arrays, lambda: ref_partition.rebuild_curve(
            rb["points"], live, rb["leaf_id"], rb["is_leaf"], rb["count"], rb["centroid"],
            cfg["engine"]["frame_margin"], 32 // cfg["dims"]))

    out = []
    for s in samples:
        summary_off, cand = curve(s["rebuilt"])
        r = ref_partition.check_step(
            s["part"], s["prev_part"], s["loads"], s["send_counts"], s["weights"], live,
            s["leaf_id"], s["order"], cfg["parts"],
        )
        r["misfiled"] = misfiled(s) + misfiled(s["rebuilt"])
        r["summary_off"] = summary_off
        r["curve_descents"] = ref_partition.curve_descents(s["order"], cand)
        if s["churn"] is not None:
            slots, got = s["churn"]
            r["misassigned"] += int(np.sum(np.asarray(got) != slots))
        out.append(r)
    return out
