"""miniAMR's two spheres on a (node, device) mesh: timesteps of 16
stencil stages over 40 fields, with an adapt every ``refine_freq``.

The objects ping-pong between their positions at timesteps ``t_from``
and ``t_to``, switching every ``refine_freq`` timesteps: each switch is
one ordinary miniAMR refine step that moves the surfaces that many
timesteps' worth, so every adapt does the same work. Per timestep,
through ``repro.mesh.simulate.DistributedSim``: on a switch, the adapt
(fields home, transfer, engine deletes and inserts, fields back to the
parents' chips), ``step()``, the halo and move plan build and the move;
then ``stages_per_ts`` stages, each a halo exchange of every field and
a sweep, with a global checksum after every ``checksum_freq``-th.

Set-up builds the two meshes and the two adapts between them and warms
up every shape (the first timestep, both adapts and a timestep on each
mesh). The window runs whole half-cycles (an adapt, then plain
timesteps up to ``refine_freq``) until ``--seconds`` have passed.

After the window, sampled timesteps are checked against ``ref_amr``:
every adapt of the window's first cycle and up to ``check_per_kind``
more per kind (plain, adapt), each from the program's own state at its
start, on ``check_vars`` variables drawn from the seed.
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

import harness
import ref_amr


class Reservoir:
    """Uniform sample of k timesteps per kind, decided before each runs."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.items, self.seen = k, rng, {}, {}

    def slot(self, kind: str):
        """Where the next item of ``kind`` goes (None: not sampled)."""
        n = self.seen[kind] = self.seen.get(kind, 0) + 1
        items = self.items.setdefault(kind, [])
        if len(items) < self.k:
            items.append(None)
            return (kind, len(items) - 1)
        j = int(self.rng.integers(n))
        return (kind, j) if j < self.k else None

    def put(self, slot, item) -> None:
        self.items[slot[0]][slot[1]] = item

    def all(self) -> list:
        return [it for items in self.items.values() for it in items if it is not None]


def objects_of(cfg: dict):
    from repro.mesh import amr

    return [amr.Spheroid(tuple(o["center"]), tuple(o["move"]), tuple(o["size"]),
                         tuple(o["inc"])) for o in cfg["objects"] if o["type"] == 2]


def geometry(cfg: dict) -> dict:
    """Root cell level and block bits of the configuration."""
    root = cfg["npx"] * cfg["init_x"]
    return {"root_level": int(np.log2(root * cfg["nx"])), "block_bits": int(np.log2(cfg["nx"]))}


# meshes and the reference's mesh readings depend on the geometry alone:
# kept per process (on the harness module, which the driver's reloads
# share), so that runs of several seeds in one process share them
_MEMO: dict = harness.__dict__.setdefault("amr_spheres_memo", {})


def _memo(what: str, cfg: dict, tr: dict, make):
    key = (what, json.dumps([cfg, tr["t_from"], tr["t_to"]], sort_keys=True))
    if key not in _MEMO:
        _MEMO[key] = make()
    return _MEMO[key]


def run(run: "harness.Run") -> "harness.Outcome":
    from repro.core import partitioner as pt
    from repro.distributed import sharding as shd
    from repro.mesh import simulate

    cfg, tr = run.cell.config, run.cell.traffic
    if any(o["type"] != 2 for o in cfg["objects"]):
        raise ValueError("only spheroid surfaces (miniAMR object type 2) are supported")
    g = geometry(cfg)
    V, stages, every = cfg["num_vars"], cfg["stages_per_ts"], cfg["checksum_freq"]
    half = cfg["refine_freq"]
    a, b_from_a, b, a_from_b = _memo("events", cfg, tr, lambda: simulate.miniamr_events(
        objects_of(cfg), tr["t_from"], tr["t_to"], root_level=g["root_level"],
        block_bits=g["block_bits"], num_refine=cfg["num_refine"],
        block_change=cfg["block_change"]))
    print(f"spheres: cells {a.mesh.n} / {b.mesh.n}, meshes ready at "
          f"{time.perf_counter() - run.t0:.1f}s", file=sys.stderr, flush=True)

    lay, eng = cfg["layout"], cfg["engine"]
    hplan = pt.HierarchyPlan(num_nodes=lay["nodes"], devices_per_node=lay["devices_per_node"])
    jm = shd.make_node_device_mesh(lay["nodes"], lay["devices_per_node"])
    u0 = np.random.default_rng([run.seed, 1]).random((a.mesh.n, V), dtype=np.float32)
    sim = simulate.DistributedSim(
        a, u0, jm, hplan,
        cfg=simulate.SimConfig(bucket_size=eng["bucket_size"],
                               engine_max_depth=eng["max_depth"],
                               node_threshold=eng["node_threshold"]),
        capacity=int(eng["capacity_factor"] * max(a.mesh.n, b.mesh.n)),
        use_pallas=True,
    )
    del u0
    cols = tuple(sorted(np.random.default_rng([run.seed, 2]).choice(
        V, tr["check_vars"], replace=False).tolist()))
    take = jax.jit(lambda u: u[:, np.asarray(cols)])
    reservoir = Reservoir(tr["check_per_kind"], np.random.default_rng([run.seed, 3]))
    first_cycle = []
    counts = {"timesteps": 0, "adapts": 0}
    times = {"plain": [], "adapt": []}

    def timestep(ev, record=None, stages=stages):
        """Run one timestep; ``record`` (a callback) gets what a check
        needs: the state's sampled columns at start and end."""
        start = None
        if record is not None:
            start = (sim.xplan.owned_idx, sim.n, take(sim.u_dev))
        k0 = len(sim.checksums)
        sim.advance(ev, stages, rebalance=ev.transfer is not None, checksum_every=every)
        if record is not None:
            record(dict(adapt=ev.transfer is not None, t_to=ev.t, start=start,
                        end=(sim.xplan.owned_idx, sim.n, take(sim.u_dev)),
                        checksum=sim.checksums[-1][2][list(cols)],
                        end_mesh=ev.mesh, start_mesh=a.mesh if ev.mesh is b.mesh else b.mesh))
        del sim.checksums[k0:]

    # warm-up: the first timestep, both adapts, a timestep on each mesh;
    # one checksum's worth of stages each (the sweep is one program for
    # any stage count)
    for ev in (a, b_from_a, b, a_from_b, a):
        timestep(ev, stages=every)
        print(f"spheres: warm-up {'adapt' if ev.transfer else 'plain'} done at "
              f"{time.perf_counter() - run.t0:.1f}s", file=sys.stderr, flush=True)
    take(sim.u_dev).block_until_ready()
    on_b = False

    with run.window():
        start = time.perf_counter()
        while True:
            for i in range(half):
                ev = (b_from_a if not on_b else a_from_b) if i == 0 else (b if not on_b else a)
                kind = "adapt" if i == 0 else "plain"
                rec = None
                if kind == "adapt" and counts["adapts"] < 2:
                    rec = first_cycle.append
                else:
                    slot = reservoir.slot(kind)
                    if slot is not None:
                        rec = functools.partial(reservoir.put, slot)
                t_ts = time.perf_counter()
                timestep(ev, rec)
                times[kind].append(time.perf_counter() - t_ts)
                counts["timesteps"] += 1
                counts["adapts"] += i == 0
            on_b = not on_b
            if time.perf_counter() - start >= run.seconds:
                break
    st = sim.finish()
    steps = counts["timesteps"]
    run.layer.update(halo_bytes_stage=st.halo_bytes_stage, ghost_cells=st.ghost_cells,
                     cells_moved=st.moved_total, plan_cache_hits=st.plan_cache_hits)
    mean_ms = {k: 1e3 * float(np.mean(v)) if v else None for k, v in times.items()}
    print(f"spheres: timesteps={steps} adapts={counts['adapts']} "
          f"plain_ms={mean_ms['plain']} adapt_ms={mean_ms['adapt']} "
          f"halo_bytes_stage={st.halo_bytes_stage} ghost_cells={st.ghost_cells} "
          f"cells_moved={st.moved_total} plan_cache_hits={st.plan_cache_hits} "
          f"floors={sim.floors}", file=sys.stderr)

    samples = first_cycle + reservoir.all()
    for s in samples:
        for key in ("start", "end"):
            owned, n, dev = s[key]
            s[key] = _unpack(owned, n, np.asarray(dev))
    meshes = {"a": a, "b": b}
    del sim, a, b, a_from_b, b_from_a
    mesh_readings = _memo("meshes", cfg, tr, lambda: check_meshes(cfg, tr, meshes))
    readings = check(cfg, mesh_readings, meshes, samples)
    limits = cfg["limits"]
    failed = sum(1 for r in readings["per_sample"]
                 if not all(harness.Check(k, v, limits[k]).ok for k, v in r.items()))
    checks = [harness.Check(k, float(readings[k]), limits[k])
              for k in ("mesh_mismatch", "nbr_mismatch", "owned_once")]
    checks += [harness.Check(k, max((r[k] for r in readings["per_sample"]), default=float("nan")),
                             limits[k]) for k in ("field_err", "checksum_gap")]
    return harness.Outcome(
        end_to_end={"step_ms": 1e3 * run.window_s / steps},
        attempted=steps, failed=failed, checks=checks,
    )


def _unpack(owned_idx, n, dev) -> tuple:
    """(owned_idx, sampled columns in cell order)."""
    out = np.zeros((n, dev.shape[1]), np.float32)
    rows = owned_idx >= 0
    out[owned_idx[rows]] = dev.reshape(owned_idx.shape + dev.shape[1:])[rows]
    return owned_idx, out


def check_meshes(cfg: dict, tr: dict, meshes: dict) -> dict:
    """The reference's two meshes and the program's meshes against them."""
    geo = ref_amr.Geometry(cfg)
    ref_a, ref_b = ref_amr.pingpong_meshes(geo, tr["t_from"], tr["t_to"])
    refs = {"a": ref_a, "b": ref_b}
    out = {"geo": geo, "refs": refs, "stencils": {}, "mesh_mismatch": 0, "nbr_mismatch": 0,
           "bm": {k: ref_amr.BlockMesh(geo, refs[k]) for k in refs}}
    for k, ev in meshes.items():
        out["mesh_mismatch"] += ref_amr.mesh_mismatch(out["bm"][k], ev.mesh.level, ev.mesh.ij)
        bad = ref_amr.nbr_mismatch(geo, ev.mesh.level, ev.mesh.ij, ev.nbr)
        out["nbr_mismatch"] += bad if bad >= 0 else ev.mesh.n
    return out


def check(cfg: dict, ref: dict, meshes: dict, samples: list) -> dict:
    """Readings of the mesh checks and of every sampled timestep (the
    samples run in threads: numpy's array passes let them go in
    parallel)."""
    geo, refs, bm, stencils = ref["geo"], ref["refs"], ref["bm"], ref["stencils"]
    which = {id(ev.mesh): k for k, ev in meshes.items()}
    for key in bm:
        if key not in stencils:
            stencils[key] = ref_amr.Stencil(bm[key], refs[key])

    def one(s):
        """(readings, owned_once) of one sampled timestep."""
        (_, u0), (owned_end, u1) = s["start"], s["end"]
        src, dst = s["start_mesh"] if s["adapt"] else s["end_mesh"], s["end_mesh"]
        blocks = bm[which[id(src)]]
        u = blocks.to_blocks(src.level, src.ij, u0.astype(np.float64))
        key = which[id(dst)]
        if s["adapt"]:
            d = ref_amr.adapt(geo, {k: u[i] for i, k in enumerate(blocks.keys)}, s["t_to"])
            if sorted(d) != bm[key].keys:
                return dict(field_err=float("inf"), checksum_gap=0.0), 0
            u = np.stack([d[k] for k in bm[key].keys])
        for _ in range(cfg["stages_per_ts"]):
            u = stencils[key].stage(u)
        want = bm[key].from_blocks(dst.level, dst.ij, u)
        field_err = float(np.max(np.abs(u1 - want)) / np.max(np.abs(want)))
        s64 = u1.astype(np.float64).sum(axis=0)
        gap = float(np.max(np.abs(s["checksum"].astype(np.float64) - s64) / np.abs(s64)))
        owned = np.bincount(owned_end[owned_end >= 0], minlength=dst.n)
        return dict(field_err=field_err, checksum_gap=gap), int(np.abs(owned - 1).sum())

    with ThreadPoolExecutor(max_workers=max(1, min(len(samples), os.cpu_count() or 1))) as ex:
        got = list(ex.map(one, samples))
    return {"mesh_mismatch": ref["mesh_mismatch"], "nbr_mismatch": ref["nbr_mismatch"],
            "owned_once": sum(o for _, o in got), "per_sample": [r for r, _ in got]}
