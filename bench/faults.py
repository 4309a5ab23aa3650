"""The control and planted faults: the timed path replaced underneath a
run, for showing that ``correct`` comes out false.

* ``control``: the plain reference put in the program's place, computed
  one precision below the configuration's float32: bfloat16.
* ``stale``: a step that returns its state unchanged.
* ``half``: half of the batch left out, the rest scaled to stand for it.
* ``altered``: one answer altered where it is produced.
* ``misfiled``: inserted points filed under another point's bucket.
* ``disorder``: two buckets swapped in the curve order.

The cell runs on one chip, so there is no exchange between chips to
leave out.

Each is a context manager keyed by (driver, mode) that patches the
program in this process and restores it on exit.
"""
from __future__ import annotations

import contextlib

import numpy as np


def _bf16():
    import ml_dtypes

    return ml_dtypes.bfloat16


@contextlib.contextmanager
def _patched(obj, name, value):
    orig = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield orig
    finally:
        setattr(obj, name, orig)


# -- repartition drift ----------------------------------------------------

@contextlib.contextmanager
def drift_control():
    """Repartitioner.step computed by ref_partition.knapsack in bfloat16
    over the engine's buckets in its curve order."""
    import jax.numpy as jnp

    import ref_partition
    from repro.core.repartition import Repartitioner

    bf16 = _bf16()

    def step(self, timeop=None):
        act = np.asarray(self.dps.active)
        lid = np.asarray(self.dps.leaf_id)
        order = np.asarray(self._border.order)
        w = np.asarray(self.dps.weights, np.float64)
        w_rank = np.bincount(lid[act], weights=w[act], minlength=order.size)[order]
        part_rank = ref_partition.knapsack(w_rank.astype(bf16), self.num_parts, dtype=bf16)
        by_node = np.zeros(order.size, np.int32)
        by_node[order] = part_rank
        part = np.where(act, by_node[lid], -1).astype(np.int32)
        loads = np.zeros(self.num_parts, bf16)
        np.add.at(loads, part_rank, w_rank.astype(bf16))
        loads = loads.astype(np.float64)
        return self._emit("incremental", jnp.asarray(part), loads,
                          float(loads.max() / loads.mean()), reused=True)

    with _patched(Repartitioner, "step", step):
        yield


@contextlib.contextmanager
def drift_stale():
    """Every step re-emits the assignment and loads it started from."""
    from repro.core.repartition import Repartitioner

    last = {}

    def step(self, timeop=None):
        if self not in last:
            last[self] = orig(self, timeop)
            return last[self]
        s = last[self]
        return self._emit("incremental", self._part, s.loads, s.imbalance, reused=True)

    with _patched(Repartitioner, "step", step) as orig:
        yield


@contextlib.contextmanager
def drift_half():
    """The bucket weights summed over every other slot, doubled."""
    import jax.numpy as jnp

    from repro.core import repartition

    def kernel(leaf_id, active, weights, order, num_parts):
        half = active & (jnp.arange(active.shape[0]) % 2 == 0)
        return orig(leaf_id, half, 2.0 * weights, order, num_parts)

    with _patched(repartition, "_bucket_slice_kernel", kernel) as orig:
        yield


@contextlib.contextmanager
def drift_altered():
    """One slot's part changed where the step produces it."""
    from repro.core.repartition import Repartitioner

    def emit(self, kind, part, loads, imbalance, reused, **extra):
        part = part.at[0].set((part[0] + 1) % self.num_parts)
        return orig(self, kind, part, loads, imbalance, reused, **extra)

    with _patched(Repartitioner, "_emit", emit) as orig:
        yield


@contextlib.contextmanager
def drift_misfiled():
    """Each inserted point filed under the leaf located for the next one."""
    import jax.numpy as jnp

    from repro.core import dynamic

    def locate(tree, pts, max_depth):
        return jnp.roll(orig(tree, pts, max_depth), 1)

    with _patched(dynamic, "locate", locate) as orig:
        yield


@contextlib.contextmanager
def drift_disorder():
    """The first and the last bucket on the curve trade places."""
    import dataclasses

    import jax.numpy as jnp

    from repro.core import kdtree

    def bucket_order(summary, **kw):
        b = orig(summary, **kw)
        last = b.num_buckets - 1
        first_node, last_node = b.order[0], b.order[last]
        order = b.order.at[0].set(last_node).at[last].set(first_node)
        rank = jnp.zeros_like(b.rank).at[order].set(jnp.arange(order.shape[0], dtype=b.rank.dtype))
        return dataclasses.replace(b, order=order, rank=rank)

    with _patched(kdtree, "bucket_order", bucket_order) as orig:
        yield


SUBSTITUTES = {
    ("repartition_drift", "control"): drift_control,
    ("repartition_drift", "stale"): drift_stale,
    ("repartition_drift", "half"): drift_half,
    ("repartition_drift", "altered"): drift_altered,
    ("repartition_drift", "misfiled"): drift_misfiled,
    ("repartition_drift", "disorder"): drift_disorder,
}


def substitute(driver: str, mode: str):
    return SUBSTITUTES[(driver, mode)]()
