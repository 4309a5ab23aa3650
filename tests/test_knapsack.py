"""Greedy knapsack slicing: the paper's load-balance guarantee as a
property test (§III-C)."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import knapsack


@given(
    n=st.integers(10, 5000),
    p=st.integers(1, 64),
    seed=st.integers(0, 10_000),
)
@settings(max_examples=30, deadline=None)
def test_balance_guarantee(n, p, seed):
    """max load - min load <= 2 * max element weight (midpoint rule);
    the paper's bound is one max-weight, achieved for unit weights."""
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.random(n).astype(np.float32) + 0.01)
    part = knapsack.slice_weighted_curve(w, p)
    assert bool((jnp.diff(part) >= 0).all()), "parts must be contiguous on the curve"
    loads = np.asarray(knapsack.part_loads(w, part, p))
    maxw = float(jnp.max(w))
    assert loads.max() - loads.min() <= 2 * maxw + 1e-4


def test_unit_weights_perfect_balance():
    w = jnp.ones(1024, jnp.float32)
    part = knapsack.slice_weighted_curve(w, 16)
    loads = np.asarray(knapsack.part_loads(w, part, 16))
    assert loads.max() - loads.min() <= 1.0 + 1e-6  # paper's exact bound


def test_boundaries_consistent():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.random(500).astype(np.float32))
    part = np.asarray(knapsack.slice_weighted_curve(w, 7))
    bounds = np.asarray(knapsack.part_boundaries(w, 7))
    assert bounds[0] == 0 and bounds[-1] == 500
    for p in range(7):
        seg = part[bounds[p] : bounds[p + 1]]
        assert (seg == p).all() or seg.size == 0


@pytest.mark.parametrize("levels", ["flat", "two_level"])
def test_parts_contiguous_when_float32_centers_are_not(levels):
    """A heavy head puts the float32 prefix at ~3e7, where one ulp (2)
    exceeds half of every later weight: the rounded element centers go
    non-monotone, and the parts must still be contiguous slices."""
    rng = np.random.default_rng(0)
    w = rng.uniform(0.5, 1.5, 65536).astype(np.float32)
    w[0] = 3e7
    if levels == "flat":
        part = np.asarray(knapsack.slice_weighted_curve(jnp.asarray(w), 4096))
    else:
        node, dev, part = map(np.asarray, knapsack.two_level_slice(jnp.asarray(w), 2, 2048))
        np.testing.assert_array_equal(part, node * 2048 + dev)
        assert (dev >= 0).all() and (dev < 2048).all()
    assert (np.diff(part) >= 0).all()


def test_greedy_bins_balances():
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.random(200).astype(np.float32) + 0.1)
    bins = np.asarray(knapsack.greedy_bins(w, 8))
    loads = np.bincount(bins, weights=np.asarray(w), minlength=8)
    assert loads.max() - loads.min() <= float(jnp.max(w)) + 1e-5


def test_incremental_reslice_neighbor_locality():
    """Paper §IV: small load changes move data only between rank
    neighbors P±1."""
    from repro.core import migration

    rng = np.random.default_rng(3)
    w0 = np.ones(4096, np.float32)
    old = np.asarray(knapsack.slice_weighted_curve(jnp.asarray(w0), 16))
    w1 = w0.copy()
    w1[rng.choice(4096, 200, replace=False)] *= 1.5  # mild load drift
    new, moved = knapsack.incremental_reslice(jnp.asarray(w1), jnp.asarray(old), 16)
    plan = migration.migration_plan(old, np.asarray(new), 16)
    if plan.total_moved:
        assert migration.neighbor_locality(plan) == 1.0
    assert plan.stay_fraction > 0.9
