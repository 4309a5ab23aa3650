"""Dynamic trees (Alg. 1) + amortized load balancing (Alg. 3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

import _delta_reference as ref
from repro.core import dynamic


def _mk(rng, n=1024, depth=8, b=32):
    pts = jnp.asarray(rng.random((n, 3)), jnp.float32)
    return dynamic.from_points(pts, max_depth=depth, bucket_size=b)


def _conserved(dps) -> bool:
    M = dps.tree.num_nodes
    holds = jax.ops.segment_sum(dps.active.astype(jnp.int32), dps.leaf_id, num_segments=M)
    return int(holds.sum()) == int(dps.active.sum()) and int(dps.tree.count[0]) == int(
        dps.active.sum()
    )


@pytest.mark.slow  # covered at smaller scale by the adjustment property test
def test_insert_locates_and_counts(rng):
    dps = _mk(rng)
    new = jnp.asarray(rng.random((500, 3)), jnp.float32)
    dps2 = dynamic.insert(dps, new, jnp.ones(500, jnp.float32))
    assert int(dps2.active.sum()) == 1524
    assert int(dps2.tree.count[0]) == 1524  # root count bumped along paths


def test_delete_decrements(rng):
    dps = _mk(rng)
    dps2 = dynamic.delete(dps, jnp.arange(100))
    assert int(dps2.active.sum()) == 924
    assert int(dps2.tree.count[0]) == 924


@pytest.mark.slow  # depth-20 build: ~30 s of XLA compile
def test_split_heavy_buckets(rng):
    # depth 20: midpoint splitters spend ~4 levels shaving empty halves
    # before reaching the 0.01-wide cluster (the paper's midpoint-vs-median
    # observation), so give SplitLeaf room to finish.
    dps = _mk(rng, depth=20)
    burst = jnp.asarray(0.3 + 0.01 * rng.random((2000, 3)), jnp.float32)
    dps = dynamic.insert(dps, burst, jnp.ones(2000, jnp.float32))
    assert int(dynamic.max_bucket_occupancy(dps)) > 2 * 32
    dps = dynamic.adjustments(dps)
    assert int(dynamic.max_bucket_occupancy(dps)) <= 2 * 32
    assert _conserved(dps)


def test_merge_light_buckets(rng):
    dps = _mk(rng)
    ids = np.nonzero(np.asarray(dps.active))[0]
    rng.shuffle(ids)
    dps = dynamic.delete(dps, jnp.asarray(ids[:900]))
    nb0 = int(dynamic.num_buckets(dps))
    dps = dynamic.adjustments(dps)
    nb1 = int(dynamic.num_buckets(dps))
    assert nb1 < nb0, f"merge should reduce buckets: {nb0} -> {nb1}"
    assert _conserved(dps)


@given(seed=st.integers(0, 1000), frac=st.floats(0.1, 0.9))
@settings(max_examples=8, deadline=None)
def test_property_adjustments_conserve(seed, frac):
    rng = np.random.default_rng(seed)
    dps = _mk(rng)  # shared shape with the other tests: one compile
    new = jnp.asarray(rng.random((400, 3)).astype(np.float32) * 0.2)
    dps = dynamic.insert(dps, new, jnp.ones(400, jnp.float32))
    ids = np.nonzero(np.asarray(dps.active))[0]
    kill = ids[: int(len(ids) * frac)]
    dps = dynamic.delete(dps, jnp.asarray(kill))
    dps = dynamic.adjustments(dps)
    assert _conserved(dps)


def test_amortized_controller_alg3():
    """Credits = LB cost; rebalance triggers when cumulative excess
    exceeds credits (Algorithm 3 semantics)."""
    c = dynamic.AmortizedController()
    c.balanced(lb_cost=5.0, num_buckets=100, timeop=0.01)
    # constant cost: never triggers
    assert not any(c.observe(0.01, 100) for _ in range(50))
    # drifting cost accumulates delta = sum(cost - base)
    c2 = dynamic.AmortizedController()
    c2.balanced(lb_cost=5.0, num_buckets=100, timeop=0.01)
    fired = [c2.observe(0.01 + 0.001 * i, 100) for i in range(40)]
    assert True in fired
    i = fired.index(True)
    # delta at trigger must exceed credits
    assert c2.delta > 5.0
    assert i > 5  # amortization delays the trigger


def test_controller_more_credits_fewer_rebalances():
    def run(lb_cost):
        c = dynamic.AmortizedController()
        c.balanced(lb_cost=lb_cost, num_buckets=100, timeop=0.01)
        n = 0
        for i in range(200):
            if c.observe(0.011 + 0.0005 * (i % 37), 100):
                c.balanced(lb_cost=lb_cost, num_buckets=100, timeop=0.01)
                n += 1
        return n

    assert run(20.0) <= run(2.0)


# --- insert/delete as one program, against the per-level reference -------

DELTA_N, DELTA_CAP = 81920, 163840   # room for a 65,536-row insert


@pytest.fixture(scope="module")
def delta_dps():
    r = np.random.default_rng(7)
    pts = jnp.asarray(r.random((DELTA_N, 3)), jnp.float32)
    w = jnp.asarray(0.5 + r.random(DELTA_N), jnp.float32)
    return dynamic.from_points(pts, w, capacity=DELTA_CAP, max_depth=10, bucket_size=32)


def _assert_same_set(got, want, weight64):
    """``got`` equals the reference but for the tree's weights, which lie
    within float32 rounding of their float64 values ``weight64``: the
    reference adds each row at every ancestor in turn (up to ~50 eps off
    at 65,536 rows), the program sums rows per leaf and then up the levels."""
    for name in ("points", "weights", "active", "leaf_id"):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(want, name)), err_msg=name)
    np.testing.assert_array_equal(np.asarray(got.tree.count), np.asarray(want.tree.count))
    eps = float(np.finfo(np.float32).eps)
    np.testing.assert_array_less(
        np.abs(np.asarray(got.tree.weight, np.float64) - weight64),
        8 * eps * np.maximum(np.abs(weight64), 1.0))


@pytest.mark.parametrize("k", [1, 7, 3000, 65536])
def test_insert_matches_per_level_reference(delta_dps, k):
    r = np.random.default_rng(k)
    new = jnp.asarray(r.random((k, 3)), jnp.float32)
    w = jnp.asarray(0.5 + r.random(k), jnp.float32)
    want, _, lid = ref.insert(delta_dps, new, w)
    tree = delta_dps.tree
    weight64 = np.asarray(tree.weight, np.float64) + ref.path_sums64(tree.num_nodes, lid, w)
    _assert_same_set(dynamic.insert(delta_dps, new, w), want, weight64)


@pytest.mark.parametrize("k", [1, 7, 3000, 65536])
def test_delete_matches_per_level_reference(delta_dps, k):
    """Ids drawn over the whole capacity: half are already inactive, and
    a batch repeats ids (the last row repeats the first)."""
    r = np.random.default_rng(k + 1)
    ids = r.integers(0, DELTA_CAP, k).astype(np.int32)
    ids[-1] = ids[0]
    ids = jnp.asarray(ids)
    want, removed = ref.delete(delta_dps, ids)
    gone = np.asarray(ids)[np.asarray(removed)]
    tree = delta_dps.tree
    weight64 = np.asarray(tree.weight, np.float64) - ref.path_sums64(
        tree.num_nodes, np.asarray(delta_dps.leaf_id)[gone], np.asarray(delta_dps.weights)[gone])
    got = dynamic.delete(delta_dps, ids)
    _assert_same_set(got, want, weight64)
    assert int(got.tree.count[0]) == DELTA_N - int(removed.sum())


@pytest.mark.parametrize("d", [2, 3, 5])
def test_locate_matches_per_array_walk(d):
    """Fresh queries and the build's own points (which land on splits'
    lower sides exactly as the build filed them)."""
    r = np.random.default_rng(d)
    pts = jnp.asarray(r.random((4096, d)), jnp.float32)
    tree = dynamic.from_points(pts, max_depth=10).tree
    q = jnp.concatenate([jnp.asarray(r.random((4096, d)), jnp.float32), pts])
    got = np.asarray(dynamic.locate(tree, q, 10))
    np.testing.assert_array_equal(got, np.asarray(ref.locate(tree, q, 10)))
    np.testing.assert_array_equal(got[4096:], np.asarray(tree.leaf_id))


def test_padded_rows_wastes_at_most_an_eighth():
    sizes = range(1, 2**22 + 1)
    padded = [dynamic.padded_rows(k) for k in sizes]
    assert all(p >= k and 8 * (p - k) <= k for k, p in zip(sizes, padded))
    assert dynamic.padded_rows(65536) == 65536
    assert dynamic.padded_rows(3000) == 3072
    # an octave of sizes compiles for m * 2**17, m in [8, 16], and no other
    assert set(padded[2**20 - 1:2**21 - 1]) == {m << 17 for m in range(8, 17)}
