"""Point location + k-NN (paper §V-A)."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import queries


def test_point_location_exact(rng):
    pts = jnp.asarray(rng.random((2048, 3)), jnp.float32)
    idx = queries.build_index(pts, bucket_size=32)
    sel = rng.choice(2048, 256, replace=False)
    q = pts[jnp.asarray(sel)]
    found, gid, ok = queries.point_location(idx, q)
    assert bool(found.all()) and bool(ok.all())
    # returned ids identify coordinates equal to the query
    np.testing.assert_array_equal(np.asarray(pts)[np.asarray(gid)], np.asarray(q))


def test_point_location_misses(rng):
    pts = jnp.asarray(rng.random((2048, 3)), jnp.float32)
    idx = queries.build_index(pts, bucket_size=32)
    q = jnp.asarray(rng.random((256, 3)) + 2.0, jnp.float32)  # outside bbox
    found, gid, ok = queries.point_location(idx, q)
    assert not bool(found.any())
    assert (np.asarray(gid) == -1).all()
    assert bool(ok.all())  # certified misses: the key runs were fully scanned


def test_point_location_duplicate_heavy(rng):
    """>bucket_cap points sharing one SFC key (one quantization cell):
    the scan must either find the match or flag the miss as uncertified —
    never miss silently (the pre-CurveIndex bug)."""
    base = np.full((200, 3), 0.5, np.float32)
    base += rng.random((200, 3)).astype(np.float32) * 1e-5  # one cell at bits=10
    rest = rng.random((1848, 3)).astype(np.float32)
    pts = jnp.asarray(np.concatenate([base, rest]))
    idx = queries.build_index(pts, bucket_size=32)
    q = pts[:200]
    found, gid, ok = queries.point_location(idx, q, bucket_cap=64)
    # every miss is flagged: found | ~ok covers all queries
    assert bool((found | ~ok).all())
    # raising the cap past the run length resolves every query exactly
    found2, gid2, ok2 = queries.point_location(idx, q, bucket_cap=256)
    assert bool(found2.all()) and bool(ok2.all())
    np.testing.assert_array_equal(np.asarray(pts)[np.asarray(gid2)], np.asarray(q))


@pytest.mark.parametrize("bucket_size", [1, 16, 300])
def test_locate_bucket_is_last_bucket_at_or_below(bucket_size, rng):
    """The bucket of a query is the last directory bucket whose first key
    is <= the query's key, clamped to bucket 0 below the first."""
    from repro.core import curve_index as ci

    pts = jnp.asarray(rng.random((1024, 3)), jnp.float32)
    idx = queries.build_index(pts, bucket_size=bucket_size)
    q = jnp.concatenate([pts[:64], jnp.asarray(rng.random((64, 3)), jnp.float32)])
    qk = np.asarray(ci.query_keys(idx, q))
    want = np.searchsorted(np.asarray(idx.bucket_keys), qk, side="right") - 1
    got = np.asarray(queries.locate_bucket(idx, q))
    np.testing.assert_array_equal(got, np.clip(want, 0, idx.num_buckets - 1))
    # a stored point's bucket holds its sorted position
    starts = np.asarray(idx.bucket_starts)
    hit = np.asarray(queries.point_location(idx, q[:64]).found)
    assert hit.all()
    lo = np.searchsorted(np.asarray(idx.keys)[: starts[-1]], qk[:64], side="left")
    assert ((starts[got[:64]] <= lo) & (lo < starts[got[:64] + 1])).all()


def test_point_location_tree_index_large_leaves(rng):
    """Tree-backed index whose leaves hold hundreds of points: every
    stored point is found, every point outside the box is a certified
    miss (the key-run search has no per-bucket window)."""
    from repro.core import partitioner

    pts = jnp.asarray(rng.random((4096, 3)), jnp.float32)
    cfg = partitioner.PartitionerConfig(curve="morton", use_tree=True, bucket_size=512)
    _, idx = partitioner.partition_with_index(pts, None, 4, cfg)
    assert idx.tree is not None and idx.max_bucket_len >= 256
    q = jnp.concatenate([pts[::16], jnp.asarray(rng.random((64, 3)) + 2.0, jnp.float32)])
    found, gid, ok = queries.point_location(idx, q, bucket_cap=idx.max_bucket_len)
    found, gid, ok = np.asarray(found), np.asarray(gid), np.asarray(ok)
    assert found[:256].all() and not found[256:].any() and ok.all()
    np.testing.assert_array_equal(np.asarray(pts)[gid[:256]], np.asarray(q[:256]))


@pytest.mark.parametrize("side", ["left", "right"])
def test_rank_is_searchsorted_over_live_keys(side, rng):
    """A key run's bounds come from searchsorted over the live keys only:
    key runs spanning several buckets, keys below the first and above
    the last, key 0, and a sentinel tail of dead slots."""
    from repro.core import curve_index as ci

    n_valid, cap = 600, 700
    live = np.sort(np.concatenate([
        rng.integers(1, 2**32 - 1, n_valid - 100, dtype=np.uint64),
        np.full(100, 2**31, np.uint64),       # one run across ~6 buckets
    ])).astype(np.uint32)
    keys = np.concatenate([live, np.full(cap - n_valid, 0xFFFFFFFF, np.uint32)])
    idx = ci.from_sorted(
        jnp.zeros((cap, 3), jnp.float32), jnp.arange(cap, dtype=jnp.int32),
        jnp.asarray(keys), n_valid=n_valid, frame_lo=jnp.zeros(3),
        frame_hi=jnp.ones(3), bits=10, bucket_size=16,
    )
    qk = np.concatenate([
        live[rng.integers(0, n_valid, 200)],
        rng.integers(0, 2**32, 200, dtype=np.uint64).astype(np.uint32),
        np.asarray([0, 1, 2**31 - 1, 2**31, 2**31 + 1, 2**32 - 1], np.uint32),
    ])
    got = queries._rank(idx, jnp.asarray(qk), side)
    np.testing.assert_array_equal(np.asarray(got), np.searchsorted(live, qk, side=side))


@pytest.mark.parametrize("k", [pytest.param(1, marks=pytest.mark.slow), 3, pytest.param(5, marks=pytest.mark.slow)])
def test_knn_recall(k, rng):
    pts = jnp.asarray(rng.random((4096, 3)), jnp.float32)
    idx = queries.build_index(pts, bucket_size=32)
    q = jnp.asarray(rng.random((128, 3)), jnp.float32)
    d_a, id_a = queries.knn(idx, q, k=k, cutoff_buckets=2)
    d_b, id_b = queries.knn_bruteforce(pts, q, k=k)
    recall = float(
        jnp.mean(jnp.any(id_a[:, :, None] == id_b[:, None, :], axis=1).astype(jnp.float32))
    )
    assert recall > 0.7, f"recall@{k}: {recall}"  # CUTOFF-bounded approximate k-NN


def test_knn_distances_sorted_and_valid(rng):
    pts = jnp.asarray(rng.random((2048, 2)), jnp.float32)
    idx = queries.build_index(pts)
    q = jnp.asarray(rng.random((64, 2)), jnp.float32)
    d, ids = queries.knn(idx, q, k=3)
    d = np.asarray(d)
    assert (np.diff(d, axis=1) >= -1e-6).all()
    assert np.isfinite(d).all()


def test_knn_window_covers_large_buckets(rng):
    """Candidate window derived from true bucket extents: with
    bucket_size > the old fixed 64-slot cap, clustered data must still
    reach full self-recall (the truncation bug regression test)."""
    cl = 0.3 + 0.05 * rng.random((1500, 3)).astype(np.float32)  # dense cluster
    rest = rng.random((548, 3)).astype(np.float32)
    pts = jnp.asarray(np.concatenate([cl, rest]))
    idx = queries.build_index(pts, bucket_size=128)
    assert idx.max_bucket_len > 64  # the regime the old window undercovered
    q = pts[:256]
    d, ids = queries.knn(idx, q, k=1, cutoff_buckets=1)
    # nearest neighbor of a stored point is itself — fails if the window
    # stops short of the true bucket extent
    assert float(np.asarray(d).max()) <= 1e-6
    d3, id3 = queries.knn(idx, q[:64], k=3, cutoff_buckets=2)
    d_b, id_b = queries.knn_bruteforce(pts, q[:64], k=3)
    recall = float(np.mean(np.any(
        np.asarray(id3)[:, :, None] == np.asarray(id_b)[:, None, :], axis=1)))
    assert recall > 0.7, recall


@given(n=st.integers(100, 2000), seed=st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_property_self_query_returns_self(n, seed):
    rng = np.random.default_rng(seed)
    pts = jnp.asarray(rng.random((n, 3)), jnp.float32)
    idx = queries.build_index(pts, bucket_size=16)
    q = pts[:64]
    d, ids = queries.knn(idx, q, k=1, cutoff_buckets=1)
    assert float(d.max()) <= 1e-6  # nearest neighbor of a stored point is itself
