"""The plain references against the program, on the CPU at tiny sizes."""
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import ref_partition
import tiny


def test_float64_knapsack_matches_program_knapsack():
    from repro.core import knapsack

    w = np.random.default_rng(3).uniform(0.5, 1.5, 5000).astype(np.float32)
    got = np.asarray(knapsack.slice_weighted_curve(jnp.asarray(w), 16))
    np.testing.assert_array_equal(got, ref_partition.knapsack(w, 16))


def test_knapsack_spread_within_twice_the_largest_weight():
    w = np.random.default_rng(4).uniform(0.5, 1.5, 5000)
    loads = np.bincount(ref_partition.knapsack(w, 16), weights=w, minlength=16)
    assert loads.max() - loads.min() <= 2 * w.max()


def test_migration_counts_match_program():
    from repro.core import repartition

    rng = np.random.default_rng(5)
    old = rng.integers(-1, 8, 1000).astype(np.int32)
    new = rng.integers(-1, 8, 1000).astype(np.int32)
    want = np.asarray(repartition._send_counts_kernel(jnp.asarray(old), jnp.asarray(new), 8))
    np.testing.assert_array_equal(ref_partition.migration_counts(old, new, 8), want)


def _engine(seed=7, n=4096):
    from repro.core import partitioner as pt
    from repro.core.repartition import Repartitioner
    from points import clustered_points

    cfg = tiny.cell("points3d.drift").config
    pts, w = clustered_points(seed, n, cfg)
    rp = Repartitioner(pts, w, 8, pt.PartitionerConfig(use_tree=True), capacity=n,
                       max_depth=8, bucket_size=32, frame_margin=0.25)
    return rp, np.asarray(pts), w


def _structure(rp, pts):
    tree, summary = rp.dps.tree, rp.summary()
    live = np.ones(pts.shape[0], bool)
    arrays = [np.asarray(a) for a in (rp.dps.leaf_id, tree.split_dim, tree.split_val,
                                      tree.is_leaf, summary.count, summary.centroid,
                                      rp._border.order)]
    return live, arrays


def test_sound_engine_step_reads_zero():
    rp, pts, w = _engine()
    prev = np.asarray(rp.part)
    w2 = w * (1.0 + 3.0 * (pts[:, 0] < 0.3))
    rp.update_weights(w2)
    step = rp.step()
    live, (lid, sd, sv, leaf, count, centroid, order) = _structure(rp, pts)
    r = ref_partition.check_step(
        np.asarray(step.part), prev, step.loads, step.plan.send_counts, np.asarray(w2),
        live, lid, order, 8)
    assert r["misassigned"] == r["descents"] == r["migration_gap"] == 0
    assert r["cut_shift"] <= 1.0 and r["load_gap"] < 1e-5
    assert ref_partition.misfiled(pts, live, lid, sd, sv, leaf) == 0
    off, cand = ref_partition.rebuild_curve(pts, live, lid, leaf, count, centroid, 0.25, 10)
    assert off == 0 and ref_partition.curve_descents(order, cand) == 0


def test_structure_faults_read_nonzero():
    rp, pts, _ = _engine()
    live, (lid, sd, sv, leaf, count, centroid, order) = _structure(rp, pts)
    nb = int(rp._border.num_buckets)
    wrong = lid.copy()
    wrong[5] = lid[order[nb - 1]] if lid[5] != lid[order[nb - 1]] else lid[order[0]]
    assert ref_partition.misfiled(pts, live, wrong, sd, sv, leaf) == 1
    _, cand = ref_partition.rebuild_curve(pts, live, lid, leaf, count, centroid, 0.25, 10)
    swapped = order.copy()
    swapped[[0, nb - 1]] = order[[nb - 1, 0]]
    assert ref_partition.curve_descents(swapped, cand) >= 1
    moved = centroid.copy()
    moved[order[3], 1] += 1e-3
    assert ref_partition.rebuild_curve(pts, live, lid, leaf, count, moved, 0.25, 10)[0] == 1
    fewer = count.copy()
    fewer[order[3]] -= 1
    assert ref_partition.rebuild_curve(pts, live, lid, leaf, fewer, centroid, 0.25, 10)[0] == 1


def test_regions_agree_with_program_locate():
    """A point is filed right exactly where the program's root-to-leaf
    walk would put it."""
    from repro.core import dynamic

    rp, pts, _ = _engine(seed=11)
    tree = rp.dps.tree
    q = np.random.default_rng(6).random((2000, 3)).astype(np.float32)
    q[:50] = pts[:50]
    q[50:60, 0] = np.asarray(tree.split_val)[0]     # on the root's split plane
    located = np.asarray(dynamic.locate(tree, q, tree.max_depth))
    live = np.ones(q.shape[0], bool)
    args = (tree.split_dim, tree.split_val, tree.is_leaf)
    assert ref_partition.misfiled(q, live, located, *args) == 0
    for wrong in (located + 1, 2 * located + 1, (located - 1) // 2):
        assert ref_partition.misfiled(q, live, wrong, *args) == np.sum(wrong != located)


@pytest.mark.parametrize("d,bits", [(3, 10), (2, 16), (3, 4)])
def test_hilbert_keys_match_program(d, bits):
    from repro.core import sfc

    cells = np.random.default_rng(bits).integers(0, 1 << bits, (5000, d)).astype(np.uint32)
    want = np.asarray(sfc.hilbert_key_from_cells(jnp.asarray(cells), bits))
    np.testing.assert_array_equal(ref_partition.hilbert_keys(cells, bits), want)


def test_hilbert_keys_visit_neighbours():
    """Consecutive keys are adjacent cells, and every cell has one key."""
    bits, d = 3, 3
    cells = np.stack(np.meshgrid(*[np.arange(1 << bits)] * d, indexing="ij"), -1).reshape(-1, d)
    keys = ref_partition.hilbert_keys(cells, bits)
    assert sorted(keys.tolist()) == list(range(1 << (bits * d)))
    path = cells[np.argsort(keys)]
    assert np.all(np.abs(np.diff(path, axis=0)).sum(axis=1) == 1)


def test_curve_check_tolerates_rounding_of_the_quantization():
    """Centroids next to cell boundaries, keyed with the division off by
    a few ulp either way (as a device may round): the order those keys
    give passes, and a swap of two distant buckets does not."""
    from repro.core import sfc

    rng = np.random.default_rng(8)
    b, bits = 4000, 10
    cells = rng.integers(1, (1 << bits) - 1, (b, 3))
    offset = rng.choice([-3e-7, 3e-7, 0.5 / 1024], (b, 3))
    c = ((cells / (1 << bits)) + offset).astype(np.float32)
    lo, hi = np.zeros(3, np.float32), np.ones(3, np.float32)
    unit = np.clip(c.astype(np.float64) * (1 + rng.choice([-4e-7, 0, 4e-7], (b, 3))),
                   0, 1 - 1e-7)
    dev_cells = np.floor(unit * (1 << bits)).astype(np.uint32)
    keys = np.asarray(sfc.hilbert_key_from_cells(jnp.asarray(dev_cells), bits))
    order = np.argsort(keys, kind="stable")
    cand = ref_partition.curve_candidates(c, lo, hi, bits)
    assert np.sum(cand.min(axis=1) != cand.max(axis=1)) > b // 2
    assert ref_partition.curve_descents(order, cand) == 0
    swapped = order.copy()
    swapped[[10, b - 10]] = order[[b - 10, 10]]
    assert ref_partition.curve_descents(swapped, cand) >= 1


def test_curve_candidates_cover_both_cells_at_an_edge():
    lo, hi = np.zeros(3, np.float32), np.ones(3, np.float32)
    c = np.array([[0.5 + 0.5 / 1024, 0.25 + 1e-3 / 1024, 0.7]], np.float32)
    cand = ref_partition.curve_candidates(c, lo, hi, 10)
    assert len(set(cand[0].tolist())) == 2
    c = np.array([[0.5 + 0.5 / 1024, 0.3 + 0.5 / 1024, 0.7 + 0.5 / 1024]], np.float32)
    assert len(set(ref_partition.curve_candidates(c, lo, hi, 10)[0].tolist())) == 1


def test_check_passes_only_below_limit():
    assert harness.Check("x", 0.0, 0).ok
    assert not harness.Check("x", 1.0, 0).ok
    assert not harness.Check("x", float("nan"), 1.0).ok
