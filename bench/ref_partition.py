"""Plain host reference for one repartition step, and the numbers compared.

The engine under test files every live point under a leaf bucket of a
kd-tree, orders the buckets along a Hilbert curve by their centroids at
its last rebuild, and assigns every bucket to one of P parts by a greedy
knapsack over the bucket weights in that order. This reference checks
each of those layers from the benchmark's own points and weights, in
float64 on the host:

* the bucket structure: each live point lies in the region of the leaf
  it is filed under, the region cut out by the tree's split planes on
  the leaf's path from the root, so the buckets are disjoint regions
  that hold their own points;
* the curve: at a rebuild, each bucket's count and centroid against
  those of its points, and its Hilbert key (a plain implementation of
  Skilling's transpose algorithm); the engine's order has to sort the
  buckets by those keys;
* the knapsack over the bucket weights in that order, the parts'
  loads, and the (P, P) migration counts between the previous and the
  new assignment.

``knapsack`` takes a dtype, so the same code computed in bfloat16 is the
control that has to come out as not correct.
"""
from __future__ import annotations

import numpy as np

SENTINEL = 0xFFFFFFFF
# a centroid's curve cell is taken as known only where the float32
# quantization on the device, with a few ulp of rounding in the division,
# cannot reach a neighbouring cell: the unit coordinate is further than
# this from a cell boundary
CELL_EDGE = 1e-6


def knapsack(w: np.ndarray, parts: int, dtype=np.float64) -> np.ndarray:
    """Greedy midpoint rule: element i goes to part
    floor((exclusive prefix + w_i / 2) / (total / P)), clipped to P-1."""
    w = np.asarray(w).astype(dtype)
    prefix = np.cumsum(w, dtype=dtype) - w
    ideal = (prefix[-1] + w[-1]) / dtype(parts)
    part = np.floor((prefix + w / dtype(2)) / ideal).astype(np.float64)
    return np.clip(part, 0, parts - 1).astype(np.int32)


def migration_counts(old: np.ndarray, new: np.ndarray, parts: int) -> np.ndarray:
    """(P, P) counts of slots assigned in both, by (old part, new part)."""
    both = (old >= 0) & (new >= 0)
    idx = old[both].astype(np.int64) * parts + new[both]
    return np.bincount(idx, minlength=parts * parts).reshape(parts, parts)


# -- bucket structure ---------------------------------------------------------

def leaf_regions(split_dim, split_val, is_leaf, d: int):
    """Region of every node of the tree, by the splits on its path from
    the root (heap node 0, children 2k+1 and 2k+2): a point goes to the
    upper child where its coordinate along the node's split dimension is
    above the split value. Returns (lo, hi, end): a point p lies in node
    k's region where lo[k] < p <= hi[k] in every dimension; ``end`` marks
    the nodes where a walk from the root stops, a leaf or a node with no
    split, reached without passing another. The regions of those nodes
    are disjoint and cover the space."""
    sd, sv = np.asarray(split_dim), np.asarray(split_val, np.float32)
    stop = np.asarray(is_leaf) | (sd < 0)
    m = sd.shape[0]
    lo = np.full((m, d), -np.inf, np.float32)
    hi = np.full((m, d), np.inf, np.float32)
    reached = np.zeros(m, bool)
    reached[0] = True
    first = 0
    while 2 * first + 1 < m:                        # one level of the heap
        k = np.arange(first, 2 * first + 1)
        go = reached[k] & ~stop[k]
        for side, bound in ((0, hi), (1, lo)):
            c = 2 * k + 1 + side
            lo[c], hi[c] = lo[k], hi[k]
            bound[c[go], sd[k[go]]] = sv[k[go]]
            reached[c] = go
        first = 2 * first + 1
    return lo, hi, reached & stop


def misfiled(points, live, leaf_id, split_dim, split_val, is_leaf) -> int:
    """Live points filed under another node than the leaf whose region
    holds them."""
    live = np.asarray(live)
    pts = np.asarray(points, np.float32)[live]
    lid = np.asarray(leaf_id)[live]
    lo, hi, end = leaf_regions(split_dim, split_val, is_leaf, pts.shape[1])
    node = np.where((lid >= 0) & (lid < end.shape[0]), lid, -1)
    inside = np.all((pts > lo[node]) & (pts <= hi[node]), axis=1)
    return int(np.sum(~((node >= 0) & end[node] & inside)))


# -- the curve ------------------------------------------------------------------

def hilbert_keys(cells: np.ndarray, bits: int) -> np.ndarray:
    """Hilbert index of (n, d) integer cells in [0, 2^bits) per dimension
    (J. Skilling, "Programming the Hilbert curve", AIP Conf. Proc. 707,
    2004: AxesToTranspose, then the transposed bits read out with
    dimension 0 as the most significant of each level)."""
    x = [np.asarray(cells[:, i], np.uint64).copy() for i in range(cells.shape[1])]
    d = len(x)
    q = 1 << (bits - 1)
    while q > 1:                                   # inverse undo
        p = np.uint64(q - 1)
        for i in range(d):
            hit = (x[i] & np.uint64(q)) != 0
            t = (x[0] ^ x[i]) & p
            x0 = np.where(hit, x[0] ^ p, x[0] ^ t)
            if i:
                x[i] = np.where(hit, x[i], x[i] ^ t)
            x[0] = x0
        q >>= 1
    for i in range(1, d):                          # Gray encode
        x[i] ^= x[i - 1]
    t = np.zeros_like(x[0])
    q = 1 << (bits - 1)
    while q > 1:
        t = np.where((x[d - 1] & np.uint64(q)) != 0, t ^ np.uint64(q - 1), t)
        q >>= 1
    x = [xi ^ t for xi in x]
    key = np.zeros_like(x[0])
    for b in range(bits - 1, -1, -1):
        for i in range(d):
            key = (key << np.uint64(1)) | ((x[i] >> np.uint64(b)) & np.uint64(1))
    return key


def frame(points: np.ndarray, margin: float) -> tuple[np.ndarray, np.ndarray]:
    """The engine's quantization frame at a rebuild, in float32: the live
    points' bounding box widened by ``margin`` times its span per side."""
    pts = np.asarray(points, np.float32)
    lo, hi = pts.min(axis=0), pts.max(axis=0)
    span = np.where(hi > lo, hi - lo, np.float32(1.0)).astype(np.float32)
    return lo - np.float32(margin) * span, hi + np.float32(margin) * span


def curve_candidates(centroid, lo, hi, bits: int) -> np.ndarray:
    """(B, 2^d) Hilbert keys a centroid can take under float32 rounding:
    all equal where its cell is known; where a unit coordinate lies
    within ``CELL_EDGE`` of a cell boundary, both cells of that
    dimension. Cells clip into the frame as the engine's do."""
    c = np.asarray(centroid, np.float64)
    lo, hi = np.asarray(lo, np.float32), np.asarray(hi, np.float32)
    span = np.where(hi > lo, hi - lo, np.float32(1.0)).astype(np.float64)
    scaled = (c - lo.astype(np.float64)) / span * (1 << bits)
    top = (1 << bits) - 1
    cell = np.clip(np.floor(scaled), 0, top).astype(np.int64)
    near = np.rint(scaled)
    edge = np.abs(scaled - near) < CELL_EDGE * (1 << bits)
    other = np.where(edge, np.where(near > scaled, cell + 1, cell - 1), cell)
    other = np.clip(other, 0, top)
    d = c.shape[1]
    out = []
    for corner in range(1 << d):
        pick = np.array([(corner >> i) & 1 for i in range(d)], bool)
        out.append(hilbert_keys(np.where(pick, other, cell), bits))
    return np.stack(out, axis=1)


def rebuild_curve(points, live, leaf_id, is_leaf, count, centroid, margin: float,
                  bits: int) -> tuple[int, np.ndarray]:
    """At a rebuild: (buckets whose count or centroid is off those of
    their live points, candidate keys per node). A bucket is a leaf that
    holds a live point; the other nodes take the sentinel key, after
    every bucket. The centroid may differ from the float64 mean by the
    float32 rounding of a sum of n terms, at most about
    n * 2^-24 * max|x|: twice that is allowed."""
    live = np.asarray(live)
    pts = np.asarray(points, np.float64)[live]
    lid = np.asarray(leaf_id)[live]
    m = np.asarray(is_leaf).shape[0]
    n = np.bincount(lid, minlength=m)
    mean = np.stack([np.bincount(lid, weights=pts[:, i], minlength=m) for i in range(pts.shape[1])],
                    axis=1) / np.maximum(n, 1)[:, None]
    held = n > 0
    tol = 2.0 * (n[:, None] + 2) * 2.0**-24 * np.abs(pts).max()
    c_eng = np.asarray(centroid, np.float64)
    off = (np.asarray(count) != n) | (held & np.any(np.abs(c_eng - mean) > tol, axis=1))
    bucket = np.asarray(is_leaf) & held
    lo, hi = frame(np.asarray(points)[live], margin)
    cand = np.full((m, 1 << pts.shape[1]), SENTINEL, np.uint64)
    cand[bucket] = curve_candidates(np.asarray(centroid)[bucket], lo, hi, bits)
    return int(off.sum()), cand


def curve_descents(order, cand: np.ndarray) -> int:
    """Places where the engine's order cannot follow the curve: walking
    the order, each node takes its smallest candidate key at or above the
    last key taken (greedy, so 0 exactly when some choice of candidates
    is sorted); a node with none counts one."""
    seq = cand[np.asarray(order)]
    lo = seq.min(axis=1)
    fixed = lo == seq.max(axis=1)
    descents, last = 0, 0
    for i in range(seq.shape[0]):
        if fixed[i]:
            k = lo[i]
        else:
            above = seq[i][seq[i] >= last]
            k = above.min() if above.size else None
        if k is None or k < last:
            descents += 1
            k = lo[i] if k is None else k
        last = k
    return descents


# -- one step -------------------------------------------------------------------

def check_step(part, prev_part, loads, send_counts, weights, live, leaf_id, order,
               parts: int) -> dict:
    """Readings for one step's slicing (host numpy arrays), over the
    bucket membership and order that ``misfiled`` and ``curve_descents``
    check:

    * ``misassigned``: live slots outside [0, P), free slots assigned,
      and live slots whose part differs from their bucket's;
    * ``descents``: places where the part goes down along the curve;
    * ``cut_shift``: largest distance, in weight along the curve,
      between a cut of the engine and of the float64 knapsack, in units
      of the largest bucket weight;
    * ``load_gap``: largest gap between the engine's reported load of a
      part and the float64 sum of its points' weights, over the mean load;
    * ``migration_gap``: largest gap in the migration count matrix.
    """
    part = np.asarray(part)
    w64 = np.asarray(weights, np.float64)
    bad = int(np.sum(live & ((part < 0) | (part >= parts))) + np.sum(~live & (part != -1)))
    lid, p_live, w_live = leaf_id[live], part[live], w64[live]
    m = order.shape[0]
    bucket_part = np.full((m,), -1, np.int64)
    bucket_part[lid] = p_live
    misassigned = bad + int(np.sum(bucket_part[lid] != p_live))

    w_rank = np.bincount(lid, weights=w_live, minlength=m)[order]
    held = w_rank > 0
    w_held, dev = w_rank[held], bucket_part[order][held]
    descents = int(np.sum(np.diff(dev) < 0))
    ref = knapsack(w_held, parts)
    along = np.concatenate([[0.0], np.cumsum(w_held)])
    cuts = np.arange(1, parts)
    shift = np.abs(along[np.searchsorted(dev, cuts)] - along[np.searchsorted(ref, cuts)])

    host_loads = np.bincount(np.clip(p_live, 0, parts - 1), weights=w_live, minlength=parts)
    load_gap = np.abs(np.asarray(loads, np.float64) - host_loads).max() / host_loads.mean()
    mig = np.abs(np.asarray(send_counts, np.int64) - migration_counts(prev_part, part, parts))
    return {
        "misassigned": misassigned,
        "descents": descents,
        "cut_shift": float(shift.max() / w_held.max()),
        "load_gap": float(load_gap),
        "migration_gap": int(mig.max()),
    }
