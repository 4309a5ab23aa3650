"""Adaptive (quadtree / octree) cell meshes — the paper's mesh workload.

The paper's software "was primarily used for partitioning 2 and 3
dimensional meshes in scientific computing" whose load distribution
changes over time. This module is that workload generator: a dyadic cell
mesh over the unit box, represented as *weighted center points* — the
exact input type of the partition core — with vectorized refine /
coarsen steps that track a moving load feature, so cell count and
weights change every timestep.

Cell addressing is purely integer: a cell is ``(level, ij)`` with
``ij in [0, 2**level)^d``; its center and extent follow in closed form,
so the whole mesh is a handful of numpy arrays and every operation
(refinement, neighbor derivation, transfer-map construction) is a
vectorized key lookup — no per-cell Python objects, no pointers.

Invariants maintained by :func:`refine_coarsen`:

* **2:1 balance** — face neighbors differ by at most one level (the
  graded-tree property every AMR halo scheme assumes; enforced by a
  refinement ripple and a conservative coarsening guard).
* **exact tiling** — active cells tile the unit box exactly (cell
  volumes are dyadic, so the conservation check is exact in float64).
* **deterministic transfer** — refine injects the parent value into its
  2^d children, coarsen averages the 2^d children in fixed child order;
  :func:`apply_transfer` is the ONE implementation both the distributed
  simulation and the single-device reference use, which is what makes
  their trajectories bit-comparable.

Cell *identity* across steps is storage-slot ids inside a
`repro.core.repartition.Repartitioner`, tracked by the DRIVER
(`mesh/simulate`), not by the mesh: trajectory meshes are shared,
immutable inputs to every backend, so driver-specific engine state
never lives on them. `Transfer.born`/`died_idx` carry the structural
bookkeeping the driver needs to keep its slot array current.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# bits per coordinate in the packed (level, ij) cell key: 20 for d <= 2,
# 19 for d = 3 (3 x 19 coordinate bits leave 6 for the level in a signed
# int64). The cap on max_level is the smaller of the coordinate bits and
# the largest level the remaining bits hold: 20 in 1-D and 2-D, 19 in 3-D
# (see ``max_level_cap``)
_COORD_BITS = 20


def _coord_bits(d: int) -> int:
    return min(_COORD_BITS, 57 // max(d, 1))


def max_level_cap(d: int) -> int:
    """Finest level a ``d``-dimensional mesh may hold: its cell keys stay
    unique (no two cells alias) up to this level."""
    b = _coord_bits(d)
    return min(b, (1 << (63 - d * b)) - 1)


@dataclass(frozen=True)
class AMRMesh:
    """A dyadic cell mesh over the unit box ``[0, 1]^d``."""

    level: np.ndarray   # (n,) int32 refinement level per active cell
    ij: np.ndarray      # (n, d) int64 integer coords in [0, 2**level)^d
    base_level: int     # coarsest allowed level (the initial uniform grid)
    max_level: int      # finest allowed level

    @property
    def n(self) -> int:
        return int(self.level.shape[0])

    @property
    def d(self) -> int:
        return int(self.ij.shape[1])

    def centers(self) -> np.ndarray:
        """(n, d) float32 cell centers — the partitioner's point set."""
        h = (0.5 ** self.level.astype(np.float64))[:, None]
        return ((self.ij.astype(np.float64) + 0.5) * h).astype(np.float32)

    def sizes(self) -> np.ndarray:
        """(n,) float32 cell side lengths."""
        return (0.5 ** self.level.astype(np.float64)).astype(np.float32)

    def volumes(self) -> np.ndarray:
        """(n,) float64 cell volumes (dyadic — exact)."""
        return 0.5 ** (self.d * self.level.astype(np.float64))


def uniform_mesh(d: int = 2, base_level: int = 3, max_level: int = 6) -> AMRMesh:
    """Uniform mesh of ``2**(d*base_level)`` cells at ``base_level``."""
    if not 0 <= base_level <= max_level:
        raise ValueError(f"bad levels base={base_level} max={max_level}")
    # a level past the cap would alias other cells' packed keys and make
    # _CellLookup return unrelated neighbors
    if max_level > max_level_cap(d):
        raise ValueError(
            f"max_level={max_level} overflows the packed cell key for d={d} "
            f"(limit {max_level_cap(d)})"
        )
    side = 1 << base_level
    grids = np.meshgrid(*([np.arange(side, dtype=np.int64)] * d), indexing="ij")
    ij = np.stack([g.reshape(-1) for g in grids], axis=1)
    n = ij.shape[0]
    return AMRMesh(
        level=np.full((n,), base_level, np.int32),
        ij=ij,
        base_level=base_level,
        max_level=max_level,
    )


# ---------------------------------------------------------------------------
# packed-key lookup (the vectorized replacement for a pointer tree)
# ---------------------------------------------------------------------------

def _pack(level: np.ndarray, ij: np.ndarray) -> np.ndarray:
    """Unique int64 key per (level, ij) cell."""
    b = _coord_bits(ij.shape[1])
    key = level.astype(np.int64)
    for a in range(ij.shape[1]):
        key = (key << b) | ij[:, a].astype(np.int64)
    return key


class _CellLookup:
    """Sorted-key index: (level, ij) -> position in the mesh's cell order."""

    def __init__(self, level: np.ndarray, ij: np.ndarray):
        keys = _pack(level, ij)
        self.order = np.argsort(keys)
        self.keys = keys[self.order]

    def find(self, level: np.ndarray, ij: np.ndarray) -> np.ndarray:
        """(k,) int64 cell index per query, -1 where absent."""
        q = _pack(level, ij)
        if self.keys.shape[0] == 0:
            return np.full(q.shape, -1, np.int64)
        pos = np.searchsorted(self.keys, q)
        pos_c = np.minimum(pos, self.keys.shape[0] - 1)
        hit = self.keys[pos_c] == q
        return np.where(hit, self.order[pos_c], -1)


def _child_offsets(d: int) -> np.ndarray:
    """(2**d, d) int64 child coordinate offsets in fixed binary order —
    the deterministic sibling order every transfer map relies on."""
    k = 1 << d
    offs = np.zeros((k, d), np.int64)
    for c in range(k):
        for a in range(d):
            offs[c, a] = (c >> (d - 1 - a)) & 1
    return offs


# ---------------------------------------------------------------------------
# face neighbors (2:1-balanced: same level, one coarser, or 2^(d-1) finer)
# ---------------------------------------------------------------------------

def neighbor_slots_per_cell(d: int) -> int:
    """Static width of the neighbor table: 2d faces x 2^(d-1) sub-slots."""
    return 2 * d * (1 << (d - 1))


def face_group(K: int) -> int:
    """Slots per face of a K-wide :func:`face_neighbors` table: the first
    slot of a face holds a same-level or coarser neighbour, the others
    only finer ones. 1 where K is no such width."""
    for d in (1, 2, 3):
        if K == neighbor_slots_per_cell(d):
            return 1 << (d - 1)
    return 1


def face_neighbors(mesh: AMRMesh) -> np.ndarray:
    """(n, K) int32 face-neighbor table, K = ``neighbor_slots_per_cell``.

    Entries index into the mesh's cell order; -1 marks an empty slot
    (domain boundary, or unused sub-slots when the neighbor is not
    finer). Face f = (axis a, direction s) owns sub-slots
    ``f * 2^(d-1) ... (f+1) * 2^(d-1) - 1``: slot 0 carries a same-level
    or coarser neighbor; a finer neighbor fills all 2^(d-1) sub-slots
    with the face-adjacent children. Under 2:1 balance these cases are
    exclusive. The table is symmetric as an edge set — j appears in i's
    row iff i appears in j's (asserted by tests, relied on by the halo
    plan's send/recv symmetry).
    """
    n, d = mesh.n, mesh.d
    sub = 1 << (d - 1)
    K = neighbor_slots_per_cell(d)
    nbr = np.full((n, K), -1, np.int64)
    look = _CellLookup(mesh.level, mesh.ij)
    lvl = mesh.level.astype(np.int64)
    # offsets of the d-1 non-face dims for finer-neighbor children
    sub_offs = _child_offsets(d - 1) if d > 1 else np.zeros((1, 0), np.int64)
    for a in range(d):
        for si, s in enumerate((-1, +1)):
            f = 2 * a + si
            ij2 = mesh.ij.copy()
            ij2[:, a] += s
            in_dom = (ij2[:, a] >= 0) & (ij2[:, a] < (1 << lvl))
            # same level; then, where that cell is absent, one coarser;
            # then one finer. Each lookup runs only on the rows still open.
            same = np.full((n,), -1, np.int64)
            rows = np.flatnonzero(in_dom)
            same[rows] = look.find(mesh.level[rows], ij2[rows])
            coarse = np.full((n,), -1, np.int64)
            rows = np.flatnonzero(in_dom & (same < 0) & (lvl > 0))
            coarse[rows] = look.find(mesh.level[rows] - 1, ij2[rows] >> 1)
            nbr[:, f * sub] = np.where(same >= 0, same, coarse)
            # one finer: the 2^(d-1) children of ij2 adjacent to the face.
            # Child a-coord: low side (2*ij2[a]) when we look in +a, high
            # side (2*ij2[a] + 1) when we look in -a.
            rows = np.flatnonzero(
                in_dom & (same < 0) & (coarse < 0) & (lvl < mesh.max_level))
            if not rows.size:
                continue
            other = [x for x in range(d) if x != a]
            base = ij2[rows] * 2
            for t in range(sub):
                child = base.copy()
                child[:, a] = base[:, a] + (1 if s < 0 else 0)
                for oi, ax in enumerate(other):
                    child[:, ax] = base[:, ax] + sub_offs[t, oi]
                nbr[rows, f * sub + t] = look.find(mesh.level[rows] + 1, child)
    return nbr.astype(np.int32)


def neighbor_edges(nbr: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Directed (src, dst) edge list of the face-adjacency graph — the
    input `repro.core.metrics.edge_metrics` expects."""
    n, K = nbr.shape
    src = np.repeat(np.arange(n, dtype=np.int64), K)
    dst = nbr.reshape(-1).astype(np.int64)
    keep = dst >= 0
    return src[keep], dst[keep]


def stencil_coeffs(mesh: AMRMesh, nbr: np.ndarray, dt: float) -> np.ndarray:
    """(n, K) float32 explicit finite-volume heat-flux coefficients.

    For face (i, j): flux = area / dist with ``area = min(h_i, h_j)^(d-1)``
    and ``dist = (h_i + h_j) / 2``; the update divides by the cell volume,
    so ``du_i = dt / h_i^d * sum_j area_ij / dist_ij * (u_j - u_i)``.
    Empty slots carry coefficient 0. Computed once per mesh on the host in
    float32 — the distributed and reference stencils consume the SAME
    array, a precondition of their bit-equality.
    """
    h = mesh.sizes().astype(np.float64)
    d = mesh.d
    nb = np.maximum(nbr, 0)
    h_j = h[nb]
    area = np.minimum(h[:, None], h_j) ** (d - 1)
    dist = 0.5 * (h[:, None] + h_j)
    c = dt * area / (dist * (h[:, None] ** d))
    return np.where(nbr >= 0, c, 0.0).astype(np.float32)


def stable_dt(mesh_or_hmin, safety: float = 0.25) -> float:
    """Explicit-stability timestep for the finest cells of the run."""
    h = mesh_or_hmin if np.isscalar(mesh_or_hmin) else float(mesh_or_hmin.sizes().min())
    d = 2 if np.isscalar(mesh_or_hmin) else mesh_or_hmin.d
    return safety * h * h / (2.0 * d)


# ---------------------------------------------------------------------------
# refine / coarsen with 2:1 balance + deterministic transfer maps
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Transfer:
    """State transfer map of one refine/coarsen step.

    ``src[k]`` lists the old-cell indices feeding new cell ``k`` (-1
    pad); ``cnt[k]`` how many. Kept and refined-child cells copy one
    source; a coarsened parent averages its 2^d children (fixed child
    order). ``born`` marks new cells that did not exist before;
    ``died_idx`` are the OLD-order indices of removed cells (refined
    parents, coarsened children). The driver keeps its slot array
    current from these: kept cells inherit ``slots[src[k, 0]]``, died
    indices map to engine deletes, born cells to engine inserts.
    """

    src: np.ndarray       # (n_new, 2^d) int64
    cnt: np.ndarray       # (n_new,) int32
    born: np.ndarray      # (n_new,) bool
    died_idx: np.ndarray  # (k,) int64 old-cell indices of removed cells


def apply_transfer(u_old: np.ndarray, tr: Transfer) -> np.ndarray:
    """Move a cell field across a refine/coarsen step (see `Transfer`).

    The ONE transfer implementation: both the distributed simulation and
    the single-device reference call this (host-side, float32), so their
    fields stay bitwise comparable across mesh changes. ``u_old`` is one
    field (n,) or V fields (n, V); each column of the (n, V) result
    equals the transfer of that column alone, bit for bit.
    """
    u = np.asarray(u_old, np.float32)
    if u.ndim == 1:
        vals = np.where(tr.src >= 0, u[np.maximum(tr.src, 0)], np.float32(0.0))
        return (vals.sum(axis=1) / tr.cnt.astype(np.float32)).astype(np.float32)
    # a one-source row sums its value and exact zeros, divided by 1:
    # value + 0.0 (which turns -0.0 into +0.0, as the sum does)
    out = u[tr.src[:, 0]] + np.float32(0.0)
    many = np.flatnonzero(tr.cnt > 1)
    if many.size:
        sub = Transfer(tr.src[many], tr.cnt[many], tr.born[many], tr.died_idx)
        out[many] = np.stack([apply_transfer(u[:, v], sub) for v in range(u.shape[1])], 1)
    return out


def apply_transfers(u_old: np.ndarray, trs) -> np.ndarray:
    """:func:`apply_transfer` through a sequence of steps, in order."""
    for tr in trs:
        u_old = apply_transfer(u_old, tr)
    return u_old


def lineage(trs, n_old: int) -> tuple[np.ndarray, np.ndarray]:
    """Cell identity across a sequence of transfers: ``(src0, died_idx)``
    with ``src0[k]`` the old index cell ``k`` was kept from (-1 for a
    cell born anywhere in the sequence) and ``died_idx`` the old cells
    no new cell was kept from. One transfer gives its own bookkeeping."""
    src0 = np.arange(n_old, dtype=np.int64)
    for tr in trs:
        src0 = np.where(tr.born, -1, src0[np.maximum(tr.src[:, 0], 0)])
    alive = np.zeros((n_old,), bool)
    alive[src0[src0 >= 0]] = True
    return src0, np.flatnonzero(~alive)


def ancestors(trs, n_old: int) -> np.ndarray:
    """(n_new,) an old cell that overlaps each new cell: the one it was
    kept from, its refined ancestor, or the first child it merged."""
    anc = np.arange(n_old, dtype=np.int64)
    for tr in trs:
        anc = anc[tr.src[:, 0]]
    return anc


def refine_coarsen(
    mesh: AMRMesh,
    refine_mask: np.ndarray,
    coarsen_mask: np.ndarray,
) -> tuple[AMRMesh, Transfer]:
    """One adaptation step: split masked cells, merge fully-masked
    sibling groups, keep the 2:1 balance.

    Refinement wins over coarsening; the refinement set is closed under
    the 2:1 ripple (a neighbor of a would-be level-(l+2) cell refines
    too); a sibling group only coarsens when every sibling agrees, none
    refines, and no face neighbor would end up two levels finer than the
    merged parent. New-cell order is deterministic: kept cells first (in
    old order), then children (refined-parent order x fixed child
    order), then merged parents (group order).
    """
    refine, cand = _graded_masks(mesh, refine_mask, coarsen_mask)
    return _apply_masks(mesh, refine, cand)


def _graded_masks(mesh: AMRMesh, refine_mask, coarsen_mask):
    """(refine, coarsen candidates) closed under the 2:1 rules of
    :func:`refine_coarsen`; a candidate merges only with all 2^d
    siblings (resolved by :func:`_apply_masks`)."""
    refine = np.asarray(refine_mask, bool) & (mesh.level < mesh.max_level)
    coarsen = np.asarray(coarsen_mask, bool) & (mesh.level > mesh.base_level)
    nbr = face_neighbors(mesh)

    # --- 2:1 refinement ripple (post-refinement levels) -------------------
    for _ in range(mesh.max_level - mesh.base_level + 1):
        post = mesh.level.astype(np.int64) + refine
        nb_post = np.where(nbr >= 0, post[np.maximum(nbr, 0)], -(10**6))
        viol = (nb_post.max(axis=1) - post) >= 2
        grow = viol & ~refine & (mesh.level < mesh.max_level)
        if not grow.any():
            break
        refine = refine | grow

    # --- coarsenable sibling groups ---------------------------------------
    coarsen = coarsen & ~refine
    post = mesh.level.astype(np.int64) + refine
    # a child may only coarsen if no face neighbor ends deeper than
    # level + 1 == parent_level + 2 - 1 (merged parent keeps 2:1)
    nb_post = np.where(nbr >= 0, post[np.maximum(nbr, 0)], -(10**6))
    safe = nb_post.max(axis=1) <= mesh.level.astype(np.int64)
    return refine, coarsen & safe


def _sibling_groups(mesh: AMRMesh, cand: np.ndarray) -> np.ndarray:
    """(g, 2^d) complete sibling groups among the candidate cells: groups
    in ascending parent key, children in their own key order (= fixed
    child order, as pack sorts ij lexicographically)."""
    k2 = 1 << mesh.d
    cand_idx = np.nonzero(cand)[0]
    if not cand_idx.size:
        return np.zeros((0, k2), np.int64)
    pk = _pack(mesh.level[cand_idx] - 1, mesh.ij[cand_idx] >> 1)
    ck = _pack(mesh.level[cand_idx], mesh.ij[cand_idx])
    order = np.lexsort((ck, pk))
    pk_s, idx_s = pk[order], cand_idx[order]
    _, starts, counts = np.unique(pk_s, return_index=True, return_counts=True)
    starts_f = starts[counts == k2]
    return idx_s[starts_f[:, None] + np.arange(k2)[None, :]]


def _apply_masks(mesh: AMRMesh, refine: np.ndarray, cand: np.ndarray):
    """Build the adapted mesh and its transfer from final masks: every
    ``refine`` cell splits, every complete sibling group of ``cand``
    cells merges (the masks must already satisfy the 2:1 rules)."""
    n, d = mesh.n, mesh.d
    k2 = 1 << d
    group_children = _sibling_groups(mesh, cand)
    removed = np.zeros(n, bool)
    if group_children.shape[0]:
        removed[group_children.reshape(-1)] = True

    keep = ~refine & ~removed
    keep_idx = np.nonzero(keep)[0]
    ref_idx = np.nonzero(refine)[0]

    offs = _child_offsets(d)
    # children: (n_ref * 2^d)
    ch_level = np.repeat(mesh.level[ref_idx] + 1, k2)
    ch_ij = (mesh.ij[ref_idx][:, None, :] * 2 + offs[None, :, :]).reshape(-1, d)
    ch_src = np.repeat(ref_idx, k2)
    # merged parents
    g = group_children.shape[0]
    pa_level = (mesh.level[group_children[:, 0]] - 1) if g else np.zeros(0, np.int32)
    pa_ij = (mesh.ij[group_children[:, 0]] >> 1) if g else np.zeros((0, d), np.int64)

    new_level = np.concatenate(
        [mesh.level[keep_idx], ch_level.astype(np.int32), pa_level.astype(np.int32)]
    )
    new_ij = np.concatenate([mesh.ij[keep_idx], ch_ij, pa_ij])
    n_new = new_level.shape[0]

    src = np.full((n_new, k2), -1, np.int64)
    cnt = np.ones((n_new,), np.int32)
    src[: keep_idx.size, 0] = keep_idx
    src[keep_idx.size : keep_idx.size + ch_src.size, 0] = ch_src
    if g:
        src[keep_idx.size + ch_src.size :, :] = group_children
        cnt[keep_idx.size + ch_src.size :] = k2
    born = np.zeros((n_new,), bool)
    born[keep_idx.size :] = True
    died_idx = np.nonzero(~keep)[0]

    out = AMRMesh(
        level=new_level,
        ij=new_ij,
        base_level=mesh.base_level,
        max_level=mesh.max_level,
    )
    return out, Transfer(src=src, cnt=cnt, born=born, died_idx=died_idx)


# ---------------------------------------------------------------------------
# the moving load feature (drives both refinement and weight drift)
# ---------------------------------------------------------------------------

def feature_center(t: float, d: int, *, x0: float = 0.2, x1: float = 0.8) -> np.ndarray:
    """Feature path: a straight walk along dim 0 from x0 to x1 (other
    dims pinned at 0.5). ``t`` in [0, 1]; restrict [x0, x1] to one
    node's span to exercise the node-local regime."""
    c = np.full((d,), 0.5, np.float64)
    c[0] = x0 + (x1 - x0) * float(t)
    return c


def feature_weights(
    centers: np.ndarray, c: np.ndarray, *, amp: float = 4.0, sigma: float = 0.12
) -> np.ndarray:
    """(n,) float32 cell costs: 1 + amp * gaussian(feature) — hot cells
    near the feature cost more per stencil update (finer physics /
    subcycling), which is the weight drift the Alg. 3 trigger meters."""
    d2 = np.sum((np.asarray(centers, np.float64) - c[None, :]) ** 2, axis=1)
    return (1.0 + amp * np.exp(-d2 / (sigma * sigma))).astype(np.float32)


def adapt_masks(
    mesh: AMRMesh,
    c: np.ndarray,
    *,
    r_refine: float = 0.15,
    r_coarsen: float = 0.30,
) -> tuple[np.ndarray, np.ndarray]:
    """Refine inside ``r_refine`` of the feature, coarsen beyond
    ``r_coarsen`` — the classic tracking-AMR policy."""
    dist = np.sqrt(
        np.sum((mesh.centers().astype(np.float64) - c[None, :]) ** 2, axis=1)
    )
    return dist < r_refine, dist > r_coarsen


# ---------------------------------------------------------------------------
# miniAMR (Mantevo's proxy for block-structured AMR codes) on the octree
# ---------------------------------------------------------------------------
#
# miniAMR holds the mesh as blocks of 2^b cells per side that refine or
# coarsen whole: a refined block becomes 2^d blocks one level finer, which
# is one refine of every cell of the block. So its mesh is this module's
# cell mesh with masks that are constant per block, and a block is the
# set of cells of one level sharing ``ij >> b``.

@dataclass(frozen=True)
class Spheroid:
    """miniAMR ``--object 2 ...``: the surface of an axis-aligned
    spheroid. At timestep ``t`` its centre is ``center + t * move`` and
    its semi-axes ``size + t * inc``."""

    center: tuple
    move: tuple
    size: tuple
    inc: tuple = (0.0, 0.0, 0.0)

    def at(self, t: float) -> tuple[np.ndarray, np.ndarray]:
        c = np.asarray(self.center, np.float64) + t * np.asarray(self.move, np.float64)
        r = np.asarray(self.size, np.float64) + t * np.asarray(self.inc, np.float64)
        return c, r


def blocks_of(mesh: AMRMesh, block_bits: int) -> tuple[AMRMesh, np.ndarray]:
    """The block mesh of a block-structured cell mesh (blocks of
    ``2**block_bits`` cells per side; levels shifted down by
    ``block_bits``) and the block index of every cell."""
    level = mesh.level - block_bits
    ij = mesh.ij >> block_bits
    _, first, inv = np.unique(_pack(level, ij), return_index=True, return_inverse=True)
    blocks = AMRMesh(
        level=level[first], ij=ij[first],
        base_level=mesh.base_level - block_bits,
        max_level=mesh.max_level - block_bits,
    )
    return blocks, inv.reshape(-1)


def surface_hit(blocks: AMRMesh, objects, t: float) -> np.ndarray:
    """(nb,) bool: the block's closed box meets some object's surface,
    i.e. the box holds points on both sides of it (miniAMR's test for a
    surface object): min over the box of sum(((x - c) / r)^2) <= 1 <=
    its max, the min at the clamped centre and the max at the farthest
    corner."""
    h = (0.5 ** blocks.level.astype(np.float64))[:, None]
    lo = blocks.ij.astype(np.float64) * h
    hi = lo + h
    hit = np.zeros((blocks.n,), bool)
    for obj in objects:
        c, r = obj.at(t)
        near = np.clip(c[None, :], lo, hi)
        far = np.where(np.abs(lo - c) > np.abs(hi - c), lo, hi)
        dmin = np.sum(((near - c) / r) ** 2, axis=1)
        dmax = np.sum(((far - c) / r) ** 2, axis=1)
        hit |= (dmin <= 1.0) & (dmax >= 1.0)
    return hit


def miniamr_adapt(
    mesh: AMRMesh, objects, t: float, *, block_bits: int = 3, block_change: int = 1,
) -> tuple[AMRMesh, tuple]:
    """miniAMR's refine step: up to ``block_change`` rounds, each marking
    every block a surface crosses for refinement and every other block
    for coarsening, grading the blocks 2:1 across faces (the rules of
    :func:`refine_coarsen`, applied to blocks), and splitting or merging
    whole blocks. Returns the new mesh, its cells in packed-key order
    (so one geometry gives one cell order, whatever the history), and
    the per-round transfers (see :func:`apply_transfers`,
    :func:`lineage`); stops early when a round changes nothing."""
    transfers = []
    for _ in range(block_change):
        blocks, cell_block = blocks_of(mesh, block_bits)
        hit = surface_hit(blocks, objects, t)
        refine, cand = _graded_masks(blocks, hit, ~hit)
        merge = np.zeros((blocks.n,), bool)
        merge[_sibling_groups(blocks, cand).reshape(-1)] = True
        if not (refine.any() or merge.any()):
            break
        mesh, tr = _apply_masks(mesh, refine[cell_block], merge[cell_block])
        transfers.append(tr)
    if transfers:
        perm = np.argsort(_pack(mesh.level, mesh.ij))
        mesh = AMRMesh(level=mesh.level[perm], ij=mesh.ij[perm],
                       base_level=mesh.base_level, max_level=mesh.max_level)
        tr = transfers[-1]
        transfers[-1] = Transfer(src=tr.src[perm], cnt=tr.cnt[perm], born=tr.born[perm],
                                 died_idx=tr.died_idx)
    return mesh, tuple(transfers)


def same_cells(a: AMRMesh, b: AMRMesh) -> bool:
    """The two meshes hold the same cells in the same order."""
    return np.array_equal(a.level, b.level) and np.array_equal(a.ij, b.ij)


def miniamr_coeffs(mesh: AMRMesh, nbr: np.ndarray) -> np.ndarray:
    """(n, K) float32 coefficients of miniAMR's 7-point average in the
    fused form ``u + sum_k c_k (u_k - u)``: 1/7 for a same-level or
    coarser face neighbour, 1/28 for each of the 4 finer ones (their
    mean stands in for the face's ghost); 0 on an empty slot, so a
    boundary face adds nothing, as if its ghost held the cell's value."""
    nb = np.maximum(nbr, 0)
    finer = mesh.level[nb] > mesh.level[:, None]
    c = np.where(finer, 1.0 / (7 * (1 << (mesh.d - 1))), 1.0 / 7)
    return np.where(nbr >= 0, c, 0.0).astype(np.float32)
