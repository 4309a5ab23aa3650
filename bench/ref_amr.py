"""Plain reference for the miniAMR cell: its own block mesh, its own
neighbours and its own 7-point stencil, in float64.

Imports nothing of the program. Blocks are the keys ``(r, x, y, z)`` of
a dict (refinement level ``r`` of the block, block coordinates at that
level), each block ``b`` cells a side, as miniAMR holds them:

* mesh: miniAMR's refine step, ``block_change`` rounds of marking every
  block a spheroid surface crosses for refinement and every other block
  for coarsening, 2:1 grading across block faces (a block refines while
  a face neighbour would end two levels finer; a block coarsens only
  with all 8 siblings and while no face neighbour would end two levels
  finer than their parent), then splitting and merging whole blocks;
* transfer: a refined block's cells inject their value into their 8
  children, a merged parent's cells are the mean of their 8 children;
* stencil: per stage, every block gets a ghost layer (a same-level
  neighbour's face cells; a coarser neighbour's cells, each standing for
  the 2x2 it covers; the mean of each 2x2 of a finer neighbour's face
  cells; its own face cells on the domain boundary), then
  ``u + (sum of the 6 ghost-or-neighbour values - 6 u) / 7``;
* adjacency: the program's cells painted by index on the finest grid,
  each face read at its four quarter points.
"""
from __future__ import annotations

import numpy as np


# -- geometry --------------------------------------------------------------

class Geometry:
    """The deployment's geometry from its configuration."""

    def __init__(self, cfg: dict):
        self.root = int(cfg["npx"]) * int(cfg["init_x"])
        assert self.root == int(cfg["npy"]) * int(cfg["init_y"]) == int(cfg["npz"]) * int(cfg["init_z"])
        self.b = int(cfg["nx"])
        assert self.b == int(cfg["ny"]) == int(cfg["nz"])
        self.max_r = int(cfg["num_refine"])
        self.block_change = int(cfg["block_change"])
        self.objects = [dict(center=np.array(o["center"], float), move=np.array(o["move"], float),
                             size=np.array(o["size"], float), inc=np.array(o["inc"], float))
                        for o in cfg["objects"]]

    def side(self, r: int) -> int:
        return self.root << r

    def hit(self, key, t: float) -> bool:
        """The block's closed box meets a spheroid surface."""
        r, *c = key
        h = 1.0 / self.side(r)
        lo = np.array(c, float) * h
        hi = lo + h
        for o in self.objects:
            cen = o["center"] + t * o["move"]
            rad = o["size"] + t * o["inc"]
            near = np.minimum(np.maximum(cen, lo), hi)
            far = np.where(np.abs(lo - cen) > np.abs(hi - cen), lo, hi)
            if np.sum(((near - cen) / rad) ** 2) <= 1.0 <= np.sum(((far - cen) / rad) ** 2):
                return True
        return False

    def neighbours(self, blocks: dict, key) -> list:
        """Face neighbours of a block among the leaves of ``blocks``."""
        r, *c = key
        out = []
        for a in range(3):
            for s in (-1, 1):
                q = list(c)
                q[a] += s
                if not 0 <= q[a] < self.side(r):
                    continue
                same = (r, *q)
                if same in blocks:
                    out.append(same)
                    continue
                coarse = (r - 1, *[x >> 1 for x in q])
                if r > 0 and coarse in blocks:
                    out.append(coarse)
                    continue
                for o1 in (0, 1):
                    for o2 in (0, 1):
                        ch = [2 * x for x in q]
                        ch[a] += 1 if s < 0 else 0
                        others = [x for x in range(3) if x != a]
                        ch[others[0]] += o1
                        ch[others[1]] += o2
                        if (r + 1, *ch) in blocks:
                            out.append((r + 1, *ch))
        return out


def adapt(geo: Geometry, blocks: dict, t: float) -> dict:
    """miniAMR's refine step on a dict of blocks; values (block fields,
    (b, b, b, v) arrays, or None) follow the blocks."""
    b = geo.b
    for _ in range(geo.block_change):
        hit = {k: geo.hit(k, t) for k in blocks}
        nbrs = {k: geo.neighbours(blocks, k) for k in blocks}
        refine = {k for k in blocks if hit[k] and k[0] < geo.max_r}
        while True:
            post = lambda k: k[0] + (k in refine)
            grow = {k for k in blocks if k not in refine and k[0] < geo.max_r
                    and any(post(n) >= k[0] + 2 for n in nbrs[k])}
            if not grow:
                break
            refine |= grow
        post = lambda k: k[0] + (k in refine)
        cand = {k for k in blocks if not hit[k] and k[0] > 0 and k not in refine
                and all(post(n) <= k[0] for n in nbrs[k])}
        parents = {}
        for k in cand:
            parents.setdefault((k[0] - 1, *[x >> 1 for x in k[1:]]), []).append(k)
        merge = {p: ks for p, ks in parents.items() if len(ks) == 8}
        if not refine and not merge:
            break
        new = {k: v for k, v in blocks.items() if k not in refine
               and (k[0] - 1, *[x >> 1 for x in k[1:]]) not in merge}
        h = b // 2
        for k in refine:
            r, x, y, z = k
            v = blocks[k]
            for o in range(8):
                ox, oy, oz = o >> 2 & 1, o >> 1 & 1, o & 1
                cv = None
                if v is not None:
                    part = v[ox * h:(ox + 1) * h, oy * h:(oy + 1) * h, oz * h:(oz + 1) * h]
                    cv = part.repeat(2, 0).repeat(2, 1).repeat(2, 2)
                new[(r + 1, 2 * x + ox, 2 * y + oy, 2 * z + oz)] = cv
        for p, ks in merge.items():
            pv = None
            if blocks[ks[0]] is not None:
                pv = np.empty_like(blocks[ks[0]])
                for k in ks:
                    ox, oy, oz = (c & 1 for c in k[1:])
                    v = blocks[k]
                    m = v.reshape(h, 2, h, 2, h, 2, -1).mean(axis=(1, 3, 5))
                    pv[ox * h:(ox + 1) * h, oy * h:(oy + 1) * h, oz * h:(oz + 1) * h] = m
            new[p] = pv
        blocks = new
    return blocks


def pingpong_meshes(geo: Geometry, t0: float, t1: float) -> tuple[dict, dict]:
    """The leaf blocks at the two timesteps, refined from the root and
    back and forth until an adapt each way reproduces both."""
    blocks = {(0, x, y, z): None for x in range(geo.root) for y in range(geo.root)
              for z in range(geo.root)}
    a = adapt(geo, blocks, t0)
    for _ in range(geo.max_r + 2):
        b = adapt(geo, a, t1)
        a2 = adapt(geo, b, t0)
        if a2.keys() == a.keys() and adapt(geo, a2, t1).keys() == b.keys():
            return a, b
        a = a2
    raise RuntimeError("the reference's adapts do not settle")


# -- cells -----------------------------------------------------------------

def _key(level, x, y, z) -> np.ndarray:
    level, x, y, z = (np.asarray(v, np.int64) for v in (level, x, y, z))
    return ((level << 48) | (x << 32) | (y << 16) | z)


class BlockMesh:
    """A leaf-block set as arrays, with the maps between program cells
    ``(level, ij)`` (cell level = block level + log2(root * b)) and
    (block, local cell)."""

    def __init__(self, geo: Geometry, blocks: dict):
        self.geo = geo
        self.keys = sorted(blocks)
        k = np.array(self.keys, np.int64).reshape(-1, 4)
        self.r, self.c = k[:, 0], k[:, 1:]
        self.bkey = _key(self.r, *self.c.T)
        self.order = np.argsort(self.bkey)
        self.cell0 = int(np.log2(geo.root * geo.b))   # cell level of root blocks

    def cell_keys(self) -> np.ndarray:
        """Sorted keys of every cell of the mesh."""
        b = self.geo.b
        loc = np.stack(np.meshgrid(*[np.arange(b)] * 3, indexing="ij"), -1).reshape(-1, 3)
        ij = self.c[:, None, :] * b + loc[None]
        lvl = np.repeat(self.r + self.cell0, b ** 3)
        return np.sort(_key(lvl, *ij.reshape(-1, 3).T))

    def locate(self, level, ij):
        """(block index, local x, y, z) of program cells; block -1 where
        the reference has no such block."""
        b = self.geo.b
        sh = int(np.log2(b))
        bk = _key(np.asarray(level) - self.cell0, *(np.asarray(ij) >> sh).T)
        pos = np.searchsorted(self.bkey[self.order], bk)
        pos = np.minimum(pos, self.bkey.size - 1)
        blk = np.where(self.bkey[self.order][pos] == bk, self.order[pos], -1)
        return blk, *(np.asarray(ij) & (b - 1)).T

    def to_blocks(self, level, ij, vals) -> np.ndarray:
        """Cell values (n, v) in program order -> (nb, b, b, b, v)."""
        blk, lx, ly, lz = self.locate(level, ij)
        b = self.geo.b
        out = np.zeros((len(self.keys), b, b, b, vals.shape[1]), np.float64)
        out[blk, lx, ly, lz] = vals
        return out

    def from_blocks(self, level, ij, u) -> np.ndarray:
        blk, lx, ly, lz = self.locate(level, ij)
        return u[blk, lx, ly, lz]


def mesh_mismatch(ref: BlockMesh, level, ij) -> int:
    """Cells in one leaf set and not in the other."""
    mine = ref.cell_keys()
    theirs = np.sort(_key(level, *np.asarray(ij).T))
    return int(np.setxor1d(mine, theirs, assume_unique=True).size
               + (theirs.size - np.unique(theirs).size))


# -- stencil ---------------------------------------------------------------

class Stencil:
    """miniAMR's 7-point average over a leaf-block set, float64."""

    def __init__(self, ref: BlockMesh, blocks: dict):
        geo, b = ref.geo, ref.geo.b
        idx = {k: i for i, k in enumerate(ref.keys)}
        h = b // 2
        # per face (a, s): lists of (block, neighbour...) by relation
        self.faces = []
        for a in range(3):
            for s in (-1, 1):
                same, coarse, fine, bound = [], [], [], []
                for i, key in enumerate(ref.keys):
                    r, *c = key
                    q = list(c)
                    q[a] += s
                    if not 0 <= q[a] < geo.side(r):
                        bound.append(i)
                        continue
                    if (r, *q) in idx:
                        same.append((i, idx[(r, *q)]))
                        continue
                    ck = (r - 1, *[x >> 1 for x in q])
                    if r > 0 and ck in idx:
                        others = [x for x in range(3) if x != a]
                        coarse.append((i, idx[ck], (c[others[0]] & 1) * h, (c[others[1]] & 1) * h))
                        continue
                    kids = []
                    others = [x for x in range(3) if x != a]
                    for o1 in (0, 1):
                        for o2 in (0, 1):
                            ch = [2 * x for x in q]
                            ch[a] += 1 if s < 0 else 0
                            ch[others[0]] += o1
                            ch[others[1]] += o2
                            kids.append(idx[(r + 1, *ch)])
                    fine.append((i, *kids))
                self.faces.append((a, s, np.array(same, np.int64).reshape(-1, 2),
                                   np.array(coarse, np.int64).reshape(-1, 4),
                                   np.array(fine, np.int64).reshape(-1, 5),
                                   np.array(bound, np.int64)))
        self.b = b

    def stage(self, u: np.ndarray) -> np.ndarray:
        """One stage on (nb, b, b, b, v) float64 fields."""
        b, h = self.b, self.b // 2
        nb, v = u.shape[0], u.shape[-1]
        rep = np.arange(b) // 2
        # sum of the 6 face neighbours' values: inside the block by shifts,
        # on its faces from the ghost layers
        s = np.zeros_like(u)
        for a in range(3):
            lo = [slice(None)] * 4
            hi = [slice(None)] * 4
            lo[a], hi[a] = slice(0, -1), slice(1, None)
            s[(slice(None), *hi)] += u[(slice(None), *lo)]
            s[(slice(None), *lo)] += u[(slice(None), *hi)]
        for a, side, same, coarse, fine, bound in self.faces:
            near, far = (0, b - 1) if side < 0 else (b - 1, 0)   # own / neighbour layer
            g = np.empty((nb, b, b, v))
            far_l = np.take(u, far, axis=1 + a)                         # (nb, b, b, v)
            g[same[:, 0]] = far_l[same[:, 1]]
            if coarse.size:
                lay = far_l[coarse[:, 1]]
                r1 = coarse[:, 2][:, None, None] + rep[None, :, None]
                r2 = coarse[:, 3][:, None, None] + rep[None, None, :]
                g[coarse[:, 0]] = lay[np.arange(len(coarse))[:, None, None], r1, r2]
            for q in range(4):
                if not fine.size:
                    break
                o1, o2 = q >> 1, q & 1
                m = far_l[fine[:, 1 + q]].reshape(-1, h, 2, h, 2, v).mean(axis=(2, 4))
                g[fine[:, 0], o1 * h:(o1 + 1) * h, o2 * h:(o2 + 1) * h] = m
            g[bound] = np.take(u[bound], near, axis=1 + a)
            sl = [slice(None)] * 4
            sl[a] = near
            s[(slice(None), *sl)] += g
        s -= 6.0 * u
        s /= 7.0
        s += u
        return s


# -- adjacency -------------------------------------------------------------

def nbr_mismatch(geo: Geometry, level, ij, nbr) -> int:
    """(cell, face) pairs whose neighbours in the program's table (``nbr``
    (n, 24): face f = 2 * axis + (side > 0) owns slots 4f .. 4f + 3)
    differ from the cells the finest grid shows across the face."""
    level = np.asarray(level, np.int64)
    ij = np.asarray(ij, np.int64)
    cell0 = int(np.log2(geo.root * geo.b))
    lmax = cell0 + geo.max_r
    side = 1 << lmax
    grid = np.full((side,) * 3, -1, np.int64)
    for lv in np.unique(level):
        sel = np.flatnonzero(level == lv)
        n_l, sc = 1 << int(lv), 1 << (lmax - int(lv))
        g6 = grid.reshape(n_l, sc, n_l, sc, n_l, sc)
        x, y, z = ij[sel].T
        g6[x, :, y, :, z, :] = sel[:, None, None, None]
    if (grid < 0).any():
        return -1   # the cells do not tile the domain
    sc = (1 << (lmax - level))[:, None]
    bad = 0
    for a in range(3):
        others = [x for x in range(3) if x != a]
        for si, s in enumerate((-1, 1)):
            f = 2 * a + si
            across = ij[:, a] * sc[:, 0] - 1 if s < 0 else (ij[:, a] + 1) * sc[:, 0]
            inside = (across >= 0) & (across < side)
            seen = np.full((level.size, 4), -1, np.int64)
            for q, (q1, q2) in enumerate(((1, 1), (1, 3), (3, 1), (3, 3))):
                pt = np.empty((level.size, 3), np.int64)
                pt[:, a] = np.clip(across, 0, side - 1)
                pt[:, others[0]] = (4 * ij[:, others[0]] + q1) * sc[:, 0] // 4
                pt[:, others[1]] = (4 * ij[:, others[1]] + q2) * sc[:, 0] // 4
                seen[:, q] = np.where(inside, grid[pt[:, 0], pt[:, 1], pt[:, 2]], -1)
            seen.sort(axis=1)
            dup = np.zeros_like(seen, bool)
            dup[:, 1:] = seen[:, 1:] == seen[:, :-1]
            seen[dup] = -1
            seen.sort(axis=1)
            prog = np.sort(np.asarray(nbr[:, 4 * f:4 * f + 4], np.int64), axis=1)
            bad += int(np.sum(np.any(seen != prog, axis=1)))
    return bad
