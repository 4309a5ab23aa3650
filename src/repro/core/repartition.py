"""Incremental repartitioning engine (paper §IV, wired end-to-end).

The paper's headline economics: a *repeated* repartition of a drifting
load distribution must cost far less than the initial one. The static
pipeline (``partitioner.partition``) pays key generation + sort + slice
every call. This module keeps the expensive artifacts alive across
timesteps and only recomputes what a delta invalidates:

===========================  =========================================
change                       work done
===========================  =========================================
weights only                 re-slice the cached curve (no key-gen,
                             no sort, no tree work)
insert / delete points       key-gen for the delta batch only, re-sort
                             cached keys, re-slice; kd-tree updated by
                             one compiled program per call (tree mode:
                             with the bucket-summary delta)
credit exhaustion            full rebuild: ``dynamic.adjustments``
                             (Alg. 1), fresh quantization frame, fresh
                             keys (Alg. 3 decides *when*)
===========================  =========================================

Keys are generated against a **frozen quantization frame** (the bounding
box captured at the last rebuild, with margin). This is what makes
cached keys reusable at all — the static path re-fits the box every
call, so old keys would silently shift. Points drifting outside the
frame are clipped into the boundary cells until the next rebuild
refreshes the frame.

Every step emits a ``migration.MigrationPlan`` so the application can
move payloads with the bounded-message exchange. Storage-slot ids are
the stable element identity across steps.

The engine's phases are host spans named ``repartition.<phase>``
(``jax.profiler.TraceAnnotation``), so they appear in any profiler trace
on the device's clock: ``step``, ``timeop``, ``slice``, ``plan``,
``rebuild``, ``update_weights``, ``insert``, ``delete``, and ``sync``
around every blocking device->host read, each of which also counts in
``RepartitionStats.host_syncs`` / ``host_pull_bytes``.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from dataclasses import dataclass, field
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import curve_index as _ci
from repro.core import dynamic as _dyn
from repro.core import kdtree as _kdtree
from repro.core import knapsack as _knapsack
from repro.core import migration as _migration
from repro.core import partitioner as _pt
from repro.core import sfc as _sfc

KEY_SENTINEL = _ci.KEY_SENTINEL  # inactive-slot key: sorts to the tail

# Process-global token source for the kernels.ops key cache. Tokens must
# be unique across engine *instances*, not just monotonic within one: the
# cache is keyed (token, curve, bits, shape, ...), so two engines with
# same-shaped point stores and private counters both starting at 0 would
# silently read each other's (stale) keys.
_TOKEN_SOURCE = itertools.count(1)
_INT32_MAX = 2**31 - 1


def _pull_to_host(x, stats: "RepartitionStats | None" = None):
    """The one blocking device->host read of this module: waits for ``x``
    (an array, or a tuple of arrays read together) inside a
    ``repartition.sync`` span and counts it once in ``stats``."""
    with TraceAnnotation("repartition.sync"):
        out = jax.device_get(x)
    if stats is not None:
        stats.host_syncs += 1
        stats.host_pull_bytes += sum(a.nbytes for a in jax.tree.leaves(out))
    return out


@functools.partial(jax.jit, static_argnames=("num_parts",))
def _slice_kernel(order, active, weights, num_parts):
    """Fused incremental re-slice: gather weights into curve order,
    knapsack-slice, scatter part ids back to slots. One dispatch per
    step — this IS the incremental path's entire device work."""
    act_sorted = active[order]
    w_sorted = jnp.where(act_sorted, weights[order], 0.0)
    part_sorted = _knapsack.slice_weighted_curve(w_sorted, num_parts)
    part_sorted = jnp.where(act_sorted, part_sorted, -1)
    part = jnp.full(order.shape, -1, jnp.int32).at[order].set(part_sorted)
    loads = _knapsack.part_loads(w_sorted, jnp.maximum(part_sorted, 0), num_parts)
    return part, loads


@functools.partial(jax.jit, static_argnames=("num_parts",))
def _bucket_slice_kernel(leaf_id, active, weights, order, num_parts):
    """Tree-mode incremental re-slice: aggregate live weights onto the
    buckets (one segment_sum), knapsack the O(B) bucket weights in the
    cached curve order, gather part ids back through leaf_id. No
    per-point sort exists anywhere in this path — inserts and deletes
    never trigger a resort, unlike the cached-key path."""
    M = order.shape[0]
    w_leaf = jax.ops.segment_sum(
        jnp.where(active, weights, 0.0), leaf_id, num_segments=M
    )
    w_rank = w_leaf[order]
    part_rank = _knapsack.slice_weighted_curve(w_rank, num_parts)
    part_by_node = jnp.zeros((M,), jnp.int32).at[order].set(part_rank)
    part = jnp.where(active, part_by_node[leaf_id], -1)
    loads = _knapsack.part_loads(w_rank, part_rank, num_parts)
    return part, loads


@functools.partial(jax.jit, static_argnames=("num_nodes", "devices_per_node"))
def _hier_bucket_slice_kernel(
    leaf_id, active, weights, order, num_nodes, devices_per_node
):
    """Two-level tree-mode re-slice: one segment_sum onto the buckets,
    nested node->device knapsack over the O(B) bucket weights in cached
    curve order, gathers back through leaf_id. The full (inter-node)
    level: node slices move too."""
    M = order.shape[0]
    w_leaf = jax.ops.segment_sum(
        jnp.where(active, weights, 0.0), leaf_id, num_segments=M
    )
    w_rank = w_leaf[order]
    node_rank, _, part_rank = _knapsack.two_level_slice(
        w_rank, num_nodes, devices_per_node
    )
    part_by_node = jnp.zeros((M,), jnp.int32).at[order].set(part_rank)
    node_by_node = jnp.zeros((M,), jnp.int32).at[order].set(node_rank)
    part = jnp.where(active, part_by_node[leaf_id], -1)
    loads = _knapsack.part_loads(w_rank, part_rank, num_nodes * devices_per_node)
    node_loads = _knapsack.part_loads(w_rank, node_rank, num_nodes)
    return part, loads, node_loads, node_by_node


@functools.partial(jax.jit, static_argnames=("num_nodes", "devices_per_node"))
def _hier_intra_slice_kernel(
    leaf_id, active, weights, order, bucket_node, num_nodes, devices_per_node
):
    """Intra-node-only re-slice: the bucket->node assignment is FROZEN
    (``bucket_node``), only each node's device slices are re-knapsacked —
    every migration this step produces is node-local by construction."""
    M = order.shape[0]
    w_leaf = jax.ops.segment_sum(
        jnp.where(active, weights, 0.0), leaf_id, num_segments=M
    )
    w_rank = w_leaf[order]
    node_rank = bucket_node[order]
    dev_rank = _knapsack.device_slice_within_nodes(
        w_rank, node_rank, num_nodes, devices_per_node
    )
    part_rank = node_rank * devices_per_node + dev_rank
    part_by_node = jnp.zeros((M,), jnp.int32).at[order].set(part_rank)
    part = jnp.where(active, part_by_node[leaf_id], -1)
    loads = _knapsack.part_loads(w_rank, part_rank, num_nodes * devices_per_node)
    node_loads = _knapsack.part_loads(w_rank, node_rank, num_nodes)
    return part, loads, node_loads


@functools.partial(jax.jit, static_argnames=("num_parts",))
def _live_loads_kernel(part, dps, num_parts):
    """The imbalance fallback's input on the device: the (P,) float32 load
    of assignment ``part`` under the store's live weights, slot by slot
    (slots with part < 0 left out, inactive slots charged 0), and the
    non-empty bucket count. A compare-and-reduce over the P parts: no
    scatter, no (C, P) intermediate (XLA fuses it into the reduction)."""
    w = jnp.where(dps.active, dps.weights, 0.0)
    hit = part[:, None] == jnp.arange(num_parts, dtype=part.dtype)
    loads = jnp.sum(jnp.where(hit, w[:, None], 0.0), axis=0)
    return loads, _dyn.num_buckets(dps)


def _summary_delta(s, is_leaf, pts, wts, leaf_ids, counts, sign: int):
    """The bucket summaries after a delta of rows (``leaf_ids`` of
    ``num_nodes`` are dropped): count/weight/centroid exact, bboxes grown
    on insert only (re-tightened at the next rebuild; a loose bbox never
    mis-keys a bucket, keys come from centroids)."""
    ones = counts * sign
    cnt = s.count.at[leaf_ids].add(ones, mode="drop")
    wsum = s.weight.at[leaf_ids].add(jnp.float32(sign) * wts, mode="drop")
    csum = s.centroid * s.count[:, None].astype(jnp.float32)
    csum = csum.at[leaf_ids].add(
        jnp.float32(sign) * pts * jnp.abs(ones)[:, None].astype(jnp.float32), mode="drop"
    )
    centroid = csum / jnp.maximum(cnt[:, None].astype(jnp.float32), 1.0)
    lo, hi = s.bbox_lo, s.bbox_hi
    if sign > 0:
        lo = lo.at[leaf_ids].min(pts, mode="drop")
        hi = hi.at[leaf_ids].max(pts, mode="drop")
    return _kdtree.BucketSummary(
        count=cnt, weight=wsum, centroid=centroid, bbox_lo=lo, bbox_hi=hi,
        is_bucket=is_leaf & (cnt > 0),
    )


@jax.jit
def _delete_program(dps, summary, slot_ids, n, unread):
    """One delete: the first ``n`` of the padded ``slot_ids`` leave the
    store and the tree; in tree mode their buckets' summaries lose them
    and ``unread`` (the device sum of applied summary entries) gains the
    count removed. Reads nothing back."""
    valid = jnp.arange(slot_ids.shape[0]) < n
    out, removed, leaf, wts = _dyn._delete_rows(dps, slot_ids, valid)
    if summary is None:
        return out, None, None
    summary = _summary_delta(
        summary, out.tree.is_leaf, dps.points[slot_ids], wts, leaf,
        removed.astype(jnp.int32), sign=-1,
    )
    return out, summary, unread + jnp.sum(removed, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("walk",))
def _insert_program(dps, summary, pts, wts, n, walk):
    """One insert: the first ``n`` rows of the padded batch go to the
    lowest free slots, into the tree, and in tree mode into their
    buckets' summaries. Returns the new state and each row's slot.
    ``walk`` is ``dynamic.locate`` as it stands at the call, so a
    substitute of it compiles a program of its own."""
    valid = jnp.arange(pts.shape[0]) < n
    out, slot, leaf = _dyn._insert_rows(dps, pts, wts, valid, walk)
    if summary is not None:
        summary = _summary_delta(
            summary, out.tree.is_leaf, pts, wts, leaf,
            (leaf < out.tree.num_nodes).astype(jnp.int32), sign=+1,
        )
    return out, summary, slot


def _pad_rows(x, rows: int, fill, dtype):
    """``x`` as ``dtype`` with its leading axis padded to ``rows`` with
    ``fill``: on the host for a host array, else one device op."""
    if isinstance(x, jax.Array):
        x = x.astype(dtype)
        pad = jnp.pad
    else:
        x = np.asarray(x, dtype)
        pad = np.pad
    k = x.shape[0]
    if k == rows:
        return x
    return pad(x, [(0, rows - k)] + [(0, 0)] * (x.ndim - 1), constant_values=fill)


@functools.partial(jax.jit, static_argnames=("num_parts",))
def _send_counts_kernel(old_part, new_part, num_parts):
    """(P, P) migration count matrix, reduced on device (elements active
    in both assignments only)."""
    both = (old_part >= 0) & (new_part >= 0)
    idx = jnp.where(both, old_part * num_parts + new_part, num_parts * num_parts)
    counts = jax.ops.segment_sum(
        jnp.ones_like(idx), idx, num_segments=num_parts * num_parts + 1
    )
    return counts[:-1].reshape(num_parts, num_parts)


@dataclass(frozen=True)
class RepartitionStep:
    """One engine step: the new assignment plus how we got it."""

    kind: Literal["incremental", "rebuild"]
    part: jax.Array            # (C,) int32 part per storage slot, -1 inactive
    plan: _migration.MigrationPlan
    loads: np.ndarray          # (P,) weight per part
    imbalance: float           # max load / mean load
    reused_keys: bool          # True iff no key generation ran this step
    # hierarchical engines only (None on flat engines):
    level: Literal["intra", "inter"] | None = None  # which re-slice level ran
    node_loads: np.ndarray | None = None            # (N,) weight per node
    node_imbalance: float | None = None


@dataclass
class RepartitionStats:
    """Cumulative counters of one engine, for operators and tests.

    ``host_syncs`` / ``host_pull_bytes`` are the engine's device->host
    traffic: each blocking read of device arrays (``jax.device_get`` of
    an array or of a tuple read together) counts once, with the bytes it
    returned. Each such read is also a ``repartition.sync`` span in any
    ``jax.profiler`` trace, nested in the ``repartition.<phase>`` span
    that made it. A plain tree-mode ``step()`` makes 3 (the imbalance
    fallback's (P,) loads with the bucket count, the part loads, the
    (P, P) migration counts); ``delete`` none, ``insert`` one.

    Every ``insert``/``delete`` runs as one compiled program over its
    batch padded to ``dynamic.padded_rows``: ``delta_programs`` counts
    those calls, ``delta_pad_rows`` the padding rows they ran, and
    ``delta_sizes`` holds the padded sizes seen (each compiles the
    insert and/or delete program once).
    """

    rebuilds: int = 0
    incremental_steps: int = 0
    # storage slots run through key generation; rebuilds are
    # capacity-shaped (fixed-shape kernels), inserts count the delta batch
    keygen_points: int = 0
    # tree mode: buckets run through (O(B)) key generation at rebuilds
    # (``summary_refreshes`` below counts the delta-scatter refreshes)
    keygen_buckets: int = 0
    # hierarchical engines: how often each re-slice level fired (an
    # intra-node step never moves an element across nodes; an inter-node
    # step re-slices both levels)
    intra_reslices: int = 0
    inter_reslices: int = 0
    # elastic part-count changes (device loss / growth): re-slices of the
    # CACHED curve onto a new part count — never a rebuild
    resizes: int = 0
    host_syncs: int = 0
    host_pull_bytes: int = 0
    delta_programs: int = 0
    delta_pad_rows: int = 0
    delta_sizes: set = field(default_factory=set)
    history: list = field(default_factory=list)
    # summary refreshes: a host count, plus the deletes' applied entries
    # summed on the device (int32, at most ``_unread_bound``) until read
    _refreshes: int = field(default=0, repr=False)
    _unread: jax.Array | None = field(default=None, repr=False)
    _unread_bound: int = field(default=0, repr=False)

    @property
    def summary_refreshes(self) -> int:
        """Tree mode: summary entries refreshed by insert/delete delta
        scatters (applied entries only, masked no-ops excluded). Reading
        it reads the device sum once."""
        self._fold_refreshes()
        return self._refreshes

    def _unread_sum(self, k: int):
        """The device sum a ``k``-entry delete adds its applied count to
        (in its own program), folded into the host count first if the
        addition could pass int32."""
        if self._unread_bound + k > _INT32_MAX:
            self._fold_refreshes()
        self._unread_bound += k
        return np.zeros((), np.int32) if self._unread is None else self._unread

    def _count_delta(self, k: int, rows: int) -> None:
        self.delta_programs += 1
        self.delta_pad_rows += rows - k
        self.delta_sizes.add(rows)

    def _fold_refreshes(self) -> None:
        if self._unread is not None:
            self._refreshes += int(_pull_to_host(self._unread, self))
            self._unread, self._unread_bound = None, 0


class Repartitioner:
    """Stateful incremental repartitioner over a dynamic point set.

    >>> rp = Repartitioner(points, weights, num_parts=16)
    >>> rp.update_weights(new_weights)      # drift the load
    >>> step = rp.step()                    # incremental or full rebuild
    >>> step.plan.total_moved, step.kind

    The amortized controller (paper Alg. 3) decides incremental-vs-rebuild
    inside ``step``; ``rebalance()`` / ``rebuild()`` force one or the
    other. ``insert``/``delete`` apply geometry deltas through the cached
    linearized kd-tree (``dynamic.locate``), so point location for the
    delta batch is a root→leaf walk, not a build.

    Two substrates, selected by ``cfg.use_tree``:

    * **cached-key mode** (default) — per-point SFC keys against the
      frozen frame; inserts/deletes re-sort the cached n-length key
      array, weight drift re-slices the cached order.
    * **tree mode** — the kd-tree's leaf buckets are the statistics
      substrate: rebuilds key the O(B) bucket centroids only (never the
      points), inserts/deletes update the dirtied bucket summaries by
      delta scatters (``dynamic.locate`` + Alg. 1 adjustments at
      rebuild), and every re-slice is a knapsack over bucket weights —
      **no per-point key array exists and no per-point sort ever runs**.
      Balance granularity is one bucket instead of one element.
    """

    def __init__(
        self,
        points: jax.Array,
        weights: jax.Array | None = None,
        num_parts: int = 8,
        cfg: _pt.PartitionerConfig = _pt.PartitionerConfig(),
        *,
        capacity: int | None = None,
        max_depth: int = 12,
        bucket_size: int = 32,
        controller: _dyn.AmortizedController | None = None,
        rebuild_cost: float | None = None,
        frame_margin: float = 0.25,
    ):
        n, d = points.shape
        if weights is None:
            weights = jnp.ones((n,), dtype=jnp.float32)
        self.num_parts = int(num_parts)
        self.cfg = cfg
        self.tree_mode = bool(cfg.use_tree)
        self.bits = cfg.bits if cfg.bits is not None else _sfc.max_bits_per_dim(d)
        self.frame_margin = float(frame_margin)
        self.controller = controller or _dyn.AmortizedController()
        # modeled cost of one full rebuild in controller units; default is
        # calibrated in rebuild() from the live imbalance baseline
        self._rebuild_cost = rebuild_cost
        self.stats = RepartitionStats()
        self._cache_token = next(_TOKEN_SOURCE)
        # versioned query-index state: bumped on every geometry / frame /
        # order change (insert, delete, rebuild) so serving layers holding
        # a CurveIndex can detect staleness and refresh incrementally
        self._index_version = 0
        self._index_cache: tuple[tuple[int, int], _ci.CurveIndex] | None = None
        # bumped only when the tracked POINT POPULATION changes (insert /
        # delete) — never on re-slices or rebuilds, which move ownership
        # of the same points. Plan caches (repro.mesh.plan_cache) key
        # their topology tier on this: AMR-free events can reuse every
        # adjacency-derived structure.
        self.topology_version = 0

        self.dps = _dyn.from_points(
            points,
            weights,
            capacity=capacity,
            max_depth=max_depth,
            bucket_size=bucket_size,
            splitter=cfg.splitter,
        )
        self._part = jnp.full((self.capacity,), -1, dtype=jnp.int32)
        self.rebuild()

    # -- basic accessors ----------------------------------------------------

    @property
    def capacity(self) -> int:
        return self.dps.capacity

    @property
    def part(self) -> jax.Array:
        """(C,) int32 part id per storage slot (-1 for inactive slots)."""
        return self._part

    @property
    def cache_token(self) -> int:
        """Bumped whenever cached keys are invalidated (geometry/frame
        change); `repro.kernels.ops.cached_sfc_key` uses it as the cache
        key for the Pallas key-gen path."""
        return self._cache_token

    def num_active(self) -> int:
        return int(self._pull(self.dps.active.sum()))

    def _pull(self, x):
        """Blocking device->host read of ``x`` (an array or a tuple of
        them, read together), in a ``repartition.sync`` span, counted once
        in ``stats.host_syncs`` / ``stats.host_pull_bytes``."""
        return _pull_to_host(x, self.stats)

    def partition_of(self, slot_ids) -> np.ndarray:
        """Current part id per given storage slot, validated.

        The slot-keyed consumer's accessor (the mesh application tracks
        its cells by slot): raises if any queried slot is inactive —
        silently reading a -1 part for a live-looking element is exactly
        the class of bug a stale slot array produces.
        """
        ids = np.asarray(slot_ids)
        if ids.size and (ids.min() < 0 or ids.max() >= self.capacity):
            # numpy would silently wrap negative ids to the tail slots —
            # the exact stale-slot read this accessor exists to catch
            raise ValueError(
                f"slot ids out of range [0, {self.capacity}): "
                f"min={ids.min()}, max={ids.max()}"
            )
        part = self._pull(self._part)[ids]
        if (part < 0).any():
            bad = ids[part < 0][:8]
            raise ValueError(f"inactive slots queried: {bad.tolist()}...")
        return part

    @property
    def index_version(self) -> int:
        """Bumped whenever the cached curve (keys/order/frame) changes —
        i.e. whenever a ``curve_index()`` held elsewhere went stale."""
        return self._index_version

    def curve_index(self, bucket_size: int = 32) -> _ci.CurveIndex:
        """The engine's cached curve as a shared, versioned ``CurveIndex``.

        Incremental refresh: reuses the cached keys, sorted order and
        frozen quantization frame — no key generation, no sort. Only the
        bucket directory is (re)carved, so refreshing after a weight-only
        step or a delta insert costs a gather + a tiny carve instead of a
        cold ``build``. Memoized per (index_version, bucket_size); ids in
        the returned index are storage-slot ids (stable across steps).
        """
        key = (self._index_version, bucket_size)
        if self._index_cache is not None and self._index_cache[0] == key:
            return self._index_cache[1]
        if self.tree_mode:
            idx = self._tree_curve_index()
        else:
            order = self._order
            idx = _ci.from_sorted(
                self.dps.points[order],
                order.astype(jnp.int32),
                self._keys[order],
                n_valid=self.num_active(),
                frame_lo=self._frame_lo,
                frame_hi=self._frame_hi,
                bits=self.bits,
                curve=self.cfg.curve,
                bucket_size=bucket_size,
                version=self._index_version,
                token=self._cache_token,
            )
        self._index_cache = (key, idx)
        return idx

    def _tree_curve_index(self) -> _ci.CurveIndex:
        """Materialize the tree-backed index: slots in bucket-major order,
        directory = the tree's buckets, queries addressed by root→leaf
        walk. The rank argsort here is the only per-slot sort in all of
        tree mode, paid once per index version (memoized by the caller),
        never by the partitioning steps themselves."""
        border = self._border
        act = self.dps.active
        M = border.rank.shape[0]
        rank_pp = border.rank[self.dps.leaf_id]
        key_pp = border.node_keys[self.dps.leaf_id]
        # inactive slots after everything; live slots in leaves that were
        # empty at the last rebuild keep their (tail) rank — the final
        # directory bucket is widened to cover them
        rank_eff = jnp.where(act, rank_pp, M + 1)
        order = jnp.argsort(rank_eff, stable=True).astype(jnp.int32)
        keys_sorted = jnp.where(act, key_pp, jnp.uint32(KEY_SENTINEL))[order]
        nb = max(1, int(self._pull(border.num_buckets)))
        cnt_leaf = jax.ops.segment_sum(
            act.astype(jnp.int32), self.dps.leaf_id, num_segments=M
        )
        cnt_rank = self._pull(cnt_leaf[border.order])
        starts = np.zeros((nb + 1,), np.int64)
        starts[1:] = np.cumsum(cnt_rank[:nb])
        starts[nb] = self.num_active()  # widen the tail bucket (see above)
        return _ci.from_buckets(
            self.dps.points[order],
            order,
            keys_sorted,
            starts,
            border.node_keys[border.order[:nb]],
            frame_lo=self._frame_lo,
            frame_hi=self._frame_hi,
            bits=self.bits,
            curve=self.cfg.curve,
            version=self._index_version,
            token=self._cache_token,
            tree=self.dps.tree,
            node_keys=border.node_keys,
        )

    # -- key generation against the frozen frame ----------------------------

    def _freeze_frame(self) -> None:
        pts = self._pull(self.dps.points)
        act = self._pull(self.dps.active)
        live = pts[act] if act.any() else np.zeros((1, pts.shape[1]), np.float32)
        lo, hi = live.min(axis=0), live.max(axis=0)
        span = np.where(hi > lo, hi - lo, 1.0)
        self._frame_lo = jnp.asarray(lo - self.frame_margin * span, jnp.float32)
        self._frame_hi = jnp.asarray(hi + self.frame_margin * span, jnp.float32)

    def _keys_in_frame(self, pts: jax.Array, *, cache: bool = False) -> jax.Array:
        """SFC keys against the frozen quantization frame (clipped).

        ``cache=True`` (the full-capacity rebuild path) routes through
        `kernels.ops.cached_sfc_key` under this engine's token, so the
        key batch is shared with any other consumer of the same token and
        dropped by `_invalidate_keys` on the next rebuild. Delta batches
        (inserts) compute directly — tiny, shape-varied, not worth cache
        entries.
        """
        if cache:
            from repro.kernels import ops as _kops

            keys = _kops.cached_sfc_key(
                pts,
                token=self._cache_token,
                curve=self.cfg.curve,
                bits=self.bits,
                use_pallas=self.cfg.use_pallas,
                lo=self._frame_lo,
                hi=self._frame_hi,
            )
        else:
            # the ONE keying convention: engine keys and query keys must
            # come from the same function or queries go to wrong buckets
            keys = _ci.keys_in_frame(
                pts, self._frame_lo, self._frame_hi,
                bits=self.bits, curve=self.cfg.curve,
            )
        self.stats.keygen_points += int(pts.shape[0])
        return keys

    def _invalidate_keys(self) -> None:
        old = self._cache_token
        self._cache_token = next(_TOKEN_SOURCE)
        try:  # notify the kernel-level cache (best effort: optional dep)
            from repro.kernels import ops as _kops

            _kops.invalidate_key_cache(old)
        except ImportError:  # pragma: no cover
            pass

    # -- delta operations ----------------------------------------------------

    def update_weights(self, weights: jax.Array, slot_ids: jax.Array | None = None) -> None:
        """Replace weights (full (C,)/(n_active,) vector, or a sparse batch
        at ``slot_ids``). Weight changes never invalidate cached keys."""
        with TraceAnnotation("repartition.update_weights"):
            if slot_ids is not None:
                new_w = self.dps.weights.at[jnp.asarray(slot_ids)].set(weights)
            else:
                weights = jnp.asarray(weights, jnp.float32)
                k = weights.shape[0]
                if k == self.capacity:
                    new_w = weights
                elif k == self.num_active():  # aligned with active slots in slot order
                    act_slots = jnp.nonzero(self.dps.active, size=k)[0]
                    new_w = self.dps.weights.at[act_slots].set(weights)
                else:
                    # any other length would silently scatter the tail into
                    # slot 0 (fixed-shape nonzero pads with 0)
                    raise ValueError(
                        f"weights length {k} matches neither capacity "
                        f"({self.capacity}) nor active count ({self.num_active()})"
                    )
            self.dps = self.dps._replace(weights=new_w)
            if self.tree_mode:
                # keep the exposed summary truthful under weight drift: one
                # segment_sum re-aggregates live weights onto the buckets
                # (count/centroid/bbox/keys are untouched — weight drift
                # moves nothing on the curve)
                w_leaf = jax.ops.segment_sum(
                    jnp.where(self.dps.active, new_w, 0.0),
                    self.dps.leaf_id,
                    num_segments=self._summary.num_nodes,
                )
                self._summary = dataclasses.replace(self._summary, weight=w_leaf)

    def insert(self, points: jax.Array, weights: jax.Array) -> jax.Array:
        """Insert a point batch; returns their storage slot ids. Keys are
        generated for the delta batch only (frozen frame); the cached
        curve order is re-sorted but not re-keyed."""
        with TraceAnnotation("repartition.insert"):
            k = points.shape[0]
            n_free = self.capacity - self.num_active()
            if k > n_free:
                # without this check the overflow scatters into one slot and
                # silently drops points (fixed-shape nonzero fill semantics)
                raise ValueError(
                    f"insert of {k} points exceeds free capacity {n_free}; "
                    f"grow the Repartitioner (capacity={self.capacity})"
                )
            rows = _dyn.padded_rows(k)
            self.stats._count_delta(k, rows)
            # bucket substrate: the located leaves are the only dirtied
            # summaries, refreshed in the same program; no key-gen, no
            # resort (there is no per-point key array to maintain)
            self.dps, summary, free = _insert_program(
                self.dps, self._summary if self.tree_mode else None,
                _pad_rows(points, rows, 0.0, np.float32),
                _pad_rows(weights, rows, 0.0, np.float32), np.int32(k),
                walk=_dyn.locate,
            )
            free = free if k == rows else free[:k]
            if self.tree_mode:
                self._summary = summary
                self.stats._refreshes += k
                self._index_version += 1
            else:
                self._keys = self._keys.at[free].set(self._keys_in_frame(points))
                self._resort()
            self.topology_version += 1
            return free

    def delete(self, slot_ids: jax.Array) -> None:
        with TraceAnnotation("repartition.delete"):
            k = len(slot_ids)
            rows = _dyn.padded_rows(k)
            self.stats._count_delta(k, rows)
            # the summary entries applied are the first-occurrence live
            # slots that the tree counters lose: summed on the device, unread
            self.dps, summary, unread = _delete_program(
                self.dps, self._summary if self.tree_mode else None,
                _pad_rows(slot_ids, rows, self.capacity, np.int32), np.int32(k),
                self.stats._unread_sum(k) if self.tree_mode else None,
            )
            if self.tree_mode:
                self._summary, self.stats._unread = summary, unread
                self._index_version += 1
            else:
                slot_ids = jnp.asarray(slot_ids)
                self._keys = self._keys.at[slot_ids].set(jnp.uint32(KEY_SENTINEL))
                self._resort()
            self.topology_version += 1

    # -- tree-mode bucket statistics -----------------------------------------

    def _refresh_bucket_stats(self) -> None:
        """Full O(B) refresh: recollect summaries over the (possibly
        adjusted) tree and re-key the bucket centroids on the frozen
        frame. This — not an O(n) point key-gen — is what a tree-mode
        rebuild pays."""
        self._summary = _kdtree.bucket_summary(
            self.dps.tree,
            self.dps.points,
            self.dps.weights,
            leaf_id=self.dps.leaf_id,
            active=self.dps.active,
        )
        self._border = _kdtree.bucket_order(
            self._summary,
            frame_lo=self._frame_lo,
            frame_hi=self._frame_hi,
            bits=self.bits,
            curve=self.cfg.curve,
        )
        self.stats.keygen_buckets += int(self._pull(self._border.num_buckets))

    def summary(self) -> "_kdtree.BucketSummary":
        """Tree mode: the live per-bucket statistics."""
        if not self.tree_mode:
            raise ValueError("bucket summaries exist only with cfg.use_tree=True")
        return self._summary

    def _resort(self) -> None:
        # sentinel keys (inactive slots) sort to the end; no key-gen here.
        # Every resort changes the curve order, so any CurveIndex snapshot
        # out there is now stale: bump the version (insert/delete/rebuild
        # all funnel through here; weight-only steps never do).
        self._order = jnp.argsort(self._keys, stable=True)
        self._index_version += 1

    # -- slicing -------------------------------------------------------------

    def _slice_current(self) -> tuple[jax.Array, np.ndarray, float]:
        """Knapsack-slice the cached curve; returns (part_per_slot, loads,
        imbalance). Tree mode slices the O(B) bucket weights; key mode
        slices the cached per-point order."""
        if self.tree_mode:
            part, loads_d = _bucket_slice_kernel(
                self.dps.leaf_id, self.dps.active, self.dps.weights,
                self._border.order, self.num_parts,
            )
        else:
            part, loads_d = _slice_kernel(
                self._order, self.dps.active, self.dps.weights, self.num_parts
            )
        loads = self._pull(loads_d)
        mean = max(float(loads.mean()), 1e-12)
        return part, loads, float(loads.max()) / mean

    def _make_plan(self, counts: np.ndarray) -> _migration.MigrationPlan:
        """Exchange-plan hook: hierarchical engines override this to emit
        level-aware plans from the same count matrix."""
        return _migration.plan_from_counts(counts)

    def _emit(self, kind: str, part: jax.Array, loads, imbalance, reused: bool,
              **extra) -> RepartitionStep:
        # stable elements only (active in both assignments) migrate
        counts = _send_counts_kernel(self._part, part, self.num_parts)
        plan = self._make_plan(self._pull(counts))
        self._part = part
        self.stats.history.append((kind, float(imbalance), int(plan.total_moved)))
        return RepartitionStep(
            kind=kind, part=part, plan=plan, loads=loads,
            imbalance=imbalance, reused_keys=reused, **extra,
        )

    # -- public stepping ------------------------------------------------------

    def rebalance(self) -> RepartitionStep:
        """Force an incremental re-slice of the cached curve (no key-gen,
        no tree adjustment)."""
        with TraceAnnotation("repartition.slice"):
            part, loads, imb = self._slice_current()
        self.stats.incremental_steps += 1
        with TraceAnnotation("repartition.plan"):
            return self._emit("incremental", part, loads, imb, reused=True)

    def rebuild(self) -> RepartitionStep:
        """Force a full rebuild: tree adjustments, fresh frame, fresh keys
        (bucket keys in tree mode — O(B), never the points)."""
        with TraceAnnotation("repartition.rebuild"):
            if self.stats.rebuilds or self.stats.incremental_steps:
                # skip Alg. 1 on the pristine initial build
                self.dps = _dyn.adjustments(self.dps)
            self._freeze_frame()
            self._invalidate_keys()
            if self.tree_mode:
                self._refresh_bucket_stats()
                self._index_version += 1
            else:
                act = self.dps.active
                keys = self._keys_in_frame(self.dps.points, cache=True)
                self._keys = jnp.where(act, keys, jnp.uint32(KEY_SENTINEL))
                self._resort()
            with TraceAnnotation("repartition.slice"):
                part, loads, imb = self._slice_current()
            self.stats.rebuilds += 1
            cost = self._rebuild_cost if self._rebuild_cost is not None else float(self.num_active())
            num_buckets = int(self._pull(_dyn.num_buckets(self.dps)))
            self.controller.balanced(lb_cost=cost, num_buckets=num_buckets, timeop=imb)
            with TraceAnnotation("repartition.plan"):
                return self._emit("rebuild", part, loads, imb, reused=False)

    def resize(self, num_parts: int) -> RepartitionStep:
        """Elastic part-count change (device loss / growth): re-slice the
        CACHED curve onto ``num_parts`` parts. No tree adjustment, no
        key generation, no sort — the paper's incremental-LB machinery IS
        the elastic-scaling mechanism. The migration count matrix spans
        ``max(old, new)`` parts so shrink paths account for units leaving
        vanished parts (the `elastic.replacement_plan` sizing convention).

        Bumps ``index_version``: the re-slice is a partition-geometry
        event serving layers must observe (a ``maybe_refresh`` picks up
        the same curve re-carved, never a cold rebuild)."""
        old_part, old_parts_n = self._part, self.num_parts
        self.num_parts = int(num_parts)
        with TraceAnnotation("repartition.slice"):
            part, loads, imb = self._slice_current()
        with TraceAnnotation("repartition.plan"):
            union = max(old_parts_n, self.num_parts)
            counts = self._pull(_send_counts_kernel(old_part, part, union))
            plan = _migration.plan_from_counts(counts)
            self._part = part
            self._index_version += 1
            self.stats.incremental_steps += 1
            self.stats.resizes += 1
            self.stats.history.append(("resize", float(imb), int(plan.total_moved)))
        return RepartitionStep(
            kind="incremental", part=part, plan=plan, loads=loads,
            imbalance=imb, reused_keys=True,
        )

    def step(self, timeop: float | None = None) -> RepartitionStep:
        """One engine step: consult the amortized controller (Alg. 3) and
        either re-slice incrementally or run a full rebuild.

        ``timeop`` is the measured per-op cost this iteration; when absent
        the live load imbalance (max/mean) of the *current* assignment
        under the *new* weights stands in for it — a hot part means slow
        ops, which is exactly the drift the credit scheme meters.
        """
        with TraceAnnotation("repartition.step"):
            with TraceAnnotation("repartition.timeop"):
                if timeop is None:
                    loads, num_buckets = self._pull(
                        _live_loads_kernel(self._part, self.dps, self.num_parts))
                    timeop = float(loads.max()) / max(float(loads.mean()), 1e-12)
                else:
                    num_buckets = self._pull(_dyn.num_buckets(self.dps))
            fire = self.controller.observe(timeop, int(num_buckets))
            return self.rebuild() if fire else self.rebalance()


class HierarchicalRepartitioner(Repartitioner):
    """Two-level (node -> device) incremental engine with a two-level
    Algorithm-3 trigger.

    The flat engine answers every drift with one knapsack over the whole
    curve — any element may move to any part, so even tiny drift can
    cross the expensive node boundary. This engine nests the response:

    * **intra-node re-slice** (the default incremental step) — the
      bucket->node assignment is frozen; only each node's device slices
      are re-knapsacked. Every move is node-local by construction.
    * **inter-node re-slice** — fires only when the *node-level*
      imbalance (max/mean node load under the frozen assignment) crosses
      ``node_threshold``; both knapsack levels re-run and node slices
      shift.
    * **rebuild** — the amortized controller (paper Alg. 3) meters drift
      exactly as in the flat engine and still decides when the tree +
      frame must be rebuilt.

    ``stats.intra_reslices`` / ``stats.inter_reslices`` count how often
    each level fires; steps carry ``level`` / ``node_loads`` /
    ``node_imbalance``, and migration plans are level-aware
    (`migration.HierarchicalMigrationPlan`: per-level round capping,
    inter-node bytes cost ``plan.inter_node_cost`` times more,
    per-level stay fractions). Runs on the bucket substrate
    (``cfg.use_tree`` is forced True: the hierarchy slices O(B) bucket
    weights).
    """

    def __init__(
        self,
        points: jax.Array,
        weights: jax.Array | None = None,
        plan: _pt.HierarchyPlan = _pt.HierarchyPlan(),
        cfg: _pt.PartitionerConfig | None = None,
        *,
        node_threshold: float = 1.10,
        **kw,
    ):
        self.plan = plan
        self.node_threshold = float(node_threshold)
        self._bucket_node: jax.Array | None = None
        self._node_loads: np.ndarray | None = None
        cfg = cfg or _pt.PartitionerConfig(use_tree=True)
        if not cfg.use_tree:
            cfg = dataclasses.replace(cfg, use_tree=True)
        super().__init__(points, weights, plan.num_parts, cfg, **kw)

    # -- hierarchy accessors -------------------------------------------------

    @property
    def node_part(self) -> jax.Array:
        """(C,) int32 node id per storage slot (-1 inactive)."""
        return jnp.where(
            self._part >= 0, self._part // self.plan.devices_per_node, -1
        )

    def node_imbalance(self) -> float:
        """Node-level max/mean load of the FROZEN node assignment under
        the live weights — the inter-node trigger's input."""
        return self._node_state()[0]

    def _node_state(self) -> tuple[float, np.ndarray]:
        # O(B), not O(n): the live bucket weights (kept current by
        # update_weights' re-aggregation and the insert/delete delta
        # scatters) already hold the active point mass per bucket —
        # aggregating them through the frozen bucket->node map costs two
        # (M,) transfers, never a point-length one
        w_leaf = self._pull(self._summary.weight)
        node_b = self._pull(self._bucket_node)
        loads = np.zeros(self.plan.num_nodes)
        np.add.at(loads, node_b, w_leaf)
        return float(loads.max() / max(loads.mean(), 1e-12)), loads

    # -- level-aware slicing hooks -------------------------------------------

    def _slice_current(self) -> tuple[jax.Array, np.ndarray, float]:
        """Full two-level slice (rebuilds and inter-node re-slices):
        refreshes the frozen bucket->node assignment."""
        part, loads_d, node_loads_d, bucket_node = _hier_bucket_slice_kernel(
            self.dps.leaf_id, self.dps.active, self.dps.weights,
            self._border.order, self.plan.num_nodes, self.plan.devices_per_node,
        )
        self._bucket_node = bucket_node
        self._node_loads = self._pull(node_loads_d)
        loads = self._pull(loads_d)
        return part, loads, float(loads.max()) / max(float(loads.mean()), 1e-12)

    def _slice_intra(self) -> tuple[jax.Array, np.ndarray, float]:
        part, loads_d, node_loads_d = _hier_intra_slice_kernel(
            self.dps.leaf_id, self.dps.active, self.dps.weights,
            self._border.order, self._bucket_node,
            self.plan.num_nodes, self.plan.devices_per_node,
        )
        self._node_loads = self._pull(node_loads_d)
        loads = self._pull(loads_d)
        return part, loads, float(loads.max()) / max(float(loads.mean()), 1e-12)

    def _make_plan(self, counts: np.ndarray) -> _migration.MigrationPlan:
        return _migration.plan_from_counts(counts, hierarchy=self.plan)

    def _emit(self, kind, part, loads, imbalance, reused, **extra) -> RepartitionStep:
        if "node_loads" not in extra and self._node_loads is not None:
            nl = self._node_loads
            extra["node_loads"] = nl
            extra["node_imbalance"] = float(nl.max() / max(nl.mean(), 1e-12))
        return super()._emit(kind, part, loads, imbalance, reused, **extra)

    # -- public stepping -----------------------------------------------------

    def resize(self, plan: _pt.HierarchyPlan) -> RepartitionStep:  # type: ignore[override]
        """Elastic mesh-shape change: re-slice the cached bucket curve
        onto a new ``HierarchyPlan`` (node count and/or device fan-out).
        Hierarchy-aware: the full two-level knapsack re-runs (a device
        pool change is by definition an inter-node event), the frozen
        bucket->node assignment refreshes, and ``index_version`` bumps so
        serving layers swap live — tree, frame, keys and bucket summaries
        are all reused (no rebuild).

        The migration count matrix spans ``max(old, new)`` part ids; the
        level-aware round schedule only applies when the union matches
        the new hierarchy (pure growth) — a shrink emits a flat plan over
        the union, since vanished parts have no (node, device) address in
        the new plan."""
        old_part, old_parts_n = self._part, self.num_parts
        self.plan = plan
        self.num_parts = int(plan.num_parts)
        with TraceAnnotation("repartition.slice"):
            part, loads, imb = self._slice_current()   # refreshes _bucket_node
        with TraceAnnotation("repartition.plan"):
            union = max(old_parts_n, self.num_parts)
            counts = self._pull(_send_counts_kernel(old_part, part, union))
            mplan = _migration.plan_from_counts(
                counts, hierarchy=plan if union == self.num_parts else None
            )
            self._part = part
            self._index_version += 1
            self.stats.incremental_steps += 1
            self.stats.inter_reslices += 1
            self.stats.resizes += 1
            self.stats.history.append(("resize", float(imb), int(mplan.total_moved)))
        nl = self._node_loads
        return RepartitionStep(
            kind="incremental", part=part, plan=mplan, loads=loads,
            imbalance=imb, reused_keys=True, level="inter",
            node_loads=nl,
            node_imbalance=float(nl.max() / max(nl.mean(), 1e-12)),
        )

    def rebalance(self, level: str | None = None) -> RepartitionStep:
        """Incremental re-slice; ``level`` forces "intra"/"inter", default
        consults the node-level trigger."""
        with TraceAnnotation("repartition.slice"):
            if level is None:
                nimb, _ = self._node_state()
                level = "inter" if nimb > self.node_threshold else "intra"
            if level == "inter":
                part, loads, imb = self._slice_current()
                self.stats.inter_reslices += 1
            elif level == "intra":
                part, loads, imb = self._slice_intra()
                self.stats.intra_reslices += 1
            else:
                raise ValueError(f"unknown re-slice level {level!r}")
        self.stats.incremental_steps += 1
        with TraceAnnotation("repartition.plan"):
            return self._emit(
                "incremental", part, loads, imb, reused=True, level=level,
            )


# ---------------------------------------------------------------------------
# Distributed engine: cached per-shard keys over `distributed_partition`
# ---------------------------------------------------------------------------

class DistributedRepartitioner:
    """Incremental repartitioning over a device mesh.

    ``partition(points, weights)`` runs the full distributed pipeline
    (key-gen → sample-sort all_to_all → global knapsack) and caches the
    per-shard sorted keys + validity mask. ``rebalance(weights_sorted)``
    then answers weight-only load changes with a single
    `partitioner.distributed_reslice` — one P-scalar all_gather plus a
    local scan, with the cached keys never touched. Geometry changes
    require a fresh ``partition``.
    """

    def __init__(
        self,
        mesh: jax.sharding.Mesh,
        axis: str,
        num_parts: int,
        cfg: _pt.PartitionerConfig = _pt.PartitionerConfig(),
        oversample: int = 8,
    ):
        self.mesh, self.axis = mesh, axis
        self.num_parts = int(num_parts)
        self.cfg, self.oversample = cfg, oversample
        self.keys_sorted: jax.Array | None = None
        self.valid: jax.Array | None = None
        self._part_sorted: jax.Array | None = None
        self.full_partitions = 0
        self.reslices = 0
        # bumped on every full partition (fresh keys => any serving index
        # built on the previous curve is stale and must be swapped)
        self.index_version = 0

    def partition(self, points: jax.Array, weights: jax.Array):
        keys, wts, part = _pt.distributed_partition(
            self.mesh, self.axis, points, weights, self.num_parts,
            cfg=self.cfg, oversample=self.oversample,
        )
        self.keys_sorted = keys
        self.valid = wts >= 0
        self._part_sorted = part
        self.full_partitions += 1
        self.index_version += 1
        return keys, wts, part

    def rebalance(self, weights_sorted: jax.Array) -> jax.Array:
        """Weight-only rebalance; ``weights_sorted`` is laid out like the
        weights returned by ``partition`` (the cached curve order)."""
        if self.valid is None:
            raise RuntimeError("rebalance() before the first partition()")
        part = _pt.distributed_reslice(
            self.mesh, self.axis, weights_sorted, self.valid, self.num_parts
        )
        self._part_sorted = part
        self.reslices += 1
        return part

    def migration_between(self, old_part: jax.Array, new_part: jax.Array) -> _migration.MigrationPlan:
        """Bounded-message exchange plan between two sorted-layout
        assignments (invalid slots excluded)."""
        valid = _pull_to_host(self.valid)
        return _migration.migration_plan(
            _pull_to_host(old_part)[valid], _pull_to_host(new_part)[valid], self.num_parts
        )


class DistributedBucketRepartitioner:
    """Incremental distributed repartitioning over bucket summaries.

    The sample-sort engine above physically re-sorts the points across
    shards and caches the sorted keys. This engine never moves a point
    for the *computation*: ``partition`` builds one local kd-tree per
    shard (keyed on a global shared frame) and caches ``(leaf_id,
    node_keys)``; every ``rebalance`` then exchanges O(B) bucket
    summaries (one all_gather) and gathers part ids home — the
    partition-recompute hot loop costs neither key generation nor an
    O(n) sort nor an all_to_all. Assignments stay in the ORIGINAL
    element layout, ready for ``sharding.apply_repartition``.
    """

    def __init__(
        self,
        mesh: jax.sharding.Mesh,
        axis: str | None = None,
        num_parts: int | None = None,
        cfg: _pt.PartitionerConfig | None = None,
        *,
        plan: _pt.HierarchyPlan | None = None,
    ):
        """Flat usage: ``(mesh, axis, num_parts)`` — internally the
        trivial ``HierarchyPlan(1, num_parts, device_axis=axis)``.
        Hierarchical usage: ``(mesh, plan=HierarchyPlan(N, D))`` on a 2-D
        (node, device) mesh — the reslice hot loop then exchanges
        node-aggregated summaries across nodes (O(B * nodes) inter-node
        bytes instead of O(B * devices))."""
        if plan is None:
            if axis is None or num_parts is None:
                raise ValueError("flat engine needs (mesh, axis, num_parts)")
            plan = _pt.HierarchyPlan(
                num_nodes=1, devices_per_node=int(num_parts), device_axis=axis
            )
        self.mesh, self.plan = mesh, plan
        self.axis = plan.device_axis if axis is None else axis
        self.num_parts = plan.num_parts
        # distributed trees default shallower than local ones: B buckets
        # per shard is the exchanged payload
        self.cfg = cfg or _pt.PartitionerConfig(use_tree=True, max_depth=8)
        self.leaf_id: jax.Array | None = None
        self.node_keys: jax.Array | None = None
        self._part: jax.Array | None = None
        self.full_partitions = 0
        self.reslices = 0
        self.index_version = 0

    def partition(self, points: jax.Array, weights: jax.Array) -> jax.Array:
        """Cold path: local trees + summary exchange. Caches the per-shard
        tree state for the reslice hot loop."""
        part, leaf_id, node_keys = _pt.hierarchical_bucket_partition(
            self.mesh, self.plan, points, weights, cfg=self.cfg
        )
        self.leaf_id, self.node_keys = leaf_id, node_keys
        self._part = part
        self.full_partitions += 1
        self.index_version += 1
        return part

    def rebalance(self, weights: jax.Array) -> jax.Array:
        """Hot path: new weights (original layout), same geometry — one
        two-stage summary exchange, no key-gen, no sort, no all_to_all."""
        if self.leaf_id is None:
            raise RuntimeError("rebalance() before the first partition()")
        part = _pt.hierarchical_bucket_reslice(
            self.mesh, self.plan, self.leaf_id, weights, self.node_keys
        )
        self._part = part
        self.reslices += 1
        return part

    def migration_between(self, old_part, new_part) -> _migration.MigrationPlan:
        """Exchange plan between two original-layout assignments —
        level-aware when the engine's hierarchy is non-trivial."""
        return _migration.migration_plan(
            _pull_to_host(old_part), _pull_to_host(new_part), self.num_parts,
            hierarchy=self.plan if self.plan.num_nodes > 1 else None,
        )
