"""Sharding rules: logical model axes -> mesh axes.

Rules live in config (``ShardingRules``), not in model code, so the perf
hillclimb can move axes without touching models. Conventions:

* params are 2-D sharded FSDP x TP: the "d_model-ish" dim over
  ``rules.fsdp`` (usually "data"), the "wide" dim (heads/ffn/vocab/
  experts) over ``rules.tp`` (usually "model"). Optimizer state mirrors
  params. The "pod" axis is pure DCN data parallel (batch only).
* activations are constrained at block boundaries to
  P(batch=rules.batch, seq=rules.seq) — sequence parallelism keeps the
  remat stash per device O(S/model) for long sequences.
* decode caches shard batch over ``rules.cache_batch`` and KV heads /
  SSM heads over ``rules.cache_heads``.

GSPMD handles non-divisible dims by padding (e.g. 56 heads on 16-way TP);
the roofline notes where that costs real FLOPs.
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShardingRules

_CTX = threading.local()


@contextlib.contextmanager
def activation_mesh(mesh: Mesh, rules: ShardingRules):
    """Enable activation sharding constraints inside model code."""
    prev = getattr(_CTX, "val", None)
    _CTX.val = (mesh, rules)
    try:
        yield
    finally:
        _CTX.val = prev


def _current() -> tuple[Mesh, ShardingRules] | None:
    return getattr(_CTX, "val", None)


def _axes_in(mesh: Mesh, axes) -> Any:
    """Filter a spec entry to axes that exist in the mesh."""
    if axes is None:
        return None
    if isinstance(axes, str):
        return axes if axes in mesh.axis_names else None
    got = tuple(a for a in axes if a in mesh.axis_names)
    return got if got else None


def constrain(x: jax.Array, *spec_entries) -> jax.Array:
    """with_sharding_constraint if an activation mesh is active, else no-op."""
    ctx = _current()
    if ctx is None:
        return x
    mesh, _ = ctx
    entries = tuple(_axes_in(mesh, e) for e in spec_entries)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*entries)))


def constrain_activations(x: jax.Array) -> jax.Array:
    """(B, S, D) block-boundary constraint: batch x seq sharding."""
    ctx = _current()
    if ctx is None:
        return x
    mesh, rules = ctx
    if x.ndim == 3:
        return constrain(x, rules.batch, rules.seq, None)
    return x


def constrain_blocked_attention(
    qb: jax.Array, kb: jax.Array, vb: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Constraints for the blocked flash-attention tensors.

    qb (nq, B, KV, G, bq, hd), kb/vb (nk, B, KV, bk, hd). Without these,
    GSPMD shards the stacked-block dim and the per-block dynamic_slice
    triggers 'involuntary full rematerialization' (replicate + repartition
    of the whole q tensor per block — an XLA SPMD warning and a large
    collective term). Pin: block dim replicated, batch on rules.batch,
    KV heads on rules.tp when divisible.
    """
    ctx = _current()
    if ctx is None:
        return qb, kb, vb
    mesh, rules = ctx
    if not rules.blocked_attn:
        return qb, kb, vb
    ax_size = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = _axes_in(mesh, rules.tp)
    kv = qb.shape[2]
    heads_ax = tp if (tp is not None and kv % ax_size.get(tp, 1) == 0) else None
    qb = constrain(qb, None, rules.batch, heads_ax, None, None, None)
    kb = constrain(kb, None, rules.batch, heads_ax, None, None)
    vb = constrain(vb, None, rules.batch, heads_ax, None, None)
    return qb, kb, vb


def constrain_moe(x: jax.Array, kind: str, num_experts: int) -> jax.Array:
    """Sharding constraints for MoE dispatch intermediates.

    GSPMD loses propagation through the per-row sort/scatter chain and
    falls back to full replication (measured 320 GiB for the (B, E, C,
    2F) expert activation at mixtral train_4k). Layouts:
      'tokens'  (B, TK, D)      -> (batch, None, None)
      'buf'     (B, E, C, D)    -> (batch, expert?, None, None)
      'h'       (B, E, C, F)    -> (batch, expert?, None, tp-if-no-EP)
    Expert axis is used only when E divides it (qwen3 128e); otherwise
    the FFN dim takes the TP axis (mixtral 8e).
    """
    ctx = _current()
    if ctx is None:
        return x
    mesh, rules = ctx
    ax_size = dict(zip(mesh.axis_names, mesh.devices.shape))
    ep = _axes_in(mesh, rules.expert)
    ep_ok = ep is not None and num_experts % ax_size.get(ep, 1) == 0
    e_ax = ep if ep_ok else None
    f_ax = None if ep_ok else rules.tp
    if kind == "tokens":
        return constrain(x, rules.batch, None, None)
    if kind == "buf":
        return constrain(x, rules.batch, e_ax, None, None)
    if kind == "h":
        return constrain(x, rules.batch, e_ax, None, f_ax)
    return x


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------

def _sanitize(spec: P, shape: tuple, mesh: Mesh) -> P:
    """Drop mesh axes that do not divide the corresponding dim.

    pjit argument shardings are strict (unlike internal GSPMD propagation,
    which pads); replication on the offending dim is always legal and the
    roofline reports the cost (e.g. minicpm's odd 122,753 vocab).
    """
    ax_size = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            out.append(None if i >= len(shape) else entry)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        total = 1
        for a in axes:
            total *= ax_size.get(a, 1)
        out.append(entry if total and shape[i] % total == 0 else None)
    return P(*out)


def _param_spec(path: str, leaf, cfg: ModelConfig, rules: ShardingRules) -> P:
    """PartitionSpec for one parameter, keyed by its tree path."""
    fsdp, tp, ep = rules.fsdp, rules.tp, rules.expert
    nd = len(leaf.shape)
    stacked = path.startswith("blocks") or path.startswith("enc_blocks") or path.startswith("dec_blocks")
    lead = (None,) if stacked else ()

    name = path.split("/")[-1]
    # MoE stacked experts (L, E, D, F) — must match before the generic
    # wi/wo rules below
    if "moe" in path and nd - len(lead) == 3:
        if ep is not None and cfg.num_experts % 16 == 0:
            return P(*lead, ep, fsdp, None)     # expert parallelism
        return P(*lead, None, fsdp, tp)         # TP within experts (mixtral)
    if name in ("embed",):
        return P(tp, fsdp)                      # (V, D)
    if name in ("lm_head",):
        return P(fsdp, tp)                      # (D, V)
    if name in ("wq", "wk", "wv", "wi", "w_in", "w_z", "w_x", "w_b", "w_c", "w_dt"):
        return P(*lead, fsdp, tp)               # (D, wide)
    if name in ("wo", "w_out"):
        return P(*lead, tp, fsdp)               # (wide, D)
    if name == "router":
        return P(*lead, fsdp, None)             # (D, E) — replicate experts dim
    # norms / scalars / vectors: replicate (tiny)
    return P(*([None] * nd))


def param_shardings(
    mesh: Mesh, cfg: ModelConfig, rules: ShardingRules, params_shapes: Any
) -> Any:
    """Pytree of NamedSharding matching a params (shape) pytree."""

    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shapes)
    out = []
    for path, leaf in flat:
        pstr = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        spec = _param_spec(pstr, leaf, cfg, rules)
        spec = P(*(_axes_in(mesh, e) for e in spec))
        spec = _sanitize(spec, tuple(leaf.shape), mesh)
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


def opt_state_shardings(mesh: Mesh, cfg, rules, opt_shapes: Any, param_sh: Any) -> Any:
    """Optimizer state mirrors param shardings (master/m/v); step replicated."""
    rep = NamedSharding(mesh, P())
    return {
        "master": param_sh,
        "m": param_sh,
        "v": param_sh,
        "step": rep,
    }


# ---------------------------------------------------------------------------
# batch / cache specs
# ---------------------------------------------------------------------------

def batch_shardings(mesh: Mesh, cfg: ModelConfig, rules: ShardingRules, batch_shapes: dict) -> dict:
    b = _axes_in(mesh, rules.batch)
    out = {}
    for k, v in batch_shapes.items():
        if k == "cache":
            out[k] = cache_shardings(mesh, cfg, rules, v)
            continue
        if k in ("token", "pos"):
            spec = P(b)
        elif hasattr(v, "ndim") and v.ndim == 3:  # frames / patches (B, T, D)
            spec = P(b, None, None)
        else:  # tokens / labels / mask (B, S)
            spec = P(b, None)
        spec = _sanitize(spec, tuple(v.shape), mesh)  # long_500k has B=1
        out[k] = NamedSharding(mesh, spec)
    return out


def logits_sharding(mesh: Mesh, cfg: ModelConfig, rules: ShardingRules, shape: tuple) -> NamedSharding:
    """(B, S, V) prefill logits: batch x vocab sharded, sanitized for odd
    vocab sizes (whisper 51,865; minicpm 122,753)."""
    b = _axes_in(mesh, rules.batch)
    tp = _axes_in(mesh, rules.tp)
    spec = _sanitize(P(b, None, tp), shape, mesh)
    return NamedSharding(mesh, spec)


def cache_shardings(mesh: Mesh, cfg: ModelConfig, rules: ShardingRules, cache_shapes: Any) -> Any:
    cb = _axes_in(mesh, rules.cache_batch)
    ch = _axes_in(mesh, rules.cache_heads)
    ax_size = dict(zip(mesh.axis_names, mesh.devices.shape))

    def _fits(axes, dim) -> bool:
        if axes is None:
            return False
        alist = (axes,) if isinstance(axes, str) else tuple(axes)
        total = 1
        for a in alist:
            total *= ax_size.get(a, 1)
        return dim % total == 0

    def one(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        nd = len(leaf.shape)
        if name in ("k", "v") and nd == 5:      # (L, B, S, KV, hd)
            L, B, S, KV, hd = leaf.shape
            b = cb if _fits(cb, B) else None
            # prefer KV-head sharding; fall back to sequence sharding when
            # heads don't divide (GQA kv=1..4) or batch can't shard (B=1)
            if _fits(ch, KV):
                spec = P(None, b, None, ch, None)
            elif _fits(ch, S):
                spec = P(None, b, ch, None, None)
            else:
                spec = P(None, b, None, None, None)
            return NamedSharding(mesh, spec)
        if name == "state" and nd == 5:          # (L, B, nh, hp, N)
            L, B, nh, hp, N = leaf.shape
            b = cb if _fits(cb, B) else None
            h = ch if _fits(ch, nh) else None
            return NamedSharding(mesh, P(None, b, h, None, None))
        if name == "memory" and nd == 3:         # (B, T_enc, D)
            B = leaf.shape[0]
            b = cb if _fits(cb, B) else None
            return NamedSharding(mesh, P(b, None, None))
        return NamedSharding(mesh, P(*([None] * nd)))

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shapes)
    return jax.tree_util.tree_unflatten(treedef, [one(p, l) for p, l in flat])


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Hierarchical (node -> device) mesh helpers + two-stage summary exchange
# ---------------------------------------------------------------------------

def make_node_device_mesh(
    num_nodes: int,
    devices_per_node: int,
    node_axis: str = "node",
    device_axis: str = "device",
) -> Mesh:
    """2-D ``(node, device)`` mesh over the available devices — the JAX
    rendering of the paper's hybrid model (MPI across nodes, threads
    within one). Axis order is node-major so ``P((node, device))`` shards
    a curve-ordered array into node-contiguous chunks."""
    from repro.launch.mesh import make_mesh

    return make_mesh((num_nodes, devices_per_node), (node_axis, device_axis))


def two_stage_bucket_slice(
    w_leaf: jax.Array,
    node_keys: jax.Array,
    *,
    plan,
    num_dev_shards: int,
) -> jax.Array:
    """Two-level global knapsack over bucket summaries; part id per LOCAL
    tree node. Runs inside ``shard_map``; ``plan`` is a
    `partitioner.HierarchyPlan`.

    Stage 1 (intra-node): ``all_gather`` of the raw (M,) per-shard
    summaries over the device axis only — full within-node detail, never
    crossing the node boundary. Stage 2 (inter-node): each node compacts
    its sorted records into ``plan.summary_bins`` (default M) equal-count
    bins and ONE ``all_gather`` over the node axis exchanges those — the
    inter-node payload is O(B * nodes), not O(B * devices); see
    `summary_exchange_bytes` for the exact accounting. The nested
    knapsack (`knapsack.two_level_slice`) then slices the bins into node
    slices and per-node device parts, and local buckets map into the
    result by bin boundary key. Granularity note: because a node's curve
    slice can contain buckets resident on every other node, BOTH levels
    slice the aggregated bins — balance granularity on this path is one
    bin (up to ``num_dev_shards`` merged bucket records) at the node
    and device level alike.

    With ``plan.num_nodes == 1`` stage 2 vanishes and the fine knapsack
    runs on the full stage-1 records — bit-identical to the historical
    flat ``distributed_bucket_partition`` math, at full bucket
    granularity.
    """
    from repro.core import knapsack as _knapsack

    M = node_keys.shape[0]
    N, D = plan.num_nodes, plan.devices_per_node
    all_k = jax.lax.all_gather(node_keys, plan.device_axis).reshape(-1)
    all_w = jax.lax.all_gather(w_leaf, plan.device_axis).reshape(-1)
    order = jnp.argsort(all_k, stable=True)
    k_sorted, w_sorted = all_k[order], all_w[order]

    if N == 1:
        _, _, part_rank = _knapsack.two_level_slice(w_sorted, 1, D)
        part_flat = (
            jnp.zeros((num_dev_shards * M,), jnp.int32).at[order].set(part_rank)
        )
        me = jax.lax.axis_index(plan.device_axis)
        return jax.lax.dynamic_slice(part_flat, (me * M,), (M,))

    # node-aggregate: A equal-count bins over the node-sorted records
    # (sentinel-keyed empty records carry 0 weight and pool at the tail)
    R = num_dev_shards * M
    A = plan.summary_bins or M
    bin_id = (jnp.arange(R, dtype=jnp.int32) * A) // R
    bin_w = jax.ops.segment_sum(w_sorted, bin_id, num_segments=A)
    # bin b's FIRST record is the smallest i with (i*A)//R == b, i.e.
    # ceil(b*R/A) — floor lands on the last record of bin b-1 whenever A
    # does not divide R, mis-keying the boundary
    bin_first = (jnp.arange(A, dtype=jnp.int32) * R + A - 1) // A
    bin_k = k_sorted[bin_first]
    gk = jax.lax.all_gather(bin_k, plan.node_axis).reshape(-1)     # (N*A,)
    gw = jax.lax.all_gather(bin_w, plan.node_axis).reshape(-1)
    gorder = jnp.argsort(gk, stable=True)
    gk_s = gk[gorder]
    _, _, part_bin = _knapsack.two_level_slice(gw[gorder], N, D)
    # local buckets inherit the part of the last bin whose first key is
    # <= their key (parts are non-decreasing along the sorted bins)
    idx = jnp.clip(
        jnp.searchsorted(gk_s, node_keys, side="right").astype(jnp.int32) - 1,
        0, N * A - 1,
    )
    return part_bin[idx]


def summary_exchange_bytes(
    plan,
    buckets_per_shard: int,
    *,
    bytes_per_record: int = 8,
) -> dict:
    """Exact inter-node byte accounting of one summary exchange (the
    reslice hot loop's only communication). A record is one bucket's
    (uint32 key, float32 weight).

    * **flat** — one all_gather over all ``N*D`` shards: every device
      ingests every remote shard's raw records.
    * **two_level** — stage 1 is intra-node (0 inter-node bytes); stage 2
      ingests the remote nodes' aggregated bins only.

    This is the closed-form *model*; the benchmark gate
    (`benchmarks/bench_hierarchy.py --smoke`) measures the same quantity
    from the compiled programs' replica groups
    (`launch.dryrun.parse_inter_node_bytes`) and holds
    ``two_level < flat`` against that measurement, with this model
    reported alongside for drift visibility.
    """
    N, D = plan.num_nodes, plan.devices_per_node
    M = int(buckets_per_shard)
    A = plan.summary_bins or M
    # per-device delivery convention — the one parse_inter_node_bytes
    # measures: every device of a gather's replica group receives each
    # remote member's operand. Flat: all N*D devices each ingest the
    # (N-1)*D remote shards' M records. Two-level: the node-axis gather
    # runs once per device column, so all N*D devices each ingest the
    # (N-1) remote nodes' A bins. Ratio: D*M/A (= D at the default A=M).
    flat = N * D * (N - 1) * D * M * bytes_per_record
    two_level = N * D * (N - 1) * A * bytes_per_record
    return {
        "flat_inter_node_bytes": int(flat),
        "two_level_inter_node_bytes": int(two_level),
        "intra_node_bytes": int(N * D * (D - 1) * M * bytes_per_record),
        "records_per_shard": M,
        "bins_per_node": int(A),
    }


# ---------------------------------------------------------------------------
# dynamic element placement (repartitioning engine integration)
# ---------------------------------------------------------------------------

def curve_sharding(mesh: Mesh, axis: str) -> NamedSharding:
    """Sharding for curve-ordered element arrays: shard i of ``axis`` holds
    the i-th contiguous chunk of the global SFC order (the layout produced
    by `repro.core.partitioner.distributed_partition`)."""
    return NamedSharding(mesh, P(axis))


def apply_repartition(
    mesh: Mesh,
    axis: str,
    payload: jax.Array,
    part: jax.Array,
    *,
    capacity: int | None = None,
    fill_value=0,
):
    """Move rows of ``payload`` (sharded on dim 0 over ``axis``) to the
    shard given by ``part`` — the output of a `Repartitioner` step,
    `distributed_reslice`, or the bucket-summary path
    (`distributed_bucket_partition` / `DistributedBucketRepartitioner`,
    whose assignments are already in this original row layout: the
    bucket path never moves rows to *compute* the partition, so this
    exchange is the only data motion in the whole cycle). Invalid rows
    (part < 0) are parked on their current shard and masked out of the
    result.

    Returns (received, valid_mask) in the fixed-capacity layout of
    `migration.execute_shard_exchange`. ``capacity`` is per (src, dst)
    pair *including* stay-home rows; the default — one shard's full row
    count — is the smallest value that can never drop a row (a pair
    cannot carry more than its source shard holds). Pass something
    smaller only with a migration plan proving the worst pair is small.
    """
    from repro.core import migration as _migration

    nshards = mesh.shape[axis]
    n_rows = payload.shape[0]
    if capacity is None:
        capacity = max(1, int(np.ceil(n_rows / nshards)))
    # P(axis) = contiguous chunks: row r lives on shard r*S//n
    me_rows = (jnp.arange(n_rows) * nshards) // n_rows  # park invalid rows locally
    dest = jnp.where(part >= 0, part, me_rows).astype(jnp.int32)
    recv, valid = _migration.execute_shard_exchange(
        mesh, axis, payload, dest, capacity, fill_value=fill_value
    )
    return recv, valid


# ---------------------------------------------------------------------------
# Distributed query serving (paper §V-A over a sharded CurveIndex)
# ---------------------------------------------------------------------------
#
# The serving layout: the CurveIndex's sorted arrays are split into
# contiguous chunks over the mesh axis (shard rank = curve rank, the same
# layout `distributed_partition` produces), with chunk boundaries cut at
# KEY-RUN boundaries (a run of equal keys never spans two chunks — the
# DistributedQueryEngine places chunks this way). A query batch arriving
# sharded P(axis), with its curve keys precomputed by the caller
# (`curve_index.query_keys` — coordinate quantization for point-keyed
# indexes, the kd-tree root→leaf walk for tree-backed ones), is answered
# with exactly two all_to_all exchanges:
#
#   1. find each local query's *owner* shard by binary search over the
#      shards' first keys (one tiny all_gather) and exchange query
#      coordinates + keys to owners;
#   2. owners answer locally against their chunk (point location: exact
#      key-run scan; kNN: curve-window candidate scan, distances + ids
#      bit-packed into one reply buffer) and the answers ride the reverse
#      all_to_all back in the mirrored lane layout — each source shard
#      gathers its results at [owner, staged position] locally, so no
#      slot ids are ever exchanged.
#
# Because queries arrive pre-keyed, the kernels never touch the
# quantization frame: tree-backed indexes (bucket keys addressed by a
# tree walk the kernel could not run) shard into exactly the same layout
# as point-keyed ones.
#
# Per-(src,dst) lane capacity is a static parameter. At the default
# (``lane_cap=None`` → the local query count) routing can never drop a
# query regardless of skew. A production engine provisions smaller lanes
# (memory ∝ nshards * lane_cap): the kernels then also return each row's
# staged lane position so the caller can detect overflow (``pos >= cap``
# means the row was dropped at the hot owner's lane) and re-dispatch only
# the dropped rows next round — skew degrades into extra rounds, never
# into wrong answers. Run-aligned chunking makes the key-run scan exact
# (a miss is certified iff the run fits ``bucket_cap``, identical to the
# single-host semantics); kNN windows clipped at a chunk seam cost a
# little recall there — the same CUTOFF economics as the local path.


def _exchange(x, axis):
    """Lane s of my buffer -> shard s (flattened on receive)."""
    r = jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=False)
    return r.reshape((-1,) + r.shape[2:])


def _answer_pl(pts_loc, ids_loc, keys_loc, rq, rqk, bucket_cap):
    """Exact point location of routed queries against the local chunk;
    (r, 3) int32 columns (found, id, ok). Shared by the flat and
    two-level serving kernels."""
    n_loc = keys_loc.shape[0]
    lo_i = jnp.searchsorted(keys_loc, rqk, side="left").astype(jnp.int32)
    hi_i = jnp.searchsorted(keys_loc, rqk, side="right").astype(jnp.int32)
    offs = jnp.arange(bucket_cap, dtype=jnp.int32)
    pos = lo_i[:, None] + offs[None, :]
    cand = jnp.clip(pos, 0, n_loc - 1)
    hit = jnp.all(pts_loc[cand] == rq[:, None, :], axis=-1) & (pos < hi_i[:, None])
    found = jnp.any(hit, axis=1)
    slot = jnp.argmax(hit, axis=1)
    gid = ids_loc[cand[jnp.arange(rq.shape[0]), slot]]
    # run-aligned chunking guarantees the whole key-equal run lives in
    # this chunk, so [lo_i, hi_i) is the query's GLOBAL run and the miss
    # certificate is identical to queries._point_location's
    ok = found | ((hi_i - lo_i) <= bucket_cap)
    return jnp.stack(
        [found.astype(jnp.int32), jnp.where(found, gid, -1), ok.astype(jnp.int32)],
        axis=-1,
    )


def _answer_knn(pts_loc, ids_loc, keys_loc, rq, rqk, k, win):
    """kNN candidate-window scan of routed queries against the local
    chunk; distances + bit-cast ids packed into one (r, 2k) reply buffer
    so each serving round stays at one reply exchange per routing hop."""
    from repro.core import curve_index as _ci

    n_loc = keys_loc.shape[0]
    pos0 = jnp.searchsorted(keys_loc, rqk, side="left").astype(jnp.int32)
    start = jnp.clip(pos0 - win // 2, 0, jnp.maximum(n_loc - win, 0))
    offs = jnp.arange(win, dtype=jnp.int32)
    pos = start[:, None] + offs[None, :]
    cand = jnp.clip(pos, 0, n_loc - 1)
    # pos < n_loc: when win exceeds the chunk, clipped indices repeat —
    # without the bound one point could fill several of the k slots
    valid = (pos < n_loc) & (keys_loc[cand] != jnp.uint32(_ci.KEY_SENTINEL))
    d2 = jnp.sum((pts_loc[cand] - rq[:, None, :]) ** 2, axis=-1)
    d2 = jnp.where(valid, d2, jnp.inf)
    neg_top, top_i = jax.lax.top_k(-d2, k)
    gids = ids_loc[jnp.take_along_axis(cand, top_i, axis=1)]
    gids = jnp.where(jnp.isfinite(-neg_top), gids, -1)
    dist = jnp.sqrt(jnp.maximum(-neg_top, 0.0))
    return jnp.concatenate(
        [dist, jax.lax.bitcast_convert_type(gids, jnp.float32)], axis=1
    )


@functools.lru_cache(maxsize=32)
def _query_serve_fn(
    mesh: Mesh,
    axis: str,
    mode: str,          # "pl" | "knn"
    k: int,
    bucket_cap: int,
    win: int,
    cap: int,           # per-(src,dst) lane capacity (rows)
):
    """Jitted two-all_to_all query-serving executor, memoized per static
    config (shard_map must run under jit — see partitioner._reslice_fn)."""
    from repro.core import curve_index as _ci
    from repro.core import migration as _migration

    nshards = mesh.shape[axis]

    def kernel(pts_loc, ids_loc, keys_loc, q_loc, qk):
        # owner shard: last shard whose first key <= qk
        firsts = jax.lax.all_gather(keys_loc[0], axis)          # (nshards,)
        owner = _ci.owner_from_firsts(firsts, qk)
        (buf_q, buf_k), pos_of = _migration.stage_rows_by_dest(
            owner, (q_loc, qk), nshards, cap, (0.0, _ci.KEY_SENTINEL)
        )
        rq = _exchange(buf_q, axis)                              # (nshards*cap, d)
        rqk = _exchange(buf_k, axis)
        # answers come back in the mirrored lane layout, so each source
        # shard gathers its own results at [owner, pos] locally — no slot
        # ids travel in either direction. Rows with pos_of >= cap were
        # dropped at staging (lane overflow): the gather is clamped and
        # the caller masks them out and re-dispatches.

        def reply(ans):                                          # (r, c) -> (q_loc, c)
            back = jax.lax.all_to_all(
                ans.reshape(nshards, cap, -1), axis,
                split_axis=0, concat_axis=0, tiled=False,
            )
            return back[owner, jnp.minimum(pos_of, cap - 1)]

        if mode == "pl":
            return reply(_answer_pl(pts_loc, ids_loc, keys_loc, rq, rqk, bucket_cap)), pos_of
        got = reply(_answer_knn(pts_loc, ids_loc, keys_loc, rq, rqk, k, win))
        return got[:, :k], jax.lax.bitcast_convert_type(got[:, k:], jnp.int32), pos_of

    out_specs = (P(axis), P(axis)) if mode == "pl" else (P(axis), P(axis), P(axis))
    return jax.jit(jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=out_specs,
        check_vma=False,
    ))


@functools.lru_cache(maxsize=32)
def _query_serve_fn_2d(
    mesh: Mesh,
    node_axis: str,
    device_axis: str,
    mode: str,          # "pl" | "knn"
    k: int,
    bucket_cap: int,
    win: int,
    cap: int,           # per-(src,dst) inter-node lane capacity (rows)
):
    """Two-level (key -> node -> device) query-serving executor.

    The flat kernel routes every query through one all_to_all whose lanes
    span all ``N*D`` shards — every mis-owned query may cross the node
    boundary. Here routing is hierarchical, mirroring the directory:

      1. **inter-node hop** — owner *node* by binary search over the N
         node first-keys; one all_to_all over the node axis (N lanes).
         Queries already on their owner node ride the self-lane, which
         never leaves the node.
      2. **node-local lookup** — ON the owner node, the owner *device*
         by search over the node's D device first-keys; one all_to_all
         over the device axis only. This stage (and its reply) is pure
         intra-node traffic.

    Answers retrace both hops through the mirrored-lane gathers, so slot
    ids never travel. Owner shards are identical to the flat kernel's
    (`curve_index.owner_from_firsts` applied per level over globally
    sorted firsts), hence so are the answers.

    Lane overflow can only happen at hop 1 (``cap`` rows per node lane):
    hop 2 sizes its device lanes at ``s_node * cap`` — the whole incoming
    buffer — so a staged query is never dropped intra-node. The returned
    positions are therefore hop-1 positions, interpreted exactly like the
    flat kernel's (``pos >= cap`` → dropped, re-dispatch).
    """
    from repro.core import curve_index as _ci
    from repro.core import migration as _migration

    s_node = mesh.shape[node_axis]
    s_dev = mesh.shape[device_axis]
    axes = (node_axis, device_axis)

    def kernel(pts_loc, ids_loc, keys_loc, q_loc, qk):
        firsts_dev = jax.lax.all_gather(keys_loc[0], device_axis)   # (S_d,) my node
        node_firsts = jax.lax.all_gather(firsts_dev[0], node_axis)  # (S_n,)
        # --- hop 1: inter-node (N lanes; self-lane stays on-node) ---------
        owner_node = _ci.owner_from_firsts(node_firsts, qk)
        (buf_q, buf_k), pos_a = _migration.stage_rows_by_dest(
            owner_node, (q_loc, qk), s_node, cap, (0.0, _ci.KEY_SENTINEL)
        )
        rq1 = _exchange(buf_q, node_axis)                   # (S_n*cap, d)
        rqk1 = _exchange(buf_k, node_axis)
        # --- hop 2: node-local device lookup (intra-node only) ------------
        owner_dev = _ci.owner_from_firsts(firsts_dev, rqk1)
        cap2 = s_node * cap
        (buf2, buf2k), pos_b = _migration.stage_rows_by_dest(
            owner_dev, (rq1, rqk1), s_dev, cap2, (0.0, _ci.KEY_SENTINEL)
        )
        rq = _exchange(buf2, device_axis)                   # (S_d*cap2, d)
        rqk = _exchange(buf2k, device_axis)

        def reply(ans):                                     # (S_d*cap2, c) -> (q_loc, c)
            back_b = jax.lax.all_to_all(
                ans.reshape(s_dev, cap2, -1), device_axis,
                split_axis=0, concat_axis=0, tiled=False,
            )[owner_dev, pos_b]                             # (cap2, c) on owner node
            back_a = jax.lax.all_to_all(
                back_b.reshape(s_node, cap, -1), node_axis,
                split_axis=0, concat_axis=0, tiled=False,
            )
            return back_a[owner_node, jnp.minimum(pos_a, cap - 1)]

        if mode == "pl":
            return reply(_answer_pl(pts_loc, ids_loc, keys_loc, rq, rqk, bucket_cap)), pos_a
        got = reply(_answer_knn(pts_loc, ids_loc, keys_loc, rq, rqk, k, win))
        return got[:, :k], jax.lax.bitcast_convert_type(got[:, k:], jnp.int32), pos_a

    spec = P(axes)
    out_specs = (spec, spec) if mode == "pl" else (spec, spec, spec)
    return jax.jit(jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec),
        out_specs=out_specs,
        check_vma=False,
    ))


def _serve_cap(mesh: Mesh, axis, n_rows: int, lane_cap: "int | None") -> int:
    """Effective per-lane capacity: the local query count (no-drop
    worst-case sizing) clipped to the caller's provisioned ``lane_cap``."""
    axes = axis if isinstance(axis, tuple) else (axis,)
    nshards = 1
    for a in axes:
        nshards *= mesh.shape[a]
    qcap = max(1, n_rows // nshards)
    return qcap if lane_cap is None else max(1, min(int(lane_cap), qcap))


def serve_point_location(
    mesh: Mesh,
    axis: "str | tuple[str, str]",
    pts_s: jax.Array,
    ids_s: jax.Array,
    keys_s: jax.Array,
    queries: jax.Array,
    qkeys: jax.Array,
    *,
    bucket_cap: int = 64,
    lane_cap: "int | None" = None,
) -> tuple[jax.Array, jax.Array, int]:
    """Distributed exact point location. ``queries`` (Q, d) and their
    precomputed curve keys ``qkeys`` (Q,) uint32 sharded over ``axis``,
    Q divisible by the shard count; returns ((Q, 3) int32 columns
    (found, id, ok), (Q,) staged lane positions, effective lane cap).
    Rows with ``pos >= cap`` overflowed their owner's lane and carry
    garbage — re-dispatch them. A ``(node_axis, device_axis)`` tuple
    routes hierarchically (key -> node -> device; see
    `_query_serve_fn_2d`) — answers are identical to the flat routing on
    the same chunk layout."""
    cap = _serve_cap(mesh, axis, queries.shape[0], lane_cap)
    if isinstance(axis, tuple):
        fn = _query_serve_fn_2d(mesh, *axis, "pl", 0, bucket_cap, 0, cap)
    else:
        fn = _query_serve_fn(mesh, axis, "pl", 0, bucket_cap, 0, cap)
    res, pos = fn(pts_s, ids_s, keys_s, queries, qkeys)
    return res, pos, cap


def serve_knn(
    mesh: Mesh,
    axis: "str | tuple[str, str]",
    pts_s: jax.Array,
    ids_s: jax.Array,
    keys_s: jax.Array,
    queries: jax.Array,
    qkeys: jax.Array,
    *,
    k: int = 3,
    win: int = 192,
    lane_cap: "int | None" = None,
) -> tuple[jax.Array, jax.Array, jax.Array, int]:
    """Distributed approximate kNN over the sharded curve. Returns
    ((Q, k) distances, (Q, k) ids, (Q,) lane positions, effective lane
    cap); invalid slots inf/-1, rows with ``pos >= cap`` dropped at the
    owner lane (re-dispatch). A ``(node_axis, device_axis)`` tuple routes
    hierarchically, as in `serve_point_location`."""
    cap = _serve_cap(mesh, axis, queries.shape[0], lane_cap)
    if isinstance(axis, tuple):
        fn = _query_serve_fn_2d(mesh, *axis, "knn", k, 0, win, cap)
    else:
        fn = _query_serve_fn(mesh, axis, "knn", k, 0, win, cap)
    d, g, pos = fn(pts_s, ids_s, keys_s, queries, qkeys)
    return d, g, pos, cap
