"""Distributed graph aggregation — the paper's NUMA + hypercube NoC on TPU.

Placement (paper §4.1): node features are row-sharded over the ``model`` mesh
axis — device *i* is core *i* and owns its rows' HBM exclusively (NUMA, no
global addressing).  Each device also owns the edge blocks whose *sources*
live on it (column *i* of the block grid): senders know their outgoing
messages, exactly like the Block-Message buffers sit in the source core.

Aggregation then runs in two stages inside ``shard_map``:

  1. **Local pre-reduction** (the Index Compressor / Reduced Register File):
     each device segment-sums its own sources into partial rows for *every*
     destination core — a single SpMM against the local feature shard.  The
     wire will carry one partial row per (block, aggregate-slot), never raw
     neighbor rows: this is the paper's N ≤ nnz compression.

  2. **Topology exchange** (:mod:`repro.topology`): the partial row-blocks
     fold down to their owner cores over the engine's configured
     interconnect — the ``log₂P`` dimension-ordered hypercube (the default
     and the fp32 oracle schedule), a ring, a dense all-pairs reference, or
     the paper's orthogonal 2-D torus.  The exchange loops that used to
     live inline here are the registered :class:`~repro.topology.Topology`
     objects' ``reduce_scatter``/``allgather``/``fold_pipelined`` plans;
     :func:`hypercube_reduce_scatter` and friends remain as delegating
     shims over :mod:`repro.topology.hypercube`.

The backward pass is the paper's Table-1 redesign, distributed: a
``custom_vjp`` runs the *mirror* schedule — all-gather the error rows over
the SAME topology (the transpose of its reduce-scatter) and walk the SAME
local edge table column-major (``Aᵀ`` without an ``Aᵀ``) — so no
transposed feature matrix, no second edge table, and no transposed
exchange schedule exist on any device, whatever the interconnect.

A UMA/SMP baseline (:func:`uma_aggregate`) does what the paper argues
against: all-gather raw features everywhere, aggregate redundantly, discard.
The roofline benchmark counts both schedules' collective bytes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blockmsg import block_tiles
from repro.cotangents import zero_ct
from repro.graph.coo import COO
from repro.graph.partition import block_partition


def _topo(name: str):
    # lazy: aggregate ← engine ← topology all import each other at module
    # level somewhere along the chain; at trace time everything is fully
    # initialized and repro.topology.base owns the one lookup path
    from repro.topology.base import _topo as lookup
    return lookup(name)


# ---------------------------------------------------------------------------
# Hypercube collective shims — canonical implementations moved to
# repro.topology.hypercube (the Topology registry owns the exchange plans);
# these names stay for the callers/tests that predate the topology axis.
# ---------------------------------------------------------------------------
def hypercube_reduce_scatter(partial: jnp.ndarray, axis_name: str,
                             ndim: int) -> jnp.ndarray:
    """Delegates to :func:`repro.topology.hypercube.hypercube_reduce_scatter`."""
    from repro.topology.hypercube import hypercube_reduce_scatter as f
    return f(partial, axis_name, ndim)


def hypercube_allgather(x: jnp.ndarray, axis_name: str, ndim: int
                        ) -> jnp.ndarray:
    """Delegates to :func:`repro.topology.hypercube.hypercube_allgather`."""
    from repro.topology.hypercube import hypercube_allgather as f
    return f(x, axis_name, ndim)


def hypercube_reduce_scatter_pipelined(partial: jnp.ndarray, axis_name: str,
                                       ndim: int, n_chunks: int = 2
                                       ) -> jnp.ndarray:
    """Delegates to
    :func:`repro.topology.hypercube.hypercube_reduce_scatter_pipelined`."""
    from repro.topology.hypercube import (
        hypercube_reduce_scatter_pipelined as f)
    return f(partial, axis_name, ndim, n_chunks)


def hypercube_allgather_pipelined(x: jnp.ndarray, axis_name: str, ndim: int,
                                  n_chunks: int = 2) -> jnp.ndarray:
    """Delegates to
    :func:`repro.topology.hypercube.hypercube_allgather_pipelined`."""
    from repro.topology.hypercube import hypercube_allgather_pipelined as f
    return f(x, axis_name, ndim, n_chunks)


# ---------------------------------------------------------------------------
# Per-device edge shards (host-side build, done once per minibatch).
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class EdgeShards:
    """Sender-side edge blocks, stacked per source core and padded.

    rows_global: [P, e_max] int32 — destination id in GLOBAL row numbering
                 (owner core × tile + slot; Fig. 7's A·64+B).
    cols_local:  [P, e_max] int32 — source slot on the owning device (D).
    vals:        [P, e_max] f32   — Ã weights (0 = padding).
    """

    rows_global: np.ndarray
    cols_local: np.ndarray
    vals: np.ndarray
    n_dst: int
    n_src: int
    n_cores: int

    @property
    def dst_per_core(self) -> int:
        return self.n_dst // self.n_cores

    @property
    def src_per_core(self) -> int:
        return self.n_src // self.n_cores


def shard_edges(coo: COO, n_cores: int,
                e_max: Optional[int] = None) -> EdgeShards:
    """Partition a (padded) COO by SOURCE core — column stripes of the block
    grid — and pad each device's edge list to a common static length."""
    blocked = block_partition(coo, n_cores)
    spc = blocked.src_per_core
    dpc = blocked.dst_per_core
    per_core: list = [[] for _ in range(n_cores)]
    for (i, j), (lr, lc, v) in blocked.block_edges.items():
        per_core[j].append((lr.astype(np.int64) + i * dpc, lc, v))
    if e_max is None:
        e_max = max((sum(len(t[0]) for t in lst) for lst in per_core),
                    default=1)
        e_max = max(int(e_max), 1)
    rows = np.zeros((n_cores, e_max), np.int32)
    cols = np.zeros((n_cores, e_max), np.int32)
    vals = np.zeros((n_cores, e_max), np.float32)
    for j, lst in enumerate(per_core):
        if not lst:
            continue
        r = np.concatenate([t[0] for t in lst])
        c = np.concatenate([t[1] for t in lst])
        v = np.concatenate([t[2] for t in lst])
        if len(r) > e_max:
            raise ValueError(f"core {j} has {len(r)} edges > e_max={e_max}")
        rows[j, :len(r)] = r
        cols[j, :len(c)] = c
        vals[j, :len(v)] = v
    return EdgeShards(rows_global=rows, cols_local=cols, vals=vals,
                      n_dst=coo.n_dst, n_src=coo.n_src, n_cores=n_cores)


# ---------------------------------------------------------------------------
# The distributed aggregate, with the paper's backward dataflow (custom_vjp).
# Shapes inside shard_map (per device): x_local [spc, d] -> y_local [dpc, d].
# ---------------------------------------------------------------------------
def _local_partials(rows_g, cols_l, vals, x_local, n_dst):
    """Stage 1: partial rows for every destination core from local sources."""
    gathered = x_local[cols_l] * vals[:, None]
    return jax.ops.segment_sum(gathered, rows_g, num_segments=n_dst)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _hypercube_aggregate(axis_name: str, ndim: int, n_dst: int,
                         topology: str, rows_g, cols_l, vals, x_local):
    n_cores = 1 << ndim
    partial = _local_partials(rows_g, cols_l, vals, x_local, n_dst)
    partial = partial.reshape(n_cores, n_dst // n_cores, -1)
    return _topo(topology).reduce_scatter(partial, axis_name, n_cores)


def _hyper_fwd(axis_name, ndim, n_dst, topology, rows_g, cols_l, vals,
               x_local):
    y = _hypercube_aggregate(axis_name, ndim, n_dst, topology, rows_g,
                             cols_l, vals, x_local)
    return y, (rows_g, cols_l, vals, x_local)


def _hyper_bwd(axis_name, ndim, n_dst, topology, res, ct):
    rows_g, cols_l, vals, x_local = res
    # mirror schedule: error rows of ALL cores over the SAME topology
    # (the transpose of its reduce-scatter)
    e_full = _topo(topology).allgather(ct, axis_name, 1 << ndim)
    e_full = e_full.reshape(n_dst, -1)
    # Aᵀ walk of the SAME local edge table (column-major = Graph Converter):
    # dx[c] += v · e[r]  — consumes global rows, produces local cols.
    n_src_local = x_local.shape[0]
    gathered = e_full[rows_g] * vals[:, None]
    dx_local = jax.ops.segment_sum(gathered, cols_l,
                                   num_segments=n_src_local)
    # adjacency is fixed: float0 for the index arrays, zeros for the weights
    return (*zero_ct((rows_g, cols_l, vals)), dx_local)


_hypercube_aggregate.defvjp(_hyper_fwd, _hyper_bwd)


def hypercube_aggregate(axis_name: str, ndim: int, n_dst: int,
                        rows_g: jnp.ndarray, cols_l: jnp.ndarray,
                        vals: jnp.ndarray, x_local: jnp.ndarray,
                        topology: str = "hypercube") -> jnp.ndarray:
    """Per-device body: ``y_local = (A @ x)_local`` via pre-reduce + fold.

    Call inside ``shard_map`` over ``axis_name``; edge arrays are this
    device's :class:`EdgeShards` slice, ``x_local`` its feature rows.
    ``topology`` names the registered interconnect the partial rows fold
    over (default: the paper's hypercube — the historical name of this
    entry point); the backward all-gathers the error rows over the same
    topology's mirror schedule.
    """
    return _hypercube_aggregate(axis_name, ndim, n_dst, topology, rows_g,
                                cols_l, vals, x_local)


# ---------------------------------------------------------------------------
# Block-tile edge shards + the fused, double-buffered aggregate.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class BlockEdgeShards:
    """Sender-side edges in the Block-Message tile layout, stacked per core.

    Device *j* (= source core *j*) holds ``rows_local[j]``: [B, eb] int32
    block-LOCAL destination slots (Fig. 7's B values) for each of the B
    destination-core tiles, plus matching ``cols_local`` (D values, local
    source slots) and ``vals``.  This is :func:`repro.core.blockmsg.block_tiles`
    per sender, padded to a common static tile size — the layout both the
    block-layout SpMM kernel and the pipelined aggregate consume directly,
    with no global row ids and no one-hot over ``n_dst``.
    """

    rows_local: np.ndarray   # [P, B, eb] int32 — dst slot within dst block
    cols_local: np.ndarray   # [P, B, eb] int32 — source slot on the sender
    vals: np.ndarray         # [P, B, eb] f32   — Ã weights (0 = padding)
    n_dst: int
    n_src: int
    n_cores: int

    @property
    def dst_per_core(self) -> int:
        return self.n_dst // self.n_cores

    @property
    def src_per_core(self) -> int:
        return self.n_src // self.n_cores


def shard_edges_blocked(coo: COO, n_cores: int,
                        eb_max: Optional[int] = None) -> BlockEdgeShards:
    """Partition a (padded) COO into per-sender Block-Message tiles.

    Same source-core striping as :func:`shard_edges`, but each sender's
    edges stay grouped per destination-core block with block-local row
    offsets.  Edge order inside every tile is the block partition's
    (row, col) sort — identical to the flat layout's order per destination
    row, so the blocked and flat aggregation paths are fp32 bit-equal.
    """
    blocked = block_partition(coo, n_cores)
    if eb_max is None:
        eb_max = max((len(r) for (r, _, _) in blocked.block_edges.values()),
                     default=1)
        eb_max = max(int(eb_max), 1)
    tiles = [block_tiles(blocked, j, eb_max=eb_max) for j in range(n_cores)]
    return BlockEdgeShards(
        rows_local=np.stack([t.rows for t in tiles]),
        cols_local=np.stack([t.cols for t in tiles]),
        vals=np.stack([t.vals for t in tiles]),
        n_dst=coo.n_dst, n_src=coo.n_src, n_cores=n_cores)


def _local_partials_blocked(rows_b, cols_b, vals_b, x_local, dpc: int):
    """Per-destination-block partial rows: [B, eb] tiles → [B, dpc, d].

    The block-local offsets are globalized with a trace-time iota
    (tile·dpc + r) and all tiles scatter through ONE segment-sum — same
    per-row add order as both the flat layout and a per-tile walk (tiles
    are concatenated in block order), so results stay fp32 bit-equal, and
    XLA sees a single large scatter instead of a batched small one (a
    vmapped per-tile segment-sum lowers to a serialized scatter loop on
    CPU).  The Pallas twin that scatters per-tile into a [dpc, bd]
    Aggregate Buffer is :func:`repro.kernels.spmm.spmm_block`.
    """
    n_blocks = rows_b.shape[0]
    rows_g = (rows_b
              + (jnp.arange(n_blocks, dtype=rows_b.dtype) * dpc)[:, None])
    gathered = x_local[cols_b.reshape(-1)] * vals_b.reshape(-1)[:, None]
    out = jax.ops.segment_sum(gathered, rows_g.reshape(-1),
                              num_segments=n_blocks * dpc)
    return out.reshape(n_blocks, dpc, -1)


def _pipelined_fwd_impl(axis_name: str, ndim: int, n_dst: int,
                        n_chunks: int, topology: str, rows_b, cols_b,
                        vals_b, x_local):
    """Block-tile partials through the topology's fused pipelined fold.

    ``Topology.fold_pipelined`` owns the exchange: the hypercube runs the
    fused SpMM + ping-pong fold (§4.3, Fig. 9 — the first round's send is
    on the wire while the still-owned half's SpMM computes); other
    topologies default to per-wave reduce-scatters whose sends are
    independent dataflow.
    """
    n_cores = 1 << ndim
    dpc = n_dst // n_cores
    if rows_b.shape[0] != n_cores:
        # fail loudly: dynamic_slice would CLAMP an out-of-range start and
        # silently duplicate blocks into both 'mine' and 'send'
        raise ValueError(
            f"tile count {rows_b.shape[0]} != 2^ndim = {n_cores}; edge "
            "arrays must come from shard_edges_blocked on the same mesh")
    return _topo(topology).fold_pipelined(
        axis_name, n_cores, n_chunks,
        lambda xc: _local_partials_blocked(rows_b, cols_b, vals_b, xc, dpc),
        x_local)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _hypercube_aggregate_pipelined(axis_name: str, ndim: int, n_dst: int,
                                   n_chunks: int, topology: str, rows_b,
                                   cols_b, vals_b, x_local):
    return _pipelined_fwd_impl(axis_name, ndim, n_dst, n_chunks, topology,
                               rows_b, cols_b, vals_b, x_local)


def _pipe_fwd(axis_name, ndim, n_dst, n_chunks, topology, rows_b, cols_b,
              vals_b, x_local):
    y = _hypercube_aggregate_pipelined(axis_name, ndim, n_dst, n_chunks,
                                       topology, rows_b, cols_b, vals_b,
                                       x_local)
    return y, (rows_b, cols_b, vals_b, x_local)


def _pipe_bwd(axis_name, ndim, n_dst, n_chunks, topology, res, ct):
    from repro.core.gcn import _spmm_t_blocked

    rows_b, cols_b, vals_b, x_local = res
    # mirror schedule, same topology, same waves: all-gather the error rows
    e_full = _topo(topology).allgather_pipelined(ct, axis_name, 1 << ndim,
                                                 n_chunks)
    # Aᵀ walk of the SAME block tiles, column-major: tile b's error rows are
    # the contiguous slab e_full[b] — one shared implementation with the
    # single-device blocked layer.
    dx_local = _spmm_t_blocked(rows_b, cols_b, vals_b,
                               e_full.reshape(n_dst, -1), x_local.shape[0])
    return (*zero_ct((rows_b, cols_b, vals_b)), dx_local)


_hypercube_aggregate_pipelined.defvjp(_pipe_fwd, _pipe_bwd)


def default_n_chunks() -> int:
    """Backend-tuned wave count for the pipelined schedule.

    On accelerators with async collectives (TPU/GPU) two waves let the wire
    hide under MAC work; on the CPU backend collectives are synchronous
    thread barriers, so extra waves only add slice copies — one wave keeps
    the blocked layout + pipelined issue order without the copy tax.
    """
    return 2 if jax.default_backend() in ("tpu", "gpu") else 1


def hypercube_aggregate_pipelined(axis_name: str, ndim: int, n_dst: int,
                                  rows_b: jnp.ndarray, cols_b: jnp.ndarray,
                                  vals_b: jnp.ndarray, x_local: jnp.ndarray,
                                  n_chunks: Optional[int] = None,
                                  topology: str = "hypercube"
                                  ) -> jnp.ndarray:
    """Per-device body: ``y_local = (A @ x)_local`` with the double-buffered
    schedule — block-tile SpMM overlapped with the topology's fold.

    Call inside ``shard_map`` over ``axis_name``; edge arrays are this
    device's :class:`BlockEdgeShards` slice ([B, eb] tiles), ``x_local`` its
    feature rows.  On the default hypercube topology, fp32 results (and the
    custom-vjp backward) are bit-equal to :func:`hypercube_aggregate` for
    ANY wave count — only the issue order differs; other topologies reorder
    the partial-row additions and match to fp32 roundoff (≤1e-5).
    ``n_chunks=None`` picks :func:`default_n_chunks`.
    """
    if n_chunks is None:
        n_chunks = default_n_chunks()
    return _hypercube_aggregate_pipelined(axis_name, ndim, n_dst,
                                          int(n_chunks), topology, rows_b,
                                          cols_b, vals_b, x_local)


# ---------------------------------------------------------------------------
# Pre-reduced ELL edge shards + the scatter-free pipelined aggregate.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class EllEdgeShards:
    """Per-sender pre-reduced ELL plans, stacked for ``shard_map``.

    ``tables`` mirrors :meth:`repro.kernels.edgeplan.EdgePlan.device_tables`
    with every leaf stacked on a leading core axis: ``cols``/``vals`` are
    per-bucket ``[P, nb, K]`` tables over the GLOBAL partial-row space
    (``dst_core·dpc + B``) with sender-local source slots, ``inv`` is
    ``[P, n_dst]``, and the ``t_*`` leaves are the column-major mirror
    (rows = sender-local source slots, columns = global error rows).
    Bucket capacities and per-bucket row counts are shared across senders so
    every device sees identical shapes.  Built once per graph and cached.

    Redundancy-merged shards (``merge="redundancy"``) additionally carry
    the stacked ``vv_*``/``vvt_*`` pre-pass tables over a shared
    virtual-vertex pad (max across senders), and ``merge_stats`` sums the
    per-sender mining stats.
    """

    tables: Dict
    n_dst: int
    n_src: int
    n_cores: int
    merge_stats: Dict = dataclasses.field(default_factory=dict)

    @property
    def n_virtual(self) -> int:
        return int(self.merge_stats.get("n_virtual", 0))

    @property
    def pair_coverage(self) -> float:
        return float(self.merge_stats.get("pair_coverage", 0.0))

    @property
    def flop_reduction(self) -> float:
        return float(self.merge_stats.get("flop_reduction", 1.0))

    @property
    def dst_per_core(self) -> int:
        return self.n_dst // self.n_cores

    @property
    def src_per_core(self) -> int:
        return self.n_src // self.n_cores


def _stack_sender_tables(flats, n_rows: int, n_cols: int, caps) -> Dict:
    """Per-sender flat edges → shape-aligned stacked ELL tables (one
    direction).  Two passes: degrees fix the shared capacities and the
    per-bucket row pads, then every sender builds against them."""
    from repro.kernels import edgeplan

    degs = [edgeplan.merged_degrees(r, c, v, n_rows, n_cols)
            for (r, c, v) in flats]
    max_deg = max((int(d.max()) for d in degs if d.size), default=0)
    caps_t = edgeplan.resolve_caps(caps, max_deg)
    caps_arr = np.asarray(caps_t, np.int64)
    nb_pad = np.zeros(len(caps_t), np.int64)
    for d in degs:
        listed = d[d > 0]
        counts = np.bincount(np.searchsorted(caps_arr, listed, side="left"),
                             minlength=len(caps_t))
        nb_pad = np.maximum(nb_pad, counts)
    tabs = [edgeplan.build_tables(r, c, v, n_rows, n_cols, caps=caps_t,
                                  nb_pad=nb_pad.tolist())
            for (r, c, v) in flats]
    keep = [b for b in range(len(caps_t)) if nb_pad[b] > 0]
    return {
        "cols": tuple(np.stack([t.cols[b] for t in tabs]) for b in keep),
        "vals": tuple(np.stack([t.vals[b] for t in tabs]) for b in keep),
        "inv": np.stack([t.inv_perm for t in tabs]),
    }


def shard_edges_ell(coo: COO, n_cores: int, caps=None,
                    merge: str = "dedup") -> EllEdgeShards:
    """Partition a (padded) COO into per-sender pre-reduced ELL plans.

    Same source-core striping as :func:`shard_edges`, but each sender's
    edges go through the Index Compressor
    (:func:`repro.core.blockmsg.sender_merge_flat` — ``compress_block`` per
    block) and land as degree-bucketed ELL tables: the local pre-reduction
    becomes a gather + degree-axis reduction with NO segment-sum scatter,
    forward and backward.  Built once per (graph, mesh) and cached on the
    COO's identity — per-step host edge prep disappears.

    ``merge="redundancy"`` runs
    :func:`repro.kernels.edgeplan.mine_pair_redundancy` per sender AFTER
    the within-block merge, so destination rows on every core gather from
    (original ∪ virtual) sender-local sources.  Virtual ids are padded to
    the max across senders so the stacked tables stay shape-aligned;
    senders with fewer virtual vertices leave the pad rows edge-free
    (their ``inv`` fills zeros).  Degrades to the plain shards when no
    sender mines a pair.
    """
    from repro.core.blockmsg import sender_merge_flat
    from repro.kernels import edgeplan

    edgeplan.validate_merge(merge)
    if caps is None:
        from repro.kernels.tune import get_config
        caps = get_config()["caps"]
    caps_key = caps if isinstance(caps, str) else tuple(caps)

    def _build() -> EllEdgeShards:
        blocked = block_partition(coo, n_cores)
        spc = blocked.src_per_core
        fwd_flats = [sender_merge_flat(blocked, j) for j in range(n_cores)]
        merge_stats: Dict = {}
        vv_keys: Dict = {}
        if merge == "redundancy":
            mines = [edgeplan.mine_pair_redundancy(r, c, v, coo.n_dst, spc)
                     for (r, c, v) in fwd_flats]
            n_vv_pad = max(m.n_virtual for m in mines)
            if n_vv_pad:
                ext = spc + n_vv_pad
                fwd_flats = [(m.rows, m.cols, m.vals) for m in mines]
                vv_flats = [m.vv_flat() for m in mines]
                vvt_flats = [(c, r, v) for (r, c, v) in vv_flats]
                vv = _stack_sender_tables(vv_flats, n_vv_pad, spc, caps)
                vvt = _stack_sender_tables(vvt_flats, spc, n_vv_pad, caps)
                vv_keys = {"vv_cols": vv["cols"], "vv_vals": vv["vals"],
                           "vv_inv": vv["inv"], "vvt_cols": vvt["cols"],
                           "vvt_vals": vvt["vals"], "vvt_inv": vvt["inv"]}
                eb = sum(m.stats["edges_before"] for m in mines)
                ea = sum(m.stats["edges_after"] for m in mines)
                nv = sum(m.stats["n_virtual"] for m in mines)
                pu = sum(m.stats["pair_uses"] for m in mines)
                merge_stats = {
                    "edges_before": eb, "edges_after": ea, "n_virtual": nv,
                    "pair_uses": pu,
                    "pair_coverage": 2.0 * pu / max(eb, 1),
                    "flop_reduction": eb / max(ea + 2 * nv, 1),
                }
            else:
                ext = spc
        else:
            ext = spc
        bwd_flats = [(c, r, v) for (r, c, v) in fwd_flats]
        fwd = _stack_sender_tables(fwd_flats, coo.n_dst, ext, caps)
        bwd = _stack_sender_tables(bwd_flats, ext, coo.n_dst, caps)
        tables = dict(fwd)
        tables["t_cols"] = bwd["cols"]
        tables["t_vals"] = bwd["vals"]
        tables["t_inv"] = bwd["inv"]
        tables.update(vv_keys)
        return EllEdgeShards(tables=tables, n_dst=coo.n_dst,
                             n_src=coo.n_src, n_cores=n_cores,
                             merge_stats=merge_stats)

    return edgeplan.cached(
        edgeplan.coo_key(coo, "ell-shards", n_cores, caps_key, merge),
        (coo.rows, coo.cols, coo.vals), _build)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _hypercube_aggregate_ell(axis_name: str, ndim: int, n_dst: int,
                             n_chunks: int, topology: str, tables, x_local):
    from repro.kernels.ops import ell_apply

    n_cores = 1 << ndim
    dpc = n_dst // n_cores
    return _topo(topology).fold_pipelined(
        axis_name, n_cores, n_chunks,
        lambda xc: ell_apply(tables, xc).reshape(n_cores, dpc, -1),
        x_local)


def _ell_fwd(axis_name, ndim, n_dst, n_chunks, topology, tables, x_local):
    y = _hypercube_aggregate_ell(axis_name, ndim, n_dst, n_chunks, topology,
                                 tables, x_local)
    return y, tables        # aggregation is linear in x: plan-only residual


def _ell_bwd(axis_name, ndim, n_dst, n_chunks, topology, res, ct):
    from repro.kernels.ops import ell_apply

    tables = res
    with jax.named_scope("mirror_backward"):
        # mirror schedule, same topology, same waves: all-gather the error rows
        e_full = _topo(topology).allgather_pipelined(ct, axis_name,
                                                     1 << ndim, n_chunks)
        # then the column-major ELL walk of the SAME plan — scatter-free Aᵀ
        dx_local = ell_apply(tables, e_full.reshape(n_dst, -1),
                             transpose=True)
    return (zero_ct(tables), dx_local)


_hypercube_aggregate_ell.defvjp(_ell_fwd, _ell_bwd)


def hypercube_aggregate_ell(axis_name: str, ndim: int, n_dst: int,
                            tables: Dict, x_local: jnp.ndarray,
                            n_chunks: Optional[int] = None,
                            topology: str = "hypercube") -> jnp.ndarray:
    """Per-device body: ``y_local = (A @ x)_local`` through the pre-reduced
    ELL engine + the double-buffered hypercube fold.

    ``tables`` is this device's :class:`EllEdgeShards` slice (leading core
    axis already stripped).  The local pre-reduction is the sender-side
    Block-Message merge MATERIALIZED: gather + degree-axis reduction, no
    segment-sum scatter — and the backward (registered here, inherited by
    the train step) all-gathers the error in mirror order and walks the
    same plan's column-major tables with the same scatter-free kernel.
    Matches :func:`hypercube_aggregate` to fp32 roundoff (≤1e-5; the merge
    reorders additions, so bit-exactness is not the contract — the blocked
    path keeps that role).
    """
    if n_chunks is None:
        n_chunks = default_n_chunks()
    return _hypercube_aggregate_ell(axis_name, ndim, n_dst, int(n_chunks),
                                    topology, tables, x_local)


def shard_edges_by_dst(coo: COO, n_cores: int,
                       e_max: Optional[int] = None) -> EdgeShards:
    """Receiver-side partition (UMA baseline): device *i* holds the edge
    blocks whose DESTINATIONS live on it (row stripe *i*), with local row
    slots and GLOBAL column ids — it must reach into remote memory for its
    neighbors' features.  Reuses :class:`EdgeShards` with the roles of
    ``rows``/``cols`` mirrored: ``rows_global`` ← local dst slot,
    ``cols_local`` ← global src id."""
    blocked = block_partition(coo, n_cores)
    spc = blocked.src_per_core
    per_core: list = [[] for _ in range(n_cores)]
    for (i, j), (lr, lc, v) in blocked.block_edges.items():
        per_core[i].append((lr, lc.astype(np.int64) + j * spc, v))
    if e_max is None:
        e_max = max((sum(len(t[0]) for t in lst) for lst in per_core),
                    default=1)
        e_max = max(int(e_max), 1)
    rows = np.zeros((n_cores, e_max), np.int32)
    cols = np.zeros((n_cores, e_max), np.int32)
    vals = np.zeros((n_cores, e_max), np.float32)
    for i, lst in enumerate(per_core):
        if not lst:
            continue
        r = np.concatenate([t[0] for t in lst])
        c = np.concatenate([t[1] for t in lst])
        v = np.concatenate([t[2] for t in lst])
        if len(r) > e_max:
            raise ValueError(f"core {i} has {len(r)} edges > e_max={e_max}")
        rows[i, :len(r)] = r
        cols[i, :len(c)] = c
        vals[i, :len(v)] = v
    return EdgeShards(rows_global=rows, cols_local=cols, vals=vals,
                      n_dst=coo.n_dst, n_src=coo.n_src, n_cores=n_cores)


def uma_aggregate(axis_name: str, ndim: int, n_dst: int,
                  rows_l: jnp.ndarray, cols_g: jnp.ndarray,
                  vals: jnp.ndarray, x_local: jnp.ndarray) -> jnp.ndarray:
    """UMA/SMP baseline (what the paper's Fig. 1 motivates AGAINST): every
    device all-gathers the RAW feature shard — bytes ∝ n_src·d with **no
    pre-reduction compression** — then aggregates its own rows from the
    replicated copy (the shared-memory random-access pattern).  Edge arrays
    come from :func:`shard_edges_by_dst`.  Kept for the collective-bytes
    comparison benchmark (roofline's collective term)."""
    n_cores = 1 << ndim
    x_full = hypercube_allgather(x_local, axis_name, ndim)   # [P, spc, d] raw
    x_full = x_full.reshape(-1, x_local.shape[-1])
    gathered = x_full[cols_g] * vals[:, None]
    return jax.ops.segment_sum(gathered, rows_l,
                               num_segments=n_dst // n_cores)


# ---------------------------------------------------------------------------
# Collective-byte accounting (feeds the roofline's collective term).
# ---------------------------------------------------------------------------
def schedule_bytes(n_dst: int, n_src: int, d: int, n_cores: int,
                   dtype_bytes: int = 4) -> dict:
    """Wire bytes per device, both schedules (analytic, matches the HLO).

    hypercube: the reduce-scatter fold sends n_dst/2 + n_dst/4 + … + n_dst/P
    pre-reduced rows = n_dst·(1 − 1/P) — independent of nnz (that is the
    Block-Message compression).  UMA: the raw all-gather ships
    n_src·(1 − 1/P) uncompressed rows and scales with neither schedule's
    reduction — on dense-ish graphs hypercube also wins because partial rows
    replace per-edge traffic."""
    hyper = int(n_dst * (1 - 1 / n_cores)) * d * dtype_bytes
    uma = int(n_src * (1 - 1 / n_cores)) * d * dtype_bytes
    return {"hypercube_bytes_per_device": hyper,
            "uma_bytes_per_device": uma,
            "ratio": uma / max(hyper, 1)}
