"""SpMM Pallas kernels — the paper's aggregation engine, MXU-native.

The FPGA aggregates with scalar MAC chains over COO edges streamed from the
Neighbor FIFO (paper §4.2).  A TPU has no efficient scalar scatter-add; the
hardware-codesign move is to *densify per chunk* so aggregation uses exactly
the same compute unit as combination — the paper's unified
aggregation+combination engine argument (§5.4), transplanted to the MXU.

Two kernel families live here:

**COO (legacy reference arm)** — an edge chunk of E edges against a dst
tile of R rows becomes two one-hot matmuls:

    G   = onehot(cols)  @ X_tile          # [E, S] @ [S, bd]  — the gather
    acc += (onehot(rows) * vals) @ G      # [R, E] @ [E, bd]  — the scatter-add

Simple, bit-faithful to the segment-sum order — but the gather one-hot
spans the WHOLE source shard per edge chunk (dense FLOPs ∝ e·n_src·d) and
``x`` is staged whole-shard into VMEM, which cannot scale past toy shards.

**Pre-reduced ELL (the hot path)** — :mod:`repro.kernels.edgeplan`
materializes the Block-Message merge (§4.3.3's Reduced Register File) as
padded per-row tables of (source, weight) pairs.  The kernel walks them
with the SOURCE dimension tiled:

    S[r, s] = Σ_k  vals[r, k] · [cols[r, k] == tile_start + s]   # VPU
    acc    += S @ X_tile                  # [br, bs] @ [bs, bd]  — MXU

One matmul per (row-tile, src-tile, feat-tile): total MXU FLOPs are
n_rows·n_src·d — the dense-adjacency bound, independent of nnz AND of the
ELL padding — and the scatter one-hot is gone entirely (the reduction over
the degree axis happens in the merge matrix S).  Entries outside the
current source tile simply never match the tile-local iota, so src tiling
is free; padding entries point at the plan's dedicated zero row and carry
weight 0.  The transpose walk (:func:`spmm_ell_t`) is the SAME kernel over
the plan's column-major tables — the kernel-level transpose-free backward.

Index arrays arrive ≥2-D (TPU layout); fp32 accumulator tiles live in VMEM
scratch across the innermost grid axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: The engine's fp32 contract (``EngineConfig.precision``): every matmul of a
#: step — these kernels' and the engine's combination ``h @ W`` — runs at
#: full f32 precision.  The TPU default for f32 operands is one bf16 pass,
#: which put the ELL kernel 2.5e-3 (relative) off a float32 reference on a
#: TPU v5e.
F32_MATMUL = jax.lax.Precision.HIGHEST


def _out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """A kernel's output type: varying over every mesh axis an operand
    varies over, so the call type-checks inside ``shard_map`` (whose
    ``check_vma`` needs it); outside one the set is empty."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


# ---------------------------------------------------------------------------
# COO family (legacy reference arm) — one kernel body, two grid layouts.
# ---------------------------------------------------------------------------
def _spmm_coo_kernel(rows_ref, cols_ref, vals_ref, x_ref, o_ref, acc_ref, *,
                     n_e: int, n_rows: int, n_src: int, edge_axis: int):
    """Shared COO body: dual one-hot matmuls over one edge chunk.

    ``edge_axis`` is the grid axis that walks edge chunks (the innermost
    one); ``n_rows`` is the scatter one-hot's row extent — the whole
    destination range for the flat layout, one core's Aggregate Buffer
    (``dpc`` rows, block-local offsets) for the Block-Message layout.
    """
    @pl.when(pl.program_id(edge_axis) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = rows_ref[0, :]                       # [be] int32
    cols = cols_ref[0, :]
    vals = vals_ref[0, :]                       # [be] f32 (0 = padding)
    be = rows.shape[0]
    x = x_ref[...]                              # [n_src, bd] VMEM tile

    # gather via one-hot matmul: G[e, :] = x[cols[e], :]; out-of-range cols
    # (the wrappers' padding routes them past n_src) match no one-hot column
    # and gather nothing at all.
    src_iota = jax.lax.broadcasted_iota(jnp.int32, (be, n_src), 1)
    onehot_src = (src_iota == cols[:, None]).astype(x.dtype)
    g = jnp.dot(onehot_src, x, precision=F32_MATMUL,
                preferred_element_type=jnp.float32)

    # scatter-add via one-hot matmul, edge weights folded into the one-hot
    dst_iota = jax.lax.broadcasted_iota(jnp.int32, (n_rows, be), 0)
    onehot_dst = jnp.where(dst_iota == rows[None, :], vals[None, :], 0.0)
    acc_ref[...] += jnp.dot(onehot_dst.astype(jnp.float32), g,
                            precision=F32_MATMUL,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(edge_axis) == n_e - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("dpc", "bd", "be", "interpret"))
def spmm_block(rows: jnp.ndarray, cols: jnp.ndarray, vals: jnp.ndarray,
               x: jnp.ndarray, dpc: int, *, bd: int = 128, be: int = 256,
               interpret: bool = False) -> jnp.ndarray:
    """Block-layout SpMM: ``y[b*dpc + r] += v * x[c]`` over per-destination-
    block COO tiles (:class:`repro.core.blockmsg.BlockTiles` arrays).

    ``rows``/``cols``/``vals``: [n_blocks, e_blk] with block-local row
    offsets in ``[0, dpc)``; ``x``: the sender's dense [n_src, d] feature
    shard.  Returns [n_blocks * dpc, d] — tile *b* is the partial rows this
    sender contributes to destination core *b*, ready for the hypercube
    fold.  ``e_blk`` and ``d`` must be multiples of (be, bd); pad edges with
    val=0 (:func:`repro.kernels.ops.spmm_block` absorbs the padding).
    """
    n_blocks, e_blk = rows.shape
    n_src, d = x.shape
    if e_blk % be or d % bd:
        raise ValueError(
            f"e_blk={e_blk}, d={d} not divisible by (be={be}, bd={bd})")
    grid = (n_blocks, d // bd, e_blk // be)
    kernel = functools.partial(_spmm_coo_kernel, n_e=grid[2], n_rows=dpc,
                               n_src=n_src, edge_axis=2)
    # edge tiles ride as [n_blocks, 1, e_blk] with the block axis squeezed:
    # a (1, be) block over [n_blocks, e_blk] breaks the TPU's (8, 128)
    # tiling rule, a (1, be) block over a unit middle axis does not
    edge_spec = pl.BlockSpec((None, 1, be), lambda i, j, k: (i, 0, k))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            edge_spec, edge_spec, edge_spec,
            pl.BlockSpec((n_src, bd), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((dpc, bd), lambda i, j, k: (i, j)),
        out_shape=_out_struct((n_blocks * dpc, d), x.dtype, rows, cols, vals,
                              x),
        scratch_shapes=[pltpu.VMEM((dpc, bd), jnp.float32)],
        interpret=interpret,
    )(rows.reshape(n_blocks, 1, e_blk).astype(jnp.int32),
      cols.reshape(n_blocks, 1, e_blk).astype(jnp.int32),
      vals.reshape(n_blocks, 1, e_blk).astype(jnp.float32), x)


@functools.partial(jax.jit, static_argnames=("n_dst", "bd", "be", "interpret"))
def spmm(rows: jnp.ndarray, cols: jnp.ndarray, vals: jnp.ndarray,
         x: jnp.ndarray, n_dst: int, *, bd: int = 128, be: int = 256,
         interpret: bool = False) -> jnp.ndarray:
    """``y[r] += v * x[c]`` over a COO edge list, y: [n_dst, d].

    ``n_dst`` is one core-block's row count (the Aggregate Buffer size);
    ``x`` is the VMEM-resident dense source block.  Edge count and feature
    dim must be multiples of (be, bd) — pad edges with val=0.
    """
    e = rows.shape[0]
    n_src, d = x.shape
    if e % be or d % bd:
        raise ValueError(f"e={e}, d={d} not divisible by (be={be}, bd={bd})")
    grid = (d // bd, e // be)
    kernel = functools.partial(_spmm_coo_kernel, n_e=grid[1], n_rows=n_dst,
                               n_src=n_src, edge_axis=1)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, be), lambda j, k: (0, k)),
            pl.BlockSpec((1, be), lambda j, k: (0, k)),
            pl.BlockSpec((1, be), lambda j, k: (0, k)),
            pl.BlockSpec((n_src, bd), lambda j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((n_dst, bd), lambda j, k: (0, j)),
        out_shape=_out_struct((n_dst, d), x.dtype, rows, cols, vals, x),
        scratch_shapes=[pltpu.VMEM((n_dst, bd), jnp.float32)],
        interpret=interpret,
    )(rows.reshape(1, e).astype(jnp.int32),
      cols.reshape(1, e).astype(jnp.int32),
      vals.reshape(1, e).astype(jnp.float32), x)


# ---------------------------------------------------------------------------
# Pre-reduced ELL family (the hot path): src-tiled, scatter-free.
# ---------------------------------------------------------------------------
#: Degree-axis tile of the ELL kernel: a bucket wider than this is walked
#: in ``ELL_BK``-wide slices along an innermost grid axis (the wrapper pads
#: K up to a multiple), so a hub bucket never stages its whole [br, K]
#: table in VMEM (a K = 512 bucket was refused VMEM on a v5e).  128 is the
#: lane width: a block that is not the whole bucket must be a multiple.
ELL_BK = 128


def _spmm_ell_kernel(cols_ref, vals_ref, x_ref, o_ref, acc_ref, merge_ref, *,
                     n_s: int, n_k: int, bs: int, kc: int = 16):
    """Gather-accumulate over one ELL row tile × one source tile × one
    degree slice.

    Builds the merge matrix S[r, s] = Σ_k vals[r,k]·[cols[r,k] == s_global]
    on the VPU (the Reduced Register File fold), summed over the degree
    slices into ``merge_ref``; after the last slice a single MXU matmul
    S @ X accumulates into ``acc_ref``.  Entries whose column lies outside
    this source tile never match the tile-local iota — source tiling costs
    nothing.  Padding entries carry weight 0 AND point at the plan's
    dedicated zero row, so they are no-ops twice over.

    Within a slice the degree axis is folded in static chunks of ``kc`` so
    the one-hot intermediate is [br, ≤kc, bs].
    """
    s_idx, k_idx = pl.program_id(2), pl.program_id(3)

    @pl.when((s_idx == 0) & (k_idx == 0))
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(k_idx == 0)
    def _init_merge():
        merge_ref[...] = jnp.zeros_like(merge_ref)

    cols = cols_ref[...]                        # [br, bk] int32
    vals = vals_ref[...]                        # [br, bk] f32 (0 = padding)
    local = cols - s_idx * bs                   # tile-local column ids
    br, bk = cols.shape
    merge = merge_ref[...]                      # [br, bs] — scatter-free
    for k0 in range(0, bk, kc):
        lc = local[:, k0:k0 + kc]
        lv = vals[:, k0:k0 + kc]
        s_iota = jax.lax.broadcasted_iota(
            jnp.int32, (br, lc.shape[1], bs), 2)
        merge += jnp.where(s_iota == lc[:, :, None], lv[:, :, None],
                           0.0).sum(axis=1)
    merge_ref[...] = merge

    @pl.when(k_idx == n_k - 1)
    def _matmul():
        acc_ref[...] += jnp.dot(merge_ref[...],
                                x_ref[...].astype(jnp.float32),
                                precision=F32_MATMUL,
                                preferred_element_type=jnp.float32)

    @pl.when((s_idx == n_s - 1) & (k_idx == n_k - 1))
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("br", "bd", "bs", "interpret"))
def spmm_ell(cols: jnp.ndarray, vals: jnp.ndarray, x: jnp.ndarray, *,
             br: int = 128, bd: int = 128, bs: int = 128,
             interpret: bool = False) -> jnp.ndarray:
    """Pre-reduced ELL SpMM: ``y[r] = Σ_k vals[r, k] · x[cols[r, k]]``.

    ``cols``/``vals``: [nb, K] one degree bucket of an
    :class:`repro.kernels.edgeplan.EllTables`; ``x``: [n_src_p, d] with the
    plan's dedicated zero row included.  Grid = (nb/br, d/bd, n_src_p/bs,
    K/bk) with the SOURCE and then the DEGREE axis innermost — only a
    [bs, bd] tile of ``x`` and a [br, bk] slice of the tables are resident
    per step (bk = min(K, ``ELL_BK``)), so the kernel scales past
    whole-shard VMEM staging and past hub buckets of any width.  All of
    nb, d, n_src_p must be tile multiples and K ≤ ``ELL_BK`` or a multiple
    of it (:func:`repro.kernels.ops.spmm_ell` absorbs padding).
    """
    nb, K = cols.shape
    n_src_p, d = x.shape
    bk = min(K, ELL_BK)
    if nb % br or d % bd or n_src_p % bs or K % bk:
        raise ValueError(f"nb={nb}, d={d}, n_src={n_src_p}, K={K} not "
                         f"divisible by (br={br}, bd={bd}, bs={bs}, "
                         f"bk={bk})")
    grid = (nb // br, d // bd, n_src_p // bs, K // bk)
    kernel = functools.partial(_spmm_ell_kernel, n_s=grid[2], n_k=grid[3],
                               bs=bs)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, bk), lambda i, j, k, kk: (i, kk)),
            pl.BlockSpec((br, bk), lambda i, j, k, kk: (i, kk)),
            pl.BlockSpec((bs, bd), lambda i, j, k, kk: (k, j)),
        ],
        out_specs=pl.BlockSpec((br, bd), lambda i, j, k, kk: (i, j)),
        out_shape=_out_struct((nb, d), x.dtype, cols, vals, x),
        scratch_shapes=[pltpu.VMEM((br, bd), jnp.float32),
                        pltpu.VMEM((br, bs), jnp.float32)],
        interpret=interpret,
    )(cols.astype(jnp.int32), vals.astype(jnp.float32), x)


def spmm_ell_t(t_cols: jnp.ndarray, t_vals: jnp.ndarray, e: jnp.ndarray, *,
               br: int = 128, bd: int = 128, bs: int = 128,
               interpret: bool = False) -> jnp.ndarray:
    """Transpose-free backward walk: ``dx[c] = Σ_k t_vals[c, k]·e[t_cols[c, k]]``.

    The SAME gather-accumulate kernel as :func:`spmm_ell`, fed the plan's
    column-major (Graph Converter order) tables — ``Aᵀ e`` as a kernel, with
    no ``Aᵀ`` table, no transposed error copy, and no segment-sum scatter.
    """
    return spmm_ell(t_cols, t_vals, e, br=br, bd=bd, bs=bs,
                    interpret=interpret)
