"""Public jit'd wrappers around the Pallas kernels.

On TPU the kernels compile natively; everywhere else (this CPU container)
the COO/ELL Pallas kernels run in ``interpret=True`` mode, which executes
the kernel body on the Python/numpy path — same tiling, same math, no MXU.
Callers never pass ``interpret`` themselves; they get the right backend
automatically.  The pre-reduced ELL apply additionally has a pure-XLA twin
(`gather + degree-axis reduction`, no scatter) used off-TPU, where an
interpreted kernel would be a correctness tool rather than a hot path.

The wrappers also absorb tile-alignment padding so layer code can call them
on the paper's natural sizes (64-node core blocks, ragged feature dims).
Padding contract: padded edge/table entries carry ``val == 0`` AND their
column index is routed AWAY from real data — COO padding points past the
source range (one-hot matches nothing, gathers nothing), ELL padding points
at the plan's dedicated zero row.  Padding must never touch real row 0.

``ell_aggregate`` is the one place the pre-reduced engine's ``custom_vjp``
is registered: forward walks the plan's dst-major tables, backward walks
the column-major tables of the SAME edges with the SAME kernel
(transpose-free, scatter-free).  The ``ell`` engine format
(:mod:`repro.engine.formats`), ``repro.distributed.aggregate`` and the
engine train step all inherit their backward from here.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

# shared zero-cotangent helper (historical local name `_zero_ct` kept for
# existing importers)
from repro.cotangents import zero_ct as _zero_ct

from . import gemm as _gemm
from . import spmm as _spmm
from . import ref as ref  # re-export for tests/benchmarks


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: jnp.ndarray, axis: int, mult: int,
            value: float = 0) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def gemm(x: jnp.ndarray, w: jnp.ndarray, bias: Optional[jnp.ndarray] = None,
         *, relu: bool = False, bm: int = 128, bn: int = 128, bk: int = 128
         ) -> jnp.ndarray:
    """Tile-padding wrapper over :func:`repro.kernels.gemm.gemm`."""
    m, k = x.shape
    _, n = w.shape
    xp = _pad_to(_pad_to(x, 0, bm), 1, bk)
    wp = _pad_to(_pad_to(w, 0, bk), 1, bn)
    bp = _pad_to(bias, 0, bn) if bias is not None else None
    out = _gemm.gemm(xp, wp, bp, bm=bm, bn=bn, bk=bk, relu=relu,
                     interpret=not _on_tpu())
    return out[:m, :n]


def spmm(rows: jnp.ndarray, cols: jnp.ndarray, vals: jnp.ndarray,
         x: jnp.ndarray, n_dst: int, *, bd: int = 128, be: int = 256
         ) -> jnp.ndarray:
    """Tile-padding wrapper over :func:`repro.kernels.spmm.spmm`.

    Padding edges point past the source range (col = n_src): their gather
    one-hot row is all-zero, so they move no data at all — val == 0 alone
    would still gather real row 0 and zero it after the fact.
    """
    n_src, d = x.shape
    rp = _pad_to(rows, 0, be)
    cp = _pad_to(cols, 0, be, value=n_src)   # out-of-range ⇒ gathers nothing
    vp = _pad_to(vals, 0, be)                # and weight 0 ⇒ scatters nothing
    xp = _pad_to(x, 1, bd)
    out = _spmm.spmm(rp, cp, vp, xp, n_dst, bd=bd, be=be,
                     interpret=not _on_tpu())
    return out[:, :d]


def spmm_block(rows: jnp.ndarray, cols: jnp.ndarray, vals: jnp.ndarray,
               x: jnp.ndarray, dpc: int, *, bd: int = 128, be: int = 256
               ) -> jnp.ndarray:
    """Tile-padding wrapper over :func:`repro.kernels.spmm.spmm_block`.

    Arguments follow the Block-Message tile layout
    (:class:`repro.core.blockmsg.BlockTiles`): [n_blocks, e_blk] edge arrays
    with block-local row offsets; returns [n_blocks * dpc, d].  Padding
    edges are routed past the source range like :func:`spmm`'s.
    """
    n_src, d = x.shape
    rp = _pad_to(rows, 1, be)
    cp = _pad_to(cols, 1, be, value=n_src)   # out-of-range ⇒ gathers nothing
    vp = _pad_to(vals, 1, be)
    xp = _pad_to(x, 1, bd)
    out = _spmm.spmm_block(rp, cp, vp, xp, dpc, bd=bd, be=be,
                           interpret=not _on_tpu())
    return out[:, :d]


# ---------------------------------------------------------------------------
# Pre-reduced ELL engine.
# ---------------------------------------------------------------------------
def _tuned_tiles(br, bd, bs):
    if br is None or bd is None or bs is None:
        from repro.kernels.tune import get_config
        cfg = get_config()
        br = cfg["br"] if br is None else br
        bd = cfg["bd"] if bd is None else bd
        bs = cfg["bs"] if bs is None else bs
    return br, bd, bs


def spmm_ell(cols: jnp.ndarray, vals: jnp.ndarray, x: jnp.ndarray, *,
             br: Optional[int] = None, bd: Optional[int] = None,
             bs: Optional[int] = None) -> jnp.ndarray:
    """Tile-padding wrapper over :func:`repro.kernels.spmm.spmm_ell`.

    ``cols``/``vals``: one [nb, K] bucket of an
    :class:`repro.kernels.edgeplan.EllTables` whose padding entries point at
    column ``n_src`` — this wrapper appends that dedicated zero row to ``x``
    before tiling, so padding gathers zeros by construction.  Tile sizes
    default to the autotuned config (:mod:`repro.kernels.tune`).
    """
    br, bd, bs = _tuned_tiles(br, bd, bs)
    nb, _ = cols.shape
    n_src, d = x.shape
    xz = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    xp = _pad_to(_pad_to(xz, 0, bs), 1, bd)
    cp = _pad_to(cols, 0, br, value=n_src)   # pad rows → all-zero-row entries
    vp = _pad_to(vals, 0, br)
    if cp.shape[1] > _spmm.ELL_BK:           # hub bucket: whole degree slices
        cp = _pad_to(cp, 1, _spmm.ELL_BK, value=n_src)
        vp = _pad_to(vp, 1, _spmm.ELL_BK)
    out = _spmm.spmm_ell(cp, vp, xp, br=br, bd=bd, bs=bs,
                         interpret=not _on_tpu())
    return out[:nb, :d]


def spmm_ell_t(t_cols: jnp.ndarray, t_vals: jnp.ndarray, e: jnp.ndarray, *,
               br: Optional[int] = None, bd: Optional[int] = None,
               bs: Optional[int] = None) -> jnp.ndarray:
    """Transpose walk through the same wrapper: ``Aᵀ e`` over the plan's
    column-major tables — see :func:`repro.kernels.spmm.spmm_ell_t`."""
    return spmm_ell(t_cols, t_vals, e, br=br, bd=bd, bs=bs)


def _ell_walk(cols_list, vals_list, inv, x, use_pallas: Optional[bool]):
    """One gather-accumulate pass over bucketed ELL tables.

    ``use_pallas=None`` picks the backend default (native kernel on TPU,
    pure-XLA elsewhere — same math, no scatter either way).  The XLA path
    unrolls the degree axis into K one-row gathers with a fused
    multiply-add: 1-D row gathers vectorize where a [nb, K, d] temporary
    does not (measured ~7x over segment-sum on CPU at smoke sizes), and
    ``mode="fill"`` realizes the plan's dedicated zero row — the padding
    column id ``n_src`` is out of range and gathers exact zeros, touching
    no real data.  Output row *r* is row ``inv[r]`` of the concatenated
    bucket outputs; rows with no edges have ``inv[r]`` past the end and
    fill with zeros without computing anything.
    """
    if use_pallas is None:
        use_pallas = _on_tpu()
    d = x.shape[-1]
    outs = []
    if use_pallas:
        for c, v in zip(cols_list, vals_list):
            if c.shape[0]:
                outs.append(spmm_ell(c, v, x))
    else:
        for c, v in zip(cols_list, vals_list):
            if not c.shape[0]:
                continue
            acc = jnp.take(x, c[:, 0], axis=0, mode="fill",
                           fill_value=0) * v[:, 0:1]
            for k in range(1, c.shape[1]):
                acc = acc + jnp.take(x, c[:, k], axis=0, mode="fill",
                                     fill_value=0) * v[:, k:k + 1]
            outs.append(acc)
    cat = (jnp.concatenate(outs, axis=0) if outs
           else jnp.zeros((1, d), x.dtype))
    return jnp.take(cat, inv, axis=0, mode="fill", fill_value=0)


def ell_apply(tables: Dict, x: jnp.ndarray, *, transpose: bool = False,
              use_pallas: Optional[bool] = None) -> jnp.ndarray:
    """Forward (or transpose) ELL walk WITHOUT the custom_vjp — the building
    block the distributed aggregate composes around its collectives.

    ``transpose=True`` walks the column-major tables (``Aᵀ e``).
    ``use_pallas`` forces the kernel (tests run it in interpret mode off-TPU
    to exercise the exact Pallas body); ``None`` picks the backend default.

    Redundancy-merged plans (tables carrying the ``vv_*``/``vvt_*`` keys
    from :meth:`repro.kernels.edgeplan.EdgePlan.device_tables`) add one
    small pre-pass with the SAME kernel: forward computes the virtual
    partials ``z = V x`` and walks the main tables over ``[x; z]``; the
    transpose splits the extended cotangent and routes the virtual slice
    back through ``Vᵀ`` — ``dx = gₒ + Vᵀ g_v`` — so the transpose-free
    contract survives the rewrite.  The main bucket tables are identical
    in shape either way; the kernel never learns merging happened.
    """
    merged = "vv_cols" in tables
    if transpose:
        g = _ell_walk(tables["t_cols"], tables["t_vals"], tables["t_inv"],
                      x, use_pallas)
        if not merged:
            return g
        # vvt tables have one output row per ORIGINAL source: the static
        # split point n_src is their inv length (no scalar leaves in the
        # tables pytree — shapes carry the metadata).
        n_src = tables["vvt_inv"].shape[0]
        dz = _ell_walk(tables["vvt_cols"], tables["vvt_vals"],
                       tables["vvt_inv"], g[n_src:], use_pallas)
        return g[:n_src] + dz
    if merged:
        z = _ell_walk(tables["vv_cols"], tables["vv_vals"], tables["vv_inv"],
                      x, use_pallas)
        x = jnp.concatenate([x, z.astype(x.dtype)], axis=0)
    return _ell_walk(tables["cols"], tables["vals"], tables["inv"], x,
                     use_pallas)




@jax.custom_vjp
def ell_aggregate(tables: Dict, x: jnp.ndarray) -> jnp.ndarray:
    """``y = A @ x`` through a pre-reduced ELL plan — THE custom_vjp.

    ``tables`` is :meth:`repro.kernels.edgeplan.EdgePlan.device_tables`
    output (keys ``cols``/``vals``/``inv`` forward, ``t_*`` transpose).
    Forward walks the dst-major tables; the registered backward walks the
    column-major tables of the SAME edges with the SAME kernel — no ``Aᵀ``,
    no transposed residual (aggregation is linear in ``x``: the plan itself
    is the only residual), and no segment-sum scatter anywhere.  Plans with
    a virtual-vertex tier route through :func:`ell_apply`'s pre-pass in
    both directions with the same contract.
    """
    return ell_apply(tables, x)


def _ell_aggregate_fwd(tables, x):
    return ell_aggregate(tables, x), tables


def _ell_aggregate_bwd(tables, ct):
    dx = ell_apply(tables, ct, transpose=True)
    return _zero_ct(tables), dx


ell_aggregate.defvjp(_ell_aggregate_fwd, _ell_aggregate_bwd)
