"""Operations and bytes that one training step of the GCN requires, counted
from the batch's real rows and edges, never from padded shapes.

A batch is the structure the benchmark records from the sampler (see
:func:`chipbench.reference.batch_inputs`): per hop, shallowest first, the
drawn (row, col) pairs and the real destination and source counts.  Hop
``l`` is aggregated at the output width of the weight that precedes it,
``dims_io[n_hops - 1 - l]``.

* The ELL aggregation kernel walks each hop twice a step, forward and
  transposed.  Each walk requires ``2 · nnz · d`` operations, ``nnz`` the
  distinct (row, col) pairs, and reads its source rows once, writes its
  output rows once and reads its table once (a 4-byte column id and a
  4-byte weight per pair), all float32.
* The model's operations per step: per hop the combination
  ``2 · n_src · d_in · d_out`` forward and as much again for the weight
  gradient and for the input gradient (none for the raw features, which
  need no gradient), and the aggregation ``2 · nnz · d_out`` forward and
  backward.  Nothing is counted for padding or for recomputation; the loss
  and the SGD update are left out as negligible.
"""
from __future__ import annotations

import numpy as np

F32 = 4


def hop_nnz(hop: dict) -> int:
    """Distinct (row, col) pairs of one hop: the aggregation matrix's
    nonzeros (a pair drawn twice is one entry of twice the weight)."""
    rows = np.asarray(hop["rows"], np.int64)
    cols = np.asarray(hop["cols"], np.int64)
    return int(np.unique(rows * int(hop["n_src"]) + cols).size)


def step_work(batch: dict, dims_io) -> dict:
    """``{"ell_ops", "ell_bytes", "model_ops"}`` of one step on ``batch``."""
    hops = batch["hops"]
    n_hops = len(hops)
    ell_ops = ell_bytes = model_ops = 0
    for l, hop in enumerate(hops):
        d_in, d_out = dims_io[n_hops - 1 - l]
        nnz = hop_nnz(hop)
        n_dst, n_src = int(hop["n_dst"]), int(hop["n_src"])
        walk_ops = 2 * nnz * d_out
        walk_bytes = (n_src + n_dst) * d_out * F32 + nnz * 2 * F32
        ell_ops += 2 * walk_ops
        ell_bytes += 2 * walk_bytes
        comb = 2 * n_src * d_in * d_out
        input_grad = comb if l != n_hops - 1 else 0
        model_ops += comb + comb + input_grad + 2 * walk_ops
    return {"ell_ops": ell_ops, "ell_bytes": ell_bytes,
            "model_ops": model_ops}


def window_work(batches: list, dims_io) -> dict:
    """Summed :func:`step_work` over the window's steps (``batches`` holds
    one batch per step, repeats included)."""
    per = {}
    total = {"ell_ops": 0, "ell_bytes": 0, "model_ops": 0}
    for b in batches:
        key = id(b)
        if key not in per:
            per[key] = step_work(b, dims_io)
        for k, v in per[key].items():
            total[k] += v
    return total
