"""GraphSAGE neighbor sampler (paper §5.1: fanouts 25 → 10).

Mini-batch construction for sampled GCN/GraphSAGE training.  Produces the
per-layer *rectangular* adjacencies the paper's sequence estimator reasons
about: layer l has A_l ∈ R^{n_l × n_{l+1}} where n_l are the nodes needed at
hop l (n_0 = batch) and n_{l+1} their sampled frontier.

Pure-numpy host-side pipeline (this is data loading, not device compute);
emits static-shaped, padded COO so the device step function never re-traces.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .coo import COO, from_edges, mean_normalize, pad_coo
from .partition import pad_to_multiple


@dataclasses.dataclass(frozen=True)
class CSRGraph:
    """Host-side full-graph container (indptr/indices CSR)."""

    indptr: np.ndarray   # [n+1] int64
    indices: np.ndarray  # [e] int32/int64, neighbor ids
    n_nodes: int

    @property
    def n_edges(self) -> int:
        return int(self.indices.shape[0])

    def degree(self, nodes: np.ndarray) -> np.ndarray:
        return self.indptr[nodes + 1] - self.indptr[nodes]


def csr_from_edges(src: np.ndarray, dst: np.ndarray, n_nodes: int) -> CSRGraph:
    """Build CSR adjacency (out-neighbors of each node), symmetrizing is the
    caller's business (datasets.py emits both directions for undirected)."""
    order = np.argsort(src, kind="stable")
    src = src[order]
    dst = dst[order]
    counts = np.bincount(src, minlength=n_nodes)
    indptr = np.zeros(n_nodes + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    return CSRGraph(indptr=indptr, indices=dst.astype(np.int64), n_nodes=n_nodes)


@dataclasses.dataclass(frozen=True)
class MiniBatch:
    """One sampled mini-batch: per-layer adjacencies + input features/labels.

    ``layers[l]`` aggregates hop-(l+1) nodes into hop-l nodes;
    ``layers[-1]`` consumes the raw input features.  All shapes are padded to
    static sizes so a single jit trace serves the whole epoch.
    """

    layers: Tuple[COO, ...]          # rectangular, row-major sorted, padded
    input_nodes: np.ndarray          # [n_last_padded] global ids of frontier
    seed_nodes: np.ndarray           # [batch] global ids of the batch
    n_real: Tuple[int, ...]          # true (unpadded) node count per hop
    batch: int = -1                  # the pipeline's batch index (-1: none)


class NeighborSampler:
    """Uniform neighbor sampling with replacement-free capped fanout.

    ``pad_multiple`` pads every hop's node count (and 16× the edge count) so
    shapes are stable; with the production mesh this is P=16 so each hop
    splits evenly across cores.
    """

    def __init__(self, graph: CSRGraph, fanouts: Sequence[int],
                 pad_multiple: int = 16, seed: int = 0):
        self.graph = graph
        self.fanouts = tuple(fanouts)
        self.pad_multiple = pad_multiple
        self.rng = np.random.default_rng(seed)

    def _sample_layer(self, seeds: np.ndarray, fanout: int,
                      rng: Optional[np.random.Generator] = None
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Return (rows_local, frontier_nodes, cols_local): for each seed node
        (row r) up to ``fanout`` sampled neighbors; frontier includes the
        seeds themselves (self loop, GCN-style Ã = A + I)."""
        g = self.graph
        rng = rng if rng is not None else self.rng
        deg = g.degree(seeds)
        take = np.minimum(deg, fanout)
        rows = np.repeat(np.arange(len(seeds), dtype=np.int64), take)
        # vectorized per-seed choice: random offsets into each CSR row
        total = int(take.sum())
        if total:
            u = rng.random(total)
            row_start = np.repeat(g.indptr[seeds], take)
            row_deg = np.repeat(deg, take).astype(np.float64)
            offs = np.floor(u * row_deg).astype(np.int64)
            picked = g.indices[row_start + offs]
        else:
            picked = np.zeros(0, np.int64)
        # frontier = seeds ∪ picked (seeds first so hop-l nodes keep ids)
        frontier, inv = np.unique(np.concatenate([seeds, picked]),
                                  return_inverse=True)
        # remap so that seeds occupy [0, len(seeds)) in the frontier ordering
        seed_pos = inv[:len(seeds)]
        remap = np.full(len(frontier), -1, np.int64)
        remap[seed_pos] = np.arange(len(seeds))
        rest = np.flatnonzero(remap < 0)
        remap[rest] = len(seeds) + np.arange(len(rest))
        frontier_sorted = np.empty_like(frontier)
        frontier_sorted[remap] = frontier
        cols = remap[inv[len(seeds):]]
        # self loops: row r aggregates frontier slot r too
        self_rows = np.arange(len(seeds), dtype=np.int64)
        rows = np.concatenate([rows, self_rows])
        cols = np.concatenate([cols, self_rows])
        return rows, frontier_sorted, cols

    def sample(self, seeds: np.ndarray,
               nnz_pad: Optional[Sequence[int]] = None,
               rng: Optional[np.random.Generator] = None) -> MiniBatch:
        """``rng``: pass a per-batch generator for deterministic-resume
        pipelines (the stateful default is fine for one-shot sampling)."""
        seeds = np.asarray(seeds, np.int64)
        layers: List[COO] = []
        n_real = [len(seeds)]
        cur = seeds
        for l, fanout in enumerate(self.fanouts):
            rows, frontier, cols = self._sample_layer(cur, fanout, rng)
            n_dst = pad_to_multiple(len(cur), self.pad_multiple)
            n_src = pad_to_multiple(len(frontier), self.pad_multiple)
            coo = mean_normalize(rows, cols, n_dst=n_dst, n_src=n_src)
            if nnz_pad is not None:
                coo = pad_coo(coo, nnz_pad[l])
            layers.append(coo)
            n_real.append(len(frontier))
            cur = frontier
        frontier_padded = np.zeros(pad_to_multiple(len(cur), self.pad_multiple),
                                   np.int64)
        frontier_padded[:len(cur)] = cur
        return MiniBatch(layers=tuple(layers), input_nodes=frontier_padded,
                         seed_nodes=seeds, n_real=tuple(n_real))

    def static_nnz(self, batch_size: int) -> Tuple[int, ...]:
        """Worst-case padded nnz per layer (fanout+selfloop bound) so the
        device step compiles once."""
        sizes = []
        cur = batch_size
        for fanout in self.fanouts:
            sizes.append(pad_to_multiple(cur * (fanout + 1), 128))
            cur = cur * (fanout + 1)  # upper bound on frontier growth
        return tuple(sizes)


def epoch_batches(n_nodes: int, batch_size: int, rng: np.random.Generator):
    """Shuffled full-epoch seed batches (drop ragged tail, as the paper's
    fixed-1024 batches do)."""
    perm = rng.permutation(n_nodes)
    n_full = (n_nodes // batch_size) * batch_size
    for s in range(0, n_full, batch_size):
        yield perm[s:s + batch_size]
