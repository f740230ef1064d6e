"""The configuration's graph, features and labels, made from its data seed.

A synthetic stand-in for a published graph dataset, with its node and edge
counts, feature width and class count: a Chung-Lu power-law graph, as
``repro.graph.datasets.make_dataset`` makes it (both endpoints of every
edge drawn independently from Pareto(alpha) node weights, self-edges
dropped, both directions stored).  Written anew here so that the
benchmark's data owe nothing to the program under test, and drawn in
O(edges) without a sort: the sources as multinomial counts in node order,
the destinations as multinomial counts paired with the sources by a random
permutation, which gives the same independent pairs.
"""
from __future__ import annotations

import numpy as np


def _place(indices, starts, keys, values, n):
    """Write ``values`` (grouped by ascending ``keys``) into each key's run
    of ``indices`` that begins at ``starts[key]``."""
    counts = np.bincount(keys, minlength=n)
    first = np.cumsum(counts) - counts
    rank = np.arange(keys.size) - first[keys]
    indices[starts[keys] + rank] = values
    return counts


def make_graph(cfg: dict) -> dict:
    """``{"indptr", "indices", "features", "labels"}`` for ``cfg``, a
    configuration file's contents (``n_nodes``, ``n_edges``, ``alpha``,
    ``feat_dim``, ``n_classes``, ``data_seed``).  Features are uniform
    with standard deviation 0.1; labels uniform over the classes."""
    n, m = int(cfg["n_nodes"]), int(cfg["n_edges"])
    rng = np.random.default_rng(int(cfg["data_seed"]))
    w = rng.pareto(float(cfg["alpha"]), n) + 1.0
    p = w / w.sum()
    nodes = np.arange(n)
    src = np.repeat(nodes, rng.multinomial(m, p))
    dst_sorted = np.repeat(nodes, rng.multinomial(m, p))
    pairing = rng.permutation(m)
    dst = dst_sorted[pairing]
    keep = src != dst
    by_dst = np.empty_like(pairing)
    by_dst[pairing] = np.arange(m)
    keep_d = keep[by_dst]
    fwd = np.bincount(src[keep], minlength=n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(fwd + np.bincount(dst[keep], minlength=n), out=indptr[1:])
    indices = np.empty(indptr[-1], np.int64)
    _place(indices, indptr[:-1], src[keep], dst[keep], n)
    _place(indices, indptr[:-1] + fwd, dst_sorted[keep_d],
           src[by_dst][keep_d], n)
    features = rng.random((n, int(cfg["feat_dim"])), dtype=np.float32)
    features -= np.float32(0.5)
    features *= np.float32(0.1 * 12 ** 0.5)
    labels = rng.integers(0, int(cfg["n_classes"]), size=n).astype(np.int32)
    return {"indptr": indptr, "indices": indices, "features": features,
            "labels": labels}
