"""PartitionSpec rules — one place that knows where every tensor lives.

Mesh axes (launch/mesh.py):
  * ``pod``   — outer data-parallel axis across pods (multi-pod mesh only)
  * ``data``  — data parallel within a pod
  * ``model`` — the 16-way "core" axis: TP for dense LMs, EP for MoE, and
                the paper's 4-D hypercube for graph aggregation (16 = 2⁴)

The rule of the paper's NUMA layout generalizes: *a tensor is sharded on the
axis that makes its heaviest consumer local.*  Node features and edge blocks
shard over ``model`` (aggregation is the consumer), LM weights shard over
``model`` on their contraction-free dim (megatron TP), activations shard
batch over (``pod``, ``data``) and sequence over ``model`` where the shape
is long (SP for 32k prefill).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

MODEL = "model"
DATA = "data"
POD = "pod"


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    """All data-parallel axes present in this mesh (pod outermost)."""
    return tuple(a for a in (POD, DATA) if a in mesh.axis_names)


def dp_size(mesh: Mesh) -> int:
    out = 1
    for a in batch_axes(mesh):
        out *= mesh.shape[a]
    return out


# --- activation specs -------------------------------------------------------
def act_batch(mesh: Mesh, *trailing: Optional[str]) -> P:
    """[batch, ...] activation: batch over all DP axes."""
    return P(batch_axes(mesh), *trailing)


def act_batch_seq(mesh: Mesh, shard_seq: bool = False) -> P:
    """[batch, seq, d] activation; optionally sequence-sharded over model
    (SP — used for long prefill where seq ≫ heads)."""
    if shard_seq:
        return P(batch_axes(mesh), MODEL, None)
    return P(batch_axes(mesh), None, None)


# --- weight specs (megatron pairing: col-shard then row-shard) --------------
def w_col(mesh: Mesh) -> P:
    """[d_in, d_out] with d_out over model (QKV proj, FFN up/gate)."""
    return P(None, MODEL)


def w_row(mesh: Mesh) -> P:
    """[d_in, d_out] with d_in over model (attn out proj, FFN down)."""
    return P(MODEL, None)


def w_replicated(mesh: Mesh) -> P:
    return P()


def embed_vocab(mesh: Mesh) -> P:
    """[vocab, d] — vocab over model (the big-embedding archs: gemma3 262k,
    seamless 256k, moonshot 164k)."""
    return P(MODEL, None)


def moe_expert(mesh: Mesh) -> P:
    """[experts, d_in, d_out] — experts over model (EP)."""
    return P(MODEL, None, None)


def kv_cache(mesh: Mesh) -> P:
    """[batch, heads_kv, seq, hd] — batch over DP, kv heads over model when
    they divide, else replicated heads (GQA kv=4/8 < 16 ⇒ batch-shard only)."""
    return P(batch_axes(mesh), MODEL, None, None)


# --- graph (paper) specs ----------------------------------------------------
def node_features(mesh: Mesh) -> P:
    """[n_nodes, d] — rows over model: the NUMA placement (core i owns its
    nodes' features in its own HBM)."""
    return P(MODEL, None)


def edge_shards(mesh: Mesh) -> P:
    """[P, e_max] sender-side edge blocks — leading axis over model."""
    return P(MODEL, None)


def gcn_weights(mesh: Mesh) -> P:
    """GCN weights are replicated over model (the paper's Weight Bank keeps a
    synchronized global copy per core) and all-reduced over DP."""
    return P()


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis typed ``AxisType.Auto``.

    ``jax.make_mesh`` types its axes ``Explicit`` by default, which makes
    every array the engine returns carry its sharding in the type: eager
    ops and ``jax.grad`` taken on those outputs outside a ``jax.set_mesh``
    context then fail to match the mesh size.  The engine places and
    shards everything itself, so it runs on the same devices with the
    axes left to the compiler.
    """
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return mesh.update(axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def leading_axis_spec(a, axis: str = MODEL) -> P:
    """Spec of a per-core-stacked host artifact: leading axis over ``axis``,
    everything else replicated — the rule every edge layout (flat COO tile,
    Block-Message tile, pre-reduced ELL table/inv leaf) shares."""
    return P(axis, *([None] * (a.ndim - 1)))


def leading_axis_put(mesh: Mesh, a, axis: str = MODEL):
    """Commit one per-core-stacked leaf to its sharding in ONE transfer.

    This placement-at-build-time is load-bearing: jit re-lays-out
    uncommitted operands on EVERY call, which was the measured cause of the
    blocked arm's ``agg_fwd_speedup < 1`` regression.  Train path and
    benchmarks must place leaves through this one helper so they can never
    measure different placements.
    """
    import numpy as np

    a = np.asarray(a)
    return jax.device_put(a, NamedSharding(mesh, leading_axis_spec(a, axis)))
