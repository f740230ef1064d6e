"""The GCN of arXiv:2411.03857 section 5.1, as the harness sees it (the
model contract is in :func:`chipbench.run.load_model`).

Its plain float32 reference over the sampled batch is written with
``segment_sum`` and nothing of the program's layouts.  Per hop, deepest
first (the engine's CoAg order)::

    h = h @ W                                      # combination
    h = segment_sum(v[e] * h[col[e]], row[e])      # mean aggregation
    h = relu(h)                                    # between hops

with ``v[e] = 1 / (entries of row[e])``, self-loops included and repeated
samples counted, and a mean cross-entropy over the batch's roots.  The
program's parameters are one ``{"w": W}`` per layer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import reference, work
from chipbench.reference import low_dot, low_scale, mm


def dims_io(cfg: dict) -> list:
    """(input width, output width) of each layer's weight."""
    widths = [cfg["feat_dim"]] + [cfg["hidden"]] * (cfg["n_layers"] - 1) \
        + [cfg["n_classes"]]
    return list(zip(widths[:-1], widths[1:]))


def init(words, cfg: dict) -> list:
    """Normal weights scaled by ``d_in ** -0.5``, one float32 matrix per
    layer, from a key's words (:func:`chipbench.reference.seed_words`)."""
    dims = dims_io(cfg)
    key = jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))
    keys = jax.random.split(key, len(dims))
    return [jax.random.normal(k, (a, b), jnp.float32) * a ** -0.5
            for k, (a, b) in zip(keys, dims)]


def to_program(leaves) -> list:
    return [{"w": w} for w in leaves]


def from_program(params) -> list:
    return [p["w"] for p in params]


def trainer_kwargs(cfg: dict) -> dict:
    return {}


@functools.partial(jax.jit, static_argnames=("n_dst", "precision", "keep"))
def _loss_and_grad(ws, x, y, rows, cols, vals, *, n_dst, precision, keep):
    passes = reference.PASSES.get(precision)

    def loss_fn(ws):
        h = x
        n_hops = len(rows)
        for l in range(n_hops - 1, -1, -1):
            w = ws[n_hops - 1 - l]
            h = mm(h, w) if passes is None else low_dot(h, w, passes)
            g = h[cols[l]]
            v = vals[l][:, None]
            msg = v * g if passes is None else low_scale(
                jnp.broadcast_to(v, g.shape), g, passes)
            h = jax.ops.segment_sum(msg, rows[l], num_segments=n_dst[l])
            if l:
                h = jnp.maximum(h, 0.0)
        logp = jax.nn.log_softmax(h, -1)
        nll = -jnp.take_along_axis(logp, y[:, None], -1)[:, 0]
        return nll[:keep].mean()

    return jax.value_and_grad(loss_fn)(ws)


def loss_and_grad(leaves, batch: dict, data: dict,
                  precision: str = "highest", keep: float = 1.0):
    """(mean loss over the first ``keep`` share of the roots, its gradient
    with respect to each weight)."""
    x, y, rows, cols, vals, n_dst = reference.batch_inputs(batch, data)
    return _loss_and_grad(list(leaves), x, y, rows, cols, vals, n_dst=n_dst,
                          precision=precision,
                          keep=int(round(batch["n_roots"] * keep)))


def output_leaves(n: int) -> list:
    """Only the last weight: every other one's gradient passes a ReLU mask
    (see :mod:`chipbench.compare`)."""
    return [False] * (n - 1) + [True]


def step_work(batch: dict, cfg: dict) -> dict:
    return work.step_work(batch, dims_io(cfg))
