"""The plain float32 reference: a GCN over the sampled batch, written with
``segment_sum`` and nothing of the program's layouts, and its SGD steps.

Per hop, deepest first (the engine's CoAg order)::

    h = h @ W                                      # combination
    h = segment_sum(v[e] * h[col[e]], row[e])      # mean aggregation
    h = relu(h)                                    # between hops

with ``v[e] = 1 / (entries of row[e])``, self-loops included and repeated
samples counted, and a mean cross-entropy over the batch's roots.  From the
timed path it takes only what the sampler chose: which global nodes each
hop holds and which (row, col) pairs it drew.  Features and labels come
from the benchmark's own data, weights from the benchmark's own init.

``precision`` picks how every product is computed:

* ``"highest"`` — float32, matmuls at ``Precision.HIGHEST``: the reference;
* ``"high"`` — three bf16 passes (``hi·hi + hi·lo + lo·hi``), TPU's
  ``Precision.HIGH``;
* ``"bf16"`` — one bf16 pass, TPU's ``Precision.DEFAULT`` for float32.

The lower two are emulated with explicit bf16 splits, forward and backward,
so they read the same on a CPU as on the chip: they are the controls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PASSES = {"high": 3, "bf16": 1}


def seed_words(seed: int) -> np.ndarray:
    """A random key's two 32-bit words, drawn from a seed of any size."""
    return np.random.SeedSequence(int(seed)).generate_state(2)


def init_params(words, dims_io) -> list:
    """Normal weights scaled by ``d_in ** -0.5``, one float32 matrix per
    layer, from a key's words (:func:`seed_words`)."""
    key = jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))
    keys = jax.random.split(key, len(dims_io))
    return [jax.random.normal(k, (a, b), jnp.float32) * a ** -0.5
            for k, (a, b) in zip(keys, dims_io)]


def _bf16(x):
    """``x`` rounded to bfloat16, kept float32.  ``reduce_precision`` and not
    a round trip through ``astype``: XLA may drop the round trip's rounding
    (excess precision), which on a TPU left the low part ``x - hi`` at 0."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split(x):
    hi = _bf16(x)
    return hi, _bf16(x - hi)


def _prod(op, a, b, passes: int):
    """``op(a, b)`` from bf16 parts: products of bf16 values are exact in
    float32, so only the dropped parts differ from float32."""
    ah, al = _split(a)
    bh, bl = _split(b)
    out = op(ah, bh)
    if passes == 3:
        out = out + op(ah, bl) + op(al, bh)
    return out


def _mm(a, b):
    return jnp.dot(a, b, precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _low_dot(a, b, passes):
    return _prod(_mm, a, b, passes)


def _low_dot_fwd(a, b, passes):
    return _prod(_mm, a, b, passes), (a, b)


def _low_dot_bwd(passes, res, ct):
    a, b = res
    return _prod(_mm, ct, b.T, passes), _prod(_mm, a.T, ct, passes)


_low_dot.defvjp(_low_dot_fwd, _low_dot_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _low_scale(v, h, passes):
    return _prod(jnp.multiply, v, h, passes)


def _low_scale_fwd(v, h, passes):
    return _prod(jnp.multiply, v, h, passes), v


def _low_scale_bwd(passes, v, ct):
    return jnp.zeros_like(v), _prod(jnp.multiply, v, ct, passes)


_low_scale.defvjp(_low_scale_fwd, _low_scale_bwd)


@functools.partial(jax.jit, static_argnames=("n_dst", "precision", "keep"))
def _loss_and_grad(ws, x, y, rows, cols, vals, *, n_dst, precision, keep):
    passes = PASSES.get(precision)

    def loss_fn(ws):
        h = x
        n_hops = len(rows)
        for l in range(n_hops - 1, -1, -1):
            w = ws[n_hops - 1 - l]
            h = _mm(h, w) if passes is None else _low_dot(h, w, passes)
            g = h[cols[l]]
            v = vals[l][:, None]
            msg = v * g if passes is None else _low_scale(
                jnp.broadcast_to(v, g.shape), g, passes)
            h = jax.ops.segment_sum(msg, rows[l], num_segments=n_dst[l])
            if l:
                h = jnp.maximum(h, 0.0)
        logp = jax.nn.log_softmax(h, -1)
        nll = -jnp.take_along_axis(logp, y[:, None], -1)[:, 0]
        return nll[:keep].mean()

    return jax.value_and_grad(loss_fn)(ws)


def batch_inputs(batch: dict, data: dict):
    """Device inputs of one batch.  ``batch``: ``nodes`` (global ids; the
    first ``n`` of them are hop-l's nodes for every hop), ``n_roots``, and
    per hop (shallowest first) ``rows``/``cols`` of the drawn pairs and
    ``n_dst``/``n_src``."""
    hops = batch["hops"]
    nodes = np.asarray(batch["nodes"], np.int64)
    x = data["features"][nodes[:hops[-1]["n_src"]]]
    y = data["labels"][nodes[:batch["n_roots"]]].astype(np.int32)
    rows, cols, vals = [], [], []
    for hop in hops:
        r = np.asarray(hop["rows"], np.int64)
        count = np.bincount(r, minlength=hop["n_dst"]).astype(np.float64)
        rows.append(jnp.asarray(r, jnp.int32))
        cols.append(jnp.asarray(hop["cols"], jnp.int32))
        vals.append(jnp.asarray((1.0 / count)[r], jnp.float32))
    return (jnp.asarray(x), jnp.asarray(y), tuple(rows), tuple(cols),
            tuple(vals), tuple(int(h["n_dst"]) for h in hops))


def loss_and_grad(ws, batch: dict, data: dict, precision: str = "highest",
                  keep: float = 1.0):
    """(mean loss over the first ``keep`` share of the roots, its gradient
    with respect to each weight)."""
    x, y, rows, cols, vals, n_dst = batch_inputs(batch, data)
    return _loss_and_grad(list(ws), x, y, rows, cols, vals, n_dst=n_dst,
                          precision=precision,
                          keep=int(round(batch["n_roots"] * keep)))


def sgd_steps(ws0, batches, data: dict, lr: float, *,
              precision: str = "highest", keep: float = 1.0) -> dict:
    """The reference's own trajectory from ``ws0`` over ``batches``: each
    step's loss, the first step's gradient, the weights after each step
    (host float32 arrays)."""
    ws = [jnp.asarray(w, jnp.float32) for w in ws0]
    out = {"losses": [], "grads1": None, "params": []}
    for batch in batches:
        loss, grads = loss_and_grad(ws, batch, data, precision, keep)
        if out["grads1"] is None:
            out["grads1"] = [np.asarray(g) for g in grads]
        ws = [w - lr * g for w, g in zip(ws, grads)]
        out["losses"].append(float(loss))
        out["params"].append([np.asarray(w) for w in ws])
    return out
