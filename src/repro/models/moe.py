"""Mixture-of-Experts FFN — token-choice top-k with capacity dispatch.

Serves llama4-maverick (128e top-1, dense/moe interleaved pairs) and
moonshot-v1 (64e top-6, all-moe).  Experts shard over the ``model`` axis
(EP); the scatter into the [E, C, d] expert buffer and the gather back are
the token-routing all-to-all — which is exactly the paper's graph message
passing with a rectangular (tokens × experts) adjacency, so the hypercube
schedule analysis (DESIGN §Arch-applicability) applies: tokens destined to
the same expert are *pre-combined per device before exchange* by the sort,
mirroring the Block-Message merge.

Capacity C bounds the per-expert buffer (tokens beyond C drop — standard
top-k MoE; the tests check the drop fraction stays tiny at the default
factor 1.25).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.compat import axis_size as compat_axis_size
from repro.compat import shard_map as compat_shard_map

from .config import ArchConfig
from .transformer import _norm_init

Params = Dict[str, Any]


def init_moe_params(key, cfg: ArchConfig, dtype=jnp.bfloat16) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe_experts
    ks = jax.random.split(key, 4)
    return {
        "router": _norm_init(ks[0], (d, e), d ** -0.5, jnp.float32),
        "w_gate": _norm_init(ks[1], (e, d, f), d ** -0.5, dtype),
        "w_up": _norm_init(ks[2], (e, d, f), d ** -0.5, dtype),
        "w_down": _norm_init(ks[3], (e, f, d), f ** -0.5, dtype),
    }


def capacity(n_tokens: int, n_experts: int, topk: int,
             factor: float = 1.25) -> int:
    # ceil: a truncated share can fall short of the load it must cover
    c = math.ceil(factor * n_tokens * topk / n_experts)
    return max(8, ((c + 7) // 8) * 8)          # pad to 8 for TPU layout


def _route(x, p, cfg):
    """Router + top-k + aux loss.  x: [b, s, d]."""
    b, s, d = x.shape
    e, k = cfg.moe_experts, cfg.moe_topk
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, k)                 # [b, s, k]
    gates = gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9)
    me = probs.mean((0, 1))
    ce = jnp.zeros(e).at[eidx.reshape(-1)].add(1.0) / (b * s * k)
    aux = e * jnp.sum(me * ce)                            # Switch aux loss
    return gates, eidx, aux


def _positions(eidx_flat, e, cap):
    """Capacity plan: position-in-expert for every routed slot ([s*k])."""
    onehot = jax.nn.one_hot(eidx_flat, e, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, 0) - 1,
                              eidx_flat[:, None], 1)[:, 0]
    keep = pos < cap
    return jnp.where(keep, pos, cap - 1), keep


def moe_ffn(x: jnp.ndarray, p: Params, cfg: ArchConfig,
            capacity_factor: float = 1.25, ep_spec=None
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [b, s, d] → (y: [b, s, d], aux_loss scalar).

    Single-device / unsharded path: PER-SAMPLE dispatch (vmap over batch) —
    top-k, position-in-expert cumsum and the scatter into [e, cap, d] all
    stay inside one sequence.  The distributed path is
    :func:`moe_ffn_ep` (explicit shard_map message passing); lm.py selects
    it when an ``ep_spec`` is configured.
    """
    if ep_spec is not None:
        return moe_ffn_ep(x, p, cfg, capacity_factor, ep_spec)
    b, s, d = x.shape
    e, k = cfg.moe_experts, cfg.moe_topk
    gates, eidx, aux = _route(x, p, cfg)
    cap = capacity(s, e, k, capacity_factor)

    def dispatch(xt, eix):
        flat_e = eix.reshape(-1)                           # [s*k]
        safe_pos, keep = _positions(flat_e, e, cap)
        xk = jnp.repeat(xt, k, axis=0)                     # [s*k, d]
        buf = jnp.zeros((e, cap, d), xt.dtype).at[flat_e, safe_pos].add(
            jnp.where(keep[:, None], xk, 0).astype(xt.dtype))
        return buf, flat_e, safe_pos, keep

    buf, flat_e, safe_pos, keep = jax.vmap(dispatch)(x, eidx)
    gate_h = jax.nn.silu(jnp.einsum("becd,edf->becf", buf, p["w_gate"]))
    up_h = jnp.einsum("becd,edf->becf", buf, p["w_up"])
    out = jnp.einsum("becf,efd->becd", gate_h * up_h, p["w_down"])

    def combine(o, fe, sp, kp, g):
        got = jnp.where(kp[:, None], o[fe, sp], 0)         # [s*k, d]
        return (got * g.reshape(-1)[:, None].astype(got.dtype)
                ).reshape(s, k, d).sum(1)

    y = jax.vmap(combine)(out, flat_e, safe_pos, keep, gates)
    return y, aux


def moe_ffn_ep(x: jnp.ndarray, p: Params, cfg: ArchConfig,
               capacity_factor: float, ep_spec) -> Tuple[jnp.ndarray, ...]:
    """Expert-parallel MoE as EXPLICIT shard_map message passing.

    §Perf iterations 1-2 (EXPERIMENTS.md): leaving the dispatch to GSPMD
    sharding constraints made the partitioner all-reduce the full
    [b, s·k, d] expanded-token tensor (~0.9 TB/device/step on moonshot) and
    re-all-gather it under remat.  This schedule is the paper's
    message-passing architecture instead — every device:

      1. all-gathers the (sequence-sharded) residual once — senders hold
         their full messages, like the NUMA cores hold their node features;
      2. routes + capacity-plans IDENTICALLY (replicated math, no wire);
      3. scatters ONLY the tokens destined to its own experts into its
         local [b_l, e_local, cap, d] buffer (the Block-Message build:
         sender-side selection, zero dispatch traffic);
      4. runs its experts;
      5. contributes partial outputs for every token and folds them with
         ONE psum_scatter back to the sequence-sharded residual (the
         delivery + local aggregation).

    Wire per layer = one all-gather + one reduce-scatter of the [b, s, d]
    activation — independent of top-k (the paper's compression argument:
    wire carries combined messages, not per-edge traffic).
    """
    b, s, d = x.shape
    e, k = cfg.moe_experts, cfg.moe_topk
    cap = capacity(s, e, k, capacity_factor)
    dp = ep_spec[0] if isinstance(ep_spec[0], tuple) else (ep_spec[0],)
    dp = tuple(a for a in dp if a)
    from jax.sharding import PartitionSpec as P_

    def body(x_l, router, wg, wu, wd):
        # x_l: [b_l, s_l, d] sequence-sharded slice
        x_full = jax.lax.all_gather(x_l, "model", axis=1, tiled=True)
        gates, eidx, aux = _route(x_full, {"router": router}, cfg)
        n_model = compat_axis_size("model")
        e_local = e // n_model
        j = jax.lax.axis_index("model")
        lo = j * e_local

        def dispatch(xt, eix, g):
            flat_e = eix.reshape(-1)                       # [s*k]
            safe_pos, keep = _positions(flat_e, e, cap)    # GLOBAL capacity
            mine = (flat_e >= lo) & (flat_e < lo + e_local)
            keep_l = keep & mine
            fe_l = jnp.where(mine, flat_e - lo, 0)
            xk = jnp.repeat(xt, k, axis=0)
            buf = jnp.zeros((e_local, cap, d), xt.dtype) \
                .at[fe_l, safe_pos].add(
                jnp.where(keep_l[:, None], xk, 0).astype(xt.dtype))
            return buf, fe_l, safe_pos, keep_l

        buf, fe_l, safe_pos, keep_l = jax.vmap(dispatch)(x_full, eidx, gates)
        gate_h = jax.nn.silu(jnp.einsum("becd,edf->becf", buf, wg))
        up_h = jnp.einsum("becd,edf->becf", buf, wu)
        out = jnp.einsum("becf,efd->becd", gate_h * up_h, wd)

        def combine(o, fe, sp, kp, g):
            got = jnp.where(kp[:, None], o[fe, sp], 0)     # [s*k, d]
            return (got * g.reshape(-1)[:, None].astype(got.dtype)
                    ).reshape(s, k, d).sum(1)

        y_partial = jax.vmap(combine)(out, fe_l, safe_pos, keep_l, gates)
        # fold partial expert outputs + return to the s-sharded residual
        y = jax.lax.psum_scatter(y_partial, "model", scatter_dimension=1,
                                 tiled=True)
        # aux leaves the region device-varying ([1] per device) and is
        # averaged outside — a replicated (P()) output would need an in-body
        # pmean, whose transpose chokes on symbolic-Zero cotangents when aux
        # is unused by the loss (older shard_map); the mean outside is the
        # same value and differentiates on every jax we support
        return y, aux[None]

    y, aux = compat_shard_map(
        body,
        in_specs=(P_(dp, "model", None), P_(), P_("model", None, None),
                  P_("model", None, None), P_("model", None, None)),
        out_specs=(P_(dp, "model", None), P_(dp + ("model",))),
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y, aux.mean()


# ---------------------------------------------------------------------------
# MoE decoder stacks
# ---------------------------------------------------------------------------
from .transformer import (KVCache, attn_block, causal_mask,  # noqa: E402
                          decode_attn_block, dense_block, global_flags,
                          h_params, init_attn_params, init_dense_layer,
                          init_ffn_params, maybe_sp, rmsnorm, stack_layers,
                          swiglu)


def init_moe_layer(key, cfg: ArchConfig, dtype=jnp.bfloat16) -> Params:
    k1, k2 = jax.random.split(key)
    pl = init_attn_params(k1, cfg, dtype)
    pl.update(init_moe_params(k2, cfg, dtype))
    pl["ln_attn"] = jnp.zeros((cfg.d_model,), dtype)
    pl["ln_ffn"] = jnp.zeros((cfg.d_model,), dtype)
    return pl


def init_moe_stack_params(key, cfg: ArchConfig, dtype=jnp.bfloat16) -> Params:
    """llama4 style (interleave=2): scan over (dense, moe) PAIRS; moonshot
    style (interleave=1): scan over moe layers only."""
    k_emb, k_a, k_b, k_head = jax.random.split(key, 4)
    params: Params = {
        "embed": _norm_init(k_emb, (cfg.vocab, cfg.d_model), 0.02, dtype),
        "ln_final": jnp.zeros((cfg.d_model,), dtype),
    }
    if cfg.moe_interleave == 2:
        n_pairs = cfg.n_layers // 2
        params["dense_layers"] = stack_layers(
            k_a, n_pairs, lambda k: init_dense_layer(k, cfg, dtype))
        params["moe_layers"] = stack_layers(
            k_b, n_pairs, lambda k: init_moe_layer(k, cfg, dtype))
    else:
        params["moe_layers"] = stack_layers(
            k_a, cfg.n_layers, lambda k: init_moe_layer(k, cfg, dtype))
    if not cfg.tie_embeddings:
        params["lm_head"] = _norm_init(k_head, (cfg.d_model, cfg.vocab),
                                       cfg.d_model ** -0.5, dtype)
    return params


def _moe_block(x, p, cfg, w_eff, positions, cf=1.25, ep_spec=None):
    h = x + attn_block(rmsnorm(x, p["ln_attn"], cfg.norm_eps), p, cfg,
                       w_eff, positions)
    y, aux = moe_ffn(rmsnorm(h, p["ln_ffn"], cfg.norm_eps), p, cfg,
                     capacity_factor=cf, ep_spec=ep_spec)
    return h + y, aux


def moe_forward(params: Params, tokens: jnp.ndarray, cfg: ArchConfig,
                *, embeddings: Optional[jnp.ndarray] = None,
                capacity_factor: float = 1.25, remat: bool = False,
                sp_spec=None, ep_spec=None, last_logits: bool = False
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """→ (logits [b, s, vocab] f32, aux_loss scalar)."""
    b, s = tokens.shape[:2]
    x = embeddings if embeddings is not None \
        else jnp.take(params["embed"], tokens, axis=0)
    positions = jnp.arange(s)[None, :]
    x = maybe_sp(x, sp_spec)

    if cfg.moe_interleave == 2:
        def body(carry, layer):
            h, aux = carry
            pd, pm = layer
            h = dense_block(h, pd, cfg, None, positions, sp_spec)
            h, a = _moe_block(h, pm, cfg, None, positions, capacity_factor,
                              ep_spec)
            return (maybe_sp(h, sp_spec), aux + a), ()
        if remat:
            body = jax.checkpoint(body)
        (x, aux), _ = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)),
            (params["dense_layers"], params["moe_layers"]))
    else:
        def body(carry, p):
            h, aux = carry
            h, a = _moe_block(h, p, cfg, None, positions, capacity_factor,
                              ep_spec)
            return (maybe_sp(h, sp_spec), aux + a), ()
        if remat:
            body = jax.checkpoint(body)
        (x, aux), _ = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), params["moe_layers"])

    if last_logits:
        x = x[:, -1:]
    x = rmsnorm(x, params["ln_final"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = jnp.einsum("bsd,dv->bsv", x, head,
                        preferred_element_type=jnp.float32)
    return logits, aux / cfg.n_layers


def moe_decode_step(params: Params, cache: KVCache, token: jnp.ndarray,
                    pos: jnp.ndarray, cfg: ArchConfig,
                    capacity_factor: float = 1.25
                    ) -> Tuple[jnp.ndarray, KVCache]:
    """One-token decode; cache spans ALL attention layers in stack order
    (interleave=2 ⇒ cache[2i] = dense layer i, cache[2i+1] = moe layer i)."""
    x = jnp.take(params["embed"], token, axis=0)
    always_global = jnp.ones((), bool)

    def attn_then(h, p, kc, vc):
        xin = rmsnorm(h, p["ln_attn"], cfg.norm_eps)
        att, kc, vc = decode_attn_block(xin, p, cfg, kc, vc, pos,
                                        always_global)
        return h + att, kc, vc

    if cfg.moe_interleave == 2:
        n_pairs = cfg.n_layers // 2
        kd, km = cache.k[0::2], cache.k[1::2]
        vd, vm = cache.v[0::2], cache.v[1::2]

        def body(h, layer):
            pd, pm, kcd, vcd, kcm, vcm = layer
            h, kcd, vcd = attn_then(h, pd, kcd, vcd)
            h = h + swiglu(rmsnorm(h, pd["ln_ffn"], cfg.norm_eps),
                           h_params(pd))
            h, kcm, vcm = attn_then(h, pm, kcm, vcm)
            y, _ = moe_ffn(rmsnorm(h, pm["ln_ffn"], cfg.norm_eps), pm, cfg,
                           capacity_factor=capacity_factor)
            return h + y, (kcd, vcd, kcm, vcm)

        x, (nkd, nvd, nkm, nvm) = jax.lax.scan(
            body, x, (params["dense_layers"], params["moe_layers"],
                      kd, vd, km, vm))
        new_k = jnp.stack([nkd, nkm], 1).reshape(cache.k.shape)
        new_v = jnp.stack([nvd, nvm], 1).reshape(cache.v.shape)
    else:
        def body(h, layer):
            p, kc, vc = layer
            h, kc, vc = attn_then(h, p, kc, vc)
            y, _ = moe_ffn(rmsnorm(h, p["ln_ffn"], cfg.norm_eps), p, cfg,
                           capacity_factor=capacity_factor)
            return h + y, (kc, vc)
        x, (new_k, new_v) = jax.lax.scan(
            body, x, (params["moe_layers"], cache.k, cache.v))

    x = rmsnorm(x, params["ln_final"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = jnp.einsum("bsd,dv->bsv", x, head,
                        preferred_element_type=jnp.float32)
    return logits, KVCache(k=new_k, v=new_v)
