"""Mixture-of-Experts FFN (GShard-style top-k routing with capacity).

TPU-native formulation: tokens are grouped (the group axis shards over
data), gating produces a [G, S_g, E, C] dispatch one-hot built from a
position-in-expert cumsum, and expert compute is two einsums whose expert
axis shards over the "model" mesh axis (EP).  Dropped tokens (over
capacity) pass through the residual — standard capacity-factor semantics.

Supports deepseek's always-on shared experts and arctic's parallel dense
residual (wired in blocks.py); Nemotron-H's non-gated relu^2 experts,
its sigmoid router (top-k chosen by score plus a selection-only bias, the
chosen scores renormalised and scaled) and a shared expert of its own
width.  An auxiliary load-balancing loss is returned for training.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MoEConfig


def init_moe(key, cfg: ArchConfig, dtype) -> Dict[str, Any]:
    m = cfg.moe
    d = cfg.d_model
    de = m.d_expert or cfg.d_ff
    ks = jax.random.split(key, 5)
    gated = m.activation != "relu2"
    p = {
        "router": jax.random.normal(ks[0], (d, m.num_experts),
                                    jnp.float32) * d ** -0.5,
        "w_up": jax.random.normal(ks[2], (m.num_experts, d, de), dtype) * d ** -0.5,
        "w_down": jax.random.normal(ks[3], (m.num_experts, de, d), dtype) * de ** -0.5,
    }
    if gated:
        p["w_gate"] = jax.random.normal(ks[1], (m.num_experts, d, de),
                                        dtype) * d ** -0.5
    if m.router == "sigmoid":
        p["router_bias"] = jnp.zeros((m.num_experts,), jnp.float32)
    if m.num_shared:
        ds = m.d_shared or de
        p["shared"] = {
            "w_up": jax.random.normal(jax.random.fold_in(ks[4], 1),
                                      (m.num_shared, d, ds), dtype) * d ** -0.5,
            "w_down": jax.random.normal(jax.random.fold_in(ks[4], 2),
                                        (m.num_shared, ds, d), dtype) * ds ** -0.5,
        }
        if gated:
            p["shared"]["w_gate"] = jax.random.normal(
                ks[4], (m.num_shared, d, ds), dtype) * d ** -0.5
    return p


def _expert_act(m: MoEConfig, x, w_gate, w_up, spec: str):
    """The experts' hidden activation: silu(x W_gate) * (x W_up), or
    relu(x W_up)^2 for non-gated experts."""
    if m.activation == "relu2":
        return jnp.square(jax.nn.relu(jnp.einsum(spec, x, w_up)))
    return jax.nn.silu(jnp.einsum(spec, x, w_gate)) * jnp.einsum(spec, x, w_up)


def _capacity(m: MoEConfig, group_size: int) -> int:
    c = int(group_size * m.top_k * m.capacity_factor / m.num_experts)
    return max(c, 1)


def moe_block(p: Dict[str, Any], x: jnp.ndarray, cfg: ArchConfig
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B, S, D] -> (out [B, S, D], aux_loss scalar)."""
    m = cfg.moe
    b, s, d = x.shape
    e, k = m.num_experts, m.top_k
    tokens = x.reshape(b * s, d)
    # groups: keep group dim == batch (shards over "data"); group_size == S
    g, sg = b, s
    xt = x                                          # [G, Sg, D]
    cap = _capacity(m, sg)

    logits = (xt.astype(jnp.float32) @ p["router"])                 # [G,Sg,E]
    if m.router == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        _, topk_i = jax.lax.top_k(probs + p["router_bias"], k)
        topk_p = jnp.take_along_axis(probs, topk_i, axis=-1)
        topk_p = topk_p / (topk_p.sum(-1, keepdims=True) + 1e-20)
        topk_p = topk_p * m.routed_scale
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        topk_p, topk_i = jax.lax.top_k(probs, k)                    # [G,Sg,k]
        topk_p = topk_p / jnp.maximum(topk_p.sum(-1, keepdims=True), 1e-9)

    # aux load-balance loss (Switch): E * sum_e f_e * p_e
    me = probs.mean(axis=(0, 1))                                    # [E]
    ce = jax.nn.one_hot(topk_i[..., 0], e).mean(axis=(0, 1))
    aux = (me * ce).sum() * e

    # position-in-expert via cumsum over the flattened (slot-major) stream
    onehot = jax.nn.one_hot(topk_i, e, dtype=jnp.float32)           # [G,Sg,k,E]
    flat = onehot.transpose(0, 2, 1, 3).reshape(g, k * sg, e)       # slot-major
    pos = jnp.cumsum(flat, axis=1) - flat                           # [G,k*Sg,E]
    pos = pos.reshape(g, k, sg, e).transpose(0, 2, 1, 3)            # [G,Sg,k,E]
    pos_in_e = (pos * onehot).sum(-1)                               # [G,Sg,k]
    keep = (pos_in_e < cap) & (topk_p > 0)
    gate = topk_p * keep

    # dispatch/combine tensors [G, Sg, E, C]
    pos_oh = jax.nn.one_hot(pos_in_e, cap, dtype=jnp.float32)       # [G,Sg,k,C]
    disp = jnp.einsum("gske,gskc->gsec", onehot * keep[..., None], pos_oh)
    comb = jnp.einsum("gske,gskc,gsk->gsec", onehot, pos_oh, gate)

    dt = x.dtype
    xe = jnp.einsum("gsd,gsec->ecgd", xt, disp.astype(dt))          # [E,C,G,D]
    xe = xe.reshape(e, cap * g, d)
    hh = _expert_act(m, xe, p.get("w_gate"), p["w_up"], "ead,edf->eaf")
    ye = jnp.einsum("eaf,efd->ead", hh, p["w_down"])                # [E,C*G,D]
    ye = ye.reshape(e, cap, g, d)
    out = jnp.einsum("ecgd,gsec->gsd", ye, comb.astype(dt))         # [G,Sg,D]

    if m.num_shared and "shared" in p:
        sh = p["shared"]
        hs = _expert_act(m, xt, sh.get("w_gate"), sh["w_up"], "gsd,ndf->ngsf")
        out = out + jnp.einsum("ngsf,nfd->gsd", hs, sh["w_down"])
    return out.astype(dt), aux.astype(jnp.float32)
