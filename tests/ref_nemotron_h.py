"""Plain float32 reference of the Nemotron-H forward pass (logits).

Written from the published description (Nemotron-H's ``config.json``
and the layer equations of Mamba-2, GQA attention and a top-k MoE), in
straightforward ``jax.numpy`` under ``jax.default_matmul_precision("highest")``
and independent of ``repro.models``; it only reads the same parameter tree.

* Mamba-2: the exact per-step recurrence, one token after another (no
  chunks): ``h_t = exp(dt_t a) h_{t-1} + dt_t x_t B_t^T``,
  ``y_t = h_t C_t + D x_t``, heads sharing B and C within their group.
* MoE: every token computed over its own top-k experts, with no capacity
  limit, plus the shared expert.
* Attention: causal GQA, softmax over all earlier positions, no RoPE.

Departure noted: the norms' scale is ``1 + w`` (the repository's
parameterisation of the published ``w``, initialised at 0 for 1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import MIXER_ATTN, MIXER_MAMBA, FFN_MOE

F32 = jnp.float32


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + w)


def conv_causal(u, w, b):
    """out[t] = b + sum_i w[i] u[t - 3 + i], zeros before the start."""
    s = u.shape[1]
    up = jnp.pad(u, ((0, 0), (3, 0), (0, 0)))
    return b + sum(w[i] * up[:, i:i + s] for i in range(4))


def mamba2(p, h, cfg):
    H, P, N, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    di = H * P
    b, s, _ = h.shape
    proj = h @ p["w_in"]
    z, xbc, dt = proj[..., :di], proj[..., di:-H], proj[..., -H:]
    xbc = jax.nn.silu(conv_causal(xbc, p["conv"], p["conv_b"]))
    x = xbc[..., :di].reshape(b, s, H, P)
    Bg = xbc[..., di:di + G * N].reshape(b, s, G, N)
    Cg = xbc[..., di + G * N:].reshape(b, s, G, N)
    grp = jnp.arange(H) // (H // G)                     # head -> its group
    Bh, Ch = Bg[:, :, grp], Cg[:, :, grp]               # [b,s,H,N]
    dt = jax.nn.softplus(dt + p["dt_bias"])             # [b,s,H]
    a = -jnp.exp(p["a_log"])

    def step(state, t):                                 # state [b,H,P,N]
        xt, bt, ct, dtt = t
        state = state * jnp.exp(dtt * a)[..., None, None] + \
            (dtt[..., None] * xt)[..., None] * bt[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct)

    seq = (x.swapaxes(0, 1), Bh.swapaxes(0, 1), Ch.swapaxes(0, 1),
           dt.swapaxes(0, 1))
    _, y = jax.lax.scan(step, jnp.zeros((b, H, P, N), F32), seq)
    y = y.swapaxes(0, 1) + x * p["d_skip"][:, None]     # [b,s,H,P]
    y = y.reshape(b, s, di) * jax.nn.silu(z)
    yg = y.reshape(b, s, G, di // G)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) +
                            cfg.norm_eps)
    return (yg.reshape(b, s, di) * (1 + p["norm"])) @ p["w_out"]


def attention(p, h, cfg):
    b, s, _ = h.shape
    H, K, hd = cfg.n_heads, cfg.n_kv, cfg.hd
    q = (h @ p["wq"]).reshape(b, s, H, hd)
    k = (h @ p["wk"]).reshape(b, s, K, hd)
    v = (h @ p["wv"]).reshape(b, s, K, hd)
    kv = jnp.arange(H) // (H // K)                      # query head -> kv head
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k[:, :, kv]) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal, sc, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v[:, :, kv])
    return o.reshape(b, s, H * hd) @ p["wo"]


def relu2_mlp(x, w_up, w_down):
    return jnp.square(jax.nn.relu(x @ w_up)) @ w_down


def moe(p, h, cfg):
    m = cfg.moe
    b, s, d = h.shape
    x = h.reshape(b * s, d)
    scores = jax.nn.sigmoid(x @ p["router"])            # [T,E]
    _, top = jax.lax.top_k(scores + p["router_bias"], m.top_k)
    w = jnp.take_along_axis(scores, top, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * m.routed_scale

    def one_token(xt, et, wt):                          # its own top-k only
        up, down = p["w_up"][et], p["w_down"][et]       # [k,d,de], [k,de,d]
        hk = jnp.square(jax.nn.relu(jnp.einsum("d,kdf->kf", xt, up)))
        return jnp.einsum("k,kf,kfd->d", wt, hk, down)

    out = jax.vmap(one_token)(x, top, w)
    sh = p["shared"]
    for i in range(m.num_shared):
        out = out + relu2_mlp(x, sh["w_up"][i], sh["w_down"][i])
    return out.reshape(b, s, d)


def logits(params, tokens, cfg):
    """[B, S, V] logits of the float32 forward pass."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(lambda a: a.astype(F32), params)
        x = params["embed"][tokens]
        for i, desc in enumerate(cfg.pattern * cfg.n_groups):
            g, j = divmod(i, cfg.period)
            lp = jax.tree_util.tree_map(lambda a: a[g],
                                        params["groups"][f"slot{j}"])
            if desc.mixer == MIXER_MAMBA:
                x = x + mamba2(lp["mamba"], rms(x, lp["norm1"], cfg.norm_eps),
                               cfg)
            elif desc.mixer == MIXER_ATTN:
                x = x + attention(lp["attn"],
                                  rms(x, lp["norm1"], cfg.norm_eps), cfg)
            if desc.ffn == FFN_MOE:
                x = x + moe(lp["moe"], rms(x, lp["norm2"], cfg.norm_eps), cfg)
        h = rms(x, params["final_norm"], cfg.norm_eps)
        return h @ params["lm_head"].T
