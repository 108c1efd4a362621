"""SSM / recurrent mixers: Mamba (SSD form), mLSTM, sLSTM.

TPU adaptation (DESIGN.md §3): Mamba's selective scan and mLSTM's matrix
memory are both instances of *gated linear attention*; we implement one
chunkwise-parallel core (`gla_chunked`) that processes the sequence in
chunks with MXU-shaped intra-chunk einsums and an O(1)-per-chunk carried
state — per-position states are never materialized (they would be
``S·d_inner·N`` bytes).  Decode is the exact single-step recurrence.

Numerical simplifications vs. the source papers, recorded here and in
DESIGN.md §8: mLSTM/sLSTM use sigmoid input gates instead of stabilized
exponential gating (the max-stabilizer m_t is unnecessary with bounded
gates); Mamba uses the scalar-decay-per-head SSD parameterization
(Mamba-2) rather than Mamba-1's diagonal A, which is the TPU-native form.

With ``cfg.ssm_groups`` set, ``mamba_block`` is Mamba-2's own layer, as
Nemotron-H has it: one in-projection to (z, x, B, C, dt), the causal conv
(with bias) over x, B and C together, B and C per group of heads, and a
gated RMSNorm, ``norm(y * silu(z))`` per group, before the out-projection.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig


# ---------------------------------------------------------------- GLA core
def gla_chunked(q, k, v, log_g, s_in, state0, norm0=None, *, chunk: int = 256):
    """Chunkwise gated linear attention.

    q,k: [B,S,H,dk]; v: [B,S,H,dv]; log_g,s_in: [B,S,H] (log-decay<=0,
    input scale).  state0: [B,H,dk,dv]; norm0: [B,H,dk] or None.
    Recurrence (inclusive): S_t = g_t S_{t-1} + s_t k_t v_t^T ; y_t = q_t·S_t
    with optional normalizer n_t = g_t n_{t-1} + s_t k_t, y /= max(|q·n|,1).
    Returns (y [B,S,H,dv], state_end, norm_end).
    """
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    c = min(chunk, s)
    n = s // c
    assert n * c == s, (s, c)
    f32 = jnp.float32

    def resh(x):
        return x.reshape(b, n, c, *x.shape[2:]).transpose(1, 0, 2, *range(3, x.ndim + 1))

    qs, ks, vs = resh(q.astype(f32)), resh(k.astype(f32)), resh(v.astype(f32))
    lgs, sins = resh(log_g.astype(f32)), resh(s_in.astype(f32))
    use_norm = norm0 is not None
    norm0 = norm0 if use_norm else jnp.zeros((b, h, dk), f32)

    def step(carry, xs):
        S_prev, n_prev = carry
        qb, kb, vb, lgb, sb = xs           # [B,c,H,*]
        lg = jnp.cumsum(lgb, axis=1)       # inclusive cumulative log decay
        # intra-chunk: A[b,h,i,j] = (q_i.k_j) exp(lg_i - lg_j) s_j  (j<=i)
        qk = jnp.einsum("bihd,bjhd->bhij", qb, kb)
        dec = lg.transpose(0, 2, 1)[:, :, :, None] - \
            lg.transpose(0, 2, 1)[:, :, None, :]
        mask = jnp.tril(jnp.ones((c, c), bool))
        A = qk * jnp.exp(jnp.where(mask, dec, 0.0)) * \
            sb.transpose(0, 2, 1)[:, :, None, :]
        A = jnp.where(mask[None, None], A, 0.0)
        y_intra = jnp.einsum("bhij,bjhv->bihv", A, vb)
        # inter-chunk
        qdec = qb * jnp.exp(lg)[..., None]
        y_inter = jnp.einsum("bihd,bhdv->bihv", qdec, S_prev)
        y = y_intra + y_inter
        if use_norm:
            den_intra = jnp.einsum("bhij,bjhd->bihd", A, kb)
            den = jnp.einsum("bihd,bihd->bih", qb, den_intra) + \
                jnp.einsum("bihd,bhd->bih", qdec, n_prev)
            y = y / jnp.maximum(jnp.abs(den), 1.0)[..., None]
        # state update
        total = lg[:, -1]                  # [B,H]
        wdec = jnp.exp(total[:, None, :] - lg) * sb   # [B,c,H]
        S_new = S_prev * jnp.exp(total)[..., None, None] + \
            jnp.einsum("bjhd,bjhv,bjh->bhdv", kb, vb, wdec)
        n_new = n_prev * jnp.exp(total)[..., None] + \
            jnp.einsum("bjhd,bjh->bhd", kb, wdec)
        return (S_new, n_new), y

    (S_end, n_end), ys = jax.lax.scan(step, (state0.astype(f32), norm0),
                                      (qs, ks, vs, lgs, sins))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(b, s, h, dv)
    return y.astype(v.dtype), S_end, (n_end if use_norm else None)


def gla_step(q, k, v, log_g, s_in, state, norm=None):
    """Exact single-token recurrence (decode).

    q,k: [B,1,H,dk]; v: [B,1,H,dv]; log_g,s_in: [B,1,H].
    """
    f32 = jnp.float32
    g = jnp.exp(log_g.astype(f32))[:, 0]                  # [B,H]
    s = s_in.astype(f32)[:, 0]
    kv = jnp.einsum("bhd,bhv->bhdv", k[:, 0].astype(f32), v[:, 0].astype(f32))
    S_new = state * g[..., None, None] + kv * s[..., None, None]
    y = jnp.einsum("bhd,bhdv->bhv", q[:, 0].astype(f32), S_new)
    n_new = None
    if norm is not None:
        n_new = norm * g[..., None] + k[:, 0].astype(f32) * s[..., None]
        den = jnp.einsum("bhd,bhd->bh", q[:, 0].astype(f32), n_new)
        y = y / jnp.maximum(jnp.abs(den), 1.0)[..., None]
    return y[:, None].astype(v.dtype), S_new, n_new


# ------------------------------------------------------------------- mamba
def _conv_dim(cfg: ArchConfig) -> int:
    """Channels of Mamba's causal conv: x alone, or x, B and C (Mamba-2)."""
    if cfg.ssm_groups:
        return cfg.ssm_inner + 2 * cfg.ssm_groups * cfg.ssm_state
    return cfg.ssm_inner


def init_mamba(key, cfg: ArchConfig, dtype) -> Dict[str, Any]:
    d = cfg.d_model
    di = cfg.ssm_inner
    hs = cfg.ssm_nheads
    N = cfg.ssm_state
    ks = jax.random.split(key, 6)
    if cfg.ssm_groups:
        cd = _conv_dim(cfg)
        return {
            "w_in": jax.random.normal(ks[0], (d, di + cd + hs), dtype) * d ** -0.5,
            "conv": jax.random.normal(ks[1], (4, cd), dtype) * 0.2,
            "conv_b": jnp.zeros((cd,), dtype),
            "dt_bias": jnp.zeros((hs,), jnp.float32),
            "a_log": jnp.zeros((hs,), jnp.float32),
            "d_skip": jnp.ones((hs,), jnp.float32),
            "norm": jnp.zeros((di,), dtype),
            "w_out": jax.random.normal(ks[4], (di, d), dtype) * di ** -0.5,
        }
    return {
        "w_in": jax.random.normal(ks[0], (d, 2 * di), dtype) * d ** -0.5,
        "conv": jax.random.normal(ks[1], (4, di), dtype) * 0.2,
        "w_bc": jax.random.normal(ks[2], (d, 2 * N), dtype) * d ** -0.5,
        "w_dt": jax.random.normal(ks[3], (d, hs), dtype) * d ** -0.5,
        "dt_bias": jnp.zeros((hs,), jnp.float32),
        "a_log": jnp.zeros((hs,), jnp.float32),           # a = -exp(a_log)
        "d_skip": jnp.ones((hs,), jnp.float32),
        "w_out": jax.random.normal(ks[4], (di, d), dtype) * di ** -0.5,
    }


def _causal_conv(x, w, conv_state=None, bias=None):
    """Depthwise causal conv, width 4.  x: [B,S,C]; w: [4,C]; bias: [C].

    With ``conv_state`` [B,3,C] (decode), prepends it instead of zeros and
    returns the updated state.
    """
    b, s, cdim = x.shape
    if conv_state is None:
        pad = jnp.zeros((b, 3, cdim), x.dtype)
    else:
        pad = conv_state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)                # [B,S+3,C]
    out = sum(xp[:, i:i + s] * w[i][None, None, :] for i in range(4))
    if bias is not None:
        out = out + bias[None, None, :]
    new_state = xp[:, -3:]
    return out, new_state


def mamba_block(p, x, cfg: ArchConfig, state=None, decode: bool = False):
    """x: [B,S,D] -> (y [B,S,D], new_state).

    state = {"ssm": [B,H,N,P], "conv": [B,3,di]} (decode) or None (train).
    """
    if cfg.ssm_groups:
        return _mamba2_block(p, x, cfg, state, decode)
    b, s, d = x.shape
    di, hs = cfg.ssm_inner, cfg.ssm_nheads
    N = cfg.ssm_state
    P = di // hs
    zx = x @ p["w_in"]
    z, xin = jnp.split(zx, 2, axis=-1)
    conv_state = state["conv"] if state is not None else None
    xin, new_conv = _causal_conv(xin, p["conv"], conv_state)
    xin = jax.nn.silu(xin)
    bc = x @ p["w_bc"]
    Bm, Cm = jnp.split(bc, 2, axis=-1)                    # [B,S,N]
    dt = jax.nn.softplus((x @ p["w_dt"]).astype(jnp.float32) +
                         p["dt_bias"])                    # [B,S,H]
    xh = xin.reshape(b, s, hs, P)
    Bh = jnp.broadcast_to(Bm[:, :, None, :], (b, s, hs, N))
    Ch = jnp.broadcast_to(Cm[:, :, None, :], (b, s, hs, N))
    y, S_end = _ssd(p, xh, Bh, Ch, dt, state, decode, cfg.ssm_chunk)
    y = (y.reshape(b, s, di) * jax.nn.silu(z)) @ p["w_out"]
    return y, {"ssm": S_end, "conv": new_conv}


def _ssd(p, xh, Bh, Ch, dt, state, decode: bool, chunk: int):
    """The SSD recurrence over heads, chunked (train, prefill) or one step
    (decode), plus the D skip.  xh: [B,S,H,P]; Bh, Ch: [B,S,H,N]; dt:
    [B,S,H].  Returns (y [B,S,H,P], final state [B,H,N,P])."""
    b, s, hs, P = xh.shape
    log_g = dt * -jnp.exp(p["a_log"])[None, None, :]
    ssm_state = state["ssm"] if state is not None else \
        jnp.zeros((b, hs, Bh.shape[-1], P), jnp.float32)
    if decode:
        y, S_end, _ = gla_step(Ch, Bh, xh, log_g, dt, ssm_state)
    else:
        y, S_end, _ = gla_chunked(Ch, Bh, xh, log_g, dt, ssm_state,
                                  chunk=chunk)
    return y + xh * p["d_skip"][None, None, :, None].astype(y.dtype), S_end


def gated_rmsnorm(scale, y, z, groups: int, eps: float):
    """Mamba-2's gated norm: RMSNorm of ``y * silu(z)`` over each of
    ``groups`` equal slices of the last axis, times ``1 + scale``."""
    dt = y.dtype
    h = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    hg = h.reshape(*h.shape[:-1], groups, h.shape[-1] // groups)
    hg = hg * jax.lax.rsqrt(jnp.mean(hg * hg, axis=-1, keepdims=True) + eps)
    return (hg.reshape(h.shape) * (1.0 + scale.astype(jnp.float32))).astype(dt)


def _mamba2_block(p, x, cfg: ArchConfig, state, decode: bool):
    """Mamba-2 with B and C per group (module docstring)."""
    b, s, _ = x.shape
    di, hs, N, G = cfg.ssm_inner, cfg.ssm_nheads, cfg.ssm_state, cfg.ssm_groups
    P = di // hs
    z, xbc, dt = jnp.split(x @ p["w_in"], [di, di + _conv_dim(cfg)], axis=-1)
    conv_state = state["conv"] if state is not None else None
    xbc, new_conv = _causal_conv(xbc, p["conv"], conv_state, p["conv_b"])
    xin, Bm, Cm = jnp.split(jax.nn.silu(xbc), [di, di + G * N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # [B,S,H]
    xh = xin.reshape(b, s, hs, P)
    # head h reads group h // (hs / G)
    Bh = jnp.repeat(Bm.reshape(b, s, G, N), hs // G, axis=2)
    Ch = jnp.repeat(Cm.reshape(b, s, G, N), hs // G, axis=2)
    y, S_end = _ssd(p, xh, Bh, Ch, dt, state, decode, cfg.ssm_chunk)
    y = gated_rmsnorm(p["norm"], y.reshape(b, s, di), z, G, cfg.norm_eps)
    return y @ p["w_out"], {"ssm": S_end, "conv": new_conv}


def mamba_state_shape(cfg: ArchConfig, batch: int):
    hs = cfg.ssm_nheads
    return {"ssm": (batch, hs, cfg.ssm_state, cfg.ssm_inner // hs),
            "conv": (batch, 3, _conv_dim(cfg))}


# ------------------------------------------------------------------- mLSTM
def init_mlstm(key, cfg: ArchConfig, dtype) -> Dict[str, Any]:
    d = cfg.d_model
    di = 2 * d
    ks = jax.random.split(key, 6)
    return {
        "wq": jax.random.normal(ks[0], (d, di), dtype) * d ** -0.5,
        "wk": jax.random.normal(ks[1], (d, di), dtype) * d ** -0.5,
        "wv": jax.random.normal(ks[2], (d, di), dtype) * d ** -0.5,
        "wz": jax.random.normal(ks[3], (d, di), dtype) * d ** -0.5,
        "w_gates": jax.random.normal(ks[4], (d, 2 * cfg.n_heads),
                                     dtype) * d ** -0.5,
        "w_out": jax.random.normal(ks[5], (di, d), dtype) * di ** -0.5,
    }


def mlstm_block(p, x, cfg: ArchConfig, state=None, decode: bool = False):
    """xLSTM mLSTM (matrix memory).  state = {"S": [B,H,dk,dv], "n": [B,H,dk]}."""
    b, s, d = x.shape
    di = 2 * d
    h = cfg.n_heads
    dk = di // h
    q = (x @ p["wq"]).reshape(b, s, h, dk) * dk ** -0.5
    k = (x @ p["wk"]).reshape(b, s, h, dk)
    v = (x @ p["wv"]).reshape(b, s, h, dk)
    gates = x @ p["w_gates"]
    f_pre, i_pre = jnp.split(gates, 2, axis=-1)           # [B,S,H]
    log_g = jax.nn.log_sigmoid(f_pre.astype(jnp.float32))
    s_in = jax.nn.sigmoid(i_pre.astype(jnp.float32))
    S0 = state["S"] if state is not None else jnp.zeros((b, h, dk, dk), jnp.float32)
    n0 = state["n"] if state is not None else jnp.zeros((b, h, dk), jnp.float32)
    if decode:
        y, S_end, n_end = gla_step(q, k, v, log_g, s_in, S0, n0)
    else:
        y, S_end, n_end = gla_chunked(q, k, v, log_g, s_in, S0, n0, chunk=256)
    z = jax.nn.silu(x @ p["wz"])
    y = (y.reshape(b, s, di) * z) @ p["w_out"]
    return y, {"S": S_end, "n": n_end}


def mlstm_state_shape(cfg: ArchConfig, batch: int):
    dk = 2 * cfg.d_model // cfg.n_heads
    return {"S": (batch, cfg.n_heads, dk, dk), "n": (batch, cfg.n_heads, dk)}


# ------------------------------------------------------------------- sLSTM
def init_slstm(key, cfg: ArchConfig, dtype) -> Dict[str, Any]:
    d = cfg.d_model
    h = cfg.n_heads
    hd = d // h
    ks = jax.random.split(key, 3)
    return {
        "w_x": jax.random.normal(ks[0], (d, 4 * d), dtype) * d ** -0.5,
        "r_h": jax.random.normal(ks[1], (h, hd, 4 * hd), dtype) * hd ** -0.5,
        "w_out": jax.random.normal(ks[2], (d, d), dtype) * d ** -0.5,
    }


def slstm_block(p, x, cfg: ArchConfig, state=None, decode: bool = False):
    """True scalar recurrence (lax.scan over time).

    state = {"c": [B,D], "n": [B,D], "h": [B,D]}.
    """
    b, s, d = x.shape
    h = cfg.n_heads
    hd = d // h
    xg = x @ p["w_x"]                                      # [B,S,4D]
    if state is None:
        state = {"c": jnp.zeros((b, d), jnp.float32),
                 "n": jnp.zeros((b, d), jnp.float32),
                 "h": jnp.zeros((b, d), jnp.float32)}

    r_h = p["r_h"].astype(jnp.float32)

    def step(carry, xt):
        c, n, hh = carry
        hr = hh.reshape(b, h, hd)
        rec = jnp.einsum("bhd,hdf->bhf", hr, r_h).reshape(b, 4 * d)
        zifo = xt.astype(jnp.float32) + rec
        z, i, f, o = jnp.split(zifo, 4, axis=-1)
        z = jnp.tanh(z)
        i = jax.nn.sigmoid(i)
        f = jax.nn.sigmoid(f)
        o = jax.nn.sigmoid(o)
        c = f * c + i * z
        n = f * n + i
        hh = o * c / jnp.maximum(n, 1.0)
        return (c, n, hh), hh

    (c, n, hh), ys = jax.lax.scan(step, (state["c"], state["n"], state["h"]),
                                  xg.transpose(1, 0, 2))
    y = ys.transpose(1, 0, 2).astype(x.dtype) @ p["w_out"]
    return y, {"c": c, "n": n, "h": hh}


def slstm_state_shape(cfg: ArchConfig, batch: int):
    d = cfg.d_model
    return {"c": (batch, d), "n": (batch, d), "h": (batch, d)}
