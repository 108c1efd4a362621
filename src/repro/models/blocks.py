"""Layer-group assembly: LayerDesc -> parameterized block functions.

A *group* is one period of the architecture's layer pattern (DESIGN.md §4);
the model scans over ``n_groups`` stacked copies.  Each layer in a group is
pre-norm: ``x + mixer(norm(x))`` then ``x + ffn(norm(x))`` (plus MoE aux
loss and, for arctic, the parallel dense residual); a layer without a
mixer (``MIXER_NONE``) or without an FFN (``FFN_NONE``) skips that half.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ArchConfig, FFN_DENSE, FFN_MOE,
                                FFN_MOE_DENSE, FFN_NONE, LayerDesc,
                                MIXER_ATTN, MIXER_ATTN_LOCAL, MIXER_MAMBA,
                                MIXER_MLSTM, MIXER_NONE, MIXER_SLSTM)
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S


def init_layer(key, cfg: ArchConfig, desc: LayerDesc, dtype,
               cross_attn: bool = False) -> Dict[str, Any]:
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {}
    if desc.mixer != MIXER_NONE:
        p["norm1"] = jnp.zeros((cfg.d_model,), dtype)
    if desc.mixer in (MIXER_ATTN, MIXER_ATTN_LOCAL):
        p["attn"] = L.init_attention(ks[0], cfg, dtype)
    elif desc.mixer == MIXER_MAMBA:
        p["mamba"] = S.init_mamba(ks[0], cfg, dtype)
    elif desc.mixer == MIXER_MLSTM:
        p["mlstm"] = S.init_mlstm(ks[0], cfg, dtype)
    elif desc.mixer == MIXER_SLSTM:
        p["slstm"] = S.init_slstm(ks[0], cfg, dtype)
    if cross_attn:
        p["norm_x"] = jnp.zeros((cfg.d_model,), dtype)
        p["xattn"] = L.init_attention(ks[1], cfg, dtype)
    if desc.ffn != FFN_NONE:
        p["norm2"] = jnp.zeros((cfg.d_model,), dtype)
    if desc.ffn in (FFN_DENSE, FFN_MOE_DENSE):
        p["mlp"] = L.init_mlp(ks[2], cfg.d_model, cfg.d_ff, dtype)
    if desc.ffn in (FFN_MOE, FFN_MOE_DENSE):
        p["moe"] = M.init_moe(ks[3], cfg, dtype)
    return p


def layer_cache_shapes(cfg: ArchConfig, desc: LayerDesc, batch: int,
                       seq: int, cross_len: int = 0) -> Dict[str, Any]:
    """Decode-state shapes for one layer (no leading group dim)."""
    out: Dict[str, Any] = {}
    if desc.mixer in (MIXER_ATTN, MIXER_ATTN_LOCAL):
        out["k"] = (batch, seq, cfg.n_kv, cfg.hd)
        out["v"] = (batch, seq, cfg.n_kv, cfg.hd)
    elif desc.mixer == MIXER_MAMBA:
        out.update(S.mamba_state_shape(cfg, batch))
    elif desc.mixer == MIXER_MLSTM:
        out.update(S.mlstm_state_shape(cfg, batch))
    elif desc.mixer == MIXER_SLSTM:
        out.update(S.slstm_state_shape(cfg, batch))
    if cross_len:
        out["xk"] = (batch, cross_len, cfg.n_kv, cfg.hd)
        out["xv"] = (batch, cross_len, cfg.n_kv, cfg.hd)
    return out


def _cache_dtype_of(name: str) -> Any:
    # attention caches in activation dtype; recurrent states fp32
    return None


def _mixer(p: Dict[str, Any], h: jnp.ndarray, cfg: ArchConfig,
           desc: LayerDesc, *, positions, mode: str, cache, cache_pos,
           new_cache: Optional[Dict[str, Any]], causal: bool) -> jnp.ndarray:
    """The layer's token mixer on the normed stream ``h``; writes its decode
    state into ``new_cache``."""
    if desc.mixer in (MIXER_ATTN, MIXER_ATTN_LOCAL):
        local = desc.mixer == MIXER_ATTN_LOCAL
        attn_cache = (cache["k"], cache["v"]) if cache is not None else None
        y, nc = L.attention_block(
            p["attn"], h, cfg, causal=causal, local=local, positions=positions,
            cache=attn_cache, cache_pos=cache_pos,
            update_cache=(mode == "prefill"))
        if nc is not None and new_cache is not None:
            new_cache["k"], new_cache["v"] = nc
    elif desc.mixer == MIXER_MAMBA:
        st = {k: cache[k] for k in ("ssm", "conv")} if cache is not None else None
        y, ns = S.mamba_block(p["mamba"], h, cfg, state=st,
                              decode=(mode == "decode"))
        if new_cache is not None:
            new_cache.update(ns)
    elif desc.mixer == MIXER_MLSTM:
        st = {k: cache[k] for k in ("S", "n")} if cache is not None else None
        y, ns = S.mlstm_block(p["mlstm"], h, cfg, state=st,
                              decode=(mode == "decode"))
        if new_cache is not None:
            new_cache.update(ns)
    elif desc.mixer == MIXER_SLSTM:
        st = {k: cache[k] for k in ("c", "n", "h")} if cache is not None else None
        y, ns = S.slstm_block(p["slstm"], h, cfg, state=st,
                              decode=(mode == "decode"))
        if new_cache is not None:
            new_cache.update(ns)
    else:
        raise ValueError(desc.mixer)
    return y


def apply_layer(p: Dict[str, Any], x: jnp.ndarray, cfg: ArchConfig,
                desc: LayerDesc, *, positions, mode: str,
                cache: Optional[Dict[str, Any]] = None,
                cache_pos=None, enc_out: Optional[jnp.ndarray] = None,
                causal: bool = True
                ) -> Tuple[jnp.ndarray, Optional[Dict[str, Any]], jnp.ndarray]:
    """mode: "train" | "prefill" | "decode".  Returns (x, new_cache, aux)."""
    aux = jnp.float32(0.0)
    new_cache: Dict[str, Any] = {} if cache is not None else None
    eps = cfg.norm_eps
    if desc.mixer != MIXER_NONE:
        x = x + _mixer(p, L.rmsnorm(p["norm1"], x, eps), cfg, desc,
                       positions=positions, mode=mode, cache=cache,
                       cache_pos=cache_pos, new_cache=new_cache,
                       causal=causal)

    if "xattn" in p and (enc_out is not None or
                         (cache is not None and "xk" in cache)):
        hx = L.rmsnorm(p["norm_x"], x, eps)
        if mode == "decode" and cache is not None and "xk" in cache:
            # cross K/V precomputed at prefill
            y = L.decode_attention(
                (hx @ p["xattn"]["wq"]).reshape(
                    x.shape[0], 1, cfg.n_heads, cfg.hd),
                cache["xk"], cache["xv"],
                jnp.asarray(cache["xk"].shape[1] - 1))
            y = y.reshape(x.shape[0], 1, cfg.n_heads * cfg.hd) @ p["xattn"]["wo"]
            new_cache["xk"], new_cache["xv"] = cache["xk"], cache["xv"]
        else:
            y, _ = L.attention_block(p["xattn"], hx, cfg, causal=False,
                                     local=False, positions=positions,
                                     kv_override=enc_out)
            if new_cache is not None:
                b = x.shape[0]
                xk = (enc_out @ p["xattn"]["wk"]).reshape(
                    b, enc_out.shape[1], cfg.n_kv, cfg.hd)
                xv = (enc_out @ p["xattn"]["wv"]).reshape(
                    b, enc_out.shape[1], cfg.n_kv, cfg.hd)
                new_cache["xk"], new_cache["xv"] = xk, xv
        x = x + y

    if desc.ffn == FFN_NONE:
        return x, new_cache, aux
    h2 = L.rmsnorm(p["norm2"], x, eps)
    if desc.ffn == FFN_DENSE:
        x = x + L.mlp_block(p["mlp"], h2)
    elif desc.ffn == FFN_MOE:
        y, aux = M.moe_block(p["moe"], h2, cfg)
        x = x + y
    elif desc.ffn == FFN_MOE_DENSE:
        y, aux = M.moe_block(p["moe"], h2, cfg)
        x = x + y + L.mlp_block(p["mlp"], h2)
    return x, new_cache, aux
