"""Pallas TPU flash attention (online softmax, causal/local, fp32 accum).

TARGET: TPU MXU — BlockSpec tiles stream K/V HBM→VMEM per (batch·head,
q-block) grid cell; scores never materialize beyond a [block_q, block_k]
VMEM tile; masked-out K/V blocks are skipped by bounding the inner loop
(causal upper bound, sliding-window lower bound).  Used by the model zoo's
prefill/train attention and by the GDP placer's segment attention; the
pure-jnp oracle is ``repro.kernels.ref.flash_attention_ref`` and the
dry-run lowers the XLA-native twin (``models.layers.chunked_attention``).

Validated against the oracle in interpret mode over shape/dtype sweeps
(tests/test_kernels.py).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, sm_scale: float,
                  block_q: int, block_k: int, seq_k: int, causal: bool,
                  window: Optional[int], q_offset: int, kv_len: int):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale          # [bq, d]
    bq, d = q.shape
    nk = min((kv_len + block_k - 1) // block_k, seq_k // block_k)

    q_pos = q_offset + qi * block_q + jax.lax.iota(jnp.int32, block_q)

    # inner-loop bounds: skip fully-masked K/V blocks
    if causal:
        hi = jnp.minimum(
            (q_offset + (qi + 1) * block_q + block_k - 1) // block_k, nk)
    else:
        hi = nk
    if window is not None:
        lo = jnp.maximum((q_offset + qi * block_q - window + 1) // block_k, 0)
    else:
        lo = 0

    def body(j, carry):
        acc, m_run, l_run = carry
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())))  # [bq,bk]
        k_pos = j * block_k + jax.lax.iota(jnp.int32, block_k)
        # padded-key guard: keys at/after the true length never reach the
        # softmax (sequence dims are padded to block multiples by the ops
        # wrappers; without this mask the zero padding attends as real keys)
        mask = (k_pos < kv_len)[None, :] | jnp.zeros((bq, 1), jnp.bool_)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(mask, s, NEG)
        m_new = jnp.maximum(m_run, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_run - m_new)
        l_new = l_run * corr + p.sum(axis=1)
        acc = acc * corr[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())))
        return acc, m_new, l_new

    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), NEG, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, _, l = jax.lax.fori_loop(lo, hi, body, (acc0, m0, l0))
    out = acc / jnp.maximum(l, 1e-20)[:, None]
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "sm_scale", "block_q", "block_k", "q_offset",
    "kv_len", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    sm_scale: Optional[float] = None, block_q: int = 128,
                    block_k: int = 128, q_offset: int = 0,
                    kv_len: Optional[int] = None,
                    interpret: bool = False) -> jnp.ndarray:
    """q: [BH, Sq, D]; k/v: [BH, Sk, D] -> [BH, Sq, D].

    GQA is handled by the ops wrapper (q heads grouped onto kv heads before
    the call).  Sq/Sk must divide block_q/block_k (wrapper pads).
    ``kv_len`` (static) is the number of REAL keys: when Sk was padded up
    to a block multiple, keys at index >= kv_len are masked out of the
    softmax and trailing fully-padded K/V blocks are never visited.
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, block_q, sk, block_k)
    kv_len = sk if kv_len is None else kv_len
    assert 0 < kv_len <= sk, (kv_len, sk)
    sm = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    grid = (bh, sq // block_q)
    kernel = functools.partial(
        _flash_kernel, sm_scale=sm, block_q=block_q, block_k=block_k,
        seq_k=sk, causal=causal, window=window, q_offset=q_offset,
        kv_len=kv_len)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, sk, d), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda h, i: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v)
