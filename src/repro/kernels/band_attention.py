"""Pallas TPU kernel: block-sparse *banded* flash attention.

The GDP decoder's attention is never dense: every query attends a causal
window of ``W`` positions (plus, in the segmented TF pass, the carried
Transformer-XL-style memory columns of the previous ``W - 1`` positions).
The generic flash kernel (``flash_attention.py``) already *skips compute*
for out-of-band K/V blocks, but the segmented decode path did not use it —
it materialized a gathered ``[S, W, heads, hd]`` band copy of K and V per
segment (O(S·W) bytes moved twice) before a dense softmax.

This kernel computes the band *in place*: the grid is (batch·head,
q-block); per cell the inner loop visits ONLY the K/V blocks intersecting
the band, streaming each [block_k, d] tile once.  Bytes touched per
segment drop from 2·S·W·hd to ~S·(1 + W/block_q)·hd (see
:func:`band_kv_blocks` — the roofline benchmark's modeled-bytes source).

Band geometry (one mechanism covers every caller):

* query row ``i`` may attend buffer column ``j`` iff
  ``diag_lo <= j - i <= diag_hi``            (static band), and
  ``kv_lo <= j < kv_len``                    (valid-column range).
* segmented TF pass with memory: K/V buffer = [W-1 memory cols | S segment
  cols]; query ``i`` attends buffer cols ``[i, i + W - 1]`` → ``diag_lo=0,
  diag_hi=W-1``.  The first segment's memory columns are *before the start
  of time*: ``kv_lo = max(0, (W-1) - base)`` masks them.  ``kv_lo`` is a
  **dynamic scalar operand** so every segment of every graph reuses ONE
  compiled program (base varies, the program does not).
* plain causal sliding-window over one sequence: ``diag_lo = q_offset -
  window + 1, diag_hi = q_offset``.
* non-causal with a valid-prefix (mha_with_memory): ``diag_lo = -T,
  diag_hi = T, kv_len = real T`` — the kv_len mask is what keeps padded
  keys out of the softmax.

Oracle: ``repro.kernels.ref.band_attention_ref``; off-TPU validation runs
in interpret mode (tests/test_kernels.py property net), and
tests/test_tpu_compile.py compiles it for a v5e.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -1e30


def _band_kernel(lo_ref, q_ref, k_ref, v_ref, o_ref, *, sm_scale: float,
                 block_q: int, block_k: int, seq_k: int, diag_lo: int,
                 diag_hi: int, kv_len: int):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale          # [bq, d]
    bq, d = q.shape
    nk = seq_k // block_k
    kv_lo = lo_ref[0]          # dynamic valid-column floor (first segment)
    row0 = qi * block_q
    rows = row0 + jax.lax.iota(jnp.int32, block_q)

    # block-sparse loop bounds: only K/V blocks intersecting the band
    # [row + diag_lo, row + diag_hi] ∩ [kv_lo, kv_len) are visited
    lo = jnp.maximum(jnp.maximum((row0 + diag_lo) // block_k, 0),
                     kv_lo // block_k)
    hi = jnp.minimum((row0 + block_q - 1 + diag_hi) // block_k + 1,
                     min((kv_len + block_k - 1) // block_k, nk))
    hi = jnp.maximum(hi, lo)

    def body(j, carry):
        acc, m_run, l_run = carry
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())))  # [bq,bk]
        cols = j * block_k + jax.lax.iota(jnp.int32, block_k)
        delta = cols[None, :] - rows[:, None]
        mask = (delta >= diag_lo) & (delta <= diag_hi)
        mask &= (cols >= kv_lo)[None, :] & (cols < kv_len)[None, :]
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
    o_ref[0] = (acc / jnp.maximum(l, 1e-20)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "diag_lo", "diag_hi", "kv_len", "sm_scale", "block_q", "block_k",
    "interpret"))
def band_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   kv_lo: jnp.ndarray, *, diag_lo: int, diag_hi: int,
                   kv_len: int, sm_scale: float = None,
                   block_q: int = 128, block_k: int = 128,
                   interpret: bool = False) -> jnp.ndarray:
    """q: [BH, Sq, D]; k/v: [BH, Sk, D] -> [BH, Sq, D].

    ``kv_lo`` is an i32[1] array (dynamic — one compiled program per
    (shape, band) regardless of its value); ``diag_lo/diag_hi/kv_len`` are
    static band geometry (see module docstring).  Sq/Sk must divide
    block_q/block_k — the ops wrappers pad and rely on ``kv_len`` to keep
    padded columns out of the softmax.  A query row with NO valid column
    anywhere in its band produces unspecified values (same contract as
    ``flash_attention``) — wrappers only ever slice such rows off.
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, block_q, sk, block_k)
    assert 0 < kv_len <= sk, (kv_len, sk)
    sm = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    kernel = functools.partial(
        _band_kernel, sm_scale=sm, block_q=block_q, block_k=block_k,
        seq_k=sk, diag_lo=diag_lo, diag_hi=diag_hi, kv_len=kv_len)
    return pl.pallas_call(
        kernel,
        grid=(bh, sq // block_q),
        in_specs=[
            pl.BlockSpec((1,), lambda h, i: (0,)),
            pl.BlockSpec((1, block_q, d), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, sk, d), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda h, i: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(jnp.asarray(kv_lo, jnp.int32).reshape(1), q, k, v)


# ------------------------------------------------------- roofline modeling
def band_kv_blocks(sq: int, sk: int, *, diag_lo: int, diag_hi: int,
                   kv_lo: int = 0, kv_len: int = None,
                   block_q: int = 128, block_k: int = 128) -> int:
    """Total K/V blocks the kernel's inner loop visits over all q blocks.

    This is the EXACT per-(batch·head) loop trip count — the same bounds
    arithmetic as ``_band_kernel`` evaluated in Python — so the roofline's
    modeled bytes-touched (``benchmarks/roofline.py --kernels``) describes
    the kernel that actually runs, not an idealized one.
    """
    kv_len = sk if kv_len is None else kv_len
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    nk = sk // bk
    total = 0
    for row0 in range(0, sq, bq):
        lo = max((row0 + diag_lo) // bk, 0, kv_lo // bk)
        hi = min((row0 + bq - 1 + diag_hi) // bk + 1,
                 (kv_len + bk - 1) // bk, nk)
        total += max(hi - lo, 0)
    return total
