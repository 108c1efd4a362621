"""Pure-jnp oracles for every Pallas kernel (the allclose anchors)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG = -1e30


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        sm_scale: Optional[float] = None,
                        q_offset: int = 0) -> jnp.ndarray:
    """q: [BH, Sq, D]; k/v: [BH, Sk, D] — plain softmax attention."""
    import math
    d = q.shape[-1]
    sm = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm
    sq, sk = q.shape[1], k.shape[1]
    qi = q_offset + jnp.arange(sq)
    ki = jnp.arange(sk)
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= ki[None, :] <= qi[:, None]
    if window is not None:
        mask &= ki[None, :] > qi[:, None] - window
    s = jnp.where(mask[None], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def band_attention_ref(q, k, v, *, diag_lo: int, diag_hi: int,
                       kv_lo: int = 0, kv_len: Optional[int] = None,
                       sm_scale: Optional[float] = None) -> jnp.ndarray:
    """q: [BH, Sq, D]; k/v: [BH, Sk, D] — banded softmax attention.

    Query row ``i`` attends column ``j`` iff ``diag_lo <= j - i <= diag_hi``
    and ``kv_lo <= j < kv_len`` (the band geometry of
    ``band_attention.band_attention``).  Rows with no valid column return 0
    here; the kernel leaves them unspecified, so parity tests must compare
    only rows with at least one valid column.
    """
    import math
    d = q.shape[-1]
    sm = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    sq, sk = q.shape[1], k.shape[1]
    kv_len = sk if kv_len is None else kv_len
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm
    qi = jnp.arange(sq)
    ki = jnp.arange(sk)
    delta = ki[None, :] - qi[:, None]
    mask = (delta >= diag_lo) & (delta <= diag_hi)
    mask &= (ki >= kv_lo)[None, :] & (ki < kv_len)[None, :]
    s = jnp.where(mask[None], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask[None], p, 0.0)          # fully-masked rows -> 0
    out = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def neighbor_maxpool_ref(z, adj) -> jnp.ndarray:
    """z: [M, H]; adj: [N, M] bool -> [N, H]; empty rows -> -1e9."""
    masked = jnp.where(adj[:, :, None], z[None, :, :].astype(jnp.float32),
                       -1e9)
    return masked.max(axis=1).astype(z.dtype)


def neighbor_maxpool_from_lists_ref(z, nbr_idx, nbr_mask) -> jnp.ndarray:
    """Padded-neighbor-list form used by the GNN (sentinel = N)."""
    z_pad = jnp.concatenate([z, jnp.full((1, z.shape[1]), -1e9, z.dtype)])
    gathered = z_pad[nbr_idx]
    masked = jnp.where(nbr_mask[..., None] > 0, gathered, -1e9)
    return masked.max(axis=1)


CSR_ROWS_PER_STEP = 8     # row blocks per checkpointed step of the oracle


def csr_maxpool_blocks_ref(z, col_blocks, adj) -> jnp.ndarray:
    """BSR-index form of the max-pool oracle (same inputs as the kernel).

    z: [M, H]; col_blocks: i32[nR, T] (sentinel -1); adj: int8[nR, T, bn,
    bm] -> [nR*bn, H] with -1e9 for rows without neighbors — the raw
    kernel contract, before the ops wrapper zeroes isolates.  Pure jnp and
    differentiable: this is the backward path of the CSR kernel's
    custom_vjp.  Row blocks are mapped ``CSR_ROWS_PER_STEP`` at a time under
    ``jax.checkpoint``, so forward and backward hold one step's
    ``[rows, T, bn, bm, H]`` tile outer product, never the whole graph's
    (at the 50k-node GNMT-8 cell that product is ~20 GB).
    """
    n_r, t_max, bn, bm = adj.shape
    m, h = z.shape
    pad_m = (-m) % bm
    zp = jnp.concatenate([z, jnp.zeros((pad_m, h), z.dtype)]) if pad_m else z
    tiles = zp.reshape(zp.shape[0] // bm, bm, h)

    @jax.checkpoint
    def row_block(args):
        cb, a = args                                           # [T], [T,bn,bm]
        zsel = tiles[jnp.clip(cb, 0, tiles.shape[0] - 1)]      # [T, bm, H]
        ok = (cb >= 0)[:, None, None] & (a > 0)                # [T, bn, bm]
        masked = jnp.where(ok[..., None],
                           zsel[:, None, :, :].astype(jnp.float32), -1e9)
        return masked.max(axis=(0, 2))                         # [bn, H]

    out = jax.lax.map(row_block, (col_blocks, adj),
                      batch_size=CSR_ROWS_PER_STEP)
    return out.reshape(n_r * bn, h).astype(z.dtype)
