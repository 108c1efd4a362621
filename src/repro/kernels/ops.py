"""jit'd dispatch wrappers around the Pallas kernels.

These are the entry points the rest of the framework calls
(``gnn.apply(agg_impl="pallas")``, ``placer`` attention, model-zoo hot
paths).  On a TPU backend they run the compiled kernels; on any other
backend they run in interpret mode (the same kernel body, evaluated by
XLA) so tests and the GDP training loop behave identically everywhere.
:func:`interpret` is the one place that choice is made.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as kref
from repro.kernels.band_attention import band_attention
from repro.kernels.csr_maxpool import BlockIndex, neighbor_maxpool_csr as _csr
from repro.kernels.flash_attention import flash_attention
from repro.kernels.segment_maxpool import (neighbor_maxpool_chunked,
                                           neighbor_maxpool_dense)

NEG = -1e9


# ------------------------------------------------------------- gradients
# pallas_call has no JVP rule, but the band/CSR wrappers sit on the PPO
# update path (logp_and_entropy under value_and_grad) when the kernel
# flags are on.  Both get a custom_vjp: the FORWARD stays the kernel, the
# BACKWARD differentiates the pure-jnp oracle at the same inputs — exact
# cotangents (same math, tolerance-level forward parity is pinned by
# tests), at the cost of re-running an oracle forward inside the vjp.

def _int_zeros(x):
    """float0 cotangent for integer/bool primals (custom_vjp contract)."""
    return np.zeros(np.shape(x), jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _band_call(q, k, v, kv_lo, diag_lo, diag_hi, kv_len, block_q, block_k):
    return band_attention(q, k, v, kv_lo, diag_lo=diag_lo, diag_hi=diag_hi,
                          kv_len=kv_len, block_q=block_q, block_k=block_k,
                          interpret=interpret())


def _band_call_fwd(q, k, v, kv_lo, diag_lo, diag_hi, kv_len, block_q,
                   block_k):
    out = _band_call(q, k, v, kv_lo, diag_lo, diag_hi, kv_len, block_q,
                     block_k)
    return out, (q, k, v, kv_lo)


def _band_call_bwd(diag_lo, diag_hi, kv_len, block_q, block_k, res, ct):
    q, k, v, kv_lo = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: kref.band_attention_ref(
            q_, k_, v_, diag_lo=diag_lo, diag_hi=diag_hi, kv_lo=kv_lo,
            kv_len=kv_len), q, k, v)
    dq, dk, dv = vjp(ct)
    return dq, dk, dv, _int_zeros(kv_lo)


_band_call.defvjp(_band_call_fwd, _band_call_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _csr_diff(z, col_blocks, adj, num_rows):
    return _csr(z, BlockIndex(col_blocks, adj), num_rows=num_rows,
                interpret=interpret())


def _csr_diff_fwd(z, col_blocks, adj, num_rows):
    return _csr_diff(z, col_blocks, adj, num_rows), (z, col_blocks, adj)


def _csr_diff_bwd(num_rows, res, ct):
    z, cb, adj = res
    _, vjp = jax.vjp(
        lambda z_: kref.csr_maxpool_blocks_ref(z_, cb, adj)[:num_rows], z)
    dz, = vjp(ct)
    return dz, _int_zeros(cb), _int_zeros(adj)


_csr_diff.defvjp(_csr_diff_fwd, _csr_diff_bwd)


def interpret() -> bool:
    """Run Pallas kernels in interpret mode: everywhere but on a TPU."""
    return jax.default_backend() != "tpu"


def _pad_to(x: jnp.ndarray, axis: int, mult: int):
    s = x.shape[axis]
    pad = (-s) % mult
    if pad == 0:
        return x, s
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), s


def _block_for(s: int, block: int = 128) -> int:
    """Largest usable block for a length-``s`` dim: ``block`` when s >= block
    (pad s up to a multiple), else the next power of two >= s (pad to it) —
    small test/segment shapes never balloon to a 128-row pad."""
    return block if s >= block else 1 << max(s - 1, 0).bit_length()


def neighbor_maxpool(z: jnp.ndarray, nbr_idx: jnp.ndarray,
                     nbr_mask: jnp.ndarray,
                     chunk: Optional[int] = None) -> jnp.ndarray:
    """GraphSAGE aggregation via the blocked masked-adjacency kernel.

    z: [N, H]; nbr_idx: [N, K] (sentinel = N); nbr_mask: [N, K].
    Returns [N, H] with isolated rows zeroed (matches gnn._neighbor_max).
    ``chunk`` routes through the row-blocked kernel wrapper whose densified
    adjacency slab is O(chunk·N) — required for paper-scale graphs where
    the one-shot [N, N] bitmask would not fit.
    """
    n, h = z.shape
    zp, _ = _pad_to(z, 0, 128)
    zp, _ = _pad_to(zp, 1, 128)
    if chunk is not None and n > chunk:
        chunk = max(64, (chunk // 64) * 64)
        pad_n = (-n) % chunk
        idxp = jnp.pad(nbr_idx, ((0, pad_n), (0, 0)),
                       constant_values=zp.shape[0])
        maskp = jnp.pad(nbr_mask, ((0, pad_n), (0, 0)))
        out = neighbor_maxpool_chunked(zp.astype(jnp.float32), idxp, maskp,
                                       chunk=chunk, interpret=interpret())
    else:
        # densify the padded neighbor lists into an adjacency bitmask
        onehot = (nbr_idx[..., None] ==
                  jnp.arange(n)[None, None, :])          # [N, K, N]
        adj = jnp.any(onehot & (nbr_mask[..., None] > 0), axis=1)   # [N, N]
        adjp, _ = _pad_to(adj, 0, 64)
        adjp, _ = _pad_to(adjp, 1, 128)
        out = neighbor_maxpool_dense(zp.astype(jnp.float32), adjp,
                                     interpret=interpret())
    out = out[:n, :h]
    return jnp.where(out <= NEG / 2, 0.0, out).astype(z.dtype)


def mha_with_memory(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    mask_q: jnp.ndarray, mask_kv: jnp.ndarray,
                    impl: str = "flash") -> jnp.ndarray:
    """Placer attention: q [S,H,hd]; k/v [T,H,hd] (memory prefix included).

    Non-causal over valid kv positions (masks here are always
    [valid prefix][padding], so kv validity reduces to the static real
    length T).  The kernel is told that length via ``kv_len``: keys the
    block-multiple padding appends are masked out of the softmax and
    never counted as context (they used to leak — regression pinned in
    tests/test_kernels.py).  ``impl="band"`` routes through the
    block-sparse band kernel with a full-width band — same math, one
    kernel family for every placer attention shape.
    """
    t = int(mask_kv.shape[0])
    s, heads, hd = q.shape
    qh = q.transpose(1, 0, 2)                       # [H, S, hd]
    kh = k.transpose(1, 0, 2)
    vh = v.transpose(1, 0, 2)
    bq, bk = _block_for(s), _block_for(t)
    qp, sq0 = _pad_to(qh, 1, bq)
    kp, _ = _pad_to(kh, 1, bk)
    vp, _ = _pad_to(vh, 1, bk)
    if impl == "band":
        out = _band_call(qp, kp, vp, jnp.int32(0),
                         -qp.shape[1], t, t, bq, bk)
    else:
        out = flash_attention(qp, kp, vp, causal=False, kv_len=t,
                              block_q=bq, block_k=bk,
                              interpret=interpret())
    return out[:, :sq0].transpose(1, 0, 2)


def causal_window_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            window: Optional[int] = None,
                            q_offset: int = 0,
                            impl: str = "flash") -> jnp.ndarray:
    """[BH, S, D] causal (optionally sliding-window) attention.

    Handles S that is not a block multiple by padding and telling the
    kernel the real length (``kv_len`` keeps padded keys out of the
    softmax; padded query rows are sliced off).  ``impl="band"`` computes
    the same mask through the block-sparse band kernel — queries near the
    diagonal visit only the K/V blocks intersecting the window band.
    """
    s = q.shape[1]
    b = _block_for(s)
    qp, s0 = _pad_to(q, 1, b)
    kp, _ = _pad_to(k, 1, b)
    vp, _ = _pad_to(v, 1, b)
    if impl == "band":
        diag_lo = q_offset - (window - 1 if window else qp.shape[1])
        out = _band_call(qp, kp, vp, jnp.int32(0),
                         diag_lo, q_offset, s0, b, b)
    else:
        out = flash_attention(qp, kp, vp, causal=True, window=window,
                              q_offset=q_offset, kv_len=s0,
                              block_q=b, block_k=b, interpret=interpret())
    return out[:, :s0]


def band_mha_with_memory(q: jnp.ndarray, kbuf: jnp.ndarray,
                         vbuf: jnp.ndarray, base: jnp.ndarray, *,
                         window: int) -> jnp.ndarray:
    """Segmented TF attention through the block-sparse band kernel.

    q: [S, heads, hd] segment queries; kbuf/vbuf: [W-1+S, heads, hd]
    (carried Transformer-XL memory columns | segment columns); ``base``:
    traced global index of q[0].  Query ``i`` attends buffer columns
    ``[i, i + W - 1]`` (``diag_lo=0, diag_hi=W-1``); memory columns from
    before the start of time are masked by the DYNAMIC ``kv_lo =
    max(0, (W-1) - base)`` — every segment of every graph reuses ONE
    compiled program regardless of ``base``.  The opt-in alternative to
    ``placer._band_attention``, the jnp path's blocked windows of dense
    [W, 2W] score tiles: here the band tiles are computed in place.
    """
    s, heads, hd = q.shape
    wm1 = window - 1
    t0 = kbuf.shape[0]
    qh = q.transpose(1, 0, 2)
    kh = kbuf.transpose(1, 0, 2)
    vh = vbuf.transpose(1, 0, 2)
    bq = _block_for(s)
    qp, _ = _pad_to(qh, 1, bq)
    # padded query rows band up to col (S_pad - 1) + W - 1: the buffer pad
    # must cover them (kv_len masks the fake columns out of real rows)
    t_need = qp.shape[1] + wm1
    bk = _block_for(t_need)
    pad_t = ((t_need + bk - 1) // bk) * bk - t0
    kp = jnp.pad(kh, ((0, 0), (0, pad_t), (0, 0)))
    vp = jnp.pad(vh, ((0, 0), (0, pad_t), (0, 0)))
    kv_lo = jnp.maximum(0, wm1 - base).astype(jnp.int32)
    out = _band_call(qp, kp, vp, kv_lo, 0, wm1, t0, bq, bk)
    return out[:, :s].transpose(1, 0, 2)


def neighbor_maxpool_csr(z: jnp.ndarray, blocks: BlockIndex,
                         num_rows: Optional[int] = None) -> jnp.ndarray:
    """GraphSAGE aggregation via the CSR-blocked kernel.

    z: [M, H]; ``blocks``: BSR adjacency index built at featurize time
    (``csr_maxpool.build_block_index``).  Returns [N, H] with isolated
    rows zeroed — identical contract to :func:`neighbor_maxpool`, but
    bytes touched scale with the non-empty adjacency tiles instead of
    the dense [chunk, M] slab.
    """
    out = _csr_diff(z.astype(jnp.float32), blocks.col_blocks, blocks.adj,
                    num_rows)
    return jnp.where(out <= NEG / 2, 0.0, out).astype(z.dtype)
