"""Pallas TPU kernel: CSR-blocked GraphSAGE neighbor max-pool.

The chunked aggregation path (``segment_maxpool.neighbor_maxpool_chunked``)
bounds peak memory but still *streams* a dense ``[chunk, M]`` adjacency
slab per row block — O(chunk·M) bytes of mostly-zero mask for dataflow
graphs whose mean degree is ~2-8.  This kernel streams only the non-empty
``[bn, bm]`` adjacency tiles.

Format (BSR — block compressed sparse row, built host-side at featurize
time by :func:`build_block_index`):

* ``col_blocks``: i32[nR, T] — for row-block ``r``, the column-block ids
  holding at least one neighbor edge, sentinel ``-1`` padded to the max
  tile count ``T`` (one compiled shape per graph).
* ``adj``: int8[nR, T, bn, bm] — the densified 0/1 tiles themselves, in
  the same order.

The grid is (row-block, feature-block, tile); the innermost axis walks the
row-block's tile list and accumulates a running max in the revisited
output tile, exactly the ``segment_maxpool`` accumulation pattern.
``col_blocks`` (flattened) and the per-row tile count are scalar-prefetched
into SMEM (``PrefetchScalarGridSpec``), so the ``z`` index_map fetches only
the referenced ``[bm, bh]`` feature tile HBM→VMEM.  Sentinel steps re-use
the row's last real tile index — the pipeline skips the copy when the
block index does not change — and are skipped under ``pl.when``, so the
inner trip count is ``T`` but the *bytes touched* are proportional to the
true tile count (:func:`nnz_blocks` — the roofline's modeled-bytes
source).  The mask tile is int8 and is applied one adjacency column at a
time as a 2-D ``[bn, bh]`` select, so no 3-D broadcast is formed.

Oracle: ``repro.kernels.ref.neighbor_maxpool_from_lists_ref`` (same
padded-neighbor-list inputs the index is built from).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e9


class BlockIndex(NamedTuple):
    """BSR adjacency: tile ids + densified tiles (see module docstring).

    Block sizes are carried by the array shapes (``adj.shape[2:]``), so the
    tuple jit-flattens to two arrays and nothing retraces on value changes.
    """
    col_blocks: jnp.ndarray   # i32[nR, T], sentinel -1
    adj: jnp.ndarray          # int8[nR, T, bn, bm], 1 = edge


def build_block_index(nbr_idx, nbr_mask, num_cols: int, *,
                      block_n: int = 64, block_m: int = 128) -> BlockIndex:
    """Host-side (numpy) BSR build from padded neighbor lists.

    ``nbr_idx``: [N, K] with sentinel >= ``num_cols``; ``nbr_mask``: [N, K];
    ``num_cols`` = M, the number of ``z`` rows the kernel may gather.
    O(nnz) work; row/col counts need not divide the block sizes (the
    kernel wrapper pads ``z`` and slices the output).
    """
    idx = np.asarray(nbr_idx)
    msk = (np.asarray(nbr_mask) > 0) & (idx < num_cols)
    n, _ = idx.shape
    n_row_blocks = max(1, -(-n // block_n))
    per_row: list = []
    for r in range(n_row_blocks):
        sl = slice(r * block_n, min((r + 1) * block_n, n))
        rr, kk = np.nonzero(msk[sl])
        cols = idx[sl][rr, kk]
        cbs = np.unique(cols // block_m)
        tiles = {}
        for c in cbs:
            t = np.zeros((block_n, block_m), bool)
            sel = cols // block_m == c
            t[rr[sel], cols[sel] % block_m] = True
            tiles[int(c)] = t
        per_row.append(tiles)
    t_max = max(1, max(len(t) for t in per_row))
    col_blocks = np.full((n_row_blocks, t_max), -1, np.int32)
    adj = np.zeros((n_row_blocks, t_max, block_n, block_m), np.int8)
    for r, tiles in enumerate(per_row):
        for t, (c, tile) in enumerate(sorted(tiles.items())):
            col_blocks[r, t] = c
            adj[r, t] = tile
    return BlockIndex(jnp.asarray(col_blocks), jnp.asarray(adj))


def nnz_blocks(blocks: BlockIndex) -> int:
    """Number of real (non-sentinel) adjacency tiles — the modeled-bytes
    unit for ``benchmarks/roofline.py --kernels``."""
    return int((np.asarray(blocks.col_blocks) >= 0).sum())


def _csr_kernel(cb_ref, nt_ref, adj_ref, z_ref, o_ref):
    r, t = pl.program_id(0), pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, NEG)

    @pl.when(t < nt_ref[r])
    def _accumulate():
        adj = adj_ref[...].astype(jnp.float32)                 # [bn, bm]
        z = z_ref[...].astype(jnp.float32)                     # [bm, bh]
        acc = o_ref[...].astype(jnp.float32)                   # [bn, bh]
        for j in range(adj.shape[1]):
            acc = jnp.maximum(acc, jnp.where(adj[:, j:j + 1] > 0,
                                             z[j:j + 1, :], NEG))
        o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_h", "interpret"))
def _csr_call(z, col_blocks, adj, *, block_h: int, interpret: bool):
    n_row_blocks, t_max, bn, bm = adj.shape
    h = z.shape[1]
    bh = min(block_h, h)
    n_tiles = (col_blocks >= 0).sum(axis=1).astype(jnp.int32)      # [nR]

    def tile(r, t, nt):
        # sentinel steps re-point at the row's last real tile (no new DMA)
        return jnp.maximum(jnp.minimum(t, nt[r] - 1), 0)

    def z_map(r, hh, t, cb, nt):
        return jnp.maximum(cb[r * t_max + tile(r, t, nt)], 0), hh

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_row_blocks, h // bh, t_max),       # t innermost: accumulation
        in_specs=[
            pl.BlockSpec((None, None, bn, bm),
                         lambda r, hh, t, cb, nt: (r, tile(r, t, nt), 0, 0)),
            pl.BlockSpec((bm, bh), z_map),
        ],
        out_specs=pl.BlockSpec((bn, bh), lambda r, hh, t, cb, nt: (r, hh)),
    )
    return pl.pallas_call(
        _csr_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_row_blocks * bn, h), z.dtype),
        interpret=interpret,
    )(col_blocks.reshape(-1), n_tiles, adj, z)


def neighbor_maxpool_csr(z: jnp.ndarray, blocks: BlockIndex, *,
                         num_rows: int = None, block_h: int = 128,
                         interpret: bool = False) -> jnp.ndarray:
    """z: [M, H] neighbor features; blocks: BSR index over [N, M] -> [N, H].

    ``num_rows`` slices the output back to the real N (the index rounds
    rows up to the row-block).  Rows with no neighbors return NEG (caller
    zeroes them) — identical contract to ``neighbor_maxpool_dense``.
    """
    n_row_blocks, _, bn, bm = blocks.adj.shape
    m, h = z.shape
    pad_m = (-m) % bm
    if pad_m:
        z = jnp.concatenate([z, jnp.zeros((pad_m, h), z.dtype)])
    pad_h = (-h) % min(block_h, h)
    if pad_h:
        z = jnp.pad(z, ((0, 0), (0, pad_h)))
    out = _csr_call(z, blocks.col_blocks, blocks.adj,
                    block_h=min(block_h, h + pad_h), interpret=interpret)
    n = num_rows if num_rows is not None else n_row_blocks * bn
    return out[:n, :h]
