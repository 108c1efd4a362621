"""GraphSAGE-style graph embedding network (paper §3.1, Eqs. 2–3).

Per iteration l::

    h_N(v) = max_{u in N(v)} sigmoid(W^l h_u + b^l)          (max-pool agg)
    h_v    = relu(f^{l+1}(concat(h_v, h_N(v))))

Trained jointly with the placer via PPO (supervised reward), replacing
GraphSAGE's unsupervised loss — exactly the paper's modification.

The neighbor max-aggregation is the per-step hot spot on 50k-node graphs;
``agg_impl="pallas"`` routes it through the blocked TPU kernel in
``repro.kernels`` (interpret mode on CPU), ``"jnp"`` is the XLA fallback.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import nn
from repro.core.featurize import GraphBatch, NUM_NUMERIC_FEATURES
from repro.core.graph import NUM_OP_TYPES
from repro.core.scale import ScaleConfig
from repro.obs import jaxprof

NEG = -1e9


def init(key, hidden: int, num_layers: int = 3, op_emb: int = 32) -> Dict[str, Any]:
    ks = nn.split_keys(key, 2 + 2 * num_layers)
    params: Dict[str, Any] = {
        "op_emb": nn.embedding_init(ks[0], NUM_OP_TYPES + 1, op_emb),
        "in": nn.dense_init(ks[1], op_emb + NUM_NUMERIC_FEATURES, hidden),
        "layers": [],
    }
    for l in range(num_layers):
        params["layers"].append({
            "agg": nn.dense_init(ks[2 + 2 * l], hidden, hidden),
            "upd": nn.dense_init(ks[3 + 2 * l], 2 * hidden, hidden),
        })
    return params


def _gather_max(z_pad: jnp.ndarray, nbr_idx: jnp.ndarray,
                nbr_mask: jnp.ndarray) -> jnp.ndarray:
    """Core padded-neighbor max: z_pad:[N+1,H] (sentinel row last),
    nbr_idx:[n,K], nbr_mask:[n,K] -> [n,H] (isolated rows -> 0)."""
    gathered = z_pad[nbr_idx]                         # [n, K, H]
    masked = jnp.where(nbr_mask[..., None] > 0, gathered, NEG)
    agg = jnp.max(masked, axis=1)
    return jnp.where(agg <= NEG / 2, 0.0, agg)        # isolated nodes -> 0


def _neighbor_max(z: jnp.ndarray, nbr_idx: jnp.ndarray, nbr_mask: jnp.ndarray,
                  agg_impl: str, chunk: Optional[int] = None,
                  csr_blocks=None) -> jnp.ndarray:
    """max over padded neighbors; z:[N,H], nbr_idx:[N,K] sentinel=N.

    ``chunk`` bounds the gather: node rows are processed ``chunk`` at a
    time (a sequential ``lax.map``), so the [*, K, H] intermediate peaks
    at O(chunk·K·H) instead of O(N·K·H) — the difference between a 50k-
    node featurization fitting in memory or not.  Per-node reductions are
    unchanged, so chunked == unchunked bit-for-bit.

    ``agg_impl="pallas_csr"`` streams only the non-empty adjacency tiles
    via the BSR index carried on the GraphBatch (``csr_blocks``; built by
    ``featurize`` when ``scale.csr`` is set) — bytes touched scale with
    the edges, not with chunk·N.
    """
    if agg_impl == "pallas":
        from repro.kernels import ops as kops
        return kops.neighbor_maxpool(z, nbr_idx, nbr_mask, chunk=chunk)
    if agg_impl == "pallas_csr":
        if csr_blocks is None:
            raise ValueError(
                "agg_impl='pallas_csr' needs a GraphBatch featurized with "
                "scale.csr=True (GraphBatch.csr_blocks is None)")
        from repro.kernels import ops as kops
        return kops.neighbor_maxpool_csr(z, csr_blocks,
                                         num_rows=z.shape[0])
    z_pad = jnp.concatenate([z, jnp.full((1, z.shape[1]), NEG, z.dtype)])
    if chunk is None or nbr_idx.shape[0] <= chunk:
        return _gather_max(z_pad, nbr_idx, nbr_mask)
    return _chunked_gather_max(z_pad, nbr_idx, nbr_mask, chunk)


# jitted so an eager caller (the segmented PPO path runs the GNN outside
# any jit) reuses one compiled program: an eager ``lax.map`` over a fresh
# closure compiles a new scan on every call
@partial(jax.jit, static_argnames=("chunk",))
def _chunked_gather_max(z_pad, nbr_idx, nbr_mask, chunk: int):
    n, k = nbr_idx.shape
    pad = (-n) % chunk
    idx = jnp.pad(nbr_idx, ((0, pad), (0, 0)), constant_values=n)
    mask = jnp.pad(nbr_mask, ((0, pad), (0, 0)))
    agg = jax.lax.map(
        lambda im: _gather_max(z_pad, im[0], im[1]),
        (idx.reshape(-1, chunk, k), mask.reshape(-1, chunk, k)))
    return agg.reshape(-1, z_pad.shape[1])[:n]


jaxprof.register("gnn.chunked_gather_max", _chunked_gather_max)


def apply(params: Dict[str, Any], gb: GraphBatch, *, agg_impl: str = "jnp",
          scale: ScaleConfig = ScaleConfig()) -> jnp.ndarray:
    """Returns node embeddings f32[N, H].

    ``scale`` (:class:`repro.core.scale.ScaleConfig`) supplies the
    chunked-gather bound (``scale.gnn_chunk``: peak memory O(chunk·K·H),
    bit-identical results)."""
    x = jnp.concatenate([params["op_emb"][gb.op], gb.feats], axis=-1)
    h = jax.nn.relu(nn.dense(params["in"], x))
    h = h * gb.node_mask[:, None]
    for lp in params["layers"]:
        z = jax.nn.sigmoid(nn.dense(lp["agg"], h))          # Eq. (2) affine+sigma
        agg = _neighbor_max(z, gb.nbr_idx, gb.nbr_mask, agg_impl,
                            scale.gnn_chunk,
                            getattr(gb, "csr_blocks", None))
        h = jax.nn.relu(nn.dense(lp["upd"], jnp.concatenate([h, agg], -1)))
        h = h * gb.node_mask[:, None]
    return h


def graph_summary(h: jnp.ndarray, node_mask: jnp.ndarray) -> jnp.ndarray:
    """Pooled per-graph representation x^(0) used for superposition."""
    denom = jnp.maximum(node_mask.sum(), 1.0)
    mean = (h * node_mask[:, None]).sum(0) / denom
    mx = jnp.max(jnp.where(node_mask[:, None] > 0, h, NEG), axis=0)
    mx = jnp.where(mx <= NEG / 2, 0.0, mx)
    return jnp.concatenate([mean, mx])
