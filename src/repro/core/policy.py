"""End-to-end GDP policy: GraphSAGE embeddings -> autoregressive placer.

The placement distribution is seq2seq: π(D|G) = Π_i π(d_i | d_<i, GNN(G)),
sampled with the exact AR scan and evaluated teacher-forced in parallel for
PPO ratios (both paths share parameters and masks).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import gnn, placer, superposition
from repro.core.featurize import GraphBatch
from repro.core.scale import ScaleConfig


@dataclasses.dataclass(frozen=True)
class PolicyConfig:
    hidden: int = 128
    gnn_layers: int = 3
    op_emb: int = 32
    placer_layers: int = 2
    heads: int = 4
    ffn: int = 512
    window: int = 256                   # causal attention context width
    max_devices: int = 16
    use_attention: bool = True          # Fig. 3 ablation switch
    use_superposition: bool = True      # Fig. 3 ablation switch
    agg_impl: str = "jnp"               # "jnp" | "pallas" | "pallas_csr"
    # Teacher-forced attention implementation: "jnp" (the default: the
    # band as blocked windows of dense products in the segmented pass, a
    # gather in the monolithic one) or "pallas_band" (block-sparse band
    # kernel; tolerance-pinned parity in tier-1).  Only the
    # TF paths route through it: AR sampling is inherently sequential and
    # its ring-buffer cache is already exactly band-sized (see
    # placer.sample_ar_segmented).
    attn_impl: str = "jnp"
    # Memory-aware decode: mask devices a node would push past their
    # memory cap (the decoder's running per-device accumulators vs
    # featurize's dev_mem_cap), so sampled placements are feasible by
    # construction whenever greedy feasibility exists.  Off by default —
    # it changes the sampling distribution, so golden-pinned runs keep
    # the paper's unconstrained decode; the paper-scale campaign turns
    # it on (at 50k nodes an unconstrained policy fork can spend its
    # whole fine-tune budget before drawing one valid sample).
    mask_full_devices: bool = False
    # Every graph-size knob (segmented decode, chunked GNN gather,
    # padding grid, jumbo bucket, hierarchy thresholds): the one place
    # they are set — see repro.core.scale.  ``scale.segment`` None is the
    # monolithic decode, bit-identical to the segmented one (pinned by
    # tests/test_segmented.py).
    scale: ScaleConfig = ScaleConfig()


def init(key, cfg: PolicyConfig) -> Dict[str, Any]:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gnn": gnn.init(k1, cfg.hidden, cfg.gnn_layers, cfg.op_emb),
        "sp": superposition.init(k2, 2 * cfg.hidden, cfg.hidden),
        "placer": placer.init(k3, cfg.hidden, cfg.placer_layers, cfg.heads,
                              cfg.ffn, cfg.max_devices),
    }


def _decode_fn(cfg: PolicyConfig, gb: GraphBatch, num_devices: int):
    """(placer decode fn, shared kwargs) for the config: the segmented
    variant plus ``segment=`` when ``cfg.scale.segment`` is set, monolithic
    otherwise.  One spot assembles the decode kwargs so the sampling,
    ratio, and greedy paths can never drift apart."""
    kwargs = dict(window=cfg.window, heads=cfg.heads,
                  num_devices=num_devices,
                  use_attention=cfg.use_attention,
                  dev_mem_cap=(gb.dev_mem_cap if cfg.mask_full_devices
                               else None),
                  mask_full=cfg.mask_full_devices)
    if cfg.scale.segment is not None:
        return placer.sample_ar_segmented, dict(kwargs,
                                                segment=cfg.scale.segment)
    return placer.sample_ar, kwargs


def incumbent_bias(cfg: PolicyConfig, gb: GraphBatch,
                   incumbent: Optional[Any],
                   migration_bias: float) -> Optional[jnp.ndarray]:
    """[N, Dmax] additive decode bias toward an incumbent placement.

    Each node's incumbent-device logit is lifted by ``migration_bias *
    mem_frac`` — heavy nodes resist moving proportionally to the bytes a
    move would ship, which is exactly the migration-aware re-placement
    objective (minimize recovery makespan + data movement).  ``incumbent``
    entries of ``-1`` (no incumbent: a new node, or padding) get a zero
    row; ``None`` incumbent or zero strength returns ``None`` — the
    decode paths then trace the exact unbiased program.
    """
    if incumbent is None or migration_bias == 0.0:
        return None
    inc = jnp.asarray(incumbent, jnp.int32)
    n = gb.mem_frac.shape[0]
    if inc.shape[0] < n:        # pad to the featurized length with "none"
        inc = jnp.concatenate(
            [inc, jnp.full((n - inc.shape[0],), -1, jnp.int32)])
    oh = jax.nn.one_hot(inc[:n], cfg.max_devices)
    return jnp.float32(migration_bias) * gb.mem_frac[:, None] * oh


def _embed(params, cfg: PolicyConfig, gb: GraphBatch):
    h = gnn.apply(params["gnn"], gb, agg_impl=cfg.agg_impl, scale=cfg.scale)
    c = None
    if cfg.use_superposition:
        x0 = gnn.graph_summary(h, gb.node_mask)
        c = superposition.gain(params["sp"], x0)
    return h, c


def sample(params, cfg: PolicyConfig, gb: GraphBatch, num_devices: int,
           key, num_samples: int, temperature: float = 1.0,
           incumbent=None, migration_bias: float = 0.0
           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (placements i32[M, N], per-node logp f32[M, N]).

    With ``cfg.scale.segment`` set the AR decode runs segment-by-segment
    (callers must NOT wrap this in an outer jit — the segmented path
    manages its own per-segment compiled programs).

    ``incumbent`` (i32[<=N], -1 = no incumbent) with ``migration_bias``
    > 0 turns on the incumbent-conditioned decode: see
    :func:`incumbent_bias`.  The defaults are bit-identical to the
    unconditioned sampler."""
    h, c = _embed(params, cfg, gb)
    keys = jax.random.split(key, num_samples)
    fn, kwargs = _decode_fn(cfg, gb, num_devices)
    bias = incumbent_bias(cfg, gb, incumbent, migration_bias)
    devs, lps = jax.vmap(lambda k: fn(
        params["placer"], h, gb.node_mask, c, k, gb.mem_frac, gb.comp_frac,
        gb.dev_feats, temperature=temperature, incumbent_bias=bias,
        **kwargs))(keys)
    return devs.astype(jnp.int32), lps


def sample_batch(params, cfg: PolicyConfig, sgb: GraphBatch,
                 num_devices: int, key, num_samples: int = 1,
                 temperature: float = 1.0
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched zero-shot inference: one call serves B stacked graphs.

    ``sgb`` is a ``stack_batches(...)`` result whose arrays carry a leading
    batch axis [B, ...]; the whole embed+AR-decode pipeline is vmapped over
    it so a micro-batching server amortizes dispatch (and, with bucketed
    padding, compilation) across requests like a continuous-batching LM
    server.  Returns (placements i32[B, M, N], logp f32[B, M, N]).
    """
    b = sgb.op.shape[0]
    keys = jax.random.split(key, b)

    def one(op, feats, nbr_idx, nbr_mask, node_mask, mem_frac, comp_frac,
            dev_feats, dev_mem_cap, k):
        gb = GraphBatch(op, feats, nbr_idx, nbr_mask, node_mask, mem_frac,
                        comp_frac, dev_feats, dev_mem_cap, op.shape[0])
        return sample(params, cfg, gb, num_devices, k, num_samples,
                      temperature)

    return jax.vmap(one)(sgb.op, sgb.feats, sgb.nbr_idx, sgb.nbr_mask,
                         sgb.node_mask, sgb.mem_frac, sgb.comp_frac,
                         sgb.dev_feats, sgb.dev_mem_cap, keys)


def logp_and_entropy(params, cfg: PolicyConfig, gb: GraphBatch,
                     num_devices: int, placements: jnp.ndarray,
                     incumbent=None, migration_bias: float = 0.0
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Teacher-forced per-node logp of placements [M,N] + mean entropy.

    ``incumbent``/``migration_bias`` must match the sampling call (both
    default off) so biased PPO ratios stay exact."""
    h, c = _embed(params, cfg, gb)
    # the shared decode kwargs already carry segment= for segmented cfgs;
    # attn_impl is TF-only (the AR sampler has no parallel attention to
    # kernelize), so it joins here rather than in _decode_fn
    kwargs = dict(_decode_fn(cfg, gb, num_devices)[1],
                  attn_impl=cfg.attn_impl)
    tf_fn = (placer.apply_tf_segmented if cfg.scale.segment is not None
             else placer.apply_tf)
    bias = incumbent_bias(cfg, gb, incumbent, migration_bias)

    def one(pl):
        lg = tf_fn(params["placer"], h, gb.node_mask, pl, c, gb.mem_frac,
                   gb.comp_frac, gb.dev_feats, incumbent_bias=bias,
                   **kwargs)
        logp = jax.nn.log_softmax(lg, axis=-1)
        node_lp = jnp.take_along_axis(logp, pl[:, None], axis=-1)[:, 0]
        p = jnp.exp(logp)
        ent = -(p * logp).sum(-1)
        return node_lp, ent

    node_lp, ent = jax.vmap(one)(placements)
    denom = jnp.maximum(gb.node_mask.sum(), 1.0)
    mean_ent = (ent * gb.node_mask[None, :]).sum() / (denom * placements.shape[0])
    return node_lp * gb.node_mask[None, :], mean_ent


def greedy(params, cfg: PolicyConfig, gb: GraphBatch, num_devices: int,
           key=None) -> jnp.ndarray:
    """Low-temperature AR decode (argmax would need a dedicated path; a
    near-zero-temperature sample is equivalent for evaluation)."""
    if key is None:
        key = jax.random.PRNGKey(0)
    h, c = _embed(params, cfg, gb)
    # temperature ~0: sharpen by scaling head params is intrusive; instead
    # draw K samples and let the caller pick the best via the simulator.
    fn, kwargs = _decode_fn(cfg, gb, num_devices)
    devs, _ = fn(params["placer"], h, gb.node_mask, c, key, gb.mem_frac,
                 gb.comp_frac, gb.dev_feats, **kwargs)
    return devs.astype(jnp.int32)
