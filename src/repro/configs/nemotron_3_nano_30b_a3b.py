"""NVIDIA-Nemotron-3-Nano-30B-A3B [hf: nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
config.json] — a Mamba-2 / MoE / attention hybrid whose 52 layers each hold
one sublayer.

``hybrid_override_pattern`` ``MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME``
gives the kind of each layer: ``M`` Mamba-2, ``E`` MoE, ``*`` GQA attention,
each pre-normed with a residual.  It is 5 x ``MEMEM*E``, then ``MEMEMEM*E``,
then ``MEMEMEME``, written out here as one group of 52 descriptors.

Sizes, all from the config: hidden 2688, vocabulary 131,072, untied
embeddings, norm eps 1e-5.  Mamba-2: 64 heads of dim 64 (d_inner 4096),
state 128, 8 groups for B and C, conv 4 with bias, chunk 128, gated RMSNorm
per group.  Attention: 32 query heads, 2 KV heads, head dim 128, no bias.
MoE: 128 routed experts of width 1856, top-6, relu^2 and not gated,
``norm_topk_prob``, ``routed_scaling_factor`` 2.5; one shared expert of
width 3712.  ``n_group`` = ``topk_group`` = 1, so routing is not
group-limited.

Assumed (the config does not settle them):

* the router scores with a sigmoid and chooses the top 6 by score plus a
  selection-only bias, as the DeepSeek-V3-style keys ``n_group``,
  ``topk_group``, ``norm_topk_prob`` and ``routed_scaling_factor`` suggest;
* attention applies no RoPE: the Mamba-2 layers carry position, and
  ``rope_theta`` / ``partial_rotary_factor`` 1 are kept in the file unused.
  RoPE would add a few elementwise nodes per attention layer and no matmul;
* the expert capacity factor is the repository's 1.25 (tokens over
  capacity pass through the residual);
* the repository's MoE auxiliary load-balancing loss is kept in the
  training loss.
"""
from repro.configs.base import (ArchConfig, FFN_MOE, FFN_NONE, LayerDesc,
                                MIXER_ATTN, MIXER_MAMBA, MIXER_NONE, MoEConfig,
                                register)

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

_KINDS = {"M": LayerDesc(mixer=MIXER_MAMBA, ffn=FFN_NONE),
          "E": LayerDesc(mixer=MIXER_NONE, ffn=FFN_MOE),
          "*": LayerDesc(mixer=MIXER_ATTN, ffn=FFN_NONE)}


def layers(pattern: str):
    return tuple(_KINDS[c] for c in pattern)


FULL = ArchConfig(
    name="nemotron-3-nano-30b-a3b", family="hybrid",
    n_layers=52, d_model=2688, n_heads=32, n_kv=2, d_ff=1856, vocab=131072,
    head_dim=128, rope=False, rope_theta=10000.0,
    pattern=layers(PATTERN),
    moe=MoEConfig(num_experts=128, top_k=6, num_shared=1, d_expert=1856,
                  d_shared=3712, capacity_factor=1.25, activation="relu2",
                  router="sigmoid", routed_scale=2.5),
    tie_embeddings=False,
    ssm_state=128, ssm_heads=64, ssm_head_dim=64, ssm_groups=8,
    ssm_chunk=128, norm_eps=1e-5,
    optimizer_state_dtype="float32",
    notes="52 one-sublayer layers: 23 Mamba-2, 23 MoE (128e top-6 relu^2 + "
          "1 shared), 6 GQA attention.",
)

# the three layer kinds in the published period MEMEM*E, at tiny widths
REDUCED = ArchConfig(
    name="nemotron-3-nano-30b-a3b", family="hybrid",
    n_layers=7, d_model=64, n_heads=4, n_kv=2, d_ff=32, vocab=256,
    head_dim=16, rope=False,
    pattern=layers(PATTERN[:7]),
    moe=MoEConfig(num_experts=8, top_k=3, num_shared=1, d_expert=32,
                  d_shared=48, capacity_factor=1.25, activation="relu2",
                  router="sigmoid", routed_scale=2.5),
    tie_embeddings=False,
    ssm_state=16, ssm_heads=8, ssm_head_dim=8, ssm_groups=2, ssm_chunk=8,
    norm_eps=1e-5,
    param_dtype="float32", activ_dtype="float32",
    optimizer_state_dtype="float32", remat=False,
)

register(FULL, REDUCED)
