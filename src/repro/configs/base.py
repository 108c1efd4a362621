"""Architecture + shape configuration system.

Every assigned architecture is a declarative :class:`ArchConfig`; the model
builder (``repro.models.model``) turns it into parameter trees, train/serve
steps and sharding specs.  ``reduced()`` produces the CPU-smoke-test version
of the same family (same block pattern, tiny dims).

Layer patterns: a model is ``scan`` over ``n_layers/period`` groups; each
group applies ``period`` layer descriptors.  Descriptors say which mixer
(attention variant / SSM) and which FFN (dense / MoE) a layer uses — this
single mechanism expresses dense stacks, gemma's local/global alternation,
deepseek/arctic MoE, xLSTM's mLSTM/sLSTM alternation, jamba's 1:7
attention:mamba interleave and Nemotron-H's layers of one sublayer each
(Mamba-2, MoE or attention alone), written out as one group.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

# ---------------------------------------------------------------- descriptors
MIXER_ATTN = "attn"            # global causal attention
MIXER_ATTN_LOCAL = "attn_local"
MIXER_MAMBA = "mamba"          # SSD-style selective SSM
MIXER_MLSTM = "mlstm"
MIXER_SLSTM = "slstm"
MIXER_NONE = "none"            # nemotron-h: a layer holding its FFN alone

FFN_DENSE = "dense"
FFN_MOE = "moe"
FFN_MOE_DENSE = "moe+dense"    # arctic: MoE in parallel with a dense residual
FFN_NONE = "none"              # xlstm: the mixer carries the channel mixing


@dataclasses.dataclass(frozen=True)
class LayerDesc:
    mixer: str = MIXER_ATTN
    ffn: str = FFN_DENSE


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    num_shared: int = 0          # deepseek: always-on shared experts
    d_expert: Optional[int] = None   # defaults to d_ff
    capacity_factor: float = 1.25
    router_dtype: str = "float32"
    d_shared: Optional[int] = None   # shared experts' width; defaults to d_expert
    activation: str = "swiglu"       # swiglu (gated) | relu2 (non-gated relu^2)
    router: str = "softmax"          # softmax | sigmoid (scores, top-k by
                                     # score + a selection-only bias)
    routed_scale: float = 1.0        # scales the routed experts' weights

    def expert_mats(self) -> int:
        """Weight matrices per expert: up, down and, when gated, gate."""
        return 3 if self.activation == "swiglu" else 2


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                  # dense | moe | audio | vlm | ssm | hybrid
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None       # default d_model // n_heads
    pattern: Tuple[LayerDesc, ...] = (LayerDesc(),)
    moe: Optional[MoEConfig] = None
    # attention flavor flags
    rope: bool = True
    rope_theta: float = 10000.0
    qk_norm: bool = False
    mrope: bool = False                  # qwen2-vl 3D rope
    attn_softcap: Optional[float] = None # gemma2
    final_softcap: Optional[float] = None
    local_window: int = 4096             # for MIXER_ATTN_LOCAL
    # structure flags
    enc_dec: bool = False                # whisper
    n_enc_layers: int = 0
    tie_embeddings: bool = True
    # ssm dims
    ssm_state: int = 64
    ssm_heads: Optional[int] = None
    ssm_head_dim: Optional[int] = None   # d_inner = heads x head dim; default 2·d_model
    ssm_groups: int = 0                  # >0: Mamba-2's own layer, B and C per
                                         # group (fused in-projection, conv over
                                         # x, B, C, gated RMSNorm); 0: the SSD
                                         # stand-in (one B, C; conv over x)
    ssm_chunk: int = 256                 # SSD chunk length
    norm_eps: float = 1e-6
    # numerics / memory policy
    param_dtype: str = "bfloat16"
    activ_dtype: str = "bfloat16"
    optimizer_state_dtype: str = "bfloat16"   # bf16 Adam for >=100B (DESIGN §6)
    remat: bool = True
    microbatches: int = 1                # gradient accumulation splits
    logits_chunk: int = 1024             # chunked cross-entropy block
    # modality frontend stub (audio frames / vision patches)
    frontend: Optional[str] = None       # None | "audio" | "vision"
    notes: str = ""

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def period(self) -> int:
        return len(self.pattern)

    @property
    def n_groups(self) -> int:
        assert self.n_layers % self.period == 0, (self.name, self.n_layers,
                                                  self.period)
        return self.n_layers // self.period

    @property
    def ssm_inner(self) -> int:
        """Mamba's inner width."""
        if self.ssm_head_dim:
            return self.ssm_heads * self.ssm_head_dim
        return 2 * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.ssm_heads or max(self.ssm_inner // 128, 1)

    def first_layers(self, n: int) -> "ArchConfig":
        """The same model cut to its first ``n`` layers, which must be whole
        periods: whole groups, or, where the pattern is written out as one
        group, a prefix that the pattern repeats and that holds every kind
        of layer the model has."""
        layers = self.pattern * self.n_groups
        if n == self.n_layers:
            return self
        head = layers[:n]
        whole = (n % self.period == 0 if self.n_groups > 1 else
                 0 < n and layers[n:2 * n] == head and
                 set(head) == set(layers))
        if not whole:
            raise ValueError(f"{self.name}: the first {n} layers are not "
                             f"whole periods of its pattern")
        if self.n_groups > 1:
            return dataclasses.replace(self, n_layers=n)
        return dataclasses.replace(self, n_layers=n, pattern=head)

    def has_mixer(self, kind: str) -> bool:
        return any(d.mixer == kind for d in self.pattern)

    def uses_moe(self) -> bool:
        return any(d.ffn in (FFN_MOE, FFN_MOE_DENSE) for d in self.pattern)

    def param_count(self) -> int:
        """Approximate total parameter count (embeddings + blocks)."""
        d, ff, hd = self.d_model, self.d_ff, self.hd
        qkv = d * hd * (self.n_heads + 2 * self.n_kv) + self.n_heads * hd * d
        dense_ffn = 3 * d * ff
        total = self.vocab * d * (1 if self.tie_embeddings else 2)
        for i in range(self.n_layers):
            desc = self.pattern[i % self.period]
            if desc.mixer in (MIXER_ATTN, MIXER_ATTN_LOCAL):
                total += qkv
            elif desc.mixer == MIXER_MAMBA and self.ssm_groups:
                di, gn = self.ssm_inner, self.ssm_groups * self.ssm_state
                total += d * (2 * di + 2 * gn + self.ssm_nheads) + di * d
            elif desc.mixer == MIXER_MAMBA:
                di = 2 * d
                total += 2 * d * di + di * d + di * (2 * self.ssm_state + 2)
            elif desc.mixer == MIXER_MLSTM:
                di = 2 * d
                total += 4 * d * di + di * d
            elif desc.mixer == MIXER_SLSTM:
                total += 8 * d * d
            if desc.ffn == FFN_DENSE:
                total += dense_ffn
            elif desc.ffn in (FFN_MOE, FFN_MOE_DENSE):
                m = self.moe
                de = m.d_expert or ff
                total += m.num_experts * m.expert_mats() * d * de + \
                    d * m.num_experts
                if m.num_shared:
                    total += m.num_shared * m.expert_mats() * d * \
                        (m.d_shared or de)
                if desc.ffn == FFN_MOE_DENSE:
                    total += dense_ffn
        if self.enc_dec:
            total += self.n_enc_layers * (qkv + dense_ffn)
            total += self.n_layers * qkv   # cross attention
        return int(total)

    def active_param_count(self) -> int:
        """Active params per token (for MoE MODEL_FLOPS = 6·N_active·D)."""
        if not self.uses_moe():
            return self.param_count()
        d, ff = self.d_model, self.d_ff
        m = self.moe
        de = m.d_expert or ff
        total = self.param_count()
        for i in range(self.n_layers):
            desc = self.pattern[i % self.period]
            if desc.ffn in (FFN_MOE, FFN_MOE_DENSE):
                inactive = (m.num_experts - m.top_k) * m.expert_mats() * d * de
                total -= inactive
        return int(total)


# -------------------------------------------------------------------- shapes
@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                    # "train" | "prefill" | "decode"


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}

# archs allowed to run long_500k (sub-quadratic token mixing) — DESIGN.md §4
SUBQUADRATIC = ("xlstm-125m", "jamba-1.5-large-398b")


def cell_is_skipped(arch_name: str, shape_name: str) -> Optional[str]:
    if shape_name == "long_500k" and arch_name not in SUBQUADRATIC:
        return "SKIP(full-attention)"
    return None


_REGISTRY: Dict[str, "ArchConfig"] = {}
_REDUCED: Dict[str, "ArchConfig"] = {}


def register(cfg: ArchConfig, reduced: ArchConfig) -> ArchConfig:
    _REGISTRY[cfg.name] = cfg
    _REDUCED[cfg.name] = reduced
    return cfg


def get_config(name: str) -> ArchConfig:
    _ensure_loaded()
    return _REGISTRY[name]


def get_reduced(name: str) -> ArchConfig:
    _ensure_loaded()
    return _REDUCED[name]


def list_archs() -> List[str]:
    _ensure_loaded()
    return sorted(_REGISTRY)


def _ensure_loaded():
    if _REGISTRY:
        return
    import importlib
    for mod in ("starcoder2_3b", "qwen3_8b", "mistral_large_123b", "gemma2_9b",
                "arctic_480b", "deepseek_moe_16b", "whisper_base",
                "qwen2_vl_7b", "xlstm_125m", "jamba_1_5_large_398b",
                "nemotron_3_nano_30b_a3b"):
        importlib.import_module(f"repro.configs.{mod}")
