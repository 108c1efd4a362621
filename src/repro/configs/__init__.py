from repro.configs.base import (  # noqa: F401
    ArchConfig, LayerDesc, MoEConfig, ShapeConfig, SHAPES,
    get_config, get_reduced, list_archs, cell_is_skipped,
    MIXER_ATTN, MIXER_ATTN_LOCAL, MIXER_MAMBA, MIXER_MLSTM, MIXER_SLSTM,
    MIXER_NONE,
    FFN_DENSE, FFN_MOE, FFN_MOE_DENSE, FFN_NONE,
)
