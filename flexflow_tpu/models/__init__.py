"""Model zoo — the reference's examples/cpp + examples/python workloads
(SURVEY.md 2.7), built on the framework's builder API."""

from .alexnet import build_alexnet
from .transformer import build_transformer, build_transformer_lm
from .resnet import build_resnet
from .inception import build_inception_v3
from .dlrm import build_dlrm
from .moe import build_moe_fused, build_moe_reference
from .candle_uno import build_candle_uno
from .nmt_lstm import build_nmt_lstm, build_nmt_seq2seq
from .cmdaplus import build_cmdaplus_lm
from .falcon_h1 import build_falcon_h1_lm
from .lfm2_moe import build_lfm2_moe_lm
from .minicpm_sala import build_minicpm_sala_lm
from .olmo_hybrid import build_olmo_hybrid_lm
from .olmoe import build_olmoe_lm
from .phi4flash import build_phi4flash_lm
from .qwen3_next import build_qwen3_next_lm

__all__ = [
    "build_alexnet",
    "build_transformer",
    "build_transformer_lm",
    "build_resnet",
    "build_inception_v3",
    "build_dlrm",
    "build_moe_reference",
    "build_moe_fused",
    "build_cmdaplus_lm",
    "build_falcon_h1_lm",
    "build_lfm2_moe_lm",
    "build_minicpm_sala_lm",
    "build_olmo_hybrid_lm",
    "build_olmoe_lm",
    "build_phi4flash_lm",
    "build_qwen3_next_lm",
    "build_candle_uno",
    "build_nmt_lstm",
    "build_nmt_seq2seq",
]
