"""Model families: GPT-2, Llama, T5, Mixtral, ViT, and the hybrid stacks
(Jamba, AFMoE, Olmo-Hybrid) — flax.linen, TPU-first."""

from .configs import (
    GPT2_125M,
    LLAMA3_8B,
    LLAMA3_70B,
    MIXTRAL_8X7B,
    PRESETS,
    T5_11B,
    TINY,
    TINY_AFMOE,
    TINY_GPT2,
    TINY_JAMBA,
    TINY_MOE,
    TINY_OLMO_HYBRID,
    TINY_T5,
    TINY_VIT,
    VIT_B16,
    VIT_L16,
    AfmoeConfig,
    EncDecConfig,
    GatedDeltaNetConfig,
    MambaConfig,
    MoEConfig,
    TransformerConfig,
    VisionConfig,
)
from .decomposition import DecodeDecomposition, PipelineDecomposition
from .afmoe import AfmoeModel, make_afmoe
from .gpt2 import GPT2Model, make_gpt2
from .jamba import JambaModel, make_jamba
from .llama import LlamaModel, make_llama
from .olmo_hybrid import OlmoHybridModel, make_olmo_hybrid
from .mixtral import make_mixtral
from .plans import decoder_lm_plan, t5_plan, vit_plan
from .t5 import T5Model, make_t5
from .vit import ViTModel, make_vit

__all__ = [
    "TransformerConfig",
    "AfmoeConfig",
    "EncDecConfig",
    "VisionConfig",
    "MoEConfig",
    "MambaConfig",
    "GatedDeltaNetConfig",
    "PRESETS",
    "GPT2_125M",
    "LLAMA3_8B",
    "LLAMA3_70B",
    "MIXTRAL_8X7B",
    "T5_11B",
    "TINY",
    "TINY_AFMOE",
    "TINY_GPT2",
    "TINY_JAMBA",
    "TINY_MOE",
    "TINY_OLMO_HYBRID",
    "TINY_T5",
    "TINY_VIT",
    "VIT_B16",
    "VIT_L16",
    "AfmoeModel",
    "DecodeDecomposition",
    "GPT2Model",
    "JambaModel",
    "LlamaModel",
    "OlmoHybridModel",
    "PipelineDecomposition",
    "T5Model",
    "ViTModel",
    "make_afmoe",
    "make_gpt2",
    "make_jamba",
    "make_llama",
    "make_mixtral",
    "make_olmo_hybrid",
    "make_t5",
    "make_vit",
    "decoder_lm_plan",
    "t5_plan",
    "vit_plan",
]
