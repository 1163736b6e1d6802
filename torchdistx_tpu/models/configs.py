"""Model configurations for the benchmark families (BASELINE.json configs).

Presets cover the five driver-set benchmark targets — GPT-2 125M,
Llama-3 8B, Llama-3 70B, T5-11B, Mixtral 8×7B — plus tiny variants used
by tests and multi-chip dry runs.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8
    top_k: int = 2
    # jitter / load-balancing loss weight
    router_aux_weight: float = 0.02


@dataclass(frozen=True)
class MambaConfig:
    """A hybrid stack's state-space side (Jamba): Mamba-1 mixers in
    every layer but one a period.  Layer ``i`` is an attention layer iff
    ``i % attn_period == attn_offset``; every layer keeps the dense MLP."""

    d_state: int = 16
    d_conv: int = 4
    expand: int = 2      # d_inner = expand * d_model
    dt_rank: int = 160
    attn_period: int = 14
    attn_offset: int = 7


@dataclass(frozen=True)
class GatedDeltaNetConfig:
    """A hybrid stack's linear-attention side (Olmo-Hybrid;
    models/olmo_hybrid.py): Gated DeltaNet mixers in every layer but one a
    period, which is a full-attention layer.  A linear layer's state is a
    ``[d_k, d_v]`` matrix a head, advanced by a gated rank-one delta rule;
    ``allow_neg_eigval`` widens the rule's step ``beta`` from (0, 1) to
    (0, 2).  Layer ``i`` is a full-attention layer iff
    ``i % attn_period == attn_offset``."""

    n_heads: int = 30       # key heads = value heads
    d_k: int = 96
    d_v: int = 192
    d_conv: int = 4
    allow_neg_eigval: bool = True
    attn_period: int = 4
    attn_offset: int = 3


@dataclass(frozen=True)
class AfmoeConfig:
    """The AFMoE stack (arcee-ai Trinity; models/afmoe.py): sandwich-normed
    blocks with gated, QK-normed attention that is windowed (with rotary)
    or full (no positional term) by layer, ``n_dense_layers`` leading
    dense MLPs and sigmoid-routed expert layers with one shared expert
    behind them.  The router scores all ``n_experts``; THIS replica holds
    the ``held_experts`` from ``first_expert`` on and computes their part
    (an expert-parallel chip's share; the whole layer where it holds all)."""

    n_experts: int = 256
    top_k: int = 4
    d_expert: int = 3072     # width of a routed expert and of the shared one
    n_dense_layers: int = 1
    layer_types: Tuple[str, ...] = ()  # "sliding" | "full", one a layer
    window: int = 4096
    route_scale: float = 2.448
    first_expert: int = 0
    held_experts: int = 32


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: Optional[int] = None  # None = MHA; < n_heads = GQA
    d_ff: int = 2048
    max_seq_len: int = 2048
    head_dim: Optional[int] = None

    # flavor
    use_bias: bool = False
    activation: str = "silu"  # "silu" (SwiGLU) | "gelu" (plain MLP)
    norm: str = "rmsnorm"  # "rmsnorm" | "layernorm"
    positions: str = "rope"  # "rope" | "learned" | "relative" | "none"
    tie_embeddings: bool = False
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    relative_pos_buckets: int = 32  # t5-style
    relative_pos_max_distance: int = 128

    moe: Optional[MoEConfig] = None
    mamba: Optional[MambaConfig] = None  # hybrid stack (models/jamba.py)
    afmoe: Optional[AfmoeConfig] = None  # AFMoE stack (models/afmoe.py)
    # hybrid linear-attention stack (models/olmo_hybrid.py)
    olmo_hybrid: Optional[GatedDeltaNetConfig] = None

    dtype: jnp.dtype = jnp.bfloat16  # activation/compute dtype
    param_dtype: jnp.dtype = jnp.float32

    # remat policy for the blocks: "none" | "full"
    remat: str = "none"

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_size(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def replace(self, **kw) -> "TransformerConfig":
        return replace(self, **kw)


@dataclass(frozen=True)
class EncDecConfig:
    """T5-style encoder-decoder: one TransformerConfig per stack."""

    encoder: TransformerConfig
    decoder: TransformerConfig
    vocab_size: int
    tie_embeddings: bool = True


@dataclass(frozen=True)
class VisionConfig:
    """ViT-style image encoder: a TransformerConfig stack over patches."""

    encoder: TransformerConfig
    image_size: int = 224
    patch_size: int = 16
    channels: int = 3
    n_classes: int = 1000
    pool: str = "cls"  # "cls" (class token) | "gap" (mean over patches)

    @property
    def n_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def seq_len(self) -> int:
        return self.n_patches + (1 if self.pool == "cls" else 0)

    def replace(self, **kw) -> "VisionConfig":
        return replace(self, **kw)


# ---------------------------------------------------------------------------
# Presets (sizes follow the public model cards; see BASELINE.md)
# ---------------------------------------------------------------------------

GPT2_125M = TransformerConfig(
    use_bias=True,
    vocab_size=50257,
    d_model=768,
    n_layers=12,
    n_heads=12,
    d_ff=3072,
    max_seq_len=1024,
    activation="gelu",
    norm="layernorm",
    positions="learned",
    tie_embeddings=True,
    norm_eps=1e-5,
)

LLAMA3_8B = TransformerConfig(
    vocab_size=128256,
    d_model=4096,
    n_layers=32,
    n_heads=32,
    n_kv_heads=8,
    d_ff=14336,
    max_seq_len=8192,
    rope_theta=500000.0,
)

LLAMA3_70B = TransformerConfig(
    vocab_size=128256,
    d_model=8192,
    n_layers=80,
    n_heads=64,
    n_kv_heads=8,
    d_ff=28672,
    max_seq_len=8192,
    rope_theta=500000.0,
    remat="full",
)

MIXTRAL_8X7B = TransformerConfig(
    vocab_size=32000,
    d_model=4096,
    n_layers=32,
    n_heads=32,
    n_kv_heads=8,
    d_ff=14336,
    max_seq_len=32768,
    rope_theta=1000000.0,
    moe=MoEConfig(n_experts=8, top_k=2),
)

_T5_STACK = TransformerConfig(
    vocab_size=32128,
    d_model=1024,
    n_layers=24,
    n_heads=128,
    head_dim=128,
    d_ff=65536,
    max_seq_len=512,
    activation="gelu",
    norm="rmsnorm",
    positions="relative",
    norm_eps=1e-6,
)

T5_11B = EncDecConfig(
    encoder=_T5_STACK,
    decoder=_T5_STACK,
    vocab_size=32128,
    tie_embeddings=True,
)

_VIT_STACK = TransformerConfig(
    vocab_size=1,  # unused by the vision family
    d_model=768,
    n_layers=12,
    n_heads=12,
    d_ff=3072,
    max_seq_len=197,
    use_bias=True,
    activation="gelu",
    norm="layernorm",
    positions="learned",
    norm_eps=1e-6,
)

VIT_B16 = VisionConfig(encoder=_VIT_STACK)

VIT_L16 = VisionConfig(
    encoder=_VIT_STACK.replace(d_model=1024, n_layers=24, n_heads=16, d_ff=4096),
)

# -- tiny variants for tests / dry runs ------------------------------------

TINY = TransformerConfig(
    vocab_size=256,
    d_model=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    d_ff=128,
    max_seq_len=128,
    dtype=jnp.float32,
)

TINY_GPT2 = GPT2_125M.replace(
    vocab_size=256, d_model=64, n_layers=2, n_heads=4, d_ff=128, max_seq_len=128,
    dtype=jnp.float32,
)

TINY_MOE = TINY.replace(moe=MoEConfig(n_experts=4, top_k=2))

# One whole period of Jamba's pattern: 13 Mamba-1 layers round one MQA
# attention layer, no positions, tied head.
TINY_JAMBA = TransformerConfig(
    vocab_size=256, d_model=64, n_layers=14, n_heads=4, n_kv_heads=1,
    d_ff=128, max_seq_len=128, positions="none", tie_embeddings=True,
    norm_eps=1e-6, dtype=jnp.float32,
    mamba=MambaConfig(d_state=16, d_conv=4, expand=2, dt_rank=8,
                      attn_period=14, attn_offset=7),
)

# Two whole periods of Olmo-Hybrid's pattern (three Gated DeltaNet layers,
# then full attention with QK-norm), no positions, untied head.
TINY_OLMO_HYBRID = TransformerConfig(
    vocab_size=256, d_model=64, n_layers=8, n_heads=4, n_kv_heads=4,
    head_dim=16, d_ff=128, max_seq_len=256, positions="none",
    tie_embeddings=False, norm_eps=1e-6, dtype=jnp.float32,
    olmo_hybrid=GatedDeltaNetConfig(n_heads=2, d_k=16, d_v=32, d_conv=4,
                                    attn_period=4, attn_offset=3),
)

TINY_AFMOE = TransformerConfig(
    vocab_size=256, d_model=64, n_layers=5, n_heads=4, n_kv_heads=1,
    head_dim=16, d_ff=128, max_seq_len=256, rope_theta=10000.0,
    tie_embeddings=False, norm_eps=1e-5, dtype=jnp.float32,
    afmoe=AfmoeConfig(
        n_experts=8, top_k=2, d_expert=32, n_dense_layers=1,
        layer_types=("sliding", "sliding", "full", "sliding", "sliding"),
        window=16, first_expert=0, held_experts=4),
)

TINY_T5 = EncDecConfig(
    encoder=_T5_STACK.replace(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, head_dim=16, d_ff=128,
        max_seq_len=64, dtype=jnp.float32,
    ),
    decoder=_T5_STACK.replace(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, head_dim=16, d_ff=128,
        max_seq_len=64, dtype=jnp.float32,
    ),
    vocab_size=256,
)

TINY_VIT = VisionConfig(
    encoder=_VIT_STACK.replace(
        d_model=64, n_layers=2, n_heads=4, d_ff=128, max_seq_len=17,
        dtype=jnp.float32,
    ),
    image_size=32,
    patch_size=8,
    n_classes=10,
)

PRESETS = {
    "gpt2-125m": GPT2_125M,
    "llama3-8b": LLAMA3_8B,
    "llama3-70b": LLAMA3_70B,
    "mixtral-8x7b": MIXTRAL_8X7B,
    "t5-11b": T5_11B,
    "vit-b16": VIT_B16,
    "vit-l16": VIT_L16,
    "tiny": TINY,
    "tiny-gpt2": TINY_GPT2,
    "tiny-moe": TINY_MOE,
    "tiny-jamba": TINY_JAMBA,
    "tiny-afmoe": TINY_AFMOE,
    "tiny-olmo-hybrid": TINY_OLMO_HYBRID,
    "tiny-t5": TINY_T5,
    "tiny-vit": TINY_VIT,
}
