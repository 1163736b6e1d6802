"""Stock sharding plans for the model families.

Megatron-style 2D (fsdp × tp) layouts over the scan-stacked parameter
trees, with expert weights over ``ep``.  Paths are the flattened flax
param paths (e.g. ``params.blocks.block.attn.wq.kernel``); the leading
layer dim stays unsharded (it belongs to ``pp`` when pipelining, handled
by parallel/pipeline.py's own layout).

All rules degrade gracefully: indivisible dims fall back to replication
with a warning (parallel/sharding.py).
"""

from __future__ import annotations

from typing import Optional

from jax.sharding import PartitionSpec as P

from ..parallel.sharding import ShardingPlan


def _block_rules(fsdp: Optional[str], tp: Optional[str]):
    """Megatron-style rules for the shared Block (attention + dense MLP)
    param layouts — one copy consumed by every family plan."""
    return [
        # attention projections [L, d, H, hd] / [L, H, hd, d]
        (r".*attn\.w[qkv]\.kernel", P(None, fsdp, tp, None)),
        (r".*attn\.wo\.kernel", P(None, tp, None, fsdp)),
        (r".*attn\.w[qkv]\.bias", P(None, tp, None)),
        (r".*attn\.wo\.bias", P()),
        # dense MLP [L, d, ff] / [L, ff, d]
        (r".*mlp\.w_(gate|up)\.kernel", P(None, fsdp, tp)),
        (r".*mlp\.w_down\.kernel", P(None, tp, fsdp)),
        (r".*mlp\.w_(gate|up)\.bias", P(None, tp)),
        (r".*mlp\.w_down\.bias", P()),
    ]


def _jamba_rules(fsdp: Optional[str], tp: Optional[str]):
    """Rules for the hybrid stack's three parameter stacks
    (models/jamba.py; flat names ``mamba_*`` / ``attn_*`` / ``ffn_*``,
    leading layer axis unsharded).  The mixer is split over ``tp`` along
    its channels (``d_inner``), as the recurrent state is
    (``serve.kv_cache.state_sharding``): ``in_proj``'s columns and
    everything channel-wise on that axis, ``out_proj``'s rows; the
    small ``x_proj`` / ``dt_proj`` along the channels too.  Attention and
    the MLP follow :func:`_block_rules` (a single KV head does not divide
    and falls back to replication)."""
    return [
        (r".*mamba_in_proj", P(None, fsdp, tp)),        # [L, d, 2*Di]
        (r".*mamba_out_proj", P(None, tp, fsdp)),       # [L, Di, d]
        (r".*mamba_x_proj", P(None, tp, None)),         # [L, Di, R+2N]
        (r".*mamba_dt_proj", P(None, None, tp)),        # [L, R, Di]
        (r".*mamba_(conv_w|A_log)", P(None, None, tp)),  # [L, K|N, Di]
        (r".*mamba_(conv_b|dt_bias|D)", P(None, tp)),   # [L, Di]
        (r".*attn_w[qkv]", P(None, fsdp, tp, None)),    # [L, d, H, hd]
        (r".*attn_wo", P(None, tp, None, fsdp)),        # [L, H, hd, d]
        (r".*ffn_w_(gate|up)", P(None, fsdp, tp)),      # [L, d, ff]
        (r".*ffn_w_down", P(None, tp, fsdp)),           # [L, ff, d]
        (r".*params\.embedding", P(tp, fsdp)),          # [V, d], tied head
    ]


def decoder_lm_plan(
    *,
    fsdp: Optional[str] = "fsdp",
    tp: Optional[str] = "tp",
    ep: Optional[str] = "ep",
) -> ShardingPlan:
    """Plan for LlamaModel / GPT2Model / Mixtral / JambaModel param trees.

    Pass ``tp=None`` (etc.) to drop an axis entirely when building a plan
    for a mesh that intentionally lacks it — no absent-axis warnings."""
    return ShardingPlan(
        _block_rules(fsdp, tp)
        + _jamba_rules(fsdp, tp)
        + [
            # MoE experts [L, E, d, ff] / [L, E, ff, d]
            (r".*moe\.w_(gate|up)", P(None, ep, fsdp, tp)),
            (r".*moe\.w_down", P(None, ep, tp, fsdp)),
            (r".*moe\.router\.kernel", P(None, fsdp, None)),
            # embeddings / head
            (r".*(embed|wte)\.embedding", P(tp, fsdp)),
            (r".*wpe\.embedding", P(None, fsdp)),
            (r".*lm_head\.kernel", P(fsdp, tp)),
            # norms and everything else: replicated (default)
        ]
    )


def vit_plan(*, fsdp: Optional[str] = "fsdp", tp: Optional[str] = "tp") -> ShardingPlan:
    """2D plan for ViTModel param trees (shared Block rules + the vision
    stem: [P, P, C, D] conv kernel over tp — the RGB channel dim is 3,
    never divisible — positions over fsdp)."""
    return ShardingPlan(
        _block_rules(fsdp, tp)
        + [
            (r".*patch_embed\.kernel", P(None, None, None, tp)),
            (r".*pos_embed", P(None, None, fsdp)),
            (r".*head\.kernel", P(fsdp, tp)),
        ]
    )


def t5_plan(*, fsdp: Optional[str] = "fsdp", tp: Optional[str] = "tp") -> ShardingPlan:
    """2D plan for T5Model param trees (BASELINE "GSPMD 2D shard")."""
    return ShardingPlan(
        [
            (r".*(attn|cross)\.w[qkv]\.kernel", P(None, fsdp, tp, None)),
            (r".*(attn|cross)\.wo\.kernel", P(None, tp, None, fsdp)),
            (r".*mlp\.w_(gate|up)\.kernel", P(None, fsdp, tp)),
            (r".*mlp\.w_down\.kernel", P(None, tp, fsdp)),
            (r".*shared_embed\.embedding", P(tp, fsdp)),
            (r".*relpos\.embedding", P(None, tp)),
            (r".*lm_head\.kernel", P(fsdp, tp)),
        ]
    )
