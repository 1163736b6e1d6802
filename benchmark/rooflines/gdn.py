"""Operations and bytes a hybrid Gated DeltaNet / attention decoder NEEDS,
from shapes (``families/olmo_hybrid.py`` ``dims``), whatever implements
them:

* the whole model, for ``serve.mfu_hybrid``: matmul FLOPs of every layer
  by its kind (the full layers' four projections; the linear layers' q, k,
  v, output gate and output, and the two per-head gates), attention's two
  products in the full layers only, and the element-wise work of the conv
  and the delta rule in the linear layers;
* the decode update (``tdx_gdn_decode_update``: one position a live lane,
  the state read and written once) and the chunk (``tdx_gdn_chunk``: the
  rule over a prompt's real positions), for their rooflines.

Per position, head and linear layer the rule touches ``d_k x d_v`` state
elements, 7 operations each: the decay, the multiply-add of ``k^T S``, the
multiply-add of the rank-one update, and the multiply-add of ``q^T S``;
``2 d_v`` more for ``beta (v - k^T S)``.  The conv is ``2 d_conv`` a
channel; the l2 norms, the output norm and its gate about 4 a channel.
Padding to a bucket, idle lanes and recompute are not needed work.

A kernel's count is what the kernel itself moves: the decode update's is
the state once in and once out, ``q`` and ``k`` and ``v`` in (bfloat16),
``beta`` and ``alpha`` in and ``o`` out (float32); the conv and its tail
run beside the kernel, in XLA, and are not in its count.  The chunk's is
the state once in and once out a call and the same vectors a position.
"""

from __future__ import annotations

from benchmark.rooflines.model import (  # noqa: F401
    causal_pairs, least_seconds)

STATE_BYTES = 4      # the delta-rule state is float32
ACT_BYTES = 2        # q, k, v and the conv tail are bfloat16
GATE_BYTES = 4       # beta, alpha and the rule's output are float32


def linear_matmul_params(c: dict) -> int:
    d, H, dk, dv = c["d_model"], c["lin_heads"], c["d_k"], c["d_v"]
    return d * (2 * H * dk + 2 * H * dv) + H * dv * d + 2 * d * H


def attention_matmul_params(c: dict) -> int:
    d, h, kv, hd = c["d_model"], c["n_heads"], c["n_kv_heads"], c["head_dim"]
    return 2 * d * h * hd + 2 * d * kv * hd


def mlp_params(c: dict) -> int:
    return 3 * c["d_model"] * c["d_ff"]


def matmul_params(c: dict, with_head: bool = True) -> int:
    """Parameters in a matmul for every position (the embedding lookup is
    none; the untied head is one)."""
    n = (c["n_linear_layers"] * linear_matmul_params(c)
         + c["n_full_layers"] * attention_matmul_params(c)
         + c["n_layers"] * mlp_params(c))
    return n + (c["d_model"] * c["vocab_size"] if with_head else 0)


def rule_flops(c: dict) -> float:
    """The delta rule of ONE linear layer at one position."""
    H, dk, dv = c["lin_heads"], c["d_k"], c["d_v"]
    return H * (7.0 * dk * dv + 2.0 * dv)


def linear_flops_per_position(c: dict) -> float:
    """Conv, rule, norms and gate of ONE linear layer at one position."""
    return (rule_flops(c) + 2.0 * c["d_conv"] * c["conv_channels"]
            + 4.0 * c["conv_channels"])


def attention_flops(c: dict, context_sum: int) -> float:
    return (4.0 * c["n_heads"] * c["head_dim"] * context_sum
            * c["n_full_layers"])


def forward_flops(c: dict, positions: int, context_sum: int,
                  head_positions: int | None = None) -> float:
    """Forward FLOPs for ``positions`` positions whose full-attention
    layers attend ``context_sum`` keys in all; the head only where logits
    are needed."""
    hp = positions if head_positions is None else head_positions
    return (2.0 * matmul_params(c, with_head=False) * positions
            + 2.0 * c["d_model"] * c["vocab_size"] * hp
            + attention_flops(c, context_sum)
            + linear_flops_per_position(c) * c["n_linear_layers"] * positions)


def _vector_bytes(c: dict) -> int:
    """What one position of one linear layer brings to the rule and takes
    from it: q, k, v in; beta and alpha a head in; o out."""
    H, dk, dv = c["lin_heads"], c["d_k"], c["d_v"]
    return ((2 * H * dk + H * dv) * ACT_BYTES + 2 * H * GATE_BYTES
            + H * dv * GATE_BYTES)


def _state_bytes(c: dict) -> int:
    return c["lin_heads"] * c["d_k"] * c["d_v"] * STATE_BYTES


def decode_update_needs(c: dict, lane_ticks: int) -> dict:
    """``lane_ticks``: live lanes summed over decode ticks.  A lane and
    linear layer: the state read and written, the vectors."""
    per = 2 * _state_bytes(c) + _vector_bytes(c)
    lg = c["n_linear_layers"]
    return {"flops": rule_flops(c) * lg * lane_ticks,
            "bytes": float(per) * lg * lane_ticks}


def chunk_needs(c: dict, positions: int, calls: int) -> dict:
    """``positions``: real prompt positions in all; ``calls``: prefill /
    chunk program calls (each reads and writes one lane's state)."""
    lg = c["n_linear_layers"]
    return {"flops": rule_flops(c) * lg * positions,
            "bytes": float(_vector_bytes(c) * positions
                           + 2 * _state_bytes(c) * calls) * lg}
