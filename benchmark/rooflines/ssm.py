"""Operations and bytes a hybrid Mamba-1 / attention decoder NEEDS, from
shapes (``families/jamba.py`` ``dims``), whatever implements them:

* the whole model, for ``serve.mfu_hybrid``: matmul FLOPs of every layer
  by its kind, attention's two products in the attention layers only, and
  the element-wise work of the conv and the recurrence in the Mamba
  layers;
* the decode update (one position a live lane: conv tail shifted, state
  advanced once) and the chunk scan (the recurrence over a prompt's real
  positions), for their rooflines.

Per position and Mamba layer the recurrence touches ``d_inner x d_state``
state elements, 7 operations each: ``D*A``, ``exp``, ``* s``,
``(D u) * B``, ``+``, and the multiply-add against ``C``; the conv is
``2 * d_conv`` a channel, and ``D u``, the skip and the gate 6 a channel.
Padding to a bucket, idle lanes and recompute are not needed work."""

from __future__ import annotations

from benchmark.rooflines.model import (  # noqa: F401
    causal_pairs, least_seconds)

STATE_BYTES = 4      # the recurrent state is float32
ACT_BYTES = 2        # activations and the conv tail are bfloat16


def mamba_matmul_params(c: dict) -> int:
    d, di = c["d_model"], c["d_inner"]
    return (d * 2 * di + di * (c["dt_rank"] + 2 * c["d_state"])
            + c["dt_rank"] * di + di * d)


def attention_matmul_params(c: dict) -> int:
    d, h, kv, hd = c["d_model"], c["n_heads"], c["n_kv_heads"], c["head_dim"]
    return 2 * d * h * hd + 2 * d * kv * hd


def mlp_params(c: dict) -> int:
    return 3 * c["d_model"] * c["d_ff"]


def matmul_params(c: dict, with_head: bool = True) -> int:
    """Parameters in a matmul for every position (the embedding lookup is
    none; the tied head is one)."""
    n = (c["n_mamba_layers"] * mamba_matmul_params(c)
         + c["n_attn_layers"] * attention_matmul_params(c)
         + c["n_layers"] * mlp_params(c))
    return n + (c["d_model"] * c["vocab_size"] if with_head else 0)


def ssm_flops_per_position(c: dict) -> float:
    """Conv, recurrence, skip and gate of ONE Mamba layer at one position."""
    di = c["d_inner"]
    return 7.0 * di * c["d_state"] + 2.0 * c["d_conv"] * di + 6.0 * di


def attention_flops(c: dict, context_sum: int) -> float:
    return (4.0 * c["n_heads"] * c["head_dim"] * context_sum
            * c["n_attn_layers"])


def forward_flops(c: dict, positions: int, context_sum: int,
                  head_positions: int | None = None) -> float:
    """Forward FLOPs for ``positions`` positions whose attention layers
    attend ``context_sum`` keys in all; the head only where logits are
    needed."""
    hp = positions if head_positions is None else head_positions
    return (2.0 * matmul_params(c, with_head=False) * positions
            + 2.0 * c["d_model"] * c["vocab_size"] * hp
            + attention_flops(c, context_sum)
            + ssm_flops_per_position(c) * c["n_mamba_layers"] * positions)


def decode_update_needs(c: dict, lane_ticks: int) -> dict:
    """``lane_ticks``: live lanes summed over decode ticks.  A lane and
    Mamba layer: the state read and written, the conv tail read and
    written, the new input in, the conv's output out and in again, the
    step size and the gate in, ``B`` and ``C`` in, ``y`` out."""
    di, n, k = c["d_inner"], c["d_state"], c["d_conv"]
    per = (2 * di * n * STATE_BYTES + 2 * (k - 1) * di * ACT_BYTES
           + 6 * di * ACT_BYTES + 2 * n * ACT_BYTES)
    return {"flops": ssm_flops_per_position(c) * c["n_mamba_layers"] * lane_ticks,
            "bytes": float(per) * c["n_mamba_layers"] * lane_ticks}


def chunk_scan_needs(c: dict, positions: int, calls: int) -> dict:
    """``positions``: real prompt positions scanned in all; ``calls``:
    prefill / chunk program calls (each reads and writes one lane's
    state).  A position and Mamba layer: step size and input in, ``B``
    and ``C`` in, ``y`` out."""
    di, n = c["d_inner"], c["d_state"]
    per_pos = 3 * di * ACT_BYTES + 2 * n * ACT_BYTES
    per_call = 2 * di * n * STATE_BYTES
    lm = c["n_mamba_layers"]
    return {"flops": 7.0 * di * n * lm * positions,
            "bytes": float(per_pos * positions + per_call * calls) * lm}
