"""Operations the model NEEDS, from shapes (the configuration's sizes under
the benchmark's names, ``configs.dims``).  Recompute, padding to a bucket
and rejected draft positions are not needed work and are not counted."""

from __future__ import annotations


def matmul_params_per_layer(c: dict) -> int:
    d, h, kv, hd, ff = (c["d_model"], c["n_heads"], c["n_kv_heads"],
                        c["head_dim"], c["d_ff"])
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = (3 if c["gated_mlp"] else 2) * d * ff
    return attn + mlp


def matmul_params(c: dict, with_head: bool = True) -> int:
    """Parameters that take part in a matmul for every position: the
    blocks, and the output head (tied or not); embedding lookups do not."""
    n = c["n_layers"] * matmul_params_per_layer(c)
    return n + (c["d_model"] * c["vocab_size"] if with_head else 0)


def attention_flops(c: dict, context_sum: int) -> float:
    """Forward FLOPs of attention itself over all layers: QK^T and PV are
    2 * head_dim * n_heads each per (query, attended key) pair;
    ``context_sum`` is the number of such pairs."""
    return 4.0 * c["n_heads"] * c["head_dim"] * context_sum * c["n_layers"]


def causal_pairs(length: int) -> int:
    """(query, key) pairs of causal attention over one sequence."""
    return length * (length + 1) // 2


def forward_flops(c: dict, positions: int, context_sum: int,
                  head_positions: int | None = None) -> float:
    """Forward FLOPs for ``positions`` token positions that attend
    ``context_sum`` keys in all; the head only where logits are needed."""
    hp = positions if head_positions is None else head_positions
    return (2.0 * matmul_params(c, with_head=False) * positions
            + 2.0 * c["d_model"] * c["vocab_size"] * hp
            + attention_flops(c, context_sum))


def train_step_flops(c: dict, batch: int, seq: int) -> float:
    """Forward and backward of one step: three times the forward (the
    backward needs two matmuls for each of the forward's), recompute not
    counted."""
    return 3.0 * forward_flops(c, batch * seq, batch * causal_pairs(seq))


def least_seconds(need: dict, peaks: dict) -> tuple:
    """The least time the chip could take for ``need`` (``flops``,
    ``bytes``), and which of its two peaks sets it."""
    fl = need["flops"] / peaks["bf16_flops_per_s"]
    by = need["bytes"] / peaks["hbm_bytes_per_s"]
    return (fl, "compute") if fl >= by else (by, "memory")
