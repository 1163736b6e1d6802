"""What a chip's share of an ``afmoe`` stack has to do, whatever
implements it, from shapes (``families/afmoe.py`` ``dims``): the expert
products from the (token, choice) pairs that landed on held experts and
the experts that got any, decode attention from the context on the
full-attention layers and ``min(context, window)`` on the window layers,
and the whole forward pass for ``serve.mfu_hybrid``.  Recompute, padding
to a bucket and pairs routed to experts held elsewhere are not this
share's work and are not counted."""

from __future__ import annotations

from benchmark.rooflines.model import least_seconds  # noqa: F401


def expert_params(c: dict) -> int:
    """One expert's three matrices (the shared expert's alike)."""
    return 3 * c["d_model"] * c["d_expert"]


def experts_needs(c: dict, pairs: int, experts_hit: int,
                  bytes_per_el: int = 2) -> dict:
    """The three grouped products of the expert layers: ``pairs`` (token,
    choice) pairs that landed on a held expert and ``experts_hit`` held
    experts that got at least one, both summed over calls and expert
    layers.  A hit expert's matrices are read once a call; a pair reads
    its input row and writes its output row (``d_model`` each) and writes
    and reads the gated activation between the products (``d_expert``)."""
    d, de = c["d_model"], c["d_expert"]
    return {"flops": 2.0 * expert_params(c) * pairs,
            "bytes": float(bytes_per_el) * (
                expert_params(c) * experts_hit + (2 * d + 2 * de) * pairs)}


def decode_attention_needs(c: dict, context_tokens: int, window_tokens: int,
                           bytes_per_el: int = 2) -> dict:
    """Decode attention over both cache groups: ``context_tokens`` is the
    sum over decode ticks and lanes of the context length (what a
    full-attention layer reads), ``window_tokens`` that of ``min(context,
    window)`` (what a window layer reads)."""
    kv, h, hd = c["n_kv_heads"], c["n_heads"], c["head_dim"]
    tokens = (context_tokens * c["n_full_layers"]
              + window_tokens * c["n_window_layers"])
    return {"flops": 4.0 * h * hd * tokens,
            "bytes": 2.0 * kv * hd * bytes_per_el * tokens}


def matmul_params(c: dict) -> float:
    """Parameters that take part in a product for every position of this
    share: attention's five projections, the dense MLPs, and in an expert
    layer the router, the shared expert and the EXPECTED share of the
    ``top_k`` routed experts that is held here (uniform routing:
    ``held / router_outputs`` of them), and the head's slice."""
    d, h, kv, hd = c["d_model"], c["n_heads"], c["n_kv_heads"], c["head_dim"]
    attn = 2 * d * h * hd + 2 * d * kv * hd + h * hd * d
    held = c["top_k"] * c["held_experts"] / c["router_outputs"]
    expert_layer = d * c["router_outputs"] + (1 + held) * expert_params(c)
    return (c["n_layers"] * attn + c["n_dense_layers"] * 3 * d * c["d_ff"]
            + c["n_expert_layers"] * expert_layer)


def windowed(c: dict, context: int) -> int:
    return min(context, c["window"])


def prefill_pairs(c: dict, length: int) -> tuple:
    """(query, key) pairs of one prompt: (full layer, window layer)."""
    w = c["window"]
    full = length * (length + 1) // 2
    if length <= w:
        return full, full
    return full, w * (w + 1) // 2 + (length - w) * w


def decode_pairs(c: dict, prompt: int, n: int) -> tuple:
    """Pairs of ``n`` decoded tokens behind a prompt of ``prompt``."""
    full = sum(prompt + j for j in range(1, n + 1))
    return full, sum(windowed(c, prompt + j) for j in range(1, n + 1))


def forward_flops(c: dict, positions: int, pairs_full: int,
                  pairs_window: int, head_positions: int) -> float:
    """Forward FLOPs of ``positions`` token positions of this share that
    attend ``pairs_full`` keys in a full layer and ``pairs_window`` in a
    window layer; the head at ``head_positions`` of them, where logits
    are needed (every decoded token; one position of a prompt)."""
    return (2.0 * matmul_params(c) * positions
            + 2.0 * c["d_model"] * c["vocab_size"] * head_positions
            + 4.0 * c["n_heads"] * c["head_dim"] * (
                pairs_full * c["n_full_layers"]
                + pairs_window * c["n_window_layers"]))
