"""What the flash-attention kernel (``ops/flash_attention.py``) has to do in
one training step, from shapes: FLOPs and HBM bytes of causal attention,
forward and backward, over all layers."""

from __future__ import annotations

from benchmark.rooflines.model import least_seconds  # noqa: F401


def needs(c: dict, batch: int, seq: int, bytes_per_el: int = 2) -> dict:
    h, hd, layers = c["n_heads"], c["head_dim"], c["n_layers"]
    pairs = batch * seq * (seq + 1) // 2
    fwd = 4.0 * h * hd * pairs            # QK^T and PV
    bwd = 8.0 * h * hd * pairs            # dV, dP, dQ, dK (S recomputed: not counted)
    qkvo = 4 * batch * seq * h * hd * bytes_per_el
    # forward reads q, k, v and writes o; backward reads q, k, v, o, do and
    # writes dq, dk, dv
    return {"flops": layers * (fwd + bwd),
            "bytes": layers * (qkvo + 2 * qkvo)}
