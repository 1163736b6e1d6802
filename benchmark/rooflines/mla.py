"""What the two latent-attention kernels of a ``pangu_ultra_moe`` share
NEED, from shapes (``families/pangu_ultra_moe.py`` ``dims``), whatever
form a program computes them in.  The count is of the published
attention's work: a row is the published ``kv_rank + rope_dim`` values
(576, 1,152 B in bfloat16), not the lanes' padding a program may store
beside it, and prefill is counted in the expanded form.  A program that
did prefill in the absorbed form would read a lower share, as it should.

* decode (``tdx_mla_decode``): for each latent row a lane attends in a
  layer, the absorbed scores over the whole row and the values over its
  latent, ``2 H (kv_rank + rope_dim + kv_rank)`` operations; bytes are
  the rows once, and each lane's query in (``H (kv_rank + rope_dim)``)
  and its latent output out (``H kv_rank``) a layer;
* prefill and chunk (``tdx_mla_prefill``): for each causal (query, key)
  pair in a layer the expanded form's ``2 H (nope + rope + v)``; bytes
  are the latent rows the call attends, once a layer.
"""

from __future__ import annotations

from benchmark.rooflines.model import least_seconds  # noqa: F401

ACT_BYTES = 2  # the latent cache and the kernels' operands are bfloat16


def row(c: dict) -> int:
    return c["kv_rank"] + c["rope_dim"]


def decode_needs(c: dict, rows: int, lane_layers: int) -> dict:
    """``rows``: latent rows attended, summed over lanes, ticks and layers;
    ``lane_layers``: live lanes summed over ticks, times the layers."""
    h, r = c["n_heads"], c["kv_rank"]
    return {"flops": 2.0 * h * (row(c) + r) * rows,
            "bytes": float(ACT_BYTES) * (row(c) * rows
                                         + h * (row(c) + r) * lane_layers)}


def prefill_needs(c: dict, pairs: int, rows: int) -> dict:
    """``pairs``: causal (query, key) pairs summed over calls and layers;
    ``rows``: the latent rows the calls attend, summed over layers."""
    qk = c["nope_dim"] + c["rope_dim"]
    return {"flops": 2.0 * c["n_heads"] * (qk + c["v_dim"]) * pairs,
            "bytes": float(ACT_BYTES) * row(c) * rows}
