"""What decode attention over a paged cache has to do, whatever implements
it (``ops/paged_attention.py`` today), from shapes: for every lane of a
decode tick, read the K and V rows of its attended length once and do the
two products against them, in every layer."""

from __future__ import annotations

from benchmark.rooflines.model import least_seconds  # noqa: F401


def needs(c: dict, attended_tokens: int, bytes_per_el: int = 2) -> dict:
    """``attended_tokens``: the sum, over decode ticks and lanes, of the
    context length the lane attended."""
    kv, h, hd, layers = c["n_kv_heads"], c["n_heads"], c["head_dim"], c["n_layers"]
    return {"flops": 4.0 * h * hd * attended_tokens * layers,
            "bytes": 2.0 * kv * hd * bytes_per_el * attended_tokens * layers}
