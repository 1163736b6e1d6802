"""The latent decode kernel's share of its roofline over the traced
slice: the least time the chip could take for the rows and FLOPs that
the slice's decode ticks need (``rooflines/mla.py`` ``decode_needs``,
from ``mla_attended`` of the ``serve.program`` spans and the live lanes of
the steps) over the time of the ``tdx_mla_decode`` kernel's events
(``benchmark/moe_trace.py`` ``named_seconds``).  A trace without them (a
parent commit, another cell) gives None."""
from benchmark import moe_trace
from benchmark.rooflines import mla


def read(ctx):
    secs = moe_trace.named_seconds(ctx, "tdx_mla_decode")
    if not secs or not ctx.get("peaks") or "kv_rank" not in ctx["c"]:
        return None
    (rows,) = moe_trace.slice_args(ctx, "mla_attended")
    lanes = sum(s.get("decode_lanes", 0) for s in ctx.get("traced_steps", []))
    if not rows:
        return None
    least, _bound = mla.least_seconds(
        mla.decode_needs(ctx["c"], rows, lanes * ctx["c"]["n_layers"]),
        ctx["peaks"])
    return 100.0 * least / secs
