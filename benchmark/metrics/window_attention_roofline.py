"""The decode-attention kernel's share of its roofline over the traced
slice in a cell with a window layer group: the least time the chip could
take for the KV bytes and FLOPs of the context on the full-attention
layers and of ``min(context, window)`` on the window layers
(``rooflines/moe.py`` ``decode_attention_needs``, from ``attended_tokens``
and ``window_tokens`` of the slice's decode ``serve.program`` spans) over
the time of the events named ``tdx_paged_attention_decode`` in the trace."""
from benchmark import moe_trace
from benchmark.rooflines import moe


def read(ctx):
    secs = moe_trace.named_seconds(ctx, "tdx_paged_attention_decode")
    if not secs or not ctx.get("peaks"):
        return None
    context, window = moe_trace.slice_args(
        ctx, "attended_tokens", "window_tokens", decode_only=True)
    if not window:
        return None
    least, _bound = moe.least_seconds(
        moe.decode_attention_needs(ctx["c"], context, window), ctx["peaks"])
    return 100.0 * least / secs
