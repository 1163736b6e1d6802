"""The decode-attention kernel's share of its roofline over the traced
slice: the least time the chip could take for the KV bytes and FLOPs that
the attended lengths need (``rooflines/paged_attention.py``) over the time
of the Mosaic custom calls in the trace.  In a serving cell every Mosaic
call is this kernel (prefill and verify attend through XLA ops)."""
from benchmark.rooflines import paged_attention


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["kernel_s"] or not ctx.get("peaks"):
        return None
    attended = sum(s["ctx_tokens"] for s in ctx.get("traced_steps", [])
                   if "decode" in s["calls"])
    if not attended:
        return None
    least, _bound = paged_attention.least_seconds(
        paged_attention.needs(ctx["c"], attended), ctx["peaks"])
    return 100.0 * least / tr["kernel_s"]
