"""The delta rule's chunk kernel's share of its roofline over the traced
slice: the least time the chip could take for the rule over the REAL
prompt positions that the slice's prefill and chunk calls computed
(``rooflines/gdn.py`` ``chunk_needs``; padding to a bucket is no needed
work) over the time of the ``tdx_gdn_chunk`` kernel's events
(``benchmark/moe_trace.py`` ``named_seconds``).  A trace without them
gives None."""
from benchmark import moe_trace
from benchmark.rooflines import gdn


def read(ctx):
    secs = moe_trace.named_seconds(ctx, "tdx_gdn_chunk")
    if not secs or not ctx.get("peaks") or "lin_heads" not in ctx["c"]:
        return None
    steps = ctx.get("traced_steps", [])
    positions = sum(s.get("prefill_tokens", 0) for s in steps)
    calls = sum(n for s in steps for k, n in s["calls"].items()
                if k.startswith(("prefill-", "chunk-")))
    if not positions:
        return None
    least, _bound = gdn.least_seconds(
        gdn.chunk_needs(ctx["c"], positions, calls), ctx["peaks"])
    return 100.0 * least / secs
