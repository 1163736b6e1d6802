"""The expert products' share of their roofline over the traced slice:
the least time the chip could take for the matrices of the held experts
that got a pair and the pairs' FLOPs and rows (``rooflines/moe.py``
``experts_needs``, from ``routed_pairs`` and ``experts_hit`` of the
slice's ``serve.program`` spans) over the time of the grouped-product
events in the trace (``benchmark/moe_trace.py``)."""
from benchmark import moe_trace
from benchmark.rooflines import moe


def read(ctx):
    secs = moe_trace.experts_seconds(ctx)
    if not secs or not ctx.get("peaks"):
        return None
    pairs, hit = moe_trace.slice_args(ctx, "routed_pairs", "experts_hit")
    if not pairs:
        return None
    least, _bound = moe.least_seconds(
        moe.experts_needs(ctx["c"], pairs, hit), ctx["peaks"])
    return 100.0 * least / secs
