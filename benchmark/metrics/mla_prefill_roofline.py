"""The latent prefill kernel's share of its roofline over the traced
slice: the least time the chip could take for the causal pairs of the
slice's prefill and chunk calls in the expanded form and the latent rows
they attend (``rooflines/mla.py`` ``prefill_needs``, from ``mla_pairs``
and ``attended_tokens`` of the ``serve.program`` spans) over the time of
the ``tdx_mla_prefill`` kernel's events (``benchmark/moe_trace.py``
``named_seconds``).  A trace without them gives None."""
from benchmark import moe_trace
from benchmark.rooflines import mla


def read(ctx):
    secs = moe_trace.named_seconds(ctx, "tdx_mla_prefill")
    if not secs or not ctx.get("peaks") or "kv_rank" not in ctx["c"]:
        return None
    pairs, attended = moe_trace.slice_args(ctx, "mla_pairs", "attended_tokens")
    (decoded,) = moe_trace.slice_args(ctx, "attended_tokens", decode_only=True)
    if not pairs:
        return None
    least, _bound = mla.least_seconds(
        mla.prefill_needs(ctx["c"], pairs,
                          (attended - decoded) * ctx["c"]["n_layers"]),
        ctx["peaks"])
    return 100.0 * least / secs
