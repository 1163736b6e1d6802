"""The flash-attention kernels' share of their roofline over the traced
slice: the least time for the FLOPs and bytes that causal attention needs,
forward and backward (``rooflines/flash_attention.py``), over the time of
the Mosaic custom calls in the trace.  In a training cell every Mosaic call
is a flash-attention kernel."""
from benchmark.rooflines import flash_attention


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["kernel_s"] or not ctx.get("peaks"):
        return None
    m, c = ctx["mix"], ctx["c"]
    # three kernels (forward, dq, dkv) per layer per step
    steps = tr["kernel_calls"] / (3.0 * c["n_layers"])
    least, _bound = flash_attention.least_seconds(
        flash_attention.needs(c, m["batch"], m["seq_len"]), ctx["peaks"])
    return 100.0 * steps * least / tr["kernel_s"]
