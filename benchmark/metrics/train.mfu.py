"""The whole training window's share of the chip's bf16 peak: 6 N tokens
plus attention, from shapes (``rooflines/model.py``), recompute not
counted, over window x peak."""
from benchmark.rooflines import model


def read(ctx):
    if not ctx.get("peaks"):
        return None
    m = ctx["mix"]
    flops = len(ctx["steps"]) * model.train_step_flops(ctx["c"], m["batch"], m["seq_len"])
    return 100.0 * flops / (ctx["window_s"] * ctx["peaks"]["bf16_flops_per_s"])
