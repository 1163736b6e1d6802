"""The whole serving window's share of the chip's bf16 peak for a stack
with recurrent layers: the FLOPs the model needs for every prompt position
prefilled and every token handed over in the window, as the cell's family
module counts them (``rooflines/ssm.py``: matmuls by layer kind,
attention's products in the attention layers only, the conv and the
recurrence in the Mamba layers), over window x peak."""


def read(ctx):
    fam = ctx.get("family")
    if not ctx.get("peaks") or fam is None:
        return None
    flops = fam.served_flops(ctx["c"], ctx["requests"], ctx["t_close"])
    return 100.0 * flops / (ctx["window_s"] * ctx["peaks"]["bf16_flops_per_s"])
