"""The whole serving window's share of the chip's bf16 peak: the FLOPs the
model needs for every prompt position prefilled and every token handed
over in the window (``rooflines/model.py``), over window x peak."""
from benchmark.rooflines import model


def read(ctx):
    if not ctx.get("peaks"):
        return None
    c, flops = ctx["c"], 0.0
    for r in ctx["requests"]:
        if r["first"] is None or r["first"] > ctx["t_close"]:
            continue
        L, n = len(r["tokens"]), r["n"]
        flops += model.forward_flops(c, L, model.causal_pairs(L), 1)
        flops += model.forward_flops(
            c, n - 1, sum(L + j for j in range(1, n)), n - 1)
    return 100.0 * flops / (ctx["window_s"] * ctx["peaks"]["bf16_flops_per_s"])
