"""Mean number of lanes that were decoding after a step that ran a decode
or verify program (benchmark's look at the engine after each step)."""


def read(ctx):
    ticks = [s["lanes"] for s in ctx["steps"]
             if any(k == "decode" or k.startswith("verify") for k in s["calls"])]
    return sum(ticks) / len(ticks) if ticks else None
