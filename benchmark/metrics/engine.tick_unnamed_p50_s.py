"""Median over the window's steps that ran a decode tick and no other
program of ``serve.step``'s self time: what of the step no child span
names.  Read only where the program call is split into launch and wait
(``serve.program.launch``): before that the greedy choice's launch and
the gauges lay outside every child."""
from benchmark import harness, spanslice


def read(ctx):
    spans = spanslice.window(ctx)
    if not spans or not spans.get("serve.program.launch"):
        return None
    steps = spanslice.decode_only_steps(spans)
    v = harness.quantile([e["args"]["self_us"] for e in steps], 0.5)
    return None if v is None else v / 1e6
