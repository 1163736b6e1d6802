"""The longest host wait of a decode or verify tick over the median one,
in the window: a tick's ``serve.program.wait`` spans summed (the afmoe
family's pair counts and, while traced, the tokens).  A steady run reads
1-2; a wait for the device that comes back a tenth of a second late
(PERF.md section 7.17) reads several times that."""
from benchmark import harness, spanlog, spanslice


def read(ctx):
    spans = spanslice.window(ctx)
    if not spans or not spans.get("serve.program.wait"):
        return None
    ticks = spanslice.nested(spanlog.decodes(spans.get("serve.program", [])),
                             spans["serve.program.wait"])
    waits = [sum(e["dur"] for e in kids) for _, kids in ticks if kids]
    mid = harness.quantile(waits, 0.5)
    return max(waits) / mid if mid else None
