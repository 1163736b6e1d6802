"""Median over the window's decode and verify ticks of the seconds the
host spent dispatching the tick's calls: the ``serve.program.launch``
spans inside the tick's ``serve.program`` summed (the program, and a plain
tick's greedy choice).  Launch is host time inside a program call in
which the chip has nothing of this tick to run yet."""
from benchmark import harness, spanlog, spanslice


def read(ctx):
    spans = spanslice.window(ctx)
    if not spans or not spans.get("serve.program.launch"):
        return None
    ticks = spanslice.nested(spanlog.decodes(spans.get("serve.program", [])),
                             spans["serve.program.launch"])
    v = harness.quantile([sum(e["dur"] for e in kids)
                          for _, kids in ticks if kids], 0.5)
    return None if v is None else v / 1e6
