"""Median ``serve.tick.d2h``: the logits' copy to the host, which starts
once the program has finished (``serve.program`` waits for them)."""
from benchmark import spanlog


def read(ctx):
    spans = spanlog.window_spans(ctx)
    return spanlog.median_s(spans.get("serve.tick.d2h", []))
