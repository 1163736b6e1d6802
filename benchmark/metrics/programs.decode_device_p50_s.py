"""Median ``serve.program`` of the decode and verify programs: from the
call of the compiled program to its logits being ready, the device's part
of a decode tick."""
from benchmark import spanlog


def read(ctx):
    spans = spanlog.window_spans(ctx)
    return spanlog.median_s(spanlog.decodes(spans.get("serve.program", [])))
