"""Median ``serve.tick.d2h`` of the decode and verify ticks: the copy of a
tick's logits to the host, which starts once the program has finished
(``serve.program`` waits for them).  A prefill fetches one row and is left
out, as it is of ``programs.decode_device_p50_s``."""
from benchmark import spanlog


def read(ctx):
    spans = spanlog.window_spans(ctx)
    return spanlog.median_s(spanlog.decodes(spans.get("serve.tick.d2h", [])))
