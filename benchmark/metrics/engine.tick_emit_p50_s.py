"""Median ``serve.tick.emit`` of the decode and verify ticks: argmax or
acceptance per lane, the token callbacks, retirement, ledger and SLO
calls, after the logits have reached the host."""
from benchmark import spanlog


def read(ctx):
    spans = spanlog.window_spans(ctx)
    return spanlog.median_s(spanlog.decodes(spans.get("serve.tick.emit", [])))
