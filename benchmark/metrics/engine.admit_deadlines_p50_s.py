"""Median over the window's steps of ``serve.admit.deadlines``: the sweep
that cancels every lane and waiting request past its deadline, which
walks the whole waiting queue every step (``scanned`` on the span)."""
from benchmark import spanlog, spanslice


def read(ctx):
    spans = spanslice.window(ctx)
    if not spans:
        return None
    return spanlog.median_s(spans.get("serve.admit.deadlines", []))
