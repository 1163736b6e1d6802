"""Seconds per 1,000 real prompt positions, timed where the work is done:
the ``serve.program`` spans of the ``prefill-<b>`` and ``chunk-<b>`` calls
in the window summed, over the ``positions`` they advanced (the prompt's
tokens, not the bucket's padded size).  While traced every call waits for
its result, so a span is the call's launch and device time."""
from benchmark import spanslice

PREFILL = ("prefill-", "chunk-")


def read(ctx):
    spans = spanslice.window(ctx)
    if not spans:
        return None
    calls = [e for e in spans.get("serve.program", [])
             if str(e["args"].get("program", "")).startswith(PREFILL)
             and "positions" in e["args"]]
    positions = sum(e["args"]["positions"] for e in calls)
    if not positions:
        return None
    return 1000.0 * sum(e["dur"] for e in calls) / 1e6 / positions
