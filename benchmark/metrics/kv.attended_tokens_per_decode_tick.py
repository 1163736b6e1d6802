"""Mean context a decode or verify tick attends over, summed over its
lanes (``attended_tokens`` of ``serve.program``, counted by the engine
before anything retires in the tick): the KV bytes a tick has to read."""
from benchmark import spanlog


def read(ctx):
    ticks = spanlog.decodes(
        spanlog.window_spans(ctx).get("serve.program", []))
    if not ticks:
        return None
    return sum(e["args"]["attended_tokens"] for e in ticks) / len(ticks)
