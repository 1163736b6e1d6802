"""Share of the window's decode and verify ticks that the engine dispatched
while the tick before was still unread, in percent: the ``serve.program``
spans of ``decode`` whose ``ahead`` is 1 over every decode and verify
``serve.program`` (docs/serving.md, decoding ahead).  A replica that reads
each tick before it dispatches the next (a drafter, or a tick that reads
more than its tokens) reads 0.  The program's counter of the same event,
``tdx.serve.decode_ticks_ahead``, runs from the process's start, warm-up
included, so the spans give the window's count; a program without that
counter gives None."""
from benchmark import spanlog, spanslice

COUNTER = "tdx.serve.decode_ticks_ahead"


def read(ctx):
    from torchdistx_tpu import observe

    if not any(r["name"] == COUNTER for r in observe.counters().snapshot()):
        return None
    spans = spanslice.window(ctx)
    ticks = spanlog.decodes(spans.get("serve.program", [])) if spans else []
    if not ticks:
        return None
    ahead = sum(e["args"].get("ahead") == 1 for e in ticks)
    return 100.0 * ahead / len(ticks)
