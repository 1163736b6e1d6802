"""Pages that live sequences returned to the window group's free list
because they fell behind the window
(``tdx.serve.window_pages_released``, from the process's start); read only
where the program serves a window group (``window_tokens`` on its
``serve.program`` spans)."""
from benchmark import spanlog


def read(ctx):
    from torchdistx_tpu import observe

    if not any("window_tokens" in e["args"] for e in
               spanlog.window_spans(ctx).get("serve.program", [])):
        return None
    return observe.counter("tdx.serve.window_pages_released").value
