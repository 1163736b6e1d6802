"""What the per-layer readers of the program's own records share: the
span tracer's events cut to the measured window, and the compile log cut
to set-up.  Both come from ``torchdistx_tpu.observe``; a program that has
no such record yet (a parent commit from before the spans) gives None, and
the reader leaves its metric out of the line.

The window is the one the end-to-end metrics were taken in: it opens
where ``ctx["clock"]`` closed set-up and ends with the last step of
``ctx["steps"]``, both on ``time.perf_counter``, which
``observe.spans.from_perf_counter`` puts on the tracer's clock."""

from __future__ import annotations

from benchmark import harness

DECODE = ("decode", "verify-")


def window_spans(ctx):
    """{name: [event]} of the tracer's closed spans that began inside the
    window, in order of start; empty where the program records none."""
    from torchdistx_tpu import observe
    from torchdistx_tpu.observe import spans

    to_us = getattr(spans, "from_perf_counter", None)
    steps = ctx.get("steps")
    if to_us is None or not steps or not isinstance(steps[-1], dict):
        return {}
    clk = ctx["clock"]
    lo, hi = to_us(clk.t0 + clk.setup_s), to_us(steps[-1]["t1"])
    out = {}
    for e in list(observe.tracer().events):
        if e.get("ph") == "X" and lo <= e["ts"] <= hi:
            out.setdefault(e["name"], []).append(e)
    for events in out.values():
        events.sort(key=lambda e: e["ts"])
    return out


def decodes(events):
    """Those of the events whose ``program`` is a decode or verify tick."""
    return [e for e in events
            if str(e["args"].get("program", "")).startswith(DECODE)]


def median_s(events):
    """Median duration in seconds; None for none."""
    v = harness.quantile([e["dur"] for e in events], 0.5)
    return None if v is None else v / 1e6


def setup_seconds(ctx, kinds):
    """Seconds of the compile log's entries of ``kinds`` that ended before
    the window opened; None where the program keeps no such log."""
    from torchdistx_tpu import observe

    log = getattr(observe, "compilelog", None)
    if log is None:
        return None
    clk = ctx["clock"]
    opened = clk.t0 + clk.setup_s
    return sum(s for t, kind, s, _name in log.entries()
               if kind in kinds and t <= opened)
