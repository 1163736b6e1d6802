"""What the readers of a tick's named moments share (the children of
``serve.program`` and of ``serve.step``): the tracer's spans cut to the
window or to the traced slice, a refusal to read a window whose events the
tracer has lost, and the steps that ran a decode tick and nothing else.

``benchmark/spanlog.py`` cuts the window; this adds what a reader of
nested spans needs besides.  The tracer keeps its last 200,000 events and
evicts the oldest, so a long traced run can lose the start of its window:
a sum or a median over what is left would read as a number of the whole
window, and so the readers give None there.  The traced slice is the
interval ``benchmark/xplane.py`` reduces: each ``bench.step`` annotation
wraps one step of ``ctx["traced_steps"]``, on the same ``perf_counter``
clock as ``t0`` / ``t1``."""

from __future__ import annotations

import bisect

from benchmark import spanlog

# Containment allows for the rounding of two microsecond timestamps.
SLACK_US = 1.0


def _to_us():
    from torchdistx_tpu.observe import spans

    return getattr(spans, "from_perf_counter", None)


def kept_since(t_us: float) -> bool:
    """Whether the tracer still holds every event it recorded from
    ``t_us`` on: it dropped none, or the oldest it kept had ended before
    then (it evicts in the order it recorded, and records a span when the
    span closes)."""
    from torchdistx_tpu import observe

    tracer = observe.tracer()
    if not getattr(tracer, "dropped", 0):
        return True
    events = tracer.events
    if not events:
        return False
    first = events[0]
    return first["ts"] + first.get("dur", 0.0) < t_us


def window(ctx):
    """{name: [event]} of the spans that began inside the window
    (``spanlog.window_spans``); None where there are none, or where the
    tracer has lost events of the window."""
    spans = spanlog.window_spans(ctx)
    if not spans:
        return None
    clk = ctx["clock"]
    if not kept_since(_to_us()(clk.t0 + clk.setup_s)):
        return None
    return spans


def traced_slice(ctx):
    """(start, end) of the traced slice on the tracer's clock, in
    microseconds; None for a run that traced no step."""
    steps = ctx.get("traced_steps")
    to_us = _to_us()
    if not steps or to_us is None:
        return None
    return to_us(steps[0]["t0"]), to_us(steps[-1]["t1"])


def seconds_inside(events, lo_us: float, hi_us: float) -> float:
    """Seconds of the events' durations that lie inside [lo, hi]."""
    return sum(max(0.0, min(e["ts"] + e["dur"], hi_us) - max(e["ts"], lo_us))
               for e in events) / 1e6


def nested(outers, events):
    """[(outer, [the events that lie inside it])] for each outer event;
    ``events`` sorted by start, as ``window`` gives them."""
    starts = [e["ts"] for e in events]
    out = []
    for o in outers:
        end = o["ts"] + o["dur"] + SLACK_US
        i = bisect.bisect_left(starts, o["ts"] - SLACK_US)
        j = bisect.bisect_right(starts, end)
        out.append((o, [e for e in events[i:j]
                        if e["ts"] + e["dur"] <= end]))
    return out


def decode_only_steps(spans):
    """The ``serve.step`` events that called at least one program, every
    one of them a decode or verify tick."""
    steps = []
    for step, calls in nested(spans.get("serve.step", []),
                              spans.get("serve.program", [])):
        names = [str(e["args"].get("program", "")) for e in calls]
        if names and all(n.startswith(spanlog.DECODE) for n in names):
            steps.append(step)
    return steps
