"""Median over the window's engine steps of the time in
``serve.tick.tables`` (capacity check, token / position / page-table build
and their transfer, before each program call), self time: a copy-on-write
program that runs inside it is ``serve.program``'s."""
import bisect

from benchmark import harness, spanlog


def read(ctx):
    spans = spanlog.window_spans(ctx)
    if not spans.get("serve.tick.tables"):
        return None
    steps = spans.get("serve.step", [])
    starts = [e["ts"] for e in steps]
    per_step = [0.0] * len(steps)
    for e in spans["serve.tick.tables"]:
        i = bisect.bisect_right(starts, e["ts"]) - 1
        if i >= 0 and e["ts"] <= steps[i]["ts"] + steps[i]["dur"]:
            per_step[i] += e["args"].get("self_us", e["dur"])
    v = harness.quantile([t for t in per_step if t], 0.5)
    return None if v is None else v / 1e6
