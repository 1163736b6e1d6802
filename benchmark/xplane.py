"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
idle gaps, time per operation and kernel time.  Reads the file with
``jax.profiler.ProfileData`` and nothing else.

What a TPU trace holds (looked at by hand, PR 25): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per executed HLO
operation, named by the operation's HLO text (``%name = type opcode(...)``)
with start and duration in nanoseconds; a ``while`` (a scan over layers)
is one event that encloses its body's events, so time per operation is
SELF time.  The plane ``/host:CPU`` holds the host threads; a
``jax.profiler.TraceAnnotation`` shows there under its own name with its
keyword arguments as stats.  The device's clock runs about a millisecond
ahead of the host's in the same file, so a gap shorter than that may be
attributed to the neighbouring step.
"""

from __future__ import annotations

import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
_HLO = re.compile(r"^%(?P<name>[^\s=]+)\s*=\s*(?P<type>.*?)\s(?P<op>[a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")
WINDOW = "bench.window"
STEP = "bench.step"


def union(intervals):
    """Merged, sorted list of (start, end) from possibly nested or
    overlapping ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def self_times(events):
    """[(key, start, end)] -> {key: seconds of self time}: an event's
    duration less what the events nested inside it cover."""
    total = defaultdict(float)
    stack = []  # (key, end)
    for key, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            total[stack[-1][0]] -= (min(e, stack[-1][1]) - s)
        total[key] += e - s
        stack.append((key, e))
    return {k: v / 1e9 for k, v in total.items()}


def op_key(hlo: str) -> str:
    """A short stable-ish name for an HLO operation: name, opcode, the
    custom call's target and the result's first shape."""
    m = _HLO.match(hlo)
    if not m:
        return hlo[:60]
    key = f"{m['name']}_{m['op']}"
    if m["op"] == "custom-call":
        t = re.search(r'custom_call_target="([^"]+)"', hlo)
        if t:
            key += ":" + t[1]
    sh = _SHAPE.search(m["type"])
    if sh:
        key += f"_{sh[1]}_{sh[2].replace(',', '_')}_"
    return key


def is_kernel(hlo: str) -> bool:
    return KERNEL_MARK in hlo


def load(path: str) -> dict:
    """{'devices': {plane: [(hlo, start_ns, end_ns)]},
    'annotations': [(name, start_ns, end_ns, stats)]}"""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices, notes = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in (WINDOW, STEP):
                        notes.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      {k: v for k, v in e.stats}))
    return {"devices": devices, "annotations": notes}


def reduce(raw: dict, step_labels: dict | None = None) -> dict:
    """Busy seconds (averaged over the chips), the window, time per
    operation, kernel time and the idle gaps by what the host was doing.

    The window is the ``bench.window`` annotation where there is one,
    else the span of the ``bench.step`` annotations, else that of the
    device's own events.  ``step_labels`` maps a step's ``i`` stat to what
    ran in it (``"decode+prefill-2048"``)."""
    step_labels = step_labels or {}
    notes = raw["annotations"]
    win = [(s, e) for n, s, e, _ in notes if n == WINDOW]
    steps = sorted((s, e, st) for n, s, e, st in notes if n == STEP)
    every = [(s, e) for evs in raw["devices"].values() for _, s, e in evs]
    if win:
        lo, hi = min(s for s, _ in win), max(e for _, e in win)
    elif steps:
        lo, hi = steps[0][0], max(e for _, e, _ in steps)
    elif every:
        lo, hi = min(s for s, _ in every), max(e for _, e in every)
    else:
        return {"busy_s": 0.0, "window_s": 0.0, "ops": {}, "kernel_s": 0.0,
                "kernel_calls": 0, "gaps": {}, "n_devices": 0}
    busy, ops, gaps = [], defaultdict(float), defaultdict(float)
    kernel_s, kernel_calls = 0.0, 0
    for evs in raw["devices"].values():
        inside = [(h, max(s, lo), min(e, hi)) for h, s, e in evs
                  if min(e, hi) > max(s, lo)]
        merged = union([(s, e) for _, s, e in inside])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for k, v in self_times([(op_key(h), s, e) for h, s, e in inside]).items():
            ops[k] += v
        for h, s, e in inside:
            if is_kernel(h):
                kernel_s += (e - s) / 1e9
                kernel_calls += 1
        edges = [lo] + [x for se in merged for x in se] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps[_blame(a, b, steps, merged, step_labels)] += (b - a) / 1e9
    n = max(len(busy), 1)
    return {
        "busy_s": sum(busy) / n, "window_s": (hi - lo) / 1e9,
        "ops": {k: v / n for k, v in ops.items()},
        "kernel_s": kernel_s / n, "kernel_calls": kernel_calls,
        "gaps": {k: v / n for k, v in gaps.items()}, "n_devices": len(busy),
    }


def _blame(a, b, steps, merged, labels) -> str:
    """Name an idle gap [a, b] by the host step that encloses its middle
    and by where in that step's device work it falls."""
    mid = (a + b) / 2
    for s, e, st in steps:
        if s <= mid <= e:
            label = labels.get(str(st.get("i")), labels.get(st.get("i"), "step"))
            inside = [(x, y) for x, y in merged if y > s and x < e]
            if not inside or b <= inside[0][0]:
                where = "host_before_first_op"
            elif a >= inside[-1][1]:
                where = "host_after_last_op"
            else:
                where = "host_between_two_ops"
            return f"step_{label}:{where}"
    return "between_steps:benchmark_loop"


def top(d: dict, n: int = 10):
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
