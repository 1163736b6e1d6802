"""The readers of a tick's named moments (``benchmark/spanslice.py`` and
the metrics that read ``serve.program.launch`` / ``.wait``,
``serve.admit.deadlines``, ``serve.step``'s own time and ``positions``):
each on hand-made tracer content with its known value, cut to the window
or to the traced slice; None with nothing to read, on a program whose
spans have no such children, and where the tracer lost events of the
window; and the manifest sound with their entries."""

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench_util import roots  # noqa: E402
from benchmark import harness, manifest, spanslice  # noqa: E402
from torchdistx_tpu import observe  # noqa: E402
from torchdistx_tpu.observe import spans  # noqa: E402

FOUR = ["mistral7b-chat-backlog", "jamba2-3b-chat-backlog",
        "trinity-large-mixed-queue", "olmo-hybrid-7b-d8-chat-backlog"]
PREFILL_CELLS = FOUR[1:]  # those of programs.prefill_s_per_ktok but the busy one
NEW = {
    # name: (value on the window below, unit, source, layer, cells)
    "programs.launch_p50_s": (
        0.0016, "s", "program_span", "program step", FOUR),
    "programs.decode_wait_max_over_p50": (
        0.300 / 0.070, "count", "program_span", "program step", FOUR),
    "programs.prefill_call_s_per_ktok": (
        1000.0 * (0.021 + 0.041) / 300, "s", "program_span", "program step",
        PREFILL_CELLS),
    "engine.tick_unnamed_p50_s": (
        0.0003, "s", "program_span", "admission / scheduler", FOUR),
    "engine.admit_deadlines_p50_s": (
        0.0007, "s", "program_span", "admission / scheduler", FOUR),
}
# The traced slice opens 0.040 s into the second step's decode call and
# closes with the third step; the device was busy 0.40 s of its 0.5 s.
SLICE_BUSY_S, SLICE_S = 0.40, 0.5
IN_PROGRAM_S = (0.083 - 0.040) + 0.021 + 0.041 + 0.3016
NEW["device.idle_in_program_share"] = (
    100.0 * (IN_PROGRAM_S - SLICE_BUSY_S) / SLICE_S, "%", "device_trace",
    "device", FOUR)


def _reader(name):
    return harness.load_module(ROOT, f"benchmark/metrics/{name}.py")


def _known(name):
    """The known value; the device share cuts spans at the slice's edges,
    timestamps of about 1.8e15 microseconds held to a quarter of one, so
    it is compared to a thousandth of a point (5 us of the 0.5 s slice)."""
    if name == "device.idle_in_program_share":
        return pytest.approx(NEW[name][0], abs=1e-3)
    return pytest.approx(NEW[name][0])


def _span(name, t, dur_s, self_s=None, **args):
    """A closed span as the tracer records it, ``t`` on perf_counter."""
    return {"name": name, "cat": "serve", "ph": "X",
            "ts": spans.from_perf_counter(t), "dur": dur_s * 1e6,
            "pid": 1, "tid": 1,
            "args": {"self_us": (dur_s if self_s is None else self_s) * 1e6,
                     **args}}


def _step(t, calls, self_s, deadlines_s):
    """A ``serve.step`` at ``t``: admission with the deadline sweep in it,
    then for each call ``(program, positions, [launch s], [wait s])`` its
    tables, its ``serve.program`` tiled by the launches and the waits, and
    its fetch; then the emit and the gauges.  Returns (events, end)."""
    ev, c = [], t + 0.0001
    ev += [_span("serve.admit", c, deadlines_s + 0.0001, 0.0001),
           _span("serve.admit.deadlines", c + 0.00005, deadlines_s,
                 scanned=9)]
    c += deadlines_s + 0.0002
    for program, positions, launches, waits in calls:
        ev.append(_span("serve.tick.tables", c, 0.001, program=program))
        c += 0.001
        dur = sum(launches) + sum(waits)
        ev.append(_span("serve.program", c, dur, 0.0, program=program,
                        lanes=4, attended_tokens=40, kv_blocks=4,
                        positions=positions))
        k = c
        for i, s in enumerate(launches):
            ev.append(_span("serve.program.launch", k, s, program=program,
                            call="greedy" if i else "program"))
            k += s
        for s in waits:
            ev.append(_span("serve.program.wait", k, s, program=program))
            k += s
        c += dur
        ev.append(_span("serve.tick.d2h", c, 0.0001, program=program,
                        bytes=16))
        c += 0.0001
    ev += [_span("serve.tick.emit", c, 0.001, program="decode", tokens=4),
           _span("serve.gauges", c + 0.001, 0.0001)]
    c += 0.0011 + self_s
    ev.insert(0, _span("serve.step", t, c - t, self_s, step=1))
    return ev, c


def _window(t):
    """Four steps inside a window opened at ``t``, and a step with large
    values wholly before it and one after it; returns (events, the four
    steps' t0 / t1)."""
    big = [("decode", 4, [0.5, 0.5], [5.0])]
    plan = [
        (t - 9.0, big, 0.5, 0.5),
        (t + 0.1, [("decode", 4, [0.001, 0.0005], [0.060])], 0.0002, 0.0005),
        (t + 0.3, [("decode", 4, [0.002, 0.001], [0.080])], 0.0004, 0.0015),
        (t + 0.5, [("prefill-128", 100, [0.001], [0.020]),
                   ("chunk-256", 200, [0.001], [0.040]),
                   ("decode", 4, [0.0011, 0.0005], [0.300])], 0.005, 0.001),
        (t + 0.9, [("decode", 4, [0.0012, 0.0006], [0.070])], 0.0003, 0.0007),
        (t + 2.0, big, 0.5, 0.5),
    ]
    events, steps = [], []
    for t0, calls, self_s, dl in plan:
        ev, t1 = _step(t0, calls, self_s, dl)
        events += ev
        steps.append({"t0": t0, "t1": t1})
    return events, steps[1:-1]


@pytest.fixture()
def ctx():
    observe.reset()
    t = time.perf_counter()
    events, steps = _window(t)
    observe.tracer().events.extend(events)
    clk = harness.Clock(t - 10.0)
    clk.setup_s = 10.0
    # The slice: from 0.040 s into step 2's decode call to step 3's end.
    call2 = [e for e in events if e["name"] == "serve.program"][2]
    lo = t + (call2["ts"] - spans.from_perf_counter(t)) / 1e6 + 0.040
    traced = [{"t0": lo, "t1": steps[2]["t1"]}]
    yield {"clock": clk, "steps": steps, "traced_steps": traced,
           "trace": {"busy_s": SLICE_BUSY_S, "window_s": SLICE_S}}
    observe.reset()


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_reads_the_window_only(ctx, name):
    assert _reader(name).read(ctx) == _known(name)


def _parent_shaped(t):
    """One decode step as a program without the named moments records
    it: ``serve.program`` with no children and no ``positions``."""
    return [_span("serve.step", t, 0.1, 0.001, step=1),
            _span("serve.admit", t + 0.001, 0.001),
            _span("serve.tick.tables", t + 0.002, 0.002, program="decode"),
            _span("serve.program", t + 0.005, 0.07, program="decode",
                  lanes=2, attended_tokens=10, kv_blocks=2),
            _span("serve.program", t + 0.08, 0.01, program="prefill-128",
                  lanes=1, attended_tokens=100, kv_blocks=0),
            _span("serve.tick.d2h", t + 0.09, 0.001, program="decode",
                  bytes=8),
            _span("serve.tick.emit", t + 0.092, 0.003, program="decode",
                  tokens=2)]


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_with_nothing_to_read_gives_none(ctx, name):
    observe.reset()
    assert _reader(name).read(ctx) is None  # no span recorded (tracing off)
    observe.tracer().events.extend(_parent_shaped(ctx["steps"][0]["t0"]))
    assert _reader(name).read(ctx) is None  # a program without the moments
    assert _reader(name).read(dict(ctx, steps=[(0.0, 1.0, 2.5)])) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_refuses_a_window_the_tracer_lost_events_of(ctx, name):
    tracer = observe.tracer()
    opened = spans.from_perf_counter(ctx["clock"].t0 + ctx["clock"].setup_s)
    kept = [e for e in tracer.events if e["ts"] >= opened]
    # What was dropped ended before the window opened: it is all there.
    tracer.dropped = 6
    assert _reader(name).read(ctx) == _known(name)
    # The oldest event kept began inside the window: some of it is gone.
    tracer.events.clear()
    tracer.events.extend(kept[1:])
    assert _reader(name).read(ctx) is None


def test_kept_since_follows_the_order_of_recording():
    observe.reset()
    tracer = observe.tracer()
    t = time.perf_counter()
    assert spanslice.kept_since(spans.from_perf_counter(t))  # none dropped
    tracer.dropped = 1
    assert not spanslice.kept_since(spans.from_perf_counter(t))  # no event
    # A long span recorded first, when it closed, after the window opened.
    tracer.events.append(_span("serve.step", t - 1.0, 2.0))
    assert not spanslice.kept_since(spans.from_perf_counter(t))
    assert spanslice.kept_since(spans.from_perf_counter(t + 1.5))
    observe.reset()


def test_the_device_reader_needs_the_traced_slice(ctx):
    read = _reader("device.idle_in_program_share").read
    assert read(dict(ctx, trace=None)) is None  # an untraced run
    assert read(dict(ctx, traced_steps=[])) is None
    assert read(dict(ctx, trace={"busy_s": 0.0, "window_s": 0.0})) is None
    # The share lies under the idle share itself: calls inside the slice
    # take no more than the slice.
    assert 0.0 <= read(ctx) <= 100.0 * (1 - SLICE_BUSY_S / SLICE_S)


def test_unnamed_time_is_read_over_decode_only_steps(ctx):
    got = spanslice.decode_only_steps(spanslice.window(ctx))
    assert [round(e["args"]["self_us"]) for e in got] == [200, 400, 300]


@pytest.mark.parametrize("grown", [False, True],
                         ids=["as-committed", "with-a-later-cell"])
def test_manifest_is_sound_with_the_new_entries(tmp_path, grown):
    root = roots(tmp_path, grown)
    assert manifest.check(root) == []
    m = harness.load_manifest(root)
    by = {p["name"]: p for p in m["per_layer"]}
    for name, (_, unit, source, layer, cells) in NEW.items():
        p = by[name]
        assert (p["unit"], p["source"], p["layer"], p["moves"], p["better"]) \
            == (unit, source, layer, "tpot_p50_s", "lower")
        assert p["workloads"] == cells
        assert os.path.exists(
            os.path.join(root, "benchmark", "metrics", f"{name}.py"))
    # Appended after every entry that was there before them.
    names = [p["name"] for p in m["per_layer"]]
    assert names.index("gdn.device_share") < min(names.index(n) for n in NEW)
