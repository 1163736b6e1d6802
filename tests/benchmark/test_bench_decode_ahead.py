"""The reader of ``engine.decode_ahead_share``: on hand-made tracer content
its known value, cut to the window; 0 for a program that reads every tick
before it dispatches the next; None for a program without the counter
``tdx.serve.decode_ticks_ahead``, with nothing to read, and where the
tracer lost events of the window; and the manifest sound with its entry."""

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench_util import roots  # noqa: E402
from benchmark import harness, manifest  # noqa: E402
from torchdistx_tpu import observe  # noqa: E402
from torchdistx_tpu.observe import spans  # noqa: E402

NAME = "engine.decode_ahead_share"
CELLS = ["jamba2-3b-chat-backlog", "olmo-hybrid-7b-d8-chat-backlog",
         "trinity-large-mixed-queue", "mistral7b-chat-backlog"]


def _reader():
    return harness.load_module(ROOT, f"benchmark/metrics/{NAME}.py")


def _program(t, program, ahead=None):
    args = {"self_us": 100.0, "program": program, "lanes": 4,
            "attended_tokens": 40, "kv_blocks": 4, "positions": 4}
    if ahead is not None:
        args["ahead"] = ahead
    return {"name": "serve.program", "cat": "serve", "ph": "X",
            "ts": spans.from_perf_counter(t), "dur": 1000.0, "pid": 1,
            "tid": 1, "args": args}


def _ctx(t, calls):
    """A window opened at ``t`` whose steps each hold one call of
    ``calls`` ((program, ahead) a call), with a decode tick dispatched
    ahead before it and one after it."""
    observe.reset()
    observe.counter("tdx.serve.decode_ticks_ahead")  # the program counts
    events = [_program(t - 5.0, "decode", 1)]
    steps = []
    for i, (program, ahead) in enumerate(calls):
        t0 = t + 0.1 * (i + 1)
        events.append(_program(t0 + 0.01, program, ahead))
        steps.append({"t0": t0, "t1": t0 + 0.05})
    events.append(_program(t + 9.0, "decode", 1))
    observe.tracer().events.extend(events)
    clk = harness.Clock(t - 10.0)
    clk.setup_s = 10.0
    return {"clock": clk, "steps": steps}


@pytest.fixture()
def t():
    yield time.perf_counter()
    observe.reset()


def test_reads_the_windows_ticks_dispatched_ahead(t):
    # Three plain ticks, one of them first after a drain; a verify tick;
    # a prefill, which is no tick.
    ctx = _ctx(t, [("decode", 0), ("decode", 1), ("prefill-128", None),
                   ("decode", 1), ("verify-2", None)])
    assert _reader().read(ctx) == pytest.approx(100.0 * 2 / 4)


def test_a_replica_that_reads_each_tick_first_reads_zero(t):
    ctx = _ctx(t, [("decode", None), ("verify-4", None), ("decode", None)])
    assert _reader().read(ctx) == 0.0


def test_nothing_to_read_gives_none(t):
    ctx = _ctx(t, [("decode", 1), ("decode", 1)])
    observe.counters().clear()  # a program without the counter: the parent's
    assert _reader().read(ctx) is None
    ctx = _ctx(t, [("prefill-128", None)])  # no tick in the window
    assert _reader().read(ctx) is None
    observe.tracer().events.clear()  # no span recorded (tracing off)
    assert _reader().read(ctx) is None


def test_a_window_the_tracer_lost_events_of_gives_none(t):
    ctx = _ctx(t, [("decode", 1), ("decode", 1)])
    tracer = observe.tracer()
    opened = spans.from_perf_counter(ctx["clock"].t0 + ctx["clock"].setup_s)
    kept = [e for e in tracer.events if e["ts"] >= opened]
    tracer.dropped = 3
    tracer.events.clear()
    tracer.events.extend(kept[1:])
    assert _reader().read(ctx) is None


@pytest.mark.parametrize("grown", [False, True],
                         ids=["as-committed", "with-a-later-cell"])
def test_manifest_is_sound_with_the_entry(tmp_path, grown):
    root = roots(tmp_path, grown)
    assert manifest.check(root) == []
    m = harness.load_manifest(root)
    p = {e["name"]: e for e in m["per_layer"]}[NAME]
    assert (p["unit"], p["source"], p["layer"], p["moves"], p["better"]) == (
        "%", "program_span", "admission / scheduler", "tpot_p50_s", "higher")
    assert p["workloads"] == CELLS
    # Appended after every entry that was there before it.
    names = [e["name"] for e in m["per_layer"]]
    assert names.index("programs.decode_wait_max_over_p50") < names.index(NAME)
    assert os.path.exists(os.path.join(root, "benchmark", "metrics",
                                       f"{NAME}.py"))
