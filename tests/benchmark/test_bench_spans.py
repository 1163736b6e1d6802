"""The readers of the program's own spans and compile log
(``benchmark/spanlog.py``, ``benchmark/metrics/*``): each on hand-made
tracer content and a hand-made compile log, cut to the window; nothing to
read gives None; and the manifest is sound with their entries."""

import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, manifest, spanlog  # noqa: E402
from torchdistx_tpu import observe  # noqa: E402
from torchdistx_tpu.observe import compilelog, spans  # noqa: E402

SPAN_METRICS = {
    # Two decode ticks and one prefill step inside the window (below).
    "engine.tick_host_share": 100.0 * (300_000 - 230_000) / 300_000,
    "engine.tick_tables_p50_s": 0.002,
    "engine.tick_emit_p50_s": 0.003,
    "programs.logits_d2h_p50_s": 0.001,
    "programs.decode_device_p50_s": 0.070,  # nearest rank of two
    "kv.attended_tokens_per_decode_tick": 1100.0,
}
LOG_METRICS = {"bringup.compile_s": 7.0, "bringup.lower_s": 1.5}


def _reader(name):
    return harness.load_module(ROOT, f"benchmark/metrics/{name}.py")


def _span(name, t, dur_s, **args):
    """An event as the tracer records a closed span, ``t`` on perf_counter."""
    return {"name": name, "cat": "serve", "ph": "X",
            "ts": spans.from_perf_counter(t), "dur": dur_s * 1e6,
            "pid": 1, "tid": 1, "args": {"self_us": dur_s * 1e6, **args}}


def _tick(t, program, device_s, attended, tables_s=0.002, emit_s=0.003):
    return [
        _span("serve.step", t, 0.1, step=1),
        _span("serve.admit", t + 0.001, 0.001),
        _span("serve.tick.tables", t + 0.002, tables_s, program=program),
        _span("serve.program", t + 0.005, device_s, program=program,
              lanes=2, attended_tokens=attended),
        _span("serve.tick.d2h", t + 0.09, 0.001, program=program, bytes=8),
        _span("serve.tick.emit", t + 0.092, emit_s, program=program,
              tokens=2),
    ]


@pytest.fixture()
def ctx():
    """A window of 1 s that opened at ``t``: a tick before it, two decode
    ticks and a prefill step inside, a tick after its last step."""
    observe.reset()
    t = time.perf_counter()
    events = (
        _tick(t - 0.5, "decode", 0.5, 9999)
        + _tick(t + 0.1, "decode", 0.070, 1000)
        + _tick(t + 0.3, "verify-2", 0.090, 1200, tables_s=0.004, emit_s=0.005)
        + _tick(t + 0.5, "prefill-128", 0.070, 100, tables_s=0.001,
                emit_s=0.001)
        + _tick(t + 2.0, "decode", 0.5, 9999))
    observe.tracer().events.extend(events)
    clk = harness.Clock(t - 10.0)
    clk.setup_s = 10.0
    yield {"clock": clk, "steps": [
        {"t0": t + 0.1, "t1": t + 0.2}, {"t0": t + 0.3, "t1": t + 0.4},
        {"t0": t + 0.5, "t1": t + 0.6}]}
    observe.reset()


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_span_reader_reads_the_window_only(ctx, name):
    assert _reader(name).read(ctx) == pytest.approx(SPAN_METRICS[name])


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_span_reader_with_nothing_to_read_gives_none(ctx, name):
    observe.reset()
    assert _reader(name).read(ctx) is None  # no span recorded (tracing off)
    observe.tracer().events.append(_span("serve.step", ctx["steps"][0]["t0"],
                                         0.1))
    assert _reader(name).read(ctx) is None  # a parent without the children
    train = dict(ctx, steps=[(0.0, 1.0, 2.5)])
    assert _reader(name).read(train) is None  # the train kind's steps


def test_tables_reader_takes_self_time_and_sums_a_step():
    observe.reset()
    t = time.perf_counter()
    ev = _tick(t + 0.1, "chunk-2048", 0.07, 2048)
    cow = _span("serve.tick.tables", t + 0.1005, 0.030, program="decode")
    cow["args"]["self_us"] = 5000.0  # 25 ms of it a cow program's
    observe.tracer().events.extend(ev + [cow])
    clk = harness.Clock(t - 1.0)
    clk.setup_s = 1.0
    got = _reader("engine.tick_tables_p50_s").read(
        {"clock": clk, "steps": [{"t0": t + 0.1, "t1": t + 0.2}]})
    assert got == pytest.approx(0.002 + 0.005)
    observe.reset()


@pytest.fixture()
def logged():
    """A compile log with entries before and after the window opened."""
    observe.reset()
    compilelog.on_duration("/jax/core/compile/jaxpr_trace_duration", 0.5,
                           fun_name="train_step")
    compilelog.on_duration("/jax/core/compile/jaxpr_to_mlir_module_duration",
                           1.0, fun_name="jit(train_step)")
    compilelog.on_duration("/jax/core/compile/backend_compile_duration", 7.0,
                           fun_name="jit(train_step)")
    compilelog.on_duration("/jax/compilation_cache/cache_retrieval_time_sec",
                           0.3)
    compilelog.on_duration("/jax/core/compile/backend_compile_duration", 0.4,
                           fun_name="jit(tdx_serve_decode)")  # a load, no compile
    opened = time.perf_counter()
    compilelog.on_duration("/jax/core/compile/backend_compile_duration", 3.0,
                           fun_name="jit(late)")
    compilelog.on_duration("/jax/core/compile/jaxpr_trace_duration", 2.0,
                           fun_name="late")
    clk = harness.Clock(opened - 20.0)
    clk.setup_s = 20.0
    yield {"clock": clk, "steps": [(opened, opened + 1.0, 2.5)]}
    observe.reset()


@pytest.mark.parametrize("name", sorted(LOG_METRICS))
def test_compile_log_reader_counts_set_up_only(logged, name):
    assert _reader(name).read(logged) == pytest.approx(LOG_METRICS[name])
    observe.reset()
    assert _reader(name).read(logged) == 0.0  # a log, and nothing in it


@pytest.mark.parametrize("name", sorted(LOG_METRICS))
def test_compile_log_reader_without_a_log_gives_none(logged, name,
                                                     monkeypatch):
    monkeypatch.delattr(observe, "compilelog")  # a parent from before it
    assert _reader(name).read(logged) is None


def test_span_reader_without_the_clock_conversion_gives_none(ctx, monkeypatch):
    monkeypatch.delattr(spans, "from_perf_counter")
    assert spanlog.window_spans(ctx) == {}
    assert _reader("engine.tick_host_share").read(ctx) is None


def test_manifest_is_sound_with_the_new_entries():
    assert manifest.check(ROOT) == []
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    by = {p["name"]: p for p in m["per_layer"]}
    serving = ["mistral7b-chat-backlog", "mistral7b-doc-prefill"]
    for name in SPAN_METRICS:
        assert by[name]["source"] == "program_span"
        assert by[name]["workloads"] == serving
        assert by[name]["moves"] == "tpot_p50_s"
    for name in LOG_METRICS:
        assert by[name]["source"] == "program_counter"
        assert "workloads" not in by[name] and by[name]["moves"] == "setup_s"
    # Appended, in the issue's order, after everything that was there.
    assert [p["name"] for p in m["per_layer"]][-8:] == [
        "bringup.compile_s", "bringup.lower_s", "engine.tick_host_share",
        "engine.tick_tables_p50_s", "engine.tick_emit_p50_s",
        "programs.logits_d2h_p50_s", "programs.decode_device_p50_s",
        "kv.attended_tokens_per_decode_tick"]
    for name in list(SPAN_METRICS) + list(LOG_METRICS):
        assert os.path.exists(
            os.path.join(ROOT, "benchmark", "metrics", f"{name}.py"))


@pytest.mark.parametrize("cell,expect", [
    ("mistral7b-chat-backlog", set(SPAN_METRICS) | set(LOG_METRICS)),
    ("mistral7b-doc-prefill", set(SPAN_METRICS) | set(LOG_METRICS)),
    ("gpt2m-train-1chip", set(LOG_METRICS)),
])
def test_each_cell_is_asked_for_its_new_metrics(cell, expect):
    m = harness.load_manifest(ROOT)
    asked = {p["name"] for p in harness.metric_names(m, cell, "per_layer")}
    assert asked & (set(SPAN_METRICS) | set(LOG_METRICS)) == expect
