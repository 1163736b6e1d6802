"""The readers of the program's own spans and compile log
(``benchmark/spanlog.py``, ``benchmark/metrics/*``): each on hand-made
tracer content and a hand-made compile log, cut to the window; nothing to
read gives None; and the manifest is sound with their entries."""

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench_util import roots  # noqa: E402
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


PR26_ORDER = [
    "bringup.compile_s", "bringup.lower_s", "engine.tick_host_share",
    "engine.tick_tables_p50_s", "engine.tick_emit_p50_s",
    "programs.logits_d2h_p50_s", "programs.decode_device_p50_s",
    "kv.attended_tokens_per_decode_tick"]
MISTRAL_SERVING = ["mistral7b-chat-backlog", "mistral7b-doc-prefill-busy"]


@pytest.mark.parametrize("grown", [False, True],
                         ids=["as-committed", "with-a-later-cell"])
def test_manifest_is_sound_with_the_new_entries(tmp_path, grown):
    root = roots(tmp_path, grown)
    assert manifest.check(root) == []
    m = harness.load_manifest(root)
    by = {p["name"]: p for p in m["per_layer"]}
    for name in SPAN_METRICS:
        assert by[name]["source"] == "program_span"
        # at least the two Mistral serving cells; later PRs append theirs
        assert set(by[name]["workloads"]) >= set(MISTRAL_SERVING)
        assert by[name]["moves"] == "tpot_p50_s"
    for name in LOG_METRICS:
        assert by[name]["source"] == "program_counter"
        assert "workloads" not in by[name] and by[name]["moves"] == "setup_s"
    # In the issue's order relative to each other, wherever later PRs'
    # entries have come to lie.
    assert [p["name"] for p in m["per_layer"]
            if p["name"] in PR26_ORDER] == PR26_ORDER
    for name in list(SPAN_METRICS) + list(LOG_METRICS):
        assert os.path.exists(
            os.path.join(root, "benchmark", "metrics", f"{name}.py"))


@pytest.mark.parametrize("cell,expect", [
    ("mistral7b-chat-backlog", set(SPAN_METRICS) | set(LOG_METRICS)),
    ("mistral7b-doc-prefill-busy", set(SPAN_METRICS) | set(LOG_METRICS)),
    ("gpt2m-train-1chip", set(LOG_METRICS)),
])
def test_each_cell_is_asked_for_its_new_metrics(cell, expect):
    m = harness.load_manifest(ROOT)
    asked = {p["name"] for p in harness.metric_names(m, cell, "per_layer")}
    assert asked & (set(SPAN_METRICS) | set(LOG_METRICS)) == expect


# What the traced run of mistral7b-doc-prefill was asked for before the cell
# was renamed (BENCHMARK.json at PR 29): the busy cell is asked for the same.
DOC_PREFILL_PER_LAYER = {
    "bringup.import_s", "bringup.backend_s", "bringup.materialize_s",
    "bringup.programs_s", "bringup.warmup_s", "bringup.other_s",
    "bringup.program_misses", "bringup.compile_s", "bringup.lower_s",
    "engine.queue_wait_p90_s", "engine.verify_tick_share",
    "engine.tick_host_share", "engine.tick_tables_p50_s",
    "engine.tick_emit_p50_s", "kv.pages_in_use_peak_share", "kv.preemptions",
    "kv.attended_tokens_per_decode_tick", "programs.decode_tick_p50_s",
    "programs.prefill_s_per_ktok", "programs.logits_d2h_p50_s",
    "programs.decode_device_p50_s", "serve.mfu", "paged_attention_roofline",
    "device.idle_share", "device.peak_hbm_share", "latency.tpot_p90_s",
    "latency.ttft_p50_s", "latency.ttft_p90_s", "loadgen.lateness_p99_s"}


@pytest.mark.parametrize("group,expect", [
    ("per_layer", DOC_PREFILL_PER_LAYER),
    ("end_to_end", {"tpot_p50_s", "setup_s"}),
])
def test_the_busy_cell_is_asked_for_what_doc_prefill_was(group, expect):
    m = harness.load_manifest(ROOT)
    assert {p["name"] for p in harness.metric_names(
        m, "mistral7b-doc-prefill-busy", group)} == expect


def test_no_metric_names_the_cell_that_is_gone():
    m = harness.load_manifest(ROOT)
    assert [p["name"] for p in m["per_layer"] + m["end_to_end"]
            if "mistral7b-doc-prefill" in p.get("workloads", [])] == []
    assert "mistral7b-doc-prefill" not in {w["name"] for w in m["workloads"]}


def test_logits_d2h_is_the_decode_ticks_fetch_and_no_prefill_s():
    """150 of 207 program calls of the jamba cell's traced window were
    one-row prefill fetches (PERF.md 5, PR 28): they no longer enter."""
    observe.reset()
    t = time.perf_counter()
    events = []
    for k in range(5):  # five prefills' one-row fetches, 0.6 ms
        events.append(_span("serve.tick.d2h", t + 0.01 * k, 0.0006,
                            program="prefill-256", bytes=262144))
    events += [_span("serve.tick.d2h", t + 0.10, 0.011, program="decode",
                     bytes=33554432),
               _span("serve.tick.d2h", t + 0.15, 0.012, program="verify-2",
                     bytes=33554432)]
    observe.tracer().events.extend(events)
    clk = harness.Clock(t - 1.0)
    clk.setup_s = 1.0
    ctx = {"clock": clk, "steps": [{"t0": t, "t1": t + 0.2}]}
    read = _reader("programs.logits_d2h_p50_s").read
    assert read(ctx) == pytest.approx(0.011)  # nearest rank of the two ticks
    observe.reset()
    observe.tracer().events.extend(events[:5])
    assert read(ctx) is None  # a window of prefills only has none to read
    observe.reset()
