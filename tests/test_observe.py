"""Unified runtime telemetry (torchdistx_tpu.observe).

Covers the subsystem itself (span nesting, thread safety, counter
aggregation, Chrome-trace / Prometheus / JSON-lines export round-trips),
its activation knobs (TDX_TRACE_DIR / override(trace_dir=...)), the
tier-1 end-to-end contract — a CPU ``materialize_module_jax`` run emits
record/compile/materialize spans and compile-cache hit/miss counters;
a train loop emits per-step spans with throughput gauges — and the
``tools/tdx_trace.py`` summary CLI.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from torchdistx_tpu import observe
import torchdistx_tpu.config as tdx_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def telemetry():
    """Force telemetry on with a clean slate; restore config-driven
    gating (and drop collected events) afterwards so other tests keep
    the zero-overhead disabled path."""
    observe.reset()
    observe.enable(True)
    try:
        yield observe
    finally:
        observe.enable(None)
        observe.reset()


class TestSpans:
    def test_nesting_and_self_time(self, telemetry):
        with observe.span("outer", category="t"):
            time.sleep(0.02)
            with observe.span("inner", category="t"):
                time.sleep(0.01)
        by_name = {e["name"]: e for e in observe.tracer().events}
        outer, inner = by_name["outer"], by_name["inner"]
        assert outer["ph"] == inner["ph"] == "X"
        # containment: inner starts after outer, ends before it
        assert inner["ts"] >= outer["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e3
        # outer's self-time excludes inner's duration
        assert outer["args"]["self_us"] <= outer["dur"] - inner["dur"] + 1e3

    def test_attrs_and_exception_annotation(self, telemetry):
        with pytest.raises(ValueError):
            with observe.span("boom", category="t", a=1) as sp:
                sp.set(b=2)
                raise ValueError("x")
        (ev,) = observe.tracer().events
        assert ev["args"]["a"] == 1 and ev["args"]["b"] == 2
        assert ev["args"]["error"] == "ValueError"

    def test_disabled_is_noop_singleton(self):
        observe.enable(False)
        try:
            n0 = len(observe.tracer().events)
            s1 = observe.span("a")
            s2 = observe.span("b")
            assert s1 is s2  # shared no-op object: zero allocation
            with s1:
                pass
            assert len(observe.tracer().events) == n0
        finally:
            observe.enable(None)

    def test_thread_safety(self, telemetry):
        barrier = threading.Barrier(4)  # all alive at once: distinct idents

        def worker(i):
            barrier.wait()
            for j in range(25):
                with observe.span(f"t{i}", category="thr"):
                    pass

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        events = [e for e in observe.tracer().events if e["ph"] == "X"]
        assert len(events) == 100
        assert len({e["tid"] for e in events}) == 4  # per-thread lanes

    def test_config_activation_scoped(self, tmp_path):
        observe.reset()
        assert not observe.enabled()
        with tdx_config.override(trace_dir=str(tmp_path)):
            assert observe.enabled()
            with observe.span("scoped"):
                pass
        assert not observe.enabled()
        assert any(e["name"] == "scoped" for e in observe.tracer().events)
        observe.reset()

    def test_env_var_resolution(self, monkeypatch, tmp_path):
        monkeypatch.setenv("TDX_TRACE_DIR", str(tmp_path))
        monkeypatch.setenv("TDX_METRICS_PATH", str(tmp_path / "m.prom"))
        cfg = tdx_config._from_env()
        assert cfg.trace_dir == str(tmp_path)
        assert cfg.metrics_path == str(tmp_path / "m.prom")


class TestCounters:
    def test_counter_aggregation_across_threads(self, telemetry):
        c = observe.counter("tdx.test.hits")

        def worker():
            for _ in range(1000):
                c.inc()

        threads = [threading.Thread(target=worker) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 4000

    def test_gauge_and_histogram(self, telemetry):
        observe.gauge("tdx.test.g").set(1.5)
        observe.gauge("tdx.test.g").set(2.5)  # same handle, last wins
        h = observe.histogram("tdx.test.h", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        snap = {r["name"]: r for r in observe.counters().snapshot()}
        assert snap["tdx.test.g"]["value"] == 2.5
        hr = snap["tdx.test.h"]
        assert hr["count"] == 3 and hr["min"] == 0.05 and hr["max"] == 5.0
        assert hr["buckets"] == {"0.1": 1, "1.0": 1, "+Inf": 1}
        # gauge sets also produce chrome counter samples (time series)
        samples = [e for e in observe.tracer().events if e["ph"] == "C"]
        assert [s["args"]["value"] for s in samples] == [1.5, 2.5]

    def test_labels_and_type_conflicts(self, telemetry):
        observe.counter("tdx.test.labeled", kind="a").inc()
        observe.counter("tdx.test.labeled", kind="b").inc(2)
        snap = [r for r in observe.counters().snapshot()
                if r["name"] == "tdx.test.labeled"]
        assert {r["labels"]["kind"]: r["value"] for r in snap} == {"a": 1, "b": 2}
        with pytest.raises(TypeError):
            observe.gauge("tdx.test.labeled", kind="a")


class TestExport:
    def test_chrome_trace_roundtrip(self, telemetry, tmp_path):
        with observe.span("phase", category="x", foo="bar"):
            pass
        observe.counter("tdx.c").inc(7)
        written = observe.flush(trace_dir=str(tmp_path))
        doc = json.load(open(written["trace"]))
        evs = doc["traceEvents"]
        span_ev = next(e for e in evs if e.get("ph") == "X")
        assert span_ev["name"] == "phase" and span_ev["args"]["foo"] == "bar"
        assert {"ts", "dur", "pid", "tid", "cat"} <= set(span_ev)
        counter_ev = next(e for e in evs if e.get("ph") == "C")
        assert counter_ev["args"]["value"] == 7
        assert any(e.get("ph") == "M" for e in evs)  # process metadata

    def test_prometheus_roundtrip(self, telemetry, tmp_path):
        observe.counter("tdx.x.total").inc(3)
        observe.gauge("tdx.x.gbps").set(1.25)
        observe.histogram("tdx.x.lat", buckets=(1.0,)).observe(0.5)
        path = tmp_path / "metrics.prom"
        observe.flush(metrics_path=str(path))
        text = path.read_text()
        assert "# TYPE tdx_x_total counter" in text
        assert "tdx_x_total 3" in text
        assert "tdx_x_gbps 1.25" in text
        assert 'tdx_x_lat_bucket{le="1.0"} 1' in text
        assert "tdx_x_lat_count 1" in text

    def test_labeled_counters_stay_distinct_in_trace(self, telemetry, tmp_path):
        observe.counter("tdx.graph.verify_failures", kind="a").inc(5)
        observe.counter("tdx.graph.verify_failures", kind="b").inc(3)
        written = observe.flush(trace_dir=str(tmp_path))
        doc = json.load(open(written["trace"]))
        samples = [e for e in doc["traceEvents"] if e.get("ph") == "C"
                   and e["name"].startswith("tdx.graph.verify_failures")]
        # two distinct counter streams, not one last-write-wins collision
        assert sorted(e["args"]["value"] for e in samples) == [3, 5]

    def test_prometheus_single_type_line_per_name(self, telemetry, tmp_path):
        observe.counter("tdx.z.fail", kind="a").inc()
        observe.counter("tdx.z.fail", kind="b").inc()
        text = observe.counters().to_prometheus()
        assert text.count("# TYPE tdx_z_fail counter") == 1
        assert 'tdx_z_fail{kind="a"} 1' in text
        assert 'tdx_z_fail{kind="b"} 1' in text

    def test_flush_drains_and_dedups(self, telemetry, tmp_path):
        with observe.span("once"):
            pass
        observe.counter("tdx.w").inc()
        d = tmp_path / "t"
        mp = tmp_path / "m.jsonl"
        assert observe.flush(trace_dir=str(d), metrics_path=str(mp))
        # nothing new since: no second trace file, no duplicate jsonl rows
        assert observe.flush(trace_dir=str(d), metrics_path=str(mp)) == {}
        assert len(list(d.iterdir())) == 1
        assert len(mp.read_text().splitlines()) == 1
        # spans were drained into the first file, not re-exported
        with observe.span("twice"):
            pass
        w2 = observe.flush(trace_dir=str(d))
        doc = json.load(open(w2["trace"]))
        span_names = [e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert span_names == ["twice"]

    def test_jsonl_metrics_roundtrip(self, telemetry, tmp_path):
        observe.counter("tdx.y").inc()
        path = tmp_path / "metrics.jsonl"
        observe.flush(metrics_path=str(path))
        recs = [json.loads(line) for line in path.read_text().splitlines()]
        assert any(r["name"] == "tdx.y" and r["value"] == 1 for r in recs)

    def test_jsonl_sink_supersedes_metrics(self, tmp_path):
        sink = observe.JsonlSink(str(tmp_path / "s.jsonl"))
        sink.log(step=1, loss=1.5, note=object())
        sink.close()
        (rec,) = [json.loads(line)
                  for line in (tmp_path / "s.jsonl").read_text().splitlines()]
        assert rec["step"] == 1 and rec["loss"] == 1.5
        assert isinstance(rec["note"], str)  # non-floats stringified

    def test_legacy_shims_warn_but_work(self, tmp_path):
        from torchdistx_tpu.utils import Metrics, StepTimer

        with pytest.warns(DeprecationWarning):
            m = Metrics(tmp_path / "legacy.jsonl")
        m.log(3, loss=0.5)
        m.close()
        (rec,) = [json.loads(line)
                  for line in (tmp_path / "legacy.jsonl").read_text().splitlines()]
        assert rec["step"] == 3 and rec["loss"] == 0.5
        with pytest.warns(DeprecationWarning):
            st = StepTimer()
        st.start()
        st.stop()
        assert st.steps == 1 and st.mean > 0


class TestStepMeter:
    def test_derived_gauges(self, telemetry):
        meter = observe.StepMeter(tokens_per_step=1000, flops_per_step=1e9,
                                  peak_tflops=100.0)
        meter.start()
        time.sleep(0.01)
        meter.stop()
        assert meter.steps == 1
        snap = {r["name"]: r["value"] for r in observe.counters().snapshot()}
        assert snap["tdx.train.tokens_per_s"] > 0
        assert snap["tdx.train.mfu_est"] > 0
        (ev,) = [e for e in observe.tracer().events if e["ph"] == "X"]
        assert ev["name"] == "train.step" and "tokens_per_s" in ev["args"]

    def test_works_disabled(self):
        observe.enable(False)
        try:
            meter = observe.StepMeter()
            meter.start()
            dt = meter.stop()
            assert dt >= 0 and meter.steps == 1
            assert not observe.tracer().events
        finally:
            observe.enable(None)

    def test_peak_tflops_table(self):
        assert observe.peak_tflops_for("TPU v5 lite") == 197.0
        assert observe.peak_tflops_for("TPU v4") == 275.0
        assert observe.peak_tflops_for("cpu") is None


@pytest.fixture()
def jax_cache(tmp_path, monkeypatch, telemetry):
    """Fresh persistent compile cache bound for the test, restored after:
    min-compile-time 0 so even toy programs persist entries (the
    hit/miss telemetry needs real cache traffic)."""
    import jax

    from torchdistx_tpu import compile_service

    monkeypatch.setenv("TDX_CACHE_MIN_COMPILE_S", "0")
    monkeypatch.setattr(compile_service, "_cache_enabled", False)
    prev_dir = getattr(jax.config, "jax_compilation_cache_dir", None)
    cache = tmp_path / "xla_cache"
    cache.mkdir()
    yield str(cache)
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    try:
        from jax._src import compilation_cache as cc

        cc.reset_cache()
    except Exception:
        pass
    compile_service._cache_enabled = False


class TestMaterializeTelemetry:
    """Tier-1 contract: a CPU materialize_module_jax run emits compile +
    materialize spans and compile-cache counters."""

    def _materialize_linear(self, cache):
        import torch

        from torchdistx_tpu.deferred_init import deferred_init
        from torchdistx_tpu.jax_bridge import materialize_module_jax

        with tdx_config.override(cache_dir=cache):
            m = deferred_init(torch.nn.Linear, 16, 8)
            return materialize_module_jax(m, seed=0)

    def test_spans_and_cache_counters(self, jax_cache):
        params = self._materialize_linear(jax_cache)
        assert set(params) == {"weight", "bias"}
        names = [e["name"] for e in observe.tracer().events if e["ph"] == "X"]
        for expected in ("record", "bridge.build_init_fn", "jax.lower",
                         "jax.compile", "jax.execute", "jax.materialize"):
            assert expected in names, f"missing span {expected!r} in {names}"
        snap = {r["name"]: r.get("value")
                for r in observe.counters().snapshot()}
        assert snap.get("tdx.jax.compile_cache_miss", 0) >= 1
        assert snap["tdx.graph.ops_recorded"] >= 2
        assert snap["tdx.graph.fakes_created"] >= 2
        assert snap["tdx.jax.bytes_materialized"] >= (16 * 8 + 8) * 4
        assert snap["tdx.jax.materialize_gbps"] > 0

    def test_second_run_hits_cache(self, jax_cache):
        self._materialize_linear(jax_cache)
        self._materialize_linear(jax_cache)
        snap = {r["name"]: r.get("value")
                for r in observe.counters().snapshot()}
        assert snap.get("tdx.jax.compile_cache_miss", 0) >= 1
        assert snap.get("tdx.jax.compile_cache_hit", 0) >= 1

    def test_trace_file_is_perfetto_loadable_shape(self, jax_cache, tmp_path):
        self._materialize_linear(jax_cache)
        written = observe.flush(trace_dir=str(tmp_path / "traces"))
        doc = json.load(open(written["trace"]))
        assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
        # every complete event carries the chrome-required keys
        for e in doc["traceEvents"]:
            if e.get("ph") == "X":
                assert {"name", "ts", "dur", "pid", "tid"} <= set(e)


class TestTrainStepTelemetry:
    def test_two_steps_emit_spans_and_gauges(self, telemetry):
        import numpy as np
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh

        from torchdistx_tpu.models import make_llama
        from torchdistx_tpu.models.configs import TransformerConfig

        from torchdistx_tpu.parallel.train import make_train_step

        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=1, n_heads=2, d_ff=64,
            max_seq_len=16, dtype=jnp.float32,
        )
        model = make_llama(cfg)
        mesh = Mesh(np.asarray(jax.devices("cpu")[:1]), ("dp",))
        tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 64)
        params = jax.jit(model.init)(jax.random.PRNGKey(1), tokens)
        init_state, train_step, shard_batch = make_train_step(model, cfg, mesh)
        state = init_state(params)
        batch = shard_batch(tokens)
        for _ in range(2):
            state, metrics = train_step(state, batch)
        steps = [e for e in observe.tracer().events
                 if e["ph"] == "X" and e["name"] == "train.step"]
        assert len(steps) == 2
        assert all(e["args"]["tokens_per_s"] > 0 for e in steps)
        snap = {r["name"]: r["value"] for r in observe.counters().snapshot()}
        assert snap["tdx.train.tokens_per_s"] > 0
        assert float(metrics["loss"]) > 0


class TestTraceCLI:
    def _make_trace_dir(self, tmp_path):
        with observe.span("jax.compile", category="jax"):
            time.sleep(0.002)
        observe.counter("tdx.jax.compile_cache_hit").inc(3)
        observe.counter("tdx.jax.compile_cache_miss").inc()
        observe.counter("tdx.ops.interpreted_calls").inc()
        d = tmp_path / "traces"
        observe.flush(trace_dir=str(d))
        return d

    def test_summary(self, telemetry, tmp_path):
        d = self._make_trace_dir(tmp_path)
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "tdx_trace.py"),
             "summary", str(d)],
            capture_output=True, text=True, timeout=60,
        )
        assert out.returncode == 0, out.stderr
        assert "jax.compile" in out.stdout
        assert "3 hit / 1 miss" in out.stdout
        assert "75% hit ratio" in out.stdout
        assert "interpreted kernel calls: 1" in out.stdout

    def test_chrome_merge(self, telemetry, tmp_path):
        d = self._make_trace_dir(tmp_path)
        merged = tmp_path / "merged.json"
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "tdx_trace.py"),
             "chrome", str(d), "-o", str(merged)],
            capture_output=True, text=True, timeout=60,
        )
        assert out.returncode == 0, out.stderr
        doc = json.load(open(merged))
        assert any(e.get("name") == "jax.compile" for e in doc["traceEvents"])

    def test_empty_dir_exit_code(self, tmp_path):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "tdx_trace.py"),
             "summary", str(tmp_path)],
            capture_output=True, text=True, timeout=60,
        )
        assert out.returncode == 2


# -- spans on the profiler's clock, inside a serving tick, and the compile log


class _CountingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records what the
    spans open and close."""

    opened: list = []
    closed: list = []

    def __init__(self, name, **kw):
        self.name = name
        type(self).opened.append((name, kw))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        type(self).closed.append(self.name)
        return False


@pytest.fixture()
def annotations(monkeypatch):
    from torchdistx_tpu.observe import spans

    _CountingAnnotation.opened, _CountingAnnotation.closed = [], []
    monkeypatch.setattr(spans, "_annotation_cls", _CountingAnnotation)
    return _CountingAnnotation


class TestProfilerMirror:
    def test_spans_land_on_host_plane_of_the_xplane(self, telemetry, tmp_path):
        """A span opened while a jax.profiler session runs is found by name
        on /host:CPU of the .xplane.pb, nested as the spans were, on a clock
        that agrees with the tracer's to a millisecond."""
        import glob

        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with observe.span("mirror.outer", category="t", k=3):
                time.sleep(0.004)
                with observe.span("mirror.inner", category="t", prog="decode"):
                    time.sleep(0.003)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(
            str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
        found = {}
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("mirror."):
                        found[e.name] = (e.start_ns, e.duration_ns,
                                         {k: v for k, v in e.stats})
        assert set(found) == {"mirror.outer", "mirror.inner"}
        (o0, od, ostats), (i0, idur, istats) = (
            found["mirror.outer"], found["mirror.inner"])
        assert o0 <= i0 and i0 + idur <= o0 + od  # nested as the spans were
        assert ostats["k"] == 3 and istats["prog"] == "decode"
        mine = {e["name"]: e for e in observe.tracer().events
                if e["ph"] == "X"}
        for name, (_s, dur_ns, _st) in found.items():
            assert abs(dur_ns / 1e3 - mine[name]["dur"]) < 1000.0
        # ... and the same offset between the two clocks for both spans.
        off = [found[n][0] / 1e3 - mine[n]["ts"] for n in found]
        assert abs(off[0] - off[1]) < 1000.0

    def test_annotation_carries_opening_args_and_nests(self, telemetry,
                                                       annotations):
        with observe.span("a", category="t", x=1) as sp:
            sp.set(late=2)
            with observe.span("b", category="t"):
                pass
        assert annotations.opened == [("a", {"x": 1}), ("b", {})]
        assert annotations.closed == ["b", "a"]

    def test_clock_conversion_is_the_events_clock(self, telemetry):
        from torchdistx_tpu.observe import spans

        t0 = time.perf_counter()
        with observe.span("clocked"):
            pass
        t1 = time.perf_counter()
        (ev,) = [e for e in observe.tracer().events if e["ph"] == "X"]
        assert spans.from_perf_counter(t0) <= ev["ts"]
        assert ev["ts"] + ev["dur"] <= spans.from_perf_counter(t1)

    def test_process_without_jax_mirrors_nothing(self):
        code = (
            "import sys, importlib.util, os\n"
            "p = os.path.join(%r, 'torchdistx_tpu', 'observe', 'spans.py')\n"
            "spec = importlib.util.spec_from_file_location('spans', p)\n"
            "m = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(m)\n"
            "t = m.Tracer()\n"
            "with t.span('x'):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules and m._annotation_cls is None\n"
            "assert [e['name'] for e in t.events] == ['x']\n" % REPO)
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr


@pytest.fixture(scope="module")
def tick_replica():
    import jax.numpy as jnp

    from torchdistx_tpu.models import TransformerConfig
    from torchdistx_tpu.serve import ServeConfig, spin_up_replica

    cfg = TransformerConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=64, dtype=jnp.float32)
    scfg = ServeConfig(
        max_batch=2, page_size=8, n_pages=16, max_pages_per_seq=3,
        prefill_buckets=(8, 16), spec_decode=False)
    return spin_up_replica(cfg, family="llama", serve_cfg=scfg), scfg


@pytest.fixture()
def tick_engine(tick_replica):
    """A fresh engine on the replica's weights and compiled programs: its
    counter handles are resolved now, after any ``observe.reset()``."""
    from torchdistx_tpu.serve import ServeEngine

    warm, scfg = tick_replica
    eng = ServeEngine("llama", warm.cfg, warm.params, serve_cfg=scfg)
    eng._programs.update(warm._programs)
    return eng


# A plain decode tick's spans: the program call's two dispatches (the
# program, then the greedy choice) and its one wait.
TICK_SPANS = ("serve.step", "serve.admit", "serve.admit.deadlines",
              "serve.tick.tables", "serve.program", "serve.program.launch",
              "serve.program.launch", "serve.program.wait", "serve.tick.d2h",
              "serve.tick.emit", "serve.gauges")


class TestServeTickSpans:
    def _two_lanes(self, eng, tag):
        from torchdistx_tpu.serve import Request

        eng.submit(Request(f"{tag}-short", [3, 1, 4], max_new_tokens=3))
        eng.submit(Request(f"{tag}-long", [2, 7, 1, 8, 2], max_new_tokens=6))
        # Admits both and prefills (and decodes once, on a replica that
        # reads each tick before it dispatches the next).
        eng.step()
        assert len(eng.active) == 2

    def test_tick_yields_the_spans_and_counts_a_retiring_lane(
            self, telemetry, tick_engine):
        """The step in which `on-short` retires.  This replica decodes
        ahead (no drafter): that step dispatches a tick for the lanes that
        go on, then hands over the tick before it, both lanes'."""
        eng = tick_engine
        self._two_lanes(eng, "on")
        while True:
            # The lanes of the tick this step dispatches, and the position
            # each writes (counting the positions of a tick in flight).
            ticked = {l.req.rid: l.length + l.ahead
                      for l in eng.active.values() if eng._decodes(l)}
            before = {n: observe.counter(n).value for n in (
                "tdx.serve.attended_tokens", "tdx.serve.decode_lane_ticks")}
            n0 = len(observe.tracer().events)
            eng.step()
            if "on-short" not in {l.req.rid for l in eng.active.values()}:
                break
        assert [l.req.rid for l in eng.active.values()] == ["on-long"]
        evs = [e for e in list(observe.tracer().events)[n0:]
               if e["ph"] == "X"]
        assert sorted(e["name"] for e in evs) == sorted(TICK_SPANS)
        by = {e["name"]: e for e in evs}
        # Each lane attends over its context with the new token in it.
        want = sum(n + 1 for n in ticked.values())
        prog = by["serve.program"]["args"]
        assert (prog["program"], prog["lanes"]) == ("decode", len(ticked))
        assert prog["attended_tokens"] == want
        assert by["serve.tick.emit"]["args"]["tokens"] == 2
        # What a plain tick brings: an int32 a lane of the batch (the
        # [2, 128] float32 logits stay on the device).
        assert by["serve.tick.d2h"]["args"]["bytes"] == 2 * 4
        assert by["serve.admit"]["args"]["admitted"] == 0
        step = by["serve.step"]
        for name in TICK_SPANS[1:]:  # all children of serve.step
            e = by[name]
            assert step["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= step["ts"] + step["dur"] + 1.0
        assert observe.counter(
            "tdx.serve.attended_tokens").value - before[
                "tdx.serve.attended_tokens"] == want
        assert observe.counter(
            "tdx.serve.decode_lane_ticks").value - before[
                "tdx.serve.decode_lane_ticks"] == len(ticked)
        eng.run()

    def test_decode_tick_counts_the_blocks_the_kernel_walks(
            self, telemetry, tick_engine):
        """``kv_blocks`` of a decode tick's ``serve.program`` and the
        always-on ``tdx.serve.attn_kv_blocks`` are the kernel's own
        block arithmetic on the lanes' lengths; a program that attends
        through jnp gathers carries 0."""
        from torchdistx_tpu.ops import kv_blocks_walked, pages_per_block

        eng = tick_engine
        self._two_lanes(eng, "kb")
        lengths = [l.length + 1 for l in eng.active.values()]
        pool = eng.k_pages
        kv, page, hd = pool.shape[2:]
        want = kv_blocks_walked(lengths, page, kv, hd, pool.dtype)
        # TINY's head dim walks a page a block: the pages under the lanes.
        assert pages_per_block(kv, page, hd, pool.dtype) == 1
        assert want == sum(-(-n // page) for n in lengths) > 0
        before = observe.counter("tdx.serve.attn_kv_blocks").value
        n0 = len(observe.tracer().events)
        eng.step()
        progs = [e["args"] for e in list(observe.tracer().events)[n0:]
                 if e["ph"] == "X" and e["name"] == "serve.program"]
        assert [(a["program"], a["kv_blocks"]) for a in progs] == [
            ("decode", want)]
        assert observe.counter(
            "tdx.serve.attn_kv_blocks").value - before == want
        eng.run()
        others = [e["args"] for e in observe.tracer().events
                  if e["ph"] == "X" and e["name"] == "serve.program"
                  and e["args"]["program"] != "decode"]
        assert others and all(a["kv_blocks"] == 0 for a in others)

    def test_prefill_and_chunk_paths_open_the_same_children(
            self, telemetry, tick_engine):
        from torchdistx_tpu.serve import Request

        eng = tick_engine
        eng.submit(Request("wide", [(7 * i) % 128 for i in range(18)],
                           max_new_tokens=2))
        eng.run()
        progs = [e["args"] for e in observe.tracer().events
                 if e["ph"] == "X" and e["name"] == "serve.program"]
        names = [a["program"] for a in progs]
        assert names[:2] == ["chunk-16", "chunk-8"]
        assert [a["attended_tokens"] for a in progs[:2]] == [16, 18]
        kinds = {e["name"] for e in observe.tracer().events if e["ph"] == "X"}
        assert set(TICK_SPANS) | {"serve.prefill"} <= kinds
        # The first chunk's logits are read by nobody: no copy, no emit.
        d2h = [e["args"]["program"] for e in observe.tracer().events
               if e["ph"] == "X" and e["name"] == "serve.tick.d2h"]
        assert "chunk-16" not in d2h and "chunk-8" in d2h

    def test_telemetry_off_records_and_annotates_nothing(
            self, tick_engine, annotations):
        from torchdistx_tpu.observe.spans import _NOOP_SPAN

        observe.enable(False)
        try:
            assert observe.span("serve.program", program="decode") is _NOOP_SPAN
            n0 = len(observe.tracer().events)
            before = observe.counter("tdx.serve.decode_lane_ticks").value
            self._two_lanes(tick_engine, "off")
            tick_engine.run()
            assert len(observe.tracer().events) == n0
            assert annotations.opened == []
            # The operators' counters are always on.
            assert observe.counter(
                "tdx.serve.decode_lane_ticks").value > before
        finally:
            observe.enable(None)


class TestCompileLog:
    def test_fresh_jit_is_logged_with_its_seconds_and_only_once(self):
        import jax
        import jax.numpy as jnp

        from torchdistx_tpu.observe import compilelog

        compilelog.install()
        salt = float(time.time_ns() % 9973)  # a program no cache has seen

        def _tdx_fresh_fn(x):
            return jnp.sin(x) * salt + jnp.cumsum(x)

        f = jax.jit(_tdx_fresh_fn)
        x = jnp.arange(8.0)
        x.block_until_ready()
        n0 = len(compilelog.entries())
        c0 = observe.counter("tdx.jax.backend_compiles").value
        s0 = observe.counter("tdx.jax.backend_compile_s").value
        l0 = observe.counter("tdx.jax.lower_s").value
        t_before = time.perf_counter()
        f(x).block_until_ready()
        new = compilelog.entries()[n0:]
        mine = [e for e in new if "_tdx_fresh_fn" in e[3]]
        assert [e[1] for e in mine] == ["trace", "lower", "backend_compile"]
        assert all(e[2] > 0 and t_before <= e[0] <= time.perf_counter()
                   for e in mine)
        assert set(e[1] for e in new) <= set(compilelog.EVENTS)
        assert observe.counter("tdx.jax.backend_compiles").value == c0 + sum(
            e[1] == "backend_compile" for e in new)
        assert observe.counter(
            "tdx.jax.backend_compile_s").value - s0 == pytest.approx(
                sum(e[2] for e in new if e[1] == "backend_compile"))
        assert observe.counter("tdx.jax.lower_s").value - l0 == pytest.approx(
            sum(e[2] for e in new if e[1] in ("trace", "lower")))
        n1 = len(compilelog.entries())
        f(x).block_until_ready()
        assert len(compilelog.entries()) == n1  # nothing compiled again

    def test_cache_load_is_one_retrieval_entry_not_a_compile(self):
        from torchdistx_tpu.observe import compilelog

        n0 = len(compilelog.entries())
        c0 = observe.counter("tdx.jax.backend_compiles").value
        compilelog.on_duration(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.2)
        compilelog.on_duration(
            "/jax/core/compile/backend_compile_duration", 0.25,
            fun_name="jit(tdx_serve_decode)")
        compilelog.on_duration("/some/other/event", 1.0)
        (entry,) = compilelog.entries()[n0:]
        assert entry[1:] == ("cache_retrieval", 0.25, "jit(tdx_serve_decode)")
        assert observe.counter("tdx.jax.backend_compiles").value == c0

    def test_nested_traces_are_covered_by_the_outermost(self):
        from torchdistx_tpu.observe import compilelog

        ev = "/jax/core/compile/jaxpr_trace_duration"
        n0 = len(compilelog.entries())
        compilelog.on_scalar(ev, 0.0, fun_name="outer")
        compilelog.on_scalar(ev, 0.0, fun_name="_where")
        compilelog.on_duration(ev, 0.001, fun_name="_where")
        compilelog.on_duration(ev, 0.5, fun_name="outer")
        assert [e[1:] for e in compilelog.entries()[n0:]] == [
            ("trace", 0.5, "outer")]
