"""Flight recorder (torchdistx_tpu.observe.flightrec): the crash ring is
independent of the tracer's export buffer, every failure trigger leaves a
schema-valid dump (chaos injection, watchdog kill, MaterializationError,
uncaught exception), dumps are throttled per reason, ``%h``/``%p`` path
templates expand, the CLI renders dumps and fleets, silent span loss is
counted — and the whole layer stays under the 2% train-step overhead
gate."""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import time

import pytest

import torchdistx_tpu.config as tdx_config
from torchdistx_tpu import observe
from torchdistx_tpu.observe import flightrec

# The compile deadline against the injected 30 s hang: far under the
# hang, far over a legitimate compile on a loaded CI box (1 s was not;
# ROADMAP D18).  test_materialize_chaos.py holds the same value.
HANG_DEADLINE_S = 4.0

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = os.path.join(REPO, "tools", "tdx_trace.py")


@pytest.fixture()
def flight(tmp_path):
    """Armed flight recorder with a clean slate, disarmed after."""
    observe.reset()
    d = tmp_path / "flight"
    with tdx_config.override(flight_dir=str(d)):
        yield str(d)
    observe.reset()


def _dumps(d, reason=None):
    pat = f"flight-*-{flightrec._safe(reason)}.json" if reason else "flight-*.json"
    return sorted(glob.glob(os.path.join(d, pat)))


class TestRing:
    def test_ring_survives_tracer_drain(self, flight):
        with observe.span("pre.crash", category="t"):
            pass
        # A flush drains the tracer's export buffer...
        assert observe.tracer().drain()
        assert not observe.tracer().events
        # ...but the crash ring still holds the event, and the dump
        # carries it.
        path = observe.flight_dump("test_reason")
        doc = json.load(open(path))
        assert any(e.get("name") == "pre.crash" for e in doc["events"])

    def test_ring_is_bounded(self, flight):
        assert flightrec._ring.maxlen is not None

    def test_dropped_events_counted(self, flight):
        from torchdistx_tpu.observe.spans import Tracer

        t = Tracer(max_events=4)
        for i in range(10):
            t.instant(f"i{i}")
        assert t.dropped == 6
        snap = {r["name"]: r["value"] for r in observe.counters().snapshot()
                if r["type"] == "counter"}
        assert snap.get("tdx.observe.dropped_events", 0) >= 6

    def test_dropped_events_surface_in_summary(self, flight, tmp_path):
        from torchdistx_tpu.observe.spans import Tracer

        t = Tracer(max_events=2)
        for i in range(7):
            t.instant(f"i{i}")
        with observe.span("s"):
            pass
        d = tmp_path / "traces"
        observe.flush(trace_dir=str(d))
        out = subprocess.run(
            [sys.executable, CLI, "summary", str(d)],
            capture_output=True, text=True, timeout=60,
        )
        assert out.returncode == 0, out.stderr
        assert "dropped" in out.stdout

    def test_dump_includes_config_env_and_snapshots(self, flight):
        observe.counter("tdx.test.flightc").inc(5)
        doc = json.load(open(observe.flight_dump("test_reason")))
        assert not flightrec.validate(doc)
        assert doc["config"]["flight_dir"] == flight
        assert "python" in doc["env"]
        final = doc["counter_snapshots"][-1]["counters"]
        assert any(r["name"] == "tdx.test.flightc" and r["value"] == 5
                   for r in final)


class TestTriggers:
    def test_chaos_injection_dumps(self, flight):
        from torchdistx_tpu.chaos.inject import execute
        from torchdistx_tpu.chaos.plan import Fault

        execute(Fault(site="step", step=1, kind="slow", arg="0"))
        (path,) = _dumps(flight, "chaos_injected")
        doc = json.load(open(path))
        assert not flightrec.validate(doc)
        assert doc["context"]["spec"].startswith("step@1=slow")

    def test_materialization_error_dumps(self, flight):
        import torch

        from torchdistx_tpu import chaos
        from torchdistx_tpu.deferred_init import deferred_init
        from torchdistx_tpu.jax_bridge import (
            MaterializationError, materialize_module_jax,
        )
        from torchdistx_tpu import compile_service

        chaos.clear()
        compile_service.reset_cache_binding()
        try:
            with tdx_config.override(
                flight_dir=flight, fault_plan="compile@1=raise x9",
                materialize_pipeline="off", materialize_retries=0,
            ):
                with pytest.raises(MaterializationError):
                    materialize_module_jax(
                        deferred_init(torch.nn.Linear, 8, 4)
                    )
        finally:
            chaos.clear()
            compile_service.reset_cache_binding()
        (path,) = _dumps(flight, "materialization_error")
        doc = json.load(open(path))
        assert not flightrec.validate(doc)
        assert doc["context"]["failed_groups"] == [0]

    def test_watchdog_kill_dumps_and_run_survives(self, flight):
        import torch

        from torchdistx_tpu import chaos
        from torchdistx_tpu.deferred_init import deferred_init
        from torchdistx_tpu.jax_bridge import materialize_module_jax
        from torchdistx_tpu import compile_service

        chaos.clear()
        compile_service.reset_cache_binding()
        try:
            with tdx_config.override(
                flight_dir=flight, fault_plan="compile@1=hang:30",
                materialize_pipeline="off",
                compile_deadline_s=HANG_DEADLINE_S,
            ):
                params = materialize_module_jax(
                    deferred_init(torch.nn.Linear, 8, 4)
                )
        finally:
            chaos.clear()
            compile_service.reset_cache_binding()
        assert set(params) == {"weight", "bias"}
        (path,) = _dumps(flight, "compile_watchdog_kill")
        doc = json.load(open(path))
        assert doc["context"]["stage"] == "compile"

    def test_unhandled_exception_dumps_in_subprocess(self, tmp_path):
        # stdlib-only child (observe imports no torch/jax): fast, and
        # proves the excepthook path works without the heavy stack.
        d = tmp_path / "fl"
        script = (
            "from torchdistx_tpu import observe\n"
            "observe.counter('tdx.t.arm').inc()\n"
            "raise RuntimeError('deliberate')\n"
        )
        r = subprocess.run(
            [sys.executable, "-c", script], capture_output=True, text=True,
            timeout=120, cwd=REPO,
            env={**os.environ, "TDX_FLIGHT_DIR": str(d),
                 "PYTHONPATH": REPO},
        )
        assert r.returncode != 0  # the exception still kills the process
        (path,) = _dumps(str(d), "unhandled_exception")
        doc = json.load(open(path))
        assert not flightrec.validate(doc)
        assert "RuntimeError: deliberate" in doc["context"]["error"]
        assert "Traceback" in doc["context"]["traceback"]

    def test_worker_thread_exception_dumps(self, tmp_path):
        # Subprocess: pytest's threadexception plugin swaps
        # threading.excepthook per-test, so the wrap can only be
        # observed in a clean interpreter.
        d = tmp_path / "fl"
        script = (
            "import threading\n"
            "from torchdistx_tpu import observe\n"
            "observe.counter('tdx.t.arm').inc()\n"
            "def boom():\n"
            "    raise ValueError('thread-boom')\n"
            "t = threading.Thread(target=boom, name='w-crash')\n"
            "t.start(); t.join()\n"
        )
        r = subprocess.run(
            [sys.executable, "-c", script], capture_output=True, text=True,
            timeout=120, cwd=REPO,
            env={**os.environ, "TDX_FLIGHT_DIR": str(d),
                 "PYTHONPATH": REPO},
        )
        assert r.returncode == 0  # a thread death doesn't kill the process
        (path,) = _dumps(str(d), "unhandled_exception")
        doc = json.load(open(path))
        assert "thread-boom" in doc["context"]["error"]
        assert doc["context"]["thread"] == "w-crash"

    def test_throttle_suppresses_repeats(self, flight):
        assert observe.flight_dump("hot_reason") is not None
        assert observe.flight_dump("hot_reason") is None  # inside interval
        assert observe.flight_dump("other_reason") is not None  # per-reason
        snap = {(r["name"], r.get("labels", {}).get("reason")): r["value"]
                for r in observe.counters().snapshot()
                if r["type"] == "counter"}
        assert snap.get(
            ("tdx.observe.flight_dumps_suppressed", "hot_reason"), 0
        ) == 1

    def test_unarmed_is_noop(self, tmp_path):
        observe.reset()
        assert not flightrec.armed()
        assert observe.flight_dump("anything") is None


class TestPathTemplates:
    def test_expand_tokens(self):
        import socket

        host = socket.gethostname().split(".")[0]
        assert tdx_config.expand_path("/x/%h/m-%p.prom") == \
            f"/x/{host}/m-{os.getpid()}.prom"
        assert tdx_config.expand_path("/plain/path") == "/plain/path"
        assert tdx_config.expand_path(None) is None

    def test_flight_dir_template(self, tmp_path):
        observe.reset()
        d = str(tmp_path / "logs" / "%h")
        with tdx_config.override(flight_dir=d):
            path = observe.flight_dump("templated")
        observe.reset()
        assert path is not None and "%h" not in path
        import socket

        assert socket.gethostname().split(".")[0] in path

    def test_metrics_path_template(self, tmp_path):
        observe.reset()
        observe.enable(True)
        try:
            observe.counter("tdx.t.m").inc()
            mp = str(tmp_path / "m-%p.jsonl")
            written = observe.flush(metrics_path=mp)
            assert written["metrics"].endswith(f"m-{os.getpid()}.jsonl")
            assert os.path.isfile(written["metrics"])
        finally:
            observe.enable(None)
            observe.reset()


class TestCLI:
    def _mk_host(self, root, name):
        d = root / name
        d.mkdir(parents=True)
        observe.reset()
        observe.enable(True)
        with observe.span("jax.compile", category="jax"):
            time.sleep(0.001)
        observe.counter("tdx.jax.compile_cache_hit").inc(2)
        observe.gauge("tdx.serve.slo.ttft_p50_s").set(0.012)
        observe.gauge("tdx.serve.slo.ttft_p95_s").set(0.040)
        observe.gauge("tdx.serve.slo.ttft_p99_s").set(0.080)
        with tdx_config.override(flight_dir=str(d)):
            observe.flight_dump("serve_fault", step=3)
        observe.flush(trace_dir=str(d))
        observe.enable(None)
        observe.reset()
        return d

    def test_flight_render(self, tmp_path):
        d = self._mk_host(tmp_path, "host-a")
        out = subprocess.run(
            [sys.executable, CLI, "flight", str(d)],
            capture_output=True, text=True, timeout=60,
        )
        assert out.returncode == 0, out.stderr
        assert "reason: serve_fault" in out.stdout
        assert "0 invalid" in out.stdout

    def test_flight_invalid_exit_code(self, tmp_path):
        bad = tmp_path / "flight-1-1-bad.json"
        bad.write_text(json.dumps({"schema": 99}))
        out = subprocess.run(
            [sys.executable, CLI, "flight", str(tmp_path)],
            capture_output=True, text=True, timeout=60,
        )
        assert out.returncode == 1
        assert "SCHEMA INVALID" in out.stdout

    def test_fleet_rollup(self, tmp_path):
        self._mk_host(tmp_path, "host-a")
        self._mk_host(tmp_path, "host-b")
        out = subprocess.run(
            [sys.executable, CLI, "fleet", str(tmp_path)],
            capture_output=True, text=True, timeout=60,
        )
        assert out.returncode == 0, out.stderr
        assert "fleet: 2 host(s)" in out.stdout
        assert "host-a" in out.stdout and "host-b" in out.stdout
        assert "serve_fault" in out.stdout
        assert "TTFT" in out.stdout  # per-host SLO digest

    def test_fleet_dedupes_counters_across_source_formats(self, tmp_path):
        # One host dir holding BOTH a .prom export and a flight dump
        # carrying the same labeled counter (the obs-smoke layout):
        # the two spellings must canonicalize to one stream, not sum.
        host = tmp_path / "hostA"
        host.mkdir()
        (host / "metrics.prom").write_text(
            'tdx_chaos_injected{kind="raise"} 3\n')
        doc = {
            "schema": 1, "reason": "chaos_injected", "time": 1.0,
            "pid": 1, "host": "hostA", "events": [], "config": {},
            "env": {}, "counter_snapshots": [{"ts": 1.0, "counters": [
                {"name": "tdx.chaos.injected", "labels": {"kind": "raise"},
                 "type": "counter", "value": 3}]}],
        }
        (host / "flight-1-001-chaos_injected.json").write_text(
            json.dumps(doc))
        out = subprocess.run(
            [sys.executable, CLI, "fleet", str(tmp_path)],
            capture_output=True, text=True, timeout=60,
        )
        assert out.returncode == 0, out.stderr
        # First hostA line is the table row (a second appears in the
        # dumps-by-reason section).
        row = next(l for l in out.stdout.splitlines()
                   if l.strip().startswith("hostA"))
        assert row.split()[-2] == "3", row  # chaos column: 3, not 6

    def test_fleet_aggregates_per_pid_metrics_files(self, tmp_path):
        # %p templating puts one file per process in a host dir:
        # counters sum across pids, singleton gauges take max.
        import importlib.util

        host = tmp_path / "hostA"
        host.mkdir()
        for pid in (111, 222):
            (host / f"m-{pid}.prom").write_text(
                "# TYPE tdx_jax_compile_cache_hit counter\n"
                "tdx_jax_compile_cache_hit 2\n"
                "# TYPE tdx_jax_link_bandwidth_gbps gauge\n"
                "tdx_jax_link_bandwidth_gbps 2.5\n"
                "# TYPE tdx_serve_tokens_per_s gauge\n"
                "tdx_serve_tokens_per_s 100\n")
        spec = importlib.util.spec_from_file_location("_tdx_trace", CLI)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        counters = mod._load_metrics_files(str(host))
        assert counters["tdx_jax_compile_cache_hit"] == 4
        assert counters["tdx_jax_link_bandwidth_gbps"] == 2.5  # max
        assert counters["tdx_serve_tokens_per_s"] == 200  # per-replica sum

    def test_flight_finds_dumps_recursively(self, tmp_path):
        deep = tmp_path / "run-3" / "host-7"
        deep.mkdir(parents=True)
        observe.reset()
        with tdx_config.override(flight_dir=str(deep)):
            observe.flight_dump("serve_fault")
        observe.reset()
        out = subprocess.run(
            [sys.executable, CLI, "flight", str(tmp_path)],
            capture_output=True, text=True, timeout=60,
        )
        assert out.returncode == 0, out.stderr
        assert "serve_fault" in out.stdout

    def test_summary_slo_digest(self, tmp_path):
        d = self._mk_host(tmp_path, "host-a")
        out = subprocess.run(
            [sys.executable, CLI, "summary", str(d)],
            capture_output=True, text=True, timeout=60,
        )
        assert out.returncode == 0, out.stderr
        assert "serve SLOs" in out.stdout
        assert "p99=80.0ms" in out.stdout
        assert "flight-recorder dumps: 1" in out.stdout


class TestOverheadGate:
    def test_train_step_overhead_under_2pct(self, tmp_path):
        """The acceptance gate: with telemetry enabled AND the flight
        recorder armed, the recorder's per-step cost stays under 2% of
        a representative train step.

        Methodology: a whole-loop A/B on this 1-core CI box drowns a
        sub-1% effect in ±5% scheduler noise, so the gate measures the
        two quantities separately, each repeat-and-min (stable), and
        compares them: (a) the FULL per-step telemetry cost — meter
        span + derived gauges + ring tee, i.e. every instruction the
        armed recorder adds to a step — measured around an
        already-resident result; (b) a real jitted step's device time.
        Both sides measured, nothing estimated."""
        import jax
        import jax.numpy as jnp

        x = jax.random.normal(jax.random.PRNGKey(0), (384, 384), jnp.float32)

        @jax.jit
        def step(x):
            return x @ x / 384.0

        ready = step(x)
        ready.block_until_ready()
        # (b) representative step time: repeat-and-min of an 8-matmul
        # chain (single-digit ms on this box — the SMALL end of real
        # train steps, so the gate is conservative).
        step_times = []
        for _ in range(7):
            t0 = time.perf_counter()
            out = x
            for _ in range(8):
                out = step(out)
            out.block_until_ready()
            step_times.append(time.perf_counter() - t0)
        t_step = min(step_times)

        # (a) full armed-recorder per-step cost.
        observe.reset()
        observe.enable(True)
        try:
            with tdx_config.override(flight_dir=str(tmp_path / "fl")):
                meter = observe.StepMeter(
                    tokens_per_step=1024, flops_per_step=1e9,
                    peak_tflops=100.0,
                )
                for _ in range(20):  # warm handles, arm the ring tee
                    meter.start()
                    meter.stop(ready)
                pair_times = []
                for _ in range(5):
                    n = 200
                    t0 = time.perf_counter()
                    for _ in range(n):
                        meter.start()
                        meter.stop(ready)
                    pair_times.append((time.perf_counter() - t0) / n)
        finally:
            observe.enable(None)
            observe.reset()
        t_meter = min(pair_times)
        overhead = t_meter / t_step
        assert overhead < 0.02, (
            f"armed recorder costs {t_meter * 1e6:.1f}µs/step = "
            f"{overhead:.2%} of a {t_step * 1e3:.2f}ms step"
        )
        # Absolute backstop: the per-step cost must stay tens of µs —
        # a 10ms step budget must never be eaten by telemetry.
        assert t_meter < 200e-6, f"{t_meter * 1e6:.1f}µs/step"
