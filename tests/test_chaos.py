"""Chaos suite: every fault type in the plan grammar (raise / hang /
corrupt / slow / preempt) is injected deterministically and SURVIVED by
``run_elastic``, with final state bitwise-equal to the fault-free run at
the same step (CPU).  See docs/robustness.md for the failure model."""

import json
import os
import signal
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

import torchdistx_tpu.config as tdx_config
from torchdistx_tpu import chaos, observe
from torchdistx_tpu.utils.checkpoint import verify_checkpoint
from torchdistx_tpu.utils.failures import (
    ReplayWindowExceeded,
    run_elastic,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_plan_leaks():
    chaos.clear()
    yield
    chaos.clear()


def _stepf(state, batch):
    return {"x": state["x"] + batch}, {"loss": float(state["x"])}


def _batches(n):
    return [jnp.float32(i) for i in range(1, n + 1)]


def _state():
    return {"x": jnp.float32(0.0)}


def _bits(x):
    return np.asarray(x).tobytes()


def _baseline(n):
    """Fault-free reference run (no checkpointing, same step order)."""
    out, steps, restarts = run_elastic(_stepf, _state(), _batches(n))
    assert (steps, restarts) == (n, 0)
    return out


def _counter(name, **labels):
    return observe.counters().counter(name, **labels).value


class TestFaultPlanGrammar:
    def test_parse_all_kinds(self):
        plan = chaos.parse_plan(
            "step@4=raise; step@3=hang:2 x2; save@2=corrupt:flip;"
            "save@1=slow:0.5; step@5=preempt; restore@2=raise"
        )
        assert len(plan.faults) == 6
        hang = plan.faults[1]
        assert (hang.site, hang.step, hang.kind, hang.arg, hang.count) == (
            "step", 3, "hang", "2", 2
        )

    def test_take_consumes_budget(self):
        plan = chaos.parse_plan("step@3=hang:2 x2")
        assert len(plan.take("step", 3)) == 1
        assert len(plan.take("step", 3)) == 1
        assert plan.take("step", 3) == []  # budget spent
        assert plan.take("save", 3) == []  # site keyed
        assert not plan  # nothing pending
        assert plan.fired == ["step@3=hang:2 x2"] * 2

    @pytest.mark.parametrize("bad", [
        "step@4", "boom@4=raise", "step@4=explode", "step@x=raise",
        "step@4=raise x0",
    ])
    def test_bad_entries_rejected(self, bad):
        with pytest.raises(ValueError):
            chaos.parse_plan(bad)

    def test_install_overrides_config_and_clear(self):
        with tdx_config.override(fault_plan="step@1=raise"):
            installed = chaos.install("step@2=hang")
            assert chaos.active_plan() is installed
            chaos.clear()
            assert chaos.active_plan().faults[0].spec() == "step@1=raise"
        assert chaos.active_plan() is None


class TestRaiseFault:
    def test_survived_with_default_retry_on(self, tmp_path):
        # No retry_on passed: the injected exception must be the REAL
        # XlaRuntimeError shape the default retry set covers.
        chaos.install("step@4=raise")
        before = _counter("tdx.chaos.injected", kind="raise")
        out, steps, restarts = run_elastic(
            _stepf, _state(), _batches(6),
            checkpoint_dir=str(tmp_path), checkpoint_every=2,
            probe_on_restart=False,
        )
        assert (steps, restarts) == (6, 1)
        assert _counter("tdx.chaos.injected", kind="raise") == before + 1
        assert _bits(out["x"]) == _bits(_baseline(6)["x"])

    def test_plan_via_config_env_knob(self, tmp_path):
        with tdx_config.override(fault_plan="step@2=raise"):
            out, steps, restarts = run_elastic(
                _stepf, _state(), _batches(3),
                checkpoint_dir=str(tmp_path), checkpoint_every=1,
                probe_on_restart=False,
            )
        assert (steps, restarts) == (3, 1)
        assert _bits(out["x"]) == _bits(_baseline(3)["x"])


class TestHangFault:
    def test_hang_killed_by_watchdog_then_restart(self, tmp_path):
        chaos.install("step@3=hang:5")
        before = _counter("tdx.elastic.watchdog_kills")
        t0 = time.perf_counter()
        out, steps, restarts = run_elastic(
            _stepf, _state(), _batches(6),
            checkpoint_dir=str(tmp_path), checkpoint_every=2,
            step_deadline=0.5, probe_on_restart=False,
        )
        wall = time.perf_counter() - t0
        assert (steps, restarts) == (6, 1)
        assert _counter("tdx.elastic.watchdog_kills") == before + 1
        # The loop waited out the 0.5 s deadline, not the 5 s hang.
        assert wall < 4.0
        assert _bits(out["x"]) == _bits(_baseline(6)["x"])
        # The abandoned worker's injected hang was cancelled: no thread
        # is left sleeping out the remaining ~4.5 s.
        deadline = time.perf_counter() + 2.0
        while any(t.name.startswith("tdx-step-")
                  for t in __import__("threading").enumerate()):
            assert time.perf_counter() < deadline, "abandoned hang thread leaked"
            time.sleep(0.05)

    @pytest.mark.slow  # multi-second hang injection — chaos-test only
    def test_repeated_hangs_exhaust_then_recover(self, tmp_path):
        # Two consecutive hangs of the same step (x2): two watchdog
        # kills, two restarts, then the spent plan lets the step pass.
        chaos.install("step@3=hang:30 x2")
        before = _counter("tdx.elastic.watchdog_kills")
        out, steps, restarts = run_elastic(
            _stepf, _state(), _batches(4),
            checkpoint_dir=str(tmp_path), checkpoint_every=2,
            step_deadline=1.5, max_restarts=3, probe_on_restart=False,
            backoff_base=0.1,
        )
        assert (steps, restarts) == (4, 2)
        assert _counter("tdx.elastic.watchdog_kills") == before + 2
        assert _bits(out["x"]) == _bits(_baseline(4)["x"])

    def test_watchdog_relays_nonretryable(self, tmp_path):
        def bug(state, batch):
            raise ValueError("a real bug, not a device failure")

        with pytest.raises(ValueError):
            run_elastic(
                bug, _state(), _batches(1),
                checkpoint_dir=str(tmp_path), step_deadline=5.0,
                probe_on_restart=False,
            )


class TestCorruptFault:
    def test_cross_process_resume_falls_back_to_n_minus_1(self, tmp_path):
        # "Process 1": the latest checkpoint (step_4) is damaged
        # post-commit — exactly what a torn write looks like on relaunch.
        chaos.install("save@4=corrupt:truncate")
        out1, steps1, _ = run_elastic(
            _stepf, _state(), _batches(4),
            checkpoint_dir=str(tmp_path), checkpoint_every=2,
            probe_on_restart=False,
        )
        assert steps1 == 4
        assert not verify_checkpoint(tmp_path / "step_4")[0]
        chaos.clear()

        # "Process 2": resume never crashes on the bad dir — it is
        # quarantined and step_2 becomes the restore point.
        before_q = _counter("tdx.ckpt.quarantined")
        out2, steps2, restarts2 = run_elastic(
            _stepf, _state(), _batches(4),
            checkpoint_dir=str(tmp_path), checkpoint_every=2,
            resume=True, probe_on_restart=False,
        )
        assert (steps2, restarts2) == (4, 0)
        assert _counter("tdx.ckpt.quarantined") == before_q + 1
        assert (tmp_path / "step_4.corrupt").is_dir()
        # The replayed step 4 re-saved a fresh, VALID step_4 checkpoint.
        assert verify_checkpoint(tmp_path / "step_4")[0]
        assert _bits(out2["x"]) == _bits(_baseline(4)["x"])

    def test_inprocess_fallback_with_list_batches(self, tmp_path):
        # In-memory batches are randomly addressable, so the in-process
        # restore can rewind past the corrupt step_4 to step_2.
        chaos.install("save@4=corrupt:truncate;step@5=raise")
        out, steps, restarts = run_elastic(
            _stepf, _state(), _batches(6),
            checkpoint_dir=str(tmp_path), checkpoint_every=2,
            probe_on_restart=False,
        )
        assert (steps, restarts) == (6, 1)
        assert (tmp_path / "step_4.corrupt").is_dir()
        assert _bits(out["x"]) == _bits(_baseline(6)["x"])

    def test_restore_site_fault_falls_back_not_crashes(self, tmp_path):
        # A fault injected DURING restore (transport failure model) must
        # be contained by the fallback machinery like a real torn read.
        chaos.install("step@3=raise;restore@2=raise")
        out, steps, restarts = run_elastic(
            _stepf, _state(), _batches(4),
            checkpoint_dir=str(tmp_path), checkpoint_every=2,
            probe_on_restart=False,
        )
        assert (steps, restarts) == (4, 1)
        assert (tmp_path / "step_2.corrupt").is_dir()  # failed-restore policy
        assert _bits(out["x"]) == _bits(_baseline(4)["x"])

    def test_resume_with_all_checkpoints_corrupt_starts_fresh(self, tmp_path):
        run_elastic(
            _stepf, _state(), _batches(2),
            checkpoint_dir=str(tmp_path), checkpoint_every=2,
            probe_on_restart=False,
        )
        for name in ("step_0", "step_2"):
            chaos.corrupt_checkpoint(tmp_path / name, mode="flip")
        out, steps, _ = run_elastic(
            _stepf, _state(), _batches(2),
            checkpoint_dir=str(tmp_path), checkpoint_every=2,
            resume=True, probe_on_restart=False,
        )
        assert steps == 2
        assert (tmp_path / "step_0.corrupt").is_dir()
        assert (tmp_path / "step_2.corrupt").is_dir()
        assert _bits(out["x"]) == _bits(_baseline(2)["x"])


class TestSlowSaveFault:
    def test_slow_save_survived(self, tmp_path):
        chaos.install("save@2=slow:0.3")
        before = _counter("tdx.chaos.injected", kind="slow")
        t0 = time.perf_counter()
        out, steps, restarts = run_elastic(
            _stepf, _state(), _batches(4),
            checkpoint_dir=str(tmp_path), checkpoint_every=2,
            probe_on_restart=False,
        )
        assert (steps, restarts) == (4, 0)
        assert time.perf_counter() - t0 >= 0.3
        assert _counter("tdx.chaos.injected", kind="slow") == before + 1
        assert _bits(out["x"]) == _bits(_baseline(4)["x"])


class TestPreemptFault:
    def test_preempt_drains_then_resume_continues_exact(self, tmp_path):
        chaos.install("step@3=preempt")
        before = _counter("tdx.elastic.drains")
        out1, steps1, restarts1 = run_elastic(
            _stepf, _state(), _batches(6),
            checkpoint_dir=str(tmp_path), checkpoint_every=100,
            probe_on_restart=False,
        )
        # Drained after finishing the step the notice arrived in.
        assert (steps1, restarts1) == (3, 0)
        assert _counter("tdx.elastic.drains") == before + 1
        marker = json.loads((tmp_path / "CLEAN_EXIT.json").read_text())
        assert marker["step"] == 3
        assert verify_checkpoint(tmp_path / "step_3")[0]
        chaos.clear()

        out2, steps2, _ = run_elastic(
            _stepf, _state(), _batches(6),
            checkpoint_dir=str(tmp_path), checkpoint_every=100,
            resume=True, probe_on_restart=False,
        )
        assert steps2 == 6  # continued 4..6; no lost or repeated updates
        assert _bits(out2["x"]) == _bits(_baseline(6)["x"])


_DRAIN_CHILD = """
import os, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from torchdistx_tpu.utils.failures import run_elastic

d = sys.argv[1]

def stepf(state, batch):
    time.sleep(0.15)
    # "started" means the LOOP is running: run_elastic's own set-up
    # (checkpoint library imports, about 1 s on this installation) comes
    # first, and a SIGTERM that lands inside it drains at step 0.
    with open(os.path.join(d, "started"), "w") as f:
        f.write("1")
    return {"x": state["x"] + batch}, {}

batches = [jnp.float32(i) for i in range(1, 41)]
run_elastic(stepf, {"x": jnp.float32(0.0)}, batches,
            checkpoint_dir=d, checkpoint_every=100, exit_on_drain=True)
print("RAN-TO-COMPLETION")  # only reachable if the signal was missed
"""


class TestSigtermDrainExitZero:
    def test_sigterm_exits_zero_and_fresh_process_resumes(self, tmp_path):
        script = tmp_path / "drain_child.py"
        script.write_text(_DRAIN_CHILD)
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", "")}
        proc = subprocess.Popen(
            [sys.executable, str(script), str(tmp_path)],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        try:
            deadline = time.time() + 120
            started = tmp_path / "started"
            while not started.exists():
                assert proc.poll() is None, proc.communicate()[1]
                assert time.time() < deadline, "child never reached the loop"
                time.sleep(0.05)
            time.sleep(0.6)  # a few 0.15 s steps in
            proc.send_signal(signal.SIGTERM)
            out, err = proc.communicate(timeout=60)
        finally:
            if proc.poll() is None:
                proc.kill()
        assert proc.returncode == 0, err
        assert "RAN-TO-COMPLETION" not in out

        marker = json.loads((tmp_path / "CLEAN_EXIT.json").read_text())
        s = marker["step"]
        assert 1 <= s < 40
        ok, reason = verify_checkpoint(tmp_path / f"step_{s}")
        assert ok, reason

        # Fresh process (this one): resume continues at exactly step s.
        out2, steps2, _ = run_elastic(
            _stepf, _state(), _batches(40),
            checkpoint_dir=str(tmp_path), checkpoint_every=100,
            resume=True, probe_on_restart=False,
        )
        assert steps2 == 40
        assert _bits(out2["x"]) == _bits(_baseline(40)["x"])


class TestStreamingReplayWindow:
    def test_streaming_loader_consumed_lazily(self, tmp_path):
        pulled = []

        def gen():
            for i in range(1, 7):
                pulled.append(i)
                yield jnp.float32(i)

        def stepf(state, batch):
            # One batch pulled per executed step — an eagerly
            # materialized iterator would show 6 on the first call.
            assert len(pulled) == int(batch)
            return {"x": state["x"] + batch}, {}

        out, steps, _ = run_elastic(
            stepf, _state(), gen(),
            checkpoint_dir=str(tmp_path), checkpoint_every=2,
            probe_on_restart=False,
        )
        assert steps == 6 and float(out["x"]) == 21.0

    def test_window_exceeded_then_relaunch_contract(self, tmp_path):
        # Streaming input: batches before the newest commit are released,
        # so the in-process fallback past corrupt step_4 must raise the
        # documented contract...
        chaos.install("save@4=corrupt:truncate;step@5=raise")
        with pytest.raises(ReplayWindowExceeded, match="resume=True"):
            run_elastic(
                _stepf, _state(), (b for b in _batches(6)),
                checkpoint_dir=str(tmp_path), checkpoint_every=2,
                probe_on_restart=False,
            )
        assert (tmp_path / "step_4.corrupt").is_dir()
        chaos.clear()

        # ... and the relaunch (fresh process, fresh iterator) resumes
        # from step_2 and completes bit-exactly.
        out, steps, _ = run_elastic(
            _stepf, _state(), (b for b in _batches(6)),
            checkpoint_dir=str(tmp_path), checkpoint_every=2,
            resume=True, probe_on_restart=False,
        )
        assert steps == 6
        assert _bits(out["x"]) == _bits(_baseline(6)["x"])

    def test_streaming_recovery_within_window(self, tmp_path):
        # A plain failure replays only batches since the last commit —
        # inside the window, streaming recovers in-process.
        chaos.install("step@5=raise")
        out, steps, restarts = run_elastic(
            _stepf, _state(), (b for b in _batches(6)),
            checkpoint_dir=str(tmp_path), checkpoint_every=2,
            probe_on_restart=False,
        )
        assert (steps, restarts) == (6, 1)
        assert _bits(out["x"]) == _bits(_baseline(6)["x"])


class TestTrainElastic:
    def test_real_train_step_recovers_from_injected_failure(self, tmp_path):
        from torchdistx_tpu.models import TINY, make_llama
        from torchdistx_tpu.parallel import make_mesh
        from torchdistx_tpu.parallel.train import train_elastic

        import jax

        mesh = make_mesh({"dp": 8})
        model = make_llama(TINY)
        key = jax.random.PRNGKey(0)
        toks = [
            jax.random.randint(jax.random.fold_in(key, i), (8, 16), 0,
                               TINY.vocab_size)
            for i in range(3)
        ]
        params = model.init(jax.random.PRNGKey(1), toks[0])

        chaos.install("step@2=raise")
        losses = []
        state, steps, restarts = train_elastic(
            model, TINY, mesh, params, toks,
            checkpoint_dir=str(tmp_path), checkpoint_every=1,
            probe_on_restart=False,
            on_metrics=lambda s, m: losses.append(float(m["loss"])),
        )
        assert (steps, restarts) == (3, 1)
        assert int(state["step"]) == 3  # optimizer state tracked the replay
        assert all(np.isfinite(loss) for loss in losses)
        assert verify_checkpoint(tmp_path / "step_3")[0]


class TestTraceSummaryVisibility:
    def test_quarantine_counters_reach_tdx_trace_summary(self, tmp_path):
        trace_dir = tmp_path / "traces"
        observe.reset()
        with tdx_config.override(trace_dir=str(trace_dir)):
            chaos.install("save@2=corrupt:truncate")
            run_elastic(
                _stepf, _state(), _batches(2),
                checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=2,
                probe_on_restart=False,
            )
            chaos.clear()
            out, steps, _ = run_elastic(
                _stepf, _state(), _batches(2),
                checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=2,
                resume=True, probe_on_restart=False,
            )
            assert steps == 2
            observe.flush(trace_dir=str(trace_dir))

        res = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "tdx_trace.py"),
             "summary", str(trace_dir)],
            capture_output=True, text=True,
        )
        assert res.returncode == 0, res.stderr
        rob = [ln for ln in res.stdout.splitlines() if ln.startswith("robustness:")]
        assert rob, res.stdout
        assert "ckpt verify failures=1" in rob[0]
        assert "ckpt quarantined=1" in rob[0]
        assert "chaos injected=1" in rob[0]
