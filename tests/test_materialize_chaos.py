"""Materialization chaos suite: every fault kind at every pipeline site
(``lower`` / ``compile`` / ``execute`` / ``cache``) is injected
deterministically and SURVIVED by the self-healing materializer, with
final parameters bitwise-equal to the fault-free run, in both engine
modes; the compile watchdog abandons hung stages within the deadline;
corrupt persistent-cache entries are quarantined and recompiled; and an
interrupted materialization resumes, skipping committed groups.  See
docs/robustness.md for the failure model."""

import json
import os
import threading
import time

import numpy as np
import pytest
import torch

import torchdistx_tpu.config as tdx_config
from torchdistx_tpu import chaos, observe
from torchdistx_tpu.deferred_init import deferred_init
from torchdistx_tpu.jax_bridge import (
    MaterializationError,
    materialize_module_jax,
)
from torchdistx_tpu import compile_service
from torchdistx_tpu.jax_bridge import materialize as mat

SITES = ("lower", "compile", "execute", "cache")
KIND_ARGS = {"raise": "", "hang": ":30", "slow": ":0.1",
             "corrupt": ":truncate"}


class Hetero(torch.nn.Module):
    """Distinct layer widths → every chain its own structural group, well
    above the pipeline node threshold (the same shape as the pipeline
    suite's model, kept small so the chaos matrix stays fast)."""

    def __init__(self, k: int = 10):
        super().__init__()
        w = [16 + 8 * i for i in range(k)]
        self.layers = torch.nn.ModuleList(
            torch.nn.Linear(w[i], w[(i + 1) % k]) for i in range(k)
        )


@pytest.fixture(autouse=True)
def _no_plan_or_cache_leaks():
    chaos.clear()
    compile_service.reset_cache_binding()
    yield
    chaos.clear()
    compile_service.reset_cache_binding()


@pytest.fixture(scope="module")
def baseline():
    """Fault-free off-mode reference parameters (the parity oracle both
    engines already pin against each other)."""
    with tdx_config.override(materialize_pipeline="off"):
        m = deferred_init(Hetero)
        params = materialize_module_jax(m, seed=0)
    return {k: np.asarray(v) for k, v in params.items()}


def _materialize(mode, *, workers=1, cache_dir=None, resume_dir=None,
                 deadline=None, retries=2, seed=0, module=None):
    with tdx_config.override(
        materialize_pipeline=mode, compile_workers=workers,
        cache_dir=cache_dir, materialize_resume_dir=resume_dir,
        compile_deadline_s=deadline or 0.0, materialize_retries=retries,
    ):
        m = module if module is not None else deferred_init(Hetero)
        params = materialize_module_jax(m, seed=seed)
    return {k: np.asarray(v) for k, v in params.items()}, mat.last_run_stats()


def _assert_bitwise(got, want):
    assert set(got) == set(want)
    for k in want:
        assert got[k].dtype == want[k].dtype, k
        assert np.array_equal(got[k], want[k]), f"{k} differs from fault-free"


def _counter(name, **labels):
    return observe.counters().counter(name, **labels).value


def _no_leaked_watchdog_threads():
    # Abandoned stage threads must wake on the cancel event and exit,
    # not sleep out an injected hang's full argument.
    deadline = time.perf_counter() + 3.0
    while any(t.name.startswith("tdx-mat-") for t in threading.enumerate()):
        assert time.perf_counter() < deadline, "abandoned stage thread leaked"
        time.sleep(0.05)


@pytest.fixture()
def fresh_cache(tmp_path, monkeypatch):
    """A fresh persistent compile cache (min compile time 0 so every
    program persists — corruption faults need real entries to damage)."""
    monkeypatch.setenv("TDX_CACHE_MIN_COMPILE_S", "0")
    cache = tmp_path / "xla_cache"
    cache.mkdir()
    return str(cache)


class TestEverySiteEveryKind:
    """The acceptance matrix: site × kind → survived, bitwise-equal, in
    both engine modes.  Group-1 faults cover both engines (the monolith
    IS group 1); workers=1 keeps the injection order deterministic."""

    @pytest.mark.parametrize("mode", ["off", "auto"])
    @pytest.mark.parametrize("site", SITES)
    @pytest.mark.parametrize("kind", ["raise", "hang", "slow", "corrupt"])
    def test_fault_survived_bitwise(self, mode, site, kind, fresh_cache,
                                    baseline):
        if kind == "corrupt":
            # Cache corruption needs committed entries: warm first.
            _materialize(mode, cache_dir=fresh_cache)
            compile_service.reset_cache_binding()
            before_q = _counter("tdx.jax.cache_quarantined")
        # The deadline must beat the injected 30 s hang while clearing a
        # LEGITIMATE monolith compile on a slow 1-core CI box.
        deadline = 4.0 if kind == "hang" else None
        before_inj = _counter("tdx.chaos.injected", kind=kind)
        chaos.install(f"{site}@1={kind}{KIND_ARGS[kind]}")
        params, st = _materialize(
            mode, cache_dir=fresh_cache, deadline=deadline
        )
        assert st["mode"] == ("monolithic" if mode == "off" else "pipelined")
        assert _counter("tdx.chaos.injected", kind=kind) == before_inj + 1
        if kind == "corrupt":
            if mode == "off" and site == "execute":
                # The monolith's only cache load precedes the execute
                # site: the damage lands on disk unread.  The NEXT cold
                # start must quarantine it and still heal.
                compile_service.reset_cache_binding()
                params2, _ = _materialize(mode, cache_dir=fresh_cache)
                _assert_bitwise(params2, baseline)
            assert _counter("tdx.jax.cache_quarantined") > before_q
        if kind == "hang":
            _no_leaked_watchdog_threads()
        _assert_bitwise(params, baseline)


class TestWatchdog:
    def test_hung_compile_abandoned_within_deadline(self, baseline):
        chaos.install("compile@1=hang:30")
        before = _counter("tdx.jax.compile_watchdog_kills")
        t0 = time.perf_counter()
        params, _ = _materialize("auto", deadline=1.0)
        wall = time.perf_counter() - t0
        # The ladder waited out the 1 s deadline (+ retry), not the 30 s
        # injected hang.
        assert wall < 20.0
        # >= rather than ==: on the 1-core CI box a legitimately slow
        # RETRY compile can also trip the 1 s deadline and count a
        # second kill (observed flaking at full-suite load); the
        # contract under test is "the hang was abandoned, counted, and
        # the run recovered", not "exactly one stage was ever slow".
        assert _counter("tdx.jax.compile_watchdog_kills") >= before + 1
        _assert_bitwise(params, baseline)
        _no_leaked_watchdog_threads()

    def test_retries_counted(self, baseline):
        chaos.install("compile@1=raise")
        before = _counter("tdx.jax.compile_retries")
        params, _ = _materialize("auto")
        assert _counter("tdx.jax.compile_retries") == before + 1
        _assert_bitwise(params, baseline)


class TestCacheQuarantine:
    def test_corrupt_entries_quarantined_recompiled_and_reusable(
        self, fresh_cache, baseline
    ):
        _, st = _materialize("auto", cache_dir=fresh_cache)
        n = st["n_programs"]
        assert n >= 2
        entries = [f for f in os.listdir(fresh_cache)
                   if f.endswith("-cache")]
        assert entries
        compile_service.reset_cache_binding()

        # Damage every entry on disk (the poisoned-cache model), no
        # chaos plan involved: the quarantine guard alone must recover.
        chaos.corrupt_cache_dir(fresh_cache, mode="truncate")
        before_q = _counter("tdx.jax.cache_quarantined")
        params, st2 = _materialize("auto", cache_dir=fresh_cache)
        assert _counter("tdx.jax.cache_quarantined") >= before_q + len(entries)
        assert "hit" not in st2["cache"] or \
            st2["cache"].get("hit", 0) < n  # corrupt entries can't all hit
        corrupt = [f for f in os.listdir(fresh_cache)
                   if f.endswith(".corrupt")]
        assert len(corrupt) >= len(entries)  # forensics kept
        _assert_bitwise(params, baseline)
        compile_service.reset_cache_binding()

        # The recompiles re-persisted clean entries: the next cold start
        # is all-hit again — the cache healed, not just survived.
        _, st3 = _materialize("auto", cache_dir=fresh_cache)
        assert st3["cache"] == {"hit": n}


class TestDegradationLadder:
    def test_exhausted_group_falls_back_to_monolith(self, baseline):
        # Group 2's execute fails more times than the ladder retries:
        # the pipelined engine gives up and the monolithic off-mode
        # program (bitwise-identical by construction) delivers.
        chaos.install("execute@2=raise x9")
        before = _counter("tdx.jax.pipeline_fallbacks")
        params, st = _materialize("auto", retries=1)
        assert _counter("tdx.jax.pipeline_fallbacks") == before + 1
        assert st["mode"] == "monolithic"  # the fallback ran last
        _assert_bitwise(params, baseline)

    def test_off_mode_exhaustion_raises_typed_error(self):
        chaos.install("compile@1=raise x9")
        with pytest.raises(MaterializationError) as ei:
            _materialize("off", retries=1)
        assert ei.value.failed_groups == [0]
        assert not ei.value.drained

    def test_nonretryable_error_fails_fast(self):
        # A corrupt fault with no cache dir bound is a plan bug
        # (ValueError), not a device failure: no retry, no fallback.
        chaos.install("lower@1=corrupt")
        with pytest.raises(ValueError, match="corrupt fault"):
            _materialize("auto", retries=2)


class TestPartialProgressResume:
    def _drain(self, module, resume_dir, plan="compile@3=preempt;compile@3=slow:1.0"):
        """Interrupt a pipelined materialization at group 3 via SIGTERM:
        groups 1-2 commit, the drain stops dispatch and leaves the
        progress manifest."""
        chaos.install(plan)
        with pytest.raises(MaterializationError) as ei:
            _materialize("auto", resume_dir=resume_dir, module=module)
        chaos.clear()
        assert ei.value.drained and ei.value.resumable
        assert ei.value.completed_groups  # something was committed
        return ei.value

    def test_sigterm_drain_then_resume_skips_committed_groups(
        self, tmp_path, baseline
    ):
        rdir = str(tmp_path / "resume")
        module = deferred_init(Hetero)
        err = self._drain(module, rdir)
        manifest = json.load(open(os.path.join(
            rdir, "MATERIALIZE_PROGRESS.json")))
        assert len(manifest["groups"]) == len(err.completed_groups)

        before = _counter("tdx.jax.groups_resumed")
        params, st = _materialize("auto", resume_dir=rdir, module=module)
        resumed = _counter("tdx.jax.groups_resumed") - before
        assert resumed == len(err.completed_groups) >= 1
        assert st["cache"].get("resumed") == resumed
        _assert_bitwise(params, baseline)
        # Success spends the progress state: nothing stale to resume.
        assert not os.path.exists(os.path.join(
            rdir, "MATERIALIZE_PROGRESS.json"))

    def test_corrupt_progress_payload_is_recomputed_not_trusted(
        self, tmp_path, baseline
    ):
        rdir = str(tmp_path / "resume")
        module = deferred_init(Hetero)
        err = self._drain(module, rdir)
        manifest = json.load(open(os.path.join(
            rdir, "MATERIALIZE_PROGRESS.json")))
        fp, rec = next(iter(manifest["groups"].items()))
        victim = os.path.join(rdir, fp, rec["outputs"][0]["file"])
        with open(victim, "r+b") as f:
            data = bytearray(f.read())
            data[0] ^= 0xFF
            f.seek(0)
            f.write(data)

        before = _counter("tdx.jax.groups_resumed")
        params, _ = _materialize("auto", resume_dir=rdir, module=module)
        # The damaged group was recomputed; any intact ones resumed.
        assert _counter("tdx.jax.groups_resumed") - before \
            == len(err.completed_groups) - 1
        _assert_bitwise(params, baseline)

    def test_stale_manifest_for_other_model_ignored(self, tmp_path, baseline):
        # NB: the other model's widths must not overlap Hetero's — a
        # deeper Hetero records IDENTICAL chains (same shapes, same
        # key_nrs) for its first layers, which the fingerprint rightly
        # treats as safely resumable.
        class Other(torch.nn.Module):
            def __init__(self, k: int = 10):
                super().__init__()
                w = [20 + 8 * i for i in range(k)]
                self.layers = torch.nn.ModuleList(
                    torch.nn.Linear(w[i], w[(i + 1) % k]) for i in range(k)
                )

        rdir = str(tmp_path / "resume")
        other = deferred_init(Other)
        self._drain(other, rdir)

        before = _counter("tdx.jax.groups_resumed")
        params, _ = _materialize("auto", resume_dir=rdir)
        assert _counter("tdx.jax.groups_resumed") == before  # nothing matched
        _assert_bitwise(params, baseline)
