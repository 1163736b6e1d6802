"""The overlapped materialization engine (docs/performance.md).

Covers the program split (partition properties, determinism), bitwise
parity of pipelined vs monolithic materialization across seeds /
param_dtype policies / mesh+plan shardings, EXACT compile-cache hit/miss
counters under TDX_COMPILE_WORKERS>1, the engine-selection knobs, and the
``tools/warm_cache.py`` warm→hit round trip.
"""

from __future__ import annotations

import importlib.util
import os
import sys

import numpy as np
import pytest
import torch

import torchdistx_tpu.config as tdx_config
from torchdistx_tpu import observe
from torchdistx_tpu.deferred_init import deferred_init
from torchdistx_tpu.jax_bridge import materialize_module_jax
from torchdistx_tpu import compile_service
from torchdistx_tpu.jax_bridge import materialize as mat
from torchdistx_tpu.jax_bridge.compile import split_init_groups
from torchdistx_tpu.jax_bridge.materialize import named_fake_tensors

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Hetero(torch.nn.Module):
    """Distinct layer widths → every chain its own structural group (no
    instance batching), comfortably above the pipeline node threshold."""

    def __init__(self, k: int = 12):
        super().__init__()
        w = [16 + 8 * i for i in range(k)]
        self.emb = torch.nn.Embedding(50, 16)
        self.layers = torch.nn.ModuleList(
            torch.nn.Linear(w[i], w[(i + 1) % k]) for i in range(k)
        )
        self.ln = torch.nn.LayerNorm(w[0])


class Repeated(torch.nn.Module):
    """Identical layers → instance batching applies inside groups."""

    def __init__(self, k: int = 10):
        super().__init__()
        self.layers = torch.nn.ModuleList(
            torch.nn.Linear(24, 24) for _ in range(k)
        )


def _materialize(model_cls, mode, *, seed=0, workers=3, mesh=None,
                 plan=None, param_dtype=None):
    with tdx_config.override(
        materialize_pipeline=mode, compile_workers=workers
    ):
        m = deferred_init(model_cls)
        params = materialize_module_jax(
            m, mesh=mesh, plan=plan, seed=seed, param_dtype=param_dtype
        )
    return {k: np.asarray(v) for k, v in params.items()}, mat.last_run_stats()


def _assert_bitwise(a, b):
    assert set(a) == set(b)
    for k in a:
        assert a[k].dtype == b[k].dtype, k
        assert np.array_equal(a[k], b[k]), f"{k} differs between engines"


class TestSplitGroups:
    def test_partition_properties(self):
        m = deferred_init(Hetero)
        fakes = list(named_fake_tensors(m).values())
        bins = split_init_groups(fakes, max_programs=8)
        flat = sorted(i for b in bins for i in b)
        assert flat == list(range(len(fakes)))  # disjoint and covering
        assert 2 <= len(bins) <= 8
        assert all(b == sorted(b) for b in bins)

    def test_deterministic(self):
        m = deferred_init(Hetero)
        fakes = list(named_fake_tensors(m).values())
        assert split_init_groups(fakes, max_programs=6) == \
            split_init_groups(fakes, max_programs=6)

    def test_max_programs_bound(self):
        m = deferred_init(Hetero)
        fakes = list(named_fake_tensors(m).values())
        assert len(split_init_groups(fakes, max_programs=3)) <= 3
        # One bin per structural group at most, however high the cap.
        many = split_init_groups(fakes, max_programs=10_000)
        assert len(many) <= len(fakes)

    def test_repeated_structures_stay_grouped(self):
        # 10 identical layers = 2 structural groups (weight, bias): the
        # split must keep instances together so scan batching survives.
        m = deferred_init(Repeated)
        fakes = list(named_fake_tensors(m).values())
        assert len(split_init_groups(fakes, max_programs=16)) <= 2


class TestParity:
    @pytest.mark.parametrize("seed", [0, 7])
    def test_bitwise_across_seeds(self, seed):
        off, st_off = _materialize(Hetero, "off", seed=seed)
        auto, st_auto = _materialize(Hetero, "auto", seed=seed)
        assert st_off["mode"] == "monolithic"
        assert st_auto["mode"] == "pipelined" and st_auto["n_programs"] >= 2
        _assert_bitwise(off, auto)

    def test_bitwise_param_dtype_policy(self):
        import jax.numpy as jnp

        off, _ = _materialize(Hetero, "off", param_dtype=jnp.bfloat16)
        auto, _ = _materialize(Hetero, "auto", param_dtype=jnp.bfloat16)
        _assert_bitwise(off, auto)
        assert all(v.dtype == jnp.bfloat16 for v in auto.values())

    def test_bitwise_sharded(self, ):
        from torchdistx_tpu.parallel import fsdp_plan, make_mesh

        mesh = make_mesh({"fsdp": 4, "tp": 2})
        plan = fsdp_plan(min_size=128)
        off, _ = _materialize(Hetero, "off", mesh=mesh, plan=plan)

        # Re-materialize pipelined and check values AND placements.
        with tdx_config.override(
            materialize_pipeline="auto", compile_workers=3
        ):
            m = deferred_init(Hetero)
            params = materialize_module_jax(m, mesh=mesh, plan=plan, seed=0)
        assert mat.last_run_stats()["mode"] == "pipelined"
        fakes = named_fake_tensors(m)
        for name, v in params.items():
            want = plan.sharding_for(name, tuple(fakes[name].shape), mesh)
            assert v.sharding == want, name
        _assert_bitwise(off, {k: np.asarray(v) for k, v in params.items()})

    def test_batched_model_parity(self):
        off, _ = _materialize(Repeated, "off")
        auto, st = _materialize(Repeated, "auto")
        # 2 structural groups but >= MIN_NODES nodes: pipelined w/ 2 bins.
        assert st["mode"] == "pipelined"
        _assert_bitwise(off, auto)


@pytest.fixture()
def telemetry():
    observe.reset()
    observe.enable(True)
    try:
        yield observe
    finally:
        observe.enable(None)
        observe.reset()


@pytest.fixture()
def fresh_cache(tmp_path, monkeypatch, telemetry):
    """A fresh persistent compile cache bound for the test (min compile
    time 0 so every miss persists and the warm rerun hits), unlatched
    before and after so neighboring tests keep their own binding."""
    import jax

    monkeypatch.setenv("TDX_CACHE_MIN_COMPILE_S", "0")
    compile_service.reset_cache_binding()
    prev_dir = getattr(jax.config, "jax_compilation_cache_dir", None)
    cache = tmp_path / "xla_cache"
    cache.mkdir()
    yield str(cache)
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    compile_service.reset_cache_binding()


def _counter_snapshot():
    return {r["name"]: r.get("value") for r in observe.counters().snapshot()}


class TestExactCacheCounters:
    def test_miss_then_hit_exact_under_workers(self, fresh_cache):
        with tdx_config.override(cache_dir=fresh_cache):
            _, st = _materialize(Hetero, "auto", workers=4)
        assert st["mode"] == "pipelined"
        n = st["n_programs"]
        assert n >= 2 and st["workers"] == 4
        snap = _counter_snapshot()
        # EXACT: one miss per program, zero hits — even with 4 concurrent
        # compiles (the outcome oracle is jax's monitoring stream,
        # attributed per compiling thread, not directory differencing).
        assert snap.get("tdx.jax.compile_cache_miss") == n
        assert "tdx.jax.compile_cache_hit" not in snap
        assert st["cache"] == {"miss": n}

        with tdx_config.override(cache_dir=fresh_cache):
            _, st2 = _materialize(Hetero, "auto", workers=4)
        snap = _counter_snapshot()
        assert st2["cache"] == {"hit": n}
        assert snap.get("tdx.jax.compile_cache_miss") == n  # unchanged
        assert snap.get("tdx.jax.compile_cache_hit") == n

    def test_uncached_without_cache_dir(self, telemetry):
        with tdx_config.override(cache_dir=None):
            _, st = _materialize(Hetero, "auto", workers=2)
        assert list(st["cache"]) == ["uncached"]

    def test_pipeline_spans_and_overlap_gauge(self, fresh_cache):
        with tdx_config.override(cache_dir=fresh_cache):
            _materialize(Hetero, "auto", workers=2)
        events = [e for e in observe.tracer().events if e["ph"] == "X"]
        names = {e["name"] for e in events}
        assert {"jax.pipeline", "jax.pipeline.group", "jax.lower",
                "jax.compile", "jax.execute", "jax.materialize"} <= names
        groups = {e["args"]["group"] for e in events
                  if e["name"] == "jax.pipeline.group"}
        assert len(groups) >= 2
        snap = _counter_snapshot()
        assert snap.get("tdx.jax.pipeline_overlap", 0) > 0


class TestKnobs:
    def test_off_forces_monolith(self):
        _, st = _materialize(Hetero, "off")
        assert st["mode"] == "monolithic" and st["n_programs"] == 1

    def test_small_model_falls_back(self):
        with tdx_config.override(materialize_pipeline="auto"):
            m = deferred_init(torch.nn.Linear, 16, 8)
            materialize_module_jax(m, seed=0)
        assert mat.last_run_stats()["mode"] == "monolithic"

    def test_bogus_mode_rejected(self):
        with tdx_config.override(materialize_pipeline="fast"):
            m = deferred_init(torch.nn.Linear, 8, 8)
            with pytest.raises(ValueError, match="TDX_MATERIALIZE_PIPELINE"):
                materialize_module_jax(m, seed=0)

    def test_env_resolution(self, monkeypatch):
        monkeypatch.setenv("TDX_MATERIALIZE_PIPELINE", "off")
        monkeypatch.setenv("TDX_COMPILE_WORKERS", "7")
        cfg = tdx_config._from_env()
        assert cfg.materialize_pipeline == "off"
        assert cfg.compile_workers == 7

    def test_override_scope_reaches_workers(self, tmp_path):
        # Per-scope activation (tdx_config.override(trace_dir=...)) is
        # thread-local; the engine must carry the caller's effective
        # config onto its compile workers, or worker-side spans and the
        # exact cache counters would silently vanish — and tracing-time
        # knobs like rng_chunk_elems would diverge between engines.
        observe.reset()
        try:
            with tdx_config.override(
                trace_dir=str(tmp_path), materialize_pipeline="auto",
                compile_workers=3,
            ):
                m = deferred_init(Hetero)
                materialize_module_jax(m, seed=0)
            assert mat.last_run_stats()["mode"] == "pipelined"
            names = {e["name"] for e in observe.tracer().events
                     if e["ph"] == "X"}
            # Worker-thread spans made it into the trace.
            assert {"jax.pipeline.group", "jax.lower", "jax.compile"} <= names
            snap = _counter_snapshot()
            n = mat.last_run_stats()["n_programs"]
            outcome_total = sum(
                v for k, v in snap.items()
                if k.startswith("tdx.jax.compile_cache_")
            )
            assert outcome_total == n  # exact, none dropped
        finally:
            observe.reset()

    def test_tensor_entry_point_instrumented(self, telemetry):
        from torchdistx_tpu.jax_bridge import materialize_tensor_jax

        t = deferred_init(torch.nn.Linear, 6, 4).weight
        v = materialize_tensor_jax(t, seed=0)
        assert v.shape == (4, 6)
        names = [e["name"] for e in observe.tracer().events
                 if e["ph"] == "X"]
        assert "jax.materialize" in names
        snap = _counter_snapshot()
        assert snap.get("tdx.jax.bytes_materialized", 0) >= 4 * 6 * 4
        assert snap.get("tdx.jax.materialize_gbps", 0) > 0


class TestWarmCacheTool:
    def _load_tool(self):
        spec = importlib.util.spec_from_file_location(
            "warm_cache", os.path.join(REPO, "tools", "warm_cache.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_warm_then_both_engines_hit(self, fresh_cache):
        wc = self._load_tool()
        summary = wc.warm(wc._demo_model, fresh_cache)
        assert summary["programs"] >= 3  # whole-model + per-group set
        assert summary["cache_entries"] > 0

        for mode, want_programs in (("auto", None), ("off", 1)):
            compile_service.reset_cache_binding()
            with tdx_config.override(cache_dir=fresh_cache):
                _, st = _materialize(wc._demo_model, mode, workers=4)
            outcomes = st["cache"]
            assert list(outcomes) == ["hit"], (mode, outcomes)
            if want_programs is not None:
                assert outcomes["hit"] == want_programs

    def test_cli_demo_model(self, fresh_cache, capsys):
        import json

        wc = self._load_tool()
        wc.main(["--model", "demo", "--cache-dir", fresh_cache,
                 "--skip-whole"])
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert out["programs"] >= 2 and out["cache_entries"] > 0

    def test_unwritable_cache_dir_fails_loudly(self, tmp_path, telemetry):
        # jax degrades cache-WRITE errors to warnings, so without the
        # upfront probe the tool would burn the compile budget and then
        # claim success having warmed nothing.  A path that is a regular
        # file is unwritable-as-a-directory for any uid (root included).
        wc = self._load_tool()
        not_a_dir = tmp_path / "cache_file"
        not_a_dir.write_text("occupied")
        with pytest.raises(OSError, match="not writable"):
            wc.warm(wc._demo_model, str(not_a_dir))
        # The failed warm must not leave a cache binding behind: a later
        # materialize with no cache configured reports uncached.
        with tdx_config.override(cache_dir=None):
            _, st = _materialize(wc._demo_model, "off")
        assert list(st["cache"]) == ["uncached"]

    def test_interrupted_warm_leaves_cache_usable(self, fresh_cache,
                                                  monkeypatch):
        # Interrupt the warm after the whole-model program but before the
        # per-group set: the partial cache must stay USABLE — each entry
        # commits independently, so a torn warm is "fewer hits", never a
        # poisoned dir that later compiles trip over.
        from torchdistx_tpu.registry import scheduler as sched

        wc = self._load_tool()

        def boom(*a, **k):
            raise RuntimeError("interrupted warm (injected)")

        monkeypatch.setattr(sched, "plan_group_specs", boom)
        with pytest.raises(RuntimeError, match="interrupted warm"):
            wc.warm(wc._demo_model, fresh_cache)
        monkeypatch.undo()
        assert len(os.listdir(fresh_cache)) >= 1  # the whole-model entry

        # The partial cache serves what it has: off-mode (the program the
        # interrupted warm DID commit) all-hits...
        compile_service.reset_cache_binding()
        with tdx_config.override(cache_dir=fresh_cache):
            _, st = _materialize(wc._demo_model, "off", workers=2)
        assert st["cache"] == {"hit": 1}

        # ...and a rerun of the warm completes the set — no quarantines,
        # no stale junk in the way — after which both engines all-hit.
        summary = wc.warm(wc._demo_model, fresh_cache)
        assert summary["programs"] >= 3
        for mode in ("auto", "off"):
            compile_service.reset_cache_binding()
            with tdx_config.override(cache_dir=fresh_cache):
                _, st = _materialize(wc._demo_model, mode, workers=2)
            assert list(st["cache"]) == ["hit"], (mode, st["cache"])
