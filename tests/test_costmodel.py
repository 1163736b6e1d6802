"""XLA device accounting (torchdistx_tpu.observe.costmodel): compiler
cost/memory probes, the link-bandwidth probe, cost attachment to
``jax.compile`` spans / run stats / the registry manifest, the
``tdx.jax.link_utilization`` and HBM high-water gauges, and the
compiler-derived MFU provenance in StepMeter and the train loop."""

from __future__ import annotations

import glob
import json
import os

import pytest

import torchdistx_tpu.config as tdx_config
from torchdistx_tpu import observe
from torchdistx_tpu.observe import costmodel


@pytest.fixture()
def telemetry():
    observe.reset()
    observe.enable(True)
    try:
        yield observe
    finally:
        observe.enable(None)
        observe.reset()


class TestProgramCosts:
    def test_costs_of_tiny_program(self):
        import jax
        import jax.numpy as jnp

        compiled = jax.jit(
            lambda a: (a @ a).sum()
        ).lower(jnp.ones((32, 32), jnp.float32)).compile()
        costs = costmodel.program_costs(compiled)
        assert costs is not None
        # 32³ MACs × 2 ≈ 65k flops, plus the reduction.
        assert costs["flops"] >= 2 * 32 * 32 * 32
        assert costs["argument_bytes"] == 32 * 32 * 4
        assert costs["peak_bytes"] > 0

    def test_probe_failure_degrades_to_none(self):
        class Broken:
            def cost_analysis(self):
                raise RuntimeError("no")

            def memory_analysis(self):
                raise AttributeError("no")

        assert costmodel.program_costs(Broken()) is None

    def test_list_and_dict_analysis_shapes(self):
        class ListShape:
            def cost_analysis(self):
                return [{"flops": 10.0, "bytes accessed": 4.0}]

            def memory_analysis(self):
                return None

        costs = costmodel.program_costs(ListShape())
        assert costs == {"flops": 10.0, "bytes_accessed": 4.0}

    def test_mfu_helper(self):
        assert costmodel.mfu(1e12, 1.0, 100.0) == pytest.approx(0.01)
        assert costmodel.mfu(0, 1.0, 100.0) is None
        assert costmodel.mfu(1e12, 1.0, None) is None


class TestLinkProbe:
    def test_measures_and_caches(self):
        costmodel.reset_link_probe()
        bw = costmodel.link_bandwidth_gbps(probe_mb=4)
        assert bw is not None and bw > 0
        assert costmodel.link_bandwidth_gbps() == bw  # cached

    def test_hbm_high_water_is_monotone(self, telemetry):
        costmodel.reset_link_probe()
        costmodel.note_program_memory({"peak_bytes": 100.0})
        costmodel.note_program_memory({"peak_bytes": 50.0})
        snap = {r["name"]: r["value"] for r in observe.counters().snapshot()}
        assert snap["tdx.jax.hbm_high_water_bytes"] == 100.0
        costmodel.note_program_memory({"peak_bytes": 300.0})
        snap = {r["name"]: r["value"] for r in observe.counters().snapshot()}
        assert snap["tdx.jax.hbm_high_water_bytes"] == 300.0


class TestMaterializeAccounting:
    def test_spans_stats_and_gauges(self, telemetry):
        import torch

        from torchdistx_tpu.deferred_init import deferred_init
        from torchdistx_tpu.jax_bridge import materialize_module_jax
        from torchdistx_tpu.jax_bridge import materialize as mat

        # Warm the link probe first: inside a span/timed region the
        # engine reads it cached-only (probing there would skew the
        # numbers it contextualizes).
        assert costmodel.link_bandwidth_gbps() > 0
        params = materialize_module_jax(deferred_init(torch.nn.Linear, 16, 8))
        assert set(params) == {"weight", "bias"}
        stats = mat.last_run_stats()
        assert stats.get("xla_flops", 0) > 0
        assert stats.get("xla_peak_bytes", 0) > 0
        (csp,) = [e for e in observe.tracer().events
                  if e["ph"] == "X" and e["name"] == "jax.compile"]
        assert csp["args"]["xla_flops"] > 0
        assert csp["args"]["xla_peak_bytes"] > 0
        snap = {r["name"]: r.get("value") for r in observe.counters().snapshot()}
        assert snap.get("tdx.jax.link_bandwidth_gbps", 0) > 0
        assert 0 < snap.get("tdx.jax.link_utilization", 0)
        assert snap.get("tdx.jax.hbm_high_water_bytes", 0) > 0
        (msp,) = [e for e in observe.tracer().events
                  if e["ph"] == "X" and e["name"] == "jax.materialize"]
        assert msp["args"]["link_utilization"] > 0

    def test_registry_manifest_carries_costs(self, telemetry, tmp_path,
                                             monkeypatch):
        import torch

        from torchdistx_tpu.deferred_init import deferred_init
        from torchdistx_tpu.jax_bridge import materialize_module_jax
        from torchdistx_tpu import compile_service

        monkeypatch.setenv("TDX_CACHE_MIN_COMPILE_S", "0")
        compile_service.reset_cache_binding()
        cache = tmp_path / "cache"
        reg = tmp_path / "registry"
        try:
            with tdx_config.override(cache_dir=str(cache),
                                     registry_dir=str(reg)):
                materialize_module_jax(deferred_init(torch.nn.Linear, 16, 8))
        finally:
            compile_service.reset_cache_binding()
        metas = glob.glob(str(reg / "*" / "meta.json"))
        assert metas, list(reg.iterdir())
        doc = json.load(open(metas[0]))
        assert doc["xla_costs"]["flops"] > 0
        assert doc["xla_costs"]["peak_bytes"] > 0


class TestMfuProvenance:
    def test_stepmeter_gauge_name_declares_source(self, telemetry):
        m = observe.StepMeter(flops_per_step=1e9, peak_tflops=100.0,
                              flops_source="xla")
        m.start()
        m.stop()
        snap = {r["name"] for r in observe.counters().snapshot()}
        assert "tdx.train.mfu" in snap
        assert "tdx.train.mfu_est" not in snap
        observe.reset()
        m2 = observe.StepMeter(flops_per_step=1e9, peak_tflops=100.0)
        m2.start()
        m2.stop()
        snap = {r["name"] for r in observe.counters().snapshot()}
        assert "tdx.train.mfu_est" in snap
        assert "tdx.train.mfu" not in snap

    def test_downgrade_poisons_stale_measured_gauge(self, telemetry):
        import math

        m = observe.StepMeter(flops_per_step=1e9, peak_tflops=100.0,
                              flops_source="xla")
        m.start()
        m.stop()
        g = {r["name"]: r["value"] for r in observe.counters().snapshot()}
        assert g["tdx.train.mfu"] > 0
        # Mid-run provenance downgrade (the AOT fallback): the measured
        # gauge must not keep exporting its last value as if live.
        m.flops_source = "estimate"
        m.start()
        m.stop()
        g = {r["name"]: r["value"] for r in observe.counters().snapshot()}
        assert math.isnan(g["tdx.train.mfu"])
        assert g["tdx.train.mfu_est"] > 0

    def test_train_step_uses_compiler_flops(self, telemetry):
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
            state, _metrics = train_step(state, batch)
        steps = [e for e in observe.tracer().events
                 if e["ph"] == "X" and e["name"] == "train.step"]
        assert len(steps) == 2
        # Compiler FLOPs flowed through (tflops attr present on every
        # step, and the step program's footprint fed the high-water
        # gauge).  On CPU there is no peak table → no mfu gauge, which
        # is the "omit, never guess" contract.
        assert all(e["args"].get("tflops", 0) > 0 for e in steps)
        snap = {r["name"]: r.get("value") for r in observe.counters().snapshot()}
        assert snap.get("tdx.train.tflops", 0) > 0
        assert snap.get("tdx.jax.hbm_high_water_bytes", 0) > 0
