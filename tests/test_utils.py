"""Tests for aux subsystems: checkpoint round-trip (sharded), profiling
timers, metrics sink."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchdistx_tpu.parallel import make_mesh
from torchdistx_tpu.utils import Metrics, StepTimer, Timer
from torchdistx_tpu.utils.checkpoint import restore_checkpoint, save_checkpoint
from jax.sharding import NamedSharding, PartitionSpec as P


class TestCheckpoint:
    def test_roundtrip_sharded(self, tmp_path):
        mesh = make_mesh({"dp": 4, "tp": 2})
        x = jax.device_put(
            jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
            NamedSharding(mesh, P("dp", "tp")),
        )
        state = {"params": {"w": x}, "step": jnp.int32(7)}
        save_checkpoint(tmp_path / "ckpt", state)
        restored = restore_checkpoint(tmp_path / "ckpt", target=state)
        assert np.array_equal(np.asarray(restored["params"]["w"]), np.asarray(x))
        assert int(restored["step"]) == 7
        assert restored["params"]["w"].sharding.spec == P("dp", "tp")

    def test_restore_into_different_sharding(self, tmp_path):
        mesh = make_mesh({"dp": 4, "tp": 2})
        x = jax.device_put(
            jnp.ones((8, 8)), NamedSharding(mesh, P("dp", "tp"))
        )
        save_checkpoint(tmp_path / "c2", {"w": x})
        target = {
            "w": jax.ShapeDtypeStruct(
                (8, 8), jnp.float32, sharding=NamedSharding(mesh, P("tp", "dp"))
            )
        }
        restored = restore_checkpoint(tmp_path / "c2", target=target)
        assert restored["w"].sharding.spec == P("tp", "dp")
        assert np.array_equal(np.asarray(restored["w"]), np.ones((8, 8)))


class TestProfiling:
    def test_timer_blocks(self):
        with Timer() as t:
            x = jnp.ones((256, 256)) @ jnp.ones((256, 256))
            t.block_on(x)
        assert t.elapsed is not None and t.elapsed > 0

    def test_step_timer(self):
        st = StepTimer()
        for _ in range(3):
            st.start()
            st.stop(jnp.ones(4) + 1)
        assert st.steps == 3 and st.mean > 0

    def test_trace_holds_the_programs_spans_and_no_python_calls(self, tmp_path):
        """``trace`` starts the profiler as the benchmark does: the
        program's spans are on /host:CPU, and no event per Python call."""
        import glob
        import os

        from torchdistx_tpu import observe
        from torchdistx_tpu.utils.profiling import trace

        def some_python_function_of_the_test():
            return sum(range(10))

        observe.enable(True)
        try:
            with trace(str(tmp_path)):
                with observe.span("operator.capture", category="t"):
                    some_python_function_of_the_test()
        finally:
            observe.enable(None)
            observe.reset()
        (path,) = glob.glob(os.path.join(
            str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
        names = {e.name for plane in jax.profiler.ProfileData.from_file(
            path).planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events}
        assert "operator.capture" in names
        assert not any("some_python_function_of_the_test" in n for n in names)
        assert not hasattr(__import__("torchdistx_tpu.utils").utils,
                           "annotate")


class TestMetrics:
    def test_jsonl_sink(self, tmp_path):
        m = Metrics(tmp_path / "m.jsonl")
        m.log(1, loss=1.5, lr=1e-3)
        m.log(2, loss=jnp.float32(1.25))
        m.close()
        lines = [json.loads(l) for l in open(tmp_path / "m.jsonl")]
        assert lines[0]["loss"] == 1.5
        assert lines[1]["loss"] == 1.25


class TestAsyncCheckpoint:
    def test_async_roundtrip_sharded(self, tmp_path):
        from torchdistx_tpu.utils import AsyncCheckpointSaver

        mesh = make_mesh({"dp": 4, "tp": 2})
        x = jax.device_put(
            jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
            NamedSharding(mesh, P("dp", "tp")),
        )
        state = {"params": {"w": x}, "step": jnp.int32(3)}
        with AsyncCheckpointSaver() as saver:
            saver.save(tmp_path / "a1", state)
            # save() returns before the write commits; exiting the context
            # waits, after which the checkpoint must be fully readable.
        restored = restore_checkpoint(tmp_path / "a1", target=state)
        assert np.array_equal(np.asarray(restored["params"]["w"]), np.asarray(x))
        assert int(restored["step"]) == 3

    def test_overlapping_saves_serialize(self, tmp_path):
        from torchdistx_tpu.utils import AsyncCheckpointSaver

        with AsyncCheckpointSaver() as saver:
            for i in range(3):
                saver.save(tmp_path / f"s{i}", {"v": jnp.float32(i)})
        for i in range(3):
            r = restore_checkpoint(tmp_path / f"s{i}", target={"v": jnp.float32(0)})
            assert float(r["v"]) == float(i)


class TestVersioning:
    def test_dunder_version_matches_version_file(self):
        import pathlib

        import torchdistx_tpu

        vf = (pathlib.Path(torchdistx_tpu.__file__).resolve().parent.parent
              / "VERSION")
        assert torchdistx_tpu.__version__ == vf.read_text().strip()

    def test_set_version_stamps(self, monkeypatch, tmp_path):
        import importlib.util
        import pathlib

        repo = pathlib.Path(__file__).resolve().parent.parent
        spec = importlib.util.spec_from_file_location(
            "set_version", repo / "scripts" / "set_version.py")
        sv = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sv)
        vf = tmp_path / "VERSION"
        vf.write_text("0.4.0.dev0\n")
        monkeypatch.setattr(sv, "VERSION_FILE", vf)
        meta = tmp_path / "meta.yaml"
        meta.write_text('{% set version = "0.4.0.dev0" %}\npackage: x\n')
        monkeypatch.setattr(sv, "CONDA_META", meta)
        assert sv.stamp("nightly", "20260801") == "0.4.0.dev20260801"
        assert vf.read_text().strip() == "0.4.0.dev20260801"
        assert sv.stamp("release") == "0.4.0"
        assert sv.stamp("release", "0.5.0rc1") == "0.5.0rc1"
        with pytest.raises(SystemExit):
            sv.stamp("release", "not-a-version")
        with pytest.raises(SystemExit):
            sv.stamp("weekly")
        # the conda pin is stamped in lockstep (smoke.sh enforces
        # equality of the two)
        assert '"0.5.0rc1"' in meta.read_text()
