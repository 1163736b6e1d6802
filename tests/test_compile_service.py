"""The compile service against the INSTALLED jax, with cache errors raised.

``compile_service.compile_program`` reaches into ``jax._src.compilation_cache`` (the
quarantine guard, cache-key recording, registry direct-serve).  jax
itself catches whatever those wrappers raise and carries on as a cache
miss with a warning — which is how a jax upgrade that added an argument
to ``get_executable_and_time`` turned every warm bring-up cold without a
single test failing.  Everything here runs under
``jax_raise_persistent_cache_errors=True`` so a signature drift is an
error, and asserts the outcomes the zero-compile bring-up contract rests
on: miss -> hit in one directory, hit from a COPY of the directory (the
key must not depend on the path), and an executable served straight from
a registry artifact on a cache-key mismatch.
"""

import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchdistx_tpu.config as tdx_config
from torchdistx_tpu import compile_service, observe
from torchdistx_tpu.registry import ArtifactRegistry


@pytest.fixture(autouse=True)
def _strict_cache(monkeypatch):
    monkeypatch.setenv("TDX_CACHE_MIN_COMPILE_S", "0")
    prev = jax.config.jax_raise_persistent_cache_errors
    jax.config.update("jax_raise_persistent_cache_errors", True)
    yield
    jax.config.update("jax_raise_persistent_cache_errors", prev)
    compile_service.reset_cache_binding()


def _program(x):
    return jnp.tanh(x @ x.T).sum(axis=0) * 3.0


_ARGS = (jax.ShapeDtypeStruct((8, 8), jnp.float32),)


def _compile(cache_dir, registry_dir=None, program_fp=None):
    """One cold-process-like compile: in-memory caches dropped, binding
    re-resolved, then the service's own entry point."""
    jax.clear_caches()
    compile_service.reset_cache_binding()
    with tdx_config.override(cache_dir=cache_dir, registry_dir=registry_dir):
        compile_service.bind_cache()
        compiled, _, _, outcome, _ = compile_service.compile_program(
            _program, _ARGS, None, program_fp=program_fp,
            init_compiler_options=False,
        )
    return compiled, outcome


def _runs_right(compiled):
    # numpy on the host for the expectation: an eager jax op would
    # persist entries of its own into the directory under test.
    x = np.arange(64, dtype=np.float32).reshape(8, 8) / 64.0
    np.testing.assert_allclose(np.asarray(compiled(x)),
                               np.tanh(x @ x.T).sum(axis=0) * 3.0, rtol=1e-5)


def test_miss_then_hit_then_hit_from_a_copied_directory(tmp_path):
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    compiled, outcome = _compile(a)
    assert outcome == "miss"
    assert jax.config.jax_persistent_cache_enable_xla_caches == "none"
    entries = os.listdir(a)
    assert any(e.endswith("-cache") for e in entries), entries
    _runs_right(compiled)

    compiled, outcome = _compile(a)
    assert outcome == "hit"
    _runs_right(compiled)

    # The directory's path must be part of no key: a cache warmed in one
    # place (a login host, the registry's install target, the chip
    # tool's mount) has to hit from another.
    shutil.copytree(a, b)
    compiled, outcome = _compile(b)
    assert outcome == "hit"
    assert sorted(os.listdir(b)) == sorted(entries)  # nothing re-persisted
    _runs_right(compiled)


def test_external_cache_dir_wins_and_is_never_rebound(tmp_path, monkeypatch):
    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    assert tdx_config.compile_cache_dir() == placed
    _, outcome = _compile(str(tmp_path / "ignored"))
    assert outcome == "miss"
    assert jax.config.jax_compilation_cache_dir == placed
    assert not os.path.exists(tmp_path / "ignored")
    compile_service.reset_cache_binding()  # un-latches, but must not unbind
    assert jax.config.jax_compilation_cache_dir == placed
    _, outcome = _compile(str(tmp_path / "ignored"))
    assert outcome == "hit"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    compile_service.reset_cache_binding()
    assert jax.config.jax_compilation_cache_dir is None


def test_default_cache_dir_is_the_checkout(monkeypatch):
    monkeypatch.delenv("TDX_CACHE_DIR")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert tdx_config._from_env().cache_dir == os.path.join(repo, ".jax_cache")
    monkeypatch.setenv("TDX_CACHE_DIR", "")
    assert tdx_config._from_env().cache_dir is None


def test_bypass_reads_and_writes_nothing(tmp_path):
    a = str(tmp_path / "a")
    _compile(a)
    before = sorted(os.listdir(a))
    jax.clear_caches()
    with tdx_config.override(cache_dir=a):
        compiled, _, _, outcome, _ = compile_service.compile_program(
            _program, _ARGS, None, bypass_cache=True,
            init_compiler_options=False,
        )
    assert outcome == "bypass"
    assert jax.config.jax_compilation_cache_dir == a  # still bound
    assert sorted(os.listdir(a)) == before
    _runs_right(compiled)


def test_registry_direct_serve_on_key_mismatch(tmp_path):
    reg_dir = str(tmp_path / "reg")
    fp = "f" * 40
    _, outcome = _compile(str(tmp_path / "c0"), reg_dir, fp)
    assert outcome == "miss"
    reg = ArtifactRegistry(reg_dir)
    (key,) = reg.keys()
    # Republish the artifact under a cache-key name no consumer will
    # ever compute: the local load must miss, and the staged payload
    # must be deserialized with THIS compile's options and devices.
    files, meta = reg.fetch(key), reg.read_meta(key)
    shutil.rmtree(reg.entry_dir(key))
    assert reg.publish(
        key, {f"{key[:16]}{i:04x}-cache": d
              for i, d in enumerate(files.values())},
        {"program_fp": meta.get("program_fp")},
    )
    served = observe.counter("tdx.registry.direct_serves").value
    compiled, outcome = _compile(str(tmp_path / "c1"), reg_dir, fp)
    assert outcome == "hit"
    assert observe.counter("tdx.registry.direct_serves").value == served + 1
    _runs_right(compiled)
