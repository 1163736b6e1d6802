"""Autotuner tests (interpret mode; numbers are meaningless on CPU but
the search/caching contract — including the real cache path resolution
through the config layer — is fully exercised)."""

import json
import os

import jax
import pytest

from torchdistx_tpu import config
from torchdistx_tpu.ops import autotune, tune_flash_blocks


@pytest.fixture
def cache_dir(tmp_path):
    # Route through the REAL _cache_path / config layer (a lambda
    # monkeypatch of _cache_path once hid an ImportError inside it).
    with config.override(cache_dir=str(tmp_path)):
        yield tmp_path


def test_returns_candidate_and_caches(cache_dir, monkeypatch):
    cands = ((16, 16), (32, 16))
    blocks = tune_flash_blocks(
        batch=1, seq_len=32, heads=2, head_dim=16, candidates=cands,
    )
    assert blocks in cands
    path = autotune._cache_path()
    assert os.path.dirname(path) == str(cache_dir)
    data = json.load(open(path))
    key = next(iter(data))
    assert jax.devices()[0].device_kind in key
    assert "bfloat16" in key  # dtype is part of the key
    assert "interpret=" in key  # interpreter winners never serve real chips
    # Second call hits the cache: measuring again would be a bug.
    monkeypatch.setattr(
        autotune, "_measure",
        lambda *a, **k: pytest.fail("re-measured despite a valid cache hit"),
    )
    again = tune_flash_blocks(
        batch=1, seq_len=32, heads=2, head_dim=16, candidates=cands,
    )
    assert again == blocks


def test_cached_winner_outside_candidates_remeasures(cache_dir):
    # A cached winner must not be served to a call whose candidate set
    # excludes it (e.g. a memory-constrained caller).
    tune_flash_blocks(
        batch=1, seq_len=32, heads=2, head_dim=16, candidates=((32, 32),),
    )
    blocks = tune_flash_blocks(
        batch=1, seq_len=32, heads=2, head_dim=16, candidates=((16, 16),),
    )
    assert blocks == (16, 16)


def test_oversized_candidates_clamp(cache_dir):
    # seq_len below every candidate: clamp like flash_attention does
    # instead of refusing to tune short contexts.
    blocks = tune_flash_blocks(
        batch=1, seq_len=8, heads=2, head_dim=16,
        candidates=((64, 64), (128, 64)), use_cache=False,
    )
    assert blocks == (8, 8)


def test_empty_candidates_raise(cache_dir):
    with pytest.raises(ValueError, match="candidate list is empty"):
        tune_flash_blocks(
            batch=1, seq_len=8, heads=2, head_dim=16,
            candidates=(), use_cache=False,
        )


def test_compile_failure_raises_block_config_error():
    # A candidate whose tiles overrun scoped vmem dies in Mosaic
    # compilation (v5e: [1024,1024] + f32 bias tile, round-4 capture).
    # _measure flags it as a per-config failure (BlockConfigError) so
    # the tuner can let survivors compete — and still detect the
    # all-configs-failed systemic case.
    import jax.numpy as jnp

    def boom(q, k, v):
        raise RuntimeError("RESOURCE_EXHAUSTED: scoped vmem")

    q = k = v = jnp.zeros((1, 8, 1, 8), jnp.float32)
    with pytest.raises(autotune.BlockConfigError):
        autotune._measure(boom, q, k, v)


def test_oom_candidate_loses_to_fitting_one(cache_dir, monkeypatch):
    import importlib

    # The package re-exports the FUNCTION under the same name; fetch
    # the module itself, which is what the tuner imports from.
    fa_mod = importlib.import_module("torchdistx_tpu.ops.flash_attention")
    real = fa_mod.flash_attention

    def gated(q, k, v, *a, block_q=None, block_k=None, **kw):
        if block_q == 32:
            raise RuntimeError("RESOURCE_EXHAUSTED: scoped vmem")
        return real(q, k, v, *a, block_q=block_q, block_k=block_k, **kw)

    monkeypatch.setattr(fa_mod, "flash_attention", gated)
    blocks = tune_flash_blocks(
        batch=1, seq_len=32, heads=2, head_dim=16,
        candidates=((32, 16), (16, 16)), use_cache=False,
    )
    assert blocks == (16, 16)


def test_all_candidates_noise_returns_smallest(cache_dir, monkeypatch):
    # Everything measured as noise (host hiccups): hand back the
    # smallest tile — the most likely to fit — and do not cache it.
    monkeypatch.setattr(autotune, "_measure", lambda *a, **k: float("inf"))
    blocks = tune_flash_blocks(
        batch=1, seq_len=64, heads=2, head_dim=16,
        candidates=((64, 64), (16, 16), (64, 16)), use_cache=True,
    )
    assert blocks == (16, 16)
    assert autotune._read_cache("anything") is None and not os.path.exists(
        autotune._cache_path()
    )


def test_all_candidates_compile_failing_raises(cache_dir, monkeypatch):
    # EVERY config failing to compile is systemic — tuning must not
    # "succeed" with the smallest tile as if it had measured something.
    def boom(*a, **k):
        raise autotune.BlockConfigError("exceeded scoped vmem limit")

    monkeypatch.setattr(autotune, "_measure", boom)
    with pytest.raises(autotune.BlockConfigError):
        tune_flash_blocks(
            batch=1, seq_len=64, heads=2, head_dim=16,
            candidates=((64, 64), (16, 16)), use_cache=False,
        )


def test_non_vmem_compile_error_propagates():
    # Only memory-shaped failures measure as inf; a broken program must
    # raise so the caller learns the kernel cannot run at this shape.
    import jax.numpy as jnp

    def boom(q, k, v):
        raise ValueError("head_dim violates Mosaic tiling rules")

    q = k = v = jnp.zeros((1, 8, 1, 8), jnp.float32)
    with pytest.raises(ValueError, match="tiling rules"):
        autotune._measure(boom, q, k, v)


def test_hbm_oom_propagates():
    # HBM OOM carries RESOURCE_EXHAUSTED too, but no block size fixes
    # it — tuning must fail loudly, not "win" with the smallest tile.
    import jax.numpy as jnp

    def boom(q, k, v):
        raise RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory allocating 12884901888 "
            "bytes in hbm"
        )

    q = k = v = jnp.zeros((1, 8, 1, 8), jnp.float32)
    with pytest.raises(RuntimeError, match="in hbm"):
        autotune._measure(boom, q, k, v)


def test_vmem_trigger_reports_matched_substring():
    assert autotune._vmem_trigger(
        RuntimeError("Scoped allocation with size 9 exceeded scoped vmem limit")
    ) == "vmem"
    assert autotune._vmem_trigger(
        RuntimeError("Scoped allocation with size 9 exceeded the limit")
    ) == "Scoped allocation"
    # A compile crash that does not name vmem is a bug, not a block
    # size to step down from.
    assert autotune._vmem_trigger(
        RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel")
    ) is None
    assert autotune._is_vmem_error(RuntimeError("VMEM overflow"))
    assert not autotune._is_vmem_error(RuntimeError("RESOURCE_EXHAUSTED: HBM"))


class _ScriptedJit:
    """jax stand-in whose jit ignores the traced fn and returns a
    scripted g — the only way to make an error first appear in
    _measure's TIMED loop (a real jit never re-executes Python after
    the warm-up compile, so a scripted failure can't fire there)."""

    def __init__(self, g):
        self._g = g

    def jit(self, f):
        return self._g


def test_timed_loop_vmem_error_translates_to_block_config(monkeypatch):
    import jax.numpy as jnp

    calls = {"n": 0}

    def scripted(carry, n):
        calls["n"] += 1
        if calls["n"] > 2:  # both warm-ups succeed; first timed call dies
            raise RuntimeError(
                "Scoped allocation with size 123 exceeded scoped vmem limit")
        return 0.0

    monkeypatch.setattr(autotune, "jax", _ScriptedJit(scripted))
    q = k = v = jnp.zeros((1, 8, 1, 8), jnp.float32)
    with pytest.raises(autotune.BlockConfigError):
        autotune._measure(lambda *c: c, q, k, v)
    assert calls["n"] == 3


def test_timed_loop_non_vmem_error_propagates(monkeypatch):
    import jax.numpy as jnp

    calls = {"n": 0}

    def scripted(carry, n):
        calls["n"] += 1
        if calls["n"] > 2:
            raise RuntimeError("INTERNAL: device halted")
        return 0.0

    monkeypatch.setattr(autotune, "jax", _ScriptedJit(scripted))
    q = k = v = jnp.zeros((1, 8, 1, 8), jnp.float32)
    with pytest.raises(RuntimeError, match="device halted"):
        autotune._measure(lambda *c: c, q, k, v)
