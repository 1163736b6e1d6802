"""Unit tests for bench.py's pure pieces: how flash / train / big-llama
results merge under the phase key schemes, the warm-stamp entry filter,
the block-size ladder, the chain timer, the train-MFU FLOP accounting,
and the compact headline line.
"""

import importlib.util
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture()
def bench(monkeypatch, tmp_path):
    spec = importlib.util.spec_from_file_location("bench", REPO / "bench.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench"] = mod
    spec.loader.exec_module(mod)
    yield mod
    sys.modules.pop("bench", None)


class TestMergeFlash:
    def test_fwd_phase_key_scheme(self, bench):
        out = {}
        bench._merge_flash_result(out, "flash", {
            "flash_ms": 1.0, "ref_ms": 4.0, "flash_tflops": 50.0,
            "speedup": 4.0, "mfu": 0.25, "device_kind": "TPU v5e",
        })
        assert out["flash_ms"] == 1.0
        assert out["ref_ms"] == 4.0            # ref keys unprefixed
        assert out["flash_speedup"] == 4.0     # bare keys gain flash_
        assert out["flash_mfu"] == 0.25
        assert out["flash_device_kind"] == "TPU v5e"

    def test_flavor_phase_key_scheme(self, bench):
        out = {}
        bench._merge_flash_result(out, "flash_bwd", {
            "flash_ms": 2.0, "ref_ms": 9.0, "speedup": 4.5, "mfu": 0.3,
        })
        assert out["flash_bwd_ms"] == 2.0      # flash_ stutter collapsed
        assert out["flash_bwd_ref_ms"] == 9.0
        assert out["flash_bwd_speedup"] == 4.5
        assert out["flash_bwd_mfu"] == 0.3

class TestWarmEntryFilter:
    def test_only_substantial_entries_count(self, bench, tmp_path):
        # _cache_entries inspects the directory the program's resolver
        # names (config.compile_cache_dir), nowhere else.
        jax_dir = tmp_path / "jax"
        jax_dir.mkdir(parents=True)
        assert bench._cache_entries() == set()  # tests run cache-less
        import torchdistx_tpu.config as tdx_config

        with tdx_config.override(cache_dir=str(jax_dir)):
            self._check(bench, jax_dir)

    @staticmethod
    def _check(bench, jax_dir):
        (jax_dir / "tiny").write_bytes(b"x" * 100)
        assert bench._cache_entries() == set()
        (jax_dir / "big").write_bytes(b"x" * 40000)
        assert bench._cache_entries() == {"big"}


class TestPeakTable:
    def test_known_kinds(self, bench):
        assert bench._peak_tflops("TPU v5e") == 197.0
        assert bench._peak_tflops("TPU v5 lite") == 197.0
        assert bench._peak_tflops("TPU v4") == 275.0

    def test_unknown_kind_omits_mfu(self, bench):
        assert bench._peak_tflops("cpu") is None


class TestFirstFittingBlocks:
    """The flash phases walk a block-size ladder because scoped-vmem
    budgets vary by chip generation (v5e lost [1024,1024]+bias by 576K
    in the round-4 capture)."""

    def test_first_candidate_fits(self, bench):
        t, blocks, reason = bench._first_fitting_blocks(
            bench_fn=lambda step: step,
            mk_step=lambda f: f,
            mk_flash=lambda block_q, block_k: (block_q, block_k),
            ladder=[(1024, 1024), (512, 512)],
        )
        assert (t, blocks, reason) == ((1024, 1024), (1024, 1024), None)

    def test_oom_demotes_down_the_ladder(self, bench):
        def bench_fn(step):
            if step[0] * step[1] > 512 * 512:
                raise RuntimeError("scoped vmem exceeded")
            return 0.001

        t, blocks, reason = bench._first_fitting_blocks(
            bench_fn=bench_fn,
            mk_step=lambda f: f,
            mk_flash=lambda block_q, block_k: (block_q, block_k),
            ladder=[(1024, 1024), (1024, 512), (512, 512)],
        )
        assert blocks == (512, 512) and t == 0.001
        # The classification trigger is recorded with the demotion.
        assert reason.startswith("vmem:")

    def test_nothing_fits_reraises_last_error(self, bench):
        def bench_fn(step):
            raise RuntimeError(f"scoped vmem exceeded at {step}")

        with pytest.raises(RuntimeError, match=r"vmem exceeded at \(256, 256\)"):
            bench._first_fitting_blocks(
                bench_fn=bench_fn,
                mk_step=lambda f: f,
                mk_flash=lambda block_q, block_k: (block_q, block_k),
                ladder=[(512, 512), (256, 256)],
            )

    def test_non_vmem_error_propagates_without_demotion(self, bench):
        # A compile crash that does not name vmem must surface, NOT be
        # mislabeled as a vmem demotion with numbers at smaller blocks.
        def bench_fn(step):
            raise RuntimeError("INTERNAL: Mosaic failed to compile")

        with pytest.raises(RuntimeError, match="Mosaic failed"):
            bench._first_fitting_blocks(
                bench_fn=bench_fn,
                mk_step=lambda f: f,
                mk_flash=lambda block_q, block_k: (block_q, block_k),
                ladder=[(1024, 1024), (512, 512)],
            )


def test_merge_train_key_scheme(bench):
    out = {}
    bench._merge_train_result(
        out, {"step_ms": 400.0, "mfu": 0.32, "device_kind": "TPU v5 lite"})
    assert out == {"train_step_ms": 400.0, "train_mfu": 0.32}  # kind stays phase-local


def test_train_mfu_flop_accounting(bench, monkeypatch):
    # Pin the useful-work FLOP formula the charter-judged MFU divides
    # by: 6*N_matmul*tokens + 6*B*H*S^2*Dh*L, recompute excluded.  A
    # hand calculation at a small config; if someone edits the formula
    # the reported MFU changes meaning and this fails.
    monkeypatch.setenv("TDX_BENCH_PLATFORM", "cpu")
    monkeypatch.setenv("TDX_TRAIN_SHAPE", "2,64,64,2,2")
    monkeypatch.setenv("TDX_TRAIN_ITERS", "1,3")
    r = bench.phase_train_mfu()  # cache-less, like every test (conftest)
    B, S, d, L, H = 2, 64, 64, 2, 2
    d_ff = 11 * d // 4
    Dh = d // H
    n_matmul = L * (4 * d * d + 3 * d * d_ff) + d * 32000
    flops = 6.0 * n_matmul * B * S + 6.0 * B * H * S * S * Dh * L
    # step_ms is rounded to 3 decimals, so the t recovered here carries
    # up to 0.5us of error — compare with a tolerance, not exactly.
    t = r["step_ms"] / 1e3
    assert r["tflops"] == pytest.approx(flops / t / 1e12, abs=0.011)
    assert r["tokens_per_s"] == pytest.approx(B * S / t, abs=1.0)
    assert "mfu" not in r  # cpu kind has no peak table entry


class TestHeadlineLine:
    """The driver records only ~2000 tail characters of stdout; the
    final line must always be a parseable compact headline (r4 lost its
    scoreboard record to a single giant line — BENCH_r04 parsed: null)."""

    def _fat_out(self, bench):
        # A worst-case detail dict: every headline key present with
        # realistically wide values, plus kilobytes of non-headline keys.
        out = {k: 123456.789 for k in bench._HEADLINE_KEYS}
        out.update({
            "metric": "gpt2-125m deferred_init→device materialize+touch wall time",
            "unit": "s",
            "device": {"platform": "tpu", "device_kind": "TPU v5 lite",
                       "device_count": 4},
            "train_mfu_error": "x" * 160,
        })
        for i in range(200):
            out[f"padding_key_{i}"] = {"nested": [i] * 8}
        return out

    def test_headline_fits_budget_and_parses(self, bench):
        h = bench._headline(self._fat_out(bench), "bench_full.json")
        line = json.dumps(h)
        assert len(line) <= bench._HEADLINE_BUDGET
        parsed = json.loads(line)
        assert parsed["metric"].startswith("gpt2-125m")
        assert "vs_baseline" in parsed
        assert parsed["detail"] == "bench_full.json"

    def test_emit_last_line_is_headline(self, bench, capsys, monkeypatch, tmp_path):
        monkeypatch.setattr(bench, "REPO", str(tmp_path))
        out = self._fat_out(bench)
        bench._emit(out)
        lines = capsys.readouterr().out.strip().splitlines()
        assert len(lines) == 2
        assert json.loads(lines[0]) == json.loads((tmp_path / "bench_full.json").read_text())
        last = json.loads(lines[-1])
        assert len(lines[-1]) <= bench._HEADLINE_BUDGET
        assert last["metric"] == out["metric"]

    def test_headline_never_drops_metric_value(self, bench):
        # Even under an absurd value blow-up the trim loop keeps the
        # front-of-list keys and stays within budget.
        out = {k: "y" * 120 for k in bench._HEADLINE_KEYS}
        h = bench._headline(out, None)
        assert len(json.dumps(h)) <= bench._HEADLINE_BUDGET
        assert "metric" in h and "value" in h


class TestChainTime:
    """_chain_time repeats the lo/hi pair and takes the smallest
    positive delta (one host hiccup must not shift the charter-judged
    train MFU, which differences only 3 steps)."""

    def _jnp(self):
        import jax.numpy as jnp
        return jnp

    def test_min_positive_delta(self, bench, monkeypatch):
        monkeypatch.setenv("TDX_CHAIN_REPEATS", "3")
        import time as _time

        def g(carry, n):
            _time.sleep(0.002 * int(n))
            return 0.0

        t = bench._chain_time(self._jnp(), g, (), 2, 10)
        assert 0.0005 < t < 0.01  # ~2 ms/iter, bounded loosely

    def test_all_nonpositive_deltas_raise(self, bench):
        import time as _time

        def g(carry, n):  # lo runs SLOWER than hi: deltas all negative
            _time.sleep(0.02 if int(n) == 2 else 0.001)
            return 0.0

        with pytest.raises(RuntimeError, match="no positive delta"):
            bench._chain_time(self._jnp(), g, (), 2, 10, repeats=2)


def test_merge_big_llama_key_scheme(bench):
    res = {"t": 12.5, "rss_mb": 2000.0, "n_params": 6738415616,
           "param_dtype": "bfloat16", "warm": True, "record_s": 0.4,
           "materialize_s": 11.0, "materialize_gbps": 1.08}
    out = {}
    bench._merge_big_llama(out, res)
    assert out["llama_big_ours_s"] == 12.5
    assert out["llama_big_param_dtype"] == "bfloat16"
    assert out["llama_big_materialize_gbps"] == 1.08
