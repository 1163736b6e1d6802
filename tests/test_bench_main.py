"""End-to-end wiring tests for bench.main() with the phase-subprocess
boundary stubbed.  A healthy run must end in a compact final stdout line
that survives the driver's ~2000-char tail capture (round 4 lost its
scoreboard record to a single giant line — BENCH_r04 parsed: null); a run
that finds no accelerator, loses it mid-way, or fails its headline phase
must exit non-zero having printed no number — there is no CPU fallback
and no cached headline to republish.
"""

import importlib.util
import io
import contextlib
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
    monkeypatch.setattr(mod, "REPO", str(tmp_path))  # bench_full.json
    monkeypatch.delenv("TDX_BENCH_PLATFORM", raising=False)
    yield mod
    sys.modules.pop("bench", None)


_TPU = {"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1,
        "_backend": "tpu"}


_HOST_PHASES = {
    "t5_sharded": {"t": 5.1, "rss_mb": 2287.0, "n_params": 75191808,
                   "n_sharded": 129, "warm": True, "_backend": "cpu"},
    "mixtral_sharded": {"t": 4.2, "rss_mb": 1731.0, "n_params": 29763856,
                        "n_sharded": 114, "warm": True, "_backend": "cpu"},
    "llama70b_lower": {"record_s": 0.65, "lower_s": 0.45,
                       "export_tpu_s": 0.43, "export_mb": 0.3,
                       "n_params": 70553706496, "n_outputs": 724,
                       "rss_mb": 1219.5},
    "t5_11b_lower": {"record_s": 0.46, "lower_s": 0.45, "export_tpu_s": 0.44,
                     "export_mb": 0.22, "n_params": 11307321344,
                     "n_outputs": 509, "rss_mb": 1216.0},
    "mixtral_8x7b_lower": {"record_s": 0.79, "lower_s": 1.25,
                           "export_tpu_s": 1.13, "export_mb": 0.06,
                           "n_params": 46702792736, "n_outputs": 14,
                           "rss_mb": 428.6},
    "materialize_pipeline": {
        "n_layers": 128, "n_cpus": 8, "repeats": 3, "cold_off_s": 36.6,
        "cold_auto_s": 26.0, "warm_auto_s": 4.0, "n_programs": 21,
        "workers": 4, "overlap": 3.8, "bitwise_equal": True,
        "pipeline_speedup": 1.408, "backend": "cpu", "_backend": "cpu"},
    "materialize_bandwidth": {
        "n_slabs": 32, "repeats": 3, "warm_default_s": 0.104,
        "warm_bf16_s": 0.122, "warm_bf16_no_overlap_s": 0.139,
        "warm_monolith_s": 0.104,
        "bitwise_equal": True, "n_bytes_mb": 268.7,
        "materialize_gbps": 2.584, "overlap_speedup": 0.933,
        "link_bandwidth_gbps": 3.137, "link_probe_mb": 32,
        "materialize_link_utilization": 0.82345, "n_programs": 8,
        "transfer_overlap": 0.61, "bytes_donated": 8398848,
        "device_put_batches": 0, "warm_execute_s": 0.077,
        "backend": "cpu", "_backend": "cpu"},
    "pp_bubble": {"schedule_analysis": {"pp4_v2_m8": {"interleaved_ticks": 26}}},
    "reshard": {
        "n_leaves": 16, "repeats": 2, "reshard_s": 0.41,
        "reshard_bytes_moved": 134217728, "reshard_bytes_total": 134217904,
        "reshard_chunks": 64, "reshard_peak_host_bytes": 16777216,
        "reshard_gbps": 0.327, "backend": "cpu", "_backend": "cpu"},
    "serving": {
        "bring_up_cold_s": 4.1, "ttft_cold_s": 4.13,
        "bring_up_warm_s": 0.77, "ttft_warm_s": 0.77,
        "ttft_warm_speedup": 5.34, "decode_tokens_per_s": 1360.0,
        "warm_local_compiles": 0, "oracle_equal": True,
        "backend": "cpu", "_backend": "cpu"},
    "serving_fleet": {
        "bring_up_cold_s": 4.3, "fleet_scale_up_warm_s": 0.81,
        "fleet_scaleup_warm_speedup": 5.26,
        "fleet_tokens_per_s": {"1": 944.6, "2": 1111.0, "4": 1027.1},
        "fleet_scaling_efficiency_2r": 1.176, "chaos_requeued": 4,
        "warm_local_compiles": 0, "oracle_equal": True,
        "host_cpu_count": 1, "backend": "cpu", "_backend": "cpu"},
    "serving_prefix": {
        "storm_requests": 48, "prefix_hits": 38,
        "prefix_tokens_reused": 1824, "prefix_cow": 2,
        "prefill_chunks": 150,
        "prefix_off_tokens_per_s": 357.2, "prefix_on_tokens_per_s": 656.9,
        "prefix_tokens_per_s_improvement": 1.839,
        "prefix_off_p95_ttft_s": 0.0132, "prefix_on_p95_ttft_s": 0.0071,
        "prefix_p95_ttft_improvement": 1.848,
        "chunked_short_ttft_coarse_s": 0.0119,
        "chunked_short_ttft_fine_s": 0.0091,
        "prefix_chunked_short_ttft_improvement": 1.31, "oracle_equal": True,
        "host_cpu_count": 1, "backend": "cpu", "_backend": "cpu"},
    "serving_spec": {
        "storm_requests": 40, "spec_off_tokens_per_s": 544.0,
        "spec_on_tokens_per_s": 1809.0,
        "spec_tokens_per_s_improvement": 3.322,
        "spec_drafted": 350, "spec_accepted": 230,
        "spec_verify_ticks": 39, "spec_accept_rate": 0.657,
        "spec_accepted_per_verify": 5.846, "oracle_equal": True,
        "host_cpu_count": 1, "backend": "cpu", "_backend": "cpu"},
    "serving_ledger": {
        "storm_requests": 48, "ledger_off_tokens_per_s": 661.0,
        "ledger_on_tokens_per_s": 657.0, "ledger_overhead_ratio": 0.994,
        "ledger_stage_queue_p50_s": 0.0021, "ledger_stage_queue_p99_s": 0.011,
        "ledger_stage_queue_share": 0.31,
        "ledger_stage_prefill_p50_s": 0.0009,
        "ledger_stage_prefill_p99_s": 0.0041,
        "ledger_stage_prefill_share": 0.12,
        "ledger_stage_decode_p50_s": 0.0034,
        "ledger_stage_decode_p99_s": 0.0089,
        "ledger_stage_decode_share": 0.55,
        "ledger_stage_guardrail_p50_s": 0.0,
        "ledger_stage_guardrail_p99_s": 0.0,
        "ledger_stage_guardrail_share": 0.02,
        "ledger_p99_blame_queue": 0.44, "ledger_p99_blame_prefill": 0.08,
        "ledger_p99_blame_decode": 0.46, "ledger_p99_blame_guardrail": 0.02,
        "ledger_e2e_p99_s": 0.021, "oracle_equal": True,
        "host_cpu_count": 1, "backend": "cpu", "_backend": "cpu"},
    "serving_rollover": {
        "storm_requests": 24, "steady_tokens_per_s": 612.0,
        "rollover_tokens_per_s": 588.0,
        "rollover_tokens_per_s_ratio": 0.961,
        "steady_p95_ttft_s": 0.031, "rollover_p95_ttft_s": 0.042,
        "rollover_roll_s": 9.4, "rollover_blue_drains": 2,
        "warm_local_compiles": 0, "oracle_equal": True,
        "host_cpu_count": 1, "backend": "cpu", "_backend": "cpu"},
    "guardrails": {
        "storm_requests": 48, "bring_up_cold_s": 4.2,
        "guardrails_breaker_trips": 1, "guardrails_hedged": 0,
        "guardrails_shed_low": 20, "warm_local_compiles": 0,
        "guardrails_off_p95_ttft_s": 0.247,
        "guardrails_on_p95_ttft_s": 0.134,
        "guardrails_p95_ttft_improvement": 1.848, "oracle_equal": True,
        "host_cpu_count": 1, "backend": "cpu", "_backend": "cpu"},
    "schedule_measured": {"schedule_measured": {
        "gpipe_step_ms": 1769.0, "flat_1f1b_step_ms": 2509.0,
        "interleaved_step_ms": 2078.0, "interleaved_vs_flat_measured": 1.208,
        "platform_note": "8-device virtual CPU mesh"}, "_backend": "cpu"},
}


def _run_main(bench, payloads):
    def fake_run_phase(name, timeout=600.0):
        return dict(payloads[name])

    bench._run_phase = fake_run_phase
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        bench.main()
    stdout = buf.getvalue()
    lines = stdout.strip().splitlines()
    # Simulate the driver: only the last ~2000 chars survive.
    headline = json.loads(stdout[-2000:].strip().splitlines()[-1])
    return json.loads(lines[0]), headline, lines


_DEVICE_PHASES = {
    "gpt2_baseline": {"t": 33.1, "rss_mb": 2500.0, "_backend": "tpu"},
    "gpt2_ours": {"t": 2.7, "rss_mb": 1800.0, "warm": True,
                  "materialize_gbps": 0.19, "_backend": "tpu"},
    "llama_ours": {"t": 2.6, "rss_mb": 4100.0, "n_params": 1480000000,
                   "materialize_gbps": 2.3, "_backend": "tpu"},
    "llama_baseline": {"t": 266.0, "rss_mb": 9000.0, "_backend": "tpu"},
    "llama_big_ours": {"t": 14.2, "rss_mb": 2100.0, "warm": True,
                       "n_params": 6738415616,
                       "param_dtype": "bfloat16", "record_s": 1.1,
                       "materialize_s": 12.0, "touch_s": 1.1,
                       "materialize_gbps": 0.95, "_backend": "tpu"},
    "flash": {"flash_ms": 0.99, "ref_ms": 4.6, "flash_tflops": 34.9,
              "ref_tflops": 7.6, "speedup": 4.64,
              "device_kind": "TPU v5 lite", "blocks": [1024, 1024],
              "mfu": 0.177, "ref_mfu": 0.038, "_backend": "tpu"},
    "flash_bwd": {"flash_ms": 3.58, "ref_ms": 13.6, "speedup": 3.79,
                  "device_kind": "TPU v5 lite", "blocks": [1024, 1024],
                  "mfu": 0.171, "ref_mfu": 0.045, "_backend": "tpu"},
    "flash_bias": {"flash_ms": 1.88, "ref_ms": 5.04, "speedup": 2.68,
                   "device_kind": "TPU v5 lite", "blocks": [512, 1024],
                   "mfu": 0.186, "ref_mfu": 0.069, "_backend": "tpu"},
    "train_mfu": {"step_ms": 185.0, "tokens_per_s": 44300, "mfu": 0.31,
                  "device_kind": "TPU v5 lite", "n_params": 124000000,
                  "_backend": "tpu"},
}


def test_healthy_branch_headline_and_detail(bench):
    payloads = {**_HOST_PHASES, **_DEVICE_PHASES, "platform": _TPU}
    full, headline, lines = _run_main(bench, payloads)
    assert len(lines) == 2
    assert headline["device"] == {"platform": "tpu",
                                  "device_kind": "TPU v5 lite",
                                  "device_count": 1}
    assert len(lines[-1]) <= bench._HEADLINE_BUDGET
    assert headline["vs_baseline"] == round(33.1 / 2.7, 3)
    assert headline["train_mfu"] == 0.31
    assert headline["flash_mfu"] == 0.177
    assert headline["llama_big_n_params"] == 6738415616
    assert headline["llama_big_materialize_gbps"] == 0.95
    assert headline["t5_11b_n_params"] == 11307321344
    assert headline["mixtral_8x7b_rss_mb"] == 428.6
    assert full["llama_1p9b_vs_baseline"] == round(266.0 / 2.6, 3)
    assert full["llama_big_param_dtype"] == "bfloat16"
    assert headline["pipeline_speedup"] == 1.408
    assert headline["reshard_gbps"] == 0.327
    assert headline["fleet_scaleup_warm_speedup"] == 5.26
    assert headline["fleet_scaling_efficiency_2r"] == 1.176
    assert full["serving_fleet"]["chaos_requeued"] == 4
    assert headline["guardrails_p95_ttft_improvement"] == 1.848
    assert full["guardrails"]["guardrails_breaker_trips"] == 1
    assert headline["prefix_tokens_per_s_improvement"] == 1.839
    assert headline["prefix_p95_ttft_improvement"] == 1.848
    assert full["serving_prefix"]["prefix_hits"] == 38
    assert headline["ledger_overhead_ratio"] == 0.994
    assert full["serving_ledger"]["ledger_p99_blame_queue"] == 0.44
    assert headline["rollover_tokens_per_s_ratio"] == 0.961
    assert full["serving_rollover"]["rollover_blue_drains"] == 2
    assert full["reshard_bytes_moved"] == 134217728
    assert full["materialize_pipeline"]["bitwise_equal"] is True
    assert full["schedule_measured"]["interleaved_vs_flat_measured"] == 1.208
    assert json.load(open(Path(bench.REPO) / "bench_full.json")) == full


def _run_expecting_failure(bench, payloads, capsys):
    bench._run_phase = lambda name, timeout=600.0: dict(payloads[name])
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""  # no number, no headline
    return str(exc.value.code)


def test_no_accelerator_fails_and_prints_no_number(bench, capsys):
    cpu = {"platform": "cpu", "device_kind": "cpu", "device_count": 1,
           "_backend": "cpu"}
    # Only the preflight child may run: any measured phase would KeyError.
    msg = _run_expecting_failure(bench, {"platform": cpu}, capsys)
    assert "no accelerator" in msg


def test_backend_init_failure_fails(bench, capsys):
    msg = _run_expecting_failure(
        bench, {"platform": {"error": "phase platform timed out"}}, capsys)
    assert "backend init failed" in msg


def test_phase_that_lands_on_cpu_fails_the_run(bench, capsys):
    # The preflight saw a TPU; a later phase silently ran on the CPU.
    payloads = {**_HOST_PHASES, **_DEVICE_PHASES, "platform": _TPU}
    payloads["flash_bwd"] = {**payloads["flash_bwd"], "_backend": "cpu"}
    msg = _run_expecting_failure(bench, payloads, capsys)
    assert "flash_bwd ran on the cpu backend" in msg
    payloads = {**_HOST_PHASES, **_DEVICE_PHASES, "platform": _TPU}
    payloads["gpt2_baseline"] = {**payloads["gpt2_baseline"],
                                 "_backend": "cpu"}
    assert "gpt2_baseline ran on the cpu" in _run_expecting_failure(
        bench, payloads, capsys)


def test_failed_headline_phase_fails_the_run(bench, capsys):
    payloads = {**_HOST_PHASES, **_DEVICE_PHASES, "platform": _TPU,
                "gpt2_ours": {"error": "boom"}}
    assert "gpt2_ours failed" in _run_expecting_failure(
        bench, payloads, capsys)


def test_explicitly_forced_cpu_runs_and_says_so(bench, monkeypatch):
    # TDX_BENCH_PLATFORM=cpu (tests, make bench-smoke) is a choice, not a
    # fallback: no preflight child, every phase on cpu, labeled.
    monkeypatch.setenv("TDX_BENCH_PLATFORM", "cpu")
    payloads = {**_HOST_PHASES,
                **{k: {**v, "_backend": "cpu"}
                   for k, v in _DEVICE_PHASES.items()}}
    full, headline, _ = _run_main(bench, payloads)
    assert headline["device"] == {"platform": "cpu", "forced": True}
    assert full["llama_big_skipped"].startswith("forced-cpu smoke")
    assert full["flash_mfu"] == 0.177  # interpret-mode numbers kept, labeled
