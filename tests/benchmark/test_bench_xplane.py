"""The trace reduction on a small recorded trace (three steps of a jitted
matmul and one flash-attention call on a v5e, PR 25) and on made-up
intervals."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import xplane  # noqa: E402

TRACE = os.path.join(ROOT, "benchmark", "testdata", "tiny.xplane.pb")


def test_union_merges_nested_and_overlapping():
    assert xplane.union([(5, 9), (0, 3), (2, 4), (6, 7)]) == [(0, 4), (5, 9)]
    assert xplane.clip([(0, 4), (5, 9)], 3, 6) == [(3, 4), (5, 6)]


def test_self_time_takes_the_children_out_of_a_while():
    out = xplane.self_times([("while", 0, 100), ("a", 10, 30), ("b", 30, 60),
                             ("c", 200, 250)])
    assert out == pytest.approx({"while": 50e-9, "a": 20e-9, "b": 30e-9,
                                 "c": 50e-9})


def test_op_key_and_kernel_by_name():
    hlo = ('%step.1 = (bf16[2,1024,64]{2,1,0}, f32[2,1024,128]{2,1,0}) '
           'custom-call(bf16[2,1024,64]{2,1,0} %b), '
           'custom_call_target="tpu_custom_call"')
    assert xplane.op_key(hlo) == "step.1_custom-call:tpu_custom_call_bf16_2_1024_64_"
    assert xplane.is_kernel(hlo)
    fusion = "%convolution_tanh_fusion = bf16[512,512]{1,0} fusion(bf16[512,512]{1,0} %x), kind=kOutput"
    assert xplane.op_key(fusion) == "convolution_tanh_fusion_fusion_bf16_512_512_"
    assert not xplane.is_kernel(fusion)


def test_made_up_trace_busy_gaps_and_blame():
    raw = {
        "devices": {"/device:TPU:0": [
            ("%a = f32[1]{0} fusion(f32[1]{0} %x)", 100, 200),
            ("%k = f32[1]{0} custom-call(f32[1]{0} %x), "
             'custom_call_target="tpu_custom_call"', 300, 400),
            ("%a = f32[1]{0} fusion(f32[1]{0} %x)", 700, 800)]},
        "annotations": [("bench.window", 0, 1000, {}),
                        ("bench.step", 50, 450, {"i": 0}),
                        ("bench.step", 600, 900, {"i": 1})],
    }
    r = xplane.reduce(raw, {"0": "decode", "1": "prefill-128"})
    assert r["busy_s"] == pytest.approx(300e-9)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["kernel_s"] == pytest.approx(100e-9) and r["kernel_calls"] == 1
    assert r["gaps"] == pytest.approx({
        "step_decode:host_before_first_op": 100e-9,
        "step_decode:host_between_two_ops": 100e-9,
        "between_steps:benchmark_loop": 300e-9,
        "step_prefill-128:host_after_last_op": 200e-9})
    assert sum(r["gaps"].values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_recorded_trace():
    raw = xplane.load(TRACE)
    assert list(raw["devices"]) == ["/device:TPU:0"]
    assert len(raw["devices"]["/device:TPU:0"]) == 24
    steps = [a for a in raw["annotations"] if a[0] == "bench.step"]
    assert [a[3]["i"] for a in sorted(steps, key=lambda a: a[1])] == [0, 1, 2]
    r = xplane.reduce(raw, {"0": "a", "1": "b", "2": "c"})
    assert r["n_devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    # the flash-attention call is the hottest op, found by its target
    kernels = {k: v for k, v in r["ops"].items() if "tpu_custom_call" in k}
    assert len(kernels) == 1
    assert r["kernel_s"] == pytest.approx(sum(kernels.values()))
    assert xplane.top(r["ops"], 1)[0][0] in kernels
    assert r["kernel_s"] / r["kernel_calls"] == pytest.approx(8.5e-6, rel=0.05)
    assert sum(r["gaps"].values()) + r["busy_s"] == pytest.approx(r["window_s"])
    assert all(k.startswith(("step_", "between_steps")) for k in r["gaps"])


def test_no_events_no_numbers():
    r = xplane.reduce({"devices": {}, "annotations": []})
    assert r["busy_s"] == 0.0 and r["window_s"] == 0.0
