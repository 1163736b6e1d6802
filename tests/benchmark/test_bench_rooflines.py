"""The FLOP and byte functions against hand counts for one Mistral layer
and one GPT-2 layer, and the peaks table."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import configs  # noqa: E402
from benchmark.rooflines import flash_attention, model, paged_attention  # noqa: E402


def dims(name):
    c = configs.dims(configs.load(ROOT, f"benchmark/configs/{name}.json"))
    if name.startswith("mistral"):
        c["n_layers"] = 1
    return c


def test_one_mistral_layer_by_hand():
    c = dims("mistral-7b-v0.3-d12")
    # q and o: 4096 x 4096 each; k and v: 4096 x 1024 each; three FFN
    # matrices of 4096 x 14336
    by_hand = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert model.matmul_params_per_layer(c) == by_hand == 218_103_808
    # one decode position attending 1000 keys: 2 matmul FLOPs a parameter,
    # plus 4 * 32 heads * 128 * 1000 for QK^T and PV, head left out
    assert model.forward_flops(c, 1, 1000, 0) == 2 * by_hand + 4 * 32 * 128 * 1000
    # the kernel at that position: K and V rows of 8 kv heads x 128 in bf16
    need = paged_attention.needs(c, 1000)
    assert need["bytes"] == 2 * 8 * 128 * 2 * 1000
    assert need["flops"] == 4 * 32 * 128 * 1000
    peaks = json.load(open(os.path.join(ROOT, "benchmark", "peaks.json")))["TPU v5 lite"]
    secs, bound = paged_attention.least_seconds(need, peaks)
    assert bound == "memory" and secs == pytest.approx(4_096_000 / 819e9)


def test_one_gpt2_layer_by_hand():
    c = dims("gpt2-medium")
    # four 1024 x 1024 attention matrices and two 1024 x 4096 of the MLP
    by_hand = 4 * 1024 * 1024 + 2 * 1024 * 4096
    assert model.matmul_params_per_layer(c) == by_hand == 12_582_912
    one = dict(c, n_layers=1)
    pairs = 1024 * 1025 // 2
    assert model.causal_pairs(1024) == pairs
    # one row of 1024 through one layer, forward, without the head
    assert model.forward_flops(one, 1024, pairs, 0) == (
        2 * by_hand * 1024 + 4 * 16 * 64 * pairs)
    # the flash kernels on that row: forward 4 h d pairs, backward twice that
    need = flash_attention.needs(one, 1, 1024)
    assert need["flops"] == 12 * 16 * 64 * pairs
    assert need["bytes"] == 3 * 4 * 1024 * 16 * 64 * 2


def test_train_step_is_six_n_tokens_plus_attention():
    c = dims("gpt2-medium")
    n = 24 * 12_582_912 + 1024 * 50257  # blocks and the tied head
    assert model.matmul_params(c) == n
    flops = model.train_step_flops(c, 4, 1024)
    attention = 3 * 4 * 16 * 64 * 4 * (1024 * 1025 // 2) * 24
    assert flops == pytest.approx(6 * n * 4096 + attention)


def test_parameter_counts_of_the_three_configurations():
    from benchmark import weights

    for name, family, billions in (("mistral-7b-v0.3-d12", "llama", 2.89),
                                   ("gpt2-xl", "gpt2", 1.56),
                                   ("gpt2-medium", "gpt2", 0.355)):
        c = configs.dims(configs.load(ROOT, f"benchmark/configs/{name}.json"))
        assert weights.n_params(family, c) / 1e9 == pytest.approx(billions, rel=0.01)


def test_peaks_are_keyed_by_the_exact_device_kind():
    peaks = json.load(open(os.path.join(ROOT, "benchmark", "peaks.json")))
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9 and "Google Cloud" in v5e["source"]
    assert "TPU v5" not in peaks and "cpu" not in peaks
