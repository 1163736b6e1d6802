"""The control has to come out as not correct: the plain reference put in
the program's place and computed in fp8, the nearest precision below the
bfloat16 that the configurations state.  Here at a size a test run can
hold; PERF.md gives the readings at the cells' own sizes on the chip."""

import os
import sys

import numpy as np
import pytest

from bench_util import ROOT, rehearse

sys.path.insert(0, ROOT)

# Tiny-size readings (CPU, PR 25, three seeds a family): bf16 at most
# 0.0017, fp8 at least 0.019.
TINY_LOGIT_GAP_LIMIT = 0.006


@pytest.mark.parametrize("family", ["llama", "gpt2"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serving_control_fails_and_bf16_passes(family, seed):
    import jax.numpy as jnp

    from benchmark import check_serve, weights
    from benchmark.reference import decoder

    c = {"d_model": 64, "n_heads": 4, "n_kv_heads": 2 if family == "llama" else 4,
         "head_dim": 16, "d_ff": 128, "n_layers": 2, "vocab_size": 2048,
         "max_seq_len": 512, "rope_theta": 1e6, "norm_eps": 1e-5}
    w = weights.make(family, c, seed, jnp.float32)
    toks = [int(t) for t in np.random.default_rng(seed).integers(0, 2048, 512)]
    logits = {q: decoder.Forward(family, c, decoder.QUANT[q], 256).logits(
        w, toks, 0, 512) for q in (None, "bf16", "fp8")}
    gap = {q: float(check_serve.gaps(logits[None], logits[q].argmax(-1)).max())
           for q in ("bf16", "fp8")}
    assert gap["bf16"] <= TINY_LOGIT_GAP_LIMIT < gap["fp8"], gap
    assert gap["fp8"] >= 3 * gap["bf16"]


def test_training_control_fails_one_number_and_the_fault_another():
    rc, line, err = rehearse("gpt2m-train-1chip", seed=21, seconds=0.3,
                             extra=("--control", "fp8"))
    assert rc == 0 and line["correct"] is True, err
    limits = {k: c["limit"] for k, c in line["checks"].items()}
    control = line["notes"]["control"]
    fault = line["notes"]["fault_half_batch"]
    numbers = ("loss_gap", "grad_norm_gap", "grad_sample_gap", "update_norm_gap")
    # fp8 fails the first-order number and passes the norms, whose gaps are
    # of second order in unbiased rounding
    assert control["grad_sample_gap"] > limits["grad_sample_gap"], (control, limits)
    assert control["grad_sample_gap"] >= 3 * line["checks"]["grad_sample_gap"]["value"]
    assert any(fault[k] > limits[k] for k in numbers), (fault, limits)
    assert fault["grad_norm_gap"] >= 10 * line["checks"]["grad_norm_gap"]["value"]
