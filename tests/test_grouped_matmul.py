"""The expert layers' grouped product (``ops/grouped_matmul.py``,
interpreted on the CPU) against ``jax.lax.ragged_dot`` on the same
operands, at reduced widths (``k = n = 256``, 8 groups): both row
tilings, empty groups, every pair in one group, no pair at all, and rows
past the last group (which the kernel leaves undefined and the caller
selects away); then ``held_expert_sum`` with the kernel against the same
function with ``ragged_dot``, with few and with most pairs on the held
experts.

Tolerances: in bfloat16 each product is accumulated in float32 and
rounded once, as ``ragged_dot`` accumulated in float32 and rounded once
is, so the two differ by the order of float32 sums alone: at most one
bfloat16 ulp of the reference value (of 1/16 where the value is smaller:
a sum that nearly cancels keeps the float32 sums' absolute difference,
which is far under that; the outputs are of order 1).  In float32 they
differ by float32 rounding (1e-5 of values of order 1)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.families import afmoe as fam  # noqa: E402
from torchdistx_tpu.models import afmoe as prog  # noqa: E402
from torchdistx_tpu.ops import grouped_matmul as gm  # noqa: E402

G, K, N = 8, 256, 256


def _operands(seed, m, dtype=jnp.bfloat16, k=K):
    rng = np.random.default_rng(seed)
    lhs = jnp.asarray(rng.standard_normal((m, k)), dtype)
    rhs = jnp.asarray(rng.standard_normal((G, k, N)) * (0.8 / k ** 0.5), dtype)
    return lhs, rhs


def _reference(lhs, rhs, sizes):
    return jax.lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32
                              ).astype(lhs.dtype)


def _ulps(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1 / 16))) - 7)
    return float((np.abs(got - want) / ulp).max()) if got.size else 0.0


# rows given, group sizes (and the width k where it is not K)
CASES = {
    "decode-tiling": (96, [9, 4, 0, 17, 6, 1, 12, 11]),
    "chunk-tiling": (1536, [150, 0, 310, 29, 401, 64, 1, 200]),
    "empty-first-and-last": (192, [0, 40, 0, 0, 33, 7, 80, 0]),
    "every-pair-in-one-group": (96, [0, 0, 0, 96, 0, 0, 0, 0]),
    "no-pair": (1536, [0] * G),
    "rows-past-the-last-group": (1536, [30, 2, 0, 90, 0, 41, 0, 77]),
    "rows-no-multiple-of-a-tile": (40, [3, 11, 0, 5, 0, 0, 9, 2]),
    "several-k-tiles": (192, [20, 0, 31, 2, 70, 0, 15, 40], 3072),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_is_ragged_dot_within_an_ulp(case):
    m, sizes, *k = CASES[case]
    sizes = jnp.asarray(sizes, jnp.int32)
    lhs, rhs = _operands(len(case), m, k=k[0] if k else K)
    rows = int(sizes.sum())
    got = gm.grouped_matmul(lhs, rhs, sizes)
    assert got.shape == (m, N) and got.dtype == jnp.bfloat16
    want = _reference(lhs, rhs, sizes)
    assert _ulps(got[:rows], want[:rows]) <= 1.0
    assert np.isfinite(np.asarray(got[:rows], np.float32)).all()


def test_float32_operands_agree_to_float32_rounding():
    m, sizes = CASES["chunk-tiling"]
    sizes = jnp.asarray(sizes, jnp.int32)
    lhs, rhs = _operands(3, m, jnp.float32)
    got = gm.grouped_matmul(lhs, rhs, sizes)
    want = jax.lax.ragged_dot(lhs, rhs, sizes,
                              precision=jax.lax.Precision.HIGHEST)
    rows = int(sizes.sum())
    np.testing.assert_allclose(np.asarray(got[:rows]), np.asarray(want[:rows]),
                               atol=1e-5, rtol=1e-5)


def test_the_row_tile_follows_the_static_row_count():
    assert gm.tiling(96, 3072) == (96, 1024)             # a decode tick
    for m in (192, 384, 512, 768, 1024):      # decode ticks, short prefills
        assert gm.tiling(m, 3072) == (128, 1024)
    for m in (1536, 3072, 6144, 8192):        # chunks and long prefills
        assert gm.tiling(m, 3072) == (256, 1024)
    assert gm.tiling(40, 256) == (48, 256)


@pytest.mark.parametrize("seed", range(4))
def test_each_group_visits_each_tile_that_holds_its_rows_once(seed):
    """The visits are exactly the (tile, group) pairs that hold rows, in
    row order, within the static bound; the steps past them repeat the
    last one, so they start no copy."""
    rng = np.random.default_rng(seed)
    m, tm = 512, 32
    sizes = rng.multinomial(int(rng.integers(0, m + 1)),
                            rng.dirichlet([0.3] * G)).astype(np.int32)
    gid, tid, starts, ends, num = (np.asarray(a) for a in gm.visits(
        jnp.asarray(sizes), m, tm))
    want = [(e, t) for e in range(G) if sizes[e]
            for t in range(int(starts[e]) // tm, (int(ends[e]) - 1) // tm + 1)]
    assert len(gid) == m // tm + G - 1 and int(num[0]) == len(want)
    assert list(zip(gid[:len(want)], tid[:len(want)])) == want
    if want:
        assert (gid[len(want):] == want[-1][0]).all()
        assert (tid[len(want):] == want[-1][1]).all()
    assert list(ends - starts) == list(sizes)


# -- the expert layer with the kernel against it with ragged_dot -------------

SHARE = {"hidden_size": 128, "head_dim": 16, "num_attention_heads": 2,
         "num_key_value_heads": 1, "intermediate_size": 128,
         "moe_intermediate_size": 256, "num_hidden_layers": 2,
         "num_dense_layers": 1, "num_experts": 2, "router_outputs": 16,
         "first_expert": 4, "num_experts_per_tok": 2, "route_scale": 2.448,
         "layer_types": ["sliding_attention", "full_attention"],
         "sliding_window": 16, "vocab_size": 256,
         "max_position_embeddings": 256, "rope_theta": 10000,
         "rms_norm_eps": 1e-5, "activation_dtype": "bfloat16"}
T = 48   # 96 pairs; 2 of 16 experts held: 12 on them expected


@pytest.mark.parametrize("held_pairs", [0, 10, 40, 90])
def test_the_expert_layer_with_the_kernel_is_the_one_with_ragged_dot(
        held_pairs, monkeypatch):
    cs = fam.dims(SHARE)
    tc = fam.transformer_config(SHARE, cs)
    rng = np.random.default_rng(held_pairs)
    lp = {n: jnp.asarray(rng.standard_normal(s) * 0.05, jnp.bfloat16)
          for n, s in (("experts_w_gate", (2, 128, 256)),
                       ("experts_w_up", (2, 128, 256)),
                       ("experts_w_down", (2, 256, 128)))}
    x = jnp.asarray(rng.standard_normal((T, 128)), jnp.float32)
    # routing: ``held_pairs`` of the 96 choices on the held experts 4 and 5
    # (each token's two choices differ), the rest on experts held elsewhere
    choices = rng.permutation(2 * T) < held_pairs
    idx = np.where(choices, 4, 9).reshape(T, 2)
    idx[:, 1] += 1
    w = jnp.asarray(rng.uniform(0.1, 1.0, (T, 2)), jnp.float32)
    valid = jnp.ones((T,), bool)
    got, sizes = prog.held_expert_sum(tc, lp, x, jnp.asarray(idx), w, valid)
    assert int(sizes.sum()) == held_pairs
    monkeypatch.setattr(prog, "grouped_matmul", jax.lax.ragged_dot)
    want, _ = prog.held_expert_sum(tc, lp, x, jnp.asarray(idx), w, valid)
    # The three products' outputs agree within an ulp, so what reaches
    # the sum may differ by the ulp of a bfloat16 activation (2^-8 of
    # values of order 0.1-1 here) times the down product's weights.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-3, rtol=2e-2)
    assert (float(np.abs(np.asarray(want)).max()) > 0.05) == (held_pairs > 0)
