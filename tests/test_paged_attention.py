"""Paged-attention parity gates (ISSUE 7 satellite): the ragged decode
kernel must match the jnp reference bit-for-tolerance across dtypes and
ragged batch shapes, and match flash attention / dense attention on
contiguous single-page layouts — the serving engine's numerical
foundation."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchdistx_tpu.ops import (
    flash_attention,
    paged_attention,
    paged_attention_reference,
)
from torchdistx_tpu.models.layers import default_attention


def _to_pool(tok_major):
    """Token-major pages [P, page, KV, D] -> the pool layout
    [P, KV, page, D] the kernel and the serving programs use."""
    return tok_major.transpose(0, 2, 1, 3)


def _rand_case(seed, *, B, H, KV, D, page, n_pages, maxp, lengths, dtype):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, H, D), dtype)
    kp = _to_pool(jnp.asarray(rng.randn(n_pages, page, KV, D), dtype))
    vp = _to_pool(jnp.asarray(rng.randn(n_pages, page, KV, D), dtype))
    # Page tables point at a shuffled, non-overlapping page assignment —
    # physical discontiguity is the point of the paged layout.
    perm = rng.permutation(n_pages - 1) + 1  # never the null page
    table = np.zeros((B, maxp), np.int32)
    flat = perm[: B * maxp].reshape(B, maxp)
    table[:, :] = flat
    return q, kp, vp, jnp.asarray(lengths, jnp.int32), jnp.asarray(table)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("H,KV", [(4, 4), (4, 2), (8, 1)])
def test_kernel_matches_reference_ragged(dtype, atol, H, KV):
    """Kernel == reference over a ragged batch (mixed lengths incl. a
    1-token and a full-capacity sequence), GQA/MQA/MHA head layouts."""
    B, D, page, maxp = 4, 16, 8, 3
    lengths = [1, page * maxp, 7, 13]
    q, kp, vp, lens, table = _rand_case(
        0, B=B, H=H, KV=KV, D=D, page=page, n_pages=16, maxp=maxp,
        lengths=lengths, dtype=dtype,
    )
    ref = paged_attention_reference(q, kp, vp, lens, table)
    out = paged_attention(q, kp, vp, lens, table)
    assert out.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=atol
    )


@pytest.mark.parametrize("page", [4, 16])
def test_kernel_matches_reference_page_sizes(page):
    B, H, KV, D, maxp = 3, 4, 2, 8, 4
    lengths = [page * maxp - 1, 2, page]
    q, kp, vp, lens, table = _rand_case(
        1, B=B, H=H, KV=KV, D=D, page=page, n_pages=32, maxp=maxp,
        lengths=lengths, dtype=jnp.float32,
    )
    ref = paged_attention_reference(q, kp, vp, lens, table)
    out = paged_attention(q, kp, vp, lens, table)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_idle_lane_outputs_zero():
    """A length-0 lane (idle batch slot) produces an all-zero kernel
    output row — the engine's padding contract."""
    q, kp, vp, _, table = _rand_case(
        2, B=2, H=4, KV=2, D=8, page=8, n_pages=8, maxp=2,
        lengths=[0, 5], dtype=jnp.float32,
    )
    out = paged_attention(q, kp, vp, jnp.asarray([0, 5], jnp.int32), table)
    assert np.all(np.asarray(out[0]) == 0.0)
    ref = paged_attention_reference(
        q, kp, vp, jnp.asarray([0, 5], jnp.int32), table
    )
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(ref[1]),
                               atol=1e-5)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-4),
                                        (jnp.bfloat16, 3e-2)])
def test_matches_flash_attention_contiguous_single_page(dtype, atol):
    """On a contiguous single-page layout (page b holds sequence b, all
    sequences full), decode output == flash attention's LAST-token
    causal output: the same math flash computes, reached through the
    page indirection."""
    B, S, H, KV, D = 3, 16, 4, 2, 16
    rng = np.random.RandomState(3)
    qf = jnp.asarray(rng.randn(B, S, H, D), dtype)
    k = jnp.asarray(rng.randn(B, S, KV, D), dtype)
    v = jnp.asarray(rng.randn(B, S, KV, D), dtype)
    table = jnp.arange(B, dtype=jnp.int32)[:, None]
    out = paged_attention(qf[:, -1], _to_pool(k), _to_pool(v),
                          jnp.full((B,), S, jnp.int32), table)
    fl = flash_attention(qf, k, v, causal=True)[:, -1]
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(fl, np.float32), atol=atol
    )


def test_matches_dense_attention_ragged_lengths():
    """For each ragged length L, decode of the L-th token == dense causal
    attention's output at position L-1 (the oracle the serving engine is
    pinned against)."""
    B, S, H, KV, D = 3, 24, 4, 2, 8
    page, maxp = 8, 3
    lengths = [5, 24, 17]
    rng = np.random.RandomState(4)
    qf = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, KV, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, KV, D), jnp.float32)
    # Lay each sequence's first `lengths[b]` tokens into its own pages.
    kp = np.zeros((1 + B * maxp, page, KV, D), np.float32)
    vp = np.zeros_like(kp)
    table = np.zeros((B, maxp), np.int32)
    for b in range(B):
        for j in range(maxp):
            pid = 1 + b * maxp + j
            table[b, j] = pid
            lo = j * page
            kp[pid, : max(0, min(page, S - lo))] = np.asarray(
                k[b, lo: lo + page])
            vp[pid, : max(0, min(page, S - lo))] = np.asarray(
                v[b, lo: lo + page])
    q_last = jnp.stack([qf[b, L - 1] for b, L in enumerate(lengths)])
    out = paged_attention(
        q_last, _to_pool(jnp.asarray(kp)), _to_pool(jnp.asarray(vp)),
        jnp.asarray(lengths, jnp.int32), jnp.asarray(table),
    )
    for b, L in enumerate(lengths):
        dense = default_attention(
            qf[b: b + 1, :L], k[b: b + 1, :L], v[b: b + 1, :L], causal=True
        )[0, -1]
        np.testing.assert_allclose(
            np.asarray(out[b]), np.asarray(dense), atol=1e-5,
            err_msg=f"lane {b} length {L}",
        )


def test_reference_gqa_grouping_matches_per_head_loop():
    """The reference's (kv, group) head packing equals a per-head dense
    computation — guards the layout identity both implementations share."""
    B, H, KV, D, page, maxp = 2, 4, 2, 8, 4, 2
    q, kp, vp, lens, table = _rand_case(
        5, B=B, H=H, KV=KV, D=D, page=page, n_pages=8, maxp=maxp,
        lengths=[6, 8], dtype=jnp.float32,
    )
    ref = paged_attention_reference(q, kp, vp, lens, table)
    groups = H // KV
    # Undo the pool layout by hand: [B, maxp, KV, page, D] -> token-major.
    k = kp[table].transpose(0, 1, 3, 2, 4).reshape(B, maxp * page, KV, D)
    v = vp[table].transpose(0, 1, 3, 2, 4).reshape(B, maxp * page, KV, D)
    for b in range(B):
        L = int(lens[b])
        for h in range(H):
            kv = h // groups
            logits = (np.asarray(q[b, h]) / np.sqrt(D)) @ np.asarray(
                k[b, :L, kv]).T
            p = np.exp(logits - logits.max())
            p /= p.sum()
            want = p @ np.asarray(v[b, :L, kv])
            np.testing.assert_allclose(np.asarray(ref[b, h]), want,
                                       atol=1e-5)


def test_shape_validation():
    q = jnp.zeros((2, 4, 8))
    kp = jnp.zeros((4, 2, 8, 8))
    lens = jnp.zeros((2,), jnp.int32)
    table = jnp.zeros((2, 2), jnp.int32)
    with pytest.raises(ValueError, match="multiple of KV heads"):
        paged_attention(jnp.zeros((2, 3, 8)), kp, kp, lens, table)
    with pytest.raises(ValueError, match="head_dim mismatch"):
        paged_attention(jnp.zeros((2, 4, 4)), kp, kp, lens, table)
    with pytest.raises(ValueError, match="batch mismatch"):
        paged_attention(q, kp, kp, lens, jnp.zeros((3, 2), jnp.int32))
