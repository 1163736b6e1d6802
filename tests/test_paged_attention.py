"""Paged-attention parity gates (ISSUE 7 satellite): the ragged decode
kernel must match the jnp reference bit-for-tolerance across dtypes and
ragged batch shapes, and match flash attention / dense attention on
contiguous single-page layouts — the serving engine's numerical
foundation."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from torchdistx_tpu.ops import (
    flash_attention,
    kv_blocks_walked,
    pages_per_block,
    paged_attention,
    paged_attention_reference,
)
from torchdistx_tpu.models.layers import default_attention

# ``ops.paged_attention`` the attribute is the function; this is the module.
pa = importlib.import_module("torchdistx_tpu.ops.paged_attention")

# The reference compiled once a shape: run eagerly, each of its dozen
# operations would compile on its own at every new shape.
_reference = jax.jit(paged_attention_reference,
                     static_argnames="v_page_offset")


def _to_pool(tok_major):
    """Token-major pages [P, page, KV, D] -> the pool layout
    [P, KV, page, D] the kernel and the serving programs use."""
    return tok_major.transpose(0, 2, 1, 3)


def _assert_live_rows_match(out, ref, lengths, atol):
    """Kernel == reference on every lane that attends anything (an idle
    lane's reference row is a uniform softmax, its kernel row zero)."""
    live = np.asarray(lengths) > 0
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    assert np.isfinite(out).all()
    assert np.all(out[~live] == 0.0)
    np.testing.assert_allclose(out[live], ref[live], atol=atol)


def _rand_case(seed, *, B, H, KV, D, page, n_pages, maxp, lengths, dtype):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, H, D), dtype)
    kp = jnp.asarray(_to_pool(rng.randn(n_pages, page, KV, D)), dtype)
    vp = jnp.asarray(_to_pool(rng.randn(n_pages, page, KV, D)), dtype)
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
    ref = _reference(q, kp, vp, lens, table)
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
    ref = _reference(q, kp, vp, lens, table)
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
    ref = _reference(q, kp, vp, jnp.asarray([0, 5], jnp.int32), table)
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
    ref = _reference(q, kp, vp, lens, table)
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


# -- the walk (PR 29): blocks of whole pages, as far as the length ----------
#
# A head dim of 128 so that a block holds many pages, as on the chip (a
# toy head dim walks a page a block, which the cases above cover), and
# one kv head of float32 so that the pools stay small: 32 pages of 16, or
# 128 of 4, make the 512 tokens of a block.

WALK = dict(H=2, KV=1, D=128, dtype=jnp.float32)


def _span(page, KV=1, D=128, dtype=jnp.float32):
    return pages_per_block(KV, page, D, dtype) * page


@pytest.mark.parametrize("blocks,off", [(1, -1), (1, 0), (1, 1), (2, -1),
                                        (2, 0), (2, 1)])
def test_walk_at_block_boundaries(blocks, off):
    """A length of ``k x block + {-1, 0, 1}`` tokens beside a one-token
    lane: the last block holds all but one of its rows, all of them, or
    one row of one page."""
    page = 16
    n = blocks * _span(page) + off
    maxp = -(-n // page) + 1
    q, kp, vp, lens, table = _rand_case(
        10 + blocks, B=2, page=page, n_pages=2 * maxp + 1, maxp=maxp,
        lengths=[n, 1], **WALK)
    ref = _reference(q, kp, vp, lens, table)
    out = paged_attention(q, kp, vp, lens, table)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("page", [4, 16])
def test_walk_table_width_no_multiple_of_the_block(page):
    """``max_pages`` = one block and five pages, every page in use: the
    second block is five pages long and the walk stops there."""
    maxp = pages_per_block(1, page, 128, jnp.float32) + 5
    q, kp, vp, lens, table = _rand_case(
        20, B=2, page=page, n_pages=2 * maxp + 1, maxp=maxp,
        lengths=[maxp * page, maxp * page - page - 1], **WALK)
    ref = _reference(q, kp, vp, lens, table)
    out = paged_attention(q, kp, vp, lens, table)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("lengths", [
    [0, 16, "full"],           # idle, one page, the whole table
    ["full", 0, 0, 3],         # idle lanes between live ones
    [0, 0, 600],               # idle lanes first: nobody fetched for them
    [5, 0],                    # an idle lane last
], ids=["idle-page-full", "idle-between", "idle-first", "idle-last"])
def test_walk_mixed_batch(lengths):
    """An idle lane passes the fetch of the next lane's first block on,
    writes a zero row and leaves the slots' order intact."""
    page, maxp = 16, 40
    lengths = [maxp * page if n == "full" else n for n in lengths]
    B = len(lengths)
    q, kp, vp, lens, table = _rand_case(
        21, B=B, page=page, n_pages=B * maxp + 1, maxp=maxp,
        lengths=lengths, **WALK)
    ref = _reference(q, kp, vp, lens, table)
    out = paged_attention(q, kp, vp, lens, table)
    _assert_live_rows_match(out, ref, lengths, 1e-5)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("H,KV,D", [(20, 1, 128),   # Jamba: Gp 24
                                    (32, 8, 128),   # Mistral / Llama-3
                                    (4, 4, 64),     # GPT-2: MHA, 64
                                    (30, 30, 128)],  # Olmo-Hybrid: group 1
                         ids=["jamba", "mistral", "gpt2", "olmo-hybrid"])
def test_walk_head_layouts_of_the_served_families(H, KV, D, dtype, atol):
    page, maxp = 16, 36
    lengths = [maxp * page, 0, 530, 17]
    q, kp, vp, lens, table = _rand_case(
        22, B=4, H=H, KV=KV, D=D, page=page, n_pages=4 * maxp + 1,
        maxp=maxp, lengths=lengths, dtype=dtype)
    ref = _reference(q, kp, vp, lens, table)
    out = paged_attention(q, kp, vp, lens, table)
    assert out.dtype == q.dtype
    _assert_live_rows_match(out, ref, lengths, atol)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 3e-2)])
def test_page_kernel_matches_reference(dtype, atol):
    """The kernel a head dim of 64 gets on the chip, where Mosaic cannot
    slice the pool (the interpreter walks every head dim, so it is asked
    for here)."""
    page, maxp = 16, 5
    lengths = [maxp * page, 0, 1, 37]
    q, kp, vp, lens, table = _rand_case(
        23, B=4, H=4, KV=4, D=64, page=page, n_pages=4 * maxp + 1,
        maxp=maxp, lengths=lengths, dtype=dtype)
    ref = _reference(q, kp, vp, lens, table)
    out = pa._paged_attention(q, kp, vp, lens, table, 1, True, walk=False)
    _assert_live_rows_match(out, ref, lengths, atol)


def test_walk_flat_pool_with_a_layer_base():
    """The serving programs' call: the layers' pools flat, ``[L x P, KV,
    page, D]``, and the table offset by the layer's base."""
    page, maxp, L = 16, 34, 3
    B, P = 2, 2 * maxp + 1
    lengths = [maxp * page - 3, 20]
    q, kp, vp, lens, table = _rand_case(
        24, B=B, page=page, n_pages=L * P, maxp=maxp, lengths=lengths,
        **WALK)
    table = table % P  # page ids of one layer
    for layer in range(L):
        base = layer * P
        ref = _reference(
            q, kp[base:base + P], vp[base:base + P], lens, table)
        out = paged_attention(q, kp, vp, lens, table + base)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)


@pytest.mark.parametrize("walk", [True, False], ids=["walk", "page-kernel"])
def test_nothing_past_the_length_is_touched(walk):
    """Table entries past ``ceil(length / page)`` point outside the pool,
    every page no sequence maps holds NaN, and VMEM starts as NaN: the
    call raises on no out-of-bounds read and returns finite rows that
    match the reference (which gets a table it can gather)."""
    page, maxp, D = 16, 40, (128 if walk else 64)
    lengths = [0, 1, 16, 17, 600, 5]
    B = len(lengths)
    q, kp, vp, lens, table = _rand_case(
        25, B=B, H=2, KV=1, D=D, page=page, n_pages=B * maxp + 1, maxp=maxp,
        lengths=lengths, dtype=jnp.float32)
    used = np.arange(maxp)[None, :] < -(-np.asarray(lengths) // page)[:, None]
    table = np.asarray(table)
    mapped = np.zeros(kp.shape[0], bool)
    mapped[table[used]] = True
    kp = jnp.where(mapped[:, None, None, None], kp, jnp.nan)
    vp = jnp.where(mapped[:, None, None, None], vp, jnp.nan)
    wild = jnp.asarray(np.where(used, table, 10 ** 6), jnp.int32)
    out = pa._paged_attention(
        q, kp, vp, lens, wild, pages_per_block(1, page, D, jnp.float32),
        pltpu.InterpretParams(out_of_bounds_reads="raise",
                              uninitialized_memory="nan"), walk=walk)
    ref = _reference(
        q, jnp.nan_to_num(kp), jnp.nan_to_num(vp), lens, jnp.asarray(table))
    _assert_live_rows_match(out, ref, lengths, 1e-5)


def test_block_arithmetic_against_a_hand_count():
    """``pages_per_block`` for the pools the repo serves, and the blocks
    a batch walks, counted by hand."""
    # 8 kv heads x 16 rows x 128 x 2 B = 32 KB a page and pool; four
    # buffers of 32 pages are 4 MiB, and 32 pages are the 512 tokens.
    assert pages_per_block(8, 16, 128, jnp.bfloat16) == 32
    # One kv head: 4 KB a page, the 512 tokens bind.
    assert pages_per_block(1, 16, 128, jnp.bfloat16) == 32
    # float32 doubles the page: the bytes bind at 16 pages.
    assert pages_per_block(8, 16, 128, jnp.float32) == 16
    # 32 kv heads (an MHA pool of 128): 128 KB a page, 8 pages.
    assert pages_per_block(32, 16, 128, jnp.bfloat16) == 8
    # A bfloat16 page of 8 rows fills a 16-row tile all the same.
    assert pages_per_block(16, 8, 128, jnp.bfloat16) == 16
    # A head dim Mosaic cannot slice goes a page at a time.
    assert pages_per_block(25, 16, 64, jnp.bfloat16) == 1
    assert pages_per_block(2, 8, 16, jnp.float32) == 1
    # 512 tokens a block: 0, 1 and 512 tokens are 0, 1 and 1 blocks, 513
    # are 2, 1,025 are 3.
    assert kv_blocks_walked([0, 1, 512, 513, 1025], 16, 8, 128,
                            jnp.bfloat16) == 7
    assert kv_blocks_walked(np.array([245] * 32), 16, 8, 128,
                            jnp.bfloat16) == 32
    # A page a block: the pages under the lengths.
    assert kv_blocks_walked([0, 1, 16, 17], 16, 25, 64, jnp.bfloat16) == 4
    assert kv_blocks_walked([], 16, 8, 128, jnp.bfloat16) == 0


def test_length_past_the_table_is_held_to_the_table():
    """A length the table cannot hold walks the table and no further, as
    the reference's mask does."""
    page, maxp = 16, 3
    q, kp, vp, _, table = _rand_case(
        26, B=2, page=page, n_pages=8, maxp=maxp, lengths=[0, 0], **WALK)
    lens = jnp.asarray([maxp * page + 40, 9], jnp.int32)
    ref = _reference(q, kp, vp, lens, table)
    out = paged_attention(q, kp, vp, lens, table)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# -- a first attended position, and keys and values in one pool (PR 34) -------


@pytest.mark.parametrize("ppb", [1, 2, 4])
@pytest.mark.parametrize("v_off", [0, 96])
def test_walk_from_a_first_position(ppb, v_off):
    """A window layer's call: the walk begins at the block that holds
    ``starts[b]`` and positions below it weigh nothing; with ``v_off`` the
    values lie that many pages behind the keys in ONE array."""
    from torchdistx_tpu.ops.paged_attention import _paged_attention

    rng = np.random.RandomState(7)
    B, H, KV, D, page, maxp = 6, 6, 1, 16, 8, 12
    pool = jnp.asarray(rng.randn(2 * 96, KV, page, D), jnp.float32)
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    lengths = jnp.asarray([0, 37, 96, 5, 64, 17], jnp.int32)
    # inside a block, at a block's edge, at 0, at the last position
    starts = jnp.asarray([0, 21, 80, 0, 63, 16], jnp.int32)
    table = jnp.asarray(rng.randint(1, 96, (B, maxp)), jnp.int32)
    out = _paged_attention(q, pool, pool, lengths, table, ppb, True,
                           starts=starts, v_page_offset=v_off)
    ref = _reference(q, pool, pool, lengths, table, starts=starts,
                     v_page_offset=v_off)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(ref)[live],
                               atol=1e-5)
    assert np.all(np.asarray(out)[~live] == 0.0)
    # and it is NOT the whole context: the mask is doing something
    whole = _reference(q, pool, pool, lengths, table, v_page_offset=v_off)
    assert np.abs(np.asarray(whole)[1] - np.asarray(ref)[1]).max() > 1e-3


def test_a_first_position_of_zero_is_the_kernel_without_one():
    """Mistral's and Jamba's calls pass no first position and trace the
    kernel they traced before there were windows: two scalar operands."""
    q, kp, vp, lengths, table = _rand_case(
        3, B=3, H=4, KV=2, D=16, page=8, n_pages=32, maxp=4,
        lengths=[9, 0, 30], dtype=jnp.float32)
    zero = jnp.zeros((3,), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(paged_attention(q, kp, vp, lengths, table)),
        np.asarray(paged_attention(q, kp, vp, lengths, table, starts=zero)))
    plain = jax.make_jaxpr(lambda *a: paged_attention(*a))(
        q, kp, vp, lengths, table)
    with_starts = jax.make_jaxpr(
        lambda *a: paged_attention(*a[:5], starts=a[5]))(
        q, kp, vp, lengths, table, zero)
    n_prefetch = lambda j: [
        e.params["grid_mapping"].num_index_operands for e in j.eqns
        if e.primitive.name == "pallas_call"]
    assert n_prefetch(plain) == [2] and n_prefetch(with_starts) == [3]


def test_a_first_position_needs_the_walk():
    q, kp, vp, lengths, table = _rand_case(
        4, B=2, H=4, KV=2, D=64, page=8, n_pages=16, maxp=2,
        lengths=[9, 12], dtype=jnp.float32)
    from torchdistx_tpu.ops.paged_attention import _paged_attention

    with pytest.raises(NotImplementedError, match="needs the walk"):
        _paged_attention(q, kp, vp, lengths, table, 1, False, walk=False,
                         starts=jnp.zeros((2,), jnp.int32))
    with pytest.raises(ValueError, match="one a sequence"):
        paged_attention(q, kp, vp, lengths, table,
                        starts=jnp.zeros((3,), jnp.int32))


def test_prefill_attention_masks_by_the_window_and_gathers_only_its_row():
    """A chunk on a window layer: positions counted from the row's first
    page; the result is the dense computation over the whole sequence with
    the window's mask."""
    from torchdistx_tpu.ops import paged_prefill_attention

    rng = np.random.RandomState(11)
    H, KV, D, page, W = 4, 1, 16, 4, 12
    T, S, s0 = 40, 8, 32                       # the chunk is [32, 40)
    k = rng.randn(T, KV, D).astype(np.float32)
    v = rng.randn(T, KV, D).astype(np.float32)
    q = rng.randn(1, S, H, D).astype(np.float32)
    # the row holds pages of positions 20.. (first live page: 20 // 4 = 5)
    first = (s0 - W + 1) // page * page        # 20
    n_live = (T - first) // page               # 5 pages
    pool = np.zeros((2 * 16, KV, page, D), np.float32)
    ids = np.asarray([7, 3, 9, 12, 5])
    for i, pid in enumerate(ids):
        rows = slice(first + i * page, first + (i + 1) * page)
        pool[pid] = k[rows].transpose(1, 0, 2)
        pool[16 + pid] = v[rows].transpose(1, 0, 2)
    table = np.zeros((1, 7), np.int32)
    table[0, :n_live] = ids
    pos = (s0 + np.arange(S))[None] - first
    out = paged_prefill_attention(
        jnp.asarray(q), jnp.asarray(pool), jnp.asarray(pool),
        jnp.asarray(pos, jnp.int32), jnp.asarray([T - first], jnp.int32),
        jnp.asarray(table), window=W, v_page_offset=16)
    # dense, over the whole sequence
    sc = np.einsum("shd,tkd->hst", q[0], k) / np.sqrt(D)
    i, j = (s0 + np.arange(S))[:, None], np.arange(T)[None]
    sc = np.where((j <= i) & (i - j < W), sc, -1e30)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("hst,tkd->shd", p, v)
    np.testing.assert_allclose(np.asarray(out)[0], want, atol=1e-5)
