"""Ragged paged-attention decode kernel (pallas TPU) + jnp reference.

The serving decode step computes attention for ONE new query token per
sequence over that sequence's whole context, which lives scattered across
fixed-size pages of a preallocated device pool
(:mod:`torchdistx_tpu.serve.kv_cache`).  A batch of decoding sequences is
*ragged* — every sequence has a different context length — and the page
indirection means K/V for one sequence is not contiguous in HBM.  This is
the TPU-native formulation of Ragged Paged Attention (arXiv:2604.15464):

* grid = (batch x kv_heads, pages); TPU grids run sequentially, so the
  online-softmax accumulators carry across the page dimension in VMEM
  scratch exactly like the training flash kernels
  (:mod:`.flash_attention`);
* the per-sequence **page table** rides the scalar-prefetch channel
  (``PrefetchScalarGridSpec``): the K/V BlockSpec index maps read the
  page id for grid cell ``(b, j)`` from SMEM and fetch that page of the
  pool — the gather happens in the pipeline's DMA stage, never
  materializing a contiguous [B, T, KV, D] copy in HBM;
* raggedness is handled by the **lengths** vector (also prefetched):
  pages entirely past a sequence's length skip their FLOPs via
  ``pl.when`` (sequential grid ⇒ skipped cells are nearly free), and the
  tail page masks per-position, so compute scales with the batch's real
  token count, not ``B x max_pages x page_size``;
* GQA/MQA: the kernel processes one kv head's query-head *group* per
  grid row — K/V pages are fetched once per group, never broadcast; the
  group dim is padded to the f32 sublane tile (8) for Mosaic;
* all matmuls accumulate in f32 (``preferred_element_type``), outputs
  cast back to the query dtype.

``paged_attention_reference`` is the plain-jnp oracle (gather pages →
dense masked softmax); the parity tests pin kernel == reference across
dtypes and ragged shapes, and kernel == ``flash_attention``'s last-token
output on contiguous single-page layouts.  On non-TPU backends the
kernel runs in interpreter mode (decided and counted by
:func:`._interpret.resolve_interpret`), keeping the CPU suite meaningful.

Conventions shared with the serving engine:

* ``q``: [B, H, D] — one decode token per sequence;
* ``k_pages`` / ``v_pages``: [P, KV, page_size, D] — the global pool.
  The page's token rows and the head dim are the two minor dims, so one
  (page, kv head) block is ``(1, 1, page_size, D)``: its last two dims
  equal the array's, which is what the Mosaic lowering requires of a
  block for every page size, head dim and dtype the repo serves (a
  ``[P, page, KV, D]`` pool would put one kv head on the second-minor
  dim — a block Mosaic refuses whenever ``KV > 1``);
* ``lengths``: [B] int32 — tokens of context per sequence INCLUDING the
  one ``q`` belongs to (its K/V must already be written to its page);
* ``page_table``: [B, max_pages] int32 — pool page ids per sequence, in
  order; entries past ``ceil(lengths[b] / page_size)`` are never read.
  A sequence with ``lengths[b] == 0`` (an idle batch slot) produces a
  zero output row in the kernel; the reference softmaxes uniform masked
  logits there instead — callers must ignore idle rows.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._interpret import resolve_interpret

_NEG = -1e30
_LANES = 128  # lane-broadcast scratch carriers, like flash_attention
_SUBLANES = 8  # f32 sublane tile: the query-group dim is padded to this


def _gather_context(pages: jax.Array, page_table: jax.Array) -> jax.Array:
    """[P, KV, page, D] pool + [B, max_pages] table -> the sequences'
    contiguous contexts [B, KV, max_pages * page, D] in f32."""
    B, maxp = page_table.shape
    _, KV, page, D = pages.shape
    ctx = pages[page_table]  # [B, maxp, KV, page, D]
    ctx = ctx.transpose(0, 2, 1, 3, 4).reshape(B, KV, maxp * page, D)
    return ctx.astype(jnp.float32)


def paged_attention_reference(
    q: jax.Array,  # [B, H, D]
    k_pages: jax.Array,  # [P, KV, page, D]
    v_pages: jax.Array,  # [P, KV, page, D]
    lengths: jax.Array,  # [B] int32
    page_table: jax.Array,  # [B, max_pages] int32
) -> jax.Array:
    """Dense jnp oracle: gather the mapped pages, mask past ``lengths``,
    f32 softmax — numerically the same computation as
    ``default_attention`` on the gathered layout."""
    B, H, D = q.shape
    KV = k_pages.shape[1]
    groups = H // KV

    k = _gather_context(k_pages, page_table)  # [B, KV, T, D]
    v = _gather_context(v_pages, page_table)
    T = k.shape[2]
    qf = q.astype(jnp.float32) * (1.0 / math.sqrt(D))
    qf = qf.reshape(B, KV, groups, D)
    logits = jnp.einsum("bkgd,bktd->bkgt", qf, k)
    mask = jnp.arange(T)[None, :] < lengths[:, None]  # [B, T]
    logits = jnp.where(mask[:, None, None], logits, _NEG)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgt,bktd->bkgd", probs, v)
    return out.reshape(B, H, D).astype(q.dtype)


def paged_prefill_attention(
    q: jax.Array,  # [B, S, H, D] — a chunk of query tokens per sequence
    k_pages: jax.Array,  # [P, KV, page, D]
    v_pages: jax.Array,  # [P, KV, page, D]
    q_positions: jax.Array,  # [B, S] int32 — absolute positions of q
    lengths: jax.Array,  # [B] int32 — valid context INCLUDING the chunk
    page_table: jax.Array,  # [B, max_pages] int32
) -> jax.Array:
    """Chunked-prefill attention through the page table: each query at
    absolute position ``t`` attends every cached position ``<= t`` — the
    already-written prefix pages (a shared system prompt, earlier
    chunks) plus the chunk's own causal context, whose K/V the caller
    scattered into the pool before calling.  Gather-based jnp like
    :func:`paged_attention_reference`; positions at or past
    ``lengths[b]`` are padding — their rows are garbage and must be
    ignored by the caller (position 0 always satisfies the mask, so no
    row softmaxes over an empty set)."""
    B, S, H, D = q.shape
    KV = k_pages.shape[1]
    groups = H // KV

    k = _gather_context(k_pages, page_table)  # [B, KV, T, D]
    v = _gather_context(v_pages, page_table)
    T = k.shape[2]
    qf = q.astype(jnp.float32) * (1.0 / math.sqrt(D))
    qf = qf.reshape(B, S, KV, groups, D)
    logits = jnp.einsum("bskgd,bktd->bskgt", qf, k)
    tpos = jnp.arange(T)[None, None, :]
    mask = (tpos <= q_positions[:, :, None]) & (
        tpos < lengths[:, None, None]
    )  # [B, S, T]
    logits = jnp.where(mask[:, :, None, None, :], logits, _NEG)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bskgt,bktd->bskgd", probs, v)
    return out.reshape(B, S, H, D).astype(q.dtype)


def _decode_kernel(
    lengths_ref,  # SMEM [B] i32 (scalar prefetch)
    table_ref,  # SMEM [B, max_pages] i32 (scalar prefetch)
    q_ref,  # [1, Gp, D]
    k_ref,  # [1, 1, page, D] — the (page, kv head) the index map selected
    v_ref,  # [1, 1, page, D]
    o_ref,  # [1, Gp, D]
    acc_ref,  # VMEM [Gp, D] f32
    m_ref,  # VMEM [Gp, _LANES] f32
    l_ref,  # VMEM [Gp, _LANES] f32
    *,
    kv_heads: int,
    page_size: int,
    sm_scale: float,
):
    i = pl.program_id(0)  # b * KV + kv
    j = pl.program_id(1)  # page ordinal within the sequence
    npages = pl.num_programs(1)
    b = i // kv_heads
    seq_len = lengths_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(j * page_size < seq_len)
    def _page():
        q = q_ref[0].astype(jnp.float32) * sm_scale  # [Gp, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [page, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [Gp, page]
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[0], page_size), 1
        )
        mask = pos < seq_len
        s = jnp.where(mask, s, _NEG)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p,
            v_ref[0, 0].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [Gp, D]
        acc_ref[:] = acc_ref[:] * corr + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == npages - 1)
    def _finish():
        # lengths == 0 (idle slot) never accumulated: l stays 0, out 0.
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


def paged_attention(
    q: jax.Array,  # [B, H, D]
    k_pages: jax.Array,  # [P, KV, page, D]
    v_pages: jax.Array,  # [P, KV, page, D]
    lengths: jax.Array,  # [B] int32
    page_table: jax.Array,  # [B, max_pages] int32
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Ragged paged-attention decode: one query token per sequence
    against its page-table-mapped context.  See the module docstring for
    the layout contract; output is [B, H, D] in ``q``'s dtype."""
    B, H, D = q.shape
    P, KV, page_size, Dk = k_pages.shape
    if Dk != D:
        raise ValueError(f"head_dim mismatch: q has {D}, pages have {Dk}")
    if v_pages.shape != k_pages.shape:
        raise ValueError(
            f"k_pages {k_pages.shape} != v_pages {v_pages.shape}"
        )
    if H % KV:
        raise ValueError(
            f"Query heads ({H}) must be a multiple of KV heads ({KV})."
        )
    if page_table.shape[0] != B or lengths.shape != (B,):
        raise ValueError(
            f"batch mismatch: q {B}, page_table {page_table.shape}, "
            f"lengths {lengths.shape}"
        )
    groups = H // KV
    maxp = page_table.shape[1]
    interpret = resolve_interpret(interpret)
    sm_scale = 1.0 / math.sqrt(D)

    # [B, H, D] -> [B*KV, Gp, D]: head h of sequence b is (kv = h //
    # groups)'s group row g = h % groups — the flash kernels' layout
    # identity.  The group dim is padded to the f32 sublane tile; padded
    # rows are zero queries whose outputs are sliced off.
    gp = max(_SUBLANES, ((groups + _SUBLANES - 1) // _SUBLANES) * _SUBLANES)
    qh = q.reshape(B, KV, groups, D).reshape(B * KV, groups, D)
    if gp != groups:
        qh = jnp.pad(qh, ((0, 0), (0, gp - groups), (0, 0)))

    grid = (B * KV, maxp)
    # Index maps see the scalar-prefetch refs after the grid indices; the
    # page id for (sequence, page ordinal) comes straight from SMEM.
    kv_spec = pl.BlockSpec(
        (1, 1, page_size, D),
        lambda i, j, lens, table: (table[i // KV, j], i % KV, 0, 0),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, gp, D), lambda i, j, lens, table: (i, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, gp, D), lambda i, j, lens, table: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((gp, D), jnp.float32),
            pltpu.VMEM((gp, _LANES), jnp.float32),
            pltpu.VMEM((gp, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel,
            kv_heads=KV,
            page_size=page_size,
            sm_scale=sm_scale,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * KV, gp, D), q.dtype),
        interpret=interpret,
        name="tdx_paged_attention_decode",
    )(lengths.astype(jnp.int32), page_table.astype(jnp.int32), qh,
      k_pages, v_pages)
    return out[:, :groups].reshape(B, KV * groups, D)
