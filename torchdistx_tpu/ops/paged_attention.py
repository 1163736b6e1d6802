"""Ragged paged-attention decode kernel (pallas TPU) + jnp reference.

The serving decode step computes attention for ONE new query token per
sequence over that sequence's whole context, which lives scattered across
fixed-size pages of a preallocated device pool
(:mod:`torchdistx_tpu.serve.kv_cache`).  A batch of decoding sequences is
*ragged* — every sequence has a different context length — and the page
indirection means K/V for one sequence is not contiguous in HBM.  This is
the TPU-native formulation of Ragged Paged Attention (arXiv:2604.15464;
jax's own ``pallas/ops/tpu/paged_attention`` walks the same way over
another pool layout):

* grid = (batch,); TPU grids run sequentially.  Inside a step the
  kernel **walks the sequence's real pages**: a loop whose trip count,
  ``ceil(lengths[b] / block tokens)``, is read from SMEM.  The work
  follows the lengths, not the page table's width: an idle lane starts
  no copy and writes a zero row, and table entries past
  ``ceil(lengths[b] / page_size)`` are never read;
* a step of the walk moves **several whole pages**: the pools stay in
  HBM (``memory_space=pl.ANY``) and one ``make_async_copy`` a page
  brings ``k_pages[table[b, j]]`` — a ``[KV, page, D]`` row of the
  pool, every kv head of the page, contiguous — into one of two VMEM
  slots, K and V.  Block ``n + 1`` is in flight while block ``n`` is
  computed, and a sequence's first block is fetched under the last
  block of the sequence before it.  The **page table** and the
  **lengths** ride the scalar-prefetch channel, so the gather happens
  in the copies, never materializing a contiguous [B, T, KV, D] in HBM;
* :func:`pages_per_block` derives the block from what the kernel sees
  (kv heads, page size, head dim, dtype): the largest power of two
  whose four buffers fit a fixed VMEM budget and a fixed number of
  tokens.  One algorithm for every caller; :func:`kv_blocks_walked` is
  the same arithmetic on host lengths, for the engine's counter;
* why (PERF.md, PR 29): through PR 28 the grid was ``(B x KV,
  max_pages)`` and a grid step fetched one ``(1, 1, page, D)`` block —
  4 KB — whether or not the sequence reached that page.  The kernel's
  time was the pipeline's per-step cost times ``B x KV x max_pages``
  steps (0.13–0.18 us each, 12,288 to 66,560 a layer): 1.5 % and 0.7 %
  of what the bytes need on a v5e;
* online softmax in f32 (running max, sum and accumulator in VMEM
  scratch across the blocks of a sequence), the tail block masked per
  position, all kv heads through each product together: the heads'
  matmuls are independent, so the unit pipelines them, and the softmax
  between them is one vectorised pass;
* GQA/MQA: K/V are fetched once per kv head and attended by the head's
  whole query group, never broadcast; the group dim is padded to the
  f32 sublane tile (8) for Mosaic;
* all matmuls take f32 operands and accumulate in f32
  (``preferred_element_type``), outputs cast back to the query dtype;
* a head dim that is no multiple of the 128 lanes (GPT-2's 64) cannot
  be walked on the chip: Mosaic (jax 0.9.0) refuses to slice a ref with
  such a minor dim, so no copy can name a page.  There the pages come
  through the BlockSpec pipeline as before, one (page, kv head) a grid
  step (``_page_kernel``; ``pages_per_block`` is 1).  The interpreter
  has no such limit, so tier-1's toy head dims still walk, a page a
  block.

``paged_attention_reference`` is the plain-jnp oracle (gather pages →
dense masked softmax); the parity tests pin kernel == reference across
dtypes, ragged shapes and block boundaries, and kernel ==
``flash_attention``'s last-token output on contiguous single-page
layouts.  On non-TPU backends the kernel runs in interpreter mode
(decided and counted by :func:`._interpret.resolve_interpret`), keeping
the CPU suite meaningful; ``tools/paged_attention_chip.py`` is the
on-chip parity and timing sweep.

Conventions shared with the serving engine:

* ``q``: [B, H, D] — one decode token per sequence;
* ``k_pages`` / ``v_pages``: [P, KV, page_size, D] — the global pool.
  The page's token rows and the head dim are the two minor dims, so a
  row of the pool is one page with all its kv heads, contiguous: what
  one copy of the walk moves.  (It is also what makes the fallback's
  ``(1, 1, page_size, D)`` block legal for Mosaic: a ``[P, page, KV,
  D]`` pool would put one kv head on the second-minor dim.)
* ``lengths``: [B] int32 — tokens of context per sequence INCLUDING the
  one ``q`` belongs to (its K/V must already be written to its page);
* ``page_table``: [B, max_pages] int32 — pool page ids per sequence, in
  order; entries past ``ceil(lengths[b] / page_size)`` are never read.
  A sequence with ``lengths[b] == 0`` (an idle batch slot) produces a
  zero output row in the kernel; the reference softmaxes uniform masked
  logits there instead — callers must ignore idle rows.
* ``starts`` (optional): [B] int32 — the first attended position of
  each sequence (a sliding-window layer: ``max(0, length - window)``);
  the walk begins at the block that holds it and positions below it
  weigh exactly zero.  Positions are the TABLE's: entry 0 of a row is
  the page of positions ``[0, page_size)``, so a caller whose row holds
  only the live pages of a window passes lengths and starts counted
  from its row's first page (the keys carry their own rotary term;
  attention itself knows no absolute position).  Without ``starts`` the
  kernel is the one it was: no operand, no mask, no arithmetic more.
* ``v_page_offset`` (static): added to a page id to find the page's
  VALUES, for a pool that keeps keys and values in one array (the
  window group, ``serve/kv_cache.py``); pass that array twice.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._interpret import resolve_interpret

_NEG = -1e30
_LANES = 128  # lane-broadcast scratch carriers, like flash_attention
_SUBLANES = 8  # f32 sublane tile: the query-group dim is padded to this
# What bounds a block of the decode kernel's walk (``pages_per_block``):
# the VMEM of its four buffers, and its tokens, which the tail block of
# a sequence computes whole.
_BLOCK_VMEM_BYTES = 4 * 1024 * 1024
_BLOCK_TOKENS = 512


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
    *,
    starts: Optional[jax.Array] = None,  # [B] int32
    v_page_offset: int = 0,
) -> jax.Array:
    """Dense jnp oracle: gather the mapped pages, mask past ``lengths``
    (and below ``starts``), f32 softmax — numerically the same
    computation as ``default_attention`` on the gathered layout."""
    B, H, D = q.shape
    KV = k_pages.shape[1]
    groups = H // KV

    k = _gather_context(k_pages, page_table)  # [B, KV, T, D]
    v = _gather_context(v_pages, page_table + v_page_offset)
    T = k.shape[2]
    qf = q.astype(jnp.float32) * (1.0 / math.sqrt(D))
    qf = qf.reshape(B, KV, groups, D)
    logits = jnp.einsum("bkgd,bktd->bkgt", qf, k)
    mask = jnp.arange(T)[None, :] < lengths[:, None]  # [B, T]
    if starts is not None:
        mask &= jnp.arange(T)[None, :] >= starts[:, None]
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
    *,
    window: Optional[int] = None,
    v_page_offset: int = 0,
) -> jax.Array:
    """Chunked-prefill attention through the page table: each query at
    absolute position ``t`` attends every cached position ``<= t`` — the
    already-written prefix pages (a shared system prompt, earlier
    chunks) plus the chunk's own causal context, whose K/V the caller
    scattered into the pool before calling.  Gather-based jnp like
    :func:`paged_attention_reference`; positions at or past
    ``lengths[b]`` are padding — their rows are garbage and must be
    ignored by the caller (position 0 always satisfies the mask, so no
    row softmaxes over an empty set).  With ``window`` a query attends
    only the ``window`` positions up to its own (``0 <= t - j < window``),
    so a caller whose table row holds only a window's live pages passes
    positions and lengths counted from the row's first page, as for
    :func:`paged_attention`'s ``starts``, and gathers ``chunk + window``
    keys, not the context; ``v_page_offset`` as there."""
    B, S, H, D = q.shape
    KV = k_pages.shape[1]
    groups = H // KV

    k = _gather_context(k_pages, page_table)  # [B, KV, T, D]
    v = _gather_context(v_pages, page_table + v_page_offset)
    T = k.shape[2]
    qf = q.astype(jnp.float32) * (1.0 / math.sqrt(D))
    qf = qf.reshape(B, S, KV, groups, D)
    logits = jnp.einsum("bskgd,bktd->bskgt", qf, k)
    tpos = jnp.arange(T)[None, None, :]
    mask = (tpos <= q_positions[:, :, None]) & (
        tpos < lengths[:, None, None]
    )  # [B, S, T]
    if window is not None:
        mask &= q_positions[:, :, None] - tpos < window
    logits = jnp.where(mask[:, :, None, None, :], logits, _NEG)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bskgt,bktd->bskgd", probs, v)
    return out.reshape(B, S, H, D).astype(q.dtype)


def pages_per_block(kv_heads: int, page_size: int, head_dim: int,
                    dtype) -> int:
    """Pages the decode kernel moves and computes in one step of its
    walk: the largest power of two whose four VMEM buffers (K and V, two
    slots each, as Mosaic tiles them) stay under ``_BLOCK_VMEM_BYTES``
    and whose tokens stay under ``_BLOCK_TOKENS``, at least one.  A
    function of the pool's own shape and dtype, so every caller gets the
    block its pages allow: 32 pages of 16 tokens for 8 kv heads of 128
    in bfloat16 (the bytes and the tokens both say so), 32 for one kv
    head (the tokens), 1 for a head dim of 64 (``_page_kernel``)."""
    if head_dim % _LANES:
        return 1
    itemsize = jnp.dtype(dtype).itemsize
    tile = _SUBLANES * max(1, 4 // itemsize)  # rows of one VMEM tile
    page_bytes = kv_heads * -(-page_size // tile) * tile * head_dim * itemsize
    n = min(_BLOCK_VMEM_BYTES // (4 * page_bytes),
            _BLOCK_TOKENS // page_size)
    return 1 << (max(1, n).bit_length() - 1)


def kv_blocks_walked(lengths, page_size: int, kv_heads: int, head_dim: int,
                     dtype) -> int:
    """Blocks one call of the decode kernel walks for these context
    ``lengths`` (host integers): ``ceil(length / block tokens)`` summed
    over the sequences, nothing for an idle one — the kernel's own trip
    counts, for the engine's ``kv_blocks``."""
    span = pages_per_block(kv_heads, page_size, head_dim, dtype) * page_size
    return int(np.sum(-(-np.asarray(lengths, np.int64) // span)))


def _attend_block(q_ref, k, v, pos0, seq_len, acc_ref, m_ref, l_ref,
                  sm_scale, start=None):
    """One step of the online softmax, every kv head at once: the block's
    keys and values ``k`` / ``v`` [KV, T, D], whose first row is position
    ``pos0`` of a sequence of ``seq_len``, against the heads' query groups
    ``q_ref[0]`` [KV, Gp, D].  The heads go through each product together
    (independent matmuls, which the unit pipelines) and through one
    vectorised softmax between them.  Everything in float32; rows at or
    past the length (and, with ``start``, below it) weigh exactly zero."""
    q = q_ref[0].astype(jnp.float32) * sm_scale
    s = jax.lax.dot_general(
        q, k.astype(jnp.float32), (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # [KV, Gp, T]
    pos = pos0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    mask = pos < seq_len
    if start is not None:
        mask &= pos >= start
    s = jnp.where(mask, s, _NEG)

    m_prev = m_ref[:, :, :1]
    l_prev = l_ref[:, :, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p, v.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # [KV, Gp, D]
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def _init_state(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)


def _write_out(o_ref, acc_ref, l_ref):
    # lengths == 0 (idle slot) never accumulated: l stays 0, out 0.
    l = jnp.maximum(l_ref[:, :, :1], 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _decode_kernel(
    lengths_ref,  # SMEM [B] i32 (scalar prefetch)
    table_ref,  # SMEM [B, max_pages] i32 (scalar prefetch)
    starts_ref,  # SMEM [B] i32 (scalar prefetch), or None: all from 0
    q_ref,  # [1, KV, Gp, D]
    k_hbm,  # [P, KV, page, D] — the pool, where it lives
    v_hbm,  # [P, KV, page, D]
    o_ref,  # [1, KV, Gp, D]
    k_buf,  # VMEM [2, KV, ppb * page, D] — two slots of one block
    v_buf,  # VMEM [2, KV, ppb * page, D]
    sem,  # DMA semaphores [2 (k, v), 2 (slot)]
    slot_ref,  # SMEM [1] i32 — the slot this sequence's block 0 is in
    acc_ref,  # VMEM [KV, Gp, D] f32
    m_ref,  # VMEM [KV, Gp, _LANES] f32
    l_ref,  # VMEM [KV, Gp, _LANES] f32
    *,
    page_size: int,
    pages_per_block: int,
    sm_scale: float,
    v_page_offset: int = 0,
):
    b = pl.program_id(0)
    last_b = pl.num_programs(0) - 1
    span = pages_per_block * page_size
    seq_len = lengths_ref[b]
    n_blocks = pl.cdiv(seq_len, span)

    def first_block(s):
        """The block a sequence's walk begins at: the one that holds its
        first attended position (block 0 without ``starts``)."""
        return 0 if starts_ref is None else starts_ref[s] // span

    blk0 = first_block(b)
    start = None if starts_ref is None else starts_ref[b]

    def block_copies(s, blk, slot, wait=False):
        """Start, or wait for, the copies of block ``blk`` of sequence
        ``s``: one a page and pool, the pages under the sequence's
        length alone."""
        first = blk * pages_per_block
        n = jnp.minimum(pages_per_block,
                        pl.cdiv(lengths_ref[s], page_size) - first)

        def page(p, _):
            pid = table_ref[s, first + p]
            rows = pl.ds(pl.multiple_of(p * page_size, page_size), page_size)
            for which, (hbm, buf, off) in enumerate((
                    (k_hbm, k_buf, 0), (v_hbm, v_buf, v_page_offset))):
                cp = pltpu.make_async_copy(
                    hbm.at[pid + off] if off else hbm.at[pid],
                    buf.at[slot, :, rows, :], sem.at[which, slot])
                if wait:
                    cp.wait()
                else:
                    cp.start()

        jax.lax.fori_loop(0, n, page, None)

    # The sequence after this one, whose first block is fetched under
    # this one's last block (an idle sequence fetches nothing).
    nxt_b = jnp.minimum(b + 1, last_b)
    nxt_starts = (b < last_b) & (lengths_ref[nxt_b] > 0)
    nxt_blk0 = first_block(nxt_b)

    @pl.when(b == 0)
    def _first():
        # What a short block leaves of a slot is multiplied by exact
        # zeros, so it has to be finite: the pool's own rows, or these.
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0

        @pl.when(seq_len > 0)
        def _():
            block_copies(0, blk0, 0)

    slot0 = slot_ref[0]
    _init_state(acc_ref, m_ref, l_ref)

    @pl.when((n_blocks == 0) & nxt_starts)
    def _idle():
        block_copies(nxt_b, nxt_blk0, slot0)

    def block(i, _):
        slot = (slot0 + i - blk0) % 2
        more = i + 1 < n_blocks

        @pl.when(more | nxt_starts)
        def _():
            block_copies(jnp.where(more, b, nxt_b),
                         jnp.where(more, i + 1, nxt_blk0), 1 - slot)

        block_copies(b, i, slot, wait=True)
        _attend_block(q_ref, k_buf[slot], v_buf[slot], i * span, seq_len,
                      acc_ref, m_ref, l_ref, sm_scale, start)

    jax.lax.fori_loop(blk0, n_blocks, block, None)
    slot_ref[0] = (slot0 + jnp.maximum(n_blocks - blk0, 0)) % 2
    _write_out(o_ref, acc_ref, l_ref)


def _page_kernel(
    lengths_ref,  # SMEM [B] i32 (scalar prefetch)
    table_ref,  # SMEM [B, max_pages] i32 (scalar prefetch)
    q_ref,  # [1, 1, Gp, D]
    k_ref,  # [1, 1, page, D] — the (page, kv head) the index map selected
    v_ref,  # [1, 1, page, D]
    o_ref,  # [1, 1, Gp, D]
    acc_ref,  # VMEM [1, Gp, D] f32
    m_ref,  # VMEM [1, Gp, _LANES] f32
    l_ref,  # VMEM [1, Gp, _LANES] f32
    *,
    page_size: int,
    sm_scale: float,
):
    """The walk for a head dim that is no multiple of the 128 lanes:
    Mosaic (jax 0.9.0) refuses to slice a ref with such a minor dim, so
    no copy can name a page of the pool, and the pages come through the
    BlockSpec pipeline, one (page, kv head) a grid step over the table's
    width.  A step past the length fetches nothing new (its index map
    stays on the last page) and skips its FLOPs."""
    j = pl.program_id(2)
    seq_len = lengths_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        _init_state(acc_ref, m_ref, l_ref)

    @pl.when(j * page_size < seq_len)
    def _page():
        _attend_block(q_ref, k_ref[0], v_ref[0], j * page_size, seq_len,
                      acc_ref, m_ref, l_ref, sm_scale)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        _write_out(o_ref, acc_ref, l_ref)


def paged_attention(
    q: jax.Array,  # [B, H, D]
    k_pages: jax.Array,  # [P, KV, page, D]
    v_pages: jax.Array,  # [P, KV, page, D]
    lengths: jax.Array,  # [B] int32
    page_table: jax.Array,  # [B, max_pages] int32
    *,
    starts: Optional[jax.Array] = None,  # [B] int32
    v_page_offset: int = 0,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Ragged paged-attention decode: one query token per sequence
    against its page-table-mapped context, from position ``starts[b]``
    on where given.  See the module docstring for the layout contract;
    output is [B, H, D] in ``q``'s dtype."""
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
    if starts is not None and starts.shape != (B,):
        raise ValueError(f"starts {starts.shape} is not one a sequence, "
                         f"({B},)")
    return _paged_attention(
        q, k_pages, v_pages, lengths, page_table,
        pages_per_block(KV, page_size, D, k_pages.dtype),
        resolve_interpret(interpret), starts=starts,
        v_page_offset=v_page_offset)


def _paged_attention(q, k_pages, v_pages, lengths, page_table, ppb,
                     interpret, walk=None, starts=None, v_page_offset=0):
    """The kernel call at ``ppb`` pages a block (the on-chip sweep,
    ``tools/paged_attention_chip.py``, times other sizes beside the
    derived one).  ``walk``: the walk or ``_page_kernel``; by default
    the walk wherever it can run, which is every head dim under the
    interpreter and a multiple of the lanes under Mosaic."""
    B, H, D = q.shape
    if walk is None:
        walk = D % _LANES == 0 or bool(interpret)
    _, KV, page_size, _ = k_pages.shape
    groups = H // KV
    maxp = page_table.shape[1]

    # [B, H, D] -> [B, KV, Gp, D]: head h of sequence b is (kv = h //
    # groups)'s group row g = h % groups — the flash kernels' layout
    # identity.  The group dim is padded to the f32 sublane tile; padded
    # rows are zero queries whose outputs are sliced off.
    gp = -(-groups // _SUBLANES) * _SUBLANES
    qh = q.reshape(B, KV, groups, D)
    if gp != groups:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, gp - groups), (0, 0)))

    def state(heads):
        return [pltpu.VMEM((heads, gp, D), jnp.float32),
                pltpu.VMEM((heads, gp, _LANES), jnp.float32),
                pltpu.VMEM((heads, gp, _LANES), jnp.float32)]

    if not walk and (starts is not None or v_page_offset):
        raise NotImplementedError(
            f"a first position or a value offset needs the walk, which a "
            f"head dim of {D} cannot take on the chip (no multiple of "
            f"{_LANES}); the page-a-step fallback attends from 0")
    lengths = jnp.minimum(lengths.astype(jnp.int32), maxp * page_size)
    prefetch = (lengths, page_table.astype(jnp.int32))
    if not walk:
        # Index maps see the scalar-prefetch refs after the grid indices.
        # The pipeline fetches a block every step: past the length it
        # stays on the sequence's last page (no new copy), and an idle
        # lane's is the pool's row 0.
        def page_of(b, kv, j, lens, table):
            last = jnp.maximum(pl.cdiv(lens[b], page_size) - 1, 0)
            page = table[b, jnp.minimum(j, last)]
            return jnp.where(lens[b] > 0, page, 0), kv, 0, 0

        q_spec = pl.BlockSpec((1, 1, gp, D),
                              lambda b, kv, j, lens, table: (b, kv, 0, 0))
        kv_spec = pl.BlockSpec((1, 1, page_size, D), page_of)
        kernel = functools.partial(_page_kernel, page_size=page_size)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, KV, maxp),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=state(1),
        )
    else:
        q_spec = pl.BlockSpec((1, KV, gp, D), lambda b, *_: (b, 0, 0, 0))
        pool_spec = pl.BlockSpec(memory_space=pl.ANY)
        block = (2, KV, ppb * page_size, D)
        kernel = functools.partial(_decode_kernel, page_size=page_size,
                                   pages_per_block=ppb,
                                   v_page_offset=v_page_offset)
        if starts is None:
            # The kernel every caller had before there were windows: two
            # scalar operands, no first position anywhere in its body.
            walk_from_0 = kernel
            kernel = lambda lens, table, *refs, **kw: walk_from_0(
                lens, table, None, *refs, **kw)
        else:
            # Below the length, so that a live sequence's walk has a block
            # (the sequence after it is fetched under that block).
            prefetch += (jnp.clip(starts.astype(jnp.int32), 0,
                                  jnp.maximum(lengths - 1, 0)),)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(B,),
            in_specs=[q_spec, pool_spec, pool_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM(block, k_pages.dtype),
                pltpu.VMEM(block, v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                *state(KV),
            ],
        )
    out = pl.pallas_call(
        functools.partial(kernel, sm_scale=1.0 / math.sqrt(D)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, gp, D), q.dtype),
        # One sequence's first block is fetched under the one before it,
        # and the softmax state runs along a sequence: steps in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid_spec.grid)),
        interpret=interpret,
        name="tdx_paged_attention_decode",
    )(
        # The walk's trip counts come from the lengths, so a length the
        # table cannot hold is held to the table (above), as the
        # reference's mask holds it.
        *prefetch, qh, k_pages, v_pages)
    return out[:, :, :groups].reshape(B, H, D)
