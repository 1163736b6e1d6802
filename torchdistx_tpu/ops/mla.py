"""Latent attention (MLA) kernels (pallas TPU) + jnp oracles.

A latent-attention layer (``models/mla.py``) caches ONE row a token,
``[c_kv ; k_rope]``: the normed ``kv_rank``-wide latent and the rotated
``rope_dim``-wide key that every head shares (576 values at the published
widths, 512 + 64).  The same attention has two forms, and each has its
kernel:

* **decode, absorbed** (``tdx_mla_decode``, :func:`mla_decode`): a head's
  key-up projection is folded into its query, ``q~_h = q_nope_h W_uk_h^T``
  (``kv_rank`` wide), so a score is ``[q~_h ; q_rope_h] . row`` over the
  whole cached row and a head's output is ``sum_s p_s c_kv(s)`` (the row's
  first ``kv_rank`` columns), which the caller multiplies by ``W_uv_h``.
  All heads read the same rows: the kernel is paged attention with one kv
  head, every query head in its group, and values that are the keys' first
  columns.  It walks each lane's real pages several at a step, as
  ``ops/paged_attention.py``'s decode kernel does (two VMEM slots, the
  next block in flight while one is computed, a lane's first block fetched
  under the last block of the lane before it; the page table and the
  lengths in SMEM), but with ONE copy a page: there is no value pool.
  A cached row is padded to a multiple of the 128 lanes, 640 at the
  published widths (the rope part ends at 576, zeros behind it): Mosaic
  (jax 0.9.0) refuses a copy out of a pool whose minor dim is not such a
  multiple ("Slice shape along dimension 3 must be aligned to tiling
  (128), but is 576"), so no copy could name a page of a 576-wide pool,
  and a pipeline of one page a grid step is what ``paged_attention``'s
  walk replaced.
  The 64 columns cost the pool and the kernel's reads 11 % more bytes;
  the query carries zeros there, and values are the lane-aligned slice of
  a block's first 512 columns.  At 128 query heads the published
  attention does 2 x 128 x (576 + 512) operations on each 1,152-byte
  row: 242 a byte, on the v5e's ridge.
* **prefill and chunk, expanded** (``tdx_mla_prefill``,
  :func:`mla_prefill`): keys and values are rebuilt from the latent
  (``k_nope_h = c_kv W_uk_h``, ``v_h = c_kv W_uv_h``) and attention runs
  with a query-key width of ``nope_dim + rope_dim`` (192) and a value
  width of ``v_dim`` (128), a flash forward over blocks of queries and
  keys, heads a few at a step.  The rotary key is one ``[T, rope_dim]``
  array for all heads, so a score is ``q_nope . k_nope + q_rope . k_rope``
  and nothing 192 wide is ever built.  Queries sit at positions
  ``start + i`` and attend keys ``j <= start + i`` below ``end``: the
  chunk's own keys and what earlier chunks wrote; a block of keys past
  both is neither fetched (its index map stays on the last one needed)
  nor computed.

Both take operands in the dtype given (bfloat16 in a bfloat16 program)
and accumulate, softmax and rescale in float32.  On non-TPU backends they
run in interpret mode (``ops/_interpret.py``); :func:`mla_decode_reference`
and :func:`mla_prefill_reference` are the oracles the tests hold them to.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._interpret import resolve_interpret

DECODE = "tdx_mla_decode"
PREFILL = "tdx_mla_prefill"
_NEG = -1e30
_LANES = 128
_SUBLANES = 8
# The decode walk's block: its two VMEM slots under this many bytes, and
# at most this many tokens (the tail block is computed whole).
_BLOCK_VMEM_BYTES = 4 * 1024 * 1024
_BLOCK_TOKENS = 512
# The prefill's blocks: queries and keys a step, and heads a step.
_PREFILL_BLOCK = 512
_PREFILL_HEADS = 4
_VMEM_LIMIT = 64 * 1024 * 1024


def pages_per_block(page_size: int, width: int, dtype) -> int:
    """Pages the decode kernel moves and computes in one step of its
    walk: the largest power of two whose two slots (rows padded to the
    lanes, as Mosaic tiles them) stay under ``_BLOCK_VMEM_BYTES`` and
    whose tokens stay under ``_BLOCK_TOKENS``; at least one.  32 pages
    of 16 tokens at the published row (640 with its padding) in
    bfloat16."""
    itemsize = jnp.dtype(dtype).itemsize
    row = -(-width // _LANES) * _LANES * itemsize
    n = min(_BLOCK_VMEM_BYTES // (2 * page_size * row),
            _BLOCK_TOKENS // page_size)
    return 1 << (max(1, n).bit_length() - 1)


def blocks_walked(lengths, page_size: int, width: int, dtype) -> int:
    """Blocks one decode call walks for these context ``lengths`` (host
    integers), nothing for an idle lane: the kernel's own trip counts."""
    span = pages_per_block(page_size, width, dtype) * page_size
    return int(np.sum(-(-np.asarray(lengths, np.int64) // span)))


# -- decode, absorbed ---------------------------------------------------------


def mla_decode_reference(q, pool, lengths, page_table, *, v_width: int,
                         scale: float):
    """Dense oracle: q [B, H, C] (the absorbed query, then the rotary
    one), pool [P, 1, page, C], lengths [B], page_table [B, maxp] ->
    [B, H, v_width] float32, the softmax-weighted sum of the rows' first
    ``v_width`` columns; an idle lane (length 0) softmaxes uniform masked
    scores (callers ignore its row)."""
    B, maxp = page_table.shape
    page = pool.shape[2]
    rows = pool[page_table][:, :, 0].reshape(B, maxp * page, -1)
    rows = rows.astype(jnp.float32)
    s = jnp.einsum("bhc,btc->bht", q.astype(jnp.float32), rows,
                   precision=jax.lax.Precision.HIGHEST) * scale
    mask = jnp.arange(maxp * page)[None, :] < lengths[:, None]
    p = jax.nn.softmax(jnp.where(mask[:, None], s, _NEG), axis=-1)
    return jnp.einsum("bht,btc->bhc", p, rows[..., :v_width],
                      precision=jax.lax.Precision.HIGHEST)


def _decode_kernel(lengths_ref, table_ref, q_ref, pool_hbm, o_ref, buf, sem,
                   slot_ref, acc_ref, m_ref, l_ref, *, page_size: int,
                   pages_per_block: int, v_width: int, scale: float):
    b = pl.program_id(0)
    last_b = pl.num_programs(0) - 1
    span = pages_per_block * page_size
    seq_len = lengths_ref[b]
    n_blocks = pl.cdiv(seq_len, span)

    def block_copies(s, blk, slot, wait=False):
        """Start, or wait for, the copies of block ``blk`` of lane ``s``:
        one a page, the pages under the lane's length alone."""
        first = blk * pages_per_block
        n = jnp.minimum(pages_per_block,
                        pl.cdiv(lengths_ref[s], page_size) - first)

        def page(p, _):
            rows = pl.ds(pl.multiple_of(p * page_size, page_size), page_size)
            cp = pltpu.make_async_copy(
                pool_hbm.at[table_ref[s, first + p]],
                buf.at[slot, :, rows, :], sem.at[slot])
            if wait:
                cp.wait()
            else:
                cp.start()

        jax.lax.fori_loop(0, n, page, None)

    nxt_b = jnp.minimum(b + 1, last_b)
    nxt_starts = (b < last_b) & (lengths_ref[nxt_b] > 0)

    @pl.when(b == 0)
    def _first():
        # What a short block leaves of a slot is multiplied by exact zeros,
        # so it has to be finite.
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0

        @pl.when(seq_len > 0)
        def _():
            block_copies(0, 0, 0)

    slot0 = slot_ref[0]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when((n_blocks == 0) & nxt_starts)
    def _idle():
        block_copies(nxt_b, 0, slot0)

    def block(i, _):
        slot = (slot0 + i) % 2
        more = i + 1 < n_blocks

        @pl.when(more | nxt_starts)
        def _():
            block_copies(jnp.where(more, b, nxt_b), jnp.where(more, i + 1, 0),
                         1 - slot)

        block_copies(b, i, slot, wait=True)
        rows = buf[slot, 0]                                   # [T, C]
        q = q_ref[0]                                          # [H, C]
        s = jax.lax.dot_general(
            q, rows.astype(q.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [H, T]
        pos = i * span + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = pos < seq_len
        s = jnp.where(mask, s, _NEG)
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_ref[...] = jnp.broadcast_to(
            l_prev * corr + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        pv = jax.lax.dot_general(
            p.astype(q.dtype), rows[:, :v_width].astype(q.dtype),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [H, R]
        acc_ref[...] = acc_ref[...] * corr + pv

    jax.lax.fori_loop(0, n_blocks, block, None)
    slot_ref[0] = (slot0 + n_blocks) % 2
    # An idle lane never accumulated: l stays 0, its row 0.
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)).astype(
        o_ref.dtype)


def mla_decode(q, pool, lengths, page_table, *, v_width: int, scale: float,
               interpret: Optional[bool] = None):
    """The absorbed form's attention, one query token a lane: q [B, H, C]
    against the lane's cached rows, pool [P, 1, page, C] (a flat pool of
    every layer: the caller offsets the table by the layer's first page),
    ``lengths`` [B] the rows that count INCLUDING the one just written (0
    for an idle lane, whose output row is 0), ``page_table`` [B, maxp].
    Returns [B, H, v_width] float32: ``sum_s p_s row_s[:v_width]``."""
    B, H, C = q.shape
    _, one, page_size, Cp = pool.shape
    if one != 1 or Cp != C:
        raise ValueError(f"pool {pool.shape} is not one row of {C} a token")
    if v_width > C:
        raise ValueError(f"values of {v_width} in rows of {C}")
    interpret = resolve_interpret(interpret)
    ppb = pages_per_block(page_size, C, pool.dtype)
    maxp = page_table.shape[1]
    lengths = jnp.minimum(lengths.astype(jnp.int32), maxp * page_size)
    hp = -(-H // _SUBLANES) * _SUBLANES
    qh = q if hp == H else jnp.pad(q, ((0, 0), (0, hp - H), (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, hp, C), lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, hp, v_width), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, 1, ppb * page_size, C), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((hp, v_width), jnp.float32),
            pltpu.VMEM((hp, _LANES), jnp.float32),
            pltpu.VMEM((hp, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, page_size=page_size,
                          pages_per_block=ppb, v_width=v_width, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, hp, v_width), jnp.float32),
        # A lane's first block is fetched under the lane before it: steps
        # in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=DECODE,
    )(lengths, page_table.astype(jnp.int32), qh, pool)
    return out[:, :H]


# -- prefill and chunk, expanded ----------------------------------------------


def mla_prefill_reference(q_nope, q_rope, k_nope, v, k_rope, start, end, *,
                          scale: float):
    """Dense oracle of :func:`mla_prefill`: float32 [H, S, dv]."""
    f = lambda x: x.astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    s = (jnp.einsum("hsd,htd->hst", f(q_nope), f(k_nope), precision=hi)
         + jnp.einsum("hsd,td->hst", f(q_rope), f(k_rope), precision=hi))
    S, T = s.shape[1:]
    qpos = start + jnp.arange(S)[:, None]
    kpos = jnp.arange(T)[None, :]
    mask = (kpos <= qpos) & (kpos < end)
    p = jax.nn.softmax(jnp.where(mask, s * scale, _NEG), axis=-1)
    return jnp.einsum("hst,htd->hsd", p, f(v), precision=hi)


def _last_key_block(start, end, i, bq: int, bk: int):
    """The last block of keys that query block ``i`` needs: the one that
    holds its last query's position or the last key below ``end``,
    whichever comes first; -1 where the block's first query lies past the
    end (padding: nothing to attend)."""
    q0 = start + i * bq
    last = jnp.minimum(q0 + bq - 1, end - 1) // bk
    return jnp.where(q0 < end, last, -1)


def _prefill_kernel(bounds_ref, qn_ref, qr_ref, kn_ref, v_ref, kr_ref, o_ref,
                    acc_ref, m_ref, l_ref, *, bq: int, bk: int, scale: float):
    i, j = pl.program_id(1), pl.program_id(2)
    start, end = bounds_ref[0], bounds_ref[1]
    last = _last_key_block(start, end, i, bq, bk)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j <= last)
    def _block():
        qn = qn_ref[...]                                      # [hb, bq, dn]
        hb, _, dr = qr_ref.shape
        s = jax.lax.dot_general(
            qn, kn_ref[...], (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)               # [hb, bq, bk]
        s += jax.lax.dot_general(
            qr_ref[...].reshape(hb * bq, dr), kr_ref[...],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).reshape(hb, bq, bk)
        s *= scale
        qpos = start + i * bq + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        mask = (kpos <= qpos) & (kpos < end)
        s = jnp.where(mask, s, _NEG)
        m_prev, l_prev = m_ref[:, :, :1], l_ref[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_ref[...] = jnp.broadcast_to(
            l_prev * corr + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)               # [hb, bq, dv]
        acc_ref[...] = acc_ref[...] * corr + pv

    @pl.when(j == pl.num_programs(2) - 1)
    def _out():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[:, :, :1], 1e-30)
                      ).astype(o_ref.dtype)


def _divisor(n: int, most: int) -> int:
    """The largest divisor of ``n`` not over ``most``."""
    return next(b for b in range(min(n, most), 0, -1) if n % b == 0)


def mla_prefill(q_nope, q_rope, k_nope, v, k_rope, start, end, *,
                scale: float, interpret: Optional[bool] = None):
    """The expanded form's attention for one sequence, heads major:
    q_nope [H, S, dn] and q_rope [H, S, dr] at positions ``start + i``;
    k_nope [H, T, dn] and v [H, T, dv] at positions ``j``; k_rope [T, dr]
    shared by the heads; ``start`` and ``end`` int32 scalars.  Query ``i``
    attends keys ``j <= start + i`` with ``j < end``.  Returns [H, S, dv]
    in ``v``'s dtype; a row whose position lies at or past ``end`` is
    garbage (zero where its whole block does)."""
    H, S, dn = q_nope.shape
    T, dr = k_rope.shape
    dv = v.shape[-1]
    interpret = resolve_interpret(interpret)
    bq, bk = _divisor(S, _PREFILL_BLOCK), _divisor(T, _PREFILL_BLOCK)
    hb = _divisor(H, _PREFILL_HEADS)
    nk = T // bk

    def key_block(h, i, j, bounds):
        return h, jnp.clip(jnp.minimum(
            j, _last_key_block(bounds[0], bounds[1], i, bq, bk)), 0, nk - 1)

    q_spec = lambda w: pl.BlockSpec((hb, bq, w),
                                    lambda h, i, j, bounds: (h, i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(H // hb, S // bq, nk),
        in_specs=[
            q_spec(dn), q_spec(dr),
            pl.BlockSpec((hb, bk, dn), lambda *a: (*key_block(*a), 0)),
            pl.BlockSpec((hb, bk, dv), lambda *a: (*key_block(*a), 0)),
            pl.BlockSpec((bk, dr), lambda *a: (key_block(*a)[1], 0)),
        ],
        out_specs=q_spec(dv),
        scratch_shapes=[pltpu.VMEM((hb, bq, dv), jnp.float32),
                        pltpu.VMEM((hb, bq, _LANES), jnp.float32),
                        pltpu.VMEM((hb, bq, _LANES), jnp.float32)],
    )
    bounds = jnp.stack([jnp.asarray(start, jnp.int32).reshape(()),
                        jnp.asarray(end, jnp.int32).reshape(())])
    return pl.pallas_call(
        functools.partial(_prefill_kernel, bq=bq, bk=bk, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, S, dv), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=PREFILL,
    )(bounds, q_nope, q_rope, k_nope, v, k_rope)
