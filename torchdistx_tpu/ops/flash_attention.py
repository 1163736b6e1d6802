"""Blockwise (flash) attention as pallas TPU kernels, forward + backward.

The hot op of every model family here is attention; XLA's default lowering
materializes the [S, T] logits in HBM. These kernels stream K/V blocks
through VMEM with the online-softmax recurrence, so per-core memory is
O(block_q x block_k) regardless of sequence length — the standard
FlashAttention scheme laid out for the TPU memory hierarchy:

* grid = (batch x heads, outer blocks, inner blocks); TPU grids run
  sequentially, so VMEM scratch accumulators carry across the innermost
  dimension and are re-initialized when its index wraps to 0;
* all block matmuls run on the MXU with float32 accumulation
  (``preferred_element_type``), everything else rides the VPU;
* GQA/MQA is handled in the index maps — K/V blocks are fetched from the
  kv-head their query head belongs to, never broadcast in HBM, in the
  backward too: the dk/dv kernel's innermost grid dimension iterates the
  (group head, q block) product and accumulates group contributions in
  VMEM scratch (layout identity: query head row ``b*H + kv*G + g`` ==
  ``bkv*G + g`` for ``bkv = b*KV + kv``);
* causal + length masking follows ``default_attention``'s convention
  (last query aligned with last key: query i sees keys j <= i + T - S);
  blocks entirely on the wrong side of the diagonal skip their FLOPs via
  ``pl.when``;
* the backward pass is the two-kernel scheme: a dq kernel (k innermost)
  and a dk/dv kernel ((g, q) innermost), both recomputing block
  probabilities from the saved per-row logsumexp instead of storing the
  S x T matrix;
* additive bias (T5-style relative positions, ``[H or 1, S, T]`` in
  ``default_attention``'s convention: logits = q k^T * scale + bias) is a
  fourth operand stream — its blocks ride the same (qi, kj) tiling, with
  the head index derived from the grid's batch*head row.  d(bias) has its
  own kernel: grid (H, nq, nk, B) with batch innermost, so each bias
  block accumulates every batch's ``p * (dp - delta)`` in VMEM scratch
  and is written exactly once — blockwise memory even though bias
  touches the full [S, T] plane.

Matches the model layer ``AttnFn`` signature (`models/layers.py`), so any
family runs on it by constructor argument, including under `jax.grad`.
On non-TPU backends the kernels run in interpreter mode (decided and
counted by :func:`._interpret.resolve_interpret`), which keeps the CPU test
suite meaningful.
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
from .segments import normalize_segment_ids

_NEG = -1e30
_LANES = 128  # TPU lane width: scratch vectors are carried at full lanes
_SEG_LANES = 8  # segment-id carriers: one int32 sublane tile is enough


def _causal_mask(q_start, k_start, block_q, block_k, seq_len_k, offset, causal):
    """Valid-key mask for one block, in default_attention's convention."""
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = k_pos < seq_len_k  # padded keys never attend
    if causal:
        mask = jnp.logical_and(mask, k_pos <= q_pos + offset)
    return mask


def _block_needed(q_start, k_start, block_q, offset, causal):
    """False only for blocks with no (q, k) pair on the causal side."""
    return jnp.logical_or(
        jnp.logical_not(causal), k_start <= q_start + (block_q - 1) + offset
    )


def _seg_mask(qseg_ref, kseg_ref):
    """[bq, bk] same-segment mask from the lane-broadcast id carriers
    (packed-sequence training: cross-segment pairs never attend)."""
    qs = qseg_ref[0][:, :1]  # [bq, 1] int32
    ks = kseg_ref[0][:, :1]  # [bk, 1]
    return qs == jnp.transpose(ks)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref,  # [1, block_q, D]
    k_ref,  # [1, block_k, D]
    v_ref,  # [1, block_k, D]
    *rest,  # [bias_ref [1, block_q, block_k] if has_bias,]
    #         [qseg_ref / kseg_ref [1, block, _SEG_LANES] i32 if has_segs,]
    #         o_ref [1, block_q, D],
    #         lse_ref [1, block_q, _LANES] (lse broadcast across full
    #           lanes, the upstream TPU flash layout — a 1-wide minor dim
    #           violates Mosaic's (8, 128) block tiling rule; ADVICE r1),
    #         acc_ref VMEM [block_q, D] f32,
    #         m_ref / l_ref VMEM [block_q, _LANES] f32
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    seq_len_k: int,
    offset: int,
    has_bias: bool = False,
    has_segs: bool = False,
):
    rest = list(rest)
    bias_ref = rest.pop(0) if has_bias else None
    segs = (rest.pop(0), rest.pop(0)) if has_segs else None
    o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = kj * block_k

    @pl.when(_block_needed(q_start, k_start, block_q, offset, causal))
    def _block():
        q = q_ref[0].astype(jnp.float32) * sm_scale  # [bq, D]
        k = k_ref[0].astype(jnp.float32)  # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]
        if bias_ref is not None:
            s = s + bias_ref[0].astype(jnp.float32)
        mask = _causal_mask(
            q_start, k_start, block_q, block_k, seq_len_k, offset, causal
        )
        if segs is not None:
            mask = jnp.logical_and(mask, _seg_mask(*segs))
        s = jnp.where(mask, s, _NEG)

        m_prev = m_ref[:, :1]  # [bq, 1]
        l_prev = l_ref[:, :1]
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)  # [bq, bk]

        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p,
            v_ref[0].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, D]
        acc_ref[:] = acc_ref[:] * corr + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kj == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))
        lse_ref[0] = lse  # all lanes equal; consumers read lane 0


# ---------------------------------------------------------------------------
# backward: dq (k innermost), then dk/dv ((group, q) innermost)
# ---------------------------------------------------------------------------


def _block_p_ds(
    q, k, lse, do, v, delta, *, causal, sm_scale, q_start, k_start, seq_len_k,
    offset, block_q, block_k, bias=None, seg_mask=None,
):
    """Recompute one block's probabilities and d(logits) from residuals.

    p  = exp(q k^T * scale [+ bias] - lse)  [bq, bk]
    ds = p * (do v^T - delta) * scale       (gradient of the raw logits)

    ``lse`` and ``delta`` arrive as [bq, 1] column vectors (lane 0 of the
    lane-broadcast row carriers).  ``d(bias)`` is ``ds / scale`` —
    i.e. ``p * (dp - delta)`` — computed by its own kernel.
    """
    s = jax.lax.dot_general(
        q * sm_scale, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    mask = _causal_mask(q_start, k_start, block_q, block_k, seq_len_k, offset, causal)
    if seg_mask is not None:
        mask = jnp.logical_and(mask, seg_mask)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [bq, bk]
    ds = p * (dp - delta) * sm_scale
    return p, ds


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    seq_len_k: int,
    offset: int,
    has_bias: bool = False,
    has_segs: bool = False,
):
    rest = list(rest)
    bias_ref = rest.pop(0) if has_bias else None
    segs = (rest.pop(0), rest.pop(0)) if has_segs else None
    dq_ref, dq_acc = rest  # dq_acc: VMEM [block_q, D] f32
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start, k_start = qi * block_q, kj * block_k

    @pl.when(_block_needed(q_start, k_start, block_q, offset, causal))
    def _block():
        _, ds = _block_p_ds(
            q_ref[0].astype(jnp.float32),
            k_ref[0].astype(jnp.float32),
            lse_ref[0, :, :1],
            do_ref[0].astype(jnp.float32),
            v_ref[0].astype(jnp.float32),
            delta_ref[0, :, :1],
            causal=causal, sm_scale=sm_scale, q_start=q_start, k_start=k_start,
            seq_len_k=seq_len_k, offset=offset, block_q=block_q, block_k=block_k,
            bias=None if bias_ref is None else bias_ref[0],
            seg_mask=None if segs is None else _seg_mask(*segs),
        )
        dq_acc[:] += jax.lax.dot_general(
            ds,
            k_ref[0].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kj == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    seq_len_k: int,
    offset: int,
    groups: int,
    has_bias: bool = False,
    has_segs: bool = False,
):
    """Grid (B*KV, nk, groups*nq): the innermost dimension walks every
    (group head, q block) pair of this kv head, accumulating dk/dv in
    VMEM — GQA needs no K/V broadcast or post-hoc group reduction."""
    rest = list(rest)
    bias_ref = rest.pop(0) if has_bias else None
    segs = (rest.pop(0), rest.pop(0)) if has_segs else None
    dk_ref, dv_ref, dk_acc, dv_acc = rest  # accs: VMEM [block_k, D] f32
    kj = pl.program_id(1)
    it = pl.program_id(2)
    n_inner = pl.num_programs(2)
    nq = n_inner // groups
    qi = it % nq

    @pl.when(it == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start, k_start = qi * block_q, kj * block_k

    @pl.when(_block_needed(q_start, k_start, block_q, offset, causal))
    def _block():
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        p, ds = _block_p_ds(
            q,
            k_ref[0].astype(jnp.float32),
            lse_ref[0, :, :1],
            do,
            v_ref[0].astype(jnp.float32),
            delta_ref[0, :, :1],
            causal=causal, sm_scale=sm_scale, q_start=q_start, k_start=k_start,
            seq_len_k=seq_len_k, offset=offset, block_q=block_q, block_k=block_k,
            bias=None if bias_ref is None else bias_ref[0],
            seg_mask=None if segs is None else _seg_mask(*segs),
        )
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bk, D]
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(it == n_inner - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _dbias_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref, *rest,
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    seq_len_k: int,
    offset: int,
    has_segs: bool = False,
):
    """Grid (H, nq, nk, B), batch innermost: the output block (h, qi, kj)
    is constant across the inner loop, so each batch's ``p * (dp - delta)``
    accumulates in VMEM and the block is written exactly once — the bias
    gradient never materializes per-batch [S, T] planes."""
    rest = list(rest)
    segs = (rest.pop(0), rest.pop(0)) if has_segs else None
    dbias_ref, acc_ref = rest  # acc: VMEM [block_q, block_k] f32
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    b = pl.program_id(3)
    nb = pl.num_programs(3)

    @pl.when(b == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start, k_start = qi * block_q, kj * block_k

    @pl.when(_block_needed(q_start, k_start, block_q, offset, causal))
    def _block():
        p, ds = _block_p_ds(
            q_ref[0].astype(jnp.float32),
            k_ref[0].astype(jnp.float32),
            lse_ref[0, :, :1],
            do_ref[0].astype(jnp.float32),
            v_ref[0].astype(jnp.float32),
            delta_ref[0, :, :1],
            causal=causal, sm_scale=sm_scale, q_start=q_start, k_start=k_start,
            seq_len_k=seq_len_k, offset=offset, block_q=block_q, block_k=block_k,
            bias=bias_ref[0],
            seg_mask=None if segs is None else _seg_mask(*segs),
        )
        acc_ref[:] += ds * (1.0 / sm_scale)  # d(logits) without the q scale

    @pl.when(b == nb - 1)
    def _finish():
        dbias_ref[0] = acc_ref[:].astype(dbias_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------


def _delta_carrier(do, out, block_q, lse_shape):
    """delta = rowsum(do * out), padded and lane-broadcast to match the
    lse carrier layout (Mosaic block-tiling rule; kernels read lane 0).
    Loop-invariant for ring callers — compute once and pass as
    ``delta3``."""
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    return jnp.broadcast_to(_pad_seq(delta, block_q)[:, :, None], lse_shape)


def _pad_seq(x: jax.Array, block: int) -> jax.Array:
    """Zero-pad axis 1 (sequence / row dim) up to a multiple of ``block``."""
    pad = (-x.shape[1]) % block
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[1] = (0, pad)
    return jnp.pad(x, widths)


def _round8(n: int) -> int:
    return max(8, ((n + 7) // 8) * 8)


def _pad_bias(bias, block_q, block_k):
    """Zero-pad a [Hb, S, T] bias up to block multiples on both planes."""
    pad_q = (-bias.shape[1]) % block_q
    pad_k = (-bias.shape[2]) % block_k
    if pad_q or pad_k:
        bias = jnp.pad(bias, ((0, 0), (0, pad_q), (0, pad_k)))
    return bias


def _bias_spec(Hb, H, block_q, block_k):
    """Bias BlockSpec for the (bh, qi, kj) grids; a head-broadcast bias
    (Hb == 1) pins the head index to 0."""
    if Hb == 1:
        return pl.BlockSpec((1, block_q, block_k), lambda bh, qi, kj: (0, qi, kj))
    return pl.BlockSpec((1, block_q, block_k), lambda bh, qi, kj: (bh % H, qi, kj))


def _seg_carrier(seg: jax.Array, block: int) -> jax.Array:
    """[B, S] int32 ids, zero-padded to a block multiple and broadcast to
    ``_SEG_LANES`` lanes (kernels read lane 0; 8 lanes — one int32
    sublane tile — is the narrowest minor dim Mosaic tiles, 16x less HBM
    traffic than a full 128-lane carrier; ADVICE r2).  Padded rows are
    provably inert: padded q rows carry zero ``do``/``delta`` and padded
    key columns are masked by ``seq_len_k``, so their contributions
    vanish regardless of id."""
    segp = _pad_seq(seg.astype(jnp.int32), block)
    return jnp.broadcast_to(segp[:, :, None], (*segp.shape, _SEG_LANES))


def _seg_carriers(qseg, kseg, block_q, block_k):
    """Both carriers, built ONCE per _flash_core call and threaded through
    the fwd/bwd pallas_calls (ADVICE r2: they used to be rebuilt per
    call)."""
    if qseg is None:
        return None
    return (_seg_carrier(qseg, block_q), _seg_carrier(kseg, block_k))


def _seg_specs(heads, block_q, block_k):
    """(q, k) carrier BlockSpecs for the (bh, qi, kj) grids: the batch
    row is bh // heads (ids are per-batch, shared by every head)."""
    return (
        pl.BlockSpec(
            (1, block_q, _SEG_LANES), lambda bh, qi, kj: (bh // heads, qi, 0)
        ),
        pl.BlockSpec(
            (1, block_k, _SEG_LANES), lambda bh, qi, kj: (bh // heads, kj, 0)
        ),
    )


def _fwd_call(
    qh, kh, vh, groups, causal, block_q, block_k, interpret,
    bias=None, heads=None, segc=None,
):
    BH, S, D = qh.shape
    T = kh.shape[1]
    sm_scale = 1.0 / math.sqrt(D)
    qp = _pad_seq(qh, block_q)
    kp, vp = _pad_seq(kh, block_k), _pad_seq(vh, block_k)
    nq, nk = qp.shape[1] // block_q, kp.shape[1] // block_k

    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda bh, qi, kj: (bh, qi, 0)),
        pl.BlockSpec((1, block_k, D), lambda bh, qi, kj: (bh // groups, kj, 0)),
        pl.BlockSpec((1, block_k, D), lambda bh, qi, kj: (bh // groups, kj, 0)),
    ]
    operands = [qp, kp, vp]
    if bias is not None:
        in_specs.append(_bias_spec(bias.shape[0], heads, block_q, block_k))
        operands.append(_pad_bias(bias, block_q, block_k))
    if segc is not None:
        in_specs.extend(_seg_specs(heads, block_q, block_k))
        operands.extend(segc)

    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, seq_len_k=T, offset=T - S,
            has_bias=bias is not None, has_segs=segc is not None,
        ),
        grid=(BH, nq, nk),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, block_q, D), lambda bh, qi, kj: (bh, qi, 0)),
            # lse carried at full lane width (Mosaic requires the minor
            # block dim be 128-divisible or the whole array dim; a bare
            # (1, bq) block trips that rule on real TPU — ADVICE r1).
            pl.BlockSpec((1, block_q, _LANES), lambda bh, qi, kj: (bh, qi, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct(qp.shape, qh.dtype),
            jax.ShapeDtypeStruct((BH, qp.shape[1], _LANES), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        interpret=interpret,
        name="tdx_flash_attention_fwd",
    )(*operands)
    return out[:, :S], lse  # lse stays padded; backward re-pads to match


def _bwd_call(
    qh, kh, vh, do, out, lse, groups, causal, block_q, block_k, interpret,
    delta3=None, bias=None, heads=None, segc=None, want_dbias=False,
):
    BH, S, D = qh.shape
    T = kh.shape[1]
    BKV = kh.shape[0]
    sm_scale = 1.0 / math.sqrt(D)

    if delta3 is None:
        delta3 = _delta_carrier(do, out, block_q, lse.shape)
    qp, dop = _pad_seq(qh, block_q), _pad_seq(do, block_q)
    kp, vp = _pad_seq(kh, block_k), _pad_seq(vh, block_k)
    dp = delta3  # [BH, Sq_padded, _LANES] like lse
    lsep = lse  # [BH, Sq_padded, _LANES], padded by fwd
    nq, nk = qp.shape[1] // block_q, kp.shape[1] // block_k
    biasp = None if bias is None else _pad_bias(bias, block_q, block_k)
    Hb = None if bias is None else bias.shape[0]

    common = dict(
        causal=causal, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, seq_len_k=T, offset=T - S,
    )
    qspec = pl.BlockSpec((1, block_q, D), lambda bh, i, j: (bh, i, 0))
    rowspec = pl.BlockSpec((1, block_q, _LANES), lambda bh, i, j: (bh, i, 0))

    dq_specs = [
        qspec,
        pl.BlockSpec((1, block_k, D), lambda bh, qi, kj: (bh // groups, kj, 0)),
        pl.BlockSpec((1, block_k, D), lambda bh, qi, kj: (bh // groups, kj, 0)),
        qspec,
        rowspec,
        rowspec,
    ]
    dq_operands = [qp, kp, vp, dop, lsep, dp]
    if bias is not None:
        dq_specs.append(_bias_spec(Hb, heads, block_q, block_k))
        dq_operands.append(biasp)
    if segc is not None:
        dq_specs.extend(_seg_specs(heads, block_q, block_k))
        dq_operands.extend(segc)
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, has_bias=bias is not None,
            has_segs=segc is not None, **common,
        ),
        grid=(BH, nq, nk),
        in_specs=dq_specs,
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(qp.shape, qh.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        name="tdx_flash_attention_dq",
    )(*dq_operands)

    # Query-head row for (kv head bkv, group g) is bkv*groups + g; the
    # innermost grid dim packs (g, qi) as it = g*nq + qi.  Batch item:
    # bkv // KV, with KV = kv heads per item.
    KV = BKV // (BH // heads) if heads else None
    kspec = pl.BlockSpec((1, block_k, D), lambda bkv, kj, it: (bkv, kj, 0))
    qspec2 = pl.BlockSpec(
        (1, block_q, D), lambda bkv, kj, it: (bkv * groups + it // nq, it % nq, 0)
    )
    rowspec2 = pl.BlockSpec(
        (1, block_q, _LANES),
        lambda bkv, kj, it: (bkv * groups + it // nq, it % nq, 0),
    )
    dkv_specs = [qspec2, kspec, kspec, qspec2, rowspec2, rowspec2]
    dkv_operands = [qp, kp, vp, dop, lsep, dp]
    if bias is not None:
        # Head within the batch item: (bkv % KV) * groups + g.
        if Hb == 1:
            bspec2 = pl.BlockSpec(
                (1, block_q, block_k), lambda bkv, kj, it: (0, it % nq, kj)
            )
        else:
            bspec2 = pl.BlockSpec(
                (1, block_q, block_k),
                lambda bkv, kj, it: ((bkv % KV) * groups + it // nq, it % nq, kj),
            )
        dkv_specs.append(bspec2)
        dkv_operands.append(biasp)
    if segc is not None:
        dkv_specs.extend([
            pl.BlockSpec(
                (1, block_q, _SEG_LANES),
                lambda bkv, kj, it: (bkv // KV, it % nq, 0),
            ),
            pl.BlockSpec(
                (1, block_k, _SEG_LANES), lambda bkv, kj, it: (bkv // KV, kj, 0)
            ),
        ])
        dkv_operands.extend(segc)
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, groups=groups, has_bias=bias is not None,
            has_segs=segc is not None, **common,
        ),
        grid=(BKV, nk, groups * nq),
        in_specs=dkv_specs,
        out_specs=(kspec, kspec),
        out_shape=(
            jax.ShapeDtypeStruct(kp.shape, kh.dtype),
            jax.ShapeDtypeStruct(vp.shape, vh.dtype),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
        name="tdx_flash_attention_dkv",
    )(*dkv_operands)

    if not want_dbias:
        return dq[:, :S], dk[:, :T], dv[:, :T]
    dbias = _dbias_call(
        qp, kp, vp, dop, lsep, dp, biasp, groups, heads, interpret, S, T,
        segc=segc, **common,
    )
    return dq[:, :S], dk[:, :T], dv[:, :T], dbias


def _dbias_call(
    qp, kp, vp, dop, lsep, dp, biasp, groups, heads, interpret, S, T,
    segc=None, *, causal, sm_scale, block_q, block_k, seq_len_k, offset,
):
    """Bias gradient at padded [Hb, Sq_p, Tk_p].  Padded rows and columns
    contribute exactly zero (do rows are zero-padded, key columns are
    masked), so the slice back to [.., S, T] is exact.

    A head-broadcast bias (Hb == 1) folds the head index into the
    innermost accumulation dimension — grid (1, nq, nk, B*H) — so the
    gradient is produced directly at [1, S, T] without ever materializing
    a per-head [H, S, T] intermediate in HBM."""
    BH = qp.shape[0]
    D = qp.shape[2]
    B = BH // heads
    nq, nk = qp.shape[1] // block_q, kp.shape[1] // block_k
    H, KV = heads, heads // groups
    Hb = biasp.shape[0]

    if Hb == 1:
        # Inner index ib enumerates every (batch, head) row directly.
        grid = (1, nq, nk, BH)
        qmap = lambda h, qi, kj, ib: (ib, qi, 0)
        kmap = lambda h, qi, kj, ib: ((ib // H) * KV + (ib % H) // groups, kj, 0)
        bmap = lambda h, qi, kj, ib: (0, qi, kj)
        qsmap = lambda h, qi, kj, ib: (ib // H, qi, 0)
        ksmap = lambda h, qi, kj, ib: (ib // H, kj, 0)
    else:
        # Grid (H, nq, nk, B) with batch innermost; query-head row of
        # (h, b) is b*H + h, its kv row b*KV + h//groups.
        grid = (H, nq, nk, B)
        qmap = lambda h, qi, kj, b: (b * H + h, qi, 0)
        kmap = lambda h, qi, kj, b: (b * KV + h // groups, kj, 0)
        bmap = lambda h, qi, kj, b: (h, qi, kj)
        qsmap = lambda h, qi, kj, b: (b, qi, 0)
        ksmap = lambda h, qi, kj, b: (b, kj, 0)
    in_specs = [
        pl.BlockSpec((1, block_q, D), qmap),
        pl.BlockSpec((1, block_k, D), kmap),
        pl.BlockSpec((1, block_k, D), kmap),
        pl.BlockSpec((1, block_q, D), qmap),
        pl.BlockSpec((1, block_q, _LANES), qmap),
        pl.BlockSpec((1, block_q, _LANES), qmap),
        pl.BlockSpec((1, block_q, block_k), bmap),
    ]
    operands = [qp, kp, vp, dop, lsep, dp, biasp]
    if segc is not None:
        in_specs.extend([
            pl.BlockSpec((1, block_q, _SEG_LANES), qsmap),
            pl.BlockSpec((1, block_k, _SEG_LANES), ksmap),
        ])
        operands.extend(segc)
    dbias = pl.pallas_call(
        functools.partial(
            _dbias_kernel, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, seq_len_k=seq_len_k, offset=offset,
            has_segs=segc is not None,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, block_k), bmap),
        out_shape=jax.ShapeDtypeStruct((Hb, qp.shape[1], kp.shape[1]), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, block_k), jnp.float32)],
        interpret=interpret,
        name="tdx_flash_attention_dbias",
    )(*operands)
    return dbias[:, :S, :T]


# ---------------------------------------------------------------------------
# differentiable core ([B*H, S, D] layout)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _flash_core(qh, kh, vh, bias, qseg, kseg, groups, heads, causal,
                block_q, block_k, interpret):
    """One differentiable core for every call shape: ``bias`` is either a
    [Hb, S, T] array or ``None`` (an empty pytree — its cotangent is
    ``None`` and the dbias pass is skipped); ``qseg``/``kseg`` are
    [B, S]/[B, T] int32 segment ids or ``None`` (integer operands, zero
    cotangent)."""
    out, _ = _fwd_call(
        qh, kh, vh, groups, causal, block_q, block_k, interpret,
        bias=bias, heads=heads,
        segc=_seg_carriers(qseg, kseg, block_q, block_k),
    )
    return out


def _flash_core_fwd(qh, kh, vh, bias, qseg, kseg, groups, heads, causal,
                    block_q, block_k, interpret):
    # Carriers are built once here and threaded through the residuals to
    # every backward pallas_call (they are tiny at _SEG_LANES wide).
    segc = _seg_carriers(qseg, kseg, block_q, block_k)
    out, lse = _fwd_call(
        qh, kh, vh, groups, causal, block_q, block_k, interpret,
        bias=bias, heads=heads, segc=segc,
    )
    return out, (qh, kh, vh, bias, segc, out, lse)


def _flash_core_bwd(groups, heads, causal, block_q, block_k, interpret,
                    res, do):
    qh, kh, vh, bias, segc, out, lse = res
    if bias is None:
        dq, dk, dv = _bwd_call(
            qh, kh, vh, do, out, lse, groups, causal, block_q, block_k,
            interpret, heads=heads, segc=segc,
        )
        return dq, dk, dv, None, None, None
    dq, dk, dv, dbias = _bwd_call(
        qh, kh, vh, do, out, lse, groups, causal, block_q, block_k, interpret,
        bias=bias, heads=heads, segc=segc, want_dbias=True,
    )
    # (a head-broadcast bias already accumulated over heads in-kernel)
    return dq, dk, dv, dbias.astype(bias.dtype), None, None


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


# ---------------------------------------------------------------------------
# public API (model AttnFn layout [B, S, H, D])
# ---------------------------------------------------------------------------


def flash_attention(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, T, KV, D]
    v: jax.Array,  # [B, T, KV, D]
    *,
    causal: bool = True,
    bias: Optional[jax.Array] = None,
    segment_ids=None,  # [B, S] or ([B, S], [B, T]): packed sequences
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash attention with the model ``AttnFn`` signature (GQA-aware,
    differentiable via pallas backward kernels).

    ``bias`` is additive on the scaled logits in ``default_attention``'s
    convention — shape ``[H or 1, S, T]`` — and runs in the kernels
    (fwd, dq/dk/dv recompute, and a dedicated dbias kernel), not via an
    XLA fallback.

    ``segment_ids`` masks cross-segment pairs in-kernel (packed-document
    training): int32 ids, [B, S] for self-attention or a
    ``([B, S], [B, T])`` pair for cross-attention.  The id carriers ride
    the lse/delta lane-broadcast layout, so the masking is blockwise too.
    A query whose segment contains no keys at all gets a zero output row
    (the XLA path softmaxes over the uniform -1e30 logits instead —
    don't build packings with empty segments).
    """
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    if H % KV:
        raise ValueError(
            f"Query heads ({H}) must be a multiple of KV heads ({KV})."
        )
    groups = H // KV
    interpret = resolve_interpret(interpret)
    bq = min(block_q, _round8(S))
    bk = min(block_k, _round8(T))

    # [B, S, H, D] -> [B*H, S, D]; KV heads stay un-broadcast, the kernel's
    # index maps route each query head to its kv group.
    qh = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kh = k.transpose(0, 2, 1, 3).reshape(B * KV, T, D)
    vh = v.transpose(0, 2, 1, 3).reshape(B * KV, T, D)
    if bias is not None:
        if (
            bias.ndim != 3
            or bias.shape[0] not in (1, H)
            or bias.shape[1] not in (1, S)
            or bias.shape[2] not in (1, T)
        ):
            raise ValueError(
                f"bias must be [H or 1, S or 1, T or 1] broadcastable to "
                f"[{H}, {S}, {T}], got {tuple(bias.shape)}."
            )
        if not interpret and T > bk and bk % _LANES:
            raise ValueError(
                f"bias kernels tile the [S, T] plane, so on TPU block_k "
                f"({bk}) must be a multiple of {_LANES} (or >= T={T}); "
                f"Mosaic rejects narrower minor block dims."
            )
        if bias.shape[1:] != (S, T):
            # Row/column-broadcast planes (e.g. ALiBi-style [H, 1, T])
            # expand before the kernel; autodiff of the broadcast sums
            # dbias back to the caller's shape.  This costs a full [H, S, T]
            # plane in HBM — same as the dense XLA path such biases used
            # previously, so acceptable, but NOT blockwise; long-context
            # callers should pass the full [H, S, T] bias (T5 does) or
            # fold position terms into q/k instead.
            bias = jnp.broadcast_to(bias, (bias.shape[0], S, T))
    qseg = kseg = None
    if segment_ids is not None:
        qseg, kseg = normalize_segment_ids(segment_ids, B, S, T)
    out = _flash_core(
        qh, kh, vh, bias, qseg, kseg, groups, H, causal, bq, bk, interpret
    )
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def make_flash_attention(*, block_q: int = 1024, block_k: int = 1024,
                         mesh=None, batch_axes=("dp", "fsdp"),
                         head_axis: str = "tp"):
    """An ``AttnFn`` with fixed block sizes, for model constructors.

    With a multi-device ``mesh`` the kernel runs under ``shard_map``:
    batch split over the ``batch_axes`` present on the mesh, heads over
    ``head_axis`` when it is — each device runs the kernel on its own
    shard.  A Mosaic call is opaque to the SPMD partitioner: left to
    GSPMD, a batch-sharded step would gather the whole batch onto every
    device and run the kernel replicated — right answers, n-fold work,
    no error."""

    def attn_fn(q, k, v, *, causal=True, bias=None, segment_ids=None):
        return flash_attention(
            q, k, v, causal=causal, bias=bias, segment_ids=segment_ids,
            block_q=block_q, block_k=block_k,
        )

    if mesh is None or mesh.devices.size == 1:
        return attn_fn
    from jax.sharding import PartitionSpec as P

    # Imported here: parallel/ imports this module (ring_flash).
    from ..parallel._attn_wrap import wrap_seq_parallel_attn

    b = tuple(a for a in batch_axes if a in mesh.axis_names) or None
    h = head_axis if head_axis in mesh.axis_names else None
    return wrap_seq_parallel_attn(
        mesh, name="flash_attention", spec=P(b, None, h, None),
        per_device=lambda q, k, v, causal, bias, segs: attn_fn(
            q, k, v, causal=causal, bias=bias, segment_ids=segs),
        bias_spec=P(h, None, None), seg_specs=(P(b, None), P(b, None)),
    )
