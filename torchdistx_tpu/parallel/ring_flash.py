"""Ring attention with pallas flash-kernel block compute.

The dense ring (`ring_attention.py`) materializes each [s, t] block of
logits in registers/HBM via XLA einsums.  This variant runs every ring
step through the blockwise pallas kernels (`ops/flash_attention.py`), so
per-device memory stays O(block_q x block_k) even for the *local* chunk —
the composition of the two long-context mechanisms: ring for the
cross-device sequence axis, flash for the on-device one.  (The reference
has no long-context layer at all, SURVEY.md §5; this is the TPU-native
design the charter calls for.)

Scheme (per device, inside ``shard_map``; local q [B, s, H, D], k/v
[B, t, KV, D], ``n`` devices on the ring):

* forward — each step holds key block ``src = (idx - i) % n``.  Under
  causal masking a block is *past* (full, un-masked flash), *diagonal*
  (causal flash), or *future* (skipped via ``lax.switch``).  Each step
  yields a block output and block logsumexp; blocks merge with the
  standard pairwise softmax-merge (rescale by ``exp(lse - max)``) so the
  result is exactly the global softmax.
* backward — a second ring pass.  The flash backward kernels recompute
  block probabilities from the *global* lse (``p = exp(s - lse)``), which
  makes each block's dq/dk/dv contribution globally normalized; dq
  accumulates locally while dk/dv accumulators rotate with their k/v
  blocks, arriving home after the full cycle (ring-flash backward).

Gradients are exact: verified against the dense oracle in
tests/test_parallel.py::TestRingFlash.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..ops._interpret import resolve_interpret
from ..ops.flash_attention import (
    _LANES,
    _bwd_call,
    _fwd_call,
    _pad_seq,
    _round8,
    _seg_carrier,
)
from ._attn_wrap import wrap_seq_parallel_attn
from .collectives import ppermute_next

_NEG = -1e30


def _merge(o, lse, o_i, lse_i):
    """Pairwise softmax merge of two normalized block outputs.

    ``o``/``o_i`` are [BH, s, D] normalized attention outputs, ``lse``/
    ``lse_i`` their [BH, s] logsumexps; returns the merged pair."""
    m = jnp.maximum(lse, lse_i)
    w = jnp.exp(lse - m)
    w_i = jnp.exp(lse_i - m)
    denom = w + w_i
    o = (o * w[..., None] + o_i * w_i[..., None]) / denom[..., None]
    return o, m + jnp.log(denom)


def _ring_fwd_loop(
    qh, kh, vh, groups, causal, axis_name, bq, bk, interpret,
    bias=None, heads=None, segs=None, idx1=None,
):
    n = lax.psum(1, axis_name)
    # ``idx1`` is the wrapper-fed [1] ring position (see
    # wrap_seq_parallel_attn's index_axis); axis_index stays as the
    # fallback for direct in-shard_map callers.
    idx = idx1[0] if idx1 is not None else lax.axis_index(axis_name)
    BH, s, D = qh.shape
    t = kh.shape[1]

    # The query carrier is loop-invariant: build it once, outside the
    # ring loop; the key carrier depends on the step's column slice and
    # is built per block (8-lane: a cheap broadcast).
    qc = None if segs is None else _seg_carrier(segs[0], bq)

    def flash_block(k_cur, v_cur, blk_causal, bias_blk=None, seg_blk=None):
        out, lse3 = _fwd_call(
            qh, k_cur, v_cur, groups, blk_causal, bq, bk, interpret,
            bias=bias_blk, heads=heads,
            segc=None if seg_blk is None else (qc, _seg_carrier(seg_blk, bk)),
        )
        return out.astype(jnp.float32), lse3[:, :s, 0]

    def step(i, carry):
        o, lse, k_cur, v_cur = carry
        src = (idx - i) % n  # which global key block k_cur holds
        # Bias rides row-sharded [H, s, T_total]; slice this step's
        # key-block columns (same scheme as the dense ring).  Segment ids
        # likewise: query ids local, key ids resident and column-sliced.
        blk = (
            None if bias is None
            else lax.dynamic_slice_in_dim(bias, src * t, t, axis=2)
        )
        seg_blk = (
            None if segs is None
            else lax.dynamic_slice_in_dim(segs[1], src * t, t, axis=1)
        )
        if causal:
            # (blk/seg_blk may be statically None — empty pytree operands)
            o_i, lse_i = lax.switch(
                jnp.where(src < idx, 0, jnp.where(src == idx, 1, 2)),
                [
                    lambda kv: flash_block(kv[0], kv[1], False, kv[2], kv[3]),
                    lambda kv: flash_block(kv[0], kv[1], True, kv[2], kv[3]),
                    lambda kv: (  # future: contributes nothing
                        jnp.zeros((BH, s, D), jnp.float32),
                        jnp.full((BH, s), _NEG, jnp.float32),
                    ),
                ],
                (k_cur, v_cur, blk, seg_blk),
            )
        else:
            o_i, lse_i = flash_block(k_cur, v_cur, False, blk, seg_blk)
        o, lse = _merge(o, lse, o_i, lse_i)
        return o, lse, ppermute_next(k_cur, axis_name), ppermute_next(v_cur, axis_name)

    o0 = jnp.zeros((BH, s, D), jnp.float32)
    lse0 = jnp.full((BH, s), _NEG, jnp.float32)
    o, lse, _, _ = lax.fori_loop(0, n, step, (o0, lse0, kh, vh))
    return o.astype(qh.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12, 13))
def _ring_flash(qh, kh, vh, bias, qseg, kseg, idx1, groups, heads, causal,
                axis_name, bq, bk, interpret):
    """One differentiable ring for every call shape: ``bias`` is either a
    row-sharded [Hb, s, T_total] array or ``None`` (an empty pytree —
    its cotangent is ``None`` and the dbias strips are skipped);
    ``qseg``/``kseg`` are [B, s] local / [B, T_total] resident segment
    ids or ``None`` (integer operands, zero cotangent); ``idx1`` is the
    optional [1] ring position (integer operand, zero cotangent)."""
    out, _ = _ring_fwd_loop(
        qh, kh, vh, groups, causal, axis_name, bq, bk, interpret,
        bias=bias, heads=heads,
        segs=None if qseg is None else (qseg, kseg), idx1=idx1,
    )
    return out


def _ring_flash_fwd(qh, kh, vh, bias, qseg, kseg, idx1, groups, heads, causal,
                    axis_name, bq, bk, interpret):
    out, lse = _ring_fwd_loop(
        qh, kh, vh, groups, causal, axis_name, bq, bk, interpret,
        bias=bias, heads=heads,
        segs=None if qseg is None else (qseg, kseg), idx1=idx1,
    )
    return out, (qh, kh, vh, bias, qseg, kseg, idx1, out, lse)


def _ring_flash_bwd(groups, heads, causal, axis_name, bq, bk, interpret,
                    res, do):
    qh, kh, vh, bias, qseg, kseg, idx1, out, lse = res
    has_bias = bias is not None
    has_segs = qseg is not None
    n = lax.psum(1, axis_name)
    idx = idx1[0] if idx1 is not None else lax.axis_index(axis_name)
    BH, s, D = qh.shape
    BKV, t = kh.shape[0], kh.shape[1]
    # Lane-broadcast padded global lse, the row-carrier layout the
    # backward kernels consume; delta likewise, hoisted out of the ring
    # loop (both are loop-invariant).
    from ..ops.flash_attention import _delta_carrier

    lse_p = _pad_seq(lse, bq)  # (BH, s_padded)
    if lse_p.shape[1] != s:
        # Padded query rows: with bias, exp(bias - 0) need not be ~1, so
        # pin padded lse large-positive to force p -> 0 there (their do
        # rows are zero anyway; this guards against inf * 0 = NaN).
        lse_p = lse_p.at[:, s:].set(jnp.float32(1e30))
    lse3 = jnp.broadcast_to(lse_p[:, :, None], (BH, lse_p.shape[1], _LANES))
    delta3 = _delta_carrier(do, out, bq, lse3.shape)

    qc = None if qseg is None else _seg_carrier(qseg, bq)

    def grads_block(k_cur, v_cur, blk_causal, bias_blk, seg_blk):
        r = _bwd_call(
            qh, k_cur, v_cur, do, out, lse3, groups, blk_causal, bq, bk,
            interpret, delta3=delta3, bias=bias_blk, heads=heads,
            segc=None if seg_blk is None else (qc, _seg_carrier(seg_blk, bk)),
            want_dbias=has_bias,
        )
        return (
            r[0].astype(jnp.float32),
            r[1].astype(jnp.float32),
            r[2].astype(jnp.float32),
            r[3] if has_bias else None,  # [Hb, s, t] f32
        )

    def zeros_block(kv):
        return (
            jnp.zeros((BH, s, D), jnp.float32),
            jnp.zeros((BKV, t, D), jnp.float32),
            jnp.zeros((BKV, t, D), jnp.float32),
            jnp.zeros((bias.shape[0], s, t), jnp.float32) if has_bias else None,
        )

    def step(i, carry):
        dq, k_cur, v_cur, dk, dv, dbias = carry
        src = (idx - i) % n
        blk = (
            lax.dynamic_slice_in_dim(bias, src * t, t, axis=2)
            if has_bias else None
        )
        seg_blk = (
            lax.dynamic_slice_in_dim(kseg, src * t, t, axis=1)
            if has_segs else None
        )
        if causal:
            dq_i, dk_i, dv_i, db_i = lax.switch(
                jnp.where(src < idx, 0, jnp.where(src == idx, 1, 2)),
                [
                    lambda kv: grads_block(kv[0], kv[1], False, kv[2], kv[3]),
                    lambda kv: grads_block(kv[0], kv[1], True, kv[2], kv[3]),
                    zeros_block,  # future: contributes nothing
                ],
                (k_cur, v_cur, blk, seg_blk),
            )
        else:
            dq_i, dk_i, dv_i, db_i = grads_block(k_cur, v_cur, False, blk, seg_blk)
        dq = dq + dq_i
        if has_bias:
            # Each global key block is visited exactly once per cycle, so
            # its dbias column strip is written (not accumulated) in place.
            dbias = lax.dynamic_update_slice_in_dim(dbias, db_i, src * t, axis=2)
        # dk/dv rotate WITH their k/v blocks: after the full cycle each
        # accumulator arrives back on its block's home device holding
        # every device's contribution.
        return (
            dq,
            ppermute_next(k_cur, axis_name),
            ppermute_next(v_cur, axis_name),
            ppermute_next(dk + dk_i, axis_name),
            ppermute_next(dv + dv_i, axis_name),
            dbias,
        )

    dq0 = jnp.zeros((BH, s, D), jnp.float32)
    dkv0 = jnp.zeros((BKV, t, D), jnp.float32)
    dbias0 = (
        jnp.zeros((bias.shape[0], s, bias.shape[2]), jnp.float32)
        if has_bias else None
    )
    dq, _, _, dk, dv, dbias = lax.fori_loop(
        0, n, step, (dq0, kh, vh, dkv0, dkv0, dbias0)
    )
    return (
        dq.astype(qh.dtype),
        dk.astype(kh.dtype),
        dv.astype(vh.dtype),
        dbias.astype(bias.dtype) if has_bias else None,
        None,  # qseg: integer operand, zero cotangent
        None,  # kseg
        None,  # idx1: ring position, zero cotangent
    )


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_flash_attention(
    q: jax.Array,  # [B, s, H, D] local sequence chunk
    k: jax.Array,  # [B, t, KV, D]
    v: jax.Array,  # [B, t, KV, D]
    *,
    axis_name: str = "sp",
    causal: bool = True,
    bias: Optional[jax.Array] = None,  # [H or 1, s, T_total] row-sharded
    segment_ids=None,  # (q_seg [B, s] local, kv_seg [B, T_total])
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
    axis_idx: Optional[jax.Array] = None,  # [1] ring position (optional)
) -> jax.Array:
    """Flash-kernel ring attention; call inside ``shard_map``.

    Causal masking requires equal local query/key chunks (self-attention);
    causal cross-attention should use the dense ring
    (:func:`ring_attention.ring_attention`), which handles the
    bottom-right offset.

    ``bias`` (additive, T5-style) arrives sharded over the query rows with
    the full key extent resident, exactly like the dense ring; each step
    slices this step's key-block columns and runs them through the
    bias-enabled flash kernels (including dbias in the backward).
    ``segment_ids`` (packed sequences) follow the same scheme: query ids
    row-sharded [B, s], key ids fully resident [B, T_total]."""
    B, s, H, D = q.shape
    t, KV = k.shape[1], k.shape[2]
    if H % KV:
        raise ValueError(f"Query heads ({H}) must be a multiple of KV heads ({KV}).")
    if causal and s != t:
        raise NotImplementedError(
            "causal ring_flash_attention requires equal q/k chunk lengths; "
            "use the dense ring for causal cross-attention."
        )
    groups = H // KV
    interpret = resolve_interpret(interpret)
    bq = min(block_q, _round8(s))
    bk = min(block_k, _round8(t))

    qh = q.transpose(0, 2, 1, 3).reshape(B * H, s, D)
    kh = k.transpose(0, 2, 1, 3).reshape(B * KV, t, D)
    vh = v.transpose(0, 2, 1, 3).reshape(B * KV, t, D)
    if bias is not None:
        n = lax.psum(1, axis_name)  # static: ring size
        if (
            bias.ndim != 3
            or bias.shape[0] not in (1, H)
            or bias.shape[1] != s
            or bias.shape[2] != n * t
        ):
            # (a [H, s, t] per-step shape here would silently clamp every
            # dynamic slice to column 0 — reject it loudly instead)
            raise ValueError(
                f"ring bias must be row-sharded [H or 1, s, T_total] = "
                f"[{H} or 1, {s}, {n * t}], got {tuple(bias.shape)}."
            )
        if not interpret and t > bk and bk % _LANES:
            raise ValueError(
                f"bias kernels tile the [s, t] plane, so on TPU block_k "
                f"({bk}) must be a multiple of {_LANES} (or >= the local "
                f"key chunk t={t}); Mosaic rejects narrower minor block dims."
            )
    qseg = kseg = None
    if segment_ids is not None:
        n = lax.psum(1, axis_name)
        qseg, kseg = segment_ids
        if tuple(qseg.shape) != (B, s) or tuple(kseg.shape) != (B, n * t):
            raise ValueError(
                f"ring segment_ids must be (q_seg [B, s]=[{B}, {s}] local, "
                f"kv_seg [B, T_total]=[{B}, {n * t}] resident), got "
                f"{tuple(qseg.shape)} / {tuple(kseg.shape)}."
            )
    out = _ring_flash(qh, kh, vh, bias, qseg, kseg, axis_idx, groups, H,
                      causal, axis_name, bq, bk, interpret)
    return out.reshape(B, H, s, D).transpose(0, 2, 1, 3)


def make_ring_flash_attention(
    mesh: Mesh,
    *,
    seq_axis: str = "sp",
    batch_axes: Tuple[str, ...] = ("dp", "fsdp"),
    head_axes: Tuple[str, ...] = ("tp",),
    block_q: int = 1024,
    block_k: int = 1024,
):
    """Build an ``AttnFn`` running flash-kernel ring attention over
    ``mesh`` — the drop-in long-context choice on TPU hardware.

    Additive bias runs through the bias-enabled flash kernels (so T5-class
    families get the blockwise path too); only causal *cross*-attention
    falls back to the dense ring (same sharding layout), which handles the
    bottom-right offset.  Models pass a single ``attn_fn`` and every call
    pattern works.
    """
    from .ring_attention import ring_attention

    present = set(mesh.axis_names)
    if seq_axis not in present:
        from ..models.layers import default_attention

        return default_attention
    b = tuple(a for a in batch_axes if a in present) or None
    h = tuple(a for a in head_axes if a in present) or None

    def per_device(q, k, v, causal, bias, segs, idx=None):
        if causal and q.shape[1] != k.shape[1]:
            # Causal cross-attention: the dense ring handles the
            # bottom-right offset the flash path does not.
            return ring_attention(
                q, k, v, axis_name=seq_axis, causal=causal, bias=bias,
                segment_ids=segs,
            )
        return ring_flash_attention(
            q, k, v, axis_name=seq_axis, causal=causal, bias=bias,
            segment_ids=segs, block_q=block_q, block_k=block_k,
            axis_idx=idx,
        )

    return wrap_seq_parallel_attn(
        mesh,
        name="ring flash attention",
        spec=P(b, seq_axis, h, None),
        # [H, S_q, S_k] bias: heads over tp, query rows over sp, full key
        # extent resident (ring steps slice the key-block columns).
        bias_spec=P(h, seq_axis, None),
        # (q_seg, kv_seg): query ids row-sharded, key ids fully resident.
        seg_specs=(P(b, seq_axis), P(b, None)),
        per_device=per_device,
        index_axis=seq_axis,
    )
