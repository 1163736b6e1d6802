"""Gated DeltaNet kernels (pallas TPU) and the per-position recurrence in
plain jnp.

A Gated DeltaNet head (Yang, Kautz, Hatamizadeh, arXiv:2412.06464) keeps
a state ``S`` of ``[d_k, d_v]`` and advances it a position at a time by a
gated rank-one delta rule::

    S_t = alpha_t * S_{t-1}                  alpha_t = exp(g_t), g_t <= 0
    u_t = beta_t * (v_t - S_t^T k_t)         beta_t in (0, 2) (negative
    S_t = S_t + k_t u_t^T                    eigenvalues allowed)
    o_t = S_t^T q_t

:func:`gdn_recurrence` is that, literally, over time (the tests' oracle).
The two kernels compute the same thing the chip's way:

* :func:`gdn_decode_update` (``tdx_gdn_decode_update``): ONE position for
  every lane of a decode tick.  The state of all lanes and layers is one
  float32 array ``[L, lanes, d_k, H * d_v]`` (``serve/kv_cache.py``): head
  ``h`` is columns ``[h * d_v, (h + 1) * d_v)``, so the minor dim is
  5,760 = 45 x 128 lanes at Olmo-Hybrid's widths where one of 192 would
  pad to 256.  A grid step is one lane: its ``[d_k, H * d_v]`` rows of
  layer ``layer`` (a scalar operand) come in through the BlockSpec
  pipeline, are advanced, and go out to the SAME buffer (the state is
  aliased to the output and donated by the program), so each lane's state
  is read once and written once.  ``k`` and ``q`` arrive as ``[d_k, H]``
  columns and are spread over their heads' ``d_v`` columns by one product
  with a one-hot ``[H, H * d_v]`` matrix (exact: a one-hot product adds
  one term); ``v``, ``alpha`` and ``beta`` arrive already in column
  order, eight lanes a block.  The rows are taken in tiles of 16, so no
  temporary holds more than a tile beside the state.  A lane with
  ``n_valid`` 0 gets ``alpha`` 1 and ``beta`` 0: its state comes back as
  it went in.
* :func:`gdn_chunk` (``tdx_gdn_chunk``): the positions of ONE sequence in
  chunks of 64, in the WY form (arXiv:2412.06464 section 3): within a
  chunk, with ``G`` the cumulative log decay from the chunk's start and
  ``Gam[i, j] = exp(G_i - G_j)`` (``j <= i``),

      A  = strictly_lower(beta_i Gam[i, j] k_i . k_j) ,  T = (I + A)^-1
      W  = T (beta exp(G) K) ,  U = T (beta V) - W S_0
      O  = (exp(G) Q) S_0 + (lower(Q K^T) * Gam) U
      S_C = exp(G_C) S_0 + (exp(G_C - G) K)^T U

  ``T`` is built by doubling: the inverse of ``I + A`` restricted to
  blocks of size ``b`` is extended to blocks of ``2b`` by
  ``T - T A_off T`` (``A_off`` the lower-left ``b x b`` block of each
  ``2b`` block), six products for 64, no division and no loop over rows.
  A grid step is (up to six heads, chunk): the heads' chains of small
  products are independent and interleave.  The state rides a VMEM
  scratch from chunk to chunk and starts from ``s0``, so a call resumes
  exactly where an earlier call on the same sequence stopped.  Positions past ``n_valid``
  get ``beta`` 0 and ``g`` 0, for which the step is exactly the identity,
  and chunks wholly past it are not computed.

Both run interpreted off-TPU (:func:`._interpret.resolve_interpret`
decides and counts).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._interpret import resolve_interpret

__all__ = ["CHUNK", "CHUNK_LEN", "DECODE_UPDATE", "gdn_chunk",
           "gdn_decode_update", "gdn_recurrence"]

DECODE_UPDATE = "tdx_gdn_decode_update"
CHUNK = "tdx_gdn_chunk"
CHUNK_LEN = 64
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
_LANES = 128
_LANE_BLOCK = 8   # lanes of v / alpha / beta / o that one block holds
_ROW_TILE = 16    # rows of the state a step of the decode kernel takes
_CHUNK_HEADS = 6  # heads a grid step of the chunk kernel takes (at most)
_VMEM_LIMIT = 64 * 1024 * 1024


def gdn_recurrence(q, k, v, beta, g, s0):
    """The rule a position at a time: q, k ``[T, H, d_k]``, v ``[T, H,
    d_v]``, beta, g ``[T, H]``, s0 ``[H, d_k, d_v]`` -> (o ``[T, H, d_v]``,
    s ``[H, d_k, d_v]``), float32 throughout."""
    def step(s, inp):
        q_t, k_t, v_t, b_t, g_t = inp
        s = jnp.exp(g_t)[:, None, None] * s
        kS = jnp.einsum("hk,hkv->hv", k_t, s, precision=HIGHEST)
        u = b_t[:, None] * (v_t - kS)
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", q_t, s, precision=HIGHEST)

    s, o = jax.lax.scan(step, s0.astype(F32), tuple(
        a.astype(F32) for a in (q, k, v, beta, g)))
    return o, s


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


# -- decode: one position a lane, in place -----------------------------------


def _decode_kernel(layer_ref, s_ref, kT_ref, qT_ref, e_ref, vab_ref,
                   o_ref, s_out, kx_scr, *, tile):
    del layer_ref  # it picks the block (the index maps)
    HV = s_ref.shape[-1]
    rows = (jax.lax.broadcasted_iota(jnp.int32, (_LANE_BLOCK, HV), 0)
            == pl.program_id(0) % _LANE_BLOCK)

    def row(x):  # [8, HV] -> this lane's [1, HV]
        return jnp.sum(jnp.where(rows, x, 0.0), axis=0, keepdims=True)

    v, alpha, beta = row(vab_ref[0]), row(vab_ref[1]), row(vab_ref[2])
    e = e_ref[...]
    prec = HIGHEST if kT_ref.dtype == F32 else None
    n_tiles = s_ref.shape[0] // tile
    ks = jnp.zeros((1, HV), F32)
    for t in range(n_tiles):
        sl = slice(t * tile, (t + 1) * tile)
        kx = jnp.dot(kT_ref[sl, :], e, precision=prec,
                     preferred_element_type=F32)              # [tile, HV]
        kx_scr[sl, :] = kx
        ks = ks + jnp.sum(kx * s_ref[sl, :], axis=0, keepdims=True)
    u = beta * (v - alpha * ks)                               # [1, HV]
    o = jnp.zeros((1, HV), F32)
    for t in range(n_tiles):
        sl = slice(t * tile, (t + 1) * tile)
        s_new = alpha * s_ref[sl, :] + kx_scr[sl, :] * u
        s_out[sl, :] = s_new
        qx = jnp.dot(qT_ref[sl, :], e, precision=prec,
                     preferred_element_type=F32)
        o = o + jnp.sum(qx * s_new, axis=0, keepdims=True)
    o_ref[...] = jnp.where(rows, o, o_ref[...])


def gdn_decode_update(state, layer, q, k, v, beta, g, n_valid, *,
                      interpret: Optional[bool] = None):
    """One position a lane through layer ``layer`` of the state.

    ``state`` float32 ``[L, lanes, d_k, H * d_v]`` (the whole carry: only
    row ``layer`` is read and written, in place); ``layer`` an int32
    scalar; q, k ``[lanes, H, d_k]`` (their dtype is the products'
    operand dtype), v ``[lanes, H, d_v]``, beta, g ``[lanes, H]``,
    ``n_valid`` ``[lanes]`` (0: the lane sits the tick out).  Returns
    (o float32 ``[lanes, H, d_v]``, state)."""
    L, B, dk, HV = state.shape
    H = q.shape[1]
    dv = HV // H
    live = (n_valid > 0)[:, None]
    alpha = jnp.where(live, jnp.exp(g.astype(F32)), 1.0)
    beta = jnp.where(live, beta.astype(F32), 0.0)
    Hp = _round_up(H, _LANES)
    Bp = _round_up(B, _LANE_BLOCK)
    # one-hot [Hp, HV]: row h is 1 on head h's d_v columns (a constant)
    e = (jnp.arange(HV, dtype=jnp.int32)[None] // dv
         == jnp.arange(Hp, dtype=jnp.int32)[:, None]).astype(q.dtype)

    def cols(x):  # [B, H, dk] -> [B, dk, Hp]
        return jnp.pad(x.transpose(0, 2, 1), ((0, 0), (0, 0), (0, Hp - H)))

    vab = jnp.stack([v.reshape(B, HV).astype(F32),
                     jnp.repeat(alpha, dv, axis=1),
                     jnp.repeat(beta, dv, axis=1)])           # [3, B, HV]
    vab = jnp.pad(vab, ((0, 0), (0, Bp - B), (0, 0)))
    tile = _ROW_TILE if dk % _ROW_TILE == 0 else dk
    lane_blk = lambda b, lay: (b // _LANE_BLOCK, 0)
    state_blk = pl.BlockSpec((None, None, dk, HV),
                             lambda b, lay: (lay[0], b, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_decode_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[
                state_blk,
                pl.BlockSpec((None, dk, Hp), lambda b, lay: (b, 0, 0)),
                pl.BlockSpec((None, dk, Hp), lambda b, lay: (b, 0, 0)),
                pl.BlockSpec((Hp, HV), lambda b, lay: (0, 0)),
                pl.BlockSpec((3, _LANE_BLOCK, HV),
                             lambda b, lay: (0, b // _LANE_BLOCK, 0)),
            ],
            out_specs=[pl.BlockSpec((_LANE_BLOCK, HV), lane_blk), state_blk],
            scratch_shapes=[pltpu.VMEM((dk, HV), F32)],
        ),
        out_shape=(jax.ShapeDtypeStruct((Bp, HV), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=resolve_interpret(interpret),
        name=DECODE_UPDATE,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), state, cols(k), cols(q),
      e, vab)
    return o[:B].reshape(B, H, dv), state


# -- prefill: one sequence in chunks, WY form --------------------------------


def _chunk_head(q_ref, k_ref, v_ref, bg_ref, o_ref, st, hh):
    """One head's chunk: ``o_ref[hh]`` and the state ``st[hh]`` advanced."""
    dot = functools.partial(jnp.dot, precision=HIGHEST,
                            preferred_element_type=F32)
    nt = (((1,), (1,)), ((), ()))
    C = q_ref.shape[1]
    K = k_ref[hh]                                         # [C, dk]
    prec = HIGHEST if K.dtype == F32 else None            # bf16: exact
    KK = jax.lax.dot_general(K, K, nt, precision=prec,
                             preferred_element_type=F32)
    QK = jax.lax.dot_general(q_ref[hh], K, nt, precision=prec,
                             preferred_element_type=F32)
    Q, K, V = (q_ref[hh].astype(F32), K.astype(F32),
               v_ref[hh].astype(F32))
    bg = bg_ref[hh]                                       # [C, 128]
    lane = jax.lax.broadcasted_iota(jnp.int32, bg.shape, 1)
    G = jnp.sum(jnp.where(lane == 0, bg, 0.0), 1, keepdims=True)
    Bt = jnp.sum(jnp.where(lane == 1, bg, 0.0), 1, keepdims=True)
    i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    G_row = jnp.sum(jnp.where(i == j, G, 0.0), 0, keepdims=True)
    gam = jnp.exp(jnp.where(j <= i, G - G_row, -1e30))    # [C, C]
    A = jnp.where(j < i, Bt * gam * KK, 0.0)
    T = (i == j).astype(F32)
    b = 1
    while b < C:
        # i // (2b) == j // (2b), i in the block's lower half, j upper
        sh = b.bit_length()
        off = ((jnp.right_shift(i, sh) == jnp.right_shift(j, sh))
               & (jnp.bitwise_and(jnp.right_shift(i, sh - 1), 1) == 1)
               & (jnp.bitwise_and(jnp.right_shift(j, sh - 1), 1) == 0))
        T = T - dot(dot(T, jnp.where(off, A, 0.0)), T)
        b *= 2
    eG = jnp.exp(G)                                       # [C, 1]
    S0 = st[hh]                                           # [dk, dv]
    W = dot(T, Bt * eG * K)
    U = dot(T, Bt * V) - dot(W, S0)
    o_ref[hh] = dot(eG * Q, S0) + dot(jnp.where(j <= i, QK * gam, 0.0), U)
    last = jax.lax.broadcasted_iota(jnp.int32, G.shape, 0) == C - 1
    G_last = jnp.sum(jnp.where(last, G, 0.0), 0, keepdims=True)  # [1, 1]
    st[hh] = jnp.exp(G_last) * S0 + jax.lax.dot_general(
        jnp.exp(G_last - G) * K, U, (((0,), (0,)), ((), ())),
        precision=HIGHEST, preferred_element_type=F32)


def _chunk_kernel(nv_ref, q_ref, k_ref, v_ref, bg_ref, s0_ref, o_ref, s_ref,
                  st):
    c = pl.program_id(1)
    C = q_ref.shape[1]

    @pl.when(c == 0)
    def _():
        st[...] = s0_ref[...]

    @pl.when(c * C < nv_ref[0])
    def _():
        # The heads of a block are independent chains of small products:
        # one grid step takes several, so that they interleave.
        for hh in range(q_ref.shape[0]):
            _chunk_head(q_ref, k_ref, v_ref, bg_ref, o_ref, st, hh)

    @pl.when(c * C >= nv_ref[0])
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        s_ref[...] = st[...]


def chunk_len(S: int) -> int:
    """The chunk a sequence of ``S`` positions is computed in: 64, or the
    power of two (at least 8) that holds a shorter one."""
    if S >= CHUNK_LEN:
        return CHUNK_LEN
    return max(8, 1 << (S - 1).bit_length())


def gdn_chunk(q, k, v, beta, g, s0, n_valid, *,
              interpret: Optional[bool] = None):
    """The positions of one sequence from state ``s0``.

    q, k ``[S, H, d_k]``, v ``[S, H, d_v]``, beta, g ``[S, H]``, s0
    float32 ``[H, d_k, d_v]``, ``n_valid`` an int32 scalar (positions
    ``>= n_valid`` leave the state as it is).  Returns (o float32
    ``[S, H, d_v]``, state float32 ``[H, d_k, d_v]``)."""
    S, H, dk = q.shape
    dv = v.shape[-1]
    C = chunk_len(S)
    Sp = _round_up(S, C)
    valid = (jnp.arange(S, dtype=jnp.int32) < n_valid)[:, None]
    beta = jnp.where(valid, beta.astype(F32), 0.0)
    g = jnp.where(valid, g.astype(F32), 0.0)

    def heads(x):  # [S, H, ...] -> [H, Sp, ...]
        x = jnp.pad(x, ((0, Sp - S),) + ((0, 0),) * (x.ndim - 1))
        return jnp.moveaxis(x, 1, 0)

    G = heads(g).reshape(H, Sp // C, C).cumsum(-1).reshape(H, Sp)
    bg = jnp.zeros((H, Sp, _LANES), F32)
    bg = bg.at[:, :, 0].set(G).at[:, :, 1].set(heads(beta))
    hb = max(d for d in range(1, _CHUNK_HEADS + 1) if H % d == 0)
    blk = lambda w: pl.BlockSpec((hb, C, w), lambda h, c, nv: (h, c, 0))
    st_blk = pl.BlockSpec((hb, dk, dv), lambda h, c, nv: (h, 0, 0))
    o, s = pl.pallas_call(
        _chunk_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H // hb, Sp // C),
            in_specs=[blk(dk), blk(dk), blk(dv), blk(_LANES), st_blk],
            out_specs=[blk(dv), st_blk],
            scratch_shapes=[pltpu.VMEM((hb, dk, dv), F32)],
        ),
        out_shape=(jax.ShapeDtypeStruct((H, Sp, dv), F32),
                   jax.ShapeDtypeStruct((H, dk, dv), F32)),
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=resolve_interpret(interpret),
        name=CHUNK,
    )(jnp.reshape(n_valid, (1,)).astype(jnp.int32), heads(q), heads(k),
      heads(v), bg, s0.astype(F32))
    return jnp.moveaxis(o, 0, 1)[:S], s
