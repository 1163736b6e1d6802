"""Grouped matrix product (pallas TPU): rows sorted by group, one matrix a
group, each group's rows through its own matrix.

``lhs [m, k]`` holds the rows of group 0, then those of group 1, and so
on (``group_sizes [g]``, whose sum may be less than ``m``: the rows past
the last group belong to none); ``rhs [g, k, n]`` holds one matrix a
group.  Row ``i`` of group ``e`` comes out as ``lhs[i] @ rhs[e]``, in
``lhs``'s dtype from a float32 accumulator: what ``jax.lax.ragged_dot``
gives, computed the way the chip reads the operands best.

* **The grid walks (row tile, group) pairs**, as ``jax.experimental.
  pallas.ops.tpu.megablox`` ``gmm`` does: a *visit* is one group in one
  tile of ``tm`` rows that holds some of its rows, in row order (a tile
  that two groups share is visited twice, one after the other).  Which
  group and tile each visit takes, each group's first and last row and
  the number of visits ride the scalar-prefetch channel
  (:func:`visits`).  The grid is static, ``(m_tiles + g - 1, k
  tiles)``, the most visits any sizes can make; a step past the last
  visit computes nothing and keeps the last visit's block indices, so
  it starts no copy.  So a tile that holds no row of a group does no
  work for it, a group with no row reads none of its matrix, and a
  group's matrix is read once for each tile its rows touch.
* **A visit** accumulates ``[tm, tk] @ [tk, n]`` products over ``k``
  in a float32 scratch and, at the last ``k`` tile, writes the rows of
  its group into the output tile, which stays in VMEM while consecutive
  visits share it.  Rows of the tile that no visit owns keep whatever
  the buffer held: the caller selects by row (``models/afmoe.py``
  ``held_expert_sum`` does) and never reads them.
* **Tiling follows the static shape** (:func:`tiling`): the row tile
  from ``m``, ``k`` in tiles of up to 1,024 rows of the whole ``n``, so
  that a matrix comes in as a few long contiguous copies (3 of 6 MB at
  the trinity cell's ``[3072, 3072]``).
* :func:`grouped_matmul` is an inner ``jax.jit``: each distinct shape and
  tiling lowers ONCE in a program however many layers call it.

Runs interpreted off-TPU (:func:`._interpret.resolve_interpret` decides
and counts).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._interpret import resolve_interpret

__all__ = ["GMM", "grouped_matmul", "tiling", "visits"]

GMM = "tdx_moe_experts_gmm"
F32 = jnp.float32
I32 = jnp.int32
_VMEM_LIMIT = 64 * 1024 * 1024


def _block(dim: int, most: int) -> int:
    """``dim`` if it is at most ``most``, else the largest multiple of 128
    under ``most`` that divides it (``dim`` where there is none)."""
    if dim <= most:
        return dim
    return next((b for b in range(most // 128 * 128, 0, -128)
                 if dim % b == 0), dim)


def tiling(m: int, k: int) -> Tuple[int, int]:
    """(tm, tk) for ``m`` rows of width ``k``.  The row tile is taken
    from the static row count: 128 rows (or all, where fewer) for a
    decode tick's few pairs a group, 256 for a chunk's or a long
    prefill's, where fewer tiles mean fewer reads of a matrix and a
    tile's FLOPs still cost about its bytes (a 256-row tile of a
    ``[1024, 3072]`` block: 1.6 GFLOP, 8 us at the v5e's peak, against
    6 MB, 7.7 us at its bandwidth)."""
    tm = 256 if m >= 1536 else min(128, -(-m // 16) * 16)
    return tm, _block(k, 1024)


def visits(group_sizes, m: int, tm: int):
    """The (row tile, group) visits of ``m`` rows in tiles of ``tm``.

    Returns int32 arrays: the group and the tile of each visit ``[P]``
    (``P = ceil(m / tm) + g - 1``; entries past the last visit repeat
    it), each group's first row and the row after its last ``[g]``, and
    the number of visits ``[1]``."""
    g = group_sizes.shape[0]
    m_tiles = -(-m // tm)
    P = m_tiles + g - 1
    ends = jnp.minimum(jnp.cumsum(group_sizes.astype(I32)), m)
    starts = jnp.concatenate([jnp.zeros((1,), I32), ends[:-1]])
    first = starts // tm
    count = jnp.where(ends > starts, (ends - 1) // tm - first + 1, 0)
    before = jnp.cumsum(count) - count
    gid = jnp.repeat(jnp.arange(g, dtype=I32), count, total_repeat_length=P)
    step = jnp.arange(P, dtype=I32)
    tid = first[gid] + step - before[gid]
    num = count.sum(dtype=I32)
    last = jnp.maximum(num - 1, 0)
    gid = jnp.where(step < num, gid, gid[last])
    tid = jnp.clip(jnp.where(step < num, tid, tid[last]), 0, m_tiles - 1)
    return gid, tid, starts, ends, num.reshape(1)


def _kernel(gid_ref, tid_ref, start_ref, end_ref, num_ref, lhs_ref, rhs_ref,
            out_ref, acc, *, tm):
    v, kk = pl.program_id(0), pl.program_id(1)
    live = v < num_ref[0]
    prec = jax.lax.Precision.HIGHEST if lhs_ref.dtype == F32 else None

    @pl.when(live & (kk == 0))
    def _():
        acc[...] = jnp.zeros(acc.shape, F32)

    @pl.when(live)
    def _():
        acc[...] += jnp.dot(lhs_ref[...], rhs_ref[...], precision=prec,
                            preferred_element_type=F32)

    @pl.when(live & (kk == pl.num_programs(1) - 1))
    def _():
        g = gid_ref[v]
        row = tid_ref[v] * tm + jax.lax.broadcasted_iota(I32, acc.shape, 0)
        mine = (row >= start_ref[g]) & (row < end_ref[g])
        out_ref[...] = jnp.where(mine, acc[...], out_ref[...].astype(F32)
                                 ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def _gmm(lhs, rhs, group_sizes, *, tiles, interpret):
    m, k = lhs.shape
    n = rhs.shape[2]
    tm, tk = tiles
    mp = -(-m // tm) * tm
    if mp != m:
        lhs = jnp.pad(lhs, ((0, mp - m), (0, 0)))
    meta = visits(group_sizes, mp, tm)
    n_k = k // tk

    def k_of(v, kk, num):
        # past the last visit: the last visit's last k tile, so no copy
        return jnp.where(v < num[0], kk, n_k - 1)

    def lhs_map(v, kk, gid, tid, st, en, num):
        return tid[v], k_of(v, kk, num)

    def rhs_map(v, kk, gid, tid, st, en, num):
        return gid[v], k_of(v, kk, num), 0

    def out_map(v, kk, gid, tid, st, en, num):
        return tid[v], 0

    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(meta[0].shape[0], n_k),
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((None, tk, n), rhs_map)],
            out_specs=pl.BlockSpec((tm, n), out_map),
            scratch_shapes=[pltpu.VMEM((tm, n), F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((mp, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=GMM,
    )(*meta, lhs, rhs.astype(lhs.dtype))
    return out[:m]


def grouped_matmul(lhs, rhs, group_sizes, *,
                   interpret: Optional[bool] = None):
    """``lhs [m, k]`` rows sorted by group, ``rhs [g, k, n]``,
    ``group_sizes [g]`` int32 (summing to at most ``m``) -> ``[m, n]`` in
    ``lhs``'s dtype; rows past the last group are left undefined."""
    return _gmm(lhs, rhs, group_sizes, tiles=tiling(*lhs.shape),
                interpret=resolve_interpret(interpret))
