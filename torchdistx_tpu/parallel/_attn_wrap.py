"""shard_map scaffolding shared by the sequence-parallel attention wrappers
(`ring_attention.make_ring_attention`, `ulysses.make_ulysses_attention`)."""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.segments import normalize_segment_ids


def wrap_seq_parallel_attn(
    mesh: Mesh,
    *,
    name: str,
    spec: P,
    per_device: Callable,  # (q, k, v, causal, bias, segs) -> out, in shard_map
    validate: Optional[Callable] = None,  # (q, k, v) -> None, raises on misuse
    bias_spec: Optional[P] = None,  # how [H, S_q, S_k] bias shards, or None
    seg_specs: Optional[Tuple[P, P]] = None,  # (q_seg, kv_seg) sharding
    index_axis: Optional[str] = None,  # feed per_device a sharded ring index
):
    """Build a model-facing ``AttnFn`` that shard_maps ``per_device``.

    Global [B, S, H, D] arrays are partitioned by ``spec``; one shard_map
    is built per (causality, has-bias, has-segs) so the mapped callable
    stays jit-cacheable.  Additive [H, S_q, S_k] bias is partitioned by
    ``bias_spec`` when the strategy supports it (ring attention shards the
    query rows and block-slices the key columns); packed-sequence
    ``segment_ids`` — normalized to a ``(q_seg [B, S], kv_seg [B, T])``
    pair — are partitioned by ``seg_specs``.  Strategies that cannot
    reshard an operand leave its spec ``None`` and reject it.

    ``index_axis`` (opt-in): prepend a ``P(index_axis)``-sharded iota so
    ``per_device`` receives its ring position as a [1] array argument
    (``idx=``) instead of calling ``lax.axis_index``.  On jax 0.4.x +
    XLA:CPU the partition-id HLO that ``axis_index`` lowers to is left
    without a manual-sharding annotation whenever its only consumers sit
    inside a while-loop carry (sharding propagation does not look back
    through the loop), and the SPMD partitioner rejects the bare
    instruction — the sharded-iota input never emits partition-id at all.
    """

    def _build(causal: bool, with_bias: bool, with_segs: bool):
        in_specs = (
            ((P(index_axis),) if index_axis is not None else ())
            + (spec, spec, spec)
            + ((bias_spec,) if with_bias else ())
            + (seg_specs if with_segs else ())
        )

        @partial(
            shard_map,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=spec,
            check_vma=False,
        )
        def _sharded(*args):
            args = list(args)
            idx = args.pop(0) if index_axis is not None else None
            q, k, v = args[:3]
            extras = args[3:]
            bias = extras.pop(0) if with_bias else None
            segs = tuple(extras) if with_segs else None
            if index_axis is not None:
                return per_device(q, k, v, causal, bias, segs, idx=idx)
            return per_device(q, k, v, causal, bias, segs)

        return _sharded

    fns = {}

    def attn_fn(q, k, v, *, causal=True, bias=None, segment_ids=None):
        if bias is not None and bias_spec is None:
            raise NotImplementedError(f"{name} does not support bias")
        if segment_ids is not None and seg_specs is None:
            raise NotImplementedError(f"{name} does not support segment_ids")
        if validate is not None:
            validate(q, k, v)
        segs = None
        if segment_ids is not None:
            segs = normalize_segment_ids(
                segment_ids, q.shape[0], q.shape[1], k.shape[1]
            )
        key = (causal, bias is not None, segs is not None)
        if key not in fns:
            fns[key] = _build(*key)
        args = (q, k, v)
        if index_axis is not None:
            args = (jnp.arange(mesh.shape[index_axis], dtype=jnp.int32),) + args
        if bias is not None:
            args += (bias,)
        if segs is not None:
            args += segs
        return fns[key](*args)

    return attn_fn
