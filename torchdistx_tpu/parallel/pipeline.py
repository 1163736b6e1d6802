"""GPipe-style pipeline parallelism over the ``pp`` mesh axis.

The models stack their layers with ``nn.scan``, so every block parameter
already carries a leading ``(n_layers, ...)`` dim — pipelining is *just a
sharding decision* on that dim: shard it over ``pp`` (each stage holds
``n_layers / pp_size`` layers), run the local layers with ``lax.scan``,
and rotate activations stage-to-stage with ``ppermute`` through the
classic fill/steady/drain schedule.  Differentiable end-to-end (ppermute
transposes to the reverse permute, so GPipe's backward schedule falls out
of jax.grad).

Entry points:

* :func:`pipeline_forward` — the per-device schedule, inside ``shard_map``;
* :func:`pipelined_decoder_apply` — full decoder LM forward (embed →
  pipelined blocks → norm/head) driven by the model family's exported
  :class:`~torchdistx_tpu.models.decomposition.PipelineDecomposition`;
* :func:`pipeline_plan_overrides` — plan rules putting the layer dim of
  block params on ``pp`` so deferred-init materializes each stage's layers
  straight onto its own devices.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .. import observe
from ..models.configs import TransformerConfig
from ..models.layers import Block, default_attention
from .collectives import ring_next, ring_prev, send_next, send_prev

# The fused (1F1B-family) schedules ship two executors (docs/
# performance.md §The schedule executor):
#
# * ``"segmented"`` (default) — phase-specialized: the tick table is
#   partitioned at build time into contiguous warmup / steady / cooldown
#   runs with statically-known archetypes, and each run is its own
#   ``lax.fori_loop`` whose body contains ONLY that archetype's work
#   (warmup ticks pay no backward vjp, drain ticks no forward chain, and
#   the head-loss ``lax.cond`` exists only where a seed can occur).  The
#   ring send of a tick's activations is issued straight after the
#   forward so XLA can overlap the ppermute with the same tick's
#   backward half (double buffering).
# * ``"uniform"`` — the historical single-loop executor: every tick runs
#   the full forward chain AND the full backward vjp with inactive work
#   discarded through masks.  Kept as the bitwise-parity baseline (the
#   segmented executor must reproduce its five outputs exactly —
#   tests/test_parallel.py, tests/test_interleave.py) and as the bench
#   A/B (`bench.py --phase schedule_measured`).
# * ``"auto"`` — resolves to one of the above per schedule: the
#   segmented executor's win is amortizing per-tick dispatch over long
#   steady runs, but for tiny schedules on small hosts its extra
#   fori_loop bodies cost more compile time than they save at runtime,
#   so ``auto`` keeps ``uniform`` there and picks ``segmented``
#   everywhere else.  The decision is emitted as a ``pp.executor_auto``
#   span so a trace shows which executor actually ran.
_EXECUTORS = ("segmented", "uniform", "auto")
# "tiny schedule on a small host" thresholds for the auto pick: at or
# under _AUTO_TINY_TICKS total ticks AND at or under _AUTO_SMALL_CORES
# host cores the segmented executor has nothing to amortize.
_AUTO_TINY_TICKS = 12
_AUTO_SMALL_CORES = 8


def _resolve_executor(
    executor: Optional[str], *, total_ticks: Optional[int] = None
) -> str:
    ex = executor or os.environ.get("TDX_PP_EXECUTOR", "segmented")
    if ex not in _EXECUTORS:
        raise ValueError(
            f"pipeline executor must be one of {_EXECUTORS}, got {ex!r} "
            f"(TDX_PP_EXECUTOR overrides the default)"
        )
    if ex != "auto":
        return ex
    ticks = int(total_ticks) if total_ticks is not None else 0
    cores = os.cpu_count() or 1
    picked = (
        "uniform"
        if ticks <= _AUTO_TINY_TICKS and cores <= _AUTO_SMALL_CORES
        else "segmented"
    )
    with observe.span("pp.executor_auto", category="pp") as sp:
        sp.set(picked=picked, total_ticks=ticks, host_cores=cores)
    return picked


def _note_schedule_segments(segs, label: str) -> None:
    """Publish the segment layout as ``tdx.pp.*`` gauges (docs/
    observability.md §counters) — trace-time, once per compile."""
    if not observe.enabled():
        return
    roles = {"warmup": 0, "steady": 0, "cooldown": 0}
    for s in segs:
        roles[s.role] = roles.get(s.role, 0) + s.ticks
    g = observe.counters().gauge
    g("tdx.pp.warmup_ticks", schedule=label).set(roles["warmup"])
    g("tdx.pp.steady_ticks", schedule=label).set(roles["steady"])
    g("tdx.pp.cooldown_ticks", schedule=label).set(roles["cooldown"])
    g("tdx.pp.segments", schedule=label).set(len(segs))


def _sum_aux(tree) -> jax.Array:
    """Sum every leaf of a (possibly empty) mutable-collection tree."""
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return jnp.float32(0.0)
    return sum(jnp.sum(l.astype(jnp.float32)) for l in leaves)


def valid_next_token_mask(segment_ids: jax.Array) -> jax.Array:
    """[B, S-1] f32 mask of valid next-token targets for packed ids:
    positions whose next token crosses a document boundary are excluded,
    and a NEGATIVE id marks the padded tail (also excluded).  The single
    definition every CE path shares — the GPipe/1F1B/dense loss
    agreement depends on them using the same predicate."""
    return jnp.logical_and(
        segment_ids[:, :-1] == segment_ids[:, 1:],
        segment_ids[:, 1:] >= 0,
    ).astype(jnp.float32)


def default_decomposition(cfg: TransformerConfig, attn_fn=default_attention):
    """Stock-family decomposition fallback: rope → Llama layout, else
    GPT-2.  Custom families must export their own
    (``model.pipeline_decomposition()``)."""
    from ..models.gpt2 import GPT2Model
    from ..models.llama import LlamaModel

    family = LlamaModel if cfg.positions == "rope" else GPT2Model
    return family(cfg, attn_fn=attn_fn).pipeline_decomposition()


def pipeline_forward(
    stage_fn: Callable,
    stage_params,
    x_mb: jax.Array,  # [n_mb, mb, S, d]
    seg_mb: Optional[jax.Array] = None,  # [n_mb, mb, S] packed ids
    *,
    axis_name: str = "pp",
    stage_arr: Optional[jax.Array] = None,  # [1] per-shard stage id
):
    """Run the GPipe schedule; call inside ``shard_map`` over ``axis_name``.

    ``stage_fn(stage_params, x, segs) -> (y, aux)`` runs this stage's
    layers; ``aux`` is a scalar side loss (MoE router balancing) summed
    over the stage's layers for that microbatch, 0.0 for dense stacks.
    ``seg_mb`` (packed-sequence ids) is replicated on every stage, so
    the ids for the microbatch stage ``s`` processes at step ``t`` are
    just ``seg_mb[t - s]`` — indexed locally, no rotation needed
    (warmup/drain steps read clipped garbage that the validity mask
    discards, exactly like the activations).  Returns ``(outs, aux)``:
    the final activations for all microbatches (valid on every stage
    after the closing psum-broadcast) and the schedule-wide aux loss —
    each stage's per-microbatch aux masked to real work steps, psummed
    over stages, averaged over microbatches (the same microbatched-aux
    semantics every gradient-accumulating trainer uses).
    """
    n = lax.psum(1, axis_name)
    # ``stage_arr`` (a P(axis_name)-sharded iota) sidesteps the jax
    # 0.4.x partition-id lowering that XLA's SPMD partitioner rejects
    # under a partial-manual shard_map (see pipeline_train_1f1b);
    # axis_index stays as the fallback for full-manual callers.
    stage = stage_arr[0] if stage_arr is not None else lax.axis_index(axis_name)
    n_mb = x_mb.shape[0]
    total = n_mb + n - 1
    has_segs = seg_mb is not None

    buf = jnp.zeros_like(x_mb[0])
    outs = jnp.zeros_like(x_mb)

    def body(t, carry):
        buf, outs, aux_acc = carry
        feed_idx = jnp.clip(t, 0, n_mb - 1)
        inp = jnp.where(stage == 0, x_mb[feed_idx], buf)
        seg_in = (
            seg_mb[jnp.clip(t - stage, 0, n_mb - 1)] if has_segs else None
        )
        y, aux = stage_fn(stage_params, inp, seg_in)
        # Warmup (t < stage) and drain (t - stage >= n_mb) steps chew
        # garbage activations; their aux must not pollute the loss.
        work = (t >= stage) & (t - stage < n_mb)
        aux_acc = aux_acc + jnp.where(work, aux, 0.0)
        mb_idx = t - (n - 1)
        valid = (stage == n - 1) & (mb_idx >= 0) & (mb_idx < n_mb)
        widx = jnp.clip(mb_idx, 0, n_mb - 1)
        outs = outs.at[widx].set(jnp.where(valid, y, outs[widx]))
        buf = send_next(y, axis_name)
        return (buf, outs, aux_acc)

    _, outs, aux_acc = lax.fori_loop(
        0, total, body, (buf, outs, jnp.float32(0.0)), unroll=False
    )
    # Broadcast the last stage's outputs to all stages; sum every
    # stage's (layer-local) aux and average over microbatches.
    outs = lax.psum(
        jnp.where(stage == n - 1, outs, jnp.zeros_like(outs)), axis_name
    )
    aux = lax.psum(aux_acc, axis_name) / n_mb
    return outs, aux


def _block_chain(cfg: TransformerConfig, attn_fn, angles, causal=True):
    block = Block(cfg, attn_fn=attn_fn, causal=causal)
    collect_aux = cfg.moe is not None

    def chain(stacked_params, x, segs=None):
        def body(carry, layer_params):
            x, aux = carry
            if collect_aux:
                y, mvars = block.apply(
                    {"params": layer_params}, x, angles=angles,
                    segment_ids=segs, mutable=["losses"],
                )
                aux = aux + _sum_aux(mvars.get("losses", {}))
            else:
                y = block.apply(
                    {"params": layer_params}, x, angles=angles,
                    segment_ids=segs,
                )
            return (y, aux), None

        (y, aux), _ = lax.scan(body, (x, jnp.float32(0.0)), stacked_params)
        return y, aux

    return chain


def pipelined_decoder_apply(
    cfg: TransformerConfig,
    params,
    tokens: jax.Array,  # [B, S] tokens (or [B, H, W, C] images for ViT)
    mesh: Mesh,
    *,
    decomp=None,
    n_microbatches: int = 4,
    axis_name: str = "pp",
    attn_fn=default_attention,
    positions: Optional[str] = None,  # None = follow cfg.positions
    segment_ids: Optional[jax.Array] = None,  # [B, S] packed ids
    return_aux: bool = False,
):
    """Full decoder-LM forward with pipelined blocks.

    Embedding and head run replicated across stages (their params are
    small relative to the blocks); the blocks' layer dim is sharded over
    ``pp``.  ``decomp`` is the family's exported
    :class:`~torchdistx_tpu.models.decomposition.PipelineDecomposition`
    (``model.pipeline_decomposition()``); when omitted, the stock families
    are resolved from ``cfg.positions`` ("rope" → Llama/Mixtral layout,
    else GPT-2) — custom families must pass their own.
    """
    if decomp is None:
        if positions is not None and positions != cfg.positions:
            import warnings

            warnings.warn(
                f"pipelined_decoder_apply: positions={positions!r} conflicts "
                f"with cfg.positions={cfg.positions!r}; the config wins. "
                f"Pass decomp= (model.pipeline_decomposition()) to override "
                f"the family explicitly."
            )
        decomp = default_decomposition(cfg, attn_fn)

    p = params["params"]
    B = tokens.shape[0]  # tokens [B, S] or images [B, H, W, C]
    assert B % n_microbatches == 0, (
        f"n_microbatches ({n_microbatches}) must divide the batch size ({B})"
    )

    x = decomp.embed(p, tokens)
    S = x.shape[1]  # post-embed length (patches + cls for vision families)
    chain = _block_chain(cfg, attn_fn, decomp.angles(S), causal=decomp.causal)

    x_mb = x.reshape(n_microbatches, B // n_microbatches, S, cfg.d_model)
    seg_mb = (
        None if segment_ids is None
        else segment_ids.reshape(n_microbatches, B // n_microbatches, S)
    )

    pp_fn = shard_map(
        lambda sid, sp, xm, sm: pipeline_forward(
            chain, sp, xm, sm, axis_name=axis_name, stage_arr=sid
        ),
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(), P()),
        out_specs=(P(), P()),
        # Full-manual over every mesh axis: the partial-manual mode
        # (axis_names={axis_name}, dp left auto) dies in XLA's SPMD
        # partitioner on this jax/XLA pair — an unannotated
        # partition-id HLO at best, a manual-subgroup CHECK crash at
        # worst.  Under full-manual the dp groups run identical
        # replicated compute, which is what the auto annotations
        # declared anyway.
        check_vma=False,
    )
    y, aux = pp_fn(
        jnp.arange(mesh.shape[axis_name], dtype=jnp.int32),
        decomp.block_params(p), x_mb, seg_mb,
    )
    x = y.reshape(B, S, cfg.d_model)

    # final norm + head (replicated compute)
    logits = decomp.head(p, x)
    return (logits, aux) if return_aux else logits


# ---------------------------------------------------------------------------
# 1F1B (one-forward-one-backward) schedule
# ---------------------------------------------------------------------------


def _mb_ce_sum(logits, tokens, segment_ids, denom):
    """Next-token CE of ONE microbatch in SUM form over the GLOBAL valid
    count ``denom`` — summing these across microbatches reproduces the
    full-batch mean CE exactly (packed segments included), which is what
    lets each microbatch's loss gradient be computed the moment its
    forward finishes (the 1F1B requirement)."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
    tgt = tokens[:, 1:]
    ll = jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    if segment_ids is None:
        return -jnp.sum(ll) / denom
    return -jnp.sum(ll * valid_next_token_mask(segment_ids)) / denom


class _FusedSetup:
    """Shared prologue of the fused (1F1B-family) schedules: everything
    before the per-schedule shard_map body.  One definition so a fix to
    the CE denominator, the segment handling, or the embed vjp can never
    land in one schedule and silently miss the other."""

    def __init__(self, cfg, params, tokens, decomp, n_microbatches,
                 attn_fn, segment_ids):
        p = params["params"]
        assert "blocks" in p and "block" in p["blocks"], (
            "the fused pipeline schedules expect scan-stacked blocks at "
            "params['params']['blocks']['block'] (the stock families' "
            "layout)"
        )
        B, _S_in = tokens.shape
        assert B % n_microbatches == 0
        self.cfg, self.decomp, self.params = cfg, decomp, params
        self.B, self.n_mb = B, n_microbatches
        self.mbs = B // n_microbatches
        self.p = p
        self.p_light = {k: v for k, v in p.items() if k != "blocks"}
        # Embed (replicated) with vjp so dx cotangents flowing out of the
        # first chunk close the loop on the embedding parameters.
        self.x, self.embed_vjp = jax.vjp(
            lambda q: decomp.embed(q, tokens), self.p_light
        )
        S = self.x.shape[1]
        self.S = S
        self.chain = _block_chain(
            cfg, attn_fn, decomp.angles(S), causal=decomp.causal
        )
        self.x_mb = self.x.reshape(self.n_mb, self.mbs, S, cfg.d_model)
        self.tok_mb = tokens.reshape(self.n_mb, self.mbs, S)
        self.has_segs = segment_ids is not None
        self.seg_mb = (
            segment_ids.reshape(self.n_mb, self.mbs, S)
            if self.has_segs else None
        )
        # Global CE denominator, known before any backward starts (packed
        # segments make it data-dependent, but it's a cheap elementwise
        # reduction over the ids).
        if self.has_segs:
            self.denom = jnp.maximum(
                jnp.sum(valid_next_token_mask(segment_ids)), 1.0
            )
        else:
            self.denom = jnp.float32(B * (S - 1))

    def head_loss(self, q, y, tok, segs):
        return _mb_ce_sum(self.decomp.head(q, y), tok, segs, self.denom)

    def finish(self, g_blk, g_light, dx_out, ce, aux):
        """Shared epilogue: close the embed vjp, mirror the variables
        structure for optax, assemble metrics."""
        (g_embed,) = self.embed_vjp(
            dx_out.reshape(self.B, self.S, self.cfg.d_model).astype(
                self.x.dtype
            )
        )
        g_light = jax.tree.map(jnp.add, g_light, g_embed)
        # Mirror the full variables structure (MoE inits carry a
        # "losses" collection next to "params"; optax needs
        # grads ≅ params).
        grads = {
            k: (
                {**g_light, "blocks": {"block": g_blk}}
                if k == "params"
                else jax.tree.map(jnp.zeros_like, v)
            )
            for k, v in self.params.items()
        }
        loss = ce + aux
        return {"loss": loss, "ce": ce, "aux": aux}, grads


def pipeline_train_1f1b(
    cfg: TransformerConfig,
    params,
    tokens: jax.Array,  # [B, S]
    mesh: Mesh,
    *,
    decomp,
    n_microbatches: int = 4,
    axis_name: str = "pp",
    attn_fn=default_attention,
    segment_ids: Optional[jax.Array] = None,
    executor: Optional[str] = None,
    _run_segments: Optional[int] = None,
):
    """Fused forward+backward pipeline step under the 1F1B schedule.

    Returns ``(metrics, grads)`` where ``grads`` matches the structure of
    ``params`` — unlike the GPipe path this does NOT go through
    ``jax.grad``: the schedule interleaves each microbatch's backward one
    stage behind its forward, so stage ``s`` holds at most ``O(pp - s)``
    in-flight microbatches of *recompute* state instead of every
    microbatch's layer activations.  Mechanics per tick ``t``:

    * forward microbatch ``f = t - stage`` (stage 0 feeds from the batch,
      others from the rotated activation buffer), stashing the stage
      INPUT only — the backward recomputes the stage interior under
      ``jax.vjp`` (remat: ~1 extra forward per microbatch, the classic
      1F1B-on-TPU tradeoff);
    * backward microbatch ``b = t - (2(pp-1) - stage)``: the LAST stage
      computes head+loss on the tick's own forward output (``b == f``
      there) and seeds the cotangent; other stages consume the cotangent
      rotated from the next stage, which arrives exactly one tick ahead
      of use.  Block-param gradients accumulate stage-locally (sharded
      over ``pp``); head/embed gradients ride a psum.

    Total ticks: ``2(pp-1) + n_mb`` — the 1F1B bubble.  The MoE router
    aux rides the same machinery: each forward's aux gets cotangent
    ``1/n_mb`` in the stage vjp, matching the GPipe semantics.

    The loss is the exact full-batch mean CE (see :func:`_mb_ce_sum`)
    plus the microbatch-averaged aux, so metrics match the GPipe path.

    ``executor`` picks the loop structure (``"segmented"`` /
    ``"uniform"``, see :data:`_EXECUTORS`); both produce bitwise-equal
    outputs.  ``_run_segments`` (segmented only) truncates the schedule
    to its first ``k`` segments — a bench hook for per-segment wall
    timing by differencing, NOT a training API (the outputs of a
    truncated run are partial accumulators).
    """
    from .interleave import flat_1f1b_segments

    su = _FusedSetup(cfg, params, tokens, decomp, n_microbatches,
                     attn_fn, segment_ids)
    n_mb = su.n_mb
    p, p_light, chain, head_loss = su.p, su.p_light, su.chain, su.head_loss
    x_mb, tok_mb, seg_mb, has_segs = su.x_mb, su.tok_mb, su.seg_mb, su.has_segs
    pp = mesh.shape[axis_name]
    flat_segs = flat_1f1b_segments(pp, n_mb)
    # Resolved AFTER the schedule size is known so "auto" can size its
    # pick to this schedule's actual tick count.
    executor = _resolve_executor(executor, total_ticks=2 * (pp - 1) + n_mb)
    if executor == "segmented":
        _note_schedule_segments(flat_segs, "1f1b")

    def schedule(stage_arr, stacked, q_light, x_mb, tok_mb, seg_mb):
        n = lax.psum(1, axis_name)
        # Stage id arrives as a P(pp)-sharded iota instead of
        # lax.axis_index: under the partial-manual shard_map (dp stays
        # auto) jax 0.4.x leaves axis_index's partition-id HLO without a
        # sharding annotation and XLA's SPMD partitioner rejects the
        # module ("PartitionId instruction is not supported for SPMD
        # partitioning") — the cause of the long-standing tier-1
        # PartitionId failures.  A sharded input needs no partitioning.
        stage = stage_arr[0]
        is_last = stage == n - 1
        T = 2 * (n - 1) + n_mb
        # Circular input stash: stage s needs microbatch i's input from
        # its forward (tick s+i) to its backward (tick 2(n-1)-s+i), a
        # window of 2(n-1-s) ticks — so a DEPTH-sized buffer suffices
        # and stashed-activation memory does not grow with n_mb.  (The
        # dx_out buffer below is O(n_mb) by necessity: it IS the embed
        # output's cotangent for the whole batch, the same size as the
        # x_mb input itself.)
        W = min(n_mb, 2 * (n - 1) + 1)

        def fwd_half(t, buf, stash, aux_acc):
            # ---- forward: microbatch f = t - stage ----------------------
            f = t - stage
            do_f = (f >= 0) & (f < n_mb)
            fi = jnp.clip(f, 0, n_mb - 1)
            inp = jnp.where(stage == 0, x_mb[fi], buf)
            segs_f = seg_mb[fi] if has_segs else None
            y, aux = chain(stacked, inp, segs_f)
            slot_f = fi % W
            stash = stash.at[slot_f].set(jnp.where(do_f, inp, stash[slot_f]))
            aux_acc = aux_acc + jnp.where(do_f, aux, 0.0)
            # Ring send issued straight after the forward (double
            # buffering): the ppermute has no data dependency on the
            # backward half below, so the transfer of tick t's
            # activations overlaps tick t's backward compute.
            buf = send_next(y, axis_name)
            return y, buf, stash, aux_acc

        def bwd_half(t, y, carry_b, *, seed):
            dbuf, stash, g_blk, g_light, dx_out, ce_acc = carry_b
            # ---- backward: microbatch b = t - (2(n-1) - stage) ----------
            b = t - (2 * (n - 1) - stage)
            do_b = (b >= 0) & (b < n_mb)
            bi = jnp.clip(b, 0, n_mb - 1)
            segs_b = seg_mb[bi] if has_segs else None

            if seed:
                def seed_last(_):
                    # b == f at the last stage: head+loss on this tick's y.
                    ce, hvjp = jax.vjp(
                        lambda q, yy: head_loss(q, yy, tok_mb[bi], segs_b),
                        q_light, y,
                    )
                    dq, dy = hvjp(jnp.float32(1.0))
                    return ce, dy.astype(y.dtype), dq

                def seed_mid(_):
                    return (
                        jnp.float32(0.0),
                        dbuf,
                        jax.tree.map(jnp.zeros_like, q_light),
                    )

                ce_j, dy, dq = lax.cond(is_last, seed_last, seed_mid, None)
                ce_acc = ce_acc + jnp.where(do_b, ce_j, 0.0)
                g_light = jax.tree.map(
                    lambda a, g: a + jnp.where(do_b, g, 0), g_light, dq
                )
            else:
                # Seed-free segment (the drain): every active backward
                # consumes a rotated cotangent; ce/g_light untouched
                # (the uniform executor adds exact +0.0 here, which is
                # bitwise-identity on accumulators built from +0.0).
                dy = dbuf

            # Recompute the stage interior and pull gradients through it;
            # the aux output's cotangent is 1/n_mb (microbatch average).
            _, cvjp = jax.vjp(
                lambda sp, xx: chain(sp, xx, segs_b), stacked, stash[bi % W]
            )
            d_sp, dx = cvjp((dy, jnp.float32(1.0 / n_mb)))
            g_blk = jax.tree.map(
                lambda a, g: a + jnp.where(do_b, g, 0), g_blk, d_sp
            )
            dx_out = dx_out.at[bi].set(
                jnp.where(do_b & (stage == 0), dx, dx_out[bi])
            )
            dbuf = send_prev(dx, axis_name)
            return (dbuf, stash, g_blk, g_light, dx_out, ce_acc)

        def make_tick(has_f: bool, has_b: bool, has_seed: bool):
            def tick(t, carry):
                buf, dbuf, stash, g_blk, g_light, dx_out, ce_acc, aux_acc = carry
                y = None
                if has_f:
                    y, buf, stash, aux_acc = fwd_half(t, buf, stash, aux_acc)
                if has_b:
                    dbuf, stash, g_blk, g_light, dx_out, ce_acc = bwd_half(
                        t, y, (dbuf, stash, g_blk, g_light, dx_out, ce_acc),
                        seed=has_seed,
                    )
                return (buf, dbuf, stash, g_blk, g_light, dx_out,
                        ce_acc, aux_acc)
            return tick

        carry = (
            jnp.zeros_like(x_mb[0]),
            jnp.zeros_like(x_mb[0]),
            jnp.zeros((W, *x_mb.shape[1:]), x_mb.dtype),
            jax.tree.map(jnp.zeros_like, stacked),
            jax.tree.map(jnp.zeros_like, q_light),
            jnp.zeros_like(x_mb),
            jnp.float32(0.0),
            jnp.float32(0.0),
        )
        if executor == "uniform":
            carry = lax.fori_loop(
                0, T, make_tick(True, True, True), carry, unroll=False
            )
        else:
            segs = flat_segs
            if _run_segments is not None:
                segs = segs[:_run_segments]
            for seg in segs:
                carry = lax.fori_loop(
                    seg.t0, seg.t1,
                    make_tick(seg.has_f, seg.has_b, seg.has_seed),
                    carry, unroll=False,
                )
        _, _, _, g_blk, g_light, dx_out, ce, aux = carry
        # Stage-local block grads stay sharded over pp (out_spec);
        # everything else reduces: head grads live on the last stage,
        # dx on stage 0, ce on the last stage, aux on all.
        g_light = lax.psum(g_light, axis_name)
        dx_out = lax.psum(
            jnp.where(stage == 0, dx_out, jnp.zeros_like(dx_out)), axis_name
        )
        ce = lax.psum(ce, axis_name)
        aux = lax.psum(aux, axis_name) / n_mb
        return g_blk, g_light, dx_out, ce, aux

    pp_fn = shard_map(
        schedule,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(), P(), P(), P()),
        out_specs=(P(axis_name), P(), P(), P(), P()),
        # Full-manual over every mesh axis: the partial-manual mode
        # (axis_names={axis_name}, dp left auto) dies in XLA's SPMD
        # partitioner on this jax/XLA pair — an unannotated
        # partition-id HLO at best, a manual-subgroup CHECK crash at
        # worst.  Under full-manual the dp groups run identical
        # replicated compute, which is what the auto annotations
        # declared anyway.
        check_vma=False,
    )
    g_blk, g_light, dx_out, ce, aux = pp_fn(
        jnp.arange(pp, dtype=jnp.int32),
        decomp.block_params(p), p_light, x_mb, tok_mb, seg_mb
    )
    return su.finish(g_blk, g_light, dx_out, ce, aux)


# ---------------------------------------------------------------------------
# Interleaved (virtual-stage) 1F1B
# ---------------------------------------------------------------------------


def _interleave_perm(n_layers: int, pp: int, v: int):
    """(perm, inv): layer-dim permutations mapping the model's layer
    order to the interleaved shard layout and back.

    Global chunk ``k`` (of ``K = pp*v``, each ``Lc = n_layers/K`` layers)
    lives on device ``k % pp`` as local chunk ``k // pp``; ``shard_map``
    splits the leading dim contiguously, so device ``d``'s slice must
    hold chunks ``d, d+pp, ..`` back to back."""
    import numpy as np

    K = pp * v
    assert n_layers % K == 0, (
        f"interleaved pipeline needs pp*n_chunks ({K}) to divide the "
        f"layer count ({n_layers})"
    )
    Lc = n_layers // K
    perm = np.empty(n_layers, dtype=np.int32)
    pos = 0
    for d in range(pp):
        for j in range(v):
            k = j * pp + d
            perm[pos:pos + Lc] = np.arange(k * Lc, (k + 1) * Lc)
            pos += Lc
    inv = np.argsort(perm).astype(np.int32)
    return perm, inv


def pipeline_train_interleaved(
    cfg: TransformerConfig,
    params,
    tokens: jax.Array,  # [B, S]
    mesh: Mesh,
    *,
    decomp,
    n_microbatches: int = 4,
    n_chunks: int = 2,
    axis_name: str = "pp",
    attn_fn=default_attention,
    segment_ids: Optional[jax.Array] = None,
    executor: Optional[str] = None,
    _run_segments: Optional[int] = None,
):
    """Interleaved (virtual-stage) 1F1B: :func:`pipeline_train_1f1b`
    semantics with ``n_chunks`` model chunks per device (VERDICT r3 next
    #7), driven by the static tables of
    :func:`~torchdistx_tpu.parallel.interleave.interleaved_schedule`.

    Each tick runs ONE chunk-forward and one chunk-backward (each
    ``1/n_chunks`` of a device's layers), so the fill/drain bubble costs
    chunk-sized stalls: measured tick counts beat the flat schedule's
    ``n_chunks * (2(pp-1) + n_mb)`` equivalents by the schedule's
    ``bubble_fraction`` (reported by ``bench.py --phase pp_bubble`` and
    docs/benchmarks.md).  The price is ``n_chunks``× more ring transfers
    per microbatch and the schedule-depth stash.

    Gradients are exact: differential-tested against the flat schedules
    and the dense microbatched oracle (tests/test_interleave.py).

    Sharding note: block params arrive in model layer order; the layer
    dim is gathered into the interleaved layout (and gradients scattered
    back) OUTSIDE ``shard_map`` — on real meshes this is a one-shot
    resharding collective per step.  Materializing straight into the
    interleaved layout via a plan override is the known follow-up.
    """
    from .interleave import interleaved_schedule

    su = _FusedSetup(cfg, params, tokens, decomp, n_microbatches,
                     attn_fn, segment_ids)
    n_mb = su.n_mb
    p, p_light, chain, head_loss = su.p, su.p_light, su.chain, su.head_loss
    x_mb, tok_mb, seg_mb, has_segs = su.x_mb, su.tok_mb, su.seg_mb, su.has_segs
    pp = mesh.shape[axis_name]
    v = n_chunks
    sched = interleaved_schedule(pp, v, n_mb)
    tbl = {k: jnp.asarray(a) for k, a in sched.tables().items()}
    sched_segs = sched.segments()
    # Resolved AFTER the schedule is built so "auto" can size its pick
    # to this schedule's actual tick count.
    executor = _resolve_executor(
        executor, total_ticks=sum(s.ticks for s in sched_segs)
    )
    if executor == "segmented":
        _note_schedule_segments(sched_segs, "interleaved")
    perm, inv = _interleave_perm(cfg.n_layers, pp, v)
    Lc = cfg.n_layers // (pp * v)

    def schedule(stage_arr, stacked, q_light, x_mb, tok_mb, seg_mb):
        # Sharded-iota stage id — see the pipeline_train_1f1b schedule
        # for why lax.axis_index cannot be used under the
        # partial-manual shard_map (jax 0.4.x PartitionId lowering).
        stage = stage_arr[0]
        # Local chunk-major view: [v, Lc, ...] per param leaf.
        stacked_r = jax.tree.map(
            lambda a: a.reshape(v, Lc, *a.shape[1:]), stacked
        )
        act_shape = x_mb.shape[1:]  # [mbs, S, d]

        def at_set(buf, slot, value, enabled):
            # clip is a trace-shape guard only: slot is -1 exactly when
            # ``enabled`` is false (the write is discarded), and every
            # ENABLED slot is proven in-bounds at schedule build time
            # (interleaved_schedule's table validation) and by the
            # tests/test_interleave.py property sweep.
            i = jnp.clip(slot, 0, buf.shape[0] - 1)
            return buf.at[i].set(jnp.where(enabled, value, buf[i]))

        def make_tick(has_f: bool, has_b: bool, has_seed: bool,
                      has_f_arr: bool, has_b_arr: bool):
            """One tick body containing ONLY the given archetype's work;
            ``make_tick(*[True]*5)`` is the uniform executor's body."""
            # A seed backward consumes its own tick's forward output, so
            # a seed-bearing segment always has forwards (schedule
            # invariant: t(B(K-1, i)) == t(F(K-1, i))).
            assert has_f or not has_seed

            def tick(t, carry):
                (buf, dbuf, inbox_f, inbox_b, stash,
                 g_blk, g_light, dx_out, ce_acc, aux_acc) = carry

                # ---- arrivals: what neighbours sent LAST tick ----------
                if has_f_arr:
                    inbox_f = at_set(inbox_f, tbl["f_arr"][stage, t], buf,
                                     tbl["f_arr"][stage, t] >= 0)
                if has_b_arr:
                    inbox_b = at_set(inbox_b, tbl["b_arr"][stage, t], dbuf,
                                     tbl["b_arr"][stage, t] >= 0)

                # ---- forward ------------------------------------------
                y = None
                if has_f:
                    floc = tbl["f_loc"][stage, t]
                    do_f = floc >= 0
                    fj = jnp.clip(floc, 0, v - 1)
                    fm = jnp.clip(tbl["f_mb"][stage, t], 0, n_mb - 1)
                    f_rd = tbl["f_rd"][stage, t]
                    inp = jnp.where(
                        f_rd < 0,  # only ever batch-feed (global chunk 0)
                        x_mb[fm],
                        inbox_f[jnp.clip(f_rd, 0, inbox_f.shape[0] - 1)],
                    )
                    segs_f = seg_mb[fm] if has_segs else None
                    sp_f = jax.tree.map(lambda a: a[fj], stacked_r)
                    y, aux = chain(sp_f, inp, segs_f)
                    stash = at_set(stash, tbl["stash_w"][stage, t], inp, do_f)
                    aux_acc = aux_acc + jnp.where(do_f, aux, 0.0)
                    # Ring send issued straight after the forward (double
                    # buffering): no data dependency on the backward half,
                    # so the ppermute overlaps this tick's backward.
                    buf = ring_next(y, axis_name)

                # ---- backward -----------------------------------------
                if has_b:
                    bloc = tbl["b_loc"][stage, t]
                    do_b = bloc >= 0
                    bj = jnp.clip(bloc, 0, v - 1)
                    bm = jnp.clip(tbl["b_mb"][stage, t], 0, n_mb - 1)
                    b_rd = tbl["b_rd"][stage, t]
                    segs_b = seg_mb[bm] if has_segs else None

                    if has_seed:
                        is_seed = do_b & (b_rd < 0)

                        def seed_last(_):
                            ce, hvjp = jax.vjp(
                                lambda q, yy: head_loss(
                                    q, yy, tok_mb[bm], segs_b),
                                q_light, y,
                            )
                            dq, dy = hvjp(jnp.float32(1.0))
                            return ce, dy.astype(y.dtype), dq

                        def seed_mid(_):
                            return (
                                jnp.float32(0.0),
                                inbox_b[jnp.clip(b_rd, 0,
                                                 inbox_b.shape[0] - 1)],
                                jax.tree.map(jnp.zeros_like, q_light),
                            )

                        ce_j, dy, dq = lax.cond(is_seed, seed_last,
                                                seed_mid, None)
                        ce_acc = ce_acc + jnp.where(do_b, ce_j, 0.0)
                        g_light = jax.tree.map(
                            lambda a, g: a + jnp.where(do_b, g, 0),
                            g_light, dq
                        )
                    else:
                        # Seed-free segment (the drain): every active
                        # backward consumes a rotated cotangent;
                        # ce/g_light untouched (the uniform executor
                        # adds exact +0.0 — bitwise identity).
                        dy = inbox_b[jnp.clip(b_rd, 0,
                                              inbox_b.shape[0] - 1)]

                    sp_b = jax.tree.map(lambda a: a[bj], stacked_r)
                    _, cvjp = jax.vjp(
                        lambda sp, xx: chain(sp, xx, segs_b),
                        sp_b,
                        stash[jnp.clip(tbl["stash_r"][stage, t], 0,
                                       stash.shape[0] - 1)],
                    )
                    d_sp, dx = cvjp((dy, jnp.float32(1.0 / n_mb)))
                    g_blk = jax.tree.map(
                        lambda a, g: a.at[bj].add(jnp.where(do_b, g, 0)),
                        g_blk, d_sp,
                    )
                    # global chunk 0's backward emits the embed cotangent
                    dx_out = dx_out.at[bm].set(
                        jnp.where(do_b & (stage == 0) & (bloc == 0),
                                  dx, dx_out[bm])
                    )
                    dbuf = ring_prev(dx, axis_name)

                return (buf, dbuf, inbox_f, inbox_b, stash,
                        g_blk, g_light, dx_out, ce_acc, aux_acc)

            return tick

        carry = (
            jnp.zeros(act_shape, x_mb.dtype),
            jnp.zeros(act_shape, x_mb.dtype),
            jnp.zeros((sched.n_f_slots, *act_shape), x_mb.dtype),
            jnp.zeros((sched.n_b_slots, *act_shape), x_mb.dtype),
            jnp.zeros((sched.n_stash_slots, *act_shape), x_mb.dtype),
            jax.tree.map(jnp.zeros_like, stacked_r),
            jax.tree.map(jnp.zeros_like, q_light),
            jnp.zeros_like(x_mb),
            jnp.float32(0.0),
            jnp.float32(0.0),
        )
        if executor == "uniform":
            carry = lax.fori_loop(
                0, sched.T, make_tick(True, True, True, True, True),
                carry, unroll=False,
            )
        else:
            segs = sched_segs
            if _run_segments is not None:
                segs = segs[:_run_segments]
            for seg in segs:
                carry = lax.fori_loop(
                    seg.t0, seg.t1,
                    make_tick(seg.has_f, seg.has_b, seg.has_seed,
                              seg.has_f_arr, seg.has_b_arr),
                    carry, unroll=False,
                )
        (_, _, _, _, _, g_blk, g_light, dx_out, ce, aux) = carry
        g_blk = jax.tree.map(
            lambda a: a.reshape(v * Lc, *a.shape[2:]), g_blk
        )
        g_light = lax.psum(g_light, axis_name)
        dx_out = lax.psum(
            jnp.where(stage == 0, dx_out, jnp.zeros_like(dx_out)), axis_name
        )
        ce = lax.psum(ce, axis_name)
        aux = lax.psum(aux, axis_name) / n_mb
        return g_blk, g_light, dx_out, ce, aux

    pp_fn = shard_map(
        schedule,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(), P(), P(), P()),
        out_specs=(P(axis_name), P(), P(), P(), P()),
        # Full-manual over every mesh axis: the partial-manual mode
        # (axis_names={axis_name}, dp left auto) dies in XLA's SPMD
        # partitioner on this jax/XLA pair — an unannotated
        # partition-id HLO at best, a manual-subgroup CHECK crash at
        # worst.  Under full-manual the dp groups run identical
        # replicated compute, which is what the auto annotations
        # declared anyway.
        check_vma=False,
    )
    blocks = decomp.block_params(p)
    blocks_il = jax.tree.map(lambda a: jnp.take(a, perm, axis=0), blocks)
    g_blk_il, g_light, dx_out, ce, aux = pp_fn(
        jnp.arange(pp, dtype=jnp.int32),
        blocks_il, p_light, x_mb, tok_mb, seg_mb
    )
    g_blk = jax.tree.map(lambda a: jnp.take(a, inv, axis=0), g_blk_il)
    return su.finish(g_blk, g_light, dx_out, ce, aux)


def pipeline_plan_overrides(axis_name: str = "pp"):
    """Plan rules sharding the layer dim of block params over ``pp`` —
    prepend to a model plan so materialization lands each stage's layers
    on its own devices."""
    return [
        (r".*blocks\.block\..*", P(axis_name)),
    ]
