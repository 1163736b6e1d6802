"""Serving programs: prefill/decode forward builders + registry-aware
compiles.

The serving runtime runs THREE compiled program kinds per replica, all
built here so the engine, the warm tool, and the smoke tests construct
byte-identical programs:

* **init** — the replica's sharded parameter materialization: the
  :mod:`..abstract` deferred-init thunk jitted with the plan's
  ``out_shardings`` (zero-storage ``deferred_init`` on any host, params
  land sharded on the replica mesh);
* **prefill-<bucket>** — one prompt (padded to a deterministic
  power-of-two bucket) through the full stack with causal attention,
  writing its K/V into the paged pool and returning the last valid
  position's logits (the first generated token);
* **decode** — one token per batch lane through the stack, K/V scattered
  into each lane's current page/slot, context attended through the page
  table via :func:`torchdistx_tpu.ops.paged_attention`, logits out;
* **chunk-<bucket>** — one CHUNK of a prompt (suffix after a cached
  prefix, or one slice of a long prompt) at an arbitrary start
  position, attending the already-written pool context through the
  page table (:func:`torchdistx_tpu.ops.paged_attention.
  paged_prefill_attention`) — the program chunked prefill and
  prefix-reuse suffixes run, one per prefill bucket so chunk shapes
  bucket exactly like prompts do;
* **verify-<k>** — the speculative-decoding verify tick: every lane
  scores its last emitted token plus up to ``k`` drafted tokens in one
  batched ragged pass against the paged cache (the batched sibling of
  ``chunk-<bucket>``), returning logits for ALL ``k+1`` positions so
  greedy accept can take the longest matching draft prefix plus one
  corrected token (docs/serving.md §Speculative decoding);
* **cow** — the copy-on-write page duplication: clone one pool page
  (all layers, K and V) into a fresh page before a grower writes into
  a shared one.

Every compile goes through
:func:`..compile_service.compile_program`, so the pod-scale
artifact registry (``TDX_REGISTRY_DIR``), the persistent compile cache,
the exact hit/miss counters, the compile watchdog, and the chaos
``lower``/``compile``/``cache``/``registry`` sites all cover serving
programs exactly as they cover init programs.  Program fingerprints are
pure functions of (family, model config, serve shape) — every host
derives the same registry key, which is what makes
``tools/warm_cache.py --decode`` + a shared registry a ZERO-compile
replica bring-up (``make serve-smoke`` pins this).

Decode-mode block math mirrors the flax models exactly by applying the
SAME flax submodules (``DenseGeneral`` / ``MLP`` / ``make_norm``) to the
recorded param subtrees — the idiom the pipeline runner established
(models/decomposition.py) — so there is no second implementation of the
projections to drift; only the attention differs (paged vs dense), and
that is pinned against the dense oracle by tests and the smoke gate.
"""

from __future__ import annotations

import hashlib
import time
import warnings
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .. import abstract, chaos, compile_service, observe, transport
from .. import config as tdx_config
from ..models import (TransformerConfig, make_afmoe, make_gpt2, make_jamba,
                      make_llama, make_olmo_hybrid)
from ..models import afmoe, jamba, olmo_hybrid
from ..models.layers import MLP, apply_rope, default_attention, make_norm
from ..ops import paged_attention, paged_prefill_attention
from ..utils.logging import get_logger
from .kv_cache import (KVCacheConfig, StateCacheConfig, WindowCacheConfig,
                       pool_sharding, state_sharding)

__all__ = [
    "ServeConfig",
    "ServeProgramSpec",
    "build_chunk_prefill_fn",
    "build_cow_fn",
    "build_decode_fn",
    "build_prefill_fn",
    "build_verify_fn",
    "compile_serving_program",
    "make_model",
    "model_family",
    "serve_program_specs",
    "warm_serving",
]


@dataclass(frozen=True)
class ServeConfig:
    """Shape of one replica's serving runtime.  Everything here is part
    of the compiled programs' identity (and so of their registry keys):
    a warm and a serve with different ServeConfigs are different
    programs by design."""

    max_batch: int = 4          # decode lanes (fixed-shape batch)
    page_size: int = 16
    n_pages: int = 64           # pool pages, incl. the reserved null page
    max_pages_per_seq: Optional[int] = None  # default: fits max_seq_len
    prefill_buckets: Tuple[int, ...] = ()    # default: powers of two
    max_new_tokens: int = 16    # default per-request budget
    # Chunked-prefill cap: max prompt tokens computed per engine tick
    # per lane (None → TDX_PREFILL_CHUNK → the largest bucket, i.e. one
    # chunk).  A HOST-side scheduling knob: the compiled program set is
    # identical at every setting.
    prefill_chunk: Optional[int] = None
    # Prefix-sharing toggle (serve/prefix.py).  Host-side too: both
    # bench arms run the same registry-warmed programs.
    prefix_cache: bool = True
    # Speculative decoding (docs/serving.md §Speculative decoding).
    # ``spec_buckets`` is the compiled verify-<k> program family — a
    # SHAPE knob, like prefill_buckets.  ``spec_decode``/``spec_k`` are
    # host-side scheduling knobs (None → TDX_SPEC_DECODE/TDX_SPEC_K):
    # both bench arms, spec on and off, run the same registry-warmed
    # program set.
    spec_buckets: Tuple[int, ...] = ()       # default: (2, 4)
    spec_decode: Optional[bool] = None
    spec_k: Optional[int] = None
    # Pages of the window layer group's pool, its null page included (a
    # stack with windowed attention layers only, ``cfg.afmoe``; default:
    # every lane's window, one chunk's overhang and the null page).
    n_window_pages: Optional[int] = None

    def resolve(self, cfg: TransformerConfig) -> "ResolvedServeConfig":
        page = self.page_size
        maxp = self.max_pages_per_seq
        cap = min(cfg.max_seq_len, (self.n_pages - 1) * page)
        if maxp is None:
            maxp = -(-cap // page)
        max_context = min(cap, maxp * page)
        buckets = tuple(self.prefill_buckets)
        if not buckets:
            b, acc = 8, []
            while b < max_context:
                acc.append(b)
                b *= 2
            acc.append(max_context)
            buckets = tuple(sorted(set(acc)))
        else:
            buckets = tuple(sorted({min(b, max_context) for b in buckets}))
        chunk = self.prefill_chunk
        if chunk is None:
            chunk = tdx_config.get().prefill_chunk
        if chunk is None or chunk <= 0:
            chunk = buckets[-1]
        chunk = max(1, min(chunk, buckets[-1]))
        spec_buckets = tuple(self.spec_buckets) or (2, 4)
        # A verify-<k> tick writes k+1 positions; k must leave room for
        # at least one prior context token.
        spec_buckets = tuple(sorted(
            {max(1, min(k, max_context - 2)) for k in spec_buckets}
        ))
        spec_on = self.spec_decode
        if spec_on is None:
            spec_on = tdx_config.get().spec_decode
        spec_k = self.spec_k
        if spec_k is None:
            spec_k = tdx_config.get().spec_k
        spec_k = max(1, min(spec_k, spec_buckets[-1]))
        if cfg.afmoe is not None and (spec_on or self.prefix_cache):
            on = [n for n, v in (("spec_decode", spec_on),
                                 ("prefix_cache", self.prefix_cache)) if v]
            raise ValueError(
                f"{' and '.join(on)} cannot be on for a stack with windowed "
                f"attention layers: a sequence returns its window group's "
                f"pages behind the window while it lives, so a shared "
                f"prefix's window pages are gone once its first reader has "
                f"moved on (prefix cache), and the family has no verify-<k> "
                f"program yet (speculation); pass "
                f"ServeConfig(spec_decode=False, prefix_cache=False)")
        n_window_pages = window_row = 0
        if cfg.afmoe is not None:
            # A chunk of the largest bucket reads its window too; a row of
            # the group's table holds the pages both can span.
            w = cfg.afmoe.window
            window_row = -(-(w + buckets[-1]) // page) + 1
            n_window_pages = self.n_window_pages or (
                self.max_batch * (w // page + 2) + window_row + 1)
            if n_window_pages - 1 < window_row:
                raise ValueError(
                    f"n_window_pages={n_window_pages} cannot hold one "
                    f"sequence's chunk and window ({window_row} pages)")
        if _recurrent(cfg) and (spec_on or self.prefix_cache):
            on = [n for n, v in (("spec_decode", spec_on),
                                 ("prefix_cache", self.prefix_cache)) if v]
            kind = "Mamba" if cfg.mamba is not None else "Gated DeltaNet"
            raise ValueError(
                f"{' and '.join(on)} cannot be on for a stack with recurrent "
                f"({kind}) layers: a recurrent state is one value a lane, not "
                f"a row a token, so a rejected draft cannot be rolled back "
                f"out of it (verify-<k>) and a shared prefix's pages hold no "
                f"state to resume from (prefix cache); pass "
                f"ServeConfig(spec_decode=False, prefix_cache=False)")
        return ResolvedServeConfig(
            max_batch=self.max_batch, page_size=page, n_pages=self.n_pages,
            max_pages_per_seq=maxp, prefill_buckets=buckets,
            max_new_tokens=self.max_new_tokens, max_context=max_context,
            prefill_chunk=chunk, prefix_cache=self.prefix_cache,
            spec_buckets=spec_buckets, spec_decode=bool(spec_on),
            spec_k=spec_k, n_window_pages=n_window_pages,
            window_pages_per_seq=window_row,
        )


@dataclass(frozen=True)
class ResolvedServeConfig:
    """A :class:`ServeConfig` with every default pinned against one model
    config — the form program fingerprints and the engine consume."""

    max_batch: int
    page_size: int
    n_pages: int
    max_pages_per_seq: int
    prefill_buckets: Tuple[int, ...]
    max_new_tokens: int
    max_context: int
    prefill_chunk: int = 0      # resolved chunk cap (host-side knob)
    prefix_cache: bool = True   # prefix sharing armed (host-side knob)
    spec_buckets: Tuple[int, ...] = (2, 4)  # compiled verify-<k> family
    spec_decode: bool = True    # speculation armed (host-side knob)
    spec_k: int = 4             # max draft length (host-side knob)
    n_window_pages: int = 0     # the window group's pool (0: no such group)
    window_pages_per_seq: int = 0  # width of the group's table row

    def kv_config(self, cfg: TransformerConfig) -> KVCacheConfig:
        """The cache's layer groups: pages for the attention layers that
        read the whole context (all layers of a gpt2 / llama stack), for
        a hybrid stack one state slot a lane for its recurrent layers
        (Mamba: ``[16, d_inner]`` and a conv over ``d_inner`` channels;
        Gated DeltaNet: ``[d_k, H d_v]`` and a conv over q, k and v's
        channels), and for a stack with windowed attention layers their
        window group."""
        window = None
        if cfg.afmoe is not None:
            n_attn, state = afmoe.n_full_layers(cfg), None
            window = WindowCacheConfig(
                n_layers=afmoe.n_window_layers(cfg),
                window=cfg.afmoe.window, n_pages=self.n_window_pages,
                max_pages_per_seq=self.window_pages_per_seq)
        elif cfg.olmo_hybrid is not None:
            n_attn = olmo_hybrid.n_full_layers(cfg)
            H, dk, dv, HV, C = olmo_hybrid.widths(cfg)
            state = StateCacheConfig(
                n_layers=olmo_hybrid.n_linear_layers(cfg), d_inner=HV,
                d_state=dk, d_conv=cfg.olmo_hybrid.d_conv,
                lanes=self.max_batch, conv_channels=C)
        elif cfg.mamba is None:
            n_attn, state = cfg.n_layers, None
        else:
            n_attn = jamba.n_attn_layers(cfg)
            state = StateCacheConfig(
                n_layers=jamba.n_mamba_layers(cfg),
                d_inner=jamba.d_inner(cfg), d_state=cfg.mamba.d_state,
                d_conv=cfg.mamba.d_conv, lanes=self.max_batch)
        return KVCacheConfig(
            n_layers=n_attn, kv_heads=cfg.kv_heads,
            head_dim=cfg.head_size, page_size=self.page_size,
            n_pages=self.n_pages, state=state, window=window,
        )

    def bucket_for(self, n_tokens: int) -> int:
        for b in self.prefill_buckets:
            if b >= n_tokens:
                return b
        raise ValueError(
            f"prompt of {n_tokens} tokens exceeds the largest prefill "
            f"bucket {self.prefill_buckets[-1]} (max_context="
            f"{self.max_context})"
        )

    def spec_bucket_for(self, n_draft: int) -> int:
        for k in self.spec_buckets:
            if k >= n_draft:
                return k
        raise ValueError(
            f"draft of {n_draft} tokens exceeds the largest verify "
            f"bucket {self.spec_buckets[-1]}"
        )


FAMILIES = ("gpt2", "llama", "jamba", "afmoe", "olmo_hybrid")


def model_family(name: str) -> str:
    """The decode family of a zoo preset name: gpt2, jamba, afmoe and
    olmo_hybrid (``tiny-olmo-hybrid``) presets by name, any other dense
    decoder serves through the llama path."""
    for family in ("gpt2", "jamba", "afmoe", "olmo_hybrid"):
        if family.replace("_", "-") in name:
            return family
    return "llama"


def _recurrent(cfg: TransformerConfig) -> bool:
    """Whether the stack has recurrent layers (a state slot a lane)."""
    return cfg.mamba is not None or cfg.olmo_hybrid is not None


def make_model(family: str, cfg: TransformerConfig):
    if cfg.moe is not None:
        raise NotImplementedError(
            f"the serving runtime has no program for the capacity-based "
            f"MoEMLP (cfg.moe: tokens over capacity are dropped, no shared "
            f"expert, every expert held); the expert layer it serves is the "
            f"afmoe family's (cfg.afmoe: sigmoid router, dropless grouped "
            f"products over the experts the replica holds). Families: "
            f"{', '.join(FAMILIES)}"
        )
    if (family == "afmoe") != (cfg.afmoe is not None):
        raise ValueError(
            f"decode family {family!r} with cfg.afmoe="
            f"{'set' if cfg.afmoe is not None else 'None'}: the afmoe "
            f"family, and no other, takes a config with cfg.afmoe")
    if (family == "jamba") != (cfg.mamba is not None):
        raise ValueError(
            f"decode family {family!r} with cfg.mamba="
            f"{'set' if cfg.mamba is not None else 'None'}: the jamba "
            f"family, and no other, takes a config with Mamba layers")
    if (family == "olmo_hybrid") != (cfg.olmo_hybrid is not None):
        raise ValueError(
            f"decode family {family!r} with cfg.olmo_hybrid="
            f"{'set' if cfg.olmo_hybrid is not None else 'None'}: the "
            f"olmo_hybrid family, and no other, takes a config with Gated "
            f"DeltaNet layers")
    if family == "gpt2":
        return make_gpt2(cfg)
    if family == "llama":
        return make_llama(cfg)
    if family == "jamba":
        return make_jamba(cfg)
    if family == "afmoe":
        return make_afmoe(cfg)
    if family == "olmo_hybrid":
        return make_olmo_hybrid(cfg)
    raise ValueError(
        f"unknown decode family {family!r}; the families that exist: "
        f"{' | '.join(FAMILIES)}")


# ---------------------------------------------------------------------------
# decode-mode block forward (shared by prefill and decode)
# ---------------------------------------------------------------------------


def _norm_keys(cfg: TransformerConfig) -> Tuple[str, str]:
    base = "RMSNorm" if cfg.norm == "rmsnorm" else "LayerNorm"
    return f"{base}_0", f"{base}_1"


def _qkv(cfg: TransformerConfig, attn_p, h):
    """The models' exact projections: the same ``nn.DenseGeneral``
    modules ``models.layers.Attention`` builds, applied to the stored
    subtrees."""
    D = cfg.head_size

    def dense(feats, p):
        return nn.DenseGeneral(
            feats, axis=-1, use_bias=cfg.use_bias, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
        ).apply({"params": p}, h)

    q = dense((cfg.n_heads, D), attn_p["wq"])
    k = dense((cfg.kv_heads, D), attn_p["wk"])
    v = dense((cfg.kv_heads, D), attn_p["wv"])
    return q, k, v


def _attn_out(cfg: TransformerConfig, attn_p, o):
    return nn.DenseGeneral(
        cfg.d_model, axis=(-2, -1), use_bias=cfg.use_bias, dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
    ).apply({"params": attn_p["wo"]}, o)


def _mlp(cfg: TransformerConfig, blk, x):
    return MLP(cfg).apply({"params": blk["mlp"]}, x)


def _decode_attention(mesh, kv_heads: int) -> Callable:
    """:func:`..ops.paged_attention` as the decode program calls it.  A
    Mosaic call is opaque to the SPMD partitioner, which would gather a
    sharded pool onto every device to run it; on a mesh whose tp axis
    splits the kv heads (:func:`.kv_cache.pool_sharding`) the kernel
    therefore runs under ``shard_map``, each tp shard on its own query
    and kv heads (contiguous head blocks keep every query head with its
    kv group), lengths and page table replicated."""
    sh = pool_sharding(mesh, kv_heads)
    if sh is None or not any(sh.spec):
        return paged_attention
    heads = P(None, sh.spec[2], None)        # q / out [B, H, D]
    pool = P(None, sh.spec[2], None, None)   # flat pool [L*P, KV, page, D]
    return shard_map(
        paged_attention, mesh=mesh, in_specs=(heads, pool, pool, P(), P()),
        out_specs=heads, check_vma=False,
    )


def _page_writer(table, start, end, S: int, page_size: int) -> Callable:
    """``write(pool, x, base)``: put ``x`` [B, S, KV, D] — the rows of
    positions ``[start, start + S)`` of each sequence, valid below
    ``end`` — into the flat pool [rows, KV, page, D], in which page ``p``
    of the layer is row ``base + p``, through the page table [B, maxp],
    a whole page at a time: the pages the positions fall into are read,
    their valid rows replaced, and the pages written back.  The update
    then indexes the pool's major dim alone and its window is a page, so
    XLA keeps the pool in the layout the decode kernel reads and updates
    the scan's carry in place.  A scatter that indexes the slot too
    (``pool.at[page, :, slot].set``) makes XLA lay the pool out with the
    kv heads under the token rows, and a carried pool then changes
    layout, whole, twice a layer; one that indexes every kv head costs a
    2,048-token chunk what the carry saves (PERF.md, PR 27).  A page
    with no valid position (padding) is routed to the layer's null page,
    which gets back what it held."""
    B = table.shape[0]
    n = (S + page_size - 2) // page_size + 1  # pages S positions can span
    ords = (start // page_size)[:, None] + jnp.arange(n, dtype=jnp.int32)
    pos = ords[:, :, None] * page_size + jnp.arange(page_size,
                                                    dtype=jnp.int32)
    src = pos - start[:, None, None]  # [B, n, page]: the slot's row of x
    valid = (src >= 0) & (src < S) & (pos < end[:, None, None])
    pages = jnp.where(
        valid.any(-1),
        jnp.take_along_axis(
            table, jnp.minimum(ords, table.shape[1] - 1), axis=1),
        0)
    src = jnp.clip(src, 0, S - 1).reshape(B, -1, 1, 1)
    valid = valid[:, :, None, :, None]

    def write(pool, x, base):
        KV, D = x.shape[2:]
        rows = base + pages
        new = jnp.take_along_axis(x, src, axis=1)
        new = new.reshape(B, n, page_size, KV, D).transpose(0, 1, 3, 2, 4)
        return pool.at[rows].set(jnp.where(valid, new, pool[rows]))

    return write


def _write_kv(kp, vp, base, table, k, v, start, end):
    """Write ``k`` and ``v`` [B, S, KV, D] into the flat pools [L*P, KV,
    page, D] at this layer's rows (:func:`_page_writer`)."""
    write = _page_writer(table, start, end, k.shape[1], kp.shape[2])
    return write(kp, k, base), write(vp, v, base)


def _decode_block(cfg, blk, x, kp, vp, base, *, angles, positions,
                  lengths, page_table, attend):
    """One layer of the decode step: x [B, 1, d]; writes this token's
    K/V at (page, slot) and attends the whole context through the page
    table.  ``kp`` / ``vp`` are the flat pools, in which this layer's
    page ``p`` is row ``base + p`` (:func:`_scan_blocks`), so the kernel
    gets the table offset by ``base`` and the layer's null page is row
    ``base``."""
    n0, n1 = _norm_keys(cfg)
    h = make_norm(cfg).apply({"params": blk[n0]}, x)
    q, k, v = _qkv(cfg, blk["attn"], h)
    if angles is not None:
        q = apply_rope(q, angles)
        k = apply_rope(k, angles)
    kp, vp = _write_kv(kp, vp, base, page_table, k, v, positions,
                       positions + 1)
    attn = attend(q[:, 0], kp, vp, lengths, page_table + base)
    x = x + _attn_out(cfg, blk["attn"], attn[:, None])
    h2 = make_norm(cfg).apply({"params": blk[n1]}, x)
    x = x + _mlp(cfg, blk, h2)
    return x, kp, vp


def _prefill_block(cfg, blk, x, kp, vp, base, *, angles, positions, length,
                   page_table):
    """One layer of prefill: x [B, S, d]; causal attention over the
    in-flight K/V (a fresh prompt attends only itself), every valid
    position's K/V written into its page (:func:`_write_kv`); padded
    positions write nothing and are segment-masked out of the valid
    rows."""
    n0, n1 = _norm_keys(cfg)
    h = make_norm(cfg).apply({"params": blk[n0]}, x)
    q, k, v = _qkv(cfg, blk["attn"], h)
    if angles is not None:
        q = apply_rope(q, angles)
        k = apply_rope(k, angles)
    valid = positions < length[:, None]  # [B, S]
    kp, vp = _write_kv(kp, vp, base, page_table, k, v, positions[:, 0],
                       length)
    attn = default_attention(q, k, v, causal=True,
                             segment_ids=valid.astype(jnp.int32))
    x = x + _attn_out(cfg, blk["attn"], attn)
    h2 = make_norm(cfg).apply({"params": blk[n1]}, x)
    x = x + _mlp(cfg, blk, h2)
    return x, kp, vp


def _chunk_block(cfg, blk, x, kp, vp, base, *, angles, positions, end,
                 page_table):
    """One layer of CHUNKED prefill: x [B, S, d] holds prompt positions
    ``[start, start+S)``; valid positions' K/V are written into their
    pages (the caller already copy-on-wrote any shared first page), and
    attention runs through the page table over the WHOLE written
    context — cached prefix pages, earlier chunks, and this chunk's
    causal self-context — which is what lets a suffix prefill skip the
    prefix's FLOPs entirely."""
    n0, n1 = _norm_keys(cfg)
    h = make_norm(cfg).apply({"params": blk[n0]}, x)
    q, k, v = _qkv(cfg, blk["attn"], h)
    if angles is not None:
        q = apply_rope(q, angles)
        k = apply_rope(k, angles)
    kp, vp = _write_kv(kp, vp, base, page_table, k, v, positions[:, 0],
                       end)
    attn = paged_prefill_attention(q, kp, vp, positions, end,
                                   page_table + base)
    x = x + _attn_out(cfg, blk["attn"], attn)
    h2 = make_norm(cfg).apply({"params": blk[n1]}, x)
    x = x + _mlp(cfg, blk, h2)
    return x, kp, vp


def _program_name(name: str) -> Callable:
    """Decorator for a serving program's body: run it under
    ``jax.named_scope(name)``, which prefixes the ``op_name`` metadata of
    every operation it stages, and call it ``name``, which ``jax.jit``
    makes the HLO module's name (``jit_<name>``).  The module's name is
    what the profiler's ``XLA Modules`` line and the compile log show, so
    two prefill buckets can be told apart there; the scope is what an
    operation's metadata carries."""

    def wrap(fn: Callable) -> Callable:
        fn = jax.named_scope(name)(fn)
        fn.__name__ = fn.__qualname__ = name
        return fn

    return wrap


def _scan_blocks(decomp, p, x, k_pages, v_pages, block_step):
    """Thread x and both pools through the scan-stacked layers.  The
    pools are the scan's CARRY, viewed flat as [L*P, KV, page, D] (the
    two major dims merged: no data moves), and layer ``l`` addresses its
    page ``p`` at row ``l*P + p``: ``block_step(blk, x, kp, vp, base)``
    gets ``base = l*P``.  A carry is updated in place, so a layer writes
    its token rows and reads the pages it attends and nothing else; as
    mapped inputs and outputs of the scan the pools cost eight copies of
    a [P, KV, page, D] slice a layer (PERF.md, PR 27)."""
    blocks = decomp.block_params(p)
    pool_shape = k_pages.shape
    n_layers, n_pages = pool_shape[:2]
    flat = (n_layers * n_pages,) + pool_shape[2:]

    def body(carry, inp):
        x, kp, vp = carry
        blk, base = inp
        return block_step(blk, x, kp, vp, base), None

    (x, k_pages, v_pages), _ = jax.lax.scan(
        body, (x, k_pages.reshape(flat), v_pages.reshape(flat)),
        (blocks, jnp.arange(n_layers, dtype=jnp.int32) * n_pages),
    )
    return x, k_pages.reshape(pool_shape), v_pages.reshape(pool_shape)


# ---------------------------------------------------------------------------
# hybrid stacks: recurrent layers beside attention layers (models/jamba.py,
# models/olmo_hybrid.py)
# ---------------------------------------------------------------------------
#
# The programs of a hybrid stack take and return two more arrays than the
# others, between the pools and the per-call operands: the recurrent
# layer group's state (serve/kv_cache.py StateCacheConfig),
#
#   decode       (params, k_pages, v_pages, ssm, conv, tokens [B],
#                 positions [B], page_table [B, maxp])
#   prefill-<b>  (params, k_pages, v_pages, ssm, conv, tokens [1, b],
#                 length [1], page_table [1, maxp], slot [1])
#   chunk-<b>    (params, k_pages, v_pages, ssm, conv, tokens [1, b],
#                 start [1], end [1], page_table [1, maxp], slot [1])
#   -> (logits, k_pages, v_pages, ssm, conv)
#
# and all four ride the layer loops as their carry, as PR 27 made the
# pools ride: a recurrent layer reads and writes row ``g`` of the state
# (all lanes in decode, lane ``slot`` in a prefill), an attention layer
# its pages at ``j * P + page``.  There is no verify-<k> and no cow
# program: a recurrent state cannot be rolled back and shares nothing.
#
# One set of builders serves every such family; the family's model module
# supplies what differs: its parameter tree, embedding and head, the walk
# over its layer pattern (``scan_layers``), the block around a mixer (``block``:
# pre-norm for jamba, post-norm for olmo_hybrid), the attention layer's
# projections (``qkv`` / ``attn_out``) and the recurrent mixer on the
# cache's state (``serve_mixer(cfg, m, h, ssm, conv, g, mixer_state)``).
# ``mixer_state(ssm, conv, g)`` -> ``(s, tail, n_valid, put)`` is the
# builders' state accessor: the rows of layer ``g`` the call advances and
# how to put them back; a decode tick's is marked ``every_lane`` (with
# its ``n_valid``), which a mixer whose kernel works in place on the whole
# state takes instead.


def _hybrid_model(cfg):
    """The model module of a hybrid stack's family."""
    return olmo_hybrid if cfg.olmo_hybrid is not None else jamba


def _hybrid_layers(cfg, fam, mixer_state, attention):
    """The two layer bodies of ``fam.scan_layers`` over the carry ``(kp, vp,
    ssm, conv)``.  ``attention(q, k, v, kp, vp, j)`` -> ``(attn [B, S, H,
    D], kp, vp)``."""

    def rec_layer(m, f, x, carry, g):
        kp, vp, ssm, conv = carry

        def mixer(h):
            nonlocal ssm, conv
            out, ssm, conv = fam.serve_mixer(cfg, m, h, ssm, conv, g,
                                             mixer_state)
            return out

        x = fam.block(cfg, f, x, mixer)
        return x, (kp, vp, ssm, conv)

    def attn_layer(a, f, x, carry, j):
        kp, vp, ssm, conv = carry

        def mixer(h):
            nonlocal kp, vp
            attn, kp, vp = attention(*fam.qkv(cfg, a, h), kp, vp, j)
            return fam.attn_out(cfg, a, attn)

        x = fam.block(cfg, f, x, mixer)
        return x, (kp, vp, ssm, conv)

    return rec_layer, attn_layer


def _run_hybrid(cfg, p, x, k_pages, v_pages, ssm, conv, mixer_state,
                attention):
    """x through the stack with the pools (viewed flat, as
    :func:`_scan_blocks` views them) and the states as the loops' carry."""
    fam = _hybrid_model(cfg)
    pool_shape = k_pages.shape
    flat = (pool_shape[0] * pool_shape[1],) + pool_shape[2:]
    x, (kp, vp, ssm, conv) = fam.scan_layers(
        cfg, p, x,
        (k_pages.reshape(flat), v_pages.reshape(flat), ssm, conv),
        *_hybrid_layers(cfg, fam, mixer_state, attention))
    return x, kp.reshape(pool_shape), vp.reshape(pool_shape), ssm, conv


def _lane_state(slot, fresh, n_valid):
    """``mixer_state`` of a one-sequence program: lane ``slot``'s rows of
    layer ``g``, ``n_valid`` positions of the call real.  ``fresh`` (the
    call holds the sequence's first position) starts from zero whatever
    the slot held: a reused lane needs no clearing pass, and a stale
    state cannot leak."""

    def put(ssm, conv, g, s, tail):
        return (jax.lax.dynamic_update_slice(ssm, s[None], (g, slot, 0, 0)),
                jax.lax.dynamic_update_slice(
                    conv, tail[None], (g, 0, slot, 0)))

    def mixer_state(ssm, conv, g):
        _, _, N, Di = ssm.shape
        s = jax.lax.dynamic_slice(ssm, (g, slot, 0, 0), (1, 1, N, Di))[0]
        tail = jax.lax.dynamic_slice(
            conv, (g, 0, slot, 0), (1, conv.shape[1], 1, conv.shape[3]))[0]
        return (jnp.where(fresh, 0.0, s),
                jnp.where(fresh, jnp.zeros_like(tail), tail), n_valid, put)

    return mixer_state


def _every_lane(n_valid):
    """``mixer_state`` of the decode program: layer ``g``'s rows of every
    lane, ``n_valid`` (1, or 0 for a lane that sits the tick out) a lane.
    Marked ``every_lane`` for a mixer that takes the whole state."""

    def put(ssm, conv, g, s, tail):
        return (jax.lax.dynamic_update_index_in_dim(ssm, s, g, 0),
                jax.lax.dynamic_update_index_in_dim(conv, tail, g, 0))

    def mixer_state(ssm, conv, g):
        return (jax.lax.dynamic_index_in_dim(ssm, g, 0, keepdims=False),
                jax.lax.dynamic_index_in_dim(conv, g, 0, keepdims=False),
                n_valid, put)

    mixer_state.every_lane = True
    mixer_state.n_valid = n_valid
    return mixer_state


def _build_hybrid_decode_fn(cfg, scfg, mesh) -> Callable:
    if cfg.olmo_hybrid is not None and mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "the olmo_hybrid programs run on one chip: the delta-rule "
            "kernels are not partitioned over a mesh yet")
    fam = _hybrid_model(cfg)
    attend = _decode_attention(mesh, cfg.kv_heads)
    n_pages = scfg.n_pages

    @_program_name("tdx_serve_decode")
    def decode_fn(params, k_pages, v_pages, ssm, conv, tokens, positions,
                  page_table):
        p = fam.param_tree(params["params"])
        x = fam.embed_tokens(cfg, p, tokens[:, None])
        live = positions > 0  # idle and mid-prefill lanes sit the tick out
        lengths = jnp.where(live, positions + 1, 0)

        def attention(q, k, v, kp, vp, j):
            base = j * n_pages
            kp, vp = _write_kv(kp, vp, base, page_table, k, v, positions,
                               positions + 1)
            o = attend(q[:, 0], kp, vp, lengths, page_table + base)
            return o[:, None], kp, vp

        x, k_pages, v_pages, ssm, conv = _run_hybrid(
            cfg, p, x, k_pages, v_pages, ssm, conv,
            _every_lane(live.astype(jnp.int32)), attention)
        logits = fam.head_logits(cfg, p, x)[:, 0]
        return logits, k_pages, v_pages, ssm, conv

    return decode_fn


def _build_hybrid_prefill_fn(cfg, scfg, bucket, *, chunked: bool) -> Callable:
    """``prefill-<b>`` (``chunked`` False: a fresh prompt, dense causal
    attention over the bucket, state from zero) and ``chunk-<b>`` (a
    prompt's positions ``[start, end)``: attention through the page
    table over what earlier chunks wrote, the recurrence resumed from
    the state and the conv tail they left)."""
    fam = _hybrid_model(cfg)
    n_pages = scfg.n_pages
    kind = "chunk" if chunked else "prefill"

    def body(params, k_pages, v_pages, ssm, conv, tokens, start, end,
             page_table, slot):
        p = fam.param_tree(params["params"])
        S = tokens.shape[1]
        positions = start[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
        x = fam.embed_tokens(cfg, p, tokens)
        mixer_state = _lane_state(slot[0], start[0] == 0, end - start)

        def attention(q, k, v, kp, vp, j):
            base = j * n_pages
            kp, vp = _write_kv(kp, vp, base, page_table, k, v,
                               positions[:, 0], end)
            if chunked:
                o = paged_prefill_attention(q, kp, vp, positions, end,
                                            page_table + base)
            else:
                valid = positions < end[:, None]
                o = default_attention(q, k, v, causal=True,
                                      segment_ids=valid.astype(jnp.int32))
            return o, kp, vp

        x, k_pages, v_pages, ssm, conv = _run_hybrid(
            cfg, p, x, k_pages, v_pages, ssm, conv, mixer_state, attention)
        last = jnp.clip(end - 1 - start, 0, S - 1)[:, None, None]
        x_last = jnp.take_along_axis(x, jnp.broadcast_to(
            last, (x.shape[0], 1, x.shape[2])), axis=1)
        return (fam.head_logits(cfg, p, x_last)[0, 0], k_pages, v_pages,
                ssm, conv)

    if chunked:
        fn = body
    else:
        def fn(params, k_pages, v_pages, ssm, conv, tokens, length,
               page_table, slot):
            return body(params, k_pages, v_pages, ssm, conv, tokens,
                        jnp.zeros_like(length), length, page_table, slot)

    return _program_name(f"tdx_serve_{kind}_{bucket}")(fn)


# ---------------------------------------------------------------------------
# the afmoe family (models/afmoe.py): a window group beside the pools
# ---------------------------------------------------------------------------
#
# The programs carry two more arrays behind the pools, as a hybrid stack's
# carry its state: the window layer group's pool (keys and values in one
# array, serve/kv_cache.py WindowCacheConfig) and the pairs each held
# expert has got (int32 [expert layers, held], a running count on the
# device that every call adds its own to and the engine reads the change
# of), and take the window group's table row and its first token's
# position behind the full group's,
#
#   decode       (params, k_pages, v_pages, w_pages, pairs, tokens [B],
#                 positions [B], page_table [B, maxp], wtable [B, wrow],
#                 wfirst [B])
#   prefill-<b>  (params, k_pages, v_pages, w_pages, pairs, tokens [1, b],
#                 length [1], page_table [1, maxp], wtable [1, wrow],
#                 wfirst [1])
#   chunk-<b>    (params, k_pages, v_pages, w_pages, pairs, tokens [1, b],
#                 start [1], end [1], page_table [1, maxp],
#                 wtable [1, wrow], wfirst [1])
#   -> (logits, k_pages, v_pages, w_pages, pairs)
#
# A full-attention layer writes and reads its pages of the full group as
# every other family's layers do.  A window layer's row holds its LIVE
# pages only, so it counts positions from ``wfirst``: it writes at
# ``position - wfirst`` and attends ``[max(0, end - window), end)`` less
# ``wfirst``, at most ``window`` keys (decode) or ``chunk + window``
# (a chunk) whatever the context.  One builder makes all three kinds: the
# layers are walked by a Python loop (models/afmoe.py says why), and what
# differs between the kinds is how a layer attends.  No verify-<k> and no
# cow program: nothing of a window group can be shared or rolled back far.


def _build_afmoe_fn(cfg, scfg, mesh, kind: str, bucket=None) -> Callable:
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "the afmoe programs are one chip's share of a layer group: the "
            "exchange that adds the other chips' heads and experts is not "
            "built yet, so there is nothing for a mesh to run")
    a = cfg.afmoe
    rows = afmoe.group_rows(cfg)
    P, Pw = scfg.n_pages, scfg.n_window_pages
    v_off = afmoe.n_window_layers(cfg) * Pw  # a window page's values
    page = scfg.page_size

    def body(params, k_pages, v_pages, w_pages, pairs, tokens, start, end,
             page_table, wtable, wfirst):
        p = afmoe.param_tree(params["params"])
        S = tokens.shape[1]
        positions = start[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
        valid = positions < end[:, None]
        x = afmoe.embed_tokens(cfg, p, tokens)
        shapes = (k_pages.shape, v_pages.shape, w_pages.shape)
        flat = lambda a_: a_.reshape((-1,) + a_.shape[2:])
        pool = {"k": flat(k_pages), "v": flat(v_pages), "w": flat(w_pages)}
        rel_end = jnp.maximum(end - wfirst, 0)
        write_full = _page_writer(page_table, start, end, S, page)
        write_win = _page_writer(wtable, start - wfirst, rel_end, S, page)

        def attend(q, k, v, sliding, j):
            """Write the call's keys and values into the layer's pages,
            then attend as the kind does."""
            if sliding:
                base = j * Pw
                pool["w"] = write_win(write_win(pool["w"], k, base), v,
                                      base + v_off)
                kp = vp = pool["w"]
                table, length, window = wtable + base, rel_end, a.window
                qpos, voff = positions - wfirst[:, None], v_off
            else:
                base = j * P
                pool["k"] = write_full(pool["k"], k, base)
                pool["v"] = write_full(pool["v"], v, base)
                kp, vp = pool["k"], pool["v"]
                table, length, window = page_table + base, end, None
                qpos, voff = positions, 0
            if kind == "prefill":  # a fresh prompt attends only itself
                return afmoe.dense_attention(q, k, v, positions, end, window)
            if kind == "chunk":
                return paged_prefill_attention(
                    q, kp, vp, qpos, length, table, window=window,
                    v_page_offset=voff)
            starts = None if window is None else jnp.maximum(
                length - window, 0)
            return paged_attention(q[:, 0], kp, vp, length, table,
                                   starts=starts, v_page_offset=voff)[:, None]

        counts = []
        for i, lp in enumerate(p["layers"]):
            sliding, j = rows[i]

            def attention(h, lp=lp, sliding=sliding, j=j):
                q, k, v, g = afmoe.qkvg(cfg, lp, h, positions, sliding)
                return afmoe.attn_out(cfg, lp, attend(q, k, v, sliding, j), g)

            x, got = afmoe.block(cfg, lp, i, x, valid, attention)
            if got is not None:
                counts.append(got)
        if kind == "decode":
            logits = afmoe.head_logits(cfg, p, x)[:, 0]
        else:
            last = jnp.clip(end - 1 - start, 0, S - 1)[:, None, None]
            x_last = jnp.take_along_axis(x, jnp.broadcast_to(
                last, (x.shape[0], 1, x.shape[2])), axis=1)
            logits = afmoe.head_logits(cfg, p, x_last)[0, 0]
        return (logits, *(pool[n].reshape(sh)
                          for n, sh in zip("kvw", shapes)),
                pairs + jnp.stack(counts).astype(jnp.int32))

    if kind == "decode":
        def fn(params, k_pages, v_pages, w_pages, pairs, tokens, positions,
               page_table, wtable, wfirst):
            # Idle and mid-prefill lanes (position 0) attend and route
            # nothing: a length of 0, the kernel's idle contract.
            end = jnp.where(positions > 0, positions + 1, 0)
            return body(params, k_pages, v_pages, w_pages, pairs,
                        tokens[:, None], positions, end, page_table, wtable,
                        wfirst)

        return _program_name("tdx_serve_decode")(fn)
    if kind == "prefill":
        def fn(params, k_pages, v_pages, w_pages, pairs, tokens, length,
               page_table, wtable, wfirst):
            return body(params, k_pages, v_pages, w_pages, pairs, tokens,
                        jnp.zeros_like(length), length, page_table, wtable,
                        wfirst)
    else:
        fn = body
    return _program_name(f"tdx_serve_{kind}_{bucket}")(fn)


def build_decode_fn(family: str, cfg: TransformerConfig,
                    scfg: ResolvedServeConfig, mesh=None) -> Callable:
    """The batched decode-step program:
    ``(params, k_pages, v_pages, tokens [B], positions [B],
    page_table [B, maxp]) -> (logits [B, vocab], k_pages, v_pages)``.
    ``positions[b]`` is the index the incoming token occupies; idle
    lanes carry position 0 and a null page table (their writes land in
    the null page, their logits are ignored).  A hybrid stack's programs
    carry the recurrent state too (the section above)."""
    model = make_model(family, cfg)
    if cfg.afmoe is not None:
        return _build_afmoe_fn(cfg, scfg, mesh, "decode")
    if _recurrent(cfg):
        return _build_hybrid_decode_fn(cfg, scfg, mesh)
    decomp = model.decode_decomposition()
    attend = _decode_attention(mesh, cfg.kv_heads)

    @_program_name("tdx_serve_decode")
    def decode_fn(params, k_pages, v_pages, tokens, positions, page_table):
        p = params["params"]
        x = decomp.embed(p, tokens[:, None], positions[:, None])
        angles = decomp.angles_at(positions[:, None])
        # Context including the incoming token; idle lanes (position 0
        # — active lanes always hold at least their non-empty prompt)
        # get length 0, the kernel's documented idle contract, so the
        # null page is written by their scatters but never READ.
        lengths = jnp.where(positions > 0, positions + 1, 0)

        def step(blk, x, kp, vp, base):
            return _decode_block(
                cfg, blk, x, kp, vp, base, angles=angles,
                positions=positions, lengths=lengths, page_table=page_table,
                attend=attend,
            )

        x, k_pages, v_pages = _scan_blocks(
            decomp, p, x, k_pages, v_pages, step
        )
        logits = decomp.head(p, x)[:, 0]  # [B, vocab]
        return logits, k_pages, v_pages

    return decode_fn


def build_prefill_fn(family: str, cfg: TransformerConfig,
                     scfg: ResolvedServeConfig, bucket: int) -> Callable:
    """The single-sequence prefill program for one prompt bucket:
    ``(params, k_pages, v_pages, tokens [1, bucket], length [1],
    page_table [1, maxp]) -> (logits [vocab], k_pages, v_pages)`` —
    logits are the LAST VALID position's (the first generated token)."""
    model = make_model(family, cfg)
    if cfg.afmoe is not None:
        return _build_afmoe_fn(cfg, scfg, None, "prefill", bucket)
    if _recurrent(cfg):
        return _build_hybrid_prefill_fn(cfg, scfg, bucket, chunked=False)
    decomp = model.decode_decomposition()

    @_program_name(f"tdx_serve_prefill_{bucket}")
    def prefill_fn(params, k_pages, v_pages, tokens, length, page_table):
        p = params["params"]
        S = tokens.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)[None]
        x = decomp.embed(p, tokens, positions)
        angles = decomp.angles_at(positions)

        def step(blk, x, kp, vp, base):
            return _prefill_block(
                cfg, blk, x, kp, vp, base, angles=angles,
                positions=positions, length=length, page_table=page_table,
            )

        x, k_pages, v_pages = _scan_blocks(
            decomp, p, x, k_pages, v_pages, step
        )
        last = jnp.clip(length - 1, 0, S - 1)[:, None, None]
        x_last = jnp.take_along_axis(x, jnp.broadcast_to(
            last, (x.shape[0], 1, x.shape[2])), axis=1)
        logits = decomp.head(p, x_last)[0, 0]  # [vocab]
        return logits, k_pages, v_pages

    return prefill_fn


def build_chunk_prefill_fn(family: str, cfg: TransformerConfig,
                           scfg: ResolvedServeConfig, bucket: int) -> Callable:
    """The single-sequence CHUNK prefill program for one chunk bucket:
    ``(params, k_pages, v_pages, tokens [1, bucket], start [1], end [1],
    page_table [1, maxp]) -> (logits [vocab], k_pages, v_pages)``.
    ``tokens`` holds prompt positions ``[start, end)`` left-aligned
    (padded past ``end - start``); attention reads the whole written
    context — cached prefix pages and earlier chunks — through the page
    table, so a suffix behind a shared prefix costs only its own FLOPs.
    Logits are the last valid position's: meaningful (the first
    generated token) only on the final chunk, ignored otherwise."""
    model = make_model(family, cfg)
    if cfg.afmoe is not None:
        return _build_afmoe_fn(cfg, scfg, None, "chunk", bucket)
    if _recurrent(cfg):
        return _build_hybrid_prefill_fn(cfg, scfg, bucket, chunked=True)
    decomp = model.decode_decomposition()

    @_program_name(f"tdx_serve_chunk_{bucket}")
    def chunk_fn(params, k_pages, v_pages, tokens, start, end, page_table):
        p = params["params"]
        S = tokens.shape[1]
        positions = start[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
        x = decomp.embed(p, tokens, positions)
        angles = decomp.angles_at(positions)

        def step(blk, x, kp, vp, base):
            return _chunk_block(
                cfg, blk, x, kp, vp, base, angles=angles,
                positions=positions, end=end, page_table=page_table,
            )

        x, k_pages, v_pages = _scan_blocks(
            decomp, p, x, k_pages, v_pages, step
        )
        last = jnp.clip(end - 1 - start, 0, S - 1)[:, None, None]
        x_last = jnp.take_along_axis(x, jnp.broadcast_to(
            last, (x.shape[0], 1, x.shape[2])), axis=1)
        logits = decomp.head(p, x_last)[0, 0]  # [vocab]
        return logits, k_pages, v_pages

    return chunk_fn


def build_verify_fn(family: str, cfg: TransformerConfig,
                    scfg: ResolvedServeConfig, k: int) -> Callable:
    """The batched speculative-verify program for one draft bucket:
    ``(params, k_pages, v_pages, tokens [B, k+1], start [B], end [B],
    page_table [B, maxp]) -> (logits [B, k+1, vocab], k_pages,
    v_pages)``.  Lane ``b`` feeds its last emitted token plus its draft,
    left-aligned in ``tokens[b]``, occupying absolute positions
    ``[start[b], end[b])`` (``end - start`` = 1 + draft length, ≤ k+1);
    padded positions past ``end`` write nothing and are masked out of
    attention, and idle lanes carry ``start == end == 0`` with a null
    table row.  Row ``i`` of the logits scores the token AFTER position
    ``start + i``, so greedy accept walks the rows left to right: accept
    while the draft token equals the row's argmax, then emit one
    corrected (or bonus) token — exactly the sequential greedy chain,
    which is what keeps speculation bitwise-equal to the oracle.  The
    batched sibling of :func:`build_chunk_prefill_fn`: same
    ``_chunk_block`` scatter-and-ragged-attend per layer, but every lane
    at once and the head applied to every position instead of the last."""
    if cfg.afmoe is not None:
        raise NotImplementedError(
            "no verify-<k> program for the afmoe family yet: a rejected "
            "draft would have to be rolled back out of a window group that "
            "has already returned the pages behind it")
    if _recurrent(cfg):
        raise NotImplementedError(
            "no verify-<k> program for a stack with recurrent layers: the "
            "tick would advance every lane's state over its whole draft, "
            "and a rejected position cannot be rolled back out of a state")
    decomp = make_model(family, cfg).decode_decomposition()

    @_program_name(f"tdx_serve_verify_{k}")
    def verify_fn(params, k_pages, v_pages, tokens, start, end, page_table):
        p = params["params"]
        S = tokens.shape[1]  # k + 1
        positions = start[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
        x = decomp.embed(p, tokens, positions)
        angles = decomp.angles_at(positions)

        def step(blk, x, kp, vp, base):
            return _chunk_block(
                cfg, blk, x, kp, vp, base, angles=angles,
                positions=positions, end=end, page_table=page_table,
            )

        x, k_pages, v_pages = _scan_blocks(
            decomp, p, x, k_pages, v_pages, step
        )
        logits = decomp.head(p, x)  # [B, k+1, vocab]
        return logits, k_pages, v_pages

    return verify_fn


def build_cow_fn() -> Callable:
    """The copy-on-write page duplication program:
    ``(k_pages, v_pages, src [1], dst [1]) -> (k_pages, v_pages)`` —
    clone page ``src`` into ``dst`` across every layer, K and V, so a
    grower about to write into a shared page writes into its private
    copy instead.  Pure pool-to-pool, no params.  Like every program
    that takes the pools it consumes them (:class:`ServeProgramSpec`
    ``consumes``) and returns them in the same buffers: a call writes
    one page of each pool and moves nothing else."""

    @_program_name("tdx_serve_cow")
    def cow_fn(k_pages, v_pages, src, dst):
        k_pages = k_pages.at[:, dst[0]].set(k_pages[:, src[0]])
        v_pages = v_pages.at[:, dst[0]].set(v_pages[:, src[0]])
        return k_pages, v_pages

    return cow_fn


# ---------------------------------------------------------------------------
# program specs, fingerprints, compiles
# ---------------------------------------------------------------------------


@dataclass
class ServeProgramSpec:
    """One compilable serving program: the function, its ABSTRACT
    arguments (lowerable without allocating a single real array — the
    warm tool never touches device memory), the output shardings, and
    the registry fingerprint.

    ``consumes`` are the positions in ``args`` of the arrays the program
    CONSUMES: the two pools and, behind them, a hybrid stack's two state
    arrays or the afmoe family's window pool and pair counts.  They are donated to the compiled program
    (:func:`compile_serving_program`), which returns each in the buffer
    it came in: after a call the arrays passed in are deleted and the
    caller owns the outputs instead (docs/serving.md §Who owns the
    pools).  ``init`` consumes nothing."""

    name: str                      # "init" | "decode" | "prefill-<S>"
    fn: Callable
    args: tuple                    # ShapeDtypeStructs (or () for init)
    out_shardings: Optional[tuple]
    program_fp: str
    init_options: bool             # init compiler effort vs serving default
    consumes: Tuple[int, ...] = ()  # positions in args; donated
    treedef: Any = None            # init only: unflatten spec for params
    # init only: the low-precision transport plan when
    # TDX_MATERIALIZE_INIT_DTYPE is armed — the compiled init program
    # then delivers eligible params in the init dtype and the bring-up
    # upcasts them on device (transport.commit_outputs).
    tplan: Any = None


def _fp(kind: str, family: str, cfg: TransformerConfig,
        scfg: ResolvedServeConfig, extra: tuple = ()) -> str:
    """Registry key material for one serving program: a pure function of
    the model + serve SHAPE (dataclass reprs are deterministic), NOT of
    the process — every host derives the same fingerprint, and
    :func:`..registry.env_key` layers the compile environment on top.

    Only fields the COMPILED program depends on enter its hash: the
    programs never read ``max_new_tokens`` (a host-side budget), and the
    init program does not depend on the serve shape at all — hashing
    either would silently invalidate warmed artifacts on changes that
    leave the compiled bytes identical (the init program is the most
    expensive compile in the set)."""
    shape = () if kind == "init" else (
        scfg.max_batch, scfg.page_size, scfg.n_pages,
        scfg.max_pages_per_seq, scfg.prefill_buckets,
    ) + ((scfg.n_window_pages, scfg.window_pages_per_seq)
         if scfg.n_window_pages else ())
    # v4: the pools and the recurrent state are donated and aliased to
    # the outputs (v3: the pools became the layer scan's carry) — same
    # key material, other compiled bytes, so artifacts published under
    # v3 must not be served.
    h = hashlib.sha1(b"tdx-serve-program-fp-v4")
    h.update(repr((kind, family, cfg, shape, extra)).encode())
    return h.hexdigest()


def _mesh_desc(mesh) -> str:
    if mesh is None:
        return "none"
    return repr(sorted((str(k), int(v)) for k, v in mesh.shape.items()))


def _abstract_params(family, cfg, *, seed, sample_len, param_dtype,
                     mesh, plan, init_dtype=None):
    """(init run_fn, init out_shardings, params treedef, abstract params
    pytree, transport plan) — the deferred-init thunk and the
    ShapeDtypeStruct tree the prefill/decode programs are lowered
    against (cast policy and planned shardings applied, so the lowered
    signature matches the arrays the init program will actually
    deliver).  With ``init_dtype`` the init program stores eligible
    params in the init dtype and the returned
    :class:`..transport.TransportPlan` describes the
    on-device upcast the bring-up must run — the ShapeDtypeStructs keep
    the POST-upcast contract dtypes, which is what the prefill/decode
    programs consume."""
    model = make_model(family, cfg)
    sample = jnp.zeros((1, sample_len), jnp.int32)
    fakes = abstract.deferred_init(
        model.init, jax.random.PRNGKey(seed), sample
    )
    run_fn, out_shardings, treedef = abstract.materialize_parts(
        fakes, mesh=mesh, plan=plan, param_dtype=param_dtype,
        init_dtype=init_dtype,
    )
    leaves = jax.tree.leaves(fakes, is_leaf=abstract.is_fake)
    sds = []
    elig = []
    for i, f in enumerate(leaves):
        dt = f.dtype
        elig.append(abstract._cast_eligible(f, f._thunk))
        if param_dtype is not None and elig[-1]:
            dt = param_dtype
        if out_shardings is not None:
            sds.append(jax.ShapeDtypeStruct(f.shape, dt,
                                            sharding=out_shardings[i]))
        else:
            sds.append(jax.ShapeDtypeStruct(f.shape, dt))
    params_abs = jax.tree.unflatten(treedef, sds)
    tplan = None
    if init_dtype is not None:
        tplan = transport.plan_transport(
            [s.dtype for s in sds], elig, init_dtype, out_shardings
        )
    return run_fn, out_shardings, treedef, params_abs, tplan


def serve_program_specs(
    family: str,
    cfg: TransformerConfig,
    serve_cfg: Optional[ServeConfig] = None,
    *,
    seed: int = 0,
    param_dtype=None,
    mesh=None,
    plan=None,
    sample_len: int = 8,
    include_init: bool = True,
    buckets: Optional[Tuple[int, ...]] = None,
) -> List[ServeProgramSpec]:
    """Every program a replica of this shape compiles, in bring-up order
    (init, prefill buckets, decode).  ``tools/warm_cache.py --decode``
    compiles exactly this list; the engine compiles members of it on
    demand — same builders, same fingerprints, so a warmed registry
    makes bring-up all-hit."""
    scfg = (serve_cfg or ServeConfig()).resolve(cfg)
    init_dtype = transport.resolve_init_dtype(
        tdx_config.get().materialize_init_dtype
    )
    run_fn, out_shardings, treedef, params_abs, tplan = _abstract_params(
        family, cfg, seed=seed, sample_len=sample_len,
        param_dtype=param_dtype, mesh=mesh, plan=plan,
        init_dtype=init_dtype,
    )
    kv = scfg.kv_config(cfg)
    # The pools' placement is part of every program's contract: committed
    # inputs, and the same sharding pinned on the way out, so where the
    # cache lives never depends on what GSPMD happens to propagate.
    pool_sh = pool_sharding(mesh, cfg.kv_heads)
    pool_sds = jax.ShapeDtypeStruct(kv.pool_shape(), cfg.dtype,
                                    sharding=pool_sh)
    i32 = jnp.int32
    B, maxp = scfg.max_batch, scfg.max_pages_per_seq
    # What every program but init takes, CONSUMES and returns: the two
    # pools and, for a hybrid stack, the recurrent layer group's state
    # behind them (such a stack's one-sequence programs also take the
    # lane's slot; it has no cow and no verify programs).
    carried, carried_sh = (pool_sds, pool_sds), (pool_sh, pool_sh)
    slot_sds = lane_sds = ()  # operands behind a one-sequence / lanes table
    if kv.window is not None:
        # The window group and the held experts' pair counts, and behind
        # every page table the group's own row and its first position.
        carried += (
            jax.ShapeDtypeStruct(kv.window_pool_shape(), cfg.dtype,
                                 sharding=pool_sh),
            jax.ShapeDtypeStruct((afmoe.n_expert_layers(cfg),
                                  cfg.afmoe.held_experts), i32))
        carried_sh += (pool_sh, None)
        wrow = kv.window.max_pages_per_seq
        slot_sds = (jax.ShapeDtypeStruct((1, wrow), i32),
                    jax.ShapeDtypeStruct((1,), i32))
        lane_sds = (jax.ShapeDtypeStruct((B, wrow), i32),
                    jax.ShapeDtypeStruct((B,), i32))
    if kv.state is not None:
        st_sh = state_sharding(mesh, kv.state.d_inner)
        carried += (
            jax.ShapeDtypeStruct(kv.state.ssm_shape(), jnp.float32,
                                 sharding=st_sh),
            jax.ShapeDtypeStruct(kv.state.conv_shape(), cfg.dtype,
                                 sharding=st_sh))
        carried_sh += (st_sh, st_sh)
        slot_sds = (jax.ShapeDtypeStruct((1,), i32),)
    # The OUTPUT CONTRACT is part of every fingerprint, exactly as the
    # torch path's _registry_program_fp hashes str(NamedSharding) per
    # slot: two plans with the same class name but different rules must
    # never collide on one registry key — the params' shardings shape
    # the init program's outputs AND the prefill/decode programs'
    # lowered input signatures.
    shard_desc = (
        "none" if out_shardings is None
        else ";".join(str(s) for s in out_shardings)
    )
    extra = (seed, sample_len, str(param_dtype), _mesh_desc(mesh),
             shard_desc)
    # The low-precision transport changes the compiled init program (and
    # under tolerance its values): its fingerprint must never collide
    # with the default path's.  Salted only when a plan is ACTIVE, so
    # default-config fingerprints — and every registry warmed with them
    # — stay byte-stable.
    init_extra = (
        extra + (("init_dtype", str(init_dtype)),)
        if tplan is not None else extra
    )

    specs: List[ServeProgramSpec] = []
    if include_init:
        specs.append(ServeProgramSpec(
            name="init", fn=run_fn, args=(),
            out_shardings=out_shardings,
            program_fp=_fp("init", family, cfg, scfg, init_extra),
            init_options=True, treedef=treedef, tplan=tplan,
        ))

    def program(name, fn, *operands, model=True):
        """A model program: ``fn`` over ``(params,) + carried + operands``
        → ``(logits,) + carried``; ``model`` False (cow): the pools
        alone, in and out."""
        head, logits_sh = ((params_abs,), (None,)) if model else ((), ())
        return ServeProgramSpec(
            name=name, fn=fn, args=(*head, *carried, *operands),
            out_shardings=None if mesh is None else logits_sh + carried_sh,
            program_fp=_fp(name, family, cfg, scfg, extra),
            init_options=False,
            consumes=tuple(range(len(head), len(head) + len(carried))),
        )

    one = jax.ShapeDtypeStruct((1,), i32)
    lanes = jax.ShapeDtypeStruct((B,), i32)
    for b in (buckets if buckets is not None else scfg.prefill_buckets):
        specs.append(program(
            f"prefill-{b}", build_prefill_fn(family, cfg, scfg, b),
            jax.ShapeDtypeStruct((1, b), i32), one,
            jax.ShapeDtypeStruct((1, maxp), i32), *slot_sds))
    for b in (buckets if buckets is not None else scfg.prefill_buckets):
        specs.append(program(
            f"chunk-{b}", build_chunk_prefill_fn(family, cfg, scfg, b),
            jax.ShapeDtypeStruct((1, b), i32), one, one,
            jax.ShapeDtypeStruct((1, maxp), i32), *slot_sds))
    pages_only = kv.state is None and kv.window is None
    if pages_only:
        specs.append(program("cow", build_cow_fn(), one, one, model=False))
    specs.append(program(
        "decode", build_decode_fn(family, cfg, scfg, mesh),
        lanes, lanes, jax.ShapeDtypeStruct((B, maxp), i32), *lane_sds))
    # The verify-<k> family is part of every replica shape's program set
    # REGARDLESS of the spec_decode host knob: warm once, then flip
    # speculation on or off without invalidating a byte of the registry
    # (the fingerprint-host-knob invariance test pins this).
    for k in (scfg.spec_buckets if pages_only else ()):
        specs.append(program(
            f"verify-{k}", build_verify_fn(family, cfg, scfg, k),
            jax.ShapeDtypeStruct((B, k + 1), i32), lanes, lanes,
            jax.ShapeDtypeStruct((B, maxp), i32)))
    return specs


def compile_serving_program(spec: ServeProgramSpec):
    """Compile one serving program through the compile service
    (:func:`..compile_service.compile_program`) — persistent cache,
    artifact registry fetch→verify→install / publish, exact
    cache-outcome counters, chaos sites, and the
    ``TDX_COMPILE_DEADLINE_S`` watchdog all included.  The arguments
    the spec ``consumes`` are donated; one that the lowering cannot
    alias to an output (jax's "Some donated buffers were not usable"
    warning) is an error here: the pools and the state enter and leave
    with one shape, dtype and sharding, and a program that copies them
    after all must not come up quietly.
    Returns ``(compiled, cache_outcome)``."""
    compile_service.bind_cache()
    cfg = tdx_config.get()
    with observe.span(
        "serve.compile", category="serve", program=spec.name
    ) as sp, warnings.catch_warnings():
        warnings.filterwarnings(
            "error", message="Some donated buffers were not usable")
        compiled, t_lower, t_compile, outcome, costs = compile_service.compile_program(
            spec.fn, tuple(spec.args), spec.out_shardings,
            fault_plan=chaos.active_plan(),
            deadline=cfg.compile_deadline_s or None,
            program_fp=spec.program_fp,
            jit_kwargs={"donate_argnums": spec.consumes},
            init_compiler_options=spec.init_options,
        )
        sp.set(cache=outcome, lower_s=round(t_lower, 4),
               compile_s=round(t_compile, 4),
               **({f"xla_{k}": v for k, v in costs.items()} if costs else {}))
    return compiled, outcome


# ---------------------------------------------------------------------------
# decode-program warming (tools/warm_cache.py --decode)
# ---------------------------------------------------------------------------


def warm_serving(
    family: str,
    cfg: TransformerConfig,
    cache_dir: str,
    *,
    registry_dir: Optional[str] = None,
    serve_cfg: Optional[ServeConfig] = None,
    seed: int = 0,
    param_dtype=None,
    mesh=None,
    plan=None,
    sample_len: int = 8,
) -> dict:
    """Warm a replica shape's WHOLE program set — init, every prefill
    bucket, decode — into ``cache_dir`` (and publish to ``registry_dir``
    when set), so a later :func:`..serve.engine.spin_up_replica` of the
    same shape performs zero local compiles.  Returns the same summary
    shape as :func:`..registry.warm_sharded` (per-program outcome
    reports; ``unwarmed`` non-empty on any failure)."""
    from ..registry.scheduler import ProgramReport

    t0 = time.perf_counter()
    log = get_logger()
    reports: List[ProgramReport] = []
    with tdx_config.override(
        cache_dir=cache_dir, registry_dir=registry_dir or None
    ):
        compile_service.reset_cache_binding()
        compile_service.bind_cache()
        try:
            specs = serve_program_specs(
                family, cfg, serve_cfg, seed=seed, param_dtype=param_dtype,
                mesh=mesh, plan=plan, sample_len=sample_len,
            )
            for spec in specs:
                t = time.perf_counter()
                fetches_before = observe.counter(
                    "tdx.registry.fetch_hit").value
                try:
                    _, outcome = compile_serving_program(spec)
                except Exception as e:  # noqa: BLE001 — report, keep warming
                    log.error("warm-serving: program %s failed (%s: %s)",
                              spec.name, type(e).__name__, str(e)[:160])
                    reports.append(ProgramReport(
                        program=spec.name, outputs=1, outcome="unwarmed",
                        seconds=time.perf_counter() - t,
                        error=f"{type(e).__name__}: {str(e)[:200]}",
                    ))
                    continue
                from ..registry import ArtifactRegistry, registry_key
                from ..registry.scheduler import classify_warm_outcome

                label = classify_warm_outcome(
                    outcome,
                    fetched=(observe.counter("tdx.registry.fetch_hit").value
                             > fetches_before),
                    published=bool(
                        registry_dir
                        and ArtifactRegistry(registry_dir).has(
                            registry_key(spec.program_fp))
                    ),
                )
                reports.append(ProgramReport(
                    program=spec.name, outputs=1, outcome=label,
                    seconds=time.perf_counter() - t, cache=outcome,
                ))
        finally:
            compile_service.reset_cache_binding()

    outcomes: Dict[str, int] = {}
    for r in reports:
        outcomes[r.outcome] = outcomes.get(r.outcome, 0) + 1
    import os

    try:
        cache_entries = len(os.listdir(cache_dir))
    except OSError:
        cache_entries = 0
    return {
        "programs": sum(1 for r in reports if r.outcome != "unwarmed"),
        "outputs": sum(r.outputs for r in reports
                       if r.outcome != "unwarmed"),
        "cache_entries": cache_entries,
        "seconds": round(time.perf_counter() - t0, 2),
        "backend": jax.default_backend(),
        "cache_dir": cache_dir,
        "registry_dir": registry_dir,
        "hosts": 1,
        "host_id": 0,
        "decode": True,
        "outcomes": outcomes,
        "program_reports": [r.as_dict() for r in reports],
        "unwarmed": [r.program for r in reports if r.outcome == "unwarmed"],
    }
