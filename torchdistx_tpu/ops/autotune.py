"""Flash-attention block-size autotuner.

The kernels' perf on a given chip hinges on (block_q, block_k): round 2's
hand search found 1024x1024 ~2x faster than the 512x512 first guess on a
v5e at S=2048 (README bench table).  This module turns that search into a
cached utility: measure each candidate on the live device with the same
data-dependent chain scheme the bench uses (dispatch latency cancels),
pick the fastest, and remember the answer per (device kind, shape,
dtype, causality) in a small JSON cache so repeated runs pay nothing.

Usage::

    from torchdistx_tpu.ops import make_flash_attention, tune_flash_blocks
    bq, bk = tune_flash_blocks(batch=4, seq_len=2048, heads=16, head_dim=64)
    attn = make_flash_attention(block_q=bq, block_k=bk)

Off-TPU the kernels run in interpreter mode where block sizes carry no
hardware meaning; the tuner still works (useful for tests) but its
numbers only matter on a real chip.
"""

from __future__ import annotations

import json
import os
import time
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ._interpret import resolve_interpret

# Candidates honor Mosaic's tiling rules for every operand this kernel
# family streams (minor dims 128-divisible; see flash_attention.py).
DEFAULT_CANDIDATES: Tuple[Tuple[int, int], ...] = (
    (512, 512), (512, 1024), (1024, 512), (1024, 1024), (2048, 1024),
    # Round-4 ISOLATED-kernel sweep winners (v5e, S=2048): whole-
    # sequence blocks won the standalone forward 2.3x and short-q/
    # full-k the standalone backward 2.6x — but neither transferred to
    # the bench's chained-step context (docs/benchmarks.md §Block
    # sizes), which is why they are candidates here, not defaults:
    # _measure now times the bench's exact chain, so a chip where they
    # genuinely win will still pick them.  A candidate that fails
    # compilation for vmem is skipped (BlockConfigError); if every
    # candidate fails, tuning raises rather than guessing.
    (2048, 2048), (512, 2048), (1024, 2048),
)


def _cache_path() -> str:
    from .. import config

    base = config.get().cache_dir or os.path.join(
        os.path.expanduser("~"), ".cache", "torchdistx_tpu"
    )
    return os.path.join(base, "flash_blocks.json")


def _cache_key(device_kind: str, shape, dtype, causal: bool,
               interpret: bool) -> str:
    # interpret is part of the key: interpreter-mode "winners" are
    # hardware-meaningless and must never be served to a real-chip call.
    return (
        f"{device_kind}|{'x'.join(map(str, shape))}|"
        f"{jnp.dtype(dtype).name}|causal={causal}|interpret={interpret}"
    )


def _read_cache(key: str):
    try:
        with open(_cache_path()) as f:
            entry = json.load(f).get(key)
        return tuple(entry) if entry else None
    except (OSError, ValueError):
        return None


def _write_cache(key: str, blocks: Tuple[int, int]) -> None:
    path = _cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = {}
        data[key] = list(blocks)
        # Atomic replace: concurrent tuners (multi-host pod startup) can
        # still lose each other's read-modify-write, but no reader ever
        # sees a torn file — at worst a key re-measures next launch.
        tmp = f"{path}.tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(data, f)
        os.replace(tmp, path)
    except OSError:
        pass  # tuning still returns the measured answer


def _is_vmem_error(e: BaseException) -> bool:
    """Does this exception look like a Mosaic scoped-vmem overrun — a
    BLOCK-SIZE-dependent failure a tuner/bench may step down from — as
    opposed to a broken program or an HBM OOM (which no block size
    fixes)?  Matched on message text because the failure arrives as a
    generic XlaRuntimeError; the v5e wording is 'Scoped allocation with
    size ... exceeded scoped vmem limit' (status RESOURCE_EXHAUSTED —
    deliberately NOT matched bare: HBM OOM carries the same status and
    must propagate).  Any other compile crash is a bug and propagates.
    Single source of truth for both the autotuner and bench.py's block
    ladder."""
    return _vmem_trigger(e) is not None


def _vmem_trigger(e: BaseException) -> "Optional[str]":
    """The substring that classified ``e`` as a vmem-shaped failure, or
    None.  Exposed separately so demotion sites can RECORD which wording
    did the classifying."""
    s = str(e)
    for m in ("vmem", "VMEM", "Scoped allocation"):
        if m in s:
            return m
    return None


class BlockConfigError(RuntimeError):
    """A single block config failed to compile for a memory-shaped
    reason (scoped vmem).  The tuner treats
    it as +inf so survivors compete; if EVERY candidate raises it, the
    failure is systemic and :func:`tune_flash_blocks` re-raises."""


def _measure(fn, q, k, v, *, extra=(), n_lo=2, n_hi=10, repeats=2) -> float:
    """Per-iteration seconds via THE BENCH'S chain scheme (bench.py
    `_flash_phase`): N data-dependent steps inside one jit, difference
    two N values.  ``fn(*carry) -> carry`` threads the full
    ``(q, k, v, *extra)`` tuple — a bwd workload feeds ALL THREE
    cotangents back exactly like a training step (a dq-only chain
    flattered (512, 2048) by 2.6x in the round-4 sweep, which inverted
    to 0.8x in the real phase), and a bias operand rides the carry
    rather than a closure (jit embeds captured arrays as program
    constants, and a [H, S, S] f32 constant bloats the program).

    The lo/hi pair is repeated and the smallest positive delta wins —
    one host-side hiccup (a GC pause) must not pin a
    wrong block size into the persistent cache.  All-nonpositive deltas
    are pure noise: report +inf so the candidate cannot win on junk."""

    @jax.jit
    def g(carry, n):
        out = lax.fori_loop(0, n, lambda i, c: tuple(fn(*c)), carry)
        return sum(x.sum() for x in out[:3])

    carry = (q, k, v, *extra)
    lo = jnp.asarray(n_lo, jnp.int32)
    hi = jnp.asarray(n_hi, jnp.int32)
    try:
        float(g(carry, lo))  # compile + warm
        float(g(carry, hi))
    except Exception as e:
        # A candidate whose tiles overrun the chip's scoped vmem fails
        # Mosaic compilation (v5e: [1024,1024] + f32 bias tile).  It
        # simply cannot win; let the survivors compete.  Anything NOT
        # memory-shaped (a genuinely broken program)
        # propagates — otherwise tuning would "succeed" with the
        # smallest tile and the caller would never learn the kernel
        # cannot run at all.
        if _is_vmem_error(e):
            raise BlockConfigError(str(e)) from e
        raise
    deltas = []
    try:
        for _ in range(repeats):
            t0 = time.perf_counter()
            float(g(carry, lo))
            t_lo = time.perf_counter() - t0
            t0 = time.perf_counter()
            float(g(carry, hi))
            t_hi = time.perf_counter() - t0
            deltas.append((t_hi - t_lo) / (n_hi - n_lo))
    except Exception as e:
        # An allocation can trip only under the hi trip count or after
        # cache effects — a vmem overrun HERE is still a per-config
        # failure and must reach tune_flash_blocks as BlockConfigError,
        # not abort the whole tuning run.
        if _is_vmem_error(e):
            raise BlockConfigError(str(e)) from e
        raise
    pos = [d for d in deltas if d > 0]
    return min(pos) if pos else float("inf")


def tune_flash_blocks(
    *,
    batch: int = 4,
    seq_len: int = 2048,
    heads: int = 16,
    head_dim: int = 64,
    kv_heads: Optional[int] = None,
    causal: bool = True,
    dtype=jnp.bfloat16,
    candidates: Sequence[Tuple[int, int]] = DEFAULT_CANDIDATES,
    use_cache: bool = True,
    interpret: Optional[bool] = None,
    workload: str = "fwd",
) -> Tuple[int, int]:
    """Measure ``candidates`` on the live device and return the fastest
    ``(block_q, block_k)``, cached per (device kind, shape, dtype,
    causality, interpret, workload).

    ``workload`` selects WHAT each candidate times — the winner for one
    workload need not win another, so it is part of the cache key:

    * ``"fwd"``  — the forward kernel;
    * ``"bwd"``  — forward + gradients wrt (q, k, v): the dq and dkv
      backward kernels dominate a training step;
    * ``"bias"`` — forward with an additive [H, S, S] f32 bias operand
      (the T5 relative-position stream).

    Oversized candidates are clamped to the (8-rounded) sequence length,
    mirroring :func:`flash_attention`'s own clamping, then deduplicated —
    every ``seq_len`` is tunable with the default candidate list.  A
    cached winner is only served when it belongs to the requested
    candidate set (after clamping); otherwise the requested set is
    re-measured."""
    from .flash_attention import _round8, flash_attention

    if workload not in ("fwd", "bwd", "bias"):
        raise ValueError(f"unknown workload {workload!r}")
    kv = kv_heads or heads
    shape = (batch, seq_len, heads, kv, head_dim)
    device_kind = jax.devices()[0].device_kind
    interpret = resolve_interpret(interpret)
    key = _cache_key(device_kind, shape, dtype, causal, interpret)
    if workload != "fwd":  # legacy keys stay valid for the fwd workload
        key += f"|workload={workload}"

    cap = _round8(seq_len)
    clamped = tuple(dict.fromkeys(
        (min(bq, cap), min(bk, cap)) for bq, bk in candidates
    ))
    if use_cache:
        cached = _read_cache(key)
        if cached is not None and (not clamped or cached in clamped):
            return cached
    if not clamped:
        raise ValueError("no candidate fits: the candidate list is empty")

    q = jax.random.normal(jax.random.PRNGKey(0), (batch, seq_len, heads, head_dim), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (batch, seq_len, kv, head_dim), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (batch, seq_len, kv, head_dim), dtype)
    bias = (
        jax.random.normal(jax.random.PRNGKey(3), (heads, seq_len, seq_len),
                          jnp.float32)
        if workload == "bias" else None
    )

    best, best_t = None, float("inf")
    compiled = []  # configs that did not crash the compiler
    cfg_failures, last_cfg_err = 0, None
    for bq, bk in clamped:

        def fn(q, k, v, *rest, bq=bq, bk=bk):
            # Mirrors the bench phase's step exactly (see _measure's
            # docstring for why fidelity matters here).
            if workload == "bwd":
                dq, dk, dv = jax.grad(
                    lambda qq, kk, vv: flash_attention(
                        qq, kk, vv, causal=causal, block_q=bq, block_k=bk,
                        interpret=interpret,
                    ).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2),
                )(q, k, v)
                return (
                    (q + 1e-6 * dq).astype(q.dtype),
                    (k + 1e-6 * dk).astype(k.dtype),
                    (v + 1e-6 * dv).astype(v.dtype),
                )
            out = flash_attention(
                q, k, v, causal=causal, bias=(rest[0] if rest else None),
                block_q=bq, block_k=bk, interpret=interpret,
            )
            return (out.astype(q.dtype), k, v, *rest)

        try:
            t = _measure(fn, q, k, v,
                         extra=(() if bias is None else (bias,)))
        except BlockConfigError as e:
            cfg_failures += 1
            last_cfg_err = e
            continue
        compiled.append((bq, bk))
        if t < best_t:
            best, best_t = (bq, bk), t
    if cfg_failures == len(clamped):
        # EVERY config overran vmem: that is systemic (the shape does
        # not fit this chip at any candidate), not a tuning outcome — raise
        # so the caller learns the kernel cannot run at all.
        raise last_cfg_err
    if best is None:
        # Every candidate that COMPILED measured as pure noise (host
        # hiccups): return the smallest-tile pick among those — never a
        # config just observed to crash — but do NOT cache it; a
        # transient hiccup must not permanently pin an unmeasured block
        # size for this (device, shape, dtype) key; the next launch
        # re-measures.
        return min(compiled, key=lambda c: c[0] * c[1])
    if use_cache:
        _write_cache(key, best)
    return best
