#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process, no children, no network: drives the main path once through
the entry points a user calls, at the full width of models the repo
supports, with random weights made from a seed.

* ``serve`` — ``serve.spin_up_replica`` on ``LLAMA3_8B`` with depth cut to
  4 layers (every width as published; about 1.9 B parameters in bf16):
  deferred-init as fakes -> init program through the compile service ->
  parameters materialized in HBM -> the replica's program set compiled ->
  requests answered through the paged cache, chosen so that every
  compiled program family executes, and checked against
  ``serve.oracle_generate`` on the same parameters.
* ``train`` — the complete ``GPT2_125M``: ``abstract.deferred_init`` ->
  ``abstract.materialize`` -> ``parallel.train.make_train_step`` with the
  flash-attention kernel, sequence 1024, three AdamW steps on one
  repeated batch; losses finite and falling.

With four or more devices both stages run again on a mesh of the first
four (``fsdp=2 x tp=2`` for serve, ``fsdp=4`` for train) and must agree
with the one-chip results, each device holding about a quarter of the
parameter bytes.

Exits non-zero, printing no result, when JAX's default backend is not a
TPU or the package is not beside this file.  Otherwise the last line of
standard output is one JSON object with exactly these keys,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
(the device as JAX reports it; ``"ok": false`` and a non-zero exit when a
phase failed); what the phases measured is on the ``summary:`` line above
it.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time
import traceback

SEED = 0

# Engine-vs-oracle logit tolerance.  Both sides compute in bfloat16 (the
# configuration's stated dtype) with f32 accumulation, but not in the
# same order: the engine runs padded fixed-shape batches through the
# paged cache, the oracle one unpadded sequence through dense attention,
# so every layer's output rounds to bf16 (8 significand bits, half-ulp
# 2^-9 relative) along a different path, and the logits themselves are
# emitted in bf16 — one ulp is 2^-5 = 0.031 at |logit| in [4, 8), and
# the seeded logits peak near 5 at this width.  0.125 is four output ulps
# there: wide enough for rounding-order noise through four layers (the
# chip measured 0.055), an order of magnitude below what computing in a
# narrower format would produce (8-bit floats: ulp 2^-1 at that size).
# bf16 logits over a 128 k vocabulary tie EXACTLY at the top now and
# then, so greedy tokens are only required to match where the oracle's
# top-two margin exceeds this tolerance.
LOGIT_ATOL = 0.125

# One-chip-vs-mesh loss tolerance: same bf16 activations, other
# reduction order (batch split four ways, gradients all-reduced).
LOSS_ATOL = 0.05


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_param_shares(params) -> dict:
    """device -> share of the parameter bytes it actually holds, read
    off ``addressable_shards`` (not the sharding's name)."""
    import jax

    held: dict = {}
    total = 0
    for leaf in jax.tree.leaves(params):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            held[shard.device] = held.get(shard.device, 0) + shard.data.nbytes
    return {str(d): b / total for d, b in held.items()}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def serve_requests(vocab: int, page: int):
    """Three waves of requests; between them every program family runs.

    wave 0: ``short`` (prefill, smallest bucket), ``mid`` (prefill,
    largest bucket), ``long`` (longer than the largest bucket: chunked),
    ``pre`` (exactly one page, which the prefix cache keeps);
    wave 1: ``pre-again`` (fully cached, page-aligned: recomputing its
    last position copies the shared page first — ``cow`` — and the
    drafter has seen ``pre``'s whole stream, so its decode is a
    ``verify`` tick), ``pre-ext`` (shared first page, fresh suffix),
    ``short-again`` (a second stream the drafter knows);
    wave 2: ``pre-short`` (budget 4: its draft fits the small verify
    bucket)."""
    import numpy as np

    from torchdistx_tpu.serve import Request

    rng = np.random.RandomState(SEED)

    def toks(n):
        return [int(t) for t in rng.randint(0, vocab, size=n)]

    short, mid, long_, pre = toks(9), toks(page + 8), toks(2 * page + 8), toks(page)
    ext = pre + toks(8)
    return [
        [Request("short", short, max_new_tokens=6),
         Request("mid", mid, max_new_tokens=6),
         Request("long", long_, max_new_tokens=6),
         Request("pre", pre, max_new_tokens=6)],
        [Request("pre-again", pre, max_new_tokens=6),
         Request("pre-ext", ext, max_new_tokens=6),
         Request("short-again", short, max_new_tokens=6)],
        [Request("pre-short", pre, max_new_tokens=4)],
    ]


def serve_stage(cfg, serve_cfg, *, mesh=None, plan=None):
    """Bring a replica up and answer the request waves.  Returns
    ``(engine, requests_by_rid)``; the caller checks the results."""
    import jax.numpy as jnp

    from torchdistx_tpu.observe.costmodel import program_costs
    from torchdistx_tpu.serve import spin_up_replica

    eng = spin_up_replica(
        cfg, family="llama", serve_cfg=serve_cfg, mesh=mesh, plan=plan,
        seed=SEED, param_dtype=jnp.bfloat16,
    )
    log(f"  bring_up_seconds={eng.bring_up_seconds:.1f} "
        f"bring_up_outcomes={json.dumps(eng.bring_up_outcomes)}")
    # Every program consumes the pools and returns them in place: what
    # the compiler aliased, beside one device's share of both pools.
    pools = (eng.k_pages, eng.v_pages, *eng.state)
    held = sum(a.addressable_shards[0].data.nbytes for a in pools)
    alias = {name: int(program_costs(prog)["alias_bytes"])
             for name, prog in sorted(eng._programs.items())}
    log(f"  pool_bytes_a_device={held} xla_alias_bytes={json.dumps(alias)}")
    short = sorted(n for n, b in alias.items() if b < held)
    check(not short, f"programs that do not alias their pools: {short}")
    reqs = {}
    for wave in serve_requests(cfg.vocab_size, serve_cfg.page_size):
        eng.run(wave)
        reqs.update({r.rid: r for r in wave})
    log(f"  program_calls={json.dumps(eng.program_calls, sort_keys=True)}")
    log(f"  spec: verify_ticks={eng.spec_verify_ticks} "
        f"drafted={eng.spec_drafted} accepted={eng.spec_accepted}")
    families = {}
    for name in eng.bring_up_outcomes:
        if name != "init":
            fam = name.split("-")[0]
            families[fam] = families.get(fam, 0) + eng.program_calls.get(name, 0)
    log(f"  family_calls={json.dumps(families, sort_keys=True)}")
    idle = sorted(f for f, n in families.items() if n == 0)
    check(not idle, f"program families never executed: {idle}")
    check(set(eng.results) == set(reqs),
          f"unanswered requests: {sorted(set(reqs) - set(eng.results))}")
    return eng, reqs


def check_against_oracle(eng, reqs, memo: dict) -> int:
    """The repo's own gate, ``serve.oracle_generate``, on the engine's
    parameters.  Per request: greedy tokens must equal the oracle's
    wherever the oracle's top-two margin exceeds ``LOGIT_ATOL`` (a
    divergence is accepted only as a near-tie), and the final step's
    logits must agree within ``LOGIT_ATOL`` given the same history.
    ``memo`` carries the oracle's answers from one replica to the next:
    deferred init yields the same parameter values on every mesh, so
    the mesh replica is held to the one-chip oracle.  Returns the plain
    count of token-equal requests."""
    import numpy as np

    from torchdistx_tpu.serve import oracle_generate

    def oracle(prompt, n):
        return oracle_generate("llama", eng.cfg, eng.params, prompt, n)

    equal = 0
    worst = 0.0
    for rid, req in sorted(reqs.items()):
        key = (tuple(req.tokens), req.max_new_tokens)
        if key not in memo:
            memo[key] = oracle(req.tokens, req.max_new_tokens)
        want, want_logits = memo[key]
        got = eng.results[rid]
        check(len(got) == len(want),
              f"{rid}: {len(got)} tokens, oracle {len(want)}")
        if got == want:
            equal += 1
        else:
            i = next(j for j, (a, b) in enumerate(zip(got, want)) if a != b)
            _, at = oracle(list(req.tokens) + want[:i], 1)
            top2 = np.sort(at)[-2:]
            margin = float(top2[1] - top2[0])
            log(f"  {rid}: diverges at step {i} (engine {got[i]}, oracle "
                f"{want[i]}), oracle top-two margin {margin:.4f}")
            check(margin <= LOGIT_ATOL,
                  f"{rid}: engine token differs at step {i} where the "
                  f"oracle's top-two margin {margin:.4f} > {LOGIT_ATOL}")
            # Teacher-force the oracle along the ENGINE's history so the
            # final logits are comparable again.
            _, want_logits = oracle(list(req.tokens) + got[:-1], 1)
        logits = eng.final_logits[rid]
        check(logits.shape == (eng.cfg.vocab_size,)
              and bool(np.isfinite(logits).all()),
              f"{rid}: final logits shape {logits.shape} / non-finite")
        diff = float(np.max(np.abs(logits - want_logits)))
        worst = max(worst, diff)
        check(diff <= LOGIT_ATOL,
              f"{rid}: final logits differ from the oracle by {diff:.4f} "
              f"> {LOGIT_ATOL}")
    log(f"  oracle: token_equal_requests={equal}/{len(reqs)} "
        f"max_final_logit_diff={worst:.4f} (atol {LOGIT_ATOL}, max |logit| "
        f"{max(float(np.abs(l).max()) for l in eng.final_logits.values()):.2f})")
    return equal


def compare_with_one_chip(eng, one_chip) -> None:
    """Mesh replica vs the one-chip replica, request by request.  The
    count of token-equal requests is a datum (a near-tie may flip under
    another reduction order, and the oracle gate has already judged
    each flip); where the histories are equal the final logits must
    agree."""
    import numpy as np

    tokens, logits = one_chip
    same = [rid for rid in tokens if eng.results[rid] == tokens[rid]]
    worst = max((float(np.max(np.abs(eng.final_logits[rid] - logits[rid])))
                 for rid in same), default=0.0)
    log(f"  vs one chip: token_equal_requests={len(same)}/{len(tokens)} "
        f"max_final_logit_diff={worst:.4f}")
    check(worst <= LOGIT_ATOL,
          f"mesh final logits differ from one chip by {worst:.4f}")


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def train_stage(cfg, *, batch: int, seq: int, mesh, plan, steps: int = 3):
    """deferred_init -> materialize -> three AdamW steps on one repeated
    batch through the flash-attention kernel.  Returns ``(per-device
    parameter shares, losses)``."""
    import jax

    from torchdistx_tpu import abstract
    from torchdistx_tpu.models import make_gpt2
    from torchdistx_tpu.ops import make_flash_attention
    from torchdistx_tpu.parallel.train import make_train_step

    model = make_gpt2(cfg, attn_fn=make_flash_attention(mesh=mesh))
    tokens = jax.random.randint(
        jax.random.PRNGKey(SEED + 1), (batch, seq), 0, cfg.vocab_size)
    t0 = time.perf_counter()
    fakes = abstract.deferred_init(model.init, jax.random.PRNGKey(SEED), tokens)
    params = abstract.materialize(fakes, mesh=mesh, plan=plan)
    jax.block_until_ready(params)
    log(f"  materialize_seconds={time.perf_counter() - t0:.1f}")
    shares = device_param_shares(params)
    init_state, step, shard_batch = make_train_step(model, cfg, mesh)
    state = init_state(params)
    losses = []
    for i in range(steps):
        t0 = time.perf_counter()
        state, metrics = step(state, shard_batch(tokens))
        losses.append(float(metrics["loss"]))
        log(f"  step {i}: loss={losses[-1]:.4f} "
            f"wall_s={time.perf_counter() - t0:.2f}"
            f"{' (compiles)' if i == 0 else ''}")
    check(all(math.isfinite(l) for l in losses),
          f"non-finite loss: {losses}")
    check(all(b < a for a, b in zip(losses, losses[1:])),
          f"losses not falling on a repeated batch: {losses}")
    return shares, losses


def check_quarter_shares(shares: dict, what: str) -> None:
    log(f"  {what} parameter bytes per device: "
        + ", ".join(f"{d}={s:.3f}" for d, s in sorted(shares.items())))
    check(len(shares) == 4 and all(0.20 <= s <= 0.30 for s in shares.values()),
          f"{what}: a device holds other than 20-30% of the parameter "
          f"bytes: {shares}")


# ---------------------------------------------------------------------------


def main() -> int:
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX default backend is {backend!r}, not a TPU; "
              f"nothing is run or reported.", file=sys.stderr)
        return 2
    try:
        import torchdistx_tpu  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the torchdistx_tpu package is not importable "
              f"from {os.getcwd()} ({e}); nothing is run or reported.",
              file=sys.stderr)
        return 2

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device: {json.dumps(device)}")
    try:
        run(devs, device)
        ok = True
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        ok = False
    except Exception:
        traceback.print_exc()
        ok = False
    # The contract's last line: these keys and no others.
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


def run(devs, device: dict) -> None:
    """Every phase; raises on the first check that does not hold."""
    import importlib.metadata as md

    import jax
    import jaxlib

    from torchdistx_tpu import _native, config as tdx_config
    from torchdistx_tpu.models import GPT2_125M, LLAMA3_8B, decoder_lm_plan
    from torchdistx_tpu.observe.step import peak_tflops_for
    from torchdistx_tpu.ops._interpret import interpreted_calls
    from torchdistx_tpu.parallel import make_mesh
    from torchdistx_tpu.serve import ServeConfig

    log(f"versions: jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={md.version('libtpu')}")
    peak = peak_tflops_for(device["kind"])
    check(peak is not None,
          f"no peak TFLOP/s known for device kind {device['kind']!r} "
          f"(observe/step.py PEAK_TFLOPS)")
    log(f"peak_bf16_tflops={peak}")
    log(f"native graph engine (torchdistx_tpu/_lib/libtdxgraph.so) loaded: "
        f"{_native.available()}")
    cache_dir = tdx_config.compile_cache_dir()
    log(f"compile cache: {cache_dir} "
        f"(JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')!r})")

    t_start = time.perf_counter()
    serve_model = LLAMA3_8B.replace(n_layers=4)
    serve_cfg = ServeConfig(max_batch=4, page_size=16, n_pages=64,
                            max_pages_per_seq=8, prefill_buckets=(16, 32))
    one = make_mesh({"dp": 1}, devices=devs[:1])
    replicated = decoder_lm_plan(fsdp=None, tp=None, ep=None)

    log("[serve] LLAMA3_8B, n_layers=4, bf16 params, one chip")
    eng, reqs = serve_stage(serve_model, serve_cfg)
    n_params = sum(x.size for x in jax.tree.leaves(eng.params))
    log(f"  parameters={n_params / 1e9:.2f}B "
        f"k_pages: shape={eng.k_pages.shape} dtype={eng.k_pages.dtype}")
    oracle_memo: dict = {}
    token_equal = check_against_oracle(eng, reqs, oracle_memo)
    bring_up = {"seconds": round(eng.bring_up_seconds, 1),
                "outcomes": eng.bring_up_outcomes}
    one_chip_serve = (dict(eng.results), dict(eng.final_logits))
    del eng

    log("[train] GPT2_125M, 12 layers, seq 1024, flash attention, one chip")
    _, losses = train_stage(GPT2_125M, batch=4, seq=1024, mesh=one,
                            plan=replicated)

    if len(devs) >= 4:
        four = devs[:4]
        log("[serve/mesh] fsdp=2 x tp=2")
        mesh = make_mesh({"fsdp": 2, "tp": 2}, devices=four)
        eng, reqs = serve_stage(serve_model, serve_cfg, mesh=mesh,
                                plan=decoder_lm_plan(ep=None))
        log(f"  k_pages sharding: {eng.k_pages.sharding} "
            f"(per-device shard {eng.k_pages.addressable_shards[0].data.shape})")
        check_quarter_shares(device_param_shares(eng.params), "serve")
        check_against_oracle(eng, reqs, oracle_memo)
        compare_with_one_chip(eng, one_chip_serve)
        del eng

        log("[train/mesh] fsdp=4")
        mesh = make_mesh({"fsdp": 4}, devices=four)
        shares, mesh_losses = train_stage(
            GPT2_125M, batch=4, seq=1024, mesh=mesh,
            plan=decoder_lm_plan(tp=None, ep=None))
        check_quarter_shares(shares, "train")
        gap = max(abs(a - b) for a, b in zip(losses, mesh_losses))
        log(f"  vs one chip: max loss gap {gap:.4f} (atol {LOSS_ATOL})")
        check(gap <= LOSS_ATOL,
              f"mesh losses {mesh_losses} differ from one-chip {losses}")

    n_interp = interpreted_calls()
    log(f"tdx.ops.interpreted_calls={n_interp}")
    check(n_interp == 0,
          f"{n_interp} kernel constructions fell back to interpret mode")
    if cache_dir:
        n_files = sum(len(fs) for _, _, fs in os.walk(cache_dir))
        log(f"compile cache entries under {cache_dir}: {n_files}")
    log(f"total_seconds={time.perf_counter() - t_start:.1f}")
    log("summary: " + json.dumps({
        "bring_up": bring_up,
        "token_equal_requests": [token_equal, len(reqs)],
        "train_losses": [round(l, 4) for l in losses],
        "interpreted_calls": n_interp,
    }))


if __name__ == "__main__":
    sys.exit(main())
