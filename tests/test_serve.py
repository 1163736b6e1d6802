"""Serving-runtime tests (ISSUE 7 tentpole): the continuous-batching
engine's outputs equal the unbatched no-cache oracle through batching,
staggered admission, page-pool preemption, and injected replica faults;
replica bring-up through a warmed registry performs zero local compiles;
the serve telemetry vocabulary is emitted."""

import os
import shutil
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import torchdistx_tpu.config as tdx_config
from torchdistx_tpu import chaos, observe
from torchdistx_tpu import compile_service
from torchdistx_tpu.models import TransformerConfig
from torchdistx_tpu.serve import (
    Request,
    ServeConfig,
    ServeEngine,
    oracle_generate,
    serve_program_specs,
    spin_up_replica,
    warm_serving,
)
from torchdistx_tpu.serve.programs import compile_serving_program

# Small enough that a full engine compiles in a few seconds on the
# 1-core CI box; vocab big enough that greedy argmax ties are
# vanishingly unlikely.
LLAMA = TransformerConfig(
    vocab_size=128, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=64, max_seq_len=64, dtype=jnp.float32,
)
GPT2 = TransformerConfig(
    vocab_size=128, d_model=32, n_layers=2, n_heads=4, d_ff=64,
    max_seq_len=64, use_bias=True, activation="gelu", norm="layernorm",
    positions="learned", tie_embeddings=True, dtype=jnp.float32,
)
SCFG = ServeConfig(max_batch=2, page_size=8, n_pages=16,
                   max_pages_per_seq=3, prefill_buckets=(8, 16))


def _params(family, cfg, seed=0):
    specs = serve_program_specs(family, cfg, SCFG, seed=seed)
    init = specs[0]
    compiled, _ = compile_serving_program(init)
    return jax.tree.unflatten(init.treedef, list(compiled()))


@pytest.fixture(scope="module")
def llama_params():
    return _params("llama", LLAMA)


@pytest.fixture(scope="module")
def llama_engine(llama_params):
    eng = ServeEngine("llama", LLAMA, llama_params, serve_cfg=SCFG)
    eng.warmup()
    return eng


def _check_oracle(eng, reqs, out):
    for r in reqs:
        want, want_logits = oracle_generate(
            eng.family, eng.cfg, eng.params, r.tokens, r.max_new_tokens,
            r.eos_id,
        )
        assert out[r.rid] == want, (r.rid, out[r.rid], want)
        np.testing.assert_allclose(
            eng.final_logits[r.rid], want_logits, atol=1e-4,
            err_msg=f"final logits of {r.rid}",
        )


def test_batched_engine_matches_unbatched_oracle(llama_engine):
    reqs = [
        Request("a", [5, 9, 2], max_new_tokens=6),
        Request("b", [17, 3, 3, 8, 1, 101], max_new_tokens=5),
        Request("c", [7] * 11, max_new_tokens=4),
    ]
    out = llama_engine.run(reqs)
    assert {"a", "b", "c"} <= set(out)
    _check_oracle(llama_engine, reqs, out)


def test_continuous_batching_staggered_arrivals(llama_engine):
    """More requests than lanes, arriving over time: every one completes
    and matches its oracle — admission interleaves with decode instead
    of waiting for the batch to drain."""
    reqs = [
        Request(f"s{i}", [(3 * i + j) % 128 for j in range(2 + i)],
                max_new_tokens=3 + (i % 3), arrival_step=i)
        for i in range(5)
    ]
    out = llama_engine.run(reqs)
    assert {r.rid for r in reqs} <= set(out)
    _check_oracle(llama_engine, reqs, out)


def test_eos_retires_early(llama_engine):
    r = Request("e", [5, 9, 2], max_new_tokens=6)
    first = oracle_generate(
        llama_engine.family, LLAMA, llama_engine.params, r.tokens, 1
    )[0][0]
    r2 = Request("e", [5, 9, 2], max_new_tokens=6, eos_id=first)
    out = llama_engine.run([r2])
    assert out["e"] == [first]  # retired at the first token, during prefill


def test_oracle_compiles_one_forward_a_length(llama_params):
    """The oracle compiles one forward a sequence length (not one program
    an operation), held per ``(family, cfg)``: a second call over lengths
    already seen compiles nothing, and its tokens and final logits are
    the stock model's, applied eagerly at each step."""
    from torchdistx_tpu.observe import compilelog
    from torchdistx_tpu.serve.programs import make_model

    compilelog.install()
    compiles = observe.counter("tdx.jax.backend_compiles")
    lowered = observe.counter("tdx.jax.lower_s")
    prompt, n = list(range(3, 44)), 5  # lengths 41..45: no other case's
    c0 = compiles.value
    toks, logits = oracle_generate("llama", LLAMA, llama_params, prompt, n)
    assert compiles.value - c0 <= n
    c1, l1 = compiles.value, lowered.value
    again = oracle_generate("llama", LLAMA, llama_params, prompt, n)
    assert (compiles.value, lowered.value) == (c1, l1)
    assert again[0] == toks
    np.testing.assert_array_equal(again[1], logits)

    model, seq, eager = make_model("llama", LLAMA), list(prompt), []
    for _ in range(n):
        last = np.asarray(model.apply(
            llama_params, jnp.asarray([seq], jnp.int32))[0, -1], np.float32)
        eager.append(int(np.argmax(last)))
        seq.append(eager[-1])
    assert toks == eager
    np.testing.assert_allclose(logits, last, atol=1e-4)


def test_page_pool_exhaustion_preempts_and_recovers(llama_params):
    """A pool too small for two long generations forces preemption: the
    youngest lane is requeued (counted), and every output still equals
    the oracle."""
    scfg = ServeConfig(max_batch=2, page_size=4, n_pages=7,
                       max_pages_per_seq=6, prefill_buckets=(8,))
    eng = ServeEngine("llama", LLAMA, llama_params, serve_cfg=scfg)
    observe.enable(True)
    try:
        def _ttft_count():
            for r in observe.counters().snapshot():
                if r["name"] == "tdx.serve.ttft_s":
                    return r["count"]
            return 0

        before = observe.counter("tdx.serve.preempted_requests").value
        ttft_before = _ttft_count()
        reqs = [
            Request("p0", [1, 2, 3, 4, 5, 6], max_new_tokens=8),
            Request("p1", [9, 8, 7, 6, 5, 4], max_new_tokens=8),
        ]
        out = eng.run(reqs)
        assert observe.counter("tdx.serve.preempted_requests").value > before
        # Re-prefills of preempted requests must not contribute bogus
        # TTFT samples: exactly one sample per request.
        assert _ttft_count() == ttft_before + len(reqs)
        _check_oracle(eng, reqs, out)
    finally:
        observe.enable(None)


def test_chaos_serve_fault_requeues_and_converges(llama_params,
                                                  llama_engine):
    """serve@N=raise mid-batch: active lanes are requeued and
    regenerated; outputs equal the fault-free oracle (recompute
    preemption, docs/serving.md)."""
    streamed: dict = {}
    eng = ServeEngine(
        "llama", LLAMA, llama_params, serve_cfg=SCFG,
        on_token=lambda rid, tok: streamed.setdefault(rid, []).append(tok),
    )
    # Same serve shape as the module fixture: reuse its compiled
    # programs (compiled executables are pure; this test targets the
    # engine loop, not compilation).
    eng._programs.update(llama_engine._programs)
    observe.enable(True)
    chaos.install("serve@2=raise;serve@4=slow:0.01")
    try:
        before = observe.counter("tdx.serve.preempted_requests").value
        rebuilds = observe.counter("tdx.serve.pool_rebuilds").value
        reqs = [
            Request("x", [1, 2, 3], max_new_tokens=5),
            Request("y", [9, 8, 7, 6], max_new_tokens=4),
        ]
        out = eng.run(reqs)
        assert observe.counter("tdx.serve.preempted_requests").value > before
        injected = chaos.active_plan()
        assert not injected.pending(), "both faults should have fired"
        # The site fires between programs: the pools were whole, and a
        # fault that finds them whole rebuilds nothing.
        assert observe.counter("tdx.serve.pool_rebuilds").value == rebuilds
        _check_oracle(eng, reqs, out)
        # The replayed prefix of a requeued request must not stream
        # twice: on_token sees each position exactly once.
        assert streamed == out, (streamed, out)
    finally:
        chaos.clear()
        observe.enable(None)


def test_fault_during_prefill_requeues_without_leaking_pages(llama_params):
    """A retryable fault while the prefill program compiles/executes —
    after the request left the queue but before its lane is active —
    must requeue the request and free its pages, not drop it (the
    chaos `compile` site fires inside the engine's first lazy program
    compile, which happens during prefill)."""
    eng = ServeEngine("llama", LLAMA, llama_params, serve_cfg=SCFG)
    observe.enable(True)
    chaos.install("compile@1=raise")
    try:
        before = observe.counter("tdx.serve.preempted_requests").value
        r = Request("pf", [8, 6, 4], max_new_tokens=3)
        out = eng.run([r])
        assert observe.counter("tdx.serve.preempted_requests").value > before
        assert eng.kv.pages_in_use == 0
        _check_oracle(eng, [r], out)
    finally:
        chaos.clear()
        observe.enable(None)


@pytest.mark.slow  # ~7 s of gpt2-family compiles; `make chaos-test` runs it
def test_gpt2_decode_matches_oracle():
    params = _params("gpt2", GPT2)
    eng = ServeEngine("gpt2", GPT2, params, serve_cfg=SCFG)
    reqs = [Request("g", [4, 5, 6, 7], max_new_tokens=4),
            Request("h", [40, 40, 2], max_new_tokens=3)]
    out = eng.run(reqs)
    _check_oracle(eng, reqs, out)


def test_run_budget_is_per_call_not_lifetime(llama_engine):
    """A long-lived replica (large cumulative step count) must still
    serve new run() calls — max_steps budgets THIS call."""
    llama_engine._step_no = 10**6
    r = Request("life", [2, 4, 6], max_new_tokens=2)
    out = llama_engine.run([r], max_steps=100)
    assert out["life"] == oracle_generate(
        "llama", LLAMA, llama_engine.params, r.tokens, 2)[0]


def test_submit_validation(llama_engine):
    with pytest.raises(ValueError, match="empty prompt"):
        llama_engine.submit(Request("bad", [], max_new_tokens=1))
    with pytest.raises(ValueError, match="max_context"):
        llama_engine.submit(Request("big", [1] * 20, max_new_tokens=20))
    # A zero budget would emit prefill's token while the oracle
    # generates nothing: rejected.
    with pytest.raises(ValueError, match="max_new_tokens"):
        llama_engine.submit(Request("zero", [1, 2], max_new_tokens=0))


def test_prompt_beyond_largest_bucket_serves_chunked(llama_engine):
    """A prompt larger than the largest prefill bucket used to be
    rejected at submit; chunked prefill serves it (and it still matches
    the oracle bitwise)."""
    assert 18 > llama_engine.scfg.prefill_buckets[-1]
    r = Request("wide", [(7 * i) % 128 for i in range(18)],
                max_new_tokens=2)
    out = llama_engine.run([r])
    _check_oracle(llama_engine, [r], out)


def test_serve_telemetry_vocabulary(llama_params, llama_engine):
    """The documented tdx.serve.* counter/gauge/histogram names are all
    emitted by one served batch (docs/observability.md)."""
    eng = ServeEngine("llama", LLAMA, llama_params, serve_cfg=SCFG)
    eng._programs.update(llama_engine._programs)
    observe.enable(True)
    try:
        eng.run([Request("t", [3, 1, 4], max_new_tokens=3)])
        snap = {r["name"]: r for r in observe.counters().snapshot()}
        for name in (
            "tdx.serve.prefills",
            "tdx.serve.decode_steps",
            "tdx.serve.requests_completed",
            "tdx.serve.kv_pages_in_use",
            "tdx.serve.queue_depth",
            "tdx.serve.tokens_per_s",
            "tdx.serve.ttft_s",
        ):
            assert name in snap, sorted(snap)
        assert snap["tdx.serve.requests_completed"]["value"] >= 1
        assert snap["tdx.serve.ttft_s"]["count"] >= 1
        # retirement freed the pages
        assert eng.kv.pages_in_use == 0
    finally:
        observe.enable(None)


@pytest.mark.slow  # ~15 s of compiles; `make chaos-test` + serve-smoke run it
def test_registry_warmed_bring_up_zero_local_compiles():
    """The autoscaling contract: warm_serving publishes the whole
    program set; a replica with a FRESH local cache then brings up with
    ZERO local compiles (every program a registry-fed hit) and still
    matches the oracle."""
    reg = tempfile.mkdtemp(prefix="tdx_serve_reg_")
    warm_cache = tempfile.mkdtemp(prefix="tdx_serve_ca_")
    fresh_cache = tempfile.mkdtemp(prefix="tdx_serve_cb_")
    observe.enable(True)
    # Persist even trivial programs: the `cow` page-copy compiles in
    # ~0.1 s on a warm process, straddling jax's default
    # min_compile_time_secs — whether warm_serving's cache file (and so
    # the registry entry) exists would otherwise depend on process
    # warmth, not the contract under test.
    old_min = os.environ.get("TDX_CACHE_MIN_COMPILE_S")
    os.environ["TDX_CACHE_MIN_COMPILE_S"] = "0"
    compile_service.reset_cache_binding()
    try:
        summary = warm_serving("llama", LLAMA, warm_cache,
                               registry_dir=reg, serve_cfg=SCFG)
        assert not summary["unwarmed"], summary
        assert summary["programs"] == len(summary["program_reports"])
        names = {r["program"] for r in summary["program_reports"]}
        assert names == {"init", "prefill-8", "prefill-16",
                         "chunk-8", "chunk-16", "cow", "decode",
                         "verify-2", "verify-4"}

        compile_service.reset_cache_binding()
        base = {r["name"]: r["value"]
                for r in observe.counters().snapshot()
                if r["type"] == "counter"}
        with tdx_config.override(cache_dir=fresh_cache, registry_dir=reg):
            eng = spin_up_replica(LLAMA, family="llama", serve_cfg=SCFG)
        snap = {r["name"]: r["value"]
                for r in observe.counters().snapshot()
                if r["type"] == "counter"}
        miss = (snap.get("tdx.jax.compile_cache_miss", 0)
                - base.get("tdx.jax.compile_cache_miss", 0))
        hits = (snap.get("tdx.jax.compile_cache_hit", 0)
                - base.get("tdx.jax.compile_cache_hit", 0))
        assert miss == 0, eng.bring_up_outcomes
        assert hits >= 4, eng.bring_up_outcomes
        assert set(eng.bring_up_outcomes.values()) == {"hit"}

        r = Request("w", [11, 22, 33], max_new_tokens=4)
        out = eng.run([r])
        _check_oracle(eng, [r], out)
    finally:
        observe.enable(None)
        if old_min is None:
            os.environ.pop("TDX_CACHE_MIN_COMPILE_S", None)
        else:
            os.environ["TDX_CACHE_MIN_COMPILE_S"] = old_min
        compile_service.reset_cache_binding()
        for d in (reg, warm_cache, fresh_cache):
            shutil.rmtree(d, ignore_errors=True)


def test_program_fingerprints_are_shape_sensitive():
    """Registry identity: same shape → same fingerprint; any serve-shape
    change → different fingerprint (a mismatched fetch is impossible by
    construction)."""
    a = {s.name: s.program_fp
         for s in serve_program_specs("llama", LLAMA, SCFG)}
    b = {s.name: s.program_fp
         for s in serve_program_specs("llama", LLAMA, SCFG)}
    assert a == b
    c = {s.name: s.program_fp
         for s in serve_program_specs(
             "llama", LLAMA,
             ServeConfig(max_batch=4, page_size=8, n_pages=16,
                         max_pages_per_seq=3, prefill_buckets=(8, 16)))}
    assert c["decode"] != a["decode"]
    # ...but the init program does not depend on the serve shape: its
    # (most expensive) artifact survives a pure capacity change.
    assert c["init"] == a["init"]
    d = {s.name: s.program_fp
         for s in serve_program_specs("llama", LLAMA, SCFG, seed=1)}
    assert d["init"] != a["init"]
    # max_new_tokens / prefill_chunk / prefix_cache / spec_decode /
    # spec_k are host-side knobs no compiled program reads: changing
    # them must NOT invalidate a warmed registry.
    e = {s.name: s.program_fp
         for s in serve_program_specs(
             "llama", LLAMA,
             ServeConfig(max_batch=2, page_size=8, n_pages=16,
                         max_pages_per_seq=3, prefill_buckets=(8, 16),
                         max_new_tokens=99, prefill_chunk=5,
                         prefix_cache=False, spec_decode=False,
                         spec_k=2))}
    assert e == a
    # ...while spec_buckets IS a shape knob: it picks which verify-<k>
    # programs exist (each one's own fp depends only on its k).
    assert {"verify-2", "verify-4"} <= set(a)
    f = {s.name: s.program_fp
         for s in serve_program_specs(
             "llama", LLAMA,
             ServeConfig(max_batch=2, page_size=8, n_pages=16,
                         max_pages_per_seq=3, prefill_buckets=(8, 16),
                         spec_buckets=(3,)))}
    assert "verify-3" in f and "verify-4" not in f
    assert f["decode"] == a["decode"]


# ---------------------------------------------------------------------------
# speculative decoding (ISSUE 19): drafts accepted, bitwise-oracle kept
# ---------------------------------------------------------------------------


def test_spec_decode_accepts_drafts_and_matches_oracle(llama_engine):
    """Self-drafting: after one generation taught the drafter a greedy
    chain, a repeat of the same prompt must accept draft tokens (more
    than one token per verify tick) while staying bitwise-equal to the
    unbatched oracle."""
    eng = llama_engine
    assert eng.scfg.spec_decode and eng._drafter is not None
    r1 = Request("sp-a", [23, 42, 17], max_new_tokens=6)
    out1 = eng.run([r1])
    _check_oracle(eng, [r1], out1)
    ticks0 = eng.spec_verify_ticks
    drafted0, accepted0 = eng.spec_drafted, eng.spec_accepted
    r2 = Request("sp-b", [23, 42, 17], max_new_tokens=6)
    out2 = eng.run([r2])
    _check_oracle(eng, [r2], out2)
    assert out2["sp-b"] == out1["sp-a"]
    assert eng.spec_verify_ticks > ticks0
    assert eng.spec_drafted > drafted0
    # The repeat's whole chain was in the drafter: accepts happened, so
    # the run took fewer verify ticks than it emitted tokens.
    accepted = eng.spec_accepted - accepted0
    assert accepted > 0, (eng.spec_drafted - drafted0, accepted)
    assert eng.spec_verify_ticks - ticks0 < 6


def test_spec_kill_switch_serves_plain_decode(llama_params, llama_engine):
    """``spec_decode=False`` (the TDX_SPEC_DECODE=0 path): no drafter,
    no verify ticks, identical tokens — the switch trades throughput,
    never output."""
    scfg = ServeConfig(max_batch=2, page_size=8, n_pages=16,
                       max_pages_per_seq=3, prefill_buckets=(8, 16),
                       spec_decode=False)
    eng = ServeEngine("llama", LLAMA, llama_params, serve_cfg=scfg)
    eng._programs.update(llama_engine._programs)
    assert eng._drafter is None and not eng.scfg.spec_decode
    reqs = [Request("ks-a", [23, 42, 17], max_new_tokens=5),
            Request("ks-b", [7] * 9, max_new_tokens=4)]
    out = eng.run(reqs)
    _check_oracle(eng, reqs, out)
    assert eng.spec_verify_ticks == 0 and eng.spec_drafted == 0
    # the env-var spelling resolves the same way
    with tdx_config.override(spec_decode=False):
        eng2 = ServeEngine("llama", LLAMA, llama_params, serve_cfg=SCFG)
    assert eng2._drafter is None and not eng2.scfg.spec_decode


def test_spec_decode_through_preemption_matches_oracle(llama_params):
    """Page-pool preemption while lanes are speculating: draft shedding
    plus token-level KV rollback keep every output bitwise-equal to the
    oracle and the preempted lane's requeue intact."""
    scfg = ServeConfig(max_batch=2, page_size=4, n_pages=7,
                       max_pages_per_seq=6, prefill_buckets=(8,))
    eng = ServeEngine("llama", LLAMA, llama_params, serve_cfg=scfg)
    observe.enable(True)
    try:
        before = observe.counter("tdx.serve.preempted_requests").value
        # Repetitive prompts make the n-gram drafter propose from the
        # first decode tick, so speculation is live when the pool runs dry.
        reqs = [
            Request("pp0", [7] * 6, max_new_tokens=8),
            Request("pp1", [7, 7, 7, 9, 9, 9], max_new_tokens=8),
        ]
        out = eng.run(reqs)
        assert observe.counter("tdx.serve.preempted_requests").value > before
        assert eng.spec_drafted > 0
        _check_oracle(eng, reqs, out)
    finally:
        observe.enable(None)
    eng.drain()
    assert eng.kv.pages_in_use == 0


def test_chaos_raise_verify_requeues_and_converges(llama_params,
                                                   llama_engine):
    """serve@N=raise:verify fires at the next speculative verify tick —
    after drafting and KV growth, before accept/rollback: active lanes
    requeue and regenerate, outputs equal the fault-free oracle, and no
    pages leak."""
    eng = ServeEngine("llama", LLAMA, llama_params, serve_cfg=SCFG)
    eng._programs.update(llama_engine._programs)
    observe.enable(True)
    # Teach the drafter this chain so the targeted tick really drafts.
    warm = Request("vf-w", [7] * 8, max_new_tokens=4)
    eng.run([warm])
    chaos.install(f"serve@{eng._step_no + 3}=raise:verify")
    try:
        before = observe.counter("tdx.serve.preempted_requests").value
        rebuilds = observe.counter("tdx.serve.pool_rebuilds").value
        reqs = [Request("vf-a", [7] * 8, max_new_tokens=6),
                Request("vf-b", [9, 8, 7, 6], max_new_tokens=4)]
        out = eng.run(reqs)
        assert not chaos.active_plan().pending(), "the fault never fired"
        assert observe.counter("tdx.serve.preempted_requests").value > before
        assert observe.counter("tdx.serve.pool_rebuilds").value == rebuilds
        _check_oracle(eng, reqs, out)
    finally:
        chaos.clear()
        observe.enable(None)
    eng.drain()
    assert eng.kv.pages_in_use == 0
    assert not eng.kv._ref
