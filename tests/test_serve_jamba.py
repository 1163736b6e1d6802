"""The jamba decode family (Mamba-1 layers beside attention layers) on the
normal serving path, at one period of the pattern and width 64 on the
CPU, against the benchmark's plain reference
(``benchmark/reference/jamba.py``, which imports nothing of the program)
on seeded random weights.  One parametrised test a property.

Tolerance: everything here runs in float32 with ``highest`` matmul
precision, so the program and the reference differ only in the order of
float32 sums (the program's state is ``[state, channel]``, the
reference's ``[channel, state]``; the served path splits a sequence into
prefill, chunks and decode ticks).  Logits are of order 1; 2e-5 absolute
is a hundred times the differences read (at most 3e-7) and a thousand
times under what any of the planted faults below moves them by.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.families import jamba as fam  # noqa: E402
from benchmark.reference import jamba as ref  # noqa: E402
from torchdistx_tpu import observe  # noqa: E402
from torchdistx_tpu.models import TINY_JAMBA, decoder_lm_plan  # noqa: E402
from torchdistx_tpu.models import jamba as prog  # noqa: E402
from torchdistx_tpu.serve import Request, ServeConfig, programs  # noqa: E402
from torchdistx_tpu.serve.engine import ServeEngine  # noqa: E402
from torchdistx_tpu.serve.kv_cache import (KVCacheConfig, PagedKVCache,  # noqa: E402
                                            StateCacheConfig)

ATOL = 2e-5
CFG = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 1,
       "intermediate_size": 128, "num_hidden_layers": 14, "vocab_size": 256,
       "max_position_embeddings": 128, "rms_norm_eps": 1e-6,
       "mamba_expand": 2, "mamba_d_state": 16, "mamba_d_conv": 4,
       "mamba_dt_rank": 8, "attn_layer_period": 14, "attn_layer_offset": 7}
C = fam.dims(CFG)
TCFG = dataclasses.replace(fam.transformer_config(CFG, C), dtype=jnp.float32)
SCFG = ServeConfig(max_batch=4, page_size=8, n_pages=64, max_pages_per_seq=8,
                   prefill_buckets=(8, 32), prefix_cache=False,
                   spec_decode=False)
N_NEW = 6


@pytest.fixture(scope="module", autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def weights():
    return fam.make(C, 7, jnp.float32)


@pytest.fixture(scope="module")
def engine(weights):
    """One replica for the whole file: every case below leaves it with no
    active lane, and a program compiles once."""
    eng = ServeEngine("jamba", TCFG, fam.param_tree(weights), serve_cfg=SCFG)
    eng.logits_seen = {}
    emit = eng._emit

    def record(lane, token, logits):
        eng.logits_seen.setdefault(lane.req.rid, []).append(
            np.array(logits, np.float32))
        return emit(lane, token, logits)

    record.__wrapped__ = emit
    eng._emit = record
    return eng


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 256, n)]


def _reference(weights, prompt, out):
    """Reference logits that predict each served token (full forward over
    the prompt and the served tokens before it)."""
    seq = list(prompt) + list(out[:-1])
    return ref.Forward(C, None, 64).logits(weights, seq, len(prompt) - 1,
                                           len(out))


def _serve(eng, reqs, **knobs):
    """Run ``reqs`` on ``eng`` with host-side knobs of the resolved serve
    config replaced; returns {rid: (tokens, [logits a token])}."""
    old = eng.scfg
    eng.scfg = dataclasses.replace(old, **knobs)
    try:
        for r in reqs:
            eng.logits_seen.pop(r.rid, None)
        out = eng.run(reqs)
    finally:
        eng.scfg = old
    return {r.rid: (out[r.rid], np.stack(eng.logits_seen[r.rid]))
            for r in reqs}


# -- the model against the reference ------------------------------------------


@pytest.mark.parametrize("what", ["mixer", "model"])
def test_full_forward_equals_the_reference(weights, what):
    toks = _prompt(1, 40)
    p = prog.param_tree(fam.param_tree(weights)["params"])
    if what == "model":
        want = ref.Forward(C, None, 64).logits(weights, toks, 0, 40)
        got = jax.jit(lambda t: prog.full_forward(TCFG, p, t))(
            jnp.asarray([toks]))[0]
    else:
        v = jax.random.normal(jax.random.PRNGKey(3), (40, 64), jnp.float32)
        lw = {k: a.astype(jnp.float32)
              for k, a in ref.layer_weights(C, weights, 3).items()}
        want = ref.mamba_mixer(C, None, None, v, lw)
        m = jax.tree.map(lambda a: a[3], p["mamba"])
        got, s, tail = prog.mamba_mixer(
            TCFG, m, v[None], jnp.zeros((1, 16, 128)),
            jnp.zeros((3, 1, 128)), jnp.asarray([40], jnp.int32))
        got = got[0]
        assert float(jnp.abs(s).max()) > 1e-3  # the recurrence carries
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)


# -- the served path ----------------------------------------------------------


@pytest.mark.parametrize("n_prompt", [5, 8, 21])
def test_prefill_then_decode_through_the_cache_equals_the_reference(
        engine, weights, n_prompt):
    prompt = _prompt(10 + n_prompt, n_prompt)
    toks, logits = _serve(engine, [Request("a", prompt, N_NEW)])["a"]
    want = _reference(weights, prompt, toks)
    np.testing.assert_allclose(logits, want, atol=ATOL)
    assert toks == [int(t) for t in want.argmax(-1)]


def _values(jaxpr):
    """Every value a jaxpr computes, inner jaxprs (loops, branches) too."""
    for eqn in jaxpr.eqns:
        yield from eqn.outvars
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _values(sub)


@pytest.mark.parametrize("name", ["decode", "prefill-8", "chunk-8"])
def test_the_recurrence_is_float32_in_a_bfloat16_program(name):
    """The configuration states a float32 recurrence under bfloat16
    weights and activations, and the benchmark's ``logit_gap`` does not
    separate a bfloat16 state (PERF.md, PR 28), so it is pinned by
    structure: the program takes and returns the SSM state as float32,
    and no value of the state's shape inside it, the loops' carries
    included, is anything else.  The conv tail is of the model's dtype."""
    cfg = dataclasses.replace(TCFG, dtype=jnp.bfloat16)
    spec = {s.name: s for s in programs.serve_program_specs(
        "jamba", cfg, SCFG, include_init=False)}[name]
    ssm_in, conv_in = spec.args[3:5]
    closed = jax.make_jaxpr(spec.fn)(*spec.args)
    ssm_out, conv_out = closed.out_avals[3:5]
    assert ssm_in.dtype == ssm_out.dtype == jnp.float32
    assert conv_in.dtype == conv_out.dtype == jnp.bfloat16
    assert ssm_in.shape == ssm_out.shape == (13, 4, C["d_state"], C["d_inner"])
    state_shaped = [v.aval for v in _values(closed.jaxpr)
                    if v.aval.shape[-2:] == (C["d_state"], C["d_inner"])]
    assert len(state_shaped) > 4  # the update is there to be read
    assert {str(a.dtype) for a in state_shaped} == {"float32"}


@pytest.mark.parametrize("chunk", [1, 2, 3, 5, 7, 8, 13])
def test_a_prompt_chunked_at_any_boundary_equals_it_unchunked(engine, chunk):
    """State and conv tail are carried from chunk to chunk: chunks of
    ``chunk`` tokens put a boundary after every multiple of it."""
    prompt = _prompt(20, 27)
    whole = _serve(engine, [Request("w", prompt, N_NEW)],
                   prefill_chunk=32)["w"]
    before = dict(engine.program_calls)
    parts = _serve(engine, [Request("c", prompt, N_NEW)],
                   prefill_chunk=chunk)["c"]
    n_chunks = sum(v - before.get(k, 0) for k, v in
                   engine.program_calls.items() if k.startswith("chunk-"))
    assert n_chunks == -(-27 // chunk)
    assert parts[0] == whole[0]
    np.testing.assert_allclose(parts[1], whole[1], atol=ATOL)


@pytest.mark.parametrize("n_prompt", [1, 3, 8])
def test_padding_a_bucket_leaves_the_state_alone(engine, n_prompt):
    """A prompt of length n in the bucket of 32 against the same prompt in
    the bucket of 8 (exact at n = 8): positions past n advance neither the
    state nor the conv tail."""
    prompt = _prompt(30 + n_prompt, n_prompt)
    small = _serve(engine, [Request("s", prompt, N_NEW)])["s"]
    big = _serve(engine, [Request("b", prompt, N_NEW)],
                 prefill_buckets=(32,))["b"]
    assert engine.program_calls["prefill-32"] >= 1
    assert big[0] == small[0]
    np.testing.assert_allclose(big[1], small[1], atol=ATOL)


@pytest.mark.parametrize("path", ["prefill", "chunk"])
def test_a_reused_lane_starts_from_zero(engine, path):
    """Lane 0 serves one request, is retired, and is given another while
    its slot holds a planted stale state: the second request's logits are
    those of a fresh engine.  (Without the reset they are not: see
    ``test_without_the_reset_a_stale_state_shows``.)"""
    knobs = {"prefill_chunk": 32 if path == "prefill" else 4}
    prompt = _prompt(40, 11)
    clean = _serve(engine, [Request("x", prompt, N_NEW)], **knobs)["x"]
    _serve(engine, [Request("first", _prompt(41, 9), N_NEW)])
    resets = observe.counter("tdx.serve.state_resets").value
    ssm, conv = engine.state
    engine.state = (ssm.at[:, 0].set(3.0), conv.at[:, :, 0].set(-2.0))
    again = _serve(engine, [Request("y", prompt, N_NEW)], **knobs)["y"]
    assert observe.counter("tdx.serve.state_resets").value == resets + 1
    assert again[0] == clean[0]
    np.testing.assert_allclose(again[1], clean[1], atol=ATOL)


def test_without_the_reset_a_stale_state_shows(weights, monkeypatch):
    """The planted fault: ``_lane_state`` told that no call is a
    sequence's first.  The same prefill then reads the stale slot."""
    scfg = SCFG.resolve(TCFG)
    params = fam.param_tree(weights)
    kv = scfg.kv_config(TCFG)
    pools = [jnp.zeros(kv.pool_shape(), jnp.float32)] * 2
    stale = (jnp.full(kv.state.ssm_shape(), 3.0, jnp.float32),
             jnp.full(kv.state.conv_shape(), -2.0, jnp.float32))
    zero = tuple(jnp.zeros_like(a) for a in stale)
    toks = np.zeros((1, 8), np.int32)
    toks[0, :5] = _prompt(50, 5)
    rest = (jnp.asarray(toks), jnp.asarray([5], jnp.int32),
            jnp.asarray([[1] + [0] * 7], jnp.int32),
            jnp.asarray([0], jnp.int32))

    def logits(state):
        fn = programs.build_prefill_fn("jamba", TCFG, scfg, 8)
        return np.asarray(jax.jit(fn)(params, *pools, *state, *rest)[0])

    np.testing.assert_allclose(logits(stale), logits(zero), atol=ATOL)
    real = programs._lane_state
    monkeypatch.setattr(programs, "_lane_state",
                        lambda slot, fresh, n: real(slot, False, n))
    assert np.abs(logits(stale) - logits(zero)).max() > 100 * ATOL


def test_preempt_and_resume_gives_the_same_logits(engine):
    prompt = _prompt(60, 14)
    calm = _serve(engine, [Request("p", prompt, N_NEW)])["p"]
    before = observe.counter("tdx.serve.recomputed_tokens").value
    engine.logits_seen.pop("q", None)
    engine.submit(Request("q", prompt, N_NEW))
    for _ in range(3):
        engine.step()
    assert engine.requeue_active(reason="pages") == 1
    assert engine.kv.state_slots_in_use == 0  # the state is dropped
    out = engine.run()["q"]
    assert observe.counter(
        "tdx.serve.recomputed_tokens").value == before + len(prompt)
    assert out == calm[0]
    # the replayed tokens' logits too: the last N_NEW emits are the resumed run
    np.testing.assert_allclose(
        np.stack(engine.logits_seen["q"][-N_NEW:]), calm[1], atol=ATOL)


def test_a_slot_refilled_after_a_cancel_serves_the_newcomer_as_if_alone(engine):
    """A lane cancelled at its deadline frees its slot and drops its
    state; the next request is admitted into the slot in the same tick,
    beside three lanes that go on decoding, and its tokens and logits are
    those it gets alone."""
    prompts = [_prompt(90 + i, 6 + i) for i in range(5)]
    alone = _serve(engine, [Request("solo", prompts[4], N_NEW)])["solo"]
    reqs = [Request(f"o{i}", prompts[i], 20 if i < 4 else N_NEW)
            for i in range(5)]
    for r in reqs:
        engine.logits_seen.pop(r.rid, None)
        engine.submit(r)
    engine.step()
    engine.step()
    assert len(engine.active) == 4 and len(engine.waiting) == 1
    doomed = engine.active[2]
    doomed.req._deadline_t = 0.0  # already past its deadline
    engine.step()
    assert "o2" in engine.cancelled
    assert engine.active[2].req.rid == "o4"
    out = engine.run()
    assert out["o4"] == alone[0]
    np.testing.assert_allclose(np.stack(engine.logits_seen["o4"]), alone[1],
                               atol=ATOL)


@pytest.mark.parametrize("order", [(0, 1, 2, 3), (3, 1, 0, 2), (2, 3, 1, 0)])
def test_lanes_are_independent(engine, order):
    """Permuting which lane a request rides permutes the outputs: each
    request's logits are those it gets alone."""
    prompts = [_prompt(70 + i, n) for i, n in enumerate((4, 9, 17, 30))]
    alone = [_serve(engine, [Request(f"solo{i}", p, N_NEW)])[f"solo{i}"]
             for i, p in enumerate(prompts)]
    reqs = [Request(f"r{i}", prompts[i], N_NEW) for i in order]
    got = _serve(engine, reqs)
    assert engine.kv.state_slots_peak == 4
    for i in order:
        assert got[f"r{i}"][0] == alone[i][0]
        np.testing.assert_allclose(got[f"r{i}"][1], alone[i][1], atol=ATOL)


# -- what the family refuses ---------------------------------------------------


@pytest.mark.parametrize("knobs, word", [
    ({"spec_decode": True, "prefix_cache": False}, "spec_decode"),
    ({"spec_decode": False, "prefix_cache": True}, "prefix_cache"),
    ({"spec_decode": True, "prefix_cache": True}, "spec_decode and prefix"),
])
def test_speculation_and_the_prefix_cache_are_refused_with_the_reason(
        knobs, word):
    with pytest.raises(ValueError, match="recurrent") as e:
        ServeConfig(**knobs).resolve(TINY_JAMBA)
    assert word in str(e.value) and "rolled back" in str(e.value)
    ServeConfig(spec_decode=False, prefix_cache=False).resolve(TINY_JAMBA)


def test_no_verify_program_and_the_error_names_the_families():
    scfg = SCFG.resolve(TCFG)
    with pytest.raises(NotImplementedError, match="rolled back"):
        programs.build_verify_fn("jamba", TCFG, scfg, 2)
    with pytest.raises(ValueError, match=r"gpt2 \| llama \| jamba"):
        programs.make_model("mamba2", TCFG.replace(mamba=None))
    with pytest.raises(ValueError, match="jamba"):
        programs.make_model("llama", TCFG)
    names = [s.name for s in programs.serve_program_specs(
        "jamba", TCFG, SCFG, include_init=False)]
    assert names == ["prefill-8", "prefill-32", "chunk-8", "chunk-32",
                     "decode"]


# -- the cache manager's second kind ------------------------------------------


def test_state_slots_are_bound_dropped_and_never_shared():
    st = StateCacheConfig(n_layers=13, d_inner=128, d_state=16, d_conv=4,
                          lanes=2)
    assert st.ssm_shape() == (13, 2, 16, 128)
    assert st.conv_shape() == (13, 3, 2, 128)
    kv = PagedKVCache(KVCacheConfig(n_layers=1, kv_heads=1, head_dim=16,
                                    page_size=8, n_pages=8, state=st))
    with pytest.raises(ValueError, match="state slot"):
        kv.alloc(1, 4)
    kv.alloc(1, 4, slot=1)
    assert kv.state_slot(1) == 1 and kv.state_slots_in_use == 1
    with pytest.raises(ValueError, match="held"):
        kv.alloc(2, 4, slot=1)
    with pytest.raises(ValueError, match="shares no pages"):
        kv.alloc_shared(3, [1], 12)
    kv.free(1)
    assert kv.state_slots_in_use == 0 and kv.state_slots_peak == 1
    kv.alloc(2, 4, slot=1)
    kv.reset()
    assert kv.state_slots_in_use == 0


# -- a mesh -------------------------------------------------------------------


def test_decode_on_a_tp2_mesh_equals_one_device(weights):
    """The plan splits the mixer's channels and the state over ``tp``
    (the single KV head stays whole, so the decode kernel runs
    replicated); GSPMD partitions the rest.  Equal to one device up to
    the order of the row-sharded projections' sums."""
    from torchdistx_tpu.parallel import make_mesh

    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    one = {s.name: s for s in programs.serve_program_specs(
        "jamba", TCFG, SCFG, include_init=False)}["decode"]
    two = {s.name: s for s in programs.serve_program_specs(
        "jamba", TCFG, SCFG, include_init=False, mesh=mesh,
        plan=decoder_lm_plan(fsdp=None, ep=None))}["decode"]
    assert two.args[3].sharding.spec[3] == "tp"          # the state
    flat = jax.tree_util.tree_leaves_with_path(two.args[0])
    assert any("tp" in str(a.sharding.spec) for _, a in flat)
    rng = np.random.default_rng(5)
    params = fam.param_tree(weights)
    cache = [jnp.asarray(rng.normal(size=a.shape), a.dtype)
             for a in one.args[1:5]]
    rest = (jnp.asarray([5, 9, 0, 200], jnp.int32),
            jnp.asarray([3, 17, 0, 9], jnp.int32),
            jnp.asarray(rng.integers(1, 64, (4, 8)), jnp.int32))
    want = jax.jit(one.fn)(params, *cache, *rest)
    placed = jax.tree.map(lambda a, s: jax.device_put(a, s.sharding),
                          (params, *cache), tuple(two.args[:5]))
    got = jax.jit(two.fn, out_shardings=two.out_shardings)(*placed, *rest)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
    # lane 2 sat the tick out: its state is exactly what it was
    np.testing.assert_array_equal(np.asarray(got[3])[:, 2],
                                  np.asarray(cache[2])[:, 2])
