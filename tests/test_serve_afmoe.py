"""The ``afmoe`` family (arcee-ai Trinity: sigmoid-routed experts with a
shared one, window and full attention mixed, gated attention) through the
serving runtime, on the CPU at a size a test can hold, against the
benchmark's plain reference (``benchmark/reference/afmoe.py``) on the
benchmark's seeded weights: the full forward, prefill, chunked prefill and
decode through BOTH cache groups with contexts past a window of 16; the
eight shares of a layer group adding up to the uncut reference layer; a
token routed to no held expert; preemption with a short window pool; the
refusals; the spans and counters.

Tolerances: everything here is float32 (program and reference), so the two
differ by the order of float32 sums alone, 1e-5 of logits of order 1;
``ATOL`` leaves that ten times of room.  A wrong mask, page or expert
moves a logit by 0.1 and more (the planted cases say so)."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.families import afmoe as fam  # noqa: E402
from benchmark.reference import afmoe as ref  # noqa: E402
from torchdistx_tpu import observe  # noqa: E402
from torchdistx_tpu.models import (TINY, TINY_AFMOE, TINY_MOE,  # noqa: E402
                                   make_afmoe)
from torchdistx_tpu.models import afmoe as prog  # noqa: E402
from torchdistx_tpu.serve import Request, ServeConfig, programs  # noqa: E402
from torchdistx_tpu.serve.engine import ServeEngine  # noqa: E402

ATOL = 1e-4
CFG = {"hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
       "num_key_value_heads": 1, "intermediate_size": 128,
       "moe_intermediate_size": 32, "num_hidden_layers": 5,
       "num_dense_layers": 1, "num_experts": 4, "router_outputs": 8,
       "first_expert": 0, "num_experts_per_tok": 2, "route_scale": 2.448,
       "layer_types": ["sliding_attention", "sliding_attention",
                       "full_attention", "sliding_attention",
                       "sliding_attention"],
       "sliding_window": 16, "vocab_size": 256,
       "max_position_embeddings": 256, "rope_theta": 10000,
       "rms_norm_eps": 1e-5, "activation_dtype": "float32"}
C = fam.dims(CFG)
TCFG = fam.transformer_config(CFG, C)
SCFG = ServeConfig(max_batch=4, page_size=8, n_pages=64, max_pages_per_seq=12,
                   prefill_buckets=(8, 32), prefill_chunk=32,
                   prefix_cache=False, spec_decode=False)
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
    eng = ServeEngine("afmoe", TCFG, fam.param_tree(weights), serve_cfg=SCFG)
    eng.logits_seen = {}
    emit = eng._emit

    def record(lane, token, logits):
        eng.logits_seen.setdefault(lane.req.rid, []).append(
            np.array(logits, np.float32))
        return emit(lane, token, logits)

    eng._emit = record
    return eng


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 256, n)]


def _reference(weights, prompt, out, **faults):
    seq = list(prompt) + list(out[:-1])
    return ref.Forward(C, None, 64, **faults).logits(
        weights, seq, len(prompt) - 1, len(out))


def _serve(eng, reqs, **knobs):
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


def test_the_configuration_is_the_tiny_preset_but_for_names():
    assert TCFG.afmoe == TINY_AFMOE.afmoe
    assert (TCFG.d_model, TCFG.n_heads, TCFG.kv_heads, TCFG.head_size) == (
        64, 4, 1, 16)


def test_full_forward_equals_the_reference(weights):
    toks = _prompt(1, 90)                       # five windows and a half
    got = make_afmoe(TCFG).apply(fam.param_tree(weights),
                                 jnp.asarray([toks], jnp.int32))[0]
    want = ref.Forward(C, None, 64).logits(weights, toks, 0, len(toks))
    np.testing.assert_allclose(np.asarray(got), want, atol=ATOL)
    assert float(np.std(want)) > 0.5            # logits of order 1, not 0


@pytest.mark.parametrize("n_prompt", [5, 8, 21, 40, 70])
def test_prefill_then_decode_through_both_groups_equals_the_reference(
        engine, weights, n_prompt):
    """5 and 8: one bucket; 21: a prefill past the window; 40 and 70: two
    and three chunks, the later ones reading window pages the earlier
    wrote; every one then decodes past a window's edge."""
    prompt = _prompt(20 + n_prompt, n_prompt)
    n_new = 24
    toks, logits = _serve(engine, [Request("a", prompt, n_new)])["a"]
    np.testing.assert_allclose(logits, _reference(weights, prompt, toks),
                               atol=ATOL)
    calls = engine.program_calls
    assert calls["decode"] > 0
    if n_prompt > 32:
        assert calls.get("chunk-32", 0) + calls.get("chunk-8", 0) > 0


@pytest.mark.parametrize("fault, least", [
    ({"window": None}, 0.1), ({"drop_expert": 1}, 0.1)])
def test_the_reference_with_a_fault_planted_is_far_from_the_program(
        engine, weights, fault, least):
    """What ATOL is small against: a window layer that attends the whole
    context, a held expert left out."""
    prompt = _prompt(61, 40)
    toks, logits = _serve(engine, [Request("f", prompt, 12)])["f"]
    assert np.abs(logits - _reference(weights, prompt, toks)).max() < ATOL
    assert np.abs(logits - _reference(weights, prompt, toks, **fault)
                  ).max() > least


@pytest.mark.parametrize("chunk", [3, 8, 13, 32])
def test_a_prompt_chunked_at_any_boundary_equals_it_unchunked(engine, chunk):
    prompt = _prompt(33, 50)
    whole = _serve(engine, [Request("w", prompt, N_NEW)],
                   prefill_chunk=32)["w"]
    cut = _serve(engine, [Request("c", prompt, N_NEW)],
                 prefill_chunk=chunk)["c"]
    assert cut[0] == whole[0]
    np.testing.assert_allclose(cut[1], whole[1], atol=ATOL)


@pytest.mark.parametrize("order", [(0, 1, 2, 3), (3, 1, 0, 2)])
def test_lanes_are_independent(engine, order):
    reqs = [Request(f"l{i}", _prompt(70 + i, n), N_NEW + i)
            for i, n in enumerate((4, 19, 45, 33))]
    alone = {r.rid: _serve(engine, [r])[r.rid] for r in reqs}
    together = _serve(engine, [reqs[i] for i in order])
    for rid, (toks, logits) in alone.items():
        assert together[rid][0] == toks
        np.testing.assert_allclose(together[rid][1], logits, atol=ATOL)


# -- the share test -----------------------------------------------------------

# The uncut layer: 16 query heads over 8 KV heads, 16 experts, top-2.  A
# share: one GQA group (2 + 1 heads) and 2 experts, as the configuration
# of the benchmark holds one of eight.
FULL = dict(CFG, num_attention_heads=16, num_key_value_heads=8,
            num_experts=16, router_outputs=16, num_hidden_layers=2,
            layer_types=["sliding_attention", "full_attention"],
            num_dense_layers=0)
SHARES = 8


def _share_weights(lw, s):
    """Chip ``s``'s slice of an uncut layer's tensors: its GQA group's
    heads and its two experts; what every chip holds whole, whole."""
    out = dict(lw)
    for n in ("wq", "wg"):
        out[n] = lw[n][:, 2 * s:2 * s + 2]
    for n in ("wk", "wv"):
        out[n] = lw[n][:, s:s + 1]
    out["wo"] = lw["wo"][2 * s:2 * s + 2]
    for n in ("experts.w_gate", "experts.w_up", "experts.w_down"):
        out[n] = lw[n][2 * s:2 * s + 2]
    return out


@pytest.mark.parametrize("layer", [0, 1], ids=["sliding", "full"])
def test_the_eight_shares_add_up_to_the_uncut_reference_layer(layer):
    """The PROGRAM's attention output and routed part of each share
    (``models/afmoe.py`` at the share's sizes), with the shared expert
    counted once, against the REFERENCE's uncut layer: what the exchange
    of the deployment would add up."""
    cf = fam.dims(FULL)
    w = fam.make(cf, 11, jnp.float32)
    lw = fam.layer_weights(w, layer)
    sliding = cf["layer_types"][layer] == "sliding"
    T = 40
    x = jnp.asarray(np.random.default_rng(3).normal(size=(T, 64)), jnp.float32)
    want_attn = ref.attention_part(cf, None, cf["window"], sliding, x, lw)
    weights_full, _ = ref.route(cf, None, x, lw)
    want_routed = ref.routed_part(cf, None, weights_full, x, lw)
    want_ffn = ref.shared_part(None, x, lw) + want_routed

    got_attn = jnp.zeros_like(want_attn)
    got_routed = jnp.zeros_like(want_routed)
    pairs = 0
    positions = jnp.arange(T, dtype=jnp.int32)[None]
    for s in range(SHARES):
        share = dict(FULL, num_attention_heads=2, num_key_value_heads=1,
                     num_experts=2, first_expert=2 * s)
        cs = fam.dims(share)
        tc = fam.transformer_config(share, cs)
        flat = {"layers.0." + k: v for k, v in _share_weights(lw, s).items()}
        lp = prog.param_tree({
            k: v for k, v in fam.param_tree(dict(
                flat, embed=w["embed"], lm_head=w["lm_head"],
                **{"final_norm.scale": w["final_norm.scale"]}))["params"]
            .items()})["layers"][0]
        q, k, v, g = prog.qkvg(tc, lp, x[None], positions, sliding)
        o = prog.dense_attention(q, k, v, positions, jnp.asarray([T]),
                                 cs["window"] if sliding else None)
        got_attn += prog.attn_out(tc, lp, o, g)[0]
        idx, wts = prog.route(tc, lp, x)
        routed, sizes = prog.held_expert_sum(
            tc, lp, x, idx, wts, jnp.ones((T,), bool))
        got_routed += routed
        pairs += int(sizes.sum())
        # the share alone against the reference given the same share
        np.testing.assert_allclose(
            np.asarray(routed),
            np.asarray(ref.routed_part(cs, None, ref.route(
                cs, None, x, _share_weights(lw, s))[0], x,
                _share_weights(lw, s))), atol=ATOL)
    assert pairs == T * 2                  # every choice landed on one share
    np.testing.assert_allclose(np.asarray(got_attn), np.asarray(want_attn),
                               atol=ATOL)
    shared = prog.gated_mlp(tc, lp["shared_w_gate"], lp["shared_w_up"],
                            lp["shared_w_down"], x)
    np.testing.assert_allclose(np.asarray(shared + got_routed),
                               np.asarray(want_ffn), atol=ATOL)


def test_a_token_routed_to_no_held_expert_gets_the_shared_expert_alone(weights):
    lp = dict(prog.param_tree(fam.param_tree(weights)["params"])["layers"][2])
    # a selection bias that sends every token to experts 6 and 7: held
    # elsewhere (this replica holds 0-3)
    lp["router_bias"] = jnp.asarray([0, 0, 0, 0, 0, 0, 9, 9], jnp.float32)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 7, 64)),
                    jnp.float32)
    y, pairs = prog.moe(TCFG, lp, x, jnp.ones((2, 7), bool))
    shared = prog.gated_mlp(TCFG, lp["shared_w_gate"], lp["shared_w_up"],
                            lp["shared_w_down"], x.reshape(14, 64))
    assert int(pairs.sum()) == 0
    np.testing.assert_allclose(np.asarray(y).reshape(14, 64),
                               np.asarray(shared), atol=1e-6)
    # and the routing weights still sum to route_scale over the two chosen
    _, w = prog.route(TCFG, lp, x.reshape(14, 64))
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.448, rtol=1e-5)


def test_padded_and_idle_positions_route_nowhere(weights):
    lp = prog.param_tree(fam.param_tree(weights)["params"])["layers"][1]
    x = jnp.asarray(np.random.default_rng(6).normal(size=(1, 8, 64)),
                    jnp.float32)
    valid = jnp.asarray([[True] * 5 + [False] * 3])
    y, pairs = prog.moe(TCFG, lp, x, valid)
    y5, pairs5 = prog.moe(TCFG, lp, x[:, :5], valid[:, :5])
    assert int(pairs.sum()) == int(pairs5.sum()) <= 5 * 2
    np.testing.assert_allclose(np.asarray(y[:, :5]), np.asarray(y5),
                               atol=1e-6)


# -- the window group under the engine ----------------------------------------


def test_a_sequence_holds_a_window_of_pages_and_returns_the_rest(engine):
    released = observe.counter("tdx.serve.window_pages_released")
    before = released.value
    prompt = _prompt(80, 70)
    seen = []
    engine.submit(Request("h", prompt, 26))
    while engine.active or engine.waiting:
        engine.step()
        for lane in engine.active.values():
            seen.append((lane.length, len(engine.kv.window_page_ids(
                lane.seq_id)), len(engine.kv.page_ids(lane.seq_id))))
    decoding = [w for length, w, _ in seen if length > 70]
    assert max(decoding) <= 16 // 8 + 1     # window / page + 1
    assert max(w for _, w, _ in seen) <= (16 + 32) // 8 + 1   # a chunk's
    assert max(f for _, _, f in seen) == 96 // 8       # the full group grows
    assert released.value - before >= 8
    assert engine.kv.window_pages_in_use == 0 and engine.kv.pages_in_use == 0


def test_a_short_window_pool_preempts_and_the_tokens_are_the_same(weights):
    """Seven usable window pages: two sequences of 20 and their windows do
    not fit beside each other, so the younger is preempted for window
    pages, prefills again, and every token is what it would have been."""
    prompts = [_prompt(90 + i, 20) for i in range(3)]
    roomy = ServeEngine("afmoe", TCFG, fam.param_tree(weights),
                        serve_cfg=SCFG)
    want = roomy.run([Request(f"r{i}", p, 10) for i, p in enumerate(prompts)])
    tight = ServeEngine(
        "afmoe", TCFG, fam.param_tree(weights),
        serve_cfg=dataclasses.replace(SCFG, n_window_pages=8))
    preempted = observe.counter("tdx.serve.preempted_requests")
    again = observe.counter("tdx.serve.recomputed_tokens")
    before = (preempted.value, again.value)
    got = tight.run([Request(f"r{i}", p, 10) for i, p in enumerate(prompts)])
    assert got == want
    assert preempted.value - before[0] >= 1
    assert again.value - before[1] >= 20
    assert tight.kv.window_pages_peak <= 7


def test_spans_and_counters_carry_the_routing_and_the_window(engine):
    names = ("moe_routed_pairs", "moe_experts_hit", "moe_pairs_max_expert")
    counters = [observe.counter("tdx.serve." + n) for n in names]
    before = [c.value for c in counters]
    observe.enable(True)
    try:
        n0 = len(observe.tracer().events)
        _serve(engine, [Request("s", _prompt(95, 40), 8)])
        spans = [e for e in list(observe.tracer().events)[n0:]
                 if e.get("name") == "serve.program"]
    finally:
        observe.enable(False)
    pairs, hit, fullest = (c.value - b for c, b in zip(counters, before))
    assert spans and all(
        {"routed_pairs", "experts_hit", "window_tokens"} <= set(e["args"])
        for e in spans)
    decode = [e["args"] for e in spans if e["args"]["program"] == "decode"]
    # one lane: 2 choices a token and expert layer, of which those on 0-3
    assert all(0 <= a["routed_pairs"] <= 2 * 4 for a in decode)
    assert all(a["window_tokens"] == 16 for a in decode)   # context past 16
    assert all(a["experts_hit"] <= a["routed_pairs"] for a in decode)
    total = sum(e["args"]["routed_pairs"] for e in spans)
    assert pairs == total > 0
    assert hit == sum(e["args"]["experts_hit"] for e in spans)
    assert 0 < fullest <= total


def test_with_telemetry_off_a_chunk_that_brings_no_logits_waits_for_nothing(
        engine):
    """A prompt's earlier chunks fetch nothing (the host goes on to the
    next call while the device runs them); their pairs are counted with
    the next call whose logits come to the host, so the total is the one
    that telemetry, which waits for every call, counts."""
    total = observe.counter("tdx.serve.moe_routed_pairs")
    req = lambda rid: [Request(rid, _prompt(96, 40), 4)]
    fetched = []
    asarray = np.asarray

    class Spy:
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def asarray(a, *args, **kw):
            if getattr(a, "dtype", None) == np.int32 and getattr(
                    a, "shape", None) == engine.state[1].shape:
                fetched.append(1)
            return asarray(a, *args, **kw)

    import torchdistx_tpu.serve.engine as engine_mod

    calls0 = dict(engine.program_calls)
    before = total.value
    engine_mod.np = Spy()
    try:
        quiet = _serve(engine, req("q"), prefill_chunk=8)["q"][0]
    finally:
        engine_mod.np = np
    off = total.value - before
    calls = sum(engine.program_calls.values()) - sum(calls0.values())
    observe.enable(True)
    try:
        before = total.value
        loud = _serve(engine, req("l"), prefill_chunk=8)["l"][0]
        on = total.value - before
    finally:
        observe.enable(False)
    assert quiet == loud and off == on > 0
    # 40 tokens in chunks of 8: four chunks fetch nothing, the fifth and
    # the decode ticks fetch the counts with their logits
    assert len(fetched) == calls - 4


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_the_greedy_choice_on_the_device_is_numpys(dtype):
    from torchdistx_tpu.serve.engine import _greedy

    x = np.random.default_rng(3).standard_normal((5, 257)).astype(np.float32)
    x[1, [7, 200]] = 9.0          # a tie: the first index
    x[2, :] = 0.0                 # all equal: index 0
    x = np.asarray(jnp.asarray(x, dtype))
    got = np.asarray(_greedy(x))
    assert got.dtype == np.int32
    assert got.tolist() == [int(np.argmax(r)) for r in x]
    assert got[1] == 7 and got[2] == 0


# -- what the family refuses --------------------------------------------------


@pytest.mark.parametrize("knobs, word", [
    (dict(spec_decode=True), "spec_decode"),
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(spec_decode=True, prefix_cache=True), "spec_decode and prefix_cache"),
])
def test_speculation_and_the_prefix_cache_are_refused_with_the_reason(
        knobs, word):
    with pytest.raises(ValueError, match="windowed attention layers") as e:
        dataclasses.replace(SCFG, **knobs).resolve(TCFG)
    assert word in str(e.value) and "its first reader" in str(e.value)


def test_make_model_names_what_exists_and_refuses_the_rest():
    with pytest.raises(NotImplementedError, match="afmoe family") as e:
        programs.make_model("llama", TINY_MOE)
    assert "capacity-based" in str(e.value)
    with pytest.raises(ValueError, match="takes a config with cfg.afmoe"):
        programs.make_model("afmoe", TINY)
    with pytest.raises(ValueError, match="takes a config with cfg.afmoe"):
        programs.make_model("llama", TINY_AFMOE)
    with pytest.raises(NotImplementedError, match="no verify-<k> program"):
        programs.build_verify_fn("afmoe", TCFG, SCFG.resolve(TCFG), 2)
    assert "afmoe" in programs.FAMILIES
    assert programs.model_family("tiny-afmoe") == "afmoe"
    names = [s.name for s in programs.serve_program_specs(
        "afmoe", TCFG, SCFG, include_init=False)]
    assert names == ["prefill-8", "prefill-32", "chunk-8", "chunk-32",
                     "decode"]


def test_the_router_is_float32_in_a_bfloat16_program():
    """A bfloat16 program keeps the residual stream, the router's product,
    its scores and the choice in float32 (the weights of the products that
    follow are bfloat16)."""
    tc = dataclasses.replace(TCFG, dtype=jnp.bfloat16)
    spec = {s.name: s for s in programs.serve_program_specs(
        "afmoe", tc, SCFG, param_dtype=jnp.bfloat16,
        include_init=False)}["decode"]
    jaxpr = jax.make_jaxpr(spec.fn)(*spec.args)

    def eqns(j):
        for e in j.eqns:
            yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from eqns(sub)

    tops = [e for e in eqns(jaxpr.jaxpr) if e.primitive.name == "top_k"]
    assert len(tops) == 4 and all(
        e.invars[0].aval.dtype == jnp.float32 for e in tops)
    router_dots = [e for e in eqns(jaxpr.jaxpr)
                   if e.primitive.name == "dot_general"
                   and e.outvars[0].aval.shape == (4, 8)]  # [lanes, experts]
    assert len(router_dots) == 4 and all(
        e.outvars[0].aval.dtype == jnp.float32
        and e.params["precision"] is not None for e in router_dots)
    products = [e for e in eqns(jaxpr.jaxpr)
                if e.primitive.name == "pallas_call"
                and e.params["name"] == "tdx_moe_experts_gmm"]
    assert len(products) == 12 and all(
        e.outvars[0].aval.dtype == jnp.bfloat16 for e in products)


@pytest.mark.parametrize("to_held", [False, True], ids=["few", "all"])
def test_the_products_over_a_share_of_the_rows_and_over_all_agree(to_held):
    """A replica that holds 2 of 16 experts gets about an eighth of the
    (token, choice) pairs, or all of them (here: a selection bias that
    sends every token to the two held experts).  Either way: the
    reference's routed part, nothing dropped."""
    share = dict(FULL, num_attention_heads=2, num_key_value_heads=1,
                 num_experts=2, first_expert=4)
    cs = fam.dims(share)
    tc = fam.transformer_config(share, cs)
    lw = dict(fam.layer_weights(fam.make(cs, 13, jnp.float32), 0))
    if to_held:
        lw["router_bias"] = jnp.zeros((16,)).at[4:6].set(9.0)
    lp = {k.removesuffix(".scale").replace(".", "_"): v for k, v in lw.items()}
    T = 48
    x = jnp.asarray(np.random.default_rng(8).normal(size=(T, 64)), jnp.float32)
    idx, wts = prog.route(tc, lp, x)
    routed, sizes = prog.held_expert_sum(tc, lp, x, idx, wts,
                                         jnp.ones((T,), bool))
    assert (int(sizes.sum()) > 48) == to_held       # 12 of 96 expected
    assert int(sizes.sum()) == (2 * T if to_held else int(
        ((idx == 4) | (idx == 5)).sum()))
    want = ref.routed_part(cs, None, ref.route(cs, None, x, lw)[0], x, lw)
    np.testing.assert_allclose(np.asarray(routed), np.asarray(want), atol=ATOL)
    assert float(np.abs(np.asarray(want)).max()) > 0.1
