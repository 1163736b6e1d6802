"""The olmo_hybrid decode family (Gated DeltaNet layers, three to each
full-attention layer) on the normal serving path, at two periods of the
pattern and width 64 on the CPU, against the benchmark's plain reference
(``benchmark/reference/olmo_hybrid.py``, which imports nothing of the
program) on seeded random weights; its two kernels in interpret mode
against the rule a position at a time; the state group's shapes and
bytes.  One parametrised test a property.

Tolerance: everything here runs in float32 with ``highest`` matmul
precision, so the program and the reference differ only in the order of
float32 sums (the chunk kernel's WY form against the reference's
position-at-a-time rule; the served path splits a sequence into prefill,
chunks and decode ticks).  Logits are of order 1 to 5; 2e-3 absolute is
five times the differences read over 128 positions (at most 4.4e-4, the
WY form's sums) and a hundred times under what a planted fault moves
them by (``tests/benchmark/test_bench_olmo_hybrid.py``).
"""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.families import olmo_hybrid as fam  # noqa: E402
from benchmark.reference import olmo_hybrid as ref  # noqa: E402
from torchdistx_tpu import observe  # noqa: E402
from torchdistx_tpu.models import TINY_OLMO_HYBRID  # noqa: E402
from torchdistx_tpu.models import olmo_hybrid as prog  # noqa: E402
from torchdistx_tpu.ops import gdn  # noqa: E402
from torchdistx_tpu.serve import Request, ServeConfig, programs  # noqa: E402
from torchdistx_tpu.serve.engine import ServeEngine  # noqa: E402

ATOL = 2e-3
TYPES = (["linear_attention"] * 3 + ["full_attention"]) * 2
CFG = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
       "intermediate_size": 128, "num_hidden_layers": 8, "vocab_size": 256,
       "max_position_embeddings": 256, "rms_norm_eps": 1e-6,
       "layer_types": TYPES, "linear_num_key_heads": 2,
       "linear_num_value_heads": 2, "linear_key_head_dim": 16,
       "linear_value_head_dim": 32, "linear_conv_kernel_dim": 4,
       "linear_allow_neg_eigval": True}
C = fam.dims(CFG)
TCFG = dataclasses.replace(fam.transformer_config(CFG, C), dtype=jnp.float32)
SCFG = ServeConfig(max_batch=4, page_size=8, n_pages=96, max_pages_per_seq=16,
                   prefill_buckets=(8, 128), prefix_cache=False,
                   spec_decode=False)
N_NEW = 5


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
    eng = ServeEngine("olmo_hybrid", TCFG, fam.param_tree(weights),
                      serve_cfg=SCFG)
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


@functools.cache
def _forward():
    """One reference for the file: its layer programs compile once a
    padded length, not once a call."""
    return ref.Forward(C, None, 128)


def _reference(weights, prompt, out):
    """Reference logits that predict each served token (full forward over
    the prompt and the served tokens before it)."""
    seq = list(prompt) + list(out[:-1])
    return _forward().logits(weights, seq, len(prompt) - 1, len(out))


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


# -- the two kernels against the rule a position at a time --------------------


# The rule, compiled once a shape (eagerly, each operation around its
# scan would compile on its own).
_recurrence = jax.jit(gdn.gdn_recurrence)


def _rule_inputs(seed, T, H, dk, dv):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    return (unit(f(T, H, dk)) / np.sqrt(dk), unit(f(T, H, dk)), f(T, H, dv),
            2 * jax.nn.sigmoid(f(T, H)), -jnp.exp(f(T, H)) * 0.3,
            f(H, dk, dv))


@pytest.mark.parametrize("S, n_valid", [(8, 8), (8, 3), (40, 40), (40, 29),
                                        (128, 128), (128, 70), (200, 131)])
def test_the_chunk_kernel_is_the_rule(S, n_valid):
    """Chunks of 64 (8, 64 or a power of two between for a short call), a
    resumed state, positions past ``n_valid`` leaving the state alone."""
    q, k, v, beta, g, s0 = _rule_inputs(S + n_valid, S, 3, 16, 32)
    o, s = gdn.gdn_chunk(q, k, v, beta, g, s0, jnp.int32(n_valid),
                         interpret=True)
    want_o, want_s = _recurrence(q[:n_valid], k[:n_valid], v[:n_valid],
                                 beta[:n_valid], g[:n_valid], s0)
    np.testing.assert_allclose(np.asarray(o[:n_valid]), np.asarray(want_o),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("lanes, live", [(4, (1, 0, 1, 1)), (9, (1,) * 9),
                                         (3, (0, 0, 1))])
def test_the_decode_kernel_is_the_rule_in_place(lanes, live):
    """One position a lane through row ``layer`` of the whole state; a
    lane with ``n_valid`` 0 sits the tick out and its state, and every
    other layer's, comes back bit for bit."""
    H, dk, dv, L, layer = 3, 16, 32, 3, 1
    rng = np.random.default_rng(lanes)
    state = jnp.asarray(rng.standard_normal((L, lanes, dk, H * dv)),
                        jnp.float32)
    q, k, v, beta, g, _ = _rule_inputs(lanes, lanes, H, dk, dv)
    nv = jnp.asarray(live, jnp.int32)
    before = np.asarray(state)
    o, after = gdn.gdn_decode_update(state, jnp.int32(layer), q, k, v, beta,
                                     g, nv, interpret=True)
    after = np.asarray(after)
    for b in range(lanes):
        s_b = before[layer, b].reshape(dk, H, dv).transpose(1, 0, 2)
        if not live[b]:
            np.testing.assert_array_equal(after[layer, b], before[layer, b])
            continue
        want_o, want_s = _recurrence(
            q[b:b + 1], k[b:b + 1], v[b:b + 1], beta[b:b + 1], g[b:b + 1],
            jnp.asarray(s_b))
        np.testing.assert_allclose(np.asarray(o[b]), np.asarray(want_o[0]),
                                   atol=1e-5)
        np.testing.assert_allclose(
            after[layer, b],
            np.asarray(want_s).transpose(1, 0, 2).reshape(dk, H * dv),
            atol=1e-5)
    others = [i for i in range(L) if i != layer]
    np.testing.assert_array_equal(after[others], before[others])


# -- the model against the reference ------------------------------------------


@pytest.mark.parametrize("what", ["linear", "model"])
def test_full_forward_equals_the_reference(weights, what):
    toks = _prompt(1, 100)
    p = prog.param_tree(fam.param_tree(weights)["params"])
    if what == "model":
        want = _forward().logits(weights, toks, 0, 100)
        got = jax.jit(lambda t: prog.full_forward(TCFG, p, t))(
            jnp.asarray([toks]))[0]
    else:
        x = jax.random.normal(jax.random.PRNGKey(3), (100, 64), jnp.float32)
        lw = {k: a.astype(jnp.float32)
              for k, a in ref.layer_weights(C, weights, 4).items()}
        want = jax.jit(lambda x, lw: ref.linear_layer(C, None, None, x, lw))(
            x, lw)
        m = jax.tree.map(lambda a: a[3], p["gdn"])
        f = jax.tree.map(lambda a: a[4], p["ffn"])
        mixer = lambda h: prog.gdn_mixer(
            TCFG, m, h, jnp.zeros((1, 16, 64)), jnp.zeros((3, 1, 128)),
            jnp.asarray([100], jnp.int32))[0]
        got = jax.jit(lambda x: prog.block(TCFG, f, x[None], mixer))(x)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)


# -- the served path ----------------------------------------------------------


@pytest.mark.parametrize("n_prompt", [5, 8, 21, 100])
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
def test_the_rule_is_float32_in_a_bfloat16_program(name):
    """The configuration states a float32 state and rule under bfloat16
    weights and activations: the program takes and returns the state as
    float32, and no value of a lane's state shape inside it, the loops'
    carries and the kernels' outputs included, is anything else.  The conv
    tail is of the model's dtype and spans q, k and v's channels."""
    cfg = dataclasses.replace(TCFG, dtype=jnp.bfloat16)
    # three lanes: a lane's rows [3, 16, 64] are then no weight's shape
    # (the attention's output projection is [4, 16, 64])
    spec = {s.name: s for s in programs.serve_program_specs(
        "olmo_hybrid", cfg, dataclasses.replace(SCFG, max_batch=3),
        include_init=False)}[name]
    ssm_in, conv_in = spec.args[3:5]
    closed = jax.make_jaxpr(spec.fn)(*spec.args)
    ssm_out, conv_out = closed.out_avals[3:5]
    assert ssm_in.dtype == ssm_out.dtype == jnp.float32
    assert conv_in.dtype == conv_out.dtype == jnp.bfloat16
    assert ssm_in.shape == ssm_out.shape == (6, 3, 16, 64)
    assert conv_in.shape == (6, 3, 3, 2 * 2 * 16 + 2 * 32)
    state_shaped = [v.aval for v in _values(closed.jaxpr)
                    if v.aval.shape[-3:] in ((3, 16, 64), (1, 16, 64),
                                             (2, 16, 32))]
    assert len(state_shaped) > 2
    assert {str(a.dtype) for a in state_shaped} == {"float32"}


@pytest.mark.parametrize("chunk", [5, 37, 64])
def test_a_prompt_chunked_at_any_boundary_equals_it_unchunked(engine, chunk):
    """State and conv tail are carried from chunk to chunk: chunks of
    ``chunk`` tokens put a boundary after every multiple of it (37: inside
    the kernel's chunk of 64, which the next call starts afresh)."""
    prompt = _prompt(20, 100)
    whole = _serve(engine, [Request("w", prompt, N_NEW)],
                   prefill_chunk=128)["w"]
    before = dict(engine.program_calls)
    parts = _serve(engine, [Request("c", prompt, N_NEW)],
                   prefill_chunk=chunk)["c"]
    n_chunks = sum(v - before.get(k, 0) for k, v in
                   engine.program_calls.items() if k.startswith("chunk-"))
    assert n_chunks == -(-100 // chunk)
    assert parts[0] == whole[0]
    np.testing.assert_allclose(parts[1], whole[1], atol=ATOL)


@pytest.mark.parametrize("n_prompt", [1, 3, 8])
def test_padding_a_bucket_leaves_the_state_alone(engine, n_prompt):
    """A prompt of length n in the bucket of 128 against the same prompt
    in the bucket of 8: positions past n advance neither the state nor
    the conv tail."""
    prompt = _prompt(30 + n_prompt, n_prompt)
    small = _serve(engine, [Request("s", prompt, N_NEW)])["s"]
    big = _serve(engine, [Request("b", prompt, N_NEW)],
                 prefill_buckets=(128,))["b"]
    assert engine.program_calls["prefill-128"] >= 1
    assert big[0] == small[0]
    np.testing.assert_allclose(big[1], small[1], atol=ATOL)


@pytest.mark.parametrize("path", ["prefill", "chunk"])
def test_a_reused_lane_starts_from_zero(engine, path):
    """Lane 0 serves one request, is retired, and is given another while
    its slot holds a planted stale state: the second request's logits are
    those of a fresh engine."""
    knobs = {"prefill_chunk": 128 if path == "prefill" else 4}
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
    stale = (jnp.full(kv.state.ssm_shape(), 0.3, jnp.float32),
             jnp.full(kv.state.conv_shape(), -2.0, jnp.float32))
    zero = tuple(jnp.zeros_like(a) for a in stale)
    toks = np.zeros((1, 8), np.int32)
    toks[0, :5] = _prompt(50, 5)
    rest = (jnp.asarray(toks), jnp.asarray([5], jnp.int32),
            jnp.asarray([[1] + [0] * 15], jnp.int32),
            jnp.asarray([0], jnp.int32))

    def logits(state):
        fn = programs.build_prefill_fn("olmo_hybrid", TCFG, scfg, 8)
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
    np.testing.assert_allclose(
        np.stack(engine.logits_seen["q"][-N_NEW:]), calm[1], atol=ATOL)


@pytest.mark.parametrize("order", [(0, 1, 2, 3), (3, 1, 0, 2)])
def test_lanes_are_independent(engine, order):
    """Permuting which lane a request rides permutes the outputs: each
    request's logits are those it gets alone, whatever the other lanes
    hold (a decode tick advances every lane's state in one kernel)."""
    prompts = [_prompt(70 + i, n) for i, n in enumerate((4, 9, 17, 30))]
    alone = [_serve(engine, [Request(f"solo{i}", p, N_NEW)])[f"solo{i}"]
             for i, p in enumerate(prompts)]
    reqs = [Request(f"r{i}", prompts[i], N_NEW) for i in order]
    got = _serve(engine, reqs)
    assert engine.kv.state_slots_peak == 4
    for i in order:
        assert got[f"r{i}"][0] == alone[i][0]
        np.testing.assert_allclose(got[f"r{i}"][1], alone[i][1], atol=ATOL)


def test_positions_through_the_rule_are_counted(engine):
    """``tdx.serve.gdn_prefill_positions`` / ``gdn_decode_positions``:
    real positions advanced, times the six linear layers."""
    pre = observe.counter("tdx.serve.gdn_prefill_positions").value
    dec = observe.counter("tdx.serve.gdn_decode_positions").value
    _serve(engine, [Request("n1", _prompt(80, 13), N_NEW),
                    Request("n2", _prompt(81, 6), N_NEW)])
    assert observe.counter(
        "tdx.serve.gdn_prefill_positions").value == pre + 6 * (13 + 6)
    # the first token of each comes from its prefill, the rest from ticks
    assert observe.counter(
        "tdx.serve.gdn_decode_positions").value == dec + 6 * 2 * (N_NEW - 1)


# -- what the family refuses and what it is given ------------------------------


@pytest.mark.parametrize("knobs, word", [
    ({"spec_decode": True, "prefix_cache": False}, "spec_decode"),
    ({"spec_decode": False, "prefix_cache": True}, "prefix_cache"),
])
def test_speculation_and_the_prefix_cache_are_refused_with_the_reason(
        knobs, word):
    with pytest.raises(ValueError, match="Gated DeltaNet") as e:
        ServeConfig(**knobs).resolve(TINY_OLMO_HYBRID)
    assert word in str(e.value) and "rolled back" in str(e.value)
    ServeConfig(spec_decode=False, prefix_cache=False).resolve(
        TINY_OLMO_HYBRID)


def test_no_verify_program_and_the_family_is_its_own():
    scfg = SCFG.resolve(TCFG)
    with pytest.raises(NotImplementedError, match="rolled back"):
        programs.build_verify_fn("olmo_hybrid", TCFG, scfg, 2)
    with pytest.raises(ValueError, match="olmo_hybrid"):
        programs.make_model("llama", TCFG)
    with pytest.raises(ValueError, match="Gated DeltaNet"):
        programs.make_model("olmo_hybrid", TCFG.replace(olmo_hybrid=None))
    assert programs.model_family("tiny-olmo-hybrid") == "olmo_hybrid"
    from torchdistx_tpu.parallel import make_mesh

    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="one chip"):
        programs.build_decode_fn("olmo_hybrid", TCFG, scfg, mesh)
    names = [s.name for s in programs.serve_program_specs(
        "olmo_hybrid", TCFG, SCFG, include_init=False)]
    assert names == ["prefill-8", "prefill-128", "chunk-8", "chunk-128",
                     "decode"]


def _cell_state(config):
    import json

    from benchmark import harness

    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    mod = harness.load_module(ROOT, cfg["family_module"])
    tcfg = mod.transformer_config(cfg, mod.dims(cfg))
    return ServeConfig(max_batch=128, page_size=16, n_pages=6400,
                       prefix_cache=False, spec_decode=False).resolve(
        tcfg).kv_config(tcfg).state


@pytest.mark.parametrize("config, ssm, conv", [
    # Jamba's group is what it was before the conv's channels became their
    # own field: 26 Mamba layers, [16, 5120] a lane, a conv over 5120
    ("jamba2-3b", (26, 128, 16, 5120), (26, 3, 128, 5120)),
    # a delta-rule layer: [96, 30 x 192] a lane, a conv over 2 x 2880 + 5760
    ("olmo-hybrid-7b-d8", (6, 128, 96, 5760), (6, 3, 128, 11520)),
])
def test_the_state_group_at_the_cells_widths(config, ssm, conv):
    st = _cell_state(config)
    assert st.ssm_shape() == ssm and st.conv_shape() == conv


def test_the_state_is_its_logical_size(engine):
    """No padding, no second copy: the engine's state arrays hold the
    layers' rules and tails, nothing else (what ``serve.spin_up.pools``
    reports as ``state_bytes``), and at the cell's widths the rows are
    tile-aligned (96 rows of 5,760 = 45 x 128 float32 lanes; 128 lanes of
    11,520 bfloat16 channels), so the chip holds no more."""
    lanes, L, H, dk, dv = 4, 6, 2, 16, 32
    want = L * lanes * (dk * H * dv * 4 + 3 * (2 * H * dk + H * dv) * 4)
    assert sum(a.nbytes for a in engine.state) == want
    st = _cell_state("olmo-hybrid-7b-d8")
    assert st.ssm_shape()[-1] % 128 == 0 and st.ssm_shape()[-2] % 8 == 0
    assert st.conv_shape()[-1] % 128 == 0 and st.conv_shape()[-2] % 16 == 0
    ssm_bytes = np.prod(st.ssm_shape()) * 4
    assert ssm_bytes == 6 * 128 * 30 * 96 * 192 * 4 == 1_698_693_120
