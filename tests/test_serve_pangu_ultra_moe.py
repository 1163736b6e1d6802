"""The ``pangu_ultra_moe`` family (openPangu-Ultra-MoE: latent attention,
MLA, over sigmoid-routed experts with a shared one) through the serving
runtime, on the CPU at a size a test can hold, against the benchmark's
plain reference (``benchmark/reference/pangu_ultra_moe.py``: float32,
``highest``, no cache, no kernel, the expanded form only) on the
benchmark's seeded weights: prefill, decode through the
latent cache (the absorbed form) and chunked prefill (the expanded form
over cached latents); the two latent kernels against their oracles; the
four shares of an expert layer adding up to the uncut reference layer;
the refusals, the counters and the empty value pool.

Tolerances: everything here is float32 (program and reference), so the two
differ by the order of float32 sums alone, 1e-5 of logits of order 1, and
the absorbed form reassociates the same products; ``ATOL`` leaves that ten
times of room.  A wrong rotary slice, norm or value half moves a logit by
0.1 and more (``tests/benchmark/test_bench_pangu_ultra_moe.py`` plants
them)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.families import pangu_ultra_moe as fam  # noqa: E402
from benchmark.reference import afmoe as ref_moe  # noqa: E402
from benchmark.reference import pangu_ultra_moe as ref  # noqa: E402
from torchdistx_tpu import observe  # noqa: E402
from torchdistx_tpu.models import TINY_PANGU_ULTRA_MOE  # noqa: E402
from torchdistx_tpu.models import afmoe as prog  # noqa: E402
from torchdistx_tpu.ops import mla as mla_ops  # noqa: E402
from torchdistx_tpu.serve import Request, ServeConfig, programs  # noqa: E402
from torchdistx_tpu.serve.engine import ServeEngine  # noqa: E402

ATOL = 1e-4
CFG = {"hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
       "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
       "v_head_dim": 8, "intermediate_size": 128,
       "moe_intermediate_size": 32, "num_hidden_layers": 2,
       "first_k_dense_replace": 1, "n_routed_experts": 4,
       "router_outputs": 8, "first_expert": 0, "num_experts_per_tok": 2,
       "routed_scaling_factor": 2.5, "vocab_size": 256,
       "max_position_embeddings": 256, "rope_theta": 10000,
       "rms_norm_eps": 1e-5, "activation_dtype": "float32"}
C = fam.dims(CFG)
TCFG = fam.transformer_config(CFG, C)
# Chunks of 8 in a table of 96 rows: a chunk rebuilds the latents of the
# first 8, 16, 32 or all 96 rows, whichever first holds its context.
SCFG = ServeConfig(max_batch=4, page_size=8, n_pages=64, max_pages_per_seq=12,
                   prefill_buckets=(8,), prefill_chunk=8,
                   prefix_cache=False, spec_decode=False)


@pytest.fixture(scope="module", autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def weights():
    return fam.make(C, 7, jnp.float32)


@pytest.fixture(scope="module")
def reference():
    """One reference for the file: its two layer programs compile once."""
    return ref.Forward(C, None, 64)


@pytest.fixture(scope="module")
def engine(weights):
    """One replica for the whole file: every case leaves it idle, and a
    program compiles once."""
    return ServeEngine("pangu_ultra_moe", TCFG, fam.param_tree(weights),
                       serve_cfg=SCFG)


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 256, n)]




def test_the_configuration_is_the_tiny_preset():
    assert TCFG.mla == TINY_PANGU_ULTRA_MOE.mla
    assert TCFG.experts == TINY_PANGU_ULTRA_MOE.experts
    assert programs.model_family("tiny-pangu-ultra-moe") == "pangu_ultra_moe"


@pytest.mark.parametrize("n_prompt", [5, 70])
def test_prefill_decode_and_chunks_through_the_latent_cache(
        engine, weights, reference, n_prompt):
    """5: one prefill, then decode through the cached latents (absorbed);
    70: nine chunks, each expanded over the latents the earlier wrote
    (rebuilt as far as 8, 16, 32 and 96 rows), then decode.  Logits of
    every served token against the reference."""
    decoded = observe.counter("tdx.serve.mla_decode_tokens")
    pairs = observe.counter("tdx.serve.mla_prefill_pairs")
    d0, p0 = decoded.value, pairs.value
    prompt = _prompt(20 + n_prompt, n_prompt)
    seen = []
    emit = engine._emit
    engine._emit = lambda lane, tok, logits: (
        seen.append(np.array(logits, np.float32)), emit(lane, tok, logits))[1]
    try:
        toks = engine.run([Request("a", prompt, 12)])["a"]
    finally:
        engine._emit = emit
    want = reference.logits(weights, prompt + toks[:-1], n_prompt - 1,
                            len(toks))
    np.testing.assert_allclose(np.stack(seen), want, atol=ATOL)
    assert float(np.std(want)) > 0.5            # logits of order 1, not 0
    # every causal pair of the prompt, in both layers; a decode tick reads
    # the lane's whole context in both
    assert pairs.value - p0 == 2 * n_prompt * (n_prompt + 1) // 2
    assert decoded.value - d0 == 2 * sum(range(n_prompt + 1, n_prompt + 12))
    assert engine.v_pages.size == 0 and engine.v_pages.shape[1] == 0
    assert engine.k_pages.shape == (2, 64, 1, 8, 128)


def test_lanes_are_independent(engine):
    reqs = [Request(f"l{i}", _prompt(70 + i, n), 6 + i)
            for i, n in enumerate((4, 45, 19))]
    alone = {r.rid: engine.run([r])[r.rid] for r in reqs}
    together = engine.run(reqs[::-1])
    assert {rid: together[rid] for rid in alone} == alone


# -- the two kernels against their oracles -----------------------------------

# The oracles compiled once a shape: eagerly, each operation would compile
# on its own.
_decode_reference = jax.jit(mla_ops.mla_decode_reference,
                            static_argnames="v_width")
_prefill_reference = jax.jit(mla_ops.mla_prefill_reference)


@pytest.mark.parametrize("block_tokens", [8, 512])
def test_the_decode_kernel_equals_its_oracle(monkeypatch, block_tokens):
    """A lane that ends mid-page, an idle lane, one past several blocks of
    the walk (8 tokens a block: a page of 4, two pages a block)."""
    monkeypatch.setattr(mla_ops, "_BLOCK_TOKENS", block_tokens)
    rng = np.random.default_rng(0)
    B, H, W, R, page, P, maxp = 3, 8, 128, 16, 4, 20, 6
    pool = jnp.asarray(rng.normal(size=(P, 1, page, W)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, H, W)), jnp.float32)
    table = jnp.asarray(rng.permutation(np.arange(1, P))[:B * maxp]
                        .reshape(B, maxp), jnp.int32)
    lengths = jnp.asarray([7, 0, 22], jnp.int32)
    got = mla_ops.mla_decode(q, pool, lengths, table, v_width=R, scale=0.3)
    want = _decode_reference(q, pool, lengths, table, v_width=R, scale=0.3)
    np.testing.assert_allclose(np.asarray(got)[[0, 2]],
                               np.asarray(want)[[0, 2]], atol=1e-5)
    assert not np.asarray(got[1]).any()                   # idle: a zero row


@pytest.mark.parametrize("start, end", [(0, 13), (16, 29), (40, 48)])
def test_the_prefill_kernel_equals_its_oracle(monkeypatch, start, end):
    """Queries at ``start + i`` over keys below ``end``, in blocks of 8
    queries and 8 keys: the causal edge inside a block, padding past the
    end, key blocks skipped."""
    monkeypatch.setattr(mla_ops, "_PREFILL_BLOCK", 8)
    rng = np.random.default_rng(1)
    H, S, T, dn, dr, dv = 4, 16, 48, 8, 4, 8
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    args = (f(H, S, dn), f(H, S, dr), f(H, T, dn), f(H, T, dv), f(T, dr))
    got = mla_ops.mla_prefill(*args, start, end, scale=0.2)
    want = _prefill_reference(*args, start, end, scale=0.2)
    n = end - start
    np.testing.assert_allclose(np.asarray(got)[:, :n],
                               np.asarray(want)[:, :n], atol=1e-5)


def test_the_absorbed_form_is_the_expanded_one(weights):
    """A token's decode-form score against a cached row, and its output,
    are the expanded form's (models/mla.py)."""
    from torchdistx_tpu.models import mla

    lp = prog.param_tree(fam.param_tree(weights)["params"])["layers"][1]
    h = jnp.asarray(np.random.default_rng(2).normal(size=(1, 9, 64)),
                    jnp.float32)

    @jax.jit
    def both(lp, h):
        angles = mla.rope_angles(TCFG, jnp.arange(9, dtype=jnp.int32)[None])
        q_nope, q_rope, c_kv, k_rope = mla.project(TCFG, lp, h, angles)
        k_nope, v = mla.expand(TCFG, lp, c_kv)
        want = mla.dense_attention(TCFG, q_nope, q_rope, k_nope, k_rope, v,
                                   jnp.arange(9)[None], jnp.asarray([9]))
        rows = mla.cache_rows(TCFG, c_kv, k_rope)[0, :, 0]  # [9, 128]
        q = mla.decode_query(TCFG, lp, q_nope[:, -1], q_rope[:, -1])[0]
        p = jax.nn.softmax(q @ rows.T * mla.scale(TCFG), -1)
        return mla.unabsorb(TCFG, lp, (p @ rows[:, :16])[None])[0], want[0, -1]

    got, want = both(lp, h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# -- the share test -----------------------------------------------------------


def test_the_four_shares_add_up_to_the_uncut_reference_layer(weights):
    """The PROGRAM's routed part of each of four shares (two of the eight
    experts each, ``models/afmoe.py`` at the share's sizes, as this family
    calls it), plus the shared expert counted once, against the
    REFERENCE's uncut expert layer: what the exchange would add up.  The
    uncut layer is the file's layer 1 with the four experts it does not
    hold drawn beside its own."""
    full = dict(CFG, n_routed_experts=8)
    cf = fam.dims(full)
    rng = np.random.default_rng(11)
    w = dict(weights)
    for n in ("experts.w_gate", "experts.w_up", "experts.w_down"):
        held = w[f"layers.1.{n}"]
        w[f"layers.1.{n}"] = jnp.concatenate([held, jnp.asarray(
            rng.normal(scale=0.2, size=held.shape), jnp.float32)])
    lw = {k[len("layers.1."):]: v for k, v in w.items()
          if k.startswith("layers.1.")}
    T = 40
    x = jnp.asarray(np.random.default_rng(3).normal(size=(T, 64)),
                    jnp.float32)
    weights_full, _ = ref_moe.route(cf, None, x, lw)
    want = (ref_moe.shared_part(None, x, lw)
            + ref_moe.routed_part(cf, None, weights_full, x, lw))
    got, pairs = jnp.zeros_like(want), 0
    for s in range(4):
        share = dict(full, n_routed_experts=2, first_expert=2 * s)
        tc = fam.transformer_config(share, fam.dims(share))
        lp = dict(prog.param_tree(fam.param_tree(w)["params"])["layers"][1])
        for n in ("experts_w_gate", "experts_w_up", "experts_w_down"):
            lp[n] = lp[n][2 * s:2 * s + 2]
        idx, wts = prog.route(tc, lp, x)
        routed, sizes = prog.held_expert_sum(tc, lp, x, idx, wts,
                                             jnp.ones((T,), bool))
        got += routed
        pairs += int(sizes.sum())
    assert pairs == T * 2                  # every choice landed on one share
    shared = prog.gated_mlp(tc, lp["shared_w_gate"], lp["shared_w_up"],
                            lp["shared_w_down"], x)
    np.testing.assert_allclose(np.asarray(shared + got), np.asarray(want),
                               atol=ATOL)


# -- refusals -----------------------------------------------------------------


def test_speculation_prefix_sharing_a_mesh_and_verify_are_refused():
    for knobs in ({"spec_decode": True, "prefix_cache": False},
                  {"spec_decode": False, "prefix_cache": True}):
        with pytest.raises(ValueError, match="latent attention"):
            ServeConfig(max_batch=2, n_pages=16, **knobs).resolve(TCFG)
    scfg = SCFG.resolve(TCFG)
    with pytest.raises(NotImplementedError, match="verify-<k>"):
        programs.build_verify_fn("pangu_ultra_moe", TCFG, scfg, 2)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(NotImplementedError, match="exchange"):
        programs.build_decode_fn("pangu_ultra_moe", TCFG, scfg, mesh)
    with pytest.raises(ValueError, match="latent attention"):
        programs.make_model("llama", TCFG)
    kv = scfg.kv_config(TCFG)
    assert kv.latent and kv.pool_shape() == (2, 64, 1, 8, 128)
    assert kv.v_pool_shape() == (2, 0, 1, 8, 128)
    names = [s.name for s in programs.serve_program_specs(
        "pangu_ultra_moe", TCFG, SCFG, include_init=False)]
    assert names == ["prefill-8", "chunk-8", "decode"]
