"""The ``openpangu-ultra-moe-718b-ep16-d5`` configuration's own benchmark
files, on the CPU at a size a test can hold: ``correct`` has to come out
false for the faults latent attention can have (rope on the nope part,
the kv latent's norm skipped, the latent cached in fp8, a scale of
1/sqrt(128), values taken from the k_nope half, a post-norm dropped) and
true for the sound program, whose fp8 control reads over the limit;
``rooflines/mla.py`` against hand counts; the three new readers on a
made-up ``ctx``, and silent without one; the configuration's file against
the catalog's row; the cell's traffic.

The seven comparisons share one compile cache (a directory of this
module's own): the weights, the reference and every program a fault
leaves as it was compile once."""

import json
import os
import sys

import numpy as np
import pytest

from bench_util import ROOT, rehearse

sys.path.insert(0, ROOT)

CELL = "pangu-ultra-moe-long-doc-backlog"
CONFIG = "benchmark/configs/openpangu-ultra-moe-718b-ep16-d5.json"
# The catalog's row (openPangu-Ultra-MoE-718B, its ``config``), as the
# configuration file must hold it but for ``reduced``.
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu",
    "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
    "moe_intermediate_size": 2048, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_theta": 25600000,
    "routed_scaling_factor": 2.5, "sandwich_norm": True,
    "tie_word_embeddings": False, "v_head_dim": 128, "vocab_size": 153600,
}


def _config():
    with open(os.path.join(ROOT, CONFIG)) as f:
        return json.load(f)


@pytest.fixture(scope="module", autouse=True)
def _one_cache(tmp_path_factory):
    from torchdistx_tpu import compile_service

    old = os.environ.get("TDX_CACHE_DIR")
    os.environ["TDX_CACHE_DIR"] = str(tmp_path_factory.mktemp("jax_cache"))
    compile_service.reset_cache_binding()
    yield
    os.environ["TDX_CACHE_DIR"] = old if old is not None else ""
    compile_service.reset_cache_binding()


# -- planted faults -------------------------------------------------------------


def _patch(monkeypatch, module, name, change):
    real = getattr(module, name)
    monkeypatch.setattr(module, name, lambda *a, **kw: change(real, *a, **kw))


def _rope_on_nope(monkeypatch):
    """The rotary term applied to the query's nope part as well."""
    from torchdistx_tpu.models import mla
    from torchdistx_tpu.models.layers import apply_rope

    def change(real, cfg, lp, h, angles):
        q_nope, q_rope, c_kv, k_rope = real(cfg, lp, h, angles)
        d = cfg.mla.rope_dim
        rot = apply_rope(q_nope[..., :d], angles)
        return (q_nope.at[..., :d].set(rot), q_rope, c_kv, k_rope)

    _patch(monkeypatch, mla, "project", change)


def _kv_norm_skipped(monkeypatch):
    """The kv latent cached (and expanded) without its RMSNorm."""
    import jax.numpy as jnp

    from torchdistx_tpu.models import mla

    def change(real, cfg, lp, h, angles):
        lp = dict(lp, kv_a_norm=jnp.ones_like(lp["kv_a_norm"]))
        q_nope, q_rope, _, k_rope = real(cfg, lp, h, angles)
        kv = jnp.einsum("bsd,dr->bsr", h.astype(cfg.dtype),
                        lp["wkv_a"].astype(cfg.dtype))
        return q_nope, q_rope, kv[..., :cfg.mla.kv_rank], k_rope

    _patch(monkeypatch, mla, "project", change)


def _latent_in_fp8(monkeypatch):
    """The cached rows rounded to float8 e4m3 (per-tensor scale)."""
    import jax.numpy as jnp

    from benchmark.reference.afmoe import fp8
    from torchdistx_tpu.models import mla

    _patch(monkeypatch, mla, "cache_rows",
           lambda real, *a: fp8(real(*a)).astype(jnp.float32))


def _scale_of_128(monkeypatch):
    """Scores scaled by 1/sqrt(nope_dim) and not 1/sqrt(nope + rope)."""
    import math

    from torchdistx_tpu.models import mla

    monkeypatch.setattr(mla, "scale",
                        lambda cfg: 1.0 / math.sqrt(cfg.mla.nope_dim))


def _values_from_k_nope(monkeypatch):
    """W_uv taken from the k_nope half of W_kvb, in both forms."""
    from torchdistx_tpu.models import mla

    def swapped(lp, cfg):
        w = lp["wkv_b"]
        return dict(lp, wkv_b=w.at[..., cfg.mla.nope_dim:].set(
            w[..., :cfg.mla.v_dim]))

    _patch(monkeypatch, mla, "expand",
           lambda real, cfg, lp, *a, **kw: real(cfg, swapped(lp, cfg), *a,
                                                **kw))
    _patch(monkeypatch, mla, "unabsorb",
           lambda real, cfg, lp, o: real(cfg, swapped(lp, cfg), o))


def _post_norm_dropped(monkeypatch):
    """The post-attention norm left out: the attention branch is added to
    the residual stream as it came."""
    import jax.numpy as jnp

    from torchdistx_tpu.models import afmoe

    def block(cfg, lp, i, x, valid, attention):
        eps = cfg.norm_eps
        x = x + attention(afmoe.rms_norm(x, lp["norm_in"], eps, cfg.dtype)
                          ).astype(jnp.float32)
        h = afmoe.rms_norm(x, lp["norm_pre_mlp"], eps,
                           cfg.dtype if afmoe.is_dense(cfg, i)
                           else jnp.float32)
        y, pairs = afmoe.ffn(cfg, lp, i, h, valid)
        return x + afmoe.rms_norm(y, lp["norm_post_mlp"], eps, x.dtype), pairs

    monkeypatch.setattr(afmoe, "block", block)


FAULTS = [_rope_on_nope, _kv_norm_skipped, _latent_in_fp8, _scale_of_128,
          _values_from_k_nope, _post_norm_dropped]


def _correct(seed=2**31 + 41):
    """``correct``'s comparison at rehearsal size, as the cell's runner
    makes it (``kinds/serve_hybrid.py``: the family module's config,
    weights and ``check``, the mix's rehearsal requests), on a replica
    built straight from the weights: the rehearsal's bring-up, warm-up and
    window loop are ``tests/benchmark/test_bench_rehearse.py``'s, and a
    planted fault changes none of them.  Returns ``check``'s
    ``logit_gap``."""
    import jax.numpy as jnp

    from benchmark import adapters, traffic
    from benchmark.families import pangu_ultra_moe as fam
    from torchdistx_tpu.serve import Request
    from torchdistx_tpu.serve.engine import ServeEngine

    cfg = _config()
    cfg.update(cfg["rehearsal"])
    with open(os.path.join(ROOT, "benchmark/traffic/long-doc-backlog.json")) as f:
        mix = json.load(f)
    mix.update(mix["rehearsal"])
    c = fam.dims(cfg)
    w = fam.make(c, seed, jnp.bfloat16)
    eng = ServeEngine("pangu_ultra_moe", fam.transformer_config(cfg, c),
                      fam.param_tree(w), param_dtype=jnp.bfloat16,
                      serve_cfg=adapters.serve_config(cfg, mix["engine"]))
    plan = traffic.serving(mix, seed, 1.0, c["vocab_size"])
    out = eng.run([Request(r["rid"], r["tokens"], r["max_new_tokens"])
                   for r in plan])
    finished = [dict(r, tokens_out=out[r["rid"]], n=len(out[r["rid"]]))
                for r in plan]
    env = {"root": ROOT, "cell": {"name": CELL}, "seed": seed, "control": "",
           "extra_notes": {}, "rehearse": True}
    checks = fam.check(env, c, w, finished)
    assert checks["tokens_compared"]["ok"]
    return checks["logit_gap"]


@pytest.mark.parametrize("plant", [None] + FAULTS,
                         ids=["sound"] + [f.__name__[1:] for f in FAULTS])
def test_a_broken_latent_attention_is_not_correct(monkeypatch, plant):
    if plant is not None:
        plant(monkeypatch)
    gap = _correct()
    if plant is None:
        assert gap["ok"] and gap["value"] == 0.0  # float32 operands here
    else:
        assert not gap["ok"] and gap["value"] > gap["limit"]


def test_the_fp8_control_is_not_correct():
    """The reference in fp8 (per-tensor e4m3 on both operands of every
    weight product) against itself in float32, at rehearsal size: over
    the rehearsal's limit, as the chip's control is over the cell's."""
    import jax.numpy as jnp

    from benchmark import check_serve
    from benchmark.families import pangu_ultra_moe as fam
    from benchmark.reference import pangu_ultra_moe as ref

    cfg = _config()
    cfg.update(cfg["rehearsal"])
    c = fam.dims(cfg)
    w = fam.make(c, 3, jnp.bfloat16)
    toks = [int(t) for t in np.random.default_rng(3).integers(0, 512, 60)]
    sound = ref.Forward(c, None, 64).logits(w, toks, 0, 60)
    low = ref.Forward(c, ref.QUANT["fp8"], 64).logits(w, toks, 0, 60)
    gap = float(check_serve.gaps(sound, low.argmax(-1)).max())
    with open(os.path.join(ROOT, f"benchmark/limits/{CELL}.json")) as f:
        assert gap > json.load(f)["rehearsal"]["logit_gap"]


# -- FLOPs and bytes by hand ---------------------------------------------------


def test_the_two_kernels_needs_by_hand():
    from benchmark.families import pangu_ultra_moe as fam
    from benchmark.rooflines import mla

    c = fam.dims(_config())
    # 2 x 128 x (576 + 512) a row; 1,152 B a row; q and out a lane
    d = mla.decode_needs(c, rows=1000, lane_layers=10)
    assert d["flops"] == 2 * 128 * (576 + 512) * 1000
    assert d["bytes"] == 1152 * 1000 + 10 * 128 * (576 + 512) * 2
    assert d["flops"] / (1152 * 1000) == pytest.approx(241.8, abs=0.1)
    p = mla.prefill_needs(c, pairs=5000, rows=300)
    assert p["flops"] == 2 * 128 * (192 + 128) * 5000
    assert p["bytes"] == 1152 * 300


def test_the_cut_by_hand():
    """The cut's arithmetic: 196.6 M an MLA block, 424.7 M the dense MLP,
    47.2 M an expert, 2.0 M the router, 294.9 M of embedding and head;
    4.92 B in all."""
    from benchmark.families import pangu_ultra_moe as fam

    c = fam.dims(_config())
    shapes = fam.layer_shapes(c, 1)
    size = lambda names: sum(int(np.prod(shapes[n][0])) for n in names)
    mla = size(["wq_a", "q_a_norm.scale", "wq_b", "wkv_a", "kv_a_norm.scale",
                "wkv_b", "wo"])
    assert mla == pytest.approx(196.6e6, rel=1e-3)
    assert 3 * 7680 * 18432 == pytest.approx(424.7e6, rel=1e-3)
    assert 3 * 7680 * 2048 == pytest.approx(47.2e6, rel=1e-3)
    assert size(["router", "router_bias"]) == pytest.approx(2.0e6, rel=2e-2)
    assert 2 * 19200 * 7680 == pytest.approx(294.9e6, rel=1e-3)
    assert fam.n_params(c) == pytest.approx(4.92e9, rel=1e-3)
    assert shapes["experts.w_up"][0] == (16, 7680, 2048)


# -- the readers ------------------------------------------------------------------


def _span(program, t, **args):
    from torchdistx_tpu.observe import spans

    return {"name": "serve.program", "cat": "serve", "ph": "X",
            "ts": spans.from_perf_counter(t), "dur": 1000.0,
            "args": dict(program=program, **args)}


def test_the_new_readers_on_a_made_up_ctx(monkeypatch):
    from benchmark import harness
    from benchmark.families import pangu_ultra_moe as fam
    from benchmark.rooflines import mla
    from torchdistx_tpu import observe

    class Clock:
        t0, setup_s = 100.0, 1.0

    class Tracer:
        events = [
            _span("decode", 102.0, attended_tokens=200_000,
                  mla_attended=1_000_000),
            _span("chunk-2048", 102.5, attended_tokens=6000,
                  mla_pairs=5 * 10_000_000),
            _span("decode", 103.0, attended_tokens=210_000,
                  mla_attended=1_050_000),
            _span("decode", 109.0, attended_tokens=1, mla_attended=5),
        ]

    monkeypatch.setattr(observe, "tracer", lambda: Tracer)
    c = fam.dims(_config())
    peaks = json.load(open(os.path.join(
        ROOT, "benchmark", "peaks.json")))["TPU v5 lite"]
    steps = [{"i": i, "t0": 101.5 + i, "t1": 102.9 + i, "calls": {},
              "decode_lanes": 64} for i in range(3)]
    ctx = {"c": c, "clock": Clock, "steps": steps, "traced_steps": steps[:2],
           "peaks": peaks, "trace": {"busy_s": 0.2, "ops": {
               "tdx_mla_decode.3_custom-call:tpu_custom_call_bf16_": 0.020,
               "tdx_mla_prefill.1_custom-call:tpu_custom_call_bf16_": 0.060,
               "fusion.12_fusion_bf16_128_3072_": 0.02}}}

    def read(name):
        return harness.load_module(
            ROOT, f"benchmark/metrics/{name}.py").read(ctx)

    dec, _ = mla.least_seconds(mla.decode_needs(
        c, 2_050_000, 128 * 5), peaks)
    assert read("mla_decode_roofline") == pytest.approx(100 * dec / 0.020)
    pre, _ = mla.least_seconds(mla.prefill_needs(c, 50_000_000, 6000 * 5),
                               peaks)
    assert read("mla_prefill_roofline") == pytest.approx(100 * pre / 0.060)
    assert read("mla.device_share") == pytest.approx(100 * 0.08 / 0.2)
    for name in ("mla_decode_roofline", "mla_prefill_roofline"):
        assert 0 < read(name) < 100


def test_the_readers_leave_their_metric_out_where_there_is_nothing_to_read():
    """A parent commit's program: no such span argument or event."""
    from benchmark import harness
    from torchdistx_tpu import observe

    observe.reset()
    ctx = {"trace": None, "peaks": None, "traced_steps": [], "steps": [],
           "c": {}}
    names = ("mla_decode_roofline", "mla_prefill_roofline", "mla.device_share")
    for name in names:
        mod = harness.load_module(ROOT, f"benchmark/metrics/{name}.py")
        assert mod.read(ctx) is None, name
    ctx["trace"] = {"busy_s": 1.0, "ops": {"fusion.1_fusion_f32_8_": 0.5}}
    for name in names:
        mod = harness.load_module(ROOT, f"benchmark/metrics/{name}.py")
        assert mod.read(ctx) is None, name


# -- the configuration's file and the traffic ----------------------------------


def test_the_configuration_holds_the_catalogs_row_but_for_five_cuts():
    cfg = _config()
    differs = sorted(k for k, v in PUBLISHED.items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == sorted([
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"])
    assert {k: PUBLISHED[k] for k in cfg["published"]} == cfg["published"]
    assert cfg["n_routed_experts"] * 16 == cfg["router_outputs"] == 256
    assert cfg["vocab_size"] * 8 == 153600
    assert cfg["published_layers"] == [0, 3, 4, 5, 6]
    for key in ("router", "groups", "rotary", "latent_norms",
                "attention_scale", "sandwich_norm", "embedding", "weights"):
        assert key in cfg["assumed"], key
    assert cfg["kind"] == "serve_hybrid" and cfg["family"] == "pangu_ultra_moe"
    sc = cfg["serve_config"]
    assert (sc["max_batch"], sc["page_size"], sc["n_pages"]) == (64, 16, 32769)
    assert sc["spec_decode"] is False and sc["prefix_cache"] is False
    for key in ("family_module", "reference"):
        assert os.path.exists(os.path.join(ROOT, cfg[key]))


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(
        ROOT, "benchmark/reference/pangu_ultra_moe.py")).read()
    assert "torchdistx_tpu" not in src.split('"""', 2)[2]
    assert "families" not in src.split('"""', 2)[2]


def test_the_long_doc_backlog_is_the_cells_traffic():
    from benchmark import traffic

    mix = json.load(open(os.path.join(
        ROOT, "benchmark/traffic/long-doc-backlog.json")))
    assert (mix["mode"], mix["rate_per_s"]) == ("backlog", 25.0)
    assert mix["engine"] == {"prefill_buckets": [512, 1024, 2048],
                             "prefill_chunk": 2048, "max_pages_per_seq": 1088}
    plan = traffic.serving(mix, 2**31 + 7, 40.0, 19200)
    assert len(plan) == 1000 and all(r["due_s"] == 0.0 for r in plan)
    lens = np.asarray([len(r["tokens"]) for r in plan])
    outs = np.asarray([r["max_new_tokens"] for r in plan])
    assert lens.min() >= 1024 and lens.max() <= 16384
    assert (lens + outs).max() <= 17408 == 1088 * 16 and outs.min() >= 32
    assert 3800 < np.median(lens) < 4400 and 230 < np.median(outs) < 280
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert CELL in e2e["tpot_p50_s"]["workloads"]
    assert CELL not in e2e["out_tok_s"]["workloads"]
    listed = [p for p in m["per_layer"] if CELL in p.get("workloads", [])]
    assert {p["moves"] for p in listed} == {"tpot_p50_s"}
    mine = [p["name"] for p in listed if p["workloads"] == [CELL]]
    assert mine == ["mla_decode_roofline", "mla_prefill_roofline",
                    "mla.device_share"]
