"""The ``trinity-large-preview-tp8-d5`` configuration's own benchmark
files, on the CPU at a size a test can hold: the control has to come out
as not correct; ``correct`` has to come out false for the faults this
model can have (a router run in bfloat16, a held expert zeroed, a window
layer that attends the whole context); ``rooflines/moe.py`` against hand
counts; the new readers on a made-up ``ctx``; the configuration's file
against the catalog's row; the cell's traffic."""

import json
import os
import sys

import numpy as np
import pytest

from bench_util import ROOT, rehearse

sys.path.insert(0, ROOT)

CELL = "trinity-large-mixed-queue"
# Rehearsal-size readings (CPU, PR 34; float32 operands, so the program
# and the reference differ by float32 sums alone): the sound program 0.0
# on every seed tried; the planted faults 0.26 and more (below); the fp8
# reference 1.9.  The cell's rehearsal limit lies between.
TINY_LOGIT_GAP_LIMIT = 0.01


def _config():
    with open(os.path.join(
            ROOT, "benchmark/configs/trinity-large-preview-tp8-d5.json")) as f:
        return json.load(f)


def _tiny():
    cfg = _config()
    cfg.update(cfg["rehearsal"])
    return cfg


# -- the control --------------------------------------------------------------


@pytest.mark.parametrize("seed", [1, 2])
def test_serving_control_fails(seed):
    import jax.numpy as jnp

    from benchmark import check_serve
    from benchmark.families import afmoe as fam
    from benchmark.reference import afmoe

    c = fam.dims(_tiny())
    w = fam.make(c, seed, jnp.bfloat16)
    toks = [int(t) for t in np.random.default_rng(seed).integers(0, 512, 200)]
    logits = {q: afmoe.Forward(c, afmoe.QUANT[q], 256).logits(w, toks, 0, 200)
              for q in (None, "fp8")}
    again = afmoe.Forward(c, None, 256).logits(w, toks, 0, 200)
    gap = float(check_serve.gaps(logits[None], logits["fp8"].argmax(-1)).max())
    same = float(check_serve.gaps(logits[None], again.argmax(-1)).max())
    assert same == 0.0 <= TINY_LOGIT_GAP_LIMIT < gap, gap


def test_the_weights_are_the_seeds_and_count_what_the_issue_counted():
    import jax.numpy as jnp

    from benchmark.families import afmoe as fam

    c = fam.dims(_tiny())
    w = fam.make(c, 2**31 + 5, jnp.float32)
    again = fam.make(c, 2**31 + 5, jnp.float32)
    assert all(bool(jnp.array_equal(w[k], again[k])) for k in w)
    assert set(w) == set(fam.shapes(c))
    assert w["layers.3.experts.w_up"].shape == (4, 64, 32)
    assert w["layers.1.router"].shape == (64, 8)      # all router outputs
    assert "layers.0.router" not in w and "layers.0.w_gate" in w
    assert 0.005 < float(jnp.std(w["layers.2.router_bias"])) < 0.02
    full = fam.dims(_config())
    # ISSUE 34's count: 4.05 B parameters, an expert 28.31 M
    assert fam.n_params(full) == pytest.approx(4.05e9, rel=0.002)
    assert 3 * 3072 * 3072 == pytest.approx(28.31e6, rel=0.001)
    tree = fam.param_tree(w)["params"]
    assert tree["l3_experts_w_up"] is w["layers.3.experts.w_up"]
    assert tree["l4_norm_post_mlp"] is w["layers.4.norm_post_mlp.scale"]
    assert tree["embedding"] is w["embed"]


# -- planted faults ------------------------------------------------------------


def _bf16_router(monkeypatch):
    """The router in the activation dtype of a bfloat16 program, as every
    other product of the program is: bfloat16 operands, scores and
    choice."""
    import jax
    import jax.numpy as jnp

    from torchdistx_tpu.models import afmoe

    def route(cfg, lp, x):
        a = cfg.afmoe
        bf = jnp.bfloat16
        s = jax.nn.sigmoid(jnp.dot(x.astype(bf), lp["router"].astype(bf)))
        _, idx = jax.lax.top_k(s + lp["router_bias"].astype(bf), a.top_k)
        picked = jnp.take_along_axis(s, idx, axis=-1).astype(jnp.float32)
        return idx.astype(jnp.int32), a.route_scale * picked / (
            picked.sum(-1, keepdims=True) + 1e-20)

    monkeypatch.setattr(afmoe, "route", route)


def _expert_zeroed(monkeypatch):
    """One held expert's down projection is zero in every expert layer:
    its pairs add nothing.  Planted where the runner hands the weights to
    the program (the family module, which it finds by its file's name)."""
    from benchmark import harness

    real_load = harness.load_module

    def load(root, rel):
        mod = real_load(root, rel)
        if rel.endswith("families/afmoe.py"):
            real = mod.param_tree

            def broken(w):
                tree = real(w)
                p = tree["params"]
                for i in (1, 2, 3, 4):
                    p[f"l{i}_experts_w_down"] = (
                        p[f"l{i}_experts_w_down"].at[1].set(0))
                return tree

            mod.param_tree = broken
        return mod

    monkeypatch.setattr(harness, "load_module", load)


def _window_attends_everything(monkeypatch):
    """The decode kernel is given no first position on a window layer and
    the chunk's attention no window: both read all the row holds."""
    from torchdistx_tpu.serve import programs

    real_decode, real_chunk = (programs.paged_attention,
                               programs.paged_prefill_attention)
    monkeypatch.setattr(
        programs, "paged_attention",
        lambda *a, starts=None, **kw: real_decode(*a, **kw))
    monkeypatch.setattr(
        programs, "paged_prefill_attention",
        lambda *a, window=None, **kw: real_chunk(*a, **kw))


@pytest.mark.parametrize("plant", [
    None, _bf16_router, _expert_zeroed, _window_attends_everything])
def test_a_broken_router_expert_or_window_is_not_correct(monkeypatch, plant):
    if plant is not None:
        plant(monkeypatch)
    rc, line, err = rehearse(CELL, seed=41, seconds=1.5)
    assert rc == 0 and line is not None, err
    c = line["checks"]["logit_gap"]
    assert c["limit"] == TINY_LOGIT_GAP_LIMIT
    if plant is None:
        assert line["correct"] is True, err
        calls = line["notes"]["engine"]["program_calls"]
        assert calls["decode"] > 0 and calls.get("chunk-32", 0) > 0
        assert line["notes"]["check"]["router_choices"] > 0
    else:
        assert line["correct"] is False, err
        assert c["value"] > c["limit"]


# -- FLOPs and bytes by hand ---------------------------------------------------


def test_the_expert_products_and_the_two_cache_groups_by_hand():
    from benchmark.families import afmoe as fam
    from benchmark.rooflines import moe

    c = fam.dims(_config())
    peaks = json.load(open(os.path.join(
        ROOT, "benchmark", "peaks.json")))["TPU v5 lite"]
    assert (c["n_window_layers"], c["n_full_layers"], c["n_expert_layers"],
            c["held_experts"], c["router_outputs"]) == (4, 1, 4, 32, 256)
    assert moe.expert_params(c) == 3 * 3072 * 3072 == 28_311_552
    # a decode tick of 128 lanes in the deployment: 2 pairs a held expert,
    # every held expert hit, four expert layers
    need = moe.experts_needs(c, pairs=4 * 64, experts_hit=4 * 32)
    assert need["flops"] == 2 * 28_311_552 * 256
    assert need["bytes"] == 2 * (28_311_552 * 128 + (2 * 3072 + 2 * 3072) * 256)
    secs, bound = moe.least_seconds(need, peaks)
    assert bound == "memory"                 # 7.25 GB: 8.9 ms at 819 GB/s
    assert secs == pytest.approx(7.25e9 / 819e9, rel=0.01)
    # a chunk of 2,048: 32 pairs a held expert, 1,024 a layer
    chunk = moe.experts_needs(c, pairs=4 * 1024, experts_hit=4 * 32)
    assert chunk["flops"] / chunk["bytes"] < 197e12 / 819e9   # memory still
    # one lane at context 10,000: the full layer reads 10,000 tokens of K
    # and V, each window layer 4,096
    kv = moe.decode_attention_needs(c, 10_000, 4_096)
    tokens = 10_000 * 1 + 4_096 * 4
    assert kv["bytes"] == 2 * 1 * 128 * 2 * tokens    # 512 B a token, layer
    assert kv["flops"] == 4 * 6 * 128 * tokens
    assert moe.windowed(c, 100) == 100 and moe.windowed(c, 9_999) == 4_096
    assert moe.prefill_pairs(c, 100) == (5050, 5050)
    full, win = moe.prefill_pairs(c, 5000)
    assert full == 5000 * 5001 // 2
    assert win == 4096 * 4097 // 2 + 904 * 4096
    assert moe.decode_pairs(c, 4090, 10) == (
        sum(range(4091, 4101)), sum(min(t, 4096) for t in range(4091, 4101)))
    # parameters a position multiplies: five projections a layer, the
    # dense MLP, and in an expert layer the router, the shared expert and
    # 4 x 32 / 256 = half a routed expert
    attn = 2 * 3072 * 768 + 2 * 3072 * 128 + 768 * 3072
    want = (5 * attn + 3 * 3072 * 12288
            + 4 * (3072 * 256 + 1.5 * 28_311_552))
    assert moe.matmul_params(c) == want
    assert moe.forward_flops(c, 1, 1000, 1000, 1) == (
        2 * want + 2 * 3072 * 25024 + 4 * 6 * 128 * (1000 + 4 * 1000))


def test_served_flops_count_prefill_once_and_a_token_each():
    from benchmark.families import afmoe as fam
    from benchmark.rooflines import moe

    c = fam.dims(_tiny())
    reqs = [{"tokens": [1] * 30, "n": 3, "first": 1.0},
            {"tokens": [1] * 7, "n": 2, "first": 9.0},     # after the close
            {"tokens": [1] * 7, "n": 0, "first": None}]
    want = (moe.forward_flops(c, 30, *moe.prefill_pairs(c, 30), 1)
            + moe.forward_flops(c, 2, *moe.decode_pairs(c, 30, 2), 2))
    assert fam.served_flops(c, reqs, t_close=5.0) == want
    assert moe.prefill_pairs(c, 30)[1] < moe.prefill_pairs(c, 30)[0]


# -- the readers ---------------------------------------------------------------


def _span(program, t, **args):
    from torchdistx_tpu.observe import spans

    return {"name": "serve.program", "ph": "X",
            "ts": spans.from_perf_counter(t), "dur": 1000.0,
            "args": dict(program=program, **args)}


def test_the_new_readers_on_a_made_up_ctx(monkeypatch):
    from benchmark import harness, moe_trace
    from benchmark.families import afmoe as fam
    from torchdistx_tpu import observe

    class Clock:
        t0, setup_s = 100.0, 1.0

    class Tracer:
        events = [
            _span("decode", 102.0, routed_pairs=240, experts_hit=110,
                  attended_tokens=300_000, window_tokens=200_000),
            _span("decode", 103.0, routed_pairs=272, experts_hit=120,
                  attended_tokens=310_000, window_tokens=205_000),
            _span("chunk-2048", 103.3, routed_pairs=4000, experts_hit=128,
                  attended_tokens=6000, window_tokens=4096),
            _span("decode", 109.0, routed_pairs=1, experts_hit=1,
                  attended_tokens=1, window_tokens=1),   # after the window
        ]

    monkeypatch.setattr(observe, "tracer", lambda: Tracer)
    c = fam.dims(_config())
    peaks = json.load(open(os.path.join(
        ROOT, "benchmark", "peaks.json")))["TPU v5 lite"]
    steps = [{"i": i, "t0": 101.5 + i, "t1": 102.4 + i, "calls": {}}
             for i in range(3)]
    ctx = {"c": c, "clock": Clock, "steps": steps, "traced_steps": steps[:2],
           "peaks": peaks, "trace": {
               "busy_s": 0.1, "ops": {
                   "ragged-dot-none.3_custom-call:tpu_custom_call_bf16_512_3072_": 0.040,
                   "ragged-dot-metadata.1_custom-call:tpu_custom_call_s32_33_": 0.001,
                   "tdx_paged_attention_decode.7_custom-call:tpu_custom_call_bf16_128_1_8_128_": 0.004,
                   "fusion.12_fusion_bf16_128_3072_": 0.02}}}

    def read(name):
        return harness.load_module(ROOT, f"benchmark/metrics/{name}.py").read(ctx)

    assert moe_trace.experts_seconds(ctx) == pytest.approx(0.041)
    assert moe_trace.slice_args(ctx, "routed_pairs", "experts_hit") == (
        240 + 272 + 4000, 110 + 120 + 128)
    from benchmark.rooflines import moe

    least, _ = moe.least_seconds(moe.experts_needs(c, 4512, 358), peaks)
    assert read("moe_experts_roofline") == pytest.approx(100 * least / 0.041)
    assert read("moe_experts_roofline") < 100
    assert read("moe.device_share") == pytest.approx(100 * 0.041 / 0.1)
    # two decode ticks in the window: (240 + 272) / (2 x 32 x 4)
    assert read("moe.pairs_per_held_expert_tick") == pytest.approx(2.0)
    kv, _ = moe.least_seconds(moe.decode_attention_needs(
        c, 610_000, 405_000), peaks)
    assert read("window_attention_roofline") == pytest.approx(
        100 * kv / 0.004)
    counters = {"tdx.serve.moe_routed_pairs": 12_800.0,
                "tdx.serve.moe_pairs_max_expert": 450.0,
                "tdx.serve.window_pages_released": 77.0}

    class Counter:
        def __init__(self, name):
            self.value = counters.get(name, 0.0)

    monkeypatch.setattr(observe, "counter", Counter)
    assert read("moe.load_max_over_mean") == pytest.approx(450 / (12_800 / 128))
    assert read("kv.window_pages_released") == 77.0


def test_readers_leave_their_metric_out_where_there_is_nothing_to_read():
    """A parent commit's program: no such span argument, counter or event."""
    from benchmark import harness
    from torchdistx_tpu import observe

    observe.reset()
    ctx = {"engine": {"program_calls": {}}, "trace": None, "peaks": None,
           "traced_steps": [], "steps": [], "c": {}, "requests": [],
           "window_s": 1.0, "t_close": 0.0}
    for name in ("moe_experts_roofline", "moe.device_share",
                 "moe.pairs_per_held_expert_tick", "moe.load_max_over_mean",
                 "window_attention_roofline", "kv.window_pages_released"):
        mod = harness.load_module(ROOT, f"benchmark/metrics/{name}.py")
        assert mod.read(ctx) is None, name
    ctx["trace"] = {"busy_s": 1.0, "ops": {"fusion.1_fusion_f32_8_": 0.5}}
    for name in ("moe_experts_roofline", "moe.device_share",
                 "window_attention_roofline"):
        mod = harness.load_module(ROOT, f"benchmark/metrics/{name}.py")
        assert mod.read(ctx) is None, name


# -- the configuration's file and the traffic ----------------------------------


def test_the_configuration_holds_the_catalogs_row_but_for_six_cuts():
    cfg = _config()
    row = None
    cat = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(cat):
        for line in open(cat):
            r = json.loads(line)
            if r["name"] == "Trinity-Large-Preview":
                row = r
    published = row["config"] if row else dict(
        {k: cfg[k] for k in cfg if k not in cfg["reduced"]}, **cfg["published"])
    differs = sorted(k for k, v in published.items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == sorted([
        "num_hidden_layers", "num_dense_layers", "layer_types", "num_experts",
        "num_attention_heads", "num_key_value_heads", "vocab_size"])
    assert {k: published[k] for k in cfg["published"]} == cfg["published"]
    # every width as published
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["sliding_window"], cfg["route_scale"]) == (
        3072, 128, 12288, 3072, 4, 4096, 2.448)
    # one GQA group, an eighth of the experts and of the vocabulary
    assert cfg["num_attention_heads"] * 8 == 48
    assert cfg["num_key_value_heads"] * 8 == 8
    assert cfg["num_experts"] * 8 == cfg["router_outputs"] == 256
    assert cfg["vocab_size"] * 8 == 200192
    # published layer 0 and published layers 6-9
    assert cfg["layer_types"] == [
        row["config"]["layer_types"][i] if row else t
        for i, t in zip(cfg["published_layers"], cfg["layer_types"])]
    assert cfg["kind"] == "serve_hybrid" and cfg["family"] == "afmoe"
    assert cfg["serve_config"]["spec_decode"] is False
    assert cfg["serve_config"]["prefix_cache"] is False
    for key in ("family_module", "reference"):
        assert os.path.exists(os.path.join(ROOT, cfg[key]))
    sc = cfg["serve_config"]
    # every lane's window, a chunk's overhang and the null page
    assert sc["n_window_pages"] >= 128 * 257 + 2048 // 16 + 1


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(ROOT, "benchmark/reference/afmoe.py")).read()
    assert "torchdistx_tpu" not in src.split('"""', 2)[2]
    assert "families" not in src.split('"""', 2)[2]


def test_the_mixed_queue_is_the_issues_traffic():
    from benchmark import traffic

    mix = json.load(open(os.path.join(
        ROOT, "benchmark/traffic/mixed-queue.json")))
    assert (mix["mode"], mix["rate_per_s"], mix["schedule_seed"]) == (
        "backlog", 25.0, 1)
    assert mix["engine"] == {"prefill_buckets": [256, 1024, 2048],
                             "prefill_chunk": 2048, "max_pages_per_seq": 800}
    plan = traffic.serving(mix, 5, 40.0, 25024)
    assert len(plan) == 1000 and all(r["due_s"] == 0.0 for r in plan)
    lens = np.asarray([len(r["tokens"]) for r in plan])
    outs = np.asarray([r["max_new_tokens"] for r in plan])
    assert lens.min() >= 64 and lens.max() <= 12288
    assert (lens + outs).max() <= 12800 and outs.min() >= 16
    assert 1400 < np.median(lens) < 1700 and 110 < np.median(outs) < 150
    past = lens > 4096
    assert 0.13 < past.mean() < 0.19               # about 16 % past the window
    assert 0.40 < lens[past].sum() / lens.sum() < 0.55   # nearly half the tokens
    again = traffic.serving(mix, 6, 40.0, 25024)
    assert [len(r["tokens"]) for r in again] == [len(r["tokens"]) for r in plan]
    assert again[0]["tokens"] != plan[0]["tokens"]
    limits = json.load(open(os.path.join(
        ROOT, f"benchmark/limits/{CELL}.json")))
    # the gap that nine served tokens in ten stay under (families/afmoe.py)
    assert limits["quantile"] == 0.9 and limits["limits"]["logit_gap"] > 0


def test_the_cell_reports_the_median_gap_and_its_six_metrics_move_it():
    """``out_tok_s`` spreads over half its bound in this cell on the
    platform's late waits (PERF.md 7.17), so the cell reports
    ``tpot_p50_s``; a per-layer metric may only move what its cell reports."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert CELL in e2e["tpot_p50_s"]["workloads"]
    assert CELL not in e2e["out_tok_s"]["workloads"]
    mine = [p for p in m["per_layer"] if p.get("workloads") == [CELL]]
    assert len(mine) == 6 and {p["moves"] for p in mine} == {"tpot_p50_s"}
    listed = [p for p in m["per_layer"] if CELL in p.get("workloads", [])]
    assert all(p["moves"] in ("tpot_p50_s", "setup_s") for p in listed)
