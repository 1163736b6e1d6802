"""The ``olmo-hybrid-7b-d8`` configuration's own benchmark files, on the
CPU at a size a test can hold: ``correct`` has to come out false for the
faults this model can have (beta not doubled, the state kept in bfloat16,
the decay dropped, the conv skipped, NoPE replaced by RoPE) and the fp8
control as not correct, the sound program as correct;
``rooflines/gdn.py`` against hand counts; the readers of the three new
metrics on a made-up trace, and silent without one; the configuration's
file against the catalog's row."""

import json
import math
import os
import sys

import numpy as np
import pytest

from bench_util import ROOT, rehearse

sys.path.insert(0, ROOT)

CELL = "olmo-hybrid-7b-d8-chat-backlog"
# The catalog's row (Olmo-Hybrid-7B, its ``config``), as the configuration
# file must hold it but for ``reduced``.
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
}


def _config():
    with open(os.path.join(ROOT, "benchmark/configs/olmo-hybrid-7b-d8.json")) as f:
        return json.load(f)


# -- planted faults -------------------------------------------------------------


def _inputs_changed(monkeypatch, change):
    from torchdistx_tpu.models import olmo_hybrid as prog

    real = prog._inputs

    def broken(cfg, m, h, tail, n_valid):
        return change(real, cfg, m, h, tail, n_valid)

    monkeypatch.setattr(prog, "_inputs", broken)


def _beta_single(monkeypatch):
    """beta = sigmoid(x W_b): the step not doubled (no negative
    eigenvalues)."""
    def change(real, *a):
        q, k, v, beta, g, gate, tail = real(*a)
        return q, k, v, beta / 2, g, gate, tail

    _inputs_changed(monkeypatch, change)


def _no_decay(monkeypatch):
    """alpha = 1: the gate's decay dropped."""
    def change(real, *a):
        q, k, v, beta, g, gate, tail = real(*a)
        return q, k, v, beta, g * 0.0, gate, tail

    _inputs_changed(monkeypatch, change)


def _no_conv(monkeypatch):
    """The conv skipped: its taps the identity (the last tap 1, the others
    0), so q, k and v are silu of their projections."""
    import jax.numpy as jnp

    def change(real, cfg, m, h, tail, n_valid):
        w = m["conv_w"]
        eye = jnp.zeros_like(w).at[-1].set(1.0)
        return real(cfg, dict(m, conv_w=eye), h, tail, n_valid)

    _inputs_changed(monkeypatch, change)


def _bf16_state(monkeypatch):
    """The state kept in bfloat16: rounded after every position of a
    prompt and every decode tick."""
    import jax
    import jax.numpy as jnp

    from torchdistx_tpu.ops import gdn

    bf = lambda s: s.astype(jnp.bfloat16).astype(jnp.float32)
    real_decode = gdn.gdn_decode_update

    def decode(state, layer, *a, **kw):
        o, state = real_decode(bf(state), layer, *a, **kw)
        return o, bf(state)

    def chunk(q, k, v, beta, g, s0, n_valid, interpret=None):
        live = jnp.arange(q.shape[0]) < n_valid

        def step(s, inp):
            q_t, k_t, v_t, b_t, g_t, on = inp
            s1 = jnp.exp(g_t)[:, None, None] * s
            u = b_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, s1))
            s1 = bf(s1 + k_t[:, :, None] * u[:, None, :])
            s = jnp.where(on, s1, s)
            return s, jnp.einsum("hk,hkv->hv", q_t, s)

        f32 = lambda x: x.astype(jnp.float32)
        s, o = jax.lax.scan(step, bf(s0), (f32(q), f32(k), f32(v), f32(beta),
                                           f32(g), live))
        return o, s

    monkeypatch.setattr(gdn, "gdn_decode_update", decode)
    monkeypatch.setattr(gdn, "gdn_chunk", chunk)


def _rope(monkeypatch):
    """NoPE replaced by RoPE (theta 10,000, after the QK-norm) in the
    full-attention layers.  Planted in the reference's place: the
    program's attention closures own the positions, the reference's layer
    is one function, and the comparison is the same either way round."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import olmo_hybrid as ref

    def rotated(c, quant, x, lw):
        lw = {k: v.astype(jnp.float32) for k, v in lw.items()}
        T, eps = x.shape[0], c["norm_eps"]
        H, hd = c["n_heads"], c["head_dim"]
        inv = 1.0 / 10000.0 ** (jnp.arange(0, hd, 2) / hd)
        ang = jnp.arange(T)[:, None, None] * inv
        cos, sin = jnp.cos(ang), jnp.sin(ang)

        def rope(y):
            y1, y2 = y[..., : hd // 2], y[..., hd // 2:]
            return jnp.concatenate([y1 * cos - y2 * sin,
                                    y2 * cos + y1 * sin], -1)

        def normed(w, scale):
            y = ref._mm("td,dhk->thk", x, lw[w], quant)
            return rope(ref._rms(y.reshape(T, -1), lw[scale], eps).reshape(
                y.shape))

        q, k = normed("wq", "q_norm.scale"), normed("wk", "k_norm.scale")
        v = ref._mm("td,dhk->thk", x, lw["wv"], quant)
        s = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(hd)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
        return ref._block(c, quant, x, lw,
                          ref._mm("thk,hkd->td", o, lw["wo"], quant))

    monkeypatch.setattr(ref, "full_layer", rotated)


@pytest.mark.parametrize("plant", [None, _beta_single, _bf16_state, _no_decay,
                                   _no_conv, _rope],
                         ids=["sound", "beta_single", "bf16_state",
                              "no_decay", "no_conv", "rope"])
def test_a_broken_rule_or_layer_is_not_correct(monkeypatch, plant):
    if plant is not None:
        plant(monkeypatch)
    rc, line, err = rehearse(CELL, seed=37, seconds=1.5)
    assert rc == 0 and line is not None, err
    c = line["checks"]["logit_gap"]
    if plant is None:
        assert line["correct"] is True, err
        assert c["value"] == 0.0  # float32 operands at this size
    else:
        assert line["correct"] is False, err
        assert c["value"] > c["limit"]


@pytest.mark.parametrize("control", ["fp8", "bf16-state"])
def test_the_controls_are_read_and_fp8_is_not_correct(control):
    """The fp8 control (per-tensor e4m3 on every weight matmul of the
    reference) reads over the limit; the reference with its state rounded
    to bfloat16 a position is read and reported.  Neither is part of
    ``correct``."""
    rc, line, err = rehearse(CELL, seed=38, seconds=1.0,
                             extra=("--control", control))
    assert rc == 0 and line["correct"] is True, err
    got = line["notes"]["control"]
    assert got["precision"] == control
    if control == "fp8":
        assert got["logit_gap"] > 10 * line["checks"]["logit_gap"]["limit"]
    else:
        assert got["logit_gap"] >= 0.0


# -- the configuration's file ---------------------------------------------------


def test_the_configuration_holds_the_catalogs_row_but_for_the_depth():
    cfg = _config()
    differs = sorted(k for k, v in PUBLISHED.items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == [
        "layer_types", "num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 8
    assert cfg["layer_types"] == PUBLISHED["layer_types"][:8]
    for key in ("positions", "block", "qk_norm", "output_gate", "conv_bias",
                "state_precision"):
        assert key in cfg["assumed"], key
    assert cfg["kind"] == "serve_hybrid" and cfg["family"] == "olmo_hybrid"
    sc = cfg["serve_config"]
    assert (sc["max_batch"], sc["page_size"], sc["n_pages"]) == (128, 16, 6400)
    assert sc["spec_decode"] is False and sc["prefix_cache"] is False
    for key in ("family_module", "reference"):
        assert os.path.exists(os.path.join(ROOT, cfg[key]))


def test_the_cut_by_hand():
    """ISSUE 37's arithmetic: 215.5 M a linear layer, 185.8 M a full one,
    770.7 M of embedding and head, 2.435 B for the eight layers."""
    from benchmark.families import olmo_hybrid as fam

    c = fam.dims(_config())
    assert (c["n_linear_layers"], c["n_full_layers"]) == (6, 2)
    assert (c["attn_layer_period"], c["attn_layer_offset"]) == (4, 3)
    assert c["conv_channels"] == 2880 + 2880 + 5760
    d = 3840
    linear = (d * 17280 + 5760 * d + 2 * d * 30 + 11520 * 4 + 30 + 30 + 192
              + 3 * d * 11008 + 2 * d)
    full = 4 * d * d + 2 * d + 3 * d * 11008 + 2 * d
    assert linear == pytest.approx(215.5e6, rel=1e-3)
    assert full == pytest.approx(185.8e6, rel=1e-3)
    assert fam.n_params(c) == 6 * linear + 2 * full + 2 * 100352 * d + d
    assert fam.n_params(c) == pytest.approx(2.435e9, rel=1e-3)
    # state a lane and linear layer: 30 x 96 x 192 float32 and the tail
    assert 30 * 96 * 192 * 4 == 2_211_840
    assert 6 * 128 * (2_211_840 + 3 * 11520 * 2) == pytest.approx(1.75e9,
                                                                  rel=0.01)


def test_the_weights_make_the_rule_carry():
    """``alpha = exp(g)`` spread over (0, 1) at a zero input, neither 1
    nor 0 everywhere; the same seed gives the same weights, a large one
    too."""
    import jax
    import jax.numpy as jnp

    from benchmark.families import olmo_hybrid as fam

    cfg = dict(_config(), **{k: v for k, v in _config()["rehearsal"].items()
                             if k != "serve_config"})
    c = fam.dims(cfg)
    w = fam.make(c, 2**31 + 5, jnp.float32)
    again = fam.make(c, 2**31 + 5, jnp.float32)
    assert all(bool(jnp.array_equal(w[k], again[k])) for k in w)
    assert set(w) == set(fam.shapes(c))
    g = -jnp.exp(w["gdn.A_log"]) * jax.nn.softplus(w["gdn.dt_bias"])
    alpha = np.asarray(jnp.exp(g))
    assert 0.0 < alpha.min() < 0.999 and alpha.max() < 1.0
    assert w["gdn.conv_w"].shape == (6, 2 * 2 * 16 + 2 * 32, 4)
    scales = np.asarray(w["ffn.post_mixer_norm.scale"])
    assert scales.std() > 0.01  # not exactly 1


# -- FLOPs and bytes by hand ---------------------------------------------------


def _peaks():
    return json.load(open(os.path.join(
        ROOT, "benchmark", "peaks.json")))["TPU v5 lite"]


def test_the_two_kernels_needs_by_hand():
    from benchmark.families import olmo_hybrid as fam
    from benchmark.rooflines import gdn

    c = fam.dims(_config())
    state = 30 * 96 * 192 * 4
    vectors = (2 * 2880 + 5760) * 2 + 2 * 30 * 4 + 5760 * 4
    need = gdn.decode_update_needs(c, 1)
    assert need["bytes"] == 6 * (2 * state + vectors)
    assert need["flops"] == 6 * 30 * (7 * 96 * 192 + 2 * 192)
    secs, bound = gdn.least_seconds(need, _peaks())
    assert bound == "memory" and secs == pytest.approx(need["bytes"] / 819e9)
    # 128 lanes: 3.4 GB of state a tick each way together, 4.2 ms
    assert gdn.decode_update_needs(c, 128)["bytes"] == pytest.approx(
        3.44e9, rel=0.01)
    chunk = gdn.chunk_needs(c, 200, 1)
    assert chunk["bytes"] == 6 * (200 * vectors + 2 * state)
    assert chunk["flops"] == 200 * need["flops"]


def test_the_whole_model_by_hand():
    from benchmark.families import olmo_hybrid as fam
    from benchmark.rooflines import gdn

    c = fam.dims(_config())
    d = 3840
    linear = d * (2 * 2880 + 2 * 5760) + 5760 * d + 2 * d * 30
    assert gdn.linear_matmul_params(c) == linear
    assert gdn.attention_matmul_params(c) == 4 * d * d
    whole = 6 * linear + 2 * 4 * d * d + 8 * 3 * d * 11008 + d * 100352
    assert gdn.matmul_params(c) == whole
    per = 30 * (7 * 96 * 192 + 2 * 192) + 2 * 4 * 11520 + 4 * 11520
    assert gdn.linear_flops_per_position(c) == per
    assert gdn.forward_flops(c, 1, 1000, 1) == (
        2 * whole + 4 * 30 * 128 * 1000 * 2 + 6 * per)


def test_served_flops_count_prefill_once_and_a_token_each():
    from benchmark.families import olmo_hybrid as fam
    from benchmark.rooflines import gdn

    c = fam.dims(_config())
    reqs = [{"tokens": [1] * 10, "n": 3, "first": 1.0},
            {"tokens": [1] * 7, "n": 2, "first": 9.0},     # after the close
            {"tokens": [1] * 7, "n": 0, "first": None}]
    want = (gdn.forward_flops(c, 10, 55, 1)
            + gdn.forward_flops(c, 2, 11 + 12, 2))
    assert fam.served_flops(c, reqs, t_close=5.0) == want


# -- the readers ---------------------------------------------------------------


def _read(name, ctx):
    from benchmark import harness

    return harness.load_module(ROOT, f"benchmark/metrics/{name}.py").read(ctx)


def test_the_new_readers_on_a_made_up_ctx():
    from benchmark.families import olmo_hybrid as fam
    from benchmark.rooflines import gdn

    c = fam.dims(_config())
    steps = [{"i": 0, "calls": {"decode": 1}, "decode_lanes": 128,
              "prefill_tokens": 0},
             {"i": 1, "calls": {"decode": 1, "prefill-256": 1},
              "decode_lanes": 127, "prefill_tokens": 180}]
    ctx = {"c": c, "peaks": _peaks(), "traced_steps": steps, "trace": {
        "busy_s": 0.05, "ops": {
            "tdx_gdn_decode_update.3_custom-call:tpu_custom_call_f32_6_128_96_5760_": 0.012,
            "tdx_gdn_chunk.1_custom-call:tpu_custom_call_f32_30_256_192_": 0.002,
            "tdx_paged_attention_decode.7_custom-call:tpu_custom_call_bf16_128_30_128_": 0.003,
            "fusion.12_fusion_bf16_128_3840_": 0.02}}}
    least, _ = gdn.least_seconds(gdn.decode_update_needs(c, 255), _peaks())
    assert _read("gdn_decode_roofline", ctx) == pytest.approx(
        100 * least / 0.012)
    least, _ = gdn.least_seconds(gdn.chunk_needs(c, 180, 1), _peaks())
    assert _read("gdn_chunk_roofline", ctx) == pytest.approx(
        100 * least / 0.002)
    assert _read("gdn.device_share", ctx) == pytest.approx(100 * 0.014 / 0.05)
    for name in ("gdn_decode_roofline", "gdn_chunk_roofline",
                 "gdn.device_share"):
        assert 0 < _read(name, ctx) < 100, name


def test_the_readers_leave_their_metric_out_where_there_is_nothing_to_read():
    """A parent commit's program, or another cell: no such event."""
    ctx = {"c": {}, "peaks": None, "traced_steps": [], "trace": None}
    names = ("gdn_decode_roofline", "gdn_chunk_roofline", "gdn.device_share")
    for name in names:
        assert _read(name, ctx) is None, name
    ctx["trace"] = {"busy_s": 1.0, "ops": {"fusion.1_fusion_f32_8_": 0.5}}
    ctx["peaks"] = _peaks()
    for name in names:
        assert _read(name, ctx) is None, name


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(ROOT, "benchmark/reference/olmo_hybrid.py")).read()
    body = src.split('"""', 2)[2]
    assert "torchdistx_tpu" not in body and "families" not in body


def test_the_cell_is_the_jamba_cells_traffic_and_reports_the_issues_metrics():
    m = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    jamba = next(w for w in m["workloads"]
                 if w["name"] == "jamba2-3b-chat-backlog")
    assert cell["traffic"] == jamba["traffic"] == "chat-backlog-wide"
    assert cell["chips"] == 1
    # ``out_tok_s`` spread 2.6 % in the first set of six (half its bound
    # is 1 %): the cell reports ``tpot_p50_s`` and ``setup_s``, and no
    # per-layer metric that moves ``out_tok_s`` lists it (PERF.md section 2).
    e2e = {e["name"] for e in m["end_to_end"]
           if CELL in e.get("workloads", [CELL])}
    assert e2e == {"tpot_p50_s", "setup_s"}
    mine = {e["name"]: e["moves"] for e in m["per_layer"]
            if CELL in e.get("workloads", [])}
    assert {"gdn_decode_roofline", "gdn_chunk_roofline", "gdn.device_share",
            "device.peak_hbm_share", "programs.decode_device_p50_s",
            "kv.attended_tokens_per_decode_tick"} <= set(mine)
    assert set(mine.values()) == {"tpot_p50_s"}
