"""Everything of the benchmark that has the shape of the ``pangu_ultra_moe``
family (openPangu-Ultra-MoE: sandwich-normed blocks of latent attention,
MLA, over a leading dense MLP and sigmoid-routed experts with a shared
one), in one module that a configuration's file names (``family_module``)
and the runner ``kinds/serve_hybrid.py`` loads: the sizes, the weights
from ``--seed`` under the benchmark's own names, the adapter into the
program's types, and the comparison that decides ``correct``.  Only
``transformer_config`` and ``param_tree`` touch the program; the
reference (``reference/pangu_ultra_moe.py``) never imports this file's
program side.  The work the two latent kernels need is counted in
``rooflines/mla.py``, the expert products' in ``rooflines/moe.py``.

The configuration is ONE CHIP'S SHARE of a 16-chip expert-parallel group
(its file says of which deployment): ``n_routed_experts`` is the experts
the chip holds (from ``first_expert`` on) of the ``router_outputs`` the
router scores, ``vocab_size`` its slice of the vocabulary; attention is
whole (data-parallel).  The reference is given the same share.

Weights.  Matrices N(0, 0.02) at the published width, norm gains
1 + N(0, 0.02), as in ``benchmark/weights.py``; the per-expert selection
bias N(0, 0.01), so that the choice is not the scores' alone.  At another
width (the tests' and the rehearsal's 64) a matrix's deviation is 0.02 x
sqrt(7680 / width), so that the router's scores and the logits spread as
they do at 7,680.  A layer's experts are drawn one at a time.
"""

from __future__ import annotations

import math

from benchmark.families.afmoe import _summary

STD = 0.02
BIAS_STD = 0.01
PUBLISHED_WIDTH = 7680
FAMILY = "pangu_ultra_moe"


def dims(cfg: dict) -> dict:
    """The share's sizes from the source's own keys."""
    L, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return {
        "d_model": cfg["hidden_size"], "n_heads": cfg["num_attention_heads"],
        "q_rank": cfg["q_lora_rank"], "kv_rank": cfg["kv_lora_rank"],
        "nope_dim": cfg["qk_nope_head_dim"],
        "rope_dim": cfg["qk_rope_head_dim"], "v_dim": cfg["v_head_dim"],
        "d_ff": cfg["intermediate_size"],
        "d_expert": cfg["moe_intermediate_size"], "n_layers": L,
        "n_dense_layers": dense, "n_expert_layers": L - dense,
        "held_experts": cfg["n_routed_experts"],
        "first_expert": cfg.get("first_expert", 0),
        "router_outputs": cfg.get("router_outputs", cfg["n_routed_experts"]),
        "top_k": cfg["num_experts_per_tok"],
        "route_scale": float(cfg["routed_scaling_factor"]),
        "vocab_size": cfg["vocab_size"],
        "max_seq_len": cfg["max_position_embeddings"],
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "gated_mlp": True, "tied_head": False,
    }


def layer_shapes(c: dict, i: int) -> dict:
    """name -> (shape, kind) of layer ``i``'s tensors."""
    d, h = c["d_model"], c["n_heads"]
    rq, r, dn, dr, dv = (c["q_rank"], c["kv_rank"], c["nope_dim"],
                         c["rope_dim"], c["v_dim"])
    out = {f"{n}.scale": ((d,), "scale") for n in (
        "norm_in", "norm_post_attn", "norm_pre_mlp", "norm_post_mlp")}
    out.update({
        "wq_a": ((d, rq), "w"), "q_a_norm.scale": ((rq,), "scale"),
        "wq_b": ((rq, h, dn + dr), "w"), "wkv_a": ((d, r + dr), "w"),
        "kv_a_norm.scale": ((r,), "scale"), "wkv_b": ((r, h, dn + dv), "w"),
        "wo": ((h, dv, d), "w"),
    })
    if i < c["n_dense_layers"]:
        ff = c["d_ff"]
        out.update({"w_gate": ((d, ff), "w"), "w_up": ((d, ff), "w"),
                    "w_down": ((ff, d), "w")})
    else:
        de, n, e = c["d_expert"], c["held_experts"], c["router_outputs"]
        out.update({
            "router": ((d, e), "w"), "router_bias": ((e,), "bias"),
            "experts.w_gate": ((n, d, de), "experts"),
            "experts.w_up": ((n, d, de), "experts"),
            "experts.w_down": ((n, de, d), "experts"),
            "shared.w_gate": ((d, de), "w"), "shared.w_up": ((d, de), "w"),
            "shared.w_down": ((de, d), "w"),
        })
    return out


def shapes(c: dict) -> dict:
    """The flat dict's names -> (shape, kind); a layer's tensors come
    under ``layers.<i>.``."""
    d, v = c["d_model"], c["vocab_size"]
    out = {"embed": ((v, d), "embed"), "lm_head": ((d, v), "w"),
           "final_norm.scale": ((d,), "scale")}
    for i in range(c["n_layers"]):
        out.update({f"layers.{i}.{n}": s
                    for n, s in layer_shapes(c, i).items()})
    return out


def n_params(c: dict) -> int:
    return sum(math.prod(shape) for shape, _ in shapes(c).values())


def make(c: dict, seed: int, dtype) -> dict:
    """The flat dict of weights, on the default device, in ``dtype``."""
    import jax
    import jax.numpy as jnp

    from benchmark.weights import seed_key

    spec = shapes(c)
    names = sorted(spec)
    f32 = jnp.float32
    w_std = STD * math.sqrt(PUBLISHED_WIDTH / c["d_model"])

    def draw(key, shape, kind):
        if kind == "experts":
            one = lambda k: (w_std * jax.random.normal(k, shape[1:], f32)
                             ).astype(dtype)
            return jax.lax.map(one, jax.random.split(key, shape[0]))
        x = jax.random.normal(key, shape, f32)
        x = {"w": w_std * x, "embed": STD * x, "bias": BIAS_STD * x,
             "scale": 1.0 + STD * x}[kind]
        return x.astype(dtype)

    @jax.jit
    def build(key):
        return {n: draw(jax.random.fold_in(key, i), *spec[n])
                for i, n in enumerate(names)}

    return build(seed_key(seed))


# -- the program's types (never imported by the reference) -------------------


def transformer_config(cfg: dict, c: dict):
    from benchmark import harness

    import jax.numpy as jnp

    try:
        from torchdistx_tpu.models import (MLAConfig, RoutedExperts,
                                           TransformerConfig)
    except ImportError as e:
        raise harness.Refused(
            f"this checkout's program has no {FAMILY} family ({e})")
    # The rehearsal's products take float32 operands (``activation_dtype``
    # of its group), as trinity's do: at width 64 with 2 of 8 experts
    # chosen, a bfloat16 rehearsal would rehearse the routing's rounding.
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[
        cfg.get("activation_dtype", "bfloat16")]
    return TransformerConfig(
        dtype=dtype,
        vocab_size=c["vocab_size"], d_model=c["d_model"],
        n_layers=c["n_layers"], n_heads=c["n_heads"], d_ff=c["d_ff"],
        max_seq_len=c["max_seq_len"], norm_eps=c["norm_eps"],
        rope_theta=c["rope_theta"], tie_embeddings=False,
        mla=MLAConfig(q_rank=c["q_rank"], kv_rank=c["kv_rank"],
                      nope_dim=c["nope_dim"], rope_dim=c["rope_dim"],
                      v_dim=c["v_dim"]),
        experts=RoutedExperts(
            n_experts=c["router_outputs"], top_k=c["top_k"],
            d_expert=c["d_expert"], n_dense_layers=c["n_dense_layers"],
            route_scale=c["route_scale"], first_expert=c["first_expert"],
            held_experts=c["held_experts"]))


def param_tree(w: dict) -> dict:
    """The program's tree from the benchmark's flat weights: the same
    device arrays under the program's names (``layers.3.experts.w_up`` ->
    ``l3_experts_w_up``; a norm's ``.scale`` is the leaf itself)."""
    p = {"embedding": w["embed"], "lm_head": w["lm_head"],
         "final_norm": w["final_norm.scale"]}
    for name, x in w.items():
        if name.startswith("layers."):
            _, i, rest = name.split(".", 2)
            p[f"l{i}_{rest.removesuffix('.scale').replace('.', '_')}"] = x
    return {"params": p}


# -- correct ------------------------------------------------------------------


# The padded lengths the reference runs a sequence at: few, because each
# is two programs (a dense and an expert layer) to compile on the chip
# (the mix's longest total is 17,408).
PADS = (2048, 4224, 8448, 17408)


def reference_logits(cache: dict, c, quant, w, r):
    """float32 [n_served, vocab]: the logits that predict each served
    token of request ``r``; and the positions whose routing the reference
    finds undecided."""
    from benchmark.reference import pangu_ultra_moe as ref

    seq = list(r["tokens"]) + list(r["tokens_out"][:-1])
    pad = min(next((p for p in PADS if p >= len(seq)), len(seq)),
              max(c["max_seq_len"], len(seq)))
    key = (pad, quant)
    if key not in cache:
        cache[key] = ref.Forward(c, ref.QUANT[quant], pad)
    fwd = cache[key]
    return fwd.logits(w, seq, len(r["tokens"]) - 1,
                      len(r["tokens_out"])), fwd.undecided


def check(env, c, w, finished: list) -> dict:
    """``logit_gap`` against ``reference/pangu_ultra_moe.py`` over a sample
    of the finished requests, the longest among them, at the quantile the
    cell's limits file names (the rehearsal's group may name its own), as
    ``families/afmoe.py`` reads it and for its reason: the expert layer is the same, and a routing choice within
    rounding of the next score turns a whole held expert in or out of a
    partial sum (PERF.md section 2).  ``--control fp8`` (or bf16) reads the
    reference in that precision beside it, under notes."""
    import time

    import numpy as np

    from benchmark import check_serve, harness

    t0 = time.perf_counter()
    limits = harness.load_json(
        env["root"], f"benchmark/limits/{env['cell']['name']}.json")
    picked = check_serve.sample(env, finished, limits["sample_requests"])
    cache, gaps, decided, low_gaps = {}, [], [], []
    how = env.get("control") or None
    for r in picked:
        ref, undecided = reference_logits(cache, c, None, w, r)
        gaps.append(check_serve.gaps(ref, r["tokens_out"]))
        decided.append(~undecided)
        if how:
            low, _ = reference_logits(cache, c, how, w, r)
            low_gaps.append(check_serve.gaps(ref, low.argmax(-1)))
    gaps = np.concatenate(gaps) if gaps else np.zeros((0,))
    decided = np.concatenate(decided) if decided else np.zeros((0,), bool)
    # The rehearsal's program runs float32 operands: no routing choice
    # turns on rounding there, so its limit holds at the widest gap
    # (its group's ``quantile``), where any fault that moves a token shows.
    group = limits["rehearsal" if env["rehearse"] else "limits"]
    q = float(group.get("quantile", limits.get("quantile", 1.0)))
    at = lambda g: float(np.quantile(g, q, method="higher")) if g.size else 0.0
    env["extra_notes"]["check"] = {
        "sampled": [r["rid"] for r in picked], "quantile": q,
        **(_summary(gaps, decided) if gaps.size else {}),
        "router_near_ties": sum(f.near_ties for k, f in cache.items()
                                if k[1] is None),
        "router_choices": sum(f.choices for k, f in cache.items()
                              if k[1] is None),
        "seconds": time.perf_counter() - t0}
    if how:
        low_gaps = np.concatenate(low_gaps)
        env["extra_notes"]["control"] = {
            "precision": how, "logit_gap": at(low_gaps),
            **_summary(low_gaps, decided)}
    lim = group["logit_gap"]
    worst = at(gaps)
    return {
        "logit_gap": {"value": worst, "limit": lim, "ok": worst <= lim},
        "tokens_compared": {"value": int(gaps.size), "limit": 1,
                            "ok": gaps.size > 0},
    }
