"""Everything of the benchmark that has the shape of the ``afmoe`` family
(arcee-ai Trinity: sandwich-normed blocks, gated and QK-normed attention
that is windowed or full by layer, leading dense MLPs, sigmoid-routed
experts with a shared one), in one module that a configuration's file
names (``family_module``) and the runner ``kinds/serve_hybrid.py`` loads:
the sizes, the weights from ``--seed`` under the benchmark's own names,
the adapter into the program's types, the comparison that decides
``correct``, and the FLOPs (``rooflines/moe.py``).  Only
``transformer_config`` and ``param_tree`` touch the program; the
reference (``reference/afmoe.py``) never imports this file's program side.

The configuration is ONE CHIP'S SHARE of a layer group (its file says of
which deployment): ``num_attention_heads`` / ``num_key_value_heads`` are
the heads the chip holds, ``num_experts`` the experts it holds (from
``first_expert`` on) of the ``router_outputs`` the router scores,
``vocab_size`` its slice of the vocabulary.  The reference is given the
same share.

Weights.  Matrices N(0, 0.02) at the published width, norm gains
1 + N(0, 0.02), as in ``benchmark/weights.py`` (every tensor moves the
output); the per-expert selection bias N(0, 0.01), so that the choice is
not the scores' alone.  At another width (the tests' and the rehearsal's
64) a matrix's deviation is 0.02 x sqrt(3072 / width), so that the
router's scores and the logits spread as they do at 3,072 (a product with
a normed vector has deviation 0.02 x sqrt(width)).  A layer's experts are
drawn one at a time: the float32 draw of a whole tensor of them is 1.2 GB.
"""

from __future__ import annotations

import math

STD = 0.02
BIAS_STD = 0.01
PUBLISHED_WIDTH = 3072
FAMILY = "afmoe"
KINDS = {"sliding_attention": "sliding", "full_attention": "full"}


def dims(cfg: dict) -> dict:
    """The share's sizes from the source's own keys."""
    L = cfg["num_hidden_layers"]
    types = [KINDS[t] for t in cfg["layer_types"]][:L]
    return {
        "d_model": cfg["hidden_size"], "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"], "d_ff": cfg["intermediate_size"],
        "d_expert": cfg["moe_intermediate_size"], "n_layers": L,
        "n_dense_layers": cfg["num_dense_layers"], "layer_types": types,
        "n_window_layers": types.count("sliding"),
        "n_full_layers": types.count("full"),
        "n_expert_layers": L - cfg["num_dense_layers"],
        "window": cfg["sliding_window"],
        "held_experts": cfg["num_experts"],
        "first_expert": cfg.get("first_expert", 0),
        "router_outputs": cfg.get("router_outputs", cfg["num_experts"]),
        "top_k": cfg["num_experts_per_tok"],
        "route_scale": float(cfg["route_scale"]),
        "vocab_size": cfg["vocab_size"],
        "max_seq_len": cfg["max_position_embeddings"],
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "gated_mlp": True, "tied_head": False,
    }


def layer_shapes(c: dict, i: int) -> dict:
    """name -> (shape, kind) of layer ``i``'s tensors."""
    d, h, kv, hd = c["d_model"], c["n_heads"], c["n_kv_heads"], c["head_dim"]
    out = {f"{n}.scale": ((d,), "scale") for n in (
        "norm_in", "norm_post_attn", "norm_pre_mlp", "norm_post_mlp")}
    out.update({
        "wq": ((d, h, hd), "w"), "wk": ((d, kv, hd), "w"),
        "wv": ((d, kv, hd), "w"), "wg": ((d, h, hd), "w"),
        "wo": ((h, hd, d), "w"),
        "q_norm.scale": ((hd,), "scale"), "k_norm.scale": ((hd,), "scale"),
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


def layer_weights(w: dict, i: int) -> dict:
    """Layer ``i``'s tensors under their own names."""
    pre = f"layers.{i}."
    return {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}


# -- the program's types (never imported by the reference) -------------------


def transformer_config(cfg: dict, c: dict):
    from benchmark import harness

    import jax.numpy as jnp

    try:
        from torchdistx_tpu.models import AfmoeConfig, TransformerConfig
    except ImportError as e:
        raise harness.Refused(
            f"this checkout's program has no {FAMILY} family ({e})")
    # The rehearsal's products take float32 operands (``activation_dtype``
    # of its group): at width 64 with 2 of 8 experts chosen one flipped
    # choice moves a logit by 2, so a bfloat16 rehearsal would rehearse
    # the routing's rounding and not the wiring.
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[
        cfg.get("activation_dtype", "bfloat16")]
    return TransformerConfig(
        dtype=dtype,
        vocab_size=c["vocab_size"], d_model=c["d_model"],
        n_layers=c["n_layers"], n_heads=c["n_heads"],
        n_kv_heads=c["n_kv_heads"], head_dim=c["head_dim"], d_ff=c["d_ff"],
        max_seq_len=c["max_seq_len"], norm_eps=c["norm_eps"],
        rope_theta=c["rope_theta"], tie_embeddings=False,
        afmoe=AfmoeConfig(
            n_experts=c["router_outputs"], top_k=c["top_k"],
            d_expert=c["d_expert"], n_dense_layers=c["n_dense_layers"],
            layer_types=tuple(c["layer_types"]), window=c["window"],
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
# is three programs (a layer of each kind) of 5-10 s to compile on the
# chip, and padding costs the reference little (the mix's longest total
# is 12,800).
PADS = (1024, 4224, 12800)


def reference_logits(cache: dict, c, quant, w, r, **faults):
    """float32 [n_served, vocab]: the logits that predict each served
    token of request ``r``."""
    from benchmark.reference import afmoe

    seq = list(r["tokens"]) + list(r["tokens_out"][:-1])
    pad = min(next((p for p in PADS if p >= len(seq)), len(seq)),
              max(c["max_seq_len"], len(seq)))
    key = (pad, quant, tuple(sorted(faults.items())))
    if key not in cache:
        cache[key] = afmoe.Forward(c, afmoe.QUANT[quant], pad, **faults)
    fwd = cache[key]
    return fwd.logits(w, seq, len(r["tokens"]) - 1,
                      len(r["tokens_out"])), fwd.undecided


# ``--control <name>``: the reference in a lower precision (fp8, bf16), or
# one of the faults the reference can plant in itself, read and reported
# under notes and never part of ``correct``.
FAULTS = {
    "bf16-router": {"router_quant": "bf16"},
    "no-window": {"window": None},
    "one-expert-out": {"drop_expert": 0},
}


QUANTILES = (0.9, 0.95, 0.98, 0.99, 1.0)


def _summary(gaps, decided):
    """What a builder needs beside the one number that is compared."""
    import numpy as np

    return {"quantiles": {str(q): float(np.quantile(gaps, q, method="higher"))
                          for q in QUANTILES},
            "mean": float(gaps.mean()),
            "not_the_reference_choice": int((gaps > 0).sum()),
            "max_over_decided": float(gaps[decided].max(initial=0.0)),
            "decided": int(decided.sum()), "tokens": int(gaps.size)}


def check(env, c, w, finished: list) -> dict:
    """``logit_gap`` against ``reference/afmoe.py`` over a sample of the
    finished requests, the longest among them: the gap by which a served
    token's reference logit lies below the reference's best, **at the
    quantile the cell's limits file names** (``quantile``; 1.0, the
    widest, is ``benchmark/check_serve.py``'s number) over all served
    tokens of the sample.  Why a quantile: 2 to 4 % of the served tokens
    sit on a routing choice within 1e-3 of the next score, which the
    rounding of bfloat16 operands upstream turns, and a turned choice
    adds or removes a whole held expert from a partial sum that the
    post-norm rescales: a different, equally valid output of this share,
    0.3 to 1.1 off where every other token reads 0.0 to 0.03.  The widest
    gap is then the size of one expert, whatever the program's precision;
    what a loss of precision or a fault moves is how MANY tokens are off
    (PERF.md section 2).  Under notes: the quantiles, the widest gap over
    the tokens whose routing the reference finds decided, and the near
    ties among the sample's routing choices; with ``--control``, the same
    for the control."""
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
        if how in FAULTS:
            low, _ = reference_logits(cache, c, None, w, r, **FAULTS[how])
        elif how:
            low, _ = reference_logits(cache, c, how, w, r)
        if how:
            low_gaps.append(check_serve.gaps(ref, low.argmax(-1)))
    gaps = np.concatenate(gaps) if gaps else np.zeros((0,))
    decided = np.concatenate(decided) if decided else np.zeros((0,), bool)
    q = float(limits.get("quantile", 1.0))
    at = lambda g: float(np.quantile(g, q, method="higher")) if g.size else 0.0
    env["extra_notes"]["check"] = {
        "sampled": [r["rid"] for r in picked], "quantile": q,
        **(_summary(gaps, decided) if gaps.size else {}),
        "router_near_ties": sum(
            f.near_ties for f in cache.values() if not f.faults),
        "router_choices": sum(
            f.choices for f in cache.values() if not f.faults),
        "seconds": time.perf_counter() - t0}
    if how:
        low_gaps = np.concatenate(low_gaps)
        env["extra_notes"]["control"] = {
            "precision": how, "logit_gap": at(low_gaps),
            **_summary(low_gaps, decided)}
    lim = limits["rehearsal" if env["rehearse"] else "limits"]["logit_gap"]
    worst = at(gaps)
    return {
        "logit_gap": {"value": worst, "limit": lim, "ok": worst <= lim},
        "tokens_compared": {"value": int(gaps.size), "limit": 1,
                            "ok": gaps.size > 0},
    }


# -- FLOPs --------------------------------------------------------------------


def served_flops(c: dict, requests: list, t_close: float) -> float:
    """FLOPs this share of the model needs for every prompt position
    prefilled and every token handed over by ``t_close``: what
    ``serve.mfu_hybrid`` divides."""
    from benchmark.rooflines import moe

    flops = 0.0
    for r in requests:
        if r["first"] is None or r["first"] > t_close:
            continue
        L, n = len(r["tokens"]), r["n"]
        flops += moe.forward_flops(c, L, *moe.prefill_pairs(c, L), 1)
        flops += moe.forward_flops(c, n - 1, *moe.decode_pairs(c, L, n - 1),
                                   n - 1)
    return flops
