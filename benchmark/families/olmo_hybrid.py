"""Everything of the benchmark that has the shape of the ``olmo_hybrid``
family (allenai Olmo-Hybrid: Gated DeltaNet layers, three to each
full-attention layer, Olmo's post-norm block), in one module that a
configuration's file names (``family_module``) and the runner
``kinds/serve_hybrid.py`` loads: the sizes, the weights from ``--seed``
under the benchmark's own names, the adapter into the program's types,
the comparison that decides ``correct``, and the FLOPs
(``rooflines/gdn.py``).  Only ``transformer_config`` and ``param_tree``
touch the program; the reference (``reference/olmo_hybrid.py``) never
imports this file's program side.

Weights.  Matrices N(0, 0.02) at the published width, norm gains
1 + N(0, 0.02) (a gain of exactly 1 would hide a misplaced norm), the
embedding N(0, 0.02), as in ``benchmark/weights.py``.  At another width
(the tests' and the rehearsal's 64) a matrix's deviation is 0.02 x
sqrt(3840 / width), so that a projection of the residual stream has the
size it has at 3,840.  Three tensors are drawn otherwise, because at
N(0, 0.02) the rule would carry nothing: the conv's taps N(0, 1/d_conv)
(standard deviation 1/2), so that the conv's output has the size of its
input and silu passes it on, neither saturated nor near zero, as
``families/jamba.py`` draws them; ``A_log = log(a)``, ``a`` uniform in
[1, 16] a head; and ``dt_bias`` the inverse softplus of a step log-uniform
in [0.001, 0.1].  ``x W_a`` moves the step by a factor of a few either
way, so ``alpha = exp(g)`` spreads over (0, 1); ``x W_b`` spreads ``beta``
over most of (0, 2), so that its doubling is seen.
"""

from __future__ import annotations

import math

STD = 0.02
PUBLISHED_WIDTH = 3840
FAMILY = "olmo_hybrid"
KINDS = {"linear_attention": "linear", "full_attention": "full"}


def dims(cfg: dict) -> dict:
    """The model's sizes from the source's own keys."""
    d, h, L = cfg["hidden_size"], cfg["num_attention_heads"], cfg[
        "num_hidden_layers"]
    types = [KINDS[t] for t in cfg["layer_types"]][:L]
    full = [i for i, t in enumerate(types) if t == "full"]
    per = full[1] - full[0] if len(full) > 1 else L
    if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
        raise ValueError("fewer key heads than value heads is not served "
                         "(ROADMAP M5)")
    if types != ["full" if i % per == full[0] else "linear"
                 for i in range(L)]:
        raise ValueError(f"layer_types {types} is no period of linear "
                         f"layers and one full layer")
    H, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    return {
        "d_model": d, "n_heads": h, "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg.get("head_dim") or d // h,
        "d_ff": cfg["intermediate_size"], "n_layers": L,
        "vocab_size": cfg["vocab_size"],
        "max_seq_len": cfg["max_position_embeddings"],
        "norm_eps": float(cfg["rms_norm_eps"]), "layer_types": types,
        "n_full_layers": len(full), "n_linear_layers": L - len(full),
        "attn_layer_period": per, "attn_layer_offset": full[0],
        "lin_heads": H, "d_k": dk, "d_v": dv,
        "d_conv": cfg["linear_conv_kernel_dim"],
        "conv_channels": 2 * H * dk + H * dv,
        "allow_neg_eigval": bool(cfg["linear_allow_neg_eigval"]),
        "gated_mlp": True, "tied_head": False,
    }


def shapes(c: dict) -> dict:
    """name -> (shape WITHOUT the layer axis, kind).  ``gdn.*`` has one row
    a linear layer, ``attn.*`` one a full-attention layer, ``ffn.*`` one a
    layer, each in layer order."""
    d, h, kv, hd, ff, v = (c["d_model"], c["n_heads"], c["n_kv_heads"],
                           c["head_dim"], c["d_ff"], c["vocab_size"])
    H, dk, dv, C = c["lin_heads"], c["d_k"], c["d_v"], c["conv_channels"]
    return {
        "embed": ((v, d), "embed"),
        "lm_head": ((d, v), "w"),
        "final_norm.scale": ((d,), "scale"),
        "gdn.wq": ((d, H * dk), "w"),
        "gdn.wk": ((d, H * dk), "w"),
        "gdn.wv": ((d, H * dv), "w"),
        "gdn.wg": ((d, H * dv), "w"),
        "gdn.wa": ((d, H), "w"),
        "gdn.wb": ((d, H), "w"),
        "gdn.conv_w": ((C, c["d_conv"]), "conv"),
        "gdn.A_log": ((H,), "a_log"),
        "gdn.dt_bias": ((H,), "dt_bias"),
        "gdn.o_norm.scale": ((dv,), "scale"),
        "gdn.wo": ((H * dv, d), "w"),
        "attn.wq": ((d, h, hd), "w"),
        "attn.wk": ((d, kv, hd), "w"),
        "attn.wv": ((d, kv, hd), "w"),
        "attn.wo": ((h, hd, d), "w"),
        "attn.q_norm.scale": ((h * hd,), "scale"),
        "attn.k_norm.scale": ((kv * hd,), "scale"),
        "ffn.post_mixer_norm.scale": ((d,), "scale"),
        "ffn.post_ffn_norm.scale": ((d,), "scale"),
        "ffn.w_gate": ((d, ff), "w"),
        "ffn.w_up": ((d, ff), "w"),
        "ffn.w_down": ((ff, d), "w"),
    }


def rows(c: dict, name: str) -> int:
    return {"gdn": c["n_linear_layers"], "attn": c["n_full_layers"],
            "ffn": c["n_layers"]}.get(name.split(".")[0], 0)


def n_params(c: dict) -> int:
    return sum(math.prod(shape) * max(rows(c, name), 1)
               for name, (shape, _) in shapes(c).items())


def make(c: dict, seed: int, dtype) -> dict:
    """The flat dict of weights, on the default device, in ``dtype``; a
    stack is drawn a row at a time inside ``lax.map``."""
    import jax
    import jax.numpy as jnp

    from benchmark.weights import seed_key

    spec = shapes(c)
    names = sorted(spec)
    f32 = jnp.float32
    w_std = STD * math.sqrt(PUBLISHED_WIDTH / c["d_model"])

    def draw(key, shape, kind):
        if kind == "a_log":
            x = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
        elif kind == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                key, shape, f32, math.log(1e-3), math.log(1e-1)))
            x = dt + jnp.log(-jnp.expm1(-dt))
        elif kind == "conv":
            x = jax.random.normal(key, shape, f32) / math.sqrt(c["d_conv"])
        elif kind == "w":
            x = w_std * jax.random.normal(key, shape, f32)
        else:  # "embed", "scale"
            x = STD * jax.random.normal(key, shape, f32)
            if kind == "scale":
                x = 1.0 + x
        return x.astype(dtype)

    @jax.jit
    def build(key):
        out = {n: draw(jax.random.fold_in(key, i), *spec[n])
               for i, n in enumerate(names) if not rows(c, n)}
        for g, grp in enumerate(("gdn", "attn", "ffn")):
            mine = [n for n in names if n.startswith(grp + ".")]

            def one_row(rkey, mine=mine):
                return {n: draw(jax.random.fold_in(rkey, j), *spec[n])
                        for j, n in enumerate(mine)}

            rkeys = jax.random.split(
                jax.random.fold_in(key, 10_000 + g), rows(c, grp))
            out.update(jax.lax.map(one_row, rkeys))
        return out

    return build(seed_key(seed))


# -- the program's types (never imported by the reference) -------------------


def transformer_config(cfg: dict, c: dict):
    from benchmark import harness

    import jax.numpy as jnp

    try:
        from torchdistx_tpu.models import GatedDeltaNetConfig, TransformerConfig
    except ImportError as e:
        raise harness.Refused(
            f"this checkout's program has no {FAMILY} family ({e})")
    # The rehearsal's products take float32 operands (``activation_dtype``
    # of its group): at width 64 the post-norm stack turns the rounding of
    # bfloat16 operands into logits 1 to 3 apart from the float32
    # reference's over 64 positions (the reference rounded the same way
    # reads 1.2 to 1.5, PERF.md section 2), which would rehearse the
    # rounding and not the wiring.
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[
        cfg.get("activation_dtype", "bfloat16")]
    return TransformerConfig(
        dtype=dtype,
        vocab_size=c["vocab_size"], d_model=c["d_model"],
        n_layers=c["n_layers"], n_heads=c["n_heads"],
        n_kv_heads=c["n_kv_heads"], head_dim=c["head_dim"], d_ff=c["d_ff"],
        max_seq_len=c["max_seq_len"], norm_eps=c["norm_eps"],
        positions="none", tie_embeddings=False,
        olmo_hybrid=GatedDeltaNetConfig(
            n_heads=c["lin_heads"], d_k=c["d_k"], d_v=c["d_v"],
            d_conv=c["d_conv"], allow_neg_eigval=c["allow_neg_eigval"],
            attn_period=c["attn_layer_period"],
            attn_offset=c["attn_layer_offset"]))


def param_tree(w: dict) -> dict:
    """The program's tree from the benchmark's flat weights.  The program
    keeps the conv's taps as [tap, channel] (its tail's layout): that one
    is a transposed copy, the other leaves are the same device arrays."""
    p = {"embedding": w["embed"], "final_norm": w["final_norm.scale"],
         "lm_head": w["lm_head"]}
    for name, x in w.items():
        grp, _, rest = name.partition(".")
        if grp not in ("gdn", "attn", "ffn"):
            continue
        rest = rest.removesuffix(".scale")
        if rest == "conv_w":
            x = x.transpose(0, 2, 1)
        p[f"{grp}_{rest}"] = x
    return {"params": p}


# -- correct ------------------------------------------------------------------


def reference_logits(cache: dict, c, quant, w, r, state_round=None):
    """float32 [n_served, vocab]: the logits that predict each served
    token of request ``r``."""
    from benchmark import check_serve
    from benchmark.reference import olmo_hybrid

    seq = list(r["tokens"]) + list(r["tokens_out"][:-1])
    pad = min(check_serve.pad_for(len(seq)), max(c["max_seq_len"], len(seq)))
    key = (pad, quant, state_round)
    if key not in cache:
        cache[key] = olmo_hybrid.Forward(
            c, olmo_hybrid.QUANT[quant], pad, olmo_hybrid.QUANT[state_round])
    return cache[key].logits(w, seq, len(r["tokens"]) - 1,
                             len(r["tokens_out"]))


def check(env, c, w, finished: list) -> dict:
    """``logit_gap`` as ``benchmark/check_serve.py`` defines it, against
    ``reference/olmo_hybrid.py``: the widest gap by which a served token's
    reference logit lies below the reference's best, over a sample of the
    finished requests, the longest among them.  ``--control fp8`` reads
    the same number for the reference in fp8 (per-tensor e4m3 on every
    weight matmul); ``--control bf16-state`` for the reference whose
    delta-rule state is rounded to bfloat16 after every position (a
    planted fault, read and reported: PERF.md)."""
    from benchmark import check_serve, harness

    limits = harness.load_json(
        env["root"], f"benchmark/limits/{env['cell']['name']}.json")
    picked = check_serve.sample(env, finished, limits["sample_requests"])
    worst, n_tokens, cache, control, flips = 0.0, 0, {}, 0.0, 0
    how = env.get("control") or None
    for r in picked:
        ref = reference_logits(cache, c, None, w, r)
        g = check_serve.gaps(ref, r["tokens_out"])
        worst = max(worst, float(g.max()))
        flips += int((g > 0).sum())
        n_tokens += len(r["tokens_out"])
        if how == "bf16-state":
            low = reference_logits(cache, c, None, w, r, state_round="bf16")
        elif how:
            low = reference_logits(cache, c, how, w, r)
        if how:
            control = max(control, float(
                check_serve.gaps(ref, low.argmax(-1)).max()))
    env["extra_notes"]["check"] = {
        "sampled": [r["rid"] for r in picked], "tokens": n_tokens,
        "served_tokens_not_the_reference_choice": flips}
    if how:
        env["extra_notes"]["control"] = {"precision": how,
                                         "logit_gap": control}
    lim = limits["rehearsal" if env["rehearse"] else "limits"]["logit_gap"]
    return {
        "logit_gap": {"value": worst, "limit": lim, "ok": worst <= lim},
        "tokens_compared": {"value": n_tokens, "limit": 1,
                            "ok": n_tokens > 0},
    }


# -- FLOPs --------------------------------------------------------------------


def served_flops(c: dict, requests: list, t_close: float) -> float:
    """FLOPs the model needs for every prompt position prefilled and every
    token handed over by ``t_close``: what ``serve.mfu_hybrid`` divides."""
    from benchmark.rooflines import gdn

    flops = 0.0
    for r in requests:
        if r["first"] is None or r["first"] > t_close:
            continue
        L, n = len(r["tokens"]), r["n"]
        flops += gdn.forward_flops(c, L, gdn.causal_pairs(L), 1)
        flops += gdn.forward_flops(
            c, n - 1, sum(L + j for j in range(1, n)), n - 1)
    return flops
