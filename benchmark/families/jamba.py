"""Everything of the benchmark that has the shape of the ``jamba`` family
(AI21-Jamba2: Mamba-1 layers beside attention layers), in one module that
a configuration's file names (``family_module``) and the runner
``kinds/serve_hybrid.py`` loads: the sizes, the weights from ``--seed``
under the benchmark's own names, the adapter into the program's types,
the comparison that decides ``correct``, and the FLOPs
(``rooflines/ssm.py``).  Only ``transformer_config`` and ``param_tree``
touch the program; the reference (``reference/jamba.py``) never imports
this file's program side.

Weights.  Matrices N(0, 0.02) at the published width, norm scales and
the skip ``D`` 1 + N(0, 0.02), the embedding and the conv's bias
N(0, 0.02), as in ``benchmark/weights.py`` (every tensor moves the
output).  At another width (the tests' and the rehearsal's 64) a
matrix's deviation is 0.02 x sqrt(2560 / width), so that a projection of
a normed vector has the size it has at 2,560: at 0.02 and width 64 every
layer adds next to nothing and the tied head echoes the input token,
which no comparison of logits can see through.  Three tensors are drawn
otherwise,
because at N(0, 0.02) the recurrence would carry nothing: the conv's
taps N(0, 1/d_conv), so that its output is of the order of its input;
``A_log = log(a)`` with ``a`` uniform in [1, d_state] for every (channel,
state) pair, the range of the published initialisation; and ``dt_bias``
the inverse softplus of a step size log-uniform in [0.001, 0.1] (the
Mamba paper's).  With the step's projection at N(0, 0.02) the step
stays within a factor of about two of that draw, so ``exp(step * A)``
lies between exp(-1.6 * 2) and exp(-0.001 / 2): spread over (0, 1),
neither 0 nor 1 everywhere.
"""

from __future__ import annotations

import math

STD = 0.02
PUBLISHED_WIDTH = 2560
FAMILY = "jamba"


def dims(cfg: dict) -> dict:
    """The model's sizes from the source's own keys."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    per, off, L = (cfg["attn_layer_period"], cfg["attn_layer_offset"],
                   cfg["num_hidden_layers"])
    n_attn = sum(1 for i in range(L) if i % per == off)
    return {
        "d_model": d, "n_heads": h,
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg.get("head_dim") or d // h,
        "d_ff": cfg["intermediate_size"], "n_layers": L,
        "vocab_size": cfg["vocab_size"],
        "max_seq_len": cfg["max_position_embeddings"],
        "norm_eps": float(cfg["rms_norm_eps"]),
        "d_inner": cfg["mamba_expand"] * d,
        "d_state": cfg["mamba_d_state"], "d_conv": cfg["mamba_d_conv"],
        "dt_rank": cfg["mamba_dt_rank"], "mamba_expand": cfg["mamba_expand"],
        "attn_layer_period": per, "attn_layer_offset": off,
        "n_attn_layers": n_attn, "n_mamba_layers": L - n_attn,
        "gated_mlp": True, "tied_head": True,
    }


def shapes(c: dict) -> dict:
    """name -> (shape WITHOUT the layer axis, kind).  ``mamba.*`` has one
    row a Mamba layer, ``attn.*`` one an attention layer, ``ffn.*`` one a
    layer, each in layer order."""
    d, h, kv, hd, ff, v = (c["d_model"], c["n_heads"], c["n_kv_heads"],
                           c["head_dim"], c["d_ff"], c["vocab_size"])
    di, n, k, r = c["d_inner"], c["d_state"], c["d_conv"], c["dt_rank"]
    return {
        "embed": ((v, d), "embed"),
        "final_norm.scale": ((d,), "scale"),
        "mamba.in_proj": ((d, 2 * di), "w"),
        "mamba.conv_w": ((di, k), "conv"),
        "mamba.conv_b": ((di,), "w"),
        "mamba.x_proj": ((di, r + 2 * n), "w"),
        "mamba.dt_norm.scale": ((r,), "scale"),
        "mamba.b_norm.scale": ((n,), "scale"),
        "mamba.c_norm.scale": ((n,), "scale"),
        "mamba.dt_proj": ((r, di), "w"),
        "mamba.dt_bias": ((di,), "dt_bias"),
        "mamba.A_log": ((di, n), "a_log"),
        "mamba.D": ((di,), "scale"),
        "mamba.out_proj": ((di, d), "w"),
        "attn.wq": ((d, h, hd), "w"),
        "attn.wk": ((d, kv, hd), "w"),
        "attn.wv": ((d, kv, hd), "w"),
        "attn.wo": ((h, hd, d), "w"),
        "ffn.norm0.scale": ((d,), "scale"),
        "ffn.norm1.scale": ((d,), "scale"),
        "ffn.w_gate": ((d, ff), "w"),
        "ffn.w_up": ((d, ff), "w"),
        "ffn.w_down": ((ff, d), "w"),
    }


def rows(c: dict, name: str) -> int:
    return {"mamba": c["n_mamba_layers"], "attn": c["n_attn_layers"],
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
            x = jnp.log(jax.random.uniform(key, shape, f32, 1.0,
                                           float(c["d_state"])))
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
        for g, grp in enumerate(("mamba", "attn", "ffn")):
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

    try:
        from torchdistx_tpu.models import MambaConfig, TransformerConfig
    except ImportError as e:
        raise harness.Refused(
            f"this checkout's program has no {FAMILY} family ({e})")
    return TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["d_model"],
        n_layers=c["n_layers"], n_heads=c["n_heads"],
        n_kv_heads=c["n_kv_heads"], head_dim=c["head_dim"], d_ff=c["d_ff"],
        max_seq_len=c["max_seq_len"], norm_eps=c["norm_eps"],
        positions="none", tie_embeddings=True,
        mamba=MambaConfig(
            d_state=c["d_state"], d_conv=c["d_conv"],
            expand=c["mamba_expand"], dt_rank=c["dt_rank"],
            attn_period=c["attn_layer_period"],
            attn_offset=c["attn_layer_offset"]))


def param_tree(w: dict) -> dict:
    """The program's tree from the benchmark's flat weights.  The program
    keeps ``A_log`` as [state, channel] and the conv's taps as [tap,
    channel] (its state's layout); those two are transposed copies, the
    other leaves are the same device arrays."""
    p = {"embedding": w["embed"], "final_norm": w["final_norm.scale"]}
    for name, x in w.items():
        grp, _, rest = name.partition(".")
        if grp not in ("mamba", "attn", "ffn"):
            continue
        rest = rest.removesuffix(".scale")
        if rest in ("A_log", "conv_w"):
            x = x.transpose(0, 2, 1)
        p[f"{grp}_{rest}"] = x
    return {"params": p}


# -- correct ------------------------------------------------------------------


def reference_logits(cache: dict, c, quant, w, r, state_round=None):
    """float32 [n_served, vocab]: the logits that predict each served
    token of request ``r``."""
    from benchmark import check_serve
    from benchmark.reference import jamba

    seq = list(r["tokens"]) + list(r["tokens_out"][:-1])
    pad = min(check_serve.pad_for(len(seq)), max(c["max_seq_len"], len(seq)))
    key = (pad, quant, state_round)
    if key not in cache:
        cache[key] = jamba.Forward(
            c, jamba.QUANT[quant], pad, jamba.QUANT[state_round])
    return cache[key].logits(w, seq, len(r["tokens"]) - 1,
                             len(r["tokens_out"]))


def check(env, c, w, finished: list) -> dict:
    """``logit_gap`` as ``benchmark/check_serve.py`` defines it, against
    ``reference/jamba.py``: the widest gap by which a served token's
    reference logit lies below the reference's best, over a sample of the
    finished requests, the longest among them.  ``--control fp8`` reads
    the same number for the reference in fp8; ``--control bf16-state``
    for the reference whose recurrent state is rounded to bfloat16 after
    every step (a planted fault, read and reported: PERF.md)."""
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
    from benchmark.rooflines import ssm

    flops = 0.0
    for r in requests:
        if r["first"] is None or r["first"] > t_close:
            continue
        L, n = len(r["tokens"]), r["n"]
        flops += ssm.forward_flops(c, L, ssm.causal_pairs(L), 1)
        flops += ssm.forward_flops(
            c, n - 1, sum(L + j for j in range(1, n)), n - 1)
    return flops
