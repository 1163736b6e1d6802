"""The only place where the benchmark's data meets the program's types:
a configuration's file -> ``TransformerConfig`` / ``ServeConfig``, and the
benchmark's flat weights -> the program's parameter tree.  Imported by
the cell kinds after the set-up clock has started; the reference never
imports it."""

from __future__ import annotations


def transformer_config(cfg: dict, c: dict):
    from torchdistx_tpu.models import TransformerConfig

    common = dict(
        vocab_size=c["vocab_size"], d_model=c["d_model"],
        n_layers=c["n_layers"], n_heads=c["n_heads"], d_ff=c["d_ff"],
        max_seq_len=c["max_seq_len"], norm_eps=c["norm_eps"],
    )
    if cfg["family"] == "llama":
        return TransformerConfig(
            n_kv_heads=c["n_kv_heads"], head_dim=c["head_dim"],
            rope_theta=c["rope_theta"], tie_embeddings=False, **common)
    return TransformerConfig(
        use_bias=True, activation="gelu", norm="layernorm",
        positions="learned", tie_embeddings=True, **common)


def serve_config(cfg: dict, engine: dict):
    """``serve_config`` of the configuration's file, with the traffic
    mix's ``engine`` group (buckets, chunk, page-table width) on top."""
    from torchdistx_tpu.serve import ServeConfig

    kw = dict(cfg["serve_config"])
    kw.update(engine)
    for k in ("prefill_buckets", "spec_buckets"):
        if k in kw:
            kw[k] = tuple(kw[k])
    return ServeConfig(**kw)


def param_tree(family: str, w: dict) -> dict:
    """The program's flax tree from the benchmark's flat weights (no
    copy: the leaves are the same device arrays)."""
    norm = "RMSNorm" if family == "llama" else "LayerNorm"

    def dense(name):
        out = {"kernel": w[f"layers.{name}"]}
        if f"layers.{name}.bias" in w:
            out["bias"] = w[f"layers.{name}.bias"]
        return out

    def nrm(prefix):
        out = {"scale": w[f"{prefix}.scale"]}
        if f"{prefix}.bias" in w:
            out["bias"] = w[f"{prefix}.bias"]
        return out

    mlp = {"w_up": dense("w_up"), "w_down": dense("w_down")}
    if family == "llama":
        mlp["w_gate"] = dense("w_gate")
    block = {
        f"{norm}_0": nrm("layers.norm0"), f"{norm}_1": nrm("layers.norm1"),
        "attn": {n: dense(n) for n in ("wq", "wk", "wv", "wo")},
        "mlp": mlp,
    }
    p = {"blocks": {"block": block}, "final_norm": nrm("final_norm")}
    if family == "llama":
        p["embed"] = {"embedding": w["embed"]}
        p["lm_head"] = {"kernel": w["lm_head"]}
    else:
        p["wte"] = {"embedding": w["wte"]}
        p["wpe"] = {"embedding": w["wpe"]}
    return {"params": p}


def flat_from_tree(family: str, tree: dict) -> dict:
    """The inverse of ``param_tree`` (for reading the program's state
    back under the benchmark's names)."""
    norm = "RMSNorm" if family == "llama" else "LayerNorm"
    p = tree["params"]
    blk = p["blocks"]["block"]
    out = {}

    def put(name, sub, key):
        if key in sub:
            out[name] = sub[key]

    for i in (0, 1):
        put(f"layers.norm{i}.scale", blk[f"{norm}_{i}"], "scale")
        put(f"layers.norm{i}.bias", blk[f"{norm}_{i}"], "bias")
    for n in ("wq", "wk", "wv", "wo"):
        put(f"layers.{n}", blk["attn"][n], "kernel")
        put(f"layers.{n}.bias", blk["attn"][n], "bias")
    for n in ("w_gate", "w_up", "w_down"):
        if n in blk["mlp"]:
            put(f"layers.{n}", blk["mlp"][n], "kernel")
            put(f"layers.{n}.bias", blk["mlp"][n], "bias")
    put("final_norm.scale", p["final_norm"], "scale")
    put("final_norm.bias", p["final_norm"], "bias")
    if family == "llama":
        out["embed"] = p["embed"]["embedding"]
        out["lm_head"] = p["lm_head"]["kernel"]
    else:
        out["wte"] = p["wte"]["embedding"]
        out["wpe"] = p["wpe"]["embedding"]
    return out
