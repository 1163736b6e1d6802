"""Reading a configuration's file: the sizes the benchmark's own code
uses (weights, reference, FLOP counts), under the benchmark's own names.
Nothing here touches the program."""

from __future__ import annotations

import json
import os


def load(root: str, rel: str) -> dict:
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def dims(cfg: dict) -> dict:
    """The model's sizes from the source's own keys."""
    if cfg["family"] == "llama":
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        return {
            "d_model": d, "n_heads": h,
            "n_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg.get("head_dim") or d // h,
            "d_ff": cfg["intermediate_size"],
            "n_layers": cfg["num_hidden_layers"],
            "vocab_size": cfg["vocab_size"],
            "max_seq_len": cfg["max_position_embeddings"],
            "rope_theta": float(cfg["rope_theta"]),
            "norm_eps": float(cfg["rms_norm_eps"]),
            "gated_mlp": True, "tied_head": False,
        }
    if cfg["family"] == "gpt2":
        d, h = cfg["n_embd"], cfg["n_head"]
        return {
            "d_model": d, "n_heads": h, "n_kv_heads": h, "head_dim": d // h,
            "d_ff": cfg.get("n_inner") or 4 * d,
            "n_layers": cfg["n_layer"],
            "vocab_size": cfg["vocab_size"],
            "max_seq_len": cfg["n_positions"],
            "norm_eps": float(cfg["layer_norm_epsilon"]),
            "gated_mlp": False, "tied_head": True,
        }
    raise ValueError(f"unknown family {cfg['family']!r}")
