"""openPangu-Ultra-MoE decoder: sandwich-normed blocks of latent
attention (MLA, ``models/mla.py``) over a leading dense MLP and then
sigmoid-routed expert layers with one shared expert.

With ``d`` the model width and RMSNorm ``x * rsqrt(mean(x^2) + eps) * w``::

    h0 = E[token]                                        (no scale)
    h  = h + RMS_post_attn(MLA(RMS_in(h)))
    h  = h + RMS_post_mlp(FFN(RMS_pre_mlp(h)))
    logits = W_head RMS_final(h)                         (untied)

    dense (the first n_dense_layers): W_down(silu(W_gate x) * W_up x)
    MoE:   s = sigmoid(W_r x) in float32 over ALL n_experts
           S = top-k of (s + b)       (b: a selection bias, choice only)
           w_e = route_scale * s_e / (sum_{e in S} s_e + 1e-20)
           y = Shared(x) + sum_{e in S} w_e Expert_e(x)

The block, the expert layer and the head are ``models/afmoe.py``'s, which
reads the expert layer's routing and share from ``cfg.experts``
(:class:`..configs.RoutedExperts`): a replica holds ``held_experts`` of
them and computes their part of the sum, as one chip of an
expert-parallel group does.  What this family adds is the attention and
the embedding; its parameters are per layer (``l<i>_<name>``), as
afmoe's are, and its layers are walked by a Python loop.
"""

from __future__ import annotations

from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from . import afmoe, mla
from .configs import TransformerConfig

F32 = jnp.float32
head_logits = afmoe.head_logits
param_tree = afmoe.param_tree
n_expert_layers = afmoe.n_expert_layers


def check(cfg: TransformerConfig) -> None:
    if cfg.mla is None or cfg.experts is None:
        raise ValueError(
            "the pangu_ultra_moe family needs cfg.mla and cfg.experts")
    afmoe.check_experts(cfg)


def layer_shapes(cfg, i: int) -> dict:
    """name -> (shape, kind) of layer ``i``'s parameters, kinds as in
    ``models/afmoe.py`` ``layer_shapes``."""
    out = {n: ((cfg.d_model,), "scale") for n in (
        "norm_in", "norm_post_attn", "norm_pre_mlp", "norm_post_mlp")}
    out.update(mla.layer_shapes(cfg))
    out.update(afmoe.ffn_shapes(cfg, i))
    return out


def embed_tokens(cfg, p, tokens):
    """The residual stream is float32 from here to the head, as in
    ``models/afmoe.py`` (the router reads it)."""
    return p["embedding"][tokens].astype(F32)


def layers(cfg, p, x, valid, attention: Callable):
    """x through every layer: ``attention(i, lp, h)`` -> [B, S, d] is the
    caller's (dense, or through a cache).  Returns (x, the pairs each
    expert layer's held experts got, int32 [expert layers, held])."""
    counts = []
    for i, lp in enumerate(p["layers"]):
        x, got = afmoe.block(cfg, lp, i, x, valid,
                             lambda h, i=i, lp=lp: attention(i, lp, h))
        if got is not None:
            counts.append(got)
    return x, jnp.stack(counts).astype(jnp.int32)


def full_forward(cfg, p, tokens):
    """tokens [B, S] -> logits [B, S, vocab] f32: no cache, expanded."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    length = jnp.full((B,), S, jnp.int32)
    angles = mla.rope_angles(cfg, positions)

    def attention(i, lp, h):
        q_nope, q_rope, c_kv, k_rope = mla.project(cfg, lp, h, angles)
        k_nope, v = mla.expand(cfg, lp, c_kv)
        return mla.out(cfg, lp, mla.dense_attention(
            cfg, q_nope, q_rope, k_nope, k_rope, v, positions, length))

    x, _ = layers(cfg, p, embed_tokens(cfg, p, tokens),
                  jnp.ones((B, S), bool), attention)
    return head_logits(cfg, p, x)


class PanguUltraMoEModel(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens: jax.Array) -> jax.Array:
        """tokens [B, S] int32 -> logits [B, S, vocab] in f32."""
        cfg = self.cfg
        pd, d = cfg.param_dtype, cfg.d_model
        inits = {"w": (nn.initializers.normal(0.02), pd),
                 "experts": (afmoe._experts_init(0.02), pd),
                 "scale": (nn.initializers.ones, F32),
                 "bias": (nn.initializers.zeros, F32)}
        flat = {
            "embedding": self.param("embedding", inits["w"][0],
                                    (cfg.vocab_size, d), pd),
            "lm_head": self.param("lm_head", inits["w"][0],
                                  (d, cfg.vocab_size), pd),
            "final_norm": self.param("final_norm", nn.initializers.ones,
                                     (d,), F32),
        }
        for i in range(cfg.n_layers):
            for name, (shape, kind) in layer_shapes(cfg, i).items():
                init, dt = inits[kind]
                flat[f"l{i}_{name}"] = self.param(
                    f"l{i}_{name}", init, shape, dt)
        return full_forward(cfg, param_tree(flat), tokens)

    def decode_decomposition(self):
        raise NotImplementedError(
            "the pangu_ultra_moe family is no stack of identical blocks: the "
            "serving programs walk its layers themselves (serve/programs.py, "
            "the pangu_ultra_moe builders)")


def make_pangu_ultra_moe(cfg: TransformerConfig) -> PanguUltraMoEModel:
    check(cfg)
    return PanguUltraMoEModel(cfg)
