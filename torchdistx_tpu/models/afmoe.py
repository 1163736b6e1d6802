"""AFMoE decoder (arcee-ai Trinity): sandwich-normed blocks, gated and
QK-normed attention that is windowed or full by layer, a few leading
dense MLPs, then sigmoid-routed expert layers with one shared expert.

With ``d`` the model width and RMSNorm ``x * rsqrt(mean(x^2) + eps) * w``::

    h0 = E[token] * sqrt(d)                                (muP embedding)
    h  = h + RMS_post_attn(Attn(RMS_in(h)))
    h  = h + RMS_post_mlp(FFN(RMS_pre_mlp(h)))
    logits = W_head RMS_final(h)                           (untied)

    Attn:  q, k, v, g = W_q x, W_k x, W_v x, W_g x         (g as wide as q)
           q, k <- RMS over the head width (one gain vector each)
           "sliding" layers: rotary on q and k, keys 0 <= i - j < window
           "full" layers: no positional term, causal
           softmax(q k / sqrt(hd)) in float32; out = W_o (o * sigmoid(g))
    dense: W_down(silu(W_gate x) * W_up x)
    MoE:   s = sigmoid(W_r x) in float32 over ALL n_experts
           S = top-k of (s + b)       (b: a stored selection bias)
           w_e = route_scale * s_e / (sum_{e in S} s_e + 1e-20)
           y = Shared(x) + sum_{e in S} w_e Expert_e(x)

**Held experts.**  The layer is told which experts it holds
(``AfmoeConfig.first_expert`` / ``held_experts``): it routes over all
``n_experts`` and computes the part of the sum that its own experts
give, as one chip of an expert-parallel group does; the pairs that land
on experts held elsewhere contribute nothing here (the exchange that
would add the other chips' parts is not in this repository yet).  With
``held_experts == n_experts`` it is the whole layer.  Nothing is dropped
and there is no capacity: the (token, choice) pairs are sorted by
expert and go through three grouped products, each the Pallas kernel
``tdx_moe_experts_gmm`` (:func:`..ops.grouped_matmul.grouped_matmul`,
which reads a held expert's matrix once for each tile of rows its pairs
touch and none of an expert that got no pair), under the scope
``tdx_moe_experts``; the router runs under ``tdx_moe_router``.  The call
also returns how many pairs each held expert got.

The stack is not one block L times (dense and expert layers, window and
full attention), and a replica holds few of its layers, so the
parameters are per layer (``l<i>_<name>``) and the layers are walked by
a Python loop: no stacked tensor is sliced, whatever a layer is.
"""

from __future__ import annotations

import math
from typing import List, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.grouped_matmul import grouped_matmul
from .configs import TransformerConfig
from .layers import apply_rope

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
EXPERTS = "tdx_moe_experts"
ROUTER = "tdx_moe_router"
_NEG = -1e30


def check(cfg: TransformerConfig) -> None:
    a = cfg.afmoe
    if a is None:
        raise ValueError("the afmoe family needs cfg.afmoe")
    if len(a.layer_types) != cfg.n_layers or set(a.layer_types) - {
            "sliding", "full"}:
        raise ValueError(
            f"layer_types {a.layer_types!r} is not one of 'sliding' | 'full' "
            f"for each of the {cfg.n_layers} layers")
    if not (0 <= a.first_expert and a.held_experts >= 1
            and a.first_expert + a.held_experts <= a.n_experts):
        raise ValueError(
            f"held experts [{a.first_expert}, {a.first_expert + a.held_experts}"
            f") are not among the {a.n_experts} the router scores")
    if not (0 <= a.n_dense_layers <= cfg.n_layers):
        raise ValueError(f"n_dense_layers={a.n_dense_layers}")


def is_sliding(cfg, i: int) -> bool:
    return cfg.afmoe.layer_types[i] == "sliding"


def is_dense(cfg, i: int) -> bool:
    return i < cfg.afmoe.n_dense_layers


def group_rows(cfg) -> List[Tuple[bool, int]]:
    """Layer -> (sliding?, its row AMONG ITS KIND): the row of the cache
    group that holds only that kind's keys and values."""
    seen = {True: 0, False: 0}
    out = []
    for i in range(cfg.n_layers):
        s = is_sliding(cfg, i)
        out.append((s, seen[s]))
        seen[s] += 1
    return out


def n_window_layers(cfg) -> int:
    return sum(is_sliding(cfg, i) for i in range(cfg.n_layers))


def n_full_layers(cfg) -> int:
    return cfg.n_layers - n_window_layers(cfg)


def n_expert_layers(cfg) -> int:
    return cfg.n_layers - cfg.afmoe.n_dense_layers


# -- layer math (pure functions of one layer's parameters) -------------------


def rms_norm(x, scale, eps, dtype=None):
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale.astype(F32)).astype(dtype or x.dtype)


def _dot(x, w, dtype):
    return jnp.dot(x.astype(dtype), w.astype(dtype))


def rope_angles(cfg, positions):
    """positions [B, S] -> [B, S, head/2] (rotate-half, no scaling)."""
    hd = cfg.head_size
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    return positions.astype(F32)[..., None] * inv


def qkvg(cfg, lp, h, positions, sliding: bool):
    """q, gate [B, S, H, D]; k, v [B, S, KV, D]: projected, q and k
    normed over the head width and, on a sliding layer, rotated."""
    proj = lambda w: jnp.einsum("bsd,dhk->bshk", h.astype(cfg.dtype),
                                w.astype(cfg.dtype))
    q, k, v, g = (proj(lp[n]) for n in ("wq", "wk", "wv", "wg"))
    q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
    k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    if sliding:
        angles = rope_angles(cfg, positions)
        q, k = apply_rope(q, angles), apply_rope(k, angles)
    return q, k, v, g


def attn_out(cfg, lp, o, g):
    o = o.astype(cfg.dtype) * jax.nn.sigmoid(g.astype(F32)).astype(cfg.dtype)
    return jnp.einsum("bshk,hkd->bsd", o, lp["wo"].astype(cfg.dtype))


def dense_attention(q, k, v, positions, length, window=None):
    """A fresh prompt's attention over its own keys: q [B, S, H, D],
    k / v [B, S, KV, D], positions [B, S], length [B]; causal, below
    ``length``, and with ``window`` only keys ``0 <= i - j < window``."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    qf = (q.astype(F32) / math.sqrt(D)).reshape(B, S, KV, H // KV, D)
    s = jnp.einsum("bskgd,btkd->bkgst", qf, k.astype(F32))
    i, j = positions[:, :, None], positions[:, None, :]
    mask = (j <= i) & (j < length[:, None, None])
    if window is not None:
        mask &= i - j < window
    s = jnp.where(mask[:, None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(F32))
    return o.reshape(B, S, H, D).astype(q.dtype)


def gated_mlp(cfg, w_gate, w_up, w_down, x):
    gate = jax.nn.silu(_dot(x, w_gate, cfg.dtype))
    return _dot(gate * _dot(x, w_up, cfg.dtype), w_down, cfg.dtype)


def route(cfg, lp, x):
    """x [T, d] -> (experts [T, k] int32 among all n_experts, weights
    [T, k] float32).  Scores, choice and weights in float32 at full
    matmul precision: a choice that flips on rounding swaps a whole
    expert's output."""
    a = cfg.afmoe
    with jax.named_scope(ROUTER):
        s = jax.nn.sigmoid(jnp.dot(
            x.astype(F32), lp["router"].astype(F32), precision=HIGHEST))
        _, idx = jax.lax.top_k(s + lp["router_bias"].astype(F32), a.top_k)
        picked = jnp.take_along_axis(s, idx, axis=-1)
        w = a.route_scale * picked / (
            picked.sum(-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w


def held_expert_sum(cfg, lp, x, idx, w, valid):
    """The held experts' part of ``sum_e w_e Expert_e(x)``: x [T, d],
    idx / w [T, k], valid [T] (a padded position routes nowhere) ->
    (float32 [T, d], pairs a held expert got, int32 [held])."""
    a = cfg.afmoe
    T, d = x.shape
    k, n = a.top_k, a.held_experts
    local = idx.reshape(-1) - a.first_expert                  # [T*k]
    held = (local >= 0) & (local < n) & jnp.repeat(valid, k)
    key = jnp.where(held, local, n)          # pairs held elsewhere go last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((n + 1,), jnp.int32).at[key].add(1)[:n]
    xs = x.astype(cfg.dtype)[order // k]                      # [T*k, d]

    # The kernel's work follows the pairs, not the rows it is given (a
    # tile of rows past the last group is never visited; an idle step of
    # its static grid copies nothing), so the products take all T*k rows
    # and a program lowers ONE shape of the kernel: a few tiers of rows,
    # a shape each, cost the trinity cell 5.3 s of set-up in lowering.
    with jax.named_scope(EXPERTS):
        dot = lambda lhs, name: grouped_matmul(
            lhs, lp[name].astype(cfg.dtype), sizes)
        act = jax.nn.silu(dot(xs, "experts_w_gate")) * dot(xs, "experts_w_up")
        y = dot(act.astype(cfg.dtype), "experts_w_down")
    # Rows past the last group belong to no expert and hold whatever the
    # kernel left there: taken out by selection, never by a product.
    wk = jnp.where(held, w.reshape(-1), 0.0)[order]
    y = jnp.where((jnp.arange(T * k) < sizes.sum())[:, None],
                  y.astype(F32) * wk[:, None], 0.0)
    back = jnp.argsort(order)                 # the pairs in token order
    return y[back].reshape(T, k, d).sum(1), sizes


def moe(cfg, lp, x, valid):
    """x [B, S, d], valid [B, S] -> (y [B, S, d], pairs [held])."""
    B, S, d = x.shape
    flat = x.reshape(B * S, d)
    idx, w = route(cfg, lp, flat)
    routed, sizes = held_expert_sum(cfg, lp, flat, idx, w, valid.reshape(-1))
    shared = gated_mlp(cfg, lp["shared_w_gate"], lp["shared_w_up"],
                       lp["shared_w_down"], flat)
    return (shared.astype(F32) + routed).reshape(B, S, d), sizes


def ffn(cfg, lp, i: int, x, valid):
    """The layer's feed-forward on the normed input: (y, pairs or None)."""
    if is_dense(cfg, i):
        return gated_mlp(cfg, lp["w_gate"], lp["w_up"], lp["w_down"], x), None
    return moe(cfg, lp, x, valid)


def block(cfg, lp, i: int, x, valid, attention):
    """One layer: ``attention(q, k, v)`` -> o [B, S, H, D] is the
    caller's (dense, or through a cache).  Returns (x, pairs or None)."""
    eps = cfg.norm_eps
    x = x + rms_norm(attention(rms_norm(x, lp["norm_in"], eps, cfg.dtype)),
                     lp["norm_post_attn"], eps, x.dtype)
    # The router reads the normed input before it is rounded to the
    # activation dtype: one rounding less between it and a flipped choice.
    h = rms_norm(x, lp["norm_pre_mlp"], eps,
                 cfg.dtype if is_dense(cfg, i) else F32)
    y, pairs = ffn(cfg, lp, i, h, valid)
    return x + rms_norm(y, lp["norm_post_mlp"], eps, x.dtype), pairs


def embed_tokens(cfg, p, tokens):
    """The residual stream is float32 from here to the head (the products
    take ``cfg.dtype`` operands): rounding it to bfloat16 at each of a
    block's two adds moves the router's scores by more than anything
    else does, and a choice that flips swaps a whole expert's output."""
    return p["embedding"][tokens].astype(F32) * math.sqrt(cfg.d_model)


def head_logits(cfg, p, x):
    x = rms_norm(x, p["final_norm"], cfg.norm_eps)
    return _dot(x, p["lm_head"], cfg.dtype).astype(F32)


def full_forward(cfg, p, tokens):
    """tokens [B, S] -> logits [B, S, vocab] f32: no cache."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    length = jnp.full((B,), S, jnp.int32)
    valid = jnp.ones((B, S), bool)
    x = embed_tokens(cfg, p, tokens)
    for i, lp in enumerate(p["layers"]):
        sliding = is_sliding(cfg, i)

        def attention(h, lp=lp, sliding=sliding):
            q, k, v, g = qkvg(cfg, lp, h, positions, sliding)
            o = dense_attention(q, k, v, positions, length,
                                cfg.afmoe.window if sliding else None)
            return attn_out(cfg, lp, o, g)

        x, _ = block(cfg, lp, i, x, valid, attention)
    return head_logits(cfg, p, x)


# -- the flax module: parameters, and the full forward ------------------------


def layer_shapes(cfg, i: int) -> dict:
    """name -> (shape, kind) of layer ``i``'s parameters; kind is
    ``"w"`` (a matrix), ``"experts"`` (one matrix a held expert),
    ``"scale"`` (a norm's gains) or ``"bias"`` (the selection bias)."""
    a, d, hd = cfg.afmoe, cfg.d_model, cfg.head_size
    H, KV = cfg.n_heads, cfg.kv_heads
    out = {n: ((d,), "scale") for n in (
        "norm_in", "norm_post_attn", "norm_pre_mlp", "norm_post_mlp")}
    out.update({
        "wq": ((d, H, hd), "w"), "wk": ((d, KV, hd), "w"),
        "wv": ((d, KV, hd), "w"), "wg": ((d, H, hd), "w"),
        "wo": ((H, hd, d), "w"),
        "q_norm": ((hd,), "scale"), "k_norm": ((hd,), "scale"),
    })
    if is_dense(cfg, i):
        out.update({"w_gate": ((d, cfg.d_ff), "w"),
                    "w_up": ((d, cfg.d_ff), "w"),
                    "w_down": ((cfg.d_ff, d), "w")})
    else:
        de, n = a.d_expert, a.held_experts
        out.update({
            "router": ((d, a.n_experts), "w"),
            "router_bias": ((a.n_experts,), "bias"),
            "experts_w_gate": ((n, d, de), "experts"),
            "experts_w_up": ((n, d, de), "experts"),
            "experts_w_down": ((n, de, d), "experts"),
            "shared_w_gate": ((d, de), "w"), "shared_w_up": ((d, de), "w"),
            "shared_w_down": ((de, d), "w"),
        })
    return out


def _experts_init(std: float):
    """N(0, std) one expert at a time: the float32 draw of a whole
    layer's experts (1.2 GB a tensor at the published width) never exists."""

    def init(key, shape, dtype=F32):
        one = lambda k: (std * jax.random.normal(k, shape[1:], F32)
                         ).astype(dtype)
        return jax.lax.map(one, jax.random.split(key, shape[0]))

    return init


class AfmoeModel(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens: jax.Array) -> jax.Array:
        """tokens [B, S] int32 -> logits [B, S, vocab] in f32."""
        cfg = self.cfg
        pd, d = cfg.param_dtype, cfg.d_model
        inits = {"w": (nn.initializers.normal(0.02), pd),
                 "experts": (_experts_init(0.02), pd),
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
            "the afmoe family is no stack of identical blocks: the serving "
            "programs walk its layers themselves (serve/programs.py, the "
            "afmoe builders)")


def param_tree(flat: dict) -> dict:
    """The view the layer functions take, from the module's flat
    parameters (``params["params"]``): ``l3_wq`` -> ``["layers"][3]["wq"]``.
    No copy."""
    layers: dict = {}
    top = {}
    for k, v in flat.items():
        head, _, name = k.partition("_")
        if head[:1] == "l" and head[1:].isdigit() and name:
            layers.setdefault(int(head[1:]), {})[name] = v
        else:
            top[k] = v
    top["layers"] = [layers[i] for i in sorted(layers)]
    return top


def make_afmoe(cfg: TransformerConfig) -> AfmoeModel:
    check(cfg)
    return AfmoeModel(cfg)
