"""Olmo-Hybrid decoder (allenai/Olmo-Hybrid-7B): Gated DeltaNet
linear-attention layers, three to each full-attention layer, Olmo's
post-norm block, no positional term, untied head.

Layer ``i`` (eps ``cfg.norm_eps`` everywhere)::

    h = x + RMSNorm(Mixer_i(x))          y = h + RMSNorm(MLP(h))
    full attention (i % attn_period == attn_offset), MHA:
        q = RMSNorm(x W_q), k = RMSNorm(x W_k)   (over the whole projection)
        v = x W_v ; softmax(q k^T / sqrt(hd) + causal) v W_o
    Gated DeltaNet (H heads, d_k, d_v):
        [q, k, v] = silu(conv1d_causal([x W_q, x W_k, x W_v]; depthwise,
                         kernel d_conv, no bias))
        q, k = l2norm(q), l2norm(k) per head ; q = q / sqrt(d_k)
        beta = 2 sigmoid(x W_b)            (sigmoid alone without
                                            allow_neg_eigval)
        g = -exp(A_log) softplus(x W_a + dt_bias) ; alpha = exp(g)
        S_t = alpha_t S_{t-1} ; u_t = beta_t (v_t - S_t^T k_t)
        S_t = S_t + k_t u_t^T ; o_t = S_t^T q_t
        out = (RMSNorm_head(o) * silu(x W_g)) W_o

The stack has the same shape as Jamba's (a recurrent kind and an
attention layer a period), so it is walked by
:func:`.jamba.scan_layers` over three parameter stacks, ``gdn`` (one row
a linear layer), ``attn`` and ``ffn`` (the norms and MLP of every layer).

Layouts are the chip's: the recurrent state of a linear layer is
``[B, d_k, H * d_v]`` float32 (head ``h`` is columns ``[h d_v, (h+1)
d_v)``; a minor dim of 192 would pad to 256), the conv tail time-major
``[d_conv - 1, B, 2 H d_k + H d_v]`` over q, k and v's channels in that
order.  The rule itself runs in the two kernels of
:mod:`..ops.gdn`: ``tdx_gdn_chunk`` for a prefill or a chunk (one
sequence, positions in chunks of 64, resumed from a lane's state) and
``tdx_gdn_decode_update`` for a decode tick (every lane, one position,
in place on the whole state).  ``q``, ``k`` and ``v`` enter them in the
activation dtype, the state and the rule are float32, and so is the
residual stream (:func:`embed_tokens` says why).  A position past
``n_valid`` gets ``beta`` 0 and ``g`` 0 (the identity) and leaves the
conv tail as it was.
"""

from __future__ import annotations

import math
from typing import Callable, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import gdn as gdn_ops
from .configs import TransformerConfig
from . import jamba
from .jamba import _dot, attn_out, mlp, rms_norm
from .layers import default_attention

F32 = jnp.float32
DECODE_UPDATE = gdn_ops.DECODE_UPDATE
CHUNK = gdn_ops.CHUNK

__all__ = ["OlmoHybridModel", "make_olmo_hybrid"]


def layer_counts(cfg: TransformerConfig) -> Tuple[int, int, int]:
    """(periods, linear layers before a period's attention layer, after)."""
    o = cfg.olmo_hybrid
    if o is None:
        raise ValueError("the olmo_hybrid family needs cfg.olmo_hybrid")
    if cfg.n_layers % o.attn_period or not 0 <= o.attn_offset < o.attn_period:
        raise ValueError(
            f"n_layers={cfg.n_layers} is not a whole number of periods of "
            f"{o.attn_period} with the attention layer at {o.attn_offset}")
    return (cfg.n_layers // o.attn_period, o.attn_offset,
            o.attn_period - o.attn_offset - 1)


def n_linear_layers(cfg: TransformerConfig) -> int:
    periods, pre, post = layer_counts(cfg)
    return periods * (pre + post)


def n_full_layers(cfg: TransformerConfig) -> int:
    return layer_counts(cfg)[0]


def widths(cfg: TransformerConfig) -> Tuple[int, int, int, int, int]:
    """(H, d_k, d_v, H d_v, conv channels 2 H d_k + H d_v)."""
    o = cfg.olmo_hybrid
    H, dk, dv = o.n_heads, o.d_k, o.d_v
    return H, dk, dv, H * dv, 2 * H * dk + H * dv


# -- layer math ----------------------------------------------------------------


def block(cfg, f, x, mixer: Callable):
    """One layer around its mixer, Olmo's post-norm ("reordered norm"):
    ``h = x + RMSNorm(mixer(x))``, ``y = h + RMSNorm(MLP(h))``."""
    eps = cfg.norm_eps
    x = x + rms_norm(mixer(x), f["post_mixer_norm"], eps)
    return x + rms_norm(mlp(cfg, f, x), f["post_ffn_norm"], eps)


def qkv(cfg, a, h):
    """q [B, S, H, D], k and v [B, S, KV, D]; q and k RMS-normed over the
    whole projection (QK-norm), no bias, no rotary."""
    B, S, _ = h.shape
    proj = lambda w: jnp.einsum("bsd,dhk->bshk", h.astype(cfg.dtype),
                                w.astype(cfg.dtype))

    def normed(w, scale):
        y = proj(w)
        return rms_norm(y.reshape(B, S, -1), scale, cfg.norm_eps).reshape(
            y.shape)

    return (normed(a["wq"], a["q_norm"]), normed(a["wk"], a["k_norm"]),
            proj(a["wv"]))


def _l2norm(x, eps):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def _inputs(cfg, m, h, tail, n_valid):
    """What the rule takes from a layer input ``h`` [B, S, d]: time-major
    q, k [S, B, H, d_k] and v [S, B, H, d_v] in the activation dtype,
    beta and g [S, B, H] float32, the output gate [B, S, H d_v], and the
    conv tail [K-1, B, C] the call leaves (the last ``d_conv - 1`` REAL
    inputs)."""
    o = cfg.olmo_hybrid
    H, dk, dv, HV, C = widths(cfg)
    B, S, _ = h.shape
    K = o.d_conv
    x = jnp.concatenate([_dot(h, m["wq"], cfg.dtype), _dot(h, m["wk"], cfg.dtype),
                         _dot(h, m["wv"], cfg.dtype)], -1)   # [B, S, C]
    full = jnp.concatenate([tail.astype(cfg.dtype), x.transpose(1, 0, 2)], 0)
    idx = n_valid[None, :] + jnp.arange(K - 1, dtype=jnp.int32)[:, None]
    new_tail = jnp.take_along_axis(full, idx[:, :, None], axis=0)
    w = m["conv_w"].astype(F32)                              # [K, C]
    conv = jax.nn.silu(sum(w[k] * full[k:k + S].astype(F32) for k in range(K)))
    eps = cfg.norm_eps
    q = _l2norm(conv[..., :H * dk].reshape(S, B, H, dk), eps) / math.sqrt(dk)
    k = _l2norm(conv[..., H * dk:2 * H * dk].reshape(S, B, H, dk), eps)
    v = conv[..., 2 * H * dk:].reshape(S, B, H, dv)
    ab = lambda w: _dot(h, w, cfg.dtype).astype(F32).transpose(1, 0, 2)
    beta = jax.nn.sigmoid(ab(m["wb"])) * (2.0 if o.allow_neg_eigval else 1.0)
    g = -jnp.exp(m["A_log"].astype(F32)) * jax.nn.softplus(
        ab(m["wa"]) + m["dt_bias"].astype(F32))
    gate = _dot(h, m["wg"], cfg.dtype)
    act = lambda t: t.astype(cfg.dtype)
    return act(q), act(k), act(v), beta, g, gate, new_tail.astype(tail.dtype)


def _output(cfg, m, o, gate):
    """o [B, S, H, d_v] float32 -> [B, S, d]: RMSNorm a head (one scale of
    width d_v), gated by silu of the gate, then W_o."""
    B, S = o.shape[:2]
    o = rms_norm(o, m["o_norm"], cfg.norm_eps).reshape(B, S, -1)
    y = o * jax.nn.silu(gate.astype(F32))
    return _dot(y.astype(cfg.dtype), m["wo"], cfg.dtype)


def _heads_major(s, cfg):
    """A lane's state [d_k, H d_v] -> [H, d_k, d_v], and back."""
    H, dk, dv, _, _ = widths(cfg)
    return s.reshape(dk, H, dv).transpose(1, 0, 2)


def _lane_major(s, cfg):
    H, dk, dv, HV, _ = widths(cfg)
    return s.transpose(1, 0, 2).reshape(dk, HV)


def gdn_mixer(cfg, m, h, s, tail, n_valid):
    """h [B, S, d]; s [B, d_k, H d_v] float32, each lane's state before
    the call; tail [K-1, B, C]; n_valid [B] int32, how many of the S
    positions are real (left-aligned).  A lane at a time through
    ``tdx_gdn_chunk``.  Returns (out [B, S, d], s', tail')."""
    q, k, v, beta, g, gate, tail = _inputs(cfg, m, h, tail, n_valid)
    outs, states = [], []
    for b in range(h.shape[0]):
        o, sb = gdn_ops.gdn_chunk(q[:, b], k[:, b], v[:, b], beta[:, b],
                                  g[:, b], _heads_major(s[b], cfg), n_valid[b])
        outs.append(o)
        states.append(_lane_major(sb, cfg))
    o = jnp.stack(outs, 0)                                   # [B, S, H, d_v]
    return _output(cfg, m, o, gate), jnp.stack(states, 0), tail


def gdn_decode(cfg, m, h, ssm, conv, layer, n_valid):
    """A decode tick's mixer: h [B, 1, d] for every lane; ``ssm`` the
    WHOLE state [L, B, d_k, H d_v] and ``conv`` the whole tail [L, K-1,
    B, C], of which row ``layer`` is advanced in place.  Returns
    (out [B, 1, d], ssm', conv')."""
    with jax.named_scope(DECODE_UPDATE):
        tail = jax.lax.dynamic_index_in_dim(conv, layer, 0, keepdims=False)
        q, k, v, beta, g, gate, tail = _inputs(cfg, m, h, tail, n_valid)
        conv = jax.lax.dynamic_update_index_in_dim(conv, tail, layer, 0)
    o, ssm = gdn_ops.gdn_decode_update(ssm, layer, q[0], k[0], v[0],
                                       beta[0], g[0], n_valid)
    return _output(cfg, m, o[:, None], gate), ssm, conv


def serve_mixer(cfg, m, h, ssm, conv, g, mixer_state):
    """The mixer as the serving programs run it on the cache's state
    (serve/programs.py, the hybrid builders): a decode tick's accessor
    (``mixer_state.every_lane``) takes the kernel that works in place on
    the whole state; a one-sequence call's (``mixer_state(ssm, conv, g)``
    -> ``(s, tail, n_valid, put)``) gives the lane's rows and how to put
    them back.  Returns ``(out, ssm, conv)``."""
    if getattr(mixer_state, "every_lane", False):
        return gdn_decode(cfg, m, h, ssm, conv, g, mixer_state.n_valid)
    with jax.named_scope(CHUNK):
        s, tail, n_valid, put = mixer_state(ssm, conv, g)
    out, s, tail = gdn_mixer(cfg, m, h, s, tail, n_valid)
    with jax.named_scope(CHUNK):
        ssm, conv = put(ssm, conv, g, s, tail)
    return out, ssm, conv


def scan_layers(cfg, p, x, carry, rec_layer: Callable,
                attn_layer: Callable):
    """:func:`.jamba.scan_layers` over this family's groups and pattern."""
    return jamba.scan_layers(cfg, p, x, carry, rec_layer, attn_layer,
                             counts=layer_counts(cfg), rec="gdn")


def embed_tokens(cfg, p, tokens):
    """The residual stream starts, and stays, in float32: a post-norm
    block adds every mixer's and MLP's output RMS-normed to unit size, so
    the stream grows layer by layer and a bfloat16 sum would round each
    addition at the stream's size (three times the logits' error at the
    rehearsal's size, PERF.md section 2).  Every matmul still takes
    bfloat16 operands."""
    return p["embed"]["embedding"][tokens].astype(F32)


def head_logits(cfg, p, x):
    x = rms_norm(x, p["final_norm"]["scale"], cfg.norm_eps)
    return jnp.dot(x.astype(cfg.param_dtype),
                   p["lm_head"]["kernel"].astype(cfg.param_dtype)).astype(F32)


def full_forward(cfg, p, tokens):
    """tokens [B, S] -> logits [B, S, vocab] f32: no cache, zero states."""
    B, S = tokens.shape
    H, dk, dv, HV, C = widths(cfg)
    K = cfg.olmo_hybrid.d_conv
    x = embed_tokens(cfg, p, tokens)
    n_valid = jnp.full((B,), S, jnp.int32)

    def rec_layer(m, f, x, carry, g):
        def mixer(h):
            return gdn_mixer(cfg, m, h, jnp.zeros((B, dk, HV), F32),
                             jnp.zeros((K - 1, B, C), cfg.dtype), n_valid)[0]

        return block(cfg, f, x, mixer), carry

    def attn_layer(a, f, x, carry, j):
        def mixer(h):
            q, k, v = qkv(cfg, a, h)
            return attn_out(cfg, a, default_attention(q, k, v, causal=True))

        return block(cfg, f, x, mixer), carry

    x, _ = scan_layers(cfg, p, x, (), rec_layer, attn_layer)
    return head_logits(cfg, p, x)


# -- the flax module: parameters, and the full forward ------------------------


def _dt_bias_init(key, shape, dtype=F32):
    """Inverse softplus of step sizes log-uniform in [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, F32)
                 * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log_init(key, shape, dtype=F32):
    """A uniform in [1, 16] a head."""
    return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0)).astype(dtype)


class OlmoHybridModel(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens: jax.Array) -> jax.Array:
        """tokens [B, S] int32 -> logits [B, S, vocab] in f32."""
        cfg = self.cfg
        d, pd, L = cfg.d_model, cfg.param_dtype, cfg.n_layers
        H, dk, dv, HV, C = widths(cfg)
        Lg, La = n_linear_layers(cfg), n_full_layers(cfg)
        Ha, KV, hd = cfg.n_heads, cfg.kv_heads, cfg.head_size
        w = nn.initializers.normal(0.02)
        ones = nn.initializers.ones

        def group(name, spec):
            return {k: self.param(f"{name}_{k}", init, shape, dt)
                    for k, (init, shape, dt) in spec.items()}

        p = {
            "embed": {"embedding": self.param(
                "embedding", w, (cfg.vocab_size, d), pd)},
            "final_norm": {"scale": self.param("final_norm", ones, (d,), F32)},
            "lm_head": {"kernel": self.param(
                "lm_head", w, (d, cfg.vocab_size), pd)},
            "gdn": group("gdn", {
                "wq": (w, (Lg, d, H * dk), pd),
                "wk": (w, (Lg, d, H * dk), pd),
                "wv": (w, (Lg, d, HV), pd),
                "wg": (w, (Lg, d, HV), pd),
                "wa": (w, (Lg, d, H), pd),
                "wb": (w, (Lg, d, H), pd),
                "conv_w": (w, (Lg, cfg.olmo_hybrid.d_conv, C), pd),
                "A_log": (_a_log_init, (Lg, H), F32),
                "dt_bias": (_dt_bias_init, (Lg, H), F32),
                "o_norm": (ones, (Lg, dv), F32),
                "wo": (w, (Lg, HV, d), pd),
            }),
            "attn": group("attn", {
                "wq": (w, (La, d, Ha, hd), pd),
                "wk": (w, (La, d, KV, hd), pd),
                "wv": (w, (La, d, KV, hd), pd),
                "wo": (w, (La, Ha, hd, d), pd),
                "q_norm": (ones, (La, Ha * hd), F32),
                "k_norm": (ones, (La, KV * hd), F32),
            }),
            "ffn": group("ffn", {
                "post_mixer_norm": (ones, (L, d), F32),
                "post_ffn_norm": (ones, (L, d), F32),
                "w_gate": (w, (L, d, cfg.d_ff), pd),
                "w_up": (w, (L, d, cfg.d_ff), pd),
                "w_down": (w, (L, cfg.d_ff, d), pd),
            }),
        }
        return full_forward(cfg, p, tokens)

    def decode_decomposition(self):
        raise NotImplementedError(
            "the olmo_hybrid family is no stack of identical blocks: the "
            "serving programs walk it with models.olmo_hybrid.scan_layers "
            "(serve/programs.py, the hybrid builders)")


def param_tree(flat: dict) -> dict:
    """The nested view the layer functions take, from the module's flat
    parameters (``params["params"]``): ``gdn_wq`` -> ``["gdn"]["wq"]``.
    No copy."""
    p = {"embed": {"embedding": flat["embedding"]},
         "final_norm": {"scale": flat["final_norm"]},
         "lm_head": {"kernel": flat["lm_head"]},
         "gdn": {}, "attn": {}, "ffn": {}}
    for k, v in flat.items():
        grp, _, name = k.partition("_")
        if grp in ("gdn", "attn", "ffn") and name:
            p[grp][name] = v
    return p


def make_olmo_hybrid(cfg: TransformerConfig) -> OlmoHybridModel:
    if cfg.olmo_hybrid is None:
        raise ValueError("make_olmo_hybrid needs a config with cfg.olmo_hybrid")
    layer_counts(cfg)
    return OlmoHybridModel(cfg)
