"""Jamba-family hybrid decoder (AI21-Jamba2): Mamba-1 mixers in every
layer but one a period, which is an attention layer (MQA/GQA, no
positional term at all); every layer's feed-forward is the dense gated
MLP; tied head.

Layer ``i`` (pre-norm, eps ``cfg.norm_eps`` everywhere)::

    h = x + Mixer_i(RMSNorm(x))          y = h + MLP(RMSNorm(h))
    Mamba:  [u, z] = v W_in ; u = silu(conv1d_causal(u; depthwise, bias))
            [d, B, C] = u W_x, each RMS-normed (Jamba's inner norms)
            D = softplus(d W_dt + b_dt) ; A = -exp(A_log)
            s_t = exp(D_t * A) * s_{t-1} + (D_t * u_t) (x) B_t
            y_t = s_t C_t + Dskip * u_t ; out = (y * silu(z)) W_out

The stack is NOT "one block, L times", so the parameters are three
stacks with a leading layer axis each — ``mamba`` (the mixers, one row a
Mamba layer), ``attn`` (one row an attention layer) and ``ffn`` (norms
and MLP of all layers) — and :func:`scan_layers` walks them as a loop of
loops: a scan over the periods whose body is a scan over the Mamba
layers before the attention layer, the attention layer, and a scan over
the Mamba layers after it.  A layer picks its rows by index, which
compiles to the same dynamic slice a ``lax.scan`` over stacked
parameters makes; nothing is unrolled, whatever the depth.

Layouts are the chip's: the recurrent state is ``[B, d_state, d_inner]``
and ``A_log`` ``[d_state, d_inner]`` (a minor dim of 16 would pad to 128
lanes, eight times the bytes), the conv tail is time-major
``[d_conv-1, B, d_inner]``.  The state and the recurrence are float32
whatever ``cfg.dtype`` is.

The mixer is ONE function for the full forward (zero state in, state
dropped), a prefill chunk (a lane's state in and out, ``n_valid``
positions of the bucket real) and a decode tick (one position a lane,
``n_valid`` 0 for a lane that sits the tick out): a position past
``n_valid`` gets step size 0, for which the recurrence is exactly the
identity, and the tail that is left behind is the last ``d_conv - 1``
REAL inputs.  The two recurrences carry the names that reach the
compiled module (``tdx_ssm_decode_update``, ``tdx_ssm_chunk_scan``).
"""

from __future__ import annotations

import contextlib
import math
from typing import Callable, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .configs import TransformerConfig
from .layers import default_attention

F32 = jnp.float32
DECODE_UPDATE = "tdx_ssm_decode_update"
CHUNK_SCAN = "tdx_ssm_chunk_scan"
# Positions a scan iteration advances (the body is unrolled that far): a
# while loop's own cost an iteration is of the order of one position's
# arithmetic at d_inner 5120.
SCAN_UNROLL = 8


def d_inner(cfg: TransformerConfig) -> int:
    return cfg.mamba.expand * cfg.d_model


def layer_counts(cfg: TransformerConfig) -> Tuple[int, int, int]:
    """(periods, Mamba layers before a period's attention layer, after)."""
    m = cfg.mamba
    if m is None:
        raise ValueError("the jamba family needs cfg.mamba")
    if cfg.n_layers % m.attn_period or not (
            0 <= m.attn_offset < m.attn_period):
        raise ValueError(
            f"n_layers={cfg.n_layers} is not a whole number of periods of "
            f"{m.attn_period} with the attention layer at {m.attn_offset}")
    return (cfg.n_layers // m.attn_period, m.attn_offset,
            m.attn_period - m.attn_offset - 1)


def n_mamba_layers(cfg: TransformerConfig) -> int:
    periods, pre, post = layer_counts(cfg)
    return periods * (pre + post)


def n_attn_layers(cfg: TransformerConfig) -> int:
    return layer_counts(cfg)[0]


# -- layer math (pure functions of a layer's rows) ---------------------------


def rms_norm(x, scale, eps):
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale.astype(F32)).astype(x.dtype)


def _dot(x, w, dtype):
    return jnp.dot(x.astype(dtype), w.astype(dtype))


def mlp(cfg, f, x):
    gate = jax.nn.silu(_dot(x, f["w_gate"], cfg.dtype))
    return _dot(gate * _dot(x, f["w_up"], cfg.dtype), f["w_down"], cfg.dtype)


def qkv(cfg, a, h):
    """q [B, S, H, D], k and v [B, S, KV, D]; no bias, no rotary."""
    proj = lambda w: jnp.einsum("bsd,dhk->bshk", h.astype(cfg.dtype),
                                w.astype(cfg.dtype))
    return proj(a["wq"]), proj(a["wk"]), proj(a["wv"])


def attn_out(cfg, a, o):
    return jnp.einsum("bshk,hkd->bsd", o.astype(cfg.dtype),
                      a["wo"].astype(cfg.dtype))


def _ssm_step(s, A, dt, u, Bm, Cm):
    """One position: s [B, N, Di] f32; dt, u [B, Di]; Bm, Cm [B, N]."""
    s = (jnp.exp(dt[:, None, :] * A[None]) * s
         + Bm[:, :, None] * (dt * u)[:, None, :])
    return s, jnp.einsum("bnd,bn->bd", s, Cm)


def selective_scan(A, dt, u, Bm, Cm, s0):
    """The recurrence over time-major inputs: dt, u [S, B, Di] f32 (dt 0
    at a position that is not real), Bm, Cm [S, B, N], s0 [B, N, Di] ->
    (y [S, B, Di], s_S).  One position is the decode update; more are
    the chunk scan."""
    S = dt.shape[0]
    if S == 1:
        with jax.named_scope(DECODE_UPDATE):
            s, y = _ssm_step(s0, A, dt[0], u[0], Bm[0], Cm[0])
            return y[None], s
    with jax.named_scope(CHUNK_SCAN):
        def body(s, inp):
            return _ssm_step(s, A, *inp)

        s, y = jax.lax.scan(body, s0, (dt, u, Bm, Cm),
                            unroll=min(S, SCAN_UNROLL))
        return y, s


def mamba_mixer(cfg, m, h, ssm, tail, n_valid):
    """h [B, S, d] (normed); ssm [B, N, Di] f32; tail [K-1, B, Di], the
    last inputs of the conv before this call; n_valid [B] int32, how
    many of the S positions are real (left-aligned).  Returns (out
    [B, S, d], ssm', tail')."""
    mc, Di, eps = cfg.mamba, d_inner(cfg), cfg.norm_eps
    B, S, _ = h.shape
    K = mc.d_conv
    uz = _dot(h, m["in_proj"], cfg.dtype)
    u, z = uz[..., :Di], uz[..., Di:]
    # The conv and its tail belong to the decode update; in a chunk they
    # are one small pass beside the scan.
    with (jax.named_scope(DECODE_UPDATE) if S == 1
          else contextlib.nullcontext()):
        full = jnp.concatenate(
            [tail.astype(cfg.dtype), u.transpose(1, 0, 2)], 0)  # [K-1+S,B,Di]
        idx = n_valid[None, :] + jnp.arange(K - 1, dtype=jnp.int32)[:, None]
        new_tail = jnp.take_along_axis(full, idx[:, :, None], axis=0)
        w = m["conv_w"].astype(F32)
        conv = m["conv_b"].astype(F32) + sum(
            w[k] * full[k:k + S].astype(F32) for k in range(K))
        u = jax.nn.silu(conv).astype(cfg.dtype)              # [S, B, Di]
    dbc = _dot(u, m["x_proj"], cfg.dtype)
    R, N = mc.dt_rank, mc.d_state
    dlt = rms_norm(dbc[..., :R], m["dt_norm"], eps)
    Bm = rms_norm(dbc[..., R:R + N], m["b_norm"], eps).astype(F32)
    Cm = rms_norm(dbc[..., R + N:], m["c_norm"], eps).astype(F32)
    dt = jax.nn.softplus(_dot(dlt, m["dt_proj"], cfg.dtype).astype(F32)
                         + m["dt_bias"].astype(F32))
    real = jnp.arange(S, dtype=jnp.int32)[:, None] < n_valid[None, :]
    dt = jnp.where(real[:, :, None], dt, 0.0)
    A = -jnp.exp(m["A_log"].astype(F32))                     # [N, Di]
    uf = u.astype(F32)
    y, ssm = selective_scan(A, dt, uf, Bm, Cm, ssm.astype(F32))
    y = y + m["D"].astype(F32) * uf
    y = (y * jax.nn.silu(z.transpose(1, 0, 2).astype(F32))).astype(cfg.dtype)
    out = _dot(y.transpose(1, 0, 2), m["out_proj"], cfg.dtype)
    return out, ssm, new_tail.astype(tail.dtype)


# -- the stack as a loop of loops --------------------------------------------


def _row(tree, i):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)


def scan_layers(cfg, p, x, carry, mamba_layer: Callable,
                attn_layer: Callable, *, counts=None, rec: str = "mamba"):
    """Thread ``(x, carry)`` through the stack.  ``mamba_layer(m, f, x,
    carry, g)`` and ``attn_layer(a, f, x, carry, j)`` get a layer's rows
    of ``p[rec]`` (the recurrent layers' group, ``"mamba"`` here) /
    ``p["attn"]`` and ``p["ffn"]`` and its index AMONG ITS KIND (``g`` of
    the recurrent layers, ``j`` of the attention layers: the row of a
    cache that holds only that kind), and return ``(x, carry)``.
    ``carry`` is whatever the caller caches (pools and states, or
    nothing); it is the scans' carry, so a layer updates it in place.
    ``counts`` (periods, recurrent layers before a period's attention
    layer, after) is this family's :func:`layer_counts` unless another
    family with the same pattern passes its own (models/olmo_hybrid.py)."""
    periods, pre, post = counts or layer_counts(cfg)
    per = pre + post + 1
    i32 = jnp.int32

    def mamba_run(x, carry, first_layer, first_g, n):
        if n == 0:
            return x, carry

        def body(c, j):
            x, carry = c
            return mamba_layer(_row(p[rec], first_g + j),
                               _row(p["ffn"], first_layer + j),
                               x, carry, first_g + j), None

        return jax.lax.scan(body, (x, carry), jnp.arange(n, dtype=i32))[0]

    def period(c, pi):
        x, carry = c
        layer0, g0 = pi * per, pi * (pre + post)
        x, carry = mamba_run(x, carry, layer0, g0, pre)
        x, carry = attn_layer(_row(p["attn"], pi),
                              _row(p["ffn"], layer0 + pre), x, carry, pi)
        x, carry = mamba_run(x, carry, layer0 + pre + 1, g0 + pre, post)
        return (x, carry), None

    return jax.lax.scan(period, (x, carry),
                        jnp.arange(periods, dtype=i32))[0]


def block(cfg, f, x, mixer: Callable):
    """One layer around its mixer, pre-norm: ``h = x + mixer(RMSNorm(x))``,
    ``y = h + MLP(RMSNorm(h))``; ``f`` the layer's row of ``p["ffn"]``."""
    eps = cfg.norm_eps
    x = x + mixer(rms_norm(x, f["norm0"], eps))
    return x + mlp(cfg, f, rms_norm(x, f["norm1"], eps))


def serve_mixer(cfg, m, h, ssm, conv, g, mixer_state):
    """The Mamba mixer as the serving programs run it on the cache's state
    (serve/programs.py, the hybrid builders): ``mixer_state(ssm, conv, g)``
    -> ``(s, tail, n_valid, put)`` gives the rows of layer ``g`` that the
    call advances and how to put them back.  Returns ``(out, ssm, conv)``."""
    # Reading the layer's rows and putting them back is part of the
    # recurrence it belongs to: the write fuses with the update, and the
    # fusion takes its root's name.
    scope = DECODE_UPDATE if h.shape[1] == 1 else CHUNK_SCAN
    with jax.named_scope(scope):
        s, tail, n_valid, put = mixer_state(ssm, conv, g)
    out, s, tail = mamba_mixer(cfg, m, h, s, tail, n_valid)
    with jax.named_scope(scope):
        ssm, conv = put(ssm, conv, g, s, tail)
    return out, ssm, conv


def embed_tokens(cfg, p, tokens):
    return p["embed"]["embedding"].astype(cfg.dtype)[tokens]


def head_logits(cfg, p, x):
    x = rms_norm(x, p["final_norm"]["scale"], cfg.norm_eps)
    emb = p["embed"]["embedding"]
    return (x.astype(cfg.param_dtype) @ emb.T).astype(F32)


def full_forward(cfg, p, tokens):
    """tokens [B, S] -> logits [B, S, vocab] f32: no cache, zero states."""
    B, S = tokens.shape
    mc, Di = cfg.mamba, d_inner(cfg)
    x = embed_tokens(cfg, p, tokens)
    n_valid = jnp.full((B,), S, jnp.int32)

    def mamba_layer(m, f, x, carry, g):
        h = rms_norm(x, f["norm0"], cfg.norm_eps)
        out, _, _ = mamba_mixer(
            cfg, m, h, jnp.zeros((B, mc.d_state, Di), F32),
            jnp.zeros((mc.d_conv - 1, B, Di), cfg.dtype), n_valid)
        x = x + out
        return x + mlp(cfg, f, rms_norm(x, f["norm1"], cfg.norm_eps)), carry

    def attn_layer(a, f, x, carry, j):
        q, k, v = qkv(cfg, a, rms_norm(x, f["norm0"], cfg.norm_eps))
        x = x + attn_out(cfg, a, default_attention(q, k, v, causal=True))
        return x + mlp(cfg, f, rms_norm(x, f["norm1"], cfg.norm_eps)), carry

    x, _ = scan_layers(cfg, p, x, (), mamba_layer, attn_layer)
    return head_logits(cfg, p, x)


# -- the flax module: parameters, and the full forward ------------------------


def _dt_bias_init(key, shape, dtype=F32):
    """Inverse softplus of step sizes log-uniform in [1e-3, 1e-1] (the
    Mamba paper's initialisation)."""
    dt = jnp.exp(jax.random.uniform(key, shape, F32)
                 * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log_init(key, shape, dtype=F32):
    """A = -(1..N) for every channel (S4D-real): shape [L, N, Di]."""
    n = jnp.arange(1, shape[-2] + 1, dtype=F32)
    return jnp.broadcast_to(jnp.log(n)[:, None], shape).astype(dtype)


class JambaModel(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens: jax.Array) -> jax.Array:
        """tokens [B, S] int32 -> logits [B, S, vocab] in f32."""
        cfg = self.cfg
        mc, Di, d, pd = cfg.mamba, d_inner(cfg), cfg.d_model, cfg.param_dtype
        Lm, La, L = n_mamba_layers(cfg), n_attn_layers(cfg), cfg.n_layers
        H, KV, hd = cfg.n_heads, cfg.kv_heads, cfg.head_size
        w = nn.initializers.normal(0.02)
        ones = nn.initializers.ones

        def group(name, spec):
            return {k: self.param(f"{name}_{k}", init, shape, dt)
                    for k, (init, shape, dt) in spec.items()}

        p = {
            "embed": {"embedding": self.param(
                "embedding", w, (cfg.vocab_size, d), pd)},
            "final_norm": {"scale": self.param("final_norm", ones, (d,), F32)},
            "mamba": group("mamba", {
                "in_proj": (w, (Lm, d, 2 * Di), pd),
                "conv_w": (w, (Lm, mc.d_conv, Di), pd),
                "conv_b": (w, (Lm, Di), pd),
                "x_proj": (w, (Lm, Di, mc.dt_rank + 2 * mc.d_state), pd),
                "dt_norm": (ones, (Lm, mc.dt_rank), F32),
                "b_norm": (ones, (Lm, mc.d_state), F32),
                "c_norm": (ones, (Lm, mc.d_state), F32),
                "dt_proj": (w, (Lm, mc.dt_rank, Di), pd),
                "dt_bias": (_dt_bias_init, (Lm, Di), F32),
                "A_log": (_a_log_init, (Lm, mc.d_state, Di), F32),
                "D": (ones, (Lm, Di), F32),
                "out_proj": (w, (Lm, Di, d), pd),
            }),
            "attn": group("attn", {
                "wq": (w, (La, d, H, hd), pd),
                "wk": (w, (La, d, KV, hd), pd),
                "wv": (w, (La, d, KV, hd), pd),
                "wo": (w, (La, H, hd, d), pd),
            }),
            "ffn": group("ffn", {
                "norm0": (ones, (L, d), F32),
                "norm1": (ones, (L, d), F32),
                "w_gate": (w, (L, d, cfg.d_ff), pd),
                "w_up": (w, (L, d, cfg.d_ff), pd),
                "w_down": (w, (L, cfg.d_ff, d), pd),
            }),
        }
        return full_forward(cfg, p, tokens)

    def decode_decomposition(self):
        raise NotImplementedError(
            "the jamba family is no stack of identical blocks: the serving "
            "programs walk it with models.jamba.scan_layers "
            "(serve/programs.py, the hybrid builders)")


def param_tree(flat: dict) -> dict:
    """The nested view the layer functions take, from the module's flat
    parameters (``params["params"]``): ``mamba_in_proj`` ->
    ``["mamba"]["in_proj"]``.  No copy."""
    p = {"embed": {"embedding": flat["embedding"]},
         "final_norm": {"scale": flat["final_norm"]},
         "mamba": {}, "attn": {}, "ffn": {}}
    for k, v in flat.items():
        grp, _, name = k.partition("_")
        if grp in ("mamba", "attn", "ffn") and name:
            p[grp][name] = v
    return p


def make_jamba(cfg: TransformerConfig) -> JambaModel:
    if cfg.mamba is None:
        raise ValueError("make_jamba needs a config with cfg.mamba set")
    layer_counts(cfg)
    return JambaModel(cfg)
