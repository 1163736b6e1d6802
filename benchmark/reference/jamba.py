"""The plain reference for the ``jamba`` family (AI21-Jamba2): the
published equations in straightforward ``jax.numpy``, float32 with
``highest`` matmul precision, a plain ``lax.scan`` over time for the
recurrence, no kernel, no cache, no batching.  It imports nothing of the
program and takes only the benchmark's own weights
(``benchmark/families/jamba.py``), in the shapes the published modelling
code keeps them: the state is ``[d_inner, d_state]``, ``A_log``
``[d_inner, d_state]``, the depthwise conv's weight ``[d_inner, d_conv]``
(the program stores all three the other way round).

With ``x`` of one sequence ``[T, d]``, layer ``i``::

    h = x + Mixer_i(RMSNorm(x))          y = h + MLP(RMSNorm(h))
    MLP(v)   = (silu(v W_gate) * (v W_up)) W_down
    Attention (i % attn_layer_period == attn_layer_offset):
        q = v W_q (H heads), k = v W_k, v' = v W_v (KV heads), no bias, no
        rotary or other position term, causal softmax(q k^T / sqrt(hd)) v', W_o
    Mamba (otherwise):
        [u, z] = v W_in
        u  = silu(conv1d_causal(u; depthwise, kernel d_conv, bias))
        [dl, B, C] = u W_x ; each RMS-normed with its own scale
        D  = softplus(dl W_dt + b_dt)
        A  = -exp(A_log)
        s_t = exp(D_t * A) * s_{t-1} + (D_t * u_t) (outer) B_t ,  s_0 = 0
        y_t = s_t C_t + Dskip * u_t
        out = (y * silu(z)) W_out
    logits = RMSNorm(y_L) E^T   (E the embedding, tied); one eps everywhere

Departures from the published code, all of them: (1) the modelling code
runs the recurrence through a fused CUDA kernel when it can; this is its
"slow path", the same mathematics; (2) weights are the benchmark's
(bfloat16 values from the seed, upcast a layer at a time), not a
checkpoint's; (3) ``num_experts`` is 1 in this configuration, so the
expert router is absent and every layer's feed-forward is the dense MLP.

``quant`` is the control's hook, as in ``reference/decoder.py``: a
function applied to both operands of every weight matmul.  The conv and
the recurrence are no matmuls and stay float32 under it.  ``state_round``
is a planted fault's hook: applied to the state after every step
(``bf16``: a recurrence carried in bfloat16).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def fp8(x):
    """Fake-quantise to float8 e4m3 with a per-tensor scale."""
    xf = x.astype(F32)
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(xf)), 1e-30)
    return (xf * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale


def bf16(x):
    return x.astype(jnp.bfloat16).astype(F32)


QUANT = {"fp8": fp8, "bf16": bf16, None: None, "": None}


def _mm(eq, a, b, quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.einsum(eq, a.astype(F32), b.astype(F32), precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _mlp(c, quant, x, lw):
    h = _rms(x, lw["norm1.scale"], c["norm_eps"])
    gate = jax.nn.silu(_mm("td,df->tf", h, lw["w_gate"], quant))
    up = _mm("td,df->tf", h, lw["w_up"], quant)
    return x + _mm("tf,fd->td", gate * up, lw["w_down"], quant)


def _attention(q, k, v):
    """q [T, H, D], k/v [T, KV, D] -> [T, H, D]; causal, no positions."""
    T, H, D = q.shape
    KV = k.shape[1]
    qg = q.reshape(T, KV, H // KV, D)
    s = jnp.einsum("tkgd,skd->kgts", qg, k, precision=HIGHEST) / math.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", p, v, precision=HIGHEST)
    return o.reshape(T, H, D)


def attention_layer(c, quant, x, lw):
    lw = {k: v.astype(F32) for k, v in lw.items()}
    h = _rms(x, lw["norm0.scale"], c["norm_eps"])
    q = _mm("td,dhk->thk", h, lw["wq"], quant)
    k = _mm("td,dhk->thk", h, lw["wk"], quant)
    v = _mm("td,dhk->thk", h, lw["wv"], quant)
    x = x + _mm("thk,hkd->td", _attention(q, k, v), lw["wo"], quant)
    return _mlp(c, quant, x, lw)


def mamba_mixer(c, quant, state_round, v, lw):
    """v [T, d] -> [T, d]: the Mamba-1 mixer on one sequence from s_0 = 0."""
    Di, N, R, K = c["d_inner"], c["d_state"], c["dt_rank"], c["d_conv"]
    eps = c["norm_eps"]
    T = v.shape[0]
    uz = _mm("td,de->te", v, lw["in_proj"], quant)
    u, z = uz[:, :Di], uz[:, Di:]
    # depthwise causal conv: output t sees inputs t-K+1 .. t, zeros before 0
    padded = jnp.concatenate([jnp.zeros((K - 1, Di), F32), u], 0)
    conv = lw["conv_b"] + sum(
        padded[k:k + T] * lw["conv_w"][:, k] for k in range(K))
    u = jax.nn.silu(conv)
    dbc = _mm("te,er->tr", u, lw["x_proj"], quant)
    dl = _rms(dbc[:, :R], lw["dt_norm.scale"], eps)
    Bm = _rms(dbc[:, R:R + N], lw["b_norm.scale"], eps)
    Cm = _rms(dbc[:, R + N:], lw["c_norm.scale"], eps)
    delta = jax.nn.softplus(_mm("tr,re->te", dl, lw["dt_proj"], quant)
                            + lw["dt_bias"])
    A = -jnp.exp(lw["A_log"])                                # [Di, N]

    def step(s, inp):
        d_t, u_t, b_t, c_t = inp
        s = (jnp.exp(d_t[:, None] * A) * s
             + (d_t * u_t)[:, None] * b_t[None, :])
        if state_round is not None:
            s = state_round(s)
        return s, jnp.einsum("en,n->e", s, c_t, precision=HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((Di, N), F32), (delta, u, Bm, Cm))
    y = (y + lw["D"] * u) * jax.nn.silu(z)
    return _mm("te,ed->td", y, lw["out_proj"], quant)


def mamba_layer(c, quant, state_round, x, lw):
    lw = {k: v.astype(F32) for k, v in lw.items()}
    h = _rms(x, lw["norm0.scale"], c["norm_eps"])
    x = x + mamba_mixer(c, quant, state_round, h, lw)
    return _mlp(c, quant, x, lw)


def head(c, quant, w, x):
    h = _rms(x, w["final_norm.scale"].astype(F32), c["norm_eps"])
    return _mm("td,vd->tv", h, w["embed"], quant)


def is_attention(c, i: int) -> bool:
    return i % c["attn_layer_period"] == c["attn_layer_offset"]


def layer_weights(c, w, i: int) -> dict:
    """Layer ``i``'s tensors: its row of ``ffn.*`` and its row of
    ``attn.*`` or ``mamba.*`` (the stacks hold one kind each, in layer
    order)."""
    attn = is_attention(c, i)
    n_attn_before = sum(is_attention(c, j) for j in range(i))
    grp, row = ("attn.", n_attn_before) if attn else ("mamba.", i - n_attn_before)
    out = {k[len("ffn."):]: v[i] for k, v in w.items() if k.startswith("ffn.")}
    out.update({k[len(grp):]: v[row] for k, v in w.items()
                if k.startswith(grp)})
    return out


class Forward:
    """Logits of one sequence at chosen positions, layer by layer (weights
    upcast a layer at a time).  Sequences are padded to a multiple of
    ``pad``: padding lies after every real position, which neither causal
    attention nor a causal recurrence ever looks at."""

    def __init__(self, c, quant=None, pad=256, state_round=None):
        self.c, self.pad = c, pad
        self._mamba = jax.jit(functools.partial(
            mamba_layer, c, quant, state_round))
        self._attn = jax.jit(functools.partial(attention_layer, c, quant))
        self._head = jax.jit(functools.partial(head, c, quant))

    def logits(self, w, tokens, first, n):
        """float32 [n, vocab]: logits at positions first..first+n-1."""
        import numpy as np

        c = self.c
        S = len(tokens)
        P = -(-S // self.pad) * self.pad
        toks = np.zeros((P,), np.int32)
        toks[:S] = tokens
        x = w["embed"][jnp.asarray(toks)].astype(F32)
        for i in range(c["n_layers"]):
            lw = layer_weights(c, w, i)
            x = (self._attn if is_attention(c, i) else self._mamba)(x, lw)
        npos = -(-n // 256) * 256
        idx = np.minimum(first + np.arange(npos), P - 1)
        top = {k: v for k, v in w.items() if "." not in k
               or k.startswith("final_norm")}
        return np.asarray(self._head(top, x[jnp.asarray(idx)])[:n])
